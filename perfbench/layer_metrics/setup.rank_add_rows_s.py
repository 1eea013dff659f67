"""Set-up, ingest, counted where it happens: rows a second of the rank's own
buffer drains (``engine.add_drain_rows`` over ``engine.add_drain`` seconds:
concat, lock wait, device add), slowest rank."""

from perfbench import ledger


def read(obs):
    rows = ledger.at_window_start(obs, "engine.add_drain_rows")
    seconds = ledger.at_window_start(obs, "engine.add_drain")
    if rows is None or seconds is None or not all(seconds):
        return None
    return min(r / s for r, s in zip(rows, seconds))
