"""Set-up, the store's growth, timed where it happens: seconds the rank
spent reallocating its row stores before the window (``engine.store_grow``:
each growth from the allocation to the end of the copy), slowest rank."""

from perfbench import ledger


def read(obs):
    grow = ledger.at_window_start(obs, "engine.store_grow")
    return None if grow is None else max(grow)
