"""Arithmetic on the program's stage ledger, for the per-layer readers that
read it (``docs/OPERATIONS.md``, Stage ledger; beside ``stats.py``, which has
the window mean).

Every stage is a ``get_perf_stats`` row ``{count, total_s, ...}`` since the
rank started: ``server.*`` at the top of a rank's entry, ``sched.*`` under
``scheduler.queues``, ``engine.*`` under ``engine.<index>``, the stub's
``client.*`` under ``rpc.client`` and the client-wide ones under ``client``
(the same in every rank's entry). A program without the ledger has none of
these rows: every function here then returns None, and so does its reader.
"""

from perfbench import stats


def window_total(before, after, path):
    """Seconds (or whatever the row sums) booked to the row in the window;
    None if the program has no such row."""
    a = stats.dig(after, path)
    if a is None:
        return None
    b = stats.dig(before, path) or {"total_s": 0.0}
    return a["total_s"] - b["total_s"]


def server(name):
    return (name,)


def sched(name):
    return ("scheduler", "queues", name)


def engine(obs, name):
    return ("engine", obs["index_id"], name)


def stub(name):
    return ("rpc", "client", name)


def client_wide_mean_ms(obs, name):
    """Window mean of a client-wide stage, read from the first rank's entry
    (every entry carries the same ``client`` block)."""
    if "stats_before" not in obs:
        return None
    mean = stats.window_mean(obs["stats_before"][0], obs["stats_after"][0],
                             ("client", name))
    return None if mean is None else 1e3 * mean


def summed_means(obs, paths):
    """Per rank, the sum of the window means of ``paths``; None if a rank
    lacks one."""
    per_path = [stats.per_rank(obs, path) for path in paths]
    if any(p is None for p in per_path):
        return None
    return [sum(rank) for rank in zip(*per_path)]


def share_of_window_pct(obs, paths):
    """Seconds booked to ``paths`` in the window over the window, in %,
    mean over the ranks."""
    per_path = [stats.per_rank(obs, path, window_total) for path in paths]
    if any(p is None for p in per_path):
        return None
    ranks = [sum(rank) for rank in zip(*per_path)]
    return 100.0 * sum(ranks) / len(ranks) / obs["window_s"]


def wire_ms(obs):
    """The wire both ways on the slowest rank: its stub's mean round trip
    (end of the send to demux completion) less its own mean
    ``server.request`` (whole frame in hand to last byte written)."""
    trip = stats.per_rank(obs, stub("client.round_trip.search"))
    rank = stats.per_rank(obs, server("server.request"))
    if trip is None or rank is None:
        return None
    slowest = max(range(len(rank)), key=rank.__getitem__)
    return 1e3 * (trip[slowest] - rank[slowest])


def compiles(obs):
    """XLA compiles inside the window, summed over the ranks; 0 is a value."""
    n = stats.per_rank(obs, server("xla.compile"), stats.window_count)
    return None if n is None else sum(n)


def per_launch_ms(obs, name):
    """Slowest rank's seconds of an engine stage in the window over its
    launches in it (a launch of several blocks books the stage once a
    block)."""
    seconds = stats.per_rank(obs, engine(obs, name), window_total)
    launches = stats.per_rank(obs, engine(obs, "device_search_s"),
                              stats.window_count)
    if seconds is None or launches is None or not all(launches):
        return None
    return 1e3 * max(s / n for s, n in zip(seconds, launches))


def at_window_start(obs, name):
    """Per rank, the engine row's total when the window started (set-up's
    work is all before it); None if a rank lacks the row."""
    if "stats_before" not in obs:
        return None
    rows = [stats.dig(b, engine(obs, name)) for b in obs["stats_before"]]
    return None if any(r is None for r in rows) else [r["total_s"] for r in rows]
