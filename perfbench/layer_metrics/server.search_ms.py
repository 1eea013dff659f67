"""Server dispatch to response write: the mean per-RPC ``search`` span of
``get_perf_stats`` over the window, on the slowest rank."""

from perfbench import stats


def read(obs):
    server = stats.per_rank(obs, ("search",))
    return None if server is None else 1e3 * max(server)
