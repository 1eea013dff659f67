"""Across ranks: the slowest rank's mean server-side ``search`` span less the
fastest's. A fan-out waits for the slowest."""

from perfbench import stats


def read(obs):
    server = stats.per_rank(obs, ("search",))
    if server is None or len(server) < 2:
        return None
    return 1e3 * (max(server) - min(server))
