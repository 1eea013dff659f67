"""Client pack, fan-out and merge, plus the wire both ways: a request's mean
time seen by the caller, less the slowest rank's mean server-side ``search``
span over the same window."""

from perfbench import stats


def read(obs):
    server = stats.per_rank(obs, ("search",))
    client = stats.client_mean_ms(obs)
    if server is None or client is None:
        return None
    return client - 1e3 * max(server)
