"""Engine: mean launch-to-fetch time of one locked device search
(``engine.<index>.device_search_s``), on the slowest rank."""

from perfbench import stats


def read(obs):
    launch = stats.per_rank(obs, ("engine", obs["index_id"], "device_search_s"))
    return None if launch is None else 1e3 * max(launch)
