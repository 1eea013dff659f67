"""Engine launch-to-fetch, join: share of the window's merged windows that
were launched while an earlier window of the same index was not yet
collected, so that the chip went from one to the next with no host work
between them: ``engine.launch_overlapped`` count over the ``engine.launch``
count (the row ``device_search_s``), all ranks together, in %. The engine
shows ``engine.launch_overlapped`` at zero beside ``device_search_s`` until a
launch books it, so a rank that serves one window at a time reads 0; a
program without the counter has no such row and reads nothing."""

from perfbench import ledger, stats


def read(obs):
    overlapped = stats.per_rank(obs, ledger.engine(obs, "engine.launch_overlapped"),
                                stats.window_count)
    launches = stats.per_rank(obs, ledger.engine(obs, "device_search_s"),
                              stats.window_count)
    if overlapped is None or launches is None or not sum(launches):
        return None
    return 100.0 * sum(overlapped) / sum(launches)
