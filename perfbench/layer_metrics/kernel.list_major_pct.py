"""Models and kernels, the IVF-flat probe scan: share of the window's scans
whose program took the list-major order (each probed list gathered once
per tile of queries that probe it): ``engine.scan_listmajor`` count over
``engine.scan`` count, all ranks together, in %. The engine shows
``engine.scan_listmajor`` at zero beside ``engine.scan`` until a scan books
it, so a scan that ran the query-major order (the Pallas arm) reads 0; a
program without the counter has no such row and reads nothing."""

from perfbench import ledger, stats


def read(obs):
    chosen = stats.per_rank(obs, ledger.engine(obs, "engine.scan_listmajor"),
                            stats.window_count)
    scans = stats.per_rank(obs, ledger.engine(obs, "engine.scan"),
                           stats.window_count)
    if chosen is None or scans is None or not sum(scans):
        return None
    return 100.0 * sum(chosen) / sum(scans)
