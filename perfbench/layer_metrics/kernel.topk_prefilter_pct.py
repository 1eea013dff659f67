"""Models and kernels, the exact scan: share of the window's scans whose
per-chunk top-k chose its segments by their maxima before sorting:
``engine.scan_prefilter`` count over ``engine.scan`` count, all ranks
together, in %. The engine shows ``engine.scan_prefilter`` at zero beside
``engine.scan`` until a scan books it, so a scan at a ``k`` too large for
its chunk (the two-stage reduction ran) reads 0; a program without the
counter has no such row and reads nothing."""

from perfbench import ledger, stats


def read(obs):
    chosen = stats.per_rank(obs, ledger.engine(obs, "engine.scan_prefilter"),
                            stats.window_count)
    scans = stats.per_rank(obs, ledger.engine(obs, "engine.scan"),
                           stats.window_count)
    if chosen is None or scans is None or not sum(scans):
        return None
    return 100.0 * sum(chosen) / sum(scans)
