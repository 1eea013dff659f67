"""Share of the window's list scans that ran the fused Pallas ADC kernel:
``engine.scan_fused`` count over ``engine.scan`` count, all ranks together,
in %. The engine shows ``engine.scan_fused`` at zero beside ``engine.scan``
until a scan is fused, so an index that fell back to the XLA one-hot (a
first-use check that demoted it, a geometry the kernel does not take) reads
0; a program without the counter has no such row and reads nothing."""

from perfbench import ledger, stats


def read(obs):
    fused = stats.per_rank(obs, ledger.engine(obs, "engine.scan_fused"),
                           stats.window_count)
    scans = stats.per_rank(obs, ledger.engine(obs, "engine.scan"),
                           stats.window_count)
    if fused is None or scans is None or not sum(scans):
        return None
    return 100.0 * sum(fused) / sum(scans)
