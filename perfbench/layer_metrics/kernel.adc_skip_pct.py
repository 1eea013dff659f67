"""Models and kernels, the PQ list scan: share of the candidate columns of
the window's scans that the ADC kernel did not compute, in %: the window's
total of ``engine.scan_adc_cols_skipped`` (the columns past the last
128-column sub-tile that holds a row of a pair's list) over that of
``engine.scan_adc_cols`` (padded rows x ``nprobe`` x the padded list
capacity), all ranks together. The engine shows both rows at zero beside
``engine.scan`` until an IVF-PQ scan books them, and the XLA arm books 0
skipped: a cell whose sizes did not reach the kernel reads 0. A program
without the counters has no such rows, and a window with no column scanned
has no share: either reads nothing."""

from perfbench import ledger, stats


def read(obs):
    skipped = stats.per_rank(obs, ledger.engine(obs, "engine.scan_adc_cols_skipped"),
                             ledger.window_total)
    cols = stats.per_rank(obs, ledger.engine(obs, "engine.scan_adc_cols"),
                          ledger.window_total)
    if skipped is None or cols is None or not sum(cols):
        return None
    return 100.0 * sum(skipped) / sum(cols)
