"""``kernel.adc_skip_pct`` (the counters are PR 35's, the metric PR 37's):
the reader on a pair of ``get_perf_stats`` snapshots as an IVF-PQ rank gives
them, with the two rows and without them, and its two entries in
``BENCHMARK.json``."""

import os

import pytest

from perfbench import loader
from pb_helpers import REPO

NAME = "kernel.adc_skip_pct"
# a launch of 128 rows x 32 probes at a padded capacity of 1024
COLS = 128 * 32 * 1024.0


def snapshots(scans, skipped_share, ranks=1, rows=("cols", "skipped")):
    """A window of ``scans`` scans a rank, ``skipped_share`` of whose
    columns the kernel did not compute; ``rows`` are the count rows the
    program has."""
    def snap(n):
        block = {"engine.scan": {"count": n, "total_s": 0.0166 * n}}
        if "cols" in rows:
            block["engine.scan_adc_cols"] = {"count": n, "total_s": COLS * n}
        if "skipped" in rows:
            block["engine.scan_adc_cols_skipped"] = {
                "count": n, "total_s": skipped_share * COLS * n}
        return {"engine": {"bench": block}}

    return {"index_id": "bench", "window_s": 20.0,
            "stats_before": [snap(5)] * ranks, "stats_after": [snap(5 + scans)] * ranks}


@pytest.fixture(scope="module", params=[NAME, NAME + ".online"])
def reader(request):
    return loader.load_module(os.path.join(REPO, "perfbench", "layer_metrics",
                                           f"{request.param}.py"))


@pytest.mark.parametrize("obs,want", [
    (snapshots(952, 0.65625), 65.625),                  # the whole window
    (snapshots(952, 0.65625, ranks=4), 65.625),         # four ranks together
    (snapshots(952, 0.0), 0.0),                         # none skipped: the XLA arm
    (snapshots(952, 0.5, rows=("cols",)), None),        # a row missing
    (snapshots(952, 0.5, rows=("skipped",)), None),
    (snapshots(952, 0.5, rows=()), None),               # the parent of PR 35
    (snapshots(0, 0.5), None),                          # no scan: no share to give
    ({"index_id": "bench", "window_s": 20.0}, None),    # an untraced run
], ids=["whole-window", "four-ranks", "none-skipped", "no-skipped-row", "no-cols-row",
        "neither-row", "no-scan", "untraced"])
def test_the_share_of_candidate_columns_the_adc_scan_skipped(reader, obs, want):
    assert reader.read(obs) == want


def test_ranks_are_summed_before_the_share_is_taken():
    """A rank that scanned more weighs more: 0.75 of 1 part and 0.25 of 3."""
    reader = loader.load_module(os.path.join(REPO, "perfbench", "layer_metrics",
                                             f"{NAME}.py"))
    a, b = snapshots(100, 0.75), snapshots(300, 0.25)
    obs = {**a, "stats_before": a["stats_before"] + b["stats_before"],
           "stats_after": a["stats_after"] + b["stats_after"]}
    assert reader.read(obs) == pytest.approx(100.0 * (75 + 75) / 400)


@pytest.mark.parametrize("name,moves,cells", [
    (NAME, "qps", ["knnlm-batch", "knnlm-4rank-batch"]),
    (NAME + ".online", "lat_p50_ms", ["knnlm-online"]),
])
def test_the_metric_is_listed_once_for_the_knnlm_cells(name, moves, cells):
    bench = loader.read_json(os.path.join(REPO, "BENCHMARK.json"))
    entry = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == [{"name": name, "unit": "%", "better": "higher",
                      "source": "program_counter", "layer": "models and kernels",
                      "moves": moves, "workloads": cells}]
