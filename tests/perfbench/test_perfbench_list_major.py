"""``kernel.list_major_pct`` (PR 31): the reader on a pair of
``get_perf_stats`` snapshots as an IVF-flat rank gives them, with the row
and without it, and its one entry in ``BENCHMARK.json``."""

import os

import pytest

from perfbench import loader
from pb_helpers import REPO

NAME = "kernel.list_major_pct"


def snapshots(scans, chosen, ranks=1):
    """A window of ``scans`` probe scans a rank, ``chosen`` of them in the
    list-major order; ``chosen`` None is a program that has no such row."""
    def snap(n_scan, n_chosen):
        block = {"engine.scan": {"count": n_scan, "total_s": 0.12 * n_scan}}
        if n_chosen is not None:
            block["engine.scan_listmajor"] = {"count": n_chosen,
                                              "total_s": float(n_chosen)}
        return {"engine": {"bench": block}}

    return {"index_id": "bench", "window_s": 20.0,
            "stats_before": [snap(4, None if chosen is None else 4)] * ranks,
            "stats_after": [snap(4 + scans, None if chosen is None else 4 + chosen)] * ranks}


@pytest.fixture(scope="module")
def reader():
    return loader.load_module(os.path.join(REPO, "perfbench", "layer_metrics",
                                           f"{NAME}.py"))


@pytest.mark.parametrize("scans,chosen,ranks,want", [
    (160, 160, 1, 100.0),   # every launch of the window
    (160, 160, 4, 100.0),
    (160, 0, 1, 0.0),       # the Pallas arm served: the query-major order
    (160, 40, 1, 25.0),
    (19, None, 1, None),    # the parent commit: no such row
    (0, 0, 1, None),        # no scan in the window: no share to give
])
def test_the_share_of_scans_that_took_the_list_major_order(reader, scans, chosen,
                                                           ranks, want):
    assert reader.read(snapshots(scans, chosen, ranks)) == want


def test_an_untraced_run_has_no_snapshots_and_reads_nothing(reader):
    assert reader.read({"index_id": "bench", "window_s": 20.0}) is None


def test_the_metric_is_listed_once_for_the_ivfsq_cell():
    bench = loader.read_json(os.path.join(REPO, "BENCHMARK.json"))
    entry = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert len(entry) == 1
    assert entry[0] == {"name": NAME, "unit": "%", "better": "higher",
                        "source": "program_counter", "layer": "models and kernels",
                        "moves": "qps", "workloads": ["ivfsq-batch"]}
