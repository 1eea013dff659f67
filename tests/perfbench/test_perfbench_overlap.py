"""``engine.overlap_pct`` (ISSUE 41): the reader on a pair of
``get_perf_stats`` snapshots as a rank gives them, with the count row
``engine.launch_overlapped`` and without it (the parent commit), the row as
an engine serves it (at zero beside ``device_search_s`` until booked), and
the two entries appended to ``BENCHMARK.json``."""

import os
import time

import numpy as np
import pytest

from perfbench import loader
from pb_helpers import REPO

NAME = "engine.overlap_pct"


def snapshots(windows, overlapped, ranks=1, rows=("launch", "overlapped")):
    """A window of ``windows`` merged windows a rank, ``overlapped`` of them
    launched over an uncollected one; ``rows`` are the rows the program has."""
    def snap(n, m):
        block = {"engine.scan": {"count": n, "total_s": 0.0166 * n}}
        if "launch" in rows:
            block["device_search_s"] = {"count": n, "total_s": 0.019 * n}
        if "overlapped" in rows:
            block["engine.launch_overlapped"] = {"count": m, "total_s": float(m)}
        return {"engine": {"bench": block}}

    return {"index_id": "bench", "window_s": 20.0,
            "stats_before": [snap(7, 3)] * ranks,
            "stats_after": [snap(7 + windows, 3 + overlapped)] * ranks}


@pytest.fixture(scope="module", params=[NAME, NAME + ".online"])
def reader(request):
    return loader.load_module(os.path.join(REPO, "perfbench", "layer_metrics",
                                           f"{request.param}.py"))


@pytest.mark.parametrize("obs,want", [
    (snapshots(1000, 950), 95.0),                          # the whole window
    (snapshots(1000, 950, ranks=4), 95.0),                 # four ranks together
    (snapshots(1000, 0), 0.0),                             # one window at a time
    (snapshots(1000, 950, rows=("launch",)), None),        # the parent: no such row
    (snapshots(1000, 950, rows=("overlapped",)), None),
    (snapshots(1000, 950, rows=()), None),
    (snapshots(0, 0), None),                               # no launch: no share to give
    ({"index_id": "bench", "window_s": 20.0}, None),       # an untraced run
], ids=["whole-window", "four-ranks", "none-overlapped", "no-overlapped-row",
        "no-launch-row", "neither-row", "no-launch", "untraced"])
def test_the_share_of_windows_launched_over_an_uncollected_one(reader, obs, want):
    assert reader.read(obs) == want


def test_ranks_are_summed_before_the_share_is_taken():
    """A rank that launched more weighs more: 0.9 of 1 part and 0.5 of 3."""
    reader = loader.load_module(os.path.join(REPO, "perfbench", "layer_metrics",
                                             f"{NAME}.py"))
    a, b = snapshots(100, 90), snapshots(300, 150)
    obs = {**a, "stats_before": a["stats_before"] + b["stats_before"],
           "stats_after": a["stats_after"] + b["stats_after"]}
    assert reader.read(obs) == pytest.approx(100.0 * (90 + 150) / 400)


def test_the_two_entries_are_appended_to_per_layer():
    bench = loader.read_json(os.path.join(REPO, "BENCHMARK.json"))
    layer = "engine launch-to-fetch, join"
    assert bench["per_layer"][-2:] == [
        {"name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
         "layer": layer, "moves": "qps",
         "workloads": ["knnlm-batch", "ivfsq-batch", "knnlm-4rank-batch", "flat768-batch"]},
        {"name": NAME + ".online", "unit": "%", "better": "higher",
         "source": "program_counter", "layer": layer, "moves": "lat_p50_ms",
         "workloads": ["knnlm-online"]}]
    assert layer in {m["layer"] for m in bench["per_layer"][:-2]}
    for cell in bench["per_layer"][-2]["workloads"] + bench["per_layer"][-1]["workloads"]:
        assert cell in {w["name"] for w in bench["workloads"]}


def test_the_engine_serves_the_row_at_zero_until_a_launch_books_it(tmp_path):
    """Beside ``device_search_s`` from the first launch on, at zero while
    every window is collected before the next is launched; one record a
    window launched over an uncollected one, whichever is collected first;
    and the reader reads the engine's own rows."""
    from distributed_faiss_tpu import Index, IndexCfg, IndexState

    rng = np.random.default_rng(0)
    x = rng.standard_normal((600, 16)).astype(np.float32)
    cfg = IndexCfg(index_builder_type="flat", dim=16, metric="l2", train_num=0)
    cfg.index_storage_dir = str(tmp_path)
    idx = Index(cfg)
    assert "engine.launch_overlapped" not in idx.perf_stats()
    idx.add_batch(x, list(range(600)), train_async_if_triggered=False)
    idx.train()
    deadline = time.time() + 60
    while (idx.get_state() != IndexState.TRAINED
           or idx.get_idx_data_num() != (0, 600)):
        assert time.time() < deadline, "train/drain timed out"
        time.sleep(0.02)
    before = idx.perf_stats()
    want = [idx.search_batched(x[:4], 3) for _ in range(3)][0]
    served = idx.perf_stats()
    assert served["device_search_s"]["count"] == 3
    assert served["engine.launch_overlapped"]["count"] == 0
    first = idx.launch_batched(x[:4], 3)
    second = idx.launch_batched(x[:4], 3)   # over the first
    third = idx.launch_batched(x[:4], 3)    # over both
    for handle in (second, first, third):
        scores, meta, _ = handle.collect()
        np.testing.assert_array_equal(scores, want[0])
        assert meta == want[1]
    alone = idx.launch_batched(x[:4], 3)    # nothing uncollected
    alone.collect()
    after = idx.perf_stats()
    assert after["device_search_s"]["count"] == 7
    assert after["engine.launch_overlapped"]["count"] == 2
    for name in ("engine.lock_wait", "engine.feed", "engine.scan",
                 "engine.refine_fetch", "engine.join", "device_search_rows"):
        assert after[name]["count"] == 7, name
    reader = loader.load_module(os.path.join(REPO, "perfbench", "layer_metrics",
                                             f"{NAME}.py"))
    obs = {"index_id": "i", "window_s": 1.0,
           "stats_before": [{"engine": {"i": before}}],
           "stats_after": [{"engine": {"i": after}}]}
    assert reader.read(obs) == pytest.approx(100.0 * 2 / 7)
    idx.retire()
