"""The ``flat768`` configuration's own yardstick: the byte model behind
``kernel.flat_roofline`` at hand-computed shapes, its three readers on a
made-up observation, and a traced CPU rehearsal of ``flat768-batch`` that
must return exactly the per-layer metrics ``BENCHMARK.json`` lists for the
cell. Nothing timed here is a speed."""

import os

import pytest

from perfbench import flat_bytes, loader
from pb_helpers import REPO
from test_perfbench_rehearsal import metrics_of, rehearse  # noqa: F401 (fixture)

V5E = "TPU v5 lite"
ROWS, DIM, K = 1751277, 768, 10


def reader(name):
    return loader.load_module(os.path.join(REPO, "perfbench", "layer_metrics",
                                           f"{name}.py"))


# ----------------------------------------------------------- the byte model


def test_the_cells_launch_is_memory_bound_at_6_57_ms():
    # 1,751,277 x 768 x 4 = 5,379,922,944; queries 128 x 768 x 4 = 393,216;
    # answers 128 x 10 x 8 = 10,240
    assert flat_bytes.least_bytes(DIM, ROWS, K, 128) == 5380326400
    assert flat_bytes.least_ops(DIM, ROWS, 128) == 2.0 * 128 * ROWS * DIM
    seconds, bound = flat_bytes.roofline_seconds(DIM, ROWS, K, 128, V5E)
    assert bound == "memory"
    assert seconds == pytest.approx(5380326400 / 819e9)
    assert seconds * 1e3 == pytest.approx(6.57, abs=0.005)


def test_a_full_window_is_still_memory_bound_and_a_1024_row_one_is_not():
    # operations pass bytes where 2 nq / 197e12 > 4 / 819e9: nq > 481
    seconds, bound = flat_bytes.roofline_seconds(DIM, ROWS, K, 256, V5E)
    assert bound == "memory" and seconds * 1e3 == pytest.approx(6.57, abs=0.005)
    assert flat_bytes.roofline_seconds(DIM, ROWS, K, 481, V5E)[1] == "memory"
    assert flat_bytes.roofline_seconds(DIM, ROWS, K, 482, V5E)[1] == "compute"
    seconds, bound = flat_bytes.roofline_seconds(DIM, ROWS, K, 1024, V5E)
    assert bound == "compute"
    assert seconds == pytest.approx(2.0 * 1024 * ROWS * DIM / 197e12)
    assert seconds * 1e3 == pytest.approx(13.98, abs=0.01)


def test_an_unknown_device_has_no_peak():
    with pytest.raises(KeyError, match="no peaks"):
        flat_bytes.roofline_seconds(DIM, ROWS, K, 128, "TPU v9")


# -------------------------------------------------------------- the readers


def row(count, total):
    return {"count": count, "total_s": total}


def made_up_obs(launches=100, busy_s=5.0, scan_rows=2 ** 21, grow_s=0.75):
    """One rank; a window of ``launches`` launches of 128 rows each."""
    before = {"scheduler": {"queues": {"batch_rows": row(10, 640.0)}},
              "engine": {"bench": {
                  "device_search_s": row(10, 1.0),
                  "engine.scan_rows": row(10, 10.0 * scan_rows),
                  "engine.store_grow": row(6, grow_s)}}}
    after = {"scheduler": {"queues": {"batch_rows": row(10 + launches,
                                                        640.0 + 128.0 * launches)}},
             "engine": {"bench": {
                 "device_search_s": row(10 + launches, 1.0 + 0.05 * launches),
                 "engine.scan_rows": row(10 + launches,
                                         (10.0 + launches) * scan_rows),
                 "engine.store_grow": row(6, grow_s)}}}
    return {"stats_before": [before], "stats_after": [after], "window_s": 20.0,
            "index_id": "bench", "traces": [{"busy_s": busy_s}],
            "devices": [{"device_kind": V5E}],
            "config": {"rows": ROWS, "ranks": 1, "k": K, "index": {"dim": DIM}}}


def without(obs, name):
    """The observation as a program without the row ``name`` gives it."""
    def strip(stats):
        engine = {k: v for k, v in stats["engine"]["bench"].items() if k != name}
        return {**stats, "engine": {"bench": engine}}
    return {**obs, "stats_before": [strip(s) for s in obs["stats_before"]],
            "stats_after": [strip(s) for s in obs["stats_after"]]}


def test_flat_roofline_is_the_floor_over_the_busy_time_a_launch():
    # 100 launches in 5 busy seconds: 50 ms a launch against a 6.569 ms floor
    floor = 5380326400 / 819e9
    assert reader("kernel.flat_roofline").read(made_up_obs()) == pytest.approx(
        100.0 * floor / 0.05)
    # a program that takes the floor's time and no more reads 100, never over
    at_floor = made_up_obs(busy_s=100 * floor)
    assert reader("kernel.flat_roofline").read(at_floor) == pytest.approx(100.0)
    for slower in (1.0, 1.5, 40.0):
        assert reader("kernel.flat_roofline").read(
            made_up_obs(busy_s=slower * 100 * floor)) <= 100.0 + 1e-9


def test_flat_pad_pct_is_the_padding_share_of_the_rows_scanned():
    want = 100.0 * (1.0 - ROWS / 2 ** 21)
    assert want == pytest.approx(16.49, abs=0.005)
    assert reader("kernel.flat_pad_pct").read(made_up_obs()) == pytest.approx(want)
    # a store with no padding at all reads 0
    assert reader("kernel.flat_pad_pct").read(
        made_up_obs(scan_rows=ROWS)) == pytest.approx(0.0, abs=1e-9)


def test_store_grow_s_is_the_rows_total_when_the_window_starts():
    assert reader("setup.store_grow_s").read(made_up_obs()) == 0.75


@pytest.mark.parametrize("name,missing", [
    ("kernel.flat_roofline", "device_search_s"),
    ("kernel.flat_pad_pct", "engine.scan_rows"),
    ("setup.store_grow_s", "engine.store_grow"),
])
def test_a_reader_finds_nothing_where_its_row_is_missing(name, missing):
    """The parent commit has neither ``engine.scan_rows`` nor
    ``engine.store_grow``; an untraced run has no snapshots at all."""
    obs = made_up_obs()
    assert reader(name).read(without(obs, missing)) is None
    bare = {k: v for k, v in obs.items() if not k.startswith("stats_")}
    assert reader(name).read(bare) is None


def test_flat_roofline_reads_nothing_without_a_trace_or_a_launch():
    obs = made_up_obs()
    assert reader("kernel.flat_roofline").read({**obs, "traces": None}) is None
    assert reader("kernel.flat_roofline").read(made_up_obs(launches=0)) is None


# ------------------------------------------------------------ the rehearsal


def test_a_traced_rehearsal_returns_exactly_the_cells_per_layer_metrics(rehearse):
    rc, lines, result = rehearse("flat768-batch", 1)
    assert rc == 0
    assert result["correct"] is True, lines
    assert result["failed"] == 0
    listed = metrics_of("flat768-batch", "per_layer")
    assert {"kernel.flat_roofline", "kernel.flat_pad_pct", "setup.store_grow_s",
            "engine.host_serial_pct"} <= listed
    assert set(result["metrics"]) == listed, [
        ln for ln in lines if "not measured" in ln]
    m = {name: v["value"] for name, v in result["metrics"].items()}
    assert 0 < m["kernel.flat_roofline"]
    # 4000 rows in a store of MIN_CAP = 4096: every launch scans it once
    assert m["kernel.flat_pad_pct"] == pytest.approx(100.0 * (1 - 4000 / 4096))
    assert m["setup.store_grow_s"] > 0
    assert m["engine.window_compiles"] == 0
