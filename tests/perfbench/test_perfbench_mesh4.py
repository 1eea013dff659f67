"""The cell ``knnlm-mesh4-batch`` (PR 45): its three readers on hand-made
observations, as a mesh rank's program gives them and as the parent's does
(no such stage); ``mesh_bytes``, the count behind ``kernel.mesh_roofline``;
its entries in ``BENCHMARK.json``; and the cell itself rehearsed on the CPU
with FOUR virtual devices for the rank it starts, traced, so that every
per-layer entry the cell is listed in prints a value. Nothing timed here is
a speed."""

import ast
import os

import pytest

from perfbench import loader, mesh_bytes, search_bytes
from pb_helpers import REPO
from test_perfbench_rehearsal import metrics_of, rehearse  # noqa: F401 (a fixture)

CELL = "knnlm-mesh4-batch"
NEW = ("kernel.mesh_roofline", "engine.launches_per_window", "mesh.place_ms")
# entries knnlm-4rank-batch is in and this cell is not: a roofline that
# divides a whole index by one chip's peak, two of a fan-out it does not have,
# and three that read the host's share of a launch, where a mesh rank's scan
# callable waits for the chip itself (engine.dispatch covers the whole scan)
LEFT_OUT = {"kernel.search_roofline", "client.fanout_skew_ms", "client.merge_ms",
            "engine.dispatch_ms", "kernel.scan_ms", "engine.host_serial_pct"}
BENCH = loader.read_json(os.path.join(REPO, "BENCHMARK.json"))
CONFIG = loader.Cell(CELL).config
INDEX = CONFIG["index"]
KIND = "TPU v5 lite"


def reader_of(name):
    return loader.load_module(os.path.join(REPO, "perfbench", "layer_metrics",
                                           f"{name}.py"))


def row(count, total):
    return {"count": count, "total_s": total}


def observed(windows=100, launches_a_window=1.0, place_s=0.0004, mesh=True,
             busy_s=3.0, chips=4, ops=(), platform="tpu"):
    """What a traced run hands a reader after ``windows`` merged windows of
    256 rows on one rank: the two snapshots, the rank's reduced trace and
    the device it reported. ``mesh`` False is a program without what PR 45
    adds (no ``engine.mesh_place``), on a local index (no
    ``device_launches``)."""
    def snap(n):
        engine = {"device_search_s": row(7 + n, 0.04 * n), "engine.scan": row(7 + n, 0.03 * n)}
        if mesh:
            engine["device_launches"] = row(7 + n, 7 + launches_a_window * n)
            engine["engine.mesh_place"] = row(3 * (7 + n), 0.01 + place_s * n)
        return {"scheduler": {"queues": {"batch_rows": row(7 + n, 256.0 * (7 + n))}},
                "engine": {"bench": engine}}

    return {"index_id": "bench", "window_s": 20.0, "config": CONFIG,
            "stats_before": [snap(0)], "stats_after": [snap(windows)],
            "cell": CELL, "devices": [{"count": chips, "device_kind": KIND,
                                       "platform": platform}],
            "traces": [{"busy_s": busy_s, "device_ops": [list(op) for op in ops]}]}


# ------------------------------------------------------------------ the entries


@pytest.mark.parametrize("name", NEW)
def test_the_three_entries_are_appended_and_list_the_cell_alone(name):
    entry = loader.by_name(BENCH["per_layer"], name, "metric")
    assert entry["workloads"] == [CELL] and entry["moves"] == "qps"
    assert [m["name"] for m in BENCH["per_layer"][-3:]] == list(NEW)
    assert callable(reader_of(name).read)


def test_no_entry_reads_the_collectives_off_the_ten_longest_operations():
    """``trace_reduce.reduce`` keeps a trace's ten longest operations, and a
    mesh rank's collectives stand tenth or lower: a share read off them is
    0 or a part, by which one made the list. The entry waits for a
    reduction that sums them over the whole trace (PERF.md 7.3)."""
    assert not [m["name"] for m in BENCH["per_layer"] if "collective" in m["name"]]
    assert not os.path.exists(os.path.join(REPO, "perfbench", "layer_metrics",
                                           "mesh.collective_pct.py"))


def test_the_cell_is_the_last_of_every_list_it_joined_and_skips_six():
    """It shares its kernels and its serving path with ``knnlm-4rank-batch``
    and joins its lists, but for ``LEFT_OUT``."""
    cell = loader.by_name(BENCH["workloads"], CELL, "workload")
    assert cell == {**cell, "config": "knnlm-mesh4", "traffic": "batch16x64", "chips": 4}
    assert BENCH["workloads"][-1] is cell and BENCH["configs"][-1]["name"] == "knnlm-mesh4"
    left_out = set()
    for entry in BENCH["per_layer"]:
        cells = entry.get("workloads", [])
        if CELL in cells:
            assert cells[-1] == CELL and cells.count(CELL) == 1, entry["name"]
        elif "knnlm-4rank-batch" in cells:
            left_out.add(entry["name"])
    assert left_out == LEFT_OUT
    qps = loader.by_name(BENCH["end_to_end"], "qps", "metric")
    assert qps["workloads"][-1] == CELL
    config = CONFIG
    assert INDEX["shard_lists"] is True and INDEX["mesh_devices"] == 0
    # the cell names the kernel a mesh index chooses by itself on a TPU: the
    # check runs a new cell on the parent's program too, which cannot choose
    # and would run the XLA one-hot at a fortieth of the rate (config.json,
    # ``assumed.pallas_adc``)
    assert INDEX["pallas_adc"] is True
    four_rank = loader.Cell("knnlm-4rank-batch").config
    assert (config["k"], config["rows"]) == (four_rank["k"], four_rank["rows"])
    # the same rows for the same seed: the two layouts of one host answer
    # the same data, under the same guarantees and limits
    assert config["corpus"] == four_rank["corpus"]
    assert config["guarantees"] == four_rank["guarantees"]
    assert ({k: v for k, v in config["limits"].items() if not k.endswith("_reason")}
            == {k: v for k, v in four_rank["limits"].items() if not k.endswith("_reason")})
    # a sub-cluster, where a query's neighbours are, is 320 rows of the one
    # index and 80 of each rank's; a chip of the mesh keeps its own shortlist
    # of the lists it owns, so the one index carries the four ranks' shortlists
    # together (with 8 recall@10 read 0.92-0.94 on the chip)
    per_sub = config["rows"] / (config["corpus"]["latent_clusters"]
                                * config["corpus"]["sub_clusters"])
    assert per_sub == 320 == config["k"] * INDEX["refine_k_factor"]
    assert INDEX["refine_k_factor"] == (four_rank["ranks"]
                                        * four_rank["index"]["refine_k_factor"])
    assert {k: v for k, v in config["index"].items()
            if k not in ("shard_lists", "mesh_devices", "pallas_adc",
                         "refine_k_factor")} == {
                k: v for k, v in four_rank["index"].items() if k != "refine_k_factor"}


# -------------------------------------------------------------- the three readers


def test_launches_per_window_reads_the_serving_contract():
    read = reader_of("engine.launches_per_window").read
    assert read(observed()) == pytest.approx(1.0)
    assert read(observed(launches_a_window=2.0)) == pytest.approx(2.0)  # a demoted kernel
    assert read(observed(mesh=False)) is None  # a local index has no such row
    assert read({"index_id": "bench"}) is None  # an untraced run


def test_place_ms_is_the_stage_over_the_launches():
    read = reader_of("mesh.place_ms").read
    assert read(observed(place_s=0.0004)) == pytest.approx(0.4)
    assert read(observed(mesh=False)) is None  # the parent's program: no such stage
    assert read(observed(windows=0)) is None
    assert read({"index_id": "bench"}) is None


def test_mesh_roofline_is_a_chips_least_time_over_its_busy_time(capsys):
    read = reader_of("kernel.mesh_roofline").read
    least_s, bound = mesh_bytes.roofline_seconds(INDEX, 4_000_000, 10, 256.0, 4, KIND)
    # a launch that does the least work and nothing else reads 100, no more
    assert read(observed(windows=100, busy_s=100 * least_s)) == pytest.approx(100.0)
    assert read(observed(windows=100, busy_s=100 * 0.030)) == pytest.approx(
        100.0 * least_s / 0.030)
    assert f"least {least_s * 1e6:.1f} us ({bound}-bound)" in capsys.readouterr().out
    assert read(observed(windows=0)) is None
    assert read({"index_id": "bench"}) is None


@pytest.mark.parametrize("chips, platform, refused", [
    (4, "tpu", False), (8, "tpu", True), (1, "tpu", True),
    (1, "cpu", False), (8, "cpu", False)])
def test_mesh_roofline_refuses_a_mesh_of_another_size_than_the_cells(
        chips, platform, refused):
    """``mesh_devices: 0`` takes every chip of the host; the cell says 4."""
    read = reader_of("kernel.mesh_roofline").read
    if refused:
        with pytest.raises(ValueError, match=f"asks for 4 chips .* mesh of {chips}"):
            read(observed(chips=chips, platform=platform))
    else:
        assert read(observed(chips=chips, platform=platform)) > 0


# -------------------------------------------------------------------- the count


def test_a_window_probes_between_one_querys_lists_and_all():
    nlist, nprobe = int(INDEX["centroids"]), int(INDEX["nprobe"])
    assert mesh_bytes.probed_lists(nlist, nprobe, 1) == pytest.approx(nprobe)
    seen = [mesh_bytes.probed_lists(nlist, nprobe, nq) for nq in (1, 2, 64, 256, 10**6)]
    assert seen == sorted(seen) and seen[-1] == pytest.approx(nlist)
    for nq, lists in zip((1, 2, 64, 256), seen):
        assert nprobe <= lists <= min(nlist, nq * nprobe)
    assert seen[3] == pytest.approx(3546, abs=1)  # the cell's window


@pytest.mark.parametrize("nq", [1, 64, 256])
@pytest.mark.parametrize("chips", [1, 2, 4, 8])
def test_a_chips_least_work_lies_between_the_whole_and_its_share(chips, nq):
    """With one chip and one query it is ``search_bytes``' count (which takes
    ``nprobe`` lists a window, the lower end); with more queries the lists a
    window probes are counted, and with more chips a chip needs no more than
    the whole mesh's least and no less than its share of it: never over 100%
    for a launch that does the least work."""
    rows, k = 4_000_000, 10
    whole_b = mesh_bytes.least_bytes(INDEX, rows, k, nq, 1)
    whole_o = mesh_bytes.least_ops(INDEX, rows, k, nq, 1)
    b = mesh_bytes.least_bytes(INDEX, rows, k, nq, chips)
    o = mesh_bytes.least_ops(INDEX, rows, k, nq, chips)
    assert whole_b / chips <= b <= whole_b and whole_o / chips <= o <= whole_o
    assert whole_o == search_bytes.least_ops(INDEX, rows, k, nq)
    assert whole_b >= search_bytes.least_bytes(INDEX, rows, k, nq)
    if nq == 1:
        assert whole_b == pytest.approx(search_bytes.least_bytes(INDEX, rows, k, nq))
    if chips > 1:
        assert b < whole_b and o < whole_o
    least_s, _ = mesh_bytes.roofline_seconds(INDEX, rows, k, nq, chips, KIND)
    assert 0 < least_s <= mesh_bytes.roofline_seconds(INDEX, rows, k, nq, 1, KIND)[0]


def test_a_device_the_table_lacks_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        mesh_bytes.roofline_seconds(INDEX, 4_000_000, 10, 256, 4, "TPU v9")


# ------------------------------------------------------------- the cell, rehearsed


def test_rehearse_the_mesh_cell_traced_on_four_virtual_devices(rehearse, monkeypatch):
    """The rank the run starts gets four virtual CPU devices, and
    ``mesh_devices: 0`` makes a four-device mesh of them."""
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    rc, lines, result = rehearse(CELL, 1)
    assert rc == 0
    assert result["correct"] is True, lines
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] == 4
    (in_use,) = [ln for ln in lines if ln.startswith("bytes_in_use per rank after set-up")]
    (per_device,) = ast.literal_eval(in_use.split(": ", 1)[1])
    assert len(per_device) == 4  # the rank lists four devices (a CPU counts no bytes)
    # every per-layer entry the cell is listed in printed a value
    assert set(result["metrics"]) == metrics_of(CELL, "per_layer", rehearse.root)
    values = {name: m["value"] for name, m in result["metrics"].items()}
    assert set(NEW) <= set(values)
    assert values["engine.launches_per_window"] == pytest.approx(1.0)
    assert values["mesh.place_ms"] > 0
    assert 0 < values["kernel.mesh_roofline"]
    # the cell forces its kernel, so a CPU runs it too, in the interpreter,
    # inside the mesh program, past the first-use check
    assert values["kernel.adc_fused_pct"] == 100
    assert 0 <= values["kernel.adc_skip_pct"] < 100
    assert values["engine.overlap_pct"] == 0  # a mesh rank serves one window at a time
