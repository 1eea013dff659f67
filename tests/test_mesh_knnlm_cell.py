"""The ``knnlm-mesh4`` deployment at a small size on the CPU: a ``knnlm``
index whose lists are partitioned over a four-device mesh inside ONE
index-server rank (``shard_lists: true``, ``parallel/mesh.py``).

- through a real ``IndexServer`` and ``IndexClient`` (the rank a process of
  its own with four virtual CPU devices), against the configuration's plain
  reference and inside the configuration's own limits; and the control: the
  same rows put through 8 bits come out NOT correct;
- the share tied to the whole: over the same trained centroids and
  codebooks the mesh index and the local ``IVFPQIndex`` give the same
  answers;
- the mesh index chooses its ADC kernel as the local index does;
- a mesh search books the count rows and the stage the local index books,
  and ``engine.mesh_place``.

Nothing timed here is a speed.
"""

import os
import socket
import time

import numpy as np
import pytest

from distributed_faiss_tpu.models.factory import build_index, index_from_state_dict
from distributed_faiss_tpu.models.ivf import IVFPQIndex
from distributed_faiss_tpu.ops import adc_pallas
from distributed_faiss_tpu.parallel import mesh as meshmod
from distributed_faiss_tpu.parallel.client import IndexClient
from distributed_faiss_tpu.parallel.mesh import ShardedIVFPQIndex, make_mesh
from distributed_faiss_tpu.testing.chaos import ServerHarness
from distributed_faiss_tpu.utils import tracing
from distributed_faiss_tpu.utils.config import IndexCfg
from distributed_faiss_tpu.utils.state import IndexState
from perfbench import control, corpus, correctness, load_gen, loader

pytestmark = pytest.mark.mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 45
CHIPS = 4


# ------------------------------------------------- served, against the reference


def cut_to_the_cpu(config):
    """The deployment's file with its scale cut and its kernel flag taken
    out, and nothing else: every published width (d 768, PQ 64x8, k 10), the
    shortlist, the precision, the guarantees and the limits stay, so the
    limits mean here what they mean on the chip. The cell's file names its
    kernel for the benchmark's check alone (its ``assumed.pallas_adc``); an
    operator's file carries no flag, the index chooses, and that is what
    this module serves: on a CPU the XLA arm."""
    config = {**config, "rows": 2000, "index": dict(config["index"]),
              "corpus": dict(config["corpus"])}
    del config["index"]["pallas_adc"]
    config["index"].update(centroids=16, nprobe=8, train_num=1000, buffer_bsz=500)
    config["corpus"].update(latent_clusters=8, sub_clusters=4)
    return config


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One rank with four virtual devices serving two indexes of the cut
    deployment: ``f16`` over the seeded rows, ``int8`` over the same rows
    put through 8 bits (what SQ8 would keep: ``perfbench/control.as_int8``).
    Yields what the comparisons need."""
    cell = loader.Cell("knnlm-mesh4-batch")
    config = cut_to_the_cpu(cell.config)
    mix = corpus.mixture_for(config, SEED)
    bsz = config["index"]["buffer_bsz"]
    chunks = [mix.chunk(corpus.CORPUS, i, bsz) for i in range(config["rows"] // bsz)]
    pool = mix.chunk(corpus.QUERIES, 0, 64)
    tmp = tmp_path_factory.mktemp("mesh4")
    with socket.socket() as s:
        s.bind(("", 0))
        port = s.getsockname()[1]
    env = {"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={CHIPS}"}
    with ServerHarness(1, str(tmp / "disc.txt"), str(tmp / "storage"),
                       base_port=port, env=env):
        client = IndexClient(str(tmp / "disc.txt"))
        try:
            acked = {}
            for index_id, kept in (("f16", chunks), ("int8", control.as_int8(chunks))):
                client.create_index(index_id, IndexCfg(**config["index"]))
                acked[index_id] = 0
                for i, x in enumerate(kept):
                    client.add_index_data(index_id, x, list(range(i * bsz, (i + 1) * bsz)))
                    acked[index_id] += x.shape[0]
                client.sync_train(index_id)
                deadline = time.time() + 240
                while not (client.get_state(index_id) == IndexState.TRAINED
                           and client.get_ntotal(index_id) == acked[index_id]):
                    assert time.time() < deadline, "the rank never indexed every row"
                    time.sleep(0.1)
            yield {"client": client, "config": config, "chunks": chunks, "pool": pool,
                   "reference": cell.reference, "acked": acked}
        finally:
            client.close()


def window_of(served, index_id):
    """Two 32-row requests, as the harness keeps a window's."""
    return [load_gen.search_once(served["client"], index_id, served["config"]["k"],
                                 served["pool"][s:s + 32], s) for s in (0, 32)]


def test_the_rank_holds_a_four_device_mesh(served):
    (ping,) = served["client"].ping(timeout=60.0)
    assert ping["device"]["count"] == CHIPS
    (stats,) = served["client"].get_perf_stats()
    assert "error" not in stats


def test_served_mesh_index_is_inside_the_deployments_limits(served):
    """recall@10 >= 0.95, distance_gap_rel <= 1.5e-3 (the configuration's
    own: float16 refine rows read 1.6e-4 to 3.6e-4 on the chip), every
    acknowledged row indexed, a stored row its own nearest."""
    config, client = served["config"], served["client"]
    checks = correctness.Checks()
    checks.add("ntotal_gap", abs(client.get_ntotal("f16") - served["acked"]["f16"]),
               "<=", 0)
    ids, rows = correctness.self_lookup_rows(served["chunks"], SEED, 32)
    r = load_gen.search_once(client, "f16", config["k"], rows, 0)
    assert r.ok, r.error
    checks.add("self_lookup_misses", int((r.ids[:, 0] != ids).sum()), "<=", 0)
    correctness.compare_window(checks, config, served["reference"], served["chunks"],
                               served["pool"], window_of(served, "f16"), SEED)
    assert [row[0] for row in checks.rows] == [
        "ntotal_gap", "self_lookup_misses", "failed_requests", "recall_at_10",
        "distance_gap_rel"]
    assert checks.correct, checks.lines()


def test_rows_put_through_8_bits_fail_a_limit(served):
    """The control: the nearest precision below the one the configuration
    states (float16 refine rows) is 8-bit rows. The same index over them
    finds the same neighbours, and its distances are the 8-bit rows', which
    the reference over the untouched rows tells apart: ``distance_gap_rel``
    reads several times the 1.5e-3 limit (9.4e-3 and up at the cell's size,
    PERF.md section 2), so the comparison comes out not correct, by that
    limit and not by every one."""
    checks = correctness.Checks()
    correctness.compare_window(checks, served["config"], served["reference"],
                               served["chunks"], served["pool"],
                               window_of(served, "int8"), SEED)
    by_name = {row[0]: row for row in checks.rows}
    assert not checks.correct
    assert by_name["failed_requests"][-1] and by_name["recall_at_10"][-1]
    name, value, _, limit, ok = by_name["distance_gap_rel"]
    assert not ok and value > 2 * limit, (value, limit)


def test_a_served_window_books_the_mesh_rows(served):
    """One launch a window, on the XLA arm (a CPU, no flag), zero columns
    skipped, and the host's placement of the replicated operands."""
    client = served["client"]
    stats = client.get_perf_stats()[0]
    before, chip_before = stats["engine"]["f16"], stats["scheduler"]["queues"]
    window_of(served, "f16")
    stats = client.get_perf_stats()[0]
    after, chip_after = stats["engine"]["f16"], stats["scheduler"]["queues"]

    def moved(name, field="count"):
        return after[name][field] - before.get(name, {"count": 0, "total_s": 0.0})[field]

    windows = moved("device_search_s")
    assert windows >= 1
    assert moved("device_launches") == windows
    assert moved("device_launches", "total_s") == windows  # 1.0 a window
    assert moved("engine.scan") == windows == moved("engine.scan_adc_cols")
    assert moved("engine.scan_fused") == 0
    assert moved("engine.scan_adc_cols", "total_s") > 0
    assert moved("engine.scan_adc_cols_skipped", "total_s") == 0
    assert moved("engine.mesh_place") >= windows
    assert 0 < moved("engine.mesh_place", "total_s") < moved("device_search_s", "total_s")
    assert moved("engine.launch_overlapped") == 0  # one window at a time
    # the chip's timeline holds the window's span on the chips: the mesh's
    # scan callable waits itself, so ``dispatched`` is taken at its launch
    # (``_counted``), not once it returns (0.8 ms of a 50 ms window, PR 45)
    busy = (chip_after["sched.chip_busy"]["total_s"]
            - chip_before.get("sched.chip_busy", {"total_s": 0.0})["total_s"])
    assert busy > 0.5 * moved("engine.scan", "total_s")


# ---------------------------------------------------- the share tied to the whole


D, M, NLIST = 64, 8, 16


def clustered(rng, n):
    """Sixteen clusters, the first four times the others' size: the longest
    list sets the capacity and the others leave sub-tiles empty."""
    centers = 4.0 * np.random.default_rng(45).standard_normal(
        (NLIST, D)).astype(np.float32)  # the same for rows and queries
    share = np.r_[4.0, np.ones(NLIST - 1)]
    return (centers[rng.choice(NLIST, size=n, p=share / share.sum())]
            + rng.standard_normal((n, D)).astype(np.float32))


def twins(rng, refine, use_pallas=False):
    """A local index and a four-device mesh index over the same trained
    centroids and codebooks and the same rows."""
    x = clustered(rng, 3000)
    local = IVFPQIndex(D, NLIST, m=M, kmeans_iters=4, pq_iters=4,
                       use_pallas=use_pallas, refine_k_factor=refine)
    local.train(x)
    local.add(x)
    mesh = ShardedIVFPQIndex(D, NLIST, m=M, mesh=make_mesh(CHIPS),
                             use_pallas=use_pallas, refine_k_factor=refine)
    mesh.centroids, mesh.codebooks = local.centroids, local.codebooks
    mesh.lists = mesh._make_lists()
    mesh.add(x)
    for idx in (local, mesh):
        idx.set_nprobe(8)
    return local, mesh, x


def test_the_mesh_index_equals_the_local_index(rng):
    """No refine: a chip's top-k of the ADC scores of the lists it owns,
    merged over the mesh, is the unsharded index's top-k. Both programs take
    the same float32 sums of the same table entries, so the distances agree
    to float32 rounding (rtol 1e-6: the two reduce a pair's scores in
    different shapes) and the ids are equal wherever two candidates do not
    tie."""
    local, mesh, x = twins(rng, refine=0)
    q = clustered(rng, 70)
    want_d, want_i = local.search(q, 10)
    got_d, got_i = mesh.search(q, 10)
    np.testing.assert_allclose(got_d, want_d, rtol=1e-6, atol=1e-6)
    differ = got_i != want_i
    assert np.allclose(got_d[differ], want_d[differ], rtol=1e-6, atol=1e-6)
    assert differ.mean() < 0.02, "ids differ beyond the odd tie"
    assert mesh.lists.ntotal == local.lists.ntotal == x.shape[0]
    owned = np.asarray(mesh.lists.sizes).reshape(CHIPS, -1).sum(1)
    assert owned.sum() == x.shape[0] and owned.min() > 0  # every chip holds a share


def test_the_mesh_index_refines_a_superset_of_the_local_shortlist(rng):
    """With the exact refine every chip rescores its own top ``adc_k`` before
    the merge: a superset of the local index's shortlist, so rank by rank
    the mesh's exact distance is the local index's or nearer, an id both
    return carries the same float32 distance (rtol 1e-6: both are
    ``exact_candidate_scores`` over the same float16 row), and a local answer
    the mesh left out was rescored on its chip too and lost to ten rows at
    least as near. How many ids the two share depends on the rows drawn (the
    session's generator), so no count of them is asserted."""
    local, mesh, _ = twins(rng, refine=8)
    q = clustered(rng, 40)
    want_d, want_i = local.search(q, 10)
    got_d, got_i = mesh.search(q, 10)
    assert (got_d <= want_d * (1 + 1e-6) + 1e-6).all()
    for row in range(q.shape[0]):
        both, gi, wi = np.intersect1d(got_i[row], want_i[row], return_indices=True)
        np.testing.assert_allclose(got_d[row, gi], want_d[row, wi], rtol=1e-6)
        left_out = want_d[row][~np.isin(want_i[row], both)]
        assert (left_out >= got_d[row, -1] * (1 - 1e-6) - 1e-6).all()


# ------------------------------------------------------------- the kernel's choice


def spy_on_the_program(monkeypatch):
    program, launched = meshmod._sharded_ivf_pq_search, []

    def spy(*args, **kw):
        launched.append(kw["use_pallas"])
        return program(*args, **kw)

    monkeypatch.setattr(meshmod, "_sharded_ivf_pq_search", spy)
    return launched, program


def built(rng, **extra):
    idx = build_index(IndexCfg(index_builder_type="knnlm", dim=D, metric="l2",
                               centroids=NLIST, code_size=M, nprobe=8,
                               refine_k_factor=8, shard_lists=True,
                               mesh_devices=CHIPS, **extra))
    x = clustered(rng, 3000)
    idx.train(x)
    idx.add(x)
    return idx, x


def test_with_no_flag_the_index_chooses_and_a_cpu_gets_the_xla_arm(rng, monkeypatch):
    idx, x = built(rng)
    assert isinstance(idx, ShardedIVFPQIndex) and idx.use_pallas is None
    assert adc_pallas.planes_supported(M, 256, idx.lists.cap)
    assert not idx._kernel_applies()  # the geometry holds; this is no TPU
    launched, _ = spy_on_the_program(monkeypatch)
    for rows in (5, 40):
        before = idx.launches
        idx.search(x[:rows], 10)
        assert idx.launches == before + 1  # one launch a window
    assert launched == [False, False]
    # where the code sees a TPU the same index takes the kernel
    monkeypatch.setattr(adc_pallas, "on_tpu", lambda: True)
    assert idx._kernel_applies()
    # and a snapshot keeps the intent as held: "choose" stays "choose"
    state = idx.state_dict()
    assert state["pallas_adc"] is None
    assert index_from_state_dict(state).use_pallas is None
    assert index_from_state_dict({**state, "pallas_adc": True}).use_pallas is True
    assert index_from_state_dict({**state, "pallas_adc": False}).use_pallas is False


def test_a_forced_flag_walks_the_ladder(rng, monkeypatch):
    """The first fused scan is checked against the XLA arm on one small
    block (two counted launches), later windows are one launch each, and a
    kernel that raises is served by the XLA arm's second dispatch and
    demoted: ``device_launches`` reads 2.0 for that window, 1.0 after."""
    idx, x = built(rng, pallas_adc=True)
    assert idx.use_pallas is True and idx._kernel_applies()
    launched, program = spy_on_the_program(monkeypatch)
    sink = tracing.LatencyStats()
    with tracing.stage("engine.launch", sink=sink):
        before = idx.launches
        d1, i1 = idx.search(x[:6], 10)
        assert launched == [True, False, True] and idx.launches == before + 3
        assert idx._pallas_runtime_ok and idx._adc_validated
        idx.search(x[:6], 10)
        assert launched[3:] == [True] and idx.launches == before + 4
    assert sink.summary()["engine.scan_fused"]["count"] == 2
    np.testing.assert_array_equal(i1[:, 0], np.arange(6))

    program.clear_cache()  # so that the injected failure is traced

    def boom(*a, **k):
        raise RuntimeError("kernel abort (injected)")

    monkeypatch.setattr(adc_pallas, "adc_scan_pallas_planes", boom)
    try:
        before = idx.launches
        d2, i2 = idx.search(x[:6], 10)
        assert launched[4:] == [True, False] and idx.launches == before + 2
        assert idx._pallas_runtime_ok is False and idx.use_pallas is True
        np.testing.assert_array_equal(i2, i1)
        idx.search(x[:6], 10)
        assert launched[6:] == [False] and idx.launches == before + 3
    finally:
        program.clear_cache()  # the stand-in is baked into the traces


# ------------------------------------------------------------------ the count rows


@pytest.mark.parametrize("forced,nq", [(False, 12), (True, 12), (True, 37)],
                         ids=["xla-arm", "fused", "fused-multiblock"])
def test_a_mesh_search_books_the_three_count_rows(rng, monkeypatch, forced, nq):
    """By the local index's rule (``IVFPQIndex._book_adc_cols``): the
    window's padded rows x ``nprobe`` x capacity once, every pair counted on
    the chip that owns its list; the XLA arm skips none, the kernel what
    lies past the last 128-column sub-tile that holds a row."""
    from distributed_faiss_tpu.models import base

    monkeypatch.setattr(base, "MAX_QUERY_BLOCK", 8)
    _, idx, x = twins(rng, refine=8, use_pallas=forced)
    idx.set_nprobe(NLIST)  # every query probes every list: the count is closed
    idx._adc_validated = True  # the first-use check has a test of its own
    sink = tracing.LatencyStats()
    with tracing.stage("engine.launch", sink=sink):
        idx.search(x[:nq], 5)
    rows = sink.summary()
    padded = 8 * base._next_pow2(-(-nq // 8), 1)
    total = padded * NLIST * idx.lists.cap
    assert rows["engine.scan"]["count"] == 1 == rows["engine.scan_adc_cols"]["count"]
    assert rows["engine.scan_adc_cols"]["total_s"] == total
    assert rows["engine.scan_adc_cols_skipped"]["count"] == 1
    assert rows.get("engine.scan_fused", {"count": 0})["count"] == int(forced)
    assert rows["engine.mesh_place"]["count"] >= 1
    skipped = rows["engine.scan_adc_cols_skipped"]["total_s"]
    if not forced:
        assert skipped == 0
        return
    sizes = idx.lists.sizes_host
    assert skipped == padded * (idx.lists.cap - -(-sizes // 128) * 128).sum() > 0
