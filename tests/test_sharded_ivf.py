"""Sharded-inverted-list IVF tests on the virtual 8-device mesh."""

import numpy as np
import pytest

from distributed_faiss_tpu.models.ivf import IVFFlatIndex
from distributed_faiss_tpu.parallel.mesh import ShardedIVFFlatIndex, ShardedPaddedLists, make_mesh


def brute_ids(q, x, k, metric):
    if metric == "dot":
        s = q @ x.T
    else:
        s = -((q[:, None, :] - x[None, :, :]) ** 2).sum(-1)
    return np.argsort(-s, axis=1)[:, :k]


def test_sharded_lists_bookkeeping(rng):
    m = make_mesh()
    lists = ShardedPaddedLists(10, (4,), np.float32, m, min_cap=8)
    li = rng.integers(0, 10, 50).astype(np.int64)
    rows = rng.standard_normal((50, 4)).astype(np.float32)
    lists.append(li, rows, np.arange(50, dtype=np.int64))
    assert lists.ntotal == 50
    np.testing.assert_array_equal(lists.sizes_host, np.bincount(li, minlength=10))
    # every appended row is present exactly once under its list's slot
    data = np.asarray(lists.data)
    ids = np.asarray(lists.ids)
    seen = ids[ids >= 0]
    assert sorted(seen.tolist()) == list(range(50))
    for g in range(50):
        slot = int(lists.slot_of(li[g]))
        row_pos = np.where(ids[slot] == g)[0]
        assert row_pos.size == 1
        np.testing.assert_allclose(data[slot, row_pos[0]], rows[g], rtol=1e-6)


def test_sharded_lists_growth(rng):
    m = make_mesh()
    lists = ShardedPaddedLists(4, (2,), np.float32, m, min_cap=8)
    for batch in range(4):
        li = np.zeros(16, np.int64)  # hammer one list to force growth
        rows = rng.standard_normal((16, 2)).astype(np.float32)
        lists.append(li, rows, np.arange(batch * 16, batch * 16 + 16, dtype=np.int64))
    assert lists.cap >= 64
    ids = np.asarray(lists.ids)
    assert sorted(ids[ids >= 0].tolist()) == list(range(64))


def test_sharded_lists_int32_cell_space_guard(rng):
    """nlist_pad * cap past int32 must refuse loudly, not wrap (scatter
    positions and the drop sentinel are int32 flat cell addresses)."""
    m = make_mesh()
    # construction-time guard fires before any device allocation
    with pytest.raises(ValueError, match="int32"):
        ShardedPaddedLists(2**26, (4,), np.float32, m, min_cap=64)
    # growth-time guard: small list count, growth request that would
    # overflow the flat space; raises before the pad allocates
    lists = ShardedPaddedLists(8, (2,), np.float32, m, min_cap=8)
    with pytest.raises(ValueError, match="int32"):
        lists._grow(2**28 + 1)
    assert lists.cap == 8  # untouched by the refused growth
    # a legal append still works after the refusal
    lists.append(np.zeros(4, np.int64), np.ones((4, 2), np.float32),
                 np.arange(4, dtype=np.int64))
    assert lists.ntotal == 4


@pytest.mark.parametrize("metric", ["dot", "l2"])
def test_sharded_ivf_full_probe_exact(rng, metric):
    """nprobe == nlist: sharded IVF must equal brute force exactly."""
    x = rng.standard_normal((1500, 16)).astype(np.float32)
    q = rng.standard_normal((6, 16)).astype(np.float32)
    idx = ShardedIVFFlatIndex(16, 8, metric)
    idx.train(x[:800])
    idx.add(x[:700])
    idx.add(x[700:])
    idx.set_nprobe(8)
    D, I = idx.search(q, 10)
    wi = brute_ids(q, x, 10, metric)
    np.testing.assert_array_equal(I, wi)


def test_sharded_ivf_matches_single_device(rng):
    """Same data, same centroids count: sharded and single-device IVF agree
    at full probe."""
    x = rng.standard_normal((2000, 16)).astype(np.float32)
    q = rng.standard_normal((8, 16)).astype(np.float32)
    sharded = ShardedIVFFlatIndex(16, 8, "l2")
    sharded.train(x)
    sharded.add(x)
    sharded.set_nprobe(8)
    single = IVFFlatIndex(16, 8, "l2")
    single.train(x)
    single.add(x)
    single.set_nprobe(8)
    Ds, Is = sharded.search(q, 10)
    Du, Iu = single.search(q, 10)
    np.testing.assert_array_equal(Is, Iu)
    np.testing.assert_allclose(Ds, Du, rtol=1e-3, atol=1e-3)


def test_sharded_ivf_partial_probe_recall(rng):
    x = rng.standard_normal((3000, 16)).astype(np.float32)
    q = rng.standard_normal((10, 16)).astype(np.float32)
    idx = ShardedIVFFlatIndex(16, 16, "l2")
    idx.train(x)
    idx.add(x)
    idx.set_nprobe(8)
    D, I = idx.search(q, 10)
    wi = brute_ids(q, x, 10, "l2")
    recall = np.mean([len(set(I[i]) & set(wi[i])) / 10 for i in range(10)])
    assert recall > 0.6


def test_sharded_ivf_state_round_trip(rng, tmp_path):
    from distributed_faiss_tpu.models.factory import index_from_state_dict
    from distributed_faiss_tpu.utils.serialization import load_state, save_state

    x = rng.standard_normal((900, 8)).astype(np.float32)
    q = rng.standard_normal((4, 8)).astype(np.float32)
    idx = ShardedIVFFlatIndex(8, 4, "l2")
    idx.train(x)
    idx.add(x)
    idx.set_nprobe(4)
    D0, I0 = idx.search(q, 6)
    p = str(tmp_path / "sivf.npz")
    save_state(p, idx.state_dict())
    # through the registry — the engine/server restore path
    idx2 = index_from_state_dict(load_state(p))
    assert isinstance(idx2, ShardedIVFFlatIndex)
    D1, I1 = idx2.search(q, 6)
    np.testing.assert_array_equal(I0, I1)
    # reconstruct path inherited from IVFFlat host mirrors
    rec = idx2.reconstruct_batch(I1[0][:3])
    np.testing.assert_allclose(rec, x[I1[0][:3]], rtol=1e-5)


@pytest.mark.parametrize("metric", ["dot", "l2"])
def test_routed_full_probe_exact(rng, metric):
    """Probe routing at nprobe == nlist: exactly brute force (uniform
    ownership — the bucket never drops)."""
    x = rng.standard_normal((1500, 16)).astype(np.float32)
    q = rng.standard_normal((6, 16)).astype(np.float32)
    idx = ShardedIVFFlatIndex(16, 8, metric, probe_routing=True)
    idx.train(x[:800])
    idx.add(x)
    idx.set_nprobe(8)
    D, I = idx.search(q, 10)
    wi = brute_ids(q, x, 10, metric)
    np.testing.assert_array_equal(I, wi)


def test_routed_matches_masked(rng):
    """Routed and masked sharded search agree given identical trained state
    (same probes -> same candidate set)."""
    x = rng.standard_normal((3000, 16)).astype(np.float32)
    q = rng.standard_normal((12, 16)).astype(np.float32)
    masked = ShardedIVFFlatIndex(16, 16, "l2")
    masked.train(x)
    masked.add(x)
    masked.set_nprobe(6)
    routed = ShardedIVFFlatIndex(16, 16, "l2", probe_routing=True)
    routed.centroids = masked.centroids
    routed.lists = masked.lists
    routed._host_pos, routed._host_assign = masked._host_pos, masked._host_assign
    routed._n = masked._n
    routed.set_nprobe(6)
    Dm, Im = masked.search(q, 10)
    Dr, Ir = routed.search(q, 10)
    np.testing.assert_array_equal(Im, Ir)
    np.testing.assert_allclose(Dm, Dr, rtol=1e-3, atol=1e-3)


def test_routed_builder(rng):
    from distributed_faiss_tpu.models.factory import build_index
    from distributed_faiss_tpu.utils.config import IndexCfg

    cfg = IndexCfg(index_builder_type="ivf_tpu", dim=8, metric="l2",
                   centroids=8, nprobe=4, shard_lists=True, probe_routing=True)
    idx = build_index(cfg)
    assert idx.probe_routing
    x = rng.standard_normal((900, 8)).astype(np.float32)
    idx.train(x)
    idx.add(x)
    D, I = idx.search(x[:4], 5)
    assert (I[:, 0] == np.arange(4)).all()


@pytest.mark.parametrize("metric", ["dot", "l2"])
def test_sharded_ivf_pq_matches_single_device(rng, metric):
    """Sharded IVF-PQ == single-device IVF-PQ when sharing trained state."""
    from distributed_faiss_tpu.models.ivf import IVFPQIndex
    from distributed_faiss_tpu.parallel.mesh import ShardedIVFPQIndex

    d, m = 32, 8
    x = rng.standard_normal((2000, d)).astype(np.float32)
    q = rng.standard_normal((6, d)).astype(np.float32)
    single = IVFPQIndex(d, 8, m=m, metric=metric)
    single.train(x)
    single.add(x)
    single.set_nprobe(8)
    sharded = ShardedIVFPQIndex(d, 8, m=m, metric=metric)
    # share the trained coarse+codebooks so rankings must be identical
    sharded.centroids, sharded.codebooks = single.centroids, single.codebooks
    from distributed_faiss_tpu.parallel.mesh import ShardedPaddedLists
    sharded.lists = ShardedPaddedLists(8, (m,), np.uint8, sharded.mesh)
    sharded.add(x)
    sharded.set_nprobe(8)
    Du, Iu = single.search(q, 10)
    Ds, Is = sharded.search(q, 10)
    np.testing.assert_array_equal(Is, Iu)
    np.testing.assert_allclose(Ds, Du, rtol=1e-3, atol=1e-3)


@pytest.mark.slow  # 65-80s each on the 1-core box (suite time budget, r4)
@pytest.mark.parametrize("metric", ["dot", "l2"])
def test_routed_pq_matches_masked(rng, metric):
    from distributed_faiss_tpu.parallel.mesh import ShardedIVFPQIndex

    d, m = 32, 8
    x = rng.standard_normal((2000, d)).astype(np.float32)
    q = rng.standard_normal((9, d)).astype(np.float32)
    masked = ShardedIVFPQIndex(d, 8, m=m, metric=metric)
    masked.train(x)
    masked.add(x)
    masked.set_nprobe(5)
    routed = ShardedIVFPQIndex(d, 8, m=m, metric=metric, probe_routing=True)
    routed.centroids, routed.codebooks = masked.centroids, masked.codebooks
    routed.lists = masked.lists
    routed._host_pos, routed._host_assign = masked._host_pos, masked._host_assign
    routed._n = masked._n
    routed.set_nprobe(5)
    Dm, Im = masked.search(q, 10)
    Dr, Ir = routed.search(q, 10)
    np.testing.assert_array_equal(Im, Ir)
    np.testing.assert_allclose(Dm, Dr, rtol=1e-3, atol=1e-3)


def test_sharded_ivf_pq_lifecycle(rng, tmp_path):
    from distributed_faiss_tpu.models.factory import build_index, index_from_state_dict
    from distributed_faiss_tpu.parallel.mesh import ShardedIVFPQIndex
    from distributed_faiss_tpu.utils.config import IndexCfg
    from distributed_faiss_tpu.utils.serialization import load_state, save_state

    cfg = IndexCfg(index_builder_type="knnlm", dim=16, metric="l2",
                   centroids=4, nprobe=4, code_size=4, shard_lists=True)
    idx = build_index(cfg)
    assert isinstance(idx, ShardedIVFPQIndex)
    x = rng.standard_normal((800, 16)).astype(np.float32)
    idx.train(x)
    idx.add(x)
    D0, I0 = idx.search(x[:4], 5)
    assert (I0[:, 0] == np.arange(4)).all()
    p = str(tmp_path / "spq.npz")
    save_state(p, idx.state_dict())
    idx2 = index_from_state_dict(load_state(p))
    D1, I1 = idx2.search(x[:4], 5)
    np.testing.assert_array_equal(I0, I1)


def test_ivf_tpu_shard_lists_builder(rng):
    from distributed_faiss_tpu.models.factory import build_index
    from distributed_faiss_tpu.utils.config import IndexCfg

    cfg = IndexCfg(index_builder_type="ivf_tpu", dim=8, metric="l2",
                   centroids=4, nprobe=4, shard_lists=True)
    idx = build_index(cfg)
    assert isinstance(idx, ShardedIVFFlatIndex)
    x = rng.standard_normal((600, 8)).astype(np.float32)
    idx.train(x)
    idx.add(x)
    D, I = idx.search(x[:3], 4)
    assert (I[:, 0] == np.arange(3)).all()


# ---------------------------------------------- sharded refine + pallas ADC


@pytest.mark.parametrize("routing", [False, True])
def test_sharded_pq_refine_scores_are_exact(rng, routing):
    """refine_k_factor on the sharded path: returned scores must equal the
    exact metric computed against the (fp16-rounded) raw rows of the
    returned ids — pins that the pre-merge rerank really rescores exactly."""
    from distributed_faiss_tpu.parallel.mesh import ShardedIVFPQIndex

    d, m = 32, 8
    x = rng.standard_normal((1500, d)).astype(np.float32)
    q = rng.standard_normal((6, d)).astype(np.float32)
    idx = ShardedIVFPQIndex(d, 8, m=m, metric="l2", probe_routing=routing,
                            refine_k_factor=8)
    idx.train(x)
    idx.add(x)
    idx.set_nprobe(8)
    D, I = idx.search(q, 5)
    assert (I >= 0).all()
    x16 = x.astype(np.float16).astype(np.float32)
    for qi in range(q.shape[0]):
        exact = ((q[qi][None, :] - x16[I[qi]]) ** 2).sum(-1)
        np.testing.assert_allclose(D[qi], exact, rtol=1e-3, atol=1e-2)


@pytest.mark.slow  # ~18s pair; exactness covered by refine_scores_are_exact
@pytest.mark.parametrize("routing", [False, True])
def test_sharded_pq_refine_lifts_recall(rng, routing):
    """Same trained state, same nprobe: the refined sharded search must
    reach at least the recall of the unrefined one, and its top-1 on
    self-queries must be the query row itself (exact rescoring pins it)."""
    from distributed_faiss_tpu.parallel.mesh import ShardedIVFPQIndex, ShardedPaddedLists

    d, m = 32, 4
    x = rng.standard_normal((2000, d)).astype(np.float32)
    q = x[:16] + 1e-5
    base_idx = ShardedIVFPQIndex(d, 16, m=m, metric="l2", probe_routing=routing)
    base_idx.train(x)
    base_idx.add(x)
    base_idx.set_nprobe(8)
    ref = ShardedIVFPQIndex(d, 16, m=m, metric="l2", probe_routing=routing,
                            refine_k_factor=16)
    ref.centroids, ref.codebooks = base_idx.centroids, base_idx.codebooks
    ref.lists = base_idx.lists
    ref.raw_lists = ShardedPaddedLists(16, (d,), np.float16, ref.mesh)
    from distributed_faiss_tpu.models.ivf import clip_f16
    assign = base_idx._host_assign_array()
    ref.raw_lists.append(assign, clip_f16(x), np.arange(x.shape[0], dtype=np.int64))
    ref._host_pos, ref._host_assign = base_idx._host_pos, base_idx._host_assign
    ref._n = base_idx._n
    ref.set_nprobe(8)

    gt = brute_ids(q, x, 10, "l2")
    _, Ib = base_idx.search(q, 10)
    _, Ir = ref.search(q, 10)
    rec_b = np.mean([len(set(Ib[i]) & set(gt[i])) / 10 for i in range(q.shape[0])])
    rec_r = np.mean([len(set(Ir[i]) & set(gt[i])) / 10 for i in range(q.shape[0])])
    assert rec_r >= rec_b - 1e-9, (rec_r, rec_b)
    assert (Ir[:, 0] == np.arange(16)).all()


@pytest.mark.parametrize("routing", [False, True])
@pytest.mark.parametrize("refine", [0, 8])
def test_sharded_pq_pallas_matches_xla(rng, routing, refine):
    """pallas_adc on the sharded path (interpreted off-TPU) must reproduce
    the XLA one-hot path bit-for-bit on ids."""
    from distributed_faiss_tpu.parallel.mesh import ShardedIVFPQIndex

    d, m = 32, 8
    x = rng.standard_normal((1200, d)).astype(np.float32)
    q = rng.standard_normal((5, d)).astype(np.float32)
    a = ShardedIVFPQIndex(d, 8, m=m, metric="l2", probe_routing=routing,
                          refine_k_factor=refine)
    a.train(x)
    a.add(x)
    a.set_nprobe(4)
    b = ShardedIVFPQIndex(d, 8, m=m, metric="l2", probe_routing=routing,
                          refine_k_factor=refine, use_pallas=True)
    b.centroids, b.codebooks = a.centroids, a.codebooks
    b.lists, b.raw_lists = a.lists, a.raw_lists
    b._host_pos, b._host_assign, b._n = a._host_pos, a._host_assign, a._n
    b.set_nprobe(4)
    Da, Ia = a.search(q, 8)
    Db, Ib = b.search(q, 8)
    assert b._pallas_runtime_ok, "pallas path silently fell back"
    np.testing.assert_array_equal(Ia, Ib)
    np.testing.assert_allclose(Da, Db, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("routing", [False, True], ids=["masked", "routed"])
def test_sharded_pq_kernel_answers_do_not_move_with_the_skip(rng, monkeypatch, routing):
    """The kernel is handed each pair's list size, 0 for a pair the chip
    does not own (masked) or a slot of the bucket's padding (routed), and
    computes no further (PR 35): the answers are those of the kernel that
    scans every capacity, bit for bit."""
    import jax.numpy as jnp

    from distributed_faiss_tpu.ops import adc_pallas
    from distributed_faiss_tpu.parallel import mesh as meshmod

    d, m = 32, 8
    x = rng.standard_normal((1200, d)).astype(np.float32)
    q = rng.standard_normal((5, d)).astype(np.float32)
    idx = meshmod.ShardedIVFPQIndex(d, 8, m=m, metric="l2", probe_routing=routing,
                                    refine_k_factor=8, use_pallas=True)
    idx.train(x)
    idx.add(x)
    idx.set_nprobe(4)
    idx.remove_rows(np.arange(0, 1200, 7))
    assert idx._kernel_applies()
    got_d, got_i = idx.search(q, 8)
    assert idx._pallas_runtime_ok, "pallas path silently fell back"

    program = (meshmod._sharded_ivf_pq_search_routed if routing
               else meshmod._sharded_ivf_pq_search)
    orig, seen = adc_pallas.adc_scan_pallas_planes, []

    def whole(lut, codes, sizes, **kw):
        seen.append(1)
        return orig(lut, codes, jnp.full_like(sizes, codes.shape[1]), **kw)

    program.clear_cache()
    monkeypatch.setattr(adc_pallas, "adc_scan_pallas_planes", whole)
    try:
        want_d, want_i = idx.search(q, 8)
    finally:
        program.clear_cache()  # the stand-in is baked into the traces
    assert seen and idx._pallas_runtime_ok
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_array_equal(got_d, want_d)


def test_sharded_pq_refine_state_round_trip(rng, tmp_path):
    from distributed_faiss_tpu.models.factory import build_index, index_from_state_dict
    from distributed_faiss_tpu.parallel.mesh import ShardedIVFPQIndex
    from distributed_faiss_tpu.utils.config import IndexCfg
    from distributed_faiss_tpu.utils.serialization import load_state, save_state

    cfg = IndexCfg(index_builder_type="knnlm", dim=16, metric="l2",
                   centroids=4, nprobe=4, code_size=4, shard_lists=True,
                   refine_k_factor=4, pallas_adc=True)
    idx = build_index(cfg)
    assert isinstance(idx, ShardedIVFPQIndex)
    assert idx.refine_k_factor == 4 and idx.use_pallas
    x = rng.standard_normal((900, 16)).astype(np.float32)
    idx.train(x)
    idx.add(x)
    D0, I0 = idx.search(x[:4], 5)
    assert (I0[:, 0] == np.arange(4)).all()
    p = str(tmp_path / "spq_refine.npz")
    save_state(p, idx.state_dict())
    idx2 = index_from_state_dict(load_state(p))
    assert idx2.refine_k_factor == 4 and idx2.raw_lists is not None
    D1, I1 = idx2.search(x[:4], 5)
    np.testing.assert_array_equal(I0, I1)
    np.testing.assert_allclose(D0, D1, rtol=1e-4, atol=1e-4)


@pytest.mark.slow  # ~10s; the routed-path equality above covers correctness
def test_routed_bucket_auto_resize_under_skew(rng, caplog):
    """Adversarial skew: every added row lands in ONE list, so one chip owns
    all (query, probe) pairs and the default 2x-slack bucket must drop.
    The driver has to resize and re-run until zero pairs are dropped —
    results must equal brute force over the hot cluster, with no recall-loss
    warning left standing."""
    import logging

    from distributed_faiss_tpu.parallel.mesh import ShardedIVFFlatIndex

    # sized so the skew actually exceeds the default bucket: cap 4096 at
    # d=64 gives pair group 64; 256 real queries x nprobe=1 all owned by one
    # chip = 256 owned pairs vs a 2x-slack bucket of 64
    d = 64
    centers = rng.standard_normal((8, d)).astype(np.float32) * 20.0
    train = np.concatenate(
        [centers[i] + 0.01 * rng.standard_normal((40, d)).astype(np.float32)
         for i in range(8)]
    )
    idx = ShardedIVFFlatIndex(d, 8, "l2", probe_routing=True)
    idx.train(train)
    # all corpus rows in the single cluster 0 -> one list owns everything
    # (unit spread keeps distances well-separated so the brute-force golden
    # comparison has no fp32 near-ties, while 20-sigma center spacing keeps
    # every row assigned to list 0)
    x = centers[0] + rng.standard_normal((4096, d)).astype(np.float32)
    idx.add(x)
    idx.set_nprobe(1)
    q = centers[0] + rng.standard_normal((256, d)).astype(np.float32)
    with caplog.at_level(logging.INFO, logger="distributed_faiss_tpu.parallel.mesh"):
        D, I = idx.search(q, 10)
    assert any("retrying block" in r.getMessage() for r in caplog.records), (
        "skew did not trigger a resize — test premise broken"
    )
    assert not any("still dropped" in r.getMessage() for r in caplog.records)
    # fp32 near-ties can swap adjacent ranks; assert via distances + recall
    gt = brute_ids(q, x, 10, "l2")
    gt_d = np.sort(((q[:, None, :] - x[gt]) ** 2).sum(-1), axis=1)
    # the kernel's qn - 2ip + bn formulation differs from the direct
    # difference-of-squares by ~1e-4 relative on these magnitudes
    np.testing.assert_allclose(np.sort(D, axis=1), gt_d, rtol=1e-3, atol=1e-2)
    recall = np.mean([len(set(I[i]) & set(gt[i])) / 10 for i in range(len(q))])
    assert recall > 0.995, recall
    assert idx._routed_slack > 2.0


def test_large_query_batch_sharded_modes(rng):
    """A few-hundred-query batch (the launch-bound serving regime the
    block sizing targets) through both sharded modes: full probe ==
    brute force, and routed == masked at partial probe."""
    x = rng.standard_normal((1200, 8)).astype(np.float32)
    q = rng.standard_normal((300, 8)).astype(np.float32)
    masked = ShardedIVFFlatIndex(8, 8, "l2")
    masked.train(x[:600])
    masked.add(x)
    masked.set_nprobe(8)
    D, I = masked.search(q, 5)
    np.testing.assert_array_equal(I, brute_ids(q, x, 5, "l2"))

    routed = ShardedIVFFlatIndex(8, 8, "l2", probe_routing=True)
    routed.centroids = masked.centroids
    routed.lists = masked.lists
    routed._host_pos, routed._host_assign = masked._host_pos, masked._host_assign
    routed._n = masked._n
    routed.set_nprobe(3)
    masked.set_nprobe(3)
    Dm, Im = masked.search(q, 5)
    Dr, Ir = routed.search(q, 5)
    np.testing.assert_array_equal(Im, Ir)
    np.testing.assert_allclose(Dm, Dr, rtol=1e-3, atol=1e-3)
