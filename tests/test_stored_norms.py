"""Stored-row-norms parity: the add-time ``||x||^2`` sidecar must be
bit-identical to the in-scan recompute (same decode + same minor-axis fp32
reduction — base.row_norms_f32), across codecs, metrics, capacity growth,
save/load, pre-norms-snapshot backfill, and the sharded masked path."""

import numpy as np
import pytest

from distributed_faiss_tpu.models.ivf import IVFFlatIndex


def build(rng, codec, metric, d=24, n=3000, nlist=16, chunks=3, **kw):
    x = rng.standard_normal((n, d)).astype(np.float32) * 2.0
    idx = IVFFlatIndex(d, nlist, metric, codec=codec, kmeans_iters=3, **kw)
    idx.train(x[: n // 2])
    # multi-batch adds so the norm sidecar rides capacity growth in
    # lockstep with the payload lists
    for c in np.array_split(x, chunks):
        idx.add(c)
    idx.set_nprobe(max(2, nlist // 4))
    return idx, x


@pytest.mark.parametrize("codec,metric", [
    ("f16", "l2"), ("sq8", "l2"), ("f16", "dot"), ("sq8", "dot"),
])
def test_stored_norm_scan_golden_equality(rng, codec, metric):
    """Acceptance: stored-norm scan == recompute scan, bit-exact (fp16 and
    sq8, l2 and dot — dot never touches norms, included as the no-op
    control)."""
    idx, x = build(rng, codec, metric)
    if metric == "l2":
        assert idx.norm_lists.cap == idx.lists.cap
    else:
        # dot never reads norms: no sidecar is built, stored/recompute is a
        # trivially identical no-op pair (kept as the control arm)
        assert idx.norm_lists is None
    q = rng.standard_normal((25, x.shape[1])).astype(np.float32)
    D_stored, I_stored = idx.search(q, 10)
    idx.use_stored_norms = False
    D_rec, I_rec = idx.search(q, 10)
    np.testing.assert_array_equal(I_stored, I_rec)
    np.testing.assert_array_equal(D_stored, D_rec)  # bit-exact, not allclose


def test_stored_norms_match_decoded_rows(rng):
    """The sidecar values themselves equal a direct norm of the decoded
    stored rows (sq8: dequantized codes, not the fp32 input)."""
    from distributed_faiss_tpu.ops import sq

    idx, x = build(rng, "sq8", "l2")
    rows = idx._rows_in_insertion_order()
    deq = np.asarray(sq.sq8_decode(
        np.asarray(rows), idx.sq_params["vmin"], idx.sq_params["span"]))
    want = np.sum(deq.astype(np.float32) ** 2, axis=1)
    got = idx._rows_in_insertion_order(lists=idx.norm_lists)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("codec", ["f16", "sq8"])
def test_save_load_roundtrip_and_prenorm_backfill(rng, codec, tmp_path):
    """Acceptance: a snapshot round-trips bit-exactly, and a PRE-NORMS
    snapshot (no 'list_norms' key — what every pre-this-PR save file looks
    like) backfills norms on load with identical search results."""
    from distributed_faiss_tpu.utils.serialization import load_state, save_state

    idx, x = build(rng, codec, "l2")
    q = rng.standard_normal((12, x.shape[1])).astype(np.float32)
    D, I = idx.search(q, 8)

    path = str(tmp_path / "snap.npz")
    save_state(path, idx.state_dict())
    state = load_state(path)
    assert "list_norms" in state
    re1 = IVFFlatIndex.from_state_dict(state)
    D1, I1 = re1.search(q, 8)
    np.testing.assert_array_equal(I, I1)
    np.testing.assert_array_equal(D, D1)

    # simulate the old on-disk format: drop the norms payload entirely
    state = {k: v for k, v in load_state(path).items() if k != "list_norms"}
    re2 = IVFFlatIndex.from_state_dict(state)
    assert re2.norm_lists is not None and re2.norm_lists.ntotal == idx.ntotal
    D2, I2 = re2.search(q, 8)
    np.testing.assert_array_equal(I, I2)
    np.testing.assert_array_equal(D, D2)


def test_sharded_masked_stored_norms_golden(rng):
    """The sharded masked scan (parallel/mesh.py) uses the same stored-norm
    gather — stored vs recompute must be bit-exact there too, so the two
    scan implementations can't drift."""
    from distributed_faiss_tpu.parallel.mesh import ShardedIVFFlatIndex

    n, d = 2500, 16
    x = rng.standard_normal((n, d)).astype(np.float32)
    idx = ShardedIVFFlatIndex(d, 8, "l2")
    idx.train(x[:1000])
    for c in np.array_split(x, 2):
        idx.add(c)
    idx.set_nprobe(4)
    q = rng.standard_normal((10, d)).astype(np.float32)
    D1, I1 = idx.search(q, 6)
    idx.use_stored_norms = False
    D0, I0 = idx.search(q, 6)
    np.testing.assert_array_equal(I1, I0)
    np.testing.assert_array_equal(D1, D0)


def test_scan_bf16_requires_refine():
    with pytest.raises(ValueError, match="refine_k_factor"):
        IVFFlatIndex(16, 4, "l2", codec="f16", scan_bf16=True)


def test_scan_bf16_with_refine_recall(rng):
    """bf16 scan + exact refine: final results
    match the fp32 pipeline on virtually every query — the shortlist is
    rescored exactly, so only genuine shortlist churn can differ."""
    idx, x = build(rng, "f16", "l2", refine_k_factor=4, scan_bf16=True)
    assert idx.refine_k_factor == 4 and idx.scan_bf16
    ref, _ = build(rng, "f16", "l2")
    ref.centroids = idx.centroids  # same coarse space for comparability
    q = rng.standard_normal((32, x.shape[1])).astype(np.float32)
    _, I_bf = idx.search(q, 10)
    ref.lists, ref.norm_lists = idx.lists, idx.norm_lists
    ref._host_assign, ref._host_pos, ref._n = idx._host_assign, idx._host_pos, idx._n
    ref.set_nprobe(idx.nprobe)
    _, I_f32 = ref.search(q, 10)
    overlap = np.mean([len(set(I_bf[i]) & set(I_f32[i])) / 10
                       for i in range(len(q))])
    assert overlap >= 0.9, overlap


def test_scan_bf16_state_roundtrip(rng):
    idx, x = build(rng, "sq8", "l2", refine_k_factor=4, scan_bf16=True)
    q = rng.standard_normal((8, x.shape[1])).astype(np.float32)
    D, I = idx.search(q, 5)
    re1 = IVFFlatIndex.from_state_dict(idx.state_dict())
    assert re1.scan_bf16 and re1.refine_k_factor == 4
    D1, I1 = re1.search(q, 5)
    np.testing.assert_array_equal(I, I1)
    np.testing.assert_allclose(D, D1, rtol=1e-5, atol=1e-5)


def test_factory_and_engine_knob_plumbing(rng):
    """cfg.extra -> builder -> index attribute plumbing for the new knobs,
    and the engine's runtime stored_norms A/B toggle."""
    from distributed_faiss_tpu.engine import Index
    from distributed_faiss_tpu.models.factory import build_index
    from distributed_faiss_tpu.utils.config import IndexCfg

    cfg = IndexCfg(index_builder_type="ivfsq", dim=16, metric="l2",
                   centroids=4, pallas_flat=True, scan_bf16=True,
                   refine_k_factor=4)
    idx = build_index(cfg)
    assert idx.use_pallas and idx.scan_bf16 and idx.refine_k_factor == 4

    # factory grammar channel
    cfg2 = IndexCfg(faiss_factory="IVF4,SQfp16,RFlat", dim=16, metric="l2",
                    centroids=4, scan_bf16=True)
    idx2 = build_index(cfg2)
    assert idx2.scan_bf16 and idx2.refine_k_factor == 8  # RFlat default

    # engine runtime toggle: applied at train time and on upd_cfg
    eng = Index(IndexCfg(index_builder_type="ivfsq", dim=16, metric="l2",
                         train_num=64, buffer_bsz=64, centroids=4,
                         stored_norms=False))
    eng.add_batch(rng.standard_normal((80, 16)).astype(np.float32), None,
                  train_async_if_triggered=False)
    assert eng.tpu_index.use_stored_norms is False
    cfg3 = eng.cfg
    cfg3.extra = dict(cfg3.extra, stored_norms=True)
    eng.upd_cfg(cfg3)
    assert eng.tpu_index.use_stored_norms is True
