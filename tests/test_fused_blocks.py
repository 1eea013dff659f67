"""Single-launch multi-block search: fused lax.map path vs per-block loop.

The fused path only engages when a batch spans multiple query blocks
(block = base.pick_query_block(...)); these tests shrink MAX_QUERY_BLOCK so
small corpora exercise it, then pin bit-parity against the per-block loop
and against brute force.
"""

import numpy as np
import pytest

from distributed_faiss_tpu.models import base
from distributed_faiss_tpu.models.flat import FlatIndex  # noqa: F401  (import check)
from distributed_faiss_tpu.models.ivf import IVFFlatIndex, IVFPQIndex


@pytest.fixture
def small_blocks(monkeypatch):
    # pick_query_block reads the module global at call time; 8-query blocks
    # force multi-block execution at test sizes (minimum floor is bypassed
    # because block starts at MAX_QUERY_BLOCK)
    monkeypatch.setattr(base, "MAX_QUERY_BLOCK", 8)


def brute(q, x, k, metric):
    if metric == "dot":
        s = q @ x.T
    else:
        s = -((q[:, None, :] - x[None, :, :]) ** 2).sum(-1)
    return np.argsort(-s, axis=1)[:, :k]


@pytest.mark.parametrize("metric,codec", [("dot", "f32"), ("l2", "f32"), ("l2", "sq8")])
def test_fused_flat_index_exact(rng, metric, codec, small_blocks):
    d, n, nq, k = 16, 300, 27, 5
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = x[:nq] + rng.standard_normal((nq, d)).astype(np.float32) * 1e-3
    idx = FlatIndex(d, metric=metric, codec=codec)
    idx.train(x)
    idx.add(x)
    D, I = idx.search(q, k)
    if codec == "f32":
        np.testing.assert_array_equal(I, brute(q, x, k, metric))
    else:
        # sq8 + l2 near-duplicate queries: the self row must still win
        np.testing.assert_array_equal(I[:, 0], np.arange(nq))


@pytest.mark.parametrize("metric", ["dot", "l2"])
def test_fused_flat_full_probe_exact(rng, metric, small_blocks):
    d, n, nq, k = 16, 400, 37, 5  # 37 queries -> 5 blocks of 8
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((nq, d)).astype(np.float32)
    idx = IVFFlatIndex(d, 8, metric=metric)
    idx.train(x)
    idx.add(x)
    idx.set_nprobe(8)  # full probe -> exact
    D, I = idx.search(q, k)
    np.testing.assert_array_equal(I, brute(q, x, k, metric))


def test_fused_matches_per_block_loop(rng, small_blocks):
    d, n, nq, k = 16, 500, 29, 4
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((nq, d)).astype(np.float32)
    idx = IVFFlatIndex(d, 16, metric="l2", codec="sq8", refine_k_factor=4)
    idx.train(x)
    idx.add(x)
    idx.set_nprobe(4)

    D_fused, I_fused = idx.search(q, k)

    # same search with the fused path disabled: route through the loop
    nprobe = 4
    import distributed_faiss_tpu.models.ivf as ivfmod
    nb = base.pick_query_block(idx.lists.cap * d * 4)
    g = ivfmod.probe_group_size(nprobe, nb * idx.lists.cap * d * 4)
    scan_k = k * idx.refine_k_factor

    def run(b):
        vals, ids, _ = ivfmod._ivf_flat_search(
            idx.centroids, idx.lists.data, idx.lists.ids, idx.lists.sizes,
            b, scan_k, nprobe, g, "l2", "sq8",
            vmin=idx.sq_params["vmin"], span=idx.sq_params["span"],
            list_norms=idx._scan_norms(),
        )
        return ivfmod._rerank_exact(idx.refine_store.data, b, ids, k, "l2")

    D_loop, I_loop = idx._search_blocks(q, k, run, block=nb, fused_fn=None)
    np.testing.assert_array_equal(I_fused, I_loop)
    np.testing.assert_allclose(D_fused, D_loop, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_fused_pq_refine_recall(rng, small_blocks, use_pallas):
    d, m, n, nq, k = 32, 8, 1500, 41, 10
    # low-intrinsic-dim mixture (isotropic corpora sink PQ recall — see
    # knnlm corpus-model note in benchmarks/)
    centers = rng.standard_normal((12, d)).astype(np.float32) * 3
    x = (centers[rng.integers(0, 12, n)]
         + rng.standard_normal((n, d)).astype(np.float32) * 0.3)
    q = (centers[rng.integers(0, 12, nq)]
         + rng.standard_normal((nq, d)).astype(np.float32) * 0.3)
    idx = IVFPQIndex(d, 16, m=m, metric="l2", refine_k_factor=8,
                     use_pallas=use_pallas)
    idx.train(x)
    idx.add(x)
    idx.set_nprobe(16)  # full probe isolates PQ+refine quality
    D, I = idx.search(q, k)
    assert idx._pallas_runtime_ok
    truth = brute(q, x, k, "l2")
    recall = np.mean([len(set(I[i]) & set(truth[i])) / k for i in range(nq)])
    assert recall >= 0.9, recall
    assert I.shape == (nq, k) and (I >= 0).all()


@pytest.mark.parametrize("use_pallas", [False, True])
def test_fused_sharded_masked_paths(rng, small_blocks, use_pallas):
    """shard_map under lax.map: the masked sharded flat and PQ+refine paths
    must survive the single-launch fusion on the 8-device mesh."""
    from distributed_faiss_tpu.parallel.mesh import (
        ShardedIVFFlatIndex, ShardedIVFPQIndex)

    d, n, nq, k = 32, 800, 21, 5
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = x[:nq] + rng.standard_normal((nq, d)).astype(np.float32) * 0.01
    flat = ShardedIVFFlatIndex(d, 8, "l2")
    flat.train(x)
    flat.add(x)
    flat.set_nprobe(8)  # full probe -> exact
    D, I = flat.search(q, k)
    np.testing.assert_array_equal(I[:, 0], np.arange(nq))

    pq_idx = ShardedIVFPQIndex(d, 8, m=4, metric="l2", refine_k_factor=8,
                               use_pallas=use_pallas)
    pq_idx.train(x)
    pq_idx.add(x)
    pq_idx.set_nprobe(8)
    Dp, Ip = pq_idx.search(q, k)
    assert pq_idx._pallas_runtime_ok
    assert (Ip[:, 0] == np.arange(nq)).mean() >= 0.9


def test_fused_engages_only_past_one_block(rng, small_blocks):
    """nq <= block must stay on the single-launch-per-block path (no padded
    map overhead for the common serving case)."""
    d = 8
    x = rng.standard_normal((200, d)).astype(np.float32)
    idx = IVFFlatIndex(d, 4, metric="l2")
    idx.train(x)
    idx.add(x)
    idx.set_nprobe(4)
    called = {"fused": 0}

    orig = idx._launch_blocks  # the driver's launch half: search collects it

    def spy(q, k, fn, block=256, fused_fn=None, **kw):
        if fused_fn is not None and np.asarray(q).shape[0] > block:
            called["fused"] += 1
        return orig(q, k, fn, block=block, fused_fn=fused_fn, **kw)

    idx._launch_blocks = spy
    idx.search(rng.standard_normal((8, d)).astype(np.float32), 3)
    assert called["fused"] == 0
    idx.search(rng.standard_normal((9, d)).astype(np.float32), 3)
    assert called["fused"] == 1
