"""Pallas flat-scan kernel golden tests (interpreter mode on CPU — same
kernel code path the TPU runs compiled) plus the guard/demotion ladder the
IVF-Flat models wrap it in."""

import numpy as np
import pytest

from distributed_faiss_tpu.ops import flat_pallas


@pytest.fixture
def problem(rng):
    nq, d, nlist, cap, g = 5, 24, 12, 128, 3
    q = rng.standard_normal((nq, d)).astype(np.float32)
    data = rng.standard_normal((nlist, cap, d)).astype(np.float16)
    ids = rng.integers(-1, 60, (nlist, cap)).astype(np.int32)
    sizes = rng.integers(0, cap + 1, (nlist,)).astype(np.int32)
    li = rng.integers(0, nlist, (nq, g)).astype(np.int32)
    return q, data, ids, sizes, li


def np_reference(q, data, ids, sizes, li, metric, norms=None):
    block = data[li].astype(np.float32)  # (nq, g, cap, d)
    ip = np.einsum("qd,qgcd->qgc", q, block)
    if metric == "dot":
        s = ip
    else:
        qn = np.sum(q * q, axis=1)[:, None, None]
        bn = norms[li] if norms is not None else np.sum(block * block, axis=3)
        s = -(qn - 2.0 * ip + bn)
    cap = data.shape[1]
    valid = (np.arange(cap)[None, None, :] < sizes[li][:, :, None]) & (ids[li] >= 0)
    return np.where(valid, s, -np.inf)


def run_kernel(q, data, ids, sizes, li, metric, norms=None, codec="f16",
               vmin=None, span=None, scan_bf16=False, tile=64):
    import jax.numpy as jnp

    return np.asarray(flat_pallas.flat_list_scan_pallas(
        jnp.asarray(q), jnp.asarray(data), jnp.asarray(ids),
        jnp.asarray(li), jnp.asarray(sizes[li]),
        None if norms is None else jnp.asarray(norms),
        None if vmin is None else jnp.asarray(vmin),
        None if span is None else jnp.asarray(span),
        metric=metric, codec=codec, scan_bf16=scan_bf16, tile=tile,
        interpret=True))


@pytest.mark.parametrize("metric", ["l2", "dot"])
def test_kernel_golden_recompute(problem, metric):
    q, data, ids, sizes, li = problem
    got = run_kernel(q, data, ids, sizes, li, metric)
    want = np_reference(q, data, ids, sizes, li, metric)
    assert np.array_equal(np.isinf(got), np.isinf(want))
    f = np.isfinite(want)
    np.testing.assert_allclose(got[f], want[f], rtol=1e-4, atol=1e-4)


def test_kernel_golden_stored_norms(problem):
    q, data, ids, sizes, li = problem
    norms = np.sum(data.astype(np.float32) ** 2, axis=2)
    got = run_kernel(q, data, ids, sizes, li, "l2", norms=norms)
    want = np_reference(q, data, ids, sizes, li, "l2", norms=norms)
    f = np.isfinite(want)
    assert np.array_equal(np.isinf(got), np.isinf(want))
    np.testing.assert_allclose(got[f], want[f], rtol=1e-4, atol=1e-4)


def test_kernel_sq8_dequant(rng):
    nq, d, nlist, cap, g = 3, 16, 8, 64, 2
    q = rng.standard_normal((nq, d)).astype(np.float32)
    codes = rng.integers(0, 256, (nlist, cap, d)).astype(np.uint8)
    vmin = rng.standard_normal(d).astype(np.float32)
    span = np.abs(rng.standard_normal(d)).astype(np.float32) + 0.5
    ids = rng.integers(0, 60, (nlist, cap)).astype(np.int32)
    sizes = np.full(nlist, cap, np.int32)
    li = rng.integers(0, nlist, (nq, g)).astype(np.int32)
    deq = vmin + codes.astype(np.float32) * (span / 255.0)
    got = run_kernel(q, codes, ids, sizes, li, "l2", codec="sq8",
                     vmin=vmin, span=span)
    want = np_reference(q, deq, ids, sizes, li, "l2")
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_kernel_cap_not_tile_multiple_small_cap(rng):
    """cap smaller than the default tile: the tile clamps to cap."""
    nq, d, nlist, cap = 2, 8, 4, 16
    q = rng.standard_normal((nq, d)).astype(np.float32)
    data = rng.standard_normal((nlist, cap, d)).astype(np.float32)
    ids = rng.integers(0, 9, (nlist, cap)).astype(np.int32)
    sizes = np.full(nlist, cap, np.int32)
    li = rng.integers(0, nlist, (nq, 1)).astype(np.int32)
    got = run_kernel(q, data, ids, sizes, li, "dot", codec="f32", tile=1024)
    want = np_reference(q, data, ids, sizes, li, "dot")
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_kernel_bf16_close(problem):
    """bf16 scan (the refine-gated fast mode): error bounded by bf16
    rounding of the operands, inf mask identical."""
    q, data, ids, sizes, li = problem
    norms = np.sum(data.astype(np.float32) ** 2, axis=2)
    got = run_kernel(q, data, ids, sizes, li, "l2", norms=norms, scan_bf16=True)
    want = np_reference(q, data, ids, sizes, li, "l2", norms=norms)
    assert np.array_equal(np.isinf(got), np.isinf(want))
    f = np.isfinite(want)
    np.testing.assert_allclose(got[f], want[f], rtol=5e-2, atol=5e-1)


def test_index_pallas_matches_xla(rng):
    """End-to-end IVFFlatIndex: pallas scan returns the XLA path's results
    (the first-use oracle check runs and passes)."""
    from distributed_faiss_tpu.models.ivf import IVFFlatIndex

    n, d = 2000, 24
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((15, d)).astype(np.float32)
    ref = IVFFlatIndex(d, 8, "l2", codec="f16", kmeans_iters=3)
    ref.train(x[:800]); ref.add(x); ref.set_nprobe(4)
    Dx, Ix = ref.search(q, 7)

    idx = IVFFlatIndex(d, 8, "l2", codec="f16", kmeans_iters=3, use_pallas=True)
    idx.centroids = ref.centroids
    idx.lists = idx._make_lists()
    assign = idx._assign_host(x)
    rows = idx._encode(x, assign)
    gids = np.arange(n, dtype=np.int64)
    pos = idx.lists.append(assign, rows, gids)
    idx._append_extra(x, assign, gids, rows)
    idx._host_assign = [assign.astype(np.int32)]
    idx._host_pos = [pos]
    idx._n = n
    idx.set_nprobe(4)
    Dp, Ip = idx.search(q, 7)
    assert idx._pallas_flat_validated and idx._pallas_runtime_ok
    np.testing.assert_array_equal(Ip, Ix)
    np.testing.assert_allclose(Dp, Dx, rtol=1e-4, atol=1e-4)


def test_flat_kernel_failure_demotes_to_xla(rng, monkeypatch):
    """An injected flat-kernel fault after validation falls back to the XLA
    path via pallas_guarded and serves the request from the oracle result."""
    from distributed_faiss_tpu.models import ivf as ivfmod
    from distributed_faiss_tpu.models.ivf import IVFFlatIndex

    n, d = 1200, 16
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((6, d)).astype(np.float32)
    idx = IVFFlatIndex(d, 8, "l2", codec="f16", kmeans_iters=3, use_pallas=True)
    idx.train(x[:600]); idx.add(x); idx.set_nprobe(4)
    want_d, want_i = idx.search(q, 5)  # validates + serves via pallas
    assert idx._pallas_runtime_ok

    def boom(*a, **k):
        raise RuntimeError("flat kernel abort (injected)")

    ivfmod._ivf_flat_search.clear_cache()
    monkeypatch.setattr(flat_pallas, "flat_list_scan_auto", boom)
    got_d, got_i = idx.search(q, 5)
    assert idx._pallas_runtime_ok is False, "flat kernel fault not demoted"
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_allclose(got_d, want_d, rtol=1e-4, atol=1e-4)


def test_first_use_oracle_mismatch_demotes(rng, monkeypatch):
    """A kernel that runs but returns wrong numbers is caught by the
    first-use oracle check — never served to a caller."""
    from distributed_faiss_tpu.models import ivf as ivfmod
    from distributed_faiss_tpu.models.ivf import IVFFlatIndex

    n, d = 1200, 16
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((6, d)).astype(np.float32)
    idx = IVFFlatIndex(d, 8, "l2", codec="f16", kmeans_iters=3, use_pallas=True)
    idx.train(x[:600]); idx.add(x); idx.set_nprobe(4)
    ref = IVFFlatIndex(d, 8, "l2", codec="f16", kmeans_iters=3)
    ref.centroids, ref.lists, ref.norm_lists = idx.centroids, idx.lists, idx.norm_lists
    ref._host_assign, ref._host_pos, ref._n = idx._host_assign, idx._host_pos, idx._n
    ref.set_nprobe(4)
    want_d, want_i = ref.search(q, 5)

    orig = flat_pallas.flat_list_scan_auto

    def skewed(*a, **k):
        return orig(*a, **k) + 1.0  # uniformly wrong scores

    ivfmod._ivf_flat_search.clear_cache()
    monkeypatch.setattr(flat_pallas, "flat_list_scan_auto", skewed)
    got_d, got_i = idx.search(q, 5)
    assert idx._pallas_flat_validated
    assert idx._pallas_runtime_ok is False, "wrong-numbers kernel survived validation"
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_allclose(got_d, want_d, rtol=1e-4, atol=1e-4)
