"""Golden tests: every kernel against numpy brute force (SURVEY §7 step 1)."""

import numpy as np
import pytest

from distributed_faiss_tpu.ops import distance, kmeans, pq, sq


def np_scores(q, x, metric):
    if metric == "dot":
        return q @ x.T
    d = ((q[:, None, :] - x[None, :, :]) ** 2).sum(-1)
    return -d


@pytest.mark.parametrize("metric", ["dot", "l2"])
def test_pairwise_scores_golden(rng, metric):
    q = rng.standard_normal((7, 32)).astype(np.float32)
    x = rng.standard_normal((50, 32)).astype(np.float32)
    got = np.asarray(distance.pairwise_scores(q, x, metric))
    want = np_scores(q, x, metric)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("metric", ["dot", "l2"])
@pytest.mark.parametrize("chunk", [16, 64, 1024])
def test_knn_golden(rng, metric, chunk):
    q = rng.standard_normal((5, 24)).astype(np.float32)
    x = rng.standard_normal((200, 24)).astype(np.float32)
    k = 10
    vals, ids = distance.knn(q, x, k, metric=metric, chunk=chunk)
    vals, ids = np.asarray(vals), np.asarray(ids)
    want = np_scores(q, x, metric)
    want_ids = np.argsort(-want, axis=1)[:, :k]
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_allclose(vals, np.take_along_axis(want, want_ids, 1), rtol=1e-4, atol=1e-4)


def test_knn_ntotal_masks_padding(rng):
    x = rng.standard_normal((64, 8)).astype(np.float32)
    x[40:] = 0.0  # capacity padding
    q = rng.standard_normal((3, 8)).astype(np.float32)
    vals, ids = distance.knn(q, x, 5, metric="l2", ntotal=40, chunk=16)
    assert np.asarray(ids).max() < 40


def test_merge_topk(rng):
    a = rng.standard_normal((4, 6)).astype(np.float32)
    b = rng.standard_normal((4, 9)).astype(np.float32)
    ia = rng.integers(0, 100, (4, 6)).astype(np.int32)
    ib = rng.integers(100, 200, (4, 9)).astype(np.int32)
    v, i = distance.merge_topk(a, ia, b, ib, 5)
    allv = np.concatenate([a, b], axis=1)
    alli = np.concatenate([ia, ib], axis=1)
    order = np.argsort(-allv, axis=1)[:, :5]
    np.testing.assert_allclose(np.asarray(v), np.take_along_axis(allv, order, 1))
    np.testing.assert_array_equal(np.asarray(i), np.take_along_axis(alli, order, 1))


def test_kmeans_decreases_inertia(rng):
    # Three well-separated blobs: k-means must recover them.
    centers = np.array([[0, 0], [10, 10], [-10, 10]], dtype=np.float32)
    x = np.concatenate(
        [c + rng.standard_normal((100, 2)).astype(np.float32) * 0.5 for c in centers]
    )
    cent = np.asarray(kmeans.kmeans(x, 3, iters=15, chunk=64))
    assert cent.shape == (3, 2)
    # each true center has a learned centroid within 0.5
    d = np.linalg.norm(centers[:, None, :] - cent[None, :, :], axis=-1)
    assert d.min(axis=1).max() < 0.5


def test_kmeans_batched_shapes(rng):
    xs = rng.standard_normal((4, 300, 8)).astype(np.float32)
    cent = np.asarray(kmeans.kmeans_batched(xs, 16, iters=5, chunk=128))
    assert cent.shape == (4, 16, 8)
    # subspaces are independent: different data -> different codebooks
    assert not np.allclose(cent[0], cent[1])


def test_sq8_round_trip(rng):
    x = rng.standard_normal((100, 16)).astype(np.float32) * 3
    params = sq.sq8_train(x)
    codes = sq.sq8_encode(x, params["vmin"], params["span"])
    assert np.asarray(codes).dtype == np.uint8
    rec = np.asarray(sq.sq8_decode(codes, params["vmin"], params["span"]))
    span = np.asarray(params["span"])
    # quantization error bounded by half a grid step per dim
    assert np.max(np.abs(rec - x) / span[None, :]) <= (1.0 / 255.0) * 0.51


def test_pq_round_trip_quality(rng):
    # PQ reconstruction should be far better than random guessing.
    d, m = 32, 8
    x = rng.standard_normal((2000, d)).astype(np.float32)
    cb = pq.pq_train(x, m, iters=10)
    assert np.asarray(cb).shape == (m, 256, d // m)
    codes = pq.pq_encode(x, cb)
    assert np.asarray(codes).shape == (2000, m)
    rec = np.asarray(pq.pq_decode(codes, cb))
    err = np.mean((rec - x) ** 2)
    base = np.mean(x**2)
    assert err < 0.5 * base


@pytest.mark.parametrize("metric", ["dot", "l2"])
def test_adc_matches_decoded_distance(rng, metric):
    """ADC(lut, codes) must equal exact score against the decoded vectors."""
    d, m = 16, 4
    x = rng.standard_normal((500, d)).astype(np.float32)
    q = rng.standard_normal((6, d)).astype(np.float32)
    cb = pq.pq_train(x, m, iters=8)
    codes = pq.pq_encode(x, cb)
    rec = np.asarray(pq.pq_decode(codes, cb))
    lut = pq.adc_lut(q, cb, metric=metric)
    got = np.asarray(pq.adc_scan_shared(lut, codes))
    want = np_scores(q, rec, metric)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


def test_adc_scan_per_query_lists(rng):
    d, m = 16, 4
    x = rng.standard_normal((300, d)).astype(np.float32)
    q = rng.standard_normal((3, d)).astype(np.float32)
    cb = pq.pq_train(x, m, iters=5)
    codes = np.asarray(pq.pq_encode(x, cb))
    lists = np.stack([codes[0:10], codes[10:20], codes[20:30]])  # (3, 10, m)
    lut = pq.adc_lut(q, cb, metric="l2")
    got = np.asarray(pq.adc_scan(lut, lists))
    rec = np.asarray(pq.pq_decode(codes, cb))
    for qi in range(3):
        want = np_scores(q[qi : qi + 1], rec[qi * 10 : (qi + 1) * 10], "l2")[0]
        np.testing.assert_allclose(got[qi], want, rtol=1e-3, atol=1e-3)


def test_bucket_and_pad():
    assert distance.bucket_size(1) == 8
    assert distance.bucket_size(8) == 8
    assert distance.bucket_size(9) == 16
    x = np.ones((3, 4), np.float32)
    p = distance.pad_rows(x, 8)
    assert p.shape == (8, 4)
    np.testing.assert_array_equal(p[:3], x)
    assert p[3:].sum() == 0


def test_segmented_topk_matches_plain(rng):
    import jax.numpy as jnp
    from distributed_faiss_tpu.ops import distance

    nq, w, k = 4, 8192, 10  # w a multiple of the segment width
    s = jnp.asarray(rng.standard_normal((nq, w)).astype(np.float32))
    gids = jnp.arange(w, dtype=jnp.int32) + 100
    sv, si = distance.segmented_topk(s, k, gids)
    import jax
    pv, pp = jax.lax.top_k(s, k)
    np.testing.assert_allclose(np.asarray(sv), np.asarray(pv))
    np.testing.assert_array_equal(np.asarray(si), np.asarray(pp) + 100)


def test_segmented_topk_fallback_narrow(rng):
    import jax.numpy as jnp
    from distributed_faiss_tpu.ops import distance

    s = jnp.asarray(rng.standard_normal((3, 500)).astype(np.float32))
    gids = jnp.arange(500, dtype=jnp.int32)
    sv, si = distance.segmented_topk(s, 7, gids)
    assert sv.shape == (3, 7) and si.shape == (3, 7)
    assert np.all(np.diff(np.asarray(sv), axis=1) <= 0)


def test_segmented_topk_nonaligned_width_padded(rng):
    """Non-segment-multiple widths take the padded fast path exactly."""
    import jax
    import jax.numpy as jnp
    from distributed_faiss_tpu.ops import distance

    nq, w, k = 3, 5000, 10  # > 2*seg, not a multiple of 2048
    s = jnp.asarray(rng.standard_normal((nq, w)).astype(np.float32))
    gids = jnp.arange(w, dtype=jnp.int32)
    sv, si = distance.segmented_topk(s, k, gids)
    pv, pp = jax.lax.top_k(s, k)
    np.testing.assert_allclose(np.asarray(sv), np.asarray(pv))
    np.testing.assert_array_equal(np.asarray(si), np.asarray(pp))


def test_segmented_topk_pad_columns_yield_minus_one(rng):
    """Regression (round-2 review): when a row has fewer than k finite
    entries and the width is non-aligned, NEG_INF pad slots must carry
    id -1 — not a clamped real column id (which the sharded refine path
    would rescore into a phantom duplicate result)."""
    import jax.numpy as jnp
    from distributed_faiss_tpu.ops import distance

    nq, w, k = 2, 5000, 16  # non-multiple of 2048 -> padded fast path
    s = np.full((nq, w), -np.inf, np.float32)
    s[:, :5] = rng.standard_normal((nq, 5)).astype(np.float32)  # 5 finite
    ids = jnp.asarray(np.arange(w, dtype=np.int32) + 7)
    sv, si = distance.segmented_topk(jnp.asarray(s), k, ids)
    si = np.asarray(si)
    sv = np.asarray(sv)
    assert np.isfinite(sv[:, :5]).all()
    assert (si[:, :5] >= 7).all()
    # every -inf slot: either a real masked column's id or -1, NEVER an id
    # fabricated from the pad region; in this fully--inf tail the only
    # guarantee callers rely on is: ids of -inf slots are allowed to be
    # anything already present in ids[w] OR -1 — pin that pads are -1 by
    # checking no id exceeds the last real column's id
    assert (si <= 7 + w - 1).all()
    rows_ids = jnp.asarray(np.tile(np.arange(w, dtype=np.int32)[None, :], (nq, 1)))
    _, si2 = distance.segmented_topk_rows(jnp.asarray(s), k, rows_ids)
    assert (np.asarray(si2) <= w - 1).all()


# ------------------------------------------- the prefilter branch of _seg_reduce

_TOPK_WIDTHS = [4608, 5000, 8192, 51277, 65536]  # 5000 and 51277 pad a segment


def _topk_case(rng, name, nq, w):
    r = rng.standard_normal((nq, w)).astype(np.float32)
    if name == "random":
        return r
    if name == "halves":  # heavy ties: positions must fall to the lower column
        return np.round(r * 2) / 2
    if name == "five_finite":
        s = np.full((nq, w), -np.inf, np.float32)
        for row in s:
            row[rng.choice(w, 5, replace=False)] = rng.standard_normal(5)
        return s
    if name == "all_neg_inf":
        return np.full((nq, w), -np.inf, np.float32)
    assert name == "all_equal"
    return np.full((nq, w), 1.5, np.float32)


@pytest.mark.parametrize("w", _TOPK_WIDTHS)
@pytest.mark.parametrize("k", [1, 10, 100])
@pytest.mark.parametrize("case", ["random", "halves", "five_finite",
                                  "all_neg_inf", "all_equal"])
def test_seg_reduce_equals_top_k_bit_for_bit(rng, case, k, w):
    """Values AND positions of ``lax.top_k``, on both sides of the rule: at
    these widths k = 1 always takes the prefilter, k = 10 from 5000 up,
    k = 100 from 51277 up; the rest is the two-stage reduction."""
    import jax
    import jax.numpy as jnp

    assert distance.topk_prefilters(k, w) == (w >= {1: 0, 10: 5000, 100: 51277}[k])
    s = jnp.asarray(_topk_case(rng, case, 3, w))
    got_v, got_p = distance._seg_reduce(s, k)
    want_v, want_p = jax.lax.top_k(s, k)
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))


@pytest.mark.parametrize("w", [5000, 51277])
@pytest.mark.parametrize("case", ["five_finite", "all_neg_inf"])
def test_prefilter_keeps_pad_columns_out_of_the_ids(rng, case, w):
    """A row with fewer than k finite scores at a width the prefilter pads:
    ``segmented_topk`` and ``segmented_topk_rows`` return the ids of real
    (masked) columns or -1, never one made from the pad region."""
    import jax
    import jax.numpy as jnp

    k = 10
    assert distance.topk_prefilters(k, w) and w % 128
    s = jnp.asarray(_topk_case(rng, case, 2, w))
    _, want_p = jax.lax.top_k(s, k)
    gids = jnp.arange(w, dtype=jnp.int32) + 7
    _, si = distance.segmented_topk(s, k, gids)
    np.testing.assert_array_equal(np.asarray(si), np.asarray(want_p) + 7)
    rows_ids = jnp.tile(jnp.arange(w, dtype=jnp.int32)[None, :], (2, 1))
    _, si2 = distance.segmented_topk_rows(s, k, rows_ids)
    np.testing.assert_array_equal(np.asarray(si2), np.asarray(want_p))


@pytest.mark.parametrize("k,w", [(80, 2048), (80, 4096), (80, 8192), (80, 32768),
                                 (10, 4096)])
def test_the_rule_keeps_todays_branch_at_the_benchmarks_other_shapes(k, w):
    """``knnlm`` merges 80 of g x 1024 columns and ``ivfsq`` 10 of 4096: a
    later edit of the rule must not move those cells' programs unseen. The
    prefilter is the only branch that reduces a maximum."""
    import jax
    import jax.numpy as jnp

    assert not distance.topk_prefilters(k, w)
    text = str(jax.make_jaxpr(lambda s: distance._seg_reduce(s, k))(
        jnp.zeros((8, w), jnp.float32)))
    assert "reduce_max" not in text
    assert text.count("top_k") == (1 if w <= 4096 else 2)


def test_the_exact_scans_chunk_takes_the_prefilter():
    import jax
    import jax.numpy as jnp

    assert distance.topk_prefilters(10, distance.SCAN_CHUNK)
    text = str(jax.make_jaxpr(lambda s: distance._seg_reduce(s, 10))(
        jnp.zeros((8, distance.SCAN_CHUNK), jnp.float32)))
    assert "reduce_max" in text
