"""Batched distance + top-k kernels.

TPU-native replacement for the FAISS flat-search surface
(reference consumes ``IndexFlatIP`` / ``IndexFlatL2`` at
distributed_faiss/index.py:25-33,94 and the C++ heap merge at
distributed_faiss/client.py:29-54).

Design notes (TPU-first):
- All scores are **bigger-is-better** internally: inner product for ``dot``,
  negated squared L2 for ``l2``. Index models convert to FAISS-style distances
  (ascending L2, descending IP) at their boundary.
- The corpus scan is a ``lax.scan`` over fixed-size chunks with a running
  top-k merge in the carry — static shapes throughout, so XLA tiles the
  ``q @ x.T`` onto the MXU and the (nq, chunk) score block never materializes
  for the whole corpus.
- Query batches are padded to power-of-two buckets (``pad_rows``) to bound the
  number of compiled program variants.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from distributed_faiss_tpu.utils import sanitize

NEG_INF = -jnp.inf

# fp32 MXU passes for distance math: bf16 matmul precision perturbs scores
# enough to reorder near-ties, which breaks exact-parity golden tests and
# recall guarantees. The storage dtype (bf16/fp16/int8) is where we save
# bandwidth instead.
_HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b):
    return jnp.dot(a, b, precision=_HIGHEST, preferred_element_type=jnp.float32)


def bucket_size(n: int, minimum: int = 8) -> int:
    """Smallest power of two >= n (>= minimum). Bounds jit cache size."""
    b = minimum
    while b < n:
        b *= 2
    return b


def pad_rows(x: np.ndarray, bucket: int):
    """Pad the leading dim of ``x`` up to ``bucket`` rows with zeros."""
    n = x.shape[0]
    if n == bucket:
        return x
    pad = np.zeros((bucket - n,) + x.shape[1:], dtype=x.dtype)
    return np.concatenate([x, pad], axis=0)


def pairwise_scores(q, x, metric: str):
    """(nq, d) x (n, d) -> (nq, n) bigger-is-better scores.

    dot: q @ x.T ; l2: -(||q||^2 - 2 q.x + ||x||^2).
    fp32 accumulation regardless of storage dtype.
    """
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    ip = _dot(q, x.T)
    if metric == "dot":
        return ip
    qn = jnp.sum(q * q, axis=1, keepdims=True)
    xn = jnp.sum(x * x, axis=1)
    return -(qn - 2.0 * ip + xn[None, :])


def merge_topk(vals_a, ids_a, vals_b, ids_b, k: int):
    """Merge two (nq, ka)/(nq, kb) bigger-is-better top-k sets into top-k."""
    vals = jnp.concatenate([vals_a, vals_b], axis=1)
    ids = jnp.concatenate([ids_a, ids_b], axis=1)
    best, pos = jax.lax.top_k(vals, k)
    return best, jnp.take_along_axis(ids, pos, axis=1)


# Exact top-k of a wide row, by one of three first stages chosen from the
# static (k, W) alone (`topk_prefilters` is the rule). What each costs on a
# v5e under jax 0.9.0 (PERF.md section 6, PR 29; ms a call on 128 rows, about
# 0.7 of it the call itself):
# - plain top_k, for narrow rows (W <= 2 * _TOPK_SEGMENT) and what the other
#   two cannot take: 0.9 at (k, W) = (10, 4096), 1.3 at (10, 65536), 2.0 at
#   (80, 32768), 8.4 at (256, 65536);
# - the prefilter, where a row's 128-column segments are at least 4 k: the
#   maximum of every segment (one read of the block, no sort), top_k over
#   those maxima (the one sort left, W / 128 wide), then top_k over the k
#   chosen segments alone (128 k wide: XLA's TopK call): 0.9 at (10, 8192),
#   1.0 at (10, 65536), 2.9 at (128, 65536); in the exact scan's loop, 32
#   chunks a launch of 256 rows, 45.7 ms a launch against 58.1 with plain
#   top_k. Segments of 256 or 512 columns, or the gather as a one-hot
#   select, a flat row gather or dynamic slices, were no faster;
# - the two-stage reduction, otherwise (k too large for the row's segments,
#   the IVF merges' k = 80): top_k of every 2048-wide segment, a full sort
#   of each along the lanes, then top_k over their union: 2.9 at
#   (80, 32768), 4.5 to 5.1 at any k over 65,536 columns, 265.7 ms for that
#   same launch of the scan. It beat plain top_k only at (256, 65536).
_TOPK_SEGMENT = 2048
_PREFILTER_SEGMENT = 128


def topk_prefilters(k: int, width: int) -> bool:
    """Whether the exact top-k of ``width``-wide rows chooses its segments
    by their maxima before it sorts (the rule `_seg_reduce` branches on;
    the host books ``engine.scan_prefilter`` from it)."""
    k = min(k, width)
    return (width > 2 * _TOPK_SEGMENT
            and -(-width // _PREFILTER_SEGMENT) >= 4 * k)


def _pad_to_segments(s, seg: int):
    """(nq, W) -> (nq, G, seg), the tail segment filled with NEG_INF."""
    nq, w = s.shape
    wp = -(-w // seg) * seg
    if wp != w:
        s = jnp.pad(s, ((0, 0), (0, wp - w)), constant_values=NEG_INF)
    return s.reshape(nq, wp // seg, seg)


def _prefilter_candidates(s, k: int):
    """The k segments of a row that can hold one of its top-k, by their
    maxima: a segment left out is beaten by k segments whose maximum is
    larger, or equal and at a lower column, so k elements precede anything
    it holds in top_k's order (value descending, column ascending). Taken in
    ascending order, so that ties among the candidates still fall to the
    lower column. Returns (scores (nq, k * seg), their columns (nq, k, seg))."""
    seg = _PREFILTER_SEGMENT
    s3 = _pad_to_segments(s, seg)
    _, chosen = jax.lax.top_k(jnp.max(s3, axis=2), k)
    chosen = jnp.sort(chosen, axis=1)
    cand = jnp.take_along_axis(s3, chosen[:, :, None], axis=1)
    cols = chosen[:, :, None] * seg + jnp.arange(seg, dtype=jnp.int32)
    return cand.reshape(-1, k * seg), cols


def _segment_candidates(s, k: int):
    """Every 2048-wide segment's own top-k: each of the row's top-k is
    inside its segment's. Returns (scores (nq, G * k), their columns
    (nq, G, k))."""
    seg = _TOPK_SEGMENT
    s3 = _pad_to_segments(s, seg)
    g = s3.shape[1]
    sv, sp = jax.lax.top_k(s3, k)
    cols = (jnp.arange(g, dtype=jnp.int32) * seg)[None, :, None] + sp
    return sv.reshape(-1, g * k), cols


def _seg_reduce(s, k: int):
    """Exact top-k over rows of (nq, W) scores: the values and positions
    ``lax.top_k(s, k)`` gives, ties and rows of fewer than k finite scores
    included, by the first stage `topk_prefilters` chooses.

    Returns (vals, pos) with pos indexing the ORIGINAL columns. Non-aligned
    widths are padded with NEG_INF. A padded column can only surface when a
    row has fewer than k finite entries; its pos is returned as -1,
    preserving the callers' invariant that a NEG_INF slot never carries a
    live id (masked columns inside the original width keep whatever id the
    caller stored there, exactly like plain top_k).
    """
    w = s.shape[1]
    kk = min(k, w)
    if topk_prefilters(kk, w):
        cand, cols = _prefilter_candidates(s, kk)
    elif w <= 2 * _TOPK_SEGMENT or kk > _TOPK_SEGMENT:
        return jax.lax.top_k(s, kk)
    else:
        cand, cols = _segment_candidates(s, kk)
    cv, cp = jax.lax.top_k(cand, kk)
    pos = jnp.take_along_axis(cols.reshape(cand.shape), cp, axis=1)
    return cv, jnp.where(pos < w, pos, -1)


def segmented_argtopk(s, k: int):
    """(vals, pos) top-k over rows; pos is -1 only for NEG_INF pad slots
    (impossible when every column is finite and k <= W)."""
    return _seg_reduce(s, k)


def segmented_topk(s, k: int, gids):
    """Exact top-k of (nq, W) scores; gids: (W,) int32 column ids."""
    cv, pos = _seg_reduce(s, k)
    safe = jnp.where(pos >= 0, pos, 0)
    return cv, jnp.where(pos >= 0, jnp.take(gids, safe), -1)


def segmented_topk_rows(s, k: int, ids):
    """segmented_topk for per-row id arrays: s, ids both (nq, W)."""
    cv, pos = _seg_reduce(s, k)
    safe = jnp.where(pos >= 0, pos, 0)
    return cv, jnp.where(pos >= 0, jnp.take_along_axis(ids, safe, axis=1), -1)


@functools.partial(jax.jit, static_argnames=("k", "metric", "chunk", "codec"))
def _knn_scan(q, x, ntotal, k: int, metric: str, chunk: int, codec: str = "raw",
              vmin=None, span=None, live=None):
    """Chunked corpus scan with running top-k.

    q: (nq, d) fp32; x: (cap, d) with cap % chunk == 0; ntotal: traced scalar —
    rows >= ntotal are masked to -inf so capacity padding never surfaces.
    codec: 'raw' (any float dtype, cast to fp32) or 'sq8' (uint8 codes
    dequantized on the fly with per-dim vmin/span — the decode fuses into the
    matmul's operand load, so SQ8 storage costs bandwidth, not FLOPs).
    live: optional (cap,) bool — the tombstone mask (mutation subsystem):
    False rows are masked to -inf exactly like capacity padding, so a
    deleted row can never surface even when k exceeds the live count. None
    (no deletions) traces the exact pre-mutation program — the
    delete-nothing byte-identity gate.
    Returns (scores (nq, k), ids (nq, k) int32) sorted descending by score.
    """
    nq = q.shape[0]
    cap = x.shape[0]
    nchunks = cap // chunk
    q = q.astype(jnp.float32)
    qn = jnp.sum(q * q, axis=1, keepdims=True)

    x_chunks = x.reshape(nchunks, chunk, x.shape[1])
    live_chunks = None if live is None else live.reshape(nchunks, chunk)

    # the never-taken select keeps a structural data dependency on x so the
    # carry's device-varying annotation stays consistent when this scan runs
    # inside shard_map (each shard carries its own top-k; without it jax
    # rejects the scan with a vma mismatch). A select — unlike `x[0,0]*0` —
    # cannot propagate NaN/Inf from the corpus into the init.
    anchor = jnp.where(jnp.zeros((), bool), x[0, 0].astype(jnp.float32), 0.0)
    init = (
        jnp.full((nq, k), NEG_INF, dtype=jnp.float32) + anchor,
        jnp.full((nq, k), -1, dtype=jnp.int32) + anchor.astype(jnp.int32),
    )

    def body(carry, inp):
        if live_chunks is None:
            ci, xc = inp
            lc = None
        else:
            ci, xc, lc = inp
        best_v, best_i = carry
        xc = xc.astype(jnp.float32)
        if codec == "sq8":
            xc = vmin[None, :] + xc * (span[None, :] / 255.0)
        ip = _dot(q, xc.T)
        if metric == "dot":
            s = ip
        else:
            xn = jnp.sum(xc * xc, axis=1)
            s = -(qn - 2.0 * ip + xn[None, :])
        base = ci * chunk
        gids = base + jnp.arange(chunk, dtype=jnp.int32)
        ok = gids[None, :] < ntotal
        if lc is not None:
            ok = ok & lc[None, :]
        s = jnp.where(ok, s, NEG_INF)
        cv, cids = segmented_topk(s, min(k, chunk), gids)
        return merge_topk(best_v, best_i, cv, cids, k), None

    xs = (jnp.arange(nchunks, dtype=jnp.int32), x_chunks)
    if live_chunks is not None:
        xs = xs + (live_chunks,)
    (vals, ids), _ = jax.lax.scan(body, init, xs)
    return vals, ids


# rows of the corpus a step of the scan scores: the width of its top-k
SCAN_CHUNK = 65536


def knn(q, x, k: int, metric: str = "l2", ntotal=None, chunk: int = SCAN_CHUNK,
        codec: str = "raw", vmin=None, span=None, live=None):
    """Exact k-nearest-neighbor scan of a (possibly capacity-padded) corpus.

    Returns bigger-is-better (scores, ids). ``ntotal`` masks padding rows;
    defaults to the full array. ``chunk`` bounds the transient score block
    (nq x chunk fp32 in VMEM-friendly tiles). ``live`` is the optional
    (cap,) bool tombstone mask (False = deleted, masked like padding);
    None runs the exact pre-mutation program.
    """
    # explicit feeds: host query batches (and the host ntotal scalar
    # below) are uploaded via device_put, not left for jit dispatch to
    # transfer implicitly — the serving path runs under DFT_XFERCHECK's
    # transfer guard, which forbids the implicit form
    if not isinstance(q, jax.Array):
        q = jax.device_put(np.asarray(q, np.float32))
    cap = x.shape[0]
    if ntotal is None:
        ntotal = cap
    chunk = min(chunk, cap)
    if cap % chunk != 0:
        # Standalone use: pad to a chunk multiple. Index models keep capacity
        # chunk-aligned so this path is cold.
        newcap = ((cap + chunk - 1) // chunk) * chunk
        x = jnp.pad(x, ((0, newcap - cap), (0, 0)))
        if live is not None:
            live = jnp.pad(live, (0, newcap - cap))
    # device_put, not jnp.asarray: ntotal is usually a host int, and the
    # serving path runs under DFT_XFERCHECK's transfer guard — the upload
    # must be an explicit transfer, not an implicit one at jit dispatch
    if not isinstance(ntotal, jax.Array):
        ntotal = jax.device_put(np.int32(ntotal))
    # maybe_checked: GRAFT_SANITIZE=1 runs the scan under checkify
    # (NaN + OOB-gather checks); identity passthrough otherwise
    return sanitize.maybe_checked(
        _knn_scan, q, x, ntotal, k=k, metric=metric,
        chunk=chunk, codec=codec, vmin=vmin, span=span, live=live)
