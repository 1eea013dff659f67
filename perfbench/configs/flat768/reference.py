"""Plain reference for the ``flat768`` deployment: exact L2 search by numpy.

What the deployment promises is the k nearest stored rows by squared L2
distance — all of them, exact search — each hit carrying the id it was
added under. This file is that answer, written from the definition: a
float32 scan in chunks that keeps a few candidates more than k, then the
candidates' distances again in float64 from the raw rows, so that a
near-tie is decided by the definition and not by the scan's rounding. It
imports nothing of the package under test and is given nothing the package
made — only the seeded rows and queries.
"""

import numpy as np


BLOCK = 16384  # rows scanned at a time: the (queries, BLOCK) distances stay in cache
SPARE = 6  # candidates kept beyond k for the float64 ordering


def _scan(chunks, q, kk):
    """The kk nearest by the float32 expansion |q|^2 - 2 q.x + |x|^2;
    (distances, ids), nearest first, ids counting through the chunks."""
    nq = q.shape[0]
    best_d = np.full((nq, kk), np.inf, np.float32)
    best_i = np.full((nq, kk), -1, np.int64)
    qn = (q * q).sum(1)[:, None]
    first = 0
    for chunk in chunks:
        for s in range(0, chunk.shape[0], BLOCK):
            xc = chunk[s:s + BLOCK]
            d2 = qn - 2.0 * (q @ xc.T) + (xc * xc).sum(1)[None, :]
            # only queries with a row here nearer than their kk-th so far
            hit = np.flatnonzero((d2 < best_d[:, -1:]).any(1))
            if hit.size == 0:
                continue
            d2 = d2[hit]
            take = min(kk, xc.shape[0])
            part = np.argpartition(d2, take - 1, axis=1)[:, :take]
            cand_d = np.concatenate([best_d[hit], np.take_along_axis(d2, part, 1)], 1)
            cand_i = np.concatenate([best_i[hit], part + first + s], 1)
            order = np.argsort(cand_d, axis=1, kind="stable")[:, :kk]
            best_d[hit] = np.take_along_axis(cand_d, order, 1)
            best_i[hit] = np.take_along_axis(cand_i, order, 1)
        first += chunk.shape[0]
    return best_d, best_i


def _rows(chunks, ids):
    """The stored rows named by ``ids`` (nq, kk), out of the chunks."""
    bounds = np.cumsum([0] + [c.shape[0] for c in chunks])
    which = np.searchsorted(bounds, ids, side="right") - 1
    out = np.empty(ids.shape + (chunks[0].shape[1],), np.float32)
    for c in np.unique(which):
        sel = which == c
        out[sel] = chunks[c][ids[sel] - bounds[c]]
    return out


def exact_topk(chunks, q, k):
    """The k nearest rows to each query over the concatenation of
    ``chunks`` (row ids count through the chunks in order). Returns
    (squared distances (nq, k) float32, ids (nq, k) int64), nearest first."""
    total = sum(c.shape[0] for c in chunks)
    kk = min(k + SPARE, total)
    _, ids = _scan(chunks, q, kk)
    d64 = exact_distances(_rows(chunks, ids), q)
    order = np.argsort(d64, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(d64, order, 1).astype(np.float32),
            np.take_along_axis(ids, order, 1))


def exact_distances(rows, q):
    """Squared L2 distance, in float64, from q[i] to each of rows[i, :, :]."""
    diff = rows.astype(np.float64) - q.astype(np.float64)[:, None, :]
    return (diff * diff).sum(2)
