"""Plain reference for the ``knnlm-mesh4`` deployment: exact L2 search by numpy.

The deployment keeps one index partitioned over the four chips of a host
and promises what one unsharded index promises: the k nearest stored rows by
squared L2 distance, each hit carrying the id it was added under, at
recall@10 >= 0.95 against the exact answer. Which chip held a row does not
change the answer, so this file knows nothing of chips: it is the exact
answer written from the definition, a float32 scan in chunks and float64
distances for the ids a caller names. It imports nothing of the package under
test and is given nothing the package made — only the seeded rows and
queries.
"""

import numpy as np


BLOCK = 16384  # rows scanned at a time: the (queries, BLOCK) distances stay in cache


def exact_topk(chunks, q, k):
    """The k nearest rows to each query over the concatenation of
    ``chunks`` (row ids count through the chunks in order). Returns
    (squared distances (nq, k) float32, ids (nq, k) int64), nearest first."""
    nq = q.shape[0]
    best_d = np.full((nq, k), np.inf, np.float32)
    best_i = np.full((nq, k), -1, np.int64)
    qn = (q * q).sum(1)[:, None]
    first = 0
    for chunk in chunks:
        for s in range(0, chunk.shape[0], BLOCK):
            xc = chunk[s:s + BLOCK]
            d2 = qn - 2.0 * (q @ xc.T) + (xc * xc).sum(1)[None, :]
            # only queries with a row here nearer than their k-th so far
            hit = np.flatnonzero((d2 < best_d[:, -1:]).any(1))
            if hit.size == 0:
                continue
            d2 = d2[hit]
            kk = min(k, xc.shape[0])
            part = np.argpartition(d2, kk - 1, axis=1)[:, :kk]
            cand_d = np.concatenate([best_d[hit], np.take_along_axis(d2, part, 1)], 1)
            cand_i = np.concatenate([best_i[hit], part + first + s], 1)
            order = np.argsort(cand_d, axis=1, kind="stable")[:, :k]
            best_d[hit] = np.take_along_axis(cand_d, order, 1)
            best_i[hit] = np.take_along_axis(cand_i, order, 1)
        first += chunk.shape[0]
    return best_d, best_i


def exact_distances(rows, q):
    """Squared L2 distance, in float64, from q[i] to each of rows[i, :, :]."""
    diff = rows.astype(np.float64) - q.astype(np.float64)[:, None, :]
    return (diff * diff).sum(2)
