"""Plain reference for the tests' ``pretend`` deployment: exact search by
the largest inner product, in numpy.

What the deployment promises is the k stored rows with the largest inner
product with the query, largest first, each hit carrying the id it was
added under, and as its score the inner product negated: upstream's client
merges every metric through one min-heap and hands back what it merged
(distributed-faiss client.py, the dot branch of its aggregation), and this
system keeps that. Written from the definition, in float64 throughout (the
tests' corpus is a few thousand rows). It imports nothing of the package
under test and is given nothing the package made: only the seeded rows and
queries.
"""

import numpy as np


def exact_topk(chunks, q, k):
    """(scores (nq, k) float32, ids (nq, k) int64), the largest inner
    product first, so the scores, negated products, ascend; ids count
    through the chunks in order, the lower id first among equals."""
    x = np.concatenate(chunks).astype(np.float64)
    score = -(q.astype(np.float64) @ x.T)
    ids = np.argsort(score, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(score, ids, 1).astype(np.float32), ids.astype(np.int64)


def exact_distances(rows, q):
    """The score as served, in float64, of each of rows[i, :, :] for q[i]:
    the inner product negated."""
    return -(rows.astype(np.float64) * q.astype(np.float64)[:, None, :]).sum(2)
