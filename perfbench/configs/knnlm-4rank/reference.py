"""Plain reference for the ``knnlm-4rank`` deployment: exact L2 search by
numpy over shared-nothing shards, merged as the deployment's client merges.

The deployment spreads batches over four ranks and promises, after the
client's merge of the four top-k lists, the k nearest stored rows over all
ranks by squared L2 distance, each with the id it was added under, at
recall@10 >= 0.95 against the exact answer. This file is that exact answer,
written from the definition: every chunk is a shard of its own here (which
rank held it does not change the answer), each shard's exact top-k is taken
in float32 and the lists are merged by distance. It imports nothing of the
package under test and is given nothing the package made — only the seeded
rows and queries.
"""

import numpy as np


BLOCK = 16384  # rows scanned at a time: the (queries, BLOCK) distances stay in cache


def shard_topk(x, q, k):
    """One shard's k nearest rows to each query: (distances, local ids)."""
    qn = (q * q).sum(1)[:, None]
    dists, ids = [], []
    for s in range(0, x.shape[0], BLOCK):
        xc = x[s:s + BLOCK]
        d2 = qn - 2.0 * (q @ xc.T) + (xc * xc).sum(1)[None, :]
        kk = min(k, xc.shape[0])
        part = np.argpartition(d2, kk - 1, axis=1)[:, :kk]
        dists.append(np.take_along_axis(d2, part, 1))
        ids.append(part + s)
    dists, ids = np.concatenate(dists, 1), np.concatenate(ids, 1)
    keep = np.argpartition(dists, k - 1, axis=1)[:, :k]
    return np.take_along_axis(dists, keep, 1), np.take_along_axis(ids, keep, 1)


def exact_topk(chunks, q, k):
    """The k nearest rows to each query over all shards (row ids count
    through the chunks in order). Returns (squared distances (nq, k)
    float32, ids (nq, k) int64), nearest first."""
    dists, ids, first = [], [], 0
    for x in chunks:
        d, i = shard_topk(x, q, k)
        dists.append(d)
        ids.append(i + first)
        first += x.shape[0]
    dists, ids = np.concatenate(dists, 1), np.concatenate(ids, 1)
    order = np.argsort(dists, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(dists, order, 1).astype(np.float32),
            np.take_along_axis(ids, order, 1).astype(np.int64))


def exact_distances(rows, q):
    """Squared L2 distance, in float64, from q[i] to each of rows[i, :, :]."""
    diff = rows.astype(np.float64) - q.astype(np.float64)[:, None, :]
    return (diff * diff).sum(2)
