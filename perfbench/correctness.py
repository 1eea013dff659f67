"""The comparison that decides ``correct``.

After the window has closed, what the timed requests returned is held
against the configuration's plain reference (``configs/<name>/reference.py``,
numpy, given only the seeded rows and queries) and against the guarantees
the configuration's file states. Every number compared is printed beside
its limit; ``correct`` is true only if every one is inside it.

  recall_at_k         a seeded sample of the window's query rows: share of
                      the reference's k nearest ids that the served answer
                      holds. Limit: the configuration's recall bar.
  distance_gap_rel    the same sample: widest |served distance - exact
                      distance of the served id| over the exact distance,
                      the exact one in float64 from the raw row. Limit: from
                      the readings in PERF.md; a store or a scan in a lower
                      precision than the configuration states is outside it.
  ntotal_gap          rows the ranks report indexed, rank by rank, against
                      the rows they acknowledged. Limit 0.
  self_lookup_misses  a seeded sample of stored rows searched by themselves
                      after the window, through the same client and the same
                      compiled shapes: rows whose nearest id is not their
                      own. Limit 0.
  failed_requests     requests of the window that raised or came back with
                      the wrong shape. Limit 0.
"""

import numpy as np


def sample_requests(results, seed, min_rows):
    """Completed requests drawn by the seed until they hold ``min_rows``
    query rows (all of them if the window had fewer)."""
    done = [r for r in results if r.ok]
    order = np.random.default_rng([int(seed), 4]).permutation(len(done))
    picked, rows = [], 0
    for i in order:
        if rows >= min_rows:
            break
        picked.append(done[i])
        rows += done[i].rows
    return picked


def gather_rows(chunks, ids):
    """The stored rows with global ids ``ids`` (any shape) out of the chunks."""
    bounds = np.cumsum([0] + [c.shape[0] for c in chunks])
    flat = ids.reshape(-1)
    which = np.searchsorted(bounds, flat, side="right") - 1
    out = np.empty((flat.shape[0], chunks[0].shape[1]), np.float32)
    for c in np.unique(which):
        sel = which == c
        out[sel] = chunks[c][flat[sel] - bounds[c]]
    return out.reshape(ids.shape + (chunks[0].shape[1],))


def recall_at_k(got, want):
    return float(np.mean([len(set(g) & set(w)) / len(w) for g, w in zip(got, want)]))


def distance_gap_rel(reference, chunks, queries, served_ids, served_scores):
    """Widest relative gap between the served distances and the exact
    distances of the served ids. An id that names no stored row is an
    infinite gap."""
    total = sum(c.shape[0] for c in chunks)
    if ((served_ids < 0) | (served_ids >= total)).any():
        return float("inf")
    exact = reference.exact_distances(gather_rows(chunks, served_ids), queries)
    gap = np.abs(served_scores.astype(np.float64) - exact) / np.maximum(exact, 1e-12)
    return float(gap.max())


class Checks:
    """The numbers compared, each beside its limit, printed as they come."""

    def __init__(self):
        self.rows = []

    def add(self, name, value, relation, limit):
        ok = {"<=": value <= limit, ">=": value >= limit}[relation]
        self.rows.append((name, value, relation, limit, bool(ok)))
        print(f"check {name}: {value!r} (limit {relation} {limit!r}) "
              f"{'ok' if ok else 'OUTSIDE'}", flush=True)

    @property
    def correct(self):
        return bool(self.rows) and all(row[-1] for row in self.rows)


def compare_window(checks, config, reference, chunks, pool, results, seed):
    """Recall and distance gap of a seeded sample of the window's requests."""
    k = int(config["k"])
    limits = config["limits"]
    picked = sample_requests(results, seed, int(limits["sample_rows"]))
    checks.add("failed_requests", sum(1 for r in results if not r.ok), "<=", 0)
    if not picked:
        checks.add("sampled_query_rows", 0, ">=", 1)
        return
    queries = np.concatenate([pool[r.first_row:r.first_row + r.rows] for r in picked])
    ids = np.concatenate([r.ids for r in picked])
    scores = np.concatenate([r.scores for r in picked])
    print(f"check sample: {len(picked)} requests, {queries.shape[0]} query rows "
          f"of {sum(r.rows for r in results if r.ok)} served in the window", flush=True)
    _, want = reference.exact_topk(chunks, queries, k)
    checks.add(f"recall_at_{k}", recall_at_k(ids, want), ">=",
               float(config["guarantees"]["recall_at_k_min"]))
    checks.add("distance_gap_rel",
               distance_gap_rel(reference, chunks, queries, ids, scores), "<=",
               float(limits["distance_gap_rel_max"]))


def self_lookup_rows(chunks, seed, n):
    total = sum(c.shape[0] for c in chunks)
    ids = np.sort(np.random.default_rng([int(seed), 5]).choice(total, n, replace=False))
    return ids, gather_rows(chunks, ids)
