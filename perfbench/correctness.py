"""The comparison that decides ``correct``.

After the window has closed, what the timed requests returned is held
against the configuration's plain reference (``configs/<name>/reference.py``,
numpy, given only the seeded rows and queries) and against the guarantees
the configuration's file states. Every number compared is printed beside
its limit; ``correct`` is true only if every one is inside it.

  recall_at_k         a seeded sample of the window's query rows: share of
                      the reference's k best ids (nearest by ``l2``, largest
                      inner products by ``dot``: the reference is the
                      configuration's own) that the served answer holds.
                      Limit: the configuration's recall bar.
  distance_gap_rel    the same sample: widest |served score - exact score
                      of the served id| over the exact one, in float64 from
                      the raw row. The configuration's ``index.metric`` says
                      what a score is: under ``l2`` a squared distance,
                      which is its own scale; under ``dot`` an inner
                      product, which may be zero or negative, so the scale
                      is its size and never less than ``limits.score_floor``.
                      Limit: from the readings in PERF.md; a store or a scan
                      in a lower precision than the configuration states is
                      outside it.
  ntotal_gap          rows the ranks report indexed, rank by rank, against
                      the rows they acknowledged. Limit 0.
  self_lookup_misses  a seeded sample of stored rows searched by themselves
                      after the window, through the same client and the same
                      compiled shapes: rows whose nearest id is not their
                      own. Limit 0. Only where the configuration's
                      ``guarantees.self_lookup_top1`` is true: on rows that
                      are not normalised an inner product promises no such
                      thing.
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


def score_scale(config, exact):
    """What a gap between two scores is measured against, by the
    configuration's metric."""
    metric = config["index"]["metric"]
    if metric == "l2":
        return np.maximum(exact, 1e-12)
    if metric == "dot":
        return np.maximum(np.abs(exact), float(config["limits"]["score_floor"]))
    raise ValueError(f"unknown metric {metric!r}: the comparison has 'l2' and 'dot'")


def distance_gap_rel(config, reference, chunks, queries, served_ids, served_scores):
    """Widest relative gap between the served scores and the exact scores
    of the served ids. An id that names no stored row is an infinite gap."""
    total = sum(c.shape[0] for c in chunks)
    if ((served_ids < 0) | (served_ids >= total)).any():
        return float("inf")
    exact = reference.exact_distances(gather_rows(chunks, served_ids), queries)
    gap = np.abs(served_scores.astype(np.float64) - exact) / score_scale(config, exact)
    return float(gap.max())


class Checks:
    """The numbers compared, each beside its limit, printed as they come."""

    def __init__(self):
        self.rows = []

    @staticmethod
    def line(name, value, relation, limit, ok):
        return (f"check {name}: {value!r} (limit {relation} {limit!r}) "
                f"{'ok' if ok else 'OUTSIDE'}")

    def add(self, name, value, relation, limit):
        ok = {"<=": value <= limit, ">=": value >= limit}[relation]
        self.rows.append((name, value, relation, limit, bool(ok)))
        print(self.line(*self.rows[-1]), flush=True)

    def lines(self):
        """Every number compared beside its limit, for the end of standard
        error: what the driver keeps of a run that is not correct."""
        return "".join(self.line(*row) + "\n" for row in self.rows)

    def as_json(self):
        """The same for the result's line; a gap that is not finite (an id
        that names no row) goes as its name, which JSON has no number for."""
        return {name: {"value": value if np.isfinite(value) else str(value),
                       "limit": f"{relation} {limit!r}", "ok": ok}
                for name, value, relation, limit, ok in self.rows}

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
               distance_gap_rel(config, reference, chunks, queries, ids, scores), "<=",
               float(limits["distance_gap_rel_max"]))


def self_lookup_rows(chunks, seed, n):
    total = sum(c.shape[0] for c in chunks)
    ids = np.sort(np.random.default_rng([int(seed), 5]).choice(total, n, replace=False))
    return ids, gather_rows(chunks, ids)
