"""Refine and fetch: the slowest rank's mean ``engine.refine_fetch`` (the exact
rerank's dispatch where the index refines, the result fetch, finalize)."""

from perfbench import ledger, stats


def read(obs):
    mean = stats.per_rank(obs, ledger.engine(obs, "engine.refine_fetch"))
    return None if mean is None else 1e3 * max(mean)
