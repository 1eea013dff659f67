"""Scheduler: mean query rows in a merged device window
(``scheduler.queues.batch_rows``; its ``total_s`` is a sum of rows), averaged
over the ranks."""

from perfbench import stats


def read(obs):
    rows = stats.per_rank(obs, ("scheduler", "queues", "batch_rows"))
    return None if rows is None else sum(rows) / len(rows)
