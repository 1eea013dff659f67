"""Scheduler: mean time a request waited in the queue before its window was
flushed (``scheduler.queues.queue_wait_s``), on the slowest rank."""

from perfbench import stats


def read(obs):
    wait = stats.per_rank(obs, ("scheduler", "queues", "queue_wait_s"))
    return None if wait is None else 1e3 * max(wait)
