"""Scheduler: share of the window the batcher thread spent with an empty queue,
waiting for any request (``scheduler.queues.sched.idle``), mean over ranks."""

from perfbench import ledger


def read(obs):
    return ledger.share_of_window_pct(obs, [ledger.sched("sched.idle")])
