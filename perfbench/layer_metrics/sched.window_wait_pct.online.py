"""Scheduler: share of the window the batcher thread held a head request and
waited for followers (``scheduler.queues.sched.window_wait``: up to
``max_wait_ms`` a window), mean over ranks."""

from perfbench import ledger


def read(obs):
    return ledger.share_of_window_pct(obs, [ledger.sched("sched.window_wait")])
