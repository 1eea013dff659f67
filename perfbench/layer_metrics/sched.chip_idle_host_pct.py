"""Scheduler: share of the window the chip stood idle while the batcher thread
worked for the next window (``scheduler.queues.sched.chip_idle.host``: the
assemble, the engine's lock and feed, the dispatch, the python between
them), mean over ranks."""

from perfbench import chip_timeline


def read(obs):
    return chip_timeline.idle_pct(obs, ("host",))
