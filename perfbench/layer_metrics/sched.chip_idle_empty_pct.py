"""Scheduler: share of the window the chip stood idle with no request queued
(``scheduler.queues.sched.chip_idle.empty``), mean over ranks: the rank's
spare capacity, which ``sched.idle_empty_pct`` (the batcher's idleness, 85% on
a saturated chip) stopped giving when two windows went in flight."""

from perfbench import chip_timeline


def read(obs):
    return chip_timeline.idle_pct(obs, ("empty",))
