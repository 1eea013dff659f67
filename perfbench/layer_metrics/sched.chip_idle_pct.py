"""Scheduler: share of the window the chip stood idle between two windows, on
the rank's own clock (the three ``scheduler.queues.sched.chip_idle.*`` rows
over the window), mean over ranks. Stands beside ``device.idle_pct``, the same
share on the device's clock: the difference is launch latency and the
completer's wake-up, which the host's clock books as busy."""

from perfbench import chip_timeline

read = chip_timeline.idle_pct
