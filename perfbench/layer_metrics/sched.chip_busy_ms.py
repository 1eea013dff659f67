"""Scheduler: a merged window's own span on the chip, as the host sees it
(``scheduler.queues.sched.chip_busy``: from the later of the window's first
dispatch and the ``ready`` of the window ahead to its own ``ready``), window
mean on the slowest rank. What ``kernel.scan_ms`` read until two windows went
in flight: the wait behind the window ahead is ``sched.chip_queue``, not in
here. Launch latency and the completer's wake-up lie inside it, so where the
chip idles between windows it is an upper reading of the device's time."""

from perfbench import chip_timeline

read = chip_timeline.busy_ms
