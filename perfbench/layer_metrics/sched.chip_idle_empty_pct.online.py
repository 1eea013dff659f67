"""``sched.chip_idle_empty_pct`` in a cell that is judged on latency: the same reading, under
a name of its own because a per-layer metric names the one end-to-end metric it
moves, and ``knnlm-online`` reports ``lat_p50_ms`` and no rate."""

from perfbench import loader

read = loader.sibling(__file__, "sched.chip_idle_empty_pct").read
