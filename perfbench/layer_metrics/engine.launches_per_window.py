"""Engine launch-to-fetch, join, a mesh rank: device dispatches a merged
window took, the window mean of the engine's ``device_launches`` row (the
index's ``launches`` counter diffed around each launch), on the rank that
took most. 1.0 is the masked mode's serving contract (one program a window,
the top-k merged on the mesh); 2.0 is a kernel that failed and was served by
the XLA path's second dispatch. An index without the counter (every local
index) has no such row and reads nothing."""

from perfbench import ledger, stats


def read(obs):
    per_window = stats.per_rank(obs, ledger.engine(obs, "device_launches"))
    return None if per_window is None else max(per_window)
