"""The 95th percentile of the window's request latencies at the caller. Kept
off the end-to-end list: in a closed loop at saturation the tail is set by
how often a request misses its callers' shared scheduler window, and it read
153.6 to 168.1 ms for p95 on one code over twelve runs (PERF.md, section 6)."""

import numpy as np


def read(obs):
    ok = [r.end - r.start for r in obs["results"] if r.ok]
    return 1e3 * float(np.percentile(ok, 95)) if ok else None
