"""Arithmetic on the program's own counters, for the per-layer readers.

``get_perf_stats`` gives every span as ``{count, total_s, ...}`` since the
rank started, with percentiles read off log-spaced buckets 1.58x wide. A
window's mean is exact from two snapshots — (total after - total before) /
(count after - count before) — so the readers use that and not the p50,
which can only take a bucket's edge.
"""


def dig(d, path):
    for key in path:
        if not isinstance(d, dict) or key not in d:
            return None
        d = d[key]
    return d


def window_mean(before, after, path):
    """Mean of the span at ``path`` over the window, or None if it did not
    fire in it."""
    a, b = dig(after, path), dig(before, path) or {"count": 0, "total_s": 0.0}
    if a is None or a["count"] <= b["count"]:
        return None
    return (a["total_s"] - b["total_s"]) / (a["count"] - b["count"])


def window_count(before, after, path):
    a, b = dig(after, path), dig(before, path) or {"count": 0}
    return None if a is None else a["count"] - b["count"]


def per_rank(obs, path, fn=window_mean):
    """``fn`` over every rank's pair of snapshots; None if any rank lacks it."""
    if "stats_before" not in obs:
        return None
    out = [fn(b, a, path) for b, a in zip(obs["stats_before"], obs["stats_after"])]
    return None if any(v is None for v in out) else out


def client_mean_ms(obs):
    ok = [r.end - r.start for r in obs["results"] if r.ok]
    return 1e3 * sum(ok) / len(ok) if ok else None
