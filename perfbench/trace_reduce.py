"""From a profiler trace to the device's busy time, its longest idle gaps
and the operations that took most time.

``read_xplane`` turns the profiler's ``.xplane.pb`` into plain rows
``(plane, line, name, start_ns, duration_ns)``; ``reduce`` works on such rows
alone, so it is checked on a small recorded trace kept with the tests.

On a TPU every chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops``
holds one event per operation run and whose line ``XLA Modules`` holds one
event per program. Busy time is the union of the operations' intervals (a
chip runs one at a time, but the union is what the definition says and
costs nothing). The window is the span from the first to the last event of
the device planes unless the caller knows better. A gap is a stretch with no
operation running, named by the programs either side of it: what the host
was doing meanwhile is not in this trace yet (PERF.md, Open questions).
"""

import bisect
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MIN_GAP_NS = 1_000  # shorter gaps are the chip's own sequencing, not the host


OPCODE = re.compile(r" ([a-z][\w\-]*)\(")


def short_name(name):
    """An operation's event is named by its whole HLO line; keep the
    instruction's own name and its opcode: ``%fusion.3 fusion``."""
    if " = " not in name:  # a program: jit_name(<hash>), the hash differs run to run
        return re.sub(r"\(\d+\)$", "", name)[:120]
    head, rest = name.split(" = ", 1)
    opcode = OPCODE.search(" " + rest)
    return f"{head} {opcode.group(1) if opcode else ''}".strip()[:120]


def read_xplane(path):
    """Rows (plane, line, name, start_ns, duration_ns) of an .xplane.pb."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                rows.append((plane.name, line.name, short_name(ev.name),
                             int(ev.start_ns), int(ev.duration_ns)))
    return rows


def outline(rows):
    """{plane: {line: events}} — what a trace holds, for a reader who has
    not seen one from this device yet."""
    out = defaultdict(lambda: defaultdict(int))
    for plane, line, _, _, _ in rows:
        out[plane][line] += 1
    return {p: dict(lines) for p, lines in out.items()}


def union(intervals):
    """Merged, sorted copy of [(start, end), ...]."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def ranked(totals, top):
    """{name: seconds} -> [[name, seconds], ...], largest first, ``top`` long."""
    return [[n, s] for n, s in sorted(totals.items(), key=lambda kv: -kv[1])[:top]]


def averaged(parts, key, top=10):
    """The ``key`` lists of several reductions as one list of means."""
    totals = defaultdict(float)
    for part in parts:
        for name, seconds in part[key]:
            totals[name] += seconds / len(parts)
    return ranked(totals, top)


def reduce_plane(rows, top=10):
    """One device plane's rows -> busy seconds, span, top operations, gaps."""
    ops = [(s, s + d, n) for _, line, n, s, d in rows if line == OPS_LINE and d > 0]
    if not ops:  # a trace without the ops line: every event counts
        ops = [(s, s + d, n) for _, _, n, s, d in rows if d > 0]
    if not ops:
        return None
    modules = sorted((s, s + d, n) for _, line, n, s, d in rows
                     if line == MODULES_LINE)
    starts = [m[0] for m in modules]

    def module_at(t):
        """The program running at, or last started before, time t."""
        i = bisect.bisect_right(starts, t) - 1
        return modules[i] if i >= 0 else (None, None, None)

    merged = union([(s, e) for s, e, _ in ops])
    by_op = defaultdict(int)
    for s, e, n in ops:
        by_op[n] += e - s
    gaps = defaultdict(int)
    for (_, e0), (s1, _) in zip(merged, merged[1:]):
        if s1 - e0 < MIN_GAP_NS:
            continue
        before, after = module_at(e0), module_at(s1)
        inside = before == after and before[1] is not None and s1 <= before[1]
        label = (f"inside {after[2]}" if inside
                 else f"after {before[2]} before {after[2]}")
        gaps[label] += s1 - e0
    return {
        "busy_s": sum(e - s for s, e in merged) / 1e9,
        "first_ns": merged[0][0],
        "last_ns": merged[-1][1],
        "device_ops": [[n, ns / 1e9] for n, ns in ranked(by_op, top)],
        "idle_gaps": [[n, ns / 1e9] for n, ns in ranked(gaps, top)],
        "modules": len(modules),
    }


def reduce(rows, device_prefix="/device:TPU:", top=10):
    """All device planes of one process's trace, averaged over its chips."""
    planes = defaultdict(list)
    for row in rows:
        if row[0].startswith(device_prefix):
            planes[row[0]].append(row)
    per_plane = {p: r for p, r in ((p, reduce_plane(rs, top))
                                   for p, rs in sorted(planes.items())) if r}
    if not per_plane:
        return {"error": f"no events on a plane named {device_prefix}*"}
    parts = list(per_plane.values())
    return {
        "chips": len(parts),
        "busy_s": sum(r["busy_s"] for r in parts) / len(parts),
        "span_s": max((r["last_ns"] - r["first_ns"]) / 1e9 for r in parts),
        "device_ops": averaged(parts, "device_ops", top),
        "idle_gaps": averaged(parts, "idle_gaps", top),
        "modules": sum(r["modules"] for r in parts),
    }
