"""A profiler session inside a rank, and what it shows: the ``profile`` op.

Only the process that holds a chip can trace it, and an operator cannot
wrap a running rank in a context manager — so the rank does it on request
(``IndexServer.profile(seconds)``, ``dfstat --profile <seconds>``):
``capture`` opens a ``jax.profiler`` session for ``seconds`` and ``reduce``
turns the rank's own ``.xplane.pb`` into

- device busy and idle seconds over the session (gaps under a
  microsecond, the chip's own sequencing, apart);
- **idle seconds by host stage**: the rank's stages are profiler events
  (``utils/tracing.stage`` -> ``TraceAnnotation``) on the clock of the
  device's ``XLA Ops`` line, so every idle gap is split among the
  launch-loop stages open on the **batcher's line** of the host plane
  during it, by overlap; what lies between two stages of a launch goes to
  ``engine.launch`` or ``server.device``, the subtotals around them, and
  what no stage covers is ``unattributed``. The launch loop runs on two
  threads (the batcher launches, the completer collects, two windows in
  flight), and only the batcher's work precedes a dispatch: the completer's
  stages (``engine.refine_fetch``, ``engine.join``, ``sched.split``, the
  wait leg of ``engine.scan``) run beside it and take no gap. The batcher's
  line is the one that holds ``sched.assemble`` events; its leg of
  ``engine.scan`` is the dispatch and reads as ``engine.dispatch``;
- **idle seconds by cause**, under the names of the scheduler's
  ``sched.chip_idle.*`` counters (serving/scheduler.py), so the program's
  clock and the device's lie side by side: ``sched.idle`` is ``empty``,
  ``sched.window_wait`` is ``window_wait``, all else ``host``;
- ``busy_per_launch_s``: busy seconds over the launches that end in the
  window (the batcher's ``engine.launch`` events), beside the counters'
  ``sched.chip_busy`` mean;
- device seconds by named scope (``coarse``, ``list_scan``, ``merge_topk``,
  ``refine``: models/ivf.py) where an operation's event carries its scope
  path, else by HLO instruction name.

``reduce_rows`` works on plain rows, so it is tested on a small recorded
trace (tests/data_stage_ledger/). Event times in an ``.xplane.pb`` count
from the session's start; ``profile_start_s`` is that start on the wall
clock, the clock spans' ``start_s`` is on.
"""

import glob
import os
import re
import tempfile
import threading
import time
from collections import defaultdict

from distributed_faiss_tpu.utils import tracing

OPS_LINE = "XLA Ops"
DEVICE_PLANE = "/device:"
SCOPES = ("coarse", "list_scan", "merge_topk", "refine")
MIN_GAP_NS = 1_000  # shorter gaps are the chip's own sequencing, not the host
MAX_SECONDS = 120.0

_session = threading.Lock()  # one session a process: the profiler's own rule


def capture(seconds: float, log_dir: str) -> str:
    """Run a profiler session for ``seconds`` in this process; the path of
    its ``.xplane.pb``. Refuses, with a plain error, while another session
    is open (this op's, or one the process's owner started through
    ``jax.profiler`` — the benchmark's ``--trace 1`` does)."""
    import jax

    seconds = float(seconds)
    if not 0 < seconds <= MAX_SECONDS:
        raise ValueError(f"profile seconds must be in (0, {MAX_SECONDS:g}]")
    if not _session.acquire(blocking=False):
        raise RuntimeError("a profile session is already open in this rank")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # keeps the host's cost small
        options.host_tracer_level = 1  # the stages' TraceAnnotations
        try:
            jax.profiler.start_trace(log_dir, profiler_options=options)
        except RuntimeError as e:
            raise RuntimeError(
                f"a profile session is already open in this rank ({e})")
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
    finally:
        _session.release()
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise RuntimeError(f"the session left no .xplane.pb under {log_dir}")
    return files[-1]


def profile(seconds: float, keep_dir: str = None) -> dict:
    """``capture`` then ``reduce``; the trace is deleted unless ``keep_dir``
    names where to leave it (the reply then says ``xplane``)."""
    if keep_dir:
        os.makedirs(keep_dir, exist_ok=True)
        return reduce(capture(seconds, keep_dir))
    with tempfile.TemporaryDirectory(prefix="dft_profile_") as tmp:
        out = reduce(capture(seconds, tmp))
    del out["xplane"]  # gone with the directory
    return out


# ------------------------------------------------------------- reduction

_HLO_HEAD = re.compile(r"^(%?[\w.\-]+) = ")


def _hlo_name(name: str) -> str:
    """An operation's event may be named by its whole HLO line; keep the
    instruction's own name."""
    m = _HLO_HEAD.match(name)
    return (m.group(1) if m else name)[:120]


def _scope_of(stats: dict):
    """The named scope in an event's op-name path (any string stat shaped
    like ``jit(f)/jit(main)/list_scan/while/body/dot_general``), or None."""
    for value in stats.values():
        if isinstance(value, str) and "/" in value:
            parts = value.split("/")
            for scope in SCOPES:
                if scope in parts:
                    return scope
    return None


def read_xplane(path: str):
    """``(rows, facts)``: rows ``(plane, line, name, start_ns, dur_ns,
    scope, is_op)`` of every event, ``is_op`` marking a device operation —
    an event of a device plane's ``XLA Ops`` line, or (the CPU backend,
    which has no device plane) any event carrying an ``hlo_op`` stat — and
    the session's own ``profile_start_time`` / ``profile_stop_time``. A
    host line is a thread, and python's threads all bear the process's
    name: the n-th line of a name in its plane is ``<name>/<n>`` here, so
    that a thread's events can be told from another's."""
    from jax.profiler import ProfileData

    rows, facts = [], {}
    for plane in ProfileData.from_file(path).planes:
        for key, value in plane.stats:
            if key in ("profile_start_time", "profile_stop_time"):
                facts[key] = int(value)
        device = plane.name.startswith(DEVICE_PLANE)
        seen = defaultdict(int)
        for line in plane.lines:
            seen[line.name] += 1
            n = seen[line.name]
            label = line.name if n == 1 else f"{line.name}/{n}"
            for ev in line.events:
                stats = dict(ev.stats)
                is_op = (line.name == OPS_LINE if device
                         else "hlo_op" in stats)
                rows.append((plane.name, label, ev.name,
                             int(ev.start_ns), int(ev.duration_ns),
                             _scope_of(stats) if is_op else None, is_op))
    return rows, facts


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _ranked(totals, top):
    return [[n, ns / 1e9] for n, ns in
            sorted(totals.items(), key=lambda kv: -kv[1])[:top]]


def _book(pieces, intervals, idle_by):
    """Book to each interval's name the nanoseconds it shares with the
    ``pieces`` (sorted ``(start, end)``); returns what is left of them.
    ``intervals`` are sorted ``(start, end, name)`` of the batcher's line
    alone (``reduce_rows`` leaves the completer's out), so disjoint but for
    ``engine.dispatch`` inside the first leg of ``engine.scan``, which bear
    one name here; where two ranks share a process and their batchers'
    overlap, the earlier one takes the shared part."""
    left, first = [], 0
    for a, b in pieces:
        while first < len(intervals) and intervals[first][1] <= a:
            first += 1
        at, i = a, first
        while i < len(intervals) and intervals[i][0] < b:
            s, e, n = intervals[i]
            s, e = max(s, at), min(e, b)
            if e > s:
                idle_by[n] += e - s
                if s > at:
                    left.append((at, s))
                at = e
            i += 1
        if b > at:
            left.append((at, b))
    return left


# what a gap is booked to, in this order: the launch loop's own stages,
# then the subtotals around them (the python between two stages of a launch)
_LEVELS = (frozenset(tracing.LAUNCH_LOOP) | {"engine.dispatch"},
           {"engine.launch"}, {"server.device"})
BATCHER_STAGE = "sched.assemble"  # only the batcher thread books it
# on the batcher's line ``engine.scan`` is its first leg: the dispatch
_ON_BATCHER = {"engine.scan": "engine.dispatch"}


def reduce_rows(rows, window_ns=None, top=12) -> dict:
    """Plain rows (``read_xplane``) -> the op's reply. ``window_ns`` is the
    ``(start, end)`` to judge, on the rows' clock. Default: from the first
    launch-loop stage's start to the last one's end — a stage already open
    when the session starts, or still open when it stops, leaves no event,
    and the gaps under it could be booked to nothing (with no stage event
    at all: the first event's start to the last event's end)."""
    ops = [(s, s + d, n, scope) for _, _, n, s, d, scope, is_op in rows
           if is_op and d > 0]
    # the batcher's lines; a session with none (no scheduler in the
    # process: the in-process batcher runs a launch on its caller's thread)
    # is judged on every line, as one thread
    batcher = {(p, l) for p, l, n, *_ in rows if n == BATCHER_STAGE}
    levels = [sorted((s, s + d, _ON_BATCHER.get(n, n) if batcher else n)
                     for p, l, n, s, d, _, is_op in rows
                     if not is_op and n in names
                     and (not batcher or (p, l) in batcher))
              for names in _LEVELS]
    stages = levels[0]
    if not ops:
        return {"error": "the session holds no device operation",
                "stage_events": len(stages)}
    if window_ns:
        lo, hi = window_ns
    elif stages:
        lo, hi = stages[0][0], max(e for _, e, _ in stages)
    else:
        lo, hi = min(r[3] for r in rows), max(r[3] + r[4] for r in rows)
    merged = [(max(s, lo), min(e, hi)) for s, e in
              _union([(s, e) for s, e, _, _ in ops]) if e > lo and s < hi]
    gaps, edge = [], lo
    for s, e in merged + [(hi, hi)]:
        if s - edge >= MIN_GAP_NS:
            gaps.append((edge, s))
        edge = max(edge, e)
    idle_by, left = defaultdict(int), gaps
    for intervals in levels:
        left = _book(left, intervals, idle_by)
    if left:
        idle_by["unattributed"] = sum(b - a for a, b in left)
    by_scope, scoped = defaultdict(int), 0
    for s, e, n, scope in ops:
        by_scope[scope or _hlo_name(n)] += e - s
        scoped += scope is not None
    busy = sum(e - s for s, e in merged)
    idle = sum(e - s for s, e in gaps)
    by_cause = dict.fromkeys(tracing.CHIP_IDLE, 0)
    for name, ns in idle_by.items():
        by_cause[tracing.CHIP_IDLE_CAUSE.get(name, tracing.CHIP_IDLE_HOST)] += ns
    launches = sum(lo < e <= hi for _, e, _ in levels[1]) if batcher else 0
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / 1e9,
        "idle_s": idle / 1e9,
        # gaps under MIN_GAP_NS: the chip's own sequencing between the
        # operations of one program; busy + idle + sequencing = window
        "sequencing_s": (hi - lo - busy - idle) / 1e9,
        "idle_by_stage": _ranked(idle_by, top),
        "idle_by_cause": {n: ns / 1e9 for n, ns in by_cause.items()},
        "busy_per_launch_s": busy / 1e9 / launches if launches else None,
        "idle_attributed_share": (1.0 - idle_by.get("unattributed", 0) / idle
                                  if idle else 1.0),
        "device_by_scope": _ranked(by_scope, top),
        "ops": len(ops),
        "ops_with_scope": scoped,
        "stage_events": len(stages),
    }


def reduce(path: str, top=12) -> dict:
    rows, facts = read_xplane(path)
    out = reduce_rows(rows, None, top)
    planes = defaultdict(lambda: defaultdict(int))
    for plane, line, *_ in rows:
        planes[plane][line] += 1
    out["planes"] = {p: dict(lines) for p, lines in planes.items()}
    out["profile_start_s"] = facts.get("profile_start_time", 0) / 1e9
    out["xplane"] = path
    return out
