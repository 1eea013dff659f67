"""Tracing / profiling / metrics.

The reference has none of this beyond log lines (SURVEY §5.1); here:
- ``LatencyStats``  — lock-protected per-operation latency counters with
  streaming percentiles (fixed log-spaced histogram buckets); the server
  records every RPC dispatch and exposes them via the ``get_perf_stats``
  RPC (observability the reference lacks). The serving scheduler records
  queue-wait / batch-occupancy / queue-depth distributions into the same
  structure (serving/scheduler.py). ``summary(raw=True)`` adds the raw
  bucket counts (the Prometheus exporter's ``_bucket`` series,
  observability/export.py) and per-bucket trace EXEMPLARS: ``record``
  optionally retains the most recent sampled ``trace_id`` per bucket, so
  a p99 row links directly to a fetchable distributed trace
  (observability/spans.py — "what made p99 spike" answers itself).
  ``LatencyStats.delta`` diffs two summaries so rate computation (the
  dfstat CLI's ``--watch`` view) is shared library code, not ad-hoc CLI
  math.
- ``stage`` / ``book`` — THE way a timed site books its interval (the
  stage ledger, docs/OPERATIONS.md#stage-ledger): one call records the
  counter (always, into a ``LatencyStats``), a profiler event (rank
  processes, so the stage sits in a profiler session's ``.xplane.pb`` on
  the clock of the device's ``XLA Ops`` line) and, for a sampled request,
  a span with its own id and its parent's. ``bind`` / ``ticket`` hand the
  sampled request from thread to thread; ``handover`` is a stage whose
  interval opens in one block and closes in a later one, on any thread (a
  window's launch and its collect). ``instant`` hands a ``now()`` reading
  up to the handover that asked for them (the scheduler's ``server.device``:
  when a window's first program was dispatched, when its last outputs were
  in hand), so no parameter carries one down or up.
"""

import bisect
import os
import threading
import time
from typing import Dict, Optional

# Streaming-percentile histogram: fixed log-spaced bucket upper bounds from
# 1 µs to 10^3 s, 5 buckets per decade (ratio 10^(1/5) ≈ 1.58x — the
# worst-case relative error of a reported percentile). Fixed buckets keep
# ``record`` O(log n_buckets) with O(1) memory per op name, so the serving
# hot path can afford per-request recording (a sorted reservoir would not).
_BUCKET_BOUNDS = tuple(1e-6 * 10 ** (i / 5) for i in range(46))
_PERCENTILES = ((0.50, "p50_s"), (0.95, "p95_s"), (0.99, "p99_s"))

# exemplar freshness bound: a bucket's retained trace_id stops being
# advertised this long after it was recorded. Matches the span rings'
# reality — an evicted trace's id would send an operator chasing a
# "no spans retained" dead lead — and comfortably exceeds any live
# diagnosis loop's poll cadence.
EXEMPLAR_TTL_S = 900.0


def bucket_bounds() -> tuple:
    """The fixed log-spaced bucket upper bounds every LatencyStats
    histogram shares — what the Prometheus exporter renders as the
    ``le`` labels of its cumulative ``_bucket`` series."""
    return _BUCKET_BOUNDS


class LatencyStats:
    def __init__(self):
        self._lock = threading.Lock()
        self._stats: Dict[str, Dict[str, float]] = {}
        self._hist: Dict[str, list] = {}
        # per-op {bucket index: (most recent sampled trace_id, recorded
        # monotonic instant)} — the exemplar linkage from a histogram row
        # to a fetchable trace, aged out after EXEMPLAR_TTL_S so a stale
        # id whose spans the rings evicted long ago is never advertised.
        # Only populated for sampled requests, so the dict stays empty
        # (and summary output byte-identical to pre-trace) when tracing
        # is off.
        self._exemplars: Dict[str, Dict[int, tuple]] = {}

    def record(self, name: str, seconds: float, exemplar=None) -> None:
        with self._lock:
            s = self._stats.setdefault(
                name, {"count": 0, "total_s": 0.0, "max_s": 0.0}
            )
            s["count"] += 1
            s["total_s"] += seconds
            s["max_s"] = max(s["max_s"], seconds)
            hist = self._hist.setdefault(name, [0] * len(_BUCKET_BOUNDS))
            # bucket i holds values <= bounds[i]; out-of-range clamps to the
            # last bucket (its reported percentile saturates at the top edge)
            bucket = min(bisect.bisect_left(_BUCKET_BOUNDS, seconds),
                         len(_BUCKET_BOUNDS) - 1)
            hist[bucket] += 1
            if exemplar is not None:
                self._exemplars.setdefault(name, {})[bucket] = (
                    exemplar, time.monotonic())

    @staticmethod
    def _percentiles(hist, count, max_s) -> Dict[str, float]:
        """Percentile estimates off the log-bucket histogram: the reported
        value is the upper edge of the bucket containing the quantile rank
        (<= 10^(1/5)x above the true value), capped at the exact max."""
        out = {}
        targets = [(q * count, key) for q, key in _PERCENTILES]
        cum = 0
        ti = 0
        last = len(hist) - 1
        for i, n in enumerate(hist):
            cum += n
            while ti < len(targets) and cum >= targets[ti][0]:
                # the last bucket is unbounded above (out-of-range clamps),
                # so its only honest upper estimate is the exact max
                est = max_s if i == last else min(_BUCKET_BOUNDS[i], max_s)
                out[targets[ti][1]] = est
                ti += 1
            if ti == len(targets):
                break
        return out

    def summary(self, raw: bool = False) -> Dict[str, Dict[str, float]]:
        """Per-op summary {count, total_s, max_s, mean_s, p50/95/99_s}.

        ``raw=True`` additionally exposes the histogram itself —
        ``"hist"`` (bucket counts aligned with ``bucket_bounds()``) and
        ``"exemplars"`` ({bucket index: trace_id}) — the view the
        Prometheus exporter and dfstat's shared rate math consume. Ops
        with a FRESH tail exemplar (recorded within ``EXEMPLAR_TTL_S``)
        at or past the p99 bucket also gain ``"p99_exemplar"``: the
        trace_id to fetch when asking what made the p99 spike (present
        in the default view too — it only appears once a sampled request
        actually landed in the tail, so pre-trace output is unchanged,
        and it ages out rather than advertising a trace the span rings
        evicted long ago)."""
        fresh_after = time.monotonic() - EXEMPLAR_TTL_S
        with self._lock:
            out = {}
            for name, s in self._stats.items():
                hist = self._hist[name]
                out[name] = dict(s)
                out[name]["mean_s"] = s["total_s"] / max(s["count"], 1)
                out[name].update(self._percentiles(
                    hist, s["count"], s["max_s"]))
                ex = {b: tid for b, (tid, t) in
                      (self._exemplars.get(name) or {}).items()
                      if t >= fresh_after}
                if ex:
                    tail = self._p99_exemplar(hist, s["count"], ex)
                    if tail is not None:
                        out[name]["p99_exemplar"] = tail
                if raw:
                    out[name]["hist"] = list(hist)
                    out[name]["exemplars"] = ex
            return out

    @staticmethod
    def _p99_exemplar(hist, count, exemplars):
        """The most recent sampled trace_id from the distribution's tail:
        the exemplar of the lowest bucket at/above the p99 rank that has
        one (tail requests land there by definition), else None."""
        target = 0.99 * count
        cum = 0
        p99_bucket = len(hist) - 1
        for i, n in enumerate(hist):
            cum += n
            if cum >= target:
                p99_bucket = i
                break
        at_or_above = [b for b in exemplars if b >= p99_bucket]
        return exemplars[min(at_or_above)] if at_or_above else None

    @staticmethod
    def delta(prev: Optional[Dict], cur: Dict) -> Dict[str, Dict]:
        """Diff two ``summary()`` snapshots of cumulative counters into
        the interval's own numbers — the one shared rate computation the
        dfstat CLI, tests, and any polling exporter all use. For every op
        in ``cur``: ``count``/``total_s`` are interval deltas (``prev``
        None or missing the op treats its baseline as zero),
        ``interval_mean_s`` is the interval's mean latency, and ``hist``
        (when both snapshots are raw) the interval's bucket counts. A
        counter that went BACKWARD (the rank restarted and its cumulative
        stats reset) is reported from zero rather than as a negative
        rate."""
        prev = prev or {}
        out = {}
        for name, c in cur.items():
            if not isinstance(c, dict) or "count" not in c:
                continue
            p = prev.get(name) or {}
            restarted = p.get("count", 0) > c["count"]
            base = {} if restarted else p
            d_count = c["count"] - base.get("count", 0)
            d_total = c["total_s"] - base.get("total_s", 0.0)
            row = {
                "count": d_count,
                "total_s": d_total,
                "interval_mean_s": d_total / d_count if d_count else 0.0,
                "max_s": c.get("max_s", 0.0),
            }
            if "hist" in c:
                ph = base.get("hist")
                row["hist"] = ([n - (ph[i] if ph and i < len(ph) else 0)
                                for i, n in enumerate(c["hist"])])
            out[name] = row
        return out

    def reset(self) -> None:
        with self._lock:
            self._stats.clear()
            self._hist.clear()
            self._exemplars.clear()


# ------------------------------------------------------------ stage ledger
#
# One thread-local context says which sampled request (if any) the thread
# is working for and where its counters go; ``stage`` reads it, so no
# ``trace_id=`` / ``span_buffer=`` parameter rides a signature for
# tracing's sake. Costs with sampling off and no profiler session open
# (CPU host, measured): about 0.5 us an annotation, 1.5 us a ``record``.

now = time.perf_counter  # the one monotonic clock stage timing reads

# The launch loop's ledger: the stages a merged window passes through
# (serving/scheduler.py, engine.py, models/base.py), each booked once a
# window. The batcher thread launches and the completer thread collects, two
# windows in flight, so the totals no longer add up to one thread's wall
# clock: ``engine.scan`` holds the time a window waits on the chip behind
# the one ahead of it. ``server.device`` (launch call to collect's end) and
# ``engine.launch`` (launch to fetch, counter ``device_search_s``) are
# subtotals that contain some of them; ``engine.dispatch`` is the host's
# share of ``engine.scan`` (its first leg), and the scheduler's
# ``sched.chip_*`` rows are the chip's own timeline (serving/scheduler.py).
LAUNCH_LOOP = (
    "sched.idle", "sched.window_wait", "sched.assemble",
    "engine.lock_wait", "engine.feed", "engine.scan", "engine.refine_fetch",
    "engine.join", "sched.split",
)


# The chip's timeline (serving/scheduler.py books it, observability/profile.py
# reduces a device trace to the same names): which wait of the batcher thread
# explains an idle chip by something else than the host's own work, the row
# each is booked to, and the row of everything else.
CHIP_IDLE_CAUSE = {"sched.idle": "sched.chip_idle.empty",
                   "sched.window_wait": "sched.chip_idle.window_wait"}
CHIP_IDLE_HOST = "sched.chip_idle.host"
CHIP_IDLE = (*CHIP_IDLE_CAUSE.values(), CHIP_IDLE_HOST)
CHIP_ROWS = ("sched.chip_busy", "sched.chip_queue", *CHIP_IDLE)


class _Context(threading.local):
    trace_id = None  # the sampled request's id; None = not sampled
    parent = None  # span id the next span booked on this thread hangs under
    spans = None  # SpanBuffer the spans land in; None = the process-local one
    sink = None  # LatencyStats the counters land in
    instants = None  # dict the enclosing handover collects ``instant``s in


class _NoCounter:
    """``sink=SPAN_ONLY``: the stage books its span and profiler event and
    no counter — for an interval whose counter nobody reads (a non-search
    op's pack, a subtotal), so the exporter carries no row for it."""

    @staticmethod
    def record(name, seconds, exemplar=None) -> None:
        pass


SPAN_ONLY = _NoCounter()

_ctx = _Context()
_annotation = None  # jax.profiler.TraceAnnotation, once a rank asked for it


# process-wide rows with no layer to own them: ``xla.compile`` (one record
# an XLA backend compile, cache hits included — they cost tens of ms too)
PROCESS = LatencyStats()
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_jax_duration(event: str, duration: float, **_kw) -> None:
    if event == _COMPILE_EVENT:
        PROCESS.record("xla.compile", duration)


def annotate_stages() -> None:
    """Rank processes call this (``IndexServer.__init__``; once counts):
    from then on every stage is also a ``jax.profiler.TraceAnnotation``, a
    no-op until a profiler session is open in the process, and every XLA
    compile is counted in ``PROCESS``'s ``xla.compile`` row (served by
    ``get_perf_stats``), so a window's compiles are count after less count
    before. A client process never calls it and never imports jax for
    tracing."""
    global _annotation
    if _annotation is None:
        import jax.monitoring
        from jax.profiler import TraceAnnotation

        jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
        _annotation = TraceAnnotation


def zero_row() -> dict:
    """A row that never fired, for a count whose 0 is a value and not a
    missing row (``xla.compile``, ``engine.scan_fused``)."""
    return {"count": 0, "total_s": 0.0, "max_s": 0.0, "mean_s": 0.0}


def compile_row(raw: bool = False) -> dict:
    """The ``xla.compile`` row, zeros before the first compile."""
    return PROCESS.summary(raw=raw).get("xla.compile", zero_row())


def ticket() -> Optional[tuple]:
    """The sampled request this thread works for, as ``(trace_id, parent
    span id, span buffer)`` to ``bind`` on another thread; None when the
    request is not sampled."""
    c = _ctx
    return None if c.trace_id is None else (c.trace_id, c.parent, c.spans)


class bind:
    """Work for ``ticket``'s request inside the block (None: change
    nothing); the thread's previous context comes back on exit."""

    __slots__ = ("_ticket", "_old")

    def __init__(self, ticket: Optional[tuple]):
        self._ticket = ticket

    def __enter__(self):
        if self._ticket is not None:
            c = _ctx
            self._old = (c.trace_id, c.parent, c.spans)
            c.trace_id, c.parent, c.spans = self._ticket
        return self

    def __exit__(self, *exc):
        if self._ticket is not None:
            c = _ctx
            c.trace_id, c.parent, c.spans = self._old


def _span_buffer(spans):
    if spans is not None:
        return spans
    from distributed_faiss_tpu.observability.spans import local_buffer

    return local_buffer()


def new_span_id() -> str:
    return os.urandom(4).hex()


def book(name: str, t0: float, sink: Optional[LatencyStats] = None,
         counter: Optional[str] = None, span_id: Optional[str] = None,
         **extra) -> float:
    """Book the interval from ``t0`` (a ``now()`` reading, possibly taken
    on another thread) to this instant under ``name``: the counter into
    ``sink`` (default: the context's) and, for a sampled request, a span
    under the context's parent. For waits that no ``with`` block can
    enclose — a queue, a hand-over between threads. ``counter`` names the
    counter row where an older name has readers; ``span_id`` is the id
    minted earlier for a span whose children were booked before it.
    Returns the seconds."""
    dt = now() - t0
    c = _ctx
    sink = sink if sink is not None else c.sink
    if sink is not None:
        sink.record(counter or name, dt, exemplar=c.trace_id)
    if c.trace_id is not None:
        _span_buffer(c.spans).record(c.trace_id, name, time.time() - dt, dt,
                               span_id=span_id or new_span_id(),
                               parent=c.parent, **extra)
    return dt


def count(name: str, value: float = 1.0) -> None:
    """Book one occurrence into the context's counter sink — a count row
    beside the stage the thread is in (``engine.scan_fused`` inside
    ``engine.scan``); nothing where no stage handed a sink down."""
    sink = _ctx.sink
    if sink is not None:
        sink.record(name, value)


def instant(name: str, first: bool = False) -> float:
    """Read the clock, and hand the reading up to the enclosing handover
    that collects instants (``handover(..., instants=True)``, in any of its
    legs; nothing where there is none: the in-process batcher). A later
    reading of the same name replaces an earlier one; with ``first`` the
    earliest stands. Returns the reading."""
    t = now()
    held = _ctx.instants
    if held is not None and not (first and name in held):
        held[name] = t
    return t


class stage:
    """``with stage("sched.assemble"):`` books the block's wall time.

    - counter, always: ``sink.record(name, dt)``; an explicit ``sink``
      also becomes the context's for the stages nested inside (the engine
      hands its ``LatencyStats`` down to the model's stages this way);
    - profiler event, in a rank process (``annotate_stages``);
    - span, when the thread works for a sampled request: ``span_id``,
      ``parent`` (the enclosing stage, or the id that crossed the wire),
      and stages nested inside hang under this one.

    ``done()`` ends the stage before the block does (a lock wait ends
    where the lock is taken: ``with stage(..) as st, lock: st.done()``).
    A block that raises books nothing: a failure's wait ceiling must not
    land in a latency row. ``t0`` is the ``now()`` reading the stage opened
    at, ``dt`` holds the seconds afterwards."""

    __slots__ = ("name", "sink", "counter", "extra", "dt", "t0", "_w0",
                 "_ann", "_span_id", "_parent", "_old_sink", "_open")

    def __init__(self, name: str, sink: Optional[LatencyStats] = None,
                 counter: Optional[str] = None, **extra):
        self.name = name
        self.sink = sink
        self.counter = counter
        self.extra = extra
        self.dt = 0.0

    def __enter__(self):
        c = _ctx
        self._old_sink = c.sink
        if self.sink is None:
            self.sink = c.sink
        else:
            c.sink = self.sink
        self._span_id = None
        if c.trace_id is not None:
            self._span_id = new_span_id()
            self._parent, c.parent = c.parent, self._span_id
            self._w0 = time.time()
        self._ann = None
        if _annotation is not None:
            self._ann = _annotation(self.name)
            self._ann.__enter__()
        self._open = True
        self.t0 = now()
        return self

    def done(self, failed: bool = False) -> None:
        if not self._open:
            return
        self._open = False
        self.dt = dt = now() - self.t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        c = _ctx
        c.sink = self._old_sink
        if self._span_id is not None:
            c.parent = self._parent
        if failed:
            return
        if self.sink is not None:
            self.sink.record(self.counter or self.name, dt,
                             exemplar=c.trace_id)
        if self._span_id is not None:
            _span_buffer(c.spans).record(c.trace_id, self.name, self._w0, dt,
                                   span_id=self._span_id,
                                   parent=self._parent, **self.extra)

    def echo(self, ticket: tuple) -> None:
        """Record this finished stage's span again in another sampled
        request's trace: a merged window is ONE launch for every request
        in it (the stage must have run for a sampled request itself)."""
        trace_id, parent, spans = ticket
        _span_buffer(spans).record(
            trace_id, self.name, self._w0, self.dt, span_id=new_span_id(),
            parent=parent, **self.extra)

    def __exit__(self, exc_type, exc, tb):
        self.done(failed=exc_type is not None)


class handover:
    """A stage in legs: ``with st:`` opens it, a later ``with st.last():``,
    on whichever thread, closes and books it — one counter record and one
    span from the first leg's start to the last leg's end, whatever ran
    between the legs (a window's launch on the batcher thread, its collect
    on the completer's). Every leg is a profiler event of the stage's name
    and sets the thread's context as ``stage`` does, so the stages nested in
    a later leg book into the sink, and hang under the span, that the first
    leg found: the sampled request crosses the threads with the stage, no
    ticket beside it. A leg that raises leaves the stage unbooked.

    ``instants=True``: the ``instant`` readings taken inside any leg, by
    whatever runs there, land in ``self.instants`` (a handover without it
    leaves them to the one around it). ``t0`` is the ``now()`` reading the
    first leg opened at."""

    __slots__ = ("name", "sink", "counter", "extra", "dt", "t0", "_w0",
                 "_ticket", "_span_id", "_ann", "_old", "_last", "instants")

    def __init__(self, name: str, sink: Optional[LatencyStats] = None,
                 counter: Optional[str] = None, instants: bool = False,
                 **extra):
        self.name = name
        self.sink = sink
        self.counter = counter
        self.extra = extra
        self.dt = 0.0
        self.t0 = None
        self._last = False
        self.instants = {} if instants else None

    def last(self) -> "handover":
        self._last = True
        return self

    def __enter__(self):
        c = _ctx
        self._old = (c.sink, c.trace_id, c.parent, c.spans, c.instants)
        if self.t0 is None:
            if self.sink is None:
                self.sink = c.sink
            self._ticket = ticket()
            self._span_id = None if self._ticket is None else new_span_id()
            self._w0 = time.time()
            self.t0 = now()
        c.sink = self.sink
        if self.instants is not None:
            c.instants = self.instants
        if self._ticket is not None:
            c.trace_id, _, c.spans = self._ticket
            c.parent = self._span_id
        self._ann = None
        if _annotation is not None:
            self._ann = _annotation(self.name)
            self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        c = _ctx
        c.sink, c.trace_id, c.parent, c.spans, c.instants = self._old
        if not self._last or exc_type is not None:
            return
        self.dt = dt = now() - self.t0
        if self.sink is not None:
            self.sink.record(self.counter or self.name, dt,
                             exemplar=self._ticket and self._ticket[0])
        if self._ticket is not None:
            trace_id, parent, spans = self._ticket
            _span_buffer(spans).record(trace_id, self.name, self._w0, dt,
                                       span_id=self._span_id, parent=parent,
                                       **self.extra)

    def echo(self, ticket: tuple) -> None:
        """As ``stage.echo``: the booked span again, in another sampled
        request's trace."""
        trace_id, parent, spans = ticket
        _span_buffer(spans).record(
            trace_id, self.name, self._w0, self.dt, span_id=new_span_id(),
            parent=parent, **self.extra)
