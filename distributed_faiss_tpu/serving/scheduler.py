"""Deadline-aware micro-batching scheduler for the serving path.

One ``SearchScheduler`` per server rank. Connection threads call
``submit`` (blocking) or — for multiplexed RPC, where the connection
reader must keep pulling frames — ``submit_async`` with a completion
callback; a named batcher thread drains the queue, coalesces
compatible requests — same ``(index_id, top_k, return_embeddings,
dim)`` — into one concatenated device batch and launches the engine's
batched search entry once; a completer thread collects the launched
windows in their order and hands every caller its row slice. Two flush
triggers: the pending compatible rows reach ``max_batch_rows``, or the
oldest queued request has waited ``max_wait_ms`` (while a window is in
flight it waits for followers longer: below).

Two windows in flight (``IN_FLIGHT``, fixed). While window n runs on the
chip the batcher assembles, feeds and dispatches window n+1 behind it, so
the chip starts n+1 the moment n ends, and the completer fetches, joins,
splits and finishes n meanwhile. ``search_fn.launch(index_id, query_batch,
top_k, return_embeddings)``, where the target offers it
(``engine.Index.launch_batched`` behind the server's), returns a handle
whose ``collect()`` gives the result; a plain ``search_fn`` is served
through a launch that runs the whole search and a handle that is already
finished. A third window waits for the first to be collected. Waiting for
followers costs the chip nothing while a window is on its way to be
collected, so then a head request waits, past ``max_wait_ms``, until the
queue holds as many rows as the newest window in flight took (or
``max_batch_rows``), or until the last of them is collected: a window does
not shrink because the loop no longer gives followers a whole launch to
queue up. Completions are published in
launch order; a window's error fails its own callers only; ``stop()``
lets the windows in flight complete and fails what is queued.

Admission control (the backpressure contract, docs/OPERATIONS.md):

- a request whose deadline has already passed is rejected with
  ``DeadlineExpired`` before it can occupy queue space — and a request
  whose deadline expires while queued is shed at flush time, in both
  cases without touching the device;
- a request arriving while ``max_queue`` requests are pending is
  rejected with ``SchedulerBusy`` — the RPC layer turns this into a
  structured BUSY response that clients retry under their RetryPolicy
  backoff, so overload degrades into client-side pacing instead of an
  unbounded server-side queue.

Identity invariant (tested in tests/test_scheduler_identity.py): query
rows are independent in every index's search, so a caller's slice of the
merged launch is bit-identical to the result of serving its request
alone. The splitter routes rows purely positionally from the extraction
order — a caller can get *no* result or an error, never another
caller's rows.

One flush = one engine call = (on a mesh-backed index) ONE pjit launch:
``search_fn`` is ``engine.Index.search_batched``, whose locked device
step routes through ``TpuIndex.launch_search`` — for a rank that owns a
device mesh the whole merged window crosses to the chips as a single
device program with the top-k reduce on-mesh, and results leave the
device once per window (parallel/mesh.py; the engine's
``device_launches`` perf rows pin the contract). The group key already
isolates ``(index_id, top_k, return_embeddings, dim)``, so every row of
a flushed batch is legal in the same launch by construction.

Observability rides the shared ``LatencyStats`` histogram surface
(utils/tracing.py): queue-wait and end-to-end latency with streaming
percentiles, batch occupancy (requests and rows per launch), queue depth
at flush, and monotonic shed/busy counters — all exported through the
rank's ``get_perf_stats`` RPC under the ``"scheduler"`` key.

The two threads keep the launch loop's stage ledger
(``utils/tracing.stage``, docs/OPERATIONS.md#stage-ledger), every stage
once a window: on the batcher thread ``sched.idle`` (queue empty),
``sched.window_wait`` (a head request waits for followers, or for one of
the two places in flight), ``sched.assemble`` (deadline shed, concat) and
the launch half of the engine's stages; on the completer thread their
collect half and ``sched.split`` (row split and completion callbacks).
``server.device`` (a span and a profiler event, no counter) runs from the
launch call's start to the collect's end. The stages' totals no longer add
up to one thread's wall clock: ``engine.scan`` of window n+1 runs beside
window n's collect (a stage that raises books nothing). A sampled request (its submitter's context held a trace,
``tracing.ticket``) additionally gets ``server.queue`` (wait + which
merge window it landed in and its occupancy) and ``server.device`` spans
in its submitter's SpanBuffer, and stamps the latency histograms'
exemplars (observability/spans.py).

The chip's timeline. One rank serves one chip and this loop is the one
place that knows the order in which windows reach it, so the completer
books, once a collected window, where the chip's time went
(``_book_chip``): ``sched.chip_busy`` (the window's own span on the chip,
as the host sees it), ``sched.chip_queue`` (its programs' wait behind the
window ahead) and, where the chip stood idle before it,
``sched.chip_idle.empty`` / ``.window_wait`` / ``.host`` by what the batcher
thread was in meanwhile. Two ``tracing.instant`` readings a window feed it,
handed up through the ``server.device`` handover by whatever ran inside it
(``models/base._Unit``): ``dispatched`` (the first device program's dispatch
call returned; where nothing says so, the launch's start) and ``ready`` (the
window's last outputs are in hand; where nothing says so, the collect's
end). Over any run of collected windows busy plus idle is the last
``ready`` less the first ``dispatched`` (``counters["chip_timeline_s"]``).
"""

import logging
import threading
import time
from collections import deque
from typing import Callable, List, Optional, Tuple

import numpy as np

from distributed_faiss_tpu.utils import lockdep, tracing, xfercheck
from distributed_faiss_tpu.utils.atomics import AtomicCounters
from distributed_faiss_tpu.utils.config import SchedulerCfg
from distributed_faiss_tpu.utils.tracing import LatencyStats

logger = logging.getLogger()


class SchedulerBusy(RuntimeError):
    """The request queue is full: the rank is overloaded. Retryable —
    clients back off and retry (rpc.BusyError client-side)."""

    def __init__(self, queue_depth: int, max_queue: int):
        self.queue_depth = queue_depth
        self.max_queue = max_queue
        super().__init__(
            f"scheduler queue full ({queue_depth}/{max_queue} requests)"
        )


class DeadlineExpired(RuntimeError):
    """The request's deadline passed before it reached the device.
    Not retryable — the client's budget is already gone."""


class SchedulerStopped(RuntimeError):
    """The scheduler was stopped while this request was queued."""


class _Request:
    __slots__ = ("index_id", "q", "k", "return_embeddings", "deadline",
                 "eager", "enqueue_t", "event", "result", "error",
                 "callback", "ticket")

    def __init__(self, index_id: str, q: np.ndarray, k: int,
                 return_embeddings: bool, deadline: Optional[float],
                 eager: bool = False, callback: Optional[Callable] = None):
        self.index_id = index_id
        self.q = q
        self.k = k
        self.return_embeddings = return_embeddings
        self.deadline = deadline  # absolute time.monotonic(), or None
        self.eager = eager  # head of queue flushes without the wait window
        self.enqueue_t = time.monotonic()
        self.event = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None
        # async completion (the mux serving path): fired exactly once with
        # (result, error) when the request completes, instead of a thread
        # blocking on ``event``
        self.callback = callback
        # the sampled trace the SUBMITTING thread works for (None for the
        # unsampled default), taken from its context: the batcher thread
        # binds it to book the queue-wait / device spans against it, and
        # its id rides the latency histograms as their exemplar
        self.ticket = tracing.ticket()

    @property
    def trace_id(self) -> Optional[str]:
        return self.ticket[0] if self.ticket is not None else None

    @property
    def key(self) -> Tuple:
        return (self.index_id, self.k, self.return_embeddings, self.q.shape[1])

    @property
    def rows(self) -> int:
        return self.q.shape[0]


def _concat_rows(live: List["_Request"], n_rows: int) -> np.ndarray:
    """Buffer-aware concat for a merged window: allocate the exact array
    the device launch consumes and copy each request's rows into their
    slice ONCE. Requests arriving over the binary wire already hold
    contiguous float32 query planes (the schema pins the dtype, and
    ``rpc.recv_frame_ex`` decoded them straight off the socket), so this
    is the only copy between socket and device — there is no per-request
    intermediate materialize, and a non-f32 straggler (legacy pickle
    frame from an old peer) converts during its one slice copy instead
    of in a separate pass."""
    out = np.empty((n_rows, live[0].q.shape[1]), np.float32)
    ofs = 0
    for r in live:
        out[ofs:ofs + r.rows] = r.q
        ofs += r.rows
    return out


def _split_rows(value, offsets: List[Tuple[int, int]]):
    """Split one element of a batched search result back per caller.

    ndarrays and lists split along the leading (row) axis; None (e.g. the
    embeddings slot when not requested) and scalars broadcast unchanged.
    """
    if value is None:
        return [None] * len(offsets)
    if isinstance(value, np.ndarray):
        return [value[lo:hi] for lo, hi in offsets]
    if isinstance(value, list):
        return [value[lo:hi] for lo, hi in offsets]
    return [value] * len(offsets)


class _Finished:
    """The handle of a window whose launch was the whole search, or
    failed: ``collect()`` returns the result or raises the error."""

    __slots__ = ("result", "error")

    def __init__(self, result=None, error: Optional[BaseException] = None):
        self.result, self.error = result, error

    def collect(self):
        if self.error is not None:
            raise self.error
        return self.result


class _Window:
    """A launched window on its way to the completer."""

    __slots__ = ("live", "handle", "device", "traced", "waits")

    def __init__(self, live, handle, device, traced):
        self.live, self.handle, self.device, self.traced = (
            live, handle, device, traced)
        # (start, end, stage) of the batcher's waits since its last launch:
        # what a gap on the chip before this window is put down to
        self.waits = ()


class SearchScheduler:
    """Bounded queue + batcher and completer threads coalescing concurrent
    searches, two windows in flight.

    ``search_fn(index_id, query_batch, top_k, return_embeddings)`` is the
    engine's already-batched entry (engine.Index.search_batched on a
    server); it must return a tuple whose ndarray/list elements have one
    leading row per query row. Where it also offers ``search_fn.launch``
    (same arguments; returns a handle whose ``collect()`` gives that
    tuple), windows are launched through it and collected later.
    """

    IN_FLIGHT = 2  # windows launched and not yet collected, at most

    def __init__(self, search_fn: Callable, cfg: Optional[SchedulerCfg] = None,
                 name: str = "search-batcher", tag: Optional[dict] = None):
        launch = getattr(search_fn, "launch", None)
        if launch is None:
            # no two-phase form: the launch is the whole search, and its end
            # the moment the window's outputs are in hand
            def launch(*call):
                result = search_fn(*call)
                tracing.instant("ready")
                return _Finished(result)
        self._launch_fn = launch
        self.cfg = cfg if cfg is not None else SchedulerCfg()
        # replica identity riding the stats surface (replication layer):
        # admission behavior is unchanged per replica, but operators need
        # queue/shed numbers attributable to (rank, shard_group). Owned by
        # the server, which updates shard_group on (re-)registration.
        self.tag = dict(tag or {})
        self._cond = lockdep.condition("SearchScheduler._cond")
        self._queue: List[_Request] = []
        # launched windows in launch order, and how many of them are not
        # collected yet (a window leaves the deque when its collect starts
        # and the count when it ends); both guarded by _cond
        self._launched: deque = deque()
        self._in_flight = 0
        self._newest_rows = 0  # rows of the window launched last
        self._batcher_done = False
        self._stopping = False
        # the batcher's own (filled and emptied in place): its sched.idle /
        # sched.window_wait intervals since its last launch, handed over
        # with the next window
        self._waits: List[Tuple[float, float, str]] = []
        # the completer's own (in place too): the waits of windows that died
        # or were empty, for the next good window's gap
        self._unbooked: List[Tuple[float, float, str]] = []
        # the chip's timeline: _chip_free is the largest ``ready`` of the
        # windows collected so far, _chip_first the first one's
        # ``dispatched``. Both and a window's rows move together under
        # _chip_lock, so that a snapshot's rows add up to its
        # chip_timeline_s (a leaf beside _cond: never held with it)
        self._chip_first: Optional[float] = None
        self._chip_free: Optional[float] = None
        self._chip_lock = lockdep.lock("SearchScheduler._chip_lock")
        self.stats = LatencyStats()
        # admission/flush counters ride the shared atomic-counter helper
        # (utils/atomics.py): the fast paths bump them without contending
        # the flush condition, and stats readers get a torn-free snapshot
        self._counters = AtomicCounters(
            ("submitted", "batches", "shed_deadline", "rejected_busy"))
        self._thread = threading.Thread(
            target=self._batcher_loop, name=name, daemon=True)
        self._completer = threading.Thread(
            target=self._completer_loop, name=f"{name}-collect", daemon=True)
        self._thread.start()
        self._completer.start()

    # ------------------------------------------------------------ client side

    def submit(self, index_id: str, query_batch: np.ndarray, top_k: int,
               return_embeddings: bool = False,
               deadline: Optional[float] = None, eager: bool = False):
        """Enqueue one search and block until its slice of a merged launch
        is ready. ``deadline`` is an absolute ``time.monotonic()`` instant;
        expired requests never reach the device. ``eager`` skips the
        max-wait window when this request heads the queue — for callers
        that cannot overlap (a legacy one-in-flight peer on the
        single-threaded selector loop, where waiting for followers that
        structurally cannot arrive would add max_wait_ms of pure latency);
        admission control and coalescing with already-queued requests
        still apply."""
        req = self.submit_async(index_id, query_batch, top_k,
                                return_embeddings, deadline=deadline,
                                eager=eager)
        # timeout-with-retry rather than one untimed wait: every admitted
        # request is eventually finished by the batcher (its loop survives
        # flush failures and stop() drains the queue) — the escape hatch
        # covers the one way that contract can break, the batcher thread
        # itself dying (interpreter teardown, untrappable error), which
        # would otherwise strand this caller forever
        while not req.event.wait(timeout=5.0):
            if not self._thread.is_alive() and not req.event.is_set():
                raise SchedulerStopped(
                    "scheduler batcher thread died with this request "
                    "in flight")
        if req.error is not None:
            raise req.error
        self.stats.record("e2e_s", time.monotonic() - req.enqueue_t,
                          exemplar=req.trace_id)
        return req.result

    def submit_async(self, index_id: str, query_batch: np.ndarray,
                     top_k: int, return_embeddings: bool = False,
                     deadline: Optional[float] = None, eager: bool = False,
                     callback: Optional[Callable] = None) -> _Request:
        """Admission-checked enqueue that returns immediately (the mux
        serving loops' entry: the connection reader must keep pulling
        frames). ``callback(result, error)`` fires exactly once — on the
        batcher thread — when the request completes; exactly one of the
        two is non-None. Admission failures (SchedulerBusy /
        DeadlineExpired / SchedulerStopped) raise synchronously in the
        caller: the request was never queued and the callback will not
        fire."""
        q = np.asarray(query_batch, np.float32)
        if q.ndim != 2:
            raise ValueError(f"query batch must be 2-D, got shape {q.shape}")
        req = _Request(index_id, q, int(top_k), bool(return_embeddings),
                       deadline, eager=eager, callback=callback)
        with self._cond:
            if self._stopping:
                raise SchedulerStopped("scheduler is stopped")
            if deadline is not None and time.monotonic() >= deadline:
                self._counters.inc("shed_deadline")
                raise DeadlineExpired(
                    "deadline expired before the request was admitted")
            if len(self._queue) >= self.cfg.max_queue:
                self._counters.inc("rejected_busy")
                raise SchedulerBusy(len(self._queue), self.cfg.max_queue)
            self._counters.inc("submitted")
            self._queue.append(req)
            self._cond.notify_all()
        return req

    def _finish(self, req: _Request) -> None:
        """Publish a request's outcome exactly once: wake a blocked
        ``submit`` and fire the async completion callback (if any). Every
        completion path funnels here, so a request can never complete
        twice (the event doubles as the fired-flag) or complete with
        neither result nor error."""
        if req.event.is_set():
            return
        if req.error is None and req.result is None:
            req.error = RuntimeError("scheduled search aborted")
        req.event.set()
        if req.callback is not None:
            if req.error is None:
                # successes only — parity with the blocking submit(), so
                # e2e_s stays comparable between mux and legacy serving
                # (shed/busy failures would otherwise pollute the p99
                # with their queue-wait ceilings)
                self.stats.record("e2e_s", time.monotonic() - req.enqueue_t,
                                  exemplar=req.trace_id)
            try:
                req.callback(req.result, req.error)
            except Exception:
                logger.exception("scheduler completion callback failed")

    # ----------------------------------------------------------- batcher side

    def _batcher_loop(self) -> None:
        try:
            self._batch_and_launch()
        finally:
            with self._cond:  # the completer ends once what is launched is done
                self._batcher_done = True
                self._cond.notify_all()

    def _batch_and_launch(self) -> None:
        while True:
            try:
                batch = self._next_batch()
            except BaseException:
                # the flush-wait itself failed (allocation under memory
                # pressure, a bug in the trigger logic): the thread MUST
                # survive — callers blocked in submit's untimed event.wait
                # would otherwise hang forever. Fail whatever is queued and
                # keep serving.
                logger.exception("scheduler flush-wait failed")
                with self._cond:
                    stranded, self._queue = self._queue, []
                for r in stranded:
                    r.error = RuntimeError("scheduler internal error")
                    self._finish(r)
                time.sleep(0.05)  # never spin hot on a persistent failure
                continue
            if batch is None:
                return  # stopped; stop() already drained the queue
            try:
                window = self._launch(batch)
            except BaseException:  # the loop must survive any launch failure
                logger.exception("scheduler batch failed")
                window = _Window(
                    [r for r in batch if not r.event.is_set()],
                    _Finished(error=RuntimeError("scheduled search aborted")),
                    None, ())
            # the batcher's waits since its last launch go with the window
            window.waits = tuple(self._waits)
            self._waits.clear()
            # to the completer, which publishes in launch order — a window
            # with nothing to collect (all shed, or dead above) too: it
            # holds its place in flight until its turn
            with self._cond:
                self._launched.append(window)
                self._cond.notify_all()

    def _next_batch(self) -> Optional[List[_Request]]:
        """Block until a flush trigger fires and a place in flight is free;
        pop and return one batch of compatible requests (FIFO from the
        head's group), counted in flight from here."""
        max_wait_s = self.cfg.max_wait_ms / 1000.0
        with self._cond:
            while True:
                if self._stopping:
                    return None
                if not self._queue:
                    # timed idle wait (blocking-under-lock): submit()
                    # notifies on every enqueue, so the timeout only
                    # bounds the window in which a lost/raced notify (or
                    # an interpreter bug) could strand the batcher — the
                    # loop re-checks the queue and stop flag each lap
                    with tracing.stage("sched.idle", sink=self.stats) as st:
                        self._cond.wait(timeout=1.0)
                    self._waited(st)
                    continue
                head = self._queue[0]
                rows = sum(r.rows for r in self._queue if r.key == head.key)
                # a window on its way to be collected keeps the chip busy:
                # waiting for followers then costs the chip nothing, and
                # the callers of the window before it are on their way
                # back. So while one is in flight the head waits, past
                # max_wait_ms, for as many rows as the newest of them
                # took: a window does not shrink because the loop no longer
                # gives followers a whole launch to queue up (two windows
                # of 64 rows cost a flat index twice one of 128). The
                # completer's notify ends the wait with the last collect;
                # max_wait_ms then applies as it always has. With no place
                # free the head waits whatever the queue holds.
                want = self.cfg.max_batch_rows
                if self._in_flight:
                    want = min(want, self._newest_rows)
                full = head.eager or rows >= want
                flush_at = head.enqueue_t + max_wait_s
                now = time.monotonic()
                if (self._in_flight >= self.IN_FLIGHT
                        or (not full and (self._in_flight or now < flush_at))):
                    timeout = 1.0 if self._in_flight else flush_at - now
                    with tracing.stage("sched.window_wait", sink=self.stats) as st:
                        self._cond.wait(timeout)
                    self._waited(st)
                    continue
                # pop whole compatible requests until the row budget is
                # reached; a single over-budget request still goes alone
                # (requests are never split)
                taken, taken_rows, rest = [], 0, []
                for r in self._queue:
                    if (r.key == head.key
                            and (taken_rows < self.cfg.max_batch_rows)):
                        taken.append(r)
                        taken_rows += r.rows
                    else:
                        rest.append(r)
                self._queue = rest
                self._in_flight += 1
                self._newest_rows = taken_rows
                self.stats.record("queue_depth", float(len(rest)))
                return taken

    def _waited(self, st: tracing.stage) -> None:
        """Keep a finished wait of the batcher (``sched.idle``,
        ``sched.window_wait``) for the next window's gap on the chip; a lap
        of the same wait right behind the last one extends it (an idle rank
        laps every second)."""
        waits, end = self._waits, st.t0 + st.dt
        if waits and waits[-1][2] == st.name:
            waits[-1] = (waits[-1][0], end, st.name)
        else:
            waits.append((st.t0, end, st.name))

    def _launch(self, batch: List[_Request]) -> _Window:
        """A window's first half, on the batcher thread: assemble and
        launch. An ``Exception`` becomes the window's error, published at
        its turn; anything else (a BaseException out of the engine) is the
        batcher loop's to catch, which sends the whole batch to be finished
        (``_finish`` publishes once)."""
        with tracing.stage("sched.assemble", sink=self.stats):
            now = time.monotonic()
            live: List[_Request] = []
            for r in batch:
                if r.deadline is not None and now >= r.deadline:
                    # shed without touching the device; the device batch
                    # only carries rows someone is still waiting for
                    self._counters.inc("shed_deadline")
                    r.error = DeadlineExpired(
                        "deadline expired while queued "
                        f"(waited {now - r.enqueue_t:.3f}s)")
                    self._finish(r)
                    continue
                live.append(r)
            if not live:
                return _Window((), _Finished(), None, ())
            window = self._counters.inc("batches")
            n_rows = sum(r.rows for r in live)
            self.stats.record("batch_requests", float(len(live)))
            self.stats.record("batch_rows", float(n_rows))
            p_now = tracing.now()
            for r in live:
                # the queue wait, and for a sampled request which merge
                # window it landed in and that window's occupancy — the
                # "why did my request wait / what did it share a launch
                # with" answer
                with tracing.bind(r.ticket):
                    tracing.book(
                        "server.queue", p_now - (now - r.enqueue_t),
                        sink=self.stats, counter="queue_wait_s",
                        window=window, occupancy_requests=len(live),
                        occupancy_rows=n_rows)
            head = live[0]
            try:
                qcat = head.q if len(live) == 1 else _concat_rows(live, n_rows)
            except Exception as exc:
                return _Window(live, _Finished(error=exc), None, ())
        # the whole window IS one device program: the engine's stages
        # nest under one representative sampled request, and every
        # other sampled request of the window gets the launch's span
        # echoed into its own trace
        traced = [r.ticket for r in live if r.ticket is not None]
        device = tracing.handover("server.device", sink=tracing.SPAN_ONLY,
                                  instants=True, window=window, rows=n_rows)
        try:
            # DFT_XFERCHECK=1 arms jax's transfer guard for the whole
            # merged-window launch (and again for its collect): any
            # implicit host<->device copy in the flush fails the
            # provoking request with provenance
            with tracing.bind(traced[0] if traced else None), device, \
                    xfercheck.guarded("scheduler merge-window flush"):
                handle = self._launch_fn(
                    head.index_id, qcat, head.k, head.return_embeddings)
        except Exception as exc:
            handle, device = _Finished(error=exc), None
        return _Window(live, handle, device, traced)

    def _completer_loop(self) -> None:
        """Collect, split and publish the launched windows, oldest first;
        ends when the batcher has and nothing is left in flight."""
        while True:
            with self._cond:
                while not self._launched:
                    if self._batcher_done:
                        return
                    self._cond.wait(timeout=1.0)
                window = self._launched.popleft()
            try:
                self._complete(window)
            except BaseException:  # the loop must survive any window
                logger.exception("scheduler batch failed")
            for r in window.live:
                self._finish(r)  # whoever _complete left unpublished

    def _complete(self, window: _Window) -> None:
        """A window's second half, on the completer thread: collect (the
        place in flight is free again as soon as that ends), split,
        publish."""
        live, device, result, error = window.live, window.device, None, None
        try:
            if device is None:
                result = window.handle.collect()
            else:
                with device.last():
                    with xfercheck.guarded("scheduler merge-window flush"):
                        result = window.handle.collect()
                    self._book_chip(window, tracing.now())
                for ticket in window.traced[1:]:
                    device.echo(ticket)
        except Exception as exc:
            error = exc
        finally:
            # not booked: the next good window's gap has them
            self._unbooked.extend(window.waits)
            with self._cond:
                self._in_flight -= 1
                self._cond.notify_all()
        if not live:
            return
        with tracing.stage("sched.split", sink=self.stats):
            if error is None:
                try:
                    if not isinstance(result, tuple):
                        result = (result,)
                    offsets, ofs = [], 0
                    for r in live:
                        offsets.append((ofs, ofs + r.rows))
                        ofs += r.rows
                    per_elem = [_split_rows(v, offsets) for v in result]
                    for i, r in enumerate(live):
                        r.result = tuple(elem[i] for elem in per_elem)
                except Exception as exc:
                    error = exc
            if error is not None:
                # one application error fails exactly the callers whose
                # rows shared the launch — never the rest of the queue.
                # Each caller gets its OWN exception object: submit()
                # re-raises from N threads concurrently, and raising one
                # shared instance races on its __traceback__ (interleaved
                # frames in error reports).
                for r in live:
                    try:
                        err = type(error)(*error.args)
                    except Exception:
                        err = RuntimeError(
                            f"scheduled search failed: {error!r}")
                    err.__cause__ = error
                    r.result, r.error = None, err
            for r in live:
                self._finish(r)

    def _book_chip(self, window: _Window, collected: float) -> None:
        """The chip's timeline, once a collected window, on the completer
        thread (inside the last leg of ``server.device``, whose span takes
        the numbers as fields): from ``free``, the largest ``ready`` of the
        windows before it, the chip was idle until this window's
        ``dispatched`` or busy with the window ahead beyond it, then busy
        with this one until its ``ready``. The idle gap is split by what
        the batcher thread was in: ``sched.idle`` (no request queued) is
        ``empty``, ``sched.window_wait`` is ``window_wait``, all else (the
        assemble, the engine's lock and feed, the dispatch, the python
        between them) ``host``. A window that died never comes here: it
        books nothing and leaves ``free`` where it was."""
        device, record = window.device, self.stats.record
        dispatched = device.instants.get("dispatched", device.t0)
        ready = device.instants.get("ready", collected)
        waits = self._unbooked + list(window.waits)
        self._unbooked.clear()
        window.waits = ()
        exemplar = window.traced[0][0] if window.traced else None
        with self._chip_lock:
            free = self._chip_free
            if free is None:  # the rank's first window: the timeline starts here
                self._chip_first = free = dispatched
            start = max(dispatched, free)
            ready = max(ready, start)  # (an index off the chip may end out of turn)
            if dispatched > free:
                idle, left = {}, dispatched - free
                for t0, t1, name in waits:
                    share = min(t1, dispatched) - max(t0, free)
                    if share > 0:
                        row = tracing.CHIP_IDLE_CAUSE[name]
                        idle[row] = idle.get(row, 0.0) + share
                        left -= share
                if left > 0:
                    idle[tracing.CHIP_IDLE_HOST] = left
                for name, seconds in idle.items():
                    record(name, seconds, exemplar=exemplar)
            record("sched.chip_busy", ready - start, exemplar=exemplar)
            record("sched.chip_queue", start - dispatched, exemplar=exemplar)
            self._chip_free = ready
        device.extra.update(chip_busy_s=ready - start,
                            chip_queue_s=start - dispatched,
                            idle_before_s=start - free)

    # ------------------------------------------------------------- lifecycle

    def stop(self) -> None:
        """Stop the batcher and fail everything still queued (callers see
        ``SchedulerStopped``; the windows in flight complete normally)."""
        with self._cond:
            self._stopping = True
            stranded, self._queue = self._queue, []
            self._cond.notify_all()
        for r in stranded:
            r.error = SchedulerStopped("scheduler stopped with request queued")
            self._finish(r)
        for thread in (self._thread, self._completer):
            thread.join(timeout=10.0)
            if thread.is_alive():  # pragma: no cover - launch wedged in device
                logger.warning("scheduler %s thread did not exit in 10s",
                               thread.name)

    # ---------------------------------------------------------- observability

    def perf_stats(self, raw: bool = False) -> dict:
        """{"counters": {...}, "queues": {metric: histogram summary}} —
        merged into the rank's get_perf_stats surface under "scheduler";
        ``raw`` adds the bucket histograms (the Prometheus exporter's
        view)."""
        with self._cond:
            # torn-free counter snapshot taken beside the queue-length
            # read (AtomicCounters._lock is a leaf: safe under _cond).
            # Increments happen lock-free on the fast paths, so the two
            # reads are adjacent, not a cross-field consistency guarantee.
            counters = self._counters.snapshot()
            counters["queued"] = len(self._queue)
        with self._chip_lock:
            queues = self.stats.summary(raw=raw)
            first, free = self._chip_first, self._chip_free
        if free is not None:
            # a cause that never took a gap reads 0, not a missing row; and
            # what busy and idle add up to: the last ``ready`` less the
            # first ``dispatched`` (queue lies inside another window's busy)
            for name in tracing.CHIP_ROWS:
                queues.setdefault(name, tracing.zero_row())
            counters["chip_timeline_s"] = free - first
        out = {"counters": counters, "queues": queues}
        if self.tag:
            out["replica"] = dict(self.tag)
        return out
