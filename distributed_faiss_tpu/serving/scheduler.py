"""Deadline-aware micro-batching scheduler for the serving path.

One ``SearchScheduler`` per server rank. Connection threads call
``submit`` (blocking) or — for multiplexed RPC, where the connection
reader must keep pulling frames — ``submit_async`` with a completion
callback; a single named batcher thread drains the queue, coalesces
compatible requests — same ``(index_id, top_k, return_embeddings,
dim)`` — into one concatenated device batch, runs the engine's batched
search entry once, and hands every caller its row slice. Two flush triggers: the pending compatible rows reach
``max_batch_rows``, or the oldest queued request has waited
``max_wait_ms``.

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
step routes through ``TpuIndex.search_batched`` — for a rank that owns a
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

The batcher thread keeps the launch loop's stage ledger
(``utils/tracing.stage``, docs/OPERATIONS.md#stage-ledger): every second
between two window ends is booked to exactly one of ``sched.idle`` (queue
empty), ``sched.window_wait`` (a head request waits for followers),
``sched.assemble`` (deadline shed, concat), the engine's stages (inside
``server.device``, the engine call: a span and a profiler event, no
counter) and ``sched.split`` (row split and completion callbacks), so the
stages' totals add up to the thread's wall clock over windows that
succeed (a stage that raises books nothing). A sampled request (its submitter's context held a trace,
``tracing.ticket``) additionally gets ``server.queue`` (wait + which
merge window it landed in and its occupancy) and ``server.device`` spans
in its submitter's SpanBuffer, and stamps the latency histograms'
exemplars (observability/spans.py).
"""

import logging
import threading
import time
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


class SearchScheduler:
    """Bounded queue + batcher thread coalescing concurrent searches.

    ``search_fn(index_id, query_batch, top_k, return_embeddings)`` is the
    engine's already-batched entry (engine.Index.search_batched on a
    server); it must return a tuple whose ndarray/list elements have one
    leading row per query row.
    """

    def __init__(self, search_fn: Callable, cfg: Optional[SchedulerCfg] = None,
                 name: str = "search-batcher", tag: Optional[dict] = None):
        self._search_fn = search_fn
        self.cfg = cfg if cfg is not None else SchedulerCfg()
        # replica identity riding the stats surface (replication layer):
        # admission behavior is unchanged per replica, but operators need
        # queue/shed numbers attributable to (rank, shard_group). Owned by
        # the server, which updates shard_group on (re-)registration.
        self.tag = dict(tag or {})
        self._cond = lockdep.condition("SearchScheduler._cond")
        self._queue: List[_Request] = []
        self._stopping = False
        self.stats = LatencyStats()
        # admission/flush counters ride the shared atomic-counter helper
        # (utils/atomics.py): the fast paths bump them without contending
        # the flush condition, and stats readers get a torn-free snapshot
        self._counters = AtomicCounters(
            ("submitted", "batches", "shed_deadline", "rejected_busy"))
        self._thread = threading.Thread(
            target=self._batcher_loop, name=name, daemon=True)
        self._thread.start()

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
                self._serve(batch)
            except BaseException:  # the loop must survive any launch failure
                logger.exception("scheduler batch failed")
                for r in batch:
                    self._finish(r)

    def _next_batch(self) -> Optional[List[_Request]]:
        """Block until a flush trigger fires; pop and return one batch of
        compatible requests (FIFO from the head's group)."""
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
                    with tracing.stage("sched.idle", sink=self.stats):
                        self._cond.wait(timeout=1.0)
                    continue
                head = self._queue[0]
                rows = sum(r.rows for r in self._queue if r.key == head.key)
                flush_at = head.enqueue_t + max_wait_s
                now = time.monotonic()
                if (not head.eager and rows < self.cfg.max_batch_rows
                        and now < flush_at):
                    with tracing.stage("sched.window_wait", sink=self.stats):
                        self._cond.wait(flush_at - now)
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
                self.stats.record("queue_depth", float(len(rest)))
                return taken

    def _serve(self, batch: List[_Request]) -> None:
        """One window: assemble, launch, split. An ``Exception`` fails the
        window's callers here; anything else (a BaseException out of the
        engine or the split) is the batcher loop's to catch, which
        finishes every request of the batch (``_finish`` publishes once)."""
        error = None
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
                return
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
                error = exc
        result = None
        if error is None:
            # the whole window IS one device program: the engine's stages
            # nest under one representative sampled request, and every
            # other sampled request of the window gets the launch's span
            # echoed into its own trace
            traced = [r.ticket for r in live if r.ticket is not None]
            try:
                with tracing.bind(traced[0] if traced else None), \
                        tracing.stage("server.device", sink=tracing.SPAN_ONLY,
                                      window=window, rows=n_rows) as launch:
                    # DFT_XFERCHECK=1 arms jax's transfer guard for the
                    # whole merged-window launch: any implicit
                    # host<->device copy in the flush fails the provoking
                    # request with provenance
                    with xfercheck.guarded("scheduler merge-window flush"):
                        result = self._search_fn(
                            head.index_id, qcat, head.k,
                            head.return_embeddings)
                for ticket in traced[1:]:
                    launch.echo(ticket)
            except Exception as exc:
                error = exc
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

    # ------------------------------------------------------------- lifecycle

    def stop(self) -> None:
        """Stop the batcher and fail everything still queued (callers see
        ``SchedulerStopped``; in-flight launches complete normally)."""
        with self._cond:
            self._stopping = True
            stranded, self._queue = self._queue, []
            self._cond.notify_all()
        for r in stranded:
            r.error = SchedulerStopped("scheduler stopped with request queued")
            self._finish(r)
        self._thread.join(timeout=10.0)
        if self._thread.is_alive():  # pragma: no cover - launch wedged in device
            logger.warning("scheduler batcher thread did not exit in 10s")

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
        out = {"counters": counters, "queues": self.stats.summary(raw=raw)}
        if self.tag:
            out["replica"] = dict(self.tag)
        return out
