"""Cross-process request tracing: trace ids, sampling, the span ring.

One sampled request = one ``trace_id`` minted client-side
(``maybe_sample``) that rides the CALL frame's optional meta element
beside ``req_id``/``deadline_s`` (parallel/rpc.py), with the id of the
caller's span as ``parent``. Every stage that touches the request books
itself through ``utils/tracing.stage`` (the stage ledger,
docs/OPERATIONS.md#stage-ledger), which records a span — ``span_id``,
``parent``, name, start, duration — into its OWN process's bounded
``SpanBuffer``; nothing is pushed anywhere. The buffers are pulled lazily over the ordinary
``get_trace_spans`` RPC op (server.py) and merged client-side
(``IndexClient.get_trace_spans`` / the dfstat ``--trace`` view) into one
causal timeline.

Design constraints (the reason this module is this small):

- **byte-identical and near-zero-cost when off.** ``DFT_TRACE_SAMPLE``
  defaults to 0: ``maybe_sample`` returns None after one env read, no
  trace key enters any frame meta (legacy 3-tuple frames and pre-trace
  peers interop unchanged), and the one recording site
  (``tracing.stage``) is gated on the thread's context holding a trace
  — the serving path's frames stay
  byte-identical to the pre-trace wire (tested in
  tests/test_observability.py).
- **spans are plain dicts.** They cross the wire through the normal
  frame skeleton (restricted unpickler: containers + scalars only) and
  into JSON unmodified.
- **wall-clock starts, monotonic durations.** ``start_s`` is
  ``time.time()`` so spans from different processes land on one
  timeline — the clock the profiler's host events are stamped with
  too (tests/test_stage_ledger.py) — and ``dur_s`` is measured with a
  monotonic clock by the recorder. Cross-HOST skew shifts a rank's spans as a block — the
  within-rank causality (queue -> coalesce -> launch) is exact, which is
  what stage attribution needs.
"""

import os
import random
import threading
from collections import deque
from typing import Optional

from distributed_faiss_tpu.utils import envutil, lockdep

# the CALL-frame meta keys a sampled trace rides under (beside
# req_id/deadline_s): the request's id, and the id of the caller's span
# that the rank's spans hang under
TRACE_META_KEY = "trace_id"
PARENT_META_KEY = "parent"

DEFAULT_BUFFER = 2048

# sampling draws come from a private generator: tracing must never
# perturb the host process's global RNG stream (the same rule as the
# RPC retry jitter, parallel/rpc.py)
_sample_rng = random.Random()


def sample_rate() -> float:
    """DFT_TRACE_SAMPLE: fraction of requests that mint a trace (0 = off,
    1 = every request). Read per call so tests and operators can flip it
    on a live process; one dict lookup — the entire cost when off."""
    return envutil.env_float("DFT_TRACE_SAMPLE", 0.0)


def mint_trace_id() -> str:
    """16 hex chars of OS entropy — collision-safe across processes
    without coordination (no counter to sync, nothing to seed)."""
    return os.urandom(8).hex()


def maybe_sample() -> Optional[str]:
    """A fresh trace_id for this request iff it is sampled, else None."""
    rate = sample_rate()
    if rate <= 0.0:
        return None
    if rate >= 1.0 or _sample_rng.random() < rate:
        return mint_trace_id()
    return None


class SpanBuffer:
    """Bounded per-process ring of trace spans.

    ``record`` appends a span dict; the deque's maxlen evicts the oldest
    once ``capacity`` (``DFT_TRACE_BUFFER``) is reached — tracing is a
    diagnosis loop, not an archive, so memory stays bounded no matter
    the sample rate. ``snapshot`` is the read side (the
    ``get_trace_spans`` RPC op and dfstat's ``--trace`` merge).
    """

    def __init__(self, capacity: Optional[int] = None, rank=None):
        if capacity is None:
            capacity = envutil.env_int("DFT_TRACE_BUFFER", DEFAULT_BUFFER)
        self.capacity = max(int(capacity), 1)
        self.rank = rank
        self._lock = lockdep.lock("SpanBuffer._lock")
        self._spans = deque(maxlen=self.capacity)
        self._counters = {"recorded": 0, "evicted": 0}

    def record(self, trace_id: str, name: str, start_s: float, dur_s: float,
               span_id: Optional[str] = None, parent: Optional[str] = None,
               **extra) -> None:
        """Append one span. ``start_s`` is wall-clock (time.time());
        ``dur_s`` a monotonic-clock duration; ``span_id`` names this span
        and ``parent`` the span that caused it (None: the request's root).
        ``extra`` must stay wire-safe (scalars/containers — it rides the
        frame skeleton)."""
        span = {
            "trace_id": trace_id,
            "span_id": span_id,
            "parent": parent,
            "name": name,
            "start_s": float(start_s),
            "dur_s": float(dur_s),
        }
        if self.rank is not None:
            span["rank"] = self.rank
        if extra:
            span["extra"] = extra
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self._counters["evicted"] += 1
            self._spans.append(span)
            self._counters["recorded"] += 1

    def snapshot(self, trace_id: Optional[str] = None) -> list:
        """Spans in recording order; ``trace_id`` filters to one trace."""
        with self._lock:
            spans = list(self._spans)
        if trace_id is None:
            return spans
        return [s for s in spans if s["trace_id"] == trace_id]

    def stats(self) -> dict:
        with self._lock:
            return {"capacity": self.capacity, "size": len(self._spans),
                    **self._counters}

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()


# ------------------------------------------------------- process-local buffer
#
# Client-side spans (stub round trips, fan-out/failover hops) have no
# IndexServer to own a buffer, so they land in one lazily-created
# process-local ring, merged into timelines by
# ``IndexClient.get_trace_spans``. Server ranks own their buffer
# explicitly (``IndexServer.spans``) — in a loopback test process both
# exist side by side and the merge dedupes.

_local_mu = threading.Lock()
_local: Optional[SpanBuffer] = None


def local_buffer() -> SpanBuffer:
    global _local
    with _local_mu:
        if _local is None:
            _local = SpanBuffer()
        return _local


def merge_timelines(*span_lists) -> list:
    """Merge per-process span lists into one timeline: dedupe exact
    duplicates (a loopback process fetching its own buffer sees each
    span twice — once locally, once over the RPC) and sort by start
    time, ties broken by duration descending so enclosing spans print
    before their children."""
    seen = set()
    merged = []
    for spans in span_lists:
        for s in spans or ():
            key = (s.get("trace_id"), s.get("span_id"), s.get("name"),
                   s.get("rank"), s.get("start_s"), s.get("dur_s"))
            if key in seen:
                continue
            seen.add(key)
            merged.append(s)
    merged.sort(key=lambda s: (s.get("start_s", 0.0), -s.get("dur_s", 0.0)))
    return merged
