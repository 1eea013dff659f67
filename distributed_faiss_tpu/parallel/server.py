"""Index server runtime: one process = one shard rank, many named indexes.

Behavioral parity with the reference's ``IndexServer``
(distributed_faiss/server.py:36-404): multi-index registry guarded by a
lock, storage path convention ``{storage_dir}/{index_id}/{rank}/``, RPC
surface (create/add/search/train/state/save/load/drop/ntotal/ids/centroids/
nprobe/config-path/stop), and two serving modes — a thread-per-connection
blocking accept loop and a selector-based single-thread loop (the
reference's selector mode is broken and its test skipped,
tests/test_rpc.py:66; ours works and is tested).

Conscious fixes vs the reference:
- ``async_train`` actually starts the thread (the reference constructs a
  Thread subclass but calls ``t.run()`` synchronously, server.py:308-318);
- ``set_omp_num_threads`` exists server-side (the reference's client calls
  a method the server never defined, client.py:338-339) — here it sets the
  host-side intra-op hint and is otherwise a no-op, since XLA owns device
  parallelism.
"""

import logging
import os
import pathlib
import selectors
import socket
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

import numpy as np

from distributed_faiss_tpu.engine import Index
from distributed_faiss_tpu.observability import export as obs_export
from distributed_faiss_tpu.observability import profile as obs_profile
from distributed_faiss_tpu.observability import spans as obs_spans
from distributed_faiss_tpu.parallel import antientropy, rpc, wire
from distributed_faiss_tpu.serving.scheduler import (
    DeadlineExpired,
    SchedulerBusy,
    SchedulerStopped,
    SearchScheduler,
)
from distributed_faiss_tpu.utils import envutil, lockdep, tracing
from distributed_faiss_tpu.utils.config import (
    AntiEntropyCfg,
    IndexCfg,
    SchedulerCfg,
    TracingCfg,
    WireCfg,
)
from distributed_faiss_tpu.utils.state import IndexState
from distributed_faiss_tpu.utils.tracing import LatencyStats

logger = logging.getLogger()


def rpc_worker_count() -> int:
    """Size of the per-server worker pool that runs mux-dispatched non-search
    ops and writes scheduler completions back to their connections.
    DFT_RPC_WORKERS overrides; the default is small — search (the hot path)
    never occupies a worker for its compute, only for its response write."""
    raw = envutil.env_int("DFT_RPC_WORKERS")
    if raw:
        return max(1, raw)
    return min(8, max(2, os.cpu_count() or 4))


def setup_server_logging(level=logging.INFO) -> None:
    """Thread-aware root-logger format (parity with the reference's server
    bootstrap, server.py:28-35: '[thread] time [level] ...' — the ops story
    is verbose logs, README.md:59-61)."""
    logging.basicConfig(
        level=level,
        format="[%(threadName)s] %(asctime)s [%(levelname)s] %(message)s",
        force=True,
    )


def device_report() -> dict:
    """Where this process runs, as jax reports it: platform, device kind
    and count, and per local device its id, coordinates (TPU) and the
    bytes currently allocated on it — how a client learns which chip a
    rank holds and how an index is spread over a rank's mesh. Creates the
    backend if nothing has yet, which is the point: a rank takes its chips
    when it starts, not at the first request."""
    import jax

    devices = jax.local_devices()

    def one(dev):
        stats = dev.memory_stats()  # None where the backend keeps none (CPU)
        return {"id": dev.id,
                "coords": list(getattr(dev, "coords", ())),
                "bytes_in_use": stats["bytes_in_use"] if stats else None}

    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
        # the chip restriction this rank was launched with (launcher.rank_env)
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "devices": [one(dev) for dev in devices],
    }


class _ConnState:
    """Per-connection serving state shared by both loops: the response
    write lock (mux responses are written by whichever thread completes
    the call) and the negotiated binary-wire capability. ``peer_wire``
    flips once the connection's client advertises binary-skeleton
    decoding (the ``wire`` CALL-meta key, or a binary frame itself) — a
    per-connection property that dies with the connection, exactly like
    the client-side half (rpc.Client._peer_wire)."""

    __slots__ = ("addr", "wlock", "peer_wire", "reader")

    def __init__(self, addr, wlock, reader=None):
        self.addr = addr
        self.wlock = wlock
        self.peer_wire = False
        # per-connection buffered frame reader (rpc.FrameReader): one
        # recv typically covers header + skeleton + plane headers, and
        # back-to-back pipelined CALL frames decode out of one recv.
        # None for throwaway per-call states, which fall back to the
        # unbuffered one-shot reader (over-reading there would DROP the
        # buffered bytes when the state dies).
        self.reader = reader


class _Call:
    """One CALL frame's clock and trace, handed from the thread that read
    it to the threads that finish it (batcher callback, RPC worker).

    ``t_frame``: the whole frame in hand (``FrameReader.frame_t``);
    ``t0``: decoded and about to be dispatched — where the per-op row
    (``search``) has always started; ``t_done``: the scheduler's callback
    fired. For a sampled request, ``root`` is the ticket its
    ``server.request`` span (``span_id``, booked last) is booked under —
    the parent that crossed the wire — and ``ticket`` the one that binds
    the workers: their spans hang under ``server.request``."""

    __slots__ = ("req_id", "fname", "sink", "t_frame", "t0", "t_done",
                 "root", "ticket", "span_id")

    def __init__(self, fname, t_frame, req_id, trace_id, parent, spans, perf):
        self.req_id = req_id
        self.fname = fname
        # the request's ledger is the search path's: any other op keeps
        # its per-op row, and its stages are spans only (rpc.stage_sink)
        self.sink = rpc.stage_sink(fname, perf)
        self.t_frame = t_frame
        self.t0 = self.t_done = None
        self.root = self.ticket = self.span_id = None
        if trace_id is not None:
            self.span_id = tracing.new_span_id()
            self.root = (trace_id, parent, spans)
            self.ticket = (trace_id, self.span_id, spans)


class _EngineSearch:
    """The scheduler's target: the engine's already-batched entry (the
    scheduler has coalesced the callers; engine.py ``search_batched`` skips
    the in-process natural batcher), in one call and in two halves —
    ``launch`` returns the handle the scheduler collects once the window
    ahead of this one is done (engine.py ``launch_batched``)."""

    def __init__(self, get_index):
        self._get_index = get_index

    def __call__(self, index_id: str, query_batch: np.ndarray, top_k: int,
                 return_embeddings: bool) -> Tuple:
        return self._get_index(index_id).search_batched(
            query_batch, top_k=top_k, return_embeddings=return_embeddings)

    def launch(self, index_id: str, query_batch: np.ndarray, top_k: int,
               return_embeddings: bool):
        return self._get_index(index_id).launch_batched(
            query_batch, top_k=top_k, return_embeddings=return_embeddings)


class IndexServer:
    def __init__(self, rank: int, index_storage_dir: str,
                 scheduler_cfg: Optional[SchedulerCfg] = None,
                 discovery_path: Optional[str] = None,
                 antientropy_cfg: Optional[AntiEntropyCfg] = None,
                 tracing_cfg: Optional[TracingCfg] = None,
                 wire_cfg: Optional[WireCfg] = None):
        self.indexes: Dict[str, Index] = {}
        self.indexes_lock = lockdep.lock("IndexServer.indexes_lock")
        # index-level drop tombstones: ids this rank has dropped, so the
        # anti-entropy sweeper never full-syncs a dropped index back from
        # a peer that missed the drop broadcast (per-id deletes ride the
        # TombstoneSet ledger; drops need their own marker). Cleared by an
        # explicit re-create/load/resync. In-memory only: a restart that
        # reloads the index from disk resurrects it regardless of the
        # sweeper, which is a persistence question, not an anti-entropy
        # one (drop_index leaves storage in place by design).
        self._dropped: set = set()
        self._v6 = False
        self.rank = rank
        self.index_storage_dir = index_storage_dir
        self.socket: Optional[socket.socket] = None
        self._stopping = threading.Event()
        self.perf = LatencyStats()  # per-RPC latency counters (SURVEY §5.1)
        # a rank's stages are profiler events too (utils/tracing.stage)
        tracing.annotate_stages()
        # background work (async training) runs on named, tracked threads so
        # stop() can wait for them instead of orphaning device work
        self._threads_lock = lockdep.lock("IndexServer._threads_lock")
        self._train_threads: List[threading.Thread] = []
        # serving scheduler: both serving loops hand `search` RPCs to its
        # bounded queue + batcher thread (serving/scheduler.py); every other
        # op keeps the direct dispatch path. DFT_SCHEDULER=0 (or an explicit
        # cfg with enabled=False) restores pre-scheduler direct serving.
        # replica-group membership (parallel/replication.py): which logical
        # shard group this rank serves. None until registered — the client
        # derives a default from discovery order and pushes it via the
        # set_shard_group op; DFT_SHARD_GROUP pins it at launch (a rank
        # rejoining a known group after restart).
        self.shard_group: Optional[int] = envutil.env_int("DFT_SHARD_GROUP")
        # distributed tracing (observability/): this rank's bounded span
        # ring — every serving stage of a sampled request records into
        # it; the get_trace_spans op is its read side. The optional
        # Prometheus listener (DFT_METRICS_PORT) starts with the serving
        # socket (_bind) and stops in stop().
        self.tracing_cfg = (tracing_cfg if tracing_cfg is not None
                            else TracingCfg.from_env())
        self.spans = obs_spans.SpanBuffer(
            capacity=self.tracing_cfg.buffer, rank=rank)
        self._metrics: Optional[obs_export.MetricsExporter] = None
        cfg = scheduler_cfg if scheduler_cfg is not None else SchedulerCfg.from_env()
        self.scheduler: Optional[SearchScheduler] = None
        if cfg.enabled:
            self.scheduler = SearchScheduler(
                _EngineSearch(self._get_index), cfg,
                name=f"search-batcher:r{rank}",
                tag={"rank": rank, "shard_group": self.shard_group})
        # request multiplexing: calls whose frame meta carries a req_id are
        # dispatched without blocking the connection's reader (search → the
        # scheduler's async completion path, everything else → this worker
        # pool) and answered with req_id-tagged frames under a
        # per-connection write lock — many calls in flight per connection,
        # out-of-order completion. Legacy (no-req_id) frames keep the
        # synchronous in-order path.
        # binary wire (parallel/wire.py): search-family responses to a
        # connection whose client advertised binary decoding go out with
        # binary skeletons instead of pickle. DFT_RPC_WIRE=pickle keeps
        # every response byte-identical to the pre-wire protocol.
        self._wire_enabled = (
            (wire_cfg if wire_cfg is not None else WireCfg.from_env())
            .encoding == "binary")
        self._rpc_worker_count = rpc_worker_count()
        self._rpc_workers = ThreadPoolExecutor(
            max_workers=self._rpc_worker_count,
            thread_name_prefix=f"rpc-worker:r{rank}")
        self._mux_lock = lockdep.lock("IndexServer._mux_lock")
        self._mux_inflight = 0
        self._mux_counters = {"mux_calls": 0, "legacy_calls": 0}
        # server-side anti-entropy (parallel/antientropy.py): a named,
        # tracked sweeper thread exchanging replica digests with this
        # rank's group peers, healing divergence by pulling, doubling as
        # the failure detector behind get_health, and holding the
        # per-group compaction lease. It needs the discovery file to
        # resolve peers, so ranks constructed without one (most unit
        # tests, standalone engines) stay inert; the thread starts once
        # the serving socket is bound (either loop) so the sweeper can
        # recognize its own discovery entry by port.
        self.discovery_path = discovery_path
        self._antientropy_cfg = (antientropy_cfg if antientropy_cfg is not None
                                 else AntiEntropyCfg.from_env())
        self._antientropy: Optional[antientropy.AntiEntropySweeper] = None

    # ------------------------------------------------------------ RPC surface

    def create_index(self, index_id: str, cfg: IndexCfg) -> bool:
        # the common duplicate case (every client broadcasts create on
        # setup) must not construct an Index at all — a construction
        # spawns save/compaction watcher threads just to retire them
        with self.indexes_lock:
            if index_id in self.indexes:
                return False
        index_storage_dir = self._get_storage_dir(index_id, cfg)
        cfg.index_storage_dir = index_storage_dir
        pathlib.Path(index_storage_dir).mkdir(parents=True, exist_ok=True)
        index = Index(cfg)
        self._wire_engine(index)
        with self.indexes_lock:
            if index_id not in self.indexes:
                self.indexes[index_id] = index
                self._dropped.discard(index_id)
                logger.info("created index %s (storage %s)", index_id, index_storage_dir)
                return True
        index.retire()  # lost the race: never let its watcher autosave
        return False

    def add_index_data(
        self,
        index_id: str,
        embeddings: np.ndarray,
        metadata=None,
        train_async_if_triggered: bool = True,
        version=None,
    ) -> None:
        self._get_index(index_id).add_batch(
            embeddings, metadata, train_async_if_triggered, version=version)

    def search(self, index_id: str, query_batch: np.ndarray, top_k: int,
               return_embeddings: bool = False, min_version=None) -> Tuple:
        index = self._get_index(index_id)
        if min_version is not None:
            # read-your-writes gate: reject BEFORE the device if this
            # replica has not incorporated the demanded version (the
            # structured rejection is group-failover-eligible client-side)
            index.assert_min_version(min_version)
        return index.search(
            query_batch, top_k=top_k, return_embeddings=return_embeddings
        )

    # ------------------------------------------------------------- mutation

    def remove_ids(self, index_id: str, ids, version=None) -> int:
        """Tombstone rows by metadata id (mutation subsystem): masked on
        device immediately, persisted to the sidecar before the ack —
        a crash after this returns can never resurrect the rows. One of
        the new wire ops; like every op it rides both serving loops
        (mux worker-pool dispatch and the legacy sync path). ``version``
        (an HLC stamp from the client) makes the delete LWW-gated and
        replay-idempotent — engine.remove_ids."""
        return self._get_index(index_id).remove_ids(ids, version=version)

    def upsert(self, index_id: str, ids, embeddings, metadata=None,
               version=None) -> int:
        """Delete + add under one op: the ids' live rows stop serving
        before the ack; replacements ingest through the normal buffered
        add path (visible when their chunk drains, like any add)."""
        return self._get_index(index_id).upsert(ids, embeddings, metadata,
                                                version=version)

    def compact_index(self, index_id: str) -> bool:
        """Operator-triggered compaction pass (the background watcher
        normally drives this once the tombstone fraction crosses
        DFT_COMPACT_THRESHOLD)."""
        return self._get_index(index_id).compact()

    def sync_train(self, index_id: str) -> None:
        self._get_index(index_id).train()

    def async_train(self, index_id: str) -> None:
        # a named, tracked thread (not _thread.start_new_thread, which is
        # invisible to shutdown): stop() joins whatever is still training
        index = self._get_index(index_id)
        t = threading.Thread(
            target=index.train, name=f"train:{index_id}:r{self.rank}",
            daemon=True)
        with self._threads_lock:
            # prune only threads that have RUN and finished (ident set, not
            # alive); and start inside the lock, so a concurrent stop() can
            # never snapshot — and try to join — a not-yet-started thread
            self._train_threads = [
                x for x in self._train_threads
                if x.ident is None or x.is_alive()]
            self._train_threads.append(t)
            # graftlint: ok(blocking-under-lock): Thread.start() is not IndexServer.start (name-based launch propagation); starting inside the lock is load-bearing — a concurrent stop() must never snapshot (and join) a not-yet-started thread
            t.start()

    def get_state(self, index_id: str) -> IndexState:
        return self._get_index(index_id).get_state()

    def get_ntotal(self, index_id: str) -> int:
        with self.indexes_lock:
            if index_id not in self.indexes:
                return 0
            index = self.indexes[index_id]
        return index.get_idx_data_num()[1]

    def get_aggregated_ntotal(self, index_id: str) -> int:
        """Buffer depth, i.e. not-yet-indexed vectors (reference
        server.py:268-272 returns the buffer size under this name).
        Missing index -> 0, matching get_ntotal's degradation so
        monitoring can poll both through drop/recreate windows."""
        with self.indexes_lock:
            if index_id not in self.indexes:
                return 0
            index = self.indexes[index_id]
        return index.get_idx_data_num()[0]

    def save_index(self, index_id: str) -> None:
        self._get_index(index_id).save()

    def load_index(self, index_id: str = "default", cfg: IndexCfg = None) -> bool:
        index_dir = self._get_storage_dir(index_id, cfg)
        if cfg:
            cfg.index_storage_dir = index_dir
        with self.indexes_lock:
            if index_id in self.indexes:
                if cfg:
                    self.indexes[index_id].upd_cfg(cfg)
                return True
        index = Index.from_storage_dir(index_dir, cfg, ignore_buffer=False)
        if index is None:
            return False
        self._wire_engine(index)
        with self.indexes_lock:
            self.indexes[index_id] = index
            self._dropped.discard(index_id)
        return True

    def drop_index(self, index_id: str) -> None:
        with self.indexes_lock:
            old = self.indexes.pop(index_id, None)
            # marked even when this rank never served the id: the drop
            # broadcast may reach a rank before the index ever synced to
            # it, and the marker is what stops the sweeper from pulling
            # the dropped index back from a peer that missed the drop
            self._dropped.add(index_id)
        if old is not None:
            # stop the dropped engine's save watcher: a late autosave
            # would resurrect the index on disk after the drop
            old.retire()

    def get_ids(self, index_id: str = "default") -> set:
        return self._get_index(index_id).get_ids()

    def get_centroids(self, index_id: str):
        return self._get_index(index_id).get_centroids()

    def set_nprobe(self, index_id: str, nprobe: int) -> None:
        return self._get_index(index_id).set_nprobe(nprobe)

    def add_buffer_to_index(self, index_id: str) -> None:
        return self._get_index(index_id).add_buffer_to_index()

    def get_rank(self) -> int:
        return self.rank

    # ------------------------------------------------------- replica membership

    def get_shard_group(self) -> Optional[int]:
        """Logical shard group this rank serves (None = unregistered)."""
        return self.shard_group

    def set_shard_group(self, group: Optional[int]) -> Optional[int]:
        """The per-rank registration op: the client (or an operator)
        assigns this rank's replica group. Tagged into the scheduler's
        perf stats so per-replica admission numbers are attributable."""
        # graftlint: atomic(shard_group): registration publish — one reference write; readers (digest answers, perf tags, fan-out planning) tolerate the pre-registration None or a one-sweep-stale group
        self.shard_group = None if group is None else int(group)
        if self.scheduler is not None:
            self.scheduler.tag["shard_group"] = self.shard_group
        logger.info("rank %d registered shard_group=%s",
                    self.rank, self.shard_group)
        return self.shard_group

    def sync_shard_from(self, index_id: str, host: str, port: int,
                        shard_group: Optional[int] = None) -> dict:
        """Online join: stream a live replica's shard and serve it.

        Dials ``host:port`` (a live replica of the target group), fetches
        its atomic export over a dedicated transfer connection
        (rpc.Client.fetch_shard -> KIND_SHARD_FETCH/KIND_SHARD_DATA),
        commits the snapshot into THIS rank's storage dir as a
        manifest-committed generation, installs the restored engine
        (replacing any stale local index), replays the buffer delta via
        the normal async add path, and registers the shard group. The
        serving loops keep answering other RPCs throughout — the only
        exclusive section is the registry swap."""
        src = rpc.Client(-1, host, port, connect_timeout=10.0, mux=False)
        try:
            snapshot = src.fetch_shard(index_id)
        finally:
            src.close()
        index = Index.import_snapshot(
            snapshot, self._get_storage_dir(index_id, None))
        self._wire_engine(index)
        with self.indexes_lock:
            old = self.indexes.get(index_id)
            self.indexes[index_id] = index
            self._dropped.discard(index_id)
        if old is not None:
            # the storage dir now belongs to the transferred shard: the
            # superseded engine must never autosave its stale state over
            # it as a newer generation
            old.retire()
        if shard_group is not None:
            self.set_shard_group(shard_group)
        buffered, ntotal = index.get_idx_data_num()
        logger.info(
            "rank %d joined via shard transfer from %s:%d (%s: %d rows, "
            "%d buffered)", self.rank, host, port, index_id, ntotal, buffered)
        return {"rank": self.rank, "index_id": index_id, "ntotal": ntotal,
                "buffered": buffered, "generation": index._generation,
                "shard_group": self.shard_group}

    # ---------------------------------------------------------- anti-entropy

    def _wire_engine(self, index: Index) -> None:
        """Install the compaction-lease gate on an engine entering the
        registry (the sweeper re-asserts every sweep, so engines that
        predate the sweeper converge too)."""
        if self._antientropy is not None:
            index.compaction_gate = self._antientropy.may_compact

    def _start_antientropy(self) -> None:
        """Start the sweeper once the serving socket is bound. Inert
        without a discovery file (nothing to resolve peers from) or with
        DFT_ANTIENTROPY=0."""
        if (self._antientropy is not None or self.discovery_path is None
                or not self._antientropy_cfg.enabled):
            return
        # graftlint: atomic(_antientropy): publish-once — assigned after the serving socket binds but before the accept loop admits any connection, so worker-pool readers only ever observe the final reference (stop() never nulls it)
        self._antientropy = antientropy.AntiEntropySweeper(
            self, self.discovery_path, self._antientropy_cfg)
        with self.indexes_lock:
            engines = list(self.indexes.values())
        for index in engines:
            self._wire_engine(index)
        self._antientropy.start()
        logger.info("anti-entropy sweeper started (rank %d, group %s, "
                    "interval %.1fs)", self.rank, self.shard_group,
                    self._antientropy_cfg.interval_s)

    def get_health(self) -> dict:
        """Failure-detector surface: this rank's view of its peers —
        suspect marks, per-peer failure counts, and the compaction-lease
        holder. Clients consult it to pre-skip suspect replicas in the
        read-failover walk (IndexClient.refresh_health); a suspect mark
        never REMOVES a replica from rotation — suspect peers are tried
        last, and still serve direct reads."""
        if self._antientropy is None:
            return {"enabled": False, "rank": self.rank,
                    "shard_group": self.shard_group, "peers": {},
                    "suspects": [], "compaction": {"held": True}}
        return self._antientropy.health_snapshot()

    def get_id_sets(self, index_id: str) -> dict:
        """Anti-entropy delta protocol: this shard's normalized live-id
        set and deletion ledger (engine.id_sets), with the per-id version
        planes and the shard watermark since ISSUE 12 (a pre-version
        caller just ignores the extra keys)."""
        return self._get_index(index_id).id_sets()

    def export_rows(self, index_id: str, ids) -> Tuple:
        """Anti-entropy delta protocol: (embeddings, metadata) for the
        requested live ids (engine.export_rows) — the pull side of a
        peer's delta repair. The pre-version 2-tuple wire shape."""
        return self._get_index(index_id).export_rows(ids)

    def export_rows_versioned(self, index_id: str, ids,
                              with_hash: bool = False) -> Tuple:
        """Versioned delta pull: (embeddings, metadata, versions) — the
        puller applies rows through the engine's LWW add gates. A
        separate op (not a changed return shape) so pre-version sweepers
        calling ``export_rows`` keep working unchanged. ``with_hash``
        (ISSUE 14) appends a per-chunk sha256 over the row payload as a
        4th element — the pulling sweeper verifies it before applying;
        default off keeps the PR-12 3-tuple wire shape."""
        return self._get_index(index_id).export_rows_versioned(
            ids, with_hash=with_hash)

    # --------------------------------------------------- generation-pinned reads

    def get_generation(self, index_id: str) -> int:
        """Newest committed snapshot generation of this rank's shard
        (0 = nothing committed) — what a client pins for point-in-time
        reads (IndexClient.pin_generations)."""
        return self._get_index(index_id).current_generation()

    def search_at_generation(self, index_id: str, query_batch: np.ndarray,
                             top_k: int, generation: int,
                             return_embeddings: bool = False) -> Tuple:
        """Point-in-time search against a retained committed generation
        (engine.search_at_generation). Deliberately NOT routed through
        the serving scheduler: pinned reads are a cold consistency path
        and must not share jit buckets or merge windows with live
        traffic."""
        return self._get_index(index_id).search_at_generation(
            query_batch, top_k=top_k, generation=generation,
            return_embeddings=return_embeddings)

    def _serve_digest(self, conn: socket.socket, payload,
                      wlock: Optional[threading.Lock] = None) -> None:
        """Answer one KIND_DIGEST with this rank's per-index replica
        digests and lease state as a KIND_DIGEST_RESP frame (failures
        degrade to a structured KIND_ERROR). Runs on the worker pool —
        digest computation may hash O(rows) on a cache miss and must not
        occupy the selector loop's shared reader. The inbound contact is
        itself liveness evidence for the failure detector."""
        t0 = tracing.now()
        try:
            req = payload if isinstance(payload, dict) else {}
            if self._antientropy is not None:
                self._antientropy.health.note_inbound(
                    req.get("rank"), req.get("group"))
            want = req.get("want")
            with self.indexes_lock:
                snapshot = list(self.indexes.items())
            digests = {iid: idx.replica_digest() for iid, idx in snapshot
                       if want is None or iid in want}
            held = (self._antientropy.may_compact()
                    if self._antientropy is not None else True)
            # per-index newest incorporated version: the peer's sweeper
            # min-merges these across the whole group to prune deletion-
            # ledger version pairs every replica has passed (pre-prune
            # peers simply ignore the key)
            watermarks = {iid: idx.version_watermark()
                          for iid, idx in snapshot
                          if want is None or iid in want}
            resp = {
                "rank": self.rank,
                "shard_group": self.shard_group,
                "digests": digests,
                "watermarks": watermarks,
                "compaction": {"held": held},
            }
            parts = rpc.pack_frame(rpc.KIND_DIGEST_RESP, resp)
            tracing.book("server.digest_exchange", t0, sink=self.perf,
                         counter="digest_exchange")
        except Exception:
            tb = traceback.format_exc()
            logger.error("digest exchange failed: %s", tb)
            parts = rpc.pack_frame(rpc.KIND_ERROR, tb)
        try:
            if wlock is not None:
                with wlock:
                    rpc._send_parts(conn, parts)
            else:
                rpc._send_parts(conn, parts)
        except OSError as e:
            logger.info("digest response write failed (peer gone?): %s", e)

    def index_loaded(self, index_id: str) -> bool:
        with self.indexes_lock:
            return (
                index_id in self.indexes
                and self.indexes[index_id].get_state() == IndexState.TRAINED
            )

    def get_config_path(self, index_id: str) -> str:
        return os.path.join(self.index_storage_dir, index_id, str(self.rank), "cfg.json")

    def set_omp_num_threads(self, num_threads: int) -> None:
        # XLA owns device parallelism; keep the knob for host-side libs
        os.environ["OMP_NUM_THREADS"] = str(num_threads)

    def get_perf_stats(self, raw: bool = False) -> dict:
        """Per-RPC latency summary {method: {count, total_s, mean_s, max_s,
        p50_s, p95_s, p99_s}}; with the serving scheduler enabled, the
        ``"scheduler"`` key adds its queue/batch distributions (queue_wait_s,
        e2e_s, batch_requests, batch_rows, queue_depth) and admission
        counters (submitted, batches, shed_deadline, rejected_busy,
        queued) — see docs/OPERATIONS.md#serving-scheduler. The ``"rpc"``
        key carries the mux serving state (in-flight dispatches, mux vs
        legacy call counts, worker-pool size; IndexClient merges each
        stub's client-side view in under ``rpc.client``), and ``"engine"``
        the per-index device-launch latency distributions — wire, queue,
        and device time side by side. The stage ledger's rows
        (docs/OPERATIONS.md#stage-ledger) sit where their layer's rows
        are: ``server.*`` beside the per-op rows, ``sched.*`` under
        ``scheduler.queues``, ``engine.*`` under ``engine.<index_id>``;
        ``xla.compile`` counts this process's XLA compiles.

        ``raw=True`` threads the raw-histogram view through every
        LatencyStats block (bucket counts + trace exemplars) — the shape
        the Prometheus exporter renders ``_bucket`` series from and
        dfstat's shared ``delta`` rate math consumes. Rows whose bucket
        retained a sampled exemplar also carry ``p99_exemplar``: the
        trace_id to feed ``get_trace_spans`` when asking what made the
        p99 spike."""
        out = self.perf.summary(raw=raw)
        # XLA compiles in this process since it started (utils/tracing):
        # a timed window's compiles are count after less count before
        out["xla.compile"] = tracing.compile_row(raw=raw)
        if self.scheduler is not None:
            out["scheduler"] = self.scheduler.perf_stats(raw=raw)
        with self._mux_lock:
            out["rpc"] = {"in_flight": self._mux_inflight,
                          **self._mux_counters}
        out["rpc"]["workers"] = self._rpc_worker_count
        # negotiated wire encoding this rank is WILLING to speak (actual
        # use is per connection — a legacy peer stays on pickle)
        out["rpc"]["wire"] = "binary" if self._wire_enabled else "pickle"
        # replica identity: which logical shard group this rank serves —
        # the client merges its fan-out counters in under
        # ``replication.client`` (parallel/replication.py)
        out["replication"] = {"rank": self.rank,
                              "shard_group": self.shard_group}
        # anti-entropy observability: sweep/digest/repair counters,
        # suspect peers, and whether this rank holds its group's
        # compaction lease — docs/OPERATIONS.md#anti-entropy--health
        out["antientropy"] = (self._antientropy.stats()
                              if self._antientropy is not None
                              else {"enabled": False})
        with self.indexes_lock:
            snapshot = list(self.indexes.items())
        out["engine"] = {iid: idx.perf_stats(raw=raw) for iid, idx in snapshot}
        # mutation observability (mutation subsystem): per-index tombstone
        # counts, live fraction, compaction run/aborted/fallback counters,
        # and compaction latency — docs/OPERATIONS.md#mutable-corpora
        out["mutation"] = {iid: idx.mutation_stats() for iid, idx in snapshot}
        # tracing observability: span-ring occupancy/eviction and the
        # metrics listener's bound port (0 = off) —
        # docs/OPERATIONS.md#tracing--metrics-export. Snapshot the
        # listener ref: stop() nulls it concurrently with outage-time
        # stats calls, and this call degrading is exactly what the
        # degrade satellite exists to prevent.
        metrics = self._metrics
        out["tracing"] = {
            **self.spans.stats(),
            "metrics_port": metrics.port if metrics else 0,
        }
        return out

    def get_trace_spans(self, trace_id: Optional[str] = None,
                        limit: int = 4096) -> List[dict]:
        """Read side of this rank's span ring (observability/spans.py):
        the spans recorded for ``trace_id`` (or every retained span when
        None), newest-last, capped at ``limit``. An ordinary RPC op — no
        new frame kinds, so legacy peers simply never call it."""
        spans = self.spans.snapshot(trace_id)
        return spans[-int(limit):] if limit else spans

    def profile(self, seconds: float = 5.0, keep: bool = False) -> dict:
        """Profile THIS rank for ``seconds`` and reduce its own trace
        (observability/profile.py): device busy and idle, idle seconds by
        the host stage open meanwhile, device seconds by named scope. An
        ordinary op (mux calls run it on the RPC worker pool; it holds one
        worker for the session); a plain application error while another
        session is open in the process. ``keep`` leaves the ``.xplane.pb``
        under this rank's storage directory and names it in the reply."""
        keep_dir = None
        if keep:
            keep_dir = os.path.join(
                self.index_storage_dir, f"profile_r{self.rank}",
                time.strftime("%Y%m%d_%H%M%S"))
        return obs_profile.profile(seconds, keep_dir)

    def ping(self) -> dict:
        """Liveness/health probe (the reference has no failure detection
        beyond startup backoff, SURVEY §5.3). get_state() runs outside
        indexes_lock so a long device call on one index can't stall the
        registry (and with it every other RPC).

        ``kernels`` surfaces runtime kernel demotions (models/ivf.py
        pallas_guarded, _first_use_check): ``pallas_degraded`` lists the
        indexes whose pallas kernel fell back to XLA on this backend — an
        operator's cue to check the rank's logs before trusting its
        serving throughput.
        ``device`` is :func:`device_report`: the platform and chips this
        rank really runs on."""
        with self.indexes_lock:
            snapshot = list(self.indexes.items())
        states = {iid: idx.get_state().name for iid, idx in snapshot}
        degraded = []
        for iid, idx in snapshot:
            tpu_index = getattr(idx, "tpu_index", None)
            # None = the index chose the kernel itself (IVFPQIndex)
            if (getattr(tpu_index, "use_pallas", False) is not False
                    and not getattr(tpu_index, "_pallas_runtime_ok", True)):
                degraded.append(iid)
        return {
            "rank": self.rank,
            "indexes": states,
            "kernels": {"pallas_degraded": degraded},
            "device": device_report(),
        }

    def stop(self) -> None:
        logger.info("stopping server rank=%d", self.rank)
        self._stopping.set()
        # the metrics listener goes first: a scrape mid-shutdown would
        # walk get_perf_stats over engines being saved; its thread is
        # named, tracked, and joined inside MetricsExporter.stop()
        if self._metrics is not None:
            self._metrics.stop()
            # graftlint: atomic(_metrics): teardown null — outage-time stats calls snapshot the reference (get_perf_stats) by design, so they observe the listener or None, never a torn state
            self._metrics = None
        # stop the anti-entropy sweeper next: a sweep mid-heal would
        # race the shutdown saves for the engine locks, and its peer
        # dials are bounded so the join is too
        if self._antientropy is not None:
            self._antientropy.stop()
        if self.socket is not None:
            try:
                self.socket.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            self.socket.close()
            self.socket = None
        # stop admitting/serving scheduled searches before saving: queued
        # requests fail fast with a structured rejection instead of racing
        # the save for the index locks
        if self.scheduler is not None:
            self.scheduler.stop()
        # the scheduler's stop has already enqueued every stranded
        # request's "stopping" response write; shutdown(wait=False) lets
        # those drain on the worker threads without letting a dead peer's
        # blocked send wedge this stop()
        self._rpc_workers.shutdown(wait=False)
        # wait (bounded) for tracked async-training threads so a shutdown
        # can't orphan a half-trained index mid-save
        with self._threads_lock:
            train_threads = list(self._train_threads)
        for t in train_threads:
            t.join(timeout=30.0)
            if t.is_alive():
                logger.warning("training thread %s still running at stop; "
                               "its index will not be saved trained", t.name)
        with self.indexes_lock:
            indexes = list(self.indexes.values())
        for index in indexes:
            index.save()

    # ------------------------------------------------------------ serving loops

    def _bind(self, port: int, v6: bool) -> socket.socket:
        fam = socket.AF_INET6 if v6 else socket.AF_INET
        s = socket.socket(fam, socket.SOCK_STREAM)
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("", port))
        s.listen(16)
        # graftlint: atomic(socket): bound once before either serving loop accepts; stop()'s null runs during teardown, where the loops already treat accept()/select() OSErrors as the exit signal
        self.socket = s
        logger.info("server rank=%d device %s", self.rank, device_report())
        self._start_metrics()
        return s

    def _start_metrics(self) -> None:
        """Start the optional Prometheus listener once the serving socket
        binds (both loops call _bind). DFT_METRICS_PORT is a BASE port —
        rank r listens on base + r, so one knob covers a local multi-rank
        launch. A bind failure (port taken) degrades to a logged warning:
        metrics must never take serving down."""
        base = self.tracing_cfg.metrics_port
        if self._metrics is not None or base <= 0:
            return
        try:
            self._metrics = obs_export.MetricsExporter(
                lambda: self.get_perf_stats(raw=True),
                port=base + self.rank, rank=self.rank).start()
            logger.info("metrics listener rank=%d on :%d", self.rank,
                        self._metrics.port)
        # OverflowError: base + rank past 65535 (HTTPServer raises it,
        # not OSError) — a misconfigured metrics port must degrade to a
        # warning, never take the serving socket down with it
        except (OSError, OverflowError) as e:
            logger.warning("metrics listener for rank %d failed to bind "
                           "port %d: %s", self.rank, base + self.rank, e)

    def start_blocking(self, port: int = rpc.DEFAULT_PORT, v6: bool = False,
                       load_index: bool = False) -> None:
        """Thread-per-connection accept loop (reference server.py:95-135)."""
        if load_index:
            self.load_index()
        s = self._bind(port, v6)
        self._start_antientropy()
        logger.info("server rank=%d listening on :%d", self.rank, port)
        while not self._stopping.is_set():
            try:
                conn, addr = s.accept()
            except OSError:
                break
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            # bound zero-progress writes: mux responses ride a small
            # shared worker pool, so a stalled peer must cost one worker
            # at most SEND_TIMEOUT_S before its connection is dropped
            rpc.bound_send_timeout(conn)
            # per-connection reader: named so stack dumps attribute to a
            # peer, daemon + deliberately unjoined — its lifetime IS the
            # connection's (it exits when the peer closes or the socket
            # dies), and joining here would hold stop() hostage to every
            # still-connected remote peer
            # graftlint: ok(thread-lifecycle): per-connection reader — lifetime is the connection's; a join path would hostage stop() to remote peers
            t = threading.Thread(
                target=self._serve_connection, args=(conn, addr),
                name=f"conn:r{self.rank}:{addr[0]}:{addr[1]}", daemon=True)
            t.start()

    def _serve_connection(self, conn: socket.socket, addr) -> None:
        # one write lock per connection: mux responses are written by
        # whichever thread completes the call (scheduler batcher via the
        # worker pool, or a worker running a direct op), so frame writes
        # must be serialized against each other and the sync path
        state = _ConnState(addr, lockdep.lock("IndexServer.conn_wlock"),
                           rpc.FrameReader(conn))
        try:
            while True:
                self._one_call(conn, state=state)
        except (rpc.ClientExit, EOFError):
            pass
        except OSError as e:
            logger.info("socket error from %s: %s", addr, e)
        except Exception as e:
            # malformed frame / undecodable payload: drop this connection
            # only — the server keeps serving everyone else
            logger.warning("dropping connection from %s: %s", addr, e)
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def _one_call(self, conn: socket.socket, eager_search: bool = False,
                  state: Optional[_ConnState] = None) -> None:
        if state is None:
            # direct callers (tests, single-shot tools): a throwaway
            # per-call state keeps every dispatch path uniform — the mux
            # response writers dereference state unconditionally
            state = _ConnState(None, lockdep.lock("IndexServer.conn_wlock"))
        # throwaway states read unbuffered (bufsize=0 never over-reads
        # past the frame; over-reading there would DROP the buffered bytes
        # when the state dies)
        reader = (state.reader if state.reader is not None
                  else rpc.FrameReader(conn, bufsize=0))
        kind, payload, was_binary = reader.recv_frame_ex()
        wlock = state.wlock
        if kind == rpc.KIND_CLOSE:
            raise rpc.ClientExit("client closed")
        if kind == rpc.KIND_SHARD_FETCH:
            # shard transfer rides its own dedicated connection (see
            # rpc.Client.fetch_shard), but the bulk export + send must
            # not occupy the reader — on the selector loop that thread
            # serves EVERY connection — so it runs on the worker pool,
            # serialized against any other writes by the connection's
            # write lock
            self._rpc_workers.submit(self._serve_shard_fetch, conn,
                                     payload, wlock)
            return
        if kind == rpc.KIND_DIGEST:
            # anti-entropy digest exchange: same worker-pool contract as
            # shard fetches — a cache-miss digest hashes O(rows) and the
            # selector loop's shared reader must never pay for it
            self._rpc_workers.submit(self._serve_digest, conn, payload,
                                     wlock)
            return
        if kind != rpc.KIND_CALL:
            raise RuntimeError(f"unexpected frame kind {kind}")
        # 3-tuple (legacy) or 4-tuple with frame meta carrying the caller's
        # remaining deadline budget (relative seconds — clock-skew-safe;
        # rebased onto this host's monotonic clock at decode), the sampled
        # trace_id (and parent span id) every serving stage attributes its
        # spans to, and, from mux clients, the req_id that pipelined
        # dispatch tags responses with
        fname, args, kwargs = payload[:3]
        frame_meta = payload[3] if len(payload) > 3 else None
        deadline = None
        if isinstance(frame_meta, dict):
            if frame_meta.get("deadline_s") is not None:
                deadline = time.monotonic() + float(frame_meta["deadline_s"])
            if was_binary or frame_meta.get("wire"):
                # the peer decodes binary skeletons (explicit advert, or
                # it just SENT one): search-family responses on this
                # connection may go out binary from here on
                state.peer_wire = True
        else:
            frame_meta = {}
        call = _Call(fname, reader.frame_t, frame_meta.get("req_id"),
                     frame_meta.get("trace_id"), frame_meta.get("parent"),
                     self.spans, self.perf)
        if call.req_id is None:
            with self._mux_lock:
                self._mux_counters["legacy_calls"] += 1
            self._call_sync(conn, call, args, kwargs, deadline, eager_search)
            return
        # mux dispatch: the reader never blocks on the call — the response
        # is written req_id-tagged under the connection's write lock by
        # whoever completes it, so calls complete out of order
        with self._mux_lock:
            self._mux_counters["mux_calls"] += 1
            self._mux_inflight += 1
        if fname == "search" and self.scheduler is not None:
            self._dispatch_scheduled(conn, state, call, args, kwargs, deadline)
        else:
            with tracing.bind(call.ticket):
                self._book_decode(call)
            try:
                self._rpc_workers.submit(
                    self._dispatch_direct, conn, state, call, args, kwargs)
            except RuntimeError:  # pool already shut down (server stopping)
                with self._mux_lock:
                    self._mux_inflight -= 1
                raise

    def _serve_shard_fetch(self, conn: socket.socket, payload,
                           wlock: Optional[threading.Lock] = None) -> None:
        """Answer one KIND_SHARD_FETCH with the engine's atomic export as
        a KIND_SHARD_DATA frame (failures degrade to a structured
        KIND_ERROR — the fetching peer raises ServerException instead of
        tearing the transfer connection down undiagnosed). Runs on the
        worker pool; a peer that vanished mid-transfer costs a logged
        OSError, never an unhandled worker exception."""
        t0 = tracing.now()
        try:
            (index_id,) = tuple(payload)[:1]
            snapshot = self._get_index(index_id).export_snapshot()
            parts = rpc.pack_frame(rpc.KIND_SHARD_DATA, snapshot)
            tracing.book("server.fetch_shard", t0, sink=self.perf,
                         counter="fetch_shard")
        except Exception:
            tb = traceback.format_exc()
            logger.error("shard fetch failed: %s", tb)
            parts = rpc.pack_frame(rpc.KIND_ERROR, tb)
        try:
            if wlock is not None:
                with wlock:
                    rpc._send_parts(conn, parts)
            else:
                rpc._send_parts(conn, parts)
        except OSError as e:
            logger.info("shard transfer write failed (peer gone?): %s", e)

    def _classify_scheduler_reject(self, error):
        """Map a scheduler admission/shed error to its structured BUSY
        response: ``(perf_name, payload)`` — or None for non-scheduler
        errors. The single source of truth for BOTH serving paths (legacy
        sync and mux), so their BUSY payloads can never diverge."""
        if isinstance(error, SchedulerBusy):
            return "search:busy", {
                "reason": "queue_full",
                "queue_depth": error.queue_depth,
                "max_queue": error.max_queue,
            }
        if isinstance(error, SchedulerStopped):
            return "search:busy", {"reason": "stopping"}
        if isinstance(error, DeadlineExpired):
            return "search:shed", {"reason": "deadline"}
        return None

    def _book_decode(self, call: _Call) -> None:
        """``server.decode`` ends and the per-op row starts: the frame is
        decoded and about to be dispatched (the caller has bound the
        call's trace)."""
        tracing.book("server.decode", call.t_frame, sink=call.sink,
                     fname=call.fname)
        call.t0 = tracing.now()

    def _book_op(self, call: _Call, name: str) -> None:
        """The per-op latency row (``search``, ``search:busy``, ...): from
        the decoded frame's dispatch to the op's completion."""
        tracing.book("server." + name, call.t0, sink=self.perf, counter=name)

    def _call_sync(self, conn, call: _Call, args, kwargs, deadline,
                   eager_search) -> None:
        """The legacy (no-req_id) path: serve the call on the reader thread
        and answer untagged, in order — an old client against a mux server
        works unchanged.

        The response write happens OUTSIDE the handler chain: a write
        failure (peer gone, or the SO_SNDTIMEO zero-progress bound firing
        mid-frame) may leave a partial frame on the stream, after which
        nothing further can be written safely — the OSError propagates and
        the serving loop drops the connection, instead of appending an
        ERROR frame to a torn stream."""
        fname = call.fname
        with tracing.bind(call.ticket):
            self._book_decode(call)
            try:
                fn = getattr(self, fname)
                if fname.startswith("_"):
                    raise AttributeError(fname)
                if fname == "search" and self.scheduler is not None:
                    # admission-controlled path: queue bound + deadline
                    # shedding
                    ret = self._scheduled_search(args, kwargs, deadline,
                                                 eager_search)
                else:
                    ret = fn(*args, **kwargs)
                self._book_op(call, fname)
                kind, payload = rpc.KIND_RESULT, ret
            except Exception as e:
                busy = self._classify_scheduler_reject(e)
                if busy is not None:
                    self._book_op(call, busy[0])
                    kind, payload = rpc.KIND_BUSY, busy[1]
                else:
                    tb = traceback.format_exc()
                    logger.error("exception in %s: %s", fname, tb)
                    kind, payload = rpc.KIND_ERROR, tb
            with tracing.stage("server.pack", sink=call.sink, fname=fname):
                try:
                    # pack before writing: an unpicklable RESULT must
                    # degrade to a structured error frame, not a torn
                    # connection
                    parts = rpc.pack_frame(kind, payload)
                except Exception:
                    tb = traceback.format_exc()
                    logger.error("could not serialize %s response: %s",
                                 fname, tb)
                    parts = rpc.pack_frame(rpc.KIND_ERROR, tb)
            with tracing.stage("server.write", sink=call.sink, fname=fname):
                rpc._send_parts(conn, parts)
        self._book_request(call)

    def _book_request(self, call: _Call) -> None:
        """``server.request``: the whole frame in hand to the last byte of
        the response written — the rank's whole share of a round trip,
        and the parent of every span the rank booked for the request."""
        with tracing.bind(call.root):
            tracing.book("server.request", call.t_frame, sink=call.sink,
                         span_id=call.span_id, fname=call.fname)

    def _scheduled_search(self, args, kwargs, deadline, eager=False):
        """Normalize a search RPC's args onto the scheduler's submit."""
        vals = dict(zip(
            ("index_id", "query_batch", "top_k", "return_embeddings"), args))
        vals.update(kwargs or {})
        self._check_search_min_version(vals)
        return self.scheduler.submit(
            vals["index_id"], vals["query_batch"], vals["top_k"],
            bool(vals.get("return_embeddings", False)), deadline=deadline,
            eager=eager)

    def _check_search_min_version(self, vals: dict) -> None:
        """Pop a search's ``min_version`` (read-your-writes) demand and
        assert it BEFORE the scheduler sees the request: the watermark
        check needs no device and must not occupy a merge window, and
        the stale-read rejection must stay a plain application error
        (group-failover-eligible client-side) on both serving paths."""
        min_version = vals.pop("min_version", None)
        if min_version is not None:
            self._get_index(vals["index_id"]).assert_min_version(min_version)

    # ------------------------------------------------------------ mux dispatch

    def _dispatch_scheduled(self, conn, state, call: _Call, args, kwargs,
                            deadline) -> None:
        """Hand a mux search to the scheduler without blocking the reader:
        the scheduler already completes out of order via per-request
        events, so its completion callback just enqueues the tagged
        response write onto the worker pool (never socket I/O on the
        batcher thread). No eager flush even on the selector loop — the
        reader keeps pulling frames, so followers CAN arrive during the
        wait window now, and coalescing them is the whole point."""

        def done(result, error):
            call.t_done = tracing.now()
            try:
                self._rpc_workers.submit(self._finish_scheduled, conn, state,
                                         call, result, error)
            except RuntimeError:
                # pool already shut down (server stopping): the client's
                # demux will fail the call when the connection drops
                with self._mux_lock:
                    self._mux_inflight -= 1

        with tracing.bind(call.ticket):
            self._book_decode(call)
            try:
                vals = dict(zip(
                    ("index_id", "query_batch", "top_k", "return_embeddings"),
                    args))
                vals.update(kwargs or {})
                self._check_search_min_version(vals)
                # (the scheduler takes the sampled request off this
                # thread's context: tracing.ticket)
                self.scheduler.submit_async(
                    vals["index_id"], vals["query_batch"], vals["top_k"],
                    bool(vals.get("return_embeddings", False)),
                    deadline=deadline, callback=done)
                return
            except Exception as e:
                error = e
        # admission rejected (BUSY/deadline/stopped) or bad args:
        # answered synchronously — the request was never queued
        self._finish_scheduled(conn, state, call, None, error)

    def _finish_scheduled(self, conn, state, call: _Call, result,
                          error) -> None:
        with tracing.bind(call.ticket):
            if call.t_done is not None:
                # the batcher's callback to an RPC worker taking it
                tracing.book("server.finish_wait", call.t_done,
                             sink=self.perf)
            if error is None:
                self._book_op(call, "search")
                self._send_mux_response(conn, state, rpc.KIND_RESULT, result,
                                        call)
                return
            busy = self._classify_scheduler_reject(error)
            if busy is not None:
                self._book_op(call, busy[0])
                self._send_mux_response(conn, state, rpc.KIND_BUSY, busy[1],
                                        call)
                return
            tb = "".join(traceback.format_exception(
                type(error), error, error.__traceback__))
            logger.error("exception in scheduled search: %s", tb)
            self._send_mux_response(conn, state, rpc.KIND_ERROR, tb, call)

    def _dispatch_direct(self, conn, state, call: _Call, args,
                         kwargs) -> None:
        """Worker-pool target for mux non-search ops."""
        fname = call.fname
        with tracing.bind(call.ticket):
            try:
                if fname.startswith("_"):
                    raise AttributeError(fname)
                fn = getattr(self, fname)
                ret = fn(*args, **(kwargs or {}))
                self._book_op(call, fname)
                self._send_mux_response(conn, state, rpc.KIND_RESULT, ret,
                                        call)
            except Exception:
                tb = traceback.format_exc()
                logger.error("exception in %s: %s", fname, tb)
                self._send_mux_response(conn, state, rpc.KIND_ERROR, tb, call)

    def _pack_mux_response(self, state, base_kind, payload, req_id, fname):
        """Frame parts for one tagged response: binary skeleton when the
        connection negotiated it AND the op is in the binary search
        family; pickle otherwise (including any payload the binary
        schema cannot carry — the per-frame fallback)."""
        if (self._wire_enabled and state.peer_wire
                and fname in wire.BINARY_CALL_OPS):
            parts = rpc.pack_binary_response(base_kind, payload, req_id)
            if parts is not None:
                return parts
        return rpc.pack_tagged_response(base_kind, payload, req_id)

    def _send_mux_response(self, conn, state, base_kind, payload,
                           call: _Call) -> None:
        """Write one req_id-tagged response frame under the connection's
        write lock (the caller has bound the call's trace). A write
        failure means the peer is gone — its demux has already failed the
        call client-side, so only log. Called exactly once per mux call
        (every dispatch path funnels here), which is what keeps the
        in-flight gauge honest."""
        wlock = state.wlock
        req_id, fname = call.req_id, call.fname
        try:
            with tracing.stage("server.pack", sink=call.sink, fname=fname):
                try:
                    parts = self._pack_mux_response(state, base_kind, payload,
                                                    req_id, fname)
                except Exception:
                    # unpicklable result: answer a structured error instead
                    # of leaving the caller waiting (zero bytes hit the
                    # wire yet)
                    tb = traceback.format_exc()
                    logger.error("could not serialize %s response: %s",
                                 fname, tb)
                    parts = rpc.pack_tagged_response(rpc.KIND_ERROR, tb,
                                                     req_id)
            # the wait for the connection's write lock plus the send
            with tracing.stage("server.write", sink=call.sink, fname=fname,
                               req_id=req_id), wlock:
                rpc._send_parts(conn, parts)
            self._book_request(call)
        except OSError as e:
            logger.info("mux response write failed (%s req=%s): %s",
                        fname, req_id, e)
            # a failed/timed-out write may have left a partial frame on
            # the stream — nothing further can be written safely. Shut the
            # socket down so the connection reader wakes, drops it, and
            # any still-queued writes for it fail fast with EPIPE.
            try:
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        except Exception:
            logger.exception("mux response write failed (%s req=%s)",
                             fname, req_id)
        finally:
            with self._mux_lock:
                self._mux_inflight -= 1

    def start(self, port: int = rpc.DEFAULT_PORT, v6: bool = False) -> None:
        """Selector-based single-thread loop. The reference ships a broken
        version of this mode (its test is @skip'ed); ours blocks per ready
        connection on a full frame, which is correct (if lower-throughput
        than the threaded mode) for well-behaved clients.

        Mux (req_id-tagged) calls get the non-blocking equivalent of the
        threaded loop: the selector thread only decodes and dispatches
        (scheduler / worker pool), and completion callbacks enqueue the
        tagged response writes — so even this single-threaded loop holds a
        whole in-flight window per connection and the scheduler can merge
        it into one device batch. Legacy calls keep the eager inline path
        (for a one-in-flight peer, waiting for followers that structurally
        cannot arrive would be pure added latency)."""
        s = self._bind(port, v6)
        self._start_antientropy()
        s.setblocking(True)
        sel = selectors.DefaultSelector()
        sel.register(s, selectors.EVENT_READ, data=None)
        logger.info("selector server rank=%d on :%d", self.rank, port)
        while not self._stopping.is_set():
            try:
                events = sel.select(timeout=0.5)
            except OSError:
                break
            for key, _ in events:
                if key.data is None:
                    try:
                        conn, addr = s.accept()
                    except OSError:
                        continue
                    # per-connection state (addr, write-lock, negotiated
                    # wire capability) — the lock serializes mux response
                    # writes from worker threads against each other and
                    # the inline legacy path
                    rpc.bound_send_timeout(conn)
                    sel.register(conn, selectors.EVENT_READ,
                                 data=_ConnState(
                                     addr,
                                     lockdep.lock("IndexServer.conn_wlock"),
                                     rpc.FrameReader(conn)))
                else:
                    conn = key.fileobj
                    addr = key.data.addr
                    try:
                        self._one_call(conn, eager_search=True,
                                       state=key.data)
                        # the buffered reader may hold complete follower
                        # frames (a pipelined burst landed in one recv):
                        # serve them NOW — buffered bytes never make the
                        # socket readable, so select() would stall them
                        # until the peer's next send
                        while (key.data.reader is not None
                               and key.data.reader.pending):
                            self._one_call(conn, eager_search=True,
                                           state=key.data)
                    except (rpc.ClientExit, EOFError, OSError):
                        sel.unregister(conn)
                        conn.close()
                    except Exception as e:
                        # malformed frame / undecodable payload (bad magic,
                        # UnpicklingError): drop this connection only — the
                        # loop keeps serving everyone else, matching the
                        # threaded mode's behavior in _serve_connection
                        logger.warning(
                            "dropping connection from %s: %s", addr, e)
                        sel.unregister(conn)
                        try:
                            conn.close()
                        except OSError:
                            pass
        sel.close()

    # ------------------------------------------------------------ internals

    def _get_index(self, index_id: str) -> Index:
        with self.indexes_lock:
            if index_id not in self.indexes:
                raise RuntimeError(f"Server has no index with id={index_id}")
            return self.indexes[index_id]

    def _get_storage_dir(self, index_id: str, cfg: Optional[IndexCfg]) -> str:
        base = cfg.index_storage_dir if cfg and cfg.index_storage_dir else None
        if not base:
            return os.path.join(self.index_storage_dir, index_id, str(self.rank))
        return os.path.join(base, str(self.rank))


def main(argv=None):
    """Standalone single-server CLI (the reference ships a broken main() —
    server.py:391-400 constructs IndexServer() with no args; ours works)."""
    import argparse

    parser = argparse.ArgumentParser(description="run one index server rank")
    parser.add_argument("--port", default=rpc.DEFAULT_PORT, type=int)
    parser.add_argument("--rank", default=0, type=int)
    parser.add_argument("--storage-dir", required=True)
    parser.add_argument("--ipv6", action="store_true")
    parser.add_argument("--load-index", action="store_true")
    parser.add_argument("--discovery", default=None,
                        help="discovery file path; enables the anti-entropy "
                             "sweeper (peer resolution)")
    args = parser.parse_args(argv)
    setup_server_logging()
    envutil.place_compile_cache()
    server = IndexServer(args.rank, args.storage_dir,
                         discovery_path=args.discovery)
    server.start_blocking(args.port, v6=args.ipv6, load_index=args.load_index)


if __name__ == "__main__":
    main()
