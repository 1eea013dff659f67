"""Per-(server, index_id) shard engine: buffer, state machine, async train/add.

Behavioral parity with the reference's ``Index``
(distributed_faiss/index.py:111-508): ingest buffer + positional metadata,
NOT_TRAINED -> TRAINING -> TRAINED <-> ADD lifecycle, threshold-triggered
async training, chunked async add (cfg.buffer_bsz), per-shard persistence
directory with autosave watcher, nprobe/centroids APIs.

Conscious fixes vs the reference (documented quirks from SURVEY.md §2.1):
- training sample: uniformly sampled from the whole buffer (the reference
  slices the first train_num rows and shuffles *after* slicing,
  index.py:210-211 — a biased sample);
- save path writes index.npz via utils.serialization instead of
  faiss.write_index; meta/buffer stay pickle for parity with arbitrary
  metadata objects.

Host threads drive jitted device steps: train/add run in worker threads
while the serving thread keeps answering get_state/search; ``index_lock``
serializes device-touching operations per index (the reference does the
same for FAISS, index.py:246-252).
"""

import hashlib
import logging
import os
import pickle
import threading
import time
from typing import List, Optional, Tuple, Union

import numpy as np

from distributed_faiss_tpu.models.base import SearchHandle
from distributed_faiss_tpu.models.factory import (
    build_index,
    index_from_state_dict,
    remove_rows_unsupported,
)
from distributed_faiss_tpu.mutation import compaction as _compaction
from distributed_faiss_tpu.mutation import tombstones as _tombstones
from distributed_faiss_tpu.mutation import versions as _versions
from distributed_faiss_tpu.mutation.tombstones import TombstoneSet
from distributed_faiss_tpu.utils import (
    envutil,
    lockdep,
    serialization,
    tracing,
    xfercheck,
)
from distributed_faiss_tpu.utils.atomics import AtomicCounters
from distributed_faiss_tpu.utils.batching import SearchBatcher
from distributed_faiss_tpu.utils.config import (
    IndexCfg,
    MutationCfg,
    VersioningCfg,
)
from distributed_faiss_tpu.utils.serialization import (
    atomic_write,
    load_state,
    save_state,
)
from distributed_faiss_tpu.utils.state import (
    NOT_TRAINED_REJECTION_FMT,
    STALE_READ_REJECTION_FMT,
    IndexState,
)
from distributed_faiss_tpu.utils.tracing import LatencyStats

logger = logging.getLogger()

_IVF_BUILDERS = ("ivf_simple", "knnlm", "ivfsq", "ivf_tpu")


class _MetaStore:
    """Growable object-ndarray metadata store.

    The search-time metadata join is nq*k lookups; as a Python list that is
    ~100k interpreted ops per 1024-query block at k=100, executed on the
    serving thread. Backing the store with a capacity-doubling object array
    makes the join one vectorized ``take`` and lets ``search`` hold
    ``buffer_lock`` only long enough to snapshot (array ref, length).

    Why reading the snapshot outside the lock is safe: the store is
    APPEND-ONLY — ``extend`` writes only slots >= the snapshotted length
    (in place when capacity suffices; into a fresh array on growth), slots
    below it are never rewritten, and object-array element access is a
    GIL-atomic pointer load. Any future mutating API (update/delete of
    existing slots) would break this invariant and must copy-on-write or
    move the join back under the lock.

    On-disk format is unchanged: persistence goes through ``tolist()`` so
    meta.pkl stays a plain pickled list.
    """

    __slots__ = ("_arr", "_n")

    def __init__(self, items=None):
        items = items if items is not None else []
        n = len(items)
        arr = np.empty(max(8, n), dtype=object)
        if n:
            # fromiter keeps nested sequences as 1-D scalars (a plain
            # object-array assignment would coerce equal-length tuples 2-D)
            arr[:n] = np.fromiter(items, dtype=object, count=n)
        self._arr, self._n = arr, n

    def __len__(self) -> int:
        return self._n

    def __iter__(self):
        return iter(self._arr[: self._n].tolist())

    def __getitem__(self, i):
        if isinstance(i, (int, np.integer)):
            if not -self._n <= i < self._n:
                raise IndexError(i)
            return self._arr[i % self._n if self._n else 0]
        raise TypeError("slice access not supported; use tolist()")

    def extend(self, items) -> None:
        if not hasattr(items, "__len__"):
            items = list(items)  # list.extend parity: accept generators
        m = len(items)
        if m == 0:
            return
        if self._n + m > self._arr.shape[0]:
            cap = max(self._arr.shape[0] * 2, self._n + m)
            new = np.empty(cap, dtype=object)
            new[: self._n] = self._arr[: self._n]
            self._arr = new
        n0 = self._n
        self._arr[n0 : n0 + m] = np.fromiter(items, dtype=object, count=m)
        self._n = n0 + m

    def snapshot(self) -> Tuple[np.ndarray, int]:
        """(backing array, filled length) — safe to read outside the lock."""
        return self._arr, self._n

    def tolist(self) -> list:
        return self._arr[: self._n].tolist()


# normalized id keys for cross-layout / cross-replica matching — shared
# with the anti-entropy digest machinery (mutation/tombstones.py)
_id_match_key = _tombstones.id_match_key

# commutative digest arithmetic: per-id 128-bit hashes summed mod 2^128,
# so the digest is independent of row insertion order (reroutes and repair
# re-sends interleave differently per replica) and a multiset of ids —
# unlike XOR — cannot cancel a duplicated id pair out
_DIGEST_MASK = (1 << 128) - 1


def _id_hash(key) -> int:
    return int.from_bytes(
        hashlib.sha1(
            repr(key).encode("utf-8", "backslashreplace")).digest()[:16],
        "little")


def _iter_live_ids(meta_arr, meta_n: int, dead_rows, id_idx: int):
    """Yield ``(position, raw_id, meta)`` for every LIVE metadata row: the
    one scan the anti-entropy surfaces (replica_digest, id_sets,
    export_rows, reconcile_deletes) all share, so the live-row rule —
    skip falsy rows, skip tombstoned positions, skip rows whose metadata
    cannot yield an id — cannot drift between digest contents and delta
    contents (a one-sided drift shows up as a permanent
    digests_mismatched loop the sweep can never heal)."""
    for p in range(meta_n):
        m = meta_arr[p]
        if not m or p in dead_rows:
            continue
        try:
            mid = m[id_idx]
        except (TypeError, IndexError, KeyError):
            continue
        yield p, mid, m


def _normalize_batch_versions(version, n: int):
    """Normalize ``add_batch``'s ``version`` argument: None (unversioned),
    ONE version stamped onto every row of the batch (a client mutation
    call ticks once), or a per-row list (the anti-entropy delta pull,
    whose rows come from different original writes). Returns
    ``(vlist, per_row)``: None or a list of n normalized version keys
    (entries may be None), and whether the caller supplied per-ROW
    versions — which is also the replace-eligibility signal: only the
    delta pull replaces an older live row in place (metadata ids are not
    required to be unique, so a plain ingest batch must never treat "id
    already live at an older version" as an upsert — shared-id corpora
    would eat their own earlier batches)."""
    if version is None:
        return None, False
    if (isinstance(version, (list, tuple)) and len(version) == 3
            and all(isinstance(c, (int, np.integer)) for c in version)):
        return [_versions.version_key(version)] * n, False
    out = [_versions.version_key(v) for v in version]
    if len(out) != n:
        raise RuntimeError(
            "versions length should match the batch size of the embeddings")
    return out, True


def _apply_sidecar_by_id(tomb: "TombstoneSet", side: dict, meta: list,
                         id_idx: int, storage_dir: str) -> None:
    """Cross-layout tombstone recovery: the standalone sidecar's POSITIONS
    belong to a layout that did not survive (a compacted generation that
    tore before the crash), but its id-keyed record is layout-independent
    — re-derive the dead rows by scanning the loaded metadata for those
    ids. Conservative by design: an id that was deleted and then re-added
    inside the lost layout is re-deleted here (a delete must never
    resurrect; re-ingest restores the upsert)."""
    ids = set()
    for v in side.get("dead_ids", ()):
        if v is None:
            continue
        ids.add(_id_match_key(v))
    if not ids:
        return
    hits = 0
    for p, m in enumerate(meta):
        if not m:
            continue
        try:
            mid = m[id_idx]
        except (TypeError, IndexError, KeyError):
            continue
        if _id_match_key(mid) in ids and p not in tomb:
            tomb.add([p], [mid])
            hits += 1
    logger.warning(
        "tombstone sidecar at %s is keyed to layout %s but generation "
        "layout is %s: re-applied %d delete(s) BY ID onto the fallback "
        "layout", storage_dir, side.get("layout"), tomb.layout, hits)


def get_index_files(index_storage_dir: str) -> Tuple[str, str, str, str]:
    """LEGACY flat file layout per shard (reference: index.py:103-108,
    .faiss -> .npz). Saves now write generation-suffixed sets committed by
    a MANIFEST (see utils/serialization.py); these names remain only so
    pre-manifest checkpoints still load."""
    index_file = os.path.join(index_storage_dir, "index.npz")
    meta_file = os.path.join(index_storage_dir, "meta.pkl")
    buffer_file = os.path.join(index_storage_dir, "buffer.pkl")
    cfg_file = os.path.join(index_storage_dir, "cfg.json")
    return index_file, meta_file, buffer_file, cfg_file


def infer_n_centroids(total_data_size: int) -> int:
    """Centroid-count tiers (reference index.py:497-508; thresholds written
    as 10e5/10e6/10e7 there, i.e. 1e6/1e7/1e8)."""
    if total_data_size < 10e5:
        return int(2 * (total_data_size ** 0.5))
    if total_data_size < 10e6:
        return 65536
    if total_data_size < 10e7:
        return 262144
    return 1048576


class Index:
    def __init__(self, cfg: IndexCfg):
        self.cfg = cfg
        self.embeddings_buffer: List[np.ndarray] = []
        self.total_data = 0
        self.id_to_metadata = _MetaStore()
        # pinned locks ride the lockdep factories: plain threading.Lock
        # by default, the DFT_LOCKDEP=1 runtime lock-order witness in the
        # lockdep test tier (utils/lockdep.py; keys match the graftlint
        # PINS map spelling)
        self.buffer_lock = lockdep.lock("Index.buffer_lock")
        self.index_lock = lockdep.lock("Index.index_lock")
        self.state = IndexState.NOT_TRAINED
        self.tpu_index = None  # models.base.TpuIndex once trained
        # set when this engine is replaced in a server's registry (shard
        # transfer install, drop_index): stops the save watcher and
        # blocks further autosaves, so a superseded engine can never
        # commit its stale state as a NEWER generation over the
        # replacement's storage dir
        self._retired = threading.Event()
        # background worker threads, tracked so retire() has a join path
        # (thread-lifecycle discipline): the two watchers wake on the
        # retired event and exit immediately; train/add are the transient
        # state-machine workers (at most one of each — the TRAINING/ADD
        # state gate), joined best-effort
        self._save_thread: Optional[threading.Thread] = None
        self._compaction_thread: Optional[threading.Thread] = None
        # graftlint: atomic(_train_thread, _add_thread): transient worker handles — the TRAINING/ADD state gate (taken under index_lock) means concurrent spawners lose the state race before both can start a worker, and retire()'s bounded best-effort join tolerates a superseded handle
        self._train_thread: Optional[threading.Thread] = None
        self._add_thread: Optional[threading.Thread] = None

        # graftlint: atomic(index_save_time): save-interval heuristic — a single float publish the save watcher reads lock-free; a stale read only shifts one autosave by an interval
        self.index_save_time = time.time()
        self.index_saved_size = 0
        # device-launch latency/occupancy distributions, surfaced through
        # the server's get_perf_stats "engine" key — lets operators read
        # wire round-trip (client rpc stats), queue wait (scheduler), and
        # device time side by side when tuning pipelining depth
        # — and the sink of this engine's stages (utils/tracing.stage:
        # lock wait, launch, join, train, buffer drain; the model's feed /
        # scan / refine_fetch stages inherit it through the context)
        self.perf = LatencyStats()
        # merged windows launched and collected (monotonic; a leaf lock of
        # their own, so a collect never waits behind an add for index_lock):
        # a launch that finds fewer collected than launched before it books
        # engine.launch_overlapped
        self._windows = AtomicCounters(("launched", "collected"))
        # newest committed snapshot generation in this shard's storage dir
        # (0 = nothing committed yet; from_storage_dir seeds it on restore)
        self._generation = 0

        # ---- mutation subsystem (mutation/) ----
        # positional dead-row set + id record; guarded by index_lock (the
        # same lock the device mask scatter holds and every device search
        # is launched under, which is what makes a scheduler-merged window
        # see one consistent tombstone snapshot — never a torn mask
        # mid-window)
        self.tombstones = TombstoneSet()
        self._mutation_counters = {
            "compactions": 0, "compactions_aborted": 0, "load_fallbacks": 0,
            # LWW version gates (mutation/versions.py): stale replays
            # that no-op'd instead of double-applying — the repair-queue
            # re-send / duplicated-fan-out idempotency signal — and adds
            # that REPLACED an older live row in place (anti-entropy
            # upsert refresh)
            "version_noop_adds": 0, "version_noop_deletes": 0,
            "version_replaced": 0,
            # deletion-ledger version pairs dropped once every registered
            # replica's watermark passed them (sweeper-driven,
            # engine.prune_ledger): the bound on sidecar growth under
            # delete-heavy churn
            "ledger_pruned": 0,
        }
        # per-id mutation versioning (ISSUE 12): per-WRITER watermarks of
        # the newest version this shard has incorporated (the
        # read-your-writes gate; writer -> (wall_ms, counter)). Per-id
        # versions live in the TombstoneSet (live map + versioned
        # ledger), all under index_lock.
        self.versioning = VersioningCfg.from_env()
        self._version_watermark = {}
        # generation-pinned point-in-time reads (search_at_generation):
        # one cached read-only snapshot of a retained committed
        # generation, loaded lazily. Its own leaf lock — a pinned read
        # must never contend with the serving locks.
        self._pinned_lock = lockdep.lock("Index._pinned_lock")
        self._pinned_cache = None
        # standalone-sidecar writer: mutations snapshot their payload (and
        # a version) under the engine locks but perform the JSON
        # rewrite+fsync OUTSIDE them — a delete storm must not stall the
        # serving path on disk I/O. The version gate keeps last-writer-
        # wins correct: a later version's payload is always a superset
        # (the set only shrinks at a compaction swap, which bumps the
        # version under the same locks), so a stale writer just skips.
        self._tombstone_io_lock = lockdep.lock("Index._tombstone_io_lock")
        self._tombstone_version = 0  # guarded by index_lock
        self._tombstone_written = 0  # guarded by _tombstone_io_lock
        # tombstone version captured by the last committed generation:
        # a delete/version-only change (ntotal unchanged) must still
        # commit on the next save, or generation-pinned reads could
        # never pin a post-delete point in time. Guarded by index_lock.
        self._saved_tombstone_version = 0
        # metadata layout epoch (seqlock): bumped under BOTH locks whenever
        # the positional row layout is replaced (compaction swap,
        # drop_index), so a search that launched on the old layout retries
        # its metadata join instead of joining old ids to new metadata.
        # Guarded by buffer_lock (the join side).
        self._meta_epoch = 0
        # cached replica digest (parallel/antientropy.py): recomputed only
        # when the cache key — (meta epoch, tombstone version, metadata
        # length), i.e. any mutation or generation bump — moves. Guarded
        # by index_lock (read/written under both engine locks).
        self._digest_cache = None
        # cross-replica compaction lease hook: the server's anti-entropy
        # sweeper installs a callable returning True while THIS rank holds
        # its group's compaction token; None (standalone/unreplicated
        # engines) means the background watcher compacts freely. The
        # explicit compact_index op is never gated — operator override.
        self.compaction_gate = None
        self.mutation_cfg = MutationCfg.from_env()
        if self.mutation_cfg.compact and cfg.index_storage_dir:
            self._run_compaction_watcher()

        # concurrent searches coalesce into shared device launches
        # (launch-bound serving — utils/batching.py); window 0 = natural
        # batching only, no added latency
        self._batcher = SearchBatcher(
            self._device_search,
            window_ms=float(cfg.extra.get("batch_window_ms", 0.0)),
        )

        if cfg.save_interval_sec > 0:
            self._run_save_watcher()

    # ------------------------------------------------------------------ ingest

    def drop_index(self) -> None:
        with self.buffer_lock:
            self.embeddings_buffer = []
            self.total_data = 0
            self.id_to_metadata = _MetaStore()
            # layout replaced: in-flight joins against the old index retry
            self._meta_epoch += 1
        with self.index_lock:
            self.tpu_index = None
            self.state = IndexState.NOT_TRAINED
            self.tombstones = TombstoneSet(layout=self.tombstones.layout)

    def add_batch(
        self,
        embeddings: np.ndarray,
        metadata: Optional[List[object]],
        train_async_if_triggered: bool = True,
        version=None,
    ) -> None:
        n = embeddings.shape[0]
        if not metadata:
            metadata = [None] * n
        if n != len(metadata):
            raise RuntimeError("metadata length should match the batch size of the embeddings")
        embeddings = np.asarray(embeddings, np.float32)

        versions_list, per_row = _normalize_batch_versions(version, n)
        if versions_list is not None:
            # versioned write path (ISSUE 12): LWW-gated per id — stale
            # replays no-op, and (per-row versions only: the delta-pull
            # path) strictly newer versions replace older live rows in
            # place. One atomic apply under both locks.
            total_data = self._add_batch_versioned(
                embeddings, metadata, versions_list,
                allow_replace=per_row)
        else:
            with self.buffer_lock:
                self.embeddings_buffer.append(embeddings)
                self.id_to_metadata.extend(metadata)
                self.total_data += n
                total_data = self.total_data

            # a re-added id is live again: drop its deletion-ledger entry
            # so anti-entropy can replicate the re-add (upsert semantics).
            # O(batch) hash lookups, and only when a delete ever happened
            # here. The unledger must be DURABLE like the delete it
            # reverses: a restart re-reads the sidecar, and a stale ledger
            # entry would let a peer's delete-wins sweep re-delete the
            # acked re-add cluster-wide
            payload = None
            with self.index_lock:
                if self.tombstones.ledger_size():
                    id_idx = self.cfg.custom_meta_id_idx
                    keys = []
                    for m in metadata:
                        if not m:
                            continue
                        try:
                            keys.append(m[id_idx])
                        except (TypeError, IndexError, KeyError):
                            continue
                    if self.tombstones.unledger(keys):
                        self._digest_cache = None
                        payload, sc_version = self._tombstone_payload_locked()
            if payload is not None:
                self._write_tombstone_sidecar(payload, sc_version)

        state = self.get_state()
        if state == IndexState.TRAINED:
            self.add_buffer_to_index()
        elif state == IndexState.NOT_TRAINED and 0 < self.cfg.train_num <= total_data:
            logger.info("buffer reached %d >= train_num, triggering training", total_data)
            if train_async_if_triggered:
                t = threading.Thread(
                    target=self.train, name=f"train:{self._thread_tag()}",
                    daemon=True)
                self._train_thread = t
                t.start()
            else:
                self.train()

    def _add_batch_versioned(self, embeddings: np.ndarray, metadata: list,
                             vlist: list, allow_replace: bool) -> int:
        """LWW-gated append (mutation/versions.py): per id, a row whose
        version loses to the current live/ledger state is a NO-OP (the
        repair-replay / duplicated-fan-out idempotency contract);
        with ``allow_replace`` (per-row versions — ONLY the anti-entropy
        delta pull, whose rows are known-unique exports) a row strictly
        newer than a versioned live occupant REPLACES it in place (the
        old rows tombstone in the same lock hold — the upsert-refresh
        path); everything else appends normally — in particular a plain
        single-stamp ingest batch NEVER replaces, because metadata ids
        are not required to be unique and an id "already live at an
        older version" is ordinary shared-id ingest there. The whole
        decide+apply runs under both engine locks so no concurrent
        delete can interleave between the gate check and the append; the
        sidecar write (ledger changes must survive a crash, or a stale
        delete would win after restart) happens outside them as ever.
        Returns the post-append buffered total (the training trigger)."""
        id_idx = self.cfg.custom_meta_id_idx
        keys = []
        for m in metadata:
            k = None
            if m:
                try:
                    k = _id_match_key(m[id_idx])
                except (TypeError, IndexError, KeyError):
                    k = None
            keys.append(k)

        def scan(meta_arr, lo, hi, want):
            found = []
            for p in range(lo, hi):
                m = meta_arr[p]
                if not m:
                    continue
                try:
                    mid = m[id_idx]
                except (TypeError, IndexError, KeyError):
                    continue
                if _id_match_key(mid) in want:
                    found.append((p, mid))
            return found

        # lock-free prescan (the remove_ids pattern): candidate positions
        # for ANY batch key against the append-only metadata snapshot, so
        # the O(rows) walk a displacement needs never runs under the
        # serving locks (a refresh pull on a large shard must not stall
        # searches chunk after chunk); the locked section below only
        # rescans the tail appended since — or everything, in the rare
        # case a compaction swapped the layout mid-flight.
        batch_keys = {k for k in keys if k is not None}
        candidates = []
        if allow_replace and batch_keys:
            with self.buffer_lock:
                epoch0 = self._meta_epoch
                meta_arr0, meta_n0 = self.id_to_metadata.snapshot()
            candidates = scan(meta_arr0, 0, meta_n0, batch_keys)
        with self.buffer_lock, self.index_lock:
            tomb = self.tombstones
            keep = [True] * len(metadata)
            replace_keys = set()
            noop = 0
            for i, (k, v) in enumerate(zip(keys, vlist)):
                self._observe_version_locked(v)
                if k is None or v is None:
                    continue
                live_v = tomb.live_version(k)
                if _versions.add_loses(v, live_v, tomb.ledger_version(k)):
                    keep[i] = False
                    noop += 1
                elif allow_replace:
                    # delta-pull rows displace ANY live occupant of their
                    # id — including an UNVERSIONED one (legacy ingest,
                    # or the crash window that drops uncommitted live
                    # versions): appending beside it would leave two live
                    # rows for the id and wedge digest convergence
                    # forever. An id with no live rows just contributes
                    # nothing to the replace scan below.
                    replace_keys.add(k)
            self._mutation_counters["version_noop_adds"] += noop
            replaced_rows = 0
            if replace_keys:
                meta_arr, meta_n = self.id_to_metadata.snapshot()
                indexed_n = (self.tpu_index.ntotal
                             if self.tpu_index is not None else 0)
                if self._meta_epoch != epoch0:
                    # layout swapped since the lock-free prescan: the
                    # candidate positions are stale — full rescan (rare)
                    candidates = scan(meta_arr, 0, meta_n, batch_keys)
                else:
                    candidates += scan(meta_arr, meta_n0, meta_n,
                                       batch_keys)
                rows, rids = [], []
                for p, mid in candidates:
                    if p in tomb:
                        continue
                    if _id_match_key(mid) in replace_keys:
                        rows.append(p)
                        rids.append(mid)
                if rows:
                    # only an ACTUAL displacement needs the tombstone
                    # mask (a pull of purely-missing rows must not hit
                    # the unsupported-kind rejection)
                    self._check_remove_supported_locked()
                    device_rows = [p for p in rows if p < indexed_n]
                    if device_rows:
                        # graftlint: ok(blocking-under-lock): the locked mask scatter is the tombstone consistency contract — device mutations serialize on index_lock like every launch
                        self.tpu_index.remove_rows(
                            np.asarray(device_rows, np.int64))
                    tomb.add(rows, rids)
                    replaced_rows = len(rows)
                    self._mutation_counters["version_replaced"] += replaced_rows
            kept_n = sum(keep)
            unledgered = 0
            if kept_n:
                if kept_n == len(metadata):
                    kept_emb, kept_meta = embeddings, metadata
                else:
                    mask = np.asarray(keep, bool)
                    kept_emb = embeddings[mask]
                    kept_meta = [m for i, m in enumerate(metadata)
                                 if keep[i]]
                self.embeddings_buffer.append(kept_emb)
                self.id_to_metadata.extend(kept_meta)
                self.total_data += kept_n
                for i, (k, v) in enumerate(zip(keys, vlist)):
                    if not keep[i] or k is None:
                        continue
                    if v is not None:
                        tomb.set_live_version(k, _versions.newest(
                            tomb.live_version(k), v))
                    # the landing write outranks any recorded delete (the
                    # add gate already compared): the id is pullable again
                    unledgered += tomb.unledger([k])
            total_data = self.total_data
            # sidecar durability point ONLY when the batch touched the
            # deletion state (re-add over a ledger entry, in-place
            # replace) — the payload is O(versioned ids), so rewriting it
            # per plain ingest batch would make a bulk load quadratic.
            # Plain appends' live versions become durable at the next
            # generation commit instead; a crash inside that window
            # degrades exactly those rows to unversioned (legacy
            # delete-wins, replayable) and the sweep re-converges them —
            # the pre-version exposure, bounded to the uncommitted tail.
            payload = None
            if replaced_rows or unledgered:
                self._digest_cache = None
                payload, sc_version = self._tombstone_payload_locked()
        if payload is not None:
            self._write_tombstone_sidecar(payload, sc_version)
        return total_data

    # ---------------------------------------------------------------- mutation

    def remove_ids(self, ids, version=None) -> int:
        """Tombstone every row whose metadata id (``cfg.custom_meta_id_idx``)
        is in ``ids``. Returns the number of rows newly tombstoned.

        ``version`` (one HLC version for the whole call — the client
        stamps once per mutation) makes the delete LWW-gated: an id whose
        live version is same-or-newer NO-OPs (the upsert outran the
        delete — the race that used to converge to delete-wins), a replay
        of an already-applied delete NO-OPs, and every id the delete DOES
        win is recorded in the deletion ledger at ``version`` — including
        ids with no local rows, so a stale add arriving later (a repair
        re-send of a write this delete superseded) is gated too.
        Unversioned calls keep the exact legacy delete-wins semantics.

        Indexed rows are masked on device immediately (one scatter under
        ``index_lock`` — the same lock every device search is launched
        under, all its programs in one hold and on the operands it found,
        so a merged window is entirely pre- or post-delete, never torn).
        Buffer-aware: rows still in the add buffer keep their positional
        slot and are masked the moment their drain chunk lands
        (_add_buffer_to_idx), so an id deleted mid-ingest never serves.
        The updated tombstone set is persisted to the standalone sidecar
        (tmp+fsync+rename) BEFORE this returns — a crash after an
        acknowledged delete can never resurrect the rows, whatever
        generation the restart falls back to (mutation/tombstones.py).

        The O(rows) id -> row scan runs OUTSIDE the locks against the
        append-only metadata snapshot (the same contract the search-time
        join rides), so a delete storm does not stall the serving path;
        only the (tiny) tail appended after the snapshot is re-scanned
        under the locks, keeping "every matching row present at call
        time" exact.
        """
        id_set = ids if isinstance(ids, (set, frozenset)) else set(ids)
        if not id_set:
            return 0
        id_idx = self.cfg.custom_meta_id_idx

        def scan(meta_arr, lo, hi):
            found = []
            for p in range(lo, hi):
                meta = meta_arr[p]
                if not meta:
                    continue
                try:
                    mid = meta[id_idx]
                except (TypeError, IndexError, KeyError):
                    continue
                if mid in id_set:
                    found.append((p, mid))
            return found

        with self.buffer_lock:
            epoch0 = self._meta_epoch
            meta_arr0, meta_n0 = self.id_to_metadata.snapshot()
        candidates = scan(meta_arr0, 0, meta_n0)  # O(rows), lock-free

        vk = _versions.version_key(version)
        with self.buffer_lock, self.index_lock:
            meta_arr, meta_n = self.id_to_metadata.snapshot()
            if self._meta_epoch != epoch0:
                # a compaction/drop swapped the positional layout between
                # the lock-free scan and this point: the candidate
                # positions are stale — rescan fully under the locks
                # (rare; the swap itself is rare)
                candidates = scan(meta_arr, 0, meta_n)
            else:
                candidates += scan(meta_arr, meta_n0, meta_n)
            indexed_n = (self.tpu_index.ntotal
                         if self.tpu_index is not None else 0)
            eligible_keys = None
            if vk is not None:
                # LWW gate per requested id (not per matched row): ids
                # the delete loses no-op; ids it wins are ledgered at vk
                # below even when no local row carries them
                self._observe_version_locked(vk)
                eligible_keys, gated = set(), 0
                for raw in id_set:
                    k = _id_match_key(raw)
                    if _versions.delete_loses(
                            vk, self.tombstones.live_version(k),
                            self.tombstones.ledger_version(k)):
                        gated += 1
                    else:
                        eligible_keys.add(k)
                self._mutation_counters["version_noop_deletes"] += gated
            rows, rids = [], []
            for p, mid in candidates:
                if p in self.tombstones:
                    continue
                if (eligible_keys is not None
                        and _id_match_key(mid) not in eligible_keys):
                    continue
                rows.append(p)
                rids.append(mid)
            if not rows and not eligible_keys:
                return 0
            if rows:
                self._check_remove_supported_locked()
                device_rows = [p for p in rows if p < indexed_n]
                if device_rows:
                    # graftlint: ok(blocking-under-lock): the locked mask scatter is the tombstone consistency contract — device mutations serialize on index_lock like every launch
                    self.tpu_index.remove_rows(
                        np.asarray(device_rows, np.int64))
                self.tombstones.add(rows, rids, version=vk)
                if vk is None:
                    # legacy delete-wins: a versioned live entry must not
                    # outlive its rows (the digest compares (id, version))
                    for mid in rids:
                        self.tombstones.drop_live_version(mid)
            if eligible_keys:
                self.tombstones.ledger_update_versioned(
                    (k, vk) for k in eligible_keys)
                for k in eligible_keys:
                    self.tombstones.drop_live_version(k)
            self._digest_cache = None
            payload, sc_version = self._tombstone_payload_locked()
            removed = len(rows)
        # durability point — AFTER the serving locks are released: the
        # sidecar rewrite+fsync must not stall concurrent searches/adds
        self._write_tombstone_sidecar(payload, sc_version)
        return removed

    def upsert(self, ids, embeddings: np.ndarray,
               metadata: Optional[List[object]] = None,
               version=None) -> int:
        """Delete + add: tombstone every live row carrying one of ``ids``,
        then ingest the replacement vectors through the normal add path
        (new rows get fresh positions, so they are NOT masked by the ids'
        tombstones — those are positional). Returns the rows tombstoned.

        Visibility ordering: the old rows stop serving before this call
        returns; the new rows become searchable when their buffer chunk
        drains (exactly like any add) — old and new are never both live.
        ``metadata`` defaults to ``(id,)`` tuples when the id rides at
        metadata position 0 (the default ``custom_meta_id_idx``).

        ``version`` stamps BOTH halves with the same HLC version; the
        LWW tie rules (add wins a tie against the ledger, loses one
        against a live row) make the pair atomic under replay: a replayed
        upsert's delete no-ops against its own live re-add, and its
        re-add no-ops against the already-live row."""
        ids = list(ids)
        embeddings = np.asarray(embeddings, np.float32)
        if embeddings.shape[0] != len(ids):
            raise RuntimeError(
                "upsert ids length should match the batch size of the "
                "embeddings")
        if metadata is None:
            if self.cfg.custom_meta_id_idx != 0:
                raise RuntimeError(
                    "upsert needs explicit metadata when "
                    "custom_meta_id_idx != 0")
            metadata = [(i,) for i in ids]
        removed = self.remove_ids(ids, version=version)
        self.add_batch(embeddings, metadata, version=version)
        return removed

    # graftlint: ok(lock-discipline): the _locked suffix is the contract — every caller holds index_lock
    def _check_remove_supported_locked(self) -> None:
        """Reject remove/upsert on index kinds without a tombstone mask
        BEFORE any tombstone is recorded — including when every matching
        row is still in the add buffer (``tpu_index`` may not even exist
        yet): accepting such a delete and letting the drain-time mask hit
        the base-class rejection would kill the drain worker and wedge
        the engine in ``ADD`` forever."""
        if self.tpu_index is not None:
            if not self.tpu_index.supports_remove_rows():
                raise RuntimeError(
                    f"{type(self.tpu_index).__name__} does not support "
                    "remove/upsert (no tombstone mask for this index kind)")
        elif remove_rows_unsupported(self.cfg):
            kind = self.cfg.index_builder_type or self.cfg.faiss_factory
            raise RuntimeError(
                f"index kind {kind!r} does not support remove/upsert "
                "(no tombstone mask for this index kind)")

    # graftlint: ok(lock-discipline): the _locked suffix is the contract — every caller holds index_lock
    def _tombstone_payload_locked(self):
        """Snapshot the sidecar payload + a monotonic version under the
        engine locks; the disk write happens outside them
        (_write_tombstone_sidecar)."""
        self._tombstone_version += 1
        return self.tombstones.to_payload(), self._tombstone_version

    def _write_tombstone_sidecar(self, payload: dict, version: int) -> None:
        """Rewrite the standalone sidecar (atomic tmp+fsync+rename) — the
        per-mutation durability point, serialized by its own writer lock
        so it never rides the serving locks. Version-gated: if a newer
        payload (a superset — the set only shrinks at a compaction swap,
        which also bumps the version) already landed, skip. No-op for
        storage-less engines (pure in-memory shards keep the in-memory
        set only)."""
        storage_dir = self.cfg.index_storage_dir
        if not storage_dir:
            return
        with self._tombstone_io_lock:
            if version <= self._tombstone_written:
                return
            os.makedirs(storage_dir, exist_ok=True)
            _tombstones.write_sidecar(storage_dir, payload)
            self._tombstone_written = version

    def tombstone_fraction(self) -> float:
        """Tombstoned fraction of the INDEXED rows (the compaction
        trigger; buffered dead rows reclaim themselves on drain+compact)."""
        with self.index_lock:
            indexed_n = (self.tpu_index.ntotal
                         if self.tpu_index is not None else 0)
            if indexed_n == 0:
                return 0.0
            return self.tombstones.count_below(indexed_n) / indexed_n

    def mutation_stats(self) -> dict:
        """The ``mutation`` perf-stats key (served per index through
        IndexServer.get_perf_stats): tombstone counts, live fraction,
        compaction counters (run / aborted mid-swap / generation
        fallbacks at load), the layout epoch, and the ``compaction_s``
        latency summary when any pass has run."""
        with self.index_lock:
            indexed_n = (self.tpu_index.ntotal
                         if self.tpu_index is not None else 0)
            dead_indexed = self.tombstones.count_below(indexed_n)
            out = {
                "tombstoned_rows": len(self.tombstones),
                "tombstoned_indexed": dead_indexed,
                "live_fraction": (
                    1.0 - dead_indexed / indexed_n if indexed_n else 1.0),
                "layout_generation": self.tombstones.layout,
                **self._mutation_counters,
            }
        comp = self.perf.summary().get("compaction_s")
        if comp:
            out["compaction_s"] = comp
        wm = self.version_watermark()
        out["version_watermark"] = list(wm) if wm is not None else None
        return out

    # ------------------------------------------------------------- versioning

    # graftlint: ok(lock-discipline): the _locked suffix is the contract — every caller holds index_lock
    def _observe_version_locked(self, vk) -> None:
        """Fold one presented version into the per-writer watermark. A
        version counts as incorporated whether it APPLIED or no-op'd —
        a gated replay means a same-or-newer write already covers it, so
        a read demanding ``min_version`` <= vk is answerable here."""
        if vk is None:
            return
        cur = self._version_watermark.get(vk[2])
        pair = (vk[0], vk[1])
        if cur is None or pair > cur:
            self._version_watermark[vk[2]] = pair

    def version_watermark(self):
        """The newest version incorporated on this shard across all
        writers (None before any versioned mutation) — what a restarting
        client's HLC seeds from (``get_id_sets``)."""
        with self.index_lock:
            items = list(self._version_watermark.items())
        if not items:
            return None
        return max((ms, ctr, w) for w, (ms, ctr) in items)

    def assert_min_version(self, min_version) -> None:
        """Read-your-writes gate: raise the structured stale-read
        rejection (group-failover-eligible, utils/state.py) when this
        replica has not yet incorporated ``min_version``. Watermarks are
        tracked PER WRITER — a client's own versions are monotonic, so
        ``watermark[writer] >= (ms, counter)`` proves every write that
        client stamped up to ``min_version`` has landed (or been
        superseded) here; another writer's higher version can never
        satisfy it by accident."""
        vk = _versions.version_key(min_version)
        if vk is None:
            return
        with self.index_lock:
            wm = self._version_watermark.get(vk[2])
        if wm is None or wm < (vk[0], vk[1]):
            raise RuntimeError(STALE_READ_REJECTION_FMT.format(
                version=list(vk), watermark=list(wm) if wm else None))

    # ----------------------------------------------------------- anti-entropy

    def replica_digest(self) -> dict:
        """Cheap, order-independent convergence digest for server-side
        anti-entropy (parallel/antientropy.py).

        ``live_hash`` is a commutative sum (mod 2^128) of per-id hashes
        over every live metadata id — buffered rows included, tombstoned
        rows excluded — so two replicas that hold the same logical rows in
        DIFFERENT insertion orders (reroutes, repair re-sends) digest
        identically; ``dead_hash`` covers the deletion ledger the same
        way. Engine-local counters (tombstone version, layout epoch,
        ntotal) deliberately stay OUT of the comparable digest — they
        differ between converged replicas that compacted at different
        times — and form the CACHE KEY instead: the digest is captured
        under the engine locks and cached until the next mutation or
        generation bump moves (meta epoch, tombstone version, metadata
        length). The O(rows) hash runs outside the locks against the
        append-only metadata snapshot (the search-join contract), so
        sweeps never stall serving."""
        with self.buffer_lock, self.index_lock:
            key = (self._meta_epoch, self._tombstone_version,
                   len(self.id_to_metadata))
            if self._digest_cache is not None and self._digest_cache[0] == key:
                return dict(self._digest_cache[1])
            meta_arr, meta_n = self.id_to_metadata.snapshot()
            dead_rows = frozenset(self.tombstones.rows())
            ledger = self.tombstones.ledger()
            live_vmap = dict(self.tombstones.live_versions())
        id_idx = self.cfg.custom_meta_id_idx
        live_sum, live_vsum, live_n = 0, 0, 0
        for _p, mid, _m in _iter_live_ids(meta_arr, meta_n, dead_rows, id_idx):
            k = _id_match_key(mid)
            live_sum = (live_sum + _id_hash(k)) & _DIGEST_MASK
            # versioned plane: hashing (id, version) catches content
            # divergence under an UNCHANGED id set — the in-place upsert
            # an id-only digest cannot see. Compared only between peers
            # that both emit it (digests_match), so pre-version replicas
            # keep converging on the id-only plane.
            live_vsum = (live_vsum
                         + _id_hash((k, live_vmap.get(k)))) & _DIGEST_MASK
            live_n += 1
        dead_sum = 0
        for k in ledger:
            dead_sum = (dead_sum + _id_hash(k)) & _DIGEST_MASK
        digest = {
            "live_n": live_n,
            "live_hash": format(live_sum, "032x"),
            "live_vhash": format(live_vsum, "032x"),
            "dead_n": len(ledger),
            "dead_hash": format(dead_sum, "032x"),
        }
        with self.buffer_lock, self.index_lock:
            if key == (self._meta_epoch, self._tombstone_version,
                       len(self.id_to_metadata)):
                self._digest_cache = (key, dict(digest))
        return digest

    def id_sets(self) -> dict:
        """Normalized id sets for the anti-entropy delta protocol:
        ``live`` = every live metadata id (buffered included), ``dead`` =
        the deletion ledger. Keys ride ``id_match_key`` normalization so
        replicas whose persistence histories differ (JSON sidecar
        round-trips turn tuples into lists) still compare equal.

        Versioned extensions (absent = pre-version peer, handled by the
        sweeper): ``live_versions``/``dead_versions`` are (key, version)
        pairs for every id carrying a real version, and ``watermark`` is
        the shard's newest incorporated version — what a restarting
        client's HLC seeds from."""
        with self.buffer_lock, self.index_lock:
            meta_arr, meta_n = self.id_to_metadata.snapshot()
            dead_rows = frozenset(self.tombstones.rows())
            ledger_items = self.tombstones.ledger_items()
            live_vmap = dict(self.tombstones.live_versions())
        id_idx = self.cfg.custom_meta_id_idx
        live = [_id_match_key(mid) for _p, mid, _m
                in _iter_live_ids(meta_arr, meta_n, dead_rows, id_idx)]
        live_keys = set(live)
        wm = self.version_watermark()
        return {
            "live": live,
            "dead": sorted((k for k, _v in ledger_items), key=repr),
            "live_versions": sorted(
                ([k, v] for k, v in live_vmap.items()
                 if v is not None and k in live_keys), key=repr),
            "dead_versions": sorted(
                ([k, v] for k, v in ledger_items if v is not None),
                key=repr),
            "watermark": list(wm) if wm is not None else None,
        }

    def export_rows(self, ids) -> Tuple[np.ndarray, list]:
        """Rows for an anti-entropy delta pull: ``(embeddings, metadata)``
        for every LIVE local row whose id is in ``ids``. Indexed rows
        come back via reconstruct (exact for raw-storage kinds —
        flat/IVF-Flat; encoded kinds round-trip through their codec,
        which is why large divergence on those prefers the full-snapshot
        sync path), buffered rows verbatim. The un-versioned wire shape,
        kept for pre-version peers."""
        emb, metas, _vers = self._export_rows(ids)
        return emb, metas

    def export_rows_versioned(self, ids, with_hash: bool = False):
        """``export_rows`` plus each row's live write version (None for
        rows that were never versioned-written) — the pull side of a
        versioned delta repair: the puller applies the rows through the
        LWW add gates instead of blindly appending.

        ``with_hash=True`` appends a per-chunk content hash
        (``serialization.row_payload_hash`` over the embedding plane +
        metadata/version lists) as a 4th element: the pulling sweeper
        verifies it BEFORE applying the rows, so a transport-corrupted
        chunk can never be installed as repaired state. Kept behind a
        keyword (default off, 3-tuple unchanged) so PR-12 sweepers
        calling the bare op keep working across a rolling upgrade; a
        NEW sweeper against a pre-hash server degrades per heal (the
        unexpected-keyword ServerException fallback,
        antientropy._heal)."""
        emb, metas, vers = self._export_rows(ids)
        if not with_hash:
            return emb, metas, vers
        return emb, metas, vers, serialization.row_payload_hash(
            emb, metas, vers)

    # graftlint: ok(blocking-under-lock): designed locked fetch — rows and their metadata must come from one atomic index state (repair path, never hot)
    def _export_rows(self, ids) -> Tuple[np.ndarray, list, list]:
        """One atomic capture under both locks (positions must pair with
        the buffer they index into) behind both export shapes."""
        want = {_id_match_key(i) for i in ids}
        with self.buffer_lock, self.index_lock:
            meta_arr, meta_n = self.id_to_metadata.snapshot()
            indexed_n = (self.tpu_index.ntotal
                         if self.tpu_index is not None else 0)
            dead_rows = frozenset(self.tombstones.rows())
            live_vmap = dict(self.tombstones.live_versions())
            id_idx = self.cfg.custom_meta_id_idx
            positions, metas, vers = [], [], []
            for p, mid, m in _iter_live_ids(meta_arr, meta_n,
                                            dead_rows, id_idx):
                k = _id_match_key(mid)
                if k in want:
                    positions.append(p)
                    metas.append(m)
                    vers.append(live_vmap.get(k))
            dim = int(self.cfg.dim)
            # the buffer concatenate is O(buffered rows) under both locks:
            # pay it only when a wanted row is actually still buffered
            # (post-drain — the common case — every hit is indexed)
            need_buffer = any(p >= indexed_n for p in positions)
            flat_buf = (np.concatenate(self.embeddings_buffer, axis=0)
                        if need_buffer and self.embeddings_buffer
                        else np.zeros((0, dim), np.float32))
            out = np.zeros((len(positions), dim), np.float32)
            keep = np.ones(len(positions), bool)
            idxed = [(j, p) for j, p in enumerate(positions) if p < indexed_n]
            if idxed:
                rec = np.asarray(self.tpu_index.reconstruct_batch(
                    np.asarray([p for _j, p in idxed], np.int64)), np.float32)
                out[[j for j, _p in idxed]] = rec
            for j, p in enumerate(positions):
                if p < indexed_n:
                    continue
                off = p - indexed_n
                if off < flat_buf.shape[0]:
                    out[j] = flat_buf[off]
                else:  # meta/buffer mismatch (legacy truncation): skip row
                    keep[j] = False
        if not keep.all():
            out = out[keep]
            metas = [m for j, m in enumerate(metas) if keep[j]]
            vers = [v for j, v in enumerate(vers) if keep[j]]
        return out, metas, vers

    def prune_ledger(self, min_watermark, min_age_s: float = 0.0) -> int:
        """Drop deletion-ledger version pairs whose delete version is
        STRICTLY below ``min_watermark`` — safe once every registered
        replica's watermark has passed them (each replica has provably
        incorporated, or been outranked past, the delete), which is the
        sweeper's call to make (antientropy.AntiEntropySweeper: all
        group peers contacted this round, none suspect, digests
        matched) — AND at least ``min_age_s`` old (wall-clock component
        of the HLC stamp): replica watermarks cannot see a CLIENT's
        bounded repair queue, whose replay of a pre-delete add carries a
        stamp the pruned pair existed to gate, so young entries wait out
        the repair-replay window (DFT_LEDGER_PRUNE_AGE_S). Unversioned
        (legacy) entries are never pruned — nothing can prove every peer
        saw them. The shrunken ledger is persisted through the same
        versioned sidecar writer as every mutation, so a crash between
        prune and write merely re-prunes later. Returns the entries
        dropped (counted in ``mutation_stats()["ledger_pruned"]``)."""
        cutoff = (int(time.time() * 1000.0 - min_age_s * 1000.0)
                  if min_age_s > 0 else None)
        with self.buffer_lock, self.index_lock:
            pruned = self.tombstones.prune_ledger(min_watermark,
                                                  max_wall_ms=cutoff)
            if not pruned:
                return 0
            self._mutation_counters["ledger_pruned"] += pruned
            self._digest_cache = None
            payload, sc_version = self._tombstone_payload_locked()
        self._write_tombstone_sidecar(payload, sc_version)
        return pruned

    def reconcile_deletes(self, dead_keys, dead_versions=None) -> int:
        """Apply a peer's deletion ledger. Versioned (``dead_versions``:
        (key, version) pairs from the peer's id_sets): each delete is
        LWW-gated — a local live write at a same-or-newer version WINS
        (the upsert-vs-delete race converges to the true last writer
        instead of delete-wins), an unversioned local live row loses to
        any versioned delete, and every peer key is max-merged into the
        local ledger — durable before return, like any delete — so a
        stale repair re-send can never be pulled back by a later sweep.
        Unversioned peer keys keep the legacy conservative rule
        (delete-wins) EXCEPT against a versioned local live row, which a
        minimal unversioned delete can never outrank. Returns the rows
        newly tombstoned."""
        keys = {_id_match_key(k) for k in dead_keys}
        if not keys:
            return 0
        vmap = {}
        for k, v in (dead_versions or ()):
            vmap[_id_match_key(k)] = _versions.version_key(v)
        with self.buffer_lock, self.index_lock:
            meta_arr, meta_n = self.id_to_metadata.snapshot()
            dead_rows = frozenset(self.tombstones.rows())
            live_vmap = dict(self.tombstones.live_versions())
        id_idx = self.cfg.custom_meta_id_idx
        raw_by_version, legacy_raw, gated = {}, [], 0
        for _p, mid, _m in _iter_live_ids(meta_arr, meta_n,
                                          dead_rows, id_idx):
            k = _id_match_key(mid)
            if k not in keys:
                continue
            vd = vmap.get(k)
            if vd is None:
                # unversioned peer delete: legacy delete-wins, EXCEPT a
                # versioned local live write outranks the minimal stamp
                if live_vmap.get(k) is not None:
                    gated += 1
                else:
                    legacy_raw.append(mid)
            else:
                raw_by_version.setdefault(vd, []).append(mid)
        removed = self.remove_ids(legacy_raw) if legacy_raw else 0
        for vd, raws in sorted(raw_by_version.items()):
            # versioned removal re-gates UNDER the engine locks (the
            # snapshot above is only a partition): a newer upsert that
            # landed between the snapshot and this point keeps its rows —
            # feeding these ids through an UNVERSIONED remove here would
            # re-open the delete-wins race inside the very mechanism
            # built to close it. One call per distinct peer version
            # (ledger versions come from whole-batch client stamps, so
            # the group count tracks delete calls, not ids).
            removed += self.remove_ids(raws, version=vd)
        with self.buffer_lock, self.index_lock:
            if gated:
                self._mutation_counters["version_noop_deletes"] += gated
            changed = self.tombstones.ledger_update_versioned(
                (k, vmap.get(k)) for k in keys
                # never ledger a key a local live write just outranked at
                # the SAME version plane it holds: recording (k, v<=live)
                # is harmless, but skipping keys whose live version wins
                # keeps the ledger from accumulating strictly-stale pairs
                if not (live_vmap.get(k) is not None
                        and _versions.compare(live_vmap.get(k),
                                              vmap.get(k)) >= 0))
            for vk in vmap.values():
                self._observe_version_locked(vk)
            if changed:
                self._digest_cache = None
                payload, sc_version = self._tombstone_payload_locked()
            else:
                payload = None
        if payload is not None:
            self._write_tombstone_sidecar(payload, sc_version)
        return removed

    def compact(self) -> bool:
        """Rewrite tombstoned rows out of the index as a fresh MANIFEST
        generation, swapped in atomically. Returns True when a compaction
        committed.

        Three phases (the serving-liveness / crash-safety split):

        1. snapshot under both locks (state_dict + row count + dead set —
           the same atomic capture a save makes);
        2. rebuild WITHOUT locks: filter the state to survivors
           (mutation/compaction.py — encoded payloads copied verbatim,
           lists rebuilt tight) and construct the new index; serving
           continues on the old one throughout;
        3. back under both locks: abort if an ADD drained new rows since
           the snapshot (the pass retries at the next interval), replay
           deletes that arrived mid-rebuild onto the new layout, commit
           the generation — rows, compacted metadata, buffer, AND the
           remapped tombstone sidecar, all sha256-manifested with the new
           layout epoch — then swap index/metadata/tombstones and bump the
           layout epoch so in-flight joins retry.

        Crash windows: SIGKILL during phase 2 leaves at most uncommitted
        orphan files (quarantined at load; previous generation + its
        layout-matched sidecar serve, tombstones intact). SIGKILL inside
        phase 3 after the manifest landed loads the NEW generation, whose
        own sidecar already carries the catch-up set; the standalone
        sidecar — rewritten later in the same lock hold — is then stale by
        layout and ignored. No interleaving mutation can slip between the
        two writes because both happen under the engine locks.
        """
        storage_dir = self.cfg.index_storage_dir
        if not storage_dir:
            return False
        t0 = tracing.now()
        with self.buffer_lock, self.index_lock:
            if self.tpu_index is None or self.state != IndexState.TRAINED:
                return False
            n0 = int(self.tpu_index.ntotal)
            dead0 = np.asarray(
                [p for p in self.tombstones.rows() if p < n0], np.int64)
            if dead0.size == 0:
                return False
            # graftlint: ok(blocking-under-lock): designed locked fetch — the compaction snapshot must capture one atomic index state (same contract as _maybe_save)
            state = self.tpu_index.state_dict()

        # ---- phase 2: rebuild with serving live ----
        delay = envutil.env_float("DFT_COMPACT_TEST_DELAY_S", 0.0)
        if delay:
            # chaos-test hook: widen the mid-pass window so the SIGKILL
            # gate can land deterministically inside an uncommitted rebuild
            time.sleep(delay)
        keep = np.ones(n0, bool)
        keep[dead0] = False
        try:
            new_state = _compaction.compact_state(state, keep)
        except _compaction.CompactionUnsupported as e:
            logger.info("compaction skipped: %s", e)
            return False
        new_index = index_from_state_dict(new_state)
        new_n = int(keep.sum())
        old2new = np.full(n0, -1, np.int64)
        old2new[keep] = np.arange(new_n)

        # ---- phase 3: catch-up + commit + swap ----
        with self.buffer_lock, self.index_lock:
            if (self.tpu_index is None or self.state != IndexState.TRAINED
                    or int(self.tpu_index.ntotal) != n0):
                # an ADD drained (or a drop/transfer swapped the engine)
                # mid-rebuild: the snapshot's positional layout is stale —
                # abort cheaply, the watcher retries against fresh state
                self._mutation_counters["compactions_aborted"] += 1
                logger.info("compaction aborted: index changed mid-rebuild")
                return False
            meta = self.id_to_metadata.tolist()
            new_meta = [meta[p] for p in range(n0) if keep[p]] + meta[n0:]
            # deletes that landed after the snapshot: remap onto the new
            # layout (rows the rebuild already dropped map to -1)
            shift = new_n - n0
            carried = {}
            for p, mid in self.tombstones.items():
                if p >= n0:
                    carried[p + shift] = mid  # buffered rows shift down
                elif keep[p]:
                    carried[int(old2new[p])] = mid
            new_tomb = TombstoneSet(carried)
            # the deletion ledger is position-free and must SURVIVE the
            # swap: compaction reclaims rows, never forgets that their
            # ids were deleted (the anti-entropy resurrect guard) — and
            # since ISSUE 12 both version planes ride along: delete
            # versions in the ledger, live write versions beside it (a
            # compaction must not demote a versioned row to legacy, or a
            # stale delete would win against it afterwards)
            new_tomb.ledger_update_versioned(self.tombstones.ledger_items())
            new_tomb.live_versions_update(self.tombstones.live_versions())
            if any(r < new_n for r in carried):
                # graftlint: ok(blocking-under-lock): locked mask scatter (tombstone consistency contract)
                new_index.remove_rows(np.asarray(
                    [r for r in carried if r < new_n], np.int64))
            disk_gens = serialization.list_generations(storage_dir)
            gen = max(self._generation,
                      disk_gens[0][0] if disk_gens else 0) + 1
            new_tomb.layout = gen
            # claim the sidecar version gate BEFORE the commit writes the
            # remapped payload: a remove_ids writer that snapshotted
            # before this swap (stale layout) must skip afterwards, never
            # overwrite the new-layout sidecar — and the engine locks keep
            # any NEW mutation out until the swap below completes
            self._tombstone_version += 1
            with self._tombstone_io_lock:
                self._tombstone_written = max(self._tombstone_written,
                                              self._tombstone_version)
            self._commit_generation(
                storage_dir, gen, new_state, new_meta,
                self.embeddings_buffer, self.cfg,
                extra={"ntotal": new_n, "layout": gen, "compacted": True},
                tombstones=new_tomb.to_payload(),
                io_lock=self._tombstone_io_lock,
                keep=self.versioning.retain_generations,
            )
            self.tpu_index = new_index
            self.id_to_metadata = _MetaStore(new_meta)
            self.tombstones = new_tomb
            self._generation = gen
            self.index_saved_size = new_n
            self._saved_tombstone_version = self._tombstone_version
            self.index_save_time = time.time()
            self._meta_epoch += 1  # in-flight joins retry on the new layout
            self._mutation_counters["compactions"] += 1
        dt = tracing.book("engine.compaction", t0, sink=self.perf,
                          counter="compaction_s")
        logger.info(
            "compacted %d tombstoned rows out (%d -> %d live) into "
            "generation %d in %.3fs", n0 - new_n, n0, new_n, gen, dt)
        return True

    def _thread_tag(self) -> str:
        """Short per-engine tag for worker-thread names (stack dumps and
        thread-leak reports must attribute to a shard, not 'Thread-N')."""
        return (os.path.basename(self.cfg.index_storage_dir or "")
                or f"mem-{id(self):x}")

    def _run_compaction_watcher(self) -> None:
        t = threading.Thread(
            target=_compaction.run_watcher, args=(self, self.mutation_cfg),
            name=f"compaction:{self._thread_tag()}", daemon=True)
        self._compaction_thread = t
        t.start()

    def get_idx_data_num(self) -> Tuple[int, int]:
        with self.buffer_lock:
            buf_total = self.total_data
        index_total = 0
        with self.index_lock:
            if self.tpu_index is not None:
                index_total = self.tpu_index.ntotal
        return buf_total, index_total

    # ------------------------------------------------------------------ train

    def train(self) -> None:
        with self.index_lock:
            if self.state in (IndexState.TRAINING, IndexState.TRAINED, IndexState.ADD):
                return
            self.state = IndexState.TRAINING
        try:
            with tracing.stage("engine.train", sink=self.perf):
                self._train_impl()
        except BaseException:
            # conscious fix vs the reference: a failed (possibly async)
            # training run must not wedge the shard in TRAINING forever —
            # reset so clients see NOT_TRAINED and the error can be retried
            with self.index_lock:
                if self.state == IndexState.TRAINING:
                    self.state = IndexState.NOT_TRAINED
            logger.exception("index training failed")
            raise

    def _train_impl(self) -> None:
        cfg = self.cfg

        with self.buffer_lock:
            if cfg.dim == 0 and self.embeddings_buffer:
                cfg.dim = int(self.embeddings_buffer[0].shape[1])
            if cfg.train_num > 0:
                train_num = cfg.train_num
            elif cfg.train_ratio >= 1.0:
                train_num = self.total_data
            else:
                train_num = int(cfg.train_ratio * self.total_data)
            all_data = (
                np.concatenate(self.embeddings_buffer, axis=0)
                if self.embeddings_buffer
                else np.zeros((0, cfg.dim), np.float32)
            )

        total_data_size = all_data.shape[0]
        train_num = min(train_num, total_data_size)
        # uniform sample over the whole buffer (conscious fix, see module doc)
        rng = np.random.default_rng(0)
        sel = rng.permutation(total_data_size)[:train_num]
        train_data = all_data[sel]

        index = self._init_index(total_data_size)
        logger.info("training %s on %s vectors", type(index).__name__, train_data.shape)
        index.train(train_data)
        index.set_nprobe(cfg.nprobe)
        logger.info("index trained")

        with self.index_lock:
            self.tpu_index = index
            self.state = IndexState.TRAINED
        self.add_buffer_to_index()

    def sync_train(self) -> None:
        self.train()

    def _init_index(self, total_data_size: int):
        cfg = self.cfg
        needs_centroids = cfg.index_builder_type in _IVF_BUILDERS or (
            cfg.faiss_factory and "IVF" in cfg.faiss_factory
        )
        if needs_centroids:
            cfg.centroids = int(cfg.centroids)
            if cfg.centroids == 0 or cfg.infer_centroids:
                cfg.centroids = infer_n_centroids(total_data_size)
                logger.info("inferred cfg.centroids=%d", cfg.centroids)
        index = build_index(cfg)
        self._apply_runtime_knobs(index)
        return index

    def _apply_runtime_knobs(self, index) -> None:
        """Runtime (non-structural) search knobs from cfg.extra — applied at
        build/load AND on upd_cfg, so a live shard can be A/B-flipped
        without retraining. Currently: ``stored_norms`` (IVF-Flat/SQ8 scan;
        False falls back to recomputing ||x||^2 per query — the bit-exact
        reference arm, benchmarks/profile_ivf.py --norms)."""
        if index is not None and hasattr(index, "use_stored_norms"):
            index.use_stored_norms = bool(self.cfg.extra.get("stored_norms", True))

    # ------------------------------------------------------------------ add

    def add_buffer_to_index(self) -> None:
        add_to_index = False
        with self.index_lock:
            if self.state == IndexState.TRAINED:
                add_to_index = True
                self.state = IndexState.ADD
            else:
                logger.info("index add already in progress (state=%s)", self.state)
        if add_to_index:
            # async so the serving thread keeps handling requests while the
            # device runs encode+append (reference: index.py:225-238)
            t = threading.Thread(
                target=self._add_buffer_to_idx,
                name=f"add:{self._thread_tag()}", daemon=True)
            self._add_thread = t
            t.start()

    def _add_buffer_to_idx(self) -> None:
        while True:
            bsz = self.cfg.buffer_bsz
            with self.buffer_lock:
                take, taken_rows = 0, 0
                for e in self.embeddings_buffer:
                    take += 1
                    taken_rows += e.shape[0]
                    if taken_rows >= bsz:
                        break
                chunks = self.embeddings_buffer[:take]
                self.embeddings_buffer = self.embeddings_buffer[take:]
                self.total_data -= taken_rows

            if taken_rows == 0:
                break
            # one booking a drained chunk: concat, the wait for index_lock
            # and the device add (set-up's ingest work, where it happens)
            with tracing.stage("engine.add_drain", sink=self.perf) as drain:
                add_data = np.concatenate(chunks, axis=0)
                with self.index_lock:
                    if self.state != IndexState.ADD or self.tpu_index is None:
                        # a concurrent drop_index tore the index down mid-add:
                        # bail without resetting state (drop already set it)
                        logger.info("add worker: index dropped mid-add, exiting")
                        return
                    self.tpu_index.add(add_data)
                    ntotal = self.tpu_index.ntotal
                    # buffer-aware deletes: rows tombstoned while they were
                    # still buffered keep their positional slot (the metadata
                    # join is positional), so they are added like any row and
                    # masked immediately — under the SAME lock hold, so no
                    # search window can see them live
                    dead_new = self.tombstones.rows_in_range(
                        ntotal - add_data.shape[0], ntotal)
                    if dead_new:
                        # unreachable for unsupported kinds (remove_ids rejects
                        # them up front, so tombstones only exist on maskable
                        # indexes) — but a mask failure here must never kill
                        # the drain worker: that would wedge the engine in ADD
                        # and every search would fail over around it forever
                        try:
                            # graftlint: ok(blocking-under-lock): the locked mask scatter is the tombstone consistency contract — device mutations serialize on index_lock like every launch
                            self.tpu_index.remove_rows(
                                np.asarray(dead_new, np.int64))
                        except Exception:
                            logger.exception(
                                "drain-time tombstone mask failed for rows %s "
                                "— rows serve until compaction", dead_new)
            self.perf.record("engine.add_drain_rows", float(add_data.shape[0]))
            logger.info(
                "added %d vectors in %.3fs (ntotal=%d)",
                add_data.shape[0], drain.dt, ntotal,
            )
            self._maybe_save(ignore_time=False)

        with self.index_lock:
            if self.state == IndexState.ADD:  # don't stomp a concurrent drop
                self.state = IndexState.TRAINED
        # rows appended between the empty-buffer check and the state flip
        # would otherwise be stranded until the NEXT add_batch (the reference
        # shares this race): re-trigger the drain if the buffer refilled
        with self.buffer_lock:
            refilled = self.total_data > 0
        if refilled:
            self.add_buffer_to_index()

    # ------------------------------------------------------------------ query

    # graftlint: ok(blocking-under-lock): the designed locked launch — a search is dispatched under index_lock so that it reads one state of the index; an index without a two-phase form runs its whole search here
    def _launch_device_search(self, query_batch: np.ndarray, top_k: int):
        """The locked device launch behind the batchers, first half: state
        check and launch under ``index_lock``, the fetch left to the
        returned ``collect()``. The lock's job — no search reads the
        index's Python-side arrays while add, growth or compaction replaces
        them (reference rationale at index.py:246-252) — is done once the
        programs are dispatched: they hold their operands, and a later
        program that donates one of them (``models/base._write_rows``) runs
        behind them on the device. So the scheduler may launch the next
        window while this one is uncollected; ``engine.launch_overlapped``
        counts the launches that found one so.

        Routes through the model's two-phase entry
        (``TpuIndex.launch_search``): the flat and IVF indexes dispatch and
        return; a mesh-backed index, or HNSW, runs its already-batched
        entry to the end here (the one-pjit-launch path — the whole merged
        window reaches the chips as a single device program with an on-mesh
        top-k reduce, and results leave the device exactly once:
        parallel/mesh.py) and hands back a finished handle. Models exposing a
        ``launches`` dispatch counter get it diffed around the launch into
        ``device_launches`` (dispatches this window took — 1.0 on the mesh
        path) and ``rows_per_launch`` (merged-window occupancy per
        dispatch), both served through ``perf_stats``. Stages
        (utils/tracing): ``engine.lock_wait``, then ``engine.launch``
        (counter ``device_search_s``), a ``handover`` from the launch's
        start to the fetch's end."""
        with tracing.stage("engine.lock_wait", sink=self.perf) as wait, \
                self.index_lock:
            wait.done()
            if self.state != IndexState.TRAINED:
                raise RuntimeError(
                    NOT_TRAINED_REJECTION_FMT.format(state=self.state))
            launches0 = getattr(self.tpu_index, "launches", None)
            rows = int(query_batch.shape[0])
            # launch to fetch: the model's engine.feed / engine.scan /
            # engine.refine_fetch stages nest inside and add up to it
            launch = tracing.handover("engine.launch", sink=self.perf,
                                      counter="device_search_s", rows=rows)
            with launch:
                pending = self.tpu_index.launch_search(query_batch, top_k)
                launches = None
                if launches0 is not None:
                    launches = launch.extra["launches"] = int(
                        self.tpu_index.launches - launches0)
            if self._windows.inc("launched") - 1 > self._windows["collected"]:
                self.perf.record("engine.launch_overlapped", 1.0)

        def collect():
            try:
                with launch.last():
                    out = pending.collect()
            finally:
                self._windows.inc("collected")
            self.perf.record("device_search_rows", float(rows))
            if launches is not None:
                self.perf.record("device_launches", float(launches))
                if launches > 0:
                    self.perf.record("rows_per_launch", rows / launches)
            return out

        return collect

    def _device_search(self, query_batch: np.ndarray, top_k: int):
        """Launch and collect in one call (the in-process batcher's entry)."""
        return self._launch_device_search(query_batch, top_k)()

    def _launch_and_join(self, launch, return_embeddings: bool) -> SearchHandle:
        """Launch now; the handle's ``collect()`` fetches and joins the
        metadata, under the layout-epoch seqlock.

        ``launch()`` starts a search and returns its fetch, ``fetch() ->
        (scores, indexes, embs_arr|None)``. A compaction swap (or
        drop/recreate) between the device launch and the join would pair
        OLD positional ids with the NEW metadata layout — silent
        wrong-metadata results. The epoch (bumped under both locks by
        every layout replacement, read here before the launch) detects the
        overlap and the collect relaunches on the new layout instead."""

        def launch_once():
            with self.buffer_lock:
                epoch0 = self._meta_epoch
            # DFT_XFERCHECK=1: the launch-to-fetch span is a guarded
            # hot-path section — data crosses the device boundary only
            # through explicit feeds (device_put) and the explicit()
            # fetch scopes down in the blocked-search drivers
            with xfercheck.guarded("engine launch-to-fetch span"):
                return epoch0, launch()

        first = launch_once()

        def collect():
            for attempt in range(8):
                epoch0, fetch = launch_once() if attempt else first
                with xfercheck.guarded("engine launch-to-fetch span"):
                    scores, indexes, embs_arr = fetch()
                with tracing.stage("engine.join", sink=self.perf):
                    with self.buffer_lock:
                        if self._meta_epoch != epoch0:
                            continue  # layout swapped mid-flight: retry on the new one
                        meta_arr, meta_n = self.id_to_metadata.snapshot()
                    return self._join_results(scores, indexes, embs_arr,
                                              return_embeddings, meta_arr, meta_n)
            raise RuntimeError(
                "metadata layout kept changing during search (compaction storm)")

        return SearchHandle(collect)

    def _run_and_join(self, run, return_embeddings: bool):
        """``_launch_and_join`` for a search with no launch half: ``run()``
        is the whole search, made at the collect."""
        return self._launch_and_join(lambda: run, return_embeddings).collect()

    def search(
        self, query_batch: np.ndarray, top_k: int = 100, return_embeddings: bool = False
    ) -> Tuple[np.ndarray, List[List[object]], Optional[List[List[np.ndarray]]]]:
        query_batch = np.asarray(query_batch, np.float32)
        if not return_embeddings:
            # hot path: concurrent callers share device launches (state
            # re-checked under the lock inside _device_search)
            run = lambda: self._batcher.search(query_batch, top_k) + (None,)
        else:
            run = lambda: self._search_reconstruct(query_batch, top_k)
        return self._run_and_join(run, return_embeddings)

    def launch_batched(
        self, query_batch: np.ndarray, top_k: int = 100, return_embeddings: bool = False
    ) -> SearchHandle:
        """The already-batched search entry for the serving scheduler
        (serving/scheduler.py), in two halves: the locked device launch
        now, the fetch and the metadata join at the handle's ``collect()``,
        which returns what ``search`` returns — same launch, same join —
        but WITHOUT the in-process SearchBatcher in front. The scheduler has
        already coalesced concurrent callers into ``query_batch``, and it
        keeps two windows in flight: the next one is launched, behind this
        one on the device, before this one is collected. What the index
        offers decides how much of a window the launch is
        (``_launch_device_search``); ``return_embeddings`` stays atomic
        under ``index_lock`` (``_search_reconstruct``), so its launch is the
        whole search and its handle comes back finished."""
        query_batch = np.asarray(query_batch, np.float32)
        if not return_embeddings:
            def launch():
                fetch = self._launch_device_search(query_batch, top_k)
                return lambda: fetch() + (None,)
        else:
            def launch():
                found = self._search_reconstruct(query_batch, top_k)
                return lambda: found
        return self._launch_and_join(launch, return_embeddings)

    def search_batched(
        self, query_batch: np.ndarray, top_k: int = 100, return_embeddings: bool = False
    ) -> Tuple[np.ndarray, List[List[object]], Optional[List[List[np.ndarray]]]]:
        """``launch_batched`` and its collect in one call."""
        return self.launch_batched(query_batch, top_k, return_embeddings).collect()

    # ------------------------------------------------- generation-pinned reads

    def current_generation(self) -> int:
        """Newest committed snapshot generation of this shard (0 = none
        committed yet) — what a client pins for point-in-time reads."""
        with self.index_lock:
            return self._generation

    # graftlint: ok(blocking-under-lock): pinned-snapshot launches serialize on their own leaf lock by design — the snapshot index is private to this path and never contends with the serving locks
    def search_at_generation(self, query_batch: np.ndarray, top_k: int = 100,
                             generation: int = 0,
                             return_embeddings: bool = False):
        """Point-in-time search against a RETAINED committed generation:
        results reflect exactly the rows (and tombstones) of snapshot
        ``generation``, regardless of every mutation since — the read
        mode the reference system cannot express at all. The snapshot is
        loaded lazily from the generation's manifest files (one cached at
        a time under ``_pinned_lock``; raise ``DFT_RETAIN_GENERATIONS``
        to keep a deeper window) and serves the generation's INDEXED
        rows — its buffered-but-unindexed tail is not searchable, same as
        it was not searchable when the generation was committed. Pruned
        or unknown generations raise a clear application error so a
        client can walk to a replica that still retains them."""
        query_batch = np.asarray(query_batch, np.float32)
        gen = int(generation)
        with self._pinned_lock:
            cached = self._pinned_cache
            if cached is None or cached[0] != gen:
                self._pinned_cache = cached = (
                    gen, self._load_generation_snapshot(gen))
            snap_index, meta_arr, meta_n = cached[1]
            scores, indexes = snap_index.search(query_batch, top_k)
            embs_arr = None
            if return_embeddings:
                flat = indexes.reshape(-1)
                if snap_index.ntotal == 0:
                    rec = np.zeros((flat.shape[0], query_batch.shape[1]),
                                   np.float32)
                else:
                    safe = np.where(flat >= 0, flat, 0)
                    rec = np.array(snap_index.reconstruct_batch(safe))
                    rec[flat < 0] = 0.0
                embs_arr = rec.reshape(indexes.shape + (query_batch.shape[1],))
        return self._join_results(scores, indexes, embs_arr,
                                  return_embeddings, meta_arr, meta_n)

    def _load_generation_snapshot(self, gen: int):
        """Load one retained generation read-only: verified manifest
        files -> (index, meta array, meta length) with the generation's
        OWN tombstone sidecar applied (a pinned read honors exactly the
        deletes committed with it — later deletes are the point of
        pinning). Memory note: this is a second resident copy of the
        shard; the cache holds ONE generation at a time."""
        storage_dir = self.cfg.index_storage_dir
        if not storage_dir:
            raise RuntimeError(
                "generation-pinned reads need a persistent shard "
                "(no index_storage_dir configured)")
        manifest = None
        for g, mpath in serialization.list_generations(storage_dir):
            if g == gen:
                manifest = serialization.load_manifest(mpath)
                break
        if manifest is None:
            raise RuntimeError(
                f"generation {gen} is not retained at {storage_dir} "
                "(pruned or never committed; raise DFT_RETAIN_GENERATIONS "
                "to keep a deeper point-in-time window)")

        def gen_path(key):
            return os.path.join(storage_dir, manifest["files"][key]["name"])

        snap_index = index_from_state_dict(load_state(gen_path("index")))
        with open(gen_path("meta"), "rb") as f:
            meta = pickle.load(f)
        meta = meta[: snap_index.ntotal]
        tomb = TombstoneSet.from_payload(
            _tombstones.load_generation_payload(storage_dir, manifest))
        dead = [p for p in tomb.rows() if p < snap_index.ntotal]
        if dead:
            snap_index.remove_rows(np.asarray(dead, np.int64))
        store = _MetaStore(meta)
        meta_arr, meta_n = store.snapshot()
        logger.info("pinned generation %d of %s for point-in-time reads "
                    "(%d rows, %d tombstoned)", gen, storage_dir,
                    snap_index.ntotal, len(dead))
        return snap_index, meta_arr, meta_n

    # graftlint: ok(blocking-under-lock): deliberate locked launches — ids and reconstructed embeddings must come from one atomic index state
    def _search_reconstruct(self, query_batch: np.ndarray, top_k: int):
        """Search + embedding reconstruction. Embeddings must come from the
        SAME index state that produced the ids, so this path stays atomic
        under index_lock instead of riding any batcher."""
        with self.index_lock:
            if self.state != IndexState.TRAINED:
                raise RuntimeError(
                    NOT_TRAINED_REJECTION_FMT.format(state=self.state))
            with tracing.stage("engine.reconstruct_search", sink=self.perf,
                               counter="reconstruct_search_s"):
                scores, indexes = self.tpu_index.search(query_batch, top_k)
            flat = indexes.reshape(-1)
            if self.tpu_index.ntotal == 0:
                # trained-but-empty window: all ids are -1
                rec = np.zeros((flat.shape[0], query_batch.shape[1]), np.float32)
            else:
                safe = np.where(flat >= 0, flat, 0)
                # designed host round-trip (the ok(host-sync) contract:
                # reconstruct returns host rows), marked explicit for the
                # transfer guard
                with xfercheck.explicit("reconstruct embeddings fetch"):
                    rec = np.array(self.tpu_index.reconstruct_batch(safe))
                rec[flat < 0] = 0.0
            embs_arr = rec.reshape(indexes.shape + (query_batch.shape[1],))
        return scores, indexes, embs_arr

    def _join_results(self, scores, indexes, embs_arr, return_embeddings,
                      meta_arr, meta_n):
        # vectorized metadata join: the caller (_run_and_join) snapshots
        # (meta_arr, meta_n) under buffer_lock AFTER verifying the layout
        # epoch; the join itself is safe outside the lock because the
        # store is append-only past the snapshotted length (see _MetaStore
        # docstring)
        valid = indexes != -1
        # single host-side pass (invalid slots are -1, always < meta_n, so
        # the max doubles as the valid-id check)
        max_id = np.max(indexes, initial=-1)
        if max_id >= meta_n:
            # loud failure on index/metadata desync (e.g. a concurrent
            # drop_index mid-search) — never serve clipped/stale metadata
            raise IndexError(
                f"search returned id {max_id} >= metadata size {meta_n}"
            )
        safe = np.where(valid, indexes, 0)
        joined = meta_arr.take(safe.ravel()).reshape(indexes.shape)
        joined[~valid] = None
        results_meta = joined.tolist()
        embs = None
        if return_embeddings:
            nq, k = indexes.shape
            embs = [[embs_arr[i, j] for j in range(k)] for i in range(nq)]
        return scores, results_meta, embs

    def perf_stats(self, raw: bool = False) -> dict:
        """Per-index stage and launch summary: ``device_search_s`` (each
        merged window's launch, its start to its fetch's end: with two
        windows in flight, the wait on the device behind the one ahead
        too), ``device_search_rows`` (rows per
        merged window) and the stages that make the launch up
        (``engine.feed``, ``engine.scan``, ``engine.refine_fetch``) or
        surround it (``engine.lock_wait``, ``engine.join``);
        ``reconstruct_search_s`` (search+reconstruct launches);
        ``engine.train`` and ``engine.add_drain`` / ``engine.add_drain_rows``
        (seconds and rows of each drained buffer chunk — the "_s" suffix on
        summary keys is historical; rows are counts); for mesh-backed
        indexes additionally
        ``device_launches`` (device dispatches per merged window — the
        one-launch serving contract means max_s == 1.0) and
        ``rows_per_launch`` (window occupancy per dispatch). Served
        through IndexServer.get_perf_stats under ``"engine"``; ``raw``
        adds the bucket histograms (the Prometheus exporter's view).
        ``engine.scan_fused`` counts the ``engine.scan`` blocks whose scan
        ran the fused Pallas ADC kernel (models/ivf.py books it); it stands
        at zero beside ``engine.scan`` until one does, so a scan that fell
        back to XLA reads 0 of n and not "no such row".
        ``engine.scan_rows`` (a count row, shown the same way) sums the
        rows of its store an exact scan read, capacity padding included,
        one record an ``engine.scan`` (models/flat.py books it);
        ``engine.scan_prefilter`` (a count row, shown the same way) counts
        the ``engine.scan`` blocks of an exact scan whose per-chunk top-k
        chose its segments by their maxima before sorting
        (``ops/distance.topk_prefilters``; models/flat.py books it);
        ``engine.scan_listmajor`` (a count row, shown the same way) counts
        the ``engine.scan`` blocks of an IVF-flat index whose probe scan
        took the list-major order (``models/ivf.listmajor_tiling``;
        ``IVFFlatIndex`` books it);
        ``engine.scan_list_rows`` and ``engine.scan_list_rows_skipped``
        (count rows, shown the same way) sum, one record a list-major
        ``engine.scan``, the list rows of its tiles at whole capacity (what
        a scan of whole padded lists gathers and multiplies) and those of
        them in sub-blocks past the end of their list, which the scan
        never gathers (``IVFFlatIndex._book_list_rows`` books both);
        ``engine.scan_adc_cols`` and ``engine.scan_adc_cols_skipped`` (count
        rows, shown the same way) sum, one record an ``engine.scan`` of an
        IVF-PQ index, the candidate columns of its (query, probe) pairs'
        whole capacity and those of them the ADC scan did not compute (the
        fused kernel stops at the end of each list; the XLA one-hot skips
        none; ``IVFPQIndex._book_adc_cols`` books both, for a mesh-sharded
        PQ index too, every chip's columns counted once);
        ``engine.mesh_place`` (a mesh index alone) is one record a
        replicated operand placed on the rank's mesh for a launch
        (``parallel/mesh._replicated``);
        ``engine.store_grow`` is one record a reallocation of a
        ``DeviceVectorStore`` (models/base.py), allocation to the end of
        the copy. ``engine.launch_overlapped`` (a count row, at zero beside
        ``device_search_s`` until booked) is one record a merged window
        that was launched while an earlier window of this index was not yet
        collected: over ``device_search_s``'s count, the share of windows
        the scheduler put on the device behind another."""
        out = self.perf.summary(raw=raw)
        if "device_search_s" in out:
            out.setdefault("engine.launch_overlapped", tracing.zero_row())
        if "engine.scan" in out:
            for name in ("engine.scan_fused", "engine.scan_rows",
                         "engine.scan_prefilter", "engine.scan_listmajor",
                         "engine.scan_list_rows", "engine.scan_list_rows_skipped",
                         "engine.scan_adc_cols", "engine.scan_adc_cols_skipped"):
                out.setdefault(name, tracing.zero_row())
        return out

    def get_centroids(self):
        with self.index_lock:
            if self.state != IndexState.TRAINED:
                raise RuntimeError("Server index is not trained")
            return self.tpu_index.get_centroids()

    def set_nprobe(self, nprobe: int) -> None:
        self.cfg.nprobe = nprobe
        with self.index_lock:
            if self.tpu_index is not None:
                self.tpu_index.set_nprobe(nprobe)

    def get_state(self) -> IndexState:
        with self.index_lock:
            return self.state

    def get_ids(self) -> set:
        id_idx = self.cfg.custom_meta_id_idx
        # Snapshot under the locks (torn-read guard, reference
        # index.py:367-368; tombstones ride index_lock), then build the
        # set outside: the O(ntotal) Python iteration must not stall
        # concurrent add_index_data. Safe because the store is append-only
        # past the snapshotted length (_MetaStore docstring).
        with self.buffer_lock, self.index_lock:
            meta_arr, meta_n = self.id_to_metadata.snapshot()
            dead = frozenset(self.tombstones.rows())
        return {meta[id_idx]
                for p, meta in enumerate(meta_arr[:meta_n].tolist())
                if meta and p not in dead}

    def upd_cfg(self, cfg: IndexCfg) -> None:
        # graftlint: atomic(cfg): operator-initiated whole-object publish — a reader holds either the old or the new IndexCfg reference, never a torn one; cross-field coherence is not promised across an upd_cfg by design
        self.cfg = cfg
        with self.index_lock:
            if self.tpu_index is not None:
                # nprobe doubles as efSearch for graph indexes (reference
                # _override_nprobe, index.py:487-495)
                self.tpu_index.set_nprobe(cfg.nprobe)
                self._apply_runtime_knobs(self.tpu_index)

    # ------------------------------------------------------------------ persistence

    def save(self) -> Union[bool, None]:
        state = self.get_state()
        if state == IndexState.TRAINED:
            return self._maybe_save(ignore_time=True)
        elif state == IndexState.ADD:
            # trigger save on completion of the in-flight add
            self.index_save_time = 0
        else:
            logger.info("index is not trained, skip saving")
            return False

    def retire(self) -> None:
        """Permanently stop persistence for this engine instance: the
        save watcher exits and ``_maybe_save`` becomes a no-op. Called
        when a server swaps this engine out of its registry — the
        storage dir now belongs to the replacement, and a late autosave
        from this instance would commit stale state as the newest
        generation there. Joins the tracked worker threads bounded: the
        watchers wake on the retired event and exit immediately; a
        still-running train/add worker past the timeout is harmless
        (``_maybe_save`` no-ops once retired), so the join is
        best-effort rather than a hostage-taking wait on device work."""
        self._retired.set()
        for t in (self._save_thread, self._compaction_thread,
                  self._train_thread, self._add_thread):
            if t is not None and t is not threading.current_thread():
                t.join(timeout=1.0)

    def _maybe_save(self, ignore_time: bool = False) -> bool:
        if self._retired.is_set():
            return False
        if not ignore_time:
            if self.cfg.save_interval_sec <= 0:
                return False
            if time.time() - self.index_save_time < self.cfg.save_interval_sec:
                return False

        with self.buffer_lock, self.index_lock:
            if self.tpu_index is None or (
                    self.tpu_index.ntotal == self.index_saved_size
                    and self._tombstone_version
                    == self._saved_tombstone_version):
                return False
            storage_dir = self.cfg.index_storage_dir

            # torn-snapshot-proof save (the _commit_generation protocol):
            # seed the generation number from BOTH the in-memory counter
            # and the newest generation on disk: a
            # fresh engine over a dir with existing generations (rank
            # restarted without --load-index, or create_index on a rejoined
            # rank) must not recycle a low number — prune_generations would
            # immediately delete the snapshot it just committed and loads
            # would roll back to the stale newest-on-disk generation
            disk_gens = serialization.list_generations(storage_dir)
            gen = max(self._generation, disk_gens[0][0] if disk_gens else 0) + 1
            # graftlint: ok(blocking-under-lock): designed locked fetch — the snapshot must capture index+buffer+meta at one atomic point
            state = self.tpu_index.state_dict()
            self._commit_generation(
                storage_dir, gen, state, self.id_to_metadata.tolist(),
                self.embeddings_buffer, self.cfg,
                extra={"ntotal": int(self.tpu_index.ntotal),
                       "layout": self.tombstones.layout},
                tombstones=self.tombstones.to_payload(),
                io_lock=self._tombstone_io_lock,
                keep=self.versioning.retain_generations,
            )
            self._generation = gen

            self.index_saved_size = self.tpu_index.ntotal
            self._saved_tombstone_version = self._tombstone_version
            self.index_save_time = time.time()
            logger.info("saved index (%d vectors) to %s as generation %d",
                        self.index_saved_size, storage_dir, gen)
            return True

    @staticmethod
    def _commit_generation(storage_dir: str, gen: int, state: dict,
                           meta: list, buffer: list, cfg: IndexCfg,
                           extra: Optional[dict] = None,
                           tombstones: Optional[dict] = None,
                           io_lock=None, keep: int = 2) -> None:
        """ONE copy of the torn-snapshot commit protocol, shared by the
        normal save path, compaction, and the shard-transfer import: every
        file of generation ``gen`` is written atomically
        (tmp+fsync+rename), and the generation only becomes loadable when
        its MANIFEST — with per-file sha256 — lands LAST. kill -9 at any
        byte offset leaves either the previous committed generation intact
        or a complete new one; load verifies checksums and quarantines
        anything in between (supersedes the reference's acknowledged
        torn-write TODO, index.py:443-446). ``tombstones`` is the
        mutation sidecar payload committed WITH the generation (so a
        loaded generation always pairs with the tombstone set valid for
        its positional layout); after the manifest lands, the standalone
        ``tombstones.json`` is refreshed from the same payload — ordering
        that keeps every crash point on a consistent (generation, sidecar)
        pair (mutation/tombstones.py). Also refreshes the unversioned
        cfg.json convenience copy (get_config_path readers expect the
        fixed name; it is NOT part of the committed set) and prunes to the
        newest ``keep`` generations (floored at 2 — the crash-fallback
        pair; instance callers pass ``versioning.retain_generations``, the
        point-in-time read window)."""
        os.makedirs(storage_dir, exist_ok=True)
        ts_payload = (tombstones if tombstones is not None
                      else TombstoneSet().to_payload())
        plan = {
            "index": ("npz", "wb", lambda f: save_state(f, state)),
            "meta": ("pkl", "wb", lambda f: pickle.dump(meta, f)),
            "buffer": ("pkl", "wb", lambda f: pickle.dump(buffer, f)),
            "cfg": ("json", "w",
                    lambda f: f.write(cfg.to_json_string() + "\n")),
            "tombstones": ("json", "w",
                           lambda f: f.write(
                               _tombstones.dump_payload(ts_payload) + "\n")),
        }
        entries = {}
        for key, (ext, mode, write_fn) in plan.items():
            name = serialization.generation_filename(key, gen, ext)
            digest = atomic_write(os.path.join(storage_dir, name), write_fn, mode)
            entries[key] = {"name": name, "sha256": digest}
        serialization.write_manifest(storage_dir, gen, entries, extra=extra)
        # the standalone sidecar shares its fixed tmp path with the
        # per-mutation writer (_write_tombstone_sidecar), which runs
        # OUTSIDE the engine locks — instance callers pass their
        # _tombstone_io_lock so the two can never interleave on the tmp
        # file (a torn rename would read as garbage and drop every delete
        # acked since the last committed generation). import_snapshot
        # commits onto a fresh engine's dir with no concurrent writers
        # and passes None.
        if io_lock is not None:
            with io_lock:
                _tombstones.write_sidecar(storage_dir, ts_payload)
        else:
            _tombstones.write_sidecar(storage_dir, ts_payload)
        atomic_write(
            os.path.join(storage_dir, "cfg.json"),
            lambda f: f.write(cfg.to_json_string() + "\n"), "w",
        )
        # retained-generation bound (DFT_RETAIN_GENERATIONS): beyond the
        # crash-fallback pair, extra retained generations are the
        # point-in-time read window for search_at_generation
        serialization.prune_generations(storage_dir, keep=max(2, int(keep)))

    # ------------------------------------------------------- shard transfer

    def export_snapshot(self) -> dict:
        """The shard-transfer unit for replica join (parallel/replication).

        One atomic capture — index state_dict + full metadata + the
        not-yet-indexed buffer (the delta a joiner replays through the
        normal add path) + cfg — taken under both locks, exactly the set
        a MANIFEST-committed save would write. Shipped over the wire as
        a KIND_SHARD_DATA frame (ndarrays ride the raw tensor path);
        ``import_snapshot`` on the receiving rank commits it to disk as
        a generation of its own before serving, so the transfer inherits
        the torn-snapshot guarantees of PR 3's persistence layer."""
        with self.buffer_lock, self.index_lock:
            # graftlint: ok(blocking-under-lock): designed locked fetch — the transfer snapshot must capture index+buffer+meta at one atomic point (same contract as _maybe_save)
            state = self.tpu_index.state_dict() if self.tpu_index is not None else None
            return {
                "format": 1,
                "generation": self._generation,
                "state": state,
                "state_name": self.state.name,
                "ntotal": int(self.tpu_index.ntotal) if self.tpu_index is not None else 0,
                "meta": self.id_to_metadata.tolist(),
                "buffer": list(self.embeddings_buffer),
                "cfg_json": self.cfg.to_json_string(),
                # mutation state travels with the shard: a replica joined
                # from this snapshot must not resurrect deleted rows
                "tombstones": self.tombstones.to_payload(),
            }

    @classmethod
    def import_snapshot(cls, snapshot: dict, storage_dir: str,
                        cfg: IndexCfg = None) -> "Index":
        """Install a transferred shard snapshot on THIS rank.

        A trained snapshot is first committed to ``storage_dir`` as a
        manifest-committed generation (atomic per-file writes + sha256
        MANIFEST landing last — the PR 3 commit protocol), so a crash
        right after the transfer restarts from the transferred shard
        instead of an empty one; then the engine restores from it and
        replays the buffer delta through the normal async add path. An
        untrained snapshot (no index yet) just replays its buffer, which
        re-triggers training at the configured threshold."""
        import json as _json

        if cfg is None:
            kwargs = _json.loads(snapshot["cfg_json"])
            kwargs.update(kwargs.pop("extra", {}))
            cfg = IndexCfg(**kwargs)
        cfg.index_storage_dir = storage_dir
        meta = list(snapshot.get("meta") or [])
        buffer = [np.asarray(b, np.float32)
                  for b in (snapshot.get("buffer") or [])]
        tomb = TombstoneSet.from_payload(snapshot.get("tombstones"))
        state = snapshot.get("state")
        if state is None:
            # nothing trained at the source: replay the raw buffer
            result = cls(cfg)
            result.tombstones = tomb
            # watermarks only: the rows are about to be replayed below,
            # so live-version entries are NOT stale here
            result._seed_version_state(prune=False)
            offset = 0
            for chunk in buffer:
                n = chunk.shape[0]
                result.add_batch(chunk, meta[offset:offset + n])
                offset += n
            return result

        tpu_index = index_from_state_dict(state)
        disk_gens = serialization.list_generations(storage_dir)
        gen = max(int(snapshot.get("generation", 0)),
                  disk_gens[0][0] if disk_gens else 0) + 1
        cls._commit_generation(
            storage_dir, gen, state, meta, buffer, cfg,
            extra={"ntotal": int(tpu_index.ntotal), "transferred": True,
                   "layout": tomb.layout},
            tombstones=tomb.to_payload(),
            keep=VersioningCfg.from_env().retain_generations,
        )
        logger.info(
            "imported transferred shard (%d vectors, %d buffered) into %s "
            "as generation %d", tpu_index.ntotal,
            sum(b.shape[0] for b in buffer), storage_dir, gen)
        result = cls._restore(cfg, tpu_index, meta, buffer, tombstones=tomb)
        result._generation = gen
        result.index_saved_size = tpu_index.ntotal
        return result

    @classmethod
    def from_storage_dir(
        cls, index_storage_dir: str, cfg: IndexCfg = None, ignore_buffer: bool = True
    ) -> Union[None, "Index"]:
        """Restore a shard (reference: index.py:284-344). Returns None when
        nothing loadable exists; re-adds a consistent leftover buffer, else
        truncates metadata to index size.

        Generations are tried NEWEST first: a manifest whose files fail the
        sha256 check (torn save — crash or disk corruption) is quarantined
        (renamed under ``quarantine/``, never deleted) and the previous
        complete generation loads instead, so a rank killed at any byte
        offset of a save still comes back with its last committed snapshot.
        Pre-manifest flat checkpoints (index.npz + meta.pkl) load through
        the legacy path.
        """
        stale = serialization.quarantine_stale_tmps(index_storage_dir)
        if stale:
            logger.warning("quarantined %d abandoned .tmp file(s): %s",
                           len(stale), stale)
        chosen = None
        fallbacks = 0
        for gen, mpath in serialization.list_generations(index_storage_dir):
            try:
                manifest = serialization.load_manifest(mpath)
                errors = serialization.verify_manifest(index_storage_dir, manifest)
            except (OSError, ValueError) as e:
                errors = [f"unreadable manifest: {e}"]
            if not errors:
                chosen = (gen, manifest)
                break
            reason = "; ".join(errors)
            logger.warning(
                "generation %d at %s is torn (%s): quarantining and falling "
                "back to the previous generation", gen, index_storage_dir, reason,
            )
            serialization.quarantine_generation(index_storage_dir, gen, reason)
            fallbacks += 1

        if chosen is None:
            return cls._from_legacy_layout(index_storage_dir, cfg, ignore_buffer)

        gen, manifest = chosen
        # data files newer than the chosen generation have no manifest (the
        # save died before its commit point): incomplete by construction
        orphans = serialization.quarantine_orphans(index_storage_dir, newer_than=gen)
        if orphans:
            logger.warning("quarantined %d uncommitted newer file(s): %s",
                           len(orphans), orphans)

        def gen_path(key):
            return os.path.join(index_storage_dir, manifest["files"][key]["name"])

        tpu_index = index_from_state_dict(load_state(gen_path("index")))
        with open(gen_path("meta"), "rb") as f:
            meta = pickle.load(f)
        assert len(meta) >= tpu_index.ntotal, (
            "Deserialized meta list should be at least of index size"
        )
        buffer = []
        if not ignore_buffer:
            with open(gen_path("buffer"), "rb") as f:
                buffer = pickle.load(f)
        if cfg is None:
            cfg = IndexCfg.from_json(gen_path("cfg"))
        # tombstone recovery: the generation's OWN sidecar applies
        # unconditionally (positions committed with the rows); the
        # standalone sidecar merges positionally when its layout epoch
        # matches, and BY ID otherwise — a crash that tears the
        # generation a post-compaction delete was keyed to must still
        # honor the delete on the fallback layout (mutation/tombstones.py)
        tomb = TombstoneSet.from_payload(
            _tombstones.load_generation_payload(index_storage_dir, manifest))
        side = _tombstones.load_sidecar(index_storage_dir)
        if side is not None:
            if int(side.get("layout", 0)) == tomb.layout:
                tomb.merge_payload(side)
            else:
                _apply_sidecar_by_id(tomb, side, meta,
                                     cfg.custom_meta_id_idx,
                                     index_storage_dir)
        result = cls._restore(cfg, tpu_index, meta, buffer, tombstones=tomb)
        result._generation = gen
        result._mutation_counters["load_fallbacks"] = fallbacks
        return result

    @classmethod
    def _from_legacy_layout(
        cls, index_storage_dir: str, cfg: IndexCfg, ignore_buffer: bool
    ) -> Union[None, "Index"]:
        """Pre-manifest checkpoints: flat index.npz/meta.pkl/buffer.pkl
        written in rename order (meta/buffer/cfg before index)."""
        index_file, meta_file, buffer_file, cfg_file = get_index_files(index_storage_dir)
        if not os.path.exists(index_file):
            logger.info("no index found at %s", index_file)
            return None

        tpu_index = index_from_state_dict(load_state(index_file))

        if not os.path.exists(meta_file):
            raise RuntimeError("no meta file found. Can't use index.")
        with open(meta_file, "rb") as f:
            meta = pickle.load(f)
        assert len(meta) >= tpu_index.ntotal, (
            "Deserialized meta list should be at least of index size"
        )

        buffer = []
        if not ignore_buffer and os.path.exists(buffer_file):
            with open(buffer_file, "rb") as f:
                buffer = pickle.load(f)

        if cfg is None:
            cfg = IndexCfg.from_json(cfg_file) if os.path.isfile(cfg_file) else IndexCfg()
        # pre-manifest checkpoints never compacted, so their layout epoch
        # is 0: a standalone sidecar with layout 0 applies directly
        tomb = None
        side = _tombstones.load_sidecar(index_storage_dir)
        if side is not None and int(side.get("layout", 0)) == 0:
            tomb = TombstoneSet.from_payload(side)
        return cls._restore(cfg, tpu_index, meta, buffer, tombstones=tomb)

    @classmethod
    def _restore(cls, cfg: IndexCfg, tpu_index, meta: list, buffer: list,
                 tombstones: Optional[TombstoneSet] = None) -> "Index":
        """Shared restore tail: wire a loaded (index, meta, buffer) triple
        into a TRAINED engine, re-adding a consistent leftover buffer and
        truncating metadata otherwise. ``tombstones`` (the recovered set)
        is installed and re-applied to the device BEFORE the buffer
        replay kicks off, so a dead buffered row is masked the moment its
        drain chunk lands — a restart never resurrects a deleted row."""
        result = cls(cfg)
        result.tpu_index = tpu_index
        result.state = IndexState.TRAINED
        result.upd_cfg(cfg)
        if tombstones is not None:
            result.tombstones = tombstones
            dead_indexed = [p for p in tombstones.rows()
                            if p < tpu_index.ntotal]
            if dead_indexed:
                tpu_index.remove_rows(np.asarray(dead_indexed, np.int64))

        buffer_size = sum(v.shape[0] for v in buffer)
        if len(meta) == tpu_index.ntotal + buffer_size:
            result.id_to_metadata = _MetaStore(meta)
            result.embeddings_buffer = buffer
            result.total_data = buffer_size
            if buffer_size > 0:
                result.add_buffer_to_index()
        else:
            if buffer_size:
                logger.warning(
                    "metadata size %d != index+buffer %d: ignoring buffer, truncating meta",
                    len(meta), tpu_index.ntotal + buffer_size,
                )
            result.id_to_metadata = _MetaStore(meta[: tpu_index.ntotal])
        result._seed_version_state()
        return result

    def _seed_version_state(self, prune: bool = True) -> None:
        """Post-restore version bookkeeping: re-seed the per-writer
        watermarks from the recovered version planes, and (``prune``)
        drop live-version entries whose rows did not survive the restore
        (a truncated buffer) — a live version without a live row would
        gate the anti-entropy re-pull of that very row forever."""
        with self.buffer_lock, self.index_lock:
            pairs = (self.tombstones.ledger_items()
                     + self.tombstones.live_versions())
            if not pairs:
                return
            for _k, v in pairs:
                self._observe_version_locked(_versions.version_key(v))
            live_pairs = self.tombstones.live_versions()
            if not prune or not live_pairs:
                return
            meta_arr, meta_n = self.id_to_metadata.snapshot()
            dead_rows = frozenset(self.tombstones.rows())
            id_idx = self.cfg.custom_meta_id_idx
            live_keys = {_id_match_key(mid) for _p, mid, _m in
                         _iter_live_ids(meta_arr, meta_n, dead_rows, id_idx)}
            for k, _v in live_pairs:
                if k not in live_keys:
                    self.tombstones.drop_live_version(k)

    def _run_save_watcher(self) -> None:
        def _watch(idx: "Index"):
            # the retired event doubles as the sleep: retire() wakes the
            # watcher immediately instead of leaking it one last interval
            while not idx._retired.wait(idx.cfg.save_interval_sec):
                idx._maybe_save(ignore_time=False)

        t = threading.Thread(target=_watch, args=(self,),
                             name=f"save:{self._thread_tag()}", daemon=True)
        self._save_thread = t
        t.start()

    # kept for API parity with the reference's static helper
    infer_n_centroids = staticmethod(infer_n_centroids)
