"""Index model interface + device-resident storage primitives.

The model zoo replaces the FAISS index types the reference consumes
(distributed_faiss/index.py:25-100). Two storage primitives solve the central
TPU design problem — XLA wants static shapes, an ANN index wants to grow:

- ``DeviceVectorStore``: a flat corpus as one (capacity, ...) HBM array.
  Capacity grows by power-of-two reallocation; writes are bucketed
  ``dynamic_update_slice`` calls so the number of compiled programs stays
  O(log) in corpus size. Rows past ``ntotal`` are masked in every kernel.

- ``PaddedLists``: ``nlist`` inverted lists as rectangular (nlist, cap, ...)
  HBM arrays with a per-list fill count. Appends are host-planned (offset
  bookkeeping in numpy) + one device scatter; capacity doubles when the
  fullest list would overflow. Probed-list access is a plain gather, which
  XLA handles with static shapes.

Convention: models speak FAISS-style at their boundary — ``search`` returns
(D, I) with D ascending for l2 / descending inner products for dot, ids are
int64, missing results are id -1 (reference behavior via FAISS C++).
"""

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from distributed_faiss_tpu.ops import distance
from distributed_faiss_tpu.utils import tracing, xfercheck


def _next_pow2(n: int, minimum: int) -> int:
    c = minimum
    while c < n:
        c *= 2
    return c


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_rows(data, block, start):
    return jax.lax.dynamic_update_slice(data, block, (start,) + (0,) * (data.ndim - 1))


@functools.partial(jax.jit, donate_argnums=(0,))
def _mask_rows_false(live, idx):
    """Scatter False into a (cap,) bool live mask at ``idx``; out-of-range
    indices (the bucket padding sentinel == cap) are dropped."""
    return live.at[idx].set(False, mode="drop")


@jax.jit
def row_norms_f32(rows):
    """Exact fp32 ``||row||^2`` over the minor axis.

    The ONE norm formula shared by add-time norm storage (models/ivf.py
    norms sidecar, mesh.py's sharded variant) and every XLA recompute
    fallback (_ivf_flat_search and the sharded masked/routed scans call
    this on their decoded blocks): a minor-axis ``jnp.sum(r * r)`` of the
    fp32-decoded rows, which XLA reduces in the same order regardless of
    the leading batch shape — so a stored norm is bit-identical to an
    in-scan recompute and switching between them cannot reorder top-k
    ties. The one necessary inline copy is the Pallas flat-scan kernel's
    in-VMEM recompute (ops/flat_pallas.py — a jitted helper can't be
    called from a kernel body); it states the same formula and is pinned
    by the same golden-equality tests (tests/test_stored_norms.py).
    """
    r = rows.astype(jnp.float32)
    return jnp.sum(r * r, axis=-1)


class DeviceVectorStore:
    """Growable row store in device HBM (rows: vectors or code tuples)."""

    MIN_CAP = 4096
    WRITE_BUCKET = 1024  # row-count buckets for dynamic_update_slice programs

    def __init__(self, row_shape: Tuple[int, ...], dtype, min_cap: int = None):
        self.row_shape = tuple(row_shape)
        self.dtype = dtype
        self.min_cap = min_cap or self.MIN_CAP
        self.cap = 0
        self.ntotal = 0
        self.data = None  # jnp (cap, *row_shape)
        # tombstone mask (mutation subsystem): (cap,) bool, False = deleted.
        # None until the first deletion — the scan entries then trace the
        # exact pre-mutation program (delete-nothing byte identity).
        self.live = None

    def _ensure(self, needed_rows: int):
        # capacity covers ntotal + bucketed write length, so the clamped
        # dynamic_update_slice can never shift a write onto live rows
        bucket = _next_pow2(needed_rows, self.WRITE_BUCKET)
        target = self.ntotal + bucket
        if self.cap >= target:
            return
        newcap = _next_pow2(target, self.min_cap)
        # one booking a growth (the first allocation included), to the end of
        # the copy: old and new store are both alive until then, which is
        # what bounds the rows a chip can hold (docs/OPERATIONS.md)
        with tracing.stage("engine.store_grow"):
            if self.data is None:
                self.data = jnp.zeros((newcap,) + self.row_shape, self.dtype)
            else:
                pad = [(0, newcap - self.cap)] + [(0, 0)] * len(self.row_shape)
                self.data = jnp.pad(self.data, pad)
            if self.live is not None:
                # new capacity rows are live until masked
                self.live = jnp.pad(self.live, (0, newcap - self.cap),
                                    constant_values=True)
            jax.block_until_ready((self.data, self.live))
        self.cap = newcap

    def mask_rows(self, rows: np.ndarray) -> None:
        """Tombstone ``rows`` (global row ids): one bucketed device scatter
        of False into the live mask. Idempotent; never shrinks ``ntotal``
        (positions stay stable — the positional metadata contract)."""
        rows = np.asarray(rows, np.int64)
        if rows.size == 0:
            return
        if self.live is None:
            self.live = jnp.ones((self.cap,), bool)
        bucket = _next_pow2(rows.size, 1024)
        idx = np.full(bucket, self.cap, np.int64)  # pad -> dropped (OOB)
        idx[: rows.size] = rows
        self.live = _mask_rows_false(self.live, jnp.asarray(idx))

    def add(self, rows: np.ndarray) -> Tuple[int, int]:
        """Append rows; returns the (start, end) id range they occupy."""
        n = rows.shape[0]
        if n == 0:
            return self.ntotal, self.ntotal
        self._ensure(n)
        bucket = _next_pow2(n, self.WRITE_BUCKET)
        block = np.zeros((bucket,) + self.row_shape, dtype=self.dtype)
        block[:n] = rows
        self.data = _write_rows(self.data, jnp.asarray(block), self.ntotal)
        start = self.ntotal
        self.ntotal += n
        return start, self.ntotal

    def rows(self, ids: np.ndarray) -> np.ndarray:
        """Fetch rows by id (host round-trip)."""
        if self.data is None:
            return np.zeros((0,) + self.row_shape, self.dtype)
        # graftlint: ok(host-sync): "host round-trip" is this method's contract
        return np.asarray(self.data[jnp.asarray(ids, jnp.int32)])

    def all_rows(self) -> np.ndarray:
        if self.data is None:
            return np.zeros((0,) + self.row_shape, self.dtype)
        return np.asarray(self.data[: self.ntotal])


@functools.partial(jax.jit, donate_argnums=(0,))
def _mask_cells_neg1(flat_ids, cells):
    """Scatter -1 into a flattened (nlist*cap,) ids plane at ``cells``;
    out-of-range cells (the bucket padding sentinel) are dropped. This IS
    the IVF tombstone materialization: every scan entry — XLA, fused
    pallas, mesh-masked, probe-routed — already ANDs ``ids >= 0`` with the
    size mask, so a -1 cell is exactly a padding slot to all of them."""
    return flat_ids.at[cells].set(-1, mode="drop")


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _scatter_lists(flat_data, flat_ids, pos, payload, gids):
    flat_data = flat_data.at[pos].set(payload, mode="drop")
    flat_ids = flat_ids.at[pos].set(gids, mode="drop")
    return flat_data, flat_ids


@jax.jit
def _gather_flat_rows(data, fidx):
    """Fetch rows at flat (slot * cap + pos) cell addresses from a padded
    (nlist, cap, *payload) list array (local or mesh-sharded — XLA inserts
    the collectives for the sharded case)."""
    return data.reshape((-1,) + data.shape[2:])[fidx]


def gather_list_rows(lists, assign, pos, bucket_min: int = 1024) -> np.ndarray:
    """Host-side driver: rows at (list, within-list position) pairs.

    This is how reconstruct/persistence read payload back from device lists
    instead of a host-RAM corpus mirror (VERDICT r4): flat cell addresses
    are built from the id -> (list, pos) map, bucket-padded to bound jit
    variants, and gathered in one launch.
    """
    n = assign.shape[0]
    if n == 0:
        return np.zeros((0,) + tuple(lists.payload_shape), lists.dtype)
    flat = np.asarray(lists.slot_of(np.asarray(assign, np.int64))) * lists.cap \
        + np.asarray(pos, np.int64)
    bucket = _next_pow2(n, bucket_min)
    fidx = np.zeros(bucket, np.int64)
    fidx[:n] = flat
    # graftlint: ok(host-sync): reconstruct/persistence host fetch by design
    out = np.asarray(_gather_flat_rows(lists.data, jnp.asarray(fidx)))
    return out[:n]


class PaddedLists:
    """nlist growable inverted lists as rectangular padded device arrays."""

    MIN_CAP = 64
    APPEND_BUCKET = 1024

    def __init__(self, nlist: int, payload_shape: Tuple[int, ...], dtype, min_cap: int = None):
        self.nlist = nlist
        self.payload_shape = tuple(payload_shape)
        self.dtype = dtype
        self.cap = min_cap or self.MIN_CAP
        self.data = jnp.zeros((nlist, self.cap) + self.payload_shape, dtype)
        self.ids = jnp.full((nlist, self.cap), -1, jnp.int32)
        self.sizes_host = np.zeros(nlist, np.int64)
        self._sizes_dev = jnp.zeros(nlist, jnp.int32)

    @property
    def sizes(self):
        # device-cached (refreshed on append) so search calls don't pay a
        # host->device transfer per query batch
        return self._sizes_dev

    @property
    def ntotal(self) -> int:
        return int(self.sizes_host.sum())

    def _grow(self, needed_cap: int):
        newcap = _next_pow2(needed_cap, self.cap)
        if newcap == self.cap:
            return
        pad_d = [(0, 0), (0, newcap - self.cap)] + [(0, 0)] * len(self.payload_shape)
        self.data = jnp.pad(self.data, pad_d)
        self.ids = jnp.pad(self.ids, [(0, 0), (0, newcap - self.cap)], constant_values=-1)
        self.cap = newcap

    @staticmethod
    def plan_append(list_idx, payload, gids, nlist, cap, sizes_host, payload_shape,
                    dtype, slot_fn, drop_value, bucket_min):
        """Host-side offset planning shared by local and mesh-sharded lists.

        Sorts the batch by target list, computes each row's write position
        ``slot_fn(list) * cap + current_size + within-batch-offset``, and
        pads everything to a power-of-two bucket (padding rows get
        ``drop_value`` so the device scatter drops them). Returns
        (counts, pos, payload, gids, within) with pos/payload/gids
        bucket-padded and ``within`` the per-row within-list positions in
        INPUT order — the id -> (list, slot) map that lets reconstruction
        and persistence read rows back from the device lists instead of
        keeping a host-RAM corpus mirror (VERDICT r4).
        """
        n = list_idx.shape[0]
        counts = np.bincount(list_idx, minlength=nlist)
        order = np.argsort(list_idx, kind="stable")
        sorted_li = list_idx[order]
        group_start = np.zeros(nlist + 1, np.int64)
        group_start[1:] = np.cumsum(counts)
        offs = np.arange(n, dtype=np.int64) - group_start[sorted_li]
        within_sorted = sizes_host[sorted_li] + offs
        pos = slot_fn(sorted_li.astype(np.int64)) * cap + within_sorted
        within = np.empty(n, np.int32)
        within[order] = within_sorted.astype(np.int32)

        bucket = _next_pow2(n, bucket_min)
        pos_b = np.full(bucket, drop_value, np.int64)
        pay_b = np.zeros((bucket,) + payload_shape, dtype)
        gid_b = np.zeros(bucket, np.int32)
        pos_b[:n] = pos
        pay_b[:n] = payload[order]
        gid_b[:n] = gids[order]
        return counts, pos_b, pay_b, gid_b, within

    def slot_of(self, l):
        """global list id -> padded slot (identity locally; the sharded
        variant overrides with strided ownership)."""
        return l

    def mask_cells(self, cells: np.ndarray) -> None:
        """Tombstone list cells (flat ``slot * cap + pos`` addresses): one
        bucketed scatter of -1 into the ids plane. Sizes are NOT
        decremented — a dead slot stays occupied (and masked) until
        compaction rewrites the list, keeping every live (slot, pos)
        address stable."""
        cells = np.asarray(cells, np.int64)
        if cells.size == 0:
            return
        bucket = _next_pow2(cells.size, 1024)
        idx = np.full(bucket, self.nlist * self.cap, np.int64)  # pad: dropped
        idx[: cells.size] = cells
        flat = _mask_cells_neg1(self.ids.reshape(self.nlist * self.cap),
                                jnp.asarray(idx))
        self.ids = flat.reshape(self.nlist, self.cap)

    def append(self, list_idx: np.ndarray, payload: np.ndarray, gids: np.ndarray):
        """Append payload rows to their assigned lists.

        list_idx: (n,) int; payload: (n, *payload_shape); gids: (n,) global ids.
        Offset planning is host-side numpy; the device side is one scatter.
        Returns the (n,) int32 within-list positions in input order.
        """
        if list_idx.shape[0] == 0:
            return np.zeros(0, np.int32)
        counts = np.bincount(list_idx, minlength=self.nlist)
        new_sizes = self.sizes_host + counts
        if new_sizes.max() > self.cap:
            self._grow(int(new_sizes.max()))
        counts, pos_b, pay_b, gid_b, within = self.plan_append(
            list_idx, payload, gids, self.nlist, self.cap, self.sizes_host,
            self.payload_shape, self.dtype, lambda l: l,
            np.iinfo(np.int32).max, self.APPEND_BUCKET,
        )

        flat_data = self.data.reshape((self.nlist * self.cap,) + self.payload_shape)
        flat_ids = self.ids.reshape(self.nlist * self.cap)
        flat_data, flat_ids = _scatter_lists(
            flat_data, flat_ids, jnp.asarray(pos_b), jnp.asarray(pay_b), jnp.asarray(gid_b)
        )
        self.data = flat_data.reshape((self.nlist, self.cap) + self.payload_shape)
        self.ids = flat_ids.reshape(self.nlist, self.cap)
        self.sizes_host = new_sizes
        self._sizes_dev = jnp.asarray(new_sizes.astype(np.int32))
        return within


class TpuIndex:
    """Abstract index model (the FAISS-index-equivalent surface).

    Subclasses: FlatIndex, IVFFlatIndex, IVFPQIndex (+ registered builders).
    """

    def __init__(self, dim: int, metric: str):
        if metric not in ("dot", "l2"):
            raise RuntimeError("Only dot and l2 metrics are supported.")
        self.dim = dim
        self.metric = metric
        self.nprobe = 1

    # --- lifecycle -------------------------------------------------------
    @property
    def is_trained(self) -> bool:
        raise NotImplementedError

    @property
    def ntotal(self) -> int:
        raise NotImplementedError

    def train(self, x: np.ndarray) -> None:
        raise NotImplementedError

    def add(self, x: np.ndarray) -> None:
        """Append vectors; ids are sequential (positional metadata join,
        reference: distributed_faiss/index.py:260-268)."""
        raise NotImplementedError

    # --- query -----------------------------------------------------------
    def search(self, q: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
        raise NotImplementedError

    def search_batched(self, q: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """Already-merged serving entry (the scheduler's launch target via
        ``engine.Index.search_batched``): ``q`` is one coalesced window of
        concurrent callers' rows. The default is plain ``search``; mesh-
        backed models whose plain path would otherwise loop host-side
        guarantee ONE device launch per call here (parallel/mesh.py), and
        models exposing a ``launches`` counter let the engine report
        launches-per-window (``Index.perf``)."""
        return self.search(q, k)

    def launch_search(self, q: np.ndarray, k: int) -> "SearchHandle":
        """``search_batched`` in two halves: launch now, ``collect()`` the
        result later, so that the engine's lock covers the launch alone and
        the scheduler can launch the next window behind this one. The
        default runs the whole search here and hands back a finished
        handle; an index whose scan can put off its wait overrides it (the
        flat and the IVF indexes; a pre-transform wrapper asks its inner
        index) and serves ``search`` as ``launch_search(...).collect()``."""
        return finished(self.search_batched(q, k))

    def reconstruct_batch(self, ids: np.ndarray) -> np.ndarray:
        """Return (approximate) stored vectors for ids (FAISS
        search_and_reconstruct parity, reference index.py:255-257)."""
        raise NotImplementedError

    # --- mutation ---------------------------------------------------------
    def supports_remove_rows(self) -> bool:
        """True when this model carries a tombstone mask (overrides
        ``remove_rows``). The engine checks this BEFORE recording any
        tombstone — including for rows still in the add buffer, where the
        mask would only be applied at drain time: accepting such a delete
        and then having the drain thread hit the base-class rejection
        would kill the worker and wedge the engine in ``ADD``."""
        return type(self).remove_rows is not TpuIndex.remove_rows

    def remove_rows(self, rows: np.ndarray) -> None:
        """Tombstone rows (global sequential ids) out of every scan path:
        a masked row can never surface in top-k, even when k exceeds the
        live count. ``ntotal`` does NOT shrink — row ids stay stable (the
        positional metadata contract); compaction (mutation/compaction.py)
        is what reclaims the capacity. Idempotent. Subclasses that cannot
        mask (graph indexes) keep this default and the engine surfaces the
        limitation as an application error."""
        raise RuntimeError(
            f"{type(self).__name__} does not support remove/upsert "
            "(no tombstone mask for this index kind)")

    # --- knobs ------------------------------------------------------------
    def set_nprobe(self, nprobe: int) -> None:
        self.nprobe = int(nprobe)

    def get_centroids(self) -> Optional[np.ndarray]:
        return None

    # --- persistence ------------------------------------------------------
    def state_dict(self) -> Dict[str, np.ndarray]:
        raise NotImplementedError

    @classmethod
    def from_state_dict(cls, state: Dict[str, np.ndarray]) -> "TpuIndex":
        raise NotImplementedError


def finalize_results(scores: np.ndarray, ids: np.ndarray, metric: str):
    """ops-convention (bigger-better scores, int32 ids) -> FAISS-style (D, I)."""
    ids = ids.astype(np.int64)
    if metric == "l2":
        return -scores, ids
    return scores, ids


MAX_QUERY_BLOCK = 1024
# 2x ivf._GROUP_BYTE_BUDGET: when probe grouping floors at g=1 (one probe's
# block-payload already exceeds the 128MB group budget), the gather transient
# equals block * per-probe bytes — this cap bounds that worst case at 256MB
# instead of letting large-cap/high-dim configs reach 4x the group budget
_QUERY_PAYLOAD_BUDGET = 256 * 1024 * 1024


def pick_query_block(probe_bytes_per_query: int, minimum: int = 256) -> int:
    """Largest power-of-two query block (<= MAX_QUERY_BLOCK) whose gathered
    per-probe payload fits the byte budget.

    The premise — a per-launch floor large next to a block's compute, so the
    block should be as large as the gather payload allows and not a fixed
    256 — and the constants here are unmeasured on a chip the process holds;
    ROADMAP S3 re-derives them from a measurement or deletes them.

    Combined worst-case transient with probe grouping: if one probe's
    payload for the chosen block exceeds the group budget, g floors at 1 and
    the transient is block * probe_bytes <= _QUERY_PAYLOAD_BUDGET (the
    ``minimum`` floor can still exceed it for extreme per-probe payloads —
    by construction, a single probe at minimum block that large would not
    fit any budget).
    """
    block = MAX_QUERY_BLOCK
    while block > minimum and block * probe_bytes_per_query > _QUERY_PAYLOAD_BUDGET:
        block //= 2
    return block


def _padded_block(q: np.ndarray, s: int, block: int):
    """(rows, rows padded to their jit bucket) of the block starting at s."""
    chunk = q[s : s + block]
    return chunk.shape[0], distance.pad_rows(
        chunk, distance.bucket_size(chunk.shape[0]))


def query_blocks(q: np.ndarray, block: int = 256):
    """Split a query batch into bucketed blocks to bound jit variants."""
    for s in range(0, q.shape[0], block):
        n, chunk = _padded_block(q, s, block)
        yield s, n, chunk


class SearchHandle:
    """A search that was launched: ``collect()``, called once, waits for it
    and returns what ``search`` returns. The two halves of every search
    (``TpuIndex.launch_search``): the launch is what must see one state of
    the index (the engine holds ``index_lock`` around it), the collect only
    waits for programs that hold their operands."""

    __slots__ = ("collect",)

    def __init__(self, collect):
        self.collect = collect


def finished(result) -> SearchHandle:
    """The handle of a search that ran to its end in the launch: its
    outputs are in hand here, which the scheduler's timeline of the chip
    takes for the window's ``ready`` where no collect reads a later one."""
    tracing.instant("ready")
    return SearchHandle(lambda: result)


class Dispatched:
    """What a scan callable hands ``blocked_search`` to put its wait off to
    the collect: ``out``, the program's outputs as dispatched, ``(vals, ids,
    ...)`` (the rerank is dispatched on them), and ``wait()``, the ``(vals,
    ids)`` to serve once the device has them — through ``settled(out)``
    where the caller has rows to count or further outputs to take off.
    ``wait`` may serve other arrays than ``out`` (models/ivf.py's
    ``GuardedScan`` serves the XLA oracle's where the kernel aborted); the
    collect then dispatches the rerank again, on those."""

    def __init__(self, out, settled=None):
        self.out = out
        self._settled = settled

    def _ready(self):
        return jax.block_until_ready(self.out)

    def wait(self):
        out = self._ready()
        return out if self._settled is None else self._settled(out)


def _start_fetch(arrays) -> None:
    """Start the copies to the host of a window's last outputs, behind the
    programs that make them: the collect's fetch then finds them landed
    (a device-to-host read started after the fact is 0.4 ms of latency on
    a v5e even for ready bytes: PERF.md, PR 35)."""
    with xfercheck.explicit("blocked_search result fetch, started with the launch"):
        for a in arrays:
            if hasattr(a, "copy_to_host_async"):
                a.copy_to_host_async()


class _Unit:
    """One dispatched scan of a window (a block, or the fused stack): what
    the collect needs to wait for it, rerank again if the wait served other
    arrays, and place its rows."""

    __slots__ = ("rows", "chunk", "dispatched", "stage", "refine_fn", "ids", "out")

    def __init__(self, fn, chunk, counts, refine_fn, rows: int):
        self.rows, self.chunk, self.refine_fn = rows, chunk, refine_fn
        self.stage = tracing.handover("engine.scan")
        with self.stage, tracing.stage("engine.dispatch"):
            out = fn(chunk, *counts)
            # the window's first program is on its way to the chip
            tracing.instant("dispatched", first=True)
            self.dispatched = out if isinstance(out, Dispatched) else Dispatched(out)
            vals, self.ids = self.dispatched.out[:2]
            self.out = self._reranked(vals, self.ids)
            _start_fetch(self.out)

    def _reranked(self, vals, ids):
        """The unit's last outputs: the scan's, or the exact rerank's
        (dispatched here) on the scan's candidates."""
        return (vals, ids) if self.refine_fn is None else self.refine_fn(self.chunk, ids)

    def collect(self):
        """(scores, ids) of the unit's real rows, on the host, ops-convention."""
        with self.stage.last():
            vals, ids = self.dispatched.wait()
        with tracing.stage("engine.refine_fetch", sink=self.stage.sink):
            if ids is not self.ids:  # the wait served other arrays
                self.out = self._reranked(vals, ids)
            with xfercheck.explicit("blocked_search result fetch"):
                vals, ids = (np.asarray(a) for a in self.out)
            # the unit's last outputs are in hand (the window's, once its
            # last unit says so)
            tracing.instant("ready")
            return (vals.reshape(-1, vals.shape[-1])[: self.rows],
                    ids.reshape(-1, ids.shape[-1])[: self.rows])


def launch_blocked_search(q: np.ndarray, k: int, metric: str, fn, block: int = 256,
                          fused_fn=None, refine_fn=None,
                          with_counts: bool = False) -> SearchHandle:
    """THE blocked search driver (shared by the IVF family, the flat index
    and the mesh indexes — one implementation so the bucketing/padding
    policy cannot drift between them), in two halves: this call is the
    launch, the returned handle's ``collect()`` the rest. ``blocked_search``
    is the two in one call.

    The launch: one device dispatch per query block (``fn`` over a padded
    (bucket, d) block). When the batch spans multiple blocks and the
    caller supplies ``fused_fn`` (a callable over (nblocks, block, d)
    stacked queries), the whole batch runs in ONE launch, saving
    (nblocks-1) per-launch floors per search call (their size on a local
    chip is unmeasured — ROADMAP S3). The trailing block is padded to full
    width inside the fused path (extra compute only); jit variants
    are keyed on nblocks, which is bucketed to powers of two so a
    variable-batch serving workload compiles O(log max_batch) fused
    variants (each sharded variant is a multi-second compile) instead of
    one per distinct batch size — offline/bench callers with a stable
    batch size still compile once. Where the index refines outside the scan
    program (``refine_fn(block, ids)``, the exact rerank) that program is
    dispatched right behind the scan, on the scan's outputs as dispatched,
    and the last outputs' copies to the host are started: nothing here
    waits for the device, so the rerank follows the scan on the chip
    without the host between them, and a caller may launch the next window
    behind this one before collecting it.

    The collect: per dispatched scan, the wait for it (``Dispatched.wait``:
    where a scan callable handed back plain outputs, or had waited itself
    as the mesh indexes' do, a plain ``block_until_ready``), the fetch and
    ``finalize_results``.

    Memory cliff (ADVICE r4): the pow2 bucket can pad the fused batch up
    to ~2x (33 blocks -> 64), doubling the stacked (nblocks, block, d)
    query input and (nblocks*block, k') output arrays for that launch.
    The per-block score/gather transients — the dominant footprint,
    bounded by ``pick_query_block``'s budget — are NOT inflated
    (``lax.map`` runs blocks sequentially), so the cliff is a few MB of
    query/output padding, not a doubled working set; callers pinning
    their own batch sizes can stay at power-of-two multiples of the
    block to avoid even that.

    Stage ledger (utils/tracing.stage; counters land in the caller's
    sink, the engine's): a dispatched scan is ``engine.feed`` (slice, pad,
    ``device_put``), ``engine.scan`` (a ``tracing.handover``: from the
    dispatch of the scan program to the moment the collect finds its
    outputs ready, so with a window ahead of this one on the chip it holds
    the wait behind that window too) and ``engine.refine_fetch`` (from
    there to the end of the fetch and ``finalize_results``: the rerank's
    device time where there is one, and the copy). No sync is added for
    the boundaries.

    ``with_counts``: the scan callables also get the real rows of what
    they are handed, as a device int32 — ``fn(chunk, n)`` a scalar,
    ``fused_fn(q3, counts)`` one count a block — so a program that can skip
    the zero padding (the list-major IVF scan) knows where it starts.
    """
    q = np.asarray(q, np.float32)
    nq = q.shape[0]
    # Feeds go through explicit jax.device_put and fetches through an
    # xfercheck.explicit() scope: the serving path runs under
    # DFT_XFERCHECK's transfer guard, which forbids the implicit
    # host<->device copies jnp.asarray/np.asarray would otherwise hide
    # at the jit boundary. (Mesh callers re-place the block onto their
    # sharding inside fn/fused_fn — also explicitly.)
    units = []
    if fused_fn is not None and nq > block:
        with tracing.stage("engine.feed"):
            nblocks = _next_pow2(-(-nq // block), 1)
            qp = np.pad(q, ((0, nblocks * block - nq), (0, 0)))
            q3 = jax.device_put(qp.reshape(nblocks, block, -1))
            rows = np.clip(nq - block * np.arange(nblocks), 0, block)
            counts = (jax.device_put(rows.astype(np.int32)),) if with_counts else ()
        units.append(_Unit(fused_fn, q3, counts, None, nq))
    else:
        for s in range(0, nq, block):
            with tracing.stage("engine.feed"):
                n, chunk = _padded_block(q, s, block)
                chunk = jax.device_put(chunk)
                counts = (jax.device_put(np.int32(n)),) if with_counts else ()
            units.append(_Unit(fn, chunk, counts, refine_fn, n))

    def collect():
        parts = [u.collect() for u in units] or [
            (np.empty((0, k), np.float32), np.empty((0, k), np.int32))]
        return finalize_results(np.concatenate([s for s, _ in parts]),
                                np.concatenate([i for _, i in parts]), metric)

    return SearchHandle(collect)


def blocked_search(q: np.ndarray, k: int, metric: str, fn, block: int = 256,
                   fused_fn=None, refine_fn=None, with_counts: bool = False):
    """``launch_blocked_search`` and its collect in one call: what every
    caller that has nothing to do meanwhile uses."""
    return launch_blocked_search(q, k, metric, fn, block, fused_fn, refine_fn,
                                 with_counts).collect()
