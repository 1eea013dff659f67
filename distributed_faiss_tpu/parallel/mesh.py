"""Multi-chip mesh parallelism: corpus sharding over ICI collectives.

This is the intra-server parallelism layer the reference doesn't have (its
only device parallelism is FAISS OpenMP threads; SURVEY §2.2): one server
rank can own a whole ``jax.sharding.Mesh`` of TPU chips, with the corpus
sharded over the ``shard`` axis and all cross-chip traffic expressed as XLA
collectives (all_gather / psum) that ride ICI — not RPC.

Components:
- ``make_mesh``             — 1D device mesh over the local chips
- ``sharded_knn``           — corpus-sharded exact search: each chip scans its
                              local block (MXU matmul + running top-k), then an
                              ``all_gather`` of the (nq, k) candidates and a
                              replicated merge; DCN never sees per-chunk scores
- ``sharded_kmeans``        — Lloyd iterations with local one-hot-matmul
                              accumulation and ``psum`` reductions for the
                              cluster sums/counts (the million-centroid path)
- ``ShardedFlatIndex``      — exact index whose corpus lives sharded in the
                              mesh's HBM (incremental device sync)
- ``IvfTpuIndex``           — the ``ivf_tpu`` builder target (BASELINE.json's
                              north star): IVF whose coarse k-means trains
                              sharded over the mesh
- ``ShardedPaddedLists``    — inverted lists partitioned across chip HBMs
                              (strided ownership, per-shard drop-routed scatter)
- ``ShardedIVFFlatIndex``   — IVF over sharded lists; two search modes:
                              ownership masking (capacity scales) and probe
                              routing (FLOPs scale too — each chip compacts
                              and scores only its owned pairs)
- ``ShardedIVFPQIndex``     — IVF-PQ over sharded code lists (per-chip
                              residual-LUT ADC, ICI merge)

Serving contract (ISSUE 6): in the default masked mode every sharded
index's ``search`` issues ONE pjit launch per call — single block direct,
multi-block through the fused ``lax.map`` entries (``_sharded_knn_fused``
and the IVF ``*_fused`` programs) — with the top-k reduce on-mesh, so a
scheduler-merged window (engine.search_batched) crosses the host/device
boundary exactly once in each direction. Probe-routed mode has no fused
multi-block entry (its pair buckets scale with the block, so stacking
blocks would square the transient): a merged window larger than the
routed block budget (``_routed_block_size``) legitimately costs one
launch per block, plus bucket-growth relaunches under skewed ownership.
Each index carries a ``launches`` dispatch counter (``_counted``; the PQ
pallas degrade ladder counts each real attempt) that the engine diffs
into its ``device_launches`` / ``rows_per_launch`` perf rows — so the
counter tells the truth in every mode, and the ==1.0 contract is the
masked mode's.

Tests exercise all of this on a virtual 8-device CPU mesh
(tests/conftest.py); the driver's dryrun_multichip does the same through
__graft_entry__.py.
"""

import functools
import logging
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_faiss_tpu.models import base
from distributed_faiss_tpu.models import ivf as ivfmod
from distributed_faiss_tpu.models.ivf import IVFFlatIndex, IVFPQIndex, probe_group_size
from distributed_faiss_tpu.ops import distance
from distributed_faiss_tpu.utils import tracing, xfercheck

_HIGHEST = jax.lax.Precision.HIGHEST
logger = logging.getLogger(__name__)

AXIS = "shard"


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1D device mesh over the local chips.

    ``n_devices=None`` applies the per-host ``DFT_MESH_DEVICES`` default
    (utils.config.MeshCfg) — so snapshot restores (``from_state_dict``
    builds with ``mesh=None``) and bare constructions honor the same host
    sizing as factory builds, and a rank restart cannot silently spread
    onto chips the operator excluded. An explicit integer (factory
    ``mesh_devices`` pins) bypasses the env; 0 means ALL visible devices
    in both channels."""
    if n_devices is None:
        from distributed_faiss_tpu.utils.config import MeshCfg

        n_devices = MeshCfg.from_env().devices
    devs = jax.devices()
    if n_devices:  # 0 = every visible device
        if n_devices > len(devs):
            raise ValueError(f"requested {n_devices} devices, have {len(devs)}")
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (AXIS,))


# --------------------------------------------------------------------- search


def local_scan_merge(q_local, x_local, ntot_local, k: int, metric: str,
                     chunk: int, axis: str = AXIS, live_local=None):
    """Per-chip exact scan + ICI all_gather candidate merge.

    The body of every sharded search: scan the local corpus block with the
    chunked running-top-k kernel, offset local ids to global (contiguous
    block layout: global id = shard * cap_local + pos), all_gather the
    (nq, k) candidates over ``axis`` and merge. Used by _sharded_knn_jit and
    the dryrun's 2D (dp, shard) variant. ``live_local`` is this chip's
    slice of the tombstone mask (mutation subsystem), AND-ed with the
    fill-count padding mask inside the scan; None (no deletions) traces
    the exact pre-mutation program."""
    cap_local = x_local.shape[0]
    vals, ids = distance._knn_scan(
        q_local, x_local, ntot_local, k, metric, min(chunk, cap_local),
        live=live_local,
    )
    base_id = jax.lax.axis_index(axis).astype(jnp.int32) * cap_local
    gids = jnp.where(ids >= 0, ids + base_id, ids)
    av = jax.lax.all_gather(vals, axis)  # (S, nq, k)
    ai = jax.lax.all_gather(gids, axis)
    nq = q_local.shape[0]
    flat_v = jnp.transpose(av, (1, 0, 2)).reshape(nq, -1)
    flat_i = jnp.transpose(ai, (1, 0, 2)).reshape(nq, -1)
    best, pos = jax.lax.top_k(flat_v, k)
    return best, jnp.take_along_axis(flat_i, pos, axis=1)


@functools.partial(
    jax.jit, static_argnames=("mesh", "k", "metric", "chunk")
)
def _sharded_knn_jit(q, x, ntotals, mesh, k: int, metric: str, chunk: int,
                     live=None):
    """q replicated, x sharded (S*cap_local, d) along rows, ntotals (S,).
    ``live``: optional row-sharded (S*cap_local,) bool tombstone mask."""

    # check_vma=False: the outputs ARE replicated (deterministic merge of
    # all_gather'ed candidates) but the static checker can't infer it
    # through the integer id path
    if live is not None:
        fn = shard_map(
            lambda q, x_local, ntot_local, live_local: local_scan_merge(
                q, x_local, ntot_local[0], k, metric, chunk,
                live_local=live_local),
            mesh=mesh,
            in_specs=(P(), P(AXIS, None), P(AXIS), P(AXIS)),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return fn(q, x, ntotals, live)

    def local(q, x_local, ntot_local):
        return local_scan_merge(q, x_local, ntot_local[0], k, metric, chunk)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(AXIS, None), P(AXIS)),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return fn(q, x, ntotals)


def _knn_chunk(cap_local: int, chunk: int = 65536) -> int:
    """Largest power-of-two scan chunk that divides the per-shard capacity
    (we can't pad a sharded array the way distance.knn pads a local one)."""
    c = 1
    while c * 2 <= min(chunk, cap_local) and cap_local % (c * 2) == 0:
        c *= 2
    return c


def sharded_knn(mesh: Mesh, q, x, ntotals, k: int, metric: str = "l2",
                chunk: int = 65536):
    """Exact k-nn over a row-sharded corpus with distributed top-k merge.

    chunk is clamped to the largest power-of-two divisor of the per-shard
    capacity (see _knn_chunk)."""
    cap_local = x.shape[0] // mesh.shape[AXIS]
    return _sharded_knn_jit(q, x, ntotals, mesh, k, metric,
                            _knn_chunk(cap_local, chunk))


@functools.partial(jax.jit, static_argnames=("mesh", "k", "metric", "chunk"))
def _sharded_knn_fused(q3, x, ntotals, mesh, k: int, metric: str, chunk: int,
                       live=None):
    """Multi-block sharded exact search in ONE launch: lax.map over stacked
    (nblocks, block, d) query blocks, shard_map per block inside — the flat
    analog of _sharded_ivf_flat_search_fused, so a merged serving window
    never pays one dispatch (or one host round-trip) per block."""

    def body(qb):
        return _sharded_knn_jit(qb, x, ntotals, mesh, k, metric, chunk,
                                live=live)

    return jax.lax.map(body, q3)


# --------------------------------------------------------------------- kmeans


@functools.partial(jax.jit, static_argnames=("mesh", "k", "chunk"))
def _kmeans_step_jit(x, w, cent, mesh, k: int, chunk: int):
    """One sharded Lloyd iteration: local accumulation + psum reduction.

    Requires chunk to divide the per-shard row count (sharded_kmeans pads
    to guarantee it)."""

    def local(x_local, w_local, cent):
        npad, d = x_local.shape
        if npad % chunk:
            raise ValueError(f"per-shard rows {npad} not a multiple of chunk {chunk}")
        nchunks = npad // chunk
        from distributed_faiss_tpu.ops.kmeans import accumulate_clusters

        sums, counts = accumulate_clusters(
            x_local.reshape(nchunks, chunk, d), w_local.reshape(nchunks, chunk), cent, k
        )
        # ICI reduction: cluster sums/counts over all shards
        sums = jax.lax.psum(sums, AXIS)
        counts = jax.lax.psum(counts, AXIS)
        return jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None], cent)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(AXIS, None), P(AXIS), P()),
        out_specs=P(),
    )
    return fn(x, w, cent)


def sharded_kmeans(mesh: Mesh, x: np.ndarray, k: int, iters: int = 10,
                   seed: int = 0, chunk: int = None):
    """Lloyd k-means over a mesh-sharded training set.

    x is padded to a shard multiple, device_put with a row sharding, and the
    iteration loop runs host-side over jitted psum steps (centroids stay
    replicated). Init: k-means++ on a bounded subsample (single-device jit —
    the sequential ++ pass doesn't shard well), falling back to uniform
    random seeding for mesh-scale k where even the subsampled ++ pass is the
    bottleneck.
    """
    x = np.asarray(x, np.float32)
    n, d = x.shape
    if k > n:
        raise ValueError(f"k={k} > n={n}")
    from distributed_faiss_tpu.ops.kmeans import auto_chunk

    S = mesh.shape[AXIS]
    per = -(-n // S)
    chunk = min(auto_chunk(k, chunk), per)
    per = -(-per // chunk) * chunk  # chunk must divide the per-shard rows
    npad = per * S
    w = np.zeros(npad, np.float32)
    w[:n] = 1.0
    if npad != n:
        x = np.concatenate([x, np.zeros((npad - n, d), np.float32)])

    xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P(AXIS, None)))
    ws = jax.device_put(jnp.asarray(w), NamedSharding(mesh, P(AXIS)))

    rng = np.random.default_rng(seed)
    if k <= 16384:
        from distributed_faiss_tpu.ops import kmeans as km

        sample_n = min(n, max(4 * k, 16384))
        sample = x[rng.permutation(n)[:sample_n]]
        cent = km.kmeans(sample, k, iters=0, seed=seed, init="kmeans++")
    else:
        cent = jnp.asarray(x[rng.permutation(n)[:k]])
    cent = jax.device_put(cent, NamedSharding(mesh, P()))
    for _ in range(iters):
        cent = _kmeans_step_jit(xs, ws, cent, mesh, k, chunk)
    return cent


def _counted(index, call):
    """Wrap a device-program launch callable so ``index.launches`` counts
    every dispatch the block/fused/routed driver issues (routed drop-retry
    relaunches included — they are real dispatches; the PQ paths count
    inside the pallas degrade ladder instead, so a proven-failure XLA
    re-dispatch is counted too). The counter is what lets
    engine._device_search report launches-per-merged-window — ==1.0 is
    the masked-mode serving contract (ISSUE 6)."""

    def wrapped(*args, **kwargs):
        index.launches += 1
        out = call(*args, **kwargs)
        # the window's first program is on its way to the chips now: a mesh
        # scan callable waits for its outputs itself, and the instant
        # ``blocked_search`` takes once it returns would put the chip's
        # whole span before the window's ``dispatched`` (the scheduler's
        # timeline read 0.8 ms busy of a 50 ms window: PERF.md, PR 45)
        tracing.instant("dispatched", first=True)
        return out

    return wrapped


def _replicated(mesh, arr):
    """Explicitly replicate a host block / single-device array onto the
    mesh. The sharded jit entries would do the same reshard implicitly at
    dispatch, but the serving path runs under DFT_XFERCHECK's transfer
    guard, which (rightly) flags implicit cross-device placement — the
    query feed is a designed transfer, so make it one. Host work a local
    index does not have: stage ``engine.mesh_place`` (one record a placed
    operand; the centroid table and codebooks stay placed after a rank's
    first launch, the query block is placed every launch)."""
    with tracing.stage("engine.mesh_place"):
        return jax.device_put(arr, NamedSharding(mesh, P()))


# --------------------------------------------------------------- index models


@jax.jit
def _take_rows(data, fidx):
    """Row gather from the sharded flat corpus (XLA inserts the cross-shard
    collectives; callers bucket fidx to bound jit variants)."""
    return data[fidx]


class ShardedFlatIndex(base.TpuIndex):
    """Exact-search index whose corpus is sharded over a device mesh.

    Rows are packed round-robin-by-block: global id = shard * cap_local +
    local position, with per-shard fill counts masking the padding. The
    search path is ``sharded_knn`` (local MXU scan -> all_gather -> merge).
    """

    def __init__(self, dim: int, metric: str = "l2", mesh: Optional[Mesh] = None):
        super().__init__(dim, metric)
        self.mesh = mesh or make_mesh()
        self.nshards = self.mesh.shape[AXIS]
        # host side holds only rows not yet written to the device corpus
        # (freed by _sync); the device array is the single full copy —
        # growth repacks on-device since the flat layout is contiguous
        # (VERDICT r4: no permanent host corpus mirror)
        self._pending: list = []
        self._n = 0
        # device-program dispatch counter (monotonic): one increment per
        # pjit launch issued by the search driver. engine._device_search
        # diffs it around each merged window to report launches-per-window
        # (docs/OPERATIONS.md#multi-chip-serving)
        self.launches = 0
        self._dev = None       # (S * cap_local, d) sharded
        self._ntotals = None   # (S,) int32
        self._cap_local = 0
        self._synced_n = 0     # rows already written to the device corpus
        self._row_sharding = NamedSharding(self.mesh, P(AXIS, None))
        self._live_sharding = NamedSharding(self.mesh, P(AXIS))
        # tombstone mask (mutation subsystem): (S * cap_local,) bool sharded
        # like the corpus rows; None until the first deletion so the
        # delete-nothing programs stay byte-identical to pre-mutation
        self._live = None
        # rows masked before they reached the device corpus (deleted while
        # still pending): applied at the next _sync
        self._pending_dead: list = []
        self._append = jax.jit(
            lambda data, block, start: jax.lax.dynamic_update_slice(
                data, block, (start, 0)
            ),
            donate_argnums=(0,),
            out_shardings=self._row_sharding,
        )
        self._mask_live = jax.jit(
            lambda live, idx: live.at[idx].set(False, mode="drop"),
            donate_argnums=(0,),
            out_shardings=self._live_sharding,
        )

    @property
    def is_trained(self) -> bool:
        return True

    @property
    def ntotal(self) -> int:
        return self._n

    def train(self, x: np.ndarray) -> None:
        pass

    def add(self, x: np.ndarray) -> None:
        x = np.asarray(x, np.float32)
        if x.shape[0] == 0:
            return
        self._pending.append(x)
        self._n += x.shape[0]
        # device sync is lazy and *incremental*: only new rows are written
        # unless capacity must grow (geometric, so repacks are O(log n))

    def _pending_array(self) -> np.ndarray:
        if len(self._pending) > 1:
            self._pending = [np.concatenate(self._pending)]
        return self._pending[0] if self._pending else np.zeros((0, self.dim), np.float32)

    def _update_counts(self) -> None:
        per = self._cap_local
        counts = np.clip(self._n - np.arange(self.nshards) * per, 0, per)
        self._ntotals = jax.device_put(
            jnp.asarray(counts.astype(np.int32)), NamedSharding(self.mesh, P(AXIS))
        )

    def _sync(self) -> None:
        if self._synced_n == self._n and self._dev is not None:
            return
        # designed host->device landing (pending rows cross to the mesh
        # here and only here): mark the whole sync explicit so a search
        # that triggers it under DFT_XFERCHECK's guard stays legal
        with xfercheck.explicit("sharded corpus sync: land host-pending rows"):
            self._sync_locked()

    def _sync_locked(self) -> None:
        S = self.nshards
        n_new = self._n - self._synced_n
        bucket = base._next_pow2(max(n_new, 1), base.DeviceVectorStore.WRITE_BUCKET)
        if self._dev is None or self._n + bucket > S * self._cap_local:
            # grow: the flat layout is contiguous (row i at flat pos i), so
            # synced rows keep their positions — pad on device and reshard;
            # no host copy of the corpus is needed for the repack
            per = base._next_pow2(max(1, -(-(self._n + bucket) // S)), 8)
            if self._dev is None:
                self._dev = jax.device_put(
                    jnp.zeros((S * per, self.dim), jnp.float32), self._row_sharding
                )
            else:
                self._dev = jax.device_put(
                    jnp.pad(self._dev, ((0, S * per - self._dev.shape[0]), (0, 0))),
                    self._row_sharding,
                )
            if self._live is not None:
                # grown capacity rows are live until masked
                self._live = jax.device_put(
                    jnp.pad(self._live, (0, S * per - self._live.shape[0]),
                            constant_values=True),
                    self._live_sharding,
                )
            self._cap_local = per
        if n_new:
            # incremental append: one dynamic_update_slice of the new rows
            block = np.zeros((bucket, self.dim), np.float32)
            block[:n_new] = self._pending_array()
            self._dev = self._append(
                self._dev, jnp.asarray(block), jnp.asarray(self._synced_n, jnp.int32)
            )
        self._pending = []
        self._synced_n = self._n
        self._update_counts()
        if self._pending_dead:
            # rows deleted while they were still host-pending: their flat
            # positions are now materialized, mask them in the same sync
            dead, self._pending_dead = self._pending_dead, []
            self._mask_now(np.concatenate(dead))

    def _mask_now(self, rows: np.ndarray) -> None:
        if self._live is None:
            self._live = jax.device_put(
                jnp.ones((self.nshards * self._cap_local,), bool),
                self._live_sharding,
            )
        bucket = base._next_pow2(rows.size, 1024)
        idx = np.full(bucket, self._live.shape[0], np.int64)  # pad: dropped
        idx[: rows.size] = rows
        self._live = self._mask_live(self._live, jnp.asarray(idx))

    def remove_rows(self, rows: np.ndarray) -> None:
        """Tombstone rows (contiguous global ids == flat device positions):
        one sharded scatter of False into the live mask, AND-ed with the
        fill-count padding mask inside every sharded scan. Rows still
        host-pending are deferred and masked by the _sync that lands them."""
        rows = np.asarray(rows, np.int64)
        if rows.size == 0:
            return
        pending = rows[rows >= self._synced_n]
        synced = rows[rows < self._synced_n]
        if pending.size:
            self._pending_dead.append(pending)
        if synced.size and self._dev is not None:
            self._mask_now(synced)

    def search(self, q: np.ndarray, k: int):
        """One pjit launch per call, however many query blocks the batch
        spans: the shared ``base.blocked_search`` driver sends a single
        block straight to the shard_map program and rides a multi-block
        batch through the fused lax.map entry (the per-block Python loop
        with its per-block np.asarray round-trip is gone — results leave
        the device exactly once per merged window). Contiguous block
        layout: shard*cap_local + pos IS the insertion-order global id, so
        no remap is needed."""
        if self._n == 0:
            d = np.full((q.shape[0], k), np.inf if self.metric == "l2" else -np.inf, np.float32)
            return d, np.full((q.shape[0], k), -1, np.int64)
        self._sync()
        chunk = _knn_chunk(self._cap_local)
        return base.blocked_search(
            q, k, self.metric,
            _counted(self, lambda b: _sharded_knn_jit(
                _replicated(self.mesh, b), self._dev, self._ntotals,
                self.mesh, k, self.metric, chunk, live=self._live)),
            block=base.pick_query_block(65536 * 4),
            fused_fn=_counted(self, lambda q3: _sharded_knn_fused(
                _replicated(self.mesh, q3), self._dev, self._ntotals,
                self.mesh, k, self.metric, chunk, live=self._live)),
        )

    def reconstruct_batch(self, ids: np.ndarray) -> np.ndarray:
        ids = np.asarray(ids, np.int64)
        if ids.size == 0 or self._n == 0:
            return np.zeros((ids.size, self.dim), np.float32)
        self._sync()
        # flat pos == global id (contiguous layout): one bucketed gather
        bucket = base._next_pow2(ids.size, 1024)
        fidx = np.zeros(bucket, np.int64)
        fidx[:ids.size] = ids
        # graftlint: ok(host-sync): reconstruct returns host rows by contract
        return np.asarray(_take_rows(self._dev, jnp.asarray(fidx)))[:ids.size]

    def state_dict(self) -> Dict[str, np.ndarray]:
        if self._n:
            self._sync()
            rows = np.asarray(self._dev[: self._n])
        else:
            rows = np.zeros((0, self.dim), np.float32)
        return {
            "kind": "sharded_flat",
            "dim": self.dim,
            "metric": self.metric,
            "trained": True,
            "rows": rows,
        }

    @classmethod
    def from_state_dict(cls, state) -> "ShardedFlatIndex":
        idx = cls(int(state["dim"]), str(state["metric"]))
        rows = state["rows"]
        if rows.shape[0]:
            idx.add(rows)
        return idx


class IvfTpuIndex(IVFFlatIndex):
    """The ``ivf_tpu`` builder (reference analog: ivf_gpu clones the coarse
    quantizer to all GPUs for clustering, index.py:71-86): coarse k-means
    runs sharded over the mesh; list scan inherits the fused single-chip path
    (multi-chip list sharding is the next scale-up step)."""

    def __init__(self, *args, mesh: Optional[Mesh] = None, kmeans_iters: int = 10, **kwargs):
        super().__init__(*args, kmeans_iters=kmeans_iters, **kwargs)
        self.mesh = mesh or make_mesh()

    def _train_centroids(self, x: np.ndarray):
        self.centroids = sharded_kmeans(self.mesh, x, self.nlist, iters=self.kmeans_iters)


# ----------------------------------------------------- sharded inverted lists


class ShardedPaddedLists:
    """Inverted lists partitioned across the mesh (strided ownership:
    list l lives on shard l % S at local slot l // S, so adjacent/hot lists
    spread over chips). Same append/data/ids/sizes surface as
    models.base.PaddedLists, but the arrays are mesh-sharded — the capacity
    axis of the corpus scales with the number of chips.
    """

    MIN_CAP = 64
    APPEND_BUCKET = 1024

    def __init__(self, nlist: int, payload_shape, dtype, mesh: Mesh, min_cap: int = None):
        self.mesh = mesh
        self.S = mesh.shape[AXIS]
        self.nlist = nlist
        self.nlist_local = -(-nlist // self.S)
        self.nlist_pad = self.nlist_local * self.S
        self.payload_shape = tuple(payload_shape)
        self.dtype = dtype
        self.cap = min_cap or self.MIN_CAP
        self._check_cell_space(self.cap)
        self._data_sharding = NamedSharding(
            mesh, P(*((AXIS,) + (None,) * (1 + len(self.payload_shape))))
        )
        self.data = jax.device_put(
            jnp.zeros((self.nlist_pad, self.cap) + self.payload_shape, dtype),
            self._data_sharding,
        )
        self.ids = jax.device_put(
            jnp.full((self.nlist_pad, self.cap), -1, jnp.int32),
            NamedSharding(mesh, P(AXIS, None)),
        )
        self.sizes_host = np.zeros(nlist, np.int64)
        self._sizes_dev = jax.device_put(
            jnp.zeros(self.nlist_pad, jnp.int32), NamedSharding(mesh, P(AXIS))
        )

    @property
    def sizes(self):
        return self._sizes_dev

    @property
    def ntotal(self) -> int:
        return int(self.sizes_host.sum())

    def slot_of(self, l):
        """global list id -> flat padded slot (strided ownership)."""
        return (l % self.S) * self.nlist_local + l // self.S

    def _sizes_padded(self) -> np.ndarray:
        out = np.zeros(self.nlist_pad, np.int64)
        out[self.slot_of(np.arange(self.nlist))] = self.sizes_host
        return out

    def _check_cell_space(self, cap: int) -> None:
        """Scatter positions and the drop sentinel are int32 flat cell
        addresses over the whole padded space (``nlist_pad * cap``); past
        int32 they would wrap silently and corrupt foreign lists. Refuse the
        configuration instead of wrapping."""
        total = self.nlist_pad * cap
        if total > np.iinfo(np.int32).max:
            raise ValueError(
                f"sharded cell space nlist_pad({self.nlist_pad}) * cap({cap}) "
                f"= {total} overflows int32 addressing; shard over more chips "
                f"or split the index (DESIGN.md scale limits)"
            )

    def _grow(self, needed_cap: int):
        newcap = base._next_pow2(needed_cap, self.cap)
        if newcap == self.cap:
            return
        self._check_cell_space(newcap)
        pad_d = [(0, 0), (0, newcap - self.cap)] + [(0, 0)] * len(self.payload_shape)
        self.data = jax.device_put(jnp.pad(self.data, pad_d), self._data_sharding)
        self.ids = jax.device_put(
            jnp.pad(self.ids, [(0, 0), (0, newcap - self.cap)], constant_values=-1),
            NamedSharding(self.mesh, P(AXIS, None)),
        )
        self.cap = newcap

    def append(self, list_idx: np.ndarray, payload: np.ndarray, gids: np.ndarray):
        """Returns the (n,) int32 within-list positions in input order (same
        contract as models.base.PaddedLists.append)."""
        if list_idx.shape[0] == 0:
            return np.zeros(0, np.int32)
        counts = np.bincount(list_idx, minlength=self.nlist)
        new_sizes = self.sizes_host + counts
        if new_sizes.max() > self.cap:
            self._grow(int(new_sizes.max()))
        drop = self.nlist_pad * self.cap  # >= size -> dropped by each shard
        _, pos_b, pay_b, gid_b, within = base.PaddedLists.plan_append(
            list_idx, payload, gids, self.nlist, self.cap, self.sizes_host,
            self.payload_shape, self.dtype, self.slot_of, drop, self.APPEND_BUCKET,
        )
        # int32 positions: the per-shard-set cell address space is documented
        # as int32 (DESIGN.md scale limits)
        self._scatter(jnp.asarray(pos_b.astype(np.int32)), jnp.asarray(pay_b),
                      jnp.asarray(gid_b))
        self.sizes_host = new_sizes
        self._sizes_dev = jax.device_put(
            jnp.asarray(self._sizes_padded().astype(np.int32)),
            NamedSharding(self.mesh, P(AXIS)),
        )
        return within

    def mask_cells(self, cells: np.ndarray) -> None:
        """Tombstone list cells (flat ``slot * cap + pos`` addresses over
        the padded space): a per-shard drop-routed scatter of -1 into the
        sharded ids plane — the same ``ids >= 0`` AND every sharded scan
        (masked, routed, PQ) already applies then hides the row. Sizes are
        not decremented (live (slot, pos) addresses stay stable until
        compaction rewrites the lists)."""
        cells = np.asarray(cells, np.int64)
        if cells.size == 0:
            return
        bucket = base._next_pow2(cells.size, self.APPEND_BUCKET)
        per = self.nlist_local * self.cap
        cap = self.cap
        # split the flat global address into (chip, chip-local position)
        # on the HOST in int64: a global address over a big padded plane
        # can exceed int32 (nlist_pad * cap > 2^31 at production scale —
        # a silent wrap would drop the delete and resurrect the row on
        # device), while the per-chip local position is bounded by the
        # chip's own plane and the chip index by the mesh size
        chip = np.full(bucket, -1, np.int64)
        lpos_in = np.zeros(bucket, np.int64)
        chip[: cells.size] = cells // per
        lpos_in[: cells.size] = cells % per

        def local(ids_local, chip, lpos_in):
            me = jax.lax.axis_index(AXIS)
            lpos = jnp.where(chip == me, lpos_in, per)
            nl = ids_local.shape[0]
            fids = ids_local.reshape(per).at[lpos].set(-1, mode="drop")
            return fids.reshape(nl, cap)

        fn = shard_map(
            local,
            mesh=self.mesh,
            in_specs=(P(AXIS, None), P(), P()),
            out_specs=P(AXIS, None),
            check_vma=False,
        )
        # shape-keyed closure like _scatter: deletions are a cold,
        # operator-driven path, and the bucket bounds the variant count
        # graftlint: ok(recompile-hazard): shape-keyed closure, cold deletion path
        self.ids = jax.jit(fn, donate_argnums=(0,))(
            self.ids, jnp.asarray(chip.astype(np.int32)),
            jnp.asarray(lpos_in.astype(np.int32)))

    def _scatter(self, pos, payload, gids):
        """Each shard drops updates outside its flat range (shard_map so the
        partitioner never replicates the sharded operands)."""
        per = self.nlist_local * self.cap
        payload_shape = self.payload_shape
        cap = self.cap

        def local(data_local, ids_local, pos, payload, gids):
            lo = jax.lax.axis_index(AXIS).astype(jnp.int32) * per
            lpos = jnp.where((pos >= lo) & (pos < lo + per), pos - lo, per)
            flat = data_local.reshape((per,) + payload_shape)
            flat = flat.at[lpos].set(payload, mode="drop")
            fids = ids_local.reshape(per).at[lpos].set(gids, mode="drop")
            nl = data_local.shape[0]
            return (flat.reshape((nl, cap) + payload_shape),
                    fids.reshape(nl, cap))

        fn = shard_map(
            local,
            mesh=self.mesh,
            in_specs=(P(AXIS, None) if not payload_shape else P(AXIS, None, None),
                      P(AXIS, None), P(), P(), P()),
            out_specs=(P(AXIS, None) if not payload_shape else P(AXIS, None, None),
                       P(AXIS, None)),
            check_vma=False,
        )
        # fn closes over the post-grow shard_map specs, so the program is
        # shape-keyed anyway; appends re-trace only on capacity doubling
        # (O(log n) times over an index's lifetime)
        # graftlint: ok(recompile-hazard): shape-keyed closure, cold growth path
        self.data, self.ids = jax.jit(fn, donate_argnums=(0, 1))(
            self.data, self.ids, pos, payload, gids
        )


def _with_optional_rows(local, operands, specs, list_norms, raw_data,
                        refining):
    """Append the optional mesh-sharded per-list operands (stored norms,
    raw refine rows) by presence and return ``(operands, specs,
    wrapped)`` where ``wrapped`` re-binds them positionally to
    ``local(*head, norms_local, raw_local)`` — ONE copy of the pop order
    shared by the masked and routed scan drivers, so adding the next
    optional operand cannot desync the two."""
    head_n = len(operands)
    operands = list(operands)
    specs = list(specs)
    have_norms = list_norms is not None
    if have_norms:
        operands.append(list_norms)
        specs.append(P(AXIS, None))
    if refining:
        operands.append(raw_data)
        specs.append(P(AXIS, None, None))

    def wrapped(*args):
        head = args[:head_n]
        rest = list(args[head_n:])
        norms_local = rest.pop(0) if have_norms else None
        raw_local = rest.pop(0) if refining else None
        return local(*head, norms_local, raw_local)

    return operands, specs, wrapped


@functools.partial(jax.jit, static_argnames=("mesh", "k", "nprobe", "g", "metric",
                                             "scan_bf16", "adc_k"))
def _sharded_ivf_flat_search(centroids, list_data, list_ids, list_sizes, q,
                             mesh, k: int, nprobe: int, g: int, metric: str,
                             list_norms=None, scan_bf16: bool = False,
                             adc_k: int = 0, raw_data=None):
    """Corpus lists sharded across the mesh; probes masked by ownership.

    Every chip runs the same probe-group gathers against its local list
    block (non-owned probes are masked out), merges a local top-k, then the
    candidates ride one all_gather. Honest trade-off (documented): each chip
    does the full gather-shape work, so this scales HBM capacity with chips,
    not FLOPs — probe bucketing/routing is the next step.

    list_norms: mesh-sharded (nlist_pad, cap) fp32 stored ``||x||^2``
    sidecar (same layout as list_data) — gathered per probe instead of
    recomputed from the block, exactly like the single-chip scan in
    models/ivf.py so the two implementations can't drift; None keeps the
    recompute path (golden/A-B reference).

    scan_bf16: bf16 MXU scan pass (halved compute-operand traffic) — the
    model gates it behind refine_k_factor > 0 exactly like the single-chip
    scan, so final scores stay exact. adc_k/raw_data enable that exact
    refine (the ShardedIVFPQIndex pattern): the scan carries LOCAL cell
    positions, keeps a per-chip shortlist of adc_k (= k * refine_k_factor),
    rescores it exactly against the chip's fp16 raw rows (raw_data — same
    padded-list layout as the payload lists), and only the refined (nq, k)
    set rides the all_gather.
    """
    q = q.astype(jnp.float32)
    coarse = distance.pairwise_scores(q, centroids, metric)
    _, probes = distance.segmented_argtopk(coarse, nprobe)  # (nq, nprobe) global list ids
    nq = q.shape[0]
    cap = list_data.shape[1]
    qn = jnp.sum(q * q, axis=1, keepdims=True)
    S = mesh.shape[AXIS]
    groups = probes.reshape(nq, nprobe // g, g).transpose(1, 0, 2)
    refining = raw_data is not None
    local_k = adc_k if refining else k

    def local(q, qn, groups, data_local, ids_local, sizes_local, norms_local,
              raw_local):
        ax = jax.lax.axis_index(AXIS).astype(jnp.int32)
        # never-taken select: structural data dependency on the sharded input
        # so the scan carry's device-varying annotation matches the body
        # (shard_map vma rule); a select can't propagate NaN/Inf values
        anchor = jnp.where(jnp.zeros((), bool), data_local.reshape(-1)[0].astype(jnp.float32), 0.0)
        init = (
            jnp.full((nq, local_k), distance.NEG_INF, jnp.float32) + anchor,
            jnp.full((nq, local_k), -1, jnp.int32) + anchor.astype(jnp.int32),
        )

        def body(carry, li):  # li: (nq, g) global list ids
            best_v, best_i = carry
            mine = (li % S) == ax
            slot = jnp.where(mine, li // S, 0)
            block = data_local[slot].astype(jnp.float32)  # (nq, g, cap, d)
            ids = ids_local[slot]
            sizes = sizes_local[slot]
            if scan_bf16:
                ip = jnp.einsum("qd,qgcd->qgc", q.astype(jnp.bfloat16),
                                block.astype(jnp.bfloat16),
                                preferred_element_type=jnp.float32)
            else:
                ip = jnp.einsum("qd,qgcd->qgc", q, block, precision=_HIGHEST,
                                preferred_element_type=jnp.float32)
            if metric == "dot":
                s = ip
            else:
                bn = (norms_local[slot] if norms_local is not None
                      else base.row_norms_f32(block))
                s = -(qn[:, :, None] - 2.0 * ip + bn)
            valid = (jnp.arange(cap)[None, None, :] < sizes[:, :, None])
            valid = valid & (ids >= 0) & mine[:, :, None]
            s = jnp.where(valid, s, distance.NEG_INF)
            if refining:
                # carry LOCAL cell positions (one position addresses both
                # the ids plane and the raw rows for the post-scan rerank
                # — the ShardedIVFPQIndex refine contract)
                carried = slot[:, :, None] * cap \
                    + jnp.arange(cap, dtype=jnp.int32)[None, None, :]
            else:
                carried = ids
            carried = jnp.where(valid, carried, -1)
            cv, cids = distance.segmented_topk_rows(
                s.reshape(nq, g * cap), min(local_k, g * cap),
                carried.reshape(nq, g * cap))
            return distance.merge_topk(best_v, best_i, cv, cids, local_k), None

        (vals, out), _ = jax.lax.scan(body, init, groups)
        if refining:
            pos = out
            safe = jnp.where(pos >= 0, pos, 0)
            ids = jnp.where(pos >= 0, ids_local.reshape(-1)[safe], -1)
            # exact rerank of this chip's shortlist BEFORE the merge: the
            # ICI then carries already-exact (nq, k) candidates
            rows = raw_local.reshape(-1, raw_local.shape[-1])[safe]
            s = ivfmod.exact_candidate_scores(q, rows, metric)
            s = jnp.where(pos >= 0, s, distance.NEG_INF)
            vals, best = jax.lax.top_k(s, k)
            ids = jnp.take_along_axis(ids, best, axis=1)
        else:
            ids = out
        # merge the S local top-k sets over ICI
        av = jax.lax.all_gather(vals, AXIS)
        ai = jax.lax.all_gather(ids, AXIS)
        fv = jnp.transpose(av, (1, 0, 2)).reshape(nq, -1)
        fi = jnp.transpose(ai, (1, 0, 2)).reshape(nq, -1)
        best, pos = jax.lax.top_k(fv, k)
        return best, jnp.take_along_axis(fi, pos, axis=1)

    # operand list/specs assembled by presence (norms x raw combinations)
    operands, specs, wrapped = _with_optional_rows(
        local,
        [q, qn, groups, list_data, list_ids, list_sizes],
        [P(), P(), P(), P(AXIS, None, None), P(AXIS, None), P(AXIS)],
        list_norms, raw_data, refining)

    fn = shard_map(
        wrapped,
        mesh=mesh,
        in_specs=tuple(specs),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return fn(*operands)


class ShardedIVFFlatIndex(IVFFlatIndex):
    """IVF-Flat with mesh-sharded inverted lists: coarse k-means trains with
    psum reductions, list storage is partitioned across chip HBMs, search
    merges per-chip candidates over ICI. The full multi-chip serving path of
    the ivf_tpu builder (enable with cfg.extra['shard_lists']=True).

    scan_bf16 + refine_k_factor are wired (ROADMAP item 2 leftover): the
    bf16 MXU scan is legal only with the exact fp16 refine, enforced by the
    parent constructor exactly like the single-chip index; the refine rows
    live in a mesh-sharded raw-row sidecar laid out like the payload lists
    (the ShardedIVFPQIndex pattern), rescored per chip BEFORE the ICI
    merge. The fused pallas flat-scan kernel (pallas_flat) remains
    single-chip-only: its scalar-prefetched gather indexes the global
    (nlist, cap) layout, which shard_map's per-chip list blocks cannot
    express without an ownership-compaction pass — a documented limitation
    (docs/OPERATIONS.md#multi-chip-serving)."""

    def __init__(self, dim: int, nlist: int, metric: str = "l2",
                 mesh: Optional[Mesh] = None, kmeans_iters: int = 10,
                 probe_routing: bool = False, refine_k_factor: int = 0,
                 scan_bf16: bool = False):
        super().__init__(dim, nlist, metric, "f32", kmeans_iters=kmeans_iters,
                         refine_k_factor=refine_k_factor, scan_bf16=scan_bf16)
        # the single-device refine store the parent builds is replaced by a
        # mesh-sharded raw-row store laid out exactly like the payload
        # lists (one (slot, pos) addresses both — the raw_lists precedent
        # in ShardedIVFPQIndex)
        self.refine_store = None
        self.raw_lists: Optional[ShardedPaddedLists] = None
        self.mesh = mesh or make_mesh()
        # probe_routing: compact owned (query, probe) pairs per chip so the
        # scan FLOPs scale with the mesh (vs ownership masking, which only
        # scales capacity); see _sharded_ivf_flat_search_routed
        self.probe_routing = probe_routing
        self.launches = 0  # device-dispatch counter (see _counted)

    def _train_centroids(self, x: np.ndarray):
        self.centroids = sharded_kmeans(self.mesh, x, self.nlist, iters=self.kmeans_iters)

    def _make_lists(self):
        # stored-norms sidecar, sharded with the same strided ownership as
        # the payload lists so one (slot, pos) addresses both (the raw_lists
        # precedent in ShardedIVFPQIndex); dot never reads norms (see the
        # single-chip _make_lists)
        if self.metric == "l2":
            self.norm_lists = ShardedPaddedLists(self.nlist, (), np.float32, self.mesh)
        if self.refine_k_factor:
            self.raw_lists = ShardedPaddedLists(
                self.nlist, (self.dim,), np.float16, self.mesh)
        return ShardedPaddedLists(self.nlist, (self.dim,), np.float32, self.mesh)

    def _append_extra(self, x: np.ndarray, assign: np.ndarray, gids: np.ndarray,
                      rows: np.ndarray) -> None:
        if self.norm_lists is not None:
            self.norm_lists.append(assign, self._row_norms(rows), gids)
        if self.raw_lists is not None:
            from distributed_faiss_tpu.models.ivf import clip_f16

            # identical (assign, gids) stream as the payload lists ->
            # identical slot layout and capacity
            self.raw_lists.append(assign, clip_f16(x), gids)

    # one launch a window, results off the device once: the whole search
    # runs in the launch and the handle comes back finished (the parent's
    # two-phase form is the local index's, not this one's)
    launch_search = base.TpuIndex.launch_search

    def search(self, q: np.ndarray, k: int):
        if self._n == 0:
            return self._empty_results(q.shape[0], k)
        # snapshot restore leaves centroids single-device; the sharded
        # entries consume them replicated — re-place explicitly (no-op
        # once cached; see ShardedIVFPQIndex.search)
        self.centroids = _replicated(self.mesh, self.centroids)
        nprobe = min(self.nprobe, self.nlist)
        norms = self._scan_norms()
        refining = bool(self.refine_k_factor) and self.raw_lists is not None
        if refining and self.raw_lists.cap != self.lists.cap:
            raise RuntimeError("raw/payload list capacities diverged")
        adc_k = k * self.refine_k_factor if refining else 0
        raw = self.raw_lists.data if refining else None
        if self.probe_routing:
            # pair group sized so the (group, cap, d) fp32 block stays <=64MB
            group = max(8, min(1024, (64 << 20) // max(1, self.lists.cap * self.dim * 4)))
            return _routed_search_blocks(
                self, q, k, nprobe, group,
                _counted(self, lambda block, n, bucket: _sharded_ivf_flat_search_routed(
                    self.centroids, self.lists.data, self.lists.ids,
                    self.lists.sizes, _replicated(self.mesh, block),
                    _replicated(self.mesh, np.int32(n)), self.mesh, k, nprobe,
                    bucket, group, self.metric, list_norms=norms,
                    scan_bf16=self.scan_bf16, adc_k=adc_k, raw_data=raw,
                )),
                local_k=adc_k or k,
            )
        nb = base.pick_query_block(self.lists.cap * self.dim * 4)
        gsz = probe_group_size(nprobe, nb * self.lists.cap * self.dim * 4)
        return self._search_blocks(
            q, k,
            _counted(self, lambda b: _sharded_ivf_flat_search(
                self.centroids, self.lists.data, self.lists.ids, self.lists.sizes,
                _replicated(self.mesh, b), self.mesh, k, nprobe, gsz,
                self.metric, list_norms=norms,
                scan_bf16=self.scan_bf16, adc_k=adc_k, raw_data=raw,
            )),
            block=nb,
            fused_fn=_counted(self, lambda q3: _sharded_ivf_flat_search_fused(
                self.centroids, self.lists.data, self.lists.ids, self.lists.sizes,
                _replicated(self.mesh, q3), self.mesh, k, nprobe, gsz,
                self.metric, list_norms=norms,
                scan_bf16=self.scan_bf16, adc_k=adc_k, raw_data=raw,
            )),
        )

    def state_dict(self):
        state = super().state_dict()
        state["kind"] = "sharded_ivf_flat"
        state["probe_routing"] = self.probe_routing
        if self.raw_lists is not None and self._n:
            # stream the fp16 refine rows back through the shared
            # id -> (list, pos) map (the ShardedIVFPQIndex pattern)
            out = np.zeros((self._n, self.dim), np.float16)
            chunk = 1 << 20
            for s in range(0, self._n, chunk):
                e = min(self._n, s + chunk)
                ids = np.arange(s, e, dtype=np.int64)
                out[s:e] = base.gather_list_rows(
                    self.raw_lists, self._host_assign_array()[ids],
                    self._host_pos_array()[ids])
            state["refine_rows"] = out
        return state

    @classmethod
    def from_state_dict(cls, state):
        idx = cls(int(state["dim"]), int(state["nlist"]), str(state["metric"]),
                  probe_routing=bool(state.get("probe_routing", False)),
                  refine_k_factor=int(state.get("refine_k_factor", 0)),
                  scan_bf16=bool(state.get("scan_bf16", False)))
        idx.nprobe = int(state["nprobe"])
        if not bool(state["trained"]):
            return idx
        idx.centroids = jnp.asarray(state["centroids"])
        idx.lists = idx._make_lists()  # also builds raw_lists when refining
        rows, assign = state["rows"], state["assign"]
        if rows.shape[0]:
            gids = np.arange(rows.shape[0], dtype=np.int64)
            pos = idx.lists.append(assign, rows, gids)
            idx._host_assign = [assign.astype(np.int32)]
            idx._host_pos = [pos]
            idx._n = rows.shape[0]
            # snapshot norms when present, backfill pre-norms snapshots
            idx._restore_norms(state, rows, assign, gids)
            if idx.raw_lists is not None:
                if "refine_rows" not in state:
                    raise ValueError(
                        "sharded IVF-flat state has refine_k_factor set but "
                        "no refine_rows payload")
                idx.raw_lists.append(
                    assign, np.asarray(state["refine_rows"], np.float16), gids)
        return idx


@functools.partial(jax.jit, static_argnames=("mesh", "k", "nprobe", "g", "metric",
                                             "scan_bf16", "adc_k"))
def _sharded_ivf_flat_search_fused(centroids, list_data, list_ids, list_sizes, q3,
                                   mesh, k: int, nprobe: int, g: int, metric: str,
                                   list_norms=None, scan_bf16: bool = False,
                                   adc_k: int = 0, raw_data=None):
    """Multi-block sharded search in one launch: lax.map over stacked query
    blocks, shard_map per block inside (launch-bound serving — see
    models.base.pick_query_block)."""

    def body(qb):
        return _sharded_ivf_flat_search(centroids, list_data, list_ids,
                                        list_sizes, qb, mesh, k, nprobe, g,
                                        metric, list_norms=list_norms,
                                        scan_bf16=scan_bf16, adc_k=adc_k,
                                        raw_data=raw_data)

    return jax.lax.map(body, q3)


@functools.partial(jax.jit, static_argnames=("mesh", "k", "nprobe", "g", "metric",
                                             "use_pallas", "adc_k"))
def _sharded_ivf_pq_search_fused(centroids, codebooks, list_codes, list_ids,
                                 list_sizes, q3, mesh, k: int, nprobe: int,
                                 g: int, metric: str, use_pallas: bool = False,
                                 adc_k: int = 0, raw_data=None):
    """Multi-block masked sharded IVF-PQ in one launch (see
    _sharded_ivf_flat_search_fused); the third output is
    ``_sharded_ivf_pq_search``'s, one count a block."""

    def body(qb):
        return _sharded_ivf_pq_search(centroids, codebooks, list_codes,
                                      list_ids, list_sizes, qb, mesh, k,
                                      nprobe, g, metric, use_pallas=use_pallas,
                                      adc_k=adc_k, raw_data=raw_data)

    return jax.lax.map(body, q3)


@functools.partial(jax.jit, static_argnames=("mesh", "k", "nprobe", "g", "metric",
                                             "use_pallas", "adc_k"))
def _sharded_ivf_pq_search(centroids, codebooks, list_codes, list_ids, list_sizes,
                           q, mesh, k: int, nprobe: int, g: int, metric: str,
                           use_pallas: bool = False, adc_k: int = 0,
                           raw_data=None):
    """IVF-PQ with mesh-sharded code lists: per-chip ADC over owned probes
    (residual LUTs for l2 computed locally against replicated centroids),
    ICI all_gather merge. Same ownership masking trade-off as
    _sharded_ivf_flat_search.

    use_pallas swaps the one-hot einsum for the fused VMEM ADC kernel.

    adc_k/raw_data enable exact refine (FAISS IndexRefine-style): the scan
    tracks LOCAL cell positions, keeps a per-chip ADC shortlist of adc_k
    (= k * refine_k_factor), rescores it exactly against the chip's raw fp16
    rows (raw_data, same padded-list layout as the codes), and only then
    merges top-k over ICI. Per-chip top-adc_k is a superset of this chip's
    contribution to the global ADC top-adc_k, so recall >= the unsharded
    refine path's; the ICI still carries only (S, nq, k).

    -> (vals, ids, the candidate columns the scan computed ADC sums for, an
    int32 scalar summed over the chips, every (query, probe) pair counted
    on the chip that owns its list: ``nq * nprobe * cap`` on the XLA arm,
    whose one-hot scores every pair on every chip).
    """
    q = q.astype(jnp.float32)
    coarse = distance.pairwise_scores(q, centroids, metric)
    _, probes = distance.segmented_argtopk(coarse, nprobe)
    nq = q.shape[0]
    cap = list_codes.shape[1]
    m, ksub, _ = codebooks.shape
    S = mesh.shape[AXIS]
    groups = probes.reshape(nq, nprobe // g, g).transpose(1, 0, 2)
    local_k = adc_k if raw_data is not None else k

    from distributed_faiss_tpu.ops import pq as pqops

    if metric != "l2":
        shared_lut = pqops.adc_lut(q, codebooks, metric=metric)

    def local(q, groups, codes_local, ids_local, sizes_local, raw_local):
        ax = jax.lax.axis_index(AXIS).astype(jnp.int32)
        # never-taken select: vma-consistent scan carry (see flat variant)
        anchor = jnp.where(jnp.zeros((), bool),
                           codes_local.reshape(-1)[0].astype(jnp.float32), 0.0)
        init = (
            jnp.full((nq, local_k), distance.NEG_INF, jnp.float32) + anchor,
            jnp.full((nq, local_k), -1, jnp.int32) + anchor.astype(jnp.int32),
        )

        def body(carry, li):  # (nq, g) global list ids
            mine = (li % S) == ax
            slot = jnp.where(mine, li // S, 0)
            codes = codes_local[slot]  # (nq, g, cap, m)
            ids = ids_local[slot]
            sizes = sizes_local[slot]
            if metric == "l2":
                r = q[:, None, :] - centroids[li]
                lut = pqops.adc_lut(r.reshape(nq * g, -1), codebooks, metric="l2")
                lut = lut.reshape(nq, g, m, ksub)
            else:
                lut = jnp.broadcast_to(shared_lut[:, None], (nq, g, m, ksub))
            # a pair this chip does not own costs the kernel nothing
            s, cols = ivfmod._adc_pair_scores(
                lut.reshape(nq * g, m, ksub), codes.reshape(nq * g, cap, m),
                jnp.where(mine, sizes, 0).reshape(nq * g), use_pallas)
            if not use_pallas:  # the one-hot's count is of every pair
                cols = jnp.sum(mine.astype(jnp.int32)) * cap
            s = s.reshape(nq, g, cap)
            valid = (jnp.arange(cap)[None, None, :] < sizes[:, :, None])
            valid = valid & (ids >= 0) & mine[:, :, None]
            s = jnp.where(valid, s, distance.NEG_INF)
            # carry LOCAL cell positions, not global ids: one position
            # addresses both ids_local and raw_local for the post-scan
            # gathers (ids always; raw rows when refining)
            pos = slot[:, :, None] * cap + jnp.arange(cap, dtype=jnp.int32)[None, None, :]
            pos = jnp.where(valid, pos, -1)
            cv, cpos = distance.segmented_topk_rows(
                s.reshape(nq, g * cap), min(local_k, g * cap), pos.reshape(nq, g * cap))
            return distance.merge_topk(carry[0], carry[1], cv, cpos, local_k), cols

        (vals, pos), cols = jax.lax.scan(body, init, groups)
        safe = jnp.where(pos >= 0, pos, 0)
        ids = jnp.where(pos >= 0, ids_local.reshape(-1)[safe], -1)
        if raw_local is not None:
            # exact rerank of this chip's shortlist BEFORE the merge: the
            # ICI then carries already-exact (nq, k) candidates
            rows = raw_local.reshape(-1, raw_local.shape[-1])[safe]
            s = ivfmod.exact_candidate_scores(q, rows, metric)
            s = jnp.where(pos >= 0, s, distance.NEG_INF)
            vals, best = jax.lax.top_k(s, k)
            ids = jnp.take_along_axis(ids, best, axis=1)
        av = jax.lax.all_gather(vals, AXIS)
        ai = jax.lax.all_gather(ids, AXIS)
        fv = jnp.transpose(av, (1, 0, 2)).reshape(nq, -1)
        fi = jnp.transpose(ai, (1, 0, 2)).reshape(nq, -1)
        best, pick = jax.lax.top_k(fv, k)
        return (best, jnp.take_along_axis(fi, pick, axis=1),
                jax.lax.psum(jnp.sum(cols), AXIS))

    if raw_data is not None:
        fn = shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), P(), P(AXIS, None, None), P(AXIS, None), P(AXIS),
                      P(AXIS, None, None)),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
        return fn(q, groups, list_codes, list_ids, list_sizes, raw_data)
    fn = shard_map(
        lambda a, b, c, d, e: local(a, b, c, d, e, None),
        mesh=mesh,
        in_specs=(P(), P(), P(AXIS, None, None), P(AXIS, None), P(AXIS)),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return fn(q, groups, list_codes, list_ids, list_sizes)


class ShardedIVFPQIndex(IVFPQIndex):
    """IVF-PQ with mesh-sharded inverted code lists: coarse k-means trains
    with psum, PQ codebooks replicate, code storage partitions across chip
    HBMs (the BASELINE.json north-star config — sharded IVF-PQ — inside one
    server rank). Enable via the knnlm builder's extra
    {'shard_lists': True}."""

    def __init__(self, dim: int, nlist: int, m: int = 64, nbits: int = 8,
                 metric: str = "l2", mesh: Optional[Mesh] = None,
                 kmeans_iters: int = 10, pq_iters: int = 15,
                 probe_routing: bool = False,
                 use_pallas: Optional[bool] = None, refine_k_factor: int = 0):
        # use_pallas as the local index holds it: None, the index chooses
        # its ADC kernel (IVFPQIndex._kernel_applies); True / False force
        super().__init__(dim, nlist, m=m, nbits=nbits, metric=metric,
                         kmeans_iters=kmeans_iters, pq_iters=pq_iters,
                         use_pallas=use_pallas,
                         refine_k_factor=refine_k_factor)
        # the single-device refine store the parent builds is replaced by a
        # mesh-sharded raw-row store laid out exactly like the code lists
        # (persistence reads it back through the shared id -> (list, pos)
        # map — no host fp16 mirror; VERDICT r4)
        self.refine_store = None
        self.raw_lists: Optional[ShardedPaddedLists] = None
        self.mesh = mesh or make_mesh()
        self.probe_routing = probe_routing
        self.launches = 0  # device-dispatch counter (see _counted)

    def _train_centroids(self, x: np.ndarray):
        self.centroids = sharded_kmeans(self.mesh, x, self.nlist, iters=self.kmeans_iters)

    def _make_lists(self):
        if self.refine_k_factor:
            self.raw_lists = ShardedPaddedLists(
                self.nlist, (self.dim,), np.float16, self.mesh
            )
        return ShardedPaddedLists(self.nlist, (self.m,), np.uint8, self.mesh)

    def _append_extra(self, x: np.ndarray, assign: np.ndarray, gids: np.ndarray,
                      rows: np.ndarray):
        if self.raw_lists is not None:
            from distributed_faiss_tpu.models.ivf import clip_f16

            # identical (assign, gids) stream as the code lists -> identical
            # slot layout and capacity, so one local position addresses both
            self.raw_lists.append(assign, clip_f16(x), gids)

    # one launch a window, results off the device once: the whole search
    # runs in the launch and the handle comes back finished (the parent's
    # two-phase form is the local index's, not this one's)
    launch_search = base.TpuIndex.launch_search

    def search(self, q: np.ndarray, k: int):
        if self._n == 0:
            return self._empty_results(q.shape[0], k)
        # the parent's PQ training (and snapshot restore) leaves codebooks
        # and centroids as single-device arrays; the sharded entries
        # consume them replicated. Re-place them explicitly — an implicit
        # reshard at jit dispatch is exactly what DFT_XFERCHECK forbids —
        # and cache the placement (device_put no-ops once they match).
        self.codebooks = _replicated(self.mesh, self.codebooks)
        self.centroids = _replicated(self.mesh, self.centroids)
        nprobe = min(self.nprobe, self.nlist)
        refining = bool(self.refine_k_factor) and self.raw_lists is not None
        if refining:
            assert self.raw_lists.cap == self.lists.cap, (
                "raw/code list capacities diverged"
            )
        adc_k = k * self.refine_k_factor if refining else 0
        raw = self.raw_lists.data if refining else None

        # pair group sized so codes + one-hot transients stay bounded; the
        # bucket rounding in _routed_search_blocks closes over the same value
        group = max(8, min(512, (32 << 20) // max(1, self.lists.cap * self.m)))

        def run_routed(block, n, bucket, pallas_on):
            return _sharded_ivf_pq_search_routed(
                self.centroids, self.codebooks, self.lists.data,
                self.lists.ids, self.lists.sizes,
                _replicated(self.mesh, block),
                _replicated(self.mesh, np.int32(n)), self.mesh, k,
                nprobe, bucket, group, self.metric, use_pallas=pallas_on,
                adc_k=adc_k, raw_data=raw,
            )

        nb = base.pick_query_block(
            self.lists.cap * (self.m + 8) + self.m * 256 * 4)

        cap = self.lists.cap
        g = probe_group_size(
            nprobe, ivfmod.pq_probe_payload_bytes(cap, self.m, nq_block=nb))

        def masked(program, b, pallas_on, adc_k=adc_k, raw=raw):
            return ivfmod._count_on_its_way(program(
                self.centroids, self.codebooks, self.lists.data, self.lists.ids,
                self.lists.sizes, _replicated(self.mesh, b), self.mesh, k,
                nprobe, g, self.metric,
                use_pallas=pallas_on, adc_k=adc_k, raw_data=raw,
            ), "ADC column count")

        def attempt(call, *args):
            # launches counts INSIDE the ladder so a proven-failure XLA
            # re-dispatch is a second counted launch (the perf rows must
            # expose the degrade, not hide it)
            return _counted(self, lambda p: call(*args, p))

        if (self._kernel_applies() and self._pallas_runtime_ok
                and not self._adc_validated):
            # first fused scan of this index (warm-up, in a served rank), as
            # the local index checks its own: the kernel's ADC scores, before
            # any refine, against the XLA path's on one small block. Always
            # of the masked program: both modes score a pair through the
            # one ivfmod._adc_pair_scores, which is what is checked, and the
            # masked program takes a block as it is, where the routed one
            # needs a pair bucket sized to the block. A routed index pays
            # for it with one compile and two launches of a program it does
            # not serve with, once, in its first window
            self._adc_validated = True
            ivfmod._first_use_check(
                self, lambda b, p: _counted(self, masked)(
                    _sharded_ivf_pq_search, b, p, adc_k=0, raw=None),
                distance.pad_rows(np.asarray(q[:8], np.float32), 8),
                self._PALLAS_KERNEL, 1e-4)

        if self.probe_routing:
            # the unsharded path's ladder (kernel -> XLA oracle -> demote)
            return _routed_search_blocks(
                self, q, k, nprobe, group,
                lambda block, n, bucket: self._guarded_scan(
                    attempt(run_routed, block, n, bucket)),
                local_k=adc_k or k,
            )

        counts = []  # (capacity columns, columns scored) of every scan

        def guarded(program, b, rows):
            """The same ladder, and at the scan's wait its count taken off
            its outputs, for the rows the local index books."""

            def settled(out, with_pallas):
                vals, ids, cols = self._fused_counted(out, with_pallas)
                counts.append((rows * nprobe * cap, cols))
                return vals, ids

            return ivfmod.GuardedScan(
                self, attempt(masked, program, b), settled).wait()

        out = self._search_blocks(
            q, k, lambda b: guarded(_sharded_ivf_pq_search, b, b.shape[0]),
            block=nb,
            fused_fn=lambda q3: guarded(_sharded_ivf_pq_search_fused, q3,
                                        q3.shape[0] * q3.shape[1]))
        self._book_adc_cols(counts)
        return out

    def state_dict(self):
        state = super().state_dict()
        state["kind"] = "sharded_ivf_pq"
        state["probe_routing"] = self.probe_routing
        if self.raw_lists is not None and self._n:
            # the raw fp16 rows share the code lists' (assign, pos) layout,
            # so the same id -> (list, pos) map streams them back from HBM
            out = np.zeros((self._n, self.dim), np.float16)
            chunk = 1 << 20
            for s in range(0, self._n, chunk):
                e = min(self._n, s + chunk)
                ids = np.arange(s, e, dtype=np.int64)
                out[s:e] = base.gather_list_rows(
                    self.raw_lists, self._host_assign_array()[ids],
                    self._host_pos_array()[ids])
            state["refine_rows"] = out
        return state

    @classmethod
    def from_state_dict(cls, state):
        idx = cls(int(state["dim"]), int(state["nlist"]), m=int(state["m"]),
                  nbits=int(state["nbits"]), metric=str(state["metric"]),
                  probe_routing=bool(state.get("probe_routing", False)),
                  use_pallas=cls._saved_kernel_intent(state),
                  refine_k_factor=int(state.get("refine_k_factor", 0)))
        idx.nprobe = int(state["nprobe"])
        if not bool(state["trained"]):
            return idx
        idx.centroids = jnp.asarray(state["centroids"])
        idx.codebooks = jnp.asarray(state["codebooks"])
        idx.lists = idx._make_lists()  # also builds raw_lists when refining
        rows, assign = state["rows"], state["assign"]
        if rows.shape[0]:
            gids = np.arange(rows.shape[0], dtype=np.int64)
            pos = idx.lists.append(assign, rows, gids)
            idx._host_assign = [assign.astype(np.int32)]
            idx._host_pos = [pos]
            idx._n = rows.shape[0]
            if idx.raw_lists is not None:
                if "refine_rows" not in state:
                    raise ValueError(
                        "sharded IVF-PQ state has refine_k_factor set but no "
                        "refine_rows payload"
                    )
                raw = np.asarray(state["refine_rows"], np.float16)
                idx.raw_lists.append(assign, raw, gids)
        return idx


# ------------------------------------------------- routed sharded IVF search


def _routed_pairs_local(probes, nq_real, nprobe: int, pair_bucket: int,
                        group: int, k: int, cap: int, S: int, anchor,
                        score_group, q=None, raw_local=None, metric=None,
                        adc_k: int = 0):
    """Shared per-chip body of probe-routed search.

    Compacts this chip's owned (query, probe) pairs into ``pair_bucket``,
    scores them in ``group``-sized batches via ``score_group(qi, li, slot,
    valid) -> (scores (g, cap), ids (g, cap))`` (qi = query row, li = global
    list id, slot = local list slot), reduces to a per-query
    (nq, k) top-k locally, and merges the (S, nq, k) candidate sets over one
    all_gather. Returns (vals, ids, dropped).

    When ``raw_local`` is given (exact refine), ``score_group`` must return a
    third (g, cap) array of LOCAL cell positions; the per-query reduction
    keeps ``adc_k`` candidates, rescans them exactly against ``raw_local``
    (flattened (slots*cap, d) fp16 rows addressed by position), and only the
    refined (nq, k) set rides the all_gather."""
    refine = raw_local is not None
    local_k = adc_k if refine else k
    nq = probes.shape[0]
    n_pairs = nq * nprobe
    ngroups = pair_bucket // group
    ax = jax.lax.axis_index(AXIS).astype(jnp.int32)
    flat_li = probes.reshape(n_pairs)
    # pairs from zero-padded query rows (pad_rows buckets) are excluded:
    # they would concentrate on a few chips and fire spurious drop warnings
    real_row = (jnp.arange(n_pairs, dtype=jnp.int32) // nprobe) < nq_real
    mine = ((flat_li % S) == ax) & real_row
    owned_count = jnp.sum(mine.astype(jnp.int32))
    # compact owned pair indices into the fixed bucket (1s sort first; note
    # top_k breaks ties by lower index, which keeps earlier pairs); pad the
    # mask when the bucket exceeds the total pair count (small query batches)
    pad = max(0, pair_bucket - n_pairs)
    mine_p = jnp.concatenate([mine, jnp.zeros(pad, bool)]) if pad else mine
    sel_val, sel_idx = jax.lax.top_k(mine_p.astype(jnp.int32), pair_bucket)
    sel_idx = jnp.minimum(sel_idx, n_pairs - 1)
    pair_valid = sel_val > 0
    pair_qi = (sel_idx // nprobe).astype(jnp.int32)   # (B,)
    pair_li = flat_li[sel_idx]                         # (B,)
    pair_slot = jnp.where(pair_valid, pair_li // S, 0)

    kk = min(local_k, cap)

    def body(carry, g_idx):
        vals_acc, ids_acc, pos_acc = carry
        s0 = g_idx * group
        qi = jax.lax.dynamic_slice(pair_qi, (s0,), (group,))
        li = jax.lax.dynamic_slice(pair_li, (s0,), (group,))
        slot = jax.lax.dynamic_slice(pair_slot, (s0,), (group,))
        valid = jax.lax.dynamic_slice(pair_valid, (s0,), (group,))
        out = score_group(qi, li, slot, valid)         # (g, cap) each
        s, ids = out[0], out[1]
        pv, pp = jax.lax.top_k(s, kk)                  # per-pair top-k
        pids = jnp.take_along_axis(ids, pp, axis=1)
        vals_acc = jax.lax.dynamic_update_slice(vals_acc, pv, (s0, 0))
        ids_acc = jax.lax.dynamic_update_slice(ids_acc, pids, (s0, 0))
        if refine:
            ppos = jnp.take_along_axis(out[2], pp, axis=1)
            pos_acc = jax.lax.dynamic_update_slice(pos_acc, ppos, (s0, 0))
        return (vals_acc, ids_acc, pos_acc), None

    init = (
        jnp.full((pair_bucket, kk), distance.NEG_INF, jnp.float32) + anchor,
        jnp.full((pair_bucket, kk), -1, jnp.int32) + anchor.astype(jnp.int32),
        jnp.full((pair_bucket, kk), -1, jnp.int32) + anchor.astype(jnp.int32),
    )
    (pair_vals, pair_ids, pair_pos), _ = jax.lax.scan(
        body, init, jnp.arange(ngroups, dtype=jnp.int32)
    )

    # reduce THIS chip's pairs to a per-query (nq, k) top-k BEFORE the
    # all_gather: ICI then carries (S, nq, k) instead of (S, B, kk), and
    # the replicated final merge is the cheap (nq, S*k) one
    dropped = jax.lax.pmax(jnp.maximum(owned_count - pair_bucket, 0), AXIS)
    QB = 16
    nqb = -(-nq // QB)

    def qmerge(carry, b_idx):
        out_v, out_i, out_p = carry
        q0 = b_idx * QB
        qids = q0 + jnp.arange(QB, dtype=jnp.int32)   # (QB,)
        m = pair_qi[None, :] == qids[:, None]         # (QB, B)
        mv = jnp.where(m[:, :, None], pair_vals[None, :, :], distance.NEG_INF)
        mi = jnp.where(m[:, :, None], pair_ids[None, :, :], -1)
        # two-stage segmented reduce over the (QB, B*kk) masked block;
        # pad sentinel -1 matches the masked entries' own -1 ids
        bv, bp = distance.segmented_argtopk(mv.reshape(QB, -1), local_k)
        safe = jnp.where(bp >= 0, bp, 0)
        bi = jnp.where(
            bp >= 0, jnp.take_along_axis(mi.reshape(QB, -1), safe, axis=1), -1)
        out_v = jax.lax.dynamic_update_slice(out_v, bv, (q0, 0))
        out_i = jax.lax.dynamic_update_slice(out_i, bi, (q0, 0))
        if refine:
            mp = jnp.where(m[:, :, None], pair_pos[None, :, :], -1)
            bpos = jnp.where(
                bp >= 0, jnp.take_along_axis(mp.reshape(QB, -1), safe, axis=1), -1)
            out_p = jax.lax.dynamic_update_slice(out_p, bpos, (q0, 0))
        return (out_v, out_i, out_p), None

    pad_q = nqb * QB
    init_q = (
        jnp.full((pad_q, local_k), distance.NEG_INF, jnp.float32) + anchor,
        jnp.full((pad_q, local_k), -1, jnp.int32) + anchor.astype(jnp.int32),
        jnp.full((pad_q, local_k), -1, jnp.int32) + anchor.astype(jnp.int32),
    )
    (loc_v, loc_i, loc_p), _ = jax.lax.scan(qmerge, init_q,
                                            jnp.arange(nqb, dtype=jnp.int32))
    loc_v, loc_i = loc_v[:nq], loc_i[:nq]
    if refine:
        # exact rescan of this chip's adc_k shortlist before the merge
        loc_p = loc_p[:nq]
        safe = jnp.where(loc_p >= 0, loc_p, 0)
        rows = raw_local.reshape(-1, raw_local.shape[-1])[safe]
        s = ivfmod.exact_candidate_scores(q, rows, metric)
        s = jnp.where(loc_p >= 0, s, distance.NEG_INF)
        loc_v, best = jax.lax.top_k(s, k)
        loc_i = jnp.take_along_axis(loc_i, best, axis=1)
    av = jax.lax.all_gather(loc_v, AXIS)              # (S, nq, k)
    ai = jax.lax.all_gather(loc_i, AXIS)
    fv = jnp.transpose(av, (1, 0, 2)).reshape(nq, -1)
    fi = jnp.transpose(ai, (1, 0, 2)).reshape(nq, -1)
    best, pos = jax.lax.top_k(fv, k)
    return best, jnp.take_along_axis(fi, pos, axis=1), dropped


@functools.partial(jax.jit, static_argnames=("mesh", "k", "nprobe", "pair_bucket",
                                             "group", "metric", "scan_bf16",
                                             "adc_k"))
def _sharded_ivf_flat_search_routed(centroids, list_data, list_ids, list_sizes, q,
                                    nq_real, mesh, k: int, nprobe: int,
                                    pair_bucket: int, group: int, metric: str,
                                    list_norms=None, scan_bf16: bool = False,
                                    adc_k: int = 0, raw_data=None):
    """Probe-routed sharded IVF: FLOPs scale with the mesh, not just capacity.

    The masked variant (_sharded_ivf_flat_search) has every chip do the full
    (nq x nprobe) gather/einsum work and zero out non-owned probes. Here each
    chip scores only the pairs it owns (see _routed_pairs_local).
    list_norms: sharded stored-norms sidecar (see _sharded_ivf_flat_search);
    None recomputes from the block. scan_bf16 runs the pair einsum in bf16
    (model-gated behind refine); adc_k/raw_data enable the pre-merge exact
    refine via _routed_pairs_local's position-carrying path (the routed PQ
    precedent).

    pair_bucket bounds per-chip work; pairs beyond it are DROPPED (skewed
    ownership). The third return value is the max dropped-pairs count across
    chips so callers can warn/resize. With strided list ownership and
    top-nprobe probing, ownership is near-uniform and the default 2x slack
    makes drops rare; full probe (nprobe == nlist) is exactly uniform and
    never drops.
    """
    q = q.astype(jnp.float32)
    coarse = distance.pairwise_scores(q, centroids, metric)
    _, probes = distance.segmented_argtopk(coarse, nprobe)  # (nq, nprobe)
    cap = list_data.shape[1]
    S = mesh.shape[AXIS]
    qn = jnp.sum(q * q, axis=1, keepdims=True)
    refining = raw_data is not None

    def local(q, qn, probes, nq_real, data_local, ids_local, sizes_local,
              norms_local, raw_local):
        anchor = jnp.where(jnp.zeros((), bool),
                           data_local.reshape(-1)[0].astype(jnp.float32), 0.0)

        def score_group(qi, li, slot, valid):
            qv = q[qi]                        # (g, d) gathered queries
            block = data_local[slot].astype(jnp.float32)  # (g, cap, d)
            ids = ids_local[slot]
            sizes = sizes_local[slot]
            if scan_bf16:
                ip = jnp.einsum("bd,bcd->bc", qv.astype(jnp.bfloat16),
                                block.astype(jnp.bfloat16),
                                preferred_element_type=jnp.float32)
            else:
                ip = jnp.einsum("bd,bcd->bc", qv, block, precision=_HIGHEST,
                                preferred_element_type=jnp.float32)
            if metric == "dot":
                s = ip
            else:
                bn = (norms_local[slot] if norms_local is not None
                      else base.row_norms_f32(block))
                s = -(qn[qi] - 2.0 * ip + bn)
            ok = (jnp.arange(cap)[None, :] < sizes[:, None]) & (ids >= 0)
            ok = ok & valid[:, None]
            s = jnp.where(ok, s, distance.NEG_INF)
            ids = jnp.where(ok, ids, -1)
            if not refining:
                return s, ids
            pos = slot[:, None] * cap + jnp.arange(cap, dtype=jnp.int32)[None, :]
            return s, ids, jnp.where(ok, pos, -1)

        return _routed_pairs_local(probes, nq_real, nprobe, pair_bucket, group,
                                   k, cap, S, anchor, score_group,
                                   q=q, raw_local=raw_local, metric=metric,
                                   adc_k=adc_k)

    operands, specs, wrapped = _with_optional_rows(
        local,
        [q, qn, probes, jnp.asarray(nq_real, jnp.int32),
         list_data, list_ids, list_sizes],
        [P(), P(), P(), P(), P(AXIS, None, None), P(AXIS, None), P(AXIS)],
        list_norms, raw_data, refining)

    fn = shard_map(
        wrapped,
        mesh=mesh,
        in_specs=tuple(specs),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return fn(*operands)


@functools.partial(jax.jit, static_argnames=("mesh", "k", "nprobe", "pair_bucket",
                                             "group", "metric", "use_pallas",
                                             "adc_k"))
def _sharded_ivf_pq_search_routed(centroids, codebooks, list_codes, list_ids,
                                  list_sizes, q, nq_real, mesh, k: int,
                                  nprobe: int, pair_bucket: int, group: int,
                                  metric: str, use_pallas: bool = False,
                                  adc_k: int = 0, raw_data=None):
    """Probe-routed sharded IVF-PQ: per-pair residual LUTs + ADC (one-hot
    einsum or fused pallas kernel) over owned pairs only (same scaffold as
    the flat variant). adc_k/raw_data enable pre-merge exact refine — see
    _routed_pairs_local."""
    from distributed_faiss_tpu.ops import pq as pqops

    q = q.astype(jnp.float32)
    coarse = distance.pairwise_scores(q, centroids, metric)
    _, probes = distance.segmented_argtopk(coarse, nprobe)
    cap = list_codes.shape[1]
    S = mesh.shape[AXIS]
    refine = raw_data is not None

    def local(q, probes, nq_real, codes_local, ids_local, sizes_local, raw_local):
        anchor = jnp.where(jnp.zeros((), bool),
                           codes_local.reshape(-1)[0].astype(jnp.float32), 0.0)

        def score_group(qi, li, slot, valid):
            qv = q[qi]                                   # (g, d)
            if metric == "l2":
                r = qv - centroids[li]                   # per-pair residual
            else:
                r = qv
            lut = pqops.adc_lut(r, codebooks, metric=metric)  # (g, m, ksub)
            codes = codes_local[slot]                    # (g, cap, m)
            ids = ids_local[slot]
            sizes = sizes_local[slot]
            s, _ = ivfmod._adc_pair_scores(              # (g, cap)
                lut, codes, jnp.where(valid, sizes, 0), use_pallas)
            ok = (jnp.arange(cap)[None, :] < sizes[:, None]) & (ids >= 0)
            ok = ok & valid[:, None]
            s = jnp.where(ok, s, distance.NEG_INF)
            ids = jnp.where(ok, ids, -1)
            if not refine:
                return s, ids
            pos = slot[:, None] * cap + jnp.arange(cap, dtype=jnp.int32)[None, :]
            return s, ids, jnp.where(ok, pos, -1)

        return _routed_pairs_local(probes, nq_real, nprobe, pair_bucket, group,
                                   k, cap, S, anchor, score_group,
                                   q=q, raw_local=raw_local, metric=metric,
                                   adc_k=adc_k)

    if refine:
        fn = shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), P(), P(), P(AXIS, None, None), P(AXIS, None), P(AXIS),
                      P(AXIS, None, None)),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
        return fn(q, probes, jnp.asarray(nq_real, jnp.int32),
                  list_codes, list_ids, list_sizes, raw_data)
    fn = shard_map(
        lambda a, b, c, d, e, f: local(a, b, c, d, e, f, None),
        mesh=mesh,
        in_specs=(P(), P(), P(), P(AXIS, None, None), P(AXIS, None), P(AXIS)),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return fn(q, probes, jnp.asarray(nq_real, jnp.int32),
              list_codes, list_ids, list_sizes)


def _routed_block_size(nprobe: int, S: int, group: int, slack: float,
                       local_k: int, budget: int = 256 * 1024 * 1024) -> int:
    """Largest query block whose routed per-chip transients fit the budget.

    Unlike the gather-based modes (bounded by a fixed (group, cap, d)
    score block), routed transients scale with the query block through
    pair_bucket: the qmerge stage broadcasts (QB=16, pair_bucket, kk)
    masked value/id(/pos) arrays per scan step, plus the (pair_bucket, kk)
    scan accumulators. Estimate = 3 arrays * 4 bytes * pair_bucket * kk *
    (QB + 1), evaluated at the bucket the block would start with."""
    block = base.MAX_QUERY_BLOCK
    while block > 256:
        bucket = routed_pair_bucket(block, nprobe, S, group, slack)
        if 3 * 4 * bucket * local_k * (16 + 1) <= budget:
            break
        block //= 2
    return block


def _routed_search_blocks(index, q, k: int, nprobe: int, group: int, call,
                          local_k: int = None):
    """Shared block-loop driver for probe-routed searches.

    ``call(block, nq_real, bucket) -> (vals, ids, dropped)``. Handles query
    bucketing, drop-driven bucket resizing, and FAISS-style finalization.

    Dropped pairs are silently-unscanned candidates (= recall loss), so a
    nonzero drop count is never just warned about: the block re-runs with a
    doubled bucket until drops reach zero or the bucket covers every pair
    (at which point drops are impossible). The grown slack persists on the
    index so later blocks — and later searches — start at the size that
    worked; each growth step is one extra compile, paid at most
    log2(S / slack) times per (shape, nprobe)."""
    S = index.mesh.shape[AXIS]
    q = np.asarray(q, np.float32)
    nq = q.shape[0]
    out_s = np.empty((nq, k), np.float32)
    out_i = np.empty((nq, k), np.int64)
    slack = float(getattr(index, "_routed_slack", 2.0))
    # take the largest block whose routed transients fit the byte budget —
    # they scale with the block through pair_bucket (see _routed_block_size);
    # the budget and the largest-block premise (base.pick_query_block) are
    # unmeasured on a local chip — ROADMAP S3
    nb = _routed_block_size(nprobe, S, group, slack,
                            local_k if local_k is not None else k)
    for s0, n, block in base.query_blocks(q, nb):
        bq = block.shape[0]
        # every pair on one chip is the worst case: a bucket this big
        # cannot drop, so the resize loop below terminates
        hard_cap = -(-bq * nprobe // group) * group
        bucket = min(routed_pair_bucket(bq, nprobe, S, group, slack), hard_cap)
        while True:
            # the raw numpy block goes through; call() device_puts it
            # onto the mesh explicitly (_replicated) so the feed passes
            # DFT_XFERCHECK's transfer guard
            vals, ids, dropped = call(block, n, bucket)
            with xfercheck.explicit("routed drop-count readback"):
                nd = int(dropped)
            if nd == 0 or bucket >= hard_cap:
                break
            bucket = min(2 * bucket, hard_cap)
            slack = min(2.0 * slack, float(S))
            logger.info(
                "probe routing dropped %d pairs (skewed list ownership); "
                "retrying block with bucket=%d", nd, bucket,
            )
        if nd:  # pragma: no cover - unreachable once bucket == hard_cap
            logger.warning(
                "probe routing still dropped %d pairs at the full-pair "
                "bucket; results may lose recall", nd,
            )
        with xfercheck.explicit("routed block result fetch"):
            out_s[s0:s0 + n] = np.asarray(vals)[:n]
            out_i[s0:s0 + n] = np.asarray(ids)[:n]
    index._routed_slack = slack
    return base.finalize_results(out_s, out_i, index.metric)


def routed_pair_bucket(nq: int, nprobe: int, S: int, group: int, slack: float = 2.0):
    """Fixed per-chip pair budget: slack x the uniform share, group-aligned."""
    b = max(group, int(-(-nq * nprobe * slack // S)))
    return -(-b // group) * group
