"""Pallas TPU kernel for the PQ asymmetric-distance (ADC) scan.

The ADC contract (ops/pq.py): scores[q, c] = sum_m lut[q, m, codes[c, m]].
SURVEY §7 calls this the kernel that decides IVF-PQ QPS. The XLA fallback
expresses the LUT gather as a one-hot einsum; this kernel fuses the whole
pipeline in VMEM so the one-hot never exists in HBM:

  per (query-block, candidate-tile) grid step, for each subspace m
  (statically unrolled): build the (TILE, ksub) one-hot on the VPU from a
  broadcasted iota compare against the uint8 codes, and accumulate
  lut_m @ onehot.T on the MXU into the (nq, TILE) output block.

VMEM budget per step: lut (nq x m*ksub fp32) + codes tile (TILE x m u8) +
one (TILE, ksub) one-hot + (nq, TILE) accumulator — a few MB at the default
TILE=512, nq<=128, m<=64, well under the ~16 MB/core budget.

``interpret=True`` (automatic off-TPU) runs the same kernel through the
Pallas interpreter so CPU tests cover the exact kernel code path.
"""

import functools
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TILE = 512

# The fused one-hot scratch is the VMEM budget driver: ONE (TILE, m*ksub)
# f32 buffer (built in place, reused every grid step). Measured on TPU
# v5e: the earlier per-subspace variant made Mosaic stack-allocate one
# (TILE, ksub) buffer per statically unrolled subspace with NO
# cross-iteration reuse — m=64/TILE=512 demanded 43.5 MB of scoped VMEM
# against the 16 MB limit. A single scratch ref sidesteps that allocator
# behavior and turns the scan into one big MXU matmul per tile.
_ONEHOT_VMEM_BUDGET = 8 * 1024 * 1024


def _fit_tile(tile: int, m: int, ksub: int, L: int, itemsize: int = 4,
              interpret: bool = False) -> int:
    if interpret:
        # the interpreter has no VMEM; keep the pre-round-2 clamp so CPU
        # tests can run any geometry
        return min(tile, max(8, L))
    fit = _ONEHOT_VMEM_BUDGET // (m * ksub * itemsize)
    fit = (fit // 128) * 128  # lane-aligned output blocks
    if fit < 128:
        # even the minimum lane-aligned tile would overflow scoped VMEM
        # (plus the LUT block); raising at trace time is deliberate — the
        # IVF-PQ models' guarded fallback catches it and retries the XLA
        # one-hot path (use a bf16 LUT to halve the footprint instead)
        raise ValueError(
            f"pallas ADC: PQ geometry m={m} ksub={ksub} itemsize={itemsize} "
            f"exceeds the VMEM one-hot budget at the minimum 128-row tile"
        )
    return min(tile, fit, max(8, L))


def on_tpu() -> bool:
    """True when jax dispatches to a TPU — the ONE shared predicate deciding
    compiled-vs-interpreted kernel mode. A backend that fails to initialize
    raises here; it is never read as "not on TPU"."""
    return jax.default_backend() == "tpu"


def _build_onehot(m: int, ksub: int, codes, onehot_ref):
    """Scatter codes (TILE, m) u8 into onehot_ref (TILE, m*ksub):
    row c gets a 1 at column mi*ksub + codes[c, mi] for each subspace.
    The one-hot inherits the scratch dtype — 0/1 are exact in bf16, so a
    bf16 LUT halves VMEM traffic (the kernel's bottleneck) losslessly on
    the one-hot side."""
    tile = codes.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (tile, ksub), 1)
    for mi in range(m):  # static unroll; each store reuses the same scratch
        cm = codes[:, mi].astype(jnp.int32).reshape(tile, 1)
        onehot_ref[:, mi * ksub:(mi + 1) * ksub] = (cm == iota).astype(onehot_ref.dtype)


def _adc_matmul(lut, onehot):
    """(nq, m*ksub) x (TILE, m*ksub) -> (nq, TILE), contracting m*ksub on
    the MXU, f32 accumulate. HIGHEST: for f32 LUTs this matches the XLA
    ADC path (pq.py) bit-for-bit intent; for bf16 LUTs the MXU's native
    bf16 pass is already exact given bf16 inputs."""
    # HIGHEST's multi-pass trick only exists for f32 operands; on bf16
    # inputs Mosaic rejects it ("Bad lhs type") — and the native bf16 MXU
    # pass is already exact for bf16 inputs, so DEFAULT is the right ask.
    precision = (jax.lax.Precision.HIGHEST if lut.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(
        lut, onehot, (((1,), (1,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32,
    )


def _adc_kernel(m: int, ksub: int, lut_ref, codes_ref, out_ref, onehot_ref):
    _build_onehot(m, ksub, codes_ref[:, :], onehot_ref)
    out_ref[:, :] = _adc_matmul(lut_ref[:, :], onehot_ref[:, :])


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def adc_scan_shared_pallas(lut, codes, tile: int = DEFAULT_TILE, interpret: bool = False):
    """ADC scan of one shared candidate list.

    lut: (nq, m, ksub) f32; codes: (L, m) uint8 -> (nq, L) f32 scores.
    Grid over candidate tiles; L is padded to a tile multiple (scores for
    padding rows are garbage and sliced off).
    """
    nq, m, ksub = lut.shape
    L = codes.shape[0]
    tile = _fit_tile(tile, m, ksub, L, jnp.dtype(lut.dtype).itemsize, interpret)
    Lp = -(-L // tile) * tile
    if Lp != L:
        codes = jnp.pad(codes, ((0, Lp - L), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_adc_kernel, m, ksub),
        grid=(Lp // tile,),
        in_specs=[
            pl.BlockSpec((nq, m * ksub), lambda i: (0, 0)),
            pl.BlockSpec((tile, m), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((nq, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((nq, Lp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tile, m * ksub), lut.dtype)],
        interpret=interpret,
    )(lut.reshape(nq, m * ksub), codes)
    return out[:, :L]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def adc_scan_pallas(lut, codes, tile: int = DEFAULT_TILE, interpret: bool = False):
    """Per-query-list ADC scan (the IVF probe path).

    lut: (nq, m, ksub) f32; codes: (nq, L, m) uint8 -> (nq, L) f32.
    Grid over (query, candidate-tile); each step scores one query's tile
    against that query's own LUT.
    """
    nq, m, ksub = lut.shape
    L = codes.shape[1]
    tile = _fit_tile(tile, m, ksub, L, jnp.dtype(lut.dtype).itemsize, interpret)
    Lp = -(-L // tile) * tile
    if Lp != L:
        codes = jnp.pad(codes, ((0, 0), (0, Lp - L), (0, 0)))

    def kernel(lut_ref, codes_ref, out_ref, onehot_ref):
        # lut_ref: (1, 1, m*ksub); codes_ref: (1, tile, m); out_ref: (1, 1, tile)
        _build_onehot(m, ksub, codes_ref[0], onehot_ref)
        out_ref[0, :, :] = _adc_matmul(lut_ref[0], onehot_ref[:, :])

    # lut rides as (nq, 1, m*ksub): compiled Mosaic requires the last two
    # block dims be 8/128-divisible OR equal to the full array dims — a
    # (1, m*ksub) block of a (nq, m*ksub) array violates that, a
    # (1, 1, m*ksub) block of (nq, 1, m*ksub) satisfies it.
    out = pl.pallas_call(
        kernel,
        grid=(nq, Lp // tile),
        in_specs=[
            pl.BlockSpec((1, 1, m * ksub), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, tile, m), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, tile), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((nq, 1, Lp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tile, m * ksub), lut.dtype)],
        interpret=interpret,
    )(lut.reshape(nq, 1, m * ksub), codes)
    return out[:, 0, :L]


# ------------------------------------------------------------ three-plane ADC
#
# What makes the bf16-table mode of adc_scan_pallas fast is not the table's
# rounding: its one-hot is bf16 and its matmul is ONE MXU pass where an f32
# table under HIGHEST takes six. Both can be had at f32 table values. The
# matmul's M dimension is 1 (one table per (query, probe) pair), so the
# MXU's other rows idle: an f32 value is exactly hi + mid + lo of three bf16
# numbers (hi = bf16(x), mid = bf16(x - hi), lo = bf16(x - hi - mid): 3 x 8
# bits of significand cover f32's 24), the one-hot side is exact in bf16,
# every product is exact, and each plane accumulates over the m selected
# entries in f32 as HIGHEST's passes do. Three live rows of a sublane-aligned
# left operand ride through the MXU for the price of one.
#
# Timed on a v5e at the knnlm cells' geometry (m=64, capacity 1024; PERF.md,
# PR 25), ns a scanned row: the XLA one-hot the cells ran 127; this kernel
# with adc_scan_pallas's layout (candidates on sublanes, the whole
# (tile, m*ksub) one-hot in a VMEM scratch, one matmul) 22.9, where the
# bf16-table mode stands too; a subspace at a time through the scratch 14.0;
# and as here 6.0 — candidates on LANES (the codes ride transposed, so every
# compare is a full-lane (ksub, tile) block and the matmul needs no
# transpose) and each subspace's one-hot a VALUE handed straight to the MXU:
# the scratch's store and load were the bottleneck, not the compare.

# rows of the table operand: hi, mid, lo, then zeros up to bf16's native
# (16, 128) tile
_PLANE_ROWS = 16
_PLANES_TILE = 1024


def _planes_vmem_bytes(m: int, ksub: int, tile: int) -> int:
    """Scoped VMEM the three-plane kernel asks for, from its shapes: the
    plane scratch, the pair's f32 table block ((1, K) pads to 8 sublanes;
    double-buffered), the codes block widened to int32 beside its two uint8
    buffers, and one subspace's compare, one-hot and partial sums."""
    K = m * ksub
    return (_PLANE_ROWS * K * 2 + 2 * 8 * K * 4
            + m * tile * 4 + 2 * max(m, 32) * tile
            + ksub * tile * (4 + 2) + 2 * _PLANE_ROWS * tile * 4)


def planes_supported(m: int, ksub: int, L: int) -> bool:
    """Geometries the three-plane kernel takes compiled: 8-bit codes, lists
    of whole lane-aligned tiles (a padded capacity, not a ragged scan) and a
    VMEM model inside the budget at the minimum 128-row tile (m up to about
    320: the table and its planes grow with m, the one-hot does not;
    compiled for v5e up to m=256).
    Backend-blind: whether to run it at all is the caller's choice
    (models.ivf.IVFPQIndex)."""
    return (ksub == 256 and L >= 128 and L % 128 == 0
            and _planes_vmem_bytes(m, ksub, 128) <= _ONEHOT_VMEM_BUDGET)


def _bf16_planes(x):
    """(1, K) f32 -> (_PLANE_ROWS, K) bf16 whose first three rows sum to x
    exactly (in f32) and whose other rows are zero. Built as whole-array
    selects: no packed-dtype row stores for Mosaic to refuse."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    hi = x.astype(bf16).astype(f32)
    rest = x - hi
    mid = rest.astype(bf16).astype(f32)
    lo = (rest - mid).astype(bf16).astype(f32)
    row = jax.lax.broadcasted_iota(jnp.int32, (_PLANE_ROWS, x.shape[1]), 0)
    planes = jnp.where(row == 0, hi, jnp.where(row == 1, mid,
                       jnp.where(row == 2, lo, jnp.zeros_like(x))))
    return planes.astype(bf16)  # every entry is a bf16 value already


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def adc_scan_pallas_planes(lut, codes, tile: int = _PLANES_TILE,
                           interpret: bool = False):
    """Per-pair ADC scan at f32 table values in one bf16 MXU pass a subspace.

    lut: (P, m, ksub) f32, one table per (query, probe) pair; codes:
    (P, L, m) uint8 -> (P, L) f32. Same contract as adc_scan_pallas. Grid
    over (pair, candidate tile); the pair's table is split into its three
    bf16 planes when the grid reaches the pair (j == 0) and reused across
    its candidate tiles, so the planes never exist in HBM.
    """
    P, m, ksub = lut.shape
    L = codes.shape[1]
    # the largest lane-aligned tile <= ``tile`` that divides the padded list
    # and keeps the VMEM model inside the budget (128 always divides; a
    # geometry whose model does not fit even there is the caller's to refuse:
    # planes_supported)
    Lp = -(-L // 128) * 128
    tile = max(t for t in range(128, min(max(tile, 128), Lp) + 1, 128)
               if Lp % t == 0 and (t == 128 or _planes_vmem_bytes(m, ksub, t)
                                   <= _ONEHOT_VMEM_BUDGET))
    # candidates on lanes: (P, m, Lp)
    codes_t = jnp.swapaxes(jnp.pad(codes, ((0, 0), (0, Lp - L), (0, 0))), 1, 2)

    def kernel(lut_ref, codes_ref, out_ref, planes_ref):
        @pl.when(pl.program_id(1) == 0)
        def _split():
            planes_ref[:, :] = _bf16_planes(lut_ref[0])

        code = codes_ref[0].astype(jnp.int32)  # (m, tile)
        sub = jax.lax.broadcasted_iota(jnp.int32, (ksub, tile), 0)
        rows = jnp.zeros((_PLANE_ROWS, tile), jnp.float32)
        for mi in range(m):  # static unroll, one MXU pass a subspace
            onehot = (code[mi:mi + 1, :] == sub).astype(jnp.bfloat16)
            rows = rows + jax.lax.dot_general(
                planes_ref[:, mi * ksub:(mi + 1) * ksub], onehot,
                (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,  # bf16 x bf16: exact products
                preferred_element_type=jnp.float32)
        # each live row is an f32 sum of m plane entries
        out_ref[0, :, :] = (rows[0:1] + rows[1:2]) + rows[2:3]

    out = pl.pallas_call(
        kernel,
        grid=(P, Lp // tile),
        in_specs=[
            pl.BlockSpec((1, 1, m * ksub), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, m, tile), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, tile), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((P, 1, Lp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((_PLANE_ROWS, m * ksub), jnp.bfloat16)],
        interpret=interpret,
    )(lut.astype(jnp.float32).reshape(P, 1, m * ksub), codes_t)
    return out[:, 0, :L]


# ---------------------------------------------------------------- nibble ADC
#
# The one-hot kernel's measured bottleneck is the VPU one-hot build: ksub=256
# stores per code byte feeding an M=1 MXU matmul (416M codes/s on v5e —
# single-digit % of HBM bw). Decomposing each 8-bit code into two 4-bit
# nibbles (hi = c >> 4, lo = c & 15) rewrites the LUT lookup as
#
#   lut[m, c] = sum_{h, l} LUT2[m, h, l] * (hi==h) * (lo==l)
#
# i.e. a 16-wide one-hot on each side instead of 256-wide. Per candidate
# tile the kernel builds (m*16, tile) hi/lo one-hot planes (full-lane
# stores, 16x fewer bytes than the 256-wide one-hot), rides the hi side
# through 8-subspace-chunk (128, 128) dense matmuls against a per-query
# block-diagonal LUT (built once per query, reused across candidate tiles),
# and folds the lo side as an elementwise select + sublane reduce:
#
#   chunk mc (8 subspaces):  T = B[mc]^T @ OhT     (128, tile) on the MXU
#                            acc += sum_sublane(T * OlT)
#
# Exactness: Oh/Ol entries are 0/1 (exact in bf16); within a chunk each
# (candidate, m*16+lo) output of the matmul sums exactly one nonzero B
# entry, so T holds exact LUT2 values; the final f32 accumulation matches
# the one-hot path's rounding class (sum of m LUT values in f32).

_NIBBLE_TILE = 1024

# Scoped-VMEM model of the nibble kernel, read off Mosaic's own accounting
# when compiling it for v5e at m = 8..128: with an f32 LUT the matmul
# temporaries are stack-allocated once per statically unrolled chunk with no
# reuse (7.8 / 13.3 / 21.4 / 33.7 KB per candidate lane at 1 / 2 / 4 / 8
# chunks — m=64 at tile 1024 asked for 34.5 MB against the 16 MB scoped
# limit); with a bf16 LUT they are reused (~9 KB per lane whatever m is).
_NIBBLE_VMEM_BUDGET = 15 * 1024 * 1024


def _fit_nibble_tile(tile: int, nchunk: int, itemsize: int, L: int,
                     interpret: bool) -> int:
    if interpret:
        return min(tile, max(8, L))  # the interpreter has no VMEM
    per_lane = 4096 * nchunk + 6144 if itemsize == 4 else 9216
    fit = 128
    while fit * 2 * per_lane <= _NIBBLE_VMEM_BUDGET:
        fit *= 2  # pow2, so a pow2 list capacity never needs padding
    return min(tile, fit, max(128, -(-L // 128) * 128))


def nibble_supported(m: int, ksub: int) -> bool:
    return ksub == 256 and m % 8 == 0


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def adc_scan_pallas_nibble(lut, codes, tile: int = _NIBBLE_TILE,
                           interpret: bool = False):
    """Nibble-decomposed per-query-list ADC scan.

    lut: (nq, m, 256) f32/bf16; codes: (nq, L, m) uint8 -> (nq, L) f32.
    Same contract as adc_scan_pallas; requires nibble_supported(m, ksub).
    """
    nq, m, ksub = lut.shape
    assert nibble_supported(m, ksub), (m, ksub)
    L = codes.shape[1]
    nchunk = m // 8
    tile = _fit_nibble_tile(tile, nchunk, jnp.dtype(lut.dtype).itemsize, L,
                            interpret)
    Lp = -(-L // tile) * tile
    if Lp != L:
        codes = jnp.pad(codes, ((0, 0), (0, Lp - L), (0, 0)))
    lut4 = lut.reshape(nq, m, 16, 16)

    def kernel(lut_ref, codes_ref, out_ref, b_ref, oh_ref, ol_ref):
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _build_b():
            # per-query block-diagonal LUT: B[mc] is (128, 128) with eight
            # (16, 16) LUT2 blocks on the diagonal — row r = mi*16 + h,
            # col x = mi*16 + lo. Rebuilt when the query index advances;
            # reused across all candidate tiles of that query.
            lane = jax.lax.broadcasted_iota(jnp.int32, (16, 128), 1)
            for mc in range(nchunk):
                for mi in range(8):
                    blk = lut_ref[0, mc * 8 + mi]  # (16, 16)
                    band = jnp.tile(blk, (1, 8))  # (16, 128)
                    band = jnp.where((lane // 16) == mi, band,
                                     jnp.zeros_like(band))
                    b_ref[mc, mi * 16:(mi + 1) * 16, :] = band

        codes_t = codes_ref[0]  # (tile, m) u8
        acc = jnp.zeros((1, codes_t.shape[0]), jnp.float32)
        sub = jax.lax.broadcasted_iota(jnp.int32, (16, codes_t.shape[0]), 0)
        for mc in range(nchunk):
            # hi/lo one-hot planes for this chunk, candidates on lanes
            for mi in range(8):
                cm = codes_t[:, mc * 8 + mi].astype(jnp.int32)  # (tile,)
                hi = jax.lax.shift_right_logical(cm, 4)[None, :]
                lo = jax.lax.bitwise_and(cm, 15)[None, :]
                oh_ref[mi * 16:(mi + 1) * 16, :] = (sub == hi).astype(oh_ref.dtype)
                ol_ref[mi * 16:(mi + 1) * 16, :] = (sub == lo).astype(ol_ref.dtype)
            # T[x, c] = sum_r B[mc][r, x] * OhT[r, c]  — one MXU matmul
            # HIGHEST for an f32 LUT, as in _adc_matmul: on the chip DEFAULT
            # is one bf16 pass, which rounds B and put the scores 5.7e-2
            # off the golden at m=64 (the interpreter multiplies in f32,
            # so only a compiled run shows it)
            t = jax.lax.dot_general(
                b_ref[mc], oh_ref[:, :], (((0,), (0,)), ((), ())),
                precision=(jax.lax.Precision.HIGHEST
                           if b_ref.dtype == jnp.float32
                           else jax.lax.Precision.DEFAULT),
                preferred_element_type=jnp.float32,
            )  # (128, tile): exact LUT2 values (one nonzero per output)
            acc = acc + jnp.sum(t * ol_ref[:, :].astype(jnp.float32), axis=0,
                                keepdims=True)
        out_ref[0, :, :] = acc

    out = pl.pallas_call(
        kernel,
        grid=(nq, Lp // tile),
        in_specs=[
            pl.BlockSpec((1, m, 16, 16), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((1, tile, m), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, tile), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((nq, 1, Lp), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((nchunk, 128, 128), lut.dtype),
            pltpu.VMEM((128, tile), lut.dtype),
            pltpu.VMEM((128, tile), lut.dtype),
        ],
        interpret=interpret,
    )(lut4, codes)
    return out[:, 0, :L]


# runtime knob: flipped off (by models.ivf.disable_nibble, which also drops
# the compiled variants that baked the dispatch in at trace time) if the
# nibble kernel fails to compile/run on the actual backend
# (benchmarks/tpu_validate.py exercises both variants)
USE_NIBBLE = True

# every jitted program that calls adc_scan_auto inside its trace registers
# here (models/ivf.py, parallel/mesh.py at import). disable_nibble must
# clear ALL of them: a nibble abort surfaces through whichever entry point
# ran first, but the same broken kernel is baked into every cached variant
# of every consumer — clearing only the one that faulted would let the next
# entry point re-fault and wrongly demote the one-hot pallas kernel too.
NIBBLE_JIT_CONSUMERS = []

# serializes USE_NIBBLE demotion + the clear_cache sweep (disable_nibble in
# models/ivf.py) so concurrent searches demote exactly once
NIBBLE_LOCK = threading.Lock()

# post-demotion stale-executable accounting (models.ivf.pallas_guarded,
# both mutated under NIBBLE_LOCK): NIBBLE_SWEEP_EPOCH counts cache sweeps
# (the demotion sweep and every excuse sweep); a failing call that STARTED
# before the latest sweep may have raced a stale executable and is excused.
# NIBBLE_SWEPT additionally grants one excuse to a call that started after
# the last sweep but picked up an executable re-inserted by an in-flight
# pre-demotion trace (a completing trace is invisible to the epoch).
NIBBLE_SWEEP_EPOCH = 0
NIBBLE_SWEPT = False

# bounded excuse budget: each excuse sweep moves the epoch, which itself
# excuses concurrent in-flight calls — under constant concurrency a
# genuinely broken one-hot kernel could otherwise be excused forever. The
# cap covers any realistic in-flight count while guaranteeing the ladder
# converges to the XLA path within NIBBLE_EXCUSES + 2 failing searches.
NIBBLE_EXCUSES_LEFT = 8


def adc_scan_shared_auto(lut, codes, tile: int = DEFAULT_TILE):
    """Pallas on TPU, interpreter elsewhere (tests run the kernel on CPU)."""
    return adc_scan_shared_pallas(lut, codes, tile=tile, interpret=not on_tpu())


def adc_scan_auto(lut, codes, tile=None):
    """Dispatch to the nibble kernel when eligible, else the one-hot kernel.

    tile=None (the default for every in-tree caller) lets each kernel use
    its own tuned tile (_NIBBLE_TILE vs DEFAULT_TILE — they have different
    VMEM footprints); an explicit tile is forwarded to whichever kernel
    dispatches.
    """
    tile_kw = {} if tile is None else {"tile": tile}
    if USE_NIBBLE and nibble_supported(lut.shape[1], lut.shape[2]):
        return adc_scan_pallas_nibble(lut, codes, interpret=not on_tpu(), **tile_kw)
    return adc_scan_pallas(lut, codes, interpret=not on_tpu(), **tile_kw)
