"""Pallas TPU kernel for the PQ asymmetric-distance (ADC) scan.

The ADC contract (ops/pq.py): scores[p, c] = sum_m lut[p, m, codes[p, c, m]],
one table per (query, probe) pair. SURVEY §7 calls this the kernel that
decides IVF-PQ QPS. The XLA arm (``pq.adc_scan``) expresses the table lookup
as a one-hot einsum at ``HIGHEST``: six MXU passes over an f32 one-hot that
XLA materializes. ``adc_scan_pallas_planes`` keeps the f32 table values and
takes ONE bf16 pass a subspace:

  The matmul's M dimension is 1 (one table a pair), so the MXU's other rows
  idle. An f32 value is exactly hi + mid + lo of three bf16 numbers
  (hi = bf16(x), mid = bf16(x - hi), lo = bf16(x - hi - mid): 3 x 8 bits of
  significand cover f32's 24), the one-hot side is exact in bf16, every
  product is exact, and each plane accumulates over the m selected entries
  in f32 as HIGHEST's passes do. Three live rows of a sublane-aligned left
  operand ride through the MXU for the price of one.

  Candidates ride on LANES (the codes are transposed, so every compare is a
  full-lane (ksub, tile) block and the matmul needs no transpose) and each
  subspace's one-hot is a VALUE handed straight to the MXU: a VMEM scratch
  for it costs a store and a load that were the bottleneck, not the compare.

  The scan stops at the end of each pair's list (PR 35): lists are padded
  to one capacity and the probed ones are a quarter full, so the kernel is
  handed each pair's size, walks the 128-column sub-tiles that hold a row
  and leaves the rest at -inf. What that took on the chip: one scan of the
  live width in a straight line (a loop step a sub-tile drains the MXU's
  pipeline every step, 0.1 us), and the table's split in the same region as
  the pair's first scan (alone, its 0.4 us no longer hides under the MXU).

Timed on a v5e at the knnlm cells' geometry (m=64, capacity 1024; PERF.md,
PR 25 and PR 35), ns a scanned row: the XLA one-hot 127, this kernel 6.15 a
column of the capacity with every list full (5.97 before PR 35); with lists
like the cells' probed ones (2.7 of 8 sub-tiles live) 2.54 a column of the
capacity, 9.07 a live one; a pair costs 0.56 us before its first column (a
grid step and its 132 kB of DMA). The kernels it replaced (one-hot in a
scratch at HIGHEST 66, two 4-bit half-codes 35.9, this one with the one-hot
in a scratch 22.9) were deleted at PR 30; they are in git at c326e80.

``interpret=True`` runs the same kernel through the Pallas interpreter, so
CPU tests cover the kernel's own code; ``on_tpu`` is the one predicate that
picks compiled or interpreted.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# scoped VMEM the kernel may ask for (_planes_vmem_bytes models its demand;
# a v5e core's scoped limit is 16 MB)
_ONEHOT_VMEM_BUDGET = 8 * 1024 * 1024


def on_tpu() -> bool:
    """True when jax dispatches to a TPU — the ONE shared predicate deciding
    compiled-vs-interpreted kernel mode. A backend that fails to initialize
    raises here; it is never read as "not on TPU"."""
    return jax.default_backend() == "tpu"


# rows of the table operand: hi, mid, lo, then zeros up to bf16's native
# (16, 128) tile
_PLANE_ROWS = 16
_PLANES_TILE = 1024
# the grain at which a scan stops at the end of a list, in columns: one lane
# tile; and the sub-tiles a step of the kernel's loop covers (PERF.md, PR 35:
# a full list +3.1% on the parent's scan at 4, +5% at 2, +14% at 1, x5.7 at 8)
_SUB_TILE = 128
_STEP_TILES = 4


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


def _step_tiles(tile: int) -> int:
    """Sub-tiles one step of the kernel's loop over a block covers: the
    widest of ``_STEP_TILES`` and its halves that divides the block."""
    return math.gcd(tile // _SUB_TILE, _STEP_TILES)


def scanned_columns(sizes, L: int):
    """Candidate columns ``adc_scan_pallas_planes`` computes ADC sums for at
    these list sizes, an int32 scalar: every pair's size rounded up to whole
    sub-tiles. The other columns of the ``P`` padded lists it leaves at
    -inf."""
    Lp = -(-L // _SUB_TILE) * _SUB_TILE
    sizes = jnp.clip(sizes.astype(jnp.int32), 0, Lp)
    return jnp.sum(-(-sizes // _SUB_TILE) * _SUB_TILE)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def adc_scan_pallas_planes(lut, codes, sizes, tile: int = _PLANES_TILE,
                           interpret: bool = False):
    """Per-pair ADC scan at f32 table values in one bf16 MXU pass a subspace,
    as far as each pair's list goes.

    lut: (P, m, ksub) f32, one table per (query, probe) pair; codes:
    (P, L, m) uint8; sizes: (P,) int32, the rows pair p's list holds ->
    (P, L) f32. Column c < sizes[p] holds ``pq.adc_scan``'s sum; so does
    every other column of a sub-tile (``_SUB_TILE`` columns) that holds a
    row, over whatever padding codes are there, for the caller to mask; a
    sub-tile wholly at or past sizes[p] is not computed and holds -inf.

    Grid over (pair, candidate block); ``sizes`` rides in SMEM (scalar
    prefetch). A block's code tile is in VMEM whole and is walked by a loop
    of traced length in steps of ``_step_tiles`` sub-tiles, as far as the
    list goes; a step scans its live sub-tiles at once, in the one of its
    straight-line variants (one a count of live sub-tiles) that is that
    wide, so a skipped sub-tile costs neither a compare nor an MXU pass,
    and no grid step; an empty list takes no step. The pair's table is
    split into its three bf16 planes inside its first step (``scan``) and
    reused by the whole steps after it, so the planes never exist in HBM.
    """
    P, m, ksub = lut.shape
    L = codes.shape[1]
    # the largest lane-aligned block <= ``tile`` that divides the padded list
    # and keeps the VMEM model inside the budget (128 always divides; a
    # geometry whose model does not fit even there is the caller's to refuse:
    # planes_supported)
    Lp = -(-L // 128) * 128
    tile = max(t for t in range(128, min(max(tile, 128), Lp) + 1, 128)
               if Lp % t == 0 and (t == 128 or _planes_vmem_bytes(m, ksub, t)
                                   <= _ONEHOT_VMEM_BUDGET))
    sub, step = _SUB_TILE, _step_tiles(tile)
    # candidates on lanes: (P, m, Lp)
    codes_t = jnp.swapaxes(jnp.pad(codes, ((0, 0), (0, Lp - L), (0, 0))), 1, 2)

    def kernel(sizes_ref, lut_ref, codes_ref, out_ref, planes_ref):
        j = pl.program_id(1)
        size = sizes_ref[pl.program_id(0)]
        out_ref[...] = jnp.full(out_ref.shape, -jnp.inf, jnp.float32)
        # sub-tiles of this block that hold a row of the pair's list
        live = jax.lax.div(jnp.clip(size - j * tile, 0, tile) + (sub - 1), sub)

        def scan(base, width, split=True):
            """The ADC sums of columns [base, base + width) of the block,
            after the table's bf16 planes if ``split``. The split is the
            vector unit's work and hides under the MXU's as long as both
            stand in one straight-line region (a region of its own costs the
            pair its whole 0.4 us)."""
            if split:
                planes_ref[:, :] = _bf16_planes(lut_ref[0])
            cols = pl.ds(base, width)
            code = codes_ref[0, :, cols].astype(jnp.int32)  # (m, width)
            iota = jax.lax.broadcasted_iota(jnp.int32, (ksub, width), 0)
            rows = jnp.zeros((_PLANE_ROWS, width), jnp.float32)
            for mi in range(m):  # static unroll, one MXU pass a subspace
                onehot = (code[mi:mi + 1, :] == iota).astype(jnp.bfloat16)
                rows = rows + jax.lax.dot_general(
                    planes_ref[:, mi * ksub:(mi + 1) * ksub], onehot,
                    (((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.DEFAULT,  # bf16 x bf16: exact products
                    preferred_element_type=jnp.float32)
            # each live row is an f32 sum of m plane entries
            out_ref[0, :, cols] = (rows[0:1] + rows[1:2]) + rows[2:3]

        def one_step(s, carry):
            """Step ``s`` of the block: its live sub-tiles, at once. The
            pair's first step makes the planes; a later one that is whole
            reuses them, and one that is not makes them again rather than
            cost every width a second variant to trace and lower."""
            base = pl.multiple_of(s * (step * sub), step * sub)
            here = jnp.minimum(live - s * step, step)  # 1..step of them
            reuses = (here == step) & ((j > 0) | (s > 0))
            jax.lax.switch(
                jnp.where(reuses, step, here - 1),
                [functools.partial(scan, base, n * sub) for n in range(1, step + 1)]
                + [functools.partial(scan, base, step * sub, split=False)])
            return carry

        jax.lax.fori_loop(0, jax.lax.div(live + (step - 1), step), one_step, 0)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(P, Lp // tile),
            in_specs=[
                pl.BlockSpec((1, 1, m * ksub), lambda i, j, sizes_ref: (i, 0, 0)),
                pl.BlockSpec((1, m, tile), lambda i, j, sizes_ref: (i, 0, j)),
            ],
            out_specs=pl.BlockSpec((1, 1, tile), lambda i, j, sizes_ref: (i, 0, j)),
            scratch_shapes=[pltpu.VMEM((_PLANE_ROWS, m * ksub), jnp.bfloat16)],
        ),
        out_shape=jax.ShapeDtypeStruct((P, 1, Lp), jnp.float32),
        interpret=interpret,
    )(sizes.astype(jnp.int32), lut.astype(jnp.float32).reshape(P, 1, m * ksub),
      codes_t)
    return out[:, 0, :L]
