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

Timed on a v5e at the knnlm cells' geometry (m=64, capacity 1024; PERF.md,
PR 25), ns a scanned row: the XLA one-hot 127, this kernel 5.99. The kernels
it replaced (one-hot in a scratch at HIGHEST 66, two 4-bit half-codes 35.9,
this one with the one-hot in a scratch 22.9) were deleted at PR 30; they are
in git at c326e80.

``interpret=True`` runs the same kernel through the Pallas interpreter, so
CPU tests cover the kernel's own code; ``on_tpu`` is the one predicate that
picks compiled or interpreted.
"""

import functools

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
    (P, L, m) uint8 -> (P, L) f32, the contract of ``pq.adc_scan``. Grid
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
