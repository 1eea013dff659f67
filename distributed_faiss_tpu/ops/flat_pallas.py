"""Pallas TPU kernel for the IVF-Flat/SQ8 list scan (the headline bench path).

The XLA probe scan (models/ivf.py:_ivf_flat_search) gathers each probed
list as a fp32 ``(nq, g, cap, d)`` block in HBM — 4 transient bytes/elem
for fp16 storage — and, for l2, runs a second full elementwise pass to
recompute ``||x||^2`` per row. This kernel keeps the whole pipeline in
VMEM: per ``(query, probe, cap-tile)`` grid step the probed list's tile is
DMA'd straight from the ``(nlist, cap, d)`` store (a scalar-prefetched
index map does the gather — the fp32 block never exists in HBM), decoded
(fp16 cast / sq8 dequant) in VMEM, dotted against the query on the MXU
with fp32 accumulation, combined with the stored row norms (ops layer of
the stored-norms tentpole; see PaddedLists sidecar in models/ivf.py), and
the size/ids validity mask is applied before the masked ``(nq, g, cap)``
score block is written out.

``scan_bf16=True`` runs the MXU dot in native bf16 (halving the kernel's
VMEM compute traffic, the measured bottleneck class); models gate it behind
``refine_k_factor > 0`` so the shortlist is always rescored exactly.

``interpret=True`` (automatic off-TPU) runs the same kernel through the
Pallas interpreter so CPU tests cover the exact kernel code path.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_faiss_tpu.ops.adc_pallas import on_tpu

NEG_INF = -jnp.inf

DEFAULT_TILE = 1024

# VMEM budget for the decoded (tile, d) fp32 block — the step's dominant
# buffer (the (1, d) query, (1, tile) ids/norms and (1, tile) output are
# noise next to it). Half the ~16 MB/core so double-buffered pipelining of
# the next tile's DMA always fits.
_BLOCK_VMEM_BUDGET = 4 * 1024 * 1024


def _fit_tile(tile: int, d: int, cap: int, interpret: bool) -> int:
    """Largest power-of-two tile that (a) divides cap — list capacities are
    power-of-two grown (models/base.py PaddedLists), so a pow2 tile always
    divides them — and (b) keeps the decoded fp32 block inside the VMEM
    budget. Interpret mode has no VMEM; only the divisibility rule holds."""
    if not interpret:
        tile = min(tile, max(128, _BLOCK_VMEM_BUDGET // (d * 4)))
    t = 1
    while t * 2 <= min(tile, cap):
        t *= 2
    while cap % t:  # non-pow2 cap (out-of-tree callers): shrink to a divisor
        t //= 2
    return max(t, 1)


def _decode_f16_bits(h):
    """int32 holding IEEE-half bit patterns (low 16 bits) -> exact float32.

    Mosaic has no vector load for IEEE float16 on v5e ("Invalid vector type
    for load"), so fp16 lists ride into the kernel bitcast to uint16 and are
    decoded with integer ops: sign/exponent/mantissa re-packed into the f32
    layout (exponent rebias 15 -> 127, all-ones exponent kept for inf/nan).
    Zeros and subnormals are built arithmetically as ``mant * 2**-24`` — the
    TPU flushes f32 denormals, so the usual shift-then-scale trick cannot
    express them. Bit-identical to XLA's f16 -> f32 convert."""
    sign = jnp.left_shift(h & 0x8000, 16)
    exp = jnp.right_shift(h, 10) & 0x1F
    mant = h & 0x3FF
    exp32 = jnp.where(exp == 31, 255, exp + 112)
    bits = sign | jnp.left_shift(exp32, 23) | jnp.left_shift(mant, 13)
    val = jax.lax.bitcast_convert_type(bits, jnp.float32)
    sub = mant.astype(jnp.float32) * (2.0 ** -24)
    return jnp.where(exp == 0, jnp.where(sign != 0, -sub, sub), val)


def _flat_kernel(metric: str, codec: str, scan_bf16: bool, stored_norms: bool,
                 tile: int, g: int, *refs):
    """Score one (query, probe, cap-tile) grid step; see module docstring."""
    li_ref, sz_ref = refs[0], refs[1]
    q_ref, data_ref, ids_ref = refs[2], refs[3], refs[4]
    pos_r = 5
    if metric == "l2" and stored_norms:
        norm_ref = refs[pos_r]
        pos_r += 1
    if codec == "sq8":
        vmin_ref, span_ref = refs[pos_r], refs[pos_r + 1]
        pos_r += 2
    out_ref = refs[pos_r]

    i, j, kt = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    qf = q_ref[0].astype(jnp.float32)  # (1, d)
    x = data_ref[0]  # (tile, d) storage dtype
    if codec == "sq8":
        # uint8 -> f32 has no Mosaic lowering; widen through int32 (as the
        # ADC kernels do for their codes)
        x = vmin_ref[:, :] + x.astype(jnp.int32).astype(jnp.float32) \
            * (span_ref[:, :] / 255.0)
    elif codec == "f16":
        x = _decode_f16_bits(x.astype(jnp.int32))  # uint16 bit patterns
    else:
        x = x.astype(jnp.float32)
    if scan_bf16:
        # native bf16 MXU pass, fp32 accumulation (HIGHEST's multi-pass
        # trick only exists for f32 operands)
        ip = jax.lax.dot_general(
            qf.astype(jnp.bfloat16), x.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32,
        )  # (1, tile)
    else:
        ip = jax.lax.dot_general(
            qf, x, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    if metric == "dot":
        s = ip
    else:
        qn = jnp.sum(qf * qf, axis=1, keepdims=True)  # (1, 1)
        if stored_norms:
            bn = norm_ref[0]  # (1, tile) exact fp32 add-time norms
        else:
            bn = jnp.sum(x * x, axis=1)[None, :]  # in-VMEM recompute
        s = -(qn - 2.0 * ip + bn)
    ids = ids_ref[0]  # (1, tile)
    pos = kt * tile + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    ok = (pos < sz_ref[i * g + j]) & (ids >= 0)
    out_ref[0, 0] = jnp.where(ok, s, NEG_INF)


@functools.partial(jax.jit, static_argnames=("metric", "codec", "scan_bf16",
                                             "tile", "interpret"))
def flat_list_scan_pallas(q, list_data, list_ids, li, sizes_g,
                          list_norms=None, vmin=None, span=None, *,
                          metric: str, codec: str = "f16",
                          scan_bf16: bool = False, tile: int = DEFAULT_TILE,
                          interpret: bool = False):
    """Fused masked scan of one probe group.

    q: (nq, d) fp32; list_data: (nlist, cap, d) f32/f16 (codec raw) or uint8
    (codec 'sq8', with per-dim vmin/span); list_ids: (nlist, cap) int32;
    li: (nq, g) int32 probed list ids; sizes_g: (nq, g) int32 fill counts of
    those lists; list_norms: (nlist, cap) fp32 stored ``||x||^2`` of the
    DECODED rows (None -> recomputed in VMEM, the A/B reference mode).
    Returns (nq, g, cap) fp32 scores, invalid slots already NEG_INF.
    """
    nq, d = q.shape
    cap = list_data.shape[1]
    g = li.shape[1]
    stored = list_norms is not None
    tile = _fit_tile(tile, d, cap, interpret)

    # singleton ride-along dims: compiled Mosaic wants the last two block
    # dims 8/128-divisible or equal to the full array dims — a (1, tile)
    # block of an (nlist, cap) array violates that, a (1, 1, tile) block of
    # (nlist, 1, cap) satisfies it (same trick as adc_pallas' LUT operand).
    def row_spec():
        return pl.BlockSpec((1, 1, tile),
                            lambda i, j, kt, li_ref, sz_ref: (li_ref[i * g + j], 0, kt))

    if codec != "sq8":
        # raw codecs decode by storage dtype; fp16 rides as its uint16 bit
        # patterns (_decode_f16_bits). XLA:TPU materialises this reinterpret
        # as a copy of the whole store per launch (its memory analysis: 541
        # MB at 1024x512x512) — what the codec costs until the lists are
        # stored as uint16 or the kernel drops it (ROADMAP S4).
        codec = "f16" if list_data.dtype == jnp.float16 else "f32"
        if codec == "f16":
            list_data = jax.lax.bitcast_convert_type(list_data, jnp.uint16)

    in_specs = [
        pl.BlockSpec((1, 1, d), lambda i, j, kt, li_ref, sz_ref: (i, 0, 0)),
        pl.BlockSpec((1, tile, d),
                     lambda i, j, kt, li_ref, sz_ref: (li_ref[i * g + j], kt, 0)),
        row_spec(),
    ]
    operands = [q.reshape(nq, 1, d), list_data,
                list_ids.reshape(-1, 1, cap)]
    if metric == "l2" and stored:
        in_specs.append(row_spec())
        operands.append(list_norms.reshape(-1, 1, cap))
    if codec == "sq8":
        const_spec = pl.BlockSpec((1, d), lambda i, j, kt, li_ref, sz_ref: (0, 0))
        in_specs += [const_spec, const_spec]
        operands += [vmin.reshape(1, d).astype(jnp.float32),
                     span.reshape(1, d).astype(jnp.float32)]

    out = pl.pallas_call(
        functools.partial(_flat_kernel, metric, codec, scan_bf16, stored, tile, g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nq, g, cap // tile),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, 1, 1, tile),
                lambda i, j, kt, li_ref, sz_ref: (i, j, 0, kt)),
        ),
        out_shape=jax.ShapeDtypeStruct((nq, g, 1, cap), jnp.float32),
        interpret=interpret,
    # the scalar-prefetched operands ride flattened: a 2-D (nq, g) int32
    # array pads its minor dim to the 128-lane width in SMEM — 2 x 512 KB at
    # nq=1024 against the 1 MB there is
    )(li.astype(jnp.int32).reshape(nq * g),
      sizes_g.astype(jnp.int32).reshape(nq * g), *operands)
    return out[:, :, 0, :]


def flat_list_scan_auto(q, list_data, list_ids, li, sizes_g, list_norms=None,
                        vmin=None, span=None, *, metric: str,
                        codec: str = "f16", scan_bf16: bool = False,
                        tile: int = DEFAULT_TILE):
    """Compiled on TPU, interpreter elsewhere (CPU tests run the kernel)."""
    return flat_list_scan_pallas(
        q, list_data, list_ids, li, sizes_g, list_norms, vmin, span,
        metric=metric, codec=codec, scan_bf16=scan_bf16, tile=tile,
        interpret=not on_tpu(),
    )
