"""Every Pallas kernel at the geometries chip_smoke.py makes it emit, and
the XLA scan program of ``ivfsq-batch`` (``listmajor_programs``), as
abstract signatures — shared by tests/test_pallas_tpu_lowering.py, which
lowers each for the ``tpu`` platform in-process, and by this file run as a
script, which compiles each for a v5e with no chip attached (libtpu's
compile-only topology) and prints one JSON line per case.

Lowering catches what Pallas refuses (casts, block shapes); only the
compile shows what Mosaic and XLA:TPU refuse — scoped VMEM, SMEM, vector
load types — which is where every kernel repair of the chip bring-up was.
"""

import json
import re
import sys

import jax
import jax.numpy as jnp

from distributed_faiss_tpu.ops import adc_pallas, flat_pallas

_FLAT_DTYPES = {"f32": "float32", "f16": "float16", "sq8": "uint8"}


def _flat(codec, metric, d, nq, g, cap, nlist=1024, scan_bf16=False):
    def sig(sds):
        prm = sds((d,), "float32") if codec == "sq8" else None
        args = (sds((nq, d), "float32"), sds((nlist, cap, d), _FLAT_DTYPES[codec]),
                sds((nlist, cap), "int32"), sds((nq, g), "int32"),
                sds((nq, g), "int32"),
                sds((nlist, cap), "float32") if metric == "l2" else None,
                prm, prm)
        return args, dict(metric=metric, codec=codec, scan_bf16=scan_bf16,
                          interpret=False)

    name = f"flat {codec} {metric} d={d} nq={nq} g={g} cap={cap}"
    return name + (" bf16" if scan_bf16 else ""), flat_pallas.flat_list_scan_pallas, sig


def _adc(m, pairs, L):
    def sig(sds):
        return ((sds((pairs, m, 256), "float32"), sds((pairs, L, m), "uint8"),
                 sds((pairs,), "int32")), dict(interpret=False))

    return (f"adc planes m={m} float32 nq={pairs} L={L}",
            adc_pallas.adc_scan_pallas_planes, sig)


def cases():
    """[(name, jitted kernel entry, sig(sds) -> (args, kwargs))]."""
    out = []
    # ivfsq / IVF1024,SQ8 at dim 512 (cap 256-512, one probe per group), the
    # f32 codec beside them, and the bf16 scan mode
    for codec in ("f32", "f16", "sq8"):
        for metric in ("l2", "dot"):
            out.append(_flat(codec, metric, 512, 256, 1, 512))
    out.append(_flat("f16", "l2", 512, 256, 1, 512, scan_bf16=True))
    out.append(_flat("f16", "l2", 512, 8, 1, 256))  # a single-query bucket
    # the widest scalar prefetch the block picker can ask for: 1024 queries
    # x 8 probes (ivf_simple width) — two of these must fit SMEM
    out.append(_flat("f32", "l2", 128, 1024, 8, 1024))
    # the three-plane ADC kernel (with each pair's list size since PR 35) at
    # the benchmark cells' shapes: one table a (query, probe) pair of an
    # online window (4 x 32), a 64-row and a 256-row window, lists of
    # capacity 1024; and its smallest geometry
    for pairs in (128, 2048, 8192):
        out.append(_adc(64, pairs, 1024))
    out.append(_adc(8, 64, 128))
    out.append(_adc(128, 16, 1024))  # twice the cells' table
    # and along the edges of planes_supported: the table's width at the
    # cells' capacity (256 is the widest compiled), the capacity at the
    # cells' table (4096 is ivfsq's, 512 the lists the older kernels were
    # compiled at), the narrowest table at a short and a long list, one
    # sublane tile of pairs (a single-query bucket), and wide with long
    for m in (16, 32, 256):
        out.append(_adc(m, 64, 1024))
    for L in (128, 256, 512, 2048, 4096):
        out.append(_adc(64, 64, L))
    for L in (1024, 4096):
        out.append(_adc(8, 64, L))
    out.append(_adc(64, 8, 1024))
    out.append(_adc(128, 16, 4096))
    # the widest scalar prefetch: a pair's list size rides in SMEM (PR 35),
    # and the callers' group budget (models/ivf._GROUP_BYTE_BUDGET over the
    # least a pair weighs, capacity 128 at m 1) keeps a call under 61,680
    # pairs; 1 MB of SMEM takes 131,072
    out.append(_adc(8, 65536, 128))
    return out


def listmajor_programs():
    """``ivfsq-batch``'s scan program (PR 31: the XLA arm, list-major, its
    loop's trip count traced; PR 43: a tile is a live sub-block of a probed
    list) at the cell's geometry — d 512, capacity 4096, 1024 float16
    lists, nprobe 64, k 10 — for the 128- and the 256-row bucket, under the
    index's own tiling: [(name, fn, sig)]."""
    from distributed_faiss_tpu.models import ivf

    d, cap, nlist, nprobe = 512, 4096, 1024, 64

    def program(rows):
        tile, group, sub = ivf.listmajor_tiling(rows, nprobe, nlist, cap, d, 2)

        def sig(sds):
            args = (sds((nlist, d), "float32"), sds((nlist, cap, d), "float16"),
                    sds((nlist, cap), "int32"), sds((nlist,), "int32"),
                    sds((rows, d), "float32"))
            return args, dict(k=10, nprobe=nprobe, g=1, metric="l2", codec="f16",
                              list_norms=sds((nlist, cap), "float32"),
                              tile=tile, group=group, sub=sub,
                              nvalid=sds((), "int32"))

        return (f"ivf flat list-major rows={rows} T={tile} G={group} sub={sub}",
                ivf._ivf_flat_search, sig)

    return [program(128), program(256)]


_ITEMSIZE = {"f16": 2, "bf16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
             "pred": 1, "s64": 8, "f64": 8}


def largest_gather_slice_bytes(hlo_text):
    """The largest slice any ``gather`` of a compiled program takes, in
    bytes (a gather's result has its operand's element type)."""
    worst = 0
    for dtype, sizes in re.findall(
            r"= (\w+)\[[\d,]*\]\S* gather\(.*?slice_sizes=\{([\d,]+)\}", hlo_text):
        n = 1
        for dim in sizes.split(","):
            n *= int(dim)
        worst = max(worst, n * _ITEMSIZE[dtype])
    return worst


def lower_for_tpu(fn, sig, sds):
    args, kwargs = sig(sds)
    return fn.trace(*args, **kwargs).lower(lowering_platforms=("tpu",))


def exact_scan_sort_widths(sds):
    """``flat768-batch``'s scan program (a 64-row window over 2^21 x 768
    float32 rows, k = 10) compiled for a v5e: how many elements a row each
    ``sort`` left in it sorts. On this chip ``lax.top_k`` over a wide row
    is such a sort; the prefilter leaves the 512 segment maxima the widest."""
    from distributed_faiss_tpu.ops import distance

    text = distance._knn_scan.trace(
        sds((64, 768), "float32"), sds((2 ** 21, 768), "float32"),
        sds((), "int32"), k=10, metric="l2", chunk=distance.SCAN_CHUNK,
    ).lower(lowering_platforms=("tpu",)).compile().as_text()
    return [int(dims.split(",")[int(axis)]) for dims, axis in re.findall(
        r"= \(?\w+\[([\d,]+)\][^=]*? sort\(.*?dimensions=\{(\d+)\}", text)]


def main():
    """Compile every case for a v5e from its topology description alone."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here: the caller skips
        print(json.dumps({"unavailable": f"{type(e).__name__}: {e}"[:300]}))
        return 0
    sharding = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)

    for name, fn, sig in cases():
        try:
            lower_for_tpu(fn, sig, sds).compile()
            print(json.dumps({"case": name, "ok": True}), flush=True)
        except Exception as e:
            print(json.dumps({"case": name, "ok": False,
                              "error": f"{type(e).__name__}: {e}"[:600]}),
                  flush=True)
    for name, fn, sig in listmajor_programs():
        try:
            compiled = lower_for_tpu(fn, sig, sds).compile()
            print(json.dumps({
                "program": name, "ok": True,
                "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
                "largest_gather_slice_bytes": largest_gather_slice_bytes(
                    compiled.as_text())}), flush=True)
        except Exception as e:
            print(json.dumps({"program": name, "ok": False,
                              "error": f"{type(e).__name__}: {e}"[:600]}),
                  flush=True)
    print(json.dumps({"exact_scan_sort_widths": exact_scan_sort_widths(sds)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
