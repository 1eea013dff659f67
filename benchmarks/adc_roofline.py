"""ADC kernel roofline: measured throughput vs v5e peaks, per variant.

VERDICT r2 missing #3: nothing quantified device utilization for the kernel
SURVEY §7 says "decides IVF-PQ QPS". This script times the two ADC
implementations (the XLA one-hot einsum and the Pallas three-plane one-hot;
the kernels that lost to it were deleted at PR 30, their timings are in
PERF.md, PR 25) at the flagship geometry and at the benchmark cells' own
(``knnlm``: one table a (query, probe) pair, 128 / 2048 / 8192 pairs of
capacity-1024 lists) and prints, per variant:

  - codes/s (candidate rows x m scored per second)
  - achieved HBM bytes/s for the true input traffic (codes + lut + out)
  - the one-hot traffic the variant generates (f32 through HBM for XLA,
    bf16 values handed to the MXU in the kernel)
  - % of v5e HBM peak (819 GB/s) for the true traffic

Runs compiled on a TPU v5e only — the one chip its peaks describe — and
exits non-zero anywhere else; it never interprets a kernel. One JSON line
per row, each naming the device.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Published peaks of one v5e chip (Google Cloud documentation, "TPU v5e"),
# keyed by the device_kind jax reports for it. A device that is not in the
# table is an error, not a default.
PEAKS = {"TPU v5 lite": {"hbm_gbs": 819.0}}


def bench(fn, *args, warmup=2, iters=8):
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main():
    import jax

    from distributed_faiss_tpu.utils import envutil

    envutil.place_compile_cache()
    device_kind = jax.devices()[0].device_kind
    if device_kind not in PEAKS:
        sys.stderr.write(
            f"adc_roofline: device_kind {device_kind!r} has no peaks here "
            f"(known: {sorted(PEAKS)}); nothing to measure against\n")
        return 1
    peaks = PEAKS[device_kind]
    # flagship knnlm-like geometry (per-(query,probe) lists, m=64), then the
    # cells' lists (capacity 1024) at the pair counts ISSUE 25 named: a loop
    # step of the served scan holds 128 to 512 pairs, and the kernels' rates
    # a row are flat in the pair count (PERF.md, PR 25)
    for nq, L in ((256, 4096), (128, 1024), (2048, 1024), (8192, 1024)):
        one_geometry(nq, 64, 256, L, device_kind, peaks)
    return 0


def one_geometry(nq, m, ksub, L, device_kind, peaks):
    import jax.numpy as jnp

    from distributed_faiss_tpu.ops import adc_pallas, pq

    rng = np.random.default_rng(0)
    lut = jnp.asarray(rng.standard_normal((nq, m, ksub)).astype(np.float32))
    codes = jnp.asarray(rng.integers(0, 256, (nq, L, m)).astype(np.uint8))
    full = jnp.full((nq,), L, jnp.int32)  # every list at its capacity

    rows = nq * L
    code_bytes = rows * m  # true codes traffic
    lut_bytes_f32 = nq * m * ksub * 4
    out_bytes = rows * 4

    variants = [
        ("xla-onehot", lambda: pq.adc_scan(lut, codes)),
        # f32 table values as three bf16 planes, bf16 one-hot, one MXU pass
        ("pallas-planes-f32",
         lambda: adc_pallas.adc_scan_pallas_planes(lut, codes, full)),
    ]

    for name, fn in variants:
        try:
            dt = bench(fn)
        except Exception as e:  # noqa: BLE001 - report and continue
            print(json.dumps({"variant": name, "device_kind": device_kind,
                              "error": repr(e)[:200]}), flush=True)
            continue
        true_bytes = code_bytes + lut_bytes_f32 + out_bytes
        onehot_bytes = 2 if "planes" in name else 4
        row = {
            "variant": name,
            "device_kind": device_kind,
            "nq": nq, "m": m, "L": L,
            "ms": round(dt * 1e3, 3),
            "codes_per_s": round(rows * m / dt / 1e6, 1),  # M codes/s
            "rows_per_s": round(rows / dt / 1e6, 2),  # M rows/s
            "true_gbs": round(true_bytes / dt / 1e9, 2),
            "hbm_pct": round(100 * true_bytes / dt / 1e9 / peaks["hbm_gbs"], 2),
            "onehot_store_gbs": round(
                rows * m * ksub * onehot_bytes / dt / 1e9, 1),
        }
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    sys.exit(main())
