#!/usr/bin/env python3
"""Dissect the headline IVF-fp16 serving latency on the live backend.

Answers one question: where does the ~0.35 s / 512-query batch go at the
bench.py operating point (n=500k, d=128, nlist=1024, nprobe=1)?
Suspects, measured independently:

  dispatch   — a trivial jitted add on a (8,) array, round-tripped to host.
               The per-launch floor every search pays whatever its size
               (unmeasured on a chip the process holds — ROADMAP S3).
  transfer   — device_put of one query block + fetch of a (block, k) result.
  search     — the fused _ivf_flat_search call itself at block sizes
               256 / 512 / 1024 (lower bound per-call; if per-call time is
               flat in block size, dispatch dominates and bigger blocks are
               near-free QPS).

A/B flags for the stored-norms + pallas flat-scan work (this PR):

  --norms {stored,recompute}   gather the add-time (nlist, cap) fp32 norm
               sidecar vs recomputing ||x||^2 from the gathered block per
               query (the pre-change behavior). Bit-exact either way.
  --kernel {xla,pallas}        the XLA gather+einsum scan vs the fused
               VMEM pallas kernel (ops/flat_pallas.py). On a non-TPU
               backend 'pallas' runs the interpreter — correct but slow;
               use it for numerics, not timing, off-chip.

Run both arms of either flag on the same machine for the A/B line in
benchmarks/RESULTS.md (BENCH_SMALL=1 for the CPU-sized corpus).

Prints one JSON line per measurement. Safe to run CPU-only (numbers are then
about the CPU path, labeled by backend).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.chdir(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timeit(fn, reps=20, warm=3):
    for _ in range(warm):
        fn()
    t0 = time.time()
    for _ in range(reps):
        fn()
    return (time.time() - t0) / reps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--norms", choices=("stored", "recompute"), default="stored")
    ap.add_argument("--kernel", choices=("xla", "pallas"), default="xla")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from distributed_faiss_tpu.models.ivf import IVFFlatIndex, _ivf_flat_search
    from distributed_faiss_tpu.utils import envutil

    envutil.place_compile_cache()
    backend = jax.devices()[0].platform
    arm = f"{args.norms}/{args.kernel}"
    rng = np.random.default_rng(0)
    small = os.environ.get("BENCH_SMALL") == "1"
    n = 50_000 if small else 500_000
    d, nlist, k, nprobe = 128, 256 if small else 1024, 10, 1

    centers = rng.standard_normal((nlist, d)).astype(np.float32) * 4.0
    assign = rng.integers(0, nlist, n)
    x = (centers[assign] + rng.standard_normal((n, d))).astype(np.float32)

    idx = IVFFlatIndex(d, nlist, "l2", codec="f16", kmeans_iters=4,
                       use_pallas=args.kernel == "pallas")
    idx.train(x[: min(n, 100_000)])
    idx.add(x)
    idx.set_nprobe(nprobe)
    idx.use_stored_norms = args.norms == "stored"

    # 1. dispatch floor
    tiny = jnp.zeros((8,), jnp.float32)
    f_tiny = jax.jit(lambda a: a + 1.0)
    t = timeit(lambda: np.asarray(f_tiny(tiny)))
    print(json.dumps({"case": "dispatch_floor", "backend": backend,
                      "ms": round(t * 1e3, 2)}))

    # 2. transfer: host->device 256x128 fp32 + device->host (256,k)
    qb = rng.standard_normal((256, d)).astype(np.float32)
    dev_res = jnp.zeros((256, k), jnp.float32)
    t = timeit(lambda: (jax.device_put(qb).block_until_ready(),
                        np.asarray(dev_res)))
    print(json.dumps({"case": "transfer_256q", "backend": backend,
                      "ms": round(t * 1e3, 2)}))

    # 3. fused search call at growing block sizes, on the selected A/B arm
    norms = idx._scan_norms()
    for block in (256, 512, 1024):
        q = (centers[rng.integers(0, nlist, block)]
             + rng.standard_normal((block, d))).astype(np.float32)
        qj = jnp.asarray(q)
        tile, group, sub = idx._scan_tiling(block, nprobe)

        def call():
            v, i, _ = _ivf_flat_search(
                idx.centroids, idx.lists.data, idx.lists.ids, idx.lists.sizes,
                qj, k, nprobe, 1, "l2", "f16", list_norms=norms,
                use_pallas=idx.use_pallas, tile=tile, group=group, sub=sub)
            np.asarray(v); np.asarray(i)

        t = timeit(call, reps=10)
        print(json.dumps({"case": f"search_block{block}", "backend": backend,
                          "arm": arm, "ms": round(t * 1e3, 2),
                          "qps_equiv": round(block / t, 1)}))

    # 4. end-to-end idx.search at the bench batch size
    q = (centers[rng.integers(0, nlist, 512)]
         + rng.standard_normal((512, d))).astype(np.float32)
    t = timeit(lambda: idx.search(q, k), reps=10)
    print(json.dumps({"case": "e2e_512q", "backend": backend, "arm": arm,
                      "ms": round(t * 1e3, 2), "qps": round(512 / t, 1)}))


if __name__ == "__main__":
    main()
