"""Headline benchmark: IVF search QPS at recall@10 >= 0.95 vs CPU exact scan.

Metric (BASELINE.md): QPS at recall@10 >= 0.95 on a SIFT-scale corpus.
The baseline is measured in-process: a numpy CPU exact brute-force scan of
the same corpus answering the same queries (the reference's compute substrate
is CPU FAISS; a BLAS matmul scan is the same arithmetic its IndexFlat runs,
and is the floor any IVF config must beat). vs_baseline = tpu_qps / cpu_qps.

Protocol:
1. synthetic clustered corpus (gaussian mixture — ANN-meaningful structure),
   N x 128 fp32; ground truth = exact TPU flat scan (fp32, HIGHEST).
2. build IVF-Flat fp16 (the ivfsq family config) on the TPU; sweep nprobe
   doubling until recall@10 >= 0.95 on held-out queries.
3. measure steady-state QPS at that nprobe (batched, device-resident index,
   results fetched to host every batch — the serving pattern).

Prints ONE json line naming the platform it ran on, from one process that
holds the device. It exits non-zero when that platform is not ``tpu``:
there is no fallback. ``BENCH_SMALL=1`` is the CPU smoke size, for checking
that the script still runs; its number is not a speed. ROADMAP S1 replaces
this file with a served-path benchmark.
"""

import json
import os
import sys
import time

import numpy as np


def make_corpus(rng, n, d, centers):
    """Draw n points from the given gaussian-mixture centers (corpus and
    queries must share centers — OOD queries make the nprobe sweep
    unrealistically pessimistic)."""
    assign = rng.integers(0, centers.shape[0], n)
    x = centers[assign] + rng.standard_normal((n, d)).astype(np.float32)
    return x.astype(np.float32)


def cpu_exact_qps(x, q, k, repeats=3):
    """numpy/BLAS brute-force top-k (the CPU-substrate floor)."""
    xn = (x * x).sum(1)
    t0 = time.time()
    for _ in range(repeats):
        d2 = xn[None, :] - 2.0 * (q @ x.T)  # ||q||^2 is rank-invariant
        part = np.argpartition(d2, k - 1, axis=1)[:, :k]
        pd = np.take_along_axis(d2, part, axis=1)
        order = np.argsort(pd, axis=1)
        np.take_along_axis(part, order, axis=1)
    dt = (time.time() - t0) / repeats
    return q.shape[0] / dt


def main():
    import jax

    from distributed_faiss_tpu.utils import envutil

    envutil.place_compile_cache()
    small = os.environ.get("BENCH_SMALL") == "1"
    platform = jax.devices()[0].platform
    if platform != "tpu" and not small:
        sys.stderr.write(
            f"bench: platform is {platform!r}, not 'tpu' — refusing to "
            "measure (BENCH_SMALL=1 runs the CPU smoke size)\n")
        return 1
    n = 50_000 if small else 500_000
    d = 128
    k = 10
    n_clusters = 256 if small else 1024
    nq_eval, nq_bench = 200, 512
    rng = np.random.default_rng(0)

    centers = rng.standard_normal((n_clusters, d)).astype(np.float32) * 4.0
    x = make_corpus(rng, n, d, centers)
    q = make_corpus(rng, nq_eval + nq_bench, d, centers)
    q_eval, q_bench = q[:nq_eval], q[nq_eval:]

    from distributed_faiss_tpu.models.flat import FlatIndex
    from distributed_faiss_tpu.models.ivf import IVFFlatIndex

    # ground truth: exact fp32 scan on device
    exact = FlatIndex(d, "l2")
    exact.add(x)
    _, gt_eval = exact.search(q_eval, k)

    # flagship serving index: IVF fp16 lists
    nlist = n_clusters
    idx = IVFFlatIndex(d, nlist, "l2", codec="f16", kmeans_iters=8)
    t0 = time.time()
    idx.train(x[rng.permutation(n)[: min(n, 100_000)]])
    idx.add(x)
    build_s = time.time() - t0

    def recall_at(nprobe):
        idx.set_nprobe(nprobe)
        _, ids = idx.search(q_eval, k)
        return np.mean([
            len(set(ids[i]) & set(gt_eval[i])) / k for i in range(nq_eval)
        ])

    nprobe, rec = 1, 0.0
    while nprobe <= nlist:
        rec = recall_at(nprobe)
        if rec >= 0.95:
            break
        nprobe *= 2
    nprobe = min(nprobe, nlist)

    # steady-state QPS at the recall-qualifying nprobe
    idx.set_nprobe(nprobe)
    idx.search(q_bench[:256], k)  # warm the jit cache
    t0 = time.time()
    reps = 2 if small else 4
    for _ in range(reps):
        idx.search(q_bench, k)
    tpu_qps = (reps * q_bench.shape[0]) / (time.time() - t0)

    cpu_qps = cpu_exact_qps(x, q_bench[:64], k)

    result = format_result(
        backend=platform, rec=rec, n=n, d=d, nprobe=nprobe,
        build_s=build_s, tpu_qps=tpu_qps, cpu_qps=cpu_qps,
    )
    print(json.dumps(result))
    return 0


def format_result(*, backend, rec, n, d, nprobe, build_s, tpu_qps, cpu_qps):
    """Assemble the one-line JSON artifact; ``backend`` is the platform jax
    reported, so a reader can never take a CPU smoke for a chip run."""
    return {
        "metric": (
            f"IVF-fp16 search QPS @ recall@10={rec:.3f} "
            f"(backend={backend}, n={n}, d={d}, nprobe={nprobe}; build {build_s:.0f}s)"
        ),
        "value": round(tpu_qps, 1),
        "unit": "qps",
        "vs_baseline": round(tpu_qps / cpu_qps, 2),
    }


if __name__ == "__main__":
    sys.exit(main())
