"""Operating curves for the flagship configs (VERDICT r2 next #6/#8).

- knnlm: nprobe x refine_k_factor recall/QPS grid at the full-size config
  (the refine store is built once; refine_k_factor is a search-time knob).
- ivfsq: nprobe recall/QPS curve post top-k/block fixes.

One JSON line per grid point; the chosen operating point is the cheapest
point clearing recall@10 >= 0.95 (BASELINE.md protocol). The single-core
numpy IVF floor (cpu_ivf_qps) is printed for the chosen points so every
headline row carries its honest baseline.

Run on the real chip: `python benchmarks/operating_curves.py [--small]`.
"""

import argparse
import json
import sys
import time

import numpy as np

from benchmarks.baseline_configs import (
    cpu_ivf_qps, make_lowrank_corpus, measure_qps, recall_at_k)


def note(msg):
    print(f"[curves] {time.strftime('%H:%M:%S')} {msg}", file=sys.stderr, flush=True)


def grid_rows(name, index, x, q, gt, k, nprobes, refine_factors=(None,),
              recall_only=False):
    rows = []
    for np_ in nprobes:
        index.set_nprobe(np_)
        for rf in refine_factors:
            if rf is not None:
                index.refine_k_factor = rf
            _, ids = index.search(q[:128], k)
            rec = recall_at_k(ids, gt, k)
            row = {"config": name, "nprobe": np_, "recall@10": round(rec, 4)}
            if not recall_only:
                qps = measure_qps(lambda qq, kk: index.search(qq, kk), q, k)
                row["qps"] = round(qps, 1)
            if rf is not None:
                row["refine_k_factor"] = rf
            rows.append(row)
            print(json.dumps(row), flush=True)
    return rows


def pick_operating_point(rows, bar=0.95):
    ok = [r for r in rows if r["recall@10"] >= bar]
    if not ok:
        return None
    if "qps" in ok[0]:
        return max(ok, key=lambda r: r["qps"])
    # recall-only mode (QPS unmeasurable on this backend at full size):
    # cheapest point clearing the bar — lowest nprobe, then lowest refine
    return min(ok, key=lambda r: (r["nprobe"], r.get("refine_k_factor", 0)))


def knnlm_curve(rng, size, recall_only=False):
    from distributed_faiss_tpu.models.flat import FlatIndex
    from distributed_faiss_tpu.models.ivf import IVFPQIndex
    from distributed_faiss_tpu.ops.adc_pallas import on_tpu

    n = {"full": 500_000, "small": 20_000, "tiny": 3_000}[size]
    nlist = {"full": 4096, "small": 128, "tiny": 32}[size]
    m = {"full": 64, "small": 16, "tiny": 8}[size]
    d = {"full": 768, "small": 256, "tiny": 64}[size]
    small = size != "full"
    k = 10
    on_chip = on_tpu()
    gen = make_lowrank_corpus(rng, d, r=max(d // 12, 8), n_latent_clusters=2 * nlist)
    x, q = gen(n), gen(128 if small else 512)
    # refine store built at the largest factor we sweep; the factor itself
    # is a search-time knob (adc_k = k * factor)
    idx = IVFPQIndex(d, nlist, m=m, metric="l2", kmeans_iters=8, pq_iters=10,
                     refine_k_factor=32, use_pallas=on_chip)
    t0 = time.time()
    idx.train(x[:min(n, 100_000)])
    idx.add(x)
    note(f"knnlm built in {time.time() - t0:.1f}s")
    exact = FlatIndex(d, "l2")
    exact.add(x)
    _, gt = exact.search(q[:128], k)
    note("ground truth ready")

    nprobes = {"full": [32, 64, 128, 256], "small": [8, 16, 32],
               "tiny": [4, 32]}[size]
    factors = [0, 8, 16, 32] if size != "tiny" else [0, 16]
    rows = grid_rows("knnlm-curve", idx, x, q, gt, k, nprobes, factors,
                     recall_only=recall_only)
    best = pick_operating_point(rows)
    if best is not None:
        idx.set_nprobe(best["nprobe"])
        floor = cpu_ivf_qps(x, np.asarray(idx.get_centroids()),
                            idx.get_assignments(), q[:32], k, best["nprobe"], "l2")
        best = dict(best, config="knnlm-operating-point",
                    cpu_ivf_qps=round(floor, 1))
        if "qps" in best:
            best["vs_cpu_ivf"] = round(best["qps"] / floor, 2)
        print(json.dumps(best), flush=True)


def ivfsq_curve(rng, size, recall_only=False):
    from distributed_faiss_tpu.models.flat import FlatIndex
    from distributed_faiss_tpu.models.ivf import IVFFlatIndex

    n = {"full": 500_000, "small": 50_000, "tiny": 4_000}[size]
    nlist = {"full": 1024, "small": 128, "tiny": 32}[size]
    d = 512 if size != "tiny" else 64
    k = 10
    centers = rng.standard_normal((nlist, d)).astype(np.float32) * 4.0
    from benchmarks.baseline_configs import clustered
    x = clustered(rng, n, d, centers)
    q = clustered(rng, 512, d, centers)
    idx = IVFFlatIndex(d, nlist, "l2", codec="f16", kmeans_iters=8)
    t0 = time.time()
    idx.train(x[:min(n, 100_000)])
    idx.add(x)
    note(f"ivfsq built in {time.time() - t0:.1f}s")
    exact = FlatIndex(d, "l2")
    exact.add(x)
    _, gt = exact.search(q[:128], k)

    nprobes = {"full": [8, 16, 32, 64, 128], "small": [4, 8, 16, 32],
               "tiny": [2, 16]}[size]
    rows = grid_rows("ivfsq-curve", idx, x, q, gt, k, nprobes,
                     recall_only=recall_only)
    best = pick_operating_point(rows)
    if best is not None:
        floor = cpu_ivf_qps(x, np.asarray(idx.get_centroids()),
                            idx.get_assignments(), q[:32], k, best["nprobe"], "l2")
        best = dict(best, config="ivfsq-operating-point",
                    cpu_ivf_qps=round(floor, 1))
        if "qps" in best:
            best["vs_cpu_ivf"] = round(best["qps"] / floor, 2)
        print(json.dumps(best), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true", help="CPU-sized corpora")
    ap.add_argument("--tiny", action="store_true", help="smoke-test sizes")
    ap.add_argument("--only", choices=["knnlm", "ivfsq"], default=None)
    ap.add_argument("--recall-only", action="store_true",
                    help="skip QPS timing (recall is backend-independent: "
                         "lets a CPU box validate the full-size recall bar)")
    args = ap.parse_args()
    from distributed_faiss_tpu.utils import envutil

    envutil.place_compile_cache()
    size = "tiny" if args.tiny else ("small" if args.small else "full")
    rng = np.random.default_rng(7)
    if args.only in (None, "knnlm"):
        knnlm_curve(rng, size, recall_only=args.recall_only)
    if args.only in (None, "ivfsq"):
        ivfsq_curve(rng, size, recall_only=args.recall_only)


if __name__ == "__main__":
    main()
