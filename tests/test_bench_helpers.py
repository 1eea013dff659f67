"""Numpy-only sanity checks for the benchmark harness helpers.

These pin the CPU baseline implementations (the denominators of every
vs_cpu_* ratio in benchmarks/RESULTS.md) without touching jax.
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.baseline_configs import (  # noqa: E402
    cpu_exact_qps,
    cpu_ivf_qps,
    make_lowrank_corpus,
    recall_at_k,
)


def _exact_topk(x, q, k, metric):
    if metric == "l2":
        d2 = (x * x).sum(1)[None, :] - 2.0 * (q @ x.T)
    else:
        d2 = -(q @ x.T)
    part = np.argpartition(d2, k - 1, axis=1)[:, :k]
    pd = np.take_along_axis(d2, part, axis=1)
    return np.take_along_axis(part, np.argsort(pd, axis=1), axis=1)


def test_cpu_ivf_qps_runs_and_full_probe_is_positive():
    rng = np.random.default_rng(0)
    n, d, nlist, k = 5000, 16, 32, 5
    cents = rng.standard_normal((nlist, d)).astype(np.float32)
    x = rng.standard_normal((n, d)).astype(np.float32)
    assign = ((x[:, None, :] - cents[None]) ** 2).sum(2).argmin(1)
    q = rng.standard_normal((8, d)).astype(np.float32)
    for metric in ("l2", "dot"):
        assert cpu_ivf_qps(x, cents, assign, q, k, 4, metric) > 0
        assert cpu_ivf_qps(x, cents, assign, q, k, nlist, metric) > 0


def test_lowrank_corpus_shapes_and_rank():
    rng = np.random.default_rng(1)
    gen = make_lowrank_corpus(rng, d=64, r=8, n_latent_clusters=16)
    x = gen(500)
    assert x.shape == (500, 64) and x.dtype == np.float32
    # energy concentrates in ~r directions (ambient noise is 0.05)
    s = np.linalg.svd(x - x.mean(0), compute_uv=False)
    assert s[7] > 10 * s[8]


def test_recall_and_exact_helpers_agree():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((2000, 8)).astype(np.float32)
    q = rng.standard_normal((16, 8)).astype(np.float32)
    gt = _exact_topk(x, q, 5, "l2")
    assert recall_at_k(gt, gt, 5) == 1.0
    assert cpu_exact_qps(x, q, 5, "l2") > 0


def test_bench_artifact_names_the_platform_it_ran_on():
    import bench

    row = bench.format_result(
        backend="tpu", rec=0.96, n=500_000, d=128, nprobe=8,
        build_s=30.0, tpu_qps=9000.0, cpu_qps=900.0,
    )
    assert "backend=tpu" in row["metric"]
    assert row["vs_baseline"] == 10.0
    assert "backend_degraded" not in row


def test_bench_refuses_to_measure_off_the_tpu():
    """No fallback: off the TPU the full-size bench exits non-zero and
    prints no artifact (BENCH_SMALL=1 is the CPU smoke size)."""
    import subprocess

    repo = os.path.join(os.path.dirname(__file__), "..")
    env = {k: v for k, v in os.environ.items() if k != "BENCH_SMALL"}
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py")],
        env={**env, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "not 'tpu'" in proc.stderr and proc.stdout.strip() == ""
