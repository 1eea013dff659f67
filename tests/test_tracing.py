"""Tracing/observability unit tests (SURVEY §5.1 — the reference has none)."""

import threading
import time

from distributed_faiss_tpu.utils.tracing import LatencyStats, stage


def test_latency_stats_concurrent():
    stats = LatencyStats()

    def worker():
        for _ in range(50):
            stats.record("op", 0.01)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    s = stats.summary()["op"]
    assert s["count"] == 400
    assert abs(s["mean_s"] - 0.01) < 1e-9
    assert s["max_s"] == 0.01
    stats.reset()
    assert stats.summary() == {}


def test_latency_stats_streaming_percentiles():
    """p50/p95/p99 from the fixed log-spaced histogram: each estimate is
    the containing bucket's upper edge — within one bucket ratio
    (10^(1/5) ~ 1.58x) above the true quantile, never below it, and capped
    at the exact max."""
    stats = LatencyStats()
    # 100 distinct values spanning ~2 decades: true p50=0.00505, p99=0.01
    for i in range(1, 101):
        stats.record("op", i * 1e-4)
    s = stats.summary()["op"]
    for key, true_q in (("p50_s", 0.00505), ("p95_s", 0.0095),
                        ("p99_s", 0.0099)):
        assert true_q <= s[key] <= true_q * 1.585, (key, s[key])
    assert s["p50_s"] <= s["p95_s"] <= s["p99_s"] <= s["max_s"]


def test_latency_stats_percentiles_degenerate_and_extreme():
    stats = LatencyStats()
    stats.record("one", 0.01)  # single sample: all percentiles == max
    s = stats.summary()["one"]
    assert s["p50_s"] == s["p95_s"] == s["p99_s"] == 0.01
    # values beyond the bucket range clamp (no crash, capped at exact max)
    stats.record("huge", 1e9)
    stats.record("tiny", 1e-12)
    assert stats.summary()["huge"]["p99_s"] == 1e9
    assert stats.summary()["tiny"]["p99_s"] <= 1e-6


def test_stage_records_its_block():
    stats = LatencyStats()
    with stage("block", stats) as st:
        time.sleep(0.02)
    s = stats.summary()["block"]
    assert s["count"] == 1
    assert s["mean_s"] >= 0.015 and s["total_s"] == st.dt


def test_instants_go_up_to_the_handover_that_collects_them():
    """``tracing.instant`` readings land in the enclosing handover that asked
    for them, in whichever leg and on whichever thread; a handover that did
    not ask leaves them to the one around it; with none around, nothing
    keeps them; ``first`` keeps the earliest."""
    from distributed_faiss_tpu.utils import tracing

    stats = LatencyStats()
    assert tracing.instant("nowhere") > 0  # no handover: the reading, no more
    outer = tracing.handover("outer", sink=stats, instants=True)
    inner = tracing.handover("inner", sink=stats)
    with outer:
        with inner:
            a = tracing.instant("dispatched", first=True)
            assert tracing.instant("dispatched", first=True) > a
        early = tracing.instant("ready")
    assert inner.instants is None and outer.instants == {
        "dispatched": a, "ready": early}
    assert tracing.instant("between the legs") and "between the legs" not in outer.instants
    got = {}

    def last_leg():
        with outer.last(), inner.last():
            got["ready"] = tracing.instant("ready")  # a later one replaces

    worker = threading.Thread(target=last_leg)
    worker.start()
    worker.join(10)
    assert outer.instants == {"dispatched": a, "ready": got["ready"]}
    assert outer.t0 <= a < early < got["ready"] <= outer.t0 + outer.dt
    assert stats.summary()["outer"]["count"] == stats.summary()["inner"]["count"] == 1
    with stage("after", stats):  # the context is what it was
        assert tracing.instant("nobody's") and "nobody's" not in outer.instants


def test_profile_capture_writes(tmp_path):
    import os

    from distributed_faiss_tpu.observability import profile

    import jax.numpy as jnp

    d = str(tmp_path / "trace")
    worker = threading.Thread(
        target=lambda: jnp.ones((32, 32)).sum().block_until_ready())
    worker.start()
    path = profile.capture(0.2, d)
    worker.join()
    assert path.endswith(".xplane.pb") and os.path.getsize(path) > 0
