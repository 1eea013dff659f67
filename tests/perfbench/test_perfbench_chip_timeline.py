"""The readers of the chip's timeline (ISSUE 42): each of the ten on a
hand-made pair of ``get_perf_stats`` snapshots as a rank gives them — the
whole window, four ranks, the parent's snapshots without the rows, an
untraced run, a window with no launch — the rows as a scheduler serves them,
and the ten ``per_layer`` entries the issue wrote, kept ready beside the tests
(``data/chip_timeline_entries.json``) until a ``benchmark`` PR can append
them: ``test_perfbench_overlap.py`` pins the list's last two."""

import os
import threading

import numpy as np
import pytest

from perfbench import loader
from pb_helpers import REPO

BATCH = ["knnlm-batch", "ivfsq-batch", "knnlm-4rank-batch", "flat768-batch"]
BASES = ("sched.chip_busy_ms", "engine.dispatch_ms", "sched.chip_idle_pct",
         "sched.chip_idle_empty_pct", "sched.chip_idle_host_pct")
NAMES = [name + suffix for name in BASES for suffix in ("", ".online")]
WINDOW_S = 20.0

# what a rank's timeline read when the window started, and what a window of
# ``n`` merged windows adds to it: 15 ms busy and 0.4 ms of dispatch a
# window, 9 ms queued behind the one ahead, and of the window's 20 s the chip
# idle 0.2 s with nothing queued, 0.3 s held for followers, 0.5 s for the host
START = {"sched.chip_busy": (40, 0.9), "sched.chip_queue": (40, 0.1),
         "sched.chip_idle.empty": (12, 30.0), "sched.chip_idle.window_wait": (3, 0.02),
         "sched.chip_idle.host": (40, 0.3)}
IDLE_S = {"sched.chip_idle.empty": 0.2, "sched.chip_idle.window_wait": 0.3,
          "sched.chip_idle.host": 0.5}


def row(count, total):
    return {"count": count, "total_s": total}


def snapshots(n, ranks=1, timeline=True, busy_s=0.015, slow_rank_s=None):
    """``n`` windows a rank; ``timeline`` False is the parent's program (the
    stage ledger without the timeline's rows); ``slow_rank_s`` gives the last
    rank another busy time a window."""
    def snap(windows, busy):
        sched = {"sched.idle": row(5 + windows, 17.0 * bool(windows))}
        engine = {"device_search_s": row(7 + windows, 0.025 * windows),
                  "engine.scan": row(7 + windows, 0.024 * windows)}
        if timeline:
            engine["engine.dispatch"] = row(7 + windows, 0.01 + 0.0004 * windows)
            for name, (count, total) in START.items():
                sched[name] = row(count + windows * (name in (
                    "sched.chip_busy", "sched.chip_queue", "sched.chip_idle.host")),
                    total + bool(windows) * IDLE_S.get(name, 0.0))
            sched["sched.chip_busy"]["total_s"] = START["sched.chip_busy"][1] + busy * windows
            sched["sched.chip_queue"]["total_s"] = START["sched.chip_queue"][1] + 0.009 * windows
        return {"scheduler": {"queues": sched}, "engine": {"bench": engine}}

    per_rank = [busy_s] * ranks
    if slow_rank_s is not None:
        per_rank[-1] = slow_rank_s
    return {"index_id": "bench", "window_s": WINDOW_S,
            "stats_before": [snap(0, b) for b in per_rank],
            "stats_after": [snap(n, b) for b in per_rank]}


WANT = {"sched.chip_busy_ms": 15.0, "engine.dispatch_ms": 0.4,
        "sched.chip_idle_pct": 100.0 * 1.0 / WINDOW_S,
        "sched.chip_idle_empty_pct": 100.0 * 0.2 / WINDOW_S,
        "sched.chip_idle_host_pct": 100.0 * 0.5 / WINDOW_S}


def reader_of(name):
    return loader.load_module(os.path.join(REPO, "perfbench", "layer_metrics",
                                           f"{name}.py"))


def base_of(name):
    return name[:-len(".online")] if name.endswith(".online") else name


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("case,obs,reads", [
    ("whole-window", snapshots(1300), True),
    ("four-ranks", snapshots(1300, ranks=4), True),
    ("the-parent", snapshots(1300, timeline=False), False),
    ("no-launch", snapshots(0), False),
    ("untraced", {"index_id": "bench", "window_s": WINDOW_S}, False),
], ids=lambda v: v if isinstance(v, str) else "")
def test_a_reader_of_the_chips_timeline(name, case, obs, reads):
    got = reader_of(name).read(obs)
    if reads:
        assert got == pytest.approx(WANT[base_of(name)], rel=1e-9)
    else:
        assert got is None


def test_the_slowest_rank_gives_the_busy_time_and_the_ranks_mean_the_idle_share():
    obs = snapshots(1300, ranks=4, slow_rank_s=0.019)
    assert reader_of("sched.chip_busy_ms").read(obs) == pytest.approx(19.0)
    # one rank of four idle twice as long as the others
    for before, after in zip(obs["stats_before"][-1:], obs["stats_after"][-1:]):
        rows = after["scheduler"]["queues"]
        for name, seconds in IDLE_S.items():
            rows[name] = row(rows[name]["count"], rows[name]["total_s"] + seconds)
    assert reader_of("sched.chip_idle_pct").read(obs) == pytest.approx(
        100.0 * (3 * 1.0 + 2.0) / 4 / WINDOW_S)


def test_a_rank_that_collected_nothing_in_the_window_gives_no_idle_share():
    """It booked no gap, whatever its chip did: no share is better than a
    mean with a rank read as never idle."""
    obs = snapshots(1300, ranks=2)
    obs["stats_after"][1] = obs["stats_before"][1]
    for name in BASES[2:]:
        assert reader_of(name).read(obs) is None


def test_the_readers_read_what_a_scheduler_serves():
    """From its first collected window on a scheduler serves all five rows,
    a cause that took no gap at zero: the readers give numbers, the idle
    shares 0 where every window queued behind the one ahead."""
    from distributed_faiss_tpu.serving import SearchScheduler
    from distributed_faiss_tpu.utils.config import SchedulerCfg

    def search_fn(index_id, q, k, return_embeddings):
        return np.zeros((q.shape[0], k), np.float32), np.zeros((q.shape[0], k), np.int64)

    def entry(sched):
        return {"scheduler": sched.perf_stats(), "engine": {"bench": {}}}

    sched = SearchScheduler(search_fn, SchedulerCfg(max_wait_ms=0.0))
    try:
        before = entry(sched)
        assert not any(n.startswith("sched.chip") for n in before["scheduler"]["queues"])
        for _ in range(3):
            sched.submit("bench", np.zeros((2, 4), np.float32), 3)
        done = threading.Event()
        sched.submit_async("bench", np.zeros((2, 4), np.float32), 3,
                           callback=lambda *_: done.set())
        assert done.wait(10)
        after = entry(sched)
    finally:
        sched.stop()
    obs = {"index_id": "bench", "window_s": 1.0,
           "stats_before": [before], "stats_after": [after]}
    assert reader_of("sched.chip_busy_ms").read(obs) > 0
    idle = reader_of("sched.chip_idle_pct").read(obs)
    parts = [reader_of(name).read(obs) for name in BASES[3:]]
    assert idle is not None and all(p is not None and 0 <= p <= idle for p in parts)
    assert reader_of("engine.dispatch_ms").read(obs) is None  # no engine here


def test_the_ten_entries_are_ready_to_append():
    """As ISSUE 42 gives them: ``program_counter``, lower is better, the four
    batch cells under ``qps`` and ``knnlm-online`` under ``lat_p50_ms``, each
    under a layer the benchmark already names and with a reader to its name.
    Once a ``benchmark`` PR has appended them they stand in ``per_layer``
    as they are here."""
    bench = loader.read_json(os.path.join(REPO, "BENCHMARK.json"))
    ready = loader.read_json(os.path.join(
        REPO, "tests", "perfbench", "data", "chip_timeline_entries.json"))["per_layer"]
    assert [m["name"] for m in ready] == NAMES
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NAMES}
    cells = {w["name"] for w in bench["workloads"]}
    reports = {m["name"]: m.get("workloads", cells) for m in bench["end_to_end"]}
    have = {m["name"]: m for m in bench["per_layer"]}
    for m in ready:
        online = m["name"].endswith(".online")
        assert m == {"name": m["name"], "unit": "ms" if "_ms" in m["name"] else "%",
                     "better": "lower", "source": "program_counter",
                     "layer": ("engine launch-to-fetch, join"
                               if m["name"].startswith("engine.") else "scheduler"),
                     "moves": "lat_p50_ms" if online else "qps",
                     "workloads": ["knnlm-online"] if online else BATCH}
        assert m["layer"] in layers
        assert set(m["workloads"]) <= cells & set(reports[m["moves"]])
        assert callable(reader_of(m["name"]).read)
        assert have.get(m["name"], m) == m
