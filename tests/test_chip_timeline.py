"""The chip's timeline, kept by the scheduler's completer (ISSUE 42;
serving/scheduler.py ``_book_chip``): a stub ``launch_fn`` marks the two
instants a window hands up (``tracing.instant``: ``dispatched`` in the
launch, ``ready`` once its collect has slept the window's time on the
"chip") and keeps them, so the rows can be held to them: busy plus idle is
the last ``ready`` less the first ``dispatched``, busy plus queue a window's
``ready`` less its ``dispatched``; an idle chip is put down to what the
batcher thread was in; two windows in flight queue and leave no gap; a
window that dies books nothing; a finished handle's launch is its span.
Pure threads and sleeps: no device work, and nothing timed here is a speed.
"""

import threading
import time

import numpy as np
import pytest

from distributed_faiss_tpu.observability import spans
from distributed_faiss_tpu.serving import SearchScheduler, scheduler
from distributed_faiss_tpu.utils import tracing
from distributed_faiss_tpu.utils.config import SchedulerCfg

pytestmark = pytest.mark.scheduler

K = 3
IDLE = tracing.CHIP_IDLE


class Chip:
    """A two-phase ``search_fn`` over a pretend chip: the launch marks
    ``dispatched`` (after ``dispatch_s`` of host work), the collect sleeps
    ``busy_s`` and marks ``ready``; ``index_id`` "bad" raises in the collect
    after its sleep. ``windows`` keeps ``(dispatched, ready)`` of every
    window that was collected, in launch order."""

    def __init__(self, busy_s=0.002, dispatch_s=0.0):
        self.busy_s, self.dispatch_s = busy_s, dispatch_s
        self.windows = []
        self.launched = 0

    def launch(self, index_id, q, k, return_embeddings):
        if self.dispatch_s:
            time.sleep(self.dispatch_s)
        self.launched += 1
        dispatched = tracing.instant("dispatched", first=True)
        chip = self

        class Handle:
            def collect(self):
                time.sleep(chip.busy_s)
                if index_id == "bad":
                    raise RuntimeError("boom in the collect")
                chip.windows.append((dispatched, tracing.instant("ready")))
                return np.zeros((q.shape[0], k), np.float32), np.zeros(
                    (q.shape[0], k), np.int64)

        return Handle()

    def __call__(self, *call):
        return self.launch(*call).collect()


def rows(n=1, dim=4):
    return np.zeros((n, dim), np.float32)


def submit(sched, index_id="idx", n=1, **kw):
    done, out = threading.Event(), {}

    def callback(result, error):
        out["error"] = error
        done.set()

    sched.submit_async(index_id, rows(n), K, callback=callback, **kw)
    return done, out


def booked(sched):
    """{row: (count, total)} of the timeline's rows, zeros where none."""
    q = sched.perf_stats()["queues"]
    return {name: (q[name]["count"], q[name]["total_s"]) if name in q else (0, 0.0)
            for name in tracing.CHIP_ROWS}


def settle(sched, n):
    """Wait until ``n`` windows are booked (the callback fires after)."""
    deadline = time.time() + 10
    while booked(sched)["sched.chip_busy"][0] < n:
        assert time.time() < deadline, "the windows were never booked"
        time.sleep(0.002)
    return booked(sched)


@pytest.fixture
def chip():
    return Chip()


@pytest.fixture
def sched(chip):
    s = SearchScheduler(chip, SchedulerCfg(max_wait_ms=0.0, max_batch_rows=1))
    yield s
    s.stop()


def test_no_row_before_the_first_window_and_all_five_after_it(sched, chip):
    assert not set(tracing.CHIP_ROWS) & set(sched.perf_stats()["queues"])
    assert "chip_timeline_s" not in sched.perf_stats()["counters"]
    assert submit(sched)[0].wait(10)
    got = settle(sched, 1)
    # the first window has no gap before it: the timeline starts with it
    assert {n: c for n, (c, _) in got.items()} == {
        "sched.chip_busy": 1, "sched.chip_queue": 1, **{n: 0 for n in IDLE}}
    (dispatched, ready), = chip.windows
    assert got["sched.chip_busy"][1] == pytest.approx(ready - dispatched, abs=1e-9)
    assert sched.perf_stats()["counters"]["chip_timeline_s"] == pytest.approx(
        ready - dispatched, abs=1e-9)


def test_the_two_identities_over_50_windows(sched, chip):
    """Three callers of single rows, so windows queue behind one another at
    times and leave gaps at others: busy + idle is the last ``ready`` less
    the first ``dispatched``, and busy + queue the windows' own ``ready``
    less ``dispatched``, to the microsecond."""
    def caller(i):
        for j in range(17):
            done, out = submit(sched)
            assert done.wait(10) and out["error"] is None
            time.sleep(0.004 * ((i + j) % 3))

    threads = [threading.Thread(target=caller, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    got = settle(sched, 51)
    assert len(chip.windows) == 51 >= 50
    first, last = chip.windows[0][0], chip.windows[-1][1]
    busy, queue = got["sched.chip_busy"][1], got["sched.chip_queue"][1]
    idle = sum(got[n][1] for n in IDLE)
    assert busy + idle == pytest.approx(last - first, abs=1e-6)
    assert busy + queue == pytest.approx(
        sum(ready - dispatched for dispatched, ready in chip.windows), abs=1e-6)
    assert got["sched.chip_busy"][0] == got["sched.chip_queue"][0] == 51
    assert sched.perf_stats()["counters"]["chip_timeline_s"] == pytest.approx(
        last - first, abs=1e-9)
    assert queue > 0 and idle > 0  # the drive met both cases


def test_an_empty_queue_is_booked_as_empty(sched, chip):
    assert submit(sched)[0].wait(10)
    settle(sched, 1)
    time.sleep(0.06)  # nothing queued: the batcher is in sched.idle
    assert submit(sched)[0].wait(10)
    got = settle(sched, 2)
    gap = chip.windows[1][0] - chip.windows[0][1]
    assert gap > 0.05
    assert sum(got[n][1] for n in IDLE) == pytest.approx(gap, abs=1e-6)
    assert got["sched.chip_idle.empty"][1] > 0.9 * gap
    assert got["sched.chip_idle.window_wait"][0] == 0


def test_a_head_held_for_followers_is_booked_as_window_wait(chip):
    sched = SearchScheduler(chip, SchedulerCfg(max_wait_ms=60.0, max_batch_rows=8))
    try:
        assert submit(sched, eager=True)[0].wait(10)
        settle(sched, 1)
        assert submit(sched)[0].wait(10)  # alone: held for max_wait_ms
        got = settle(sched, 2)
        gap = chip.windows[1][0] - chip.windows[0][1]
        assert sum(got[n][1] for n in IDLE) == pytest.approx(gap, abs=1e-6)
        assert got["sched.chip_idle.window_wait"][1] > 0.05
        assert got["sched.chip_idle.window_wait"][1] > 0.8 * gap
    finally:
        sched.stop()


def test_a_slow_assemble_is_booked_as_host(chip, monkeypatch):
    concat = scheduler._concat_rows

    def slow(live, n_rows):
        time.sleep(0.05)
        return concat(live, n_rows)

    monkeypatch.setattr(scheduler, "_concat_rows", slow)
    sched = SearchScheduler(chip, SchedulerCfg(max_wait_ms=20.0, max_batch_rows=8))
    try:
        assert submit(sched, eager=True)[0].wait(10)
        settle(sched, 1)
        pair = [submit(sched) for _ in range(2)]  # two requests: a concat
        assert all(done.wait(10) for done, _ in pair)
        got = settle(sched, 2)
        assert chip.launched == 2
        gap = chip.windows[1][0] - chip.windows[0][1]
        assert sum(got[n][1] for n in IDLE) == pytest.approx(gap, abs=1e-6)
        assert got["sched.chip_idle.host"][1] > 0.045
        # the wait for the follower, before it, is not the host's
        assert got["sched.chip_idle.host"][1] < gap - 0.015
    finally:
        sched.stop()


def test_the_dispatch_before_the_first_program_is_host():
    chip = Chip(dispatch_s=0.03)
    sched = SearchScheduler(chip, SchedulerCfg(max_wait_ms=0.0, max_batch_rows=1))
    try:
        for n in (1, 2):
            assert submit(sched)[0].wait(10)
            got = settle(sched, n)
        assert got["sched.chip_idle.host"][1] >= 0.03
    finally:
        sched.stop()


def test_two_windows_in_flight_queue_and_leave_no_gap():
    chip = Chip(busy_s=0.03)
    sched = SearchScheduler(chip, SchedulerCfg(max_wait_ms=0.0, max_batch_rows=1))
    try:
        calls = [submit(sched) for _ in range(4)]
        assert all(done.wait(10) for done, _ in calls)
        got = settle(sched, 4)
        assert [got[n][0] for n in IDLE] == [0, 0, 0]
        assert got["sched.chip_queue"][1] > 0.05  # each waited behind the one ahead
        # a window's own span is what the chip worked it, not its wait as well
        assert got["sched.chip_busy"][1] == pytest.approx(
            chip.windows[-1][1] - chip.windows[0][0], abs=1e-6)
        assert got["sched.chip_busy"][1] / 4 < 0.045
    finally:
        sched.stop()


def test_a_window_that_raises_books_nothing_and_leaves_free_where_it_was(sched, chip):
    assert submit(sched)[0].wait(10)
    settle(sched, 1)
    time.sleep(0.03)
    done, out = submit(sched, "bad")
    assert done.wait(10) and isinstance(out["error"], RuntimeError)
    assert booked(sched)["sched.chip_busy"][0] == 1
    time.sleep(0.03)
    assert submit(sched)[0].wait(10)
    got = settle(sched, 2)
    assert chip.launched == 3 and len(chip.windows) == 2
    # the next good window's gap starts at the last good ``ready``, and the
    # dead window's waits explain their part of it
    gap = chip.windows[1][0] - chip.windows[0][1]
    assert gap > 0.06
    assert sum(got[n][1] for n in IDLE) == pytest.approx(gap, abs=1e-6)
    assert got["sched.chip_idle.empty"][1] > 0.055
    busy = got["sched.chip_busy"][1]
    assert busy + gap == pytest.approx(
        chip.windows[1][1] - chip.windows[0][0], abs=1e-6)


def test_a_finished_handle_books_its_launch():
    """A plain ``search_fn`` (HNSW's kind of launch: the whole search): the
    window's span on the chip is its launch, nothing queues, and the gap to
    the next one is the batcher's."""
    spent = []

    def search_fn(index_id, q, k, return_embeddings):
        t0 = tracing.now()
        time.sleep(0.02)
        spent.append(tracing.now() - t0)
        return np.zeros((q.shape[0], k), np.float32), np.zeros((q.shape[0], k), np.int64)

    sched = SearchScheduler(search_fn, SchedulerCfg(max_wait_ms=0.0, max_batch_rows=1))
    try:
        calls = [submit(sched) for _ in range(3)]
        assert all(done.wait(10) for done, _ in calls)
        got = settle(sched, 3)
        assert got["sched.chip_queue"] == (3, 0.0)
        assert sum(spent) <= got["sched.chip_busy"][1] < sum(spent) + 0.01
        # from one launch's end to the next one's start: the batcher's python
        assert got["sched.chip_idle.host"][1] < 0.01
    finally:
        sched.stop()


def test_a_sampled_requests_device_span_carries_the_windows_numbers(sched, chip):
    assert submit(sched)[0].wait(10)
    settle(sched, 1)
    time.sleep(0.02)
    tid = spans.mint_trace_id()
    with tracing.bind((tid, None, None)):
        done, _ = submit(sched)
    assert done.wait(10)
    settle(sched, 2)
    device, = [s["extra"] for s in spans.local_buffer().snapshot(tid)
               if s["name"] == "server.device"]
    (_, free), (dispatched, ready) = chip.windows
    assert device["chip_busy_s"] == pytest.approx(ready - dispatched, abs=1e-9)
    assert device["chip_queue_s"] == 0.0
    assert device["idle_before_s"] == pytest.approx(dispatched - free, abs=1e-9)
    assert device["chip_busy_s"] + device["chip_queue_s"] == pytest.approx(
        ready - dispatched, abs=1e-6)


def test_an_index_that_runs_to_the_end_in_its_launch_says_ready_there():
    """``models/base.finished`` (the default ``launch_search``: HNSW, the
    mesh indexes) marks the window's ``ready`` in the launch, so the
    scheduler takes the launch's end and not the collect's (the metadata
    join, the wait for the window ahead to be split)."""
    from distributed_faiss_tpu.models import base

    device = tracing.handover("server.device", sink=tracing.SPAN_ONLY, instants=True)
    with device:
        handle = base.finished("rows")
        after = tracing.now()
    assert set(device.instants) == {"ready"}
    assert device.t0 <= device.instants["ready"] <= after
    assert handle.collect() == "rows"
