"""Two windows in flight (serving/scheduler.py): with a fake two-phase
``search_fn`` whose collect waits on an event, window n+1 is launched before
n is collected, never more than two are uncollected, completions are
published in launch order, an error fails its own window only, a deadline is
shed before the launch, ``stop()`` lets what is in flight complete, a
window does not shrink while one is in flight, and a caller's slice is what
it gets alone. The cases that do not need the event run over the plain
(serial) ``search_fn`` too. Pure threads and numpy: no device work."""

import threading
import time

import numpy as np
import pytest

from distributed_faiss_tpu.serving import (
    DeadlineExpired,
    SchedulerStopped,
    SearchScheduler,
)
from distributed_faiss_tpu.utils.config import SchedulerCfg

pytestmark = pytest.mark.scheduler

K = 3


def answer(q, k=K):
    """A per-row search: what a row gets does not depend on its window."""
    q = np.asarray(q, np.float32)
    scores = np.cumsum(np.repeat(q.sum(axis=1, keepdims=True), k, axis=1), axis=1)
    ids = (np.abs(q[:, :1]) * 1000).astype(np.int64) + np.arange(k)
    return scores, ids


class Serial:
    """A plain ``search_fn``: one call serves the window (and, ``gated``,
    waits in it until the test lets every window go)."""

    def __init__(self, gated=False):
        self.windows = []  # rows of every window, in launch order
        self.go = threading.Event()
        if not gated:
            self.go.set()

    def __call__(self, index_id, q, k, return_embeddings):
        self.windows.append(q.shape[0])
        assert self.go.wait(20), "the test never let the window go"
        if index_id == "bad":
            raise RuntimeError("boom in the search")
        return answer(q, k)

    def open_all(self):
        self.go.set()


class TwoPhase(Serial):
    """A ``search_fn`` that also offers ``launch``: the launch records the
    window and returns, the collect waits for the window's gate (all gates
    open unless ``gated``) and records that it ran."""

    def __init__(self, gated=False):
        super().__init__(gated)
        self.gated = gated
        self.gates = []
        self.collected = []  # window numbers, in collect order
        self.uncollected_max = 0
        self._lock = threading.Lock()

    def launch(self, index_id, q, k, return_embeddings):
        if index_id == "bad-launch":
            raise RuntimeError("boom in the launch")
        with self._lock:
            n = len(self.windows)
            self.windows.append(q.shape[0])
            gate = threading.Event()
            if not self.gated:
                gate.set()
            self.gates.append(gate)
            self.uncollected_max = max(self.uncollected_max,
                                       len(self.windows) - len(self.collected))
        fn = self

        class Handle:
            def collect(self):
                assert gate.wait(20), "the test never opened the gate"
                with fn._lock:
                    fn.collected.append(n)
                if index_id == "bad":
                    raise RuntimeError("boom in the collect")
                return answer(q, k)

        return Handle()

    def __call__(self, *call):
        return self.launch(*call).collect()

    def open_all(self):
        for gate in self.gates:
            gate.set()


FORMS = {"serial": Serial, "two-phase": TwoPhase}


def until(cond, what, timeout=10.0):
    deadline = time.time() + timeout
    while not cond():
        assert time.time() < deadline, what
        time.sleep(0.002)


def rows(n, seed=0, dim=4):
    return np.random.default_rng(seed).standard_normal((n, dim)).astype(np.float32)


def submit_async(sched, q, index_id="idx", **kw):
    """(done event, outcome dict) of one asynchronous submit."""
    done, out = threading.Event(), {}

    def callback(result, error):
        out["result"], out["error"], out["at"] = result, error, time.monotonic()
        done.set()

    sched.submit_async(index_id, q, K, callback=callback, **kw)
    return done, out


@pytest.fixture
def gated():
    fn = TwoPhase(gated=True)
    sched = SearchScheduler(fn, SchedulerCfg(max_wait_ms=0.0, max_batch_rows=1))
    yield fn, sched
    fn.open_all()
    sched.stop()


def test_window_n_plus_1_is_launched_before_n_is_collected(gated):
    fn, sched = gated
    first = submit_async(sched, rows(1, 1))
    until(lambda: len(fn.windows) == 1, "window 0 was never launched")
    second = submit_async(sched, rows(1, 2))
    until(lambda: len(fn.windows) == 2, "window 1 waited for window 0's collect")
    assert fn.collected == [] and not first[0].is_set() and not second[0].is_set()
    fn.gates[0].set()
    assert first[0].wait(10)
    fn.gates[1].set()
    assert second[0].wait(10)
    assert fn.collected == [0, 1]
    for (_, out), seed in ((first, 1), (second, 2)):
        np.testing.assert_array_equal(out["result"][0], answer(rows(1, seed))[0])


def test_never_more_than_two_uncollected(gated):
    fn, sched = gated
    calls = [submit_async(sched, rows(1, i)) for i in range(5)]
    until(lambda: len(fn.windows) == 2, "two windows were not launched")
    time.sleep(0.1)  # a third would have been launched by now
    assert len(fn.windows) == SearchScheduler.IN_FLIGHT == 2
    assert sched.perf_stats()["counters"]["queued"] == 3
    for n in range(5):  # every collect frees one place, no more
        fn.gates[n].set()
        assert calls[n][0].wait(10)
        until(lambda: len(fn.windows) == min(5, n + 3), "the freed place stayed empty")
    assert fn.uncollected_max == 2 and fn.collected == [0, 1, 2, 3, 4]


def test_completions_are_published_in_launch_order(gated):
    fn, sched = gated
    first = submit_async(sched, rows(1, 1))
    until(lambda: len(fn.windows) == 1, "window 0 was never launched")
    second = submit_async(sched, rows(1, 2))
    until(lambda: len(fn.windows) == 2, "window 1 was never launched")
    fn.gates[1].set()  # the later window is done first
    time.sleep(0.1)
    assert not second[0].is_set(), "window 1 was published ahead of window 0"
    fn.gates[0].set()
    assert first[0].wait(10) and second[0].wait(10)
    assert first[1]["at"] <= second[1]["at"]


@pytest.mark.parametrize("form,bad", [("serial", "bad"), ("two-phase", "bad"),
                                      ("two-phase", "bad-launch")])
def test_an_error_in_one_window_fails_its_callers_only(form, bad):
    """The window between two good ones fails, in the launch or in the
    collect: its callers get the error, each an object of its own, the
    others their rows, and the loop serves on."""
    fn = FORMS[form]()
    sched = SearchScheduler(fn, SchedulerCfg(max_wait_ms=20.0))
    try:
        calls = [submit_async(sched, rows(2, 1), "good"),
                 submit_async(sched, rows(2, 2), bad),
                 submit_async(sched, rows(2, 3), bad),
                 submit_async(sched, rows(2, 4), "good")]
        for done, _ in calls:
            assert done.wait(10)
        for (_, out), seed in ((calls[0], 1), (calls[3], 4)):
            assert out["error"] is None
            np.testing.assert_array_equal(out["result"][1], answer(rows(2, seed))[1])
        errors = [calls[1][1]["error"], calls[2][1]["error"]]
        assert all(isinstance(e, RuntimeError) and "boom" in str(e) for e in errors)
        assert errors[0] is not errors[1]
        assert sched.submit("good", rows(1, 5), K)[0].shape == (1, K)
    finally:
        sched.stop()


@pytest.mark.parametrize("form", list(FORMS))
def test_a_deadline_is_shed_before_the_launch(form):
    """A request that expires in the queue, behind two gated windows (or a
    slow serial one), never reaches ``search_fn``."""
    fn = FORMS[form](gated=True)
    sched = SearchScheduler(fn, SchedulerCfg(max_wait_ms=0.0, max_batch_rows=1))
    try:
        ahead = 2 if form == "two-phase" else 1
        held = [submit_async(sched, rows(1, i)) for i in range(ahead)]
        until(lambda: len(fn.windows) == ahead, "the windows ahead were not launched")
        doomed = submit_async(sched, rows(1, 9), deadline=time.monotonic() + 0.05)
        time.sleep(0.15)  # expires while queued
        fn.open_all()
        assert doomed[0].wait(10)
        assert isinstance(doomed[1]["error"], DeadlineExpired)
        for done, out in held:
            assert done.wait(10) and out["error"] is None
        assert len(fn.windows) == ahead, "the expired rows reached search_fn"
        assert sched.perf_stats()["counters"]["shed_deadline"] == 1
    finally:
        fn.open_all()
        sched.stop()


def test_stop_completes_both_windows_in_flight_and_fails_the_queued():
    fn = TwoPhase(gated=True)
    sched = SearchScheduler(fn, SchedulerCfg(max_wait_ms=0.0, max_batch_rows=1))
    calls = [submit_async(sched, rows(1, i)) for i in range(4)]
    until(lambda: len(fn.windows) == 2, "two windows were not launched")
    stopper = threading.Thread(target=sched.stop, name="stopper")
    stopper.start()
    for done, out in calls[2:]:  # the queued ones fail at once
        assert done.wait(10) and isinstance(out["error"], SchedulerStopped)
    assert not calls[0][0].is_set() and not calls[1][0].is_set()
    fn.open_all()
    stopper.join(15)
    assert not stopper.is_alive()
    for (done, out), seed in zip(calls[:2], range(2)):
        assert done.is_set() and out["error"] is None
        np.testing.assert_array_equal(out["result"][0], answer(rows(1, seed))[0])
    assert len(fn.windows) == 2
    with pytest.raises(SchedulerStopped):
        sched.submit("idx", rows(1), K)
    assert not sched._thread.is_alive() and not sched._completer.is_alive()


@pytest.mark.parametrize("form", list(FORMS))
def test_a_callers_slice_is_bit_for_bit_what_it_gets_alone(form):
    fn = FORMS[form]()
    sched = SearchScheduler(fn, SchedulerCfg(max_wait_ms=2.0, max_batch_rows=16))
    n_callers, each = 8, 12
    want = {(c, j): answer(rows(1 + (c + j) % 5, 100 * c + j))
            for c in range(n_callers) for j in range(each)}
    got, errors = {}, []

    def caller(c):
        try:
            for j in range(each):
                got[c, j] = sched.submit("idx", rows(1 + (c + j) % 5, 100 * c + j), K)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=caller, args=(c,)) for c in range(n_callers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    sched.stop()
    assert not errors and len(got) == n_callers * each
    for key, (scores, ids) in want.items():
        np.testing.assert_array_equal(got[key][0], scores)
        np.testing.assert_array_equal(got[key][1], ids)
    # rows were merged, and every row was searched once
    assert len(fn.windows) < n_callers * each
    assert sum(fn.windows) == sum(s.shape[0] for s, _ in want.values())


def test_a_window_does_not_shrink_while_one_is_in_flight():
    """While a window is on its way to be collected the head waits, past
    ``max_wait_ms``, for as many rows as that window took; the last collect
    ends the wait and ``max_wait_ms`` applies again."""
    fn = TwoPhase(gated=True)
    sched = SearchScheduler(fn, SchedulerCfg(max_wait_ms=1.0, max_batch_rows=64))
    try:
        pair = [submit_async(sched, rows(4, i), eager=(i == 1)) for i in range(2)]
        until(lambda: fn.windows == [8], "the first two requests did not share a window")
        half = submit_async(sched, rows(4, 2))
        time.sleep(0.05)  # fifty times max_wait_ms
        assert fn.windows == [8], "a window of 4 rows was launched behind one of 8"
        other = submit_async(sched, rows(4, 3))
        until(lambda: fn.windows == [8, 8], "8 rows in the queue did not flush")
        lone = submit_async(sched, rows(4, 4))
        time.sleep(0.05)
        assert fn.windows == [8, 8]
        fn.gates[0].set()  # one still in flight: the lone request still waits
        assert pair[0][0].wait(10) and pair[1][0].wait(10)
        time.sleep(0.05)
        assert fn.windows == [8, 8]
        fn.gates[1].set()  # the last collect: now max_wait_ms rules
        until(lambda: fn.windows == [8, 8, 4], "the head waited on with nothing in flight")
        fn.gates[2].set()
        for done, out in (half, other, lone):
            assert done.wait(10) and out["error"] is None
    finally:
        fn.open_all()
        sched.stop()


def test_a_plain_search_fn_is_served_through_a_finished_handle():
    """No ``launch``: the whole search runs in the batcher's launch, one
    window at a time, and the completer splits what it finds finished."""
    inside, overlap = threading.Lock(), []

    def search_fn(index_id, q, k, return_embeddings):
        overlap.append(not inside.acquire(blocking=False))
        try:
            time.sleep(0.01)
            return answer(q, k)
        finally:
            if not overlap[-1]:
                inside.release()

    sched = SearchScheduler(search_fn, SchedulerCfg(max_wait_ms=0.0, max_batch_rows=1))
    try:
        calls = [submit_async(sched, rows(1, i)) for i in range(6)]
        for (done, out), seed in zip(calls, range(6)):
            assert done.wait(10) and out["error"] is None
            np.testing.assert_array_equal(out["result"][1], answer(rows(1, seed))[1])
        assert overlap == [False] * 6
    finally:
        sched.stop()
