"""Fast unit tier for the self-healing write path: RetryPolicy backoff
math, MultiRankError contents, add_index_data rerouting, and broadcast
aggregation — all against in-process fake stubs (no sockets), so this
runs in tier-1; the live-cluster versions are in tests/test_chaos.py."""

import random
import threading
from collections import deque
from multiprocessing.dummy import Pool as ThreadPool

import numpy as np
import pytest

from distributed_faiss_tpu.utils import tracing
from distributed_faiss_tpu.parallel import replication, rpc
from distributed_faiss_tpu.parallel.client import (
    REROUTE_LOG_LEN,
    IndexClient,
    MultiRankError,
)
from distributed_faiss_tpu.utils.config import ReplicationCfg
from distributed_faiss_tpu.utils import lockdep
from distributed_faiss_tpu.utils.atomics import AtomicCounters


# ------------------------------------------------------------- RetryPolicy


def test_backoff_math_exact_without_jitter():
    p = rpc.RetryPolicy(max_attempts=5, base_delay=0.1, multiplier=2.0,
                        max_delay=10.0, jitter=0.0)
    assert p.delay(0) == pytest.approx(0.1)
    assert p.delay(1) == pytest.approx(0.2)
    assert p.delay(2) == pytest.approx(0.4)
    assert p.delay(3) == pytest.approx(0.8)


def test_backoff_caps_at_max_delay():
    p = rpc.RetryPolicy(base_delay=0.1, multiplier=10.0, max_delay=0.5,
                        jitter=0.0)
    assert p.delay(0) == pytest.approx(0.1)
    assert p.delay(1) == pytest.approx(0.5)  # 1.0 capped
    assert p.delay(7) == pytest.approx(0.5)


def test_backoff_jitter_bounds():
    p = rpc.RetryPolicy(base_delay=0.1, multiplier=2.0, max_delay=10.0,
                        jitter=0.5)
    for attempt in range(4):
        nominal = min(10.0, 0.1 * 2.0 ** attempt)
        for _ in range(50):
            d = p.delay(attempt)
            assert nominal * 0.5 <= d <= nominal * 1.5


def test_backoff_jitter_uses_private_rng():
    p = rpc.RetryPolicy(jitter=0.5)
    random.seed(99)
    state = random.getstate()
    for _ in range(20):
        p.delay(0)
    assert random.getstate() == state


def test_policy_validates_params():
    with pytest.raises(ValueError):
        rpc.RetryPolicy(max_attempts=0)
    with pytest.raises(ValueError):
        rpc.RetryPolicy(jitter=1.5)


def test_run_retries_transport_then_succeeds():
    p = rpc.RetryPolicy(max_attempts=3, base_delay=0.001, jitter=0.0)
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionResetError("boom")
        return "ok"

    assert p.run(flaky) == "ok"
    assert len(calls) == 3


def test_run_gives_up_after_max_attempts():
    p = rpc.RetryPolicy(max_attempts=3, base_delay=0.001, jitter=0.0)
    calls = []

    def dead():
        calls.append(1)
        raise EOFError("connection closed mid-frame")

    with pytest.raises(EOFError):
        p.run(dead)
    assert len(calls) == 3


@pytest.mark.parametrize("exc", [
    rpc.ServerException("remote traceback"),
    ValueError("bad argument"),
    RuntimeError("client to h:1 is closed"),
])
def test_run_does_not_retry_application_errors(exc):
    """Transport errors only: a live rank rejecting the request (or a local
    programming error) must propagate on the FIRST attempt."""
    p = rpc.RetryPolicy(max_attempts=5, base_delay=0.001, jitter=0.0)
    calls = []

    def app_error():
        calls.append(1)
        raise exc

    with pytest.raises(type(exc)):
        p.run(app_error)
    assert len(calls) == 1
    assert not p.is_retryable(exc)
    assert p.is_retryable(ConnectionRefusedError("down"))


def test_stream_corruption_is_retryable():
    """A garbled RESPONSE surfaces client-side as FrameError (bad magic) or
    UnpicklingError; generic_fun has already dropped the connection, so the
    write path must treat both as transport and retry on a clean redial."""
    import pickle

    p = rpc.RetryPolicy()
    assert p.is_retryable(rpc.FrameError("bad frame magic b'xxxx'"))
    assert p.is_retryable(pickle.UnpicklingError("corrupt skeleton"))
    # plain RuntimeError (e.g. "client is closed") stays non-retryable
    assert not p.is_retryable(RuntimeError("client to h:1 is closed"))


# ----------------------------------------------------------- fake cluster


class FakeStub:
    """Quacks like rpc.Client for the fan-out helpers: scripted per-call
    behaviors, records every (fname, args) it acks."""

    def __init__(self, sid, behaviors=None):
        self.id = sid
        self.host = "fake"
        self.port = 9000 + sid
        self.behaviors = list(behaviors or [])  # exceptions to raise, in order
        self.acked = []

    def generic_fun(self, fname, args=(), kwargs=None, timeout=None):
        if self.behaviors:
            b = self.behaviors.pop(0)
            if isinstance(b, BaseException):
                raise b
        self.acked.append((fname, args))
        return f"ok-{self.id}"


def make_client(stubs, retry=None, replication_cfg=None):
    c = object.__new__(IndexClient)
    c.sub_indexes = stubs
    c.num_indexes = len(stubs)
    c.pool = ThreadPool(len(stubs))
    c.cur_server_ids = {}
    c._rng = random.Random(0)
    c.retry = retry or rpc.RetryPolicy(max_attempts=2, base_delay=0.001,
                                       jitter=0.0)
    c._stats_lock = lockdep.lock("IndexClient._stats_lock")
    c.reroutes = deque(maxlen=REROUTE_LOG_LEN)
    c.stats = tracing.LatencyStats()
    c.counters = AtomicCounters(
                  ("reroutes", "failovers", "under_replicated", "quorum_failures"))
    c.rcfg = replication_cfg or ReplicationCfg()
    eff = min(c.rcfg.replication, max(len(stubs), 1))
    c.quorum = replication.quorum_size(eff, min(c.rcfg.write_quorum, eff))
    c.repair_queue = replication.RepairQueue(c.rcfg.repair_queue_len)
    c._preferred = {}
    c.membership = replication.MembershipTable(
        replication.assign_groups(len(stubs), c.rcfg.replication))
    c.cfg = None
    return c


def test_add_index_data_reroutes_to_next_live_rank():
    dead = FakeStub(0, behaviors=[ConnectionRefusedError("down")] * 10)
    live = FakeStub(1)
    client = make_client([dead, live])
    client.cur_server_ids["idx"] = 0  # force first placement on the dead rank

    emb = np.zeros((4, 8), np.float32)
    client.add_index_data("idx", emb, [1, 2, 3, 4])

    assert len(live.acked) == 1  # the batch landed exactly once, on rank 1
    assert live.acked[0][0] == "add_index_data"
    assert len(client.reroutes) == 1
    skip = client.reroutes[0]
    assert skip["skipped_server"] == 0 and skip["index_id"] == "idx"
    assert skip["rerouted_to"] == 1
    # round-robin resumes AFTER the rank that actually acked
    assert client.cur_server_ids["idx"] == 0


def test_add_index_data_transient_failure_retries_same_rank():
    flaky = FakeStub(0, behaviors=[ConnectionResetError("blip")])
    other = FakeStub(1)
    client = make_client([flaky, other])
    client.cur_server_ids["idx"] = 0

    client.add_index_data("idx", np.zeros((2, 8), np.float32), [1, 2])
    assert len(flaky.acked) == 1  # retry healed in place: no reroute
    assert list(client.reroutes) == []
    assert client.cur_server_ids["idx"] == 1


def test_add_index_data_raises_when_every_rank_dead():
    stubs = [FakeStub(i, behaviors=[OSError("down")] * 10) for i in range(3)]
    client = make_client(stubs)
    with pytest.raises(RuntimeError, match="every rank"):
        client.add_index_data("idx", np.zeros((1, 8), np.float32), [1])
    assert len(client.reroutes) == 3  # every skip recorded


def test_add_index_data_application_error_propagates():
    """A live rank REJECTING the batch (index not created, bad args) must
    raise immediately — rerouting it would hide a misconfigured shard."""
    rejecting = FakeStub(0, behaviors=[rpc.ServerException("no such index")])
    other = FakeStub(1)
    client = make_client([rejecting, other])
    client.cur_server_ids["idx"] = 0
    with pytest.raises(rpc.ServerException):
        client.add_index_data("idx", np.zeros((1, 8), np.float32), [1])
    assert other.acked == [] and list(client.reroutes) == []


def test_broadcast_success_returns_rank_ordered_results():
    client = make_client([FakeStub(0), FakeStub(1), FakeStub(2)])
    assert client._broadcast("save_index", ("idx",)) == ["ok-0", "ok-1", "ok-2"]


def test_broadcast_collects_every_rank_outcome():
    """One dead rank + one rejecting rank: the op still runs everywhere
    else, and MultiRankError carries all three outcomes."""
    ok = FakeStub(0)
    dead = FakeStub(1, behaviors=[ConnectionRefusedError("down")] * 10)
    reject = FakeStub(2, behaviors=[rpc.ServerException("not trained")])
    client = make_client([ok, dead, reject])

    with pytest.raises(MultiRankError) as ei:
        client._broadcast("sync_train", ("idx",))
    err = ei.value
    assert err.op == "sync_train"
    assert len(err.outcomes) == 3
    assert [o["ok"] for o in err.outcomes] == [True, False, False]
    assert err.results == ["ok-0"]
    assert [o["server"] for o in err.failures] == [1, 2]
    assert "ConnectionRefusedError" in err.failures[0]["error"]
    assert isinstance(err.failures[1]["exception"], rpc.ServerException)
    # the healthy rank DID run the op (no first-error abort)
    assert ok.acked == [("sync_train", ("idx",))]
    # operator-facing message names every failing rank with host:port
    msg = str(err)
    assert "rank 1 (fake:9001)" in msg and "rank 2 (fake:9002)" in msg


def test_broadcast_retry_heals_transient_rank():
    flaky = FakeStub(0, behaviors=[ConnectionResetError("blip")])
    client = make_client([flaky, FakeStub(1)],
                         retry=rpc.RetryPolicy(max_attempts=3,
                                               base_delay=0.001, jitter=0.0))
    assert client._broadcast("set_nprobe", ("idx", 8)) == ["ok-0", "ok-1"]


def test_broadcast_is_thread_safe_under_concurrent_ops():
    stubs = [FakeStub(i) for i in range(4)]
    client = make_client(stubs)
    errors = []

    def worker():
        try:
            for _ in range(10):
                client._broadcast("save_index", ("idx",))
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert all(len(s.acked) == 40 for s in stubs)
