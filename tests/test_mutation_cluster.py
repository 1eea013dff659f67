"""Mutation x distributed layer (fast, fake-stub tier, mirroring
tests/test_replication.py): quorum delete fan-out, repair-queue deletes,
upsert routing, the ADD-drain read failover satellite, and the server's
``mutation`` perf key."""

import random
import threading
import time
from collections import deque
from multiprocessing.pool import ThreadPool

import numpy as np
import pytest

from distributed_faiss_tpu.utils import tracing
from distributed_faiss_tpu.parallel import replication, rpc
from distributed_faiss_tpu.parallel.client import (
    IndexClient,
    QuorumError,
    REROUTE_LOG_LEN,
)
from distributed_faiss_tpu.parallel.replication import (
    MembershipTable,
    assign_groups,
)
from distributed_faiss_tpu.utils.config import IndexCfg, ReplicationCfg
from distributed_faiss_tpu.utils import lockdep
from distributed_faiss_tpu.utils.atomics import AtomicCounters

pytestmark = [pytest.mark.mutation, pytest.mark.replication]

DRAIN_TB = ("Traceback...\nRuntimeError: Server index is not trained. "
            "state: IndexState.ADD")


class FakeStub:
    """rpc.Client stand-in: scripted transport failures, per-call ack log,
    integer remove_ids results, optional per-fname application errors."""

    def __init__(self, sid, score=None, always_fail=False, removed=1,
                 app_errors=None, shard_group=None):
        self.id = sid
        self.host = "fake"
        self.port = 9000 + sid
        self.score = float(sid if score is None else score)
        self.always_fail = always_fail
        self.removed = removed
        self.app_errors = dict(app_errors or {})
        self.shard_group = shard_group
        self.acked = []

    def generic_fun(self, fname, args=(), kwargs=None, **_kw):
        if self.always_fail:
            raise ConnectionRefusedError(f"rank {self.id} down")
        if fname in self.app_errors:
            raise self.app_errors[fname]
        self.acked.append((fname, args))
        if fname == "remove_ids":
            return self.removed
        if fname == "search":
            _index_id, q, k, _emb = args
            nq = q.shape[0]
            scores = np.tile(self.score + np.arange(k, dtype=np.float32),
                             (nq, 1))
            meta = [[(self.id, j) for j in range(k)] for _ in range(nq)]
            return (scores, meta, None)
        if fname == "get_shard_group":
            return self.shard_group
        return f"ok-{self.id}"


def make_client(stubs, rcfg=None, groups=None):
    c = object.__new__(IndexClient)
    c.sub_indexes = stubs
    c.num_indexes = len(stubs)
    c.pool = ThreadPool(max(len(stubs), 1))
    c.cur_server_ids = {}
    c._rng = random.Random(0)
    c.retry = rpc.RetryPolicy(max_attempts=2, base_delay=0.001, jitter=0.0)
    c._stats_lock = lockdep.lock("IndexClient._stats_lock")
    c.reroutes = deque(maxlen=REROUTE_LOG_LEN)
    c.stats = tracing.LatencyStats()
    c.counters = AtomicCounters(
                  ("reroutes", "failovers", "under_replicated", "quorum_failures"))
    c.rcfg = rcfg or ReplicationCfg()
    eff = min(c.rcfg.replication, max(len(stubs), 1))
    c.quorum = replication.quorum_size(eff, min(c.rcfg.write_quorum, eff))
    c.repair_queue = replication.RepairQueue(c.rcfg.repair_queue_len)
    c._preferred = {}
    c.membership = MembershipTable(
        groups if groups is not None
        else assign_groups(len(stubs), c.rcfg.replication))
    c.cfg = IndexCfg(metric="l2")
    return c


# ----------------------------------------------------------- quorum deletes


def test_remove_ids_fans_to_every_replica_of_every_group():
    stubs = [FakeStub(i) for i in range(4)]  # R=2 -> groups {0:[0,2], 1:[1,3]}
    client = make_client(stubs, rcfg=ReplicationCfg(replication=2))
    removed = client.remove_ids("idx", [7, 8])
    # every replica of every group saw the delete exactly once
    for s in stubs:
        assert [f for f, _ in s.acked] == ["remove_ids"]
    # max per group, summed over groups
    assert removed == 2
    assert len(client.repair_queue) == 0


def test_remove_ids_quorum_records_missed_replica_and_repairs():
    """quorum=1, one replica dead: the delete ACKS, the dead replica lands
    in the repair queue as an op=remove_ids record; once it heals,
    repair_under_replicated re-sends the DELETE (not an add)."""
    a, b = FakeStub(0), FakeStub(1, always_fail=True)
    client = make_client(
        [a, b], rcfg=ReplicationCfg(replication=2, write_quorum=1),
        groups=[0, 0])
    assert client.remove_ids("idx", [1, 2, 3]) == 1
    assert client.counters["under_replicated"] == 1
    item = list(client.repair_queue._items)[0]
    assert item["op"] == "remove_ids" and item["ids"] == [1, 2, 3]
    assert item["missing"] == [1]

    b.always_fail = False
    out = client.repair_under_replicated()
    assert out == {"repaired": 1, "still_pending": 0}
    assert [f for f, _ in b.acked] == ["remove_ids"]
    assert b.acked[0][1] == ("idx", [1, 2, 3])


def test_remove_ids_below_quorum_raises_never_reroutes():
    """A whole dead group raises QuorumError AFTER the other groups were
    still attempted; the dead group's delete is recorded for repair and
    never re-sent to another group."""
    stubs = [FakeStub(0, always_fail=True), FakeStub(1),
             FakeStub(2, always_fail=True), FakeStub(3)]
    # groups: {0: [0, 2] both dead, 1: [1, 3] alive}
    client = make_client(stubs, rcfg=ReplicationCfg(replication=2),
                         groups=[0, 1, 0, 1])
    with pytest.raises(QuorumError) as exc:
        client.remove_ids("idx", [5])
    assert exc.value.group == 0
    # the LIVE group still processed the delete (deletes are per-group
    # data: no cross-group reroute could substitute)
    assert [f for f, _ in stubs[1].acked] == ["remove_ids"]
    assert [f for f, _ in stubs[3].acked] == ["remove_ids"]
    assert client.counters["quorum_failures"] == 1
    item = list(client.repair_queue._items)[0]
    assert item["op"] == "remove_ids" and set(item["missing"]) == {0, 2}


def test_remove_ids_application_error_propagates():
    err = rpc.ServerException("no tombstone support for this index kind")
    stubs = [FakeStub(0, app_errors={"remove_ids": err}), FakeStub(1)]
    client = make_client(stubs, groups=[0, 1])
    with pytest.raises(rpc.ServerException):
        client.remove_ids("idx", [1])


def test_upsert_deletes_everywhere_then_places_once():
    stubs = [FakeStub(i) for i in range(2)]
    client = make_client(stubs, groups=[0, 1])
    client.cur_server_ids["idx"] = 0
    emb = np.zeros((1, 8), np.float32)
    removed = client.upsert("idx", [9], emb)
    assert removed == 2  # both groups reported a tombstoned row
    # delete hit both; the add landed on exactly one group
    assert [f for f, _ in stubs[0].acked][0] == "remove_ids"
    adds = [s for s in stubs
            if any(f == "add_index_data" for f, _ in s.acked)]
    assert len(adds) == 1
    # default metadata carries the id at position 0
    fname, args = adds[0].acked[-1]
    assert args[2] == [(9,)]


def test_upsert_validates_shapes():
    client = make_client([FakeStub(0)], groups=[0])
    with pytest.raises(RuntimeError, match="match the batch size"):
        client.upsert("idx", [1, 2], np.zeros((1, 4), np.float32))


# ------------------------------------------- ADD-drain read failover (sat.)


def drain_exc():
    return rpc.ServerException(DRAIN_TB)


def test_drain_failover_eligibility_is_narrow():
    assert replication.drain_failover_eligible(drain_exc())
    assert not replication.drain_failover_eligible(
        rpc.ServerException("Server index is not trained. state: "
                            "IndexState.NOT_TRAINED"))
    assert not replication.drain_failover_eligible(
        RuntimeError(DRAIN_TB))  # only wire-level ServerException


def test_search_fails_over_past_draining_replica_and_pins():
    """The regression for the slow-draining victim: an R=2 group keeps
    serving while one replica is mid-ADD."""
    draining = FakeStub(0, app_errors={"search": drain_exc()})
    peer = FakeStub(1, score=1.0)
    client = make_client([draining, peer],
                         rcfg=ReplicationCfg(replication=2), groups=[0, 0])
    scores, meta = client.search(np.zeros((2, 4), np.float32), 3, "idx")
    assert meta[0][0] == (1, 0)  # served by the peer
    assert client.counters["failovers"] == 1
    assert client._preferred[0] == 1  # pinned for subsequent calls


def test_search_raises_when_whole_group_is_draining():
    stubs = [FakeStub(i, app_errors={"search": drain_exc()})
             for i in range(2)]
    client = make_client(stubs, rcfg=ReplicationCfg(replication=2),
                         groups=[0, 0])
    with pytest.raises(rpc.ServerException):
        client.search(np.zeros((1, 4), np.float32), 3, "idx")


def test_search_other_application_errors_never_fail_over():
    bad = FakeStub(0, app_errors={"search": rpc.ServerException("boom")})
    peer = FakeStub(1)
    client = make_client([bad, peer], rcfg=ReplicationCfg(replication=2),
                         groups=[0, 0])
    with pytest.raises(rpc.ServerException, match="boom"):
        client.search(np.zeros((1, 4), np.float32), 3, "idx")
    assert client.counters["failovers"] == 0


def test_partial_search_drain_failover():
    draining = FakeStub(0, app_errors={"search": drain_exc()})
    peer = FakeStub(1, score=1.0)
    client = make_client([draining, peer],
                         rcfg=ReplicationCfg(replication=2), groups=[0, 0])
    scores, meta, missing = client.search(
        np.zeros((1, 4), np.float32), 3, "idx", allow_partial=True)
    assert missing == []  # the group served; nothing degraded
    assert meta[0][0] == (1, 0)


# ----------------------------------------------------- server perf surface


def test_server_perf_stats_grows_mutation_key(tmp_path, monkeypatch):
    monkeypatch.setenv("DFT_COMPACT", "0")
    from distributed_faiss_tpu.parallel.server import IndexServer

    srv = IndexServer(0, str(tmp_path))
    cfg = IndexCfg(index_builder_type="flat", dim=8, metric="l2",
                   train_num=5)
    srv.create_index("m", cfg)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((40, 8)).astype(np.float32)
    srv.add_index_data("m", x, [(i,) for i in range(40)],
                       train_async_if_triggered=False)
    deadline = time.time() + 30
    while srv.get_ntotal("m") < 40:
        assert time.time() < deadline
        time.sleep(0.02)
    assert srv.remove_ids("m", [1, 2]) == 2
    stats = srv.get_perf_stats()
    mu = stats["mutation"]["m"]
    assert mu["tombstoned_rows"] == 2
    assert mu["live_fraction"] == pytest.approx(38 / 40)
    assert mu["compactions"] == 0
    assert srv.compact_index("m") is True
    assert srv.get_perf_stats()["mutation"]["m"]["compactions"] == 1
    srv.stop()


def test_upsert_without_cfg_requires_explicit_metadata():
    """A cfg-less client cannot know custom_meta_id_idx: synthesizing
    (id,) metadata could put the id in the wrong slot, creating rows no
    later remove_ids could ever match — it must raise instead."""
    client = make_client([FakeStub(0)], groups=[0])
    client.cfg = None
    with pytest.raises(RuntimeError, match="needs the client"):
        client.upsert("idx", [1], np.zeros((1, 4), np.float32))
    # explicit metadata keeps working without a cfg
    client.upsert("idx", [1], np.zeros((1, 4), np.float32),
                  metadata=[("doc", 1)])
