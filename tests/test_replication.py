"""Fast unit tier for shard replication (ISSUE 8): quorum math, group
assignment, membership table, read-fanout planning, quorum writes with
under-replication + repair, and read failover ordering — all against
in-process fake stubs (mirroring tests/test_retry.py), so it runs in
tier-1 AND under the dedicated ``replication`` CI job. The live-cluster
SIGKILL-under-storm acceptance gate is in tests/test_replication_chaos.py.
"""

import random
import threading
from multiprocessing.dummy import Pool as ThreadPool

import numpy as np
import pytest

from distributed_faiss_tpu.utils import tracing
from distributed_faiss_tpu.parallel import replication, rpc
from distributed_faiss_tpu.parallel.client import (
    REROUTE_LOG_LEN,
    IndexClient,
    QuorumError,
)
from distributed_faiss_tpu.parallel.replication import (
    MembershipTable,
    RepairQueue,
    assign_groups,
    plan_read_fanout,
    quorum_size,
)
from distributed_faiss_tpu.utils import lockdep
from distributed_faiss_tpu.utils.atomics import AtomicCounters
from distributed_faiss_tpu.utils.config import IndexCfg, ReplicationCfg

pytestmark = pytest.mark.replication


# ------------------------------------------------------------- quorum math


def test_quorum_majority_default():
    assert quorum_size(1) == 1
    assert quorum_size(2) == 2
    assert quorum_size(3) == 2
    assert quorum_size(4) == 3
    assert quorum_size(5) == 3


def test_quorum_explicit_overrides_majority():
    assert quorum_size(3, 1) == 1
    assert quorum_size(3, 3) == 3


def test_quorum_validates():
    with pytest.raises(ValueError):
        quorum_size(0)
    with pytest.raises(ValueError):
        quorum_size(2, 3)


def test_replication_cfg_env_and_validation():
    cfg = ReplicationCfg.from_env({"DFT_REPLICATION": "2",
                                   "DFT_WRITE_QUORUM": "1"})
    assert cfg.replication == 2 and cfg.write_quorum == 1
    assert ReplicationCfg().replication == 1  # default: pre-replication
    with pytest.raises(ValueError):
        ReplicationCfg(replication=0)
    with pytest.raises(ValueError):
        ReplicationCfg(replication=2, write_quorum=3)
    with pytest.raises(TypeError):
        ReplicationCfg(bogus=1)


# ------------------------------------------------------- group assignment


def test_assign_groups_striping():
    assert assign_groups(4, 1) == [0, 1, 2, 3]     # R=1: one group per rank
    assert assign_groups(4, 2) == [0, 1, 0, 1]     # modular striping
    assert assign_groups(6, 3) == [0, 1, 0, 1, 0, 1]
    # remainder ranks land as extra replicas, never an under-replicated tail
    assert assign_groups(5, 2) == [0, 1, 0, 1, 0]


def test_assign_groups_clamps_oversized_factor():
    assert assign_groups(2, 5) == [0, 0]  # R > N: everyone replicates one shard


def test_membership_register_remove_and_snapshot():
    t = MembershipTable([0, 1, 0, 1])
    assert t.groups() == [0, 1]
    assert t.replicas(0) == [0, 2] and t.replicas(1) == [1, 3]
    assert t.group_of(3) == 1
    t.remove(2)
    assert t.replicas(0) == [0]
    t.register(2, 1)  # online join into the OTHER group
    assert t.replicas(1) == [1, 3, 2] and t.group_of(2) == 1
    t.register(2, 1)  # idempotent
    assert t.replicas(1) == [1, 3, 2]
    snap = t.snapshot()
    snap[0].append(99)  # snapshot is a copy
    assert t.replicas(0) == [0]


def test_plan_read_fanout_pins_and_rotates():
    t = MembershipTable([0, 1, 0, 1])
    plan = plan_read_fanout(t, {})
    assert plan == [(0, 0, [0, 2]), (1, 1, [1, 3])]
    # a pinned replica leads its group's failover ordering
    plan = plan_read_fanout(t, {0: 2})
    assert plan == [(0, 2, [2, 0]), (1, 1, [1, 3])]
    # a stale pin (position left the group) falls back to the head
    t.remove(2)
    plan = plan_read_fanout(t, {0: 2})
    assert plan == [(0, 0, [0]), (1, 1, [1, 3])]


def test_repair_queue_bounded_with_counters():
    q = RepairQueue(maxlen=3)
    for i in range(5):
        q.record({"batch": i})
    assert len(q) == 3
    s = q.stats()
    assert s["recorded"] == 5 and s["dropped"] == 2 and s["pending"] == 3
    items = q.drain()
    assert [it["batch"] for it in items] == [2, 3, 4]  # oldest dropped
    assert len(q) == 0
    q.mark_repaired(2)
    assert q.stats()["repaired"] == 2


# ----------------------------------------------------------- fake cluster


class FakeStub:
    """Quacks like rpc.Client for the replicated fan-out: scripted
    failures, per-call log, shard-group registration, and deterministic
    search results (score base = ``score``)."""

    def __init__(self, sid, score=None, always_fail=False, fail_first=0,
                 shard_group=None):
        self.id = sid
        self.host = "fake"
        self.port = 9000 + sid
        self.score = float(sid if score is None else score)
        self.always_fail = always_fail
        self.fail_first = fail_first
        self.shard_group = shard_group
        self.attempts = 0
        self.acked = []  # (fname, args) for every call that succeeded

    def generic_fun(self, fname, args=(), kwargs=None, **_kw):
        self.attempts += 1
        if self.always_fail:
            raise ConnectionRefusedError(f"rank {self.id} down")
        if self.fail_first > 0:
            self.fail_first -= 1
            raise ConnectionResetError(f"rank {self.id} blip")
        self.acked.append((fname, args))
        if fname == "search":
            _index_id, q, k, _emb = args
            nq = q.shape[0]
            d = self.score + np.arange(k, dtype=np.float32)
            scores = np.tile(d, (nq, 1))
            meta = [[(self.id, j) for j in range(k)] for _ in range(nq)]
            return (scores, meta, None)
        if fname == "get_shard_group":
            return self.shard_group
        if fname == "set_shard_group":
            self.shard_group = args[0]
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
    from collections import deque
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
    c.cfg = None
    return c


# ------------------------------------------------------------ quorum writes


def test_write_fans_out_to_every_replica_and_acks_on_full_quorum():
    a, b = FakeStub(0), FakeStub(1)
    client = make_client([a, b], rcfg=ReplicationCfg(replication=2))
    assert client.quorum == 2  # majority of 2
    client.cur_server_ids["idx"] = 0

    emb = np.zeros((4, 8), np.float32)
    client.add_index_data("idx", emb, [1, 2, 3, 4])

    # BOTH replicas got the batch, nothing under-replicated
    assert [f for f, _ in a.acked] == ["add_index_data"]
    assert [f for f, _ in b.acked] == ["add_index_data"]
    assert len(client.repair_queue) == 0
    assert client.counters.snapshot() == {
        "reroutes": 0, "failovers": 0,
        "under_replicated": 0, "quorum_failures": 0}


def test_write_quorum_reached_records_missed_replica_for_repair():
    """quorum=1, one replica dead: the write ACKS (the live replica has
    it) and the dead replica lands in the repair queue; once it heals,
    repair_under_replicated() re-sends and drains the queue."""
    live = FakeStub(0)
    dead = FakeStub(1, always_fail=True)
    client = make_client([live, dead],
                         rcfg=ReplicationCfg(replication=2, write_quorum=1))
    client.cur_server_ids["idx"] = 0

    client.add_index_data("idx", np.zeros((2, 8), np.float32), [1, 2])
    assert len(live.acked) == 1
    assert len(client.repair_queue) == 1
    assert client.counters["under_replicated"] == 1
    assert list(client.reroutes) == []  # quorum met: no reroute

    # still dead: repair keeps it queued
    out = client.repair_under_replicated()
    assert out == {"repaired": 0, "still_pending": 1}
    assert len(client.repair_queue) == 1

    dead.always_fail = False  # rank restarted
    out = client.repair_under_replicated()
    assert out == {"repaired": 1, "still_pending": 0}
    assert len(client.repair_queue) == 0
    assert [f for f, _ in dead.acked] == ["add_index_data"]
    assert client.repair_queue.stats()["repaired"] == 1


def test_write_below_quorum_with_partial_ack_raises_quorum_error():
    """Majority quorum of R=2 is 2: one dead replica means a PARTIAL
    placement — the batch must NOT reroute to another group (that would
    duplicate the minority replica's rows across shards) and must not
    report success."""
    live = FakeStub(0)
    dead = FakeStub(1, always_fail=True)
    client = make_client([live, dead], rcfg=ReplicationCfg(replication=2))
    client.cur_server_ids["idx"] = 0

    with pytest.raises(QuorumError) as ei:
        client.add_index_data("idx", np.zeros((1, 8), np.float32), [1])
    assert ei.value.acked == [0] and ei.value.needed == 2
    assert client.counters["quorum_failures"] == 1
    assert len(client.repair_queue) == 1  # partial placement recorded
    assert list(client.reroutes) == []    # never rerouted


def test_write_reroutes_to_next_group_when_whole_group_dead():
    # 4 ranks, R=2: groups {0: [0, 2], 1: [1, 3]}; group 0 fully dead
    stubs = [FakeStub(0, always_fail=True), FakeStub(1),
             FakeStub(2, always_fail=True), FakeStub(3)]
    client = make_client(stubs, rcfg=ReplicationCfg(replication=2))
    client.cur_server_ids["idx"] = 0

    client.add_index_data("idx", np.zeros((2, 8), np.float32), [1, 2])
    # the batch landed on BOTH replicas of the next group
    assert len(stubs[1].acked) == 1 and len(stubs[3].acked) == 1
    # one reroute record per dead replica skipped, pointing at group 1
    assert {r["skipped_server"] for r in client.reroutes} == {0, 2}
    assert all(r["rerouted_to"] == 1 for r in client.reroutes)
    assert client.counters["reroutes"] == 2
    assert len(client.repair_queue) == 0  # nothing acked in the dead group


def test_write_quorum_clamps_to_shrunken_group():
    """After mark_rank_left shrinks a group to one replica, writes must
    keep acking on that replica — a majority-of-R quorum demanding acks
    from replicas that no longer exist would fail the shard forever."""
    a, b = FakeStub(0), FakeStub(1)
    client = make_client([a, b], rcfg=ReplicationCfg(replication=2))
    assert client.quorum == 2
    client.mark_rank_left(1)
    client.cur_server_ids["idx"] = 0
    client.add_index_data("idx", np.zeros((1, 8), np.float32), [1])
    assert len(a.acked) == 1 and b.acked == []
    assert client.counters["quorum_failures"] == 0
    assert len(client.repair_queue) == 0


def test_write_raises_when_every_group_dead():
    stubs = [FakeStub(i, always_fail=True) for i in range(4)]
    client = make_client(stubs, rcfg=ReplicationCfg(replication=2))
    with pytest.raises(RuntimeError, match="every rank"):
        client.add_index_data("idx", np.zeros((1, 8), np.float32), [1])
    assert client.counters["reroutes"] == 4  # every replica skip recorded


def test_reroute_ring_is_bounded_but_counters_are_not():
    live = FakeStub(1)
    dead = FakeStub(0, always_fail=True)
    client = make_client([dead, live])  # R=1: two single-rank groups
    n = REROUTE_LOG_LEN + 7
    for i in range(n):
        client.cur_server_ids["idx"] = 0  # always place on the dead rank
        client.add_index_data("idx", np.zeros((1, 4), np.float32), [i])
    assert len(client.reroutes) == REROUTE_LOG_LEN  # ring capped
    assert client.counters["reroutes"] == n         # totals keep counting
    assert len(live.acked) == n                     # every batch still acked


# -------------------------------------------------------- read failover


def search_client(stubs, **kw):
    c = make_client(stubs, **kw)
    c.cfg = IndexCfg(metric="l2", dim=8)
    return c


def test_search_reads_one_replica_per_group_never_double_counts():
    """Two replicas of one shard (identical corpus): exactly one serves
    the read, so its rows appear ONCE in the merge — the old all-ranks
    fan-out would have returned each row twice."""
    a = FakeStub(0, score=0.0)
    b = FakeStub(1, score=0.0)
    client = search_client([a, b], rcfg=ReplicationCfg(replication=2))

    scores, meta = client.search(np.zeros((2, 8), np.float32), 4, "idx")
    searched = [s for s in (a, b)
                if any(f == "search" for f, _ in s.acked)]
    assert len(searched) == 1  # one replica per group
    # top-4 of one block [0,1,2,3] — duplicated replicas would give [0,0,1,1]
    assert scores[0].tolist() == [0.0, 1.0, 2.0, 3.0]
    assert [m[1] for m in meta[0]] == [0, 1, 2, 3]


def test_search_failover_skips_dead_replica_and_pins_next():
    dead = FakeStub(0, always_fail=True)
    live = FakeStub(1, score=5.0)
    client = search_client([dead, live], rcfg=ReplicationCfg(replication=2))

    scores, meta = client.search(np.zeros((1, 8), np.float32), 3, "idx")
    assert scores[0].tolist() == [5.0, 6.0, 7.0]
    assert meta[0][0][0] == 1  # served by the survivor
    assert client.counters["failovers"] == 1
    assert dead.attempts == 1

    # the survivor is PINNED: the dead replica is not even dialed again
    client.search(np.zeros((1, 8), np.float32), 3, "idx")
    assert dead.attempts == 1
    assert client.counters["failovers"] == 1  # no second failover


def test_search_failover_merges_across_groups_deterministically():
    # groups {0: [0, 2], 1: [1, 3]}; group 0's preferred replica is dead
    stubs = [FakeStub(0, score=0.0, always_fail=True),
             FakeStub(1, score=10.0),
             FakeStub(2, score=0.0),
             FakeStub(3, score=10.0)]
    client = search_client(stubs, rcfg=ReplicationCfg(replication=2))
    scores, meta = client.search(np.zeros((1, 8), np.float32), 4, "idx")
    # group 0 served by replica 2 (same shard content as 0): merged top-4
    # is group 0's block, identical to what a healthy cluster returns
    assert scores[0].tolist() == [0.0, 1.0, 2.0, 3.0]
    assert all(m[0] == 2 for m in meta[0])


def test_search_partial_reports_group_only_when_every_replica_dead():
    stubs = [FakeStub(0, always_fail=True), FakeStub(1, score=1.0),
             FakeStub(2, always_fail=True), FakeStub(3, score=7.0)]
    # groups {0: [0, 2], 1: [1, 3]} — group 0 fully dead, group 1 healthy
    client = search_client(stubs, rcfg=ReplicationCfg(replication=2))
    scores, meta, missing = client.search(
        np.zeros((1, 8), np.float32), 2, "idx", allow_partial=True)
    assert scores[0].tolist() == [1.0, 2.0]
    assert {m["server"] for m in missing} == {0, 2}  # every replica tried

    # strict mode: a shard with no live replica raises
    with pytest.raises(rpc.TRANSPORT_ERRORS):
        client.search(np.zeros((1, 8), np.float32), 2, "idx")


def test_search_application_error_propagates_without_failover():
    class RejectingStub(FakeStub):
        def generic_fun(self, fname, args=(), kwargs=None, **_kw):
            self.attempts += 1
            if fname == "search":
                raise rpc.ServerException("index not trained")
            return super().generic_fun(fname, args, kwargs, **_kw)

    rejecting = RejectingStub(0)
    other = FakeStub(1, score=1.0)
    client = search_client([rejecting, other],
                           rcfg=ReplicationCfg(replication=2))
    with pytest.raises(rpc.ServerException):
        client.search(np.zeros((1, 8), np.float32), 2, "idx")
    # a live rank REJECTING the request must not look like a dead one
    assert not any(f == "search" for f, _ in other.acked)


def test_get_ntotal_counts_groups_once_and_survives_dead_replica():
    class CountStub(FakeStub):
        def __init__(self, sid, ntotal, **kw):
            super().__init__(sid, **kw)
            self._ntotal = ntotal

        def generic_fun(self, fname, args=(), kwargs=None, **_kw):
            if fname == "get_ntotal" and not self.always_fail:
                self.attempts += 1
                return self._ntotal
            return super().generic_fun(fname, args, kwargs, **_kw)

    # groups {0: [0, 2], 1: [1, 3]}; replica 0 dead, 2 mid-repair (fewer
    # rows than its dead peer would have had); group 1 converged
    stubs = [CountStub(0, 100, always_fail=True), CountStub(1, 40),
             CountStub(2, 90), CountStub(3, 40)]
    client = make_client(stubs, rcfg=ReplicationCfg(replication=2))
    # per-group max over LIVE replicas, summed: 90 + 40 — rows never
    # counted once per replica, and a dead replica degrades to its peer
    assert client.get_ntotal("idx") == 130

    stubs[2].always_fail = True  # whole group dark -> the error surfaces
    with pytest.raises(rpc.TRANSPORT_ERRORS):
        client.get_ntotal("idx")


def test_retired_engine_never_autosaves_again(tmp_path):
    """A superseded engine (shard-transfer install, drop_index) must stop
    persisting: its save watcher exits and _maybe_save no-ops, so stale
    state can never land as the newest generation over the replacement's
    storage dir."""
    from distributed_faiss_tpu.engine import Index
    from distributed_faiss_tpu.utils import serialization

    storage = str(tmp_path / "shard")
    cfg = IndexCfg(index_builder_type="flat", dim=8, metric="l2",
                   train_num=10, index_storage_dir=storage)
    idx = Index(cfg)
    rng = np.random.default_rng(0)
    idx.add_batch(rng.standard_normal((20, 8)).astype(np.float32),
                  [(i,) for i in range(20)], train_async_if_triggered=False)
    import time

    from distributed_faiss_tpu.utils.state import IndexState
    deadline = time.time() + 30
    # wait for the ADD->TRAINED flip too (the drain worker zeroes the
    # count first, and save() during ADD defers and returns None)
    while (idx.get_idx_data_num()[0] > 0
           or idx.get_state() != IndexState.TRAINED):
        assert time.time() < deadline
        time.sleep(0.02)
    assert idx.save()
    gens = serialization.list_generations(storage)
    idx.retire()
    # more rows arrive at the stale instance; save must now refuse
    idx.add_batch(rng.standard_normal((20, 8)).astype(np.float32),
                  [(20 + i,) for i in range(20)],
                  train_async_if_triggered=False)
    deadline = time.time() + 30
    while idx.get_idx_data_num()[0] > 0:
        assert time.time() < deadline
        time.sleep(0.02)
    assert not idx.save()
    assert serialization.list_generations(storage) == gens


# ----------------------------------------------- membership from the wire


def test_build_membership_honors_registered_groups_with_fallback():
    stubs = [FakeStub(0, shard_group=1), FakeStub(1, shard_group=0),
             FakeStub(2), FakeStub(3, always_fail=True)]
    client = make_client(stubs, rcfg=ReplicationCfg(replication=2))
    table = client._build_membership()
    # explicit registrations win; silent/dead ranks get derived striping
    # (derived for 4 ranks @ R=2 is [0, 1, 0, 1])
    assert table.group_of(0) == 1 and table.group_of(1) == 0
    assert table.group_of(2) == 0 and table.group_of(3) == 1


def test_register_groups_pushes_assignments():
    stubs = [FakeStub(0), FakeStub(1)]
    client = make_client(stubs, rcfg=ReplicationCfg(replication=2))
    client._register_groups()
    assert stubs[0].shard_group == 0 and stubs[1].shard_group == 0


def test_replication_stats_surface():
    client = make_client([FakeStub(0), FakeStub(1)],
                         rcfg=ReplicationCfg(replication=2, write_quorum=1))
    stats = client.get_replication_stats()
    assert stats["replication"] == 2 and stats["quorum"] == 1
    assert stats["groups"] == {0: [0, 1]}
    assert stats["counters"]["reroutes"] == 0
    assert stats["repair"]["pending"] == 0


# ------------------------------------------- shard transfer over the wire


def test_shard_transfer_over_the_wire(tmp_path):
    """End-to-end online join on loopback: rank B (empty) streams rank
    A's shard via the new KIND_SHARD_FETCH/KIND_SHARD_DATA frames
    (server.sync_shard_from -> rpc.Client.fetch_shard), commits it as a
    MANIFEST generation in ITS OWN storage dir, registers the group, and
    serves byte-identical results."""
    import socket
    import time

    from distributed_faiss_tpu.parallel.server import IndexServer
    from distributed_faiss_tpu.utils import serialization
    from distributed_faiss_tpu.utils.state import IndexState

    def free_port():
        s = socket.socket()
        s.bind(("", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    pa = free_port()
    a = IndexServer(0, str(tmp_path / "a"))
    b = IndexServer(1, str(tmp_path / "b"))
    threading.Thread(target=a.start_blocking, args=(pa,), daemon=True).start()
    time.sleep(0.3)
    try:
        cfg = IndexCfg(index_builder_type="flat", dim=16, metric="l2",
                       train_num=20)
        a.create_index("t", cfg)
        rng = np.random.default_rng(0)
        x = rng.standard_normal((60, 16)).astype(np.float32)
        a.add_index_data("t", x, [("m", i) for i in range(60)])
        deadline = time.time() + 60
        while not (a.get_state("t") == IndexState.TRAINED
                   and a.get_aggregated_ntotal("t") == 0):
            assert time.time() < deadline, "source shard never drained"
            time.sleep(0.05)

        # a fetch for a missing index degrades to a structured error
        probe = rpc.Client(9, "localhost", pa, mux=False)
        with pytest.raises(rpc.ServerException):
            probe.fetch_shard("no-such-index")
        probe.close()

        out = b.sync_shard_from("t", "localhost", pa, shard_group=3)
        assert out["ntotal"] == 60 and out["buffered"] == 0
        assert b.get_shard_group() == 3

        sa = a.search("t", x[:5], 4)
        sb = b.search("t", x[:5], 4)
        np.testing.assert_array_equal(sa[0], sb[0])
        assert sa[1] == sb[1]

        # the transferred shard is durably committed on B's disk: a crash
        # right after the join restarts from this generation
        gens = serialization.list_generations(
            str(tmp_path / "b" / "t" / "1"))
        assert gens, "transfer was not committed as a MANIFEST generation"
    finally:
        a.stop()
        b.stop()


def test_repair_resend_is_versioned_noop_on_healed_replica(tmp_path):
    """ISSUE 12 satellite regression: ``repair_under_replicated()``
    re-sends used to DOUBLE-APPLY on a replica that already healed via
    anti-entropy. With per-id versions the re-send carries the batch's
    original stamp and the healed replica's LWW add gate no-ops it —
    over a real loopback server, ntotal and the digest stay put and the
    engine counts the no-op."""
    import socket
    import time

    from distributed_faiss_tpu.parallel.server import IndexServer
    from distributed_faiss_tpu.utils.config import VersioningCfg
    from distributed_faiss_tpu.utils.state import IndexState

    def free_port():
        s = socket.socket()
        s.bind(("", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    port = free_port()
    srv = IndexServer(0, str(tmp_path / "a"))
    threading.Thread(target=srv.start_blocking, args=(port,),
                     daemon=True).start()
    time.sleep(0.3)
    stub = rpc.Client(0, "localhost", port)
    client = make_client([stub])
    client.vcfg = VersioningCfg()
    from distributed_faiss_tpu.mutation.versions import HLC

    client._hlc = HLC(writer_id=5)
    client._seeded = {"t"}
    client._last_write_version = {}
    client._unversioned_ranks = set()
    try:
        cfg = IndexCfg(index_builder_type="flat", dim=8, metric="l2",
                       train_num=10)
        stub.generic_fun("create_index", ("t", cfg))
        x = np.random.default_rng(0).standard_normal((30, 8)).astype(
            np.float32)
        meta = [(i,) for i in range(30)]
        client.cur_server_ids["t"] = 0
        client.add_index_data("t", x, meta)
        deadline = time.time() + 60
        while not (stub.generic_fun("get_state", ("t",))
                   == IndexState.TRAINED
                   and stub.generic_fun("get_aggregated_ntotal",
                                        ("t",)) == 0):
            assert time.time() < deadline
            time.sleep(0.05)
        assert stub.generic_fun("get_ntotal", ("t",)) == 30
        # fabricate the outage's repair record: the SAME batch, the SAME
        # version, re-sent to a replica that (here: trivially) already
        # holds it — the pre-version behavior appended 30 duplicate rows
        client.repair_queue.record({
            "op": "add", "index_id": "t", "group": 0, "missing": [0],
            "failures": [], "embeddings": x, "metadata": meta,
            "version": client.last_write_version("t"),
        })
        out = client.repair_under_replicated()
        assert out == {"repaired": 1, "still_pending": 0}
        time.sleep(0.3)
        assert stub.generic_fun("get_ntotal", ("t",)) == 30
        assert stub.generic_fun("get_aggregated_ntotal", ("t",)) == 0
        mut = stub.generic_fun("get_perf_stats")["mutation"]["t"]
        assert mut["version_noop_adds"] == 30, mut
    finally:
        stub.close()
        srv.stop()


def test_mark_rank_left_removes_from_rotation():
    a, b = FakeStub(0, score=3.0), FakeStub(1, score=3.0)
    client = search_client([a, b], rcfg=ReplicationCfg(replication=2))
    client.search(np.zeros((1, 8), np.float32), 2, "idx")
    client.mark_rank_left(0)
    client.search(np.zeros((1, 8), np.float32), 2, "idx")
    # after the leave, only the remaining replica serves
    assert any(f == "search" for f, _ in b.acked)
    assert client.membership.replicas(0) == [1]
