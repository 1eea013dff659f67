"""Fast unit tier for server-side anti-entropy (ISSUE 10): replica
digests (order-independence, caching, delete sensitivity), the health
table / failure detector, compaction-lease leader math, suspect pre-skip
in the read plan, repair-queue overflow (drop warning + degraded flag +
sweep coverage), the opt-in periodic repair driver, and loopback
sweep-heal end-to-end (delta pull, full sync, delete reconciliation) —
plus the ChaosProxy drop-kind fault pinning that a suspect-marked peer
still serves direct reads. The live-cluster repair-queue-overflow
convergence gate is in tests/test_antientropy_chaos.py."""

import os
import random
import socket
import threading
import time
from collections import deque
from multiprocessing.dummy import Pool as ThreadPool

import numpy as np
import pytest

from distributed_faiss_tpu.utils import tracing
from distributed_faiss_tpu.engine import Index
from distributed_faiss_tpu.mutation.tombstones import TombstoneSet, id_match_key
from distributed_faiss_tpu.parallel import antientropy, replication, rpc
from distributed_faiss_tpu.parallel.antientropy import (
    AntiEntropySweeper,
    HealthTable,
    digests_match,
    read_peers,
)
from distributed_faiss_tpu.parallel.client import REROUTE_LOG_LEN, IndexClient
from distributed_faiss_tpu.parallel.replication import (
    MembershipTable,
    RepairQueue,
    assign_groups,
    plan_read_fanout,
)
from distributed_faiss_tpu.parallel.server import IndexServer
from distributed_faiss_tpu.testing.chaos import ChaosProxy, Fault
from distributed_faiss_tpu.utils.config import (
    AntiEntropyCfg,
    IndexCfg,
    ReplicationCfg,
)
from distributed_faiss_tpu.utils.state import IndexState
from distributed_faiss_tpu.utils import lockdep, racecheck
from distributed_faiss_tpu.utils.atomics import AtomicCounters

pytestmark = pytest.mark.antientropy

DIM = 8


def free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def flat_cfg(**kw):
    kw.setdefault("index_builder_type", "flat")
    kw.setdefault("dim", DIM)
    kw.setdefault("metric", "l2")
    kw.setdefault("train_num", 10)
    return IndexCfg(**kw)


def wait_for(cond, timeout=30.0, msg="condition never held"):
    deadline = time.time() + timeout
    while not cond():
        assert time.time() < deadline, msg
        time.sleep(0.02)


def drained(engine):
    return engine.get_idx_data_num()[0] == 0


# ---------------------------------------------------------------- config


def test_antientropy_cfg_env_and_validation():
    cfg = AntiEntropyCfg.from_env({"DFT_ANTIENTROPY": "0",
                                   "DFT_ANTIENTROPY_INTERVAL": "7.5",
                                   "DFT_SUSPECT_AFTER": "5",
                                   "DFT_COMPACT_LEASE_TTL": "30",
                                   "DFT_ANTIENTROPY_DELTA_MAX": "99"})
    assert cfg.enabled is False and cfg.interval_s == 7.5
    assert cfg.suspect_after == 5 and cfg.lease_ttl_s == 30.0
    assert cfg.delta_max_rows == 99
    assert AntiEntropyCfg().enabled is True  # default on
    with pytest.raises(ValueError):
        AntiEntropyCfg(interval_s=0)
    with pytest.raises(ValueError):
        AntiEntropyCfg(suspect_after=0)
    with pytest.raises(ValueError):
        AntiEntropyCfg(lease_ttl_s=0)
    with pytest.raises(TypeError):
        AntiEntropyCfg(bogus=1)
    with pytest.raises(ValueError):
        ReplicationCfg(repair_interval_s=-1)


def test_read_peers_parses_and_dedupes(tmp_path):
    p = tmp_path / "disc.txt"
    assert read_peers(str(p)) == []  # missing file degrades to no peers
    p.write_text("3\nhosta,1000\n\nhostb,2000\nhosta,1000\ngarbage\n")
    assert read_peers(str(p)) == [("hosta", 1000), ("hostb", 2000)]


# ---------------------------------------------------------------- digests


def make_engine(tmp_path=None, name="e"):
    cfg = flat_cfg()
    if tmp_path is not None:
        cfg.index_storage_dir = str(tmp_path / name)
    return Index(cfg)


def test_replica_digest_is_insertion_order_independent():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((20, DIM)).astype(np.float32)
    a, b = make_engine(), make_engine()
    a.add_batch(x, [(i,) for i in range(20)], train_async_if_triggered=False)
    order = list(reversed(range(20)))
    b.add_batch(x[order], [(i,) for i in order], train_async_if_triggered=False)
    wait_for(lambda: drained(a) and drained(b))
    da, db = a.replica_digest(), b.replica_digest()
    assert digests_match(da, db) and da == db
    assert da["live_n"] == 20 and da["dead_n"] == 0


def test_replica_digest_caches_until_mutation():
    a = make_engine()
    a.add_batch(np.zeros((12, DIM), np.float32),
                [(i,) for i in range(12)], train_async_if_triggered=False)
    wait_for(lambda: drained(a))
    d1 = a.replica_digest()
    with a.buffer_lock, a.index_lock:
        assert a._digest_cache is not None  # cached
    assert a.replica_digest() == d1
    a.remove_ids([3])
    d2 = a.replica_digest()
    assert not digests_match(d1, d2)
    assert d2["live_n"] == 11 and d2["dead_n"] == 1
    # an add moves the digest too (buffered rows count immediately)
    a.add_batch(np.ones((1, DIM), np.float32), [(99,)],
                train_async_if_triggered=False)
    assert a.replica_digest()["live_n"] == 12


def test_digest_dead_side_is_informational_not_compared():
    # converged live sets with different ledgers must still MATCH —
    # ledgers legitimately differ (a delete for a never-held id records
    # nothing), so comparing them would mismatch forever
    a = {"live_n": 3, "live_hash": "aa", "dead_n": 0, "dead_hash": "00"}
    b = {"live_n": 3, "live_hash": "aa", "dead_n": 2, "dead_hash": "ff"}
    assert digests_match(a, b)
    assert not digests_match(a, {**a, "live_hash": "bb"})
    assert not digests_match(a, None)


def test_ledger_survives_compaction_and_readds_unledger(tmp_path):
    eng = make_engine(tmp_path, "led")
    rng = np.random.default_rng(1)
    x = rng.standard_normal((20, DIM)).astype(np.float32)
    eng.add_batch(x, [(i,) for i in range(20)], train_async_if_triggered=False)
    wait_for(lambda: drained(eng))
    eng.remove_ids([2, 3])
    with racecheck.peeking():  # white-box peek, reviewed
        assert eng.tombstones.ledger() == {2, 3}
    assert eng.compact()
    # rows reclaimed, ledger intact
    with racecheck.peeking():  # white-box peek, reviewed
        assert len(eng.tombstones) == 0
    with racecheck.peeking():  # white-box peek, reviewed
        assert eng.tombstones.ledger() == {2, 3}
    # a legal re-add (upsert) removes its ledger entry
    eng.add_batch(x[2:3], [(2,)], train_async_if_triggered=False)
    with racecheck.peeking():  # white-box peek, reviewed
        assert eng.tombstones.ledger() == {3}


def test_tombstone_payload_roundtrips_ledger():
    t = TombstoneSet()
    t.add([5], [(5,)])
    t.ledger_update([("x", 1)])
    p = t.to_payload()
    back = TombstoneSet.from_payload(p)
    assert back.ledger() == t.ledger()
    # pre-ledger payloads seed the ledger from dead_ids
    legacy = {"format": 1, "layout": 0, "dead_rows": [1], "dead_ids": [(7,)]}
    assert TombstoneSet.from_payload(legacy).ledger() == {id_match_key((7,))}


def test_reconcile_deletes_applies_and_records(tmp_path):
    eng = make_engine(tmp_path, "rec")
    x = np.random.default_rng(2).standard_normal((10, DIM)).astype(np.float32)
    eng.add_batch(x, [(i,) for i in range(10)], train_async_if_triggered=False)
    wait_for(lambda: drained(eng))
    removed = eng.reconcile_deletes([4, 77])
    assert removed == 1  # 77 never lived here
    sets = eng.id_sets()
    assert 4 not in set(sets["live"])
    # BOTH keys recorded (pull guard), durable in the sidecar
    assert set(sets["dead"]) >= {4, 77}
    side_path = os.path.join(eng.cfg.index_storage_dir, "tombstones.json")
    assert os.path.exists(side_path)


def test_export_rows_returns_live_rows_only():
    eng = make_engine()
    x = np.random.default_rng(3).standard_normal((10, DIM)).astype(np.float32)
    eng.add_batch(x, [(i,) for i in range(10)], train_async_if_triggered=False)
    wait_for(lambda: drained(eng))
    eng.remove_ids([1])
    emb, meta = eng.export_rows([0, 1, 5, 42])
    assert [m[0] for m in meta] == [0, 5]  # dead + absent ids skipped
    np.testing.assert_allclose(emb, x[[0, 5]], rtol=1e-6)
    # buffered rows export verbatim too
    eng.add_batch(x[:2] + 10.0, [(100,), (101,)],
                  train_async_if_triggered=False)
    emb2, meta2 = eng.export_rows([101])
    assert meta2 == [(101,)]
    np.testing.assert_allclose(emb2[0], x[1] + 10.0, rtol=1e-6)


# ------------------------------------------------------- health / suspects


def test_health_table_suspect_and_recovery():
    h = HealthTable()
    addr = ("hosta", 1234)
    boom = ConnectionRefusedError("down")
    assert h.note_fail(addr, 3, boom) is False
    assert h.note_fail(addr, 3, boom) is False
    assert h.note_fail(addr, 3, boom) is True  # crossed the threshold
    assert h.note_fail(addr, 3, boom) is False  # already suspect
    assert [s["host"] for s in h.suspects()] == ["hosta"]
    h.note_ok(addr, rank=1, group=0)  # one good round trip clears it
    assert h.suspects() == []
    assert h.known_group(*addr) == (True, 0)
    assert h.known_group("other", 1) == (False, None)


def test_health_alive_ranks_uses_both_directions_and_ttl():
    h = HealthTable()
    h.note_ok(("a", 1), rank=2, group=0)
    h.note_inbound(5, group=0)
    h.note_inbound(7, group=1)  # another group: not in this electorate
    assert h.alive_ranks(0, ttl_s=10.0) == {2, 5}
    assert h.alive_ranks(1, ttl_s=10.0) == {7}
    assert h.alive_ranks(0, ttl_s=0.0) == set()  # aged out


class _FakeServer:
    def __init__(self, rank, group):
        self.rank = rank
        self.shard_group = group
        self.socket = None
        self.indexes = {}
        self.indexes_lock = threading.Lock()


def test_compaction_lease_lowest_live_rank_leads(tmp_path):
    cfg = AntiEntropyCfg(interval_s=600, lease_ttl_s=10.0)
    sw = AntiEntropySweeper(_FakeServer(rank=2, group=0),
                            str(tmp_path / "d"), cfg)
    # alone in the group: self is the lowest live rank -> holds the token
    assert sw.may_compact() is True
    # a LOWER live rank appears -> token moves there
    sw.health.note_ok(("peer", 1), rank=0, group=0)
    assert sw.may_compact() is False
    # a lower rank in ANOTHER group is irrelevant
    sw2 = AntiEntropySweeper(_FakeServer(rank=2, group=1),
                             str(tmp_path / "d"), cfg)
    sw2.health.note_ok(("peer", 1), rank=0, group=0)
    assert sw2.may_compact() is True
    # unreplicated rank (no group): always holds its own token
    sw3 = AntiEntropySweeper(_FakeServer(rank=9, group=None),
                             str(tmp_path / "d"), cfg)
    assert sw3.may_compact() is True


def test_compaction_lease_expires_with_ttl(tmp_path):
    cfg = AntiEntropyCfg(interval_s=600, lease_ttl_s=0.2)
    sw = AntiEntropySweeper(_FakeServer(rank=3, group=0),
                            str(tmp_path / "d"), cfg)
    sw.health.note_ok(("peer", 1), rank=1, group=0)
    assert sw.may_compact() is False  # rank 1 leads while live
    time.sleep(0.3)
    assert sw.may_compact() is True  # leader silent past the TTL: take over


def test_plan_read_fanout_pre_skips_suspects_without_removing():
    t = MembershipTable([0, 1, 0, 1])
    plan = plan_read_fanout(t, {}, suspects={0})
    # group 0: suspect 0 rotated to the TAIL, still present
    assert plan[0] == (0, 2, [2, 0])
    assert plan[1] == (1, 1, [1, 3])
    # a suspect pinned replica is demoted too (re-pick a healthy lead)
    plan = plan_read_fanout(t, {0: 0}, suspects={0})
    assert plan[0] == (0, 2, [2, 0])
    # every replica suspect: ordering unchanged (suspicion never blacklists)
    plan = plan_read_fanout(t, {}, suspects={0, 2})
    assert plan[0] == (0, 0, [0, 2])


# -------------------------------------------- repair queue overflow (S1/S3)


def test_repair_queue_drop_warns_and_degrades(caplog):
    q = RepairQueue(maxlen=1)
    with caplog.at_level("WARNING"):
        q.record({"batch": 0})
        assert not any("repair queue full" in r.message for r in caplog.records)
        q.record({"batch": 1})  # first drop: WARNING fires
    assert q.stats()["dropped"] == 1
    warns = [r for r in caplog.records if "repair queue full" in r.message]
    assert len(warns) == 1
    # rate-limited: an immediate second drop stays quiet
    with caplog.at_level("WARNING"):
        caplog.clear()
        q.record({"batch": 2})
    assert not any("repair queue full" in r.message for r in caplog.records)
    assert q.stats()["dropped"] == 2


class FakeStub:
    """Quacks like rpc.Client for the fan-out paths under test."""

    def __init__(self, sid, score=0.0, always_fail=False, health=None):
        self.id = sid
        self.host = "fake"
        self.port = 9000 + sid
        self.score = float(score)
        self.always_fail = always_fail
        self.health = health
        self.acked = []

    def generic_fun(self, fname, args=(), kwargs=None, **_kw):
        if self.always_fail:
            raise ConnectionRefusedError(f"rank {self.id} down")
        self.acked.append((fname, args))
        if fname == "search":
            _iid, q, k, _emb = args
            d = self.score + np.arange(k, dtype=np.float32)
            return (np.tile(d, (q.shape[0], 1)),
                    [[(self.id, j) for j in range(k)] for _ in range(q.shape[0])],
                    None)
        if fname == "get_health":
            if self.health is None:
                raise rpc.ServerException("no health op")
            return self.health
        return f"ok-{self.id}"

    def close(self):
        pass


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
    c._suspects = set()
    c.membership = MembershipTable(
        groups if groups is not None
        else assign_groups(len(stubs), c.rcfg.replication))
    c.cfg = IndexCfg(metric="l2", dim=DIM)
    return c


def test_repair_queue_overflow_survivors_still_repair_and_degraded_flag():
    """Records past maxlen bump ``dropped``; repair of the SURVIVORS still
    completes; get_replication_stats surfaces degraded=True. The dropped
    batches are exactly what the server-side sweep covers (loopback test
    below + the chaos gate)."""
    live = FakeStub(0)
    dead = FakeStub(1, always_fail=True)
    client = make_client(
        [live, dead],
        rcfg=ReplicationCfg(replication=2, write_quorum=1,
                            repair_queue_len=2))
    client.cur_server_ids["idx"] = 0
    for i in range(5):  # 5 under-replicated batches into a 2-slot queue
        client.add_index_data("idx", np.zeros((1, DIM), np.float32), [(i,)])
    stats = client.get_replication_stats()
    assert stats["repair"]["dropped"] == 3
    assert stats["degraded"] is True
    assert len(client.repair_queue) == 2
    dead.always_fail = False
    out = client.repair_under_replicated()
    assert out == {"repaired": 2, "still_pending": 0}
    # only the two surviving records could be replayed — the three
    # dropped batches are unreachable to client-driven repair by design
    assert len(dead.acked) == 2
    assert client.get_replication_stats()["degraded"] is True  # sticky


# ------------------------------------------------- periodic repair driver


def test_periodic_repair_driver_heals_without_explicit_calls():
    live = FakeStub(0)
    dead = FakeStub(1, always_fail=True,
                    health={"enabled": True, "suspects": []})
    client = make_client(
        [live, dead],
        rcfg=ReplicationCfg(replication=2, write_quorum=1,
                            repair_interval_s=0.05))
    client.cur_server_ids["idx"] = 0
    client.add_index_data("idx", np.zeros((2, DIM), np.float32),
                          [(0,), (1,)])
    assert len(client.repair_queue) == 1
    # start the driver the way __init__ does (fixture clients skip it)
    client._repair_stop = threading.Event()
    client._repair_thread = threading.Thread(
        target=client._repair_loop, name="repair-driver", daemon=True)
    client._repair_thread.start()
    dead.always_fail = False  # rank heals; the DRIVER must repair it
    wait_for(lambda: len(client.repair_queue) == 0, timeout=10,
             msg="driver never repaired the queued record")
    assert any(f == "add_index_data" for f, _ in dead.acked)
    assert client._repair_thread.name == "repair-driver"
    client._repair_stop.set()
    client._repair_thread.join(timeout=10)
    assert not client._repair_thread.is_alive()


def test_refresh_health_marks_suspects_and_search_pre_skips():
    """The server-side failure detector's suspect list reorders the read
    walk: the suspect replica is tried LAST (not removed)."""
    health = {"enabled": True, "suspects": [{"host": "fake", "port": 9000}]}
    a = FakeStub(0, score=1.0, health=health)
    b = FakeStub(1, score=1.0, health=health)
    client = make_client([a, b], rcfg=ReplicationCfg(replication=2))
    suspects = client.refresh_health()
    assert suspects == {0}
    assert client.get_replication_stats()["suspects"] == [0]
    client.search(np.zeros((1, DIM), np.float32), 3, "idx")
    # the suspect replica 0 served nothing; the healthy peer did
    assert not any(f == "search" for f, _ in a.acked)
    assert any(f == "search" for f, _ in b.acked)


def test_refresh_health_falls_past_sweeper_disabled_replica():
    """A replica whose sweeper is inert (no discovery file /
    DFT_ANTIENTROPY=0) answers get_health with the enabled=False stub:
    the client must ask the NEXT replica instead of settling for the
    stub's empty suspect view (regression: the walk used to break on the
    first replica that answered at all, so a disabled replica listed
    first permanently hid the group's real suspects)."""
    stub = {"enabled": False, "suspects": []}
    real = {"enabled": True, "suspects": [{"host": "fake", "port": 9000}]}
    a = FakeStub(0, health=stub)
    b = FakeStub(1, health=real)
    client = make_client([a, b], rcfg=ReplicationCfg(replication=2))
    assert client.refresh_health() == {0}
    assert any(f == "get_health" for f, _ in b.acked)


# ------------------------------------------------ loopback sweep end-to-end


def start_server(rank, port, storage, disc, group, cfg):
    os.environ["DFT_SHARD_GROUP"] = str(group)
    try:
        srv = IndexServer(rank, storage, discovery_path=disc,
                          antientropy_cfg=cfg)
    finally:
        del os.environ["DFT_SHARD_GROUP"]
    threading.Thread(target=srv.start_blocking, args=(port,),
                     daemon=True).start()
    deadline = time.time() + 30
    while srv.socket is None:
        assert time.time() < deadline
        time.sleep(0.02)
    return srv


def test_sweep_full_syncs_missing_index_then_delta_heals(tmp_path):
    """Loopback end-to-end: an empty replica's sweep streams the whole
    index from its peer (full-sync path, MANIFEST-committed), a diverged
    replica's sweep pulls the id-delta, deletes reconcile (never
    resurrect), and the lease lands on the lowest live rank."""
    pa, pb = free_port(), free_port()
    disc = str(tmp_path / "disc.txt")
    with open(disc, "w") as f:
        f.write(f"2\nlocalhost,{pa}\nlocalhost,{pb}\n")
    cfg = AntiEntropyCfg(interval_s=600)  # idle thread; tests drive sweeps
    a = start_server(0, pa, str(tmp_path / "a"), disc, 0, cfg)
    b = start_server(1, pb, str(tmp_path / "b"), disc, 0, cfg)
    try:
        assert a._antientropy is not None and b._antientropy is not None
        a.create_index("t", flat_cfg())
        rng = np.random.default_rng(0)
        x = rng.standard_normal((40, DIM)).astype(np.float32)
        a.add_index_data("t", x, [(i,) for i in range(40)])
        wait_for(lambda: (a.get_state("t") == IndexState.TRAINED
                          and a.get_aggregated_ntotal("t") == 0))
        a.remove_ids("t", [5, 6])

        # --- B is EMPTY: its sweep must full-sync the index in
        out = b._antientropy.sweep_once()
        assert any(h.get("full_sync") for h in out["healed"])
        wait_for(lambda: b.get_aggregated_ntotal("t") == 0)
        da = a._get_index("t").replica_digest()
        db = b._get_index("t").replica_digest()
        assert digests_match(da, db) and da == db
        assert b._antientropy.stats()["full_syncs"] == 1

        # --- diverge again: rows + a delete land on A only
        a.add_index_data("t", x[:5] + 30.0, [(100 + i,) for i in range(5)])
        a.remove_ids("t", [7])
        out = b._antientropy.sweep_once()
        healed = [h for h in out["healed"] if h["index_id"] == "t"]
        assert healed and healed[0]["pulled"] == 5 and healed[0]["removed"] == 1
        wait_for(lambda: b.get_aggregated_ntotal("t") == 0)
        da = a._get_index("t").replica_digest()
        db = b._get_index("t").replica_digest()
        assert digests_match(da, db) and da == db
        # deleted ids never resurrected on either side
        for srv in (a, b):
            ids = srv.get_ids("t")
            assert (5,) not in ids and (6,) not in ids and (7,) not in ids
        # byte-identical serving
        sa, sb = a.search("t", x[:4], 3), b.search("t", x[:4], 3)
        np.testing.assert_array_equal(sa[0], sb[0])
        assert sa[1] == sb[1]

        # --- A's own sweep sees convergence, nothing to pull
        out = a._antientropy.sweep_once()
        assert out["healed"] == []
        stats = a._antientropy.stats()
        assert stats["digests_matched"] >= 1 and stats["suspect_peers"] == []

        # --- lease: exactly one holder per group (lowest live rank)
        assert a._antientropy.may_compact() is True
        assert b._antientropy.may_compact() is False
        assert a.get_health()["compaction"]["held"] is True
        assert b.get_health()["compaction"]["held"] is False
        # perf-stats surface
        perf = a.get_perf_stats()["antientropy"]
        assert perf["enabled"] and "rows_repaired" in perf
        # compaction gates installed on the engines
        assert a._get_index("t").compaction_gate is not None
    finally:
        a.stop()
        b.stop()


def test_sweep_never_resurrects_dropped_index(tmp_path):
    """drop_index leaves a drop tombstone: a sweep that sees a peer still
    serving the dropped index must NOT full-sync it back (regression: the
    marker existed but nothing ever wrote or consulted it, so on a
    sweeping cluster a dropped index came back within one interval from
    any in-group peer that missed the drop). An explicit resync clears
    the marker and the index heals back in."""
    pa, pb = free_port(), free_port()
    disc = str(tmp_path / "disc.txt")
    with open(disc, "w") as f:
        f.write(f"2\nlocalhost,{pa}\nlocalhost,{pb}\n")
    cfg = AntiEntropyCfg(interval_s=600)  # idle thread; tests drive sweeps
    a = start_server(0, pa, str(tmp_path / "a"), disc, 0, cfg)
    b = start_server(1, pb, str(tmp_path / "b"), disc, 0, cfg)
    try:
        a.create_index("t", flat_cfg())
        rng = np.random.default_rng(5)
        x = rng.standard_normal((20, DIM)).astype(np.float32)
        a.add_index_data("t", x, [(i,) for i in range(20)])
        wait_for(lambda: (a.get_state("t") == IndexState.TRAINED
                          and a.get_aggregated_ntotal("t") == 0))
        b._antientropy.sweep_once()
        wait_for(lambda: b.get_aggregated_ntotal("t") == 0)
        assert "t" in b.indexes

        # drop on B; A still serves the index (missed-drop scenario)
        b.drop_index("t")
        out = b._antientropy.sweep_once()
        assert "t" not in b.indexes, "sweep resurrected a dropped index"
        assert not any(h["index_id"] == "t" for h in out["healed"])

        # an explicit resync clears the marker; healing resumes
        b.sync_shard_from("t", "localhost", pa)
        wait_for(lambda: b.get_aggregated_ntotal("t") == 0)
        da = a._get_index("t").replica_digest()
        db = b._get_index("t").replica_digest()
        assert digests_match(da, db)
        b._antientropy.sweep_once()
        assert "t" in b.indexes
    finally:
        a.stop()
        b.stop()


def test_sweep_learns_group_registered_after_first_exchange(tmp_path):
    """Group registration can postdate the first digest exchange
    (set_shard_group arrives with the first IndexClient): a peer whose
    group was cached as None while unregistered must keep being dialed —
    a stale cached None can never wedge a genuine group peer out of the
    sweep (regression: the skip branch used to stop dialing forever,
    silently disabling digests, healing, and the lease for the cluster's
    whole life whenever a client arrived after the first sweep)."""
    pa, pb = free_port(), free_port()
    disc = str(tmp_path / "disc.txt")
    with open(disc, "w") as f:
        f.write(f"2\nlocalhost,{pa}\nlocalhost,{pb}\n")
    cfg = AntiEntropyCfg(interval_s=600)  # idle thread; tests drive sweeps
    a = IndexServer(0, str(tmp_path / "a"), discovery_path=disc,
                    antientropy_cfg=cfg)
    b = IndexServer(1, str(tmp_path / "b"), discovery_path=disc,
                    antientropy_cfg=cfg)
    for srv, port in ((a, pa), (b, pb)):
        threading.Thread(target=srv.start_blocking, args=(port,),
                         daemon=True).start()
    wait_for(lambda: a.socket is not None and b.socket is not None)
    try:
        assert a.shard_group is None and b.shard_group is None
        # first exchanges happen UNREGISTERED: both sides cache the
        # peer's group as None (liveness-only contact)
        a._antientropy.sweep_once()
        b._antientropy.sweep_once()
        assert b._antientropy.health.known_group("localhost", pa) == (True,
                                                                      None)
        # groups register afterwards — what IndexClient._register_groups
        # does on its first construction
        a.set_shard_group(0)
        b.set_shard_group(0)
        # diverge A; B's next sweep must still dial A (a cached None is
        # not a concrete other group), learn group 0, and heal
        a.create_index("t", flat_cfg())
        rng = np.random.default_rng(3)
        x = rng.standard_normal((20, DIM)).astype(np.float32)
        a.add_index_data("t", x, [(i,) for i in range(20)])
        wait_for(lambda: (a.get_state("t") == IndexState.TRAINED
                          and a.get_aggregated_ntotal("t") == 0))
        out = b._antientropy.sweep_once()
        assert out["skipped"] == 0
        assert any(h.get("full_sync") for h in out["healed"])
        _k, g = b._antientropy.health.known_group("localhost", pa)
        assert g == 0
        da = a._get_index("t").replica_digest()
        db = b._get_index("t").replica_digest()
        assert digests_match(da, db)
    finally:
        a.stop()
        b.stop()


def test_one_directional_divergence_stays_quiet(tmp_path, caplog):
    """The AHEAD side of a one-directional divergence (the peer is simply
    behind) has an empty pull delta but a non-empty local_only set — the
    normal transient the pull-only design expects (the peer's own sweep
    heals it), NOT invisible divergence: no empty_deltas bump, no
    operator warning (regression: the ahead replica warned 'divergence is
    invisible to id sets' once per rate-limit window during every
    ordinary heal)."""
    pa, pb = free_port(), free_port()
    disc = str(tmp_path / "disc.txt")
    with open(disc, "w") as f:
        f.write(f"2\nlocalhost,{pa}\nlocalhost,{pb}\n")
    cfg = AntiEntropyCfg(interval_s=600)
    a = start_server(0, pa, str(tmp_path / "a"), disc, 0, cfg)
    b = start_server(1, pb, str(tmp_path / "b"), disc, 0, cfg)
    try:
        rng = np.random.default_rng(6)
        x = rng.standard_normal((12, DIM)).astype(np.float32)
        for srv in (a, b):
            srv.create_index("t", flat_cfg())
            srv.add_index_data("t", x, [(i,) for i in range(12)])
            wait_for(lambda: (srv.get_state("t") == IndexState.TRAINED
                              and srv.get_aggregated_ntotal("t") == 0))
        # one NEW id on A only: A is ahead, B is behind
        y = rng.standard_normal((1, DIM)).astype(np.float32)
        a.add_index_data("t", y, [(100,)])
        wait_for(lambda: a.get_aggregated_ntotal("t") == 0)
        assert not digests_match(a._get_index("t").replica_digest(),
                                 b._get_index("t").replica_digest())
        with caplog.at_level("WARNING"):
            out = a._antientropy.sweep_once()
        healed = [h for h in out["healed"] if h["index_id"] == "t"]
        assert healed == [{"index_id": "t", "peer": ("localhost", pb),
                           "removed": 0, "pulled": 0, "refreshed": 0,
                           "full_sync": False}]
        assert a._antientropy.stats()["empty_deltas"] == 0
        assert not any("id-set delta is empty" in r.message
                       for r in caplog.records)
        # the behind side's own sweep heals the divergence
        b._antientropy.sweep_once()
        wait_for(lambda: b.get_aggregated_ntotal("t") == 0)
        wait_for(lambda: digests_match(a._get_index("t").replica_digest(),
                                       b._get_index("t").replica_digest()))
        assert b._antientropy.stats()["rows_repaired"] == 1
    finally:
        a.stop()
        b.stop()


def test_empty_delta_mismatch_counts_and_warns(tmp_path, caplog):
    """A digest mismatch whose id-set delta is empty (an id duplicated on
    one side by an at-least-once ingest retry) cannot be healed by the
    sweep — but it must be SURFACED: the empty_deltas counter moves and a
    rate-limited warning names the remedies (regression: the mismatch
    counter climbed silently forever with no heal and no log)."""
    pa, pb = free_port(), free_port()
    disc = str(tmp_path / "disc.txt")
    with open(disc, "w") as f:
        f.write(f"2\nlocalhost,{pa}\nlocalhost,{pb}\n")
    cfg = AntiEntropyCfg(interval_s=600)
    a = start_server(0, pa, str(tmp_path / "a"), disc, 0, cfg)
    b = start_server(1, pb, str(tmp_path / "b"), disc, 0, cfg)
    try:
        rng = np.random.default_rng(5)
        x = rng.standard_normal((12, DIM)).astype(np.float32)
        for srv in (a, b):
            srv.create_index("t", flat_cfg())
            srv.add_index_data("t", x, [(i,) for i in range(12)])
            wait_for(lambda: (srv.get_state("t") == IndexState.TRAINED
                              and srv.get_aggregated_ntotal("t") == 0))
        # duplicate ONE id on A only: live_n diverges, id SETS stay equal
        a.add_index_data("t", x[:1], [(0,)])
        wait_for(lambda: a.get_aggregated_ntotal("t") == 0)
        da = a._get_index("t").replica_digest()
        db = b._get_index("t").replica_digest()
        assert not digests_match(da, db)
        with caplog.at_level("WARNING"):
            out = b._antientropy.sweep_once()
        healed = [h for h in out["healed"] if h["index_id"] == "t"]
        assert healed == [{"index_id": "t", "peer": ("localhost", pa),
                           "removed": 0, "pulled": 0, "refreshed": 0,
                           "full_sync": False}]
        assert b._antientropy.stats()["empty_deltas"] == 1
        assert any("id-set delta is empty" in r.message
                   for r in caplog.records)
        # rate limit: an immediate second sweep bumps the counter only
        caplog.clear()
        with caplog.at_level("WARNING"):
            b._antientropy.sweep_once()
        assert b._antientropy.stats()["empty_deltas"] == 2
        assert not any("id-set delta is empty" in r.message
                       for r in caplog.records)
    finally:
        a.stop()
        b.stop()


def test_sweep_detects_dead_peer_and_marks_suspect(tmp_path):
    pa = free_port()
    dead_port = free_port()  # nothing listens here
    disc = str(tmp_path / "disc.txt")
    with open(disc, "w") as f:
        f.write(f"2\nlocalhost,{pa}\nlocalhost,{dead_port}\n")
    cfg = AntiEntropyCfg(interval_s=600, suspect_after=2,
                         exchange_timeout_s=0.5)
    a = start_server(0, pa, str(tmp_path / "a"), disc, 0, cfg)
    try:
        a._antientropy.sweep_once()
        assert a.get_health()["suspects"] == []  # one failure: not yet
        a._antientropy.sweep_once()
        suspects = a.get_health()["suspects"]
        assert [s["port"] for s in suspects] == [dead_port]
        assert a.get_perf_stats()["antientropy"]["suspect_peers"]
    finally:
        a.stop()


def test_digest_frames_blackholed_marks_suspect_but_direct_reads_serve(
        tmp_path):
    """ChaosProxy drop-kind fault (S6): blackhole ONLY the KIND_DIGEST
    frames on the path A uses to reach B — A's failure detector marks B
    suspect, while B keeps serving reads both through the faulted proxy
    (query frames pass) and directly."""
    pa, pb = free_port(), free_port()
    proxy = ChaosProxy("localhost", pb).start()
    proxy.set_fault(Fault(Fault.DROP_KIND, direction="up",
                          drop_kinds={rpc.KIND_DIGEST}))
    disc = str(tmp_path / "disc.txt")
    with open(disc, "w") as f:
        # A resolves B through the proxy; B runs sweeper-inert
        f.write(f"2\nlocalhost,{pa}\nlocalhost,{proxy.port}\n")
    cfg = AntiEntropyCfg(interval_s=600, suspect_after=2,
                         exchange_timeout_s=0.5)
    a = start_server(0, pa, str(tmp_path / "a"), disc, 0, cfg)
    b = IndexServer(1, str(tmp_path / "b"))
    b.set_shard_group(0)
    threading.Thread(target=b.start_blocking, args=(pb,), daemon=True).start()
    time.sleep(0.3)
    try:
        b.create_index("t", flat_cfg())
        x = np.random.default_rng(4).standard_normal((20, DIM)).astype(
            np.float32)
        b.add_index_data("t", x, [(i,) for i in range(20)])
        wait_for(lambda: (b.get_state("t") == IndexState.TRAINED
                          and b.get_aggregated_ntotal("t") == 0))
        # two sweeps, both digest exchanges blackholed -> suspect
        a._antientropy.sweep_once()
        a._antientropy.sweep_once()
        assert [s["port"] for s in a.get_health()["suspects"]] \
            == [proxy.port]
        # the SAME proxied link still serves query traffic (only digest
        # frames are dropped)...
        via_proxy = rpc.Client(7, "localhost", proxy.port, mux=False)
        scores, meta, _ = via_proxy.generic_fun(
            "search", ("t", x[:2], 3, False))
        assert scores.shape == (2, 3)
        via_proxy.close()
        # ...and the suspect-marked peer still serves DIRECT reads
        direct = rpc.Client(8, "localhost", pb, mux=False)
        scores, meta, _ = direct.generic_fun("search", ("t", x[:2], 3, False))
        assert scores.shape == (2, 3)
        direct.close()
    finally:
        proxy.stop()
        a.stop()
        b.stop()


def test_compaction_watcher_defers_without_lease(tmp_path):
    """The background watcher consults the lease gate; a rank that does
    not hold its group's token defers, and the explicit compact op still
    works (operator override)."""
    eng = make_engine(tmp_path, "gate")
    x = np.random.default_rng(5).standard_normal((20, DIM)).astype(np.float32)
    eng.add_batch(x, [(i,) for i in range(20)], train_async_if_triggered=False)
    wait_for(lambda: drained(eng))
    eng.remove_ids(list(range(10)))
    eng.compaction_gate = lambda: False
    from distributed_faiss_tpu.utils.config import MutationCfg
    from distributed_faiss_tpu.mutation import compaction

    # one watcher pass worth of logic: gate blocks the threshold trigger
    assert eng.tombstone_fraction() >= 0.25
    gate = eng.compaction_gate
    assert gate() is False  # the watcher's check (run_watcher consults it)
    assert eng._mutation_counters["compactions"] == 0
    # explicit operator compact bypasses the lease
    assert eng.compact()
    assert eng._mutation_counters["compactions"] == 1


# -------------------------------------------- content-hash verified pulls


def test_export_rows_versioned_hash_roundtrip(tmp_path):
    """with_hash=True appends a sha256 the receiver can recompute over
    the decoded payload; the bare call keeps the PR-12 3-tuple shape."""
    from distributed_faiss_tpu.utils import serialization

    eng = make_engine(tmp_path, "h")
    x = np.random.default_rng(0).standard_normal((12, DIM)).astype(np.float32)
    eng.add_batch(x, [(i,) for i in range(12)],
                  train_async_if_triggered=False)
    wait_for(lambda: drained(eng))
    bare = eng.export_rows_versioned(list(range(5)))
    assert len(bare) == 3 and len(bare[1]) == 5
    emb, meta, vers, digest = eng.export_rows_versioned(
        list(range(5)), with_hash=True)
    np.testing.assert_array_equal(emb, bare[0])
    assert serialization.row_payload_hash(emb, meta, vers) == digest
    # any payload change breaks the hash
    assert serialization.row_payload_hash(emb + 1.0, meta, vers) != digest
    assert serialization.row_payload_hash(emb, meta[:-1], vers) != digest
    # canonicalization: set-valued metadata hashes by CONTENT, not by
    # per-process repr order (str-hash randomization), and equal sets
    # built differently hash equal while different sets differ
    h1 = serialization.row_payload_hash(
        emb[:1], [({"a", "b", "c"},)], [None])
    h2 = serialization.row_payload_hash(
        emb[:1], [(set(["c", "b", "a"]),)], [None])
    h3 = serialization.row_payload_hash(
        emb[:1], [({"a", "b", "z"},)], [None])
    assert h1 == h2 and h1 != h3


def test_heal_rejects_corrupt_chunk_and_marks_peer(tmp_path):
    """A delta pull whose chunk fails content-hash verification is
    counted, retried once, NEVER applied, and surfaces as a transport
    failure feeding the failure detector; with the corruption gone the
    next sweep heals normally."""
    pa, pb = free_port(), free_port()
    disc = str(tmp_path / "disc.txt")
    with open(disc, "w") as f:
        f.write(f"2\nlocalhost,{pa}\nlocalhost,{pb}\n")
    cfg = AntiEntropyCfg(interval_s=600)
    a = start_server(0, pa, str(tmp_path / "a"), disc, 0, cfg)
    b = start_server(1, pb, str(tmp_path / "b"), disc, 0, cfg)
    try:
        a.create_index("t", flat_cfg())
        rng = np.random.default_rng(1)
        x = rng.standard_normal((30, DIM)).astype(np.float32)
        a.add_index_data("t", x, [(i,) for i in range(30)])
        wait_for(lambda: (a.get_state("t") == IndexState.TRAINED
                          and a.get_aggregated_ntotal("t") == 0))
        b._antientropy.sweep_once()  # full-sync B in
        wait_for(lambda: b.get_aggregated_ntotal("t") == 0)

        # diverge: 6 fresh rows land on A only, and A's export corrupts
        # the payload while keeping its claimed hash (simulated transport
        # corruption past the TCP checksum)
        a.add_index_data("t", x[:6] + 9.0, [(100 + i,) for i in range(6)])
        wait_for(lambda: a.get_aggregated_ntotal("t") == 0)
        eng_a = a._get_index("t")
        orig = eng_a.export_rows_versioned

        def corrupting(ids, with_hash=False):
            out = orig(ids, with_hash=with_hash)
            if with_hash:
                emb, meta, vers, digest = out
                return emb + 1.0, meta, vers, digest  # payload != hash
            return out

        eng_a.export_rows_versioned = corrupting
        before = b.get_ntotal("t")
        out = b._antientropy.sweep_once()
        stats = b._antientropy.stats()
        assert stats["chunk_hash_mismatch"] == 2  # first try + one retry
        assert b.get_ntotal("t") == before, "corrupt rows were applied"
        assert out["failed"] >= 1
        peers = b._antientropy.health.snapshot()
        assert any(e.get("failures", 0) >= 1 for e in peers.values())

        # corruption clears -> the next sweep heals and verifies clean
        eng_a.export_rows_versioned = orig
        b._antientropy.sweep_once()
        wait_for(lambda: b.get_aggregated_ntotal("t") == 0)
        assert b._antientropy.stats()["chunk_hash_mismatch"] == 2
        da = a._get_index("t").replica_digest()
        db = b._get_index("t").replica_digest()
        assert digests_match(da, db)
    finally:
        a.stop()
        b.stop()


# ---------------------------------------------- deletion-ledger pruning


def test_tombstone_prune_ledger_unit():
    from distributed_faiss_tpu.mutation.versions import HLC

    clock = HLC(writer_id=1)
    ts = TombstoneSet()
    v1, v2, v3 = clock.tick(), clock.tick(), clock.tick()
    ts.ledger_update_versioned([("a", v1), ("b", v2)])
    ts.ledger_update(["legacy"])  # version None: never prunable
    assert ts.prune_ledger(None) == 0
    assert ts.prune_ledger(v1) == 0          # strictly below only
    assert ts.prune_ledger(v2) == 1          # drops ("a", v1)
    assert ts.ledger() == frozenset({"b", "legacy"})
    assert ts.prune_ledger(v3) == 1          # drops ("b", v2)
    assert ts.ledger() == frozenset({"legacy"})
    assert ts.prune_ledger(v3) == 0
    # the age bound: a below-floor entry YOUNGER than the cutoff
    # survives (a client's repair queue may still replay the pre-delete
    # add this pair gates — DFT_LEDGER_PRUNE_AGE_S)
    v4, v5 = clock.tick(), clock.tick()
    ts.ledger_update_versioned([("c", v4)])
    assert ts.prune_ledger(v5, max_wall_ms=v4[0] - 10_000) == 0
    assert "c" in ts.ledger()
    assert ts.prune_ledger(v5, max_wall_ms=v4[0]) == 1


def test_ledger_prunes_after_cluster_watermark_never_while_suspect(tmp_path):
    """The sweeper prunes deletion-ledger version pairs once every
    registered replica's watermark passed them — and NEVER while a group
    peer is unreachable/suspect this round (a replica we cannot hear
    from might be missing exactly the delete we would prune). A
    decommissioned address REMOVED from discovery stops blocking (its
    stale suspect entry is out of scope)."""
    from distributed_faiss_tpu.mutation.versions import HLC

    pa, pb, pdead = free_port(), free_port(), free_port()
    disc = str(tmp_path / "disc.txt")
    with open(disc, "w") as f:
        f.write(f"2\nlocalhost,{pa}\nlocalhost,{pb}\n")
    cfg = AntiEntropyCfg(interval_s=600, suspect_after=1,
                         exchange_timeout_s=1.0, ledger_prune_age_s=0.0)
    a = start_server(0, pa, str(tmp_path / "a"), disc, 0, cfg)
    b = start_server(1, pb, str(tmp_path / "b"), disc, 0, cfg)
    try:
        clock = HLC(writer_id=7)
        rng = np.random.default_rng(2)
        x = rng.standard_normal((20, DIM)).astype(np.float32)
        for srv in (a, b):
            srv.create_index("t", flat_cfg())
            srv.add_index_data("t", x, [(i,) for i in range(20)],
                               version=clock.tick())
        wait_for(lambda: all(s.get_state("t") == IndexState.TRAINED
                             and s.get_aggregated_ntotal("t") == 0
                             for s in (a, b)))
        vdel = clock.tick()
        for srv in (a, b):
            srv.remove_ids("t", [0, 1, 2], version=vdel)
        for eng in (a._get_index("t"), b._get_index("t")):
            with racecheck.peeking():  # white-box peek, reviewed
                assert eng.tombstones.ledger_size() == 3
        # the delete IS the watermark: nothing is strictly below it yet
        a._antientropy.sweep_once()
        with racecheck.peeking():  # white-box peek, reviewed
            assert a._get_index("t").tombstones.ledger_size() == 3

        # a newer write on both replicas moves every watermark past vdel
        vnew = clock.tick()
        for srv in (a, b):
            srv.add_index_data("t", x[:1] + 50.0, [(200,)], version=vnew)
        wait_for(lambda: all(s.get_aggregated_ntotal("t") == 0
                             for s in (a, b)))

        # ... but with an UNREACHABLE registered peer in discovery, the
        # sweep must NOT prune (dial failure -> suspect after 1 miss)
        with open(disc, "w") as f:
            f.write(f"3\nlocalhost,{pa}\nlocalhost,{pb}\n"
                    f"localhost,{pdead}\n")
        a._antientropy.sweep_once()
        with racecheck.peeking():  # white-box peek, reviewed
            assert a._get_index("t").tombstones.ledger_size() == 3
        a._antientropy.sweep_once()  # now suspect-marked: still no prune
        with racecheck.peeking():  # white-box peek, reviewed
            assert a._get_index("t").tombstones.ledger_size() == 3
        # the dead address is decommissioned (removed from discovery) but
        # a LIVE unregistered peer (no shard_group yet — a fresh restart
        # no client has dialed) joins: it might be a member of OUR
        # group, so it must block pruning exactly like a failed dial
        pc = free_port()
        c = IndexServer(2, str(tmp_path / "c"), discovery_path=disc,
                        antientropy_cfg=cfg)
        threading.Thread(target=c.start_blocking, args=(pc,),
                         daemon=True).start()
        wait_for(lambda: c.socket is not None)
        with open(disc, "w") as f:
            f.write(f"3\nlocalhost,{pa}\nlocalhost,{pb}\nlocalhost,{pc}\n")
        try:
            a._antientropy.sweep_once()
            with racecheck.peeking():  # white-box peek, reviewed
                assert a._get_index("t").tombstones.ledger_size() == 3
            # ... until it registers into a DIFFERENT group: another
            # group's replica never blocks ours
            c.set_shard_group(1)
            a._antientropy.sweep_once()
        finally:
            c.stop()
            # decommission c before B's own sweep: a dead listed peer
            # would (correctly) block B's pruning
            with open(disc, "w") as f:
                f.write(f"2\nlocalhost,{pa}\nlocalhost,{pb}\n")
        eng_a = a._get_index("t")
        with racecheck.peeking():  # white-box peek, reviewed
            assert eng_a.tombstones.ledger_size() == 0
        assert eng_a.mutation_stats()["ledger_pruned"] == 3
        assert a._antientropy.stats()["ledger_pruned"] == 3
        # B prunes from its own sweep
        b._antientropy.sweep_once()
        with racecheck.peeking():  # white-box peek, reviewed
            assert b._get_index("t").tombstones.ledger_size() == 0
        # pruning persisted: the reloaded sidecar stays pruned
        sets = eng_a.id_sets()
        assert sets["dead"] == []
    finally:
        a.stop()
        b.stop()


def test_delete_churn_ledger_stays_bounded(tmp_path):
    """The ISSUE 14 regression: delete-heavy churn used to grow the
    sidecar's version-pair ledger without bound; with sweeper-driven
    pruning the ledger retains only entries at/after the cluster
    watermark floor."""
    from distributed_faiss_tpu.mutation.versions import HLC

    pa, pb = free_port(), free_port()
    disc = str(tmp_path / "disc.txt")
    with open(disc, "w") as f:
        f.write(f"2\nlocalhost,{pa}\nlocalhost,{pb}\n")
    cfg = AntiEntropyCfg(interval_s=600, ledger_prune_age_s=0.0)
    a = start_server(0, pa, str(tmp_path / "a"), disc, 0, cfg)
    b = start_server(1, pb, str(tmp_path / "b"), disc, 0, cfg)
    try:
        clock = HLC(writer_id=9)
        rng = np.random.default_rng(3)
        x = rng.standard_normal((10, DIM)).astype(np.float32)
        for srv in (a, b):
            srv.create_index("t", flat_cfg())
            srv.add_index_data("t", x, [(i,) for i in range(10)],
                               version=clock.tick())
        wait_for(lambda: all(s.get_state("t") == IndexState.TRAINED
                             and s.get_aggregated_ntotal("t") == 0
                             for s in (a, b)))
        batch, rounds = 8, 5
        next_id = 1000
        for _r in range(rounds):
            ids = list(range(next_id, next_id + batch))
            next_id += batch
            vadd = clock.tick()
            for srv in (a, b):
                srv.add_index_data("t", rng.standard_normal(
                    (batch, DIM)).astype(np.float32),
                    [(i,) for i in ids], version=vadd)
            wait_for(lambda: all(s.get_aggregated_ntotal("t") == 0
                                 for s in (a, b)))
            vdel = clock.tick()
            for srv in (a, b):
                srv.remove_ids("t", ids, version=vdel)
            a._antientropy.sweep_once()
            b._antientropy.sweep_once()
        total_deleted = batch * rounds
        for srv in (a, b):
            with racecheck.peeking():  # white-box peek, reviewed
                size = srv._get_index("t").tombstones.ledger_size()
            # without pruning this is total_deleted (40); with it, only
            # the final round's pairs (nothing newer outranks them yet)
            # survive
            assert size <= batch, (size, total_deleted)
        assert a._antientropy.stats()["ledger_pruned"] > 0
    finally:
        a.stop()
        b.stop()
