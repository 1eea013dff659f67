"""Per-id mutation versions (ISSUE 12): HLC ordering, the LWW gates,
idempotent replays, sidecar round-trip + legacy payload upgrade, the
read-your-writes watermark, and generation-pinned point-in-time reads —
engine + client plumbing against fake stubs. Fast tests run in tier-1;
the live-cluster upsert-vs-delete SIGKILL gate is in
tests/test_versions_chaos.py."""

import json
import random
import threading
import time
from multiprocessing.dummy import Pool as ThreadPool

import numpy as np
import pytest

from distributed_faiss_tpu.utils import tracing
from distributed_faiss_tpu.engine import Index
from distributed_faiss_tpu.mutation import tombstones, versions
from distributed_faiss_tpu.mutation.tombstones import TombstoneSet
from distributed_faiss_tpu.mutation.versions import HLC
from distributed_faiss_tpu.parallel import replication, rpc
from distributed_faiss_tpu.parallel.client import IndexClient
from distributed_faiss_tpu.utils import serialization
from distributed_faiss_tpu.utils.config import (
    IndexCfg,
    ReplicationCfg,
    VersioningCfg,
)
from distributed_faiss_tpu.utils.state import (
    STALE_READ_REJECTION_FMT,
    STALE_READ_REJECTION_PREFIX,
    IndexState,
)
from distributed_faiss_tpu.utils import lockdep, racecheck
from distributed_faiss_tpu.utils.atomics import AtomicCounters

pytestmark = pytest.mark.versions

DIM = 16


@pytest.fixture
def rng():
    return np.random.default_rng(12)


@pytest.fixture(autouse=True)
def _no_background_compaction(monkeypatch):
    monkeypatch.setenv("DFT_COMPACT", "0")


def flat_cfg(tmp_path, **kw):
    kw.setdefault("index_builder_type", "flat")
    kw.setdefault("dim", DIM)
    kw.setdefault("metric", "l2")
    kw.setdefault("train_num", 10)
    kw.setdefault("index_storage_dir", str(tmp_path / "shard"))
    return IndexCfg(**kw)


def wait_drained(idx, n, timeout=30.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if (idx.get_idx_data_num() == (0, n)
                and idx.get_state() == IndexState.TRAINED):
            return
        time.sleep(0.02)
    raise AssertionError(f"engine never drained to {n} rows: "
                         f"{idx.get_idx_data_num()} ({idx.get_state()})")


def build_engine(tmp_path, rng, n=60, version=None, **kw):
    idx = Index(flat_cfg(tmp_path, **kw))
    x = rng.standard_normal((n, DIM)).astype(np.float32)
    idx.add_batch(x, [(i,) for i in range(n)],
                  train_async_if_triggered=False, version=version)
    wait_drained(idx, n)
    return idx, x


# ------------------------------------------------------------------ HLC


def test_hlc_ticks_strictly_increase():
    clock = HLC(writer_id=1)
    stamps = [clock.tick() for _ in range(200)]
    assert stamps == sorted(stamps)
    assert len(set(stamps)) == len(stamps)


def test_hlc_observe_advances_past_remote():
    clock = HLC(writer_id=1)
    future = (clock.tick()[0] + 60_000, 5, 9)
    clock.observe(future)
    nxt = clock.tick()
    assert versions.compare(nxt, future) > 0


def test_hlc_restart_with_backward_wall_clock_stamps_ahead():
    """The restart story (ISSUE 12 satellite): a client re-created on a
    machine whose wall clock runs BEHIND the cluster seeds its clock from
    the max observed version (get_id_sets watermark) and still stamps
    strictly ahead — wall clock alone would issue stale stamps every
    replica no-ops."""
    wall = {"ms": 1_000_000}
    old = HLC(writer_id=1, clock_ms=lambda: wall["ms"] + 50_000)
    pre_restart = [old.tick() for _ in range(3)]
    cluster_max = pre_restart[-1]
    # restarted client: wall clock 50 s behind its own earlier stamps
    fresh = HLC(writer_id=1, clock_ms=lambda: wall["ms"])
    stale = fresh.tick()
    assert versions.compare(stale, cluster_max) < 0  # the failure mode
    seeded = HLC(writer_id=1, clock_ms=lambda: wall["ms"])
    seeded.observe(cluster_max)
    assert versions.compare(seeded.tick(), cluster_max) > 0


def test_version_key_normalizes_json_lists():
    v = (1234, 5, 6)
    assert versions.version_key(list(v)) == v
    assert versions.version_key(None) is None
    assert versions.version_key(json.loads(json.dumps(list(v)))) == v
    with pytest.raises(ValueError):
        versions.version_key("nope")


def test_compare_total_order_with_none_minimal():
    a, b = (10, 0, 1), (10, 0, 2)
    assert versions.compare(None, a) < 0 < versions.compare(a, None)
    assert versions.compare(None, None) == 0
    assert versions.compare(a, b) < 0  # writer id breaks the tie
    assert versions.newest(a, b) == b
    assert versions.newest(None, a) == a


def test_lww_gates_tie_semantics():
    v = (10, 0, 1)
    newer, older = (11, 0, 1), (9, 0, 1)
    # add: loses to same-or-newer LIVE (replay) and strictly newer DEAD
    assert versions.add_loses(v, live=v, dead=None)
    assert versions.add_loses(v, live=newer, dead=None)
    assert versions.add_loses(v, live=None, dead=newer)
    assert not versions.add_loses(v, live=older, dead=None)
    assert not versions.add_loses(v, live=None, dead=v)  # upsert's own delete
    assert not versions.add_loses(v, live=None, dead=None)
    # delete: loses to same-or-newer LIVE (upsert won) and same-or-newer DEAD
    assert versions.delete_loses(v, live=v, dead=None)
    assert versions.delete_loses(v, live=None, dead=v)
    assert not versions.delete_loses(v, live=older, dead=older)
    assert not versions.delete_loses(v, live=None, dead=None)


def test_versioning_cfg_env_and_validation():
    cfg = VersioningCfg.from_env({"DFT_VERSIONING": "0",
                                  "DFT_RETAIN_GENERATIONS": "4"})
    assert cfg.enabled is False and cfg.retain_generations == 4
    assert VersioningCfg().enabled is True
    assert VersioningCfg().retain_generations == 2
    for bad in (0, 1):  # 1 would be silently floored to the engine's
        with pytest.raises(ValueError):  # crash-fallback pair — reject it
            VersioningCfg(retain_generations=bad)
    with pytest.raises(TypeError):
        VersioningCfg(bogus=1)


# ------------------------------------------------------ sidecar round-trip


def test_payload_round_trips_version_planes():
    t = TombstoneSet()
    t.add([3, 4], [("m", 3), ("m", 4)], version=(10, 0, 1))
    t.set_live_version(("m", 7), (11, 2, 1))
    payload = json.loads(tombstones.dump_payload(t.to_payload()))
    assert payload["format"] == tombstones.PAYLOAD_FORMAT == 2
    back = TombstoneSet.from_payload(payload)
    assert back.ledger_version(("m", 3)) == (10, 0, 1)
    assert back.live_version(("m", 7)) == (11, 2, 1)
    assert back.ledger() == t.ledger()
    assert back.max_version() == (11, 2, 1)


def test_legacy_payload_upgrades_to_version_none():
    """A format-1 payload (no version planes) loads with every version
    None — unversioned is minimal, so any later stamped write outranks
    the recovered legacy state (the documented upgrade semantics)."""
    legacy = {"format": 1, "layout": 0, "dead_rows": [2],
              "dead_ids": [("m", 2)], "dead_ledger": [["m", 2]]}
    t = TombstoneSet.from_payload(legacy)
    assert t.ledger_version(("m", 2)) is None
    assert t.live_version(("m", 9)) is None
    assert t.max_version() is None
    assert not versions.add_loses((1, 0, 1), t.live_version(("m", 2)),
                                  t.ledger_version(("m", 2)))


def test_merge_payload_max_merges_versions():
    a = TombstoneSet()
    a.add([1], [("m", 1)], version=(5, 0, 1))
    b = TombstoneSet()
    b.add([1], [("m", 1)], version=(9, 0, 1))
    b.set_live_version(("m", 2), (4, 0, 2))
    a.merge_payload(b.to_payload())
    assert a.ledger_version(("m", 1)) == (9, 0, 1)
    assert a.live_version(("m", 2)) == (4, 0, 2)
    a.merge_payload(TombstoneSet().to_payload())  # empty merge: no-op
    assert a.ledger_version(("m", 1)) == (9, 0, 1)


# ------------------------------------------------------------ engine gates


def test_versioned_add_replay_is_noop(tmp_path, rng):
    """The repair-queue idempotency fast path: a re-send of a batch the
    replica already holds (same version — anti-entropy healed it, or the
    ack was lost) must not double-apply."""
    clock = HLC(writer_id=1)
    v1 = clock.tick()
    idx, x = build_engine(tmp_path, rng, n=40, version=v1)
    try:
        idx.add_batch(x, [(i,) for i in range(40)],
                      train_async_if_triggered=False, version=v1)
        assert idx.get_idx_data_num() == (0, 40)
        assert idx.mutation_stats()["version_noop_adds"] == 40
        # digest unchanged by the replay
        assert idx.replica_digest() == idx.replica_digest()
    finally:
        idx.retire()


def test_upsert_vs_delete_converges_to_last_writer(tmp_path, rng):
    """The PR 9/10 documented loss, closed: a delete replayed AFTER a
    newer upsert no-ops instead of destroying the upsert; a delete newer
    than the live write still wins."""
    clock = HLC(writer_id=1)
    v1 = clock.tick()
    idx, x = build_engine(tmp_path, rng, n=40, version=v1)
    try:
        v_del = clock.tick()
        assert idx.remove_ids([7], version=v_del) == 1
        v_up = clock.tick()
        idx.upsert([7], rng.standard_normal((1, DIM)).astype(np.float32),
                   version=v_up)
        wait_drained(idx, 41)
        # stale delete replay (e.g. a repair re-send): the upsert wins
        assert idx.remove_ids([7], version=v_del) == 0
        assert 7 in idx.get_ids()
        assert idx.mutation_stats()["version_noop_deletes"] >= 1
        # upsert replay: both halves no-op
        before = idx.get_idx_data_num()
        idx.upsert([7], rng.standard_normal((1, DIM)).astype(np.float32),
                   version=v_up)
        time.sleep(0.2)
        assert idx.get_idx_data_num() == before
        # a NEWER delete still wins
        v_del2 = clock.tick()
        assert idx.remove_ids([7], version=v_del2) == 1
        assert 7 not in idx.get_ids()
    finally:
        idx.retire()


def test_versioned_add_replaces_older_live_row(tmp_path, rng):
    """The anti-entropy refresh path: a PER-ROW-versioned add (the
    delta-pull shape — export_rows_versioned output) of an id that is
    live at a strictly OLDER version replaces the old row in place (the
    in-place upsert a peer pulls during a heal). A plain single-stamp
    batch must NOT replace (shared-id corpora: see the companion test)."""
    clock = HLC(writer_id=1)
    v1 = clock.tick()
    idx, x = build_engine(tmp_path, rng, n=30, version=v1)
    try:
        v2 = clock.tick()
        new_vec = rng.standard_normal((1, DIM)).astype(np.float32)
        idx.add_batch(new_vec, [(5,)], train_async_if_triggered=False,
                      version=[v2])
        deadline = time.time() + 30
        # the drain worker flips ADD -> TRAINED only after the buffer
        # count is already observable as 0: wait for BOTH (like
        # wait_drained) or the search below races the state flip
        while (idx.get_idx_data_num()[0] > 0
               or idx.get_state() != IndexState.TRAINED):
            assert time.time() < deadline
            time.sleep(0.02)
        assert idx.mutation_stats()["version_replaced"] == 1
        scores, meta, _ = idx.search(new_vec, 1)
        assert meta[0][0] == (5,)
        # only ONE live row carries id 5 (the old one is tombstoned)
        sets = idx.id_sets()
        assert sets["live"].count(5) == 1
    finally:
        idx.retire()


def test_plain_versioned_ingest_never_replaces_shared_ids(tmp_path, rng):
    """Regression: metadata ids are NOT required to be unique (the
    integration goldens ingest every row under one shared id). A plain
    single-stamp ingest batch whose id is already live at an older
    version must APPEND like legacy ingest — treating it as an upsert
    would make shared-id corpora eat their own earlier batches."""
    clock = HLC(writer_id=1)
    idx = Index(flat_cfg(tmp_path))
    try:
        x = rng.standard_normal((40, DIM)).astype(np.float32)
        for s in range(0, 40, 10):
            idx.add_batch(x[s:s + 10], [("doc", s + i) for i in range(10)],
                          train_async_if_triggered=False,
                          version=clock.tick())
        wait_drained(idx, 40)
        with racecheck.peeking():  # white-box peek, reviewed
            assert len(idx.tombstones) == 0
        assert idx.mutation_stats()["version_replaced"] == 0
        sets = idx.id_sets()
        assert sets["live"].count("doc") == 40
    finally:
        idx.retire()


def test_refresh_pull_replaces_unversioned_live_row(tmp_path, rng):
    """Review regression (F1): a delta-pull row must displace an
    UNVERSIONED live occupant of its id too (legacy ingest, or the crash
    window that drops uncommitted live versions) — appending beside it
    would leave two live rows for the id and wedge digest convergence
    forever."""
    idx, x = build_engine(tmp_path, rng, n=20)  # unversioned ingest
    try:
        clock = HLC(writer_id=3)
        v = clock.tick()
        new_vec = rng.standard_normal((1, DIM)).astype(np.float32)
        idx.add_batch(new_vec, [(4,)], train_async_if_triggered=False,
                      version=[v])  # the delta-pull shape
        deadline = time.time() + 30
        # buffer-empty alone races the drain worker's ADD -> TRAINED
        # flip (see the companion test): wait for both
        while (idx.get_idx_data_num()[0] > 0
               or idx.get_state() != IndexState.TRAINED):
            assert time.time() < deadline
            time.sleep(0.02)
        sets = idx.id_sets()
        assert sets["live"].count(4) == 1  # replaced, not duplicated
        assert idx.mutation_stats()["version_replaced"] == 1
        _s, meta, _e = idx.search(new_vec, 1)
        assert meta[0][0] == (4,)
    finally:
        idx.retire()


def test_mixed_version_reconcile_records_per_key_versions(tmp_path, rng):
    """Review regression (F3): peer deletes carrying DIFFERENT versions
    apply through the versioned remove path — each key's ledger entry
    records its OWN delete version, and a local live write newer than
    its key's delete survives while older keys delete."""
    clock = HLC(writer_id=1)
    v1 = clock.tick()
    idx, x = build_engine(tmp_path, rng, n=20, version=v1)
    try:
        v_up = clock.tick()
        idx.upsert([8], rng.standard_normal((1, DIM)).astype(np.float32),
                   version=v_up)
        wait_drained(idx, 21)
        vd_old = (v1[0], v1[1] + 1, 9)   # beats v1, loses to v_up
        vd_new = clock.tick()            # beats everything so far
        removed = idx.reconcile_deletes(
            [7, 8], [[7, list(vd_new)], [8, list(vd_old)]])
        assert removed == 1              # 7 deleted; 8's upsert survives
        assert 8 in idx.get_ids() and 7 not in idx.get_ids()
        with racecheck.peeking():  # white-box peek, reviewed
            assert idx.tombstones.ledger_version(7) == vd_new
    finally:
        idx.retire()


def test_versioned_state_survives_restart(tmp_path, rng):
    """SIGKILL-equivalent: versions persist in the sidecar/generation
    payloads, so a stale delete arriving AFTER a restart still loses to
    the pre-restart upsert, and the watermark re-seeds."""
    clock = HLC(writer_id=1)
    v1 = clock.tick()
    idx, x = build_engine(tmp_path, rng, n=30, version=v1)
    v_up = clock.tick()
    idx.upsert([3], rng.standard_normal((1, DIM)).astype(np.float32),
               version=v_up)
    wait_drained(idx, 31)
    assert idx.save()
    idx.retire()
    back = Index.from_storage_dir(str(tmp_path / "shard"),
                                  ignore_buffer=False)
    try:
        stale = clock.tick()  # newer than v_up? no — craft older:
        assert back.remove_ids([3], version=v1) == 0  # older than v_up
        assert 3 in back.get_ids()
        back.assert_min_version(v_up)  # watermark recovered
        with pytest.raises(RuntimeError,
                           match=STALE_READ_REJECTION_PREFIX):
            back.assert_min_version(stale)  # not yet applied here
        assert back.replica_digest() == idx.replica_digest()
    finally:
        back.retire()


def test_reconcile_deletes_versioned_gates(tmp_path, rng):
    clock = HLC(writer_id=1)
    v1 = clock.tick()
    idx, x = build_engine(tmp_path, rng, n=20, version=v1)
    try:
        # peer delete OLDER than the local live write: live wins
        older = (v1[0] - 1, 0, 9)
        assert idx.reconcile_deletes([4], [[4, list(older)]]) == 0
        assert 4 in idx.get_ids()
        # peer delete NEWER: applies, and is recorded at the peer version
        newer = clock.tick()
        assert idx.reconcile_deletes([4], [[4, list(newer)]]) == 1
        assert 4 not in idx.get_ids()
        with racecheck.peeking():  # white-box peek, reviewed
            assert idx.tombstones.ledger_version(4) == newer
        # unversioned peer delete vs a versioned live row: the versioned
        # write outranks the minimal legacy delete
        assert idx.reconcile_deletes([5]) == 0
        assert 5 in idx.get_ids()
    finally:
        idx.retire()


def test_digest_version_plane_sees_content_divergence(tmp_path, rng):
    """Two replicas with IDENTICAL id sets but different write versions
    (one missed an in-place upsert) mismatch on live_vhash while
    live_hash still matches — the divergence the id-only digest could
    never see; a version-aware vs pre-version comparison falls back to
    the id plane."""
    from distributed_faiss_tpu.parallel.antientropy import digests_match

    clock = HLC(writer_id=1)
    v1 = clock.tick()
    a, x = build_engine(tmp_path / "a", rng, n=20, version=v1)
    b, _ = build_engine(tmp_path / "b",
                        np.random.default_rng(12), n=20, version=v1)
    try:
        da, db = a.replica_digest(), b.replica_digest()
        assert digests_match(da, db) and da["live_vhash"] == db["live_vhash"]
        v2 = clock.tick()
        # per-row shape: the in-place refresh (replace) — the id SET
        # stays identical, only the write version moves
        a.add_batch(rng.standard_normal((1, DIM)).astype(np.float32),
                    [(9,)], train_async_if_triggered=False, version=[v2])
        deadline = time.time() + 30
        while a.get_idx_data_num()[0] > 0:
            assert time.time() < deadline
            time.sleep(0.02)
        da, db = a.replica_digest(), b.replica_digest()
        assert da["live_hash"] == db["live_hash"]  # same id set
        assert da["live_vhash"] != db["live_vhash"]
        assert not digests_match(da, db)
        # pre-version peer (no live_vhash): id plane decides
        legacy = {k: v for k, v in db.items() if k != "live_vhash"}
        assert digests_match(da, legacy)
    finally:
        a.retire()
        b.retire()


def test_versioned_export_rows_round_trip(tmp_path, rng):
    clock = HLC(writer_id=1)
    v1 = clock.tick()
    a, x = build_engine(tmp_path / "a", rng, n=20, version=v1)
    b, _ = build_engine(tmp_path / "b",
                        np.random.default_rng(12), n=10, version=v1)
    try:
        emb, meta, vers = a.export_rows_versioned([15, 16])
        assert len(meta) == 2 and all(v == v1 for v in vers)
        b.add_batch(emb, meta, version=vers)
        deadline = time.time() + 30
        while b.get_idx_data_num()[0] > 0:
            assert time.time() < deadline
            time.sleep(0.02)
        assert {15, 16} <= b.get_ids()
        # replaying the same pull is a no-op
        before = b.get_idx_data_num()
        b.add_batch(emb, meta, version=vers)
        assert b.get_idx_data_num() == before
    finally:
        a.retire()
        b.retire()


# --------------------------------------------- read-your-writes watermark


def test_assert_min_version_per_writer(tmp_path, rng):
    clock1, clock2 = HLC(writer_id=1), HLC(writer_id=2)
    v1 = clock1.tick()
    idx, x = build_engine(tmp_path, rng, n=20, version=v1)
    try:
        idx.assert_min_version(None)  # no demand: always fine
        idx.assert_min_version(v1)
        # ANOTHER writer's higher wall-clock version must not satisfy a
        # demand from writer 2 (per-writer watermarks)
        v2 = (v1[0] + 1, 0, 2)
        with pytest.raises(RuntimeError,
                           match=STALE_READ_REJECTION_PREFIX):
            idx.assert_min_version(v2)
        idx.remove_ids([1], version=v2)
        idx.assert_min_version(v2)
    finally:
        idx.retire()


def test_stale_read_matcher_matches_live_raise_site(tmp_path, rng):
    """Drift guard (the drain-failover precedent): the replicated read
    path classifies the stale-read rejection by the shared prefix
    constant — a reworded raise site must fail THIS test, not silently
    disable the failover."""
    clock = HLC(writer_id=1)
    idx, x = build_engine(tmp_path, rng, n=20, version=clock.tick())
    try:
        future = (clock.tick()[0] + 10_000, 0, 1)
        with pytest.raises(RuntimeError) as ei:
            idx.assert_min_version(future)
        wrapped = rpc.ServerException(
            f"remote traceback:\nRuntimeError: {ei.value}")
        assert replication.stale_read_failover_eligible(wrapped)
        assert not replication.stale_read_failover_eligible(
            rpc.ServerException("Server has no index with id=t"))
        assert not replication.stale_read_failover_eligible(
            RuntimeError(str(ei.value)))  # not a ServerException
        # the format constant really is what the raise site used
        assert STALE_READ_REJECTION_FMT.split("{")[0] in str(ei.value)
    finally:
        idx.retire()


# ------------------------------------------------ generation-pinned reads


def test_search_at_generation_serves_pinned_snapshot(tmp_path, rng):
    clock = HLC(writer_id=1)
    idx, x = build_engine(tmp_path, rng, n=40, version=clock.tick())
    try:
        assert idx.save()
        g1 = idx.current_generation()
        idx.remove_ids([5], version=clock.tick())
        assert idx.save()  # delete-only change commits a new generation
        g2 = idx.current_generation()
        assert g2 == g1 + 1
        # pinned read at g1: the deleted id still serves
        _s, meta, _e = idx.search_at_generation(x[5:6], 3, generation=g1)
        assert meta[0][0] == (5,)
        # pinned read at g2 (and the live path): it does not
        _s, meta2, _e = idx.search_at_generation(x[5:6], 3, generation=g2)
        assert (5,) not in [m for m in meta2[0] if m]
        _s, live_meta, _e = idx.search(x[5:6], 3)
        assert (5,) not in [m for m in live_meta[0] if m]
        # unknown / pruned generation: clear application error
        with pytest.raises(RuntimeError, match="not retained"):
            idx.search_at_generation(x[:1], 3, generation=g2 + 50)
    finally:
        idx.retire()


def test_retain_generations_knob_widens_the_window(tmp_path, rng,
                                                   monkeypatch):
    monkeypatch.setenv("DFT_RETAIN_GENERATIONS", "3")
    clock = HLC(writer_id=1)
    idx, x = build_engine(tmp_path, rng, n=30, version=clock.tick())
    try:
        assert idx.versioning.retain_generations == 3
        gens = []
        for i in range(3):
            idx.remove_ids([i], version=clock.tick())
            assert idx.save()
            gens.append(idx.current_generation())
        on_disk = [g for g, _m in serialization.list_generations(
            str(tmp_path / "shard"))]
        assert on_disk == sorted(gens, reverse=True)  # all 3 retained
        _s, meta, _e = idx.search_at_generation(x[1:2], 2,
                                                generation=gens[0])
        assert meta[0][0] == (1,)  # deleted in gens[1], alive in gens[0]
    finally:
        idx.retire()


# ----------------------------------------------------- client plumbing


class FakeStub:
    """Quacks like rpc.Client for the versioned write fan-out: records
    every call with kwargs, optionally rejects the ``version`` keyword
    like a pre-version server, and serves a watermark through
    get_id_sets."""

    def __init__(self, sid, legacy=False, watermark=None, fail=False):
        self.id = sid
        self.host, self.port = "fake", 9000 + sid
        self.legacy = legacy
        self.watermark = watermark
        self.fail = fail
        self.calls = []

    def generic_fun(self, fname, args=(), kwargs=None, **_kw):
        if self.fail:
            raise ConnectionRefusedError(f"rank {self.id} down")
        if self.legacy and kwargs and "version" in kwargs:
            raise rpc.ServerException(
                f"TypeError: {fname}() got an unexpected keyword "
                "argument 'version'")
        self.calls.append((fname, args, dict(kwargs or {})))
        if fname == "get_id_sets":
            return {"live": [], "dead": [], "watermark": self.watermark}
        if fname == "get_shard_group":
            return None
        if fname == "remove_ids":
            return 1
        return f"ok-{self.id}"


def make_client(stubs, rcfg=None, vcfg=None):
    c = object.__new__(IndexClient)
    c.sub_indexes = stubs
    c.num_indexes = len(stubs)
    c.pool = ThreadPool(max(len(stubs), 1))
    c.cur_server_ids = {}
    c._rng = random.Random(0)
    c.retry = rpc.RetryPolicy(max_attempts=2, base_delay=0.001, jitter=0.0)
    c._stats_lock = lockdep.lock("IndexClient._stats_lock")
    from collections import deque

    c.reroutes = deque(maxlen=8)
    c.stats = tracing.LatencyStats()
    c.counters = AtomicCounters(
                  ("reroutes", "failovers", "under_replicated", "quorum_failures"))
    c.rcfg = rcfg or ReplicationCfg()
    eff = min(c.rcfg.replication, max(len(stubs), 1))
    c.quorum = replication.quorum_size(eff, min(c.rcfg.write_quorum, eff))
    c.repair_queue = replication.RepairQueue(c.rcfg.repair_queue_len)
    c._preferred = {}
    c.membership = replication.MembershipTable(
        replication.assign_groups(len(stubs), c.rcfg.replication))
    c.cfg = None
    c.vcfg = vcfg if vcfg is not None else VersioningCfg()
    c._hlc = HLC(writer_id=42) if c.vcfg.enabled else None
    c._seeded = set()
    c._last_write_version = {}
    c._unversioned_ranks = set()
    return c


def test_client_stamps_one_version_per_batch_across_replicas():
    a, b = FakeStub(0), FakeStub(1)
    client = make_client([a, b], rcfg=ReplicationCfg(replication=2))
    client.cur_server_ids["idx"] = 0
    client.add_index_data("idx", np.zeros((2, 8), np.float32), [1, 2])
    va = [kw["version"] for f, _a, kw in a.calls if f == "add_index_data"]
    vb = [kw["version"] for f, _a, kw in b.calls if f == "add_index_data"]
    assert va and va == vb  # the SAME stamp reached both replicas
    assert client.last_write_version("idx") == va[0]
    # a second batch gets a strictly newer stamp
    client.add_index_data("idx", np.zeros((1, 8), np.float32), [3])
    va2 = [kw["version"] for f, _a, kw in a.calls if f == "add_index_data"]
    assert versions.compare(va2[-1], va[0]) > 0


def test_client_seeds_clock_from_cluster_watermark():
    remote = (int(time.time() * 1000) + 90_000, 3, 7)  # far-future peer
    a = FakeStub(0, watermark=list(remote))
    client = make_client([a])
    client.cur_server_ids["idx"] = 0
    client.add_index_data("idx", np.zeros((1, 8), np.float32), [1])
    assert any(f == "get_id_sets" for f, _a, _k in a.calls)  # seeded once
    v = client.last_write_version("idx")
    assert versions.compare(v, remote) > 0
    # second mutation does not re-seed
    n_seeds = sum(1 for f, _a, _k in a.calls if f == "get_id_sets")
    client.remove_ids("idx", [1])
    assert sum(1 for f, _a, _k in a.calls
               if f == "get_id_sets") == n_seeds


def test_repair_resend_carries_original_version():
    """ISSUE 12 satellite: the repair record holds the batch's ORIGINAL
    stamp, and the re-send presents it — so a replica that already
    healed via anti-entropy no-ops instead of double-applying."""
    live, dead = FakeStub(0), FakeStub(1, fail=True)
    client = make_client([live, dead],
                         rcfg=ReplicationCfg(replication=2, write_quorum=1))
    client.cur_server_ids["idx"] = 0
    client.add_index_data("idx", np.zeros((2, 8), np.float32), [1, 2])
    v = client.last_write_version("idx")
    assert len(client.repair_queue) == 1
    item = client.repair_queue.drain()[0]
    assert item["version"] == v
    client.repair_queue.record(item)
    dead.fail = False  # rank healed (e.g. by the sweep)
    out = client.repair_under_replicated()
    assert out == {"repaired": 1, "still_pending": 0}
    resent = [kw for f, _a, kw in dead.calls if f == "add_index_data"]
    assert resent and resent[0]["version"] == v


def test_versioned_delete_repair_record_carries_version():
    live, dead = FakeStub(0), FakeStub(1, fail=True)
    client = make_client([live, dead],
                         rcfg=ReplicationCfg(replication=2, write_quorum=1))
    client.remove_ids("idx", [1, 2])
    v = client.last_write_version("idx")
    item = client.repair_queue.drain()[0]
    assert item["op"] == "remove_ids" and item["version"] == v


def test_client_degrades_gracefully_against_pre_version_server():
    """Rolling-upgrade compat: a rank that rejects the ``version``
    keyword is retried without it and remembered — ingest never wedges,
    and the degrade is visible in get_replication_stats."""
    new, old = FakeStub(0), FakeStub(1, legacy=True)
    client = make_client([new, old], rcfg=ReplicationCfg(replication=2))
    client.cur_server_ids["idx"] = 0
    client.add_index_data("idx", np.zeros((1, 8), np.float32), [1])
    assert [kw for f, _a, kw in old.calls
            if f == "add_index_data"] == [{}]  # un-versioned re-send
    assert "version" in [kw for f, _a, kw in new.calls
                         if f == "add_index_data"][0]
    stats = client.get_replication_stats()
    assert stats["versioning"]["enabled"] is True
    assert stats["versioning"]["unversioned_ranks"] == [1]
    # subsequent writes skip the doomed attempt entirely
    calls_before = len(old.calls)
    client.add_index_data("idx", np.zeros((1, 8), np.float32), [2])
    extra = old.calls[calls_before:]
    assert [kw for f, _a, kw in extra if f == "add_index_data"] == [{}]


def test_failed_write_does_not_poison_read_your_writes():
    """Review regression (F2): a write that acks NOWHERE must not become
    the read-your-writes floor — no replica will ever incorporate its
    stamp, so RYW searches would reject everywhere until the next
    successful write."""
    stubs = [FakeStub(0, fail=True), FakeStub(1, fail=True)]
    client = make_client(stubs, rcfg=ReplicationCfg(replication=2))
    client.cur_server_ids["idx"] = 0
    with pytest.raises(RuntimeError, match="every rank"):
        client.add_index_data("idx", np.zeros((1, 8), np.float32), [1])
    assert client.last_write_version("idx") is None
    # ...and an acked write DOES move the floor
    for s in stubs:
        s.fail = False
    client.add_index_data("idx", np.zeros((1, 8), np.float32), [1])
    assert client.last_write_version("idx") is not None


def test_seed_clock_observes_every_replica_not_first():
    """Review regression (F4): a quorum-minority write lives only on
    SOME replicas — seeding must max-merge every reachable watermark,
    not stop at the first responder (a laggard answering first would
    let a backward-clock restart stamp below the client's own writes)."""
    now_ms = int(time.time() * 1000)
    behind = [now_ms + 30_000, 0, 7]
    ahead = [now_ms + 90_000, 2, 7]
    stubs = [FakeStub(0, watermark=behind), FakeStub(1, watermark=ahead)]
    client = make_client(stubs, rcfg=ReplicationCfg(replication=2))
    client.cur_server_ids["idx"] = 0
    client.add_index_data("idx", np.zeros((1, 8), np.float32), [1])
    assert versions.compare(client.last_write_version("idx"),
                            tuple(ahead)) > 0


def test_seed_clock_retries_after_total_outage():
    """Review regression: a transient total outage during the first
    mutation must not latch 'seeded' — the next mutation re-seeds, or a
    backward-clock restart would stamp below its own pre-restart writes
    forever."""
    remote = (int(time.time() * 1000) + 120_000, 0, 7)
    a = FakeStub(0, watermark=list(remote), fail=True)
    client = make_client([a])
    client.cur_server_ids["idx"] = 0
    with pytest.raises(RuntimeError, match="every rank"):
        client.add_index_data("idx", np.zeros((1, 8), np.float32), [1])
    with client._stats_lock:
        assert "idx" not in client._seeded  # outage: seed NOT latched
    a.fail = False  # cluster back: the next mutation re-seeds
    client.add_index_data("idx", np.zeros((1, 8), np.float32), [1])
    with client._stats_lock:
        assert "idx" in client._seeded
    assert versions.compare(client.last_write_version("idx"), remote) > 0


def test_full_sync_vetoed_by_gated_peer_delete(tmp_path):
    """Review regression: a local live write that OUTRANKED a peer's
    delete must veto the full-snapshot sync — the peer snapshot holds
    that id DELETED, so installing it would lose the winning upsert even
    though local_only/local_newer/extra_dead are all empty. The heal
    must fall back to the chunked delta instead."""
    import socket
    import threading

    from distributed_faiss_tpu.parallel.server import IndexServer
    from distributed_faiss_tpu.utils.config import AntiEntropyCfg

    def free_port():
        s = socket.socket()
        s.bind(("", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    pa, pb = free_port(), free_port()
    disc = str(tmp_path / "disc.txt")
    with open(disc, "w") as f:
        f.write(f"2\nlocalhost,{pa}\nlocalhost,{pb}\n")
    # delta_max_rows=1 makes ANY multi-row divergence full-sync-eligible
    cfg = AntiEntropyCfg(interval_s=600, delta_max_rows=1)
    servers = []
    try:
        rng = np.random.default_rng(3)
        x = rng.standard_normal((30, DIM)).astype(np.float32)
        clock = HLC(writer_id=1)
        v1 = clock.tick()
        for rank, port, path in ((0, pa, "a"), (1, pb, "b")):
            srv = IndexServer(rank, str(tmp_path / path),
                              discovery_path=disc, antientropy_cfg=cfg)
            srv.set_shard_group(0)
            threading.Thread(target=srv.start_blocking, args=(port,),
                             daemon=True).start()
            servers.append(srv)
        time.sleep(0.3)
        for srv in servers:
            srv.create_index("t", IndexCfg(index_builder_type="flat",
                                           dim=DIM, metric="l2",
                                           train_num=10))
            srv.add_index_data("t", x, [(i,) for i in range(30)],
                               version=v1)
            deadline = time.time() + 60
            while not (srv.get_state("t") == IndexState.TRAINED
                       and srv.get_aggregated_ntotal("t") == 0):
                assert time.time() < deadline
                time.sleep(0.05)
        a, b = servers
        # peer B: delete id 5 @v2 and add MANY rows A lacks (> delta_max)
        v2 = clock.tick()
        b._get_index("t").remove_ids([5], version=v2)
        extra = rng.standard_normal((8, DIM)).astype(np.float32)
        b._get_index("t").add_batch(extra, [(100 + i,) for i in range(8)],
                                    train_async_if_triggered=False,
                                    version=clock.tick())
        # local A: upsert id 5 at a NEWER version — it must survive
        v3 = clock.tick()
        a._get_index("t").upsert([5], x[5:6] + 1.0, version=v3)
        deadline = time.time() + 60
        while (a.get_aggregated_ntotal("t") > 0
               or b.get_aggregated_ntotal("t") > 0):
            assert time.time() < deadline
            time.sleep(0.05)
        out = a._antientropy.sweep_once()
        healed = [h for h in out["healed"] if h["index_id"] == "t"]
        assert healed and healed[0]["full_sync"] is False, healed
        assert 5 in a._get_index("t").get_ids(), "full sync ate the upsert"
        with racecheck.peeking():  # white-box peek, reviewed
            assert a._get_index("t").tombstones.live_version(5) == v3
        assert {100 + i for i in range(8)} <= a._get_index("t").get_ids()
    finally:
        for srv in servers:
            srv.stop()


def test_versioning_off_sends_no_version():
    a = FakeStub(0)
    client = make_client([a], vcfg=VersioningCfg(enabled=False))
    client.cur_server_ids["idx"] = 0
    client.add_index_data("idx", np.zeros((1, 8), np.float32), [1])
    adds = [kw for f, _a, kw in a.calls if f == "add_index_data"]
    assert adds == [{}]
    assert not any(f == "get_id_sets" for f, _a, _k in a.calls)
    assert client.last_write_version("idx") is None
