"""The exact-search builder (``flat``) on the served path, on the CPU.

``FlatIndex`` through an engine against a numpy scan, both metrics and both
search branches; its launch ledger (docs/OPERATIONS.md#stage-ledger):
``engine.scan`` runs from the dispatch to the end of the wait for the scan,
the per-block branch books ``engine.feed``, a flat rank's launch-loop
stages add up to its batcher thread's wall clock; ``engine.scan_rows``
books the store's capacity for every block scanned, ``engine.scan_prefilter``
the scans whose per-chunk top-k took the prefilter; ``engine.store_grow``
counts the reallocations of a ``DeviceVectorStore``. Nothing timed here is a
speed.
"""

import threading
import time

import jax
import numpy as np
import pytest

from distributed_faiss_tpu import (
    Index,
    IndexCfg,
    IndexClient,
    IndexServer,
    IndexState,
)
from distributed_faiss_tpu.models import base, flat
from distributed_faiss_tpu.utils import tracing
from distributed_faiss_tpu.utils.tracing import LatencyStats
from test_observability import free_port, wait_listening, write_discovery
from test_stage_ledger import INDEX_ID, best_of, launch_loop_closure

pytestmark = pytest.mark.observability

ROWS, DIM = 6000, 32
BLOCK = base.pick_query_block(65536 * 4)  # rows a flat launch scans at once


def seeded(rows=ROWS, dim=DIM, seed=7):
    return np.random.default_rng(seed).standard_normal((rows, dim)).astype(np.float32)


def engine_of(storage, x, metric="l2", batch=1500):
    """A trained engine holding ``x``, added in ``batch``-row calls with the
    row number as metadata, every buffer drained."""
    cfg = IndexCfg(index_builder_type="flat", dim=x.shape[1], metric=metric,
                   train_num=batch, buffer_bsz=batch)
    cfg.index_storage_dir = str(storage)
    idx = Index(cfg)
    for s in range(0, x.shape[0], batch):
        idx.add_batch(x[s:s + batch], list(range(s, min(s + batch, x.shape[0]))),
                      train_async_if_triggered=False)
    return drained(idx)


def drained(idx):
    """``idx`` once it is trained and its buffer is empty."""
    deadline = time.time() + 120
    while idx.get_state() != IndexState.TRAINED or idx.get_idx_data_num()[0] > 0:
        assert time.time() < deadline, "train/drain timed out"
        time.sleep(0.02)
    return idx


def numpy_scan(x, q, k, metric):
    """(scores, ids) of the k best rows by a float64 scan, best first."""
    x64, q64 = x.astype(np.float64), q.astype(np.float64)
    if metric == "l2":
        s = ((q64 ** 2).sum(1)[:, None] - 2.0 * q64 @ x64.T
             + (x64 ** 2).sum(1)[None, :])
        order = np.argsort(s, axis=1, kind="stable")[:, :k]
    else:
        s = q64 @ x64.T
        order = np.argsort(-s, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(s, order, 1), order


def row_of(stats, name):
    return stats.get(name, {"count": 0, "total_s": 0.0})


# ------------------------------------------------------- against the scan


@pytest.mark.parametrize("metric", ["l2", "dot"])
@pytest.mark.parametrize("nq,branch", [(48, "block"), (BLOCK + 200, "fused")])
def test_a_flat_engine_returns_the_numpy_scans_rows(tmp_path, metric, nq, branch):
    x = seeded()
    idx = engine_of(tmp_path, x, metric)
    q = seeded(nq, seed=11)
    scores, meta, _ = idx.search(q, 10)
    want_s, want_i = numpy_scan(x, q, 10, metric)
    got = np.array(meta)
    assert got.shape == (nq, 10)
    assert (got == want_i).mean() == 1.0  # gaussian rows: no ties to break
    assert np.allclose(scores, want_s, rtol=1e-4, atol=1e-4)
    # and the branch the test names is the one that ran
    stats = idx.perf_stats()
    fed = row_of(stats, "engine.feed")["count"]
    assert fed == 1 and row_of(stats, "engine.scan")["count"] == 1
    blocks = row_of(stats, "engine.scan_rows")["total_s"] / idx.tpu_index.store.cap
    assert blocks == (1 if branch == "block" else 2)


# ------------------------------------------------------ the launch ledger


@pytest.mark.parametrize("nq", [600, BLOCK + 8], ids=["block", "fused"])
def test_engine_scan_covers_the_wait_for_the_scan(tmp_path, monkeypatch, nq):
    """A scan whose result is 0.4 s late (a host callback the outputs hang
    on: the dispatch returns at once) is booked to ``engine.scan``; the
    fetch that follows finds the result there."""
    idx = engine_of(tmp_path, seeded(2000))
    q = seeded(nq, seed=3)

    def late(vals):
        def host(v):
            time.sleep(0.4)
            return v
        return jax.pure_callback(host, jax.ShapeDtypeStruct(vals.shape, vals.dtype),
                                 vals)

    late_jit = jax.jit(late)

    def slowed(fn):
        def run(*args, **kw):
            vals, ids = fn(*args, **kw)
            return late_jit(vals), ids
        return run

    monkeypatch.setattr(flat.distance, "knn", slowed(flat.distance.knn))
    monkeypatch.setattr(flat, "_flat_search_fused", slowed(flat._flat_search_fused))
    idx.search(q, 5)  # every compile outside the timing
    before = idx.perf_stats()
    idx.search(q, 5)
    after = idx.perf_stats()

    def moved(name):
        return row_of(after, name)["total_s"] - row_of(before, name)["total_s"]

    assert moved("engine.scan") >= 0.4
    assert moved("engine.refine_fetch") < 0.2
    assert moved("device_search_s") >= moved("engine.scan")


def test_scan_rows_books_the_capacity_for_every_block_scanned(tmp_path):
    idx = engine_of(tmp_path, seeded())
    cap = idx.tpu_index.store.cap
    assert cap == 8192 and idx.tpu_index.store.ntotal == ROWS
    # before the first scan the row has fired nothing
    assert row_of(idx.perf_stats(), "engine.scan_rows")["count"] == 0
    idx.search(seeded(16, seed=1), 5)
    one = idx.perf_stats()["engine.scan_rows"]
    assert (one["count"], one["total_s"]) == (1, float(cap))
    idx.search(seeded(2 * BLOCK + 1, seed=2), 5)  # three blocks, padded to four
    two = idx.perf_stats()["engine.scan_rows"]
    assert (two["count"], two["total_s"]) == (2, float(cap + 4 * cap))
    assert idx.perf_stats()["engine.scan"]["count"] == 2


def test_scan_rows_stands_at_zero_beside_engine_scan_where_nothing_books_it(tmp_path):
    """An IVF index books ``engine.scan`` and no ``engine.scan_rows``: the
    engine serves the row at zero, 0 of n and not a missing row."""
    x = seeded(3000)
    cfg = IndexCfg(index_builder_type="ivf_simple", dim=DIM, metric="l2",
                   train_num=2000, centroids=16, nprobe=4)
    cfg.index_storage_dir = str(tmp_path)
    idx = Index(cfg)
    idx.add_batch(x, list(range(3000)), train_async_if_triggered=False)
    idx.train()
    drained(idx).search(x[:8], 5)
    stats = idx.perf_stats()
    assert stats["engine.scan"]["count"] >= 1
    assert stats["engine.scan_rows"]["count"] == 0
    assert stats["engine.scan_prefilter"]["count"] == 0
    # nor the PQ scan's pair of rows (PR 35)
    assert stats["engine.scan_adc_cols"]["count"] == 0
    assert stats["engine.scan_adc_cols_skipped"]["count"] == 0


def test_scan_prefilter_counts_the_scans_whose_top_k_chose_segments_first(tmp_path):
    """The host books what the traced code does, by the same rule on
    (k, chunk): the store's 8192 rows are one chunk, 64 segments, so k = 10
    takes the prefilter (64 >= 4 x 10) and k = 20 the two-stage reduction."""
    idx = engine_of(tmp_path, seeded())
    assert idx.tpu_index.store.cap == 8192
    assert flat.distance.topk_prefilters(10, 8192)
    assert not flat.distance.topk_prefilters(20, 8192)
    assert row_of(idx.perf_stats(), "engine.scan_prefilter")["count"] == 0
    # a scan that did not take it: the row is served at zero, not left out
    idx.search(seeded(4, seed=5), 20)
    stats = idx.perf_stats()
    assert stats["engine.scan"]["count"] == 1
    assert stats["engine.scan_prefilter"]["count"] == 0
    idx.search(seeded(16, seed=1), 10)
    assert idx.perf_stats()["engine.scan_prefilter"]["count"] == 1
    idx.search(seeded(2 * BLOCK + 1, seed=2), 10)  # one fused scan of four blocks
    idx.search(seeded(16, seed=3), 20)
    stats = idx.perf_stats()
    assert stats["engine.scan"]["count"] == 4
    assert stats["engine.scan_prefilter"]["count"] == 2


def test_store_grow_counts_the_doublings_of_a_store_grown_past_min_cap():
    store = base.DeviceVectorStore((8,), np.float32)
    sink = LatencyStats()
    rows = np.ones((1000, 8), np.float32)
    caps = []
    with tracing.stage("test.adds", sink=sink):
        for _ in range(12):
            store.add(rows)
            caps.append(store.cap)
    # 1024-row write buckets: the allocation at MIN_CAP, then a doubling
    # when 4000 + 1024 and when 8000 + 1024 rows no longer fit
    assert sorted(set(caps)) == [4096, 8192, 16384]
    grown = sink.summary()["engine.store_grow"]
    assert grown["count"] == 3 and grown["total_s"] > 0
    assert np.array_equal(store.all_rows(), np.ones((12000, 8), np.float32))
    # without a sink (a store used as a library) the stage books nothing
    # and the store grows all the same
    lone = base.DeviceVectorStore((8,), np.float32)
    lone.add(rows)
    assert lone.cap == 4096


def test_a_flat_engine_books_its_stores_growth_in_the_add_drain(tmp_path):
    idx = engine_of(tmp_path, seeded())  # 6000 rows in 1500-row drains
    stats = idx.perf_stats()
    # 2048-row write buckets: 4096 at the first drain, 8192 at the third
    assert stats["engine.store_grow"]["count"] == 2
    assert stats["engine.store_grow"]["total_s"] <= stats["engine.add_drain"]["total_s"]


# ------------------------------------------------------------ a flat rank


@pytest.fixture(scope="module")
def rank(tmp_path_factory):
    """One rank holding a flat index, and a client: ``test_stage_ledger``'s
    fixture with the exact-search builder in the IVF-PQ one's place."""
    tmp = tmp_path_factory.mktemp("flat_ledger")
    # rows enough that a launch lasts tens of milliseconds here: the python
    # between two stages, 0.4 ms a window, is then under the 2% it is held to
    x = seeded(120000)
    idx = engine_of(tmp / "s", x, batch=20000)
    port = free_port()
    srv = IndexServer(0, str(tmp))
    srv.indexes[INDEX_ID] = idx
    srv._wire_engine(idx)
    threading.Thread(target=srv.start_blocking, args=(port,),
                     name=f"flat-ledger-server:{port}", daemon=True).start()
    assert wait_listening(port)
    client = IndexClient(write_discovery(tmp, [port]))
    client.cfg = idx.cfg
    for rows in (8, 8, 16, 32, 64):  # negotiate the binary wire, and compile
        client.search(x[:rows], 5, INDEX_ID)  # every bucket a window can have
    yield {"srv": srv, "client": client, "idx": idx, "x": x}
    client.close()
    srv.stop()


def test_a_flat_ranks_launch_loop_stages_are_booked_once_a_window(rank):
    """As ``test_stage_ledger`` holds it for IVF: with two windows in flight
    every stage and count row is booked once a window, and the launch's own
    three stages are what launch-to-fetch is made of — ``engine.feed``
    among them, and ``engine.scan_rows`` once a scan."""
    best_of(3, lambda: launch_loop_closure(rank, ("engine.scan_rows",)), 0.03)


def test_a_flat_rank_serves_the_new_rows_in_get_perf_stats(rank):
    served = rank["client"].get_perf_stats()[0]["engine"][INDEX_ID]
    cap = rank["idx"].tpu_index.store.cap
    assert served["engine.store_grow"]["count"] >= 1
    assert served["engine.scan_rows"]["total_s"] == (
        served["engine.scan_rows"]["count"] * float(cap))
    assert served["engine.feed"]["count"] == served["engine.scan"]["count"] > 0
    assert served["engine.scan_fused"]["count"] == 0
