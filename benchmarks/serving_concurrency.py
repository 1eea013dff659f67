#!/usr/bin/env python3
"""Multi-client serving throughput: dynamic batching vs per-call launches.

Measures aggregate QPS (and per-request p99 latency) of T concurrent
client threads, each issuing B-query searches against one engine Index:

  percall  — each caller drives its own device launch (the reference's
             serving model: one launch per RPC under index_lock)
  natural  — the SearchBatcher with window 0 (callers arriving while a
             launch is in flight coalesce into the next one)
  window   — SearchBatcher with a small wait window (leader waits
             window_ms for followers before launching)

plus the serving-scheduler A/B (``--scheduler``, default both arms):

  scheduler_off — the per-call reference serving shape (same path as
                  percall: one padded device batch per request)
  scheduler_on  — requests flow through serving.SearchScheduler (bounded
                  queue + batcher thread, 2 ms flush window), the path
                  the RPC serving loops use

plus the RPC-multiplexing A/B (``--mux``, default both arms): a real
IndexServer + ONE IndexClient driven by ``--inflight`` threads over
loopback.

  rpc_mux_off — the serial stub (DFT_RPC_MUX=0): the stub lock holds the
                connection for the whole round trip, so one call is in
                flight per rank no matter how many caller threads
  rpc_mux_on  — pipelined stub: the whole in-flight window rides one
                connection and reaches the server scheduler TOGETHER, so
                a single client's W concurrent searches become merged
                device batches (the row reports the max merged
                batch_requests the scheduler observed — >1 is impossible
                in the off arm)

plus the tracing-overhead A/B (``--trace-sample``, off by default): the
mux serving path driven with DFT_TRACE_SAMPLE=0 vs 1 on the same
engine — one JSON row with both arms' qps/p99 and the deltas, so the
observability subsystem's "near-zero when off, bounded when sampled"
claim is a measured number (RESULTS.md),

plus the mesh-sharded serving A/B (``--mesh``, off by default): a
mesh-backed engine (flat corpus sharded over a virtual 8-device CPU
mesh, forced via XLA_FLAGS before jax imports) served per-request vs
through the scheduler:

  mesh_scheduler_off — one device launch per request on the mesh
  mesh_scheduler_on  — merged windows through serving.SearchScheduler;
                       the row reports the engine's new launch counters
                       (launches_per_window_max MUST be exactly 1.0:
                       one pjit launch per merged batch, results leave
                       the device once per window)

The scheduler AND mux arms cross-check RESULT IDENTITY: every client's
results must be byte-identical to direct/sequential serving (the batch
or connection a row rides must not change its answer).

Where the per-dispatch floor is large next to a request's compute,
batching multiplies multi-client QPS; on CPU the floor is tiny so the gap
narrows. The floor on a chip the process holds is unmeasured (ROADMAP S3).

Prints one JSON line per mode/arm (qps, p99_ms) for the trajectory file.
"""

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.chdir(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_clients(search, queries, n_threads, reps, k=10):
    """Drive n_threads concurrent callers of ``search(q, k)``; returns
    (aggregate qps, p99 per-request latency in ms)."""
    barrier = threading.Barrier(n_threads + 1)
    errs = []
    lats = [[] for _ in range(n_threads)]

    def client(tid):
        q = queries[tid]
        barrier.wait()
        try:
            for _ in range(reps):
                t0 = time.perf_counter()
                search(q, k)
                lats[tid].append(time.perf_counter() - t0)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=client, args=(t,)) for t in range(n_threads)]
    for t in ts:
        t.start()
    barrier.wait()
    t0 = time.time()
    for t in ts:
        t.join()
    dt = time.time() - t0
    assert not errs, errs[:1]
    total = n_threads * reps * queries[0].shape[0]
    all_lats = np.array([x for row in lats for x in row])
    return total / dt, float(np.percentile(all_lats, 99) * 1000.0)


def make_search(idx, mode):
    from distributed_faiss_tpu.utils.batching import SearchBatcher

    if mode == "percall":
        return idx._device_search
    if mode == "natural":
        return SearchBatcher(idx._device_search, window_ms=0).search
    if mode == "window":
        return SearchBatcher(idx._device_search, window_ms=3).search
    raise ValueError(mode)


def scheduler_arms(idx, arm):
    """(name, search(q, k)) pairs for the requested --scheduler arm(s)."""
    from distributed_faiss_tpu.serving import SearchScheduler
    from distributed_faiss_tpu.utils.config import SchedulerCfg

    arms = []
    if arm in ("off", "both"):
        # the reference serving shape: one padded launch per request
        arms.append(("scheduler_off", idx._device_search))
    if arm in ("on", "both"):
        sched = SearchScheduler(
            lambda _iid, q, k, _re: idx._device_search(q, k),
            SchedulerCfg(max_wait_ms=2.0, max_batch_rows=1024, max_queue=512),
            name="bench-batcher",
        )
        arms.append(("scheduler_on",
                     lambda q, k: sched.submit("bench", q, k)))
    return arms


def check_identity(idx, arms, queries, k, reps=3):
    """Every client's results must match the direct per-call launch exactly
    — with the arm driven CONCURRENTLY, so the scheduler arm's rows really
    ride merged batches (a sequential probe would submit one request per
    flush and never reach the concat/split path this check exists for)."""
    golden = [idx._device_search(q, k) for q in queries]
    identical = {}
    for name, search in arms:
        res = [[] for _ in queries]
        errs = []
        barrier = threading.Barrier(len(queries))

        def client(t, search=search, res=res, barrier=barrier, errs=errs):
            barrier.wait()
            try:
                for _ in range(reps):
                    res[t].append(search(queries[t], k))
            except Exception as e:  # a silent dead thread would leave
                errs.append(e)      # res[t] empty and the check vacuous

        ts = [threading.Thread(target=client, args=(t,))
              for t in range(len(queries))]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs, (name, errs[:1])
        arm_ok = True
        for t, (g_scores, g_ids) in enumerate(golden):
            assert len(res[t]) == reps, (name, t, len(res[t]))
            for scores, ids in res[t]:
                if not (np.array_equal(scores, g_scores)
                        and np.array_equal(ids, g_ids)):
                    arm_ok = False
        identical[name] = arm_ok  # per arm: a scheduler divergence must
    return identical              # not stamp the direct-launch row false


def _loopback_server(idx):
    """One IndexServer (blocking loop, scheduler on) serving the trained
    engine over loopback: returns (srv, discovery path, teardown). Light
    teardown only — no srv.stop(), which would save the whole bench
    corpus; the process exits right after the arms."""
    import socket as socketlib
    import tempfile

    from distributed_faiss_tpu.parallel.server import IndexServer
    from distributed_faiss_tpu.utils.config import SchedulerCfg

    tmp = tempfile.mkdtemp(prefix="mux_bench_")
    s = socketlib.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    srv = IndexServer(0, tmp, scheduler_cfg=SchedulerCfg(max_wait_ms=2.0))
    srv.indexes["bench"] = idx  # serve the trained engine directly
    srv._wire_engine(idx)
    threading.Thread(target=srv.start_blocking, args=(port,),
                     daemon=True).start()
    deadline = time.time() + 10
    while time.time() < deadline:
        try:
            socketlib.create_connection(("localhost", port), timeout=1).close()
            break
        except OSError:
            time.sleep(0.05)
    disc = os.path.join(tmp, "disc.txt")
    with open(disc, "w") as f:
        f.write(f"1\nlocalhost,{port}\n")

    def teardown():
        srv._stopping.set()
        if srv.socket is not None:
            try:
                srv.socket.close()
            except OSError:
                pass
        if srv.scheduler is not None:
            srv.scheduler.stop()

    return srv, disc, teardown


def _warmed_request_list(idx, queries, k, inflight, mux_batch):
    """Per-caller request batches for a loopback-client arm, with every
    merged-batch jit bucket the scheduler can produce (2..W coalesced
    requests) pre-warmed: without this, first-use compiles of the larger
    row counts land inside the measured window and dominate the
    pipelined arm's p99 (a serial arm only ever launches the native
    size). Shared by the mux and trace-overhead A/Bs so both measure
    identical compile behavior."""
    qlist = [queries[t % len(queries)][:mux_batch] for t in range(inflight)]
    warm = np.concatenate(qlist, axis=0)
    for rows in range(mux_batch, mux_batch * inflight + 1, mux_batch):
        idx.search_batched(warm[:rows], k)
    return qlist


def run_mux_arms(idx, queries, k, arm, inflight, reps, backend,
                 mux_batch=4):
    """RPC-level A/B: one IndexServer (blocking loop, scheduler on) serving
    the already-trained engine, ONE IndexClient per arm, ``inflight``
    caller threads. Returns one JSON-ready row per arm.

    Requests are ``mux_batch`` rows each (default 4): individual user
    queries are small, and small launches sit on the per-dispatch floor —
    the regime multiplexing exists for. The serial arm pays one floor per
    request, serialized; the mux arm's in-flight window coalesces into one
    launch per flush (every backend has a dispatch floor; how large it is
    on a local chip is unmeasured — ROADMAP S3)."""
    from distributed_faiss_tpu.parallel.client import IndexClient

    srv, disc, teardown = _loopback_server(idx)
    qlist = _warmed_request_list(idx, queries, k, inflight, mux_batch)
    arms = [("rpc_mux_off", "0")] if arm in ("off", "both") else []
    if arm in ("on", "both"):
        arms.append(("rpc_mux_on", "1"))

    rows = []
    saved = os.environ.get("DFT_RPC_MUX")
    try:
        # golden: sequential serving through a serial client
        os.environ["DFT_RPC_MUX"] = "0"
        ref = IndexClient(disc)
        ref.cfg = idx.cfg
        golden = [ref.search(q, k, "bench") for q in qlist]
        ref.close()
        for name, env in arms:
            os.environ["DFT_RPC_MUX"] = env
            client = IndexClient(disc)
            client.cfg = idx.cfg
            srv.scheduler.stats.reset()  # per-arm merged-batch observation

            res = [[] for _ in qlist]
            errs = []
            barrier = threading.Barrier(inflight)

            def caller(t, client=client, res=res, errs=errs,
                       barrier=barrier):
                barrier.wait()
                try:
                    for _ in range(reps):
                        res[t].append(client.search(qlist[t], k, "bench"))
                except Exception as e:  # pragma: no cover
                    errs.append(e)

            ts = [threading.Thread(target=caller, args=(t,))
                  for t in range(inflight)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            assert not errs, (name, errs[:1])
            identical = all(
                len(res[t]) == reps
                and all(np.array_equal(sc, golden[t][0]) and m == golden[t][1]
                        for sc, m in res[t])
                for t in range(len(qlist)))

            qps, p99 = run_clients(
                lambda q, kk, client=client: client.search(q, kk, "bench"),
                qlist, inflight, reps, k)
            merged = srv.scheduler.stats.summary().get(
                "batch_requests", {}).get("max_s", 0.0)
            rows.append({
                "case": name, "backend": backend, "threads": inflight,
                "batch": qlist[0].shape[0], "qps": round(qps, 1),
                "p99_ms": round(p99, 2), "identical": identical,
                "merged_batch_max": merged,
            })
            client.close()
    finally:
        if saved is None:
            os.environ.pop("DFT_RPC_MUX", None)
        else:
            os.environ["DFT_RPC_MUX"] = saved
        teardown()
    return rows


def _serialize_microbench(idx, queries, k, encoding, mux_batch, iters=200):
    """Median per-frame-pair (search CALL + tagged RESULT) encode+decode
    cost under one skeleton encoding, measured over a socketpair
    in-process. The deterministic half of the --wire A/B: loopback QPS
    on a compute-bound CPU backend is noisy, the serialization cost per
    frame is not. Returns microseconds per CALL+RESULT round."""
    import socket as socketlib

    from distributed_faiss_tpu.parallel import rpc

    q = queries[0][:mux_batch]
    result = idx.search_batched(q, k)
    meta = {"req_id": 1, "wire": 1}
    a, b = socketlib.socketpair()
    try:
        def one_round(i):
            if encoding == "binary":
                call = rpc.pack_binary_call("search", ("bench", q, k, False),
                                            {}, meta)
                resp = rpc.pack_binary_response(rpc.KIND_RESULT, result, i)
                assert call is not None and resp is not None
            else:
                call = rpc.pack_frame(
                    rpc.KIND_CALL, ("search", ("bench", q, k, False), {},
                                    meta))
                resp = rpc.pack_tagged_response(rpc.KIND_RESULT, result, i)
            rpc._send_parts(a, call)
            rpc.recv_frame(b)
            rpc._send_parts(b, resp)
            rpc.recv_frame(a)

        one_round(0)  # warm
        t0 = time.perf_counter()
        for i in range(iters):
            one_round(i)
        return (time.perf_counter() - t0) / iters * 1e6
    finally:
        a.close()
        b.close()


def run_wire_arms(idx, queries, k, arm, inflight, reps, backend,
                  mux_batch=4):
    """Binary-wire A/B (ISSUE 14): the same loopback server + ONE mux
    IndexClient per arm, with DFT_RPC_WIRE flipped client-side —
    ``pickle`` never advertises, so the whole path stays on pickle
    skeletons; ``binary`` negotiates per connection and the hot search
    frames ride the compact binary encoding. Each row reports QPS/p99,
    the identity check vs sequential pickle serving, whether the stub
    actually negotiated, and the in-process per-frame serialization
    microbench (encode+decode of one CALL+RESULT pair)."""
    from distributed_faiss_tpu.parallel.client import IndexClient

    srv, disc, teardown = _loopback_server(idx)
    qlist = _warmed_request_list(idx, queries, k, inflight, mux_batch)
    arms = [("wire_pickle", "pickle")] if arm in ("pickle", "both") else []
    if arm in ("binary", "both"):
        arms.append(("wire_binary", "binary"))

    rows = []
    saved = os.environ.get("DFT_RPC_WIRE")
    try:
        os.environ["DFT_RPC_WIRE"] = "pickle"
        ref = IndexClient(disc)
        ref.cfg = idx.cfg
        golden = [ref.search(q, k, "bench") for q in qlist]
        ref.close()
        for name, env in arms:
            os.environ["DFT_RPC_WIRE"] = env
            client = IndexClient(disc)
            client.cfg = idx.cfg
            client.search(qlist[0], k, "bench")  # dial + negotiate

            res = [[] for _ in qlist]
            errs = []
            barrier = threading.Barrier(inflight)

            def caller(t, client=client, res=res, errs=errs,
                       barrier=barrier):
                barrier.wait()
                try:
                    for _ in range(reps):
                        res[t].append(client.search(qlist[t], k, "bench"))
                except Exception as e:  # pragma: no cover
                    errs.append(e)

            ts = [threading.Thread(target=caller, args=(t,))
                  for t in range(inflight)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            assert not errs, (name, errs[:1])
            identical = all(
                len(res[t]) == reps
                and all(np.array_equal(sc, golden[t][0]) and m == golden[t][1]
                        for sc, m in res[t])
                for t in range(len(qlist)))

            qps, p99 = run_clients(
                lambda q, kk, client=client: client.search(q, kk, "bench"),
                qlist, inflight, reps, k)
            negotiated = client.sub_indexes[0].rpc_stats()["peer_wire"]
            rows.append({
                "case": name, "backend": backend, "threads": inflight,
                "batch": qlist[0].shape[0], "qps": round(qps, 1),
                "p99_ms": round(p99, 2), "identical": identical,
                "negotiated": negotiated,
                "serialize_us_per_call_result": round(
                    _serialize_microbench(idx, queries, k, env, mux_batch),
                    2),
            })
            client.close()
    finally:
        if saved is None:
            os.environ.pop("DFT_RPC_WIRE", None)
        else:
            os.environ["DFT_RPC_WIRE"] = saved
        teardown()
    return rows


def run_trace_arms(idx, queries, k, inflight, reps, backend, mux_batch=4):
    """Tracing-overhead A/B (the ISSUE 13 acceptance number): the same
    loopback server + ONE mux IndexClient serving ``inflight`` caller
    threads, once with DFT_TRACE_SAMPLE=0 (tracing off — the claim is
    byte-identical frames and near-zero cost) and once with =1 (every
    request traced end to end — the worst case; production samples a
    fraction). Returns one JSON row carrying both arms AND the deltas,
    so "near-zero when off, bounded when sampled" is a measured number
    in RESULTS.md, not an assertion."""
    from distributed_faiss_tpu.parallel.client import IndexClient

    srv, disc, teardown = _loopback_server(idx)
    qlist = _warmed_request_list(idx, queries, k, inflight, mux_batch)
    results = {}
    saved = os.environ.get("DFT_TRACE_SAMPLE")
    try:
        for name, env in (("off", "0"), ("on", "1")):
            os.environ["DFT_TRACE_SAMPLE"] = env
            client = IndexClient(disc)
            client.cfg = idx.cfg
            spans0 = srv.spans.stats()["recorded"]
            qps, p99 = run_clients(
                lambda q, kk, client=client: client.search(q, kk, "bench"),
                qlist, inflight, reps, k)
            results[name] = {
                "qps": qps, "p99_ms": p99,
                "spans": srv.spans.stats()["recorded"] - spans0,
            }
            client.close()
    finally:
        if saved is None:
            os.environ.pop("DFT_TRACE_SAMPLE", None)
        else:
            os.environ["DFT_TRACE_SAMPLE"] = saved
        teardown()
    off, on = results["off"], results["on"]
    return [{
        "case": "trace_overhead", "backend": backend, "threads": inflight,
        "batch": mux_batch,
        "qps_off": round(off["qps"], 1), "qps_on": round(on["qps"], 1),
        "p99_off_ms": round(off["p99_ms"], 2),
        "p99_on_ms": round(on["p99_ms"], 2),
        "qps_delta_pct": round(
            100.0 * (off["qps"] - on["qps"]) / max(off["qps"], 1e-9), 2),
        "p99_delta_pct": round(
            100.0 * (on["p99_ms"] - off["p99_ms"])
            / max(off["p99_ms"], 1e-9), 2),
        "spans_off": off["spans"], "spans_on": on["spans"],
    }]


def run_mesh_arms(arm, n_threads=8, batch=32, reps=4, k=10):
    """Mesh-sharded serving A/B: per-request launches vs scheduler-merged
    windows against ONE mesh-backed engine rank. Returns JSON-ready rows
    carrying the launch counters (ISSUE 6 acceptance: exactly one device
    launch per merged window, identical results across arms)."""
    import jax

    from distributed_faiss_tpu.engine import Index
    from distributed_faiss_tpu.parallel.mesh import ShardedFlatIndex
    from distributed_faiss_tpu.utils.config import IndexCfg
    from distributed_faiss_tpu.utils.state import IndexState

    small = os.environ.get("BENCH_SMALL") == "1"
    n, d = (50_000 if small else 200_000), 64
    rng = np.random.default_rng(1)
    x = rng.standard_normal((n, d)).astype(np.float32)
    cfg = IndexCfg(index_builder_type="flat", dim=d, metric="l2",
                   train_num=1024, mesh_shards=True)
    idx = Index(cfg)
    idx.add_batch(x, list(range(n)), train_async_if_triggered=False)
    idx.train()
    deadline = time.time() + 1800
    while (idx.get_state() != IndexState.TRAINED
           or idx.get_idx_data_num()[0] > 0):
        assert time.time() < deadline, "mesh train/drain timed out"
        time.sleep(0.2)
    assert isinstance(idx.tpu_index, ShardedFlatIndex)
    ndev = idx.tpu_index.nshards

    queries = [rng.standard_normal((batch, d)).astype(np.float32)
               for _ in range(n_threads)]
    idx.search(queries[0], k)  # warm the jit cache
    # warm the merged-window row buckets the scheduler can produce
    warm = np.concatenate(queries, axis=0)
    for rows in range(batch, batch * n_threads + 1, batch):
        idx.search_batched(warm[:rows], k)

    arms = scheduler_arms(idx, arm)
    identical = check_identity(idx, arms, queries, k)
    backend = jax.devices()[0].platform
    out = []
    for name, search in arms:
        idx.perf.reset()
        qps, p99 = run_clients(search, queries, n_threads, reps, k)
        s = idx.perf.summary()
        launches = s.get("device_launches", {})
        out.append({
            "case": f"mesh_{name}", "backend": backend,
            "mesh_devices": ndev, "threads": n_threads, "batch": batch,
            "qps": round(qps, 1), "p99_ms": round(p99, 2),
            "identical": identical[name],
            "launches_per_window_max": launches.get("max_s", 0.0),
            "windows": launches.get("count", 0),
            "rows_per_launch_max":
                s.get("rows_per_launch", {}).get("max_s", 0.0),
        })
    return out


def run_churn_arm(n_threads=8, batch=32, reps=8, k=10):
    """Mutable-corpora churn arm (mutation subsystem): interleaved
    delete/upsert under a live query storm, with and without an active
    compaction pass.

      churn_idle       — baseline: storm only, no mutations
      churn_mutating   — storm + a mutator thread upserting/deleting ids
                         between launches (tombstones accumulate)
      churn_compacting — same storm while a compaction pass rewrites 30%
                         tombstoned rows into a fresh generation mid-run
                         (phase 2 overlaps serving; the commit+swap holds
                         the engine locks briefly)

    Identity is asserted the strong way: after every arm, no deleted id
    may appear in a verification search.
    """
    import tempfile

    from distributed_faiss_tpu.engine import Index
    from distributed_faiss_tpu.utils.config import IndexCfg
    from distributed_faiss_tpu.utils.state import IndexState
    import jax

    backend = jax.devices()[0].platform
    small = os.environ.get("BENCH_SMALL") == "1"
    n = 20_000 if small else 200_000
    d = 128
    rng = np.random.default_rng(3)
    x = rng.standard_normal((n, d)).astype(np.float32)
    tmp = tempfile.mkdtemp(prefix="dft-churn-")
    os.environ["DFT_COMPACT"] = "0"  # the arm drives compaction explicitly
    cfg = IndexCfg(index_builder_type="ivfsq", dim=d, metric="l2",
                   train_num=min(n, 50_000), centroids=128, nprobe=4,
                   index_storage_dir=os.path.join(tmp, "shard"))
    idx = Index(cfg)
    idx.add_batch(x, [(i,) for i in range(n)],
                  train_async_if_triggered=False)
    idx.train()
    deadline = time.time() + 1800
    while (idx.get_state() != IndexState.TRAINED
           or idx.get_idx_data_num()[0] > 0):
        assert time.time() < deadline, "churn arm train/drain timed out"
        time.sleep(0.5)
    queries = [
        x[rng.integers(0, n, batch)] + 0.01 for _ in range(n_threads)]
    idx.search(queries[0], k)  # warm the jit cache

    def storm(extra=None):
        stop = threading.Event()
        state = {"mutations": 0}
        side = None
        if extra is not None:
            side = threading.Thread(target=extra, args=(stop, state),
                                    daemon=True)
            side.start()
        def churn_search(q, kk):
            # ride through the engine's transient mid-ADD rejection (the
            # drain window an R>=2 client fails over across — the retry
            # wait is honest single-replica serving cost here)
            while True:
                try:
                    return idx.search(q, kk)
                except RuntimeError as e:
                    if "IndexState.ADD" not in str(e):
                        raise
                    time.sleep(0.0005)

        qps, p99 = run_clients(churn_search, queries, n_threads, reps, k)
        stop.set()
        if side is not None:
            side.join(timeout=60)
        return qps, p99, state

    def mutator(stop, state):
        mrng = np.random.default_rng(11)
        next_id = n
        while not stop.is_set():
            victims = mrng.integers(0, n, 8).tolist()
            idx.remove_ids(victims)
            # upsert: re-add half of them with fresh vectors
            up = victims[:4]
            idx.upsert(up, mrng.standard_normal((4, d)).astype(np.float32),
                       [(i,) for i in up])
            state["mutations"] += 12
            next_id += 4
            time.sleep(0.002)

    rows = []
    qps, p99, _ = storm()
    rows.append({"case": "churn_idle", "backend": backend,
                 "threads": n_threads, "batch": batch,
                 "qps": round(qps, 1), "p99_ms": round(p99, 2)})

    qps, p99, st = storm(mutator)
    rows.append({"case": "churn_mutating", "backend": backend,
                 "threads": n_threads, "batch": batch,
                 "qps": round(qps, 1), "p99_ms": round(p99, 2),
                 "mutations": st["mutations"]})

    # cross the compaction threshold, then run the storm with the pass
    # live. The previous arm's upserts may still be draining when this
    # starts; compact() aborts (returns False) if an ADD lands
    # mid-rebuild, so retry until a pass commits — an uncaught assert in
    # a daemon thread would otherwise surface only minutes later as an
    # undiagnosable compactions==0 failure.
    idx.remove_ids(list(range(0, int(0.3 * n), 1)))

    def compactor(stop, state):
        deadline = time.time() + 120
        while not idx.compact():
            assert time.time() < deadline, "compaction never committed"
            time.sleep(0.2)

    qps, p99, st = storm(compactor)
    mu = idx.mutation_stats()
    rows.append({"case": "churn_compacting", "backend": backend,
                 "threads": n_threads, "batch": batch,
                 "qps": round(qps, 1), "p99_ms": round(p99, 2),
                 "compactions": mu["compactions"],
                 "compaction_s": round(
                     mu.get("compaction_s", {}).get("max_s", 0.0), 3)})
    assert mu["compactions"] >= 1, mu
    # the strong check: no tombstoned id in a verification search
    d_, m_, _ = idx.search(x[:64], k)
    dead = {(i,) for i in range(0, int(0.3 * n))}
    assert not any(mm in dead for row in m_ for mm in row)
    return rows


def run_convergence_arm(reps=1):
    """Versioning A/B (ISSUE 12): time an R=2 group's server-side
    anti-entropy convergence to IDENTICAL wire digests after a one-sided
    mutation burst (deletes + upserts applied to one replica only — the
    outage shape), with per-id versions on vs off.

    Each arm reports ``convergence_s`` (burst -> byte-identical digests
    over the wire) and ``upserts_replicated``: whether the peer replica
    ends up serving the upserted VECTORS. With versioning on the sweep
    refresh-pulls them (rows_refreshed); with versioning off the id-only
    digest cannot see an in-place upsert, so the digests converge while
    the content silently doesn't — the exact blind spot the versioned
    plane exists to close (the row records it honestly)."""
    import socket as socketlib
    import tempfile
    import threading

    from distributed_faiss_tpu.mutation.versions import HLC
    from distributed_faiss_tpu.parallel import antientropy, rpc
    from distributed_faiss_tpu.parallel.client import IndexClient
    from distributed_faiss_tpu.parallel.server import IndexServer
    from distributed_faiss_tpu.utils.config import (
        AntiEntropyCfg,
        IndexCfg,
        ReplicationCfg,
        VersioningCfg,
    )
    from distributed_faiss_tpu.utils.state import IndexState
    import jax

    backend = jax.devices()[0].platform
    small = os.environ.get("BENCH_SMALL") == "1"
    n, d, burst = (4_000 if small else 20_000), 64, 64

    def free_port():
        s = socketlib.socket()
        s.bind(("", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def wire_digest(port, index_id):
        resp = rpc.digest_exchange(
            "localhost", port, {"rank": None, "group": None, "want": None},
            timeout=5.0)
        return resp["digests"].get(index_id)

    rows = []
    for versioned in (True, False):
        tmp = tempfile.mkdtemp(prefix="dft-vconv-")
        pa, pb = free_port(), free_port()
        disc = os.path.join(tmp, "disc.txt")
        with open(disc, "w") as f:
            f.write(f"2\nlocalhost,{pa}\nlocalhost,{pb}\n")
        ae = AntiEntropyCfg(interval_s=0.25)
        a = IndexServer(0, os.path.join(tmp, "a"), discovery_path=disc,
                        antientropy_cfg=ae)
        b = IndexServer(1, os.path.join(tmp, "b"), discovery_path=disc,
                        antientropy_cfg=ae)
        threading.Thread(target=a.start_blocking, args=(pa,),
                         daemon=True).start()
        threading.Thread(target=b.start_blocking, args=(pb,),
                         daemon=True).start()
        time.sleep(0.5)
        client = IndexClient(
            disc,
            replication_cfg=ReplicationCfg(replication=2, write_quorum=1),
            versioning_cfg=VersioningCfg(enabled=versioned))
        try:
            cfg = IndexCfg(index_builder_type="flat", dim=d, metric="l2",
                           train_num=min(n, 2048))
            client.create_index("conv", cfg)
            rng = np.random.default_rng(7)
            x = rng.standard_normal((n, d)).astype(np.float32)
            step = max(n // 4, 1)
            for s in range(0, n, step):
                client.add_index_data(
                    "conv", x[s:s + step],
                    [(i,) for i in range(s, min(s + step, n))])
            deadline = time.time() + 600
            while not (client.get_state("conv") == IndexState.TRAINED
                       and client.get_buffer_depth("conv") == 0):
                assert time.time() < deadline, "ingest never drained"
                time.sleep(0.1)
            deadline = time.time() + 120
            while wire_digest(pa, "conv") != wire_digest(pb, "conv"):
                assert time.time() < deadline, "never converged pre-burst"
                time.sleep(0.2)

            # one-sided burst on rank A only (the outage shape): deletes
            # + upserts the peer never saw
            clock = HLC(writer_id=99)
            eng = a._get_index("conv")
            dead_ids = list(range(0, burst))
            up_ids = list(range(burst, 2 * burst))
            new_vecs = (x[up_ids] + 0.5).astype(np.float32)
            eng.remove_ids(dead_ids,
                           version=clock.tick() if versioned else None)
            eng.upsert(up_ids, new_vecs, [(i,) for i in up_ids],
                       version=clock.tick() if versioned else None)
            while a.get_aggregated_ntotal("conv") > 0:
                time.sleep(0.05)
            t0 = time.perf_counter()
            deadline = time.time() + 300
            while True:
                da, db = wire_digest(pa, "conv"), wire_digest(pb, "conv")
                if da is not None and da == db:
                    break
                assert time.time() < deadline, "burst never converged"
                time.sleep(0.1)
            dt = time.perf_counter() - t0
            while b.get_aggregated_ntotal("conv") > 0:
                time.sleep(0.05)
            # did the upserted CONTENT replicate to the peer? exact-match
            # distance, not nearest-id: the stale row is still the
            # nearest ID to its own upsert, so only a ~zero l2 distance
            # proves the peer serves the new VECTORS
            sc, meta, _e = b._get_index("conv").search(new_vecs[:8], 1)
            replicated = (
                [m[0] for m in meta] == [(i,) for i in up_ids[:8]]
                and float(np.abs(sc).max()) < 1e-3)
            ae_stats = b.get_perf_stats()["antientropy"]
            rows.append({
                "case": "churn_convergence", "backend": backend,
                "versioning": "on" if versioned else "off",
                "rows": n, "burst_deletes": burst, "burst_upserts": burst,
                "convergence_s": round(dt, 2),
                "rows_repaired": ae_stats["rows_repaired"],
                "rows_refreshed": ae_stats.get("rows_refreshed", 0),
                "upserts_replicated": replicated,
            })
        finally:
            client.close()
            for srv in (a, b):
                # light teardown (run_mux_arms precedent): no full stop()
                # saves — the process exits right after the arms
                srv._stopping.set()
                if srv._antientropy is not None:
                    srv._antientropy.stop()
                if srv.socket is not None:
                    try:
                        srv.socket.close()
                    except OSError:
                        pass
                if srv.scheduler is not None:
                    srv.scheduler.stop()
    # the headline contract: versions make the sweep converge CONTENT,
    # not just id sets
    by_arm = {r["versioning"]: r for r in rows}
    assert by_arm["on"]["upserts_replicated"] is True, by_arm
    return rows


def main():
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--scheduler", choices=("on", "off", "both", "none"), default="both",
        help="serving-scheduler A/B arm(s) to run (default: both, with a "
             "result-identity cross-check)")
    parser.add_argument(
        "--mux", choices=("on", "off", "both", "none"), default="both",
        help="RPC-multiplexing A/B arm(s): real server + ONE IndexClient "
             "over loopback (default: both, with identity cross-check and "
             "the merged-batch observation)")
    parser.add_argument(
        "--inflight", type=int, default=8, metavar="W",
        help="concurrent caller threads on the single mux-arm client "
             "(the per-connection in-flight window; default 8)")
    parser.add_argument(
        "--mux-batch", type=int, default=4,
        help="rows per request in the mux arms (default 4: user-sized "
             "requests riding the per-launch dispatch floor)")
    parser.add_argument(
        "--wire", choices=("binary", "pickle", "both", "none"),
        default="none",
        help="binary-wire A/B arm(s): the mux serving path with "
             "DFT_RPC_WIRE=pickle vs binary on the same engine — per-arm "
             "qps/p99, cross-arm identity, negotiation check, and an "
             "in-process per-frame serialization microbench (default: "
             "none)")
    parser.add_argument(
        "--trace-sample", action="store_true",
        help="tracing-overhead A/B arm: the mux serving path with "
             "DFT_TRACE_SAMPLE=0 vs 1 on the same engine — one JSON row "
             "with both arms' qps/p99 and the deltas (off by default)")
    parser.add_argument(
        "--mesh", choices=("on", "off", "both", "none"), default="none",
        help="mesh-sharded serving A/B arm(s) on a virtual 8-device CPU "
             "mesh (forces XLA_FLAGS before jax imports; default: none — "
             "run with --mesh both for the one-launch-per-window check)")
    parser.add_argument(
        "--churn", choices=("on", "convergence", "both", "none"),
        default="none",
        help="mutable-corpora churn arms: 'on' = interleaved delete/upsert "
             "under a live query storm with/without an active compaction "
             "pass; 'convergence' = R=2 anti-entropy "
             "convergence-to-identical-digests after a one-sided mutation "
             "burst, per-id versioning on vs off (default: none)")
    parser.add_argument(
        "--modes", default="percall,natural,window",
        help="comma list of legacy batcher modes to run ('' = skip)")
    args = parser.parse_args()

    if args.mesh != "none":
        # must land before the first jax import anywhere in this process
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        if (any(args.modes.split(",")) or args.scheduler != "none"
                or args.mux != "none"):
            # the flag is process-wide: every arm in this invocation runs
            # on the forced topology, so its rows are not comparable to
            # single-device baseline rows (RESULTS.md r6-r8)
            print("WARNING: --mesh forces an 8-virtual-device host platform "
                  "for the whole process; run the scheduler/mux/legacy arms "
                  "in a separate invocation for baseline-comparable rows",
                  file=sys.stderr, flush=True)

    import jax

    from distributed_faiss_tpu.engine import Index
    from distributed_faiss_tpu.utils import envutil
    from distributed_faiss_tpu.utils.config import IndexCfg
    from distributed_faiss_tpu.utils.state import IndexState

    envutil.place_compile_cache()
    small = os.environ.get("BENCH_SMALL") == "1"
    n = 50_000 if small else 500_000
    d, k = 128, 10
    n_threads, batch, reps = 8, 32, 4 if small else 8
    backend = jax.devices()[0].platform

    modes = [m for m in args.modes.split(",") if m]
    need_single = (bool(modes) or args.scheduler != "none"
                   or args.mux != "none" or args.trace_sample
                   or args.wire != "none")
    if need_single:
        rng = np.random.default_rng(0)
        centers = rng.standard_normal((256, d)).astype(np.float32) * 4.0
        a = rng.integers(0, 256, n)
        x = (centers[a] + rng.standard_normal((n, d))).astype(np.float32)

        cfg = IndexCfg(index_builder_type="ivfsq", dim=d, metric="l2",
                       train_num=min(n, 100_000), centroids=256, nprobe=4)
        idx = Index(cfg)
        idx.add_batch(x, list(range(n)), train_async_if_triggered=False)
        idx.train()
        deadline = time.time() + 1800
        while idx.get_state() != IndexState.TRAINED:
            assert time.time() < deadline, "train timed out"
            time.sleep(0.5)

        queries = [
            (centers[rng.integers(0, 256, batch)]
             + rng.standard_normal((batch, d))).astype(np.float32)
            for _ in range(n_threads)
        ]
        idx.search(queries[0], k)  # warm the jit cache

    for mode in modes:
        qps, p99 = run_clients(make_search(idx, mode), queries,
                               n_threads, reps, k)
        print(json.dumps({
            "case": f"concurrency_{mode}", "backend": backend,
            "threads": n_threads, "batch": batch, "qps": round(qps, 1),
            "p99_ms": round(p99, 2),
        }), flush=True)

    if args.scheduler != "none":
        arms = scheduler_arms(idx, args.scheduler)
        identical = check_identity(idx, arms, queries, k)
        for name, search in arms:
            qps, p99 = run_clients(search, queries, n_threads, reps, k)
            print(json.dumps({
                "case": name, "backend": backend, "threads": n_threads,
                "batch": batch, "qps": round(qps, 1),
                "p99_ms": round(p99, 2), "identical": identical[name],
            }), flush=True)
        assert all(identical.values()), \
            f"results diverged from direct launches: {identical}"

    if args.mux != "none":
        rows = run_mux_arms(idx, queries, k, args.mux, args.inflight,
                            reps, backend, mux_batch=args.mux_batch)
        for row in rows:
            print(json.dumps(row), flush=True)
        assert all(r["identical"] for r in rows), \
            f"mux results diverged from sequential serving: {rows}"
        by_case = {r["case"]: r for r in rows}
        if "rpc_mux_on" in by_case:
            # the tentpole observation: a single client's in-flight window
            # reached the scheduler as one merged batch (impossible with
            # the serial stub)
            assert by_case["rpc_mux_on"]["merged_batch_max"] > 1, by_case

    if args.wire != "none":
        rows = run_wire_arms(idx, queries, k, args.wire, args.inflight,
                             reps, backend, mux_batch=args.mux_batch)
        for row in rows:
            print(json.dumps(row), flush=True)
        assert all(r["identical"] for r in rows), \
            f"wire results diverged from sequential pickle serving: {rows}"
        by_case = {r["case"]: r for r in rows}
        if "wire_binary" in by_case:
            assert by_case["wire_binary"]["negotiated"] is True, by_case
        if len(by_case) == 2:
            # the tentpole number: the binary skeleton encodes+decodes a
            # CALL+RESULT pair measurably cheaper than pickle
            assert (by_case["wire_binary"]["serialize_us_per_call_result"]
                    < by_case["wire_pickle"]["serialize_us_per_call_result"]), \
                by_case

    if args.trace_sample:
        rows = run_trace_arms(idx, queries, k, args.inflight, reps,
                              backend, mux_batch=args.mux_batch)
        for row in rows:
            print(json.dumps(row), flush=True)
        # the off arm must stay within noise of untraced serving; the on
        # arm is the 100%-sampled worst case and merely needs to be
        # bounded (spans actually recorded proves the arm traced)
        assert rows[0]["spans_on"] > 0 and rows[0]["spans_off"] == 0, rows

    if args.mesh != "none":
        rows = run_mesh_arms(args.mesh, n_threads=n_threads, batch=batch,
                             reps=reps, k=k)
        for row in rows:
            print(json.dumps(row), flush=True)
        assert all(r["identical"] for r in rows), \
            f"mesh results diverged from direct launches: {rows}"
        for r in rows:
            # the ISSUE 6 acceptance: every merged window crossed to the
            # mesh as exactly ONE pjit launch
            assert r["launches_per_window_max"] == 1.0, r

    if args.churn in ("on", "both"):
        for row in run_churn_arm(n_threads=n_threads, batch=batch,
                                 reps=reps, k=k):
            print(json.dumps(row), flush=True)

    if args.churn in ("convergence", "both"):
        for row in run_convergence_arm():
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
