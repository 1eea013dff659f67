#!/usr/bin/env python3
"""One run of one benchmark cell on the served path.

    python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Starts the cell's index-server ranks (one per chip), builds the seeded index
through a real ``IndexClient`` (set-up), warms the request shapes the cell's
traffic can produce, drives the traffic for ``--seconds``, then compares what
the timed requests returned with the configuration's plain reference. The
last line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, when traced ``breakdown``, and last
``checks``, every number compared beside its limit (they are also the last
lines of standard error). With
``--trace 0`` the metrics are the cell's end-to-end metrics; with ``--trace
1`` the ranks record a profiler trace of the window and the metrics are the
cell's per-layer metrics.

This process stays off jax: a chip belongs to one process and the ranks are
the ones that need it. A rank that does not report a TPU, or a machine with
fewer chips than the cell asks for, ends the run non-zero with no result.
"""

import argparse
import json
import os
import queue
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

from distributed_faiss_tpu.parallel import launcher
from distributed_faiss_tpu.parallel.client import IndexClient
from distributed_faiss_tpu.utils.config import IndexCfg, SchedulerCfg
from distributed_faiss_tpu.utils.state import IndexState
from perfbench import cluster, corpus, correctness, load_gen, loader, trace_reduce

INDEX_ID = "bench"
CLOCK_OFFSET = time.time() - time.perf_counter()  # perf_counter -> wall clock


class RunFailure(RuntimeError):
    pass


class Watchdog(threading.Thread):
    """Bounds every wait (copied from ``chip_smoke.py``): a phase past its
    deadline, or a rank that died under a live client whose calls would
    otherwise block for ever, ends the run non-zero with the rank logs'
    tails. ``os._exit`` because the main thread may be parked in a socket."""

    def __init__(self):
        super().__init__(name="perfbench-watchdog", daemon=True)
        self.lock = threading.Lock()
        self.label, self.deadline, self.ranks = "start", time.time() + 120, None
        self.done = threading.Event()
        self.start()

    def phase(self, label, seconds):
        with self.lock:
            self.label, self.deadline = label, time.time() + seconds

    def watch(self, ranks):
        with self.lock:
            self.ranks = ranks

    def run(self):
        while not self.done.wait(0.5):
            with self.lock:
                label, deadline, ranks = self.label, self.deadline, self.ranks
            dead = ranks.dead() if ranks is not None else []
            if time.time() > deadline:
                reason = f"phase '{label}' exceeded its time limit"
            elif dead:
                reason = f"rank(s) {dead} exited during '{label}'"
            else:
                continue
            sys.stderr.write(f"perfbench: FAILED: {reason}\n")
            if ranks is not None:
                sys.stderr.write(ranks.log_tails() + "\n")
                ranks.stop()
            sys.stderr.flush()
            os._exit(1)


def note(msg):
    print(msg, flush=True)


# ------------------------------------------------------------------ set-up


def connect(ranks, wd, platform):
    """The client, and every rank's own report of the device it holds."""
    wd.phase("ranks start", 300)
    wd.watch(ranks)
    t0 = time.time()
    client = IndexClient(ranks.discovery)
    pings = client.ping(timeout=240.0)
    for p in pings:
        if "device" not in p:
            raise RunFailure(f"a rank did not report its device: {p}")
        dev = p["device"]
        note(f"rank {p['rank']}: platform={dev['platform']} "
             f"device_kind={dev['device_kind']!r} count={dev['count']} "
             f"visible_chips={dev['visible_chips']}")
        if dev["platform"] != platform:
            raise RunFailure(
                f"rank {p['rank']} runs on platform={dev['platform']!r}, not "
                f"{platform!r}: nothing it measured would say anything about the chip")
    note(f"{ranks.num} rank(s) up in {time.time() - t0:.2f}s")
    return client, [p["device"] for p in pings]


class StatePoller(threading.Thread):
    """Watches each rank's index state while the index is built, to time
    training from outside: the seconds a rank was seen TRAINING."""

    def __init__(self, client, period=0.1):
        super().__init__(name="perfbench-states", daemon=True)
        self.client, self.period = client, period
        self.stop_event = threading.Event()
        self.first = [None] * len(client.sub_indexes)
        self.last = [None] * len(client.sub_indexes)

    def run(self):
        while not self.stop_event.wait(self.period):
            now = time.time()
            for i, stub in enumerate(self.client.sub_indexes):
                if stub.generic_fun("get_state", (INDEX_ID,)) == IndexState.TRAINING:
                    self.first[i] = self.first[i] or now
                    self.last[i] = now

    def finish(self):
        self.stop_event.set()
        self.join(timeout=30)
        spans = [l - f + self.period for f, l in zip(self.first, self.last) if f]
        return max(spans, default=0.0)


def build(client, discovery, config, mix, wd):
    """create -> add in ``buffer_bsz`` batches with the row number as
    metadata, chunks generated by a few threads while earlier ones are
    added -> train -> wait until every row is indexed. Returns (chunks,
    rows acknowledged per stub position, set-up facts)."""
    cfg = IndexCfg(**config["index"])
    rows, bsz = int(config["rows"]), int(cfg.buffer_bsz)
    nchunks = -(-rows // bsz)
    wd.phase("build", 900)
    client.create_index(INDEX_ID, cfg)

    slots = [queue.Queue(maxsize=1) for _ in range(nchunks)]
    generators = 2 + 2 * len(client.sub_indexes)  # the ranks drain in parallel
    ahead = threading.Semaphore(2 * generators)  # bounds chunks made ahead
    todo = queue.Queue()
    for i in range(nchunks):
        todo.put(i)

    def generate():
        while True:
            ahead.acquire()  # before taking a number: the lowest one out never waits
            try:
                i = todo.get_nowait()
            except queue.Empty:
                ahead.release()
                return
            slots[i].put(mix.chunk(corpus.CORPUS, i, min(bsz, rows - i * bsz)))

    makers = [threading.Thread(target=generate, name=f"corpus{i}", daemon=True)
              for i in range(generators)]
    for t in makers:
        t.start()
    poller = StatePoller(client)
    poller.start()

    # one sender a rank, each through a client of its own: a client places
    # batches round-robin one call at a time, and one caller alone keeps four
    # ranks waiting for rows (65 s to send 4e6 rows against 30 s to index them)
    clients = [client] + [IndexClient(discovery)
                          for _ in range(len(client.sub_indexes) - 1)]
    groups = len(client.membership.snapshot())
    acked = [0] * len(client.sub_indexes)
    chunks = [None] * nchunks
    errors = []
    lock = threading.Lock()

    def send(sender):
        mine = clients[sender]
        try:
            for i in range(sender, nchunks, len(clients)):
                x = slots[i].get()
                ahead.release()
                first = i * bsz
                mine.add_index_data(INDEX_ID, x, list(range(first, first + x.shape[0])))
                with lock:  # the client moves its cursor past the group that acked
                    acked[(mine.cur_server_ids[INDEX_ID] - 1) % groups] += x.shape[0]
                    chunks[i] = x
        except Exception as e:  # raised again below, in the main thread
            errors.append(e)

    t_first = time.time()
    senders = [threading.Thread(target=send, args=(j,), name=f"sender{j}", daemon=True)
               for j in range(len(clients))]
    for t in senders:
        t.start()
    for t in senders:
        t.join()
    for extra in clients[1:]:
        extra.close()
    if errors:
        raise RunFailure(f"adding rows failed: {errors[0]!r}")
    t_sent = time.time()
    client.sync_train(INDEX_ID)  # a rank that never crossed train_num trains now
    while not (client.get_state(INDEX_ID) == IndexState.TRAINED
               and client.get_ntotal(INDEX_ID) == rows):
        time.sleep(0.05)  # the buffers drain on the ranks; wd bounds this
    t_done = time.time()
    train_s = poller.finish()
    facts = {"rows": rows, "train_s": train_s, "send_s": t_sent - t_first,
             "build_s": t_done - t_first,
             "add_rows_s": rows / max(t_done - t_first - train_s, 1e-9)}
    note(f"build: rows={rows} send_s={facts['send_s']:.2f} train_s={train_s:.2f} "
         f"build_s={facts['build_s']:.2f} add_rows_s={facts['add_rows_s']:.0f} "
         f"acked_per_rank={acked}")
    return chunks, acked, facts


def warm(client, config, traffic, pool, wd):
    """One request of every size a merged device window can have."""
    wd.phase("warm-up", 900)
    k = int(config["k"])
    for n in load_gen.request_sizes(traffic, SchedulerCfg.from_env().max_batch_rows):
        t1 = time.time()
        r = load_gen.search_once(client, INDEX_ID, k, pool[:n], 0)
        if not r.ok:
            raise RunFailure(f"warm-up request of {n} rows failed: {r.error}")
        note(f"warm-up: {n} rows in {time.time() - t1:.3f}s")


# --------------------------------------------------------------- the window


def bytes_in_use(client):
    """Per rank, the bytes allocated on each of its devices."""
    return [[d["bytes_in_use"] for d in p["device"]["devices"]]
            for p in client.ping(timeout=60.0)]


def perf_stats(client):
    stats = client.get_perf_stats()
    for s in stats:
        if "error" in s:
            raise RunFailure(f"a rank did not give its perf stats: {s}")
    return stats


def measure(client, ranks, config, traffic, pool, seed, seconds, trace,
            device_prefix, wd):
    wd.phase("window", seconds + 300)
    obs = {}
    if trace:
        ranks.ask("trace_start", "trace_started")
        obs["stats_before"] = perf_stats(client)
    results, t0, t1 = load_gen.drive(client, INDEX_ID, int(config["k"]), pool,
                                     traffic, seed, seconds)
    if trace:
        obs["stats_after"] = perf_stats(client)
        wd.phase("trace reduction", 600)
        obs["traces"] = ranks.ask("trace_stop", "trace.json",
                                  {"device_prefix": device_prefix}, timeout=580)
    obs.update(results=results, window_s=t1 - t0)
    return obs, t0


def after_window(client, ranks, config, chunks, acked, seed, checks, wd):
    """The guarantees that are read from the live ranks: every acknowledged
    row is indexed on the rank that acknowledged it, and, where the
    configuration promises it, a stored row searched by itself comes back
    first."""
    wd.phase("read-back", 300)
    per_rank = [stub.generic_fun("get_ntotal", (INDEX_ID,))
                for stub in client.sub_indexes]
    note(f"ntotal per rank: {per_rank}; acknowledged per rank: {acked}")
    checks.add("ntotal_gap", sum(abs(a - b) for a, b in zip(per_rank, acked)), "<=", 0)
    if config["guarantees"].get("self_lookup_top1"):
        n = int(config["limits"]["self_lookup_rows"])
        ids, rows = correctness.self_lookup_rows(chunks, seed, n)
        r = load_gen.search_once(client, INDEX_ID, int(config["k"]), rows, 0)
        misses = n if not r.ok else int((r.ids[:, 0] != ids).sum())
        checks.add("self_lookup_misses", misses, "<=", 0)
    note(f"bytes_in_use per rank after the window: {bytes_in_use(client)}")
    mem = ranks.ask("memstats", "memstats.json")
    peaks = [d["peak_bytes_in_use"] or 0 for rank in mem for d in rank]
    return max(peaks, default=0)


# ------------------------------------------------------------------ results


def end_to_end_metrics(cell, obs, setup_s):
    ok = [r for r in obs["results"] if r.ok]
    lat_ms = np.array([r.end - r.start for r in ok]) * 1e3
    note(f"window: {obs['window_s']:.3f}s, {len(obs['results'])} requests, "
         f"{len(ok)} completed, {sum(r.rows for r in ok)} query rows; latency "
         f"samples: {len(lat_ms)}")
    values = {
        "qps": sum(r.rows for r in ok) / obs["window_s"],
        "lat_p50_ms": float(np.percentile(lat_ms, 50)) if len(lat_ms) else None,
        "setup_s": setup_s,
    }
    return {name: values[name] for name in cell.end_to_end()}


def layer_metrics(cell, obs):
    out = {}
    for metric, reader in cell.layer_readers():
        value = reader.read(obs)
        if value is None:
            note(f"{metric['name']}: not measured (its reader found nothing to read)")
        else:
            out[metric["name"]] = value
    return out


def run(args, cell, workdir, platform, device_prefix, t_start, wd):
    config, traffic = cell.config, cell.traffic
    mix = corpus.mixture_for(config, args.seed)
    pool = mix.chunk(corpus.QUERIES, 0, int(traffic["query_pool_rows"]))
    checks = correctness.Checks()
    with cluster.Ranks(int(config["ranks"]), workdir, cell.root) as ranks:
        client = None
        try:
            client, devices = connect(ranks, wd, platform)
            chunks, acked, facts = build(client, ranks.discovery, config, mix, wd)
            warm(client, config, traffic, pool, wd)
            note(f"bytes_in_use per rank after set-up: {bytes_in_use(client)}")
            obs, t0 = measure(client, ranks, config, traffic, pool, args.seed,
                              args.seconds, args.trace, device_prefix, wd)
            setup_s = t0 + CLOCK_OFFSET - t_start  # process start to first send
            peak = after_window(client, ranks, config, chunks, acked, args.seed,
                                checks, wd)
        except Exception:
            sys.stderr.write(ranks.log_tails() + "\n")
            raise
        finally:
            if client is not None:
                client.close()
    # the ranks are gone and the chip is free: the reference has the host
    wd.watch(None)
    wd.phase("reference", 600)
    t_ref = time.time()
    correctness.compare_window(checks, config, cell.reference, chunks, pool,
                               obs["results"], args.seed)
    note(f"reference comparison took {time.time() - t_ref:.2f}s")

    obs.update(config=config, traffic=traffic, cell=cell.name, setup=facts,
               devices=devices, index_id=INDEX_ID)
    device = {"platform": devices[0]["platform"], "kind": devices[0]["device_kind"],
              "count": sum(d["count"] for d in devices), "memory_peak_bytes": peak}
    result = {"correct": checks.correct, "attempted": len(obs["results"]),
              "failed": sum(1 for r in obs["results"] if not r.ok)}
    if args.trace:
        traces = obs["traces"]
        note(f"trace: planes and lines of rank 0: {json.dumps(traces[0].get('planes'))}")
        device["busy_s"] = sum(t["busy_s"] for t in traces) / len(traces)
        device["window_s"] = obs["window_s"]
        values = layer_metrics(cell, obs)
        result["breakdown"] = {key: trace_reduce.averaged(traces, key)
                               for key in ("device_ops", "idle_gaps")}
    else:
        values = end_to_end_metrics(cell, obs, setup_s)
    result["metrics"] = {name: {"value": v, "unit": cell.unit(name)}
                         for name, v in values.items()}
    result["device"] = device
    result["checks"] = checks.as_json()  # last in the line: the contract's place
    sys.stderr.write(checks.lines())
    return result


def main(argv=None, *, platform="tpu", device_prefix="/device:TPU:",
         root=loader.ROOT):
    """``platform``, ``device_prefix`` and ``root`` are for the tests' CPU
    rehearsal; the command line cannot reach them."""
    t_start = time.time()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    cell = loader.Cell(args.workload, root)
    chips = launcher.local_tpu_chips()
    if platform == "tpu" and chips < cell.chips:
        sys.stderr.write(f"perfbench: FAILED: cell {cell.name!r} needs "
                         f"{cell.chips} TPU chip(s); this machine has {chips}\n")
        return 3
    workdir = tempfile.mkdtemp(prefix="perfbench_")
    wd = Watchdog()
    try:
        result = run(args, cell, workdir, platform, device_prefix, t_start, wd)
    except (RunFailure, cluster.RankFailure) as e:
        sys.stderr.write(f"perfbench: FAILED: {type(e).__name__}: {e}\n")
        return 1
    finally:
        wd.done.set()
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
