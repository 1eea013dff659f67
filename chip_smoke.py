#!/usr/bin/env python3
"""Chip smoke: the served path, once, on the TPU chip(s) this machine holds.

    python3 chip_smoke.py [--seed N] [--four-chips]

Starts index-server ranks the way the README does (``launcher.launch_local``)
and drives them through ``IndexClient`` — client -> wire -> server ->
scheduler -> engine -> model -> kernels — at the published widths of the
deployments the repo supports, checking every answer against a plain numpy
exact scan made in this process. This process never creates a jax backend:
a chip belongs to one process, and the ranks are the ones that need it.

Phases (default, one rank):
  main    upstream ``knnlm`` (BASELINE.json config 3): dim 768, 4096
          centroids, PQ 64x8, refine_k_factor 8, nprobe 32 — recall@10 >= 0.95
  exact   upstream ``flat`` (config 1): L2, dim 128, 1e6 rows — ids equal to
          the numpy scan's, ties aside
  kernel  every Pallas kernel compiled by Mosaic at its deployment's
          geometry: knnlm with pallas_adc forced, ivfsq and IVF1024,SQ8
          at dim 512 + pallas_flat — same recall check, and no
          demotion in ``ping()["kernels"]``; then benchmarks/tpu_validate.py
          in a child once the rank has exited (the direct-kernel parity run)

``--four-chips`` runs the main phase on both documented four-chip layouts
instead: four ranks of one chip each, then one rank holding a four-chip mesh
(sharded knnlm, masked and probe-routed, and a mesh-sharded flat index).

Any failed check, dead rank or expired wait is a non-zero exit with the
rank logs' tails on stderr. The last stdout line of a passing run is one
JSON object: {"ok": true, "device": {"platform", "kind", "count"}} as the
rank's own ``ping()`` reported it. Where the rank is not on a TPU (no
accelerator, ``JAX_PLATFORMS=cpu``) the run fails before any phase.
"""

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

from benchmarks.baseline_configs import clustered, make_lowrank_corpus, recall_at_k
from distributed_faiss_tpu.parallel import launcher
from distributed_faiss_tpu.parallel.client import IndexClient
from distributed_faiss_tpu.utils import envutil
from distributed_faiss_tpu.utils.config import IndexCfg
from distributed_faiss_tpu.utils.state import IndexState

REPO = os.path.dirname(os.path.abspath(__file__))
K = 10
RECALL_BAR = 0.95

# Row counts. Widths are the deployments' own and are never cut; rows are
# what fits the 1200 s contract with cold compiles (README's CPU recall run
# used the same 5e5 for knnlm; upstream's flat config is 1e6 as written).
ROWS_MAIN = 500_000
ROWS_EXACT = 1_000_000
ROWS_KERNEL = 200_000

KNNLM = dict(index_builder_type="knnlm", dim=768, metric="l2", centroids=4096,
             code_size=64, nbits=8, refine_k_factor=8, nprobe=32,
             train_num=200_000, buffer_bsz=50_000)


# --------------------------------------------------------------------- data


def lowrank_mixture(rng, d, clusters):
    """Sampler gen(n) -> (n, d) fp32 for the IVF phases: a gaussian mixture
    in d // 12 latent dimensions under a fixed orthonormal embedding, plus
    small ambient noise. Embeddings (kNN-LM keys, passage encoders) have low
    intrinsic dimension; an isotropic d >= 512 mixture is the degenerate
    case where every same-cluster distance concentrates at sqrt(2d) and no
    quantizer, FAISS's included, can rank neighbours."""
    return make_lowrank_corpus(rng, d, r=d // 12, n_latent_clusters=clusters)


def sift_shaped(rng, n_corpus, n_queries):
    """bench.py's corpus at SIFT's width: an isotropic 128-d gaussian
    mixture, queries sharing the corpus's centres."""
    centres = rng.standard_normal((1024, 128)).astype(np.float32) * 4.0
    return (clustered(rng, n_corpus, 128, centres),
            clustered(rng, n_queries, 128, centres))


# ---------------------------------------------------------------- reference


def exact_topk(x, q, k, chunk=100_000):
    """Plain numpy exact L2 scan: the (nq, k) ids, nearest first."""
    best_d = np.full((q.shape[0], k), np.inf, np.float32)
    best_i = np.full((q.shape[0], k), -1, np.int64)
    qn = (q * q).sum(1)[:, None]
    for s in range(0, x.shape[0], chunk):
        xc = x[s:s + chunk]
        d2 = qn - 2.0 * (q @ xc.T) + (xc * xc).sum(1)[None, :]
        part = np.argpartition(d2, k - 1, axis=1)[:, :k]
        cand_d = np.concatenate([best_d, np.take_along_axis(d2, part, 1)], 1)
        cand_i = np.concatenate([best_i, part + s], 1)
        order = np.argsort(cand_d, axis=1, kind="stable")[:, :k]
        best_d = np.take_along_axis(cand_d, order, 1)
        best_i = np.take_along_axis(cand_i, order, 1)
    return best_i


def ids_equal_ties_aside(x, q, got, ref, rtol=1e-5):
    """Every returned id equals the reference's at its rank, or sits at the
    same distance (float64, from the raw rows) — a tie the two scans may
    order differently. Returns (rows identical, tie swaps, wrong)."""
    differ = np.argwhere(got != ref)
    swaps = wrong = 0
    for i, j in differ:
        if got[i, j] < 0 or len(set(got[i])) != got.shape[1]:
            wrong += 1
            continue
        qd = q[i].astype(np.float64)
        d_got = ((x[got[i, j]].astype(np.float64) - qd) ** 2).sum()
        d_ref = ((x[ref[i, j]].astype(np.float64) - qd) ** 2).sum()
        if abs(d_got - d_ref) <= rtol * max(d_ref, 1.0):
            swaps += 1
        else:
            wrong += 1
    identical = got.shape[0] - len(set(differ[:, 0].tolist()))
    return identical, swaps, wrong


# -------------------------------------------------------------- the cluster


class SmokeFailure(RuntimeError):
    pass


class Ranks:
    """Local ranks started through the launcher, always killed on exit."""

    def __init__(self, num, workdir, env):
        self.num = num
        self.dir = tempfile.mkdtemp(prefix=f"ranks{num}_", dir=workdir)
        self.discovery = os.path.join(self.dir, "discovery.txt")
        self.env = env
        self.procs = []

    def __enter__(self):
        with socket.socket() as s:  # a free base port for this launch
            s.bind(("", 0))
            port = s.getsockname()[1]
        self.procs = launcher.launch_local(
            self.num, self.discovery, os.path.join(self.dir, "storage"),
            base_port=port, env=self.env, log_dir=self.dir)
        return self

    def __exit__(self, *exc):
        self.stop()

    def dead(self):
        return [r for r, p in enumerate(self.procs) if p.poll() is not None]

    def stop(self):
        procs, self.procs = self.procs, []
        for p in procs:
            p.kill()
        for p in procs:
            p.wait(timeout=30)

    def log_tails(self, nbytes=6000):
        out = []
        for rank in range(self.num):
            path = os.path.join(self.dir, f"rank{rank}.log")
            if os.path.exists(path):
                with open(path, "rb") as f:
                    f.seek(max(0, os.path.getsize(path) - nbytes))
                    out.append(f"--- rank {rank} log tail ---\n"
                               + f.read().decode("utf-8", "replace"))
        return "\n".join(out)


def report_failure(reason, ranks):
    sys.stderr.write(f"chip_smoke: FAILED: {reason}\n")
    if ranks is not None:
        sys.stderr.write(ranks.log_tails() + "\n")
    sys.stderr.flush()


class Watchdog(threading.Thread):
    """Bounds every wait: a phase past its deadline, or a watched rank that
    died under a live client (whose calls would otherwise block for ever),
    ends the run non-zero with the log tails. ``os._exit`` because the main
    thread may be parked in a socket read."""

    def __init__(self):
        super().__init__(name="smoke-watchdog", daemon=True)
        self.lock = threading.Lock()
        self.label, self.deadline, self.ranks = "start", time.time() + 120, None
        self.start()

    def phase(self, label, seconds):
        with self.lock:
            self.label, self.deadline = label, time.time() + seconds

    def watch(self, ranks):
        with self.lock:
            self.ranks = ranks

    def run(self):
        while True:
            time.sleep(0.5)
            with self.lock:
                label, deadline, ranks = self.label, self.deadline, self.ranks
            dead = ranks.dead() if ranks is not None else []
            if time.time() > deadline:
                reason = f"phase '{label}' exceeded its time limit"
            elif dead:
                reason = f"rank(s) {dead} exited during '{label}'"
            else:
                continue
            report_failure(reason, ranks)
            if ranks is not None:
                for p in ranks.procs:
                    p.kill()
            os._exit(1)


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def cache_entries(cache_dir):
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def require_tpu(pings):
    """Every rank's own report must say tpu; returns rank 0's device block."""
    for p in pings:
        check("device" in p, f"rank did not report its device: {p}")
        dev = p["device"]
        print(f"rank {p['rank']}: platform={dev['platform']} "
              f"device_kind={dev['device_kind']!r} count={dev['count']} "
              f"visible_chips={dev['visible_chips']} devices={dev['devices']}",
              flush=True)
        check(dev["platform"] == "tpu",
              f"rank {p['rank']} runs on platform={dev['platform']!r}, not "
              "'tpu': no accelerator was found (or JAX_PLATFORMS excludes "
              "it) — nothing below would say anything about the chip")
    return pings[0]["device"]


def check_no_demotion(client, label):
    for p in client.ping(timeout=60.0):
        check("kernels" in p, f"{label}: ping failed: {p}")
        check(not p["kernels"]["pallas_degraded"],
              f"{label}: a Pallas kernel was demoted at run time: {p['kernels']}")


def scheduler_counters(client):
    return [s["scheduler"]["counters"] for s in client.get_perf_stats()]


def bytes_in_use(client):
    """Per rank, the bytes allocated on each of its devices."""
    return [[d["bytes_in_use"] for d in p["device"]["devices"]]
            for p in client.ping(timeout=60.0)]


# ------------------------------------------------------------------ phases


def build(client, index_id, cfg, x, wd, label):
    """create -> add in buffer_bsz batches (ids as metadata) -> sync_train
    -> wait until every row is indexed. Training is synchronous end to end
    (``train_async_if_triggered=False`` for the batch that crosses
    train_num, ``sync_train`` for ranks that never cross it), so a device
    failure in training raises here instead of resetting the state to
    NOT_TRAINED behind a poll."""
    n = x.shape[0]
    wd.phase(f"{label}: build", 600)
    client.create_index(index_id, cfg)
    t0 = time.time()
    longest = 0.0
    for s in range(0, n, cfg.buffer_bsz):
        e = min(n, s + cfg.buffer_bsz)
        t1 = time.time()
        client.add_index_data(index_id, x[s:e], list(range(s, e)),
                              train_async_if_triggered=False)
        longest = max(longest, time.time() - t1)
    t1 = time.time()
    client.sync_train(index_id)
    train_s = time.time() - t1
    if train_s < longest:  # training ran inside the crossing add call
        train_s = longest
    while not (client.get_state(index_id) == IndexState.TRAINED
               and client.get_ntotal(index_id) == n):
        time.sleep(0.25)  # the buffer drains on the rank; wd bounds this
    total = time.time() - t0
    print(f"{label}: rows={n} train_s={train_s:.1f} "
          f"add_s={total - train_s:.1f} (host clock, compile included)", flush=True)


def search_ids(client, index_id, q):
    scores, meta = client.search(q, K, index_id)
    check(scores.shape == (q.shape[0], K) and np.isfinite(scores).all(),
          f"{index_id}: scores are not finite {(q.shape[0], K)}: {scores.shape}")
    check(len(meta) == q.shape[0] and all(len(row) == K for row in meta),
          f"{index_id}: metadata matrix is not {(q.shape[0], K)}")
    return np.array([[-1 if m is None else m for m in row] for row in meta],
                    np.int64)


ROUNDS, ROUND_ROWS = 5, 32  # the two concurrent callers' requests


def concurrent_callers(client, index_id, q, label):
    """Two callers released together, ROUNDS times: the scheduler must merge
    at least one pair of their requests into a shared device window.
    Returns the ids in q's order."""
    counters0 = scheduler_counters(client)
    gate = threading.Barrier(2)
    results, errors = {}, []

    def caller(who):
        try:
            for r in range(ROUNDS):
                lo = (2 * r + who) * ROUND_ROWS
                gate.wait(timeout=120)
                results[lo] = search_ids(client, index_id, q[lo:lo + ROUND_ROWS])
        except Exception as e:  # reported below; the run fails on it
            errors.append(e)
            gate.abort()

    threads = [threading.Thread(target=caller, args=(w,), name=f"caller{w}")
               for w in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        check(not t.is_alive(), f"{label}: a concurrent caller did not return")
    check(not errors, f"{label}: a concurrent caller failed: {errors}")
    # requests that shared a device window with another one
    merged = sum(
        (c["submitted"] - c0["submitted"]) - (c["batches"] - c0["batches"])
        for c, c0 in zip(scheduler_counters(client), counters0))
    check(merged >= 1,
          f"{label}: the scheduler never merged two concurrent requests")
    return np.concatenate([results[lo] for lo in sorted(results)]), merged


def reference(x, q, wd, label):
    """The numpy scan's top-k ids for q over x (made once per corpus)."""
    wd.phase(f"{label}: numpy reference", 600)
    t0 = time.time()
    gt = exact_topk(x, q, K)
    print(f"{label}: numpy exact scan of {q.shape[0]} queries over "
          f"{x.shape} in {time.time() - t0:.1f}s", flush=True)
    return gt


def serve_and_check(client, index_id, q, gt, wd, label):
    """A few requests of different shapes at k=10 — one single query,
    256-row batches, two concurrent callers — then recall@10 of everything
    returned against the numpy scan's ids ``gt``. q holds 1 + n * 256 +
    2 * ROUNDS * ROUND_ROWS rows."""
    wd.phase(f"{label}: search", 600)
    got, times = [], []
    tail = 2 * ROUNDS * ROUND_ROWS
    for lo in [0] + list(range(1, q.shape[0] - tail, 256)):
        block = q[lo:lo + (1 if lo == 0 else 256)]
        t0 = time.time()
        got.append(search_ids(client, index_id, block))
        times.append(time.time() - t0)
    ids, merged = concurrent_callers(client, index_id, q[-tail:], label)
    got.append(ids)
    rec = recall_at_k(np.concatenate(got), gt, K)
    print(f"{label}: queries={q.shape[0]} recall@{K}={rec:.4f} "
          f"first_1_row_s={times[0]:.2f} first_256_rows_s={times[1]:.2f} "
          f"later_256_rows_s={[round(t, 3) for t in times[2:]]} "
          f"requests_in_shared_windows={merged}", flush=True)
    check(rec >= RECALL_BAR, f"{label}: recall@{K} {rec:.4f} < {RECALL_BAR}")


def knnlm_phase(client, index_id, x, q, gt, wd, label, **extra):
    cfg = IndexCfg(**KNNLM, **extra)
    build(client, index_id, cfg, x, wd, label)
    serve_and_check(client, index_id, q, gt, wd, label)
    check_no_demotion(client, label)


def exact_phase(client, index_id, rng, wd, label, **extra):
    """Upstream's flat configuration as written (L2, dim 128, 1e6 rows):
    one single query and one 256-row batch, ids equal to the numpy scan's."""
    x, q = sift_shaped(rng, ROWS_EXACT, 257)
    cfg = IndexCfg(index_builder_type="flat", dim=128, metric="l2",
                   buffer_bsz=50_000, **extra)
    build(client, index_id, cfg, x, wd, label)
    wd.phase(f"{label}: search", 300)
    t0 = time.time()
    got = np.concatenate([search_ids(client, index_id, q[:1]),
                          search_ids(client, index_id, q[1:])])
    search_s = time.time() - t0
    identical, swaps, wrong = ids_equal_ties_aside(
        x, q, got, reference(x, q, wd, label))
    print(f"{label}: rows={ROWS_EXACT} queries={q.shape[0]} "
          f"rows_identical={identical} tie_swaps={swaps} wrong={wrong} "
          f"search_s={search_s:.2f} (compile included)", flush=True)
    check(wrong == 0, f"{label}: {wrong} returned ids differ from the numpy scan")


def kernel_phase(client, rng, x768, q768, wd):
    """Each Pallas kernel through the served path at its deployment's
    geometry: the three-plane ADC kernel forced on (the main phase's index
    chooses it itself on a chip), then the flat-scan kernel on both its
    codecs. tpu_validate.py compiles them directly once the rank is gone."""
    x, q = x768[:ROWS_KERNEL], q768[-(257 + 2 * ROUNDS * ROUND_ROWS):]
    gt = reference(x, q, wd, "kernel knnlm")
    knnlm_phase(client, "knnlm-pallas", x, q, gt, wd, "kernel knnlm-pallas",
                pallas_adc=True)
    client.drop_index("knnlm-pallas")
    gen = lowrank_mixture(rng, 512, 2048)
    x, q = gen(ROWS_KERNEL), gen(257 + 2 * ROUNDS * ROUND_ROWS)
    gt = reference(x, q, wd, "kernel ivf 512-d")
    for index_id, cfg in (
        ("ivfsq-pallas", IndexCfg(
            index_builder_type="ivfsq", dim=512, metric="l2", centroids=1024,
            nprobe=64, train_num=100_000, buffer_bsz=50_000, pallas_flat=True)),
        ("ivf-sq8-pallas", IndexCfg(
            faiss_factory="IVF1024,SQ8", dim=512, metric="l2", centroids=1024,
            nprobe=64, train_num=100_000, buffer_bsz=50_000, pallas_flat=True)),
    ):
        label = f"kernel {index_id}"
        build(client, index_id, cfg, x, wd, label)
        serve_and_check(client, index_id, q, gt, wd, label)
        check_no_demotion(client, label)
        client.drop_index(index_id)


def direct_kernel_child(env, wd):
    """The Pallas kernels compiled directly against numpy goldens, in a
    child of its own: it needs the chip, so it runs after the rank exited."""
    wd.phase("direct kernels (tpu_validate)", 600)
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "tpu_validate.py")],
        env=env, capture_output=True, text=True, timeout=580)
    sys.stdout.write(proc.stdout)
    check(proc.returncode == 0,
          f"tpu_validate.py exited {proc.returncode}:\n{proc.stderr[-4000:]}")
    print(f"direct kernels: all cases ok in {time.time() - t0:.1f}s", flush=True)


def connect(ranks, wd, label):
    wd.phase(f"{label}: ranks start", 300)
    wd.watch(ranks)
    t0 = time.time()
    client = IndexClient(ranks.discovery)
    device = require_tpu(client.ping(timeout=240.0))
    print(f"{label}: {ranks.num} rank(s) up in {time.time() - t0:.1f}s", flush=True)
    return client, device


def launch_refuses(num, workdir, env):
    """More local ranks than chips must be an error at launch."""
    try:
        with Ranks(num, workdir, env):
            pass
    except RuntimeError as e:
        print(f"launch_local({num}) refused: {e}", flush=True)
        return
    raise SmokeFailure(f"launch_local({num}) started more ranks than chips")


def knnlm_corpus(rng, wd):
    wd.phase("generating the knnlm corpus", 300)
    gen = lowrank_mixture(rng, 768, 8192)
    return gen(ROWS_MAIN), gen(1 + 3 * 256 + 2 * ROUNDS * ROUND_ROWS)


def one_chip(args, workdir, env, wd):
    rng = np.random.default_rng(args.seed)
    with Ranks(1, workdir, env) as ranks:
        client, device = connect(ranks, wd, "one rank")
        launch_refuses(device["count"] + 1, workdir, env)
        x768, q768 = knnlm_corpus(rng, wd)
        gt768 = reference(x768, q768, wd, "main knnlm")
        knnlm_phase(client, "knnlm", x768, q768, gt768, wd, "main knnlm")
        print(f"main knnlm: bytes_in_use={bytes_in_use(client)}", flush=True)
        client.drop_index("knnlm")
        exact_phase(client, "flat", rng, wd, "exact")
        client.drop_index("flat")
        kernel_phase(client, rng, x768, q768, wd)
        client.close()
    direct_kernel_child(env, wd)
    return device


def four_chips(args, workdir, env, wd):
    rng = np.random.default_rng(args.seed)

    # four ranks of one chip each: upstream's shared-nothing layout
    with Ranks(4, workdir, env) as ranks:
        client, device = connect(ranks, wd, "four ranks")
        x768, q768 = knnlm_corpus(rng, wd)
        pings = client.ping(timeout=60.0)
        chips = [p["device"]["visible_chips"] for p in pings]
        check(all(p["device"]["count"] == 1 for p in pings)
              and len(set(chips)) == 4 and None not in chips,
              f"four ranks: not one distinct chip each: {chips}")
        launch_refuses(5, workdir, env)
        gt768 = reference(x768, q768, wd, "knnlm")
        knnlm_phase(client, "knnlm", x768, q768, gt768, wd, "four ranks knnlm")
        per_rank = [stub.generic_fun("get_ntotal", ("knnlm",))
                    for stub in client.sub_indexes]
        print(f"four ranks knnlm: ntotal per rank={per_rank} "
              f"bytes_in_use={bytes_in_use(client)}", flush=True)
        check(sum(per_rank) == ROWS_MAIN
              and max(per_rank) - min(per_rank) <= KNNLM["buffer_bsz"],
              f"four ranks: rows are not spread round-robin: {per_rank}")
        client.close()

    # one rank holding the four-chip mesh
    with Ranks(1, workdir, env) as ranks:
        client, device = connect(ranks, wd, "mesh rank")
        check(device["count"] == 4, f"mesh rank sees {device['count']} chips, not 4")
        for index_id, extra in (
            ("knnlm-masked", dict(shard_lists=True, mesh_devices=4)),
            ("knnlm-routed", dict(shard_lists=True, mesh_devices=4,
                                  probe_routing=True)),
        ):
            before = bytes_in_use(client)[0]
            knnlm_phase(client, index_id, x768, q768, gt768, wd,
                        f"mesh {index_id}", **extra)
            check_spread(before, bytes_in_use(client)[0], f"mesh {index_id}")
            client.drop_index(index_id)
        before = bytes_in_use(client)[0]
        exact_phase(client, "flat-mesh", rng, wd, "mesh flat",
                    mesh_shards=True, mesh_devices=4)
        check_spread(before, bytes_in_use(client)[0], "mesh flat")
        client.close()
    return device


def check_spread(before, after, label):
    """The index must live on all four chips, not on the first: every chip
    grew, and none holds less than an eighth of the fullest."""
    print(f"{label}: bytes_in_use per device before={before} after={after}",
          flush=True)
    check(len(after) == 4 and all(a > b for a, b in zip(after, before))
          and min(after) * 8 >= max(after),
          f"{label}: index bytes are not spread over the four chips: {after}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="four one-chip ranks, then one four-chip mesh rank")
    args = ap.parse_args()

    from jax._src import xla_bridge

    wd = Watchdog()
    cache_dir = envutil.place_compile_cache()
    before = cache_entries(cache_dir)
    # children find the cache through the variable jax itself reads, and
    # the package wherever this script was started from
    env = {"JAX_COMPILATION_CACHE_DIR": cache_dir,
           "PYTHONPATH": os.pathsep.join(
               [REPO] + [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep) if p])}
    t0 = time.time()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        try:
            run = four_chips if args.four_chips else one_chip
            device = run(args, workdir, {**os.environ, **env}, wd)
            check(not xla_bridge._backends,
                  "this process created a jax backend; it must stay off the chip")
        except Exception as e:
            wd.phase("failing", 60)
            report_failure(f"{type(e).__name__}: {e}", wd.ranks)
            raise SystemExit(1)
    print(f"compile cache {cache_dir}: {before} entries before, "
          f"{cache_entries(cache_dir)} after; wall_s={time.time() - t0:.1f}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": device["count"]}}), flush=True)


if __name__ == "__main__":
    main()
