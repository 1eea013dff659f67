#!/usr/bin/env python3
"""An add that grows the store while searches are in flight, on the device
the process finds (a TPU through the chip tool, the CPU in a rehearsal).

    python3 benchmarks/growth_under_search.py [--rows 200000] [--seconds 20]

One engine in this process behind a ``SearchScheduler`` (two windows in
flight), for a ``flat`` index (the store every launch reads is the one an
add's ``_write_rows`` donates and a growth reallocates) and for a ``knnlm``
index with exact refine (the rerank reads the donated refine store). Four
callers search rows of the first half of the corpus without a pause while
the second half is added in batches, across a doubling of the stores'
capacity. What it holds the engine to (ISSUE 41, Tentpole 3):

- every search is correct whatever was being added: a stored row's nearest
  neighbour is itself, and a window launched just before a drain starts
  (the rank turns new searches away while it drains, as it always has) is
  answered from the store it was launched on though the add runs before
  its collect (a pending search holds its operands; a program that
  donates one of them runs behind it on the device);
- no deadlock: callers and adder end inside their time limit;
- ``ntotal`` equals the acknowledged rows once the buffer is drained, and a
  search launched after that finds the new rows first (``self_lookup_top1``).

Prints one JSON line an index kind; exits 1 if any check fails.
"""

import argparse
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_faiss_tpu import Index, IndexCfg, IndexState  # noqa: E402
from distributed_faiss_tpu.parallel.server import _EngineSearch  # noqa: E402
from distributed_faiss_tpu.serving import SearchScheduler  # noqa: E402
from distributed_faiss_tpu.utils.config import SchedulerCfg  # noqa: E402

K, CALLERS, ROWS_A_REQUEST = 10, 4, 64


def drained(index, rows, limit_s):
    deadline = time.time() + limit_s
    while time.time() < deadline:
        if (index.get_state() == IndexState.TRAINED
                and index.get_idx_data_num() == (0, rows)):
            return True
        time.sleep(0.05)
    return False


def check(kind, cfg, x, seconds):
    import jax

    half = x.shape[0] // 2
    index = Index(cfg)
    index.add_batch(x[:half], list(range(half)), train_async_if_triggered=False)
    index.train()
    ok = {"drained_first_half": drained(index, half, 600)}
    for rows in range(ROWS_A_REQUEST, CALLERS * ROWS_A_REQUEST + 1, ROWS_A_REQUEST):
        index.search_batched(x[:rows], K)  # compile every window's shape first
    # what a rank hands its scheduler: the engine's batched entry, both forms
    sched = SearchScheduler(_EngineSearch(lambda _id: index), SchedulerCfg(),
                            name=f"growth-{kind}")
    stop, wrong, errors = threading.Event(), [], []
    searches, rejected = [0] * CALLERS, [0] * CALLERS

    def caller(i):
        rng = np.random.default_rng(i)
        while not stop.is_set():
            ids = rng.integers(0, half, ROWS_A_REQUEST)
            try:
                _, meta, _ = sched.submit(kind, x[ids], K)
            except RuntimeError as e:
                # a rank turns searches away while it drains its buffer (a
                # client fails over to a replica): the windows at stake are
                # those launched just before, collected during the add
                if "IndexState.ADD" in str(e):
                    rejected[i] += 1
                    continue
                errors.append(repr(e))  # a check that fails, not a crash to hide
                return
            wrong.extend(int(want) for want, got in zip(ids, meta) if got[0] != want)
            searches[i] += 1

    threads = [threading.Thread(target=caller, args=(i,), name=f"caller{i}")
               for i in range(CALLERS)]
    for t in threads:
        t.start()
    t0, before = time.time(), index.perf_stats()
    step = max(1024, half // 16)
    for lo in range(half, x.shape[0], step):
        hi = min(x.shape[0], lo + step)
        index.add_batch(x[lo:hi], list(range(lo, hi)))
        time.sleep(seconds / 32)  # spread over the window: searches in between
    ok["drained_all"] = drained(index, x.shape[0], 600)
    time.sleep(max(0.0, seconds - (time.time() - t0)))
    stop.set()
    for t in threads:
        t.join(60)
    ok["no_deadlock"] = not any(t.is_alive() for t in threads)
    after = index.perf_stats()
    new = np.arange(half, x.shape[0])[:: max(1, half // 256)]
    _, meta, _ = sched.submit(kind, x[new], K)
    sched.stop()
    ok["ntotal_equals_acknowledged"] = index.get_idx_data_num() == (0, x.shape[0])
    ok["every_search_correct"] = not wrong and not errors
    ok["new_rows_found_first"] = [m[0] for m in meta] == list(new)

    def moved(name):
        return after.get(name, {"count": 0})["count"] - before.get(name, {"count": 0})["count"]

    out = {"kind": kind, "device": jax.devices()[0].device_kind, "ok": all(ok.values()),
           **ok, "rows": int(x.shape[0]), "searches": sum(searches),
           "turned_away_while_adding": sum(rejected),
           "wrong_rows": len(wrong), "errors": errors[:3],
           "windows": moved("device_search_s"),
           "windows_overlapped": moved("engine.launch_overlapped"),
           "store_grows": moved("engine.store_grow")}
    print(json.dumps(out), flush=True)
    index.retire()
    return out["ok"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    rng = np.random.default_rng(args.seed)
    centres = rng.standard_normal((64, args.dim)).astype(np.float32) * 4
    x = (centres[rng.integers(0, 64, args.rows)]
         + rng.standard_normal((args.rows, args.dim)).astype(np.float32))
    good = True
    with tempfile.TemporaryDirectory(prefix="growth_") as tmp:
        for kind, extra in (
                ("flat", dict(index_builder_type="flat", train_num=0)),
                ("knnlm", dict(index_builder_type="knnlm", train_num=args.rows // 4,
                               centroids=256, nprobe=32, code_size=32,
                               extra={"refine_k_factor": 8}))):
            cfg = IndexCfg(dim=args.dim, metric="l2", **extra)
            cfg.index_storage_dir = os.path.join(tmp, kind)
            good = check(kind, cfg, x, args.seconds) and good
    sys.exit(0 if good else 1)


if __name__ == "__main__":
    main()
