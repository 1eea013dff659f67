"""The list-major probe scan (``models/ivf._ivf_flat_search``, the XLA arm)
timed alone on the chip, a launch at a time, against another checkout's.

Two kinds of store, both at ``ivfsq-batch``'s widths (d 512, float16 rows,
1024 lists, nprobe 64, k 10, stored norms):

  cell  the cell's own index: ``perfbench``'s seeded corpus (1e6 rows, lists
        of 977 rows on average under a capacity of 4096), k-means and the
        cell's query stream, built in this process by ``IVFFlatIndex``
  full  every list filled to the share ``--fill`` of its capacity
        (``--cap``, default 512 and 4096; default share 1: the case in
        which stopping at the end of a list saves nothing), random rows

Each row of output is one JSON line naming the device: milliseconds a
launch of ``--rows`` query rows (mean over ``--iters`` launches of fresh
queries, after a warm-up), and for this checkout the program's count output
(sub-blocks at whole capacity, sub-blocks gathered). ``--parent DIR`` times
the same launches through DIR's ``distributed_faiss_tpu/models/ivf.py`` on
the same arrays, in the order parent, change, change, parent.
``--sub-blocks`` tells the rule the lists are empty (``fill`` 0), so that a
full store too is scanned a sub-block a tile; with and without it at
``--fill 1,0.9,0.75,0.5`` is the timing behind the rule's
``_WHOLE_LIST_FILL``. ``--set NAME=INT`` sets a constant of ``models/ivf``
(``_LIST_BLOCK_BYTES``, ``_GATHER_SLICE_BYTES``) before the change's
programs are traced: how ``_LIST_BLOCK_BYTES`` was checked at half and
twice its value (the readings of all three: PERF.md section 6, PR 43).
``--profile DIR`` traces ``--iters`` launches of this checkout's program
and prints the device operations that took most time.

TPU only: exits non-zero anywhere else. Nothing here is a benchmark cell.
"""

import argparse
import importlib.util
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

D, NLIST, NPROBE, K = 512, 1024, 64, 10


def load_ivf(root, name):
    """``models/ivf.py`` of another checkout as a module of its own (its
    imports resolve to this checkout's ``ops`` and ``models.base``, which
    the comparison holds fixed)."""
    path = os.path.join(root, "distributed_faiss_tpu", "models", "ivf.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_store(seed):
    """(centroids, data, ids, sizes, norms) of the cell's index and a
    function giving its i-th block of queries."""
    from distributed_faiss_tpu.models.ivf import IVFFlatIndex
    from perfbench import corpus

    with open(os.path.join(REPO, "perfbench", "configs", "ivfsq", "config.json")) as f:
        config = json.load(f)
    mix = corpus.mixture_for(config, seed)
    idx = IVFFlatIndex(D, NLIST, "l2", codec="f16")
    chunk = config["index"]["buffer_bsz"]
    t0 = time.perf_counter()
    idx.train(np.concatenate([mix.chunk(corpus.CORPUS, i, chunk)
                              for i in range(config["index"]["train_num"] // chunk)]))
    for i in range(config["rows"] // chunk):
        idx.add(mix.chunk(corpus.CORPUS, i, chunk))
    sizes = np.asarray(idx.lists.sizes)
    print(json.dumps({"store": "cell", "rows": int(sizes.sum()), "cap": idx.lists.cap,
                      "longest": int(sizes.max()), "mean": float(sizes.mean()),
                      "build_s": round(time.perf_counter() - t0, 1)}), flush=True)
    store = (idx.centroids, idx.lists.data, idx.lists.ids, idx.lists.sizes,
             idx.norm_lists.data)
    return store, lambda i, n: mix.chunk(corpus.QUERIES, i, n)


def full_store(cap, seed, share=1.0):
    """Every list filled to ``share`` of its capacity: random float16 rows,
    made on the device."""
    import jax
    import jax.numpy as jnp

    from distributed_faiss_tpu.models import base

    key = jax.random.PRNGKey(seed)
    data = jax.random.normal(key, (NLIST, cap, D), jnp.float16)
    norms = jax.jit(lambda x: base.row_norms_f32(x.astype(jnp.float32)))(data)
    cents = jax.random.normal(jax.random.fold_in(key, 1), (NLIST, D), jnp.float32)
    ids = jnp.arange(NLIST * cap, dtype=jnp.int32).reshape(NLIST, cap)
    sizes = jnp.full((NLIST,), int(cap * share), jnp.int32)
    print(json.dumps({"store": "full", "cap": cap, "rows_a_list": int(cap * share)}),
          flush=True)
    return ((cents, data, ids, sizes, norms),
            lambda i, n: np.random.default_rng([seed, i]).standard_normal(
                (n, D)).astype(np.float32))


def timed(mod, store, queries, rows, iters, fill=None):
    """Mean seconds a launch of ``mod._ivf_flat_search`` over ``iters``
    query blocks, the last launch's outputs and the tiling. ``fill``: the
    share of the lists' capacity in use as the rule is told it (None: what
    the store holds, as ``IVFFlatIndex._scan_tiling`` reads it; a checkout
    whose rule takes no fill is not told)."""
    import inspect

    import jax

    cents, data, ids, sizes, norms = store
    shape = (rows, NPROBE, NLIST, data.shape[1], D, data.dtype.itemsize)
    if "fill" in inspect.signature(mod.listmajor_tiling).parameters:
        if fill is None:
            fill = float(np.asarray(sizes).sum()) / (NLIST * data.shape[1])
        tiling = mod.listmajor_tiling(*shape, fill=fill)
    else:
        tiling = mod.listmajor_tiling(*shape)
    static = dict(zip(("tile", "group", "sub"), tiling))

    def launch(q):
        return mod._ivf_flat_search(
            cents, data, ids, sizes, q, k=K, nprobe=NPROBE, g=1, metric="l2",
            codec="f16", list_norms=norms, nvalid=jax.device_put(np.int32(rows)),
            **static)

    blocks = [jax.device_put(queries(i, rows)) for i in range(iters)]
    for q in blocks[:2]:
        jax.block_until_ready(launch(q))
    t0 = time.perf_counter()
    for q in blocks:
        out = launch(q)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters, out, tiling


def report(which, store_name, mod, store, queries, args, device, fill=None):
    secs, out, tiling = timed(mod, store, queries, args.rows, args.iters, fill)
    row = {"program": which, "store": store_name, "rows": args.rows,
           "tiling": list(tiling), "ms_a_launch": round(secs * 1e3, 4),
           "device": device}
    if len(out) > 2:
        whole, live = (int(v) for v in np.asarray(out[2]).reshape(-1, 2).sum(0))
        row.update(sub_blocks_at_capacity=whole, sub_blocks_gathered=live,
                   skipped_pct=round(100.0 * (whole - live) / max(whole, 1), 2))
    print(json.dumps(row), flush=True)
    return out


def profile(mod, store, queries, args, out_dir):
    import glob

    import jax

    from perfbench import trace_reduce

    jax.profiler.start_trace(out_dir)
    timed(mod, store, queries, args.rows, args.iters)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(out_dir, "plugins", "profile", "*", "*.xplane.pb"))
    totals = {}
    for plane, line, name, _, dur in trace_reduce.read_xplane(path):
        if plane.startswith("/device:TPU:") and line == trace_reduce.OPS_LINE:
            totals[name] = totals.get(name, 0) + dur
    launches = args.iters + 2
    for name, ns in sorted(totals.items(), key=lambda kv: -kv[1])[:args.top]:
        print(json.dumps({"op": name, "ms_a_launch": round(ns / 1e6 / launches, 4)}),
              flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--store", default="cell,full", help="cell, full, or both")
    ap.add_argument("--cap", default="512,4096", help="capacities of the full stores")
    ap.add_argument("--fill", default="1", help="shares of its capacity a full store's list holds")
    ap.add_argument("--rows", type=int, default=128)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=3000006007)
    ap.add_argument("--parent", default=None, help="a checkout to time beside this one")
    ap.add_argument("--sub-blocks", action="store_true",
                    help="tell the rule fill 0: sub-block tiles whatever the store holds")
    ap.add_argument("--set", action="append", default=[], metavar="NAME=INT")
    ap.add_argument("--profile", default=None, metavar="DIR")
    ap.add_argument("--top", type=int, default=16)
    args = ap.parse_args()

    import jax

    from distributed_faiss_tpu.models import ivf
    from distributed_faiss_tpu.utils import envutil

    envutil.place_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.stderr.write(f"listmajor_scan: needs a TPU, found {dev.platform!r}\n")
        return 1
    device = f"{dev.platform}:{dev.device_kind}"
    for item in args.set:
        name, value = item.split("=")
        value = int(value) if value.lstrip("-").isdigit() else value
        setattr(ivf, name, value)
    parent = load_ivf(args.parent, "parent_ivf") if args.parent else None

    stores = []
    if "cell" in args.store:
        stores.append(("cell", lambda: cell_store(args.seed)))
    if "full" in args.store:
        for cap in (int(c) for c in args.cap.split(",")):
            for share in (float(f) for f in args.fill.split(",")):
                stores.append((f"full{cap}" + (f"@{share}" if share < 1 else ""),
                               lambda cap=cap, share=share: full_store(cap, args.seed, share)))
    for name, make in stores:
        store, queries = make()
        fill = 0.0 if args.sub_blocks else None
        if parent is None:
            report("change", name, ivf, store, queries, args, device, fill)
        else:
            outs = [report(which, name, mod, store, queries, args, device, fill)
                    for which, mod in (("parent", parent), ("change", ivf),
                                       ("change", ivf), ("parent", parent))]
            same = bool(np.array_equal(np.asarray(outs[0][1]), np.asarray(outs[1][1])))
            gap = float(np.max(np.abs(np.asarray(outs[0][0]) - np.asarray(outs[1][0]))))
            print(json.dumps({"store": name, "same_neighbours_as_parent": same,
                              "largest_score_difference": gap}), flush=True)
        if args.profile:
            profile(ivf, store, queries, args, os.path.join(args.profile, name))
        del store
    return 0


if __name__ == "__main__":
    sys.exit(main())
