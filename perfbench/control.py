"""The control of the comparison that decides ``correct``: the same check,
fed answers computed in the nearest precision below the one the
configuration states, has to come out NOT correct.

    python3 -m perfbench.control --workload <cell> --seeds 1 2 3 --rows int8
    python3 -m perfbench.control --workload <cell> --seeds 1 2 3 --seconds 5 \\
        --index '{"index_builder_type": null, "faiss_factory": "IVF1024,SQ8"}'

The configurations keep their rows in float16 and take distances in float32,
so the step below is 8-bit rows. ``--rows int8`` puts the plain reference in
the program's place over rows kept as per-dimension 8-bit codes (what SQ8
keeps), at the cell's own size, and needs no chip; ``--rows float16`` is the
same at the stated precision, for comparison, and the control of a
configuration that states float32 rows, where ``--rows float32`` is the
comparison. ``--index`` runs the program
itself on the chip with keys of the configuration's index replaced — for
``ivfsq`` the program's own ``IVF1024,SQ8`` path — through the whole harness.
The benchmark's own runs never come here; PERF.md records the readings.
"""

import argparse
import json
import shutil
import sys
import tempfile
import time

import numpy as np

from perfbench import corpus, correctness, load_gen, loader


def as_int8(chunks):
    """Every row as per-dimension 8-bit codes over the corpus's range."""
    lo = np.min([c.min(0) for c in chunks], axis=0)
    hi = np.max([c.max(0) for c in chunks], axis=0)
    step = (hi - lo) / 255.0
    return [(lo + np.round((c - lo) / step) * step).astype(np.float32) for c in chunks]


def as_float16(chunks):
    return [c.astype(np.float16).astype(np.float32) for c in chunks]


def as_float32(chunks):
    return chunks


KEPT_AS = {"int8": as_int8, "float16": as_float16, "float32": as_float32}


def reference_in_the_programs_place(cell, seed, kept_as):
    """Answers for a sample of the cell's queries from an exact scan over the
    rows as ``kept_as`` leaves them, held against the untouched reference."""
    config, traffic = cell.config, cell.traffic
    mix = corpus.mixture_for(config, seed)
    rows, bsz = int(config["rows"]), int(config["index"]["buffer_bsz"])
    chunks = [mix.chunk(corpus.CORPUS, i, min(bsz, rows - i * bsz))
              for i in range(-(-rows // bsz))]
    pool = mix.chunk(corpus.QUERIES, 0, int(traffic["query_pool_rows"]))
    stored = KEPT_AS[kept_as](chunks)
    per, k = int(traffic["rows_per_request"]), int(config["k"])
    results = []
    for i in range(-(-int(config["limits"]["sample_rows"]) // per)):
        q = pool[i * per:(i + 1) * per]
        _, ids = cell.reference.exact_topk(stored, q, k)
        dist = cell.reference.exact_distances(correctness.gather_rows(stored, ids), q)
        results.append(load_gen.Result(0.0, 1.0, i * per, per, True,
                                       dist.astype(np.float32), ids))
    checks = correctness.Checks()
    correctness.compare_window(checks, config, cell.reference, chunks, pool, results, seed)
    return checks


def program_with_another_index(cell, seed, seconds, index):
    from perfbench import run  # starts ranks: only this mode needs the chip

    cell.config["index"] = {k: v for k, v in {**cell.config["index"], **index}.items()
                            if v is not None}
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0)
    workdir = tempfile.mkdtemp(prefix="perfbench_control_")
    wd = run.Watchdog()
    try:
        result = run.run(args, cell, workdir, "tpu", "/device:TPU:", time.time(), wd)
    finally:
        wd.done.set()
        shutil.rmtree(workdir, ignore_errors=True)
    return result["correct"]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rows", choices=sorted(KEPT_AS))
    ap.add_argument("--index", type=json.loads)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        cell = loader.Cell(args.workload)
        if args.index is not None:
            correct = program_with_another_index(cell, seed, args.seconds, args.index)
        else:
            correct = reference_in_the_programs_place(cell, seed, args.rows).correct
        print(f"control {args.workload} seed={seed} "
              f"{args.rows or json.dumps(args.index)}: correct={correct}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
