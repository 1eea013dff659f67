#!/usr/bin/env python3
"""Bulk-ingest throughput at reference scale (VERDICT r3 item 6).

The reference's headline workflow is a memmap load across a server fleet
(README.md:147-176, scripts/load_data.py with periodic saves). This runs
that exact pipeline — scripts/load_data.py against a launch_local
subprocess cluster — at 1e7 x 128-d rows / 4 ranks by default and reports
end-to-end ingest rows/s (memmap read + fp32 convert + binary RPC +
server buffering + async index add), excluding the final save.

CPU-only by construction: the ranks are pinned to ``JAX_PLATFORMS=cpu``,
so the figure is the protocol path's host cost and never a device speed.
This process stays off jax entirely — it launches the ranks.

    python benchmarks/ingest_scale.py [--rows 10000000] [--dim 128]
        [--ranks 4] [--bs 20000] [--keep]

Prints one JSON line: {"metric": "bulk ingest rows/s ...", ...}.
"""

import argparse
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rss_kb(pids):
    """Summed VmRSS of the given pids (0 for ones already gone)."""
    total = 0
    for pid in pids:
        try:
            with open(f"/proc/{pid}/status") as f:
                for line in f:
                    if line.startswith("VmRSS:"):
                        total += int(line.split()[1])
                        break
        except OSError:
            pass
    return total


class RssSampler(threading.Thread):
    """Samples the server fleet's summed RSS during ingest (VERDICT r4: the
    IVF/PQ family must not mirror the corpus in host RAM — growth per row
    should be codes+ids+position-map+metadata, not a second payload copy)."""

    def __init__(self, pids, period=1.0):
        super().__init__(daemon=True)
        self.pids = pids
        self.period = period
        self.samples = []  # (t, rss_kb)
        # NB: must not be named _stop — Thread.join() calls self._stop()
        self._halt = threading.Event()

    def run(self):
        while not self._halt.is_set():
            self.samples.append((time.time(), _rss_kb(self.pids)))
            self._halt.wait(self.period)

    def stop(self):
        self._halt.set()
        self.join()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=10_000_000)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--ranks", type=int, default=4)
    ap.add_argument("--bs", type=int, default=20_000)
    ap.add_argument("--base-port", type=int, default=13741)
    ap.add_argument("--builder", choices=("flat", "ivfpq"), default="flat",
                    help="flat = reference default; ivfpq = the knnlm "
                         "IVF-PQ family (exercises encode+list append and "
                         "the no-host-mirror memory story)")
    ap.add_argument("--centroids", type=int, default=1024,
                    help="nlist for --builder ivfpq")
    ap.add_argument("--keep", action="store_true",
                    help="keep the temp dir (memmap + index storage)")
    ap.add_argument("--verify-reload", action="store_true",
                    help="after ingest+save: kill the fleet, relaunch with "
                         "load_index=True, and golden-check a search batch "
                         "against pre-kill results (the reference's "
                         "save/restore workflow, README.md:147-176)")
    args = ap.parse_args()

    sys.path.insert(0, REPO)
    # the ranks place their own compile cache (envutil.place_compile_cache,
    # called by launcher.run_server)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    tmp = tempfile.mkdtemp(prefix="ingest_scale_")
    mmap_path = os.path.join(tmp, "data.mmap")
    disc = os.path.join(tmp, "disc.txt")
    storage = os.path.join(tmp, "storage")

    from distributed_faiss_tpu.parallel import launcher
    from distributed_faiss_tpu.utils.config import IndexCfg

    t_mk = time.time()
    subprocess.run(
        [sys.executable, "scripts/load_data.py", "--data", mmap_path,
         "--dtype", "fp16", "--dim", str(args.dim), "--discovery", disc,
         "--make-random", str(args.rows)],
        cwd=REPO, env=env, check=True, capture_output=True,
    )
    print(f"memmap ready ({args.rows}x{args.dim} fp16, "
          f"{os.path.getsize(mmap_path) / 2 ** 30:.2f} GiB, "
          f"{time.time() - t_mk:.0f}s)", file=sys.stderr)

    if args.builder == "ivfpq":
        cfg = IndexCfg(index_builder_type="knnlm", dim=args.dim, metric="l2",
                       train_num=100_000, centroids=args.centroids)
    else:
        cfg = IndexCfg(index_builder_type="flat", dim=args.dim, metric="l2",
                       train_num=100_000)
    cfg_path = os.path.join(tmp, "cfg.json")
    cfg.save(cfg_path)

    procs = launcher.launch_local(args.ranks, disc, storage,
                                  base_port=args.base_port, env=env)
    rc = 1
    sampler = RssSampler([p.pid for p in procs])
    sampler.start()
    try:
        t0 = time.time()
        out = subprocess.run(
            [sys.executable, "scripts/load_data.py", "--data", mmap_path,
             "--dtype", "fp16", "--dim", str(args.dim), "--bs", str(args.bs),
             "--discovery", disc, "--index-id", "ingest", "--cfg", cfg_path],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=4 * 3600,
        )
        wall = time.time() - t0
        log = out.stdout + out.stderr
        if out.returncode != 0:
            print(log[-4000:], file=sys.stderr)
            raise SystemExit(f"loader failed rc={out.returncode}")
        # "load complete: N rows in Xs; ntotal=N" — ingest only, save excluded
        m = re.search(r"load complete: (\d+) rows in ([\d.]+)s; ntotal=(\d+)",
                      log)
        assert m, log[-2000:]
        rows, secs, ntotal = int(m.group(1)), float(m.group(2)), int(m.group(3))
        assert ntotal == rows, (ntotal, rows)
        rate = rows / secs
        sampler.stop()
        # RSS growth per ingested row over the steady second half of the
        # INGEST interval only — the final save deliberately materializes a
        # full per-rank host array (the bytes the save file needs) and must
        # not contaminate the steady-state number (r5 review). NOTE: on the
        # CPU jax backend "device" arrays live in process RSS too, so the
        # floor is one payload copy (codes/vectors + ids); the
        # no-host-mirror claim is growth ~= that single copy, not 2x.
        rss_per_row = None
        # anchor the window to the loader's own ingest-start timestamp
        # (ADVICE r5): t0 is the subprocess spawn time, which includes
        # python/jax startup and client connect, so a t0-anchored window
        # shifts earlier than the true ingest interval and absorbs
        # pre-ingest compile/allocation RSS growth into the per-row number
        m_ts = re.search(r"ingest start ts=([\d.]+)", log)
        ingest_t0 = float(m_ts.group(1)) if m_ts else t0
        ingest_t1 = ingest_t0 + secs
        window = [s for s in sampler.samples
                  if ingest_t0 + 0.5 * secs <= s[0] <= ingest_t1]
        if len(window) >= 2:
            dt = window[-1][0] - window[0][0]
            if dt > 1:
                rows_in_window = rate * dt
                rss_per_row = (window[-1][1] - window[0][1]) * 1024.0 / rows_in_window
        result = {
            "metric": (f"bulk ingest rows/s (backend=cpu protocol path, "
                       f"{args.ranks} subprocess ranks, {args.builder}, "
                       f"{rows}x{args.dim} fp16 memmap, bs={args.bs}; "
                       f"wall incl. save {wall:.0f}s)"),
            "value": round(rate, 1),
            "unit": "rows/s",
            "rows": rows,
            "ingest_seconds": round(secs, 1),
            "rss_peak_mb": round(max(s[1] for s in sampler.samples) / 1024.0, 1)
            if sampler.samples else None,
        }
        if rss_per_row is not None:
            result["rss_bytes_per_row_steady"] = round(rss_per_row, 1)
        if args.verify_reload:
            import numpy as np

            from distributed_faiss_tpu.parallel.client import IndexClient

            rng = np.random.default_rng(7)
            q = rng.standard_normal((16, args.dim)).astype(np.float32)
            client = IndexClient(disc, cfg_path=cfg_path)
            ref_scores, ref_meta = client.search(q, 5, "ingest")
            client.close()
            for p in procs:
                p.kill()
            for p in procs:
                p.wait()
            t_re = time.time()
            disc2 = os.path.join(tmp, "disc_reload.txt")
            procs = launcher.launch_local(
                args.ranks, disc2, storage, base_port=args.base_port + 100,
                env=env)
            client = IndexClient(disc2)
            assert client.load_index("ingest", cfg), "reload failed"
            deadline = time.time() + 1800
            while time.time() < deadline:
                if client.get_ntotal("ingest") == rows:
                    break
                time.sleep(2)
            got_scores, got_meta = client.search(q, 5, "ingest")
            client.close()
            np.testing.assert_allclose(got_scores, ref_scores, rtol=1e-4,
                                       atol=1e-4)
            assert got_meta == ref_meta, "metadata changed across reload"
            result["reload_seconds"] = round(time.time() - t_re, 1)
            result["reload_golden_equal"] = True
        print(json.dumps(result))
        rc = 0
    finally:
        if sampler.is_alive():
            sampler.stop()
        for p in procs:
            p.kill()
        if not args.keep:
            shutil.rmtree(tmp, ignore_errors=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
