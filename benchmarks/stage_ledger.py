#!/usr/bin/env python3
"""Both stage ledgers of one benchmark cell, from an UNTRACED run's counters.

    python3 benchmarks/stage_ledger.py --workload <cell> --seed <n> --seconds <s>
        [--profile <seconds>]

``perfbench.run --trace 0`` with one thing added: two ``get_perf_stats``
snapshots around its window, which the benchmark takes in traced runs only
(ROADMAP S2, item (c): once it takes them always, this script goes). Prints
every per-layer metric the snapshots can feed, and the two closures of
docs/OPERATIONS.md's stage ledger. ``--profile`` has every rank run its
``profile`` op that long, three seconds into the window. Beside a ``--trace
1`` run of the same cell and seed the numbers size what the profiler does to
a run. A builder's tool: no benchmark file reads it.
"""

import argparse
import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_faiss_tpu.utils import tracing  # noqa: E402
from perfbench import ledger, loader, run, stats  # noqa: E402

OBS = {}
untraced_window = run.measure


def measure(profile_s):
    def with_snapshots(client, *args, **kwargs):
        def ask():
            time.sleep(3.0)
            # on threads of its own: a worker of the client's fan-out pool
            # held for the session would be a request less in flight
            with ThreadPoolExecutor(len(client.sub_indexes)) as own:
                OBS["profiles"] = list(own.map(
                    lambda s: s.generic_fun("profile", (profile_s,),
                                            timeout=profile_s + 120),
                    client.sub_indexes))

        asker = threading.Thread(target=ask, name="profile-asker")
        before = run.perf_stats(client)
        if profile_s:
            asker.start()
        obs, t0 = untraced_window(client, *args, **kwargs)
        after = run.perf_stats(client)
        if profile_s:
            asker.join()
        OBS.update(obs, stats_before=before, stats_after=after,
                   index_id=run.INDEX_ID)
        return obs, t0

    return with_snapshots


def closures(obs):
    """Per rank, the launch loop's stages over the window and the launch's
    three over ``device_search_s``; the client's five over ``client.search``
    (the slowest stub's, as the fan-out waits for it) and that over the
    callers' own mean."""
    def total(path):
        return stats.per_rank(obs, path, ledger.window_total)

    loop = [total(ledger.sched(n) if n.startswith("sched.")
                  else ledger.engine(obs, n)) for n in tracing.LAUNCH_LOOP]
    inner = [total(ledger.engine(obs, n))
             for n in ("engine.feed", "engine.scan", "engine.refine_fetch")]
    launch = total(ledger.engine(obs, "device_search_s"))
    whole = ledger.client_wide_mean_ms(obs, "client.search")
    parts = [ledger.client_wide_mean_ms(obs, n)
             for n in ("client.fanout_wait", "client.merge")]
    parts += [1e3 * max(stats.per_rank(obs, ledger.stub(n)))
              for n in ("client.pack", "client.send", "client.round_trip.search")]
    return {
        "launch_loop_over_window": [sum(r) / obs["window_s"] for r in zip(*loop)],
        "feed_scan_refine_over_launch": [sum(r) / s
                                         for r, s in zip(zip(*inner), launch)],
        "client_stages_over_client_search": sum(parts) / whole,
        "client_search_over_callers_mean": whole / stats.client_mean_ms(obs),
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--profile", type=float, default=0.0)
    args = ap.parse_args()
    run.measure = measure(args.profile)
    rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                   "--seconds", str(args.seconds), "--trace", "0"])
    if rc or not OBS:
        return rc or 1
    cell = loader.Cell(args.workload)
    OBS.update(setup={}, config=cell.config, traffic=cell.traffic)
    metrics = {}
    for metric, reader in cell.layer_readers():
        try:
            metrics[metric["name"]] = reader.read(OBS)
        except (KeyError, TypeError):  # needs the trace or the set-up's facts
            continue
    print("LEDGER " + json.dumps({
        "cell": args.workload, "seed": args.seed, "window_s": OBS["window_s"],
        "per_layer_untraced": {k: v for k, v in metrics.items() if v is not None},
        "closures": closures(OBS), "profiles": OBS.get("profiles"),
    }, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
