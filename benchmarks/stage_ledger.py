#!/usr/bin/env python3
"""Both stage ledgers of one benchmark cell, and the chip's timeline, from a
run's counters.

    python3 benchmarks/stage_ledger.py --workload <cell> --seed <n> --seconds <s>
        [--trace 1] [--profile <seconds>]

``perfbench.run --trace 0`` with one thing added: two ``get_perf_stats``
snapshots around its window, which the benchmark takes in traced runs only
(ROADMAP S2, item (c): once it takes them always, this script goes). Prints
every per-layer metric the snapshots can feed — the benchmark's own, and
every reader file under ``perfbench/layer_metrics/`` that no entry of
``BENCHMARK.json`` names yet (the chip timeline's ten, PR 42), and
``kernel.list_skip_pct`` (PR 43, read here until it has such a file) — the two
closures of docs/OPERATIONS.md's stage ledger, and the chip's timeline as
the scheduler books it, a rank: its five rows over the window, whether busy
plus idle is what ``chip_timeline_s`` moved by, and, under ``--trace 1``, the
device trace's busy seconds and busy time a launch beside them.
``--profile`` has every rank run its ``profile`` op that long, three seconds
into the window, with a snapshot of the counters either side of the call, so
that ``idle_by_cause`` can be laid beside the ``sched.chip_idle.*`` rows of
the same seconds. Beside a ``--trace 1`` run of the same cell and seed the
untraced numbers size what the profiler does to a run. A builder's tool: no
benchmark file reads it.
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
the_window = run.measure


def measure(profile_s):
    def with_snapshots(client, *args, **kwargs):
        def ask():
            time.sleep(3.0)
            # on threads of its own: a worker of the client's fan-out pool
            # held for the session would be a request less in flight
            def one(stub):
                before = stub.generic_fun("get_perf_stats", ())
                reply = stub.generic_fun("profile", (profile_s,),
                                         timeout=profile_s + 120)
                after = stub.generic_fun("get_perf_stats", ())
                reply["counters_meanwhile"] = chip_rows(before, after)
                return reply

            with ThreadPoolExecutor(len(client.sub_indexes)) as own:
                OBS["profiles"] = list(own.map(one, client.sub_indexes))

        asker = threading.Thread(target=ask, name="profile-asker")
        before = run.perf_stats(client)
        if profile_s:
            asker.start()
        obs, t0 = the_window(client, *args, **kwargs)
        after = run.perf_stats(client)
        if profile_s:
            asker.join()
        # (a traced window has taken its own pair, closer to the window)
        OBS.update({"stats_before": before, "stats_after": after, **obs},
                   index_id=run.INDEX_ID)
        return obs, t0

    return with_snapshots


def chip_rows(before, after):
    """One rank's timeline between two snapshots: every row's count and
    seconds, what ``chip_timeline_s`` (the last ``ready`` less the first
    ``dispatched``) moved by, and busy + idle less that: 0 by construction."""
    def moved(name, field):
        a, b = (stats.dig(s, ledger.sched(name)) or {} for s in (after, before))
        return a.get(field, 0) - b.get(field, 0)

    line = [stats.dig(s, ("scheduler", "counters", "chip_timeline_s"))
            for s in (before, after)]
    if line[1] is None:
        return None  # a program without the timeline
    out = {name: [moved(name, "count"), moved(name, "total_s")]
           for name in tracing.CHIP_ROWS}
    out["chip_timeline_s"] = line[1] - (line[0] or 0.0)
    out["busy_plus_idle_less_timeline_s"] = (
        sum(out[name][1] for name in tracing.CHIP_ROWS if name != "sched.chip_queue")
        - out["chip_timeline_s"])
    return out


def chip_timeline(obs):
    """Per rank: the rows over the window and, in a traced run, the device
    trace's busy seconds and launches beside them."""
    out = []
    for i, (before, after) in enumerate(zip(obs["stats_before"], obs["stats_after"])):
        rank = chip_rows(before, after)
        if rank is None:
            return None
        launches = stats.window_count(before, after,
                                      ledger.engine(obs, "device_search_s"))
        rank["launches"] = launches
        if obs.get("traces"):
            busy = obs["traces"][i]["busy_s"]
            rank["trace_busy_s"] = busy
            rank["trace_busy_ms_a_launch"] = 1e3 * busy / launches if launches else None
            rank["trace_idle_pct"] = 100.0 * (1.0 - busy / obs["window_s"])
        out.append(rank)
    return out


def unlisted_readers(cell):
    """The reader files no entry of ``BENCHMARK.json`` names (yet), for this
    cell's kind: ``.online`` ones where it is judged on latency."""
    listed = {m["name"] for m in cell.bench["per_layer"]}
    online = "lat_p50_ms" in cell.end_to_end()
    folder = os.path.join(cell.root, "perfbench", "layer_metrics")
    for name in sorted(f[:-3] for f in os.listdir(folder) if f.endswith(".py")):
        if name not in listed and name.endswith(".online") == online:
            yield name, loader.load_module(os.path.join(folder, f"{name}.py"))


def list_skip_pct(obs):
    """``kernel.list_skip_pct``, until a ``benchmark`` PR makes these lines
    ``perfbench/layer_metrics/kernel.list_skip_pct.py`` (PERF.md 7.1 p;
    ``ivfsq-batch``, moves ``qps``): share of the list rows of the window's
    list-major scans, at their lists' whole capacity, that lay in
    sub-blocks past the end of their list and were never gathered, in %:
    the window's total of ``engine.scan_list_rows_skipped`` over that of
    ``engine.scan_list_rows``, all ranks together. A program without the
    counters, or a window with no list-major scan, reads nothing."""
    skipped = stats.per_rank(obs, ledger.engine(obs, "engine.scan_list_rows_skipped"),
                             ledger.window_total)
    rows = stats.per_rank(obs, ledger.engine(obs, "engine.scan_list_rows"),
                          ledger.window_total)
    if skipped is None or rows is None or not sum(rows):
        return None
    return 100.0 * sum(skipped) / sum(rows)


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


def main(argv=None, **rehearsal):
    """``rehearsal``: ``perfbench.run.main``'s own keywords, for a run on the
    CPU over a cut copy of the benchmark; the command line cannot reach them."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--profile", type=float, default=0.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    run.measure = measure(args.profile)
    rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                   "--seconds", str(args.seconds), "--trace", str(args.trace)],
                  **rehearsal)
    if rc or not OBS:
        return rc or 1
    cell = loader.Cell(args.workload, rehearsal.get("root", loader.ROOT))
    OBS.update(setup={}, config=cell.config, traffic=cell.traffic)
    metrics = {}
    readers = [(m["name"], r) for m, r in cell.layer_readers()]
    for name, reader in readers + list(unlisted_readers(cell)):
        try:
            metrics[name] = reader.read(OBS)
        except (KeyError, TypeError):  # needs the trace or the set-up's facts
            continue
    metrics.setdefault("kernel.list_skip_pct", list_skip_pct(OBS))
    print("LEDGER " + json.dumps({
        "cell": args.workload, "seed": args.seed, "window_s": OBS["window_s"],
        "traced": bool(args.trace),
        "per_layer": {k: v for k, v in metrics.items() if v is not None},
        "not_read": sorted(k for k, v in metrics.items() if v is None),
        "chip_timeline": chip_timeline(OBS),
        "closures": closures(OBS), "profiles": OBS.get("profiles"),
    }, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
