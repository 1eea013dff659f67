"""dfstat — the live cluster ops CLI.

One command that answers "what is the cluster doing RIGHT NOW":

    python -m distributed_faiss_tpu.observability.dfstat \\
        --discovery /path/to/disc.txt [--watch] [--interval 2] [--json]

Each poll fans ``get_perf_stats`` out to every rank in the discovery
file (dead ranks degrade to an error row — the CLI exists for outages),
diffs the cumulative counters against the previous poll with the shared
``LatencyStats.delta`` helper (the same rate math the tests pin — no
ad-hoc CLI arithmetic), and renders one line per rank: search rate and
latency percentiles, scheduler queue depth/shed/busy, mux in-flight,
anti-entropy sweep health and suspects, and per-index mutation
live-fraction. Under each rank that collected a window in the interval
a ``chip`` line says where its chip's time went, from the scheduler's
timeline rows (``sched.chip_busy`` and the three ``sched.chip_idle.*``,
docs/OPERATIONS.md#stage-ledger): busy %, idle % by cause, a window's own
milliseconds on the chip — utilisation with no profiler session.
``--watch`` redraws every ``--interval`` seconds;
``--json`` emits one machine-readable JSON document per poll instead.

``--trace <id>`` switches to the distributed-trace view: every rank's
span ring is pulled over the ordinary ``get_trace_spans`` op, merged
with nothing local (dfstat records no spans), and printed as one causal
timeline — offset, duration, stage, rank, and the stage's extras
(merge-window occupancy, failover hops) — the "which stage of which
request paid the p99" answer the cumulative counters cannot give.
Trace ids come from the ``p99_exemplar`` fields in the stats view (or
any sampled client's logs).

``--profile <seconds>`` asks every rank to run a profiler session on
itself for that long (the ``profile`` op, observability/profile.py) and
prints each rank's reduction: device busy and idle seconds, the idle
seconds by the host stage that was open on the batcher thread meanwhile
and by cause (the timeline rows' names), busy seconds a launch, and device
seconds by named scope. ``--keep`` leaves each rank's ``.xplane.pb`` in its storage
directory and prints where.
"""

import argparse
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor

from distributed_faiss_tpu.observability import spans as obs_spans
from distributed_faiss_tpu.parallel import replication, rpc
from distributed_faiss_tpu.utils.tracing import CHIP_IDLE, LatencyStats


def _connect(discovery_path: str, connect_timeout: float = 3.0):
    """Mutable ``[host, port, stub-or-None]`` per discovery entry; a rank
    that is down now keeps its row with stub None — every poll retries
    it (``_stub_of``), so a rank that comes back mid ``--watch`` rejoins
    the view instead of rendering DEAD until the CLI restarts."""
    with open(discovery_path) as f:
        _num, entries = replication.parse_discovery_lines(f)
    out = []
    for i, (host, port) in enumerate(entries):
        try:
            stub = rpc.Client(i, host, port, connect_timeout=connect_timeout)
        except OSError:
            stub = None
        out.append([host, port, stub])
    return out


def _stub_of(entry, connect_timeout: float = 1.0):
    """The entry's live stub, redialing one that never connected (a rank
    mid-restart when the CLI started). Returns None while it stays down
    — the poll degrades that rank to an error row and moves on."""
    if entry[2] is None:
        try:
            # stub id is only a log label; -1 marks a CLI redial stub
            entry[2] = rpc.Client(-1, entry[0], entry[1],
                                  connect_timeout=connect_timeout)
        except OSError:
            return None
    return entry[2]


def _fanout_pool(stubs) -> ThreadPoolExecutor:
    """One executor per CLI session, reused across polls (--watch must
    not churn a thread per rank per repaint); workers spawn lazily, so
    a one-shot invocation pays only for the ranks it has."""
    return ThreadPoolExecutor(max_workers=max(len(stubs), 1),
                              thread_name_prefix="dfstat-fanout")


def poll(stubs, pool: ThreadPoolExecutor) -> list:
    """One stats sweep, all ranks CONCURRENTLY (one wedged rank costs
    its own 5 s timeout, not 5 s x ranks of repaint stall — the same
    degraded fan-out shape as IndexClient.get_perf_stats): per rank
    either the get_perf_stats dict or a structured ``{"error": ...}``
    row (rank down / mid-restart)."""

    def one(entry):
        stub = _stub_of(entry)
        if stub is None:
            return {"error": "unreachable", "host": entry[0],
                    "port": entry[1]}
        try:
            return stub.generic_fun("get_perf_stats", timeout=5.0)
        except rpc.RETRYABLE_ERRORS + (rpc.ServerException,) as e:
            return {"error": f"{type(e).__name__}: {e}",
                    "host": entry[0], "port": entry[1]}

    return list(pool.map(one, stubs))


def _rate_row(prev: dict, cur: dict, dt: float) -> dict:
    """Per-rank derived numbers for one poll interval, all through the
    shared LatencyStats.delta (satellite contract: tested library math)."""
    ops = LatencyStats.delta(prev if isinstance(prev, dict) else None, cur)
    search = ops.get("search", {})
    row = {
        "search_per_s": (search.get("count", 0) / dt) if dt > 0 else 0.0,
        "search_ms": search.get("interval_mean_s", 0.0) * 1e3,
        "search_p99_ms": cur.get("search", {}).get("p99_s", 0.0) * 1e3,
        "p99_exemplar": cur.get("search", {}).get("p99_exemplar"),
    }
    sched = cur.get("scheduler") or {}
    counters = sched.get("counters") or {}
    prev_counters = ((prev or {}).get("scheduler") or {}).get("counters") or {}

    def counter_delta(key):
        # same restart rule as LatencyStats.delta: a cumulative counter
        # that went backward means the rank restarted — report the new
        # life's total from zero, never a negative rate
        c, p = counters.get(key, 0), prev_counters.get(key, 0)
        return c if c < p else c - p

    row.update({
        "queued": counters.get("queued", 0),
        "shed": counter_delta("shed_deadline"),
        "busy": counter_delta("rejected_busy"),
    })
    row["chip"] = _chip_row((prev or {}).get("scheduler"), sched)
    row["in_flight"] = (cur.get("rpc") or {}).get("in_flight", 0)
    repl = cur.get("replication") or {}
    row["rank"] = repl.get("rank")
    row["group"] = repl.get("shard_group")
    ae = cur.get("antientropy") or {}
    row["suspects"] = len(ae.get("suspect_peers") or ())
    row["mismatched"] = ae.get("digests_mismatched", 0)
    row["lease"] = ae.get("compaction_held")
    mut = cur.get("mutation") or {}
    live = [m.get("live_fraction") for m in mut.values()
            if isinstance(m, dict) and m.get("live_fraction") is not None]
    row["live_frac"] = min(live) if live else 1.0
    return row


def _chip_row(prev_sched, sched):
    """Where the chip's time went in the interval, from the scheduler's
    timeline rows: ``busy_pct`` and ``idle_pct`` by cause (of the timeline
    the interval's windows cover: busy plus idle), a window's own
    ``busy_ms`` and its ``queue_ms`` behind the window ahead. None where
    the rank collected no window in it, or has no such rows."""
    rows = LatencyStats.delta((prev_sched or {}).get("queues"),
                              (sched or {}).get("queues") or {})
    busy = rows.get("sched.chip_busy")
    if not busy or not busy["count"]:
        return None
    idle = {row.rsplit(".", 1)[1]: rows.get(row, {}).get("total_s", 0.0)
            for row in CHIP_IDLE}
    timeline = busy["total_s"] + sum(idle.values())
    return {
        "windows": busy["count"],
        "busy_pct": 100.0 * busy["total_s"] / timeline if timeline else 0.0,
        "idle_pct": {c: 100.0 * s / timeline if timeline else 0.0
                     for c, s in idle.items()},
        "busy_ms": 1e3 * busy["interval_mean_s"],
        "queue_ms": 1e3 * rows.get("sched.chip_queue", {}).get(
            "interval_mean_s", 0.0),
    }


def _render_chip(chip: dict) -> str:
    idle = chip["idle_pct"]
    return (f"     └ chip: busy {chip['busy_pct']:.1f}%, idle "
            f"{sum(idle.values()):.1f}% (empty {idle['empty']:.1f}, "
            f"window_wait {idle['window_wait']:.1f}, host {idle['host']:.1f}); "
            f"{chip['windows']} windows, {chip['busy_ms']:.2f} ms on the chip "
            f"each, {chip['queue_ms']:.2f} ms behind the one ahead")


_HEADER = (f"{'rank':>4} {'grp':>3} {'srch/s':>8} {'ms':>7} {'p99ms':>8} "
           f"{'queued':>6} {'shed':>5} {'busy':>5} {'infl':>4} "
           f"{'susp':>4} {'mism':>4} {'lease':>5} {'live%':>6}")


def _render_row(row: dict) -> str:
    return (f"{row['rank'] if row['rank'] is not None else '?':>4} "
            f"{row['group'] if row['group'] is not None else '-':>3} "
            f"{row['search_per_s']:>8.1f} {row['search_ms']:>7.2f} "
            f"{row['search_p99_ms']:>8.2f} {row['queued']:>6} "
            f"{row['shed']:>5} {row['busy']:>5} {row['in_flight']:>4} "
            f"{row['suspects']:>4} {row['mismatched']:>4} "
            f"{'yes' if row['lease'] else ('-' if row['lease'] is None else 'no'):>5} "
            f"{row['live_frac'] * 100:>6.1f}")


def render_stats(prev: list, cur: list, dt: float, as_json: bool) -> str:
    rows = []
    lines = [] if as_json else [_HEADER]
    for i, entry in enumerate(cur):
        p = prev[i] if prev and i < len(prev) else None
        if "error" in entry:
            row = {"rank": None, "error": entry["error"],
                   "host": entry.get("host"), "port": entry.get("port")}
            rows.append(row)
            if not as_json:
                lines.append(f"   ? DEAD {entry.get('host')}:"
                             f"{entry.get('port')} — {entry['error']}")
            continue
        row = _rate_row(p if p and "error" not in p else None, entry, dt)
        rows.append(row)
        if not as_json:
            lines.append(_render_row(row))
            if row.get("chip"):
                lines.append(_render_chip(row["chip"]))
            if row.get("p99_exemplar"):
                lines.append(f"     └ p99 exemplar trace: "
                             f"{row['p99_exemplar']} "
                             f"(dfstat --trace {row['p99_exemplar']})")
    if as_json:
        return json.dumps({"interval_s": round(dt, 3), "ranks": rows})
    return "\n".join(lines)


def render_trace(spans: list, trace_id: str, as_json: bool) -> str:
    """One causal timeline: offsets from the earliest span's start."""
    if as_json:
        return json.dumps({"trace_id": trace_id, "spans": spans})
    if not spans:
        return (f"trace {trace_id}: no spans retained (evicted ring, "
                "unsampled request, or wrong id)")
    t0 = min(s["start_s"] for s in spans)
    lines = [f"trace {trace_id} — {len(spans)} spans, "
             f"{(max(s['start_s'] + s['dur_s'] for s in spans) - t0) * 1e3:.2f} ms end-to-end"]
    for s in spans:
        rank = s.get("rank")
        where = f"rank {rank}" if rank is not None else "client"
        extra = s.get("extra") or {}
        extras = " ".join(f"{k}={v}" for k, v in extra.items())
        lines.append(f"  +{(s['start_s'] - t0) * 1e3:>9.3f} ms "
                     f"{s['dur_s'] * 1e3:>9.3f} ms  {s['name']:<16} "
                     f"{where:<8} {extras}")
    return "\n".join(lines)


def fetch_trace(stubs, trace_id: str, pool: ThreadPoolExecutor) -> list:
    """Pull + merge every reachable rank's spans for ``trace_id``,
    concurrently (the poll() fan-out shape)."""

    def one(entry):
        stub = _stub_of(entry)
        if stub is None:
            return []
        try:
            return stub.generic_fun("get_trace_spans", (trace_id,),
                                    timeout=5.0)
        except rpc.RETRYABLE_ERRORS + (rpc.ServerException,):
            return []  # dead or pre-trace rank: the timeline degrades

    per_rank = list(pool.map(one, stubs))
    return obs_spans.merge_timelines(*per_rank)


def fetch_profiles(stubs, seconds: float, keep: bool,
                   pool: ThreadPoolExecutor) -> list:
    """Every reachable rank profiles itself for ``seconds``, all at once
    (one rank's window says little about a fan-out)."""

    def one(entry):
        stub = _stub_of(entry)
        if stub is None:
            return {"error": "unreachable"}
        try:
            return stub.generic_fun("profile", (seconds, keep),
                                    timeout=seconds + 120.0)
        except rpc.RETRYABLE_ERRORS + (rpc.ServerException,) as e:
            return {"error": f"{type(e).__name__}: {e}".strip()[-300:]}

    return list(pool.map(one, stubs))


def render_profiles(profiles: list, as_json: bool) -> str:
    if as_json:
        return json.dumps({"ranks": profiles}, default=str)
    lines = []
    for rank, p in enumerate(profiles):
        if "error" in p:
            lines.append(f"rank {rank}: no profile: {p['error']}")
            continue
        lines.append(
            f"rank {rank}: window {p['window_s']:.3f} s, device busy "
            f"{p['busy_s']:.3f} s, idle {p['idle_s']:.3f} s "
            f"({100 * p['idle_attributed_share']:.1f}% of it on a named "
            f"host stage); {p['ops_with_scope']} of {p['ops']} device "
            f"operations carry a scope")
        lines.append("  idle seconds by host stage (the batcher thread's):")
        lines += [f"    {s:>10.4f}  {n}" for n, s in p["idle_by_stage"]]
        if p.get("idle_by_cause"):
            lines.append("  idle seconds by cause (beside the sched.chip_idle.* rows):")
            lines += [f"    {s:>10.4f}  {n}" for n, s in p["idle_by_cause"].items()]
        if p.get("busy_per_launch_s"):
            lines.append(f"  device busy a launch: {1e3 * p['busy_per_launch_s']:.3f} ms "
                         "(beside sched.chip_busy's mean)")
        lines.append("  device seconds by scope (else HLO name):")
        lines += [f"    {s:>10.4f}  {n}" for n, s in p["device_by_scope"]]
        if p.get("xplane"):
            lines.append(f"  trace kept at {p['xplane']}")
    return "\n".join(lines)


def main(argv=None, out=None) -> int:
    out = sys.stdout if out is None else out
    parser = argparse.ArgumentParser(
        prog="dfstat", description=__doc__.splitlines()[0])
    parser.add_argument("--discovery", required=True,
                        help="cluster discovery file (host,port per rank)")
    parser.add_argument("--interval", type=float, default=2.0,
                        help="seconds between polls (rates are per interval)")
    parser.add_argument("--watch", action="store_true",
                        help="repaint continuously until interrupted")
    parser.add_argument("--count", type=int, default=1,
                        help="polls to run without --watch (default 1; the "
                             "first poll shows totals-as-rates)")
    parser.add_argument("--json", action="store_true",
                        help="machine-readable output (one JSON doc/poll)")
    parser.add_argument("--trace", default=None, metavar="TRACE_ID",
                        help="print the merged span timeline for one "
                             "sampled request instead of the stats view")
    parser.add_argument("--profile", type=float, default=None,
                        metavar="SECONDS",
                        help="have every rank profile itself for SECONDS "
                             "and print device idle by host stage")
    parser.add_argument("--keep", action="store_true",
                        help="with --profile: keep each rank's .xplane.pb")
    args = parser.parse_args(argv)

    stubs = _connect(args.discovery)
    pool = _fanout_pool(stubs)
    try:
        if args.profile is not None:
            profiles = fetch_profiles(stubs, args.profile, args.keep, pool)
            print(render_profiles(profiles, args.json), file=out)
            return 0 if all("error" not in p for p in profiles) else 1
        if args.trace is not None:
            spans = fetch_trace(stubs, args.trace, pool)
            print(render_trace(spans, args.trace, args.json), file=out)
            return 0 if spans else 1
        prev, prev_t = None, time.monotonic() - max(args.interval, 1e-9)
        n = 0
        while True:
            cur = poll(stubs, pool)
            now = time.monotonic()
            text = render_stats(prev, cur, now - prev_t, args.json)
            if args.watch and not args.json:
                out.write("\x1b[2J\x1b[H")  # clear + home
            print(text, file=out, flush=True)
            prev, prev_t = cur, now
            n += 1
            if not args.watch and n >= args.count:
                return 0
            try:
                time.sleep(args.interval)
            except KeyboardInterrupt:  # pragma: no cover - interactive
                return 0
    finally:
        pool.shutdown(wait=False)
        for _h, _p, stub in stubs:
            if stub is not None:
                stub.close()


if __name__ == "__main__":
    sys.exit(main())
