"""Observability tier: distributed tracing, metrics export, dfstat CLI.

Covers the ISSUE 13 contract end to end: the trace-meta wire compat
matrix (traced mux client vs legacy untagged server, traced serial
client vs traced server, no-meta legacy frames), sampling-off
byte-identity with the pre-trace wire, SpanBuffer bound/eviction,
exemplar linkage from a p99 histogram row to a fetchable trace, the
Prometheus exporter (cumulative _bucket series over the real bounds +
the HTTP listener lifecycle), the shared LatencyStats.delta rate math,
stats-fan-out degradation with a dead rank, and a loopback-cluster
dfstat + ``--trace`` end-to-end drive whose merged timeline accounts for
the observed e2e latency across a replica failover.

Marked ``observability`` (own CI job, mirroring the scheduler tier); the
subprocess SIGKILL stats-degrade case is additionally ``slow``.
"""

import io
import json
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from distributed_faiss_tpu import (
    Index,
    IndexCfg,
    IndexClient,
    IndexServer,
    IndexState,
)
from distributed_faiss_tpu.observability import dfstat, export, spans
from distributed_faiss_tpu.parallel import rpc
from distributed_faiss_tpu.utils.config import ReplicationCfg, TracingCfg
from distributed_faiss_tpu.utils.tracing import LatencyStats, bucket_bounds

pytestmark = pytest.mark.observability


def free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def wait_listening(port, timeout=10.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        try:
            socket.create_connection(("localhost", port), timeout=1).close()
            return True
        except OSError:
            time.sleep(0.05)
    return False


def write_discovery(tmp_path, ports, name="disc.txt"):
    p = tmp_path / name
    p.write_text("\n".join(
        [str(len(ports))] + [f"localhost,{port}" for port in ports]) + "\n")
    return str(p)


def make_trained_engine(storage, n=400, d=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    cfg = IndexCfg(index_builder_type="flat", dim=d, metric="l2",
                   train_num=64)
    cfg.index_storage_dir = str(storage)
    idx = Index(cfg)
    idx.add_batch(x, [("doc", i) for i in range(n)],
                  train_async_if_triggered=False)
    idx.train()
    deadline = time.time() + 60
    while (idx.get_state() != IndexState.TRAINED
           or idx.get_idx_data_num()[0] > 0):
        assert time.time() < deadline, "train/drain timed out"
        time.sleep(0.05)
    return idx, x


def start_server(storage, engine=None, index_id="obs", tracing_cfg=None):
    port = free_port()
    srv = IndexServer(0, str(storage), tracing_cfg=tracing_cfg)
    if engine is not None:
        srv.indexes[index_id] = engine
        srv._wire_engine(engine)
    threading.Thread(target=srv.start_blocking, args=(port,),
                     name=f"obs-server:{port}", daemon=True).start()
    assert wait_listening(port)
    return srv, port


# ------------------------------------------------------------- span buffer


def test_span_buffer_bound_and_eviction():
    buf = spans.SpanBuffer(capacity=4, rank=3)
    for i in range(10):
        buf.record("t1" if i % 2 else "t2", f"stage{i}", 100.0 + i, 0.01,
                   window=i)
    st = buf.stats()
    assert st == {"capacity": 4, "size": 4, "recorded": 10, "evicted": 6}
    kept = buf.snapshot()
    assert [s["extra"]["window"] for s in kept] == [6, 7, 8, 9]
    assert all(s["rank"] == 3 for s in kept)
    # filtered read side (the get_trace_spans contract)
    assert all(s["trace_id"] == "t1" for s in buf.snapshot("t1"))
    buf.clear()
    assert buf.snapshot() == []


def test_span_buffer_capacity_from_env(monkeypatch):
    monkeypatch.setenv("DFT_TRACE_BUFFER", "7")
    assert spans.SpanBuffer().capacity == 7


def test_merge_timelines_dedupes_and_sorts():
    a = {"trace_id": "t", "name": "x", "start_s": 2.0, "dur_s": 0.1,
         "rank": 0}
    b = {"trace_id": "t", "name": "y", "start_s": 1.0, "dur_s": 0.2}
    merged = spans.merge_timelines([a, b], [dict(a)])  # exact dup dropped
    assert merged == [b, a]


def test_sampling_rate_knob(monkeypatch):
    monkeypatch.delenv("DFT_TRACE_SAMPLE", raising=False)
    assert spans.maybe_sample() is None  # default off: no RNG draw, no id
    monkeypatch.setenv("DFT_TRACE_SAMPLE", "1")
    tid = spans.maybe_sample()
    assert isinstance(tid, str) and len(tid) == 16
    monkeypatch.setenv("DFT_TRACE_SAMPLE", "0")
    assert spans.maybe_sample() is None


# -------------------------------------------------- exemplars + delta math


def test_exemplar_links_p99_bucket_to_trace():
    stats = LatencyStats()
    for _ in range(200):
        stats.record("op", 0.001)  # the body of the distribution, unsampled
    stats.record("op", 0.5, exemplar="tail-trace")
    s = stats.summary()["op"]
    assert s["p99_exemplar"] == "tail-trace"
    raw = stats.summary(raw=True)["op"]
    assert sum(raw["hist"]) == 201
    assert list(raw["exemplars"].values()) == ["tail-trace"]
    # an exemplar in the BODY must not masquerade as the tail's
    stats2 = LatencyStats()
    stats2.record("op", 0.001, exemplar="body-trace")
    for _ in range(200):
        stats2.record("op", 0.5)
    assert "p99_exemplar" not in stats2.summary()["op"]


def test_exemplar_ages_out(monkeypatch):
    """A tail exemplar older than EXEMPLAR_TTL_S stops being advertised
    — the span rings evicted its trace long ago, and a dead lead is
    worse than no lead."""
    import distributed_faiss_tpu.utils.tracing as tracing_mod

    stats = LatencyStats()
    stats.record("op", 0.5, exemplar="old-trace")
    assert stats.summary()["op"]["p99_exemplar"] == "old-trace"
    monkeypatch.setattr(tracing_mod, "EXEMPLAR_TTL_S", 0.0)
    s = stats.summary()["op"]
    assert "p99_exemplar" not in s
    assert stats.summary(raw=True)["op"]["exemplars"] == {}


def test_exemplars_absent_without_sampling():
    """Pre-trace output shape is unchanged when nothing passes an
    exemplar — the byte-identity contract's stats-surface half."""
    stats = LatencyStats()
    stats.record("op", 0.01)
    assert "p99_exemplar" not in stats.summary()["op"]
    assert stats.summary(raw=True)["op"]["exemplars"] == {}


def test_delta_shared_rate_math():
    stats = LatencyStats()
    stats.record("op", 0.1)
    prev = stats.summary(raw=True)
    stats.record("op", 0.3)
    stats.record("op", 0.5)
    cur = stats.summary(raw=True)
    d = LatencyStats.delta(prev, cur)["op"]
    assert d["count"] == 2
    assert abs(d["total_s"] - 0.8) < 1e-9
    assert abs(d["interval_mean_s"] - 0.4) < 1e-9
    assert sum(d["hist"]) == 2
    # no previous snapshot: totals ARE the interval
    assert LatencyStats.delta(None, cur)["op"]["count"] == 3
    # counter going backward (rank restarted) reports from zero, never
    # a negative rate
    fresh = LatencyStats()
    fresh.record("op", 0.1)
    d = LatencyStats.delta(cur, fresh.summary(raw=True))["op"]
    assert d["count"] == 1 and d["total_s"] > 0


# ------------------------------------------------------ wire compat matrix


class _LegacyServer:
    """A pre-trace, pre-mux peer: reads CALL frames, uses ONLY
    payload[:3] (unknown meta ignored — the legacy compat contract), and
    answers untagged, in order."""

    def __init__(self):
        self.port = free_port()
        self.metas = []
        self._lsock = socket.socket()
        self._lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._lsock.bind(("", self.port))
        self._lsock.listen(5)
        threading.Thread(target=self._loop, name="legacy-server",
                         daemon=True).start()

    def _loop(self):
        try:
            conn, _ = self._lsock.accept()
            while True:
                kind, payload = rpc.recv_frame(conn)
                if kind != rpc.KIND_CALL:
                    return
                fname = payload[0]
                self.metas.append(payload[3] if len(payload) > 3 else None)
                rpc.send_frame(conn, rpc.KIND_RESULT, f"legacy:{fname}")
        except (OSError, EOFError):
            pass


def test_traced_mux_client_vs_legacy_server():
    """A traced mux client against an untagged in-order server: the call
    completes FIFO, the unknown trace key is simply ignored, and the
    client still records its own spans."""
    srv = _LegacyServer()
    client = rpc.Client(0, "localhost", srv.port, mux=True)
    tid = spans.mint_trace_id()
    assert client.generic_fun("ping", trace_id=tid) == "legacy:ping"
    meta = srv.metas[0]
    assert meta["trace_id"] == tid and "req_id" in meta
    local = spans.local_buffer().snapshot(tid)
    assert {s["name"] for s in local} == {"client.pack", "client.send",
                                          "client.rpc"}
    client.close()


def test_traced_serial_client_vs_traced_server(tmp_path):
    """DFT_RPC_MUX=0 stub with a trace: the frame grows the meta element
    (trace only — no req_id), the real server attributes queue/device
    spans to it on the legacy sync path."""
    idx, x = make_trained_engine(tmp_path / "s")
    srv, port = start_server(tmp_path, engine=idx)
    client = rpc.Client(1, "localhost", port, mux=False)
    tid = spans.mint_trace_id()
    client.generic_fun("search", ("obs", x[:3], 4), trace_id=tid)
    want = {"server.queue", "server.device", "server.write"}
    deadline = time.time() + 5  # server.write ends after the reply is read
    while True:
        names = {s["name"] for s in srv.spans.snapshot(tid)}
        if want <= names or time.time() > deadline:
            break
        time.sleep(0.01)
    assert want <= names
    client.close()


def test_no_meta_legacy_frames_still_served(tmp_path):
    """A hand-rolled 3-tuple CALL frame (the pre-deadline, pre-trace
    wire) against the current server: served unchanged."""
    idx, x = make_trained_engine(tmp_path / "s")
    srv, port = start_server(tmp_path, engine=idx)
    sock = socket.create_connection(("localhost", port), timeout=10)
    rpc.send_frame(sock, rpc.KIND_CALL, ("get_rank", (), {}))
    kind, payload = rpc.recv_frame(sock)
    assert (kind, payload) == (rpc.KIND_RESULT, 0)
    rpc.send_frame(sock, rpc.KIND_CLOSE, None)
    sock.close()


def _capture_one_frame(lsock, got):
    conn, _ = lsock.accept()
    buf = b""
    # header + skeleton length is enough to bound the frame (no tensors
    # in a no-arg call)
    while len(buf) < rpc._HDR.size:
        buf += conn.recv(4096)
    _magic, _kind, skel_len, narr = rpc._HDR.unpack(buf[:rpc._HDR.size])
    total = rpc._HDR.size + skel_len
    while len(buf) < total:
        buf += conn.recv(4096)
    got.append(buf[:total])
    rpc.send_frame(conn, rpc.KIND_RESULT, None)
    conn.close()


def test_sampling_off_byte_identity(monkeypatch):
    """The headline cost contract: with DFT_TRACE_SAMPLE=0 the serial
    stub's CALL frame is byte-for-byte the pre-trace wire."""
    monkeypatch.setenv("DFT_TRACE_SAMPLE", "0")
    got = []
    lsock = socket.socket()
    lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    lsock.bind(("", 0))
    lsock.listen(1)
    port = lsock.getsockname()[1]
    t = threading.Thread(target=_capture_one_frame, args=(lsock, got),
                         name="frame-capture", daemon=True)
    t.start()
    client = rpc.Client(0, "localhost", port, mux=False)
    client.generic_fun("get_rank", trace_id=spans.maybe_sample())
    client.close()
    t.join(timeout=10)
    lsock.close()
    expected = b"".join(
        bytes(p) for p in rpc.pack_frame(rpc.KIND_CALL, ("get_rank", (), {})))
    assert got and got[0] == expected


# ------------------------------------------------------ prometheus export


def test_render_prometheus_histogram_and_gauges():
    stats = LatencyStats()
    stats.record("queue_wait_s", 2e-6)
    stats.record("queue_wait_s", 5e-6)
    tree = {"scheduler": {"counters": {"queued": 3, "shed_deadline": 1}},
            "ops": stats.summary(raw=True),
            "replication": {"shard_group": None, "note": "skipped"}}
    text = export.render_prometheus(tree, labels={"rank": 2})
    lines = text.splitlines()
    assert 'dft_scheduler_counters_queued{rank="2"} 3' in lines
    assert f'dft_ops_queue_wait_s_count{{rank="2"}} 2' in lines
    # cumulative over the REAL bounds: everything <= 2e-6 has count 1,
    # the +Inf bucket equals the count
    b = [ln for ln in lines if "dft_ops_queue_wait_s_bucket" in ln]
    le_2u = [ln for ln in b if f'le="{bucket_bounds()[3]:.6g}"' in ln]
    assert le_2u and le_2u[0].endswith(" 1")
    assert [ln for ln in b if 'le="+Inf"' in ln][0].endswith(" 2")
    # None / strings never render
    assert "shard_group" not in text and "note" not in text


def test_metrics_exporter_http_lifecycle(tmp_path):
    idx, x = make_trained_engine(tmp_path / "s")
    srv, port = start_server(tmp_path, engine=idx)
    exp = export.MetricsExporter(
        lambda: srv.get_perf_stats(raw=True), port=0, rank=0).start()
    idx.search_batched(x[:2], 3)
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{exp.port}/metrics", timeout=10).read().decode()
    assert 'dft_engine_obs_device_search_s_count{rank="0"}' in body
    assert "dft_tracing_capacity" in body
    with pytest.raises(urllib.error.HTTPError):
        urllib.request.urlopen(
            f"http://127.0.0.1:{exp.port}/other", timeout=10)
    exp.stop()
    assert not exp._thread.is_alive()


def test_server_metrics_listener_via_env(tmp_path):
    """DFT_METRICS_PORT wiring: base + rank, started with the serving
    socket, surfaced in get_perf_stats, stopped in stop()."""
    base = free_port()
    srv, port = start_server(
        tmp_path, tracing_cfg=TracingCfg(metrics_port=base))
    deadline = time.time() + 10
    while srv._metrics is None and time.time() < deadline:
        time.sleep(0.05)
    assert srv._metrics is not None and srv._metrics.port == base
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{base}/metrics", timeout=10).read().decode()
    assert 'dft_rpc_workers{rank="0"}' in body
    assert srv.get_perf_stats()["tracing"]["metrics_port"] == base
    srv.stop()
    assert srv._metrics is None


# ------------------------------------------- loopback cluster end-to-end


@pytest.fixture
def cluster(tmp_path, monkeypatch):
    """Two ranks serving ONE replica group (R=2) + a client, tracing
    every request. Each rank sits behind a ChaosProxy so a test can kill
    it crash-shaped (connections torn, port refusing) without the
    graceful-stop handshake."""
    from distributed_faiss_tpu.testing.chaos import ChaosProxy

    monkeypatch.setenv("DFT_TRACE_SAMPLE", "1")
    idx_a, x = make_trained_engine(tmp_path / "a", seed=1)
    idx_b, _ = make_trained_engine(tmp_path / "b", seed=1)
    srv_a, port_a = start_server(tmp_path / "a", engine=idx_a)
    srv_b, port_b = start_server(tmp_path / "b", engine=idx_b)
    proxies = (ChaosProxy("127.0.0.1", port_a).start(),
               ChaosProxy("127.0.0.1", port_b).start())
    disc = write_discovery(tmp_path, [p.port for p in proxies])
    client = IndexClient(
        disc, replication_cfg=ReplicationCfg(replication=2, write_quorum=1))
    client.cfg = idx_a.cfg
    # dead-rank drills must fail fast, not burn the full redial budget
    for stub in client.sub_indexes:
        stub.RECONNECT_TIMEOUT = 0.3
    yield {"client": client, "servers": (srv_a, srv_b),
           "proxies": proxies, "ports": tuple(p.port for p in proxies),
           "disc": disc, "x": x}
    client.close()
    for p in proxies:
        p.stop()


def test_trace_end_to_end_accounts_for_latency(cluster):
    """The acceptance gate's core: a traced search's merged timeline
    carries client, queue-wait, device, and write spans whose server-side
    stages nest inside the client's rpc span — the stage sum accounts for
    the observed e2e latency (wire + interpreter overhead is the only
    remainder)."""
    client, x = cluster["client"], cluster["x"]
    tid = spans.mint_trace_id()
    client.search(x[:4], 5, "obs", trace_id=tid)
    timeline = client.get_trace_spans(tid)
    names = [s["name"] for s in timeline]
    for required in ("client.search", "client.pack", "client.rpc",
                     "server.queue", "server.device", "server.write"):
        assert required in names, (required, names)
    by = {}
    for s in timeline:
        by.setdefault(s["name"], []).append(s)
    e2e = by["client.search"][0]["dur_s"]
    rpc_dur = max(s["dur_s"] for s in by["client.rpc"])
    stage_sum = sum(max(s["dur_s"] for s in by[n])
                    for n in ("server.queue", "server.device",
                              "server.write"))
    assert stage_sum <= rpc_dur + 1e-3
    assert rpc_dur <= e2e + 1e-3
    # the stages ACCOUNT for the e2e latency: what's left is wire +
    # interpreter overhead, not an unattributed mystery
    assert e2e - stage_sum < max(0.5, 0.5 * e2e)
    # causality: queue precedes device precedes write on the wall clock
    q, d, w = (by[n][0]["start_s"] for n in ("server.queue",
                                             "server.device",
                                             "server.write"))
    assert q <= d <= w
    # window attribution: the queue span names its merge window/occupancy
    assert by["server.queue"][0]["extra"]["occupancy_rows"] >= 4


def test_trace_survives_rank_failover(cluster):
    """SIGKILL-shaped death of the preferred replica mid-storm: the
    traced search fails over, the timeline records the client.failover
    hop, and the trace fetch itself degrades past the dead rank."""
    client, x = cluster["client"], cluster["x"]
    client.search(x[:2], 3, "obs")  # pin a preferred replica
    with client._stats_lock:
        preferred = dict(client._preferred)
    victim_pos = preferred.get(0, 0)
    # crash-shaped: the proxy tears every connection down and the port
    # starts refusing — no graceful stop handshake
    cluster["proxies"][victim_pos].stop()
    tid = spans.mint_trace_id()
    out = client.search(x[:4], 5, "obs", trace_id=tid)
    assert out[0].shape == (4, 5)
    timeline = client.get_trace_spans(tid)
    names = [s["name"] for s in timeline]
    assert "client.failover" in names
    hop = next(s for s in timeline if s["name"] == "client.failover")
    assert hop["extra"]["replica"] == victim_pos
    # the surviving rank's server spans still made it into the merge
    assert "server.device" in names


def test_exemplar_yields_fetchable_trace(cluster):
    """get_perf_stats -> p99_exemplar -> get_trace_spans: the diagnosis
    loop closes without ever reading a log line."""
    client, x = cluster["client"], cluster["x"]
    for _ in range(4):
        client.search(x[:2], 3, "obs")
    exemplar = None
    for entry in client.get_perf_stats():
        if "error" in entry:
            continue
        exemplar = (entry.get("scheduler", {}).get("queues", {})
                    .get("e2e_s", {}).get("p99_exemplar")) or exemplar
    assert exemplar is not None
    timeline = client.get_trace_spans(exemplar)
    assert any(s["name"] == "server.device" for s in timeline)


def test_perf_stats_degrades_per_dead_rank(cluster):
    """Satellite bugfix: one dead rank must not fail the whole stats
    call — its entry becomes a structured error row, survivors intact."""
    client = cluster["client"]
    cluster["proxies"][1].stop()  # rank 1 dies crash-shaped
    stats = client.get_perf_stats()
    assert len(stats) == 2
    assert "error" not in stats[0] and "scheduler" in stats[0]
    assert "error" in stats[1]
    assert stats[1]["port"] == cluster["ports"][1]


def test_dfstat_stats_and_trace_views(cluster, capsys):
    """The ops CLI end to end over a live loopback cluster: the stats
    view renders per-rank rows + rates via the shared delta math, --json
    parses, and --trace prints the merged causal timeline."""
    client, x, disc = cluster["client"], cluster["x"], cluster["disc"]
    tid = spans.mint_trace_id()
    client.search(x[:4], 5, "obs", trace_id=tid)

    out = io.StringIO()
    assert dfstat.main(["--discovery", disc, "--count", "2",
                        "--interval", "0.2"], out=out) == 0
    text = out.getvalue()
    assert "rank" in text and "srch/s" in text
    assert "DEAD" not in text

    out = io.StringIO()
    assert dfstat.main(["--discovery", disc, "--count", "1", "--json"],
                       out=out) == 0
    doc = json.loads(out.getvalue())
    assert len(doc["ranks"]) == 2
    assert all("search_p99_ms" in r for r in doc["ranks"])

    out = io.StringIO()
    assert dfstat.main(["--discovery", disc, "--trace", tid], out=out) == 0
    trace_text = out.getvalue()
    assert tid in trace_text
    for stage in ("server.queue", "server.device", "server.write"):
        assert stage in trace_text
    # unknown trace: clear message + nonzero exit
    out = io.StringIO()
    assert dfstat.main(["--discovery", disc, "--trace", "deadbeef" * 2],
                       out=out) == 1
    assert "no spans" in out.getvalue()


def test_dfstat_redials_rank_that_came_back(tmp_path):
    """A rank unreachable when dfstat starts (mid-restart) must rejoin
    the view on a later poll — not render DEAD until the CLI restarts."""
    port = free_port()
    disc = write_discovery(tmp_path, [port])
    entries = dfstat._connect(disc, connect_timeout=0.2)
    pool = dfstat._fanout_pool(entries)
    assert entries[0][2] is None
    assert "error" in dfstat.poll(entries, pool)[0]  # still down
    srv = IndexServer(0, str(tmp_path))
    threading.Thread(target=srv.start_blocking, args=(port,),
                     name=f"obs-server:{port}", daemon=True).start()
    assert wait_listening(port)
    cur = dfstat.poll(entries, pool)[0]  # the rank came back: redialed
    assert "error" not in cur and "rpc" in cur
    pool.shutdown(wait=False)
    entries[0][2].close()


# ------------------------------------------------- SIGKILL degrade (slow)


@pytest.mark.slow
def test_perf_stats_degrade_with_sigkilled_rank(tmp_path):
    """The satellite's regression gate with a REAL SIGKILL: stats fan-out
    against a subprocess cluster where one rank dies -9 keeps the
    survivors' stats and reports the corpse as a structured error row."""
    from distributed_faiss_tpu.testing.chaos import ServerHarness

    disc = str(tmp_path / "disc.txt")
    storage = str(tmp_path / "storage")
    with ServerHarness(2, disc, storage, base_port=free_port()) as harness:
        client = IndexClient(disc)
        cfg = IndexCfg(index_builder_type="flat", dim=8, metric="l2",
                       train_num=32)
        client.create_index("obs", cfg)
        harness.kill(1)
        stats = client.get_perf_stats()
        assert len(stats) == 2
        # discovery order is registration order, not rank order: find the
        # corpse by its port
        dead_port = harness.port(1)
        by_port = {stub.port: entry
                   for stub, entry in zip(client.sub_indexes, stats)}
        assert "error" in by_port[dead_port]
        assert by_port[dead_port]["port"] == dead_port
        survivor = by_port[harness.port(0)]
        assert "error" not in survivor and "scheduler" in survivor
        client.close()
