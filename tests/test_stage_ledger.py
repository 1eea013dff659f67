"""The stage ledger (utils/tracing.stage, docs/OPERATIONS.md#stage-ledger).

On a loopback rank on the CPU, over the path the benchmark serves
(scheduler on, mux connection, binary wire): both ledgers close — the
launch loop's stages add up to the batcher thread's wall clock, a
request's client stages to ``client.search``; a sampled request's spans
form one tree; the ``profile`` op yields a trace that holds the stage
events on the clock the spans are on, and a reduction whose idle seconds by
stage add up to the idle total; ``xla.compile`` counts compiles. Then, on
small recorded inputs kept beside this file (``data_stage_ledger/``), the
program's trace reduction and each per-layer reader this ledger feeds.
Nothing timed here is a speed.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from distributed_faiss_tpu import (
    Index,
    IndexCfg,
    IndexClient,
    IndexServer,
    IndexState,
)
from distributed_faiss_tpu.observability import profile, spans
from distributed_faiss_tpu.parallel import rpc, wire
from distributed_faiss_tpu.utils import tracing
from test_observability import free_port, wait_listening, write_discovery

pytestmark = pytest.mark.observability

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data_stage_ledger")
REPO = os.path.dirname(HERE)
INDEX_ID = "led"
REQUEST_LEDGER = (("client", "client.fanout_wait"),
                  ("rpc", "client", "client.pack"),
                  ("rpc", "client", "client.send"),
                  ("rpc", "client", "client.round_trip.search"),
                  ("client", "client.merge"))


@pytest.fixture(scope="module")
def rank(tmp_path_factory):
    """One rank holding a small IVF-PQ index with exact refine (so the scan
    and the refine are two programs, as in the knnlm cells), and a client."""
    tmp = tmp_path_factory.mktemp("ledger")
    rng = np.random.default_rng(0)
    n, d = 3000, 32
    x = rng.standard_normal((n, d)).astype(np.float32)
    cfg = IndexCfg(index_builder_type="knnlm", dim=d, metric="l2",
                   train_num=2000, centroids=16, nprobe=4, code_size=8,
                   extra={"refine_k_factor": 4})
    cfg.index_storage_dir = str(tmp / "s")
    idx = Index(cfg)
    idx.add_batch(x, list(range(n)), train_async_if_triggered=False)
    idx.train()
    deadline = time.time() + 120
    while (idx.get_state() != IndexState.TRAINED
           or idx.get_idx_data_num()[0] > 0):
        assert time.time() < deadline, "train/drain timed out"
        time.sleep(0.05)
    port = free_port()
    srv = IndexServer(0, str(tmp))
    srv.indexes[INDEX_ID] = idx
    srv._wire_engine(idx)
    threading.Thread(target=srv.start_blocking, args=(port,),
                     name=f"ledger-server:{port}", daemon=True).start()
    assert wait_listening(port)
    disc = write_discovery(tmp, [port])
    client = IndexClient(disc)
    client.cfg = cfg
    for rows in (8, 8, 16, 32, 64):  # negotiate the binary wire, and compile
        client.search(x[:rows], 5, INDEX_ID)  # every bucket a window can have
    assert client.sub_indexes[0].rpc_stats()["peer_wire"]
    yield {"srv": srv, "client": client, "idx": idx, "x": x, "disc": disc}
    client.close()
    srv.stop()


def drive(rank, callers=4, requests=20, rows=8, until=None):
    """Callers in threads: ``requests`` searches each, or searches until
    the ``until`` event is set."""
    client, x = rank["client"], rank["x"]

    def caller(i):
        n = 0
        while (n < requests) if until is None else not until.is_set():
            client.search(x[i * rows:(i + 1) * rows], 5, INDEX_ID)
            n += 1

    threads = [threading.Thread(target=caller, args=(i,), name=f"caller{i}")
               for i in range(callers)]
    for t in threads:
        t.start()
    return threads


def dig(tree, path):
    for key in path:
        tree = tree.get(key, {})
    return tree


# ------------------------------------------------------------ the ledgers


def best_of(attempts, measure, within):
    """The closure checks time python on a loaded machine, where a thread
    can lose the interpreter for milliseconds between two stages: a
    measurement is repeated before it is believed to be off."""
    for _ in range(attempts):
        got, want, detail = measure()
        if abs(got - want) <= within * want:
            return
    raise AssertionError((got, want, detail))


# what a merged window books once, two in flight or not (ISSUE 41, Tentpole
# 7; ISSUE 42: the dispatch inside engine.scan, and the chip's timeline)
ONCE_A_WINDOW = ("sched.assemble", "sched.split", "batch_rows",
                 "engine.lock_wait", "engine.feed", "engine.scan",
                 "engine.dispatch", "engine.refine_fetch", "engine.join",
                 "device_search_s", "device_search_rows",
                 "sched.chip_busy", "sched.chip_queue")
CHIP_IDLE = tracing.CHIP_IDLE
# the completer's stages: they run beside the batcher's and explain no gap
COMPLETER = {"engine.scan", "engine.refine_fetch", "engine.join", "sched.split"}


def test_every_launch_loop_stage_is_booked_once_a_window_with_two_in_flight(rank):
    """The batcher launches and the completer collects, so the stages no
    longer add up to one thread's wall clock; what holds: over 50 windows
    every stage and count row of the launch loop is booked once a window,
    ``engine.launch_overlapped`` counts windows (some, never more than
    there were), and the launch's own three stages are what
    launch-to-fetch is made of — the wait behind the window ahead lies in
    ``engine.scan``, whose host share, ``engine.dispatch``, lies inside it;
    and the chip's timeline closes on the rank's own clock: busy plus idle
    over the windows is what ``chip_timeline_s`` moved by, a window's busy
    plus queue lies inside its launch-to-collect."""
    best_of(3, lambda: launch_loop_closure(
        rank, ("engine.scan_adc_cols", "engine.scan_adc_cols_skipped")), 0.03)


def launch_loop_closure(rank, once_a_scan=()):
    sched, idx = rank["srv"].scheduler, rank["idx"]
    windows = 50

    def booked():
        rows = {**sched.stats.summary(), **idx.perf_stats()}
        return {n: dict(rows.get(n, tracing.zero_row())) for n in (
            *tracing.LAUNCH_LOOP, *ONCE_A_WINDOW, *once_a_scan, *CHIP_IDLE,
            "queue_wait_s", "engine.launch_overlapped")}

    def timeline():
        return sched.perf_stats()["counters"]["chip_timeline_s"]

    def settled():
        """Nothing queued, in flight or still being split."""
        deadline = time.time() + 10
        while time.time() < deadline:
            rows = booked()
            if (sched.perf_stats()["counters"]["batches"]
                    == rows["device_search_s"]["count"] == rows["sched.split"]["count"]):
                return rows
            time.sleep(0.01)
        raise AssertionError("the windows in flight never drained")

    before, line0 = settled(), timeline()
    enough = threading.Event()
    callers = drive(rank, until=enough)
    try:
        deadline = time.time() + 60
        while (idx.perf.summary()["device_search_s"]["count"]
               < before["device_search_s"]["count"] + windows):
            assert time.time() < deadline, "the drive made too few windows"
            time.sleep(0.01)
    finally:
        enough.set()
        for t in callers:
            t.join()
    after = settled()

    def moved(name, field="count"):
        return after[name][field] - before[name][field]

    n = moved("device_search_s")
    assert n >= windows
    for name in (*ONCE_A_WINDOW, *once_a_scan):
        assert moved(name) == n, (name, moved(name), n)
    assert moved("queue_wait_s") >= n  # once a request
    assert 0 < moved("engine.launch_overlapped") <= n
    assert 0 < moved("engine.dispatch", "total_s") < moved("engine.scan", "total_s")
    # the chip's timeline: some windows queued behind the one ahead, a gap
    # is booked once a cause at most, and busy + idle is the timeline
    assert moved("sched.chip_queue", "total_s") > 0
    assert all(moved(name) <= n for name in CHIP_IDLE)
    assert (moved("sched.chip_busy", "total_s")
            + sum(moved(name, "total_s") for name in CHIP_IDLE)
            == pytest.approx(timeline() - line0, abs=1e-6))
    inner = sum(moved(name, "total_s") for name in
                ("engine.feed", "engine.scan", "engine.refine_fetch"))
    return inner, moved("device_search_s", "total_s"), {
        name: moved(name, "total_s") for name in tracing.LAUNCH_LOOP}


def test_a_windows_two_instants_are_read_at_the_dispatch_and_at_the_fetch(rank, monkeypatch):
    """No fallback on the served path: ``dispatched`` is read in the unit's
    dispatch on the batcher thread, ``ready`` after its fetch on the
    completer's, once each for a window of one block, and the window's
    span on the chip lies between them."""
    seen = []
    real = tracing.instant

    def spy(name, first=False):
        t = real(name, first)
        seen.append((name, threading.current_thread().name, t))
        return t

    monkeypatch.setattr(tracing, "instant", spy)
    sched = rank["srv"].scheduler
    before = sched.stats.summary()["sched.chip_busy"]
    rank["client"].search(rank["x"][:8], 5, INDEX_ID)
    deadline = time.time() + 10
    while sched.stats.summary()["sched.chip_busy"]["count"] == before["count"]:
        assert time.time() < deadline
        time.sleep(0.005)
    (d_name, d_thread, dispatched), (r_name, r_thread, ready) = seen
    assert (d_name, r_name) == ("dispatched", "ready")
    assert r_thread.endswith("-collect") and not d_thread.endswith("-collect")
    busy = sched.stats.summary()["sched.chip_busy"]["total_s"] - before["total_s"]
    assert busy == pytest.approx(ready - dispatched, abs=1e-9)  # the chip was free


def test_a_requests_client_stages_add_up_to_client_search(rank):
    best_of(3, lambda: request_closure(rank), 0.05)


def request_closure(rank):
    client, x = rank["client"], rank["x"]
    before = client.get_perf_stats()[0]
    for j in range(30):
        client.search(x[j:j + 32], 5, INDEX_ID)
    after = client.get_perf_stats()[0]

    def window(path):
        a, b = dig(after, path), dig(before, path)
        return (a["total_s"] - b.get("total_s", 0.0),
                a["count"] - b.get("count", 0))

    parts = {path[-1]: window(path) for path in REQUEST_LEDGER}
    whole, n = window(("client", "client.search"))
    assert n == 30 and all(c >= 30 for _, c in parts.values()), parts
    # the rank's whole share of a round trip lies inside the round trip
    rank_share, _ = window(("server.request",))
    assert rank_share <= parts["client.round_trip.search"][0]
    for name in ("server.decode", "search", "server.finish_wait",
                 "server.pack", "server.write"):
        assert window((name,))[1] >= 29, name  # the last may still be booking
    return sum(s for s, _ in parts.values()), whole, parts


def test_counters_are_booked_with_sampling_off_and_spans_are_not(rank):
    """``DFT_TRACE_SAMPLE`` is 0 here: the stages that were timed only for
    sampled requests leave their counters all the same, and no span."""
    client, x = rank["client"], rank["x"]
    recorded = spans.local_buffer().stats()["recorded"]
    before = client.get_perf_stats()[0]
    client.search(x[:8], 5, INDEX_ID)
    after = client.get_perf_stats()[0]
    # (server.decode: booked before the reply can have been read; the
    # stages after the send may be booked a moment after it)
    for path in (("client", "client.search"), ("rpc", "client", "client.pack"),
                 ("server.decode",)):
        assert dig(after, path)["count"] > dig(before, path)["count"], path
    assert spans.local_buffer().stats()["recorded"] == recorded


def test_scan_fused_stands_at_zero_beside_engine_scan_on_the_cpu(rank):
    """The knnlm index chooses its ADC kernel itself; on the CPU backend it
    keeps the XLA one-hot, and the rank says so: ``engine.scan_fused`` is
    served at zero beside a counting ``engine.scan`` (0 of n, not a missing
    row), and no index is listed as degraded."""
    client, x = rank["client"], rank["x"]
    assert rank["idx"].tpu_index.use_pallas is None
    before = client.get_perf_stats()[0]["engine"][INDEX_ID]
    client.search(x[:8], 5, INDEX_ID)
    after = client.get_perf_stats()[0]["engine"][INDEX_ID]
    assert after["engine.scan"]["count"] > before["engine.scan"]["count"]
    assert after["engine.scan_fused"]["count"] == 0
    assert rank["srv"].ping()["kernels"]["pallas_degraded"] == []
    rank["idx"].tpu_index._pallas_runtime_ok = False  # as a demotion leaves it
    try:
        assert rank["srv"].ping()["kernels"]["pallas_degraded"] == [INDEX_ID]
    finally:
        rank["idx"].tpu_index._pallas_runtime_ok = True


def test_scan_adc_cols_count_the_capacity_and_nothing_skipped_on_the_cpu(rank):
    """An IVF-PQ rank books the candidate columns of its pairs' capacity
    once an ``engine.scan`` (``engine.scan_adc_cols``) and, beside it, those
    the ADC scan left uncomputed: the XLA one-hot, which the CPU backend
    keeps, computes them all, so the rank reads 0 skipped of what it
    scanned, not a missing row."""
    client, x = rank["client"], rank["x"]
    index = rank["idx"].tpu_index
    before = client.get_perf_stats()[0]["engine"][INDEX_ID]
    client.search(x[:8], 5, INDEX_ID)
    after = client.get_perf_stats()[0]["engine"][INDEX_ID]
    scans = after["engine.scan"]["count"] - before["engine.scan"]["count"]
    cols, skipped = after["engine.scan_adc_cols"], after["engine.scan_adc_cols_skipped"]
    assert scans >= 1
    assert cols["count"] - before["engine.scan_adc_cols"]["count"] == scans
    assert skipped["count"] - before["engine.scan_adc_cols_skipped"]["count"] == scans
    a_scan = 8 * min(index.nprobe, index.nlist) * index.lists.cap
    assert cols["total_s"] - before["engine.scan_adc_cols"]["total_s"] == scans * a_scan
    assert skipped["total_s"] == 0


def test_every_span_of_a_sampled_request_hangs_under_client_search(rank):
    client, x = rank["client"], rank["x"]
    tid = spans.mint_trace_id()
    client.search(x[:8], 5, INDEX_ID, trace_id=tid)
    deadline = time.time() + 10  # server.request is booked after the reply
    while time.time() < deadline:
        timeline = client.get_trace_spans(tid)
        if any(s["name"] == "server.request" for s in timeline):
            break
        time.sleep(0.02)
    ids = [s["span_id"] for s in timeline]
    assert all(ids) and len(set(ids)) == len(ids)
    roots = [s["name"] for s in timeline if s["parent"] is None]
    assert roots == ["client.search"]
    assert all(s["parent"] in ids for s in timeline if s["parent"] is not None)
    by_name = {s["name"]: s for s in timeline}
    assert set(tracing.LAUNCH_LOOP) - {"sched.idle", "sched.window_wait",
                                       "sched.assemble", "sched.split"} <= set(by_name)
    # the rank's spans crossed the wire's parent: they sit under the stub's
    # round trip, the engine's under the scheduler's launch
    by_id = {s["span_id"]: s for s in timeline}
    assert by_id[by_name["server.request"]["parent"]]["name"] == "client.rpc"
    assert by_id[by_name["server.queue"]["parent"]]["name"] == "server.request"
    assert by_id[by_name["engine.scan"]["parent"]]["name"] == "engine.launch"
    assert by_id[by_name["engine.dispatch"]["parent"]]["name"] == "engine.scan"
    # the window's place on the chip's timeline rides its server.device span
    device = by_name["server.device"]["extra"]
    assert device["chip_busy_s"] > 0 and device["chip_queue_s"] >= 0
    assert device["idle_before_s"] >= 0
    assert (device["chip_busy_s"] + device["chip_queue_s"]
            <= by_name["server.device"]["dur_s"])
    assert by_id[by_name["engine.launch"]["parent"]]["name"] == "server.device"
    assert all(s.get("rank") == 0 for s in timeline
               if s["name"].startswith(("server.", "engine.")))


REQUEST_STAGES = {"server.decode", "server.finish_wait", "server.pack",
                  "server.write", "server.request"}


def test_only_search_books_request_stages(rank):
    """The request's ledger is the served search path's: any other op keeps
    its per-op row and ``client.round_trip.<op>`` and books no stage row
    (six rows an op a rank would reach the exporter, with no reader)."""
    client = rank["client"]
    stub = client.sub_indexes[0]
    before = client.get_perf_stats()[0]
    while True:  # an earlier search's server.request is booked after its reply
        time.sleep(0.05)
        again = client.get_perf_stats()[0]
        if again["server.request"]["count"] == before["server.request"]["count"]:
            break
        before = again
    for _ in range(3):
        stub.generic_fun("get_ntotal", (INDEX_ID,))
    after = client.get_perf_stats()[0]
    assert after["get_ntotal"]["count"] - dig(before, ("get_ntotal",)).get(
        "count", 0) == 3
    trips = after["rpc"]["client"]["client.round_trip.get_ntotal"]["count"]
    assert trips >= 3
    staged = {k for k in after if k.startswith("server.")}
    assert staged == REQUEST_STAGES, staged
    assert {k for k in after["rpc"]["client"] if k.startswith("client.")
            and not k.startswith("client.round_trip.")} == {"client.pack",
                                                            "client.send"}
    # and the search rows did not move: they hold searches only
    for path in (("server.request",), ("server.pack",),
                 ("rpc", "client", "client.pack"),
                 ("rpc", "client", "client.send")):
        assert dig(after, path)["count"] == dig(before, path)["count"], path
    # (server.device is a span and a profiler event, not a counter)
    assert "server.device" not in after["scheduler"]["queues"]


def test_round_trip_s_is_what_it_was(rank):
    """``round_trip_s``: all ops, the stub-lock wait and the write included
    (``client.send`` + ``client.round_trip.<op>``), a sampled request's id
    as its exemplar."""
    client, x = rank["client"], rank["x"]
    stub = client.sub_indexes[0]

    def rows():
        return stub.stats.summary(raw=True)

    before = rows()
    tid = spans.mint_trace_id()
    client.search(x[:8], 5, INDEX_ID, trace_id=tid)
    after = rows()

    def moved(name):
        return after[name]["total_s"] - before[name]["total_s"]

    assert after["round_trip_s"]["count"] - before["round_trip_s"]["count"] == 1
    assert moved("round_trip_s") == pytest.approx(
        moved("client.send") + moved("client.round_trip.search"), rel=1e-9)
    assert tid in after["round_trip_s"]["exemplars"].values()


def test_a_window_that_dies_leaves_no_caller_waiting():
    """A BaseException out of the engine call kills the window, not its
    callers nor the batcher: the loop finishes every request of the batch
    (aborted), and the next window is served."""
    from distributed_faiss_tpu.serving.scheduler import (
        SchedulerCfg, SearchScheduler)

    class Died(BaseException):
        pass

    def search_fn(index_id, q, k, return_embeddings):
        if index_id == "dies":
            raise Died("out of the engine")
        return q[:, :1], np.zeros((q.shape[0], 1), np.int64)

    sched = SearchScheduler(search_fn, SchedulerCfg(max_wait_ms=1.0),
                            name="dying-batcher")
    done = threading.Event()
    got = {}

    def callback(result, error):
        got["error"] = error
        done.set()

    q = np.zeros((2, 4), np.float32)
    try:
        sched.submit_async("dies", q, 3, False, callback=callback)
        assert done.wait(10), "the caller was left waiting"
        assert isinstance(got["error"], RuntimeError)
        assert sched.submit("lives", q, 3)[0].shape == (2, 1)
    finally:
        sched.stop()


def test_a_sampled_search_stays_on_the_binary_wire():
    """The CALL skeleton carries the parent beside the trace id: a sampled
    search is not pushed onto the pickle frame for want of a flag."""
    q = np.zeros((2, 4), np.float32)
    meta = {"req_id": 7, "wire": 1, "trace_id": "ab" * 8, "parent": "cd" * 4}
    parts = rpc.pack_binary_call("search", ("i", q, 3), {}, meta)
    assert parts is not None
    skel, planes = wire.encode_call("search", ("i", q, 3), {}, meta)
    fname, args, kwargs, back = wire.decode_call(skel, planes)
    assert back == meta and fname == "search"
    # and with sampling off the skeleton is what it was: no flag, no bytes
    plain = {"req_id": 7, "wire": 1}
    assert wire.decode_call(*wire.encode_call("search", ("i", q, 3), {},
                                              plain))[3] == plain


# -------------------------------------------------- the profile op, compiles


def test_the_profile_op_puts_the_idle_seconds_on_host_stages(rank):
    client, x = rank["client"], rank["x"]
    stub = client.sub_indexes[0]
    stop = threading.Event()
    threads = drive(rank, until=stop)
    tid = spans.mint_trace_id()
    second = {}

    def ask_again():
        time.sleep(0.3)
        try:
            stub.generic_fun("profile", (0.1,))
        except rpc.ServerException as e:
            second["error"] = str(e)
        while not stop.is_set():  # sampled searches all through the session
            client.search(x[:8], 5, INDEX_ID, trace_id=tid)

    again = threading.Thread(target=ask_again, name="second-session")
    again.start()
    reply = stub.generic_fun("profile", (1.0, True))
    stop.set()
    again.join()
    for t in threads:
        t.join()
    # one session a process; the refusal is a plain application error
    assert "already open" in second["error"]
    assert os.path.exists(reply["xplane"])
    assert reply["stage_events"] > 0 and reply["ops"] > 0
    assert (reply["busy_s"] + reply["idle_s"] + reply["sequencing_s"]
            == pytest.approx(reply["window_s"], rel=1e-6))
    assert sum(s for _, s in reply["idle_by_stage"]) == pytest.approx(
        reply["idle_s"], rel=1e-6)
    stages = {n for n, _ in reply["idle_by_stage"]}
    assert stages <= (set(tracing.LAUNCH_LOOP) | {
        "engine.dispatch", "engine.launch", "server.device", "unattributed"})
    # only the batcher's work precedes a dispatch: no stage of the
    # completer takes a gap, and the batcher's leg of engine.scan is the
    # dispatch
    assert not stages & COMPLETER
    # the same seconds under the names of the scheduler's counters
    assert set(reply["idle_by_cause"]) == set(CHIP_IDLE)
    assert sum(reply["idle_by_cause"].values()) == pytest.approx(
        reply["idle_s"], rel=1e-6)
    by_stage = dict(map(tuple, reply["idle_by_stage"]))
    assert reply["idle_by_cause"]["sched.chip_idle.empty"] == pytest.approx(
        by_stage.get("sched.idle", 0.0))
    assert 0 < reply["busy_per_launch_s"] <= reply["busy_s"]
    # (how much of it: a chip's question — here the rank shares one GIL
    # with its callers, and a batcher thread waiting for it is in no stage)
    assert 0.0 < reply["idle_attributed_share"] <= 1.0
    # the .xplane.pb holds the stages as events of the rank's host plane
    rows, facts = profile.read_xplane(reply["xplane"])
    staged = {name for plane, _, name, *_ in rows if plane == "/host:CPU"}
    # (a saturated queue never idles and never waits for followers)
    assert set(tracing.LAUNCH_LOOP) - {"sched.idle",
                                       "sched.window_wait"} <= staged
    assert {"server.device", "engine.launch", "server.pack"} <= staged
    # one clock: a sampled search's engine.scan span, placed on the
    # session's clock by its wall-clock start, is that stage's event
    events = sorted(start for _, _, name, start, *_ in rows
                    if name == "engine.scan")
    placed = [s["start_s"] * 1e9 - facts["profile_start_time"]
              for s in rank["srv"].spans.snapshot(tid)
              if s["name"] == "engine.scan"]
    inside = [t for t in placed if events[0] + 5e6 < t < events[-1] - 5e6]
    assert inside, "no sampled search ran inside the session"
    for t in inside:
        nearest = min(abs(start - t) for start in events)
        assert nearest < 2e6, f"{nearest} ns apart"


def test_dfstat_profile_prints_every_ranks_reduction(rank):
    import io

    from distributed_faiss_tpu.observability import dfstat

    stop = threading.Event()
    threads = drive(rank, until=stop)
    out = io.StringIO()
    rc = dfstat.main(["--discovery", rank["disc"], "--profile", "1.0"], out=out)
    stop.set()
    for t in threads:
        t.join()
    text = out.getvalue()
    assert rc == 0, text
    assert "rank 0: window" in text and "idle seconds by host stage" in text
    assert "idle seconds by cause" in text and "sched.chip_idle.host" in text
    assert "device busy a launch" in text


def test_dfstat_prints_a_chip_line_from_the_timeline_rows(rank):
    """Utilisation from two ``get_perf_stats`` snapshots, no profiler: busy
    and idle by cause are shares of the timeline the interval's windows
    cover, so they add up to the whole of it."""
    import io

    from distributed_faiss_tpu.observability import dfstat

    stop = threading.Event()
    threads = drive(rank, until=stop)
    try:
        out = io.StringIO()
        assert dfstat.main(["--discovery", rank["disc"], "--count", "2",
                            "--interval", "0.5", "--json"], out=out) == 0
        text = io.StringIO()
        assert dfstat.main(["--discovery", rank["disc"], "--count", "2",
                            "--interval", "0.3"], out=text) == 0
    finally:
        stop.set()
        for t in threads:
            t.join()
    chip = json.loads(out.getvalue().splitlines()[-1])["ranks"][0]["chip"]
    assert chip["windows"] > 0 and chip["busy_ms"] > 0
    assert set(chip["idle_pct"]) == {"empty", "window_wait", "host"}
    assert chip["busy_pct"] + sum(chip["idle_pct"].values()) == pytest.approx(100.0)
    assert "chip: busy" in text.getvalue() and "window_wait" in text.getvalue()
    # an interval with no window says nothing of the chip
    idle = io.StringIO()
    assert dfstat.main(["--discovery", rank["disc"], "--count", "2",
                        "--interval", "0.2", "--json"], out=idle) == 0
    assert json.loads(idle.getvalue().splitlines()[-1])["ranks"][0]["chip"] is None


def test_the_builders_tool_reads_the_timeline_and_the_readers_no_entry_names(rank):
    """``benchmarks/stage_ledger.py``: a rank's timeline between two
    snapshots closes on ``chip_timeline_s``; a program without it gives
    None; and the reader files that wait for their ``BENCHMARK.json``
    entries are found by the cell's kind."""
    import sys

    sys.path.insert(0, REPO)
    from perfbench import loader

    tool = loader.load_module(os.path.join(REPO, "benchmarks", "stage_ledger.py"))
    client, x = rank["client"], rank["x"]
    before = client.get_perf_stats()[0]
    for j in range(5):
        client.search(x[j:j + 8], 5, INDEX_ID)
    time.sleep(0.2)  # the last window's split
    after = client.get_perf_stats()[0]
    rows = tool.chip_rows(before, after)
    assert rows["sched.chip_busy"][0] == rows["sched.chip_queue"][0] >= 1
    assert rows["chip_timeline_s"] > 0
    assert abs(rows["busy_plus_idle_less_timeline_s"]) < 1e-6
    bare = {"scheduler": {"counters": {}, "queues": {}}}
    assert tool.chip_rows(bare, bare) is None
    ten = {base + suffix for base in (
        "sched.chip_busy_ms", "engine.dispatch_ms", "sched.chip_idle_pct",
        "sched.chip_idle_empty_pct", "sched.chip_idle_host_pct")
        for suffix in ("", ".online")}
    for cell, online in (("knnlm-batch", False), ("knnlm-online", True)):
        cell = loader.Cell(cell)
        found = {name for name, _ in tool.unlisted_readers(cell)}
        listed = {m["name"] for m, _ in cell.layer_readers()}
        assert {n for n in ten if n.endswith(".online") == online} <= found | listed
        assert all(n.endswith(".online") == online for n in found)


def test_xla_compile_counts_a_fresh_shape_once_and_a_repeat_never(rank):
    import jax

    fn = jax.jit(lambda a: a * 3.0 + 1.0)
    fresh = jax.device_put(np.ones((3, 7, 11), np.float32))
    n0 = tracing.compile_row()["count"]
    fn(fresh).block_until_ready()
    n1 = tracing.compile_row()["count"]
    fn(fresh).block_until_ready()
    assert (n1 - n0, tracing.compile_row()["count"] - n1) == (1, 0)
    # and the rank serves the row
    served = rank["client"].get_perf_stats()[0]["xla.compile"]
    assert served["count"] >= n1 and served["total_s"] > 0


# ---------------------------------------------------- on recorded inputs


def recorded(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def test_the_trace_reduction_on_a_recorded_trace():
    """A hand-sized trace: one device plane, the batcher's host line. Each
    gap is split among the stages open during it, by overlap, then the
    subtotals around them; the default window is the stages' extent."""
    trace = recorded("trace_small.json")
    out = profile.reduce_rows([tuple(r) for r in trace["rows"]],
                              tuple(trace["window_ns"]))
    want = trace["expected"]
    assert out["busy_s"] == pytest.approx(want["busy_s"])
    assert out["idle_s"] == pytest.approx(want["idle_s"])
    assert dict(map(tuple, out["idle_by_stage"])) == pytest.approx(
        want["idle_by_stage"])
    assert dict(map(tuple, out["device_by_scope"])) == pytest.approx(
        want["device_by_scope"])
    assert out["idle_attributed_share"] == pytest.approx(
        want["idle_attributed_share"])
    assert (out["ops"], out["ops_with_scope"]) == (5, 4)
    trimmed = profile.reduce_rows([tuple(r) for r in trace["rows"]])
    assert trimmed["window_s"] == pytest.approx(38000e-9)  # [0, 38000)
    assert trimmed["idle_attributed_share"] == 1.0


def test_the_trace_reduction_is_thread_aware_on_a_recorded_trace():
    """Two windows in flight: while the chip idles between them the
    completer fetches, joins and splits the one behind and the batcher
    waits, assembles, feeds and dispatches the one ahead. Only the
    batcher's line (the one that holds ``sched.assemble``) explains the
    gap; read as one thread, as before PR 42, the completer is blamed."""
    trace = recorded("trace_two_threads.json")
    rows = [tuple(r) for r in trace["rows"]]
    out = profile.reduce_rows(rows, tuple(trace["window_ns"]))
    want = trace["expected"]
    assert out["busy_s"] == pytest.approx(want["busy_s"])
    assert out["idle_s"] == pytest.approx(want["idle_s"])
    assert dict(map(tuple, out["idle_by_stage"])) == pytest.approx(
        want["idle_by_stage"])
    assert out["idle_by_cause"] == pytest.approx(want["idle_by_cause"])
    assert sum(out["idle_by_cause"].values()) == pytest.approx(out["idle_s"])
    assert out["busy_per_launch_s"] == pytest.approx(want["busy_per_launch_s"])
    assert not {n for n, _ in out["idle_by_stage"]} & COMPLETER
    one_thread = [(plane, "python" if plane == "/host:CPU" else line, *rest)
                  for plane, line, *rest in rows]
    blamed = dict(map(tuple, profile.reduce_rows(
        one_thread, tuple(trace["window_ns"]))["idle_by_stage"]))
    assert blamed["engine.refine_fetch"] > 0
    # and a session with no batcher in it (no scheduler: the in-process
    # batcher launches on its caller's thread) is judged on every line
    no_batcher = [r for r in rows if r[2] != "sched.assemble"]
    alone = profile.reduce_rows(no_batcher, tuple(trace["window_ns"]))
    assert "engine.refine_fetch" in dict(map(tuple, alone["idle_by_stage"]))
    assert alone["busy_per_launch_s"] is None


def new_readers():
    import sys

    sys.path.insert(0, REPO)
    from perfbench import loader

    want = recorded("stats_pair.json")["expected"]
    return [(name, loader.load_module(os.path.join(
        REPO, "perfbench", "layer_metrics", f"{name}.py")))
            for name in sorted(want)]


@pytest.mark.parametrize("name,reader", new_readers(), ids=lambda v: v if isinstance(v, str) else "")
def test_a_reader_on_a_recorded_stats_pair(name, reader):
    pair = recorded("stats_pair.json")
    obs = {k: pair[k] for k in ("stats_before", "stats_after", "window_s",
                                "index_id")}
    assert reader.read(obs) == pytest.approx(pair["expected"][name])
    # a program without the ledger (the parent commit) has none of the rows:
    # the reader finds nothing to read and says so, it does not raise
    bare = {"search": {"count": 1, "total_s": 1.0},
            "scheduler": {"queues": {}}, "engine": {"bench": {}},
            "rpc": {"client": {}}}
    assert reader.read({**obs, "stats_before": [bare], "stats_after": [bare]}) is None
    assert reader.read({"window_s": 1.0, "index_id": "bench"}) is None


def test_every_new_reader_is_in_the_benchmark_and_has_a_recorded_case():
    bench = recorded("../../BENCHMARK.json")
    names = {m["name"] for m in bench["per_layer"]}
    want = set(recorded("stats_pair.json")["expected"])
    assert want <= names and len(want) == 21
