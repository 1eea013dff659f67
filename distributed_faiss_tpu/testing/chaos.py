"""Chaos harness: a TCP fault-injection proxy + a rank kill/restart driver.

``ChaosProxy`` sits between ``rpc.Client`` and an ``IndexServer`` and
injects scriptable transport faults — added latency, connection reset
(RST), blackhole (accept-then-stall), frame garbling, and cut-mid-frame —
without ever parsing or unpickling the stream: it forwards raw bytes, so
it cannot mask a protocol bug by "fixing" frames in flight. Faults are
assigned per ACCEPTED connection from a ``plan`` list (connection 0 gets
``plan[0]``, ...); connections beyond the plan get the settable default
fault (``set_fault``), which starts as pass-through.

``ServerHarness`` drives real server rank subprocesses: launch a cluster,
SIGKILL one rank, restart it on the same port (without re-appending to
the discovery file — the client already holds the server list). Together
they are the oracle for the self-healing write path (client retry +
reroute), the degraded read path, and torn-snapshot recovery.
"""

import logging
import os
import socket
import struct
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from distributed_faiss_tpu.parallel import launcher, rpc
from distributed_faiss_tpu.utils import lockdep

logger = logging.getLogger()

_CHUNK = 65536


class Fault:
    """One scripted transport fault.

    kinds:
      - ``latency``: sleep ``delay`` seconds before forwarding each chunk
        in ``direction``.
      - ``reset``: hard RST the client after ``after_bytes`` upstream bytes
        (0 = immediately on accept).
      - ``blackhole``: accept, then never read or forward a byte — the
        peer's recv hangs until its own deadline fires.
      - ``garble``: XOR the bytes in window [``after_bytes``,
        ``after_bytes + nbytes``) of ``direction`` with 0xFF (frame
        corruption that keeps the stream length intact).
      - ``cut``: forward exactly ``after_bytes`` bytes of ``direction``,
        then close both sides mid-frame.
      - ``drop_kind``: parse the ``direction`` stream at FRAME granularity
        (header fields only — lengths and the kind byte; payload bytes are
        never decoded or unpickled) and silently swallow every frame whose
        wire kind is in ``drop_kinds``, forwarding all other frames
        untouched. This is the surgical fault the anti-entropy failure
        detector is tested with: blackhole only the KIND_DIGEST exchange
        while query traffic on the same link flows normally.
    """

    LATENCY = "latency"
    RESET = "reset"
    BLACKHOLE = "blackhole"
    GARBLE = "garble"
    CUT = "cut"
    DROP_KIND = "drop_kind"
    KINDS = frozenset({LATENCY, RESET, BLACKHOLE, GARBLE, CUT, DROP_KIND})

    def __init__(self, kind: str, delay: float = 0.05, after_bytes: int = 0,
                 nbytes: int = 8, direction: str = "up", drop_kinds=None):
        if kind not in self.KINDS:
            raise ValueError(f"unknown fault kind {kind!r}")
        if direction not in ("up", "down"):
            raise ValueError("direction must be 'up' (client->server) or 'down'")
        if kind == self.DROP_KIND and not drop_kinds:
            raise ValueError("drop_kind fault needs a non-empty drop_kinds set")
        self.kind = kind
        self.delay = delay
        self.after_bytes = after_bytes
        self.nbytes = nbytes
        self.direction = direction
        self.drop_kinds = frozenset(int(k) for k in (drop_kinds or ()))

    def __repr__(self):
        return (f"Fault({self.kind!r}, delay={self.delay}, "
                f"after_bytes={self.after_bytes}, nbytes={self.nbytes}, "
                f"direction={self.direction!r}, drop_kinds={set(self.drop_kinds)})")


def _rst_close(sock: socket.socket) -> None:
    """Close with SO_LINGER(1, 0): the kernel sends RST, not FIN."""
    try:
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                        struct.pack("ii", 1, 0))
    except OSError:
        pass
    try:
        sock.close()
    except OSError:
        pass


def _quiet_close(sock: socket.socket) -> None:
    """shutdown + close. The shutdown is load-bearing: a bare close() while
    ANOTHER thread is blocked in recv() on the same fd leaves the kernel-side
    connection open (the blocked syscall pins the file description), so the
    peer never sees FIN and a "dead" connection hangs forever; shutdown()
    tears the connection down immediately and wakes the blocked recv."""
    try:
        sock.shutdown(socket.SHUT_RDWR)
    except OSError:
        pass
    try:
        sock.close()
    except OSError:
        pass


class ChaosProxy:
    """TCP interposer with scriptable fault plans (one fault per accepted
    connection; None = pass-through)."""

    def __init__(self, target_host: str, target_port: int,
                 listen_port: int = 0, plan: Optional[List[Optional[Fault]]] = None):
        self.target = (target_host, target_port)
        self._listen_port = listen_port
        self._lock = lockdep.lock("ChaosProxy._lock")
        self._plan: List[Optional[Fault]] = list(plan) if plan else []
        self._default_fault: Optional[Fault] = None
        self._accepted = 0
        self._conns: List[socket.socket] = []
        # forwarding threads (accept loop, per-connection handler/pump):
        # tracked so stop() can join them after tearing their sockets
        # down — a drill must not bleed pump threads into the next test
        # (the DFT_THREADCHECK witness polices exactly that)
        self._threads: List[threading.Thread] = []
        self._accept_thread: Optional[threading.Thread] = None
        self._stopping = threading.Event()
        self._listener: Optional[socket.socket] = None
        self.port: Optional[int] = None

    # ------------------------------------------------------------- lifecycle

    def start(self) -> "ChaosProxy":
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", self._listen_port))
        s.listen(16)
        # graftlint: atomic(_listener, port): published in start() strictly before the accept thread exists — Thread.start() is the happens-before edge, and neither is ever rebound while the proxy lives
        self._listener = s
        self.port = s.getsockname()[1]
        self._accept_thread = threading.Thread(
            target=self._accept_loop, daemon=True,
            name=f"chaos-accept:{self.port}")
        self._accept_thread.start()
        return self

    def stop(self) -> None:
        self._stopping.set()
        if self._listener is not None:
            _quiet_close(self._listener)
        with self._lock:
            conns, self._conns = self._conns, []
            threads, self._threads = self._threads, []
        for c in conns:
            _quiet_close(c)
        # closed sockets wake every pump/handler out of recv; the joins
        # are bounded so a wedged kernel socket can't hostage teardown
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=5.0)
        for t in threads:
            if t is not threading.current_thread():
                t.join(timeout=5.0)

    def __enter__(self) -> "ChaosProxy":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ----------------------------------------------------------------- plan

    def set_fault(self, fault: Optional[Fault]) -> None:
        """Default fault for connections beyond the scripted plan."""
        with self._lock:
            self._default_fault = fault

    def connections_seen(self) -> int:
        with self._lock:
            return self._accepted

    def _next_fault(self) -> Optional[Fault]:
        with self._lock:
            idx = self._accepted
            self._accepted += 1
            if idx < len(self._plan):
                return self._plan[idx]
            return self._default_fault

    # ------------------------------------------------------------ forwarding

    def _accept_loop(self) -> None:
        while not self._stopping.is_set():
            try:
                client, addr = self._listener.accept()
            except OSError:
                break
            fault = self._next_fault()
            t = threading.Thread(target=self._handle, args=(client, fault),
                                 daemon=True,
                                 name=f"chaos-conn:{self.port}")
            with self._lock:
                self._threads.append(t)
            t.start()

    def _handle(self, client: socket.socket, fault: Optional[Fault]) -> None:
        if fault is not None and fault.kind == Fault.RESET and fault.after_bytes == 0:
            _rst_close(client)
            return
        if fault is not None and fault.kind == Fault.BLACKHOLE:
            # accept-then-stall: never read a byte; the connection looks
            # established but nothing ever flows until the proxy stops
            with self._lock:
                self._conns.append(client)
            self._stopping.wait()
            _quiet_close(client)
            return
        try:
            upstream = socket.create_connection(self.target, timeout=10)
        except OSError:
            _quiet_close(client)
            return
        with self._lock:
            self._conns.append(client)
            self._conns.append(upstream)
        up_fault = fault if fault is not None and fault.direction == "up" else None
        down_fault = fault if fault is not None and fault.direction == "down" else None
        t = threading.Thread(target=self._pump,
                             args=(client, upstream, up_fault),
                             daemon=True, name=f"chaos-pump:{self.port}")
        with self._lock:
            self._threads.append(t)
        t.start()
        self._pump(upstream, client, down_fault)

    def _pump(self, src: socket.socket, dst: socket.socket,
              fault: Optional[Fault]) -> None:
        if fault is not None and fault.kind == Fault.DROP_KIND:
            self._pump_frames(src, dst, fault)
            return
        sent = 0
        try:
            while True:
                data = src.recv(_CHUNK)
                if not data:
                    break
                if fault is not None:
                    if fault.kind == Fault.LATENCY:
                        time.sleep(fault.delay)
                    elif fault.kind == Fault.GARBLE:
                        data = self._garble(data, sent, fault)
                    elif fault.kind == Fault.RESET:
                        if sent + len(data) >= fault.after_bytes:
                            dst.sendall(data[: max(0, fault.after_bytes - sent)])
                            # linger-RST src (only THIS thread recvs it, so
                            # close really fires the RST); the peer socket
                            # has the other pump blocked in recv and needs
                            # the shutdown-first teardown
                            _rst_close(src)
                            _quiet_close(dst)
                            self._forget(src, dst)
                            return
                    elif fault.kind == Fault.CUT:
                        if sent + len(data) >= fault.after_bytes:
                            dst.sendall(data[: max(0, fault.after_bytes - sent)])
                            _quiet_close(dst)
                            _quiet_close(src)
                            self._forget(src, dst)
                            return
                dst.sendall(data)
                sent += len(data)
        except OSError:
            pass
        # one direction ended: tear down both so the peer sees EOF, not a
        # half-open connection
        _quiet_close(src)
        _quiet_close(dst)
        self._forget(src, dst)

    # frame header shared with parallel/rpc.py (magic, kind u8, skel_len
    # u32, narr u32) — aliased, not mirrored, so a wire-format change
    # cannot silently desync the proxy into corrupting streams instead of
    # dropping frames. The proxy reads LENGTH fields and the kind byte
    # only; payload bytes are forwarded (or dropped) opaque, never
    # unpickled. _read_exact stays local: the pump needs owned bytes
    # (indexing, .decode()), not a view into rpc.FrameReader's buffer.
    _FRAME_HDR = rpc._HDR
    _FRAME_MAGIC = rpc.MAGIC

    @staticmethod
    def _read_exact(sock: socket.socket, n: int) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            chunk = sock.recv(min(_CHUNK, n - len(buf)))
            if not chunk:
                raise EOFError("stream ended mid-frame")
            buf += chunk
        return bytes(buf)

    def _pump_frames(self, src: socket.socket, dst: socket.socket,
                     fault: Fault) -> None:
        """Frame-granular pump for drop_kind faults: swallow whole frames
        of the targeted kinds, forward every other frame byte-exact. A
        stream that stops framing (bad magic — not this protocol, or
        already desynced) degrades to raw forwarding of what was read."""
        import numpy as _np

        try:
            while True:
                head = self._read_exact(src, self._FRAME_HDR.size)
                magic, kind, skel_len, narr = self._FRAME_HDR.unpack(head)
                if magic != self._FRAME_MAGIC:
                    # unknown dialect: stop parsing, forward verbatim
                    dst.sendall(head)
                    while True:
                        data = src.recv(_CHUNK)
                        if not data:
                            break
                        dst.sendall(data)
                    break
                parts = [head, self._read_exact(src, skel_len)]
                for _ in range(narr):
                    dl = self._read_exact(src, 1)
                    dt = self._read_exact(src, dl[0])
                    nd = self._read_exact(src, 1)
                    dims_raw = self._read_exact(src, 8 * nd[0])
                    dims = struct.unpack(f"<{nd[0]}Q", dims_raw)
                    itemsize = _np.dtype(dt.decode()).itemsize
                    nbytes = itemsize
                    for d in dims:
                        nbytes *= d
                    parts += [dl, dt, nd, dims_raw,
                              self._read_exact(src, int(nbytes))]
                if kind in fault.drop_kinds:
                    continue  # swallowed: the peer never sees this frame
                for p in parts:
                    dst.sendall(p)
        except (OSError, EOFError, ValueError, TypeError):
            # ValueError/TypeError: a desynced stream fed garbage into
            # np.dtype(dt.decode()) — same terminal condition as a torn
            # socket, and the cleanup below must still run (a dead pump
            # thread that skips it leaks both sockets and wedges the
            # peer mid-frame until its own timeout)
            pass
        _quiet_close(src)
        _quiet_close(dst)
        self._forget(src, dst)

    def _forget(self, *socks) -> None:
        """Drop finished sockets from the live list — a long-lived proxy
        (operator game-day drills) must not accumulate two dead socket
        objects per connection until stop()."""
        with self._lock:
            for s in socks:
                if s in self._conns:
                    self._conns.remove(s)

    @staticmethod
    def _garble(data: bytes, sent: int, fault: Fault) -> bytes:
        lo = max(fault.after_bytes, sent)
        hi = min(fault.after_bytes + fault.nbytes, sent + len(data))
        if lo >= hi:
            return data
        buf = bytearray(data)
        for i in range(lo - sent, hi - sent):
            buf[i] ^= 0xFF
        return bytes(buf)


class ServerHarness:
    """Process-level chaos: launch, SIGKILL, and restart real server ranks.

    Initial launch goes through ``launcher.launch_local`` (ranks register
    in the discovery file); ``restart`` re-spawns a single rank on its
    original port WITHOUT re-appending a discovery entry — live clients
    already hold the server list, and their stubs redial the same
    host:port automatically on the next call.
    """

    def __init__(self, num_servers: int, discovery_path: str, storage_dir: str,
                 base_port: int = 13700, env: Optional[dict] = None):
        self.num_servers = num_servers
        self.discovery_path = discovery_path
        self.storage_dir = storage_dir
        self.base_port = base_port
        self.env = dict(env) if env else {}
        self._lock = lockdep.lock("ServerHarness._lock")
        self.procs: Dict[int, subprocess.Popen] = {}

    def port(self, rank: int) -> int:
        return self.base_port + rank

    def start(self) -> "ServerHarness":
        procs = launcher.launch_local(
            self.num_servers, self.discovery_path, self.storage_dir,
            base_port=self.base_port, env=self.env,
        )
        with self._lock:
            self.procs = dict(enumerate(procs))
        return self

    def __enter__(self) -> "ServerHarness":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def kill(self, rank: int) -> None:
        """SIGKILL one rank (no shutdown hooks run — the crash case)."""
        with self._lock:
            proc = self.procs[rank]
        proc.kill()
        proc.wait()

    def restart(self, rank: int, load_index: bool = False,
                extra_env: Optional[dict] = None) -> None:
        """Re-spawn a killed rank on its original port. ``extra_env``
        overlays per-rank environment for THIS spawn only — e.g.
        ``DFT_SHARD_GROUP`` so a rejoining rank comes back pre-registered
        into its replica group (replication membership)."""
        cmd = [sys.executable, "-m", "distributed_faiss_tpu.parallel.server",
               "--rank", str(rank), "--port", str(self.port(rank)),
               "--storage-dir", self.storage_dir,
               "--discovery", self.discovery_path]
        if load_index:
            cmd.append("--load-index")
        # the same chips the rank held before it died (launcher.rank_env)
        env = launcher.rank_env(
            rank, self.num_servers,
            {**os.environ, **self.env, **(extra_env or {})})
        proc = subprocess.Popen(cmd, env=env)
        with self._lock:
            self.procs[rank] = proc

    def wait_port(self, rank: int, timeout: float = 30.0) -> None:
        """Block until the rank's accept loop answers (post-restart sync)."""
        deadline = time.time() + timeout
        while True:
            try:
                socket.create_connection(("localhost", self.port(rank)),
                                         timeout=1).close()
                return
            except OSError:
                if time.time() > deadline:
                    raise TimeoutError(
                        f"rank {rank} (port {self.port(rank)}) never came up")
                time.sleep(0.1)

    def stop(self) -> None:
        with self._lock:
            procs, self.procs = list(self.procs.values()), {}
        for p in procs:
            try:
                p.kill()
            except OSError:
                pass
        for p in procs:  # reap: no zombie ranks left behind
            try:
                p.wait(timeout=10)
            except (OSError, subprocess.TimeoutExpired):
                pass


class QueryStorm:
    """Live query load for fault windows: N client threads re-issue one
    search in a tight loop while the test injects faults (SIGKILL a rank,
    garble a link), then ``stop()`` hands back every (result, error)
    observed. The replication acceptance gate asserts byte-identity of
    every storm result against the healthy cluster's golden answer —
    proving a rank death under load costs neither rows nor correctness.

    ``allow_partial`` selects the degraded-read contract under test:
    False (the default) means every storm search must be served complete
    (replication failover), True tolerates the pre-replication partial
    contract. Errors are collected, never raised into the storm threads.
    """

    def __init__(self, client, index_id: str, query, topk: int,
                 threads: int = 4, allow_partial: bool = False,
                 interval: float = 0.0):
        self.client = client
        self.index_id = index_id
        self.query = query
        self.topk = topk
        self.allow_partial = allow_partial
        self.interval = interval
        self.num_threads = threads
        self._stop = threading.Event()
        self._lock = lockdep.lock("QueryStorm._lock")
        self.results: List[tuple] = []
        self.errors: List[BaseException] = []
        self._threads: List[threading.Thread] = []

    def start(self) -> "QueryStorm":
        for i in range(self.num_threads):
            t = threading.Thread(target=self._run, daemon=True,
                                 name=f"query-storm-{i}")
            self._threads.append(t)
            t.start()
        return self

    def __enter__(self) -> "QueryStorm":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                out = self.client.search(
                    self.query, self.topk, self.index_id,
                    allow_partial=self.allow_partial)
            except Exception as e:
                with self._lock:
                    self.errors.append(e)
            else:
                with self._lock:
                    self.results.append(out)
            if self.interval:
                time.sleep(self.interval)

    def stop(self) -> Tuple[List[tuple], List[BaseException]]:
        """End the storm and return (results, errors) collected so far."""
        self._stop.set()
        for t in self._threads:
            t.join(timeout=30.0)
        with self._lock:
            return list(self.results), list(self.errors)
