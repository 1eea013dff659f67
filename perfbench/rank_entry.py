"""One index-server rank, started by the benchmark.

    python3 -m perfbench.rank_entry <rank> <port> <discovery> <storage> <control_dir>

The serving side is the program's own and untouched: the parent applies
``launcher.rank_env`` and this process calls ``launcher.run_server``, exactly
as ``launcher.launch_local`` would. What is added is one watcher thread that
answers command files in ``control_dir`` — the only way into a process that
holds a chip, since only that process can trace it or read its memory peak:

  trace_start  ->  ``jax.profiler.start_trace``; answers ``trace_started``
  trace_stop   ->  ``stop_trace``, reduce the trace here, answer ``trace.json``
  memstats     ->  per-device ``memory_stats()``, answer ``memstats.json``

The thread runs in every mode and sleeps between polls; only ``--trace 1``
ever writes the trace commands.
"""

import glob
import json
import os
import sys
import threading
import time

POLL_S = 0.02


def _answer(path, obj):
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)  # the parent never sees a half-written answer


def _take(control_dir, name):
    path = os.path.join(control_dir, name)
    if not os.path.exists(path):
        return False
    os.unlink(path)
    return True


def _memstats():
    import jax

    out = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        out.append({"id": dev.id,
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    return out


def _reduce_trace(trace_dir, device_prefix):
    from perfbench import trace_reduce

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        return {"error": f"no .xplane.pb under {trace_dir}"}
    events = trace_reduce.read_xplane(files[-1])
    out = trace_reduce.reduce(events, device_prefix)
    out["planes"] = trace_reduce.outline(events)
    return out


def _serve(control_dir, command, answer, fn):
    """Run ``fn`` if ``command`` was asked; whatever happens, answer."""
    if not _take(control_dir, command):
        return
    try:
        reply = fn()
    except Exception as e:  # the parent must hear of it, not wait for ever
        reply = {"error": f"{type(e).__name__}: {e}"}
    _answer(os.path.join(control_dir, answer), reply)


def watch(control_dir, rank, stop):
    """Serve command files until ``stop`` is set."""
    import jax

    trace_dir = os.path.join(control_dir, f"trace{rank}")

    def trace_start():
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # keeps the host's cost small
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        return {"t": time.time()}

    def trace_stop():
        jax.profiler.stop_trace()
        with open(os.path.join(control_dir, "trace_stop_args")) as f:
            args = json.load(f)
        return _reduce_trace(trace_dir, args["device_prefix"])

    while not stop.wait(POLL_S):
        _serve(control_dir, "trace_start", "trace_started", trace_start)
        _serve(control_dir, "trace_stop", "trace.json", trace_stop)
        _serve(control_dir, "memstats", "memstats.json", _memstats)


def main(argv):
    rank, port, discovery, storage, control_dir = argv
    from distributed_faiss_tpu.parallel import launcher

    os.makedirs(control_dir, exist_ok=True)
    stop = threading.Event()
    watcher = threading.Thread(target=watch, args=(control_dir, int(rank), stop),
                               name="perfbench-watch", daemon=True)
    watcher.start()
    try:
        launcher.run_server(int(rank), int(port), discovery, storage,
                            host="localhost")
    finally:
        stop.set()
        watcher.join(timeout=5)


if __name__ == "__main__":
    main(sys.argv[1:])
