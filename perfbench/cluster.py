"""Local index-server ranks for one run: started, asked, always stopped.

Each rank is ``perfbench.rank_entry`` in a process of its own, with the
environment ``launcher.rank_env`` gives local rank i of n (its own chip on a
TPU host), as ``launcher.launch_local`` does it. Copied from ``chip_smoke.py``'s
``Ranks`` (proven on the chip, PR 21), plus the command files ``rank_entry``
answers. This process never creates a jax backend: the ranks hold the chips.
"""

import json
import os
import socket
import subprocess
import sys
import time

from distributed_faiss_tpu.parallel import launcher


class RankFailure(RuntimeError):
    pass


class Ranks:
    def __init__(self, num, workdir, repo_root):
        self.num = num
        self.dir = workdir
        self.repo_root = repo_root
        self.discovery = os.path.join(workdir, "discovery.txt")
        self.procs = []

    def control_dir(self, rank):
        return os.path.join(self.dir, f"control{rank}")

    def __enter__(self):
        with socket.socket() as s:  # a free base port for this launch
            s.bind(("", 0))
            port = s.getsockname()[1]
        pythonpath = [self.repo_root] + [
            p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep) if p]
        base_env = {**os.environ, "PYTHONPATH": os.pathsep.join(pythonpath)}
        launcher.write_discovery_header(self.discovery, self.num)
        for rank in range(self.num):
            os.makedirs(self.control_dir(rank), exist_ok=True)
            cmd = [sys.executable, "-m", "perfbench.rank_entry", str(rank),
                   str(port + rank), self.discovery,
                   os.path.join(self.dir, "storage"), self.control_dir(rank)]
            with open(os.path.join(self.dir, f"rank{rank}.log"), "ab") as log:
                self.procs.append(subprocess.Popen(
                    cmd, env=launcher.rank_env(rank, self.num, base_env),
                    cwd=self.repo_root, stdout=log, stderr=subprocess.STDOUT))
        return self

    def __exit__(self, *exc):
        self.stop()

    def stop(self):
        procs, self.procs = self.procs, []
        for p in procs:
            p.kill()
        for p in procs:
            p.wait(timeout=30)

    def dead(self):
        return [r for r, p in enumerate(self.procs) if p.poll() is not None]

    def log_tails(self, nbytes=6000):
        out = []
        for rank in range(self.num):
            path = os.path.join(self.dir, f"rank{rank}.log")
            if os.path.exists(path):
                with open(path, "rb") as f:
                    f.seek(max(0, os.path.getsize(path) - nbytes))
                    out.append(f"--- rank {rank} log tail ---\n"
                               + f.read().decode("utf-8", "replace"))
        return "\n".join(out)

    # ------------------------------------------------------- command files

    def ask(self, command, answer, args=None, timeout=120.0):
        """Write ``command`` into every rank's control directory and wait
        for each rank's ``answer`` file; returns the parsed answers."""
        for rank in range(self.num):
            cdir = self.control_dir(rank)
            stale = os.path.join(cdir, answer)
            if os.path.exists(stale):
                os.unlink(stale)
            if args is not None:
                with open(os.path.join(cdir, f"{command}_args"), "w") as f:
                    json.dump(args, f)
            with open(os.path.join(cdir, command), "w"):
                pass
        out = []
        deadline = time.time() + timeout
        for rank in range(self.num):
            path = os.path.join(self.control_dir(rank), answer)
            while not os.path.exists(path):
                if time.time() > deadline or self.dead():
                    raise RankFailure(
                        f"rank {rank} did not answer {command!r} "
                        f"(dead ranks: {self.dead()})")
                time.sleep(0.01)
            with open(path) as f:
                reply = json.load(f)
            if isinstance(reply, dict) and "error" in reply:
                raise RankFailure(f"rank {rank}, {command!r}: {reply['error']}")
            out.append(reply)
        return out
