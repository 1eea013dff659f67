"""CPU rehearsal of every cell at a tiny size, through the real command's
``main`` with the look for a TPU overridden here, in the test — the command
line has no such option, and run as a command it still fails off the TPU.
Nothing timed here is a speed: the result lines say ``platform: cpu``.

Also the test the contract asks for with the timed path broken underneath:
the rest of a run is driven as it is, and ``correct`` comes out false.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from distributed_faiss_tpu.parallel.client import IndexClient
from perfbench import loader, run, search_bytes
from pb_helpers import REPO, tiny_root

CELLS = [w["name"] for w in loader.read_json(os.path.join(REPO, "BENCHMARK.json"))["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}
SEED = 2**31 + 4242  # the driver's seeds pass 32 signed bits


@pytest.fixture()
def rehearse(tmp_path, monkeypatch, capfd):
    root = tiny_root(str(tmp_path))
    peaks = json.load(open(search_bytes.PEAKS))
    peaks["cpu"] = peaks["TPU v5 lite"]  # so the roofline reader's arithmetic runs
    table = tmp_path / "peaks_with_cpu.json"
    table.write_text(json.dumps(peaks))
    monkeypatch.setattr(search_bytes, "PEAKS", str(table))
    monkeypatch.setenv("PYTHONPATH", REPO)
    monkeypatch.setenv("TMPDIR", str(tmp_path))

    def go(cell, trace, seconds=1.5):
        rc = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                       str(seconds), "--trace", str(trace)],
                      platform="cpu", device_prefix="/host:CPU", root=root)
        out, go.err = capfd.readouterr()
        lines = out.strip().splitlines()
        return rc, lines, (json.loads(lines[-1]) if rc == 0 else None)

    go.root = root  # a test may add a cell of its own to the copy
    return go


def metrics_of(cell, key, root=REPO):
    bench = loader.read_json(os.path.join(root, "BENCHMARK.json"))
    return {m["name"] for m in bench[key] if cell in m.get("workloads", [cell])}


def expected_checks(config):
    """The numbers a run compares, in the order it prints them: by the
    configuration's own k, and the self lookup only where it is promised."""
    promised = config["guarantees"].get("self_lookup_top1")
    return (["ntotal_gap"] + (["self_lookup_misses"] if promised else [])
            + ["failed_requests", f"recall_at_{config['k']}", "distance_gap_rel"])


def assert_the_checks_are_printed_three_times(config, lines, result, err):
    """As they come on standard output, as the last lines of standard error,
    and last in the result's line, each number beside its limit."""
    names = expected_checks(config)
    printed = [ln for ln in lines if ln.startswith("check ") and "limit" in ln]
    assert [ln.split(":")[0] for ln in printed] == ["check " + n for n in names]
    assert err.strip().splitlines()[-len(names):] == printed
    assert list(result)[-1] == "checks" and list(result["checks"]) == names
    for row in result["checks"].values():
        assert set(row) == {"value", "limit", "ok"}
    assert result["correct"] == all(row["ok"] for row in result["checks"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_rehearse_a_cell_end_to_end_on_the_cpu(cell, rehearse):
    rc, lines, result = rehearse(cell, 0)
    assert rc == 0
    assert set(result) == RESULT_KEYS  # the contract's keys and nothing else
    assert result["correct"] is True, lines
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == metrics_of(cell, "end_to_end")
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    ranks = loader.Cell(cell).config["ranks"]
    assert result["device"]["platform"] == "cpu"  # and says so: never a speed
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert sum(1 for ln in lines if ln.startswith("rank ") and "platform=cpu" in ln) == ranks
    assert_the_checks_are_printed_three_times(loader.Cell(cell).config, lines, result,
                                              rehearse.err)
    assert any(ln.startswith("bytes_in_use per rank after set-up") for ln in lines)


@pytest.mark.parametrize("cell", ["knnlm-online", "knnlm-4rank-batch"])
def test_rehearse_a_traced_run_on_the_cpu(cell, rehearse):
    rc, lines, result = rehearse(cell, 1)
    assert rc == 0
    assert set(result) == RESULT_KEYS | {"breakdown"}
    assert result["correct"] is True, lines
    assert set(result["metrics"]) == metrics_of(cell, "per_layer")
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert 0 < result["device"]["busy_s"] and 0 < result["device"]["window_s"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(result["breakdown"]["device_ops"]) <= 10
    assert len(result["breakdown"]["idle_gaps"]) <= 10
    online = ".online" if cell == "knnlm-online" else ""
    assert result["metrics"]["sched.rows_per_window" + online]["value"] >= 1
    assert result["metrics"]["device.idle_pct" + online]["value"] < 100


def altered_ids(search):
    """The client's search with one answer altered where it is produced:
    every row's hits come back as another row's."""
    def broken(self, q, k, index_id, **kw):
        scores, meta = search(self, q, k, index_id, **kw)
        return scores, meta[1:] + meta[:1] if len(meta) > 1 else [
            [m + 1 for m in row] for row in meta]
    return broken


def lower_precision(search):
    """The client's search with its distances as a much lower precision
    would give them: rounded to float8's 3 bits of mantissa (e4m3)."""
    def broken(self, q, k, index_id, **kw):
        scores, meta = search(self, q, k, index_id, **kw)
        as_int = np.asarray(scores, np.float32).view(np.uint32)
        keep = np.uint32(0xFFF00000)  # sign, exponent, 3 bits of mantissa
        return ((as_int + np.uint32(0x00080000)) & keep).view(np.float32), meta
    return broken


@pytest.mark.parametrize("breakage", [altered_ids, lower_precision])
def test_a_broken_timed_path_comes_out_not_correct(breakage, rehearse, monkeypatch):
    monkeypatch.setattr(IndexClient, "search", breakage(IndexClient.search))
    rc, lines, result = rehearse("knnlm-batch", 0)
    assert rc == 0 and result["failed"] == 0
    assert result["correct"] is False
    assert any("OUTSIDE" in ln for ln in lines)


def test_the_command_fails_off_the_tpu_and_prints_no_result(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
           "TMPDIR": str(tmp_path)}
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", "knnlm-batch",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "FAILED" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())
    assert not os.listdir(tmp_path), "the run left files behind"


def test_the_command_fails_where_only_the_benchmarks_files_are(tmp_path):
    import shutil
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for path in loader.read_json(os.path.join(REPO, "BENCHMARK.json"))["paths"]:
        shutil.copytree(os.path.join(REPO, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", "knnlm-batch",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())
