"""BENCHMARK.json against the contract's shapes, and the loader: a cell's
files are found by name, and a configuration, a mix, a per-layer metric and
a cell are added by new files and entries alone."""

import hashlib
import json
import os
import re
import shutil

import pytest

from perfbench import loader
from pb_helpers import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
FILE = re.compile(r"^[A-Za-z0-9_.\-/]+$")
BENCH = loader.read_json(os.path.join(REPO, "BENCHMARK.json"))
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def all_names():
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[key]:
            yield key, entry["name"]
    for w in BENCH["workloads"]:
        yield "config of " + w["name"], w["config"]
        yield "traffic of " + w["name"], w["traffic"]
    for c in BENCH["configs"]:
        for key in c["reduced"]:
            yield "reduced of " + c["name"], key


@pytest.mark.parametrize("where,name", sorted(set(all_names())))
def test_every_name_is_made_of_the_allowed_characters(where, name):
    assert NAME.match(name), (where, name)


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_unit_a_direction_and_a_source(metric):
    assert UNIT.match(metric["unit"]), metric
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if metric in BENCH["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer", "moves",
                               "workloads"}
        moved = loader.by_name(BENCH["end_to_end"], metric["moves"], "metric")
        reported = set(moved.get("workloads", cells))
        assert set(metric.get("workloads", cells)) <= reported
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


def test_the_file_has_exactly_the_contracts_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25 for m in BENCH["end_to_end"])
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)
    assert all(w["chips"] in (1, 4) and len(w["why"]) <= 200 for w in BENCH["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
    for word in BENCH["command"]:
        assert 1 <= len(word) <= 200 and not word.startswith("/") and ".." not in word


def test_every_file_under_paths_is_named_from_the_allowed_characters():
    tracked = []
    for path in BENCH["paths"]:
        for base, dirs, files in os.walk(os.path.join(REPO, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            tracked += [os.path.relpath(os.path.join(base, f), REPO) for f in files
                        if not f.endswith(".pyc")]
    assert tracked and all(FILE.match(f) for f in tracked), tracked


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_a_cells_files_are_found_by_name(cell):
    c = loader.Cell(cell)
    assert c.config["name"] == c.entry["config"]
    assert c.config["index"]["dim"] in (512, 768)  # published widths, never cut
    assert callable(c.reference.exact_topk) and callable(c.reference.exact_distances)
    assert c.traffic["kind"] == "closed_loop"
    assert "setup_s" in c.end_to_end() and len(c.end_to_end()) >= 2
    readers = c.layer_readers()
    assert readers and all(callable(r.read) for _, r in readers)
    bench_config = loader.by_name(BENCH["configs"], c.entry["config"], "configuration")
    assert set(bench_config["reduced"]) == set(c.config["reduced"])
    assert bench_config["source"] == c.config["source"]


def digest(root):
    out = {}
    for base, _, files in os.walk(os.path.join(root, "perfbench")):
        for f in files:
            with open(os.path.join(base, f), "rb") as fh:
                out[os.path.join(base, f)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_a_later_pr_adds_a_cell_by_new_files_and_entries_alone(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "perfbench"), os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digest(root)

    # what a later PR would add: a directory of its own with one of each
    extra = os.path.join(root, "perfbench_more")
    os.makedirs(os.path.join(extra, "configs", "flat"))
    os.makedirs(os.path.join(extra, "traffic"))
    os.makedirs(os.path.join(extra, "layer_metrics"))
    with open(os.path.join(extra, "configs", "flat", "config.json"), "w") as f:
        json.dump({"name": "flat", "ranks": 1, "rows": 10, "k": 10,
                   "index": {"index_builder_type": "flat", "dim": 128}}, f)
    with open(os.path.join(extra, "configs", "flat", "reference.py"), "w") as f:
        f.write("def exact_topk(chunks, q, k):\n    return 'flat reference'\n")
    with open(os.path.join(extra, "traffic", "batch1x256.json"), "w") as f:
        json.dump({"kind": "closed_loop", "callers": 1, "rows_per_request": 256, "stagger_s": 0,
                   "query_pool_rows": 1024}, f)
    with open(os.path.join(extra, "layer_metrics", "wire.bytes.py"), "w") as f:
        f.write("def read(obs):\n    return obs['wire_bytes']\n")
    bench = loader.read_json(os.path.join(root, "BENCHMARK.json"))
    bench["paths"].append("perfbench_more")
    bench["configs"].append({"name": "flat", "source": "x", "reduced": [], "why": "y",
                             "file": "perfbench_more/configs/flat/config.json"})
    bench["workloads"].append({"name": "flat-batch", "config": "flat",
                               "traffic": "batch1x256", "chips": 1, "why": "z"})
    loader.by_name(bench["end_to_end"], "qps", "metric")["workloads"].append("flat-batch")
    bench["per_layer"].append({"name": "wire.bytes", "unit": "bytes", "better": "lower",
                               "source": "program_counter", "layer": "wire",
                               "moves": "qps", "workloads": ["flat-batch"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = loader.Cell("flat-batch", root)
    assert cell.config["index"]["index_builder_type"] == "flat"
    assert cell.reference.exact_topk(None, None, 10) == "flat reference"
    assert cell.traffic["rows_per_request"] == 256
    assert cell.end_to_end() == ["qps", "setup_s"]
    readers = {m["name"]: r for m, r in cell.layer_readers()}
    assert "wire.bytes" in readers and readers["wire.bytes"].read({"wire_bytes": 7}) == 7
    # metrics without a ``workloads`` key would be read here too; those with
    # one are read only where they say
    assert "client.fanout_skew_ms" not in readers
    # and an old cell does not see the new metric
    old = {m["name"] for m, _ in loader.Cell("knnlm-batch", root).layer_readers()}
    assert "wire.bytes" not in old
    assert digest(root) == before, "adding a cell edited a file that was there"


def test_an_unknown_name_says_what_exists():
    with pytest.raises(KeyError, match="knnlm-batch"):
        loader.Cell("no-such-cell")
