"""BENCHMARK.json against the contract's shapes, and the loader: a cell's
files are found by name, and a configuration, a mix, a per-layer metric and
a cell are added by new files and entries alone."""

import hashlib
import os
import re

import pytest

from perfbench import load_gen, loader
from pb_helpers import REPO, add_pretend_cell, copy_benchmark

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
    dim = c.config["index"]["dim"]
    assert isinstance(dim, int) and dim > 0
    assert "dim" not in c.config["reduced"]  # a published width, never cut
    assert callable(c.reference.exact_topk) and callable(c.reference.exact_distances)
    assert c.traffic["kind"] in load_gen.KINDS
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
    """With names no real cell would take, and at the shapes of the
    deployments that wait: d=128, k=100, the dot metric."""
    root = copy_benchmark(str(tmp_path))
    before = digest(root)
    name = add_pretend_cell(root)

    cell = loader.Cell(name, root)
    assert cell.config["index"] == {"index_builder_type": "flat", "dim": 128,
                                    "metric": "dot", "buffer_bsz": 1000}
    assert cell.config["k"] == 100
    assert cell.config["guarantees"]["self_lookup_top1"] is False
    assert cell.reference.__doc__.startswith("Plain reference for the tests' ``pretend``")
    assert cell.traffic["rows_per_request"] == 256
    assert cell.end_to_end() == ["qps", "setup_s"]
    bench = loader.read_json(os.path.join(root, "BENCHMARK.json"))
    assert bench["per_layer"][-1]["name"] == "pretend.bytes"  # appended at the end
    readers = {m["name"]: r for m, r in cell.layer_readers()}
    assert readers["pretend.bytes"].read({"wire_bytes": 7}) == 7
    assert readers["pretend.bytes"].read({}) is None
    # metrics without a ``workloads`` key would be read here too; those with
    # one are read only where they say
    assert "client.fanout_skew_ms" not in readers
    # and an old cell does not see the new metric
    old = {m["name"] for m, _ in loader.Cell("knnlm-batch", root).layer_readers()}
    assert "pretend.bytes" not in old
    after = digest(root)
    assert {f: h for f, h in after.items() if f in before} == before, \
        "adding a cell edited a file that was there"


@pytest.mark.parametrize("taken", ["pretend", "pretend-cell", "pretend1x256",
                                   "pretend.bytes"])
def test_the_pretend_cells_names_are_no_real_ones(taken):
    real = {name for _, name in all_names()}
    assert taken not in real


def test_an_unknown_name_says_what_exists():
    with pytest.raises(KeyError, match="knnlm-batch"):
        loader.Cell("no-such-cell")
