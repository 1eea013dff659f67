"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

A configuration is the directory of the ``file`` its ``configs`` entry names
(``config.json`` with ``reference.py`` beside it); a traffic mix is
``traffic/<name>.json`` and a per-layer metric ``layer_metrics/<name>.py``
under any directory listed in ``paths``. Nothing here knows a name in
advance, so a later PR adds a cell, a mix, a configuration or a metric by
adding files and entries and edits no file that is already there.
"""

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    """A python file as a module, whatever its name (metric names have dots)."""
    name = "perfbench_file_" + re.sub(r"\W", "_", os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def sibling(path, name):
    """The reader ``<name>.py`` beside the file at ``path``."""
    return load_module(os.path.join(os.path.dirname(path), f"{name}.py"))


def by_name(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json; it has "
                   f"{[e['name'] for e in entries]}")


def find_under_paths(root, bench, relative):
    for path in bench["paths"]:
        candidate = os.path.join(root, path, relative)
        if os.path.exists(candidate):
            return candidate
    raise FileNotFoundError(
        f"{relative} is under none of BENCHMARK.json's paths {bench['paths']}")


class Cell:
    """Everything one ``--workload`` needs, loaded from its files."""

    def __init__(self, workload, root=ROOT):
        self.root = root
        self.bench = read_json(os.path.join(root, "BENCHMARK.json"))
        self.entry = by_name(self.bench["workloads"], workload, "workload")
        self.name = workload
        self.chips = int(self.entry["chips"])
        config_entry = by_name(self.bench["configs"], self.entry["config"],
                               "configuration")
        config_file = os.path.join(root, config_entry["file"])
        self.config = read_json(config_file)
        self.reference = load_module(
            os.path.join(os.path.dirname(config_file), "reference.py"))
        self.traffic = read_json(find_under_paths(
            root, self.bench, os.path.join("traffic", f"{self.entry['traffic']}.json")))

    def _reported_here(self, metric):
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self):
        """Names of the end-to-end metrics this cell reports."""
        return [m["name"] for m in self.bench["end_to_end"] if self._reported_here(m)]

    def layer_readers(self):
        """[(metric entry, its reader module)] of this cell's per-layer metrics."""
        out = []
        for metric in self.bench["per_layer"]:
            if self._reported_here(metric):
                path = find_under_paths(self.root, self.bench, os.path.join(
                    "layer_metrics", f"{metric['name']}.py"))
                out.append((metric, load_module(path)))
        return out

    def unit(self, metric_name):
        for metric in self.bench["end_to_end"] + self.bench["per_layer"]:
            if metric["name"] == metric_name:
                return metric["unit"]
        raise KeyError(metric_name)
