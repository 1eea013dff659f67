"""Shared by the perfbench tests: a tiny copy of the benchmark that the
CPU can hold, made in a temporary directory without touching the real files,
and the pretend cell a later PR would add to it."""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PRETEND = os.path.join(REPO, "tests", "perfbench", "data", "pretend")

# what a test's copy cuts an index to, for the keys the index has: a
# configuration that leaves ``train_num`` out keeps its own default (0: the
# rank buffers every row until ``sync_train``), one without lists gets none
INDEX_CUTS = {"dim": 32, "centroids": 16, "nprobe": 16, "train_num": 1000,
              "buffer_bsz": 1000, "code_size": 8}


def copy_benchmark(dst):
    """BENCHMARK.json and perfbench/ as they are, under ``dst``."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(REPO, "perfbench"), os.path.join(dst, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return dst


def rewrite_json(path, change):
    with open(path) as f:
        data = json.load(f)
    change(data)
    with open(path, "w") as f:
        json.dump(data, f)


def tiny_root(dst, rows=4000):
    """BENCHMARK.json and perfbench/ copied to ``dst``, every configuration
    cut to ``rows`` rows a rank at d=32 and every mix to a small query pool.
    Only keys a configuration has are cut. Widths are cut here, in a test's
    own copy, and nowhere else."""
    copy_benchmark(dst)

    def cut_config(config):
        config["rows"] = rows * config["ranks"]
        index = config["index"]
        index.update({k: v for k, v in INDEX_CUTS.items() if k in index})
        config["corpus"].update(latent_dim=8, latent_clusters=8, sub_clusters=4)
        config["limits"].update(sample_rows=64, self_lookup_rows=16,
                                distance_gap_rel_max=1e-2)  # d=32: distances are small

    def cut_mix(mix):
        mix.update(query_pool_rows=512, callers=min(mix["callers"], 4),
                   rows_per_request=min(mix["rows_per_request"], 16))

    configs = os.path.join(dst, "perfbench", "configs")
    for name in os.listdir(configs):
        rewrite_json(os.path.join(configs, name, "config.json"), cut_config)
    traffic = os.path.join(dst, "perfbench", "traffic")
    for name in os.listdir(traffic):
        rewrite_json(os.path.join(traffic, name), cut_mix)
    return dst


def add_pretend_cell(root):
    """What a later PR would add to the benchmark under ``root``: a
    directory of its own with a configuration (d=128, k=100, dot: the shapes
    of the deployments that wait), its reference, a mix and a per-layer
    metric, and the entries that name them, the metric's appended at the end
    of ``per_layer``. No file that was there is opened for writing but
    BENCHMARK.json."""
    shutil.copytree(PRETEND, os.path.join(root, "perfbench_more"),
                    ignore=shutil.ignore_patterns("__pycache__"))

    def add_entries(bench):
        bench["paths"].append("perfbench_more")
        bench["configs"].append({
            "name": "pretend", "source": "x", "reduced": [], "why": "y",
            "file": "perfbench_more/configs/pretend/config.json"})
        bench["workloads"].append({"name": "pretend-cell", "config": "pretend",
                                   "traffic": "pretend1x256", "chips": 1, "why": "z"})
        for m in bench["end_to_end"]:
            if m["name"] == "qps":
                m["workloads"].append("pretend-cell")
        bench["per_layer"].append({
            "name": "pretend.bytes", "unit": "bytes", "better": "lower",
            "source": "program_counter", "layer": "wire", "moves": "qps",
            "workloads": ["pretend-cell"]})

    rewrite_json(os.path.join(root, "BENCHMARK.json"), add_entries)
    return "pretend-cell"
