"""Shared by the perfbench tests: a tiny copy of the benchmark that the
CPU can hold, made in a temporary directory without touching the real files."""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def tiny_root(dst, rows=4000):
    """BENCHMARK.json and perfbench/ copied to ``dst``, every configuration
    cut to ``rows`` rows a rank at d=32 and every mix to a small query pool.
    Widths are cut here, in a test's own copy, and nowhere else."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(REPO, "perfbench"), os.path.join(dst, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    configs = os.path.join(dst, "perfbench", "configs")
    for name in os.listdir(configs):
        path = os.path.join(configs, name, "config.json")
        with open(path) as f:
            config = json.load(f)
        config["rows"] = rows * config["ranks"]
        config["index"].update(dim=32, centroids=16, nprobe=16, train_num=1000,
                               buffer_bsz=1000)
        if "code_size" in config["index"]:
            config["index"]["code_size"] = 8
        config["corpus"].update(latent_dim=8, latent_clusters=8, sub_clusters=4)
        config["limits"].update(sample_rows=64, self_lookup_rows=16,
                                distance_gap_rel_max=1e-2)  # d=32: distances are small
        with open(path, "w") as f:
            json.dump(config, f)
    traffic = os.path.join(dst, "perfbench", "traffic")
    for name in os.listdir(traffic):
        path = os.path.join(traffic, name)
        with open(path) as f:
            mix = json.load(f)
        mix.update(query_pool_rows=512, callers=min(mix["callers"], 4),
                   rows_per_request=min(mix["rows_per_request"], 16))
        with open(path, "w") as f:
            json.dump(mix, f)
    return dst
