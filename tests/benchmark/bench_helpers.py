"""Helpers of the tests of the benchmark itself (CPU only).

``make_bench_root`` makes a temporary copy of what the benchmark consists
of (``BENCHMARK.json`` and ``benchmark/``) with tiny cells added the way a
later PR adds cells: new files and new ``BENCHMARK.json`` entries, no edit
to a file of the harness.
"""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_CONFIG = {
    "name": "tiny-vit",
    "source": "none: a CPU test preset, not a published architecture",
    "model": "vit",
    "kwargs": {"patch_size": 7, "embed_dim": 32, "depth": 2,
               "num_heads": 2, "mlp_ratio": 4, "num_classes": 10},
    "dtype": "bf16",
    "reference": "vit",
    "reduced": [],
}
TINY_JOB = {"runner": "train", "batch_per_chip": 4, "steps_per_pass": 2,
            "lr": 1e-3}


def add_cell(root, spec, *, name, config, traffic, chips):
    """Add one cell to ``spec`` with files for ``config`` and ``traffic``
    (dicts), as a later PR would."""
    bench = os.path.join(root, "benchmark")
    cfg_file = f"benchmark/configs/{config['name']}.json"
    with open(os.path.join(root, cfg_file), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "traffic", f"{traffic['name']}.json"),
              "w") as f:
        json.dump({k: v for k, v in traffic.items() if k != "name"}, f)
    if not any(c["name"] == config["name"] for c in spec["configs"]):
        spec["configs"].append(
            {"name": config["name"], "source": config["source"],
             "file": cfg_file, "reduced": [], "why": "test"})
    spec["workloads"].append(
        {"name": name, "config": config["name"], "traffic": traffic["name"],
         "chips": chips, "why": "test"})
    for metric in spec["per_layer"]:
        if "workloads" in metric and chips > 1:
            metric["workloads"].append(name)


def write_spec(root, spec):
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)


def make_bench_root(tmp_path):
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    add_cell(root, spec, name="tiny_1chip", config=TINY_CONFIG,
             traffic={"name": "tiny_scan", **TINY_JOB}, chips=1)
    add_cell(root, spec, name="tiny_dp4", config=TINY_CONFIG,
             traffic={"name": "tiny_dp4_zero1", **TINY_JOB,
                      "optimizer_sharding": "zero1"}, chips=4)
    write_spec(root, spec)
    return root, spec
