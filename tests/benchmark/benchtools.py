"""Tiny cells for the benchmark's CPU tests.

`tiny_root` lays out a directory the harness can run from: a
BENCHMARK.json naming a small cell, its configuration and traffic
files, and the repository's metric readers. The cell keeps the real
one's shape (the same ring of senders walked the same way) at a few
hundred accounts and a few transactions a block.
"""

import copy
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")


def load(rel):
    with open(os.path.join(REPO, rel)) as f:
        return json.load(f)


def tiny_config() -> dict:
    cfg = load("benchmark/configs/cchain-transfers-50k.json")
    cfg.update(name="tiny-transfers", accounts=300, senders=16,
               state_seed=7)
    return cfg


def tiny_mix() -> dict:
    mix = load("benchmark/traffic/ring1000-full.json")
    mix.update(txs_per_block=6, sign_workers=1, sign_ahead_blocks=2)
    return mix


def tiny_root(tmp_path) -> str:
    """A run directory with one tiny cell, named tiny-transfers."""
    root = str(tmp_path)
    os.makedirs(os.path.join(root, "benchmark", "configs"))
    os.makedirs(os.path.join(root, "benchmark", "traffic"))
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(root, "benchmark", "metrics"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = copy.deepcopy(load("BENCHMARK.json"))
    bench["configs"], bench["workloads"] = [], []
    name = "tiny-transfers"
    cfg_rel = f"benchmark/configs/{name}.json"
    write(root, cfg_rel, tiny_config())
    write(root, f"benchmark/traffic/{name}.json", tiny_mix())
    bench["configs"].append({"name": name, "source": "tests",
                             "file": cfg_rel, "reduced": [],
                             "why": "CPU test"})
    bench["workloads"].append({"name": name, "config": name,
                               "traffic": name, "chips": 1,
                               "why": "CPU test"})
    names = [w["name"] for w in bench["workloads"]]
    for m in bench["per_layer"]:
        m["workloads"] = names
    write(root, "BENCHMARK.json", bench)
    return root


def write(root, rel, obj) -> None:
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)
