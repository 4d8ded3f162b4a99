"""A copy of the benchmark at fixture sizes, for the CPU tests.

``make_root`` copies ``BENCHMARK.json`` and the benchmark's directories into
a temporary root, links the program beside them, and adds, as new files only,
a 2-layer configuration, short lengths, and a traffic mix and a cell for each
cell of the real benchmark. Nothing that was there is edited; the temporary ``BENCHMARK.json``
gets the new entries.
"""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_CONFIG = {
    "source": "fixture for the CPU tests", "model_type": "gpt2", "n_layer": 2,
    "n_embd": 64, "n_head": 4, "n_positions": 64, "n_inner": None,
    "vocab_size": 300, "dtype": "bfloat16", "reduced": []}
TINY_LENGTHS = {
    "source": "fixture for the CPU tests", "pool": 8,
    "prompt": {"mean": 6, "sigma": 0.5, "min": 2, "max": 12},
    "output": {"mean": 6, "sigma": 0.5, "min": 2, "max": 12}}


def _dump(obj, *path):
    with open(os.path.join(*path), "w") as f:
        json.dump(obj, f)


def _load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


def make_root(tmp, users=3, ramp_seconds=0.5):
    """Returns ``(root, {real cell name: fixture cell name})``."""
    root = str(tmp)
    bench = _load(REPO, "BENCHMARK.json")
    for path in bench["paths"]:
        shutil.copytree(os.path.join(REPO, path), os.path.join(root, path))
    os.symlink(os.path.join(REPO, "client_tpu"), os.path.join(root, "client_tpu"))
    home = os.path.join(root, bench["paths"][0])
    _dump(TINY_CONFIG, home, "configs", "tiny.json")
    _dump(TINY_LENGTHS, home, "lengths", "tiny.json")
    bench["configs"].append({
        "name": "tiny", "source": "fixture", "reduced": [], "why": "fixture",
        "file": bench["paths"][0] + "/configs/tiny.json"})
    names = {}
    for entry in list(bench["workloads"]):
        real, mix = entry["name"], entry["traffic"] + "-tiny"
        fixture = "tiny." + real.split(".")[-1]
        names[real] = fixture
        traffic = _load(home, "traffic", entry["traffic"] + ".json")
        traffic.update(ramp_seconds=ramp_seconds, lengths="tiny")
        _dump(traffic, home, "traffic", mix + ".json")
        cell = _load(home, "cells", real + ".json")
        cell["users"] = users
        if "slots" in cell["args"]:
            cell["args"]["slots"] = users
        cell["limits"] = {"served_gap_max": 0.01}
        _dump(cell, home, "cells", fixture + ".json")
        bench["workloads"].append(dict(entry, name=fixture, config="tiny", traffic=mix))
        for metric in bench["per_layer"] + bench["end_to_end"]:
            if real in metric.get("workloads", []):
                metric["workloads"].append(fixture)
    _dump(bench, root, "BENCHMARK.json")
    return root, names
