"""A copy of the benchmark at fixture sizes, for the CPU tests, and the checks
a configuration and a cell are held to, as functions.

``copy_root`` copies ``BENCHMARK.json`` and the benchmark's directories into
a temporary root and links the program beside them. ``add_fixtures`` then
adds, as new files only, short lengths and, for each configuration of that
root's ``BENCHMARK.json``, one configuration at fixture size
(``tiny-<name>``): the family's own, from its arithmetic module's
``fixture(config)`` (``benchmark/family.py``), or the GPT-2 family's
``TINY_CONFIG`` where it has none. Each cell gets a twin (``tiny.<cell>``, the
whole cell name, so that no two cells share one) with its own builder and
arguments, the fixture of its own configuration, the family's limits (0.01
where it states none) and a traffic mix of its own API over the short
lengths. Nothing that was there is edited; the temporary ``BENCHMARK.json``
gets the new entries. ``make_root`` is the two together.

A later PR's family is taken through the same: its files and entries are
added to the copy before ``add_fixtures`` runs, and ``check_configuration``
and ``check_cell_resolves`` are called on what it brought.
"""

import json
import os
import re
import shutil

from benchmark import family, run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")

TINY_CONFIG = {
    "source": "fixture for the CPU tests", "model_type": "gpt2", "n_layer": 2,
    "n_embd": 64, "n_head": 4, "n_positions": 64, "n_inner": None,
    "vocab_size": 300, "dtype": "bfloat16", "reduced": []}
TINY_LIMITS = {"served_gap_max": 0.01}
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


def fixture_of(config):
    """``(configuration, limits)`` at fixture size: the family's own, or the
    GPT-2 family's."""
    own = getattr(family.arithmetic(config), "fixture", None)
    return own(config) if own is not None else (TINY_CONFIG, TINY_LIMITS)


def copy_root(tmp):
    root = str(tmp)
    for path in _load(REPO, "BENCHMARK.json")["paths"]:
        shutil.copytree(os.path.join(REPO, path), os.path.join(root, path))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    os.symlink(os.path.join(REPO, "client_tpu"), os.path.join(root, "client_tpu"))
    return root


def add_fixtures(root, users=3, ramp_seconds=0.5):
    """Returns ``{real cell name: fixture cell name}``."""
    bench = _load(root, "BENCHMARK.json")
    home = os.path.join(root, bench["paths"][0])
    _dump(TINY_LENGTHS, home, "lengths", "tiny.json")
    limits = {}
    for entry in list(bench["configs"]):
        tiny = "tiny-" + entry["name"]
        config, limits[entry["name"]] = fixture_of(_load(root, entry["file"]))
        _dump(config, home, "configs", tiny + ".json")
        bench["configs"].append({
            "name": tiny, "source": "fixture", "reduced": [], "why": "fixture",
            "file": bench["paths"][0] + "/configs/" + tiny + ".json"})
    names = {}
    for entry in list(bench["workloads"]):
        real, mix = entry["name"], entry["traffic"] + "-tiny"
        fixture = names[real] = "tiny." + real
        traffic = _load(home, "traffic", entry["traffic"] + ".json")
        traffic.update(ramp_seconds=ramp_seconds, lengths="tiny")
        _dump(traffic, home, "traffic", mix + ".json")
        cell = _load(home, "cells", real + ".json")
        cell["users"] = users
        if "slots" in cell["args"]:
            cell["args"]["slots"] = users
        cell["limits"] = dict(limits[entry["config"]])
        _dump(cell, home, "cells", fixture + ".json")
        bench["workloads"].append(dict(
            entry, name=fixture, config="tiny-" + entry["config"], traffic=mix))
        for metric in bench["per_layer"] + bench["end_to_end"]:
            if real in metric.get("workloads", []):
                metric["workloads"].append(fixture)
    _dump(bench, root, "BENCHMARK.json")
    return names


def make_root(tmp, users=3, ramp_seconds=0.5):
    """Returns ``(root, {real cell name: fixture cell name})``."""
    root = copy_root(tmp)
    return root, add_fixtures(root, users, ramp_seconds)


def check_configuration(root, bench, config):
    """An entry of ``configs`` against its file: the contract's keys, the
    source, and what a cut configuration must say of its cut."""
    assert sorted(config) == ["file", "name", "reduced", "source", "why"]
    assert NAME.match(config["name"]) and config["source"].startswith("https://")
    assert any(config["file"].startswith(p + "/") for p in bench["paths"])
    stated = _load(root, config["file"])
    assert stated["source"] == config["source"]
    reduced = config["reduced"]
    assert stated["reduced"] == reduced and isinstance(reduced, list)
    assert len(reduced) <= 16 and len(set(reduced)) == len(reduced)
    assert all(NAME.match(key) and key in stated for key in reduced)
    assert stated["departures"] and "dtype" in stated["assumed"]
    assert config["name"] in {w["config"] for w in bench["workloads"]}
    if not reduced:
        return
    # a cut configuration says what the source has, and what the cut stands for
    published, deployment = stated["published"], stated["deployment"]
    assert sorted(published) == sorted(reduced)
    number = lambda v: isinstance(v, (int, float)) and not isinstance(v, bool)
    for key in reduced:
        assert stated[key] != published[key]
        if number(stated[key]) and number(published[key]):
            assert stated[key] < published[key]
    assert isinstance(deployment, str) and "\n" not in deployment
    assert re.search(r"\d", deployment) and "chips" in deployment
    assert "layer" in deployment
    # total_params is of what is held here: the same count over the
    # published values gives more
    total_params = family.arithmetic(stated).total_params
    assert 0 < total_params(stated) < total_params({**stated, **published})


def check_cell_resolves(root, cell):
    """A cell's entry against the files it names."""
    resolved = run.resolve_cell(root, cell)
    arithmetic = family.arithmetic(resolved["config"])
    assert arithmetic.total_params(resolved["config"]) > 0
    assert arithmetic.vocab(resolved["config"]) > 1
    assert resolved["traffic"]["api"] in ("sequence", "stream")
    assert resolved["cell"]["users"] >= 1 and "users" not in resolved["traffic"]
    lengths = resolved["traffic"]["lengths"]
    assert lengths["source"] and lengths["pool"] >= 1
    longest = lengths["prompt"]["max"] + lengths["output"]["max"]
    assert longest <= arithmetic.max_len(resolved["config"])
    assert resolved["cell"]["limits"]["served_gap_max"] > 0
    assert all(limit >= 0 for limit in resolved["cell"]["limits"].values())
    assert resolved["cell"]["step_program"].startswith("jit_")
    assert {m["name"] for m in resolved["end_to_end"]} >= {"setup_s"}
    assert len(resolved["end_to_end"]) >= 2 and resolved["per_layer"]
    return resolved
