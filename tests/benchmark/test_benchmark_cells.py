"""``BENCHMARK.json`` against the contract it was written to, and every
cell's entry against the files it names."""

import json
import os
import re

import pytest

from benchmark import family, run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert sorted(BENCH) == sorted([
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"])
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in BENCH["paths"])
    assert os.path.isfile(os.path.join(ROOT, BENCH["command"][1]))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_files_that_exist(cell):
    resolved = run.resolve_cell(ROOT, cell)
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
    assert resolved["cell"]["step_program"].startswith("jit_")
    assert {m["name"] for m in resolved["end_to_end"]} >= {"setup_s"}
    assert len(resolved["end_to_end"]) >= 2 and resolved["per_layer"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_entry(cell):
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert sorted(entry) == ["chips", "config", "name", "traffic", "why"]
    assert entry["chips"] in (1, 4) and 1 <= len(entry["why"]) <= 200
    assert NAME.match(entry["name"]) and NAME.match(entry["traffic"])
    assert entry["config"] in {c["name"] for c in BENCH["configs"]}


def test_a_pair_of_configuration_and_traffic_appears_once():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert len(set(CELLS)) == len(CELLS)


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration(config):
    assert sorted(config) == ["file", "name", "reduced", "source", "why"]
    assert NAME.match(config["name"]) and config["source"].startswith("https://")
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    with open(os.path.join(ROOT, config["file"])) as f:
        stated = json.load(f)
    assert stated["source"] == config["source"]
    assert stated["reduced"] == config["reduced"] == []
    assert stated["departures"] and "dtype" in stated["assumed"]
    assert config["name"] in {w["config"] for w in BENCH["workloads"]}


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric in BENCH["end_to_end"]:
        allowed |= {"bound"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        allowed |= {"layer", "moves"}
        assert metric["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        assert "\n" not in metric["layer"] and 1 <= len(metric["layer"]) <= 200
    assert set(metric) <= allowed
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)


def test_metric_names_are_unique_and_setup_is_there():
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] <= 0.1 and "workloads" not in setup


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader(metric):
    home = os.path.join(ROOT, BENCH["paths"][0], "layer_metrics")
    with open(os.path.join(home, metric["name"] + ".json")) as f:
        spec = json.load(f)
    assert ("fact" in spec) != ("reader" in spec)
    if "reader" in spec:
        assert os.path.isfile(os.path.join(home, spec["reader"]))
    # nothing to read gives nothing, never 0
    assert run.read_layer_metric(
        os.path.join(ROOT, BENCH["paths"][0]), metric["name"],
        {"config": run.resolve_cell(ROOT, CELLS[0])["config"]}) is None


def test_roofline_and_mfu_names():
    names = {m["name"]: m for m in BENCH["per_layer"]}
    assert names["step_roofline"]["unit"] == "%" and names["step_mfu"]["unit"] == "%"
    assert names["step_mfu"]["moves"] == names["step_roofline"]["moves"]


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 4)
