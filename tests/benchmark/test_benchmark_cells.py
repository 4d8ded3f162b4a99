"""``BENCHMARK.json`` against the contract it was written to, and every
cell's entry against the files it names."""

import json
import os
import re
import sys

import pytest

import benchmark_fixture
from benchmark import run
from benchmark_fixture import NAME

ROOT = benchmark_fixture.REPO
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
    benchmark_fixture.check_cell_resolves(ROOT, cell)


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
    benchmark_fixture.check_configuration(ROOT, BENCH, config)


@pytest.mark.parametrize("name", ["gpt2-large", "cerebras-gpt-1.3b"])
def test_the_present_configurations_are_not_cut(name):
    config = next(c for c in BENCH["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, config["file"])) as f:
        assert json.load(f)["reduced"] == config["reduced"] == []


CUT = {"source": "https://example.org/cut/config.json", "hidden_size": 64,
       "num_hidden_layers": 3, "num_experts": 4, "vocab_size": 128,
       "reduced": ["num_hidden_layers", "num_experts", "vocab_size"],
       "published": {"num_hidden_layers": 12, "num_experts": 16, "vocab_size": 512},
       "deployment": "each layer is divided over 4 chips, a quarter of the experts "
                     "and of the vocabulary on each; the first 3 layers are kept",
       "departures": ["random weights"], "assumed": {"dtype": "bfloat16"},
       "arithmetic": "cut_arithmetic"}
CUT_ARITHMETIC = """
def vocab(config):
    return config["vocab_size"]
def max_len(config):
    return 64
def work(config, positions):
    return {"tokens_processed": 0, "flops": 0.0}
def step_least(config, work, width):
    return {"bytes": 1.0, "flops": 1.0}
def total_params(config):
    config = HELD
    d = config["hidden_size"]
    return (config["num_hidden_layers"] * config["num_experts"] * 3 * d * d
            + 2 * config["vocab_size"] * d)
"""


@pytest.mark.parametrize("fault, change", [
    ("sound", {}),
    ("no_published", {"published": None}),
    ("a_key_not_published", {"published": {"num_hidden_layers": 12, "num_experts": 16}}),
    ("not_smaller", {"num_experts": 32}),
    ("no_deployment", {"deployment": None}),
    ("deployment_names_no_chips", {"deployment": "the first 3 layers are kept"}),
    ("a_key_the_file_lacks", {"reduced": CUT["reduced"] + ["num_heads"]}),
    ("total_params_of_what_is_published", {}),
], ids=lambda v: v if isinstance(v, str) else "")
def test_a_cut_configuration_says_its_cut(tmp_path, monkeypatch, fault, change):
    """``check_configuration`` on a configuration that is not in
    ``BENCHMARK.json``: ``reduced`` may say something, and then the file must
    say the rest. Each fault differs from the sound file in one thing."""
    stated = {k: v for k, v in {**CUT, **change}.items() if v is not None}
    (tmp_path / "benchmark").mkdir()
    (tmp_path / "benchmark" / "cut.json").write_text(json.dumps(stated))
    (tmp_path / "cut_arithmetic.py").write_text(CUT_ARITHMETIC.replace(
        "HELD", '{**config, **config["published"]}'
        if fault == "total_params_of_what_is_published" else "config"))
    monkeypatch.syspath_prepend(str(tmp_path))
    monkeypatch.delitem(sys.modules, "cut_arithmetic", raising=False)
    entry = {"name": "cut", "source": CUT["source"], "file": "benchmark/cut.json",
             "reduced": stated["reduced"], "why": "a test"}
    bench = {"paths": ["benchmark"], "workloads": [{"config": "cut"}]}
    if fault == "sound":
        benchmark_fixture.check_configuration(str(tmp_path), bench, entry)
    else:
        with pytest.raises((AssertionError, KeyError)):
            benchmark_fixture.check_configuration(str(tmp_path), bench, entry)


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
