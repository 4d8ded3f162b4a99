"""Each cell end to end at fixture sizes on the CPU: the users' process, the
serving child, GRPC between them, the window, the reference's verdict and the
result line. The look for a chip is skipped (``require_tpu=False``); the
command itself is seen to refuse a machine without one."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

import benchmark_fixture
from benchmark import calibrate, run

REPO = benchmark_fixture.REPO
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
SECONDS = 1.5


@pytest.fixture(scope="module")
def fixture_root(tmp_path_factory):
    return benchmark_fixture.make_root(tmp_path_factory.mktemp("bench"))


def check_line(result, owed, traced):
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(result)[-1] == "compared"
    json.loads(json.dumps(result))
    assert result["attempted"] > 0 and result["failed"] == 0
    device = result["device"]
    assert device["platform"] == "cpu" and device["kind"] and device["count"] >= 1
    assert "memory_peak_bytes" in device
    for name, metric in result["metrics"].items():
        assert name in owed and metric["unit"] == owed[name]
        assert isinstance(metric["value"], float) and metric["value"] > 0
    for number in result["compared"].values():
        assert number["value"] is not None and "limit" in number
    if traced:
        # there is no TPU plane in a CPU trace: the trace's metrics are left
        # out of the line, never reported as 0
        assert "step_device_ms" not in result["metrics"]
        assert "breakdown" not in result


def cell_at_fixture_size(root, names, cell, traced):
    """A cell's twin through a whole run; the metrics owed are those the
    root's own ``BENCHMARK.json`` lists for it."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    result = run.run_cell(root, names[cell], seed=2**31 + 17, seconds=SECONDS,
                          trace=traced, require_tpu=False)
    assert result["correct"] is True, result["compared"]
    kind = "per_layer" if traced else "end_to_end"
    owed = {m["name"]: m["unit"] for m in bench[kind]
            if names[cell] in m.get("workloads", [names[cell]])}
    check_line(result, owed, traced)
    if not traced:
        assert set(result["metrics"]) == set(owed)
    elif "batch_width_mean" in owed:
        assert {"batch_width_mean", "server_request_ms",
                "client_self_ms"} <= set(result["metrics"])
    return result


def an_altered_token_is_not_correct(root, names, cell):
    faulty = os.path.join(root, "tests", "benchmark", "faulty_server.py")
    result = run.run_cell(root, names[cell], seed=5, seconds=SECONDS, trace=False,
                          require_tpu=False, server_command=[sys.executable, faulty])
    assert result["correct"] is False
    compared = result["compared"]
    assert compared["served_gap_max"]["value"] > compared["served_gap_max"]["limit"]
    if run.resolve_cell(root, names[cell])["traffic"]["api"] == "sequence":
        assert compared["argmax_mismatch"]["value"] > 0
    assert result["failed"] == 0  # late or wrong is not failed


def calibration_judges_sound_seeds_and_the_control(root, names, cell):
    """``calibrate.py``'s loop: a seed's served tokens and, in their place,
    the family's control's go through the run's own comparison and limits."""
    resolved = run.resolve_cell(root, names[cell])
    with run.Serving(resolved, 11, require_tpu=False) as serving:
        first, second = calibrate.read_seeds(
            serving, resolved, [11, 2**31 + 12], control_seeds=1, seconds=1.0)
    limits = resolved["cell"]["limits"]
    for reading in (first, second):
        assert reading["correct"] is True and reading["failed"] == 0
        assert set(limits) <= set(reading["compared"])
        for name, limit in limits.items():
            assert reading["compared"][name]["value"] <= limit
    assert first["control_gap_max"] > limits["served_gap_max"]
    assert first["control_correct"] is False
    assert "control_correct" not in second
    return first, second


@pytest.mark.parametrize("traced", [False, True], ids=["timed", "traced"])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_at_fixture_size(fixture_root, cell, traced):
    cell_at_fixture_size(*fixture_root, cell, traced)


@pytest.mark.parametrize("cell", CELLS)
def test_an_altered_token_is_not_correct(fixture_root, cell):
    an_altered_token_is_not_correct(*fixture_root, cell)


@pytest.mark.parametrize("cell", CELLS)
def test_calibration_judges_sound_seeds_and_the_control(fixture_root, cell):
    calibration_judges_sound_seeds_and_the_control(*fixture_root, cell)


def test_two_cells_that_end_in_the_same_word_get_two_twins(tmp_path):
    root = benchmark_fixture.copy_root(tmp_path)
    home = os.path.join(root, "benchmark")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    first = next(w for w in bench["workloads"] if w["name"] == "gpt2-large.seq16")
    shutil.copy(os.path.join(home, "cells", "gpt2-large.seq16.json"),
                os.path.join(home, "cells", "cerebras-gpt-1.3b.seq16.json"))
    bench["workloads"].append(dict(first, name="cerebras-gpt-1.3b.seq16",
                                   config="cerebras-gpt-1.3b"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    names = benchmark_fixture.add_fixtures(root)
    assert len(set(names.values())) == len(names) == len(CELLS) + 1
    twins = [run.resolve_cell(root, names[c])
             for c in ("gpt2-large.seq16", "cerebras-gpt-1.3b.seq16")]
    assert twins[0]["cell_path"] != twins[1]["cell_path"]
    assert [t["entry"]["config"] for t in twins] == [
        "tiny-gpt2-large", "tiny-cerebras-gpt-1.3b"]
    # a family with no fixture of its own gets the GPT-2 family's and 0.01
    assert all(t["config"] == benchmark_fixture.TINY_CONFIG
               and t["cell"]["limits"] == {"served_gap_max": 0.01} for t in twins)


def _digests(root):
    out = {}
    for folder, _, files in os.walk(root):
        if ".benchmark_run" in folder or ".jax_cache" in folder:
            continue
        for name in files:
            path = os.path.join(folder, name)
            if os.path.islink(path) or "__pycache__" in path:
                continue
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(f.read()).hexdigest()
    return out


def _gpt2_again(add):
    """The GPT-2 family at other numbers: a configuration and a builder that
    lean on the family's own files."""
    add(dict(benchmark_fixture.TINY_CONFIG, n_layer=1, n_embd=32, n_head=2),
        "configs", "later.json")
    add("from benchmark import builders\n\n\n"
        "def generate(config, seed, **args):\n"
        "    return builders.tiny_lm_generate(config, seed, **args)\n",
        "later_builders.py")
    return ["configs/later.json", "later_builders.py"], {}, {}


LATER_ARITHMETIC = """
MARK = 4321.0


def vocab(config):
    return config["vocab_size"]


def max_len(config):
    return config["max_position_embeddings"]


def total_params(config):
    d, v = config["hidden_size"], config["vocab_size"]
    return (12 * d * d * config["num_hidden_layers"] + 2 * d * v
            + d * config["max_position_embeddings"])


def work(config, positions):
    tokens = sum(1 for _ in positions)
    return {"tokens_processed": tokens, "flops": 1e6 * tokens, "mark": MARK}


def step_least(config, work, width):
    # 1/8 ms at the fixture's peaks, whatever the step
    return {"bytes": 1e9 * 0.125e-3 * width, "flops": 1.0}


def init_scale(path, leaf):
    return 0.03 if path[0] == "unembed" else None
"""
LATER_REFERENCE = """
from benchmark import reference as gpt2


def served_token_gaps(params, config, sessions, length{control}):
    return gpt2.served_token_gaps(
        params, {{"n_head": config["num_attention_heads"]}}, sessions, length{passed})
"""
LATER_BUILDER = """
from benchmark.builders import NoDraw


def generate(config, seed, **args):
    from client_tpu.models.decoder import TinyDecoderModel
    from client_tpu.models.generate import TinyGenerateModel

    cls = type("LaterDecoder", (TinyDecoderModel,), {
        "VOCAB": config["vocab_size"], "D_MODEL": config["hidden_size"],
        "HEADS": config["num_attention_heads"],
        "LAYERS": config["num_hidden_layers"],
        "MAX_LEN": config["max_position_embeddings"]})
    decoder = cls(seed=NoDraw(), **args)
    decoder._ensure_built()
    return TinyGenerateModel(decoder=decoder), decoder
"""
FACT_READERS = {  # metric -> the fact it reads, as a reader file of its own
    "work_mark": "facts['work']['mark']",
    "registry_success":
        "facts['registry']['client_tpu_server_request_success_count']",
    "server_success": "float(facts['server']['success_count'])",
    "server_output_pairs": "float(facts['server']['compute_output_count']"
                           " + facts['server']['compute_input_count'])",
}


def _another_family(add, control=True):
    """A family that is not GPT-2's: a configuration with none of its keys,
    its own arithmetic (with an ``init_scale``), reference and builder, and
    readers of what a traced run hands them, all as new files."""
    add({"source": "fixture", "model_type": "later", "hidden_size": 32,
         "num_hidden_layers": 1, "num_attention_heads": 2,
         "max_position_embeddings": 64, "vocab_size": 300, "dtype": "bfloat16",
         "arithmetic": "benchmark.later_arithmetic",
         "reference": "benchmark.later_reference", "reduced": []},
        "configs", "later.json")
    add(LATER_ARITHMETIC, "later_arithmetic.py")
    add(LATER_REFERENCE.format(
        control=", control=False" if control else "",
        passed=", control=control" if control else ""), "later_reference.py")
    add(LATER_BUILDER, "later_builders.py")
    files = ["configs/later.json", "later_arithmetic.py", "later_builders.py",
             "later_reference.py"]
    for name, fact in FACT_READERS.items():
        add({"reader": name + ".py"}, "layer_metrics", name + ".json")
        add(f"def read(facts):\n    return {fact}\n", "layer_metrics", name + ".py")
        files += [f"layer_metrics/{name}.json", f"layer_metrics/{name}.py"]
    return files, {}, {}


CUT_FAMILY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "cut_family")


def _cut_family(add):
    """A family with none of the GPT-2 keys whose configuration is cut to a
    chip's share (``reduced`` of three keys, ``published``, ``deployment``),
    whose experts are stacked ``[experts, d, f]``, whose builder leaves
    ``_params`` as shapes, whose arithmetic has a ``fixture`` and whose
    reference returns a second reading, held by a limit in its cell file:
    ``tests/benchmark/cut_family/``, copied in as a later PR's new files."""
    files = []
    for name in sorted(os.listdir(CUT_FAMILY)):
        with open(os.path.join(CUT_FAMILY, name)) as f:
            path = ("configs", name) if name.endswith(".json") else (name,)
            add(f.read(), *path)
        files.append("/".join(path))
    with open(os.path.join(CUT_FAMILY, "later.json")) as f:
        stated = json.load(f)
    return (files,
            {"builder": "benchmark.later_cut_builders:generate",
             "limits": {"served_gap_max": 0.1, "near_tie_share": 0.25}},
            {"source": stated["source"], "reduced": stated["reduced"]})


def _later_pr(root, family, **options):
    """What a later PR brings, as new files and new entries; the files."""
    home = os.path.join(root, "benchmark")

    def add(obj, *path):
        assert not os.path.exists(os.path.join(home, *path))
        with open(os.path.join(home, *path), "w") as f:
            f.write(obj if isinstance(obj, str) else json.dumps(obj))

    files, cell, entry = family(add, **options)
    add({"source": "fixture", "pool": 4,
         "prompt": {"mean": 4, "sigma": 0.3, "min": 2, "max": 8},
         "output": {"mean": 3, "sigma": 0.3, "min": 2, "max": 6}},
        "lengths", "short.json")
    add({"api": "stream", "ramp_seconds": 0.3, "lengths": "short"},
        "traffic", "bursty.json")
    add({"builder": "benchmark.later_builders:generate", "args": {}, "users": 2,
         "step_program": "jit_step", "limits": {"served_gap_max": 0.01}, **cell},
        "cells", "later.bursty.json")
    add({"reader": "tokens_per_session.py"}, "layer_metrics", "tokens_per_session.json")
    add("def read(facts):\n"
        "    w = facts['window']\n"
        "    return w['output_tokens_per_s'] * facts['seconds'] / w['sessions_timed']\n",
        "layer_metrics", "tokens_per_session.py")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "later", "source": "fixture", "reduced": [],
                             "why": "fixture", "file": "benchmark/configs/later.json",
                             **entry})
    bench["workloads"].append({"name": "later.bursty", "config": "later",
                               "traffic": "bursty", "chips": 1, "why": "fixture"})
    readers = ["tokens_per_session"] + [
        f[len("layer_metrics/"):-len(".json")] for f in files
        if f.startswith("layer_metrics/") and f.endswith(".json")]
    for name in readers:
        bench["per_layer"].append({
            "name": name, "unit": "tokens", "better": "higher",
            "source": "host_clock", "layer": "Client", "moves": "output_tokens_per_s",
            "workloads": ["later.bursty"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return sorted("benchmark/" + p for p in files + [
        "cells/later.bursty.json", "layer_metrics/tokens_per_session.json",
        "layer_metrics/tokens_per_session.py", "lengths/short.json",
        "traffic/bursty.json"])


@pytest.fixture
def forget_later_modules():
    """The later PR's modules are imported from a temporary root by name."""
    yield
    for name in [m for m in sys.modules if m.startswith("benchmark.later_")]:
        del sys.modules[name]


def _through_the_benchmarks_own_checks(root, before, added):
    """The later PR's cut configuration and its cell, held to what every
    configuration and cell of ``BENCHMARK.json`` is held to: the fixtures are
    made after its entries are there, so its cell gets its twin, of its own
    family's fixture, like any other."""
    import jax

    from benchmark import builders

    names = benchmark_fixture.add_fixtures(root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "later")
    assert len(entry["reduced"]) == 3
    benchmark_fixture.check_configuration(root, bench, entry)
    real = benchmark_fixture.check_cell_resolves(root, "later.bursty")
    assert not {"n_embd", "n_head", "n_layer", "n_positions"} & set(real["config"])
    twin = run.resolve_cell(root, names["later.bursty"])
    limits = {"served_gap_max": 0.003, "near_tie_share": 0.3}
    assert twin["config"]["hidden_size"] == 32 and twin["cell"]["limits"] == limits
    assert twin["config"]["reference"] == real["config"]["reference"]
    # its builder hands over shapes, stacked experts among them
    _, decoder = builders.resolve(twin["cell"]["builder"])(twin["config"], 0)
    decoder._ensure_built()
    leaves = jax.tree_util.tree_leaves(decoder._params)
    assert all(isinstance(leaf, jax.ShapeDtypeStruct) for leaf in leaves)
    assert decoder._params["layers"][1]["experts_in"].shape == (4, 32, 32)

    for traced in (False, True):
        result = cell_at_fixture_size(root, names, "later.bursty", traced)
        near = result["compared"]["near_tie_share"]
        assert near["limit"] == 0.3 and 0.0 <= near["value"] <= 0.3
        assert result["compared"]["served_gap_max"]["limit"] == 0.003
        assert list(result)[-1] == "compared"
    an_altered_token_is_not_correct(root, names, "later.bursty")
    first, _ = calibration_judges_sound_seeds_and_the_control(
        root, names, "later.bursty")
    assert first["compared"]["near_tie_share"]["limit"] == 0.3

    after = _digests(root)
    assert [p for p in before if p != "BENCHMARK.json" and after[p] != before[p]] == []
    assert set(added) <= set(after) - set(before)


@pytest.mark.parametrize("family", [_gpt2_again, _another_family, _cut_family],
                         ids=["gpt2_again", "another_family", "cut_family"])
def test_a_later_pr_adds_files_and_edits_none(tmp_path, monkeypatch, family,
                                              forget_later_modules):
    """A configuration, a mix with its lengths, a builder, a per-layer metric
    and a cell, as new files and new entries, run by the harness as it
    stands: once of the GPT-2 family again, once of a family that brings its
    own arithmetic, reference, builder and weight scales under a
    configuration with none of the GPT-2 keys, and once of a family whose
    configuration is cut, taken through the benchmark's own parametrised
    checks at its own fixture."""
    root = benchmark_fixture.copy_root(tmp_path)
    if family is not _cut_family:
        benchmark_fixture.add_fixtures(root)
    before = _digests(root)
    added = _later_pr(root, family)
    # the users' process finds the later PR's modules where the command
    # would: under the root it runs from
    monkeypatch.setattr("benchmark.__path__", [os.path.join(root, "benchmark")])
    if family is _cut_family:
        _through_the_benchmarks_own_checks(root, before, added)
        return

    result = run.run_cell(root, "later.bursty", seed=3, seconds=SECONDS,
                          trace=True, require_tpu=False)
    assert result["correct"] is True, result["compared"]
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["tokens_per_session"] > 1
    after = _digests(root)
    changed = [p for p in before if p != "BENCHMARK.json" and after[p] != before[p]]
    assert changed == []
    assert sorted(set(after) - set(before)) == added
    if family is not _another_family:
        return
    config = run.resolve_cell(root, "later.bursty")["config"]
    assert not {"n_embd", "n_head", "n_layer", "n_positions", "n_inner"} & set(config)
    # the window's work was counted by the family's own arithmetic
    assert metrics["work_mark"] == 4321.0
    # ... and so is the step's roofline: 1/8 ms of least time over a step of
    # 1 ms, a number the GPT-2 arithmetic cannot give
    facts = {"config": config, "trace": {"step_device_ms": 1.0},
             "peaks": {"hbm_bytes_per_s": 1e9, "flops_per_s": 1e12},
             "work": {"tokens_processed": 7}, "batch_histogram": None}
    home = os.path.join(root, "benchmark")
    assert run.read_layer_metric(home, "step_roofline", facts) == pytest.approx(12.5)
    # the registry's series are the window's difference, as the statistics
    # verb's pairs are, and not the total since the warm-up (four sessions
    # and the ramp's more); the verb's seven pairs are all there
    assert abs(metrics["registry_success"] - metrics["server_success"]) <= 2
    assert metrics["server_success"] >= result["counts"]["sessions_finished"] > 0
    assert metrics["server_output_pairs"] == 2 * metrics["server_success"]


def test_a_reference_without_a_control_fails_calibration_by_name(
        tmp_path, monkeypatch, forget_later_modules):
    root, _ = benchmark_fixture.make_root(tmp_path)
    _later_pr(root, _another_family, control=False)
    monkeypatch.setattr("benchmark.__path__", [os.path.join(root, "benchmark")])
    resolved = run.resolve_cell(root, "later.bursty")
    with run.Serving(resolved, 5, require_tpu=False) as serving:
        # a run needs no control, and this reference can judge one
        sound = list(calibrate.read_seeds(serving, resolved, [5], 0, seconds=1.0))
        assert sound[0]["correct"] is True
        with pytest.raises(run.NoResult, match="benchmark.later_reference.*control"):
            list(calibrate.read_seeds(serving, resolved, [6], 1, seconds=1.0))


def _command(root, cell):
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), "--workload",
         cell, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))


def test_the_command_gives_no_result_without_a_tpu(fixture_root):
    root, names = fixture_root
    done = _command(root, names[CELLS[-1]])
    assert done.returncode != 0 and done.stdout.strip() == ""
    assert "TPU" in done.stderr


def test_the_command_gives_no_result_without_the_program(tmp_path):
    root, names = benchmark_fixture.make_root(tmp_path)
    os.unlink(os.path.join(root, "client_tpu"))
    done = _command(root, names[CELLS[-1]])
    assert done.returncode != 0 and done.stdout.strip() == ""


def test_an_unknown_cell_gives_no_result(fixture_root, capsys):
    assert run.main(["--workload", "no.such.cell", "--seed", "1",
                     "--seconds", "1"]) == 1
    assert capsys.readouterr().out == ""


def test_the_result_is_the_last_line_and_the_compared_numbers_end_stderr(
        monkeypatch, capsys):
    canned = {"correct": True, "attempted": 3, "failed": 0,
              "metrics": {"setup_s": {"value": 1.5, "unit": "s"}},
              "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                         "memory_peak_bytes": 1},
              "compared": {"sessions_failed": {"value": 0, "limit": 0},
                           "served_gap_max": {"value": 0.02, "limit": 0.1}}}
    monkeypatch.setattr(run, "run_cell", lambda *a, **k: canned)
    assert run.main(["--workload", "x", "--seed", str(2**31 + 5), "--seconds",
                     "40", "--trace", "1"]) == 0
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == canned
    assert err.strip().splitlines()[-2:] == [
        "compared sessions_failed: 0 (limit 0)",
        "compared served_gap_max: 0.02 (limit 0.1)"]


@pytest.mark.parametrize("records, want", [
    ([], 0),
    ([{"error": None, "tokens": [1, 2], "tokens_out": 2, "prompt": [1] * 3,
       "token_times": [1.0, 2.0]},
      {"error": None, "tokens": [1], "tokens_out": 2, "prompt": [1] * 9,
       "token_times": [1.0]},
      {"error": "x", "tokens": [1, 2], "tokens_out": 2, "prompt": [1] * 9,
       "token_times": [1.0, 2.0]}], 1),
    ([{"error": None, "tokens": [1] * n, "tokens_out": n, "prompt": [1] * n,
       "token_times": [float(n)] * n} for n in range(1, 30)], run.CHECK_SESSIONS),
])
def test_the_sample_is_of_finished_sessions_with_the_longest_in_it(records, want):
    sample = run.pick_sample(records, seed=2**31 + 1)
    assert len(sample) == want
    assert sample == run.pick_sample(records, seed=2**31 + 1)
    if sample:
        assert len(sample[0]["tokens"]) == max(
            len(r["tokens"]) for r in records
            if r["error"] is None and len(r["tokens"]) == r["tokens_out"])


@pytest.mark.parametrize("since, until, lengths", [
    (10.0, 20.0, list(range(10, 21))),  # the ramp's and the late ones left out
    (25.0, 29.0, [25, 26, 27, 28, 29]),
    (40.0, 50.0, []),
])
def test_the_sample_is_of_sessions_finished_inside_the_window(since, until, lengths):
    records = [{"error": None, "tokens": [1] * n, "tokens_out": n, "prompt": [1],
                "token_times": [float(n)] * n} for n in range(1, 30)]
    sample = run.pick_sample(records, seed=7, since=since, until=until)
    assert len(sample) == min(len(lengths), run.CHECK_SESSIONS)
    assert {len(r["tokens"]) for r in sample} <= set(lengths)
    if sample:
        assert len(sample[0]["tokens"]) == lengths[-1]


def test_window_positions_follow_the_tokens_received():
    record = {"prompt": [0] * 10, "t_send": 0.0,
              "token_times": [1.0, 1.1, 1.2, 5.0]}
    # window 0.5 .. 2.0: half of the prompt's span, and the steps that gave
    # the tokens at 1.1 and 1.2 (positions 10 and 11)
    assert run.window_positions([record], 0.5, 1.5) == [0, 1, 2, 3, 4, 10, 11]
    assert run.window_positions([dict(record, token_times=[])], 0.0, 9.0) == []
