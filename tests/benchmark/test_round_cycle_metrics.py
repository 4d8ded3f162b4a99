"""The per-layer metrics that read the round workers' phase clock and the
per-request readings through the program's registry (``facts["registry"]``:
the window's difference of every series labelled with the served model): each
of the eight readers on hand-made facts, each entry of ``BENCHMARK.json``, and
a traced fixture cell of each engine."""

import json
import os
import runpy

import pytest

import benchmark_fixture
from benchmark import run

REPO = benchmark_fixture.REPO
HOME = os.path.join(REPO, "benchmark")
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
# the seven cells the entries were brought with; a later PR's cell is appended
# behind them
A, B, C, D, E, F, G = [w["name"] for w in BENCH["workloads"]][:7]
# name -> (unit, layer, cells, the end-to-end metric it should move)
ENTRIES = {
    "round_cycle_ms": ("ms", "Round worker", [A, B, C, E, F, G], "token_gap_p50_ms"),
    "round_host_ms": ("ms", "Round worker", [A, B, C, E, F, G], "token_gap_p50_ms"),
    "round_dispatch_ms": ("ms", "Round worker", [A, B, C, E, F, G], "token_gap_p50_ms"),
    "readback_transfer_ms": ("ms", "Round worker", [A, G], "token_gap_p50_ms"),
    "round_stride": ("rounds", "Sequence batcher", [A, G], "token_gap_p50_ms"),
    "answer_wake_ms": ("ms", "Server core", [A, G], "token_gap_p50_ms"),
    "token_handoff_ms": ("ms", "Round worker", [B, C, E, F], "token_gap_p50_ms"),
    "server_ttft_ms": ("ms", "Server core", [B, C], "ttft_p50_ms"),
}

PHASE = "client_tpu_server_round_phase_"
# a window of 100 rounds of the slot batcher: 1,000 ms of turns of which 150
# waiting for work and 420 for the device; 80 transfers; 90 continuation
# requests 225 rounds apart in all; 95 answers; and of a stream model's: 40
# streams, 2,000 tokens
REGISTRY = {
    PHASE + "count{phase=dispatch}": 100, PHASE + "ns{phase=dispatch}": 310e6,
    PHASE + "count{phase=wait_work}": 7, PHASE + "ns{phase=wait_work}": 150e6,
    PHASE + "count{phase=device_wait}": 100, PHASE + "ns{phase=device_wait}": 420e6,
    PHASE + "count{phase=readback}": 80, PHASE + "ns{phase=readback}": 72e6,
    PHASE + "count{phase=hand_out}": 120, PHASE + "ns{phase=hand_out}": 48e6,
    "client_tpu_server_sequence_stride_rounds": 225,
    "client_tpu_server_sequence_stride_count": 90,
    "client_tpu_server_answer_wake_ns": 190e6,
    "client_tpu_server_answer_wake_count": 95,
    "client_tpu_server_token_handoff_ns": 500e6,
    "client_tpu_server_token_handoff_count": 2000,
    "client_tpu_server_first_response_ns": 3600e6,
    "client_tpu_server_first_response_count": 40,
    # another model's series never reach a reader, a series of another kind does
    "client_tpu_server_decode_steps{live=256}": 100,
}
WANT = {
    "round_cycle_ms": (310 + 420 + 72 + 48) / 100,
    "round_host_ms": (310 + 72 + 48) / 100,
    "round_dispatch_ms": 3.1,
    "readback_transfer_ms": 0.9,
    "round_stride": 2.5,
    "answer_wake_ms": 2.0,
    "token_handoff_ms": 0.25,
    "server_ttft_ms": 90.0,
}
# what each reader divides by, and what it divides
READS = {
    "round_cycle_ms": (PHASE + "count{phase=dispatch}", None),
    "round_host_ms": (PHASE + "count{phase=dispatch}", None),
    "round_dispatch_ms": (PHASE + "count{phase=dispatch}", PHASE + "ns{phase=dispatch}"),
    "readback_transfer_ms": (PHASE + "count{phase=readback}", PHASE + "ns{phase=readback}"),
    "round_stride": ("client_tpu_server_sequence_stride_count",
                     "client_tpu_server_sequence_stride_rounds"),
    "answer_wake_ms": ("client_tpu_server_answer_wake_count",
                       "client_tpu_server_answer_wake_ns"),
    "token_handoff_ms": ("client_tpu_server_token_handoff_count",
                         "client_tpu_server_token_handoff_ns"),
    "server_ttft_ms": ("client_tpu_server_first_response_count",
                       "client_tpu_server_first_response_ns"),
}


def _read(name, registry):
    return run.read_layer_metric(HOME, name, {"registry": registry})


@pytest.mark.parametrize("name", ENTRIES)
def test_reader_on_hand_made_facts(name):
    assert _read(name, REGISTRY) == pytest.approx(WANT[name], rel=1e-12)
    count, total = READS[name]
    # nothing to read gives nothing, never 0: no registry (a program before
    # the registry reached the readers), a program without the series (the
    # parent of the PR that brought them), a window in which nothing counted
    assert run.read_layer_metric(HOME, name, {}) is None
    assert run.read_layer_metric(HOME, name, {"registry": None}) is None
    assert _read(name, {}) is None
    assert _read(name, {"client_tpu_server_decode_steps{live=256}": 100}) is None
    assert _read(name, {k: v for k, v in REGISTRY.items() if k != count}) is None
    assert _read(name, {**REGISTRY, count: 0}) is None
    if total is not None:
        assert _read(name, {k: v for k, v in REGISTRY.items() if k != total}) is None
        assert _read(name, {**REGISTRY, total: 0}) is None


def test_the_wait_for_work_is_in_no_cycle_and_the_wait_for_the_device_in_no_host_turn():
    idle = {**REGISTRY, PHASE + "ns{phase=wait_work}": 9e12}
    for name in ("round_cycle_ms", "round_host_ms"):
        assert _read(name, idle) == pytest.approx(WANT[name])
    slow = {**REGISTRY, PHASE + "ns{phase=device_wait}": 840e6}
    assert _read("round_host_ms", slow) == pytest.approx(WANT["round_host_ms"])
    assert _read("round_cycle_ms", slow) == pytest.approx(
        WANT["round_cycle_ms"] + 4.2)
    # a phase the readers have never heard of is part of the turn
    more = {**REGISTRY, PHASE + "ns{phase=prefill_chunk}": 100e6,
            PHASE + "count{phase=prefill_chunk}": 30}
    for name in ("round_cycle_ms", "round_host_ms"):
        assert _read(name, more) == pytest.approx(WANT[name] + 1.0)


@pytest.mark.parametrize("name", ENTRIES)
def test_entry(name):
    entry = dict(next(m for m in BENCH["per_layer"] if m["name"] == name))
    unit, layer, cells, moves = ENTRIES[name]
    listed = entry.pop("workloads")
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": "program_counter", "layer": layer, "moves": moves}
    # the cells it was brought with; a later cell is appended behind them
    assert listed[:len(cells)] == cells
    # every cell on the list reports the end-to-end metric the entry moves
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == moves)
    assert set(listed) <= set(moved.get("workloads", listed))
    # the cell without a round worker (the per-stream loop) is on no list
    assert D not in listed
    with open(os.path.join(HOME, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == name + ".py" and spec["what"].startswith("registry: ")


def test_the_entries_follow_the_eighteen_that_were_there():
    assert [m["name"] for m in BENCH["per_layer"][18:26]] == list(ENTRIES)


def test_a_cell_appended_by_a_later_pr_breaks_nothing_here(tmp_path, monkeypatch):
    """This module, loaded against a ``BENCHMARK.json`` as the PR that brings
    the next cell leaves it (an eighth cell of the slot batcher, appended to
    ``workloads`` and behind the cells of every list A is on; a metric of its
    own behind these eight): it imports, and every entry still reads as it
    was brought."""
    root = benchmark_fixture.copy_root(tmp_path)
    later = "cerebras-gpt-1.3b.seq8"
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({**bench["workloads"][0], "name": later,
                               "config": "cerebras-gpt-1.3b"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if A in metric.get("workloads", []):
            metric["workloads"].append(later)
    bench["per_layer"].append({**bench["per_layer"][-1], "name": "later_ms",
                               "workloads": [later]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    monkeypatch.setattr(benchmark_fixture, "REPO", root)
    loaded = runpy.run_path(__file__)
    assert [w["name"] for w in loaded["BENCH"]["workloads"]][7:] == [later]
    assert loaded["HOME"] == os.path.join(root, "benchmark")
    assert [loaded[c] for c in "ABCDEFG"] == [A, B, C, D, E, F, G]
    for name in loaded["ENTRIES"]:
        loaded["test_entry"](name)
        assert (later in next(m for m in loaded["BENCH"]["per_layer"]
                              if m["name"] == name)["workloads"]) == (
            A in ENTRIES[name][2])
    loaded["test_the_entries_follow_the_eighteen_that_were_there"]()


@pytest.fixture(scope="module")
def fixture_root(tmp_path_factory):
    return benchmark_fixture.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", [A, B, F], ids=["batcher", "rounds", "rounds-chunks"])
def test_a_traced_fixture_cell_of_each_engine_reports_its_new_metrics(
        fixture_root, cell):
    root, names = fixture_root
    result = run.run_cell(root, names[cell], seed=2**31 + 36, seconds=1.5,
                          trace=True, require_tpu=False)
    assert result["correct"] is True, result["compared"]
    metrics = {name: m["value"] for name, m in result["metrics"].items()}
    owed = {name for name, (_, _, cells, _) in ENTRIES.items() if cell in cells}
    assert owed == set(ENTRIES) & set(metrics)
    for name in owed:
        assert isinstance(metrics[name], float) and metrics[name] > 0, name
        assert result["metrics"][name]["unit"] == ENTRIES[name][0]
    # the host's own turn is the cycle less the waits for the device, and
    # the dispatch call is a part of it
    assert metrics["round_dispatch_ms"] < metrics["round_host_ms"] < metrics["round_cycle_ms"]
    if cell == A:
        assert metrics["round_stride"] >= 1.0
        assert metrics["readback_transfer_ms"] < metrics["round_host_ms"]
