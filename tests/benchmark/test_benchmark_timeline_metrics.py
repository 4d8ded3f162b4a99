"""The per-layer metrics that read the program's request timeline through the
statistics verb (``layer_metrics/request_parts.py`` and its four readers): on
hand-made facts, on the facts a program without the timeline gives, and in a
traced run of every fixture cell."""

import json
import os

import pytest

import benchmark_fixture
from benchmark import run

REPO = benchmark_fixture.REPO
HOME = os.path.join(REPO, "benchmark")
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
BATCHER = ("queue_wait_ms", "own_rounds_ms", "window_hold_ms")
NEW = BATCHER + ("stream_self_ms",)


def server(success, queue, compute_infer):
    pairs = {"success": success, "queue": queue, "compute_infer": compute_infer,
             "fail": (0, 0)}
    return {f"{kind}_{field}": value for kind, pair in pairs.items()
            for field, value in zip(("count", "ns"), pair)}


@pytest.mark.parametrize("facts, want", [
    # 10 requests of 270 ms: 140 queued, 45 in their own rounds, 85 held
    ({"server": server((10, 2_700_000_000), (10, 1_400_000_000), (10, 450_000_000))},
     {"queue_wait_ms": 140.0, "own_rounds_ms": 45.0, "window_hold_ms": 85.0,
      "stream_self_ms": 225.0}),
    # 4 streams of 2 s, 1.9 s of each inside the generator, no queue
    ({"server": server((4, 8_000_000_000), (4, 0), (4, 7_600_000_000))},
     {"queue_wait_ms": None, "own_rounds_ms": 1900.0, "window_hold_ms": None,
      "stream_self_ms": 100.0}),
    # a program without the timeline: queue never counted, compute_infer the
    # whole of execute: nothing to read, for any of the four
    ({"server": server((10, 2_700_000_000), (0, 0), (10, 2_690_000_000))},
     dict.fromkeys(NEW)),
    ({"server": server((0, 0), (0, 0), (0, 0))}, dict.fromkeys(NEW)),
    ({"server": None}, dict.fromkeys(NEW)),
    ({}, dict.fromkeys(NEW)),
], ids=["batched", "stream", "no-timeline", "no-requests", "no-statistics",
        "no-facts"])
def test_readers_on_hand_made_facts(facts, want):
    for name, value in want.items():
        got = run.read_layer_metric(HOME, name, facts)
        assert got == (None if value is None else pytest.approx(value)), name


@pytest.mark.parametrize("name", NEW)
def test_entry(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["unit"] == "ms" and entry["better"] == "lower"
    assert entry["source"] == "program_counter"
    assert entry["moves"] == "token_gap_p50_ms"
    batcher = name in BATCHER
    assert entry["layer"] == ("Sequence batcher" if batcher else "Server core")
    traffic = {w["name"]: w["traffic"] for w in BENCH["workloads"]}
    want = "alpaca-seq" if batcher else "alpaca-stream"
    assert entry["workloads"] == [c for c in CELLS if traffic[c] == want]


@pytest.fixture(scope="module")
def fixture_root(tmp_path_factory):
    return benchmark_fixture.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", CELLS)
def test_traced_fixture_cell_reports_its_request_parts(fixture_root, cell):
    root, names = fixture_root
    result = run.run_cell(root, names[cell], seed=2**31 + 29, seconds=1.5,
                          trace=True, require_tpu=False)
    assert result["correct"] is True, result["compared"]
    metrics = {name: m["value"] for name, m in result["metrics"].items()}
    owed = {m["name"] for m in BENCH["per_layer"]
            if cell in m.get("workloads", [cell])}
    assert set(NEW) & owed == set(NEW) & set(metrics)
    if "seq" in cell:
        assert set(BATCHER) <= set(metrics)
        assert all(metrics[name] > 0 for name in BATCHER)
        # the three are the whole of a request inside the core
        assert sum(metrics[name] for name in BATCHER) == pytest.approx(
            metrics["server_request_ms"], rel=1e-9)
    else:
        assert metrics["stream_self_ms"] > 0
        assert not set(BATCHER) & set(metrics)
