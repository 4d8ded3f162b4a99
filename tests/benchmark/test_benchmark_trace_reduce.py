"""The reduction from a profiler trace to busy, idle, step time and gaps:
on a trace recorded on the chip (``data/recorded_trace.json``: four
executions of ``jit_step`` of gpt2-large.stream16 in a row) and on traces
small enough to work by hand."""

import json
import os

import pytest

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "recorded_trace.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced(recorded):
    return trace_reduce.reduce(recorded, "jit_step")


def test_recorded_steps(reduced):
    assert reduced["chips"] == 1
    assert reduced["step_count"] == 4
    assert reduced["step_device_ms"] == pytest.approx(2.6555, abs=1e-3)


def test_recorded_busy_is_the_union_of_the_operations(recorded, reduced):
    device = next(p for p in recorded["planes"] if p["name"].startswith("/device"))
    ops = next(l for l in device["lines"] if l["name"] == "XLA Ops")["events"]
    modules = next(l for l in device["lines"] if l["name"] == "XLA Modules")["events"]
    # operations of one device run one at a time: the union is their sum,
    # and it cannot pass what the programs that hold them took
    assert reduced["busy_s"] == pytest.approx(sum(e[2] for e in ops) / 1e9, rel=1e-3)
    assert reduced["busy_s"] <= sum(e[2] for e in modules) / 1e9
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_recorded_idle_share_and_longest_gap(reduced):
    idle = 1 - reduced["busy_s"] / reduced["window_s"]
    assert idle == pytest.approx(0.605, abs=0.01)
    assert reduced["longest_gap_ms"] == pytest.approx(10.804, abs=1e-2)
    assert sum(s for _, s in reduced["idle_gaps"]) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-3)


def test_recorded_breakdown(reduced):
    names = [name for name, _ in reduced["device_ops"]]
    assert names[:2] == ["slice-done", "multiply_reduce_fusion"]
    assert len(reduced["device_ops"]) <= 10 and len(reduced["idle_gaps"]) <= 10
    assert all(" = " not in n and not n.startswith("%") for n in names)
    seconds = [s for _, s in reduced["device_ops"]]
    assert seconds == sorted(seconds, reverse=True)
    # the host was waiting for the allocator through the longest gaps
    assert reduced["idle_gaps"][0][0] == "DeferredTpuAllocator::Allocate"


def test_another_program_name_finds_no_step(recorded):
    assert trace_reduce.reduce(recorded, "jit_batched_step")["step_device_ms"] is None


def hand_trace(ops, modules=(), host=()):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [list(e) for e in ops]},
            {"name": "XLA Modules", "events": [list(e) for e in modules]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [list(e) for e in host]}]}]}


def test_hand_worked_busy_idle_and_gap_names():
    trace = hand_trace(
        ops=[("fusion.1", 0, 100), ("fusion.2", 100, 50), ("copy", 400, 100),
             ("fusion.1", 600, 100)],
        modules=[("jit_step(1)", 0, 150), ("jit_step(1)", 400, 300),
                 ("jit_other(2)", 800, 10)],
        host=[("model.execute", 100, 400), ("np.asarray", 200, 100),
              ("idle_wait", 480, 200)])
    r = trace_reduce.reduce(trace, "jit_step")
    assert r["busy_s"] == pytest.approx(350e-9)
    # the traced span runs to the last event of all, the program at 800..810
    assert r["window_s"] == pytest.approx(810e-9)
    assert r["step_count"] == 2 and r["step_device_ms"] == pytest.approx(225e-6)
    assert r["longest_gap_ms"] == pytest.approx(250e-6)
    assert r["device_ops"][0] == ["fusion", pytest.approx(250e-9)]
    # the gap 150..400 has its middle at 275: the shortest span over it is
    # np.asarray; the gap 500..600 has 550, under idle_wait alone
    assert dict(map(tuple, r["idle_gaps"])) == {
        "np.asarray": pytest.approx(250e-9), "idle_wait": pytest.approx(100e-9),
        "no_host_span": pytest.approx(110e-9)}


def test_idle_at_the_edges_of_the_traced_span_is_idle():
    trace = hand_trace(ops=[("a", 1000, 100), ("a", 1200, 100)],
                       host=[("waiting", 0, 900)])
    trace["span_ns"] = [0, 2000]
    r = trace_reduce.reduce(trace, "x")
    assert r["busy_s"] == pytest.approx(200e-9)
    assert r["window_s"] == pytest.approx(2000e-9)
    assert r["longest_gap_ms"] == pytest.approx(1000e-6)
    assert dict(map(tuple, r["idle_gaps"])) == {
        "waiting": pytest.approx(1000e-9), "no_host_span": pytest.approx(800e-9)}


def test_overlapping_operations_count_once():
    r = trace_reduce.reduce(hand_trace(ops=[("a", 0, 100), ("b", 50, 100)]), "x")
    assert r["busy_s"] == pytest.approx(150e-9) and r["longest_gap_ms"] == 0.0


@pytest.mark.parametrize("planes", [
    [],
    [{"name": "/host:CPU", "lines": [{"name": "t", "events": [["x", 0, 5]]}]}],
    [{"name": "/device:TPU:0", "lines": []}],
])
def test_nothing_on_a_device_reads_as_nothing(planes):
    assert trace_reduce.reduce({"planes": planes}, "jit_step") is None


def test_two_chips_are_averaged():
    trace = hand_trace(ops=[("a", 0, 100), ("a", 300, 100)])
    second = json.loads(json.dumps(trace["planes"][0]))
    second["name"] = "/device:TPU:1"
    second["lines"][0]["events"] = [["a", 0, 400]]
    trace["planes"].append(second)
    r = trace_reduce.reduce(trace, "x")
    assert r["chips"] == 2
    assert r["busy_s"] == pytest.approx(300e-9) and r["window_s"] == pytest.approx(400e-9)


@pytest.mark.parametrize("intervals, want", [
    ([], []),
    ([(0, 1)], [(0, 1)]),
    ([(5, 6), (0, 2), (1, 3)], [(0, 3), (5, 6)]),
    ([(0, 10), (2, 3)], [(0, 10)]),
    ([(0, 1), (1, 2)], [(0, 2)]),
])
def test_merge(intervals, want):
    assert trace_reduce.merge(intervals) == want


@pytest.mark.parametrize("event, short, kind", [
    ("%fusion.12 = f32[16]{0} fusion(f32[16]{0} %p), kind=kLoop", "fusion.12", "fusion"),
    ("%copy-done = bf16[4]{0} copy-done(%copy-start)", "copy-done", "copy-done"),
    ("jit_step(7289971653275059145)", "jit_step(7289971653275059145)",
     "jit_step(7289971653275059145)"),
    ("%broadcast_select_fusion.3 = (bf16[1]) fusion()", "broadcast_select_fusion.3",
     "broadcast_select_fusion"),
])
def test_operation_names(event, short, kind):
    assert trace_reduce.short_name(event) == short
    assert trace_reduce.op_kind(short) == kind


def test_find_xplane_takes_the_newest(tmp_path):
    assert trace_reduce.find_xplane(str(tmp_path)) is None
    for stamp in ("2026_01_01", "2026_01_02"):
        d = tmp_path / "plugins" / "profile" / stamp
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(b"")
    assert "2026_01_02" in trace_reduce.find_xplane(str(tmp_path))
