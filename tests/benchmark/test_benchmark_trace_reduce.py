"""The reduction from a profiler trace to busy, idle, step time and gaps:
on a trace recorded on the chip (``data/recorded_trace.json``: four
executions of ``jit_step`` of gpt2-large.stream16 in a row) and on traces
small enough to work by hand."""

import json
import os

import pytest

from benchmark import family, run, stats, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
HOME = os.path.join(os.path.dirname(os.path.dirname(HERE)), "benchmark")


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


@pytest.mark.parametrize("histogram, roofline", [
    (None, 71.47829224890668),
    ({"1": 10, "3": 25, "16": 5}, 72.96046402283974),
], ids=["one_sequence_a_step", "the_batchers_widths"])
def test_roofline_and_mfu_on_the_recorded_trace_read_as_before_the_family_seam(
        reduced, histogram, roofline):
    """Pinned with ``step_roofline.py`` adding the bytes up itself and
    ``run.py`` calling ``shapes.work`` (the parent of PR 27); now both go
    through the configuration's arithmetic. Equal to the last digit."""
    with open(os.path.join(HOME, "configs", "gpt2-large.json")) as f:
        config = json.load(f)
    positions = list(range(4, 68)) * 40 + list(range(20, 120)) * 30
    facts = {"config": config, "seconds": 51.0, "chips": 1, "trace": reduced,
             "peaks": stats.peaks_for("TPU v5 lite"), "batch_histogram": histogram,
             "work": family.arithmetic(config).work(config, positions)}
    assert run.read_layer_metric(HOME, "step_roofline", facts) == roofline
    assert run.read_layer_metric(HOME, "step_mfu", facts) == 0.0860172791081915


GPT2_LARGE_WEIGHTS = {shape: "weights" for shape in (
    "bf16[1280,3840]", "bf16[1280,1280]", "bf16[1280,5120]", "bf16[5120,1280]",
    "bf16[50257,1280]", "bf16[1280,50257]", "bf16[1024,1280]")}


@pytest.fixture(scope="module")
def batched():
    """``data/recorded_trace_scopes.json``: two rounds of gpt2-large.seq16's
    ``jit_batched_step`` in a row, recorded on the chip in PR 27 from the tree
    that was measured, in ``load_xplane``'s form with the names."""
    with open(os.path.join(HERE, "data", "recorded_trace_scopes.json")) as f:
        return trace_reduce.reduce(json.load(f), "jit_batched_step", GPT2_LARGE_WEIGHTS)


def test_recorded_scopes_add_up_to_the_step_programs_device_time(batched):
    assert batched["step_count"] == 2
    assert batched["step_device_ms"] == pytest.approx(10.1588, abs=1e-3)
    program_s = batched["step_count"] * batched["step_device_ms"] / 1e3
    scopes = {name: s for name, s, _, _ in batched["scopes"]}
    assert sum(scopes.values()) == pytest.approx(program_s, rel=0.01)
    # with nothing lost between the operations either: they fill the program
    assert 0 <= scopes["(no operation)"] < 0.01 * program_s
    assert all(s >= 0 for s in scopes.values())
    seconds = [row[1] for row in batched["scopes"]]
    assert seconds == sorted(seconds, reverse=True)


def test_recorded_scopes_find_what_the_program_names(batched):
    scopes = {name: (s, n, us) for name, s, n, us in batched["scopes"]}
    # every scope of ``client_tpu/models/decoder.py``'s step
    assert {"embed", "attn_qkv", "cache_update", "attention", "attn_proj", "mlp",
            "unembed"} <= set(scopes)
    program_s = batched["step_count"] * batched["step_device_ms"] / 1e3
    # attention over the 1,024 reserved positions of 16 slots is the largest
    assert batched["scopes"][0][0] == "attention"
    assert scopes["attention"][0] / program_s == pytest.approx(0.371, abs=0.005)
    # 36 layers, k and v, two rounds: each row's dynamic_update_slice and more
    assert scopes["cache_update"][1] >= 2 * 36 * 2
    # the compiler's own slices and copies, by what they move
    assert scopes["(copy of bf16[16,20,1024,64])"][0] / program_s == pytest.approx(
        0.212, abs=0.005)
    assert "(slice of weights)" in scopes and "(slice of bf16[16,20,1024,64])" in scopes
    # the row-write loop holds its body's operations: its own time is what
    # is left, not the 72 loops' whole length
    assert scopes["(while)"][1] == 72 and scopes["(while)"][2] < 10.0
    assert scopes["unembed"][2] == pytest.approx(44.6, abs=0.5)  # mean us


def test_recorded_scopes_without_the_weights_shapes_name_the_arrays(batched):
    with open(os.path.join(HERE, "data", "recorded_trace_scopes.json")) as f:
        bare = trace_reduce.reduce(json.load(f), "jit_batched_step")
    names = {row[0] for row in bare["scopes"]}
    assert "(slice of weights)" not in names and "(slice of bf16[1280,3840])" in names
    for key in ("busy_s", "window_s", "step_device_ms", "device_ops", "idle_gaps"):
        assert bare[key] == batched[key]


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


def test_hand_worked_scopes_of_the_step_program():
    trace = hand_trace(
        ops=[("fusion.1", 0, 40), ("slice-start.7", 40, 2), ("fusion.2", 50, 30),
             ("slice-done.7", 80, 18), ("copy.3", 100, 20), ("reshape.9", 120, 5),
             ("fusion.1", 300, 60),  # in another program: no scope of the step's
             ("fusion.1", 400, 40), ("while.2", 440, 100)],
        modules=[("jit_step(1)", 0, 150), ("jit_other(2)", 300, 60),
                 ("jit_step(1)", 400, 150)])
    trace["planes"][0]["op_names"] = {
        "fusion.1": "jit(step)/attn_qkv/dot_general:",
        "fusion.2": "jit(step)/mlp/jit(_gelu)/tanh:",
        "while.2": "jit(step)/cache_update/while:",
        "reshape.9": "jit(step)/reshape:"}  # in no named scope
    trace["planes"][0]["operands"] = {
        "slice-start.7": "bf16[8,4]", "slice-done.7": "bf16[8,4]",
        "copy.3": "bf16[2,16,4]", "reshape.9": "f32[4]"}
    r = trace_reduce.reduce(trace, "jit_step", {"bf16[8,4]": "weights"})
    assert {name: (pytest.approx(s), n, pytest.approx(us))
            for name, s, n, us in r["scopes"]} == {
        "cache_update": (100e-9, 1, 0.1), "attn_qkv": (80e-9, 2, 0.04),
        "mlp": (30e-9, 1, 0.03), "(slice of weights)": (20e-9, 2, 0.01),
        "(copy of bf16[2,16,4])": (20e-9, 1, 0.02), "(reshape)": (5e-9, 1, 0.005),
        "(no operation)": (45e-9, 2, 0.0225)}
    assert [row[0] for row in r["scopes"]][:2] == ["cache_update", "attn_qkv"]
    # the scopes and the time between operations are the program's time
    assert sum(row[1] for row in r["scopes"]) == pytest.approx(
        r["step_count"] * r["step_device_ms"] / 1e3)
    # a trace without the names (one recorded before they were read) still
    # reduces: every operation by its kind
    for plane in trace["planes"]:
        plane.pop("op_names", None), plane.pop("operands", None)
    bare = {row[0] for row in trace_reduce.reduce(trace, "jit_step")["scopes"]}
    assert bare == {"(fusion)", "(slice)", "(copy)", "(reshape)", "(while)",
                    "(no operation)"}
    assert trace_reduce.reduce(trace, "jit_none")["scopes"] == []


@pytest.mark.parametrize("op_name, scope", [
    ("jit(step)/attn_qkv/dot_general:", "attn_qkv"),
    ("jit(step)/attn_qkv/jit(_var)/reduce_sum:", "attn_qkv"),
    ("jit(batched_step)/cache_update/while/body/dynamic_update_slice:", "cache_update"),
    ("jit(step)/jit(_norm)/mlp/mul:", "mlp"),
    ("jit(step)/transpose(jvp(loss))/layer/add:", "layer"),
    ("jit(step)/broadcast_in_dim:", None),
    ("", None),
])
def test_the_scope_is_the_outermost_named_one(op_name, scope):
    assert trace_reduce.scope_of(op_name) == scope


@pytest.mark.parametrize("hlo, operand", [
    ("%slice-done.5 = bf16[5,1024,64]{1,2,0:T(8,128)(2,1)S(1)} async-done(((bf16[20,"
     "1024,64]{1,2,0:T(8,128)(2,1)}), bf16[5,1024,64]{1,2,0}, s32[]{:S(2)}) %slice-start.5)",
     "bf16[20,1024,64]"),
    ("%copy-done = bf16[1280,3840]{1,0:S(1)} copy-done((bf16[1280,3840]{1,0:S(1)}, "
     "bf16[1280,3840]{1,0}, u32[]{:S(2)}) %copy-start)", "bf16[1280,3840]"),
    ("%x = f32[] add(f32[] %a, f32[] %b)", "f32[]"),
    ("jit_step(7289971653275059145)", None),
])
def test_the_operand_is_the_largest_array_the_line_names(hlo, operand):
    assert trace_reduce.operand_of(hlo) == operand


def _message(*fields):
    """Protobuf wire format by hand: ``(number, int | bytes)`` fields."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += varint(number << 3) + varint(value)
        else:
            out += varint(number << 3 | 2) + varint(len(value)) + value
    return out


def test_the_tf_op_stat_is_read_from_the_events_metadata(tmp_path):
    stat_meta = lambda i, name: _message((1, i), (2, _message((1, i), (2, name))))
    event_meta = lambda i, name, *stats: _message(
        (1, i), (2, _message((1, i), (2, name), *[(5, s) for s in stats])))
    line = _message((2, b"XLA Ops"), (4, _message((1, 1), (2, 100), (3, 50))))
    device = _message(
        (1, 3), (2, b"/device:TPU:0"), (3, line),
        (4, event_meta(1, b"%fusion.1 = f32[4] fusion()",
                       _message((1, 300), (3, 7)),  # another stat, a number
                       _message((1, 200), (5, b"jit(step)/mlp/dot_general:")))),
        (4, event_meta(2, b"%fusion.2 = f32[4] fusion()",
                       _message((1, 200), (7, 201)))),  # by reference
        (4, event_meta(3, b"%slice-start.1 = f32[4] slice-start()")),  # none
        (5, stat_meta(200, b"tf_op")), (5, stat_meta(300, b"flops")),
        (5, stat_meta(201, b"jit(step)/attention/exp:")))
    host = _message((2, b"/host:CPU"), (4, event_meta(1, b"np.asarray")))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_message((1, device), (1, host), (4, b"hostname")))
    assert trace_reduce.op_stats(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = f32[4] fusion()": "jit(step)/mlp/dot_general:",
        "%fusion.2 = f32[4] fusion()": "jit(step)/attention/exp:",
        "%slice-start.1 = f32[4] slice-start()": None},
        "/host:CPU": {"np.asarray": None}}
    assert set(trace_reduce.op_stats(str(path), stat="no_such_stat")[
        "/device:TPU:0"].values()) == {None}


def test_load_xplane_keeps_the_scope_names_and_the_unnamed_operands(tmp_path):
    """A whole XSpace by hand, read by jax's ``ProfileData`` and by
    ``op_stats``, through ``load_xplane`` and ``reduce``."""
    meta = lambda i, name, *stats: _message(
        (1, i), (2, _message((1, i), (2, name), *[(5, s) for s in stats])))
    event = lambda i, at_ns, ns: _message((1, i), (2, at_ns * 1000), (3, ns * 1000))
    tf_op = lambda name: _message((1, 9), (5, name))
    device = _message(
        (1, 1), (2, b"/device:TPU:0"),
        (3, _message((1, 1), (2, b"XLA Modules"), (4, event(1, 0, 100)))),
        (3, _message((1, 2), (2, b"XLA Ops"), (4, event(2, 0, 40)),
                     (4, event(3, 40, 30)), (4, event(4, 70, 20)))),
        (4, meta(1, b"jit_step(42)")),
        (4, meta(2, b"%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p)",
                 tf_op(b"jit(step)/attention/dot_general:"))),
        (4, meta(3, b"%slice-done.2 = bf16[2,8]{1,0} async-done(((bf16[8,8]{1,0}), "
                    b"bf16[2,8]{1,0}, s32[]) %slice-start.2)")),
        (4, meta(4, b"%copy.5 = bf16[3,8]{1,0} copy(bf16[3,8]{0,1} %q)")),
        (5, _message((1, 9), (2, _message((1, 9), (2, b"tf_op"))))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_message((1, device)))
    trace = trace_reduce.load_xplane(str(path))
    plane = trace["planes"][0]
    assert plane["op_names"] == {"fusion.1": "jit(step)/attention/dot_general:"}
    assert plane["operands"] == {"slice-done.2": "bf16[8,8]", "copy.5": "bf16[3,8]"}
    json.loads(json.dumps(trace))
    reduced = trace_reduce.reduce(trace, "jit_step", {"bf16[8,8]": "weights"})
    assert [(name, round(s * 1e9)) for name, s, _, _ in reduced["scopes"]] == [
        ("attention", 40), ("(slice of weights)", 30), ("(copy of bf16[3,8])", 20),
        ("(no operation)", 10)]


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
