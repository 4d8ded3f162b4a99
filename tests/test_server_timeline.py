"""The request timeline inside the serving path (``server/timeline.py``): the
marks of a request through the core, the sequence batcher and the stream
path, the statistics verb filled from them, the one end-of-request recorder,
the host spans of a profiler session and the named scopes of the steps.

Counts and orderings only: no wall-clock threshold anywhere.
"""

import glob
import re
import threading

import numpy as np
import pytest

from client_tpu.models.batched import BatchedMatMulModel
from client_tpu.models.decoder_batched import (
    ROUNDS_IN_FLIGHT,
    BatchedDecoderModel,
)
from client_tpu.models.decoder import TinyDecoderModel
from client_tpu.models.generate import TinyGenerateModel
from client_tpu.models.simple import AddSubModel
from client_tpu.server import ServerCore, timeline
from tests.conftest import GatedStep

PARTS = ("compute_input", "queue", "compute_infer", "compute_output")
TRACEPARENT = "00-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01"


def _tokens(tokens, request_id="", **parameters):
    return {"id": request_id, "parameters": parameters, "inputs": [{
        "name": "TOKENS", "datatype": "INT32", "shape": [1, len(tokens)],
        "array": np.array([tokens], np.int32)}]}


def _session(core, seq, prompt, outputs):
    """One user of the sequence API: the prompt, then a request a token."""
    first = core.infer("decoder_lm_batched", "", _tokens(
        prompt, f"s{seq}-prompt", sequence_id=seq, sequence_start=True))
    token = int(first[0]["outputs"][1]["array"][0, 0])
    for i in range(outputs):
        reply = core.infer("decoder_lm_batched", "", _tokens(
            [token], f"s{seq}-{i}", sequence_id=seq,
            sequence_end=i == outputs - 1))
        token = int(reply[0]["outputs"][1]["array"][0, 0])


def _generate(core, prompt, max_tokens, **request):
    return list(core.infer_stream("tiny_lm_generate", "", dict(request, inputs=[
        {"name": "TOKENS", "datatype": "INT32", "shape": [1, len(prompt)],
         "array": np.array([prompt], np.int32)},
        {"name": "MAX_TOKENS", "datatype": "INT32", "shape": [1],
         "array": np.array([max_tokens], np.int32)}])))


def _add_sub(core):
    a = np.arange(16, dtype=np.int32).reshape(1, 16)
    return core.infer("simple", "", {"id": "plain", "inputs": [
        {"name": name, "datatype": "INT32", "shape": [1, 16], "array": a}
        for name in ("INPUT0", "INPUT1")]})


def _matmul(core, model):
    x = np.ones((1, model.IN_DIM), np.float32)
    return core.infer(model.name, "", {"inputs": [
        {"name": "X", "datatype": "FP32", "shape": [1, model.IN_DIM],
         "array": x}]})


def _concurrently(*calls):
    errors = []

    def run(call):
        try:
            call()
        except Exception as e:  # shown below
            errors.append(e)

    threads = [threading.Thread(target=run, args=(call,)) for call in calls]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    assert not any(t.is_alive() for t in threads)


@pytest.fixture
def traced_core():
    """Both language-model paths, a plain model and a dynamically batched
    one behind one core that records every request."""
    batched = BatchedDecoderModel(seed=0, slots=4)
    matmul = BatchedMatMulModel()
    core = ServerCore([batched, TinyGenerateModel(seed=0), AddSubModel(), matmul])
    core.trace_settings.update(trace_level=["TIMESTAMPS"], trace_rate="1")
    yield core, batched, matmul
    batched.unload()


def _drive_all(core, matmul):
    _concurrently(*[
        (lambda seq=seq: _session(core, seq, list(range(1, 2 + 3 * seq)), 4))
        for seq in (1, 2, 3)])
    _generate(core, [1, 2, 3], 5, id="stream")
    _add_sub(core)
    _concurrently(*[lambda: _matmul(core, matmul)] * 3)


def test_four_parts_add_up_to_success_for_every_model(traced_core):
    core, _, matmul = traced_core
    _drive_all(core, matmul)
    rows = core.statistics()["model_stats"]
    assert {row["name"] for row in rows} == {
        "decoder_lm_batched", "tiny_lm_generate", "simple", matmul.name}
    for row in rows:
        stats = row["inference_stats"]
        assert stats["success"]["count"] > 0 and stats["fail"]["count"] == 0
        assert sum(stats[part]["ns"] for part in PARTS) == stats["success"]["ns"]
        assert all(stats[part]["count"] == stats["success"]["count"]
                   for part in PARTS)
        assert all(stats[part]["ns"] >= 0 for part in PARTS)


def test_the_parts_say_what_each_path_has(traced_core):
    core, _, matmul = traced_core
    _drive_all(core, matmul)
    stats = {row["name"]: row["inference_stats"]
             for row in core.statistics()["model_stats"]}
    # the slot batcher: a real queue, over a drained run one count a request
    batched = stats["decoder_lm_batched"]
    assert batched["queue"]["ns"] > 0
    assert batched["queue"]["count"] == batched["success"]["count"] == 15
    # a stream has no queue and says so; the generator's time is less than
    # the stream's, the rest being the cache, the responses and their writes
    stream = stats["tiny_lm_generate"]
    assert stream["queue"] == {"count": 1, "ns": 0}
    assert 0 < stream["compute_infer"]["ns"] < stream["success"]["ns"]
    assert stream["compute_input"]["ns"] > 0 and stream["compute_output"]["ns"] > 0
    # a model that marks nothing: no queue, and execute is its compute_infer
    assert stats["simple"]["queue"] == {"count": 1, "ns": 0}
    assert stats["simple"]["compute_infer"]["ns"] > 0
    # the dynamic batcher marks its requests as the sequence batcher does
    assert stats[matmul.name]["queue"]["ns"] > 0


def test_marks_of_every_request_are_monotone(traced_core):
    core, _, matmul = traced_core
    _drive_all(core, matmul)
    orders = {
        "decoder_lm_batched": (
            "recv", "inputs_resolved", "model_enter", "enqueued", "collected",
            "first_dispatch", "last_dispatch", "on_host", "resolved",
            "model_exit", "done"),
        "tiny_lm_generate": (
            "recv", "inputs_resolved", "model_enter", "cache_ready",
            "prefill_done", "first_response", "model_exit", "done"),
        "simple": ("recv", "inputs_resolved", "model_enter", "model_exit", "done"),
        matmul.name: ("recv", "inputs_resolved", "model_enter", "enqueued",
                      "first_dispatch", "last_dispatch", "model_exit", "done"),
    }
    records = core.recent_traces(1000)
    assert {r["model_name"] for r in records} == set(orders)
    for record in records:
        stamps = record["timestamps"]
        order = orders[record["model_name"]]
        # with TIMESTAMPS a record has every mark of its path
        assert set(order) <= set(stamps), (record["model_name"], sorted(stamps))
        times = [stamps[name] for name in order]
        assert times == sorted(times), (record["model_name"], stamps)
        # Triton's own four stay, as aliases of the marks
        assert stamps["request_start_ns"] == stamps["recv"]
        assert stamps["request_end_ns"] == stamps["done"]
        anchor = record["clock_anchor"]
        assert anchor["wall_ns"] > 0 and anchor["perf_ns"] <= stamps["recv"]


def test_a_record_carries_the_identifiers_its_spans_share(traced_core):
    core, _, _ = traced_core
    core.infer("decoder_lm_batched", "", dict(
        _tokens([5, 6], "with-parent", sequence_id=77, sequence_start=True,
                sequence_end=True), traceparent=TRACEPARENT))
    record = core.recent_traces()[-1]
    assert record["request_id"] == "with-parent" and record["sequence_id"] == 77
    assert record["trace_id"] == TRACEPARENT.split("-")[1]
    assert record["client_span_id"] == TRACEPARENT.split("-")[2]
    counts = record["counts"]
    assert record["first_round_id"] == counts["first_round_id"] == 0
    assert counts["rounds_own"] == 2
    assert counts["rounds_waited"] == counts["rounds_held"] == 0
    assert counts["round_widths"] == [1, 1] and counts["responses"] == 1
    # the access record's queue is the timeline's queue, not recv -> execute
    access = core.access_records()[-1]
    stamps = record["timestamps"]
    assert access["queue_ns"] == stamps["first_dispatch"] - stamps["enqueued"]
    assert access["compute_ns"] == stamps["last_dispatch"] - stamps["first_dispatch"]
    assert access["total_ns"] == stamps["done"] - stamps["recv"]


def test_a_round_mate_of_a_prompt_is_answered_after_its_own_round():
    """A single-token request that arrives with a longer prompt shares the
    prompt's first round and is answered after it, while the prompt still
    has rounds to go: nobody is held for another's rounds."""
    model = BatchedDecoderModel(seed=0, slots=2)
    core = ServerCore([model])
    core.trace_settings.update(trace_level=["TIMESTAMPS"], trace_rate="1")
    gate = GatedStep(model)
    try:
        # a round held at the gate, so that both requests are on the queue
        # when the next one is made up
        held = threading.Thread(target=core.infer, args=(
            "decoder_lm_batched", "", _tokens(
                [9], "held", sequence_id=9, sequence_start=True,
                sequence_end=True)))
        held.start()
        gate.at(0)
        mates = threading.Thread(target=_concurrently, args=(
            lambda: core.infer("decoder_lm_batched", "", _tokens(
                [7], "single", sequence_id=1, sequence_start=True)),
            lambda: core.infer("decoder_lm_batched", "", _tokens(
                [1, 2, 3, 4, 5], "prompt", sequence_id=2, sequence_start=True))))
        mates.start()
        gate.queued(2)
        gate.let(6)
        for thread in (held, mates):
            thread.join(timeout=120)
            assert not thread.is_alive()
    finally:
        gate.let(100)
        model.unload()
    records = {r["request_id"]: r for r in core.recent_traces()}
    single, prompt = records["single"], records["prompt"]
    assert single["counts"]["rounds_own"] == 1
    assert single["counts"]["rounds_held"] <= ROUNDS_IN_FLIGHT
    assert single["counts"]["rounds_waited"] <= ROUNDS_IN_FLIGHT
    assert single["counts"]["round_widths"] == [2]
    assert prompt["counts"]["rounds_own"] == 5
    assert prompt["counts"]["rounds_held"] == 0
    assert prompt["counts"]["round_widths"] == [2, 1, 1, 1, 1]
    assert single["first_round_id"] == prompt["first_round_id"] == 1
    # answered after its own round, before the prompt's last was dispatched
    assert (single["timestamps"]["last_dispatch"]
            <= single["timestamps"]["on_host"]
            <= single["timestamps"]["resolved"]
            < prompt["timestamps"]["last_dispatch"])


def test_batch_stats_rounds_equal_the_batch_histogram(traced_core):
    core, batched, matmul = traced_core
    _drive_all(core, matmul)
    row = core.statistics("decoder_lm_batched")["model_stats"][0]
    rounds = {r["batch_size"]: r["compute_infer"]["count"]
              for r in row["batch_stats"]}
    assert rounds == batched.batch_histogram and sum(rounds.values()) > 0
    assert all(r["compute_infer"]["ns"] > 0 for r in row["batch_stats"])
    # an execution is a round, a request an inference
    assert row["execution_count"] == sum(rounds.values())
    assert row["inference_count"] == 15


@pytest.mark.parametrize("path, prompt, max_tokens, chunk, want", [
    # on rounds every token is handed from the worker to the stream's thread
    ("rounds", [1, 2, 3], 6, 1,
     {"dispatch": 5, "readback": 6, "handoff": 6, "yielded": 6}),
    ("rounds", [4], 1, 1,
     {"dispatch": 0, "readback": 1, "handoff": 1, "yielded": 1}),
    # a burst's tokens rode a round each
    ("rounds", [1, 2], 7, 3,
     {"dispatch": 6, "readback": 7, "handoff": 7, "yielded": 7}),
    ("alone", [1, 2, 3], 6, 1,
     {"dispatch": 5, "readback": 6, "handoff": 0, "yielded": 6}),
    ("alone", [4], 1, 1,
     {"dispatch": 0, "readback": 1, "handoff": 0, "yielded": 1}),
    # ``decode_k``: a dispatch and a read-back a burst
    ("alone", [1, 2], 7, 3,
     {"dispatch": 2, "readback": 3, "handoff": 0, "yielded": 7}),
], ids=["a-token-a-round", "one-token-a-round", "chunked-on-rounds",
        "a-token-a-dispatch", "one-token", "chunked"])
def test_a_streams_interval_counts_follow_its_tokens(
        path, prompt, max_tokens, chunk, want):
    decoder = TinyDecoderModel(seed=0)
    decoder._ensure_built()
    if path == "alone":  # as a decoder that offers no round program
        decoder._round_fn = None
    model = TinyGenerateModel(decoder=decoder)
    core = ServerCore([model])
    core.trace_settings.update(trace_level=["TIMESTAMPS"], trace_rate="1")
    try:
        responses = _generate(core, prompt, max_tokens,
                              parameters={"chunk": chunk})
    finally:
        model.unload()
    assert len(responses) == max_tokens
    record = core.recent_traces()[-1]
    counts = record["counts"]
    assert counts["responses"] == max_tokens
    # a stream on rounds shares an identifier with the round that gave its
    # first token (the model's first stream: a prompt's rounds come before)
    first_round = len(prompt) - 1 if path == "rounds" else None
    assert record["first_round_id"] == counts["first_round_id"] == first_round
    for name, count in want.items():
        interval = counts[name]
        assert interval["count"] == count, name
        assert (interval["ns"] > 0) == (count > 0), name


def test_phases_add_up_and_tile_the_time_between_them():
    phases = timeline.Phases()
    assert phases.rows() == {}
    # (phase, start, end): the first has nothing before it; 3 ns lie between
    # the first and the second, none between the second and the third
    for phase, start, end in (("dispatch", 100, 105), ("readback", 108, 115),
                              ("dispatch", 115, 126)):
        phases.add(phase, start, end)
    assert phases.rows() == {"dispatch": (2, 16), "readback": (1, 7),
                             "between": (2, 3)}
    # the rows add up to the time from the first phase to the end of the last
    assert sum(ns for _, ns in phases.rows().values()) == 126 - 100
    rows = phases.rows()  # a copy: the worker's next turn does not move it
    phases.add("dispatch", 130, 131)
    assert rows["dispatch"] == (2, 16) and phases.rows()["dispatch"] == (3, 17)
    assert phases.rows()["between"] == (3, 7)


@pytest.mark.parametrize("name, phase", [
    (timeline.SPAN_ROUND_PREPARE, "prepare"),
    (timeline.SPAN_ROUND_DISPATCH, "dispatch"),
    (timeline.SPAN_BATCH_WAIT_WORK, "wait_work"),
    (timeline.SPAN_BATCH_READBACK, "readback"),
    (timeline.SPAN_STREAM_ADMIT, "admit"),
    (timeline.SPAN_PREFILL_CHUNK, "prefill_chunk"),
    (timeline.SPAN_HAND_OUT, "hand_out"),
    (timeline.SPAN_DEVICE_WAIT, "device_wait"),
])
def test_a_span_into_phases_feeds_it_from_its_own_edges(name, phase):
    """The annotation, the marks' edges and the counter are one pair of clock
    readings: what lands in the phases is ``end_ns - start_ns``, exactly, and
    what lies between two spans is the second's start less the first's end."""
    assert phase in timeline.PHASES and name in timeline.SPAN_NAMES
    phases = timeline.Phases()
    with timeline.span(name, into=phases) as first:
        pass
    assert phases.rows() == {phase: (1, first.end_ns - first.start_ns)}
    with timeline.span(name, into=phases) as second:
        sum(range(100))
    assert first.start_ns <= first.end_ns <= second.start_ns <= second.end_ns
    assert phases.rows() == {phase: (2, first.ns + second.ns),
                             "between": (1, second.start_ns - first.end_ns)}


def test_a_span_without_into_is_the_two_clock_readings_alone():
    phases = timeline.Phases()
    with timeline.span(timeline.SPAN_ROUND_DISPATCH) as s:
        pass
    assert s.ns == s.end_ns - s.start_ns >= 0
    assert phases.rows() == {}


def test_the_vocabulary_is_what_the_spans_are_keyed_to():
    """One dict keys each span that feeds a phase to it: ``PHASES`` is its
    values and ``between``, each engine's names stand for the phases that
    engine has, and a span of any other name cannot feed a ``Phases``."""
    assert set(timeline.PHASE_OF) <= set(timeline.SPAN_NAMES)
    assert timeline.PHASES == tuple(dict.fromkeys(timeline.PHASE_OF.values())) + (
        "between",)
    by_engine = {}
    for name, phase in timeline.PHASE_OF.items():
        engine = name.split(".")[1]
        assert phase not in by_engine.setdefault(engine, set()), name
        by_engine[engine].add(phase)
    assert by_engine["batcher"] == set(timeline.PHASES) - {"prefill_chunk", "between"}
    assert by_engine["generate"] == set(timeline.PHASES) - {"collect", "between"}
    phases = timeline.Phases()
    for name in set(timeline.SPAN_NAMES) - set(timeline.PHASE_OF):
        with pytest.raises(KeyError):
            timeline.span(name, into=phases)
        with timeline.span(name):  # without ``into`` it is a span as before
            pass
    with pytest.raises(KeyError):
        timeline.span("client_tpu.generate.round_x", into=phases)
    assert phases.rows() == {}


def _series(core, model):
    """The registry's series labelled with ``model``, as the benchmark's
    serving process reads them: ``name{other labels}`` -> value."""
    out = {}
    for name, metric in core.metrics_registry().snapshot().items():
        for series in metric["series"]:
            labels = dict(series["labels"])
            if labels.pop("model", None) == model and "value" in series:
                out[name + "".join(f"{{{k}={v}}}" for k, v in sorted(
                    labels.items()))] = series["value"]
    return out


def test_the_registry_has_the_phases_and_the_readings_by_model(traced_core):
    core, batched, matmul = traced_core
    _drive_all(core, matmul)
    _generate(core, [1, 2], 3)  # a second stream: the worker's wait ended
    first = {m: _series(core, m) for m in
             ("decoder_lm_batched", "tiny_lm_generate", "simple", matmul.name)}
    phase = lambda kind, name: f"client_tpu_server_round_phase_{kind}{{phase={name}}}"
    engines = {
        "decoder_lm_batched": (
            ("wait_work", "collect", "admit", "prepare", "dispatch", "record",
             "hand_out", "device_wait", "readback", "between"),
            ("sequence_stride_rounds", "answer_wake_ns")),
        "tiny_lm_generate": (
            ("wait_work", "admit", "prepare", "dispatch", "record", "hand_out",
             "device_wait", "readback", "between"),
            ("first_response_ns", "token_handoff_ns")),
    }
    for model, (phases, readings) in engines.items():
        series = first[model]
        got = {key[key.index("=") + 1:-1] for key in series
               if key.startswith("client_tpu_server_round_phase_ns")}
        assert got == set(phases), (model, got)
        for name in phases:
            assert series[phase("count", name)] > 0 and series[phase("ns", name)] > 0
        for name in readings:
            count = name.rsplit("_", 1)[0] + "_count"
            assert series["client_tpu_server_" + name] > 0
            assert series["client_tpu_server_" + count] > 0
    # 12 continuation requests of three sequences, 15 answers; 2 streams of
    # 5 and 3 tokens
    batcher, streams = first["decoder_lm_batched"], first["tiny_lm_generate"]
    assert batcher["client_tpu_server_sequence_stride_count"] == 12
    assert batcher["client_tpu_server_sequence_stride_rounds"] >= 12
    assert batcher["client_tpu_server_answer_wake_count"] == 15
    assert streams["client_tpu_server_first_response_count"] == 2
    assert streams["client_tpu_server_token_handoff_count"] == 8
    assert batcher[phase("count", "dispatch")] == sum(
        batched.batch_histogram.values())
    # a model without a round worker has none of them, and a stream no
    # batcher's reading
    new = ("round_phase", "sequence_stride", "answer_wake", "first_response",
           "token_handoff")
    for model in ("simple", matmul.name):
        assert not [k for k in first[model] if any(n in k for n in new)]
    assert "client_tpu_server_answer_wake_ns" not in streams
    assert "client_tpu_server_token_handoff_ns" not in batcher
    # cumulative: more traffic moves every series up and none down
    _session(core, 9, [1, 2, 3], 2)
    _generate(core, [3], 2)
    _generate(core, [3], 1)
    for model in engines:
        second = _series(core, model)
        moved = {k: second[k] - v for k, v in first[model].items()
                 if k.startswith(("client_tpu_server_round_phase",
                                  "client_tpu_server_sequence_stride",
                                  "client_tpu_server_answer_wake",
                                  "client_tpu_server_first_response",
                                  "client_tpu_server_token_handoff"))}
        assert moved and all(d > 0 for d in moved.values()), (model, moved)


def test_with_trace_level_off_no_record_is_built():
    model = BatchedDecoderModel(seed=0, slots=2)
    core = ServerCore([model, TinyGenerateModel(seed=0)])
    assert core.trace_settings["trace_level"] == ["OFF"]
    try:
        _session(core, 1, [1, 2], 2)
        _generate(core, [1, 2, 3], 3, traceparent=TRACEPARENT)
    finally:
        model.unload()
    assert core.recent_traces() == []
    # statistics and the traceparent join do not wait for a trace setting
    assert core.statistics("decoder_lm_batched")["model_stats"][0][
        "inference_stats"]["success"]["count"] == 3
    access = core.access_records()
    assert len(access) == 1 and access[0]["responses"] == 3
    assert access[0]["queue_ns"] == 0 < access[0]["first_response_ns"]


def test_a_model_finds_no_timeline_outside_a_request():
    """The timeline is current only while the core runs the model's code."""
    assert timeline.current() is None
    model = BatchedDecoderModel(seed=0, slots=2)
    try:
        out = model.execute({"TOKENS": np.array([[3, 4]], np.int32)}, {
            "sequence_id": 9, "sequence_start": True, "sequence_end": True})
    finally:
        model.unload()
    assert out["NEXT_TOKEN"].shape == (1, 1)
    core = ServerCore([TinyGenerateModel(seed=0)])
    stream = core.infer_stream("tiny_lm_generate", "", {"inputs": [
        {"name": "TOKENS", "datatype": "INT32", "shape": [1, 2],
         "array": np.array([[1, 2]], np.int32)}]})
    next(stream)
    assert timeline.current() is None  # not while the stream is suspended
    stream.close()
    cancelled = core.statistics()["model_stats"][0]["inference_stats"]["cancel"]
    assert cancelled["count"] == 1


def test_a_profiler_session_holds_every_span_name(tmp_path, traced_core):
    import jax
    from jax.profiler import ProfileData

    from client_tpu.models.routed_decoder import RoutedDecoderModel

    core, _, matmul = traced_core
    _session(core, 50, [1, 2], 1)  # built and compiled before the session
    _generate(core, [1], 1)
    # the one decoder with a prefill program, whose dispatches take
    # ``prefill_chunk`` (the GPT-2 decoder's prompt is the step a token)
    routed = RoutedDecoderModel({
        "hidden_size": 32, "num_hidden_layers": 1, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 16, "moe_intermediate_size": 16,
        "num_experts": 4, "num_experts_per_tok": 2, "rms_norm_eps": 1e-6,
        "rope_theta": 1e7, "vocab_size": 64, "max_position_embeddings": 64,
        "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                      "q_chunk_size": 4, "kv_chunk_size": 4, "topk": 8}}, seed=0)
    routed._ensure_built()
    routed._ensure_warm()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _drive_all(core, matmul)
        # a second stream inside the session: the rounds' worker has waited
        # for it from the first one's end, a ``wait_work`` with both edges here
        _generate(core, [1], 1)
        routed.prefill(routed._fresh_cache(), [1, 2, 3], 0)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    assert len(found) == 1
    host = {event.name
            for plane in ProfileData.from_file(found[0]).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for event in line.events}
    assert set(timeline.SPAN_NAMES) <= host, set(timeline.SPAN_NAMES) - host


def test_compiles_are_counted_by_the_program():
    import jax
    import jax.numpy as jnp

    core = ServerCore([AddSubModel()])
    x = jnp.arange(7)  # made, and its program compiled, before the count
    before = (timeline.COMPILES.count, timeline.COMPILES.ns)
    jax.jit(lambda x: x * 3 + before[0])(x).block_until_ready()
    assert timeline.COMPILES.count == before[0] + 1
    assert timeline.COMPILES.ns > before[1]
    text = core.metrics_registry().prometheus_text()
    assert f"client_tpu_server_compile_count {timeline.COMPILES.count}" in text
    assert "client_tpu_server_compile_seconds" in text
    # one listener a process, however many cores
    ServerCore([])
    jax.jit(lambda x: x * 5 - before[0])(x).block_until_ready()
    assert timeline.COMPILES.count == before[0] + 2


def test_a_request_that_compiled_says_so():
    core = ServerCore([TinyGenerateModel(seed=3)])
    core.trace_settings.update(trace_level=["TIMESTAMPS"], trace_rate="1")
    _generate(core, [1, 2], 2)  # builds the decoder and compiles its step
    _generate(core, [1, 2], 2)
    first, second = core.recent_traces()
    assert first["counts"]["compiled_ns"] > 0
    assert second["counts"]["compiled_ns"] == 0


STEP_SCOPES = ("embed", "attn_qkv", "cache_update", "attention", "attn_proj",
               "mlp", "unembed")


def test_the_steps_keep_their_jit_names_and_carry_their_scopes():
    """``step_device_ms`` finds the programs as ``jit_step`` and
    ``jit_batched_step``; device time reads by the scopes."""
    import jax.numpy as jnp

    model = BatchedDecoderModel(seed=0, slots=2)
    generate = TinyGenerateModel(decoder=model._decoder)
    try:
        model._ensure_built()
        decoder = model._decoder
        step = decoder._step_fn.lower(
            decoder._params, decoder._fresh_cache(), 0, 0)
        row = lambda dtype: jnp.zeros((2,), dtype)
        batched = model._batched_step.lower(
            decoder._params, model._caches, row(jnp.int32), row(jnp.int32),
            row(jnp.bool_))
        chunk = generate._chunk_fn(2).lower(
            decoder._params, decoder._fresh_cache(), 0, 0)
    finally:
        model.unload()
    for lowered, name, scopes in (
            (step, "jit_step", STEP_SCOPES),
            # the rows of the active slots are written under ``cache_update``
            (batched, "jit_batched_step", STEP_SCOPES),
            (chunk, "jit_decode_k", STEP_SCOPES + ("greedy_argmax",))):
        text = lowered.as_text(debug_info=True)
        assert f"module @{name} " in text
        for scope in scopes:  # at the head of an operation's name, or inside it
            assert re.search(rf'["/]{scope}["/]', text), (name, scope)
