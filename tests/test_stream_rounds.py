"""The streams of one model share a round (models/stream_rounds.py).

Tokens, counts and the order of rounds; no clock. A ``Gate`` in the place of
the rounds' one dispatch call lets a test hold the worker before a dispatch,
release the rounds one at a time and read what each round was given.
"""

import threading

import numpy as np
import pytest

from client_tpu.models.decoder import (
    LANES,
    TinyDecoderModel,
    in_whole_turns,
    slots_a_turn,
)
from client_tpu.models.decoder_tp import TPDecoderModel
from client_tpu.models.generate import TinyGenerateModel
from client_tpu.models.stream_rounds import ROUNDS_IN_FLIGHT
from client_tpu.server import ServerCore, timeline
from tests.conftest import (
    check_the_phases_tile_the_workers_time,
    spans_into_phases,
)

# the fixture's heads (32 wide: narrower than the chip's lanes, so a round
# reads every slot) and heads as wide as the lanes (a round reads the
# occupied slots in whole turns), by head width
HEAD_SIZES = {32: {}, 128: {"D_MODEL": 256, "HEADS": 2}}
# at 1,024 positions: two rungs
LONG_SIZES = {32: {"D_MODEL": 64, "HEADS": 2, "LAYERS": 2, "MAX_LEN": 1024},
              128: {"D_MODEL": 256, "HEADS": 2, "LAYERS": 2, "MAX_LEN": 1024}}


def _decoder(head, sizes=HEAD_SIZES):
    return type(f"Heads{head}", (TinyDecoderModel,), sizes[head])(seed=0)


def _width(head, slots, occupied):
    """The slots a round reads whose highest occupied slot is
    ``occupied - 1``."""
    return slots if head < LANES else in_whole_turns(slots, occupied)

RNG = np.random.default_rng(33)
PROMPTS = [[int(t) for t in RNG.integers(0, 256, n)]
           for n in (3, 1, 7, 4, 2, 9, 5, 6)]
BUDGET = 9


def _inputs(prompt, max_tokens, end_id=None):
    inputs = {"TOKENS": np.array([prompt], np.int32),
              "MAX_TOKENS": np.array([max_tokens], np.int32)}
    if end_id is not None:
        inputs["END_ID"] = np.array([end_id], np.int32)
    return inputs


def _tokens(model, prompt, max_tokens=BUDGET, end_id=None, **parameters):
    out = list(model.execute_decoupled(_inputs(prompt, max_tokens, end_id),
                                       parameters))
    assert [int(r["INDEX"][0, 0]) for r in out] == list(range(len(out)))
    return [int(r["NEXT_TOKEN"][0, 0]) for r in out]


def _without_round(decoder):
    """The decoder as one that offers no round program (a subclass that jits
    a step of its own): its streams step their own sequences."""
    decoder._ensure_built()
    decoder._round_fn = None
    return decoder


@pytest.fixture(scope="module")
def alone():
    """What each prompt's stream gives on the per-stream path, a stream at a
    time: ``alone(prompt, max_tokens, head=32)``, on the fixture's decoder or
    one of ``HEAD_SIZES``."""
    models, known = {}, {}

    def tokens(prompt, max_tokens=BUDGET, head=32):
        key = (tuple(prompt), max_tokens, head)
        if key not in known:
            if head not in models:
                models[head] = TinyGenerateModel(
                    decoder=_without_round(_decoder(head)))
            known[key] = _tokens(models[head], prompt, max_tokens)
        return known[key]

    return tokens


@pytest.fixture
def served():
    """``served(slots)``: a stream model on rounds, built; unloaded after."""
    models = []

    def make(slots, decoder=None):
        model = TinyGenerateModel(
            decoder=decoder or TinyDecoderModel(seed=0), slots=slots)
        model._ensure_built()
        assert model._rounds is not None
        models.append(model)
        return model

    yield make
    for model in models:
        model.unload()


class Gate:
    """In the place of the rounds' ``_step``: every dispatch waits for a
    permit (unless ``free``) and is recorded with what the host gave it."""

    def __init__(self, model, free=False):
        self.step = model._rounds._step
        self.free = free
        self.permits = threading.Semaphore(0)
        self.calls = []  # (ctl, live)
        self.reached = 0  # dispatches that came to the gate
        self.dispatched = threading.Condition()
        model._rounds._step = self

    def __call__(self, ctl, live):
        with self.dispatched:
            self.reached += 1
            self.dispatched.notify_all()
        if not self.free:
            assert self.permits.acquire(timeout=120), "no permit for the round"
        self.step(ctl, live)
        with self.dispatched:
            self.calls.append((ctl.copy(), live))
            self.dispatched.notify_all()

    def let(self, rounds):
        """``rounds`` more rounds, and wait until they are dispatched."""
        want = len(self.calls) + rounds
        for _ in range(rounds):
            self.permits.release()
        with self.dispatched:
            assert self.dispatched.wait_for(
                lambda: len(self.calls) >= want, timeout=120)

    def held(self, n):
        """Wait until the worker stands at the gate with its ``n``-th
        dispatch: that turn's admission is behind it."""
        with self.dispatched:
            assert self.dispatched.wait_for(
                lambda: self.reached >= n, timeout=120)

    def open(self):
        self.free = True
        for _ in range(64):
            self.permits.release()

    def shut(self):
        """Closed again, as new: no permit left, nothing recorded."""
        self.free = False
        while self.permits.acquire(blocking=False):
            pass
        self.calls.clear()
        self.reached = 0


def _arrived(rounds, n):
    """Wait until ``n`` streams lie on the worker's queue."""
    arrivals = rounds._arrivals
    waited = threading.Event()
    for _ in range(12000):
        if arrivals.qsize() >= n:
            return
        waited.wait(0.01)
    raise AssertionError(f"{arrivals.qsize()} of {n} streams arrived")


def _concurrently(model, prompts, max_tokens=BUDGET):
    out, errors = {}, []

    def user(i, prompt):
        try:
            out[i] = _tokens(model, prompt, max_tokens)
        except Exception as e:  # shown below
            errors.append(e)

    users = [threading.Thread(target=user, args=(i, p))
             for i, p in enumerate(prompts)]
    for u in users:
        u.start()
    return users, out, errors


def _joined(users, errors):
    for u in users:
        u.join(timeout=120)
    assert not errors, errors
    assert not any(u.is_alive() for u in users)


# -- same tokens --------------------------------------------------------------


@pytest.mark.parametrize("streams", [1, 3, 8])
def test_concurrent_streams_give_what_each_gives_alone(served, alone, streams):
    model = served(8)
    users, out, errors = _concurrently(model, PROMPTS[:streams])
    _joined(users, errors)
    for i, prompt in enumerate(PROMPTS[:streams]):
        assert out[i] == alone(prompt), i
    assert sum(n * rounds for n, rounds in model.batch_histogram.items()) == sum(
        len(p) + BUDGET - 1 for p in PROMPTS[:streams])


def test_a_stream_gives_what_the_sequence_api_gives(served):
    """``decoder_lm`` over the same weights, a token a request, greedy."""
    decoder = TinyDecoderModel(seed=0)
    model = served(4, decoder)
    prompt = PROMPTS[2]
    want, tokens = [], prompt
    for i in range(BUDGET):
        reply = decoder.execute(
            {"TOKENS": np.array([tokens], np.int32)},
            {"sequence_id": 7, "sequence_start": i == 0,
             "sequence_end": i == BUDGET - 1})
        want.append(int(reply["NEXT_TOKEN"][0, 0]))
        tokens = [want[-1]]
    assert _tokens(model, prompt) == want


@pytest.mark.parametrize("chunk", [2, 4, 50])
def test_a_chunked_stream_rides_the_rounds_and_arrives_in_bursts(
        served, alone, chunk):
    model = served(4)
    gate = Gate(model, free=True)
    assert _tokens(model, PROMPTS[0], chunk=chunk) == alone(PROMPTS[0])
    assert len(gate.calls) == len(PROMPTS[0]) + BUDGET - 1  # a round a token
    assert not model._chunk_fns  # no ``decode_k`` on this path


def test_a_stream_admitted_midway_joins_the_next_round(served, alone):
    model = served(4)
    gate = Gate(model)
    first, second = PROMPTS[2], PROMPTS[3]
    users, out, errors = _concurrently(model, [first])
    gate.let(4)
    gate.held(5)  # the fifth round's admission is over: it comes too late
    later, out_later, errors_later = _concurrently(model, [second])
    _arrived(model._rounds, 1)
    gate.open()
    _joined(users + later, errors + errors_later)
    assert out[0] == alone(first) and out_later[0] == alone(second)
    for n, (ctl, _) in enumerate(gate.calls[:5]):
        assert list(ctl[2]) == [1, 0, 0, 0] and ctl[1, 0] == n
    ctl = gate.calls[5][0]
    assert list(ctl[2]) == [1, 1, 0, 0]  # the lowest free slot
    assert (ctl[0, 1], ctl[1, 1]) == (second[0], 0)  # its first prompt token
    assert (ctl[0, 0], ctl[1, 0]) == (first[5], 5)  # the other's own next
    # once a stream decodes, the host supplies no token of its: fed back
    decoding = gate.calls[len(first)][0]
    assert decoding[0, 0] == -1 and decoding[2, 0] == 1


# -- the slot is given back ---------------------------------------------------


@pytest.mark.parametrize("ending", ["budget", "end_id", "closed", "cancel"])
def test_every_ending_frees_the_slot_for_a_stream_that_reads_no_stale_row(
        served, alone, ending):
    """One slot: the second stream is served at all only if the first gave
    its slot back, and gives what it gives alone only if it reads none of
    the rows the first left there."""
    model = served(1)
    first, second = PROMPTS[5], PROMPTS[0]
    want = alone(first, 100)
    if ending == "budget":
        assert _tokens(model, first, 5) == want[:5]
    elif ending == "end_id":
        end_id = want[3]
        assert _tokens(model, first, 100, end_id=end_id) == want[:want.index(end_id) + 1]
    elif ending == "closed":
        stream = model.execute_decoupled(_inputs(first, 100), {})
        assert int(next(stream)["NEXT_TOKEN"][0, 0]) == want[0]
        stream.close()
    else:
        core = ServerCore([model])
        stream = core.infer_stream("tiny_lm_generate", "", {"inputs": [
            {"name": name, "datatype": "INT32", "shape": list(array.shape),
             "array": array} for name, array in _inputs(first, 100).items()]})
        next(stream), next(stream)
        stream.close()  # what a frontend does when its client cancels
        assert core.statistics()["model_stats"][0]["inference_stats"][
            "cancel"]["count"] == 1
    assert _tokens(model, second) == alone(second)
    assert _tokens(model, first, 5) == want[:5]
    rounds = model._rounds
    assert not rounds._members and rounds._free == [0]


def test_a_stream_that_ends_by_its_end_id_is_found_a_round_late(served, alone):
    """The rounds in flight behind the one that chose the ``END_ID`` carried
    the stream once more each: their rows lie in a freed slot, their tokens
    are dropped."""
    model = served(2)
    gate = Gate(model, free=True)
    prompt = PROMPTS[0]
    want = alone(prompt, 50)
    got = _tokens(model, prompt, 50, end_id=want[2])
    assert got == want[:want.index(want[2]) + 1]
    needed = len(prompt) + len(got) - 1
    assert needed <= len(gate.calls) <= needed + ROUNDS_IN_FLIGHT - 1


@pytest.mark.parametrize("head", HEAD_SIZES)
def test_more_streams_than_slots_all_finish(served, alone, head):
    model = served(2, _decoder(head))
    gate = Gate(model)
    users, out, errors = _concurrently(model, PROMPTS[:1])
    gate.held(1)
    # the first is held at its dispatch; the others lie on the queue and
    # are taken together: one seated, four wait
    others, out_others, errors_others = _concurrently(model, PROMPTS[1:6])
    _arrived(model._rounds, 5)
    gate.open()
    _joined(users + others, errors + errors_others)
    out.update({i + 1: tokens for i, tokens in out_others.items()})
    for i, prompt in enumerate(PROMPTS[:6]):
        assert out[i] == alone(prompt, head=head), i
    assert model.slot_waits == 4
    assert max(model.batch_histogram) == 2
    assert set(model.rounds_by_width) == {2}


@pytest.mark.parametrize("head", HEAD_SIZES)
def test_the_lowest_free_slot_keeps_the_round_narrow(served, head):
    """Eight slots, four a turn of the attention: three streams sit in slots
    0-2 and their rounds read four slots' caches; a fifth stream makes the
    rounds read eight. (Where heads are narrower than the lanes every round
    reads all eight.)"""
    model = served(8, _decoder(head))
    gate = Gate(model, free=True)
    users, out, errors = _concurrently(model, PROMPTS[:3], 4)
    _joined(users, errors)
    assert set(model.rounds_by_width) == {_width(head, 8, 3)}
    gate.shut()
    users, out, errors = _concurrently(model, PROMPTS[:1], 4)
    gate.held(1)
    others, _, errors_others = _concurrently(model, PROMPTS[1:5], 4)
    _arrived(model._rounds, 4)
    gate.open()
    _joined(users + others, errors + errors_others)
    assert [int(ctl[2].sum()) for ctl, _ in gate.calls[:2]] == [1, 5]
    assert list(gate.calls[1][0][2]) == [1, 1, 1, 1, 1, 0, 0, 0]
    assert set(model.rounds_by_width) == {_width(head, 8, 3), _width(head, 8, 5)}


@pytest.mark.parametrize("head", HEAD_SIZES)
@pytest.mark.parametrize("slots,a_turn", [(16, 4), (8, 4), (6, 2), (3, 1), (1, 1)])
def test_the_attention_takes_the_occupied_slots_in_whole_turns(
        served, alone, slots, a_turn, head):
    """Whatever the table's size the streams get their own tokens, and a
    round counts the slots its attention read: the occupied ones, rounded up
    to the turn, where heads fill the lanes; every slot where they are
    narrower."""
    model = served(slots, _decoder(head))
    assert slots_a_turn(slots) == a_turn
    users, out, errors = _concurrently(model, PROMPTS[:3])
    _joined(users, errors)
    for i, prompt in enumerate(PROMPTS[:3]):
        assert out[i] == alone(prompt, head=head), i
    assert all(width % a_turn == 0 and width <= slots
               for width in model.rounds_by_width)
    assert max(model.rounds_by_width) == (
        slots if head < LANES else min(slots, -(-3 // a_turn) * a_turn))


# -- nothing compiles once it serves ------------------------------------------


@pytest.mark.parametrize("head", LONG_SIZES)
def test_every_rung_is_compiled_before_the_first_round(served, head):
    timeline.COMPILES.listen()
    model = served(8, _decoder(head, LONG_SIZES))
    decoder = model._decoder
    assert decoder._rungs == (256, 1024)
    assert not decoder._warm  # no single-sequence rung was built for it
    before = timeline.COMPILES.count
    gate = Gate(model, free=True)
    long = [int(t) for t in RNG.integers(0, 256, 250)]
    users, out, errors = _concurrently(model, [long] + PROMPTS[:5], 12)
    _joined(users, errors)
    assert timeline.COMPILES.count == before
    assert {live for _, live in gate.calls} == {256, 1024}
    assert 8 in model.rounds_by_width and set(model.rounds_by_width) <= {
        _width(head, 8, occupied) for occupied in range(1, 9)}
    assert model.steps_by_rung.by_rung()[1024] == 5  # positions 256 to 260
    assert sum(model.steps_by_rung.by_rung().values()) == len(gate.calls)
    # (at these positions a single sequence's step rounds a near tie the
    # other way on the CPU, as it does against the slot batcher: the stream
    # is held to what it gives with no other stream beside it)
    assert out[0] == _tokens(model, long, 12)
    assert timeline.COMPILES.count == before


# -- the decoder says whether it has a round ----------------------------------


def _routed():
    from client_tpu.models.routed_decoder import RoutedDecoderModel

    return RoutedDecoderModel({
        "hidden_size": 32, "num_hidden_layers": 1, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 16, "moe_intermediate_size": 16,
        "num_experts": 4, "num_experts_per_tok": 2, "rms_norm_eps": 1e-6,
        "rope_theta": 1e7, "vocab_size": 64, "max_position_embeddings": 64,
        "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                      "q_chunk_size": 4, "kv_chunk_size": 4, "topk": 8}}, seed=0)


@pytest.mark.parametrize("make", [
    lambda: TinyDecoderModel(seed=0, attention_impl="pallas"), _routed,
    lambda: TPDecoderModel(seed=0)], ids=["pallas", "routed", "own-step"])
def test_a_decoder_without_a_round_keeps_the_per_stream_loop(make):
    decoder = make()
    model = TinyGenerateModel(decoder=decoder)
    core = ServerCore([model])
    model._ensure_built()
    assert decoder._round_fn is None and model._rounds is None
    steps, fresh = [], decoder._fresh_cache

    def counted():
        steps.append(1)
        return fresh()

    decoder._fresh_cache = counted
    assert len(_tokens(model, [1, 2, 3], 4)) == 4
    assert len(steps) == 1  # a cache of its own
    assert model.batch_histogram == {} and model.rounds_by_width == {}
    snapshot = core.metrics_registry().snapshot()
    assert snapshot["client_tpu_server_stream_rounds"]["series"] == []
    assert snapshot["client_tpu_server_stream_slot_waits"]["series"] == []
    assert sum(model.steps_by_rung.by_rung().values()) >= 3  # the decode steps


# -- what ran is counted ------------------------------------------------------


@pytest.mark.parametrize("head", HEAD_SIZES)
def test_the_histogram_and_the_registry_count_what_ran(served, head):
    model = served(4, _decoder(head))
    core = ServerCore([model])
    gate = Gate(model)
    users, out, errors = _concurrently(model, [PROMPTS[0]], 4)
    gate.held(1)
    others, _, errors_others = _concurrently(model, [PROMPTS[1]], 4)
    _arrived(model._rounds, 1)
    gate.open()
    _joined(users + others, errors + errors_others)
    # one round of the first alone, then both; a prompt of 3 and of 1 with 4
    # tokens each are 6 and 4 rounds
    rounds = len(gate.calls)
    assert model.batch_histogram == {1: rounds - 4, 2: 4}
    width = _width(head, 4, 2)
    assert width == 4  # two slots' turn, or all four
    assert model.rounds_by_width == {width: rounds}
    assert model.steps_by_rung.by_rung() == {128: rounds}
    assert model.steps_by_rung.totals()["prefill_tokens"] == 4
    assert model.steps_by_rung.totals()["prefill_chunks"] == 4
    assert model.steps_by_rung.totals()["prefill_ns"] > 0
    snapshot = core.metrics_registry().snapshot()
    series = lambda name: {
        tuple(v for k, v in sorted(row["labels"].items()) if k != "model"):
        row["value"] for row in snapshot[name]["series"]
        if row["labels"]["model"] == "tiny_lm_generate"}
    assert series("client_tpu_server_stream_rounds") == {(str(width),): rounds}
    assert series("client_tpu_server_stream_slot_waits") == {(): 0}
    assert series("client_tpu_server_decode_steps") == {("128",): rounds}
    text = core.metrics_registry().prometheus_text()
    assert ('client_tpu_server_stream_rounds{model="tiny_lm_generate",'
            f'width="{width}"}} {rounds}') in text
    # the statistics verb's batch_stats: a round is an execution
    row = core.statistics("tiny_lm_generate")["model_stats"][0]
    assert {r["batch_size"]: r["compute_infer"]["count"]
            for r in row["batch_stats"]} == model.batch_histogram


def test_a_streams_marks_are_those_of_its_rounds(served):
    model = served(2)
    core = ServerCore([model])
    core.trace_settings.update(trace_level=["TIMESTAMPS"], trace_rate="1")
    out = list(core.infer_stream("tiny_lm_generate", "", {"inputs": [
        {"name": name, "datatype": "INT32", "shape": list(array.shape),
         "array": array} for name, array in _inputs([1, 2, 3], 5).items()]}))
    assert len(out) == 5
    record = core.recent_traces()[-1]
    stamps, counts = record["timestamps"], record["counts"]
    order = ("recv", "model_enter", "cache_ready", "prefill_done",
             "first_response", "model_exit", "done")
    assert [stamps[name] for name in order] == sorted(stamps[name] for name in order)
    assert counts["dispatch"]["count"] == 4  # the first token's are the prefill
    assert counts["readback"]["count"] == counts["yielded"]["count"] == 5
    stats = core.statistics()["model_stats"][0]["inference_stats"]
    assert stats["queue"] == {"count": 1, "ns": 0}
    assert 0 < stats["compute_infer"]["ns"] < stats["success"]["ns"]


# -- failures and the end -----------------------------------------------------


def test_a_round_that_fails_fails_its_streams_and_the_next_is_served(
        served, alone):
    model = served(2)
    rounds = model._rounds
    step = rounds._step

    def refused(*args, **kwargs):
        raise RuntimeError("the round was refused")

    rounds._step = refused
    with pytest.raises(RuntimeError, match="refused"):
        _tokens(model, PROMPTS[0])
    rounds._step = step
    assert _tokens(model, PROMPTS[0]) == alone(PROMPTS[0])
    assert not model._rounds._members and sorted(model._rounds._free) == [0, 1]


def test_unload_fails_the_streams_in_progress_and_a_later_build_serves(
        served, alone):
    model = served(2)
    gate, rounds = Gate(model), model._rounds
    stream = model.execute_decoupled(_inputs(PROMPTS[0], 50), {})
    waiting = threading.Thread(target=model.unload)
    failed = []

    def user():
        try:
            list(stream)
        except Exception as e:
            failed.append(e)

    using = threading.Thread(target=user)
    using.start()
    gate.let(1)
    gate.held(2)
    waiting.start()
    _arrived(rounds, 1)  # unload's word to the worker
    gate.open()
    for t in (waiting, using):
        t.join(timeout=120)
        assert not t.is_alive()
    assert len(failed) == 1 and "shutting down" in str(failed[0])
    assert model._rounds is None and not model.ready
    model.load()
    assert _tokens(model, PROMPTS[1]) == alone(PROMPTS[1])


def test_the_round_is_traced_as_the_step_and_carries_its_scopes():
    """``step_device_ms`` of a stream cell finds the round as ``jit_step``;
    device time reads by the step's scopes and ``greedy_argmax``."""
    import re

    import jax.numpy as jnp

    decoder = TinyDecoderModel(seed=0)
    decoder._ensure_built()
    text = decoder._round_fn.lower(
        decoder._params, decoder._fresh_table(4), jnp.zeros((4,), jnp.int32),
        jnp.zeros((3, 4), jnp.int32), live=128).as_text(debug_info=True)
    assert "module @jit_step " in text
    for scope in ("embed", "attn_qkv", "cache_update", "attention", "attn_proj",
                  "mlp", "unembed", "greedy_argmax"):
        assert re.search(rf'["/]{scope}["/]', text), scope


# -- the worker's turns by phase ----------------------------------------------


def test_each_phase_is_counted_with_its_rounds_and_the_phases_add_up(
        served, monkeypatch):
    """One stream, a prompt of three and four tokens: six rounds, each read
    back and handed out; then a second stream, whose arrival ends the
    worker's wait."""
    from client_tpu.models import stream_rounds

    noted = spans_into_phases(stream_rounds, monkeypatch)
    model = served(2)
    assert _tokens(model, [1, 2, 3], 4) and _tokens(model, [5], 2)
    model.unload()  # the worker's last wait has ended: every span is in
    counts = {phase: count for phase, (count, _) in model.phases.rows().items()}
    assert set(counts) == {"wait_work", "admit", "prepare", "dispatch", "record",
                           "device_wait", "readback", "hand_out", "between"}
    assert sum(model.batch_histogram.values()) == 6 + 2
    for phase in ("prepare", "dispatch", "record", "device_wait", "readback",
                  "hand_out"):
        assert counts[phase] == 8, phase
    # once a turn
    turns = [name for name, _, _, _ in noted].count(timeline.SPAN_TURN)
    assert counts["admit"] == turns
    # the worker waited for each stream and for the sentinel
    assert counts["wait_work"] == 3
    check_the_phases_tile_the_workers_time(model.phases, noted)


def test_every_token_has_one_hand_off(served):
    model = served(4)
    core = ServerCore([model])
    core.trace_settings.update(trace_level=["TIMESTAMPS"], trace_rate="1")

    def stream(prompt, max_tokens, **parameters):
        return len(list(core.infer_stream("tiny_lm_generate", "", {
            "parameters": parameters, "inputs": [
                {"name": name, "datatype": "INT32", "shape": list(array.shape),
                 "array": array}
                for name, array in _inputs(prompt, max_tokens).items()]})))

    assert stream([1, 2, 3], 5) == 5 and stream([4], 7, chunk=3) == 7
    first, burst = core.recent_traces()
    for record, tokens in ((first, 5), (burst, 7)):
        counts = record["counts"]
        assert counts["handoff"]["count"] == counts["yielded"]["count"] == tokens
        assert counts["handoff"]["ns"] > 0
    # the rounds that gave the first tokens: the third, and the sixth after
    # it (a stream leaves with the dispatch of its last round)
    assert [first["first_round_id"], burst["first_round_id"]] == [2, 7]
    series = {name: metric["series"][0]["value"] for name, metric in
              core.metrics_registry().snapshot().items()
              if name.startswith(("client_tpu_server_token_handoff",
                                  "client_tpu_server_first_response"))}
    assert series["client_tpu_server_token_handoff_count"] == 12
    assert series["client_tpu_server_token_handoff_ns"] == (
        first["counts"]["handoff"]["ns"] + burst["counts"]["handoff"]["ns"])
    stamps = [r["timestamps"] for r in (first, burst)]
    assert series["client_tpu_server_first_response_count"] == 2
    assert series["client_tpu_server_first_response_ns"] == sum(
        s["first_response"] - s["recv"] for s in stamps)
