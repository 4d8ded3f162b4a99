"""The decoder that keeps a window beside chunk summaries
(models/window_summary_decoder.py) against its plain reference
(benchmark/window_summary_reference.py) on seeded weights at a small size,
and on the rounds (models/stream_rounds.py): the step, the chunked prefill,
the round, the slot prefill, a slot used again, and what is counted.

Tokens, logits and counts; no clock.
"""

import threading

import numpy as np
import pytest

from benchmark import window_summary_arithmetic as arithmetic
from benchmark import window_summary_reference as reference
from client_tpu.models.generate import TinyGenerateModel
from client_tpu.models.window_summary_decoder import (
    WindowSummaryDecoderModel,
    summary_ladder,
)
from client_tpu.server import ServerCore, timeline

# a window of 8 positions, chunks of 2, prompts taken 4 positions a dispatch,
# 64 positions: rungs of 0, 16 and 32 summary rows
CONFIG = dict(
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4, intermediate_size=128,
    window_size=8, chunk_size=2, prefill_chunk=4, num_pred_heads=8, vocab_size=320,
    rope_theta=100000, rms_norm_eps=1e-5, norm_add_unit_offset=True,
    max_position_embeddings=64)
TOKENS = np.random.default_rng(34).integers(0, 320, 60).astype(np.int32)


def _decoder(dtype, seed=3):
    decoder = WindowSummaryDecoderModel(
        dict(CONFIG, dtype=dtype), seed=seed, init_scale=arithmetic.init_scale)
    decoder._ensure_built()
    return decoder


@pytest.fixture(scope="module")
def exact():
    """Float32 weights: the program's mathematics against the reference's."""
    return _decoder("float32")


@pytest.fixture(scope="module")
def served_type():
    """Weights, state and products in bfloat16, as served."""
    return _decoder("bfloat16")


def _through_the_state(decoder, tokens, prompt):
    """Every head's logits at the positions from the prompt's last on:
    the prompt by chunks, then a step a token, teacher-forced."""
    logits, caches = decoder.prefill_heads(decoder._fresh_cache(), tokens[:prompt], 0)
    out = [np.asarray(logits)]
    for pos in range(prompt, len(tokens)):
        logits, caches = decoder._step_at(
            caches, int(tokens[pos]), pos, decoder.rung_for(pos + 1))
        out.append(np.asarray(logits))
    return np.stack(out)


def test_the_sizes_and_the_ladder():
    decoder = _decoder("float32")
    s = decoder.sizes
    assert (s.heads, s.head_dim, s.window, s.chunk, s.prefill_chunk) == (4, 16, 8, 2, 4)
    assert s.summaries_a_window == 4 and s.summary_rows == 32
    assert decoder.ladder() == summary_ladder(s) == (0, 16, 32)
    # the first window reads no summary; then four rows a window before
    assert [decoder.rung_for(reach) for reach in (1, 8, 9, 40, 41, 64)] == [
        0, 0, 16, 16, 32, 32]
    table = decoder._fresh_table(3)
    assert table[0]["k"].shape == (3, 4, 8, 16) and table[0]["sk"].shape == (3, 4, 32, 16)
    with pytest.raises(ValueError):
        WindowSummaryDecoderModel(dict(CONFIG, prefill_chunk=3))
    with pytest.raises(ValueError):
        WindowSummaryDecoderModel(dict(CONFIG, num_key_value_heads=2))


# a prompt that ends mid-chunk and mid-window; one of a single token; one that
# ends with a chunk and a window; one past three windows: each then decoded to
# position 44, across windows and with the last chunk unfinished
@pytest.mark.parametrize("prompt", [21, 1, 16, 27])
def test_prefill_by_chunks_then_steps_give_the_reference_s_logits(exact, prompt):
    tokens = TOKENS[:45]
    got = _through_the_state(exact, tokens, prompt)
    want = np.asarray(reference.forward(
        exact._params, CONFIG, tokens, np.arange(prompt - 1, len(tokens))))
    assert got.shape == want.shape == (len(tokens) - prompt + 1, 8, 320)
    assert np.abs(want).max() > 0.3
    np.testing.assert_allclose(got, want, atol=2e-5)  # all 2,560 logits


def test_an_odd_number_of_windows_is_read_in_whole_blocks():
    """Five windows reserved: the round's blocks of summary rows are one
    window's worth, so that the table is whole blocks."""
    config = dict(CONFIG, max_position_embeddings=40, dtype="float32")
    decoder = WindowSummaryDecoderModel(config, seed=3, init_scale=arithmetic.init_scale)
    decoder._ensure_built()
    assert decoder.ladder() == (0, 20)
    tokens = TOKENS[:39]
    got = _through_the_state(decoder, tokens, 21)
    want = np.asarray(reference.forward(decoder._params, config, tokens, np.arange(20, 39)))
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_served_type_stays_near_the_reference(served_type):
    """bfloat16 weights, state and products against float32 throughout: head
    0's served byte lies within the fixture's limit of the reference's best,
    and every head's logits within bfloat16's rounding of them."""
    tokens, prompt = TOKENS[:45], 21
    got = _through_the_state(served_type, tokens, prompt)
    want = np.asarray(reference.forward(
        served_type._params, CONFIG, tokens, np.arange(prompt - 1, len(tokens))))
    assert np.abs(got - want).max() < 0.02
    chosen = got[:, 0].argmax(-1)
    gaps = want[:, 0].max(-1) - np.take_along_axis(want[:, 0], chosen[:, None], 1)[:, 0]
    assert gaps.max() <= arithmetic.fixture(CONFIG)[1]["served_gap_max"]


def test_the_float8_control_fails_the_limit(served_type):
    """The reference's own pass in float8 puts other bytes first, further
    from the float32 best than the limit allows."""
    sessions = [{"prompt": [int(t) for t in TOKENS[at:at + 11]],
                 "tokens": [int(t) for t in TOKENS[at + 11:at + 23]]}
                for at in (0, 7, 19, 30)]
    read = reference.served_token_gaps(
        served_type._params, CONFIG, sessions, 24, control=True)
    assert read["positions"] == 48
    assert read["control_gap_max"] > arithmetic.fixture(CONFIG)[1]["served_gap_max"]


def test_a_prefill_from_the_middle_of_a_chunk_writes_what_one_from_the_start_does(exact):
    """``prefill`` in two calls, the second from a position that is on no
    grid: the rows before it keep what they held, and the summaries of the
    chunks that end in it are made of both calls' rows."""
    tokens = TOKENS[:30]
    _, whole = exact.prefill_heads(exact._fresh_cache(), tokens, 0)
    _, caches = exact.prefill_heads(exact._fresh_cache(), tokens[:13], 0)
    logits, parts = exact.prefill_heads(caches, tokens[13:], 13)
    for one, other in zip(whole, parts):
        for name in ("sk", "sv"):
            np.testing.assert_allclose(np.asarray(one[name][:, :, :15]),
                                       np.asarray(other[name][:, :, :15]), atol=1e-6)
    want = np.asarray(reference.forward(exact._params, CONFIG, tokens, [29]))
    np.testing.assert_allclose(np.asarray(logits), want[0], atol=2e-5)


def test_a_round_of_a_table_gives_what_the_single_step_gives(exact):
    """The round over a table of four slots, three occupied at positions in
    three windows, against each sequence's own step: the same bytes chosen,
    the same logits."""
    import jax.numpy as jnp

    prompts = {0: 19, 1: 5, 3: 30}  # slot -> prompt length
    table = exact._fresh_table(4)
    fed = jnp.zeros(4, jnp.int32)
    singles = {}
    for slot, n in prompts.items():
        tokens = TOKENS[slot:slot + n]
        for base in range(0, n, 4):
            hi = min(4, n - base)
            block = np.zeros(4, np.int32)  # a dispatch may still read the last
            block[:hi] = tokens[base:base + hi]
            fed, _, table = exact._prefill_program(
                exact._params, table, exact._tables, fed, block,
                np.array([slot, base, 0, hi, base + hi == n], np.int32),
                live=exact.rung_for(base + hi))
        logits, caches = exact.prefill_heads(exact._fresh_cache(), tokens, 0)
        singles[slot] = (int(np.asarray(logits)[0].argmax()), caches)
    assert {slot: int(fed[slot]) for slot in prompts} == {
        slot: first for slot, (first, _) in singles.items()}
    pos = dict(prompts)
    for _ in range(12):  # every stream crosses a chunk's end, two a window's
        ctl = np.zeros((3, 4), np.int32)
        ctl[0] = -1
        for slot in prompts:
            ctl[1, slot], ctl[2, slot] = pos[slot], 1
        chosen, logits, table = exact._step_program(
            exact._params, table, exact._tables, fed, ctl)
        for slot in prompts:
            token, caches = singles[slot]
            own, caches = exact._step_at(caches, token, pos[slot],
                                         exact.rung_for(pos[slot] + 1))
            np.testing.assert_allclose(np.asarray(logits[slot]), np.asarray(own),
                                       atol=1e-5)
            assert int(chosen[slot]) == int(np.asarray(own)[0].argmax())
            singles[slot] = (int(chosen[slot]), caches)
            pos[slot] += 1
        fed = chosen


# -- on the rounds ------------------------------------------------------------

def _tokens(model, prompt, max_tokens):
    out = list(model.execute_decoupled(
        {"TOKENS": np.array([prompt], np.int32),
         "MAX_TOKENS": np.array([max_tokens], np.int32)}, {}))
    assert [int(r["INDEX"][0, 0]) for r in out] == list(range(len(out)))
    return [int(r["NEXT_TOKEN"][0, 0]) for r in out]


def _alone(decoder, prompt, max_tokens):
    """What a stream gets by its own steps on a fresh cache."""
    logits, caches = decoder.prefill(decoder._fresh_cache(), prompt, 0)
    out, pos = [], len(prompt)
    for _ in range(max_tokens):
        out.append(int(np.asarray(logits).argmax()))
        logits, caches = decoder.decode_step(caches, out[-1], pos)
        pos += 1
    return out


@pytest.fixture
def served(exact):
    models = []

    def make(slots):
        model = TinyGenerateModel(decoder=exact, slots=slots)
        model._ensure_built()
        assert model._rounds is not None and model._rounds._slot_prefill is not None
        models.append(model)
        return model

    yield make
    for model in models:
        model.unload()


JOBS = [(21, 20), (3, 30), (9, 12), (17, 25), (30, 30), (5, 8), (12, 40)]


def test_streams_on_the_rounds_give_what_each_gives_alone(served, exact):
    """Seven streams over four slots: prompts by chunks into their slots
    beside the rounds in flight, slots taken again by later streams."""
    model = served(4)
    prompts = [[int(t) for t in TOKENS[i:i + n]] for i, (n, _) in enumerate(JOBS)]
    out, errors = {}, []

    def user(i):
        try:
            out[i] = _tokens(model, prompts[i], JOBS[i][1])
        except Exception as e:  # shown below
            errors.append(e)

    users = [threading.Thread(target=user, args=(i,)) for i in range(len(JOBS))]
    for u in users:
        u.start()
    for u in users:
        u.join(timeout=300)
    assert not errors, errors
    for i, (_, budget) in enumerate(JOBS):
        assert out[i] == _alone(exact, prompts[i], budget), i
    totals = model.steps_by_rung.totals()
    assert totals["prefill_tokens"] == sum(n for n, _ in JOBS)
    assert totals["prefill_chunks"] == sum(-(-n // 4) for n, _ in JOBS)
    # a member a round from its second byte on: the first is its last chunk's
    assert sum(n * rounds for n, rounds in model.batch_histogram.items()) == sum(
        budget - 1 for _, budget in JOBS)


def test_a_slot_taken_again_by_a_shorter_stream_shows_nothing_stale(served, exact):
    """One slot: a stream that fills five windows and twenty summary rows,
    then one that stays in its second window. Nothing of the slot is cleared;
    the ring rows and summary rows the first left are masked by position."""
    model = served(1)
    long_prompt = [int(t) for t in TOKENS[:33]]
    assert _tokens(model, long_prompt, 12) == _alone(exact, long_prompt, 12)
    table = model._rounds._caches
    assert float(np.abs(np.asarray(table[0]["sk"][0, :, 16:20])).min()) > 0
    short = [int(t) for t in TOKENS[40:43]]
    assert _tokens(model, short, 9) == _alone(exact, short, 9)


class Gate:
    """In the place of the rounds' two dispatch calls: each waits for a
    permit and is recorded: ``("round", positions of the members)`` or
    ``("chunk", slot, base, tokens)``."""

    def __init__(self, model):
        rounds = model._rounds
        self.step, self.chunk = rounds._step, rounds._chunk
        self.permits = threading.Semaphore(0)
        self.calls = []
        self.reached = 0  # dispatches that came to the gate
        self.dispatched = threading.Condition()
        rounds._step, rounds._chunk = self._round, self._a_chunk

    def _wait(self, call):
        with self.dispatched:
            self.reached += 1
            self.dispatched.notify_all()
        assert self.permits.acquire(timeout=120), "no permit for the dispatch"
        with self.dispatched:
            self.calls.append(call)
            self.dispatched.notify_all()

    def _round(self, ctl, live):
        self._wait(("round", tuple(int(p) for p in ctl[1][ctl[2] > 0])))
        self.step(ctl, live)

    def _a_chunk(self, block, ctl, live):
        self._wait(("chunk", int(ctl[0]), int(ctl[1]), int(ctl[3])))
        self.chunk(block, ctl, live)

    def let(self, dispatches):
        want = len(self.calls) + dispatches
        for _ in range(dispatches):
            self.permits.release()
        with self.dispatched:
            assert self.dispatched.wait_for(
                lambda: len(self.calls) >= want, timeout=120)

    def held(self, n):
        """Wait until the worker stands at the gate with its ``n``-th
        dispatch: that turn's admission is behind it."""
        with self.dispatched:
            assert self.dispatched.wait_for(lambda: self.reached >= n, timeout=120)

    def open(self):
        for _ in range(4096):
            self.permits.release()


def test_a_prompt_is_taken_a_chunk_a_turn_between_the_rounds(served, exact):
    """A stream decodes; a second arrives with a prompt of three chunks. Each
    turn of the worker is one chunk of it at most, then the round of the
    first, which waits one chunk between two bytes and for no prompt; the
    second joins the rounds after its last chunk; both get what they get
    alone."""
    model = served(2)
    gate = Gate(model)
    first, second = [int(t) for t in TOKENS[:3]], [int(t) for t in TOKENS[20:31]]
    out = {}
    one = threading.Thread(target=lambda: out.update(a=_tokens(model, first, 14)))
    one.start()
    gate.let(3)  # its one chunk, two rounds
    assert gate.calls == [("chunk", 0, 0, 3), ("round", (3,)), ("round", (4,))]
    # the worker stands at the gate with the first's next round, that turn's
    # admission behind it, when the second arrives: it is seated next turn
    gate.held(4)
    other = threading.Thread(target=lambda: out.update(b=_tokens(model, second, 6)))
    other.start()
    rounds = model._rounds
    for _ in range(12000):
        if rounds._arrivals.qsize():
            break
        threading.Event().wait(0.01)
    gate.let(9)
    assert gate.calls[3:] == [
        ("round", (5,)), ("chunk", 1, 0, 4), ("round", (6,)), ("chunk", 1, 4, 4),
        ("round", (7,)), ("chunk", 1, 8, 3), ("round", (8, 11)), ("round", (9, 12)),
        ("round", (10, 13))]
    gate.open()
    for user in (one, other):
        user.join(timeout=300)
    assert out["a"] == _alone(exact, first, 14)
    assert out["b"] == _alone(exact, second, 6)


def _stream(core, prompt, max_tokens):
    return [int(r["outputs"][0]["array"].reshape(-1)[0]) for r in core.infer_stream(
        "tiny_lm_generate", "", {"inputs": [
            {"name": "TOKENS", "datatype": "INT32", "shape": [1, len(prompt)],
             "array": np.array([prompt], np.int32)},
            {"name": "MAX_TOKENS", "datatype": "INT32", "shape": [1],
             "array": np.array([max_tokens], np.int32)}]})]


def test_served_through_the_core_with_its_counts_in_the_registry(exact):
    """``ServerCore``'s decoupled stream over the rounds, and the registry's
    series: the prompt's side as the other decoders fill it, and the rows of
    the two kinds of state, counted from positions."""
    model = TinyGenerateModel(decoder=exact, slots=2)
    core = ServerCore([model])
    try:
        prompt = [int(t) for t in TOKENS[:21]]
        assert _stream(core, prompt, 6) == _alone(exact, prompt, 6)
        snapshot = core.metrics_registry().snapshot()
    finally:
        model.unload()
    value = lambda name: {row["labels"]["model"]: row["value"]
                          for row in snapshot[name]["series"]}["tiny_lm_generate"]
    assert value("client_tpu_server_prefill_tokens") == 21
    assert value("client_tpu_server_prefill_chunks") == 6
    assert value("client_tpu_server_prefill_ns") > 0
    # five rounds, at positions 21 to 25: ring rows 6, 7, 8, 1, 2 and the
    # summaries of two windows, then three; chunks end at 1, 3 ... 19 in the
    # prompt and at 21, 23, 25 after it
    assert value("client_tpu_server_window_rows_read") == 6 + 7 + 8 + 1 + 2
    assert value("client_tpu_server_summary_rows_read") == 3 * 8 + 2 * 12
    assert value("client_tpu_server_summaries_written") == 10 + 3
    steps = {row["labels"]["live"]: row["value"]
             for row in snapshot["client_tpu_server_decode_steps"]["series"]}
    assert steps == {"16": 5}  # rounds alone: a chunk is not a step


def test_a_prompt_s_chunks_are_a_phase_of_the_worker_s_turns(served, monkeypatch):
    """Ten bytes are three chunks of four, the last of which gives the first
    byte; two rounds give the other two. The turns are cut as the GPT-2
    decoder's are, with ``prefill_chunk`` between ``admit`` and the round."""
    from client_tpu.models import stream_rounds
    from tests.conftest import (
        check_the_phases_tile_the_workers_time,
        spans_into_phases,
    )

    noted = spans_into_phases(stream_rounds, monkeypatch)
    model = served(2)
    assert len(_tokens(model, [int(t) for t in TOKENS[:10]], 3)) == 3
    model.unload()
    counts = {phase: count for phase, (count, _) in model.phases.rows().items()}
    assert counts["prefill_chunk"] == 3 and counts["dispatch"] == 2
    # a chunk and a round are each prepared and recorded
    assert counts["prepare"] == counts["record"] == 3 + 2
    # read back: the last chunk and the two rounds; the two chunks before it
    # are waited for as well, nobody decoding beside them
    assert counts["readback"] == counts["hand_out"] == 3
    assert counts["device_wait"] == 3 + 2
    assert counts["wait_work"] == 2  # the stream, and the sentinel
    check_the_phases_tile_the_workers_time(model.phases, noted)


def test_a_profiler_session_holds_a_span_a_slot_prefill_dispatch(tmp_path, served):
    import glob

    import jax
    from jax.profiler import ProfileData

    model = served(2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _tokens(model, [int(t) for t in TOKENS[:10]], 3)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    names = [event.name for plane in ProfileData.from_file(found[0]).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for event in line.events]
    assert names.count(timeline.SPAN_PREFILL_CHUNK) == 3
    assert names.count(timeline.SPAN_DISPATCH) == 2


def test_the_sequence_api_gives_head_0_s_logits(exact):
    """``execute``: a prompt through the chunks, a continuation through the
    step; ``LOGITS`` are head 0's 320."""
    prompt = [int(t) for t in TOKENS[:13]]
    reply = exact.execute({"TOKENS": np.array([prompt], np.int32)},
                          {"sequence_id": 5, "sequence_start": True})
    assert reply["LOGITS"].shape == (1, 320)
    want = np.asarray(reference.forward(exact._params, CONFIG, prompt, [12]))[0, 0]
    np.testing.assert_allclose(reply["LOGITS"][0], want, atol=2e-5)
    nxt = int(reply["NEXT_TOKEN"][0, 0])
    reply = exact.execute({"TOKENS": np.array([[nxt]], np.int32)},
                          {"sequence_id": 5, "sequence_end": True})
    want = np.asarray(reference.forward(exact._params, CONFIG, prompt + [nxt], [13]))
    np.testing.assert_allclose(reply["LOGITS"][0], want[0, 0], atol=2e-5)
