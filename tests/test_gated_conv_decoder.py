"""The decoder of gated short convolutions beside grouped-query attention
(models/gated_conv_decoder.py) against its plain reference
(benchmark/gated_conv_reference.py) on seeded weights at the family's fixture
sizes, and on the rounds (models/stream_rounds.py): the chunked prefill with
its carried conv state, the round, a slot used again, a stream seated
mid-prompt while others decode, what the grouped products read, and the
sequence API and per-stream loop beside the rounds.

Tokens, logits and counts; no clock. The fixture is served in float32, so
its logits are held to the reference's to ``ATOL``: float32's rounding of a
few layers, where bfloat16 in
its place reads 1.0e-3 to 1.3e-3 over three seeds, thirty times and more
(``test_bfloat16_in_its_place_fails_the_tolerance``).
"""

import json
import os
import threading

import numpy as np
import pytest

from benchmark import gated_conv_arithmetic as arithmetic
from benchmark import gated_conv_reference as reference
from client_tpu.models.gated_conv_decoder import (
    FED_TALLY,
    GatedConvDecoderModel,
    sizes_of,
)
from client_tpu.models.generate import TinyGenerateModel
from client_tpu.server import ServerCore

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs", "lfm2-24b-a2b.json")) as _f:
    PUBLISHED = json.load(_f)
# the family's fixture: a dense conv layer, then routed attention, conv and
# attention layers; 8 experts, 2 a token; prompts taken 4 positions a chunk
CONFIG, _ = arithmetic.fixture(PUBLISHED)
CHUNK = 4
ATOL = 3e-5
TOKENS = np.random.default_rng(41).integers(0, 300, 60).astype(np.int32)


def _decoder(dtype="float32", seed=3):
    decoder = GatedConvDecoderModel(dict(CONFIG, dtype=dtype), seed=seed,
                                    init_scale=arithmetic.init_scale)
    decoder._ensure_built()
    return decoder


@pytest.fixture(scope="module")
def exact():
    """Float32 weights: the program's mathematics against the reference's."""
    return _decoder()


def _through_the_state(decoder, tokens, prompt):
    """The logits at the positions from the prompt's last on: the prompt by
    chunks, then a step a token, teacher-forced."""
    logits, caches = decoder.prefill(decoder._fresh_cache(), tokens[:prompt], 0)
    out = [np.asarray(logits)]
    for pos in range(prompt, len(tokens)):
        logits, caches = decoder.decode_step(caches, int(tokens[pos]), pos)
        out.append(np.asarray(logits))
    return np.stack(out)


def _want(decoder, tokens, first):
    return np.asarray(reference.forward(
        decoder._params, CONFIG, tokens, np.arange(first, len(tokens)))[0])


def test_the_sizes_the_table_and_what_is_refused():
    s = sizes_of(CONFIG)
    assert s.kinds == ("conv", "full_attention", "conv", "full_attention")
    assert (s.dense, s.heads, s.kv_heads, s.head_dim, s.experts) == (1, 4, 2, 16, 8)
    assert [s.place(i) for i in range(4)] == [0, 0, 1, 1]
    table = _decoder()._fresh_table(3)
    assert [a.shape for a in table["k"]] == [(3, 64, 32)] * 2
    assert [a.shape for a in table["conv"]] == [(3, 2, 64)] * 2
    published = sizes_of(PUBLISHED)
    assert (published.d_model, published.heads, published.kv_heads, published.head_dim,
            published.mlp_width, published.expert_width, published.experts,
            published.experts_per_token, published.vocab) == (
        2048, 32, 8, 64, 11776, 1536, 64, 4, 65536)
    assert published.theta == 1e6 and published.eps == 1e-5
    for change in ({"conv_L_cache": 4}, {"conv_bias": True}, {"num_dense_layers": 5},
                   {"layer_types": ["conv"] * 3}, {"use_expert_bias": False},
                   {"prefill_chunk": 5}):
        with pytest.raises(ValueError):
            sizes_of(dict(CONFIG, **change))


# prompts that end 0, 1 and 2 tokens past a chunk's end, then steps to
# position 23 that cross two more
@pytest.mark.parametrize("prompt", [8, 9, 10])
def test_prefill_by_chunks_then_steps_give_the_reference_s_logits(exact, prompt):
    tokens = TOKENS[:24]
    got = _through_the_state(exact, tokens, prompt)
    want = _want(exact, tokens, prompt - 1)
    assert got.shape == want.shape == (24 - prompt + 1, 300)
    assert np.abs(want).max() > 0.3
    np.testing.assert_allclose(got, want, atol=ATOL)


def test_bfloat16_in_its_place_fails_the_tolerance():
    """The same prompt and steps with bfloat16 weights, state and products:
    the logits part from the float32 reference by more than ``ATOL``, so the
    tolerance above holds float32's arithmetic and not bfloat16's."""
    served = _decoder("bfloat16")
    tokens = TOKENS[:24]
    got = _through_the_state(served, tokens, 9)
    assert np.abs(got - _want(served, tokens, 8)).max() > 10 * ATOL


def test_the_float8_control_fails_the_limit(exact):
    """The reference's own pass in float8 puts other tokens first, further
    from the float32 best than the fixture's limit allows."""
    sessions = [{"prompt": [int(t) for t in TOKENS[at:at + 9]],
                 "tokens": [int(t) for t in TOKENS[at + 9:at + 20]]}
                for at in (0, 11, 23, 37)]
    read = reference.served_token_gaps(exact._params, CONFIG, sessions, 24,
                                       control=True)
    limits = arithmetic.fixture(PUBLISHED)[1]
    assert read["control_gap_max"] > 10 * limits["served_gap_max"]
    assert read["near_tie_share"] <= limits["near_tie_share"]


def test_a_prefill_from_the_middle_of_a_chunk_carries_the_conv_state(exact):
    """``prefill`` in two calls, the second from a position on no grid: its
    first tokens' convolution reads the state the first call left, and the
    rows before it keep what they held."""
    tokens = TOKENS[:18]
    _, caches = exact.prefill(exact._fresh_cache(), tokens[:7], 0)
    logits, caches = exact.prefill(caches, tokens[7:], 7)
    np.testing.assert_allclose(np.asarray(logits), _want(exact, tokens, 17)[0],
                               atol=ATOL)


def _chunks(decoder, table, fed, slot, tokens, first=0, upto=None):
    """A prompt's chunks ``first`` onward (up to the chunk that holds token
    ``upto``) into ``slot`` of the table, as the rounds' worker takes them."""
    upto = len(tokens) if upto is None else upto
    for base in range(first * CHUNK, upto, CHUNK):
        hi = min(CHUNK, len(tokens) - base)
        block = np.zeros(CHUNK, np.int32)
        block[:hi] = tokens[base:base + hi]
        fed, _, table = decoder._prefill_program(
            decoder._params, table, decoder._tables, fed, block,
            np.array([slot, base, 0, hi, base + hi == len(tokens)], np.int32),
            live=decoder.rung_for(base + hi))
    return fed, table


def _round(decoder, table, fed, members):
    """One round of the members ``{slot: position}``: fed tokens alone."""
    slots = fed.shape[0] - FED_TALLY
    ctl = np.zeros((3, slots), np.int32)
    ctl[0] = -1
    for slot, pos in members.items():
        ctl[1, slot], ctl[2, slot] = pos, 1
    return decoder._step_program(decoder._params, table, decoder._tables, fed, ctl,
                                 live=decoder.rung_for(max(members.values()) + 1))


def test_the_rounds_leave_a_stream_seated_mid_prompt_as_it_was(exact):
    """Four slots: two streams decode while a third is seated with one chunk
    of its prompt taken; slot 3 is empty and holds another stream's state.
    The rounds give the two decoding streams the reference's logits, and
    the third's remaining chunks then continue from the state its first
    left, which no round touched."""
    import jax.numpy as jnp

    table = exact._fresh_table(4)
    fed = jnp.zeros(4 + FED_TALLY, jnp.int32)
    prompts = {0: TOKENS[:9], 1: TOKENS[20:26], 2: TOKENS[40:51], 3: TOKENS[30:37]}
    for slot in (0, 1, 3):
        fed, table = _chunks(exact, table, fed, slot, prompts[slot])
    fed, table = _chunks(exact, table, fed, 2, prompts[2], upto=1)
    held = np.asarray(table["conv"][0][2:])  # slot 2's, and slot 3's
    assert np.abs(held).min() > 0
    streams = {slot: list(prompts[slot]) for slot in (0, 1)}
    for _ in range(6):
        members = {slot: len(tokens) for slot, tokens in streams.items()}
        chosen = np.asarray(fed)[:4]
        fed, logits, table = _round(exact, table, fed, members)
        for slot, tokens in streams.items():
            tokens.append(int(chosen[slot]))
            np.testing.assert_allclose(np.asarray(logits[slot]),
                                       _want(exact, np.array(tokens), len(tokens) - 1)[0],
                                       atol=ATOL)
    np.testing.assert_array_equal(np.asarray(table["conv"][0][2:]), held)
    fed, table = _chunks(exact, table, fed, 2, prompts[2], first=1)
    first = np.asarray(fed)[2]  # the prompt's last chunk chose it
    fed, logits, table = _round(exact, table, fed, {2: 11})
    want = _want(exact, np.append(prompts[2], first), 10)
    np.testing.assert_allclose(np.asarray(logits[2]), want[1], atol=ATOL)


def test_a_new_stream_starts_from_no_conv_state(exact):
    """A slot that held a longer stream's state: a new prompt's first chunk
    starts from zeros, and its logits are the reference's."""
    import jax.numpy as jnp

    table = exact._fresh_table(1)
    fed = jnp.zeros(1 + FED_TALLY, jnp.int32)
    fed, table = _chunks(exact, table, fed, 0, TOKENS[:30])
    assert float(np.abs(np.asarray(table["conv"][1])).min()) > 0
    short = TOKENS[31:37]
    fed, table = _chunks(exact, table, fed, 0, short)
    first = np.asarray(fed)[0]
    fed, logits, table = _round(exact, table, fed, {0: 6})
    want = _want(exact, np.append(short, first), 5)
    np.testing.assert_allclose(np.asarray(logits[0]), want[1], atol=ATOL)


def _experts_of(decoder, tokens):
    """The experts the reference routes the last of ``tokens`` to, a set a
    routed layer, and the narrowest margin of its choices."""
    chosen, margin, real = [], np.inf, len(tokens)

    def routed(x, layer, s, precision, real=real):
        nonlocal margin
        _, which, _, margins = reference._route(
            x, layer, eps=s["eps"], k=s["k"], renormalise=s["renormalise"],
            scaling=s["scaling"], precision=precision)
        chosen.append(set(np.asarray(which)[real - 1].tolist()))
        margin = min(margin, float(np.asarray(margins)[real - 1]))
        return original(x, layer, s, precision, real)

    original = reference._routed
    reference._routed = routed
    try:
        reference.forward(decoder._params, CONFIG, tokens, [len(tokens) - 1])
    finally:
        reference._routed = original
    return chosen, margin


def test_unoccupied_slots_add_no_pairs(exact):
    """A round of two members in a table of four: the tally behind the
    choices counts, over the routed layers, the distinct experts of the two
    members' tokens and no other, and one round; the chunks' pair counts the
    chunks and the experts of their own tokens."""
    import jax.numpy as jnp

    table = exact._fresh_table(4)
    fed = jnp.zeros(4 + FED_TALLY, jnp.int32)
    prompts = {1: TOKENS[:6], 3: TOKENS[10:15]}
    for slot, tokens in prompts.items():
        fed, table = _chunks(exact, table, fed, slot, tokens)
    before = np.asarray(fed)[4:]
    assert before[1] == 0 and before[3] == 4  # two chunks each
    chosen = np.asarray(fed)[:4]
    fed, _, table = _round(exact, table, fed, {1: 6, 3: 5})
    want = 0
    routes = [_experts_of(exact, np.append(tokens, chosen[slot]))
              for slot, tokens in prompts.items()]
    assert min(margin for _, margin in routes) > 1e-4  # no tie to decide
    for layer in zip(*(experts for experts, _ in routes)):
        want += len(set().union(*layer))
    after = np.asarray(fed)[4:]
    assert (after - before).tolist() == [want, 1, 0, 0]
    assert want < 3 * 2 * 2  # some expert is shared: pairs are not counted


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


JOBS = [(13, 20), (3, 30), (9, 12), (17, 25), (30, 30), (5, 8), (12, 40)]


def test_streams_on_the_rounds_and_the_per_stream_loop_give_the_same_tokens(exact):
    """Seven streams over four slots through the core: prompts by chunks
    into their slots beside the rounds in flight, slots taken again by later
    streams; each gets what it gets alone, and what the per-stream loop (a
    decoder of the same weights without the round) gives it. The registry
    has the rounds' tally."""
    model = TinyGenerateModel(decoder=exact, slots=4)
    core = ServerCore([model])
    prompts = [[int(t) for t in TOKENS[i:i + n]] for i, (n, _) in enumerate(JOBS)]
    out, errors = {}, []

    def user(i):
        try:
            out[i] = _tokens(model, prompts[i], JOBS[i][1])
        except Exception as e:  # shown below
            errors.append(e)

    try:
        users = [threading.Thread(target=user, args=(i,)) for i in range(len(JOBS))]
        for u in users:
            u.start()
        for u in users:
            u.join(timeout=300)
        snapshot = core.metrics_registry().snapshot()
    finally:
        model.unload()
    assert not errors, errors
    alone = GatedConvDecoderModel(CONFIG, seed=3, init_scale=arithmetic.init_scale)
    alone._ensure_built()
    alone._round_fn = None
    loop = TinyGenerateModel(decoder=alone)
    for i, (_, budget) in enumerate(JOBS):
        assert out[i] == _alone(exact, prompts[i], budget), i
        assert _tokens(loop, prompts[i], budget) == out[i], i
    totals = model.steps_by_rung.totals()
    assert totals["prefill_tokens"] == sum(n for n, _ in JOBS)
    assert totals["prefill_chunks"] == sum(-(-n // CHUNK) for n, _ in JOBS)
    series = {(row["labels"]["program"], name): row["value"]
              for name in ("client_tpu_server_experts_reached",
                           "client_tpu_server_experts_reached_rounds")
              for row in snapshot[name]["series"]
              if row["labels"]["model"] == "tiny_lm_generate"}
    rounds = sum(model.batch_histogram.values())
    assert series[("round", "client_tpu_server_experts_reached_rounds")] == rounds
    assert series[("chunk", "client_tpu_server_experts_reached_rounds")] == sum(
        -(-n // CHUNK) for n, _ in JOBS)
    # a round reads, a routed layer, at least one expert and at most its
    # members' pairs or all of them
    reached = series[("round", "client_tpu_server_experts_reached")]
    members = sum(n * k for n, k in model.batch_histogram.items())
    assert 3 * rounds <= reached <= 3 * min(2 * members, 8 * rounds)


def test_the_sequence_api_gives_the_reference_s_logits(exact):
    """``execute``: a prompt through the chunks, a continuation through the
    step."""
    prompt = [int(t) for t in TOKENS[:13]]
    reply = exact.execute({"TOKENS": np.array([prompt], np.int32)},
                          {"sequence_id": 5, "sequence_start": True})
    np.testing.assert_allclose(reply["LOGITS"][0], _want(exact, np.array(prompt), 12)[0],
                               atol=ATOL)
    nxt = int(reply["NEXT_TOKEN"][0, 0])
    reply = exact.execute({"TOKENS": np.array([[nxt]], np.int32)},
                          {"sequence_id": 5, "sequence_end": True})
    np.testing.assert_allclose(reply["LOGITS"][0],
                               _want(exact, np.array(prompt + [nxt]), 13)[0], atol=ATOL)
