"""The decoder of sliding-window layers beside full layers without positions,
routed to ReLU-gated experts before attention
(models/window_routed_decoder.py) against its plain reference
(benchmark/window_routed_reference.py) on seeded weights at the family's
fixture sizes (a window of 16, chunks of 8), and on the rounds
(models/stream_rounds.py): prompts that end before, at and past the window,
sessions that wrap the ring, a chunk whose writes replace ring rows its own
queries read, a slot used again after a longer stream, a stream seated
mid-prompt while others decode, what the grouped products read, the rows the
steps attend to, and the sequence API and per-stream loop beside the rounds.

Tokens, logits and counts; no clock. The fixture is served in float32, so
its logits are held to the reference's to ``ATOL``: float32's rounding of
eight layers, where bfloat16 in its place reads 1.1e-3 to 1.7e-3 over three
seeds, forty times and more (``test_bfloat16_in_its_place_fails_the_tolerance``).
"""

import json
import os
import threading

import numpy as np
import pytest

from benchmark import window_routed_arithmetic as arithmetic
from benchmark import window_routed_reference as reference
from client_tpu.models.decoder import RungCount
from client_tpu.models.generate import TinyGenerateModel
from client_tpu.models.window_routed_decoder import (
    FED_TALLY,
    WindowRoutedDecoderModel,
    ring_positions,
    sizes_of,
)
from client_tpu.server import ServerCore

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "smallthinker-21ba3b-instruct.json")) as _f:
    PUBLISHED = json.load(_f)
# the family's fixture: two periods of [full, window, window, window], a
# window of 16, prompts taken 8 positions a chunk, 8 experts, 2 a token
CONFIG, _ = arithmetic.fixture(PUBLISHED)
WINDOW, CHUNK = 16, 8
ATOL = 3e-5
TOKENS = np.random.default_rng(45).integers(0, 300, 80).astype(np.int32)


def _decoder(dtype="float32", seed=3):
    decoder = WindowRoutedDecoderModel(dict(CONFIG, dtype=dtype), seed=seed,
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
    assert s.sliding == (False, True, True, True) * 2
    assert (s.heads, s.kv_heads, s.head_dim, s.experts, s.experts_per_token) == (
        4, 2, 16, 8, 2)
    assert (s.window, s.prefill_chunk, s.max_len) == (16, 8, 64)
    assert [s.place(i) for i in range(8)] == [0, 0, 1, 2, 1, 3, 4, 5]
    table = _decoder()._fresh_table(3)
    assert [a.shape for a in table["k"]] == [(3, 64, 32)] * 2
    assert [a.shape for a in table["ring_v"]] == [(3, 16, 32)] * 6
    published = sizes_of(PUBLISHED)
    assert (published.d_model, published.heads, published.kv_heads, published.head_dim,
            published.expert_width, published.experts, published.experts_per_token,
            published.vocab, published.window) == (
        2560, 28, 4, 128, 768, 64, 6, 151936, 4096)
    assert published.sliding == (False, True, True, True) * 2
    assert published.theta == 1.5e6 and published.eps == 1e-6
    assert WindowRoutedDecoderModel(PUBLISHED, seed=None).ladder() == (4096, 16384)
    for change in ({"rope_layout": [1, 1, 1, 1, 0, 1, 1, 1]},
                   {"sliding_window_layout": [0, 1, 1, 1]},
                   {"moe_primary_router_apply_softmax": False},
                   {"tie_word_embeddings": True}, {"prefill_chunk": 6},
                   {"sliding_window_size": 12}, {"sliding_window_size": 128}):
        with pytest.raises(ValueError):
            sizes_of(dict(CONFIG, **change))


def test_a_ring_row_s_position_is_derived_from_the_reach():
    import jax.numpy as jnp

    got = np.asarray(ring_positions(jnp.array([0, 3, 16, 21]), 16))
    assert got[0].max() < 0  # nothing written
    assert got[1].tolist()[:4] == [0, 1, 2, -13]
    assert got[2].tolist() == list(range(16))
    assert got[3].tolist() == [16, 17, 18, 19, 20] + list(range(5, 16))


# prompts that end before, at, one and two past the window, one that wraps
# the ring inside its prompt, each then stepped to position 49: past the
# ring's third wrap
@pytest.mark.parametrize("prompt", [15, 16, 17, 18, 37])
def test_prefill_by_chunks_then_steps_give_the_reference_s_logits(exact, prompt):
    tokens = TOKENS[:50]
    got = _through_the_state(exact, tokens, prompt)
    want = _want(exact, tokens, prompt - 1)
    assert got.shape == want.shape == (50 - prompt + 1, 300)
    assert np.abs(want).max() > 0.3
    np.testing.assert_allclose(got, want, atol=ATOL)


def test_bfloat16_in_its_place_fails_the_tolerance():
    """The same prompt and steps with bfloat16 weights, rows and products:
    the logits part from the float32 reference by more than ``ATOL``, so the
    tolerance above holds float32's arithmetic and not bfloat16's."""
    served = _decoder("bfloat16")
    tokens = TOKENS[:30]
    got = _through_the_state(served, tokens, 17)
    assert np.abs(got - _want(served, tokens, 16)).max() > 10 * ATOL


def test_the_float8_control_fails_the_limit(exact):
    """The reference's own pass in float8 puts other tokens first, further
    from the float32 best than the fixture's limit allows."""
    sessions = [{"prompt": [int(t) for t in TOKENS[at:at + 9]],
                 "tokens": [int(t) for t in TOKENS[at + 9:at + 22]]}
                for at in (0, 11, 23, 37)]
    read = reference.served_token_gaps(exact._params, CONFIG, sessions, 24,
                                       control=True)
    limits = arithmetic.fixture(PUBLISHED)[1]
    assert read["control_gap_max"] > 10 * limits["served_gap_max"]
    assert read["near_tie_share"] <= limits["near_tie_share"]


def test_a_prefill_from_the_middle_of_a_chunk_past_the_window(exact):
    """``prefill`` in two calls, the second from a position on no grid and
    past the window: its window layers read the ring rows the first call
    wrote, its own chunk's earlier rows among them, and none it replaces."""
    tokens = TOKENS[:30]
    _, caches = exact.prefill(exact._fresh_cache(), tokens[:19], 0)
    logits, caches = exact.prefill(caches, tokens[19:], 19)
    np.testing.assert_allclose(np.asarray(logits), _want(exact, tokens, 29)[0],
                               atol=ATOL)


def _chunks(decoder, table, fed, slot, tokens, first=0, upto=None):
    """A prompt's chunks ``first`` onward (up to the chunk that holds token
    ``upto``) into ``slot`` of the table, as the rounds' worker takes them;
    the logits of the last chunk taken."""
    upto = len(tokens) if upto is None else upto
    logits = None
    for base in range(first * CHUNK, upto, CHUNK):
        hi = min(CHUNK, len(tokens) - base)
        block = np.zeros(CHUNK, np.int32)
        block[:hi] = tokens[base:base + hi]
        fed, logits, table = decoder._prefill_program(
            decoder._params, table, decoder._tables, fed, block,
            np.array([slot, base, 0, hi, base + hi == len(tokens)], np.int32),
            live=decoder.rung_for(base + hi))
    return fed, table, logits


def _round(decoder, table, fed, members):
    """One round of the members ``{slot: position}``: fed tokens alone."""
    slots = fed.shape[0] - FED_TALLY
    ctl = np.zeros((3, slots), np.int32)
    ctl[0] = -1
    for slot, pos in members.items():
        ctl[1, slot], ctl[2, slot] = pos, 1
    return decoder._step_program(decoder._params, table, decoder._tables, fed, ctl,
                                 live=decoder.rung_for(max(members.values()) + 1))


@pytest.mark.parametrize("reads_first", [True, False], ids=["as-built", "written-first"])
def test_a_chunk_past_the_window_reads_the_rows_its_writes_replace(exact, reads_first):
    """A prompt whose last whole chunk (positions 16 to 23) replaces the ring
    rows of positions 0 to 7, which its queries at 16 to 22 still attend to.
    Taken as built, its logits are the reference's; with the chunk's rows
    written into the ring before its attention reads it, they are not, by
    far more than the tolerance: the case sees the order."""
    import jax.numpy as jnp

    tokens = TOKENS[:24]
    fed = jnp.zeros(1 + FED_TALLY, jnp.int32)
    table = exact._fresh_table(1)
    fed, table, _ = _chunks(exact, table, fed, 0, tokens, upto=16)
    if not reads_first:
        # the window layers' rows of positions 16 to 23 put where they go
        # before the chunk runs: what a write before the read would leave
        _, ahead = exact.prefill(exact._fresh_cache(), tokens, 0)
        table = dict(table, ring_k=[a.at[:, :8].set(b[:, :8]) for a, b in
                                    zip(table["ring_k"], ahead["ring_k"])],
                     ring_v=[a.at[:, :8].set(b[:, :8]) for a, b in
                             zip(table["ring_v"], ahead["ring_v"])])
    fed, table, logits = _chunks(exact, table, fed, 0, tokens, first=2)
    gap = np.abs(np.asarray(logits) - _want(exact, tokens, 23)[0]).max()
    assert (gap < ATOL) == reads_first, gap


def test_a_slot_used_again_reads_no_row_of_its_last_stream(exact):
    """A slot that held a stream of 45 positions, its ring written three
    times round and its full layers' rows to 44: a new prompt of 6 tokens
    and its rounds to position 20 read none of those rows, and give the
    reference's logits."""
    import jax.numpy as jnp

    table = exact._fresh_table(1)
    fed = jnp.zeros(1 + FED_TALLY, jnp.int32)
    fed, table, _ = _chunks(exact, table, fed, 0, TOKENS[:45])
    assert float(np.abs(np.asarray(table["ring_k"][0])).min()) > 0
    stream = list(TOKENS[50:56])
    fed, table, _ = _chunks(exact, table, fed, 0, np.array(stream))
    for pos in range(6, 20):
        stream.append(int(np.asarray(fed)[0]))
        fed, logits, table = _round(exact, table, fed, {0: pos})
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   _want(exact, np.array(stream), pos)[0], atol=ATOL)


def test_the_rounds_leave_a_stream_seated_mid_prompt_as_it_was(exact):
    """Four slots: two streams decode across the window while a third is
    seated with one chunk of its prompt taken; slot 3 is empty and holds
    another stream's rows. The rounds give the two decoding streams the
    reference's logits, and the third's remaining chunks then continue from
    the rows its first left, which no round touched."""
    import jax.numpy as jnp

    table = exact._fresh_table(4)
    fed = jnp.zeros(4 + FED_TALLY, jnp.int32)
    prompts = {0: TOKENS[:13], 1: TOKENS[20:26], 2: TOKENS[40:61], 3: TOKENS[30:37]}
    for slot in (0, 1, 3):
        fed, table, _ = _chunks(exact, table, fed, slot, prompts[slot])
    fed, table, _ = _chunks(exact, table, fed, 2, prompts[2], upto=1)
    held = [np.asarray(a[2:]) for a in table["ring_k"] + table["k"]]
    streams = {slot: list(prompts[slot]) for slot in (0, 1)}
    for _ in range(7):
        members = {slot: len(tokens) for slot, tokens in streams.items()}
        chosen = np.asarray(fed)[:4]
        fed, logits, table = _round(exact, table, fed, members)
        for slot, tokens in streams.items():
            tokens.append(int(chosen[slot]))
            np.testing.assert_allclose(np.asarray(logits[slot]),
                                       _want(exact, np.array(tokens), len(tokens) - 1)[0],
                                       atol=ATOL)
    for before, after in zip(held, table["ring_k"] + table["k"]):
        np.testing.assert_array_equal(np.asarray(after[2:]), before)
    fed, table, _ = _chunks(exact, table, fed, 2, prompts[2], first=1)
    first = np.asarray(fed)[2]  # the prompt's last chunk chose it
    fed, logits, table = _round(exact, table, fed, {2: 21})
    want = _want(exact, np.append(prompts[2], first), 20)
    np.testing.assert_allclose(np.asarray(logits[2]), want[1], atol=ATOL)


def _experts_of(decoder, tokens):
    """The experts the reference routes the last of ``tokens`` to, a set a
    layer, and the narrowest margin of its choices."""
    chosen, last = [], len(tokens) - 1

    def routed(x, layer, which, best, s, precision, real):
        chosen.append(set(np.asarray(which)[last].tolist()))
        return original(x, layer, which, best, s, precision, real)

    original = reference._routed
    reference._routed = routed
    try:
        _, margins = reference.forward(decoder._params, CONFIG, tokens, [last])
    finally:
        reference._routed = original
    return chosen, float(margins.min())


def test_unoccupied_slots_add_no_pairs(exact):
    """A round of two members in a table of four: the tally behind the
    choices counts, over the layers, the distinct experts of the two
    members' tokens and no other, and one round; the chunks' pair counts the
    chunks."""
    import jax.numpy as jnp

    table = exact._fresh_table(4)
    fed = jnp.zeros(4 + FED_TALLY, jnp.int32)
    prompts = {1: TOKENS[:18], 3: TOKENS[20:25]}
    for slot, tokens in prompts.items():
        fed, table, _ = _chunks(exact, table, fed, slot, tokens)
    before = np.asarray(fed)[4:]
    assert before[1] == 0 and before[3] == 4  # three chunks and one
    chosen = np.asarray(fed)[:4]
    fed, _, table = _round(exact, table, fed, {1: 18, 3: 5})
    want = 0
    routes = [_experts_of(exact, np.append(tokens, chosen[slot]))
              for slot, tokens in prompts.items()]
    assert min(margin for _, margin in routes) > 1e-4  # no tie to decide
    for layer in zip(*(experts for experts, _ in routes)):
        want += len(set().union(*layer))
    after = np.asarray(fed)[4:]
    assert (after - before).tolist() == [want, 1, 0, 0]
    assert want < 8 * 2 * 2  # some expert is shared: pairs are not counted


def test_the_rows_the_steps_attend_to_are_counted_by_kind(exact):
    """``count_positions``: a decode step at ``t`` reads ``min(t + 1,
    window)`` rows in each of the 6 window layers and ``t + 1`` in each of
    the 2 full layers; a prompt's positions count none."""
    count = RungCount()
    exact.count_positions(count, [0, 9, 15, 16, 40], decoding=True)
    exact.count_positions(count, np.arange(30), decoding=False)
    assert count.attention_rows() == {
        "sliding": 6 * (1 + 10 + 16 + 16 + 16), "full": 2 * (1 + 10 + 16 + 17 + 41)}


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


JOBS = [(13, 20), (3, 30), (19, 12), (17, 25), (30, 30), (5, 8), (12, 40)]


def test_streams_on_the_rounds_and_the_per_stream_loop_give_the_same_tokens(exact):
    """Seven streams over four slots through the core: prompts by chunks
    into their slots beside the rounds in flight, slots taken again by later
    streams, sessions past the window; each gets what it gets alone, and what
    the per-stream loop (a decoder of the same weights without the round)
    gives it. The registry has the rounds' tally and the rows their steps
    attended to."""
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
    alone = WindowRoutedDecoderModel(CONFIG, seed=3, init_scale=arithmetic.init_scale)
    alone._ensure_built()
    alone._round_fn = None
    loop = TinyGenerateModel(decoder=alone)
    for i, (_, budget) in enumerate(JOBS):
        assert out[i] == _alone(exact, prompts[i], budget), i
        assert _tokens(loop, prompts[i], budget) == out[i], i
    totals = model.steps_by_rung.totals()
    assert totals["prefill_tokens"] == sum(n for n, _ in JOBS)
    assert totals["prefill_chunks"] == sum(-(-n // CHUNK) for n, _ in JOBS)
    series = lambda name, label: {
        row["labels"][label]: row["value"] for row in snapshot[name]["series"]
        if row["labels"]["model"] == "tiny_lm_generate"}
    rounds = sum(model.batch_histogram.values())
    assert series("client_tpu_server_experts_reached_rounds", "program") == {
        "round": rounds, "chunk": sum(-(-n // CHUNK) for n, _ in JOBS)}
    reached = series("client_tpu_server_experts_reached", "program")["round"]
    members = sum(n * k for n, k in model.batch_histogram.items())
    assert 8 * rounds <= reached <= 8 * min(2 * members, 8 * rounds)
    # a round's members are at positions prompt .. prompt + budget - 2 (the
    # first token is the prompt's last chunk's)
    steps = [p for n, budget in JOBS for p in range(n, n + budget - 1)]
    reach = np.array(steps) + 1
    assert series("client_tpu_server_attention_rows_read", "kind") == {
        "sliding": 6 * np.minimum(reach, WINDOW).sum(), "full": 2 * reach.sum()}
    # a key and a value row a layer a member, by a turn of a loop off the chip
    assert series("client_tpu_server_rows_written", "path") == {"loop": 2 * 8 * members}


def test_the_sequence_api_gives_the_reference_s_logits(exact):
    """``execute``: a prompt past the window through the chunks, a
    continuation through the step."""
    prompt = [int(t) for t in TOKENS[:21]]
    reply = exact.execute({"TOKENS": np.array([prompt], np.int32)},
                          {"sequence_id": 5, "sequence_start": True})
    np.testing.assert_allclose(reply["LOGITS"][0], _want(exact, np.array(prompt), 20)[0],
                               atol=ATOL)
    nxt = int(reply["NEXT_TOKEN"][0, 0])
    reply = exact.execute({"TOKENS": np.array([[nxt]], np.int32)},
                          {"sequence_id": 5, "sequence_end": True})
    np.testing.assert_allclose(reply["LOGITS"][0],
                               _want(exact, np.array(prompt + [nxt]), 21)[0], atol=ATOL)
