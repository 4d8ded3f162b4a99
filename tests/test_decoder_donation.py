"""The KV cache is written in place.

``jit_step``, ``jit_batched_step`` and ``jit_decode_k`` own the caches they
are given: every cache leaf is donated and aliased to an output, the batched
step writes one row a layer of each *active* slot and touches no other, and a
step that fails after it took its caches leaves the models serving (the
sequence is gone, and says so; no client sees a deleted array).

Counts and equalities only: the CPU deletes a donated input as the chip does.
"""

import re

import numpy as np
import pytest

from client_tpu.models.decoder import TinyDecoderModel
from client_tpu.models.decoder_batched import BatchedDecoderModel
from client_tpu.models.generate import TinyGenerateModel

SLOTS = 4
MAX_LEN = TinyDecoderModel.MAX_LEN
PROGRAMS = ("jit_step", "jit_batched_step", "jit_decode_k")


@pytest.fixture(scope="module")
def built():
    model = BatchedDecoderModel(seed=0, slots=SLOTS)
    model._ensure_built()
    yield model, model._decoder, TinyGenerateModel(decoder=model._decoder)
    model.unload()


def _leaves(caches):
    import jax

    return jax.tree_util.tree_leaves(caches)


def _program(built, name):
    """The jitted program of that name and arguments for one call of it."""
    import jax.numpy as jnp

    model, decoder, generate = built
    if name == "jit_batched_step":
        row = lambda dtype: jnp.zeros((SLOTS,), dtype)
        return model._batched_step, (
            decoder._params, model._fresh_caches(), row(jnp.int32),
            row(jnp.int32), jnp.ones((SLOTS,), bool))
    fn = decoder._step_fn if name == "jit_step" else generate._chunk_fn(2)
    return fn, (decoder._params, decoder._fresh_cache(), 3, 0)


@pytest.mark.parametrize("name", PROGRAMS)
def test_every_cache_leaf_is_aliased_to_an_output(built, name):
    fn, args = _program(built, name)
    text = fn.lower(*args).as_text()
    assert f"module @{name} " in text
    assert len(re.findall(r"tf\.aliasing_output", text)) == 2 * built[1].LAYERS
    assert "jax.buffer_donor" not in text  # donated and left without an output


@pytest.mark.parametrize("name", PROGRAMS)
def test_the_donated_caches_are_dead_after_the_call(built, name):
    fn, args = _program(built, name)
    given = _leaves(args[1])
    _, returned = fn(*args)
    assert len(given) == 2 * built[1].LAYERS
    assert all(leaf.is_deleted() for leaf in given)
    assert not any(leaf.is_deleted() for leaf in _leaves(returned))


def _filled_caches(model, seed):
    """Stacked caches of noise (so that any write shows), as numpy and on
    the device."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    host = jax.tree_util.tree_map(
        lambda leaf: np.asarray(jnp.asarray(
            rng.standard_normal(leaf.shape, dtype=np.float32), leaf.dtype)),
        model._fresh_caches())
    return host, jax.tree_util.tree_map(jnp.asarray, host)


def _by_head(table, heads):
    """A table (or a slot's cache) as ``_fresh_table`` lays it, [.., H/P, M,
    P x Dh], laid a head a row, [.., H, M, Dh]: what the references below
    read and write."""
    *lead, rows, length, width = table.shape
    P = heads // rows
    return table.reshape(*lead, rows, length, P, width // P).swapaxes(
        -3, -2).reshape(*lead, heads, length, width // P)


def _rows_changed(before, after):
    """Positions at which one slot's [H/P, M, P x Dh] cache differs, bit for
    bit."""
    differs = before.view(np.uint16) != after.view(np.uint16)
    return np.flatnonzero(differs.any(axis=(0, 2))).tolist()


def _assert_rows_written(before, after, written):
    """Of stacked caches ``before`` (numpy) and ``after``, slot ``n`` of
    every layer's k and v differs at the positions ``written[n]``, bit for
    bit, and nowhere else."""
    for layer, (layer_before, layer_after) in enumerate(zip(before, after)):
        for half in ("k", "v"):
            got = np.asarray(layer_after[half])
            for slot, rows in enumerate(written):
                changed = _rows_changed(layer_before[half][slot], got[slot])
                assert changed == rows, (layer, half, slot)


@pytest.mark.parametrize("pos, active", [
    # a mixed round
    ([5, 9, 0, 17], [True, False, True, False]),
    # a full slot, whose position clamps onto its last live row, and a freed
    # one (whatever position it was left at) ride beside one active slot
    ([MAX_LEN, 3, MAX_LEN - 1, 0], [False, True, False, False]),
    # every slot, and none
    ([1, 2, 3, 4], [True, True, True, True]),
    ([1, MAX_LEN, 3, 4], [False, False, False, False]),
], ids=["mixed", "full_and_freed", "all", "none"])
def test_a_round_writes_row_pos_of_each_active_slot_and_nothing_else(
        built, pos, active):
    import jax.numpy as jnp

    model, decoder, _ = built
    before, caches = _filled_caches(model, seed=5)
    _, after = model._batched_step(
        decoder._params, caches, jnp.arange(SLOTS, dtype=jnp.int32) + 11,
        jnp.asarray(pos, jnp.int32), jnp.asarray(active))
    _assert_rows_written(
        before, after, [[p] if a else [] for p, a in zip(pos, active)])


@pytest.mark.parametrize("length", [64, 192, 256])
def test_a_table_of_any_length_is_written_as_the_fixtures_is(length):
    """The batcher's rows go through a window of positions where whole
    windows cover the table (256: two), and through one window as long as the
    table where they do not (192) or it is shorter than one (64)."""
    import jax.numpy as jnp

    cls = type("OtherLength", (TinyDecoderModel,), {"MAX_LEN": length})
    model = BatchedDecoderModel(seed=0, slots=SLOTS)
    model._decoder = cls(seed=0)  # composed before the batcher builds
    model._ensure_built()
    try:
        pos = [0, length - 1, length, length // 2 + 3]
        active = [True, True, False, True]
        before, caches = _filled_caches(model, seed=8)
        _, after = model._batched_step(
            model._decoder._params, caches,
            jnp.arange(SLOTS, dtype=jnp.int32) + 11,
            jnp.asarray(pos, jnp.int32), jnp.asarray(active))
        _assert_rows_written(
            before, after, [[p] if a else [] for p, a in zip(pos, active)])
    finally:
        model.unload()


# -- the same over sixteen slots, for both programs that write a table --------

WIDE = 16  # the stream model's table and the benchmark's batcher


@pytest.fixture(scope="module")
def wide():
    """The two programs whose rows ``write_table_rows`` writes, each as
    ``(caches, fed, tokens, pos, active) -> (fed, caches)`` over a table of
    sixteen slots: the slot batcher's step and the stream model's round
    (``fed`` is the round's own carry: the choices of the round before,
    still on the device)."""
    import jax.numpy as jnp

    model = BatchedDecoderModel(seed=0, slots=WIDE)
    model._ensure_built()
    decoder = model._decoder

    def batched_step(caches, fed, tokens, pos, active):
        logits, caches = model._batched_step(
            decoder._params, caches, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(pos, jnp.int32), jnp.asarray(active))
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), caches

    def a_round(caches, fed, tokens, pos, active):
        ctl = np.stack([tokens, pos, active]).astype(np.int32)
        return decoder._round_fn(decoder._params, caches, fed, ctl,
                                 live=MAX_LEN)

    yield model, {"jit_batched_step": batched_step, "jit_step": a_round}
    model.unload()


EVERY = [True] * WIDE
NOBODY = [False] * WIDE
APART = [(7 * slot) % 100 + 1 for slot in range(WIDE)]  # no two alike
BUT_FIVE = [slot != 5 for slot in range(WIDE)]
SOME = [slot % 3 != 1 for slot in range(WIDE)]


def _next(pos, active):
    return [p + a for p, a in zip(pos, active)]


# rounds dispatched one behind the other with nothing read in between, each
# ``(pos, active)``; the tokens are the round's own
ROUND_SEQUENCES = {
    "all_sixteen": [(APART, EVERY)],
    # the batcher's warm-up: the caches come back as they were
    "none": [([0] * WIDE, NOBODY), (APART[:-1] + [MAX_LEN], NOBODY)],
    # a seated slot sits a round out (whatever token rode in its place) and
    # is a member of the next, at the position it was left at
    "sits_out_then_joins": [(APART, BUT_FIVE), (_next(APART, BUT_FIVE), EVERY)],
    # two rounds in flight on one table, as the stream model's worker and
    # the batcher's dispatch them: the second before the first is read
    "two_in_flight": [(APART, SOME), (_next(APART, SOME), SOME)],
}


@pytest.mark.parametrize("sequence", ROUND_SEQUENCES)
@pytest.mark.parametrize("program", ["jit_batched_step", "jit_step"])
def test_rounds_on_a_table_of_sixteen_write_their_members_rows_alone(
        wide, program, sequence):
    import jax.numpy as jnp

    model, programs = wide
    rounds = ROUND_SEQUENCES[sequence]

    def run(rounds, first=0):
        before, caches = _filled_caches(model, seed=7)
        fed = jnp.zeros((WIDE,), jnp.int32)
        for n, (pos, active) in enumerate(rounds, first):
            tokens = np.arange(WIDE) + 11 + WIDE * n
            fed, caches = programs[program](caches, fed, tokens, pos, active)
        return before, caches

    before, after = run(rounds)
    # the last round alone, on the same table: its first layer's rows depend
    # on nothing but its tokens and positions
    _, alone = run(rounds[-1:], first=len(rounds) - 1)
    _assert_rows_written(before, after, [
        [pos[slot] for pos, active in rounds if active[slot]]
        for slot in range(WIDE)])
    last_pos, last_active = rounds[-1]
    for half in ("k", "v"):
        got, want = np.asarray(after[0][half]), np.asarray(alone[0][half])
        for slot in np.flatnonzero(last_active):
            row = (slot, slice(None), last_pos[slot])
            assert got[row].tobytes() == want[row].tobytes(), (half, slot)


def test_the_batched_rows_are_the_single_slot_steps_rows(built):
    """The batcher's row writes against the one-slot definition they stand
    for: an active slot's caches are its own through ``jit_step``, and an
    inactive slot's are what they were before the round."""
    import jax
    import jax.numpy as jnp

    model, decoder, _ = built
    pos, active = [5, 9, 0, 17], [True, False, True, False]
    tokens = [11, 12, 13, 14]
    before, caches = _filled_caches(model, seed=6)
    _, after = model._batched_step(
        decoder._params, caches, jnp.asarray(tokens, jnp.int32),
        jnp.asarray(pos, jnp.int32), jnp.asarray(active))
    for slot in range(SLOTS):
        one = jax.tree_util.tree_map(lambda a: jnp.asarray(a[slot]), before)
        if active[slot]:
            _, one = decoder._step_fn(
                decoder._params, one, tokens[slot], pos[slot])
        for got, want in zip(_leaves(after), _leaves(one)):
            got = np.asarray(got[slot], np.float32)
            want = np.asarray(want, np.float32)
            if active[slot]:
                np.testing.assert_allclose(got, want, atol=2e-2)
            else:
                assert got.tobytes() == want.tobytes()


# -- the batcher's top rung reads its caches where they lie (PR 37) ----------

# narrow widths and 1,024 positions: two rungs, 256 and the top one
LongDecoder = type("LongDecoder", (TinyDecoderModel,), {
    "D_MODEL": 64, "HEADS": 2, "LAYERS": 2, "MAX_LEN": 1024})
TOP = LongDecoder.MAX_LEN
TOP_SLOTS = 8
# members across the rung, some slots out; the second round one on
TOP_POS = [0, 255, 256, 511, 700, 1000, 1022, 400]
TOP_ACTIVE = [True, True, False, True, True, False, True, True]
# where a reference reads a table laid a head a row, what a program of
# another shape leaves (logits, and the rows of the layers after the first;
# tokens are held where the reference's best leads by more than twice this)
OTHER_SHAPE = 1e-2


def _norm(x):
    import jax.numpy as jnp
    from jax import lax

    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + 1e-5)).astype(x.dtype)


def _attention(q, k, v, pos, live):
    """A slot's attention in float32, one product a part, over the first
    ``live`` positions of its cache ``k``, ``v`` [H/P, M, P x Dh] (a head a
    row, P of 1, in the plain products) for its query ``q`` [H, Dh]:
    [H, Dh]. Each head's query takes its own lanes of a row, and each head
    keeps its own lanes of the weighing."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    (heads, dim), (rows, _, width) = q.shape, k.shape
    P = width // dim
    k, v = k[:, :live].astype(f32), v[:, :live].astype(f32)
    if P == 1:
        scores = jnp.einsum("hd,hmd->hm", q.astype(f32), k)
    else:
        spread = (q.astype(f32).reshape(rows, P, 1, dim)
                  * jnp.eye(P, dtype=f32)[:, :, None]).reshape(rows, P, width)
        scores = jnp.einsum("hjc,hmc->hjm", spread, k).reshape(heads, live)
    scores = jnp.where((jnp.arange(live) <= pos)[None, :],
                       scores * (dim ** -0.5), -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    if P == 1:
        return jnp.einsum("hm,hmd->hd", probs, v)
    whole = jnp.einsum("hjm,hmc->hjc", probs.reshape(rows, P, live), v)
    own = np.arange(P)
    return whole.reshape(rows, P, P, dim)[:, own, own].reshape(heads, dim)


def _parent_batched_step(decoder):
    """The reference: the slot batcher's step as PR 36 left it, ``vmap`` of
    the single-slot step through a jitted layer whose attention reads the
    whole cache in one product (``decoder.py:attention`` at the top rung)
    after the row at ``pos`` is written where ``active``; over a table laid
    as it is given."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    D, H = decoder.D_MODEL, decoder.HEADS
    Dh, f32, bf16 = D // H, jnp.float32, jnp.bfloat16

    @jax.jit
    def layer_of(layer, cache, x, pos, active):
        q, k_new, v_new = jnp.split(_norm(x) @ layer["qkv"], 3)
        rows, width = cache["k"].shape[0], cache["k"].shape[-1]
        k, v = (jnp.where(active, lax.dynamic_update_slice(
                    cache[half], row.reshape(rows, 1, width), (0, pos, 0)),
                    cache[half])
                for half, row in (("k", k_new), ("v", v_new)))
        attn = _attention(q.reshape(H, Dh), k, v, pos, TOP)
        x = x + (attn.reshape(D).astype(bf16) @ layer["proj"])
        x = x + (jax.nn.gelu(_norm(x) @ layer["mlp_in"]) @ layer["mlp_out"])
        return x, {"k": k, "v": v}

    def step(params, caches, token, pos, active):
        x = params["embed"][token] + params["pos"][pos]
        new = []
        for layer, cache in zip(params["layers"], caches):
            x, cache = layer_of(layer, cache, x, pos, active)
            new.append(cache)
        return (_norm(x) @ params["unembed"]).astype(f32), new

    return jax.jit(jax.vmap(step, in_axes=(None, 0, 0, 0, 0)))


@pytest.fixture(scope="module")
def two_rungs():
    model = BatchedDecoderModel(seed=0, slots=TOP_SLOTS)
    model._decoder = LongDecoder(seed=0)  # composed before the batcher builds
    model._ensure_built()
    yield model
    model.unload()


def test_the_top_rung_reads_as_the_parents_form_did(two_rungs):
    """Two rounds at the top rung, members at positions across it and some
    slots out: logits and caches are the parent's form's over the same
    table, to what float32 sums in another order leave, and a slot that is
    out of both rounds gets its caches back bit for bit."""
    model = two_rungs
    assert model._decoder._rungs == (256, TOP)
    _top_rung_against_the_parents_form(model, by_head=False)


def _top_rung_against_the_parents_form(model, by_head):
    """The test above for the batcher ``model``; with ``by_head`` the
    reference reads the table laid a head a row, its float32 math, to what a
    program of another shape leaves."""
    import jax
    import jax.numpy as jnp

    decoder = model._decoder
    heads = decoder.HEADS
    lay = (lambda a: _by_head(a, heads)) if by_head else (lambda a: a)
    before, caches = _filled_caches(model, seed=9)
    want_caches = jax.tree_util.tree_map(lambda a: jnp.asarray(lay(a)), before)
    reference = _parent_batched_step(decoder)
    pos, active = TOP_POS, TOP_ACTIVE
    for n in range(2):
        tokens = jnp.arange(TOP_SLOTS, dtype=jnp.int32) + 11 + TOP_SLOTS * n
        args = (tokens, jnp.asarray(pos, jnp.int32), jnp.asarray(active))
        logits, caches = model._batched_step(
            decoder._params, caches, *args, live=TOP)
        want, want_caches = reference(decoder._params, want_caches, *args)
        members = np.flatnonzero(active)
        np.testing.assert_allclose(np.asarray(logits)[members],
                                   np.asarray(want)[members],
                                   atol=OTHER_SHAPE if by_head else 1e-5)
        pos = _next(pos, active)
    _assert_caches_agree(caches, want_caches, before, lay, TOP_ACTIVE)


def _assert_caches_agree(caches, want_caches, before, lay, active):
    """A program's caches against a reference's, which ``lay`` lays as the
    reference reads them: to the rounding of a row a layer on (the first
    layer's rows, which come before any attention, bit for bit), and a slot
    that was never active as it was ``before``."""
    for layer, (got, wanted) in enumerate(zip(caches, want_caches)):
        for half in ("k", "v"):
            g = lay(np.asarray(got[half], np.float32))
            w = np.asarray(wanted[half], np.float32)
            np.testing.assert_allclose(g, w, atol=2e-2)
            if layer == 0:  # its rows come before any attention
                assert g.tobytes() == w.tobytes()
            for slot in np.flatnonzero(~np.asarray(active)):
                assert (np.asarray(got[half][slot]).tobytes()
                        == before[layer][half][slot].tobytes()), (layer, slot)


# -- a narrow-headed round reads every slot where it lies --------------------

ROUND_SLOTS = 16
# members spread over the table and none in its last four slots, which the
# turns' form does not read at all; by rung, the first round's positions
ROUND_ACTIVE = [slot < 12 and slot % 3 != 1 for slot in range(ROUND_SLOTS)]
ROUND_POS = {256: [(37 * slot) % 250 for slot in range(ROUND_SLOTS)],
             TOP: [(61 * slot + 300) % 1022 for slot in range(ROUND_SLOTS)]}


def _turns_round(decoder):
    """The reference: a round whose attention takes the occupied slots in
    turns of ``slots_a_turn``, each turn's slots and the prefix of their
    positions a slice of the table read by ``vmap`` of the single slot's
    attention, the form a round takes where heads fill the lanes; over a
    table laid as it is given. Gives the choices, the caches and the
    logits."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax import lax

    from client_tpu.models.decoder import slots_a_turn

    D, H, M = decoder.D_MODEL, decoder.HEADS, decoder.MAX_LEN
    Dh, f32, bf16 = D // H, jnp.float32, jnp.bfloat16

    # each part a jitted call over the slots, as the round's parts are: the
    # CPU rounds a bfloat16 carried across a call that it keeps in float32
    # within one computation
    def part(fn, in_axes):
        return jax.vmap(jax.jit(fn), in_axes)

    @functools.partial(jax.jit, static_argnames="live")
    def layer_of(layer, cache, x, pos, at, occupied, *, live):
        slots = x.shape[0]
        a_turn = slots_a_turn(slots)
        rows, width = cache["k"].shape[1], cache["k"].shape[-1]
        q, k_new, v_new = jnp.split(
            part(lambda layer, x: _norm(x) @ layer["qkv"], (None, 0))(
                layer, x), 3, axis=-1)
        k, v = (jnp.where(at[:, None, :, None],
                          row.reshape(slots, rows, 1, width), cache[half])
                for half, row in (("k", k_new), ("v", v_new)))
        q = q.reshape(slots, H, Dh)
        read = part(functools.partial(_attention, live=live), 0)

        def turn(n, attn):
            those = functools.partial(
                lax.dynamic_slice_in_dim, start_index=n * a_turn,
                slice_size=a_turn)
            return lax.dynamic_update_slice_in_dim(
                attn, read(those(q), those(k), those(v), those(pos)),
                n * a_turn, 0)

        attn = lax.fori_loop(0, -(-occupied // a_turn), turn,
                             jnp.zeros((slots, H, Dh), f32))

        def rest(layer, x, attn):
            x = x + (attn.reshape(D).astype(bf16) @ layer["proj"])
            return x + (jax.nn.gelu(_norm(x) @ layer["mlp_in"])
                        @ layer["mlp_out"])

        x = part(rest, (None, 0, 0))(layer, x, attn)
        return x, {"k": k, "v": v}

    @functools.partial(jax.jit, static_argnames="live")
    def a_round(params, caches, fed, ctl, *, live):
        given, pos, active = ctl[0], ctl[1], ctl[2] > 0
        slots = active.shape[0]
        occupied = jnp.max(jnp.where(active, jnp.arange(slots) + 1, 0))
        token = jnp.where(given >= 0, given, fed)
        x = params["embed"][token] + params["pos"][pos]
        at = (jnp.arange(M)[None, :] == pos[:, None]) & active[:, None]
        new = []
        for layer, cache in zip(params["layers"], caches):
            x, cache = layer_of(layer, cache, x, pos, at, occupied, live=live)
            new.append(cache)
        logits = jax.vmap(
            lambda x: (_norm(x) @ params["unembed"]).astype(f32))(x)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), new, logits

    return a_round


@pytest.mark.parametrize("live", [256, TOP])
def test_a_narrow_headed_round_reads_as_its_turns_did(live):
    """Two rounds of a table of sixteen at each rung of a decoder whose
    heads are narrower than the lanes, members spread across the table and
    some slots unoccupied, the second fed the first's choices: the members'
    tokens are the turns' form's over the same table, the caches are its to
    what float32 sums in another order leave (the first layer's rows, which
    come before any attention, bit for bit), and an unoccupied slot's caches
    come back as they were."""
    from client_tpu.models.decoder import LANES

    decoder = LongDecoder(seed=0)
    decoder._ensure_built()
    assert decoder._rungs == (256, TOP)
    assert decoder.D_MODEL // decoder.HEADS < LANES
    _round_against_its_turns(decoder, live, by_head=False)


def _round_against_its_turns(decoder, live, by_head):
    """The test above for ``decoder`` at rung ``live``; with ``by_head`` the
    reference reads the table laid a head a row, its float32 math, and a
    member's token is held to the reference's where the reference's best
    logit leads the next by more than twice what a program of another shape
    leaves."""
    import types

    import jax
    import jax.numpy as jnp

    heads = decoder.HEADS
    lay = (lambda a: _by_head(a, heads)) if by_head else (lambda a: a)
    before, caches = _filled_caches(types.SimpleNamespace(
        _fresh_caches=lambda: decoder._fresh_table(ROUND_SLOTS)), seed=10)
    want_caches = jax.tree_util.tree_map(lambda a: jnp.asarray(lay(a)), before)
    reference = _turns_round(decoder)
    fed = want_fed = jnp.zeros((ROUND_SLOTS,), jnp.int32)
    pos, active = ROUND_POS[live], ROUND_ACTIVE
    members = np.flatnonzero(active)
    for n in range(2):
        # a prompt's tokens in the first round, the fed choices in the second
        given = np.arange(ROUND_SLOTS) + 11 if n == 0 else -np.ones(ROUND_SLOTS)
        ctl = np.stack([given, pos, active]).astype(np.int32)
        fed, caches = decoder._round_fn(decoder._params, caches, fed, ctl,
                                        live=live)
        want_fed, want_caches, logits = reference(
            decoder._params, want_caches, want_fed, ctl, live=live)
        held = members
        if by_head:
            top = np.sort(np.asarray(logits)[members], axis=-1)
            held = members[top[:, -1] - top[:, -2] > 2 * OTHER_SHAPE]
            assert len(held) >= len(members) // 2, (n, len(held))
            # the fed choices are the program's own, as the round's next
            # members take them
            want_fed = fed
        np.testing.assert_array_equal(np.asarray(fed)[held],
                                      np.asarray(want_fed)[held])
        pos = _next(pos, active)
    _assert_caches_agree(caches, want_caches, before, lay, active)


# -- a table laid heads_a_row heads a row reads as one laid a head a row -----


@pytest.mark.parametrize("head", [32, 64, 128])
def test_a_table_of_heads_side_by_side_reads_as_a_head_a_row(head):
    """At heads of 32, 64 and 128 (256 wide, two rungs) the table holds as
    many heads a row as fill the lanes, so that a position's row of a slot
    is 128 wide at each; the batcher's top rung gives the logits, and the
    round at both rungs the tokens, of the same float32 math over a table
    laid a head a row, to what a program of another shape leaves, and both
    write the same rows (the tests above, against that layout)."""
    from client_tpu.models.decoder import LANES, heads_a_row

    cls = type(f"Heads{head}", (TinyDecoderModel,), {
        "D_MODEL": 256, "HEADS": 256 // head, "LAYERS": 2, "MAX_LEN": TOP})
    model = BatchedDecoderModel(seed=0, slots=TOP_SLOTS)
    model._decoder = cls(seed=0)  # composed before the batcher builds
    model._ensure_built()
    try:
        decoder = model._decoder
        P = heads_a_row(decoder.HEADS, head)
        assert P * head == LANES
        assert model._fresh_caches()[0]["k"].shape == (
            TOP_SLOTS, decoder.HEADS // P, TOP, LANES)
        _top_rung_against_the_parents_form(model, by_head=True)
        for live in (256, TOP):
            _round_against_its_turns(decoder, live, by_head=True)
    finally:
        model.unload()


# -- the batcher and the round are one table step ----------------------------


@pytest.mark.parametrize("live", [256, TOP], ids=["short", "top"])
@pytest.mark.parametrize("head", [32, 64, 128])
def test_the_batchers_step_and_the_round_are_one_table_step(head, live):
    """At heads of 32, 64 and 128, at a short rung and the top one: from one
    table of sixteen with the same members, positions and tokens, the slot
    batcher's logits choose what the stream model's round chooses for every
    member, and the two tables after the call are bit-equal."""
    import jax
    import jax.numpy as jnp

    cls = type(f"Heads{head}", (TinyDecoderModel,), {
        "D_MODEL": 256, "HEADS": 256 // head, "LAYERS": 2, "MAX_LEN": TOP})
    model = BatchedDecoderModel(seed=0, slots=ROUND_SLOTS)
    model._decoder = cls(seed=0)  # composed before the batcher builds
    model._ensure_built()
    try:
        decoder = model._decoder
        assert decoder._rungs == (256, TOP)
        tokens = np.arange(ROUND_SLOTS) + 11
        pos = [(61 * slot + 300) % (live - 1) for slot in range(ROUND_SLOTS)]
        before, caches = _filled_caches(model, seed=11)
        logits, batched = model._batched_step(
            decoder._params, caches, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(pos, jnp.int32), jnp.asarray(ROUND_ACTIVE),
            live=live)
        ctl = np.stack([tokens, pos, ROUND_ACTIVE]).astype(np.int32)
        chosen, rounded = decoder._round_fn(
            decoder._params, jax.tree_util.tree_map(jnp.asarray, before),
            jnp.zeros((ROUND_SLOTS,), jnp.int32), ctl, live=live)
        members = np.flatnonzero(ROUND_ACTIVE)
        np.testing.assert_array_equal(
            np.argmax(np.asarray(logits), axis=-1)[members],
            np.asarray(chosen)[members])
        for got, want in zip(_leaves(batched), _leaves(rounded)):
            assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    finally:
        model.unload()


# -- a step that fails after it took its caches ------------------------------


def _fails_after(step):
    """``step``, run for its donation, then an error as a device would give."""
    def failing(*args):
        step(*args)
        raise RuntimeError("the device fell over")
    return failing


def _request(model, seq, tokens, start=False, end=False):
    return model.execute(
        {"TOKENS": np.array([tokens], np.int32)},
        {"sequence_id": seq, "sequence_start": start, "sequence_end": end})


def test_decoder_lm_drops_the_sequence_whose_step_failed():
    model = TinyDecoderModel(seed=0)
    first = _request(model, 7, [1, 2, 3], start=True)
    _request(model, 8, [4, 5], start=True)
    step = model._step_fn
    model._step_fn = _fails_after(step)
    with pytest.raises(RuntimeError, match="fell over"):
        _request(model, 7, [9])
    model._step_fn = step
    # typed, and not "Array has been deleted"
    with pytest.raises(ValueError, match="no live state"):
        _request(model, 7, [9])
    assert model.live_sequences() == 1
    _request(model, 8, [6], end=True)  # the other sequence never noticed
    again = _request(model, 7, [1, 2, 3], start=True, end=True)
    np.testing.assert_array_equal(again["LOGITS"], first["LOGITS"])
    assert model.live_sequences() == 0


def test_the_batcher_serves_again_after_a_step_took_the_caches_and_failed():
    reference = TinyDecoderModel(seed=0)
    want = _request(reference, 1, [1, 2, 3], start=True, end=True)
    model = BatchedDecoderModel(seed=0, slots=SLOTS)
    try:
        _request(model, 21, [1, 2, 3], start=True)
        _request(model, 22, [4, 5], start=True)
        step = model._batched_step
        model._batched_step = _fails_after(step)
        with pytest.raises(RuntimeError, match="fell over"):
            _request(model, 21, [9])
        model._batched_step = step
        # every slot's cache went with the step: the bystander ended too
        assert model.live_sequences() == 0
        with pytest.raises(ValueError, match="no live state"):
            _request(model, 22, [6])
        assert not any(leaf.is_deleted() for leaf in _leaves(model._caches))
        got = _request(model, 23, [1, 2, 3], start=True, end=True)
        assert int(got["NEXT_TOKEN"][0, 0]) == int(want["NEXT_TOKEN"][0, 0])
        np.testing.assert_allclose(got["LOGITS"], want["LOGITS"], atol=1e-2)
    finally:
        model.unload()


def test_the_batcher_keeps_bystanders_when_the_step_failed_before_it_ran():
    model = BatchedDecoderModel(seed=0, slots=SLOTS)
    try:
        _request(model, 31, [1, 2, 3], start=True)
        _request(model, 32, [4, 5], start=True)
        step = model._batched_step

        def refused(*args):
            raise RuntimeError("refused before dispatch")

        model._batched_step = refused
        with pytest.raises(RuntimeError, match="refused"):
            _request(model, 31, [9])
        model._batched_step = step
        assert model.live_sequences() == 1  # the window's sequence alone
        _request(model, 32, [6], end=True)
    finally:
        model.unload()


# -- what paced the streams before, the allocator's wait, is gone -------------


def test_a_streams_prefill_waits_for_each_of_its_steps():
    """No step call waits for room any more, so the prefill waits itself: a
    prompt enqueued whole would hold every other stream's next token."""
    model = TinyGenerateModel(seed=0)
    decoder = model._decoder
    decoder._ensure_built()
    decoder._round_fn = None  # the per-stream path: a decoder without a round
    model._ensure_built()
    step, waits = decoder._step_fn, []

    class Watched:
        def __init__(self, logits):
            self.logits = logits

        def block_until_ready(self):
            waits.append(1)
            return self.logits.block_until_ready()

        def __array__(self, *args, **kwargs):
            return np.asarray(self.logits)

    def watched_step(*args):
        logits, caches = step(*args)
        return Watched(logits), caches

    decoder._step_fn = watched_step
    out = list(model.execute_decoupled(
        {"TOKENS": np.array([[1, 2, 3, 4]], np.int32),
         "MAX_TOKENS": np.array([3], np.int32)}, {}))
    assert len(out) == 3
    assert len(waits) == 4  # the prompt's; a decode step's read-back waits
