"""The slot batcher's attention at its top rung, and the stream round's at a
shorter rung, on the real chip, a round's layers at a time.

At the top rung (``live`` equal to the cache's length) every member of a round
of the slot batcher attends to its whole cache. Compiled for a v5e, the
parent's form there, ``vmap`` of ``models/decoder.py:attention``, has every
stacked cache staged through fast memory and back each round, beside the
``while`` that writes the round's rows (PERF.md section 6, PR 37). This tool
times the forms the read could take, each alone, so that the choice among them
rests on a number that is in the repository and not in prose:

- ``parent``: ``vmap`` of ``attention``: one product a stacked cache, read
  whole;
- ``pieces``: the same with the positions cut into static pieces of a
  shortest rung, each piece read as the shortest rung reads its prefix, the
  scores put together before one softmax;
- ``slot_turns``: a ``while`` of turns of ``SLOTS_A_TURN`` slots, each a
  ``dynamic_slice`` of the table (the stream round's ``turn``);
- ``table``: one product over the whole table, written without ``vmap``;
- ``four_turns``: a ``while`` of four turns, a quarter of the positions of
  every slot a turn (a shortest rung of G's ladder), each a slice of the table
  fused into its product; both products at ``HIGHEST`` precision, float32
  as the parent's are (at the default the chip rounds the query and the
  probabilities to bfloat16);
- ``two_turns``: the same in two turns of half the positions (what
  ``models/decoder.py:read_table`` keeps since PR 37);
- ``packed_two_turns``: ``two_turns`` over the table laid as the decoder
  lays it, ``heads_a_row`` heads side by side a row ([16, 10,
  1024, 128]; ``row_write_chip.packed``): each head's query takes its own
  lanes of a [P, 128] operand, zeros elsewhere, one product contracts the
  whole row, and each head keeps its own lanes of the weighing (what
  ``read_table`` does);
- ``packed_lanes_two_turns``: the same table, each turn's rows cut into
  their heads' lanes, [.., P, Dh], and each head's products its own;

at G's table (gpt2-large: 16 slots, 20 heads of 64, 1,024 positions, 36
layers), all sixteen slots members.

The stream round (``models/decoder.py:round_layer``) reads the same table at
C's rung, 256 of the 1,024 positions, in the forms it could take
(``ROUND_FORMS``), each at 16 members of 16 and at 6 of 16 (the lowest
slots, as lowest-free-first admission seats them):

- ``slot_turns``: a ``while`` of turns of ``SLOTS_A_TURN`` slots over the
  occupied ones, each a ``dynamic_slice`` of the table's prefix read by
  ``vmap`` of ``attention`` (the round's form where heads fill the lanes);
- ``every_slot``: ``vmap`` of ``attention`` over every slot's prefix, no
  turns: what the slot batcher's rung-256 step compiles to;
- ``slot_turns_mxu``: ``slot_turns`` with each turn's two products over its
  slice written as one product on the matrix unit, both at ``HIGHEST``, as
  ``read_table`` writes them;
- ``packed_every_slot``, ``packed_lanes_every_slot``, ``packed_slot_turns``:
  ``every_slot`` with ``packed_two_turns``' products at ``HIGHEST`` (the
  one-slot ``attention``: at the default precision the chip read them 1e-3
  to 2e-3 off), the same with ``packed_lanes_two_turns``' products at the
  default, and ``slot_turns`` with the former, over the table laid
  ``heads_a_row`` heads a row.

(Position turns over every slot, ``read_table``'s form at the top rung, are
no candidate here: compiled for a v5e, ``every_slot`` sets nothing of the
table aside at rung 256.) Its rows report the slots each form reads and the
form's read, its dispatch less the rows and weights' at the same members,
in ms and in GB/s of those slots' prefixes; the agreement is with
``slot_turns`` over the members alone (a slot that is no member reads what
it may).

One dispatch is a round's layers without their arithmetic: each makes a query
and a member's rows from the running state (a layer's weights are 12 d^2, as
the GPT-2 block has them), writes the rows into its donated pair of tables
(``row_write_chip``'s ``loop_window``, the decoder's own form), reads the pair
through the form and streams the rest of its weights into the next state. So
each read waits for the layer before it and shares the chip's memory with the
weights and the writes, as in the round's program: alone, with queries that
are inputs, the compiler overlaps the parent's staging with the writes and it
reads cheaper than it does in a round (PERF.md section 6, PR 37).
``rows_and_weights`` is the dispatch with no read (``packed_rows_and_weights``
over the table laid ``heads_a_row`` heads a row, whose rows ``row_write_chip``'s
``packed`` writes; a packed form's read is its dispatch less that one's). The
``shape`` a row reports is the table as its form lays it. Reported: the median
dispatch in ms and the cache bytes the attention reads (each pair once) over
it in GB/s. ``agreement`` says that every form's attention equals the
parent's to a ten-thousandth of its largest value: what float32 sums in
another order leave, and no mask, position or rounding out of place (the
parent takes the query as its product left it, unrounded: a form that
rounds it to bfloat16 reads 6e-4 off on the chip).

Run on the chip (or with --small off the chip for a pipeline check):
    python tools/top_rung_read_chip.py [--json-out PATH] [--small]
        [--forms two_turns,packed_two_turns]
        [--round-forms every_slot,packed_every_slot]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import row_write_chip  # noqa: E402

# name: (table shape [slots, H, M, Dh], layers a round reads, a shortest rung)
TABLES = {"gpt2-large": ((16, 20, 1024, 64), 36, 256)}
SMALL_TABLES = {"small": ((4, 2, 64, 8), 2, 16)}
FORMS = ("parent", "pieces", "slot_turns", "table", "four_turns", "two_turns",
         "packed_two_turns", "packed_lanes_two_turns")
# name: (table shape, layers a round reads, the rung, members of a round)
ROUND_TABLES = {"gpt2-large": ((16, 20, 1024, 64), 36, 256, (16, 6))}
SMALL_ROUND_TABLES = {"small": ((8, 2, 64, 8), 2, 16, (8, 3))}
ROUND_FORMS = ("slot_turns", "every_slot", "slot_turns_mxu",
               "packed_every_slot", "packed_lanes_every_slot",
               "packed_slot_turns")
# the largest difference from the parent's attention a form may leave, over
# the parent's largest value
AGREE = 1e-4


def is_packed(form):
    """Whether ``form`` reads the table laid ``heads_a_row`` heads a row."""
    return form.startswith("packed")


def packed_products(jnp, head, precision=None):
    """``(score, weigh)`` over a table laid ``heads_a_row`` heads a row, for
    heads of ``head``: ``score(q, k)`` of the queries [.., H/P, P x Dh] (a
    row's heads side by side, as the table lays a position) and the keys
    [.., H/P, m, P x Dh] is [.., H/P, P, m]; ``weigh(probs, v)`` of those
    over the values is [.., H/P, P x Dh], each head in its own lanes. Each
    head's query takes its own lanes of a [P, P x Dh] operand, zeros
    elsewhere, and one product contracts the whole row; each head keeps its
    own lanes of the weighing by a select and a sum (``decoder.py:weighed``).
    """
    f32 = jnp.float32

    def score(q, k):
        *lead, rows, width = q.shape
        P = width // head
        spread = (q.astype(f32).reshape(*lead, rows, P, 1, head)
                  * jnp.eye(P, dtype=f32)[:, :, None]).reshape(
                      *lead, rows, P, width)
        return jnp.einsum("...hjc,...hmc->...hjm", spread, k.astype(f32),
                          precision=precision)

    def weigh(probs, v):
        whole = jnp.einsum("...hjm,...hmc->...hjc", probs, v.astype(f32),
                           precision=precision)
        P, width = whole.shape[-2:]
        own = jnp.arange(width) // head == jnp.arange(P)[:, None]
        return jnp.sum(jnp.where(own, whole, 0.0), axis=-2)

    return score, weigh


def lanes_products(jnp, head, precision=None):
    """``packed_products``' pair with each row cut into its heads' lanes,
    [.., P, Dh], and each head's products its own."""
    f32 = jnp.float32

    def score(q, k):
        *lead, rows, width = q.shape
        P = width // head
        return jnp.einsum(
            "...hpd,...hmpd->...hpm",
            q.astype(f32).reshape(*lead, rows, P, head),
            k.astype(f32).reshape(*k.shape[:-1], P, head),
            precision=precision)

    def weigh(probs, v):
        P = probs.shape[-2]
        out = jnp.einsum("...hpm,...hmpd->...hpd", probs,
                         v.astype(f32).reshape(*v.shape[:-1], P, head),
                         precision=precision)
        return out.reshape(*out.shape[:-2], P * head)

    return score, weigh


def packed_read(jax, jnp, products, head, live=None):
    """One slot's attention at rung ``live`` (its whole cache, unless given)
    through ``products`` (``packed_products`` or ``lanes_products``): the
    query [H/P, P x Dh], the caches [H/P, M, P x Dh], float32 [H/P, P x
    Dh]."""
    score, weigh = products

    def one(q, k, v, pos):
        reach = live or k.shape[1]
        scores = score(q, k[:, :reach]) * (head ** -0.5)
        scores = jnp.where((jnp.arange(reach) <= pos)[None, None, :],
                           scores, -jnp.inf)
        return weigh(jax.nn.softmax(scores, axis=-1), v[:, :reach])

    return one


def forms(jax, jnp, lax, piece, head=None):
    """Every form, each ``(q, k, v, pos) -> attention`` over the queries
    [slots, H, Dh] bf16, the stacked caches (k, v) [slots, H, M, Dh] bf16 and
    the positions int32 [slots]: float32 [slots, H, Dh]; a packed form over
    queries [slots, H/P, P x Dh] and caches [slots, H/P, M, P x Dh] laid as
    ``row_write_chip.packed`` lays them, heads of ``head``: float32 [slots,
    H/P, P x Dh]."""
    from client_tpu.models.decoder import slots_a_turn

    f32 = jnp.float32

    def one(q, k, v, pos):
        # models/decoder.py:attention at the top rung, as the parent read it
        dim, live = q.shape[-1], k.shape[1]
        scores = jnp.einsum("hd,hmd->hm", q.astype(f32),
                            k.astype(f32)) * (dim ** -0.5)
        scores = jnp.where((jnp.arange(live) <= pos)[None, :], scores, -jnp.inf)
        return jnp.einsum("hm,hmd->hd", jax.nn.softmax(scores, axis=-1),
                          v.astype(f32))

    def parent(q, k, v, pos):
        return jax.vmap(one)(q, k, v, pos)

    def pieces(q, k, v, pos):
        def each(q, k, v, pos):
            dim, live = q.shape[-1], k.shape[1]
            cut = [slice(at, at + piece) for at in range(0, live, piece)]
            scores = jnp.concatenate(
                [jnp.einsum("hd,hmd->hm", q.astype(f32), k[:, c].astype(f32))
                 for c in cut], axis=-1) * (dim ** -0.5)
            scores = jnp.where((jnp.arange(live) <= pos)[None, :], scores,
                               -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            return sum(jnp.einsum("hm,hmd->hd", probs[:, c], v[:, c].astype(f32))
                       for c in cut)

        return jax.vmap(each)(q, k, v, pos)

    def slot_turns(q, k, v, pos):
        slots, q = q.shape[0], q.astype(f32)
        a_turn = slots_a_turn(slots)
        attend = jax.vmap(one)

        def turn(n, attn):
            those = functools.partial(lax.dynamic_slice_in_dim,
                                      start_index=n * a_turn, slice_size=a_turn)
            return lax.dynamic_update_slice_in_dim(
                attn, attend(those(q), those(k), those(v), those(pos)),
                n * a_turn, 0)

        return lax.fori_loop(0, slots // a_turn, turn,
                             jnp.zeros(q.shape, f32))

    def table(q, k, v, pos):
        live, dim = k.shape[2], q.shape[-1]
        scores = jnp.einsum("shd,shmd->shm", q.astype(f32),
                            k.astype(f32)) * (dim ** -0.5)
        mask = jnp.arange(live)[None, :] <= pos[:, None]
        scores = jnp.where(mask[:, None, :], scores, -jnp.inf)
        return jnp.einsum("shm,shmd->shd", jax.nn.softmax(scores, axis=-1),
                          v.astype(f32))

    def turns(count):
        def read(q, k, v, pos):
            slots, heads, live, dim = k.shape
            span = live // count
            rows = lambda cache, n: lax.dynamic_slice_in_dim(
                cache, n * span, span, axis=2)

            def score(n, scores):
                return lax.dynamic_update_slice_in_dim(
                    scores,
                    jnp.einsum("shd,shmd->shm", q.astype(f32),
                               rows(k, n).astype(f32),
                               precision=lax.Precision.HIGHEST),
                    n * span, axis=2)

            scores = lax.fori_loop(0, count, score,
                                   jnp.zeros((slots, heads, live), f32))
            mask = jnp.arange(live)[None, :] <= pos[:, None]
            scores = jnp.where(mask[:, None, :], scores * (dim ** -0.5),
                               -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)

            def weigh(n, attn):
                return attn + jnp.einsum(
                    "shm,shmd->shd",
                    lax.dynamic_slice_in_dim(probs, n * span, span, axis=2),
                    rows(v, n).astype(f32), precision=lax.Precision.HIGHEST)

            return lax.fori_loop(0, count, weigh,
                                 jnp.zeros((slots, heads, dim), f32))

        return read

    def packed_turns(products):
        score, weigh = products

        def read(q, k, v, pos):
            slots, rows, live, width = k.shape
            span, P = live // 2, width // head
            cut = lambda a, n: lax.dynamic_slice_in_dim(a, n * span, span,
                                                        axis=2)

            def scored(n, scores):
                return lax.dynamic_update_slice_in_dim(
                    scores, score(q, cut(k, n)), n * span, axis=3)

            scores = lax.fori_loop(
                0, 2, scored, jnp.zeros((slots, rows, P, live), f32))
            mask = jnp.arange(live)[None, :] <= pos[:, None]
            scores = jnp.where(mask[:, None, None, :], scores * (head ** -0.5),
                               -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)

            def weighed(n, attn):
                return attn + weigh(
                    lax.dynamic_slice_in_dim(probs, n * span, span, axis=3),
                    cut(v, n))

            return lax.fori_loop(0, 2, weighed,
                                 jnp.zeros((slots, rows, width), f32))

        return read

    highest = lax.Precision.HIGHEST
    return dict(zip(FORMS, (
        parent, pieces, slot_turns, table, turns(4), turns(2),
        packed_turns(packed_products(jnp, head, highest)),
        packed_turns(lanes_products(jnp, head, highest)))))


def round_forms(jax, jnp, lax, live, head=None):
    """The stream round's forms at rung ``live``, each ``(q, k, v, pos,
    active) -> attention`` over the operands of ``forms`` and the round's
    members ``active`` bool [slots]: float32 [slots, H, Dh] (a packed
    form's as ``forms`` has them), a member's row its attention over its
    cache's first ``live`` positions."""
    from client_tpu.models.decoder import slots_a_turn

    f32 = jnp.float32

    def one(q, k, v, pos):
        # models/decoder.py:attention at rung ``live``
        dim = q.shape[-1]
        scores = jnp.einsum("hd,hmd->hm", q.astype(f32),
                            k[:, :live].astype(f32)) * (dim ** -0.5)
        scores = jnp.where((jnp.arange(live) <= pos)[None, :], scores, -jnp.inf)
        return jnp.einsum("hm,hmd->hd", jax.nn.softmax(scores, axis=-1),
                          v[:, :live].astype(f32))

    def on_the_matrix_unit(q, k, v, pos):
        # the same over slots [n, H, live, Dh], each product one of the
        # matrix unit's, as models/decoder.py:read_table writes them
        product = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST)
        scores = product("shd,shmd->shm", q.astype(f32),
                         k.astype(f32)) * (q.shape[-1] ** -0.5)
        mask = jnp.arange(live)[None, :] <= pos[:, None]
        scores = jnp.where(mask[:, None, :], scores, -jnp.inf)
        return product("shm,shmd->shd", jax.nn.softmax(scores, axis=-1),
                       v.astype(f32))

    def turns(attend):
        def read(q, k, v, pos, active):
            slots, heads, _, dim = k.shape
            a_turn = slots_a_turn(slots)
            occupied = jnp.max(jnp.where(active, jnp.arange(slots) + 1, 0))

            def turn(n, attn):
                at = n * a_turn
                those = functools.partial(
                    lax.dynamic_slice_in_dim, start_index=at, slice_size=a_turn)
                prefix = lambda cache: lax.dynamic_slice(
                    cache, (at, 0, 0, 0), (a_turn, heads, live, dim))
                return lax.dynamic_update_slice_in_dim(
                    attn, attend(those(q), prefix(k), prefix(v), those(pos)),
                    at, 0)

            return lax.fori_loop(0, -(-occupied // a_turn), turn,
                                 jnp.zeros((slots, heads, dim), f32))

        return read

    def every_slot(q, k, v, pos, active):
        return jax.vmap(one)(q, k, v, pos)

    packed_one = packed_read(
        jax, jnp, packed_products(jnp, head, lax.Precision.HIGHEST), head, live)
    lanes_one = packed_read(jax, jnp, lanes_products(jnp, head), head, live)
    return dict(zip(ROUND_FORMS, (
        turns(jax.vmap(one)), every_slot, turns(on_the_matrix_unit),
        lambda q, k, v, pos, active: jax.vmap(packed_one)(q, k, v, pos),
        lambda q, k, v, pos, active: jax.vmap(lanes_one)(q, k, v, pos),
        turns(jax.vmap(packed_one)))))


def slots_read(form, slots, members):
    """The slots whose caches a round form reads, of a table of ``slots``
    with its ``members`` lowest slots occupied."""
    from client_tpu.models.decoder import in_whole_turns

    return (slots if form.endswith("every_slot")
            else in_whole_turns(slots, members))


def _top_rung(read):
    """A top-rung form as ``layered`` calls a read: every slot a member."""
    return read and (lambda q, k, v, pos, active: read(q, k, v, pos))


def layered(jax, jnp, lax, read, packed=False):
    """One dispatch: the layers of a round without their arithmetic. Each
    makes a query and a member's rows from the running state, writes the
    rows into its donated pair of tables (``row_write_chip``'s ``packed``
    where the tables are laid so), reads the pair through ``read`` (or not
    at all, for ``None``) and streams the rest of its weights into the next
    state. Returns the tables and every layer's attention. The query and
    the rows are laid as a position of the table."""
    write = row_write_chip.forms(jnp, lax)[
        "packed" if packed else "loop_window"]

    def dispatch(tables, weights, x, pos, active):
        slots, heads, _, dim = tables[0][0].shape
        split = lambda part: part.reshape(slots, heads, 1, dim)
        out, attns = [], []
        for caches, (qkv, rest) in zip(tables, weights):
            q, k_new, v_new = jnp.split(x @ qkv, 3, axis=-1)
            caches = write(caches, (split(k_new), split(v_new)), pos, active)
            out.append(caches)
            q = q.reshape(slots, heads, dim)
            if read is None:
                attn = q.astype(jnp.float32)
            else:
                attn = read(q, *caches, pos, active)
                attns.append(attn)
            y = attn.reshape(slots, heads * dim).astype(jnp.bfloat16) @ rest
            x = x + y.reshape(slots, -1, heads * dim).sum(axis=1).astype(
                jnp.bfloat16)
        return out, (jnp.stack(attns) if attns else x)

    return jax.jit(dispatch, donate_argnums=0)


def _operands(jax, jnp, np, shape, layers, seed=0, reach=None, members=None,
              packed=False):
    """``row_write_chip``'s tables (laid as its ``packed`` form lays them,
    where ``packed``), a layer's weights (the query's and the rows' 3 d^2
    and 9 d^2 more, as the GPT-2 block has 12 d^2) made on the device, a
    running state, the ``members`` lowest slots members (every slot, unless
    given) at positions across the first ``reach`` of the table (all of it,
    unless given)."""
    tables, _, _, _ = row_write_chip._operands(
        jnp, np, shape, layers, shape[0], seed,
        form="packed" if packed else None)
    slots, heads, length, dim = shape
    width = heads * dim
    keys = jax.random.split(jax.random.PRNGKey(seed), 2 * layers + 1)
    draw = lambda key, *dims: (jax.random.normal(key, dims, jnp.float32)
                               * width ** -0.5).astype(jnp.bfloat16)
    weights = [(draw(keys[2 * n], width, 3 * width),
                draw(keys[2 * n + 1], width, 9 * width))
               for n in range(layers)]
    x = draw(keys[-1], slots, width) * width ** 0.5
    reach = reach or length
    pos = jnp.asarray((reach // 2 + 37 * np.arange(slots)) % reach, jnp.int32)
    members = slots if members is None else members
    return tables, weights, x, pos, jnp.asarray(np.arange(slots) < members)


def _by_slot(attn, np):
    """Every layer's attention [layers, slots, ..] as [layers, slots, H x
    Dh], a head's lanes after another's in either layout."""
    attn = np.asarray(attn, np.float64)
    return attn.reshape(attn.shape[0], attn.shape[1], -1)


def check_agreement(jax, jnp, np, lax, tables, chosen=FORMS):
    """Each form of ``chosen`` its attention after one dispatch against the
    parent's."""
    cases, ok = [], True
    for name, (shape, layers, piece) in tables.items():
        made = forms(jax, jnp, lax, piece, shape[3])
        got = {}
        for form_name in ("parent",) + tuple(f for f in chosen
                                             if f != "parent"):
            packed = is_packed(form_name)
            operands = _operands(jax, jnp, np, shape, 1, packed=packed)
            _, attn = layered(jax, jnp, lax, _top_rung(made[form_name]),
                              packed)(*operands)
            got[form_name] = _by_slot(attn, np)
        scale = np.abs(got["parent"]).max()
        case = {"table": name, "shape": list(shape)}
        for form_name in [f for f in FORMS[1:] if f in got]:
            worst = float(np.abs(got[form_name] - got["parent"]).max() / scale)
            case[form_name] = {"agrees": worst <= AGREE, "worst": worst}
            ok = ok and worst <= AGREE
        cases.append(case)
    return {"ok": ok, "cases": cases}


BASELINES = ("rows_and_weights", "packed_rows_and_weights")


def _baselines(chosen):
    """The dispatches with no read that ``chosen`` forms are read against:
    one a layout they take."""
    return tuple(b for b in BASELINES
                 if any(is_packed(f) == is_packed(b) for f in chosen))


def bench_forms(jax, jnp, np, lax, tables, repeats, chosen=FORMS):
    """The median dispatch of every form of ``chosen``, and of the rows and
    weights alone."""
    out = []
    for name, (shape, layers, piece) in tables.items():
        made = forms(jax, jnp, lax, piece, shape[3])
        slots, heads, length, dim = shape
        read_bytes = 2 * slots * heads * length * dim * 2 * layers
        for form_name in _baselines(chosen) + tuple(chosen):
            packed = is_packed(form_name)
            program = layered(jax, jnp, lax, _top_rung(made.get(form_name)),
                              packed)
            operands = _operands(jax, jnp, np, shape, layers, packed=packed)
            row = {"table": name, "shape": list(operands[0][0][0].shape),
                   "layers": layers, "form": form_name}
            ms = _time(jax, program, operands, repeats, row)
            if ms and form_name not in BASELINES:
                row["read_gb_s"] = round(read_bytes / ms / 1e6, 1)
            out.append(row)
    return out


def _time(jax, program, operands, repeats, row):
    """The median of ``repeats`` dispatches of ``program`` after a first, in
    ms, also into ``row``; an error in ``row`` and None where it fails."""
    try:
        caches, rest = operands[0], operands[1:]
        caches, attn = program(caches, *rest)
        jax.block_until_ready((caches, attn))  # compiled and warm
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            caches, attn = program(caches, *rest)
            jax.block_until_ready((caches, attn))
            times.append(time.perf_counter() - t0)
        ms = sorted(times)[len(times) // 2] * 1000
        row["ms_a_dispatch"] = round(ms, 4)
        del caches, attn
        return ms
    except Exception as e:
        row["error"] = f"{type(e).__name__}: {e}"[:300]
        return None


def check_round_agreement(jax, jnp, np, lax, tables, chosen=ROUND_FORMS):
    """Each round form of ``chosen`` its attention of the members after one
    dispatch against ``slot_turns``'s, at every number of members."""
    cases, ok = [], True
    for name, (shape, _, live, memberships) in tables.items():
        made = round_forms(jax, jnp, lax, live, shape[3])
        for members in memberships:
            got = {}
            for form_name in ("slot_turns",) + tuple(
                    f for f in chosen if f != "slot_turns"):
                packed = is_packed(form_name)
                operands = _operands(jax, jnp, np, shape, 1, reach=live,
                                     members=members, packed=packed)
                _, attn = layered(jax, jnp, lax, made[form_name],
                                  packed)(*operands)
                got[form_name] = _by_slot(attn, np)[:, :members]
            scale = np.abs(got["slot_turns"]).max()
            case = {"table": name, "shape": list(shape), "live": live,
                    "members": members}
            for form_name in [f for f in ROUND_FORMS[1:] if f in got]:
                worst = float(
                    np.abs(got[form_name] - got["slot_turns"]).max() / scale)
                case[form_name] = {"agrees": worst <= AGREE, "worst": worst}
                ok = ok and worst <= AGREE
            cases.append(case)
    return {"ok": ok, "cases": cases}


def bench_round_forms(jax, jnp, np, lax, tables, repeats, chosen=ROUND_FORMS):
    """The median dispatch of every round form of ``chosen``, and of the
    rows and weights alone, at every number of members; a form's read is
    its dispatch less the rows and weights' in its layout, and its GB/s are
    of the slots it reads over that."""
    out = []
    for name, (shape, layers, live, memberships) in tables.items():
        made = round_forms(jax, jnp, lax, live, shape[3])
        slots, heads, _, dim = shape
        for members in memberships:
            alone = {}
            for form_name in _baselines(chosen) + tuple(chosen):
                packed = is_packed(form_name)
                program = layered(jax, jnp, lax, made.get(form_name), packed)
                operands = _operands(jax, jnp, np, shape, layers, reach=live,
                                     members=members, packed=packed)
                row = {"table": name, "shape": list(operands[0][0][0].shape),
                       "layers": layers, "live": live, "members": members,
                       "form": form_name}
                ms = _time(jax, program, operands, repeats, row)
                if form_name in BASELINES:
                    alone[packed] = ms
                else:
                    rest = alone.get(packed)
                    row["slots_read"] = slots_read(form_name, slots, members)
                    if ms and rest:
                        row["read_ms"] = round(ms - rest, 4)
                    if ms and rest and ms > rest:
                        row["read_gb_s"] = round(
                            2 * row["slots_read"] * heads * live * dim * 2
                            * layers / (ms - rest) / 1e6, 1)
                out.append(row)
    return out


def run(small: bool, repeats: int = 15, chosen=FORMS,
        round_chosen=ROUND_FORMS):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    tables = SMALL_TABLES if small else TABLES
    round_tables = SMALL_ROUND_TABLES if small else ROUND_TABLES
    device = jax.devices()[0]
    result = {"platform": jax.default_backend(),
              "device_kind": device.device_kind}
    for key, check, bench, of, these in (
            ("", check_agreement, bench_forms, tables, chosen),
            ("round_", check_round_agreement, bench_round_forms, round_tables,
             round_chosen)):
        try:
            result[key + "agreement"] = check(jax, jnp, np, lax, of, these)
        except Exception as e:
            result[key + "agreement"] = {
                "ok": False, "error": f"{type(e).__name__}: {e}"[:500]}
        result[key + "forms"] = bench(jax, jnp, np, lax, of, repeats, these)
    return result


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--json-out", default=None)
    parser.add_argument("--small", action="store_true",
                        help="tables of four and eight slots, two layers: a "
                        "pipeline check off the chip, no number of the chip's")
    parser.add_argument("--repeats", type=int, default=15,
                        help="timed dispatches a form; the median is reported")
    parser.add_argument("--forms", type=row_write_chip._chosen(FORMS),
                        default=FORMS, help="the top rung's forms to time, a "
                        "comma between (every one unless given); each is "
                        "checked against parent")
    parser.add_argument("--round-forms", type=row_write_chip._chosen(
                            ROUND_FORMS), default=ROUND_FORMS,
                        help="the round's forms to time, the same way; each "
                        "is checked against slot_turns")
    args = parser.parse_args(argv)

    result = run(args.small, args.repeats, args.forms, args.round_forms)
    text = json.dumps(result, indent=1)
    print(text)
    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(text + "\n")
    agreed = all(result[key].get("ok")
                 for key in ("agreement", "round_agreement"))
    return 0 if agreed else 1


if __name__ == "__main__":
    sys.exit(main())
