"""A decoder read from a configuration whose attention keeps two kinds of
state: an exact window and summaries of everything before it.

``WindowSummaryDecoderModel(config)`` is served as ``TinyDecoderModel`` is:
by ``TinyGenerateModel(decoder=...)`` on the decoupled stream, where its
streams share rounds (``stream_rounds.py``) and a prompt is taken a chunk a
dispatch into its slot, and by its own ``execute`` on the sequence API. Its
sizes are the keys of a ``config.json`` with ``window_size``, ``chunk_size``
and ``num_pred_heads``; no model's name appears here.

The block, for a token's float32 residual ``x`` at position ``t``, with
``s = head_dim ** -0.5``, window ``w = t // window_size``:

- ``a = rms(x) * (1 + ln1)``; ``q = a wq``, ``k = a wk``, ``v = a wv`` (as
  many key-value heads as query heads, no bias); rotate-half rotary over the
  whole head on ``q`` and ``k``.
- A chunk ``c`` of ``chunk_size`` positions, once complete, has a head a
  summary: ``kbar = sum_j softmax_j(s k_j . mu) k_j`` and ``vbar = sum_j
  softmax_j(s k_j . phi) v_j`` over its rows (``mu``, ``phi``: a learned
  vector a head).
- The token attends, in one softmax, to the positions ``j <= t`` of its own
  window exactly and to the summaries of every chunk of every earlier
  window.
- ``x += o wo``; ``b = rms(x) * (1 + ln2)``; ``x += (silu(b wg) * (b wu)) wd``.
- ``rms(x) * (1 + final_norm)``, then ``num_pred_heads`` heads of
  ``vocab_size`` logits each (head ``i`` scores the token at ``t + 1 + i``);
  head 0's is the token served.

What the serving path is made of:

- **Two kinds of state in one slot, a layer**: a *ring* of ``window_size``
  key and value rows (position ``t`` lies in row ``t mod window_size``) and a
  *summary table* of one key and one value row a chunk, ``max_len /
  chunk_size`` rows; ``[slots, heads, rows, head_dim]``, head-major, so that a
  step's products read them as they lie. Nothing of a slot is ever cleared:
  a ring row past ``t mod window_size`` (the window before, or another
  stream's) and a summary row from ``(window_size / chunk_size) * w`` on are
  masked by position. A single sequence's cache is a table of one slot.
- **A ladder of summary rows for the prompt's chunks** (``summary_ladder``):
  a chunk's program at rung ``live`` reads the first ``live`` rows of the
  summary table beside the whole ring (its kernel takes compile-time
  shapes); the host picks the shortest rung that covers the position's
  ``(window_size / chunk_size) * w`` (``rung_for``). Rung 0 is the first
  window: no summary is read and the program has none of that code. The
  round has no rung: it reads the summaries a block of two windows' worth at
  a time, a turn as many blocks as its furthest member's position needs.
- **The step is the round** (``jit_step``): one program advances every
  occupied slot of a table by one token, and a single sequence is a table
  of one. The products with the weights take every slot; the ring rows are
  written a turn of a loop an active slot, and the chunks that this round's
  tokens complete are gathered, summarised together and written, a turn a
  such slot; the attention is one softmax worked as parts, the ring's rows
  and the summary rows a block at a time, each kind over the occupied slots
  ``decoder.SLOTS_A_TURN`` a turn, merged by their largest scores and their
  sums. Plain XLA: the products over ``[heads, rows, head_dim]`` need no
  other layout, and read the state at 85% of HBM speed (my chip run, PR 34).
- **A prompt goes a chunk a dispatch into its slot** (``jit_slot_prefill``):
  ``prefill_chunk`` positions on the table's own grid of that size (a chunk
  never straddles a window), their ring rows written, every summary the
  chunk completes written, their attention one call of
  ``ops/chunk_attention.py`` over ``[summary rows | ring rows]`` laid
  position-major for it (the kernel's skip rule takes a key block's index
  for a position: with the summaries first and the base moved by their
  count it holds as it is). The last chunk of a prompt leaves head 0's
  choice where the next round reads it.

Weights, state and matrix products in the configuration's ``dtype``
(bfloat16 where it states none; float32 accumulation); a float32 residual
stream, norms, rotary, attention softmax, chunk weights and logits.

Named scopes: ``embed``, ``attn_qkv``, ``rope``, ``cache_update``,
``summarise``, ``window_attention``, ``summary_attention`` (the chunk's one
kernel: ``eva_attention``), ``attn_proj``, ``mlp``, ``unembed``.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import numpy as np

from ..server.timeline import SPAN_PREFILL_CHUNK, span
from .decoder import RungCount, TinyDecoderModel, in_whole_turns, slots_a_turn
from .routed_decoder import rms, rotary, rotary_table, seeded_params

# the shortest rung that reads summaries covers this many windows' worth
SHORTEST_RUNG_WINDOWS = 4


class Sizes(NamedTuple):
    """What the block reads of a configuration."""

    vocab: int
    d_model: int
    layers: int
    heads: int
    head_dim: int
    mlp_width: int
    window: int
    chunk: int
    pred_heads: int
    eps: float
    theta: float
    unit_offset: bool
    max_len: int
    prefill_chunk: int
    dtype: str

    @property
    def summaries_a_window(self) -> int:
        return self.window // self.chunk

    @property
    def summary_rows(self) -> int:
        return self.max_len // self.chunk


def sizes_of(config: Dict[str, Any]) -> Sizes:
    """The configuration's keys, checked against what this block can run."""
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    if int(config.get("num_key_value_heads", heads)) != heads:
        raise ValueError("every query head has a key-value head of its own")
    if d % heads:
        raise ValueError(f"{d} is not {heads} whole heads")
    window, chunk = int(config["window_size"]), int(config["chunk_size"])
    # the positions a sequence may reach here: what is reserved, where the
    # file says so, and the published context otherwise
    max_len = int(config.get("reserved_positions", config["max_position_embeddings"]))
    prefill_chunk = int(config.get("prefill_chunk", min(512, window)))
    if window % prefill_chunk or prefill_chunk % chunk or max_len % window:
        raise ValueError(
            f"{max_len} positions, windows of {window}, prefill chunks of "
            f"{prefill_chunk} and chunks of {chunk} do not divide each other")
    return Sizes(
        vocab=int(config["vocab_size"]), d_model=d,
        layers=int(config["num_hidden_layers"]), heads=heads, head_dim=d // heads,
        mlp_width=int(config["intermediate_size"]), window=window, chunk=chunk,
        pred_heads=int(config.get("num_pred_heads", 1)),
        eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]),
        unit_offset=bool(config.get("norm_add_unit_offset", False)),
        max_len=max_len, prefill_chunk=prefill_chunk,
        dtype=str(config.get("dtype", "bfloat16")))


def summary_ladder(s: Sizes) -> Tuple[int, ...]:
    """The counts of summary rows a program may read, shortest first: none
    (the first window), then the whole table, half of it, a quarter ... for
    as long as the rung covers ``SHORTEST_RUNG_WINDOWS`` windows' summaries.
    The ratio is 2 where the positions' ladder has 4 (``decoder.ladder_of``):
    a round's rung is its furthest member's, and at the top rung the
    summaries cost a member as many bytes as its ring."""
    rungs = [s.summary_rows]
    while rungs[0] // 2 >= SHORTEST_RUNG_WINDOWS * s.summaries_a_window:
        rungs.insert(0, rungs[0] // 2)
    return (0,) + tuple(rungs)


def param_shapes(s: Sizes):
    """The weights' tree as ``jax.ShapeDtypeStruct``."""
    import jax
    import jax.numpy as jnp

    w = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.dtype(s.dtype))
    d, f = s.d_model, s.mlp_width
    layer = lambda: {
        "ln1": w(d), "wq": w(d, d), "wk": w(d, d), "wv": w(d, d), "wo": w(d, d),
        "mu": w(s.heads, s.head_dim), "phi": w(s.heads, s.head_dim),
        "ln2": w(d), "wg": w(d, f), "wu": w(d, f), "wd": w(f, d)}
    return {"embed": w(s.vocab, d), "layers": [layer() for _ in range(s.layers)],
            "final_norm": w(d), "unembed": w(d, s.pred_heads * s.vocab)}


def plain_scale(path: Tuple[str, ...], leaf):
    """The deviation (or ``(mean, deviation)``) a seeded model's leaf is
    drawn at unless its maker brings a rule (``init_scale``): gains 0 about
    the unit offset, the chunk vectors and the table 1, a matrix by its
    fan-in."""
    name = path[-1]
    if name in ("mu", "phi", "embed"):
        return 1.0
    if len(leaf.shape) == 1:
        return (0.0, 0.0)
    return leaf.shape[0] ** -0.5


def summarise(keys, values, mu, phi, scale: float):
    """The summaries of complete chunks: ``keys``, ``values`` [..., chunk,
    head_dim] give ``(kbar, vbar)`` [..., head_dim], the chunk's rows weighted
    by the float32 softmax of ``scale * k . mu`` and of ``scale * k . phi``
    over the chunk; ``mu``, ``phi`` [..., 1, head_dim], a head's vector laid
    against the head's axis of the rows."""
    import jax
    import jax.numpy as jnp

    k32, v32 = keys.astype(jnp.float32), values.astype(jnp.float32)
    weigh = lambda vector: jax.nn.softmax(scale * jnp.sum(
        k32 * vector.astype(jnp.float32), axis=-1), axis=-1)[..., None]
    kbar = jnp.sum(weigh(mu) * k32, axis=-2)
    vbar = jnp.sum(weigh(phi) * v32, axis=-2)
    return kbar.astype(keys.dtype), vbar.astype(values.dtype)


class WindowSummaryDecoderModel(TinyDecoderModel):
    """``window_summary_lm``: the block above at a configuration's sizes."""

    name = "window_summary_lm"

    def __init__(self, config: Dict[str, Any], seed: Optional[int] = 0,
                 init_scale: Callable = plain_scale):
        """``seed=None`` leaves the weights as shapes for whoever puts them
        there. ``init_scale(path, leaf)``: the rule a seeded model's weights
        are drawn by (``benchmark/family.py``'s, so a family's own can be
        given)."""
        super().__init__(seed=seed)
        self._init_scale = init_scale
        self.sizes = s = sizes_of(config)
        self.VOCAB, self.D_MODEL, self.HEADS = s.vocab, s.d_model, s.heads
        self.LAYERS, self.MAX_LEN = s.layers, s.max_len
        self._rungs = summary_ladder(s)
        self._slot_prefill_chunk = s.prefill_chunk

    def ladder(self) -> Tuple[int, ...]:
        return summary_ladder(self.sizes)

    def rung_for(self, reach: int) -> int:
        """The shortest rung that covers the summary rows a token at position
        ``reach - 1`` attends to: every chunk of every window before its
        own."""
        s = self.sizes
        rows = s.summaries_a_window * ((reach - 1) // s.window)
        return next(live for live in self._rungs if live >= rows)

    def slots_read(self, slots: int, occupied: int) -> int:
        """Its round reads the occupied slots in whole turns at every head
        width (``over_turns``)."""
        return in_whole_turns(slots, occupied)

    def count_positions(self, count: RungCount, positions, decoding: bool) -> None:
        """What the tokens at ``positions`` read and wrote of the two kinds
        of state, from the positions alone: a decode step's ring rows and
        summary rows, and the summaries that the tokens, a prompt's or a
        step's, completed."""
        s = self.sizes
        positions = np.asarray(positions, np.int64)
        written = int(np.sum(positions % s.chunk == s.chunk - 1))
        if not decoding:
            count.add_rows(0, 0, written)
            return
        count.add_rows(int(np.sum(positions % s.window + 1)),
                       int(np.sum(s.summaries_a_window * (positions // s.window))),
                       written)

    # -- programs ------------------------------------------------------------
    def _build(self):
        import jax
        import jax.numpy as jnp
        from jax import lax

        from ..ops.chunk_attention import chunk_attention

        s = self.sizes
        H, Dh, W, C, Q = s.heads, s.head_dim, s.window, s.chunk, s.prefill_chunk
        scale = Dh ** -0.5
        shapes = param_shapes(s)
        self._params = (shapes if self._seed is None
                        else seeded_params(shapes, self._seed, self._init_scale))

        f32 = jnp.float32
        # a product with a weight, accumulated and handed on in float32
        matmul = lambda x, w: lax.dot_general(
            x, w, (((x.ndim - 1,), (0,)), ((), ())), preferred_element_type=f32)

        def norm(x, gain):
            gain = gain.astype(f32)
            return rms(x, 1.0 + gain if s.unit_offset else gain, s.eps)

        def qkv(layer, x, angles):
            """Queries, keys and values [n, heads, head_dim] of ``x`` [n, d],
            the first two turned by the positions' ``angles``."""
            n, dtype = x.shape[0], layer["wq"].dtype
            with jax.named_scope("attn_qkv"):
                a = norm(x, layer["ln1"]).astype(dtype)
                q, k, v = (matmul(a, layer[name]).reshape(n, H, Dh)
                           for name in ("wq", "wk", "wv"))
            with jax.named_scope("rope"):
                q = rotary(q, *angles).astype(dtype)
                k = rotary(k, *angles).astype(dtype)
            return q, k, v.astype(dtype)

        def rest_of_layer(layer, x, attn):
            """The layer after its attention ``attn`` [n, heads * head_dim]."""
            dtype = layer["wo"].dtype
            with jax.named_scope("attn_proj"):
                x = x + matmul(attn.astype(dtype), layer["wo"])
            with jax.named_scope("mlp"):
                b = norm(x, layer["ln2"]).astype(dtype)
                hidden = jax.nn.silu(matmul(b, layer["wg"])) * matmul(b, layer["wu"])
                return x + matmul(hidden.astype(dtype), layer["wd"])

        def unembed(params, x):
            """Every head's logits [n, pred_heads, vocab] in float32."""
            with jax.named_scope("unembed"):
                h = norm(x, params["final_norm"]).astype(params["unembed"].dtype)
                return matmul(h, params["unembed"]).reshape(
                    x.shape[0], s.pred_heads, s.vocab)

        def angles_at(tables, positions):
            return tuple(table[positions] for table in tables)

        # -- the step: every occupied slot of a table, one token -----------
        def in_turn(flags, body, carry):
            """``body(slot, carry)`` for each slot flagged, lowest first, one
            turn of a loop each: the work follows the number of such slots.
            (Their order by a comparison of ranks, sixteen by sixteen: a sort
            is a program of its own on the chip.)"""
            places = jnp.arange(flags.shape[0])
            rank = jnp.cumsum(flags) - 1
            order = jnp.sum(jnp.where(
                flags[None, :] & (rank[None, :] == places[:, None]), places[None, :], 0),
                axis=1)
            return lax.fori_loop(0, jnp.sum(flags, dtype=jnp.int32),
                                 lambda n, carry: body(order[n], carry), carry)

        def put(table, rows, slot, row):
            """``table`` with slot ``slot``'s rows of ``rows`` [slots, heads,
            n, head_dim] written from row ``row`` on, where they lie."""
            return lax.dynamic_update_slice(
                table, lax.dynamic_index_in_dim(rows, slot, keepdims=True),
                (slot, 0, row, 0))

        def write_step_rows(state, k, v, layer, pos, active):
            """The table with each active slot's new ring row written and,
            for the slots whose token completes a chunk, that chunk's
            summary. Three loops, each a turn a slot it has work for: the
            ring rows; the complete chunks' ring rows gathered into one
            small batch, summarised together; the summaries' rows. Every
            update is sliced from an array shaped as the table holds its rows
            ([slots, heads, rows, head_dim]) and nothing conditional holds a
            table: for an update with its heads where a tile has its rows,
            as for a gather or a ``cond`` over a table, the chip's compiler
            lays the whole table out anew and back, a layer, a round (a
            described v5e, PR 34)."""
            at = pos % W
            with jax.named_scope("cache_update"):
                k, v = k[:, :, None, :], v[:, :, None, :]
                ring_k, ring_v = in_turn(
                    active,
                    lambda slot, ring: (put(ring[0], k, slot, at[slot]),
                                        put(ring[1], v, slot, at[slot])),
                    (state["k"], state["v"]))
            with jax.named_scope("summarise"):
                due = active & (pos % C == C - 1)
                chunk = lambda ring, slot: lax.dynamic_slice(
                    ring, (slot, 0, at[slot] - (C - 1), 0), (1, H, C, Dh))
                blank = jnp.zeros((pos.shape[0], H, C, Dh), k.dtype)
                rows_k, rows_v = in_turn(
                    due,
                    lambda slot, rows: (
                        lax.dynamic_update_slice(rows[0], chunk(ring_k, slot),
                                                 (slot, 0, 0, 0)),
                        lax.dynamic_update_slice(rows[1], chunk(ring_v, slot),
                                                 (slot, 0, 0, 0))),
                    (blank, blank))
                kbar, vbar = (bar[:, :, None, :] for bar in summarise(
                    rows_k, rows_v, layer["mu"][None, :, None, :],
                    layer["phi"][None, :, None, :], scale))
                sk, sv = in_turn(
                    due,
                    lambda slot, held: (put(held[0], kbar, slot, pos[slot] // C),
                                        put(held[1], vbar, slot, pos[slot] // C)),
                    (state["sk"], state["sv"]))
            return {"k": ring_k, "v": ring_v, "sk": sk, "sv": sv}

        def part_of_softmax(q, keys, values, valid):
            """What the rows ``keys``, ``values`` [a, heads, rows, head_dim],
            of which those ``valid`` [a, 1, rows] count, give the queries
            ``q`` [a, heads, head_dim] of a softmax that has other parts:
            the largest score, the sum of the exponentials under it and the
            values so weighted, in float32."""
            scores = jnp.einsum("ahd,ahkd->ahk", q, keys,
                                preferred_element_type=f32) * scale
            scores = jnp.where(valid, scores, -1e30)
            top = jnp.max(scores, axis=-1, keepdims=True)
            weights = jnp.where(valid, jnp.exp(scores - top), 0.0)
            given = jnp.einsum("ahk,ahkd->ahd", weights.astype(values.dtype), values,
                               preferred_element_type=f32)
            return top, jnp.sum(weights, axis=-1, keepdims=True), given

        def merged(one, other):
            """Two parts of one softmax as one: ``(largest score, sum of the
            exponentials under it, values so weighted)`` each."""
            top = jnp.maximum(one[0], other[0])
            mine, its = jnp.exp(one[0] - top), jnp.exp(other[0] - top)
            return top, one[1] * mine + other[1] * its, one[2] * mine + other[2] * its

        def over_turns(q, pos, turns, part):
            """``part(q, pos, first slot)`` of each turn's ``a_turn`` slots,
            for ``turns`` turns: the occupied slots and no slot's state
            beyond."""
            slots = q.shape[0]
            a_turn = slots_a_turn(slots)

            def turn(n, parts):
                at = n * a_turn
                mine = part(lax.dynamic_slice_in_dim(q, at, a_turn),
                            lax.dynamic_slice_in_dim(pos, at, a_turn), at)
                return tuple(lax.dynamic_update_slice_in_dim(whole, own, at, 0)
                             for whole, own in zip(parts, mine))

            return lax.fori_loop(0, turns, turn, nothing(slots))

        # a part of a softmax over no row yet
        nothing = lambda n: (jnp.full((n, H, 1), -1e30, f32), jnp.zeros((n, H, 1), f32),
                             jnp.zeros((n, H, Dh), f32))
        # the summary rows a turn's attention takes at a time: it reads as
        # many such blocks as its furthest member's windows have summaries, so
        # a round costs what its members' positions cost and one long stream
        # is paid for by its own turn (with a compile-time count of rows, the
        # furthest member's of the round, a round had two costs a sixth
        # apart and a cell's median gap sat between them: PERF.md, PR 34)
        # (two windows' worth, where the table is whole blocks of that)
        block = s.summaries_a_window * (1 if (s.max_len // s.window) % 2 else 2)

        def step_layer(layer, state, x, angles, pos, active, turns):
            """One layer of the step. The attention is one softmax over two
            kinds of rows, worked as parts that are merged by their largest
            scores and sums: the ring's rows up to the token's own, and the
            summary rows of the windows before its own, a block at a time.
            Each kind is a loop of its own over the turns, so that a trace
            tells them apart (it reads the outermost scope)."""
            slots = x.shape[0]
            q, k, v = qkv(layer, x, angles)
            state = write_step_rows(state, k, v, layer, pos, active)
            rows_of = lambda name, at, first, rows: lax.dynamic_slice(
                state[name], (at, 0, first, 0), (slots_a_turn(slots), H, rows, Dh))
            with jax.named_scope("window_attention"):
                exact = over_turns(
                    q, pos, turns, lambda q, pos, at: part_of_softmax(
                        q, rows_of("k", at, 0, W), rows_of("v", at, 0, W),
                        jnp.arange(W)[None, None, :] <= (pos % W)[:, None, None]))

            def summaries(q, pos, at):
                held = s.summaries_a_window * (pos // W)

                def a_block(i, so_far):
                    first = i * block
                    return merged(so_far, part_of_softmax(
                        q, rows_of("sk", at, first, block), rows_of("sv", at, first, block),
                        (first + jnp.arange(block))[None, None, :] < held[:, None, None]))

                return lax.fori_loop(0, -(-jnp.max(held) // block), a_block,
                                     nothing(q.shape[0]))

            with jax.named_scope("summary_attention"):
                _, total, given = merged(exact, over_turns(q, pos, turns, summaries))
            with jax.named_scope("window_attention"):
                attn = given / total
            return rest_of_layer(layer, x, attn.reshape(slots, H * Dh)), state

        a_step_layer = jax.jit(step_layer)

        # traced as ``jit_step``: a round is the step of a model whose streams
        # share it, a single sequence's step is a round of a table of one
        # slot, and a trace is read by that name
        def step(params, table, angle_tables, fed, ctl):
            """One token of every occupied slot. ``table``: [L] dicts of the
            ring ``k``, ``v`` [slots, heads, window, head_dim] and the
            summaries ``sk``, ``sv`` [slots, heads, max_len / chunk,
            head_dim], donated. ``fed`` int32 [slots]: the tokens the round
            before chose, still on the device. ``ctl`` int32 [3, slots], the
            host's word a slot: a token of its own or -1 for the fed one; the
            position; whether a stream sits there. Returns head 0's greedy
            choice of every slot, int32 [slots], every head's logits
            [slots, pred_heads, vocab] and the table."""
            given, pos, active = ctl[0], ctl[1], ctl[2] > 0
            slots = active.shape[0]
            occupied = jnp.max(jnp.where(active, jnp.arange(slots) + 1, 0))
            turns = -(-occupied // slots_a_turn(slots))
            token = jnp.where(given >= 0, given, fed)
            with jax.named_scope("embed"):
                x = params["embed"][token].astype(f32)
            angles = angles_at(angle_tables, pos)
            new_table = []
            for layer, state in zip(params["layers"], table):
                x, state = a_step_layer(layer, state, x, angles, pos, active, turns)
                new_table.append(state)
            logits = unembed(params, x)
            with jax.named_scope("greedy_argmax"):
                return (jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32), logits,
                        new_table)

        # -- a prompt's chunk into its slot ----------------------------------
        def chunk_layer(layer, state, x, angles, slot, base, kept, done, *, live):
            """One layer over the ``Q`` positions from ``base`` on of one
            slot: the ring rows of the tokens ``kept`` [Q] written, the
            summaries of the chunks ``done`` [Q / C] written, and the
            attention of each position over its window so far and the first
            ``live`` summary rows."""
            dtype = layer["wq"].dtype
            q, k, v = qkv(layer, x, angles)
            at = base % W
            with jax.named_scope("cache_update"):
                rows = {}
                for name, new in (("k", k), ("v", v)):
                    new = new.transpose(1, 0, 2)[None]  # [1, H, Q, Dh]
                    old = lax.dynamic_slice(state[name], (slot, 0, at, 0), new.shape)
                    rows[name] = jnp.where(kept[None, None, :, None], new, old)
                    state = dict(state, **{name: lax.dynamic_update_slice(
                        state[name], rows[name], (slot, 0, at, 0))})
            with jax.named_scope("summarise"):
                chunks = lambda r: r[0].reshape(H, Q // C, C, Dh)
                kbar, vbar = summarise(
                    chunks(rows["k"]), chunks(rows["v"]), layer["mu"][:, None, None, :],
                    layer["phi"][:, None, None, :], scale)
                for name, new in (("sk", kbar), ("sv", vbar)):
                    old = lax.dynamic_slice(state[name], (slot, 0, base // C, 0),
                                            (1,) + new.shape)
                    state = dict(state, **{name: lax.dynamic_update_slice(
                        state[name], jnp.where(done[None, None, :, None], new[None], old),
                        (slot, 0, base // C, 0))})
            with jax.named_scope("eva_attention"):
                # [summary rows | ring rows], a position a row and a head a
                # column block, as the kernel reads keys; with the base moved
                # by the summaries' count its skip rule holds as it is
                its = lambda name, rows: lax.dynamic_slice(
                    state[name], (slot, 0, 0, 0), (1, H, rows, Dh))[0]
                laid = lambda summaries, ring: jnp.concatenate(
                    ([its(summaries, live)] if live else []) + [its(ring, W)],
                    axis=1).transpose(1, 0, 2).reshape(live + W, H * Dh)
                column = jnp.arange(live + W)[None, :]
                mask = jnp.where(
                    column < live,
                    column < s.summaries_a_window * (base // W),
                    column - live <= at + jnp.arange(Q)[:, None])
                attn = chunk_attention(
                    q.reshape(Q, H * Dh), laid("sk", "k"), laid("sv", "v"), mask,
                    live + at, kv_heads=H, head_dim=Dh, block_q=min(256, Q),
                    block_k=math.gcd(live + W, 512))
            return rest_of_layer(layer, x, attn), state

        a_chunk_layer = jax.jit(chunk_layer, static_argnames="live")

        def slot_prefill(params, table, angle_tables, fed, tokens, ctl, *, live):
            """One chunk of a prompt into a slot. ``tokens`` int32 [Q] are at
            positions ``base + i``; ``ctl`` int32 [5]: the slot, ``base`` (on
            the grid of ``Q``), the chunk's own tokens ``lo`` to ``hi`` of
            the block, and whether it is the prompt's last. Rows outside
            ``lo`` to ``hi`` keep what they held; the summaries of the chunks
            that end inside them are written. With ``last``, every head's
            logits after token ``hi - 1`` and head 0's choice in ``fed`` at
            the slot, where the next round reads it; else zeros and ``fed``
            as it was."""
            slot, base, lo, hi, last = (ctl[i] for i in range(5))
            places = jnp.arange(Q, dtype=jnp.int32)
            kept = (places >= lo) & (places < hi)
            ends = jnp.arange(Q // C, dtype=jnp.int32) * C + (C - 1)
            done = (ends >= lo) & (ends < hi)
            with jax.named_scope("embed"):
                x = params["embed"][tokens].astype(f32)
            angles = angles_at(angle_tables, base + places)
            new_table = []
            for layer, state in zip(params["layers"], table):
                x, state = a_chunk_layer(layer, state, x, angles, slot, base, kept,
                                         done, live=live)
                new_table.append(state)
            logits = lax.cond(
                last,
                lambda: unembed(params, lax.dynamic_slice_in_dim(x, hi - 1, 1))[0],
                lambda: jnp.zeros((s.pred_heads, s.vocab), f32))
            with jax.named_scope("greedy_argmax"):
                fed = jnp.where(
                    last & (jnp.arange(fed.shape[0]) == slot),
                    jnp.argmax(logits[0]).astype(jnp.int32), fed)
            return fed, logits, new_table

        # the rotary's angles: an argument of the programs beside the
        # weights, and no weight (made on the host: routed_decoder.rotary_table)
        self._tables = tuple(jnp.asarray(table)
                             for table in rotary_table(Dh, s.max_len, s.theta))
        self._step_program = jax.jit(step, donate_argnums=1)
        self._prefill_program = jax.jit(
            slot_prefill, donate_argnums=1, static_argnames="live")

        # (the round has no rung: its summaries are read a block at a time,
        # as many as its members need; ``live`` is the rounds' worker's word
        # for the rung that a chunk of the same reach would take)
        def round_fn(params, table, fed, ctl, *, live=None):
            chosen, _, table = self._step_program(params, table, self._tables, fed, ctl)
            return chosen, table

        def slot_prefill_fn(params, table, fed, tokens, ctl, *, live):
            fed, _, table = self._prefill_program(
                params, table, self._tables, fed, tokens, ctl, live=live)
            return fed, table

        # TinyDecoderModel's contract, (params, caches, token, pos, live=): a
        # single sequence's cache is a table of one slot
        def step_fn(params, caches, token, pos, *, live=None):
            ctl = np.array([[token], [pos], [1]], np.int32)
            _, logits, caches = self._step_program(
                params, caches, self._tables, np.zeros(1, np.int32), ctl)
            return logits[0], caches

        self._step_fn, self._round_fn = step_fn, round_fn
        self._slot_prefill_fn = slot_prefill_fn

    def _fresh_table(self, slots: int):
        """``slots`` sequences' state, stacked: a layer the ring ``k``, ``v``
        [slots, heads, window, head_dim] and the summaries ``sk``, ``sv``
        [slots, heads, max_len / chunk, head_dim], zeros."""
        import jax.numpy as jnp

        s = self.sizes
        dtype = self._params["embed"].dtype
        ring = (slots, s.heads, s.window, s.head_dim)
        summaries = (slots, s.heads, s.summary_rows, s.head_dim)
        return [{"k": jnp.zeros(ring, dtype), "v": jnp.zeros(ring, dtype),
                 "sk": jnp.zeros(summaries, dtype), "sv": jnp.zeros(summaries, dtype)}
                for _ in range(s.layers)]

    def _fresh_cache(self):
        return self._fresh_table(1)

    def _step_at(self, caches, token, pos, live: int):
        """Every head's logits [pred_heads, vocab] and the caches."""
        return self._step_fn(self._params, caches, token, pos, live=live)

    def _chunk_at(self, caches, block, base: int, lo: int, hi: int, last: bool,
                  live: int):
        """One chunk into a single sequence's cache: every head's logits (of
        the last chunk) and the caches."""
        ctl = np.array([0, base, lo, hi, last], np.int32)
        _, logits, caches = self._prefill_program(
            self._params, caches, self._tables, np.zeros(1, np.int32), block, ctl,
            live=live)
        return logits, caches

    def _ensure_warm(self) -> None:
        """A single sequence's step and chunk compiled at every rung, by one
        real call of each on a scratch cache, before the first is served."""
        if self._warm:
            return
        with self._warm_lock:
            if self._warm:
                return
            caches = self._fresh_cache()
            block = np.zeros(self.sizes.prefill_chunk, np.int32)
            for live in self._rungs:
                _, caches = self._step_at(caches, 0, 0, live)
                logits, caches = self._chunk_at(caches, block, 0, 0, 1, True, live)
            logits.block_until_ready()
            self._warm = True

    def decode_step(self, caches, token: int, pos: int,
                    count: Optional[RungCount] = None):
        """One token of one sequence: head 0's logits [vocab] (the token
        served is their argmax) and the caches."""
        self._ensure_warm()
        count = self.steps_by_rung if count is None else count
        live = self.rung_for(pos + 1)
        count.add(live)
        self.count_positions(count, [pos], decoding=True)
        logits, caches = self._step_at(caches, token, pos, live)
        return logits[0], caches

    def _advance(self, caches, tokens, pos: int):
        """The sequence API's request: a prompt through ``prefill``, a
        continuation's one token through the step."""
        if len(tokens) == 1:
            return self.decode_step(caches, int(tokens[0]), pos)
        return self.prefill(caches, tokens, pos)

    def prefill_heads(self, caches, tokens, pos: int,
                      count: Optional[RungCount] = None):
        """``tokens`` from ``pos`` on, a chunk a dispatch, each waited for;
        every head's logits [pred_heads, vocab] after the last token and the
        caches."""
        self._ensure_warm()
        count = self.steps_by_rung if count is None else count
        chunk = self.sizes.prefill_chunk
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        end, logits = pos + tokens.size, None
        while pos < end:
            base = pos - pos % chunk
            upto = min(end, base + chunk)
            block = np.zeros(chunk, np.int32)
            block[pos - base:upto - base] = tokens[tokens.size - (end - pos):
                                                   tokens.size - (end - upto)]
            with span(SPAN_PREFILL_CHUNK):
                logits, caches = self._chunk_at(
                    caches, block, base, pos - base, upto - base, upto == end,
                    self.rung_for(upto))
                logits.block_until_ready()
            count.add_prefill(upto - pos)
            self.count_positions(count, np.arange(pos, upto), decoding=False)
            pos = upto
        return logits, caches

    def prefill(self, caches, tokens, pos: int, count: Optional[RungCount] = None):
        """As ``prefill_heads``, with head 0's logits [vocab]."""
        logits, caches = self.prefill_heads(caches, tokens, pos, count)
        return logits[0], caches
