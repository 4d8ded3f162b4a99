"""A decoder read from a configuration whose attention layers are of two
kinds, a sliding window with rotary positions and full causal attention with
none, each layer's tokens routed to ReLU-gated experts by a router that reads
the layer's input before attention.

``WindowRoutedDecoderModel(config)`` is served as ``TinyDecoderModel`` is: by
``TinyGenerateModel(decoder=...)`` on the decoupled stream, where its streams
share rounds (``stream_rounds.py``) and a prompt is taken a chunk a dispatch
into its slot, and by its own ``execute`` on the sequence API. Its sizes are
the keys of a ``config.json`` with ``sliding_window_layout`` and
``rope_layout`` (a flag a layer each), ``sliding_window_size`` and the
``moe_*`` keys of primary experts; no model's name appears here.

The block, for a token's float32 residual ``x`` at position ``t``, every norm
an RMS norm with a gain:

- ``h1 = rms(x; ln1)``. The router reads ``h1``, before attention: logits
  ``h1 router`` in float32, a softmax over every expert, the
  ``moe_num_active_primary_experts`` largest renormalised (``norm_topk_prob``;
  ``routed_decoder.route``).
- ``q = h1 wq`` (query heads), ``k = h1 wk``, ``v = h1 wv`` (key-value heads,
  each shared by a group of query heads); no norm over a head, no bias.
  - A *window layer* (``sliding_window_layout`` 1, ``rope_layout`` 1):
    rotate-half rotary over the whole head; the token attends to the keys
    ``j`` with ``t - window < j <= t``.
  - A *full layer* (both flags 0): no rotary; the token attends to every
    ``j <= t``.
  Softmax attention scaled by ``head_dim ** -0.5``; ``x += o wo``.
- ``h2 = rms(x; ln2)``; ``x += sum_e w_e (relu(h2 G_e) * (h2 U_e)) D_e`` over
  the token's chosen experts (``routed_decoder.expert_layer`` with ``relu``).
- ``rms(x; final_norm)``, an untied head, float32 logits.

What the serving path is made of:

- **Two kinds of attention state in one slot** (``_fresh_table``): a full
  layer's key and value rows, ``k``, ``v`` [slots, max_len, kv_heads *
  head_dim] a layer, a position a row at its position; a window layer's
  ring, ``ring_k``, ``ring_v`` [slots, window, kv_heads * head_dim] a layer,
  position ``t`` at row ``t mod window``. A ring row's position is not
  stored: it is derived from the position of the token that reads it, as
  the latest position at or before it that lies on that row. Nothing is
  cleared: a new stream writes its rows from position 0, and a ring row whose
  derived position is negative is one that stream has not written, whatever
  an earlier occupant left there.
- **The round** (``jit_step``): one token of every occupied slot, one
  program a rung of the ladder. The products with the weights take every
  slot; a layer's row of every occupied slot is written where it lies (at
  ``t`` in a full layer, at ``t mod window`` in a window layer: on the chip
  one ``ops/row_write.py`` kernel, elsewhere a turn of a loop a slot), then
  its attention reads each occupied slot's rows where they lie
  (``ops/slot_attention.py``): a window layer its ring's rows ``<= t`` (once
  ``t`` reaches the window every row is the stream's), a full layer its rows
  ``<= t``; an unoccupied slot's none. A routed layer's tokens go through one
  grouped product; an unoccupied slot's pairs go to no expert, and what the
  product gives it is dropped.
- **A prompt a chunk a dispatch into its slot** (``jit_slot_prefill``):
  ``prefill_chunk`` positions on the table's own grid of that size, a whole
  number of chunks to the window, so a chunk's rows are one run of ring rows.
  A window layer's chunk attends over the ring as it was beside the chunk's
  own rows, and only then writes them: a chunk past the window writes over
  rows its own earliest queries still attend to. A full layer writes its rows
  and then attends over the rung's prefix. Both attentions are the blocked
  kernel of ``ops/chunk_attention.py`` under a mask, which keeps the chunk's
  scores out of HBM (at the prefix of 16,384 positions they would be a
  gigabyte a layer). The chunk's padded rows route to no expert. The last
  chunk of a prompt leaves its choice where the next round reads it.
- **What the experts read, counted on the device**, as
  ``gated_conv_decoder.py`` counts it: the distinct experts each routed
  layer's grouped product read, summed over the layers, and the dispatches,
  a pair for the rounds and a pair for the chunks, ride as ``FED_TALLY``
  int32 behind the slots' choices in ``fed``; ``stream_rounds.py`` reads them
  back with the choices and hands the difference to ``count_tally``. The
  rows the rounds' members attend to are counted on the host from positions
  (``count_positions``): ``client_tpu_server_attention_rows_read{model,kind}``.
  A single sequence's steps count nothing.

Weights, rows and matrix products in the configuration's ``dtype``
(bfloat16 where it states none; float32 accumulation); a float32 residual
stream, norms, rotary, router scores, attention softmax and logits. A single
sequence's cache is a table of one slot.

Named scopes: ``embed``, ``moe_route`` (before attention), ``attn_qkv``,
``rope``, ``cache_update`` (both kinds of row write), ``sliding_attention``,
``full_attention``, ``attn_proj``, ``moe_experts``, ``unembed``,
``greedy_argmax``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import numpy as np

from ..server.timeline import SPAN_PREFILL_CHUNK, span
from .decoder import RungCount, TinyDecoderModel, ladder_of
from .gated_conv_decoder import FED_TALLY, in_turn
from .routed_decoder import (
    expert_layer,
    plain_scale,
    rms,
    rotary,
    rotary_table,
    route,
    seeded_params,
)


class Sizes(NamedTuple):
    """What the block reads of a configuration."""

    vocab: int
    d_model: int
    sliding: Tuple[bool, ...]
    heads: int
    kv_heads: int
    head_dim: int
    expert_width: int
    experts: int
    experts_per_token: int
    renormalise: bool
    eps: float
    theta: float
    window: int
    max_len: int
    prefill_chunk: int
    dtype: str

    @property
    def layers(self) -> int:
        return len(self.sliding)

    def place(self, i: int) -> int:
        """Layer ``i``'s index among the layers of its kind: its place in the
        table's list of that kind."""
        return self.sliding[:i].count(self.sliding[i])


def sizes_of(config: Dict[str, Any]) -> Sizes:
    """The configuration's keys, checked against what this block can run."""
    layers = int(config["num_hidden_layers"])
    sliding = tuple(int(f) for f in config["sliding_window_layout"])
    turned = tuple(int(f) for f in config["rope_layout"])
    if len(sliding) != layers or len(turned) != layers:
        raise ValueError(f"sliding_window_layout and rope_layout must give each of "
                         f"the {layers} layers a flag")
    if not set(sliding) <= {0, 1} or sliding != turned:
        raise ValueError("a window layer turns its queries and keys and a full "
                         "layer does not: the two layouts must be the same flags")
    if not config.get("moe_primary_router_apply_softmax", False):
        raise ValueError("the router is a softmax over every expert")
    if config.get("tie_word_embeddings", False):
        raise ValueError("the head is a matrix of its own")
    if config.get("rope_scaling") is not None:
        raise ValueError("the rotary's angles are not scaled")
    if any(int(config.get(key, 0)) for key in ("moe_num_secondary_experts",
                                               "num_shared_experts")):
        raise ValueError("a routed layer has primary experts alone")
    heads, kv_heads = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    if heads % kv_heads:
        raise ValueError(f"{heads} query heads do not share {kv_heads} key-value heads")
    # the positions a sequence may reach here: what is reserved, where the
    # file says so, and the published context otherwise
    max_len = int(config.get("reserved_positions", config["max_position_embeddings"]))
    window = int(config["sliding_window_size"])
    prefill_chunk = int(config.get("prefill_chunk", 512))
    if max_len % prefill_chunk or window % prefill_chunk:
        raise ValueError(f"{max_len} positions and a window of {window} are not both "
                         f"whole chunks of {prefill_chunk}")
    if window > max_len:
        raise ValueError(f"a window of {window} is longer than the {max_len} positions")
    return Sizes(
        vocab=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
        sliding=tuple(bool(f) for f in sliding), heads=heads, kv_heads=kv_heads,
        head_dim=int(config["head_dim"]),
        expert_width=int(config["moe_ffn_hidden_size"]),
        experts=int(config["moe_num_primary_experts"]),
        experts_per_token=int(config["moe_num_active_primary_experts"]),
        renormalise=bool(config.get("norm_topk_prob", True)),
        eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]),
        window=window, max_len=max_len, prefill_chunk=prefill_chunk,
        dtype=str(config.get("dtype", "bfloat16")))


def param_shapes(s: Sizes):
    """The weights' tree as ``jax.ShapeDtypeStruct``."""
    import jax
    import jax.numpy as jnp

    w = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.dtype(s.dtype))
    d, f, rows = s.d_model, s.expert_width, s.kv_heads * s.head_dim
    layer = lambda: {
        "ln1": w(d), "router": w(d, s.experts), "wq": w(d, s.heads * s.head_dim),
        "wk": w(d, rows), "wv": w(d, rows), "wo": w(s.heads * s.head_dim, d),
        "ln2": w(d), "experts_gate": w(s.experts, d, f), "experts_up": w(s.experts, d, f),
        "experts_down": w(s.experts, f, d)}
    return {"embed": w(s.vocab, d), "layers": [layer() for _ in range(s.layers)],
            "final_norm": w(d), "unembed": w(d, s.vocab)}


def ring_positions(reach, rows: int):
    """The position each of a ring's ``rows`` rows holds once every position
    before ``reach`` [n] has been written: the latest such position on that
    row, [n, rows], negative where there is none yet."""
    import jax.numpy as jnp

    row = jnp.arange(rows)[None, :]
    last = reach[:, None] - 1
    return last - jnp.mod(last - row, rows)


class WindowRoutedDecoderModel(TinyDecoderModel):
    """``window_routed_lm``: the block above at a configuration's sizes."""

    name = "window_routed_lm"
    fed_tally = FED_TALLY

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
        self._rungs = self.ladder()
        self._slot_prefill_chunk = s.prefill_chunk

    def ladder(self) -> Tuple[int, ...]:
        """``decoder.ladder_of``'s rungs that are no shorter than the window:
        a window layer reads its whole ring at every rung, so a shorter rung
        would shorten the full layers' reads alone (at the published sizes
        under 4% of a round's bytes) for two programs more to build."""
        s = self.sizes
        return tuple(live for live in ladder_of(s.max_len) if live >= s.window)

    def slots_read(self, slots: int, occupied: int) -> int:
        """Its round's attention reads the members' rows alone, which lie in
        the slots up to the highest occupied."""
        return occupied

    def count_tally(self, count: RungCount, tally) -> None:
        """What the dispatches read back since the last read-back added to
        the tally behind the choices (``FED_TALLY``, in its order)."""
        count.add_reached("round", int(tally[0]), int(tally[1]))
        count.add_reached("chunk", int(tally[2]), int(tally[3]))

    def count_positions(self, count: RungCount, positions, decoding: bool) -> None:
        """The rows the decode steps at ``positions`` attend to, by kind and
        summed over the layers of that kind: ``min(t + 1, window)`` a window
        layer, ``t + 1`` a full layer. A prompt's tokens count none."""
        if not decoding:
            return
        s = self.sizes
        reach = np.asarray(positions, np.int64) + 1
        windows = sum(s.sliding)
        count.add_attention_rows(
            "sliding", windows * int(np.minimum(reach, s.window).sum()))
        count.add_attention_rows("full", (s.layers - windows) * int(reach.sum()))

    # -- programs ------------------------------------------------------------
    def _build(self):
        import jax
        import jax.numpy as jnp
        from jax import lax

        from .. import ops
        from ..ops import row_write
        from ..ops.chunk_attention import chunk_attention
        from ..ops.slot_attention import slot_attention

        s = self.sizes
        H, KV, Dh, Q, E, W = (s.heads, s.kv_heads, s.head_dim, s.prefill_chunk,
                              s.experts, s.window)
        C = KV * Dh  # a row's lanes
        self.rows_path = ("kernel" if ops._on_tpu() and all(
            row_write.takes((1, 1, rows, C)) for rows in (W, s.max_len)) else "loop")
        shapes = param_shapes(s)
        self._params = (shapes if self._seed is None
                        else seeded_params(shapes, self._seed, self._init_scale))

        f32 = jnp.float32
        # a product with a weight, accumulated and handed on in float32
        matmul = lambda x, w: lax.dot_general(
            x, w, (((x.ndim - 1,), (0,)), ((), ())), preferred_element_type=f32)
        norm = lambda x, gain: rms(x, gain, s.eps)

        # -- the parts of a layer ------------------------------------------
        def routed_first(layer, x, taken):
            """``h1`` [n, d] float32, and the router's choice over it: the
            experts [n, k] (``E``, none, for a token not ``taken``), their
            weights, and the distinct experts the pairs reach."""
            with jax.named_scope("moe_route"):
                h1 = norm(x, layer["ln1"])
                which, gates = route(h1, layer["router"], s)
                which = jnp.where(taken[:, None], which, E)
                reached = jnp.sum(jnp.any(
                    which.reshape(-1)[:, None] == jnp.arange(E)[None, :], axis=0),
                    dtype=jnp.int32)
            return h1, which, gates, reached

        def qkv(layer, h1, angles):
            """Queries [n, heads, head_dim], key and value rows [n, kv_heads *
            head_dim] of ``h1``; turned by the positions' ``angles`` where a
            window layer gives them."""
            n, dtype = h1.shape[0], layer["wq"].dtype
            with jax.named_scope("attn_qkv"):
                h = h1.astype(dtype)
                q = matmul(h, layer["wq"]).reshape(n, H, Dh)
                k = matmul(h, layer["wk"])
                v = matmul(h, layer["wv"]).astype(dtype)
            if angles is None:
                return q.astype(dtype), k.astype(dtype), v
            with jax.named_scope("rope"):
                q = rotary(q, *angles).astype(dtype)
                k = rotary(k.reshape(n, KV, Dh), *angles).astype(dtype).reshape(n, C)
            return q, k, v

        def routed_rest(layer, x, attn, which, gates, taken):
            """The attention's projection, then the experts the router chose
            before it, over ``h2``; a token not ``taken`` is given nothing."""
            with jax.named_scope("attn_proj"):
                x = x + matmul(attn.astype(layer["wo"].dtype), layer["wo"])
            with jax.named_scope("moe_experts"):
                h2 = norm(x, layer["ln2"]).astype(layer["experts_gate"].dtype)
                given = expert_layer(h2, which, gates, layer, s, activation=jax.nn.relu)
                return x + jnp.where(taken[:, None], given, 0.0)

        def write_rows(keys, values, k, v, at, active):
            """Every occupied slot's key and value row [slots, C] into its
            row ``at`` of the tables [slots, rows, C]: on the chip one
            ``row_write`` kernel, whose tables stay in HBM (as a loop's turns
            the chip's compiler staged a whole ring table in fast memory
            round the loop, by copies outside any named scope); elsewhere a
            turn of a loop an occupied slot."""
            if self.rows_path == "kernel":
                tables = row_write.write_table_rows(
                    (keys[:, None], values[:, None]), (k[:, None, None], v[:, None, None]),
                    at, active)
                return tables[0][:, 0], tables[1][:, 0]
            put = lambda table, rows, slot: lax.dynamic_update_slice(
                table, lax.dynamic_index_in_dim(rows, slot)[:, None], (slot, at[slot], 0))
            return in_turn(active, lambda slot, held: (put(held[0], k, slot),
                                                       put(held[1], v, slot)),
                           (keys, values))

        def round_layer(layer, keys, values, x, angles, pos, active, *, sliding, live):
            """A layer of a round: every occupied slot's row written (at its
            position, or its ring row), then its query over its ring or its
            prefix, within the rung's ``live`` positions."""
            h1, which, gates, reached = routed_first(layer, x, active)
            q, k, v = qkv(layer, h1, angles if sliding else None)
            at = pos % W if sliding else pos
            with jax.named_scope("cache_update"):
                keys, values = write_rows(keys, values, k, v, at, active)
            # a ring row is the stream's once its position has been reached:
            # every row from the window's length on; a full layer's rows up
            # to its position. An unoccupied slot reads none.
            reach = jnp.where(active, jnp.minimum(pos + 1, W if sliding else live), 0)
            with jax.named_scope("sliding_attention" if sliding else "full_attention"):
                attn = slot_attention(q, keys, values, reach, kv_heads=KV, head_dim=Dh)
            return routed_rest(layer, x, attn, which, gates, active), keys, values, reached

        def chunk_layer(layer, keys, values, x, angles, slot, base, lo, kept, *,
                        sliding, live):
            """A layer of a chunk of one slot, its tokens ``kept`` at ``base``
            on (the others keep what their rows held and route nowhere)."""
            h1, which, gates, reached = routed_first(layer, x, kept)
            q, k, v = qkv(layer, h1, angles if sliding else None)
            q = q.reshape(Q, H * Dh)
            at = base + jnp.arange(Q)
            start = base % W if sliding else base

            def write(table, rows):
                old = lax.dynamic_slice(table, (slot, start, 0), (1, Q, C))
                return lax.dynamic_update_slice(
                    table, jnp.where(kept[None, :, None], rows[None], old), (slot, start, 0))

            held = lambda table, n: lax.dynamic_slice(table, (slot, 0, 0), (1, n, C))[0]
            if sliding:
                # the ring as it was, then the chunk's own rows: the queries
                # at ``at`` attend to the ring rows whose position lies in
                # their window and to the chunk's kept rows up to their own
                with jax.named_scope("sliding_attention"):
                    ring = ring_positions((base + lo)[None], W)
                    mask = jnp.concatenate([
                        (ring >= 0) & (ring > at[:, None] - W),
                        (jnp.arange(Q)[None, :] <= jnp.arange(Q)[:, None]) & kept[None, :]],
                        axis=1)
                    attn = chunk_attention(
                        q, jnp.concatenate([held(keys, W), k]),
                        jnp.concatenate([held(values, W), v]), mask, W,
                        kv_heads=KV, head_dim=Dh, block_k=Q)
                with jax.named_scope("cache_update"):
                    keys, values = write(keys, k), write(values, v)
            else:
                with jax.named_scope("cache_update"):
                    keys, values = write(keys, k), write(values, v)
                with jax.named_scope("full_attention"):
                    mask = jnp.arange(live)[None, :] <= at[:, None]
                    attn = chunk_attention(q, held(keys, live), held(values, live),
                                           mask, base, kv_heads=KV, head_dim=Dh)
            return routed_rest(layer, x, attn, which, gates, kept), keys, values, reached

        # one trace a kind of layer, whatever the number of layers that take
        # it (the chip's compiler inlines the calls)
        round_layer = jax.jit(round_layer, static_argnames=("sliding", "live"))
        chunk_layer = jax.jit(chunk_layer, static_argnames=("sliding", "live"))

        def unembed(params, x):
            """Logits [n, vocab] in float32."""
            with jax.named_scope("unembed"):
                h = norm(x, params["final_norm"]).astype(params["unembed"].dtype)
                return matmul(h, params["unembed"])

        def layers(params, table, x, layer_fn):
            """Every layer over ``x``: ``layer_fn(layer, keys, values, x,
            sliding)`` gives the new ``x``, that layer's rows and the experts
            its product reached; those summed."""
            table = {name: list(held) for name, held in table.items()}
            reached = jnp.zeros((), jnp.int32)
            for i, layer in enumerate(params["layers"]):
                j, sliding = s.place(i), s.sliding[i]
                kk, vv = ("ring_k", "ring_v") if sliding else ("k", "v")
                x, table[kk][j], table[vv][j], n = layer_fn(
                    layer, table[kk][j], table[vv][j], x, sliding)
                reached = reached + n
            return x, table, reached

        def tallied(fed, slots, reached, column):
            """The running sums behind the choices: ``reached`` and one
            dispatch more, in the round's pair or the chunk's."""
            add = jnp.zeros(FED_TALLY, jnp.int32).at[column].set(reached).at[column + 1].set(1)
            return fed[slots:] + add

        # traced as ``jit_step``: a round is the step of a model whose streams
        # share it, a single sequence's step is a round of a table of one
        # slot, and a trace is read by that name
        def step(params, table, angle_tables, fed, ctl, *, live):
            """One token of every occupied slot. ``table``: the state of
            ``_fresh_table``, donated. ``fed`` int32 [slots + FED_TALLY]: the
            tokens the dispatch before chose, still on the device, and the
            tally. ``ctl`` int32 [3, slots], the host's word a slot: a token
            of its own or -1 for the fed one; the position; whether a stream
            sits there. Returns ``fed`` for the next (the greedy choice of
            every slot, the tally), the logits [slots, vocab] and the
            table."""
            given, pos, active = ctl[0], ctl[1], ctl[2] > 0
            slots = active.shape[0]
            token = jnp.where(given >= 0, given, fed[:slots])
            with jax.named_scope("embed"):
                x = params["embed"][token].astype(f32)
            angles = tuple(table_[pos] for table_ in angle_tables)
            x, table, reached = layers(
                params, table, x,
                lambda layer, keys, values, x, sliding: round_layer(
                    layer, keys, values, x, angles, pos, active, sliding=sliding,
                    live=live))
            logits = unembed(params, x)
            with jax.named_scope("greedy_argmax"):
                chosen = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                fed = jnp.concatenate([chosen, tallied(fed, slots, reached, 0)])
            return fed, logits, table

        def slot_prefill(params, table, angle_tables, fed, tokens, ctl, *, live):
            """One chunk of a prompt into a slot. ``tokens`` int32 [Q] are at
            positions ``base + i``; ``ctl`` int32 [5]: the slot, ``base`` (on
            the grid of ``Q``), the chunk's own tokens ``lo`` to ``hi`` of
            the block, and whether it is the prompt's last. Rows outside
            ``lo`` to ``hi`` keep what they held and route to no expert. With
            ``last``, the logits after token ``hi - 1`` and their choice in
            ``fed`` at the slot, where the next round reads it; else zeros and
            the choices as they were."""
            slot, base, lo, hi = ctl[0], ctl[1], ctl[2], ctl[3]
            last = ctl[4] > 0
            slots = fed.shape[0] - FED_TALLY
            places = jnp.arange(Q, dtype=jnp.int32)
            kept = (places >= lo) & (places < hi)
            with jax.named_scope("embed"):
                x = params["embed"][tokens].astype(f32)
            angles = tuple(lax.dynamic_slice_in_dim(table_, base, Q)
                           for table_ in angle_tables)
            x, table, reached = layers(
                params, table, x,
                lambda layer, keys, values, x, sliding: chunk_layer(
                    layer, keys, values, x, angles, slot, base, lo, kept,
                    sliding=sliding, live=live))
            logits = lax.cond(
                last, lambda: unembed(params, lax.dynamic_slice_in_dim(x, hi - 1, 1))[0],
                lambda: jnp.zeros((s.vocab,), f32))
            with jax.named_scope("greedy_argmax"):
                chosen = jnp.where(last & (jnp.arange(slots) == slot),
                                   jnp.argmax(logits).astype(jnp.int32), fed[:slots])
                fed = jnp.concatenate([chosen, tallied(fed, slots, reached, 2)])
            return fed, logits, table

        # the rotary's angles: an argument of the programs beside the
        # weights, and no weight (made on the host: routed_decoder.rotary_table)
        self._tables = tuple(jnp.asarray(t) for t in rotary_table(Dh, s.max_len, s.theta))
        self._step_program = jax.jit(step, donate_argnums=1, static_argnames="live")
        self._prefill_program = jax.jit(
            slot_prefill, donate_argnums=1, static_argnames="live")

        def round_fn(params, table, fed, ctl, *, live):
            fed, _, table = self._step_program(params, table, self._tables, fed, ctl,
                                               live=live)
            return fed, table

        def slot_prefill_fn(params, table, fed, tokens, ctl, *, live):
            fed, _, table = self._prefill_program(
                params, table, self._tables, fed, tokens, ctl, live=live)
            return fed, table

        # TinyDecoderModel's contract, (params, caches, token, pos, live=): a
        # single sequence's cache is a table of one slot
        def step_fn(params, caches, token, pos, *, live=s.max_len):
            ctl = jnp.stack([jnp.asarray(token, jnp.int32).reshape(1),
                             jnp.asarray(pos, jnp.int32).reshape(1),
                             jnp.ones(1, jnp.int32)])
            _, logits, caches = self._step_program(
                params, caches, self._tables, jnp.zeros(1 + FED_TALLY, jnp.int32), ctl,
                live=live)
            return logits[0], caches

        self._step_fn, self._round_fn = step_fn, round_fn
        self._slot_prefill_fn = slot_prefill_fn

    def _fresh_table(self, slots: int):
        """``slots`` sequences' state, stacked, zeros: a full layer's key and
        value rows ``k``, ``v`` [slots, max_len, kv_heads * head_dim] and a
        window layer's ring ``ring_k``, ``ring_v`` [slots, window, kv_heads *
        head_dim], a list a kind, in the order of the layers of that kind."""
        import jax.numpy as jnp

        s = self.sizes
        dtype = self._params["embed"].dtype
        row = s.kv_heads * s.head_dim
        zeros = lambda n, rows: [jnp.zeros((slots, rows, row), dtype) for _ in range(n)]
        windows = sum(s.sliding)
        return {"k": zeros(s.layers - windows, s.max_len),
                "v": zeros(s.layers - windows, s.max_len),
                "ring_k": zeros(windows, s.window), "ring_v": zeros(windows, s.window)}

    def _fresh_cache(self):
        return self._fresh_table(1)

    def _chunk_at(self, caches, block, base: int, lo: int, hi: int, last: bool,
                  live: int):
        """One chunk into a single sequence's cache: the logits (of the last
        chunk) and the caches."""
        import jax.numpy as jnp

        _, logits, caches = self._prefill_program(
            self._params, caches, self._tables, jnp.zeros(1 + FED_TALLY, jnp.int32),
            block, np.array([0, base, lo, hi, last], np.int32), live=live)
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

    def _advance(self, caches, tokens, pos: int):
        """The sequence API's request: a prompt through ``prefill``, a
        continuation's one token through the step."""
        if len(tokens) == 1:
            return self.decode_step(caches, int(tokens[0]), pos)
        return self.prefill(caches, tokens, pos)

    def prefill(self, caches, tokens, pos: int, count: Optional[RungCount] = None):
        """``tokens`` from ``pos`` on, a chunk a dispatch on the cache's grid
        of ``prefill_chunk`` positions, each waited for; the logits after the
        last token and the caches."""
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
            pos = upto
        return logits, caches
