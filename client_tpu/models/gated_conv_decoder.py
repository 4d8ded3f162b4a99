"""A decoder read from a configuration whose layers are of two kinds, gated
short convolutions and grouped-query attention, with a dense feed-forward
layer before layers of routed experts.

``GatedConvDecoderModel(config)`` is served as ``TinyDecoderModel`` is: by
``TinyGenerateModel(decoder=...)`` on the decoupled stream, where its streams
share rounds (``stream_rounds.py``) and a prompt is taken a chunk a dispatch
into its slot, and by its own ``execute`` on the sequence API. Its sizes are
the keys of a ``config.json`` with ``layer_types`` (``conv`` or
``full_attention``, a layer each), ``num_dense_layers`` and ``conv_L_cache``;
no model's name appears here.

The block, for a token's float32 residual ``x`` at position ``t``, every norm
an RMS norm with a gain:

- A *conv layer*: ``h = rms(x; ln1)``; ``[B, C, u] = h conv_in`` (no bias);
  ``y_t = C_t * sum_j w_j * (B * u)_{t-2+j}`` over ``j = 0, 1, 2``, zero
  before position 0 (a depthwise causal convolution of length 3, ``conv_w``
  [3, d], tap ``j`` a row); ``x += y conv_out``.
- An *attention layer*: ``h = rms(x; ln1)``; ``q = h wq`` (query heads),
  ``k = h wk``, ``v = h wv`` (key-value heads, each shared by a group of query
  heads); ``q`` and ``k`` RMS-normed over each head, then rotate-half rotary
  over the whole head; causal softmax attention; ``x += o wo``.
- Then the feed-forward layer on ``h2 = rms(x; ln2)``: the first
  ``num_dense_layers`` layers a SwiGLU, ``x += (silu(h2 mlp_gate) * (h2
  mlp_up)) mlp_down``; every later one routed: ``s = sigmoid(h2 router)`` in
  float32, the ``num_experts_per_tok`` largest of ``s + expert_bias`` (the
  bias chooses and weighs nothing), each weighted by its ``s`` renormalised
  (``norm_topk_prob``) times ``routed_scaling_factor``; SwiGLU experts
  stacked ``[experts, d, f]``.
- ``rms(x; final_norm)``, an untied head, float32 logits.

What the serving path is made of:

- **Two kinds of state in one slot** (``_fresh_table``): an attention layer's
  key and value rows, ``k``, ``v`` [slots, max_len, kv_heads * head_dim] a
  layer, a position a row written at its position; and a conv layer's state,
  ``conv`` [slots, 2, d] a layer, the last two ``B * u`` rows, carried from
  token to token and indexed by no position. Nothing of the rows is cleared:
  a new stream writes them from position 0 and reads none beyond its own.
  The conv state is another matter, since no position masks it: a token at
  position 0, the first chunk of a prompt included, starts from zeros
  whatever the slot's last occupant left, and a dispatch writes the state of
  the slots it advances and of no other (a stream whose prompt is still
  being taken sits in its slot between two of its chunks while the rounds
  run).
- **The round** (``jit_step``): one token of every occupied slot, one
  program a rung of ``decoder.ladder_of``. The products with the weights take
  every slot; an attention layer's rows are written a turn of a loop an
  occupied slot and its attention reads every slot's prefix of the rung
  where it lies: a row of 512 lanes holds eight heads of 64, so each query
  head is laid in its group's lanes (zeros elsewhere) and multiplied with
  the whole row, and its own 64 lanes of the values' product are kept (the
  rows are never laid out anew for a narrower head); a conv layer's new
  state is written under the occupied slots' mask (2 x d a slot). A routed
  layer's tokens are routed together into one grouped product
  (``routed_decoder.expert_layer``, unchanged): an unoccupied slot's pairs go
  to no expert, so the product reads the experts the members reach and no
  other, and what it gives an unoccupied slot is dropped.
- **A prompt a chunk a dispatch into its slot** (``jit_slot_prefill``):
  ``prefill_chunk`` positions on the table's own grid of that size, the conv
  state carried in from the slot (zeros at the prompt's start) and written
  back after the chunk's last token, the rows of its tokens written, a
  causal attention over the rung's prefix; the chunk's padded rows route to
  no expert. The last chunk of a prompt leaves its choice where the next
  round reads it.
- **What the experts read, counted on the device**: the distinct experts
  each routed layer's grouped product read, summed over the layers, and the
  dispatches, a pair for the rounds and a pair for the chunks, ride as
  ``FED_TALLY`` int32 behind the slots' choices in the array a dispatch
  hands the next (``fed``), running sums that wrap. ``stream_rounds.py``
  reads them in the read-back it makes anyway and hands the difference to
  ``count_tally``: ``client_tpu_server_experts_reached{model,program}`` and
  its ``_rounds`` in the registry. A single sequence's steps count nothing.

Weights, state and matrix products in the configuration's ``dtype``
(bfloat16 where it states none; float32 accumulation); a float32 residual
stream, norms, rotary, router scores, attention softmax and logits. A single
sequence's cache is a table of one slot.

Named scopes: ``embed``, ``conv_proj``, ``short_conv`` (the convolution and
its state write), ``attn_qkv``, ``rope``, ``cache_update``, ``attention``,
``attn_proj``, ``mlp`` (the dense layer), ``moe_route``, ``moe_experts``,
``unembed``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import numpy as np

from ..server.timeline import SPAN_PREFILL_CHUNK, span
from .decoder import RungCount, TinyDecoderModel, ladder_of
from .routed_decoder import expert_layer, rms, rotary, rotary_table, seeded_params

KINDS = ("conv", "full_attention")
# the B * u rows a conv layer's state holds: a convolution of length 3 reads
# the token's own and the two before it
CONV_STATE = 2
# int32 behind the slots' choices in ``fed``: the experts the rounds' grouped
# products read and the rounds; the same for the prompts' chunks
FED_TALLY = 4


class Sizes(NamedTuple):
    """What the block reads of a configuration."""

    vocab: int
    d_model: int
    kinds: Tuple[str, ...]
    dense: int
    heads: int
    kv_heads: int
    head_dim: int
    mlp_width: int
    expert_width: int
    experts: int
    experts_per_token: int
    renormalise: bool
    scaling: float
    eps: float
    theta: float
    max_len: int
    prefill_chunk: int
    dtype: str

    @property
    def layers(self) -> int:
        return len(self.kinds)

    def place(self, i: int) -> int:
        """Layer ``i``'s index among the layers of its kind: its place in the
        table's list of that kind."""
        return self.kinds[:i].count(self.kinds[i])


def sizes_of(config: Dict[str, Any]) -> Sizes:
    """The configuration's keys, checked against what this block can run."""
    kinds = tuple(config["layer_types"])
    layers = int(config["num_hidden_layers"])
    if len(kinds) != layers or not set(kinds) <= set(KINDS):
        raise ValueError(f"layer_types must give each of the {layers} layers one of {KINDS}")
    if int(config["conv_L_cache"]) != CONV_STATE + 1:
        raise ValueError(f"a conv layer's state holds {CONV_STATE} rows: "
                         f"a convolution of length {CONV_STATE + 1}")
    if config.get("conv_bias", False):
        raise ValueError("the convolution and its projections have no bias")
    dense = int(config.get("num_dense_layers", 0))
    if not 0 <= dense <= layers:
        raise ValueError(f"{dense} dense layers of {layers}")
    if not config.get("use_expert_bias", False):
        raise ValueError("every routed layer chooses its experts with a bias")
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    kv_heads = int(config["num_key_value_heads"])
    if d % heads or heads % kv_heads:
        raise ValueError(f"{d} is not {heads} whole heads over {kv_heads} key-value heads")
    # the positions a sequence may reach here: what is reserved, where the
    # file says so, and the published context otherwise
    max_len = int(config.get("reserved_positions", config["max_position_embeddings"]))
    prefill_chunk = int(config.get("prefill_chunk", 256))
    if max_len % prefill_chunk:
        raise ValueError(f"{max_len} positions are not whole chunks of {prefill_chunk}")
    rope = config.get("rope_parameters") or {}
    return Sizes(
        vocab=int(config["vocab_size"]), d_model=d, kinds=kinds, dense=dense,
        heads=heads, kv_heads=kv_heads, head_dim=d // heads,
        mlp_width=int(config["intermediate_size"]),
        expert_width=int(config["moe_intermediate_size"]),
        experts=int(config["num_experts"]),
        experts_per_token=int(config["num_experts_per_tok"]),
        renormalise=bool(config.get("norm_topk_prob", True)),
        scaling=float(config.get("routed_scaling_factor", 1.0)),
        eps=float(config["norm_eps"]),
        theta=float(rope.get("rope_theta", config.get("rope_theta", 10000.0))),
        max_len=max_len, prefill_chunk=prefill_chunk,
        dtype=str(config.get("dtype", "bfloat16")))


def param_shapes(s: Sizes):
    """The weights' tree as ``jax.ShapeDtypeStruct``."""
    import jax
    import jax.numpy as jnp

    w = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.dtype(s.dtype))
    d, rows = s.d_model, s.kv_heads * s.head_dim

    def layer(i):
        if s.kinds[i] == "conv":
            own = {"conv_in": w(d, 3 * d), "conv_w": w(CONV_STATE + 1, d),
                   "conv_out": w(d, d)}
        else:
            own = {"wq": w(d, s.heads * s.head_dim), "wk": w(d, rows), "wv": w(d, rows),
                   "q_norm": w(s.head_dim), "k_norm": w(s.head_dim),
                   "wo": w(s.heads * s.head_dim, d)}
        if i < s.dense:
            own.update(mlp_gate=w(d, s.mlp_width), mlp_up=w(d, s.mlp_width),
                       mlp_down=w(s.mlp_width, d))
        else:
            f = s.expert_width
            own.update(router=w(d, s.experts), expert_bias=w(s.experts),
                       experts_gate=w(s.experts, d, f), experts_up=w(s.experts, d, f),
                       experts_down=w(s.experts, f, d))
        return {"ln1": w(d), "ln2": w(d), **own}

    return {"embed": w(s.vocab, d), "layers": [layer(i) for i in range(s.layers)],
            "final_norm": w(d), "unembed": w(d, s.vocab)}


def plain_scale(path: Tuple[str, ...], leaf):
    """The deviation (or ``(mean, deviation)``) a seeded model's leaf is
    drawn at unless its maker brings a rule (``init_scale``): gains 1, the
    expert bias 0, the convolution's taps by their count, stacked experts by
    their own fan-in, the table 1, every other matrix by its first axis."""
    name = path[-1]
    if name == "expert_bias":
        return (0.0, 0.0)
    if len(leaf.shape) == 1:
        return (1.0, 0.0)
    if name == "conv_w":
        return leaf.shape[0] ** -0.5
    if name == "embed":
        return 1.0
    return leaf.shape[-2] ** -0.5


def sigmoid_route(h2, router, bias, s: Sizes):
    """The router: float32 scores of the float32 ``h2`` [tokens, d] (at full
    precision, as ``routed_decoder.route`` scores: a score that the rounding
    of ``h2`` would move decides which experts run), ``sigmoid``; the
    ``experts_per_token`` largest of the scores plus ``bias`` ([tokens, k]
    ids), each weighted by its own score, renormalised where the
    configuration says so, times the scaling factor."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    scores = jax.nn.sigmoid(lax.dot_general(
        h2.astype(jnp.float32), router.astype(jnp.float32), (((1,), (0,)), ((), ())),
        precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32))
    _, which = lax.top_k(scores + bias.astype(jnp.float32), s.experts_per_token)
    gates = jnp.take_along_axis(scores, which, axis=-1)
    if s.renormalise:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return which.astype(jnp.int32), gates * s.scaling


def in_turn(flags, body, carry):
    """``body(slot, carry)`` for each slot flagged, lowest first, one turn of
    a loop each (their order by a comparison of ranks: a sort is a program of
    its own on the chip)."""
    import jax.numpy as jnp
    from jax import lax

    places = jnp.arange(flags.shape[0])
    rank = jnp.cumsum(flags) - 1
    order = jnp.sum(jnp.where(
        flags[None, :] & (rank[None, :] == places[:, None]), places[None, :], 0),
        axis=1)
    return lax.fori_loop(0, jnp.sum(flags, dtype=jnp.int32),
                         lambda n, carry: body(order[n], carry), carry)


class GatedConvDecoderModel(TinyDecoderModel):
    """``gated_conv_lm``: the block above at a configuration's sizes."""

    name = "gated_conv_lm"
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
        self._rungs = ladder_of(s.max_len)
        self._slot_prefill_chunk = s.prefill_chunk

    def ladder(self) -> Tuple[int, ...]:
        return ladder_of(self.sizes.max_len)

    def slots_read(self, slots: int, occupied: int) -> int:
        """Its round's attention reads every slot's prefix where it lies."""
        return slots

    def count_tally(self, count: RungCount, tally) -> None:
        """What the dispatches read back since the last read-back added to
        the tally behind the choices (``FED_TALLY``, in its order)."""
        count.add_reached("round", int(tally[0]), int(tally[1]))
        count.add_reached("chunk", int(tally[2]), int(tally[3]))

    # -- programs ------------------------------------------------------------
    def _build(self):
        import jax
        import jax.numpy as jnp
        from jax import lax

        s = self.sizes
        H, KV, Dh, Q, E = s.heads, s.kv_heads, s.head_dim, s.prefill_chunk, s.experts
        shapes = param_shapes(s)
        self._params = (shapes if self._seed is None
                        else seeded_params(shapes, self._seed, self._init_scale))

        f32 = jnp.float32
        # a product with a weight, accumulated and handed on in float32
        matmul = lambda x, w: lax.dot_general(
            x, w, (((x.ndim - 1,), (0,)), ((), ())), preferred_element_type=f32)
        norm = lambda x, gain: rms(x, gain, s.eps)
        # query head h's group of key-value heads, as a mask over a row's lanes
        own = jnp.asarray((np.arange(KV * Dh)[None, :] // Dh)
                          == (np.arange(H)[:, None] // (H // KV)))

        # -- the parts of a layer ------------------------------------------
        def conv_in(layer, x):
            """``C`` (float32) and ``B * u`` (in the state's type) [n, d]."""
            with jax.named_scope("conv_proj"):
                h = norm(x, layer["ln1"]).astype(layer["conv_in"].dtype)
                b, c, u = jnp.split(matmul(h, layer["conv_in"]), 3, axis=-1)
                return c, (b * u).astype(layer["conv_in"].dtype)

        def conv_out(layer, x, y):
            with jax.named_scope("conv_proj"):
                return x + matmul(y.astype(layer["conv_out"].dtype), layer["conv_out"])

        def taps(layer, before, before_last, now, c):
            """``y = C * (w0 * before + w1 * before_last + w2 * now)``."""
            w = layer["conv_w"].astype(f32)
            return c * (w[0] * before.astype(f32) + w[1] * before_last.astype(f32)
                        + w[2] * now.astype(f32))

        def conv_round(layer, x, state, fresh, active):
            """A conv layer of a round: ``x`` [slots, d], each slot's state
            [slots, 2, d] (from zeros where ``fresh``), written where
            ``active``."""
            c, bu = conv_in(layer, x)
            with jax.named_scope("short_conv"):
                prior = jnp.where(fresh[:, None, None], jnp.zeros_like(state), state)
                y = taps(layer, prior[:, 0], prior[:, 1], bu, c)
                new = jnp.stack([prior[:, 1], bu], axis=1)
                state = jnp.where(active[:, None, None], new, state)
            return conv_out(layer, x, y), state

        def conv_chunk(layer, x, state, slot, fresh, lo, hi):
            """A conv layer of a chunk of one slot: the tokens in rows ``lo``
            to ``hi`` of ``x`` [Q, d] follow the slot's state (zeros where
            ``fresh``), which is written as the last of them leaves it."""
            c, bu = conv_in(layer, x)
            with jax.named_scope("short_conv"):
                held = lax.dynamic_index_in_dim(state, slot, keepdims=False)
                prior = jnp.where(fresh, jnp.zeros_like(held), held)
                # the rows before ``lo`` are no token's: the position a row
                # ``back`` before row ``i`` is the chunk's own row from ``lo``
                # on, the state's before it
                line = jnp.concatenate([prior, bu], axis=0)  # [2 + Q, d]

                def back(rows, by):
                    at = rows - by
                    return jnp.clip(jnp.where(at >= lo, at + CONV_STATE,
                                              at - lo + CONV_STATE), 0, None)

                rows = jnp.arange(Q)
                y = taps(layer, line[back(rows, 2)], line[back(rows, 1)], bu, c)
                last = jnp.stack([line[back(hi, 2)], line[back(hi, 1)]])
                state = lax.dynamic_update_index_in_dim(
                    state, jnp.where(hi > lo, last, held), slot, 0)
            return conv_out(layer, x, y), state

        def qkv(layer, x, angles):
            """Queries [n, heads, head_dim], key and value rows [n, kv_heads *
            head_dim] of ``x`` [n, d], queries and keys turned by the
            positions' ``angles``."""
            n, dtype = x.shape[0], layer["wq"].dtype
            with jax.named_scope("attn_qkv"):
                h = norm(x, layer["ln1"]).astype(dtype)
                q = norm(matmul(h, layer["wq"]).reshape(n, H, Dh), layer["q_norm"])
                k = norm(matmul(h, layer["wk"]).reshape(n, KV, Dh), layer["k_norm"])
                v = matmul(h, layer["wv"]).astype(dtype)
            with jax.named_scope("rope"):
                q = rotary(q, *angles).astype(dtype)
                k = rotary(k, *angles).astype(dtype).reshape(n, KV * Dh)
            return q, k, v

        def attend(q, keys, values, mask):
            """``q`` [b, n, heads, head_dim] over the rows ``keys``, ``values``
            [b, positions, kv_heads * head_dim] as the table holds them, under
            ``mask`` [b, n, positions]: [b, n, heads * head_dim] in float32.
            Each head is laid in its group's lanes of a row and zeros in the
            others, so the products take the rows whole; of the values'
            product each head keeps its own group's lanes."""
            with jax.named_scope("attention"):
                laid = jnp.where(own, jnp.tile(q, (1, 1, 1, KV)), 0).astype(keys.dtype)
                scores = jnp.einsum("bnhc,bkc->bnhk", laid, keys,
                                    preferred_element_type=f32) * Dh ** -0.5
                scores = jnp.where(mask[:, :, None, :], scores, -1e30)
                probs = jax.nn.softmax(scores, axis=-1).astype(values.dtype)
                given = jnp.einsum("bnhk,bkc->bnhc", probs, values,
                                   preferred_element_type=f32)
                b, n = q.shape[:2]
                return jnp.sum(jnp.where(own, given, 0.0).reshape(b, n, H, KV, Dh),
                               axis=3).reshape(b, n, H * Dh)

        def attn_out(layer, x, attn):
            with jax.named_scope("attn_proj"):
                return x + matmul(attn.astype(layer["wo"].dtype), layer["wo"])

        def attention_round(layer, keys, values, x, angles, pos, active, *, live):
            """An attention layer of a round: every occupied slot's rows
            written at its position, a turn of a loop each, then every slot's
            query over its prefix of ``live`` positions."""
            q, k, v = qkv(layer, x, angles)
            with jax.named_scope("cache_update"):
                put = lambda table, rows, slot: lax.dynamic_update_slice(
                    table, lax.dynamic_index_in_dim(rows, slot)[:, None],
                    (slot, pos[slot], 0))
                keys, values = in_turn(
                    active, lambda slot, held: (put(held[0], k, slot),
                                                put(held[1], v, slot)),
                    (keys, values))
            mask = (jnp.arange(live)[None, :] <= pos[:, None])[:, None, :]
            attn = attend(q[:, None], keys[:, :live], values[:, :live], mask)
            return attn_out(layer, x, attn[:, 0]), keys, values

        def attention_chunk(layer, keys, values, x, angles, slot, base, kept, *, live):
            """An attention layer of a chunk of one slot: the rows of the
            tokens ``kept`` written from ``base`` on (the others keep what
            they held), then a causal attention over the slot's prefix of
            ``live`` positions."""
            q, k, v = qkv(layer, x, angles)
            with jax.named_scope("cache_update"):
                def write(table, rows):
                    old = lax.dynamic_slice(table, (slot, base, 0), (1, Q, KV * Dh))
                    return lax.dynamic_update_slice(
                        table, jnp.where(kept[None, :, None], rows[None], old),
                        (slot, base, 0))

                keys, values = write(keys, k), write(values, v)
            prefix = lambda table: lax.dynamic_slice(
                table, (slot, 0, 0), (1, live, KV * Dh))
            mask = jnp.arange(live)[None, :] <= (base + jnp.arange(Q))[:, None]
            attn = attend(q[None], prefix(keys), prefix(values), mask[None])
            return attn_out(layer, x, attn[0]), keys, values

        def dense(layer, x):
            with jax.named_scope("mlp"):
                dtype = layer["mlp_gate"].dtype
                h2 = norm(x, layer["ln2"]).astype(dtype)
                hidden = jax.nn.silu(matmul(h2, layer["mlp_gate"])) * matmul(
                    h2, layer["mlp_up"])
                return x + matmul(hidden.astype(dtype), layer["mlp_down"])

        def routed(layer, x, taken):
            """The routed layer over the tokens ``taken`` [n] (the others
            make no pair and are given nothing), and the distinct experts
            their pairs reach."""
            with jax.named_scope("moe_route"):
                h2 = norm(x, layer["ln2"])
                which, gates = sigmoid_route(h2, layer["router"], layer["expert_bias"], s)
                which = jnp.where(taken[:, None], which, E)  # E: no expert
                reached = jnp.sum(jnp.any(
                    which.reshape(-1)[:, None] == jnp.arange(E)[None, :], axis=0),
                    dtype=jnp.int32)
            with jax.named_scope("moe_experts"):
                given = expert_layer(h2.astype(layer["experts_gate"].dtype), which,
                                     gates, layer, s)
                return x + jnp.where(taken[:, None], given, 0.0), reached

        # one trace a part, whatever the number of layers that take it (the
        # chip's compiler inlines the calls)
        conv_round, conv_chunk, dense, routed = (
            jax.jit(f) for f in (conv_round, conv_chunk, dense, routed))
        attention_round = jax.jit(attention_round, static_argnames="live")
        attention_chunk = jax.jit(attention_chunk, static_argnames="live")

        def unembed(params, x):
            """Logits [n, vocab] in float32."""
            with jax.named_scope("unembed"):
                h = norm(x, params["final_norm"]).astype(params["unembed"].dtype)
                return matmul(h, params["unembed"])

        def layers(params, table, x, conv, attention, taken):
            """Every layer over ``x``: ``conv(layer, state, x)`` and
            ``attention(layer, keys, values, x)`` give the new ``x`` and that
            layer's state; the experts reached, summed."""
            table = {name: list(held) for name, held in table.items()}
            reached = jnp.zeros((), jnp.int32)
            for i, layer in enumerate(params["layers"]):
                j = s.place(i)
                if s.kinds[i] == "conv":
                    x, table["conv"][j] = conv(layer, table["conv"][j], x)
                else:
                    x, table["k"][j], table["v"][j] = attention(
                        layer, table["k"][j], table["v"][j], x)
                if i < s.dense:
                    x = dense(layer, x)
                else:
                    x, n = routed(layer, x, taken)
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
                lambda layer, state, x: conv_round(layer, x, state, pos == 0, active),
                lambda layer, keys, values, x: attention_round(
                    layer, keys, values, x, angles, pos, active, live=live),
                active)
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
                lambda layer, state, x: conv_chunk(
                    layer, x, state, slot, base + lo == 0, lo, hi),
                lambda layer, keys, values, x: attention_chunk(
                    layer, keys, values, x, angles, slot, base, kept, live=live),
                kept)
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
        """``slots`` sequences' state, stacked, zeros: an attention layer's
        key and value rows ``k``, ``v`` [slots, max_len, kv_heads * head_dim]
        and a conv layer's ``conv`` [slots, 2, d], a list a kind, in the
        order of the layers of that kind."""
        import jax.numpy as jnp

        s = self.sizes
        dtype = self._params["embed"].dtype
        rows = (slots, s.max_len, s.kv_heads * s.head_dim)
        attention = s.kinds.count("full_attention")
        return {"k": [jnp.zeros(rows, dtype) for _ in range(attention)],
                "v": [jnp.zeros(rows, dtype) for _ in range(attention)],
                "conv": [jnp.zeros((slots, CONV_STATE, s.d_model), dtype)
                         for _ in range(s.kinds.count("conv"))]}

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
