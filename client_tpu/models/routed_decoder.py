"""A decoder read from a configuration: routed experts, grouped-query
attention over the rows a learned indexer picks, and a prefill of many
tokens a dispatch, behind ``TinyDecoderModel``'s contract.

``RoutedDecoderModel(config)`` is served as ``TinyDecoderModel`` is: by
``TinyGenerateModel(decoder=...)`` on the decoupled stream and by its own
``execute`` on the sequence API. Its sizes are the keys of a Qwen3-MoE style
``config.json`` with an ``sa_config`` (the published sparse-attention
indexer's keys); no model's name appears here.

The block, for a token's residual ``x`` at position ``t``:

- ``h = rms(x; ln1)``; ``q = h wq`` (query heads), ``k = h wk``, ``v = h wv``
  (key-value heads, each shared by a group of query heads); ``q`` and ``k``
  RMS-normed over each head, then rotate-half rotary over the whole head.
- The indexer: ``qI = h idx_wq`` (its heads), ``kI = layernorm(h idx_wk)``
  (one head), the same rotary over their width; ``w = h idx_ww / sqrt(heads *
  width)``; ``I[t, s] = sum_j w[j] relu(qI[j] . kI_s)``.
- ``S_t``: every ``s <= t`` while ``t < topk``; else the ``topk`` positions of
  largest ``I[t, s]`` (the lower position first where two are equal).
- Each query head attends to its group's ``k_s, v_s``, ``s`` in ``S_t``.
- ``h2 = rms(x; ln2)``; a float32 softmax router over every expert, the best
  ``num_experts_per_tok`` renormalised; SwiGLU experts stacked
  ``[experts, d, f]``.
- ``rms(x; final_norm)``, an untied head, float32 logits.

What the serving path is made of:

- **Three cache rows a position a layer**, ``k``, ``v`` ``[max_len, kv_heads *
  head_dim]`` and the indexer's key ``ki`` ``[max_len, indexer width]``,
  written in place at the position (the programs donate their caches).
- **A ladder** (``decoder.ladder_of``): a step or a prefill chunk at rung
  ``live`` scores, selects among and reads only the first ``live`` positions.
  At a rung of ``topk`` or under every causal position is kept, so the
  program has no indexer beyond its key's write: that is exact. Above it the
  step scores ``live`` positions, keeps ``topk`` (``lax.top_k``) and gathers
  those rows alone; a prefill chunk finds each query's ``topk``-th score by
  bisection on the float's bits and attends under that mask in a blocked
  kernel (``ops/chunk_attention.py``) that keeps the scores out of HBM.
  Two routines because the two want different things of the same choice:
  a step's one query wants the ``topk`` *indices*, to gather those rows and
  read nothing else (``lax.top_k`` gives them; a mask would have to be
  compacted into them); a chunk's ``q_chunk_size`` queries want a *mask*
  over the prefix, since their ``q_chunk_size * topk`` indices would gather
  2 GB of rows a layer at the published sizes where the kernel reads each
  key block once for all of them (``largest_mask`` gives it without
  sorting 16 M scores a layer; ``tests/test_routed_decoder.py`` holds the
  two to the same set). ``top_k`` over a chunk's scores was not timed on
  the chip.
- **Prefill** (``prefill``): chunks of ``q_chunk_size`` tokens laid on the
  cache's own grid of that size (a chunk never straddles ``topk``; the last
  one is padded and masked; rows outside the chunk's tokens keep what they
  held), one waited-for dispatch at a time; the head runs for the last chunk
  alone.
- **An expert layer that is told which experts it holds** (``experts``:
  first and count; all of them by default): the router scores every expert,
  the token-expert pairs are sorted by expert and the grouped matrix product
  (``jax.experimental.pallas.ops.tpu.megablox.gmm``; interpret mode on the
  CPU) reads each reached expert's weights once and gives zero for pairs
  whose expert lives elsewhere. On one chip there is no exchange.

Weights, caches and matrix products in the configuration's ``dtype``
(bfloat16 where it states none; float32 accumulation); a float32 residual
stream, and float32 norms, rotary, router scores and softmax, index scores,
attention softmax and logits. Weights are an argument read from ``_params`` at each call; built
with ``seed=None`` they are ``jax.ShapeDtypeStruct`` and nothing is
allocated (``benchmark/family.py``).

Named scopes of both programs: ``embed``, ``attn_qkv``, ``rope``,
``cache_update``, ``indexer``, ``select``, ``sparse_attention``,
``attn_proj``, ``moe_route``, ``moe_experts``, ``unembed``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import numpy as np

from ..server.timeline import SPAN_PREFILL_CHUNK, span
from .decoder import RungCount, TinyDecoderModel, ladder_of


class Sizes(NamedTuple):
    """What the block reads of a configuration."""

    vocab: int
    d_model: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    expert_width: int
    experts: int
    experts_per_token: int
    renormalise: bool
    eps: float
    theta: float
    index_heads: int
    index_dim: int
    topk: int
    chunk: int
    max_len: int
    dtype: str


def sizes_of(config: Dict[str, Any]) -> Sizes:
    """The configuration's keys, checked against what this block can run."""
    sa = config["sa_config"]
    if int(sa.get("indexer_num_kv_heads", 1)) != 1:
        raise ValueError("the indexer's key has one head")
    if config.get("mlp_only_layers") or int(config.get("decoder_sparse_step", 1)) != 1:
        raise ValueError("every layer is a layer of routed experts")
    heads, kv_heads = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    if heads % kv_heads:
        raise ValueError(f"{heads} query heads do not share {kv_heads} key-value heads")
    # the positions a sequence may reach here: what is reserved, where the
    # file says so, and the published context otherwise
    max_len = int(config.get("reserved_positions", config["max_position_embeddings"]))
    chunk = int(sa["q_chunk_size"])
    if max_len % chunk:
        raise ValueError(f"{max_len} positions are not whole chunks of {chunk}")
    return Sizes(
        vocab=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
        layers=int(config["num_hidden_layers"]), heads=heads, kv_heads=kv_heads,
        head_dim=int(config["head_dim"]),
        expert_width=int(config["moe_intermediate_size"]),
        experts=int(config["num_experts"]),
        experts_per_token=int(config["num_experts_per_tok"]),
        renormalise=bool(config.get("norm_topk_prob", True)),
        eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]),
        index_heads=int(sa["indexer_num_heads"]), index_dim=int(sa["indexer_head_dim"]),
        topk=int(sa["topk"]), chunk=chunk, max_len=max_len,
        dtype=str(config.get("dtype", "bfloat16")))


def param_shapes(s: Sizes, held: Optional[int] = None):
    """The weights' tree as ``jax.ShapeDtypeStruct``; ``held`` experts a
    layer (all of them where it is left out)."""
    import jax
    import jax.numpy as jnp

    held = s.experts if held is None else held
    w = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.dtype(s.dtype))
    d, f = s.d_model, s.expert_width
    layer = lambda: {
        "ln1": w(d), "wq": w(d, s.heads * s.head_dim),
        "wk": w(d, s.kv_heads * s.head_dim), "wv": w(d, s.kv_heads * s.head_dim),
        "q_norm": w(s.head_dim), "k_norm": w(s.head_dim),
        "wo": w(s.heads * s.head_dim, d),
        "idx_wq": w(d, s.index_heads * s.index_dim), "idx_wk": w(d, s.index_dim),
        "idx_ww": w(d, s.index_heads),
        "idx_k_norm": w(s.index_dim), "idx_k_bias": w(s.index_dim),
        "ln2": w(d), "router": w(d, s.experts),
        "experts_gate": w(held, d, f), "experts_up": w(held, d, f),
        "experts_down": w(held, f, d)}
    return {"embed": w(s.vocab, d), "layers": [layer() for _ in range(s.layers)],
            "final_norm": w(d), "unembed": w(d, s.vocab)}


def plain_scale(path: Tuple[str, ...], leaf):
    """The deviation (or ``(mean, deviation)``) a seeded model's leaf is
    drawn at unless its maker brings a rule (``init_scale``): gains 1, the
    bias 0, the table 1, a matrix by its fan-in (the axis before the last:
    a stack of experts' own)."""
    if len(leaf.shape) == 1:
        return (0.0 if path[-1].endswith("bias") else 1.0, 0.0)
    return 1.0 if path[-1] == "embed" else leaf.shape[-2] ** -0.5


def seeded_params(shapes, seed: int, init_scale: Callable):
    """Weights of the shapes' tree drawn on the host from ``seed``: each leaf
    normal at the deviation (or ``(mean, deviation)``) that
    ``init_scale(path, leaf)`` gives it, ``path`` being its keys as
    strings."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    paths, tree = jax.tree_util.tree_flatten_with_path(shapes)

    def draw(path, leaf):
        drawn = init_scale(
            tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path), leaf)
        mean, scale = drawn if isinstance(drawn, tuple) else (0.0, drawn)
        return jnp.asarray(mean + scale * rng.standard_normal(
            leaf.shape).astype(np.float32), dtype=leaf.dtype)

    return jax.tree_util.tree_unflatten(
        tree, [draw(path, leaf) for path, leaf in paths])


# -- the block's parts: pure functions of arrays ------------------------------

def rms(x, gain, eps: float):
    import jax.numpy as jnp
    from jax import lax

    x32 = x.astype(jnp.float32)
    scaled = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return scaled * gain.astype(jnp.float32)


def layer_norm(x, gain, bias, eps: float):
    import jax.numpy as jnp
    from jax import lax

    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    scaled = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return scaled * gain.astype(jnp.float32) + bias.astype(jnp.float32)


def rotary_table(width: int, positions: int, theta: float):
    """Cosines and sines [positions, width / 2] of ``position * theta ** (-2 i
    / width)``, worked out on the host in float64 and rounded once: a float32
    power and product on the chip put an angle at position 29,000 off by 0.03
    (my chip run, PR 32)."""
    half = width // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    angle = np.arange(positions, dtype=np.float64)[:, None] * freq
    return np.cos(angle).astype(np.float32), np.sin(angle).astype(np.float32)


def rotary(x, cos, sin):
    """Rotate-half rotary over the last axis of ``x`` [n, heads, width]
    (float32) by the angles whose ``cos`` and ``sin`` [n, width / 2] are
    given."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def index_scores(q_index, weights, keys):
    """``I[c, s] = sum_j weights[c, j] relu(q_index[c, j] . keys[s])`` in
    float32; ``q_index`` [queries, heads, width] and ``keys`` [positions,
    width] bfloat16, ``weights`` [queries, heads] float32. Head by head, so
    that what lives beside the scores is one head's products."""
    import jax.numpy as jnp
    from jax import lax

    if q_index.shape[0] == 1:  # a step: every head's products at once
        dots = lax.dot_general(q_index[0], keys, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
        return jnp.sum(weights[0][:, None] * jnp.maximum(dots, 0.0), axis=0)[None]

    def one_head(j, acc):
        dots = lax.dot_general(
            lax.dynamic_index_in_dim(q_index, j, 1, keepdims=False), keys,
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        w = lax.dynamic_index_in_dim(weights, j, 1, keepdims=True)
        return acc + w * jnp.maximum(dots, 0.0)

    zero = jnp.zeros((q_index.shape[0], keys.shape[0]), jnp.float32)
    return lax.fori_loop(0, q_index.shape[1], one_head, zero)


def _ordered_bits(scores):
    """Float32 scores as unsigned integers that sort as the floats do."""
    import jax.numpy as jnp
    from jax import lax

    bits = lax.bitcast_convert_type(scores, jnp.int32)
    ordered = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return lax.bitcast_convert_type(ordered, jnp.uint32) ^ jnp.uint32(0x80000000)


def largest_mask(scores, k: int):
    """For each row of ``scores`` [rows, n] (float32, no NaN) the mask of its
    ``k`` largest entries, exactly ``k`` of them, the lower index first where
    entries are equal: what ``lax.top_k`` picks, as a mask and without a
    sort. The ``k``-th largest value is found bit by bit (32 counts over the
    row), then equal entries are admitted in order until ``k`` are in."""
    import jax.numpy as jnp
    from jax import lax

    keys = _ordered_bits(scores)

    def one_bit(i, found):
        trial = found | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(keys >= trial[:, None], axis=-1, dtype=jnp.int32) >= k
        return jnp.where(enough, trial, found)

    kth = lax.fori_loop(0, 32, one_bit, jnp.zeros(scores.shape[0], jnp.uint32))
    above = keys > kth[:, None]
    equal = keys == kth[:, None]
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    return above | (equal & (jnp.cumsum(equal, axis=-1, dtype=jnp.int32)
                             <= room[:, None]))


def attend(q, keys, values, mask, s: Sizes, base=None):
    """Grouped-query attention of ``q`` [queries, heads * head_dim] over
    ``keys``, ``values`` [positions, kv_heads * head_dim] under ``mask``
    [queries, positions]; [queries, heads * head_dim] in float32. Over a
    prefix of the cache, for queries at consecutive positions from ``base``
    on, it is the blocked kernel (``ops/chunk_attention.py``), which reads
    the keys where they lie and keeps the scores out of HBM. Over rows that
    were gathered for one query (``base`` left out) it is two small
    products."""
    import jax
    import jax.numpy as jnp

    if base is not None:
        from ..ops.chunk_attention import chunk_attention

        return chunk_attention(q, keys, values, mask, base,
                               kv_heads=s.kv_heads, head_dim=s.head_dim)
    positions, group = keys.shape[0], s.heads // s.kv_heads
    qg = q.reshape(s.kv_heads, group, s.head_dim)
    kg = keys.reshape(positions, s.kv_heads, s.head_dim)
    vg = values.reshape(positions, s.kv_heads, s.head_dim)
    scores = jnp.einsum("ghd,sgd->ghs", qg, kg,
                        preferred_element_type=jnp.float32) * s.head_dim ** -0.5
    probs = jax.nn.softmax(jnp.where(mask[0][None, None, :], scores, -jnp.inf),
                           axis=-1).astype(values.dtype)
    out = jnp.einsum("ghs,sgd->ghd", probs, vg, preferred_element_type=jnp.float32)
    return out.reshape(1, s.heads * s.head_dim)


def route(h2, router, s: Sizes):
    """The router: float32 scores of the float32 ``h2`` [tokens, d] (a product
    of 2048 by 128 a token: at full precision it costs nothing, and a score
    that the rounding of ``h2`` would move decides which experts run), a
    softmax over every expert, the best ``experts_per_token`` of each token
    ([tokens, k] ids and weights), renormalised where the configuration says
    so."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    logits = lax.dot_general(
        h2.astype(jnp.float32), router.astype(jnp.float32), (((1,), (0,)), ((), ())),
        precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32)
    best, which = lax.top_k(jax.nn.softmax(logits, axis=-1), s.experts_per_token)
    if s.renormalise:
        best = best / jnp.sum(best, axis=-1, keepdims=True)
    return which.astype(jnp.int32), best


def expert_layer(h2, which, gates, layer, s: Sizes, first: int = 0,
                 interpret: Optional[bool] = None):
    """What the experts held here (``first`` onward, as many as ``layer``'s
    stacks hold) give the tokens ``h2`` [tokens, d] routed as ``which`` and
    weighted as ``gates`` [tokens, k]: [tokens, d] in float32, with nothing
    for a pair whose expert lives elsewhere. The pairs are sorted by expert;
    the grouped product reads the weights of each expert that some pair
    reaches once."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from ..ops import _on_tpu

    interpret = (not _on_tpu()) if interpret is None else interpret
    tokens, k = which.shape
    pairs = tokens * k
    flat = which.reshape(pairs)
    order = jnp.argsort(flat, stable=True)
    counts = jnp.sum(flat[:, None] == jnp.arange(s.experts)[None, :], axis=0,
                     dtype=jnp.int32)
    rows = h2[order // k]  # [pairs, d], by expert
    offset = jnp.asarray(first, jnp.int32)
    tile_rows = min(128, pairs)

    def grouped(x, stacked, out_dtype):
        # a block of an expert's weights of at most 3 MiB: twice that, the
        # rows' block and the accumulator stay inside the 16 MiB a kernel
        # may scope on a v5e
        depth, width = stacked.shape[1:]
        depth_tile = min(depth, 4096 // stacked.dtype.itemsize)
        return gmm(x, stacked, counts, out_dtype,
                   (tile_rows, depth_tile, min(width, 1024)),
                   offset, interpret=interpret)

    gate = grouped(rows, layer["experts_gate"], jnp.float32)
    up = grouped(rows, layer["experts_up"], jnp.float32)
    hidden = (jax.nn.silu(gate) * up).astype(rows.dtype)
    down = grouped(hidden, layer["experts_down"], jnp.float32)
    # back to the pairs' own order, each weighted, a token's pairs added up
    back = jnp.argsort(order)
    return jnp.sum(down[back].reshape(tokens, k, -1) * gates[..., None], axis=1)


# -- the model ------------------------------------------------------------------

class RoutedDecoderModel(TinyDecoderModel):
    """``routed_lm``: the block above at a configuration's sizes."""

    name = "routed_lm"

    def __init__(self, config: Dict[str, Any], seed: Optional[int] = 0,
                 experts: Optional[Tuple[int, int]] = None,
                 init_scale: Callable = plain_scale):
        """``seed=None`` leaves the weights as shapes for whoever puts them
        there. ``experts``: ``(first, count)`` of the experts held here.
        ``init_scale(path, leaf)``: the rule a seeded model's weights are
        drawn by (``benchmark/family.py``'s, so a family's own can be
        given)."""
        super().__init__(seed=seed)
        self._init_scale = init_scale
        self.sizes = s = sizes_of(config)
        self.VOCAB, self.D_MODEL, self.HEADS = s.vocab, s.d_model, s.heads
        self.LAYERS, self.MAX_LEN = s.layers, s.max_len
        self.experts_held = (0, s.experts) if experts is None else tuple(experts)
        self._rungs = ladder_of(s.max_len)
        self._prefill_fn = None

    def ladder(self) -> Tuple[int, ...]:
        return ladder_of(self.sizes.max_len)

    def selects(self, pos: int) -> bool:
        """Whether the token at ``pos`` attends to a chosen subset."""
        return pos >= self.sizes.topk

    # -- programs ------------------------------------------------------------
    def _build(self):
        import jax
        import jax.numpy as jnp
        from jax import lax

        s = self.sizes
        first, held = self.experts_held
        shapes = param_shapes(s, held)
        self._params = (shapes if self._seed is None
                        else seeded_params(shapes, self._seed, self._init_scale))

        # the type of the weights is the type of the caches and of every
        # matrix product's operands and result (bfloat16 as served)
        matmul = lambda x, w: lax.dot_general(
            x, w, (((x.ndim - 1,), (0,)), ((), ())), preferred_element_type=w.dtype)

        def layer_of(layer, cache, x, positions, angles, kept, base, *, live):
            """One layer over ``x`` [n, d] at ``positions`` [n], whose rotary
            ``angles`` are (cos, sin) of a head and of the indexer; rows of
            the cache are written for the tokens ``kept`` [n] alone, as one
            block at ``base``."""
            head_angles, index_angles = angles
            n, dtype = x.shape[0], layer["wq"].dtype
            with jax.named_scope("attn_qkv"):
                h = rms(x, layer["ln1"], s.eps).astype(dtype)
                q = rms(matmul(h, layer["wq"]).reshape(n, s.heads, s.head_dim),
                        layer["q_norm"], s.eps)
                k = rms(matmul(h, layer["wk"]).reshape(n, s.kv_heads, s.head_dim),
                        layer["k_norm"], s.eps)
                v = matmul(h, layer["wv"])
            with jax.named_scope("rope"):
                q = rotary(q, *head_angles).astype(dtype).reshape(n, -1)
                k = rotary(k, *head_angles).astype(dtype).reshape(n, -1)
            with jax.named_scope("indexer"):
                ki = layer_norm(matmul(h, layer["idx_wk"]), layer["idx_k_norm"],
                                layer["idx_k_bias"], s.eps)
                ki = rotary(ki[:, None, :], *index_angles)[:, 0].astype(dtype)
            with jax.named_scope("cache_update"):
                written = {}
                for name, rows in (("k", k), ("v", v), ("ki", ki)):
                    if n > 1:  # a chunk's block: rows outside it keep theirs
                        old = lax.dynamic_slice(cache[name], (base, 0), rows.shape)
                        rows = jnp.where(kept[:, None], rows, old)
                    written[name] = lax.dynamic_update_slice(
                        cache[name], rows, (base, 0))
            causal = jnp.arange(live)[None, :] <= positions[:, None]
            if live <= s.topk:
                with jax.named_scope("sparse_attention"):
                    attn = attend(q, written["k"][:live], written["v"][:live],
                                  causal, s, base)
            else:
                with jax.named_scope("indexer"):
                    qi = rotary(
                        matmul(h, layer["idx_wq"]).reshape(
                            n, s.index_heads, s.index_dim).astype(jnp.float32),
                        *index_angles).astype(dtype)
                    wi = (matmul(h, layer["idx_ww"]).astype(jnp.float32)
                          * (s.index_heads * s.index_dim) ** -0.5)
                    scores = jnp.where(
                        causal, index_scores(qi, wi, written["ki"][:live]), -jnp.inf)
                if n == 1:
                    with jax.named_scope("select"):
                        _, chosen = lax.top_k(scores[0], s.topk)
                    with jax.named_scope("sparse_attention"):
                        attn = attend(q, written["k"][chosen], written["v"][chosen],
                                      (chosen <= positions[0])[None, :], s)
                else:
                    with jax.named_scope("select"):
                        mask = largest_mask(scores, s.topk) & causal
                    with jax.named_scope("sparse_attention"):
                        attn = attend(q, written["k"][:live], written["v"][:live],
                                      mask, s, base)
            with jax.named_scope("attn_proj"):
                x = x + lax.dot_general(
                    attn.astype(dtype), layer["wo"], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            with jax.named_scope("moe_route"):
                h2 = rms(x, layer["ln2"], s.eps)
                which, gates = route(h2, layer["router"], s)
            with jax.named_scope("moe_experts"):
                x = x + expert_layer(h2.astype(dtype), which, gates, layer, s, first)
            return x, written

        def unembed(params, x):
            with jax.named_scope("unembed"):
                h = rms(x, params["final_norm"], s.eps).astype(params["unembed"].dtype)
                return lax.dot_general(h, params["unembed"], (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

        def angles_at(tables, first, n):
            """The ``n`` rows from ``first`` on of each table of angles."""
            return tuple(tuple(lax.dynamic_slice_in_dim(table, first, n)
                               for table in pair) for pair in tables)

        def routed_step(params, caches, tables, token, pos, *, live=s.max_len):
            """One token at ``pos``: ``(logits, caches)``, the caches the
            donated ones with row ``pos`` written."""
            pos = jnp.asarray(pos, jnp.int32)
            with jax.named_scope("embed"):
                x = params["embed"][token][None, :].astype(jnp.float32)
            positions, angles = pos[None], angles_at(tables, pos, 1)
            new_caches = []
            for layer, cache in zip(params["layers"], caches):
                x, cache = layer_of(layer, cache, x, positions, angles, None, pos,
                                    live=live)
                new_caches.append(cache)
            return unembed(params, x[0]), new_caches

        def routed_prefill(params, caches, tables, tokens, base, lo, hi, last, *,
                           live=s.max_len):
            """One chunk: the tokens in slots ``lo`` to ``hi`` of ``tokens``
            [chunk] are at positions ``base + slot``; their rows are written,
            every other row of the block keeps what it held. With ``last``
            the logits after slot ``hi - 1``; zeros otherwise."""
            base = jnp.asarray(base, jnp.int32)
            slots = jnp.arange(s.chunk, dtype=jnp.int32)
            kept = (slots >= lo) & (slots < hi)
            with jax.named_scope("embed"):
                x = params["embed"][tokens].astype(jnp.float32)
            angles = angles_at(tables, base, s.chunk)
            new_caches = []
            for layer, cache in zip(params["layers"], caches):
                x, cache = layer_of(layer, cache, x, base + slots, angles, kept,
                                    base, live=live)
                new_caches.append(cache)
            logits = lax.cond(
                last,
                lambda: unembed(params, lax.dynamic_index_in_dim(
                    x, hi - 1, 0, keepdims=False)),
                lambda: jnp.zeros((s.vocab,), jnp.float32))
            return logits, new_caches

        # the rotary's angles, a table a width: an argument of the programs
        # beside the weights, and no weight
        self._tables = tuple(
            tuple(jnp.asarray(table) for table in rotary_table(width, s.max_len, s.theta))
            for width in (s.head_dim, s.index_dim))
        step = self._step_program = jax.jit(
            routed_step, donate_argnums=1, static_argnames="live")
        chunk = self._prefill_program = jax.jit(
            routed_prefill, donate_argnums=1, static_argnames="live")
        # TinyDecoderModel's contract: (params, caches, token, pos, live=)
        self._step_fn = lambda params, caches, *args, **live: step(
            params, caches, self._tables, *args, **live)
        self._prefill_fn = lambda params, caches, *args, **live: chunk(
            params, caches, self._tables, *args, **live)

    def _fresh_cache(self):
        import jax.numpy as jnp

        s = self.sizes
        row, dtype = s.kv_heads * s.head_dim, self._params["embed"].dtype
        return [{"k": jnp.zeros((s.max_len, row), dtype),
                 "v": jnp.zeros((s.max_len, row), dtype),
                 "ki": jnp.zeros((s.max_len, s.index_dim), dtype)}
                for _ in range(s.layers)]

    def _ensure_warm(self) -> None:
        """Step and prefill compiled at every rung, by one real call of each
        on a scratch cache, before the first sequence is served."""
        if self._warm:
            return
        with self._warm_lock:
            if self._warm:
                return
            caches = self._fresh_cache()
            tokens = np.zeros(self.sizes.chunk, np.int32)
            for live in self._rungs:
                _, caches = self._step_at(caches, 0, 0, live)
                logits, caches = self._prefill_fn(
                    self._params, caches, tokens, 0, 0, 1, True, live=live)
            logits.block_until_ready()
            self._warm = True

    def decode_step(self, caches, token: int, pos: int,
                    count: Optional[RungCount] = None):
        count = self.steps_by_rung if count is None else count
        if self.selects(pos):
            count.add_selecting()
        return super().decode_step(caches, token, pos, count)

    def _advance(self, caches, tokens, pos: int):
        """The sequence API's request: a prompt through ``prefill``, a
        continuation's one token through the step (a chunk program for one
        token would do 512 tokens' work)."""
        if len(tokens) == 1:
            return self.decode_step(caches, int(tokens[0]), pos)
        return self.prefill(caches, tokens, pos)

    def prefill(self, caches, tokens, pos: int, count: Optional[RungCount] = None):
        """``tokens`` from ``pos`` on, a chunk a dispatch, each waited for
        (one dispatch of a stream in the device's queue at a time);
        ``(logits, caches)`` after the last token."""
        self._ensure_warm()
        count = self.steps_by_rung if count is None else count
        chunk = self.sizes.chunk
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        end, logits = pos + tokens.size, None
        while pos < end:
            base = pos - pos % chunk
            upto = min(end, base + chunk)
            block = np.zeros(chunk, np.int32)
            block[pos - base:upto - base] = tokens[tokens.size - (end - pos):
                                                   tokens.size - (end - upto)]
            with span(SPAN_PREFILL_CHUNK):
                logits, caches = self._prefill_fn(
                    self._params, caches, block, base, pos - base, upto - base,
                    upto == end, live=self.rung_for(upto))
                logits.block_until_ready()
            count.add_prefill(upto - pos)
            pos = upto
        return logits, caches
