"""The gated-convolution family's reference: the full forward pass in float32.

Written from the equations of the family (the configuration file's
``assumed``), in straightforward ``jax.numpy`` at ``highest`` precision: no
cache, no conv state, no table of slots, no rounds, no kernel, and nothing
imported from the program. For a token's residual ``x`` at position ``t``,
every norm an RMS norm with a gain:

- A conv layer: ``h = rms(x; ln1)``; ``[B, C, u] = h conv_in``;
  ``y_t = C_t * sum_j conv_w[j] * (B * u)_{t-2+j}`` over the whole sequence,
  zero before position 0; ``x += y conv_out``.
- An attention layer: ``h = rms(x; ln1)``; ``q = rms_head(h wq; q_norm)``,
  ``k = rms_head(h wk; k_norm)``, ``v = h wv``; rotate-half rotary over the
  whole head at ``rope_theta``; every query head attends causally to its
  group's keys and values; ``x += o wo``.
- ``h2 = rms(x; ln2)``. The first ``num_dense_layers`` layers:
  ``x += (silu(h2 mlp_gate) * (h2 mlp_up)) mlp_down``. Every later one:
  ``s = sigmoid(h2 router)``; a token's experts the ``num_experts_per_tok``
  largest of ``s + expert_bias``, each weighted by its ``s``, renormalised
  (``norm_topk_prob``), times ``routed_scaling_factor``;
  ``x += sum_e g_e down_e(silu(gate_e h2) * up_e h2)``, token by token.
- ``rms(x; final_norm)``, the untied head.

How it is made to fit beside the served weights (they come in as data, in
bfloat16, and are cast a matrix at a time): each session is passed alone at
the traffic's longest length rounded up to a power of two, so that a run's
sessions and its seeds share one set of programs; the padding lies after
every real position and reaches none, its query blocks are not worked and
its rows go to no expert. The queries are worked in blocks of
``QUERY_BLOCK`` against every key, the experts one at a time over the tokens
routed to them (``routed_reference._experts``), and the head over the
positions that produced a served token alone.

**Near ties of the router are set aside.** With random weights the 4th and
5th biased scores of a token lie within rounding of each other at some
positions; the served program, whose residual stream has been through
bfloat16 products, and this pass then take different experts, and the gap
there reads the tie, not the arithmetic. A served position at which, in any
routed layer, the last chosen and the first unchosen biased score lie within
``NEAR`` is not compared, and the share of served positions so set aside is
returned as ``near_tie_share`` for the cell's file to hold to a limit. A tie
at an earlier position is not carried forward: it reaches a later token as
one row among those attended to and through no conv state beyond the next
two positions. ``NEAR`` is a distance of scores (sigmoids, 0 to 1, plus the bias), not of
logits as the routed family's, and is what bfloat16 can reorder directly: in
the first routed layer at the published widths a pass of the layers before it
with every product's inputs rounded to bfloat16 moves a biased score by
0.00036 (deviation), 0.0010 at the 99th percentile and 0.0019 at most, and
every choice it changed had a margin under 0.0012 (this reference against
itself, 1,024 tokens over two seeds, the family's weights, on the CPU;
PERF.md section 6). In a later routed layer the rounding is joined by the
first's changed choices, at the position and at the two after it through the
conv state: there the scores move by 0.00066 (deviation) and 0.019 at most,
and two of ten changed choices had margins of 0.0025 and 0.0054; the cell's
limit on ``served_gap_max`` holds what so reaches a compared position.

``control=True`` is the same pass with every weight matrix and every matrix
product's input rounded to float8 (e4m3, one scale a tensor), the nearest
step below the bfloat16 the configuration states.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import _HIGHEST, _gaps
from benchmark.routed_reference import _experts, _fp8, padded_length

NEAR = 0.002  # biased scores: set aside where the last in and first out lie this close
BANDS = (1, 2, 4, None)  # of NEAR: the widest gap is reported by margin, band by band
QUERY_BLOCK = 256


def _sizes(config):
    heads = int(config["num_attention_heads"])
    rope = config.get("rope_parameters") or {}
    return dict(
        heads=heads, kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["hidden_size"]) // heads, eps=float(config["norm_eps"]),
        theta=float(rope.get("rope_theta", config.get("rope_theta", 10000.0))),
        kinds=tuple(config["layer_types"]), dense=int(config.get("num_dense_layers", 0)),
        k=int(config["num_experts_per_tok"]),
        renormalise=bool(config.get("norm_topk_prob", True)),
        scaling=float(config.get("routed_scaling_factor", 1.0)))


def _mm(x, w, precision: str):
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x, w = _fp8(x), _fp8(w)
    return jnp.matmul(x, w, precision=_HIGHEST)


def _rms(x, gain, eps):
    return (x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * gain.astype(jnp.float32))


def _rotary(x, theta):
    """``x`` [positions, heads, width] at positions 0, 1, ...: rotate-half,
    the angles worked out on the host in float64 and rounded once."""
    half = x.shape[-1] // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    angle = np.arange(x.shape[0], dtype=np.float64)[:, None, None] * freq
    cos, sin = np.cos(angle).astype(np.float32), np.sin(angle).astype(np.float32)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@partial(jax.jit, static_argnames=("eps", "precision"))
def _conv(x, layer, *, eps, precision):
    """``x`` after a conv layer, over the whole sequence."""
    b, c, u = jnp.split(_mm(_rms(x, layer["ln1"], eps), layer["conv_in"], precision),
                        3, axis=-1)
    bu = jnp.concatenate([jnp.zeros((2, x.shape[1]), jnp.float32), b * u])
    w = layer["conv_w"].astype(jnp.float32)
    y = c * (w[0] * bu[:-2] + w[1] * bu[1:-1] + w[2] * bu[2:])
    return x + _mm(y, layer["conv_out"], precision)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "eps", "theta",
                                   "precision"))
def _projections(x, layer, *, heads, kv_heads, head_dim, eps, theta, precision):
    """Queries, keys (both normed over each head and turned) and values."""
    n = x.shape[0]
    h = _rms(x, layer["ln1"], eps)
    q = _rms(_mm(h, layer["wq"], precision).reshape(n, heads, head_dim),
             layer["q_norm"], eps)
    k = _rms(_mm(h, layer["wk"], precision).reshape(n, kv_heads, head_dim),
             layer["k_norm"], eps)
    v = _mm(h, layer["wv"], precision).reshape(n, kv_heads, head_dim)
    return _rotary(q, theta), _rotary(k, theta), v


@partial(jax.jit, static_argnames=("block", "precision"))
def _attention(x, q, k, v, wo, blocks, *, block, precision):
    """``x`` plus the attention's projected output: the first ``blocks``
    blocks of ``block`` queries, each against every key under the causal
    mask, every query head over its group's keys and values."""
    n, heads, width = q.shape
    group = heads // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    if precision == "fp8":
        q, k, v = _fp8(q), _fp8(k), _fp8(v)

    def one(i, out):
        first = i * block
        qb = jax.lax.dynamic_slice_in_dim(q, first, block, axis=0)
        scores = jnp.einsum("bhd,khd->hbk", qb, k, precision=_HIGHEST) * width ** -0.5
        causal = jnp.arange(n)[None, None, :] <= (first + jnp.arange(block))[None, :, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        if precision == "fp8":
            probs = _fp8(probs)
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.einsum("hbk,khd->bhd", probs, v, precision=_HIGHEST), first, axis=0)

    attn = jax.lax.fori_loop(0, blocks, one, jnp.zeros_like(q))
    return x + _mm(attn.reshape(n, heads * width), wo, precision)


@partial(jax.jit, static_argnames=("eps", "precision"))
def _dense(x, layer, *, eps, precision):
    h2 = _rms(x, layer["ln2"], eps)
    hidden = jax.nn.silu(_mm(h2, layer["mlp_gate"], precision)) * _mm(
        h2, layer["mlp_up"], precision)
    return x + _mm(hidden, layer["mlp_down"], precision)


@partial(jax.jit, static_argnames=("eps", "k", "renormalise", "scaling", "precision"))
def _route(x, layer, *, eps, k, renormalise, scaling, precision):
    """``h2``, the chosen experts and their weights, and the margin between
    the last chosen and the first unchosen biased score."""
    h2 = _rms(x, layer["ln2"], eps)
    scores = jax.nn.sigmoid(_mm(h2, layer["router"], precision))
    ranked, which = jax.lax.top_k(scores + layer["expert_bias"].astype(jnp.float32), k + 1)
    which = which[:, :k]
    best = jnp.take_along_axis(scores, which, axis=-1)
    if renormalise:
        best = best / jnp.sum(best, axis=-1, keepdims=True)
    return h2, which, best * scaling, ranked[:, k - 1] - ranked[:, k]


def _routed(x, layer, s, precision: str, real: int):
    """``x`` plus what the experts give the tokens routed to them, and each
    position's margin. The host puts the token-expert pairs in the order of
    their experts (none of a row from ``real`` on, the padding) and the chip
    runs the experts one at a time."""
    h2, which, best, margin = _route(
        x, layer, eps=s["eps"], k=s["k"], renormalise=s["renormalise"],
        scaling=s["scaling"], precision=precision)
    experts = layer["experts_gate"].shape[0]
    local = np.asarray(which).reshape(-1)
    here = np.arange(local.size) // s["k"] < real
    order = np.flatnonzero(here)
    order = order[np.argsort(local[order], kind="stable")]  # pairs, by expert
    starts = np.searchsorted(local[order], np.arange(experts))
    longest = padded_length(np.diff(np.append(starts, order.size)).max(initial=1))
    tokens = np.zeros(local.size + longest, np.int32)
    tokens[:order.size] = order // s["k"]
    where = np.zeros(local.size, np.int32)
    where[order] = np.arange(order.size)
    return _experts(
        x, h2, jnp.asarray(tokens), jnp.asarray(starts, jnp.int32),
        jnp.asarray(where.reshape(which.shape)),
        best * jnp.asarray(here.reshape(which.shape), jnp.float32),
        layer["experts_gate"], layer["experts_up"], layer["experts_down"],
        longest=longest, precision=precision), margin


@partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, gain, unembed, eps, precision):
    return _mm(_rms(x, gain, eps), unembed, precision)


def _forward(params, config, tokens, want, precision, padded):
    """``forward`` with the logits' rows padded to a power of two (the last
    wanted position again), so that sessions of different output lengths
    share the programs."""
    if precision not in ("float32", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    s = _sizes(config)
    n = padded_length(len(tokens)) if padded is None else padded
    if n < len(tokens) or n != padded_length(n):
        raise ValueError(f"{len(tokens)} tokens do not pad to {n}")
    ids = np.zeros(n, np.int32)
    ids[:len(tokens)] = tokens
    x = params["embed"][jnp.asarray(ids)].astype(jnp.float32)
    block = min(QUERY_BLOCK, n)
    blocks = -(-len(tokens) // block)  # those with a token in them
    want = np.asarray(want)
    margins = []
    for i, layer in enumerate(params["layers"]):
        if s["kinds"][i] == "conv":
            x = _conv(x, layer, eps=s["eps"], precision=precision)
        else:
            q, k, v = _projections(
                x, layer, precision=precision,
                **{key: s[key] for key in ("heads", "kv_heads", "head_dim", "eps",
                                           "theta")})
            x = _attention(x, q, k, v, layer["wo"], blocks, block=block,
                           precision=precision)
            del q, k, v
        if i < s["dense"]:
            x = _dense(x, layer, eps=s["eps"], precision=precision)
        else:
            x, margin = _routed(x, layer, s, precision, real=len(tokens))
            margins.append(np.asarray(margin)[want])
    rows = np.full(padded_length(want.size), want[-1])
    rows[:want.size] = want
    logits = _head(x[jnp.asarray(rows)], params["final_norm"], params["unembed"],
                   s["eps"], precision)
    return logits, np.stack(margins) if margins else np.full((1, want.size), np.inf)


def forward(params: Dict[str, Any], config: Dict[str, Any], tokens, want,
            precision: str = "float32", padded=None):
    """Logits [len(want), vocab] at the positions ``want`` of the full pass
    over one sequence of ``tokens``, and each routed layer's margin there;
    the sequence is passed at ``padded`` positions (a power of two; its own
    length rounded up where left out)."""
    logits, margins = _forward(params, config, tokens, want, precision, padded)
    return logits[:len(want)], margins


def served_token_gaps(params: Dict[str, Any], config: Dict[str, Any],
                      sessions: Sequence[Dict[str, Any]], length: int,
                      control: bool = False) -> Dict[str, Any]:
    """Teacher-force each session alone at ``length`` (the longest a session
    of the traffic may be) rounded up to a power of two, and read at every
    position that produced a served token how far that token's logit lies
    below the reference's best; positions at a near tie of the router are set
    aside and counted (``near_tie_share``). With ``control`` the same for the
    token the fp8 pass puts first."""
    served: List[float] = []
    lowered: List[float] = []
    nearest: List[float] = []
    produced = aside = 0
    n = padded_length(length)
    for session in sessions:
        full = list(session["prompt"]) + list(session["tokens"])
        if len(full) > length:
            raise ValueError(f"session of {len(full)} tokens, room for {length}")
        want = np.arange(len(session["prompt"]) - 1, len(full) - 1)
        logits, margins = _forward(params, config, full, want, "float32", n)
        target = np.zeros(logits.shape[0], np.int32)
        target[:want.size] = session["tokens"]
        margin = margins.min(axis=0)  # a position's narrowest, over the layers
        compared = margin >= NEAR
        produced += want.size
        aside += int((~compared).sum())
        gaps = np.asarray(_gaps(logits, jnp.asarray(target)))[:want.size]
        served.extend(gaps[compared].tolist())
        nearest.extend(margin[compared].tolist())
        if control:
            low, _ = _forward(params, config, full, want, "fp8", n)
            top = jnp.argmax(low, axis=-1).astype(jnp.int32)
            lowered.extend(np.asarray(_gaps(logits, top))[:want.size][compared].tolist())
    out = {"positions": len(served), "near_tie_share": aside / max(produced, 1)}
    if served:
        gaps, margin = np.asarray(served), np.asarray(nearest)
        out["served_gap_max"] = float(gaps.max())
        out["served_gap_p99"] = float(np.percentile(gaps, 99))
        # where the widest gaps lie: [from, to) in units of NEAR, the compared
        # positions whose narrowest margin is there, their widest gap
        out["gap_by_margin"] = [
            [lo, hi, int(band.sum()), float(gaps[band].max(initial=0.0))]
            for lo, hi in zip(BANDS, BANDS[1:])
            for band in [(margin >= lo * NEAR)
                         & (margin < (np.inf if hi is None else hi * NEAR))]]
    if control and lowered:
        out["control_gap_max"] = float(max(lowered))
        out["control_gap_median"] = float(np.median(lowered))
    return out
