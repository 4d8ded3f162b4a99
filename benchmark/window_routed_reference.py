"""The window-and-full, routed family's reference: the full forward pass in
float32.

Written from the equations of the family (the configuration file's
``assumed``), in straightforward ``jax.numpy`` at ``highest`` precision: no
cache, no ring, no table of slots, no rounds, no kernel, and nothing imported
from the program. For a token's residual ``x`` at position ``t``, every norm
an RMS norm with a gain:

- ``h1 = rms(x; ln1)``. The router, before attention: ``p = softmax(h1
  router)``; the token's experts the ``moe_num_active_primary_experts``
  largest of ``p``, each weighted by its ``p`` renormalised
  (``norm_topk_prob``).
- ``q = h1 wq``, ``k = h1 wk``, ``v = h1 wv``. A window layer
  (``sliding_window_layout`` 1) turns ``q`` and ``k`` by rotate-half rotary
  over the whole head at ``rope_theta`` and masks every key but ``t - window
  < j <= t``; a full layer turns nothing and masks every key after ``t``.
  Every query head attends to its group's keys and values; ``x += o wo``.
- ``h2 = rms(x; ln2)``; ``x += sum_e p_e down_e(relu(gate_e h2) * up_e
  h2)`` over the token's experts, the experts one at a time over the tokens
  routed to them.
- ``rms(x; final_norm)``, the untied head.

The window is a mask over the whole sequence, not a ring: what the program
keeps of a window layer's rows, and where, is not this file's to know.

How it is made to fit beside the served weights (they come in as data, in
bfloat16, and are cast a matrix at a time): each session is passed alone at
the traffic's longest length rounded up to a power of two (16,384 for the
long-context cell), so that a run's sessions and its seeds share one set of
programs; the padding lies after every real position and reaches none, its
query blocks are not worked and its rows go to no expert. The queries are
worked in blocks of ``QUERY_BLOCK`` against every key under the layer's mask,
the experts one at a time over the tokens routed to them, and the head over
the positions that produced a served token alone.

**Near ties of the router are set aside.** With random weights the 6th and
7th router probabilities of a token lie within rounding of each other at
some positions; the served program, whose residual stream has been through
bfloat16 products, and this pass then take different experts, and the gap
there reads the tie, not the arithmetic. A served position at which, in any
layer, the 6th and the 7th probability lie within ``NEAR`` is not compared,
and the share of served positions so set aside is returned as
``near_tie_share`` for the cell's file to hold to a limit. A tie at an
earlier position is not carried forward: it reaches a later token only as
one key-value row among the thousands attended to. ``NEAR`` is a distance of
probabilities (0 to 1, after the softmax over 64 and before the
renormalisation) and lies above what bfloat16 moves them by: this reference
against itself with every product's inputs but the router's rounded to
bfloat16 (the served router is float32) moves a router probability of
layers 1 to 3 by 1.9e-5 to 7.1e-5 at the 99.9th percentile (layer 0's not
at all: it reads the table), and by 1.7e-3 at most where an earlier layer
chose otherwise; of the 12 choices it changed, 11 had margins under 1.5e-5
and one 3.6e-4 (the family's weights at the published widths, published
layers 0 to 3, 2,048 positions, two seeds, on the CPU; PERF.md section 4).
With ``NEAR`` at 1e-4 a layer sets aside about 3% of the positions. What a
choice changed above it moves reaches the compared logits through the
residual scale of ``window_routed_arithmetic.init_scale``, and the cell's
limit on ``served_gap_max`` holds it.

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

from benchmark.gated_conv_reference import _head, _mm, _rms, _rotary
from benchmark.reference import _HIGHEST, _gaps
from benchmark.routed_reference import _fp8, padded_length

NEAR = 1e-4  # router probabilities: set aside where the 6th and 7th lie this close
BANDS = (1, 2, 4, None)  # of NEAR: the widest gap is reported by margin, band by band
QUERY_BLOCK = 256


def _sizes(config):
    return dict(
        heads=int(config["num_attention_heads"]),
        kv_heads=int(config["num_key_value_heads"]), head_dim=int(config["head_dim"]),
        eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]),
        sliding=tuple(bool(f) for f in config["sliding_window_layout"]),
        window=int(config["sliding_window_size"]),
        k=int(config["moe_num_active_primary_experts"]),
        renormalise=bool(config.get("norm_topk_prob", True)))


@partial(jax.jit, static_argnames=("eps", "k", "renormalise", "precision"))
def _route(x, layer, *, eps, k, renormalise, precision):
    """``h1``, the chosen experts and their weights, and the margin between
    the last chosen and the first unchosen router probability."""
    h1 = _rms(x, layer["ln1"], eps)
    probs = jax.nn.softmax(_mm(h1, layer["router"], precision), axis=-1)
    ranked, which = jax.lax.top_k(probs, k + 1)
    best = ranked[:, :k]
    if renormalise:
        best = best / jnp.sum(best, axis=-1, keepdims=True)
    return h1, which[:, :k], best, ranked[:, k - 1] - ranked[:, k]


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "theta", "turned",
                                   "precision"))
def _projections(h1, layer, *, heads, kv_heads, head_dim, theta, turned, precision):
    """Queries, keys and values, turned where the layer is a window's."""
    n = h1.shape[0]
    q = _mm(h1, layer["wq"], precision).reshape(n, heads, head_dim)
    k = _mm(h1, layer["wk"], precision).reshape(n, kv_heads, head_dim)
    v = _mm(h1, layer["wv"], precision).reshape(n, kv_heads, head_dim)
    if turned:
        q, k = _rotary(q, theta), _rotary(k, theta)
    return q, k, v


@partial(jax.jit, static_argnames=("block", "window", "precision"))
def _attention(x, q, k, v, wo, blocks, *, block, window, precision):
    """``x`` plus the attention's projected output: the first ``blocks``
    blocks of ``block`` queries, each against every key under the causal
    mask, and under the window where ``window`` is given; every query head
    over its group's keys and values."""
    n, heads, width = q.shape
    group = heads // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    if precision == "fp8":
        q, k, v = _fp8(q), _fp8(k), _fp8(v)

    def one(i, out):
        first = i * block
        qb = jax.lax.dynamic_slice_in_dim(q, first, block, axis=0)
        scores = jnp.einsum("bhd,khd->hbk", qb, k, precision=_HIGHEST) * width ** -0.5
        t = (first + jnp.arange(block))[None, :, None]
        j = jnp.arange(n)[None, None, :]
        seen = j <= t if window is None else (j <= t) & (j > t - window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        if precision == "fp8":
            probs = _fp8(probs)
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.einsum("hbk,khd->bhd", probs, v, precision=_HIGHEST), first, axis=0)

    attn = jax.lax.fori_loop(0, blocks, one, jnp.zeros_like(q))
    return x + _mm(attn.reshape(n, heads * width), wo, precision)


@partial(jax.jit, static_argnames=("eps", "longest", "precision"))
def _experts(x, ln2, tokens, starts, where, best, gates, ups, downs, *, eps, longest,
             precision):
    """``x`` plus the experts' part over ``h2 = rms(x; ln2)``, one program a
    layer. ``tokens`` are the tokens of the token-expert pairs in the order
    of their experts, expert ``e``'s from ``starts[e]`` on. The experts run
    one at a time, in order, each over the ``longest`` rows from its start:
    its own pairs and then some of the next experts', which those write over
    in their turn. Then each token's pairs, found again at ``where`` [tokens,
    k], are weighted by ``best`` and added, a pair at a time."""
    mm = partial(_mm, precision=precision)
    h2 = _rms(x, ln2, eps)

    def one(e, out):
        pick = lambda stack: jax.lax.dynamic_index_in_dim(stack, e, 0, keepdims=False)
        taken = h2[jax.lax.dynamic_slice_in_dim(tokens, starts[e], longest)]
        given = mm(jax.nn.relu(mm(taken, pick(gates))) * mm(taken, pick(ups)),
                   pick(downs))
        return jax.lax.dynamic_update_slice_in_dim(out, given, starts[e], axis=0)

    out = jax.lax.fori_loop(0, gates.shape[0], one,
                            jnp.zeros((tokens.shape[0], x.shape[1]), jnp.float32))

    def add(j, x):  # in turn, so that one pair's rows are live at a time
        at = jax.lax.dynamic_index_in_dim(where, j, 1, keepdims=False)
        return x + out[at] * jax.lax.dynamic_index_in_dim(best, j, 1)

    return jax.lax.fori_loop(0, where.shape[1], add, x)


def _routed(x, layer, which, best, s, precision: str, real: int):
    """``x`` plus what the experts the router chose before attention give
    the tokens routed to them. The host puts the token-expert pairs in the
    order of their experts (none of a row from ``real`` on, the padding) and
    the chip runs the experts one at a time."""
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
        x, layer["ln2"], jnp.asarray(tokens), jnp.asarray(starts, jnp.int32),
        jnp.asarray(where.reshape(which.shape)),
        best * jnp.asarray(here.reshape(which.shape), jnp.float32),
        layer["experts_gate"], layer["experts_up"], layer["experts_down"],
        eps=s["eps"], longest=longest, precision=precision)


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
        sliding = s["sliding"][i]
        h1, which, best, margin = _route(
            x, layer, eps=s["eps"], k=s["k"], renormalise=s["renormalise"],
            precision=precision)
        q, k, v = _projections(
            h1, layer, turned=sliding, precision=precision,
            **{key: s[key] for key in ("heads", "kv_heads", "head_dim", "theta")})
        x = _attention(x, q, k, v, layer["wo"], blocks, block=block,
                       window=s["window"] if sliding else None, precision=precision)
        del h1, q, k, v
        x = _routed(x, layer, which, best, s, precision, real=len(tokens))
        margins.append(np.asarray(margin)[want])
    rows = np.full(padded_length(want.size), want[-1])
    rows[:want.size] = want
    logits = _head(x[jnp.asarray(rows)], params["final_norm"], params["unembed"],
                   s["eps"], precision)
    return logits, np.stack(margins)


def forward(params: Dict[str, Any], config: Dict[str, Any], tokens, want,
            precision: str = "float32", padded=None):
    """Logits [len(want), vocab] at the positions ``want`` of the full pass
    over one sequence of ``tokens``, and each layer's router margin there;
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
