"""The routed family's reference: the full forward pass in float32.

Written from the equations of the family (ISSUE 32, and the configuration
file's ``assumed``), in straightforward ``jax.numpy`` at ``highest``
precision: no cache, no chunking of the mathematics, and nothing imported
from the program. For a token's residual ``x`` at position ``t``:

- ``h = rms(x; ln1)``; ``q = h wq``, ``k = h wk``, ``v = h wv``; ``q`` and
  ``k`` RMS-normed over each head (``q_norm``, ``k_norm``), then rotate-half
  rotary over the whole head at ``rope_theta``.
- Indexer: ``qI = h idx_wq`` (``indexer_num_heads`` of ``indexer_head_dim``),
  ``kI = layernorm(h idx_wk; idx_k_norm, idx_k_bias)``, the same rotary;
  ``w = h idx_ww / sqrt(heads * width)``;
  ``I[t, s] = sum_j w[j] relu(qI[j] . kI_s)`` for ``s <= t``.
- ``S_t``: every ``s <= t`` while ``t < topk``; else the ``topk`` positions of
  largest ``I[t, s]`` (where entries are equal, the lower position first).
- Every query head attends to its group's ``k_s, v_s``, ``s`` in ``S_t``,
  softmax of ``q . k_s / sqrt(head_dim)``; ``x += (heads) wo``.
- ``h2 = rms(x; ln2)``; ``p = softmax(h2 router)``; the ``num_experts_per_tok``
  largest, renormalised (``norm_topk_prob``);
  ``x += sum_e p_e down_e(silu(gate_e h2) * up_e h2)`` over the experts held.
- ``rms(x; final_norm)``, the untied head.

How it is made to fit beside the served weights (they come in as data, in
bfloat16, and are cast a matrix at a time): each session is passed alone,
queries in blocks of 256 against all the keys, one group of query heads at a
time, the experts one at a time over the tokens routed to them, and the head
over the positions that produced a served token alone. **What it costs is
compiling, not running** (my chip runs, PR 32, two layers timed): a 12k
session's layers run in 0.35 s each and a 20k session's in 0.8 s, while the
programs of one length take 25 s to compile in float32 and 66 s with the
control's rounding, the head's 2 and 18 s. So every shape is a function of
the traffic and not of the session: all sessions of a call are passed at one
length (the traffic's longest, rounded up to a power of two; the padding
lies after every real position and reaches none, its query blocks are not
worked and its rows go to no expert), the served positions in a power of two
of rows, an expert's rows in a power of two; a run's eight sessions share
one set of programs, and so do its seeds.

**Near ties of the router are set aside.** With random weights the 8th and
9th router scores of a token lie within rounding of each other at some
positions; bfloat16 and float32 then take different experts and the gap
there reads the tie, not the arithmetic. A served position at which, in any
layer, the last chosen and the first unchosen router logit lie within
``NEAR`` is not compared, and the share of served positions so set aside is
returned as ``near_tie_share`` for the cell's file to hold to a limit. A tie
at an earlier position is not carried forward: it reaches a later token only
as one key-value row among the 2,048 attended to.

**Near ties of the indexer's selection are not set aside, and cannot be.**
Every selecting position has some: index scores worked from bfloat16
queries and keys (the cache's stated type) put some tens of rows on the
other side of the 2,048th score, so there is no position without. What
that costs was read on the CPU at the published widths (2 layers, 256 kept
of up to 768, this reference against itself with every product's inputs
rounded to bfloat16; PERF.md section 6, PR 32): where every causal position
is kept the two passes' router logits part by 0.0009 (deviation) in layer 0,
where 256 are chosen by 0.006 in layer 0 and 0.019 in layer 1; a position's
widest logit difference is 0.010 before and 0.033 after (medians), 0.11 at
most; and 1 to 6% of the positions whose margin is over ``NEAR`` take
another expert after all, which moves their logits by 0.24 (median). That,
and not the products' rounding, is what ``served_gap_max`` reads at a
selecting position, and why the cell's limit lies where it does:
``gap_by_margin`` in the answer says, band by band of the narrowest
margin, where the widest gaps lie. On the chip, the program against this
reference (six seeds, PR 32): 0.15 to 0.21 at positions whose margin is
under twice ``NEAR``, 0.03 to 0.09 from there to four times, 0.017 at most
beyond, where a sound run agrees with float32 as the GPT-2 cells do; the
fp8 control read 0.48, 0.74, 0.83. (At a size where 8 rows are attended to
one row of 8 moves a logit by 0.1, at the position and after it. The
family's fixture for the CPU tests is therefore served in float32,
``"dtype"`` in its configuration, where no tie is decided otherwise than
here; the bfloat16 path is held to this reference by
``tests/test_routed_decoder.py`` and, at the published widths, on the chip.)

``control=True`` is the same pass with every weight matrix and every matrix
product's input rounded to float8 (e4m3, one scale a tensor), the nearest
step below the bfloat16 the configuration states.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import _HIGHEST, _gaps

NEAR = 0.01  # router logits: set aside where the last in and first out lie this close
BANDS = (1, 2, 4, None)  # of NEAR: the widest gap is reported by margin, band by band
QUERY_BLOCK = 256


def _fp8(x):
    """Round to float8 e4m3 with one scale for the tensor, back to float32:
    what ``benchmark/reference.py`` does through the float8 type, worked in
    float32 arithmetic (a v5e converts to the type an element at a time, a
    minute for a layer's experts): the step at a value of binary exponent
    ``e`` is ``2 ** (e - 3)``, ``2 ** -9`` below the least normal exponent
    -6, halves round to even, and nothing passes 448."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    scaled = x / scale
    exponent = (jax.lax.bitcast_convert_type(jnp.abs(scaled), jnp.int32) >> 23) - 127
    step = jax.lax.bitcast_convert_type(
        (jnp.maximum(exponent, -6) - 3 + 127) << 23, jnp.float32)
    return jnp.clip(jnp.round(scaled / step) * step, -448.0, 448.0) * scale


def _mm(x, w, precision: str):
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x, w = _fp8(x), _fp8(w)
    return jnp.matmul(x, w, precision=_HIGHEST)


def _rms(x, gain, eps):
    return (x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * gain.astype(jnp.float32))


def _layer_norm(x, gain, bias, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return (x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * gain.astype(jnp.float32) + bias.astype(jnp.float32))


def _rotary(x, theta):
    """``x`` [positions, heads, width] at positions 0, 1, ...: pairs
    ``(i, i + width / 2)`` turned by ``position * theta ** (-2 i / width)``.
    The angles' cosines and sines are worked out on the host in float64 (at
    position 29,000 a float32 power and product on the chip put an angle off
    by 0.03) and rounded once."""
    half = x.shape[-1] // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    angle = np.arange(x.shape[0], dtype=np.float64)[:, None, None] * freq
    cos, sin = np.cos(angle).astype(np.float32), np.sin(angle).astype(np.float32)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _sizes(config):
    sa = config["sa_config"]
    return dict(
        heads=int(config["num_attention_heads"]),
        kv_heads=int(config["num_key_value_heads"]), head_dim=int(config["head_dim"]),
        eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]),
        index_heads=int(sa["indexer_num_heads"]), index_dim=int(sa["indexer_head_dim"]),
        topk=int(sa["topk"]), experts_per_token=int(config["num_experts_per_tok"]),
        renormalise=bool(config.get("norm_topk_prob", True)))


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "eps", "theta",
                                   "index_heads", "index_dim", "precision"))
def _projections(x, layer, *, heads, kv_heads, head_dim, eps, theta,
                 index_heads, index_dim, precision):
    """Queries, keys, values and the indexer's three, over all positions."""
    n = x.shape[0]
    mm = partial(_mm, precision=precision)
    h = _rms(x, layer["ln1"], eps)
    q = _rms(mm(h, layer["wq"]).reshape(n, heads, head_dim), layer["q_norm"], eps)
    k = _rms(mm(h, layer["wk"]).reshape(n, kv_heads, head_dim), layer["k_norm"], eps)
    v = mm(h, layer["wv"]).reshape(n, kv_heads, head_dim)
    qi = mm(h, layer["idx_wq"]).reshape(n, index_heads, index_dim)
    ki = _layer_norm(mm(h, layer["idx_wk"]), layer["idx_k_norm"],
                     layer["idx_k_bias"], eps)
    wi = mm(h, layer["idx_ww"]) * (index_heads * index_dim) ** -0.5
    return (_rotary(q, theta), _rotary(k, theta), v, _rotary(qi, theta),
            _rotary(ki[:, None, :], theta)[:, 0], wi)


@partial(jax.jit, static_argnames=("topk", "precision"))
def chosen_positions(qi, wi, ki, first, *, topk, precision="float32"):
    """``S_t`` as a mask [queries, positions] for the queries at positions
    ``first`` onward, and the index scores it was taken from."""
    if precision == "fp8":
        qi, ki = _fp8(qi), _fp8(ki)
    queries, heads, width = qi.shape
    dots = jnp.matmul(qi.reshape(queries * heads, width), ki.T,
                      precision=_HIGHEST).reshape(queries, heads, -1)
    scores = jnp.sum(wi[:, :, None] * jnp.maximum(dots, 0.0), axis=1)
    at = first + jnp.arange(qi.shape[0])
    causal = jnp.arange(ki.shape[0])[None, :] <= at[:, None]
    if ki.shape[0] <= topk:
        return causal, scores
    scores = jnp.where(causal, scores, -jnp.inf)
    kth = jax.lax.top_k(scores, topk)[0][:, -1:]
    above, equal = scores > kth, scores == kth
    room = topk - jnp.sum(above, axis=-1, keepdims=True)
    return (above | (equal & (jnp.cumsum(equal, axis=-1) <= room))) & causal, scores


def _attend_group(q, k, v, mask, precision):
    """One group: ``q`` [queries, group heads, head_dim] over ``k``, ``v``
    [positions, head_dim] under ``mask`` [queries, positions]."""
    if precision == "fp8":
        q, k = _fp8(q), _fp8(k)
    queries, heads, width = q.shape
    scores = jnp.matmul(q.reshape(queries * heads, width), k.T, precision=_HIGHEST)
    scores = scores.reshape(queries, heads, -1) * width ** -0.5
    probs = jax.nn.softmax(jnp.where(mask[:, None, :], scores, -jnp.inf), axis=-1)
    if precision == "fp8":
        probs, v = _fp8(probs), _fp8(v)
    return jnp.matmul(probs.reshape(queries * heads, -1), v,
                      precision=_HIGHEST).reshape(queries, heads, width)


@partial(jax.jit, static_argnames=("block", "topk", "precision"))
def _attention_block(q, k, v, qi, ki, wi, first, *, block, topk, precision):
    """The attention of the ``block`` queries from position ``first`` on
    (a traced number: one program serves every block of a length) over all
    the keys: their chosen positions, then group by group."""
    rows = lambda a: jax.lax.dynamic_slice_in_dim(a, first, block, axis=0)
    mask, _ = chosen_positions(rows(qi), rows(wi), ki, first, topk=topk,
                               precision=precision)
    q = rows(q)
    group = q.shape[1] // k.shape[1]
    return jnp.concatenate([
        _attend_group(q[:, g * group:(g + 1) * group], k[:, g], v[:, g], mask, precision)
        for g in range(k.shape[1])], axis=1)


@partial(jax.jit, static_argnames=("precision",))
def _project_out(x, attn, wo, precision):
    return x + _mm(attn, wo, precision)


@partial(jax.jit, static_argnames=("eps", "k", "renormalise", "precision"))
def _route(x, layer, *, eps, k, renormalise, precision):
    """``h2``, the chosen experts and their weights, and the margin between
    the last chosen and the first unchosen router logit."""
    h2 = _rms(x, layer["ln2"], eps)
    logits = _mm(h2, layer["router"], precision)
    best, which = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    if renormalise:
        best = best / jnp.sum(best, axis=-1, keepdims=True)
    ranked = jax.lax.top_k(logits, k + 1)[0]
    return h2, which, best, ranked[:, k - 1] - ranked[:, k]


@partial(jax.jit, static_argnames=("longest", "precision"))
def _experts(x, h2, tokens, starts, where, best, gates, ups, downs, *, longest,
             precision):
    """``x`` plus the experts' part, one program a layer. ``tokens`` are the
    tokens of the token-expert pairs in the order of their experts, expert
    ``e``'s from ``starts[e]`` on. The experts run one at a time, in order,
    each over the ``longest`` rows from its start: its own pairs and then
    some of the next experts', which those write over in their turn. Then
    each token's pairs, found again at ``where`` [tokens, k], are weighted by
    ``best`` and added, a pair at a time."""
    mm = partial(_mm, precision=precision)

    def one(e, out):
        pick = lambda stack: jax.lax.dynamic_index_in_dim(stack, e, 0, keepdims=False)
        taken = h2[jax.lax.dynamic_slice_in_dim(tokens, starts[e], longest)]
        given = mm(jax.nn.silu(mm(taken, pick(gates))) * mm(taken, pick(ups)),
                   pick(downs))
        return jax.lax.dynamic_update_slice_in_dim(out, given, starts[e], axis=0)

    out = jax.lax.fori_loop(0, gates.shape[0], one,
                            jnp.zeros((tokens.shape[0], x.shape[1]), jnp.float32))

    def add(j, x):  # in turn, so that one pair's rows are live at a time
        at = jax.lax.dynamic_index_in_dim(where, j, 1, keepdims=False)
        return x + out[at] * jax.lax.dynamic_index_in_dim(best, j, 1)

    return jax.lax.fori_loop(0, where.shape[1], add, x)


def expert_layer(x, layer, config, first: int = 0, precision: str = "float32",
                 real: Optional[int] = None):
    """``x`` plus what the experts in ``layer``'s stacks (``first`` onward)
    give; and the router's margin at each position. The host puts the
    token-expert pairs in the order of their experts (a pair whose expert is
    not held weighs nothing; nor does one of a row from ``real`` on, the
    padding after a session's tokens, whose rows are all one token and would
    all go to the same eight experts) and the chip runs the experts one at
    a time."""
    s = _sizes(config)
    h2, which, best, margin = _route(
        x, layer, eps=s["eps"], k=s["experts_per_token"],
        renormalise=s["renormalise"], precision=precision)
    held = layer["experts_gate"].shape[0]
    local = np.asarray(which).reshape(-1) - first
    here = (local >= 0) & (local < held)
    if real is not None:
        here &= np.arange(local.size) // s["experts_per_token"] < real
    order = np.flatnonzero(here)
    order = order[np.argsort(local[order], kind="stable")]  # pairs, by expert
    starts = np.searchsorted(local[order], np.arange(held))
    # a power of two, so that a run's sessions share one program or two (on
    # a v5e the program takes 4 to 11 s to compile and 0.03 s to run)
    longest = padded_length(np.diff(np.append(starts, order.size)).max(initial=1))
    tokens = np.zeros(local.size + longest, np.int32)  # one length a session
    tokens[:order.size] = order // s["experts_per_token"]
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


def padded_length(n: int) -> int:
    """The next power of two, 8 at least."""
    return max(8, 1 << (int(n) - 1).bit_length())


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
    first_expert = int(config.get("first_expert", 0))
    want = np.asarray(want)
    block = min(QUERY_BLOCK, n)
    blocks = -(-len(tokens) // block)  # those with a token in them
    nothing = jnp.zeros((block, s["heads"], s["head_dim"]), jnp.float32)
    margins = []
    for layer in params["layers"]:
        q, k, v, qi, ki, wi = _projections(
            x, layer, precision=precision,
            **{key: s[key] for key in ("heads", "kv_heads", "head_dim", "eps",
                                       "theta", "index_heads", "index_dim")})
        attn = jnp.concatenate([
            _attention_block(q, k, v, qi, ki, wi, at * block, block=block,
                             topk=s["topk"], precision=precision)
            if at < blocks else nothing  # a block of padding attends to nothing
            for at in range(n // block)], axis=0).reshape(n, -1)
        x = _project_out(x, attn, layer["wo"], precision)
        del q, k, v, qi, ki, wi, attn  # room for the experts' rows
        x, margin = expert_layer(x, layer, config, first_expert, precision,
                                 real=len(tokens))
        margins.append(np.asarray(margin)[want])
    rows = np.full(padded_length(want.size), want[-1])
    rows[:want.size] = want
    logits = _head(x[jnp.asarray(rows)], params["final_norm"], params["unembed"],
                   s["eps"], precision)
    return logits, np.stack(margins)


def forward(params: Dict[str, Any], config: Dict[str, Any], tokens, want,
            precision: str = "float32", padded: Optional[int] = None):
    """Logits [len(want), vocab] at the positions ``want`` of the full pass
    over one sequence of ``tokens``, and each layer's router margin there.
    The sequence is passed at ``padded`` positions (a power of two; its own
    length rounded up where left out); the padding lies after every real
    position and reaches none, its query blocks are not worked and its rows
    go to no expert."""
    logits, margins = _forward(params, config, tokens, want, precision, padded)
    return logits[:len(want)], margins


def served_token_gaps(params: Dict[str, Any], config: Dict[str, Any],
                      sessions: Sequence[Dict[str, Any]], length: int,
                      control: bool = False) -> Dict[str, Any]:
    """Teacher-force each session alone, not padded to the longest in a
    batch but each at ``length`` (the longest a session of the traffic may
    be) rounded up to a power of two, so that one set of programs serves
    every session and every seed, and read at
    every position that produced a served token how far that token's logit
    lies below the reference's best; positions at a near tie of the router
    are set aside and counted (``near_tie_share``). With ``control`` the
    same for the token the fp8 pass puts first."""
    served: List[float] = []
    lowered: List[float] = []
    nearest: List[float] = []
    produced = aside = 0
    n = padded_length(length)  # every session at the traffic's one length
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
            gaps = np.asarray(_gaps(logits, top))[:want.size]
            lowered.extend(gaps[compared].tolist())
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
        out["control_gap_max"] = max(lowered)
        out["control_gap_median"] = float(np.median(lowered))
    return out
