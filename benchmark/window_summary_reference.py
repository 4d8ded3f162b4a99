"""The window-and-summaries family's reference: the full forward pass in
float32.

Written from the equations of the family (ISSUE 34, and the configuration
file's ``assumed``), in straightforward ``jax.numpy`` at ``highest``
precision: no ring, no table of slots, no rounds, no kernel, and nothing
imported from the program. With ``s = head_dim ** -0.5``, a token at position
``t`` in window ``w = t // window_size``, a head ``h`` with learned vectors
``mu_h``, ``phi_h``:

- ``a = rms(x) * (1 + ln1)``; ``q, k, v = a wq, a wk, a wv``; rotate-half
  rotary on ``q`` and ``k`` at ``rope_theta``.
- Every complete chunk ``c`` (positions ``chunk_size * c`` onward,
  ``chunk_size`` of them): ``kbar_c = sum_j softmax_j(s k_j . mu_h) k_j``,
  ``vbar_c = sum_j softmax_j(s k_j . phi_h) v_j``.
- ``o_t``: one softmax over the exact positions ``j <= t`` of window ``w``
  and the summaries of the chunks ``c < (window_size / chunk_size) * w``
  (every chunk of every earlier window); values ``v_j`` and ``vbar_c``.
- ``x += o wo``; ``b = rms(x) * (1 + ln2)``; ``x += (silu(b wg) * (b wu)) wd``.
- ``logits = (rms(x) * (1 + final_norm)) unembed``: ``num_pred_heads`` heads
  of ``vocab_size`` each; head 0 scores the next byte, and is what is served.

How it is made to fit beside the served weights (they come in as data, in
bfloat16, and are cast a matrix at a time): a session is passed alone, at the
traffic's longest length rounded up to whole windows, so that a run's sessions
and its seeds share one set of programs; the rows are worked a window at a
time and the queries in blocks of ``QUERY_BLOCK`` against their own window's
keys and every summary, under a mask; the blocks past a session's last token
are not worked (they reach no real position); the head runs over the
positions that produced a served token alone. No ``[T, T]`` array exists.

There is no discrete choice in this family (no router, no selection), so no
position is set aside: the served byte's gap is read at every served
position. ``control=True`` is the same pass with every weight matrix and
every matrix product's input rounded to float8 (e4m3, one scale a tensor),
the nearest step below the bfloat16 the configuration states.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import _HIGHEST, _gaps
from benchmark.routed_reference import _fp8, padded_length

QUERY_BLOCK = 512


def _sizes(config):
    heads = int(config["num_attention_heads"])
    return dict(
        heads=heads, head_dim=int(config["hidden_size"]) // heads,
        window=int(config["window_size"]), chunk=int(config["chunk_size"]),
        pred_heads=int(config.get("num_pred_heads", 1)),
        vocab=int(config["vocab_size"]), eps=float(config["rms_norm_eps"]),
        theta=float(config["rope_theta"]),
        offset=1.0 if config.get("norm_add_unit_offset", False) else 0.0)


def _round(x, precision: str):
    return _fp8(x) if precision == "fp8" else x


def _mm(x, w, precision: str):
    return jnp.matmul(_round(x, precision), _round(w.astype(jnp.float32), precision),
                      precision=_HIGHEST)


def _norm(x, gain, eps, offset):
    return (x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * (offset + gain.astype(jnp.float32)))


def angles(positions: int, width: int, theta: float):
    """Cosines and sines [positions, width / 2] of ``position * theta ** (-2
    i / width)``, worked out on the host in float64 and rounded once."""
    half = width // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    angle = np.arange(positions, dtype=np.float64)[:, None] * freq
    return np.cos(angle).astype(np.float32), np.sin(angle).astype(np.float32)


def _rotate(x, cos, sin):
    """Rotate-half over the last axis of ``x`` [rows, heads, width]."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _by_window(body, outs, windows, rows: int):
    """``body(first row)`` for each of the first ``windows`` blocks of ``rows``
    rows, its results written into ``outs`` at those rows."""
    def one(i, outs):
        first = i * rows
        return tuple(jax.lax.dynamic_update_slice_in_dim(out, new, first, axis=0)
                     for out, new in zip(outs, body(first)))

    return jax.lax.fori_loop(0, windows, one, outs)


@partial(jax.jit, static_argnames=("heads", "window", "eps", "offset", "precision"))
def _projections(x, layer, cos, sin, windows, *, heads, window, eps, offset,
                 precision):
    """Queries, keys (both turned) and values [T, heads, head_dim] of the
    first ``windows`` windows' rows; zeros after them."""
    length, d = x.shape

    def body(first):
        rows = lambda a: jax.lax.dynamic_slice_in_dim(a, first, window, axis=0)
        a = _norm(rows(x), layer["ln1"], eps, offset)
        q, k, v = (_mm(a, layer[name], precision).reshape(window, heads, d // heads)
                   for name in ("wq", "wk", "wv"))
        return _rotate(q, rows(cos), rows(sin)), _rotate(k, rows(cos), rows(sin)), v

    zeros = jnp.zeros((length, heads, d // heads), jnp.float32)
    return _by_window(body, (zeros, zeros, zeros), windows, window)


@partial(jax.jit, static_argnames=("chunk", "precision"))
def _summaries(k, v, mu, phi, *, chunk, precision):
    """Every chunk's key and value [T / chunk, heads, head_dim]."""
    length, heads, width = k.shape
    kc = k.reshape(length // chunk, chunk, heads, width)
    vc = v.reshape(length // chunk, chunk, heads, width)

    def weights(vector):
        logits = jnp.einsum("cjhd,hd->chj", _round(kc, precision),
                            _round(vector.astype(jnp.float32), precision),
                            precision=_HIGHEST) * width ** -0.5
        return _round(jax.nn.softmax(logits, axis=-1), precision)

    kbar = jnp.einsum("chj,cjhd->chd", weights(mu), _round(kc, precision),
                      precision=_HIGHEST)
    vbar = jnp.einsum("chj,cjhd->chd", weights(phi), _round(vc, precision),
                      precision=_HIGHEST)
    return kbar, vbar


@partial(jax.jit, static_argnames=("block", "window", "chunk", "precision"))
def _attention(q, k, v, kbar, vbar, blocks, *, block, window, chunk, precision):
    """``o`` [T, heads, head_dim] for the first ``blocks`` blocks of ``block``
    queries: each block against its own window's keys and every summary,
    under the mask of the equations, one softmax over both."""
    length, heads, width = q.shape
    scale = width ** -0.5
    kbar_r, vbar_r = _round(kbar, precision), _round(vbar, precision)

    def body(first):
        w = first // window
        qb = _round(jax.lax.dynamic_slice_in_dim(q, first, block, axis=0), precision)
        kw = _round(jax.lax.dynamic_slice_in_dim(k, w * window, window, axis=0),
                    precision)
        vw = _round(jax.lax.dynamic_slice_in_dim(v, w * window, window, axis=0),
                    precision)
        at = first + jnp.arange(block)
        exact = jnp.einsum("bhd,khd->hbk", qb, kw, precision=_HIGHEST) * scale
        exact = jnp.where(
            (w * window + jnp.arange(window))[None, None, :] <= at[None, :, None],
            exact, -jnp.inf)
        before = jnp.einsum("bhd,chd->hbc", qb, kbar_r, precision=_HIGHEST) * scale
        before = jnp.where(
            jnp.arange(length // chunk)[None, None, :] < (window // chunk) * w,
            before, -jnp.inf)
        probs = _round(jax.nn.softmax(jnp.concatenate([exact, before], axis=-1),
                                      axis=-1), precision)
        return (jnp.einsum("hbk,khd->bhd", probs[..., :window], vw,
                           precision=_HIGHEST)
                + jnp.einsum("hbc,chd->bhd", probs[..., window:], vbar_r,
                             precision=_HIGHEST),)

    return _by_window(body, (jnp.zeros_like(q),), blocks, block)[0]


@partial(jax.jit, static_argnames=("window", "eps", "offset", "precision"))
def _rest_of_layer(x, attn, layer, windows, *, window, eps, offset, precision):
    """``x`` after the attention's projection and the gated MLP, a window's
    rows at a time; the rows after ``windows`` windows stay as they were."""
    def body(first):
        rows = lambda a: jax.lax.dynamic_slice_in_dim(a, first, window, axis=0)
        y = rows(x) + _mm(rows(attn).reshape(window, -1), layer["wo"], precision)
        b = _norm(y, layer["ln2"], eps, offset)
        hidden = jax.nn.silu(_mm(b, layer["wg"], precision)) * _mm(
            b, layer["wu"], precision)
        return (y + _mm(hidden, layer["wd"], precision),)

    return _by_window(body, (x,), windows, window)[0]


@partial(jax.jit, static_argnames=("eps", "offset", "precision"))
def _head(x, gain, unembed, eps, offset, precision):
    return _mm(_norm(x, gain, eps, offset), unembed, precision)


def _forward(params, config, tokens, want, precision, padded):
    """``forward`` with the logits' rows padded to a power of two (the last
    wanted position again), so that sessions of different output lengths
    share the programs."""
    if precision not in ("float32", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    s = _sizes(config)
    window = s["window"]
    n = -(-max(padded, len(tokens)) // window) * window
    block = min(QUERY_BLOCK, window)
    windows, blocks = -(-len(tokens) // window), -(-len(tokens) // block)
    ids = np.zeros(n, np.int32)
    ids[:len(tokens)] = tokens
    x = params["embed"][jnp.asarray(ids)].astype(jnp.float32)
    cos, sin = (jnp.asarray(a) for a in angles(n, s["head_dim"], s["theta"]))
    norm = dict(eps=s["eps"], offset=s["offset"], precision=precision)
    for layer in params["layers"]:
        q, k, v = _projections(x, layer, cos, sin, windows, heads=s["heads"],
                               window=window, **norm)
        kbar, vbar = _summaries(k, v, layer["mu"], layer["phi"], chunk=s["chunk"],
                                precision=precision)
        attn = _attention(q, k, v, kbar, vbar, blocks, block=block, window=window,
                          chunk=s["chunk"], precision=precision)
        del q, k, v, kbar, vbar
        x = _rest_of_layer(x, attn, layer, windows, window=window, **norm)
    want = np.asarray(want)
    rows = np.full(padded_length(want.size), want[-1])
    rows[:want.size] = want
    logits = _head(x[jnp.asarray(rows)], params["final_norm"], params["unembed"],
                   **norm)
    return logits.reshape(rows.size, s["pred_heads"], s["vocab"])


def forward(params: Dict[str, Any], config: Dict[str, Any], tokens, want,
            precision: str = "float32", padded: int = 0):
    """Logits [len(want), num_pred_heads, vocab] at the positions ``want`` of
    the full pass over one sequence of ``tokens``, passed at ``padded``
    positions (whole windows; its own length rounded up where left out)."""
    return _forward(params, config, tokens, want, precision, padded)[:len(want)]


def served_token_gaps(params: Dict[str, Any], config: Dict[str, Any],
                      sessions: Sequence[Dict[str, Any]], length: int,
                      control: bool = False) -> Dict[str, Any]:
    """Teacher-force each session alone, at ``length`` (the longest a session
    of the traffic may be) rounded up to whole windows, and read at every
    position that produced a served byte how far that byte's logit, of head
    0's, lies below the reference's best. With ``control`` the same for the
    byte the fp8 pass puts first."""
    served: List[float] = []
    lowered: List[float] = []
    for session in sessions:
        full = list(session["prompt"]) + list(session["tokens"])
        if len(full) > length:
            raise ValueError(f"session of {len(full)} tokens, room for {length}")
        want = np.arange(len(session["prompt"]) - 1, len(full) - 1)
        logits = _forward(params, config, full, want, "float32", length)[:, 0]
        target = np.zeros(logits.shape[0], np.int32)
        target[:want.size] = session["tokens"]
        served.extend(np.asarray(_gaps(logits, jnp.asarray(target)))[:want.size]
                      .tolist())
        if control:
            low = _forward(params, config, full, want, "fp8", length)[:, 0]
            top = jnp.argmax(low, axis=-1).astype(jnp.int32)
            lowered.extend(np.asarray(_gaps(logits, top))[:want.size].tolist())
    out: Dict[str, Any] = {"positions": len(served)}
    if served:
        out["served_gap_max"] = float(max(served))
        out["served_gap_p99"] = float(np.percentile(served, 99))
    if control and lowered:
        out["control_gap_max"] = float(max(lowered))
        out["control_gap_median"] = float(np.median(lowered))
    return out
