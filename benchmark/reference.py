"""The GPT-2 family's reference: the decoder's full forward pass in float32.

The module a configuration gets where it names no ``"reference"``
(``benchmark/family.py`` has the contract: ``served_token_gaps(params,
config, sessions, length, control=False)``); a model of another family brings
its own as a new file, with its own control behind ``control``.

Straightforward ``jax.numpy``: no cache, no batching tricks, no kernels, and
nothing imported from the program. The weights come in as data (the
benchmark makes them from the seed and hands the same arrays to the program
and to this file). Every matrix product runs at ``highest`` precision, so on
a TPU it is a true float32 product and not a bfloat16 one.

The architecture, as the served decoder computes it and as the configuration
files note under ``departures``: learned token and position tables; pre-norm
blocks with layer norm that has no scale or shift (eps 1e-5); one fused qkv
matrix, full causal multi-head attention scaled by head_dim ** -0.5, an
output projection; a 4x MLP with tanh-approximated GELU (GPT-2's
``gelu_new``); no biases; a final norm and an output head that is not tied to
the token table.

``precision="fp8"`` is the control: the same pass with every weight matrix
and every matrix product's input rounded to float8 (e4m3, one scale a
tensor), the nearest step below the bfloat16 the configurations state. It is
what a later change might be tempted to serve, and the comparison has to
tell it from a sound run.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0


def _fp8(x):
    """Round to float8 e4m3 with one scale for the tensor, back to float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _matmul(x, w, precision: str):
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x, w = _fp8(x), _fp8(w)
    return jnp.matmul(x, w, precision=_HIGHEST)


def _norm(x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


@partial(jax.jit, static_argnames=("heads", "precision"))
def _block(x, layer, heads: int, precision: str):
    """One pre-norm block over ``x`` [rows, positions, d]."""
    rows, length, d = x.shape
    head_dim = d // heads
    qkv = _matmul(_norm(x), layer["qkv"], precision)
    q, k, v = (t.reshape(rows, length, heads, head_dim)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision=_HIGHEST) * head_dim ** -0.5
    causal = jnp.tril(jnp.ones((length, length), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=_HIGHEST)
    x = x + _matmul(attn.reshape(rows, length, d), layer["proj"], precision)
    hidden = _gelu(_matmul(_norm(x), layer["mlp_in"], precision))
    return x + _matmul(hidden, layer["mlp_out"], precision)


@jax.jit
def _embed(embed, pos, tokens):
    length = tokens.shape[1]
    return (embed[tokens].astype(jnp.float32)
            + pos[:length].astype(jnp.float32)[None])


@partial(jax.jit, static_argnames=("precision",))
def _head(x, unembed, precision: str):
    return _matmul(_norm(x), unembed, precision)


def forward(params: Dict[str, Any], tokens, heads: int,
            precision: str = "float32"):
    """Logits [rows, positions, vocab] of the full pass over ``tokens``
    [rows, positions], layer by layer."""
    if precision not in ("float32", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    x = _embed(params["embed"], params["pos"], jnp.asarray(tokens, jnp.int32))
    for layer in params["layers"]:
        x = _block(x, layer, heads=heads, precision=precision)
    return _head(x, params["unembed"], precision=precision)


@jax.jit
def _gaps(logits, chosen):
    """How far each ``chosen`` token's logit lies below the row's best."""
    picked = jnp.take_along_axis(logits, chosen[..., None], axis=-1)[..., 0]
    return jnp.max(logits, axis=-1) - picked


def teacher_forced(sessions: Sequence[Dict[str, Any]], length: int, rows: int):
    """``sessions`` as ``rows`` rows of ``length`` positions: the tokens fed
    (prompt, then served tokens), at each position that produced a served
    token that token, and where those positions are."""
    tokens = np.zeros((rows, length), np.int32)
    target = np.zeros((rows, length), np.int32)
    valid = np.zeros((rows, length), bool)
    for r, s in enumerate(sessions):
        full = list(s["prompt"]) + list(s["tokens"])
        if len(full) > length:
            raise ValueError(f"session of {len(full)} tokens, room for {length}")
        tokens[r, :len(full)] = full
        first = len(s["prompt"]) - 1  # the position that produced token 0
        target[r, first:len(full) - 1] = s["tokens"]
        valid[r, first:len(full) - 1] = True
    return tokens, target, valid


def served_token_gaps(params: Dict[str, Any], config: Dict[str, Any],
                      sessions: Sequence[Dict[str, Any]], length: int,
                      control: bool = False, block: int = 4) -> Dict[str, Any]:
    """Teacher-force the reference over each session's prompt and served
    tokens and read, at every position that produced a served token, how far
    that token's reference logit lies below the reference's best: 0 where
    the served token is the reference's own choice, small where the two
    nearly tie, large where the served token is wrong.

    With ``control`` the fp8 pass is run over the same tokens as well, and
    the same gap is read for the token *it* puts first at each position.

    ``sessions``: ``{"prompt": [...], "tokens": [...]}``; every row is padded
    to ``length`` positions, so one compiled program serves every sample.
    Of ``config`` this family's reference reads the number of heads.
    """
    heads = int(config["n_head"])
    served: List[float] = []
    lowered: List[float] = []
    for at in range(0, len(sessions), block):
        tokens, target, valid = teacher_forced(sessions[at:at + block], length, block)
        logits = forward(params, tokens, heads)
        served.extend(np.asarray(_gaps(logits, jnp.asarray(target)))[valid]
                      .tolist())
        if control:
            low = forward(params, tokens, heads, precision="fp8")
            top = jnp.argmax(low, axis=-1).astype(jnp.int32)
            lowered.extend(np.asarray(_gaps(logits, top))[valid].tolist())
    out = {"positions": len(served), "served_gap_max": max(served)}
    if control:
        out["control_gap_max"] = max(lowered)
        out["control_gap_median"] = float(np.median(lowered))
    return out
