"""The cut family's reference: the full pass in float32 over the experts
held here, the fp8 pass as its control, and a reading of its own.

With random weights the last chosen and the first unchosen router score of a
token lie within rounding of each other at some positions; bfloat16 and
float32 then take different experts, and the gap there reads the tie and not
the arithmetic. Where the two scores' logits lie within ``NEAR`` the position
is set aside (and, where the tie is not in the last layer, every later
position of the row, whose keys and values it reaches), and the share of the
served positions set aside is returned as ``near_tie_share`` for the cell's
file to hold to a limit."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import later_cut_arithmetic as arithmetic
from benchmark import reference as gpt2

NEAR = 0.02


def forward(params, config, tokens, precision="float32"):
    """Logits [rows, positions, vocab] and, for each layer of experts, the
    margin [rows, positions] between the last chosen and the first unchosen
    router logit."""
    heads, K = config["num_attention_heads"], config["num_experts_per_tok"]
    held, scored = config["num_experts"], arithmetic.routed(config)
    mm = lambda x, w: gpt2._matmul(x, w, precision)
    rows, length = tokens.shape
    x = gpt2._embed(params["embed"], params["pos"], jnp.asarray(tokens, jnp.int32))
    d = x.shape[-1]
    margins = []
    for layer in params["layers"]:
        q, k, v = (t.reshape(rows, length, heads, d // heads)
                   for t in jnp.split(mm(gpt2._norm(x), layer["qkv"]), 3, axis=-1))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            precision=gpt2._HIGHEST) * (d // heads) ** -0.5
        scores = jnp.where(jnp.tril(jnp.ones((length, length), bool)), scores, -jnp.inf)
        attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v,
                          precision=gpt2._HIGHEST)
        x = x + mm(attn.reshape(rows, length, d), layer["proj"])
        h = gpt2._norm(x)
        if "router" not in layer:
            x = x + mm(gpt2._gelu(mm(h, layer["mlp_in"])), layer["mlp_out"])
            continue
        logits = mm(h, layer["router"])
        ranked = jnp.sort(logits, axis=-1)
        margins.append(ranked[..., -K] - ranked[..., -K - 1])
        best, which = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), K)
        gate = jnp.sum(jax.nn.one_hot(which, scored) * (best / best.sum(-1, keepdims=True))[..., None],
                       axis=-2)[..., :held]
        for e in range(held):
            out = mm(gpt2._gelu(mm(h, layer["experts_in"][e])), layer["experts_out"][e])
            x = x + gate[..., e:e + 1] * out
    return gpt2._head(x, params["unembed"], precision=precision), margins


def served_token_gaps(params, config, sessions, length, control=False):
    tokens, target, valid = gpt2.teacher_forced(sessions, length, len(sessions))
    logits, margins = forward(params, config, tokens)
    near = [np.asarray(m) < NEAR for m in margins]
    aside = near[-1].copy()
    for earlier in near[:-1]:  # reaches the later positions' keys and values
        aside |= np.maximum.accumulate(earlier, axis=1)
    compared = valid & ~aside
    served = np.asarray(gpt2._gaps(logits, jnp.asarray(target)))[compared]
    out = {"positions": int(compared.sum()),
           "near_tie_share": float((valid & aside).sum() / valid.sum())}
    if served.size:
        out["served_gap_max"] = float(served.max())
    if control:
        low, _ = forward(params, config, tokens, precision="fp8")
        top = jnp.argmax(low, axis=-1).astype(jnp.int32)
        out["control_gap_max"] = float(np.asarray(gpt2._gaps(logits, top))[compared].max())
    return out
