"""Single-query KV-cache attention (flash decoding) as a Pallas TPU kernel.

The LLM decode hot op: one query vector per sequence attends over its whole
KV cache. ``flash_attention`` (the prefill kernel) streams K/V blocks
against a *block* of queries; at decode there is exactly one live query
position, so the kernel keeps the running online-softmax state for a single
row while K/V blocks stream through VMEM — the op is HBM-bandwidth-bound
(every decode step re-reads the cache), which is why padding the lone query
row up to the 8-sublane tile costs ~nothing: the MXU work is noise next to
the cache traffic.

Layout: the query row is padded to an [8, d] tile (row 0 live — Mosaic's
minimum f32 sublane tile); the grid is (batch*heads, nk) with the K axis
sequential ("arbitrary") so the (m, l, acc) scratch carries across K
blocks. The valid lengths arrive as ONE whole vector in SMEM, indexed by
the grid's row (Mosaic refuses a blocked rank-1 SMEM operand); K slots
above a row's length (unwritten cache tail) are masked in-kernel, so the
same compiled kernel serves every decode position — no shape-polymorphic
retraces, the same property the decoder's dense path has
(models/decoder.py).

``jax.vmap`` of the kernel folds the mapped axis into the batch axis (a
``custom_vmap`` rule): the default batching rule would block the SMEM
vector per mapped row, which Mosaic refuses too, and the kernel is already
batched. The sequence batcher's slot-batched step (``jax.vmap`` of the
single-sequence step, models/decoder_batched.py) therefore runs this one
kernel over [slots, heads, dim].

Runs in interpret mode on the CPU (CI exactness vs dense attention);
compiled to Mosaic on the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import _on_tpu

_SUBLANES = 8  # f32 min sublane tile; the padded query-row block height


def _decode_kernel(pos_ref, k_ref, v_ref, q_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, scale, block_k, nk):
    from jax.experimental import pallas as pl

    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # last valid cache slot for this sequence/head (whole vector in SMEM)
    pos = pos_ref[pl.program_id(0)]

    # K blocks wholly above pos contribute nothing — skip the whole body
    @pl.when(ik * block_k <= pos)
    def _body():
        # native-dtype operands + f32 accumulation: bf16 caches ride the
        # full-rate MXU path instead of the pre-cast fp32 one (same change
        # as flash_attention.py — decode is bandwidth-bound so the win is
        # smaller, but the halved VMEM footprint of bf16 blocks also helps)
        q = q_ref[0]                                 # [8, d] (row 0 live)
        k = k_ref[0]                                 # [bk, d]
        v = v_ref[0]                                 # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                    # [8, bk] f32
        k_pos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (_SUBLANES, block_k), 1
        )
        s = jnp.where(k_pos <= pos, s, -jnp.inf)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(-1)[:, None])
        p = jnp.exp(s - m_new[:, :1])
        correction = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * correction + p.sum(-1)[:, None]
        acc_scr[...] = (
            acc_scr[...] * correction[:, :1]
            + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        )
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[...][:, :1], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k, v, pos, block_k: int = 128,
                     interpret: bool | None = None):
    """One-step decode attention. q: [batch, heads, dim]; k, v:
    [batch, heads, max_len, dim]; pos: [batch] int32 — cache slots
    ``<= pos[b]`` attend (the decoder's position-based mask,
    models/decoder.py). Returns [batch, heads, dim] in q's dtype."""
    if interpret is None:
        interpret = not _on_tpu()

    @jax.custom_batching.custom_vmap
    def call(q, k, v, pos):
        return _pallas_decode(q, k, v, pos, block_k, interpret)

    @call.def_vmap
    def _fold_mapped_axis(axis_size, in_batched, q, k, v, pos):
        def fold(x, batched):
            if not batched:
                x = jnp.broadcast_to(x, (axis_size,) + x.shape)
            return x.reshape((axis_size * x.shape[1],) + x.shape[2:])

        out = call(*(fold(x, b) for x, b in zip((q, k, v, pos), in_batched)))
        return out.reshape((axis_size, -1) + out.shape[1:]), True

    return call(q, k, v, pos)


def _pallas_decode(q, k, v, pos, block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, heads, dim = q.shape
    max_len = k.shape[2]
    block_k = min(block_k, max_len)
    padded = -(-max_len // block_k) * block_k
    if padded != max_len:
        pad = [(0, 0), (0, 0), (0, padded - max_len), (0, 0)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)  # tail is masked by the pos comparison
    nk = padded // block_k
    scale = dim ** -0.5

    bh = batch * heads
    # query row padded to the sublane tile; K/V flattened to [bh, M, d]
    qb = jnp.zeros((bh, _SUBLANES, dim), q.dtype).at[:, 0, :].set(
        q.reshape(bh, dim))
    kb = k.reshape(bh, padded, dim)
    vb = v.reshape(bh, padded, dim)
    pos_b = jnp.repeat(pos.astype(jnp.int32), heads)  # [bh]

    kernel = functools.partial(
        _decode_kernel, scale=scale, block_k=block_k, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(bh, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_k, dim), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dim), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, _SUBLANES, dim), lambda b, j: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, _SUBLANES, dim), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, _SUBLANES, dim), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((_SUBLANES, 128), jnp.float32),  # running max
            pltpu.VMEM((_SUBLANES, 128), jnp.float32),  # running sum
            pltpu.VMEM((_SUBLANES, dim), jnp.float32),  # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(pos_b, kb, vb, qb)

    return out[:, 0, :].reshape(batch, heads, dim)


def decode_attention_reference(q, k, v, pos):
    """Dense fp32 reference (the decoder's einsum path, batched)."""
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhd,bhmd->bhm", qf, kf) * scale
    mask = jnp.arange(k.shape[2])[None, :] <= pos[:, None]  # [b, m]
    s = jnp.where(mask[:, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhm,bhmd->bhd", p, vf).astype(q.dtype)
