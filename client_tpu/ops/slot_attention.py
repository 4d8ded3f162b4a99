"""One query a slot over that slot's rows of a table, as a Pallas TPU kernel.

What a round of the window-and-full decoder needs
(``models/window_routed_decoder.py``): every slot's one query, grouped-query
heads, over that slot's key and value rows ``[slots, rows, kv_heads *
head_dim]`` where the table holds them (a group's keys are a column block of
a row, so nothing is transposed), each slot attending to its leading
``reach`` rows. Blocked online softmax as in ``chunk_attention.py``; a
block of keys is one group's lanes of ``block_k`` rows, and a block wholly
at or past a slot's reach is neither fetched (its index is clamped to the
last block the slot reads) nor computed, so a slot of reach 0 reads nothing.

The rows are read by the kernel's own pipeline from where the table lies, so
a round's attention carries the time of its reads. As an einsum over the
table the chip's compiler staged a whole ring table in fast memory before the
product, by copies outside the attention's named scope (PERF.md section 5).

A block of queries is one group's heads, padded to the eight sublanes of a
float32 tile. Compiled to Mosaic on the chip (``head_dim`` a multiple of 128
there); interpret mode on the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import _on_tpu

_SUBLANES = 8  # a float32 tile's rows: a group's heads, padded


def _kernel(reach_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, scale, block_k, nk):
    from jax.experimental import pallas as pl

    slot, ik = pl.program_id(0), pl.program_id(2)
    reach = reach_ref[slot]

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ik * block_k < reach)
    def _body():
        q, k, v = q_ref[0, 0], k_ref[0], v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kept = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < reach
        s = jnp.where(kept, s, -1e30)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(-1)[:, None])
        p = jnp.where(kept, jnp.exp(s - m_new[:, :1]), 0.0)
        correction = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * correction + p.sum(-1)[:, None]
        acc_scr[...] = acc_scr[...] * correction[:, :1] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...][:, :1], 1e-30)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "kv_heads", "head_dim", "block_k", "interpret"))
def slot_attention(q, keys, values, reach, *, kv_heads: int, head_dim: int,
                   block_k: int = 2048, interpret: bool | None = None):
    """``q`` [slots, heads, head_dim], slot ``b``'s one query over its rows
    ``j < reach[b]`` of ``keys``, ``values`` [slots, rows, kv_heads *
    head_dim] (int32 ``reach`` [slots]; a slot with nothing to attend to gets
    zeros); [slots, heads * head_dim] in float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, _ = q.shape
    rows = keys.shape[1]
    group = heads // kv_heads
    block_k = min(block_k, rows)
    if rows % block_k or group > _SUBLANES:
        raise ValueError(f"{rows} rows are not blocks of {block_k}, or a group "
                         f"of {group} heads is more than {_SUBLANES} rows")
    nk = rows // block_k
    qb = jnp.zeros((slots, kv_heads, _SUBLANES, head_dim), keys.dtype).at[
        :, :, :group].set(q.reshape(slots, kv_heads, group, head_dim).astype(keys.dtype))
    reach = jnp.clip(reach.astype(jnp.int32), 0, rows)
    # the last block a slot reads: later steps ask for it again, which the
    # pipeline does not fetch anew
    along = lambda b, g, j, reach: (
        b, jnp.minimum(j, jnp.maximum(reach[b] - 1, 0) // block_k), g)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=head_dim ** -0.5, block_k=block_k, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(slots, kv_heads, nk),
            in_specs=[
                pl.BlockSpec((1, 1, _SUBLANES, head_dim),
                             lambda b, g, j, reach: (b, g, 0, 0)),
                pl.BlockSpec((1, block_k, head_dim), along),
                pl.BlockSpec((1, block_k, head_dim), along),
            ],
            out_specs=pl.BlockSpec((1, 1, _SUBLANES, head_dim),
                                   lambda b, g, j, reach: (b, g, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((_SUBLANES, 128), jnp.float32),  # running max
                pltpu.VMEM((_SUBLANES, 128), jnp.float32),  # running sum
                pltpu.VMEM((_SUBLANES, head_dim), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((slots, kv_heads, _SUBLANES, head_dim),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=not _on_tpu() if interpret is None else interpret,
        name="slot_attention",
    )(reach, qb, keys, values)
    return out[:, :, :group].reshape(slots, heads * head_dim)
