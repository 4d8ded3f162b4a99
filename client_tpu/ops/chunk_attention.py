"""Attention of a prefill chunk (or of one step) under a mask, as a Pallas TPU
kernel.

What the routed decoder's prefill chunk needs (``models/routed_decoder.py``):
``n`` queries at consecutive positions from ``base`` on, grouped-query heads,
keys and values read from the cache where they lie (``[positions, kv_heads *
head_dim]``: a group's keys are a column block of it, so nothing is
transposed), and a mask ``[n, positions]`` a query (the positions its indexer
chose, or the causal ones), shared by every head. Blocked online softmax as in
``flash_attention.py``: the ``[queries, positions]`` scores never reach HBM.
A step over the live prefix is the same with one query; because the kernel
takes the cache as it lies, the compiler cannot lay the whole cache out anew
for the step's product, which it did for a plain one (2 ms a step at the
published sizes: my chip run, PR 32).

A block of queries is ``block_q`` positions and every head of one group,
head-major, so that the positions' mask block is stacked once a head. Key
blocks wholly past the last position of the query block are skipped, and
their index is clamped so that nothing is fetched for them.

Compiled to Mosaic on the chip (``head_dim`` a multiple of 128 there);
interpret mode on the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import _on_tpu


def _kernel(base_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, m_scr, l_scr, acc_scr,
            *, scale, group, block_q, block_k, nk):
    from jax.experimental import pallas as pl

    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # the block's last query is at base + (iq + 1) * block_q - 1: key blocks
    # that begin after it hold nothing it may attend to
    @pl.when(ik * block_k <= base_ref[0] + (iq + 1) * block_q - 1)
    def _body():
        q, k, v = q_ref[0, 0], k_ref[...], v_ref[...]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kept = mask_ref[...].astype(jnp.int32) != 0  # [block_q, block_k]
        kept = (jnp.broadcast_to(kept, s.shape) if block_q == 1 else
                jnp.concatenate([kept] * group, axis=0))  # a head after a head
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
    "kv_heads", "head_dim", "block_q", "block_k", "interpret"))
def chunk_attention(q, keys, values, mask, base, *, kv_heads: int, head_dim: int,
                    block_q: int = 64, block_k: int = 1024,
                    interpret: bool | None = None):
    """``q`` [n, heads * head_dim] at positions ``base`` onward over ``keys``,
    ``values`` [positions, kv_heads * head_dim] under ``mask`` [n, positions]
    (true where the query may attend; a query with nothing to attend to gets
    zeros); [n, heads * head_dim] in float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, positions = mask.shape
    group = q.shape[1] // (kv_heads * head_dim)
    block_q, block_k = min(block_q, n), min(block_k, positions)
    if n % block_q or positions % block_k:
        raise ValueError(f"{n} queries over {positions} positions do not divide "
                         f"into blocks of {block_q} by {block_k}")
    nq, nk = n // block_q, positions // block_k
    rows = group * block_q
    # [kv_heads, query blocks, group * block_q, head_dim], head-major in a block
    qb = q.reshape(nq, block_q, kv_heads, group, head_dim).transpose(
        2, 0, 3, 1, 4).reshape(kv_heads, nq, rows, head_dim)
    last = lambda i, base: jnp.minimum(
        nk - 1, (base[0] + (i + 1) * block_q - 1) // block_k)
    along = lambda g, i, j, base: (jnp.minimum(j, last(i, base)), g)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=head_dim ** -0.5, group=group,
                          block_q=block_q, block_k=block_k, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(kv_heads, nq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, rows, head_dim), lambda g, i, j, base: (g, i, 0, 0)),
                pl.BlockSpec((block_k, head_dim), along),
                pl.BlockSpec((block_k, head_dim), along),
                pl.BlockSpec((block_q, block_k),
                             lambda g, i, j, base: (i, jnp.minimum(j, last(i, base)))),
            ],
            out_specs=pl.BlockSpec((1, 1, rows, head_dim),
                                   lambda g, i, j, base: (g, i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rows, 128), jnp.float32),  # running max (lanes bcast)
                pltpu.VMEM((rows, 128), jnp.float32),  # running sum
                pltpu.VMEM((rows, head_dim), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((kv_heads, nq, rows, head_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=not _on_tpu() if interpret is None else interpret,
    )(jnp.asarray(base, jnp.int32).reshape(1), qb, keys, values,
      mask.astype(jnp.int8))
    return out.reshape(kv_heads, nq, group, block_q, head_dim).transpose(
        1, 3, 0, 2, 4).reshape(n, kv_heads * group * head_dim)
