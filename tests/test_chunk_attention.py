"""The prefill chunk's attention kernel against dense attention under the
same mask (interpret mode on the CPU; compiled for a described v5e in
``tests/benchmark/test_routed_chip_compile.py``)."""

import numpy as np
import pytest

from client_tpu.ops.chunk_attention import chunk_attention


def dense(q, keys, values, mask, kv_heads, head_dim):
    n, positions = mask.shape
    group = q.shape[1] // (kv_heads * head_dim)
    qg = q.reshape(n, kv_heads, group, head_dim).astype(np.float64)
    kg = keys.reshape(positions, kv_heads, head_dim).astype(np.float64)
    vg = values.reshape(positions, kv_heads, head_dim).astype(np.float64)
    scores = np.einsum("ngqd,sgd->ngqs", qg, kg) * head_dim ** -0.5
    scores = np.where(mask[:, None, None, :], scores, -np.inf)
    top = scores.max(axis=-1, keepdims=True)
    weights = np.where(mask[:, None, None, :], np.exp(scores - np.where(
        np.isfinite(top), top, 0.0)), 0.0)
    total = weights.sum(axis=-1, keepdims=True)
    out = np.einsum("ngqs,sgd->ngqd", weights / np.maximum(total, 1e-30), vg)
    return out.reshape(n, -1)


@pytest.mark.parametrize("n,positions,base,kv_heads,group,head_dim,block_q,block_k", [
    (4, 64, 0, 2, 2, 16, 64, 1024),     # the fixture's chunk: one block each way
    (4, 64, 8, 2, 2, 16, 64, 1024),
    (64, 256, 64, 2, 4, 32, 16, 64),    # four query blocks, four key blocks
    (64, 256, 192, 1, 8, 128, 32, 128),
    (32, 512, 100, 4, 1, 16, 8, 128),   # a base inside a key block
])
def test_the_kernel_is_dense_attention_under_the_mask(
        n, positions, base, kv_heads, group, head_dim, block_q, block_k):
    import jax.numpy as jnp

    rng = np.random.default_rng(n + positions + base)
    q = rng.standard_normal((n, kv_heads * group * head_dim)).astype(np.float32)
    keys = rng.standard_normal((positions, kv_heads * head_dim)).astype(np.float32)
    values = rng.standard_normal((positions, kv_heads * head_dim)).astype(np.float32)
    at = base + np.arange(n)
    causal = np.arange(positions)[None, :] <= at[:, None]
    mask = causal & (rng.random((n, positions)) < 0.3)
    mask[1] = False  # a query with nothing to attend to gets zeros
    mask[2] = causal[2]
    got = np.asarray(chunk_attention(
        jnp.asarray(q), jnp.asarray(keys), jnp.asarray(values), jnp.asarray(mask),
        base, kv_heads=kv_heads, head_dim=head_dim, block_q=block_q, block_k=block_k))
    want = dense(q, keys, values, mask, kv_heads, head_dim)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    assert (got[1] == 0).all() and np.abs(got[2]).max() > 0


def test_blocks_that_do_not_divide_are_refused():
    import jax.numpy as jnp

    with pytest.raises(ValueError):
        chunk_attention(jnp.zeros((24, 32)), jnp.zeros((100, 32)), jnp.zeros((100, 32)),
                        jnp.zeros((24, 100), bool), 0, kv_heads=2, head_dim=16,
                        block_q=16, block_k=64)
