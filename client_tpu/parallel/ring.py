"""Ring attention: context parallelism for long sequences.

Long-context serving shards the sequence axis across the mesh; attention
then needs every query block to see every key/value block. Ring attention
keeps Q resident per device and rotates K/V one hop around the ring each
step (``lax.ppermute`` — rides ICI on real hardware), accumulating the
softmax online (log-sum-exp streaming), so no device ever materializes the
full [seq, seq] score matrix and per-device memory is O(seq/n · seq/n).

This is the TPU-native answer to the template's long-context mandate: the
client framework's server side can host sequence lengths that exceed a
single chip's HBM. Exact (matches full attention to numerical tolerance).
"""

from __future__ import annotations


def full_attention(q, k, v, causal: bool = False):
    """Reference dense attention. q,k,v: [batch, seq, heads, dim]."""
    import jax.numpy as jnp

    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        seq = q.shape[1]
        mask = jnp.tril(jnp.ones((seq, seq), bool))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jnp.exp(scores - scores.max(-1, keepdims=True))
    probs = probs / probs.sum(-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def ring_attention(q, k, v, mesh, axis: str = "data", causal: bool = False):
    """Exact attention with the sequence axis sharded over ``axis``.

    q, k, v: [batch, seq, heads, dim]; seq must divide by the axis size.
    Returns [batch, seq, heads, dim] with the same sharding. ``causal``
    masks at block granularity: a K/V block strictly after the query block
    contributes nothing, the diagonal block applies the in-block triangle —
    the standard causal-ring formulation (the compute for skipped blocks
    still rotates; a production kernel would also skip the FLOPs).
    """
    import jax.numpy as jnp
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    if q.shape[1] % n != 0:
        raise ValueError(f"seq {q.shape[1]} must divide by mesh axis size {n}")
    scale = q.shape[-1] ** -0.5
    perm = [(i, (i + 1) % n) for i in range(n)]

    def block(q_blk, k_blk, v_blk):
        # q_blk/k_blk/v_blk: the local [batch, seq/n, heads, dim] shards
        batch, sq, heads, dim = q_blk.shape
        my_index = lax.axis_index(axis)

        def scores_of(k_cur):
            return jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_cur) * scale

        def step(carry, i):
            k_cur, v_cur, acc, m, l = carry
            # rotate at the top of iterations 1..n-1: the ring sends exactly
            # 2(n-1) collectives, none wasted on a discarded final hop
            k_cur, v_cur = lax.cond(
                i > 0,
                lambda kv: (
                    lax.ppermute(kv[0], axis, perm),
                    lax.ppermute(kv[1], axis, perm),
                ),
                lambda kv: kv,
                (k_cur, v_cur),
            )
            s = scores_of(k_cur)  # [b, h, sq, sk]
            if causal:
                # after i hops this device holds the block that started at
                # device (my_index - i) mod n
                kv_index = (my_index - i) % n
                q_pos = my_index * sq + jnp.arange(sq)
                k_pos = kv_index * sq + jnp.arange(sq)
                allowed = q_pos[:, None] >= k_pos[None, :]  # [sq, sk]
                s = jnp.where(allowed[None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            correction = jnp.exp(m - m_new)
            l_new = l * correction + p.sum(-1)
            acc_new = (
                acc * correction[..., None]
                + jnp.einsum("bhqk,bkhd->bhqd", p, v_cur)
            )
            return (k_cur, v_cur, acc_new, m_new, l_new), None

        # the accumulators must carry the same varying-axes type as the
        # per-shard data or lax.scan rejects the carry
        def varying(x):
            return lax.pcast(x, (axis,), to="varying")

        acc0 = varying(jnp.zeros((batch, heads, sq, dim), jnp.float32))
        m0 = varying(jnp.full((batch, heads, sq), -jnp.inf, jnp.float32))
        l0 = varying(jnp.zeros((batch, heads, sq), jnp.float32))
        (k_fin, v_fin, acc, m, l), _ = lax.scan(
            step,
            (k_blk.astype(jnp.float32), v_blk.astype(jnp.float32), acc0, m0, l0),
            jnp.arange(n),
        )
        del k_fin, v_fin
        # causal first row(s) see at least the diagonal block, so l > 0 for
        # every query; keep the guard for numerical robustness anyway
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.transpose(out, (0, 2, 1, 3)).astype(q_blk.dtype)

    spec = P(None, axis, None, None)
    return shard_map(
        block, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )(q, k, v)


def place_sharded(arr, mesh, axis: str = "data"):
    """Shard [batch, seq, ...] on the sequence dim over ``axis``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    ndim = arr.ndim
    spec = [None] * ndim
    spec[1] = axis
    return jax.device_put(arr, NamedSharding(mesh, P(*spec)))
