"""Pipeline parallelism: GPipe-style microbatch streaming over the mesh.

Stage s of the network lives on device s of the pipeline axis; activations
hop one ICI link per step (``lax.ppermute``) while microbatches stream in,
so all devices compute concurrently once the pipeline fills. Exact: the
result equals applying the stages sequentially.

Layout: stage parameters are stacked on a leading axis sharded over the
pipeline axis (device s holds stack[s]); the input batch is split into
microbatches that enter at device 0 and exit at device S-1 after S hops.
"""

from __future__ import annotations


def mlp_stage_params(key, n_stages: int, dim: int):
    """Stacked per-stage MLP params: (W [S, dim, dim], b [S, dim])."""
    import jax
    import jax.numpy as jnp

    kw, kb = jax.random.split(key)
    scale = (2.0 / dim) ** 0.5
    w = jax.random.normal(kw, (n_stages, dim, dim), jnp.float32) * scale
    b = jax.random.normal(kb, (n_stages, dim), jnp.float32) * 0.01
    return w, b


def sequential_mlp(w, b, x):
    """Reference: apply all stages in order on one device."""
    import jax.numpy as jnp

    h = x
    for s in range(w.shape[0]):
        h = jnp.maximum(h @ w[s] + b[s], 0.0)
    return h


def pipeline_forward(w, b, x, mesh, axis: str = "model", n_microbatches: int = 4):
    """Run the stacked-stage MLP as a pipeline over ``axis``.

    w: [S, dim, dim], b: [S, dim] with S == mesh.shape[axis];
    x: [batch, dim] with batch divisible by n_microbatches.
    Returns [batch, dim], equal to ``sequential_mlp(w, b, x)``.
    """
    import jax.numpy as jnp
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    n_stages = mesh.shape[axis]
    if w.shape[0] != n_stages:
        raise ValueError(f"need {n_stages} stages for mesh axis '{axis}', got {w.shape[0]}")
    batch, dim = x.shape
    if batch % n_microbatches != 0:
        raise ValueError(f"batch {batch} must divide by n_microbatches {n_microbatches}")
    mb = batch // n_microbatches
    x_mb = x.reshape(n_microbatches, mb, dim)
    total_steps = n_stages + n_microbatches - 1
    # one hop toward the next stage; the wrap link's payload is ignored
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def block(w_blk, b_blk, x_all):
        # w_blk: [1, dim, dim] this device's stage; x_all: [M, mb, dim] replicated
        stage_w = w_blk[0]
        stage_b = b_blk[0]
        stage_index = lax.axis_index(axis)

        def step(carry, t):
            buf = carry  # [mb, dim]: activation arriving at this device
            mb_index = jnp.clip(t, 0, n_microbatches - 1)
            fresh = lax.dynamic_index_in_dim(x_all, mb_index, 0, keepdims=False)
            feed = jnp.where(stage_index == 0, fresh, buf)
            y = jnp.maximum(feed @ stage_w + stage_b, 0.0)
            buf_next = lax.ppermute(y, axis, perm)
            return buf_next, y

        buf0 = lax.pcast(jnp.zeros((mb, dim), x.dtype), (axis,), to="varying")
        _, ys = lax.scan(step, buf0, jnp.arange(total_steps))
        return ys[None]  # [1, T, mb, dim]; concat over devices outside

    ys = shard_map(
        block,
        mesh=mesh,
        in_specs=(P(axis, None, None), P(axis, None), P(None, None, None)),
        out_specs=P(axis, None, None, None),
    )(w, b, x_mb)
    # device S-1 emits microbatch m at step (S-1) + m
    last = ys[n_stages - 1]
    out = last[n_stages - 1 : n_stages - 1 + n_microbatches]
    return out.reshape(batch, dim)
