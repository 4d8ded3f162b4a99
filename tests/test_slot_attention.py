"""The kernel of one query a slot over that slot's rows (ops/slot_attention.py),
in interpret mode, against the plain masked softmax: grouped-query heads read
from rows that hold the key-value heads side by side, each slot to its own
reach, across several blocks of rows, and a slot of reach 0 given zeros."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from client_tpu.ops.slot_attention import slot_attention

SLOTS, HEADS, KV, DH, ROWS = 5, 6, 2, 16, 32


def _dense(q, keys, values, reach):
    """[slots, heads * head_dim]: every head over its group's lanes of the
    rows ``j < reach``, in float32."""
    group = HEADS // KV
    k = keys.astype(jnp.float32).reshape(SLOTS, ROWS, KV, DH)
    v = values.astype(jnp.float32).reshape(SLOTS, ROWS, KV, DH)
    qg = q.astype(jnp.float32).reshape(SLOTS, KV, group, DH)
    scores = jnp.einsum("bgid,bjgd->bgij", qg, k) * DH ** -0.5
    kept = jnp.arange(ROWS)[None, None, None, :] < reach[:, None, None, None]
    probs = jax.nn.softmax(jnp.where(kept, scores, -1e30), axis=-1)
    out = jnp.einsum("bgij,bjgd->bgid", jnp.where(kept, probs, 0.0), v)
    return jnp.where(reach[:, None] > 0, out.reshape(SLOTS, HEADS * DH), 0.0)


@pytest.mark.parametrize("block_k", [8, 32])
def test_each_slot_attends_to_its_own_rows_up_to_its_reach(block_k):
    rng = np.random.default_rng(7)
    q, keys, values = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
                       for shape in ((SLOTS, HEADS, DH), (SLOTS, ROWS, KV * DH),
                                     (SLOTS, ROWS, KV * DH)))
    # nothing; within the first block; a block's last row; past a block; all
    reach = jnp.asarray([0, 3, 8, 17, ROWS], jnp.int32)
    got = slot_attention(q, keys, values, reach, kv_heads=KV, head_dim=DH,
                         block_k=block_k, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_dense(q, keys, values, reach)),
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(got[0]).any()


def test_rows_past_the_reach_change_nothing():
    """What a slot left past its reach (an earlier stream's rows) is never
    read."""
    rng = np.random.default_rng(8)
    q, keys, values = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
                       for shape in ((SLOTS, HEADS, DH), (SLOTS, ROWS, KV * DH),
                                     (SLOTS, ROWS, KV * DH)))
    reach = jnp.asarray([1, 5, 9, 16, 31], jnp.int32)
    past = jnp.arange(ROWS)[None, :, None] >= reach[:, None, None]
    call = lambda k, v: np.asarray(slot_attention(
        q, k, v, reach, kv_heads=KV, head_dim=DH, block_k=8, interpret=True))
    np.testing.assert_array_equal(
        call(keys, values), call(jnp.where(past, 1e4, keys), jnp.where(past, -1e4, values)))
