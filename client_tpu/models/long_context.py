"""Long-context encoder: ring attention served behind the v2 protocol.

Demonstrates the long-context serving path end-to-end: the request's
sequence is sharded over the device mesh, self-attention runs as ring
attention (K/V rotating over ICI, online softmax — no [seq, seq] matrix
ever materializes), and the encoded sequence returns through the normal
data plane. On a single device the ring degenerates gracefully (one hop).
"""

from __future__ import annotations

import math
import threading
from typing import Any, Dict, List

import numpy as np

from .base import Model, TensorSpec


class LongContextEncoderModel(Model):
    """``long_context_encoder``: FP32 [seq, dim] -> attended [seq, dim].

    One multi-head self-attention layer with fixed (seeded) projections —
    the fixture contract for exercising context parallelism, not a trained
    model. ``seq`` must divide by the mesh's data-axis size (except in
    flash mode, which handles arbitrary lengths on one device).
    """

    name = "long_context_encoder"
    platform = "jax_ring_attention"

    def __init__(
        self, dim: int = 64, heads: int = 4, seed: int = 0, n_devices: int = 0,
        attention: str = "ring",
    ):
        """``attention``: "ring" (default — O(seq/n²) memory), "ulysses"
        (all-to-all head repartition, fewer collective steps; heads must
        divide the mesh), or "auto" (see parallel/ulysses.py)."""
        super().__init__()
        if attention not in ("ring", "ulysses", "auto", "flash"):
            raise ValueError(
                f"attention must be ring|ulysses|auto|flash, got {attention!r}"
            )
        self._dim = dim
        self._heads = heads
        self._seed = seed
        self._n_devices = n_devices  # 0 = all available
        self._attention = attention
        self._lock = threading.Lock()
        self._built = None

    def inputs(self) -> List[TensorSpec]:
        return [TensorSpec("sequence", "FP32", [-1, self._dim])]

    def outputs(self) -> List[TensorSpec]:
        return [TensorSpec("encoded", "FP32", [-1, self._dim])]

    def _ensure_built(self):
        with self._lock:
            if self._built is not None:
                return self._built
            import jax
            import jax.numpy as jnp
            from jax.sharding import Mesh

            from ..parallel.ring import place_sharded
            from ..parallel.ulysses import sequence_parallel_attention

            available = len(jax.devices())
            n = self._n_devices or available
            if n > available:
                raise ValueError(
                    f"requested {n} devices but only {available} available"
                )
            # the ring runs over a flat (n, 1) data mesh
            mesh = Mesh(
                np.array(jax.devices()[:n]).reshape(n, 1), ("data", "model")
            )
            rng = jax.random.PRNGKey(self._seed)
            kq, kk, kv, ko = jax.random.split(rng, 4)
            scale = self._dim**-0.5
            wq = jax.random.normal(kq, (self._dim, self._dim), jnp.float32) * scale
            wk = jax.random.normal(kk, (self._dim, self._dim), jnp.float32) * scale
            wv = jax.random.normal(kv, (self._dim, self._dim), jnp.float32) * scale
            wo = jax.random.normal(ko, (self._dim, self._dim), jnp.float32) * scale
            self.weights = (wq, wk, wv, wo)  # for a reference beside it

            heads = self._heads
            head_dim = self._dim // heads

            attention_mode = self._attention

            @jax.jit  # one compile per sequence length, then cached
            def encode(xb):  # [1, seq, dim] device array
                seq = xb.shape[1]

                def project(w):
                    return (xb @ w).reshape(1, seq, heads, head_dim)

                if attention_mode == "flash":
                    # single-device blocked kernel (Pallas); no mesh hop.
                    # arbitrary lengths: the kernel pads + masks internally
                    from ..ops.flash_attention import flash_attention

                    out = flash_attention(
                        project(wq), project(wk), project(wv),
                    )
                else:
                    out = sequence_parallel_attention(
                        project(wq), project(wk), project(wv), mesh,
                        axis="data", mode=attention_mode,
                    )
                return (out.reshape(1, seq, self._dim) @ wo)[0]

            def run(x):  # [seq, dim] host array
                xb = jnp.asarray(x, jnp.float32)[None]
                if attention_mode != "flash":
                    # the mesh schemes want the sequence sharded; flash is
                    # single-device — placing it on the mesh would just make
                    # XLA all-gather it back per request
                    xb = place_sharded(xb, mesh)
                return encode(xb)

            self._built = (mesh, run)
            return self._built

    def execute(self, inputs: Dict[str, np.ndarray], parameters: Dict[str, Any]):
        mesh, encode = self._ensure_built()
        x = np.asarray(inputs["sequence"], dtype=np.float32)
        n = mesh.shape["data"]
        # flash is single-device (pads + masks internally); only the mesh
        # schemes shard the sequence and need the divisibility
        if self._attention != "flash" and x.shape[0] % n != 0:
            raise ValueError(
                f"sequence length {x.shape[0]} must divide by the mesh's "
                f"data-axis size {n}"
            )
        return {"encoded": encode(x)}
