"""Tensor-parallel autoregressive decode behind the v2 sequence API.

``decoder_lm`` (models/decoder.py) serves one sequence per device;
``decoder_lm_tp`` is the multi-chip serving story: the SAME decode step —
same weights, same math, same wire contract — executed SPMD over a
``jax.sharding.Mesh`` axis, the way a production LLM too big for one chip
is served. Megatron-style layout, expressed as shardings (XLA/GSPMD
inserts the collectives — no hand-written psum):

- attention is head-sharded: ``wq/wk/wv [D, H, Dh]`` and the per-sequence
  KV caches ``[H, M, Dh]`` are partitioned on the head axis, so cache
  update + softmax + weighted sum are fully local per shard (zero
  attention collectives);
- ``mlp_in [D, 4D]`` is column-parallel (sharded output features) — each
  output element is still a FULL contraction, so no re-association;
- the row-side contractions (attention output projection, ``mlp_out``)
  run replicated on gathered activations: an explicit sharding constraint
  all-gathers the per-shard ``[H, Dh]`` / ``[4D]`` activation vectors
  (tiny next to the caches) and the whole contraction happens on every
  device. This trades Megatron's psum for an all-gather deliberately:
  a psum re-associates the contraction's partial sums, and re-associated
  f32 rounding near an argmax tie changes greedy tokens — the serving
  guarantee here is BIT-equality with the single-device decoder, so
  collectives move data and never split a reduction;
- embeddings/unembed are replicated (tiny for this fixture; a production
  vocab would shard the unembed and all-gather logits).

The KV cache for every live sequence stays device-resident and sharded
for the sequence's whole life — requests only ship one token over the
wire, which is the sequence API's entire point (reference contract:
simple_grpc_sequence_stream_infer_client.py:59-81).

Serving logic (sequence table, per-CORRID locks, validation) is inherited
from TinyDecoderModel unchanged — this class only swaps the compiled step
and cache placement, which is exactly the separation a tpu-first design
wants: parallelism is a compilation/placement concern, not a protocol one.
"""

from __future__ import annotations

from typing import Optional

from .decoder import TinyDecoderModel


class TPDecoderModel(TinyDecoderModel):
    """``decoder_lm_tp``: TinyDecoderModel sharded over a mesh axis."""

    name = "decoder_lm_tp"

    def __init__(self, seed: int = 0, tp: Optional[int] = None, mesh=None,
                 axis: str = "model"):
        """``mesh``+``axis``: serve over an existing mesh's axis (the
        server's multi-chip mesh); ``tp``: build a private 1D mesh over the
        first ``tp`` devices. HEADS (4) must divide by the axis size."""
        super().__init__(seed=seed)
        self._mesh = mesh
        self._axis = axis
        self._tp = tp

    def _ensure_mesh(self):
        import jax
        from jax.sharding import Mesh

        if self._mesh is None:
            import numpy as np

            devices = jax.devices()
            if self._tp:
                tp = self._tp
            else:
                # auto: the largest divisor of HEADS that fits the host —
                # a 3-device host serves tp=2, not a divisibility error
                tp = max(d for d in range(1, self.HEADS + 1)
                         if self.HEADS % d == 0 and d <= len(devices))
                if tp < 2:
                    raise ValueError(
                        f"{self.name} shards over at least 2 devices and "
                        f"this host has {len(devices)}; serve decoder_lm")
            if tp > len(devices):
                raise ValueError(
                    f"tp={tp} but only {len(devices)} devices")
            self._mesh = Mesh(np.array(devices[:tp]), (self._axis,))
        size = self._mesh.shape[self._axis]
        if self.HEADS % size:
            raise ValueError(
                f"HEADS={self.HEADS} not divisible by {self._axis} axis "
                f"size {size}")
        return self._mesh

    @property
    def tp_degree(self) -> int:
        return self._ensure_mesh().shape[self._axis]

    # -- compiled pieces -----------------------------------------------------
    def _build(self):
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import NamedSharding, PartitionSpec as P

        # mesh validation FIRST: if it raises after super()._build() had
        # set _step_fn, _ensure_built would consider the model built and
        # silently serve single-device decode under the tp name
        mesh = self._ensure_mesh()
        super()._build()  # base params + single-device step (same seed)
        ax = self._axis
        D, H, V, M = self.D_MODEL, self.HEADS, self.VOCAB, self.MAX_LEN
        Dh = D // H

        def put(x, spec):
            return jax.device_put(x, NamedSharding(mesh, spec))

        # re-express the fused qkv/proj weights head-major and place them;
        # numerically identical contractions, just indexed per head
        tp_layers = []
        for layer in self._params["layers"]:
            qkv = layer["qkv"]  # [D, 3D]
            tp_layers.append({
                "wq": put(qkv[:, :D].reshape(D, H, Dh), P(None, ax, None)),
                "wk": put(qkv[:, D:2 * D].reshape(D, H, Dh),
                          P(None, ax, None)),
                "wv": put(qkv[:, 2 * D:].reshape(D, H, Dh),
                          P(None, ax, None)),
                "proj": put(layer["proj"].reshape(H, Dh, D), P()),
                "mlp_in": put(layer["mlp_in"], P(None, ax)),
                "mlp_out": put(layer["mlp_out"], P()),
            })
        self._params = {
            "embed": put(self._params["embed"], P()),
            "pos": put(self._params["pos"], P()),
            "layers": tp_layers,
            "unembed": put(self._params["unembed"], P()),
        }
        self._cache_sharding = NamedSharding(mesh, P(ax, None, None))

        def norm(x):
            x32 = x.astype(jnp.float32)
            mu = jnp.mean(x32, axis=-1, keepdims=True)
            var = jnp.var(x32, axis=-1, keepdims=True)
            return ((x32 - mu) * lax.rsqrt(var + 1e-5)).astype(x.dtype)

        def step(params, caches, token, pos):
            x = params["embed"][token] + params["pos"][pos]  # [D] replicated
            new_caches = []
            for layer, cache in zip(params["layers"], caches):
                h = norm(x)
                # head-sharded projections: outputs [H, Dh] partitioned on H
                q = jnp.einsum("d,dhk->hk", h, layer["wq"])
                k_new = jnp.einsum("d,dhk->hk", h, layer["wk"])[:, None, :]
                v_new = jnp.einsum("d,dhk->hk", h, layer["wv"])[:, None, :]
                k = lax.dynamic_update_slice(cache["k"], k_new, (0, pos, 0))
                v = lax.dynamic_update_slice(cache["v"], v_new, (0, pos, 0))
                new_caches.append({"k": k, "v": v})
                # attention fully local per head shard
                scores = jnp.einsum(
                    "hd,hmd->hm", q.astype(jnp.float32),
                    k.astype(jnp.float32)) * (Dh ** -0.5)
                mask = jnp.arange(M) <= pos
                scores = jnp.where(mask[None, :], scores, -jnp.inf)
                probs = jax.nn.softmax(scores, axis=-1)
                attn = jnp.einsum("hm,hmd->hd", probs, v.astype(jnp.float32))
                # all-gather the head-sharded activations, then contract
                # WHOLE on every device (bit-equality; see module doc)
                attn = jax.lax.with_sharding_constraint(
                    attn, NamedSharding(mesh, P()))
                x = x + jnp.einsum(
                    "hk,hkd->d", attn.astype(jnp.bfloat16), layer["proj"])
                h2 = norm(x)
                h_mid = jax.nn.gelu(h2 @ layer["mlp_in"])  # [4D] sharded
                h_mid = jax.lax.with_sharding_constraint(
                    h_mid, NamedSharding(mesh, P()))
                x = x + h_mid @ layer["mlp_out"]
            logits = (norm(x) @ params["unembed"]).astype(jnp.float32)
            return logits, new_caches

        self._rungs = (M,)  # this step reads the whole cache
        # the base's table step and round read the base's weights
        self._table_fn = self._round_fn = None
        self.rows_path = None  # nor does its step write a table's rows
        self._step_fn = jax.jit(
            step, out_shardings=(
                NamedSharding(mesh, P()),
                [{"k": self._cache_sharding, "v": self._cache_sharding}
                 for _ in range(self.LAYERS)],
            ))

    def _fresh_cache(self):
        import jax
        import jax.numpy as jnp

        Dh = self.D_MODEL // self.HEADS
        zeros = jnp.zeros((self.HEADS, self.MAX_LEN, Dh), jnp.bfloat16)
        return [
            {
                "k": jax.device_put(zeros, self._cache_sharding),
                "v": jax.device_put(zeros, self._cache_sharding),
            }
            for _ in range(self.LAYERS)
        ]
