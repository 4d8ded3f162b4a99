"""Flagship vision classifier: the ``densenet_onnx`` fixture contract on XLA.

The reference's image_client targets a ``densenet_onnx`` model served by
tritonserver (image_client.py: parse_model :60, preprocess :154, postprocess
:196); the model itself is an ONNX artifact the client repo doesn't contain.
Here the contract — input ``data_0`` FP32 [3,224,224] (CHW), output ``fc6_1``
FP32 [1000,1,1], classification labels — is served by a TPU-first flax CNN:

- bfloat16 activations/matmuls (MXU-native), float32 params
- NHWC layout internally (TPU convolution-friendly); the CHW wire format of
  the fixture is transposed once at the boundary
- dense-block-style feature reuse, global average pooling (any input HW)
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional

import numpy as np

from .base import Model, TensorSpec


def _build_flax_model(num_classes: int, width: int = 32, stages=(2, 2, 2)):
    import flax.linen as nn
    import jax.numpy as jnp

    class ConvBlock(nn.Module):
        features: int

        @nn.compact
        def __call__(self, x):
            x = nn.Conv(self.features, (3, 3), padding="SAME", use_bias=False,
                        dtype=jnp.bfloat16)(x)
            x = nn.GroupNorm(num_groups=8, dtype=jnp.bfloat16)(x)
            return nn.relu(x)

    class DenseStage(nn.Module):
        """Dense-block flavor: each layer sees the concat of all prior maps."""

        growth: int
        layers: int

        @nn.compact
        def __call__(self, x):
            for _ in range(self.layers):
                y = ConvBlock(self.growth)(x)
                x = jnp.concatenate([x, y], axis=-1)
            return x

    class DenseNetish(nn.Module):
        num_classes: int
        width: int
        stages: tuple = (2, 2, 2)

        @nn.compact
        def __call__(self, x):  # x: [N, H, W, C] bf16
            x = nn.Conv(self.width, (7, 7), strides=(2, 2), padding="SAME",
                        use_bias=False, dtype=jnp.bfloat16)(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
            for i, layers in enumerate(self.stages):
                x = DenseStage(growth=self.width * (2**min(i, 2)), layers=layers)(x)
                # transition: 1x1 squeeze + stride-2 pool
                x = ConvBlock(self.width * (2**min(i, 2)))(x)
                x = nn.avg_pool(x, (2, 2), strides=(2, 2))
            x = jnp.mean(x, axis=(1, 2))  # global average pool
            x = nn.Dense(self.num_classes, dtype=jnp.bfloat16)(x)
            return x.astype(jnp.float32)

    return DenseNetish(num_classes=num_classes, width=width, stages=tuple(stages))


class ImagePreprocessModel(Model):
    """``preprocess``: raw UINT8 HWC image -> normalized FP32 CHW [3,224,224].

    The ensemble front stage (reference: the DALI/preprocess member of
    ensemble_image_client's pipeline): nearest-neighbor resize + INCEPTION
    scaling fused on-device via the Pallas normalize kernel.
    """

    name = "preprocess"

    def inputs(self) -> List[TensorSpec]:
        return [TensorSpec("raw_image", "UINT8", [-1, -1, 3])]

    def outputs(self) -> List[TensorSpec]:
        return [TensorSpec("preprocessed", "FP32", [3, 224, 224])]

    def execute(self, inputs: Dict[str, np.ndarray], parameters: Dict[str, Any]):
        from ..ops import preprocess_image

        # resize + INCEPTION normalize + CHW layout: one compiled program
        arr = preprocess_image(
            np.asarray(inputs["raw_image"]), 224, 224,
            scale=2.0 / 255.0, shift=-1.0,
        )
        return {"preprocessed": np.ascontiguousarray(arr)}


class DenseNetModel(Model):
    """Server-side vision model with the densenet_onnx wire contract."""

    name = "densenet_onnx"
    platform = "jax_flax"
    max_batch_size = 0  # fixture contract: one CHW image per request

    # stage depths: "lite" is the CI/protocol-testing default; "121" is the
    # densenet-121 layout (6/12/24/16 dense layers) for real-chip rounds
    ARCHS = {"lite": (2, 2, 2), "121": (6, 12, 24, 16)}

    def __init__(
        self,
        num_classes: int = 1000,
        width: int = 32,
        seed: int = 0,
        tensor_parallel: int = 1,
        arch: str = "lite",
    ):
        """``tensor_parallel > 1`` shards parameter output-feature axes over a
        (1, tp) device mesh; XLA inserts the collectives (serving-side scale,
        no client change). ``arch``: "lite" (default) or "121"
        (densenet-121 stage depths — budget for the compile on CPU)."""
        super().__init__()
        if arch not in self.ARCHS:
            raise ValueError(f"arch must be one of {sorted(self.ARCHS)}")
        self._num_classes = num_classes
        self._width = width
        self._seed = seed
        self._tensor_parallel = tensor_parallel
        self._stages = self.ARCHS[arch]
        self._lock = threading.Lock()
        self._module = None
        self._params = None
        self._jit_fn = None
        self._labels = [f"class_{i}" for i in range(num_classes)]

    def inputs(self) -> List[TensorSpec]:
        return [TensorSpec("data_0", "FP32", [3, 224, 224])]

    def outputs(self) -> List[TensorSpec]:
        return [TensorSpec("fc6_1", "FP32", [self._num_classes, 1, 1])]

    def labels(self) -> Optional[List[str]]:
        return self._labels

    # -- lazy build (first inference pays init+compile once) ----------------
    def _ensure_built(self):
        with self._lock:
            if self._jit_fn is not None:
                return
            import jax
            import jax.numpy as jnp

            devices = jax.devices()
            tp = self._tensor_parallel
            if tp > len(devices):
                raise ValueError(
                    f"tensor_parallel={tp} but only {len(devices)} devices")
            self._module = _build_flax_model(
                self._num_classes, self._width, self._stages
            )
            rng = jax.random.PRNGKey(self._seed)
            dummy = jnp.zeros((1, 224, 224, 3), jnp.bfloat16)
            # one compiled program: eager init dispatches (and compiles)
            # every op of the forward pass one by one; same values
            self._params = jax.jit(self._module.init)(rng, dummy)

            if tp > 1:
                from jax.sharding import Mesh

                from ..parallel import shard_params

                # (1, tp): serve-time batch stays whole, weights shard on
                # 'model' (make_mesh's dp-leaning factorization fits training)
                mesh = Mesh(
                    np.array(devices[:tp]).reshape(1, tp), ("data", "model")
                )
                self._params = shard_params(self._params, mesh)

            @jax.jit
            def forward(params, chw_batch):
                # wire contract is CHW float32; go NHWC bf16 for the MXU
                x = jnp.transpose(chw_batch, (0, 2, 3, 1)).astype(jnp.bfloat16)
                return self._module.apply(params, x)

            self._jit_fn = forward

    def forward_fn(self):
        """(jittable_fn, params) for direct embedding (entry(), parallel)."""
        self._ensure_built()
        return self._jit_fn, self._params

    def execute(self, inputs: Dict[str, np.ndarray], parameters: Dict[str, Any]):
        self._ensure_built()
        import jax.numpy as jnp

        arr = inputs["data_0"]
        x = jnp.asarray(arr).reshape((1, 3) + tuple(arr.shape[-2:]))
        logits = self._jit_fn(self._params, x)  # [1, num_classes]
        return {"fc6_1": logits.reshape(self._num_classes, 1, 1)}
