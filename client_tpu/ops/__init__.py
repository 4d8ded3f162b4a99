"""Jitted data-plane ops and Pallas kernels for the hot client/server paths.

The reference client's compute is numpy on the CUDA host (dtype conversion,
image preprocessing in examples). Here those run through XLA/Pallas so the
data plane stays on-device:

- ``normalize_image``: fused scale/shift/cast preprocessing (the
  image_client NONE/INCEPTION/VGG scaling modes) as a Pallas VPU kernel on
  TPU, interpret-mode on CPU. The elementwise kernels run a grid over
  rows, so they hold one block in VMEM whatever the tensor's size.
- ``to_bf16`` / ``from_bf16``: BF16 wire conversion as jitted casts (the
  serializers' device-side twin).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _on_tpu() -> bool:
    """Kernels compile to Mosaic on the chip and run in interpret mode on
    the CPU the tests pin; any other backend is an error, not a fallback."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels run on 'tpu' (compiled) or 'cpu' (interpret "
            f"mode), not on backend {backend!r}")
    return backend == "tpu"


class KernelTooLargeError(ValueError):
    """A row block of the tensor does not fit the kernel's VMEM budget."""


_BLOCK_BYTES = 2 << 20  # per operand block: in and out, double-buffered,
#                         plus f32 temporaries stay inside the 16 MiB a
#                         v5e kernel may scope
_ROW_TILE = 32          # int8 sublane tile; a multiple of f32's 8, bf16's 16


def _rowwise_call(kernel, x, out_dtype):
    """Run ``kernel`` over ``[rows, last-axis]`` blocks of ``x``, a grid
    over rows: the whole array in VMEM stops compiling at 64 MiB."""
    from jax.experimental import pallas as pl

    cols = x.shape[-1]
    x2 = x.reshape(-1, cols)
    rows = x2.shape[0]
    row_bytes = -(-cols // 128) * 128 * 4  # lanes pad to 128; f32 compute
    if _ROW_TILE * row_bytes > _BLOCK_BYTES:
        raise KernelTooLargeError(
            f"one {_ROW_TILE}-row block of shape {tuple(x.shape)} needs "
            f"{_ROW_TILE * row_bytes} B of VMEM (budget {_BLOCK_BYTES} B): "
            f"reshape to a last axis of at most "
            f"{_BLOCK_BYTES // _ROW_TILE // 4} elements")
    block_rows = min(rows, _BLOCK_BYTES // row_bytes // _ROW_TILE * _ROW_TILE)
    spec = pl.BlockSpec((block_rows, cols), lambda i: (i, 0))
    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, block_rows),),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x2.shape, out_dtype),
        interpret=not _on_tpu(),
    )(x2)
    return out.reshape(x.shape)


def _normalize_kernel(x_ref, o_ref, *, scale, shift):
    o_ref[...] = (x_ref[...] * scale + shift).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "shift", "out_dtype"))
def normalize_image(x, scale: float = 1.0, shift: float = 0.0, out_dtype=jnp.bfloat16):
    """Fused ``x * scale + shift`` cast to ``out_dtype``.

    image_client scaling modes map directly: INCEPTION => scale=2/255,
    shift=-1; VGG => per-channel shift (applied before this call); NONE =>
    scale=1, shift=0 (pure cast).
    """
    kernel = functools.partial(_normalize_kernel, scale=scale, shift=shift)
    return _rowwise_call(kernel, x, out_dtype)


@jax.jit
def to_bf16(x):
    """Device-side BF16 downcast (round-to-nearest-even on the VPU)."""
    return x.astype(jnp.bfloat16)


@jax.jit
def from_bf16(x):
    """Device-side BF16 -> float32 upcast."""
    return x.astype(jnp.float32)


def stage_to_device(host_array, device=None):
    """Async host->HBM staging (returns immediately; fence at use)."""
    return jax.device_put(host_array, device)


# ---------------------------------------------------------------------------
# image preprocessing (resize + normalize fused under one jit)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("out_h", "out_w"))
def resize_nearest(img, out_h: int = 224, out_w: int = 224):
    """Nearest-neighbor resize of an HWC image via XLA gathers.

    The device-side twin of image_client's PIL resize (reference
    image_client.py preprocess :154): two index gathers XLA fuses with
    whatever follows.
    """
    h, w = img.shape[0], img.shape[1]
    ys = jnp.clip(
        (jnp.arange(out_h) * (h / out_h) + 0.5).astype(jnp.int32), 0, h - 1
    )
    xs = jnp.clip(
        (jnp.arange(out_w) * (w / out_w) + 0.5).astype(jnp.int32), 0, w - 1
    )
    return img[ys][:, xs]


@functools.partial(
    jax.jit, static_argnames=("out_h", "out_w", "scale", "shift", "out_dtype")
)
def preprocess_image(
    img, out_h: int = 224, out_w: int = 224, scale: float = 2.0 / 255.0,
    shift: float = -1.0, out_dtype=jnp.float32,
):
    """resize -> normalize -> HWC->CHW, one compiled program.

    The whole ensemble front stage (ImagePreprocessModel) as a single XLA
    computation: gathers fuse into the normalize elementwise, and the
    transpose is a layout assignment rather than a copy.
    """
    x = resize_nearest(img.astype(jnp.float32), out_h, out_w)
    x = x * scale + shift
    return jnp.transpose(x, (2, 0, 1)).astype(out_dtype)


# ---------------------------------------------------------------------------
# classification postprocess
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("k",))
def topk_classification(logits, k: int):
    """(values, indices) of the top-k logits along the last axis.

    ``jax.lax.top_k`` lowers to the TPU's sort unit; the server's
    classification extension ranks with this instead of a host argsort.
    """
    return jax.lax.top_k(logits, k)


def _softmax_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    o_ref[...] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(o_ref.dtype)


@jax.jit
def softmax_probabilities(logits):
    """Numerically-stable softmax over the last axis as a Pallas VPU kernel
    (max-subtract, exp, normalize fused in one pass over VMEM)."""
    return _rowwise_call(_softmax_kernel, logits, jnp.float32)


# ---------------------------------------------------------------------------
# int8 wire quantization (bandwidth-limited transports)
# ---------------------------------------------------------------------------


def _quantize_kernel(x_ref, o_ref, *, inv_scale):
    x = x_ref[...].astype(jnp.float32) * inv_scale
    o_ref[...] = jnp.clip(jnp.round(x), -127.0, 127.0).astype(jnp.int8)


def _dequantize_kernel(q_ref, o_ref, *, scale):
    o_ref[...] = (q_ref[...].astype(jnp.float32) * scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale",))
def quantize_int8(x, scale: float):
    """Symmetric int8 quantization ``round(x/scale)`` clipped to [-127,127].

    Shrinks wire tensors 4x for bandwidth-limited hops; pair with
    ``dequantize_int8`` on the receiving side. Pallas VPU kernel on TPU.
    """
    kernel = functools.partial(_quantize_kernel, inv_scale=1.0 / scale)
    return _rowwise_call(kernel, x, jnp.int8)


@functools.partial(jax.jit, static_argnames=("scale", "out_dtype"))
def dequantize_int8(q, scale: float, out_dtype=jnp.float32):
    """Inverse of :func:`quantize_int8`."""
    kernel = functools.partial(_dequantize_kernel, scale=scale)
    return _rowwise_call(kernel, q, out_dtype)


def flash_attention(q, k, v, causal: bool = False, **kwargs):
    """Blocked online-softmax attention (Pallas kernel; see
    ops/flash_attention.py)."""
    from .flash_attention import flash_attention as impl

    return impl(q, k, v, causal=causal, **kwargs)
