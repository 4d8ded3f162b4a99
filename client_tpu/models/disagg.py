"""Disaggregated prefill/decode model pair over the decoder_lm weights.

Production LLM fleets split compute-bound prefill from memory-bound
decode onto differently-provisioned replicas (Hermes, arXiv:2409.04249).
The client-side orchestration (``client_tpu.disagg``) needs server
fixtures for both halves of that split, sharing weights (and the single
compiled decode step) with the zoo's ``decoder_lm``/``tiny_lm_generate``
so the disaggregated token stream is assertable BIT-EXACT against
monolithic generation:

- ``decoder_lm_disagg_prefill`` — stateless prefill: runs the prompt
  through a fresh KV cache and RETURNS the cache as a tensor (plus the
  first greedy token and the fill position). Pure function of the
  prompt, which is what makes re-prefill recovery idempotent by
  construction: re-running it over prompt + already-emitted tokens
  reproduces the exact KV state the lost decode replica held.
- ``decoder_lm_kv_decode`` — decoupled decode-from-handed-off-KV:
  accepts the exported KV tensor, the fill position and the first
  pending token, and streams greedy tokens exactly like
  ``tiny_lm_generate``'s per-token path (one response per token, INDEX
  offset by ``START_INDEX`` so a resumed stream numbers tokens
  globally).

The KV rides the wire as FP32 (``[LAYERS*2, HEADS, MAX_LEN, Dh]``; row
``2l`` is layer ``l``'s K, row ``2l+1`` its V). bf16 → fp32 widening is
exact and narrowing an exactly-representable value back is exact, so
the round-trip is bit-preserving while keeping the handoff buffer a
plain numpy dtype the client can digest (blake2b) and stage through the
shared-memory arena without bf16 special-casing.

Wire contracts:
  decoder_lm_disagg_prefill (unary):
    inputs:  TOKENS     INT32[1, -1]  prompt token ids
    outputs: KV         FP32[L*2, H, M, Dh]  the filled cache
             NEXT_TOKEN INT32[1, 1]   greedy argmax after the last token
             POS        INT32[1, 1]   tokens consumed (cache fill level)
  decoder_lm_kv_decode (decoupled — use streaming inference):
    inputs:  KV          FP32[L*2, H, M, Dh]  handed-off cache
             POS         INT32[1]     cache fill level
             FIRST_TOKEN INT32[1]     first pending (un-emitted) token
             MAX_TOKENS  INT32[1]     tokens to emit (optional, default 16)
             END_ID      INT32[1]     stop token id (optional; stops AFTER
                                      emitting it)
             START_INDEX INT32[1]     INDEX of the first emitted token
                                      (optional, default 0 — resumed
                                      streams pass tokens-already-emitted)
    outputs: NEXT_TOKEN  INT32[1, 1]  one generated token per response
             INDEX       INT32[1, 1]  global position of that token
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List

import numpy as np

from .base import Model, TensorSpec
from .decoder import RungCount, TinyDecoderModel


def _kv_shape(dec: TinyDecoderModel) -> List[int]:
    return [dec.LAYERS * 2, dec.HEADS, dec.MAX_LEN, dec.D_MODEL // dec.HEADS]


class DisaggPrefillModel(Model):
    """``decoder_lm_disagg_prefill``: stateless prompt prefill that
    exports the KV cache for handoff to a decode-role replica."""

    name = "decoder_lm_disagg_prefill"
    platform = "jax"
    max_batch_size = 0

    def __init__(self, seed: int = 0, decoder: TinyDecoderModel = None):
        super().__init__()
        # weight/step sharing by composition (see TinyGenerateModel):
        # bit-exactness across serving styles requires ONE parameter set
        self._decoder = (decoder if decoder is not None
                         else TinyDecoderModel(seed=seed))
        self.steps_by_rung = RungCount()

    def inputs(self) -> List[TensorSpec]:
        return [TensorSpec("TOKENS", "INT32", [1, -1])]

    def outputs(self) -> List[TensorSpec]:
        return [
            TensorSpec("KV", "FP32", _kv_shape(self._decoder)),
            TensorSpec("NEXT_TOKEN", "INT32", [1, 1]),
            TensorSpec("POS", "INT32", [1, 1]),
        ]

    def execute(self, inputs: Dict[str, np.ndarray],
                parameters: Dict[str, Any]) -> Dict[str, np.ndarray]:
        dec = self._decoder
        dec._ensure_built()
        tokens = np.asarray(inputs["TOKENS"]).reshape(-1).astype(np.int64)
        if tokens.size == 0:
            raise ValueError("empty prompt")
        if np.any(tokens < 0) or np.any(tokens >= dec.VOCAB):
            raise ValueError(f"tokens out of range [0, {dec.VOCAB})")
        if tokens.size >= dec.MAX_LEN:
            raise ValueError(f"prompt longer than max_len {dec.MAX_LEN}")

        # same compiled step, rung for rung, the monolithic paths use —
        # nothing new compiles, and the produced cache is bit-identical to
        # the state tiny_lm_generate would hold after the same token sequence
        caches, pos = dec._fresh_cache(), 0
        logits = None
        for t in tokens:
            logits, caches = dec.decode_step(
                caches, int(t), pos, self.steps_by_rung)
            pos += 1

        # [L*2, H, M, Dh] fp32: exact widening of the bf16 cache
        kv = np.stack(
            [np.asarray(c[half], dtype=np.float32)
             for c in caches for half in ("k", "v")])
        logits_np = np.asarray(logits, dtype=np.float32)
        return {
            "KV": kv,
            "NEXT_TOKEN": np.array([[int(logits_np.argmax())]],
                                   dtype=np.int32),
            "POS": np.array([[pos]], dtype=np.int32),
        }


class KvDecodeModel(Model):
    """``decoder_lm_kv_decode``: decoupled greedy decode resuming from a
    handed-off KV cache (the decode half of the disaggregated split)."""

    name = "decoder_lm_kv_decode"
    platform = "jax"
    max_batch_size = 0
    decoupled = True

    DEFAULT_MAX_TOKENS = 16

    def __init__(self, seed: int = 0, decoder: TinyDecoderModel = None):
        super().__init__()
        self._decoder = (decoder if decoder is not None
                         else TinyDecoderModel(seed=seed))
        self.steps_by_rung = RungCount()

    def inputs(self) -> List[TensorSpec]:
        return [
            TensorSpec("KV", "FP32", _kv_shape(self._decoder)),
            TensorSpec("POS", "INT32", [1]),
            TensorSpec("FIRST_TOKEN", "INT32", [1]),
            TensorSpec("MAX_TOKENS", "INT32", [1], optional=True),
            TensorSpec("END_ID", "INT32", [1], optional=True),
            TensorSpec("START_INDEX", "INT32", [1], optional=True),
        ]

    def outputs(self) -> List[TensorSpec]:
        return [
            TensorSpec("NEXT_TOKEN", "INT32", [1, 1]),
            TensorSpec("INDEX", "INT32", [1, 1]),
        ]

    def execute(self, inputs, parameters):
        raise ValueError(
            "decoder_lm_kv_decode is a decoupled model; use streaming "
            "inference")

    def execute_decoupled(
        self, inputs: Dict[str, np.ndarray], parameters: Dict[str, Any]
    ) -> Iterable[Dict[str, np.ndarray]]:
        import jax.numpy as jnp

        dec = self._decoder
        dec._ensure_built()
        L, H, M = dec.LAYERS, dec.HEADS, dec.MAX_LEN
        Dh = dec.D_MODEL // H

        kv = np.asarray(inputs["KV"], dtype=np.float32)
        if kv.shape != (L * 2, H, M, Dh):
            raise ValueError(
                f"KV shape {kv.shape} != expected {(L * 2, H, M, Dh)}")
        pos = int(np.asarray(inputs["POS"]).reshape(-1)[0])
        if not 0 < pos <= M:
            raise ValueError(f"POS out of range (0, {M}]")
        next_token = int(np.asarray(inputs["FIRST_TOKEN"]).reshape(-1)[0])
        if not 0 <= next_token < dec.VOCAB:
            raise ValueError(f"FIRST_TOKEN out of range [0, {dec.VOCAB})")
        budget = int(
            np.asarray(inputs.get("MAX_TOKENS", self.DEFAULT_MAX_TOKENS))
            .reshape(-1)[0])
        if budget < 1:
            raise ValueError("MAX_TOKENS must be >= 1")
        end_id = None
        if "END_ID" in inputs:
            end_id = int(np.asarray(inputs["END_ID"]).reshape(-1)[0])
        start_index = int(
            np.asarray(inputs.get("START_INDEX", 0)).reshape(-1)[0])
        if start_index < 0:
            raise ValueError("START_INDEX must be >= 0")

        # narrow back to the bf16 the cache was exported from (exact:
        # every value is bf16-representable) — the step function then
        # sees bit-identical state to the monolithic decode loop
        caches = [
            {"k": jnp.asarray(kv[2 * l], jnp.bfloat16),
             "v": jnp.asarray(kv[2 * l + 1], jnp.bfloat16)}
            for l in range(L)
        ]

        def response(token_id: int, index: int):
            return {
                "NEXT_TOKEN": np.array([[token_id]], dtype=np.int32),
                "INDEX": np.array([[index]], dtype=np.int32),
            }

        # mirrors tiny_lm_generate's per-token path exactly (budget
        # check, END_ID emitted then stop, one step per emitted token)
        emitted = 0
        while emitted < budget:
            yield response(next_token, start_index + emitted)
            emitted += 1
            if emitted >= budget or (end_id is not None
                                     and next_token == end_id):
                return
            if pos >= M:
                return  # static cache exhausted
            logits, caches = dec.decode_step(
                caches, next_token, pos, self.steps_by_rung)
            pos += 1
            next_token = int(np.asarray(logits).argmax())
