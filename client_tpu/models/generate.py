"""Decoupled autoregressive generation — the LLM serving pattern.

The reference's decoupled transaction policy (repeat_int32 fixture,
SURVEY §2.4 "decoupled/repeat models"; model_transaction_policy in
grpc_service.proto) exists so one request can stream many responses.
Production LLM serving on Triton (the TensorRT-LLM / vLLM backends) is
exactly this shape: the client sends one request carrying the prompt and
``max_tokens`` and receives one streamed response per generated token.
``tiny_lm_generate`` is that contract implemented tpu-first, sharing
weights with the stateful ``decoder_lm`` fixture so greedy generation is
bit-exact across both serving styles (the cross-check the tests pin).

TPU-first choices:
- one compiled decode step a rung of the decoder's ladder (static-shape KV
  cache, position-based mask, attention over the live prefix — see
  decoder.py) serves prefill AND every generated token: no
  shape-polymorphic retraces, ever, and every rung is compiled before the
  first stream's first step (``_ensure_built``);
- multi-token decoding runs INSIDE XLA via ``lax.scan`` when the request
  sets the ``chunk`` parameter > 1: the greedy argmax→feed-back loop is a
  scan carry, so K tokens cost one device dispatch instead of K (the
  dispatch-bound regime is exactly where this wins); these programs, one a
  K and compiled on demand, read the whole cache: a ladder would multiply
  them;
  chunk=1 (the default) dispatches per token, which is what a
  streaming-latency harness should measure;
- greedy argmax happens on-device in int32 — the host only ever sees the
  emitted token ids, one int per token.

Wire contract (decoupled — use streaming inference):
  inputs:  TOKENS     INT32[1, -1]  prompt token ids
           MAX_TOKENS INT32[1]      max tokens to generate (optional,
                                    default 16, clamped to cache room)
           END_ID     INT32[1]      stop token id (optional; generation
                                    stops AFTER emitting it)
  outputs: NEXT_TOKEN INT32[1, 1]   one generated token per response
           INDEX      INT32[1, 1]   0-based position of that token
  request parameters: "chunk": int — tokens per device dispatch (default 1)
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, Iterable, List

import numpy as np

from ..server.timeline import (
    SPAN_DISPATCH,
    SPAN_FRESH_CACHE,
    SPAN_PREFILL,
    SPAN_READBACK,
    StreamMarks,
    current,
    span,
)
from .base import Model, TensorSpec
from .decoder import RungCount, TinyDecoderModel


class TinyGenerateModel(Model):
    """``tiny_lm_generate``: decoupled streaming generation over the
    decoder_lm transformer (same seed → identical weights)."""

    name = "tiny_lm_generate"
    platform = "jax"
    max_batch_size = 0
    decoupled = True

    DEFAULT_MAX_TOKENS = 16

    def __init__(self, seed: int = 0, decoder: TinyDecoderModel = None):
        super().__init__()
        # weight/step sharing by composition: generation must agree with the
        # sequence-API decoder token-for-token. Pass the zoo's decoder_lm
        # instance to share its weights and compiled step (params/step are
        # read-only at serving time; only per-request cache state is local)
        self._decoder = decoder if decoder is not None else TinyDecoderModel(seed=seed)
        self._lock = threading.Lock()
        self._chunk_fns: Dict[int, Any] = {}  # scan length K -> jitted fn
        self.steps_by_rung = RungCount()  # per-token steps; not a chunk's

    def inputs(self) -> List[TensorSpec]:
        return [
            TensorSpec("TOKENS", "INT32", [1, -1]),
            TensorSpec("MAX_TOKENS", "INT32", [1], optional=True),
            TensorSpec("END_ID", "INT32", [1], optional=True),
        ]

    def outputs(self) -> List[TensorSpec]:
        return [
            TensorSpec("NEXT_TOKEN", "INT32", [1, 1]),
            TensorSpec("INDEX", "INT32", [1, 1]),
        ]

    # -- compiled pieces -----------------------------------------------------
    def _ensure_built(self):
        self._decoder._ensure_built()
        self._decoder._ensure_warm()

    def _chunk_fn(self, k: int):
        """Jitted K-token greedy decode: the argmax→feed-back loop as a
        ``lax.scan`` carry, one device dispatch for K tokens."""
        with self._lock:
            fn = self._chunk_fns.get(k)
            if fn is not None:
                return fn

        import jax
        import jax.numpy as jnp
        from jax import lax

        step = self._decoder._step_fn

        def decode_k(params, caches, token, pos):
            # int32 up front: the scan carry pytree must keep identical
            # dtypes across iterations (weak-typed host ints would not)
            token = jnp.asarray(token, jnp.int32)
            pos = jnp.asarray(pos, jnp.int32)

            def body(carry, _):
                caches, token, pos = carry
                logits, caches = step(params, caches, token, pos)
                with jax.named_scope("greedy_argmax"):
                    nxt = jnp.argmax(logits).astype(jnp.int32)
                return (caches, nxt, pos + jnp.int32(1)), nxt

            (caches, _, _), toks = lax.scan(
                body, (caches, token, pos), None, length=k)
            return toks, caches

        fn = jax.jit(decode_k, donate_argnums=1)
        with self._lock:
            self._chunk_fns.setdefault(k, fn)
        return self._chunk_fns[k]

    # -- serving -------------------------------------------------------------
    def execute(self, inputs, parameters):
        raise ValueError(
            "tiny_lm_generate is a decoupled model; use streaming inference")

    def execute_decoupled(
        self, inputs: Dict[str, np.ndarray], parameters: Dict[str, Any]
    ) -> Iterable[Dict[str, np.ndarray]]:
        self._ensure_built()
        dec = self._decoder
        max_len = dec.MAX_LEN

        tokens = np.asarray(inputs["TOKENS"]).reshape(-1).astype(np.int64)
        if tokens.size == 0:
            raise ValueError("empty prompt")
        if np.any(tokens < 0) or np.any(tokens >= dec.VOCAB):
            raise ValueError(f"tokens out of range [0, {dec.VOCAB})")
        if tokens.size >= max_len:
            raise ValueError(f"prompt longer than max_len {max_len}")

        max_tokens = int(
            np.asarray(inputs.get("MAX_TOKENS", self.DEFAULT_MAX_TOKENS))
            .reshape(-1)[0])
        if max_tokens < 1:
            raise ValueError("MAX_TOKENS must be >= 1")
        end_id = None
        if "END_ID" in inputs:
            end_id = int(np.asarray(inputs["END_ID"]).reshape(-1)[0])
        chunk = int(parameters.get("chunk", 1))
        if chunk < 1:
            raise ValueError("chunk parameter must be >= 1")

        # room left in the static cache bounds generation length
        budget = min(max_tokens, max_len - int(tokens.size))

        # the stream's marks (server/timeline.py), on the request's timeline
        # where the core opened one. The dispatch intervals are host times:
        # a step call returns when the step is enqueued, not when it has run
        # (the step writes the cache it is given in place, so the call waits
        # for no room)
        marks = StreamMarks()
        timeline = current()
        if timeline is not None:
            timeline.stream = marks

        # prefill: the decoder's own (``TinyDecoderModel``: the single
        # compiled step over the prompt, each step waited for; a decoder with
        # a prefill program: a chunk of tokens a dispatch)
        with span(SPAN_FRESH_CACHE) as s:
            caches, pos = dec._fresh_cache(), 0
        marks.cache_ready = s.end_ns
        with span(SPAN_PREFILL) as s:
            logits, caches = dec.prefill(caches, tokens, pos, self.steps_by_rung)
            pos += int(tokens.size)
        marks.prefill_done = s.end_ns
        self.steps_by_rung.add_prefill_ns(marks.prefill_done - marks.cache_ready)

        def response(token_id: int, index: int):
            return {
                "NEXT_TOKEN": np.array([[token_id]], dtype=np.int32),
                "INDEX": np.array([[index]], dtype=np.int32),
            }

        emitted = 0

        def suspended(token_id: int):
            t_yield = time.perf_counter_ns()
            yield response(token_id, emitted)
            marks.yielded.add(time.perf_counter_ns() - t_yield, emitted)

        with span(SPAN_READBACK) as s:
            next_token = int(np.asarray(logits).argmax())
        marks.readback.add(s.ns, emitted)
        if chunk == 1:
            # per-token dispatch: one streamed response per device step —
            # honest TTFT/inter-token latency for a perf harness
            while emitted < budget:
                yield from suspended(next_token)
                emitted += 1
                if emitted >= budget or (end_id is not None
                                         and next_token == end_id):
                    return
                with span(SPAN_DISPATCH) as s:
                    logits, caches = dec.decode_step(
                        caches, next_token, pos, self.steps_by_rung)
                marks.dispatch.add(s.ns, emitted)
                pos += 1
                with span(SPAN_READBACK) as s:
                    next_token = int(np.asarray(logits).argmax())
                marks.readback.add(s.ns, emitted)
            return

        # chunked: first token came from prefill; subsequent tokens arrive
        # K at a time from one scan dispatch and stream out burst-wise
        yield from suspended(next_token)
        emitted += 1
        if end_id is not None and next_token == end_id:
            return
        while emitted < budget:
            k = min(chunk, budget - emitted, max_len - pos)
            if k <= 0:
                return
            with span(SPAN_DISPATCH) as s:
                toks, caches = self._chunk_fn(k)(
                    dec._params, caches, next_token, pos)
            marks.dispatch.add(s.ns, emitted)
            pos += k
            with span(SPAN_READBACK) as s:
                toks = np.asarray(toks).reshape(-1)
            marks.readback.add(s.ns, emitted)
            for t in toks:
                yield from suspended(int(t))
                emitted += 1
                if end_id is not None and int(t) == end_id:
                    return
            next_token = int(toks[-1])
