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

Two paths, chosen by what the composed decoder provides and sharing no
scheduling logic:

**The streams share a round** where the decoder offers the round program
and the stacked caches (``decoder._round_fn``: ``TinyDecoderModel`` and its
subclasses with ``attention_impl="einsum"``, and
``WindowSummaryDecoderModel``, which also offers a slot prefill: its prompts
go a chunk a dispatch into their slots between the rounds, where the others'
ride the rounds a token at a time). ``stream_rounds.py`` has the mechanism;
in short:
- a table of ``slots`` caches is reserved once at build (default
  ``DEFAULT_SLOTS``), donated to every round and written in place. A stream
  takes the lowest free slot at admission, gives it back when its budget is
  spent, its ``END_ID`` is emitted, its client cancels or its generator is
  closed, and waits, first come first seated, where none is free; no stream
  is refused for want of a slot;
- one worker dispatches the rounds: a round consumes the next token of
  every seated stream, a prompt token for a stream still in its prompt, the
  token the round before chose for a decoding one. That token is chosen on
  the device (greedy argmax, int32) and fed back there; the host sees one
  int32 a slot a round and supplies prompt tokens alone. A stream's
  ``execute_decoupled`` validates, hands over its prompt, budget and
  ``END_ID`` and then only waits for its tokens and yields them;
- a round's program has a rung (the live prefix of the positions:
  decoder.py's ladder), and is the decoder's table step, the slot
  batcher's too, between the token's select and the argmax. Its attention
  reads by decoder.py's one rule for a table (``read_round``): the caches
  of the occupied slots alone, ``decoder.SLOTS_A_TURN`` slots a turn of a
  loop that ends after the highest occupied one, or, where heads are
  narrower than the chip's lanes, every slot's where it lies
  (``decoder.slots_read`` counts which): one program a rung, each compiled
  in ``_ensure_built``, before the first round;
- ``chunk`` > 1 keeps its wire meaning on this path: after the first token
  the tokens are delivered K at a time, off the same rounds.

**A stream steps its own sequence** where the decoder has no round
(``RoutedDecoderModel``, whose experts a ``vmap`` over slots would gather a
slot and whose prefill is a chunk program; ``attention_impl="pallas"``,
whose kernel takes every slot's whole cache; a subclass that jits its own
step): a cache a session, the decoder's own ``prefill``, one dispatch a
token, the logits' argmax on the host; ``chunk`` > 1 runs K steps INSIDE
XLA via ``lax.scan`` (``decode_k``: the greedy argmax→feed-back loop is a
scan carry, so K tokens cost one device dispatch; one program a K, compiled
on demand, over the whole cache).

On either path one compiled program a rung serves prefill AND every
generated token: no shape-polymorphic retraces, and the host only ever
sees the emitted token ids.

Wire contract (decoupled — use streaming inference):
  inputs:  TOKENS     INT32[1, -1]  prompt token ids
           MAX_TOKENS INT32[1]      max tokens to generate (optional,
                                    default 16, clamped to cache room)
           END_ID     INT32[1]      stop token id (optional; generation
                                    stops AFTER emitting it)
  outputs: NEXT_TOKEN INT32[1, 1]   one generated token per response
           INDEX      INT32[1, 1]   0-based position of that token
  request parameters: "chunk": int — tokens per burst (default 1)
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Dict, Iterable, List, Optional

import numpy as np

from ..server.timeline import (
    SPAN_DISPATCH,
    SPAN_FRESH_CACHE,
    SPAN_PREFILL,
    SPAN_READBACK,
    Phases,
    StreamMarks,
    current,
    span,
)
from .base import Model, TensorSpec
from .decoder import RungCount, TinyDecoderModel
from .stream_rounds import Stream, StreamRounds


def _handed(stream: Stream, marks: StreamMarks):
    """The stream's tokens as its own thread takes them off its queue, each
    marked with how long it lay between the end of the worker's read-back
    and here."""
    for token, read_back_ns in stream.tokens():
        marks.handoff.add(time.perf_counter_ns() - read_back_ns)
        yield token


def _suspended(marks: StreamMarks, token_id: int, index: int):
    """Yield the token's response, and mark how long the stream stayed
    suspended there."""
    t_yield = time.perf_counter_ns()
    yield {"NEXT_TOKEN": np.array([[token_id]], dtype=np.int32),
           "INDEX": np.array([[index]], dtype=np.int32)}
    marks.yielded.add(time.perf_counter_ns() - t_yield)


class TinyGenerateModel(Model):
    """``tiny_lm_generate``: decoupled streaming generation over the
    decoder_lm transformer (same seed → identical weights)."""

    name = "tiny_lm_generate"
    platform = "jax"
    max_batch_size = 0
    decoupled = True

    DEFAULT_MAX_TOKENS = 16
    # the reserved caches of the round path: as many streams as one chip's
    # users in the largest stream cell (16 x 402.7 MB + 2.84 GB of weights
    # for cerebras-gpt-1.3b, 16 x 188.7 MB + 1.68 GB for gpt2-large)
    DEFAULT_SLOTS = 16

    def __init__(self, seed: int = 0, decoder: TinyDecoderModel = None,
                 slots: int = DEFAULT_SLOTS):
        super().__init__()
        # weight/step sharing by composition: generation must agree with the
        # sequence-API decoder token-for-token. Pass the zoo's decoder_lm
        # instance to share its weights and compiled step (params/step are
        # read-only at serving time; only per-request cache state is local)
        self._decoder = decoder if decoder is not None else TinyDecoderModel(seed=seed)
        self._lock = threading.Lock()
        self._chunk_fns: Dict[int, Any] = {}  # scan length K -> jitted fn
        # per-token steps, not a chunk's; a round counts once, at its rung
        self.steps_by_rung = RungCount()
        # the round path's (stream_rounds.py), empty on the other: the table
        # and its worker, made by ``_ensure_built``; rounds by the streams
        # they carried and by the slots their attention read; streams that
        # found no free slot at their admission; and ``(members,
        # dispatch_ns)`` of every round for the statistics verb's
        # batch_stats (ServerCore.add_model binds its recorder here); and the
        # worker's turns by phase (server/timeline.py: ``Phases``)
        self.slots = int(slots)
        self._rounds: Optional[StreamRounds] = None
        self.batch_histogram: Dict[int, int] = {}
        self.rounds_by_width: Dict[int, int] = {}
        self.slot_waits = 0
        self.report_batch = None
        self.phases = Phases()

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
        """Every program of the path the decoder provides, compiled: the
        round's at every rung, and none of a single sequence's, or else
        every rung of the decoder's own step."""
        self._decoder._ensure_built()
        if self._decoder._round_fn is None:
            self._decoder._ensure_warm()
            return
        with self._lock:
            if self._rounds is None:
                self._rounds = StreamRounds(self)

    def unload(self) -> None:
        """The worker joined, its streams failed, the table let go of; a
        later ``_ensure_built`` makes them anew."""
        with self._lock:
            rounds, self._rounds = self._rounds, None
        if rounds is not None:
            rounds.close()
        super().unload()

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
        # where the core opened one
        marks = StreamMarks()
        timeline = current()
        if timeline is not None:
            timeline.stream = marks
        rounds = self._rounds
        if rounds is not None:
            yield from self._ride(rounds, [int(t) for t in tokens], budget,
                                  end_id, chunk, marks)
        else:
            yield from self._step_alone(tokens, budget, end_id, chunk, marks)

    def _ride(self, rounds: StreamRounds, prompt: List[int], budget: int,
              end_id, chunk: int, marks: StreamMarks):
        """The round path: the stream waits for its tokens and yields them,
        the first alone and the rest ``chunk`` at a time. The marks are the
        worker's: ``cache_ready`` the slot taken, ``prefill_done`` the
        return of the dispatch of the round that consumed the last prompt
        token, a token's ``dispatch`` and ``readback`` those of the round
        that carried it; its ``handoff`` is this thread's."""
        stream = rounds.open(prompt, budget, end_id, marks)
        try:
            tokens = _handed(stream, marks)
            with span(SPAN_PREFILL):  # admission, its prompt's rounds, the
                burst = list(itertools.islice(tokens, 1))  # last's read-back
            emitted = 0
            while burst:
                for token in burst:
                    yield from _suspended(marks, token, emitted)
                    emitted += 1
                burst = list(itertools.islice(tokens, chunk))
        finally:
            stream.gone = True  # ended, cancelled or closed: the slot is free

    def _step_alone(self, tokens, budget: int, end_id, chunk: int,
                    marks: StreamMarks):
        """The per-stream path: a cache of its own and a dispatch a token.
        The dispatch intervals are host times: a step call returns when the
        step is enqueued, not when it has run (the step writes the cache it
        is given in place, so the call waits for no room)."""
        dec = self._decoder
        max_len = dec.MAX_LEN
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

        emitted = 0
        with span(SPAN_READBACK) as s:
            next_token = int(np.asarray(logits).argmax())
        marks.readback.add(s.ns)
        if chunk == 1:
            # per-token dispatch: one streamed response per device step —
            # honest TTFT/inter-token latency for a perf harness
            while emitted < budget:
                yield from _suspended(marks, next_token, emitted)
                emitted += 1
                if emitted >= budget or (end_id is not None
                                         and next_token == end_id):
                    return
                with span(SPAN_DISPATCH) as s:
                    logits, caches = dec.decode_step(
                        caches, next_token, pos, self.steps_by_rung)
                marks.dispatch.add(s.ns)
                pos += 1
                with span(SPAN_READBACK) as s:
                    next_token = int(np.asarray(logits).argmax())
                marks.readback.add(s.ns)
            return

        # chunked: first token came from prefill; subsequent tokens arrive
        # K at a time from one scan dispatch and stream out burst-wise
        yield from _suspended(marks, next_token, emitted)
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
            marks.dispatch.add(s.ns)
            pos += k
            with span(SPAN_READBACK) as s:
                toks = np.asarray(toks).reshape(-1)
            marks.readback.add(s.ns)
            for t in toks:
                yield from _suspended(marks, int(t), emitted)
                emitted += 1
                if end_id is not None and int(t) == end_id:
                    return
            next_token = int(toks[-1])
