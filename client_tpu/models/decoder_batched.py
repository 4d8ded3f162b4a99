"""Slot-based sequence batcher: concurrent decodes share one dispatch.

tritonserver's *sequence batcher* (direct mode) assigns each live sequence
a batch slot and runs every slot's next step in a single model execution —
the client repo exposes it through the same sequence_id/start/end controls
the ``decoder_lm`` fixture serves (SURVEY §5 long-context/sequence).
``decoder_lm`` executes each sequence's step as its own device dispatch;
at S concurrent sequences that is S dispatches per token — exactly the
regime batching exists for, since an [S, ...] step costs barely more than
a [1, ...] step until S fills the MXU tile.

``decoder_lm_batched`` is the TPU-first version: per-slot KV caches live
stacked on device ([slots, heads, max_len, head_dim] per layer), a
coalescer thread gathers whatever sequence requests are in flight inside a
~2 ms window, and ONE jitted batched step (``jax.vmap`` of the decoder's
single-sequence step — the identical math, so tokens are bit-comparable)
advances them all. The step owns the stacked caches (they are donated to
it) and writes one [heads, 1, head_dim] row a layer for every active slot,
in place. Slots whose sequence has no pending request this round ride along
masked: the step writes no row of theirs (``active``, decoder.py's
``write_active_rows``) and their logits are dropped, which keeps the
executable static-shape — the same compile-once property the
single-sequence decoder has. Prompts longer than
one token naturally lockstep: each coalescer round consumes the next token
of every gathered request, so two sequences prefilling together share
every dispatch.

Weights come from a composed TinyDecoderModel (same seed ⇒ greedy tokens
match the unbatched fixture token-for-token — pinned by the tests).
"""

from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future, InvalidStateError
from concurrent.futures import TimeoutError as FuturesTimeout
from typing import Any, Dict, List

import numpy as np

from ..server.timeline import (
    SPAN_ADMIT,
    SPAN_BATCH_READBACK,
    SPAN_COLLECT,
    SPAN_ROUND_DISPATCH,
    SPAN_ROUND_PREPARE,
    SPAN_WAIT_RESULT,
    BatchMarks,
    current,
    span,
)
from .base import Model, TensorSpec
from .decoder import TinyDecoderModel


class _SeqRequest:
    __slots__ = ("seq_id", "tokens", "start", "end", "future", "marks")

    def __init__(self, seq_id, tokens, start, end):
        self.seq_id = seq_id
        self.tokens = tokens  # list of ints, consumed one per round
        self.start = start
        self.end = end
        self.future: Future = Future()
        # the request's way through the batcher (server/timeline.py); the
        # dispatch marks are host times and run ahead of the device
        self.marks = BatchMarks()

    # The caller may cancel() the future (120s timeout) at any moment —
    # set_result/set_exception on a cancelled future raises
    # InvalidStateError, and an unguarded raise inside the worker's
    # resolution loop would strand every later request in the window.
    def resolve(self, value) -> None:
        try:
            if not self.future.done():
                self.future.set_result(value)
        except InvalidStateError:
            pass  # caller cancelled between the check and the set

    def fail(self, exc: BaseException) -> None:
        try:
            if not self.future.done():
                self.future.set_exception(exc)
        except InvalidStateError:
            pass


class BatchedDecoderModel(Model):
    """``decoder_lm_batched``: the decoder_lm contract, slot-batched."""

    name = "decoder_lm_batched"
    platform = "jax"
    max_batch_size = 0
    stateful = True

    def __init__(self, seed: int = 0, slots: int = 8,
                 max_delay_s: float = 0.002, attention_impl: str = "einsum",
                 idle_ttl_s: float = 300.0):
        super().__init__()
        self._decoder = TinyDecoderModel(seed=seed,
                                         attention_impl=attention_impl)
        self.slots = int(slots)
        self._max_delay_s = max_delay_s
        # Idle-sequence reaper TTL (reference semantics:
        # max_sequence_idle_microseconds in tritonserver's sequence
        # batcher). Must exceed the 120 s caller timeout so a slot whose
        # window is merely slow is never reclaimed under an in-flight step.
        self._idle_ttl_s = float(idle_ttl_s)
        self._last_seen: Dict[Any, float] = {}
        self._lock = threading.Lock()
        self._built = False
        self._queue: "queue.Queue[_SeqRequest]" = queue.Queue(maxsize=1024)
        self._closed = False
        self._carry: List[_SeqRequest] = []
        # observability for tests/tuning: rounds executed per batch width
        self.batch_histogram: Dict[int, int] = {}
        self._rounds = 0  # rounds dispatched so far: the next round's id
        # ``(width, dispatch_ns)`` of every round, for the statistics verb's
        # batch_stats; ServerCore.add_model binds its recorder here
        self.report_batch = None
        self._worker = None  # started lazily with the first build

    def inputs(self) -> List[TensorSpec]:
        return [TensorSpec("TOKENS", "INT32", [1, -1])]

    def outputs(self) -> List[TensorSpec]:
        return [
            TensorSpec("LOGITS", "FP32", [1, self._decoder.VOCAB]),
            TensorSpec("NEXT_TOKEN", "INT32", [1, 1]),
        ]

    # -- compiled pieces -----------------------------------------------------
    def _ensure_built(self):
        with self._lock:
            if self._built:
                return
            self._decoder._ensure_built()
            import jax

            dec = self._decoder
            S = self.slots
            # (params, caches, token, pos, active) per sequence; a slot
            # that is not ``active`` writes no cache row, inside the step
            vstep = jax.vmap(dec._step_fn, in_axes=(None, 0, 0, 0, 0))

            def batched_step(params, caches, tokens, pos, active):
                return vstep(params, caches, tokens, pos, active)

            self._batched_step = jax.jit(batched_step, donate_argnums=1)
            self._caches = self._fresh_caches()
            # positions live HOST-side (0 on start, +1 per active token —
            # fully derivable without a device readback) and ship to the
            # device each round alongside the token vector; carrying them
            # on-device would cost a blocking readback per request in
            # _run_window, the exact per-dispatch cost the batcher
            # amortizes
            self._pos = np.zeros((S,), np.int32)
            self._slot_of: Dict[Any, int] = {}
            self._free = list(range(S))
            self._worker = threading.Thread(
                target=self._run, name="sequence-batcher", daemon=True)
            self._worker.start()
            self._built = True

    def _fresh_caches(self):
        """Every slot's cache, stacked: [slots, heads, max_len, head_dim] a
        layer, zeros."""
        import jax.numpy as jnp

        dec = self._decoder
        shape = (self.slots, dec.HEADS, dec.MAX_LEN, dec.D_MODEL // dec.HEADS)
        return [{"k": jnp.zeros(shape, jnp.bfloat16),
                 "v": jnp.zeros(shape, jnp.bfloat16)}
                for _ in range(dec.LAYERS)]

    # -- serving (caller side) ----------------------------------------------
    def execute(self, inputs: Dict[str, np.ndarray],
                parameters: Dict[str, Any]):
        self._ensure_built()
        seq_id = parameters.get("sequence_id", 0)
        if not seq_id:
            raise ValueError("decoder_lm_batched requires a sequence_id")
        start = bool(parameters.get("sequence_start", False))
        end = bool(parameters.get("sequence_end", False))
        tokens = np.asarray(inputs["TOKENS"]).reshape(-1).astype(np.int64)
        if tokens.size == 0:
            raise ValueError("empty prompt")
        if np.any(tokens < 0) or np.any(tokens >= self._decoder.VOCAB):
            raise ValueError(f"tokens out of range [0, {self._decoder.VOCAB})")
        if not start and len(tokens) != 1:
            raise ValueError("continuation requests carry exactly one token")
        if self._closed:
            raise ValueError("model is shutting down")
        req = _SeqRequest(seq_id, [int(t) for t in tokens], start, end)
        timeline = current()
        if timeline is not None:
            timeline.batch = req.marks
        req.marks.enqueued = time.perf_counter_ns()
        try:
            # bounded wait: with a wedged worker the queue fills, and an
            # unbounded put() would hang callers before the future timeout
            # below ever ran — overload must surface as a typed 503
            self._queue.put(req, timeout=30)
        except queue.Full:
            from ..server.core import InferError

            raise InferError(
                "sequence batcher queue full (worker stalled?)", 503
            ) from None
        if self._closed:
            # unload() raced us: the worker may already be past its
            # sentinel, leaving this request stranded behind it — fail it
            # here (the worker wins harmlessly if it got there first)
            req.fail(ValueError("model is shutting down"))
        try:
            with span(SPAN_WAIT_RESULT):
                logits = req.future.result(timeout=120)
        except FuturesTimeout:
            # the worker is wedged or the dispatch is pathologically slow;
            # the caller is gone either way, so surface a gateway-timeout
            # rather than an untyped 500. The slot is NOT freed here — the
            # window may still be in flight and a new sequence claiming the
            # slot would share its cache; the window's own error path (or
            # sequence_end) reclaims it.
            req.future.cancel()
            from ..server.core import InferError

            raise InferError(
                "batched decode timed out after 120s", 504) from None
        with span(SPAN_BATCH_READBACK) as readback:
            logits_np = np.asarray(logits, dtype=np.float32).reshape(
                1, self._decoder.VOCAB)
        req.marks.on_host = readback.end_ns
        return {
            "LOGITS": logits_np,
            "NEXT_TOKEN": np.array([[int(logits_np.argmax())]], dtype=np.int32),
        }

    def live_sequences(self) -> int:
        self._ensure_built()
        with self._lock:
            return len(self._slot_of)

    def unload(self) -> None:
        self._closed = True
        self._queue.put(None)
        if self._worker is not None:
            self._worker.join(timeout=10)
        # fail anything that slipped in behind the sentinel (the worker has
        # exited; nothing else will ever resolve those futures)
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            if req is not None:
                req.fail(ValueError("model is shutting down"))
        super().unload()

    # -- coalescer worker ----------------------------------------------------
    def _collect(self) -> List[_SeqRequest]:
        """One window: at most one request per sequence (two requests on a
        sequence must observe each other's cache updates, so the second
        waits for the next round — the reference sequence batcher
        serializes per CORRID the same way)."""
        window, seen, still_carried = [], set(), []

        def take(req: _SeqRequest) -> None:
            window.append(req)
            seen.add(req.seq_id)
            req.marks.collected = time.perf_counter_ns()

        for req in self._carry:
            if req.seq_id in seen:
                still_carried.append(req)  # FIFO within a sequence
            else:
                take(req)
        self._carry = still_carried
        if not window:
            first = self._queue.get()
            if first is None:
                return []
            take(first)
        deadline = time.monotonic() + self._max_delay_s
        while len(window) < self.slots:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            try:
                nxt = self._queue.get(timeout=remaining)
            except queue.Empty:
                break
            if nxt is None:
                self._queue.put(None)
                break
            if nxt.seq_id in seen:
                # serialize per CORRID but KEEP collecting: a fast client's
                # back-to-back request must not shut other sequences out of
                # this round
                self._carry.append(nxt)
                continue
            take(nxt)
        return window

    def _admit(self, req: _SeqRequest) -> int:
        """Resolve the request to a slot (allocating on sequence_start)."""
        with self._lock:
            if req.start:
                if req.seq_id in self._slot_of:
                    slot = self._slot_of[req.seq_id]  # restart in place
                elif self._free:
                    slot = self._free.pop()
                    self._slot_of[req.seq_id] = slot
                else:
                    raise ValueError(
                        f"no free sequence slot (capacity {self.slots}); "
                        "end a sequence first")
                self._last_seen[req.seq_id] = time.monotonic()
                return slot
            slot = self._slot_of.get(req.seq_id)
            if slot is None:
                raise ValueError(
                    f"sequence {req.seq_id} has no live state "
                    "(missing sequence_start?)")
            self._last_seen[req.seq_id] = time.monotonic()
            return slot

    def _reap_idle(self, exclude) -> None:
        """Free slots whose sequence has been idle past the TTL.

        Covers the 120 s-timeout abandonment path: a client that times out
        mid-sequence and walks away would otherwise hold one of ``slots``
        forever (only a same-id restart or unload reclaimed it). Sequences
        with a request in the current window or carried for the next round
        are excluded — they are active by definition.
        """
        now = time.monotonic()
        with self._lock:
            for seq_id, last in list(self._last_seen.items()):
                if seq_id in exclude:
                    continue
                if now - last > self._idle_ttl_s:
                    self._free_slot(seq_id)

    def _run(self) -> None:
        while True:
            with span(SPAN_COLLECT):
                window = self._collect()
            if not window:
                return
            try:
                self._run_window(window)
            except Exception as e:  # the worker thread must NEVER die — a
                # dead coalescer wedges every future request on the model
                for req in window:
                    req.fail(e)

    def _run_window(self, window: List[_SeqRequest]) -> None:
        import jax
        import jax.numpy as jnp

        with span(SPAN_ADMIT):
            active_reqs = self._admit_window(window)

        # lockstep rounds: each round consumes ONE token from every
        # request that still has tokens left (prompts prefill together)
        dec = self._decoder
        last_logits: Dict[int, Any] = {}
        first_round = self._rounds
        try:
            while any(req.tokens for req, _ in active_reqs):
                with span(SPAN_ROUND_PREPARE):
                    tokens = np.zeros((self.slots,), np.int32)
                    active = np.zeros((self.slots,), bool)
                    for req, slot in active_reqs:
                        if req.tokens:
                            tokens[slot] = req.tokens.pop(0)
                            active[slot] = True
                    # snapshot pos: device_put may alias the host buffer
                    # (CPU zero-copy) or read it after dispatch returns
                    # (ImmutableUntilTransferCompletes), so handing JAX
                    # self._pos itself and then mutating it in place races
                    # the in-flight step — the round-3 nondeterminism
                    on_device = (jnp.asarray(tokens),
                                 jnp.asarray(self._pos.copy()),
                                 jnp.asarray(active))
                with span(SPAN_ROUND_DISPATCH) as dispatch:
                    logits, self._caches = self._batched_step(
                        dec._params, self._caches, *on_device)
                self._pos[active] += 1
                width = int(active.sum())
                self.batch_histogram[width] = (
                    self.batch_histogram.get(width, 0) + 1)
                if self.report_batch is not None:
                    self.report_batch(width, dispatch.ns)
                for req, slot in active_reqs:
                    if active[slot]:
                        last_logits[slot] = logits[slot]
                        req.marks.round(dispatch, self._rounds, width,
                                        last=not req.tokens)
                self._rounds += 1
        except Exception as e:  # a failed dispatch must not strand callers
            for req, _ in active_reqs:
                req.fail(e)
            with self._lock:
                # a failed step ends the sequence regardless of req.end:
                # the client has no valid continuation state (the cache may
                # be partially updated), and keeping the slot would leak
                # capacity one failed window at a time
                ended = [req.seq_id for req, _ in active_reqs]
                if any(leaf.is_deleted() for leaf in
                       jax.tree_util.tree_leaves(self._caches)):
                    # the step had taken every slot's cache with it: all
                    # live sequences end, and the next window starts clean
                    self._caches = self._fresh_caches()
                    ended = list(self._slot_of)
                for seq_id in ended:
                    self._free_slot(seq_id)
            return

        for req, slot in active_reqs:
            if req.end:
                with self._lock:
                    self._free_slot(req.seq_id)
            req.marks.rounds_window = self._rounds - first_round
            if slot in last_logits:
                req.marks.resolved = time.perf_counter_ns()
                req.resolve(last_logits[slot])
            else:
                req.fail(ValueError("request executed no decode step"))

    def _admit_window(self, window: List[_SeqRequest]) -> List[tuple]:
        """``(request, slot)`` of the window's requests that have a slot and
        room in its cache; the others are failed here."""
        # reap BEFORE admitting so a full house of abandoned sequences
        # frees up for this window's sequence_start requests
        self._reap_idle(
            exclude={req.seq_id for req in window}
            | {r.seq_id for r in self._carry})

        active_reqs: List[tuple] = []
        for req in window:
            try:
                slot = self._admit(req)
            except Exception as e:
                req.fail(e)
                continue
            if req.start:
                # zero pos; cache rows are fully overwritten as the
                # prompt streams in, and masked reads never see slots
                # beyond pos, so stale cache content is harmless
                self._pos[slot] = 0
            pos_here = int(self._pos[slot])
            if pos_here + len(req.tokens) > self._decoder.MAX_LEN:
                req.fail(ValueError(
                    f"sequence longer than max_len {self._decoder.MAX_LEN}"))
                with self._lock:
                    self._free_slot(req.seq_id)
                continue
            active_reqs.append((req, slot))
        return active_reqs

    def _free_slot(self, seq_id) -> None:
        slot = self._slot_of.pop(seq_id, None)
        self._last_seen.pop(seq_id, None)
        if slot is not None:
            self._free.append(slot)
