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
stacked on device (decoder.py's ``_fresh_table``: [slots, heads / P,
max_len, P x head_dim] per layer, P heads side by side a row so that a row
fills the chip's lanes), and ONE jitted step of the whole table advances
every sequence that has a request in progress: decoder.py's table step, the
one the stream model's round is built on (``_table_fn``). The decoder owns
that program and this module the schedule. The step owns the stacked caches
(they are donated to it) and writes one position's row a layer for every
active slot, in place.

**The unit of scheduling is the round**, one dispatch of that step. The
worker keeps one table of the requests in progress, one a sequence. Before
each dispatch it takes whatever has arrived into the table
(``sequence_start`` takes a slot there; a second request of a sequence
that has one in the table is carried, FIFO, until that one has left), and
the round consumes the next token of every request of the table: a
one-token request is in the table for one round, a prompt for one round a
token, and two prompts in progress share every dispatch. A request leaves
the table with the round that consumes its last token. A round's logits
come to the host once, all rows in one transfer, and every request it
answers gets its row as a view; ``sequence_end`` frees its slot there. The
answers are handed out as soon as the next round, if there is one to send,
has been dispatched: a request waits for no other request's rounds to run,
only for that one dispatch call, which the woken callers would otherwise
slow while the device stands idle. At most ``ROUNDS_IN_FLIGHT`` rounds are
dispatched and not yet read back, so a request waits for at most that many
rounds before its own, however long a prompt beside it is; while the device
works, the round in flight is the gather. A slot whose sequence has no
request in the table is masked: the step writes no row of its
(``active``, decoder.py's ``write_table_rows``: a masked slot is not
touched) and its logits row goes unread, which keeps the executable
static-shape — the same compile-once property the single-sequence decoder
has. Once a rung, that is: a round's attention reads the prefix of the
caches that covers the furthest of the round's members (decoder.py's
ladder; a slot that rides along inactive may hold any older position, its
row is not read), and the worker compiles every rung's program before it
takes its first request. Which slots and positions the attention reads is
decoder.py's one rule for a table (``read_round``).

Weights come from a composed TinyDecoderModel (same seed ⇒ greedy tokens
match the unbatched fixture token-for-token — pinned by the tests).
"""

from __future__ import annotations

import collections
import queue
import threading
import time
from concurrent.futures import Future, InvalidStateError
from concurrent.futures import TimeoutError as FuturesTimeout
from typing import Any, Deque, Dict, List, Tuple

import numpy as np

from ..server.timeline import (
    SPAN_ADMIT,
    SPAN_BATCH_DEVICE_WAIT,
    SPAN_BATCH_HAND_OUT,
    SPAN_BATCH_READBACK,
    SPAN_BATCH_RECORD,
    SPAN_BATCH_TURN,
    SPAN_BATCH_WAIT_WORK,
    SPAN_COLLECT,
    SPAN_ROUND_DISPATCH,
    SPAN_ROUND_PREPARE,
    SPAN_WAIT_RESULT,
    BatchMarks,
    Phases,
    current,
    span,
)
from .base import Model, TensorSpec
from .decoder import RungCount, TinyDecoderModel

# Rounds dispatched and not yet read back, at most. It is the fairness rule:
# a worker that ran ahead of the device would enqueue a prompt's every round
# at once, and each decode request would join behind them all. Chosen on the
# chip (PERF.md section 6, PR 29): with 1 the device waits for the host's
# turn between two rounds; with 2 it never waits, and every request waits for
# the round in flight and the one dispatched behind it, which costs more.
ROUNDS_IN_FLIGHT = 1


class _SeqRequest:
    __slots__ = ("seq_id", "tokens", "start", "end", "future", "marks",
                 "rounds_before")

    def __init__(self, seq_id, tokens, start, end, rounds_before):
        self.seq_id = seq_id
        self.tokens = tokens  # list of ints, consumed one per round
        self.start = start
        self.end = end
        self.rounds_before = rounds_before  # rounds dispatched when it came
        self.future: Future = Future()
        # the request's way through the batcher (server/timeline.py); the
        # dispatch marks are host times and run ahead of the device
        self.marks = BatchMarks()

    # The caller may cancel() the future (120s timeout) at any moment —
    # set_result/set_exception on a cancelled future raises
    # InvalidStateError, and an unguarded raise inside the worker's
    # resolution loop would strand every later request of the round.
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
                 attention_impl: str = "einsum", idle_ttl_s: float = 300.0):
        super().__init__()
        self._decoder = TinyDecoderModel(seed=seed,
                                         attention_impl=attention_impl)
        self.slots = int(slots)
        # Idle-sequence reaper TTL (reference semantics:
        # max_sequence_idle_microseconds in tritonserver's sequence
        # batcher). Must exceed the 120 s caller timeout so a slot whose
        # round is merely slow is never reclaimed under an in-flight step.
        self._idle_ttl_s = float(idle_ttl_s)
        self._last_seen: Dict[Any, float] = {}
        self._lock = threading.Lock()
        self._built = False
        self._queue: "queue.Queue[_SeqRequest]" = queue.Queue(maxsize=1024)
        self._closed = False
        self._taking = True  # the worker takes requests off the queue
        # the worker's own: the requests in progress, one a sequence, with
        # their slots; later requests of those sequences, FIFO; the rounds
        # dispatched and not yet read back, oldest first: ``(round id,
        # logits on the device, the requests it answers)``; and the answers
        # read back and not yet handed out: ``(request, row, round id)``
        self._table: Dict[Any, Tuple[_SeqRequest, int]] = {}
        self._carry: List[_SeqRequest] = []
        self._in_flight: Deque[
            Tuple[int, Any, List[Tuple[_SeqRequest, int]]]] = collections.deque()
        self._answers: List[Tuple[_SeqRequest, np.ndarray, int]] = []
        # observability for tests/tuning: rounds executed per batch width,
        # and per rung of the decoder's ladder
        self.batch_histogram: Dict[int, int] = {}
        self.steps_by_rung = RungCount()
        # the worker's turns by phase (server/timeline.py: ``Phases``), for
        # the registry's ``round_phase`` series
        self.phases = Phases()
        self._warm = False  # every rung's program is compiled
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
            S = self.slots
            # the decoder's table step, traced as ``jit_batched_step``:
            # (params, caches, tokens, pos, active) a slot each, caches
            # donated, ``live`` the round's one compile-time length; a slot
            # that is not ``active`` has no row written
            self._batched_step = self._decoder._table_fn
            self._caches = self._fresh_caches()
            # positions live HOST-side (0 on start, +1 per active token —
            # fully derivable without a device readback) and ship to the
            # device each round alongside the token vector; carrying them
            # on-device would cost a blocking readback per request, the
            # exact per-dispatch cost the batcher amortizes
            self._pos = np.zeros((S,), np.int32)
            # the id of the round that carried each slot's last token, for
            # a continuation request's ``stride_rounds``
            self._last_round = [0] * S
            self._slot_of: Dict[Any, int] = {}
            self._free = list(range(S))
            self._worker = threading.Thread(
                target=self._run, name="sequence-batcher", daemon=True)
            self._worker.start()
            self._built = True

    def _fresh_caches(self):
        """Every slot's cache, stacked, zeros."""
        return self._decoder._fresh_table(self.slots)

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
        req = _SeqRequest(seq_id, [int(t) for t in tokens], start, end,
                          self._rounds)
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
                # the request's row of its last round's logits, on the host
                row = req.future.result(timeout=120)
        except FuturesTimeout:
            # the worker is wedged or the dispatch is pathologically slow;
            # the caller is gone either way, so surface a gateway-timeout
            # rather than an untyped 500. The slot is NOT freed here — the
            # request may still be in the table and a new sequence claiming
            # the slot would share its cache; the round's own error path
            # (or sequence_end) reclaims it.
            req.future.cancel()
            from ..server.core import InferError

            raise InferError(
                "batched decode timed out after 120s", 504) from None
        logits_np = row.reshape(1, self._decoder.VOCAB)
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

    # -- the worker: one turn a round ----------------------------------------
    def _run(self) -> None:
        try:
            # while whoever built the model starts its frontend
            self._ensure_warm()
        except Exception:
            pass  # the first round tries again, and fails its requests
        # until unload's sentinel comes off the queue; then what is begun
        # is ended
        while (self._taking or self._table or self._carry or self._in_flight
               or self._answers):
            try:
                self._turn()
            except Exception as e:  # the worker thread must NEVER die — a
                # dead coalescer wedges every future request on the model
                self._abandon(e)

    def _turn(self) -> None:
        """Take what has arrived into the table, dispatch one round over the
        table, hand out the answers of the round read back before it, and
        read back as many rounds as the bound asks for.

        The dispatch comes before the answers because every answer wakes a
        caller's thread, and those threads would take the interpreter from
        the dispatch while the device stands idle: answers first read a gap
        of 42.6 ms on the chip, dispatch first 34.6 (PERF.md section 6,
        PR 29). An answer waits for that one dispatch call, never for a
        round to run."""
        phases = self.phases
        with span(SPAN_BATCH_TURN):
            waited = []
            if self._taking and not (self._carry or self._table
                                     or self._answers):
                # nothing to run and no answer to hand out (what is carried
                # joins the next round): wait for a request
                with span(SPAN_BATCH_WAIT_WORK, into=phases):
                    waited.append(self._queue.get())
            with span(SPAN_COLLECT, into=phases):
                arrivals = self._arrivals(waited)
            if arrivals:
                with span(SPAN_ADMIT, into=phases):
                    self._admit_arrivals(arrivals)
            if self._table:
                self._dispatch_round()
            with span(SPAN_BATCH_HAND_OUT, into=phases):
                self._answer()
            # with nothing to dispatch there is nothing to wait with either
            while self._in_flight and (len(self._in_flight) >= ROUNDS_IN_FLIGHT
                                       or not self._table):
                self._read_back()

    def _arrivals(self, waited: List[Any]) -> List[_SeqRequest]:
        """The requests that join the next round: at most one a sequence,
        and none of a sequence that has a request in the table (two requests
        on a sequence must observe each other's cache updates, so the second
        waits its turn — the reference sequence batcher serializes per CORRID
        the same way). The carried requests come first, then what the turn
        ``waited`` for, if it did, and everything on the queue."""
        arrivals: List[_SeqRequest] = []
        carried, self._carry = self._carry, []
        busy = set(self._table)

        def sort(req) -> None:
            if req is None:  # unload's sentinel
                self._taking = False
            elif req.seq_id in busy:
                # serialize per CORRID but KEEP taking: a fast client's
                # back-to-back request must not shut other sequences out of
                # this round
                self._carry.append(req)  # FIFO within a sequence
            else:
                busy.add(req.seq_id)
                arrivals.append(req)

        for req in carried + waited:
            sort(req)
        while self._taking:
            try:
                sort(self._queue.get_nowait())
            except queue.Empty:
                break
        return arrivals

    def _admit_arrivals(self, arrivals: List[_SeqRequest]) -> None:
        """Into the table with its slot goes each arrival that has one and
        room in its cache; the others are failed here."""
        # reap BEFORE admitting so a full house of abandoned sequences
        # frees up for these sequence_start requests
        self._reap_idle(exclude={req.seq_id for req in arrivals})
        for req in arrivals:
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
            if int(self._pos[slot]) + len(req.tokens) > self._decoder.MAX_LEN:
                req.fail(ValueError(
                    f"sequence longer than max_len {self._decoder.MAX_LEN}"))
                with self._lock:
                    self._free_slot(req.seq_id)
                continue
            req.marks.collected = time.perf_counter_ns()
            self._table[req.seq_id] = (req, slot)

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
        with a request arriving, in the table or carried are excluded —
        they are active by definition.
        """
        busy = set(exclude) | set(self._table)
        busy.update(req.seq_id for req in self._carry)
        now = time.monotonic()
        with self._lock:
            for seq_id, last in list(self._last_seen.items()):
                if seq_id in busy:
                    continue
                if now - last > self._idle_ttl_s:
                    self._free_slot(seq_id)

    def _step_at(self, tokens, pos, active, live: int):
        """The jitted step over every slot at one rung; the whole length is
        the step's own default. It owns the stacked caches."""
        params = self._decoder._params
        if live == self._decoder.MAX_LEN:
            return self._batched_step(params, self._caches, tokens, pos, active)
        return self._batched_step(
            params, self._caches, tokens, pos, active, live=live)

    def _ensure_warm(self) -> None:
        """Every rung's program compiled before the first round, by one
        real step a rung over the slots' own caches with no slot active (no
        row is written, the caches come back as they were): a round that
        crosses a rung in the middle of serving finds its program there. A
        ladder of one rung has nothing to build ahead. The worker's alone."""
        if self._warm:
            return
        rungs = self._decoder._rungs
        if len(rungs) > 1:
            zeros = np.zeros((self.slots,), np.int32)
            nobody = np.zeros((self.slots,), bool)
            for live in rungs:
                _, self._caches = self._step_at(zeros, zeros, nobody, live)
        self._warm = True

    def _dispatch_round(self) -> None:
        """One round: the next token of every request of the table, in one
        dispatch. A request whose tokens are spent leaves the table for the
        round's record, to be answered when the round is read back."""
        phases = self.phases
        with span(SPAN_ROUND_PREPARE, into=phases):
            members = list(self._table.values())
            tokens = np.zeros((self.slots,), np.int32)
            active = np.zeros((self.slots,), bool)
            for req, slot in members:
                tokens[slot] = req.tokens.pop(0)
                active[slot] = True
            # snapshot pos: the step's device_put may alias the host buffer
            # (CPU zero-copy) or read it after dispatch returns
            # (ImmutableUntilTransferCompletes), so handing JAX
            # self._pos itself and then mutating it in place races
            # the in-flight step — the round-3 nondeterminism. The three
            # go to the step as they are: it puts them on the device
            # inside its one call, where three calls of jnp.asarray
            # each let go of the interpreter on the way
            pos = self._pos.copy()
            # the shortest rung that covers the furthest member
            live = self._decoder.rung_for(int(pos[active].max()) + 1)
        try:
            self._ensure_warm()
            with span(SPAN_ROUND_DISPATCH, into=phases) as dispatch:
                logits, self._caches = self._step_at(tokens, pos, active, live)
        except Exception as e:  # a failed dispatch must not strand callers
            self._table.clear()
            self._fail_round(e, members)
            return
        with span(SPAN_BATCH_RECORD, into=phases):
            self._pos[active] += 1
            width = len(members)
            self.batch_histogram[width] = self.batch_histogram.get(width, 0) + 1
            self.steps_by_rung.add(live)
            self._decoder.count_rows_written(self.steps_by_rung, width)
            if self.report_batch is not None:
                self.report_batch(width, dispatch.ns)
            answered = []
            for req, slot in members:
                if not req.marks.rounds_own:
                    req.marks.rounds_waited = self._rounds - req.rounds_before
                    if not req.start:
                        req.marks.stride_rounds = (
                            self._rounds - self._last_round[slot])
                self._last_round[slot] = self._rounds
                req.marks.round(dispatch, self._rounds, width,
                                last=not req.tokens)
                if not req.tokens:
                    del self._table[req.seq_id]
                    answered.append((req, slot))
            if answered:
                # the transfer begins when the step ends, with no host thread
                # having to be scheduled in between
                logits.copy_to_host_async()
            self._in_flight.append((self._rounds, logits, answered))
            self._rounds += 1

    def _read_back(self) -> None:
        """The oldest round in flight: wait for it (``device_wait``) and then
        bring its logits to the host in one transfer (``readback``: the
        transfer and its laying out alone); each request that ended with it
        has its row, a view, from here on, and ``sequence_end`` gives its
        slot up here, so that the next round's arrivals find it. A round
        that answers nobody (prompts midway) is waited for all the same,
        which is what holds the bound, and has nothing to transfer."""
        phases = self.phases
        round_id, logits, answered = self._in_flight[0]
        with span(SPAN_BATCH_DEVICE_WAIT, into=phases):
            logits.block_until_ready()
        if answered:
            with span(SPAN_BATCH_READBACK, into=phases) as readback:
                rows = np.asarray(logits)
        self._in_flight.popleft()
        if not answered:
            return
        with span(SPAN_BATCH_RECORD, into=phases):
            for req, slot in answered:
                if req.end:
                    with self._lock:
                        self._free_slot(req.seq_id)
                req.marks.on_host = readback.end_ns
                self._answers.append((req, rows[slot], round_id))

    def _answer(self) -> None:
        """Hand out the answers that are on the host."""
        answers, self._answers = self._answers, []
        for req, row, round_id in answers:
            req.marks.rounds_held = self._rounds - round_id - 1
            req.marks.resolved = time.perf_counter_ns()
            req.resolve(row)

    def _fail_round(self, exc: BaseException,
                    members: List[Tuple[_SeqRequest, int]],
                    caches_lost: bool = False) -> None:
        """A step that failed fails the requests of its round, and ends
        their sequences regardless of req.end: the client has no valid
        continuation state (the cache may be partially updated), and keeping
        the slot would leak capacity one failed round at a time."""
        import jax

        for req, _ in members:
            req.fail(exc)
        with self._lock:
            ended = [req.seq_id for req, _ in members]
            if caches_lost or any(leaf.is_deleted() for leaf in
                                  jax.tree_util.tree_leaves(self._caches)):
                # the step had taken every slot's cache with it: all
                # live sequences end, and the next round starts clean
                self._caches = self._fresh_caches()
                ended = list(self._slot_of)
            for seq_id in ended:
                self._free_slot(seq_id)

    def _abandon(self, exc: BaseException) -> None:
        """A turn failed outside a dispatch (a round's logits that cannot be
        read are a step that failed on the device, and every later round
        was fed its caches): every request begun fails with its round."""
        self._answer()  # what is on the host already is sound
        begun = list(self._table.values())
        for _, _, answered in self._in_flight:
            begun.extend(answered)
        self._table.clear()
        self._in_flight.clear()
        self._fail_round(exc, begun, caches_lost=True)

    def _free_slot(self, seq_id) -> None:
        slot = self._slot_of.pop(seq_id, None)
        self._last_seen.pop(seq_id, None)
        if slot is not None:
            self._free.append(slot)
