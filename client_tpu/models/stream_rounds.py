"""The rounds of a stream model: every live stream's next token, one dispatch.

A decoupled stream that steps its own sequence makes one dispatch a token,
and each reads every weight for that one token; S streams of one model read
them S times a token-time where once would do. Here the unit of device work
is the **round**: one dispatch of the decoder's round program
(``decoder.py:round_program``, ``vmap`` of the step's own parts, so tokens
are bit-comparable with the single-sequence step on the CPU) that advances
every live stream of the model by one token.

**The table of slots.** ``slots`` stacked caches ([slots, heads, max_len,
head_dim] a layer) are reserved once, donated to every round and written in
place. A stream takes the lowest free slot at admission and gives it back
when its budget is spent, its ``END_ID`` is emitted, its consumer goes away
(a client's cancel, a closed generator) or the model is unloaded. A stream
that finds no free slot waits for one, first come first seated; none is
refused. Nothing of a slot is cleared: a new stream writes its rows from
position 0 and reads none beyond its own position.

**One worker runs the rounds.** A round consumes the next token of every
member: a prompt's token, which the host supplies, for a stream still in its
prompt, and for a decoding one the token the round before chose, **which
never leaves the device**: a round returns the greedy choice of every slot
(int32, [slots]) and that array is the next round's input. What comes to the
host a round is that one small array; what goes to the device is one
([3, slots], int32): a prompt's token or -1, the position, whether a stream
sits there. A stream joins the next round after its admission and waits for
no other stream's prompt.

Because a stream's next token does not pass through the host, up to
``ROUNDS_IN_FLIGHT`` rounds are dispatched and not yet read back. A stream
whose budget is spent leaves the table with the dispatch of its last round
(the host counts); one that ends by its ``END_ID`` is found when that round
is read back, up to ``ROUNDS_IN_FLIGHT - 1`` rounds late: the rows it wrote
meanwhile lie in a slot that is freed, and their tokens are dropped.

**A round costs what its live streams cost.** Its attention reads a
compile-time prefix of the positions (the decoder's ladder: the rung that
covers the furthest member) and, of the slots, the occupied ones and no
cache beyond: ``decoder.SLOTS_A_TURN`` slots a turn of a loop that the
program ends after the highest occupied slot (decoder.py's ``round_layer``;
lowest-free-first admission keeps the occupied slots compact). Where heads
are narrower than the chip's lanes the turns read slowly and the round reads
every slot instead; the decoder says which slots a round read
(``slots_read``). So there is one program a rung, as for a single sequence,
and every rung's is compiled before the worker takes its first stream. (A
ladder of compile-time widths was built first and measured: a program more
is 0.6 to 1.3 s of a warm set-up, which does not shrink side by side, and
the set-up's bound paid for three of the six; PERF.md section 6.)

**A prompt a chunk a dispatch, where the decoder offers it.** A decoder
whose prompts are too long for a round a token (a byte model's are four
times a token model's) brings a slot prefill (``decoder._slot_prefill_fn``:
one program that takes the table, a slot and a chunk of prompt positions and
writes that slot's state). A stream of such a decoder is seated as any other
but is no member of the rounds while its prompt is being taken: each turn
the worker dispatches at most one chunk, of the oldest such prompt, and then
the round, so a decoding stream waits one chunk at most between two tokens.
The prompt's last chunk chooses the stream's first token on the device and
leaves it where the next round reads it; that dispatch is read back as a
round is. A decoder without one (``None``) takes the code it took before:
no program more, a prompt a token a round.
"""

from __future__ import annotations

import collections
import heapq
import queue
import threading
import time
from typing import Any, Deque, Dict, Iterator, List, Optional, Tuple

import numpy as np

from ..server.timeline import (
    SPAN_DEVICE_WAIT,
    SPAN_DISPATCH,
    SPAN_FRESH_CACHE,
    SPAN_HAND_OUT,
    SPAN_PREFILL_CHUNK,
    SPAN_PREPARE,
    SPAN_READBACK,
    SPAN_RECORD,
    SPAN_STREAM_ADMIT,
    SPAN_TURN,
    SPAN_WAIT_WORK,
    StreamMarks,
    span,
)

# Rounds dispatched and not yet read back, at most. With 1 the device waits
# for the host's turn between two rounds (the read-back, the streams' wakes,
# the next dispatch call); with 2 the next round is queued behind the one
# that runs. Chosen on the chip (PERF.md section 6, PR 33).
ROUNDS_IN_FLIGHT = 2
STREAM_TIMEOUT_S = 120.0
_END = object()  # on a stream's queue: no more tokens


class Stream:
    """One stream's place in the rounds. The worker's, but for ``out`` (its
    tokens, each beside the clock reading that ended its round's read-back,
    then ``_END`` or what failed it) and ``gone``, which the consumer's side
    sets when it goes away."""

    __slots__ = ("prompt", "budget", "end_id", "marks", "out", "gone", "slot",
                 "at", "pos", "due", "done", "waited")

    def __init__(self, prompt: List[int], budget: int, end_id: Optional[int],
                 marks: StreamMarks):
        self.prompt, self.budget, self.end_id = prompt, budget, end_id
        self.marks = marks
        self.out: "queue.SimpleQueue[Any]" = queue.SimpleQueue()
        self.gone = False
        self.slot = -1
        self.at = 0  # prompt tokens consumed
        self.pos = 0  # the next round's position: the rounds it was carried
        self.due = 0  # tokens its dispatched rounds give
        self.done = False  # ended: nothing more goes on ``out``
        self.waited = False  # counted as having found no free slot

    def tokens(self) -> Iterator[Tuple[int, int]]:
        """The stream's tokens as their rounds are read back, each with the
        end of that read-back on the worker's clock."""
        while True:
            try:
                item = self.out.get(timeout=STREAM_TIMEOUT_S)
            except queue.Empty:
                from ..server.core import InferError

                raise InferError(
                    f"no round gave the stream a token in {STREAM_TIMEOUT_S:.0f}s",
                    504) from None
            if item is _END:
                return
            if isinstance(item, BaseException):
                raise item
            yield item


class StreamRounds:
    """The table, the worker and the rounds in flight of one stream model.
    ``model`` is the served model whose counts it fills (``steps_by_rung``,
    ``batch_histogram``, ``rounds_by_width``, ``slot_waits``,
    ``report_batch``, and ``phases``: the worker's turns by phase) and whose
    ``_decoder`` it steps; the decoder's
    ``_params`` are read at every dispatch, so new weights are served from
    the next round on."""

    def __init__(self, model):
        import jax
        import jax.numpy as jnp

        self._model = model
        self._decoder = decoder = model._decoder
        self.slots = int(model.slots)
        self._caches = decoder._fresh_table(self.slots)
        # a decoder may keep a tally of its own behind the slots' choices
        # (``decoder.fed_tally`` int32, running sums that wrap), which comes
        # back with them and goes to ``decoder.count_tally`` by difference
        self._tally = getattr(decoder, "fed_tally", 0)
        self._fed = jnp.zeros((self.slots + self._tally,), jnp.int32)
        self._arrivals: "queue.SimpleQueue[Optional[Stream]]" = queue.SimpleQueue()
        self._taking = True
        # the worker's own: the rounds' members by slot, the seated streams
        # whose prompts are being taken a chunk a dispatch (oldest first; none
        # where the decoder has no slot prefill), the free slots (a heap: the
        # lowest first), the streams that wait for one, and the rounds
        # dispatched and not yet read back, oldest first: ``(choices on the
        # device, [(stream, slot, index of the token)], dispatch, id)``; the
        # id counts the dispatches that are read back, a round or a prompt's
        # last chunk
        self._members: Dict[int, Stream] = {}
        self._prompts: Deque[Stream] = collections.deque()
        self._slot_prefill = decoder._slot_prefill_fn
        self._free = list(range(self.slots))
        self._waiting: Deque[Stream] = collections.deque()
        self._in_flight: Deque[
            Tuple[Any, List[Tuple[Stream, int, int]], span, int]] = (
                collections.deque())
        self._dispatched = 0
        # every rung's program compiled, by one real round each with nobody
        # seated (no row is written), before the first stream is taken
        nobody = np.zeros((3, self.slots), np.int32)
        for live in decoder._rungs:
            self._step(nobody, live)
            if self._slot_prefill is not None:  # a chunk of no token
                self._chunk(np.zeros(decoder._slot_prefill_chunk, np.int32),
                            np.zeros(5, np.int32), live)
        jax.block_until_ready(self._fed)  # (under ``eval_shape`` a shape)
        if self._tally:  # what the set-up's dispatches tallied is not counted
            self._tallied = np.asarray(self._fed)[self.slots:]
        self._worker = threading.Thread(
            target=self._run, name="stream-rounds", daemon=True)
        self._worker.start()

    # -- the streams' side ---------------------------------------------------
    def open(self, prompt: List[int], budget: int, end_id: Optional[int],
             marks: StreamMarks) -> Stream:
        stream = Stream(prompt, budget, end_id, marks)
        self._arrivals.put(stream)
        return stream

    def close(self) -> None:
        """End the worker: every stream begun or waiting fails, and the
        table is let go of (the device frees it when the rounds in flight
        have run)."""
        self._arrivals.put(None)
        self._worker.join(timeout=10)
        self._caches = self._fed = None

    def _step(self, ctl: np.ndarray, live: int) -> None:
        """One dispatch of the round's program of that rung over the table,
        which it owns, and the choices of the round before."""
        decoder = self._decoder
        self._fed, self._caches = decoder._round_fn(
            decoder._params, self._caches, self._fed, ctl, live=live)

    def _chunk(self, block: np.ndarray, ctl: np.ndarray, live: int) -> None:
        """One dispatch of the slot prefill of that rung: a chunk of a
        prompt into its slot of the table."""
        decoder = self._decoder
        self._fed, self._caches = self._slot_prefill(
            decoder._params, self._caches, self._fed, block, ctl, live=live)

    # -- the worker: one turn a round ----------------------------------------
    def _run(self) -> None:
        while self._taking:
            try:
                self._turn()
            except Exception as e:  # the worker must not die: every later
                self._abandon(e)    # stream of the model would hang
        self._abandon(ValueError("model is shutting down"), renew=False)
        for stream in self._waiting:
            self._end(stream, ValueError("model is shutting down"))

    def _turn(self) -> None:
        with span(SPAN_TURN):
            if not (self._members or self._prompts or self._in_flight
                    or self._waiting):
                # nothing to run: wait for a stream
                with span(SPAN_WAIT_WORK, into=self._model.phases):
                    self._take(self._arrivals.get())
            self._admit()
            if self._prompts and self._taking:
                self._take_chunk()
            if self._members and self._taking:
                self._dispatch()
            while self._in_flight and (len(self._in_flight) >= ROUNDS_IN_FLIGHT
                                       or not self._members):
                self._read_back()

    def _take(self, stream: Optional[Stream]) -> None:
        """What came off the queue: a stream, which queues behind those
        that wait, or ``close``'s sentinel."""
        if stream is None:
            self._taking = False
        else:
            self._waiting.append(stream)

    def _admit(self) -> None:
        """The slots of the streams whose consumers went away are given
        back; what has arrived queues behind those that wait; the free slots
        are taken, lowest first, first come first seated."""
        with span(SPAN_STREAM_ADMIT, into=self._model.phases):
            for stream in [s for s in (*self._members.values(), *self._prompts)
                           if s.gone]:
                self._end(stream)
            while self._taking:
                try:
                    self._take(self._arrivals.get_nowait())
                except queue.Empty:
                    break
            if not self._taking:
                return
            while self._waiting and self._free:
                stream = self._waiting.popleft()
                if stream.gone:
                    continue
                with span(SPAN_FRESH_CACHE) as taken:
                    stream.slot = heapq.heappop(self._free)
                    if self._slot_prefill is None:
                        self._members[stream.slot] = stream
                    else:
                        self._prompts.append(stream)
                stream.marks.cache_ready = taken.end_ns
            for stream in self._waiting:
                if not stream.waited:
                    stream.waited = True
                    self._model.slot_waits += 1

    def _take_chunk(self) -> None:
        """The next chunk of the oldest prompt that is being taken, into its
        slot. The prompt's last chunk chooses the first token on the device:
        the stream joins the rounds, and the dispatch is read back as a round
        that gave that one token."""
        import jax

        decoder, count = self._decoder, self._model.steps_by_rung
        phases = self._model.phases
        with span(SPAN_PREPARE, into=phases):
            stream = self._prompts[0]
            size = decoder._slot_prefill_chunk
            base, n = stream.at, min(size, len(stream.prompt) - stream.at)
            last = base + n == len(stream.prompt)
            block = np.zeros(size, np.int32)
            block[:n] = stream.prompt[base:base + n]
            ctl = np.array([stream.slot, base, 0, n, last], np.int32)
        with span(SPAN_PREFILL_CHUNK, into=phases) as dispatch:
            self._chunk(block, ctl, decoder.rung_for(base + n))
        with span(SPAN_RECORD, into=phases):
            stream.at += n
            decoder.count_positions(count, np.arange(base, base + n), decoding=False)
            if last:
                self._fed.copy_to_host_async()
                self._prompts.popleft()
                self._members[stream.slot] = stream
                stream.pos = len(stream.prompt)
                stream.marks.prefill_done = dispatch.end_ns
                count.add_prefill(len(stream.prompt),
                                  chunks=-(-len(stream.prompt) // size))
                count.add_prefill_ns(dispatch.end_ns - stream.marks.cache_ready)
                self._sent([(stream, stream.slot, 0)], dispatch)
                stream.due = 1
                if stream.due == stream.budget:
                    self._vacate(stream)
        if not last and not self._members:
            # nobody decodes: nothing else paces the worker, so a chunk is
            # waited for before the next is dispatched
            with span(SPAN_DEVICE_WAIT, into=phases):
                jax.block_until_ready(self._fed)

    def _sent(self, gives: List[Tuple[Stream, int, int]], dispatch: span) -> None:
        """The dispatch just made, among those to be read back, under the
        next id."""
        self._in_flight.append((self._fed, gives, dispatch, self._dispatched))
        self._dispatched += 1

    def _dispatch(self) -> None:
        """One round: the next token of every member."""
        model, decoder = self._model, self._decoder
        phases, count = model.phases, model.steps_by_rung
        with span(SPAN_PREPARE, into=phases):
            members = sorted(self._members.items())
            ctl = np.zeros((3, self.slots), np.int32)
            ctl[0] = -1
            for slot, stream in members:
                if stream.at < len(stream.prompt):
                    ctl[0, slot] = stream.prompt[stream.at]
                    stream.at += 1
                ctl[1, slot] = stream.pos
                ctl[2, slot] = 1
                stream.pos += 1
            # the shortest rung that covers the furthest member; the slots its
            # attention reads, as the decoder's program takes them
            live = decoder.rung_for(int(ctl[1].max()) + 1)
            width = decoder.slots_read(self.slots, members[-1][0] + 1)
            decoder.count_positions(count, ctl[1][ctl[2] > 0], decoding=True)
        with span(SPAN_DISPATCH, into=phases) as dispatch:
            self._step(ctl, live)
        with span(SPAN_RECORD, into=phases):
            # the transfer begins when the round ends, with no host thread
            # having to be scheduled in between
            self._fed.copy_to_host_async()
            gives = []
            for slot, stream in members:
                if stream.at < len(stream.prompt):
                    continue  # midway through its prompt: this round gives none
                if not stream.due:
                    stream.marks.prefill_done = dispatch.end_ns
                    count.add_prefill(len(stream.prompt), chunks=len(stream.prompt))
                    count.add_prefill_ns(dispatch.end_ns - stream.marks.cache_ready)
                gives.append((stream, slot, stream.due))
                stream.due += 1
                if stream.due == stream.budget:
                    self._vacate(stream)  # its last round: the slot is the next's
            self._sent(gives, dispatch)
            count.add(live)
            decoder.count_rows_written(count, len(members))
            carried = model.batch_histogram
            carried[len(members)] = carried.get(len(members), 0) + 1
            model.rounds_by_width[width] = model.rounds_by_width.get(width, 0) + 1
            if model.report_batch is not None:
                model.report_batch(len(members), dispatch.ns)

    def _read_back(self) -> None:
        """The oldest dispatch in flight: wait for it (``device_wait``), bring
        its choices to the host (``readback``) and hand every stream it gave
        a token that token, beside the clock reading that ended the
        read-back (``hand_out``)."""
        phases = self._model.phases
        fed, gives, dispatch, round_id = self._in_flight[0]
        with span(SPAN_DEVICE_WAIT, into=phases) as wait:
            fed.block_until_ready()
        with span(SPAN_READBACK, into=phases) as readback:
            chosen = np.asarray(fed)
            if self._tally:
                tally = chosen[self.slots:]
                self._decoder.count_tally(
                    self._model.steps_by_rung,
                    tally.astype(np.uint32) - self._tallied.astype(np.uint32))
                self._tallied = tally
        with span(SPAN_HAND_OUT, into=phases):
            self._in_flight.popleft()
            for stream, slot, index in gives:
                if stream.done:
                    continue  # ended by its END_ID a round ago, or its consumer left
                token = int(chosen[slot])
                if index:  # the first token's dispatches are its prefill
                    stream.marks.dispatch.add(dispatch.ns)
                else:
                    stream.marks.first_round_id = round_id
                stream.marks.readback.add(readback.end_ns - wait.start_ns)
                stream.out.put((token, readback.end_ns))
                if index + 1 == stream.budget or token == stream.end_id:
                    self._end(stream)
            if gives:
                # the interpreter, once, to the streams that were handed a
                # token: where the device paces the rounds they would run at
                # the next read-back anyway; where the host does (a small
                # model, the CPU) the worker never waits, and each would stand
                # a switch interval (5 ms) behind it
                time.sleep(0)

    def _vacate(self, stream: Stream) -> None:
        if self._members.get(stream.slot) is stream:
            del self._members[stream.slot]
        elif stream in self._prompts:
            self._prompts.remove(stream)
        else:
            return
        heapq.heappush(self._free, stream.slot)

    def _end(self, stream: Stream, failed: Optional[BaseException] = None) -> None:
        self._vacate(stream)
        if not stream.done:
            stream.done = True
            stream.out.put(_END if failed is None else failed)

    def _abandon(self, exc: BaseException, renew: bool = True) -> None:
        """A turn failed (a dispatch; a round's choices that cannot be read
        are a round that failed on the device, and every later round was fed
        its caches): every stream begun fails. The streams that wait for a
        slot find a clean table, where one is to be served again."""
        import jax
        import jax.numpy as jnp

        begun = [*self._members.values(), *self._prompts]
        for _, gives, _, _ in self._in_flight:
            begun.extend(stream for stream, _, _ in gives)
        self._in_flight.clear()
        for stream in begun:
            self._end(stream, exc)
        if renew and any(
                leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(
                    (self._caches, self._fed))):
            self._caches = self._decoder._fresh_table(self.slots)
            self._fed = jnp.zeros((self.slots + self._tally,), jnp.int32)
            self._tallied = np.zeros(self._tally, np.int32)
