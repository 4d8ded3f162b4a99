"""One request's timeline inside the serving path, and the spans beside it.

``ServerCore`` opens one :class:`Timeline` a request and makes it the
:func:`current` one while the model's code runs (a context variable: nothing
is added to ``Model.execute``'s signature). A model that batches or streams
hangs its own marks on it, :class:`BatchMarks` or :class:`StreamMarks`; a
model that marks nothing reports through the core's five marks alone. At the
end of the request one recorder reads the timeline: the statistics verb
(:meth:`Timeline.parts`), the Triton trace record and the ``traceparent``
access record all come from it, and so do the per-request readings of the
registry (:meth:`Timeline.readings`).

The two round workers (``models/decoder_batched.py``, ``models/stream_rounds.py``)
cut every turn into **phases**, one shared vocabulary (:data:`PHASES`): each is a
:func:`span` that also adds its time to the model's :class:`Phases`, so the span
on the profiler's clock, the per-round counter in the registry and the request's
marks are one pair of clock readings.

Marks are ``time.perf_counter_ns()`` of the host. **The dispatch marks are
host times**: a jitted call returns when the program is enqueued, not when
the device has run it, so the host's dispatch runs ahead of the device by as
many steps as the runtime's queue takes (a step writes its donated cache in
place and allocates no other, so no call waits for room). ``enqueued`` and
``on_host`` are exact:
the first precedes any device work of the request, the second follows the
copy of its last round's logits to the host.

:func:`span` is the one helper both come from: a
``jax.profiler.TraceAnnotation`` of a fixed name, which lands on the host
plane of a profiler session and is inert without one, whose two edges are
also the clock readings the marks are set from.
"""

from __future__ import annotations

import sys
import threading
import time
from contextvars import ContextVar
from typing import Any, Dict, List, Optional, Tuple

# host spans on the profiler's clock: fixed names, read by name from the
# xplane's host plane (PERF.md section 3 says which reading each is for)
SPAN_RESOLVE_INPUTS = "client_tpu.core.resolve_inputs"
SPAN_BUILD_RESPONSE = "client_tpu.core.build_response"
# the slot batcher's worker, a turn and its phases; ``wait_result`` is the
# caller's
SPAN_BATCH_TURN = "client_tpu.batcher.turn"
SPAN_BATCH_WAIT_WORK = "client_tpu.batcher.wait_work"
SPAN_COLLECT = "client_tpu.batcher.collect"
SPAN_ADMIT = "client_tpu.batcher.admit"
SPAN_ROUND_PREPARE = "client_tpu.batcher.round_prepare"
SPAN_ROUND_DISPATCH = "client_tpu.batcher.round_dispatch"
SPAN_BATCH_RECORD = "client_tpu.batcher.record"
SPAN_BATCH_HAND_OUT = "client_tpu.batcher.hand_out"
SPAN_BATCH_DEVICE_WAIT = "client_tpu.batcher.device_wait"
SPAN_BATCH_READBACK = "client_tpu.batcher.readback"
SPAN_WAIT_RESULT = "client_tpu.batcher.wait_result"
# the streams: the rounds' worker, a turn and its phases (``fresh_cache``
# lies inside ``admit``); ``prefill`` is the stream's own thread
SPAN_TURN = "client_tpu.generate.turn"
SPAN_WAIT_WORK = "client_tpu.generate.wait_work"
SPAN_STREAM_ADMIT = "client_tpu.generate.admit"
SPAN_FRESH_CACHE = "client_tpu.generate.fresh_cache"
SPAN_PREFILL = "client_tpu.generate.prefill"
SPAN_PREFILL_CHUNK = "client_tpu.generate.prefill_chunk"
SPAN_PREPARE = "client_tpu.generate.prepare"
SPAN_DISPATCH = "client_tpu.generate.dispatch"
SPAN_RECORD = "client_tpu.generate.record"
SPAN_HAND_OUT = "client_tpu.generate.hand_out"
SPAN_DEVICE_WAIT = "client_tpu.generate.device_wait"
SPAN_READBACK = "client_tpu.generate.readback"
SPAN_NAMES = (
    SPAN_RESOLVE_INPUTS, SPAN_BUILD_RESPONSE, SPAN_BATCH_TURN,
    SPAN_BATCH_WAIT_WORK, SPAN_COLLECT, SPAN_ADMIT, SPAN_ROUND_PREPARE,
    SPAN_ROUND_DISPATCH, SPAN_BATCH_RECORD, SPAN_BATCH_HAND_OUT,
    SPAN_BATCH_DEVICE_WAIT, SPAN_BATCH_READBACK, SPAN_WAIT_RESULT, SPAN_TURN,
    SPAN_WAIT_WORK, SPAN_STREAM_ADMIT, SPAN_FRESH_CACHE, SPAN_PREFILL,
    SPAN_PREFILL_CHUNK, SPAN_PREPARE, SPAN_DISPATCH, SPAN_RECORD,
    SPAN_HAND_OUT, SPAN_DEVICE_WAIT, SPAN_READBACK)

# a worker's turn, cut into phases that add up to it: one vocabulary, the
# span of each engine that times a phase keyed to it here (the batcher's
# ``round_prepare`` and ``round_dispatch`` keep their profiler names); an
# engine uses the phases it has. ``wait_work`` is the wait for a request or a
# stream when nothing is in progress, and is left out of every metric;
# ``between`` has no span: it is what lies between two phases (``Phases``)
PHASE_OF = {
    SPAN_BATCH_WAIT_WORK: "wait_work", SPAN_WAIT_WORK: "wait_work",
    SPAN_COLLECT: "collect",
    SPAN_ADMIT: "admit", SPAN_STREAM_ADMIT: "admit",
    SPAN_PREFILL_CHUNK: "prefill_chunk",
    SPAN_ROUND_PREPARE: "prepare", SPAN_PREPARE: "prepare",
    SPAN_ROUND_DISPATCH: "dispatch", SPAN_DISPATCH: "dispatch",
    SPAN_BATCH_RECORD: "record", SPAN_RECORD: "record",
    SPAN_BATCH_HAND_OUT: "hand_out", SPAN_HAND_OUT: "hand_out",
    SPAN_BATCH_DEVICE_WAIT: "device_wait", SPAN_DEVICE_WAIT: "device_wait",
    SPAN_BATCH_READBACK: "readback", SPAN_READBACK: "readback",
}
PHASES = tuple(dict.fromkeys(PHASE_OF.values())) + ("between",)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_CURRENT: ContextVar[Optional["Timeline"]] = ContextVar(
    "client_tpu_timeline", default=None)


def current() -> Optional["Timeline"]:
    """The timeline of the request this thread is executing, if the core
    opened one."""
    return _CURRENT.get()


class Phases:
    """What one model's round worker took, by phase: ``{phase: [count,
    ns]}``. Written by the worker alone, through ``span(NAME,
    into=phases)``, with no lock on the way; the registry's collector reads
    it as it stands (a row read between its two additions is one count
    ahead of its ns).

    The phases tile the worker's time: what lies between the end of one
    phase and the start of the next (the few instructions between two
    spans, and whatever the thread waited there to be given the processor
    or the interpreter back) is added as ``between``, from the two readings
    the spans took anyway. So the rows add up to the time from the worker's
    first phase to the end of its last, exactly."""

    __slots__ = ("_rows", "_edge")

    def __init__(self):
        self._rows: Dict[str, List[int]] = {}
        self._edge: Optional[int] = None  # the end of the last phase

    def add(self, phase: str, start_ns: int, end_ns: int) -> None:
        if self._edge is not None:
            self._count("between", start_ns - self._edge)
        self._count(phase, end_ns - start_ns)
        self._edge = end_ns

    def _count(self, phase: str, ns: int) -> None:
        row = self._rows.get(phase)
        if row is None:
            row = self._rows[phase] = [0, 0]
        row[0] += 1
        row[1] += ns

    def rows(self) -> Dict[str, Tuple[int, int]]:
        """``{phase: (count, ns)}`` as it stands."""
        return {phase: (row[0], row[1]) for phase, row in list(self._rows.items())}


class span:
    """``with span(NAME) as s:`` — a host span of a fixed name; afterwards
    ``s.start_ns`` and ``s.end_ns`` are its edges for the marks. With
    ``into=phases`` its time is also added there, under the phase its name
    is keyed to (:data:`PHASE_OF`; a name that is keyed to none raises
    ``KeyError`` here), from those same two readings. Until jax
    has been imported no profiler session can exist and the span is the two
    clock readings alone."""

    __slots__ = ("_annotation", "_into", "_phase", "start_ns", "end_ns")

    def __init__(self, name: str, into: Optional[Phases] = None):
        jax = sys.modules.get("jax")
        self._annotation = (
            jax.profiler.TraceAnnotation(name) if jax is not None else None)
        self._into = into
        self._phase = PHASE_OF[name] if into is not None else None

    def __enter__(self) -> "span":
        if self._annotation is not None:
            self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.end_ns = time.perf_counter_ns()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        if self._into is not None:
            self._into.add(self._phase, self.start_ns, self.end_ns)

    @property
    def ns(self) -> int:
        return self.end_ns - self.start_ns


class Interval:
    """A host interval that recurs within one request: how often, and how
    long in all."""

    __slots__ = ("count", "ns")

    def __init__(self):
        self.count = 0
        self.ns = 0

    def add(self, ns: int) -> None:
        self.count += 1
        self.ns += ns

    def as_dict(self) -> Dict[str, int]:
        return {"count": self.count, "ns": self.ns}


class BatchMarks:
    """A request's way through a batcher. ``enqueued``: put on the batcher's
    queue; ``collected``: taken into the table of requests in progress;
    ``first_dispatch``: start of the dispatch of the first round that carries
    one of its tokens; ``last_dispatch``: return of the dispatch of the round
    that consumed its last token; ``on_host``: that round's logits on the
    host, one transfer for all its rows, in the batcher's thread;
    ``resolved``: its future set, after its own last round's logits reached
    the host and the next round, if there was one to send, was dispatched.
    Counted: ``rounds_own``, the rounds that carried a token of its;
    ``rounds_waited``, the rounds dispatched between ``enqueued`` and
    ``first_dispatch``; ``rounds_held``, the rounds dispatched between its
    last round and ``resolved``; either is at most the batcher's bound on
    rounds in flight; ``stride_rounds``, for a continuation request, its
    first round's id less the id of the round that carried its sequence's
    token before (1: the sequence missed no round)."""

    __slots__ = ("enqueued", "collected", "first_dispatch", "last_dispatch",
                 "on_host", "resolved", "rounds_own", "rounds_waited",
                 "rounds_held", "first_round_id", "round_widths",
                 "stride_rounds")
    MARKS = ("enqueued", "collected", "first_dispatch", "last_dispatch",
             "on_host", "resolved")

    def __init__(self):
        self.enqueued = self.collected = None
        self.first_dispatch = self.last_dispatch = None
        self.on_host = self.resolved = None
        self.rounds_own = 0
        self.rounds_waited = 0
        self.rounds_held = 0
        self.first_round_id: Optional[int] = None
        self.round_widths: List[int] = []
        self.stride_rounds: Optional[int] = None

    def round(self, dispatch: span, round_id: Optional[int], width: int,
              last: bool) -> None:
        """One dispatched round carried a token of this request."""
        if not self.rounds_own:
            self.first_dispatch = dispatch.start_ns
            self.first_round_id = round_id
        self.rounds_own += 1
        self.round_widths.append(width)
        if last:
            self.last_dispatch = dispatch.end_ns


class StreamMarks:
    """A decoupled generation. ``cache_ready``: its cache allocated;
    ``prefill_done``: the prompt's steps dispatched; then per token the
    host intervals ``dispatch`` (the step call to its return: a wait for
    the allocator is in here), ``readback`` (the wait for the device, the
    logits or the choices to the host and the argmax), ``handoff`` (on
    rounds: from the end of the worker's read-back to the stream's own
    thread holding the token: a queue, the interpreter) and ``yielded``
    (suspended at ``yield``: the core builds the response and the frontend
    writes it). ``first_round_id``: on rounds, the id of the dispatch that
    gave the first token."""

    __slots__ = ("cache_ready", "prefill_done", "dispatch", "readback",
                 "handoff", "yielded", "first_round_id")
    MARKS = ("cache_ready", "prefill_done")

    def __init__(self):
        self.cache_ready = self.prefill_done = None
        self.dispatch = Interval()
        self.readback = Interval()
        self.handoff = Interval()
        self.yielded = Interval()
        self.first_round_id: Optional[int] = None


class Timeline:
    """The marks of one request: ``recv`` (entry into the core),
    ``inputs_resolved``, ``model_enter``, ``model_exit``, ``done`` (response
    built; for a stream, the last one), ``first_response`` (streams), and
    what the model hung on it."""

    __slots__ = ("recv", "inputs_resolved", "model_enter", "model_exit",
                 "done", "first_response", "responses", "compiled_ns",
                 "batch", "stream", "_compile_ns_at_recv", "_token")
    MARKS = ("recv", "inputs_resolved", "model_enter", "model_exit",
             "first_response", "done")

    def __init__(self):
        self.recv = time.perf_counter_ns()
        self.inputs_resolved = self.model_enter = self.model_exit = None
        self.done = self.first_response = None
        self.responses = 0
        self.compiled_ns = 0
        self.batch: Optional[BatchMarks] = None
        self.stream: Optional[StreamMarks] = None
        self._compile_ns_at_recv = COMPILES.ns

    def close(self) -> None:
        """The request is over: ``done`` if nothing set it, and the compile
        time of the process since ``recv``."""
        if self.done is None:
            self.done = time.perf_counter_ns()
        self.compiled_ns = COMPILES.ns - self._compile_ns_at_recv

    def __enter__(self) -> "Timeline":
        """``with timeline:`` round the model's code makes it the thread's
        :func:`current` one. Leave the block before yielding to a caller: a
        context variable set across a ``yield`` stays set in the consumer."""
        self._token = _CURRENT.set(self)
        return self

    def __exit__(self, *exc: Any) -> None:
        _CURRENT.reset(self._token)

    def parts(self) -> Tuple[int, int, int, int]:
        """``(compute_input, queue, compute_infer, compute_output)`` in ns,
        Triton's four, which add up to ``done - recv``.

        A batched request: ``recv`` to ``enqueued``; to ``first_dispatch``;
        its own rounds, to ``last_dispatch``; the rest: its last round on
        the device, that round's transfer to the host, the next round's
        dispatch, the response. A stream: ``recv`` to ``cache_ready``; no queue;
        the time inside the generator; the time suspended at ``yield``. Any
        other: ``recv`` to ``model_enter``; no queue; the model's
        ``execute``; the response."""
        total = self.done - self.recv
        b, s = self.batch, self.stream
        if b is not None and b.last_dispatch is not None:
            return (b.enqueued - self.recv, b.first_dispatch - b.enqueued,
                    b.last_dispatch - b.first_dispatch,
                    self.done - b.last_dispatch)
        if s is not None and s.cache_ready is not None:
            before = s.cache_ready - self.recv
            return before, 0, total - before - s.yielded.ns, s.yielded.ns
        if self.model_exit is None:
            return total, 0, 0, 0
        return (self.model_enter - self.recv, 0,
                self.model_exit - self.model_enter, self.done - self.model_exit)

    def marks(self) -> Dict[str, int]:
        """Every mark that was set, by name."""
        out = {name: getattr(self, name) for name in self.MARKS}
        for group in (self.batch, self.stream):
            if group is not None:
                out.update((name, getattr(group, name)) for name in group.MARKS)
        return {name: at for name, at in out.items() if at is not None}

    def counts(self) -> Dict[str, Any]:
        """What was counted on the way: a batched request's rounds, a
        stream's intervals, the responses, the compile time."""
        out: Dict[str, Any] = {"responses": self.responses,
                               "compiled_ns": self.compiled_ns}
        if self.batch is not None:
            b = self.batch
            out.update(rounds_own=b.rounds_own, rounds_waited=b.rounds_waited,
                       rounds_held=b.rounds_held,
                       first_round_id=b.first_round_id,
                       round_widths=list(b.round_widths),
                       stride_rounds=b.stride_rounds)
        if self.stream is not None:
            s = self.stream
            out.update(dispatch=s.dispatch.as_dict(),
                       readback=s.readback.as_dict(),
                       handoff=s.handoff.as_dict(),
                       yielded=s.yielded.as_dict(),
                       first_round_id=s.first_round_id)
        return out

    def readings(self) -> List[Tuple[str, int, int]]:
        """What the request read of the rounds that carried it, for the
        registry's per-request series (a pair a name,
        ``client_tpu_server_<name>_<unit>`` and ``_count``): ``(name, count,
        sum)``, each from marks that are set anyway. A batched request: the
        rounds between two tokens of its sequence, and ``resolved`` to
        ``done`` (its future set to its response built: the caller's wake).
        A stream: ``recv`` to ``first_response``, and its tokens'
        hand-offs."""
        out: List[Tuple[str, int, int]] = []
        b, s = self.batch, self.stream
        if b is not None and b.stride_rounds is not None:
            out.append(("sequence_stride", 1, b.stride_rounds))
        if b is not None and b.resolved is not None:
            out.append(("answer_wake", 1, self.done - b.resolved))
        if self.first_response is not None:
            out.append(("first_response", 1, self.first_response - self.recv))
        if s is not None and s.handoff.count:
            out.append(("token_handoff", s.handoff.count, s.handoff.ns))
        return out


class CompileCounter:
    """Compiles of this process, counted by one ``jax.monitoring`` listener:
    how many and how long. jax has no call to take one listener away, so
    there is one counter a process, :data:`COMPILES`, however many cores."""

    def __init__(self):
        self._lock = threading.Lock()
        self._listening = False
        self.count = 0
        self.ns = 0

    def listen(self) -> None:
        with self._lock:
            if self._listening:
                return
            self._listening = True
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration_secs: float, **_: Any) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.count += 1
                self.ns += int(duration_secs * 1e9)


COMPILES = CompileCounter()
