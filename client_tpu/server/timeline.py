"""One request's timeline inside the serving path, and the spans beside it.

``ServerCore`` opens one :class:`Timeline` a request and makes it the
:func:`current` one while the model's code runs (a context variable: nothing
is added to ``Model.execute``'s signature). A model that batches or streams
hangs its own marks on it, :class:`BatchMarks` or :class:`StreamMarks`; a
model that marks nothing reports through the core's five marks alone. At the
end of the request one recorder reads the timeline: the statistics verb
(:meth:`Timeline.parts`), the Triton trace record and the ``traceparent``
access record all come from it.

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
SPAN_COLLECT = "client_tpu.batcher.collect"
SPAN_ADMIT = "client_tpu.batcher.admit"
SPAN_ROUND_PREPARE = "client_tpu.batcher.round_prepare"
SPAN_ROUND_DISPATCH = "client_tpu.batcher.round_dispatch"
SPAN_WAIT_RESULT = "client_tpu.batcher.wait_result"
SPAN_BATCH_READBACK = "client_tpu.batcher.readback"
SPAN_FRESH_CACHE = "client_tpu.generate.fresh_cache"
SPAN_PREFILL = "client_tpu.generate.prefill"
SPAN_PREFILL_CHUNK = "client_tpu.generate.prefill_chunk"
SPAN_DISPATCH = "client_tpu.generate.dispatch"
SPAN_READBACK = "client_tpu.generate.readback"
SPAN_NAMES = (
    SPAN_RESOLVE_INPUTS, SPAN_BUILD_RESPONSE, SPAN_COLLECT, SPAN_ADMIT,
    SPAN_ROUND_PREPARE, SPAN_ROUND_DISPATCH, SPAN_WAIT_RESULT,
    SPAN_BATCH_READBACK, SPAN_FRESH_CACHE, SPAN_PREFILL, SPAN_PREFILL_CHUNK,
    SPAN_DISPATCH, SPAN_READBACK)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_CURRENT: ContextVar[Optional["Timeline"]] = ContextVar(
    "client_tpu_timeline", default=None)


def current() -> Optional["Timeline"]:
    """The timeline of the request this thread is executing, if the core
    opened one."""
    return _CURRENT.get()


class span:
    """``with span(NAME) as s:`` — a host span of a fixed name; afterwards
    ``s.start_ns`` and ``s.end_ns`` are its edges for the marks. Until jax
    has been imported no profiler session can exist and the span is the two
    clock readings alone."""

    __slots__ = ("_annotation", "start_ns", "end_ns")

    def __init__(self, name: str):
        jax = sys.modules.get("jax")
        self._annotation = (
            jax.profiler.TraceAnnotation(name) if jax is not None else None)

    def __enter__(self) -> "span":
        if self._annotation is not None:
            self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.end_ns = time.perf_counter_ns()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)

    @property
    def ns(self) -> int:
        return self.end_ns - self.start_ns


class Interval:
    """A host interval that recurs within one request: how often, how long
    in all, and the longest with the index it fell on."""

    __slots__ = ("count", "ns", "longest_ns", "longest_at")

    def __init__(self):
        self.count = 0
        self.ns = 0
        self.longest_ns = 0
        self.longest_at = -1

    def add(self, ns: int, at: int) -> None:
        """One more of ``ns``, at index ``at`` (a stream's token)."""
        self.count += 1
        self.ns += ns
        if ns > self.longest_ns:
            self.longest_ns = ns
            self.longest_at = at

    def as_dict(self) -> Dict[str, int]:
        return {"count": self.count, "ns": self.ns,
                "longest_ns": self.longest_ns, "longest_at": self.longest_at}


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
    rounds in flight."""

    __slots__ = ("enqueued", "collected", "first_dispatch", "last_dispatch",
                 "on_host", "resolved", "rounds_own", "rounds_waited",
                 "rounds_held", "first_round_id", "round_widths")
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
    ``prefill_done``: the prompt's steps dispatched; then per token three
    host intervals: ``dispatch`` (the step call to its return: a wait for
    the allocator is in here), ``readback`` (the logits to the host and the
    argmax) and ``yielded`` (suspended at ``yield``: the core builds the
    response and the frontend writes it)."""

    __slots__ = ("cache_ready", "prefill_done", "dispatch", "readback",
                 "yielded")
    MARKS = ("cache_ready", "prefill_done")

    def __init__(self):
        self.cache_ready = self.prefill_done = None
        self.dispatch = Interval()
        self.readback = Interval()
        self.yielded = Interval()


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
        stream's three intervals, the responses, the compile time."""
        out: Dict[str, Any] = {"responses": self.responses,
                               "compiled_ns": self.compiled_ns}
        if self.batch is not None:
            b = self.batch
            out.update(rounds_own=b.rounds_own, rounds_waited=b.rounds_waited,
                       rounds_held=b.rounds_held,
                       first_round_id=b.first_round_id,
                       round_widths=list(b.round_widths))
        if self.stream is not None:
            s = self.stream
            out.update(dispatch=s.dispatch.as_dict(),
                       readback=s.readback.as_dict(),
                       yielded=s.yielded.as_dict())
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
