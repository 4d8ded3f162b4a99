"""Dynamic batcher: coalesce concurrent single requests into MXU-sized
batches.

The scheduler component of the serving stack (tritonserver's dynamic
batcher role — the reference *client* repo exposes it only through
`InferBatchStatistics` in the protocol, which this feeds): batching is THE
TPU throughput lever, because an [8, ...] matmul costs barely more than an
[1, ...] one on the systolic array until the batch fills the MXU tile.

Mechanics: requests enter a queue; the worker pops the first, then keeps
collecting until ``max_batch`` requests are in hand or ``max_delay_s``
passes (latency bound). Compatible requests — same input names, dtypes,
and per-request non-batch dims — are stacked along axis 0, executed ONCE,
and the output rows are scattered back to each caller's Future. A request
incompatible with the rest of the window simply forms its own group:
nothing blocks behind shape mismatches.

Eligibility is decided by the core (stateless, non-decoupled models with
``max_batch_size > 1``; shm-bound and sequence requests bypass).
"""

from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future
from typing import Any, Callable, Dict, List, Tuple

import numpy as np

from .timeline import SPAN_ROUND_DISPATCH, BatchMarks, span


class _Pending:
    __slots__ = ("inputs", "parameters", "future", "marks", "rows")

    def __init__(self, inputs, parameters):
        self.inputs = inputs
        self.parameters = parameters
        self.future: Future = Future()
        self.marks = BatchMarks()
        self.marks.enqueued = time.perf_counter_ns()
        # rows this request contributes to the stacked batch (axis 0)
        first = next(iter(inputs.values()))
        self.rows = int(first.shape[0]) if first.ndim else 1


def _compat_key(inputs: Dict[str, np.ndarray],
                parameters: Dict[str, Any]) -> Tuple:
    """Requests merge ONLY when their inputs line up AND their parameters
    are identical — execute() may honor any parameter, so merging across
    differing parameters would silently compute under the wrong ones."""
    return (
        tuple(sorted(
            (name, str(arr.dtype), arr.shape[1:])
            for name, arr in inputs.items())),
        repr(sorted(parameters.items(), key=lambda kv: kv[0])),
    )


class DynamicBatcher:
    """Per-model batching queue in front of ``execute``.

    ``report``: optional callback ``(batch_rows, exec_ns)`` invoked once per
    executed batch — the core feeds it into the protocol's
    ``InferBatchStatistics``. What one request waited and ran is marked on
    its own timeline (``submit``'s third argument), as the sequence batcher
    marks its: ``enqueued``, then the edges of the execution that carried it.
    """

    def __init__(
        self,
        execute: Callable[[Dict[str, np.ndarray], Dict[str, Any]], Dict[str, np.ndarray]],
        max_batch: int,
        max_delay_s: float = 0.002,
        max_queue: int = 1024,
        report: Callable[[int, int], None] = None,
    ):
        self._execute = execute
        self._max_batch = max(int(max_batch), 1)
        self._max_delay_s = max_delay_s
        self._report = report
        self._queue: "queue.Queue[_Pending]" = queue.Queue(maxsize=max_queue)
        self._closed = False
        self._carry: _Pending = None  # didn't fit the last window's cap
        self._worker = threading.Thread(
            target=self._run, name="dynamic-batcher", daemon=True)
        self._worker.start()

    # -- caller side --------------------------------------------------------
    def submit(self, inputs: Dict[str, np.ndarray],
               parameters: Dict[str, Any], timeline=None) -> Future:
        if self._closed:
            raise RuntimeError("batcher is closed")
        item = _Pending(inputs, parameters)
        if timeline is not None:
            timeline.batch = item.marks
        self._queue.put(item)
        return item.future

    def close(self) -> None:
        self._closed = True
        self._queue.put(None)  # wake the worker
        self._worker.join(timeout=5)
        # a submit() that passed the _closed check right before close() may
        # have enqueued behind the sentinel: fail it rather than strand it
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            if item is not None and not item.future.done():
                item.future.set_exception(RuntimeError("batcher closed"))

    # -- worker -------------------------------------------------------------
    def _collect(self) -> List[_Pending]:
        if self._carry is not None:
            first, self._carry = self._carry, None
        else:
            first = self._queue.get()
        if first is None:
            return []
        window = [first]
        rows = first.rows
        deadline = time.monotonic() + self._max_delay_s
        while rows < self._max_batch:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            try:
                nxt = self._queue.get(timeout=remaining)
            except queue.Empty:
                break
            if nxt is None:
                self._queue.put(None)  # re-signal shutdown after this batch
                break
            if rows + nxt.rows > self._max_batch:
                # would overflow the model's declared cap: starts the next
                # window instead (declared max_batch_size is a contract)
                self._carry = nxt
                break
            window.append(nxt)
            rows += nxt.rows
        return window

    def _run(self) -> None:
        while True:
            window = self._collect()
            if not window:
                return
            # group by compatibility; each group executes once
            groups: Dict[Tuple, List[_Pending]] = {}
            for item in window:
                groups.setdefault(
                    _compat_key(item.inputs, item.parameters), []).append(item)
            for items in groups.values():
                self._run_group(items)

    def _run_group(self, items: List[_Pending]) -> None:
        try:
            if len(items) == 1:
                stacked = items[0].inputs
            else:
                stacked = {
                    name: np.concatenate([it.inputs[name] for it in items], axis=0)
                    for name in items[0].inputs
                }
            # safe: the group key pins identical parameters across items
            with span(SPAN_ROUND_DISPATCH) as execution:
                outputs = self._execute(stacked, items[0].parameters)
            batch_rows = sum(it.rows for it in items)
            for it in items:
                it.marks.round(execution, None, batch_rows, last=True)
            if self._report is not None:
                self._report(batch_rows, execution.ns)
            offset = 0
            for it in items:
                sliced = {
                    name: np.asarray(arr)[offset:offset + it.rows]
                    for name, arr in outputs.items()
                }
                offset += it.rows
                it.future.set_result(sliced)
        except Exception as e:  # noqa: BLE001 — every caller must hear it
            for it in items:
                if not it.future.done():
                    it.future.set_exception(e)
