"""Protocol-neutral server core: model registry, shm data plane, stats, infer.

Both the HTTP and GRPC frontends marshal requests into the neutral dict shape
consumed by :meth:`ServerCore.infer`; the core resolves shared-memory
placement, executes the model, tracks statistics, and applies the
classification extension.
"""

from __future__ import annotations

import base64
import json
import threading
import time
from collections import deque
from concurrent.futures import TimeoutError as FuturesTimeoutError
from typing import Any, Dict, List, Optional

import numpy as np

from ..models.base import Model
from ..utils import triton_to_np_dtype
from .timeline import (
    COMPILES,
    SPAN_BUILD_RESPONSE,
    SPAN_RESOLVE_INPUTS,
    Timeline,
    span,
)

_BUILTIN_SHM_FAMILIES = ("system", "cuda", "tpu")


class _Region:
    """A registered shared-memory region the server can read/write."""

    def __init__(
        self,
        name: str,
        family: str,
        key: str,
        offset: int,
        byte_size: int,
        device_id: int = 0,
        raw_handle: Optional[str] = None,
    ):
        self.name = name
        self.family = family
        self.key = key
        self.offset = offset
        self.byte_size = byte_size
        self.device_id = device_id
        self.raw_handle = raw_handle
        self._shm = None

    def _buffer(self) -> memoryview:
        if self._shm is None:
            from ..utils.shared_memory import attach_shared_memory

            self._shm = attach_shared_memory(self.key)
        return self._shm.buf

    def _check_range(self, nbytes: int, offset: int, op: str) -> int:
        if offset < 0 or nbytes < 0 or nbytes + offset > self.byte_size:
            raise ValueError(
                f"shared-memory {op} of {nbytes}B at offset {offset} exceeds "
                f"region '{self.name}' ({self.byte_size}B)"
            )
        return self.offset + offset

    def read(self, byte_size: int, offset: int) -> memoryview:
        base = self._check_range(byte_size, offset, "read")
        return self._buffer()[base : base + byte_size]

    def write(self, data: bytes, offset: int) -> None:
        base = self._check_range(len(data), offset, "write")
        self._buffer()[base : base + len(data)] = data

    def read_tensor(self, datatype: str, shape, byte_size: int, offset: int):
        """Materialize a tensor of ``datatype``/``shape`` from the region."""
        return _bytes_to_array(bytes(self.read(byte_size, offset)), datatype, shape)

    def write_tensor(self, arr, datatype: str, offset: int, limit: int, name: str = "?") -> int:
        """Serialize ``arr`` into the region; returns bytes written."""
        payload = _array_to_bytes(np.asarray(arr), datatype)
        if len(payload) > limit:
            raise InferError(
                f"output '{name}' ({len(payload)}B) exceeds shared-memory region "
                f"allotment of {limit}B", 400,
            )
        self.write(payload, offset)
        return len(payload)

    def close(self) -> None:
        if self._shm is not None:
            self._shm.close()
            self._shm = None

    def status(self) -> Dict[str, Any]:
        if self.family == "system":
            return {
                "name": self.name,
                "key": self.key,
                "offset": self.offset,
                "byte_size": self.byte_size,
            }
        return {
            "name": self.name,
            "device_id": self.device_id,
            "byte_size": self.byte_size,
        }


class _TpuRegion(_Region):
    """A registered tpu_shared_memory region — device-aware data plane.

    In-process registrations resolve to the client's own
    ``TpuSharedMemoryRegion`` object, so tensors bound with
    ``set_shared_memory_region_from_jax`` are handed to the model as live
    ``jax.Array``s (zero copies) and jax outputs are pinned back into the
    region's device cache the same way.
    """

    def __init__(self, name: str, raw_handle_b64: str, device_id: int, byte_size: int):
        from ..utils.tpu_shared_memory import attach_from_raw_handle

        self._region = attach_from_raw_handle(raw_handle_b64)
        super().__init__(
            name, "tpu", self._region.shm_key, 0, byte_size, device_id,
            raw_handle=raw_handle_b64,
        )

    def read(self, byte_size: int, offset: int) -> memoryview:
        return self._region.read_host(byte_size, offset)

    def write(self, data: bytes, offset: int) -> None:
        self._region.write_host(data, offset)

    def read_tensor(self, datatype: str, shape, byte_size: int, offset: int):
        if datatype == "BYTES":
            return super().read_tensor(datatype, shape, byte_size, offset)
        from ..utils import triton_to_np_dtype
        from ..utils.tpu_shared_memory import get_contents_as_jax

        nbytes = int(np.prod(shape)) * np.dtype(triton_to_np_dtype(datatype)).itemsize
        if nbytes > byte_size:
            raise InferError(
                f"shm input needs {nbytes}B for shape {list(shape)} {datatype} but "
                f"only {byte_size}B were supplied", 400,
            )
        return get_contents_as_jax(self._region, datatype, shape, offset)

    def write_tensor(self, arr, datatype: str, offset: int, limit: int, name: str = "?") -> int:
        from ..utils.tpu_shared_memory import (
            _is_jax_array,
            set_shared_memory_region_from_jax,
        )

        if datatype != "BYTES" and _is_jax_array(arr):
            nbytes = arr.dtype.itemsize * arr.size
            if nbytes > limit:
                raise InferError(
                    f"output '{name}' ({nbytes}B) exceeds shared-memory region "
                    f"allotment of {limit}B", 400,
                )
            set_shared_memory_region_from_jax(self._region, arr, offset)
            return nbytes
        return super().write_tensor(arr, datatype, offset, limit, name)

    def close(self) -> None:
        self._region.detach()


class _ModelStats:
    """What the statistics verb reports for one model. Every successful
    request counts once in each of Triton's four parts, which add up to its
    ``success`` ns (``Timeline.parts`` says what each holds for a batched
    model, for a decoupled one and for any other). Beside them, for the
    registry, what its requests read of the rounds that carried them
    (``Timeline.readings``)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.inference_count = 0
        self.execution_count = 0
        self.last_inference = 0
        self.success = [0, 0]  # count, ns
        self.fail = [0, 0]
        # client cancel/disconnect mid-stream: neither a success nor a
        # model failure (reference tracks cancelled requests separately)
        self.cancel = [0, 0]
        self.compute_input = [0, 0]
        self.queue = [0, 0]
        self.compute_infer = [0, 0]
        self.compute_output = [0, 0]
        self.batches: Dict[int, List[int]] = {}  # batch_size -> [count, ns]
        self.readings: Dict[str, List[int]] = {}  # reading -> [count, sum]

    def record(self, total_ns: int, parts, batch: int,
               executed: bool = True, readings=()) -> None:
        """One successful request. ``executed=False`` for a request a
        batcher carried: the execution is counted once by record_batch, not
        once per request (reference semantics: execution_count <
        inference_count under batching). ``readings``: the request's
        ``(name, count, sum)`` of ``Timeline.readings``."""
        with self.lock:
            for name, count, total in readings:
                row = self.readings.setdefault(name, [0, 0])
                row[0] += count
                row[1] += total
            self.inference_count += batch
            if executed:
                self.execution_count += 1
            self.last_inference = int(time.time() * 1000)
            self.success[0] += 1
            self.success[1] += total_ns
            for row, ns in zip((self.compute_input, self.queue,
                                self.compute_infer, self.compute_output),
                               parts):
                row[0] += 1
                row[1] += ns

    def record_fail(self, total_ns: int) -> None:
        with self.lock:
            self.fail[0] += 1
            self.fail[1] += total_ns

    def record_cancel(self, total_ns: int) -> None:
        with self.lock:
            self.cancel[0] += 1
            self.cancel[1] += total_ns
            self.last_inference = int(time.time() * 1000)

    def record_batch(self, batch_size: int, exec_ns: int) -> None:
        """One execution by a batcher (InferBatchStatistics feed): a batch
        of the dynamic batcher, a round of the sequence batcher."""
        with self.lock:
            row = self.batches.setdefault(batch_size, [0, 0])
            row[0] += 1
            row[1] += exec_ns
            self.execution_count += 1

    def as_dict(self, name: str, version: str) -> Dict[str, Any]:
        pair = lambda row: {"count": row[0], "ns": row[1]}
        with self.lock:
            return {
                "name": name,
                "version": version,
                "last_inference": self.last_inference,
                "inference_count": self.inference_count,
                "execution_count": self.execution_count,
                "inference_stats": {
                    "success": pair(self.success),
                    "fail": pair(self.fail),
                    "cancel": pair(self.cancel),
                    "queue": pair(self.queue),
                    "compute_input": pair(self.compute_input),
                    "compute_infer": pair(self.compute_infer),
                    "compute_output": pair(self.compute_output),
                },
                "batch_stats": [
                    {"batch_size": size, "compute_infer": pair(row)}
                    for size, row in sorted(self.batches.items())
                ],
            }


class InferError(Exception):
    """Server-side inference failure with an HTTP-ish status code."""

    def __init__(self, msg: str, status: int = 400):
        super().__init__(msg)
        self.status = status


class ServerCore:
    """Registry + data plane + execution; shared by all protocol frontends."""

    def __init__(self, models: Optional[List[Model]] = None, name: str = "client_tpu_server"):
        self._name = name
        self._lock = threading.Lock()
        self._models: Dict[str, Model] = {}
        self._stats: Dict[str, _ModelStats] = {}
        self._regions: Dict[str, _Region] = {}
        self._batchers: Dict[str, Any] = {}  # model name -> (max_batch, DynamicBatcher)
        self.batch_timeout_s = 60.0  # future wait for one batched request
        self.trace_settings: Dict[str, Any] = {
            "trace_level": ["OFF"],
            "trace_rate": "1000",
            "trace_count": "-1",
            "log_frequency": "0",
            "trace_file": "",
            "trace_mode": "triton",
        }
        self.log_settings: Dict[str, Any] = {
            "log_file": "",
            "log_info": True,
            "log_warning": True,
            "log_error": True,
            "log_verbose_level": 0,
            "log_format": "default",
        }
        self.live = True
        # ready is the DRAINABLE half of health: frontends flip it false on
        # drain/close so pool ready-probes route away while in-flight
        # requests still complete (live stays true until the process exits)
        self.ready = True
        # rolling per-request trace records, populated when trace_level
        # includes TIMESTAMPS (Triton writes these to trace_file; we keep a
        # ring buffer and mirror to trace_file when one is configured)
        self._traces: List[Dict[str, Any]] = []
        self._trace_seq = 0
        self._trace_candidates = 0
        # W3C trace-context access records: every request that arrived with
        # a (valid) traceparent gets a server-side span joined on the same
        # trace id, so client phase timings and server queue/compute
        # timings line up (client_tpu.observe; scraped via /metrics)
        self._access: deque = deque(maxlen=1024)
        self._metrics_registry = None
        # a timeline's marks are perf_counter_ns(); this pair places them on
        # the wall clock, which is the one the profiler's planes are on
        self.clock_anchor = (time.time_ns(), time.perf_counter_ns())
        COMPILES.listen()
        for m in models or []:
            self.add_model(m)

    # -- registry ----------------------------------------------------------
    def add_model(self, model: Model) -> None:
        with self._lock:
            self._models[model.name] = model
            stats = self._stats.setdefault(model.name, _ModelStats())
        if hasattr(model, "report_batch"):  # a model with a batcher of its own
            model.report_batch = stats.record_batch
        if hasattr(model, "bind"):  # ensembles resolve members at execute time
            model.bind(self.model)

    def model(self, name: str, version: str = "") -> Model:
        m = self._models.get(name)
        if m is None:
            raise InferError(f"Request for unknown model: '{name}' is not found", 400)
        if version and version not in m.versions:
            raise InferError(
                f"Request for unknown model: '{name}' version {version} is not found", 400
            )
        return m

    def model_ready(self, name: str, version: str = "") -> bool:
        try:
            return self.model(name, version).ready
        except InferError:
            return False

    def server_metadata(self) -> Dict[str, Any]:
        return {
            "name": self._name,
            "version": "2.x-client_tpu",
            "extensions": [
                "classification",
                "sequence",
                "model_repository",
                "model_repository(unload_dependents)",
                "schedule_policy",
                "model_configuration",
                "system_shared_memory",
                "cuda_shared_memory",
                "tpu_shared_memory",
                "binary_tensor_data",
                "parameters",
                "statistics",
                "trace",
                "logging",
            ],
        }

    def repository_index(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [
                {
                    "name": m.name,
                    "version": m.versions[-1],
                    "state": "READY" if m.ready else "UNAVAILABLE",
                    "reason": "",
                }
                for m in self._models.values()
            ]

    def load_model(self, name: str, config: Optional[str] = None) -> None:
        model = self.model(name)
        if config:
            try:
                override = json.loads(config)
            except Exception as e:
                raise InferError(f"invalid config override: {e}", 400)
            if not isinstance(override, dict):
                raise InferError("config override must be a JSON object", 400)
            if override.get("name", name) != name:
                raise InferError(
                    "config override cannot rename the model", 400
                )
        else:
            # Triton semantics: a plain load reverts to the repository config
            override = {}
        model.config_override = override
        model.load()

    def unload_model(self, name: str) -> None:
        self.model(name).unload()

    def statistics(self, name: str = "", version: str = "") -> Dict[str, Any]:
        with self._lock:
            names = [name] if name else list(self._models.keys())
        out = []
        for n in names:
            m = self.model(n)
            out.append(self._stats[n].as_dict(n, version or m.versions[-1]))
        return {"model_stats": out}

    def _trace_enabled(self) -> bool:
        """Honors trace_level plus the trace_rate (sample 1-in-N) and
        trace_count (stop after N, -1 = unlimited) settings."""
        level = self.trace_settings.get("trace_level", [])
        if "TIMESTAMPS" not in level and "TENSORS" not in level:
            return False
        with self._lock:
            try:
                rate = max(int(self.trace_settings.get("trace_rate", 1) or 1), 1)
                count = int(self.trace_settings.get("trace_count", -1))
            except (TypeError, ValueError):
                rate, count = 1, -1
            if count >= 0 and self._trace_seq >= count:
                return False
            self._trace_candidates += 1
            return (self._trace_candidates - 1) % rate == 0

    def _record(self, model_name: str, request: Dict[str, Any],
                tl: Timeline, batch: int = 1) -> None:
        """The one end-of-request recorder: the statistics verb, the Triton
        trace record (``trace_level`` ``TIMESTAMPS``, sampled by
        ``trace_rate`` / ``trace_count``, mirrored to ``trace_file``) and the
        ``traceparent`` access record all read the request's timeline."""
        tl.close()
        parts = tl.parts()
        self._stats[model_name].record(
            tl.done - tl.recv, parts, batch, executed=tl.batch is None,
            readings=tl.readings())
        ids = None
        if request.get("traceparent"):
            from ..observe import parse_traceparent

            ids = parse_traceparent(request["traceparent"])
        if self._trace_enabled():
            self._record_trace(model_name, request, tl, ids)
        if ids is not None:
            self._record_access(model_name, request, tl, parts, ids)

    def _record_trace(self, model_name: str, request: Dict[str, Any],
                      tl: Timeline, ids) -> None:
        """Every mark of the timeline under its name (beside Triton's four
        timestamps), what was counted on the way, and the identifiers that
        spans of one request share: the request id, the sequence id, the
        ``traceparent`` ids where it had one, its first round's id."""
        counts = tl.counts()
        record = {
            "id": None,  # its place in the ring, given under the lock
            "model_name": model_name,
            "request_id": request.get("id", ""),
            "sequence_id": request.get("parameters", {}).get("sequence_id", 0),
            "first_round_id": counts.get("first_round_id"),
            "timestamps": {
                "request_start_ns": tl.recv,
                "compute_start_ns": tl.model_enter,
                "compute_end_ns": tl.model_exit,
                "request_end_ns": tl.done,
                **tl.marks(),
            },
            "counts": counts,
            "clock_anchor": {"wall_ns": self.clock_anchor[0],
                             "perf_ns": self.clock_anchor[1]},
        }
        if ids is not None:
            record["trace_id"], record["client_span_id"] = ids[0], ids[1]
        with self._lock:
            self._trace_seq += 1
            record["id"] = self._trace_seq
            self._traces.append(record)
            if len(self._traces) > 1024:
                del self._traces[: len(self._traces) - 1024]
            trace_file = self.trace_settings.get("trace_file")
        if trace_file:
            try:
                with open(trace_file, "a") as f:
                    f.write(json.dumps(record) + "\n")
            except OSError:
                pass

    def recent_traces(self, count: int = 100) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._traces[-count:])

    # -- observability (client_tpu.observe counterpart) ----------------------
    def _record_access(self, model_name: str, request: Dict[str, Any],
                       tl: Timeline, parts, ids) -> None:
        """A server-side span for a request that carried a W3C
        ``traceparent`` (frontends stash the header/metadata value under
        the reserved ``traceparent`` request key). ``client_span_id`` is
        the parent id from the header, the client's request span, so one
        trace id joins client phases to the server's timings: ``queue_ns``
        is the timeline's queue (0 where the model has none) and
        ``compute_ns`` its compute_infer. Streamed (decoupled) requests
        additionally carry the server-side first-response latency, the join
        target for the client's StreamSpan TTFT."""
        from ..observe import make_span_id

        record = {
            "trace_id": ids[0],
            "client_span_id": ids[1],
            "server_span_id": make_span_id(),
            "model_name": model_name,
            "request_id": request.get("id", ""),
            "queue_ns": parts[1],
            "compute_ns": parts[2],
            "total_ns": tl.done - tl.recv,
            "responses": tl.responses,
            "wall_time_s": time.time(),
        }
        if tl.first_response is not None:
            record["first_response_ns"] = tl.first_response - tl.recv
        with self._lock:
            self._access.append(record)

    def access_records(self, count: int = 100) -> List[Dict[str, Any]]:
        """The most recent traceparent-joined server spans (newest last)."""
        with self._lock:
            return list(self._access)[-count:]

    def metrics_registry(self):
        """The server's ``observe.MetricsRegistry`` (created on first use):
        live/ready gauges plus per-model request/latency series refreshed
        from the model statistics at scrape time. Both HTTP frontends serve
        its Prometheus rendering at ``GET /metrics``."""
        with self._lock:
            if self._metrics_registry is not None:
                return self._metrics_registry
        from ..observe import MetricsRegistry

        reg = MetricsRegistry()
        live = reg.gauge(
            "client_tpu_server_live", "Server liveness (1 live)")
        ready = reg.gauge(
            "client_tpu_server_ready",
            "Server readiness (0 while draining; live stays 1)")
        gauges = {
            "inference_count": reg.gauge(
                "client_tpu_server_inference_count",
                "Inferences completed (batched requests each count)",
                ("model",)),
            "execution_count": reg.gauge(
                "client_tpu_server_execution_count",
                "Model executions (execution < inference under batching)",
                ("model",)),
            "success": reg.gauge(
                "client_tpu_server_request_success_count",
                "Successful requests", ("model",)),
            "fail": reg.gauge(
                "client_tpu_server_request_fail_count",
                "Failed requests", ("model",)),
            "cancel": reg.gauge(
                "client_tpu_server_request_cancel_count",
                "Client-cancelled/abandoned streaming requests", ("model",)),
            "queue_seconds": reg.gauge(
                "client_tpu_server_queue_seconds",
                "Cumulative batching-queue wait", ("model",)),
            "compute_seconds": reg.gauge(
                "client_tpu_server_compute_seconds",
                "Cumulative model compute time", ("model",)),
        }
        traced = reg.gauge(
            "client_tpu_server_traced_requests",
            "Traceparent-joined access records currently buffered")
        compile_count = reg.gauge(
            "client_tpu_server_compile_count",
            "XLA compiles in this process since the first server core")
        compile_seconds = reg.gauge(
            "client_tpu_server_compile_seconds",
            "Cumulative XLA compile time in this process")

        decode_steps = reg.gauge(
            "client_tpu_server_decode_steps",
            "Decode steps dispatched, by the rung of the decoder's ladder "
            "they read: the live positions of the cache",
            ("model", "live"))
        # a model whose streams share a round (models/stream_rounds.py)
        stream_rounds = reg.gauge(
            "client_tpu_server_stream_rounds",
            "Rounds dispatched for a model's streams, by their width: the "
            "leading slots of the table whose caches the attention read",
            ("model", "width"))
        stream_slot_waits = reg.gauge(
            "client_tpu_server_stream_slot_waits",
            "Streams that found no free slot at their admission and waited "
            "for one", ("model",))
        # what the decoder counts beside its steps (models/decoder.py:
        # RungCount.TOTALS and ROWS), one series a served model each
        decoder_totals = {
            "selecting_steps": reg.gauge(
                "client_tpu_server_selecting_steps",
                "Decode steps that attended to a chosen subset of the cache "
                "(a step past the indexer's topk positions)", ("model",)),
            "prefill_tokens": reg.gauge(
                "client_tpu_server_prefill_tokens",
                "Prompt tokens prefilled", ("model",)),
            "prefill_chunks": reg.gauge(
                "client_tpu_server_prefill_chunks",
                "Prefill dispatches (a decoder without a prefill program: "
                "one a token)", ("model",)),
            "prefill_ns": reg.gauge(
                "client_tpu_server_prefill_ns",
                "Host time of the streams' prefills, cache_ready to "
                "prefill_done", ("model",)),
            "window_rows_read": reg.gauge(
                "client_tpu_server_window_rows_read",
                "Rows of the exact window that decode steps attended to "
                "(a decoder with a window beside summaries)", ("model",)),
            "summary_rows_read": reg.gauge(
                "client_tpu_server_summary_rows_read",
                "Chunk summaries that decode steps attended to", ("model",)),
            "summaries_written": reg.gauge(
                "client_tpu_server_summaries_written",
                "Chunk summaries written, by prompts' chunks and by decode "
                "steps", ("model",)),
        }

        # what a decoder's routed layers read (RungCount.reached): a series
        # a served model and program only where its dispatches tally it
        experts_reached = reg.gauge(
            "client_tpu_server_experts_reached",
            "Distinct experts the grouped products of a program's routed "
            "layers read, summed over the layers and the dispatches",
            ("model", "program"))
        experts_reached_rounds = reg.gauge(
            "client_tpu_server_experts_reached_rounds",
            "Dispatches of the program over which experts_reached is summed",
            ("model", "program"))

        # the rows a model's rounds wrote into its table (RungCount.written)
        rows_written = reg.gauge(
            "client_tpu_server_rows_written",
            "Key and value rows that a model's rounds wrote into its table of "
            "caches, counted at dispatch, by how they were written: kernel "
            "(one DMA kernel a layer) or loop (a turn a member)",
            ("model", "path"))

        # the turns of a model's round worker by phase (timeline.PHASES), and
        # what its requests read of the rounds (Timeline.readings): a pair
        # of series each, a sum and the count it is over
        round_phase_ns = reg.gauge(
            "client_tpu_server_round_phase_ns",
            "Host time of the round worker's turns, by phase; the phases "
            "but wait_work add up to the turns", ("model", "phase"))
        round_phase_count = reg.gauge(
            "client_tpu_server_round_phase_count",
            "Times the round worker went through a phase; dispatch counts "
            "the rounds", ("model", "phase"))
        readings = {
            name: (reg.gauge(f"client_tpu_server_{name}_{unit}",
                             what + ", summed", ("model",)),
                   reg.gauge(f"client_tpu_server_{name}_count",
                             what + ": how many", ("model",)))
            for name, unit, what in (
                ("sequence_stride", "rounds", "Rounds from the token before "
                 "to a continuation request's own round (1: no round missed)"),
                ("answer_wake", "ns", "A batched request's future set to its "
                 "response built"),
                ("first_response", "ns", "A stream's entry into the core to "
                 "its first response built"),
                ("token_handoff", "ns", "The end of a round's read-back to "
                 "the stream's own thread holding its token"))}

        def collect():
            live.set(1.0 if self.live else 0.0)
            ready.set(1.0 if (self.live and self.ready) else 0.0)
            for row in self.statistics()["model_stats"]:
                model = row["name"]
                gauges["inference_count"].labels(model).set(
                    row["inference_count"])
                gauges["execution_count"].labels(model).set(
                    row["execution_count"])
                stats = row["inference_stats"]
                gauges["success"].labels(model).set(stats["success"]["count"])
                gauges["fail"].labels(model).set(stats["fail"]["count"])
                gauges["cancel"].labels(model).set(stats["cancel"]["count"])
                gauges["queue_seconds"].labels(model).set(
                    stats["queue"]["ns"] / 1e9)
                gauges["compute_seconds"].labels(model).set(
                    stats["compute_infer"]["ns"] / 1e9)
            with self._lock:
                traced.set(len(self._access))
                models = list(self._models.items())
                stats = list(self._stats.items())
            for name, model_stats in stats:
                with model_stats.lock:
                    read = {k: tuple(v) for k, v in model_stats.readings.items()}
                for reading, (count, total) in read.items():
                    readings[reading][0].labels(name).set(total)
                    readings[reading][1].labels(name).set(count)
            for name, model in models:
                phases = getattr(model, "phases", None)
                if phases is not None:  # a model with a round worker
                    for phase, (count, ns) in phases.rows().items():
                        round_phase_ns.labels(name, phase).set(ns)
                        round_phase_count.labels(name, phase).set(count)
                count = getattr(model, "steps_by_rung", None)
                if count is not None:  # a model that steps a decoder
                    for rung, steps in count.by_rung().items():
                        decode_steps.labels(name, rung).set(steps)
                    for total, value in {**count.totals(), **count.rows()}.items():
                        decoder_totals[total].labels(name).set(value)
                    for program, (reached, n) in count.reached().items():
                        experts_reached.labels(name, program).set(reached)
                        experts_reached_rounds.labels(name, program).set(n)
                    for path, rows in count.written().items():
                        rows_written.labels(name, path).set(rows)
                rounds = getattr(model, "rounds_by_width", None)
                if rounds:  # it has run rounds: the slot table is in use
                    for width, n in dict(rounds).items():
                        stream_rounds.labels(name, width).set(n)
                    stream_slot_waits.labels(name).set(model.slot_waits)
            compile_count.set(COMPILES.count)
            compile_seconds.set(COMPILES.ns / 1e9)

        reg.add_collector(collect)
        with self._lock:
            if self._metrics_registry is None:
                self._metrics_registry = reg
            return self._metrics_registry

    def orca_report(self, fmt: str, model_name: str = "") -> str:
        """Per-response load metrics in ORCA json or text form."""
        stats = self._stats.get(model_name)
        count = infer_ns = 0
        if stats is not None:
            with stats.lock:
                count = stats.inference_count
                infer_ns = (
                    stats.compute_infer[1] // max(stats.compute_infer[0], 1)
                )
        metrics = {
            "inference_count": count,
            "avg_compute_infer_us": infer_ns // 1000,
            "active_models": len(self._models),
        }
        if fmt == "json":
            return json.dumps({"named_metrics": metrics}, separators=(",", ":"))
        return ", ".join(f"named_metrics.{k}={v}" for k, v in metrics.items())

    # -- shared memory -----------------------------------------------------
    def register_system_region(self, name: str, key: str, offset: int, byte_size: int) -> None:
        self._register(_Region(name, "system", key, offset, byte_size))

    def register_handle_region(
        self, family: str, name: str, raw_handle_b64: str, device_id: int, byte_size: int
    ) -> None:
        """Register a tpu (or cuda-format) region from its serialized handle.

        tpu raw handles are base64 JSON descriptors produced by
        ``utils.tpu_shared_memory.get_raw_handle`` and carry the host shm key
        of the region's host window.
        """
        if family == "tpu":
            try:
                region: _Region = _TpuRegion(name, raw_handle_b64, device_id, byte_size)
            except Exception as e:
                raise InferError(f"failed to attach tpu shared-memory region: {e}", 400)
        else:
            try:
                desc = json.loads(base64.b64decode(raw_handle_b64))
                key = desc["shm_key"]
            except Exception as e:
                raise InferError(
                    f"failed to decode {family} shared-memory handle: {e}", 400
                )
            region = _Region(
                name,
                family,
                key,
                int(desc.get("offset", 0)),
                byte_size,
                device_id,
                raw_handle=raw_handle_b64,
            )
        self._register(region)

    def _register(self, region: _Region) -> None:
        with self._lock:
            existing = self._regions.get(region.name)
            if existing is not None:
                # Triton semantics: an active name must be unregistered first
                region.close()
                raise InferError(
                    f"shared memory region '{region.name}' already in manager",
                    400,
                )
            self._regions[region.name] = region

    def unregister_region(self, name: str = "", family: Optional[str] = None) -> None:
        with self._lock:
            if name:
                r = self._regions.pop(name, None)
                if r is not None:
                    r.close()
            else:
                for key in list(self._regions):
                    if family is None or self._regions[key].family == family:
                        self._regions.pop(key).close()

    def region_status(self, family: str, name: str = "") -> List[Dict[str, Any]]:
        with self._lock:
            return [
                r.status()
                for r in self._regions.values()
                if r.family == family and (not name or r.name == name)
            ]

    def _region(self, name: str) -> _Region:
        with self._lock:
            r = self._regions.get(name)
        if r is None:
            raise InferError(
                f"Unable to find shared memory region: '{name}'", 400
            )
        return r

    # -- inference ---------------------------------------------------------
    def infer(self, model_name: str, model_version: str, request: Dict[str, Any],
              decoupled_ok: bool = False):
        """Execute one inference.

        ``request``: {"id", "parameters", "inputs": [...], "outputs": [...]}
        where each input dict has name/datatype/shape plus exactly one of
        "array" (host ndarray) or "shm" ((region, byte_size, offset)).

        Returns a list of response dicts (len>1 only for decoupled models);
        each response: {"model_name","model_version","id","parameters",
        "outputs": [{name, datatype, shape, "array"|"shm"}]}.
        """
        tl = Timeline()
        model = self.model(model_name, model_version)
        if not model.ready:
            raise InferError(f"Request for unknown model: '{model_name}' is not ready", 400)
        if model.decoupled and not decoupled_ok:
            raise InferError(
                f"model '{model_name}' is a decoupled model: use streaming inference", 400
            )
        if model.decoupled:
            # delegate to the incremental generator (it owns the recording
            # for the decoupled path); materializing here keeps infer()'s
            # list-of-responses contract
            return list(self._decoupled_stream(
                model, model_name, model_version, request, tl))
        stats = self._stats[model_name]
        try:
            with span(SPAN_RESOLVE_INPUTS) as s:
                inputs = self._resolve_inputs(model, request)
            tl.inputs_resolved = s.end_ns
            params = request.get("parameters", {})
            tl.model_enter = time.perf_counter_ns()
            if self._batchable(model, params):
                try:
                    raw = self._batcher_for(model).submit(
                        inputs, params, tl).result(timeout=self.batch_timeout_s)
                except FuturesTimeoutError:
                    raise InferError(
                        f"batched inference timed out after "
                        f"{self.batch_timeout_s:.0f}s (the execution may "
                        f"still complete server-side; raise "
                        f"core.batch_timeout_s for cold-compile workloads)",
                        504,
                    )
            else:
                with tl:  # the model's code finds it as timeline.current()
                    raw = model.execute(inputs, params)
            tl.model_exit = time.perf_counter_ns()
        except InferError:
            stats.record_fail(time.perf_counter_ns() - tl.recv)
            raise
        except Exception as e:
            stats.record_fail(time.perf_counter_ns() - tl.recv)
            raise InferError(f"inference failed: {e}", 400)

        with span(SPAN_BUILD_RESPONSE) as s:
            response = self._build_response(model, model_version, request, raw)
        tl.done = s.end_ns
        tl.responses = 1
        batch = 1
        if model.effective_max_batch_size():
            first = next(iter(raw.values()))
            batch = int(first.shape[0]) if first.ndim else 1
        self._record(model_name, request, tl, batch)
        return [response]

    def infer_stream(self, model_name: str, model_version: str,
                     request: Dict[str, Any]):
        """Incremental inference: a generator yielding response dicts AS the
        model produces them. For decoupled models every yield reaches the
        caller before the next response is computed — a streaming frontend
        that forwards each yield gives true time-to-first-token (the
        reference's decoupled transaction policy streams the same way:
        TRITONBACKEND_ResponseSend per response, not a batch at the end).
        Non-decoupled models yield their single infer() response."""
        model = self.model(model_name, model_version)
        if not model.decoupled:
            yield from self.infer(model_name, model_version, request)
            return
        if not model.ready:
            raise InferError(
                f"Request for unknown model: '{model_name}' is not ready", 400)
        yield from self._decoupled_stream(
            model, model_name, model_version, request, Timeline())

    def _decoupled_stream(self, model: Model, model_name: str,
                          model_version: str, request: Dict[str, Any],
                          tl: Timeline):
        """Drive ``execute_decoupled`` lazily, building + yielding each
        response as it is produced. Owns the recording for the whole
        decoupled request (exactly-once, whether it completes, fails
        mid-stream, or the consumer abandons the generator)."""
        stats = self._stats[model_name]
        try:
            with span(SPAN_RESOLVE_INPUTS) as s:
                inputs = self._resolve_inputs(model, request)
            tl.inputs_resolved = s.end_ns
            params = request.get("parameters", {})
        except InferError:
            stats.record_fail(time.perf_counter_ns() - tl.recv)
            raise
        except Exception as e:
            stats.record_fail(time.perf_counter_ns() - tl.recv)
            raise InferError(f"inference failed: {e}", 400)

        tl.model_enter = time.perf_counter_ns()
        gen = iter(model.execute_decoupled(inputs, params))
        try:
            while True:
                # the timeline is current while the model's generator runs
                # and not while this one is suspended at its yield
                try:
                    with tl:
                        raw = next(gen)
                except StopIteration:
                    break
                with span(SPAN_BUILD_RESPONSE) as s:
                    response = self._build_response(
                        model, model_version, request, raw)
                if tl.first_response is None:
                    tl.first_response = s.end_ns
                tl.responses += 1
                yield response
        except GeneratorExit:
            # consumer went away mid-stream (client cancel/disconnect):
            # a separate cancel bucket — counting it as success made
            # abandonment indistinguishable from completed generations
            stats.record_cancel(time.perf_counter_ns() - tl.recv)
            raise
        except InferError:
            stats.record_fail(time.perf_counter_ns() - tl.recv)
            raise
        except Exception as e:
            stats.record_fail(time.perf_counter_ns() - tl.recv)
            raise InferError(f"inference failed: {e}", 400)
        tl.model_exit = time.perf_counter_ns()
        # inference_count counts the REQUEST once, regardless of how many
        # responses streamed (reference decoupled semantics: response count
        # != request count)
        self._record(model_name, request, tl)

    # -- dynamic batching ---------------------------------------------------
    def _batchable(self, model: Model, params: Dict[str, Any]) -> bool:
        """Coalescing is for stateless, non-sequence, non-decoupled models
        that declared batch capacity; sequence requests must never merge."""
        return (
            model.effective_max_batch_size() > 1
            and not model.decoupled
            and not getattr(model, "stateful", False)
            and not params.get("sequence_id")
        )

    def _batcher_for(self, model: Model):
        from .batcher import DynamicBatcher

        max_batch = model.effective_max_batch_size()
        stale = None
        with self._lock:
            entry = self._batchers.get(model.name)
            if entry is not None and entry[0] == max_batch:
                return entry[1]
            stale = entry[1] if entry is not None else None
            stats = self._stats[model.name]
            batcher = DynamicBatcher(
                model.execute, max_batch, report=stats.record_batch)
            self._batchers[model.name] = (max_batch, batcher)
        if stale is not None:
            # max_batch_size changed via load override; close OUTSIDE the
            # core lock — close() joins the worker (seconds under load) and
            # every server operation takes this lock
            stale.close()
        return batcher

    def _resolve_inputs(self, model: Model, request: Dict[str, Any]) -> Dict[str, np.ndarray]:
        specs = {s.name: s for s in model.inputs()}
        out: Dict[str, np.ndarray] = {}
        for inp in request.get("inputs", []):
            name = inp["name"]
            spec = specs.get(name)
            if spec is None:
                raise InferError(
                    f"unexpected inference input '{name}' for model '{model.name}'", 400
                )
            datatype = inp.get("datatype", spec.datatype)
            if datatype != spec.datatype:
                raise InferError(
                    f"inference input '{name}' has datatype {datatype}; "
                    f"model expects {spec.datatype}", 400,
                )
            shape = inp.get("shape", [])
            if not spec.matches(shape):
                raise InferError(
                    f"unexpected shape {shape} for input '{name}' "
                    f"(model expects {spec.shape})", 400,
                )
            shm = inp.get("shm")
            if shm is not None:
                region_name, byte_size, offset = shm
                region = self._region(region_name)
                try:
                    region._check_range(byte_size, offset, "read")
                except ValueError as e:
                    raise InferError(str(e), 400)
                out[name] = region.read_tensor(datatype, shape, byte_size, offset)
            else:
                arr = inp.get("array")
                if arr is None:
                    raise InferError(f"input '{name}' has no data", 400)
                out[name] = arr
        missing = {s for s in set(specs) - set(out) if not specs[s].optional}
        if missing:
            raise InferError(
                f"expected {len(specs)} inputs but got {len(out)} inputs for "
                f"model '{model.name}' (missing: {sorted(missing)})", 400,
            )
        return out

    def _build_response(
        self, model: Model, model_version: str, request: Dict[str, Any],
        raw: Dict[str, np.ndarray],
    ) -> Dict[str, Any]:
        requested = request.get("outputs")
        out_specs: List[Dict[str, Any]] = []
        if requested:
            for r in requested:
                if r["name"] not in raw:
                    raise InferError(
                        f"unexpected inference output '{r['name']}' for model "
                        f"'{model.name}'", 400,
                    )
                out_specs.append(r)
        else:
            out_specs = [{"name": n} for n in raw.keys()]

        outputs = []
        for spec in out_specs:
            name = spec["name"]
            arr = raw[name]  # np.ndarray or jax.Array; stays on device if jax
            class_count = spec.get("classification", 0)
            if class_count:
                arr = _classification(
                    arr, class_count, model.labels(),
                    batched=model.effective_max_batch_size() > 0,
                )
                datatype = "BYTES"
            else:
                from ..utils import np_to_triton_dtype

                datatype = np_to_triton_dtype(arr.dtype)
            entry: Dict[str, Any] = {
                "name": name,
                "datatype": datatype,
                "shape": list(arr.shape),
            }
            shm = spec.get("shm")
            if shm is not None:
                region_name, byte_size, offset = shm
                written = self._region(region_name).write_tensor(
                    arr, datatype, offset, byte_size, name
                )
                entry["shm"] = (region_name, written, offset)
            else:
                entry["array"] = np.asarray(arr)
            outputs.append(entry)
        resp: Dict[str, Any] = {
            "model_name": model.name,
            "model_version": model_version or model.versions[-1],
            "outputs": outputs,
        }
        if request.get("id"):
            resp["id"] = request["id"]
        return resp


def _bytes_to_array(buf: bytes, datatype: str, shape) -> np.ndarray:
    from ..utils import deserialize_bf16_tensor, deserialize_bytes_tensor

    if datatype == "BYTES":
        return deserialize_bytes_tensor(buf).reshape(shape)
    if datatype == "BF16":
        return deserialize_bf16_tensor(buf).reshape(shape)
    return np.frombuffer(buf, dtype=triton_to_np_dtype(datatype)).reshape(shape)


def _array_to_bytes(arr: np.ndarray, datatype: str) -> bytes:
    from ..utils import serialize_bf16_tensor, serialize_byte_tensor

    if datatype == "BYTES":
        s = serialize_byte_tensor(arr)
        return s.item() if s.size else b""
    if datatype == "BF16":
        s = serialize_bf16_tensor(arr)
        return s.item() if s.size else b""
    return np.ascontiguousarray(arr).tobytes()


def _classification(
    arr, k: int, labels: Optional[List[str]], batched: bool = False
) -> np.ndarray:
    """classification extension: top-k "value:index[:label]" strings.

    Triton semantics: for batched models the first dim is the batch and each
    element's (flattened) remainder is its class vector; for non-batched
    models the whole (flattened) tensor is one class vector — e.g. densenet's
    [1000,1,1] output.

    When the model returned a device-resident jax.Array (the XLA model zoo
    does), ranking runs on-device via ``ops.topk_classification`` and only
    the k winners cross to the host — instead of pulling the whole class
    vector back for a host argsort. Device dtypes are <=32-bit under the
    default jax config, so no precision caveat applies on that path; ties
    break lowest-index-first there (a stable descending sort), while the
    host path keeps its historical highest-index-first order.
    """
    on_device = type(arr).__module__.startswith(("jax", "jaxlib"))
    if batched and arr.ndim >= 1:
        flat_batch = arr.reshape((arr.shape[0], -1))
    else:
        flat_batch = arr.reshape((1, -1))
    k = min(k, flat_batch.shape[-1])
    if on_device:
        from ..ops import topk_classification

        values, indices = topk_classification(flat_batch, k)
        values, indices = np.asarray(values), np.asarray(indices)
    else:
        flat_batch = np.asarray(flat_batch)
        indices = np.argsort(flat_batch, axis=-1)[:, ::-1][:, :k]
        values = np.take_along_axis(flat_batch, indices, axis=-1)
    rows = []
    for row_values, row_indices in zip(values, indices):
        entries = []
        for value, i in zip(row_values, row_indices):
            s = f"{value:f}:{i}"
            if labels and i < len(labels):
                s += f":{labels[i]}"
            entries.append(s.encode("utf-8"))
        rows.append(entries)
    out = np.array(rows, dtype=np.object_)
    if not batched:
        return out.reshape(-1)
    return out.reshape((arr.shape[0], k))
