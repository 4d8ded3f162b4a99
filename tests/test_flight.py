"""Flight-recorder tests (ISSUE 13).

Covers the tentpole surface: verdict matrix (error/shed/SLO-breach/
slow-threshold/reservoir retain; fast-healthy drops wholesale), the
bounded retained ring under 16-thread + asyncio load, cross-layer causal
stitching end-to-end on all four frontends and through the full
cache -> batch -> pool -> frontend composition, stream commits, the
attribution/tail-divergence detector, postmortem bundle round-trip, the
disabled-path no-op, the OpenMetrics exemplar opt-in (satellite), the
Tracer concurrent-dump ordering fix (satellite), the perf ``--flight``
row (satellite), the committed BENCH_FLIGHT.json claims (satellite), and
the ``flight_smoke`` chaos marker: a latency-faulted replica in a
3-replica pool is NAMED by the retained timelines.
"""

import asyncio
import json
import random
import threading
import time

import numpy as np
import pytest

import client_tpu.grpc as grpcclient
import client_tpu.http as httpclient
from client_tpu import flight
from client_tpu.flight import FlightRecorder, FlightTimeline
from client_tpu.models import default_model_zoo
from client_tpu.observe import (
    MetricsRegistry,
    RequestSpan,
    StreamSpan,
    Telemetry,
    Tracer,
)
from client_tpu.resilience import CircuitOpenError
from client_tpu.server import (
    AioHttpInferenceServer,
    GrpcInferenceServer,
    HttpInferenceServer,
    ServerCore,
)
from client_tpu.testing import ChaosProxy, Fault
from client_tpu.utils import InferenceServerException

SEEDED = lambda: random.Random(0xF11647)  # noqa: E731


def _simple_inputs(mod):
    a = np.arange(16, dtype=np.int32).reshape(1, 16)
    b = np.ones((1, 16), dtype=np.int32)
    in0 = mod.InferInput("INPUT0", [1, 16], "INT32").set_data_from_numpy(a)
    in1 = mod.InferInput("INPUT1", [1, 16], "INT32").set_data_from_numpy(b)
    return a + b, [in0, in1]


def _recorder(**kw):
    kw.setdefault("rng", SEEDED())
    return FlightRecorder(**kw)


# -- unit: scratch lifecycle ---------------------------------------------------
def test_note_without_scratch_is_noop():
    assert flight.active_scratch() is None
    flight.note("pool", "route", url="u")  # must not raise, must not leak
    assert flight.active_scratch() is None


def test_nested_begin_returns_none_and_inner_notes_land_on_outer():
    rec = _recorder(baseline_ratio=1.0)
    outer = rec.begin("cache", "m")
    assert outer is not None
    assert rec.begin("pool", "m") is None  # nested layer: note-only
    flight.note("pool", "route", url="u")
    assert rec.commit(outer) == "baseline"
    [t] = rec.retained()
    assert [(e[1], e[2]) for e in t.events] == [("pool", "route")]
    assert flight.active_scratch() is None


def test_commit_idempotent_and_clears_context():
    rec = _recorder(baseline_ratio=1.0)
    scratch = rec.begin("pool", "m")
    assert rec.commit(scratch) == "baseline"
    assert rec.commit(scratch) is None  # double commit: counted no-op
    assert flight.active_scratch() is None
    assert rec.stats()["requests"] == 1
    # post-commit notes must never mutate the retained timeline
    [t] = rec.retained()
    n = len(t.events)
    token = flight._SCRATCH.set(scratch)  # simulate a stale context copy
    try:
        flight.note("pool", "route")
    finally:
        flight._SCRATCH.reset(token)
    assert len(t.events) == n


def test_disabled_recorder_begins_nothing():
    rec = _recorder()
    rec.enabled = False
    assert rec.begin("pool", "m") is None
    tel = Telemetry(rng=SEEDED())  # no flight at all
    span = tel.begin("http", "m")
    tel.finish(span)  # must not touch flight machinery
    assert getattr(span, "flight", None) is None


def test_max_events_truncates_not_grows():
    rec = _recorder(baseline_ratio=1.0, max_events=8)
    scratch = rec.begin("pool", "m")
    for i in range(50):
        flight.note("pool", "route", attempt=i)
    rec.commit(scratch)
    [t] = rec.retained()
    assert len(t.events) == 8
    assert t.truncated == 42


# -- unit: verdicts ------------------------------------------------------------
def test_verdict_matrix():
    rec = _recorder(baseline_ratio=0.0, slo_ms=50.0,
                    threshold_min_samples=10**9)
    # error
    s = rec.begin("pool", "m")
    assert rec.commit(s, error=RuntimeError("boom")) == "error"
    # shed: the typed admission rejection (status-matched, like perf)
    s = rec.begin("pool", "m")
    shed_exc = InferenceServerException("shed", status="ADMISSION_REJECTED")
    assert rec.commit(s, error=shed_exc) == "shed"
    # a breaker fast-fail counts as shed too, not error
    s = rec.begin("pool", "m")
    assert rec.commit(s, error=CircuitOpenError()) == "shed"
    # slo breach: healthy but over the declared objective
    s = rec.begin("pool", "m")
    s.start_ns -= int(60e6)  # pretend 60 ms elapsed
    assert rec.commit(s) == "slo_breach"
    # fast healthy: dropped wholesale
    s = rec.begin("pool", "m")
    assert rec.commit(s) is None
    stats = rec.stats()
    assert stats["retained"] == {
        "error": 1, "shed": 2, "slo_breach": 1, "slow": 0,
        "disrupted": 0, "baseline": 0, "mark": 0}
    assert stats["dropped"] == 1
    assert rec.stats()["retained_fraction"] == 0.8


def test_rolling_slow_threshold_retains_the_tail():
    rec = _recorder(baseline_ratio=0.0, slow_quantile=0.9,
                    threshold_min_samples=64)
    for _ in range(200):  # teach it what normal looks like (~0 ms)
        rec.commit(rec.begin("pool", "m"))
    assert rec.stats()["slow_threshold_ms"] is not None
    s = rec.begin("pool", "m")
    s.start_ns -= int(25e6)  # 25 ms: far beyond the learned p90
    assert rec.commit(s) == "slow"
    # training traffic's own ~p90 stragglers may retain too (that IS the
    # slowest-percentile mechanism); the injected 25 ms one must be there
    slows = [t for t in rec.retained() if t.verdict == "slow"]
    assert any(t.duration_ms >= 25.0 for t in slows)


def test_baseline_reservoir_samples_healthy_traffic():
    rec = _recorder(baseline_ratio=1.0)
    rec.commit(rec.begin("pool", "m"))
    assert [t.verdict for t in rec.retained()] == ["baseline"]
    assert rec.last_anomalies() == []  # baseline is NOT an anomaly


# -- unit: the bounded ring ----------------------------------------------------
def test_ring_bound_under_threads_and_asyncio():
    rec = _recorder(capacity=64, baseline_ratio=1.0)

    def worker():
        for i in range(500):
            s = rec.begin("pool", "m")
            flight.note("pool", "route", attempt=i)
            rec.commit(s)

    async def aio_worker():
        for i in range(250):
            s = rec.begin("pool", "m")
            flight.note("pool", "route", attempt=i)
            rec.commit(s)
            if i % 50 == 0:
                await asyncio.sleep(0)

    async def aio_main():
        await asyncio.gather(*(aio_worker() for _ in range(4)))

    threads = [threading.Thread(target=worker) for _ in range(16)]
    aio_thread = threading.Thread(target=lambda: asyncio.run(aio_main()))
    for t in threads + [aio_thread]:
        t.start()
    for t in threads + [aio_thread]:
        t.join()
    stats = rec.stats()
    expected = 16 * 500 + 4 * 250
    assert stats["requests"] == expected
    assert stats["retained_total"] == expected  # all-retained soak
    assert stats["ring"] == 64  # bounded: never grows past capacity
    assert stats["evicted"] == expected - 64
    seqs = [t.seq for t in rec.retained()]
    assert seqs == sorted(seqs)  # oldest-first snapshot
    assert min(seqs) > 1  # the oldest timelines were evicted


# -- unit: attribution & tail divergence --------------------------------------
def _timeline(verdict, segments, model="m"):
    """A synthetic retained timeline: ``segments`` = [(layer, url, ms)]
    laid out back-to-back."""
    scratch = flight._Scratch("pool", model, "infer", 512)
    t0 = scratch.start_ns
    offset = 0
    for layer, url, ms in segments:
        attrs = {"url": url} if url else None
        scratch.events.append((t0 + offset, layer, "step", attrs))
        offset += int(ms * 1e6)
    return FlightTimeline(1, verdict, scratch, t0 + offset, None)


def test_attribution_names_layer_and_url():
    t = _timeline("slow", [("pool", "hostA:1", 1.0), ("span", "hostA:1", 40.0),
                           ("cache", None, 2.0)])
    att = t.attribution()
    assert att["dominant"] == "span:hostA:1"
    assert att["ms"]["span:hostA:1"] == pytest.approx(40.0, abs=0.5)
    assert att["dominant_share"] > 0.9


def test_tail_divergence_fires_on_one_bad_endpoint():
    rec = _recorder()
    with rec._lock:
        for _ in range(10):
            rec._ring.append(_timeline(
                "slow", [("pool", None, 0.1), ("span", "bad:1", 50.0)]))
        for _ in range(10):
            rec._ring.append(_timeline(
                "baseline", [("pool", None, 0.1), ("span", "good:2", 2.0)]))
    verdict = rec.tail_divergence()
    assert verdict is not None
    assert verdict["dominant"] == "span:bad:1"
    assert verdict["tail_share"] == 1.0
    assert verdict["baseline_share"] == 0.0


def test_tail_divergence_quiet_when_everything_is_slow_the_same_way():
    rec = _recorder()
    with rec._lock:
        for _ in range(10):
            rec._ring.append(_timeline(
                "slow", [("span", "a:1", 50.0)]))
        for _ in range(10):
            rec._ring.append(_timeline(
                "baseline", [("span", "a:1", 45.0)]))
    assert rec.tail_divergence() is None  # the median looks the same


def test_tail_divergence_needs_enough_tail():
    rec = _recorder()
    with rec._lock:
        for _ in range(3):
            rec._ring.append(_timeline("slow", [("span", "bad:1", 50.0)]))
    assert rec.tail_divergence(min_tail=8) is None


# -- unit: exporters -----------------------------------------------------------
def test_timeline_dict_and_jsonl_round_trip(tmp_path):
    rec = _recorder(baseline_ratio=1.0)
    s = rec.begin("pool", "m")
    flight.note("pool", "route", url="u", attempt=1)
    rec.commit(s)
    [t] = rec.retained()
    d = t.as_dict()
    assert json.loads(json.dumps(d)) == d
    path = tmp_path / "flight.jsonl"
    assert rec.dump_jsonl(str(path)) == 1
    [line] = path.read_text().splitlines()
    assert json.loads(line)["verdict"] == "baseline"


def test_find_by_any_wire_trace_id():
    tel = Telemetry(flight=_recorder(baseline_ratio=1.0), rng=SEEDED())
    rec = tel.flight
    span = tel.begin("http", "m")
    rec.span_begin(span, "u:1")
    tel.finish(span)
    assert rec.find(span.trace_id) is not None
    assert rec.find("0" * 32) is None


def test_to_chrome_trace_merges_tracer_spans_sorted():
    tel = Telemetry(flight=_recorder(baseline_ratio=1.0), rng=SEEDED())
    rec = tel.flight
    span = tel.begin("http", "m")
    rec.span_begin(span, "u:1")
    t0 = time.perf_counter_ns()
    span.phase("ttfb", t0, t0 + 1000)
    tel.finish(span)
    doc = rec.to_chrome_trace()
    names = [e["name"] for e in doc["traceEvents"]]
    assert any(n == "ttfb" for n in names)  # merged from the tracer ring
    assert any(n.startswith("span.begin") for n in names)
    ts = [e["ts"] for e in doc["traceEvents"]]
    assert ts == sorted(ts)


def test_last_anomalies_newest_first():
    rec = _recorder(baseline_ratio=1.0)
    rec.commit(rec.begin("pool", "ok"))
    for i in range(3):
        s = rec.begin("pool", f"bad{i}")
        rec.commit(s, error=RuntimeError(str(i)))
    rows = rec.last_anomalies(2)
    assert [r["model"] for r in rows] == ["bad2", "bad1"]
    assert all(r["verdict"] == "error" for r in rows)


# -- telemetry integration -----------------------------------------------------
def test_span_owned_scratch_commits_via_finish():
    tel = Telemetry(flight=_recorder(baseline_ratio=0.0), rng=SEEDED())
    rec = tel.flight
    span = tel.begin("http", "m")
    rec.span_begin(span, "h:1")
    assert getattr(span, "flight", None) is not None  # span owns it
    tel.finish(span, error=RuntimeError("boom"))
    [t] = rec.retained()
    assert t.verdict == "error"
    assert t.trace_id == span.trace_id
    names = [(e[1], e[2]) for e in t.events]
    assert ("span", "begin") in names and ("span", "finish") in names


def test_flight_metrics_exported_at_scrape():
    tel = Telemetry(flight=_recorder(baseline_ratio=0.0), rng=SEEDED())
    span = tel.begin("http", "m")
    tel.flight.span_begin(span, "h:1")
    tel.finish(span, error=RuntimeError("x"))
    text = tel.registry.prometheus_text()
    assert 'client_tpu_flight_retained_total{verdict="error"} 1' in text
    assert "client_tpu_flight_ring 1" in text


def test_stream_commit_verdicts():
    rec = _recorder(baseline_ratio=0.0)
    # errored stream retains
    span = StreamSpan("t" * 32, "s" * 16, "http", "m", "generate_stream",
                      True)
    span.mark()
    span.end_ns = time.perf_counter_ns()
    assert rec.commit_stream(span, error=RuntimeError("died")) == "error"
    # reconnected-but-finished stream retains as disrupted, with the
    # reconnect point event on the timeline
    span = StreamSpan("u" * 32, "r" * 16, "http", "m", "generate_stream",
                      True)
    span.mark()
    span.reconnect(abandoned=2)
    span.mark()
    span.end_ns = time.perf_counter_ns()
    assert rec.commit_stream(span) == "disrupted"
    disrupted = [t for t in rec.retained() if t.verdict == "disrupted"]
    [t] = disrupted
    assert ("stream", "reconnect") in [(e[1], e[2]) for e in t.events]
    # healthy stream with baseline off: dropped
    span = StreamSpan("v" * 32, "q" * 16, "http", "m", "generate_stream",
                      True)
    span.mark()
    span.end_ns = time.perf_counter_ns()
    assert rec.commit_stream(span) is None


# -- satellite: OpenMetrics exemplars -----------------------------------------
def test_exemplars_opt_in_links_bucket_to_trace():
    reg = MetricsRegistry(exemplars=True)
    tel = Telemetry(registry=reg, rng=SEEDED())
    span = tel.begin("http", "m")
    tel.finish(span)
    text = reg.prometheus_text()
    lines = [l for l in text.splitlines()
             if l.startswith("client_tpu_request_seconds_bucket")
             and "# {trace_id=" in l]
    assert lines, text
    assert span.trace_id in lines[0]
    # the exemplar's trace id resolves to a retained flight timeline
    # when a recorder is armed on the same telemetry
    tel2 = Telemetry(registry=MetricsRegistry(exemplars=True),
                     flight=_recorder(baseline_ratio=1.0), rng=SEEDED())
    span2 = tel2.begin("http", "m")
    tel2.flight.span_begin(span2, "h:1")
    tel2.finish(span2)
    text2 = tel2.registry.prometheus_text()
    assert span2.trace_id in text2
    assert tel2.flight.find(span2.trace_id) is not None
    # snapshot carries them JSON-pure when enabled
    snap = tel2.registry.snapshot()
    assert json.loads(json.dumps(snap)) == snap


def test_exemplars_off_by_default_keeps_exposition_conformant():
    import re

    reg = MetricsRegistry()
    tel = Telemetry(registry=reg, rng=SEEDED())
    tel.finish(tel.begin("http", "m"))
    sample_re = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
        r'(\{[a-zA-Z_][a-zA-Z0-9_]*="([^"\\]|\\.)*"'
        r'(,[a-zA-Z_][a-zA-Z0-9_]*="([^"\\]|\\.)*")*\})?'
        r' [-+]?(\d+(\.\d+)?([eE][-+]?\d+)?|\d+e[-+]?\d+)$')
    for line in reg.prometheus_text().splitlines():
        if line.startswith("#") or not line.strip():
            continue
        assert sample_re.match(line), line


# -- satellite: tracer concurrent-dump ordering fix ---------------------------
def test_tracer_dump_sorted_while_writer_hammers():
    """Regression: the chrome dump must snapshot the ring under ONE lock
    acquire and emit events sorted by start timestamp — a dump racing the
    hot path used to interleave spans in finish order (an early-started,
    late-finished span appeared after requests it preceded)."""
    tracer = Tracer(capacity=512)
    stop = threading.Event()

    def writer():
        i = 0
        while not stop.is_set():
            span = RequestSpan(f"{i:032x}", f"{i:016x}", "http", "m",
                               "infer", True)
            t = time.perf_counter_ns()
            span.phase("ttfb", t, t + 100)
            span.end_ns = time.perf_counter_ns()
            tracer.keep(span)
            i += 1

    threads = [threading.Thread(target=writer) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(30):
            doc = tracer.chrome_trace()
            ts = [e["ts"] for e in doc["traceEvents"]]
            assert ts == sorted(ts)
            json.dumps(doc)  # never torn into something unserializable
    finally:
        stop.set()
        for t in threads:
            t.join()
    # out-of-order finish: the earlier-started span must dump FIRST
    tracer.clear()
    early = RequestSpan("a" * 32, "a" * 16, "http", "m", "infer", True)
    time.sleep(0.001)
    late = RequestSpan("b" * 32, "b" * 16, "http", "m", "infer", True)
    late.end_ns = time.perf_counter_ns()
    tracer.keep(late)  # finishes (and lands in the ring) first
    early.end_ns = time.perf_counter_ns()
    tracer.keep(early)
    events = tracer.chrome_trace()["traceEvents"]
    assert events[0]["args"]["trace_id"] == "a" * 32


# -- e2e: all four frontends stitch -------------------------------------------
def _flight_tel():
    return Telemetry(flight=_recorder(baseline_ratio=1.0), rng=SEEDED())


def _assert_wire_timeline(rec, frontend):
    spans = [t for t in rec.retained() if t.frontend == frontend]
    assert spans, [t.frontend for t in rec.retained()]
    t = spans[-1]
    names = [(e[1], e[2]) for e in t.events]
    assert ("span", "begin") in names and ("span", "finish") in names
    assert t.trace_id is not None and t.trace_id in t.trace_ids
    ts = [e[0] for e in t.events]
    assert ts == sorted(ts)


def test_e2e_stitch_http_sync_and_grpc_sync():
    core = ServerCore(default_model_zoo())
    tel = _flight_tel()
    with HttpInferenceServer(core) as server:
        with httpclient.InferenceServerClient(server.url) as client:
            client.configure_telemetry(tel)
            expected, inputs = _simple_inputs(httpclient)
            result = client.infer("simple", inputs)
            np.testing.assert_array_equal(result.as_numpy("OUTPUT0"),
                                          expected)
    _assert_wire_timeline(tel.flight, "http")
    with GrpcInferenceServer(core) as server:
        with grpcclient.InferenceServerClient(server.url) as client:
            client.configure_telemetry(tel)
            expected, inputs = _simple_inputs(grpcclient)
            result = client.infer("simple", inputs)
            np.testing.assert_array_equal(result.as_numpy("OUTPUT0"),
                                          expected)
    _assert_wire_timeline(tel.flight, "grpc")


def test_e2e_stitch_aio_frontends():
    import client_tpu.grpc.aio as grpcaio
    import client_tpu.http.aio as aioclient

    core = ServerCore(default_model_zoo())
    tel = _flight_tel()
    server = AioHttpInferenceServer(core).start()
    try:
        async def drive_http():
            async with aioclient.InferenceServerClient(server.url) as c:
                c.configure_telemetry(tel)
                expected, inputs = _simple_inputs(aioclient)
                result = await c.infer("simple", inputs)
                np.testing.assert_array_equal(
                    result.as_numpy("OUTPUT0"), expected)

        asyncio.run(drive_http())
    finally:
        server.stop()
    _assert_wire_timeline(tel.flight, "http_aio")
    with GrpcInferenceServer(core) as gserver:
        async def drive_grpc():
            async with grpcaio.InferenceServerClient(gserver.url) as c:
                c.configure_telemetry(tel)
                expected, inputs = _simple_inputs(grpcaio)
                result = await c.infer("simple", inputs)
                np.testing.assert_array_equal(
                    result.as_numpy("OUTPUT0"), expected)

        asyncio.run(drive_grpc())
    _assert_wire_timeline(tel.flight, "grpc_aio")


def test_e2e_cross_layer_stitch_on_one_timeline():
    """retry + pool failover + batch + cache events land on ONE timeline
    in causal order: a dead first endpoint forces a failover, and the
    full cache -> batch -> pool composition reports into the scratch the
    cache layer owns."""
    from client_tpu.batch import BatchingClient
    from client_tpu.cache import CachingClient
    from client_tpu.pool import PoolClient

    core = ServerCore(default_model_zoo())
    with HttpInferenceServer(core) as server:
        tel = _flight_tel()
        pool = PoolClient(["127.0.0.1:1", f"127.0.0.1:{server.port}"],
                          protocol="http", telemetry=tel,
                          routing="round_robin", health_interval_s=None)
        client = CachingClient(BatchingClient(pool))
        try:
            expected, inputs = _simple_inputs(httpclient)
            result = client.infer("simple", inputs)
            np.testing.assert_array_equal(result.as_numpy("OUTPUT0"),
                                          expected)
        finally:
            # the cache's entry holds a lease of the process's arena: closed
            # here, so that a later test file of this worker finds nothing
            # leased (tests/test_disagg.py counts the leased bytes)
            client.close()
    timelines = [t for t in tel.flight.retained() if t.frontend == "cache"]
    assert len(timelines) == 1  # ONE timeline for the whole composition
    t = timelines[0]
    names = [(e[1], e[2]) for e in t.events]
    for needed in (("cache", "leader"), ("batch", "join"),
                   ("pool", "route"), ("pool", "failover"),
                   ("span", "begin"), ("span", "finish"),
                   ("batch", "dispatched")):
        assert needed in names, (needed, names)
    ts = [e[0] for e in t.events]
    assert ts == sorted(ts)  # causal order
    # the failover is attributed: the dead endpoint appears, then the
    # live one serves
    routes = [e[3]["url"] for e in t.events
              if (e[1], e[2]) == ("pool", "route")]
    assert routes[0] == "127.0.0.1:1"
    assert routes[-1].endswith(str(server.port))


def test_batch_settle_never_fans_foreign_span_finishes():
    """Regression: the batch dispatcher settles EVERY coalesced caller's
    span on the leader's thread — those foreign completions must not
    land on the leader's active flight scratch (the span-finish note is
    membership-gated on the scratch's bound trace ids)."""
    from client_tpu.batch import BatchingClient

    core = ServerCore(default_model_zoo())
    with HttpInferenceServer(core) as server:
        tel = _flight_tel()
        with httpclient.InferenceServerClient(server.url,
                                              concurrency=8) as inner:
            inner.configure_telemetry(tel)
            client = BatchingClient(inner, window_us=20_000)
            n = 6
            barrier = threading.Barrier(n)
            errors = []

            def caller():
                try:
                    barrier.wait()
                    x = np.ones((1, 64), dtype=np.float32)
                    inp = httpclient.InferInput(
                        "X", [1, 64], "FP32").set_data_from_numpy(x)
                    client.infer("batched_matmul", [inp])
                except Exception as e:  # pragma: no cover
                    errors.append(e)

            threads = [threading.Thread(target=caller) for _ in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errors
    for t in tel.flight.retained():
        if t.frontend != "batch":
            continue
        finishes = [e for e in t.events
                    if (e[1], e[2]) == ("span", "finish")]
        begins = [e for e in t.events
                  if (e[1], e[2]) == ("span", "begin")]
        # one finish per wire span THIS timeline bound — never the whole
        # batch's caller spans fanned onto the leader
        assert len(finishes) <= len(begins), t.as_dict()


def test_shed_request_retains_with_shed_verdict():
    """An admission-shed pool request never reaches the wire but still
    commits a retained timeline with the shed event on it."""
    from client_tpu.admission import AdaptiveLimiter, AdmissionController
    from client_tpu.pool import PoolClient
    from client_tpu.utils import InferenceServerException

    core = ServerCore(default_model_zoo())
    with HttpInferenceServer(core) as server:
        tel = _flight_tel()
        ctrl = AdmissionController(
            limiter=AdaptiveLimiter(initial_limit=1, min_limit=1,
                                    max_limit=1),
            max_queue=0)
        pool = PoolClient([f"127.0.0.1:{server.port}"], protocol="http",
                          telemetry=tel, admission=ctrl,
                          health_interval_s=None)
        try:
            # saturate the one slot, then a low-priority arrival sheds
            token = ctrl.acquire()
            _, inputs = _simple_inputs(httpclient)
            with pytest.raises(InferenceServerException):
                pool.infer("simple", inputs, priority=9)
            token.release()
        finally:
            pool.close()
    shed = [t for t in tel.flight.retained() if t.verdict == "shed"]
    assert shed, [t.verdict for t in tel.flight.retained()]
    names = [(e[1], e[2]) for e in shed[-1].events]
    assert ("admission", "shed") in names


# -- postmortem bundle ---------------------------------------------------------
def test_postmortem_bundle_schema_round_trip():
    from client_tpu import doctor

    core = ServerCore(default_model_zoo())
    with HttpInferenceServer(core) as server:
        tel = Telemetry(sample="always", flight=_recorder(baseline_ratio=1.0),
                        rng=SEEDED())
        snap = doctor.collect_snapshot(
            [f"127.0.0.1:{server.port}"], telemetry=tel,
            requests_per_endpoint=3, probe_timeout_s=10.0)
        bundle = doctor.postmortem_bundle(snap, tel)
    assert bundle["kind"] == "client_tpu_postmortem"
    assert bundle["version"] == 2
    for key in ("snapshot", "flight", "metrics", "slo_report"):
        assert key in bundle, sorted(bundle)
    # snapshot carries the flight summary section + the fleet state the
    # bundle spec demands
    for key in ("endpoints", "admission", "cache", "shm", "anomalies",
                "flight"):
        assert key in bundle["snapshot"], sorted(bundle["snapshot"])
    assert bundle["flight"]["timelines"], "probe requests not retained"
    # fully JSON-pure: a postmortem must survive the disk round trip
    assert json.loads(json.dumps(bundle)) == bundle


# -- perf harness row ----------------------------------------------------------
def test_perf_flight_row():
    from client_tpu.perf import PerfRunner

    core = ServerCore(default_model_zoo())
    with HttpInferenceServer(core) as server:
        runner = PerfRunner(f"127.0.0.1:{server.port}", "http", "simple",
                            flight=True)
        row = runner.run(2, 40)
    fl = row["client_flight"]
    assert fl["requests"] >= 40
    assert fl["events_per_request"] > 0
    assert fl["ring"] <= fl["capacity"]
    assert fl["dropped"] + fl["retained_total"] == fl["requests"]


# -- committed artifact --------------------------------------------------------
def test_bench_flight_artifact_claims():
    """The committed BENCH_FLIGHT.json must re-validate under its own
    --check invariants (≤1 µs/event record cost, one-branch disabled
    path, bounded ring, chaos attribution naming the faulted replica)."""
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parent.parent
    artifact = root / "BENCH_FLIGHT.json"
    assert artifact.exists(), "BENCH_FLIGHT.json not committed"
    doc = json.loads(artifact.read_text())
    assert doc["record"]["enabled_ns"]["p50"] <= 1000.0
    assert doc["chaos"]["named_faulted_endpoint"] is True
    proc = subprocess.run(
        [sys.executable, str(root / "tools" / "bench_flight.py"),
         "--check", "--output", str(artifact)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# -- chaos smoke ---------------------------------------------------------------
@pytest.mark.flight_smoke
def test_flight_smoke_names_faulted_replica():
    """3-replica pool, one replica behind a latency proxy: the retained
    slow-tail timelines must attribute the latency to the faulted
    endpoint (tail_divergence names it), while the ring stays bounded."""
    core = ServerCore(default_model_zoo())
    servers = [HttpInferenceServer(core).start() for _ in range(3)]
    proxy = ChaosProxy("127.0.0.1", servers[0].port).start()
    proxy.fault = Fault("latency", latency_s=0.05)
    faulted_url = f"127.0.0.1:{proxy.port}"
    urls = [faulted_url] + [f"127.0.0.1:{s.port}" for s in servers[1:]]
    # p80 threshold: with round-robin a third of requests carry the
    # +50 ms fault, so the learned threshold lands at the slow cluster's
    # edge and essentially every faulted request verdicts "slow" — wide
    # margins keep this deterministic under suite/scheduler noise
    rec = _recorder(capacity=256, slow_quantile=0.8,
                    threshold_min_samples=48, baseline_ratio=0.05)
    tel = Telemetry(sample="off", flight=rec, rng=SEEDED())
    from client_tpu.pool import PoolClient

    pool = PoolClient(urls, protocol="http", telemetry=tel,
                      routing="round_robin", health_interval_s=None)
    try:
        for _ in range(320):
            _, inputs = _simple_inputs(httpclient)
            pool.infer("simple", inputs)
    finally:
        pool.close()
        proxy.stop()
        for s in servers:
            s.stop()
    stats = rec.stats()
    assert stats["requests"] == 320
    assert stats["ring"] <= rec.capacity
    divergence = rec.tail_divergence(min_tail=4)
    assert divergence is not None, rec.stats()
    assert divergence["dominant"].endswith(faulted_url), divergence
    # and the anomalous timelines themselves carry the evidence
    slow = [t for t in rec.retained() if t.verdict == "slow"]
    assert slow
    assert all(t.attribution()["dominant"].endswith(faulted_url)
               for t in slow[-4:])
