"""Leak tier: every client path in a loop, its memory read before and after.

The reference's ``memory_leak_test.cc`` (324 LoC) loops inferences for
external leak tooling over hours; this file is the in-repo equivalent, in two
forms of the same ten rows:

- **Counted** (``CLIENT_TPU_SOAK_SECONDS`` unset; what tier-1 and a plain
  ``pytest`` run): ``WARMUP_ITERS`` steps, then ``COUNTED_ITERS`` more, and the
  growth between the two points, after ``gc.collect()`` and ``malloc_trim``,
  stays under an absolute budget: resident set for every row, and
  ``tracemalloc``'s traced total for the rows whose client is this process.
  No clock is read, so a busy machine changes how long a row takes and
  nothing else. Writes no file.
- **Timed** (``CLIENT_TPU_SOAK_SECONDS=600 pytest -m soak``; an operator's
  soak, and the form the committed ``SOAK_r0*.json`` came from): each row
  drives its path for that many seconds, samples resident-set size on a steady
  cadence, fits a least-squares slope over the final third of the samples,
  fails on sustained growth and writes ``SOAK_latest.json``.
"""

import gc
import json
import os
import subprocess
import sys
import threading
import time
import tracemalloc
from pathlib import Path

import numpy as np
import pytest

import client_tpu.grpc as grpcclient
import client_tpu.http as httpclient
import client_tpu.utils.shared_memory as sysshm
import client_tpu.utils.tpu_shared_memory as tpushm

pytestmark = pytest.mark.soak

# 0: the counted form
SOAK_SECONDS = float(os.environ.get("CLIENT_TPU_SOAK_SECONDS", "0"))
SAMPLE_EVERY = max(SOAK_SECONDS / 60.0, 1.0)
# Sustained growth budget. Runs >= 1800 s assert leak-scale (64 KB/min):
# the r05 instrumented 3600 s grpc_stream capture (SOAK_STREAM_r05.json)
# pinned all growth to warmup + glibc retention of
# freed chunks — tracemalloc flat (101 KB/hr), mallinfo2 in-use bounded
# (713 KB/hr, sign-flipping tail). The warmup is a fixed few MB, so the
# final-third slope amortizes with duration — measured post-trim:
# 106 KB/min at 600 s (SOAK_r05, tail-300s already 34), 41 at 1800 s
# (SOAK_r04), 25 at 3600 s (SOAK_STREAM_r05) — hence 64 (2.6x the hour
# reading) only once the window is unambiguously post-warmup; shorter
# runs keep the 512 warmup headroom and rely on the tail assert below
# for the steady-state claim.
MAX_SLOPE_KB_PER_MIN = float(os.environ.get(
    "CLIENT_TPU_SOAK_MAX_SLOPE", "512" if SOAK_SECONDS < 1800 else "64"))

REPO = Path(__file__).resolve().parent.parent
RESULTS: dict = {}


def _rss_kb(pid: int = 0) -> int:
    path = f"/proc/{pid or 'self'}/status"
    with open(path) as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1])
    return 0


def _fit_slope_kb_per_min(window):
    t = np.array([s[0] for s in window])
    r = np.array([s[1] for s in window], dtype=np.float64)
    if len(window) < 3 or t[-1] - t[0] < 1.0:
        return 0.0
    slope_per_s = np.polyfit(t - t[0], r, 1)[0]
    return float(slope_per_s * 60.0)


def _slope_kb_per_min(samples):
    """Least-squares slope over the steady-state final third.

    Transport warmup is real but finite (grpc stream flow-control buffers
    plateau after ~1 min: 59.8->63.3 MB then dead flat through 210k
    inferences in the 2026-07 trace); the final-third window keeps short
    smoke runs from reading that ramp as a leak while a true leak still
    shows a positive slope at any duration."""
    return _fit_slope_kb_per_min(samples[2 * len(samples) // 3 :])


# The tail window pins the "warmup plateaus, then flat" explanation: the
# final-third slope tolerates a ramp that never quite flattens, the tail
# assert does not. Applied only when the run is long enough that the tail is
# unambiguously post-warmup (>=TAIL_MIN_RUN_S) so smoke runs don't flake.
TAIL_WINDOW_S = 300.0
TAIL_MIN_RUN_S = float(os.environ.get("CLIENT_TPU_SOAK_TAIL_MIN_RUN", "480"))
MAX_TAIL_SLOPE_KB_PER_MIN = float(
    os.environ.get("CLIENT_TPU_SOAK_MAX_TAIL_SLOPE", "64")
)


def _tail_slope_kb_per_min(samples):
    """Slope over the trailing ``min(TAIL_WINDOW_S, run/2)`` seconds.

    Returns ``(slope, span_seconds)`` so failure messages report the window
    actually fitted (a 480 s run fits 240 s, not the full 300)."""
    if not samples:
        return 0.0, 0.0
    span = min(TAIL_WINDOW_S, (samples[-1][0] - samples[0][0]) / 2.0)
    cutoff = samples[-1][0] - span
    return _fit_slope_kb_per_min([s for s in samples if s[0] >= cutoff]), span


def _malloc_trim() -> None:
    """Release glibc's free-but-unreturned heap back to the OS.

    The r03 600 s capture caught the grpc stream tail ramping at ~92 KB/min
    — but malloc_trim(0) recovered ~84% of that growth on a controlled
    repro (and tracemalloc showed python-level allocations dead flat), so
    the ramp is allocator retention of freed chunks, not reachable growth.
    Sampling post-trim makes the slope measure what the tier is FOR
    (unreclaimable growth) while the raw pre-trim figure is still recorded
    per sample for the fragmentation picture."""
    import ctypes

    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except Exception:
        pass  # non-glibc: raw == trimmed


# The counted form. A step that kept its 256 KiB payload would grow by 75 MiB
# over COUNTED_ITERS; one that kept a kilobyte of Python objects, by 300 KiB
# of traced memory. What a clean path adds past warm-up does not scale with the
# count: allocator and transport high-water, and 10 to 40 KB a row of traced
# memory while ``integrity.py``'s ring of 4,096 overhead samples fills.
WARMUP_ITERS = 100
COUNTED_ITERS = 300
MAX_RSS_GROWTH_KB = 16 * 1024
MAX_TRACED_GROWTH_KB = 128


def _settled_kb(pid: int):
    """(resident set, traced Python memory) in KB with nothing left to
    collect; the second is None for another process."""
    gc.collect()
    if pid:
        return _rss_kb(pid), None
    _malloc_trim()
    return _rss_kb(), tracemalloc.get_traced_memory()[0] // 1024


def _soak_counted(name: str, step, pid: int):
    if not pid:
        tracemalloc.start()
    try:
        for _ in range(WARMUP_ITERS):
            step()
        rss_before, traced_before = _settled_kb(pid)
        for _ in range(COUNTED_ITERS):
            step()
        rss_after, traced_after = _settled_kb(pid)
    finally:
        tracemalloc.stop()
    assert rss_after - rss_before < MAX_RSS_GROWTH_KB, (
        f"{name}: RSS {rss_before} -> {rss_after} KB over {COUNTED_ITERS} "
        f"steps after {WARMUP_ITERS} of warm-up")
    if not pid:
        assert traced_after - traced_before < MAX_TRACED_GROWTH_KB, (
            f"{name}: traced Python memory {traced_before} -> {traced_after} "
            f"KB over {COUNTED_ITERS} steps after {WARMUP_ITERS} of warm-up")


def _soak(name: str, step, pid: int = 0, trim: bool = False):
    """Run ``step()`` in a loop and assert that memory does not grow: by the
    count where ``SOAK_SECONDS`` is 0, else for SOAK_SECONDS, sampling RSS and
    asserting that the steady-state slope is flat. ``pid`` samples another
    process (native). ``trim=True`` samples post-``malloc_trim`` (own process
    only) and additionally records the raw pre-trim slope."""
    if not SOAK_SECONDS:
        return _soak_counted(name, step, pid)
    deadline = time.monotonic() + SOAK_SECONDS
    samples = []
    raw_samples = []
    next_sample = 0.0
    iters = 0
    while time.monotonic() < deadline:
        step()
        iters += 1
        now = time.monotonic()
        if now >= next_sample:
            gc.collect()
            if trim and not pid:
                raw_samples.append((now, _rss_kb(pid)))
                _malloc_trim()
            samples.append((now, _rss_kb(pid)))
            next_sample = now + SAMPLE_EVERY
    slope = _slope_kb_per_min(samples)
    tail_slope, tail_span = _tail_slope_kb_per_min(samples)
    RESULTS[name] = {
        "iters": iters,
        "seconds": SOAK_SECONDS,
        "rss_start_kb": samples[0][1],
        "rss_end_kb": samples[-1][1],
        "slope_kb_per_min": round(slope, 1),
        "tail_slope_kb_per_min": round(tail_slope, 1),
        "samples": len(samples),
    }
    if raw_samples:
        RESULTS[name]["raw_slope_kb_per_min"] = round(
            _slope_kb_per_min(raw_samples), 1)
        RESULTS[name]["raw_tail_slope_kb_per_min"] = round(
            _tail_slope_kb_per_min(raw_samples)[0], 1)
        RESULTS[name]["trim"] = True
    assert slope < MAX_SLOPE_KB_PER_MIN, (
        f"{name}: RSS slope {slope:.1f} KB/min over {SOAK_SECONDS:.0f}s "
        f"({samples[0][1]} -> {samples[-1][1]} KB, {iters} iters)"
    )
    if SOAK_SECONDS >= TAIL_MIN_RUN_S:
        assert tail_slope < MAX_TAIL_SLOPE_KB_PER_MIN, (
            f"{name}: tail-window RSS slope {tail_slope:.1f} KB/min "
            f"(last {tail_span:.0f}s of {SOAK_SECONDS:.0f}s) — warmup "
            f"should have plateaued; sustained growth is a leak"
        )


_SERVER_SCRIPT = """
import sys
sys.path.insert(0, {repo!r})
from client_tpu.models import default_model_zoo
from client_tpu.server import GrpcInferenceServer, HttpInferenceServer, ServerCore
import time
core = ServerCore(default_model_zoo())
h = HttpInferenceServer(core).start()
g = GrpcInferenceServer(core).start()
print("PORTS", h.port, g.port, flush=True)
time.sleep(86400)
"""


class _Endpoints:
    def __init__(self, http_port, grpc_port):
        self.http_url = f"127.0.0.1:{http_port}"
        self.grpc_url = f"127.0.0.1:{grpc_port}"


@pytest.fixture(scope="module")
def servers():
    """Servers live in their own process: RSS sampled here is the CLIENT's.

    (Sharing the process conflated server-side arena growth with client
    leaks — the 2026-07 diagnosis showed a perfectly flat client at 174k
    inferences once the server moved out.)"""
    env = dict(os.environ)
    # the leak hunt needs a server, not an accelerator: the child runs on
    # the cpu backend unless the caller overrides, and never takes a chip
    # from the process that holds it
    env["PYTHONPATH"] = ""
    env["JAX_PLATFORMS"] = os.environ.get("CLIENT_TPU_SOAK_SERVER_PLATFORM", "cpu")
    proc = subprocess.Popen(
        [sys.executable, "-c", _SERVER_SCRIPT.format(repo=str(REPO))],
        stdout=subprocess.PIPE, text=True, env=env,
    )
    try:
        import select

        ready, _, _ = select.select([proc.stdout], [], [], 120)
        assert ready, "soak server subprocess did not start within 120s"
        line = proc.stdout.readline().strip()
        assert line.startswith("PORTS"), line
        _, http_port, grpc_port = line.split()
        yield _Endpoints(int(http_port), int(grpc_port))
    finally:
        proc.terminate()
        proc.wait(timeout=10)


@pytest.fixture(scope="module", autouse=True)
def _dump_results(servers):
    yield
    if not RESULTS:
        # the counted form records nothing and leaves the tree as it found
        # it; a timed run that exercised no _soak rows (e.g. only the
        # probe-tool smoke) must not rewrite a committed artifact's config
        return
    # default to a gitignored scratch file: committed round artifacts
    # (SOAK_rNN.json) are historical records and must only be rewritten by
    # deliberately pointing CLIENT_TPU_SOAK_OUT at them
    out = REPO / os.environ.get("CLIENT_TPU_SOAK_OUT", "SOAK_latest.json")
    existing = {}
    if out.exists():
        try:
            existing = json.loads(out.read_text())
        except ValueError:
            pass
    existing.update(RESULTS)
    existing["config"] = {
        "soak_seconds": SOAK_SECONDS,
        "max_slope_kb_per_min": MAX_SLOPE_KB_PER_MIN,
    }
    out.write_text(json.dumps(existing, indent=1))


_PAYLOAD = np.random.default_rng(7).integers(0, 1000, (1, 65536)).astype(np.int32)


def test_soak_http_sync_wire(servers):
    with httpclient.InferenceServerClient(servers.http_url) as client:
        def step():
            inp = httpclient.InferInput("INPUT0", [1, 65536], "INT32")
            inp.set_data_from_numpy(_PAYLOAD)
            r = client.infer("custom_identity_int32", [inp])
            assert r.as_numpy("OUTPUT0") is not None
        _soak("http_sync_wire", step)


def test_soak_http_async_pool(servers):
    with httpclient.InferenceServerClient(servers.http_url, concurrency=4) as client:
        def step():
            reqs = []
            for _ in range(4):
                inp = httpclient.InferInput("INPUT0", [1, 65536], "INT32")
                inp.set_data_from_numpy(_PAYLOAD)
                reqs.append(client.async_infer("custom_identity_int32", [inp]))
            for r in reqs:
                assert r.get_result().as_numpy("OUTPUT0") is not None
        _soak("http_async_pool", step)


def test_soak_grpc_sync_wire(servers):
    with grpcclient.InferenceServerClient(servers.grpc_url) as client:
        def step():
            inp = grpcclient.InferInput("INPUT0", [1, 65536], "INT32")
            inp.set_data_from_numpy(_PAYLOAD)
            r = client.infer("custom_identity_int32", [inp])
            assert r.as_numpy("OUTPUT0") is not None
        _soak("grpc_sync_wire", step)


def test_soak_grpc_stream(servers):
    with grpcclient.InferenceServerClient(servers.grpc_url) as client:
        got = threading.Semaphore(0)
        errors = []

        def callback(result, error):
            if error is not None:
                errors.append(error)
            got.release()

        client.start_stream(callback)

        def step():
            inp = grpcclient.InferInput("INPUT0", [1, 65536], "INT32")
            inp.set_data_from_numpy(_PAYLOAD)
            client.async_stream_infer("custom_identity_int32", [inp])
            assert got.acquire(timeout=30)

        try:
            _soak("grpc_stream", step, trim=True)
        finally:
            client.stop_stream()
        assert not errors, errors[:3]


def test_soak_llm_generate(servers):
    """Decoupled generation path: server-side per-token streaming + the
    incremental ServerCore.infer_stream generator + per-session stream
    requests — none of which the identity rows exercise. Leak surface:
    per-request generator state, per-response encode buffers, KV caches
    created/dropped per session."""
    with grpcclient.InferenceServerClient(servers.grpc_url) as client:
        import queue as _q

        responses: "_q.Queue" = _q.Queue()
        client.start_stream(lambda r, e: responses.put((r, e)))
        prompt = np.arange(1, 9, dtype=np.int32).reshape(1, 8)

        def step():
            tok = grpcclient.InferInput("TOKENS", [1, 8], "INT32")
            tok.set_data_from_numpy(prompt)
            mx = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
            mx.set_data_from_numpy(np.array([4], np.int32))
            client.async_stream_infer(
                "tiny_lm_generate", [tok, mx],
                enable_empty_final_response=True)
            got = 0
            while True:
                result, error = responses.get(timeout=30)
                assert error is None, error
                if result.is_null_response():
                    break
                got += 1
            assert got == 4

        try:
            _soak("llm_generate_stream", step, trim=True)
        finally:
            client.stop_stream()


def test_soak_system_shm(servers):
    nbytes = _PAYLOAD.nbytes
    with httpclient.InferenceServerClient(servers.http_url) as client:
        region = sysshm.create_shared_memory_region("soak_sys", "/soak_sys", nbytes)
        client.register_system_shared_memory("soak_sys", "/soak_sys", nbytes)
        try:
            def step():
                sysshm.set_shared_memory_region(region, [_PAYLOAD])
                inp = httpclient.InferInput("INPUT0", [1, 65536], "INT32")
                inp.set_shared_memory("soak_sys", nbytes)
                out = httpclient.InferRequestedOutput("OUTPUT0")
                out.set_shared_memory("soak_sys", nbytes)
                r = client.infer("custom_identity_int32", [inp], outputs=[out])
                assert r is not None
            _soak("system_shm", step)
        finally:
            client.unregister_system_shared_memory("soak_sys")
            sysshm.destroy_shared_memory_region(region)


def test_soak_tpu_shm_churn(servers):
    """Full create/register/infer/unregister/destroy lifecycle per step —
    the attachment-leak hunter, at soak duration."""
    import jax.numpy as jnp

    data = jnp.arange(16, dtype=jnp.int32).reshape(1, 16)
    b = np.ones((1, 16), dtype=np.int32)
    with httpclient.InferenceServerClient(servers.http_url) as client:
        def step():
            region = tpushm.create_shared_memory_region("soak_tpu", 128)
            try:
                tpushm.set_shared_memory_region_from_jax(region, data)
                client.register_tpu_shared_memory(
                    "soak_tpu", tpushm.get_raw_handle(region), 0, 128
                )
                i0 = httpclient.InferInput("INPUT0", [1, 16], "INT32")
                i0.set_shared_memory("soak_tpu", 64)
                i1 = httpclient.InferInput("INPUT1", [1, 16], "INT32")
                i1.set_data_from_numpy(b)
                client.infer("simple", [i0, i1])
            finally:
                client.unregister_tpu_shared_memory("soak_tpu")
                tpushm.destroy_shared_memory_region(region)
        _soak("tpu_shm_churn", step)


def test_soak_stream_probe_tool(tmp_path):
    """The instrumented attribution tool (tools/soak_stream_probe.py) keeps
    working end-to-end: both phases produce samples with every metric
    series and computed slopes. Phases as short as three samples need (the
    tool samples four times in a phase shorter than two minutes) — this pins
    the harness, not the numbers (SOAK_STREAM_r05.json is the committed
    measurement)."""
    out = tmp_path / "probe_smoke.json"
    proc = subprocess.run(
        [sys.executable, "tools/soak_stream_probe.py",
         "--seconds", "8", "--ab-seconds", "8", "--out", str(out)],
        capture_output=True, text=True, timeout=500, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-800:]
    data = json.loads(out.read_text())
    for phase in ("default_arenas", "arena_max_1"):
        p = data[phase]
        assert "error" not in p, (phase, p.get("error"), proc.stderr[-800:])
        assert p["iters"] > 0 and not p["errors"], p.get("errors")
        assert len(p["samples"]) >= 3
        for key in ("rss_raw_kb", "rss_trimmed_kb", "malloc_in_use_kb",
                    "tracemalloc_kb"):
            assert key in p["samples"][0], key
            assert key in p["slopes"], key
        assert p["tracemalloc_top"]
    assert data["arena_max_1"]["arena_max"] == "1"


def _ten_more_served(client, proc):
    """A step of the native row's counted form. The bench counts its own
    iterations where nobody can read them; the server counts what it
    answered: a step waits for ten more."""
    def served() -> int:
        stats = client.get_inference_statistics("identity_fp32")
        return stats["model_stats"][0]["inference_count"]

    target = served()

    def step():
        nonlocal target
        target += 10
        deadline = time.monotonic() + 60
        while served() < target:
            assert proc.poll() is None, "native_bench exited early"
            assert time.monotonic() < deadline, "native_bench stalled"
            time.sleep(0.005)
    return step


@pytest.mark.parametrize("arenas", ["default", "pinned"])
def test_soak_native_client(servers, native_build, arenas):
    """The C++ client under sustained load, RSS sampled from outside
    (reference memory_leak_test.cc's role for the native library).

    History of the attribution: r02 measured 186.7 KB/min with default
    arenas and blamed glibc per-thread arena high-water (ASan/LSan clean).
    The r03 600 s capture DISPROVED that: ``MALLOC_ARENA_MAX=1`` ramped
    just as fast (382 vs 326 KB/min). The real mechanism is glibc
    retention of freed chunks (malloc_trim recovers it; a direct 12k-iter
    client-loop probe with mallinfo2 shows in-use heap dead flat at
    ~306 KB). The bench therefore trims periodically
    (``CLIENT_TPU_BENCH_TRIM_EVERY``) so the sampled slope measures
    reachable growth — a true leak still fails; both arena variants stay
    as regression nets that arena count doesn't matter post-trim."""
    env = {
        **os.environ,
        "CLIENT_TPU_TEST_URL": servers.http_url,
        "CLIENT_TPU_BENCH_TRIM_EVERY": "200",
    }
    name = "native_client"
    if arenas == "pinned":
        env["MALLOC_ARENA_MAX"] = "1"
        name = "native_client_arena1"
    proc = subprocess.Popen(
        [str(native_build / "native_bench"), str(1 << 16), str(10_000_000)],
        env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        if SOAK_SECONDS:
            time.sleep(min(5.0, SOAK_SECONDS / 10))  # let it reach steady state

            def step():
                assert proc.poll() is None, "native_bench exited early"
                time.sleep(0.25)
            _soak(name, step, pid=proc.pid)
            RESULTS[name]["trim_every"] = 200
        else:
            with httpclient.InferenceServerClient(servers.http_url) as client:
                _soak(name, _ten_more_served(client, proc), pid=proc.pid)
    finally:
        proc.terminate()
        proc.wait(timeout=10)
