"""Benchmark: the zero-copy TPU data plane vs the wire path.

Measures the client-framework hot path end-to-end — real KServe v2 HTTP/GRPC
round trips against the in-process server — in three data-plane modes:

- wire:       tensor bytes serialized into the request/response both ways
- shm=system: POSIX shared-memory negotiation (no tensor bytes on the wire)
- shm=tpu:    tpu_shared_memory with jax.Array binding (colocated regions:
              tensors stay on-device; only the control message rides HTTP)

Workloads:
1. identity FP32 at 4 MiB and 64 MiB — the pure data-plane race (what
   `perf_analyzer --shared-memory={none,system,cuda}` measures on the
   reference stack; reference README.md:630-651 makes only qualitative
   claims, so the wire path is the measured baseline)
2. the same race from a client in ANOTHER process (identity_xproc): this
   process serves and holds the chip; a numpy-only client child (it never
   imports jax) writes the host window and registers the region by raw
   handle, so the server pays one H2D on read and one D2H mirror on write.
   The colocated in-process row is the design's best case; this row is
   what a real client/server split pays.
3. densenet_onnx contract (BASELINE.json config #3): jax.Array image in,
   classification out — wire HTTP, tpu-shm HTTP, and GRPC with jax.Array
   inputs.

Prints ONE JSON line: headline = 4 MiB identity shm=tpu p50, vs_baseline =
speedup over the wire path; everything else rides in "detail". It measures
the TPU: where JAX finds none it fails, and a mode that errors ends the run
with a non-zero exit code.
"""

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

N_WARMUP = 5
N_ITERS = 200
MODE_TIME_CAP_S = 60.0  # per mode+size; report actual iters when capped
IDENTITY_SIZES = (1 << 20, 1 << 24)  # fp32 elems: 4 MiB and 64 MiB
DENSENET_WIDTH = 96
DENSENET_ITERS = 50


def _percentile(values, q):
    from client_tpu.perf import _percentile as impl

    return impl(sorted(values), q)


def _stats(times):
    return {
        "p50_ms": round(_percentile(times, 0.5) * 1000, 3),
        "p99_ms": round(_percentile(times, 0.99) * 1000, 3),
        "iters": len(times),
    }


def _timed_loop(step, iters=N_ITERS, min_iters=20):
    """min_iters: how many measured samples must exist before the time cap
    can break the loop — lowered for modes where a single round trip is
    slow (64 MiB over the wire)."""
    times = []
    deadline = time.monotonic() + MODE_TIME_CAP_S
    for i in range(N_WARMUP + iters):
        t0 = time.perf_counter()
        step()
        if i >= N_WARMUP:
            times.append(time.perf_counter() - t0)
        if time.monotonic() > deadline and len(times) >= min_iters:
            break
    return times


# ---------------------------------------------------------------------------
# identity matrix
# ---------------------------------------------------------------------------


def bench_identity_wire(client, httpclient, x_np, min_iters=20):
    def step():
        inp = httpclient.InferInput("INPUT0", list(x_np.shape), "FP32")
        inp.set_data_from_numpy(x_np)
        result = client.infer("identity_fp32", [inp])
        assert result.as_numpy("OUTPUT0").shape == x_np.shape

    return _timed_loop(step, min_iters=min_iters)


def bench_identity_shm(client, httpclient, x_np, family, min_iters=20):
    import uuid

    import numpy as np

    # uuid-suffixed names/keys: two concurrent bench runs on one host must
    # never attach each other's regions (fixed "/bench_in" keys used to
    # collide and corrupt both runs)
    name_in = f"bench_in_{uuid.uuid4().hex[:8]}"
    name_out = f"bench_out_{uuid.uuid4().hex[:8]}"
    nbytes = x_np.nbytes
    if family == "system":
        import client_tpu.utils.shared_memory as shm

        rin = shm.create_shared_memory_region(name_in, f"/{name_in}", nbytes)
        rout = shm.create_shared_memory_region(name_out, f"/{name_out}", nbytes)
        client.register_system_shared_memory(name_in, f"/{name_in}", nbytes)
        client.register_system_shared_memory(name_out, f"/{name_out}", nbytes)

        def write_input():
            shm.set_shared_memory_region(rin, [x_np])

        def read_output():
            return shm.get_contents_as_numpy(rout, np.float32, list(x_np.shape))

        def cleanup():
            client.unregister_system_shared_memory()
            shm.destroy_shared_memory_region(rin)
            shm.destroy_shared_memory_region(rout)

    else:  # tpu
        import jax

        import client_tpu.utils.tpu_shared_memory as tpushm
        from client_tpu._base import InferStat, RequestTimers

        x_dev = jax.device_put(x_np)
        x_dev.block_until_ready()
        rin = tpushm.create_shared_memory_region(name_in, nbytes, colocated=True)
        rout = tpushm.create_shared_memory_region(name_out, nbytes, colocated=True)
        client.register_tpu_shared_memory(name_in, tpushm.get_raw_handle(rin), 0, nbytes)
        client.register_tpu_shared_memory(name_out, tpushm.get_raw_handle(rout), 0, nbytes)
        stat = InferStat()
        current = {}

        def write_input():
            timers = RequestTimers()
            timers.capture(RequestTimers.REQUEST_START)
            current["timers"] = timers
            tpushm.set_shared_memory_region_from_jax(rin, x_dev, timers=timers)

        def read_output():
            timers = current["timers"]
            out = tpushm.get_contents_as_jax(
                rout, "FP32", list(x_np.shape), timers=timers
            )
            out.block_until_ready()
            timers.capture(RequestTimers.REQUEST_END)
            stat.update(timers)
            return out

        def cleanup():
            client.unregister_tpu_shared_memory()
            tpushm.destroy_shared_memory_region(rin)
            tpushm.destroy_shared_memory_region(rout)

    try:
        def step():
            write_input()
            inp = httpclient.InferInput("INPUT0", list(x_np.shape), "FP32")
            inp.set_shared_memory(name_in, nbytes)
            out0 = httpclient.InferRequestedOutput("OUTPUT0")
            out0.set_shared_memory(name_out, nbytes)
            client.infer("identity_fp32", [inp], outputs=[out0])
            read_output()

        times = _timed_loop(step, min_iters=min_iters)
        if family == "tpu":
            d = stat.as_dict()
            n = max(d["completed_request_count"], 1)
            # device-transfer stats (both ~0 when colocated cache hits hold
            # the array on-device, which is the zero-copy claim in numbers)
            times_extra = {
                "d2h_avg_us": round(d["cumulative_d2h_time_ns"] / n / 1000, 1),
                "h2d_avg_us": round(d["cumulative_h2d_time_ns"] / n / 1000, 1),
            }
            return times, times_extra
        return times
    finally:
        cleanup()


# ---------------------------------------------------------------------------
# cross-process tpu-shm (the deployment-realistic split)
# ---------------------------------------------------------------------------

def xproc_client(url):
    """The client child's whole job: wire vs tpu-shm against the server in
    the PARENT process, numpy only. The chip belongs to the serving process;
    this one never imports jax (asserted before it reports).

    Reference parity: cudashm's cross-process semantics
    (cuda_shared_memory/__init__.py:107-170 — the raw handle IS the
    cross-process contract); perf_analyzer --shared-memory=cuda measures
    this split, never an in-process handover.
    """
    import uuid

    import numpy as np

    import client_tpu.http as httpclient
    import client_tpu.utils.tpu_shared_memory as tpushm

    rng = np.random.default_rng(0)
    out = {}
    client = httpclient.InferenceServerClient(
        url, concurrency=2, network_timeout=300.0)
    try:
        for n_elems in IDENTITY_SIZES:
            x_np = rng.standard_normal(
                n_elems, dtype=np.float32).reshape(1, n_elems)
            nbytes = x_np.nbytes
            row = {"wire": _stats(
                bench_identity_wire(client, httpclient, x_np))}
            # uuid-suffixed registration names: two runs registering
            # "xp_in" against one server would collide on the name
            name_in = f"xp_in_{uuid.uuid4().hex[:8]}"
            name_out = f"xp_out_{uuid.uuid4().hex[:8]}"
            rin = tpushm.create_shared_memory_region(name_in, nbytes)
            rout = tpushm.create_shared_memory_region(name_out, nbytes)
            client.register_tpu_shared_memory(
                name_in, tpushm.get_raw_handle(rin), 0, nbytes)
            client.register_tpu_shared_memory(
                name_out, tpushm.get_raw_handle(rout), 0, nbytes)
            try:
                def step():
                    tpushm.set_shared_memory_region(rin, [x_np])
                    inp = httpclient.InferInput(
                        "INPUT0", list(x_np.shape), "FP32")
                    inp.set_shared_memory(name_in, nbytes)
                    o = httpclient.InferRequestedOutput("OUTPUT0")
                    o.set_shared_memory(name_out, nbytes)
                    client.infer("identity_fp32", [inp], outputs=[o])
                    res = tpushm.get_contents_as_numpy(
                        rout, "FP32", list(x_np.shape))
                    assert res.shape == x_np.shape

                row["tpu_shm_xproc"] = _stats(_timed_loop(step))
            finally:
                client.unregister_tpu_shared_memory()
                tpushm.destroy_shared_memory_region(rin)
                tpushm.destroy_shared_memory_region(rout)
            out[f"{nbytes // (1 << 20)}MiB"] = row
    finally:
        client.close()
    assert "jax" not in sys.modules, "the client child must stay off jax"
    print(json.dumps(out))


def bench_identity_xproc(url):
    """Run :func:`xproc_client` in a child process against this process's
    server and return its rows."""
    repo = os.path.dirname(os.path.abspath(__file__))
    proc = subprocess.run(
        [sys.executable, "-c",
         f"import sys; sys.path.insert(0, {repo!r}); "
         f"import bench; bench.xproc_client({url!r})"],
        capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"xproc client child exited {proc.returncode}: "
            f"{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# densenet contract (BASELINE.json config #3)
# ---------------------------------------------------------------------------


def bench_densenet(http_client, grpc_client, httpclient, grpcclient):
    import jax
    import numpy as np

    import client_tpu.utils.tpu_shared_memory as tpushm

    rng = np.random.default_rng(1)
    img_np = rng.standard_normal((3, 224, 224), dtype=np.float32)
    img_dev = jax.device_put(img_np)
    img_dev.block_until_ready()
    out = {}

    # wire HTTP, numpy input
    def step_wire():
        inp = httpclient.InferInput("data_0", [3, 224, 224], "FP32")
        inp.set_data_from_numpy(img_np)
        r = http_client.infer("densenet_onnx", [inp])
        assert r.as_numpy("fc6_1") is not None

    step_wire()  # build+compile outside the timed loop
    out["http_wire"] = _stats(_timed_loop(step_wire, DENSENET_ITERS))

    # GRPC, jax.Array input (device array fed straight to the tensor model)
    def step_grpc():
        inp = grpcclient.InferInput("data_0", [3, 224, 224], "FP32")
        inp.set_data_from_numpy(img_dev)
        r = grpc_client.infer("densenet_onnx", [inp])
        assert r.as_numpy("fc6_1") is not None

    step_grpc()
    out["grpc_jax_array"] = _stats(_timed_loop(step_grpc, DENSENET_ITERS))

    # tpu-shm HTTP: image written from the device array into a colocated
    # region; logits land in a region read back as a jax.Array
    import uuid

    in_bytes = img_np.nbytes
    out_bytes = 1000 * 4
    name_in = f"dn_in_{uuid.uuid4().hex[:8]}"
    name_out = f"dn_out_{uuid.uuid4().hex[:8]}"
    rin = tpushm.create_shared_memory_region(name_in, in_bytes, colocated=True)
    rout = tpushm.create_shared_memory_region(name_out, out_bytes, colocated=True)
    http_client.register_tpu_shared_memory(name_in, tpushm.get_raw_handle(rin), 0, in_bytes)
    http_client.register_tpu_shared_memory(name_out, tpushm.get_raw_handle(rout), 0, out_bytes)
    try:
        def step_shm():
            tpushm.set_shared_memory_region_from_jax(rin, img_dev)
            inp = httpclient.InferInput("data_0", [3, 224, 224], "FP32")
            inp.set_shared_memory(name_in, in_bytes)
            o = httpclient.InferRequestedOutput("fc6_1")
            o.set_shared_memory(name_out, out_bytes)
            http_client.infer("densenet_onnx", [inp], outputs=[o])
            logits = tpushm.get_contents_as_jax(rout, "FP32", [1000, 1, 1])
            logits.block_until_ready()

        step_shm()
        out["http_tpu_shm"] = _stats(_timed_loop(step_shm, DENSENET_ITERS))
    finally:
        http_client.unregister_tpu_shared_memory()
        tpushm.destroy_shared_memory_region(rin)
        tpushm.destroy_shared_memory_region(rout)
    return out


def bench_genai(grpc_url, http_url):
    """LLM serving metrics (genai-perf's role): TTFT / inter-token latency /
    token throughput in the three transports, at c=1 and c=4 — the
    decoupled-vs-sequence-batched comparison. A session that errors fails
    the run."""
    from client_tpu.genai_perf import GenAiPerfRunner

    out = {}
    for mode, runner_mode, url, model in (
        ("decoupled", "decoupled", grpc_url, "tiny_lm_generate"),
        ("generate_sse", "generate", http_url, "tiny_lm_generate"),
        ("sequence_batched", "sequence", grpc_url, "decoder_lm_batched"),
    ):
        runner = GenAiPerfRunner(url, model, runner_mode,
                                 prompt_tokens=16, output_tokens=16)
        runner.run(1, 1)  # warm the compile outside the measured sessions
        for conc in (1, 4):
            r = runner.run(conc, 6)
            if r["errors"]:
                raise RuntimeError(
                    f"genai {mode} c={conc}: {r['errors']} sessions failed")
            out[f"{mode}_c{conc}"] = {
                key: r[key]
                for key in ("sessions", "errors", "ttft_ms",
                            "inter_token_ms", "output_tokens_per_sec",
                            "requests_per_sec")
            }
    return out


def bench_native(url):
    """The C++ client's own wire-vs-tpu-shm race (native_bench), embedded
    when the native build exists; named as skipped otherwise."""
    binary = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "native", "build",
        "native_bench",
    )
    if not os.path.exists(binary):
        return {"skipped": "native/build/native_bench is not built"}
    proc = subprocess.run(
        # race the same payload as the Python headline (IDENTITY_SIZES[0])
        [binary, str(IDENTITY_SIZES[0]), "50"], capture_output=True, text=True,
        timeout=240, env={**os.environ, "CLIENT_TPU_TEST_URL": url},
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"native_bench exited {proc.returncode}: {proc.stderr[-500:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main():
    import jax
    import numpy as np

    first = jax.devices()[0]
    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(jax.devices())}
    if first.platform != "tpu":
        print(json.dumps({
            "ok": False, "device": device,
            "error": "bench.py measures the TPU and JAX found none"}))
        return 1

    from client_tpu.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()

    import client_tpu.grpc as grpcclient
    import client_tpu.http as httpclient
    from client_tpu.models.decoder_batched import BatchedDecoderModel
    from client_tpu.models.generate import TinyGenerateModel
    from client_tpu.models.simple import IdentityModel
    from client_tpu.models.vision import DenseNetModel
    from client_tpu.server import GrpcInferenceServer, HttpInferenceServer, ServerCore

    core = ServerCore([
        IdentityModel("identity_fp32", "FP32", delay_s=0.0),
        DenseNetModel(width=DENSENET_WIDTH),
        TinyGenerateModel(),
        BatchedDecoderModel(seed=0, slots=8),
    ])
    server = HttpInferenceServer(core)
    server.start()
    grpc_server = GrpcInferenceServer(core)
    grpc_server.start()
    # generous socket timeout: a 64 MiB round trip over the wire is slow
    client = httpclient.InferenceServerClient(
        server.url, concurrency=2, network_timeout=300.0)
    grpc_client = grpcclient.InferenceServerClient(grpc_server.url)

    rng = np.random.default_rng(0)
    identity = {}
    headline = None
    # a mode that raises ends the run: no partial headline, exit code != 0
    try:
        for n_elems in IDENTITY_SIZES:
            label = f"{n_elems * 4 // (1 << 20)}MiB"
            # 64 MiB wire/system rows are slow per iter: let the time cap
            # break them early rather than forcing 20
            floor = 20 if n_elems <= IDENTITY_SIZES[0] else 5
            x_np = rng.standard_normal(n_elems, dtype=np.float32).reshape(1, n_elems)
            wire = bench_identity_wire(
                client, httpclient, x_np, min_iters=floor)
            sysshm = bench_identity_shm(
                client, httpclient, x_np, "system", min_iters=floor)
            tpushm_t, tpu_xfer = bench_identity_shm(
                client, httpclient, x_np, "tpu", min_iters=floor)
            identity[label] = {
                "wire": _stats(wire),
                "system_shm": _stats(sysshm),
                "tpu_shm": {**_stats(tpushm_t), **tpu_xfer},
                "tpu_shm_infer_per_sec": round(
                    1.0 / _percentile(tpushm_t, 0.5), 1),
                "speedup_tpu_vs_wire": round(
                    _percentile(wire, 0.5) / _percentile(tpushm_t, 0.5), 3),
            }
            # the metric line is labeled "4 MiB": only that size may
            # feed it — a 64 MiB substitution would misreport
            if n_elems == IDENTITY_SIZES[0]:
                headline = (
                    _percentile(tpushm_t, 0.5),
                    _percentile(wire, 0.5),
                )

        xproc = bench_identity_xproc(server.url)
        densenet = bench_densenet(client, grpc_client, httpclient, grpcclient)
        genai = bench_genai(grpc_server.url, server.url)
        native = bench_native(server.url)
    finally:
        client.close()
        grpc_client.close()
        server.stop()
        grpc_server.stop()

    tpu_p50, wire_p50 = headline
    result = {
        "metric": "identity 4MiB infer p50 latency, shm=tpu",
        "value": round(tpu_p50 * 1000, 3),
        "unit": "ms",
        "vs_baseline": round(wire_p50 / tpu_p50, 3),
        "device": device,
        "detail": {
            "compile_cache_dir": cache_dir,
            "identity": identity,
            "identity_xproc": xproc,
            "densenet_onnx": {
                "width": DENSENET_WIDTH,
                **densenet,
            },
            "llm_genai": genai,
            "native_cpp_client": native,
        },
    }
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
