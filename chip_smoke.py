"""Chip smoke: the serving path, once, on the TPU, through the real sockets.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: the sharded phase only

One process, and it holds the chip. It builds a ``ServerCore`` the way
``client_tpu/serve.py`` does, starts the HTTP and GRPC frontends on
ephemeral ports and drives them with the repo's own clients: client ->
socket -> ``ServerCore`` -> jitted step on the chip -> response -> client.
The one child it spawns (the cross-process phase) is a numpy-only client
that never imports jax.

Each phase prints one JSON line (name, sizes, ok, smoke timings, compile
cache); a phase that fails raises, so the script exits non-zero and later
phases do not run. Timings are smoke timings — proof of life, not results.
The last line of stdout is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and on a machine whose JAX finds no TPU it is ``"ok": false`` at once, exit
code 1, with no phase run: no CPU pin, no probe subprocess, no interpret
mode. ``tests/test_chip_smoke.py`` runs the same phase functions at tiny
sizes on the CPU.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)


@dataclasses.dataclass
class Sizes:
    """What the smoke runs at. The defaults are the chip's; the CPU tests
    pass tiny ones, and ``kernel_marker=None`` where Pallas interprets."""

    vision_arch: str = "121"
    vision_width: int = 32
    vision_classes: int = 1000
    vision_concurrency: int = 8
    identity_bytes: Tuple[int, ...] = (4 << 20, 64 << 20)
    xproc_bytes: int = 4 << 20
    lm_prompt: int = 16
    lm_new_tokens: int = 32
    lm_sequences: int = 8
    long_context_seqs: Tuple[int, ...] = (4096, 300)
    sharded_seq: int = 4096
    # what a Mosaic-compiled Pallas kernel leaves in the step's HLO
    kernel_marker: Optional[str] = "tpu_custom_call"
    seed: int = 0


class SmokeFailure(AssertionError):
    pass


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


# ---------------------------------------------------------------------------
# compile cache accounting + phase lines
# ---------------------------------------------------------------------------

class CompileCache:
    """Where compiled programs are kept, and JAX's own count of the
    persistent cache's hits and misses (``jax.monitoring`` events)."""

    EVENTS = {
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "misses",
    }

    def __init__(self, directory: str):
        self.counts = {"dir": directory, "hits": 0, "misses": 0}

    def on_event(self, event: str, **_kwargs) -> None:
        key = self.EVENTS.get(event)
        if key:
            self.counts[key] += 1


class Phase:
    """One phase line: ``with Phase(...) as ph`` times the phase, ``with
    ph.first_call()`` sets apart the calls that compile. The line is printed
    only when the body ran to its end — a failure propagates."""

    def __init__(self, name: str, cache: CompileCache, **sizes: Any):
        self.name = name
        self.cache = cache
        self.fields: Dict[str, Any] = dict(sizes)
        self._first_s = 0.0

    def __enter__(self) -> "Phase":
        self._t0 = time.perf_counter()
        return self

    @contextlib.contextmanager
    def first_call(self):
        t0 = time.perf_counter()
        yield
        self._first_s += time.perf_counter() - t0

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            return
        wall = time.perf_counter() - self._t0
        print(json.dumps({
            "phase": self.name, "ok": True, **self.fields,
            "smoke_wall_s": round(wall, 3),
            "smoke_first_compile_s": round(self._first_s, 3),
            "compile_cache": dict(self.cache.counts),
        }), flush=True)


# ---------------------------------------------------------------------------
# server + clients
# ---------------------------------------------------------------------------


class Served:
    """A ServerCore behind HTTP and GRPC frontends on ephemeral ports, with
    one client each — built the way client_tpu/serve.py builds them."""

    def __init__(self, models: List[Any], http_concurrency: int = 8):
        import client_tpu.grpc as grpcclient
        import client_tpu.http as httpclient
        from client_tpu.server import (
            GrpcInferenceServer,
            HttpInferenceServer,
            ServerCore,
        )

        self.httpclient = httpclient
        self.grpcclient = grpcclient
        self.models = {m.name: m for m in models}
        self.core = ServerCore(models)
        self._http_server = HttpInferenceServer(self.core).start()
        self._grpc_server = GrpcInferenceServer(self.core).start()
        self.http_url = self._http_server.url
        self.grpc_url = self._grpc_server.url
        # 64 MiB bodies over loopback: generous socket timeout
        self.http = httpclient.InferenceServerClient(
            self.http_url, concurrency=http_concurrency,
            network_timeout=300.0)
        self.grpc = grpcclient.InferenceServerClient(self.grpc_url)

    def __enter__(self) -> "Served":
        return self

    def __exit__(self, *exc) -> None:
        self.http.close()
        self.grpc.close()
        self._http_server.stop()
        self._grpc_server.stop()
        for model in self.models.values():
            model.unload()  # joins the sequence batcher's worker


def one_chip_models(sizes: Sizes) -> List[Any]:
    """The zoo serve.py serves (--vision --long-context --attention flash),
    with the vision model at the densenet-121 layout and a Pallas-attention
    twin of each decoder."""
    from client_tpu.models import default_model_zoo
    from client_tpu.models.decoder import TinyDecoderModel
    from client_tpu.models.decoder_batched import BatchedDecoderModel
    from client_tpu.models.ensemble import build_image_ensemble
    from client_tpu.models.long_context import LongContextEncoderModel
    from client_tpu.models.vision import DenseNetModel

    models = default_model_zoo()
    preprocess, _, ensemble = build_image_ensemble(
        num_classes=sizes.vision_classes)
    models += [
        preprocess,
        DenseNetModel(num_classes=sizes.vision_classes,
                      width=sizes.vision_width, arch=sizes.vision_arch),
        ensemble,
        LongContextEncoderModel(attention="flash"),
    ]
    for twin, name in (
        (TinyDecoderModel(attention_impl="pallas"), "decoder_lm_pallas"),
        (BatchedDecoderModel(slots=sizes.lm_sequences,
                             attention_impl="pallas"),
         "decoder_lm_batched_pallas"),
    ):
        twin.name = name
        models.append(twin)
    return models


def _tensor(client_mod, name: str, arr: np.ndarray, datatype: str):
    """An ``InferInput`` of ``client_mod`` (http: binary data) holding arr."""
    inp = client_mod.InferInput(name, list(arr.shape), datatype)
    inp.set_data_from_numpy(arr)
    return inp


@contextlib.contextmanager
def _tpu_regions(served: Served, byte_sizes: Dict[str, int],
                 colocated: bool = False):
    """tpu-shm regions, created and registered by raw handle; unregistered
    and destroyed on the way out."""
    import client_tpu.utils.tpu_shared_memory as tpushm

    regions = [tpushm.create_shared_memory_region(
        name, nbytes, colocated=colocated)
        for name, nbytes in byte_sizes.items()]
    try:
        for region in regions:
            served.http.register_tpu_shared_memory(
                region.name, tpushm.get_raw_handle(region), 0,
                region.byte_size)
        yield regions
    finally:
        served.http.unregister_tpu_shared_memory()
        for region in regions:
            tpushm.destroy_shared_memory_region(region)


# ---------------------------------------------------------------------------
# phase: protocol
# ---------------------------------------------------------------------------


def phase_protocol(served: Served, sizes: Sizes,
                   cache: CompileCache) -> None:
    with Phase("protocol", cache, model="simple", shape=[1, 16]) as ph:
        a = np.arange(16, dtype=np.int32).reshape(1, 16)
        b = np.full((1, 16), 3, np.int32)
        for label, client, mod in (
            ("http", served.http, served.httpclient),
            ("grpc", served.grpc, served.grpcclient),
        ):
            meta = client.get_server_metadata()
            check("tpu_shared_memory" in meta["extensions"],
                  f"{label}: server metadata lacks the tpu shm extension")
            model_meta = client.get_model_metadata("simple")
            check([t["name"] for t in model_meta["inputs"]]
                  == ["INPUT0", "INPUT1"], f"{label}: simple's metadata")
            with ph.first_call():
                result = client.infer("simple", [
                    _tensor(mod, "INPUT0", a, "INT32"),
                    _tensor(mod, "INPUT1", b, "INT32")])
            check(np.array_equal(result.as_numpy("OUTPUT0"), a + b)
                  and np.array_equal(result.as_numpy("OUTPUT1"), a - b),
                  f"{label}: simple add/sub differs from numpy")

        values, total = [4, 3, 2, 1], None
        for i, v in enumerate(values):
            result = served.http.infer(
                "simple_sequence",
                [_tensor(served.httpclient, "INPUT",
                             np.array([[v]], np.int32), "INT32")],
                sequence_id=4001, sequence_start=(i == 0),
                sequence_end=(i == len(values) - 1))
            total = int(result.as_numpy("OUTPUT")[0, 0])
        check(total == sum(values), f"simple_sequence total {total}")


# ---------------------------------------------------------------------------
# phase: vision at full width
# ---------------------------------------------------------------------------


def phase_vision(served: Served, sizes: Sizes,
                 cache: CompileCache) -> None:
    import jax

    import client_tpu.utils.tpu_shared_memory as tpushm

    classes, n = sizes.vision_classes, sizes.vision_concurrency
    with Phase("vision", cache, model="densenet_onnx",
               arch=sizes.vision_arch, width=sizes.vision_width,
               input=[3, 224, 224], classes=classes, concurrent=n) as ph:
        rng = np.random.default_rng(sizes.seed + 1)
        img = rng.standard_normal((3, 224, 224), dtype=np.float32)
        img_dev = jax.device_put(img)

        with ph.first_call():  # compiles the init and the forward pass
            forward, params = served.models["densenet_onnx"].forward_fn()
            direct = np.asarray(forward(params, img_dev[None])).reshape(-1)
        check(direct.shape == (classes,) and np.isfinite(direct).all(),
              "direct logits are not finite [classes]")
        check(float(direct.max() - direct.min()) > 0,
              "direct logits are constant")

        def over_http(_):
            r = served.http.infer("densenet_onnx", [
                _tensor(served.httpclient, "data_0", img, "FP32")])
            return r.as_numpy("fc6_1").reshape(-1)

        def over_grpc(_):
            r = served.grpc.infer("densenet_onnx", [
                _tensor(served.grpcclient, "data_0", img, "FP32")])
            return r.as_numpy("fc6_1").reshape(-1)

        # tpu-shm: one colocated region holds the image as a jax.Array; each
        # concurrent request gets its own output region
        in_bytes, out_bytes = img.nbytes, classes * 4
        with _tpu_regions(
                served, {"smoke_dn_in": in_bytes,
                         **{f"smoke_dn_out{i}": out_bytes for i in range(n)}},
                colocated=True) as (rin, *routs):
            tpushm.set_shared_memory_region_from_jax(rin, img_dev)

            def over_tpu_shm(i):
                inp = served.httpclient.InferInput(
                    "data_0", [3, 224, 224], "FP32")
                inp.set_shared_memory(rin.name, in_bytes)
                out = served.httpclient.InferRequestedOutput("fc6_1")
                out.set_shared_memory(routs[i].name, out_bytes)
                served.http.infer("densenet_onnx", [inp], outputs=[out])
                logits = tpushm.get_contents_as_jax(
                    routs[i], "FP32", [classes, 1, 1])
                return np.asarray(logits).reshape(-1)

            with ThreadPoolExecutor(n) as pool:
                for label, send in (("http", over_http), ("grpc", over_grpc),
                                    ("tpu_shm", over_tpu_shm)):
                    for got in pool.map(send, range(n)):
                        check(got.tobytes() == direct.tobytes(),
                              f"{label} logits differ from forward_fn()")

        # the ensemble runs preprocess_image, then the same densenet
        from client_tpu.ops import preprocess_image

        raw = rng.integers(0, 256, size=(256, 320, 3), dtype=np.uint8)
        with ph.first_call():
            r = served.http.infer("ensemble_image", [
                _tensor(served.httpclient, "IMAGE", raw, "UINT8")])
        got = r.as_numpy("CLASSIFICATION").reshape(-1)
        want = np.asarray(forward(
            params, preprocess_image(raw, 224, 224)[None])).reshape(-1)
        check(got.tobytes() == want.tobytes(),
              "ensemble_image differs from preprocess_image + forward_fn()")
        ph.fields["logit_range"] = [float(direct.min()), float(direct.max())]


# ---------------------------------------------------------------------------
# phase: data plane at real sizes
# ---------------------------------------------------------------------------


def _identity_over_regions(served: Served, name_in: str, name_out: str,
                           nbytes: int, shape) -> None:
    inp = served.httpclient.InferInput("INPUT0", list(shape), "FP32")
    inp.set_shared_memory(name_in, nbytes)
    out = served.httpclient.InferRequestedOutput("OUTPUT0")
    out.set_shared_memory(name_out, nbytes)
    served.http.infer("identity_fp32", [inp], outputs=[out])


def phase_data_plane(served: Served, sizes: Sizes, cache: CompileCache,
                     platform: str) -> None:
    import jax

    import client_tpu.utils.shared_memory as sysshm
    import client_tpu.utils.tpu_shared_memory as tpushm
    from client_tpu._base import InferStat, RequestTimers

    with Phase("data_plane", cache, model="identity_fp32",
               bytes=list(sizes.identity_bytes),
               arms=["wire", "system_shm", "tpu_shm_numpy",
                     "tpu_shm_colocated"]) as ph:
        rng = np.random.default_rng(sizes.seed + 2)
        for nbytes in sizes.identity_bytes:
            x = rng.standard_normal(nbytes // 4, dtype=np.float32)[None]
            shape, want = x.shape, x.tobytes()

            # wire: tensor bytes in the request and the response
            with ph.first_call():
                r = served.http.infer("identity_fp32", [
                    _tensor(served.httpclient, "INPUT0", x, "FP32")])
            check(r.as_numpy("OUTPUT0").tobytes() == want,
                  f"wire {nbytes}B: output bytes differ")

            # system shm
            names = ("smoke_sys_in", "smoke_sys_out")
            regions = [sysshm.create_shared_memory_region(
                name, f"/{name}", nbytes) for name in names]
            try:
                for name in names:
                    served.http.register_system_shared_memory(
                        name, f"/{name}", nbytes)
                sysshm.set_shared_memory_region(regions[0], [x])
                _identity_over_regions(served, names[0], names[1], nbytes,
                                       shape)
                got = sysshm.get_contents_as_numpy(
                    regions[1], np.float32, list(shape))
                check(got.tobytes() == want,
                      f"system shm {nbytes}B: output bytes differ")
                del got  # a view over the mapping: drop before unmapping
            finally:
                served.http.unregister_system_shared_memory()
                for region in regions:
                    sysshm.destroy_shared_memory_region(region)

            # tpu shm, written from numpy: host window in, host window out
            with _tpu_regions(
                    served, {"smoke_tpu_in": nbytes,
                             "smoke_tpu_out": nbytes}) as (rin, rout):
                tpushm.set_shared_memory_region(rin, [x])
                _identity_over_regions(served, rin.name, rout.name, nbytes,
                                       shape)
                got = tpushm.get_contents_as_numpy(rout, "FP32", list(shape))
                check(got.tobytes() == want,
                      f"tpu shm from numpy {nbytes}B: output bytes differ")
                del got

            # tpu shm colocated, written from a jax.Array: the tensor never
            # leaves HBM — no H2D/D2H interval is timed and neither host
            # window is ever written
            x_dev = jax.device_put(x)
            x_dev.block_until_ready()
            with _tpu_regions(
                    served, {"smoke_colo_in": nbytes,
                             "smoke_colo_out": nbytes},
                    colocated=True) as (rin, rout):
                stat, timers = InferStat(), RequestTimers()
                timers.capture(RequestTimers.REQUEST_START)
                tpushm.set_shared_memory_region_from_jax(
                    rin, x_dev, timers=timers)
                _identity_over_regions(served, rin.name, rout.name, nbytes,
                                       shape)
                out = tpushm.get_contents_as_jax(
                    rout, "FP32", list(shape), timers=timers)
                out.block_until_ready()
                timers.capture(RequestTimers.REQUEST_END)
                stat.update(timers)
                check(isinstance(out, jax.Array),
                      f"colocated {nbytes}B: result is {type(out).__name__}")
                on = {d.platform for d in out.devices()}
                check(on == {platform},
                      f"colocated {nbytes}B: result lives on {on}")
                copied = stat.as_dict()
                host_copy_ns = (copied["cumulative_h2d_time_ns"]
                                + copied["cumulative_d2h_time_ns"])
                check(host_copy_ns == 0,
                      f"colocated {nbytes}B: {host_copy_ns} ns of H2D/D2H")
                host_bytes = sum(
                    int(np.count_nonzero(np.frombuffer(
                        region.host_buffer(), np.uint8)))
                    for region in (rin, rout))
                check(host_bytes == 0,
                      f"colocated {nbytes}B: {host_bytes} host-window bytes "
                      f"were written")
                # the smoke's own readback, after the zero-copy checks
                check(np.asarray(out).tobytes() == want,
                      f"colocated {nbytes}B: output bytes differ")
        ph.fields["colocated_host_copy_bytes"] = 0
        ph.fields["colocated_h2d_d2h_ns"] = 0


# ---------------------------------------------------------------------------
# phase: cross-process (the server owns the chip, the client stays off jax)
# ---------------------------------------------------------------------------

XPROC_CLIENT = """
import json, sys
sys.path.insert(0, {repo!r})
import numpy as np
import client_tpu.http as httpclient
import client_tpu.utils.tpu_shared_memory as tpushm

nbytes = {nbytes}
x = np.random.default_rng({seed}).standard_normal(
    nbytes // 4, dtype=np.float32)[None]
rin = tpushm.create_shared_memory_region("xproc_in", nbytes)
rout = tpushm.create_shared_memory_region("xproc_out", nbytes)
try:
    with httpclient.InferenceServerClient(
            {url!r}, network_timeout=300.0) as client:
        for region in (rin, rout):
            client.register_tpu_shared_memory(
                region.name, tpushm.get_raw_handle(region), 0, nbytes)
        tpushm.set_shared_memory_region(rin, [x])
        inp = httpclient.InferInput("INPUT0", list(x.shape), "FP32")
        inp.set_shared_memory("xproc_in", nbytes)
        out = httpclient.InferRequestedOutput("OUTPUT0")
        out.set_shared_memory("xproc_out", nbytes)
        client.infer("identity_fp32", [inp], outputs=[out])
        got = tpushm.get_contents_as_numpy(rout, "FP32", list(x.shape))
        equal = got.tobytes() == x.tobytes()
        del got
        client.unregister_tpu_shared_memory()
finally:
    tpushm.destroy_shared_memory_region(rin)
    tpushm.destroy_shared_memory_region(rout)
jax_imported = "jax" in sys.modules
print(json.dumps({{"bytes_equal": equal, "jax_imported": jax_imported}}))
sys.exit(0 if equal and not jax_imported else 1)
"""


def phase_cross_process(served: Served, sizes: Sizes,
                        cache: CompileCache) -> None:
    with Phase("cross_process", cache, model="identity_fp32",
               bytes=sizes.xproc_bytes,
               arrangement="server process holds the chip; numpy-only "
                           "client child registers a tpu-shm region by raw "
                           "handle") as ph:
        script = XPROC_CLIENT.format(
            repo=REPO, nbytes=sizes.xproc_bytes, seed=sizes.seed + 3,
            url=served.http_url)
        # subprocess.run kills the child at the timeout; it needs no chip
        proc = subprocess.run(
            [sys.executable, "-c", script], capture_output=True, text=True,
            timeout=300)
        check(proc.returncode == 0,
              f"client child exited {proc.returncode}: {proc.stderr[-2000:]}")
        child = json.loads(proc.stdout.strip().splitlines()[-1])
        check(child == {"bytes_equal": True, "jax_imported": False},
              f"client child reported {child}")
        ph.fields["child"] = child


# ---------------------------------------------------------------------------
# phase: language model
# ---------------------------------------------------------------------------


def _stream_grpc(served: Served, prompt: np.ndarray, n: int) -> List[int]:
    import queue

    results: "queue.Queue" = queue.Queue()
    served.grpc.start_stream(callback=lambda r, e: results.put((r, e)))
    try:
        served.grpc.async_stream_infer(
            "tiny_lm_generate",
            [_tensor(served.grpcclient, "TOKENS", prompt[None], "INT32"),
             _tensor(served.grpcclient, "MAX_TOKENS", np.array([n], np.int32),
                         "INT32")],
            enable_empty_final_response=True)
        tokens = []
        while True:
            result, error = results.get(timeout=300)
            check(error is None, f"decoupled stream error: {error}")
            if result.is_final_response() and result.is_null_response():
                return tokens
            tokens.append(int(result.as_numpy("NEXT_TOKEN").reshape(-1)[0]))
    finally:
        served.grpc.stop_stream()


def _stream_sse(served: Served, prompt: np.ndarray, n: int) -> List[int]:
    return [int(event["NEXT_TOKEN"]) for event in served.http.generate_stream(
        "tiny_lm_generate",
        {"TOKENS": [prompt.tolist()], "MAX_TOKENS": n})]


def _decode_sequence(served: Served, model: str, seq_id: int,
                     prompt: np.ndarray, n: int, feed=None, end: bool = True):
    """Decode over the sequence API: the prompt on sequence_start, then one
    token per request — the model's own greedy token, or ``feed[i]`` (the
    reference's) where given. Returns (tokens [n], logits [n, vocab])."""
    tokens, logits, nxt = [], [], prompt[None].astype(np.int32)
    for i in range(n):
        result = served.http.infer(
            model, [_tensor(served.httpclient, "TOKENS", nxt, "INT32")],
            sequence_id=seq_id, sequence_start=(i == 0),
            sequence_end=(end and i == n - 1))
        logits.append(result.as_numpy("LOGITS")[0])
        tokens.append(int(result.as_numpy("NEXT_TOKEN")[0, 0]))
        nxt = np.array([[tokens[-1] if feed is None else feed[i]]], np.int32)
    return tokens, np.stack(logits)


# Programs of another shape (the slot-batched step, the Pallas kernel, the
# tp step) round differently from decoder_lm's step: logits are bf16-valued
# and sit within an ulp or two of each other (|logit| <= 1: ulp 2^-8..2^-7),
# so greedy tokens may part at a near-tie. Each is therefore fed decoder_lm's
# tokens and held to its logits; only the SAME compiled step is bit-equal.
LM_LOGIT_TOL = 2e-2


def _held_to_reference(name: str, got, want) -> Dict[str, Any]:
    """``got``/``want``: per sequence (tokens, logits). Logits within
    LM_LOGIT_TOL everywhere; tokens may differ only at a near-tie."""
    diff = max(float(np.abs(g[1] - w[1]).max()) for g, w in zip(got, want))
    check(diff <= LM_LOGIT_TOL,
          f"{name}: logits off decoder_lm's by {diff} > {LM_LOGIT_TOL}")
    parted, steps = 0, 0
    for (tokens, _), (ref_tokens, ref_logits) in zip(got, want):
        top2 = np.sort(ref_logits, axis=-1)[:, -2:]
        for i, (a, b) in enumerate(zip(tokens, ref_tokens)):
            steps += 1
            if a != b:
                parted += 1
                margin = float(top2[i, 1] - top2[i, 0])
                check(margin <= 2 * LM_LOGIT_TOL,
                      f"{name}: token {a} != {b} at a margin of {margin}")
    return {"max_abs_logit_diff": diff, "greedy_tokens_parted": parted,
            "of_steps": steps,
            "logits_bit_equal": all(
                g[1].tobytes() == w[1].tobytes() for g, w in zip(got, want))}


def _served_step_hlo(model) -> str:
    """The compiled text of the step ``model`` serves, lowered from what it
    serves with."""
    if hasattr(model, "_batched_step"):
        dec, slots = model._decoder, model.slots
        args = (dec._params, model._caches, np.zeros((slots,), np.int32),
                np.zeros((slots,), np.int32), np.zeros((slots,), bool))
        return model._batched_step.lower(*args).compile().as_text()
    return model._step_fn.lower(
        model._params, model._fresh_cache(), 0, 0).compile().as_text()


def phase_language_model(served: Served, sizes: Sizes,
                         cache: CompileCache) -> None:
    n, count = sizes.lm_new_tokens, sizes.lm_sequences
    with Phase("language_model", cache, prompt_tokens=sizes.lm_prompt,
               new_tokens=n, concurrent_sequences=count) as ph:
        rng = np.random.default_rng(sizes.seed + 4)
        prompts = rng.integers(0, 256, size=(count, sizes.lm_prompt),
                               dtype=np.int32)

        with ph.first_call():
            generated = _stream_grpc(served, prompts[0], n)
        check(len(generated) == n, f"stream gave {len(generated)} tokens")
        check(_stream_sse(served, prompts[0], n) == generated,
              "SSE /generate_stream tokens differ from the GRPC stream's")

        with ThreadPoolExecutor(count) as pool:
            with ph.first_call():
                reference = list(pool.map(
                    lambda i: _decode_sequence(
                        served, "decoder_lm", 5000 + i, prompts[i], n),
                    range(count)))
            # the streams share a round, a program of another shape than
            # decoder_lm's step: decoder_lm is fed the stream's tokens, and
            # each has to lie within a near-tie of decoder_lm's own choice
            _, forced = _decode_sequence(
                served, "decoder_lm", 6000, prompts[0], n, feed=generated)
            behind = max(float(row.max() - row[token])
                         for row, token in zip(forced, generated))
            check(behind <= 2 * LM_LOGIT_TOL,
                  f"tiny_lm_generate chose a token {behind} under decoder_lm's")
            ph.fields["stream_behind_decoder_lm"] = behind
            ph.fields["stream_round_widths"] = sorted(
                served.models["tiny_lm_generate"].rounds_by_width)
            ph.fields["logit_tol"] = LM_LOGIT_TOL
            for model in ("decoder_lm_batched", "decoder_lm_pallas",
                          "decoder_lm_batched_pallas"):
                with ph.first_call():
                    got = list(pool.map(
                        lambda i: _decode_sequence(
                            served, model, 5000 + i, prompts[i], n,
                            feed=reference[i][0]),
                        range(count)))
                ph.fields[model] = _held_to_reference(model, got, reference)

        batched = served.models["decoder_lm_batched_pallas"]
        ph.fields["batch_widths"] = sorted(batched.batch_histogram)
        check(max(batched.batch_histogram) > 1 or count == 1,
              "the sequence batcher never shared a dispatch")
        if sizes.kernel_marker:
            for name in ("decoder_lm_pallas", "decoder_lm_batched_pallas"):
                check(sizes.kernel_marker in _served_step_hlo(
                    served.models[name]),
                    f"{name}: no {sizes.kernel_marker} in the served step")
        ph.fields["kernel_in_served_step"] = sizes.kernel_marker
        ph.fields["tokens"] = generated


# ---------------------------------------------------------------------------
# phase: long context
# ---------------------------------------------------------------------------


def plain_encoder(x, weights, heads: int):
    """The long_context_encoder's layer as plain float32 jax.numpy: the
    whole [heads, seq, seq] score matrix, full-precision matmuls."""
    import jax
    import jax.numpy as jnp

    wq, wk, wv, wo = weights
    seq, dim = x.shape
    hi = jax.lax.Precision.HIGHEST

    def project(w):
        return jnp.matmul(x, w, precision=hi).reshape(seq, heads, dim // heads)

    q, k, v = project(wq), project(wk), project(wv)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=hi)
    probs = jax.nn.softmax(scores * (dim // heads) ** -0.5, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v, precision=hi)
    return jnp.matmul(out.reshape(seq, dim), wo, precision=hi)


LONG_CONTEXT_TOL = 2e-2  # default-precision f32 projections on the MXU


def _encode(served: Served, model: str, x: np.ndarray) -> np.ndarray:
    r = served.http.infer(
        model, [_tensor(served.httpclient, "sequence", x, "FP32")])
    return r.as_numpy("encoded")


def phase_long_context(served: Served, sizes: Sizes,
                       cache: CompileCache) -> None:
    model = served.models["long_context_encoder"]
    with Phase("long_context", cache, model=model.name,
               attention="flash", seqs=list(sizes.long_context_seqs),
               dim=model._dim, heads=model._heads,
               tol=LONG_CONTEXT_TOL) as ph:
        rng = np.random.default_rng(sizes.seed + 5)
        errors = []
        for seq in sizes.long_context_seqs:
            x = rng.standard_normal((seq, model._dim), dtype=np.float32)
            with ph.first_call():
                got = _encode(served, model.name, x)
            want = np.asarray(plain_encoder(x, model.weights, model._heads))
            check(got.shape == want.shape and np.isfinite(got).all(),
                  f"seq {seq}: encoded is not finite {want.shape}")
            errors.append(float(np.abs(got - want).max()))
            check(errors[-1] <= LONG_CONTEXT_TOL,
                  f"seq {seq}: max |flash - plain| = {errors[-1]}")
        ph.fields["max_abs_err"] = errors


# ---------------------------------------------------------------------------
# --chips 4: the sharded phase and its single-device comparison
# ---------------------------------------------------------------------------


def _distinct_devices(tree) -> int:
    import jax

    devices = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        devices |= set(leaf.sharding.device_set)
    return len(devices)


def phase_sharded(sizes: Sizes, cache: CompileCache, chips: int) -> None:
    """decoder_lm_tp at tp=chips and ring attention over chips devices,
    each beside its single-device twin in the same server."""
    import jax

    from client_tpu.models.decoder import TinyDecoderModel
    from client_tpu.models.decoder_tp import TPDecoderModel
    from client_tpu.models.long_context import LongContextEncoderModel
    from client_tpu.parallel.ring import place_sharded

    tp = TPDecoderModel(seed=0, tp=chips)
    ring = LongContextEncoderModel(attention="ring", n_devices=chips)
    flash = LongContextEncoderModel(attention="flash")
    ring.name, flash.name = "long_context_ring", "long_context_flash"
    n = sizes.lm_new_tokens
    with Served([TinyDecoderModel(seed=0), tp, ring, flash]) as served:
        with Phase("sharded_decoder", cache, model=tp.name, tp=chips,
                   prompt_tokens=sizes.lm_prompt, new_tokens=n) as ph:
            rng = np.random.default_rng(sizes.seed + 6)
            prompt = rng.integers(0, 256, size=sizes.lm_prompt,
                                  dtype=np.int32)
            with ph.first_call():
                want = _decode_sequence(served, "decoder_lm", 6001, prompt, n)
            # the tp sequence stays open, so that its live caches can be
            # looked at where they sit
            with ph.first_call():
                got = _decode_sequence(served, tp.name, 6002, prompt, n,
                                       feed=want[0], end=False)
            ph.fields.update(
                logit_tol=LM_LOGIT_TOL,
                **_held_to_reference(tp.name, [got], [want]))
            ph.fields["param_devices"] = _distinct_devices(tp._params)
            ph.fields["cache_devices"] = _distinct_devices(
                tp._sequences[6002]["caches"])
            check(ph.fields["param_devices"] == chips
                  and ph.fields["cache_devices"] == chips,
                  f"tp params/caches sit on {ph.fields['param_devices']}/"
                  f"{ph.fields['cache_devices']} devices, not {chips}")
            check(tp.tp_degree == chips, f"tp degree {tp.tp_degree}")
            served.http.infer(
                tp.name, [_tensor(served.httpclient, "TOKENS", np.array(
                    [[want[0][-1]]], np.int32), "INT32")],
                sequence_id=6002, sequence_end=True)

        seq = sizes.sharded_seq
        with Phase("sharded_long_context", cache, attention="ring",
                   devices=chips, seq=seq, compared_with="flash on one",
                   tol=LONG_CONTEXT_TOL) as ph:
            rng = np.random.default_rng(sizes.seed + 7)
            x = rng.standard_normal((seq, ring._dim), dtype=np.float32)
            with ph.first_call():
                got = _encode(served, ring.name, x)
                want = _encode(served, flash.name, x)
            check(np.isfinite(got).all(), "ring output is not finite")
            ph.fields["max_abs_diff"] = float(np.abs(got - want).max())
            check(ph.fields["max_abs_diff"] <= LONG_CONTEXT_TOL,
                  f"max |ring - flash| = {ph.fields['max_abs_diff']}")
            mesh, run = ring._ensure_built()
            placed = place_sharded(jax.numpy.asarray(x)[None], mesh)
            ph.fields["input_devices"] = _distinct_devices(placed)
            ph.fields["output_devices"] = _distinct_devices(run(x))
            check(ph.fields["input_devices"] == chips,
                  f"ring input sits on {ph.fields['input_devices']} devices")


# ---------------------------------------------------------------------------


def run_one_chip(sizes: Sizes, cache: CompileCache, platform: str) -> None:
    with Served(one_chip_models(sizes),
                http_concurrency=max(sizes.vision_concurrency,
                                     sizes.lm_sequences)) as served:
        phase_protocol(served, sizes, cache)
        phase_vision(served, sizes, cache)
        phase_data_plane(served, sizes, cache, platform)
        phase_cross_process(served, sizes, cache)
        phase_language_model(served, sizes, cache)
        phase_long_context(served, sizes, cache)
    # the C++ client is not part of the committed tree's run: nothing here
    # builds native/, and no stale native/build is picked up
    print(json.dumps({"phase": "native_client", "ok": True, "skipped":
                      "native/ is not built by the smoke"}), flush=True)


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="chip_smoke.py")
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the sharded phase and its single-device twin")
    args = parser.parse_args(argv)

    import jax

    first = jax.devices()[0]
    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(jax.devices())}
    if first.platform != "tpu" or device["count"] < args.chips:
        print(json.dumps({"ok": False, "device": device}), flush=True)
        return 1

    from client_tpu.compile_cache import enable_compile_cache

    cache = CompileCache(enable_compile_cache())
    jax.monitoring.register_event_listener(cache.on_event)
    sizes = Sizes()
    t0 = time.perf_counter()
    if args.chips == 1:
        run_one_chip(sizes, cache, first.platform)
    else:
        phase_sharded(sizes, cache, args.chips)
    print(json.dumps({
        "smoke": "summary", "chips": args.chips,
        "smoke_wall_s": round(time.perf_counter() - t0, 3),
        "compile_cache": dict(cache.counts),
        "claim": None,
    }), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
