"""Compute-bound chip benchmark: MXU sustained rate, Pallas flash attention,
and the densenet model family, with MFU estimates.

A compute-bound chip benchmark: infer/sec plus an MFU estimate. Per-dispatch
wall-clock is dominated by dispatch overhead for sub-ms ops, so every
measurement here chains N iterations INSIDE one jitted computation
(`lax.fori_loop` / unrolled chain) and divides one dispatch's wall time by
N. First compile is excluded by a warmup dispatch.

Prints one JSON object; run on the chip via
    python tools/chip_bench.py [--json-out PATH]

Reference parity: perf_analyzer's concurrency/throughput role for the
compute-bound regime (the reference publishes no numbers); MFU framing
follows the public scaling-book convention (achieved FLOPs / peak FLOPs).
A device whose peak is not in the table is an error, not a default.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# bf16 peak TFLOP/s per chip generation (public spec sheets); device_kind
# strings as PJRT reports them
PEAK_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,  # v5e
    "TPU v5": 459.0,  # v5p
    "TPU v6 lite": 918.0,  # v6e/Trillium
}


def _peak_for(kind: str) -> float:
    for prefix, peak in sorted(PEAK_TFLOPS.items(), key=lambda kv: -len(kv[0])):
        if kind.startswith(prefix):
            return peak
    raise ValueError(
        f"no bf16 peak on record for device_kind {kind!r}; add it to "
        f"PEAK_TFLOPS with its source")


def _timed_single_dispatch(fn, *args, iters_inside: int, repeats: int = 5):
    """Median wall time of one dispatch that runs ``iters_inside`` steps.

    The shared timing primitive for every chip tool (decode_attn_chip,
    flash_sweep import it) — methodology changes here change all numbers
    together, keeping them comparable."""
    fn(*args).block_until_ready()  # compile + warm
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        times.append((time.perf_counter() - t0) / iters_inside)
    return sorted(times)[len(times) // 2]


def bench_dispatch_overhead(jax, jnp, np, repeats=9):
    """Median wall time of a trivial synchronous dispatch: the
    per-dispatch floor every blocked measurement pays. Subtract it mentally
    from any single-dispatch number."""
    one = jnp.ones((8,), jnp.float32)
    f = jax.jit(lambda x: x + 1.0)
    dt = _timed_single_dispatch(f, one, iters_inside=1, repeats=repeats)
    return round(dt * 1000, 3)


def bench_matmul(jax, jnp, np, n=4096, chain=16, pipeline=8):
    """Sustained MXU rate: ``chain`` dependent n^3 bf16 matmuls per dispatch.

    Two timings: ``blocked`` (block every dispatch — includes one full
    dispatch RTT, the honest end-to-end number) and ``pipelined``
    (``pipeline`` dispatches in flight, block the last — amortizes the
    dispatch overhead, the best estimate of the device-side rate)."""
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((n, n), dtype=np.float32),
                    dtype=jnp.bfloat16)

    @jax.jit
    def chained(x):
        # pure dependent chain: each matmul needs the previous result, so
        # nothing can be elided or reordered; XLA does not rewrite
        # (x@a)@a -> x@(a@a). A tanh between steps (tried first) adds ~4 ms
        # of VPU transcendental per step and corrupts the MXU number.
        for _ in range(chain):
            x = x @ a
        return x

    dt_blocked = _timed_single_dispatch(chained, a, iters_inside=chain)

    chained(a).block_until_ready()
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        out = None
        for _ in range(pipeline):
            out = chained(a)
        out.block_until_ready()
        times.append((time.perf_counter() - t0) / (pipeline * chain))
    dt_pipelined = sorted(times)[len(times) // 2]

    flops = 2 * n**3
    return {"n": n, "chain": chain,
            "ms_per_matmul_blocked": round(dt_blocked * 1000, 3),
            "tflops_blocked": round(flops / dt_blocked / 1e12, 3),
            "ms_per_matmul_pipelined": round(dt_pipelined * 1000, 3),
            "tflops": round(flops / dt_pipelined / 1e12, 3)}


def bench_flash_attention(jax, jnp, np, batch=4, seq=2048, heads=8, dim=128,
                          steps=10):
    """Pallas flash attention under real Mosaic, chained in one dispatch."""
    from client_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(1)
    shape = (batch, seq, heads, dim)

    def mk():
        return jnp.asarray(rng.standard_normal(shape, dtype=np.float32),
                           dtype=jnp.bfloat16)

    q, k, v = mk(), mk(), mk()

    @jax.jit
    def chained(q, k, v):
        def body(_, acc):
            o = flash_attention(q, k, v)
            # full-output reduction: a scalar slice would let XLA narrow
            # the computation (it can't see into pallas_call, but keep the
            # protocol uniform with bench_densenet where slicing bit)
            return acc + jnp.sum(o.astype(jnp.float32))

        return jax.lax.fori_loop(0, steps, body, jnp.float32(0))

    dt = _timed_single_dispatch(chained, q, k, v, iters_inside=steps)
    flops = 4 * batch * heads * seq * seq * dim  # QK^T + PV, 2*S*S*D each
    return {"batch": batch, "seq": seq, "heads": heads, "dim": dim,
            "ms_per_call": round(dt * 1000, 3),
            "tflops": round(flops / dt / 1e12, 3)}


def _flax_model_flops(width, stages, num_classes):
    """Forward-pass FLOPs for models/vision.py's DenseNetish at 224x224 via
    XLA's own cost analysis (exact for the compiled graph)."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models.vision import _build_flax_model

    module = _build_flax_model(num_classes, width, stages)
    params = module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 224, 224, 3), jnp.bfloat16))
    lowered = jax.jit(module.apply).lower(
        params, jnp.zeros((1, 224, 224, 3), jnp.bfloat16))
    cost = lowered.compile().cost_analysis()
    return float(cost["flops"]), module, params


def bench_densenet(jax, jnp, np, width, arch, steps=20, batch=8):
    """On-device forward rate for the densenet family at serving batch."""
    from client_tpu.models.vision import DenseNetModel

    flops1, module, params = _flax_model_flops(
        width, DenseNetModel.ARCHS[arch], 1000)
    rng = np.random.default_rng(2)
    x = jnp.asarray(
        rng.standard_normal((batch, 224, 224, 3), dtype=np.float32),
        dtype=jnp.bfloat16)

    @jax.jit
    def chained(params, x):
        def body(_, carry):
            out = module.apply(params, x)
            # sum over the WHOLE batch: carrying out[0, 0] alone let XLA
            # slice the conv stack to batch=1 (measured "MFU" 1.28 — the
            # impossible number that exposed it)
            return carry + jnp.sum(out.astype(jnp.float32))

        return jax.lax.fori_loop(0, steps, body, jnp.float32(0))

    dt = _timed_single_dispatch(chained, params, x, iters_inside=steps)
    flops = flops1 * batch  # cost_analysis counted the batch=1 graph
    return {"width": width, "arch": arch, "batch": batch,
            "ms_per_batch": round(dt * 1000, 3),
            "images_per_sec": round(batch / dt, 1),
            "gflops_per_image": round(flops1 / 1e9, 2),
            "tflops": round(flops / dt / 1e12, 2)}


def bench_generate(jax, jnp, np, prompt=32, k=64):
    """Autoregressive decode rate for the tiny_lm_generate fixture.

    Two numbers: per-token dispatch (each step blocked — the chunk=1
    streaming-serving latency, paying one dispatch RTT per token) and the
    lax.scan chunked path (K tokens inside ONE XLA dispatch — the
    dispatch-amortized device decode rate). Their ratio is the dispatch
    amortization the scan-in-XLA design buys (genai-perf's ITL regime)."""
    from client_tpu.models.generate import TinyGenerateModel

    model = TinyGenerateModel()
    model._ensure_built()
    dec = model._decoder
    rng = np.random.default_rng(3)
    toks = rng.integers(0, dec.VOCAB, size=prompt)

    caches, pos = dec._fresh_cache(), 0
    logits = None
    for t in toks:
        logits, caches = dec._step_fn(dec._params, caches, int(t), pos)
        pos += 1
    first = int(np.asarray(logits).argmax())

    k = min(k, dec.MAX_LEN - pos - 1)
    chunk_fn = model._chunk_fn(k)

    # both programs own the cache they are given (donated), so each call
    # hands the returned one to the next; the rows from ``pos`` on are
    # rewritten every time, which changes no timing
    def chunked(token, p):
        nonlocal caches
        out, caches = chunk_fn(dec._params, caches, token, p)
        return out

    dt_chunked = _timed_single_dispatch(chunked, first, pos, iters_inside=k)

    # per-token: block every step — the feed-back loop round-trips the
    # host for the argmax, so serving really does pay this per token
    def one_step(token, p):
        nonlocal caches
        logits, caches = dec._step_fn(dec._params, caches, token, p)
        return logits

    dt_token = _timed_single_dispatch(
        one_step, first, pos, iters_inside=1, repeats=7)

    return {
        "prompt_tokens": int(prompt), "chunk": int(k),
        "ms_per_token_dispatch": round(dt_token * 1000, 3),
        "tokens_per_sec_dispatch": round(1.0 / dt_token, 1),
        "ms_per_token_chunked": round(dt_chunked * 1000, 3),
        "tokens_per_sec_chunked": round(1.0 / dt_chunked, 1),
        "chunk_amortization": round(dt_token / dt_chunked, 1),
    }


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--json-out", default=None)
    parser.add_argument(
        "--small", action="store_true",
        help="tiny shapes: the whole pipeline in seconds")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    device = jax.devices()[0]
    peak = _peak_for(device.device_kind)
    result = {
        "platform": jax.default_backend(),
        "device_kind": device.device_kind,
        "peak_bf16_tflops": peak,
    }

    result["dispatch_overhead_ms"] = bench_dispatch_overhead(jax, jnp, np)
    if args.small:
        mm = bench_matmul(jax, jnp, np, n=256, chain=4, pipeline=2)
        fa = bench_flash_attention(
            jax, jnp, np, batch=1, seq=256, heads=2, dim=64, steps=2)
        gen = bench_generate(jax, jnp, np, prompt=8, k=8)
        dn_specs = ((8, "lite", 1),)
    else:
        mm = bench_matmul(jax, jnp, np)
        fa = bench_flash_attention(jax, jnp, np)
        gen = bench_generate(jax, jnp, np)
        dn_specs = ((96, "lite", 8), (256, "lite", 8), (64, "121", 8))
    result["matmul_bf16"] = mm
    result["flash_attention"] = fa
    result["llm_decode"] = gen
    dn = {}
    for width, arch, batch in dn_specs:
        key = f"w{width}_{arch}"
        dn[key] = bench_densenet(jax, jnp, np, width, arch, batch=batch)
    result["densenet"] = dn

    result["mfu"] = {
        "matmul": round(mm["tflops"] / peak, 3),
        "flash_attention": round(fa["tflops"] / peak, 3),
        **{f"densenet_{k}": round(v["tflops"] / peak, 3)
           for k, v in dn.items()},
    }
    impossible = [k for k, v in result["mfu"].items() if v > 1.0]
    if impossible:
        # physically impossible: the timing did not wait for the device
        raise RuntimeError(f"MFU rows {impossible} exceed 1.0: {result}")

    text = json.dumps(result, indent=1)
    print(text)
    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
