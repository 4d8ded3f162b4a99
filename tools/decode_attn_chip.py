"""Flash-decoding kernel on the real chip: Mosaic exactness + latency curve.

``ops/decode_attention.py`` compiled under Mosaic and timed against the
dense path (chip_smoke.py proves the kernel serves; this tool measures it).
Two sections, one JSON:

- ``exactness``: compiled kernel vs the dense fp32 reference at several
  (shape, cache position) points, including the ragged-tail and pos=0
  extremes the CI tier pins off-chip (tests/test_decode_attention.py) and
  the decoder_lm serving shape.
- ``latency``: ms/step pallas vs einsum over cache length and fill level —
  the decode hot op is HBM-bandwidth-bound, so the interesting curve is
  traffic (the kernel's block skip reads only ``pos`` worth of cache; the
  dense path always reads MAX_LEN), plus the honest small-shape crossover:
  at the decoder_lm fixture size the whole cache fits one tile and dense
  einsum may win.

Timing methodology matches tools/chip_bench.py: ``steps`` iterations
chained inside ONE dispatch via ``lax.fori_loop`` with a carry-dependent
input perturbation (q * (1 + 0*acc)) so XLA cannot hoist the loop-invariant
attention out of the loop, divided by steps — dispatch overhead amortized
away.

Run on the chip (or with --interpret off-chip for a pipeline check):
    python tools/decode_attn_chip.py [--json-out PATH] [--small]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.chip_bench import _timed_single_dispatch  # noqa: E402


def check_exactness(jnp, np, interpret):
    from client_tpu.ops.decode_attention import (
        decode_attention,
        decode_attention_reference,
    )

    cases = [
        # (batch, heads, max_len, dim, positions, dtype)
        (1, 4, 128, 32, [0, 5, 127], "float32"),   # decoder_lm shape
        (3, 2, 200, 64, [0, 99, 199], "float32"),  # ragged block tail
        (2, 8, 384, 128, [100, 383], "float32"),   # multi-block, MXU dim
        (4, 8, 1024, 128, [0, 511, 1023], "bfloat16"),  # serving-scale bf16
    ]
    if interpret:
        # off-chip pipeline check only — the interpreter walks the grid in
        # Python, so keep to the CI-tier shapes (tests cover the rest)
        cases = cases[:2]
    rows = []
    ok = True
    for batch, heads, max_len, dim, positions, dtype in cases:
        rng = np.random.default_rng(0)
        dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
        q = jnp.asarray(rng.standard_normal((batch, heads, dim)), dt)
        k = jnp.asarray(
            rng.standard_normal((batch, heads, max_len, dim)), dt)
        v = jnp.asarray(
            rng.standard_normal((batch, heads, max_len, dim)), dt)
        # every listed position is exercised (batch-broadcast), so small
        # batches don't silently drop the pos extremes
        diff = 0.0
        for p in positions:
            pos = jnp.full((batch,), p, jnp.int32)
            out = decode_attention(q, k, v, pos, interpret=interpret)
            ref = decode_attention_reference(q, k, v, pos)
            diff = max(diff, float(jnp.max(jnp.abs(
                out.astype(jnp.float32) - ref.astype(jnp.float32)))))
        tol = 2e-2 if dtype == "bfloat16" else 1e-5
        rows.append({
            "shape": [batch, heads, max_len, dim], "dtype": dtype,
            "positions": positions, "max_abs_diff": diff,
            "tol": tol, "ok": diff < tol,
        })
        ok = ok and diff < tol
    return {"ok": ok, "cases": rows}


def bench_latency(jax, jnp, np, interpret, small):
    """ms/step pallas vs einsum over (max_len, fill) — plus the serving
    shape row feeding the BatchedDecoderModel default choice."""
    from client_tpu.ops.decode_attention import (
        decode_attention,
        decode_attention_reference,
    )

    if small:
        grid = [(2, 2, 128, 32, [127], 2)]
    else:
        grid = [
            # (batch, heads, max_len, dim, fills, steps)
            (8, 8, 2048, 128, [64, 512, 2047], 20),
            (8, 8, 8192, 128, [8191], 10),
            (16, 8, 4096, 128, [4095], 10),
            # decoder_lm_batched serving shape (slots=8): the honest
            # small-shape row — whichever impl wins here is the default
            (8, 4, 128, 32, [127], 40),
        ]

    def timed(impl_fn, q, k, v, pos, steps):
        @jax.jit
        def chained(q, k, v, pos):
            def body(_, acc):
                # carry-dependent perturbation: blocks XLA from hoisting
                # the loop-invariant attention out of the fori_loop (q is
                # tiny, so the extra elementwise is noise vs cache traffic);
                # cast back so the f32 carry doesn't promote the bf16 query
                # and silently bench a mixed-dtype dot
                qq = (q * (1.0 + 0.0 * acc)).astype(q.dtype)
                o = impl_fn(qq, k, v, pos)
                return acc + jnp.sum(o.astype(jnp.float32))

            return jax.lax.fori_loop(0, steps, body, jnp.float32(0))

        return _timed_single_dispatch(chained, q, k, v, pos, iters_inside=steps)

    rows = []
    for batch, heads, max_len, dim, fills, steps in grid:
        rng = np.random.default_rng(1)
        q = jnp.asarray(
            rng.standard_normal((batch, heads, dim)), jnp.bfloat16)
        k = jnp.asarray(
            rng.standard_normal((batch, heads, max_len, dim)), jnp.bfloat16)
        v = jnp.asarray(
            rng.standard_normal((batch, heads, max_len, dim)), jnp.bfloat16)
        for fill in fills:
            pos = jnp.full((batch,), fill, jnp.int32)
            row = {"batch": batch, "heads": heads, "max_len": max_len,
                   "dim": dim, "fill": fill}
            try:
                dt_p = timed(
                    lambda q, k, v, pos: decode_attention(
                        q, k, v, pos, interpret=interpret),
                    q, k, v, pos, steps)
                row["pallas_ms"] = round(dt_p * 1000, 4)
                # cache traffic actually needed: (fill+1) K+V rows, bf16
                need = batch * heads * (fill + 1) * dim * 2 * 2
                row["pallas_gbps_effective"] = round(need / dt_p / 1e9, 1)
            except Exception as e:
                row["pallas_error"] = f"{type(e).__name__}: {e}"[:300]
            try:
                dt_e = timed(decode_attention_reference, q, k, v, pos, steps)
                row["einsum_ms"] = round(dt_e * 1000, 4)
            except Exception as e:
                row["einsum_error"] = f"{type(e).__name__}: {e}"[:300]
            if "pallas_ms" in row and "einsum_ms" in row:
                row["pallas_speedup"] = round(
                    row["einsum_ms"] / row["pallas_ms"], 3)
            rows.append(row)
    return rows


def run(interpret: bool, small: bool):
    import jax
    import jax.numpy as jnp
    import numpy as np

    device = jax.devices()[0]
    result = {
        "platform": jax.default_backend(),
        "device_kind": device.device_kind,
        "mosaic_compiled": not interpret,
    }
    try:
        result["exactness"] = check_exactness(jnp, np, interpret)
    except Exception as e:
        result["exactness"] = {
            "ok": False, "error": f"{type(e).__name__}: {e}"[:500]}
    try:
        result["latency"] = bench_latency(jax, jnp, np, interpret, small)
    except Exception as e:
        result["latency_error"] = f"{type(e).__name__}: {e}"[:500]
    return result


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--json-out", default=None)
    parser.add_argument("--interpret", action="store_true",
                        help="interpret mode (a pipeline check off the chip); "
                        "without it the kernel must compile")
    parser.add_argument("--small", action="store_true")
    args = parser.parse_args()

    result = run(args.interpret, args.small)
    text = json.dumps(result, indent=1)
    print(text)
    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(text + "\n")
    return 0 if result.get("exactness", {}).get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
