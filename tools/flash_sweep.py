"""Flash-attention block-size sweep on the real chip.

An early session read 9.6 TF/s (4.9% MFU) at the benched shape
4×2048×8×128 — a starting hypothesis, on today's code not measured.
Roofline first: per head the kernel does 4·S²·D FLOPs over
8·S·D bytes of HBM traffic → arithmetic intensity S/2 ≈ 1024 FLOP/byte at
S=2048 — two orders of magnitude past the v5e ridge point (~240), so the
shape is COMPUTE-bound and low MFU is kernel inefficiency, not bandwidth.
The two levers this tool measures:

- operand dtype: the round-4 kernel issues bf16×bf16→f32 dots (full-rate
  MXU) instead of pre-cast f32×f32 (~4x slower) — the expected dominant
  term;
- block_q × block_k: bigger blocks amortize grid/scratch overhead and the
  per-block VPU work (exp + running-max bookkeeping) against more MXU
  FLOPs per invocation.

Sweeps the block grid at the benched shape, reports TF/s + MFU per config,
and runs the bf16 exactness tier (vs dense fp32 reference) for the best
config. One JSON. The kernel runs compiled on the chip; ``--interpret``
is the one, explicit, way to run it anywhere else.

    python tools/flash_sweep.py [--json-out PATH] [--small]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.chip_bench import _peak_for, _timed_single_dispatch  # noqa: E402


def sweep(jax, jnp, np, interpret, small):
    from client_tpu.ops.flash_attention import flash_attention

    if small:
        batch, seq, heads, dim, steps = 1, 256, 2, 64, 2
        blocks = [(128, 128)]
    else:
        batch, seq, heads, dim, steps = 4, 2048, 8, 128, 10
        blocks = [(bq, bk)
                  for bq in (128, 256, 512, 1024)
                  for bk in (128, 256, 512, 1024)]

    rng = np.random.default_rng(1)
    shape = (batch, seq, heads, dim)

    def mk():
        return jnp.asarray(rng.standard_normal(shape, dtype=np.float32),
                           dtype=jnp.bfloat16)

    q, k, v = mk(), mk(), mk()
    flops = 4 * batch * heads * seq * seq * dim  # QK^T + PV

    rows = []
    for bq, bk in blocks:
        row = {"block_q": bq, "block_k": bk}
        try:
            def chained(q, k, v, _bq=bq, _bk=bk):
                def body(_, acc):
                    # carry-dependent cast-preserving perturbation: stops
                    # XLA hoisting the loop-invariant call (cheap vs S²D)
                    qq = (q * (1.0 + 0.0 * acc)).astype(q.dtype)
                    o = flash_attention(qq, k, v, block_q=_bq, block_k=_bk,
                                        interpret=interpret)
                    return acc + jnp.sum(o.astype(jnp.float32))

                return jax.lax.fori_loop(0, steps, body, jnp.float32(0))

            dt = _timed_single_dispatch(jax.jit(chained), q, k, v, iters_inside=steps)
            row["ms_per_call"] = round(dt * 1000, 3)
            row["tflops"] = round(flops / dt / 1e12, 2)
        except Exception as e:
            row["error"] = f"{type(e).__name__}: {e}"[:300]
        rows.append(row)

    ok_rows = [r for r in rows if "tflops" in r]
    best = max(ok_rows, key=lambda r: r["tflops"]) if ok_rows else None

    result = {"shape": list(shape), "rows": rows, "best": best}

    if best:
        # bf16 exactness tier at the winning config (vs dense fp32)
        qs, ks, vs = q[:1, :512], k[:1, :512], v[:1, :512]
        out = flash_attention(
            qs, ks, vs, block_q=min(best["block_q"], 512),
            block_k=min(best["block_k"], 512), interpret=interpret
        ).astype(jnp.float32)
        qf, kf, vf = (t.astype(jnp.float32) for t in (qs, ks, vs))
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * (dim ** -0.5)
        p = jax.nn.softmax(s, axis=-1)
        ref = jnp.einsum("bhqk,bkhd->bqhd", p, vf)
        diff = float(jnp.max(jnp.abs(out - ref)))
        result["exactness"] = {"max_abs_diff": diff, "tol": 5e-2,
                               "ok": diff < 5e-2}
    return result


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--json-out", default=None)
    parser.add_argument("--interpret", action="store_true")
    parser.add_argument("--small", action="store_true")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    interpret = args.interpret
    device = jax.devices()[0]
    # interpret mode runs off the chip, where no peak applies
    peak = None if interpret else _peak_for(device.device_kind)
    result = {
        "platform": jax.default_backend(),
        "device_kind": device.device_kind,
        "peak_bf16_tflops": peak,
        "mosaic_compiled": not interpret,
    }
    result.update(sweep(jax, jnp, np, interpret, args.small))
    if peak and result.get("best"):
        result["best_mfu"] = round(result["best"]["tflops"] / peak, 3)

    text = json.dumps(result, indent=1)
    print(text)
    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
