"""Sweep the perf harness across the data-plane modes in one run.

Spins the in-process server on the backend JAX finds (one process: the
server and the perf workers share it, so the workers' device arrays and the
server's are the same chip's), then sweeps protocol x shared-memory-mode x
concurrency and prints a markdown table plus a JSON blob (written to
BASELINE_SWEEP.json) that names the platform. Rows from a CPU backend prove
control flow and counts; they are not device numbers.

    python tools/baseline_sweep.py                  # quick matrix
    python tools/baseline_sweep.py --full           # c=1..32, more requests

This is the driver for SURVEY.md §6 (concurrency sweeps with p50/p99 per
data-plane mode).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--full", action="store_true", help="c=1..32 sweep")
    parser.add_argument("--model", default="custom_identity_int32")
    parser.add_argument("--elems", type=int, default=1 << 18, help="tensor elems (default 1 MiB int32)")
    parser.add_argument("--requests", type=int, default=0, help="override measurement requests")
    parser.add_argument("--out", default="BASELINE_SWEEP.json")
    args = parser.parse_args()

    import jax

    from client_tpu.models import default_model_zoo
    from client_tpu.perf import PerfRunner
    from client_tpu.server import GrpcInferenceServer, HttpInferenceServer, ServerCore

    platform = jax.default_backend()
    concurrencies = [1, 2, 4, 8, 16, 32] if args.full else [1, 4, 16]
    requests = args.requests or (400 if args.full else 150)

    core = ServerCore(default_model_zoo())
    rows = []
    with HttpInferenceServer(core) as hs, GrpcInferenceServer(core) as gs:
        urls = {"http": hs.url, "grpc": gs.url, "native": hs.url, "native-grpc": gs.url}
        protocols = ["http", "grpc"]
        try:
            from client_tpu.native import available

            if available():
                protocols += ["native", "native-grpc"]
        except Exception:
            pass
        for protocol in protocols:
            for shm in ("none", "system", "tpu"):
                if protocol in ("native", "native-grpc") and shm == "system":
                    continue
                for c in concurrencies:
                    try:
                        runner = PerfRunner(
                            urls[protocol], protocol, args.model,
                            shared_memory=shm,
                            shape_overrides={"INPUT0": [1, args.elems]},
                        )
                        r = runner.run(concurrency=c, measurement_requests=requests)
                    except Exception as e:
                        rows.append({
                            "protocol": protocol, "shm": shm, "concurrency": c,
                            "error": str(e)[:200],
                        })
                        continue
                    rows.append({
                        "protocol": protocol, "shm": shm, "concurrency": c,
                        "infer_per_sec": r["infer_per_sec"],
                        "p50_ms": r["latency_ms"]["p50"],
                        "p99_ms": r["latency_ms"]["p99"],
                        "errors": r["errors"],
                    })
                    print(json.dumps(rows[-1]), flush=True)

    payload = {
        "platform": platform,
        "model": args.model,
        "tensor_bytes": args.elems * 4,
        "requests_per_point": requests,
        "timestamp": time.strftime("%Y-%m-%d %H:%M:%S"),
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=1)

    # markdown table
    print(f"\n### Sweep ({platform}, {args.elems * 4 // (1 << 20)} MiB {args.model}, {requests} req/pt)\n")
    print("| protocol | shm | c | infer/s | p50 ms | p99 ms |")
    print("|---|---|---|---|---|---|")
    for row in rows:
        if "error" in row:
            print(f"| {row['protocol']} | {row['shm']} | {row['concurrency']} | error: {row['error'][:40]} | | |")
        else:
            print(
                f"| {row['protocol']} | {row['shm']} | {row['concurrency']} | "
                f"{row['infer_per_sec']} | {row['p50_ms']} | {row['p99_ms']} |"
            )
    return 0


if __name__ == "__main__":
    sys.exit(main())
