"""Standalone server CLI: serve the model zoo over HTTP + GRPC.

The framework's tritonserver stand-in for examples, the perf harness, and
development::

    python -m client_tpu.serve --http-port 8000 --grpc-port 8001 [--vision]

This process owns the chip: JAX gives a TPU to one process at a time, so
clients of this server run in other processes and stay off jax (numpy in,
numpy out — ``client_tpu.http``/``.grpc``/``.utils.tpu_shared_memory`` never
import it). Compiled programs are kept in the persistent compile cache
(``client_tpu/compile_cache.py``).

Ctrl-C stops it immediately; SIGTERM drains gracefully — ``v2/health/ready``
/ ``ServerReady`` flip to not-ready first (so multi-endpoint pools route
away), in-flight requests finish, then the listeners close.
"""

from __future__ import annotations

import argparse
import signal
import sys
import time
from typing import List, Optional


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="client_tpu.serve")
    parser.add_argument("--http-port", type=int, default=8000)
    parser.add_argument("--grpc-port", type=int, default=8001)
    parser.add_argument("--no-http", action="store_true")
    parser.add_argument("--no-grpc", action="store_true")
    parser.add_argument(
        "--vision", action="store_true",
        help="also serve the densenet_onnx vision model (first request compiles)",
    )
    parser.add_argument(
        "--tensor-parallel", type=int, default=1,
        help="shard vision-model weights over N devices (serving-side tp)",
    )
    parser.add_argument("--identity-fp32", action="store_true",
                        help="also serve a dynamic-shape FP32 identity model")
    parser.add_argument(
        "--long-context", action="store_true",
        help="also serve the ring/ulysses long_context_encoder (sp)",
    )
    parser.add_argument(
        "--attention", choices=("ring", "ulysses", "auto", "flash"),
        default="ring",
        help="sequence-parallel scheme for --long-context (flash = the "
        "single-device Pallas kernel)",
    )
    parser.add_argument(
        "--moe", action="store_true",
        help="also serve the expert-parallel moe_ffn model (ep)",
    )
    parser.add_argument(
        "--http-frontend", choices=("threaded", "aio"), default="threaded",
        help="threaded: best single-client latency; aio: higher sustained "
        "rate and tighter p99 at many concurrent connections",
    )
    parser.add_argument("-v", "--verbose", action="store_true")
    args = parser.parse_args(argv)

    from .compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")

    from .models import default_model_zoo
    from .models.simple import IdentityModel
    from .server import (
        AioHttpInferenceServer,
        GrpcInferenceServer,
        HttpInferenceServer,
        ServerCore,
    )

    models = default_model_zoo()
    if args.identity_fp32:
        models.append(IdentityModel("identity_fp32", "FP32"))
    if args.vision:
        from .models.ensemble import build_image_ensemble

        models.extend(build_image_ensemble(tensor_parallel=args.tensor_parallel))
    if args.long_context:
        from .models.long_context import LongContextEncoderModel

        models.append(LongContextEncoderModel(attention=args.attention))
    if args.moe:
        from .models.moe import MoEFFNModel

        models.append(MoEFFNModel())
    core = ServerCore(models)

    servers = []
    if not args.no_http:
        if args.http_frontend == "aio":
            http = AioHttpInferenceServer(core, port=args.http_port)
        else:
            http = HttpInferenceServer(core, port=args.http_port, verbose=args.verbose)
        http.start()
        servers.append(http)
        print(f"HTTP  server ({args.http_frontend}) listening on {http.url}")
    if not args.no_grpc:
        grpc_srv = GrpcInferenceServer(core, port=args.grpc_port, verbose=args.verbose)
        grpc_srv.start()
        servers.append(grpc_srv)
        print(f"GRPC  server listening on {grpc_srv.url}")
    print(f"models: {', '.join(m.name for m in models)}")

    class _Drain(Exception):
        pass

    def on_sigterm(signum, frame):
        # disarm: systemd/k8s stop sequences often deliver repeat SIGTERMs;
        # a second one must not abort the graceful close already underway
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        raise _Drain()

    signal.signal(signal.SIGTERM, on_sigterm)
    draining = False
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    except _Drain:
        draining = True
    finally:
        # shutdown is underway: further signals must not abort it mid-stop
        # (the finally also guarantees every server stops on ANY exit path)
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        signal.signal(signal.SIGINT, signal.SIG_IGN)
        if draining:
            # graceful: flip ready everywhere FIRST so pool probes route
            # away, then let each frontend finish in-flight work and close
            print("SIGTERM: draining (ready -> not-ready, finishing in-flight)")
            core.ready = False
            time.sleep(1.0)
        for s in servers:
            try:
                if draining:
                    s.close(grace_s=0.0)
                else:
                    s.stop()
            except Exception as e:
                print(f"error stopping {type(s).__name__}: {e}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
