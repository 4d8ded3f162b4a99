"""Instrumented grpc_stream soak: answer the growth question for good.

The 1800 s SOAK_r04 capture left "is grpc_stream RSS growth
bounded?" open (raw tail slope 125.3 KB/min, steeper than the whole-run
48.9). This tool instruments the loop itself instead of re-measuring the
symptom:

  - every 30 s (a quarter of the phase where that is shorter): raw RSS,
    post-``malloc_trim`` RSS, ``mallinfo2`` (in-use heap /
    free-but-unreturned / mmapped), and the ``tracemalloc`` traced total — so Python-level reachable growth, glibc retention, and OS-view
    RSS are separated in ONE trace;
  - an A/B at the process level: the same loop re-run with
    ``MALLOC_ARENA_MAX=1`` in the same artifact, pinning (or refuting) the
    arena theory.

Usage (writes SOAK_STREAM_r05.json at the repo root):

    python tools/soak_stream_probe.py [--seconds 3600] [--ab-seconds 1800]

The client loop runs in a child process per variant (the parent holds the
server), exactly like tests/test_soak_slope.py's topology so numbers are
comparable with SOAK_r0*.json.

Reference role: memory_leak_test.cc's long-loop leak hunting
(/root/reference/src/c++/tests/memory_leak_test.cc), with the attribution
instrumentation the reference leaves to external tooling (valgrind massif).
"""

from __future__ import annotations

import argparse
import ctypes
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SAMPLE_EVERY_S = 30.0  # at most: a phase under two minutes takes four samples


def _rss_kb() -> int:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1])
    return 0


class _Mallinfo2(ctypes.Structure):
    _fields_ = [(n, ctypes.c_size_t) for n in (
        "arena", "ordblks", "smblks", "hblks", "hblkhd", "usmblks",
        "fsmblks", "uordblks", "fordblks", "keepcost")]


def _mallinfo() -> dict:
    try:
        libc = ctypes.CDLL("libc.so.6")
        libc.mallinfo2.restype = _Mallinfo2
        mi = libc.mallinfo2()
        return {
            "in_use_kb": mi.uordblks // 1024,
            "free_unreturned_kb": mi.fordblks // 1024,
            "arena_kb": mi.arena // 1024,
            "mmapped_kb": mi.hblkhd // 1024,
        }
    except Exception:
        return {}


def _malloc_trim() -> None:
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except Exception:
        pass


def _fit_kb_per_min(samples, key):
    import numpy as np

    pts = [(s["t"], s[key]) for s in samples if key in s]
    if len(pts) < 3:
        return 0.0
    t = np.array([p[0] for p in pts], dtype=np.float64)
    v = np.array([p[1] for p in pts], dtype=np.float64)
    return float(np.polyfit(t - t[0], v, 1)[0] * 60.0)


def _slopes(samples, key):
    tail = [s for s in samples if s["t"] >= samples[-1]["t"] - 300.0]
    return {
        "overall_kb_per_min": round(_fit_kb_per_min(samples, key), 1),
        "tail300_kb_per_min": round(_fit_kb_per_min(tail, key), 1),
    }


def child_loop(url: str, seconds: float) -> dict:
    """The grpc_stream loop with in-loop instrumentation (child process)."""
    import threading
    import tracemalloc

    import numpy as np

    import client_tpu.grpc as grpcclient

    tracemalloc.start(10)
    payload = np.random.default_rng(7).integers(
        0, 1000, (1, 65536)).astype(np.int32)
    samples: list = []
    sample_every = min(SAMPLE_EVERY_S, seconds / 4.0)
    t_start = time.monotonic()

    with grpcclient.InferenceServerClient(url) as client:
        got = threading.Semaphore(0)
        errors: list = []

        def callback(result, error):
            if error is not None:
                errors.append(str(error))
            got.release()

        client.start_stream(callback)
        deadline = t_start + seconds
        next_sample = t_start  # sample immediately for a t=0 baseline
        iters = 0
        try:
            while time.monotonic() < deadline and not errors:
                inp = grpcclient.InferInput("INPUT0", [1, 65536], "INT32")
                inp.set_data_from_numpy(payload)
                client.async_stream_infer("custom_identity_int32", [inp])
                assert got.acquire(timeout=30)
                iters += 1
                now = time.monotonic()
                if now >= next_sample:
                    import gc

                    gc.collect()
                    entry = {"t": round(now - t_start, 1),
                             "rss_raw_kb": _rss_kb()}
                    entry.update({f"malloc_{k}": v
                                  for k, v in _mallinfo().items()})
                    traced, _peak = tracemalloc.get_traced_memory()
                    entry["tracemalloc_kb"] = traced // 1024
                    _malloc_trim()
                    entry["rss_trimmed_kb"] = _rss_kb()
                    samples.append(entry)
                    next_sample = now + sample_every
        finally:
            client.stop_stream()

    # where do the surviving Python allocations live? (flat totals with a
    # growing site would still be a churn hotspot worth naming)
    top = tracemalloc.take_snapshot().statistics("lineno")[:5]
    return {
        "iters": iters,
        "seconds": seconds,
        "sample_every_s": sample_every,
        "errors": errors[:3],
        "arena_max": os.environ.get("MALLOC_ARENA_MAX", "default"),
        "samples": samples,
        "tracemalloc_top": [
            {"site": str(stat.traceback), "kb": stat.size // 1024,
             "count": stat.count}
            for stat in top
        ],
        "slopes": {
            key: _slopes(samples, key)
            for key in ("rss_raw_kb", "rss_trimmed_kb", "malloc_in_use_kb",
                        "malloc_free_unreturned_kb", "tracemalloc_kb")
            if samples and key in samples[0]
        },
    }


_SERVER_SCRIPT = """
import sys
sys.path.insert(0, {repo!r})
from client_tpu.models import default_model_zoo
from client_tpu.server import GrpcInferenceServer, ServerCore
import time
g = GrpcInferenceServer(ServerCore(default_model_zoo())).start()
print("PORT", g.port, flush=True)
time.sleep(86400)
"""


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seconds", type=float, default=3600.0,
                        help="default-arena instrumented run length")
    parser.add_argument("--ab-seconds", type=float, default=1800.0,
                        help="MALLOC_ARENA_MAX=1 comparison run length "
                             "(0 skips the A/B)")
    parser.add_argument("--out", default=os.path.join(
        ROOT, "SOAK_STREAM_r05.json"))
    parser.add_argument("--child", action="store_true",
                        help="internal: run the client loop")
    parser.add_argument("--url")
    parser.add_argument("--json-out")
    args = parser.parse_args()

    if args.child:
        result = child_loop(args.url, args.seconds)
        with open(args.json_out, "w") as f:
            json.dump(result, f)
        return 0

    env = dict(os.environ)
    # a host-memory leak hunt: server child and client child both run on
    # the cpu backend, so neither takes a chip
    env["PYTHONPATH"] = ""
    env["JAX_PLATFORMS"] = "cpu"
    server = subprocess.Popen(
        [sys.executable, "-c", _SERVER_SCRIPT.format(repo=ROOT)],
        stdout=subprocess.PIPE, text=True, env=env,
    )
    try:
        line = server.stdout.readline().strip()
        assert line.startswith("PORT"), line
        url = f"127.0.0.1:{line.split()[1]}"

        out = {"url": url}
        plan = [("default_arenas", args.seconds, None)]
        if args.ab_seconds > 0:
            plan.append(("arena_max_1", args.ab_seconds, "1"))
        for name, seconds, arena_max in plan:
            child_env = dict(env)
            if arena_max is not None:
                child_env["MALLOC_ARENA_MAX"] = arena_max
            # beside the artifact, pid-suffixed: a pytest smoke run and a
            # real long capture must never read each other's child output
            tmp = os.path.join(
                os.path.dirname(os.path.abspath(args.out)) or ROOT,
                f".soak_child_{name}_{os.getpid()}.json")
            print(json.dumps({"phase": name, "seconds": seconds}),
                  file=sys.stderr, flush=True)
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--child",
                 "--url", url, "--seconds", str(seconds), "--json-out", tmp],
                env=child_env, timeout=seconds + 300,
            )
            if proc.returncode == 0 and os.path.exists(tmp):
                with open(tmp) as f:
                    out[name] = json.load(f)
                os.unlink(tmp)
            else:
                out[name] = {"error": f"child rc={proc.returncode}"}
            print(json.dumps({"phase": name,
                              "slopes": out[name].get("slopes")}),
                  file=sys.stderr, flush=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
        print(json.dumps({"ok": True, "out": args.out}))
        return 0
    finally:
        server.terminate()
        server.wait(timeout=10)


if __name__ == "__main__":
    sys.exit(main())
