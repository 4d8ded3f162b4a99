"""Test configuration: an 8-device virtual CPU mesh, pinned before jax starts.

Tests run on the CPU backend (``JAX_PLATFORMS=cpu``): Pallas kernels in
interpret mode, sharding/parallel tests on a virtual multi-device topology
(``--xla_force_host_platform_device_count=8``). The chip is reached through
the chip tool only (``python chip_smoke.py``); what its compiler accepts is
held here by ``tests/test_chip_compile.py``, which compiles for a described
topology without one.
"""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def native_built() -> bool:
    """Build (or locate) the native library; shared by the native test tiers
    so no test module needs to import another test module."""
    import subprocess
    from pathlib import Path

    build = Path(__file__).resolve().parent.parent / "native" / "build"
    targets = [build / "native_smoke", build / "libclient_tpu_http.so",
               build / "hpack_tool"]
    if all(t.exists() for t in targets):
        return True
    native = build.parent
    try:
        subprocess.run(
            ["cmake", "-S", str(native), "-B", str(build), "-G", "Ninja"],
            check=True, capture_output=True, timeout=120,
        )
        subprocess.run(
            ["ninja", "-C", str(build)], check=True, capture_output=True,
            timeout=300,
        )
        return True
    except Exception:
        return False


class GatedStep:
    """The slot batcher's jitted step behind a gate, for tests that decide
    what is on the queue when a round is dispatched: every call waits to be
    let through (``let``), and the call numbered ``fail_at`` raises instead
    of running. ``at(n)`` waits until call ``n`` stands at the gate. Counts
    and orderings, no clock."""

    def __init__(self, model, fail_at=None):
        import threading

        model._ensure_built()
        self._model, self._step, self._fail_at = model, model._batched_step, fail_at
        self.calls = 0
        self._arrived = threading.Condition()
        self._gate = threading.Semaphore(0)
        model._batched_step = self

    def __call__(self, *args):
        with self._arrived:
            call, self.calls = self.calls, self.calls + 1
            self._arrived.notify_all()
        assert self._gate.acquire(timeout=60), "the test never let the round go"
        if call == self._fail_at:
            raise RuntimeError(f"step {call} failed")
        return self._step(*args)

    def at(self, call: int) -> None:
        with self._arrived:
            assert self._arrived.wait_for(lambda: self.calls > call, timeout=60), (
                f"round {call} was never dispatched")

    def queued(self, n: int) -> None:
        """Wait until ``n`` requests are on the batcher's queue."""
        import time

        deadline = time.monotonic() + 60
        while self._model._queue.qsize() < n:
            assert time.monotonic() < deadline, "the requests never arrived"
            time.sleep(0.001)

    def let(self, rounds: int = 1) -> None:
        for _ in range(rounds):
            self._gate.release()
