"""Spawn an inference server in its OWN process for the cross-process
tests (tests/test_tpu_shm_xproc.py).

The child runs on the CPU backend (``JAX_PLATFORMS=cpu``): this is the
tests' arrangement, and a chip belongs to one process at a time. On the chip
the roles are the other way round — the serving process holds the chip and
the client child is numpy-only (chip_smoke.py's cross-process phase,
bench.py's identity_xproc rows).
"""

from __future__ import annotations

import os
import select
import subprocess
import sys

IDENTITY_SERVER_SCRIPT = """
import sys
sys.path.insert(0, {repo!r})
from client_tpu.models.simple import IdentityModel
from client_tpu.server import HttpInferenceServer, ServerCore
import time
core = ServerCore([IdentityModel("identity_fp32", "FP32", delay_s=0.0)])
h = HttpInferenceServer(core).start()
print("PORT", h.port, flush=True)
time.sleep(86400)
"""

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class XprocServer:
    """A server subprocess announcing ``PORT <n>`` on stdout.

    The handshake validates the announcement line and tears the child down
    on ANY startup failure (crash before PORT, stray stdout line, timeout) —
    a half-started child sleeping 24h must never outlive its spawner.
    """

    def __init__(self, script: str = IDENTITY_SERVER_SCRIPT, timeout_s: float = 120.0):
        env = dict(os.environ)
        env["PYTHONPATH"] = ""
        env["JAX_PLATFORMS"] = "cpu"
        self._proc = subprocess.Popen(
            [sys.executable, "-c", script.format(repo=_REPO)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        )
        try:
            ready, _, _ = select.select([self._proc.stdout], [], [], timeout_s)
            if not ready:
                raise RuntimeError(f"server subprocess did not start in {timeout_s:.0f}s")
            line = self._proc.stdout.readline().strip()
            if not line.startswith("PORT "):
                err = ""
                if self._proc.poll() is not None:
                    err = (self._proc.stderr.read() or "")[-500:]
                raise RuntimeError(
                    f"server subprocess announced {line!r} instead of 'PORT <n>'"
                    + (f"; stderr tail: {err}" if err else "")
                )
            self.port = int(line.split()[1])
            self.url = f"127.0.0.1:{self.port}"
        except BaseException:
            self.stop()
            raise

    def stop(self) -> None:
        self._proc.terminate()
        try:
            self._proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            self._proc.kill()
            self._proc.wait(timeout=10)

    def __enter__(self) -> "XprocServer":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()
