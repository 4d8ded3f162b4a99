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
