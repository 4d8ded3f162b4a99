"""In-process embedding tests: the Python half directly, and the C host
binary end-to-end (java-api-bindings parity — reference builds JavaCPP over
the tritonserver C API; here `native/src/server_embed.cc` embeds CPython
and `native/tests/embed_smoke.c` is the plain-C host)."""

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent


def test_embed_python_half_roundtrip():
    """create -> infer (two-part body) -> metadata -> destroy, no HTTP."""
    from client_tpu.server import embed

    handle = embed.create('{"models": ["simple"]}')
    try:
        a = np.arange(16, dtype=np.int32).reshape(1, 16)
        b = np.ones((1, 16), dtype=np.int32)
        header = json.dumps({
            "inputs": [
                {"name": "INPUT0", "datatype": "INT32", "shape": [1, 16],
                 "parameters": {"binary_data_size": 64}},
                {"name": "INPUT1", "datatype": "INT32", "shape": [1, 16],
                 "parameters": {"binary_data_size": 64}},
            ],
            "outputs": [
                {"name": "OUTPUT0", "parameters": {"binary_data": True}},
                {"name": "OUTPUT1", "parameters": {"binary_data": True}},
            ],
        }).encode()
        body = header + a.tobytes() + b.tobytes()
        out, header_len = embed.infer(handle, "simple", "", body, len(header))
        assert header_len > 0
        tail = out[header_len:]
        assert len(tail) == 128
        got_sum = np.frombuffer(tail[:64], dtype=np.int32).reshape(1, 16)
        got_diff = np.frombuffer(tail[64:], dtype=np.int32).reshape(1, 16)
        np.testing.assert_array_equal(got_sum, a + b)
        np.testing.assert_array_equal(got_diff, a - b)

        meta = json.loads(embed.metadata_json(handle, "simple"))
        assert {i["name"] for i in meta["inputs"]} == {"INPUT0", "INPUT1"}
        stats = json.loads(embed.statistics_json(handle))
        assert stats["model_stats"][0]["name"] == "simple"
    finally:
        embed.destroy(handle)


def test_embed_unknown_model_raises():
    from client_tpu.server import embed

    with pytest.raises(ValueError):
        embed.create('{"models": ["no_such_model"]}')
    handle = embed.create('{"models": ["simple"]}')
    try:
        with pytest.raises(Exception):
            embed.infer(handle, "missing", "", b"{}", -1)
    finally:
        embed.destroy(handle)
    with pytest.raises(ValueError):
        embed.infer(handle, "simple", "", b"{}", -1)  # destroyed handle


def test_embed_c_host_end_to_end(native_build):
    """The compiled C binary hosts the interpreter + server and verifies
    infer arithmetic, admin JSON, HTTP frontend, and the error path."""
    embed_smoke = native_build / "embed_smoke"
    if not embed_smoke.is_file():
        # native/CMakeLists.txt leaves the target out where cmake finds no
        # Python3 Development.Embed: a tool absent, not a build missing
        pytest.skip("no Python3 embed development files for cmake")
    # Minimal env on purpose: no PYTHONHOME (a venv prefix is not a full
    # installation home and wedges Py_InitializeFromConfig), no PYTHONPATH
    # (the binary injects the repo path itself via ctpu_embed_init) — but
    # the venv's site-packages must be reachable for numpy/jax, so pass it
    # through PYTHONPATH like a plain C host deployment would.
    site = str(Path(sys.prefix) / "lib" /
               f"python{sys.version_info.major}.{sys.version_info.minor}" /
               "site-packages")
    proc = subprocess.run(
        [str(embed_smoke), str(REPO)],
        capture_output=True, text=True, timeout=240,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": site},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PASS embed_smoke" in proc.stdout
