"""Generate-extension protocol mapping (docs/generate_extension.md).

Unit coverage for the flat-JSON → core-request mapping shared by both HTTP
frontends, plus e2e cases the cancel-stats suite doesn't touch: BYTES
tensors both directions, the versions/ route, and scalar collapsing.
"""

import numpy as np
import pytest

from client_tpu.models import default_model_zoo
from client_tpu.server import ServerCore
from client_tpu.server.core import InferError
from client_tpu.server.http_server import (
    _generate_core_request,
    _generate_event,
)


@pytest.fixture(scope="module")
def core():
    return ServerCore(default_model_zoo())


def _model(core, name):
    return core.model(name, "")


def test_mapping_conforms_shapes(core):
    model = _model(core, "tiny_lm_generate")
    req = _generate_core_request(
        model, {"TOKENS": [1, 2, 3], "MAX_TOKENS": 8, "id": "x"})
    by_name = {i["name"]: i for i in req["inputs"]}
    # [1,2,3] conformed to the declared [1,-1] rank by a leading 1
    assert by_name["TOKENS"]["shape"] == [1, 3]
    assert by_name["TOKENS"]["datatype"] == "INT32"
    np.testing.assert_array_equal(
        by_name["TOKENS"]["array"], [[1, 2, 3]])
    # scalar 8 conformed to [1]
    assert by_name["MAX_TOKENS"]["shape"] == [1]
    assert req["id"] == "x"


def test_mapping_rejects_unknowns_and_bad_dtypes(core):
    model = _model(core, "tiny_lm_generate")
    with pytest.raises(InferError, match="unexpected generate input"):
        _generate_core_request(model, {"BOGUS": 1})
    with pytest.raises(InferError, match="does not parse as INT32"):
        _generate_core_request(model, {"TOKENS": ["not-a-number"]})
    with pytest.raises(InferError, match="JSON object"):
        _generate_core_request(model, [1, 2])
    with pytest.raises(InferError, match="must be an object"):
        _generate_core_request(model, {"parameters": 7})


def test_bytes_inputs_accept_json_numbers(core):
    """JSON numbers for a BYTES input map to their string form, not
    bytes(int) (which would be that many NUL bytes)."""
    model = _model(core, "simple_string")
    req = _generate_core_request(
        model, {"INPUT0": [[i for i in range(16)]],
                "INPUT1": [[str(i) for i in range(16)]]})
    by_name = {i["name"]: i for i in req["inputs"]}
    assert by_name["INPUT0"]["array"][0][3] == b"3"
    assert by_name["INPUT1"]["array"][0][3] == b"3"


def test_event_flattening_scalar_collapse():
    resp = {
        "model_name": "m", "model_version": "1", "id": "r",
        "outputs": [
            {"name": "ONE", "datatype": "INT32",
             "array": np.array([[5]], np.int32)},
            {"name": "MANY", "datatype": "FP32",
             "array": np.array([1.5, 2.5], np.float32)},
            {"name": "TEXT", "datatype": "BYTES",
             "array": np.array([b"hi"], dtype=object)},
        ],
    }
    event = _generate_event(resp)
    assert event["ONE"] == 5          # single element -> scalar
    assert event["MANY"] == [1.5, 2.5]
    assert event["TEXT"] == "hi"      # bytes -> str
    assert event["id"] == "r"


def test_bytes_model_roundtrip_and_version_route(core):
    """BYTES in/out over /generate, on both frontends, via the versioned
    route: string-encoded integers go in, sum/diff strings come out."""
    import client_tpu.http as httpclient
    from client_tpu.server import HttpInferenceServer

    with HttpInferenceServer(core) as server:
        with httpclient.InferenceServerClient(server.url) as client:
            a = [str(10 + i) for i in range(16)]
            b = [str(i) for i in range(16)]
            out = client.generate(
                "simple_string", {"INPUT0": [a], "INPUT1": [b]},
                model_version="1",
            )
            assert out["model_name"] == "simple_string"
            assert out["OUTPUT0"] == [str(10 + 2 * i) for i in range(16)]
            assert out["OUTPUT1"] == ["10"] * 16


def test_generate_composes_with_sequence_api(core):
    """The 'parameters' passthrough lets /generate drive STATEFUL models:
    a client can step decoder_lm token by token with sequence_id/start/end
    in the payload — the generate extension composes with the sequence
    API rather than being stateless-only."""
    import client_tpu.http as httpclient
    from client_tpu.models.decoder import TinyDecoderModel
    from client_tpu.server import HttpInferenceServer

    ref = TinyDecoderModel(seed=0)
    ref._ensure_built()

    with HttpInferenceServer(core) as server:
        with httpclient.InferenceServerClient(
            server.url, network_timeout=300.0
        ) as client:
            def step(tokens, start, end):
                out = client.generate(
                    "decoder_lm", {"TOKENS": [tokens]},
                    parameters={"sequence_id": 4242,
                                "sequence_start": start,
                                "sequence_end": end},
                )
                return out["NEXT_TOKEN"]

            toks = [step([1, 2, 3], True, False)]
            for i in range(3):
                toks.append(step([toks[-1]], False, i == 2))

    # greedy tokens must match the in-process decoder exactly
    expected = []
    import numpy as np

    caches, pos = ref._fresh_cache(), 0
    logits = None
    for t in [1, 2, 3]:
        logits, caches = ref._step_fn(ref._params, caches, int(t), pos)
        pos += 1
    nxt = int(np.asarray(logits).argmax())
    expected.append(nxt)
    for _ in range(3):
        logits, caches = ref._step_fn(ref._params, caches, nxt, pos)
        pos += 1
        nxt = int(np.asarray(logits).argmax())
        expected.append(nxt)
    assert toks == expected


def test_sync_stream_server_death_raises_typed_error():
    """Server PROCESS dies mid-SSE (kill -9, no terminal chunk): the
    iterator raises InferenceServerException (the client's typed
    contract), not a raw urllib3 error. An in-process server.stop() is
    too gentle — in-flight handler threads run to completion — so the
    server lives in a subprocess the test kills."""
    import os
    import signal
    import subprocess
    import sys
    from pathlib import Path

    import client_tpu.http as httpclient
    from client_tpu.utils import InferenceServerException

    script = (
        "import sys; sys.path.insert(0, {repo!r})\n"
        "from client_tpu.models import default_model_zoo\n"
        "from client_tpu.server import HttpInferenceServer, ServerCore\n"
        "import time\n"
        "s = HttpInferenceServer(ServerCore(default_model_zoo())).start()\n"
        "print('PORT', s.port, flush=True)\n"
        "time.sleep(600)\n"
    ).format(repo=str(Path(__file__).resolve().parent.parent))
    env = {**os.environ, "PYTHONPATH": "", "JAX_PLATFORMS": "cpu"}
    proc = subprocess.Popen(
        [sys.executable, "-c", script], stdout=subprocess.PIPE, text=True,
        env=env,
    )
    try:
        import select

        # deadline on startup: a wedged child must fail the test, not hang
        # the suite
        ready, _, _ = select.select([proc.stdout], [], [], 120)
        assert ready, "server subprocess did not start within 120s"
        line = proc.stdout.readline().strip()
        assert line.startswith("PORT"), line
        url = f"127.0.0.1:{line.split()[1]}"
        with httpclient.InferenceServerClient(url) as client:
            stream = client.generate_stream(
                "repeat_int32",
                {"IN": list(range(10)), "DELAY": [0] + [400] * 9},
            )
            first = next(stream)
            assert first["OUT"] == 0
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=10)
            with pytest.raises(InferenceServerException):
                for _ in stream:
                    pass
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=10)


def test_aio_frontend_same_mapping(core):
    import asyncio

    from client_tpu.server import AioHttpInferenceServer

    with AioHttpInferenceServer(core) as server:
        import client_tpu.http.aio as aioclient

        async def run():
            async with aioclient.InferenceServerClient(server.url) as client:
                out = await client.generate(
                    "simple_string",
                    {"INPUT0": [[str(i) for i in range(16)]],
                     "INPUT1": [[str(i) for i in range(16)]]},
                    model_version="1",
                )
                assert out["OUTPUT1"] == ["0"] * 16

        asyncio.run(run())
