"""End-to-end HTTP tests: real client against the in-process v2 server.

This is the reference's integration tier (SURVEY.md §4 tier 2) made
self-contained: the ``simple`` INT32 sum/diff contract over a live local
server (BASELINE.json, config #1).
"""

import numpy as np
import pytest

import client_tpu.http as httpclient
from client_tpu.models import default_model_zoo
from client_tpu.server import HttpInferenceServer, ServerCore
from client_tpu.utils import InferenceServerException


@pytest.fixture(scope="module")
def server():
    with HttpInferenceServer(ServerCore(default_model_zoo())) as s:
        yield s


@pytest.fixture(scope="module")
def client(server):
    with httpclient.InferenceServerClient(server.url, concurrency=4) as c:
        yield c


def _simple_inputs():
    a = np.arange(16, dtype=np.int32).reshape(1, 16)
    b = np.ones((1, 16), dtype=np.int32)
    in0 = httpclient.InferInput("INPUT0", [1, 16], "INT32")
    in1 = httpclient.InferInput("INPUT1", [1, 16], "INT32")
    in0.set_data_from_numpy(a)
    in1.set_data_from_numpy(b)
    return a, b, [in0, in1]


def test_health_and_metadata(client):
    assert client.is_server_live()
    assert client.is_server_ready()
    assert client.is_model_ready("simple")
    assert not client.is_model_ready("nonexistent")
    md = client.get_server_metadata()
    assert "tpu_shared_memory" in md["extensions"]
    mmd = client.get_model_metadata("simple")
    assert mmd["name"] == "simple"
    assert mmd["inputs"][0]["datatype"] == "INT32"
    cfg = client.get_model_config("simple")
    assert cfg["backend"] == "jax"


def test_simple_infer_binary(client):
    a, b, inputs = _simple_inputs()
    outputs = [
        httpclient.InferRequestedOutput("OUTPUT0"),
        httpclient.InferRequestedOutput("OUTPUT1"),
    ]
    result = client.infer("simple", inputs, outputs=outputs, request_id="1")
    np.testing.assert_array_equal(result.as_numpy("OUTPUT0"), a + b)
    np.testing.assert_array_equal(result.as_numpy("OUTPUT1"), a - b)
    assert result.get_response()["id"] == "1"


def test_simple_infer_json_mode(client):
    a, b, _ = _simple_inputs()
    in0 = httpclient.InferInput("INPUT0", [1, 16], "INT32")
    in1 = httpclient.InferInput("INPUT1", [1, 16], "INT32")
    in0.set_data_from_numpy(a, binary_data=False)
    in1.set_data_from_numpy(b, binary_data=False)
    outputs = [httpclient.InferRequestedOutput("OUTPUT0", binary_data=False)]
    result = client.infer("simple", [in0, in1], outputs=outputs)
    np.testing.assert_array_equal(result.as_numpy("OUTPUT0"), a + b)
    # JSON-mode output carries a data list, not a binary tail
    assert "data" in result.get_output("OUTPUT0")


def test_infer_default_outputs(client):
    a, b, inputs = _simple_inputs()
    result = client.infer("simple", inputs)
    np.testing.assert_array_equal(result.as_numpy("OUTPUT0"), a + b)
    np.testing.assert_array_equal(result.as_numpy("OUTPUT1"), a - b)


def test_async_infer(client):
    a, b, inputs = _simple_inputs()
    handles = [client.async_infer("simple", inputs) for _ in range(8)]
    for h in handles:
        result = h.get_result()
        np.testing.assert_array_equal(result.as_numpy("OUTPUT0"), a + b)


def test_string_model(client):
    data = np.array([[str(i) for i in range(16)]], dtype=np.object_)
    ones = np.array([["1"] * 16], dtype=np.object_)
    in0 = httpclient.InferInput("INPUT0", [1, 16], "BYTES")
    in1 = httpclient.InferInput("INPUT1", [1, 16], "BYTES")
    in0.set_data_from_numpy(data)
    in1.set_data_from_numpy(ones)
    result = client.infer("simple_string", [in0, in1])
    out = result.as_numpy("OUTPUT0")
    assert out[0, 5] == b"6"


def test_identity_bytes_roundtrip(client):
    payload = np.array([[b"hello", b"\x00\xffworld"]], dtype=np.object_)
    inp = httpclient.InferInput("INPUT0", [1, 2], "BYTES")
    inp.set_data_from_numpy(payload)
    result = client.infer("simple_identity", [inp])
    np.testing.assert_array_equal(result.as_numpy("OUTPUT0"), payload)


def test_compression(client):
    a, b, inputs = _simple_inputs()
    for algo in ("gzip", "deflate"):
        result = client.infer(
            "simple", inputs, request_compression_algorithm=algo,
            response_compression_algorithm="gzip",
        )
        np.testing.assert_array_equal(result.as_numpy("OUTPUT0"), a + b)


def test_error_unknown_model(client):
    _, _, inputs = _simple_inputs()
    with pytest.raises(InferenceServerException, match="unknown model"):
        client.infer("nonexistent_model", inputs)


def test_error_wrong_shape(client):
    in0 = httpclient.InferInput("INPUT0", [1, 8], "INT32")
    in0.set_data_from_numpy(np.zeros((1, 8), dtype=np.int32))
    in1 = httpclient.InferInput("INPUT1", [1, 8], "INT32")
    in1.set_data_from_numpy(np.zeros((1, 8), dtype=np.int32))
    with pytest.raises(InferenceServerException, match="shape"):
        client.infer("simple", [in0, in1])


def test_repository_control(client):
    index = client.get_model_repository_index()
    names = {m["name"] for m in index}
    assert {"simple", "simple_identity", "repeat_int32"} <= names
    client.unload_model("simple_string")
    assert not client.is_model_ready("simple_string")
    client.load_model("simple_string")
    assert client.is_model_ready("simple_string")


def test_statistics(client):
    _, _, inputs = _simple_inputs()
    client.infer("simple", inputs)
    stats = client.get_inference_statistics("simple")
    entry = stats["model_stats"][0]
    assert entry["name"] == "simple"
    assert entry["inference_count"] >= 1
    assert entry["inference_stats"]["success"]["count"] >= 1
    all_stats = client.get_inference_statistics()
    assert len(all_stats["model_stats"]) >= 2


def test_trace_and_log_settings(client):
    ts = client.get_trace_settings()
    assert ts["trace_level"] == ["OFF"]
    updated = client.update_trace_settings(settings={"trace_level": ["TIMESTAMPS"]})
    assert updated["trace_level"] == ["TIMESTAMPS"]
    assert client.get_trace_settings("simple")["trace_level"] == ["TIMESTAMPS"]
    client.update_trace_settings(settings={"trace_level": ["OFF"]})

    ls = client.get_log_settings()
    assert ls["log_info"] is True
    updated = client.update_log_settings({"log_verbose_level": 2})
    assert updated["log_verbose_level"] == 2


def test_sequence_model(client):
    total = 0
    for i, (start, end) in enumerate([(True, False), (False, False), (False, True)]):
        inp = httpclient.InferInput("INPUT", [1, 1], "INT32")
        inp.set_data_from_numpy(np.array([[i + 1]], dtype=np.int32))
        result = client.infer(
            "simple_sequence", [inp], sequence_id=99, sequence_start=start, sequence_end=end
        )
        total += i + 1
        assert result.as_numpy("OUTPUT")[0, 0] == total


def test_classification_extension(client):
    a = np.arange(16, dtype=np.int32).reshape(1, 16)
    b = np.zeros((1, 16), dtype=np.int32)
    in0 = httpclient.InferInput("INPUT0", [1, 16], "INT32")
    in1 = httpclient.InferInput("INPUT1", [1, 16], "INT32")
    in0.set_data_from_numpy(a)
    in1.set_data_from_numpy(b)
    outputs = [httpclient.InferRequestedOutput("OUTPUT0", class_count=3)]
    result = client.infer("simple", [in0, in1], outputs=outputs)
    top = result.as_numpy("OUTPUT0")
    # non-batched model (max_batch_size=0): whole tensor is one class vector
    assert top.shape == (3,)
    # top value is 15 at index 15
    value, idx = top[0].decode().split(":")[:2]
    assert int(idx) == 15 and float(value) == 15.0


def test_client_stats(client):
    _, _, inputs = _simple_inputs()
    before = client.client_infer_stat()["completed_request_count"]
    client.infer("simple", inputs)
    after = client.client_infer_stat()
    assert after["completed_request_count"] == before + 1
    assert after["cumulative_total_request_time_ns"] > 0


def test_basic_auth_plugin(server):
    import base64 as b64

    with httpclient.InferenceServerClient(server.url) as c:
        c.register_plugin(httpclient.BasicAuth("user", "pass"))
        assert c.is_server_live()  # plugin applied without breaking requests
        expected = "Basic " + b64.b64encode(b"user:pass").decode()
        req = httpclient.Request({})
        c.plugin()(req)
        assert req.headers["authorization"] == expected
        c.unregister_plugin()
        assert c.plugin() is None


# ---------------------------------------------------------------------------
# aiohttp frontend: the same client tests against the event-loop server
# ---------------------------------------------------------------------------


def test_aio_frontend_full_flow():
    import client_tpu.utils.shared_memory as shm
    from client_tpu.server.http_server_aio import AioHttpInferenceServer

    core = ServerCore(default_model_zoo())
    with AioHttpInferenceServer(core) as server:
        with httpclient.InferenceServerClient(server.url) as client:
            assert client.is_server_live()
            assert client.is_model_ready("simple")
            a = np.arange(16, dtype=np.int32).reshape(1, 16)
            b = np.ones((1, 16), dtype=np.int32)
            in0 = httpclient.InferInput("INPUT0", [1, 16], "INT32").set_data_from_numpy(a)
            in1 = httpclient.InferInput("INPUT1", [1, 16], "INT32").set_data_from_numpy(b)
            result = client.infer("simple", [in0, in1])
            np.testing.assert_array_equal(result.as_numpy("OUTPUT0"), a + b)
            # admin surface
            md = client.get_server_metadata()  # /v2 (async handler, not lambda)
            assert "tpu_shared_memory" in md["extensions"]
            all_stats = client.get_inference_statistics()  # /v2/models/stats
            assert any(m["name"] == "simple" for m in all_stats["model_stats"])
            assert client.get_model_config("simple")["backend"] == "jax"
            index = client.get_model_repository_index()
            assert any(m["name"] == "simple" for m in index)
            stats = client.get_inference_statistics("simple")
            assert stats["model_stats"][0]["inference_count"] >= 1
            assert client.get_trace_settings()["trace_level"] == ["OFF"]
            # shm negotiation
            region = shm.create_shared_memory_region("aiofr", "/aio_frontend", 128)
            try:
                shm.set_shared_memory_region(region, [a, b])
                client.register_system_shared_memory("aiofr", "/aio_frontend", 128)
                i0 = httpclient.InferInput("INPUT0", [1, 16], "INT32").set_shared_memory("aiofr", 64)
                i1 = httpclient.InferInput("INPUT1", [1, 16], "INT32").set_shared_memory("aiofr", 64, offset=64)
                r = client.infer("simple", [i0, i1])
                np.testing.assert_array_equal(r.as_numpy("OUTPUT0"), a + b)
                # status GETs exercise the action-less shm routes
                assert client.get_system_shared_memory_status()[0]["name"] == "aiofr"
                assert client.get_tpu_shared_memory_status() == []
                client.unregister_system_shared_memory()
            finally:
                shm.destroy_shared_memory_region(region)
            # errors still map correctly
            with pytest.raises(InferenceServerException, match="unknown model"):
                client.infer("missing", [in0, in1])


@pytest.mark.parametrize("datatype,model", [("BF16", "identity_bf16"), ("FP16", "identity_fp16")])
def test_half_precision_identity_roundtrip(client, datatype, model):
    """BF16/FP16 wire round trips: native half dtypes end to end."""
    from client_tpu.utils import triton_to_np_dtype

    np_dtype = np.dtype(triton_to_np_dtype(datatype))
    data = np.array([[1.5, -2.25, 0.125, 3.0]], dtype=np_dtype)
    inp = httpclient.InferInput("INPUT0", [1, 4], datatype)
    inp.set_data_from_numpy(data)
    result = client.infer(model, [inp])
    out = result.as_numpy("OUTPUT0")
    assert out.dtype == np_dtype
    np.testing.assert_array_equal(out, data)
    # as_jax places the half-precision result on a jax device
    jax_out = result.as_jax("OUTPUT0")
    assert type(jax_out).__module__.startswith(("jax", "jaxlib"))
    np.testing.assert_array_equal(np.asarray(jax_out), data)


def test_server_rejects_hostile_binary_data_size(server):
    """A malformed binary_data_size in a raw request is a 400 protocol error,
    not a 500 (the server validates before slicing the binary tail)."""
    import http.client as hc
    import json as _json

    for bad in (-4, "4", True):
        header = _json.dumps({
            "inputs": [
                {"name": "INPUT0", "datatype": "INT32", "shape": [1, 16],
                 "parameters": {"binary_data_size": bad}},
                {"name": "INPUT1", "datatype": "INT32", "shape": [1, 16],
                 "parameters": {"binary_data_size": 64}},
            ]
        }).encode()
        body = header + b"\x00" * 128
        conn = hc.HTTPConnection("127.0.0.1", server.port, timeout=10)
        try:
            conn.request(
                "POST", "/v2/models/simple/infer", body,
                {"Inference-Header-Content-Length": str(len(header)),
                 "Content-Type": "application/octet-stream"},
            )
            resp = conn.getresponse()
            payload = resp.read()
            assert resp.status == 400, (bad, resp.status, payload)
            assert b"binary_data_size" in payload
        finally:
            conn.close()
    # declared size overrunning the tail is also a 400
    header = _json.dumps({
        "inputs": [
            {"name": "INPUT0", "datatype": "INT32", "shape": [1, 16],
             "parameters": {"binary_data_size": 1 << 20}},
        ]
    }).encode()
    conn = hc.HTTPConnection("127.0.0.1", server.port, timeout=10)
    try:
        conn.request(
            "POST", "/v2/models/simple/infer", header + b"\x00" * 64,
            {"Inference-Header-Content-Length": str(len(header)),
             "Content-Type": "application/octet-stream"},
        )
        resp = conn.getresponse()
        payload = resp.read()
        assert resp.status == 400, (resp.status, payload)
        assert b"overruns" in payload
    finally:
        conn.close()
