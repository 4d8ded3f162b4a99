"""Hostile-server tier for the native h2/gRPC transport.

The Python-client twin is tests/test_client_robustness.py; this file points
raw byte-level TCP servers at the hand-rolled HTTP/2 client
(native/src/h2.cc via the ctypes NativeGrpcClient) and requires typed
errors — never hangs, crashes, or garbage results — when the peer
misbehaves at the frame level.
"""

import socket
import struct
import threading
import time

import pytest

pytestmark = pytest.mark.usefixtures("native_build")


class _ByteServer:
    """Accepts one connection and runs ``behavior(conn)`` on it."""

    def __init__(self, behavior):
        self._behavior = behavior
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind(("127.0.0.1", 0))
        self._listener.listen(4)
        self.port = self._listener.getsockname()[1]
        self.url = f"127.0.0.1:{self.port}"
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._alive = True
        self._thread.start()

    def _loop(self):
        while self._alive:
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return
            try:
                self._behavior(conn)
            except Exception:
                # keep accepting: a behavior bug must surface as the
                # client-side error under test, not a dead accept loop
                pass
            finally:
                try:
                    conn.close()
                except OSError:
                    pass

    def close(self):
        self._alive = False
        self._listener.close()


def _frame(ftype, flags, stream_id, payload=b""):
    return (
        struct.pack(">I", len(payload))[1:]
        + bytes((ftype, flags))
        + struct.pack(">I", stream_id)
        + payload
    )


def _hpack_lit(name, value):
    """Literal-without-indexing HPACK field (tiny names/values only)."""
    return (b"\x00" + bytes((len(name),)) + name
            + bytes((len(value),)) + value)


def _read_preface_and_ack(conn):
    """Consume the client preface + SETTINGS, reply with our SETTINGS+ACK."""
    conn.settimeout(10)
    buf = b""
    while len(buf) < 24:
        chunk = conn.recv(4096)
        if not chunk:
            raise OSError("peer closed before completing the preface")
        buf += chunk
    assert buf.startswith(b"PRI * HTTP/2.0")
    conn.sendall(_frame(0x4, 0, 0))       # empty SETTINGS
    conn.sendall(_frame(0x4, 0x1, 0))     # SETTINGS ACK
    return buf[24:]


def _infer(url, timeout_s=10.0):
    from client_tpu.native import NativeGrpcClient

    import numpy as np

    with NativeGrpcClient(url) as client:
        data = np.arange(16, dtype=np.int32).reshape(1, 16)
        return client.infer(
            "custom_identity_int32", [("INPUT0", data)],
            client_timeout_s=timeout_s,
        )


def _expect_error(url, match=None, timeout_s=10.0):
    from client_tpu.utils import InferenceServerException

    t0 = time.monotonic()
    with pytest.raises(InferenceServerException) as exc:
        _infer(url, timeout_s)
    elapsed = time.monotonic() - t0
    if match:
        assert match in str(exc.value), str(exc.value)
    return elapsed


def test_immediate_close():
    """Peer closes right after accept: UNAVAILABLE, no hang."""
    server = _ByteServer(lambda conn: conn.close())
    try:
        _expect_error(server.url, "StatusCode.UNAVAILABLE")
    finally:
        server.close()


def test_garbage_bytes_instead_of_h2():
    """A non-h2 peer (e.g. an HTTP/1.1 server) produces a typed error."""
    def behavior(conn):
        conn.settimeout(10)
        conn.recv(4096)
        conn.sendall(b"HTTP/1.1 400 Bad Request\r\nContent-Length: 0\r\n\r\n")
        time.sleep(0.5)

    server = _ByteServer(behavior)
    try:
        _expect_error(server.url, "StatusCode.UNAVAILABLE")
    finally:
        server.close()


def test_rst_stream_reset():
    """Server RSTs the request stream: 'reset by peer' surfaces."""
    def behavior(conn):
        _read_preface_and_ack(conn)
        # drain whatever the client sends, then reset stream 1
        conn.settimeout(2)
        try:
            conn.recv(65536)
        except socket.timeout:
            pass
        conn.sendall(_frame(0x3, 0, 1, struct.pack(">I", 0x8)))  # CANCEL
        time.sleep(1)

    server = _ByteServer(behavior)
    try:
        _expect_error(server.url, "reset by peer")
    finally:
        server.close()


def test_silent_server_honors_timeout():
    """Server accepts, ACKs settings, then never answers: the client
    timeout bounds the call (DEADLINE_EXCEEDED), not a hang."""
    def behavior(conn):
        _read_preface_and_ack(conn)
        time.sleep(30)

    server = _ByteServer(behavior)
    try:
        elapsed = _expect_error(
            server.url, "DEADLINE_EXCEEDED", timeout_s=2.0
        )
        assert elapsed < 10, f"timeout not honored: {elapsed:.1f}s"
    finally:
        server.close()


def test_goaway_then_close():
    """GOAWAY + close: the client reports the debug data, not garbage."""
    def behavior(conn):
        _read_preface_and_ack(conn)
        conn.settimeout(2)
        try:
            conn.recv(65536)
        except socket.timeout:
            pass
        payload = struct.pack(">II", 0, 0x0) + b"maintenance"
        conn.sendall(_frame(0x7, 0, 0, payload))
        conn.close()

    server = _ByteServer(behavior)
    try:
        # the GOAWAY handler errors affected streams the moment the frame
        # arrives (typed, with debug data) rather than waiting for close
        _expect_error(server.url, "maintenance")
    finally:
        server.close()


def test_truncated_grpc_frame():
    """A well-formed h2 response whose gRPC message framing lies about its
    length must be rejected, not mis-parsed."""
    def behavior(conn):
        _read_preface_and_ack(conn)
        conn.settimeout(2)
        try:
            conn.recv(65536)
        except socket.timeout:
            pass
        # HEADERS: :status 200 (static table 8) + content-type
        block = b"\x88" + _hpack_lit(b"content-type", b"application/grpc")
        conn.sendall(_frame(0x1, 0x4, 1, block))  # END_HEADERS
        # DATA: frame header claims 100-byte message, delivers 4
        body = b"\x00" + struct.pack(">I", 100) + b"\x00" * 4
        conn.sendall(_frame(0x0, 0, 1, body))
        # trailers: grpc-status 0, END_STREAM
        trailers = _hpack_lit(b"grpc-status", b"0")
        conn.sendall(_frame(0x1, 0x5, 1, trailers))
        time.sleep(1)

    server = _ByteServer(behavior)
    try:
        _expect_error(server.url, "truncated gRPC response frame")
    finally:
        server.close()


def test_native_stream_survives_server_death():
    """Killing the server mid-stream delivers an error callback and
    stop_stream() returns promptly (the reader polls on a bounded deadline
    instead of blocking forever)."""
    import queue

    import numpy as np

    from client_tpu.models import default_model_zoo
    from client_tpu.native import NativeGrpcClient
    from client_tpu.server import GrpcInferenceServer, ServerCore

    server = GrpcInferenceServer(ServerCore(default_model_zoo())).start()
    results = queue.Queue()
    client = NativeGrpcClient(server.url)
    try:
        client.start_stream(lambda outputs, error: results.put((outputs, error)))
        client.stream_infer(
            "simple_sequence", [("INPUT", np.array([[3]], dtype=np.int32))],
            sequence=(777, True, False),
        )
        outputs, error = results.get(timeout=20)
        assert error is None and int(outputs["OUTPUT"][0, 0]) == 3

        server.stop(grace=0)
        outputs, error = results.get(timeout=30)
        assert outputs is None
        assert error is not None and "UNAVAILABLE" in error, error

        t0 = time.monotonic()
        client.stop_stream()
        assert time.monotonic() - t0 < 10, "stop_stream hung after server death"
    finally:
        client.close()


def test_garbage_proto_payload_never_crashes():
    """A well-formed h2+gRPC exchange whose protobuf payload is random
    garbage must yield a typed error or an empty result — never a crash or
    a hang (fuzzes InferResultGrpc::Parse end-to-end)."""
    import random

    from client_tpu.native import NativeGrpcClient
    from client_tpu.utils import InferenceServerException

    import numpy as np

    rng = random.Random(1234)

    def make_behavior(payload):
        def behavior(conn):
            _read_preface_and_ack(conn)
            conn.settimeout(2)
            try:
                conn.recv(65536)
            except socket.timeout:
                pass

            block = b"\x88" + _hpack_lit(b"content-type", b"application/grpc")
            conn.sendall(_frame(0x1, 0x4, 1, block))
            framed = b"\x00" + struct.pack(">I", len(payload)) + payload
            conn.sendall(_frame(0x0, 0, 1, framed))
            trailers = _hpack_lit(b"grpc-status", b"0")
            conn.sendall(_frame(0x1, 0x5, 1, trailers))
            time.sleep(0.5)

        return behavior

    for trial in range(8):
        payload = bytes(rng.randrange(256) for _ in range(rng.randrange(1, 300)))
        server = _ByteServer(make_behavior(payload))
        try:
            with NativeGrpcClient(server.url) as client:
                data = np.arange(4, dtype=np.int32).reshape(1, 4)
                try:
                    out = client.infer(
                        "m", [("INPUT0", data)], client_timeout_s=10.0
                    )
                    # parsed "successfully": garbage decoded to an output set
                    # (possibly empty) — acceptable, as long as nothing crashed
                    assert isinstance(out, dict)
                except InferenceServerException:
                    pass  # typed rejection is the expected common case
        finally:
            server.close()


# ---------------------------------------------------------------------------
# TLS (VERDICT r2 #4): https on both native clients against self-signed certs
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def self_signed_cert(tmp_path_factory):
    """(cert_path, key_path) for CN=localhost with SAN 127.0.0.1."""
    import subprocess

    d = tmp_path_factory.mktemp("tls")
    cert, key = str(d / "cert.pem"), str(d / "key.pem")
    subprocess.run(
        [
            "openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
            "-keyout", key, "-out", cert, "-days", "2", "-subj",
            "/CN=127.0.0.1",
            "-addext", "subjectAltName=IP:127.0.0.1,DNS:localhost",
        ],
        check=True, capture_output=True,
    )
    return cert, key


def test_native_grpc_over_tls(self_signed_cert):
    """grpc-over-TLS on the library's own h2 (ALPN h2, system libssl
    runtime): round trip against a grpcio secure port, CA-pinned.
    Reference: grpc SslOptions, grpc_client.h:43-60."""
    import grpc as grpc_mod
    import numpy as np

    from client_tpu.models import default_model_zoo
    from client_tpu.native import NativeGrpcClient
    from client_tpu.server import GrpcInferenceServer, ServerCore

    cert, key = self_signed_cert
    creds = grpc_mod.ssl_server_credentials(
        [(open(key, "rb").read(), open(cert, "rb").read())]
    )
    core = ServerCore(default_model_zoo())
    with GrpcInferenceServer(core, credentials=creds) as server:
        data = np.arange(1024, dtype=np.int32).reshape(1, 1024)
        with NativeGrpcClient(
            f"https://{server.url}", ssl_options={"ca_cert": cert}
        ) as client:
            assert client.is_server_live()
            out = client.infer(
                "custom_identity_int32", [("INPUT0", data)], outputs=["OUTPUT0"]
            )
            np.testing.assert_array_equal(out["OUTPUT0"].reshape(data.shape), data)

        # bi-di streaming rides the same TLS connection plumbing
        import queue

        results = queue.Queue()
        with NativeGrpcClient(
            f"https://{server.url}", ssl_options={"ca_cert": cert}
        ) as stream_client:
            stream_client.start_stream(
                lambda outputs, error: results.put((outputs, error))
            )
            stream_client.stream_infer(
                "simple_sequence",
                [("INPUT", np.array([[5]], dtype=np.int32))],
                sequence=(717, True, True),
            )
            outputs, error = results.get(timeout=30)
            assert error is None, error
            assert int(outputs["OUTPUT"][0, 0]) == 5
            stream_client.stop_stream()

        # verification is real: without the CA the handshake must fail
        with NativeGrpcClient(
            f"https://{server.url}"
        ) as untrusted:
            from client_tpu.utils import InferenceServerException

            with pytest.raises(InferenceServerException, match="TLS|certificate|verify"):
                untrusted.is_server_live()

        # explicit opt-out mirrors the reference's verify_peer=false
        with NativeGrpcClient(
            f"https://{server.url}",
            ssl_options={"verify_peer": False, "verify_host": False},
        ) as insecure:
            assert insecure.is_server_live()


def test_native_http_over_tls(self_signed_cert):
    """https on the libcurl client (HttpSslOptions parity) through a
    TLS-terminating proxy in front of the in-process HTTP server.
    Reference: http_client.h:45-103."""
    import ssl as ssl_mod

    import numpy as np

    from client_tpu.models import default_model_zoo
    from client_tpu.native import NativeClient
    from client_tpu.server import HttpInferenceServer, ServerCore

    cert, key = self_signed_cert
    with HttpInferenceServer(ServerCore(default_model_zoo())) as plain:
        ctx = ssl_mod.SSLContext(ssl_mod.PROTOCOL_TLS_SERVER)
        ctx.load_cert_chain(cert, key)
        listener = socket.socket()
        listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        listener.bind(("127.0.0.1", 0))
        listener.listen(8)
        tls_port = listener.getsockname()[1]
        alive = [True]

        def pump(src, dst):
            try:
                while True:
                    chunk = src.recv(65536)
                    if not chunk:
                        break
                    dst.sendall(chunk)
            except OSError:
                pass
            finally:
                for s in (src, dst):
                    try:
                        s.shutdown(socket.SHUT_RDWR)
                    except OSError:
                        pass

        def accept_loop():
            while alive[0]:
                try:
                    conn, _ = listener.accept()
                    tls_conn = ctx.wrap_socket(conn, server_side=True)
                except OSError:
                    return
                upstream = socket.create_connection(("127.0.0.1", plain.port))
                threading.Thread(target=pump, args=(tls_conn, upstream), daemon=True).start()
                threading.Thread(target=pump, args=(upstream, tls_conn), daemon=True).start()

        t = threading.Thread(target=accept_loop, daemon=True)
        t.start()
        try:
            data = np.arange(512, dtype=np.int32).reshape(1, 512)
            with NativeClient(
                f"https://127.0.0.1:{tls_port}", ssl_options={"ca_cert": cert}
            ) as client:
                assert client.is_server_live()
                out = client.infer_raw(
                    "custom_identity_int32", "INPUT0", data, "OUTPUT0"
                )
                np.testing.assert_array_equal(out, data.reshape(-1))

            # un-pinned CA must fail peer verification
            from client_tpu.utils import InferenceServerException

            with NativeClient(f"https://127.0.0.1:{tls_port}") as untrusted:
                with pytest.raises(InferenceServerException):
                    untrusted.is_server_live()
        finally:
            alive[0] = False
            listener.close()


# ---------------------------------------------------------------------------
# mid-stream GOAWAY / RST storms (VERDICT r2 #9)
# ---------------------------------------------------------------------------


def test_goaway_during_active_bidi_stream():
    """GOAWAY (last_stream_id=0) while a bi-di stream is active: the reader
    delivers a typed error to the callback, the stream goes inactive, and a
    later stream_infer refuses instead of hanging (reference stream-death
    semantics, grpc/_infer_stream.py:157-167)."""
    import queue

    import numpy as np

    from client_tpu.native import NativeGrpcClient
    from client_tpu.utils import InferenceServerException

    def behavior(conn):
        _read_preface_and_ack(conn)
        conn.settimeout(2)
        try:
            conn.recv(65536)  # HEADERS (+ first DATA) for the stream
        except socket.timeout:
            pass
        # GOAWAY last_stream_id=0, NO_ERROR, debug text; keep the socket
        # open: the typed failure must come from the GOAWAY itself, not a
        # subsequent close
        payload = struct.pack(">II", 0, 0x0) + b"draining"
        conn.sendall(_frame(0x7, 0, 0, payload))
        time.sleep(3)

    server = _ByteServer(behavior)
    results = queue.Queue()
    try:
        with NativeGrpcClient(server.url) as client:
            client.start_stream(
                lambda outputs, error: results.put((outputs, error))
            )
            client.stream_infer(
                "custom_identity_int32",
                [("INPUT0", np.arange(4, dtype=np.int32).reshape(1, 4))],
            )
            outputs, error = results.get(timeout=10)
            assert outputs is None
            assert "GOAWAY" in error or "draining" in error, error
            # the stream is dead: further sends must refuse, not hang
            with pytest.raises(InferenceServerException, match="no longer|stream"):
                client.stream_infer(
                    "custom_identity_int32",
                    [("INPUT0", np.zeros((1, 4), dtype=np.int32))],
                )
    finally:
        server.close()


def test_goaway_fails_multiplexed_async_inflight():
    """GOAWAY with a window of async RPCs in flight: every callback fires
    with a typed error — none is silently dropped or left hanging."""
    import queue

    import numpy as np

    from client_tpu.native import NativeGrpcClient

    def behavior(conn):
        _read_preface_and_ack(conn)
        conn.settimeout(2)
        try:
            conn.recv(65536)
        except socket.timeout:
            pass
        payload = struct.pack(">II", 0, 0x0) + b"overloaded"
        conn.sendall(_frame(0x7, 0, 0, payload))
        time.sleep(3)

    server = _ByteServer(behavior)
    results = queue.Queue()
    n = 4
    try:
        with NativeGrpcClient(server.url) as client:
            data = np.arange(16, dtype=np.int32).reshape(1, 16)
            for i in range(n):
                client.async_infer(
                    "custom_identity_int32", [("INPUT0", data)],
                    lambda outputs, error, i=i: results.put((i, outputs, error)),
                )
            seen = set()
            for _ in range(n):
                i, outputs, error = results.get(timeout=15)
                seen.add(i)
                assert outputs is None
                assert error, f"request {i} completed without error?"
            assert seen == set(range(n))
    finally:
        server.close()


def test_rst_storm_does_not_kill_the_connection():
    """The server RSTs EVERY stream it sees: each request gets its typed
    error, the connection survives (RST kills streams, not connections),
    and no state leaks across requests."""
    def behavior(conn):
        buf = _read_preface_and_ack(conn)
        conn.settimeout(8)
        # parse REAL h2 frame headers (9 bytes: len24/type/flags/stream_id)
        # and RST each HEADERS frame's stream — a byte-scan heuristic can
        # misread payload bytes as frame types and storm garbage ids
        rst_sent = set()
        deadline = time.monotonic() + 8
        while time.monotonic() < deadline:
            while len(buf) >= 9:
                length = struct.unpack(">I", b"\x00" + buf[:3])[0]
                ftype = buf[3]
                sid = struct.unpack(">I", buf[5:9])[0] & 0x7FFFFFFF
                if len(buf) < 9 + length:
                    break
                buf = buf[9 + length:]
                if ftype == 0x1 and sid and sid not in rst_sent:  # HEADERS
                    rst_sent.add(sid)
                    conn.sendall(
                        _frame(0x3, 0, sid, struct.pack(">I", 0x8)))
            try:
                chunk = conn.recv(65536)
            except socket.timeout:
                return
            if not chunk:
                return
            buf += chunk

    server = _ByteServer(behavior)
    try:
        from client_tpu.native import NativeGrpcClient

        import numpy as np

        with NativeGrpcClient(server.url) as client:
            from client_tpu.utils import InferenceServerException

            data = np.arange(16, dtype=np.int32).reshape(1, 16)
            for _ in range(3):
                with pytest.raises(InferenceServerException, match="reset|RST|stream"):
                    client.infer(
                        "custom_identity_int32", [("INPUT0", data)],
                        outputs=["OUTPUT0"], client_timeout_s=5.0,
                    )
    finally:
        server.close()
