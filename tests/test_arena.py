"""Pooled shm arena (client_tpu.arena): leases, trimming, cached
registrations, and the transparent zero-copy fast path.

Covers: (a) size-class allocation + ref-counted lease/release semantics
(double release raises; ``as_numpy`` after the last release raises the
typed ``ArenaLeaseReleased``); (b) concurrent lease/release stress on sync
threads AND asyncio tasks asserting no two live leases ever share a slab
and residency returns to zero (checked through the DataPlaneRecorder
gauges, not just the arena's own counters); (c) registration caching — an
RPC only on a region's first use per endpoint — with invalidation on
server-side unregister and on pool endpoint ejection; (d) the transparent
promotion fast path on the http/grpc/aio frontends plus zero-copy output
views; (e) LRU watermark trimming; (f) the ``arena_smoke`` chaos marker
(run by tools/chaos_smoke.sh): promotion x retry resilience under a
flapping proxy with residency back to zero.
"""

import asyncio
import gc
import threading

import numpy as np
import pytest

import client_tpu.grpc as grpcclient
import client_tpu.http as httpclient
from client_tpu import observe
from client_tpu.arena import (
    ArenaError,
    ArenaLeaseReleased,
    ShmArena,
    default_arena,
)
from client_tpu.models import default_model_zoo
from client_tpu.pool import EndpointEjected, EndpointHealthChanged, PoolClient
from client_tpu.resilience import (
    CircuitBreaker,
    ResiliencePolicy,
    RetryPolicy,
)
from client_tpu.server import (
    GrpcInferenceServer,
    HttpInferenceServer,
    ServerCore,
)
from client_tpu.testing import ChaosProxy, Fault


@pytest.fixture()
def arena():
    a = ShmArena()
    yield a
    a.close(force=True)


@pytest.fixture(scope="module")
def http_server():
    with HttpInferenceServer(ServerCore(default_model_zoo())) as s:
        yield s


@pytest.fixture(scope="module")
def grpc_server():
    with GrpcInferenceServer(ServerCore(default_model_zoo())) as s:
        yield s


# -- allocation & lease semantics ---------------------------------------------
def test_size_classes_and_hits(arena):
    l1 = arena.lease(100)       # -> min class (4096)
    l2 = arena.lease(4097)      # -> 8192
    l3 = arena.lease(5 * 1024)  # -> 8192 (hit: same class as l2's region)
    assert l1.byte_size == 4096
    assert l2.byte_size == 8192
    assert l3.byte_size == 8192
    s = arena.stats()
    assert s["misses"] == 2 and s["hits"] == 1
    for lease in (l1, l2, l3):
        lease.release()
    assert arena.stats()["leased_bytes"] == 0


def test_oversize_lease_gets_dedicated_region(arena):
    big = arena.lease(arena.max_class_bytes + 1)
    assert big.byte_size % 4096 == 0
    assert big.byte_size >= arena.max_class_bytes + 1
    big.release()


def test_double_release_raises_and_retain_pins(arena):
    lease = arena.lease(64)
    lease.retain()
    lease.release()
    assert not lease.released  # one holder left
    lease.release()
    assert lease.released
    with pytest.raises(ArenaError):
        lease.release()
    with pytest.raises(ArenaLeaseReleased):
        lease.retain()


def test_as_numpy_view_after_release_raises_typed(arena):
    lease = arena.lease(1024)
    lease.write_numpy(np.arange(256, dtype=np.float32))
    view = lease.as_numpy("FP32", [256])
    assert view[7] == 7.0
    lease.release()
    with pytest.raises(ArenaLeaseReleased):
        lease.as_numpy("FP32", [256])
    with pytest.raises(ArenaLeaseReleased):
        lease.memoryview()


def test_as_numpy_is_zero_copy(arena):
    lease = arena.lease(1024)
    lease.write_numpy(np.zeros(256, dtype=np.float32))
    view = lease.as_numpy("FP32", [256])
    # mutate the slab through the lease; the view must see it (same pages)
    lease.write_numpy(np.full(256, 3.0, dtype=np.float32))
    assert view[0] == 3.0
    lease.release()


def test_write_bounds_checked(arena):
    lease = arena.lease(100)
    with pytest.raises(ArenaError):
        lease.write(b"x" * (lease.byte_size + 1))
    with pytest.raises(ArenaError):
        lease.as_numpy("FP32", [4096])  # 16 KiB read from a 4 KiB slab
    lease.release()


def test_lru_trim_watermarks():
    a = ShmArena(region_target_bytes=4096, high_watermark_bytes=2 * 4096,
                 low_watermark_bytes=4096)
    try:
        # three single-slab regions
        leases = [a.lease(4096) for _ in range(3)]
        assert a.stats()["regions"] == 3
        for lease in leases:
            lease.release()
        # releasing pushed free bytes past the high watermark: LRU regions
        # were destroyed until free bytes <= low watermark
        s = a.stats()
        assert s["free_bytes"] <= 4096
        assert s["regions_trimmed"] >= 2
        assert s["leased_bytes"] == 0
    finally:
        a.close(force=True)


def test_close_refuses_with_outstanding_leases(arena):
    lease = arena.lease(64)
    with pytest.raises(ArenaError):
        arena.close()
    lease.release()
    arena.close()
    with pytest.raises(ArenaError):
        arena.lease(64)


# -- concurrency stress -------------------------------------------------------
def test_thread_stress_no_double_lease_and_residency_zero():
    recorder = observe.enable_dataplane()
    a = ShmArena()
    errors = []
    live_lock = threading.Lock()
    live = set()  # (region key, offset) of currently-held slabs

    def worker(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(200):
                lease = a.lease(int(rng.integers(1, 32 * 1024)))
                slot = (lease.region_key, lease.offset)
                with live_lock:
                    assert slot not in live, "double-leased slab"
                    live.add(slot)
                lease.write(b"x" * min(lease.nbytes, 64))
                with live_lock:
                    live.remove(slot)
                lease.release()
        except BaseException as e:  # pragma: no cover - failure path
            errors.append(e)

    try:
        threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        s = a.stats()
        assert s["leases"] == 8 * 200 == s["releases"]
        assert s["leased_bytes"] == 0 and s["leased_slabs"] == 0
        # the recorder's per-class gauges must agree: leased bytes all zero
        snap = recorder.snapshot()["arena"]
        assert snap["leases"], "recorder saw no arena activity"
        for row in snap["bytes"].values():
            assert row["leased"] == 0
    finally:
        observe.install_dataplane(None)
        a.close(force=True)


def test_asyncio_stress_residency_zero():
    a = ShmArena()

    async def worker(seed):
        rng = np.random.default_rng(seed)
        for _ in range(100):
            lease = a.lease(int(rng.integers(1, 16 * 1024)))
            await asyncio.sleep(0)  # force interleaving across tasks
            lease.retain()
            lease.release()
            await asyncio.sleep(0)
            lease.release()

    async def main():
        await asyncio.gather(*(worker(i) for i in range(16)))

    try:
        asyncio.run(main())
        s = a.stats()
        assert s["leased_bytes"] == 0 and s["leased_slabs"] == 0
        assert s["leases"] == 16 * 100
    finally:
        a.close(force=True)


# -- cached registrations -----------------------------------------------------
def test_registration_cached_and_invalidated_on_unregister(http_server, arena):
    recorder = observe.enable_dataplane()
    try:
        with httpclient.InferenceServerClient(http_server.url) as client:
            lease = arena.lease(4096)
            region = lease._region
            assert arena.ensure_registered(client, region) is True
            assert arena.ensure_registered(client, region) is False
            assert arena.ensure_registered(client, region) is False
            s = arena.stats()
            assert s["registrations_issued"] == 1
            assert s["registrations_cached"] == 2
            # exactly ONE register RPC reached the wire
            assert recorder.registered_totals().get("system", 0) == 1
            # server-side unregister drops the cache entry -> re-issue
            client.unregister_system_shared_memory(region.name)
            assert arena.stats()["registrations_invalidated"] == 1
            assert arena.ensure_registered(client, region) is True
            assert recorder.registered_totals().get("system", 0) == 2
            lease.release()
    finally:
        observe.install_dataplane(None)


def test_unregister_all_invalidates_every_entry(http_server, arena):
    with httpclient.InferenceServerClient(http_server.url) as client:
        l1, l2 = arena.lease(4096), arena.lease(64 * 1024)
        arena.ensure_registered(client, l1._region)
        arena.ensure_registered(client, l2._region)
        assert len(arena.registration_entries().get(http_server.url, [])) == 2
        client.unregister_system_shared_memory()  # name="" -> all
        assert arena.registration_entries() == {}
        l1.release()
        l2.release()


def test_registration_invalidated_on_pool_ejection(http_server, arena):
    pool = PoolClient([http_server.url], protocol="http", shm_arena=arena,
                      health_interval_s=None)
    try:
        ep = pool.pool.endpoints[0]
        lease = arena.lease(4096)
        arena.ensure_registered(ep.client, lease._region)
        assert arena.registration_entries().get(http_server.url)
        # the active prober flipping the endpoint unhealthy must drop the
        # cached registrations (the replica may have restarted)
        pool.pool.set_health(ep, False)
        assert not arena.registration_entries().get(http_server.url)
        # re-use after recovery re-issues and re-caches
        pool.pool.set_health(ep, True)
        assert arena.ensure_registered(ep.client, lease._region) is True
        lease.release()
    finally:
        pool.close()


def test_arena_event_observer_chains():
    from client_tpu.pool import _arena_event_observer

    class _FakeArena:
        def __init__(self):
            self.invalidated = []

        def invalidate_endpoint(self, url):
            self.invalidated.append(url)

    fake = _FakeArena()
    seen = []
    obs = _arena_event_observer(fake, chain=seen.append)
    obs(EndpointEjected("u1", 1.0, 3, 1))
    # BOTH health edges drop: a replica that just healed may have
    # restarted during the outage, so a request re-homed onto it (a
    # disagg re-prefill, say) must re-verify its registration instead of
    # trusting the pre-outage cache entry
    obs(EndpointHealthChanged("u2", healthy=True))
    obs(EndpointHealthChanged("u3", healthy=False))
    from client_tpu.pool import EndpointReadmitted

    obs(EndpointReadmitted("u4"))
    assert fake.invalidated == ["u1", "u2", "u3", "u4"]
    assert len(seen) == 4  # caller's observer still sees every event


# -- transparent fast path ----------------------------------------------------
def _simple_pair():
    a = np.arange(16, dtype=np.int32).reshape(1, 16)
    b = np.ones((1, 16), dtype=np.int32)
    return a, b


def _staged_inputs(mod, a, b, arena=None):
    in0 = mod.InferInput("INPUT0", [1, 16], "INT32")
    in0.set_data_from_numpy(a, arena=arena)
    in1 = mod.InferInput("INPUT1", [1, 16], "INT32")
    in1.set_data_from_numpy(b, arena=arena)
    return [in0, in1]


def test_http_promotion_and_output_lease(http_server, arena):
    a, b = _simple_pair()
    with httpclient.InferenceServerClient(http_server.url) as client:
        client.configure_arena(arena)
        for _ in range(3):
            inputs = _staged_inputs(httpclient, a, b)
            out0 = arena.request_output("OUTPUT0", a.nbytes)
            out1 = httpclient.InferRequestedOutput("OUTPUT1")
            result = client.infer("simple", inputs, outputs=[out0, out1])
            view = result.as_numpy("OUTPUT0")
            np.testing.assert_array_equal(view, a + b)
            # OUTPUT1 rode the wire (not requested via shm)
            np.testing.assert_array_equal(result.as_numpy("OUTPUT1"), a - b)
            result.release_arena()
            out0.release_arena_lease()
            with pytest.raises(ArenaLeaseReleased):
                result.as_numpy("OUTPUT0")
        s = arena.stats()
        # promotion releases per request; outputs released above
        assert s["leased_bytes"] == 0
        # one register RPC per region, everything else cache hits
        assert s["registrations_issued"] <= 2
        # inputs stayed reusable: promotion restored their raw staging
        assert inputs[0]._raw_data is not None


def test_http_promotion_leaves_wire_mode_untouched_without_arena(http_server):
    a, b = _simple_pair()
    with httpclient.InferenceServerClient(http_server.url) as client:
        result = client.infer("simple", _staged_inputs(httpclient, a, b))
        np.testing.assert_array_equal(result.as_numpy("OUTPUT0"), a + b)


def test_explicit_arena_staging_set_data_from_numpy(http_server, arena):
    a, b = _simple_pair()
    with httpclient.InferenceServerClient(http_server.url) as client:
        inputs = _staged_inputs(httpclient, a, b, arena=arena)
        assert inputs[0]._arena_lease is not None
        assert inputs[0]._raw_data is None  # bytes live in the slab only
        result = client.infer("simple", inputs)
        np.testing.assert_array_equal(result.as_numpy("OUTPUT0"), a + b)
        # re-staging releases the old lease
        inputs[0].set_data_from_numpy(a)
        assert inputs[0]._arena_lease is None
        inputs[1].release_arena_lease()
        assert arena.stats()["leased_bytes"] == 0


def test_grpc_promotion_and_output_lease(grpc_server, arena):
    a, b = _simple_pair()
    with grpcclient.InferenceServerClient(grpc_server.url) as client:
        client.configure_arena(arena)
        inputs = _staged_inputs(grpcclient, a, b)
        out0 = arena.request_output("OUTPUT0", a.nbytes)
        result = client.infer("simple", inputs, outputs=[out0])
        np.testing.assert_array_equal(result.as_numpy("OUTPUT0"), a + b)
        result.release_arena()
        with pytest.raises(ArenaLeaseReleased):
            result.as_numpy("OUTPUT0")
        assert arena.stats()["leased_bytes"] == 0


def test_aio_promotion(http_server, arena):
    import client_tpu.http.aio as aioclient

    a, b = _simple_pair()

    async def main():
        client = aioclient.InferenceServerClient(http_server.url)
        try:
            client.configure_arena(arena)
            for _ in range(2):
                inputs = _staged_inputs(aioclient, a, b)
                result = await client.infer("simple", inputs)
                np.testing.assert_array_equal(result.as_numpy("OUTPUT0"), a + b)
        finally:
            await client.close()

    asyncio.run(main())
    s = arena.stats()
    assert s["leased_bytes"] == 0
    assert s["registrations_issued"] <= 1


def test_coalescing_composes_with_arena(http_server, arena):
    """Stacked (coalesced) requests are promoted by the inner client: the
    joined payload rides a slab, every caller still gets its exact rows."""
    inner = httpclient.InferenceServerClient(http_server.url, concurrency=8)
    inner.configure_arena(arena)
    client = inner.coalescing(window_us=5000, batch_max_rows=16)
    from client_tpu.models.batched import BatchedMatMulModel

    w = BatchedMatMulModel(seed=0)._w_np
    results = {}
    errors = []

    def call(i):
        x = np.full((1, 64), float(i), dtype=np.float32)
        inp = httpclient.InferInput("X", [1, 64], "FP32")
        inp.set_data_from_numpy(x)
        try:
            results[i] = client.infer("batched_matmul", [inp]).as_numpy("Y")
        except Exception as e:  # pragma: no cover - failure path
            errors.append(e)

    threads = [threading.Thread(target=call, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    inner.close()
    assert not errors, errors
    for i, y in results.items():
        x = np.full((1, 64), float(i), dtype=np.float32)
        np.testing.assert_allclose(y, x @ w, rtol=1e-3, atol=1e-3)
    assert arena.stats()["leased_bytes"] == 0


def test_default_arena_via_true(http_server):
    a, b = _simple_pair()
    # the default arena is the process's: a cache that an earlier test file
    # of this worker built on it may still hold entries, so the request is
    # held to leaving what it found
    gc.collect()
    held = default_arena().stats()["leased_bytes"]
    with httpclient.InferenceServerClient(http_server.url) as client:
        client.configure_arena(True)
        assert client.arena() is default_arena()
        result = client.infer("simple", _staged_inputs(httpclient, a, b))
        np.testing.assert_array_equal(result.as_numpy("OUTPUT0"), a + b)
        assert default_arena().stats()["leased_bytes"] == held


# -- tpu family ---------------------------------------------------------------
def test_tpu_family_lease_jax_roundtrip(http_server):
    import jax

    a = ShmArena(default_family="tpu", colocated=True)
    try:
        x = np.arange(16, dtype=np.float32).reshape(1, 16)
        dev = jax.device_put(x)
        dev.block_until_ready()
        lease = a.lease(x.nbytes, family="tpu")
        lease.write_jax(dev)
        # colocated cache hit: the SAME device buffer comes back
        back = lease.as_jax("FP32", [1, 16])
        np.testing.assert_array_equal(np.asarray(back), x)
        # host view flushes the device entry through the window
        np.testing.assert_array_equal(lease.as_numpy("FP32", [1, 16]), x)
        lease.release()
        assert a.stats()["leased_bytes"] == 0
    finally:
        a.close(force=True)


def test_tpu_slab_reuse_never_leaks_stale_device_entries():
    """Review hardening: a slab that held a pinned jax tensor must serve
    fresh host bytes to its NEXT occupant — the release evicts overlapping
    device entries, and direct host writes invalidate them, so a stale
    device entry can never shadow or clobber new contents."""
    import jax

    a = ShmArena(default_family="tpu", colocated=True)
    try:
        x = np.full((1, 16), 7.0, dtype=np.float32)
        l1 = a.lease(x.nbytes, family="tpu")
        l1.write_jax(jax.device_put(x))
        l1.release()
        # the freed slab is reused by a host-side write of different bytes
        y = np.full((1, 16), 3.0, dtype=np.float32)
        l2 = a.lease(y.nbytes, family="tpu")
        assert (l2.region_key, l2.offset) == (l1.region_key, l1.offset)
        l2.write_numpy(y)
        np.testing.assert_array_equal(l2.as_numpy("FP32", [1, 16]), y)
        # overwrite-in-place after a jax write on the SAME lease too
        l2.write_jax(jax.device_put(x))
        l2.write_numpy(y)
        np.testing.assert_array_equal(l2.as_numpy("FP32", [1, 16]), y)
        l2.release()
    finally:
        a.close(force=True)


def test_rebinding_same_lease_is_idempotent(arena):
    """Review hardening: re-binding a lease to the tensor that already
    holds it must not self-release (set_shared_memory drops OTHER leases,
    never the one being bound)."""
    from client_tpu._tensor import InferInput, InferRequestedOutput

    lease = arena.lease(64)
    inp = InferInput("X", [16], "INT32")
    lease.bind_input(inp)
    lease.bind_input(inp)  # idempotent re-bind
    assert not lease.released and inp._arena_lease is lease
    out = InferRequestedOutput("Y")
    olease = arena.lease(64)
    olease.bind_output(out)
    olease.bind_output(out)
    assert not olease.released and out._arena_lease is olease
    inp.release_arena_lease()
    out.release_arena_lease()
    assert arena.stats()["leased_bytes"] == 0


def test_released_lease_refuses_to_bind(http_server, arena):
    """Review hardening: reusing a request object whose lease was released
    raises the typed error at infer time instead of pointing the server at
    a slab that may already back another request."""
    a_np, b_np = _simple_pair()
    with httpclient.InferenceServerClient(http_server.url) as client:
        inputs = _staged_inputs(httpclient, a_np, b_np)
        out0 = arena.request_output("OUTPUT0", a_np.nbytes)
        result = client.infer("simple", inputs, outputs=[out0])
        result.release_arena()
        with pytest.raises(ArenaLeaseReleased):
            client.infer("simple", inputs, outputs=[out0])
        # re-staging the output with a fresh lease works again
        out0.release_arena_lease()
        arena.lease(a_np.nbytes).bind_output(out0)
        result = client.infer("simple", inputs, outputs=[out0])
        np.testing.assert_array_equal(result.as_numpy("OUTPUT0"),
                                      a_np + b_np)
        result.release_arena()


# -- chaos smoke --------------------------------------------------------------
@pytest.mark.arena_smoke
def test_arena_promotion_under_flap_chaos(http_server):
    """The arena data plane x retry resilience under a flapping proxy:
    every request completes (retries re-run the whole bind/settle cycle),
    no slab is double-leased, residency returns to zero, and registrations
    stay amortized (re-issued at most a handful of times after flaps)."""
    proxy = ChaosProxy("127.0.0.1", http_server.port).start()
    proxy.fault = Fault("flap", every=7)
    arena = ShmArena()
    a, b = _simple_pair()
    errors = []
    try:
        client = httpclient.InferenceServerClient(proxy.url, concurrency=8)
        client.configure_resilience(ResiliencePolicy(
            retry=RetryPolicy(max_attempts=6, initial_backoff_s=0.01,
                              max_backoff_s=0.05),
            breaker=CircuitBreaker(min_calls=256),
        ))
        client.configure_arena(arena)

        def worker():
            try:
                for _ in range(20):
                    inputs = _staged_inputs(httpclient, a, b)
                    result = client.infer("simple", inputs)
                    np.testing.assert_array_equal(
                        result.as_numpy("OUTPUT0"), a + b)
            except Exception as e:  # pragma: no cover - failure path
                errors.append(e)

        threads = [threading.Thread(target=worker) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        client.close()
        assert not errors, errors
        s = arena.stats()
        assert s["leased_bytes"] == 0 and s["leased_slabs"] == 0
        assert s["leases"] == s["releases"]
        # the cache kept registrations amortized: 4*20 requests needed at
        # most a few issued RPCs (first use + post-flap re-registers)
        assert s["registrations_issued"] <= 10
        assert s["registrations_cached"] > s["registrations_issued"]
    finally:
        proxy.stop()
        arena.close(force=True)


# -- doctor integration -------------------------------------------------------
def test_doctor_snapshot_reports_arena_section(http_server):
    from client_tpu import doctor

    a = ShmArena()
    try:
        lease = a.lease(4096)
        snap = doctor.collect_snapshot([http_server.url], model="simple")
        rows = snap["shm"]["arena"]
        assert any(r["stats"]["leased_bytes"] == 4096 for r in rows)
        assert "arena_leased_bytes" in snap["shm"]
        # lease predates the probe: baseline includes it, no leak flag
        assert "shm_arena_leak" not in [f["flag"] for f in snap["anomalies"]]
        summary = doctor.render_summary(snap)
        assert "arena" in summary
        lease.release()
    finally:
        a.close(force=True)


def test_doctor_flags_arena_leak():
    """Leased bytes above the pre-probe baseline => shm_arena_leak."""
    from client_tpu.doctor import _anomalies

    snap = {
        "endpoints": [], "endpoint_stats": {}, "slos": [],
        "shm": {"arena_leased_bytes": {"before_probe": 0,
                                       "after_probe": 8192}},
    }
    flags = [f["flag"] for f in _anomalies(snap, 10000.0, 250.0)]
    assert "shm_arena_leak" in flags
    snap["shm"]["arena_leased_bytes"]["after_probe"] = 0
    flags = [f["flag"] for f in _anomalies(snap, 10000.0, 250.0)]
    assert "shm_arena_leak" not in flags


# -- committed artifact invariants -------------------------------------------
def test_bench_arena_artifact_claims():
    """BENCH_ARENA.json is the committed proof for the acceptance criteria:
    steady-state region create/destroy AND registration RPCs per request
    -> 0 under sustained load, p50 no worse than the per-use-site
    baseline's (within noise)."""
    import json
    from pathlib import Path

    path = Path(__file__).resolve().parent.parent / "BENCH_ARENA.json"
    data = json.loads(path.read_text())
    steady = data["arena"]["steady_state"]
    assert steady["regions_created"] == 0
    assert steady["regions_destroyed"] == 0
    assert steady["registration_rpcs"] == 0
    assert steady["requests"] > 0
    base = data["per_use_site"]
    assert base["regions_created_per_request"] > 0.5
    assert base["registration_rpcs_per_request"] > 0.5
    # latency: arena p50 must not regress past baseline + noise floor
    assert (data["arena"]["p50_ms"]
            <= base["p50_ms"] + data["noise_floor_ms"])
