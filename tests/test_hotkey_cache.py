"""Hot-key serving: singleflight + response cache + affinity routing.

Proves the ISSUE acceptance criteria: (a) N concurrent identical infers
collapse onto EXACTLY one wire request and every caller gets a
bit-identical result; a failed leader fans the SAME typed error; (b)
cache hits are zero-copy arena-lease-pinned views, and a trimmed/evicted
entry raises the typed ``ArenaLeaseReleased`` instead of aliased memory;
(c) TTL expiry, stale-while-revalidate, explicit invalidation and
automatic invalidation on ``unload_model`` broadcasts; (d) affinity
routing lands a key on a deterministic home, re-homes deterministically
under ejection (``hotkey_smoke`` chaos: zero errors attributable to
routing through a replica kill/heal cycle) and returns home on recovery;
(e) the sequence-pin GC regression (pins no longer leak when a caller
dies without ``sequence_end``); (f) the zipfian hot-key trace knob is
deterministic, v3-stamped and byte-identical for pre-v3 specs; (g) the
committed BENCH_HOTKEY.json artifact's claims re-validate.
"""

import asyncio
import threading
import time

import numpy as np
import pytest

import client_tpu.http as httpclient
from client_tpu import trace as trace_mod
from client_tpu._base import InferenceServerClientBase
from client_tpu.arena import ArenaLeaseReleased, ShmArena
from client_tpu.cache import (
    AioCachingClient,
    CachedInferResult,
    CachingClient,
    ResponseCache,
    content_key,
)
from client_tpu.models import default_model_zoo
from client_tpu.observe import REQUEST_PHASES, Telemetry
from client_tpu.pool import (
    EndpointPool,
    EndpointState,
    PoolClient,
    SequenceAbandoned,
)
from client_tpu.resilience import ResiliencePolicy
from client_tpu.server import HttpInferenceServer, ServerCore
from client_tpu.testing import ChaosProxy, Fault
from client_tpu.utils import InferenceServerException
from tests.conftest import standing_behind


# -- helpers ------------------------------------------------------------------
def _fp32_input(value, rows=1, cols=8, name="X"):
    arr = np.full((rows, cols), float(value), dtype=np.float32)
    inp = httpclient.InferInput(name, [rows, cols], "FP32")
    inp.set_data_from_numpy(arr)
    return arr, inp


class FakeResult:
    """Server-shaped result: echoes X*2 as Y (FP32)."""

    def __init__(self, inputs):
        arr = np.frombuffer(
            bytes(inputs[0]._get_binary_data()), dtype=np.float32
        ).reshape(inputs[0].shape())
        self._arr = arr * 2.0
        self._response = {
            "model_name": "stub",
            "outputs": [{
                "name": "Y", "datatype": "FP32",
                "shape": list(arr.shape),
                "parameters": {"binary_data_size": int(arr.nbytes)},
            }],
        }

    def get_response(self):
        return self._response

    def get_output(self, name):
        return self._response["outputs"][0] if name == "Y" else None

    def as_numpy(self, name):
        return self._arr if name == "Y" else None


class StubInner(InferenceServerClientBase):
    """Scriptable inner client counting wire-level infers."""

    _FRONTEND = "stub"

    def __init__(self, held=False, fail=None):
        super().__init__()
        self.calls = 0
        # a held stub answers no wire request until the test sets ``release``
        self.release = threading.Event()
        if not held:
            self.release.set()
        self.fail = fail  # optional exception instance to raise
        self.unloaded = []
        self._lock = threading.Lock()

    def infer(self, model_name, inputs, **kwargs):
        with self._lock:
            self.calls += 1
        assert self.release.wait(timeout=60), "the wire request was never released"
        if self.fail is not None:
            raise self.fail
        return FakeResult(inputs)

    def unload_model(self, model_name, **kwargs):
        self.unloaded.append(model_name)

    def load_model(self, model_name, **kwargs):
        pass

    def close(self):
        pass


class AioStubInner(InferenceServerClientBase):
    _FRONTEND = "stub_aio"
    _BATCH_AIO = True

    def __init__(self):
        super().__init__()
        self.calls = 0
        self.release = None  # an asyncio.Event holds the wire request

    async def infer(self, model_name, inputs, **kwargs):
        self.calls += 1
        if self.release is not None:
            await asyncio.wait_for(self.release.wait(), timeout=60)
        return FakeResult(inputs)

    async def close(self):
        pass


@pytest.fixture()
def arena():
    a = ShmArena(name_prefix="hotkey_test")
    yield a
    a.close(force=True)


def _run_threads(n, fn):
    errors = []

    def wrapped(i):
        try:
            fn(i)
        except Exception as e:  # pragma: no cover - surfaced by assert
            errors.append((i, e))

    threads = [threading.Thread(target=wrapped, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    return errors


def _run_collapsed(client, inner, n, fn):
    """``_run_threads`` over a held stub: the leader's wire request is let go
    once the other ``n - 1`` callers stand behind it."""
    errors = []
    runner = threading.Thread(target=lambda: errors.extend(_run_threads(n, fn)))
    runner.start()
    standing_behind(client, n - 1)
    inner.release.set()
    runner.join(timeout=60)
    return errors


# -- content key --------------------------------------------------------------
def test_content_key_algebra():
    _, a = _fp32_input(1.0)
    _, b = _fp32_input(1.0)
    _, c = _fp32_input(2.0)
    assert content_key("m", [a]) == content_key("m", [b])
    assert content_key("m", [a]) != content_key("m", [c])
    assert content_key("m", [a]) != content_key("other", [b])
    # parameters are semantic: different priority => different key
    assert content_key("m", [a], {"priority": 1}) != \
        content_key("m", [b], {"priority": 2})
    # request_id is NOT semantic
    assert content_key("m", [a], {"request_id": "x"}) == \
        content_key("m", [b], {"request_id": "y"})
    # affinity_key is a routing hint, not semantics: sessions sending the
    # same payload share one key (else the cache fragments per session)
    assert content_key("m", [a], {"affinity_key": "s1"}) == \
        content_key("m", [b], {"affinity_key": "s2"})
    # the exclusion matrix: sequences / resilience overrides / shm bypass
    assert content_key("m", [a], {"sequence_id": 3}) is None
    assert content_key("m", [a], {"resilience": False}) is None
    shm = httpclient.InferInput("X", [1, 8], "FP32")
    shm.set_shared_memory("region", 32)
    assert content_key("m", [shm]) is None


def test_cache_lookup_phase_registered():
    assert "cache_lookup" in REQUEST_PHASES


# -- singleflight -------------------------------------------------------------
def test_singleflight_collapses_to_one_wire_request(arena):
    inner = StubInner(held=True)
    client = CachingClient(inner, cache=ResponseCache(ttl_s=30.0,
                                                      arena=arena))
    results = [None] * 16

    def call(i):
        _, inp = _fp32_input(7.0)
        results[i] = client.infer("m", [inp])

    assert _run_collapsed(client, inner, 16, call) == []
    assert inner.calls == 1, f"expected 1 wire request, got {inner.calls}"
    ref = results[0].as_numpy("Y")
    for r in results[1:]:
        np.testing.assert_array_equal(r.as_numpy("Y"), ref)
    stats = client.cache_stats()
    assert stats["wire_requests"] == 1
    assert stats["singleflight_collapsed"] == 15
    assert stats["collapse_ratio"] > 0.9


def test_singleflight_without_cache(arena):
    inner = StubInner(held=True)
    client = CachingClient(inner, cache=None, singleflight=True)
    results = [None] * 8

    def call(i):
        _, inp = _fp32_input(3.0)
        results[i] = client.infer("m", [inp])

    assert _run_collapsed(client, inner, 8, call) == []
    assert inner.calls == 1
    # no cache: a later identical call is a fresh wire request
    _, inp = _fp32_input(3.0)
    client.infer("m", [inp])
    assert inner.calls == 2


def test_singleflight_leader_failure_fans_same_typed_error(arena):
    boom = InferenceServerException("server exploded", status="500")
    inner = StubInner(held=True, fail=boom)
    client = CachingClient(inner, cache=ResponseCache(ttl_s=30.0,
                                                      arena=arena))
    caught = [None] * 8

    def call(i):
        _, inp = _fp32_input(9.0)
        try:
            client.infer("m", [inp])
        except InferenceServerException as e:
            caught[i] = e

    assert _run_collapsed(client, inner, 8, call) == []
    assert inner.calls == 1
    # every caller got the SAME typed error object
    assert all(e is boom for e in caught), caught
    # errors are never cached: the next call hits the wire again
    inner.fail = None
    _, inp = _fp32_input(9.0)
    r = client.infer("m", [inp])
    assert inner.calls == 2
    assert r.as_numpy("Y") is not None


def test_singleflight_aio_collapses():
    async def main():
        inner = AioStubInner()
        inner.release = asyncio.Event()
        arena = ShmArena(name_prefix="hotkey_aio")
        try:
            client = AioCachingClient(
                inner, cache=ResponseCache(ttl_s=30.0, arena=arena))

            async def call():
                _, inp = _fp32_input(4.0)
                return await client.infer("m", [inp])

            async def release():
                # the last task of the gather: every turn of the loop it
                # gives up lets each caller run to where it waits, so after
                # a hundred the eleven stand behind the leader whatever the
                # clock did
                for _ in range(100):
                    await asyncio.sleep(0)
                assert inner.calls == 1
                inner.release.set()

            *results, _ = await asyncio.wait_for(asyncio.gather(
                *[call() for _ in range(12)], release()), timeout=60)
            assert inner.calls == 1
            ref = results[0].as_numpy("Y")
            for r in results[1:]:
                np.testing.assert_array_equal(r.as_numpy("Y"), ref)
            # cache hit afterwards
            r = await call()
            assert r.cached and inner.calls == 1
            await client.close()
        finally:
            arena.close(force=True)

    asyncio.run(main())


# -- response cache -----------------------------------------------------------
def test_cache_hit_is_zero_copy_lease_view(arena):
    inner = StubInner()
    client = CachingClient(inner, cache=ResponseCache(ttl_s=30.0,
                                                      arena=arena))
    _, inp = _fp32_input(5.0)
    miss = client.infer("m", [inp])
    hit = client.infer("m", [inp])
    assert inner.calls == 1
    assert isinstance(hit, CachedInferResult) and hit.cached
    arr = hit.as_numpy("Y")
    np.testing.assert_array_equal(arr, miss.as_numpy("Y"))
    # zero-copy: the view is backed by the arena mapping, and a second
    # view shares the same memory (no per-hit copies)
    arr2 = hit.as_numpy("Y")
    assert np.shares_memory(arr, arr2)
    assert arr.base is not None
    # get_output/get_response quack like InferResult, sans wire params
    out = hit.get_output("Y")
    assert out["datatype"] == "FP32" and out["shape"] == [1, 8]
    assert "binary_data_size" not in (out.get("parameters") or {})


def test_release_without_retain_cannot_break_the_entry(arena):
    """A caller's release() drops only ITS retains: bare release is a
    no-op, and a retained view survives eviction until released."""
    inner = StubInner()
    client = CachingClient(inner, cache=ResponseCache(ttl_s=30.0,
                                                      arena=arena))
    _, inp = _fp32_input(4.0)
    client.infer("m", [inp])
    hit = client.infer("m", [inp])
    hit.release()  # no retain held: must NOT release the cache's lease
    hit2 = client.infer("m", [inp])
    assert hit2.cached and hit2.as_numpy("Y") is not None
    assert inner.calls == 1  # entry stayed servable
    # pin past eviction: retained view outlives invalidate()
    pinned = client.infer("m", [inp]).retain()
    before = pinned.as_numpy("Y").copy()
    client.invalidate(model="m")
    np.testing.assert_array_equal(pinned.as_numpy("Y"), before)
    pinned.release()
    with pytest.raises(ArenaLeaseReleased):
        pinned.as_numpy("Y")


def test_evicted_entry_raises_typed_released_error(arena):
    inner = StubInner()
    client = CachingClient(inner, cache=ResponseCache(ttl_s=30.0,
                                                      arena=arena))
    _, inp = _fp32_input(1.0)
    client.infer("m", [inp])
    hit = client.infer("m", [inp])
    assert client.invalidate(model="m") == 1
    with pytest.raises(ArenaLeaseReleased):
        hit.as_numpy("Y")


def test_cache_capacity_eviction_lru(arena):
    cache = ResponseCache(ttl_s=30.0, max_bytes=3 * 4096, arena=arena)
    inner = StubInner()
    client = CachingClient(inner, cache=cache, singleflight=False)
    held = {}
    for v in range(6):  # each entry = one 4096B slab; watermark fits 3
        _, inp = _fp32_input(float(v))
        client.infer("m", [inp])
        _, inp = _fp32_input(float(v))
        held[v] = client.infer("m", [inp])  # hit: a cached view
    stats = cache.stats()
    assert stats["entries"] <= 3
    assert stats["evictions"]["capacity"] >= 3
    assert stats["bytes_resident"] <= 3 * 4096
    # the LRU victims' views now raise typed; the survivors still serve
    live = dead = 0
    for v, result in held.items():
        try:
            result.as_numpy("Y")
            live += 1
        except ArenaLeaseReleased:
            dead += 1
    assert live >= 1 and dead >= 3, (live, dead)


def test_cache_ttl_expiry_injected_clock(arena):
    now = [100.0]
    cache = ResponseCache(ttl_s=1.0, arena=arena, clock=lambda: now[0])
    inner = StubInner()
    client = CachingClient(inner, cache=cache, singleflight=False)
    _, inp = _fp32_input(2.0)
    client.infer("m", [inp])
    _, inp = _fp32_input(2.0)
    assert client.infer("m", [inp]).cached
    assert inner.calls == 1
    now[0] += 1.5  # past TTL (no stale window): miss + ttl eviction
    _, inp = _fp32_input(2.0)
    r = client.infer("m", [inp])
    assert inner.calls == 2
    assert cache.stats()["evictions"]["ttl"] == 1
    assert isinstance(r, CachedInferResult)  # re-inserted


def test_stale_while_revalidate(arena):
    now = [0.0]
    cache = ResponseCache(ttl_s=1.0, stale_while_revalidate_s=5.0,
                          arena=arena, clock=lambda: now[0])
    inner = StubInner()
    client = CachingClient(inner, cache=cache)
    _, inp = _fp32_input(6.0)
    client.infer("m", [inp])
    assert inner.calls == 1
    now[0] = 2.0  # expired but inside the staleness window
    _, inp = _fp32_input(6.0)
    stale = client.infer("m", [inp])
    assert stale.cached and stale.stale  # typed opt-in: marked stale
    # ONE background revalidation repopulates the entry
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and inner.calls < 2:
        time.sleep(0.01)
    assert inner.calls == 2
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        _, inp = _fp32_input(6.0)
        fresh = client.infer("m", [inp])
        if fresh.cached and not fresh.stale:
            break
        time.sleep(0.01)
    assert fresh.cached and not fresh.stale
    assert client.cache_stats()["revalidations"] == 1
    # past the staleness window: a plain miss
    now[0] = 20.0
    _, inp = _fp32_input(6.0)
    client.infer("m", [inp])
    assert inner.calls == 3


def test_invalidation_on_unload_model_broadcast(arena):
    inner = StubInner()
    client = CachingClient(inner, cache=ResponseCache(ttl_s=30.0,
                                                      arena=arena))
    _, inp = _fp32_input(8.0)
    client.infer("m", [inp])
    _, other = _fp32_input(8.0, name="X")
    client.infer("m2", [other])
    assert client.cache_stats()["entries"] == 2
    client.unload_model("m")
    assert inner.unloaded == ["m"]
    assert client.cache_stats()["entries"] == 1  # only m was dropped
    _, inp = _fp32_input(8.0)
    client.infer("m", [inp])
    assert inner.calls == 3  # m's entry was gone; m2's survives


def test_cached_views_survive_arena_trim_pressure():
    """Leases pin their regions: watermark trims destroy only FULLY-free
    regions, so cached entries stay valid under allocation churn."""
    arena = ShmArena(name_prefix="hotkey_trim",
                     high_watermark_bytes=64 * 1024,
                     low_watermark_bytes=16 * 1024)
    try:
        inner = StubInner()
        client = CachingClient(
            inner, cache=ResponseCache(ttl_s=30.0, arena=arena))
        _, inp = _fp32_input(3.0)
        client.infer("m", [inp])
        hit = client.infer("m", [inp])
        before = hit.as_numpy("Y").copy()
        # churn far past the high watermark: repeated lease/release forces
        # trim passes while the cache entry's lease is live
        for _ in range(40):
            lease = arena.lease(8 * 1024)
            lease.write(b"x" * 8 * 1024)
            lease.release()
        time.sleep(0.2)  # async trim thread settles
        np.testing.assert_array_equal(hit.as_numpy("Y"), before)
    finally:
        arena.close(force=True)


def test_uncacheable_outputs_fall_through(arena):
    """A result whose output bytes the client can't decode (as_numpy None)
    is served but never cached."""

    class OpaqueResult(FakeResult):
        def as_numpy(self, name):
            return None

    class OpaqueInner(StubInner):
        def infer(self, model_name, inputs, **kwargs):
            self.calls += 1
            return OpaqueResult(inputs)

    inner = OpaqueInner()
    client = CachingClient(inner, cache=ResponseCache(ttl_s=30.0,
                                                      arena=arena))
    _, inp = _fp32_input(1.0)
    r = client.infer("m", [inp])
    assert isinstance(r, OpaqueResult)
    _, inp = _fp32_input(1.0)
    client.infer("m", [inp])
    assert inner.calls == 2  # nothing was cached
    assert client.cache_stats()["cache"]["uncacheable"] == 2


def test_cache_telemetry_span_and_metrics(arena):
    tel = Telemetry(sample="always")
    inner = StubInner()
    client = CachingClient(
        inner, cache=ResponseCache(ttl_s=30.0, arena=arena), telemetry=tel)
    _, inp = _fp32_input(2.0)
    client.infer("m", [inp])
    _, inp = _fp32_input(2.0)
    client.infer("m", [inp])
    traces = tel.recent_traces()
    cache_spans = [t for t in traces if t["frontend"] == "stub+cache"]
    assert len(cache_spans) == 2
    for span in cache_spans:
        assert any(p["name"] == "cache_lookup" for p in span["phases"])
    text = tel.registry.prometheus_text()
    assert 'client_tpu_cache_requests_total{model="m",outcome="hit"} 1' \
        in text
    assert 'client_tpu_cache_requests_total{model="m",outcome="miss"} 1' \
        in text
    assert "client_tpu_cache_bytes_resident" in text
    assert "client_tpu_cache_entries 1" in text


# -- live-server composition --------------------------------------------------
@pytest.fixture(scope="module")
def http_server():
    server = HttpInferenceServer(ServerCore(default_model_zoo())).start()
    yield server
    server.close()


def test_caching_hook_on_frontend_live(http_server):
    client = httpclient.InferenceServerClient(http_server.url).caching(
        ttl_s=30.0)
    assert isinstance(client, CachingClient)
    x = np.arange(64, dtype=np.float32).reshape(1, 64)
    inp = httpclient.InferInput("X", [1, 64], "FP32").set_data_from_numpy(x)
    miss = client.infer("batched_matmul", [inp])
    hit = client.infer("batched_matmul", [inp])
    assert hit.cached
    np.testing.assert_array_equal(hit.as_numpy("Y"), miss.as_numpy("Y"))
    client.close()


def test_caching_composes_with_coalescing_live(http_server):
    """cache(batch(client)): a collapsed group's one miss may ride a
    batch; hits never reach the dispatcher."""
    inner = httpclient.InferenceServerClient(http_server.url)
    client = inner.coalescing(window_us=5000, batch_max_rows=16).caching(
        ttl_s=30.0)
    results = [None] * 8

    def call(i):
        x = np.full((1, 64), float(i % 2), dtype=np.float32)
        inp = httpclient.InferInput(
            "X", [1, 64], "FP32").set_data_from_numpy(x)
        results[i] = client.infer("batched_matmul", [inp])

    assert _run_threads(8, call) == []
    stats = client.cache_stats()
    # two distinct keys -> exactly two wire requests, 6 callers collapsed
    # or served from cache
    assert stats["wire_requests"] == 2, stats
    for i in range(8):
        expected = results[i % 2].as_numpy("Y")
        np.testing.assert_array_equal(results[i].as_numpy("Y"), expected)
    client.close()


# -- affinity routing ---------------------------------------------------------
def _affinity_pool(n=4, **kwargs):
    eps = [EndpointState(f"10.0.0.{i}:8000", object(), ResiliencePolicy())
           for i in range(n)]
    return EndpointPool(eps, routing="affinity", **kwargs), eps


def test_affinity_same_key_same_home():
    pool, eps = _affinity_pool()
    home = pool.select(affinity_key="user-1")
    assert all(pool.select(affinity_key="user-1") is home
               for _ in range(50))
    # keys spread across the fleet
    homes = {pool.select(affinity_key=f"k{i}").url for i in range(64)}
    assert len(homes) == len(eps)


def test_affinity_rehomes_deterministically_and_returns():
    pool, eps = _affinity_pool()
    home = pool.select(affinity_key="sess")
    home.ejected = True
    home.ejected_until = time.monotonic() + 100
    alt = pool.select(affinity_key="sess")
    assert alt is not home
    assert all(pool.select(affinity_key="sess") is alt for _ in range(30))
    # an independent pool over the same urls re-homes to the SAME
    # alternate — deterministic across clients, not just within one
    pool2, eps2 = _affinity_pool()
    eps2[eps.index(home)].ejected = True
    eps2[eps.index(home)].ejected_until = time.monotonic() + 100
    assert pool2.select(affinity_key="sess").url == alt.url
    # heal: the key returns home
    home.ejected = False
    assert pool.select(affinity_key="sess") is home
    snap = pool.snapshot()
    # counters are DISJOINT: the alt's picks were all re-homes, never
    # double-counted as routed; routed+rehomed+spilled = total picks
    assert snap[alt.url]["affinity"]["rehomed"] == 31
    assert snap[alt.url]["affinity"]["routed"] == 0
    assert snap[home.url]["affinity"]["routed"] == 2
    total = sum(s["affinity"]["routed"] + s["affinity"]["rehomed"]
                + s["affinity"]["spilled"] for s in snap.values())
    assert total == 33  # 2 at home + 31 re-homed = every pick, once


def test_affinity_bounded_load_spills_then_recovers():
    pool, eps = _affinity_pool(affinity_bound=1.5)
    home = pool.select(affinity_key="hot")
    # drown the home: bound = 1.5 * (total+1)/n — 40 outstanding on one
    # endpoint of 4 is far past it
    home.outstanding = 40
    spilled = pool.select(affinity_key="hot")
    assert spilled is not home
    assert pool.snapshot()[spilled.url]["affinity"]["spilled"] >= 1
    home.outstanding = 0
    assert pool.select(affinity_key="hot") is home


def test_affinity_keyless_falls_back_least_outstanding():
    pool, eps = _affinity_pool()
    eps[2].outstanding = 0
    for other in (0, 1, 3):
        eps[other].outstanding = 5
    assert pool.select() is eps[2]


@pytest.mark.hotkey_smoke
def test_affinity_chaos_kill_heal_zero_routing_errors():
    """A replica kill/heal cycle under affinity routing: every keyed
    request succeeds (failover re-homes deterministically, never queues
    on the dead replica), and the key returns home after heal."""
    cores = [ServerCore(default_model_zoo()) for _ in range(3)]
    servers = [HttpInferenceServer(c).start() for c in cores]
    proxies = [ChaosProxy("127.0.0.1", s.port).start() for s in servers]
    client = PoolClient(
        [p.url for p in proxies], protocol="http", routing="affinity",
        health_interval_s=0.05, probe_timeout_s=0.5,
        eject_after=2, base_ejection_s=0.3,
    )
    x = np.ones((1, 64), dtype=np.float32)
    inp = httpclient.InferInput("X", [1, 64], "FP32").set_data_from_numpy(x)
    keys = [f"sess-{i}" for i in range(12)]
    try:
        # find the proxy homing the first key, then kill exactly it
        client.infer("batched_matmul", [inp], affinity_key=keys[0],
                     client_timeout=10.0)
        stats = client.endpoint_stats()
        victim_url = max(
            stats, key=lambda u: stats[u]["affinity"]["routed"])
        victim = [p for p in proxies if p.url == victim_url][0]
        errors = []
        rehomed_seen = False
        for i in range(60):
            if i == 15:
                victim.fault = Fault("reset", after_bytes=0)
                victim.reset_active()
            if i == 40:
                victim.heal()
            for key in keys:
                try:
                    r = client.infer("batched_matmul", [inp],
                                     affinity_key=key, client_timeout=10.0)
                    assert r.as_numpy("Y") is not None
                except Exception as e:  # pragma: no cover - assert target
                    errors.append(f"iter {i} key {key}: {e}")
            time.sleep(0.01)
        assert errors == [], errors[:5]
        stats = client.endpoint_stats()
        rehomed_seen = any(
            s["affinity"]["rehomed"] > 0 for s in stats.values())
        assert rehomed_seen, stats
        # after heal the victim serves keyed traffic again
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if client.endpoint_stats()[victim_url]["healthy"]:
                break
            time.sleep(0.05)
        before = client.endpoint_stats()[victim_url]["affinity"]["routed"]
        for _ in range(3):
            for key in keys:
                client.infer("batched_matmul", [inp], affinity_key=key,
                             client_timeout=10.0)
        after = client.endpoint_stats()[victim_url]["affinity"]["routed"]
        assert after > before, "healed home never took its keys back"
    finally:
        client.close()
        for p in proxies:
            p.stop()
        for s in servers:
            s.stop()


# -- sequence pin GC (satellite bugfix) ---------------------------------------
def test_seq_pin_gc_regression():
    """Pins leaked forever when a caller died without sequence_end; the
    idle GC sweeps them and fires the existing SequenceAbandoned event."""

    class Stub:
        _FRONTEND = "stub"

        def __init__(self, url):
            self._url = url

        def configure_resilience(self, p):
            return self

        def close(self):
            pass

    events = []
    client = PoolClient(["a:1", "b:1"], client_factory=Stub,
                        health_interval_s=None, on_event=events.append,
                        seq_pin_idle_s=0.05)
    try:
        for sid in (11, 12, 13):
            client._seq_endpoint(sid)
            client._seq_mark_established(sid)
        assert len(client._seq_pins) == 3
        time.sleep(0.12)
        # an unrelated sequence triggers the sweep (the prober cadence
        # would too); its own fresh pin must survive
        client._seq_endpoint(99)
        assert set(client._seq_pins) == {99}
        assert client._seq_established == set()
        assert set(client._seq_last_used) == {99}
        abandoned = [e for e in events if isinstance(e, SequenceAbandoned)]
        assert sorted(e.sequence_id for e in abandoned) == [11, 12, 13]
        assert all(e.cause.status() == "SEQUENCE_PIN_EXPIRED"
                   for e in abandoned)
        # an ACTIVE sequence is never swept: recent use refreshes it
        time.sleep(0.06)
        client._seq_endpoint(99)  # refresh
        time.sleep(0.03)
        client._seq_endpoint(100)
        assert 99 in client._seq_pins
    finally:
        client.close()


# -- zipfian hot-key trace (satellite) ----------------------------------------
def test_hot_key_trace_deterministic_and_stamped():
    spec = ("mixed:duration_s=2,rate=80,stream_fraction=0.2,"
            "seq_fraction=0.1,hot_key_universe=16,hot_key_alpha=1.1")
    a = trace_mod.generate(spec, seed=9)
    b = trace_mod.generate(spec, seed=9)
    assert trace_mod.dumps_trace(a.records, a.header) == \
        trace_mod.dumps_trace(b.records, b.header)
    keyed = [r for r in a.records if r.content_key is not None]
    assert keyed and all(r.kind in ("unary", "generate_stream")
                         for r in keyed)
    assert all(r.to_obj()["v"] == 3 for r in keyed)
    # sequences carry no key (they have their own group affinity)
    assert all(r.content_key is None for r in a.records
               if r.kind == "sequence")
    # same key => identical stream sizing
    sizing = {}
    for r in a.records:
        if r.kind == "generate_stream" and r.content_key is not None:
            prev = sizing.setdefault(
                r.content_key, (r.prompt_tokens, r.output_tokens))
            assert prev == (r.prompt_tokens, r.output_tokens)
    # zipf head: the hottest key owns well over the uniform share
    from collections import Counter

    hottest = Counter(r.content_key for r in keyed).most_common(1)[0][1]
    assert hottest > 2 * len(keyed) / 16


def test_hot_key_knob_off_is_byte_identical():
    base = "mixed:duration_s=2,rate=60,stream_fraction=0.2,seq_fraction=0.1"
    a = trace_mod.generate(base, seed=5)
    b = trace_mod.generate(base + ",hot_key_universe=0", seed=5)
    assert trace_mod.dumps_trace(a.records) == trace_mod.dumps_trace(b.records)
    assert all(r.content_key is None for r in a.records)


def test_hot_key_records_round_trip_and_forward_compat():
    recs = trace_mod.heavy_tail(seed=1, duration_s=1.0, rate=30,
                                hot_key_universe=8)
    text = trace_mod.dumps_trace(recs)
    loaded = trace_mod.loads_trace(text)
    assert [r.content_key for r in loaded.records] == \
        [r.content_key for r in recs]
    # a record from a NEWER format than this loader understands is
    # skipped, counted, never fatal (version-relative so format bumps
    # cannot silently turn the probe record into a loadable one)
    future_v = trace_mod.TRACE_VERSION + 1
    newer = text + ('{"at_s":0.5,"content_key":1,"kind":"unary",'
                    '"model":"m","dtypes":{"X":"FP32"},"shapes":{"X":[1]},'
                    '"type":"request","v":%d}\n' % future_v)
    l2 = trace_mod.loads_trace(newer)
    assert l2.skipped == 1 and len(l2.records) == len(recs)


def test_replay_keyed_payloads_byte_identical(http_server):
    """Same content_key => the replayer stages byte-identical inputs
    (the identity the cache collapses on); different keys differ."""
    from client_tpu.perf import PerfRunner, _ReplayResources

    runner = PerfRunner(http_server.url, "http", "batched_matmul",
                        shape_overrides={"X": [1, 64]})
    recs = [
        trace_mod.TraceRecord(at_s=0.0, kind="unary", model="batched_matmul",
                              shapes={"X": [1, 64]}, dtypes={"X": "FP32"},
                              content_key=k)
        for k in (3, 3, 4)
    ]
    resources = _ReplayResources(runner, recs)
    a = resources.inputs_for(recs[0])[0]._get_binary_data()
    b = resources.inputs_for(recs[1])[0]._get_binary_data()
    c = resources.inputs_for(recs[2])[0]._get_binary_data()
    assert bytes(a) == bytes(b)
    assert bytes(a) != bytes(c)
    # a fresh resources object reproduces the same bytes (pure function
    # of (seed, key), not of record order)
    resources2 = _ReplayResources(runner, [recs[2], recs[0]])
    assert bytes(resources2.inputs_for(recs[0])[0]._get_binary_data()) == \
        bytes(a)
    runner.close()


@pytest.mark.hotkey_smoke
def test_replay_cached_arm_collapses_wire_requests(http_server):
    """The proof workload e2e: a zipfian trace replayed through
    cache+singleflight issues measurably fewer wire requests than
    logical requests, zero errors."""
    from client_tpu.perf import PerfRunner

    tr = trace_mod.generate(
        "mixed:duration_s=1.5,rate=100,stream_fraction=0,seq_fraction=0,"
        "unary_model=batched_matmul,hot_key_universe=12,hot_key_alpha=1.1",
        seed=17)
    runner = PerfRunner(http_server.url, "http", "batched_matmul",
                        shape_overrides={"X": [1, 64]},
                        cache=True, singleflight=True)
    try:
        row = runner.run_trace(tr, speed=1.0, replay_workers=12,
                               slos=["error_rate<1%"])
        assert row["errors"] == 0
        cc = row["client_cache"]
        assert cc["wire_requests"] < cc["logical_requests"] / 2, cc
        assert cc["hit_rate"] > 0.3, cc
        assert cc["bytes_resident"] > 0
        assert row["slo_ok"]
    finally:
        runner.close()


# -- doctor -------------------------------------------------------------------
def test_doctor_cache_section_and_thrash_flag(arena):
    from client_tpu.doctor import _anomalies, _cache_status

    inner = StubInner()
    cache = ResponseCache(ttl_s=30.0, max_bytes=2 * 4096, arena=arena)
    client = CachingClient(inner, cache=cache, singleflight=False)
    # thrash: a working set far over max_bytes, near-zero hit rate
    for v in range(60):
        _, inp = _fp32_input(float(v))
        client.infer("m", [inp])
    rows = _cache_status()
    assert any(r.get("evictions", {}).get("capacity", 0) > 0 for r in rows)
    snap = {"endpoints": [], "endpoint_stats": {}, "slos": [],
            "cache": [cache.stats()], "shm": {}}
    flags = _anomalies(snap, churn_threshold_ops_s=0.0, skew_warn_ms=250.0)
    assert any(f["flag"] == "cache_thrash" for f in flags), flags


def test_doctor_affinity_skew_flag():
    from client_tpu.doctor import _anomalies

    stats = {
        "a:1": {"affinity": {"routed": 90, "rehomed": 0, "spilled": 0,
                             "keys": 30}},
        "b:1": {"affinity": {"routed": 5, "rehomed": 0, "spilled": 0,
                             "keys": 2}},
        "c:1": {"affinity": {"routed": 5, "rehomed": 0, "spilled": 0,
                             "keys": 2}},
    }
    snap = {"endpoints": [], "endpoint_stats": stats, "slos": [],
            "cache": [], "shm": {}}
    flags = _anomalies(snap, churn_threshold_ops_s=0.0, skew_warn_ms=250.0)
    skew = [f for f in flags if f["flag"] == "affinity_skew"]
    assert skew and skew[0]["url"] == "a:1", flags
    # a balanced spread never flags
    for s in stats.values():
        s["affinity"]["keys"] = 10
    flags = _anomalies(snap, churn_threshold_ops_s=0.0, skew_warn_ms=250.0)
    assert not any(f["flag"] == "affinity_skew" for f in flags)


# -- committed artifact -------------------------------------------------------
def test_bench_hotkey_artifact_claims():
    """The committed BENCH_HOTKEY.json must re-validate under its own
    --check invariants (collapse happened, >=2x win at equal SLOs,
    miss-path overhead inside the noise floor)."""
    import json
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parent.parent
    artifact = root / "BENCH_HOTKEY.json"
    assert artifact.exists(), "BENCH_HOTKEY.json not committed"
    doc = json.loads(artifact.read_text())
    assert doc["arms"]["cached"]["client_cache"]["wire_requests"] < \
        doc["arms"]["cached"]["client_cache"]["logical_requests"]
    proc = subprocess.run(
        [sys.executable, str(root / "tools" / "bench_hotkey.py"),
         "--check", "--output", str(artifact)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
