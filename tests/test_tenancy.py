"""Multi-tenant QoS: weighted-fair admission, quotas, SLOs and isolation.

Proves the ISSUE acceptance criteria: (a) per-tenant token-bucket quotas
shed with the typed ``over_quota`` reason and an HONEST ``retry_after_s``
(the bucket's refill eta) — a policy denial even on an idle controller,
never retried, never a breaker outcome, never a federation spill signal;
(b) the per-lane waiter stacks drain weighted-fair across tenants (a
single tenant keeps the exact legacy LIFO order; async admit/cancel
returns the slot); (c) the tenant is folded into the shared
``batch.plan_request`` key, so cache, singleflight and coalescing all
partition by tenant while tenantless callers keep byte-identical keys,
and the response cache's byte budget partitions per tenant (one tenant's
churn never evicts another's hot set); (d) per-tenant SLO burn windows,
the doctor's ``noisy_neighbor`` anomaly NAMES the adversarial tenant,
and telemetry exports per-tenant gauges; (e) trace format v4 stamps
``tenant`` per record (older loaders skip-and-count exactly those), the
``multi_tenant`` generator is deterministic and its compliant arrivals
are invariant under adding an adversary — the property that makes the
committed BENCH_TENANCY.json an honest A/B, whose claims re-validate
here and live (tenancy_smoke marker).
"""

import asyncio
import json
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import client_tpu.http as httpclient
from client_tpu import trace as trace_mod
from client_tpu._base import InferenceServerClientBase
from client_tpu.admission import (
    AdaptiveLimiter,
    AdmissionController,
    AdmissionRejected,
    LANE_DEFAULT,
    SHED_OVER_QUOTA,
    SHED_QUEUE_TIMEOUT,
    SPILL_REASONS,
    is_spill_signal,
)
from client_tpu.arena import ShmArena
from client_tpu.batch import plan_request
from client_tpu.cache import CachingClient, ResponseCache, content_key
from client_tpu.observe import Telemetry
from client_tpu.resilience import (
    SHED,
    CircuitBreaker,
    ResiliencePolicy,
    RetryPolicy,
    classify_fault,
)
from client_tpu.tenancy import (
    DEFAULT_TENANT_LABEL,
    TenancyPolicy,
    TenantSpec,
    parse_tenancy_spec,
)
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

    def __init__(self, held=False):
        super().__init__()
        self.calls = 0
        # a held stub answers no wire request until the test sets ``release``
        self.release = threading.Event()
        if not held:
            self.release.set()
        self._lock = threading.Lock()

    def infer(self, model_name, inputs, **kwargs):
        with self._lock:
            self.calls += 1
        assert self.release.wait(timeout=60), "the wire request was never released"
        return FakeResult(inputs)

    def close(self):
        pass


@pytest.fixture()
def arena():
    a = ShmArena(name_prefix="tenancy_test")
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


# -- spec parsing & validation ------------------------------------------------
def test_tenant_spec_validation():
    with pytest.raises(ValueError):
        TenantSpec(DEFAULT_TENANT_LABEL)  # reserved for tenantless traffic
    with pytest.raises(ValueError):
        TenantSpec("a", weight=0.0)
    with pytest.raises(ValueError):
        TenantSpec("a", rate=-1.0)
    with pytest.raises(ValueError):
        TenantSpec("a", burst=4.0)  # burst without rate is meaningless
    with pytest.raises(ValueError):
        TenantSpec("a", rate=10.0, burst=0.5)
    with pytest.raises(ValueError):
        TenantSpec("a", slo_objective=1.0)
    with pytest.raises(ValueError):
        TenantSpec("a", slo_ms=0.0)
    # default burst: one full second of rate, floored at a single token
    assert TenantSpec("a", rate=0.5).burst == 1.0
    assert TenantSpec("a", rate=40.0).burst == 40.0
    assert TenantSpec("a").burst is None  # unmetered


def test_parse_tenancy_spec_surface():
    policy = parse_tenancy_spec(
        "a,w=2,r=50,b=10,slo_ms=250,slo_objective=0.95;b")
    assert policy.weight("a") == 2.0
    spec = policy.spec("a")
    assert spec.rate == 50.0 and spec.burst == 10.0
    assert spec.slo_ms == 250.0 and spec.slo_objective == 0.95
    assert policy.spec("b").rate is None  # unmetered, weight 1
    assert policy.weight("b") == 1.0
    for bad in ("", "a,bogus=1", "a,weight", ",rate=5", "a;a"):
        with pytest.raises(ValueError):
            parse_tenancy_spec(bad)


def test_undeclared_tenant_rides_default_template():
    policy = parse_tenancy_spec("a,rate=1,burst=1")
    # an undeclared tenant is auto-registered from the default template:
    # unmetered, weight 1 — admitted like tenantless traffic, separately
    # accounted
    ok, hint = policy.try_take("stranger")
    assert ok and hint is None
    assert policy.weight("stranger") == 1.0
    assert "stranger" in policy.tenants()


# -- token-bucket quotas ------------------------------------------------------
def test_quota_retry_after_is_the_refill_eta():
    now = [100.0]
    policy = parse_tenancy_spec("a,rate=2,burst=1", clock=lambda: now[0])
    ok, hint = policy.try_take("a")
    assert ok and hint is None  # the burst token
    ok, hint = policy.try_take("a")
    assert not ok
    assert hint == pytest.approx(0.5)  # one whole token at 2/s
    now[0] += 0.25  # half a token refilled
    ok, hint = policy.try_take("a")
    assert not ok
    assert hint == pytest.approx(0.25)
    now[0] += 0.25
    ok, hint = policy.try_take("a")
    assert ok  # the hint was honest: exactly when a token exists again


def test_over_quota_sheds_on_an_idle_controller():
    """A quota is policy, not a load response: the denial fires with every
    admission slot free, typed and attributed, with the refill eta in both
    the field and the message (what shed rows surface)."""
    now = [0.0]
    ctrl = AdmissionController(tenancy="a,rate=1,burst=1",
                               clock=lambda: now[0])
    tok = ctrl.acquire(tenant="a")
    tok.release(0.01)
    assert ctrl.inflight == 0  # idle again
    with pytest.raises(AdmissionRejected) as ei:
        ctrl.acquire(tenant="a")
    exc = ei.value
    assert exc.reason == SHED_OVER_QUOTA
    assert exc.tenant == "a"
    assert exc.retry_after_s == pytest.approx(1.0)
    assert "over_quota" in str(exc)
    assert "tenant=a" in str(exc)
    assert "retry_after=1.000s" in str(exc)
    # a quota denial must never become federation spillover: moving the
    # excess to another cell would launder the quota away
    assert SHED_OVER_QUOTA not in SPILL_REASONS
    assert not is_spill_signal(exc)


def test_over_quota_is_shed_domain_never_retried_never_breaker():
    assert classify_fault(
        AdmissionRejected(SHED_OVER_QUOTA, LANE_DEFAULT, tenant="a")) == SHED
    breaker = CircuitBreaker(min_calls=2, window=4)
    policy = ResiliencePolicy(
        retry=RetryPolicy(max_attempts=5, initial_backoff_s=0.0),
        breaker=breaker)
    attempts = [0]

    def op():
        attempts[0] += 1
        raise AdmissionRejected(SHED_OVER_QUOTA, LANE_DEFAULT, tenant="a",
                                retry_after_s=0.25)

    for _ in range(4):
        with pytest.raises(AdmissionRejected):
            policy.execute(op)
    assert attempts[0] == 4  # one attempt per call: SHED never retries
    assert breaker.state == CircuitBreaker.CLOSED
    assert len(breaker._outcomes) == 0  # a quota storm must not trip it


def test_force_admit_charges_quota_with_bounded_debt():
    """Established sequence steps are force-admitted but still charged:
    the debt is bounded at one burst below empty, so the tenant's new
    admissions shed until the bucket climbs back."""
    now = [0.0]
    ctrl = AdmissionController(tenancy="a,rate=1,burst=2",
                               clock=lambda: now[0])
    for _ in range(10):
        ctrl.acquire(force=True, tenant="a").release(0.01)
    row = ctrl.snapshot()["tenancy"]["tenants"]["a"]
    assert row["quota_tokens"] == -2.0  # clamped at -burst, not -8
    with pytest.raises(AdmissionRejected) as ei:
        ctrl.acquire(tenant="a")
    assert ei.value.reason == SHED_OVER_QUOTA


# -- weighted-fair drain ------------------------------------------------------
def test_single_tenant_drain_is_exact_legacy_lifo():
    """With one tenant the WFQ queues must reduce to the legacy behavior:
    newest waiter first (mirrors test_controller_lifo_fresh_beats_stale
    with a tenant attached)."""
    ctrl = AdmissionController(limiter=AdaptiveLimiter(
        initial_limit=1, max_limit=1), max_queue_wait_s=2.0)
    tok = ctrl.acquire(tenant="t")
    order = []

    def waiter(tag, started):
        started.set()
        t = ctrl.acquire(tenant="t")
        order.append(tag)
        time.sleep(0.05)  # hold so the other waiter cannot ride our release
        t.release()

    s1, s2 = threading.Event(), threading.Event()
    old = threading.Thread(target=waiter, args=("old", s1))
    old.start()
    s1.wait()
    time.sleep(0.05)  # old is parked
    new = threading.Thread(target=waiter, args=("new", s2))
    new.start()
    s2.wait()
    time.sleep(0.05)  # new is parked on top of old
    tok.release(0.01)
    old.join()
    new.join()
    assert order == ["new", "old"]


def test_weighted_fair_interleave_across_tenants():
    """Weights 2:1 under contention: the drain picks the tenant with the
    smallest virtual finish time (vtime advances 1/weight per admit), so
    tenant a takes two slots for every one of b's — and within a tenant
    the order stays LIFO."""
    ctrl = AdmissionController(
        limiter=AdaptiveLimiter(initial_limit=1, max_limit=1),
        max_queue_wait_s=10.0, tenancy="a,weight=2;b,weight=1")
    tok = ctrl.acquire()
    order = []

    def waiter(tag, tenant, started):
        started.set()
        t = ctrl.acquire(tenant=tenant)
        order.append(tag)
        time.sleep(0.05)
        t.release()

    threads = []
    for tag, tenant in (("a1", "a"), ("a2", "a"), ("a3", "a"),
                        ("b1", "b"), ("b2", "b"), ("b3", "b")):
        started = threading.Event()
        th = threading.Thread(target=waiter, args=(tag, tenant, started))
        th.start()
        started.wait()
        time.sleep(0.05)  # parked before the next arrives
        threads.append(th)
    tok.release(0.01)
    for th in threads:
        th.join()
    # vtime trace: a drains at 0, .5, 1.0 (then empty); b at 0, 1.0, 2.0;
    # ties break toward a (first queue parked). LIFO inside each tenant.
    assert order == ["a3", "b3", "a2", "a1", "b2", "b1"]
    # the fairness statement: while both tenants are backlogged (first
    # three admits), a holds exactly its 2:1 weighted share
    assert order[:3].count("a3") + order[:3].count("a2") == 2


def test_async_admit_cancel_returns_slot_with_tenant():
    async def main():
        ctrl = AdmissionController(
            limiter=AdaptiveLimiter(initial_limit=1, max_limit=1),
            max_queue_wait_s=0.2, tenancy="a,weight=2")
        tok = await ctrl.acquire_async(tenant="a")
        # parked waiter admitted on release
        task = asyncio.ensure_future(ctrl.acquire_async(tenant="a"))
        await asyncio.sleep(0.02)
        tok.release(0.01)
        tok2 = await task
        assert tok2.waited_s > 0.0
        assert tok2.tenant == "a"
        # parked waiter times out -> queue_timeout, attributed
        task = asyncio.ensure_future(ctrl.acquire_async(tenant="a"))
        with pytest.raises(AdmissionRejected) as exc:
            await task
        assert exc.value.reason == SHED_QUEUE_TIMEOUT
        assert exc.value.tenant == "a"
        # cancellation never leaks the slot (even when the wakeup races)
        task = asyncio.ensure_future(ctrl.acquire_async(tenant="a"))
        await asyncio.sleep(0.02)
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task
        tok2.release(0.01)
        assert ctrl.inflight == 0
        t3 = await ctrl.acquire_async(tenant="a")  # capacity handed on
        t3.release(0.01)

    asyncio.run(main())


def test_snapshot_tenant_sections_gated_on_use():
    """Tenantless controllers keep the pre-tenancy snapshot schema
    byte-identical: no ``tenancy`` section, no per-lane ``tenants``."""
    ctrl = AdmissionController()
    ctrl.acquire().release(0.01)
    snap = ctrl.snapshot()
    assert "tenancy" not in snap
    assert all("tenants" not in row for row in snap["lanes"].values())
    # a real tenant queuing materializes the per-lane depth map
    ctrl2 = AdmissionController(
        limiter=AdaptiveLimiter(initial_limit=1, max_limit=1),
        max_queue_wait_s=0.05)
    tok = ctrl2.acquire()
    with pytest.raises(AdmissionRejected):
        ctrl2.acquire(tenant="a")  # parks, times out
    tok.release(0.01)
    lanes = ctrl2.snapshot()["lanes"]
    assert lanes[LANE_DEFAULT]["tenants"] == {"a": 0}


# -- per-tenant SLO windows & the noisy-neighbor verdict ----------------------
def test_per_tenant_slo_window_burn_and_breach():
    now = [0.0]
    policy = parse_tenancy_spec("a,slo_ms=100,slo_objective=0.9",
                                clock=lambda: now[0])
    for _ in range(10):
        policy.on_result("a", 0.05, True)  # in SLO
    row = policy.snapshot()["tenants"]["a"]
    assert row["window"]["burn_rate"] == 0.0
    assert not row["window"]["breached"]
    for _ in range(5):
        policy.on_result("a", 0.5, True)  # ok transport, blown latency
    row = policy.snapshot()["tenants"]["a"]
    assert row["slo_breaches_total"] == 5
    assert row["window"]["bad"] == 5
    # (5 bad / 15) against a 10% budget: burning 3.3x
    assert row["window"]["burn_rate"] > 1.0
    assert row["window"]["breached"]


def test_noisy_neighbor_named_in_snapshot():
    now = [0.0]
    ctrl = AdmissionController(tenancy="adv,rate=1,burst=1;good,rate=100",
                               clock=lambda: now[0])
    ctrl.acquire(tenant="adv").release(0.01)
    for _ in range(40):
        with pytest.raises(AdmissionRejected):
            ctrl.acquire(tenant="adv")
    for _ in range(5):
        ctrl.acquire(tenant="good").release(0.005)
    ten = ctrl.snapshot()["tenancy"]
    assert ten["tenants"]["adv"]["shed"] == {SHED_OVER_QUOTA: 40}
    assert ten["tenants"]["good"]["admitted_total"] == 5
    assert ten["tenants"]["good"]["shed"] == {}
    noisy = ten["noisy_neighbors"]
    assert [v["tenant"] for v in noisy] == ["adv"]
    assert noisy[0]["over_quota_sheds"] == 40
    assert noisy[0]["admitted_total"] == 1


def test_doctor_flags_noisy_neighbor():
    from client_tpu.doctor import _anomalies

    base = {
        "endpoints": [], "endpoint_stats": {}, "slos": [],
        "admission": [], "shm": {},
        "tenancy": [{
            "tenants": {}, "window_s": 30.0,
            "noisy_neighbors": [{
                "tenant": "adv0", "over_quota_sheds": 120,
                "admitted_total": 10, "offered_over_admitted": 13.0,
            }],
        }],
    }
    flags = _anomalies(base, churn_threshold_ops_s=0.0, skew_warn_ms=250.0)
    nn = [f for f in flags if f["flag"] == "noisy_neighbor"]
    assert len(nn) == 1
    assert nn[0]["tenant"] == "adv0"
    assert "'adv0'" in nn[0]["detail"] and "120" in nn[0]["detail"]
    # a policy row that failed to snapshot never crashes the triage
    base["tenancy"].append({"error": "boom"})
    flags = _anomalies(base, churn_threshold_ops_s=0.0, skew_warn_ms=250.0)
    assert len([f for f in flags if f["flag"] == "noisy_neighbor"]) == 1


def test_tenancy_telemetry_gauges_export():
    tel = Telemetry()
    now = [0.0]
    policy = parse_tenancy_spec("a,rate=1,burst=1,slo_ms=100",
                                clock=lambda: now[0]).attach_telemetry(tel)
    ctrl = AdmissionController(tenancy=policy)
    ctrl.acquire(tenant="a").release(0.01)
    with pytest.raises(AdmissionRejected):
        ctrl.acquire(tenant="a")
    text = tel.registry.prometheus_text()
    assert 'client_tpu_tenant_admitted_total{tenant="a"}' in text
    assert "client_tpu_tenant_shed_total" in text
    assert SHED_OVER_QUOTA in text
    assert 'client_tpu_tenant_quota_tokens{tenant="a"}' in text
    assert 'client_tpu_tenant_slo_burn_rate{tenant="a"}' in text


# -- content-key & cache isolation --------------------------------------------
def test_plan_request_folds_tenant_into_extra_key():
    """The one cross-tenant isolation point: cache keys, singleflight
    groups and coalesced batches all partition here."""
    _, x = _fp32_input(1.0)
    p_none = plan_request([x], {})
    p_none2 = plan_request([x], {"tenant": None})
    p_a = plan_request([x], {"tenant": "a"})
    p_b = plan_request([x], {"tenant": "b"})
    assert all(p is not None for p in (p_none, p_none2, p_a, p_b))
    extra = lambda p: p[4]  # noqa: E731 - (sig, rows, raw, out_sig, extra)
    assert extra(p_none) == extra(p_none2)  # tenantless: byte-identical
    assert extra(p_a) != extra(p_none)
    assert extra(p_a) != extra(p_b)


def test_content_key_tenant_algebra():
    _, a = _fp32_input(1.0)
    _, b = _fp32_input(1.0)
    assert content_key("m", [a]) == content_key("m", [b], {"tenant": None})
    assert content_key("m", [a], {"tenant": "x"}) != content_key("m", [b])
    assert content_key("m", [a], {"tenant": "x"}) != \
        content_key("m", [b], {"tenant": "y"})
    assert content_key("m", [a], {"tenant": "x"}) == \
        content_key("m", [b], {"tenant": "x"})


def test_cache_never_serves_across_tenants(arena):
    cache = ResponseCache(ttl_s=30.0, arena=arena)
    inner = StubInner()
    client = CachingClient(inner, cache=cache)
    _, x1 = _fp32_input(3.0)
    client.infer("stub", [x1], tenant="a")
    assert inner.calls == 1
    _, x2 = _fp32_input(3.0)
    client.infer("stub", [x2], tenant="b")
    assert inner.calls == 2  # b must NOT be served a's cached response
    assert cache.stats()["hits"] == 0
    _, x3 = _fp32_input(3.0)
    client.infer("stub", [x3], tenant="a")
    assert inner.calls == 2  # a's own repeat is the hit
    assert cache.stats()["hits"] == 1
    # tenantless traffic is its own partition, not a's
    _, x4 = _fp32_input(3.0)
    client.infer("stub", [x4])
    assert inner.calls == 3
    assert cache.stats()["hits"] == 1


def test_singleflight_never_collapses_across_tenants():
    inner = StubInner(held=True)
    client = CachingClient(inner, cache=None, singleflight=True)
    tenants = ["a", "b", "a", "b"]

    def fn(i):
        _, x = _fp32_input(5.0)
        r = client.infer("stub", [x], tenant=tenants[i])
        assert np.allclose(r.as_numpy("Y"), 10.0)

    errors = []
    runner = threading.Thread(target=lambda: errors.extend(_run_threads(4, fn)))
    runner.start()
    # the wire requests are held until each twin stands behind a leader
    standing_behind(client, 2)
    assert [f.followers for f in client._flights.values()] == [1, 1]
    inner.release.set()
    runner.join(timeout=60)
    assert not errors
    # one leader per tenant: the same-tenant twin collapsed onto it, the
    # other tenant never did
    assert inner.calls == 2


def test_cache_eviction_never_crosses_tenant_partitions(arena):
    """Flooding tenant b evicts only b's entries: with max_entries=4 and
    two partitions each tenant owns 2 slots, and a's hot entry survives
    b's churn."""
    cache = ResponseCache(ttl_s=30.0, max_entries=4, arena=arena)
    inner = StubInner()
    client = CachingClient(inner, cache=cache)
    _, xa = _fp32_input(1.0)
    client.infer("stub", [xa], tenant="a")
    for i in range(6):  # distinct payloads: b churns past its budget
        _, xb = _fp32_input(10.0 + i)
        client.infer("stub", [xb], tenant="b")
    stats = cache.stats()
    assert stats["tenants"]["a"]["entries"] == 1  # untouched by b's flood
    assert stats["tenants"]["b"]["entries"] == 2  # trimmed to b's share
    assert stats["evictions"]["capacity"] == 4  # all four victims were b's
    calls = inner.calls
    _, xa2 = _fp32_input(1.0)
    client.infer("stub", [xa2], tenant="a")
    assert inner.calls == calls  # a's entry still serves from cache


# -- trace format v4 & the multi_tenant generator -----------------------------
_GEN_SPEC = ("multi_tenant:tenants=2,rate=40,duration_s=1.5,adversaries=1,"
             "adversary_factor=10,hot_key_universe=8")


def test_trace_v4_tenant_roundtrip_and_forward_compat(monkeypatch):
    tr = trace_mod.generate(_GEN_SPEC, seed=11)
    assert all(r.tenant for r in tr.records)
    text = trace_mod.dumps_trace(tr.records, tr.header)
    assert '"v":4' in text and '"tenant":' in text
    back = trace_mod.loads_trace(text)
    assert back.skipped == 0
    assert [r.tenant for r in back.records] == \
        [r.tenant for r in tr.records]
    # an older (v3) loader skips exactly the tenant-stamped records,
    # counted, never fatal
    monkeypatch.setattr(trace_mod, "TRACE_VERSION", 3)
    old = trace_mod.loads_trace(text)
    assert old.records == []
    assert old.skipped == len(tr.records)
    monkeypatch.undo()
    # tenantless specs keep producing byte-identical traces: no tenant
    # field, no version stamp
    plain = trace_mod.generate("poisson_burst:rate=30,duration_s=1", seed=3)
    plain_text = trace_mod.dumps_trace(plain.records, plain.header)
    assert '"tenant"' not in plain_text
    assert '"v":4' not in plain_text


def test_multi_tenant_generator_determinism_and_invariance():
    t1 = trace_mod.generate(_GEN_SPEC, seed=11)
    t2 = trace_mod.generate(_GEN_SPEC, seed=11)
    assert trace_mod.dumps_trace(t1.records, t1.header) == \
        trace_mod.dumps_trace(t2.records, t2.header)
    names = {r.tenant for r in t1.records}
    assert names == {"t0", "t1", "adv0"}
    counts = {}
    for r in t1.records:
        counts[r.tenant] = counts.get(r.tenant, 0) + 1
    # the adversary offers ~10x a compliant tenant's load
    assert counts["adv0"] > 5 * counts["t0"]
    # THE honest-A/B property: removing the adversary leaves the
    # compliant tenants' arrivals (times, keys) literally identical —
    # per-tenant child RNGs, not one shared stream
    iso = trace_mod.generate(
        _GEN_SPEC.replace("adversaries=1", "adversaries=0"), seed=11)

    def compliant(tr):
        return [(r.tenant, r.at_s, r.content_key) for r in tr.records
                if not (r.tenant or "").startswith("adv")]

    assert compliant(iso) == compliant(t1)


def test_multi_tenant_generator_rejects_bad_params():
    with pytest.raises(ValueError):
        trace_mod.generate("multi_tenant:tenants=0", seed=1)
    with pytest.raises(ValueError):
        trace_mod.generate("multi_tenant:adversaries=-1", seed=1)
    with pytest.raises(ValueError):
        trace_mod.generate(
            "multi_tenant:adversaries=1,adversary_factor=0", seed=1)


# -- the committed isolation proof --------------------------------------------
def test_bench_tenancy_artifact_claims():
    """BENCH_TENANCY.json is the committed proof for the acceptance
    criteria: an adversary at 10x its quota costs the compliant tenants
    <5% of their isolated-baseline capacity and zero SLO breaches, its
    rejects are all typed over_quota, the noisy neighbor is named, and
    the shed retry_after hints are present. The --check validator is the
    single source of truth for what the artifact must keep claiming."""
    import tools.bench_tenancy as bench

    path = Path(__file__).resolve().parent.parent / "BENCH_TENANCY.json"
    doc = json.loads(path.read_text())
    failures = bench.check(doc)
    assert failures == 0


# -- tenancy smoke: live adversarial isolation --------------------------------
@pytest.mark.tenancy_smoke
def test_tenancy_isolation_smoke():
    """Both bench arms shortened against a live server, judged by what the
    quota and the weighted-fair queues guarantee at any speed of the
    machine, in counts: the adversary is the arms' only difference, no
    compliant request is shed or fails, every adversary reject is a typed
    ``over_quota`` shed and never an error, and the snapshot names the
    adversary. (The capacity ratio of two timed replays is the operator's
    ``capacity_gate --tenancy``; here the queue-wait cap is out of reach, so
    no shed is the machine's.)"""
    import tools.bench_tenancy as bench

    arms = bench.run_arms(duration_s=2.0, max_queue_wait_s=60.0)
    isolated = arms["isolated"]["tenants"]
    adversarial = arms["adversarial"]["tenants"]
    for arm in (isolated, adversarial):
        for tenant in bench.COMPLIANT:
            row = arm[tenant]
            assert (row["issued"], row["shed"], row["errors"]) == (
                isolated[tenant]["issued"], 0, 0), (tenant, row)
            assert row["ok"] == row["issued"] > 0, (tenant, row)
    adversary = adversarial[bench.ADVERSARY]
    assert bench.ADVERSARY not in isolated
    assert adversary["errors"] == 0, adversary
    assert set(adversary["shed_by_reason"]) == {"over_quota"}, adversary
    assert adversary["ok"] + adversary["shed"] == adversary["issued"], adversary
    noisy = [v["tenant"]
             for v in arms["adversarial"]["tenancy"]["noisy_neighbors"]]
    assert noisy == [bench.ADVERSARY]
