"""Generate BENCH_TENANCY.json: multi-tenant isolation under an
adversarial neighbor.

The claim to prove (the tenancy ISSUE): with per-tenant quotas and
weighted-fair admission armed (``client_tpu.tenancy``), an adversarial
tenant offering **10x its declared quota** costs the compliant tenants
less than 5% of their capacity and zero SLO breaches — and every one of
the adversary's rejected requests is a *typed* ``over_quota`` shed with
an honest ``retry_after_s`` hint, never an error and never a
breaker/retry signal.

Method (two arms, ONE compliant workload):

1. **isolated** — a seeded ``multi_tenant`` trace with only the
   compliant tenants (``t0``, ``t1``), replayed through an
   admission+tenancy-armed pool. This is the compliant tenants'
   baseline: ok counts, latencies, per-tenant SLO windows.
2. **adversarial** — the SAME spec plus one adversary (``adv0``)
   offering ``ADVERSARY_FACTOR``x the per-tenant rate against a quota of
   exactly that rate. The generator draws each tenant's arrivals (and
   payload keys) from its own child rng, so the compliant records in
   this arm are byte-identical to the isolated arm's — the adversary is
   the ONLY delta.

The invariants (``check``):

- ``compliant_capacity``: compliant ok-count in the adversarial arm >=
  ``MIN_COMPLIANT_CAPACITY_RATIO`` (95%) of the isolated arm's.
- ``compliant_slo``: zero compliant SLO-window breaches and zero
  compliant sheds/errors in the adversarial arm (the per-tenant burn
  windows come from the controller's tenancy snapshot).
- ``adversary_typed``: the adversary's rejects are 100% ``over_quota``
  sheds (no errors — a quota denial is policy, not failure) and its
  excess actually shed (>= half its offered traffic).
- ``noisy_neighbor_named``: the tenancy snapshot's noisy-neighbor
  verdict names ``adv0`` — what ``client_tpu.doctor`` flags.
- ``retry_after_honest``: shed rows carry positive ``retry_after_s``
  hints (the token bucket's refill eta), surfaced in the replay row.

``--check`` re-validates the committed artifact (CI:
``tests/test_tenancy.py::test_bench_tenancy_artifact_claims``);
``tools/capacity_gate.py --tenancy`` re-RUNS both arms on a shortened
twin of the trace and fails when the isolation no longer holds live.

Usage::

    JAX_PLATFORMS=cpu python tools/bench_tenancy.py [-o BENCH_TENANCY.json]
    JAX_PLATFORMS=cpu python tools/bench_tenancy.py --check
"""

from __future__ import annotations

import argparse
import contextlib
import json
import platform
import sys
import time
from pathlib import Path
from typing import Any, Dict

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# per-compliant-tenant offered rate (req/s) and the adversary's multiple
# of ITS OWN quota; the compliant load is sized well under one replica's
# capacity so any compliant loss in the adversarial arm is attributable
# to the adversary, not to saturation
RATE = 30.0
TENANTS = 2
ADVERSARY_FACTOR = 10.0
DURATION_S = 6.0
TRACE_SEED = 2026
# compliant tenants: quota at 2x their offered rate (they never hit it),
# a 250ms/99% SLO window; adversary: quota exactly RATE, so its offered
# ADVERSARY_FACTOR x RATE is 10x quota and ~90% of it must shed typed
COMPLIANT_SLO_MS = 250.0
TENANCY_SPEC = (
    f"t0,rate={2 * RATE:g},burst={2 * RATE:g},weight=1,"
    f"slo_ms={COMPLIANT_SLO_MS:g},slo_objective=0.99;"
    f"t1,rate={2 * RATE:g},burst={2 * RATE:g},weight=1,"
    f"slo_ms={COMPLIANT_SLO_MS:g},slo_objective=0.99;"
    f"adv0,rate={RATE:g},burst={RATE:g}"
)
_BASE = (f"tenants={TENANTS},rate={RATE:g},duration_s={DURATION_S:g},"
         f"model=simple,hot_key_universe=16,hot_key_alpha=1.1")
ISOLATED_SPEC = f"multi_tenant:{_BASE},adversaries=0"
ADVERSARIAL_SPEC = (f"multi_tenant:{_BASE},adversaries=1,"
                    f"adversary_factor={ADVERSARY_FACTOR:g}")
COMPLIANT = tuple(f"t{i}" for i in range(TENANTS))
ADVERSARY = "adv0"
MIN_COMPLIANT_CAPACITY_RATIO = 0.95
MIN_ADVERSARY_SHED_FRACTION = 0.5
REPLAY_WORKERS = 32


@contextlib.contextmanager
def arm_runner(max_queue_wait_s: float = 0.05):
    """A fresh in-process server + a PerfRunner with the tenancy-armed
    admission controller (both arms use the SAME runner config; the arm
    is the trace). Shared with ``tools/capacity_gate.py --tenancy`` so
    the gate re-runs exactly this definition. ``max_queue_wait_s`` is the
    controller's queue-wait cap (PerfRunner's default): the one shed a slow
    machine causes by itself is ``queue_timeout``, and a caller that counts
    sheds by reason raises the cap to take the machine out of the count."""
    import numpy as np

    from client_tpu.http import InferenceServerClient, InferInput
    from client_tpu.models import default_model_zoo
    from client_tpu.perf import PerfRunner
    from client_tpu.server import HttpInferenceServer, ServerCore

    server = HttpInferenceServer(ServerCore(default_model_zoo())).start()
    runner = None
    try:
        with InferenceServerClient(server.url) as client:
            inputs = []
            for name in ("INPUT0", "INPUT1"):
                inp = InferInput(name, [1, 16], "INT32")
                inp.set_data_from_numpy(np.zeros((1, 16), dtype=np.int32))
                inputs.append(inp)
            client.infer("simple", inputs)  # jit warm
        runner = PerfRunner(
            server.url, "http", "simple",
            endpoints=[server.url],
            admission=True,
            admission_max_queue_wait_s=max_queue_wait_s,
            tenancy=TENANCY_SPEC,
        )
        feature = ("1-replica PoolClient, admission controller with "
                   "per-tenant weighted-fair queues + token-bucket "
                   "quotas (client_tpu.tenancy)")
        yield runner, feature
    finally:
        if runner is not None:
            runner.close()
        server.stop()


def _tenant_rows(row: Dict[str, Any]) -> Dict[str, Any]:
    return row.get("tenants") or {}


def _policy_rows(row: Dict[str, Any]) -> Dict[str, Any]:
    """The controller's own per-tenant story (quota tokens, SLO burn
    windows, noisy-neighbor verdicts) out of the replay row's
    ``client_admission`` snapshot."""
    return (row.get("client_admission") or {}).get("tenancy") or {}


def run_arm(runner, tr, name: str) -> Dict[str, Any]:
    row = runner.run_trace(tr, speed=1.0, replay_workers=REPLAY_WORKERS)
    tenants = _tenant_rows(row)
    policy = _policy_rows(row)
    out = {
        "records": len(tr.records),
        "issued": row["issued"],
        "ok": row["requests"],
        "errors": row["errors"],
        "shed": row["shed"],
        "tenants": tenants,
        "shed_retry_after_ms": row.get("shed_retry_after_ms"),
        "tenancy": policy,
    }
    compliant_ok = sum(tenants.get(t, {}).get("ok", 0) for t in COMPLIANT)
    print(f"arm {name}: ok={row['requests']} shed={row['shed']} "
          f"errors={row['errors']} compliant_ok={compliant_ok}"
          + (f" noisy={[v['tenant'] for v in policy.get('noisy_neighbors', [])]}"
             if policy else ""),
          flush=True)
    return out


def _compliant_ok(arm: Dict[str, Any]) -> int:
    return sum(arm["tenants"].get(t, {}).get("ok", 0) for t in COMPLIANT)


def check(doc: Dict[str, Any]) -> int:
    """Validate the committed artifact's claims; prints each verdict and
    returns the number of violations."""
    failures = 0

    def claim(name: str, ok: bool, detail: str) -> None:
        nonlocal failures
        print(f"  [{'ok' if ok else 'FAIL'}] {name}: {detail}")
        if not ok:
            failures += 1

    iso = doc["arms"]["isolated"]
    adv = doc["arms"]["adversarial"]
    iso_ok, adv_ok = _compliant_ok(iso), _compliant_ok(adv)
    ratio = adv_ok / iso_ok if iso_ok else 0.0
    claim("compliant_capacity",
          iso_ok > 0 and ratio >= MIN_COMPLIANT_CAPACITY_RATIO,
          f"compliant ok {adv_ok}/{iso_ok} = {ratio:.3f} >= "
          f"{MIN_COMPLIANT_CAPACITY_RATIO}")

    policy_tenants = (adv.get("tenancy") or {}).get("tenants") or {}
    breaches = {t: policy_tenants.get(t, {}).get("slo_breaches_total")
                for t in COMPLIANT}
    compliant_clean = all(
        adv["tenants"].get(t, {}).get("shed", 1) == 0
        and adv["tenants"].get(t, {}).get("errors", 1) == 0
        for t in COMPLIANT)
    claim("compliant_slo",
          compliant_clean and all(b == 0 for b in breaches.values()),
          f"zero compliant sheds/errors and SLO breaches {breaches} all 0")

    adv_row = adv["tenants"].get(ADVERSARY) or {}
    reasons = adv_row.get("shed_by_reason") or {}
    offered = adv_row.get("issued", 0)
    claim("adversary_typed",
          offered > 0
          and adv_row.get("errors", 1) == 0
          and set(reasons) == {"over_quota"}
          and adv_row.get("shed", 0)
          >= MIN_ADVERSARY_SHED_FRACTION * offered,
          f"adversary {adv_row.get('shed', 0)}/{offered} shed, reasons "
          f"{reasons}, errors {adv_row.get('errors')}")

    noisy = [v.get("tenant")
             for v in (adv.get("tenancy") or {}).get("noisy_neighbors", [])]
    claim("noisy_neighbor_named", ADVERSARY in noisy,
          f"noisy-neighbor verdicts {noisy} name {ADVERSARY!r} "
          f"(what client_tpu.doctor flags)")

    retry = adv.get("shed_retry_after_ms") or {}
    claim("retry_after_honest", (retry.get("p50") or 0.0) > 0.0,
          f"shed retry_after hints present, p50={retry.get('p50')}ms")
    return failures


def run_arms(duration_s: float, **runner_kwargs) -> Dict[str, Any]:
    """Both arms on a shortened twin of the workload, each against a fresh
    server: ``{"isolated": ..., "adversarial": ...}``."""
    from client_tpu import trace as trace_mod

    arms = {}
    for name, spec in (("isolated", ISOLATED_SPEC),
                       ("adversarial", ADVERSARIAL_SPEC)):
        tr = trace_mod.generate(spec, seed=TRACE_SEED, duration_s=duration_s)
        with arm_runner(**runner_kwargs) as (runner, _):
            arms[name] = run_arm(runner, tr, name)
    return arms


def probe_isolation(duration_s: float, attempts: int) -> Dict[str, Any]:
    """Re-run both arms on a shortened twin of the workload and re-judge
    the isolation invariants live — the ``capacity_gate --tenancy``
    body. Returns ``{"arms": ..., "problems": [...]}``."""
    problems: list = []
    verdict: Dict[str, Any] = {"attempts": []}
    for attempt in range(max(1, attempts)):
        arms = run_arms(duration_s)
        problems = []
        iso_ok, adv_ok = (_compliant_ok(arms["isolated"]),
                          _compliant_ok(arms["adversarial"]))
        if not iso_ok or adv_ok / iso_ok < MIN_COMPLIANT_CAPACITY_RATIO:
            problems.append(
                f"compliant capacity {adv_ok}/{iso_ok} under "
                f"{MIN_COMPLIANT_CAPACITY_RATIO}")
        adv_row = arms["adversarial"]["tenants"].get(ADVERSARY) or {}
        if adv_row.get("errors", 1) != 0 or set(
                adv_row.get("shed_by_reason") or {}) - {"over_quota"}:
            problems.append(
                f"adversary sheds not cleanly typed: "
                f"errors={adv_row.get('errors')} "
                f"reasons={adv_row.get('shed_by_reason')}")
        noisy = [v.get("tenant") for v in (arms["adversarial"].get("tenancy")
                                           or {}).get("noisy_neighbors", [])]
        if ADVERSARY not in noisy:
            problems.append(f"noisy-neighbor verdict missing: {noisy}")
        verdict["attempts"].append({
            "attempt": attempt + 1,
            "compliant_ok": {"isolated": iso_ok, "adversarial": adv_ok},
            "problems": list(problems),
        })
        verdict["arms"] = arms
        if not problems:
            break
    verdict["problems"] = problems
    return verdict


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("-o", "--output", default="BENCH_TENANCY.json")
    parser.add_argument("--check", action="store_true",
                        help="validate the committed artifact's claims "
                             "instead of re-measuring")
    args = parser.parse_args(argv)

    if args.check:
        doc = json.loads(Path(args.output).read_text())
        failures = check(doc)
        print("OK" if failures == 0 else f"{failures} claim(s) failed")
        return 1 if failures else 0

    from client_tpu import trace as trace_mod

    iso_tr = trace_mod.generate(ISOLATED_SPEC, seed=TRACE_SEED)
    adv_tr = trace_mod.generate(ADVERSARIAL_SPEC, seed=TRACE_SEED)
    out: Dict[str, Any] = {
        "generated_unix": int(time.time()),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "note": (
            "multi-tenant isolation: the same compliant workload replayed "
            "with and without an adversarial tenant offering "
            f"{ADVERSARY_FACTOR:g}x its quota; per-tenant weighted-fair "
            "queues + token-bucket quotas (client_tpu.tenancy) must keep "
            "the compliant tenants' capacity within "
            f"{(1 - MIN_COMPLIANT_CAPACITY_RATIO) * 100:g}% and their SLO "
            "windows clean while the adversary's excess sheds typed "
            "over_quota with honest retry_after hints"
        ),
        "trace": {
            "isolated_spec": ISOLATED_SPEC,
            "adversarial_spec": ADVERSARIAL_SPEC,
            "seed": TRACE_SEED,
            "duration_s": DURATION_S,
            "isolated_records": len(iso_tr.records),
            "adversarial_records": len(adv_tr.records),
        },
        "tenancy_spec": TENANCY_SPEC,
        "compliant_tenants": list(COMPLIANT),
        "adversary": ADVERSARY,
        "adversary_factor": ADVERSARY_FACTOR,
        "limits": {
            "min_compliant_capacity_ratio": MIN_COMPLIANT_CAPACITY_RATIO,
            "min_adversary_shed_fraction": MIN_ADVERSARY_SHED_FRACTION,
            "compliant_slo_ms": COMPLIANT_SLO_MS,
        },
        "search": {"replay_workers": REPLAY_WORKERS},
        "arms": {},
    }
    with arm_runner() as (runner, feature):
        print(f"arm isolated: {feature}", flush=True)
        arm = run_arm(runner, iso_tr, "isolated")
        arm["feature"] = feature
        out["arms"]["isolated"] = arm
    with arm_runner() as (runner, feature):
        print(f"arm adversarial: {feature}", flush=True)
        arm = run_arm(runner, adv_tr, "adversarial")
        arm["feature"] = feature
        out["arms"]["adversarial"] = arm
    iso_ok, adv_ok = (_compliant_ok(out["arms"]["isolated"]),
                      _compliant_ok(out["arms"]["adversarial"]))
    out["compliant_capacity_ratio"] = (round(adv_ok / iso_ok, 4)
                                       if iso_ok else None)

    Path(args.output).write_text(json.dumps(out, indent=2) + "\n")
    print(json.dumps({
        "compliant_ok_isolated": iso_ok,
        "compliant_ok_adversarial": adv_ok,
        "compliant_capacity_ratio": out["compliant_capacity_ratio"],
        "adversary_shed": (out["arms"]["adversarial"]["tenants"]
                           .get(ADVERSARY, {}).get("shed")),
    }, indent=2))
    failures = check(out)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
