"""Overload hardening: admission control (queue/inflight caps, the
EWMA p99 SLO tracker with shed-or-degrade), per-request deadlines
(dispatch gate and mid-ladder expiry), the per-(op, rung) circuit
breaker with its half-open probe protocol, the process-global retry
budget, the behavioral chaos kinds (``delay``/``reject``) with the
scripted schedule parser, and the servebench soak harness whose
conservation audit proves submitted == admitted + shed with zero
lost or hung futures.

The breaker/shed/audit invariants are ALSO enforced repo-wide by the
``tools/lint_all.py`` ``soak-smoke`` gate (tests/test_lint.py) and
fuzzed under adversarial schedules by the racefuzz ``admission`` and
``orphaned_future`` probes — this file pins the fine-grained
contracts and the e2e evidence trail (every decision a named flight
event)."""
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import mca_overrides
from dplasma_tpu.observability.metrics import MetricsRegistry
from dplasma_tpu.observability.report import (REPORT_SCHEMA,
                                              RunReport, load_report)
from dplasma_tpu.observability.telemetry import FlightRecorder
from dplasma_tpu.resilience import inject
from dplasma_tpu.serving import (AdmissionError, DeadlineExceeded,
                                 ServingTimeout, SolverService,
                                 admission as adm)

NB = 4


def _spd(rng, n, dtype=np.float32):
    g = rng.standard_normal((n, n)).astype(dtype)
    return g @ g.T + n * np.eye(n, dtype=dtype)


def _rhs(rng, n, nrhs, dtype=np.float32):
    return rng.standard_normal((n, nrhs)).astype(dtype)


def _ctrl(**kw):
    kw.setdefault("metrics", MetricsRegistry())
    kw.setdefault("flight", FlightRecorder(capacity=64))
    return adm.AdmissionController(**kw)


# ------------------------------------------------- controller decisions

def test_decide_queue_cap_sheds_with_reason():
    c = _ctrl(max_queue=2)
    assert c.decide("posv", 1, 0) == (adm.ADMIT, None)
    d, why = c.decide("posv", 2, 0)
    assert d == adm.SHED and "serving.max_queue" in why
    assert c.metrics.counter("serving_admitted_total").value == 1
    assert c.metrics.counter("serving_shed_total").value == 1


def test_decide_inflight_cap_sheds():
    c = _ctrl(max_inflight=2)
    assert c.decide("gesv", 0, 1)[0] == adm.ADMIT
    d, why = c.decide("gesv", 0, 2)
    assert d == adm.SHED and "serving.max_inflight" in why


def test_decide_slo_pressure_degrades_ir_sheds_direct():
    c = _ctrl(slo_p99_ms=10.0)
    c._ewma_p99_ms = 50.0           # over SLO
    with mca_overrides({"ir.precision": "f32"}):
        # an _ir op has a cheaper rung to give up -> DEGRADE, and the
        # degraded request still counts ADMITTED (conservation)
        d, why = c.decide("posv_ir", 0, 0)
        assert d == adm.DEGRADE and "slo_p99_ms" in why
        assert adm.degraded_precision() == "bf16"
        # a direct solve has no precision rung -> SHED
        assert c.decide("posv", 0, 0)[0] == adm.SHED
        # bf16 still has the block-scaled int8 rung below it
        with mca_overrides({"ir.precision": "bf16"}):
            assert adm.degraded_precision() == "int8"
        # at the int8 floor there is nothing left to give up -> SHED
        with mca_overrides({"ir.precision": "int8"}):
            assert adm.degraded_precision() is None
            assert c.decide("posv_ir", 0, 0)[0] == adm.SHED
    assert c.metrics.counter("serving_admitted_total").value == 1
    assert c.metrics.counter("serving_degraded_total").value == 1
    assert c.metrics.counter("serving_shed_total").value == 2


def test_decide_disabled_admits_everything():
    with mca_overrides({"serving.admission": "off"}):
        c = _ctrl(max_queue=1)
    assert not c.enabled
    assert c.decide("posv", 10 ** 6, 10 ** 6) == (adm.ADMIT, None)


def test_observe_folds_ewma_every_eighth_sample():
    c = _ctrl(slo_p99_ms=100.0)     # alpha default 0.25
    c.observe(0.2)                  # first sample seeds the EWMA
    assert c.ewma_p99_ms() == pytest.approx(200.0)
    for _ in range(7):              # samples 2..8: skipped
        c.observe(0.05)
    assert c.ewma_p99_ms() == pytest.approx(200.0)
    c.observe(0.05)                 # 9th folds: 0.25*50 + 0.75*200
    assert c.ewma_p99_ms() == pytest.approx(162.5)


def test_resolve_deadline_explicit_mca_and_none():
    assert adm.resolve_deadline(0.5, now=100.0) == pytest.approx(100.5)
    assert adm.resolve_deadline(None) == 0.0
    assert adm.resolve_deadline(0.0, now=5.0) == 0.0
    with mca_overrides({"serving.default_deadline_s": "0.25"}):
        assert adm.resolve_deadline(None, now=10.0) \
            == pytest.approx(10.25)
        # the explicit argument wins over the MCA default
        assert adm.resolve_deadline(2.0, now=10.0) \
            == pytest.approx(12.0)


def test_retry_budget_exhausts_and_reports():
    c = _ctrl(retry_budget=2)
    assert c.take_retry() and c.take_retry()
    assert not c.take_retry()
    assert c.summary()["retry_budget"] == {"limit": 2, "used": 2}
    unlimited = _ctrl(retry_budget=0)
    assert all(unlimited.take_retry() for _ in range(10))
    assert unlimited.summary()["retry_budget"]["used"] == 0


# ----------------------------------------------------- circuit breaker

def test_breaker_state_machine_full_cycle():
    c = _ctrl(breaker_failures=2, breaker_cooldown_s=0.0)
    fl = c.flight
    assert c.breaker_allow("posv", "retry")
    c.breaker_record("posv", "retry", False)
    assert c.breaker_state("posv", "retry") == adm.CLOSED
    c.breaker_record("posv", "retry", False)    # 2nd consecutive fail
    assert c.breaker_state("posv", "retry") == adm.OPEN
    assert c.metrics.counter("serving_breaker_open_total").value == 1
    assert c.metrics.gauge("serving_breaker_open").value == 1
    assert any(e["kind"] == "breaker_open" for e in fl.events())
    # cooldown 0: the next allow admits ONE half-open probe
    assert c.breaker_allow("posv", "retry")
    assert c.breaker_state("posv", "retry") == adm.HALF_OPEN
    assert c.metrics.gauge("serving_breaker_half_open").value == 1
    assert any(e["kind"] == "breaker_half_open" for e in fl.events())
    # a second caller is rejected while the probe is in flight
    assert not c.breaker_allow("posv", "retry")
    # probe success closes and zeroes the failure count
    c.breaker_record("posv", "retry", True)
    assert c.breaker_state("posv", "retry") == adm.CLOSED
    assert c.metrics.gauge("serving_breaker_open").value == 0
    assert any(e["kind"] == "breaker_close" for e in fl.events())
    # a half-open probe FAILURE re-opens immediately (one strike)
    c.breaker_record("posv", "retry", False)
    c.breaker_record("posv", "retry", False)
    assert c.breaker_allow("posv", "retry")     # half-open probe
    c.breaker_record("posv", "retry", False)
    assert c.breaker_state("posv", "retry") == adm.OPEN
    s = c.summary()["breakers"]["posv:retry"]
    # opens: consecutive-fail (x2) + the probe failure re-open
    assert s["opens"] == 3 and s["probes"] == 2


def test_breaker_is_per_op_per_rung():
    c = _ctrl(breaker_failures=1, breaker_cooldown_s=60.0)
    c.breaker_record("posv", "retry", False)
    assert not c.breaker_allow("posv", "retry")
    # the same rung of ANOTHER op, and another rung of the SAME op,
    # stay closed — one poisoned executable cannot brown out the rest
    assert c.breaker_allow("gesv", "retry")
    assert c.breaker_allow("posv", "algo_fallback")


# ----------------------------------------------- chaos kinds + schedule

def test_parse_plan_rejects_unknown_kind_at_parse_time():
    with pytest.raises(ValueError) as ei:
        inject.parse_plan("bitlfip@gemm", 1)
    msg = str(ei.value)
    assert "unknown fault kind 'bitlfip'" in msg
    # the error teaches the valid kinds (the typo is one edit away)
    for kind in inject.KINDS:
        assert kind in msg


def test_parse_schedule_phases_and_quiet_slots():
    phases = inject.parse_schedule(
        "nan@serving:0.5, off ,delay@serving", seed=7)
    assert len(phases) == 3
    assert phases[0].plan.kind == "nan" and phases[0].plan.seed == 7
    assert phases[1].plan is None
    assert phases[2].plan.kind == "delay" \
        and phases[2].plan.seed == 9      # armed phase k seeds seed+k
    with pytest.raises(ValueError):
        inject.parse_schedule("  ", seed=7)


def test_delay_kind_sleeps_and_records_without_corrupting():
    x = jnp.ones((2, 2), dtype=jnp.float32)
    with mca_overrides({"chaos.delay_ms": "30"}):
        inject.arm(inject.parse_plan("delay@serving:1:1", 3))
        try:
            t0 = time.perf_counter()
            y = inject.tap("serving", x)
            dt = time.perf_counter() - t0
        finally:
            faults = inject.disarm()
    assert np.array_equal(np.asarray(y), np.asarray(x))
    assert dt >= 0.025
    assert [f["kind"] for f in faults] == ["delay"]


def test_reject_kind_raises_structured_and_charges_budget():
    inject.arm(inject.parse_plan("reject@serving:1:1", 3))
    try:
        with pytest.raises(inject.InjectedReject,
                           match="injected reject at serving"):
            inject.tap("serving", jnp.ones((2, 2)))
        # count=1 exhausted: the next tap passes through clean
        y = inject.tap("serving", jnp.ones((2, 2)))
        assert np.all(np.asarray(y) == 1.0)
    finally:
        faults = inject.disarm()
    assert [f["kind"] for f in faults] == ["reject"]


def test_injected_reject_walks_ladder_and_heals():
    rng = np.random.default_rng(3872)
    svc = SolverService(nb=NB, max_batch=4, max_wait_ms=0)
    a, b = _spd(rng, 8), _rhs(rng, 8, 2)
    inject.arm(inject.parse_plan("reject@serving:1:1", 3872))
    try:
        f = svc.submit("posv", a, b)
        svc.flush()
        x = f.result(120.0)
    finally:
        inject.disarm()
    meta = f.meta
    assert meta["ok"] and meta["resilience"]["outcome"] == "remediated"
    assert np.allclose(a @ np.asarray(x), b, atol=1e-3)
    evs = svc.telemetry.flight.events()
    assert any(e["kind"] == "inject"
               and e.get("fault", {}).get("kind") == "reject"
               for e in evs)
    assert svc.summary()["remediated"] == 1
    svc.close()


# -------------------------------------------------------- service e2e

def test_submit_shed_raises_structured_and_lands_flight_event():
    rng = np.random.default_rng(3872)
    svc = SolverService(nb=NB, max_batch=8, max_wait_ms=0)
    svc.admission.max_queue = 1
    f1 = svc.submit("posv", _spd(rng, 8), _rhs(rng, 8, 2))
    with pytest.raises(AdmissionError) as ei:
        svc.submit("posv", _spd(rng, 8), _rhs(rng, 8, 2))
    exc = ei.value
    assert exc.request_id == f1.request_id + 1
    assert "shed" in str(exc) and "serving.max_queue" in exc.reason
    sheds = [e for e in svc.telemetry.flight.events()
             if e["kind"] == "shed"]
    assert [e["request"] for e in sheds] == [exc.request_id]
    # a shed request never got a submit event — it never entered the
    # queue, so the conservation audit counts it exactly once
    assert not any(e["kind"] == "submit"
                   and e.get("request") == exc.request_id
                   for e in svc.telemetry.flight.events())
    svc.flush()
    f1.result(120.0)
    s = svc.admission.summary()
    assert s["admitted"] == 1 and s["shed"] == 1
    svc.close()


def test_slo_pressure_degrades_ir_request_end_to_end():
    rng = np.random.default_rng(3872)
    svc = SolverService(nb=NB, max_batch=4, max_wait_ms=0)
    svc.admission.slo_p99_ms = 1.0
    svc.admission._ewma_p99_ms = 1e9          # force SLO pressure
    a = _spd(rng, 8, np.float64)
    b = _rhs(rng, 8, 2, np.float64)
    f = svc.submit("posv_ir", a, b)
    svc.flush()
    x = f.result(300.0)
    assert np.allclose(a @ np.asarray(x), b, atol=1e-6)
    degr = [e for e in svc.telemetry.flight.events()
            if e["kind"] == "degrade"]
    assert [e["request"] for e in degr] == [f.request_id]
    assert degr[0]["precision"] == "bf16"
    s = svc.admission.summary()
    # DEGRADE counts admitted too: submitted == admitted + shed
    assert s["degraded"] == 1 and s["admitted"] == 1 \
        and s["shed"] == 0
    svc.close()


def test_deadline_expires_in_dispatch_queue():
    rng = np.random.default_rng(3872)
    svc = SolverService(nb=NB, max_batch=8, max_wait_ms=0)
    f = svc.submit("posv", _spd(rng, 8), _rhs(rng, 8, 2),
                   deadline_s=1e-6)
    svc.flush()
    with pytest.raises(DeadlineExceeded) as ei:
        f.result(120.0)
    assert ei.value.request_id == f.request_id
    evs = [e for e in svc.telemetry.flight.events()
           if e["kind"] == "deadline_expired"]
    assert evs and evs[0]["request"] == f.request_id \
        and evs[0]["where"] == "dispatch"
    assert svc.metrics.counter(
        "serving_deadline_expired_total").value == 1
    svc.close()


def test_deadline_expires_mid_ladder():
    """A gate-failed request whose deadline expires DURING the
    remediation walk stops climbing: the ladder records a 'deadline'
    attempt, the future fails with the structured error, and the
    expiry is a flight event at where='ladder'."""
    rng = np.random.default_rng(3872)
    svc = SolverService(nb=NB, max_batch=4, max_wait_ms=0)
    a, b = _spd(rng, 8), _rhs(rng, 8, 2)
    # warm the batch executable so dispatch latency is well inside
    # the 1 s deadline (a loaded host can still take a few hundred ms
    # to re-dispatch) — the expiry lands in the slow rung
    fw = svc.submit("posv", a, b)
    svc.flush()
    fw.result(120.0)

    def slow_bad_solo(r):
        time.sleep(2.0)             # expires the deadline mid-rung
        return jnp.full((r.n, r.nrhs), jnp.nan,
                        dtype=r.a.dtype), None

    svc._solo = slow_bad_solo
    inject.arm(inject.parse_plan("nan@serving:1:1", 3872))
    try:
        f = svc.submit("posv", a, b, deadline_s=1.0)
        svc.flush()
        with pytest.raises(DeadlineExceeded):
            f.result(120.0)
    finally:
        inject.disarm()
    evs = [e for e in svc.telemetry.flight.events()
           if e["kind"] == "deadline_expired"]
    assert evs and evs[-1]["where"] == "ladder" \
        and evs[-1]["request"] == f.request_id
    # the walk's summary records the deadline as its last attempt
    summ = svc.resilience[-1]
    assert summ["attempts"][-1]["action"] == "deadline"
    svc.close()


def test_breaker_opens_on_poisoned_rung_and_future_still_resolves():
    rng = np.random.default_rng(3872)
    svc = SolverService(nb=NB, max_batch=4, max_wait_ms=0)
    svc.admission.breaker_failures = 1

    def _raise(_r):
        raise RuntimeError("poisoned rung")

    svc._solo = _raise
    svc._escalate = _raise
    inject.arm(inject.parse_plan("nan@serving:1:1", 3872))
    try:
        f = svc.submit("posv", _spd(rng, 8), _rhs(rng, 8, 2))
        svc.flush()
        with pytest.raises(RuntimeError, match="poisoned rung"):
            f.result(120.0)
    finally:
        inject.disarm()
    # the raising rung opened its breaker, visibly: state, gauge,
    # counter, and the named flight event — and the failed future
    # still RESOLVED (conservation holds under the failure)
    states = {k: v["state"]
              for k, v in svc.admission.summary()["breakers"].items()}
    assert any(k.startswith("posv:") and v == adm.OPEN
               for k, v in states.items()), states
    assert svc.metrics.counter(
        "serving_breaker_open_total").value >= 1
    assert any(e["kind"] == "breaker_open"
               for e in svc.telemetry.flight.events())
    assert svc.metrics.counter("serving_resolved_total").value == 1
    svc.close()


def test_result_timeout_raises_serving_timeout_naming_request():
    rng = np.random.default_rng(3872)
    svc = SolverService(nb=NB, max_batch=8, max_wait_ms=0)
    orig_drive = svc._drive
    svc._drive = lambda group: None          # dispatch never happens
    f = svc.submit("posv", _spd(rng, 8), _rhs(rng, 8, 2))
    with pytest.raises(ServingTimeout) as ei:
        f.result(timeout=0.05)
    assert ei.value.request_id == f.request_id
    assert f"request {f.request_id}" in str(ei.value)
    # the orphan recovers once dispatch is back: no request is lost
    svc._drive = orig_drive
    svc.flush()
    f.result(120.0)
    assert svc.metrics.counter("serving_resolved_total").value == 1
    svc.close()


def test_flight_ring_overflow_during_shed_storm_stays_auditable():
    """Satellite: a shed storm overflowing the bounded flight ring
    keeps the audit honest — the drop count is visible in the dump
    and (events still held + dropped) still covers the shed count."""
    rng = np.random.default_rng(3872)
    svc = SolverService(nb=NB, max_batch=64, max_wait_ms=0)
    small = FlightRecorder(capacity=8)
    svc.telemetry.flight = small
    svc.admission.flight = small
    svc.admission.max_queue = 1
    a, b = _spd(rng, 8), _rhs(rng, 8, 2)
    futs, shed = [], 0
    for _ in range(20):
        try:
            futs.append(svc.submit("posv", a, b))
        except AdmissionError:
            shed += 1
    svc.flush()
    for f in futs:
        f.result(120.0)
    assert shed == 19 and len(futs) == 1
    summ = small.summary()
    assert summ["dropped"] > 0           # overflow happened, visibly
    held_shed = small.counts().get("shed", 0)
    assert held_shed + summ["dropped"] >= shed
    s = svc.admission.summary()
    assert s["admitted"] == 1 and s["shed"] == 19
    assert svc.metrics.counter("serving_resolved_total").value == 1
    svc.close()


def test_run_report_admission_section_roundtrip(tmp_path):
    rng = np.random.default_rng(3872)
    svc = SolverService(nb=NB, max_batch=4, max_wait_ms=0)
    f = svc.submit("posv", _spd(rng, 8), _rhs(rng, 8, 2))
    svc.flush()
    f.result(120.0)
    rep = RunReport("admission-test")
    adm_s = svc.admission.summary()
    adm_s["audit"] = {"submitted": 1, "admitted": 1, "shed": 0,
                      "resolved": 1, "lost": 0, "balanced": True}
    rep.add_admission(adm_s)
    p = str(tmp_path / "r.json")
    rep.write(p)
    doc = load_report(p)
    assert doc["schema"] == REPORT_SCHEMA == 18
    assert doc["admission"]["admitted"] == 1
    assert doc["admission"]["audit"]["balanced"] is True
    assert doc["admission"]["retry_budget"] == {"limit": 0, "used": 0}
    svc.close()


# ---------------------------------------------------- servebench soak

def test_servebench_soak_audit_balances_under_chaos(tmp_path):
    """Acceptance (tier-1-sized): a soak burst under a chaos schedule
    mixing nan faults with induced overload balances its conservation
    audit — and the v15 report carries the audit plus the lower-better
    shed/deadline fractions and the admission-overhead entry."""
    import sys
    sys.path.insert(0, str(tmp_path.parent))
    from tools import servebench
    hist = str(tmp_path / "h.jsonl")
    rep = str(tmp_path / "r.json")
    rc = servebench.main(["--requests", "8", "--sizes", "12",
                          "--max-nrhs", "2", "--ops", "posv",
                          "--reps", "1", "--history", hist,
                          "--report", rep, "--soak",
                          "--soak-seconds", "0.2",
                          "--chaos", "nan@serving:0.3:2,off",
                          "--mca", "serving.max_queue=4"])
    assert rc == 0
    doc = json.load(open(rep))
    assert doc["schema"] == 18
    audit = doc["admission"]["audit"]
    assert audit["balanced"] is True
    assert audit["submitted"] == audit["admitted"] + audit["shed"]
    assert audit["lost"] == 0 and audit["hung"] == 0
    assert audit["shed"] > 0             # the queue cap actually bit
    metrics = {e["metric"]: e for e in doc["entries"]}
    for m in ("serving.shed_frac", "serving.deadline_miss_frac",
              "serving.admission_overhead_frac"):
        assert metrics[m]["better"] == "lower", m
    # a repeat run gates clean against the first through perfdiff
    from tools import perfdiff
    assert perfdiff.main([hist, rep]) == 0


def test_servebench_trace_record_replay_roundtrip(tmp_path):
    from tools import servebench
    reqs = servebench.make_workload(6, 3872, ["posv", "gesv"],
                                    [8, 12], 3)
    p = str(tmp_path / "trace.jsonl")
    servebench.record_trace(p, reqs)
    back = servebench.load_trace(p, 3872)
    assert [(op, a.shape, b.shape) for op, a, b in back] \
        == [(op, a.shape, b.shape) for op, a, b in reqs]
    with pytest.raises(ValueError, match="no requests"):
        empty = tmp_path / "empty.jsonl"
        empty.write_text("")
        servebench.load_trace(str(empty), 1)


@pytest.mark.slow
def test_servebench_soak_sustained_mixed_chaos(tmp_path):
    """The sustained soak acceptance: mixed posv/gesv traffic for
    several seconds under a schedule mixing nan faults, delay
    stragglers, and induced overload (a deliberately tight queue
    cap) — the conservation audit balances with zero lost or hung
    futures across every wave."""
    import sys
    sys.path.insert(0, str(tmp_path.parent))
    from tools import servebench
    rep = str(tmp_path / "r.json")
    rc = servebench.main(
        ["--requests", "48", "--sizes", "12,16",
         "--max-nrhs", "2", "--reps", "2",
         "--history", str(tmp_path / "h.jsonl"),
         "--report", rep, "--soak", "--soak-seconds", "4",
         "--chaos",
         "nan@serving:0.05,delay@serving:0.1,off",
         "--mca", "serving.max_queue=24",
         "--mca", "chaos.delay_ms=5"])
    assert rc == 0
    doc = json.load(open(rep))
    audit = doc["admission"]["audit"]
    assert audit["balanced"] is True
    assert audit["lost"] == 0 and audit["hung"] == 0
    assert audit["waves"] >= 2
    assert audit["submitted"] == audit["admitted"] + audit["shed"]


@pytest.mark.slow
def test_servebench_admission_overhead_within_budget(tmp_path):
    """Acceptance: measured admission overhead on the UN-stressed
    servebench path (default caps, no SLO pressure, no chaos) is
    < 5% vs admission-off — gated alongside trace_overhead_frac
    (one re-measure allowed: the figure is timing)."""
    import sys
    sys.path.insert(0, str(tmp_path.parent))
    from tools import servebench
    overhead = None
    for attempt in range(2):
        rep = str(tmp_path / f"r{attempt}.json")
        rc = servebench.main(["--requests", "64", "--sizes", "12,16",
                              "--max-nrhs", "2", "--reps", "4",
                              "--history", str(tmp_path / "h.jsonl"),
                              "--report", rep])
        assert rc == 0
        doc = json.load(open(rep))
        overhead = doc["serving"][0]["admission_overhead_frac"]
        assert overhead is not None
        if overhead < 0.05:
            break
    assert overhead < 0.05, \
        f"admission overhead {overhead:.3f} >= 5% budget"
