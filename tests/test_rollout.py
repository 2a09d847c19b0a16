"""Safe model rollout (mxnet_tpu/serving/rollout.py): versioned deploys,
shadow/canary traffic splitting, SLO- and accuracy-gated automatic
rollback, zero-downtime hot-swap — and THE chaos acceptance test: a
rollout whose canary silently skews its answers under a request storm is
auto-rolled back by the shadow-agreement gate with zero deadline
violations, the incumbent restored to 100% of traffic, and the whole run
lockwatch-clean — all proven from telemetry counters, the trace ring and
the /rolloutz status document."""
import base64
import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from mxnet_tpu.base import MXNetError
from mxnet_tpu.observability import catalog
from mxnet_tpu.serving import (Draining, MemoryBudgetExceeded, ModelConfig,
                               ModelServer, RolloutManager,
                               ServingEndpoints)
from mxnet_tpu.serving import chaos as schaos
from mxnet_tpu.serving import load as sload
from mxnet_tpu.serving import rollout as srollout
from mxnet_tpu.serving.rollout import STAGES, _hash_frac

pytestmark = [pytest.mark.serve, pytest.mark.rollout]


@pytest.fixture(scope="module")
def tiny():
    return sload.tiny_model()


@pytest.fixture(scope="module")
def tiny2():
    # different seed -> different weights -> different argmaxes: the
    # "silently wrong" candidate a rollout gate must catch
    return sload.tiny_model(seed=1)


def _cfg(tiny, name="m", **kw):
    sym_json, pbytes, feat, _ = tiny
    d = dict(feature_shape=feat, buckets=(1, 2, 4, 8), max_queue=32,
             deadline_ms=2000.0, max_wait_ms=3.0, breaker_cooldown_s=0.25)
    d.update(kw)
    return ModelConfig(name, sym_json, pbytes, **d)


def _wait(pred, timeout=20.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError("timed out waiting for %s" % msg)


def _wait_serving(srv, model="m", timeout=30.0):
    ro = srv._rollout.get(model)
    _wait(lambda: ro.state in ("serving", "refused"), timeout,
          "canary of %r to finish loading" % model)
    assert ro.state == "serving", ro.status()
    return ro


def _pump(srv, payload, n, model="m", rng=None):
    """n submissions, everything collected (ok or typed). With ``rng``
    every payload is a fresh random sample (shadow-agreement tests need
    varied inputs — identical payloads compare identically forever)."""
    shape = np.asarray(payload).shape
    mk = (lambda: payload) if rng is None \
        else (lambda: rng.randn(*shape).astype(np.float32))
    def submit():
        # a submit that races a version's retirement gets the documented
        # typed Draining at admission; like a client it retries, and the
        # splitter by then points at the live version
        try:
            return srv.submit(model, mk())
        except Draining:
            return srv.submit(model, mk())

    futs = [submit() for _ in range(n)]
    out = {"ok": 0, "error": 0}
    for f in futs:
        try:
            f.result(30.0)
            out["ok"] += 1
        except Exception:
            out["error"] += 1
    return out


def _rollout_events(srv, model="m"):
    evs = []
    for tr in srv.tracer.traces(model=model, outcome="event"):
        for sp in tr.spans:
            if sp["stage"] == "rollout":
                evs.append(sp["tags"])
    return evs


# ------------------------------------------------------------- splitter
def test_hash_frac_is_deterministic_and_uniform():
    keys = ["req-%d" % i for i in range(4000)]
    fracs = [_hash_frac(k) for k in keys]
    assert fracs == [_hash_frac(k) for k in keys]     # stable
    assert all(0.0 <= f < 1.0 for f in fracs)
    # roughly uniform: the 1% canary band gets ~1% of keys
    band = sum(1 for f in fracs if f < 0.01)
    assert 10 <= band <= 90, band


def test_stage_ladder_shape():
    assert [s for s, _ in STAGES] == ["shadow", "1", "10", "50", "100"]
    fracs = [f for _, f in STAGES]
    assert fracs == sorted(fracs) and fracs[0] == 0.0 and fracs[-1] == 1.0


# ------------------------------------------------------ start validation
def test_start_validates_model_knobs_stage_and_duplicates(tiny):
    srv = ModelServer([_cfg(tiny)], drain_on_preemption=False).start(
        warm=True)
    try:
        mgr = RolloutManager.attach(srv)
        assert RolloutManager.attach(srv) is mgr       # idempotent
        with pytest.raises(MXNetError):
            mgr.start("ghost", "v2")
        with pytest.raises(MXNetError):
            mgr.start("m", "v2", not_a_knob=1)
        with pytest.raises(MXNetError):
            mgr.start("m", "v2", stage="99")
        with pytest.raises(MXNetError):
            mgr.start("m", "v2", tier="fp16")
        ro = mgr.start("m", "v2", dwell_s=60.0)
        with pytest.raises(MXNetError):                # one per model
            mgr.start("m", "v3")
        _wait_serving(srv)
        mgr.abort("m")
        assert ro.state == "aborted"
        # terminal state: a new rollout may start
        ro2 = mgr.start("m", "v3", dwell_s=60.0)
        _wait_serving(srv)
        mgr.abort("m")
        assert ro2.state == "aborted"
    finally:
        srv.close(timeout=10.0)


# ------------------------------------------------- happy-path promotion
def test_happy_path_auto_promotes_to_100_and_hot_swaps(tiny):
    """A good canary (identical weights) ramps shadow -> 1 -> 10 -> 50
    -> 100 on evidence alone, then hot-swaps in with zero dropped
    requests: every submitted request is answered ok and correct, the
    outcome taxonomy sums to the submissions, and the swapped state
    serves the new version id."""
    sym_json, pbytes, feat, ref = tiny
    srv = ModelServer([_cfg(tiny)], drain_on_preemption=False).start(
        warm=True)
    payload = np.zeros(feat, np.float32)
    before = catalog.SERVE_REQUESTS.value(model="m", outcome="ok")
    try:
        mgr = RolloutManager.attach(srv)
        # identical weights: a p99 gap between the two versions is the
        # box's load, not the canary's doing — the latency gate has its
        # own test (test_latency_storm_canary_trips_p99_gate), so here
        # its slack is wide enough that only the ramp is under test
        ro = mgr.start("m", "v2", dwell_s=0.05, min_shadow=3,
                       min_requests=2, shadow_sample=0.5, p99_slack=1e3)
        _wait_serving(srv)
        submitted = ok = 0
        deadline = time.monotonic() + 60.0
        while ro.state == "serving" and time.monotonic() < deadline:
            got = _pump(srv, payload, 20)
            submitted += 20
            ok += got["ok"]
            assert got["error"] == 0
        assert ro.state == "promoted", ro.status()
        assert ok == submitted
        _wait(lambda: ro.retired, msg="canary retirement")

        # the hot-swap is live: the incumbent slot now serves v2 and
        # still answers correctly (identical weights -> identical math)
        st = srv._models["m"]
        assert st.rollout_version == "v2"
        assert mgr.status()["live"] == {"m": "v2"}
        f = srv.submit("m", payload)
        np.testing.assert_allclose(f.result(30.0), ref(payload),
                                   rtol=1e-4, atol=1e-5)

        # full ramp history, in order, edge-triggered (one entry each)
        actions = [h["action"] for h in ro.history]
        assert actions == ["start", "serving", "stage", "stage", "stage",
                           "stage", "promoted", "retired"]
        stages = [h["stage"] for h in ro.history if h["action"] == "stage"]
        assert stages == ["1", "10", "50", "100"]

        # proof from telemetry: version-attributed requests for both the
        # incumbent and the canary, agreement published, stage gauge at
        # the top of the ladder
        assert catalog.ROLLOUT_VERSION_REQUESTS.value(
            model="m", version="v2", outcome="ok") > 0
        assert catalog.ROLLOUT_VERSION_REQUESTS.value(
            model="m", version="v0", outcome="ok") > 0
        assert catalog.ROLLOUT_STAGE.value(model="m") == len(STAGES) - 1
        agreement = catalog.ROLLOUT_SHADOW_AGREEMENT.value(model="m")
        assert agreement is not None and agreement > 0.99
        # ok-counter delta covers every submission (nothing vanished in
        # the swap) — the zero-downtime invariant, from the registry
        d = catalog.SERVE_REQUESTS.value(model="m", outcome="ok") - before
        assert d == ok + 1
        ramps = [e.get("ramp") for e in _rollout_events(srv)
                 if e["action"] == "stage"]
        assert ramps == ["1", "10", "50", "100"]
    finally:
        srv.close(timeout=10.0)


# ------------------------------------------- THE chaos acceptance test
@pytest.mark.chaos
def test_bad_canary_storm_auto_rolls_back_incumbent_unharmed(
        tiny, tiny2, monkeypatch):
    """THE acceptance test: a canary with silently-skewed answers under
    a request storm. The shadow-agreement gate must roll it back
    automatically; the incumbent must never notice: zero deadline
    violations, zero client-visible canary answers, incumbent back at
    100% of traffic and still correct afterwards. Proven from counter
    deltas, trace-ring rollout events and /rolloutz state — the whole
    run under the lock-order sanitizer with zero findings."""
    from mxnet_tpu.analysis import lockwatch

    monkeypatch.setenv("MXNET_LOCKCHECK", "1")   # before any lock is made
    lockwatch.reset()
    sym_json, pbytes, feat, ref = tiny
    _, pbytes2, _, _ = tiny2
    srv = ModelServer([_cfg(tiny)], drain_on_preemption=False).start(
        warm=True)
    payload = np.zeros(feat, np.float32)
    rb_before = catalog.ROLLOUT_ROLLBACKS.value(reason="agreement")
    ok_before = catalog.SERVE_REQUESTS.value(model="m", outcome="ok")
    v2_before = {oc: catalog.ROLLOUT_VERSION_REQUESTS.value(
        model="m", version="v2", outcome=oc)
        for oc in ("ok", "error", "shed", "expired")}
    try:
        mgr = RolloutManager.attach(srv)
        ro = mgr.start("m", "v2", param_bytes=pbytes2, dwell_s=60.0,
                       shadow_sample=0.6, min_shadow=8,
                       min_agreement=0.98)
        _wait_serving(srv)
        rng = np.random.RandomState(11)
        varied = lambda: rng.randn(*feat).astype(np.float32)  # noqa: E731
        with schaos.bad_canary(srv, "m", mode="skew") as chaos:
            storm = schaos.request_storm(srv, "m", varied, qps=300,
                                         duration_s=1.0, threads=4)
            _wait(lambda: ro.state == "rolled_back", 30.0,
                  "agreement gate to roll the canary back")
        assert chaos["calls"] >= 1
        assert ro.last_reason == "agreement"
        agreement = ro.agreement()
        assert agreement is not None and agreement < 0.98

        # rollback is edge-triggered: exactly one counter bump, one
        # trace-ring rollback event with the failing stage + reason
        assert catalog.ROLLOUT_ROLLBACKS.value(
            reason="agreement") - rb_before == 1
        # (the ring is process-global: filter to THIS rollout's reason)
        rb_events = [e for e in _rollout_events(srv)
                     if e["action"] == "rollback"
                     and e.get("reason") == "agreement"]
        assert len(rb_events) == 1
        assert rb_events[0]["version"] == "v2"
        assert rb_events[0]["ramp"] == "shadow"

        # the canary NEVER answered a client (shadow never promotes a
        # canary answer), and its executables are gone after retirement
        _wait(lambda: ro.retired, msg="canary retirement")
        for oc in ("ok", "error", "shed", "expired"):
            assert catalog.ROLLOUT_VERSION_REQUESTS.value(
                model="m", version="v2",
                outcome=oc) - v2_before[oc] == 0
        assert ro.canary.cache is None
        assert ro.fraction == 0.0
        assert catalog.ROLLOUT_STAGE.value(model="m") == -1

        # the incumbent never dispatched expired work and is back at
        # 100%: fresh traffic all lands on it, all correct
        st = srv.stats("m")
        assert st["deadline_violations"] == 0
        assert st["rollout"]["state"] == "rolled_back"
        got = _pump(srv, payload, 30)
        assert got == {"ok": 30, "error": 0}
        f = srv.submit("m", payload)
        np.testing.assert_allclose(f.result(30.0), ref(payload),
                                   rtol=1e-4, atol=1e-5)
        d_ok = catalog.SERVE_REQUESTS.value(model="m",
                                            outcome="ok") - ok_before
        assert d_ok >= storm["ok"] + 31
    finally:
        srv.close(timeout=10.0)
    lockwatch.assert_no_findings()


@pytest.mark.chaos
def test_faulting_canary_at_ten_percent_rolls_back(tiny):
    """Deterministic canary faults at the 10% stage: the error-rate /
    breaker gate rolls back; incumbent-routed requests never fail."""
    sym_json, pbytes, feat, ref = tiny
    srv = ModelServer([_cfg(tiny)], drain_on_preemption=False).start(
        warm=True)
    payload = np.zeros(feat, np.float32)
    try:
        mgr = RolloutManager.attach(srv)
        ro = mgr.start("m", "v2", stage="10", dwell_s=60.0,
                       shadow_sample=0.0, max_error_frac=0.05)
        _wait_serving(srv)
        with schaos.bad_canary(srv, "m", mode="fault"):
            deadline = time.monotonic() + 30.0
            while ro.state == "serving" and time.monotonic() < deadline:
                _pump(srv, payload, 25)
        assert ro.state == "rolled_back", ro.status()
        assert ro.last_reason in ("error_rate", "breaker")
        _wait(lambda: ro.retired, msg="canary retirement")
        # canary ok-answers can predate the fault injection window, but
        # after rollback the version serves nothing more
        errs = catalog.ROLLOUT_VERSION_REQUESTS.value(
            model="m", version="v2", outcome="error")
        sheds = catalog.ROLLOUT_VERSION_REQUESTS.value(
            model="m", version="v2", outcome="shed")
        assert errs + sheds >= 1
        got = _pump(srv, payload, 20)
        assert got == {"ok": 20, "error": 0}
        assert srv.stats("m")["deadline_violations"] == 0
    finally:
        srv.close(timeout=10.0)


@pytest.mark.chaos
def test_latency_storm_canary_trips_p99_gate(tiny):
    """A canary that answers correctly but slowly (latency storm) at the
    50% stage: the p99-vs-incumbent delta gate rolls it back."""
    sym_json, pbytes, feat, _ = tiny
    srv = ModelServer([_cfg(tiny)], drain_on_preemption=False).start(
        warm=True)
    payload = np.zeros(feat, np.float32)
    try:
        mgr = RolloutManager.attach(srv)
        ro = mgr.start("m", "v2", stage="50", dwell_s=60.0,
                       shadow_sample=0.0, p99_slack=0.5)
        _wait_serving(srv)
        with schaos.bad_canary(srv, "m", mode="latency", delay=0.05):
            deadline = time.monotonic() + 40.0
            while ro.state == "serving" and time.monotonic() < deadline:
                got = _pump(srv, payload, 20)
                assert got["error"] == 0    # slow, not wrong
        assert ro.state == "rolled_back", ro.status()
        assert ro.last_reason in ("p99_delta", "slo_burn")
        assert srv.stats("m")["deadline_violations"] == 0
    finally:
        srv.close(timeout=10.0)


def test_rollback_disabled_flies_blind_with_edge_triggered_events(
        tiny, tiny2):
    """rollback=False (the configuration MXL-T220 flags): the gate still
    evaluates but only records ONE gate_failed event per distinct
    reason — no transition, the canary keeps serving."""
    _, pbytes2, feat, _ = tiny2
    srv = ModelServer([_cfg(tiny)], drain_on_preemption=False).start(
        warm=True)
    payload = np.zeros(feat, np.float32)
    try:
        mgr = RolloutManager.attach(srv)
        ro = mgr.start("m", "v2", param_bytes=pbytes2, dwell_s=60.0,
                       shadow_sample=0.6, min_shadow=4, rollback=False,
                       auto=False)
        _wait_serving(srv)
        rng = np.random.RandomState(5)
        deadline = time.monotonic() + 30.0
        while ro.last_reason != "agreement" \
                and time.monotonic() < deadline:
            _pump(srv, payload, 10, rng=rng)
        assert ro.state == "serving"        # still up: flying blind
        assert ro.last_reason == "agreement"
        _pump(srv, payload, 20, rng=rng)    # more gate ticks, same reason
        fails = [h for h in ro.history if h["action"] == "gate_failed"]
        assert len(fails) == 1              # edge-triggered
        mgr.rollback("m", reason="operator")
        assert ro.state == "rolled_back"
        assert catalog.ROLLOUT_ROLLBACKS.value(reason="operator") >= 1
    finally:
        srv.close(timeout=10.0)


# -------------------------------------------------- memory-safe loading
def test_canary_refused_when_hbm_budget_would_be_exceeded(tiny):
    """A canary that does not fit next to the resident versions is
    REFUSED at load with the typed memory error in its status — the
    incumbent keeps serving, nothing OOMs."""
    sym_json, pbytes, feat, ref = tiny
    srv = ModelServer([_cfg(tiny)], drain_on_preemption=False).start(
        warm=True)
    payload = np.zeros(feat, np.float32)
    before = catalog.MEM_REFUSALS.value(reason="rollout")
    try:
        mgr = RolloutManager.attach(srv)
        with schaos.hbm_pressure(budget_bytes=1):
            ro = mgr.start("m", "v2", dwell_s=60.0)
            _wait(lambda: ro.state == "refused", msg="memory refusal")
        assert "HBM budget" in (ro.error or "")
        assert ro.status()["state"] == "refused"
        assert [h["action"] for h in ro.history] == ["start", "refused"]
        assert ro.history[-1]["reason"] == "MemoryBudgetExceeded"
        assert catalog.MEM_REFUSALS.value(reason="rollout") - before == 1
        f = srv.submit("m", payload)        # incumbent untouched
        np.testing.assert_allclose(f.result(30.0), ref(payload),
                                   rtol=1e-4, atol=1e-5)
    finally:
        srv.close(timeout=10.0)


# --------------------------------------------------- bad_canary guards
def test_bad_canary_requires_live_canary_and_known_mode(tiny):
    srv = ModelServer([_cfg(tiny)], drain_on_preemption=False).start(
        warm=True)
    try:
        from mxnet_tpu.resilience.chaos import ChaosError
        with pytest.raises(ChaosError):
            with schaos.bad_canary(srv, "m"):
                pass                        # no rollout in flight
        RolloutManager.attach(srv).start("m", "v2", dwell_s=60.0)
        _wait_serving(srv)
        with pytest.raises(ChaosError):
            with schaos.bad_canary(srv, "m", mode="wat"):
                pass
        srv._rollout.abort("m")
    finally:
        srv.close(timeout=10.0)


# --------------------------------------------------------------- http
def test_rolloutz_endpoints_drive_a_full_rollout(tiny):
    sym_json, pbytes, feat, _ = tiny
    srv = ModelServer([_cfg(tiny)], drain_on_preemption=False).start(
        warm=True)
    ep = ServingEndpoints(srv, port=0).start()
    base = "http://127.0.0.1:%d" % ep.port

    def _get(path):
        return json.loads(urllib.request.urlopen(
            base + path, timeout=10).read())

    def _post(doc):
        req = urllib.request.Request(
            base + "/rolloutz", data=json.dumps(doc).encode(),
            headers={"Content-Type": "application/json"})
        return json.loads(urllib.request.urlopen(req, timeout=30).read())

    try:
        # rollout mode off: /rolloutz is a typed 404, /healthz untouched
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/rolloutz", timeout=10)
        assert ei.value.code == 404
        doc = _post({"action": "start", "model": "m", "version": "v2",
                     "param_b64": base64.b64encode(pbytes).decode(),
                     "knobs": {"dwell_s": 60.0, "shadow_sample": 0.5}})
        assert doc["version"] == "v2" and doc["state"] in ("loading",
                                                           "serving")
        _wait_serving(srv)
        status = _get("/rolloutz")
        assert status["rollouts"]["m"]["state"] == "serving"
        assert status["stages"] == [s for s, _ in STAGES]
        # duplicate start -> 409; unknown model -> 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post({"action": "start", "model": "m", "version": "v3"})
        assert ei.value.code == 409
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post({"action": "promote", "model": "ghost"})
        assert ei.value.code == 404
        # operator promote walks the ladder; operator rollback is typed
        assert _post({"action": "promote", "model": "m"})["stage"] == "1"
        doc = _post({"action": "rollback", "model": "m",
                     "reason": "operator"})
        assert doc["state"] == "rolled_back"
        assert _get("/rolloutz")["rollouts"]["m"]["state"] == "rolled_back"
    finally:
        ep.stop()
        srv.close(timeout=10.0)


# ------------------------------------------------------- HLO invariance
def _stablehlo_text(srv, model, bucket):
    import jax
    pred = srv._models[model].cache.get(bucket)
    ex = pred._exec
    fn = ex._compiled(False)
    if not hasattr(fn, "lower"):
        pytest.skip("eager executor: no lowered program to compare")
    inputs = {n: a._data for n, a in ex.arg_dict.items()}
    inputs.update({n: a._data for n, a in ex.aux_dict.items()})
    return fn.lower(inputs, jax.random.PRNGKey(0)).as_text()


def test_served_stablehlo_identical_with_rollout_machinery_on(tiny):
    """The zero-overhead claim, at the program level: attaching the
    rollout manager and running a rollout to the shadow stage changes
    NOTHING about the incumbent's served executable — its StableHLO is
    bitwise identical to a rollout-less server's."""
    srv_off = ModelServer([_cfg(tiny)], drain_on_preemption=False).start(
        warm=True)
    try:
        hlo_off = _stablehlo_text(srv_off, "m", 4)
    finally:
        srv_off.close(timeout=10.0)

    srv_on = ModelServer([_cfg(tiny)], drain_on_preemption=False).start(
        warm=True)
    try:
        mgr = RolloutManager.attach(srv_on)
        ro = mgr.start("m", "v2", dwell_s=60.0)
        _wait_serving(srv_on)
        hlo_on = _stablehlo_text(srv_on, "m", 4)
        assert hlo_on == hlo_off            # bitwise, not "equivalent"
        mgr.abort("m")
        _wait(lambda: ro.retired, msg="canary retirement")
        assert _stablehlo_text(srv_on, "m", 4) == hlo_off
    finally:
        srv_on.close(timeout=10.0)


# ------------------------------------------------------- drain contract
def test_server_drain_closes_canary_queue_and_sweeps_it(tiny):
    srv = ModelServer([_cfg(tiny)], drain_on_preemption=False).start(
        warm=True)
    mgr = RolloutManager.attach(srv)
    ro = mgr.start("m", "v2", dwell_s=60.0)
    _wait_serving(srv)
    can = ro.canary
    srv.begin_drain()
    assert srv.drain(timeout=15.0)
    assert can.queue._closed
    assert not can.worker.is_alive()
    srv.close(timeout=10.0)


def test_offline_agreement_harness_reuses_quant_flow(tiny, tiny2):
    """evaluate_agreement() re-runs the quant accuracy harness over the
    buffered shadow inputs: identical weights agree at 1.0, skewed
    weights don't."""
    _, pbytes2, feat, _ = tiny2
    srv = ModelServer([_cfg(tiny)], drain_on_preemption=False).start(
        warm=True)
    payload_rng = np.random.RandomState(7)
    try:
        mgr = RolloutManager.attach(srv)
        ro = mgr.start("m", "v2", param_bytes=pbytes2, dwell_s=60.0,
                       shadow_sample=1.0, min_shadow=4, rollback=False,
                       auto=False)
        _wait_serving(srv)
        deadline = time.monotonic() + 30.0
        while len(ro.shadow_inputs) < 4 and time.monotonic() < deadline:
            futs = [srv.submit(
                "m", payload_rng.randn(*feat).astype(np.float32))
                for _ in range(8)]
            for f in futs:
                f.result(30.0)
        assert len(ro.shadow_inputs) >= 4
        report = ro.evaluate_agreement()
        assert report is not None
        # harness convention: incumbent rides the fp32 slot (accuracy
        # 1.0 by construction), candidate the quantized slot — its
        # "int8_acc" IS top-1 agreement with the incumbent
        assert report["n"] >= 4
        assert report["fp32_acc"] == 1.0
        assert 0.0 <= report["int8_acc"] <= 1.0
        mgr.abort("m")
    finally:
        srv.close(timeout=10.0)


def test_perfwatch_normalizes_rollout_metrics():
    """perfwatch reads the rollout gate surface: worst-model shadow
    agreement (up-is-good) and total rollbacks (down-is-good) from a
    telemetry snapshot, and the agreement riding a loadgen
    --during-rollout serving ledger row."""
    from mxnet_tpu.observability import perfwatch as pw
    snap = {"metrics": {
        "mxtpu_rollout_shadow_agreement": {"type": "gauge", "series": [
            {"labels": {"model": "a"}, "value": 0.99},
            {"labels": {"model": "b"}, "value": 0.91}]},
        "mxtpu_rollout_rollbacks_total": {"type": "counter", "series": [
            {"labels": {"reason": "agreement"}, "value": 2},
            {"labels": {"reason": "slo_burn"}, "value": 1}]}}}
    n = pw.normalize(snap)
    assert n["metrics"]["rollout_agreement"] == 0.91     # worst model
    assert n["metrics"]["rollout_rollbacks"] == 3.0
    base = {"metrics": {"rollout_agreement": 0.99,
                        "rollout_rollbacks": 1.0}}
    assert pw.compare({"metrics": {"rollout_agreement": 0.80}},
                      base)["status"] == "regression"
    assert pw.compare({"metrics": {"rollout_rollbacks": 5.0}},
                      base)["status"] == "regression"
    assert pw.compare({"metrics": {"rollout_agreement": 1.0,
                                   "rollout_rollbacks": 0.0}},
                      base)["status"] == "ok"
    row = {"label": "serving", "qps": 100.0, "p99_ms": 5.0,
           "rollout": {"agreement": 0.97, "state": "promoted"}}
    norm = pw.normalize(row)
    assert norm["kind"] == "serving_row"
    assert norm["metrics"]["rollout_agreement"] == 0.97
