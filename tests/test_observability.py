"""Observability subsystem tests (the ``obs`` marker).

Covers the ISSUE-3 contract: registry semantics (labels, buckets,
concurrency), span → profiler round trip, flight-recorder crash dumps
(including a chaos-injected watchdog timeout), Prometheus text-format
golden output, the built-in trainer/checkpoint/kvstore instrumentation —
and the overhead guard: with telemetry disabled, the fused step's compiled
HLO is bitwise identical and no registry series move.
"""
import json
import os
import sys
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, observability as obs, parallel, profiler
from mxnet_tpu.gluon import nn
from mxnet_tpu.observability import catalog, flight_recorder, metrics
from mxnet_tpu.observability.metrics import MetricsRegistry
from mxnet_tpu.resilience import ResilientTrainer, chaos

pytestmark = pytest.mark.obs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_net(prefix):
    mx.random.seed(11)
    net = nn.HybridSequential(prefix=prefix)
    net.add(nn.Dense(8, activation="relu", prefix=prefix + "d0_"),
            nn.Dense(3, prefix=prefix + "d1_"))
    net.initialize(mx.init.Xavier())
    return net


def _batch(b=16, d=6):
    rng = np.random.RandomState(42)
    return (rng.randn(b, d).astype("f4"),
            rng.randint(0, 3, (b,)).astype("f4"))


# ----------------------------------------------------------------- registry
def test_counter_labels_and_values():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "help")
    c.inc(); c.inc(2, worker="0"); c.inc(worker="0"); c.inc(worker="1")
    assert c.value() == 1
    assert c.value(worker="0") == 3
    assert c.value(worker="1") == 1
    # label order must not create distinct series
    c2 = reg.counter("c2_total")
    c2.inc(a="1", b="2"); c2.inc(b="2", a="1")
    assert c2.value(b="2", a="1") == 2


def test_gauge_set_inc_dec():
    reg = MetricsRegistry()
    g = reg.gauge("g")
    assert g.value() is None
    g.set(5.0); g.inc(2); g.dec()
    assert g.value() == 6.0


def test_histogram_buckets_sum_count_max():
    reg = MetricsRegistry()
    h = reg.histogram("h_ms", buckets=(1, 10, 100))
    for v in (0.5, 0.9, 5, 50, 5000):
        h.observe(v)
    [s] = h.series()
    assert s["count"] == 5 and s["max"] == 5000
    assert s["sum"] == pytest.approx(5056.4)
    # cumulative le-semantics: le=1 → 2, le=10 → 3, le=100 → 4, +Inf → 5
    assert s["buckets"] == {"1": 2, "10": 3, "100": 4, "+Inf": 5}


def test_histogram_boundary_value_lands_in_its_bucket():
    reg = MetricsRegistry()
    h = reg.histogram("hb", buckets=(10,))
    h.observe(10)          # le=10 includes 10 (prometheus semantics)
    [s] = h.series()
    assert s["buckets"]["10"] == 1


def test_get_or_create_idempotent_and_type_checked():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(mx.MXNetError, match="already registered"):
        reg.gauge("x")


def test_concurrent_increments_sum_exactly():
    reg = MetricsRegistry()
    c = reg.counter("threads_total")
    h = reg.histogram("threads_ms", buckets=(10,))
    n, per = 8, 500

    def work():
        for _ in range(per):
            c.inc(thread="shared")
            h.observe(1.0)

    threads = [threading.Thread(target=work) for _ in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value(thread="shared") == n * per
    [s] = h.series()
    assert s["count"] == n * per and s["buckets"]["10"] == n * per


def test_snapshot_contains_full_catalog():
    """Pre-declared families appear in every snapshot even with no series —
    a scraper never sees a 404-shaped absence."""
    snap = obs.snapshot()
    for fam in ("mxtpu_trainer_step_ms", "mxtpu_kv_publish_ms",
                "mxtpu_checkpoint_save_ms", "mxtpu_span_ms",
                "mxtpu_jit_traces_total",
                "mxtpu_quant_calib_batches_total", "mxtpu_quant_nodes",
                "mxtpu_quant_acc_delta",
                "mxtpu_quant_serve_requests_total"):
        assert fam in snap["metrics"], fam


def test_prometheus_text_format_golden():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests")
    c.inc(3, code="200"); c.inc(code='he"llo')
    g = reg.gauge("temp")
    g.set(1.5)
    h = reg.histogram("lat_ms", "latency", buckets=(1, 10))
    h.observe(0.5); h.observe(7); h.observe(70)
    assert reg.render_prometheus() == (
        '# HELP lat_ms latency\n'
        '# TYPE lat_ms histogram\n'
        'lat_ms_bucket{le="1"} 1\n'
        'lat_ms_bucket{le="10"} 2\n'
        'lat_ms_bucket{le="+Inf"} 3\n'
        'lat_ms_sum 77.5\n'
        'lat_ms_count 3\n'
        '# HELP req_total requests\n'
        '# TYPE req_total counter\n'
        'req_total{code="200"} 3\n'
        'req_total{code="he\\"llo"} 1\n'
        '# TYPE temp gauge\n'
        'temp 1.5\n')


def test_write_snapshot_formats(tmp_path):
    j = str(tmp_path / "m.json")
    p = str(tmp_path / "m.prom")
    obs.write_snapshot(j)
    obs.write_snapshot(p)
    assert json.load(open(j))["version"] == 1
    assert "# TYPE" in open(p).read()


def test_exporter_thread_writes_and_stops(tmp_path):
    path = str(tmp_path / "exp.json")
    assert metrics.start_exporter(path, interval=0.05)
    assert metrics.start_exporter(path, interval=0.05)   # idempotent
    metrics.stop_exporter()                              # final snapshot
    doc = json.load(open(path))
    assert doc["version"] == 1 and "mxtpu_trainer_step_ms" in doc["metrics"]
    metrics.stop_exporter()                              # idempotent


def test_enabled_tracks_env(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    assert not metrics.enabled()
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    assert metrics.enabled()


# -------------------------------------------------------------------- spans
def test_span_feeds_histogram_and_profiler(tmp_path):
    profiler.set_config(profile_all=True,
                        filename=str(tmp_path / "t.json"))
    profiler.start()
    h0 = obs.spans.SPAN_MS.count(span="obs_rt")
    with obs.span("obs_rt", category="test"):
        pass
    profiler.stop()
    assert obs.spans.SPAN_MS.count(span="obs_rt") == h0 + 1
    profiler.dump(finished=True)
    trace = json.load(open(str(tmp_path / "t.json")))
    names = [e.get("name") for e in trace["traceEvents"]]
    assert "obs_rt" in names


def test_span_decorator_and_active_stack():
    seen = {}

    @obs.span("outer_span")
    def fn():
        with obs.span("inner_span"):
            seen["active"] = obs.active_spans()
        return 7

    n0 = obs.spans.SPAN_MS.count(span="outer_span")
    assert fn() == 7
    assert seen["active"] == ("outer_span", "inner_span")
    assert obs.active_spans() == ()
    assert obs.spans.SPAN_MS.count(span="outer_span") == n0 + 1


def test_span_noop_when_disabled(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    n0 = obs.spans.SPAN_MS.count(span="dis_span")
    with obs.span("dis_span"):
        assert obs.active_spans() == ()
    assert obs.spans.SPAN_MS.count(span="dis_span") == n0


def test_profiler_pause_resume_refcounted(tmp_path):
    """Satellite: nested pause/resume — a library resume inside a user
    pause must NOT restart recording."""
    profiler.set_config(profile_all=True, filename=str(tmp_path / "p.json"))
    profiler.start()
    assert profiler.recording()
    profiler.pause()            # user
    profiler.pause()            # library span bracketing its own pause
    profiler.resume()           # library resume — still user-paused
    assert not profiler.recording()
    profiler.resume()
    assert profiler.recording()
    profiler.resume()           # extra resumes never go negative
    profiler.pause()
    assert not profiler.recording()
    profiler.resume()
    profiler.stop()


def test_profiler_aggregate_dump_mode(tmp_path):
    """Satellite: dump() with aggregate_stats writes the count/total/mean/
    max table next to the chrome trace."""
    fn = str(tmp_path / "agg.json")
    profiler.set_config(profile_all=True, filename=fn, aggregate_stats=True)
    profiler.start()
    profiler.record_event("op_a", "operator", 0.0, 10.0)
    profiler.record_event("op_a", "operator", 10.0, 30.0)
    profiler.record_event("op_b", "operator", 0.0, 5.0)
    profiler.stop()
    profiler.dump(finished=True)
    table = open(fn + ".aggregate.txt").read()
    assert "Max(us)" in table
    lines = [l for l in table.splitlines() if l.startswith("op_a")]
    assert len(lines) == 1
    calls, total, mean, mx_ = lines[0].split()[-4:]
    assert (calls, total, mean, mx_) == ("2", "40.0", "20.0", "30.0")


# ---------------------------------------------------------- flight recorder
def test_flight_recorder_ring_and_dump(tmp_path):
    fr = flight_recorder.FlightRecorder(capacity=3)
    for i in range(5):
        fr.record(i, loss=float(i), step_ms=1.0)
    assert len(fr) == 3
    path = fr.dump(path=str(tmp_path / "f.json"), reason="unit")
    doc = json.load(open(path))
    assert [r["step"] for r in doc["records"]] == [2, 3, 4]
    assert doc["reason"] == "unit" and doc["version"] == 1


def test_flight_recorder_resolves_device_scalars_lazily(tmp_path):
    import jax.numpy as jnp
    fr = flight_recorder.FlightRecorder(capacity=4)
    fr.record(1, loss=jnp.float32(2.5), step_ms=1.0)
    doc = json.load(open(fr.dump(path=str(tmp_path / "f.json"))))
    assert doc["records"][0]["loss"] == 2.5


def test_flight_recorder_disabled_no_records_no_dump(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    fr = flight_recorder.FlightRecorder(capacity=4)
    fr.record(1, loss=1.0)
    assert len(fr) == 0
    assert fr.dump(path=str(tmp_path / "no.json")) is None
    assert not os.path.exists(str(tmp_path / "no.json"))


@pytest.mark.chaos
def test_watchdog_timeout_dumps_flight_recorder(tmp_path, monkeypatch):
    """Acceptance: a chaos-injected hang trips the step watchdog, which
    appends the recorder tail to the stack dump and writes the JSON
    artifact; its last record is the final COMPLETED step."""
    fpath = str(tmp_path / "wd_flight.json")
    monkeypatch.setenv("MXNET_TELEMETRY_FLIGHT_PATH", fpath)
    flight_recorder.get_recorder().clear()
    x, y = _batch()
    rt = ResilientTrainer(
        _make_net("obswd_"), gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1}, directory=str(tmp_path / "run"),
        preemption=False, retry=False, step_deadline=120.0)
    fired0 = catalog.WATCHDOG_FIRED.value()
    # the healthy steps compile: beside five other workers that takes more
    # than the second the hung step gets, and an interrupt out here ends
    # the worker and with it the whole run
    for _ in range(3):
        rt.step(x, y)
    rt._watchdog.deadline = 1.0
    with chaos.hung_step(rt, hang=30.0) as st:
        with pytest.raises(KeyboardInterrupt):
            rt.step(x, y)
    assert st["hung"] == 1
    assert rt._watchdog.fired
    assert catalog.WATCHDOG_FIRED.value() == fired0 + 1
    doc = json.load(open(fpath))
    assert doc["reason"].startswith("watchdog_timeout")
    assert doc["records"][-1]["step"] == 3      # the hung step 4 never landed
    rt.close()


def test_trainer_exception_dumps_flight_recorder(tmp_path, monkeypatch):
    fpath = str(tmp_path / "exc_flight.json")
    monkeypatch.setenv("MXNET_TELEMETRY_FLIGHT_PATH", fpath)
    flight_recorder.get_recorder().clear()
    x, y = _batch()
    rt = ResilientTrainer(
        _make_net("obsexc_"), gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1}, directory=str(tmp_path / "run"),
        preemption=False, retry=False)
    rt.step(x, y)

    def boom(*a):
        raise RuntimeError("injected step failure")

    rt.trainer.step = boom
    with pytest.raises(RuntimeError, match="injected step failure"):
        rt.step(x, y)
    doc = json.load(open(fpath))
    assert doc["reason"].startswith("trainer_exception")
    assert doc["records"][-1]["step"] == 1
    assert doc["extra"]["step_count"] == 1
    rt.close()


# ------------------------------------------------- built-in instrumentation
def test_trainer_step_metrics_and_flight_records():
    flight_recorder.get_recorder().clear()
    x, y = _batch()
    t = parallel.DataParallelTrainer(
        _make_net("obst_"), gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1}, grad_guard=True)
    n0 = catalog.STEP_MS.count()
    s0 = catalog.SAMPLES_TOTAL.value()
    c0 = catalog.CAPTURES_TOTAL.value()
    for _ in range(3):
        t.step(x, y)
    # the step time is the entry-to-entry cadence: three steps have two
    assert catalog.STEP_MS.count() == n0 + 2
    assert catalog.SAMPLES_TOTAL.value() == s0 + 3 * 16
    assert catalog.CAPTURES_TOTAL.value() == c0 + 1
    assert catalog.SAMPLES_PER_SEC.value() > 0
    recs = flight_recorder.get_recorder().tail(3)
    assert [r["step"] for r in recs] == [1, 2, 3]
    # anomaly_stats drains the guard counters into gauges
    stats = t.anomaly_stats()
    assert catalog.GRAD_SKIPPED.value() == stats["grad_skipped_steps"]
    assert catalog.GRAD_NORM_EMA.value() == pytest.approx(
        stats["grad_norm_ema"])


def test_checkpoint_save_restore_verify_metrics(tmp_path):
    import jax.numpy as jnp
    from mxnet_tpu.checkpoint import ShardedCheckpointer
    ck = ShardedCheckpointer(str(tmp_path / "ck"))
    s0 = catalog.CKPT_SAVE_MS.count(mode="sync")
    r0 = catalog.CKPT_RESTORE_MS.count()
    b0 = catalog.CKPT_BYTES.value()
    v0 = catalog.CKPT_VERIFY_FAILURES.value()
    ck.save(1, {"w": jnp.ones((4, 4))})
    assert catalog.CKPT_SAVE_MS.count(mode="sync") == s0 + 1
    assert catalog.CKPT_BYTES.value() > b0
    assert catalog.CKPT_LAST_BYTES.value() > 0
    ck.restore(1)
    assert catalog.CKPT_RESTORE_MS.count() == r0 + 1
    assert ck.verify(1)
    assert catalog.CKPT_VERIFY_FAILURES.value() == v0
    chaos.tear_checkpoint(str(tmp_path / "ck"), 1, mode="truncate")
    assert not ck.verify(1)
    assert catalog.CKPT_VERIFY_FAILURES.value() == v0 + 1
    ck.close()


def test_kv_publish_latency_and_retry_metrics(monkeypatch):
    monkeypatch.setenv("MXNET_KV_RETRY_ATTEMPTS", "3")
    monkeypatch.setenv("MXNET_KV_RETRY_BASE", "0.001")
    monkeypatch.setenv("MXNET_KV_RETRY_JITTER", "0")
    kv = mx.kv.create("dist_sync")
    kv.init("obs_w", mx.nd.ones((2,)))
    p0 = catalog.KV_PUBLISH_MS.count()
    r0 = catalog.KV_PUBLISH_RETRIES.value()
    f0 = catalog.KV_PUBLISH_FAILURES.value()

    class FlakyClient:
        calls = 0

        def key_value_set_bytes(self, *a, **kw):
            FlakyClient.calls += 1
            if FlakyClient.calls == 1:
                raise RuntimeError("transient blip")

    kv._publish_weight_retry(FlakyClient(), "obs_w")
    # per-attempt latency: the failed first attempt counts too (an
    # incident's slow attempts must not be hidden from the histogram)
    assert catalog.KV_PUBLISH_MS.count() == p0 + 2
    assert catalog.KV_PUBLISH_RETRIES.value() == r0 + 1
    assert catalog.KV_PUBLISH_FAILURES.value() == f0

    class DeadClient:
        def key_value_set_bytes(self, *a, **kw):
            raise RuntimeError("down")

    with pytest.raises(mx.TransientKVError):
        kv._publish_weight_retry(DeadClient(), "obs_w")
    assert catalog.KV_PUBLISH_MS.count() == p0 + 2 + 3
    assert catalog.KV_PUBLISH_FAILURES.value() == f0 + 1
    assert catalog.KV_PUBLISH_RETRIES.value() == r0 + 1 + 3


def test_monitor_publishes_gauges_and_sorts_deterministically():
    from mxnet_tpu.monitor import Monitor
    mon = Monitor(1, sort=True)
    mon.tic()
    mon.queue.append((1, "zeta", 2.0))
    mon.queue.append((1, "alpha", 1.0))
    mon.queue.append((0, "zeta", 3.0))
    res = mon.toc()
    # (name, step) key: alpha first, then zeta step 0 before zeta step 1
    assert [(n, k) for n, k, _ in res] == [(1, "alpha"), (0, "zeta"),
                                           (1, "zeta")]
    assert catalog.MONITOR_STAT.value(stat="alpha") == 1.0
    # last write wins for the same stat name
    assert catalog.MONITOR_STAT.value(stat="zeta") == 2.0


def test_speedometer_emits_gauge(caplog):
    import logging
    from mxnet_tpu.callback import Speedometer
    from collections import namedtuple
    P = namedtuple("P", ["epoch", "nbatch", "eval_metric", "locals"])
    import time as _time
    sp = Speedometer(batch_size=32, frequent=2, auto_reset=False)
    with caplog.at_level(logging.INFO):
        for nb in range(1, 5):
            _time.sleep(0.002)     # real dt: the speed division needs one
            sp(P(epoch=0, nbatch=nb, eval_metric=None, locals=None))
    v = catalog.SPEEDOMETER_SPS.value()
    assert v is not None and v > 0
    # log line stays (format unchanged)
    assert any("samples/sec" in r.message for r in caplog.records)


# --------------------------------------------------------- overhead guards
def test_disabled_telemetry_moves_no_series(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    x, y = _batch()
    t = parallel.DataParallelTrainer(
        _make_net("obsoff_"), gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1})
    before = json.dumps(obs.snapshot()["metrics"], sort_keys=True)
    t.step(x, y)
    t.step(x, y)
    after = json.dumps(obs.snapshot()["metrics"], sort_keys=True)
    assert before == after


def test_step_hlo_identical_with_telemetry_on_off(monkeypatch):
    """Acceptance: telemetry must never enter the trace — the fused step
    lowered with MXNET_TELEMETRY=0 and =1 produces identical StableHLO."""
    import jax

    def lowered_text(prefix):
        x, y = _batch()
        t = parallel.DataParallelTrainer(
            _make_net(prefix), gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1}, grad_guard=True)
        t._capture(2, sample_arrays=[x, y])
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = NamedSharding(t._mesh, P(t._axis))
        ax = [jax.device_put(a, spec) for a in (x, y)]
        rng = jax.random.PRNGKey(0)
        return t._step_fn.lower(t._params, t._aux, t._opt_state,
                                t._guard_state, rng, *ax).as_text()

    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    on = lowered_text("hloa_")
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    off = lowered_text("hloa_")      # same prefix/seed => same param names
    assert on == off


@pytest.mark.lint
def test_instrumented_step_still_lints_clean():
    """Satellite self-check: the telemetry-instrumented fused step must not
    introduce host syncs (MXL-T201) or any other trace finding."""
    from mxnet_tpu import analysis
    sys.path.insert(0, os.path.join(ROOT, "example"))
    try:
        import resilient_training
    finally:
        sys.path.pop(0)
    spec = resilient_training.make_lint_spec()
    report = analysis.lint_trainer(spec["trainer"], *spec["data"])
    assert report.by_rule("MXL-T201") == []
    assert report.findings == [], report.to_text()


# ------------------------------------------------- perf observability (ISSUE 6)
from mxnet_tpu.observability import perfwatch as pw_mod, xcost  # noqa: E402


def test_roofline_classification_synthetic(monkeypatch):
    """Roofline math on synthetic cost dicts: intensity vs the ridge point
    decides compute- vs memory-bound; missing peaks degrade to unknown."""
    monkeypatch.setenv("MXNET_PERF_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("MXNET_PERF_PEAK_HBM_GBPS", "100")   # ridge = 10 F/B
    hi = xcost.analyze_cost({"flops": 1e9, "bytes accessed": 1e6},
                            device_kind="weird accelerator")
    assert hi["arithmetic_intensity"] == pytest.approx(1000.0)
    assert hi["ridge_intensity"] == pytest.approx(10.0)
    assert hi["roofline"] == "compute-bound"
    lo = xcost.analyze_cost({"flops": 5e6, "bytes accessed": 1e6},
                            device_kind="weird accelerator")
    assert lo["roofline"] == "memory-bound"
    assert lo["optimal_ms_compute"] == pytest.approx(5e6 / 1e12 * 1e3)
    assert lo["optimal_ms_memory"] == pytest.approx(1e6 / 1e11 * 1e3)
    monkeypatch.delenv("MXNET_PERF_PEAK_FLOPS")
    monkeypatch.delenv("MXNET_PERF_PEAK_HBM_GBPS")
    unk = xcost.analyze_cost({"flops": 1e6}, device_kind="cpu")
    assert unk["roofline"] == "unknown"
    # the shared device table is the bench table: per-chip bf16 peaks
    assert xcost.peak_flops("TPU v5 lite") == 197e12
    assert xcost.peak_flops("TPU v4") == 275e12
    assert xcost.peak_hbm_bw("TPU v5p") == 2765e9
    assert xcost.peak_flops("cpu") is None


def test_cost_ledger_append_read_and_corruption(tmp_path):
    led = xcost.CostLedger(str(tmp_path / "ledger.jsonl"))
    led.append({"label": "a", "fingerprint": "f1", "flops": 1.0})
    led.append({"label": "b", "fingerprint": "f2", "flops": 2.0})
    with open(led.path, "a") as f:
        f.write("{torn line never finishe\n")
    led.append({"label": "c", "fingerprint": "f1", "flops": 3.0})
    rows = led.rows()
    assert [r["label"] for r in rows] == ["a", "b", "c"]
    assert all(r["version"] == 1 and "time" in r and "pid" in r
               for r in rows)
    assert [r["flops"] for r in led.rows(fingerprint="f1")] == [1.0, 3.0]
    assert led.last()["label"] == "c"
    assert len(led) == 3
    assert xcost.CostLedger(str(tmp_path / "missing.jsonl")).rows() == []


def _perf_env(monkeypatch, tmp_path):
    path = str(tmp_path / "cost_ledger.jsonl")
    monkeypatch.setenv("MXNET_PERF_LEDGER", path)
    # the CPU backend is not in the device table: pin synthetic peaks so
    # roofline classification and MFU have a denominator
    monkeypatch.setenv("MXNET_PERF_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("MXNET_PERF_PEAK_HBM_GBPS", "100")
    return path


def test_jitted_step_persists_cost_row_and_live_perf_gauges(
        tmp_path, monkeypatch):
    """Acceptance: a jitted training step persists a CostLedger row (FLOPs,
    bytes, roofline class, executable fingerprint) and publishes live
    mxtpu_mfu / mxtpu_device_util / mxtpu_step_breakdown_ms gauges into a
    telemetry snapshot."""
    path = _perf_env(monkeypatch, tmp_path)
    x, y = _batch()
    t = parallel.DataParallelTrainer(
        _make_net("perfacc_"), gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1})
    for _ in range(4):
        t.step(x, y)
    rows = xcost.CostLedger(path).rows()
    assert len(rows) == 1          # once per executable, not per step
    row = rows[0]
    assert row["flops"] > 0 and row["bytes_accessed"] > 0
    assert row["roofline"] in ("compute-bound", "memory-bound")
    assert row["arithmetic_intensity"] == pytest.approx(
        row["flops"] / row["bytes_accessed"])
    assert len(row["fingerprint"]) == 64        # the aot StableHLO digest
    assert row["aot_key"]["in_shapes"]
    assert row["label"] == "DataParallelTrainer.step"
    # live gauges in the snapshot
    snap = obs.snapshot()["metrics"]

    def gauge(name, **labels):
        for s in snap[name]["series"]:
            if s["labels"] == {k: str(v) for k, v in labels.items()}:
                return s["value"]
        return None

    assert gauge("mxtpu_mfu") > 0
    assert 0.0 <= gauge("mxtpu_device_util") <= 1.0
    assert gauge("mxtpu_step_breakdown_ms", bucket="dispatch") > 0
    for bucket in ("h2d_transfer", "host_prep", "feed_stall", "host_other"):
        assert gauge("mxtpu_step_breakdown_ms", bucket=bucket) is not None
    # the counter moved and the trainer's own view agrees
    stats = t.perf_stats()
    assert stats["flops_per_step"] == row["flops"]
    assert stats["mfu"] > 0 and stats["steps"] == 4
    assert obs.catalog.COST_LEDGER_ROWS.value() >= 1


def test_perf_layer_distinct_executables_distinct_rows(tmp_path, monkeypatch):
    """A second input signature (re-capture) gets its own ledger row keyed
    by its own fingerprint."""
    path = _perf_env(monkeypatch, tmp_path)
    x, y = _batch()
    x2, y2 = _batch(b=8)
    t = parallel.DataParallelTrainer(
        _make_net("perfmulti_"), gluon.loss.SoftmaxCrossEntropyLoss(),
        "sgd", {"learning_rate": 0.1})
    t.step(x, y)
    t.step(x2, y2)      # batch 8: fresh signature, fresh executable
    rows = xcost.CostLedger(path).rows()
    assert len(rows) == 2
    assert rows[0]["fingerprint"] != rows[1]["fingerprint"]
    # MFU uses the stepped signature's OWN flops, not the last-captured
    # one: after returning to batch 16 the live value must match row 0
    assert t.perf_stats()["flops_per_step"] == rows[1]["flops"]
    t.step(x, y)
    assert t.perf_stats()["flops_per_step"] == rows[0]["flops"]


def test_kv_path_costs_the_programs_it_runs(tmp_path, monkeypatch):
    """The hybrid kv path never executes the fused step: its ledger row is
    the SUM of the grad + apply programs it actually dispatches, labeled
    kv_step, with a fingerprint derived from both."""
    path = _perf_env(monkeypatch, tmp_path)
    x, y = _batch()
    t = parallel.DataParallelTrainer(
        _make_net("perfkv_"), gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1}, kvstore=mx.kv.create("local"))
    for _ in range(3):
        t.step(x, y)
    rows = xcost.CostLedger(path).rows()
    assert len(rows) == 1
    assert rows[0]["label"] == "DataParallelTrainer.kv_step"
    assert rows[0]["flops"] > 0 and len(rows[0]["fingerprint"]) == 64
    assert t.perf_stats()["flops_per_step"] == rows[0]["flops"]


def test_attribution_off_no_breakdown_no_ledger_requirement(
        tmp_path, monkeypatch):
    """step_attribution=False publishes nothing and perf_stats is empty —
    but the cost ledger still captures (they are independent gates)."""
    path = _perf_env(monkeypatch, tmp_path)
    before = obs.catalog.STEP_BREAKDOWN.series()
    x, y = _batch()
    t = parallel.DataParallelTrainer(
        _make_net("perfoff_"), gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1}, step_attribution=False)
    t.step(x, y)
    t.step(x, y)
    assert t.perf_stats() == {}
    assert obs.catalog.STEP_BREAKDOWN.series() == before
    assert len(xcost.CostLedger(path).rows()) == 1


def test_step_hlo_identical_with_perf_layer_on_off(tmp_path, monkeypatch):
    """Acceptance: the perf layer is host-side only — the fused step
    lowered with the full perf stack live (ledger capturing, attribution
    publishing, real steps run) is bitwise identical StableHLO to a run
    with everything off."""
    import jax

    def lowered_text(prefix, on):
        if on:
            _perf_env(monkeypatch, tmp_path)
            monkeypatch.setenv("MXNET_TELEMETRY", "1")
        else:
            monkeypatch.setenv("MXNET_TELEMETRY", "0")
            monkeypatch.delenv("MXNET_PERF_LEDGER", raising=False)
        x, y = _batch()
        t = parallel.DataParallelTrainer(
            _make_net(prefix), gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1},
            step_attribution=None if on else False)
        t.step(x, y)        # the perf stack actually runs on-path
        t.step(x, y)
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = NamedSharding(t._mesh, P(t._axis))
        ax = [jax.device_put(a, spec) for a in (x, y)]
        rng = jax.random.PRNGKey(0)
        return t._step_fn.lower(t._params, t._aux, t._opt_state,
                                t._guard_state, rng, *ax).as_text()

    on = lowered_text("hlop_", True)
    off = lowered_text("hlop_", False)   # same prefix/seed => same names
    assert on == off


# ------------------------------------------------------- perfwatch (library)
def test_perfwatch_compare_directions():
    base = {"metrics": {"throughput": 100.0, "mfu": 0.2,
                        "flops_per_step": 1e9}}
    assert pw_mod.compare({"metrics": {"throughput": 95.0}},
                          base)["status"] == "ok"
    res = pw_mod.compare({"metrics": {"throughput": 89.9}}, base)
    assert res["status"] == "regression"
    [ch] = [c for c in res["checks"] if c["regressed"]]
    assert ch["metric"] == "throughput"
    # an improvement is never a regression, whatever its magnitude
    assert pw_mod.compare({"metrics": {"throughput": 300.0,
                                       "flops_per_step": 1e8}},
                          base)["status"] == "ok"
    # flops direction is inverted: a fatter step program regresses
    assert pw_mod.compare({"metrics": {"flops_per_step": 1.2e9}},
                          base)["status"] == "regression"
    # nothing shared = incomparable, never a silent pass
    assert pw_mod.compare({"metrics": {}}, base)["status"] == "incomparable"
    # per-metric threshold override
    assert pw_mod.compare({"metrics": {"mfu": 0.19}}, base,
                          thresholds={"mfu": 2.0})["status"] == "regression"


def test_perfwatch_normalize_artifacts(tmp_path):
    bench_row = {"metric": "m", "value": 2468.3, "mfu": 0.154,
                 "flops_per_step": 3.1e12, "unit": "img/s/chip"}
    n = pw_mod.normalize(bench_row)
    assert n["kind"] == "bench_row"
    assert n["metrics"] == {"throughput": 2468.3, "mfu": 0.154,
                            "flops_per_step": 3.1e12}
    # a wrapper that holds the row under "parsed"
    assert pw_mod.normalize({"parsed": bench_row})["kind"] == "bench_row"
    # ledger JSONL: last parseable row wins
    led = tmp_path / "l.jsonl"
    led.write_text(json.dumps({"roofline": "memory-bound", "flops": 1e9})
                   + "\n" +
                   json.dumps({"roofline": "compute-bound", "flops": 2e9})
                   + "\n")
    norm, err = pw_mod.load_artifact(str(led))
    assert err == "" and norm["kind"] == "ledger_row"
    assert norm["metrics"]["flops_per_step"] == 2e9
    # snapshot
    snap = {"metrics": {"mxtpu_mfu": {"type": "gauge", "series": [
        {"labels": {}, "value": 0.5}]}}}
    assert pw_mod.normalize(snap)["metrics"] == {"mfu": 0.5}


def test_perfwatch_live_hook_warns_and_counts():
    w = pw_mod.PerfWatch(baseline={"mfu": 0.5}, check_every=2)
    catalog.MFU.set(0.2)
    c0 = catalog.PERF_REGRESSIONS.value(metric="mfu")
    assert w.on_step(1) is None          # not on the cadence
    res = w.on_step(2)
    assert res["status"] == "regression" and res["step"] == 2
    assert catalog.PERF_REGRESSIONS.value(metric="mfu") == c0 + 1
    assert w.events and w.events[-1]["metric"] == "mfu"
    catalog.MFU.set(0.55)
    assert w.on_step(4)["status"] == "ok"
    assert catalog.PERF_REGRESSIONS.value(metric="mfu") == c0 + 1


def test_perfwatch_missing_baseline_disarms(tmp_path):
    w = pw_mod.PerfWatch(baseline=str(tmp_path / "nope.json"))
    assert w.baseline is None and w.baseline_error
    assert w.on_step(100) is None and w.check() is None


def test_resilient_trainer_perfwatch_hook(tmp_path):
    """ResilientTrainer(perfwatch=...) checks the live gauges on its step
    cadence and records the breach (warn-only: training continues)."""
    x, y = _batch()
    rt = ResilientTrainer(
        _make_net("perfrt_"), gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1}, directory=str(tmp_path / "run"),
        preemption=False, retry=False,
        perfwatch={"baseline": {"samples_per_sec": 1e15}, "check_every": 2})
    for _ in range(4):
        rt.step(x, y)
    assert rt.perfwatch.last_result["status"] == "regression"
    assert any(e["metric"] == "samples_per_sec" for e in rt.perfwatch.events)
    assert rt.step_count == 4            # warn-only, the loop kept going
    rt.close()
