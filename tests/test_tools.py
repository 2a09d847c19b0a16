"""tools/ tests: im2rec list+pack round-trip, launch env contract, diagnose
(reference: tools are exercised by example scripts + nightly jobs)."""
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import im2rec  # noqa: E402
import launch  # noqa: E402


def _make_images(root, classes=("cat", "dog"), per_class=3):
    from PIL import Image
    rng = np.random.RandomState(0)
    for ci, cls in enumerate(classes):
        d = os.path.join(root, cls)
        os.makedirs(d, exist_ok=True)
        for i in range(per_class):
            arr = rng.randint(0, 255, (16, 20, 3), dtype=np.uint8)
            Image.fromarray(arr).save(os.path.join(d, f"{cls}{i}.jpg"))


def test_im2rec_list_and_pack(tmp_path):
    pytest.importorskip("PIL")
    root = str(tmp_path / "imgs")
    _make_images(root)
    prefix = str(tmp_path / "data")
    im2rec.main([prefix, root, "--list", "--recursive"])
    lst = prefix + ".lst"
    assert os.path.exists(lst)
    rows = list(im2rec.read_list(lst))
    assert len(rows) == 6
    assert {int(l) for _, _, l in rows} == {0, 1}   # two class labels

    im2rec.main([prefix, root, "--resize", "16"])
    assert os.path.exists(prefix + ".rec") and os.path.exists(prefix + ".idx")

    from mxnet_tpu import recordio
    r = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "r")
    seen = 0
    for idx, _, label in rows:
        header, img = recordio.unpack_img(r.read_idx(idx))
        assert header.label == label
        assert img.shape[2] == 3 and min(img.shape[:2]) == 16
        seen += 1
    assert seen == 6


def test_launch_worker_env():
    env = launch.worker_env(2, 4, "10.0.0.1:9870", base={})
    assert env["DMLC_WORKER_ID"] == "2"
    assert env["DMLC_NUM_WORKER"] == "4"
    assert env["JAX_PROCESS_ID"] == "2"
    assert env["JAX_COORDINATOR_ADDRESS"] == "10.0.0.1:9870"


def test_launch_local_runs_n_processes(tmp_path):
    out = tmp_path / "ranks"
    out.mkdir()
    script = tmp_path / "w.py"
    script.write_text(
        "import os\n"
        f"open(os.path.join({str(out)!r}, os.environ['DMLC_WORKER_ID']), 'w')"
        ".write(os.environ['DMLC_NUM_WORKER'])\n")
    rc = launch.launch_local(3, [sys.executable, str(script)])
    assert rc == 0
    assert sorted(os.listdir(out)) == ["0", "1", "2"]
    assert (out / "1").read_text() == "3"


def test_crashloop_cli_parses_and_completes(tmp_path):
    """crashloop runs a trivially-succeeding command to completion and
    relays its digest line."""
    import crashloop
    script = tmp_path / "ok.py"
    script.write_text("print('FINAL_PARAM_DIGEST=abc123')\n")
    rc = crashloop.main(["--interval", "30", "--max-restarts", "2",
                         "--expect-digest", "abc123", "--",
                         sys.executable, str(script)])
    assert rc == 0
    rc = crashloop.main(["--interval", "30", "--max-restarts", "0",
                         "--expect-digest", "different", "--",
                         sys.executable, str(script)])
    assert rc == 3          # digest mismatch is a recovery bug


@pytest.mark.slow
@pytest.mark.chaos
def test_crashloop_kills_and_recovers_example(tmp_path):
    """End-to-end recovery: the resilient example, SIGTERM'd repeatedly,
    still completes and reaches the uninterrupted run's exact digest."""
    import time
    import crashloop
    example = os.path.join(REPO, "example", "resilient_training.py")
    # uninterrupted reference digest
    t0 = time.monotonic()
    p = subprocess.run([sys.executable, example, "--ckpt-dir",
                        str(tmp_path / "ref"), "--steps", "300"],
                       capture_output=True, text=True, timeout=300)
    ref_s = time.monotonic() - t0
    assert p.returncode == 0, p.stdout + p.stderr
    digest = [l for l in p.stdout.splitlines()
              if l.startswith("FINAL_PARAM_DIGEST=")][0].split("=", 1)[1]
    # the kill lands at 0.7 of what the uninterrupted run took on this box
    # just now, and the steps take as long as the start-up does: a loaded
    # box stretches both, so every attempt gets past its start-up and a
    # third of the way through the steps, and three or four attempts finish.
    # (A fixed 6 s on a box where importing and compiling took 6 s advanced
    # one step per attempt and ran out of restarts.)
    rc = crashloop.main(["--interval", "%.1f" % (0.7 * ref_s),
                         "--max-restarts", "20",
                         "--expect-digest", digest, "--",
                         sys.executable, example, "--ckpt-dir",
                         str(tmp_path / "run"), "--steps", "300"])
    assert rc == 0


def test_crashloop_devices_schedule_env(tmp_path, capsys):
    """--devices-schedule forces the per-attempt visible device count
    (replacing any count the target sets itself) and arms MXNET_ELASTIC;
    attempts past the schedule reuse its last entry."""
    import crashloop
    counter = tmp_path / "n"
    script = tmp_path / "probe.py"
    # graceful-preemption shape: exit 0 with no digest on the first two
    # attempts (crashloop restarts), complete with a digest on the third
    script.write_text(
        "import os, pathlib\n"
        "p = pathlib.Path(%r)\n"
        "n = int(p.read_text()) if p.exists() else 0\n"
        "p.write_text(str(n + 1))\n"
        "print('ENV', os.environ['XLA_FLAGS'], '|',\n"
        "      os.environ.get('JAX_PLATFORMS'), '|',\n"
        "      os.environ.get('MXNET_ELASTIC'))\n"
        "if n >= 2:\n"
        "    print('FINAL_PARAM_DIGEST=done')\n" % str(counter))
    rc = crashloop.main(["--interval", "30", "--max-restarts", "3",
                         "--devices-schedule", "8,4", "--expect-digest",
                         "done", "--", sys.executable, str(script)])
    out = capsys.readouterr().out
    assert rc == 0
    envs = [l for l in out.splitlines() if l.startswith("ENV ")]
    assert len(envs) == 3
    for line, n in zip(envs, (8, 4, 4)):    # schedule clamps at its tail
        assert "--xla_force_host_platform_device_count=%d" % n in line
        assert line.count("device_count") == 1      # replaced, not stacked
        assert "| cpu |" in line and line.endswith("1")
    assert "sees 8 visible device(s)" in out
    assert "sees 4 visible device(s)" in out


def test_crashloop_expect_params_tolerance(tmp_path, capsys):
    """--expect-params is the digest's float-tolerance sibling for elastic
    schedules: allclose within rtol/atol passes, beyond it is the same
    rc=3 'trajectory diverged' verdict."""
    import crashloop
    ref = tmp_path / "ref.npz"
    run = tmp_path / "run.npz"
    w = np.arange(8.0, dtype="float32")
    np.savez(ref, w=w)
    script = tmp_path / "ok.py"
    script.write_text("print('FINAL_PARAM_DIGEST=x')\n")
    base = ["--interval", "30", "--max-restarts", "0",
            "--expect-params", str(ref), "--params-file", str(run),
            "--", sys.executable, str(script)]

    np.savez(run, w=w + 1e-7)           # within tolerance
    assert crashloop.main(base) == 0
    assert "params match" in capsys.readouterr().out

    np.savez(run, w=w + 1.0)            # way outside
    assert crashloop.main(base) == 3
    assert "PARAMS MISMATCH" in capsys.readouterr().out

    np.savez(run, v=w)                  # different param set
    assert crashloop.main(base) == 3


@pytest.mark.slow
@pytest.mark.chaos
def test_crashloop_elastic_device_churn(tmp_path):
    """The elastic acceptance bar, end to end across real processes: a
    ZeRO-1 run killed mid-epoch at 8 devices, resumed at 4 (checkpoint
    adopted, opt-state re-sharded, iterator credited back), later
    attempts back at 8 — final params within documented tolerance of the
    uninterrupted 8-device run (cross-topology resumes change the
    reduction order, so the comparison is --expect-params, not the
    bitwise digest)."""
    import crashloop
    example = os.path.join(REPO, "example", "resilient_training.py")
    ref = str(tmp_path / "ref.npz")
    run = str(tmp_path / "run.npz")
    p = subprocess.run([sys.executable, example, "--ckpt-dir",
                        str(tmp_path / "ref"), "--epochs", "8",
                        "--elastic", "--dump-params", ref],
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "elastic: training on 8 visible device(s)" in p.stdout
    rc = crashloop.main(["--interval", "2", "--grace", "60",
                         "--max-restarts", "25", "--kill-mid-epoch",
                         "--devices-schedule", "8,4,8",
                         "--expect-params", ref, "--params-file", run,
                         "--", sys.executable, example, "--ckpt-dir",
                         str(tmp_path / "run"), "--epochs", "8",
                         "--elastic", "--dump-params", run])
    assert rc == 0


@pytest.mark.slow
@pytest.mark.chaos
def test_crashloop_inject_nan_self_heals(tmp_path):
    """crashloop --inject-nan exports the NaN storm to the target; the
    recovery ladder self-heals (snapshot rollback, no restart) and the
    digest still matches the uninjected --recovery run."""
    import crashloop
    example = os.path.join(REPO, "example", "resilient_training.py")
    p = subprocess.run([sys.executable, example, "--ckpt-dir",
                        str(tmp_path / "ref"), "--steps", "30",
                        "--recovery"],
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stdout + p.stderr
    digest = [l for l in p.stdout.splitlines()
              if l.startswith("FINAL_PARAM_DIGEST=")][0].split("=", 1)[1]
    rc = crashloop.main(["--interval", "600", "--max-restarts", "0",
                         "--inject-nan", "6",
                         "--expect-digest", digest, "--",
                         sys.executable, example, "--ckpt-dir",
                         str(tmp_path / "run"), "--steps", "30"])
    assert rc == 0


def test_crashloop_inject_nan_first_attempt_only(tmp_path, capsys):
    """The storm env rides the FIRST attempt only: a restart re-arming it
    would poison fresh relative step windows — including sub-trip tails
    whose skips are never replayed, breaking --expect-digest."""
    import crashloop
    marker = tmp_path / "ran_once"
    script = tmp_path / "probe.py"
    # first run: record the storm env, exit 0 with no digest (crashloop
    # treats that as a graceful preemption and restarts); second run:
    # record again and print the digest to finish
    script.write_text(
        "import os\n"
        "print('STORM=%s RECOVERY=%s' % ("
        "os.environ.get('MXNET_CHAOS_NAN_STORM'), "
        "os.environ.get('MXNET_CHAOS_RECOVERY')))\n"
        f"m = {str(marker)!r}\n"
        "if os.path.exists(m):\n"
        "    print('FINAL_PARAM_DIGEST=abc')\n"
        "else:\n"
        "    open(m, 'w').close()\n")
    rc = crashloop.main(["--interval", "600", "--max-restarts", "3",
                         "--inject-nan", "4", "--expect-digest", "abc",
                         "--", sys.executable, str(script)])
    assert rc == 0
    storms = [l for l in capsys.readouterr().out.splitlines()
              if l.startswith("STORM=")]
    # the storm disarms after attempt 0, but the recovery/bf16 stack it
    # implied stays on — restarts must not resume the lineage into a
    # different-arithmetic trainer
    assert storms == ["STORM=4 RECOVERY=1", "STORM=None RECOVERY=1"]


_LINT_FIXTURE = """\
import numpy as np
import jax.numpy as jnp

def _bad(p):
    return p + np.float64(1.0)          # f64 creep: MXL-T207

def make_bad_spec():
    return (_bad, (jnp.zeros((8,), jnp.float32),))

def _clean(p):
    return p * jnp.float32(2.0)

def make_clean_spec():
    return {"fn": _clean, "args": (jnp.zeros((8,), jnp.float32),),
            "donate_argnums": (0,)}
"""


@pytest.mark.lint
def test_mxlint_cli_json_smoke(tmp_path):
    """tools/mxlint.py end-to-end: JSON output, exit code 0 on a clean step,
    1 on an error-severity finding, 2 on an unloadable target — no network,
    no TPU (abstract eval only)."""
    import json
    fixture = tmp_path / "step_specs.py"
    fixture.write_text(_LINT_FIXTURE)
    mxlint = os.path.join(REPO, "tools", "mxlint.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}

    p = subprocess.run(
        [sys.executable, mxlint, "trace", f"{fixture}:make_clean_spec",
         "--format", "json"],
        capture_output=True, text=True, timeout=240, env=env)
    assert p.returncode == 0, p.stdout + p.stderr
    data = json.loads(p.stdout)
    assert data["summary"] == {"errors": 0, "warnings": 0, "total": 0}

    p = subprocess.run(
        [sys.executable, mxlint, "trace", f"{fixture}:make_bad_spec",
         "--format", "json"],
        capture_output=True, text=True, timeout=240, env=env)
    assert p.returncode == 1, p.stdout + p.stderr
    data = json.loads(p.stdout)
    assert any(f["rule"] == "MXL-T207" for f in data["findings"])
    assert data["summary"]["errors"] >= 1

    p = subprocess.run(
        [sys.executable, mxlint, "graph", f"{fixture}:no_such_thing"],
        capture_output=True, text=True, timeout=240, env=env)
    assert p.returncode == 2
    assert "cannot lint" in p.stderr


def test_diagnose_runs():
    p = subprocess.run([sys.executable, os.path.join(REPO, "tools",
                                                     "diagnose.py")],
                       capture_output=True, text=True, timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": "cpu",
                            "PYTHONPATH": ""})
    assert p.returncode == 0, p.stderr
    assert "Framework Info" in p.stdout
    assert "native lib   : ok" in p.stdout


@pytest.mark.obs
def test_mxtop_cli_smoke(tmp_path):
    """tools/mxtop.py end-to-end on both artifact kinds — exit codes follow
    the mxlint convention: 0 healthy, 1 anomalies, 2 unloadable."""
    import json
    mxtop = os.path.join(REPO, "tools", "mxtop.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}

    # healthy metrics snapshot → 0
    snap = {"version": 1, "time": 1.0, "pid": 1, "metrics": {
        "mxtpu_trainer_step_ms": {"type": "histogram", "help": "", "series": [
            {"labels": {}, "sum": 30.0, "count": 3, "max": 20.0,
             "buckets": {"10": 2, "+Inf": 3}}]},
        "mxtpu_trainer_steps_total": {"type": "counter", "help": "",
                                      "series": [{"labels": {}, "value": 3}]},
    }}
    ok = tmp_path / "snap.json"
    ok.write_text(json.dumps(snap))
    p = subprocess.run([sys.executable, mxtop, str(ok)], env=env,
                       capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "mxtpu_trainer_step_ms" in p.stdout

    # anomaly counter above zero → 1
    snap["metrics"]["mxtpu_watchdog_timeouts_total"] = {
        "type": "counter", "help": "",
        "series": [{"labels": {}, "value": 1}]}
    bad = tmp_path / "snap_bad.json"
    bad.write_text(json.dumps(snap))
    p = subprocess.run([sys.executable, mxtop, str(bad)], env=env,
                       capture_output=True, text=True, timeout=60)
    assert p.returncode == 1, p.stdout + p.stderr
    assert "anomaly signal" in p.stdout

    # crash-reason flight recording → 1; --format json round-trips
    flight = {"version": 1, "reason": "watchdog_timeout: step 7", "time": 1.0,
              "pid": 1, "extra": {}, "records": [
                  {"step": 7, "time": 1.0, "loss": 0.5, "step_ms": 9.0,
                   "spans": ["module_fit_epoch"]}]}
    fp = tmp_path / "flight.json"
    fp.write_text(json.dumps(flight))
    p = subprocess.run([sys.executable, mxtop, str(fp)], env=env,
                       capture_output=True, text=True, timeout=60)
    assert p.returncode == 1, p.stdout + p.stderr
    assert "watchdog_timeout: step 7" in p.stdout
    p = subprocess.run([sys.executable, mxtop, "--format", "json", str(fp)],
                       env=env, capture_output=True, text=True, timeout=60)
    assert p.returncode == 0
    assert json.loads(p.stdout)["kind"] == "flight"

    # unloadable → 2
    p = subprocess.run([sys.executable, mxtop, str(tmp_path / "nope.json")],
                       env=env, capture_output=True, text=True, timeout=60)
    assert p.returncode == 2
    garbage = tmp_path / "garbage.json"
    garbage.write_text("{not json")
    p = subprocess.run([sys.executable, mxtop, str(garbage)], env=env,
                       capture_output=True, text=True, timeout=60)
    assert p.returncode == 2
    assert "cannot read" in p.stderr


@pytest.mark.obs
def test_perfwatch_cli_smoke(tmp_path):
    """tools/perfwatch.py end-to-end: 0 at parity, 1 on a >=10% synthetic
    throughput regression vs a cached baseline row, 2 on a missing
    baseline — the mxlint exit convention."""
    import json
    pwcli = os.path.join(REPO, "tools", "perfwatch.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps({
        "metric": "resnet50_train_throughput_per_chip", "value": 2468.3,
        "unit": "img/s/chip", "mfu": 0.1541,
        "flops_per_step": 3.1488e12}))

    parity = tmp_path / "parity.json"
    parity.write_text(json.dumps({
        "metric": "resnet50_train_throughput_per_chip", "value": 2470.0,
        "mfu": 0.155}))
    p = subprocess.run([sys.executable, pwcli, str(parity),
                        "--baseline", str(baseline)],
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "status: ok" in p.stdout

    regressed = tmp_path / "reg.json"
    regressed.write_text(json.dumps({
        "metric": "resnet50_train_throughput_per_chip", "value": 2221.0}))
    p = subprocess.run([sys.executable, pwcli, str(regressed),
                        "--baseline", str(baseline)],
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 1, p.stdout + p.stderr
    assert "REGRESSION" in p.stdout

    # --format json round-trips the checks
    p = subprocess.run([sys.executable, pwcli, str(regressed),
                        "--baseline", str(baseline), "--format", "json"],
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 1
    doc = json.loads(p.stdout)
    assert doc["status"] == "regression"
    assert any(c["metric"] == "throughput" and c["regressed"]
               for c in doc["checks"])

    # a tighter threshold flips a small delta into a regression
    p = subprocess.run([sys.executable, pwcli, str(parity),
                        "--baseline", str(baseline),
                        "--metric-threshold", "mfu=0.01"],
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 0          # parity improved mfu: still ok

    p = subprocess.run([sys.executable, pwcli, str(parity),
                        "--baseline", str(tmp_path / "missing.json")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 2
    assert "no usable baseline" in p.stderr


@pytest.mark.obs
def test_mxtop_perf_cli_smoke(tmp_path):
    """mxtop.py perf: ledger + snapshot render, --format json, exit 2 when
    nothing loads."""
    import json
    mxtop = os.path.join(REPO, "tools", "mxtop.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    ledger = tmp_path / "ledger.jsonl"
    ledger.write_text(
        json.dumps({"time": 1.0, "label": "DataParallelTrainer.step",
                    "flops": 6877.0, "bytes_accessed": 27793.0,
                    "arithmetic_intensity": 0.247,
                    "roofline": "memory-bound", "fingerprint": "f" * 64})
        + "\n{torn\n")
    snap = tmp_path / "snap.json"
    snap.write_text(json.dumps({"version": 1, "pid": 1, "metrics": {
        "mxtpu_mfu": {"type": "gauge", "help": "", "series": [
            {"labels": {}, "value": 0.21}]},
        "mxtpu_device_util": {"type": "gauge", "help": "", "series": [
            {"labels": {}, "value": 0.9}]},
        "mxtpu_step_breakdown_ms": {"type": "gauge", "help": "", "series": [
            {"labels": {"bucket": "dispatch"}, "value": 12.5},
            {"labels": {"bucket": "feed_stall"}, "value": 2.0}]},
    }}))
    p = subprocess.run([sys.executable, mxtop, "perf", str(snap),
                        "--ledger", str(ledger)],
                       env=env, capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "memory-bound" in p.stdout
    assert "mxtpu_mfu" in p.stdout and "dispatch" in p.stdout
    # ledger-only and snapshot-only both render
    p = subprocess.run([sys.executable, mxtop, "perf", "--ledger",
                        str(ledger)],
                       env=env, capture_output=True, text=True, timeout=60)
    assert p.returncode == 0 and "cost ledger" in p.stdout
    p = subprocess.run([sys.executable, mxtop, "perf", str(snap),
                        "--format", "json"],
                       env=env, capture_output=True, text=True, timeout=60)
    assert p.returncode == 0
    assert json.loads(p.stdout)["kind"] == "perf"
    # nothing loadable -> 2
    p = subprocess.run([sys.executable, mxtop, "perf", "--ledger",
                        str(tmp_path / "nope.jsonl")],
                       env=env, capture_output=True, text=True, timeout=60)
    assert p.returncode == 2
    assert "nothing to show" in p.stderr


@pytest.mark.tuner
def test_mxtune_cli_tunes_and_feeds_perfwatch(tmp_path):
    """tools/mxtune.py end-to-end on the CPU backend: a 2-candidate space
    where the big batch wins -> exit 0 (tuned), ranked report with
    provenance, warm-start cache on disk — and the --emit-best row works
    as a tools/perfwatch.py --baseline (the tuner->watchdog handoff)."""
    import json
    mxtune = os.path.join(REPO, "tools", "mxtune.py")
    pwcli = os.path.join(REPO, "tools", "perfwatch.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": "",
           "MXNET_PERF_PEAK_FLOPS": "1e12",
           "MXNET_PERF_PEAK_HBM_GBPS": "1"}
    cache = tmp_path / "trials.jsonl"
    best = tmp_path / "best_row.json"
    p = subprocess.run(
        [sys.executable, mxtune, "--model", "tiny",
         "--space", "batch=8,32;layout=NCHW", "--steps", "2",
         "--warmup", "1", "--top-k", "1", "--cache", str(cache),
         "--emit-best", str(best), "--format", "json"],
        env=env, capture_output=True, text=True, timeout=420)
    assert p.returncode == 0, p.stdout + p.stderr
    doc = json.loads(p.stdout)
    assert doc["improved"] is True
    assert doc["best"]["candidate"]["batch"] == 32
    assert doc["best"]["provenance"] == "measured"
    assert {t["provenance"] for t in doc["trials"]} \
        <= {"predicted", "measured", "cached"}
    assert cache.exists() and best.exists()

    # the tuner-produced measured ledger row is a usable perfwatch baseline
    row = json.loads(best.read_text())
    assert row["label"] == "tuner.trial" and row["measured_step_ms"] > 0
    worse = tmp_path / "worse.json"
    worse.write_text(json.dumps({
        "metric": "resnet50_train_throughput_per_chip",
        "value": row["throughput_img_s_per_chip"] * 0.5}))
    p = subprocess.run([sys.executable, pwcli, str(worse),
                        "--baseline", str(best)],
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 1, p.stdout + p.stderr
    assert "REGRESSION" in p.stdout
    parity = tmp_path / "parity.json"
    parity.write_text(json.dumps({
        "metric": "resnet50_train_throughput_per_chip",
        "value": row["throughput_img_s_per_chip"] * 1.02}))
    p = subprocess.run([sys.executable, pwcli, str(parity),
                        "--baseline", str(best)],
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stdout + p.stderr


@pytest.mark.tuner
def test_mxtune_cli_no_improvement_and_cannot_run(tmp_path):
    """Exit 1 when the baseline IS the best known config (single-candidate
    space); exit 2 on an unusable space/model — the mxlint convention."""
    mxtune = os.path.join(REPO, "tools", "mxtune.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": "",
           "MXNET_PERF_PEAK_FLOPS": "1e12",
           "MXNET_PERF_PEAK_HBM_GBPS": "1"}
    p = subprocess.run(
        [sys.executable, mxtune, "--model", "tiny",
         "--space", "batch=8;layout=NCHW", "--predict-only",
         "--cache", str(tmp_path / "c1.jsonl"),
         "--emit-best", str(tmp_path / "nope.json")],
        env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode == 1, p.stdout + p.stderr
    # a predicted-only row is refused as a perfwatch baseline: its
    # optimal-roof step time would flag every healthy measured run
    assert not (tmp_path / "nope.json").exists()
    assert "--emit-best skipped" in p.stderr

    p = subprocess.run(
        [sys.executable, mxtune, "--model", "tiny",
         "--space", "bogus=1", "--cache", str(tmp_path / "c2.jsonl")],
        env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode == 2
    assert "unknown search-space dimension" in p.stderr

    p = subprocess.run(
        [sys.executable, mxtune, "--model", "nope",
         "--cache", str(tmp_path / "c3.jsonl")],
        env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode == 2
    assert "unknown --model" in p.stderr


@pytest.mark.passes
def test_mxopt_cli_json_and_dead_nodes(tmp_path):
    """tools/mxopt.py end-to-end: a saved NCHW conv graph gets layout
    rewrites + a before/after lint delta (MXL-G107 before, clean after),
    dead JSON nodes are counted, --emit round-trips, and a bad target
    or an unknown pass name exits 2."""
    import json
    import mxnet_tpu.symbol as sym_mod

    def op(opname, *ins, **kw):
        return sym_mod._invoke_sym(opname, list(ins), kw)

    data = sym_mod.Variable("data")
    out = op("Convolution", data, kernel=(3, 3), num_filter=8,
             no_bias=True, layout="NCHW", stride=(1, 1), pad=(1, 1),
             num_group=1, dilate=(1, 1), name="mc1")
    raw = json.loads(out.tojson())
    # graft an unreachable node so dead-node elimination has work
    raw["nodes"].append({"op": "null", "name": "orphan", "attrs": {},
                         "inputs": []})
    gpath = tmp_path / "net.json"
    gpath.write_text(json.dumps(raw))
    mxopt = os.path.join(REPO, "tools", "mxopt.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}

    emitted = tmp_path / "net_opt.json"
    p = subprocess.run(
        [sys.executable, mxopt, str(gpath), "--shape", "data:2,3,8,8",
         "--emit", str(emitted), "--format", "json"],
        capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 0, p.stdout + p.stderr
    rep = json.loads(p.stdout)
    assert rep["rewrites"]["layout"] >= 1
    assert rep["dead_nodes_eliminated"] == 1
    # G107 fires on the before-lint (passes declared off), not after
    assert rep["lint_before"]["warnings"] >= 1
    assert rep["lint_after"]["warnings"] == 0
    # the emitted graph loads and the orphan is gone
    re = sym_mod.load_json(emitted.read_text())
    assert "orphan" not in [n.name for n in re.topo_nodes()]
    assert "NHWC" in [str((n.attrs or {}).get("layout"))
                      for n in re.topo_nodes() if n.op == "Convolution"]

    p = subprocess.run([sys.executable, mxopt, str(tmp_path / "nope.json")],
                       capture_output=True, text=True, timeout=120, env=env)
    assert p.returncode == 2
    # a name that is no pass (the stem's lowering is the op's) exits 2 too
    p = subprocess.run(
        [sys.executable, mxopt, str(gpath), "--shape", "data:2,3,8,8",
         "--passes", "fold,layout,s2d,fusion"],
        capture_output=True, text=True, timeout=120, env=env)
    assert p.returncode == 2
    assert "unknown graph pass 's2d' (registered: " in p.stderr


# ------------------------------------------------------------- collbench
def test_collbench_cli_smoke(tmp_path):
    """tools/collbench.py end-to-end on the virtual 8-device mesh: JSON
    rows on stdout, every row persisted to the given ledger, exit 0; bad
    arguments exit 2 (mxlint convention)."""
    import json
    cli = os.path.join(REPO, "tools", "collbench.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    ledger = str(tmp_path / "coll.jsonl")

    p = subprocess.run(
        [sys.executable, cli, "--ops", "psum,reduce_scatter",
         "--sizes", "16K", "--devices", "1,8", "--steps", "2",
         "--warmup", "1", "--compression", "0.5",
         "--ledger", ledger, "--format", "json"],
        capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 0, p.stdout + p.stderr
    rows = [json.loads(l) for l in p.stdout.splitlines() if l.strip()]
    # 2 ops x 2 device counts + 1 compressed comparison per count
    assert len(rows) == 6, rows
    ops = {(r["op"], r["n_devices"]) for r in rows}
    assert ("psum", 8) in ops and ("psum_compressed", 8) in ops
    for r in rows:
        assert r["label"] == "collbench" and r["ms"] > 0
    with open(ledger) as f:
        assert len(f.readlines()) == len(rows)

    # bad device count -> cannot run
    p = subprocess.run([sys.executable, cli, "--devices", "99",
                        "--sizes", "4K", "--steps", "1",
                        "--ledger", ledger],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 2, p.stdout + p.stderr

    # partial sweep: the 1-device cells measure, 99 fails -> exit 1 with
    # the measured rows still emitted (not misclassified as 'cannot run')
    p = subprocess.run([sys.executable, cli, "--devices", "1,99",
                        "--ops", "psum", "--sizes", "4K", "--steps", "1",
                        "--ledger", ledger, "--format", "json"],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 1, p.stdout + p.stderr
    partial = [json.loads(l) for l in p.stdout.splitlines() if l.strip()]
    assert len(partial) == 1 and partial[0]["n_devices"] == 1

    # unparsable size -> cannot run, before any backend init
    p = subprocess.run([sys.executable, cli, "--sizes", "banana"],
                       capture_output=True, text=True, timeout=60, env=env)
    assert p.returncode == 2


# ---------------------------------------------------------------------------
# Serving CLIs: mxserve selfcheck + loadgen exit-code matrices (mxlint 0/1/2
# convention).
# ---------------------------------------------------------------------------
@pytest.mark.serve
def test_mxserve_cli_selfcheck_matrix(tmp_path):
    """mxserve --selfcheck drives N requests through the full batching
    path in-process: 0 = all served, 1 = degraded (injected executor
    fault), 2 = cannot load the model."""
    cli = os.path.join(REPO, "tools", "mxserve.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    p = subprocess.run([sys.executable, cli, "--model", "tiny",
                        "--selfcheck", "8"],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "ok=8 failed=0" in p.stdout

    p = subprocess.run([sys.executable, cli, "--model", "tiny",
                        "--selfcheck", "4", "--chaos", "executor_fault"],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 1, p.stdout + p.stderr
    assert "failed=4" in p.stdout

    p = subprocess.run([sys.executable, cli, "--model",
                        str(tmp_path / "missing.json"),
                        "--feature-shape", "4"],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 2, p.stdout + p.stderr
    assert "cannot load the model" in p.stderr


@pytest.mark.serve
def test_loadgen_cli_matrix_and_serving_row(tmp_path):
    """loadgen --selfhost: 0 = sustained at bounded p99 (serving row in
    the ledger, perfwatch-comparable), 1 = degraded (impossible deadline
    forces expiry), 2 = bad args before any backend init."""
    import json as _json
    cli = os.path.join(REPO, "tools", "loadgen.py")
    ledger = str(tmp_path / "serve_ledger.jsonl")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    p = subprocess.run([sys.executable, cli, "--selfhost", "--qps", "60",
                        "--duration", "0.8", "--ledger", ledger,
                        "--format", "json"],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 0, p.stdout + p.stderr
    row = _json.loads(p.stdout.strip().splitlines()[-1])
    assert row["label"] == "serving" and row["qps"] > 0
    assert row["p99_ms"] > 0 and row["shed"] == 0

    # the persisted row is a full perfwatch baseline: self-compare is ok
    from mxnet_tpu.observability import perfwatch
    norm, err = perfwatch.load_artifact(ledger)
    assert not err and norm["kind"] == "serving_row"
    assert perfwatch.compare(norm, norm)["status"] == "ok"

    # overload + 1ms deadline: everything expires/sheds -> degraded
    p = subprocess.run([sys.executable, cli, "--selfhost", "--qps", "80",
                        "--duration", "0.6", "--deadline-ms", "1",
                        "--max-queue", "4"],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 1, p.stdout + p.stderr

    p = subprocess.run([sys.executable, cli, "--selfhost", "--qps", "-3"],
                       capture_output=True, text=True, timeout=60, env=env)
    assert p.returncode == 2, p.stdout + p.stderr


@pytest.mark.quant
def test_mxquant_cli_matrix(tmp_path):
    """mxquant calibrate→quantize→compare: 0 = ok (table written /
    nodes quantized / agreement within tolerance), 1 = degraded (nothing
    quantized), 2 = cannot load the model — the mxlint exit convention."""
    import json as _json
    cli = os.path.join(REPO, "tools", "mxquant.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    table = str(tmp_path / "calib.json")
    emitted = str(tmp_path / "q.json")
    eparams = str(tmp_path / "q.params")
    ledger = str(tmp_path / "quant_ledger.jsonl")

    # calibrate: writes a loadable CalibTable
    p = subprocess.run([sys.executable, cli, "calibrate", "--model", "tiny",
                        "--batches", "2", "--mode", "naive",
                        "--out", table],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 0, p.stdout + p.stderr
    doc = _json.load(open(table))
    assert doc["mode"] == "naive" and doc["ranges"]

    # quantize from the table: emits int8 symbol + params, exit 0
    p = subprocess.run([sys.executable, cli, "quantize", "--model", "tiny",
                        "--table", table, "--emit", emitted,
                        "--emit-params", eparams],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 0, p.stdout + p.stderr
    emitted_doc = _json.load(open(emitted))
    ops = {n.get("op") for n in emitted_doc["nodes"]}
    assert "_contrib_quantize" in ops and os.path.exists(eparams)

    # compare: agreement within --acc-tol, label="quant" ledger row
    p = subprocess.run([sys.executable, cli, "compare", "--model", "tiny",
                        "--table", table, "--steps", "2",
                        "--eval-samples", "16", "--ledger", ledger],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 0, p.stdout + p.stderr
    row = _json.loads(p.stdout.strip().splitlines()[-1])
    assert row["label"] == "quant"
    assert row["f32_ms"] > 0 and row["int8_ms"] > 0
    assert row["quantized_nodes"] >= 1

    # excluding every candidate leaves nothing to quantize: degraded
    p = subprocess.run([sys.executable, cli, "quantize", "--model", "tiny",
                        "--exclude", "conv0,fc0,fc1"],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 1, p.stdout + p.stderr

    # a missing model file cannot run
    p = subprocess.run([sys.executable, cli, "quantize", "--model",
                        str(tmp_path / "missing.json"),
                        "--feature-shape", "4"],
                       capture_output=True, text=True, timeout=120, env=env)
    assert p.returncode == 2, p.stdout + p.stderr


# ---------------------------------------------------------------------------
# Tracing CLI: mxtrace view/exit-code matrix (mxlint 0/1/2 convention) and
# the mxtop trace summary view.
# ---------------------------------------------------------------------------
def _write_trace_dump(path, with_error=False):
    """Synthesize a trace-ring dump through the REAL tracing API (no
    hand-rolled schema): finished RequestTraces -> Tracer.write_dump."""
    from mxnet_tpu.observability.tracing import Tracer

    tracer = Tracer(capacity=16, sample=1.0)
    for i in range(3):
        rt = tracer.start_request("m")
        t0 = rt.submitted_at
        rt.span("admission", t0, t0 + 0.0001)
        rt.span("queue", t0 + 0.0001, t0 + 0.001)
        rt.span("forward", t0 + 0.001, t0 + 0.004, batch=2)
        tracer.finish(rt, "ok", latency_ms=4.0 + i)
    last_ok = rt.trace_id
    if with_error:
        rt = tracer.start_request("m")
        rt.span("admission", rt.submitted_at, rt.submitted_at + 0.0001)
        tracer.finish(rt, "error", latency_ms=0.2, reason="isolation")
    tracer.write_dump(path)
    return last_ok


@pytest.mark.trace
def test_mxtrace_cli_matrix(tmp_path):
    """mxtrace: 0 = healthy dump, 1 = anomalous traces in view, 2 =
    unloadable artifact / unknown trace id — and the summary, timeline,
    json and chrome views all render from one dump."""
    import json as _json
    cli = os.path.join(REPO, "tools", "mxtrace.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    ok_dump = str(tmp_path / "ok.json")
    bad_dump = str(tmp_path / "bad.json")
    ok_tid = _write_trace_dump(ok_dump)
    _write_trace_dump(bad_dump, with_error=True)

    # healthy dump: summary view, exit 0
    p = subprocess.run([sys.executable, cli, ok_dump],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "retained: 3" in p.stdout and "ok=3" in p.stdout

    # anomalous dump: exit 1, '!' marker rows
    p = subprocess.run([sys.executable, cli, bad_dump],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 1, p.stdout + p.stderr
    assert "anomalous trace(s)" in p.stdout

    # errors-only narrows the view to the anomalies
    p = subprocess.run([sys.executable, cli, bad_dump, "--errors-only"],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 1
    assert "retained: 1" in p.stdout and "error" in p.stdout

    # single-timeline view resolves a trace id (prefix match works)
    p = subprocess.run([sys.executable, cli, ok_dump,
                        "--trace-id", ok_tid[:12]],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 0, p.stdout + p.stderr
    for stage in ("admission", "queue", "forward"):
        assert stage in p.stdout
    assert "batch=2" in p.stdout

    # json + chrome formats parse
    p = subprocess.run([sys.executable, cli, ok_dump, "--format", "json"],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 0
    doc = _json.loads(p.stdout)
    assert len(doc["traces"]) == 3
    p = subprocess.run([sys.executable, cli, ok_dump, "--format", "chrome"],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 0
    chrome = _json.loads(p.stdout)
    assert chrome["traceEvents"] and \
        {e["ph"] for e in chrome["traceEvents"]} == {"X"}

    # unknown trace id / unloadable artifact: cannot run
    p = subprocess.run([sys.executable, cli, ok_dump,
                        "--trace-id", "feedfacefeedface"],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 2, p.stdout + p.stderr
    p = subprocess.run([sys.executable, cli, str(tmp_path / "nope.json")],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 2


@pytest.mark.trace
def test_mxtop_trace_view(tmp_path):
    """mxtop.py trace: the at-a-glance trace-ring summary rides mxtop's
    exit convention (0 healthy / 1 anomalies / 2 unloadable)."""
    cli = os.path.join(REPO, "tools", "mxtop.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    dump = str(tmp_path / "ring.json")
    _write_trace_dump(dump, with_error=True)
    p = subprocess.run([sys.executable, cli, "trace", dump],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 1, p.stdout + p.stderr
    assert "retained: 4" in p.stdout
    p = subprocess.run([sys.executable, cli, "trace",
                        str(tmp_path / "missing.json")],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 2


@pytest.mark.trace
def test_loadgen_reports_trace_evidence_and_dump(tmp_path):
    """loadgen --selfhost ends with resolvable trace evidence: slow
    trace_ids in the text report and a --trace-dump artifact mxtrace
    can read back."""
    import json as _json
    cli = os.path.join(REPO, "tools", "loadgen.py")
    dump = str(tmp_path / "traces.json")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": "",
           "MXNET_TRACE_SAMPLE": "1.0"}
    p = subprocess.run([sys.executable, cli, "--selfhost", "--qps", "60",
                        "--duration", "0.8", "--trace-dump", dump],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "slow   trace " in p.stdout       # clickable evidence lines
    doc = _json.load(open(dump))
    assert doc["kind"] == "trace_ring" and doc["traces"]
    # every reported slow trace resolves in the dumped ring
    reported = [l.split()[3] for l in p.stdout.splitlines()
                if l.startswith("loadgen: slow")]
    ring_ids = {t["trace_id"] for t in doc["traces"]}
    assert reported and set(reported) <= ring_ids


@pytest.mark.fleet
def test_mxfleet_cli_matrix(tmp_path):
    """mxfleet: selfcheck proves the fleet control loop in one process
    (exit 0); status/resize against a live fleet speak /fleetz (0 on
    healthy, 1 on a typed TopologyMismatch refusal); a dead URL is
    "cannot run" (2), never a silent 0."""
    cli = os.path.join(REPO, "tools", "mxfleet.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    p = subprocess.run([sys.executable, cli, "selfcheck"],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "PASS" in p.stdout

    # nothing listening: cannot run (2), for status and resize alike
    dead = "http://127.0.0.1:9"
    p = subprocess.run([sys.executable, cli, "status", "--url", dead],
                       capture_output=True, text=True, timeout=60, env=env)
    assert p.returncode == 2, p.stdout + p.stderr
    p = subprocess.run([sys.executable, cli, "resize", "--url", dead,
                        "--model", "a", "--chips", "2"],
                       capture_output=True, text=True, timeout=60, env=env)
    assert p.returncode == 2, p.stdout + p.stderr

    # against a live two-tenant fleet: status reads /fleetz, resize
    # round-trips a plan, an over-budget ask is a 409 refusal (exit 1)
    from mxnet_tpu.serving import load as sload
    from mxnet_tpu.serving.endpoints import ServingEndpoints
    from mxnet_tpu.serving.fleet import FleetController, TenantPolicy
    from mxnet_tpu.serving.server import ModelConfig, ModelServer
    sym, params, shape, _ = sload.tiny_model()
    mk = lambda n: ModelConfig(n, sym, params, feature_shape=shape,
                               buckets=(1, 2), max_queue=8,
                               deadline_ms=500.0, slo_p99_ms=200.0)
    server = ModelServer([mk("a"), mk("b")], drain_on_preemption=False)
    fleet = FleetController(
        server, 3,
        [TenantPolicy("a", quota_qps=100.0, ceiling_chips=2),
         TenantPolicy("b", chips=2, ceiling_chips=2)])
    server.start(warm=False)
    ep = ServingEndpoints(server, port=0).start()
    base = "http://127.0.0.1:%d" % ep.port
    try:
        p = subprocess.run([sys.executable, cli, "status", "--url", base],
                           capture_output=True, text=True, timeout=60,
                           env=env)
        assert p.returncode == 0, p.stdout + p.stderr
        assert "chips placed" in p.stdout and "b" in p.stdout
        p = subprocess.run([sys.executable, cli, "resize", "--url", base,
                            "--model", "b", "--chips", "1"],
                           capture_output=True, text=True, timeout=60,
                           env=env)
        assert p.returncode == 0, p.stdout + p.stderr
        assert "resized 'b' shrink -> 1" in p.stdout
        p = subprocess.run([sys.executable, cli, "resize", "--url", base,
                            "--model", "a", "--chips", "2"],
                           capture_output=True, text=True, timeout=60,
                           env=env)
        assert p.returncode == 0, p.stdout + p.stderr
        assert "resized 'a' grow -> 2" in p.stdout
        # a=2 b=1 on a 3-chip budget: asking a -> 3 would overcommit
        p = subprocess.run([sys.executable, cli, "resize", "--url", base,
                            "--model", "a", "--chips", "3"],
                           capture_output=True, text=True, timeout=60,
                           env=env)
        assert p.returncode == 1, p.stdout + p.stderr
        assert "REFUSED" in p.stderr and "TopologyMismatch" in p.stderr
    finally:
        ep.stop()
        fleet.detach()
        server.close(timeout=10.0)


@pytest.mark.fleet
def test_loadgen_tenants_cli_matrix(tmp_path):
    """loadgen --tenants: mixed-traffic selfhost run over a fleet emits a
    label="fleet" ledger row perfwatch can baseline (exit 0); malformed
    specs and --url are rejected before any backend init (exit 2)."""
    import json as _json
    cli = os.path.join(REPO, "tools", "loadgen.py")
    ledger = str(tmp_path / "fleet_ledger.jsonl")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    p = subprocess.run([sys.executable, cli,
                        "--tenants", "a:50:guaranteed,b:25:best_effort",
                        "--fleet-chips", "3", "--duration", "0.8",
                        "--ledger", ledger, "--format", "json"],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 0, p.stdout + p.stderr
    row = _json.loads(p.stdout.strip().splitlines()[-1])
    assert row["label"] == "fleet"
    assert row["qps[a]"] > 0 and row["qps[b]"] > 0
    assert row["priority[b]"] == "best_effort"

    # the persisted row is a perfwatch baseline; bracketed metrics
    # inherit their family's direction in self-compare
    from mxnet_tpu.observability import perfwatch
    norm, err = perfwatch.load_artifact(ledger)
    assert not err and norm["kind"] == "fleet_row"
    assert perfwatch.compare(norm, norm)["status"] == "ok"

    # bad args die before any backend init: one tenant, and --url
    p = subprocess.run([sys.executable, cli, "--tenants", "a:50"],
                       capture_output=True, text=True, timeout=60, env=env)
    assert p.returncode == 2, p.stdout + p.stderr
    p = subprocess.run([sys.executable, cli, "--tenants", "a:50,b:25",
                        "--url", "http://127.0.0.1:9"],
                       capture_output=True, text=True, timeout=60, env=env)
    assert p.returncode == 2, p.stdout + p.stderr


# ----------------------------------------------- mxrace CLI (0/1/2 matrix)
_RACE_BAD_SRC = """\
import queue
import threading


class Blocky:
    def __init__(self):
        self._lock = threading.Lock()
        self._q = queue.Queue()

    def bad(self):
        with self._lock:
            return self._q.get()
"""

_RACE_CLEAN_SRC = """\
import threading


class Tidy:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0

    def bump(self):
        with self._lock:
            self.n += 1
"""


def test_mxrace_cli_matrix(tmp_path):
    """tools/mxrace.py static scan: 0 clean, 1 findings at/above --fail-on,
    2 unusable target — the mxlint exit convention."""
    import json as _json
    cli = os.path.join(REPO, "tools", "mxrace.py")
    clean = tmp_path / "clean.py"
    clean.write_text(_RACE_CLEAN_SRC)
    bad = tmp_path / "bad.py"
    bad.write_text(_RACE_BAD_SRC)

    p = subprocess.run([sys.executable, cli, str(clean)],
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "clean" in p.stdout

    p = subprocess.run([sys.executable, cli, str(bad)],
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 1, p.stdout + p.stderr
    assert "MXL-C301" in p.stdout

    p = subprocess.run([sys.executable, cli, str(bad), "--format", "json"],
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 1
    data = _json.loads(p.stdout)
    assert data["findings"][0]["rule"] == "MXL-C301"

    # C301 is a warning: raising the bar to error passes it
    p = subprocess.run([sys.executable, cli, str(bad),
                        "--fail-on", "error"],
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stdout + p.stderr

    # run-level suppression from the command line
    p = subprocess.run([sys.executable, cli, str(bad),
                        "--suppress", "MXL-C301"],
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stdout + p.stderr

    # unusable targets exit 2: missing path, unparsable source
    p = subprocess.run([sys.executable, cli, str(tmp_path / "nope.py")],
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 2
    syn = tmp_path / "syn.py"
    syn.write_text("def broken(:\n")
    p = subprocess.run([sys.executable, cli, str(syn)],
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 2, p.stdout + p.stderr


def test_mxrace_report_subcommand(tmp_path):
    """`mxrace report <json>` pretty-prints a lockwatch artifact: exit 1
    when it carries findings, 0 when clean, 2 when unreadable."""
    import json as _json
    cli = os.path.join(REPO, "tools", "mxrace.py")
    rep = tmp_path / "lw.json"
    rep.write_text(_json.dumps({
        "findings": [{"rule": "MXL-C300", "site": "t.B", "other_site": "t.A",
                      "thread": "w0", "message": "lock-order inversion",
                      "stack": "  at x\n", "other_stack": "  at y\n"}],
        "order_graph": {"t.A": ["t.B"], "t.B": ["t.A"]}}))
    p = subprocess.run([sys.executable, cli, "report", str(rep)],
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 1, p.stdout + p.stderr
    assert "MXL-C300" in p.stdout and "t.A -> t.B" in p.stdout

    rep.write_text(_json.dumps({"findings": [], "order_graph": {}}))
    p = subprocess.run([sys.executable, cli, "report", str(rep)],
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0
    assert "no findings" in p.stdout

    p = subprocess.run([sys.executable, cli, "report",
                        str(tmp_path / "missing.json")],
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 2


# ------------------------------------------------------------------ mxmem
@pytest.mark.mem
def test_mxmem_report_cli_matrix(tmp_path):
    """mxmem report: ledger-only render exits 0, a snapshot with OOM/
    refusal counters above zero flags trouble (exit 1), --format json
    round-trips, and unreadable inputs exit 2."""
    import json as _json
    cli = os.path.join(REPO, "tools", "mxmem.py")
    env = {**os.environ, "PYTHONPATH": ""}
    ledger = tmp_path / "ledger.jsonl"
    with open(ledger, "w") as f:
        f.write(_json.dumps({
            "label": "memory", "mem_label": "serve:m:b4", "model": "m",
            "bucket": 4, "fingerprint": "f1", "peak_memory_bytes": 4096,
            "memory": {"argument_bytes": 1024, "output_bytes": 1024,
                       "temp_bytes": 2048}}) + "\n")
        f.write("{torn line\n")                       # corrupt: skipped
        f.write(_json.dumps({"label": "step", "fingerprint": "f2"}) + "\n")
        f.write(_json.dumps({                          # latest f1 wins
            "label": "memory", "mem_label": "serve:m:b4", "model": "m",
            "bucket": 4, "fingerprint": "f1", "peak_memory_bytes": 8192,
            "memory": {"argument_bytes": 2048, "output_bytes": 2048,
                       "temp_bytes": 4096}}) + "\n")

    p = subprocess.run([sys.executable, cli, "report", "--ledger",
                        str(ledger)], capture_output=True, text=True,
                       timeout=120, env=env)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "memory ledger (1 executable(s)" in p.stdout
    assert "serve:m:b4" in p.stdout and "8.00 KiB" in p.stdout

    # a snapshot whose trouble counters moved makes the report exit 1
    snap = tmp_path / "snap.json"
    snap.write_text(_json.dumps({"pid": 1, "metrics": {
        "mxtpu_hbm_bytes_in_use": {"series": [
            {"labels": {"device": "0"}, "value": 123456}]},
        "mxtpu_oom_total": {"series": [
            {"labels": {"context": "serving"}, "value": 1}]},
        "mxtpu_mem_refusals_total": {"series": [
            {"labels": {"reason": "no_memory"}, "value": 2}]}}}))
    p = subprocess.run([sys.executable, cli, "report", str(snap),
                        "--ledger", str(ledger)], capture_output=True,
                       text=True, timeout=120, env=env)
    assert p.returncode == 1, p.stdout + p.stderr
    assert "mxtpu_hbm_bytes_in_use" in p.stdout
    assert "mxtpu_oom_total" in p.stdout
    assert "2 memory-trouble signal(s)" in p.stdout

    p = subprocess.run([sys.executable, cli, "report", "--format", "json",
                        "--ledger", str(ledger)], capture_output=True,
                       text=True, timeout=120, env=env)
    assert p.returncode == 0, p.stdout + p.stderr
    doc = _json.loads(p.stdout)
    assert doc["kind"] == "mem" and len(doc["rows"]) == 1
    assert doc["rows"][0]["peak_memory_bytes"] == 8192

    # nothing loadable -> 2
    p = subprocess.run([sys.executable, cli, "report", "--ledger",
                        str(tmp_path / "missing.jsonl")],
                       capture_output=True, text=True, timeout=120, env=env)
    assert p.returncode == 2
    assert "nothing to show" in p.stderr


@pytest.mark.mem
def test_mxmem_postmortem_cli(tmp_path):
    """mxmem postmortem renders a real memwatch artifact and ALWAYS exits
    1 (an OOM artifact is the anomaly); non-postmortem JSON exits 2."""
    import json as _json
    from mxnet_tpu.observability import memwatch
    cli = os.path.join(REPO, "tools", "mxmem.py")
    env = {**os.environ, "PYTHONPATH": ""}
    pm = str(tmp_path / "mxtpu_oom.json")
    memwatch.write_postmortem(
        "unit", exc=RuntimeError("RESOURCE_EXHAUSTED: oom"), path=pm)
    p = subprocess.run([sys.executable, cli, "postmortem", pm],
                       capture_output=True, text=True, timeout=120, env=env)
    assert p.returncode == 1, p.stdout + p.stderr
    assert "OOM postmortem (unit)" in p.stdout
    assert "RESOURCE_EXHAUSTED" in p.stdout

    p = subprocess.run([sys.executable, cli, "postmortem", pm,
                        "--format", "json"], capture_output=True,
                       text=True, timeout=120, env=env)
    assert p.returncode == 1
    assert _json.loads(p.stdout)["doc"]["kind"] == "mxtpu_oom"

    other = tmp_path / "other.json"
    other.write_text(_json.dumps({"kind": "flight_recorder"}))
    p = subprocess.run([sys.executable, cli, "postmortem", str(other)],
                       capture_output=True, text=True, timeout=120, env=env)
    assert p.returncode == 2
    assert "not an mxtpu_oom.json" in p.stderr


@pytest.mark.mem
def test_mxtop_mem_view(tmp_path):
    """`mxtop mem` is the same report surface, reached from the fleet
    operator's muscle-memory entry point."""
    import json as _json
    cli = os.path.join(REPO, "tools", "mxtop.py")
    env = {**os.environ, "PYTHONPATH": ""}
    ledger = tmp_path / "ledger.jsonl"
    ledger.write_text(_json.dumps({
        "label": "memory", "mem_label": "train_step", "fingerprint": "f9",
        "peak_memory_bytes": 1 << 20,
        "memory": {"argument_bytes": 1 << 18, "output_bytes": 1 << 18,
                   "temp_bytes": 1 << 19}}) + "\n")
    p = subprocess.run([sys.executable, cli, "mem", "--ledger",
                        str(ledger)], capture_output=True, text=True,
                       timeout=120, env=env)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "mxmem — HBM memory report" in p.stdout
    assert "train_step" in p.stdout and "1.00 MiB" in p.stdout


@pytest.mark.rollout
def test_mxrollout_cli_matrix(tmp_path):
    """mxrollout: selfcheck proves the bad-canary gate loop in one
    process (exit 0 + PASS); status/start/rollback against a live server
    speak /rolloutz (0 healthy, 1 on a 409 refusal or a rolled-back
    rollout); a dead URL or rollout-mode-off server is "cannot run" (2),
    never a silent 0."""
    cli = os.path.join(REPO, "tools", "mxrollout.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    p = subprocess.run([sys.executable, cli, "selfcheck"],
                       capture_output=True, text=True, timeout=300,
                       env=env)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "PASS" in p.stdout

    # nothing listening: cannot run (2)
    dead = "http://127.0.0.1:9"
    p = subprocess.run([sys.executable, cli, "status", "--url", dead],
                       capture_output=True, text=True, timeout=60, env=env)
    assert p.returncode == 2, p.stdout + p.stderr

    # against a live server: status is 2 before any rollout manager is
    # attached (rollout mode off), the CLI start attaches one, a second
    # start is a typed 409 refusal (1), rollback turns status unhealthy
    from mxnet_tpu.serving import load as sload
    from mxnet_tpu.serving.endpoints import ServingEndpoints
    from mxnet_tpu.serving.server import ModelConfig, ModelServer
    sym, params, shape, _ = sload.tiny_model()
    _, params2, _, _ = sload.tiny_model(seed=1)
    pfile = tmp_path / "v2.params"
    pfile.write_bytes(params2)
    cfg = ModelConfig("m", sym, params, feature_shape=shape,
                      buckets=(1, 2), max_queue=16, deadline_ms=1000.0,
                      slo_p99_ms=200.0)
    server = ModelServer([cfg], drain_on_preemption=False)
    server.start(warm=False)
    ep = ServingEndpoints(server, port=0).start()
    base = "http://127.0.0.1:%d" % ep.port
    run = lambda *a: subprocess.run([sys.executable, cli, *a, "--url",
                                     base], capture_output=True,
                                    text=True, timeout=120, env=env)
    try:
        p = run("status")
        assert p.returncode == 2, p.stdout + p.stderr
        assert "rollout mode off" in p.stderr
        p = run("start", "--model", "m", "--version", "v2",
                "--params", str(pfile), "--knob", "dwell_s=600",
                "--knob", "shadow_sample=0")
        assert p.returncode == 0, p.stdout + p.stderr
        assert "start 'm'" in p.stdout and "version=v2" in p.stdout
        p = run("status")
        assert p.returncode == 0, p.stdout + p.stderr
        assert "v2" in p.stdout and "shadow" in p.stdout
        p = run("start", "--model", "m", "--version", "v3")
        assert p.returncode == 1, p.stdout + p.stderr
        assert "REFUSED" in p.stderr
        p = run("rollback", "--model", "m", "--reason", "drill")
        assert p.returncode == 0, p.stdout + p.stderr
        p = run("status")
        assert p.returncode == 1, p.stdout + p.stderr
        assert "ROLLED_BACK" in p.stdout
        p = run("promote", "--model", "nope")
        assert p.returncode == 2, p.stdout + p.stderr
    finally:
        ep.stop()
        server.close(timeout=10.0)


@pytest.mark.rollout
def test_loadgen_during_rollout_evidence(tmp_path):
    """loadgen --during-rollout: the selfhost run carries a live rollout
    of the same model, prints per-version latency/outcome evidence plus
    the ramp timeline, and the ledger row embeds the whole readout. The
    flag is selfhost-only: with --url it is rejected before any backend
    init (exit 2)."""
    import json as _json
    cli = os.path.join(REPO, "tools", "loadgen.py")
    ledger = str(tmp_path / "ledger.jsonl")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    p = subprocess.run([sys.executable, cli, "--url", "http://x:1",
                        "--during-rollout"], capture_output=True,
                       text=True, timeout=60, env=env)
    assert p.returncode == 2, p.stdout + p.stderr
    assert "selfhost-only" in p.stderr

    # the 1% stage hands the candidate qps/100 requests a second and needs
    # three of them to move on: at 120 qps for 2.5 s the whole run expects
    # under three candidate requests, and none about one run in twelve.
    # 200 qps for 6 s expects a dozen at that stage alone, loaded or not.
    # The candidate has the incumbent's weights, so a p99 gap between them
    # is the box's load: the latency gate (tests/test_rollout.py holds it)
    # gets a slack here that only the ramp's evidence is under test
    p = subprocess.run([sys.executable, cli, "--selfhost",
                        "--during-rollout", "--qps", "200",
                        "--duration", "6", "--ledger", ledger],
                       capture_output=True, text=True, timeout=300,
                       env={**env, "MXNET_ROLLOUT_P99_SLACK": "1000"})
    assert p.returncode == 0, p.stdout + p.stderr
    assert "loadgen: rollout version" in p.stdout
    assert "timeline: start -> serving" in p.stdout
    rows = [_json.loads(l) for l in open(ledger)]
    ro = rows[-1].get("rollout")
    assert ro and ro["version"] == "candidate" and ro["incumbent"]
    assert ro["state"] in ("serving", "promoted")
    assert [h["action"] for h in ro["timeline"]][:2] == ["start",
                                                         "serving"]
    vs = ro["versions"]
    assert set(vs) == {ro["incumbent"], "candidate"}
    for row in vs.values():
        assert abs(sum(row["fractions"].values()) - 1.0) < 1e-6 \
            or sum(row["counts"].values()) == 0
    # the candidate actually served sampled traffic during the run
    assert sum(vs["candidate"]["counts"].values()) > 0
    assert "p50_ms" in vs[ro["incumbent"]]
