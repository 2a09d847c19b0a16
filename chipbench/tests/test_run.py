"""A CPU rehearsal of run.py at a tiny size (data/tinybench): it fails for want
of a chip, passes with the look for a chip skipped, and comes out NOT correct
with the timed path broken underneath, once for each fault a one-chip training
cell can have (a returned loss altered by a percent is not among them: on the
chip no loss has an upper reading, so no loss is compared, PERF.md section 2).
The control (the reference in float8, put in the program's place) goes through
``calibrate.py`` and ``check.judge`` and fails by a limit, not by a NaN."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import calibrate, harness, run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TINY = os.path.join(DATA, "tinybench", "BENCHMARK.json")


def fake_chip(chips):
    import jax
    devs = jax.devices()
    assert devs[0].platform == "cpu" and len(devs) >= chips
    return devs[:chips], harness.load_json(harness.HERE, "peaks.json")["TPU v5 lite"]


def rehearse(workload, trace=0, seed=2147484001):
    return run.run(["--workload", workload, "--seed", str(seed), "--seconds", "0.3",
                    "--trace", str(trace)], bench_path=TINY, root=DATA,
                   require_chip=fake_chip)


def test_without_a_chip_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         "resnet50_v1.train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=harness.ROOT, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not [l for l in p.stdout.splitlines() if l.startswith("{")]


def test_unknown_device_kind_is_an_error(monkeypatch):
    import jax

    class Dev:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(harness.NoChip, match="peaks.json"):
        harness.require_chip(1)

    class V5e(Dev):
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [V5e()])
    with pytest.raises(harness.NoChip, match="asks for 4"):
        harness.require_chip(4)


@pytest.mark.parametrize("workload,trace", [("tiny.train", 0), ("tiny.train", 1),
                                            ("tiny.train_fed", 1)])
def test_rehearsal_is_correct_and_well_formed(workload, trace, capsys):
    r = rehearse(workload, trace)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last)["correct"] is True
    assert list(r)[-1] == "compared" and r["correct"] and r["failed"] == 0
    assert r["attempted"] > 0 and set(r["compared"]) == {
        "grad1_median_leaf", "dparam_median_leaf", "state1_median_leaf"}
    assert set(r["info"]["recorded"]) == {
        "loss1", "loss2", "loss3", "grad1_worst_leaf", "dparam_worst_leaf",
        "sign1_median_leaf", "ddiff_median_leaf", "ddiff_worst_leaf"}
    want = ({"setup_s", "train.items_per_s_per_chip", "train.step_ms_p95"} if not trace
            else {"trainer.compiles_in_window", "step.mfu"})
    assert set(r["metrics"]) == want          # no device number from a CPU
    if trace:
        assert r["metrics"]["trainer.compiles_in_window"]["value"] == 0


def _faults():
    from mxnet_tpu.parallel import DataParallelTrainer
    real = DataParallelTrainer.step

    def part(frac):
        def step(self, x, y):
            n = int(x.shape[0]) // frac
            return real(self, x[:n], y[:n])
        return step

    return {
        "state_unchanged": ("optax", "apply_updates", lambda params, updates: params),
        "half_of_the_batch_left_out": (DataParallelTrainer, "step", part(2)),
    }


@pytest.mark.parametrize("fault", ["state_unchanged", "half_of_the_batch_left_out"])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    import importlib
    faults = _faults()
    target, name, broken = faults[fault]
    if isinstance(target, str):
        target = importlib.import_module(target)
    monkeypatch.setattr(target, name, broken)
    r = rehearse("tiny.train")
    assert r["correct"] is False
    over = [k for k, v in r["compared"].items() if not v["value"] <= v["limit"]]
    assert over, r["compared"]
    if fault == "state_unchanged":
        assert abs(r["compared"]["dparam_median_leaf"]["value"] - 1.0) < 1e-6


def test_the_control_and_the_fault_fail_by_a_limit():
    """calibrate.py's own path at the tiny size: the program passes on every
    seed; the reference in float8_e4m3 reads finite numbers and is over the
    limit of the running statistics' first change; half the batch is over
    the limits of the gradient and the change."""
    lines = calibrate.main(
        ["--workload", "tiny.train", "--seeds", "3", "--controls", "3", "--faults", "3",
         "--witnesses", "1"], bench_path=TINY, root=DATA, require_chip=fake_chip)
    by = {}
    for l in lines:
        by.setdefault(l["side"], []).append(l)
    assert len(by["program"]) == 3 and all(l["correct"] for l in by["program"])
    for l in by["control_float8_e4m3"]:
        assert not l["correct"] and "state1_median_leaf" in l["over"]
        assert all(v == v for v in l["numbers"].values())      # no NaN
    fp8 = min(l["numbers"]["state1_median_leaf"] for l in by["control_float8_e4m3"])
    bf16 = by["witness_reference_bfloat16"][0]["numbers"]["state1_median_leaf"]
    assert fp8 > 3 * bf16       # rounding moves this number in proportion
    for l in by["fault_batch_part"]:
        assert not l["correct"] and "grad1_median_leaf" in l["over"]
