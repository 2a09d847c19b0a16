"""The looped decoder family (``reference/looped_decoder_lm.py``, the cell
``ouro_2_6b.train``'s) at toy widths through ``run.py`` on the CPU, as
``test_tokens.py`` runs the token rehearsal: a bench that only ADDS files
(data/loopbench: the cell's configuration with every size cut, ONE row of 256
tokens a step, ``adam``, bfloat16 compute, limits set from readings). The
sound program is correct; the float8 control, the planted fault (for a batch
of one row: the second half of its positions left out) and the second and
third steps not applied are NOT, each by a named limit and every number
finite."""
import math
import os

import pytest

from chipbench import calibrate, harness, run
from chipbench.tests.test_run import DATA, fake_chip

BENCH = os.path.join(DATA, "loopbench", "BENCHMARK.json")
SEEDS = (2147484001, 7, 1234567891)
HELD = {"sign1_median_leaf", "ddiff_median_leaf"}
# by hand, a token: a layer 4 x 64 x 64 + 3 x 64 x 176 = 50,176 and attention
# 4 x 32 x 257 / 2 = 16,448; a pass 2 x 66,624 + 64 x 512 + 64 = 166,080;
# four passes, x 6
FLOPS_PER_TOKEN = 6 * 4 * (2 * (50176 + 16448) + 64 * 512 + 64)
SEGMENTS = 2 * 4   # the layer-calls; the exits are no segments since PR 32


def rehearse(trace=0, seed=SEEDS[0]):
    return run.run(["--workload", "looped.train", "--seed", str(seed),
                    "--seconds", "0.3", "--trace", str(trace)],
                   bench_path=BENCH, root=DATA, require_chip=fake_chip)


def test_the_rehearsal_configuration_is_the_cells_cut_down():
    cell = harness.load_json(harness.ROOT, "chipbench", "configs", "ouro_2_6b.json")
    tiny = harness.load_json(DATA, "loopbench", "configs", "tiny_looped.json")
    for key in ("builder", "reference", "loss", "loss_kwargs", "total_ut_steps",
                "rope_theta", "rms_norm_eps", "compute_dtype", "item"):
        assert tiny[key] == cell[key], key
    assert tiny["batch_per_chip"] == cell["batch_per_chip"] == 1


@pytest.mark.parametrize("trace", [0, 1])
def test_the_looped_rehearsal_is_correct_and_counts_tokens(trace):
    r = rehearse(trace)
    assert r["correct"] and r["failed"] == 0 and set(r["compared"]) == HELD
    info = r["info"]
    assert math.isclose(info["items_per_s_per_chip"],
                        info["steps"] * 256 / info["window_s"])
    if not trace:
        assert set(r["metrics"]) == {"setup_s", "train.items_per_s_per_chip",
                                     "train.step_ms_p95"}
        return
    # no device trace on the CPU: the trace's readers leave their metric out
    assert set(r["metrics"]) == {
        "trainer.compiles_in_window", "step.mfu", "trainer.enqueue_ms",
        "trainer.host_ms", "trainer.capture_s", "jit.compile_s",
        "trainer.remat_segments"}
    assert r["metrics"]["trainer.compiles_in_window"]["value"] == 0
    # the counter is the process's: a multiple of one capture's segments here
    segments = r["metrics"]["trainer.remat_segments"]["value"]
    assert segments >= SEGMENTS and segments % SEGMENTS == 0
    peak = harness.load_json(harness.HERE, "peaks.json")["TPU v5 lite"]["bf16_flops_per_s"]
    assert math.isclose(r["metrics"]["step.mfu"]["value"],
                        100.0 * FLOPS_PER_TOKEN * info["items_per_s_per_chip"] / peak)


def test_the_flash_reader_finds_the_kernel_by_name_or_nothing():
    reader = harness.load_module(os.path.join(
        harness.HERE, "metrics", "kernels.flash_fwd_roofline.py"), "flash_reader")
    cell = harness.load_json(harness.ROOT, "chipbench", "configs", "ouro_2_6b.json")
    peaks = {"bf16_flops_per_s": 197e12}
    run_ = {"cfg": cell, "n_items": 4096, "chips": 1, "peaks": peaks, "trace": None}
    assert reader.read(run_) is None
    # 16 layer-calls forward and 16 recomputed, 1.5 ms each, over 2 steps
    by_op = {(name % i, "custom-call"): 2 * 1_500_000_000 for i in range(16)
             for name in ("jvp_flash_attention_fwd_.%d", "flash_attention_fwd.%d")}
    by_op["fusion.1", "convolution fusion"] = 10 ** 12
    dev = {"busy_ps": 1, "by_op": by_op}
    run_["trace"] = {"steps": 2, "devices": [dev]}
    least = 2 * 8390656 * 16 * 4096 / 197e12             # 5.58 ms a step
    assert math.isclose(reader.read(run_), 100.0 * least / 48e-3)
    run_["trace"] = {"steps": 2, "devices": [{"busy_ps": 1, "by_op": {
        ("fusion.1", "convolution fusion"): 10 ** 12}}]}
    assert reader.read(run_) is None                      # the forward fell back
    assert reader.read(dict(run_, cfg={"image": 224})) is None


def _faults():
    from mxnet_tpu.parallel import DataParallelTrainer, data_parallel
    real = DataParallelTrainer.step

    def half(self, x, y):
        n = int(x.shape[1]) // 2          # one row: half its positions
        return real(self, x[:, :n], y[:, :n])

    def first_step_only(self, x, y):
        if self._step_fn is None:
            return real(self, x, y)
        kept = data_parallel._copy_tree((self._params, self._aux, self._opt_state))
        loss = real(self, x, y)
        self._params, self._aux, self._opt_state = kept
        return loss

    return {"half_of_the_tokens_left_out": half,
            "second_and_third_steps_not_applied": first_step_only}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("fault", ["half_of_the_tokens_left_out",
                                   "second_and_third_steps_not_applied"])
def test_a_broken_timed_path_is_not_correct(fault, seed, monkeypatch):
    from mxnet_tpu.parallel import DataParallelTrainer
    monkeypatch.setattr(DataParallelTrainer, "step", _faults()[fault])
    r = rehearse(seed=seed)
    assert r["correct"] is False
    numbers = dict(r["info"]["recorded"], **{k: v["value"] for k, v in r["compared"].items()})
    assert all(math.isfinite(v) for v in numbers.values()), numbers
    over = {k for k, v in r["compared"].items() if not v["value"] <= v["limit"]}
    assert "ddiff_median_leaf" in over
    if fault == "half_of_the_tokens_left_out":
        assert "sign1_median_leaf" in over
    else:
        assert numbers["sign1_median_leaf"] < r["compared"]["sign1_median_leaf"]["limit"]
        assert 0.5 < numbers["ddiff_median_leaf"] < 0.8       # one step of three


def test_the_control_and_the_fault_fail_and_the_witness_passes():
    lines = calibrate.main(
        ["--workload", "looped.train", "--seeds", "3", "--controls", "3", "--faults", "3",
         "--witnesses", "3"], bench_path=BENCH, root=DATA, require_chip=fake_chip)
    by = {}
    for l in lines:
        assert all(math.isfinite(v) for v in l["numbers"].values()), l
        by.setdefault(l["side"], []).append(l)
    assert {k: len(v) for k, v in by.items()} == {
        "program": 3, "control_float8_e4m3": 3, "fault_batch_part": 3,
        "witness_reference_bfloat16": 3}
    for side in ("program", "witness_reference_bfloat16"):
        assert all(l["correct"] for l in by[side]), side
    for side in ("control_float8_e4m3", "fault_batch_part"):
        for l in by[side]:
            assert not l["correct"] and set(l["over"]) == HELD, l
    sound = max(l["numbers"]["sign1_median_leaf"] for l in by["program"])
    control = min(l["numbers"]["sign1_median_leaf"] for l in by["control_float8_e4m3"])
    assert control > 3 * sound
