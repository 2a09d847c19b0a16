"""The ZAYA1 decoder family (``reference/zaya_decoder_lm.py``, the cell
``zaya1_8b.train``'s) at toy widths through ``run.py`` on the CPU, as
``test_looped.py`` runs the looped family: a bench that only ADDS files
(data/moebench: the cell's configuration with every size cut, 4 of 8 experts
held, TWO rows of 128 tokens a step, ``sgd`` with momentum, bfloat16 compute,
limits set from readings). The harness finds the cell and follows three
steps; the sound program is correct; the float8 control, the planted fault
(one row of the two left out) and the second and third steps not applied are
NOT, each by a named limit and every number finite."""
import math
import os

import pytest

from chipbench import calibrate, harness, run
from chipbench.tests.test_run import DATA, fake_chip

BENCH = os.path.join(DATA, "moebench", "BENCHMARK.json")
SEEDS = (2147484001, 7, 1234567891)
HELD = {"sign1_median_leaf", "ddiff_median_leaf"}
# by hand, a token: attention projections 64 x (64 + 32 + 16 + 16) + 64 x 64,
# convolutions 96 x 2 + 96 x 16 x 2, scores 4 x 32 x 129 / 2, router 64 x 32
# + 2 x 32 x 32 + 32 x 8, the held half of the experts 3 x 64 x 48 / 2; two
# layers, the head 64 x 512; x 6
LAYER = (64 * 128 + 64 * 64 + 96 * 2 + 96 * 16 * 2 + 4 * 32 * 129 / 2
         + 64 * 32 + 2 * 32 * 32 + 32 * 8 + 3 * 64 * 48 / 2)
FLOPS_PER_TOKEN = 6 * (2 * LAYER + 64 * 512)
LAYERS = 2


def rehearse(trace=0, seed=SEEDS[0]):
    return run.run(["--workload", "moe.train", "--seed", str(seed),
                    "--seconds", "0.3", "--trace", str(trace)],
                   bench_path=BENCH, root=DATA, require_chip=fake_chip)


def test_the_rehearsal_configuration_is_the_cells_cut_down():
    cell = harness.load_json(harness.ROOT, "chipbench", "configs", "zaya1_8b.json")
    tiny = harness.load_json(DATA, "moebench", "configs", "tiny_zaya.json")
    for key in ("builder", "reference", "loss", "optimizer", "cca_time0", "cca_time1",
                "rope_parameters", "rms_norm_eps", "compute_dtype", "item", "reduced",
                "num_experts_per_tok", "partial_rotary_factor", "tie_word_embeddings"):
        assert tiny[key] == cell[key], key
    assert tiny["batch_per_chip"] == cell["batch_per_chip"] == 2
    for c in (cell, tiny):       # half the experts held, from the first
        assert 2 * c["num_experts"] == c["published"]["num_experts"]
        assert c["deployment"]["first_expert"] == 0


def test_required_flops_equal_the_count_by_hand():
    bench = harness.load_json(BENCH)
    _cell, cfg, _mix, _limits, ref = harness.find_cell(bench, "moe.train", DATA)
    assert ref.train_flops_per_item(cfg) == FLOPS_PER_TOKEN


@pytest.mark.parametrize("trace", [0, 1])
def test_the_moe_rehearsal_is_correct_and_counts_tokens(trace):
    r = rehearse(trace)
    assert r["correct"] and r["failed"] == 0 and set(r["compared"]) == HELD
    info = r["info"]
    assert len(info["program"]) == len(info["reference"]) == 3
    assert math.isclose(info["items_per_s_per_chip"],
                        info["steps"] * 2 * 128 / info["window_s"])
    if not trace:
        assert set(r["metrics"]) == {"setup_s", "train.items_per_s_per_chip",
                                     "train.step_ms_p95"}
        return
    # no device trace on the CPU: the trace's readers leave their metric out,
    # the grouped-product roofline among them; the expert layers took the
    # plain route, so the counter's reader reads 0 grouped layers
    assert set(r["metrics"]) == {
        "trainer.compiles_in_window", "step.mfu", "trainer.enqueue_ms",
        "trainer.host_ms", "trainer.capture_s", "jit.compile_s",
        "trainer.moe_grouped_layers"}
    assert r["metrics"]["trainer.compiles_in_window"]["value"] == 0
    assert r["metrics"]["trainer.moe_grouped_layers"]["value"] == 0
    from mxnet_tpu.observability import catalog
    plain = catalog.MOE_LOWERED.value(route="plain")
    assert plain >= LAYERS and plain % LAYERS == 0
    peak = harness.load_json(harness.HERE, "peaks.json")["TPU v5 lite"]["bf16_flops_per_s"]
    assert math.isclose(r["metrics"]["step.mfu"]["value"],
                        100.0 * FLOPS_PER_TOKEN * info["items_per_s_per_chip"] / peak)


def test_the_gmm_reader_finds_the_kernels_by_name_or_nothing():
    reader = harness.load_module(os.path.join(
        harness.HERE, "metrics", "kernels.moe_gmm_roofline.py"), "gmm_reader")
    cell = harness.load_json(harness.ROOT, "chipbench", "configs", "zaya1_8b.json")
    peaks = {"bf16_flops_per_s": 197e12}
    run_ = {"cfg": cell, "n_items": 16384, "chips": 1, "peaks": peaks, "trace": None}
    assert reader.read(run_) is None
    # four layers of 3 forward, 3 transposed and 3 weight-gradient products,
    # 0.5 ms each, over 2 steps
    by_op = {("%s.%d" % (name, i), "custom-call"): 2 * 500_000_000
             for i in range(12) for name in ("moe_gmm_t", "moe_gmm", "moe_gmm_dw")}
    by_op["fusion.1", "convolution fusion"] = 10 ** 12
    run_["trace"] = {"steps": 2, "devices": [{"busy_ps": 1, "by_op": by_op}]}
    least = 6 * 3 * 2048 * 2048 * 0.5 * 4 * 16384 / 197e12       # 12.6 ms a step
    assert math.isclose(reader.read(run_), 100.0 * least / 18e-3)
    run_["trace"] = {"steps": 2, "devices": [{"busy_ps": 1, "by_op": {
        ("fusion.1", "convolution fusion"): 10 ** 12}}]}
    assert reader.read(run_) is None                      # the layers fell back
    assert reader.read(dict(run_, cfg={"image": 224})) is None


def _faults():
    from mxnet_tpu.parallel import DataParallelTrainer, data_parallel
    real = DataParallelTrainer.step

    def one_row(self, x, y):
        return real(self, x[:1], y[:1])

    def first_step_only(self, x, y):
        if self._step_fn is None:
            return real(self, x, y)
        kept = data_parallel._copy_tree((self._params, self._aux, self._opt_state))
        loss = real(self, x, y)
        self._params, self._aux, self._opt_state = kept
        return loss

    return {"one_row_of_two_left_out": one_row,
            "second_and_third_steps_not_applied": first_step_only}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("fault", ["one_row_of_two_left_out",
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
    if fault == "one_row_of_two_left_out":
        assert "sign1_median_leaf" in over
    else:
        assert numbers["sign1_median_leaf"] < r["compared"]["sign1_median_leaf"]["limit"]


def test_the_control_and_the_fault_fail_and_the_witness_passes():
    lines = calibrate.main(
        ["--workload", "moe.train", "--seeds", "3", "--controls", "3", "--faults", "3",
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
