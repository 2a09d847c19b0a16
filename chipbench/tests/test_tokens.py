"""The token path, proved on a second rehearsal bench (data/tokenbench: an
embedding, two gated-SiLU dense layers, an untied head; vocabulary 512, rows
of 128 tokens, ``adam``, bfloat16 compute) that only ADDS files: its own
configuration with a stated cut, its own reference, a ``tokens`` mix, limits
set from readings. The sound program and the bfloat16 witness are correct;
half the rows left out, the float8 control and the second and third steps not
applied are NOT, each by a named limit and with every number finite."""
import hashlib
import math
import os

import numpy as np
import pytest

from chipbench import calibrate, follow, harness, run, traffic
from chipbench.tests.test_run import DATA, fake_chip

BENCH = os.path.join(DATA, "tokenbench", "BENCHMARK.json")
SEEDS = (2147484001, 7, 1234567891)
HELD = {"sign1_median_leaf", "ddiff_median_leaf"}
# by hand: 2 layers x (64x128 + 64x128 + 128x64) + the head 64x512 = 81,920
# multiply-adds a token, x 6
FLOPS_PER_TOKEN = 6 * (2 * 3 * 64 * 128 + 64 * 512)


def rehearse(workload, trace=0, seed=SEEDS[0]):
    return run.run(["--workload", workload, "--seed", str(seed), "--seconds", "0.3",
                    "--trace", str(trace)], bench_path=BENCH, root=DATA,
                   require_chip=fake_chip)


@pytest.mark.parametrize("workload,trace", [("lm.train", 0), ("lm.train", 1),
                                            ("lm.train_fed", 0), ("lm.train_fed", 1)])
def test_the_token_rehearsal_is_correct_and_counts_tokens(workload, trace):
    r = rehearse(workload, trace)
    assert r["correct"] and r["failed"] == 0 and set(r["compared"]) == HELD
    assert "state1_median_leaf" not in r["info"]["recorded"]    # no such leaf
    assert {"grad1_median_leaf", "dparam_median_leaf"} <= set(r["info"]["recorded"])
    info = r["info"]
    tokens = info["steps"] * 8 * 128
    assert math.isclose(info["items_per_s_per_chip"], tokens / info["window_s"])
    if not trace:
        assert set(r["metrics"]) == {"setup_s", "train.items_per_s_per_chip",
                                     "train.step_ms_p95"}
        return
    fed = {"feed.starved_ms", "feed.produce_ms"} if workload.endswith("fed") else set()
    assert set(r["metrics"]) == fed | {
        "trainer.compiles_in_window", "step.mfu", "trainer.enqueue_ms",
        "trainer.host_ms", "trainer.capture_s", "jit.compile_s"}
    assert r["metrics"]["trainer.compiles_in_window"]["value"] == 0
    peak = harness.load_json(harness.HERE, "peaks.json")["TPU v5 lite"]["bf16_flops_per_s"]
    assert math.isclose(r["metrics"]["step.mfu"]["value"],
                        100.0 * FLOPS_PER_TOKEN * info["items_per_s_per_chip"] / peak)


def _faults():
    from mxnet_tpu.parallel import DataParallelTrainer, data_parallel
    real = DataParallelTrainer.step

    def half(self, x, y):
        n = int(x.shape[0]) // 2
        return real(self, x[:n], y[:n])

    def first_step_only(self, x, y):
        if self._step_fn is None:
            return real(self, x, y)
        kept = data_parallel._copy_tree((self._params, self._aux, self._opt_state))
        loss = real(self, x, y)
        self._params, self._aux, self._opt_state = kept
        return loss

    return {"half_of_the_rows_left_out": half,
            "second_and_third_steps_not_applied": first_step_only}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("fault", ["half_of_the_rows_left_out",
                                   "second_and_third_steps_not_applied"])
def test_a_broken_timed_path_is_not_correct(fault, seed, monkeypatch):
    from mxnet_tpu.parallel import DataParallelTrainer
    monkeypatch.setattr(DataParallelTrainer, "step", _faults()[fault])
    r = rehearse("lm.train", seed=seed)
    assert r["correct"] is False
    numbers = dict(r["info"]["recorded"], **{k: v["value"] for k, v in r["compared"].items()})
    assert all(math.isfinite(v) for v in numbers.values()), numbers
    over = {k for k, v in r["compared"].items() if not v["value"] <= v["limit"]}
    assert "ddiff_median_leaf" in over
    if fault == "half_of_the_rows_left_out":
        # the gap of norms the image cells hold is blind to it under Adam
        assert "sign1_median_leaf" in over and numbers["grad1_median_leaf"] < 1e-3
    else:
        assert numbers["sign1_median_leaf"] < r["compared"]["sign1_median_leaf"]["limit"]
        assert 0.5 < numbers["ddiff_median_leaf"] < 0.8       # one step of three


def test_the_control_and_the_fault_fail_and_the_witness_passes():
    lines = calibrate.main(
        ["--workload", "lm.train", "--seeds", "3", "--controls", "3", "--faults", "3",
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


def test_adam_is_optax_s_chain_over_three_steps():
    import jax
    import jax.numpy as jnp
    import optax
    opt = {"name": "adam", "learning_rate": 1e-2, "beta1": 0.8, "beta2": 0.9,
           "epsilon": 1e-6, "wd": 0.05}
    key = jax.random.PRNGKey(0)
    leaves = [jax.random.normal(jax.random.fold_in(key, i), s, jnp.float32)
              for i, s in enumerate([(7, 5), (5,), (3, 5)])]

    def loss_fn(lv, x, y):
        h = jnp.tanh(x @ lv[0] + lv[1])
        return jnp.mean(jnp.square(h @ lv[2].T - y)), []

    batches = [(jax.random.normal(jax.random.fold_in(key, 10 + i), (4, 7), jnp.float32),
                jax.random.normal(jax.random.fold_in(key, 20 + i), (4, 3), jnp.float32))
               for i in range(3)]
    init_state, step = follow.make_step(loss_fn, [True] * 3, opt)
    got = follow.follow(init_state, step, leaves, [True] * 3, batches, opt["learning_rate"])

    tx = optax.chain(optax.add_decayed_weights(opt["wd"]),
                     optax.adam(opt["learning_rate"], b1=opt["beta1"], b2=opt["beta2"],
                                eps=opt["epsilon"]))
    params, state, losses, first = list(leaves), tx.init(list(leaves)), [], None
    for x, y in batches:
        (loss, _aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, x, y)
        updates, state = tx.update(grads, state, params)
        params = optax.apply_updates(params, updates)
        losses.append(float(loss))
        first = first or [np.asarray(p - l) for p, l in zip(params, leaves)]
    np.testing.assert_allclose(got["loss"], losses, rtol=1e-6)
    for mine, theirs in zip(got["update1"], first):      # of weights of order 1
        np.testing.assert_allclose(mine, theirs, atol=1e-6)
    for mine, w0, w in zip(got["change"], leaves, params):
        np.testing.assert_allclose(mine, np.asarray(w - w0), atol=1e-6)
    with pytest.raises(SystemExit, match="does not follow"):
        follow.make_step(loss_fn, [True] * 3, dict(opt, name="lion"))


# sha256 over the first two batches of the tiny image configuration at seed
# 2147484001 as the PARENT of the PR that brought traffic kinds made them
# (dtype, shape and bytes of x and labels): the images generator is untouched
PARENT_IMAGES = {
    ("resident", "NHWC"): "b5ee6db58b3997dcc7fc1db6a215fd023b856a90a4e07092c4522bd1ac8038b1",
    ("resident", "NCHW"): "c75231893ec354b924903cda8abfa88250b0de69216b189b0d0d90e72b8e20ea",
    ("fed_uint8", "NHWC"): "417112f47a3a5cb8f99d7e6f4f85661a543bde0762339ffda0ff9d3caca34dd2",
    ("fed_uint8", "NCHW"): "f6cd770001933fd0a74d7caacd4d59aa1df8c59e95b379dd673ad9aedf7605c5",
}


def _sharding():
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    return NamedSharding(Mesh(np.array(jax.devices()[:1]), ("dp",)), PartitionSpec("dp"))


@pytest.mark.parametrize("mix,layout", sorted(PARENT_IMAGES))
def test_the_image_generator_makes_the_parent_s_bytes(mix, layout):
    import mxnet_tpu  # noqa: F401  (x64 on, as in every run)
    cfg = dict(harness.load_json(DATA, "tinybench", "configs", "tiny_resnet.json"),
               layout=layout)
    pool = traffic.make_pool(harness.load_json(DATA, "tinybench", "traffic", mix + ".json"),
                             cfg, 2147484001, 8, _sharding())
    h = hashlib.sha256()
    for x, y in pool[:2]:
        x, y = np.asarray(x), np.asarray(y)
        h.update(str((x.dtype, x.shape, y.dtype, y.shape)).encode())
        h.update(x.tobytes())
        h.update(y.tobytes())
    assert h.hexdigest() == PARENT_IMAGES[mix, layout]


def test_token_rows_are_packed_documents_with_zipf_ids():
    import jax
    import mxnet_tpu  # noqa: F401
    mix = harness.load_json(DATA, "tokenbench", "traffic", "tokens.json")
    cfg = {"vocab_held": 512, "seq_len": 2048}
    make = jax.jit(lambda k, i: traffic.batch_tokens(mix, cfg, k, i, 16))
    key = traffic.seed_key(2 ** 31 + 5)
    x, y = (np.asarray(a) for a in make(key, 0))
    assert x.shape == y.shape == (16, 2048) and x.dtype == y.dtype == np.int32
    assert (x[:, 1:] == y[:, :-1]).all()                   # labels are the next ids
    stream = np.concatenate([x, y[:, -1:]], 1).reshape(-1)
    assert 0 <= stream.min() and stream.max() < 512 and stream[0] == mix["boundary_id"]
    # documents: lognormal lengths, median 300, clipped to [8, 8192]
    opens = np.flatnonzero(stream == mix["boundary_id"])
    lengths = np.diff(opens)
    assert lengths.min() >= mix["lengths"]["min"] and 150 < np.median(lengths) < 600
    assert lengths.max() > 4 * np.median(lengths)          # a heavy tail
    # Zipf over the other 511 ids: rank 1 is 2 ** 1.1 times as frequent as rank 2
    counts = np.bincount(stream, minlength=512)[1:]
    assert counts[0] > counts[1] > counts[3] > counts[15] > counts[127]
    assert 1.7 < counts[0] / counts[1] < 2.7
    again, _ = make(key, 0)
    other, _ = make(key, 1)
    assert (np.asarray(again) == x).all() and (np.asarray(other) != x).any()
    pool = traffic.make_pool(dict(mix, placement="host", pool=1), cfg, 2 ** 31 + 5, 16,
                             _sharding())
    assert isinstance(pool[0][0], np.ndarray) and (pool[0][0] == x).all()
    with pytest.raises(SystemExit, match="unknown traffic kind"):
        traffic.make_pool(dict(mix, kind="audio"), cfg, 1, 16, _sharding())
