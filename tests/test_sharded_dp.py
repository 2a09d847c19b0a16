"""Sharded-optimizer data parallelism + collectives bandwidth lab
(ISSUE 10): the virtual 8-device equivalence matrix for the comm levers —
reduce_scatter vs replicated step-equivalence (fused + kv capture paths),
bf16-reduce tolerance, in-trace bucketing, ZeRO opt-state sharding +
bitwise kill/resume through ShardedCheckpointer, the compression= wire
lever, and the collbench measurement lab."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel, resilience
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import collbench, collectives
from mxnet_tpu.parallel.collectives import bucket_assignment

N_DEV = 8


def _make_net(prefix, hidden=16, out=8):
    """Every param's leading dim divides the 8-device mesh, so the ZeRO
    path shards the complete optimizer state (exact 8x per-chip shrink)."""
    mx.random.seed(3)
    net = nn.HybridSequential(prefix=prefix)
    net.add(nn.Dense(hidden, activation="relu", prefix=prefix + "d0_"),
            nn.Dense(out, prefix=prefix + "d1_"))
    net.initialize(mx.init.Xavier())
    return net


def _batch(rng, n=32, in_dim=10, classes=8):
    return (rng.randn(n, in_dim).astype("float32"),
            rng.randint(0, classes, n).astype("float32"))


def _train(prefix, rng_seed=17, steps=5, **kw):
    rng = np.random.RandomState(0)
    X, Y = _batch(rng)
    t = parallel.DataParallelTrainer(
        _make_net(prefix), gluon.loss.SoftmaxCrossEntropyLoss(),
        "sgd", {"learning_rate": 0.5, "momentum": 0.9}, **kw)
    mx.random.seed(rng_seed)
    t._rng_counter = 0
    loss = None
    for _ in range(steps):
        loss = t.step(X, Y)
    return t, float(loss)


def _params_close(a, b, **tol):
    for ka, kb in zip(sorted(a._params), sorted(b._params)):
        np.testing.assert_allclose(np.asarray(a._params[ka]),
                                   np.asarray(b._params[kb]), **tol)


# =========================================================== step equivalence
def test_reduce_scatter_step_equivalent_fused():
    """ZeRO-1 (reduce-scatter grads, sharded update, all-gather params)
    must match the replicated all-reduce baseline step for step. On the
    CPU backend the two reduction orders agree to float tolerance; the
    documented bound is what the acceptance criterion pins."""
    base, lb = _train("sdp_base_")
    rs, lr = _train("sdp_rs_", grad_reduce="reduce_scatter")
    assert abs(lb - lr) < 1e-5, (lb, lr)
    _params_close(base, rs, rtol=2e-5, atol=2e-6)


def test_reduce_scatter_step_equivalent_kv():
    """Same equivalence through the hybrid kv capture path (grad program +
    kvstore wire + sharded apply program)."""
    base, lb = _train("sdpk_base_", kvstore=mx.kv.create("local"))
    rs, lr = _train("sdpk_rs_", kvstore=mx.kv.create("local"),
                    grad_reduce="reduce_scatter")
    assert abs(lb - lr) < 1e-5, (lb, lr)
    _params_close(base, rs, rtol=2e-5, atol=2e-6)


def test_opt_state_sharded_eight_x():
    """The acceptance criterion: per-chip optimizer-state bytes shrink ~8x
    on the 8-device mesh (exactly 8x here — every leaf's leading dim tiles
    the mesh), and the sharded leaves actually carry the 'dp' sharding."""
    base, _ = _train("sdpb_", steps=1)
    rs, _ = _train("sdps_", steps=1, grad_reduce="reduce_scatter")
    b, s = base.opt_state_bytes(), rs.opt_state_bytes()
    assert b["per_chip_bytes"] == b["total_bytes"]
    assert s["total_bytes"] == b["total_bytes"]
    assert s["per_chip_bytes"] * N_DEV == s["total_bytes"], (b, s)
    sharded = [l for l in jax.tree_util.tree_leaves(rs._opt_state)
               if getattr(l, "ndim", 0) >= 1]
    assert sharded
    for leaf in sharded:
        assert "dp" in str(leaf.sharding.spec), (leaf.shape, leaf.sharding)
    # indivisible leading dims fall back to replication instead of crashing
    odd, _ = _train("sdpo_", steps=1, grad_reduce="reduce_scatter")
    assert odd.comm_config()["grad_reduce"] == "reduce_scatter"


def test_bf16_reduce_tolerance():
    """grad_reduce_dtype='bf16': gradients cross the reduction in bf16 but
    the master math stays f32 (accumulate-in-f32) — trajectories agree to
    bf16 tolerance, and the lever provably changes the program."""
    base, _ = _train("sdpf_base_")
    bf16, _ = _train("sdpf_bf16_", grad_reduce_dtype="bf16")
    _params_close(base, bf16, rtol=5e-2, atol=5e-3)
    # f32 master params stay f32 all the way through
    assert all(v.dtype == jnp.float32 for v in bf16._params.values())
    rng = np.random.RandomState(0)
    X, Y = _batch(rng)
    assert base._lowered_digest(base.lower(X, Y)) != \
        bf16._lowered_digest(bf16.lower(X, Y))


def test_bf16_reduce_on_kv_wire():
    """The kv path casts gradients to the reduction dtype before the wire
    and back to f32 after — same tolerance contract as the fused path."""
    base, _ = _train("sdpw_base_", kvstore=mx.kv.create("local"))
    bf16, _ = _train("sdpw_bf16_", kvstore=mx.kv.create("local"),
                     grad_reduce_dtype="bf16")
    _params_close(base, bf16, rtol=5e-2, atol=5e-3)


def test_bucket_bytes_equivalent():
    """In-trace bucketing (flat concat per bucket_assignment bucket) is
    numerically an identity on the gradient values — same trajectory,
    different (fused-collective) program."""
    base, lb = _train("sdpbk_base_")
    bkt, lk = _train("sdpbk_bkt_", bucket_bytes=256)
    assert abs(lb - lk) < 1e-6
    _params_close(base, bkt, rtol=1e-6, atol=1e-7)
    rng = np.random.RandomState(0)
    X, Y = _batch(rng)
    assert base._lowered_digest(base.lower(X, Y)) != \
        bkt._lowered_digest(bkt.lower(X, Y))


def test_comm_lever_validation():
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    with pytest.raises(MXNetError, match="grad_reduce"):
        parallel.DataParallelTrainer(_make_net("sdvv1_"), loss_fn,
                                     grad_reduce="ring")
    with pytest.raises(MXNetError, match="grad_reduce_dtype"):
        parallel.DataParallelTrainer(_make_net("sdvv2_"), loss_fn,
                                     grad_reduce_dtype="float64")
    with pytest.raises(MXNetError, match="bucket_bytes"):
        parallel.DataParallelTrainer(_make_net("sdvv3_"), loss_fn,
                                     grad_reduce="reduce_scatter",
                                     bucket_bytes=1 << 20)
    # in-trace bucketing has no kv-path consumer: a silently-inert lever
    # would stamp false provenance — refused like its siblings
    with pytest.raises(MXNetError, match="MXNET_UPDATE_AGGREGATION_SIZE"):
        parallel.DataParallelTrainer(_make_net("sdvv5_"), loss_fn,
                                     kvstore=mx.kv.create("local"),
                                     bucket_bytes=1 << 20)
    with pytest.raises(MXNetError, match="compression"):
        parallel.DataParallelTrainer(_make_net("sdvv4_"), loss_fn,
                                     compression={"type": "2bit",
                                                  "threshold": 0.5})


def test_aot_key_covers_comm_levers():
    """A serialized executable must refuse reuse across comm configs: the
    levers change the compiled program and the opt-state placement."""
    rng = np.random.RandomState(0)
    X, Y = _batch(rng)
    keys = set()
    for kw in ({}, {"grad_reduce": "reduce_scatter"},
               {"grad_reduce_dtype": "bf16"}, {"bucket_bytes": 512}):
        t, _ = _train("sdpak%d_" % len(keys), steps=1, **kw)
        k = t._aot_key([jnp.asarray(X), jnp.asarray(Y)])
        keys.add((k["grad_reduce"], k["grad_reduce_dtype"],
                  k["bucket_bytes"]))
    assert len(keys) == 4, keys


# ======================================================= sharded checkpoints
def _resilient(prefix, directory, **kw):
    return resilience.ResilientTrainer(
        _make_net(prefix), gluon.loss.SoftmaxCrossEntropyLoss(),
        "sgd", {"learning_rate": 0.5, "momentum": 0.9},
        directory=directory, preemption=False, **kw)


@pytest.mark.parametrize("use_kv", [False, True], ids=["fused", "kv"])
def test_sharded_optstate_kill_resume_bitwise(tmp_path, use_kv):
    """THE resilience acceptance: a kill/resume through ShardedCheckpointer
    restores the ZeRO-sharded opt-state exactly — bitwise state, bitwise
    continued trajectory vs an uninterrupted run, on both capture paths."""
    rng = np.random.RandomState(0)
    X, Y = _batch(rng)
    kw = dict(grad_reduce="reduce_scatter")
    if use_kv:
        kw["kvstore"] = mx.kv.create("local")

    mx.random.seed(17)
    ref = _resilient("sdr_ref_", str(tmp_path / "ref"), **kw)
    for _ in range(6):
        ref.step(X, Y)

    mx.random.seed(17)
    if use_kv:
        kw["kvstore"] = mx.kv.create("local")
    a = _resilient("sdr_run_", str(tmp_path / "run"), **kw)
    for _ in range(3):
        a.step(X, Y)
    a.save()
    a.close()

    mx.random.seed(4242)        # the restarted process re-pins the seed
    if use_kv:
        kw["kvstore"] = mx.kv.create("local")
    b = _resilient("sdr_run_", str(tmp_path / "run"), **kw)
    b.ensure_initialized(X, Y)
    assert b.resumed_from is not None
    # restored opt-state: bitwise AND back on its sharded placement
    for la, lb in zip(jax.tree_util.tree_leaves(a.trainer._opt_state),
                      jax.tree_util.tree_leaves(b.trainer._opt_state)):
        assert np.array_equal(np.asarray(la), np.asarray(lb))
        if getattr(lb, "ndim", 0) >= 1:
            assert "dp" in str(lb.sharding.spec), lb.sharding
    for _ in range(3):
        b.step(X, Y)
    for ka, kb in zip(sorted(ref.trainer._params),
                      sorted(b.trainer._params)):
        assert np.array_equal(np.asarray(ref.trainer._params[ka]),
                              np.asarray(b.trainer._params[kb])), ka
    ref.close()
    b.close()


# ========================================================= compression lever
def test_compression_lever_converges():
    """compression= wires the 2-bit error-feedback codec into the kv
    gradient path end to end: training converges, and the final loss lands
    within tolerance of the uncompressed run (error feedback loses no
    gradient mass)."""
    kv = mx.kv.create("local")
    comp, lc = _train("sdpc_comp_", steps=25, kvstore=kv,
                      compression={"type": "2bit", "threshold": 0.05})
    plain, lp = _train("sdpc_plain_", steps=25,
                       kvstore=mx.kv.create("local"))
    assert kv.comm_stats["compressed_payload_bytes"] > 0, kv.comm_stats
    assert lc < 0.6 and lp < 0.6, (lc, lp)      # both learned something
    assert abs(lc - lp) < 0.35, (lc, lp)        # and land close together
    assert comp.comm_config()["compression"] == {"type": "2bit",
                                                 "threshold": 0.05}
    assert plain.comm_config()["compression"] is None


def test_bucketed_allreduce_compressed_error_feedback(rng):
    """Host-level compressed allreduce: quantized-shard sum semantics plus
    the exact error-feedback identity (emitted + residual == input)."""
    mesh = parallel.local_mesh("dp")
    gs = [jnp.asarray(rng.randn(8, 4).astype("float32")) for _ in range(3)]
    out, res = collectives.bucketed_allreduce(
        gs, mesh, "dp", bucket_bytes=64,
        compression={"type": "2bit", "threshold": 0.5})
    for g, o, r in zip(gs, out, res):
        dense = np.asarray(g)
        q = np.where(dense >= 0.5, 0.5,
                     np.where(dense <= -0.5, -0.5, 0.0)).astype("float32")
        expect = np.tile(q.sum(axis=0, keepdims=True), (8, 1))
        np.testing.assert_allclose(np.asarray(o), expect, atol=1e-6)
        np.testing.assert_allclose(np.asarray(r), dense - q, atol=1e-6)
    # threading the residuals: sub-threshold mass fires on the next call
    small = [jnp.full((8, 4), 0.3, jnp.float32)]
    out1, res1 = collectives.bucketed_allreduce(
        small, mesh, "dp", compression={"type": "2bit", "threshold": 0.5})
    assert float(jnp.abs(out1[0]).max()) == 0.0      # nothing fired yet
    out2, res2 = collectives.bucketed_allreduce(
        small, mesh, "dp", compression={"type": "2bit", "threshold": 0.5},
        residuals=res1)
    np.testing.assert_allclose(np.asarray(out2[0]), 8 * 0.5)  # all 8 fired
    np.testing.assert_allclose(np.asarray(res2[0]), 0.1, atol=1e-6)


def test_bucket_assignment_rule():
    assert bucket_assignment([4, 4, 4], 100) == [[0, 1, 2]]
    assert bucket_assignment([60, 60, 60], 100) == [[0, 1], [2]]
    assert bucket_assignment([200, 4], 100) == [[0], [1]]
    assert bucket_assignment([], 100) == []


# =============================================================== collectives
def test_broadcast_selects_src_value(rng):
    """Regression for the broadcast that returned x on every branch: the
    result must be the SRC member's value on every device."""
    try:
        from jax import shard_map
    except ImportError:
        from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = parallel.local_mesh("dp")
    x = jnp.asarray(rng.randn(8, 4).astype("float32"))
    for src in (0, 3, 7):
        fn = jax.jit(shard_map(
            lambda v, s=src: collectives.broadcast(v, "dp", src=s),
            mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))
        got = np.asarray(fn(x))
        expect = np.tile(np.asarray(x)[src:src + 1], (8, 1))
        np.testing.assert_allclose(got, expect, atol=1e-6)


# ================================================================= collbench
def test_collbench_rows_and_ledger(tmp_path):
    from mxnet_tpu.observability import xcost
    led = xcost.CostLedger(str(tmp_path / "coll.jsonl"))
    rows = collbench.run(device_counts=(1, 8), payload_sizes=(1 << 14,),
                         steps=2, warmup=1, compression=0.5, ledger=led)
    # 4 ops x 2 counts + 1 compressed row per count
    assert len(rows) == 2 * (len(collbench.OPS) + 1)
    on_disk = led.rows()
    assert len(on_disk) == len(rows)
    for row in on_disk:
        assert row["label"] == "collbench"
        assert row["ms"] > 0
        assert row["op"] in collbench.OPS + ("psum_compressed",)
        if row["n_devices"] > 1:
            assert row["bytes_per_s"] > 0
    comp = [r for r in on_disk if r["op"] == "psum_compressed"
            and r["n_devices"] == 8][0]
    dense = [r for r in on_disk if r["op"] == "psum"
             and r["n_devices"] == 8][0]
    # the on/off comparison: 2-bit codes move ~16-32x fewer wire bytes
    assert comp["algo_bytes"] < dense["algo_bytes"] / 8
    assert comp["wire_reduction_x"] > 8
    # a sweep WITHOUT psum in ops still lands the comparison's dense
    # baseline (measured inside bench_compression) instead of dropping it
    led2 = xcost.CostLedger(str(tmp_path / "coll2.jsonl"))
    rows2 = collbench.run(ops=("reduce_scatter",), device_counts=(8,),
                          payload_sizes=(1 << 14,), steps=2, warmup=0,
                          compression=0.5, ledger=led2)
    assert {r["op"] for r in rows2} == {"reduce_scatter", "psum",
                                        "psum_compressed"}


def test_collbench_telemetry(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    from mxnet_tpu import observability as obs
    collbench.bench_collective("psum", n_devices=8,
                               payload_bytes=1 << 12, steps=2, warmup=0)
    snap = obs.snapshot()["metrics"]
    assert "mxtpu_collective_ms" in snap
    series = snap["mxtpu_collective_ms"]["series"]
    assert any(s["labels"].get("op") == "psum" and s["count"] > 0
               for s in series), series
    bts = snap["mxtpu_collective_bytes_total"]["series"]
    assert any(s["labels"].get("op") == "psum" and s["value"] > 0
               for s in bts), bts


def test_collbench_algo_bytes():
    assert collbench.algo_bytes("psum", 800, 8) == 1400       # 2*(7/8)
    assert collbench.algo_bytes("reduce_scatter", 800, 8) == 700
    assert collbench.algo_bytes("all_gather", 800, 8) == 700
    assert collbench.algo_bytes("ppermute", 800, 8) == 800
    assert collbench.algo_bytes("psum", 800, 1) == 0
    with pytest.raises(MXNetError):
        collbench.algo_bytes("gossip", 800, 8)


def test_scaling_row_shape(tmp_path):
    from mxnet_tpu.observability import xcost
    led = xcost.CostLedger(str(tmp_path / "scale.jsonl"))
    extra = {"model": "tiny", "provenance": "test"}
    row = collbench.scaling_row(batch_per_chip=8, image=8, steps=2,
                                warmup=1, ledger=led, extra=extra)
    assert row["metric"] == "multichip_scaling_efficiency"
    # the persisted row carries the caller's identity fields too (a ledger
    # row without them would match any model-filtered reader)
    assert {k: led.rows()[-1][k] for k in extra} == extra
    assert row["n_devices"] == N_DEV
    assert row["img_s_per_chip_1"] > 0 and row["img_s_per_chip_n"] > 0
    assert row["value"] == round(
        row["img_s_per_chip_n"] / row["img_s_per_chip_1"], 4)
    assert row["comm_config"]["grad_reduce"] == "reduce_scatter"
    ob = row["opt_state_bytes"]
    assert ob["per_chip_bytes"] < ob["total_bytes"]
    assert led.rows()[-1]["metric"] == "multichip_scaling_efficiency"
