#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that mxnet_tpu still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the full width of ResNet-50 (random weights from ``--seed``), in ONE process:

  train       model_zoo resnet50_v1 (NHWC) under parallel.DataParallelTrainer,
              sgd+momentum, bf16 compute, batch 256 at 224x224: finite falling
              loss, state on the TPU device, no recompile after the first step
  serve       the same net exported and served by serving.ModelServer through
              ModelConfig (buckets 1/8/32): answers match a direct hybridized
              forward, executor on the TPU device
  kernels     nd.contrib.flash_attention (B*H=16, T=2048, D=128, bf16, causal;
              the forward kernel and the backward kernel; again at
              (16, 4096, 128) under jax.checkpoint with a recomputed
              segment's policy: one forward kernel, the backward kernel,
              the plain call's gradients) and the
              softmax_cross_entropy op at (4096, 32768) bf16, each against its
              jnp reference and each shown to lower to a Pallas kernel
              (``tpu_custom_call``)
  imperative  an autograd.record() LSTM language-model loop on mx.tpu()
              NDArrays (PTB widths), exercising the per-op jit cache

``--chips 4`` runs ONLY the multi-chip phase and its one-device comparison:
the ResNet-50 trainer step on a 4-device ``dp`` mesh with the default
all-reduce and with ``grad_reduce="reduce_scatter"`` against the one-device
step from identical weights and data, then a short
``Module(context=[mx.tpu(i) for i in range(4)])`` fit.

The LAST stdout line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
Any failed phase, and any platform other than ``tpu``, gives ``"ok": false``
and a non-zero exit. ``--rehearse`` runs every phase at a tiny size so the
control flow can be checked on the CPU backend (Pallas in interpret mode);
its verdict is still ``"ok": false``.
"""
import argparse
import functools
import json
import os
import re
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def emit(**fields):
    print(json.dumps(fields, default=str), flush=True)


def mem_stats(dev):
    stats = dev.memory_stats() or {}
    return {k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use",
                                  "bytes_limit") if k in stats}


def on_device(tree, devices):
    """Every array leaf of ``tree`` lives exactly on ``devices``."""
    import jax
    want = set(devices)
    leaves = [l for l in jax.tree_util.tree_leaves(tree)
              if hasattr(l, "devices")]
    assert leaves, "no array leaves to check"
    for leaf in leaves:
        assert set(leaf.devices()) == want, (leaf.devices(), want)
    return sorted(str(d) for d in want)


def rel_err(got, ref):
    import numpy as np
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    assert np.all(np.isfinite(got)), "non-finite values"
    return float(np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) + 1e-12))


def compiles():
    from mxnet_tpu.observability import jit_hooks
    return int(jit_hooks.JIT_COMPILES.value() or 0)


# ------------------------------------------------------------------- sizes
FULL = dict(batch=256, image=224, classes=1000, train_steps=6, lr=0.02,
            buckets=(1, 8, 32), bursts=(1, 3, 8, 5, 20, 32, 2, 11),
            fa=(2, 8, 2048, 128), fa_dtype="bfloat16",
            fa_kept=(1, 16, 4096, 128),     # ouro_2_6b.train's layer-call
            ce=(4096, 32768), ce_dtype="bfloat16",
            # zaya1_8b.train's expert layer: tokens, width, hidden, held
            moe=(16384, 2048, 2048, 8), moe_dtype="bfloat16",
            lm=dict(vocab=10000, embed=200, hidden=200, layers=2,
                    batch=32, bptt=35, steps=6),
            mc_steps=3, mc_loss_tol=2.5e-2,
            fit_image=224, fit_batch=64, fit_batches=3,
            probe=dict(features=512, hidden=1024, classes=16, batch=256,
                       steps=3))
TINY = dict(batch=8, image=32, classes=10, train_steps=3,
            # 8 images of 32 px leave the last stage's BatchNorms 8 samples a
            # channel: at the cells' 0.02 the three losses are chaos (a
            # rounding moves them by 30%), and "the loss falls" is a coin
            lr=0.002,
            buckets=(1, 2, 4), bursts=(1, 3, 4, 2),
            fa=(1, 2, 256, 128), fa_dtype="float32",
            fa_kept=(1, 2, 256, 128),
            ce=(64, 512), ce_dtype="float32",
            moe=(192, 128, 256, 4), moe_dtype="float32",
            lm=dict(vocab=50, embed=16, hidden=16, layers=1,
                    batch=4, bptt=5, steps=3),
            mc_steps=2, mc_loss_tol=0.25,
            fit_image=64,   # above 32 px the symbol takes its ImageNet stem
            fit_batch=8, fit_batches=2,
            probe=dict(features=16, hidden=32, classes=4, batch=16,
                       steps=3))


def build_resnet(cfg, seed, prefix):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    mx.random.seed(seed)
    net = vision.resnet50_v1(classes=cfg["classes"], layout="NHWC",
                             prefix=prefix)
    net.initialize(mx.init.Xavier())
    return net


def resnet_batch(cfg, seed, batch=None):
    import numpy as np
    rs = np.random.RandomState(seed)
    b = batch or cfg["batch"]
    x = rs.uniform(-1, 1, (b, cfg["image"], cfg["image"], 3)) \
        .astype("float32")
    y = rs.randint(0, cfg["classes"], (b,)).astype("float32")
    return x, y


def net_weights(net, trainer, prefix=""):
    """The net's trainable weights as host arrays, names less ``prefix``."""
    return {p.name[len(prefix):]: p.data().asnumpy()
            for p in net.collect_params().values()
            if p.name in trainer._params}


def make_trainer(net, mesh, lr, **kw):
    """The benchmark cells' trainer configuration with a small learning
    rate: 0.1 with no warm-up overshoots on a repeated batch (7.8 -> 13.0 at
    step 3 in the CPU rehearsal), and "the loss falls" has to be a check
    that means something."""
    from mxnet_tpu import gluon, parallel
    return parallel.DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": lr, "momentum": 0.9, "wd": 1e-4},
        compute_dtype="bfloat16", mesh=mesh, **kw)


# ------------------------------------------------------------------ phases
def phase_train(cfg, seed, dev):
    """ResNet-50 through DataParallelTrainer on one chip. Returns the net
    (trained weights synced back) for the serve phase."""
    import numpy as np
    from mxnet_tpu.parallel import local_mesh
    net = build_resnet(cfg, seed, "smoke_")
    trainer = make_trainer(net, local_mesh("dp", devices=[dev]), cfg["lr"])
    x, y = resnet_batch(cfg, seed)
    c0 = compiles()
    t0 = time.perf_counter()
    losses = [float(trainer.step(x, y))]
    first_s = time.perf_counter() - t0
    c1 = compiles()
    t0 = time.perf_counter()
    for _ in range(cfg["train_steps"] - 1):
        losses.append(float(trainer.step(x, y)))
    rest_s = time.perf_counter() - t0
    recompiles = compiles() - c1
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], "loss did not fall: %r" % (losses,)
    assert recompiles == 0, "%d recompiles after the first step" % recompiles
    placed = on_device((trainer._params, trainer._aux, trainer._opt_state),
                       [dev])
    # the step donates the trainer's state; the net's own arrays must have
    # survived it (asnumpy raises on a deleted buffer), before and after
    # the trained weights are handed back
    net_weights(net, trainer)
    trainer.sync_to_net()
    net_weights(net, trainer)
    emit(phase="train", ok=True, losses=[round(l, 4) for l in losses],
         first_step_s=round(first_s, 2), compiles_first_step=c1 - c0,
         later_steps_s=round(rest_s, 2), recompiles_later=recompiles,
         state_devices=placed, memory=mem_stats(dev))
    return net


def phase_serve(cfg, seed, dev, net):
    """Export → ModelConfig → ModelServer; answers vs the direct hybridized
    forward of the same net."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.serving import ModelConfig, ModelServer
    top = max(cfg["buckets"])
    x, _ = resnet_batch(cfg, seed + 1, batch=top)
    net.hybridize()
    t0 = time.perf_counter()
    direct = net(mx.nd.array(x)).asnumpy()
    direct_s = time.perf_counter() - t0
    assert direct.shape == (top, cfg["classes"]), direct.shape
    with tempfile.TemporaryDirectory() as tmp:
        sym_file, param_file = net.export(os.path.join(tmp, "resnet50"))
        with open(sym_file) as f:
            sym_json = f.read()
        with open(param_file, "rb") as f:
            param_bytes = f.read()
    cfg_m = ModelConfig("resnet50", sym_json, param_bytes,
                        feature_shape=x.shape[1:], buckets=cfg["buckets"],
                        deadline_ms=10000, max_wait_ms=20, max_queue=256)
    want_dev = (1 if dev.platform == "cpu" else 2, dev.id)
    assert (cfg_m.dev_type, cfg_m.dev_id) == want_dev, \
        "ModelConfig did not default to %r: %r" % (
            want_dev, (cfg_m.dev_type, cfg_m.dev_id))
    srv = ModelServer([cfg_m], drain_on_preemption=False)
    t0 = time.perf_counter()
    srv.start(warm=True)
    warm_s = time.perf_counter() - t0
    try:
        n_req, worst = 0, 0.0
        for burst in cfg["bursts"]:
            futs = [srv.submit("resnet50", x[i]) for i in range(burst)]
            for i, fut in enumerate(futs):
                got = fut.result(timeout=120)
                assert got.shape == (cfg["classes"],), got.shape
                worst = max(worst, rel_err(got, direct[i]))
            n_req += burst
        stats = srv.stats("resnet50")
        pred = srv._models["resnet50"].cache.get(top)
        placed = on_device([a._data for a in pred._args.values()], [dev])
    finally:
        srv.close()
    assert worst < 2e-2, "server answers differ from direct forward: %g" \
        % worst
    assert stats["counts"]["ok"] == n_req, stats["counts"]
    assert stats["buckets_compiled"] == sorted(cfg["buckets"]), stats
    assert stats["device"] == str(dev), (stats["device"], str(dev))
    emit(phase="serve", ok=True, requests=n_req, batches=stats["batches"],
         buckets_compiled=stats["buckets_compiled"], max_rel_err=worst,
         server_device=stats["device"], predictor_devices=placed,
         direct_forward_s=round(direct_s, 2), warm_s=round(warm_s, 2),
         p50_ms=stats.get("p50_ms"), p99_ms=stats.get("p99_ms"),
         memory=mem_stats(dev))


def phase_kernels(cfg, seed, dev, on_tpu):
    """Both Pallas kernels through their nd ops, against the jnp
    references, with the path that ran asserted from the lowered text."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.ops.registry import jitted_op, normalize_attrs

    def pallas_in(lowered):
        text = lowered.as_text()
        took = "tpu_custom_call" in text
        assert took or not on_tpu, "op did not lower to a Pallas kernel"
        return took

    ctx = mx.context.current_context()
    mx.random.seed(seed)
    # ---- flash attention: forward + gradient through the nd op
    B, H, T, D = cfg["fa"]
    dt = cfg["fa_dtype"]
    q, k, v, w = [mx.nd.random_normal(shape=(B, H, T, D), dtype=dt)
                  for _ in range(4)]
    for a in (q, k, v):
        a.attach_grad()
    t0 = time.perf_counter()
    with autograd.record():
        out = mx.nd.contrib.flash_attention(q, k, v, causal=True)
        loss = (out * w).sum()
    loss.backward()
    got = [np.asarray(a.astype("float32").asnumpy())
           for a in (out, q.grad, k.grad, v.grad)]
    fa_s = time.perf_counter() - t0
    scale = 1.0 / (D ** 0.5)
    flat = lambda a: a._data.reshape(B * H, T, D)  # noqa: E731

    def ref_loss(qf, kf, vf):
        o, _ = pk._fa_reference(qf, kf, vf, scale, True, 0, 0)
        return jnp.sum(o.astype(jnp.float32) * flat(w).astype(jnp.float32)), o

    with jax.default_matmul_precision("highest"):
        (_, ref_out), ref_grads = jax.value_and_grad(
            ref_loss, argnums=(0, 1, 2), has_aux=True)(
                *[flat(a).astype(jnp.float32) for a in (q, k, v)])
    refs = [ref_out] + list(ref_grads)
    fa_err = {n: rel_err(g.reshape(B * H, T, D), r) for n, g, r in
              zip(("out", "dq", "dk", "dv"), got, refs)}
    tol = 3e-2 if dt == "bfloat16" else 2e-4
    assert max(fa_err.values()) < tol, fa_err
    fa_pallas = pallas_in(jitted_op(
        "_contrib_flash_attention", normalize_attrs({"causal": True})
    ).lower(q._data, k._data, v._data))

    # ---- the same kernels inside a recomputed segment: under jax.checkpoint
    # with the lowering's policy the named residuals (out, log-sum-exp) are
    # kept through Mosaic's custom call, the forward kernel runs once, the
    # backward pass is the backward kernel (no scan), and the gradients are
    # those of the plain call
    from mxnet_tpu.ops.registry import KEPT_IN_SEGMENT
    qs, ks, vs, ws = [a._data for a in (
        mx.nd.random_normal(shape=cfg["fa_kept"], dtype=dt) for _ in range(4))]
    seg_loss = lambda *a: jnp.sum(  # noqa: E731
        (pk.flash_attention(*a, causal=True) * ws).astype(jnp.float32))
    t0 = time.perf_counter()
    kept = jax.jit(jax.grad(jax.checkpoint(
        seg_loss, policy=jax.checkpoint_policies.save_only_these_names(
            KEPT_IN_SEGMENT)), argnums=(0, 1, 2))).lower(qs, ks, vs).compile()
    kept_text = kept.as_text()
    kept_calls = {name: len(re.findall(r"%%\w*%s[_.\d]* = " % name,
                                       kept_text))
                  for name in ("flash_attention_fwd", "flash_attention_bwd")}
    assert set(kept_calls.values()) == {1 if on_tpu else 0}, kept_calls
    assert (" while(" in kept_text) != on_tpu, "the backward is a scan"
    plain = jax.jit(jax.grad(seg_loss, argnums=(0, 1, 2)))(qs, ks, vs)
    kept_err = {n: rel_err(g, p) for n, g, p in
                zip(("dq", "dk", "dv"), kept(qs, ks, vs), plain)}
    # the same kernel and the same backward in another program: at most a
    # rounding of the type where XLA fused differently
    assert max(kept_err.values()) < (2 ** -7 if dt == "bfloat16" else 1e-6), \
        kept_err
    kept_s = time.perf_counter() - t0

    # ---- fused softmax cross-entropy through the nd op
    N, C = cfg["ce"]
    logits = mx.nd.random_normal(scale=3.0, shape=(N, C),
                                 dtype=cfg["ce_dtype"])
    labels = mx.nd.array(np.random.RandomState(seed).randint(0, C, (N,))
                         .astype("float32"), ctx=ctx)
    t0 = time.perf_counter()
    got_ce = float(mx.nd.softmax_cross_entropy(logits, labels).asnumpy()[0])
    ce_s = time.perf_counter() - t0
    x32 = logits._data.astype(jnp.float32)
    lab = labels._data.astype(jnp.int32)
    ref_ce = float(jnp.sum(jax.nn.logsumexp(x32, axis=1)
                           - jnp.take_along_axis(x32, lab[:, None],
                                                 axis=1)[:, 0]))
    ce_err = abs(got_ce - ref_ce) / abs(ref_ce)
    assert np.isfinite(got_ce) and ce_err < 1e-3, (got_ce, ref_ce)
    ce_pallas = pallas_in(jitted_op("softmax_cross_entropy", ())
                          .lower(logits._data, labels._data))
    # ---- routed experts: the grouped products (tokens sorted by expert,
    # uneven groups, one of them empty, half the tokens routed to experts
    # held elsewhere) and their gradients against the per-expert loop in
    # float32 at the highest precision
    from mxnet_tpu.ops import nn as ops_nn
    from mxnet_tpu.ops.registry import get_op
    n_tok, W, F, held = cfg["moe"]
    mdt = jnp.dtype(cfg["moe_dtype"])
    rs = np.random.RandomState(seed + 1)
    load = rs.dirichlet(np.full(2 * held, 0.7))
    load[1] = 0.0                                   # expert 1 gets no token
    expert = jnp.asarray(rs.choice(2 * held, n_tok, p=load / load.sum()),
                         jnp.int32)
    xm = jnp.asarray(rs.randn(n_tok, W), mdt)
    gate_m = jnp.asarray(rs.uniform(0.1, 1.0, n_tok), jnp.float32)
    wm = [jnp.asarray(rs.randn(held, *sh) / np.sqrt(sh[1]), mdt)
          for sh in ((F, W), (F, W), (W, F))]
    ym = jnp.asarray(rs.randn(n_tok, W), jnp.float32)
    moe_op = functools.partial(get_op("_contrib_moe_experts").fn,
                               first_expert=0, num_experts=2 * held)
    def moe_loss(fn, x, g, *w):
        out = fn(x, expert, g, *w).astype(jnp.float32)
        return jnp.sum(out * ym), out

    grouped = jax.jit(jax.value_and_grad(
        functools.partial(moe_loss, moe_op), argnums=(0, 1, 2, 3, 4),
        has_aux=True))
    moe_pallas = pallas_in(grouped.lower(xm, gate_m, *wm))
    t0 = time.perf_counter()
    got_m = jax.block_until_ready(grouped(xm, gate_m, *wm))
    moe_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(3):
        got_m = jax.block_until_ready(grouped(xm, gate_m, *wm))
    moe_ms = (time.perf_counter() - t0) / 3 * 1e3
    plain_fn = ops_nn._moe_experts_fn(0, "plain", pk.MOE_TILE_ROWS)
    with jax.default_matmul_precision("highest"):
        ref_m = jax.jit(jax.value_and_grad(
            functools.partial(moe_loss, plain_fn), argnums=(0, 1, 2, 3, 4),
            has_aux=True))(
                xm.astype(jnp.float32), gate_m,
                *[a.astype(jnp.float32) for a in wm])
    moe_err = {n: rel_err(np.asarray(g.astype(jnp.float32)), r) for n, g, r in
               zip(("dx", "dgate", "dw_gate", "dw_up", "dw_down"),
                   got_m[1], ref_m[1])}
    moe_err["out"] = rel_err(np.asarray(got_m[0][1]), ref_m[0][1])
    assert max(moe_err.values()) < (3e-2 if mdt == jnp.bfloat16 else 2e-4), \
        moe_err
    counts = np.bincount(np.asarray(expert), minlength=2 * held)[:held]
    assert counts[1] == 0 and float(jnp.abs(got_m[1][2][1]).max()) == 0.0

    placed = on_device([out._data, q.grad._data, logits._data], [dev])
    emit(phase="kernels", ok=True, routed_experts=dict(
        shape=[n_tok, W, F, held], dtype=str(mdt), pallas=moe_pallas,
        tokens_per_held_expert=counts.tolist(), rel_err=moe_err,
        first_call_s=round(moe_s, 2), forward_and_gradient_ms=round(moe_ms, 3)),
        flash_attention=dict(
        shape=[B * H, T, D], dtype=dt, rel_err=fa_err, pallas=fa_pallas,
        seconds=round(fa_s, 2)), flash_attention_in_segment=dict(
        shape=list(cfg["fa_kept"]), dtype=dt, kernels=kept_calls,
        rel_err_to_plain=kept_err, seconds=round(kept_s, 2)),
        softmax_cross_entropy=dict(
        shape=[N, C], dtype=cfg["ce_dtype"], rel_err=ce_err, value=got_ce,
        pallas=ce_pallas, seconds=round(ce_s, 2)),
        devices=placed, memory=mem_stats(dev))


def phase_imperative(cfg, seed, dev):
    """LSTM language model trained op by op under autograd.record() — the
    word_language_model path (BASELINE config 3)."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn, rnn
    from mxnet_tpu.ops.registry import jitted_op
    lm = cfg["lm"]
    ctx = mx.context.current_context()
    mx.random.seed(seed)

    class RNNModel(gluon.Block):
        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                self.encoder = nn.Embedding(lm["vocab"], lm["embed"])
                self.rnn = rnn.LSTM(lm["hidden"], lm["layers"],
                                    input_size=lm["embed"])
                self.decoder = nn.Dense(lm["vocab"], in_units=lm["hidden"])

        def forward(self, inputs, hidden):
            output, hidden = self.rnn(self.encoder(inputs), hidden)
            return self.decoder(output.reshape((-1, lm["hidden"]))), hidden

    model = RNNModel(prefix="smoke_lm_")
    model.initialize(mx.init.Xavier(), ctx=ctx)
    trainer = gluon.Trainer(model.collect_params(), "sgd",
                            {"learning_rate": 1.0}, kvstore=None)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rs = np.random.RandomState(seed)
    tokens = rs.randint(0, lm["vocab"], (lm["bptt"] + 1, lm["batch"])) \
        .astype("float32")
    data = mx.nd.array(tokens[:-1], ctx=ctx)
    target = mx.nd.array(tokens[1:].reshape(-1), ctx=ctx)
    hidden = model.rnn.begin_state(batch_size=lm["batch"], ctx=ctx)
    info0 = jitted_op.cache_info()
    t0 = time.perf_counter()
    losses = []
    for _ in range(lm["steps"]):
        hidden = [h.detach() for h in hidden]
        with autograd.record():
            output, hidden = model(data, hidden)
            L = loss_fn(output, target)
        L.backward()
        trainer.step(lm["bptt"] * lm["batch"])
        losses.append(float(L.mean().asscalar()))
    # evaluation outside record(): every op goes through _imperative's
    # per-op jit cache, and the second pass must be all hits
    evals = []
    for _ in range(2):
        info_e = jitted_op.cache_info()
        out_e, _ = model(data, [h.detach() for h in hidden])
        evals.append(float(loss_fn(out_e, target).mean().asscalar()))
    seconds = time.perf_counter() - t0
    info1 = jitted_op.cache_info()
    assert all(np.isfinite(losses + evals)), (losses, evals)
    assert losses[-1] < losses[0], "loss did not fall: %r" % (losses,)
    assert evals[0] == evals[1] and evals[0] < losses[0], (evals, losses)
    assert info1.misses == info_e.misses and info1.hits > info_e.hits, \
        "second evaluation pass missed the per-op jit cache"
    placed = on_device([p.data()._data
                        for p in model.collect_params().values()]
                       + [output._data], [dev])
    emit(phase="imperative", ok=True, losses=[round(l, 4) for l in losses],
         eval_loss=round(evals[0], 4), seconds=round(seconds, 2),
         devices=placed,
         op_cache=dict(hits=info1.hits - info0.hits,
                       misses=info1.misses - info0.misses),
         memory=mem_stats(dev))


def run_modes(devices, name, build_net, build_trainer, x, y, steps,
              inspect=None):
    """``steps`` steps from identical weights on one device, on all of
    ``devices`` with the default all-reduce, and with ZeRO-1. Returns
    ``{mode: row}``; each multi-device row carries its worst loss deviation
    from the one-device run and the relative error of its total update."""
    import numpy as np
    from mxnet_tpu.parallel import local_mesh
    rows, weights = {}, {}
    for mode, devs, kw in (
            ("one_device", devices[:1], {}),
            ("all_reduce", devices, {}),
            ("reduce_scatter", devices, {"grad_reduce": "reduce_scatter"})):
        prefix = "%s_%s_" % (name, mode)
        net = build_net(prefix)
        trainer = build_trainer(net, local_mesh("dp", devices=devs), **kw)
        t0 = time.perf_counter()
        losses = [float(trainer.step(x, y))]
        first_s = time.perf_counter() - t0
        # the trainer works on its own copies: after a step the net still
        # holds the initial weights (and its buffers, see phase_train)
        init = net_weights(net, trainer, prefix)
        losses += [float(trainer.step(x, y)) for _ in range(steps - 1)]
        assert all(np.isfinite(losses)), losses
        trainer.sync_to_net()
        weights[mode] = (init, net_weights(net, trainer, prefix))
        rows[mode] = dict(losses=[round(l, 6) for l in losses],
                          first_step_s=round(first_s, 2),
                          param_devices=on_device(
                              (trainer._params, trainer._aux), devs))
        if inspect is not None and len(devs) > 1:
            rows[mode].update(inspect(mode, trainer, devs))
        del trainer, net
    norm = lambda d: float(np.sqrt(sum(  # noqa: E731
        np.sum(np.square(v, dtype=np.float64)) for v in d.values())))
    init1, final1 = weights["one_device"]
    for mode in ("all_reduce", "reduce_scatter"):
        init_n, final_n = weights[mode]
        assert all(np.array_equal(init_n[k], init1[k]) for k in init1), \
            "%s did not start from the one-device weights" % mode
        rows[mode]["worst_loss_rel_err"] = max(
            abs(a - b) / abs(b) for a, b in zip(
                rows[mode]["losses"], rows["one_device"]["losses"]))
        rows[mode]["update_rel_err"] = \
            norm({k: final_n[k] - final1[k] for k in init1}) \
            / norm({k: final1[k] - init1[k] for k in init1})
    return rows


def phase_multichip(cfg, seed, devices, on_tpu):
    """The 4-device dp mesh against the one-device step: ResNet-50 at full
    width, then a well-conditioned probe."""
    import jax
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.observability import jit_hooks
    # what the compiled step must hold. The TPU compiler spells ZeRO-1's
    # gradient reduction as a fused "all-reduce-scatter"; the CPU backend of
    # a rehearsal keeps all-reduce + slice, so only the all-gather shows
    wants = {"all_reduce": ("all-reduce",),
             "reduce_scatter": ("reduce-scatter", "all-gather") if on_tpu
             else ("all-gather",)}
    x, y = resnet_batch(cfg, seed)

    def inspect(mode, trainer, devs):
        hits0 = int(jit_hooks.JIT_CACHE_HITS.value() or 0)
        t0 = time.perf_counter()
        text = trainer.lower(x, y).compile().as_text()
        row = dict(text_compile_s=round(time.perf_counter() - t0, 2),
                   text_compile_cache_hits=int(
                       jit_hooks.JIT_CACHE_HITS.value() or 0) - hits0,
                   collectives=list(wants[mode]),
                   opt_state_bytes=trainer.opt_state_bytes())
        for want in wants[mode]:
            assert want in text, "no %s in the compiled step" % want
        assert mode == "reduce_scatter" or "reduce-scatter" not in text
        sharded = set()
        for leaf in jax.tree_util.tree_leaves(trainer._opt_state):
            assert len(leaf.devices()) == len(devs), leaf.devices()
            if leaf.ndim and leaf.shape[0] % len(devs) == 0:
                sharded.add(leaf.addressable_shards[0].data.shape[0]
                            * len(devs) == leaf.shape[0])
        split = row["opt_state_bytes"]["per_chip_bytes"] \
            < row["opt_state_bytes"]["total_bytes"]
        want_split = mode == "reduce_scatter"
        assert split == want_split and sharded == {want_split}, \
            "optimizer state split=%r, want %r: %r" % (split, want_split, row)
        return row

    # ---- ResNet-50 at full width. Losses are what can be held: from a
    # random init this net is chaotic in bf16 — on ONE chip the first update
    # of the same batch in another order differs by 40% (cosine 0.92), and
    # bf16 against f32 compute is near orthogonal (my chip run, PR 21) — so
    # the update error is printed, not asserted. Measured loss deviation,
    # four chips against one: 0.5% (a quarter-size update would give 3%);
    # the tiny rehearsal net is wilder still (12% at step two).
    rows = run_modes(devices, "smoke",
                     lambda prefix: build_resnet(cfg, seed, prefix),
                     functools.partial(make_trainer, lr=cfg["lr"]), x, y,
                     cfg["mc_steps"], inspect)
    for mode in ("all_reduce", "reduce_scatter"):
        assert rows[mode]["worst_loss_rel_err"] < cfg["mc_loss_tol"], \
            (mode, rows)
    emit(phase="multichip_trainer", ok=True, **rows)

    # ---- the probe: a small MLP in true f32, where the three reductions
    # must give the same update to rounding
    pr = cfg["probe"]
    rs = np.random.RandomState(seed)
    px = rs.uniform(-1, 1, (pr["batch"], pr["features"])).astype("float32")
    py = rs.randint(0, pr["classes"], (pr["batch"],)).astype("float32")

    def build_mlp(prefix):
        mx.random.seed(seed)
        net = nn.HybridSequential(prefix=prefix)
        with net.name_scope():
            net.add(nn.Dense(pr["hidden"], activation="relu",
                             in_units=pr["features"]),
                    nn.Dense(pr["hidden"], activation="relu",
                             in_units=pr["hidden"]),
                    nn.Dense(pr["classes"], in_units=pr["hidden"]))
        net.initialize(mx.init.Xavier())
        return net

    def mlp_trainer(net, mesh, **kw):
        return parallel.DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1, "momentum": 0.9}, mesh=mesh, **kw)

    with jax.default_matmul_precision("highest"):
        probe = run_modes(devices, "probe", build_mlp, mlp_trainer, px, py,
                          pr["steps"])
    for mode in ("all_reduce", "reduce_scatter"):
        assert probe[mode]["worst_loss_rel_err"] < 1e-5, (mode, probe)
        assert probe[mode]["update_rel_err"] < 1e-4, (mode, probe)
    emit(phase="multichip_probe", ok=True, **probe)


def phase_module_fit(cfg, seed, devices, on_tpu):
    """``Module`` over four contexts — what
    example/image-classification/common/fit.py builds for ``--gpus 0,1,2,3``
    — on the example's own ResNet-50 symbol."""
    import numpy as np
    import mxnet_tpu as mx
    sys.path.insert(0, os.path.join(HERE, "example", "image-classification"))
    from symbols import resnet as resnet_sym
    ctxs = [mx.tpu(i) for i in range(len(devices))] if on_tpu \
        else [mx.cpu(i) for i in range(len(devices))]
    image = cfg["fit_image"]
    sym = resnet_sym.get_symbol(num_classes=cfg["classes"], num_layers=50,
                                image_shape="3,%d,%d" % (image, image))
    rs = np.random.RandomState(seed)
    n = cfg["fit_batch"] * cfg["fit_batches"]
    it = mx.io.NDArrayIter(
        rs.uniform(-1, 1, (n, 3, image, image)).astype("float32"),
        rs.randint(0, cfg["classes"], (n,)).astype("float32"),
        batch_size=cfg["fit_batch"], label_name="softmax_label")
    mx.random.seed(seed)
    mod = mx.mod.Module(sym, context=ctxs)
    metric = mx.metric.CrossEntropy()
    t0 = time.perf_counter()
    mod.fit(it, num_epoch=2, kvstore="device", optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            initializer=mx.init.Xavier(), eval_metric=metric)
    seconds = time.perf_counter() - t0
    ce = float(metric.get()[1])
    assert np.isfinite(ce), ce
    args, _ = mod.get_params()
    for a in args.values():
        assert np.all(np.isfinite(a.asnumpy()))
    ex = mod._exec_group.execs[0]
    used = sorted({str(d) for a in ex.arg_dict.values()
                   for d in a._data.devices()})
    if on_tpu:
        assert all(d.startswith("TPU") for d in used), used
    emit(phase="multichip_module_fit", ok=True, contexts=[str(c) for c in ctxs],
         executor_devices=used, train_cross_entropy=ce,
         seconds=round(seconds, 2),
         memory=[mem_stats(d) for d in devices])


# -------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the multi-chip phase and its one-device "
                         "comparison (default 1: the four one-chip phases)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever backend is there, Pallas "
                         "interpreted; the verdict is always ok=false")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["MXTPU_PALLAS_INTERPRET"] = "1"

    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import native
    from mxnet_tpu.base import enable_compile_cache
    from mxnet_tpu.observability import jit_hooks

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_tpu = device["platform"] == "tpu"

    def verdict(ok, **extra):
        print(json.dumps({"ok": bool(ok), "device": device, **extra}),
              flush=True)
        return 0 if ok else 1

    if not on_tpu and not args.rehearse:
        return verdict(False, error="no TPU: jax.devices() is %d x %s"
                       % (len(devices), device["kind"]))
    if len(devices) < args.chips:
        return verdict(False, error="--chips %d needs %d devices, found %d"
                       % (args.chips, args.chips, len(devices)))

    cache_dir = enable_compile_cache()
    jit_hooks.install()
    emit(phase="setup", device=device, seed=args.seed, chips=args.chips,
         rehearse=args.rehearse, compile_cache_dir=cache_dir,
         native_lib_loaded=native.get_lib() is not None,
         jax=jax.__version__, x64=bool(jax.config.jax_enable_x64),
         default_context=str(mx.current_context()))
    cfg = TINY if args.rehearse else FULL
    t_all = time.perf_counter()
    error = None
    try:
        if args.chips == 4:
            phase_multichip(cfg, args.seed, devices[:4], on_tpu)
            phase_module_fit(cfg, args.seed, devices[:4], on_tpu)
        else:
            dev = devices[0]
            net = phase_train(cfg, args.seed, dev)
            phase_serve(cfg, args.seed, dev, net)
            phase_kernels(cfg, args.seed, dev, on_tpu)
            phase_imperative(cfg, args.seed, dev)
    except BaseException:   # any failure ends the run; the verdict says so
        traceback.print_exc()
        error = traceback.format_exc().strip().splitlines()[-1]
    emit(phase="summary", seconds=round(time.perf_counter() - t_all, 1),
         backend_compiles=compiles(),
         compile_cache_hits=int(jit_hooks.JIT_CACHE_HITS.value() or 0),
         compile_cache_dir=cache_dir)
    if error is not None:
        return verdict(False, error=error)
    if not on_tpu:
        return verdict(False, error="rehearsal on %s: every phase passed, "
                       "which proves nothing about the chip"
                       % device["platform"])
    return verdict(True)


if __name__ == "__main__":
    sys.exit(main())
