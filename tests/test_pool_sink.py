"""A max pool sinks in front of the per-channel monotone maps that feed it
(PR 30): the ``fusion`` pass turns ``BatchNorm -> relu -> Pooling(max)`` into
``_MaxPoolBatchNorm -> relu`` (``ops/nn.py``), so BatchNorm's apply and the
ReLU run on the pooled map. It must compute EXACTLY what the graph declares:
the same values to the last bit of the data's type, the same statistics and
running statistics, and the same gradients up to which of several taps that
the maps send to ONE value wins (there the first, here the one whose input is
largest). Everything the rule does not take keeps its graph, node for node.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

import mxnet_tpu.symbol as sym_mod
from mxnet_tpu.executor import _GraphLowering
from mxnet_tpu.observability import catalog
from mxnet_tpu.ops import get_op
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.passes import PassManager

pytestmark = pytest.mark.passes

_WINDOWS = {
    # name: (kernel, stride, pad)
    "3x3s2p1": ((3, 3), (2, 2), (1, 1)),
    "2x2s2": ((2, 2), (2, 2), (0, 0)),
    "3x3s2p0": ((3, 3), (2, 2), (0, 0)),
}


def _op(opname, *ins, **kw):
    return sym_mod._invoke_sym(opname, list(ins), kw)


def _stem(layout, window="3x3s2p1", use_global_stats=False, act="relu",
          pool_type="max", global_pool=False, second_reader=None, bn=True,
          channels=8):
    """data -> Convolution -> BatchNorm -> relu -> max pool: a ResNet stem."""
    kernel, stride, pad = _WINDOWS[window]
    x = _op("Convolution", sym_mod.Variable("data"), kernel=(3, 3),
            num_filter=channels, no_bias=True, layout=layout, pad=(1, 1),
            name="conv")
    taps = {"conv": x}
    if bn:
        x = taps["bn"] = _op(
            "BatchNorm", x, axis=-1 if layout == "NHWC" else 1, eps=1e-5,
            momentum=0.9, fix_gamma=False,
            use_global_stats=use_global_stats, name="bn")
    if act == "gelu":
        x = taps["act"] = _op("LeakyReLU", x, act_type="gelu", name="act")
    elif act == "relu_op":
        x = taps["act"] = _op("relu", x, name="act")
    elif act:
        x = taps["act"] = _op("Activation", x, act_type=act, name="act")
    out = _op("Pooling", x, kernel=kernel, stride=stride, pad=pad,
              pool_type=pool_type, global_pool=global_pool, layout=layout,
              name="pool")
    if second_reader:
        out = sym_mod.Group([out, _op("sum", taps[second_reader],
                                      name="reader")])
    return out


def _ops(sym):
    return [n.op for n in sym.topo_nodes() if not n.is_var]


def _run_passes(sym, shape, names=None):
    # parameters stay where the graph declares them, so one set of values
    # feeds the graph and its rewrite
    return PassManager(names, rehome_params=False).run(
        sym, shapes={"data": shape})


_GAMMA = {
    "positive": lambda rng, c: rng.uniform(0.5, 1.5, c),
    "mixed": lambda rng, c: rng.uniform(0.5, 1.5, c)
    * np.where(np.arange(c) % 2, -1.0, 1.0),
    "zero": lambda rng, c: np.where(
        np.arange(c) == 2, 0.0,
        rng.uniform(0.5, 1.5, c) * np.where(np.arange(c) % 3, 1.0, -1.0)),
}


def _values(rng, layout, gamma, n=4, hw=(12, 12), c=8, cin=3):
    data = rng.randn(*((n, *hw, cin) if layout == "NHWC"
                       else (n, cin, *hw))).astype("float32")
    weight = rng.uniform(-1, 1, (c, 3, 3, cin) if layout == "NHWC"
                         else (c, cin, 3, 3)).astype("float32")
    return {"data": data, "conv_weight": weight,
            "bn_gamma": _GAMMA[gamma](rng, c).astype("float32"),
            "bn_beta": rng.uniform(-0.5, 0.5, c).astype("float32"),
            "bn_moving_mean": rng.uniform(-0.2, 0.2, c).astype("float32"),
            "bn_moving_var": rng.uniform(0.5, 1.5, c).astype("float32")}


_TRAINED = ("data", "conv_weight", "bn_gamma", "bn_beta")


def _out_shape(sym, values):
    return sym.infer_shape(data=values["data"].shape)[1][0]


def _value_and_grads(sym, values, cot, dtype=jnp.float32, is_train=True):
    """The graph's output, BatchNorm's aux updates, and the gradients of
    sum(output * cot) with respect to the stem's four trained inputs."""
    fn = _GraphLowering(sym).lower(is_train=is_train)
    consts = {k: jnp.asarray(v) for k, v in values.items()
              if k not in _TRAINED}

    def run(trained):
        feed = {k: v.astype(dtype) if k in ("data", "conv_weight") else v
                for k, v in trained.items()}
        outs, aux = fn({**feed, **consts}, jax.random.PRNGKey(0))
        return outs[0], aux

    trained = {k: jnp.asarray(values[k]) for k in _TRAINED}
    with jax.default_matmul_precision("highest"):
        out, vjp, aux = jax.vjp(run, trained, has_aux=True)
        (grads,) = vjp(jnp.asarray(cot, out.dtype))
    return out, aux, grads


@pytest.mark.parametrize("stats", ["batch", "global"])
@pytest.mark.parametrize("window", sorted(_WINDOWS))
@pytest.mark.parametrize("layout", ["NHWC", "NCHW"])
@pytest.mark.parametrize("gamma", sorted(_GAMMA))
def test_rewritten_stem_is_the_declared_one_in_float32(rng, gamma, layout,
                                                       window, stats):
    """Against the same graph with the passes off: the output, the running
    statistics and every gradient. NCHW goes through the layout pass first,
    as ``resnet34_v1.train`` does."""
    sym = _stem(layout, window, use_global_stats=stats == "global")
    values = _values(rng, layout, gamma)
    res = _run_passes(sym, values["data"].shape)
    assert "_MaxPoolBatchNorm" in _ops(res.symbol)
    assert "BatchNorm" not in _ops(res.symbol)
    assert res.counts["fusion"] >= 2        # through the ReLU, then BatchNorm
    assert res.symbol.list_arguments() == sym.list_arguments()
    assert res.symbol.list_auxiliary_states() == sym.list_auxiliary_states()
    cot = rng.randn(*_out_shape(sym, values)).astype("float32")
    want, aux_want, g_want = _value_and_grads(sym, values, cot)
    before = catalog.POOL_SUNK.value()
    got, aux_got, g_got = _value_and_grads(res.symbol, values, cot)
    assert catalog.POOL_SUNK.value() == before + 1
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert sorted(aux_got) == sorted(aux_want) == \
        ["bn_moving_mean", "bn_moving_var"]
    for k in aux_want:
        np.testing.assert_allclose(aux_got[k], aux_want[k], rtol=1e-6)
    for k in _TRAINED:
        a, e = np.asarray(g_got[k]), np.asarray(g_want[k])
        if k == "bn_gamma" and gamma == "zero":
            # at a scale of exactly 0 every tap of a window ties: the
            # declared graph gives the window's gradient to its first tap,
            # the sunk one to its largest, and d/dgamma reads that tap's
            # input. Both are one-sided derivatives of the same function
            a, e = np.delete(a, 2), np.delete(e, 2)
        scale = np.abs(e).max()
        np.testing.assert_allclose(a, e, rtol=1e-5, atol=1e-5 * scale,
                                   err_msg=k)


@pytest.mark.parametrize("window", sorted(_WINDOWS))
@pytest.mark.parametrize("gamma", sorted(_GAMMA))
def test_rewritten_stem_in_bfloat16_rounds_where_the_declared_one_does(
        rng, gamma, window):
    """The cells' compute type. Values: equal to the last bit. Gradients: the
    two forms differ only in which of the taps that ROUND to one value wins,
    so each is held against float32 on the same inputs, and the sunk form's
    gap is no larger than the declared one's."""
    sym = _stem("NHWC", window)
    values = _values(rng, "NHWC", gamma, n=8, hw=(16, 16))
    # what both types can hold exactly, so that float32 sees the same inputs
    for k in ("data", "conv_weight"):
        values[k] = np.asarray(jnp.asarray(values[k], jnp.bfloat16)
                               .astype(jnp.float32))
    res = _run_passes(sym, values["data"].shape)
    cot = np.asarray(jnp.asarray(rng.randn(*_out_shape(sym, values)),
                                 jnp.bfloat16).astype(jnp.float32))
    _, _, g32 = _value_and_grads(sym, values, cot)
    want, aux_want, g_want = _value_and_grads(sym, values, cot, jnp.bfloat16)
    got, aux_got, g_got = _value_and_grads(res.symbol, values, cot,
                                           jnp.bfloat16)
    assert got.dtype == want.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))
    for k in aux_want:
        np.testing.assert_array_equal(np.asarray(aux_got[k]),
                                      np.asarray(aux_want[k]))

    def gap(g, k):
        a = np.asarray(g[k].astype(jnp.float32))
        e = np.asarray(g32[k])
        if k == "bn_gamma" and gamma == "zero":
            a, e = np.delete(a, 2), np.delete(e, 2)
        return np.linalg.norm(a - e) / np.linalg.norm(e)

    # where the gradient lands: no further from float32 than the declared
    # form's (2-10% here; the sunk form picks the tap float32 picks more
    # often). The two vectors of 8 are sums of those, a few percent either way
    for k in ("data", "conv_weight"):
        assert gap(g_got, k) <= 1.02 * gap(g_want, k), k
    for k in ("bn_gamma", "bn_beta"):
        assert gap(g_got, k) <= max(2.5 * gap(g_want, k), 0.05), k


def test_inference_graph_takes_the_same_rule(rng):
    """A ModelServer graph: is_train false, the running statistics are the
    map, the forward is the declared one to the bit and nothing is updated."""
    sym = _stem("NHWC")
    values = _values(rng, "NHWC", "mixed")
    res = _run_passes(sym, values["data"].shape)
    outs = []
    for s in (sym, res.symbol):
        fn = _GraphLowering(s).lower(is_train=False)
        out, aux = fn({k: jnp.asarray(v) for k, v in values.items()},
                      jax.random.PRNGKey(0))
        assert aux == {}
        outs.append(np.asarray(out[0]))
    np.testing.assert_array_equal(outs[0], outs[1])


# --------------------------------------------------------------------------
# the sign inside the kernels (Pallas interpreter)
# --------------------------------------------------------------------------
@pytest.fixture
def one_device(monkeypatch):
    """The kernels under their interpreter, in a program that names its mesh
    of one device, as the trainer does."""
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    with jax.sharding.use_abstract_mesh(AbstractMesh((1,), ("dp",))):
        yield


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("window", sorted(_WINDOWS))
def test_kernels_pool_the_signed_map_without_making_it(rng, monkeypatch,
                                                       one_device, window,
                                                       dtype):
    """At a shape the kernels take, the op hands them the sign: the forward
    multiplies in VMEM, the backward folds it into dy at the pooled size.
    Against the op on the reduce_window route, value and gradients."""
    kernel, stride, pad = _WINDOWS[window]
    c = 32
    attrs = dict(eps=1e-5, fix_gamma=False, axis=-1, pool_kernel=kernel,
                 pool_stride=stride, pool_pad=pad, pool_layout="NHWC")
    op = get_op("_MaxPoolBatchNorm").fn
    x = jnp.asarray(rng.randn(128, 8, 8, c), dtype)
    gamma = jnp.asarray(_GAMMA["zero"](rng, c), jnp.float32)
    beta = jnp.asarray(rng.uniform(-0.5, 0.5, c), jnp.float32)
    aux = (jnp.zeros(c, jnp.float32), jnp.ones(c, jnp.float32))
    seen = []
    monkeypatch.setattr(pk, "max_pool_fwd", lambda *a, _f=pk.max_pool_fwd: (
        seen.append(len(a)), _f(*a))[1])

    def run(x, gamma, beta):
        return op(x, gamma, beta, *aux, **attrs)

    before = (catalog.POOL_SUNK.value(), catalog.POOL_BWD_LOWERED.value())
    (got, mean, var), vjp = jax.vjp(run, x, gamma, beta)
    assert seen == [6], "the kernel took the sign as its own operand"
    assert (catalog.POOL_SUNK.value(), catalog.POOL_BWD_LOWERED.value()) == \
        (before[0] + 1, before[1] + 1)
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "0")
    (want, mean_w, var_w), vjp_w = jax.vjp(run, x, gamma, beta)
    assert seen == [6]
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))
    np.testing.assert_array_equal(np.asarray(mean), np.asarray(mean_w))
    cot = (jnp.asarray(rng.randn(*want.shape), dtype), jnp.zeros_like(mean),
           jnp.zeros_like(var))
    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -7
    for a, e in zip(vjp(cot), vjp_w(cot)):
        a, e = (np.asarray(v.astype(jnp.float32)) for v in (a, e))
        np.testing.assert_allclose(a, e, rtol=tol, atol=tol * np.abs(e).max())


# --------------------------------------------------------------------------
# what the rule leaves alone
# --------------------------------------------------------------------------
_BYPASS = {
    "avg_pool": dict(pool_type="avg"),
    "global_pool": dict(global_pool=True),
    "tanh": dict(act="tanh"),
    "gelu": dict(act="gelu"),
    "relu_read_twice": dict(second_reader="act"),
    "batchnorm_read_twice": dict(second_reader="bn"),
}


@pytest.mark.parametrize("case", sorted(_BYPASS))
def test_bypassed_graph_is_the_declared_one(case):
    """The symbol object itself comes back: nothing was rebuilt."""
    sym = _stem("NHWC", **_BYPASS[case])
    res = _run_passes(sym, (4, 12, 12, 3), names=["fusion"])
    if case == "batchnorm_read_twice":
        # the ReLU has one reader: the pool passes it, and stops at BatchNorm
        assert res.counts["fusion"] == 1
        assert _ops(res.symbol) == ["Convolution", "BatchNorm", "Pooling",
                                    "Activation", "sum"]
    else:
        assert res.counts["fusion"] == 0
        assert res.symbol is sym


def test_batchnorm_over_another_axis_than_the_pools_channels_stays():
    data = sym_mod.Variable("data")
    x = _op("BatchNorm", data, axis=1, fix_gamma=False, name="bn")
    out = _op("Pooling", x, kernel=(2, 2), stride=(2, 2), pool_type="max",
              layout="NHWC", name="pool")
    res = _run_passes(out, (4, 8, 8, 6), names=["fusion"])
    assert res.symbol is out


@pytest.mark.parametrize("act", ["relu", "relu_op"])
def test_chain_without_a_batchnorm_is_a_reorder_of_two_nodes(rng, act):
    """VGG, AlexNet, SqueezeNet: conv -> relu -> max pool. The pool passes
    the ReLU (the same rule's first half) and no new op is made: values and
    gradients to the bit, since a ReLU rounds nothing."""
    sym = _stem("NHWC", bn=False, act=act)
    values = {k: v for k, v in _values(rng, "NHWC", "mixed").items()
              if not k.startswith("bn_")}
    res = _run_passes(sym, values["data"].shape, names=["fusion"])
    assert res.counts["fusion"] == 1
    assert _ops(res.symbol) == ["Convolution", "Pooling",
                                "relu" if act == "relu_op" else "Activation"]
    before = catalog.POOL_SUNK.value()
    outs = []
    for s in (sym, res.symbol):
        fn = _GraphLowering(s).lower(is_train=True)

        def run(v, fn=fn):
            return fn(v, jax.random.PRNGKey(0))[0][0]
        out, vjp = jax.vjp(run, {k: jnp.asarray(v)
                                 for k, v in values.items()})
        outs.append((out, vjp(jnp.ones_like(out))[0]))
    assert catalog.POOL_SUNK.value() == before
    np.testing.assert_array_equal(np.asarray(outs[0][0]),
                                  np.asarray(outs[1][0]))
    for k in values:
        np.testing.assert_array_equal(np.asarray(outs[0][1][k]),
                                      np.asarray(outs[1][1][k]))


def test_passes_off_keeps_the_declared_stem(rng):
    """``passes=False`` on the trainer: BatchNorm, ReLU and the pool as the
    block declares them, and the counter stays."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo.vision.resnet import BasicBlockV1, ResNetV1
    x = rng.uniform(-1, 1, (8, 32, 32, 3)).astype("float32")
    y = rng.randint(0, 4, (8,)).astype("float32")
    counts = {}
    for passes in (None, False):
        mx.random.seed(11)
        net = ResNetV1(BasicBlockV1, [1, 1], [8, 8, 16], classes=4,
                       layout="NHWC", prefix="sink%s_" % (passes is None))
        net.initialize(mx.init.Xavier())
        tr = parallel.DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.05}, passes=passes)
        before = catalog.POOL_SUNK.value()
        tr.lower(x, y)
        counts[passes] = catalog.POOL_SUNK.value() - before
        prov = tr.passes_provenance()
        if passes is None:
            assert prov["rewrites"]["fusion"] == 2
    assert counts == {None: 1, False: 0}


# --------------------------------------------------------------------------
# the trainer's dp mesh: the sign column is whole on every device
# --------------------------------------------------------------------------
@pytest.mark.parametrize("devices", [8, 2])
def test_sunk_stem_over_the_trainers_dp_mesh(rng, monkeypatch, devices):
    """conv -> BatchNorm (scales of both signs) -> relu -> max pool through
    ``DataParallelTrainer`` on a ``dp`` mesh, 128 rows a device, kernels
    interpreted: the pool inside ``_MaxPoolBatchNorm`` runs per shard of the
    batch (``_per_shard``), each device with the whole column of signs. The
    same losses, trained weights and running statistics as on one device."""
    import mxnet_tpu as mx
    from jax.sharding import Mesh
    from mxnet_tpu import gluon, nd, parallel
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    batch, c = 128 * devices, 32
    calls = []
    monkeypatch.setattr(pk, "max_pool_fwd", lambda *a, _f=pk.max_pool_fwd: (
        calls.append((a[0].shape[-1], len(a))), _f(*a))[1])
    x = rng.uniform(-1, 1, (batch, 8, 8, 3)).astype("float32")
    y = rng.randint(0, 4, (batch,)).astype("float32")
    gamma = _GAMMA["mixed"](rng, c).astype("float32")
    ends = {}
    for n in (1, devices):
        mx.random.seed(5)
        net = gluon.nn.HybridSequential(prefix="sinkdp_")
        net.add(gluon.nn.Conv2D(c, 3, padding=1, use_bias=False,
                                layout="NHWC", in_channels=3,
                                prefix="sinkdp_c_"),
                gluon.nn.BatchNorm(axis=-1, in_channels=c,
                                   prefix="sinkdp_bn_"),
                gluon.nn.Activation("relu"),
                gluon.nn.MaxPool2D(3, 2, 1, layout="NHWC"),
                gluon.nn.Dense(4, prefix="sinkdp_fc_"))
        net.initialize(mx.init.Xavier())
        net[1].gamma.set_data(nd.array(gamma))
        tr = parallel.DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.05, "momentum": 0.9},
            mesh=Mesh(np.array(jax.devices()[:n]), ("dp",)))
        del calls[:]
        before = (catalog.POOL_SUNK.value(), catalog.POOL_BWD_LOWERED.value())
        losses = [float(tr.step(x, y)) for _ in range(2)]
        assert (catalog.POOL_SUNK.value() - before[0],
                catalog.POOL_BWD_LOWERED.value() - before[1]) == (1, 1)
        assert tr.passes_provenance()["rewrites"]["fusion"] == 2
        # each device's kernel saw its own 128 rows and the sign operand
        assert set(calls) == {(batch // n, 6)}, calls
        tr.sync_to_net()
        ends[n] = (losses, {k: p.data().asnumpy()
                            for k, p in net.collect_params().items()})
    np.testing.assert_allclose(ends[devices][0], ends[1][0], rtol=1e-5)
    assert (ends[1][1]["sinkdp_bn_gamma"] < 0).any()
    for k, want in ends[1][1].items():
        np.testing.assert_allclose(ends[devices][1][k], want, rtol=1e-4,
                                   atol=1e-6, err_msg=k)


@pytest.mark.parametrize("lr", [0.02, 0.002])
def test_rehearsal_net_steps_alike_sunk_and_declared(lr):
    """``chip_smoke.py``'s rehearsal net (ResNet-50 on 8 images of 32 px) in
    float32, where taps do not tie: the default trainer, stem's pool sunk,
    and the declared graph (``passes=False``) read the same first loss and
    agree on the second, so on one gradient, at the cells' learning rate
    and at the rehearsal's. From the third loss on this net is chaos at
    either rate (a 5e-5 gap at step two is 15% at step three), which is why
    the rehearsal's "the loss falls" is checked at 0.002 and why its
    bfloat16 losses, where the two forms break ties differently, are not
    compared."""
    import chip_smoke
    from mxnet_tpu import gluon, parallel
    cfg, seed = chip_smoke.TINY, 0
    x, y = chip_smoke.resnet_batch(cfg, seed)
    losses = {}
    for passes in (None, False):
        net = chip_smoke.build_resnet(cfg, seed, "agree%d_" % (passes is None))
        tr = parallel.DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": lr, "momentum": 0.9, "wd": 1e-4},
            mesh=parallel.local_mesh("dp", devices=jax.devices()[:1]),
            passes=passes)
        before = catalog.POOL_SUNK.value()
        losses[passes] = [float(tr.step(x, y)) for _ in range(2)]
        assert catalog.POOL_SUNK.value() - before == (passes is None)
    np.testing.assert_allclose(losses[None][0], losses[False][0], rtol=1e-6)
    np.testing.assert_allclose(losses[None][1], losses[False][1], rtol=1e-3)
