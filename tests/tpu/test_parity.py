"""CPU ↔ TPU operator parity (reference
``tests/python/gpu/test_operator_gpu.py``: rerun the CPU op suite on the
accelerator and ``check_consistency`` the results).

On a machine WITHOUT a TPU (the CI mesh forces the CPU platform) every test
skips cleanly. On the chip, through the builder's tool:

    chiprun -- env MXTPU_REAL_TPU=1 python -m pytest tests/tpu/ -q

``MXTPU_REAL_TPU=1`` makes tests/conftest.py leave the platform alone, so
JAX takes the TPU and every symbol below is compared on cpu vs tpu, fp32
and bf16, in that one process.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import check_consistency

sym = mx.sym


def _has_tpu():
    try:
        return mx.num_tpus() > 0
    except Exception:
        return False


pytestmark = pytest.mark.skipif(not _has_tpu(),
                                reason="no TPU present; parity runs on the "
                                       "chip via MXTPU_REAL_TPU=1")


def _ctx_list(**shapes):
    return [dict(ctx=mx.cpu(), **shapes),
            dict(ctx=mx.tpu(), **shapes)]


def _ctx_list_bf16(**shapes):
    cl = _ctx_list(**shapes)
    cl.append(dict(ctx=mx.tpu(),
                   type_dict={"__default__": "bfloat16"}, **shapes))
    return cl


def test_fully_connected_parity():
    net = sym.FullyConnected(sym.Variable("data"), num_hidden=16, name="fc")
    check_consistency(net, _ctx_list_bf16(data=(8, 32)))


def test_convolution_parity():
    net = sym.Convolution(sym.Variable("data"), kernel=(3, 3), num_filter=8,
                          pad=(1, 1), name="conv")
    check_consistency(net, _ctx_list_bf16(data=(2, 4, 16, 16)))


def test_batchnorm_relu_pool_parity():
    d = sym.Variable("data")
    net = sym.Convolution(d, kernel=(3, 3), num_filter=4, name="c")
    net = sym.BatchNorm(net, fix_gamma=False, name="bn")
    net = sym.Activation(net, act_type="relu")
    net = sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="max")
    check_consistency(net, _ctx_list(data=(2, 3, 8, 8)))


_POOL_WINDOWS = {
    "3x3s2p1": dict(kernel=(3, 3), stride=(2, 2), pad=(1, 1)),   # the ResNets'
    "3x3s2p0": dict(kernel=(3, 3), stride=(2, 2)),       # AlexNet, Inception
    "2x2s2": dict(kernel=(2, 2), stride=(2, 2)),                        # VGG
    "3x3s1p1": dict(kernel=(3, 3), stride=(1, 1), pad=(1, 1)),
    "3x3s2full": dict(kernel=(3, 3), stride=(2, 2), pooling_convention="full"),
}


@pytest.mark.parametrize("name", sorted(_POOL_WINDOWS))
def test_max_pool_kernels_parity(name):
    """A max pool in the shape that keeps its winning taps (batch 128,
    channels 32; PR 28): on the TPU both Pallas kernels, in the CPU context
    of the same process ``reduce_window`` and its own gradient; value and
    gradient must agree, windows of zeros and all. float32 on both sides:
    rounding the data to bfloat16 makes new ties and moves the gradient to
    another element of the window. (With several chips and no mesh named the
    op keeps ``reduce_window`` on both sides.)"""
    net = sym.Pooling(sym.Activation(sym.Variable("data"), act_type="relu"),
                      pool_type="max", name="pool", **_POOL_WINDOWS[name])
    check_consistency(net, _ctx_list(data=(128, 32, 12, 12)))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(_POOL_WINDOWS))
def test_max_pool_kernels_equal_reduce_windows_gradient_on_the_chip(
        monkeypatch, name, dtype):
    """The same op twice on the TPU, from the same data in the same type:
    through the two kernels, and with Pallas switched off as
    ``reduce_window`` and ``select-and-scatter``. The same output to the bit,
    the same element of every window, and gradients equal up to the order of
    at most nine float32 additions."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.observability import catalog
    from mxnet_tpu.ops import get_op, pallas_kernels as pk
    pool = get_op("Pooling").fn
    rs = np.random.RandomState(3)
    x = jnp.asarray(np.maximum(rs.randn(128, 12, 12, 32), 0), dtype)
    dy = jnp.asarray(rs.randn(128, 12, 12, 32), dtype)

    def run():
        before = catalog.POOL_BWD_LOWERED.value()
        # a mesh of one device, named: on a host with several chips the op
        # could not otherwise see that its batch stays whole
        with jax.sharding.use_abstract_mesh(
                jax.sharding.AbstractMesh((1,), ("dp",))):
            out, vjp = jax.vjp(lambda x: pool(
                x, pool_type="max", layout="NHWC", **_POOL_WINDOWS[name]), x)
            g = vjp(dy[:, :out.shape[1], :out.shape[2]])[0]
        return (np.asarray(out.astype(jnp.float32)),
                np.asarray(g.astype(jnp.float32)),
                catalog.POOL_BWD_LOWERED.value() - before)

    out_k, g_k, lowered = run()
    assert lowered == 1
    monkeypatch.setattr(pk, "pallas_off", lambda: True)
    out_x, g_x, lowered = run()
    assert lowered == 0
    np.testing.assert_array_equal(out_k, out_x)
    np.testing.assert_array_equal(g_k != 0, g_x != 0)
    tol = 1e-6 if dtype == "float32" else 2.0 ** -8
    np.testing.assert_allclose(g_k, g_x, rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", ["3x3s2p1", "3x3s2p0", "2x2s2"])
def test_sunk_pool_equals_the_declared_stem_on_the_chip(name, dtype):
    """``BatchNorm -> relu -> max pool`` as declared and as the fusion pass
    rewrites it (``_MaxPoolBatchNorm -> relu``, PR 30), both on the TPU from
    the same data, scales of both signs and one at exactly 0, at a shape the
    kernels take (so the sign is multiplied in VMEM): the same values to the
    bit, the same statistics, and in float32 the same gradients."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.observability import catalog
    from mxnet_tpu.ops import get_op
    bn, pool, sunk = (get_op(n).fn for n in ("BatchNorm", "Pooling",
                                             "_MaxPoolBatchNorm"))
    window = _POOL_WINDOWS[name]
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(128, 12, 12, 32), dtype)
    gamma = jnp.asarray(rs.uniform(0.5, 1.5, 32)
                        * np.where(np.arange(32) % 2, -1.0, 1.0)
                        * (np.arange(32) != 6), jnp.float32)
    beta = jnp.asarray(rs.uniform(-0.5, 0.5, 32), jnp.float32)
    aux = (jnp.zeros(32, jnp.float32), jnp.ones(32, jnp.float32))
    attrs = dict(eps=1e-5, fix_gamma=False, axis=-1)

    def declared(x, gamma, beta):
        a, mean, var = bn(x, gamma, beta, *aux, **attrs)
        return pool(jnp.maximum(a, 0), pool_type="max", layout="NHWC",
                    **window), mean, var

    def rewritten(x, gamma, beta):
        a, mean, var = sunk(x, gamma, beta, *aux, pool_layout="NHWC", **attrs,
                            **{"pool_" + k: v for k, v in window.items()})
        return jnp.maximum(a, 0), mean, var

    before = (catalog.POOL_SUNK.value(), catalog.POOL_BWD_LOWERED.value())
    with jax.sharding.use_abstract_mesh(
            jax.sharding.AbstractMesh((1,), ("dp",))):
        (want, mean_w, var_w), vjp_w = jax.vjp(declared, x, gamma, beta)
        (got, mean, var), vjp = jax.vjp(rewritten, x, gamma, beta)
        cot = (jnp.asarray(rs.randn(*want.shape), dtype),
               jnp.zeros_like(mean), jnp.zeros_like(var))
        g_want, g_got = vjp_w(cot), vjp(cot)
    assert (catalog.POOL_SUNK.value() - before[0],
            catalog.POOL_BWD_LOWERED.value() - before[1]) == (1, 2)
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))
    np.testing.assert_array_equal(np.asarray(mean), np.asarray(mean_w))
    np.testing.assert_array_equal(np.asarray(var), np.asarray(var_w))
    if dtype == "float32":
        for a, e, what in zip(g_got, g_want, ("data", "gamma", "beta")):
            a, e = np.asarray(a), np.asarray(e)
            if what == "gamma":     # at a scale of 0 every tap ties
                a, e = np.delete(a, 6), np.delete(e, 6)
            np.testing.assert_allclose(a, e, rtol=1e-5,
                                       atol=1e-5 * np.abs(e).max(),
                                       err_msg=what)


def test_sunk_stem_over_a_dp_mesh_of_the_chips():
    """conv -> BatchNorm (scales of both signs) -> relu -> max pool through
    ``DataParallelTrainer`` on the host's four chips, 128 rows a chip: the
    pool inside ``_MaxPoolBatchNorm`` runs its kernels per shard of the
    batch, each chip with the whole column of signs. The same losses,
    trained weights and running statistics as on one chip (float32: taps do
    not tie; the sums over the batch are added in another order)."""
    import jax
    from jax.sharding import Mesh
    from mxnet_tpu import gluon, nd, parallel
    from mxnet_tpu.observability import catalog
    chips = 4
    if jax.device_count() < chips:
        pytest.skip("needs the four chips of one host (chiprun --chips 4)")
    batch, c = 128 * chips, 32
    rs = np.random.RandomState(11)
    x = rs.uniform(-1, 1, (batch, 16, 16, 3)).astype("float32")
    y = rs.randint(0, 4, (batch,)).astype("float32")
    gamma = (rs.uniform(0.5, 1.5, c)
             * np.where(np.arange(c) % 2, -1.0, 1.0)).astype("float32")
    ends = {}
    for n in (1, chips):
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
            {"learning_rate": 0.002, "momentum": 0.9},
            mesh=Mesh(np.array(jax.devices()[:n]), ("dp",)))
        before = (catalog.POOL_SUNK.value(), catalog.POOL_BWD_LOWERED.value())
        losses = [float(tr.step(x, y)) for _ in range(3)]
        assert (catalog.POOL_SUNK.value() - before[0],
                catalog.POOL_BWD_LOWERED.value() - before[1]) == (1, 1)
        text = tr.lower(x, y).compile().as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 2
        # each chip's kernel on its own rows, with the column of signs
        assert "[8,8,%d,%d]" % (c, batch // n) in text \
            and "f32[%d,1]" % c in text
        tr.sync_to_net()
        ends[n] = (losses, {k: p.data().asnumpy()
                            for k, p in net.collect_params().items()})
    print("losses", {n: e[0] for n, e in ends.items()})
    np.testing.assert_allclose(ends[chips][0], ends[1][0], rtol=1e-3)
    for k, want in ends[1][1].items():
        np.testing.assert_allclose(ends[chips][1][k], want, rtol=1e-2,
                                   atol=1e-3 * np.abs(want).max(),
                                   err_msg=k)


def test_softmax_ce_parity():
    net = sym.SoftmaxOutput(
        sym.FullyConnected(sym.Variable("data"), num_hidden=10),
        sym.Variable("sm_label"), name="sm")
    check_consistency(net, _ctx_list(data=(16, 32), sm_label=(16,)))


def test_elemwise_chain_parity():
    a, b = sym.Variable("a"), sym.Variable("b")
    net = sym.tanh(a * b + sym.exp(a) - sym.sqrt(sym.abs(b) + 1.0))
    check_consistency(net, _ctx_list(a=(4, 64), b=(4, 64)))


def test_dot_transpose_parity():
    a, b = sym.Variable("a"), sym.Variable("b")
    net = sym.dot(a, sym.transpose(b))
    check_consistency(net, _ctx_list_bf16(a=(8, 32), b=(16, 32)))


def test_reduction_broadcast_parity():
    a = sym.Variable("a")
    net = sym.broadcast_mul(a, sym.sum(a, axis=0, keepdims=True))
    check_consistency(net, _ctx_list(a=(8, 16)))


def test_rnn_fused_parity():
    data = sym.Variable("data")
    params = sym.Variable("params")
    state = sym.Variable("state")
    net = sym.RNN(data, params, state, mode="rnn_tanh", state_size=8,
                  num_layers=1, name="rnn")
    from mxnet_tpu.ops.rnn import rnn_packed_param_size
    n = rnn_packed_param_size("rnn_tanh", 1, False, 4, 8)
    check_consistency(net, _ctx_list(data=(5, 2, 4), params=(n,),
                                     state=(1, 2, 8)))


def test_layernorm_softmax_parity():
    d = sym.Variable("data")
    net = sym.softmax(sym.LayerNorm(d, sym.Variable("g"), sym.Variable("b"),
                                    name="ln"))
    check_consistency(net, _ctx_list(data=(4, 32), g=(32,), b=(32,)))
