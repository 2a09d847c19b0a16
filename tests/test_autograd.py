"""Autograd tests (reference: tests/python/unittest/test_autograd.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd
from mxnet_tpu.test_utils import assert_almost_equal


def test_simple_backward(rng):
    x = nd.array(rng.randn(3, 4))
    x.attach_grad()
    with autograd.record():
        y = (x * x + 2 * x).sum()
    y.backward()
    assert_almost_equal(x.grad, 2 * x.asnumpy() + 2, rtol=1e-5)


def test_chain_and_fanout(rng):
    x = nd.array(rng.randn(5))
    x.attach_grad()
    with autograd.record():
        a = x * 2
        b = a + x          # x used twice
        loss = (b * b).sum()
    loss.backward()
    # b = 3x, loss = 9x², d/dx = 18x
    assert_almost_equal(x.grad, 18 * x.asnumpy(), rtol=1e-5)


def test_head_gradient(rng):
    x = nd.array(rng.randn(3))
    x.attach_grad()
    with autograd.record():
        y = x * 4
    y.backward(nd.array([1.0, 2.0, 3.0]))
    assert_almost_equal(x.grad, np.array([4.0, 8.0, 12.0]))


def test_grad_req_add(rng):
    x = nd.array(rng.randn(3))
    x.attach_grad(grad_req="add")
    for _ in range(3):
        with autograd.record():
            y = (x * x).sum()
        y.backward()
    assert_almost_equal(x.grad, 3 * 2 * x.asnumpy(), rtol=1e-5)


def test_pause_and_modes(rng):
    x = nd.array(rng.randn(3))
    x.attach_grad()
    assert not autograd.is_recording()
    with autograd.record():
        assert autograd.is_recording()
        assert autograd.is_training()
        with autograd.pause():
            assert not autograd.is_recording()
            z = x * 10  # not recorded
        y = (x * x).sum()
        with autograd.predict_mode():
            assert not autograd.is_training()
    y.backward()
    assert_almost_equal(x.grad, 2 * x.asnumpy(), rtol=1e-5)


def test_detach(rng):
    x = nd.array(rng.randn(3))
    x.attach_grad()
    with autograd.record():
        y = x * 2
        z = (y.detach() * x).sum()  # grad should only flow through second x
    z.backward()
    assert_almost_equal(x.grad, 2 * x.asnumpy(), rtol=1e-5)


def test_autograd_grad_api(rng):
    x = nd.array(rng.randn(4))
    x.attach_grad()
    with autograd.record():
        y = (x * x * x).sum()
    (gx,) = autograd.grad(y, x)
    assert_almost_equal(gx, 3 * x.asnumpy() ** 2, rtol=1e-4)
    assert x.grad.asnumpy().sum() == 0  # untouched by grad()


def test_multi_output_op_grad(rng):
    x = nd.array(rng.randn(4, 3, 2, 2))
    gamma = nd.ones((3,))
    beta = nd.zeros((3,))
    mm, mv = nd.zeros((3,)), nd.ones((3,))
    x.attach_grad()
    with autograd.record():
        out = nd.BatchNorm(x, gamma, beta, mm, mv, fix_gamma=False)
        loss = (out[0] * out[0]).sum()
    loss.backward()
    assert np.isfinite(x.grad.asnumpy()).all()
    assert abs(x.grad.asnumpy()).sum() > 0


def test_custom_function(rng):
    class Sigmoid(autograd.Function):
        def forward(self, x):
            y = nd.sigmoid(x)
            self.save_for_backward(y)
            return y

        def backward(self, dy):
            (y,) = self.saved_tensors
            return dy * y * (1 - y)

    x = nd.array(rng.randn(5))
    x.attach_grad()
    f = Sigmoid()
    with autograd.record():
        y = f(x)
        loss = y.sum()
    loss.backward()
    s = 1 / (1 + np.exp(-x.asnumpy()))
    assert_almost_equal(x.grad, s * (1 - s), rtol=1e-4, atol=1e-5)


def test_softmax_output_implicit_grad(rng):
    x = nd.array(rng.randn(4, 10))
    label = nd.array([1.0, 2.0, 3.0, 4.0])
    x.attach_grad()
    with autograd.record():
        out = nd.SoftmaxOutput(x, label)
    out.backward()
    p = np.exp(x.asnumpy()) / np.exp(x.asnumpy()).sum(1, keepdims=True)
    oh = np.eye(10)[[1, 2, 3, 4]]
    assert_almost_equal(x.grad, p - oh, rtol=1e-4, atol=1e-5)


def test_exception_surfaces_at_sync(rng):
    # async error semantics: bad op surfaces at wait/asnumpy, not at launch
    x = nd.array(rng.randn(2, 3))
    y = nd.array(rng.randn(4, 5))
    with pytest.raises(Exception):
        z = nd.dot(x, y)  # incompatible shapes
        z.wait_to_read()


def test_recorded_step_compiles_once_and_matches_plain_jax_grad():
    """A steady-state imperative step traces and compiles nothing: every
    recorded op, the scan-holding RNN and CTCLoss among them, runs its cached
    (outputs, pullback) executable. The reference is the same computation written as
    one plain jax.grad over the ops' raw fns."""
    import re
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import gluon
    from mxnet_tpu.observability.jit_hooks import JIT_COMPILES, JIT_TRACES
    from mxnet_tpu.ops.registry import get_op

    T, B, I, H, C = 6, 3, 5, 8, 4

    class Net(gluon.Block):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.rnn = gluon.rnn.GRU(H, layout="NTC", input_size=I)
                self.out = gluon.nn.Dense(C, flatten=False, in_units=H)

        def forward(self, x):
            return self.out(self.rnn(x))

    def reference(p, x, label):
        flat = jnp.concatenate([p["l0_i2h_weight"].ravel(),
                                p["l0_h2h_weight"].ravel(),
                                p["l0_i2h_bias"], p["l0_h2h_bias"]])
        seq = get_op("RNN").fn(
            jnp.swapaxes(x, 0, 1), flat, jnp.zeros((1, B, H)), state_size=H,
            num_layers=1, mode="gru", state_outputs=True, is_train=True)[0]
        logits = get_op("FullyConnected").fn(
            seq, p["weight"], p["bias"], num_hidden=C, flatten=False)
        return get_op("CTCLoss").fn(logits, label, blank_label="last").sum()

    mx.random.seed(3)
    net = Net()
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    ctc = gluon.loss.CTCLoss()
    rs = np.random.RandomState(0)
    x = nd.array(rs.randn(B, T, I))
    label = nd.array([[0, 1, -1], [2, 2, 1], [1, -1, -1]])
    params = {re.sub(r"^.*(gru|dense)\d+_", "", k): v
              for k, v in net.collect_params().items()}
    for step in range(3):
        before = {k: v.data()._data for k, v in params.items()}
        n0 = (JIT_TRACES.value(), JIT_COMPILES.value())
        with autograd.record():
            L = ctc(net(x), label)
        L.backward()
        trainer.step(B)
        loss = L.asnumpy().sum()
        grads = {k: v.data().grad.asnumpy() for k, v in params.items()}
    assert (JIT_TRACES.value(), JIT_COMPILES.value()) == n0
    ref_loss, ref_grads = jax.value_and_grad(reference)(
        before, x._data, label._data)
    assert_almost_equal(loss, np.asarray(ref_loss), rtol=1e-6, atol=1e-6)
    for k, g in grads.items():
        assert_almost_equal(g, np.asarray(ref_grads[k]), rtol=1e-6, atol=1e-6)
