"""Tests for mx.operator (CustomOp), mx.viz, mx.rtc, mx.registry, sparse retain
regressions. Reference: tests/python/unittest/test_operator.py (CustomOp part),
test_viz.py, test_rtc.py."""
import numpy as np
import pytest

import mxnet_tpu as mx
import mxnet_tpu.operator as mxop


class _Sigmoid(mxop.CustomOp):
    def forward(self, is_train, req, in_data, out_data, aux):
        x = in_data[0].asnumpy()
        self.assign(out_data[0], req[0], mx.nd.array(1.0 / (1.0 + np.exp(-x))))

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        y = out_data[0].asnumpy()
        gy = out_grad[0].asnumpy()
        self.assign(in_grad[0], req[0], mx.nd.array(gy * y * (1 - y)))


@mxop.register("test_sigmoid")
class _SigmoidProp(mxop.CustomOpProp):
    def __init__(self):
        super(_SigmoidProp, self).__init__(need_top_grad=True)

    def create_operator(self, ctx, in_shapes, in_dtypes):
        return _Sigmoid()


def test_custom_op_forward_backward():
    x = mx.nd.array(np.array([0.0, 1.0, -2.0], dtype="float32"))
    x.attach_grad()
    with mx.autograd.record():
        y = mx.nd.Custom(x, op_type="test_sigmoid")
    y.backward()
    expect = 1.0 / (1.0 + np.exp(-x.asnumpy()))
    np.testing.assert_allclose(y.asnumpy(), expect, rtol=1e-6)
    np.testing.assert_allclose(x.grad.asnumpy(), expect * (1 - expect), rtol=1e-6)


def test_custom_op_symbolic():
    data = mx.sym.Variable("data")
    s = mx.sym.Custom(data, op_type="test_sigmoid", name="sig")
    x = mx.nd.array(np.array([0.5, -0.5], dtype="float32"))
    ex = s.bind(mx.cpu(), {"data": x})
    out = ex.forward()[0].asnumpy()
    np.testing.assert_allclose(out, 1.0 / (1.0 + np.exp(-x.asnumpy())), rtol=1e-6)


def test_custom_op_chained_grad():
    """Custom op composed with builtin ops keeps the chain rule intact."""
    x = mx.nd.array(np.array([0.3, 0.7], dtype="float32"))
    x.attach_grad()
    with mx.autograd.record():
        y = mx.nd.Custom(2.0 * x, op_type="test_sigmoid")
        z = (y * y).sum()
    z.backward()
    xv = x.asnumpy()
    s = 1.0 / (1.0 + np.exp(-2.0 * xv))
    expect = 2.0 * s * (s * (1 - s) * 2.0)
    np.testing.assert_allclose(x.grad.asnumpy(), expect, rtol=1e-5)


def _mlp_symbol():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=10, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu", name="relu1")
    return mx.sym.FullyConnected(act, num_hidden=2, name="fc2")


def test_print_summary_param_counts(capsys):
    out = _mlp_symbol()
    total = mx.viz.print_summary(out, shape={"data": (1, 5)})
    # fc1: 5*10+10, fc2: 10*2+2
    assert total == 60 + 22
    assert "fc1" in capsys.readouterr().out


def test_plot_network():
    graphviz = pytest.importorskip("graphviz")
    out = _mlp_symbol()
    dot = mx.viz.plot_network(out, shape={"data": (1, 5)})
    src = dot.source
    assert "fc1" in src and "fc2" in src and "relu1" in src
    # weights hidden by default
    assert "fc1_weight" not in src


def test_rtc_pallas_kernel():
    def axpy_kernel(x_ref, y_ref, o_ref):
        o_ref[...] = 2.0 * x_ref[...] + y_ref[...]

    mod = mx.rtc.PallasModule(axpy_kernel)
    k = mod.get_kernel("axpy_kernel")
    x = mx.nd.array(np.arange(16.0, dtype="float32").reshape(2, 8))
    y = mx.nd.ones((2, 8))
    out = k.launch([x, y], interpret=True)
    np.testing.assert_allclose(out.asnumpy(), 2 * x.asnumpy() + 1)


def test_rtc_cuda_module_raises():
    with pytest.raises(mx.MXNetError):
        mx.rtc.CudaModule("__global__ void k() {}")


def test_registry_create_from_json():
    from mxnet_tpu import registry

    class Animal(object):
        pass

    class Dog(Animal):
        def __init__(self, legs=4):
            self.legs = int(legs)

    reg = registry.get_register_func(Animal, "animal")
    reg(Dog)
    create = registry.get_create_func(Animal, "animal")
    assert create("dog").legs == 4
    assert create('["dog", {"legs": 3}]').legs == 3
    d = Dog()
    assert create(d) is d


def test_sparse_retain_unsorted_indices():
    """Regression: retain must handle indices stored unsorted."""
    vals = np.array([[2, 2], [1, 1]], dtype="float32")
    arr = mx.nd.sparse.row_sparse_array((vals, [4, 1]), shape=(10, 2))
    out = arr.retain(mx.nd.array([1, 4]))
    np.testing.assert_allclose(out.data.asnumpy(), [[1, 1], [2, 2]])


def test_sparse_retain_preserves_dtype():
    """Regression: retain must not promote int values to float."""
    vals = np.array([[1, 2], [3, 4]], dtype="int32")
    arr = mx.nd.sparse.row_sparse_array((vals, [0, 2]), shape=(5, 2))
    out = arr.retain(mx.nd.array([0, 1]))
    assert out.data.asnumpy().dtype == np.int32
    np.testing.assert_array_equal(out.data.asnumpy(), [[1, 2], [0, 0]])


def test_check_consistency_machinery(rng):
    """check_consistency compares contexts/dtypes (here cpu fp32 vs cpu
    bf16 — the dtype ladder) and raises on real divergence."""
    import pytest
    from mxnet_tpu.test_utils import check_consistency
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=8,
                                name="fc")
    ctx_list = [dict(ctx=mx.cpu(), data=(4, 16)),
                dict(ctx=mx.cpu(), data=(4, 16),
                     type_dict={"__default__": "bfloat16"})]
    outs = check_consistency(net, ctx_list)
    assert len(outs) == 2 and outs[0][0].shape == (4, 8)

    # a genuinely divergent "context" must be caught: scale one input set
    with pytest.raises(AssertionError):
        check_consistency(net, ctx_list, tol=1e-12)


def test_context_memory_info():
    """HBM/host allocator observability (reference MXGetGPUMemoryInformation
    / pooled storage manager counters)."""
    x = mx.nd.ones((256, 256))
    x.wait_to_read()
    info = mx.cpu().memory_info()
    assert "device" in info and info["live_arrays"] >= 1
    assert info["live_array_bytes"] >= 256 * 256 * 4


def test_server_profiler_commands_local(tmp_path, monkeypatch):
    """profile_process='server' routes through the kvstore control channel;
    a single-process store executes its own server role (reference
    KVStoreServerProfilerCommand, include/mxnet/kvstore.h:49)."""
    from mxnet_tpu import profiler
    monkeypatch.chdir(tmp_path)
    kv = mx.kv.create("local")
    profiler.set_kvstore_handle(kv)
    try:
        profiler.set_config(filename="srv.json", profile_all=True,
                            profile_process="server")
        profiler.set_state(state="run", profile_process="server")
        mx.nd.ones((4, 4)).asnumpy()
        profiler.pause(profile_process="server")
        profiler.resume(profile_process="server")
        profiler.set_state(state="stop", profile_process="server")
        profiler.dump(profile_process="server")
        import json as _json
        with open("rank0_srv.json") as f:
            assert "traceEvents" in _json.load(f)
    finally:
        profiler.set_kvstore_handle(None)


def test_server_profiler_requires_kvstore_handle():
    from mxnet_tpu import profiler
    profiler.set_kvstore_handle(None)
    with pytest.raises(mx.base.MXNetError, match="set_kvstore_handle"):
        profiler.set_state(state="run", profile_process="server")
