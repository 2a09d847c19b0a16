"""Max-pool backward from the saved winning tap (PR 28): ``ops/nn.py``
``_max_pool_taps``. An eligible ``Pooling(pool_type="max")`` keeps, under
differentiation, the first tap of each window that equals its maximum, and its
backward hands each input position the dy of the windows whose tap it is:
``pallas_kernels.max_pool_fwd`` / ``max_pool_bwd``, on the TPU and, here, under
the Pallas interpreter. They must give ``jax.vjp`` of ``lax.reduce_window``
(``select-and-scatter`` with ``ge``): the same support, values up to the order
of at most k_h*k_w additions. Every other platform, every shape the kernels do
not take and every program in which the op cannot see how its batch is split
keep ``reduce_window``'s own gradient, bit for bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import AbstractMesh, Mesh, PartitionSpec

import mxnet_tpu as mx
import mxnet_tpu.ops.nn as ops_nn
from mxnet_tpu import autograd, gluon, nd, parallel
from mxnet_tpu.observability import catalog
from mxnet_tpu.ops import get_op
from mxnet_tpu.ops import pallas_kernels as pk

_POOL = get_op("Pooling").fn


@pytest.fixture
def interpreted(monkeypatch):
    """The kernels under their interpreter, as a TPU process would run them."""
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")


@pytest.fixture
def one_device(interpreted):
    """... in a program that names its mesh, of one device, as the trainer
    does. This process holds eight: a pool traced here with no mesh named
    could be split by ``jit`` and keeps ``reduce_window``."""
    with jax.sharding.use_abstract_mesh(AbstractMesh((1,), ("dp",))):
        yield

# kernel, stride, pad, (H, W) at the smallest batch and width the kernel's
# blocks take (128 in the lanes, 32 in the sublanes)
_WINDOWS = {
    "3x3s2p1": ((3, 3), (2, 2), (1, 1), (8, 8)),
    "3x3s2p0": ((3, 3), (2, 2), (0, 0), (10, 8)),
    "2x2s2": ((2, 2), (2, 2), (0, 0), (8, 6)),
    "3x3s1p1": ((3, 3), (1, 1), (1, 1), (5, 6)),
    "2x3s1x2": ((2, 3), (1, 2), (0, 1), (7, 8)),
}


def _geometry(shape, layout, kernel, stride, pad, convention):
    """reduce_window's window, strides and padding, written out as the
    reference pooling-inl.h has them (kFull: ceil the output size)."""
    spatial = (1, 2) if layout == "NHWC" else (2, 3)
    window, strides, padding = [1] * 4, [1] * 4, [(0, 0)] * 4
    for j, a in enumerate(spatial):
        window[a], strides[a], hi = kernel[j], stride[j], pad[j]
        if convention == "full":
            out = -(-(shape[a] + 2 * pad[j] - kernel[j]) // stride[j]) + 1
            hi = max((out - 1) * stride[j] + kernel[j] - shape[a] - pad[j],
                     pad[j])
        padding[a] = (pad[j], hi)
    return tuple(window), tuple(strides), tuple(padding)


def _plain(x, geometry):
    return lax.reduce_window(x, -jnp.inf, lax.max, *geometry)


def _input(rng, kind, shape, dtype):
    x = rng.randn(*shape).astype("float32")
    if kind == "relu":      # whole windows of zeros: every tap ties
        x = np.maximum(x, 0) * (rng.rand(*shape) > 0.7)
    elif kind == "constant":
        x[:] = 1.5
    return jnp.asarray(x + 0.0, dtype)       # + 0.0: no negative zero


def _shape(layout, hw, n=128, c=32):
    return (n, *hw, c) if layout == "NHWC" else (n, c, *hw)


def _check_against_reduce_window(rng, x, attrs, geometry):
    def op(x):
        return _POOL(x, pool_type="max", **attrs)
    got, vjp = jax.vjp(op, x)
    # the reference in float32: the widening is exact and keeps the order,
    # so it picks the same element of every window and adds without rounding
    want, vjp_want = jax.vjp(lambda x: _plain(x, geometry),
                             x.astype(jnp.float32))
    assert got.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want))
    dy = jnp.asarray(rng.randn(*want.shape).astype("float32"), x.dtype)
    g = np.asarray(vjp(dy)[0].astype(jnp.float32))
    g_want = np.asarray(vjp_want(dy.astype(jnp.float32))[0])
    assert g.shape == x.shape
    np.testing.assert_array_equal(g != 0, g_want != 0)
    # float32: the order of <= 9 additions; bfloat16: one rounding of the sum
    tol = 1e-6 if x.dtype == jnp.float32 else 2.0 ** -8
    np.testing.assert_allclose(g, g_want, rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", ["random", "relu", "constant"])
@pytest.mark.parametrize("layout", ["NHWC", "NCHW"])
@pytest.mark.parametrize("convention", ["valid", "full"])
@pytest.mark.parametrize("name", sorted(_WINDOWS))
def test_gradient_equals_reduce_windows(rng, one_device, name, convention,
                                        layout, kind, dtype):
    kernel, stride, pad, hw = _WINDOWS[name]
    shape = _shape(layout, hw)
    x = _input(rng, kind, shape, dtype)
    attrs = dict(kernel=kernel, stride=stride, pad=pad,
                 pooling_convention=convention,
                 layout=layout if layout == "NHWC" else None)
    before = catalog.POOL_BWD_LOWERED.value()
    _check_against_reduce_window(
        rng, x, attrs, _geometry(shape, layout, kernel, stride, pad,
                                 convention))
    assert catalog.POOL_BWD_LOWERED.value() == before + 1


@pytest.fixture
def tpu_process(monkeypatch):
    """A process whose default backend is the TPU, as far as the op asks;
    what is lowered here is still lowered for the CPU."""
    monkeypatch.setattr(pk, "use_pallas", lambda: True)
    with jax.sharding.use_abstract_mesh(AbstractMesh((1,), ("dp",))):
        yield


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", ["random", "relu", "constant"])
@pytest.mark.parametrize("convention", ["valid", "full"])
@pytest.mark.parametrize("name", sorted(_WINDOWS))
def test_program_for_another_platform_keeps_reduce_windows_gradient(
        rng, tpu_process, name, convention, kind, dtype):
    """A CPU context in a TPU process: the op takes the new route when it is
    traced, and the program lowered for the CPU computes ``reduce_window`` and
    its own gradient from the input, to the bit."""
    kernel, stride, pad, hw = _WINDOWS[name]
    shape = _shape("NHWC", hw)
    geometry = _geometry(shape, "NHWC", kernel, stride, pad, convention)
    x = _input(rng, kind, shape, dtype)
    before = catalog.POOL_BWD_LOWERED.value()
    out, vjp = jax.vjp(lambda x: _POOL(
        x, pool_type="max", kernel=kernel, stride=stride, pad=pad,
        pooling_convention=convention, layout="NHWC"), x)
    assert catalog.POOL_BWD_LOWERED.value() == before + 1
    want, vjp_want = jax.vjp(lambda x: _plain(x, geometry), x)
    dy = jnp.asarray(rng.randn(*out.shape).astype("float32"), dtype)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(vjp(dy)[0]),
                                  np.asarray(vjp_want(dy)[0]))


def test_index_is_the_first_maximal_tap_in_window_order(one_device):
    """What the forward keeps: row-major tap numbers, first among equals. A
    window of zeros keeps its first tap that is not padding (padding holds
    -inf and never ties)."""
    x = np.zeros((128, 4, 4, 32), "float32")
    x[:, 1, 2, :] = 3.0      # padded (2, 3)
    pool = ops_nn._max_pool_taps("NHWC", (3, 3), (2, 2),
                                 ((0, 0), (1, 1), (1, 1), (0, 0)))
    out, (idx, *_) = pool.fwd(jnp.asarray(x))
    assert idx.dtype == jnp.int8 and idx.shape == out.shape == (128, 2, 2, 32)
    # window (0,0): rows/cols 0 are padding -> tap (1,1); (0,1) holds the peak
    # as tap (2,1); (1,0): column 0 is padding -> tap (0,1); (1,1): the peak
    # is its tap (0,1)
    np.testing.assert_array_equal(np.asarray(idx[0, :, :, 0]),
                                  [[4, 7], [1, 1]])
    np.testing.assert_array_equal(np.asarray(out[5, :, :, 3]),
                                  [[0.0, 3.0], [0.0, 3.0]])
    assert (np.asarray(idx) == np.asarray(idx[:1, :, :, :1])).all()


_BYPASS = {
    # name: (shape, attrs)
    "global": ((128, 8, 8, 32), dict(kernel=(3, 3), global_pool=True,
                                     pool_type="max", layout="NHWC")),
    "avg": ((128, 8, 8, 32), dict(kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                                  pool_type="avg", layout="NHWC")),
    "sum": ((128, 8, 8, 32), dict(kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                                  pool_type="sum", layout="NHWC")),
    "lp": ((128, 8, 8, 32), dict(kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                                 pool_type="lp", layout="NHWC")),
    "taps16": ((128, 8, 8, 32), dict(kernel=(4, 4), stride=(2, 2),
                                     pad=(1, 1), pool_type="max",
                                     layout="NHWC")),
    "batch8": ((8, 8, 8, 32), dict(kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                                   pool_type="max", layout="NHWC")),
    "channels3": ((128, 8, 8, 3), dict(kernel=(3, 3), stride=(2, 2),
                                       pad=(1, 1), pool_type="max",
                                       layout="NHWC")),
    "odd_height": ((128, 9, 8, 32), dict(kernel=(3, 3), stride=(2, 2),
                                         pad=(1, 1), pool_type="max",
                                         layout="NHWC")),
    # float32 rows of 256 columns: the kernels' blocks would not fit VMEM
    "wide_rows": ((128, 2, 256, 32), dict(kernel=(2, 2), stride=(2, 2),
                                          pool_type="max", layout="NHWC")),
    "float16": ((128, 8, 8, 32), dict(kernel=(3, 3), stride=(2, 2),
                                      pad=(1, 1), pool_type="max",
                                      layout="NHWC")),
    "pool1d": ((128, 32, 8), dict(kernel=(3,), stride=(2,), pad=(1,),
                                  pool_type="max")),
    "pool3d": ((128, 32, 4, 4, 4), dict(kernel=(2, 2, 2), stride=(2, 2, 2),
                                        pool_type="max")),
}


def _todays_pooling(x, attrs):
    """The op as it stood before PR 28, written out."""
    saved = ops_nn._pool_tap_eligible
    ops_nn._pool_tap_eligible = lambda *a, **k: False
    try:
        return _POOL(x, **attrs)
    finally:
        ops_nn._pool_tap_eligible = saved


@pytest.mark.parametrize("case", sorted(_BYPASS))
def test_bypassed_is_todays_path_bitwise(rng, one_device, case):
    shape, attrs = _BYPASS[case]
    dtype = jnp.float16 if case == "float16" else jnp.float32
    x = jnp.asarray(rng.randn(*shape).astype("float32"), dtype)
    before = catalog.POOL_BWD_LOWERED.value()
    got, vjp = jax.vjp(lambda x: _POOL(x, **attrs), x)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda x: _POOL(x, **attrs).astype(jnp.float32).sum()))(x)
    assert catalog.POOL_BWD_LOWERED.value() == before
    want, vjp_want = jax.vjp(lambda x: _todays_pooling(x, attrs), x)
    assert str(jaxpr) == str(jax.make_jaxpr(jax.grad(
        lambda x: _todays_pooling(x, attrs).astype(jnp.float32).sum()))(x))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    dy = jnp.asarray(rng.randn(*want.shape).astype("float32"), dtype)
    np.testing.assert_array_equal(np.asarray(vjp(dy)[0]),
                                  np.asarray(vjp_want(dy)[0]))


def test_integer_pool_is_bypassed(one_device):
    """An integer map never meets the custom backward (and -inf has no
    integer form: the op's own path is what it was)."""
    for dtype, want in ((jnp.int32, False), (jnp.int8, False),
                        (jnp.bfloat16, True)):
        assert ops_nn._pool_tap_eligible(
            jax.ShapeDtypeStruct((128, 8, 8, 32), dtype), "NHWC", (3, 3),
            (2, 2), False) == want


# --------------------------------------------------------------------------
# what the op can see of the program around it
# --------------------------------------------------------------------------
def _in_shard_map(axis_names):
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("dp", "mp"))
    seen = []
    jax.jit(jax.shard_map(
        lambda x: (seen.append(ops_nn._pool_batch_split()), x)[1], mesh=mesh,
        in_specs=PartitionSpec("dp"), out_specs=PartitionSpec("dp"),
        axis_names=axis_names)).lower(jnp.zeros((8, 4)))
    return seen[0]


@pytest.mark.parametrize("case,want", [
    ("no_mesh_eight_devices", None),    # jit may split it: cannot tell
    ("mesh_of_one", ()),
    ("dp8", "dp"),
    ("dp8_mp1", "dp"),
    ("dp4_mp2", None),                  # which of them holds the batch?
    ("inside_shard_map", ()),           # the op holds its own rows
    ("shard_map_over_dp_only", None),   # mp is still jit's to split
])
def test_split_of_the_batch_the_op_sees(case, want):
    sizes = {"mesh_of_one": (1,), "dp8": (8,), "dp8_mp1": (8, 1),
             "dp4_mp2": (4, 2)}
    if case in sizes:
        with jax.sharding.use_abstract_mesh(
                AbstractMesh(sizes[case], ("dp", "mp")[:len(sizes[case])])):
            got = ops_nn._pool_batch_split()
    elif case == "no_mesh_eight_devices":
        assert jax.device_count() == 8
        got = ops_nn._pool_batch_split()
    else:
        got = _in_shard_map({"dp", "mp"} if case == "inside_shard_map"
                            else {"dp"})
    assert (got[1] if got else got) == want


@pytest.mark.parametrize("batch,devices,want", [
    (1024, 8, True),     # 128 rows a device
    (512, 8, False),     # 64: a shard does not fill the kernels' blocks
    (512, 4, True),
    (257, 2, False),     # the mesh does not divide the batch
    (128, 1, True),
])
def test_eligibility_is_decided_on_a_devices_share(interpreted, batch,
                                                   devices, want):
    with jax.sharding.use_abstract_mesh(AbstractMesh((devices,), ("dp",))):
        assert ops_nn._pool_tap_eligible(
            jax.ShapeDtypeStruct((batch, 8, 8, 32), jnp.bfloat16), "NHWC",
            (3, 3), (2, 2), False) == want


def test_not_eligible_where_the_kernels_do_not_run():
    """No TPU and no interpreter (this process), or ``MXTPU_PALLAS=0``."""
    with jax.sharding.use_abstract_mesh(AbstractMesh((1,), ("dp",))):
        assert not ops_nn._pool_tap_eligible(
            jax.ShapeDtypeStruct((128, 8, 8, 32), jnp.bfloat16), "NHWC",
            (3, 3), (2, 2), False)


# --------------------------------------------------------------------------
# what the lowered program holds
# --------------------------------------------------------------------------
_ELIGIBLE = dict(kernel=(3, 3), stride=(2, 2), pad=(1, 1), pool_type="max",
                 layout="NHWC")


def _grad_text(x, attrs, **lower_kw):
    return jax.jit(jax.grad(
        lambda x: _POOL(x, **attrs).astype(jnp.float32).sum())) \
        .trace(x).lower(**lower_kw).as_text()


def test_lowered_gradient_holds_no_select_and_scatter(one_device):
    x = jnp.zeros((128, 8, 8, 32), jnp.bfloat16)
    before = catalog.POOL_BWD_LOWERED.value()
    text = _grad_text(x, _ELIGIBLE)
    assert catalog.POOL_BWD_LOWERED.value() == before + 1
    assert "select_and_scatter" not in text
    assert "reduce_window" not in text       # the kernel gives value and index
    assert "optimization_barrier" in text
    compiled = jax.jit(jax.grad(
        lambda x: _POOL(x, **_ELIGIBLE).astype(jnp.float32).sum())) \
        .lower(x).compile().as_text()
    assert "select-and-scatter" not in compiled


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_lowered_for_a_platform_holds_that_platforms_route(tpu_process,
                                                           platform):
    """One trace, lowered for the TPU: the two kernels under their names and
    no ``select_and_scatter``; lowered for the CPU: the reverse."""
    x = jnp.zeros((128, 8, 8, 32), jnp.bfloat16)
    text = _grad_text(x, _ELIGIBLE, lowering_platforms=(platform,))
    on_chip = platform == "tpu"
    assert ("select_and_scatter" in text) != on_chip
    assert text.count("tpu_custom_call") == (2 if on_chip else 0)
    for name in ("max_pool_fwd", "max_pool_bwd"):
        assert (name in text) == on_chip, name


@pytest.mark.parametrize("case", ["batch8", "taps16", "global"])
def test_lowered_gradient_of_a_bypass_still_selects_and_scatters(one_device,
                                                                 case):
    shape, attrs = _BYPASS[case]
    before = catalog.POOL_BWD_LOWERED.value()
    text = _grad_text(jnp.zeros(shape, jnp.float32), attrs)
    assert catalog.POOL_BWD_LOWERED.value() == before
    assert "select_and_scatter" in text


def test_no_mesh_named_on_several_devices_is_bypassed(interpreted):
    """``jit`` could split such a program over this process's eight devices,
    and a Mosaic kernel cannot be split: the pool is ``reduce_window``."""
    before = catalog.POOL_BWD_LOWERED.value()
    text = _grad_text(jnp.zeros((128, 8, 8, 32), jnp.bfloat16), _ELIGIBLE)
    assert catalog.POOL_BWD_LOWERED.value() == before
    assert "select_and_scatter" in text


def test_forward_only_computes_no_index(one_device):
    """Inference (ModelServer, predict, evaluation) traces the primal: one
    reduce_window, no taps, no int8, no barrier, and the counter stays."""
    x = jnp.zeros((128, 8, 8, 32), jnp.bfloat16)
    before = catalog.POOL_BWD_LOWERED.value()
    jaxpr = jax.make_jaxpr(lambda x: _POOL(x, **_ELIGIBLE))(x)
    text = jax.jit(lambda x: _POOL(x, **_ELIGIBLE)).lower(x).as_text()
    assert catalog.POOL_BWD_LOWERED.value() == before
    assert "reduce_window" in text
    for absent in ("i8", "optimization_barrier", "compare", "select"):
        assert absent not in text, absent
    (eqn,) = jaxpr.jaxpr.eqns
    assert eqn.primitive.name == "custom_vjp_call"
    assert [e.primitive.name for e in eqn.params["call_jaxpr"].eqns] == \
        ["reduce_window_max"]


# --------------------------------------------------------------------------
# the routes that reach the op
# --------------------------------------------------------------------------
def test_recorded_imperative_pool_takes_the_new_backward(rng, one_device):
    """autograd.record() -> registry.jitted_op_vjp -> jax.vjp of the op."""
    x = nd.array(np.maximum(rng.randn(128, 8, 8, 32), 0).astype("float32"))
    x.attach_grad()
    before = catalog.POOL_BWD_LOWERED.value()
    with autograd.record():
        y = nd.Pooling(x, kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                       pool_type="max", layout="NHWC")
        loss = (y * y).sum()
    loss.backward()
    assert catalog.POOL_BWD_LOWERED.value() == before + 1
    xj = jnp.asarray(x.asnumpy())
    geometry = _geometry(xj.shape, "NHWC", (3, 3), (2, 2), (1, 1), "valid")
    want = jax.grad(lambda x: jnp.sum(_plain(x, geometry) ** 2))(xj)
    np.testing.assert_allclose(x.grad.asnumpy(), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


class _PoolNet(gluon.HybridBlock):
    def __init__(self, layout, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.conv = gluon.nn.Conv2D(32, 3, padding=1, layout=layout,
                                        in_channels=3)
            self.pool = gluon.nn.MaxPool2D(3, 2, 1, layout=layout)
            self.out = gluon.nn.Dense(4)

    def hybrid_forward(self, F, x):
        return self.out(self.pool(F.relu(self.conv(x))))


def _three_steps(rng, layout, **trainer_kw):
    """Three SGD steps of a conv-relu-pool-dense net on a mesh of one device,
    with the new backward and with it switched off: the same losses and
    parameters."""
    shape = (128, 8, 8, 3) if layout == "NHWC" else (128, 3, 8, 8)
    x = rng.uniform(-1, 1, shape).astype("float32")
    y = rng.randint(0, 4, (128,)).astype("float32")
    ends, counts = [], []
    saved = ops_nn._pool_tap_eligible
    for lowered in (True, False):
        ops_nn._pool_tap_eligible = saved if lowered \
            else (lambda *a, **k: False)
        try:
            mx.random.seed(5)
            net = _PoolNet(layout, prefix="pb%s_" % layout.lower())
            net.initialize(mx.init.Xavier())
            tr = parallel.DataParallelTrainer(
                net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                {"learning_rate": 0.05, "momentum": 0.9},
                mesh=Mesh(np.array(jax.devices()[:1]), ("dp",)), **trainer_kw)
            before = catalog.POOL_BWD_LOWERED.value()
            losses = [float(tr.step(x, y)) for _ in range(3)]
            counts.append(catalog.POOL_BWD_LOWERED.value() - before)
            tr.sync_to_net()
            ends.append((losses, {k: p.data().asnumpy() for k, p in
                                  net.collect_params().items()}))
        finally:
            ops_nn._pool_tap_eligible = saved
    (l_on, p_on), (l_off, p_off) = ends
    np.testing.assert_allclose(l_on, l_off, rtol=1e-5)
    for k in p_on:
        np.testing.assert_allclose(p_on[k], p_off[k], rtol=1e-5, atol=1e-6,
                                   err_msg=k)
    return counts


def test_trainer_with_full_remat_takes_the_new_backward(rng, interpreted):
    """jax.checkpoint around the run: the forward is traced again for the
    backward, index and all."""
    on, off = _three_steps(rng, "NHWC", remat="full")
    assert on >= 1 and off == 0


def test_layout_pass_on_an_nchw_fed_block_takes_the_new_backward(rng,
                                                                 interpreted):
    """The route resnet34_v1.train takes: an NCHW block fed NCHW, rewritten
    to NHWC by the layout pass of the default pipeline."""
    on, off = _three_steps(rng, "NCHW")
    assert on == 1 and off == 0


def test_nchw_with_the_passes_off_takes_it_too(rng, interpreted):
    on, off = _three_steps(rng, "NCHW", passes=False)
    assert on == 1 and off == 0


@pytest.mark.parametrize("batch,route", [(1024, "kernels"), (512, "bypass")])
def test_partitioned_over_the_trainers_dp_mesh(rng, monkeypatch, interpreted,
                                               batch, route):
    """A program that ``jit`` partitions over a mesh cannot hold a Mosaic
    kernel unless the kernel is split by hand. ``DataParallelTrainer`` names
    its mesh around its gradient, and the pool then runs its kernels per shard
    of the batch under ``shard_map``; where a shard's share (64 of 512 over
    eight devices) does not fill the kernels' blocks the pool is
    ``reduce_window`` with its own gradient, which ``jit`` partitions as it
    always did. Interpreted here."""
    calls = []
    for name in ("max_pool_fwd", "max_pool_bwd"):
        def spy(*a, _f=getattr(pk, name), _n=name, **k):
            calls.append((_n, a[0].shape[-1]))
            return _f(*a, **k)
        monkeypatch.setattr(pk, name, spy)
    x = rng.uniform(-1, 1, (batch, 8, 8, 3)).astype("float32")
    y = rng.randint(0, 4, (batch,)).astype("float32")
    losses = {}
    for devices in (1, 8):
        mx.random.seed(5)
        net = _PoolNet("NHWC", prefix="pbdp%d_" % devices)
        net.initialize(mx.init.Xavier())
        tr = parallel.DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.05, "momentum": 0.9},
            mesh=Mesh(np.array(jax.devices()[:devices]), ("dp",)))
        del calls[:]
        before = catalog.POOL_BWD_LOWERED.value()
        losses[devices] = [float(tr.step(x, y)) for _ in range(2)]
        kernels = devices == 1 or route == "kernels"
        assert catalog.POOL_BWD_LOWERED.value() - before == kernels
        assert ("select_and_scatter" in tr.lower(x, y).as_text()) != kernels
        assert {n for _, n in calls} == \
            ({batch // devices} if kernels else set()), calls
    np.testing.assert_allclose(losses[8], losses[1], rtol=1e-5)


def test_trainer_mesh_with_a_second_axis_is_bypassed(rng, interpreted):
    """Where another axis than the data's holds devices the trainer does not
    name its mesh (the name would not say which axis holds the batch), so the
    pool keeps ``reduce_window`` and the step compiles as it did."""
    x = rng.uniform(-1, 1, (1024, 8, 8, 3)).astype("float32")
    y = rng.randint(0, 4, (1024,)).astype("float32")
    net = _PoolNet("NHWC", prefix="pb2ax_")
    net.initialize(mx.init.Xavier())
    tr = parallel.DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.05},
        mesh=Mesh(np.array(jax.devices()).reshape(4, 2), ("dp", "mp")))
    before = catalog.POOL_BWD_LOWERED.value()
    assert np.isfinite(float(tr.step(x, y)))
    assert catalog.POOL_BWD_LOWERED.value() == before
    assert "select_and_scatter" in tr.lower(x, y).as_text()


@pytest.mark.parametrize("check_vma", [False, True])
def test_differentiated_inside_a_shard_map(rng, interpreted, check_vma):
    """A pipeline stage, or a user's own ``shard_map``: every axis is Manual
    and the op holds its own 128 rows. Where the region does not check which
    values vary the kernels run on them, unwrapped; where it does, the
    kernels' constants would not type, and the pool is ``reduce_window``."""
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    spec = PartitionSpec("dp")
    x = _input(rng, "relu", (1024, 8, 8, 32), jnp.float32)
    geometry = _geometry(x.shape, "NHWC", (3, 3), (2, 2), (1, 1), "valid")

    def loss(x):
        return jax.shard_map(
            lambda x: jnp.sum(_POOL(x, **_ELIGIBLE) ** 2, keepdims=True)
            .reshape(1), mesh=mesh, in_specs=spec, out_specs=spec,
            check_vma=check_vma)(x).sum()

    before = catalog.POOL_BWD_LOWERED.value()
    got = jax.jit(jax.grad(loss))(x)
    assert catalog.POOL_BWD_LOWERED.value() - before == (not check_vma)
    want = jax.grad(lambda x: jnp.sum(_plain(x, geometry) ** 2))(x)
    np.testing.assert_array_equal(np.asarray(got) != 0, np.asarray(want) != 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-6)
