"""Space-to-depth stem: the Convolution op computes a stride-2 few-channel
stem as a stride-(1,2) convolution over row pairs folded into channels. It
must compute EXACTLY the convolution the model declares, value and
gradients, on the model's own weight — a lowering, not an approximation —
and it must engage where the op sees a stem and nowhere else.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import mxnet_tpu as mx
import mxnet_tpu.ops.nn as ops_nn
from mxnet_tpu import gluon, parallel
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo import vision
from mxnet_tpu.gluon.model_zoo.vision.resnet import BasicBlockV1, ResNetV1
from mxnet_tpu.observability import catalog
from mxnet_tpu.ops import get_op


# ``ops/nn.py`` computes a stem-shaped Convolution through space-to-depth
# itself (of the rows: the form that costs the image nothing on the chip), on
# the traced weight, so the parameter keeps the model's (O,kh,kw,C) shape and
# so does its gradient.
_CONV = get_op("Convolution").fn


def _plain_conv(x, w, b, stride, pad, dilate, groups, spec):
    """Today's path, written out: one conv_general_dilated plus the bias."""
    lhs, rhs = spec
    dn = lax.conv_dimension_numbers(x.shape, w.shape, (lhs, rhs, lhs))
    out = lax.conv_general_dilated(
        x, w, window_strides=stride, padding=[(p, p) for p in pad],
        rhs_dilation=dilate, dimension_numbers=dn, feature_group_count=groups)
    if b is not None:
        out = out + b.reshape(tuple(-1 if a == "C" else 1 for a in lhs))
    return out


def _lowered_count():
    return catalog.CONV_S2D_LOWERED.value()


@pytest.mark.parametrize("bias", [False, True], ids=["nobias", "bias"])
@pytest.mark.parametrize("cin", [1, 3, 4])
@pytest.mark.parametrize("pad", [0, 1, 2, 3])
@pytest.mark.parametrize("kernel", [(7, 7), (5, 5), (3, 3), (2, 2), (7, 4)],
                         ids=lambda k: "k%dx%d" % k)
def test_op_lowering_equals_plain_conv_value_and_gradients(rng, kernel, pad,
                                                           cin, bias):
    O, (kh, kw) = 5, kernel
    # even height; the width may be anything (odd here when the pad is)
    x = jnp.asarray(rng.uniform(-1, 1, (2, 12, 16 + pad % 2, cin))
                    .astype("float32"))
    w = jnp.asarray(rng.uniform(-1, 1, (O, kh, kw, cin)).astype("float32"))
    b = jnp.asarray(rng.uniform(-1, 1, (O,)).astype("float32")) if bias \
        else None
    attrs = dict(kernel=kernel, stride=(2, 2), pad=(pad, pad), num_filter=O,
                 no_bias=not bias, layout="NHWC")

    def via_op(x, w, b):
        return _CONV(x, w, b, **attrs)

    def plain(x, w, b):
        return _plain_conv(x, w, b, (2, 2), (pad, pad), (1, 1), 1,
                           ("NHWC", "OHWI"))

    before = _lowered_count()
    with jax.default_matmul_precision("highest"):
        want, vjp_want = jax.vjp(plain, x, w, b)
        got, vjp_got = jax.vjp(via_op, x, w, b)
        cot = jnp.asarray(rng.uniform(-1, 1, want.shape).astype("float32"))
        g_want, g_got = vjp_want(cot), vjp_got(cot)
    assert _lowered_count() == before + 1
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert g_got[1].shape == (O, kh, kw, cin)      # the model's own shape
    for a, e in zip(g_got[:2 + bias], g_want[:2 + bias]):
        np.testing.assert_allclose(a, e, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kernel,pad", [
    ((7, 7), (3, 0)), ((7, 7), (0, 3)), ((5, 3), (1, 2)), ((3, 5), (2, 1)),
    ((2, 3), (5, 4)), ((4, 4), (1, 1))],
    ids=lambda v: "x".join(map(str, v)))
def test_op_lowering_mixed_pad_parities_and_bf16(rng, kernel, pad):
    """Each spatial dim folds its own pad: odd pads shift that dim's blocks
    by one tap, even pads do not. Also in bfloat16, the cells' compute type,
    where both paths round the same products."""
    x = jnp.asarray(rng.uniform(-1, 1, (2, 12, 16, 3)).astype("float32"))
    w = jnp.asarray(rng.uniform(-1, 1, (5,) + kernel + (3,)).astype("float32"))
    attrs = dict(kernel=kernel, stride=(2, 2), pad=pad, num_filter=5,
                 no_bias=True, layout="NHWC")
    with jax.default_matmul_precision("highest"):
        want = _plain_conv(x, w, None, (2, 2), pad, (1, 1), 1,
                           ("NHWC", "OHWI"))
        got = _CONV(x, w, None, **attrs)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    xb, wb = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    got_b = _CONV(xb, wb, None, **attrs)
    assert got_b.dtype == jnp.bfloat16 and got_b.shape == want.shape
    np.testing.assert_allclose(
        got_b.astype(jnp.float32),
        _plain_conv(xb, wb, None, (2, 2), pad, (1, 1), 1, ("NHWC", "OHWI"))
        .astype(jnp.float32), rtol=2e-2, atol=2e-2)


def test_op_lowering_feeds_the_rearranged_weight_to_a_stride1x2_conv(rng):
    """What the step's HLO holds: one stride-(1,2) convolution over the
    rows-to-depth of the UNPADDED image, padded by the convolution (2 row
    blocks low, 1 high, 3 columns each side for a 7x7 pad-3 stem), whose
    weight operand is the (O,4,7,6) twin of the kernel with one zero row in
    front (the pad is odd) and none behind."""
    x = jnp.zeros((2, 32, 32, 3), jnp.float32)
    w = jnp.asarray(rng.uniform(-1, 1, (8, 7, 7, 3)).astype("float32"))
    jaxpr = jax.make_jaxpr(lambda x, w: _CONV(
        x, w, None, kernel=(7, 7), stride=(2, 2), pad=(3, 3), num_filter=8,
        no_bias=True, layout="NHWC"))(x, w)
    convs = [e for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "conv_general_dilated"]
    assert len(convs) == 1
    assert convs[0].params["window_strides"] == (1, 2)
    assert tuple(convs[0].params["padding"]) == ((2, 1), (3, 3))
    assert [tuple(v.aval.shape) for v in convs[0].invars] == \
        [(2, 16, 32, 6), (8, 4, 7, 6)]
    pads = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pad"]
    assert [tuple(e.invars[0].aval.shape) for e in pads] == [(8, 7, 7, 3)], \
        "only the kernel is padded, never the image"
    assert [tuple(int(v) for v in c)
            for c in pads[0].params["padding_config"]] == \
        [(0, 0, 0), (1, 0, 0), (0, 0, 0), (0, 0, 0)]
    assert "optimization_barrier" in [e.primitive.name
                                      for e in jaxpr.jaxpr.eqns]
    # the twin itself: W'[o,du,v,r*C+c] = Wpad[o,2du+r,v,c]
    wp = np.concatenate([np.zeros((8, 1, 7, 3), "float32"), np.asarray(w)], 1)
    twin = np.asarray(ops_nn._rows_to_depth2(jnp.asarray(wp), 4))
    for du in range(4):
        for r in range(2):
            np.testing.assert_array_equal(twin[:, du, :, r * 3:r * 3 + 3],
                                          wp[:, 2 * du + r])


_BYPASS = {
    # name: (data shape, weight shape, attrs, (lhs, rhs))
    "cin5": ((2, 12, 12, 5), (4, 3, 3, 5),
             dict(kernel=(3, 3), stride=(2, 2), pad=(1, 1), layout="NHWC"),
             ("NHWC", "OHWI")),
    "odd_height": ((2, 13, 12, 3), (4, 3, 3, 3),
                          dict(kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                               layout="NHWC"), ("NHWC", "OHWI")),
    "stride1": ((2, 12, 12, 3), (4, 3, 3, 3),
                dict(kernel=(3, 3), stride=(1, 1), pad=(1, 1),
                     layout="NHWC"), ("NHWC", "OHWI")),
    "stride2x1": ((2, 12, 12, 3), (4, 3, 3, 3),
                  dict(kernel=(3, 3), stride=(2, 1), pad=(1, 1),
                       layout="NHWC"), ("NHWC", "OHWI")),
    "dilation2": ((2, 12, 12, 3), (4, 3, 3, 3),
                  dict(kernel=(3, 3), stride=(2, 2), pad=(2, 2),
                       dilate=(2, 2), layout="NHWC"), ("NHWC", "OHWI")),
    "groups2": ((2, 12, 12, 4), (4, 3, 3, 2),
                dict(kernel=(3, 3), stride=(2, 2), pad=(1, 1), num_group=2,
                     layout="NHWC"), ("NHWC", "OHWI")),
    "kernel1x3": ((2, 12, 12, 3), (4, 1, 3, 3),
                  dict(kernel=(1, 3), stride=(2, 2), pad=(0, 1),
                       layout="NHWC"), ("NHWC", "OHWI")),
    "nchw": ((2, 3, 12, 12), (4, 3, 3, 3),
             dict(kernel=(3, 3), stride=(2, 2), pad=(1, 1)),
             ("NCHW", "OIHW")),
    "conv1d_nwc": ((2, 12, 3), (4, 3, 3),
                   dict(kernel=(3,), stride=(2,), pad=(1,), layout="NWC"),
                   ("NWC", "OWI")),
    "conv3d_ndhwc": ((2, 6, 6, 6, 3), (4, 3, 3, 3, 3),
                     dict(kernel=(3, 3, 3), stride=(2, 2, 2), pad=(1, 1, 1),
                          layout="NDHWC"), ("NDHWC", "ODHWI")),
}


@pytest.mark.parametrize("case", sorted(_BYPASS))
def test_op_lowering_bypassed_is_todays_path_bitwise(rng, case):
    dshape, wshape, attrs, spec = _BYPASS[case]
    nd_ = len(attrs["kernel"])
    x = jnp.asarray(rng.uniform(-1, 1, dshape).astype("float32"))
    w = jnp.asarray(rng.uniform(-1, 1, wshape).astype("float32"))
    b = jnp.asarray(rng.uniform(-1, 1, (wshape[0],)).astype("float32"))
    before = _lowered_count()
    got = _CONV(x, w, b, num_filter=wshape[0], **attrs)
    jaxpr = jax.make_jaxpr(lambda x, w: _CONV(
        x, w, None, num_filter=wshape[0], no_bias=True, **attrs))(x, w)
    assert _lowered_count() == before
    assert [e.primitive.name for e in jaxpr.jaxpr.eqns] == \
        ["conv_general_dilated"]
    want = _plain_conv(x, w, b, attrs["stride"], attrs["pad"],
                       attrs.get("dilate", (1,) * nd_),
                       attrs.get("num_group", 1), spec)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _tiny_nhwc_resnet(prefix):
    mx.random.seed(11)
    net = ResNetV1(BasicBlockV1, [1, 1], [8, 8, 16], classes=4,
                   layout="NHWC", prefix=prefix)
    net.initialize(mx.init.Xavier())
    return net


def test_three_momentum_steps_train_the_7x7_stem_not_a_rehomed_one(
        rng, monkeypatch):
    """The test the re-homing s2d pass would have failed: three SGD-momentum
    steps under the cells' passes end, after sync_to_net, where the same run
    ends with the lowering switched off."""
    x = rng.uniform(-1, 1, (8, 32, 32, 3)).astype("float32")
    y = rng.randint(0, 4, (8,)).astype("float32")
    ends = []
    for lowered in (True, False):
        if not lowered:
            monkeypatch.setattr(ops_nn, "_s2d_eligible",
                                lambda *a, **k: False)
        net = _tiny_nhwc_resnet("s2d3_")
        tr = parallel.DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4},
            passes=["fold", "layout", "fusion"])
        before = _lowered_count()
        with jax.default_matmul_precision("highest"):
            losses = [float(tr.step(x, y)) for _ in range(3)]
        assert (_lowered_count() > before) is lowered
        assert tr._params["s2d3_conv2d0_weight"].shape == (8, 7, 7, 3)
        tr.sync_to_net()
        ends.append((losses, {k: p.data().asnumpy() for k, p in
                              net.collect_params().items()}))
    (l_on, p_on), (l_off, p_off) = ends
    np.testing.assert_allclose(l_on, l_off, rtol=1e-5)
    assert p_on.keys() == p_off.keys()
    for k in p_on:
        np.testing.assert_allclose(p_on[k], p_off[k], rtol=1e-5, atol=1e-7,
                                   err_msg=k)


def test_default_passes_lower_to_the_cells_step(rng):
    """DataParallelTrainer() with no ``passes`` and with the benchmark
    cells' explicit list build the same program: the one whose stem is
    lowered through space-to-depth and whose max pool reads the stem
    convolution's output itself (PR 30), which the passes off do not build."""
    x = rng.uniform(-1, 1, (8, 32, 32, 3)).astype("float32")
    y = rng.randint(0, 4, (8,)).astype("float32")
    digests = []
    for kw in ({}, {"passes": ["fold", "layout", "fusion"]},
               {"passes": False}):
        net = _tiny_nhwc_resnet("s2dflt_")
        tr = parallel.DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.05, "momentum": 0.9}, **kw)
        before = catalog.POOL_SUNK.value(), _lowered_count()
        digests.append(tr._lowered_digest(tr.lower(x, y)))
        assert _lowered_count() > before[1]
        assert catalog.POOL_SUNK.value() - before[0] == \
            (kw.get("passes") is not False)
    assert digests[0] == digests[1] != digests[2]


_ZOO = {
    # id: (zoo name, net kwargs, trainer passes, image, engages, pool:
    #      (stems whose max pool sank in front of BatchNorm, ReLUs that a max
    #       pool passed))
    "resnet18_v1-nhwc": ("resnet18_v1", {"layout": "NHWC"}, None, 32, True,
                         (1, 1)),
    "resnet18_v2-nhwc": ("resnet18_v2", {"layout": "NHWC"}, None, 32, True,
                         (1, 1)),
    "resnet18_v1-7x7s2": ("resnet18_v1", {}, None, 32, True, (1, 1)),
    # conv -> relu -> max pool once; its other two pools read a concat
    "squeezenet1_0-7x7s2": ("squeezenet1_0", {}, None, 64, True, (0, 1)),
    "mobilenet1_0-3x3s2": ("mobilenet1_0", {}, None, 32, True, (0, 0)),
    "densenet121-7x7s2": ("densenet121", {}, None, 224, True, (1, 1)),
    "resnet18_v1-nchw-nopasses": ("resnet18_v1", {}, False, 32, False,
                                  (0, 0)),
    "resnet18_v1-thumbnail-3x3s1": ("resnet18_v1", {"thumbnail": True}, None,
                                    32, False, (0, 0)),
    "alexnet-11x11s4": ("alexnet", {}, None, 64, False, (0, 3)),
    "vgg11-3x3s1": ("vgg11", {}, None, 32, False, (0, 5)),
}


@pytest.mark.parametrize("case", sorted(_ZOO))
def test_zoo_stems_take_the_ops_lowering_or_bypass_it(case):
    """Where the stem's lowering is decided, over the zoo: in the op, from
    the shapes it is handed. A net built channel-last hands its stem over as
    it is; one built NCHW does once the default passes have made it
    channel-last, and never under ``passes=False``. Likewise the stem's max
    pool: the fusion pass moves it wherever a BatchNorm or a ReLU with no
    other reader feeds it, whatever the net is called. One trace of the train
    step each, nothing compiled."""
    name, kwargs, passes, image, engages, pool = _ZOO[case]
    mx.random.seed(0)
    net = getattr(vision, name)(classes=10, **kwargs)
    net.initialize(mx.init.Xavier())
    convs = []
    net.apply(lambda b: convs.append(b) if isinstance(b, nn.Conv2D) else None)
    stem = convs[0]._kwargs               # children are visited in order
    channel_last = stem["layout"] == "NHWC"
    # what the op sees of the stem, by the predicate it decides with
    assert ops_nn._s2d_eligible(
        (8, image, image, 3), (stem["num_filter"],) + stem["kernel"] + (3,),
        "NHWC" if channel_last or passes is None else "NCHW",
        stem["stride"], stem["dilate"], stem["num_group"]) is engages
    x = np.zeros((8, image, image, 3) if channel_last
                 else (8, 3, image, image), "float32")
    tr = parallel.DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1}, passes=passes)
    before, sunk_before = _lowered_count(), catalog.POOL_SUNK.value()
    text = tr.lower(x, np.zeros((8,), "float32")).as_text()
    assert (_lowered_count() > before) is engages
    # the max pools the fusion pass moved (PR 30): in front of a BatchNorm's
    # apply (the op's counter, once a trace) and past a ReLU (the graph)
    graph = tr._pass_result.symbol.topo_nodes() if passes is None else []
    passed = [n for n in graph if n.op in ("Activation", "relu")
              and n.inputs[0][0].op in ("Pooling", "_MaxPoolBatchNorm")]
    assert (catalog.POOL_SUNK.value() - sunk_before, len(passed)) == pool
    if passes is None and kwargs.get("layout") == "NHWC":
        # nothing else for the pass to do in a net built channel-last
        assert tr.passes_provenance()["rewrites"]["fusion"] == sum(pool)
    # the lowering's mark in the program: a stride-(1,2) convolution
    assert ("stride = [1, 2]" in text) is engages
