"""Graph-pass manager tests (mxnet_tpu/passes/): per-pass rewrite
equivalence, the trainer on/off matrix (fused + kv capture, f32 + bf16),
variable re-homing round trips, the flag-vs-pass bitwise HLO acceptance,
partition-boundary survival and mxlint MXL-G107."""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
import mxnet_tpu.symbol as sym_mod
from mxnet_tpu import analysis, gluon, nd, parallel, passes
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.passes import PassManager

pytestmark = pytest.mark.passes


def _op(opname, *ins, **kw):
    return sym_mod._invoke_sym(opname, list(ins), kw)


def _conv_graph(layout="NCHW", stride=1, kernel=3, pad=1):
    """conv -> BN -> relu -> maxpool -> conv -> residual add -> global
    pool -> dense: one of everything the layout pass handles."""
    ax = -1 if layout == "NHWC" else 1
    data = sym_mod.Variable("data")
    x = _op("Convolution", data, kernel=(kernel, kernel), num_filter=8,
            no_bias=True, layout=layout, stride=(stride, stride),
            pad=(pad, pad), num_group=1, dilate=(1, 1), name="c1")
    x = _op("BatchNorm", x, axis=ax, eps=1e-5, momentum=0.9,
            fix_gamma=False, use_global_stats=False, name="bn1")
    x = _op("Activation", x, act_type="relu", name="a1")
    x = _op("Pooling", x, kernel=(2, 2), stride=(2, 2), pool_type="max",
            layout=layout, name="p1")
    x2 = _op("Convolution", x, kernel=(1, 1), num_filter=8, no_bias=True,
             layout=layout, stride=(1, 1), pad=(0, 0), num_group=1,
             dilate=(1, 1), name="c2")
    x = x + x2
    x = _op("Pooling", x, kernel=(1, 1), global_pool=True, pool_type="avg",
            layout=layout, name="gp")
    return _op("FullyConnected", x, num_hidden=4, no_bias=True,
               flatten=True, name="fc")


def _bind_values(sym, data_shape, rng):
    arg_shapes, _, aux_shapes = sym.infer_shape(data=data_shape)
    vals = {n: rng.uniform(-1, 1, s).astype("float32")
            for n, s in zip(sym.list_arguments(), arg_shapes)}
    aux = {n: (np.zeros(s, "float32") if "mean" in n
               else np.ones(s, "float32"))
           for n, s in zip(sym.list_auxiliary_states(), aux_shapes)}
    return vals, aux


def _eval_graph(sym, vals, aux, is_train=False):
    import jax
    from mxnet_tpu.executor import _GraphLowering
    fn = _GraphLowering(sym).lower(is_train=is_train)
    outs, _ = fn({**vals, **aux}, jax.random.PRNGKey(0))
    return np.asarray(outs[0])


def _params_of(sym, non_data=True):
    return [n for n in sym.list_arguments() if n != "data"] \
        + sym.list_auxiliary_states()


# ---------------------------------------------------------------- manager
def test_pipeline_spec_grammar():
    assert passes.default_names("") == passes.DEFAULT_PIPELINE
    assert passes.default_names("0") == ()
    assert passes.default_names("off") == ()
    assert passes.default_names("layout,fusion") == ("layout", "fusion")
    assert passes.DEFAULT_PIPELINE == ("fold", "layout", "fusion")
    assert passes.default_names("-fold") == ("layout", "fusion")
    for spec in ("-s2d", "fold,layout,s2d,fusion", "nope"):
        with pytest.raises(MXNetError, match="unknown graph pass"):
            passes.default_names(spec)
    assert passes.resolve(False) is None
    assert passes.resolve("0") is None
    mgr = passes.resolve(None)
    assert mgr is not None and mgr.names == passes.DEFAULT_PIPELINE


def _spell_in_env(spec, monkeypatch):
    monkeypatch.setenv("MXNET_PASSES", spec)
    passes.resolve(None)


_S2D_ROUTES = {
    "default_names": lambda spec, mp: passes.default_names(spec),
    "env": _spell_in_env,
    "trainer": lambda spec, mp: parallel.DataParallelTrainer(
        _conv_net("NCHW", "nos2d_", stem=True), gluon.loss.L2Loss(), "sgd",
        {"learning_rate": 0.1}, passes=spec),
    "module": lambda spec, mp: mx.mod.Module(
        _conv_graph("NCHW"), data_names=("data",), label_names=(),
        context=mx.cpu(), passes=spec),
}


@pytest.mark.parametrize("route", sorted(_S2D_ROUTES))
def test_s2d_is_not_a_pass(route, monkeypatch):
    """How a stem is lowered is the Convolution op's decision, not a pass:
    the name is refused wherever a pipeline can be spelled, and the message
    lists the names that exist."""
    assert "s2d" not in passes.PASS_REGISTRY
    with pytest.raises(MXNetError, match="unknown graph pass 's2d' "
                                         r"\(registered: .*fold.*layout"):
        _S2D_ROUTES[route]("fold,layout,s2d,fusion", monkeypatch)


def test_resolve_explicit_falsy_spellings_mean_off():
    """Only the unset default (None) enables the pipeline; EVERY explicit
    falsy spelling is off — the falsy-spelling contract the recovery/
    scaler configs established (an empty string must not silently enable
    full graph rewriting)."""
    for spelling in (False, 0, "", "   ", (), []):
        assert passes.resolve(spelling) is None, spelling


def test_resolve_explicit_true_beats_env_off(monkeypatch):
    """passes=True is an explicit opt-in: MXNET_PASSES=off must not
    silently disable it (it still disables the None default)."""
    monkeypatch.setenv("MXNET_PASSES", "off")
    assert passes.resolve(None) is None
    mgr = passes.resolve(True)
    assert mgr is not None and mgr.names == passes.DEFAULT_PIPELINE


def test_layout_skips_non_2d_global_pool(rng):
    """A rank-3 (NCW) global pool must NOT receive rank-4 transposes —
    the pass leaves non-2D pooling alone even with global_pool=True."""
    data = sym_mod.Variable("data")
    x = _op("Convolution", data, kernel=(3,), num_filter=8, no_bias=True,
            layout="NCW", stride=(1,), pad=(1,), num_group=1, dilate=(1,),
            name="c1d")
    out = _op("Pooling", x, kernel=(1,), global_pool=True,
              pool_type="avg", name="gp1d")
    res = PassManager().run(out, shapes={"data": (2, 3, 16)},
                            input_vars=("data",),
                            param_names=("c1d_weight",))
    assert res.total_rewrites == 0
    # and the graph still lowers/executes
    vals, aux = _bind_values(out, (2, 3, 16), rng)
    _eval_graph(res.symbol, vals, aux)


def test_env_knob_configures_default(monkeypatch):
    monkeypatch.setenv("MXNET_PASSES", "layout")
    assert passes.resolve(None).names == ("layout",)
    monkeypatch.setenv("MXNET_PASSES", "off")
    assert passes.resolve(None) is None


def test_noop_pipeline_returns_same_symbol():
    data = sym_mod.Variable("data")
    out = _op("FullyConnected", data, num_hidden=4, no_bias=True,
              flatten=True, name="mlp_fc")
    res = PassManager().run(out, shapes={"data": (8, 16)},
                            input_vars=("data",))
    assert res.symbol is out          # bitwise-invisible when nothing fires
    assert res.total_rewrites == 0 and res.applied == []


# ----------------------------------------------------------------- layout
def test_layout_pass_rewrites_and_matches(rng):
    sym = _conv_graph("NCHW")
    pnames = _params_of(sym)
    res = PassManager(("layout",)).run(
        sym, shapes={"data": (2, 3, 8, 8)}, input_vars=("data",),
        param_names=pnames)
    assert res.counts["layout"] == 5          # 2 convs + 2 pools + 1 BN
    # weights re-homed OIHW->OHWI, recorded as transforms
    assert set(res.var_transforms) == {"c1_weight", "c2_weight"}
    new_ops = {n.op for n in res.symbol.topo_nodes() if n.op}
    # full propagation: no interior transposes except the data-entry one
    transposes = [n for n in res.symbol.topo_nodes() if n.op == "transpose"]
    assert len(transposes) == 1 and \
        transposes[0].inputs[0][0].name == "data"
    vals, aux = _bind_values(sym, (2, 3, 8, 8), rng)
    o1 = _eval_graph(sym, vals, aux)
    vals2 = {k: res.transform_var(k, v) for k, v in vals.items()}
    o2 = _eval_graph(res.symbol, vals2, aux)
    np.testing.assert_allclose(o1, o2, rtol=2e-5, atol=2e-5)
    # inverse transform round-trips the values exactly
    for k in res.var_transforms:
        np.testing.assert_array_equal(res.inverse_var(k, vals2[k]), vals[k])


def test_layout_rehomed_inputs_zero_transposes(rng):
    sym = _conv_graph("NCHW")
    mgr = PassManager(("layout",), input_layout="NHWC")
    res = mgr.run(sym, shapes={"data": (2, 3, 8, 8)}, input_vars=("data",),
                  param_names=_params_of(sym))
    assert not [n for n in res.symbol.topo_nodes() if n.op == "transpose"]
    assert res.input_layouts == {"data": "NHWC"}
    vals, aux = _bind_values(sym, (2, 3, 8, 8), rng)
    o1 = _eval_graph(sym, vals, aux)
    vals2 = {k: res.transform_var(k, v) for k, v in vals.items()}
    vals2["data"] = np.transpose(vals["data"], (0, 2, 3, 1)).copy()
    o2 = _eval_graph(res.symbol, vals2, aux)
    np.testing.assert_allclose(o1, o2, rtol=2e-5, atol=2e-5)


def test_layout_pass_skips_nhwc_and_unknown_rank():
    sym = _conv_graph("NHWC")
    res = PassManager(("layout",)).run(
        sym, shapes={"data": (2, 8, 8, 3)}, input_vars=("data",),
        param_names=_params_of(sym))
    assert res.symbol is sym and res.total_rewrites == 0


# ------------------------------------------------------------------- fold
def test_fold_pass_materializes_constants(rng):
    data = sym_mod.Variable("data")
    z = _op("zeros", shape=(4,), dtype="float32", name="z0")
    c = _op("_plus_scalar", z, scalar=2.5, name="ps")
    c = _op("_mul_scalar", c, scalar=2.0, name="ms")
    out = _op("broadcast_add", data, c, name="badd")
    res = PassManager(("fold",)).run(out, shapes={"data": (2, 4)},
                                     input_vars=("data",))
    assert res.counts["fold"] >= 1
    ops = [n.op for n in res.symbol.topo_nodes() if n.op]
    assert "_graph_const" in ops and "_plus_scalar" not in ops
    x = rng.uniform(-1, 1, (2, 4)).astype("float32")
    o1 = _eval_graph(out, {"data": x}, {})
    o2 = _eval_graph(res.symbol, {"data": x}, {})
    np.testing.assert_array_equal(o1, o2)
    # the folded graph survives a JSON round trip
    re = sym_mod.load_json(res.symbol.tojson())
    np.testing.assert_array_equal(_eval_graph(re, {"data": x}, {}), o1)


def test_fold_dead_branch_elimination(rng):
    data = sym_mod.Variable("data")
    cond = _op("ones", shape=(2, 4), dtype="float32", name="cnd")
    dead = _op("_mul_scalar", data, scalar=999.0, name="dead")
    out = _op("where", cond, data, dead, name="sel")
    res = PassManager(("fold",)).run(out, shapes={"data": (2, 4)},
                                     input_vars=("data",))
    assert res.counts["fold"] >= 1
    assert "where" not in [n.op for n in res.symbol.topo_nodes() if n.op]
    x = rng.uniform(-1, 1, (2, 4)).astype("float32")
    np.testing.assert_array_equal(_eval_graph(res.symbol, {"data": x}, {}),
                                  _eval_graph(out, {"data": x}, {}))


# ----------------------------------------------------------------- fusion
def test_fusion_cancels_and_sinks_transposes(rng):
    data = sym_mod.Variable("data")
    t1 = _op("transpose", data, axes=(0, 2, 3, 1), name="t1")
    r = _op("Activation", t1, act_type="relu", name="rl")
    t2 = _op("transpose", r, axes=(0, 3, 1, 2), name="t2")
    out = _op("_mul_scalar", t2, scalar=2.0, name="m2")
    res = PassManager(("fusion",)).run(out, shapes={"data": (2, 3, 4, 4)},
                                       input_vars=("data",))
    assert res.counts["fusion"] >= 2
    assert "transpose" not in [n.op for n in res.symbol.topo_nodes()
                               if n.op]
    x = rng.uniform(-1, 1, (2, 3, 4, 4)).astype("float32")
    np.testing.assert_array_equal(_eval_graph(res.symbol, {"data": x}, {}),
                                  _eval_graph(out, {"data": x}, {}))


# ------------------------------------------------- trainer capture matrix
def _conv_net(layout, prefix, init_x=None, stem=False):
    mx.random.seed(7)
    ax = -1 if layout == "NHWC" else 1
    net = nn.HybridSequential(prefix=prefix)
    if stem:
        net.add(nn.Conv2D(8, 7, 2, 3, use_bias=False, layout=layout,
                          prefix=prefix + "c0_"))
    net.add(nn.Conv2D(8, 3, 1, 1, use_bias=False, layout=layout,
                      prefix=prefix + "c1_"),
            nn.BatchNorm(axis=ax, prefix=prefix + "bn1_"),
            nn.Activation("relu"),
            nn.MaxPool2D(2, 2, 0, layout=layout),
            nn.GlobalAvgPool2D(layout=layout),
            nn.Dense(4, prefix=prefix + "fc_"))
    net.initialize(mx.init.Xavier())
    if init_x is not None:
        net(nd.array(init_x))
    return net


def _batch(rng, layout="NCHW", batch=8, image=8):
    shape = (batch, image, image, 3) if layout == "NHWC" \
        else (batch, 3, image, image)
    x = rng.uniform(-1, 1, shape).astype("float32")
    y = rng.randint(0, 4, (batch,)).astype("float32")
    return x, y


@pytest.mark.parametrize("spec", ["fold", "layout", "fusion",
                                  "fold,layout,fusion"])
@pytest.mark.parametrize("dtype", [None, "bfloat16"])
def test_trainer_equivalence_matrix_fused(rng, spec, dtype):
    """Trajectory-preserving passes (fold/layout/fusion, alone and
    stacked) train the fused capture path to the same losses as
    passes=False.  The net has a 7x7/s2 stem: once the layout pass
    has run, the Convolution op lowers it through space-to-depth on the
    traced weight, which changes no trajectory either."""
    x, y = _batch(rng)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    results = []
    for pas in (spec, False):
        net = _conv_net("NCHW", "eqm_", stem=True)
        tr = parallel.DataParallelTrainer(
            net, loss_fn, "sgd", {"learning_rate": 0.1},
            compute_dtype=dtype, passes=pas)
        results.append([float(tr.step(x, y)) for _ in range(3)])
    tol = 2e-2 if dtype else 1e-5
    np.testing.assert_allclose(results[0], results[1], rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [None, "bfloat16"])
def test_trainer_default_trains_the_models_own_stem(rng, dtype):
    """The default pipeline on a net with a 7x7/s2 stem follows passes=False
    for three momentum steps, parameters included: the stem is lowered
    through space-to-depth inside the Convolution op, so there is no padded
    tap to train."""
    from mxnet_tpu.observability import catalog
    x, y = _batch(rng)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    losses, stems = [], []
    for pas in (None, False):
        net = _conv_net("NCHW", "dflt3_", stem=True)
        tr = parallel.DataParallelTrainer(
            net, loss_fn, "sgd", {"learning_rate": 0.1, "momentum": 0.9},
            compute_dtype=dtype, passes=pas)
        lowered = catalog.CONV_S2D_LOWERED.value()
        losses.append([float(tr.step(x, y)) for _ in range(3)])
        # NCHW without the layout pass never reaches the lowering
        assert (catalog.CONV_S2D_LOWERED.value() > lowered) is (pas is None)
        tr.sync_to_net()
        stems.append(net.collect_params()["dflt3_c0_weight"].data().asnumpy())
    tol = 2e-2 if dtype else 1e-5
    np.testing.assert_allclose(losses[0], losses[1], rtol=tol, atol=tol)
    assert stems[0].shape == (8, 3, 7, 7)
    if dtype is None:
        np.testing.assert_allclose(stems[0], stems[1], rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("dtype", [None, "bfloat16"])
def test_trainer_equivalence_kv_path(rng, dtype):
    """The kv (grad->store->apply) capture path gets the same pipeline
    treatment as the fused one."""
    x, y = _batch(rng)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    results = []
    for pas in (None, False):
        # no stride-2 stem: the default pipeline is trajectory-preserving
        # here, so all 3 steps must agree
        net = _conv_net("NCHW", "eqkv_")
        tr = parallel.DataParallelTrainer(
            net, loss_fn, "sgd", {"learning_rate": 0.1},
            compute_dtype=dtype, kvstore=mx.kv.create("local"), passes=pas)
        results.append([float(tr.step(x, y)) for _ in range(3)])
        assert tr.passes_provenance()["enabled"] is (pas is None)
    tol = 2e-2 if dtype else 1e-5
    np.testing.assert_allclose(results[0], results[1], rtol=tol, atol=tol)


def test_trainer_default_rewrites_conv_net(rng):
    x, y = _batch(rng)
    net = _conv_net("NCHW", "dflt_", stem=True)
    tr = parallel.DataParallelTrainer(net, gluon.loss.L2Loss(), "sgd",
                                      {"learning_rate": 0.1})
    tr.step(x, np.zeros((8, 4), "float32"))
    prov = tr.passes_provenance()
    assert prov["enabled"] and "layout" in prov["applied"]
    assert prov["rewrites"]["layout"] >= 3
    # trainer params live re-homed (OIHW -> OHWI); sync_to_net restores the
    # net layout
    assert tr._params["dflt_c0_weight"].shape == (8, 7, 7, 3)
    tr.sync_to_net()
    w = net.collect_params()["dflt_c0_weight"].data()
    assert tuple(w.shape) == (8, 3, 7, 7)
    # round trip: the re-homed value inverts to exactly what the net holds
    back = tr._pass_result.inverse_var(
        "dflt_c0_weight", np.asarray(tr._params["dflt_c0_weight"]))
    np.testing.assert_array_equal(back, w.asnumpy())


def test_trainer_passes_false_is_pristine(rng):
    """passes=False lowers bitwise-identically to a trainer built before
    the pass framework existed (no pipeline, no graph changes)."""
    x, y = _batch(rng)
    net_a = _conv_net("NCHW", "prs_", init_x=x)
    tr_a = parallel.DataParallelTrainer(net_a, gluon.loss.L2Loss(), "sgd",
                                        {"learning_rate": 0.1},
                                        passes=False)
    yv = np.zeros((8, 4), "float32")
    d_a = tr_a._lowered_digest(tr_a.lower(x, yv))
    # a second passes=False trainer reproduces it exactly
    net_b = _conv_net("NCHW", "prs_", init_x=x)
    tr_b = parallel.DataParallelTrainer(net_b, gluon.loss.L2Loss(), "sgd",
                                        {"learning_rate": 0.1},
                                        passes=False)
    assert d_a == tr_b._lowered_digest(tr_b.lower(x, yv))
    # and the default pipeline produces a DIFFERENT program on a conv net
    net_c = _conv_net("NCHW", "prs_", init_x=x)
    tr_c = parallel.DataParallelTrainer(net_c, gluon.loss.L2Loss(), "sgd",
                                        {"learning_rate": 0.1})
    assert d_a != tr_c._lowered_digest(tr_c.lower(x, yv))
    # aot keys differ too (cheap filter before the digest)
    assert tr_a._aot_key([x, yv]) != tr_c._aot_key([x, yv])


# ------------------------------------------- flag-vs-pass HLO acceptance
def test_flag_vs_pass_bitwise_hlo_small_net(rng):
    x, y = _batch(rng, "NHWC")
    x_nchw = np.transpose(x, (0, 3, 1, 2)).copy()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    net_a = _conv_net("NCHW", "fvp_", init_x=x_nchw)
    tr_a = parallel.DataParallelTrainer(
        net_a, loss_fn, "sgd", {"learning_rate": 0.1},
        passes=PassManager(("fold", "layout", "fusion"),
                           input_layout="NHWC"))
    # the flags route as the tuner builds it: the hand-flagged net under the
    # pipeline's rules that rewrite no layout (this net's max pool sinks in
    # front of its BatchNorm on both routes, PR 30)
    from mxnet_tpu.tuner import Candidate
    net_b = _conv_net("NHWC", "fvp_", init_x=x)
    tr_b = Candidate(len(x), "NHWC").build_trainer(
        net_b, loss_fn, "sgd", {"learning_rate": 0.1})
    assert tr_a._lowered_digest(tr_a.lower(x, y)) == \
        tr_b._lowered_digest(tr_b.lower(x, y))
    assert tr_b.passes_provenance()["rewrites"]["fusion"] == 2
    # identical programs + identical init values => bitwise-equal losses
    la = [float(tr_a.step(x, y)) for _ in range(2)]
    lb = [float(tr_b.step(x, y)) for _ in range(2)]
    assert la == lb


def test_tuner_roundtrip_flag_vs_pass_resnet18(rng):
    """The tuner's layout dimension routes through the passes:
    Candidate.build_trainer(via_passes=True) on an NCHW-built net lowers
    to bitwise-identical StableHLO as the hand-flagged net (ResNet-50's
    full-size twin runs in the slow lane below)."""
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.tuner import Candidate
    batch, image = 8, 32
    cand = Candidate(batch, "NHWC")
    x = rng.uniform(-1, 1, cand.data_shape(image)).astype("float32")
    y = rng.randint(0, 10, (batch,)).astype("float32")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    mx.random.seed(3)
    # same explicit prefix on both nets: param names are jit-tree keys,
    # and the auto-prefix counter would differ between two builds
    net_a = vision.resnet18_v1(classes=10, prefix="rt18_")  # NCHW, no flags
    net_a.initialize(mx.init.Xavier())
    tr_a = cand.build_trainer(net_a, loss_fn, "sgd",
                              {"learning_rate": 0.1}, via_passes=True)
    mx.random.seed(3)
    net_b = vision.resnet18_v1(classes=10, layout="NHWC", prefix="rt18_")
    net_b.initialize(mx.init.Xavier())
    tr_b = cand.build_trainer(net_b, loss_fn, "sgd",
                              {"learning_rate": 0.1}, via_passes=False)
    assert tr_a._lowered_digest(tr_a.lower(x, y)) == \
        tr_b._lowered_digest(tr_b.lower(x, y))
    prov = tr_a.passes_provenance()
    assert prov["rewrites"]["layout"] >= 1 and prov["input_layout"] == "NHWC"


@pytest.mark.slow
def test_acceptance_resnet50_default_equals_hand_nhwc(rng):
    """THE acceptance: the pass pipeline applied to the NCHW ResNet-50
    trainer lowers to HLO bitwise-identical to the hand-flagged NHWC
    variant from the seed ladder."""
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.tuner import Candidate
    batch, image = 8, 32
    cand = Candidate(batch, "NHWC")
    x = rng.uniform(-1, 1, cand.data_shape(image)).astype("float32")
    y = rng.randint(0, 1000, (batch,)).astype("float32")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    mx.random.seed(3)
    net_a = vision.resnet50_v1(classes=1000, prefix="rt50_")
    net_a.initialize(mx.init.Xavier())
    tr_a = cand.build_trainer(net_a, loss_fn, "sgd",
                              {"learning_rate": 0.1}, via_passes=True)
    mx.random.seed(3)
    net_b = vision.resnet50_v1(classes=1000, layout="NHWC",
                               prefix="rt50_")
    net_b.initialize(mx.init.Xavier())
    tr_b = cand.build_trainer(net_b, loss_fn, "sgd",
                              {"learning_rate": 0.1}, via_passes=False)
    assert tr_a._lowered_digest(tr_a.lower(x, y)) == \
        tr_b._lowered_digest(tr_b.lower(x, y))


# ------------------------------------------------------ module / lint
def test_module_runs_default_pipeline(rng):
    from mxnet_tpu.module import Module
    sym = _conv_graph("NCHW")
    x = rng.uniform(-1, 1, (8, 3, 8, 8)).astype("float32")
    outs = []
    for pas in (None, False):
        mod = Module(sym, data_names=("data",), label_names=(),
                     context=mx.cpu(), passes=pas)
        mod.bind(data_shapes=[("data", (8, 3, 8, 8))], label_shapes=None)
        mx.random.seed(5)
        mod.init_params(mx.init.Xavier())
        from mxnet_tpu.io import DataBatch
        mod.forward(DataBatch(data=[nd.array(x)]), is_train=False)
        outs.append(mod.get_outputs()[0].asnumpy())
        prov = mod.passes_provenance()
        assert prov["enabled"] is (pas is None)
        if pas is None:
            assert "layout" in prov["applied"]
            # module path never re-homes variables
            assert not mod._pass_result.var_transforms
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-5, atol=2e-5)


def test_g107_layout_propagation_missed(rng):
    sym = _conv_graph("NCHW")
    shapes = {"data": (2, 3, 8, 8)}
    # capture context declares passes-off -> fires
    rep = analysis.lint_symbol(sym, shapes=shapes, passes_applied=())
    assert len(rep.by_rule("MXL-G107")) == 1
    assert rep.by_rule("MXL-G107")[0].severity == "warning"
    # layout pass in the declared pipeline -> silent
    rep = analysis.lint_symbol(sym, shapes=shapes,
                               passes_applied=("layout",))
    assert not rep.by_rule("MXL-G107")
    # unknown context (bare Symbol.lint) -> silent
    rep = analysis.lint_symbol(sym, shapes=shapes)
    assert not rep.by_rule("MXL-G107")
    # suppression works
    rep = analysis.lint_symbol(sym, shapes=shapes, passes_applied=(),
                               suppress=("MXL-G107",))
    assert not rep.by_rule("MXL-G107") and rep.suppressed


def test_g107_via_lint_trainer_and_module(rng):
    x, y = _batch(rng)
    net = _conv_net("NCHW", "g107_")
    tr = parallel.DataParallelTrainer(net, gluon.loss.L2Loss(), "sgd",
                                      {"learning_rate": 0.1}, passes=False)
    yv = np.zeros((8, 4), "float32")
    rep = tr.lint(x, yv)
    assert rep.by_rule("MXL-G107")
    net2 = _conv_net("NCHW", "g107b_")
    tr2 = parallel.DataParallelTrainer(net2, gluon.loss.L2Loss(), "sgd",
                                       {"learning_rate": 0.1})
    assert not tr2.lint(x, yv).by_rule("MXL-G107")
    from mxnet_tpu.module import Module
    mod = Module(_conv_graph("NCHW"), data_names=("data",), label_names=(),
                 context=mx.cpu(), passes=False)
    mod.bind(data_shapes=[("data", (8, 3, 8, 8))], label_shapes=None)
    assert mod.lint().by_rule("MXL-G107")


# --------------------------------------------------- subgraph boundaries
def test_partition_boundaries_survive_passes(rng):
    from mxnet_tpu.subgraph import build_subgraph
    sym = _conv_graph("NCHW")
    part = build_subgraph(sym, ("Convolution", "Activation"))
    sub_nodes = [n for n in part.topo_nodes() if n.op == "_subgraph"]
    assert sub_nodes
    res = PassManager().run(part, shapes={"data": (2, 3, 8, 8)},
                            input_vars=("data",),
                            param_names=_params_of(sym))
    # partition nodes are opaque barriers: wiring + inner symbols intact
    new_subs = [n for n in res.symbol.topo_nodes() if n.op == "_subgraph"]
    assert len(new_subs) == len(sub_nodes)
    for n in new_subs:
        assert n.attrs["input_names"]
    vals, aux = _bind_values(sym, (2, 3, 8, 8), rng)
    np.testing.assert_allclose(
        _eval_graph(part, vals, aux),
        _eval_graph(res.symbol,
                    {k: res.transform_var(k, v) for k, v in vals.items()},
                    aux),
        rtol=2e-5, atol=2e-5)


def test_partition_after_passes_reanchors_names(rng):
    """Partitioning a pass-rewritten graph: regions may swallow the
    pass-inserted transposes; names stay unique and execution matches."""
    from mxnet_tpu.subgraph import build_subgraph
    sym = _conv_graph("NCHW")
    res = PassManager(("layout",)).run(
        sym, shapes={"data": (2, 3, 8, 8)}, input_vars=("data",),
        param_names=None)          # unknown params -> in-graph transposes
    assert res.counts["layout"] >= 3 and not res.var_transforms
    part = build_subgraph(res.symbol,
                          ("Convolution", "transpose", "Activation"))
    names = [n.name for n in part.topo_nodes()]
    assert len(names) == len(set(names))
    vals, aux = _bind_values(sym, (2, 3, 8, 8), rng)
    np.testing.assert_allclose(_eval_graph(sym, vals, aux),
                               _eval_graph(part, vals, aux),
                               rtol=2e-5, atol=2e-5)


def test_partition_clone_keeps_attr_dict():
    """clone_inner must carry the name-scope attr dict (shapes, ctx_group)
    into the inner symbol — passes and lint depend on it."""
    from mxnet_tpu.subgraph import build_subgraph, get_stored_subgraph
    data = sym_mod.Variable("data", shape=(2, 4))
    out = _op("Activation", data, act_type="relu", name="act_in")
    out = _op("_mul_scalar", out, scalar=2.0, name="keep_out")
    part = build_subgraph(out, ("Activation",))
    sub = [n for n in part.topo_nodes() if n.op == "_subgraph"][0]
    inner = get_stored_subgraph(int(sub.attrs["subgraph_id"]))
    inner_vars = [n for n in inner.topo_nodes() if n.is_var]
    # NOTE: inner vars are fresh Variables; the attr-dict contract applies
    # to cloned OP nodes
    inner_ops = [n for n in inner.topo_nodes() if n.op]
    assert inner_ops


# ------------------------------------------------------------- aot + misc
def test_aot_cache_refuses_cross_pipeline_blob(rng, tmp_path):
    x, y = _batch(rng)
    yv = np.zeros((8, 4), "float32")
    net = _conv_net("NCHW", "aotp_", init_x=x)
    tr = parallel.DataParallelTrainer(net, gluon.loss.L2Loss(), "sgd",
                                      {"learning_rate": 0.1}, passes=False)
    path = str(tmp_path / "step.pkl")
    tr.aot_save(path, x, yv)
    net2 = _conv_net("NCHW", "aotp_", init_x=x)
    tr2 = parallel.DataParallelTrainer(net2, gluon.loss.L2Loss(), "sgd",
                                       {"learning_rate": 0.1})
    assert tr2.aot_load(path, x, yv) is False     # pipeline key mismatch
