"""The looped decoder LM (``gluon.contrib.transformer.LoopedDecoderLM`` under
``gluon.loss.ExpectedExitCELoss``) against its plain reference
(``chipbench/reference/looped_decoder_lm.py``) at a small size on the CPU,
and what it forced of the trainer and the lowering: several outputs to the
loss, one name per shared parameter, recomputation by segment.

Sizes: vocabulary 512, width 64, 4 heads of 16, FFN 176, 2 layers, 4 passes,
rows of 32, float32. Tolerances: both sides compute in float32 on the CPU
backend with the same formulas in another order of operations, so values
agree to a few float32 roundings of numbers of size 1 to 10 (1e-5); a change
of a leaf after three Adam steps is lr x a ratio of two such numbers, which
carries their relative error on (1e-4 relative to the leaf's change).
"""
import functools
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel
from mxnet_tpu.executor import _GraphLowering
from mxnet_tpu.gluon.contrib import transformer as tfm
from mxnet_tpu.observability import catalog
from mxnet_tpu.ops.registry import get_op

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import follow, harness, traffic  # noqa: E402

SMALL = dict(hidden_size=64, intermediate_size=176, num_attention_heads=4,
             head_dim=16, num_hidden_layers=2, vocab_size=512, vocab_held=512,
             seq_len=32, batch_per_chip=2, items_per_row=32,
             compute_dtype="float32")
ROWS = 2


@pytest.fixture(scope="module")
def ref():
    return harness.load_module(
        os.path.join(REPO, "chipbench", "reference", "looped_decoder_lm.py"),
        "reference_looped_decoder_lm")


@pytest.fixture(scope="module")
def cell_cfg():
    return harness.load_json(REPO, "chipbench", "configs", "ouro_2_6b.json")


@pytest.fixture(scope="module")
def mix():
    return harness.load_json(REPO, "chipbench", "traffic", "packed_4k.json")


def small_cfg(cell_cfg, **builder):
    """The cell's configuration at the tests' sizes."""
    cfg = dict(cell_cfg, **SMALL)
    cfg["builder_kwargs"] = dict(
        vocab_size=512, units=64, hidden_size=176, num_layers=2, num_heads=4,
        loops=cfg["total_ut_steps"], rotary_theta=cfg["rope_theta"],
        epsilon=cfg["rms_norm_eps"], **builder)
    cfg["num_hidden_layers"] = cfg["builder_kwargs"]["num_layers"]
    cfg["total_ut_steps"] = cfg["builder_kwargs"]["loops"]
    cfg["loss_kwargs"] = dict(cell_cfg["loss_kwargs"],
                              exits=cfg["total_ut_steps"])
    return cfg


def program(cfg, ref, seed=5, **trainer_kwargs):
    cfg = dict(cfg, trainer_kwargs=trainer_kwargs)
    return harness.build_program(cfg, ref, seed, jax.devices()[:1])


def batches(cfg, mix, seed, n):
    key = traffic.seed_key(seed)
    return [tuple(np.asarray(a) for a in
                  traffic.batch_tokens(mix, cfg, key, i, ROWS))
            for i in range(n)]


def ids(a):
    return mx.nd.array(a, dtype="int32")


# ------------------------------------------- (a) the block and its reference
def test_block_equals_the_reference_exit_by_exit(ref, cell_cfg, mix):
    cfg = small_cfg(cell_cfg)
    net, _trainer, _mesh, _t = program(cfg, ref)
    specs = ref.leaf_specs(cfg)
    assert [tuple(p.shape) for p in net.collect_params().values()] == \
        [tuple(s) for _k, s, _t in specs]
    assert all(t for _k, _s, t in specs)
    leaves = jax.jit(lambda k: ref.init(cfg, k))(traffic.seed_key(5))
    (x, y), = batches(cfg, mix, 5, 1)
    states, gates, head = net(ids(x))
    want_logits, want_gates = ref.exits(cfg, leaves, x)
    np.testing.assert_allclose(net.exit_logits(ids(x)).asnumpy(),
                               np.asarray(want_logits), atol=1e-5)
    np.testing.assert_allclose(gates.asnumpy(), np.asarray(want_gates),
                               atol=1e-5)
    loss = gluon.loss.ExpectedExitCELoss(**cfg["loss_kwargs"])
    got = float(loss(states, gates, head, ids(y)).mean().asscalar())
    assert abs(got - float(ref.loss_fn(cfg, leaves, x, y)[0])) < 1e-5


def test_exit_distribution_sums_to_one_and_entropy_enters(ref, cell_cfg, mix):
    """beta moves the loss by beta x the mean entropy of the distribution the
    gates define; with one exit the loss is the plain cross-entropy."""
    cfg = small_cfg(cell_cfg)
    leaves = jax.jit(lambda k: ref.init(cfg, k))(traffic.seed_key(3))
    (x, y), = batches(cfg, mix, 3, 1)
    _logits, gates = ref.exits(cfg, leaves, x)
    lam = np.asarray(gates, np.float64)
    rest, probs = np.ones_like(lam[0]), []
    for g in lam[:-1]:
        probs.append(g * rest)
        rest = rest * (1 - g)
    probs.append(rest)
    p = np.stack(probs)
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-12)
    entropy = float(-(p * np.log(p)).sum(0).mean())
    at = lambda beta: float(ref.loss_fn(  # noqa: E731
        dict(cfg, loss_kwargs={"beta": beta}), leaves, x, y)[0])
    assert abs((at(0.0) - at(0.1)) - 0.1 * entropy) < 1e-5
    one = small_cfg(cell_cfg)
    one["total_ut_steps"] = 1
    logits, _ = ref.exits(one, leaves, x)
    logp = jax.nn.log_softmax(logits[0], axis=-1)
    ce = -np.take_along_axis(np.asarray(logp), y[..., None], -1).mean()
    assert abs(float(ref.loss_fn(one, leaves, x, y)[0]) - ce) < 1e-5


# ------------------------------------------ (b) three steps under the trainer
def test_three_adam_steps_follow_the_reference(ref, cell_cfg, mix):
    cfg = small_cfg(cell_cfg)
    net, trainer, _mesh, trainable = program(cfg, ref)
    leaves = jax.jit(lambda k: ref.init(cfg, k))(traffic.seed_key(5))
    init_state, step = follow.make_step(
        functools.partial(ref.loss_fn, cfg), trainable, cfg["optimizer"])
    cur, state = list(leaves), init_state(list(leaves))
    for x, y in batches(cfg, mix, 5, 3):
        got = float(trainer.step(x, y))
        cur, state, want = step(cur, state, x, y)
        assert abs(got - float(want)) < 1e-5
    trainer.sync_to_net()
    for (kind, shape, _t), w, r, w0 in zip(ref.leaf_specs(cfg),
                                           harness.host_leaves(net), cur, leaves):
        moved = np.linalg.norm(np.asarray(r) - np.asarray(w0))
        assert np.linalg.norm(w - np.asarray(r)) < 1e-4 * moved, (kind, shape)


# -------------------------------------- (c) a shared leaf's gradient is a sum
def test_shared_leaf_gradient_is_the_sum_over_its_uses(ref, cell_cfg, mix):
    """Every call of a child makes a variable node of the parameter's name;
    give the nodes of one parameter a name each and the lowered graph has
    four untied copies, whose gradients add up to the shared leaf's."""
    cfg = small_cfg(cell_cfg)
    net, _trainer, _mesh, _t = program(cfg, ref)
    loss = gluon.loss.ExpectedExitCELoss(**cfg["loss_kwargs"])
    from mxnet_tpu import symbol as sym
    (x, y), = batches(cfg, mix, 5, 1)
    values = {p.name: p.data()._data for p in net.collect_params().values()}
    values.update(__data0=jnp.asarray(x), __label=jnp.asarray(y))
    shared = net.layers[0].ffn.up.weight.name

    def graph():
        return loss(*net(sym.Variable("__data0")), sym.Variable("__label"))

    def grad_of(symbol, names):
        fn = _GraphLowering(symbol).lower(True)

        def f(ws):
            outs, _ = fn(dict(values, **ws), jax.random.PRNGKey(0))
            return jnp.mean(outs[0])
        return jax.grad(f)({n: values[shared] for n in names})

    tied = grad_of(graph(), [shared])[shared]
    untied = graph()
    copies = [n for n in untied.topo_nodes() if n.is_var and n.name == shared]
    assert len(copies) == cfg["total_ut_steps"]
    for k, node in enumerate(copies):
        node.name = "%s#%d" % (shared, k)
    parts = grad_of(untied, [n.name for n in copies])
    assert all(float(jnp.abs(g).max()) > 0 for g in parts.values())
    # the same sum in another order: float32 roundings of the parts
    np.testing.assert_allclose(sum(parts.values()), tied, atol=1e-6)


# --------------------------------------------- (d) recomputation by segment
def _checkpointed_calls(jaxpr_text):
    return len(re.findall(r"\bremat2\[", jaxpr_text))


def test_segments_change_neither_loss_nor_update(ref, cell_cfg, mix,
                                                 monkeypatch):
    cfg = small_cfg(cell_cfg)
    # one segment a layer-call; the exits are no segments
    segments = cfg["num_hidden_layers"] * cfg["total_ut_steps"]
    runs = {}
    for name, want in (("segments", segments), ("plain", 0)):
        if name == "plain":     # the same graph, its attribute not read
            from mxnet_tpu import executor
            monkeypatch.setattr(executor, "_mirror_segments", lambda nodes: [])
        net, trainer, _mesh, _t = program(cfg, ref)
        before = catalog.REMAT_SEGMENTS.value()
        losses = [float(trainer.step(x, y)) for x, y in batches(cfg, mix, 5, 2)]
        assert catalog.REMAT_SEGMENTS.value() - before == want
        trainer.sync_to_net()
        runs[name] = losses, harness.host_leaves(net)
        args = (trainer._params, trainer._aux, trainer._opt_state,
                trainer._guard_state, jax.random.PRNGKey(0)) + tuple(
                    jnp.asarray(a) for a in batches(cfg, mix, 5, 1)[0])
        jaxpr = str(jax.make_jaxpr(trainer._step_fn.__wrapped__)(*args))
        # a segment is one checkpointed call forward (its backward is the
        # transpose of the same equation)
        assert _checkpointed_calls(jaxpr) == want, name
    # the same operations, once kept and once recomputed: XLA's CPU fusions
    # differ, a float32 rounding of a loss of 6 and of steps of 1e-3
    np.testing.assert_allclose(runs["segments"][0], runs["plain"][0], atol=1e-6)
    for a, b in zip(runs["segments"][1], runs["plain"][1]):
        np.testing.assert_allclose(a, b, atol=1e-6)


LAYERS, PASSES = 2, 3
#: a layer-call's products that do not widen their first operand and are
#: kept: out-proj, down and the attention (qkv, gate and up widen)
PRODUCTS = 3
#: ... as ``dot_general``s on the CPU, where the attention's forward is the
#: plain form's two (q k^T and p v)
DOTS = 4


def _toy_loss(dtype="float32"):
    """A looped decoder of 2 layers and 3 passes under its loss, as a graph:
    (symbol, f(parameters) -> loss, parameters)."""
    from mxnet_tpu import symbol as sym
    mx.random.seed(7)
    net = tfm.looped_decoder_lm(vocab_size=64, units=32, hidden_size=48,
                                num_layers=LAYERS, num_heads=2, loops=PASSES)
    net.initialize(mx.init.Xavier())
    x = np.random.RandomState(0).randint(0, 64, (2, 8))
    net(ids(x))
    loss = gluon.loss.ExpectedExitCELoss(exits=PASSES)
    symbol = loss(*net(sym.Variable("__data0")), sym.Variable("__label"))
    values = {p.name: p.data()._data.astype(dtype)
              for p in net.collect_params().values()}

    def f(lowering, ws):
        outs, _ = lowering.lower(True)(
            dict(ws, __data0=jnp.asarray(x), __label=jnp.asarray(x[:, ::-1])),
            jax.random.PRNGKey(0))
        return jnp.mean(outs[0].astype(jnp.float32))

    return symbol, f, values


def test_a_segment_keeps_its_products(monkeypatch):
    """The gradient of a graph with segments traces a segment's products
    that do not widen once: against the same graph under a bare
    ``jax.checkpoint`` (every segment's forward traced again for its
    backward) it holds fewer ``dot_general``s by exactly those products."""
    symbol, f, values = _toy_loss()
    lowering = _GraphLowering(symbol)
    assert len(lowering.segments) == LAYERS * PASSES

    def dots():
        before = catalog.REMAT_SEGMENTS.value(), catalog.REMAT_KEPT.value()
        text = str(jax.make_jaxpr(jax.grad(
            functools.partial(f, lowering)))(values))
        assert (catalog.REMAT_SEGMENTS.value() - before[0],
                catalog.REMAT_KEPT.value() - before[1]) == (
            LAYERS * PASSES, LAYERS * PASSES * PRODUCTS)
        return text.count("dot_general")

    kept = dots()
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        lambda *names: None)
    assert dots() - kept == LAYERS * PASSES * DOTS


def test_the_ops_that_call_themselves_products():
    """A product says so where it is registered; the lowering holds no list."""
    from mxnet_tpu.ops.registry import _REGISTRY
    assert {op.name for op in _REGISTRY.values() if op.product} == {
        "FullyConnected", "dot", "batch_dot", "Convolution", "Deconvolution",
        "_contrib_flash_attention", "_contrib_moe_experts"}


@pytest.mark.parametrize("hidden,kept", [(4, 1), (8, 1), (9, 0), (24, 0)])
def test_a_segment_keeps_a_product_that_does_not_widen(hidden, kept):
    """Inside a segment a product's result is named, and so kept, where it
    is no larger than the product's first operand; a widening one is traced
    again for the backward pass with the cheap ops. Outside, none is named."""
    from mxnet_tpu import symbol as sym
    x = sym.Variable("x")
    h = sym.FullyConnected(x, num_hidden=8, name="outside")
    with mx.AttrScope(force_mirroring="a"):
        h = sym.tanh(sym.FullyConnected(h, num_hidden=hidden, name="fc"))
    fn = _GraphLowering(sym.sum(h)).lower(True)
    ins = {"x": jnp.ones((4, 8)), "outside_weight": jnp.ones((8, 8)),
           "outside_bias": jnp.ones((8,)), "fc_weight": jnp.ones((hidden, 8)),
           "fc_bias": jnp.ones((hidden,))}
    before = catalog.REMAT_KEPT.value()
    text = str(jax.make_jaxpr(jax.grad(
        lambda i: fn(i, jax.random.PRNGKey(0))[0][0]))(ins))
    assert catalog.REMAT_KEPT.value() - before == kept
    assert text.count("name[") == kept
    # outside's product, forward and two gradients; fc's the same, and once
    # more where it is recomputed
    assert text.count("dot_general") == 3 + 3 + (1 - kept)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-6), ("bfloat16", 2 ** -6)])
def test_kept_products_give_the_unsegmented_gradients(monkeypatch, dtype, tol):
    """A kept value is the value the recomputation would have produced: the
    gradients are those of the same graph with the attribute not read, to
    XLA's choice of fusions: a float32 rounding; in bfloat16 a few of its
    own (2**-8 each: inside a fusion a recomputed value is not rounded to
    bfloat16 between two ops, a stored one is; measured 0.0088 of a leaf's
    gradient norm at worst)."""
    from mxnet_tpu import executor
    symbol, f, values = _toy_loss(dtype)
    grad = lambda: jax.jit(jax.grad(functools.partial(  # noqa: E731
        f, _GraphLowering(symbol))))(values)
    got = grad()
    monkeypatch.setattr(executor, "_mirror_segments", lambda nodes: [])
    want = grad()
    for name in values:
        a, b = (np.asarray(g[name], np.float32) for g in (got, want))
        assert np.linalg.norm(a - b) <= tol * np.linalg.norm(b), name


@pytest.mark.parametrize("route", ["kernel", "xla"])
def test_flash_attention_keeps_its_residuals_under_a_policy(
        monkeypatch, rng, route):
    """Under ``jax.checkpoint`` with the segments' policy the attention's
    forward is traced once (``out`` and the log-sum-exp are kept for the
    backward: the backward kernel, or the blockwise scan), under a bare one
    twice; the gradient is the unwrapped call's."""
    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.ops.registry import KEPT_IN_SEGMENT
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET",
                       "1" if route == "kernel" else "0")
    q, k, v = (jnp.asarray(rng.randn(1, 2, 16, 128), jnp.float32)
               for _ in range(3))
    loss = lambda *a: jnp.sum(pk.flash_attention(*a, causal=True) ** 2)  # noqa: E731

    def forwards(fn):
        text = str(jax.make_jaxpr(jax.grad(fn, argnums=(0, 1, 2)))(q, k, v))
        if route == "kernel":
            return text.count("name=flash_attention_fwd")
        # the plain form is two products (q k^T, p v); the blockwise
        # backward's scan body holds five of its own
        return (text.count("dot_general") - 5) // 2

    kept = jax.checkpoint(
        loss, policy=jax.checkpoint_policies.save_only_these_names(
            KEPT_IN_SEGMENT))
    assert (forwards(loss), forwards(kept), forwards(jax.checkpoint(loss))) \
        == (1, 1, 2)
    got = jax.grad(kept, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_segment_is_one_run_of_equal_attributes():
    """Nodes of one AttrScope lower as one checkpointed function; a node
    between two runs of one name splits them; values and gradients are the
    unsegmented graph's."""
    from mxnet_tpu import symbol as sym

    def graph(scoped):
        scope = (lambda n: mx.AttrScope(force_mirroring=n)) if scoped \
            else (lambda n: mx.AttrScope())
        x = sym.Variable("x")
        with scope("a"):
            h = sym.tanh(sym.FullyConnected(x, num_hidden=8, name="fc1"))
        h = h * 2.0
        with scope("a"):
            h = sym.tanh(sym.FullyConnected(h, num_hidden=8, name="fc2"))
            h = sym.sigmoid(h)
        return sym.sum(h)

    low = _GraphLowering(graph(True))
    assert [len(run) for run in low.segments] == [2, 3]
    assert _GraphLowering(graph(False)).segments == []
    rng = np.random.RandomState(0)
    ins = {"x": rng.randn(4, 8), "fc1_weight": rng.randn(8, 8),
           "fc1_bias": rng.randn(8), "fc2_weight": rng.randn(8, 8),
           "fc2_bias": rng.randn(8)}
    ins = {k: jnp.asarray(v, jnp.float32) for k, v in ins.items()}

    def value_and_grad(symbol):
        fn = _GraphLowering(symbol).lower(True)
        return jax.value_and_grad(
            lambda i: fn(i, jax.random.PRNGKey(0))[0][0])(ins)

    (va, ga), (vb, gb) = value_and_grad(graph(True)), value_and_grad(graph(False))
    assert float(va) == float(vb)
    for k in ins:
        np.testing.assert_allclose(ga[k], gb[k], rtol=1e-6, atol=1e-7)
    text = str(jax.make_jaxpr(lambda i: _GraphLowering(graph(True)).lower(True)(
        i, jax.random.PRNGKey(0)))(ins))
    assert _checkpointed_calls(text) == 2


def _node_by_node(symbol, is_train):
    """The lowering as it stood before segments: one op after another."""
    from mxnet_tpu._imperative import _op_signature_flags
    from mxnet_tpu.executor import _AUX_UPDATE_RULES
    nodes = symbol.topo_nodes()

    def fn(inputs, rng):
        vals, aux_updates = {}, {}
        for i, node in enumerate(nodes):
            if node.is_var:
                vals[id(node)] = (inputs[node.name],)
                continue
            opdef = get_op(node.op)
            in_arrays = [vals[id(src)][idx] for (src, idx) in node.inputs]
            attrs = dict(node.attrs)
            accepts_train, accepts_rng = _op_signature_flags(opdef)
            if accepts_train and "is_train" not in attrs:
                attrs["is_train"] = is_train
            if accepts_rng:
                attrs["rng"] = jax.random.fold_in(rng, i)
            out = opdef.fn(*in_arrays, **attrs)
            out = out if isinstance(out, tuple) else (out,)
            vals[id(node)] = out
            if is_train and node.op in _AUX_UPDATE_RULES:
                upd = _AUX_UPDATE_RULES[node.op](attrs, in_arrays, out)
                for in_idx, new_val in upd.items():
                    src, _ = node.inputs[in_idx]
                    if src.is_var:
                        aux_updates[src.name] = new_val
        return [vals[id(n)][idx] for (n, idx) in symbol._outputs], aux_updates

    return fn


@pytest.mark.parametrize("is_train", [True, False])
def test_a_graph_without_the_attribute_lowers_as_before(is_train):
    """A ResNet's loss graph (BatchNorm's aux updates, no segment) lowers to
    the text the node-by-node interpreter gives, and the counter stays."""
    from mxnet_tpu import symbol as sym
    from mxnet_tpu.gluon.model_zoo import vision
    net = vision.resnet18_v1(classes=10)
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((2, 3, 32, 32)))
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    symbol = loss(net(sym.Variable("__data0")), sym.Variable("__label"))
    lowering = _GraphLowering(symbol)
    assert lowering.segments == []
    ins = {p.name: p.data()._data for p in net.collect_params().values()}
    ins.update(__data0=jnp.zeros((2, 3, 32, 32), jnp.float32),
               __label=jnp.zeros((2,), jnp.float32))
    before = catalog.REMAT_SEGMENTS.value()
    texts = [str(jax.make_jaxpr(lambda i: fn(i, jax.random.PRNGKey(0)))(ins))
             for fn in (lowering.lower(is_train), _node_by_node(symbol, is_train))]
    assert texts[0] == texts[1]
    assert catalog.REMAT_SEGMENTS.value() == before


# ------------------------------------ (e) several outputs reach the loss
class _TwoHeads(gluon.HybridBlock):
    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.body = gluon.nn.Dense(16, activation="relu", in_units=8)
            self.a = gluon.nn.Dense(4, in_units=16)
            self.b = gluon.nn.Dense(4, in_units=16)

    def hybrid_forward(self, F, x):
        h = self.body(x)
        return self.a(h), self.b(h)


class _FirstHead(_TwoHeads):
    def hybrid_forward(self, F, x):
        return self.a(self.body(x))


class _BothHeadsLoss(gluon.loss.Loss):
    def __init__(self, **kw):
        super().__init__(None, 0, **kw)

    def hybrid_forward(self, F, first, second, label):
        ce = gluon.loss.SoftmaxCrossEntropyLoss()
        return ce(first, label) + ce(second, label)


def _train(net_cls, loss, seed=3, steps=3):
    mx.random.seed(seed)
    net = net_cls()
    net.initialize(mx.init.Xavier())
    rng = np.random.RandomState(seed)
    x = rng.randn(8, 8).astype("float32")
    y = rng.randint(0, 4, (8,)).astype("float32")
    trainer = parallel.DataParallelTrainer(
        net, loss, "sgd", {"learning_rate": 0.1},
        mesh=parallel.local_mesh("dp", devices=jax.devices()[:1]))
    losses = [float(trainer.step(x, y)) for _ in range(steps)]
    trainer.sync_to_net()
    return net, losses, catalog.LOSS_INPUTS.value()


def test_a_loss_of_one_prediction_gets_the_first_output_as_ever():
    """A two-output net under SoftmaxCrossEntropyLoss trains as the net that
    returns the first output alone: the second head is never reached."""
    two, got, reached = _train(_TwoHeads, gluon.loss.SoftmaxCrossEntropyLoss())
    one, want, _ = _train(_FirstHead, gluon.loss.SoftmaxCrossEntropyLoss())
    assert got == want and got[-1] < got[0]
    assert reached == 1
    for a, b in zip(two.collect_params().values(), one.collect_params().values()):
        np.testing.assert_array_equal(a.data().asnumpy(), b.data().asnumpy())
    mx.random.seed(3)
    fresh = _TwoHeads()
    fresh.initialize(mx.init.Xavier())
    np.testing.assert_array_equal(two.b.weight.data().asnumpy(),
                                  fresh.b.weight.data().asnumpy())


def test_a_loss_of_two_predictions_gets_both():
    two, losses, reached = _train(_TwoHeads, _BothHeadsLoss())
    assert reached == 2 and losses[-1] < losses[0]
    mx.random.seed(3)
    fresh = _TwoHeads()
    fresh.initialize(mx.init.Xavier())
    assert np.abs(two.b.weight.data().asnumpy()
                  - fresh.b.weight.data().asnumpy()).max() > 0


@pytest.mark.parametrize("loss,want", [
    (gluon.loss.SoftmaxCrossEntropyLoss(), 1), (gluon.loss.L2Loss(), 1),
    (gluon.loss.TripletLoss(), 1), (gluon.loss.CosineEmbeddingLoss(), 2),
    (gluon.loss.ExpectedExitCELoss(exits=4), 3), (_BothHeadsLoss(), 2),
    (lambda pred, label: pred, 1)],
    ids=["softmax_ce", "l2", "triplet_names_no_label", "cosine", "exits",
         "both_heads", "not_a_block"])
def test_how_many_outputs_a_loss_takes(loss, want):
    from mxnet_tpu.parallel.data_parallel import _loss_predictions
    assert _loss_predictions(loss) == want


# ------------------------------------------ (f) one name a shared parameter
def test_param_names_hold_a_shared_parameter_once(ref, cell_cfg, mix):
    class Twice(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.fc = gluon.nn.Dense(8, in_units=8)
                self.out = gluon.nn.Dense(4, in_units=8)

        def hybrid_forward(self, F, x):
            return self.out(self.fc(F.relu(self.fc(x))))

    net = Twice()
    net.initialize(mx.init.Xavier())
    trainer = parallel.DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd", {"learning_rate": 0.1},
        mesh=parallel.local_mesh("dp", devices=jax.devices()[:1]))
    trainer.step(np.zeros((4, 8), "float32"), np.zeros((4,), "float32"))
    assert sorted(trainer._param_names) == sorted(net.collect_params().keys())
    cfg = small_cfg(cell_cfg)
    looped, trainer, _mesh, _t = program(cfg, ref)
    trainer.step(*batches(cfg, mix, 5, 1)[0])
    assert len(trainer._param_names) == len(set(trainer._param_names)) == \
        len(ref.leaf_specs(cfg))
    assert trainer.footprint()["params_bytes"] == 4 * sum(
        int(np.prod(s)) for _k, s, _t in ref.leaf_specs(cfg))


# ------------------------------------------------ (g) the required FLOPs
def test_required_flops_equal_the_count_by_hand(ref, cell_cfg):
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632          # 51,380,224
    attention = 16 * (128 + 128) * (4096 + 1) // 2     # 8,390,656
    one_pass = 4 * (layer + attention) + 2048 * 49152 + 2048
    assert (layer, attention, one_pass) == (51380224, 8390656, 339748864)
    assert ref.train_flops_per_item(cell_cfg) == 6 * 4 * one_pass == 8153972736
    reader = harness.load_module(os.path.join(
        REPO, "chipbench", "metrics", "kernels.flash_fwd_roofline.py"), "flash")
    assert reader.forward_attention_flops_per_item(cell_cfg) == \
        2 * attention * 4 * 4


def test_configuration_keeps_the_published_sizes(cell_cfg):
    rows = [json.loads(l) for l in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")] \
        if os.path.exists("/opt/skills/guides/model-configs/architectures.jsonl") \
        else []
    published = {"head_dim": 128, "hidden_size": 2048, "intermediate_size": 5632,
                 "num_attention_heads": 16, "num_key_value_heads": 16,
                 "rms_norm_eps": 1e-6, "rope_theta": 1000000,
                 "total_ut_steps": 4, "vocab_size": 49152,
                 "max_position_embeddings": 65536, "early_exit_threshold": 1}
    for row in rows:
        if row["name"] == "Ouro-2.6B":
            published = {k: v for k, v in row["config"].items()
                         if isinstance(v, (int, float)) and not isinstance(v, bool)}
    for k, v in published.items():
        if k not in cell_cfg["reduced"]:
            assert cell_cfg[k] == v, k
    assert cell_cfg["reduced"] == ["num_hidden_layers"]
    assert cell_cfg["published"] == {"num_hidden_layers": 48}
    kw = cell_cfg["builder_kwargs"]
    assert (kw["vocab_size"], kw["units"], kw["hidden_size"], kw["num_heads"],
            kw["loops"], kw["rotary_theta"], kw["epsilon"], kw["num_layers"]) == (
        cell_cfg["vocab_size"], cell_cfg["hidden_size"],
        cell_cfg["intermediate_size"], cell_cfg["num_attention_heads"],
        cell_cfg["total_ut_steps"], cell_cfg["rope_theta"],
        cell_cfg["rms_norm_eps"], cell_cfg["num_hidden_layers"])
    assert cell_cfg["loss_kwargs"]["exits"] == cell_cfg["total_ut_steps"]


# ------------------------------------------------- (i) the one-row fault
def test_one_row_fault_leaves_out_half_the_positions(ref, cell_cfg, mix):
    cfg = small_cfg(cell_cfg)
    leaves = jax.jit(lambda k: ref.init(cfg, k))(traffic.seed_key(5))
    (x, y), = batches(cfg, mix, 5, 1)
    one_x, one_y = x[:1], y[:1]
    loss = lambda *a, **k: float(ref.loss_fn(cfg, leaves, *a, **k)[0])  # noqa: E731
    # calibrate.py's slice on one chip with one row: rows // 2 == 0
    fault = loss(one_x, one_y, rows=slice(0, 0))
    assert fault != loss(one_x, one_y)
    # causal: the first half of a row does not see the second
    assert abs(fault - loss(one_x[:, :16], one_y[:, :16])) < 1e-6
    # a slice that leaves rows is rows left out, as in every family
    assert abs(loss(x, y, rows=slice(0, 1)) - loss(one_x, one_y)) < 1e-6


# ----------------------------------------------------- the ops underneath
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rms_norm_statistic_is_float32(rng, dtype):
    x = jnp.asarray(rng.randn(2, 5, 64) * 3, dtype)
    g = jnp.asarray(rng.rand(64) + 0.5, dtype)
    out = get_op("RMSNorm").fn(x, g, eps=1e-6)
    assert out.dtype == x.dtype
    xf, gf = np.asarray(x, np.float64), np.asarray(g, np.float64)
    want = xf / np.sqrt((xf ** 2).mean(-1, keepdims=True) + 1e-6) * gf
    # one rounding to the data's type: 2**-24 or 2**-8 of values up to ~5
    np.testing.assert_allclose(np.asarray(out, np.float64), want,
                               atol=1e-5 if dtype == "float32" else 4e-2)


def test_rotary_embedding_is_the_half_rotation(rng, ref):
    x = rng.randn(2, 4, 32, 16).astype("float32")
    got = get_op("_contrib_rotary_embedding").fn(jnp.asarray(x), theta=1e6)
    np.testing.assert_allclose(got, ref._rope(jnp.asarray(x), 1e6), atol=1e-6)
    # position 0 is not turned; a turn keeps each pair's length
    np.testing.assert_allclose(got[:, :, 0], x[:, :, 0], atol=1e-7)
    pairs = lambda a: np.asarray(a)[..., :8] ** 2 + np.asarray(a)[..., 8:] ** 2  # noqa: E731
    np.testing.assert_allclose(pairs(got), pairs(x), rtol=1e-5)


def test_rotary_attention_scores_depend_on_distance_alone(rng):
    """q and k turned by their positions give a product that depends on the
    difference of the positions: the reason for the rotation."""
    rope = get_op("_contrib_rotary_embedding").fn
    q = np.tile(rng.randn(1, 1, 1, 16).astype("float32"), (1, 1, 12, 1))
    k = np.tile(rng.randn(1, 1, 1, 16).astype("float32"), (1, 1, 12, 1))
    s = np.einsum("bhqd,bhkd->qk", rope(jnp.asarray(q), theta=100.0),
                  rope(jnp.asarray(k), theta=100.0))
    for d in range(1, 6):
        np.testing.assert_allclose(np.diagonal(s, -d), s[d, 0], atol=1e-4)


def test_flash_attention_counts_its_route():
    q = jnp.zeros((1, 2, 16, 16))
    before = catalog.FLASH_ATTENTION_LOWERED.value(route="xla"), \
        catalog.FLASH_ATTENTION_LOWERED.value(route="pallas")
    get_op("_contrib_flash_attention").fn(q, q, q, causal=True)
    assert (catalog.FLASH_ATTENTION_LOWERED.value(route="xla"),
            catalog.FLASH_ATTENTION_LOWERED.value(route="pallas")) == \
        (before[0] + 1, before[1])


def test_flash_attention_kernel_route_under_the_interpreter(monkeypatch, rng):
    """Where the kernel runs (here its interpreter) and the shape tiles, the
    op takes it and says so; the value is the plain form's."""
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    q, k, v = (jnp.asarray(rng.randn(1, 2, 16, 128), jnp.float32)
               for _ in range(3))
    before = catalog.FLASH_ATTENTION_LOWERED.value(route="pallas")
    with jax.sharding.use_abstract_mesh(
            jax.sharding.AbstractMesh((1,), ("dp",))):
        got = get_op("_contrib_flash_attention").fn(q, k, v, causal=True)
    assert catalog.FLASH_ATTENTION_LOWERED.value(route="pallas") == before + 1
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "0")
    want = get_op("_contrib_flash_attention").fn(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_capture_counts_the_forward_kernels_blocks(monkeypatch):
    """A captured step of a toy-width looped decoder (one head of 128 so the
    kernel runs, here under its interpreter; 1,024 tokens, two 512-row
    blocks) counts each layer-call's forward once: a full, two masked and a
    skipped block a head, beside one backward kernel a layer-call; the
    capture's plain forward and the next step count nothing."""
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    layers, passes, heads = 2, 2, 1
    mx.random.seed(7)
    net = tfm.looped_decoder_lm(vocab_size=64, units=128, hidden_size=64,
                                num_layers=layers, num_heads=heads,
                                loops=passes)
    net.initialize(mx.init.Xavier())
    trainer = parallel.DataParallelTrainer(
        net, gluon.loss.ExpectedExitCELoss(exits=passes), "adam",
        {"learning_rate": 1e-3},
        mesh=parallel.local_mesh("dp", devices=jax.devices()[:1]))
    x = np.random.RandomState(0).randint(0, 64, (1, 1024))
    kinds = ("full", "masked", "skipped")

    def counts():
        return tuple(catalog.FLASH_ATTENTION_FWD_BLOCKS.value(kind=k)
                     for k in kinds) + (
            catalog.FLASH_ATTENTION_BWD_LOWERED.value(route="pallas"),)

    calls = layers * passes
    first = (calls * heads, 2 * calls * heads, calls * heads, calls)
    for want in (first, (0, 0, 0, 0)):
        before = counts()
        assert np.isfinite(float(trainer.step(ids(x), ids(x[:, ::-1]))))
        assert tuple(a - b for a, b in zip(counts(), before)) == want


def test_decoder_cell_is_causal_and_hybridizes(rng):
    cell = tfm.SandwichDecoderCell(32, 64, 2, rotary_theta=1e4)
    cell.initialize(mx.init.Xavier())
    x = rng.randn(1, 8, 32).astype("float32")
    base = cell(mx.nd.array(x)).asnumpy()
    x2 = x.copy()
    x2[0, -1] += 1.0
    pert = cell(mx.nd.array(x2)).asnumpy()
    np.testing.assert_allclose(pert[0, :-1], base[0, :-1], atol=1e-5)
    assert np.abs(pert[0, -1] - base[0, -1]).max() > 1e-3
    cell.hybridize()
    np.testing.assert_allclose(cell(mx.nd.array(x)).asnumpy(), base, atol=1e-5)


def test_a_nets_parameters_die_with_the_net():
    """The autograd registry of leaves finds an array for as long as someone
    holds it and does not hold it itself: on the chip the net's float32
    weights and their gradient buffers (3.3 GB for the cell) outlived the
    net and the plain reference beside them no longer fitted."""
    import gc
    import weakref
    from mxnet_tpu import autograd
    net = gluon.nn.Dense(4, in_units=8)
    net.initialize(mx.init.Xavier())
    w = net.weight.data()
    with autograd.record():
        out = net(mx.nd.ones((2, 8))).sum()
    out.backward()
    assert float(np.abs(w.grad.asnumpy()).sum()) > 0      # found while held
    held, grad = weakref.ref(w), weakref.ref(w.grad)
    del net, w, out
    gc.collect()
    assert held() is None and grad() is None
    assert all(a is not None for a in autograd._all_leaves.values())
