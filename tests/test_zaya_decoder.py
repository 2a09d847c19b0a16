"""The ZAYA1 decoder LM (``gluon.contrib.transformer.ZayaDecoderLM`` under
``gluon.loss.TiedHeadCELoss``) against its plain reference
(``chipbench/reference/zaya_decoder_lm.py``) at a small size on the CPU, and
what it forced: an expert layer that is told which experts it holds (the
shares add up, nothing is dropped, grouped and plain routes agree), a router
MLP in float32, attention in a compressed latent (causal convolutions, a
shifted value head, grouped key/value heads, partial rotary), a tied head.

Sizes: vocabulary 512, width 64, 4 query and 2 key/value heads of 16, 8
experts of 48 of which 4 are held, router 32, 2 layers, 2 rows of 32, float32.
Tolerances: both sides compute in float32 on the CPU backend with the same
formulas in another order of operations, so values agree to a few float32
roundings (1e-5); gradients of size 1e-2 to 1 likewise (2e-5 absolute).
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon.contrib import transformer as tfm
from mxnet_tpu.observability import catalog
from mxnet_tpu.ops import nn as ops_nn
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.registry import get_op

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import follow, harness, traffic  # noqa: E402

ROWS, ROUTED = 2, 8


@pytest.fixture(scope="module")
def ref():
    return harness.load_module(
        os.path.join(REPO, "chipbench", "reference", "zaya_decoder_lm.py"),
        "reference_zaya_decoder_lm")


@pytest.fixture(scope="module")
def cell_cfg():
    return harness.load_json(REPO, "chipbench", "configs", "zaya1_8b.json")


@pytest.fixture(scope="module")
def mix():
    return harness.load_json(REPO, "chipbench", "traffic", "packed_8k.json")


def small_cfg(cell_cfg, held=4, first=0, layers=2):
    """The cell's configuration at the tests' sizes: ``held`` of 8 experts
    from ``first``."""
    cfg = dict(cell_cfg, hidden_size=64, moe_intermediate_size=48,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               router_hidden_size=32, num_hidden_layers=layers,
               vocab_size=512, vocab_held=512, seq_len=32,
               batch_per_chip=ROWS, items_per_row=32,
               compute_dtype="float32", num_experts=held)
    cfg["published"] = dict(cell_cfg["published"], num_experts=ROUTED)
    cfg["deployment"] = dict(cell_cfg["deployment"], first_expert=first)
    cfg["builder_kwargs"] = dict(
        cell_cfg["builder_kwargs"], vocab_size=512, units=64, hidden_size=48,
        num_layers=layers, num_heads=4, num_kv_heads=2, head_dim=16,
        num_experts=ROUTED, router_hidden=32, experts_held=held,
        first_expert=first, rotary_dim=8)
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


def seeded_leaves(cfg, ref, seed=5):
    return jax.jit(lambda k: ref.init(cfg, k))(traffic.seed_key(seed))


# ------------------------------------------- (a) the block and its reference
def test_block_equals_the_reference(ref, cell_cfg, mix):
    cfg = small_cfg(cell_cfg)
    net, _trainer, _mesh, _t = program(cfg, ref)
    specs = ref.leaf_specs(cfg)
    assert [tuple(p.shape) for p in net.collect_params().values()] == \
        [tuple(s) for _k, s, _t in specs]
    assert all(t for _k, _s, t in specs)
    leaves = seeded_leaves(cfg, ref)
    (x, y), = batches(cfg, mix, 5, 1)
    np.testing.assert_allclose(net.logits(ids(x)).asnumpy(),
                               np.asarray(ref.logits(cfg, leaves, x)),
                               atol=1e-5)
    states, weight = net(ids(x))
    got = float(gluon.loss.TiedHeadCELoss()(states, weight, ids(y))
                .mean().asscalar())
    assert abs(got - float(ref.loss_fn(cfg, leaves, x, y)[0])) < 1e-5


def test_every_leafs_gradient_equals_the_references(ref, cell_cfg, mix):
    """One step of plain SGD (no momentum, no decay) at lr 1 through the
    trainer's captured, segmented step: minus the update IS the gradient, of
    every leaf, the tied embedding's the sum of both its uses."""
    cfg = small_cfg(cell_cfg)
    cfg["optimizer"] = {"name": "sgd", "learning_rate": 1.0, "momentum": 0.0,
                        "wd": 0.0}
    net, trainer, _mesh, _t = program(cfg, ref)
    leaves = seeded_leaves(cfg, ref)
    (x, y), = batches(cfg, mix, 5, 1)
    loss, grads = jax.value_and_grad(
        lambda lv: ref.loss_fn(cfg, lv, x, y)[0])(leaves)
    before = harness.host_leaves(net)
    got = float(trainer.step(ids(x), ids(y)))
    trainer.sync_to_net()
    assert abs(got - float(loss)) < 1e-5
    names = list(net.collect_params())
    assert len(trainer._param_names) == len(names) == len(leaves)
    for name, b, a, g in zip(names, before, harness.host_leaves(net), grads):
        np.testing.assert_allclose(b - a, np.asarray(g), atol=2e-5,
                                   err_msg=name)
    assert float(np.abs(np.asarray(grads[0])).max()) > 1e-4


def test_three_steps_follow_the_reference(ref, cell_cfg, mix):
    """The cell's own optimizer (SGD, momentum 0.95) over three batches."""
    cfg = small_cfg(cell_cfg)
    net, trainer, _mesh, trainable = program(cfg, ref)
    data = batches(cfg, mix, 5, 3)

    class Feed:
        pool = iter(data)

        def next(self):
            x, y = next(self.pool)
            return ids(x), ids(y)

    lr = cfg["optimizer"]["learning_rate"]
    prog = harness.follow_program(net, trainer, Feed(), trainable, lr, 3)
    step = follow.make_step(lambda lv, x, y: ref.loss_fn(cfg, lv, x, y),
                            trainable, cfg["optimizer"])
    refd = follow.follow(*step, seeded_leaves(cfg, ref), trainable,
                         iter(data), lr)
    np.testing.assert_allclose(prog["loss"], refd["loss"], rtol=1e-5)
    for p, r in zip(prog["change"], refd["change"]):
        assert np.abs(p - r).max() <= 1e-4 * max(np.abs(r).max(), 1e-6) + 1e-8


def test_tied_embedding_is_one_parameter_read_twice(ref, cell_cfg, mix):
    """Listed once by the block and by the trainer; its gradient is the
    lookup's plus the head's (each alone is not)."""
    cfg = small_cfg(cell_cfg, layers=1)
    cfg["optimizer"] = {"name": "sgd", "learning_rate": 1.0, "momentum": 0.0,
                        "wd": 0.0}
    net, trainer, _mesh, _t = program(cfg, ref)
    names = list(net.collect_params())
    assert sum(n.endswith("embed_weight") for n in names) == 1
    leaves = seeded_leaves(cfg, ref)
    (x, y), = batches(cfg, mix, 5, 1)

    def _two(lookup, head):
        q = lambda t: t  # noqa: E731
        h, _r = ref._layers(cfg, [lookup] + list(leaves[1:]), x, q)
        ce = ref._cross_entropy(h.reshape(-1, h.shape[-1]), head,
                                jnp.asarray(y).reshape(-1), q)
        return jnp.mean(ce)

    g_lookup, g_head = jax.grad(_two, argnums=(0, 1))(leaves[0], leaves[0])
    before = harness.host_leaves(net)[0]
    trainer.step(ids(x), ids(y))
    trainer.sync_to_net()
    assert trainer._param_names.count(names[0]) == 1
    got = before - harness.host_leaves(net)[0]
    np.testing.assert_allclose(got, np.asarray(g_lookup + g_head), atol=2e-5)
    assert np.abs(got - np.asarray(g_head)).max() > 1e-4
    assert np.abs(got - np.asarray(g_lookup)).max() > 1e-4


# ------------------------------------------------- (b) the chip's share
def _expert_layer(rng, tokens=96, width=64, hidden=48, dtype=np.float32):
    x = rng.randn(tokens, width).astype(dtype)
    gate = rng.uniform(0.1, 1.0, tokens).astype(np.float32)
    w = [0.2 * rng.randn(ROUTED, *s).astype(dtype)
         for s in ((hidden, width), (hidden, width), (width, hidden))]
    return x, gate, w


def _share(x, expert, gate, w, first, held):
    op = get_op("_contrib_moe_experts").fn
    return op(jnp.asarray(x), jnp.asarray(expert, jnp.int32),
              jnp.asarray(gate), *(jnp.asarray(a[first:first + held])
                                   for a in w),
              first_expert=first, num_experts=ROUTED)


def _whole(x, expert, gate, w):
    """Every token through its own expert, one at a time."""
    out = np.zeros_like(x, dtype=np.float64)
    for t, e in enumerate(expert):
        g, u = w[0][e] @ x[t], w[1][e] @ x[t]
        out[t] = gate[t] * (w[2][e] @ (g / (1 + np.exp(-g)) * u))
    return out


def test_the_shares_add_up(rng):
    """Experts 0-3 and experts 4-7, each as one chip's share of the layer,
    sum to the uncut layer's output; so do eight shares of one."""
    x, gate, w = _expert_layer(rng)
    expert = rng.randint(0, ROUTED, len(x))
    want = _whole(x, expert, gate, w)
    two = [np.asarray(_share(x, expert, gate, w, f, 4)) for f in (0, 4)]
    np.testing.assert_allclose(two[0] + two[1], want, atol=1e-5)
    here = expert < 4
    assert np.abs(two[0][~here]).max() == 0 and np.abs(two[1][here]).max() == 0
    eight = sum(np.asarray(_share(x, expert, gate, w, f, 1))
                for f in range(ROUTED))
    np.testing.assert_allclose(eight, want, atol=1e-5)


def test_model_shares_add_up_to_the_uncut_layer(ref, cell_cfg, mix):
    """The same through the block and the reference: a one-layer model that
    holds experts 0-3, one that holds 4-7 and one that holds all eight, on
    the same weights. Attention, router, norms and join are whole in each,
    so the expert terms are what differ: with ``y_A``, ``y_B`` the two
    shares' expert sub-layer outputs (before the join), the uncut layer's is
    their sum."""
    (x, _y), = batches(small_cfg(cell_cfg), mix, 5, 1)
    full_cfg = small_cfg(cell_cfg, held=ROUTED, layers=1)
    full = seeded_leaves(full_cfg, ref)
    outs = {}
    for name, first, held in (("a", 0, 4), ("b", 4, 4), ("all", 0, ROUTED)):
        cfg = small_cfg(cell_cfg, held=held, first=first, layers=1)
        net, _tr, _m, _t = program(cfg, ref)
        params = list(net.collect_params().values())
        for p, leaf in zip(params, full):
            if p.name.endswith(("experts_gate_weight", "experts_up_weight",
                                "experts_down_weight")):
                leaf = leaf[first:first + held]
            p.set_data(mx.nd.array(np.asarray(leaf)))
        cell = net.layers[0]
        h = mx.nd.Embedding(ids(x), params[0].data(), input_dim=512,
                            output_dim=64)
        h = h + cell.join_a(cell.attn(cell.norm_a(h)))
        m = cell.norm_m(h)
        expert, gate, _state = cell.router(m)
        outs[name] = cell.experts(m, expert, gate).asnumpy()
        want = ref._expert_sublayer(
            cfg, lambda t: t, jnp.asarray(h.asnumpy()), None,
            [jnp.asarray(p.data().asnumpy()) for p in params[14:25]])
        got = (h + cell.join_m(cell.experts(m, expert, gate))).asnumpy()
        np.testing.assert_allclose(got, np.asarray(want[0]), atol=1e-5)
    np.testing.assert_allclose(outs["a"] + outs["b"], outs["all"], atol=1e-6)
    assert np.abs(outs["a"]).max() > 1e-4 and np.abs(outs["b"]).max() > 1e-4


@pytest.mark.parametrize("route", ["plain", "grouped"])
@pytest.mark.parametrize("case", ["all_to_one", "one_gets_none",
                                  "none_held"])
def test_nothing_is_dropped(rng, monkeypatch, route, case):
    """Every token that chose a held expert gets its term, however uneven
    the load: all tokens on one expert (far past any capacity), a held
    expert with no token (an empty group), no token for any held expert.
    The grouped route runs under the Pallas interpreter at tiles of 16
    rows, at lane-sized widths."""
    if route == "grouped":
        monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
        monkeypatch.setattr(pk, "MOE_TILE_ROWS", 16)
        x, gate, w = _expert_layer(rng, tokens=72, width=128, hidden=256)
    else:
        x, gate, w = _expert_layer(rng)
    n = len(x)
    expert = {"all_to_one": np.full(n, 2),
              "one_gets_none": np.where(np.arange(n) % 4 == 1, 0,
                                        np.arange(n) % 4),
              "none_held": 4 + np.arange(n) % 4}[case]
    before = catalog.MOE_LOWERED.value(route=route)

    def loss(x, gate, *w):
        return jnp.sum(_share(x, expert, gate, w, 0, 4) ** 2)

    got = np.asarray(_share(x, expert, gate, w, 0, 4))
    want = np.where((expert < 4)[:, None], _whole(x, expert, gate, w), 0.0)
    np.testing.assert_allclose(got, want, atol=2e-4)
    assert catalog.MOE_LOWERED.value(route=route) == before   # no gradient
    grads = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(
        jnp.asarray(x), jnp.asarray(gate), *(jnp.asarray(a) for a in w))
    assert catalog.MOE_LOWERED.value(route=route) == before + 1
    assert catalog.MOE_EXPERTS_HELD.value() == 4
    assert catalog.MOE_EXPERTS_ROUTED.value() == ROUTED
    monkeypatch.setenv("MXTPU_PALLAS", "0")           # the plain form's
    plain = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(
        jnp.asarray(x), jnp.asarray(gate), *(jnp.asarray(a) for a in w))
    for g, p in zip(grads, plain):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(np.asarray(g), np.asarray(p), atol=1e-3,
                                   rtol=1e-4)
    if case == "none_held":
        assert all(float(jnp.abs(g).max()) == 0 for g in grads)
    if case == "one_gets_none":     # expert 1's weights get a zero gradient
        assert all(float(jnp.abs(g[1]).max()) == 0 for g in grads[2:])


def test_sorted_tokens_fill_whole_tiles(rng):
    """The plan of the grouped route: every held token has one row, every
    row one token or none, a group's rows are consecutive tiles, an empty
    group keeps one tile, the tiles behind the last group count to it."""
    expert = np.array([5, 0, 2, 2, 7, 2, 0, 2, 2, 9, 2, 2, 0, 2], np.int32)
    pos, src, group = (np.asarray(a) for a in ops_nn._moe_sorted(
        jnp.asarray(expert), 0, 4, 4))
    assert len(src) == (4 + 4) * 4
    held = expert < 4
    assert (pos[~held] == len(src)).all()
    assert sorted(src[pos[held]]) == sorted(np.flatnonzero(held))
    assert (src[pos[held]] == np.flatnonzero(held)).all()
    assert (np.sort(pos[held]) == np.flatnonzero(src < len(expert))).all()
    assert list(group) == [0, 1, 2, 2, 3, 3, 3, 3]     # the rest to the last
    assert (group[pos[held] // 4] == expert[held]).all()


# --------------------------------------------------- (c) the router
def test_router_is_float32_and_mixes_the_state(rng):
    """bfloat16 inputs, float32 outputs at the highest precision: the state
    equals the float64 product of the SAME rounded inputs to 1e-6; the mix
    adds the layer before's state; the gate is the softmax's largest."""
    op = get_op("_contrib_moe_router").fn
    x = jnp.asarray(rng.randn(2, 9, 64), jnp.bfloat16)
    wd = jnp.asarray(0.1 * rng.randn(32, 64), jnp.bfloat16)
    bd = jnp.asarray(0.1 * rng.randn(32), jnp.bfloat16)
    w1, w2 = (jnp.asarray(0.3 * rng.randn(32, 32), jnp.bfloat16)
              for _ in range(2))
    w3 = jnp.asarray(0.3 * rng.randn(ROUTED, 32), jnp.bfloat16)
    expert, gate, state = op(x, wd, bd, w1, w2, w3)
    assert (expert.dtype, gate.dtype, state.dtype) == \
        (jnp.int32, jnp.float32, jnp.float32)
    f64 = lambda a: np.asarray(a.astype(jnp.float32), np.float64)  # noqa: E731
    want = f64(x) @ f64(wd).T + f64(bd)
    np.testing.assert_allclose(np.asarray(state), want, atol=2e-6)
    assert ((np.asarray(gate) >= 1.0 / ROUTED - 1e-6)
            & (np.asarray(gate) <= 1.0)).all()
    prev = jnp.asarray(rng.randn(2, 9, 32), jnp.float32)
    mix = jnp.asarray(rng.randn(32), jnp.bfloat16)
    _e, _g, mixed = op(x, wd, bd, w1, w2, w3, prev, mix)
    np.testing.assert_allclose(np.asarray(mixed),
                               want + f64(mix) * np.asarray(prev), atol=4e-6)
    with pytest.raises(mx.base.MXNetError):
        op(x, wd, bd, w1, w2, w3, prev)


def test_one_top1_choice(rng):
    """``parallel.expert_parallel`` chooses by the ops' ``top1``."""
    from mxnet_tpu.parallel import expert_parallel
    x = jnp.asarray(rng.randn(20, 8), jnp.float32)
    w = jnp.asarray(rng.randn(8, 5), jnp.float32)
    idx, gate = expert_parallel.top1_gate(x, w)
    want_idx, want = ops_nn.top1(jax.nn.softmax(x @ w, axis=-1))
    assert (np.asarray(idx) == np.asarray(want_idx)).all()
    np.testing.assert_allclose(np.asarray(gate), np.asarray(want), rtol=1e-6)


# ----------------------------------------------- (d) the attention block
def _attention(rng, **kw):
    attn = tfm.CompressedLatentAttention(
        64, 4, 2, 16, rotary_theta=5e6, rotary_dim=8, epsilon=1e-5, **kw)
    attn.initialize(mx.init.Normal(0.3))
    for name, p in attn.collect_params().items():
        if name.endswith("bias"):
            p.set_data(mx.nd.array(0.3 * rng.randn(*p.shape)))
    return attn


def test_convolutions_and_value_shift_are_causal(rng):
    """Changing token t leaves every position before t unchanged, through
    the two convolutions (kernels 2 and 3: reach 3 back), the value shift
    and the attention; position t itself and later ones move."""
    attn = _attention(rng, conv_kernels=(2, 3))
    x = rng.randn(2, 12, 64).astype(np.float32)
    base = attn(mx.nd.array(x)).asnumpy()
    x2 = x.copy()
    x2[:, 7] += 1.0
    moved = attn(mx.nd.array(x2)).asnumpy()
    np.testing.assert_array_equal(moved[:, :7], base[:, :7])
    assert np.abs(moved[:, 7:] - base[:, 7:]).min(axis=-1).min() > 1e-6
    # the pieces alone: conv(x)_t reads t-k+1..t; the second value head
    # reads t-1
    conv = attn._causal(mx.nd, attn.conv2, mx.nd.array(
        rng.randn(1, 6, 96).astype(np.float32)), 3)
    assert conv.shape == (1, 6, 96)


def test_value_head_one_sees_the_token_before(rng):
    """With queries and keys silenced (zero temperature: uniform causal
    attention), the output is the running mean of v, whose second head is
    the first head's projection one position late."""
    attn = _attention(rng)
    params = attn.collect_params()
    for name, p in params.items():
        if name.endswith("temperature"):
            p.set_data(mx.nd.zeros(p.shape))
        if name.endswith("value1_weight"):
            p.set_data(params[name.replace("value1", "value0")].data())
        if name.endswith("proj_weight"):
            p.set_data(mx.nd.array(np.eye(64, dtype=np.float32)))
    x = rng.randn(1, 10, 64).astype(np.float32)
    out = attn(mx.nd.array(x)).asnumpy().reshape(1, 10, 4, 16)
    steps = np.arange(1, 11)[None, :, None]
    v0 = np.cumsum(x @ params[[n for n in params if n.endswith(
        "value0_weight")][0]].data().asnumpy().T, axis=1) / steps
    np.testing.assert_allclose(out[:, :, 0], v0, atol=1e-5)   # kv head 0
    np.testing.assert_allclose(out[:, :, 1], v0, atol=1e-5)
    late = np.concatenate([np.zeros((1, 1, 16)), v0[:, :-1] * (
        steps[:, :-1] / steps[:, 1:])], axis=1)
    np.testing.assert_allclose(out[:, :, 2], late, atol=1e-5)  # kv head 1
    np.testing.assert_allclose(out[:, :, 3], late, atol=1e-5)


def test_grouped_heads_equal_the_repeated_head_form(rng, ref, cell_cfg):
    """Two key/value heads serving two query heads each give what four
    key/value heads do when each pair holds the same keys and values: the
    reference's attention sub-layer, whose ``_attention`` takes repeated
    heads, equals the block."""
    cfg = small_cfg(cell_cfg)
    attn = _attention(rng)
    params = [jnp.asarray(p.data().asnumpy())
              for p in attn.collect_params().values()]
    x = rng.randn(2, 12, 64).astype(np.float32)
    # temperature, q, k, v0, v1, conv1 w b, conv2 w b, proj -> the
    # reference's sub-layer leaves with a unit norm and a unit join in front
    f32 = jnp.float32
    leaves = [jnp.ones(64, f32)] + params + [jnp.ones(64, f32),
                                             jnp.zeros(64, f32)]
    want = ref._attention_sublayer(cfg, lambda t: t, jnp.asarray(x), leaves)
    norm = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5)
    got = x + attn(mx.nd.array(norm)).asnumpy()
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5)


def test_partial_rotary_leaves_the_other_channels_alone(rng, ref):
    """Channels 8-15 of 16 pass; channels 0-7 are the half-rotation over 8
    with angles theta**(-2i/8); the whole-axis form is what it was."""
    op = get_op("_contrib_rotary_embedding").fn
    x = rng.randn(2, 3, 9, 16).astype(np.float32)
    out = np.asarray(op(jnp.asarray(x), theta=5e6, rotary_dim=8))
    np.testing.assert_array_equal(out[..., 8:], x[..., 8:])
    np.testing.assert_allclose(out, np.asarray(ref._rope(x, 5e6, 8)),
                               atol=1e-6)
    assert np.abs(out[..., 1:, :8] - x[..., 1:, :8]).max() > 1e-2
    np.testing.assert_allclose(
        np.asarray(op(jnp.asarray(x), theta=1e4, rotary_dim=16)),
        np.asarray(op(jnp.asarray(x), theta=1e4)), atol=0)
    with pytest.raises(mx.base.MXNetError):
        op(jnp.asarray(x), rotary_dim=18)


def test_rms_norm_without_a_gain(rng):
    op = get_op("RMSNorm").fn
    x = jnp.asarray(rng.randn(3, 5, 16), jnp.bfloat16)
    out = op(x, eps=1e-5, no_gain=True)
    assert out.dtype == jnp.bfloat16
    xf = np.asarray(x.astype(jnp.float32))
    np.testing.assert_allclose(
        np.asarray(out.astype(jnp.float32)),
        xf / np.sqrt((xf ** 2).mean(-1, keepdims=True) + 1e-5), rtol=1e-2)
    np.testing.assert_array_equal(
        np.asarray(op(x, jnp.ones(16, jnp.bfloat16), eps=1e-5)),
        np.asarray(out))


# --------------------------------- (e) what the other cells' graphs keep
def _parent_rms_norm(data, gamma, axis=-1, eps=1e-6):
    """The op as the parent commit had it."""
    from jax import lax
    ax = int(axis) % data.ndim
    xf = data.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(jnp.square(xf), axis=ax, keepdims=True)
                    + jnp.float32(eps))
    g = ops_nn._per_channel(gamma.astype(jnp.float32), ax, data.ndim)
    return (xf * inv * g).astype(data.dtype)


def _parent_rotary(data, theta=10000.0):
    t, d = data.shape[-2], data.shape[-1]
    half = d // 2
    inv_freq = jnp.float32(theta) ** (
        -jnp.arange(half, dtype=jnp.float32) * jnp.float32(2.0 / d))
    pos = jnp.arange(t, dtype=jnp.float32)
    angle = pos[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    xf = data.astype(jnp.float32)
    out = xf * jnp.concatenate([cos, cos], axis=-1) \
        + jnp.roll(xf, half, axis=-1) * jnp.concatenate([-sin, sin], axis=-1)
    return out.astype(data.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_ops_the_looped_decoder_uses_trace_to_the_parents_text(dtype):
    """The two ops this model gave a new attribute lower, without it, to the
    jaxpr they did: ``ouro_2_6b.train``'s step is the parent's."""
    x = jax.ShapeDtypeStruct((1, 4, 32, 16), dtype)
    g = jax.ShapeDtypeStruct((16,), dtype)
    text = lambda f, *a: str(jax.make_jaxpr(f)(*a))  # noqa: E731
    assert text(lambda a, b: get_op("RMSNorm").fn(a, b, eps=1e-6), x, g) == \
        text(lambda a, b: _parent_rms_norm(a, b, eps=1e-6), x, g)
    assert text(lambda a: get_op("_contrib_rotary_embedding").fn(
        a, theta=1e6), x) == text(lambda a: _parent_rotary(a, theta=1e6), x)


def test_one_dimensional_convolutions_bypass_the_stem_lowering(rng):
    """Depthwise and grouped 1-D convolutions over (B, T, C), stride 1 or 2,
    few channels or many: none is a stem, the counter stays, and each equals
    ``lax.conv_general_dilated``; a ResNet stem still takes the lowering."""
    op = get_op("Convolution").fn
    before = catalog.CONV_S2D_LOWERED.value()
    for c, groups, stride in ((96, 96, 1), (96, 6, 1), (4, 1, 2), (4, 4, 2)):
        x = jnp.asarray(rng.randn(2, 12, c), jnp.float32)
        w = jnp.asarray(rng.randn(c, 2, c // groups), jnp.float32)
        got = op(x, w, kernel=(2,), stride=(stride,), pad=(1,), num_filter=c,
                 num_group=groups, no_bias=True, layout="NWC")
        want = jax.lax.conv_general_dilated(
            x, w, (stride,), [(1, 1)], feature_group_count=groups,
            dimension_numbers=("NWC", "OWI", "NWC"))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)
    assert catalog.CONV_S2D_LOWERED.value() == before
    stem = jnp.asarray(rng.randn(2, 16, 16, 3), jnp.float32)
    op(stem, jnp.asarray(rng.randn(8, 7, 7, 3), jnp.float32), kernel=(7, 7),
       stride=(2, 2), pad=(3, 3), num_filter=8, no_bias=True, layout="NHWC")
    assert catalog.CONV_S2D_LOWERED.value() == before + 1


# ------------------------------------------ (f) the trainer's normal path
def test_step_is_segmented_and_counts_its_expert_layers(ref, cell_cfg, mix):
    """A capture of the two-layer model: one recomputed segment a layer, one
    differentiated expert layer each (the plain route on the CPU), the tied
    embedding and a second output handed to the loss, no compile after the
    first step."""
    cfg = small_cfg(cell_cfg)
    net, trainer, _mesh, _t = program(cfg, ref)
    (x, y), = batches(cfg, mix, 5, 1)
    seg = catalog.REMAT_SEGMENTS.value()
    plain = catalog.MOE_LOWERED.value(route="plain")
    grouped = catalog.MOE_LOWERED.value(route="grouped")
    first = float(trainer.step(ids(x), ids(y)))
    assert catalog.REMAT_SEGMENTS.value() - seg == cfg["num_hidden_layers"]
    assert catalog.MOE_LOWERED.value(route="plain") - plain == \
        cfg["num_hidden_layers"]
    assert catalog.MOE_LOWERED.value(route="grouped") == grouped
    assert catalog.LOSS_INPUTS.value() == 2
    from mxnet_tpu.observability import jit_hooks
    compiled = jit_hooks.JIT_COMPILES.value()
    assert float(trainer.step(ids(x), ids(y))) < first
    assert jit_hooks.JIT_COMPILES.value() == compiled


def test_bfloat16_step_runs_and_routes_in_float32(ref, cell_cfg, mix):
    """The cell's compute type at the small size: losses stay within a few
    percent of the float32 reference over three steps, and the share of
    tokens whose expert differs from the reference's choice is small."""
    cfg = dict(small_cfg(cell_cfg), compute_dtype="bfloat16")
    net, trainer, _mesh, _t = program(cfg, ref)
    leaves = seeded_leaves(cfg, ref)
    data = batches(cfg, mix, 5, 3)
    step = follow.make_step(lambda lv, x, y: ref.loss_fn(cfg, lv, x, y),
                            [True] * len(leaves), cfg["optimizer"])
    state, cur = step[0](leaves), leaves
    for x, y in data:
        cur, state, want = step[1](cur, state, x, y)
        got = float(trainer.step(ids(x), ids(y)))
        assert abs(got - float(want)) < 0.03 * float(want)


# ------------------------------------------------ (g) the configuration
def test_required_flops_equal_the_count_by_hand(ref, cell_cfg):
    """Per token, forward multiply-adds: attention projections and
    convolutions 5.57 M, scores and values 8.39 M, router 0.66 M, the held
    half of the experts 6.29 M a layer; the head 67.1 M; x 6."""
    projections = 2048 * (1024 + 256 + 128 + 128) + 1024 * 2048
    convolutions = 1280 * 2 + 1280 * 128 * 2
    scores = 8 * (128 + 128) * (8192 + 1) / 2
    router = 2048 * 256 + 2 * 256 * 256 + 256 * 16
    experts = 3 * 2048 * 2048 * 1 * (8 / 16)
    layer = projections + convolutions + scores + router + experts
    assert abs(projections + convolutions - 5.57e6) < 5e3
    assert abs(layer - 20.9e6) < 5e4
    want = 6 * (4 * layer + 2048 * 32784)
    assert ref.train_flops_per_item(cell_cfg) == want
    assert abs(want - 0.905e9) < 1e6
    assert abs(want * 2 * 8192 - 14.8e12) < 0.05e12


def test_configuration_keeps_the_published_sizes(cell_cfg):
    """Every number of the catalog's entry under the same key, but the three
    cuts, which are listed with the published values and the deployment."""
    import json
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    published = [r for r in rows if r["name"] == "ZAYA1-8B"][0]
    assert cell_cfg["source"] == published["source_url"]
    cut = {"num_hidden_layers": 4, "num_experts": 8, "vocab_size": 32784}
    for key, value in published["config"].items():
        assert cell_cfg[key] == cut.get(key, value), key
    assert cell_cfg["reduced"] == sorted(cut, key=list(cut).index)
    assert cell_cfg["published"] == {k: published["config"][k] for k in cut}
    assert cell_cfg["deployment"]["chips_sharing_a_layer"] == 2
    assert cell_cfg["vocab_held"] * 8 == 262272
    kw = cell_cfg["builder_kwargs"]
    assert (kw["units"], kw["hidden_size"], kw["num_heads"],
            kw["num_kv_heads"], kw["head_dim"], kw["num_experts"],
            kw["router_hidden"], kw["experts_held"], kw["rotary_dim"]) == \
        (2048, 2048, 8, 2, 128, 16, 256, 8, 64)
    assert sum(int(np.prod(s)) for _k, s, _t in harness.load_module(
        os.path.join(REPO, "chipbench", "reference", "zaya_decoder_lm.py"),
        "reference_zaya_count").leaf_specs(cell_cfg)) == 494788360


def test_fault_leaves_out_a_row(ref, cell_cfg, mix):
    cfg = small_cfg(cell_cfg)
    leaves = seeded_leaves(cfg, ref)
    (x, y), = batches(cfg, mix, 5, 1)
    whole = float(ref.loss_fn(cfg, leaves, x, y)[0])
    part = float(ref.loss_fn(cfg, leaves, x, y, rows=slice(0, 1))[0])
    alone = float(ref.loss_fn(cfg, leaves, x[:1], y[:1])[0])
    assert part == alone and part != whole
