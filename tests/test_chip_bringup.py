"""Chip bring-up guards — what CPU tests could not see until the code met
the TPU's own compiler and a one-chip host.

* Mosaic compiles, for a DESCRIBED v5e (no chip attached; the
  ``on-chip-measurement`` guide's third rehearsal), of both Pallas kernels at
  the widths ``chip_smoke.py`` runs and of an ``rtc.PallasModule`` kernel —
  with ``mxnet_tpu`` imported, so the package's ``jax_enable_x64`` default is
  on, which is what used to make every one of them fail to legalize. Skipped
  where the topology cannot be described.
* the device rules: one compile-cache directory, no accelerator context
  served by the host, the server's default device, interpret mode only on
  request, and the trainer's state against the net's on a one-device mesh.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import pallas_kernels as pk

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------- compiles for the chip
@pytest.fixture(scope="module")
def four_chips():
    """The devices of a described v5e 2x2 host."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no libtpu here, or it cannot describe a v5e
        pytest.skip("TPU topology cannot be described: %r" % (e,))
    return topo.devices


@pytest.fixture(scope="module")
def one_chip(four_chips):
    """Sharding on one device of that host."""
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(four_chips[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *specs):
    assert jax.config.jax_enable_x64, "the package default must be on"
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    assert "f64[" not in text
    return text


@pytest.mark.parametrize("shape,dtype", [
    ((16, 2048, 128), jnp.bfloat16),
    ((16, 2048, 128), jnp.float32),
    ((8, 4096, 128), jnp.bfloat16),
])
def test_flash_attention_kernel_compiles_for_v5e(one_chip, no_compile_cache,
                                                 shape, dtype):
    s = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    _compile(lambda q, k, v: pk._fa_pallas(q, k, v, 0.088, True, 0, 0),
             s, s, s)


def test_flash_attention_grad_compiles_for_v5e(one_chip, no_compile_cache,
                                               monkeypatch):
    """The public entry point, forward kernel plus blockwise backward, at
    the smoke's shape (use_pallas asks the default backend, which is the CPU
    here: steered in the test, as the guide says)."""
    monkeypatch.setattr(pk, "use_pallas", lambda: True)
    s = jax.ShapeDtypeStruct((2, 8, 2048, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        out = pk.flash_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32))

    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), s, s, s)


@pytest.mark.parametrize("shape,dtype", [
    ((256, 1024), jnp.float32),
    ((4096, 10240), jnp.float32),     # 20.25 MiB of scoped VMEM before tiling
    ((4096, 32768), jnp.bfloat16),    # 32.25 MiB before tiling
    ((4096, 50304), jnp.bfloat16),    # ragged class tail (50304 % 2048 != 0)
])
def test_cross_entropy_kernel_compiles_for_v5e(one_chip, no_compile_cache,
                                               shape, dtype):
    s = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    _compile(pk._ce_lse_pallas, s)


@pytest.mark.parametrize("shape,layout,dtype", [
    ((512, 112, 112, 64), "NHWC", jnp.bfloat16),   # resnet34_v1.train's pool
    ((256, 112, 112, 64), "NHWC", jnp.bfloat16),   # resnet50_v1.train's
    ((128, 64, 56, 56), None, jnp.float32),        # NCHW, float32, 2x2/2
    ((128, 112, 112, 64), "NHWC", jnp.float32),    # 32-channel blocks: VMEM
])
def test_max_pool_backward_compiles_for_v5e(one_chip, no_compile_cache,
                                            monkeypatch, shape, layout, dtype):
    """The Pooling op's gradient at the cells' shapes, traced as a TPU process
    that names its one-device mesh traces it: the two kernels are in the
    program, ``select-and-scatter`` is not, and the transposes to the kernels'
    (H, W, C, N) order cost no copy of the activation (PR 28)."""
    from mxnet_tpu.ops import get_op
    pool = get_op("Pooling").fn
    monkeypatch.setattr(pk, "use_pallas", lambda: True)
    window = dict(kernel=(3, 3), stride=(2, 2), pad=(1, 1)) if layout \
        else dict(kernel=(2, 2), stride=(2, 2))
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x):
        y = pool(jnp.maximum(x, 0) * 2, pool_type="max", layout=layout,
                 **window)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    with jax.sharding.use_abstract_mesh(
            jax.sharding.AbstractMesh((1,), ("dp",))):
        text = _compile(jax.grad(loss), x)
    assert "select-and-scatter" not in text
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    entry = text[text.index("ENTRY"):]
    assert layout is None or " transpose(" not in entry \
        and " copy(" not in entry, \
        "the activation is relaid around the kernel"


def test_rtc_kernel_compiles_for_v5e(one_chip, no_compile_cache):
    """A user kernel written as Pallas users write them — Python ints in the
    index map — through ``rtc.PallasModule``."""
    from jax.experimental import pallas as pl

    def axpy(x_ref, y_ref, o_ref):
        o_ref[...] = 2.0 * x_ref[...] + y_ref[...]

    kernel = mx.rtc.PallasModule(axpy).get_kernel("axpy")
    s = jax.ShapeDtypeStruct((1024, 512), jnp.float32, sharding=one_chip)
    spec = pl.BlockSpec((256, 512), lambda i: (i, 0))
    fn = kernel._jitted([s, s], grid=(4,), in_specs=[spec, spec],
                        out_specs=spec, interpret=False)
    assert "tpu_custom_call" in fn.lower(s, s).compile().as_text()


def test_stem_weight_gradient_stays_space_to_depth_on_v5e(one_chip,
                                                          no_compile_cache):
    """The Convolution op's stem lowering at the cells' size: the chip's
    compiler keeps the weight gradient a convolution that yields the
    (64,4,7,6) rows-to-depth twin. Without the barrier in ``_conv_s2d`` it
    folds the traced rearrangement back into the strided (64,7,7,3) form
    (``rhs_dilate=2x2``), the 1.74 ms op the lowering is there to replace
    (PERF.md, PR 25)."""
    from mxnet_tpu.ops import get_op
    conv = get_op("Convolution").fn

    def loss(x, w, cot):
        y = conv(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), None,
                 kernel=(7, 7), stride=(2, 2), pad=(3, 3), num_filter=64,
                 no_bias=True, layout="NHWC")
        return jnp.sum((y * cot).astype(jnp.float32))

    specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
             for shape, dtype in (((256, 224, 224, 3), jnp.float32),
                                  ((64, 7, 7, 3), jnp.float32),
                                  ((256, 112, 112, 64), jnp.bfloat16))]
    grad = jax.jit(jax.grad(loss, argnums=1)).lower(*specs).compile()
    convs = [l for l in grad.as_text().splitlines() if " convolution(" in l]
    assert len(convs) == 1 and "bf16[64,4,7,6]" in convs[0].split("=")[1]
    # rows are stride 1 (the blocks), columns keep the stem's stride 2
    assert "rhs_dilate=1x2" in convs[0] and "pad=2_1x3_" in convs[0]
    assert tuple(grad.out_info.shape) == (64, 7, 7, 3)


@pytest.mark.parametrize("batch,chips", [(256, 1), (512, 1), (1024, 4)],
                         ids=["resnet50", "resnet34", "resnet50-dp4"])
def test_sunk_pool_leaves_no_full_size_pass_on_v5e(four_chips,
                                                   no_compile_cache,
                                                   monkeypatch, batch, chips):
    """The cells' stem with its pool sunk (``_MaxPoolBatchNorm``, PR 30) and
    one 3x3 convolution behind it, forward and backward at the cells' sizes:
    the stem map [N,112,112,64] is written by the stem convolution (its
    statistics in the epilogue), read by the pool's kernel as a bitcast,
    written by the scatter and read by the weight gradient. No loop fusion
    touches it: not BatchNorm's apply or the ReLU in front of the pool, not a
    product with the sign, not BatchNorm's backward reductions behind the
    scatter. The sign reaches the kernel as a column of 64. On a ``dp`` mesh
    of the host's four chips, as ``DataParallelTrainer`` names it, each chip
    holds that program on its own 256 rows and the whole column."""
    import re
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from mxnet_tpu.ops import get_op
    conv, bn_pool = get_op("Convolution").fn, get_op("_MaxPoolBatchNorm").fn
    monkeypatch.setattr(pk, "use_pallas", lambda: True)

    def loss(w, gamma, beta, w1, x, cot):
        y = conv(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), None,
                 kernel=(7, 7), stride=(2, 2), pad=(3, 3), num_filter=64,
                 no_bias=True, layout="NHWC")
        a, _, _ = bn_pool(y, gamma, beta, jnp.zeros(64), jnp.ones(64),
                          fix_gamma=False, axis=-1, pool_kernel=(3, 3),
                          pool_stride=(2, 2), pool_pad=(1, 1),
                          pool_layout="NHWC")
        z = conv(jnp.maximum(a, 0), w1.astype(jnp.bfloat16), None,
                 kernel=(3, 3), pad=(1, 1), num_filter=64, no_bias=True,
                 layout="NHWC")
        return jnp.sum((z * cot).astype(jnp.float32))

    mesh = Mesh(np.array(four_chips[:chips]), ("dp",))
    whole = NamedSharding(mesh, PartitionSpec())
    rows = NamedSharding(mesh, PartitionSpec("dp"))
    specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
             for shape, dtype, sharding in (
                 ((64, 7, 7, 3), jnp.float32, whole),
                 ((64,), jnp.float32, whole), ((64,), jnp.float32, whole),
                 ((64, 3, 3, 64), jnp.float32, whole),
                 ((batch, 224, 224, 3), jnp.float32, rows),
                 ((batch, 56, 56, 64), jnp.bfloat16, rows))]
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        text = _compile(jax.grad(loss, argnums=(0, 1, 2, 3)), *specs)
    entry = text[text.index("ENTRY"):]
    assert entry.count('custom_call_target="tpu_custom_call"') == 2
    assert "f32[64,1]" in entry, "the kernel takes no sign"
    assert ("all-reduce" in text) == (chips > 1)
    n = batch // chips
    full = re.compile(r"\[(%d,112,112,64|112,112,64,%d)\]" % (n, n))
    touching = [l for l in entry.splitlines()
                if full.search(l.split(", metadata=")[0])]
    assert touching
    for line in touching:
        assert " copy(" not in line and " transpose(" not in line, line
        if " fusion(" in line:
            assert "kind=kLoop" not in line and "kind=kInput" not in line, \
                "a full-size pass over the stem map: " + line[:200]


@pytest.mark.parametrize("chips,kernels", [(1, 2), (4, 1)],
                         ids=["one-chip", "dp4"])
def test_attention_and_cross_entropy_ops_under_a_named_mesh_on_v5e(
        four_chips, no_compile_cache, monkeypatch, chips, kernels):
    """The ops a decoder LM's step is made of, differentiated under the mesh
    ``DataParallelTrainer`` names: on one chip the attention forward and the
    cross-entropy's log-sum-exp are Mosaic kernels; on a ``dp`` mesh of four
    each chip runs the attention kernel on its own rows (``shard_map``; jit
    would refuse to partition it) and the log-sum-exp is XLA's."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from mxnet_tpu.ops import get_op
    monkeypatch.setattr(pk, "use_pallas", lambda: True)
    flash = get_op("_contrib_flash_attention").fn
    ce = get_op("softmax_cross_entropy").fn

    def loss(q, k, v, y):
        o = flash(q, k, v, causal=True)
        logits = o.reshape(o.shape[0], -1, 128)[:, :2048]
        return jnp.sum(ce(logits, y, per_row=True)) \
            + jnp.sum(o.astype(jnp.float32))

    mesh = Mesh(np.array(four_chips[:chips]), ("dp",))
    rows = NamedSharding(mesh, PartitionSpec("dp"))
    q = jax.ShapeDtypeStruct((4, 16, 1024, 128), jnp.bfloat16, sharding=rows)
    y = jax.ShapeDtypeStruct((4, 2048), jnp.int32, sharding=rows)
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, q, q, y)
    assert text.count('custom_call_target="tpu_custom_call"') == kernels


def test_looped_decoder_cell_step_fits_and_keeps_its_kernels_on_v5e(
        one_chip, no_compile_cache, monkeypatch):
    """The cell ``ouro_2_6b.train``'s own step (``harness.build_program``, one
    row of 4,096 tokens, Adam, bfloat16), compiled for a described v5e. Each
    of its 16 layer-calls is a recomputed segment that keeps its input, the
    products that do not widen (out-projection, down-projection) and the
    attention's output and log-sum-exp; norms, rotary, SiLU gates, residual
    adds and the widening products (qkv, gate, up) are recomputed; the 4
    exits are no segments. So the step takes 8 to 9.5 GB (8.64 by this
    compile; 7.98 GB when a segment kept its input alone; 12.1 GB with
    every product kept and 14.8 GB without segments, which with the net's
    own copy of the weights are both past the chip), its XLA products are
    under 36 TFLOP (35.0; 40.4 when every segment and every exit ran twice,
    30.4 with every product kept), every attention forward is the Pallas
    kernel, once (16 calls), and every exit's log-sum-exp too (4); no
    [4096, 49152] float32 buffer stands alone in
    the step: one exit's logits are bfloat16 and the label's column is found
    by an iota inside the fused gradient, not by a one-hot. Weights are
    zeros (shapes are all a compile reads)."""
    import re
    sys.path.insert(0, REPO)
    from chipbench import harness
    bench = harness.load_json(REPO, "BENCHMARK.json")
    _cell, cfg, _mix, _limits, ref = harness.find_cell(bench, "ouro_2_6b.train")
    monkeypatch.setattr(mx.init, "Xavier", mx.init.Zero)
    monkeypatch.setattr(ref, "init", lambda c, k: [
        jnp.zeros(shape, jnp.float32) for _k, shape, _t in ref.leaf_specs(c)])
    monkeypatch.setattr(pk, "use_pallas", lambda: True)
    net, trainer, _mesh, _t = harness.build_program(cfg, ref, 1, jax.devices()[:1])
    trainer._capture(2, sample_arrays=None)
    spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)  # noqa: E731
    state = jax.tree_util.tree_map(spec, (
        trainer._params, trainer._aux, trainer._opt_state, trainer._guard_state))
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((1, cfg["seq_len"]), jnp.int32, sharding=one_chip)
    step = jax.jit(trainer._step_fn.__wrapped__, donate_argnums=(0, 1, 2, 3))
    compiled = step.lower(*state, rng, ids, ids).compile()
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert 8.0e9 < peak < 9.5e9, peak
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    assert cost["flops"] < 36e12, cost["flops"]
    text = compiled.as_text()
    assert "f64[" not in text
    calls = cfg["num_hidden_layers"] * cfg["total_ut_steps"]
    assert text.count('custom_call_target="tpu_custom_call"') == \
        calls + cfg["total_ut_steps"]
    entry = text[text.index("ENTRY"):].split("\n", 1)[1]
    logits = re.compile(r"= (\w+)\[(?:1,)?%d,%d\]" % (
        cfg["seq_len"], cfg["vocab_held"]))
    kinds = [m.group(1) for m in map(logits.search, entry.splitlines()) if m]
    assert kinds and set(kinds) == {"bf16"}, sorted(set(kinds))


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_routed_experts_op_under_a_named_mesh_on_v5e(
        four_chips, no_compile_cache, monkeypatch, chips, dtype):
    """The expert layer of ``zaya1_8b.train`` at its widths (2,048 x 2,048,
    8 experts held of 16), differentiated under the mesh the trainer names:
    three grouped products forward, three transposed and three per-group
    outer products backward, all Mosaic kernels; on a ``dp`` mesh of four
    each chip sorts and multiplies its own rows (``shard_map``) against the
    whole held weights. float32 is what the capture's op-by-op forward
    compiles."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from mxnet_tpu.ops import get_op
    monkeypatch.setattr(pk, "use_pallas", lambda: True)
    experts = get_op("_contrib_moe_experts").fn

    def loss(x, gate, w_gate, w_up, w_down, expert):
        out = experts(x, expert, gate, w_gate, w_up, w_down, first_expert=0,
                      num_experts=16)
        return jnp.sum(out.astype(jnp.float32))

    mesh = Mesh(np.array(four_chips[:chips]), ("dp",))
    rows = NamedSharding(mesh, PartitionSpec("dp"))
    whole = NamedSharding(mesh, PartitionSpec())
    x = jax.ShapeDtypeStruct((4, 2048, 2048), dtype, sharding=rows)
    gate = jax.ShapeDtypeStruct((4, 2048), jnp.float32, sharding=rows)
    expert = jax.ShapeDtypeStruct((4, 2048), jnp.int32, sharding=rows)
    w = jax.ShapeDtypeStruct((8, 2048, 2048), dtype, sharding=whole)
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        text = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), x, gate, w,
                        w, w, expert)
    assert text.count('custom_call_target="tpu_custom_call"') == 9


def test_routed_experts_cell_step_fits_and_keeps_its_kernels_on_v5e(
        one_chip, no_compile_cache, monkeypatch):
    """The cell ``zaya1_8b.train``'s own step (``harness.build_program``, two
    rows of 8,192 tokens, SGD momentum, bfloat16), compiled for a described
    v5e. Each of its 4 layers is a recomputed segment; every expert layer
    takes the grouped route (4 differentiated traces) and every attention
    forward the Pallas kernel. The step takes 8.5 to 10.5 GB, of which 3.96
    GB are its arguments; its Pallas calls are the 4 attention forwards and
    36 grouped products: 12 forward (a segment keeps their results: its
    backward pass runs none of them again), 12 for the tokens' gradient and
    12 for the weights'. The logits are bfloat16 and no float32 buffer of their
    size stands in the step. Weights are zeros (shapes are all a compile
    reads)."""
    import re
    sys.path.insert(0, REPO)
    from chipbench import harness
    from mxnet_tpu.observability import catalog
    bench = harness.load_json(REPO, "BENCHMARK.json")
    _cell, cfg, _mix, _limits, ref = harness.find_cell(bench, "zaya1_8b.train")
    monkeypatch.setattr(mx.init, "Xavier", mx.init.Zero)
    monkeypatch.setattr(ref, "init", lambda c, k: [
        jnp.zeros(shape, jnp.float32) for _k, shape, _t in ref.leaf_specs(c)])
    monkeypatch.setattr(pk, "use_pallas", lambda: True)
    net, trainer, _mesh, _t = harness.build_program(cfg, ref, 1, jax.devices()[:1])
    grouped = catalog.MOE_LOWERED.value(route="grouped")
    trainer._capture(2, sample_arrays=None)
    spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)  # noqa: E731
    state = jax.tree_util.tree_map(spec, (
        trainer._params, trainer._aux, trainer._opt_state, trainer._guard_state))
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((cfg["batch_per_chip"], cfg["seq_len"]),
                               jnp.int32, sharding=one_chip)
    step = jax.jit(trainer._step_fn.__wrapped__, donate_argnums=(0, 1, 2, 3))
    compiled = step.lower(*state, rng, ids, ids).compile()
    assert catalog.MOE_LOWERED.value(route="grouped") - grouped == \
        cfg["num_hidden_layers"]
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert 8.5e9 < peak < 10.5e9, peak
    text = compiled.as_text()
    assert "f64[" not in text
    layers = cfg["num_hidden_layers"]
    assert text.count('custom_call_target="tpu_custom_call"') == layers * 10
    for name, calls in (("moe_gmm_t", 3), ("moe_gmm_dw", 3)):
        assert len(re.findall(r"%%(?:jvp_)?%s[_.\d]* = " % name, text)) \
            == calls * layers, name
    entry = text[text.index("ENTRY"):].split("\n", 1)[1]
    logits = re.compile(r"= (\w+)\[(?:\d,)?%d,%d\]" % (
        cfg["batch_per_chip"] * cfg["seq_len"], cfg["vocab_held"]))
    kinds = [m.group(1) for m in map(logits.search, entry.splitlines()) if m]
    assert kinds and set(kinds) == {"bf16"}, sorted(set(kinds))


def test_rtc_does_not_choose_interpret_mode(monkeypatch):
    """No chip and no request for the interpreter: the launch fails, it does
    not quietly run the kernel on the host."""
    monkeypatch.delenv("MXTPU_PALLAS_INTERPRET", raising=False)

    def twice(x_ref, o_ref):
        o_ref[...] = 2.0 * x_ref[...]

    kernel = mx.rtc.PallasModule(twice).get_kernel("twice")
    x = mx.nd.ones((8, 128))
    with pytest.raises(Exception, match="(?i)interpret|cpu"):
        kernel.launch([x]).asnumpy()
    np.testing.assert_allclose(
        kernel.launch([x], interpret=True).asnumpy(), 2.0)


# ------------------------------------------------------ compile-cache rule
def test_compile_cache_honours_env(monkeypatch, tmp_path):
    from mxnet_tpu import base
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert base.enable_compile_cache() == str(tmp_path)
    # no directory is set in code when the variable names one
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_path_in_checkout(monkeypatch):
    from mxnet_tpu import base
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = base.enable_compile_cache()
        second = base.enable_compile_cache()
        assert first == second == base.compile_cache_dir() \
            == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_no_cache_directory_named_outside_the_helper():
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("tests", "chiprun_out", "__pycache__")]
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path, errors="replace") as f:
                    if "jax_compilation_cache_dir" in f.read():
                        hits.append(os.path.relpath(path, REPO))
    assert hits == [os.path.join("mxnet_tpu", "base.py")], hits


# ------------------------------------------------------------ device rules
@pytest.mark.parametrize("ctx", [mx.tpu(0), mx.gpu(0)], ids=str)
def test_accelerator_context_without_accelerator_raises(ctx):
    assert mx.num_tpus() == 0          # the CPU test mesh
    with pytest.raises(RuntimeError, match="no accelerator"):
        ctx.jax_device()
    with pytest.raises(RuntimeError, match="no accelerator"):
        mx.nd.zeros((2,), ctx=ctx)
    assert mx.current_context() == mx.cpu(0)


@pytest.mark.parametrize("ctx,explicit,want", [
    (mx.cpu(0), {}, (1, 0)),
    (mx.cpu(3), {}, (1, 3)),                   # follows current_context()
    (mx.tpu(2), {}, (2, 2)),                   # ... the chip when it is one
    (mx.tpu(2), {"dev_type": 1}, (1, 0)),      # explicit CPU stays CPU
    (mx.cpu(0), {"dev_type": 2, "dev_id": 1}, (2, 1)),
], ids=["cpu0", "cpu3", "tpu2", "explicit-cpu", "explicit-accel"])
def test_model_config_default_device_follows_context(ctx, explicit, want):
    from mxnet_tpu.serving import ModelConfig, load as sload
    sym_json, pbytes, feat, _ = sload.tiny_model()
    with ctx:
        cfg = ModelConfig("m", sym_json, pbytes, feature_shape=feat,
                          buckets=(1, 2), **explicit)
    assert (cfg.dev_type, cfg.dev_id) == want


def test_predictor_arrays_live_on_its_context():
    """The executor runs where its arrays are: all of them on the asked
    device, not on whatever current_context() happens to be."""
    from mxnet_tpu.serving import ModelConfig, ModelServer, load as sload
    sym_json, pbytes, feat, ref = sload.tiny_model()
    dev = jax.devices()[3]
    cfg = ModelConfig("m", sym_json, pbytes, feature_shape=feat,
                      buckets=(1, 2), dev_type=1, dev_id=3)
    with ModelServer([cfg], drain_on_preemption=False) as srv:
        x = np.arange(4, dtype="float32")
        np.testing.assert_allclose(srv.predict("m", x, timeout=30), ref(x),
                                   rtol=1e-5)
        assert srv.stats("m")["device"] == str(dev)
        pred = srv._models["m"].cache.get(1)
        for arr in list(pred._args.values()) + pred._outputs:
            assert arr._data.devices() == {dev}


def test_module_with_several_contexts_says_it_uses_one(caplog):
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(data, num_hidden=3),
                               name="softmax")
    mod = mx.mod.Module(net, context=[mx.cpu(0), mx.cpu(1)])
    with caplog.at_level("WARNING", logger="mxnet_tpu"):
        mod.bind(data_shapes=[("data", (4, 5))],
                 label_shapes=[("softmax_label", (4,))])
    assert "one executor on cpu(0)" in caplog.text


def test_module_binds_a_stride2_stem_under_the_default_passes():
    """What train_imagenet.py builds. Under the default passes the layout
    pass hands the stem to the Convolution op channel-last (Module never
    re-homes, so a transpose sits between the weight and its conv) and the
    op lowers it through space-to-depth itself; the parameter keeps the
    shape the symbol declares."""
    from mxnet_tpu.observability import catalog
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=8, kernel=(7, 7), stride=(2, 2),
                             pad=(3, 3), no_bias=True, name="conv0")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, global_pool=True, pool_type="avg",
                         kernel=(1, 1))
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=4, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    batch = mx.io.DataBatch(
        [mx.nd.array(np.random.RandomState(0).rand(2, 3, 32, 32))],
        [mx.nd.zeros((2,))])
    outs, params = [], None
    for passes in (None, False):
        mod = mx.mod.Module(net, context=mx.cpu(), passes=passes)
        mod.bind(data_shapes=[("data", (2, 3, 32, 32))],
                 label_shapes=[("softmax_label", (2,))], for_training=False)
        if params is None:
            mx.random.seed(0)
            mod.init_params(mx.init.Xavier())
            params = mod.get_params()
        else:
            mod.set_params(*params)
        assert params[0]["conv0_weight"].shape == (8, 3, 7, 7)
        lowered = catalog.CONV_S2D_LOWERED.value()
        mod.forward(batch, is_train=False)
        # the op lowers the stem only where the layout pass made it NHWC
        assert (catalog.CONV_S2D_LOWERED.value() > lowered) is (passes is None)
        outs.append(mod.get_outputs()[0].asnumpy())
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-6)


# --------------------------------- trainer state against the net's own
def _tiny_trainer(n_devices):
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon import nn
    mx.random.seed(0)
    net = nn.HybridSequential(prefix="bringup%d_" % n_devices)
    with net.name_scope():
        net.add(nn.Dense(8, in_units=4), nn.BatchNorm(in_channels=8),
                nn.Dense(3, in_units=8))
    net.initialize(mx.init.Xavier())
    mesh = parallel.local_mesh("dp", devices=jax.devices()[:n_devices])
    trainer = parallel.DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.05, "momentum": 0.9}, mesh=mesh)
    x = np.random.RandomState(0).rand(4, 4).astype("float32")
    y = np.array([0, 1, 2, 0], "float32")
    return net, trainer, x, y


@pytest.mark.parametrize("n_devices", [1, 4])
def test_trainer_step_compiles_once(n_devices):
    """State is placed on the mesh at capture; before, step one saw it on
    the net's device, jit saw another type at step two, and the whole step
    was traced and compiled twice."""
    _, trainer, x, y = _tiny_trainer(n_devices)
    float(trainer.step(x, y))
    size = trainer._step_fn._cache_size()
    for _ in range(2):
        float(trainer.step(x, y))
    assert size == trainer._step_fn._cache_size() == 1


@pytest.mark.parametrize("n_devices", [1, 4])
def test_trainer_never_donates_the_nets_buffers(n_devices):
    """The step donates its state. Placing an array on a mesh that holds its
    device aliases it, so the trainer keeps copies: the net's arrays stay
    readable after a step, and after step / sync_to_net / step."""
    net, trainer, x, y = _tiny_trainer(n_devices)
    before = {p.name: p.data().asnumpy()
              for p in net.collect_params().values()}
    float(trainer.step(x, y))
    for p in net.collect_params().values():
        np.testing.assert_array_equal(p.data().asnumpy(), before[p.name])
    trainer.sync_to_net()
    float(trainer.step(x, y))
    after = {p.name: p.data().asnumpy()
             for p in net.collect_params().values()}
    assert any(not np.array_equal(after[k], before[k]) for k in before)


# ------------------------------------------------------------ the smoke
def test_chip_smoke_refuses_the_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120, env=env)
    assert p.returncode != 0, p.stdout + p.stderr
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
