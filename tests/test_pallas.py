"""Pallas kernel suite — runs the SAME kernels the TPU path uses, under the
Pallas interpreter on the CPU test mesh (MXTPU_PALLAS_INTERPRET=1), checked
against the pure-jnp reference path and jax autodiff.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.parallel.ring_attention import local_attention, ring_attention


@pytest.fixture
def interp(monkeypatch):
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    yield


def _naive_attn(q, k, v, causal=False, scale=None):
    d = q.shape[-1]
    sc = scale if scale is not None else 1.0 / (d ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * sc
    if causal:
        qpos = jnp.arange(q.shape[2])
        kpos = jnp.arange(k.shape[2])
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
    return jax.nn.softmax(s, axis=-1) @ v


def test_flash_attention_interpret_matches_naive(rng, interp):
    q = jnp.asarray(rng.randn(2, 2, 16, 128).astype("float32"))
    k = jnp.asarray(rng.randn(2, 2, 16, 128).astype("float32"))
    v = jnp.asarray(rng.randn(2, 2, 16, 128).astype("float32"))
    assert pk.use_pallas()
    out = pk.flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_naive_attn(q, k, v)),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_causal_multiblock(rng, interp):
    # T > 128 forces multiple k blocks through the online-softmax scratch path
    q = jnp.asarray(rng.randn(1, 2, 160, 128).astype("float32"))
    k = jnp.asarray(rng.randn(1, 2, 160, 128).astype("float32"))
    v = jnp.asarray(rng.randn(1, 2, 160, 128).astype("float32"))
    out = pk.flash_attention(q, k, v, causal=True)
    ref = _naive_attn(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_grad_matches_naive(rng):
    # jnp fallback path (no interpret env) — custom blockwise VJP vs autodiff
    q = jnp.asarray(rng.randn(1, 2, 24, 16).astype("float32"))
    k = jnp.asarray(rng.randn(1, 2, 24, 16).astype("float32"))
    v = jnp.asarray(rng.randn(1, 2, 24, 16).astype("float32"))

    def loss_flash(q, k, v):
        return jnp.sum(pk.flash_attention(q, k, v, causal=True) ** 2)

    def loss_naive(q, k, v):
        return jnp.sum(_naive_attn(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_flash_attention_grad_interpret(rng, interp):
    q = jnp.asarray(rng.randn(1, 1, 16, 128).astype("float32"))
    k = jnp.asarray(rng.randn(1, 1, 16, 128).astype("float32"))
    v = jnp.asarray(rng.randn(1, 1, 16, 128).astype("float32"))

    def loss_flash(q, k, v):
        return jnp.sum(pk.flash_attention(q, k, v) ** 2)

    def loss_naive(q, k, v):
        return jnp.sum(_naive_attn(q, k, v) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


# (BH, Tq, Tk, causal, q_offset, k_offset), with 512-row blocks
FORWARD_BLOCKS = [
    (1, 2048, 2048, True, 0, 0),       # 6 full, 4 masked, 6 skipped
    (1, 2048, 2048, False, 0, 0),      # 16 full
    (2, 520, 520, True, 0, 0),         # ragged: every computed block masked
    (1, 1000, 1000, True, 0, 0),
    (1, 1000, 1000, False, 0, 0),
    (1, 1024, 1536, True, 0, 600),     # q block 0 sees no key: whole rows
    (1, 1536, 1024, True, 512, 0),     # queries later than keys
]
# the row held whole where the key axis tiles; the grid over key blocks
# (what a row too long for VMEM takes) everywhere
FORWARD_ROUTES = [(c, r) for c in FORWARD_BLOCKS
                  for r in (["row", "grid"] if c[2] % 512 == 0 else ["grid"])]


@pytest.mark.parametrize("traced", [False, True], ids=["ints", "traced"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case,route", FORWARD_ROUTES, ids=[
    "-".join(map(str, c + (r,))) for c, r in FORWARD_ROUTES])
def test_flash_forward_blocks_equal_the_plain_form(rng, interp, monkeypatch,
                                                   case, route, dtype,
                                                   traced):
    """The forward kernel's block paths (the row's keys held in VMEM and
    the key blocks a q block sees looped over in the kernel; or the grid
    over every key block, those above the diagonal naming the last
    computed one), with offsets that are Python ints and traced under
    ``jit``, against ``_fa_reference`` in float32: ``out`` and ``lse``,
    rows that see no key at all included (``lse`` at the mask's value,
    ``out`` 0)."""
    if route == "grid":
        monkeypatch.setattr(pk, "_FA_KV_ROW_BYTES", 0)
    bh, tq, tk, causal, q_off, k_off = case
    q = jnp.asarray(rng.randn(bh, tq, 128), dtype)
    k, v = (jnp.asarray(rng.randn(bh, tk, 128), dtype) for _ in range(2))
    assert pk._fa_whole_row(k, 512) == (route == "row")
    scale = 128 ** -0.5
    if traced:
        got = jax.jit(lambda q, k, v, a, b: pk._fa_pallas(
            q, k, v, scale, causal, a, b))(q, k, v, jnp.int32(q_off),
                                           jnp.int32(k_off))
    else:
        got = pk._fa_pallas(q, k, v, scale, causal, q_off, k_off)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    with jax.default_matmul_precision("highest"):
        want = pk._fa_reference(*f32, scale, causal, q_off, k_off)
    blind = (np.arange(tq) + q_off < k_off) if causal else np.zeros(tq, bool)
    assert np.all(np.asarray(want[1])[:, blind] == pk._NEG_INF)
    assert np.all(np.asarray(got[1])[:, blind] == pk._NEG_INF)
    assert not np.any(np.asarray(got[0], np.float32)[:, blind])
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if dtype == jnp.float32:
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
        else:
            assert np.max(np.abs(a - b)) < 3e-2 * np.max(np.abs(b))


def test_flash_attention_grad_through_the_block_paths(rng, interp):
    """The gradient through ``_flash_core`` where the forward holds a full,
    a skipped and two masked blocks (1,024 rows of 512-row blocks)."""
    q, k, v = (jnp.asarray(rng.randn(1, 1, 1024, 128), jnp.float32)
               for _ in range(3))

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v, causal=True) ** 2)

    got = jax.grad(loss(pk.flash_attention), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(_naive_attn), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_forward_counts_its_blocks_by_kind(interp):
    """One differentiated trace at T = 2,048 (nq = 4 blocks of 512) counts
    nq(nq-1)/2 full, nq masked and nq(nq-1)/2 skipped blocks a head; so
    does a trace of ``flash_attention_with_lse`` with offsets that are
    Python ints; with traced offsets, or a plain forward trace, none."""
    from mxnet_tpu.observability import catalog
    bh, nq = 2, 4
    q = jnp.zeros((1, bh, 2048, 128), jnp.float32)
    kinds = ("full", "masked", "skipped")

    def counted(fn, *args):
        before = [catalog.FLASH_ATTENTION_FWD_BLOCKS.value(kind=k)
                  for k in kinds]
        jax.make_jaxpr(fn)(*args)
        return tuple(catalog.FLASH_ATTENTION_FWD_BLOCKS.value(kind=k) - b
                     for k, b in zip(kinds, before))

    each = (nq * (nq - 1) // 2, nq, nq * (nq - 1) // 2)
    want = tuple(bh * n for n in each)
    loss = lambda q: jnp.sum(pk.flash_attention(q, q, q, causal=True))  # noqa: E731
    assert counted(jax.grad(loss), q) == want
    assert counted(lambda q: pk.flash_attention_with_lse(
        q, q, q, causal=True), q) == want
    assert counted(lambda q, a, b: pk.flash_attention_with_lse(
        q, q, q, causal=True, q_offset=a, k_offset=b),
        q, jnp.int32(0), jnp.int32(0)) == (0, 0, 0)
    assert counted(lambda q: pk.flash_attention(q, q, q, causal=True),
                   q) == (0, 0, 0)


# 128: one class block; 2176 = 2048 + 128: two blocks with a ragged tail
@pytest.mark.parametrize("classes", [128, 2176])
def test_softmax_cross_entropy_interpret(rng, interp, classes):
    logits = jnp.asarray(rng.randn(16, classes).astype("float32"))
    labels = jnp.asarray(rng.randint(0, classes, size=16).astype("int32"))
    loss = pk.softmax_cross_entropy(logits, labels)
    ref = -jax.nn.log_softmax(logits, axis=1)[jnp.arange(16), labels]
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_softmax_cross_entropy_grad(rng):
    logits = jnp.asarray(rng.randn(8, 12).astype("float32"))
    labels = jnp.asarray(rng.randint(0, 12, size=8).astype("int32"))

    g = jax.grad(lambda x: jnp.sum(pk.softmax_cross_entropy(x, labels)))(logits)
    ref = jax.grad(lambda x: -jnp.sum(
        jax.nn.log_softmax(x, axis=1)[jnp.arange(8), labels]))(logits)
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_nd_softmax_cross_entropy_op(rng):
    import mxnet_tpu as mx
    x = rng.randn(6, 10).astype("float32")
    y = rng.randint(0, 10, size=6).astype("float32")
    out = mx.nd.softmax_cross_entropy(mx.nd.array(x), mx.nd.array(y))
    ref = -np.asarray(jax.nn.log_softmax(jnp.asarray(x), axis=1))[
        np.arange(6), y.astype(int)].sum()
    assert out.shape == (1,)
    np.testing.assert_allclose(out.asnumpy()[0], ref, rtol=1e-5)


def test_nd_contrib_flash_attention(rng):
    import mxnet_tpu as mx
    q = rng.randn(1, 2, 8, 16).astype("float32")
    k = rng.randn(1, 2, 8, 16).astype("float32")
    v = rng.randn(1, 2, 8, 16).astype("float32")
    out = mx.nd.contrib.flash_attention(mx.nd.array(q), mx.nd.array(k),
                                        mx.nd.array(v), causal=True)
    ref = _naive_attn(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                      causal=True)
    np.testing.assert_allclose(out.asnumpy(), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_grad_matches_local(rng):
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("sp",))
    B, H, T, D = 1, 2, 32, 8
    q = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
    k = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
    v = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, axis="sp",
                                      causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_naive_attn(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


def test_ring_attention_interpret_pallas(rng, interp):
    # full ring path with the Pallas kernel as the per-step partial
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:2]).reshape(2)
    mesh = Mesh(devs, ("sp",))
    B, H, T, D = 1, 1, 32, 128
    q = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
    k = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
    v = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
    out = ring_attention(q, k, v, mesh, axis="sp", causal=True)
    ref = _naive_attn(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
