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
