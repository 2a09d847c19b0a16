"""The attention backward as a Pallas kernel (``flash_attention_bwd``: dk
and dv a key block, dq summed in VMEM for the whole row), run under the
Pallas interpreter
(MXTPU_PALLAS_INTERPRET=1) and held to ``jax.grad`` of the plain form
``_fa_reference``: at small tiles (many blocks, the diagonal crossing some of
them, blocks above it skipped), at the forward's own tiles through the public
entry point, with offsets, ragged lengths and a key/value head shared by
four query heads; and the rule that routes the backward: the kernels exactly
where the forward ran its kernel, the blockwise scan elsewhere.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.observability import catalog
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.registry import KEPT_IN_SEGMENT


@pytest.fixture
def interp(monkeypatch):
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")


def _reference_grads(q, k, v, g, scale, causal, q_offset=0, k_offset=0):
    """dq, dk, dv of the plain form in float32 at the highest precision."""
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    with jax.default_matmul_precision("highest"):
        _, vjp = jax.vjp(lambda *a: pk._fa_reference(
            *a, scale, causal, q_offset, k_offset)[0], *f32)
        return vjp(g.astype(jnp.float32))


def _close(got, want, dtype):
    """The forward's tolerances: float32 as its interpreter tests, bfloat16
    as chip_smoke.py holds the kernels at that type."""
    for a, b in zip(got, want):
        assert a.dtype == dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if dtype == jnp.float32:
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
        else:
            assert np.max(np.abs(a - b)) < 3e-2 * np.max(np.abs(b))


# (BH, Tq, Tk, block_q, block_k, causal, q_offset, k_offset)
SMALL_TILES = [
    (2, 64, 64, 16, 16, True, 0, 0),       # 4 x 4 blocks, 6 skipped
    (3, 72, 72, 16, 32, True, 0, 0),       # ragged both ways, blocks unequal
    (2, 40, 56, 16, 16, False, 0, 0),      # no mask, ragged, Tq != Tk
    (2, 48, 48, 16, 16, True, 16, 0),      # queries later than keys
    (2, 48, 64, 16, 16, True, 0, 24),      # rows that see no key at all
    (1, 96, 32, 32, 16, True, 64, 32),     # a ring step's offsets
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", SMALL_TILES,
                         ids=["-".join(map(str, c)) for c in SMALL_TILES])
def test_kernels_at_small_tiles_equal_the_plain_gradient(rng, interp, case,
                                                         dtype):
    bh, tq, tk, bq, bk, causal, q_off, k_off = case
    q, g = (jnp.asarray(rng.randn(bh, tq, 128), dtype) for _ in range(2))
    k, v = (jnp.asarray(rng.randn(bh, tk, 128), dtype) for _ in range(2))
    scale = 128 ** -0.5
    out, lse = pk._fa_reference(q, k, v, scale, causal, q_off, k_off)
    got = pk._fa_bwd_pallas(q, k, v, out, lse, g, scale, causal, q_off,
                            k_off, block_q=bq, block_k=bk)
    _close(got, _reference_grads(q, k, v, g, scale, causal, q_off, k_off),
           dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("t", [24, 1040], ids=["one-block", "ragged-3-blocks"])
def test_public_gradient_takes_the_kernels(rng, interp, t, causal, dtype):
    """``flash_attention``'s gradient at the kernels' own tiles (512 rows
    forward, 1,024 backward): a length of one block and one of 8 x 130
    (three and two blocks, the last ragged); the backward counts one
    ``pallas`` trace."""
    q, k, v, w = (jnp.asarray(rng.randn(1, 2, t, 128), dtype)
                  for _ in range(4))
    loss = lambda *a: jnp.sum(  # noqa: E731
        (pk.flash_attention(*a, causal=causal) * w).astype(jnp.float32))
    before = catalog.FLASH_ATTENTION_BWD_LOWERED.value(route="pallas")
    got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert catalog.FLASH_ATTENTION_BWD_LOWERED.value(route="pallas") \
        == before + 1
    flat = [x.reshape(2, t, 128) for x in (q, k, v, w)]
    want = _reference_grads(*flat, 128 ** -0.5, causal)
    _close([x.reshape(2, t, 128) for x in got], want, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_shared_key_value_heads_sum_back(rng, interp, dtype):
    """Two key/value heads broadcast to eight query heads in front of the
    op, as ``CompressedLatentAttention`` does: the kernel's dk and dv of the
    eight are summed back to the two by the broadcast's own gradient."""
    q, w = (jnp.asarray(rng.randn(1, 8, 40, 128), dtype) for _ in range(2))
    k, v = (jnp.asarray(rng.randn(1, 2, 40, 128), dtype) for _ in range(2))
    wide = lambda x: jnp.repeat(x, 4, axis=1)  # noqa: E731

    def loss(attend, q, k, v):
        return jnp.sum((attend(q, wide(k), wide(v)) * w).astype(jnp.float32))

    got = jax.grad(functools.partial(loss, lambda *a: pk.flash_attention(
        *a, causal=True)), argnums=(0, 1, 2))(q, k, v)

    def plain(q, k, v):
        return pk._fa_reference(q[0], k[0], v[0], 128 ** -0.5, True, 0,
                                0)[0][None]

    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    with jax.default_matmul_precision("highest"):
        want = jax.grad(functools.partial(loss, plain),
                        argnums=(0, 1, 2))(*f32)
    assert got[1].shape == got[2].shape == (1, 2, 40, 128)
    _close(got, want, dtype)


def test_a_shape_the_forward_does_not_tile_keeps_the_scan(rng, interp):
    """Head size 64 is no multiple of 128: the forward is the plain form and
    the backward the blockwise scan, as before, counted as ``scan``; an
    inference trace counts nothing."""
    q, k, v = (jnp.asarray(rng.randn(1, 2, 16, 64), jnp.float32)
               for _ in range(3))
    loss = lambda *a: jnp.sum(pk.flash_attention(*a, causal=True) ** 2)  # noqa: E731
    counts = lambda: (  # noqa: E731
        catalog.FLASH_ATTENTION_BWD_LOWERED.value(route="scan"),
        catalog.FLASH_ATTENTION_BWD_LOWERED.value(route="pallas"))
    before = counts()
    pk.flash_attention(q, k, v, causal=True)
    assert counts() == before
    got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert counts() == (before[0] + 1, before[1])
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))
    assert "pallas_call" not in text and "scan" in text
    want = jax.grad(lambda *a: jnp.sum(pk._fa_reference(
        *[x.reshape(2, 16, 64) for x in a], 64 ** -0.5, True, 0, 0)[0] ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_a_row_whose_dq_does_not_fit_keeps_the_scan(rng, interp,
                                                    monkeypatch):
    """The kernel sums a row's dq in VMEM; where that does not fit, a shape
    the forward tiles keeps the scan, counted as ``scan``."""
    q = jnp.asarray(rng.randn(1, 2, 16, 128), jnp.float32)
    loss = lambda q: jnp.sum(pk.flash_attention(q, q, q, causal=True))  # noqa: E731
    monkeypatch.setattr(pk, "_FA_BWD_ROW_BYTES", 16 * 128 * 12 - 1)
    before = catalog.FLASH_ATTENTION_BWD_LOWERED.value(route="scan")
    text = str(jax.make_jaxpr(jax.grad(loss))(q))
    assert catalog.FLASH_ATTENTION_BWD_LOWERED.value(route="scan") \
        == before + 1
    assert re.findall(r"name=(flash_attention_\w+)", text) \
        == ["flash_attention_fwd"]


def test_kernel_false_keeps_the_scan(rng, interp):
    """An op that cannot see how its batch is split keeps the forward off
    the kernel (``kernel=False``), and so the backward too."""
    q = jnp.asarray(rng.randn(1, 2, 16, 128), jnp.float32)
    text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
        pk.flash_attention(q, q, q, causal=True, kernel=False))))(q))
    assert "pallas_call" not in text


def test_a_segment_runs_no_forward_kernel_in_its_backward(rng, interp):
    """Under ``jax.checkpoint`` with the segments' policy the gradient holds
    ONE forward kernel (its ``out`` and log-sum-exp are kept) and the
    backward kernel, and equals the unwrapped call's."""
    q, k, v = (jnp.asarray(rng.randn(1, 2, 32, 128), jnp.float32)
               for _ in range(3))
    loss = lambda *a: jnp.sum(pk.flash_attention(*a, causal=True) ** 2)  # noqa: E731
    kept = jax.checkpoint(loss, policy=jax.checkpoint_policies
                          .save_only_these_names(KEPT_IN_SEGMENT))
    text = str(jax.make_jaxpr(jax.grad(kept, argnums=(0, 1, 2)))(q, k, v))
    names = re.findall(r"name=(flash_attention_\w+)", text)
    assert sorted(names) == ["flash_attention_bwd", "flash_attention_fwd"]
    got = jax.grad(kept, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
