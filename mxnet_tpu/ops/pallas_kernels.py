"""Hand-written Pallas TPU kernels for the hot paths XLA doesn't fuse itself.

This is the TPU-native analogue of the reference's hand-tuned CUDA kernels
(e.g. ``src/operator/nn/softmax-inl.h``, the fused ``cudnn_rnn-inl.h`` path,
and the NVRTC escape hatch ``src/common/rtc.cc``): where the reference drops
to CUDA for ops the framework's codegen can't produce efficiently, we drop to
Pallas for ops XLA can't fuse well — chiefly blockwise (flash) attention,
whose online-softmax accumulation pattern defeats XLA fusion and would
otherwise materialize the T×T score matrix in HBM.

Kernels:
* ``flash_attention``      — O(T·block) memory attention, fwd in Pallas with a
                             per-row log-sum-exp side output; bwd is a
                             blockwise ``lax.scan`` (recompute, never holds a
                             full T×T block). Used by ``parallel.ring_attention``
                             as the per-ring-step partial, and exposed as
                             ``mx.nd.contrib.flash_attention``.
* ``softmax_cross_entropy`` — row-fused logsumexp - logit[label], no
                             materialized softmax; grad is the classic
                             ``softmax - onehot`` (fused by XLA, the label's
                             column found by an iota, no one-hot built).

Gating: Pallas compiles only on TPU. ``use_pallas()`` is True on a TPU
backend (override off with ``MXTPU_PALLAS=0``); on CPU the same kernels run
under the Pallas interpreter when ``MXTPU_PALLAS_INTERPRET=1`` (the unit-test
path — tests/conftest.py pins the CPU backend), else a pure-jnp reference
path runs. All three paths share one numerics contract and one test suite.

The package turns ``jax_enable_x64`` on, under which a bare Python ``0`` in a
BlockSpec index map traces as i64 and Mosaic refuses the kernel. Every
integer an index map returns is therefore spelled ``_I0`` / built from the
i32 grid indices; tests/test_chip_bringup.py compiles both kernels for the
described v5e with the package imported.
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .registry import KEPT_IN_SEGMENT

__all__ = ["flash_attention", "flash_attention_with_lse",
           "flash_forward_tiles",
           "softmax_cross_entropy", "max_pool_fwd", "max_pool_bwd",
           "moe_gmm", "moe_tgmm", "moe_gmm_eligible", "use_pallas"]

_NEG_INF = -1e30  # avoid actual -inf inside kernels (exp/max corner cases)
_I0 = np.int32(0)  # index-map zero: a Python 0 is i64 under jax_enable_x64


def _interpret() -> bool:
    return os.environ.get("MXTPU_PALLAS_INTERPRET", "0") == "1"


def pallas_off() -> bool:
    return os.environ.get("MXTPU_PALLAS", "1") == "0"


def use_pallas() -> bool:
    """Whether the Pallas kernel path is active for the current backend."""
    if pallas_off():
        return False
    if _interpret():
        return True
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def _fa_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
               acc_ref, m_ref, l_ref, *, scale, causal, block_q, block_k,
               nk_total, tk_total):
    """Grid (BH, nQ, nK); k is the innermost (sequential) axis.

    Scratch (acc, m, l) carries the online-softmax state across k iterations
    for one (bh, q-block); at the final k step the normalized output and the
    row log-sum-exp are written out.
    """
    iq, ik = pl.program_id(1), pl.program_id(2)
    # Mosaic can't legalize f64 constants: pin every python-float scalar to f32
    scale = jnp.float32(scale)
    neg_inf = jnp.float32(_NEG_INF)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, neg_inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    # global positions: q_offset/k_offset arrive via SMEM (they are traced
    # values in the ring-attention loop, so they can't be python ints baked
    # into the kernel). A key block that lies wholly past this query block's
    # last position is all mask: it changes nothing and is not computed
    q0 = iq * block_q + offs_ref[0]
    k0 = ik * block_k + offs_ref[1]
    seen = (k0 <= q0 + (block_q - 1)) if causal else True

    @pl.when(seen)
    def _block():
        # the products run in the operands' own type (bfloat16 at the MXU's
        # full rate), accumulated in float32; the softmax is float32
        q, k, v = q_ref[0], k_ref[0], v_ref[0]           # (bq, D), (bk, D) x 2
        # zero the ragged tail (padded block rows may hold garbage/NaN)
        krow = lax.broadcasted_iota(jnp.int32, v.shape, 0) + ik * block_k
        v = jnp.where(krow < tk_total, v, jnp.zeros_like(v))
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        # mask ragged tail of the key axis (grid pads the last block)
        k_idx = lax.broadcasted_iota(jnp.int32, s.shape, 1) + ik * block_k
        s = jnp.where(k_idx < tk_total, s, neg_inf)
        if causal:
            qpos = lax.broadcasted_iota(jnp.int32, s.shape, 0) + q0
            kpos = lax.broadcasted_iota(jnp.int32, s.shape, 1) + k0
            s = jnp.where(qpos >= kpos, s, neg_inf)

        m_prev = m_ref[...]                              # (bq, 128)
        blk_max = jnp.max(s, axis=1)[:, None]            # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(blk_max, m_prev.shape))
        p = jnp.exp(s - m_new[:, :1])                    # (bq, bk)
        p = jnp.where(s <= neg_inf / 2, jnp.float32(0.0), p)
        corr = jnp.exp(m_prev[:, :1] - m_new[:, :1])     # (bq, 1)
        l_ref[...] = l_ref[...] * jnp.broadcast_to(corr, l_ref.shape) \
            + jnp.broadcast_to(jnp.sum(p, axis=1)[:, None], l_ref.shape)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk_total - 1)
    def _finalize():
        l = l_ref[...][:, :1]                            # (bq, 1)
        safe_l = jnp.maximum(l, jnp.float32(1e-30))
        o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)
        m = m_ref[...][:, :1]
        lse = jnp.where(l <= jnp.float32(0.0), neg_inf, m + jnp.log(safe_l))
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _vma_kw(x):
    """Propagate shard_map varying-axes type onto pallas out_shape (jax vma)."""
    vma = jax.typeof(x).vma
    return {"vma": vma} if vma else {}


def _fa_pallas(q, k, v, scale, causal, q_offset, k_offset,
               block_q=512, block_k=512):
    """q,k,v: (BH, T, D) → (out (BH,Tq,D), lse (BH,Tq)) via pallas_call.
    Blocks of 512 x 512 scores (1 MiB in float32): at 128 x 128 the grid's
    own steps, 16,384 a call at (16, 4096, 128), were most of the kernel's
    9.4 ms (PERF.md, PR 31)."""
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)
    nq, nk = pl.cdiv(Tq, block_q), pl.cdiv(Tk, block_k)
    offs = jnp.asarray([q_offset, k_offset], jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,   # offs (q/k global offsets) land in SMEM
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, iq, ik, offs: (b, iq, _I0)),
            pl.BlockSpec((1, block_k, D), lambda b, iq, ik, offs: (b, ik, _I0)),
            pl.BlockSpec((1, block_k, D), lambda b, iq, ik, offs: (b, ik, _I0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, iq, ik, offs: (b, iq, _I0)),
            pl.BlockSpec((1, block_q, 128),
                         lambda b, iq, ik, offs: (b, iq, _I0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        functools.partial(_fa_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk_total=nk,
                          tk_total=Tk),
        grid_spec=grid_spec,
        name="flash_attention_fwd",   # the custom call's in a device trace
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, D), q.dtype, **_vma_kw(q)),
            jax.ShapeDtypeStruct((BH, Tq, 128), jnp.float32, **_vma_kw(q)),
        ],
        interpret=_interpret(),
    )(offs, q, k, v)
    return out, lse[:, :, 0]


def _fa_reference(q, k, v, scale, causal, q_offset, k_offset):
    """Pure-jnp path (CPU fallback); same (out, lse) contract."""
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * scale
    if causal:
        qpos = jnp.arange(q.shape[1]) + q_offset
        kpos = jnp.arange(k.shape[1]) + k_offset
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(s <= _NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = (p @ v.astype(jnp.float32)) / jnp.maximum(l, 1e-30)
    lse = jnp.where(l[..., 0] <= 0.0, _NEG_INF, m[..., 0] + jnp.log(
        jnp.maximum(l[..., 0], 1e-30)))
    return out.astype(q.dtype), lse


def flash_forward_tiles(q, k) -> bool:
    """The tiling rule: the Pallas kernel takes head dimensions that are a
    multiple of 128 and sequence lengths that are a multiple of 8, where
    Pallas runs at all; every other (..., T, D) shape takes the jnp
    reference (same numerics, T x T scores in HBM). Callers that must know
    which ran check the lowered text for ``tpu_custom_call``, as
    chip_smoke.py does, or read ``mxtpu_flash_attention_lowered_total``."""
    tile_ok = q.shape[-1] % 128 == 0 and q.shape[-2] % 8 == 0 \
        and k.shape[-2] % 8 == 0
    # the pallas *interpreter* can't run inside a vma-checked shard_map
    # (dynamic_slice varying-axes mismatch, jax#...); the compiled TPU path can
    interp_in_manual = _interpret() and bool(_vma_kw(q))
    return use_pallas() and tile_ok and not interp_in_manual


def _fa_fwd_dispatch(q, k, v, scale, causal, q_offset, k_offset, kernel=True):
    """(out, lse) by the Pallas kernel where the shape tiles
    (``flash_forward_tiles``) and the caller does not keep it off
    (``kernel=False``: an op that cannot see how its batch is split)."""
    if kernel and flash_forward_tiles(q, k):
        return _fa_pallas(q, k, v, scale, causal, q_offset, k_offset)
    return _fa_reference(q, k, v, scale, causal, q_offset, k_offset)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_core(q, k, v, scale, causal, q_offset, k_offset, block_k, kernel):
    out, _ = _fa_fwd_dispatch(q, k, v, scale, causal, q_offset, k_offset,
                              kernel)
    return out


def _flash_core_fwd(q, k, v, scale, causal, q_offset, k_offset, block_k,
                    kernel):
    out, lse = _fa_fwd_dispatch(q, k, v, scale, causal, q_offset, k_offset,
                                kernel)
    # named for a recomputed segment's policy (executor._GraphLowering): with
    # both kept, its backward pass does not run the forward kernel again.
    # ``lse`` is no output of the op, so only this rule can name it; outside
    # a ``jax.checkpoint`` a name is an identity that lowers to nothing
    out = checkpoint_name(out, KEPT_IN_SEGMENT)
    lse = checkpoint_name(lse, KEPT_IN_SEGMENT)
    return out, (q, k, v, out, lse)


def flash_attention_bwd(q, k, v, out, lse, g, scale, causal,
                        q_offset=0, k_offset=0, block_k=128):
    """Blockwise (flash) backward: scan over k blocks, O(T·block_k) memory.

    Standard recompute form: D = rowsum(dO∘O); per k-block
    p = exp(q·kᵀ·scale − lse); dv += pᵀ·dO; dp = dO·vᵀ;
    ds = p∘(dp − D)·scale; dq += ds·k; dk = dsᵀ·q.

    Shapes (BH, T, D); offsets may be traced scalars (the ring-attention
    backward calls this per ring step with rotating k/v shards). Returns
    (dq, dk, dv) in float32.
    """
    BH, Tq, Dh = q.shape
    Tk = k.shape[1]
    bk = min(block_k, Tk)
    nblk = -(-Tk // bk)
    pad = nblk * bk - Tk
    qf = q.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    delta = jnp.sum(g32 * out.astype(jnp.float32), axis=-1)   # (BH, Tq)

    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0))).astype(jnp.float32)
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0))).astype(jnp.float32)
    kb = kp.reshape(BH, nblk, bk, Dh).transpose(1, 0, 2, 3)
    vb = vp.reshape(BH, nblk, bk, Dh).transpose(1, 0, 2, 3)

    qpos = jnp.arange(Tq) + q_offset

    def body(dq, blk):
        i, kblk, vblk = blk
        s = jnp.einsum("bqd,bkd->bqk", qf, kblk) * scale
        kpos = jnp.arange(bk) + i * bk + k_offset
        valid = (jnp.arange(bk) + i * bk) < Tk
        mask = valid[None, :]
        if causal:
            mask = mask & (qpos[:, None] >= kpos[None, :])
        p = jnp.exp(s - lse[..., None])
        p = jnp.where(mask[None], p, 0.0)
        dv = jnp.einsum("bqk,bqd->bkd", p, g32)
        dp = jnp.einsum("bqd,bkd->bqk", g32, vblk)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bqk,bkd->bqd", ds, kblk)
        dk = jnp.einsum("bqk,bqd->bkd", ds, qf)
        return dq, (dk, dv)

    dq0 = jnp.zeros_like(qf)
    dq, (dks, dvs) = lax.scan(body, dq0,
                              (jnp.arange(nblk), kb, vb))
    dk = dks.transpose(1, 0, 2, 3).reshape(BH, nblk * bk, Dh)[:, :Tk]
    dv = dvs.transpose(1, 0, 2, 3).reshape(BH, nblk * bk, Dh)[:, :Tk]
    return dq, dk, dv


def _flash_core_bwd(scale, causal, q_offset, k_offset, block_k, kernel,
                    res, g):
    q, k, v, out, lse = res
    dq, dk, dv = flash_attention_bwd(q, k, v, out, lse, g, scale, causal,
                                     q_offset, k_offset, block_k)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    q_offset: int = 0, k_offset: int = 0,
                    kernel: bool = True):
    """Memory-efficient attention. q,k,v: (B, H, T, D) → (B, H, Tq, D).

    Differentiable (custom VJP, blockwise backward). On TPU the forward is a
    Pallas kernel where the shape tiles (D % 128 == 0, T % 8 == 0, see
    ``flash_forward_tiles``) and ``kernel`` is left on; elsewhere a jnp
    reference path with identical numerics.
    """
    B, H, Tq, Dh = q.shape
    sc = scale if scale is not None else 1.0 / (Dh ** 0.5)
    qf = q.reshape(B * H, Tq, Dh)
    kf = k.reshape(B * H, k.shape[2], Dh)
    vf = v.reshape(B * H, v.shape[2], Dh)
    out = _flash_core(qf, kf, vf, sc, causal, q_offset, k_offset, 128,
                      bool(kernel))
    return out.reshape(B, H, Tq, Dh)


def flash_attention_with_lse(q, k, v, causal: bool = False,
                             scale: Optional[float] = None,
                             q_offset=0, k_offset=0
                             ) -> Tuple[jax.Array, jax.Array]:
    """(out, lse) partial-attention primitive for ring attention merging.

    Not differentiable through the Pallas path directly — ring attention
    wraps the whole ring loop in its own VJP-friendly formulation, and this
    fwd-only primitive is used inside ``lax.fori_loop`` where the per-step
    K/V blocks rotate. lse has shape (B, H, Tq).
    """
    B, H, Tq, Dh = q.shape
    sc = scale if scale is not None else 1.0 / (Dh ** 0.5)
    out, lse = _fa_fwd_dispatch(q.reshape(B * H, Tq, Dh),
                                k.reshape(B * H, k.shape[2], Dh),
                                v.reshape(B * H, v.shape[2], Dh),
                                sc, causal, q_offset, k_offset)
    return out.reshape(B, H, Tq, Dh), lse.reshape(B, H, Tq)


# ---------------------------------------------------------------------------
# fused softmax cross-entropy
# ---------------------------------------------------------------------------

_CE_BLOCK_N = 256    # rows per block
_CE_BLOCK_C = 2048   # classes per block: a (256, 2048) f32 working tile is
#                      2 MiB, so the double-buffered input plus the f32
#                      temporaries stay well inside the 16 MiB scoped-VMEM
#                      limit at any vocabulary width


def _ce_kernel(logits_ref, lse_ref, m_ref, l_ref, *, block_c, n_classes,
               nc_total):
    """Grid (nN, nC); the class axis is innermost (sequential). Scratch
    (m, l) carries the running row max / rescaled exp-sum across class
    blocks; the row log-sum-exp is written at the last class step.

    Labels stay OUTSIDE the kernel: a (bn, 1) int32 tile is a shape Mosaic
    may refuse to legalize, and the label gather is a cheap XLA gather the
    compiler fuses with the subtraction anyway. Only the reduction that
    would otherwise materialize softmax lives here."""
    ic = pl.program_id(1)
    neg_inf = jnp.float32(_NEG_INF)

    @pl.when(ic == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, neg_inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    x = logits_ref[...].astype(jnp.float32)              # (bn, bc)
    if n_classes % block_c:
        # mask the ragged tail of the class axis (grid pads the last block)
        col = lax.broadcasted_iota(jnp.int32, x.shape, 1) + ic * block_c
        x = jnp.where(col < n_classes, x, neg_inf)
    m_prev = m_ref[...]                                  # (bn, 128)
    m_new = jnp.maximum(m_prev, jnp.max(x, axis=1, keepdims=True))
    l_ref[...] = l_ref[...] * jnp.exp(m_prev - m_new) \
        + jnp.sum(jnp.exp(x - m_new[:, :1]), axis=1, keepdims=True)
    m_ref[...] = m_new

    @pl.when(ic == nc_total - 1)
    def _finalize():
        lse_ref[...] = m_ref[...] + jnp.log(l_ref[...])


def _ce_lse_pallas(logits):
    """Row log-sum-exp of (N, C) logits via pallas_call → (N,) float32."""
    N, C = logits.shape
    bn, bc = min(_CE_BLOCK_N, N), min(_CE_BLOCK_C, C)
    nc = pl.cdiv(C, bc)
    return pl.pallas_call(
        functools.partial(_ce_kernel, block_c=bc, n_classes=C, nc_total=nc),
        name="cross_entropy_lse",   # the custom call's in a device trace
        grid=(pl.cdiv(N, bn), nc),
        in_specs=[pl.BlockSpec((bn, bc), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bn, 128), lambda i, j: (i, _I0)),
        out_shape=jax.ShapeDtypeStruct((N, 128), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, 128), jnp.float32),
                        pltpu.VMEM((bn, 128), jnp.float32)],
        interpret=_interpret(),
    )(logits)[:, 0]


def softmax_cross_entropy(logits, labels, kernel: bool = True):
    """Per-row CE: logsumexp(logits) − logits[label]. logits (N,C), labels (N,).

    Fused in one Pallas kernel on TPU (no materialized softmax) unless the
    caller keeps it off (``kernel=False``: an op that cannot see how its rows
    are split over devices); the gradient is the classic
    ``(softmax − onehot) · g`` in one pass from the saved row log-sum-exp,
    which XLA fuses on its own.
    """
    return _ce_rows(logits, labels, bool(kernel))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _ce_rows(logits, labels, kernel):
    return _ce_fwd(logits, labels, kernel)[0]


def _ce_fwd(logits, labels, kernel):
    N, C = logits.shape
    labels = labels.astype(jnp.int32)
    if kernel and use_pallas() and C % 128 == 0 and N % 8 == 0:
        lse = _ce_lse_pallas(logits)
    else:
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=1)
    picked = jnp.take_along_axis(
        logits, labels[:, None], axis=1)[:, 0].astype(jnp.float32)
    return lse - picked, (logits, labels, lse)


def _ce_bwd(kernel, res, g):
    # the label's column by comparison with an iota, inside the one fused
    # pass over the logits: no (N, C) one-hot is ever built
    logits, labels, lse = res
    p = jnp.exp(logits.astype(jnp.float32) - lse[:, None])
    col = lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    d = jnp.where(col == labels[:, None], p - 1.0, p) * g[:, None]
    return d.astype(logits.dtype), None


_ce_rows.defvjp(_ce_fwd, _ce_bwd)


# ---------------------------------------------------------------------------
# max pooling that keeps its winning taps (PERF.md, PR 28)
# ---------------------------------------------------------------------------
# Both kernels see (H, W, C, N): batch in the lanes, channels in the sublanes,
# so rows and columns are untiled and striding or interleaving them is
# addressing, not a relayout. That is the order XLA:TPU itself keeps a conv
# net's activations in from batch 128 up, so the caller's transposes to and
# from it are bitcasts. The grid walks (batch blocks, channel blocks, rows)
# with the rows innermost and in order, and every source row is read from HBM
# ONCE: a step that needs rows of earlier steps finds them in a VMEM scratch
# (`hist`), and one that needs a later row runs that many steps late.
_POOL_LANES = 128      # batch elements per block: the lane axis
_POOL_SUBLANES = 32    # channels per block, at least: one int8 tile
_POOL_VMEM_LIMIT = 32 << 20   # of v5e's 128 MiB; the default scope is 16
_POOL_VMEM_BYTES = 24 << 20   # what a pool's blocks may take of it


def _pool_bwd_plan(k, s, lo):
    """One spatial axis of the scatter, seen from the input: position
    ``s*m + r`` (r < s) of the UNPADDED input is padded position
    ``s*m + r + lo``, which window ``m + d`` holds as tap ``i`` for every
    ``(d, i)`` in ``plan[r]``. Static, from the window alone."""
    plan = []
    for r in range(s):
        delta, rr = divmod(r + lo, s)
        plan.append([(delta - q, rr + s * q) for q in range((k - 1) // s + 1)
                     if rr + s * q < k])
    return plan


def _reach(offsets):
    """(ahead, behind): how many steps late a row kernel runs so that its
    furthest source row has arrived, and how many earlier rows it keeps."""
    return max(0, max(offsets)), max(0, -min(offsets))


def _source(cur, hist, back, depth):
    """The ref that holds the block loaded ``back`` steps ago."""
    return cur if back == 0 else hist.at[depth - back]


def _remember(hists, curs, depth):
    for hist, cur in zip(hists, curs):
        for p in range(depth - 1):
            hist[p] = hist[p + 1]
        hist[depth - 1] = cur[...]


def _pool_fwd_kernel(x_ref, *refs, kernel, stride, pad_lo, h, w, n_h, ahead,
                     depth, signed):
    """One step loads input rows [s_h*j, s_h*j + s_h) and writes output row
    o = j - ahead with the winning tap of each of its windows. A later tap
    wins only by being GREATER, so the first among equals is kept: what
    select-and-scatter with ``ge`` picks. A tap outside the map holds -inf;
    only taps that can fall outside are masked. The k_w - s_w columns a
    window shares with the next one are carried, not loaded again. Where
    ``signed``, the second ref is a (channels, 1) column of +-1 and every tap
    is multiplied by its channel's as it is read (exact), so the pool is of
    ``x * sign`` and no pass over ``x`` makes that product."""
    if signed:
        sign_ref, *refs = refs
    out_ref, idx_ref, *hist = refs
    (kh, kw), (sh, sw), (lo_h, lo_w) = kernel, stride, pad_lo
    n_w = out_ref.shape[1]
    o = pl.program_id(2) - ahead
    rows = []
    for i in range(kh):
        block, r = divmod(i - lo_h, sh)
        e = o * sh - lo_h + i
        inside = i - lo_h >= 0 and (n_h - 1) * sh - lo_h + i < h
        rows.append((_source(x_ref, hist[0] if hist else None, ahead - block,
                             depth), r,
                     None if inside else (e >= 0) & (e < h)))
    shared = max(kw - sw, 0)
    tile = out_ref.shape[2:]
    sign = jnp.broadcast_to(sign_ref[...], tile) if signed else None

    def value(ref, r, row_ok, j, ow=None):
        """Tap value as float32, -inf outside the map (``row_ok`` None: this
        tap's row never leaves it; ``ow`` None: the first window's)."""
        col = j - lo_w + (0 if ow is None else ow * sw)
        if ow is None and not 0 <= col < w:
            return jnp.full(tile, -jnp.inf, jnp.float32)
        ok = row_ok
        # over all windows this tap's column runs from j - lo_w to:
        if ow is not None and not (j - lo_w >= 0
                                   and (n_w - 1) * sw - lo_w + j < w):
            col_ok = (col >= 0) & (col < w)
            ok = col_ok if ok is None else ok & col_ok
            col = jnp.clip(col, 0, w - 1)
        v = ref[r, col].astype(jnp.float32)
        if signed:
            v = v * sign
        return v if ok is None else jnp.where(ok, v, -jnp.inf)

    def column(ow, carried):
        best, tap, keep = None, jnp.zeros(tile, jnp.int32), []
        for i, (ref, r, row_ok) in enumerate(rows):
            vals = list(carried[i * shared:(i + 1) * shared])
            for j in range(shared, kw):
                vals.append(value(ref, r, row_ok, j, ow))
            keep += vals[sw:sw + shared]
            for j, v in enumerate(vals):
                if best is None:
                    best = v
                else:
                    better = v > best
                    best = jnp.where(better, v, best)
                    tap = jnp.where(better, i * kw + j, tap)
        out_ref[0, ow] = best.astype(out_ref.dtype)
        idx_ref[0, ow] = tap.astype(jnp.int8)
        return tuple(keep)

    @pl.when(o >= 0)
    def _():
        first = tuple(value(ref, r, row_ok, j)
                      for ref, r, row_ok in rows for j in range(shared))
        lax.fori_loop(0, n_w, column, first)

    _remember(hist, [x_ref], depth)


def _pool_bwd_kernel(dy_ref, idx_ref, out_ref, *hist, plan_h, plan_w, n_h,
                     n_w, kw, ahead, depth):
    """One step loads row j of dy and of the index and writes the s_h input
    rows of block m = j - ahead: each position sums, in float32, the dy of
    the windows whose saved tap it is. A window outside the map holds no tap
    at all. A source column that the next block of columns needs too is
    carried, not loaded again."""
    m = pl.program_id(2) - ahead
    offs_h = sorted({d for terms in plan_h for d, _ in terms})
    offs_w = sorted({d for terms in plan_w for d, _ in terms})
    src = {d: (_source(dy_ref, hist[0] if hist else None, ahead - d, depth),
               _source(idx_ref, hist[1] if hist else None, ahead - d, depth),
               (m + d >= 0) & (m + d < n_h)) for d in offs_h}
    s_w = len(plan_w)
    tile = out_ref.shape[2:]
    again = [dw for dw in offs_w if dw - 1 in offs_w]   # seen as dw-1 next

    def source(dh, ow):
        """(dy as float32, tap as int32 or -1 outside the map) of source
        column ``ow``, a traced index or, before the loop, a Python int."""
        dy_r, idx_r, ok = src[dh]
        if isinstance(ow, int):
            if not 0 <= ow < n_w:
                return (jnp.zeros(tile, jnp.float32),
                        jnp.full(tile, -1, jnp.int32))
        else:
            ok = ok & (ow >= 0) & (ow < n_w)
            ow = jnp.clip(ow, 0, n_w - 1)
        return (dy_r[0, ow].astype(jnp.float32),
                jnp.where(ok, idx_r[0, ow].astype(jnp.int32), -1))

    def column(w, carried):
        tiles, it = {}, iter(carried)
        for dh in offs_h:
            for dw in offs_w:
                if dw + 1 in again:
                    tiles[dh, dw] = (next(it), next(it))
                else:
                    tiles[dh, dw] = source(dh, w + dw)
        for rh, terms_h in enumerate(plan_h):
            for rw, terms_w in enumerate(plan_w):
                acc = None
                for dh, i in terms_h:
                    for dw, j in terms_w:
                        g, ix = tiles[dh, dw]
                        term = jnp.where(ix == i * kw + j, g, 0.0)
                        acc = term if acc is None else acc + term
                if acc is None:     # a position no window reaches
                    acc = jnp.zeros(tile, jnp.float32)
                out_ref[rh, w * s_w + rw] = acc.astype(out_ref.dtype)
        return tuple(v for dh in offs_h for dw in again for v in tiles[dh, dw])

    @pl.when(m >= 0)
    def _():
        first = tuple(v for dh in offs_h for dw in again
                      for v in source(dh, dw - 1))
        lax.fori_loop(0, out_ref.shape[1] // s_w, column, first)

    _remember(hist, [dy_ref, idx_ref], depth)


def _channel_block(hwcn, kernel, stride, itemsize):
    """Channels per block: 64 where they divide the channels and the blocks
    fit (1.88 -> 1.73 ms and 1.83 -> 1.62 ms a step for the two kernels at
    [512,112,112,64] bfloat16; larger blocks, or unrolling the column loop,
    gave nothing more: PERF.md, PR 28), else 32, one int8 tile; None where
    even that does not fit. What has to fit is the larger kernel's blocks,
    the forward's: s_h input rows double buffered, as many again kept, a row
    of output and of taps."""
    _, w, c, _ = hwcn
    keep = -(-kernel[0] // stride[0])
    column = _POOL_LANES * ((2 + keep) * stride[0] * w * itemsize
                            + 2 * -(-w // stride[1]) * (itemsize + 1))
    for cb in (2 * _POOL_SUBLANES, _POOL_SUBLANES):
        if c % cb == 0 and cb * column <= _POOL_VMEM_BYTES:
            return cb
    return None


def pool_eligible(hwcn, kernel, stride, itemsize):
    """Whether the two kernels take an input of spatial-first shape
    (H, W, C, N): whole lane and sublane blocks that fit in VMEM, whole row
    and column blocks."""
    h, w, c, n = hwcn
    return (n % _POOL_LANES == 0 and c % _POOL_SUBLANES == 0
            and h % stride[0] == 0 and w % stride[1] == 0
            and _channel_block(hwcn, kernel, stride, itemsize) is not None)


def _pool_call(name, kernel_fn, grid, in_specs, out_specs, out_shape, scratch,
               *args):
    # traced with x64 off (the package turns it on): every index in the
    # kernel and its index maps is then i32, which is all Mosaic takes.
    # The name is the custom call's in a device trace.
    with jax.enable_x64(False):
        return pl.pallas_call(
            kernel_fn, name=name, grid=grid, in_specs=in_specs,
            out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_POOL_VMEM_LIMIT),
            interpret=_interpret())(*args)


def max_pool_fwd(x, out_hw, kernel, stride, pad_lo, sign=None):
    """Max pool of (H, W, C, N) and the winning tap of each window, in one
    read of the input: (out, idx), both (n_h, n_w, C, N), idx int8 in
    row-major window order, first among equals. With ``sign`` (C,) of +-1,
    the pool of ``x * sign``, the product made block by block in VMEM."""
    h, w, c, n = x.shape
    (n_h, n_w), (kh, _), (sh, _) = out_hw, kernel, stride
    cb = _channel_block(x.shape, kernel, stride, x.dtype.itemsize)
    nb = _POOL_LANES
    signs = [] if sign is None else [sign.astype(jnp.float32).reshape(c, 1)]
    ahead, behind = _reach([(i - pad_lo[0]) // sh for i in range(kh)])
    depth = ahead + behind
    dst = pl.BlockSpec((1, n_w, cb, nb), lambda b, ch, j: (
        jnp.clip(j - ahead, 0, n_h - 1), 0, ch, b))
    return _pool_call(
        "max_pool_fwd",
        functools.partial(_pool_fwd_kernel, kernel=kernel, stride=stride,
                          pad_lo=pad_lo, h=h, w=w, n_h=n_h, ahead=ahead,
                          depth=depth, signed=sign is not None),
        (n // nb, c // cb, n_h + ahead),
        [pl.BlockSpec((sh, w, cb, nb), lambda b, ch, j: (
            jnp.minimum(j, h // sh - 1), 0, ch, b))]
        + [pl.BlockSpec((cb, 1), lambda b, ch, j: (ch, 0))] * len(signs),
        [dst, dst],
        [jax.ShapeDtypeStruct((n_h, n_w, c, n), x.dtype, **_vma_kw(x)),
         jax.ShapeDtypeStruct((n_h, n_w, c, n), jnp.int8, **_vma_kw(x))],
        [pltpu.VMEM((depth, sh, w, cb, nb), x.dtype)] if depth else [], x,
        *signs)


def max_pool_bwd(idx, dy, in_hw, kernel, stride, pad_lo):
    """dx (H, W, C, N) of a 2-D max pool from the winning tap of each window
    (``idx`` int8 and ``dy``, both (n_h, n_w, C, N)): reads dy and the index
    once, writes dx, touches nothing else."""
    n_h, n_w, c, n = dy.shape
    (h, w), (kh, kw), (sh, sw) = in_hw, kernel, stride
    plan_h = _pool_bwd_plan(kh, sh, pad_lo[0])
    plan_w = _pool_bwd_plan(kw, sw, pad_lo[1])
    cb = _channel_block((h, w, c, n), kernel, stride, dy.dtype.itemsize)
    nb = _POOL_LANES
    ahead, behind = _reach([d for terms in plan_h for d, _ in terms])
    depth = ahead + behind
    src = pl.BlockSpec((1, n_w, cb, nb), lambda b, ch, j: (
        jnp.minimum(j, n_h - 1), 0, ch, b))
    return _pool_call(
        "max_pool_bwd",
        functools.partial(_pool_bwd_kernel, plan_h=plan_h, plan_w=plan_w,
                          n_h=n_h, n_w=n_w, kw=kw, ahead=ahead, depth=depth),
        (n // nb, c // cb, h // sh + ahead),
        [src, src],
        pl.BlockSpec((sh, w, cb, nb), lambda b, ch, j: (
            jnp.clip(j - ahead, 0, h // sh - 1), 0, ch, b)),
        jax.ShapeDtypeStruct((h, w, c, n), dy.dtype, **_vma_kw(dy)),
        [pltpu.VMEM((depth, 1, n_w, cb, nb), dy.dtype),
         pltpu.VMEM((depth, 1, n_w, cb, nb), jnp.int8)] if depth else [],
        dy, idx)


# ---------------------------------------------------------------------------
# grouped matrix products (routed experts over tokens sorted by expert)
# ---------------------------------------------------------------------------
#: rows of one tile of the sorted tokens. A group (the tokens of one held
#: expert) starts at a tile's first row and is padded with zero rows to its
#: last tile's end, so a tile belongs to ONE group and a product over it is a
#: plain matrix product against that group's weight; an empty group keeps one
#: tile of zero rows, and the tiles behind the last group's count to it
MOE_TILE_ROWS = 512
_GMM_VMEM_LIMIT = 64 * 1024 * 1024
_GMM_BLOCK_BYTES = 24 * 1024 * 1024     # the blocks of ``moe_gmm``
_TGMM_BLOCK_BYTES = 40 * 1024 * 1024    # of ``moe_tgmm``: a float32 accumulator
_GMM_MAX_CONTRACTION = 4096             # the contraction is one block


def _gmm_cols(k, n, itemsize, tile):
    """Columns of the result a block of ``moe_gmm`` holds: the widest of
    1024..128 that divides ``n`` and fits beside a (tile, k) block of rows,
    both double buffered, and the float32 product; None where none does."""
    for tn in (1024, 512, 256, 128):
        blocks = 2 * (tile * k + k * tn + tile * tn) * itemsize + tile * tn * 4
        if n % tn == 0 and blocks <= _GMM_BLOCK_BYTES:
            return tn
    return None


def _tgmm_cols(k, n, itemsize, tile):
    """Rows of a group's (n, k) weight gradient a block of ``moe_tgmm``
    holds, by the same rule."""
    for tn in (1024, 512, 256, 128):
        blocks = (2 * (tile * tn + tile * k) * itemsize + tn * k * 4
                  + 2 * tn * k * itemsize)
        if n % tn == 0 and blocks <= _TGMM_BLOCK_BYTES:
            return tn
    return None


def moe_gmm_eligible(width, hidden, itemsize, tile=MOE_TILE_ROWS):
    """Whether the three kernels take an expert layer of these widths: whole
    lane blocks, a contraction that is one block, blocks that fit in VMEM in
    both directions (the forward contracts over ``width`` and ``hidden``, the
    backward over the other of each pair)."""
    return (use_pallas() and width % 128 == 0 and hidden % 128 == 0
            and max(width, hidden) <= _GMM_MAX_CONTRACTION and tile % 8 == 0
            and all(_gmm_cols(a, b, itemsize, tile) is not None
                    and _tgmm_cols(a, b, itemsize, tile) is not None
                    for a, b in ((width, hidden), (hidden, width))))


def _gmm_kernel(group_ref, lhs_ref, rhs_ref, out_ref, *, dims):
    del group_ref       # the index maps read it
    out_ref[...] = lax.dot_general(
        lhs_ref[...], rhs_ref[0], dims,
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


def moe_gmm(lhs, rhs, tile_group, transpose_rhs=False, tile=MOE_TILE_ROWS):
    """``out[r] = lhs[r] @ rhs[g]`` (``rhs[g].T`` with ``transpose_rhs``) for
    every row ``r`` of the tiles of group ``g``: lhs (tiles * tile, k) sorted
    by group as ``MOE_TILE_ROWS`` says, rhs (groups, k, n) or (groups, n, k),
    ``tile_group`` (tiles,) int32 the group of each tile in order. Group
    sizes are data; the grid is not: every tile is multiplied, the rows that
    hold no token are zeros, so a step's time does not follow the routing. A
    grid over the tiles in use alone is the faster form (PERF.md 7.3(b))."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tn = _gmm_cols(k, n, lhs.dtype.itemsize, tile)
    dims = (((1,), (1,)), ((), ())) if transpose_rhs \
        else (((1,), (0,)), ((), ()))
    with jax.enable_x64(False):
        if transpose_rhs:
            rhs_spec = pl.BlockSpec((1, tn, k),
                                    lambda j, i, group: (group[i], j, 0))
        else:
            rhs_spec = pl.BlockSpec((1, k, tn),
                                    lambda j, i, group: (group[i], 0, j))
        return pl.pallas_call(
            functools.partial(_gmm_kernel, dims=dims),
            name="moe_gmm_t" if transpose_rhs else "moe_gmm",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(n // tn, m // tile),
                in_specs=[pl.BlockSpec((tile, k), lambda j, i, group: (i, 0)),
                          rhs_spec],
                out_specs=pl.BlockSpec((tile, tn), lambda j, i, group: (i, j))),
            out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype, **_vma_kw(lhs)),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_GMM_VMEM_LIMIT),
            interpret=_interpret())(tile_group, lhs, rhs)


def _tgmm_kernel(group_ref, dy_ref, x_ref, out_ref, acc_ref):
    i, last = pl.program_id(1), pl.num_programs(1) - 1
    g = group_ref[i]
    opens = jnp.logical_or(i == 0, group_ref[jnp.maximum(i - 1, 0)] != g)
    closes = jnp.logical_or(i == last,
                            group_ref[jnp.minimum(i + 1, last)] != g)

    @pl.when(opens)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += lax.dot_general(
        dy_ref[...], x_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(closes)
    def _():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def moe_tgmm(dy, x, tile_group, groups, tile=MOE_TILE_ROWS):
    """``out[g] = dy[rows of g].T @ x[rows of g]``, (groups, n, k): each
    group's weight gradient in the weight's own (out, in) layout, from dy
    (rows, n) and x (rows, k) sorted as for ``moe_gmm``. A group's tiles are
    consecutive and every group has one, so every block of the result is
    written once; rows that hold no token are zero on both sides."""
    m, n = dy.shape
    k = x.shape[1]
    tn = _tgmm_cols(k, n, x.dtype.itemsize, tile)
    with jax.enable_x64(False):
        return pl.pallas_call(
            _tgmm_kernel, name="moe_gmm_dw",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(n // tn, m // tile),
                in_specs=[
                    pl.BlockSpec((tile, tn), lambda j, i, group: (i, j)),
                    pl.BlockSpec((tile, k), lambda j, i, group: (i, 0))],
                out_specs=pl.BlockSpec((1, tn, k),
                                       lambda j, i, group: (group[i], j, 0)),
                scratch_shapes=[pltpu.VMEM((tn, k), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((groups, n, k), x.dtype,
                                           **_vma_kw(x)),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_GMM_VMEM_LIMIT),
            interpret=_interpret())(tile_group, dy, x)
