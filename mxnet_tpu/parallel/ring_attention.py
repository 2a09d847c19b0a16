"""Ring attention — sequence/context parallelism over the 'sp' mesh axis.

The reference predates attention entirely (SURVEY.md §5.7: its long-sequence
story is bucketing + fused cuDNN RNN); this module is the long-context
capability the north star requires as first-class. Design follows the
blockwise/ring formulation (Liu et al.; see PAPERS.md): each device holds a
sequence shard of Q, K, V; K/V blocks rotate around the ICI ring via
``ppermute`` while each device accumulates its Q-shard's attention with an
online (log-sum-exp) softmax — memory O(T/n · T/n), full overlap of compute
with neighbor transfers.

The per-ring-step partial attention is the Pallas flash kernel
(``ops.pallas_kernels.flash_attention_with_lse``) on TPU; the whole ring loop
carries a custom VJP implementing the ring-flash backward: a second ring pass
where dK/dV accumulators rotate with their K/V blocks, so each shard's
gradient arrives back at its owner after n hops with every device's
contribution summed — no cross-shard gather, all traffic on ICI.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.pallas_kernels import (flash_attention, flash_attention_with_lse,
                                  flash_attention_bwd, _NEG_INF)

__all__ = ["ring_attention", "local_attention", "ring_attention_sharded"]


def _pvary(x, axis_name):
    return lax.pcast(x, (axis_name,), to="varying")


def local_attention(q, k, v, causal: bool = False, scale: Optional[float] = None,
                    q_offset: int = 0, k_offset: int = 0):
    """Single-device attention (flash path); q,k,v: (B, H, T, D)."""
    return flash_attention(q, k, v, causal=causal, scale=scale,
                           q_offset=q_offset, k_offset=k_offset)


def _merge(acc, lse, o_blk, lse_blk):
    """Merge a normalized partial (o_blk, lse_blk) into the running (acc, lse).

    out = Σ_b exp(lse_b − lse_tot)·o_b with lse_tot = logaddexp over blocks.
    """
    m = jnp.maximum(lse, lse_blk)
    safe_m = jnp.where(m <= _NEG_INF / 2, 0.0, m)
    e_old = jnp.where(lse <= _NEG_INF / 2, 0.0, jnp.exp(lse - safe_m))
    e_blk = jnp.where(lse_blk <= _NEG_INF / 2, 0.0, jnp.exp(lse_blk - safe_m))
    denom = jnp.maximum(e_old + e_blk, 1e-30)
    lse_comb = jnp.where((lse <= _NEG_INF / 2) & (lse_blk <= _NEG_INF / 2),
                         _NEG_INF, safe_m + jnp.log(denom))
    # invariant: acc = Σ_b o_b · exp(lse_b − lse_comb)  (exact, normalized)
    w_old = e_old / denom
    w_blk = e_blk / denom
    acc_new = acc * w_old[..., None] + o_blk.astype(jnp.float32) * w_blk[..., None]
    return acc_new, lse_comb


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_attention_local(q, k, v, axis_name: str, causal: bool,
                          scale: Optional[float]):
    """Runs inside shard_map. q,k,v: (B, H, T_local, D) on each device."""
    out, _ = _ring_fwd_impl(q, k, v, axis_name, causal, scale)
    return out


def _ring_fwd_impl(q, k, v, axis_name, causal, scale):
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    sc = scale if scale is not None else 1.0 / (D ** 0.5)
    perm = [(i, (i + 1) % n) for i in range(n)]  # pass kv to the next rank

    acc0 = jnp.zeros((B, H, Tq, D), jnp.float32)
    lse0 = jnp.full((B, H, Tq), _NEG_INF, jnp.float32)
    # constants start 'unvarying' over the manual axis; the loop carry becomes
    # varying after the first iteration — pre-cast so types line up (jax vma)
    acc0, lse0 = (_pvary(x, axis_name) for x in (acc0, lse0))

    def body(i, carry):
        acc, lse, k_blk, v_blk = carry
        src = (my - i) % n  # whose kv shard we hold this tick
        o_blk, lse_blk = flash_attention_with_lse(
            q, k_blk, v_blk, causal=causal, scale=sc,
            q_offset=my * Tq, k_offset=src * Tk)
        acc, lse = _merge(acc, lse, o_blk, lse_blk)
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return acc, lse, k_next, v_next

    acc, lse, _, _ = lax.fori_loop(0, n, body, (acc0, lse0, k, v))
    return acc.astype(q.dtype), lse


def _ring_fwd(q, k, v, axis_name, causal, scale):
    out, lse = _ring_fwd_impl(q, k, v, axis_name, causal, scale)
    return out, (q, k, v, out, lse)


def _ring_bwd(axis_name, causal, scale, res, g):
    """Second ring pass: dK/dV accumulators travel WITH their K/V blocks."""
    q, k, v, out, lse = res
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    sc = scale if scale is not None else 1.0 / (D ** 0.5)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def flat(x):
        return x.reshape(B * H, x.shape[2], D)

    qf, outf, gf = flat(q), flat(out), flat(g)
    lsef = lse.reshape(B * H, Tq)

    dq0 = jnp.zeros_like(q, dtype=jnp.float32)  # varying (inherits from q)
    dk0 = _pvary(jnp.zeros((B, H, Tk, D), jnp.float32), axis_name)
    dv0 = _pvary(jnp.zeros((B, H, Tk, D), jnp.float32), axis_name)

    def body(i, carry):
        dq, dk, dv, k_blk, v_blk = carry
        src = (my - i) % n
        # shared blockwise flash backward (O(Tq·block) memory per step)
        dq_c, dk_c, dv_c = flash_attention_bwd(
            qf, flat(k_blk), flat(v_blk), outf, lsef, gf, sc, causal,
            q_offset=my * Tq, k_offset=src * Tk)
        dq = dq + dq_c.reshape(B, H, Tq, D)
        # accumulators ride the ring alongside their kv block
        dk = lax.ppermute(dk + dk_c.reshape(B, H, Tk, D), axis_name, perm)
        dv = lax.ppermute(dv + dv_c.reshape(B, H, Tk, D), axis_name, perm)
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return dq, dk, dv, k_next, v_next

    dq, dk, dv, _, _ = lax.fori_loop(
        0, n, body, (dq0, dk0, dv0, k, v))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_attention_local.defvjp(_ring_fwd, _ring_bwd)


def _ring_local(q, k, v, *, axis_name, causal, scale):
    # custom_vjp nondiff args must be positional — keyword-friendly shim
    return _ring_attention_local(q, k, v, axis_name, causal, scale)


def ring_attention(q, k, v, mesh: Mesh, axis: str = "sp", causal: bool = False,
                   scale: Optional[float] = None):
    """Global-array entry: q,k,v (B, H, T, D) with T sharded over ``axis``."""
    fn = shard_map(
        functools.partial(_ring_local, axis_name=axis, causal=causal,
                          scale=scale),
        mesh=mesh,
        in_specs=(P(None, None, axis, None),) * 3,
        out_specs=P(None, None, axis, None))
    return fn(q, k, v)


def ring_attention_sharded(axis: str = "sp", causal: bool = False,
                           scale: Optional[float] = None):
    """For composition inside an existing shard_map region."""
    return functools.partial(_ring_local, axis_name=axis,
                             causal=causal, scale=scale)
