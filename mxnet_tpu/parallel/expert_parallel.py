"""Expert parallelism — the exchange over the 'ep' mesh axis of a layer of
top-1 routed experts.

Absent from the reference (SURVEY.md §2.3 "Expert parallelism: Absent").
The layer itself lives in ``ops/nn.py``: ``_contrib_moe_router`` chooses,
``_contrib_moe_experts`` is told which experts its holder has and computes
their part of the result for ALL tokens it is given, dropless, as a grouped
product over tokens sorted by expert (``gluon.contrib.transformer.
RoutedExperts`` on the ``gluon`` path; the benchmark's ``zaya1_8b.train``
runs one holder's share of such a layer on one chip, with no exchange and
nothing standing in for it). This module is the mesh half: what moves
tokens between holders when experts live one (or more) per device along
'ep'. It has ONE all_to_all out and one back around dense per-expert
matmuls, with a CAPACITY so that every shape is static: tokens past an
expert's capacity fall back to the residual path (Switch-style). The top-1
choice is the ops' own (``ops.nn.top1``): one routing code, not two. A
dropless exchange (all_gather of the tokens' routes, then each device's
``_contrib_moe_experts`` over the gathered tokens and a reduce_scatter of
the parts) is what the four-chip cell of this layer needs and is not built
yet (ROADMAP R3).

Surfaces mirror tensor_parallel.py:
- ``ep_moe_ffn`` — for use INSIDE shard_map regions (axis_name = 'ep');
- ``MoEParams.init`` + ``moe_ffn_reference`` — a single-device reference
  implementation tests compare the sharded path against.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["MoEParams", "moe_ffn_reference", "ep_moe_ffn", "top1_gate"]


class MoEParams(NamedTuple):
    """Per-device shard: this device's experts' weights.
    w_gate is replicated; w1/b1/w2/b2 lead with a local-experts axis."""
    w_gate: jax.Array        # (D, E_total)
    w1: jax.Array            # (E_local, D, H)
    b1: jax.Array            # (E_local, H)
    w2: jax.Array            # (E_local, H, D)
    b2: jax.Array            # (E_local, D)

    @staticmethod
    def init(key, d_model: int, d_hidden: int, n_experts: int,
             n_local: int = None, dtype=jnp.float32) -> "MoEParams":
        n_local = n_local or n_experts
        ks = jax.random.split(key, 3)
        scale1 = 1.0 / jnp.sqrt(d_model)
        scale2 = 1.0 / jnp.sqrt(d_hidden)
        return MoEParams(
            w_gate=jax.random.normal(ks[0], (d_model, n_experts),
                                     dtype) * scale1,
            w1=jax.random.normal(ks[1], (n_local, d_model, d_hidden),
                                 dtype) * scale1,
            b1=jnp.zeros((n_local, d_hidden), dtype),
            w2=jax.random.normal(ks[2], (n_local, d_hidden, d_model),
                                 dtype) * scale2,
            b2=jnp.zeros((n_local, d_model), dtype))


def top1_gate(x, w_gate):
    """Switch-style top-1 gating: (expert id, gate probability) per token,
    chosen as the routed-experts ops choose (``ops.nn.top1``)."""
    from ..ops.nn import top1
    return top1(jax.nn.softmax(jnp.einsum("td,de->te", x, w_gate), axis=-1))


def _expert_ffn(w1, b1, w2, b2, tokens):
    """(E, C, D) tokens through per-expert FFN — batched MXU matmuls."""
    h = jax.nn.relu(jnp.einsum("ecd,edh->ech", tokens, w1) + b1[:, None, :])
    return jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]


def moe_ffn_reference(params: MoEParams, x, capacity_factor: float = 1.25):
    """Single-device MoE (all experts local) — the semantics the EP path
    must reproduce. x: (T, D) -> (T, D)."""
    T, D = x.shape
    E = params.w_gate.shape[1]
    cap = int(max(1, capacity_factor * T / E))
    idx, gate = top1_gate(x, params.w_gate)

    # position of each token within its expert's capacity buffer
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)          # (T, E)
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1             # (T, E)
    pos_in_e = jnp.max(pos, axis=1)                           # (T,)
    keep = pos_in_e < cap

    buf = jnp.zeros((E, cap, D), x.dtype)
    buf = buf.at[idx, jnp.clip(pos_in_e, 0, cap - 1)].add(
        jnp.where(keep[:, None], x, 0))
    out_buf = _expert_ffn(params.w1, params.b1, params.w2, params.b2, buf)
    y = out_buf[idx, jnp.clip(pos_in_e, 0, cap - 1)]
    # dropped tokens pass through the residual (zero expert contribution)
    return jnp.where(keep[:, None], gate[:, None] * y, 0.0)


def ep_moe_ffn(params: MoEParams, x_local, axis_name: str = "ep",
               capacity_factor: float = 1.25):
    """Expert-parallel MoE for use INSIDE shard_map: tokens sharded on
    ``axis_name`` (x_local: (T/n, D)), experts sharded the same way
    (params.w1 etc: (E/n, ...), w_gate replicated).

    all_to_all #1 routes each device's per-expert capacity buffers to the
    expert's owner; the FFN runs locally; all_to_all #2 routes results
    back. Shapes stay static (capacity truncation), so XLA overlaps the
    collectives with compute on the ICI torus.
    """
    n = lax.psum(1, axis_name)
    Tl, D = x_local.shape
    E_local = params.w1.shape[0]
    E = n * E_local
    cap = int(max(1, capacity_factor * Tl / E))

    idx, gate = top1_gate(x_local, params.w_gate)
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)
    pos_in_e = jnp.max(jnp.cumsum(onehot, axis=0) * onehot - 1, axis=1)
    keep = pos_in_e < cap
    slot = jnp.clip(pos_in_e, 0, cap - 1)

    # local capacity buffers for EVERY global expert: (E, cap, D)
    buf = jnp.zeros((E, cap, D), x_local.dtype)
    buf = buf.at[idx, slot].add(jnp.where(keep[:, None], x_local, 0))

    # (E, cap, D) -> (n, E_local, cap, D): split by owner, trade buffers so
    # each device holds its experts' tokens from all devices
    buf = buf.reshape(n, E_local, cap, D)
    buf = lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0,
                         tiled=False)   # (n=source device, E_local, cap, D)
    recv = buf.transpose(1, 0, 2, 3).reshape(E_local, n * cap, D)

    out = _expert_ffn(params.w1, params.b1, params.w2, params.b2, recv)

    # route results back to the owning devices
    out = out.reshape(E_local, n, cap, D).transpose(1, 0, 2, 3)
    out = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                         tiled=False)                     # (n, E_local, cap, D)
    out = out.reshape(E, cap, D)

    y = out[idx, slot]
    return jnp.where(keep[:, None], gate[:, None] * y, 0.0)
