"""Ulysses-style sequence parallelism: all-to-all head/sequence exchange.

Complement to ring attention (see PAPERS.md, DeepSpeed-Ulysses): with T
sharded over 'sp', two ``all_to_all`` collectives re-shard to heads-parallel
so each device computes FULL-sequence attention for H/n heads, then shard
back. Cheaper than ring when H ≥ n and T/n blocks are small; ring wins at
very long T. Both are exposed so models can pick per-config.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .ring_attention import local_attention

__all__ = ["ulysses_attention", "ulysses_sharded"]


def _ulysses_local(q, k, v, axis_name: str, causal: bool, scale):
    """q,k,v: (B, H, T_local, D). all_to_all → (B, H_local, T, D)."""
    # split heads across ranks, gather sequence
    def seq2head(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def head2seq(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = seq2head(q), seq2head(k), seq2head(v)
    out = local_attention(qh, kh, vh, causal=causal, scale=scale)
    return head2seq(out)


def ulysses_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                      causal: bool = False, scale: Optional[float] = None):
    """Global entry: q,k,v (B, H, T, D), T sharded on ``axis``; H must be
    divisible by the axis size."""
    fn = shard_map(
        functools.partial(_ulysses_local, axis_name=axis, causal=causal,
                          scale=scale),
        mesh=mesh,
        in_specs=(P(None, None, axis, None),) * 3,
        out_specs=P(None, None, axis, None))
    return fn(q, k, v)


def ulysses_sharded(axis: str = "sp", causal: bool = False, scale=None):
    return functools.partial(_ulysses_local, axis_name=axis, causal=causal,
                             scale=scale)
