"""Tensor parallelism — parameter sharding over the 'tp' mesh axis.

Absent from the reference (SURVEY.md §2.3 "Tensor parallelism: Absent —
build as first-class"). Megatron-style pairing: a column-parallel matmul
(output features sharded, no comm) feeds a row-parallel matmul (input
features sharded, one psum) — one allreduce per MLP/attention block.

Two surfaces:
- functional ops for use inside shard_map regions;
- ``shard_gluon_params``: annotate a gluon net's Parameters with
  PartitionSpecs by regex rule so pjit-based trainers shard them (the
  sharding-annotation route: XLA's SPMD partitioner then inserts the same
  collectives automatically).
"""
from __future__ import annotations

import functools
import re
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["column_parallel_dense", "row_parallel_dense", "tp_mlp",
           "shard_gluon_params", "DEFAULT_TP_RULES"]


# ---- inside-shard_map functional layers ----------------------------------
def column_parallel_dense(x, w_shard, b_shard=None):
    """x: (..., I) replicated; w_shard: (O/n, I) local. Output (..., O/n)
    stays sharded — no communication."""
    y = jnp.einsum("...i,oi->...o", x, w_shard)
    if b_shard is not None:
        y = y + b_shard
    return y


def row_parallel_dense(x_shard, w_shard, axis_name: str, b=None):
    """x_shard: (..., I/n); w_shard: (O, I/n). psum reduces the partial
    products; bias added once post-reduce."""
    y = lax.psum(jnp.einsum("...i,oi->...o", x_shard, w_shard), axis_name)
    if b is not None:
        y = y + b
    return y


def tp_mlp(x, w1_shard, b1_shard, w2_shard, b2, axis_name: str, act=jax.nn.relu):
    """Fused column→row parallel MLP block: ONE allreduce total."""
    h = act(column_parallel_dense(x, w1_shard, b1_shard))
    return row_parallel_dense(h, w2_shard, axis_name, b2)


# ---- gluon param annotation ------------------------------------------------
# rule: regex on parameter name -> PartitionSpec (axis names must exist in
# the mesh; None entries replicate that dim)
DEFAULT_TP_RULES = [
    (r".*_i2h_weight$", P("tp", None)),     # RNN input projections: col-parallel
    (r".*dense\d*_weight$", P("tp", None)),  # Dense weight (O, I): col-parallel
    (r".*conv\d*_weight$", P("tp", None, None, None)),  # conv out-channels
]


def shard_gluon_params(net, mesh: Mesh, rules=None, default=P()) -> Dict[str, NamedSharding]:
    """Assign a NamedSharding to every Parameter of ``net`` by first-match
    regex rule; stores it on ``Parameter.sharding`` and returns the map."""
    rules = rules if rules is not None else DEFAULT_TP_RULES
    compiled = [(re.compile(pat), spec) for pat, spec in rules]
    out = {}
    for p in net.collect_params().values():
        spec = default
        for pat, s in compiled:
            if pat.match(p.name):
                # drop axes that exceed the param's rank
                s = P(*list(s)[:len(p.shape or ())]) if p.shape else s
                spec = s
                break
        sh = NamedSharding(mesh, spec)
        p.sharding = sh
        out[p.name] = sh
    return out
