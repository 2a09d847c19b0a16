"""Fusion-friendly reordering: hoist casts/transposes so XLA fuses across,
and sink max pools in front of the per-channel monotone maps that feed them.

Four structural rewrites, iterated to a fixpoint:

* **compose/cancel** — ``transpose(transpose(x, q), p)`` becomes one
  transpose with the composed permutation, or disappears entirely when the
  composition is the identity (the pair the layout pass's boundaries can
  leave behind, and the classic user-graph wart);
* **sink through unary** — ``relu(transpose(x))`` → ``transpose(relu(x))``
  (casts included: a ``Cast`` stranded under a transpose blocks XLA from
  fusing the convert into the producer's HBM pass).  Sinking moves
  transposes toward consumers where the compose rule can cancel them;
* **sink through binary** — ``add(transpose(x), transpose(y))`` with equal
  permutations → ``transpose(add(x, y))``;
* **sink a max pool** — ``maxpool(relu(x))`` → ``relu(maxpool(x))``, then
  ``maxpool(BatchNorm(x))`` → ``_MaxPoolBatchNorm(x)`` (``ops/nn.py``): the
  statistics still come from all of ``x``, the apply and the ReLU run on
  the pooled map, a quarter of the size for a ResNet stem.

The three transpose rules are bitwise-exact (pure data-movement reordering
around elementwise math). The pool rule is exact in VALUE, rounding
included: a maximum commutes with a non-decreasing map, a ReLU is one and
so is BatchNorm's per-channel ``y*s + b`` once the sign of ``s`` is taken
into the pool (docs/passes.md). Its gradient is the unsunk graph's up to
WHICH of several taps that the maps send to one value wins (the first
there, the one whose ``x`` is largest here). Rewrites only fire when the
intermediate has a single consumer — duplicating a transpose to sink it, or
keeping the full-size map for a second reader, would pessimize.
"""
from __future__ import annotations

from typing import Dict, Tuple

from ..symbol.symbol import Symbol, _Node
from ..ops.nn import _conv_layout
from .manager import Pass, PassContext, Namer, is_barrier, register_pass
from .layout import UNARY_ELEMWISE, MULTI_ELEMWISE, _truthy

__all__ = ["FusionReorderPass"]

_MAX_ROUNDS = 8


def _axes_of(node) -> Tuple[int, ...]:
    axes = (node.attrs or {}).get("axes")
    if isinstance(axes, (tuple, list)) and axes:
        return tuple(int(a) for a in axes)
    return ()


def _is_transpose(node) -> bool:
    return node is not None and node.op == "transpose" and bool(_axes_of(node))


def _is_max_pool(node) -> bool:
    attrs = node.attrs or {}
    return node.op == "Pooling" and attrs.get("pool_type", "max") == "max" \
        and not _truthy(attrs.get("global_pool", False))


def _is_relu(node) -> bool:
    return node.op == "relu" or (
        node.op == "Activation"
        and (node.attrs or {}).get("act_type", "relu") == "relu")


#: the Pooling attributes a max pool is made of, as _MaxPoolBatchNorm names them
_POOL_ATTRS = {"kernel": "pool_kernel", "stride": "pool_stride",
               "pad": "pool_pad", "pooling_convention": "pool_convention",
               "layout": "pool_layout"}


def _bn_on_pool_channels(bn, pool) -> bool:
    """Whether BatchNorm's axis is the pool's channel axis."""
    kernel = tuple((pool.attrs or {}).get("kernel") or ())
    try:
        lhs, _ = _conv_layout(len(kernel), (pool.attrs or {}).get("layout"))
        axis = int((bn.attrs or {}).get("axis", 1))
    except (KeyError, TypeError, ValueError):
        return False
    return len(lhs) == len(kernel) + 2 and axis % len(lhs) == lhs.find("C")


@register_pass
class FusionReorderPass(Pass):
    name = "fusion"

    def apply(self, sym: Symbol, ctx: PassContext):
        total = 0
        for _ in range(_MAX_ROUNDS):
            sym, n = self._round(sym)
            total += n
            if n == 0:
                break
        return sym, total

    def _round(self, sym: Symbol):
        nodes = sym.topo_nodes()
        if not any(_is_transpose(n) or _is_max_pool(n)
                   for n in nodes if not n.is_var):
            return sym, 0
        consumers: Dict[int, int] = {}
        for n in nodes:
            for (src, _) in n.inputs:
                consumers[id(src)] = consumers.get(id(src), 0) + 1
        for (hn, _) in sym._outputs:
            consumers[id(hn)] = consumers.get(id(hn), 0) + 1

        namer = Namer(sym)
        remap: Dict[Tuple[int, int], Tuple[_Node, int]] = {}
        count = 0

        def map_entry(entry):
            src, idx = entry
            if src.is_var:
                return (src, idx)
            return remap[(id(src), idx)]

        def register(node, entry_or_node):
            if isinstance(entry_or_node, tuple):
                remap[(id(node), 0)] = entry_or_node
            else:
                for i in range(node.num_outputs):
                    remap[(id(node), i)] = (entry_or_node, i)

        def clone(node, ins, attrs=None):
            if attrs is None and all(
                    a is b[0] and i == b[1]
                    for (a, i), b in zip(node.inputs, ins)):
                return node
            nn = _Node(node.op, node.name,
                       dict(node.attrs) if attrs is None else attrs, ins)
            nn._attr_dict = dict(node._attr_dict)
            return nn

        for node in nodes:
            if node.is_var:
                continue
            if is_barrier(node):
                register(node, clone(node, [map_entry(e)
                                            for e in node.inputs]))
                continue

            ins = [map_entry(e) for e in node.inputs]

            # ---- compose / cancel consecutive transposes
            if _is_transpose(node) and len(ins) == 1 \
                    and _is_transpose(ins[0][0]) and ins[0][1] == 0:
                inner = ins[0][0]
                p, q = _axes_of(node), _axes_of(inner)
                if len(p) == len(q):
                    composed = tuple(q[a] for a in p)
                    count += 1
                    if composed == tuple(range(len(composed))):
                        register(node, inner.inputs[0])
                    else:
                        register(node, clone(
                            node, [inner.inputs[0]],
                            dict(node.attrs, axes=composed)))
                    continue

            # ---- sink a single-consumer transpose through unary elemwise
            if node.op in UNARY_ELEMWISE and len(node.inputs) == 1 \
                    and _is_transpose(ins[0][0]) and ins[0][1] == 0 \
                    and consumers.get(id(node.inputs[0][0]), 0) == 1:
                t = ins[0][0]
                inner_op = _Node(node.op, node.name, dict(node.attrs),
                                 [t.inputs[0]])
                inner_op._attr_dict = dict(node._attr_dict)
                out_t = _Node("transpose", namer.fresh(node.name + "_sunk"),
                              {"axes": _axes_of(t)}, [(inner_op, 0)])
                out_t._attr_dict = dict(node._attr_dict)
                register(node, out_t)
                count += 1
                continue

            # ---- sink matching transposes through binary elemwise
            if node.op in MULTI_ELEMWISE and len(node.inputs) == 2 \
                    and all(_is_transpose(i[0]) and i[1] == 0 for i in ins) \
                    and _axes_of(ins[0][0]) == _axes_of(ins[1][0]) \
                    and all(consumers.get(id(e[0]), 0) == 1
                            for e in node.inputs):
                ta, tb = ins[0][0], ins[1][0]
                inner_op = _Node(node.op, node.name, dict(node.attrs),
                                 [ta.inputs[0], tb.inputs[0]])
                inner_op._attr_dict = dict(node._attr_dict)
                out_t = _Node("transpose", namer.fresh(node.name + "_sunk"),
                              {"axes": _axes_of(ta)}, [(inner_op, 0)])
                out_t._attr_dict = dict(node._attr_dict)
                register(node, out_t)
                count += 1
                continue

            # ---- sink a max pool in front of the single-consumer ReLU or
            # BatchNorm that feeds it (the producer as this round found it:
            # one that an earlier rule of the round rewrote waits a round)
            if _is_max_pool(node) and len(ins) == 1 and ins[0][1] == 0 \
                    and consumers.get(id(node.inputs[0][0]), 0) == 1 \
                    and ins[0][0].op == node.inputs[0][0].op:
                fed = ins[0][0]
                if _is_relu(fed):
                    pooled = clone(node, [fed.inputs[0]])
                    register(node, (clone(fed, [(pooled, 0)]), 0))
                    count += 1
                    continue
                if fed.op == "BatchNorm" and _bn_on_pool_channels(fed, node):
                    attrs = dict(fed.attrs)
                    for k, v in _POOL_ATTRS.items():
                        if k in node.attrs:
                            attrs[v] = node.attrs[k]
                    sunk = _Node("_MaxPoolBatchNorm", fed.name, attrs,
                                 list(fed.inputs))
                    sunk._attr_dict = dict(fed._attr_dict)
                    register(node, (sunk, 0))
                    count += 1
                    continue

            register(node, clone(node, ins))

        if count == 0:
            return sym, 0
        new_heads = [map_entry(e) for e in sym._outputs]
        return Symbol(new_heads), count
