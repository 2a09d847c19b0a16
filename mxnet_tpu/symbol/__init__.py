"""``mx.sym`` — symbolic namespace.

Like ``mx.nd``, every registered operator is exposed lazily as a graph-node
constructor (reference codegen: ``python/mxnet/symbol/register.py``). Calling
``sym.FullyConnected(data, num_hidden=10, name="fc1")`` creates a node and
auto-creates weight/bias Variables named ``fc1_weight``/``fc1_bias`` when not
supplied — same behavior as the reference's symbol composition.
"""
from __future__ import annotations

from typing import Any, Dict, List

from .symbol import Symbol, Variable, var, Group, load, load_json, _Node
from ..ops.registry import get_op, list_ops, _REGISTRY
from ..base import MXNetError

__all__ = ["Symbol", "Variable", "var", "Group", "load", "load_json", "zeros",
           "ones"]


def _invoke_sym(op_name: str, sym_inputs: List[Symbol], kwargs: Dict[str, Any]) -> Symbol:
    from ..name import NameManager
    from ..attribute import AttrScope
    opdef = get_op(op_name)
    hint = op_name.lower().lstrip("_")
    name = NameManager.current().get(kwargs.pop("name", None), hint)
    scope_attr = AttrScope.current().get(kwargs.pop("attr", None))
    kwargs.pop("ctx", None)

    # variadic ops (Concat/add_n/stack: arg_names() None) consume every output
    # of a multi-output input; fixed-arity ops take output 0 (NNVM behavior)
    variadic = opdef.arg_names() is None
    entries = []
    for s in sym_inputs:
        if not isinstance(s, Symbol):
            raise MXNetError(f"{op_name}: expected Symbol input, got {type(s)}")
        if len(s._outputs) > 1 and variadic:
            entries.extend(s._outputs)
        else:
            entries.append(s._outputs[0])

    # split keyword Symbol args (e.g. weight=..., bias=...) from attrs
    arg_names = opdef.arg_names() or []
    kw_syms: Dict[str, Symbol] = {k: v for k, v in kwargs.items()
                                  if isinstance(v, Symbol)}
    attrs = {k: v for k, v in kwargs.items() if not isinstance(v, Symbol)}

    if arg_names:
        # build the input list in signature order, auto-creating variables
        final: List = []
        pos = 0
        for i, an in enumerate(arg_names):
            if an in kw_syms:
                final.append(kw_syms[an]._outputs[0])
            elif pos < len(entries):
                final.append(entries[pos])
                pos += 1
            else:
                # auto-create variable (params like weight/bias/gamma/beta)
                if op_name == "FullyConnected" and an == "bias" and attrs.get("no_bias"):
                    continue
                if op_name in ("Convolution", "Deconvolution") and an == "bias" \
                        and attrs.get("no_bias", op_name == "Deconvolution"):
                    continue
                if op_name == "LeakyReLU" and an == "gamma" \
                        and attrs.get("act_type", "leaky") != "prelu":
                    continue
                if op_name == "RMSNorm" and an == "gamma" \
                        and attrs.get("no_gain"):
                    continue
                vnode = _Node(None, f"{name}_{an}", {}, [])
                final.append((vnode, 0))
        entries = final
    node = _Node(op_name, name, attrs, entries)
    if scope_attr:
        node._attr_dict.update(scope_attr)
    return Symbol([(node, i) for i in range(node.num_outputs)])


def _make_sym_func(op_name: str):
    def fn(*args, **kwargs):
        syms = [a for a in args if isinstance(a, Symbol)]
        return _invoke_sym(op_name, syms, dict(kwargs))

    fn.__name__ = op_name
    fn.__doc__ = get_op(op_name).doc
    return fn


_func_cache: Dict[str, Any] = {}


def __getattr__(name: str):
    if name == "contrib":
        import importlib
        return importlib.import_module(__name__ + ".contrib")
    if name not in _REGISTRY and not name.startswith("__"):
        try:  # lazy-provider ops (registry._LAZY_PROVIDERS) resolve on access
            get_op(name)
        except Exception:
            pass
    if name in _REGISTRY:
        if name not in _func_cache:
            _func_cache[name] = _make_sym_func(name)
        return _func_cache[name]
    raise AttributeError(f"module 'mxnet_tpu.symbol' has no attribute {name!r}")


def __dir__():
    return sorted(set(list(globals()) + list_ops()))


def zeros(shape, dtype="float32", **kw):
    return _invoke_sym("_zeros", [], {"shape": tuple(shape) if not isinstance(shape, int) else (shape,), "dtype": dtype})


def ones(shape, dtype="float32", **kw):
    return _invoke_sym("_ones", [], {"shape": tuple(shape) if not isinstance(shape, int) else (shape,), "dtype": dtype})


def _scalar_or_bcast(bcast_op, scalar_op, rscalar_op=None):
    """Reference-style module-level binary (symbol.py:pow/maximum/minimum/
    hypot): Symbol-Symbol uses the broadcast op, Symbol-scalar the scalar
    op (reversed variant when the scalar is on the left)."""
    def fn(left, right):
        l_sym = isinstance(left, Symbol)
        r_sym = isinstance(right, Symbol)
        if l_sym and r_sym:
            return _invoke_sym(bcast_op, [left, right], {})
        if l_sym:
            return _invoke_sym(scalar_op, [left], {"scalar": float(right)})
        if r_sym:
            return _invoke_sym(rscalar_op or scalar_op, [right],
                               {"scalar": float(left)})
        raise TypeError("at least one operand must be a Symbol")
    return fn


maximum = _scalar_or_bcast("broadcast_maximum", "_maximum_scalar")
minimum = _scalar_or_bcast("broadcast_minimum", "_minimum_scalar")
hypot = _scalar_or_bcast("broadcast_hypot", "_hypot_scalar")
