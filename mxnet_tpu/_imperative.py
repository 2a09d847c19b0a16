"""Imperative op invocation — the TPU-native ``Imperative::Invoke`` path.

Reference parity: ``src/imperative/imperative.cc:38-120`` (Invoke → infer →
dispatch → engine push) and ``MXImperativeInvokeEx``
(``src/c_api/c_api_ndarray.cc:132``).

TPU-first: "push to the dependency engine" becomes "call a cached jitted XLA
executable" — jax's async dispatch IS the engine (ordering by data dependence,
results returned as futures, errors surfaced at the next sync point). Each
(op, attrs) pair compiles once per shape/dtype signature and is then a single
async XLA dispatch, which is how the per-op latency the reference hides with
its C++ threaded engine stays hidden here (SURVEY.md stage 3 / hard part #2).
"""
from __future__ import annotations

import functools
import inspect
from typing import Any, Dict, Sequence

import jax

from . import random as _random
from .base import MXNetError
from .ops.registry import (OpDef, get_op, jitted_op, jitted_op_vjp,
                           normalize_attrs, op_vjp, pullback)

__all__ = ["invoke", "invoke_raw"]


def _op_signature_flags(opdef: OpDef):
    if not hasattr(opdef, "_sig_flags"):
        try:
            params = inspect.signature(opdef.fn).parameters
            opdef._sig_flags = ("is_train" in params, "rng" in params)
        except (TypeError, ValueError):
            opdef._sig_flags = (False, False)
    return opdef._sig_flags


def _prepare(opdef: OpDef, inputs, attrs, is_train: bool = None):
    """The one preparation of an op call, recorded or not: fill ``is_train``
    and ``rng``, normalise the attrs. Returns ``(attr key, rng kwargs)``."""
    accepts_train, accepts_rng = _op_signature_flags(opdef)
    attrs = dict(attrs)
    if accepts_train and "is_train" not in attrs:
        from . import autograd
        attrs["is_train"] = bool(autograd.is_training()) if is_train is None else is_train
    if accepts_rng and attrs.get("rng") is None:
        attrs["rng"] = _random.next_key()
    rng = attrs.pop("rng", None)
    if rng is None:
        return normalize_attrs(attrs), {}
    for v in inputs:
        if hasattr(v, "devices"):
            rng = jax.device_put(rng, list(v.devices())[0])
            break
    return normalize_attrs(attrs), {"rng": rng}


def _dispatch(opdef: OpDef, key, inputs, kw, diff_idx=None):
    """Run a prepared op through its cached executable. Returns its outputs,
    or ``(outputs, pullback)`` w.r.t. ``inputs[diff_idx]`` when recording."""
    if not opdef.host:   # a host op has no fixed-shape XLA lowering
        try:
            if diff_idx is None:
                return jitted_op(opdef.name, key)(*inputs, **kw)
            out, vjp = jitted_op_vjp(opdef.name, key, diff_idx)(inputs, kw)
            return out, functools.partial(pullback, vjp)
        except TypeError:
            pass         # attrs or values jit cannot take: run eagerly
    fn = functools.partial(opdef.fn, **dict(key))
    if diff_idx is None:
        return fn(*inputs, **kw)
    return op_vjp(fn, diff_idx)(inputs, kw)


def invoke_raw(op_name: str, inputs: Sequence[Any], attrs: Dict[str, Any],
               is_train: bool = None):
    """Run an op on raw jax arrays, returning raw jax array(s)."""
    opdef = get_op(op_name)
    key, kw = _prepare(opdef, inputs, attrs, is_train)
    return _dispatch(opdef, key, inputs, kw)


def invoke(op_name: str, inputs, attrs, out=None):
    """Imperative entry used by the generated ``mx.nd.*`` wrappers: unwraps
    NDArrays, records on the autograd tape when active, rewraps outputs."""
    from .ndarray.ndarray import NDArray, _wrap, _unwrap
    from . import autograd, profiler, engine

    opdef = get_op(op_name)
    in_datas = [_unwrap(x) for x in inputs]

    profiling = profiler.is_active("imperative")
    t0 = profiler._prof.us() if profiling else 0.0

    if autograd.is_recording() and opdef.differentiable:
        out_data = autograd._record_invoke(opdef, inputs, in_datas, attrs)
    else:
        out_data = invoke_raw(op_name, in_datas, attrs)

    if engine.is_naive():
        for o in (out_data if isinstance(out_data, tuple) else (out_data,)):
            if hasattr(o, "block_until_ready"):
                o.block_until_ready()
    if profiling:
        profiler.record_event(op_name, "operator", t0, profiler._prof.us() - t0)

    n_out = opdef.out_count(dict(attrs))
    if isinstance(out_data, tuple):
        outs = [_wrap(o) for o in out_data]
    else:
        outs = [_wrap(out_data)]
    # attach autograd graph nodes recorded above
    if autograd.is_recording() and opdef.differentiable:
        autograd._attach_outputs(outs)

    if out is not None:
        targets = out if isinstance(out, (list, tuple)) else [out]
        for t, o in zip(targets, outs):
            t._set_data(o._data)
        return out
    if len(outs) == 1:
        return outs[0]
    return outs
