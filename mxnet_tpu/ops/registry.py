"""Operator registry — the TPU-native analogue of the NNVM op registry.

Reference parity: ``NNVM_REGISTER_OP`` + per-op attrs ``FCompute``,
``FInferShape``, ``FInferType``, ``FGradient`` (``include/mxnet/op_attr_types.h:66-313``,
registration style ``src/operator/nn/fully_connected.cc:239-279``).

TPU-first design: an op is a *pure jax function* ``fn(*arrays, **attrs)``.
That single artifact subsumes the reference's per-op attribute zoo:

* ``FCompute<cpu/gpu>``  → the jax function itself (XLA compiles per backend);
* ``FInferShape/FInferType`` → ``jax.eval_shape`` over the same function;
* ``FGradient``          → ``jax.vjp`` over the same function (with optional
  per-op override for custom gradients like ``SoftmaxOutput``);
* kernel autotuning (``operator_tune.h``) → XLA's cost model; nothing to do.

Both frontend namespaces (``mxnet_tpu.ndarray`` — imperative, and
``mxnet_tpu.symbol`` — graph-building) are generated from this registry at
import, mirroring the reference's codegen from the C registry
(``python/mxnet/ndarray/register.py``).
"""
from __future__ import annotations

import functools
import importlib
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax

from ..base import MXNetError

__all__ = ["OpDef", "register", "get_op", "list_ops", "alias", "jitted_op",
           "KEPT_IN_SEGMENT"]

_REGISTRY: Dict[str, "OpDef"] = {}

#: the ``jax.ad_checkpoint.checkpoint_name`` of what the backward pass of a
#: recomputed segment keeps besides the segment's inputs: the result of the
#: ``product`` ops inside it that do not widen their first operand
#: (``executor._GraphLowering``) and the attention kernel's residuals
#: (``pallas_kernels._flash_core_fwd``)
KEPT_IN_SEGMENT = "mxtpu_segment_product"


class OpDef:
    """One registered operator.

    Parameters
    ----------
    name : canonical op name (MXNet-compatible, e.g. ``FullyConnected``).
    fn : pure function ``fn(*arrays, **attrs) -> array | tuple``. Arrays are
        jax arrays; attrs are hashable python values (the registry coerces
        lists to tuples at call sites).
    num_outputs : static output count, or a callable ``attrs -> int`` for ops
        like ``split`` whose arity depends on attrs.
    needs_rng : op consumes a PRNG key; the runtime threads one in as the
        ``rng`` keyword (imperative: from the global seed stream; symbolic:
        as a traced input so jitted graphs stay functional).
    product : output 0 is a matrix or convolution product (or the attention
        built of them): dear to compute again, so a recomputed segment of a
        graph keeps it for the backward pass where it is no larger than the
        op's first operand (``executor._GraphLowering``), as the reference's
        mirror pass never mirrors ``Convolution`` or ``FullyConnected``.
    grad : optional custom gradient: ``grad(attrs) -> fn`` returning a
        function with a ``jax.custom_vjp`` already applied, or None to use
        plain ``jax.vjp`` over ``fn``.
    differentiable : False marks ops with no gradient (integer ops etc.).
    """

    def __init__(self, name: str, fn: Callable, num_outputs=1, needs_rng: bool = False,
                 differentiable: bool = True, doc: str = "", arg_names=None,
                 aux_args=(), host: bool = False, product: bool = False):
        self.name = name
        self.fn = fn
        self.num_outputs = num_outputs
        self.needs_rng = needs_rng
        self.product = product
        self.differentiable = differentiable
        # host=True: data-dependent shapes/rejection loops with no fixed-shape
        # XLA lowering; imperative path runs fn eagerly (no jit) so it may do
        # numpy work on host, like the reference's CPU-only op kernels.
        self.host = host
        self.doc = doc or (fn.__doc__ or "")
        self._arg_names = arg_names  # explicit array-input names, else derived
        self.aux_args = tuple(aux_args)  # names that are auxiliary states (BN stats)

    def arg_names(self):
        """Array-input parameter names, for symbolic auto-variable creation
        (the reference derives these from the C op signature the same way)."""
        if self._arg_names is None:
            import inspect
            names = []
            try:
                for p in inspect.signature(self.fn).parameters.values():
                    if p.kind == p.VAR_POSITIONAL:
                        names = None  # variadic: caller must pass arrays
                        break
                    if p.default is p.empty:
                        names.append(p.name)
                    else:
                        break  # optional arrays (bias=None etc.) need explicit
                               # arg_names= annotation at registration
            except (TypeError, ValueError):
                names = None
            self._arg_names = names
        return self._arg_names

    def out_count(self, attrs: Dict[str, Any]) -> int:
        if callable(self.num_outputs):
            return self.num_outputs(attrs)
        return self.num_outputs

    def __repr__(self):
        return f"OpDef({self.name})"


def _hashable(v):
    if isinstance(v, list):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, tuple):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    return v


def normalize_attrs(attrs: Dict[str, Any]) -> Tuple[Tuple[str, Any], ...]:
    return tuple(sorted((k, _hashable(v)) for k, v in attrs.items() if v is not None))


def register(name: str, num_outputs=1, needs_rng: bool = False,
             differentiable: bool = True, aliases: Sequence[str] = (),
             arg_names=None, aux_args=(), host: bool = False,
             product: bool = False):
    """Decorator: register ``fn`` as operator ``name`` (plus aliases)."""

    def deco(fn: Callable):
        opdef = OpDef(name, fn, num_outputs=num_outputs, needs_rng=needs_rng,
                      differentiable=differentiable, arg_names=arg_names,
                      aux_args=aux_args, host=host, product=product)
        _REGISTRY[name] = opdef
        for a in aliases:
            _REGISTRY[a] = opdef
        return fn

    return deco


def alias(existing: str, *names: str) -> None:
    opdef = _REGISTRY[existing]
    for n in names:
        _REGISTRY[n] = opdef


#: modules outside ``ops/`` that register operators on import; tried once on
#: a registry miss so symbolic graphs referencing them resolve without the
#: user importing the submodule (the reference registers everything at load).
_LAZY_PROVIDERS = ["mxnet_tpu.contrib.quantization", "mxnet_tpu.operator",
                   "mxnet_tpu.passes.fold"]


def get_op(name: str) -> OpDef:
    try:
        return _REGISTRY[name]
    except KeyError:
        pass
    provider_errs = []
    for mod in list(_LAZY_PROVIDERS):
        try:
            importlib.import_module(mod)
        except Exception as e:
            # leave in the list: a circular import during package init
            # resolves itself on a later lookup — but surface the error so
            # a genuinely broken provider isn't silently invisible
            provider_errs.append(f"{mod}: {e!r}")
            continue
        # the provider import may re-enter get_op (ops registering ops) and
        # already have removed itself via the inner call
        if mod in _LAZY_PROVIDERS:
            _LAZY_PROVIDERS.remove(mod)
        if name in _REGISTRY:
            return _REGISTRY[name]
    msg = f"operator {name!r} is not registered"
    if provider_errs:
        msg += " (lazy op providers failed to import: " \
               + "; ".join(provider_errs) + ")"
    raise MXNetError(msg)


def list_ops():
    return sorted(_REGISTRY)


@functools.lru_cache(maxsize=16384)
def jitted_op(name: str, attr_items: Tuple[Tuple[str, Any], ...]):
    """Per-op compiled-executable cache, keyed by (op, attrs); XLA adds the
    (shapes, dtypes) key underneath. This is the imperative fast path the
    reference gets from its async C++ engine (SURVEY.md stage 3): each
    distinct (op, attrs, shapes) pair compiles once, then dispatches async.
    """
    opdef = get_op(name)
    attrs = dict(attr_items)
    fn = functools.partial(opdef.fn, **attrs)
    return jax.jit(fn)


def op_vjp(fn, diff_idx: Tuple[int, ...]):
    """``(inputs, kw) -> (outputs, pullback)`` of ``fn(*inputs, **kw)`` with
    respect to ``inputs[i] for i in diff_idx``; the pullback is a pytree of
    residuals, so the pair can cross a ``jit`` boundary."""
    def pair(inputs, kw):
        def closed(*diff):
            full = list(inputs)
            for i, d in zip(diff_idx, diff):
                full[i] = d
            return fn(*full, **kw)
        return jax.vjp(closed, *(inputs[i] for i in diff_idx))
    return pair


@functools.lru_cache(maxsize=16384)
def jitted_op_vjp(name: str, attr_items: Tuple[Tuple[str, Any], ...],
                  diff_idx: Tuple[int, ...]):
    """The recorded form of ``jitted_op``: forward and residuals of one op as
    ONE cached executable per (op, attrs, differentiable slots, shapes), so
    a steady-state step under ``autograd.record()`` neither traces nor
    compiles. ``pullback`` below runs what it returns."""
    return jax.jit(op_vjp(jitted_op(name, attr_items), diff_idx))


@jax.jit
def pullback(vjp, ct):
    """Run a pullback that ``jitted_op_vjp`` returned. Its treedef (the
    backward jaxpr) is the same object on every call of one compiled
    forward, so this too compiles once per (op, attrs, shapes)."""
    return vjp(ct)
