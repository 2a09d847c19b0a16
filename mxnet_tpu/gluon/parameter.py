"""Gluon Parameter / ParameterDict.

Reference parity: ``python/mxnet/gluon/parameter.py`` (Parameter with deferred
initialization :118+, ParameterDict :500+). On TPU a parameter's per-device
replication (``list_data``) generalizes to a ``jax.sharding`` placement: a
Parameter can carry a named-sharding spec consumed by the parallel trainer
(SURVEY.md §2.3 tensor parallelism "for free" via pjit).
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Optional

import numpy as np

from .. import autograd, initializer
from ..base import MXNetError
from ..context import Context, cpu, current_context
from ..ndarray import NDArray, array as nd_array, zeros as nd_zeros
from ..observability import spans as _spans

__all__ = ["DeferredInitializationError", "Parameter", "Constant", "ParameterDict"]


class DeferredInitializationError(MXNetError):
    """Raised when a parameter's value is requested before its shape is known."""


class Parameter:
    def __init__(self, name: str, grad_req: str = "write", shape=None,
                 dtype="float32", lr_mult: float = 1.0, wd_mult: float = 1.0,
                 init=None, allow_deferred_init: bool = False,
                 differentiable: bool = True, stype: str = "default",
                 grad_stype: str = "default", sharding=None):
        self.name = name
        self._grad_req = grad_req if differentiable else "null"
        if isinstance(shape, int):
            shape = (shape,)
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self.sharding = sharding  # optional jax PartitionSpec for pjit paths
        self._data: Optional[NDArray] = None
        self._deferred_init = None  # (init, ctx) pending shape
        self._ctx: Optional[Context] = None

    # ------------------------------------------------------------- lifecycle
    @property
    def grad_req(self) -> str:
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req: str) -> None:
        self._grad_req = req
        if self._data is not None:
            if req == "null":
                self._data._grad = None
                self._data._ag_node = None
            else:
                self._data.attach_grad(req)

    def _shape_known(self) -> bool:
        return self.shape is not None and all(s > 0 for s in self.shape)

    def initialize(self, init=None, ctx=None, default_init=initializer.Uniform(),
                   force_reinit: bool = False) -> None:
        if self._data is not None and not force_reinit:
            return
        if ctx is None:
            ctx = current_context()
        if isinstance(ctx, (list, tuple)):
            ctx = ctx[0]  # single-process SPMD: one logical placement
        self._ctx = ctx
        chosen = init or self.init or default_init
        if not self._shape_known():
            if not self.allow_deferred_init:
                raise MXNetError(
                    f"cannot initialize parameter {self.name!r}: shape unknown "
                    f"({self.shape}); set allow_deferred_init=True or provide shape")
            self._deferred_init = (chosen, ctx)
            return
        self._finish_init(chosen, ctx)

    def _finish_init(self, init, ctx) -> None:
        perm = getattr(self, "_init_perm", None)
        if perm is not None:
            # draw in the canonical (reference NCHW-style) axis order, then
            # permute — channel-last weights get the exact same init values
            # and fan-in/fan-out scaling as their channel-first twins
            canon = [0] * len(self.shape)
            for i, p in enumerate(perm):
                canon[p] = self.shape[i]
            host = np.zeros(tuple(canon), dtype=self.dtype)
            initializer.create(init)(self.name, host)
            host = np.ascontiguousarray(host.transpose(perm))
        else:
            host = np.zeros(self.shape, dtype=self.dtype)
            initializer.create(init)(self.name, host)
        self._data = nd_array(host, ctx=ctx, dtype=self.dtype)
        if self._grad_req != "null":
            self._data.attach_grad(self._grad_req)
        self._deferred_init = None

    def _finish_deferred_init(self, shape) -> None:
        if self._deferred_init is None:
            return
        self.shape = tuple(shape)
        init, ctx = self._deferred_init
        self._finish_init(init, ctx)

    # ------------------------------------------------------------- accessors
    def data(self, ctx=None) -> NDArray:
        if self._data is None:
            if self._deferred_init is not None:
                raise DeferredInitializationError(
                    f"parameter {self.name!r} has deferred init; first forward "
                    f"must infer its shape")
            raise MXNetError(f"parameter {self.name!r} is not initialized")
        return self._data

    def list_data(self) -> List[NDArray]:
        return [self.data()]

    def list_ctx(self):
        return [self._ctx or current_context()]

    @property
    def grad(self) -> Optional[NDArray]:
        d = self.data()
        if d._grad is None:
            raise MXNetError(f"parameter {self.name!r} has grad_req='null'")
        return d._grad

    def list_grad(self):
        return [self.grad]

    @_spans.span("gluon.set_data")
    def set_data(self, data) -> None:
        if self._data is None:
            if self._deferred_init is not None:
                self.shape = tuple(data.shape)
                init, ctx = self._deferred_init
                self._finish_init(init, ctx)
            else:
                raise MXNetError(f"parameter {self.name!r} is not initialized")
        arr = data if isinstance(data, NDArray) else nd_array(data)
        self._data._set_data(arr.astype(self.dtype, copy=False)._data)

    def zero_grad(self) -> None:
        if self._data is not None and self._data._grad is not None:
            g = self._data._grad
            g._set_data((g._data * 0))

    def reset_ctx(self, ctx) -> None:
        if self._data is not None:
            self._data._set_data(self._data.as_in_context(ctx)._data)
            if self._grad_req != "null":
                self._data.attach_grad(self._grad_req)
            self._ctx = ctx

    def cast(self, dtype) -> None:
        self.dtype = dtype
        if self._data is not None:
            self._data._set_data(self._data.astype(dtype)._data)
            if self._grad_req != "null":
                self._data.attach_grad(self._grad_req)

    def var(self):
        from .. import symbol as sym
        return sym.Variable(self.name, shape=self.shape, dtype=self.dtype)

    def __repr__(self):
        return f"Parameter {self.name} (shape={self.shape}, dtype={self.dtype})"


class Constant(Parameter):
    """Non-differentiable parameter with a fixed value (reference
    gluon/parameter.py:Constant)."""

    def __init__(self, name, value):
        value = np.asarray(value)
        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=str(value.dtype),
                         init=initializer.Constant(0.0))
        self._value = value

    def _finish_init(self, init, ctx):
        self._data = nd_array(self._value, ctx=ctx)
        self._deferred_init = None


class ParameterDict:
    """Name-scoped dictionary of parameters with a shared prefix."""

    def __init__(self, prefix: str = "", shared: Optional["ParameterDict"] = None):
        self._prefix = prefix
        self._params: "OrderedDict[str, Parameter]" = OrderedDict()
        self._shared = shared

    @property
    def prefix(self) -> str:
        return self._prefix

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def __iter__(self):
        return iter(self._params)

    def __len__(self):
        return len(self._params)

    def __getitem__(self, name: str) -> Parameter:
        return self._params[name]

    def __contains__(self, name) -> bool:
        return name in self._params

    def get(self, name: str, **kwargs) -> Parameter:
        """Get-or-create ``prefix+name`` (reference ParameterDict.get)."""
        full = self._prefix + name
        param = self._get_impl(full)
        if param is None:
            param = Parameter(full, **kwargs)
            self._params[full] = param
        else:
            for k, v in kwargs.items():
                if k == "shape" and v is not None and param.shape is not None:
                    v = tuple(v)
                    if v != param.shape and all(s > 0 for s in param.shape):
                        raise MXNetError(
                            f"parameter {full!r} shape mismatch: {param.shape} vs {v}")
                    continue
                if getattr(param, k, None) in (None, "float32") and v is not None \
                        and k in ("shape", "dtype", "init"):
                    setattr(param, k, v)
        return param

    def get_constant(self, name: str, value=None) -> Constant:
        full = self._prefix + name
        p = self._get_impl(full)
        if p is None:
            p = Constant(full, value)
            self._params[full] = p
        return p

    def _get_impl(self, full_name):
        if full_name in self._params:
            return self._params[full_name]
        if self._shared is not None:
            p = self._shared._get_impl(full_name)
            if p is not None:
                self._params[full_name] = p
            return p
        return None

    def update(self, other: "ParameterDict") -> None:
        for k, v in other.items():
            if k in self._params and self._params[k] is not v:
                raise MXNetError(f"duplicate parameter name {k!r}")
            self._params[k] = v

    @_spans.span("gluon.initialize")
    def initialize(self, init=initializer.Uniform(), ctx=None, verbose=False,
                   force_reinit=False) -> None:
        for p in self.values():
            p.initialize(None, ctx, init, force_reinit=force_reinit)

    def zero_grad(self) -> None:
        for p in self.values():
            p.zero_grad()

    def reset_ctx(self, ctx) -> None:
        for p in self.values():
            p.reset_ctx(ctx)

    def setattr(self, name, value) -> None:
        for p in self.values():
            setattr(p, name, value)

    def save(self, fname: str, strip_prefix: str = "") -> None:
        from ..ndarray import save as nd_save
        out = {}
        for name, p in self.items():
            key = name[len(strip_prefix):] if name.startswith(strip_prefix) else name
            out[key] = p.data()
        nd_save(fname, out)

    def load(self, fname: str, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix="") -> None:
        from ..ndarray import load as nd_load
        loaded = nd_load(fname)
        loaded = {restore_prefix + k: v for k, v in loaded.items()}
        for name, p in self.items():
            if name in loaded:
                if p._data is None and p._deferred_init is None:
                    p.shape = tuple(loaded[name].shape)
                    p.initialize(ctx=ctx)
                p.set_data(loaded[name])
            elif not allow_missing:
                raise MXNetError(f"parameter {name!r} missing in file {fname}")
        if not ignore_extra:
            extra = set(loaded) - set(self.keys())
            if extra:
                raise MXNetError(f"file {fname} has extra parameters {sorted(extra)}")

    def __repr__(self):
        lines = "\n".join(f"  {p!r}" for p in self.values())
        return f"ParameterDict(prefix={self._prefix!r}\n{lines})"
