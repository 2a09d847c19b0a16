"""Runtime-compiled user kernels (``mx.rtc``) — the Pallas escape hatch.

Reference parity: ``src/common/rtc.cc:35-49`` / ``include/mxnet/rtc.h:39``
(``CudaModule``: frontend-supplied CUDA source JIT-compiled with NVRTC and
launched on engine streams) and ``python/mxnet/rtc.py``.

TPU-first: instead of CUDA C source, the user supplies a *Pallas kernel
function* (refs in, refs out). ``PallasModule.get_kernel`` wraps it in a
``pl.pallas_call`` and the returned :class:`Kernel` launches on NDArray
arguments, with a grid in place of CUDA's block/grid dims. The kernel
compiles to a Mosaic kernel for the TPU; the Pallas interpreter runs it only
when the caller asks (``launch(interpret=True)`` or
``MXTPU_PALLAS_INTERPRET=1``, the same switch ``ops.pallas_kernels`` reads),
never because no chip was found.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .base import MXNetError
from .ops.pallas_kernels import _interpret

__all__ = ["PallasModule", "Kernel", "CudaModule"]


class Kernel:
    """A launchable kernel (reference ``CudaModule::Kernel``, rtc.h:58)."""

    def __init__(self, name: str, kernel_fn: Callable, module: "PallasModule"):
        self._name = name
        self._kernel_fn = kernel_fn
        self._module = module
        self._cache: Dict[Tuple, Callable] = {}

    @property
    def name(self) -> str:
        return self._name

    def _jitted(self, raw, grid=None, out_shapes=None, out_dtypes=None,
                in_specs=None, out_specs=None, interpret=None,
                pl_kwargs=None) -> Callable:
        """The jitted ``pallas_call`` for arguments shaped like ``raw``
        (arrays or ``jax.ShapeDtypeStruct``s), cached per signature."""
        from jax.experimental import pallas as pl

        if out_shapes is None:
            out_shapes = [tuple(raw[0].shape)]
        if out_dtypes is None:
            out_dtypes = [raw[0].dtype] * len(out_shapes)
        if interpret is None:
            interpret = _interpret()

        key = (tuple(tuple(s) for s in out_shapes), tuple(map(str, out_dtypes)),
               grid, interpret,
               tuple((a.shape, str(a.dtype)) for a in raw))
        fn = self._cache.get(key)
        if fn is None:
            out_struct = [jax.ShapeDtypeStruct(tuple(s), d)
                          for s, d in zip(out_shapes, out_dtypes)]
            call_kwargs = dict(pl_kwargs or {})
            if grid is not None:
                call_kwargs["grid"] = grid
            if in_specs is not None:
                call_kwargs["in_specs"] = in_specs
            if out_specs is not None:
                call_kwargs["out_specs"] = out_specs
            call = pl.pallas_call(
                self._kernel_fn,
                out_shape=out_struct[0] if len(out_struct) == 1 else out_struct,
                interpret=interpret, **call_kwargs)

            def run(*arrays):
                # the package turns jax_enable_x64 on, under which the
                # Python ints of an ordinary index map (``lambda i: (i, 0)``)
                # trace as i64 and Mosaic refuses the kernel: user kernels
                # are traced with it off
                with jax.enable_x64(False):
                    return call(*arrays)

            fn = jax.jit(run)
            self._cache[key] = fn
        return fn

    def launch(self, args: Sequence[Any], ctx=None, grid=None,
               out_shapes=None, out_dtypes=None, in_specs=None,
               out_specs=None, interpret: Optional[bool] = None, **pl_kwargs):
        """Launch on NDArray/array args; returns NDArray output(s).

        ``grid``: pallas grid tuple (replaces CUDA grid/block dims).
        ``out_shapes``: shapes of outputs; defaults to the first arg's shape.
        ``interpret``: run under the Pallas interpreter instead of compiling
        for the TPU; ``None`` reads ``MXTPU_PALLAS_INTERPRET``.
        """
        from .ndarray.ndarray import _wrap, _unwrap

        raw = [_unwrap(a) for a in args]
        fn = self._jitted(raw, grid, out_shapes, out_dtypes, in_specs,
                          out_specs, interpret, pl_kwargs)
        out = fn(*raw)
        if isinstance(out, (tuple, list)):
            return [_wrap(o) for o in out]
        return _wrap(out)


class PallasModule:
    """A named collection of Pallas kernels (reference CudaModule, rtc.h:39).

    Parameters
    ----------
    kernels : dict name -> pallas kernel function, OR a single function
        (registered under its ``__name__``).
    """

    def __init__(self, kernels, exports=None):
        if callable(kernels):
            kernels = {kernels.__name__: kernels}
        self._kernels: Dict[str, Callable] = dict(kernels)
        if exports is not None:
            missing = set(exports) - set(self._kernels)
            if missing:
                raise MXNetError("exported kernels not found: %s" % missing)
            self._kernels = {k: self._kernels[k] for k in exports}

    def get_kernel(self, name: str, signature: str = "") -> Kernel:
        """Look up a kernel. ``signature`` is accepted for reference-API
        compatibility but unused (Python kernels carry their own types)."""
        if name not in self._kernels:
            raise MXNetError("kernel %r not found in module (have: %s)"
                             % (name, sorted(self._kernels)))
        return Kernel(name, self._kernels[name], self)


class CudaModule:
    """Unavailable on TPU — kept so reference code fails with a clear error
    pointing at :class:`PallasModule`."""

    def __init__(self, *a, **kw):
        raise MXNetError(
            "CudaModule (NVRTC runtime CUDA compilation) is not available on "
            "TPU. Write the kernel as a Pallas function and use "
            "mx.rtc.PallasModule instead.")
