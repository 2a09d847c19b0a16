"""mxnet_tpu — a TPU-native deep-learning framework with MXNet's capabilities.

A brand-new framework (not a port): the reference's C++ dependency engine,
CUDA/mshadow kernels, NNVM memory planning and NCCL/ps-lite communication are
replaced by XLA async dispatch, jax/Pallas compute, whole-graph XLA lowering
and ICI/DCN collectives. See SURVEY.md at the repo root for the blueprint and
per-module docstrings for reference file:line parity citations.

Public surface (mirrors ``python/mxnet``):
    mx.nd        imperative arrays       mx.sym      symbolic graphs
    mx.autograd  tape autograd           mx.gluon    imperative models + JIT
    mx.mod       Module API              mx.kv       KVStore (XLA collectives)
    mx.io        data iterators          mx.optimizer / mx.metric / mx.init
"""
from __future__ import annotations

import time as _time
_t0 = _time.perf_counter()   # the span mxnet_tpu.import, recorded at the end

__version__ = "0.1.0"

import jax as _jax

# MXNet exposes float64/int64 dtypes on request; jax hides them by default.
# Default creation paths still produce float32 (MXNET_DEFAULT_DTYPE).
_jax.config.update("jax_enable_x64", True)

from . import base
from .base import (MXNetError, TransientKVError, TransientIOError,
                   CorruptRecordError)
from .context import Context, cpu, gpu, tpu, cpu_pinned, current_context, num_gpus, num_tpus, gpu_memory_info
from . import ops
from . import ndarray
from . import ndarray as nd
from . import autograd
from . import random
from .ndarray import NDArray
from .attribute import AttrScope
from . import name
from . import attribute

# Under tools/launch.py the coordinator env trio is set: join the
# jax.distributed cluster NOW, before anything can initialize the XLA
# backend (the reference's ps-lite StartAsync happens equally early via the
# tracker env). No-op outside a launched job.
import os as _os
if _os.environ.get("JAX_COORDINATOR_ADDRESS") \
        or _os.environ.get("MXNET_COORDINATOR_ADDRESS"):
    from .kvstore import _maybe_join_cluster as _join
    _join()
    del _join

# Submodules imported lazily to keep import light and avoid cycles.
import importlib as _importlib

_lazy = {
    "symbol": ".symbol", "sym": ".symbol",
    "gluon": ".gluon",
    "optimizer": ".optimizer",
    "metric": ".metric",
    "initializer": ".initializer", "init": ".initializer",
    "lr_scheduler": ".lr_scheduler",
    "io": ".io",
    "recordio": ".recordio",
    "image": ".image",
    "kvstore": ".kvstore", "kv": ".kvstore",
    "module": ".module", "mod": ".module",
    "model": ".model",
    "callback": ".callback",
    "monitor": ".monitor",
    "profiler": ".profiler",
    "parallel": ".parallel",
    "rnn": ".rnn",
    "visualization": ".visualization", "viz": ".visualization",
    "rtc": ".rtc",
    "operator": ".operator",
    "registry": ".registry",
    "kvstore_server": ".kvstore_server",
    "engine": ".engine",
    "executor": ".executor",
    "test_utils": ".test_utils",
    "util": ".util",
    "interop": ".interop",
    "contrib": ".contrib",
    "checkpoint": ".checkpoint",
    "gradient_compression": ".gradient_compression",
    "resilience": ".resilience",
    "analysis": ".analysis",
    "observability": ".observability",
    "tuner": ".tuner",
    "passes": ".passes",
    "serving": ".serving",
    "quant": ".quant",
}


from .observability import metrics as _metrics, spans as _spans  # noqa: E402


def __getattr__(name):
    if name in _lazy:
        t0 = _time.perf_counter()
        mod = _importlib.import_module(_lazy[name], __name__)
        globals()[name] = mod
        if _metrics.enabled():
            _spans.record("mxnet_tpu.import", t0, _time.perf_counter())
        return mod
    raise AttributeError(f"module 'mxnet_tpu' has no attribute {name!r}")


if _metrics.enabled():
    _spans.record("mxnet_tpu.import", _t0, _time.perf_counter())
