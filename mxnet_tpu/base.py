"""Substrate: typed config registry, logging, errors, small utilities.

TPU-native replacement for the reference's dmlc-core slice: the ~60 `MXNET_*`
environment variables read via ``dmlc::GetEnv`` at point of use (reference
``docs/faq/env_var.md``) and the ``dmlc::Parameter`` declarative structs
(reference ``include/dmlc/parameter.h`` usage, e.g. ``src/imperative/cached_op.h:32``)
collapse here into one typed, env-overridable config registry (SURVEY.md 5.6).
"""
from __future__ import annotations

import logging
import os
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Type

__all__ = [
    "MXNetError",
    "TransientKVError",
    "TransientIOError",
    "CorruptRecordError",
    "config",
    "register_config",
    "get_env",
    "compile_cache_dir",
    "enable_compile_cache",
    "string_types",
    "numeric_types",
    "integer_types",
    "logger",
]

logger = logging.getLogger("mxnet_tpu")

string_types = (str,)
numeric_types = (float, int)
integer_types = (int,)


class MXNetError(RuntimeError):
    """Framework error type (mirrors the reference's ``MXNetError`` raised
    through the C-API thread-local error string, ``src/c_api/c_api_error.cc``)."""


class TransientKVError(MXNetError):
    """A kvstore operation failed for a plausibly-transient reason (the
    coordination service was briefly unreachable, a publish lost a race)
    after its internal retry budget was exhausted. The resilience layer
    (``mxnet_tpu.resilience.retry_transient``) treats this — unlike a bare
    ``MXNetError`` — as retryable with backoff rather than fatal."""


class TransientIOError(MXNetError):
    """A data read failed for a plausibly-transient reason (torn read off a
    network filesystem, a briefly-unreachable object store). Like
    :class:`TransientKVError`, ``retry_transient`` retries it with backoff
    instead of killing the run; ``io.ResilientDataIter`` raises it through
    only after the ``MXNET_IO_RETRY_*`` budget is exhausted."""


class CorruptRecordError(MXNetError):
    """A record decoded to garbage (bad magic, truncated payload, failed
    checksum). Deliberately NOT transient — re-reading the same bytes gives
    the same garbage — but ``io.ResilientDataIter`` may *skip* the batch
    within its ``MXNET_IO_SKIP_BUDGET`` instead of failing the run."""


@dataclass
class _ConfigEntry:
    name: str
    default: Any
    typ: Type
    doc: str = ""
    validator: Optional[Callable[[Any], bool]] = None


class _ConfigRegistry:
    """Typed config registry, env-overridable.

    Every knob is registered once with a type, default and docstring; reads
    check ``os.environ`` first (so ``MXNET_ENGINE_TYPE=...`` style overrides
    keep working) and fall back to programmatic ``set()`` values, then the
    default.
    """

    def __init__(self) -> None:
        self._entries: Dict[str, _ConfigEntry] = {}
        self._values: Dict[str, Any] = {}
        self._lock = threading.Lock()

    def register(self, name: str, default: Any, typ: Type = None, doc: str = "",
                 validator: Optional[Callable[[Any], bool]] = None) -> None:
        typ = typ or type(default)
        with self._lock:
            self._entries[name] = _ConfigEntry(name, default, typ, doc, validator)

    def _coerce(self, entry: _ConfigEntry, raw: str) -> Any:
        if entry.typ is bool:
            return raw.lower() not in ("0", "false", "off", "")
        return entry.typ(raw)

    def get(self, name: str, default: Any = None) -> Any:
        env = os.environ.get(name)
        entry = self._entries.get(name)
        if env is not None:
            if entry is not None:
                return self._coerce(entry, env)
            return env
        if name in self._values:
            return self._values[name]
        if entry is not None:
            return entry.default
        return default

    def set(self, name: str, value: Any) -> None:
        entry = self._entries.get(name)
        if entry is not None and entry.validator is not None and not entry.validator(value):
            raise MXNetError(f"invalid value {value!r} for config {name}")
        with self._lock:
            self._values[name] = value

    def describe(self) -> str:
        lines = []
        for e in sorted(self._entries.values(), key=lambda e: e.name):
            lines.append(f"{e.name} (default={e.default!r}, type={e.typ.__name__}): {e.doc}")
        return "\n".join(lines)

    def entries(self) -> Dict[str, _ConfigEntry]:
        return dict(self._entries)


config = _ConfigRegistry()


def register_config(name: str, default: Any, typ: Type = None, doc: str = "",
                    validator=None) -> None:
    config.register(name, default, typ, doc, validator)


def get_env(name: str, default: Any = None) -> Any:
    return config.get(name, default)


# Core knobs (parity with reference docs/faq/env_var.md where meaningful on TPU).
register_config("MXNET_ENGINE_TYPE", "XLAAsync", str,
                "Scheduler flavor. XLAAsync rides XLA's async dispatch; "
                "Naive forces synchronous execution after every op (debug).")
register_config("MXNET_EXEC_BULK_EXEC_TRAIN", True, bool,
                "Fuse op segments into one compiled XLA program during training.")
register_config("MXNET_EXEC_BULK_EXEC_INFERENCE", True, bool,
                "Fuse op segments into one compiled XLA program during inference.")
register_config("MXNET_BACKWARD_DO_MIRROR", False, bool,
                "Trade FLOPs for memory via rematerialization (jax.checkpoint).")
register_config("MXNET_KVSTORE_BIGARRAY_BOUND", 1 << 20, int,
                "Size above which a gradient is sharded across the reduce axis.")
register_config("MXNET_KVSTORE_ASYNC_MAX_STALENESS", 0, int,
                "dist_async only: max pushes a key's owner may lag before "
                "pushers throttle. 0 = unbounded (reference async behavior).")
register_config("MXNET_KVSTORE_ASYNC_GAP_TIMEOUT", 30.0, float,
                "dist_async only: seconds the key owner waits on a missing "
                "push sequence number (a pusher that died mid-send) before "
                "skipping it.")
register_config("MXNET_UPDATE_AGGREGATION_SIZE", 4, int,
                "Number of gradient tensors aggregated per fused allreduce bucket.")
register_config("MXNET_KVSTORE_HEARTBEAT_INTERVAL", 2.0, float,
                "Seconds between liveness heartbeats a dist kvstore rank "
                "writes to the coordination service.")
register_config("MXNET_KVSTORE_BARRIER_TIMEOUT", 300.0, float,
                "Seconds a dist kvstore barrier waits before raising with a "
                "dead-peer diagnosis (num_dead_node).")
register_config("MXNET_ENFORCE_DETERMINISM", False, bool,
                "Disallow non-deterministic reductions.")
register_config("MXNET_PROFILER_AUTOSTART", False, bool,
                "Start the chrome-trace profiler at import time.")
register_config("MXNET_DEFAULT_DTYPE", "float32", str,
                "Default dtype for created arrays.")
register_config("MXNET_TPU_MATMUL_PRECISION", "default", str,
                "jax matmul precision: default|high|highest.")
register_config("MXNET_SEED", -1, int, "Global PRNG seed; -1 = nondeterministic.")
register_config("MXNET_KV_RETRY_ATTEMPTS", 5, int,
                "Max attempts for transient kvstore coordination-service "
                "operations (e.g. dist_async weight publish) before raising "
                "TransientKVError.")
register_config("MXNET_KV_RETRY_BASE", 0.05, float,
                "Initial backoff delay (seconds) between kvstore retries; "
                "doubles every attempt.")
register_config("MXNET_KV_RETRY_MAX", 2.0, float,
                "Upper bound (seconds) on a single kvstore retry backoff "
                "delay.")
register_config("MXNET_KV_RETRY_JITTER", 0.25, float,
                "Multiplicative jitter fraction on kvstore retry delays "
                "(delay *= 1 + jitter*U[0,1)) to decorrelate rank retries.")
register_config("MXNET_RESILIENCE_RETRY_ATTEMPTS", 3, int,
                "Max attempts resilience.retry_transient makes around a "
                "transiently-failing training step.")
register_config("MXNET_RESILIENCE_RETRY_BASE", 0.5, float,
                "Initial backoff delay (seconds) for resilience.retry_transient.")
register_config("MXNET_RESILIENCE_RETRY_MAX", 30.0, float,
                "Upper bound (seconds) on a single resilience retry delay.")
register_config("MXNET_RESILIENCE_SAVE_EVERY", 0, int,
                "Default ResilientTrainer checkpoint cadence in steps "
                "(0 = only explicit/preemption saves).")
register_config("MXNET_RESILIENCE_KEEP", 3, int,
                "Committed checkpoints a ResilientTrainer keeps before "
                "pruning old steps.")
register_config("MXNET_RESILIENCE_STEP_DEADLINE", 0.0, float,
                "Seconds a single ResilientTrainer step may take before the "
                "watchdog dumps all thread stacks and fails loud "
                "(0 = watchdog off).")


# ---- persistent compilation cache: the one rule ---------------------------
# The directory is part of the cache key, so it must never move. When
# JAX_COMPILATION_CACHE_DIR is set jax reads it itself and no directory is
# set in code; otherwise the cache lives at one fixed path inside the
# checkout (git-ignored) — never one built from a temporary name, pid or
# time. chip_smoke.py, chipbench and the tools all come through here; no
# other site names a cache directory.
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def compile_cache_dir() -> str:
    """Where JAX's persistent compilation cache lives under the rule
    above."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _CHECKOUT_CACHE


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on under the rule above and
    return its directory. Call before the first compile."""
    path = compile_cache_dir()
    if path == _CHECKOUT_CACHE:     # the variable, when set, is read by jax
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class classproperty:  # noqa: N801  (descriptor, lowercase by convention)
    def __init__(self, fget):
        self.fget = fget

    def __get__(self, obj, owner):
        return self.fget(owner)
