"""Device contexts.

Reference parity: ``include/mxnet/base.h`` ``Context`` (devtype/devid) and
``python/mxnet/context.py``. On TPU the context maps onto a ``jax.Device``;
``mx.tpu(i)`` is first-class, ``mx.gpu(i)`` aliases to the i-th accelerator so
reference scripts run unchanged, and ``mx.cpu()`` is the host platform.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus", "num_tpus", "gpu_memory_info"]


class Context:
    """A device context. Hashable, comparable, usable as a ``with`` target
    (mirroring ``python/mxnet/context.py:Context``)."""

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}
    _default_ctx = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            self.device_type, self.device_id = device_type.device_type, device_type.device_id
        else:
            if device_type not in self.devstr2type:
                raise ValueError(f"unknown device type {device_type!r}")
            self.device_type = device_type
            self.device_id = device_id
        self._old_ctx: Optional[Context] = None

    @property
    def device_typeid(self) -> int:
        return self.devstr2type[self.device_type]

    # -- jax mapping ---------------------------------------------------------
    def jax_device(self) -> jax.Device:
        """Resolve this context to a concrete jax.Device."""
        kind = self.device_type
        if kind in ("cpu", "cpu_pinned", "cpu_shared"):
            devs = _devices_of("cpu")
            if not devs:  # cpu backend always exists in practice
                devs = jax.local_devices()
            return devs[min(self.device_id, len(devs) - 1)]
        # gpu is an alias for "the accelerator" so reference scripts with
        # ctx=mx.gpu() run unchanged on TPU hosts.
        accel = _accelerator_devices()
        if not accel:
            raise RuntimeError(f"no accelerator devices for context {self}")
        if self.device_id >= len(accel):
            raise RuntimeError(f"{self}: only {len(accel)} device(s) present")
        return accel[self.device_id]

    # -- equality / printing -------------------------------------------------
    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    def __enter__(self):
        self._old_ctx = getattr(Context._default_ctx, "value", None)
        Context._default_ctx.value = self
        return self

    def __exit__(self, *exc):
        Context._default_ctx.value = self._old_ctx
        return False

    def empty_cache(self):
        """Reference: ``MXStorageEmptyCache``. XLA owns the HBM pool; this is
        a hint only."""
        try:
            for buf in jax.live_arrays():
                pass  # XLA's allocator has no user-visible trim; no-op by design
        except Exception:
            pass

    def memory_info(self) -> dict:
        """HBM pool observability (reference GPUPooledStorageManager stats,
        pooled_storage_manager.h:58-66 / MXGetGPUMemoryInformation): bytes
        in use / limit / peak from the device allocator, plus the count and
        bytes of live arrays this process holds on the device."""
        dev = self.jax_device()
        info = {"device": str(dev)}
        try:
            stats = dev.memory_stats() or {}
            info.update({
                "bytes_in_use": stats.get("bytes_in_use"),
                "bytes_limit": stats.get("bytes_limit"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                "largest_alloc_size": stats.get("largest_alloc_size"),
            })
        except Exception:
            info["bytes_in_use"] = None   # backend exposes no allocator stats
        live_n = live_b = 0
        try:
            for a in jax.live_arrays():
                if dev in getattr(a, "devices", lambda: set())():
                    live_n += 1
                    live_b += a.size * a.dtype.itemsize
        except Exception:
            pass
        info["live_arrays"] = live_n
        info["live_array_bytes"] = live_b
        return info


def _devices_of(platform: str):
    """PROCESS-LOCAL devices: like the reference, a worker's Context
    addresses its own devices — under jax.distributed the global list
    contains other hosts' devices, which are not addressable here."""
    try:
        return jax.local_devices(backend=platform)
    except RuntimeError:
        return []


def _accelerator_devices():
    """This process's non-CPU devices; empty on a host with no chip — an
    accelerator context then raises (``Context.jax_device``), like the
    reference's ``mx.gpu()`` on a GPU-less build, and is never served by
    the host CPU."""
    return [d for d in jax.local_devices() if d.platform != "cpu"]


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Alias for the accelerator device (TPU here); keeps reference scripts
    (``ctx=mx.gpu(0)``) working."""
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def num_gpus() -> int:
    return len(_accelerator_devices())


def num_tpus() -> int:
    return num_gpus()


def gpu_memory_info(device_id: int = 0):
    """(free_bytes, total_bytes) of the accelerator's HBM — reference
    ``mx.context.gpu_memory_info`` / ``MXGetGPUMemoryInformation64``. Total
    is the allocator's byte limit; on backends that expose no allocator
    stats (some PJRT plugins) both values are 0."""
    info = gpu(device_id).memory_info()
    total = info.get("bytes_limit") or 0
    used = info.get("bytes_in_use") or 0
    return (max(total - used, 0), total)


def current_context() -> Context:
    ctx = getattr(Context._default_ctx, "value", None)
    if ctx is None:
        # default to the accelerator if one exists, else cpu — unlike the
        # reference (default cpu), because on a TPU host that is always what
        # the user means; tests pin JAX_PLATFORMS=cpu so this stays cpu there.
        ctx = Context("tpu", 0) if _accelerator_devices() else Context("cpu", 0)
        Context._default_ctx.value = ctx
    return ctx
