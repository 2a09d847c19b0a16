"""Collectives bandwidth lab — measure the interconnect, then pick levers.

ROADMAP item 5's measurement half: the reference grew a
``tools/bandwidth/measure.py`` harness to size its allreduce tree against
PCIe/NVLink reality; the TPU-native twin measures the XLA collective path —
psum / reduce-scatter / all-gather / ppermute bytes/sec vs device count and
payload size, plus the 2-bit-compressed allreduce (error-feedback codec
over an allgather of packed codes) against its dense baseline — so the
``DataParallelTrainer`` comm levers (``grad_reduce=``,
``grad_reduce_dtype=``, ``bucket_bytes=``, ``compression=``) are chosen
from data, not vibes ("measure bytes/s per collective, then pick the
reduction strategy from data" — the Julia-to-TPU pod-scaling methodology,
PAPERS.md).

Every measurement persists as a :class:`~mxnet_tpu.observability.xcost.
CostLedger` row (``label="collbench"``) and publishes
``mxtpu_collective_bytes_total`` / ``mxtpu_collective_ms`` telemetry.
:func:`scaling_row` is the multichip training measurement: img/s/chip
at N devices vs 1 — the real scaling-efficiency number the ≥90% claim is
judged against.

Reported bandwidth is **algorithm bandwidth**: the ring-algorithm bus
bytes each chip moves per operation (all-reduce ``2(n-1)/n``, reduce-
scatter / all-gather ``(n-1)/n``, ppermute ``1x`` of the payload) divided
by wall time — the unit NCCL/collective benchmarks report, so numbers
compare across device counts.

CLI: ``tools/collbench.py``. Docs:
``docs/performance.md`` "Scale-out performance".
"""
from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError, logger
from ..observability import catalog as _telemetry
from ..observability import metrics as _metrics
from ..observability import xcost as _xcost
from . import collectives as _coll

__all__ = ["OPS", "algo_bytes", "bench_collective", "bench_compression",
           "run", "scaling_row", "default_device_counts"]

OPS = ("psum", "reduce_scatter", "all_gather", "ppermute")


def default_device_counts(n_total: Optional[int] = None) -> List[int]:
    """1, 2, 4, ... up to the device count (always including the total):
    the sweep axis of the bytes/sec-vs-devices curve."""
    n_total = int(n_total if n_total is not None else len(jax.devices()))
    counts = []
    c = 1
    while c < n_total:
        counts.append(c)
        c *= 2
    counts.append(n_total)
    return sorted(set(counts))


def _submesh(n_devices: int, axis: str) -> Mesh:
    devices = jax.devices()
    if n_devices > len(devices):
        raise MXNetError(f"collbench: asked for {n_devices} devices, have "
                         f"{len(devices)}")
    return Mesh(np.asarray(devices[:n_devices]), (axis,))


def algo_bytes(op: str, payload_bytes: int, n_devices: int) -> int:
    """Ring-algorithm bus bytes per chip for one operation on a
    ``payload_bytes`` global payload."""
    n = max(1, int(n_devices))
    if op == "psum":
        return int(2 * (n - 1) / n * payload_bytes)
    if op in ("reduce_scatter", "all_gather"):
        return int((n - 1) / n * payload_bytes)
    if op == "ppermute":
        return int(payload_bytes) if n > 1 else 0
    raise MXNetError(f"collbench: unknown op {op!r} (want one of {OPS})")


@functools.lru_cache(maxsize=64)
def _coll_fn(op: str, mesh: Mesh, axis: str):
    n = mesh.shape[axis]

    def f(x):                       # x: this member's local block (m,)
        if op == "psum":
            return _coll.allreduce(x, axis)
        if op == "reduce_scatter":
            return _coll.reduce_scatter(x, axis)       # (m/n,)
        if op == "all_gather":
            return _coll.allgather(x, axis)            # (n*m,)
        if op == "ppermute":
            return _coll.ppermute(x, axis,
                                  [(i, (i + 1) % n) for i in range(n)])
        raise MXNetError(f"collbench: unknown op {op!r}")

    return jax.jit(shard_map(f, mesh=mesh, in_specs=P(axis),
                             out_specs=P(axis)))


def _payload(payload_bytes: int, n: int, dtype) -> jnp.ndarray:
    """A global array of ~payload_bytes, sized so every op tiles: the
    element count is a multiple of n*n (reduce_scatter needs the local
    block divisible by n again)."""
    itemsize = jnp.dtype(dtype).itemsize
    quantum = n * n
    elems = max(quantum, (payload_bytes // itemsize) // quantum * quantum)
    rng = np.random.RandomState(0)
    return jnp.asarray(rng.uniform(-1, 1, (elems,)).astype(dtype))


def bench_collective(op: str, n_devices: Optional[int] = None,
                     payload_bytes: int = 1 << 20, dtype="float32",
                     steps: int = 10, warmup: int = 2,
                     axis: str = "dp") -> Dict[str, Any]:
    """Measure one collective: returns a ledger-shaped row with ``ms``
    (mean wall per op), ``algo_bytes`` and ``bytes_per_s``."""
    if steps < 1:
        raise MXNetError("collbench: steps must be >= 1")
    n = int(n_devices if n_devices is not None else len(jax.devices()))
    mesh = _submesh(n, axis)
    x = _payload(payload_bytes, n, dtype)
    spec = NamedSharding(mesh, P(axis))
    xd = jax.device_put(x, spec)
    fn = _coll_fn(op, mesh, axis)
    out = fn(xd)
    jax.block_until_ready(out)          # compile outside the window
    for _ in range(max(0, warmup)):
        out = fn(xd)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(xd)
    jax.block_until_ready(out)
    dt = max(time.perf_counter() - t0, 1e-9) / steps
    nbytes = int(x.size) * jnp.dtype(dtype).itemsize
    moved = algo_bytes(op, nbytes, n)
    dev = mesh.devices.ravel()[0]
    row = {
        "label": "collbench", "op": op, "n_devices": n,
        "payload_bytes": nbytes, "algo_bytes": moved,
        "ms": dt * 1e3, "bytes_per_s": moved / dt,
        "dtype": str(jnp.dtype(dtype)), "compression": None,
        "steps": steps, "device_kind": dev.device_kind,
        "platform": dev.platform,
    }
    _publish(row)
    return row


def bench_compression(n_devices: Optional[int] = None,
                      payload_bytes: int = 1 << 20,
                      threshold: float = 0.5, steps: int = 10,
                      warmup: int = 2, axis: str = "dp",
                      dense_row: Optional[Dict[str, Any]] = None
                      ) -> List[Dict[str, Any]]:
    """The gradient-compression on/off bandwidth comparison: one dense
    psum row and one 2-bit-compressed allreduce row (error-feedback codec
    via ``collectives.bucketed_allreduce(compression=...)``) over the same
    payload. The compressed row's ``algo_bytes`` counts the PACKED codes
    the allgather exchange actually moves — 16x fewer wire bytes than f32,
    bought with quantize/dequantize compute; this comparison is where that
    trade is measured instead of assumed. ``dense_row`` reuses an
    already-measured psum row for this (count, size) cell instead of
    measuring (and counting telemetry for) the dense baseline twice."""
    from ..gradient_compression import GradientCompression
    n = int(n_devices if n_devices is not None else len(jax.devices()))
    mesh = _submesh(n, axis)
    x = _payload(payload_bytes, n, "float32")
    spec = NamedSharding(mesh, P(axis))
    xd = jax.device_put(x, spec)
    rows = [dense_row if dense_row is not None else
            bench_collective("psum", n_devices=n,
                             payload_bytes=payload_bytes, steps=steps,
                             warmup=warmup, axis=axis)]
    gc = GradientCompression({"type": "2bit", "threshold": threshold})
    res = None

    def one():
        nonlocal res
        out, res = _coll.bucketed_allreduce(
            [xd], mesh, axis, bucket_bytes=1 << 62,
            compression=gc, residuals=res)
        return out[0]

    out = one()                         # compile outside the window
    jax.block_until_ready(out)
    for _ in range(max(0, warmup)):
        out = one()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = one()
    jax.block_until_ready(out)
    dt = max(time.perf_counter() - t0, 1e-9) / steps
    # wire bytes: every rank allgathers each peer's packed shard — the
    # all_gather algo bytes of the PACKED payload
    local = int(x.size) // n
    packed_global = n * gc.compressed_nbytes(local)
    moved = algo_bytes("all_gather", packed_global, n)
    dev = mesh.devices.ravel()[0]
    row = {
        "label": "collbench", "op": "psum_compressed", "n_devices": n,
        "payload_bytes": int(x.size) * 4, "algo_bytes": moved,
        "ms": dt * 1e3, "bytes_per_s": moved / dt if moved else 0.0,
        "dtype": "float32",
        "compression": {"type": "2bit", "threshold": threshold},
        "wire_reduction_x": (rows[0]["algo_bytes"] / moved
                            if moved else None),
        "steps": steps, "device_kind": dev.device_kind,
        "platform": dev.platform,
    }
    _publish(row)
    rows.append(row)
    return rows


def _publish(row: Dict[str, Any]) -> None:
    if _metrics.enabled():
        _telemetry.COLL_MS.observe(row["ms"], op=row["op"])
        _telemetry.COLL_BYTES.inc(int(row["payload_bytes"]), op=row["op"])


def run(ops: Sequence[str] = OPS,
        device_counts: Optional[Sequence[int]] = None,
        payload_sizes: Sequence[int] = (1 << 16, 1 << 20, 4 << 20),
        dtype="float32", steps: int = 10, warmup: int = 2,
        compression: Optional[float] = None, axis: str = "dp",
        ledger: Optional[_xcost.CostLedger] = None,
        emit=None) -> List[Dict[str, Any]]:
    """The full sweep: every (op, device count, payload size) cell, plus
    the compressed-vs-dense pair per (count, size) when ``compression``
    (a threshold) is given. Rows stream through ``emit`` as they land and
    persist to ``ledger`` (or the ambient ``MXNET_PERF_LEDGER``)."""
    led = ledger if ledger is not None else _xcost.get_ledger()
    rows: List[Dict[str, Any]] = []

    def _land(row):
        rows.append(row)
        if led is not None:
            try:
                led.append(row)
            except Exception as e:   # the lab must not die on bookkeeping
                logger.warning("collbench: ledger append failed: %r", e)
        if emit is not None:
            emit(row)

    for n in (device_counts if device_counts is not None
              else default_device_counts()):
        for size in payload_sizes:
            dense = None
            for op in ops:
                row = bench_collective(op, n_devices=n, payload_bytes=size,
                                       dtype=dtype, steps=steps,
                                       warmup=warmup, axis=axis)
                if op == "psum" and str(jnp.dtype(dtype)) == "float32":
                    dense = row     # reusable baseline for the compressed
                    #                 comparison: same payload, same cell
                _land(row)
            if compression is not None:
                pair = bench_compression(
                    n_devices=n, payload_bytes=size,
                    threshold=compression, steps=steps,
                    warmup=warmup, axis=axis, dense_row=dense)
                if dense is None:
                    # the ops loop did not measure the dense baseline this
                    # cell (psum absent / non-f32 dtype): the comparison's
                    # freshly-measured dense side must land too, not be
                    # paid for and dropped
                    _land(pair[0])
                for row in pair[1:]:
                    _land(row)
    return rows


# --------------------------------------------------------- scaling benchmark
def _scaling_net(prefix: str, classes: int):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    mx.random.seed(0)
    net = nn.HybridSequential(prefix=prefix)
    net.add(nn.Conv2D(8, 3, padding=1, activation="relu",
                      prefix=prefix + "c0_"),
            nn.GlobalAvgPool2D(prefix=prefix + "p0_"),
            nn.Dense(classes, prefix=prefix + "d0_"))
    net.initialize(mx.init.Xavier())
    return net, gluon.loss.SoftmaxCrossEntropyLoss()


def _measure_throughput(trainer, x, y, steps: int, warmup: int) -> float:
    spec = NamedSharding(trainer.mesh, P("dp"))
    loss = trainer.step(x, y)          # compile
    float(loss)
    xd = jax.device_put(jnp.asarray(x), spec)
    yd = jax.device_put(jnp.asarray(y), spec)
    for _ in range(max(0, warmup)):
        loss = trainer.step(xd, yd)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step(xd, yd)
    float(loss)
    dt = max(time.perf_counter() - t0, 1e-9)
    return steps * int(x.shape[0]) / dt


def scaling_row(batch_per_chip: int = 8, image: int = 16, classes: int = 4,
                steps: int = 6, warmup: int = 2,
                grad_reduce: str = "reduce_scatter",
                grad_reduce_dtype=None,
                n_devices: Optional[int] = None,
                builder=None, data=None,
                ledger: Optional[_xcost.CostLedger] = None,
                extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The REAL multichip scaling-efficiency measurement: the same
    per-chip batch trained on 1 device and on N,
    with the gradient reduction configured by the comm levers, reported as
    ``img/s/chip at N / img/s/chip at 1`` — the number the ≥90% claim
    (ROADMAP item 5) is judged against, with full lever provenance in the
    row. ``builder(prefix, classes) -> (net, loss_fn)`` and
    ``data(global_batch) -> (x, y)`` override the default tiny conv
    workload."""
    from .data_parallel import DataParallelTrainer
    builder = builder or _scaling_net
    n = int(n_devices if n_devices is not None else len(jax.devices()))
    if data is None:
        rng = np.random.RandomState(0)

        def data(gbatch):
            x = rng.uniform(-1, 1, (gbatch, 3, image, image)) \
                .astype("float32")
            y = (np.arange(gbatch) % classes).astype("float32")
            return x, y

    results = {}
    comm = None
    opt_bytes = {}
    for label, count in (("1", 1), ("n", n)):
        if label == "n" and n == 1:
            results["n"] = results["1"]
            break
        mesh = _submesh(count, "dp")
        net, loss_fn = builder("collb_%s_" % label, classes)
        trainer = DataParallelTrainer(
            net, loss_fn, "sgd", {"learning_rate": 0.1, "momentum": 0.9},
            mesh=mesh, grad_reduce=grad_reduce if count > 1 else "all_reduce",
            grad_reduce_dtype=grad_reduce_dtype if count > 1 else None)
        x, y = data(batch_per_chip * count)
        results[label] = _measure_throughput(trainer, x, y, steps, warmup) \
            / count
        if count == n:
            comm = trainer.comm_config()
            opt_bytes = trainer.opt_state_bytes()
        del trainer, net
    # published throughputs are rounded; derive the ratio from the SAME
    # rounded numbers so the row is self-consistent for any reader that
    # recomputes efficiency from its own fields
    per_1 = round(results["1"], 2)
    per_n = round(results["n"], 2)
    eff = per_n / per_1 if per_1 else 0.0
    dev = jax.devices()[0]
    row = {
        "metric": "multichip_scaling_efficiency",
        "value": round(eff, 4), "unit": "ratio",
        "label": "bench.multichip",
        "n_devices": n,
        "img_s_per_chip_1": per_1,
        "img_s_per_chip_n": per_n,
        "batch_per_chip": batch_per_chip,
        "comm_config": comm,
        "opt_state_bytes": opt_bytes,
        "device_kind": dev.device_kind, "platform": dev.platform,
        "steps": steps,
    }
    if extra:
        # caller provenance (model / provenance / degraded) merged BEFORE
        # the ledger append, so the persisted row carries the same
        # identity as the printed one — a model-filtered baseline reader
        # must never match a row whose model field only existed in memory
        row.update(extra)
    led = ledger if ledger is not None else _xcost.get_ledger()
    if led is not None:
        try:
            led.append(row)
        except Exception as e:
            logger.warning("collbench: scaling-row ledger append failed: %r",
                           e)
    return row
