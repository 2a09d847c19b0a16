"""Collective primitives over ICI/DCN.

TPU-native replacement for the reference's comm stack (SURVEY.md §5.8):
ncclAllReduce/Bcast (kvstore_nccl.h:402,482), the CommDeviceTree spanning
trees (comm_tree.h, gpu_topology.h), and ps-lite ZPush/ZPull all become XLA
collectives on a named mesh axis. The topology-aware tree construction the
reference builds by parsing PCIe/NVLink link matrices is XLA's job here —
collectives ride the ICI torus with compiler-chosen algorithms.

These wrappers are meant for use inside ``shard_map``-ed functions; outside,
use ``psum_arrays`` which wraps its own shard_map.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..observability import catalog as _telemetry
from ..observability import metrics as _obs_metrics


def _count_dispatch(op: str, arrays) -> None:
    """Host-side dispatch accounting (counters only, never inside a traced
    function — the inside-shard_map primitives above stay untouched)."""
    if not _obs_metrics.enabled():
        return
    _telemetry.COLL_DISPATCHES.inc(op=op)
    nbytes = 0
    for a in arrays:
        size = getattr(a, "size", None)
        dt = getattr(a, "dtype", None)
        if size is not None and dt is not None:
            nbytes += int(size) * int(jnp.dtype(dt).itemsize)
    if nbytes:
        _telemetry.COLL_BYTES.inc(nbytes, op=op)

__all__ = ["allreduce", "allgather", "reduce_scatter", "broadcast", "ppermute",
           "all_to_all", "psum_arrays", "cross_process_allreduce",
           "cross_process_allreduce_many", "cross_process_alltoall",
           "cross_process_allgather_tiled", "cross_process_broadcast0",
           "bucket_assignment", "bucketed_allreduce"]


# ---- inside-shard_map primitives (thin, named-axis) -----------------------
def allreduce(x, axis_name: str):
    return lax.psum(x, axis_name)


def allgather(x, axis_name: str, axis: int = 0, tiled: bool = True):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: str, axis: int = 0):
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def broadcast(x, axis_name: str, src: int = 0):
    """Every member gets the ``src`` member's value: mask every other
    contribution to zero and psum (one collective; XLA lowers the
    one-nonzero-operand psum to a broadcast from ``src`` on TPU)."""
    idx = lax.axis_index(axis_name)
    return lax.psum(jnp.where(idx == src, x, jnp.zeros_like(x)), axis_name)


def ppermute(x, axis_name: str, perm):
    return lax.ppermute(x, axis_name, perm)


def all_to_all(x, axis_name: str, split_axis: int, concat_axis: int):
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


# ---- coordination-service fallback ----------------------------------------
# XLA cross-process collectives need backend support (TPU ICI/DCN, or a
# CPU/GPU build with cross-host collectives). Where the backend has none —
# a multiprocess computation raises "Multiprocess computations aren't
# implemented on the CPU backend" — the dist kvstore must still work
# (tests/test_dist.py runs real multi-process clusters on CPU).
# The coordination service (already joined for barriers/heartbeats) is a
# correct, slow wire: each rank publishes its host array under a
# round-numbered key and reads every peer's. Used only when the XLA path
# is impossible; TPU traffic never touches it.

_coord_rounds: dict = {}


@functools.lru_cache(maxsize=1)
def _xla_cross_process_ok() -> bool:
    """Probe (once, collectively — every rank calls this before its first
    host-level collective, in the same program order) whether the backend
    can run a real multiprocess computation."""
    if jax.process_count() == 1:
        return True
    try:
        from jax.experimental import multihost_utils
        multihost_utils.process_allgather(jnp.zeros((1,), jnp.float32)[None],
                                          tiled=True)
        return True
    except Exception:
        return False


def _coord_timeout_ms() -> int:
    from ..base import get_env
    return int(float(get_env("MXNET_KVSTORE_BARRIER_TIMEOUT", 300.0)) * 1000)


def _coord_gather(x, tag: str):
    """Rank-ordered list of every process's copy of host array ``x``,
    exchanged over the coordination KV. Per-tag round numbers keep
    successive calls collision-free (ranks call collectives in identical
    program order — the same invariant barrier ids rely on)."""
    import numpy as np

    from .. import kvstore as _kv
    client = _kv._dist_client()
    if client is None:
        raise RuntimeError("coordination-service collective fallback "
                           "requires a joined jax.distributed cluster")
    nprocs, rank = jax.process_count(), jax.process_index()
    rnd = _coord_rounds.get(tag, 0)
    _coord_rounds[tag] = rnd + 1
    key = lambda rr, p: "mxcoll/%s/%d/%d" % (tag, rr, p)
    client.key_value_set_bytes(key(rnd, rank),
                               _kv._encode_array(np.asarray(x)))
    timeout_ms = _coord_timeout_ms()
    out = [np.asarray(_kv._decode_array(
        client.blocking_key_value_get_bytes(key(rnd, p), timeout_ms)))
        for p in range(nprocs)]
    # reclaim this rank's round-(n-2) key: every peer observed in round
    # rnd-1 had fully finished its rnd-2 reads (calls are sequential per
    # rank), so nobody can still need it
    if rnd >= 2:
        try:
            client.key_value_delete(key(rnd - 2, rank))
        except Exception:
            pass
    return out


def cross_process_broadcast0(x):
    """Every process gets process 0's host-local array (the kvstore init
    weight broadcast). XLA collective when the backend supports it, the
    coordination KV otherwise (one write by rank 0, one read per peer;
    keys are kept — init runs a bounded number of times and a reader may
    lag arbitrarily, so reclaiming here could strand it)."""
    if jax.process_count() == 1:
        return jnp.asarray(x)
    _count_dispatch("cp_broadcast", (x,))
    if _xla_cross_process_ok():
        from jax.experimental import multihost_utils
        return jnp.asarray(multihost_utils.broadcast_one_to_all(x))
    from .. import kvstore as _kv
    client = _kv._dist_client()
    rnd = _coord_rounds.get("bcast0", 0)
    _coord_rounds["bcast0"] = rnd + 1
    key = "mxcoll/bcast0/%d" % rnd
    if jax.process_index() == 0:
        import numpy as np
        client.key_value_set_bytes(key, _kv._encode_array(np.asarray(x)))
    blob = client.blocking_key_value_get_bytes(key, _coord_timeout_ms())
    return jnp.asarray(_kv._decode_array(blob))


# ---- host-level helpers ----------------------------------------------------
@functools.lru_cache(maxsize=64)
def _psum_fn(mesh: Mesh, axis: str, n: int):
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=tuple(P(axis) for _ in range(n)),
                       out_specs=tuple(P(axis) for _ in range(n)))
    def f(*xs):
        return tuple(lax.psum(x, axis) for x in xs)

    return jax.jit(f)


def psum_arrays(arrays: Sequence, mesh: Mesh, axis: str = "dp") -> List:
    """Allreduce a list of arrays sharded on ``axis`` (leading dim)."""
    _count_dispatch("psum", arrays)
    fn = _psum_fn(mesh, axis, len(arrays))
    return list(fn(*arrays))


def cross_process_allreduce(x):
    """Sum an identical-shaped host-local array across processes (the
    dist_sync push path). Gathers on a new leading axis (tiled concat — the
    stacking path rejects multi-host arrays) and reduces it."""
    if jax.process_count() == 1:
        return x
    _count_dispatch("cp_allreduce", (x,))
    if not _xla_cross_process_ok():
        import numpy as np
        parts = _coord_gather(x, "allreduce")
        return jnp.asarray(np.sum(np.stack(parts, axis=0), axis=0))
    from jax.experimental import multihost_utils
    gathered = multihost_utils.process_allgather(x[None], tiled=True)
    return jnp.asarray(gathered).sum(axis=0)


def cross_process_allreduce_many(arrays: Sequence) -> List:
    """Allreduce a whole bucket of host-local arrays with ONE collective:
    flatten+concat per dtype, gather once, sum, split back. This is the
    network-level half of the reference's MXNET_UPDATE_AGGREGATION_SIZE
    batching (kvstore_nccl.h aggregates push/pull pairs the same way)."""
    arrays = list(arrays)
    if jax.process_count() == 1 or len(arrays) <= 1:
        return [cross_process_allreduce(a) for a in arrays]
    out: List = [None] * len(arrays)
    by_dtype: dict = {}
    for i, a in enumerate(arrays):
        by_dtype.setdefault(jnp.asarray(a).dtype, []).append(i)
    for dt, idxs in by_dtype.items():
        flat = jnp.concatenate([jnp.ravel(jnp.asarray(arrays[i]))
                                for i in idxs])
        red = cross_process_allreduce(flat)
        off = 0
        for i in idxs:
            n = arrays[i].size
            out[i] = red[off:off + n].reshape(arrays[i].shape)
            off += n
    return out


def cross_process_alltoall(x):
    """All-to-all exchange of per-destination rows across processes.

    ``x`` is a host-local ``(nprocs, s)`` array whose row ``j`` is this
    rank's payload for process ``j``. Returns a host-local ``(nprocs, s)``
    array whose row ``p`` is process ``p``'s payload for THIS rank.

    This is the wire primitive behind the reduce-scatter-shaped compressed
    gradient exchange (kvstore ``_reduce_compressed``): each rank ships only
    one 1/N-sized shard to each peer (total bytes on the wire per rank ~= the
    full payload ONCE, vs N x for an allgather), mirroring how the
    reference's compressed push fans worker payloads out across server
    shards (kvstore_dist.h:593-643 part offsets) rather than replicating
    them to every node.
    """
    nprocs = jax.process_count()
    x = jnp.asarray(x)
    if nprocs == 1:
        return x
    _count_dispatch("cp_alltoall", (x,))
    if not _xla_cross_process_ok():
        import numpy as np
        # row p of MY result is row my_rank of rank p's matrix
        parts = _coord_gather(x, "alltoall")
        mine = jax.process_index()
        return jnp.asarray(np.stack([parts[p][mine]
                                     for p in range(nprocs)], axis=0))
    from jax.experimental import multihost_utils
    mesh, fn = _alltoall_fn(nprocs)
    g = multihost_utils.host_local_array_to_global_array(
        x[None], mesh, P("proc"))
    out = fn(g)
    local = multihost_utils.global_array_to_host_local_array(
        out, mesh, P("proc"))
    return jnp.asarray(local)[0]


@functools.lru_cache(maxsize=8)
def _alltoall_fn(nprocs: int):
    """One process mesh + jitted alltoall per cluster size — jax.jit caches
    compilations per (shape, dtype) under the stable function identity (the
    module's _psum_fn pattern), so the per-step compressed exchange does not
    retrace."""
    import numpy as np
    devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    mesh = Mesh(np.array(devs).reshape(nprocs, -1), ("proc", "dev"))

    def f(blk):                       # (1, nprocs, s) local block
        y = lax.all_to_all(blk, "proc", split_axis=1, concat_axis=0,
                           tiled=True)          # (nprocs, 1, s)
        return y.reshape(blk.shape)             # (1, nprocs, s)

    try:
        fn = shard_map(f, mesh=mesh, in_specs=P("proc"), out_specs=P("proc"))
    except TypeError:  # older shard_map signature
        fn = shard_map(f, mesh, in_specs=P("proc"), out_specs=P("proc"))
    return mesh, jax.jit(fn)


def cross_process_allgather_tiled(x):
    """Tiled allgather of a host-local 1-D shard: returns the rank-order
    concatenation ``(nprocs * s,)`` on every process."""
    if jax.process_count() == 1:
        return jnp.asarray(x)
    _count_dispatch("cp_allgather", (x,))
    if not _xla_cross_process_ok():
        import numpy as np
        parts = _coord_gather(np.asarray(x), "allgather")
        return jnp.asarray(np.concatenate(parts, axis=0).reshape(-1))
    from jax.experimental import multihost_utils
    return jnp.asarray(
        multihost_utils.process_allgather(jnp.asarray(x)[None], tiled=True)
    ).reshape(-1)


def bucket_assignment(nbytes: Sequence[int],
                      bucket_bytes: int) -> List[List[int]]:
    """Greedy order-preserving bucketing: indices are appended in order
    until a bucket reaches ``bucket_bytes``, then a new one starts. This is
    the ONE bucket-assignment rule — shared by :func:`bucketed_allreduce`
    and by ``DataParallelTrainer``'s in-trace gradient bucketing
    (``bucket_bytes=``), so a tuner-searched bucket size means the same
    grouping on both paths."""
    buckets: List[List[int]] = []
    cur: List[int] = []
    size = 0
    for i, n in enumerate(nbytes):
        cur.append(i)
        size += int(n)
        if size >= bucket_bytes:
            buckets.append(cur)
            cur, size = [], 0
    if cur:
        buckets.append(cur)
    return buckets


@functools.lru_cache(maxsize=32)
def _compressed_psum_fn(mesh: Mesh, axis: str, threshold: float, n: int):
    """shard_map'd compressed allreduce of ``n`` arrays: each member 2-bit
    quantizes its local block against its residual shard, the 16x-smaller
    packed payloads cross the interconnect via one tiled all_gather per
    array, and every member dequantize-sums all ranks' codes locally —
    wire bytes = ranks x packed vs ranks x f32 for the dense psum."""
    from ..gradient_compression import (_quantize_2bit, _dequantize_sum_rows)

    def f(*xs_and_res):
        xs, res = xs_and_res[:n], xs_and_res[n:]
        outs, new_res = [], []
        for x, r in zip(xs, res):
            shape = x.shape
            packed, nr = _quantize_2bit(x.astype(jnp.float32),
                                        r.astype(jnp.float32),
                                        threshold=threshold)
            rows = lax.all_gather(packed, axis)          # (ranks, s) uint8
            dense = _dequantize_sum_rows(rows, threshold=threshold)
            outs.append(dense[:x.size].reshape(shape).astype(x.dtype))
            new_res.append(nr)
        return tuple(outs) + tuple(new_res)

    specs = tuple(P(axis) for _ in range(2 * n))
    return jax.jit(shard_map(f, mesh=mesh, in_specs=specs, out_specs=specs))


def bucketed_allreduce(grads: List, mesh: Mesh, axis: str = "dp",
                       bucket_bytes: int = 4 << 20,
                       compression=None, residuals: Optional[List] = None):
    """Bucket small gradients into fused allreduce dispatches, preserving
    order so early (high-priority) buckets land first — the reference's
    priority=-index comm overlap (model.py:150-160) and
    MXNET_UPDATE_AGGREGATION_SIZE batching (kvstore_nccl.h).

    ``compression`` (a :class:`~mxnet_tpu.gradient_compression.
    GradientCompression` or its params dict) routes every bucket through
    the 2-bit error-feedback codec: each mesh member quantizes its local
    shard, only the packed codes cross the interconnect (allgather + local
    dequantize-sum — the reference's compressed push shape), and the
    caller-held ``residuals`` (same shapes/shardings as ``grads``; zeros
    when None) carry the error feedback. With compression the return value
    is ``(reduced, new_residuals)`` so the caller can thread the residual
    stream into the next call; without it, just ``reduced`` (unchanged
    signature)."""
    gc = None
    if compression is not None:
        from ..gradient_compression import GradientCompression
        gc = compression if isinstance(compression, GradientCompression) \
            else GradientCompression(compression)
    out: List = [None] * len(grads)
    new_res: List = [None] * len(grads)
    if gc is not None and residuals is None:
        residuals = [jnp.zeros_like(jnp.asarray(g, jnp.float32))
                     for g in grads]
    for bucket in bucket_assignment(
            [g.size * g.dtype.itemsize for g in grads], bucket_bytes):
        if gc is None:
            reduced = psum_arrays([grads[j] for j in bucket], mesh, axis)
            for j, r in zip(bucket, reduced):
                out[j] = r
        else:
            _count_dispatch("psum_compressed", [grads[j] for j in bucket])
            fn = _compressed_psum_fn(mesh, axis, gc.threshold, len(bucket))
            res = fn(*([grads[j] for j in bucket]
                       + [residuals[j] for j in bucket]))
            for k, j in enumerate(bucket):
                out[j] = res[k]
                new_res[j] = res[len(bucket) + k]
    if gc is None:
        return out
    return out, new_res
