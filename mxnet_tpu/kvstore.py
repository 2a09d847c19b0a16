"""KVStore — the distributed/multi-device communication facade.

Reference parity: ``include/mxnet/kvstore.h:59`` (Init/Push/Pull/
PullRowSparse/Barrier/RunServer/rank/num_workers) and the five comm tiers of
``src/kvstore/`` (SURVEY.md §5.8): CommCPU ('local'), CommDevice/'device'
P2P reduce, KVStoreNCCL, ps-lite 'dist_sync'/'dist_async', and
'dist_sync_device'.

TPU-first: ALL five tiers collapse into XLA collectives.
- Within one process, SPMD arrays make per-device gradient copies a non-issue:
  'local'/'device'/'nccl' reduce a *list* of per-slice NDArrays with one
  fused add (XLA fuses the tree) and broadcast back by reference.
- Across hosts ('dist_sync'), the reduce is a psum over the 'hosts' axis of a
  global mesh, driven through ``mxnet_tpu.parallel.collectives.allreduce_tree``
  — no parameter server, no ZeroMQ: ICI/DCN collectives do the transport,
  matching the north star in BASELINE.json.
- The bucketed/priority push (reference priority=-index, 2-bit compression
  hooks) is preserved: pushes aggregate into buckets of
  MXNET_UPDATE_AGGREGATION_SIZE tensors and dispatch as one fused XLA
  computation per bucket, so early layers' reduces still land first.
- ``update_on_kvstore`` (server-side optimizer, kvstore_dist_server.h:346)
  runs the optimizer inside the store exactly once per key, mirroring sync
  mode semantics.
"""
from __future__ import annotations

import sys
import threading
from typing import Any, Callable, Dict, List, Optional, Union

import jax
import jax.numpy as jnp

from .base import MXNetError, TransientKVError, get_env
from .ndarray import NDArray
from .ndarray.ndarray import _unwrap, _wrap
from .observability import catalog as _telemetry
from .observability import metrics as _obs_metrics

__all__ = ["KVStore", "create"]


def create(name: str = "local") -> "KVStore":
    """Factory (reference kvstore.cc:40-72 type-string dispatch)."""
    if not isinstance(name, str):
        raise MXNetError("name must be a string")
    if "dist" in name:
        return KVStoreDist(name)
    return KVStoreLocal(name)


class KVStore:
    """Base interface; both impls keep the reference's observable API."""

    def __init__(self, name: str):
        self.type = name
        self._updater: Optional[Callable] = None
        self._optimizer = None
        self._store: Dict[Any, NDArray] = {}
        self._compression_params = None
        self._gc = None                 # GradientCompression when active
        self._gc_residuals: Dict[Any, Any] = {}
        # (priority, seq, key, [per-device arrays]) awaiting dispatch
        self._pending: List[tuple] = []
        # communication instrumentation (reference ps-lite counts its sent
        # bytes per van connection; here the unit is the fused bucket):
        # bucket_reduces = dispatched fused buckets, compressed_payload_bytes
        # = packed uint8 bytes that would cross the wire, dense_reduce_elems
        # = f32 elements reduced uncompressed. Read by the dryrun/driver to
        # prove the collective path actually ran.
        self.comm_stats: Dict[str, int] = {
            "pushes": 0, "bucket_reduces": 0,
            "compressed_payload_bytes": 0, "dense_reduce_elems": 0}

    # ------------------------------------------------------------- data plane
    def init(self, key, value) -> None:
        keys, values = _key_value(key, value)
        for k, v in zip(keys, values):
            if k in self._store:
                continue
            self._store[k] = _wrap(jnp.array(_unwrap(v if not isinstance(v, list)
                                                     else v[0])))

    def push(self, key, value, priority: int = 0) -> None:
        """Enqueue a push. Like the reference (which schedules pushes on the
        async engine with a priority hint, model.py:150-160), push returns
        immediately; the reduce is dispatched at the next flush point (pull/
        barrier/state IO) in priority order, aggregated into buckets of
        MXNET_UPDATE_AGGREGATION_SIZE tensors fused into one XLA computation
        each."""
        keys, values = _key_value(key, value)
        for k, vlist in zip(keys, values):
            if not isinstance(vlist, list):
                vlist = [vlist]
            if k not in self._store:
                raise MXNetError(f"key {k} was not init'd")
            self._pending.append((priority, len(self._pending), k,
                                  [_unwrap(v) for v in vlist]))
            self.comm_stats["pushes"] += 1
            if _obs_metrics.enabled():
                _telemetry.KV_PUSH_TOTAL.inc()

    def _flush(self) -> None:
        """Dispatch pending pushes: highest priority first (ties keep push
        order), in fused buckets (reference MXNET_UPDATE_AGGREGATION_SIZE,
        model.py:130-148)."""
        if not self._pending:
            return
        pending, self._pending = self._pending, []
        # priority orders DISTINCT keys; same-key pushes must keep issue
        # order (the reference serializes them through the key's engine
        # write var regardless of priority hint) — so every entry of a key
        # sorts with the key's first-seen priority, and the stable sort
        # preserves seq order within the key
        key_prio: Dict[Any, int] = {}
        for prio, _, k, _ in pending:
            key_prio.setdefault(k, prio)
        pending.sort(key=lambda t: (-key_prio[t[2]], t[1]))
        agg = max(1, int(get_env("MXNET_UPDATE_AGGREGATION_SIZE", 4)))
        for start in range(0, len(pending), agg):
            bucket = pending[start:start + agg]
            merged_list = _fused_bucket_sum(tuple(tuple(v) for _, _, _, v
                                                  in bucket))
            if self._gc is not None:
                # quantize each merged grad against its key's error-feedback
                # residual; what travels further (and what lands in the
                # store) is the {-t,0,+t} reconstruction
                shapes = [m.shape for m in merged_list]
                packed_list = [self._quantize_with_residual(k, m)
                               for (_, _, k, _), m in zip(bucket, merged_list)]
                self.comm_stats["compressed_payload_bytes"] += sum(
                    int(p.size) for p in packed_list)
                merged_list = self._reduce_compressed(packed_list, shapes)
            else:
                # ONE cross-process collective per bucket, not per key —
                # this is where the aggregation actually reaches the network
                self.comm_stats["dense_reduce_elems"] += sum(
                    int(m.size) for m in merged_list)
                merged_list = self._global_reduce_bucket(
                    merged_list, [k for _, _, k, _ in bucket])
            self.comm_stats["bucket_reduces"] += 1
            for (prio, _, k, _), merged in zip(bucket, merged_list):
                if self._updater is not None:
                    # server-side optimizer semantics (update_on_kvstore=True)
                    self._updater(k, _wrap(merged), self._store[k])
                else:
                    self._store[k]._set_data(merged)

    def pull(self, key, out=None, priority: int = 0, ignore_sparse: bool = True):
        self._flush()
        keys, outs = _key_value(key, out)
        if _obs_metrics.enabled():
            _telemetry.KV_PULL_TOTAL.inc(len(keys))
        for k, olist in zip(keys, outs):
            if k not in self._store:
                raise MXNetError(f"key {k} was not init'd")
            if not isinstance(olist, list):
                olist = [olist]
            # Copy-on-write alias: every out shares the stored buffer. This
            # is sound because jax arrays are immutable — NDArray "mutation"
            # (o[:] = ..., +=) always rebinds o._data to a NEW array and can
            # never write through to the store. Any future raw-buffer
            # mutation path (e.g. dlpack in-place) must copy here first.
            src = self._store[k]._data
            for o in olist:
                # broadcast back to each out's home device (the reference
                # comm broadcast direction): a pull into a replica on
                # another device must not silently rehome the replica
                if hasattr(src, "devices") and hasattr(o._data, "devices") \
                        and o._data.devices() != src.devices():
                    o._set_data(jax.device_put(
                        src, next(iter(o._data.devices()))))
                else:
                    o._set_data(src)

    def pushpull(self, key, value, out=None, priority: int = 0) -> None:
        self.push(key, value, priority)
        self.pull(key, out if out is not None else value, priority)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None) -> None:
        """Gather only touched rows (reference kvstore.h PullRowSparse).
        Dense emulation: gather(rows) of the stored value."""
        if row_ids is None:
            raise MXNetError("row_sparse_pull requires row_ids")
        self._flush()
        keys, outs = _key_value(key, out)
        rid_list = row_ids if isinstance(row_ids, list) else [row_ids]
        for k, olist in zip(keys, outs):
            if not isinstance(olist, list):
                olist = [olist]
            src = self._store[k]._data
            for o, rid in zip(olist, rid_list):
                idx = _unwrap(rid).astype(jnp.int32)
                rows = jnp.take(src, idx, axis=0)
                full = jnp.zeros_like(src).at[idx].set(rows)
                o._set_data(full)

    # ------------------------------------------------------------- reduction
    def _quantize_with_residual(self, k, merged):
        """2-bit error-feedback quantization of one merged gradient against
        its key's residual stream (shared by the sync bucket path and the
        async push encoder)."""
        res = self._gc_residuals.get(k)
        if res is None:
            res = jnp.zeros(merged.shape, jnp.float32)
        packed, res = self._gc.quantize(merged, res)
        self._gc_residuals[k] = res
        return packed

    def _global_reduce_bucket(self, merged_list, keys):
        return merged_list  # single-host: nothing to do

    def _reduce_compressed(self, packed_list, shapes):
        """Single-host: decode the packed payload straight back."""
        return [self._gc.dequantize(p, s)
                for p, s in zip(packed_list, shapes)]

    # ------------------------------------------------------------- control
    def set_updater(self, updater: Callable) -> None:
        self._flush()   # earlier pushes keep their pre-updater semantics
        self._updater = updater

    def set_optimizer(self, optimizer) -> None:
        """Run the optimizer inside the store (reference ships a pickled
        optimizer to servers via the 'optimizer' control command,
        kvstore_dist_server.h:206-227)."""
        self._flush()   # earlier pushes keep their pre-updater semantics
        from . import optimizer as opt_mod
        self._optimizer = optimizer
        updater = opt_mod.get_updater(optimizer)
        self._raw_updater = updater

        def _apply(k, grad, weight):
            updater(k if isinstance(k, int) else hash(k) % (1 << 30), grad, weight)

        self._updater = _apply

    def set_gradient_compression(self, compression_params: Dict) -> None:
        """Activate 2-bit gradient compression with error feedback
        (reference gradient_compression.cc). Every subsequent push is
        quantized to {-t, 0, +t} against a per-key residual; on dist stores
        the 16x-smaller packed payload is what crosses the network."""
        from .gradient_compression import GradientCompression
        self._flush()  # earlier pushes keep their uncompressed semantics
        self._gc = GradientCompression(compression_params)
        self._gc_residuals = {}
        self._compression_params = dict(compression_params)

    # ------------------------------------------------------------- control
    def _send_command_to_servers(self, head: int, body: str) -> None:
        """Send a control command to every server node and return once all
        have executed it (reference MXKVStoreSendCommmandToServers,
        python/mxnet/kvstore.py:616). In the serverless TPU design each
        process hosts its own store shard, so a single-process store IS its
        server: execute locally."""
        _exec_server_command(head, body, self.rank)

    # ------------------------------------------------------------- topology
    @property
    def rank(self) -> int:
        return 0

    @property
    def num_workers(self) -> int:
        return 1

    def barrier(self) -> None:
        self._flush()

    def save_optimizer_states(self, fname: str, dump_optimizer: bool = False) -> None:
        self._flush()
        if getattr(self, "_raw_updater", None) is None:
            raise MXNetError("no optimizer set on kvstore")
        with open(fname, "wb") as f:
            f.write(self._raw_updater.get_states(dump_optimizer))

    def load_optimizer_states(self, fname: str) -> None:
        self._flush()   # pending grads must consume the OLD state
        if getattr(self, "_raw_updater", None) is None:
            raise MXNetError("no optimizer set on kvstore")
        with open(fname, "rb") as f:
            self._raw_updater.set_states(f.read())


class KVStoreLocal(KVStore):
    """'local' / 'device' / 'nccl': single-process reduce+broadcast."""


class KVStoreDist(KVStore):
    """'dist_sync' / 'dist_async' / 'dist_sync_device': multi-host via the
    jax.distributed coordinator + psum over DCN/ICI (replaces ps-lite
    workers/servers/scheduler and tools/launch.py roles)."""

    _next_instance = 0

    def __init__(self, name: str):
        super().__init__(name)
        _maybe_join_cluster()
        self._nprocs = jax.process_count()
        self._rank = jax.process_index()
        # barrier ids must be unique across kvstore instances in one job;
        # ranks create their dist stores in the same program order, so a
        # class-level creation index agrees everywhere without a handshake
        self._instance_id = KVStoreDist._next_instance
        KVStoreDist._next_instance += 1
        self._barrier_seq = 0
        self._last_compressed_stats: Dict[str, int] = {}
        self._hb_stop = threading.Event()
        # True async mode (reference kvstore_dist_server.h:348-358
        # sync_mode_=false): each push is applied IMMEDIATELY by the rank
        # that owns the key — no barrier, no cross-worker aggregation —
        # and pulls read the owner's latest published weight, which may be
        # stale. Single-process dist_async degenerates to the local
        # immediate-apply semantics, which is already exact.
        self._async_mode = (name == "dist_async" and self._nprocs > 1)
        self._async_dead = None     # set by the applier thread on fatal error
        if self._nprocs > 1:
            self._start_heartbeat()
            self._start_command_listener()
        if self._async_mode:
            self._start_async_applier()

    # ------------------------------------------------------- fault surface
    # The reference's ps-lite van exchanges heartbeats and the scheduler
    # tracks dead nodes (include/mxnet/kvstore.h:345-355 get_num_dead_node,
    # ps-lite postoffice UpdateHeartbeat). TPU-native: the jax.distributed
    # coordination service IS the scheduler — each rank beats a timestamp
    # into its key-value store, and liveness reads are plain KV lookups.

    def _send_command_to_servers(self, head: int, body: str) -> None:
        """Broadcast a control command to every rank's server role over the
        coordination service and block until ALL ranks ack execution — the
        reference's ps-lite control channel (kvstore_dist.h SendCommandToServers
        waits on each server's reply) without servers: an atomic sequence
        counter orders commands, every rank's listener thread executes them
        in order and writes an ack key."""
        if self._nprocs == 1:
            return super()._send_command_to_servers(head, body)
        client = _dist_client()
        import json as _json
        seq = int(client.key_value_increment("mxtpu_cmd_seq", 1))
        client.key_value_set("mxtpu_cmd/%d" % seq,
                             _json.dumps([int(head), str(body)]),
                             allow_overwrite=True)
        timeout_ms = int(float(get_env("MXNET_KVSTORE_BARRIER_TIMEOUT",
                                       300.0)) * 1000)
        for r in range(self._nprocs):
            client.blocking_key_value_get("mxtpu_cmd_ack/%d/%d" % (seq, r),
                                          timeout_ms)

    _listener_started = False

    # Background threads that talk to the coordination client must be
    # stopped and joined BEFORE interpreter teardown: one caught mid-RPC
    # while the client is destroyed throws in C++ with no Python frame left
    # ("FATAL: exception not rethrown", exit 250 on otherwise-successful
    # workers). One module-wide atexit handler; entries hold only
    # (event, thread, join_timeout) so kvstore instances stay collectable.
    _bg_threads: list = []
    _shutdown_hooked = False

    @classmethod
    def _register_bg_thread(cls, stop_event, thread, join_timeout):
        cls._bg_threads.append((stop_event, thread, join_timeout))
        if not cls._shutdown_hooked:
            cls._shutdown_hooked = True
            import atexit

            def _stop_all():
                for ev, _, _ in cls._bg_threads:
                    ev.set()
                for _, t, to in cls._bg_threads:
                    t.join(timeout=to)

            atexit.register(_stop_all)

    def _start_command_listener(self) -> None:
        client = _dist_client()
        # one listener per PROCESS: the command channel is global, a second
        # kvstore instance must not double-execute (or double-ack) commands
        if client is None or KVStoreDist._listener_started:
            return
        KVStoreDist._listener_started = True
        rank = self._rank
        stop = self._hb_stop

        def listen():
            import json as _json
            next_seq = 1
            while not stop.wait(0.0):
                try:
                    raw = client.blocking_key_value_get(
                        "mxtpu_cmd/%d" % next_seq, 1000)
                except Exception:
                    continue        # nothing yet: poll again
                try:
                    head, body = _json.loads(raw)
                    _exec_server_command(int(head), body, rank)
                    ack = "ok"
                except Exception as e:   # command failed: still ack (the
                    ack = "error: %r" % (e,)   # sender must not hang)
                try:
                    client.key_value_set(
                        "mxtpu_cmd_ack/%d/%d" % (next_seq, rank), ack,
                        allow_overwrite=True)
                except Exception:
                    return
                next_seq += 1

        t = threading.Thread(target=listen, daemon=True,
                             name="mxtpu-kv-cmd-listener")
        t.start()
        self._cmd_thread = t
        # the listener blocks in 1s-bounded gets; join a bit past that
        KVStoreDist._register_bg_thread(stop, t, 2.0)

    def _start_heartbeat(self) -> None:
        client = _dist_client()
        if client is None:
            return
        interval = float(get_env("MXNET_KVSTORE_HEARTBEAT_INTERVAL", 2.0))
        rank = self._rank
        stop = self._hb_stop

        def beat():
            while not stop.wait(interval):
                try:
                    client.key_value_set("mxtpu_hb/%d" % rank,
                                         repr(time.time()),
                                         allow_overwrite=True)
                except Exception:
                    return      # coordinator gone: nothing left to report to
        try:
            client.key_value_set("mxtpu_hb/%d" % rank, repr(time.time()),
                                 allow_overwrite=True)
        except Exception:
            return
        t = threading.Thread(target=beat, daemon=True,
                             name="mxtpu-kv-heartbeat")
        t.start()
        self._hb_thread = t
        KVStoreDist._register_bg_thread(stop, t, interval + 1.0)

    # ----------------------------------------------------- true async mode
    # Serverless translation of the reference's async server loop
    # (kvstore_dist_server.h:164,348-358): key ownership is sharded over
    # ranks by stable hash; a push SHIPS the local gradient to the owner's
    # mailbox in the coordination KV and returns immediately; the owner's
    # applier thread consumes mailboxes in sequence order, runs the
    # store-side optimizer, and republishes the weight; a pull reads the
    # latest published weight with no barrier. Staleness is bounded (when
    # MXNET_KVSTORE_ASYNC_MAX_STALENESS > 0) by throttling pushers while
    # the owner's applied counter lags the global push counter.

    def _owner(self, key) -> int:
        import zlib
        return zlib.crc32(str(key).encode()) % self._nprocs

    def _as_key(self, kind: str, k, seq: Optional[int] = None) -> str:
        base = "mxas_%s/%d/%s" % (kind, self._instance_id, k)
        return base if seq is None else "%s/%d" % (base, seq)

    def _publish_weight(self, client, k) -> None:
        client.key_value_set_bytes(self._as_key("w", k),
                                   _encode_array(self._store[k]._data),
                                   allow_overwrite=True)

    def _encode_push(self, k, merged) -> bytes:
        """Gradient wire format: '2bit' payloads carry the same packed
        uint8 stream the sync compressed path ships (quantized against
        this worker's residual), dense ones the raw f32 bytes. The header
        is self-describing (codec type + shape + threshold) so the owner
        decodes with the PUSHER's codec parameters — ranks need no
        set_gradient_compression ordering handshake."""
        if self._gc is not None:
            packed = self._quantize_with_residual(k, merged)
            self.comm_stats["compressed_payload_bytes"] += int(packed.size)
            import numpy as _np
            import json as _json
            head = _json.dumps(["2bit", list(merged.shape),
                                self._gc.threshold]).encode()
            return (b"\x01" + len(head).to_bytes(4, "big") + head
                    + _np.asarray(packed).tobytes())
        return b"\x00" + _encode_array(merged)

    @staticmethod
    def _decode_push(blob: bytes):
        if blob[:1] == b"\x00":
            return _decode_array(blob[1:])
        import numpy as _np
        import json as _json
        from .gradient_compression import GradientCompression
        hl = int.from_bytes(blob[1:5], "big")
        enc, shape, threshold = _json.loads(blob[5:5 + hl].decode())
        packed = jnp.asarray(_np.frombuffer(blob[5 + hl:], _np.uint8))
        return GradientCompression(
            {"type": enc, "threshold": threshold}).dequantize(
                packed, tuple(shape))

    def _publish_weight_retry(self, client, k) -> None:
        """Publish key ``k``'s weight with exponential backoff + jitter
        (MXNET_KV_RETRY_ATTEMPTS/BASE/MAX/JITTER). Exhaustion raises
        TransientKVError — typed so the resilience layer can distinguish
        "coordination service flaked, retry the step" from a fatal
        programming error."""
        attempts = max(1, int(get_env("MXNET_KV_RETRY_ATTEMPTS", 5)))
        last = None
        tel = _obs_metrics.enabled()
        for i in range(attempts):
            t0 = time.perf_counter() if tel else 0.0
            try:
                # EVERY attempt lands in the latency histogram, failed ones
                # included — during an incident the slow/timed-out attempts
                # are exactly the signal a dashboard must not hide
                try:
                    return self._publish_weight(client, k)
                finally:
                    if tel:
                        _telemetry.KV_PUBLISH_MS.observe(
                            (time.perf_counter() - t0) * 1000.0)
            except (TypeError, ValueError, KeyError, AttributeError,
                    MXNetError):
                # deterministic programming errors: retrying cannot help
                # and typing them transient would feed them into the
                # resilience retry loop — propagate as-is, immediately
                raise
            except Exception as e:
                last = e
                if tel:
                    _telemetry.KV_PUBLISH_RETRIES.inc()
                if i < attempts - 1:
                    time.sleep(_kv_backoff_delay(i))
        if tel:
            _telemetry.KV_PUBLISH_FAILURES.inc()
        raise TransientKVError(
            "publish of key %r failed after %d attempts (last: %r) — the "
            "coordination service looks unreachable; tune MXNET_KV_RETRY_* "
            "to retry longer" % (k, int(get_env("MXNET_KV_RETRY_ATTEMPTS",
                                                5)), last)) from last

    def _start_async_applier(self) -> None:
        client = _dist_client()
        if client is None:
            return
        stop = self._hb_stop
        rank = self._rank

        def _mark_done(k, nxt, delete_push: bool) -> bool:
            try:
                client.key_value_set(self._as_key("done", k), str(nxt),
                                     allow_overwrite=True)
                if delete_push:
                    client.key_value_delete(self._as_key("push", k, nxt))
                return True
            except Exception:
                return False        # coordinator gone: shut the role down

        def _die(reason: str):
            # the owner role is down: record it LOUDLY. The local rank's
            # next pull/flush raises; remote ranks notice via the
            # staleness bound (or stale reads) — thread death is invisible
            # to process-level heartbeats by construction.
            self._async_dead = reason
            print("mxtpu dist_async: applier on rank %d died: %s"
                  % (rank, reason), file=sys.stderr, flush=True)

        def apply_loop():
            applied: Dict[Any, int] = {}
            gap_since: Dict[Any, float] = {}
            gap_timeout = float(get_env("MXNET_KVSTORE_ASYNC_GAP_TIMEOUT",
                                        30.0))
            while not stop.wait(0.0):
                owned = [k for k in list(self._store.keys())
                         if self._owner(k) == rank]
                if self._updater is None or not owned:
                    if stop.wait(0.05):
                        return
                    continue
                for k in owned:
                    if stop.is_set():
                        return
                    nxt = applied.get(k, 0) + 1
                    try:
                        # bounded server-side wait, not client polling: the
                        # coordinator holds the request until the key lands
                        # or 50 ms pass, keeping other keys + stop serviced
                        blob = client.blocking_key_value_get_bytes(
                            self._as_key("push", k, nxt), 50)
                    except Exception:
                        # nothing at seq nxt. If the global counter shows
                        # LATER pushes exist, the pusher of nxt died between
                        # increment and mailbox write; after a grace window
                        # skip the gap so healthy workers keep applying
                        # (the reference's server likewise survives a dead
                        # pusher — its unsent message simply never arrives).
                        total = _kv_counter_read(client,
                                                 self._as_key("seq", k))
                        if total >= nxt:
                            first = gap_since.setdefault((k, nxt),
                                                         time.time())
                            if time.time() - first > gap_timeout:
                                gap_since.pop((k, nxt), None)
                                applied[k] = nxt
                                if not _mark_done(k, nxt, delete_push=False):
                                    return _die(
                                        "coordination service unreachable "
                                        "skipping dead push of %r" % (k,))
                        continue
                    gap_since.pop((k, nxt), None)
                    try:
                        grad = _wrap(jnp.asarray(self._decode_push(blob)))
                        self._updater(k, grad, self._store[k])
                        ok = True
                    except Exception:
                        ok = False  # poisoned push: skip it, keep serving
                                    # (reference server catch-all)
                    if ok:
                        try:
                            self._publish_weight_retry(client, k)
                        except TransientKVError as e:
                            # update applied locally but could not be
                            # published: do NOT advance 'done' — bounded-
                            # staleness pushers block, and this rank fails
                            # loud on its next call
                            return _die(str(e))
                    applied[k] = nxt
                    if not _mark_done(k, nxt, delete_push=True):
                        return _die("coordination service unreachable "
                                    "marking key %r done" % (k,))

        t = threading.Thread(target=apply_loop, daemon=True,
                             name="mxtpu-kv-async-applier")
        t.start()
        self._async_thread = t
        KVStoreDist._register_bg_thread(stop, t, 1.0)

    def _flush(self) -> None:
        if not self._async_mode:
            return super()._flush()
        if self._async_dead:
            raise MXNetError("dist_async owner role on this rank is dead: "
                             + str(self._async_dead))
        if not self._pending:
            return
        if self._updater is None:
            raise MXNetError(
                "dist_async applies updates in the store: call "
                "set_optimizer (update_on_kvstore) before pushing — the "
                "reference's async mode is server-side-update only "
                "(kvstore_dist_server.h:348-358)")
        pending, self._pending = self._pending, []
        client = _dist_client()
        merged: Dict[Any, Any] = {}
        order: List[Any] = []
        for _, _, k, vlist in pending:
            s = vlist[0]
            for v in vlist[1:]:
                s = s + v
            if k in merged:
                merged[k] = merged[k] + s
            else:
                merged[k] = s
                order.append(k)
        bound = int(get_env("MXNET_KVSTORE_ASYNC_MAX_STALENESS", 0))
        for k in order:
            seq = int(client.key_value_increment(self._as_key("seq", k), 1))
            client.key_value_set_bytes(self._as_key("push", k, seq),
                                       self._encode_push(k, merged[k]))
            self.comm_stats["bucket_reduces"] += 1
            if bound > 0:
                # bounded staleness: wait while the owner's applied counter
                # lags the global push counter by more than the bound; a
                # deadline overrun FAILS LOUD (the owner's applier is gone
                # — matching barrier()'s dead-peer semantics) instead of
                # silently pushing into the void
                timeout = float(get_env("MXNET_KVSTORE_BARRIER_TIMEOUT",
                                        300.0))
                deadline = time.time() + timeout
                done = 0
                while True:
                    done = _kv_counter_read(client, self._as_key("done", k))
                    if seq - done <= bound:
                        break
                    if time.time() >= deadline:
                        raise MXNetError(
                            "dist_async staleness bound %d violated for "
                            "key %r after %.0fs: owner rank %d applied "
                            "%d of %d pushes — the owner's applier is "
                            "likely dead (check num_dead_node())"
                            % (bound, k, timeout, self._owner(k), done,
                               seq))
                    time.sleep(0.02)

    def pull(self, key, out=None, priority: int = 0,
             ignore_sparse: bool = True):
        if not self._async_mode:
            return super().pull(key, out, priority, ignore_sparse)
        if self._async_dead:
            raise MXNetError("dist_async owner role on this rank is dead: "
                             + str(self._async_dead))
        self._flush()
        client = _dist_client()
        timeout_ms = int(float(get_env("MXNET_KVSTORE_BARRIER_TIMEOUT",
                                       300.0)) * 1000)
        keys, outs = _key_value(key, out)
        for k, olist in zip(keys, outs):
            if k not in self._store:
                raise MXNetError(f"key {k} was not init'd")
            if not isinstance(olist, list):
                olist = [olist]
            blob = client.blocking_key_value_get_bytes(self._as_key("w", k),
                                                       timeout_ms)
            arr = jnp.asarray(_decode_array(blob))
            for o in olist:
                o._set_data(arr)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        if not self._async_mode:
            return super().row_sparse_pull(key, out, priority, row_ids)
        if row_ids is None:
            raise MXNetError("row_sparse_pull requires row_ids")
        # async: the authoritative value is the owner's PUBLISHED weight,
        # not this rank's local store copy (which only the owner updates)
        self._flush()
        client = _dist_client()
        timeout_ms = int(float(get_env("MXNET_KVSTORE_BARRIER_TIMEOUT",
                                       300.0)) * 1000)
        keys, outs = _key_value(key, out)
        rid_list = row_ids if isinstance(row_ids, list) else [row_ids]
        for k, olist in zip(keys, outs):
            if k not in self._store:
                raise MXNetError(f"key {k} was not init'd")
            if not isinstance(olist, list):
                olist = [olist]
            blob = client.blocking_key_value_get_bytes(self._as_key("w", k),
                                                       timeout_ms)
            src = jnp.asarray(_decode_array(blob))
            for o, rid in zip(olist, rid_list):
                idx = _unwrap(rid).astype(jnp.int32)
                rows = jnp.take(src, idx, axis=0)
                o._set_data(jnp.zeros_like(src).at[idx].set(rows))

    def num_dead_node(self, node_id: int = -1, timeout: float = 60.0) -> int:
        """Number of peer processes with no heartbeat in the last ``timeout``
        seconds (reference ``get_num_dead_node(node_id, timeout)``,
        include/mxnet/kvstore.h:345-355; node_id -1 means every node, else
        probe that single rank). A rank that never wrote a heartbeat (never
        created its kvstore, or died before connecting) counts as dead."""
        if self._nprocs == 1:
            return 0
        client = _dist_client()
        if client is None:
            raise MXNetError("num_dead_node requires a joined cluster")
        ids = list(range(self._nprocs)) if node_id < 0 else [int(node_id)]
        now = time.time()
        dead = 0
        for i in ids:
            try:
                ts = float(client.key_value_try_get("mxtpu_hb/%d" % i))
            except Exception:
                ts = None
            if ts is None or now - ts > timeout:
                dead += 1
        return dead

    def barrier(self, timeout: Optional[float] = None) -> None:
        """Global barrier with dead-peer detection. Uses the coordination
        service's native timed barrier (replacing ps-lite's scheduler
        BARRIER control message); on timeout the error names how many peers
        look dead so a hung job fails loud instead of forever (reference
        worker behavior when the scheduler reports dead nodes)."""
        self._flush()
        if self._nprocs <= 1:
            return
        if timeout is None:
            timeout = float(get_env("MXNET_KVSTORE_BARRIER_TIMEOUT", 300.0))
        client = _dist_client()
        if client is None:
            raise MXNetError("dist kvstore barrier requires a joined cluster")
        self._barrier_seq += 1
        try:
            client.wait_at_barrier(
                "mxtpu_kv_barrier_%d_%d" % (self._instance_id,
                                            self._barrier_seq),
                int(timeout * 1000))
        except Exception as e:
            msg = repr(e).lower()
            if "deadline" not in msg and "timeout" not in msg \
                    and "timed out" not in msg:
                raise   # a programming/transport error, not a hung peer
            hb_window = min(timeout, 60.0)
            try:
                ndead = self.num_dead_node(-1, timeout=hb_window)
            except Exception:
                ndead = -1
            raise MXNetError(
                "kvstore barrier timed out after %.1fs (%s peer(s) sent no "
                "heartbeat in the last %.0fs — a worker likely died; see "
                "num_dead_node()): %s"
                % (timeout, "unknown" if ndead < 0 else ndead, hb_window,
                   e)) from e

    @property
    def rank(self) -> int:
        return self._rank

    @property
    def num_workers(self) -> int:
        return self._nprocs

    def init(self, key, value) -> None:
        """Init + broadcast: rank 0's value wins everywhere, so workers with
        independently-initialized params start in lockstep (the reference's
        workers pull server-held initial weights after init,
        kvstore_dist.h:217-246)."""
        super().init(key, value)
        if self._nprocs == 1:
            return
        keys, _ = _key_value(key, value)
        from .parallel import collectives
        for k in keys:
            v = self._store[k]._data
            self._store[k]._set_data(
                jnp.asarray(collectives.cross_process_broadcast0(v)))
        if self._async_mode:
            # the owner seeds the published weight every pull will read
            client = _dist_client()
            for k in keys:
                if self._owner(k) == self._rank:
                    self._publish_weight(client, k)

    def _global_reduce_bucket(self, merged_list, keys):
        if self._nprocs == 1:
            return merged_list
        from .parallel import collectives
        return collectives.cross_process_allreduce_many(merged_list)

    def _reduce_compressed(self, packed_list, shapes):
        """The compressed wire path, reduce-scatter shaped (the reference
        fans each worker's compressed push out across server shards by part
        offset, kvstore_dist.h:593-643, so no node ever decodes more than
        its share; with no server the shard owners are the ranks
        themselves):

        1. alltoall — each rank ships packed shard ``j`` (1/N of the bucket's
           uint8 payload, 16x smaller than fp32) to rank ``j``: the packed
           bytes cross the wire ONCE per rank, not N times;
        2. each rank decodes + sums ONLY its own shard from all N peers —
           per-rank decode work is the payload size, independent of N;
        3. one tiled allgather of the dense f32 partial sums rebuilds the
           full reduced gradient everywhere (the reference's dense server->
           worker pull direction — compressed is push-only there too,
           gradient_compression.cc:44-50).
        """
        if self._nprocs == 1:
            return super()._reduce_compressed(packed_list, shapes)
        import numpy as _np
        from .parallel import collectives
        nprocs = self._nprocs
        sizes = [int(p.size) for p in packed_list]
        flat = packed_list[0] if len(packed_list) == 1 \
            else jnp.concatenate(packed_list)
        nbytes = int(flat.size)
        shard = -(-nbytes // nprocs)                 # ceil: bytes per shard
        pad = shard * nprocs - nbytes
        if pad:
            # trailing pad bytes decode to code 0b00 == 0.0 — sliced off below
            flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.uint8)])
        recv = collectives.cross_process_alltoall(
            flat.reshape(nprocs, shard))             # (nprocs, shard)
        dense_shard = self._gc.dequantize_rows_sum(recv)      # (4*shard,)
        dense = collectives.cross_process_allgather_tiled(dense_shard)
        # instrumentation for the O(1/N)-decode contract (tests/dist)
        self._last_compressed_stats = {
            "payload_bytes": nbytes,
            "wire_packed_bytes_per_rank": shard * nprocs,    # alltoall total
            "decode_bytes_per_rank": int(recv.size),         # == padded payload
            "dense_allgather_elems": int(dense.size),
        }
        out, off = [], 0
        for psize, shape in zip(sizes, shapes):
            n = int(_np.prod(shape)) if shape else 1
            out.append(dense[4 * off:4 * off + n].reshape(shape))
            off += psize
        return out

# ----------------------------------------------------------------- helpers
import functools
import os
import time


def _kv_backoff_delay(attempt: int) -> float:
    """MXNET_KV_RETRY_* knobs bound to the shared backoff policy
    (resilience.retry.backoff_delay)."""
    from .resilience.retry import backoff_delay
    return backoff_delay(attempt,
                         float(get_env("MXNET_KV_RETRY_BASE", 0.05)),
                         float(get_env("MXNET_KV_RETRY_MAX", 2.0)),
                         float(get_env("MXNET_KV_RETRY_JITTER", 0.25)))


# Server-side control commands (reference KVStoreServerProfilerCommand,
# include/mxnet/kvstore.h:49: kSetConfig, kState, kPause, kDump — plus the
# optimizer/controller blob channel the reference runs over the same wire).
CMD_SET_PROFILER_CONFIG = 0
CMD_SET_PROFILER_STATE = 1
CMD_PROFILER_PAUSE = 2
CMD_PROFILER_DUMP = 3

_server_controller = [None]     # KVStoreServer-installed custom handler


def set_controller(fn) -> None:
    """Install the server-command handler (reference KVStoreServer.controller:
    servers dispatch unrecognized command heads to the user controller)."""
    _server_controller[0] = fn


def _exec_server_command(head: int, body: str, rank: int) -> None:
    """Run one control command in this process's server role."""
    from . import profiler as _profiler
    if head == CMD_SET_PROFILER_CONFIG:
        _profiler._server_set_config(body, rank)
    elif head == CMD_SET_PROFILER_STATE:
        _profiler._server_set_state(body)
    elif head == CMD_PROFILER_PAUSE:
        _profiler._server_pause(body)
    elif head == CMD_PROFILER_DUMP:
        _profiler._server_dump(rank)
    elif _server_controller[0] is not None:
        _server_controller[0](head, body)
    # unknown heads without a controller are ignored, like the reference
    # server's default switch arm


def _encode_array(a) -> bytes:
    """Self-describing tensor wire format for the coordination KV:
    4-byte header length, JSON [dtype, shape] header, raw bytes."""
    import json as _json
    import numpy as _np
    a = _np.asarray(a)
    head = _json.dumps([a.dtype.str, list(a.shape)]).encode()
    return len(head).to_bytes(4, "big") + head + a.tobytes()


def _decode_array(b: bytes):
    import json as _json
    import numpy as _np
    hl = int.from_bytes(b[:4], "big")
    dt, shape = _json.loads(b[4:4 + hl].decode())
    return _np.frombuffer(b[4 + hl:], dtype=_np.dtype(dt)).reshape(shape)


def _dist_client():
    """The jax.distributed coordination-service client (None when no
    cluster was joined) — the TPU-native stand-in for ps-lite's scheduler
    connection."""
    from jax._src import distributed as _jdist
    return getattr(_jdist.global_state, "client", None)


def _kv_counter_read(client, key: str) -> int:
    """Current integer value of ``key`` (a ``key_value_increment`` counter
    or a plainly-set high-water mark), 0 when it was never written
    (``key_value_try_get`` raises on an absent key)."""
    try:
        return int(client.key_value_try_get(key))
    except Exception:
        return 0


_cluster_joined = False


def _maybe_join_cluster() -> None:
    """Join the jax.distributed cluster from the env set by tools/launch.py
    (reference: the dmlc tracker exports DMLC_* and every worker's kvstore
    ctor calls ps::StartAsync, kvstore_dist.h:47-67). Makes
    ``create('dist_sync')`` work unchanged under ``launch.py -n N``."""
    global _cluster_joined
    coord = os.environ.get("JAX_COORDINATOR_ADDRESS") \
        or os.environ.get("MXNET_COORDINATOR_ADDRESS")
    nprocs = os.environ.get("JAX_NUM_PROCESSES")
    pid = os.environ.get("JAX_PROCESS_ID")
    if _cluster_joined or not (coord and nprocs and pid):
        return
    # must not touch the backend (process_count()/devices() would initialize
    # it and make initialize() below illegal) — probe the distributed client
    # state directly
    from jax._src import distributed as _jdist
    if getattr(_jdist.global_state, "client", None) is not None:
        _cluster_joined = True
        return
    try:
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=int(nprocs),
                                   process_id=int(pid))
    except RuntimeError as e:
        if "must be called before" not in str(e):
            raise   # real failure (unreachable coordinator etc.) — keep it
        raise MXNetError(
            "cannot join the distributed cluster: the XLA backend was "
            "already initialized by earlier array work. Create the dist "
            "kvstore (or import mxnet_tpu under tools/launch.py, which "
            "joins at import) before any computation.") from e
    _cluster_joined = True


@functools.lru_cache(maxsize=256)
def _bucket_sum_compiled(sig):
    """One jitted computation summing every key's device list in a bucket —
    replaces CommCPU's OMP tree / CommDevice P2P ring (comm.h:103,451) and
    the aggregated dispatch the reference gets from batching engine pushes
    (kvstore_nccl.h MXNET_UPDATE_AGGREGATION_SIZE)."""
    arities = tuple(n for n, _, _ in sig)

    def f(*flat):
        out, i = [], 0
        for n in arities:
            group = flat[i:i + n]
            i += n
            acc = group[0]
            for x in group[1:]:
                acc = acc + x
            out.append(acc)
        return tuple(out)

    return jax.jit(f)


def _fused_bucket_sum(groups):
    """groups: tuple of per-key tuples of arrays → list of merged arrays.

    Mixed-device groups (one executor replica per device pushing into the
    same store) are aligned onto one device first — the reference CommCPU
    copies every device's gradient into the CPU merge buffer the same way
    (comm.h:103)."""
    devs = {next(iter(a.devices())) for g in groups for a in g
            if hasattr(a, "devices")}
    if len(devs) > 1:
        target = sorted(devs, key=str)[0]
        groups = tuple(tuple(jax.device_put(a, target) for a in g)
                       for g in groups)
    sig = tuple((len(g), tuple(g[0].shape), str(g[0].dtype)) for g in groups)
    flat = [x for g in groups for x in g]
    return list(_bucket_sum_compiled(sig)(*flat))


def _key_value(keys, values):
    single = not isinstance(keys, (list, tuple))
    if single:
        keys = [keys]
        values = [values]
    else:
        keys = list(keys)
        if values is not None and len(values) == len(keys) and not isinstance(
                values[0], (list, tuple, NDArray)):
            values = list(values)
    return keys, list(values) if values is not None else [None] * len(keys)
