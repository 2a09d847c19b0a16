"""Async double-buffered device staging for input pipelines.

The reference hides host->device input latency by running decode/augment in
a C++ thread pool and handing the engine pre-staged batches (PrefetcherIter,
src/io/iter_prefetcher.h:1; the OMP decode loop in
src/io/iter_image_recordio_2.cc:672-736). The TPU-native equivalent: a
background thread issues ``jax.device_put`` for batch k+1 (and k+2, ...,
up to ``depth``) while the jitted train step for batch k runs on the chip,
so the H2D DMA overlaps compute instead of serializing with it.

Two extra levers the reference's design also uses:

- **uint8 on the wire**: images travel as uint8 and are normalized ON the
  device (the reference augmenters emit uint8 records; mean/std live in the
  graph). 4x fewer bytes than float32 -> 4x the effective feed rate when
  the interconnect, not the decode, is the bottleneck. Labels are never
  cast or rescaled.
- **depth>1 double buffering**: transfers for multiple future batches are
  in flight concurrently; jax arrays are functional so "buffers" need no
  explicit alternation — each staged batch owns fresh device memory and is
  dropped when the consumer moves on.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Iterable, Iterator, Optional

import jax
import numpy as np

from ..base import MXNetError
from ..ndarray.ndarray import NDArray, _unwrap, _wrap
from ..observability import catalog as _telemetry
from ..observability import metrics as _metrics
from ..observability import spans as _spans
from .io import DataBatch, DataIter, has_state, _join_producer, _put_or_stop

__all__ = ["prefetch_to_device", "DeviceFeedIter"]

_STOP = object()


def _stage(tree, sharding):
    """Issue (async) device transfers for every array leaf of ``tree``."""

    def put(a):
        if isinstance(a, NDArray):
            a = _unwrap(a)
        if a is None:
            return None
        if sharding is not None:
            return jax.device_put(a, sharding)
        return jax.device_put(a)

    return jax.tree_util.tree_map(put, tree,
                                  is_leaf=lambda x: isinstance(x, NDArray))


# _put_or_stop lives in io.py (shared with PrefetchingIter); re-exported
# here because "a stop-aware bounded put like device_feed._put_or_stop" is
# the documented idiom.


def prefetch_to_device(source: Iterable, sharding=None,
                       depth: int = 2) -> Iterator:
    """Yield items of ``source`` with their array leaves already committed
    to device memory, staging ``depth`` items ahead on a background thread.

    ``source`` yields pytrees (tuples/lists/dicts) of numpy arrays,
    NDArrays, or jax arrays; ``sharding`` is an optional
    ``jax.sharding.Sharding`` the leaves are placed with (e.g.
    ``NamedSharding(mesh, P('dp'))`` to split the batch across the mesh).

    The producer thread only *issues* transfers (``jax.device_put`` is
    asynchronous); the PJRT runtime performs the DMA concurrently with
    whatever computation the consumer has in flight. Closing/abandoning the
    generator stops the producer and releases its staged buffers.
    """
    if depth < 1:
        raise MXNetError("prefetch depth must be >= 1")
    q: "queue.Queue" = queue.Queue(maxsize=depth)
    stop = threading.Event()

    def producer():
        try:
            for item in source:
                if not _put_or_stop(q, _stage(item, sharding), stop):
                    return
        except Exception as e:                 # surface at the consumer
            _put_or_stop(q, e, stop)
            return
        _put_or_stop(q, _STOP, stop)

    t = threading.Thread(target=producer, daemon=True,
                         name="mxtpu-device-feed")
    t.start()
    try:
        while True:
            item = q.get()
            if item is _STOP:
                return
            if isinstance(item, Exception):
                raise item
            yield item
    finally:
        # consumer done/abandoned: unblock and drain the producer so no
        # staged device buffers stay pinned
        stop.set()
        try:
            while True:
                q.get_nowait()
        except queue.Empty:
            pass


class DeviceFeedIter(DataIter):
    """DataIter combinator: batches come out with ``.data``/``.label``
    already resident on device (optionally sharded over a mesh axis),
    staged ``depth`` batches ahead of the consumer.

    Drop-in around any DataIter — the TPU-native PrefetcherIter
    (reference src/io/iter_prefetcher.h:1)::

        feed = DeviceFeedIter(ImageRecordIter(...),
                              sharding=NamedSharding(mesh, P('dp')),
                              wire_dtype='uint8', scale=1/255.)
        for batch in feed:
            trainer.step(batch.data[0], batch.label[0])  # no H2D stall

    ``wire_dtype``/``scale``/``shift``: when set, DATA leaves are cast to
    ``wire_dtype`` BEFORE the transfer and rescaled on device afterwards
    (``x * scale + shift`` in float32) — the reference's uint8-record
    design, cutting wire bytes 4x vs float32. Labels travel untouched.
    """

    def __init__(self, base: DataIter, sharding=None, depth: int = 2,
                 wire_dtype: Optional[str] = None, scale: float = 1.0,
                 shift: float = 0.0):
        super().__init__(getattr(base, "batch_size", 0))
        self._base = base
        self._sharding = sharding
        self._depth = depth
        self._wire_dtype = np.dtype(wire_dtype) if wire_dtype else None
        self._rescale = None
        if self._wire_dtype is not None:
            import jax.numpy as jnp
            scale_, shift_ = float(scale), float(shift)

            @jax.jit
            def rescale(a):
                return a.astype(jnp.float32) * scale_ + shift_

            self._rescale = rescale
        # state protocol (see PrefetchingIter): the resume point is the
        # base state after the last batch DELIVERED to the consumer; the
        # producer snapshots base state alongside every batch it stages, so
        # staged-but-undelivered depth is implicitly credited back on resume
        # (neither skipped nor duplicated)
        self._track_state = has_state(base)
        self._last_state = base.state() if self._track_state else None
        self._closed = False
        # terminal condition already delivered (StopIteration or a producer
        # exception): the producer thread has exited, so a further next()
        # must re-raise instead of blocking forever on an empty queue (a
        # retry wrapper re-calling next() after a transient error would
        # otherwise hang silently). reset()/set_state() clear it.
        self._terminal = None
        self._queue: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._thread = None
        self._delivered = 0     # batches handed to the consumer so far
        self._start()

    # DataDesc passthrough so Module/fit loops see the base iterator's shape
    @property
    def provide_data(self):
        return self._base.provide_data

    @property
    def provide_label(self):
        return self._base.provide_label

    def _put_arrays(self, arrs, is_label):
        out = []
        for a in arrs or []:
            h = _unwrap(a) if isinstance(a, NDArray) else a
            # contract (docstring): with wire_dtype set, every DATA leaf is
            # cast to wire_dtype for the transfer and rescaled to f32 on
            # device as x*scale + shift — including leaves that ALREADY
            # arrive as the wire dtype (uint8 image records) and float wire
            # dtypes; source dtype never silently disables the rescale
            wire = not is_label and self._wire_dtype is not None
            if wire:
                h = np.asarray(h)
                if h.dtype != self._wire_dtype:
                    h = h.astype(self._wire_dtype)
            d = (jax.device_put(h, self._sharding)
                 if self._sharding is not None else jax.device_put(h))
            if wire:
                d = self._rescale(d)
            out.append(_wrap(d))
        return out

    def _producer(self, q, stop, m):
        # q/stop arrive as ARGUMENTS (not re-read from self) so a stale
        # thread from before a reset() can never touch the new queue; m is
        # the number of the first batch this thread stages (the consumer's
        # count of deliveries: the spans of one batch share it on both sides)
        try:
            while not stop.is_set():
                unit = ("batch", m)
                try:
                    with _spans.span("feed.base_next", unit=unit):
                        b = self._base.next()
                except StopIteration:
                    _put_or_stop(q, _STOP, stop)
                    return
                state = self._base.state() if self._track_state else None
                with _spans.span("feed.stage", unit=unit):
                    staged = DataBatch(
                        data=self._put_arrays(b.data, is_label=False),
                        label=self._put_arrays(b.label, is_label=True),
                        pad=b.pad, index=b.index,
                        bucket_key=getattr(b, "bucket_key", None))
                # blocked here, the feed is ahead of the step
                with _spans.span("feed.put_wait", unit=unit):
                    if not _put_or_stop(q, (staged, state), stop):
                        return
                m += 1
        except Exception as e:
            _put_or_stop(q, e, stop)

    def _start(self):
        self._thread = threading.Thread(
            target=self._producer,
            args=(self._queue, self._stop, self._delivered),
            daemon=True, name="mxtpu-device-feed-iter")
        self._thread.start()

    def _stop_producer(self):
        # drain-while-join (shared helper): dropping the staged items also
        # releases their pinned device buffers
        _join_producer(self._thread, self._queue, self._stop,
                       "DeviceFeedIter")
        self._thread = None

    def _restart(self):
        self._stop = threading.Event()
        self._queue = queue.Queue(maxsize=self._depth)
        self._start()

    def reset(self):
        """Stop the producer, rewind the base iterator, restart staging."""
        if self._closed:
            raise MXNetError("DeviceFeedIter is closed")
        self._stop_producer()
        self._terminal = None
        self._base.reset()
        if self._track_state:
            self._last_state = self._base.state()
        self._restart()

    # ------------------------------------------------- checkpointable state
    def state(self) -> dict:
        """Resume point of the base iterator as of the last batch this feed
        DELIVERED — in-flight staged batches are excluded by construction."""
        if not self._track_state:
            raise MXNetError(
                "DeviceFeedIter.state: base iterator %s has no state "
                "protocol" % type(self._base).__name__)
        return {"iter": "DeviceFeedIter", "base": dict(self._last_state)}

    def set_state(self, state: dict) -> None:
        """Rewind the base iterator to a checkpointed resume point and
        restart staging from there. The producer is stopped and its staged
        depth drained first (those batches were never consumed, so dropping
        them neither skips nor duplicates data)."""
        if self._closed:
            raise MXNetError("DeviceFeedIter is closed")
        if not self._track_state:
            raise MXNetError("DeviceFeedIter.set_state: base iterator has "
                             "no state protocol")
        self._stop_producer()
        self._terminal = None
        self._base.set_state(state["base"])
        self._last_state = dict(state["base"])
        self._restart()

    def close(self):
        """Stop the producer and release the staged (pinned) device
        buffers; closes the base iterator too. Idempotent; terminal."""
        if self._closed:
            return
        self._closed = True
        self._stop_producer()
        self._base.close()

    def next(self) -> DataBatch:
        if self._closed:
            raise MXNetError("DeviceFeedIter is closed")
        if self._terminal is not None:
            # producer already exited: fail fast, never block on the queue
            if self._terminal is StopIteration:
                raise StopIteration
            raise self._terminal
        # blocked here, the step is starved
        with _spans.span("feed.get_wait",
                         unit=("batch", self._delivered)) as wait:
            item = self._queue.get()
        if wait.t1 is not None:
            _telemetry.IO_FEED_STALL_MS.observe(wait.ms)
        if item is _STOP:
            self._terminal = StopIteration
            raise StopIteration
        if isinstance(item, Exception):
            self._terminal = item
            raise item
        staged, state = item
        self._delivered += 1
        if state is not None:
            self._last_state = state
        if _metrics.enabled():
            _telemetry.IO_QUEUE_DEPTH.set(self._queue.qsize(),
                                          iter="DeviceFeedIter")
        return staged

    def iter_next(self):
        raise MXNetError("use next() on DeviceFeedIter")
