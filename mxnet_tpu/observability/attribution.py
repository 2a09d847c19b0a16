"""Step-time attribution — where does the training cadence actually go?

A wall-clock step number alone cannot say whether a slow run is
device-bound, host-bound or starving on input. This module decomposes the
steady-state step cadence into host-observable buckets, entirely outside
the jitted program (nothing here can change the compiled HLO, and nothing
ever syncs the device):

=============  ===========================================================
bucket          meaning
=============  ===========================================================
dispatch        the span ``trainer.enqueue``: time inside the jitted-step
                call. Under async dispatch this is enqueue cost — until
                the device queue fills, at which point XLA's backpressure
                blocks here and the bucket converges to true device
                compute time.
h2d_transfer    the span ``trainer.put``: ``jax.device_put`` of the batch.
host_prep       the rest of ``trainer.step`` up to the end of the enqueue
                (unwrap, capture, rng fold-in).
feed_stall      time the data pipeline blocked the consumer in ``next()``
                between our steps — the delta of the
                ``mxtpu_io_feed_stall_ms`` histogram (``feed.get_wait`` in
                ``DeviceFeedIter``), attributed to the step that waited.
host_other      remaining time between the previous step's return and this
                step's entry (user code, metric reads, logging).
=============  ===========================================================

Published as rolling means into ``mxtpu_step_breakdown_ms{bucket=}``, plus:

- ``mxtpu_device_util`` — a lag-1 saturation probe: the fraction of recent
  steps whose *previous* result was still not ready (``is_ready()``, a
  non-blocking host call) when the next dispatch completed. ~1.0 means the
  device never drains (compute-bound pipeline); ~0.0 means the device idles
  waiting on the host.
- ``mxtpu_mfu`` — live model-FLOPs utilization: the executable's
  cost-ledger FLOPs (``xcost``) over mean cadence x peak FLOP/s x chips.
  MFU stops being a bench-day artifact and becomes a per-run gauge.

Enabled by default whenever telemetry is on (``MXNET_PERF_ATTRIBUTION=0``
or ``DataParallelTrainer(step_attribution=False)`` turns it off — mxlint
MXL-T210 flags that pairing, because a hot loop with telemetry but no
attribution is exactly the blind spot this module exists to close).
"""
from __future__ import annotations

import threading
from collections import deque
from typing import Any, Dict, Optional

from ..base import get_env, register_config
from . import catalog as _catalog
from . import metrics as _metrics
from . import xcost as _xcost

__all__ = ["BUCKETS", "attribution_config", "StepAttribution"]

register_config("MXNET_PERF_ATTRIBUTION", True, bool,
                "Default for DataParallelTrainer step-time attribution "
                "(mxtpu_step_breakdown_ms / mxtpu_device_util / mxtpu_mfu "
                "gauges). Host-side only; 0 disables the bookkeeping "
                "(mxlint MXL-T210 flags telemetry-on/attribution-off).")

BUCKETS = ("dispatch", "h2d_transfer", "host_prep", "feed_stall",
           "host_other")

# Process-wide claim cursor over the io feed-stall histogram sum: each new
# stall millisecond is attributed to exactly ONE attribution instance (the
# next one to observe a step), so two live trainers never double-count the
# same stall. The cursor starts at the current total the first time any
# instance claims, so pre-training stalls are charged to nobody. Stalls
# from an unrelated iterator (e.g. an eval loop) still land on whichever
# trainer steps next — the gauge is a per-process attribution, exact only
# in the common one-training-loop case (documented in
# docs/observability.md).
_stall_lock = threading.Lock()
_stall_claimed: Optional[float] = None


def _claim_feed_stall_ms() -> float:
    global _stall_claimed
    _, s = _catalog.IO_FEED_STALL_MS.totals()
    with _stall_lock:
        if _stall_claimed is None:
            _stall_claimed = s
            return 0.0
        d = max(0.0, s - _stall_claimed)
        _stall_claimed = s
        return d


def attribution_config(arg) -> Optional[Dict[str, Any]]:
    """Normalize the ``step_attribution`` ctor arg. None = the
    MXNET_PERF_ATTRIBUTION env default; any explicit falsy spelling
    (False/0/{}) = off; True/dict = on, dict may override ``window``
    (rolling steps the published means average over)."""
    if arg is None:
        if not get_env("MXNET_PERF_ATTRIBUTION", True):
            return None
        arg = True
    if not arg:
        return None
    cfg = dict(arg) if isinstance(arg, dict) else {}
    return {"window": max(2, int(cfg.get("window", 32)))}


class StepAttribution:
    """Rolling-window step decomposition for one trainer.

    ``observe()`` is called by ``DataParallelTrainer.step`` after each
    dispatch with the step's own timing marks; everything else (feed-stall
    delta, previous-loss readiness, gauge publication) happens here. All
    reads are non-blocking host calls — the device is never synced.
    """

    def __init__(self, cfg: Dict[str, Any], device_kind: Optional[str] = None,
                 n_devices: int = 1):
        self.window = int(cfg["window"])
        self.device_kind = device_kind
        self.n_devices = max(1, int(n_devices))
        self._win: deque = deque(maxlen=self.window)       # bucket tuples
        self._cadence: deque = deque(maxlen=self.window)   # seconds
        self._busy: deque = deque(maxlen=self.window)      # bools
        self._prev_exit: Optional[float] = None
        self._prev_loss = None
        self.steps = 0

    # ------------------------------------------------------------- feeding
    def observe(self, t_entry: float, t_exit: float, *,
                cadence_s: Optional[float], transfer_ms: float,
                dispatch_ms: float, loss_ref=None,
                flops_per_step: Optional[float] = None) -> None:
        """Record one step from its spans: ``trainer.step``'s start and
        ``trainer.enqueue``'s end (perf_counter), the time since the previous
        step's entry (None on the first), the durations of ``trainer.put``
        and ``trainer.enqueue``; ``loss_ref`` is the step's async device
        scalar (kept one step, polled non-blocking, never synced)."""
        total_ms = max(0.0, (t_exit - t_entry) * 1e3)
        host_prep = max(0.0, total_ms - transfer_ms - dispatch_ms)
        # new stall milliseconds of the io iterators (DeviceFeedIter's
        # ``feed.get_wait``, PrefetchingIter, ResilientDataIter) since any
        # attribution's last claim
        feed = _claim_feed_stall_ms()
        if self._prev_exit is not None:
            between = max(0.0, (t_entry - self._prev_exit) * 1e3 - feed)
        else:
            between = 0.0
        busy = None
        prev = self._prev_loss
        if prev is not None and hasattr(prev, "is_ready"):
            try:
                busy = not prev.is_ready()
            except Exception:       # deleted buffer on a retry path
                busy = None
        self._prev_loss = loss_ref
        if cadence_s is not None:
            self._cadence.append(max(1e-9, cadence_s))
        self._prev_exit = t_exit
        self._win.append((dispatch_ms, transfer_ms, host_prep, feed, between))
        if busy is not None:
            self._busy.append(busy)
        self.steps += 1
        self._publish(flops_per_step)

    # ----------------------------------------------------------- publishing
    def _means(self) -> Dict[str, float]:
        n = len(self._win)
        if not n:
            return {b: 0.0 for b in BUCKETS}
        sums = [0.0] * len(BUCKETS)
        for rec in self._win:
            for i, v in enumerate(rec):
                sums[i] += v
        return {b: sums[i] / n for i, b in enumerate(BUCKETS)}

    def _publish(self, flops_per_step: Optional[float]) -> None:
        for bucket, mean in self._means().items():
            _catalog.STEP_BREAKDOWN.set(mean, bucket=bucket)
        if self._busy:
            _catalog.DEVICE_UTIL.set(
                sum(1.0 for b in self._busy if b) / len(self._busy))
        mfu = self.mfu(flops_per_step)
        if mfu is not None:
            _catalog.MFU.set(mfu)

    def mfu(self, flops_per_step: Optional[float]) -> Optional[float]:
        """Model-FLOPs utilization over the window, or None when the flops
        (cost ledger) or the device peak (table/override) is unknown."""
        if not flops_per_step or not self._cadence:
            return None
        peak = _xcost.peak_flops(self.device_kind)
        if not peak:
            return None
        cad = sum(self._cadence) / len(self._cadence)
        return flops_per_step / (cad * peak * self.n_devices)

    def stats(self) -> Dict[str, Any]:
        """Point-in-time view of the window (for tools/tests): bucket
        means, device_util, mean cadence ms, steps observed."""
        out: Dict[str, Any] = {"buckets_ms": self._means(),
                               "steps": self.steps}
        out["device_util"] = (
            sum(1.0 for b in self._busy if b) / len(self._busy)
            if self._busy else None)
        out["cadence_ms"] = (
            sum(self._cadence) / len(self._cadence) * 1e3
            if self._cadence else None)
        return out
