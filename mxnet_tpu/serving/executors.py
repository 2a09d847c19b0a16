"""Bucketed executable cache — "compile few executables, route many requests".

One serving model owns a small ladder of padded batch buckets (keyed the
way ``BucketingModule`` keys its per-length executors); each bucket binds
ONE :class:`~mxnet_tpu.native.predict_bridge.Predictor` — i.e. one jitted
XLA program with fixed shapes — built lazily and kept for the life of the
server. A request batch of ``n`` rows is padded up to the smallest bucket
``>= n`` and dispatched through that program; the compiled-graph cost is
paid once per bucket, never per request (the TVM/Relay serving idiom).

Buckets default from the autotuner's warm-start cache when one exists:
``tuner.best_cached(device_kind, model=name)`` names the fastest measured
batch for this device, and the ladder is the powers of two up to it — a
serving deployment inherits the tuned config without re-searching. With
no cache (or ``MXNET_SERVE_BUCKETS`` set) an explicit/static ladder is
used. All predictors after the first share parameters via
``Predictor.reshape`` (the params are loaded and placed once).
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..analysis.lockwatch import make_lock
from ..base import MXNetError, get_env, logger, register_config

__all__ = ["BucketExecutorCache", "default_buckets"]

register_config("MXNET_SERVE_BUCKETS", "", str,
                "Comma list of padded-batch bucket sizes for the serving "
                "executable cache (e.g. '1,4,16,64'). Empty = derive from "
                "the tuner cache's best measured batch for this device/"
                "model, falling back to 1,2,4,8,16,32.")

_FALLBACK_BUCKETS = (1, 2, 4, 8, 16, 32)
_MAX_DEFAULT_BUCKET = 128


def _device_kind() -> Tuple[Optional[str], Optional[str]]:
    """``(device_kind, platform)`` of device 0, or ``(None, None)`` with
    no usable backend. THE device-provenance probe for serving — also
    stamped into ledger rows by :func:`serving.load.ledger_row`."""
    try:
        import jax
        d = jax.devices()[0]
        return d.device_kind, d.platform
    except Exception:
        return None, None


def resolve_device(dev_type: Optional[int],
                   dev_id: Optional[int]) -> Tuple[int, int]:
    """``(dev_type, dev_id)`` in the predictor's C-ABI codes (1 = cpu,
    2 = accelerator). ``dev_type=None`` follows
    :func:`~mxnet_tpu.context.current_context` — the chip when the host has
    one — so a server built with no device argument serves on the
    accelerator; an explicit ``dev_type=1`` still means the host CPU."""
    if dev_type is None:
        from ..context import current_context
        ctx = current_context()
        dev_type = 1 if ctx.device_type.startswith("cpu") else 2
        if dev_id is None:
            dev_id = ctx.device_id
    return int(dev_type), int(dev_id or 0)


def default_buckets(model: Optional[str] = None) -> Tuple[Tuple[int, ...], str]:
    """The bucket ladder to serve with, plus its provenance string.

    Priority: ``MXNET_SERVE_BUCKETS`` env > tuner warm-start cache (powers
    of two up to the best MEASURED batch for this device/model signature)
    > the static fallback ladder.
    """
    env = str(get_env("MXNET_SERVE_BUCKETS", "") or "").strip()
    if env:
        try:
            buckets = tuple(sorted({int(t) for t in env.split(",")
                                    if t.strip()}))
        except ValueError as e:
            raise MXNetError("MXNET_SERVE_BUCKETS: bad bucket list %r (%s)"
                             % (env, e))
        if not buckets or any(b < 1 for b in buckets):
            raise MXNetError("MXNET_SERVE_BUCKETS: buckets must be positive "
                             "ints, got %r" % (env,))
        return buckets, "env"
    try:
        from ..tuner import best_cached
        best = best_cached(device_kind=_device_kind()[0], model=model)
    except Exception:
        best = None
    if best and best.get("batch"):
        top = min(int(best["batch"]), _MAX_DEFAULT_BUCKET)
        ladder = [1]
        while ladder[-1] * 2 <= top:
            ladder.append(ladder[-1] * 2)
        if ladder[-1] != top:
            ladder.append(top)
        return tuple(ladder), "tuner:%s" % (best.get("config_key")
                                            or best.get("model") or "cached")
    return _FALLBACK_BUCKETS, "default"


class BucketExecutorCache:
    """bucket batch size -> bound Predictor, built lazily, params shared.

    Thread-use contract: the cache itself is lock-protected, and every
    Predictor carries its own per-handle lock, but a bucket's predictor is
    a single bound executor — the server drives each model from ONE worker
    thread (handle-per-worker), so dispatches never contend on a handle.
    """

    def __init__(self, symbol_json: str, param_bytes: bytes = b"", *,
                 input_name: str = "data",
                 feature_shape: Sequence[int],
                 buckets: Sequence[int],
                 dev_type: Optional[int] = None,
                 dev_id: Optional[int] = None,
                 output_keys: Optional[List[str]] = None,
                 chips: int = 1, model: Optional[str] = None):
        if not buckets:
            raise MXNetError("BucketExecutorCache needs at least one bucket")
        # serving model name, stamped into this cache's memory-ledger rows
        # (memwatch.model_footprint filters on it); None = anonymous cache
        self.model = str(model) if model else None
        self.input_name = str(input_name)
        self.feature_shape = tuple(int(x) for x in feature_shape)
        self.declared_buckets = tuple(sorted({int(b) for b in buckets}))
        if self.declared_buckets[0] < 1:
            raise MXNetError("bucket sizes must be >= 1, got %r"
                             % (self.declared_buckets,))
        self._symbol_json = symbol_json
        self._param_bytes = param_bytes
        self._dev = resolve_device(dev_type, dev_id)
        self._output_keys = output_keys
        self._lock = make_lock("serving.executors.BucketExecutorCache._lock")
        self._preds: Dict[int, object] = {}
        self._base = None           # first-built predictor: owns the params
        self.chips = 1
        self.bucket_cap: Optional[int] = None
        self.buckets = self.declared_buckets
        if int(chips) != 1:
            self.rebind(int(chips))

    @property
    def device(self):
        """The ``jax.Device`` every bucket's executor is committed to."""
        from ..native.predict_bridge import device_context
        return device_context(*self._dev).jax_device()

    @staticmethod
    def effective_buckets(declared: Sequence[int],
                          chips: int) -> Tuple[int, ...]:
        """The servable ladder at ``chips``: every declared bucket that
        tiles row-wise over the chip count (per-chip rows integral —
        the serving twin of the elastic trainer's global-batch re-split).
        Empty = an impossible split; the fleet refuses it with a typed
        ``TopologyMismatch`` via ``resilience.elastic.plan_chip_split``
        before ever calling :meth:`rebind`."""
        chips = int(chips)
        return tuple(b for b in sorted({int(x) for x in declared})
                     if chips >= 1 and b % chips == 0)

    def rebind(self, chips: int) -> Tuple[int, ...]:
        """Re-bind the cache's executables for a new chip count.

        The effective bucket ladder is re-derived (declared buckets that
        divide by ``chips``), every bucket's bound executable is dropped
        (its shapes assumed the old split) — but ``_base`` is KEPT, so
        the params stay loaded/placed once and new buckets re-bind via
        ``Predictor.reshape``. Returns the new ladder. Raises
        :class:`MXNetError` on an impossible split — callers that want
        the typed ``TopologyMismatch`` validate through
        ``resilience.elastic.plan_chip_split`` first."""
        chips = int(chips)
        eff = self.effective_buckets(self.declared_buckets, chips)
        if not eff:
            raise MXNetError(
                "no declared bucket in %r tiles over %d chip(s) "
                "(per-chip rows must be integral): impossible split"
                % (self.declared_buckets, chips))
        with self._lock:
            self.chips = chips
            self.buckets = self._capped_locked(eff)
            # executables for the old split are stale; params live on in
            # _base and are re-placed exactly once per server lifetime
            self._preds = {}
            return self.buckets

    def _capped_locked(self, ladder: Tuple[int, ...]) -> Tuple[int, ...]:
        """Apply the degraded-mode bucket cap to ``ladder``, keeping at
        least the smallest bucket (a cap below the whole ladder degrades
        to singles, it never empties the ladder)."""
        cap = self.bucket_cap
        if cap is None:
            return ladder
        capped = tuple(b for b in ladder if b <= cap)
        return capped or ladder[:1]

    def set_bucket_cap(self, cap: Optional[int]) -> Tuple[int, ...]:
        """Cap (or uncap, ``None``) the routable ladder — the degraded
        ladder's "drop the biggest bucket" rung. Cheap and reversible:
        already-bound executables above the cap stay cached (no re-bind
        when the cap lifts), they just stop being routed to. Returns the
        new effective ladder."""
        with self._lock:
            self.bucket_cap = None if cap is None else int(cap)
            eff = self.effective_buckets(self.declared_buckets, self.chips)
            self.buckets = self._capped_locked(eff)
            return self.buckets

    @property
    def max_bucket(self) -> int:
        with self._lock:        # rebind() swaps the ladder concurrently
            return self.buckets[-1]

    def bucket_for(self, n: int) -> int:
        """Smallest bucket >= n. n above the largest bucket is a caller
        bug — the batcher caps assembly at max_bucket."""
        with self._lock:        # one consistent ladder for the whole scan
            buckets = self.buckets
        for b in buckets:
            if b >= n:
                return b
        raise MXNetError("batch of %d rows exceeds the largest bucket %d"
                         % (n, buckets[-1]))

    def get(self, bucket: int):
        """The bound predictor for one bucket, building it on first use.
        A fresh bind also records this bucket's ``label="memory"`` ledger
        row (memwatch) when the cost ledger is on."""
        with self._lock:
            p = self._preds.get(bucket)
            if p is not None:
                return p
            if bucket not in self.buckets:
                raise MXNetError("unknown bucket %d (ladder: %r)"
                                 % (bucket, self.buckets))
            from ..native.predict_bridge import Predictor
            shape = {self.input_name: (bucket,) + self.feature_shape}
            if self._base is None:
                p = Predictor(self._symbol_json, self._param_bytes,
                              self._dev[0], self._dev[1], shape,
                              output_keys=self._output_keys)
                self._base = p
            else:
                p = self._base.reshape(shape)
            self._preds[bucket] = p
            chips = self.chips  # snapshot: rebind() swaps it under _lock
        # outside the cache lock: the memory row needs an analysis
        # compile, and holding _lock through a compile would stall
        # bucket_for/rebind on an unrelated bucket's first bind
        self._record_memory_row(int(bucket), p, chips)
        return p

    def _record_memory_row(self, bucket: int, pred, chips: int) -> None:
        """One ``label="memory"`` ledger row for a freshly bound bucket:
        the per-executable byte accounting model_footprint and the fleet's
        placement math read back. Gated like every capture (telemetry +
        ledger + MXNET_MEM_CAPTURE); never raises."""
        from ..observability import memwatch as _memwatch
        from ..observability import metrics as _m
        from ..observability import xcost as _xcost
        if not (_m.enabled() and _xcost.enabled()
                and _memwatch.capture_enabled()):
            return
        try:
            ex = pred._exec
            fn = ex._compiled(False)
            if not hasattr(fn, "lower"):
                return                      # eagerly-run executor: no program
            import jax
            inputs = {n: a._data for n, a in ex.arg_dict.items()}
            inputs.update({n: a._data for n, a in ex.aux_dict.items()})
            lowered = fn.lower(inputs, jax.random.PRNGKey(0))
            kind, platform = _device_kind()
            _memwatch.record_executable(
                lowered, label="serving.bucket",
                device_kind=kind, platform=platform, n_devices=chips,
                extra={"model": self.model, "bucket": int(bucket)})
        except Exception as e:              # accounting must never bind-fail
            logger.warning("bucket memory row capture failed (model=%r "
                           "bucket=%d): %r", self.model, bucket, e)

    def warm(self, buckets: Optional[Sequence[int]] = None) -> List[int]:
        """Compile (bind + one dummy forward) the given buckets — all of
        them by default — so the first real request never pays a compile.
        Returns the list warmed."""
        done = []
        with self._lock:        # snapshot the ladder; get() re-validates
            ladder = self.buckets
        for b in (buckets or ladder):
            pred = self.get(int(b))
            dummy = np.zeros((int(b),) + self.feature_shape, np.float32)
            pred.predict({self.input_name: dummy})
            done.append(int(b))
        return done

    def compiled_buckets(self) -> List[int]:
        with self._lock:
            return sorted(self._preds)

    def run(self, batch: np.ndarray) -> np.ndarray:
        """Dispatch ``batch`` (n rows of ``feature_shape``) through the
        right bucket; returns the FIRST output's first ``n`` rows (the
        padding rows are computed and discarded — the price of shape
        stability)."""
        batch = np.ascontiguousarray(batch, dtype=np.float32)
        n = int(batch.shape[0])
        b = self.bucket_for(n)
        if batch.shape[1:] != self.feature_shape:
            raise MXNetError(
                "batch feature shape %r does not match the model's %r"
                % (tuple(batch.shape[1:]), self.feature_shape))
        if b != n:
            padded = np.zeros((b,) + self.feature_shape, np.float32)
            padded[:n] = batch
            batch = padded
        outs = self.get(b).predict({self.input_name: batch})
        return np.asarray(outs[0])[:n]
