"""Chip-loss self-healing and tail tolerance for the model server.

Three cooperating mechanisms, all host-side (the compiled forward's
StableHLO is bitwise identical with every one of them on or off):

**DeviceSentinel** — the third failure class. Next to *transient*
(``resilience.retry.is_transient``: retry with backoff) and *OOM*
(``memwatch.is_oom``: typed refusal, never retried) sits *device-fatal*
(:func:`is_device_fatal`): DEVICE_LOST / "failed to enqueue" / data-loss
markers that mean the CHIP is suspect, not the request. A device-fatal
dispatch error quarantines the chip (typed
:class:`~mxnet_tpu.serving.errors.ChipQuarantined`, counted in
``mxtpu_chip_quarantines_total{reason}``), the server re-plans the bucket
ladder over the survivors via ``plan_chip_split`` + ``rebind``
(:func:`replan_after_loss` — memory-checked through memwatch's
``placement_check``), and the failed batch's live batchmates are
re-dispatched on the survivors — in-flight work is never silently lost.
Re-admission is breaker-style half-open: after ``MXNET_SENTINEL_
COOLDOWN_S`` the chip is probed (an injectable canary; optimistic
time-based re-admission with no probe configured) and, on success,
restored — capacity rebinds back to the pre-loss chip count.

**DegradedLadder** — the serving twin of the resilience recovery ladder:
``healthy → reduced buckets (drop the biggest) → int8 tier fallback →
guaranteed-traffic-only admission → static shed``. Transitions are
edge-triggered (one trace-ring event + ``mxtpu_serve_degraded_rung``
gauge move per rung change); effects are applied by the model's own
worker thread outside the dispatch path, and the ladder de-escalates one
rung per healthy cooldown interval.

**HedgeMonitor + retry budget** — opt-in per-model hedged requests
(``ModelConfig(hedge=True)``): a request still unanswered after a
rolling-p99-derived delay is dispatched a second time directly against
the bucket cache; the first result wins (the loser's is dropped —
``mxtpu_serve_hedges_total{outcome}``). Every retry and every hedge
spends from a shared token-bucket :class:`~mxnet_tpu.serving.queueing.
RetryBudget` funded at ~``MXNET_SERVE_RETRY_BUDGET`` (default 10%) of
admitted traffic, so tail-tolerance can never amplify an overload into a
retry storm — denials are typed and counted
(``mxtpu_retry_budget_denied_total``), never silent.
"""
from __future__ import annotations

import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..analysis.lockwatch import make_lock
from ..base import get_env, logger, register_config
from ..observability import memwatch as _memwatch
from .errors import Overloaded

__all__ = ["is_device_fatal", "device_fatal_reason", "chip_of",
           "DeviceSentinel", "DegradedLadder", "HedgeMonitor",
           "replan_after_loss", "RUNGS"]

register_config("MXNET_SENTINEL_COOLDOWN_S", 5.0, float,
                "Seconds a quarantined chip sits out before the device "
                "sentinel attempts half-open re-admission (probe it if a "
                "canary is configured, readmit optimistically otherwise).")
register_config("MXNET_SENTINEL_PROBE_S", 0.0, float,
                "Interval of the background per-chip canary probe (a tiny "
                "jitted program). 0 (default) = no probe thread; "
                "quarantined chips re-admit on cooldown expiry alone.")

# Substrings that mark a DEVICE-fatal runtime error: the chip (or its
# runtime attachment) is gone or corrupting, so the error must never be
# retried in place — quarantine + re-place instead. Ordered: the first
# match names the quarantine reason label.
_DEVICE_FATAL_MARKERS: Tuple[Tuple[str, str], ...] = (
    ("device_lost", "device_lost"),
    ("device lost", "device_lost"),
    ("failed to enqueue", "enqueue"),
    ("data_loss", "data_loss"),
    ("data loss", "data_loss"),
    ("hardware failure", "other"),
)

_CHIP_RE = re.compile(r"chip\s*[#:]?\s*(\d+)")


def _walk(exc: BaseException):
    """The exception plus its cause/context chain (cycle-safe) — the same
    walk memwatch.is_oom does, so a wrapped device-fatal error keeps its
    classification through retry and boundary layers."""
    seen = set()
    e: Optional[BaseException] = exc
    while e is not None and id(e) not in seen:
        seen.add(id(e))
        yield e
        e = e.__cause__ if e.__cause__ is not None else e.__context__


def is_device_fatal(exc: BaseException) -> bool:
    """Third failure class: does this error mean the CHIP is suspect?

    True for DEVICE_LOST / failed-to-enqueue / data-loss markers anywhere
    in the cause chain. An OOM is NOT device-fatal (``memwatch.is_oom``
    wins — RESOURCE_EXHAUSTED is a capacity fact with its own typed
    fate); neither class is ever retried by ``retry_transient``.
    """
    if _memwatch.is_oom(exc):
        return False
    for e in _walk(exc):
        msg = str(e).lower()
        if any(m in msg for m, _ in _DEVICE_FATAL_MARKERS):
            return True
    return False


def device_fatal_reason(exc: BaseException) -> str:
    """The quarantine reason label for a device-fatal error:
    ``device_lost`` | ``enqueue`` | ``data_loss`` | ``other``."""
    for e in _walk(exc):
        msg = str(e).lower()
        for marker, reason in _DEVICE_FATAL_MARKERS:
            if marker in msg:
                return reason
    return "other"


def chip_of(exc: BaseException) -> Optional[int]:
    """Which chip a device-fatal error blames: an explicit ``chip_idx``
    attribute anywhere in the cause chain (the runtime/chaos contract),
    else the first ``chip N`` mention in the message, else None (the
    caller falls back to the model's bound device)."""
    for e in _walk(exc):
        idx = getattr(e, "chip_idx", None)
        if idx is not None:
            return int(idx)
    for e in _walk(exc):
        m = _CHIP_RE.search(str(e).lower())
        if m:
            return int(m.group(1))
    return None


def replan_after_loss(server, st, chip: int, cause: BaseException):
    """Re-place one model's bucket ladder on the survivors of a chip loss.

    Called from the dispatch path with the model's ``dispatch_mutex``
    already held (the failed dispatch IS the quiesce), so the rebind is
    race-free by construction. Picks the largest chip count below the
    current one whose effective ladder is non-empty, validates it through
    ``plan_chip_split`` (typed) and memwatch's ``placement_check``
    (params replicate per chip — a shrink CONCENTRATES the footprint),
    rebinds, and notes the fleet bookkeeping. Returns the reshard plan,
    or None when no feasible smaller placement exists (single chip, no
    tiling bucket, or nothing fits the HBM budget) — the caller then
    escalates the degraded ladder instead.
    """
    from ..resilience.elastic import TopologyMismatch, plan_chip_split
    cache = st.cache
    old = cache.chips
    if old <= 1:
        return None
    declared = cache.declared_buckets
    model = st.cfg.name
    for new in range(old - 1, 0, -1):
        if not cache.effective_buckets(declared, new):
            continue
        try:
            plan = plan_chip_split(model, declared, old, new)
        except TopologyMismatch:
            continue
        try:
            fp = _memwatch.model_footprint(cache, model=model)
            chk = _memwatch.placement_check(fp, new)
        except Exception:
            chk = {"ok": True}
        if not chk.get("ok", True):
            server._count_mem_refusal("chip_loss")
            logger.error("chip-loss replan of %r to %d chip(s) refused: "
                         "survivors would not fit the HBM budget "
                         "(need ~%s bytes/chip, budget %s)", model, new,
                         chk.get("need_bytes"), chk.get("budget_bytes"))
            continue
        eff = cache.rebind(new)
        server._sentinel._note_replan(model, old)
        server.tracer.record_event(
            "replan", model=model, chip=int(chip), old_chips=old,
            new_chips=new, reason="chip_loss",
            buckets=",".join(str(b) for b in eff))
        fleet = getattr(server, "_fleet", None)
        if fleet is not None:
            fleet.note_chip_loss(model, old, new, chip)
        logger.error("chip %d lost (%r): model %r re-placed %d -> %d "
                     "chip(s); effective buckets %r", chip, cause, model,
                     old, new, eff)
        return plan
    return None


class DeviceSentinel:
    """Quarantine set + half-open re-admission for suspect chips.

    One per server. :meth:`quarantine` is called from the dispatch path
    (under that model's ``dispatch_mutex``) and only touches the
    sentinel's own state; re-admission (:meth:`maybe_readmit`, driven by
    the per-model worker tick or the optional canary thread) NEVER holds
    the sentinel lock across a ``dispatch_mutex`` acquisition — the two
    lock orders would otherwise form the exact cycle lockwatch exists to
    catch. A chip past its cooldown is probed (injectable canary via
    :meth:`set_probe`; none configured = optimistic re-admission — live
    traffic is the probe, exactly the circuit breaker's half-open
    bargain); a failed probe re-arms the cooldown and counts
    ``reason="probe"``. When the last chip re-admits, every model whose
    ladder was re-planned after a loss is restored to its pre-loss chip
    count.
    """

    def __init__(self, server, cooldown_s: Optional[float] = None,
                 probe_interval_s: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic):
        self._server = server
        self.cooldown_s = float(get_env("MXNET_SENTINEL_COOLDOWN_S", 5.0)
                                if cooldown_s is None else cooldown_s)
        self.probe_interval_s = float(
            get_env("MXNET_SENTINEL_PROBE_S", 0.0)
            if probe_interval_s is None else probe_interval_s)
        self._clock = clock
        self._lock = make_lock("serving.health.DeviceSentinel._lock")
        self._quarantined: Dict[int, Dict[str, Any]] = {}
        self._restore: Dict[str, int] = {}     # model -> pre-loss chips
        self._probe: Optional[Callable[[int], bool]] = None
        self._last_unhealthy: Optional[float] = None
        self._next_tick = 0.0                  # benign-race tick gate
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -------------------------------------------------------- quarantine
    def quarantine(self, chip: int, reason: str = "other",
                   model: Optional[str] = None) -> None:
        """Put ``chip`` in quarantine (idempotent — a repeat extends the
        cooldown and keeps the original ``since``)."""
        now = self._clock()
        chip = int(chip)
        with self._lock:
            info = self._quarantined.get(chip)
            since = info["since"] if info else now
            self._quarantined[chip] = {"since": since, "reason": reason,
                                       "until": now + self.cooldown_s}
            n = len(self._quarantined)
        self._last_unhealthy = now
        self._count_quarantine(reason, n)
        self._server.tracer.record_event("quarantine", chip=chip,
                                         reason=reason, model=model)
        logger.error("device sentinel: chip %d QUARANTINED (%s, model=%r);"
                     " re-admission probe in %.1fs", chip, reason, model,
                     self.cooldown_s)

    def is_quarantined(self, chip: int) -> bool:
        with self._lock:
            return int(chip) in self._quarantined

    def quarantined(self) -> Dict[int, Dict[str, Any]]:
        with self._lock:
            return {c: dict(i) for c, i in self._quarantined.items()}

    def count(self) -> int:
        with self._lock:
            return len(self._quarantined)

    def _note_replan(self, model: str, old_chips: int) -> None:
        """Remember the FIRST pre-loss chip count per model so a cascade
        of losses still restores to the original placement."""
        with self._lock:
            self._restore.setdefault(model, int(old_chips))

    def set_probe(self, probe: Optional[Callable[[int], bool]]) -> None:
        """Install the re-admission canary: ``probe(chip) -> bool``. The
        chaos quarantine-flap lever plugs in here; None = optimistic
        time-based re-admission."""
        with self._lock:
            self._probe = probe

    # ------------------------------------------------------ re-admission
    def tick(self, st=None) -> None:
        """Cheap periodic hook, called by each model worker per loop (and
        by the canary thread): apply pending ladder effects, then — at
        most every ``cooldown/4`` (capped 50 ms) — run re-admission and
        de-escalation checks."""
        ladder = getattr(st, "ladder", None) if st is not None else None
        if ladder is not None:
            ladder.apply()
        now = self._clock()
        if now < self._next_tick:
            return
        self._next_tick = now + min(0.05, max(0.001, self.cooldown_s / 4))
        self.maybe_readmit()
        if ladder is not None and ladder.rung > 0 and self.count() == 0:
            last_bad = max(self._last_unhealthy or 0.0, ladder.last_change)
            if now - last_bad >= self.cooldown_s:
                ladder.de_escalate("healthy")

    def maybe_readmit(self) -> List[int]:
        """Half-open re-admission for every chip past its cooldown.
        Returns the chips re-admitted this pass."""
        now = self._clock()
        with self._lock:
            due = [c for c, i in self._quarantined.items()
                   if now >= i["until"]]
            probe = self._probe
        readmitted: List[int] = []
        for chip in due:
            ok = True
            if probe is not None:
                try:
                    ok = bool(probe(chip))
                except Exception:
                    ok = False
            if ok:
                with self._lock:
                    info = self._quarantined.pop(chip, None)
                    n = len(self._quarantined)
                if info is None:
                    continue
                readmitted.append(chip)
                self._set_gauge(n)
                self._server.tracer.record_event("readmit", chip=chip,
                                                 reason=info["reason"])
                logger.warning("device sentinel: chip %d re-admitted "
                               "after %.1fs quarantine (%s)", chip,
                               now - info["since"], info["reason"])
            else:
                with self._lock:
                    if chip in self._quarantined:
                        self._quarantined[chip]["until"] = \
                            now + self.cooldown_s
                    n = len(self._quarantined)
                self._last_unhealthy = now
                self._count_quarantine("probe", n)
                logger.error("device sentinel: chip %d FAILED its re-"
                             "admission probe; cooling down %.1fs more",
                             chip, self.cooldown_s)
        if readmitted:
            with self._lock:
                restore = dict(self._restore) if not self._quarantined \
                    else {}
                if restore:
                    self._restore.clear()
            if restore:
                self._restore_capacity(restore)
        return readmitted

    def _restore_capacity(self, restore: Dict[str, int]) -> None:
        """Every quarantined chip is back: rebind each re-planned model
        to its pre-loss chip count (through the fleet when one is
        attached, so placement bookkeeping and counters stay true)."""
        from ..resilience.elastic import plan_chip_split
        server = self._server
        fleet = getattr(server, "_fleet", None)
        for model, chips in restore.items():
            st = server._models.get(model)
            if st is None or st.cache.chips == chips:
                continue
            try:
                if fleet is not None:
                    fleet.resize(model, chips, reason="readmit")
                else:
                    plan_chip_split(model, st.cache.declared_buckets,
                                    st.cache.chips, chips)
                    with st.dispatch_mutex:
                        eff = st.cache.rebind(chips)
                    server.tracer.record_event(
                        "replan", model=model, new_chips=chips,
                        reason="readmit",
                        buckets=",".join(str(b) for b in eff))
                logger.warning("device sentinel: model %r restored to %d "
                               "chip(s) after re-admission", model, chips)
            except Exception as e:      # restoration must never kill a worker
                logger.error("post-readmission restore of %r to %d "
                             "chip(s) failed: %r", model, chips, e)

    # ------------------------------------------------------ canary probe
    def start(self) -> "DeviceSentinel":
        """Spawn the background canary thread when MXNET_SENTINEL_PROBE_S
        is set; otherwise a no-op (the worker tick drives re-admission)."""
        if self.probe_interval_s <= 0:
            return self
        if self._thread is not None and self._thread.is_alive():
            return self
        self._stop.clear()
        t = threading.Thread(target=self._run, daemon=True,
                             name="mxserve-sentinel")
        self._thread = t
        t.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=max(1.0, 2 * self.probe_interval_s))
        self._thread = None

    def _run(self) -> None:
        while not self._stop.wait(self.probe_interval_s):
            try:
                self._canary()
                self.maybe_readmit()
            except Exception as e:      # the sentinel must never die
                logger.exception("sentinel canary pass failed: %r", e)

    def _canary(self) -> None:
        """One canary heartbeat: a tiny jitted program on the backend. A
        device-fatal failure quarantines the blamed chip — the sentinel
        notices a dead chip even between real dispatches."""
        try:
            import jax
            import jax.numpy as jnp
            fn = getattr(self, "_canary_fn", None)
            if fn is None:
                fn = jax.jit(lambda x: x + 1.0)
                self._canary_fn = fn
            np.asarray(fn(jnp.zeros((8,), jnp.float32)))
        except Exception as e:
            if is_device_fatal(e):
                chip = chip_of(e)
                self.quarantine(chip if chip is not None else 0,
                                reason=device_fatal_reason(e))

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {"quarantined": {c: dict(i)
                                    for c, i in self._quarantined.items()},
                    "cooldown_s": self.cooldown_s,
                    "restore": dict(self._restore)}

    # --------------------------------------------------------- telemetry
    @staticmethod
    def _count_quarantine(reason: str, n: int) -> None:
        from ..observability import metrics as _m
        if _m.enabled():
            from ..observability import catalog as _c
            _c.CHIP_QUARANTINES.inc(reason=reason)
            _c.QUARANTINED_CHIPS.set(n)

    @staticmethod
    def _set_gauge(n: int) -> None:
        from ..observability import metrics as _m
        if _m.enabled():
            from ..observability import catalog as _c
            _c.QUARANTINED_CHIPS.set(n)


RUNGS = ("healthy", "reduced_buckets", "int8", "guaranteed_only", "shed")


class DegradedLadder:
    """Per-model degraded-mode ladder — the serving twin of the
    resilience recovery ladder.

    Rungs: 0 healthy · 1 reduced buckets (biggest dropped — less padding
    waste, smaller working set) · 2 int8 tier fallback (the cheaper
    executable) · 3 guaranteed-traffic-only admission · 4 static shed.
    Transitions are EDGE-triggered: one ``mxtpu_serve_degraded_rung``
    gauge move and one trace-ring ``degraded`` event per change, never
    per request. Escalation happens where trouble is seen (the dispatch
    path, under ``dispatch_mutex``); the executable-level *effects*
    (bucket cap, tier swap) are applied by the model's own worker via
    :meth:`apply` OUTSIDE the dispatch, which takes ``dispatch_mutex``
    itself — so no rung change ever nests one model's mutex under
    another lock. Admission effects (rungs 3/4) are immediate pure
    checks in ``submit``.
    """

    def __init__(self, server, st):
        self._server = server
        self._st = st
        self._lock = make_lock("serving.health.DegradedLadder._lock")
        self._rung = 0
        self._applied = 0
        self._saved = None          # (cfg, cache) before the int8 swap
        self.last_change = 0.0

    @property
    def rung(self) -> int:
        with self._lock:
            return self._rung

    def name(self, rung: Optional[int] = None) -> str:
        return RUNGS[self.rung if rung is None else int(rung)]

    # ------------------------------------------------------- transitions
    def escalate(self, reason: str) -> int:
        with self._lock:
            if self._rung >= len(RUNGS) - 1:
                return self._rung
            self._rung += 1
            rung = self._rung
            self.last_change = time.monotonic()
        self._publish(rung, "up", reason)
        return rung

    def de_escalate(self, reason: str = "healthy") -> int:
        with self._lock:
            if self._rung <= 0:
                return 0
            self._rung -= 1
            rung = self._rung
            self.last_change = time.monotonic()
        self._publish(rung, "down", reason)
        return rung

    def _publish(self, rung: int, direction: str, reason: str) -> None:
        model = self._st.cfg.name
        from ..observability import metrics as _m
        if _m.enabled():
            from ..observability import catalog as _c
            _c.SERVE_DEGRADED_RUNG.set(rung, model=model)
        self._server.tracer.record_event(
            "degraded", model=model, rung=rung, mode=RUNGS[rung],
            direction=direction, reason=reason)
        log = logger.error if direction == "up" else logger.warning
        log("degraded ladder: model %r %s to rung %d (%s): %s", model,
            "ESCALATED" if direction == "up" else "de-escalated", rung,
            RUNGS[rung], reason)

    # --------------------------------------------------------- admission
    def admit_check(self, req) -> None:
        """Rungs 3/4 gate admission; pure check, raises typed
        ``Overloaded`` carrying ``degraded=True`` (counted shed with
        reason="degraded")."""
        rung = self.rung
        if rung >= 4:
            e = Overloaded(
                "model %r degraded to static shed (rung 4): retry "
                "against another replica" % self._st.cfg.name)
            e.degraded = True
            raise e
        if rung == 3 and getattr(req, "priority", None) != "guaranteed":
            e = Overloaded(
                "model %r serving guaranteed traffic only (degraded "
                "rung 3): best-effort work shed" % self._st.cfg.name)
            e.degraded = True
            raise e

    # ----------------------------------------------------------- effects
    def apply(self) -> None:
        """Bring the executable-level effects in line with the current
        rung. Called by the model's worker each loop; a no-op (one int
        compare) when nothing changed. Takes ``dispatch_mutex`` itself —
        callers must not hold it (or any ladder/sentinel lock)."""
        target = self.rung
        if target == self._applied:
            return
        st = self._st
        with st.dispatch_mutex:
            self._apply_bucket_cap(target)
            self._apply_tier(target)
            self._applied = target

    def _apply_bucket_cap(self, rung: int) -> None:
        st = self._st
        declared = st.cache.declared_buckets
        if rung >= 1 and len(declared) > 1:
            st.cache.set_bucket_cap(declared[-2])
        else:
            st.cache.set_bucket_cap(None)

    def _apply_tier(self, rung: int) -> None:
        """Rung >= 2: swap to the int8 executable (best-effort — a graph
        the quant pass can't rewrite keeps serving f32); below: restore
        the saved f32 state. The old cache is kept whole, so restoration
        re-places nothing."""
        st = self._st
        if rung >= 2:
            if st.cfg.tier == "int8" or self._saved is not None:
                return
            try:
                import copy

                from ..quant import ensure_tier
                from .executors import BucketExecutorCache
                cfg2 = copy.copy(st.cfg)
                cfg2.tier = "int8"
                cfg2 = ensure_tier(cfg2)
                cache2 = BucketExecutorCache(
                    cfg2.symbol_json, cfg2.param_bytes,
                    input_name=cfg2.input_name,
                    feature_shape=cfg2.feature_shape,
                    buckets=st.cache.declared_buckets,
                    dev_type=cfg2.dev_type, dev_id=cfg2.dev_id,
                    output_keys=cfg2.output_keys,
                    chips=st.cache.chips, model=cfg2.name)
                cache2.set_bucket_cap(st.cache.bucket_cap)
                self._saved = (st.cfg, st.cache)
                st.cfg, st.cache = cfg2, cache2
                logger.warning("degraded ladder: model %r now serving "
                               "the int8 tier", cfg2.name)
            except Exception as e:
                logger.error("degraded ladder: int8 fallback for %r "
                             "unavailable (%r); staying on %s", st.cfg.name,
                             e, st.cfg.tier)
        elif self._saved is not None:
            cfg, cache = self._saved
            self._saved = None
            try:
                if cache.chips != st.cache.chips:
                    cache.rebind(st.cache.chips)
                cache.set_bucket_cap(st.cache.bucket_cap)
            except Exception as e:
                logger.error("degraded ladder: could not re-align the "
                             "restored f32 cache for %r: %r", cfg.name, e)
            st.cfg, st.cache = cfg, cache
            logger.warning("degraded ladder: model %r restored to the "
                           "%s tier", cfg.name, cfg.tier)


class HedgeMonitor:
    """Fires hedged duplicates of requests still unanswered after a
    rolling-p99-derived delay.

    One thread per server, started only when some model opted in
    (``ModelConfig(hedge=True)``). The hedge runs DIRECTLY against the
    bucket cache (bucket 1) on its own short-lived thread — the model's
    serial worker may be stuck behind the very straggler the hedge is
    racing, so going through the queue could never win. First completed
    result claims the request's future (``PendingResult`` is first-wins);
    the loser's result is dropped and counted. Every hedge spends a
    retry-budget token first — a denied hedge is counted
    (``budget_denied``), never fired.
    """

    _SCAN_S = 0.05      # idle wake to notice stop/new registrations

    def __init__(self, server, clock: Callable[[], float] = time.monotonic):
        self._server = server
        self._clock = clock
        self._lock = make_lock("serving.health.HedgeMonitor._lock")
        self._cond = threading.Condition(self._lock)
        self._pending: List[Tuple[float, Any, Any]] = []
        self._stopped = False
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "HedgeMonitor":
        if self._thread is not None and self._thread.is_alive():
            return self
        with self._cond:
            self._stopped = False
        t = threading.Thread(target=self._run, daemon=True,
                             name="mxserve-hedge")
        self._thread = t
        t.start()
        return self

    def stop(self) -> None:
        with self._cond:
            self._stopped = True
            self._cond.notify_all()
        t = self._thread
        if t is not None:
            t.join(timeout=2.0)
        self._thread = None

    def hedge_delay_ms(self, st) -> float:
        """The hedge trigger delay: the model's rolling p99 once at least
        32 completed requests inform it, else the configured
        ``hedge_delay_ms`` floor."""
        with st.lock:
            lat = st.latencies[-512:]
        if len(lat) >= 32:
            return float(np.percentile(np.asarray(lat, np.float64), 99))
        return float(st.cfg.hedge_delay_ms)

    def register(self, st, req) -> None:
        """Arm one hedge for an admitted request (called by submit)."""
        fire_at = self._clock() + self.hedge_delay_ms(st) / 1e3
        with self._cond:
            if self._stopped:
                return
            self._pending.append((fire_at, st, req))
            self._cond.notify()

    def _run(self) -> None:
        while True:
            with self._cond:
                if self._stopped:
                    return
                now = self._clock()
                due = [e for e in self._pending if e[0] <= now]
                if due:
                    self._pending = [e for e in self._pending
                                     if e[0] > now]
                else:
                    nxt = min((e[0] for e in self._pending),
                              default=now + self._SCAN_S)
                    self._cond.wait(
                        timeout=max(0.001, min(nxt - now, self._SCAN_S)))
                    continue
            for _, st, req in due:
                try:
                    self._maybe_fire(st, req)
                except Exception as e:  # the monitor must never die
                    logger.exception("hedge fire failed for %r: %r",
                                     st.cfg.name, e)

    def _maybe_fire(self, st, req) -> None:
        if req.pending.done():
            return                      # answered in time: no hedge needed
        now = self._clock()
        if req.deadline is not None and req.deadline <= now:
            return                      # past deadline: a hedge can't help
        budget = st.budget
        if budget is not None and not budget.try_spend("hedge"):
            self._server._count_budget_denied(st, "hedge")
            self._count(st, "budget_denied")
            return
        with st.lock:
            st.hedges["fired"] += 1
        threading.Thread(target=self._run_hedge, args=(st, req),
                         daemon=True, name="mxserve-hedge-fire").start()

    def _run_hedge(self, st, req) -> None:
        try:
            rows = st.cache.run(req.data[None])
        except Exception as e:
            # the hedge errored: drop it silently-but-counted — the
            # PRIMARY dispatch stays authoritative for errors (a hedge
            # must never complete a request that might still succeed)
            logger.warning("hedge dispatch for %r failed (dropped): %r",
                           st.cfg.name, e)
            self._count(st, "lost")
            return
        if self._server._complete(st, req, value=rows[0], outcome="ok"):
            self._count(st, "won")
        else:
            self._count(st, "lost")     # the primary got there first

    def _count(self, st, outcome: str) -> None:
        with st.lock:
            st.hedges[outcome] = st.hedges.get(outcome, 0) + 1
        from ..observability import metrics as _m
        if _m.enabled():
            from ..observability import catalog as _c
            _c.SERVE_HEDGES.inc(model=st.cfg.name, outcome=outcome)
