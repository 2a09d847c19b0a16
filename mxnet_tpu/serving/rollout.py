"""Safe model rollout: versioned deploys, shadow/canary traffic, gated
automatic rollback, zero-downtime hot-swap.

The serving spine can shed, trace, quantize, autoscale and survive chip
loss — but every model version was frozen at server start: shipping a
retrained checkpoint meant a restart, exactly the failure window all
that machinery exists to avoid. This module is the deploy edge:
multiple versions of one model resident as independent executables,
with **traffic as the only thing that moves**.

**Versioned registry** — :meth:`RolloutManager.start` loads a candidate
version next to the incumbent: its own :class:`~mxnet_tpu.serving.
executors.BucketExecutorCache` + params + circuit breaker + SLO
tracker, built and warmed on a background loader thread while the
incumbent keeps serving. The load is memory-checked the same way
server start is (memwatch HBM budget): a canary that does not fit next
to the resident models is refused with a typed
:class:`~mxnet_tpu.serving.errors.MemoryBudgetExceeded` — it never
OOMs the incumbent.

**Traffic splitter** — a deterministic hash of the request's trace id
(so one request never flip-flops between versions across client
retries, and the server-side retry/hedge paths act on whichever
version's state admitted it) drives the staged ramp
``shadow → 1% → 10% → 50% → 100%``. Shadow mode answers every request
from the incumbent and dual-dispatches a sampled fraction against the
canary, scoring top-1 agreement — the same statistic the quant
``evaluate_agreement`` harness reports for int8 tiers (and
:meth:`Rollout.evaluate_agreement` re-runs that harness verbatim over
the buffered shadow inputs for an offline-grade readout).

**Rollback gate** — each ramp stage holds for a dwell window and
promotes only if the canary's own SLO burn rate, p99-vs-incumbent
delta, error fraction, breaker state and shadow agreement all pass.
Any gate failure triggers automatic rollback: edge-triggered (one
trace-ring ``rollout`` event + one
``mxtpu_rollout_rollbacks_total{reason=}`` bump per transition), with
the incumbent back at 100% of new traffic in one atomic splitter swap.

**Zero-downtime promotion/retirement** — the final swap happens under
the model's existing ``dispatch_mutex`` (the same quiesce point fleet
resizes and the degraded ladder use), so the in-flight batch finishes
on the old executable and the next dispatch runs the new one; the
retiring version's queue is closed (typed ``Draining`` to the racing
submit, accepted work finishes) and its executables are dropped only
after its worker drained. No accepted request is ever lost to a swap,
and the served StableHLO is bitwise identical with the rollout layer
on or off (pinned by test_rollout).

Operate it via ``GET/POST /rolloutz`` (endpoints.py) or
``tools/mxrollout.py``; guard it with mxlint MXL-T220
(``ungated-rollout``). Docs: ``docs/serving.md``.
"""
from __future__ import annotations

import copy
import threading
import time
import zlib
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..analysis.lockwatch import make_lock
from ..base import MXNetError, get_env, logger, register_config
from ..observability import memwatch as _memwatch
from ..observability import tracing as _tracing
from . import health as _health
from .errors import MemoryBudgetExceeded

__all__ = ["RolloutManager", "Rollout", "STAGES"]

register_config("MXNET_ROLLOUT_DWELL_S", 10.0, float,
                "Seconds each rollout ramp stage holds before the gate "
                "may promote it. The rollback gate is evaluated "
                "continuously; the dwell only paces promotion.")
register_config("MXNET_ROLLOUT_SHADOW_SAMPLE", 0.25, float,
                "Fraction of incumbent-served requests dual-dispatched "
                "against the canary for shadow agreement scoring "
                "(deterministic on the request hash). 0 disables shadow "
                "comparison — mxlint MXL-T220 flags it.")
register_config("MXNET_ROLLOUT_MIN_AGREEMENT", 0.98, float,
                "Minimum shadow top-1 agreement (canary vs incumbent) "
                "the gate requires; below it the rollout rolls back "
                "with reason='agreement'.")
register_config("MXNET_ROLLOUT_MIN_SHADOW", 8, int,
                "Shadow samples required before the agreement score is "
                "trusted (and before the shadow stage may promote).")
register_config("MXNET_ROLLOUT_MIN_REQUESTS", 20, int,
                "Canary-served requests a ramp stage needs before it "
                "may promote (the gate never promotes on no evidence).")
register_config("MXNET_ROLLOUT_P99_SLACK", 0.5, float,
                "Allowed canary p99 regression vs the incumbent: the "
                "gate rolls back when canary_p99 > incumbent_p99 * "
                "(1 + slack) with enough samples on both sides.")
register_config("MXNET_ROLLOUT_MAX_ERRORS", 0.05, float,
                "Canary error fraction (errors / finished) above which "
                "the gate rolls back with reason='error_rate'.")
register_config("MXNET_ROLLOUT_AUTO", True, bool,
                "Automatic stage promotion: the gate promotes each "
                "stage after its dwell when every check passes. 0 = "
                "operator-paced (POST /rolloutz promote / "
                "tools/mxrollout.py promote); rollback stays automatic.")
register_config("MXNET_ROLLOUT_ROLLBACK", True, bool,
                "Automatic rollback on gate failure. 0 disables it — "
                "gate failures only log and event (flying blind; "
                "mxlint MXL-T220 flags it).")

# the staged ramp: (stage name, fraction of new traffic the canary
# answers). Shadow answers nothing — it only dual-dispatches samples.
STAGES: Tuple[Tuple[str, float], ...] = (
    ("shadow", 0.0), ("1", 0.01), ("10", 0.10), ("50", 0.50),
    ("100", 1.0))

_AGREE_WINDOW = 256         # rolling shadow agreement samples
_SHADOW_BUFFER = 64         # buffered shadow inputs for evaluate_agreement
_HISTORY = 64               # retained transition history entries
_MIN_P99_SAMPLES = 20       # ok latencies before a p99 delta is trusted


def _hash_frac(key: str) -> float:
    """Deterministic [0, 1) split point for one request key: the same
    trace id always lands on the same side of every stage fraction, so
    a client retry carrying its traceparent never flip-flops versions
    (and a ramp-up only MOVES the boundary — requests already on the
    canary side stay there)."""
    return (zlib.crc32(key.encode("utf-8", "replace")) & 0xFFFFFFFF) \
        / 4294967296.0


class _Route:
    """One splitter decision: which version state admits the request,
    and whether to arm a shadow dual-dispatch after admission."""

    __slots__ = ("state", "shadow", "rollout")

    def __init__(self, state=None, shadow=False, rollout=None):
        self.state = state
        self.shadow = shadow
        self.rollout = rollout


class Rollout:
    """One model's in-flight rollout: candidate version state, ramp
    position, gate evidence and transition history. All mutable fields
    are guarded by the owning :class:`RolloutManager`'s lock; effects
    that need the model's ``dispatch_mutex`` (the final hot-swap) are
    applied with no manager lock held."""

    def __init__(self, manager, model: str, version: str,
                 incumbent: str, cfg, knobs: Dict[str, Any]):
        self.manager = manager
        self.model = model
        self.version = str(version)
        self.incumbent = str(incumbent)
        self.cfg = cfg                      # candidate ModelConfig
        self.knobs = knobs
        self.state = "loading"              # loading|serving|promoted|
        #                                     rolled_back|refused|aborted
        self.stage_idx = 0
        self.stage_since = time.monotonic()
        self.started_at = time.monotonic()
        self.canary = None                  # _ModelState once loaded
        self.error: Optional[str] = None
        self.last_reason: Optional[str] = None
        self.retired = False                # canary executables dropped
        # shadow agreement evidence: rolling 0/1 window + raw input
        # buffer for the offline evaluate_agreement re-run
        self.agree: List[int] = []
        self.shadow_n = 0
        self.shadow_errors = 0
        self.shadow_inputs: List[np.ndarray] = []
        # canary counts at stage entry (promotion needs per-stage traffic)
        self.stage_base = 0
        self.history: List[Dict[str, Any]] = []

    # ------------------------------------------------------------- readout
    @property
    def stage(self) -> str:
        return STAGES[self.stage_idx][0]

    @property
    def fraction(self) -> float:
        if self.state != "serving":
            return 0.0
        return STAGES[self.stage_idx][1]

    def agreement(self) -> Optional[float]:
        if not self.agree:
            return None
        return float(sum(self.agree)) / len(self.agree)

    def evaluate_agreement(self) -> Optional[Dict[str, Any]]:
        """Re-run the quant accuracy harness (``quant.flow.
        evaluate_agreement``) over the buffered shadow inputs: incumbent
        in the fp32 slot, canary in the quantized slot — the offline-
        grade agreement readout behind the rolling gate statistic.
        Returns None when nothing is buffered or the graphs cannot be
        re-bound host-side."""
        inputs = list(self.shadow_inputs)
        st = self.manager._server._models.get(self.model)
        if not inputs or st is None or self.cfg is None:
            return None
        try:
            from ..native.predict_bridge import _load_param_bytes
            from ..quant.flow import evaluate_agreement
            from ..symbol import load_json
            isym = load_json(st.cfg.symbol_json)
            iarg, iaux = _load_param_bytes(st.cfg.param_bytes)
            csym = load_json(self.cfg.symbol_json)
            carg, caux = _load_param_bytes(self.cfg.param_bytes)
            return evaluate_agreement(isym, iarg, iaux, csym, carg, caux,
                                      [np.stack(inputs)])
        except Exception as e:
            logger.warning("rollout %r/%s: offline agreement harness "
                           "unavailable: %r", self.model, self.version, e)
            return None

    def status(self) -> Dict[str, Any]:
        out = {
            "model": self.model, "version": self.version,
            "incumbent": self.incumbent, "state": self.state,
            "stage": self.stage, "stage_index": self.stage_idx,
            "fraction": self.fraction,
            "stage_age_s": round(time.monotonic() - self.stage_since, 3),
            "age_s": round(time.monotonic() - self.started_at, 3),
            "dwell_s": self.knobs["dwell_s"],
            "auto": self.knobs["auto"],
            "rollback_enabled": self.knobs["rollback"],
            "retired": self.retired,
            "shadow": {"sample": self.knobs["shadow_sample"],
                       "n": self.shadow_n, "errors": self.shadow_errors,
                       "agreement": self.agreement(),
                       "min_agreement": self.knobs["min_agreement"]},
            "history": list(self.history),
        }
        if self.error is not None:
            out["error"] = self.error
        if self.last_reason is not None:
            out["last_reason"] = self.last_reason
        can = self.canary
        if can is not None and not self.retired:
            with can.lock:
                lat = np.asarray(can.latencies, np.float64)
                out["canary"] = {
                    "counts": dict(can.counts),
                    "breaker": can.breaker.snapshot(),
                    "tier": can.cfg.tier,
                    "queue_depth": can.queue.depth,
                }
            if lat.size:
                out["canary"]["p50_ms"] = float(np.percentile(lat, 50))
                out["canary"]["p99_ms"] = float(np.percentile(lat, 99))
            if can.slo is not None:
                out["canary"]["slo"] = can.slo.snapshot()
        return out


class RolloutManager:
    """Per-server rollout registry + splitter + gate driver.

    Attach with :meth:`attach` (idempotent — mirrors how the fleet
    controller hangs off ``server._fleet``). With no manager attached,
    or no rollout started, the serving path, ``stats()`` and the HTTP
    surface are byte-identical to a rollout-less server.

    Lock discipline (lockwatch-clean by construction): the manager lock
    guards splitter/gate state only and is NEVER held across a
    ``dispatch_mutex`` acquisition, a queue operation or an executor
    build; hot-swap effects run on the model's own worker tick or an
    operator thread with the manager lock released — exactly the
    sentinel/ladder discipline.
    """

    def __init__(self, server):
        self._server = server
        self._lock = make_lock("serving.rollout.RolloutManager._lock")
        self._rollouts: Dict[str, Rollout] = {}
        self._live: Dict[str, str] = {}     # model -> promoted version id
        self._shadows: set = set()          # live shadow-dispatch threads
        self._next_tick = 0.0
        server._rollout = self

    # ------------------------------------------------------------ attach
    @classmethod
    def attach(cls, server) -> "RolloutManager":
        ro = getattr(server, "_rollout", None)
        return ro if ro is not None else cls(server)

    # ------------------------------------------------------------- start
    def start(self, model: str, version: str,
              symbol_json: Optional[str] = None,
              param_bytes: Optional[bytes] = None,
              tier: Optional[str] = None, stage: Optional[str] = None,
              **knobs) -> Rollout:
        """Begin rolling ``version`` out for ``model``.

        The candidate config is the incumbent's with ``symbol_json`` /
        ``param_bytes`` / ``tier`` overridden (an int8-tier canary of
        the same graph needs only ``tier="int8"``). Loading and warming
        happen on a background thread; the incumbent serves untouched
        until the canary is ready. ``stage`` names the entry stage
        (default ``shadow``). Knob overrides (``dwell_s``,
        ``shadow_sample``, ``min_agreement``, ``min_shadow``,
        ``min_requests``, ``p99_slack``, ``max_error_frac``, ``auto``,
        ``rollback``) win over their ``MXNET_ROLLOUT_*`` defaults.
        """
        server = self._server
        st = server._models.get(model)
        if st is None:
            raise MXNetError("unknown model %r (serving: %s)"
                             % (model, ", ".join(sorted(server._models))))
        cfg2 = copy.copy(st.cfg)
        if symbol_json is not None:
            cfg2.symbol_json = symbol_json
        if param_bytes is not None:
            cfg2.param_bytes = param_bytes
        if tier is not None:
            if tier not in ("f32", "int8"):
                raise MXNetError("tier must be 'f32' or 'int8', got %r"
                                 % (tier,))
            cfg2.tier = tier
        resolved = dict(
            dwell_s=float(get_env("MXNET_ROLLOUT_DWELL_S", 10.0)),
            shadow_sample=float(
                get_env("MXNET_ROLLOUT_SHADOW_SAMPLE", 0.25)),
            min_agreement=float(
                get_env("MXNET_ROLLOUT_MIN_AGREEMENT", 0.98)),
            min_shadow=int(get_env("MXNET_ROLLOUT_MIN_SHADOW", 8)),
            min_requests=int(get_env("MXNET_ROLLOUT_MIN_REQUESTS", 20)),
            p99_slack=float(get_env("MXNET_ROLLOUT_P99_SLACK", 0.5)),
            max_error_frac=float(
                get_env("MXNET_ROLLOUT_MAX_ERRORS", 0.05)),
            auto=bool(get_env("MXNET_ROLLOUT_AUTO", True)),
            rollback=bool(get_env("MXNET_ROLLOUT_ROLLBACK", True)))
        unknown = set(knobs) - set(resolved)
        if unknown:
            raise MXNetError("unknown rollout knob(s): %s"
                             % ", ".join(sorted(unknown)))
        resolved.update(knobs)
        stage_names = [s for s, _ in STAGES]
        entry = "shadow" if stage is None else str(stage)
        if entry not in stage_names:
            raise MXNetError("unknown rollout stage %r (stages: %s)"
                             % (entry, ", ".join(stage_names)))
        with self._lock:
            cur = self._rollouts.get(model)
            if cur is not None and cur.state in ("loading", "serving"):
                raise MXNetError(
                    "model %r already has rollout %r in state %r: "
                    "promote, roll it back or abort it first"
                    % (model, cur.version, cur.state))
            incumbent = self._live.get(model, "v0")
            ro = Rollout(self, model, version, incumbent, cfg2, resolved)
            ro.stage_idx = stage_names.index(entry)
            self._rollouts[model] = ro
        st.rollout_version = incumbent
        self._note(ro, "start", stage=entry, tier=cfg2.tier)
        t = threading.Thread(target=self._load, args=(ro, st),
                             daemon=True,
                             name="mxserve-rollout-load-%s" % model)
        t.start()
        return ro

    def _load(self, ro: Rollout, st) -> None:
        """Background loader: build + memory-check + warm the candidate
        version, then open it for traffic. Failures are typed into the
        rollout status — the incumbent never notices."""
        from .server import _ModelState
        server = self._server
        try:
            can = _ModelState(ro.cfg)
            ro.cfg = can.cfg        # ensure_tier may have rewritten it
            if st.cache.chips > 1:
                can.cache.rebind(st.cache.chips)
            budget = _memwatch.hbm_budget_bytes()
            if budget is not None:
                used = 0
                for other in server._models.values():
                    fp = _memwatch.model_footprint(
                        other.cache, model=other.cfg.name)
                    used += _memwatch.per_chip_bytes(fp, other.cache.chips)
                fp = _memwatch.model_footprint(can.cache, model=ro.model)
                need = _memwatch.per_chip_bytes(fp, can.cache.chips)
                avail = (int(budget) - used
                         - int(_memwatch.pressure()["ballast_bytes"]))
                if need > avail:
                    server._count_mem_refusal("rollout")
                    raise MemoryBudgetExceeded(
                        "canary %r of model %r needs ~%d bytes/chip next "
                        "to the resident versions but only %d of the "
                        "%d-byte HBM budget remain — the incumbent keeps "
                        "serving; ship a smaller tier (tier='int8') or "
                        "free capacity first"
                        % (ro.version, ro.model, need, max(0, avail),
                           int(budget)))
            can.cache.warm()
            # the canary's OWN gate instruments, labeled by version so
            # its burn gauges never collide with the incumbent's
            if can.cfg.slo_p99_ms > 0:
                can.slo = _tracing.SLOTracker(
                    "%s@%s" % (ro.model, ro.version), can.cfg.slo_p99_ms,
                    can.cfg.slo_availability)
            can.ladder = _health.DegradedLadder(server, can)
            can.rollout_version = ro.version
            can.rollout_canary = True
            worker = threading.Thread(
                target=server._worker, args=(can,), daemon=True,
                name="mxserve-%s@%s" % (ro.model, ro.version))
            can.worker = worker
        except Exception as e:
            with self._lock:
                ro.state = "refused"
                ro.error = str(e)
            self._note(ro, "refused", reason=type(e).__name__)
            logger.error("rollout %r/%s refused at load: %r", ro.model,
                         ro.version, e)
            return
        with self._lock:
            if ro.state != "loading":       # aborted while loading
                return
            ro.canary = can
            ro.state = "serving"
            ro.stage_since = time.monotonic()
        worker.start()
        self._set_stage_gauge(ro)
        self._note(ro, "serving", stage=ro.stage)

    # ---------------------------------------------------------- splitter
    def route(self, model: str, trace) -> Optional[_Route]:
        """The traffic splitter, consulted by ``ModelServer.submit``:
        which version state admits this request, and whether to arm a
        shadow dual-dispatch. One dict lookup + one crc32 when a
        rollout is live; None (untouched submit path) otherwise."""
        with self._lock:
            ro = self._rollouts.get(model)
            if ro is None or ro.state != "serving" or ro.canary is None:
                return None
            frac = STAGES[ro.stage_idx][1]
            sample = ro.knobs["shadow_sample"]
        key = trace.trace_id if trace is not None \
            else _tracing.new_span_id()
        h = _hash_frac(key)
        if frac > 0.0 and h < frac:
            return _Route(state=ro.canary, rollout=ro)
        # incumbent-served: shadow-sample deterministically from the top
        # of the hash range so the sampled set is stable under ramping
        shadow = sample > 0.0 and h >= 1.0 - sample
        return _Route(state=None, shadow=shadow, rollout=ro)

    def shadow_dispatch(self, ro: Rollout, req) -> None:
        """Dual-dispatch one admitted incumbent request against the
        canary on a short-lived thread (the hedge-fire pattern): wait
        for the authoritative incumbent answer, run the canary's own
        executable on the same input, score top-1 agreement. The canary
        NEVER answers the request — a shadow failure is evidence,
        not an error the client sees."""
        t = threading.Thread(target=self._shadow_thread, args=(ro, req),
                             daemon=True, name="mxserve-shadow")
        with self._lock:
            self._shadows.add(t)
        t.start()

    def _shadow_thread(self, ro: Rollout, req) -> None:
        try:
            self._shadow_run(ro, req)
        finally:
            with self._lock:
                self._shadows.discard(threading.current_thread())

    def join_shadows(self, timeout: float) -> None:
        """Wait (bounded) for the shadow dispatches in flight. The server's
        close calls it: a daemon thread still inside the canary's XLA
        executable when the interpreter exits aborts the process (the
        forced unwind of a finalizing interpreter meets C++ frames)."""
        deadline = time.monotonic() + timeout
        with self._lock:
            threads = list(self._shadows)
        for t in threads:
            t.join(max(0.0, deadline - time.monotonic()))

    def _shadow_run(self, ro: Rollout, req) -> None:
        can = ro.canary
        if can is None:
            return
        try:
            rows = can.cache.run(req.data[None])
            canary_top = int(np.argmax(np.atleast_1d(
                np.asarray(rows[0]).ravel())))
        except Exception as e:
            with self._lock:
                ro.shadow_n += 1
                ro.shadow_errors += 1
                ro.agree.append(0)          # a canary that cannot answer
                del ro.agree[:-_AGREE_WINDOW]   # does not agree
            logger.warning("rollout %r/%s: shadow dispatch failed: %r",
                           ro.model, ro.version, e)
            self._publish_agreement(ro)
            return
        try:
            value = req.pending.result(timeout=5.0)
        except Exception:
            return      # incumbent never answered ok: nothing to compare
        inc_top = int(np.argmax(np.atleast_1d(
            np.asarray(value).ravel())))
        data = np.asarray(req.data)     # host transfer: not under the lock
        with self._lock:
            ro.shadow_n += 1
            ro.agree.append(1 if canary_top == inc_top else 0)
            del ro.agree[:-_AGREE_WINDOW]
            ro.shadow_inputs.append(data)
            del ro.shadow_inputs[:-_SHADOW_BUFFER]
        self._publish_agreement(ro)

    # ------------------------------------------------------------- gate
    def tick(self, st) -> None:
        """Cheap periodic hook on the model worker loop (rides next to
        the sentinel tick): drive gate evaluation, stage promotion and
        canary retirement for this model's rollout. Rate-limited; a
        server with no rollout pays one attribute read."""
        now = time.monotonic()
        if now < self._next_tick:
            return
        self._next_tick = now + 0.05
        with self._lock:
            ros = [ro for ro in self._rollouts.values()
                   if ro.state == "serving" or
                   (ro.state in ("promoted", "rolled_back", "aborted")
                    and not ro.retired)]
        for ro in ros:
            if ro.state == "serving":
                self._evaluate(ro)
            else:
                self._maybe_retire(ro)

    def _gate(self, ro: Rollout) -> Optional[str]:
        """Evaluate every rollback check; returns the failing reason or
        None. Pure readout — no locks beyond the states' own."""
        can = ro.canary
        st = self._server._models.get(ro.model)
        if can is None or st is None:
            return None
        if can.breaker.snapshot()["state"] == "open":
            return "breaker"
        with can.lock:
            counts = dict(can.counts)
            can_lat = np.asarray(can.latencies, np.float64)
        finished = sum(counts.values())
        if finished >= 4 and counts.get("error", 0) / finished \
                > ro.knobs["max_error_frac"]:
            return "error_rate"
        if can.slo is not None:
            burn = can.slo.fast_burn()
            if can.slo.events("fast") >= 20 \
                    and burn > can.slo.burn_threshold:
                return "slo_burn"
        with st.lock:
            inc_lat = np.asarray(st.latencies, np.float64)
        if can_lat.size >= _MIN_P99_SAMPLES \
                and inc_lat.size >= _MIN_P99_SAMPLES:
            can_p99 = float(np.percentile(can_lat, 99))
            inc_p99 = float(np.percentile(inc_lat, 99))
            if can_p99 > inc_p99 * (1.0 + ro.knobs["p99_slack"]):
                return "p99_delta"
        with self._lock:
            agreement = ro.agreement()
            n = ro.shadow_n
        if ro.knobs["shadow_sample"] > 0 and n >= ro.knobs["min_shadow"] \
                and agreement is not None \
                and agreement < ro.knobs["min_agreement"]:
            return "agreement"
        return None

    def _stage_ready(self, ro: Rollout) -> bool:
        """Has this stage accumulated enough evidence to promote?"""
        with self._lock:
            if time.monotonic() - ro.stage_since < ro.knobs["dwell_s"]:
                return False
            if ro.stage == "shadow":
                return (ro.knobs["shadow_sample"] <= 0
                        or ro.shadow_n >= ro.knobs["min_shadow"])
            base = ro.stage_base
        can = ro.canary
        with can.lock:
            finished = sum(can.counts.values())
        return finished - base >= ro.knobs["min_requests"]

    def _evaluate(self, ro: Rollout) -> None:
        reason = self._gate(ro)
        if reason is not None:
            if ro.knobs["rollback"]:
                self.rollback(ro.model, reason=reason)
            else:
                # rollback disabled: edge-trigger ONE gate_failed event
                # per distinct reason, keep serving (flying blind —
                # MXL-T220 flags this configuration)
                with self._lock:
                    if ro.last_reason == reason:
                        return
                    ro.last_reason = reason
                self._note(ro, "gate_failed", stage=ro.stage,
                           reason=reason)
            return
        with self._lock:
            ro.last_reason = None
        if ro.knobs["auto"] and self._stage_ready(ro):
            self.promote(ro.model)

    # ------------------------------------------------------ transitions
    def promote(self, model: str) -> Dict[str, Any]:
        """Advance the rollout one stage (the operator override and the
        auto-gate both land here); from the 100% stage this is the
        final hot-swap + retirement."""
        with self._lock:
            ro = self._rollouts.get(model)
            if ro is None or ro.state != "serving":
                raise MXNetError("no live rollout for model %r" % model)
            if ro.stage_idx + 1 < len(STAGES):
                ro.stage_idx += 1
                ro.stage_since = time.monotonic()
                can = ro.canary
                stage = ro.stage
                final = False
            else:
                final = True
        if not final:
            with can.lock:
                ro.stage_base = sum(can.counts.values())
            self._set_stage_gauge(ro)
            self._note(ro, "stage", stage=stage)
            return ro.status()
        return self._final_promote(ro)

    def _final_promote(self, ro: Rollout) -> Dict[str, Any]:
        """The zero-downtime hot-swap: under the model's quiesce mutex
        (in-flight batch finishes first, next dispatch waits), the
        incumbent state adopts the canary's config + executables + SLO
        tracker; the retiring executables drop with the swapped-out
        references. The canary's private queue then drains (accepted
        work finishes on the now-shared executables) and its state is
        retired."""
        server = self._server
        st = server._models[ro.model]
        can = ro.canary
        with st.dispatch_mutex:
            st.cfg, st.cache = can.cfg, can.cache
            if can.slo is not None:
                st.slo = can.slo
            st.rollout_version = ro.version
        with self._lock:
            ro.state = "promoted"
            self._live[ro.model] = ro.version
        can.queue.close()       # racing submits get typed Draining;
        #                         queued canary work still finishes
        self._set_stage_gauge(ro)
        self._note(ro, "promoted", stage=ro.stage)
        logger.warning("rollout: model %r promoted to version %r "
                       "(incumbent %r retiring)", ro.model, ro.version,
                       ro.incumbent)
        self._retire_async(ro)
        return ro.status()

    def rollback(self, model: str, reason: str = "operator"
                 ) -> Dict[str, Any]:
        """Roll the canary back: one atomic splitter swap puts the
        incumbent back at 100% of new traffic; the canary queue closes
        and drains (accepted work still finishes — zero-downtime in
        both directions), then its executables drop. Edge-triggered:
        one trace-ring event + one rollbacks counter bump."""
        with self._lock:
            ro = self._rollouts.get(model)
            if ro is None or ro.state not in ("loading", "serving"):
                raise MXNetError("no live rollout for model %r" % model)
            ro.state = "aborted" if reason == "abort" else "rolled_back"
            ro.last_reason = reason
            can = ro.canary
        if can is not None:
            can.queue.close()
        self._count_rollback(reason)
        self._set_stage_gauge(ro, value=-1)
        self._note(ro, "rollback", stage=ro.stage, reason=reason)
        logger.error("rollout: model %r version %r ROLLED BACK at stage "
                     "%r (%s); incumbent %r back at 100%%", model,
                     ro.version, ro.stage, reason, ro.incumbent)
        self._retire_async(ro)
        return ro.status()

    def abort(self, model: str) -> Dict[str, Any]:
        """Operator abort: rollback with reason='abort' (cancels a
        still-loading canary too)."""
        return self.rollback(model, reason="abort")

    def _retire_async(self, ro: Rollout) -> None:
        """Prompt retirement without riding traffic: the worker loop
        only ticks when requests flow (take_batch parks on an empty
        queue), so a terminal transition spawns a joiner that waits for
        the canary worker to drain and then retires it. The periodic
        tick stays as the backstop."""
        def _join_then_retire():
            can = ro.canary
            w = can.worker if can is not None else None
            # w.ident None = aborted before _load ever started the
            # worker: nothing to join, straight to retirement
            if w is not None and w.ident is not None:
                w.join(timeout=60.0)
            self._maybe_retire(ro)
        threading.Thread(target=_join_then_retire, daemon=True,
                         name="mxserve-rollout-retire-%s" % ro.model
                         ).start()

    def _maybe_retire(self, ro: Rollout) -> None:
        """Finish retirement once the canary worker drained: complete
        anything still queued as typed Draining, drop the executable
        references. Non-blocking — called from ticks until done."""
        can = ro.canary
        if can is None:
            with self._lock:
                ro.retired = True
            return
        worker = can.worker
        if worker is not None and worker.is_alive():
            return
        with self._lock:
            if ro.retired:
                return
            ro.retired = True
        from .errors import Draining
        for req in can.queue.drain_remaining():
            self._server._complete(
                can, req, error=Draining(
                    "version %r retired before this request was "
                    "dispatched" % ro.version),
                outcome="shed", reason="rollout_retired")
        if ro.state != "promoted":
            # promoted: the executables now ARE the incumbent's — only
            # a rolled-back/aborted canary drops its cache here
            can.cache = None
        self._note(ro, "retired", stage=ro.stage)

    # ----------------------------------------------------- drain/close
    def begin_drain(self) -> None:
        """Server drain: close every live canary queue (same atomic
        admission-vs-drain contract as the primary queues)."""
        for can in self.worker_states():
            can.queue.close()

    def worker_states(self) -> List[Any]:
        """Live canary states whose workers the server's drain/close
        must join and sweep, exactly like its primary states."""
        with self._lock:
            return [ro.canary for ro in self._rollouts.values()
                    if ro.canary is not None and not ro.retired
                    and ro.state != "promoted"]

    # ---------------------------------------------------------- readout
    def model_status(self, model: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            ro = self._rollouts.get(model)
        return None if ro is None else ro.status()

    def status(self) -> Dict[str, Any]:
        with self._lock:
            models = list(self._rollouts)
            live = dict(self._live)
        return {"rollouts": {m: self._rollouts[m].status()
                             for m in models},
                "live": live, "stages": [s for s, _ in STAGES]}

    def get(self, model: str) -> Optional[Rollout]:
        with self._lock:
            return self._rollouts.get(model)

    # --------------------------------------------------------- telemetry
    def _note(self, ro: Rollout, action: str, **tags) -> None:
        """One transition: trace-ring ``rollout`` event + bounded
        history entry (the /rolloutz and loadgen timeline source)."""
        entry = {"t": time.time(), "action": action,
                 "version": ro.version}
        entry.update({k: v for k, v in tags.items() if v is not None})
        with self._lock:
            ro.history.append(entry)
            del ro.history[:-_HISTORY]
        # 'stage' is a reserved span field: the trace-ring event carries
        # the ramp stage under ramp= instead
        ev = {("ramp" if k == "stage" else k): v
              for k, v in tags.items() if v is not None}
        self._server.tracer.record_event(
            "rollout", model=ro.model, action=action,
            version=ro.version, **ev)

    def _set_stage_gauge(self, ro: Rollout,
                         value: Optional[int] = None) -> None:
        from ..observability import metrics as _m
        if _m.enabled():
            from ..observability import catalog as _c
            _c.ROLLOUT_STAGE.set(ro.stage_idx if value is None else value,
                                 model=ro.model)

    def _publish_agreement(self, ro: Rollout) -> None:
        agreement = ro.agreement()
        if agreement is None:
            return
        from ..observability import metrics as _m
        if _m.enabled():
            from ..observability import catalog as _c
            _c.ROLLOUT_SHADOW_AGREEMENT.set(round(agreement, 4),
                                            model=ro.model)

    @staticmethod
    def _count_rollback(reason: str) -> None:
        from ..observability import metrics as _m
        if _m.enabled():
            from ..observability import catalog as _c
            _c.ROLLOUT_ROLLBACKS.inc(reason=reason)
