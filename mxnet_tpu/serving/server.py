"""Overload-safe batching model server.

The "millions of users" front end over the C-predict executor stack: a
:class:`ModelServer` owns, per model, a bounded request queue
(:mod:`.queueing`), a single dispatch worker (handle-per-worker over the
:mod:`.executors` bucket cache) and a circuit breaker (:mod:`.breaker`).
Its headline property is that it *degrades gracefully instead of
collapsing*:

- **admission control** — a full queue answers a typed
  :class:`~mxnet_tpu.serving.errors.Overloaded` in microseconds instead of
  accepting work it cannot finish;
- **deadlines end-to-end** — every request carries an absolute deadline
  (default per model); expired work is shed *before* dispatch, so a
  request past its deadline is never sent to the chip;
- **load shedding under depth** — the batch-assembly wait shrinks
  linearly as the queue fills (zero at capacity), and admission sheds
  already-expired queue entries before rejecting live work;
- **fault isolation** — executor faults retry with the shared
  :func:`~mxnet_tpu.resilience.retry.retry_transient` backoff; a batch
  that still fails is re-dispatched request-by-request so one poison
  request cannot take its batchmates down; repeated faults open a
  per-model circuit breaker that fails fast until a cooldown probe
  succeeds;
- **drain on SIGTERM** — via the resilience
  :class:`~mxnet_tpu.resilience.preemption.PreemptionGuard`: accepted
  work finishes, new work is rejected with a typed ``Draining``.

Telemetry lands in the PR-3 registry (``mxtpu_serve_*`` families,
pre-declared in ``observability/catalog.py``); ``serving/load.py`` turns
a load-generator run into a CostLedger row perfwatch can guard. Every
request additionally records a **trace**: non-overlapping stage spans
(admission → queue → assembly → dispatch → forward → respond) that sum
to its latency, tail-sampled into the ring ``tools/mxtrace.py`` reads
(``observability/tracing.py``), with declared SLOs
(``ModelConfig(slo_p99_ms=)``) guarded as rolling burn rates.
Everything here is host-side threading + numpy; the only device work is
the bucket executor's jitted forward.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..analysis.lockwatch import make_lock
from ..base import MXNetError, get_env, logger, register_config
from ..observability import memwatch as _memwatch
from ..observability import tracing as _tracing
from . import health as _health
from .breaker import CircuitBreaker
from .errors import (ChipQuarantined, CircuitOpen, DeadlineExceeded,
                     Draining, ExecutorFault, MemoryBudgetExceeded,
                     Overloaded, Preempted, QuotaExceeded, ServingError)
from .executors import (BucketExecutorCache, default_buckets,
                        resolve_device)
from .queueing import BoundedRequestQueue, RetryBudget

__all__ = ["ModelConfig", "ModelServer", "PendingResult"]

register_config("MXNET_SERVE_MAX_QUEUE", 64, int,
                "Default per-model request-queue bound (admission control). "
                "0 = unbounded — mxlint MXL-T214 flags a server built this "
                "way; an unbounded queue turns overload into unbounded "
                "latency instead of typed rejections.")
register_config("MXNET_SERVE_DEADLINE_MS", 250.0, float,
                "Default per-request latency deadline. Expired requests "
                "are answered DeadlineExceeded and never dispatched to "
                "the device. 0 = no default deadline (MXL-T214 flags it).")
register_config("MXNET_SERVE_MAX_WAIT_MS", 5.0, float,
                "Base batch-assembly window: how long the batcher waits "
                "after the first request for the batch to fill. Shrinks "
                "linearly with queue depth, zero at capacity.")
register_config("MXNET_SERVE_RETRIES", 2, int,
                "Transient-executor-fault retries per dispatch (shared "
                "retry_transient backoff underneath).")
register_config("MXNET_SERVE_BREAKER_THRESHOLD", 3, int,
                "Consecutive failed dispatches that open a model's "
                "circuit breaker.")
register_config("MXNET_SERVE_BREAKER_COOLDOWN", 5.0, float,
                "Seconds an open circuit breaker waits before letting one "
                "half-open probe batch through.")
register_config("MXNET_SERVE_TRACE", True, bool,
                "Per-request tracing on the serving path: every request "
                "records admission/queue/assembly/dispatch/forward/"
                "respond spans into the tail-sampled trace ring "
                "(MXNET_TRACE_RING/_SAMPLE; tools/mxtrace.py). Host-side "
                "only — the compiled forward's HLO is identical either "
                "way. 0 disables; mxlint MXL-T216 flags an untraced "
                "server with declared deadlines/SLOs. Per-model "
                "override: ModelConfig(trace=).")
register_config("MXNET_SERVE_HEDGE", False, bool,
                "Opt-in hedged requests: a request still unanswered after "
                "a rolling-p99-derived delay is dispatched a second time; "
                "first result wins, the loser is dropped (counted in "
                "mxtpu_serve_hedges_total). Per-model override: "
                "ModelConfig(hedge=).")
register_config("MXNET_SERVE_HEDGE_DELAY_MS", 20.0, float,
                "Hedge trigger delay floor: used until the model has "
                "enough completed requests (32) for the rolling p99 to "
                "derive the delay. Per-model: ModelConfig(hedge_delay_ms=).")
register_config("MXNET_SERVE_RETRY_BUDGET", 0.1, float,
                "Retry-budget fraction: retries + hedges together may "
                "spend at most ~this fraction of admitted traffic "
                "(token bucket; denials counted in "
                "mxtpu_retry_budget_denied_total, never silent). 0 "
                "disables the budget — mxlint MXL-T219 flags a server "
                "with retries/hedging but no budget. Per-model: "
                "ModelConfig(retry_budget=).")
register_config("MXNET_SERVE_TIER", "f32", str,
                "Default serving tier for models whose ModelConfig does "
                "not name one: 'f32' serves the graph as loaded; 'int8' "
                "quantizes symbol+params at server start "
                "(quant.ensure_tier — calibrate offline with "
                "tools/mxquant.py for calibrated ranges). Per-model "
                "override: ModelConfig(tier=...).")


def _now() -> float:
    return time.monotonic()


class PendingResult:
    """Client-side future for one submitted request. First-wins: with
    hedging on, the primary dispatch and the hedge race to complete it —
    the first :meth:`_complete` claims the result, later ones are
    dropped (return False) so a request is answered exactly once."""

    __slots__ = ("_ev", "_win", "_value", "_error", "_outcome", "done_at")

    def __init__(self):
        self._ev = threading.Event()
        self._win = threading.Lock()    # leaf lock: claim is atomic
        self._value = None
        self._error: Optional[BaseException] = None
        self._outcome: Optional[str] = None
        self.done_at: Optional[float] = None    # monotonic completion time

    def done(self) -> bool:
        return self._ev.is_set()

    def outcome(self) -> Optional[str]:
        """'ok' | 'shed' | 'expired' | 'error' once completed."""
        with self._win:
            return self._outcome

    def error(self) -> Optional[BaseException]:
        self._ev.wait()
        with self._win:
            return self._error

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        if not self._ev.wait(timeout):
            raise TimeoutError("result not ready")
        with self._win:
            error, value = self._error, self._value
        if error is not None:
            raise error
        return value

    def _claim(self, value=None, error=None, outcome="ok") -> bool:
        """Atomically claim the result WITHOUT waking waiters — the
        winning completer finishes its accounting first, so counters are
        already consistent when ``result()`` returns."""
        with self._win:
            if self._outcome is not None:
                return False            # a racing completer already won
            self._value, self._error, self._outcome = value, error, outcome
            self.done_at = time.monotonic()
        return True

    def _complete(self, value=None, error=None, outcome="ok") -> bool:
        if not self._claim(value=value, error=error, outcome=outcome):
            return False
        self._ev.set()
        return True


class _Request:
    __slots__ = ("data", "deadline", "submitted_at", "dispatch_at",
                 "pending", "trace", "enqueued_at", "dequeued_at",
                 "forward_t0", "forward_t1", "priority")

    def __init__(self, data: np.ndarray, deadline: Optional[float],
                 submitted_at: float, priority: Optional[str] = None):
        self.data = data
        self.deadline = deadline
        self.submitted_at = submitted_at
        self.dispatch_at: Optional[float] = None
        # priority class, stamped by the caller or the fleet's tenant
        # policy: "guaranteed" | "best_effort" | None (no fleet — single-
        # tenant servers never consult it)
        self.priority = priority
        self.pending = PendingResult()
        # tracing stamps (monotonic seconds): together with submitted_at/
        # dispatch_at they bound the non-overlapping stage spans —
        # admission ends at enqueued_at, queue at dequeued_at, assembly
        # at dispatch_at, dispatch at forward_t0, forward at forward_t1,
        # respond at completion
        self.trace = None
        self.enqueued_at: Optional[float] = None
        self.dequeued_at: Optional[float] = None
        self.forward_t0: Optional[float] = None
        self.forward_t1: Optional[float] = None


class ModelConfig:
    """Everything the server needs to serve one model.

    ``max_queue`` / ``deadline_ms`` / ``max_wait_ms`` / retry + breaker
    knobs default from the ``MXNET_SERVE_*`` environment; explicit
    ``max_queue=0`` or ``deadline_ms=0`` mean *unbounded* / *no default
    deadline* — both legal, both flagged by mxlint MXL-T214.
    ``dev_type`` / ``dev_id`` left unset follow ``current_context()`` (the
    chip when the host has one, see ``executors.resolve_device``);
    ``dev_type=1`` pins the host CPU.
    """

    def __init__(self, name: str, symbol_json: str, param_bytes: bytes = b"",
                 *, feature_shape: Sequence[int], input_name: str = "data",
                 buckets: Optional[Sequence[int]] = None,
                 max_queue: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 max_wait_ms: Optional[float] = None,
                 retries: Optional[int] = None,
                 breaker_threshold: Optional[int] = None,
                 breaker_cooldown_s: Optional[float] = None,
                 dev_type: Optional[int] = None,
                 dev_id: Optional[int] = None,
                 output_keys: Optional[List[str]] = None,
                 tier: Optional[str] = None,
                 trace: Optional[bool] = None,
                 trace_sample: Optional[float] = None,
                 slo_p99_ms: Optional[float] = None,
                 slo_availability: Optional[float] = None,
                 hedge: Optional[bool] = None,
                 hedge_delay_ms: Optional[float] = None,
                 retry_budget: Optional[float] = None):
        if not name:
            raise MXNetError("ModelConfig needs a model name")
        self.name = str(name)
        self.symbol_json = symbol_json
        self.param_bytes = param_bytes
        self.input_name = str(input_name)
        self.feature_shape = tuple(int(x) for x in feature_shape)
        if buckets is not None:
            self.buckets = tuple(sorted({int(b) for b in buckets}))
            self.bucket_provenance = "explicit"
        else:
            self.buckets, self.bucket_provenance = default_buckets(self.name)
        self.max_queue = int(get_env("MXNET_SERVE_MAX_QUEUE", 64)
                             if max_queue is None else max_queue)
        self.deadline_ms = float(get_env("MXNET_SERVE_DEADLINE_MS", 250.0)
                                 if deadline_ms is None else deadline_ms)
        self.max_wait_ms = float(get_env("MXNET_SERVE_MAX_WAIT_MS", 5.0)
                                 if max_wait_ms is None else max_wait_ms)
        self.retries = int(get_env("MXNET_SERVE_RETRIES", 2)
                           if retries is None else retries)
        self.breaker_threshold = int(
            get_env("MXNET_SERVE_BREAKER_THRESHOLD", 3)
            if breaker_threshold is None else breaker_threshold)
        self.breaker_cooldown_s = float(
            get_env("MXNET_SERVE_BREAKER_COOLDOWN", 5.0)
            if breaker_cooldown_s is None else breaker_cooldown_s)
        if self.max_queue < 0:
            raise MXNetError("max_queue must be >= 0 (0 = unbounded)")
        if self.deadline_ms < 0 or self.max_wait_ms < 0:
            raise MXNetError("deadline_ms/max_wait_ms must be >= 0")
        self.tier = str(get_env("MXNET_SERVE_TIER", "f32")
                        if tier is None else tier).lower()
        if self.tier not in ("f32", "int8"):
            raise MXNetError("tier must be 'f32' or 'int8', got %r"
                             % (self.tier,))
        self.trace = bool(get_env("MXNET_SERVE_TRACE", True)
                          if trace is None else trace)
        self.trace_sample = float(get_env("MXNET_TRACE_SAMPLE", 0.05)
                                  if trace_sample is None else trace_sample)
        if not (0.0 <= self.trace_sample <= 1.0):
            raise MXNetError("trace_sample must be in [0, 1], got %r"
                             % (self.trace_sample,))
        self.slo_p99_ms = float(get_env("MXNET_SERVE_SLO_P99_MS", 0.0)
                                if slo_p99_ms is None else slo_p99_ms)
        if self.slo_p99_ms < 0:
            raise MXNetError("slo_p99_ms must be >= 0 (0 = no SLO)")
        self.slo_availability = float(
            get_env("MXNET_SERVE_SLO_AVAILABILITY", 0.999)
            if slo_availability is None else slo_availability)
        self.hedge = bool(get_env("MXNET_SERVE_HEDGE", False)
                          if hedge is None else hedge)
        self.hedge_delay_ms = float(
            get_env("MXNET_SERVE_HEDGE_DELAY_MS", 20.0)
            if hedge_delay_ms is None else hedge_delay_ms)
        if self.hedge_delay_ms < 0:
            raise MXNetError("hedge_delay_ms must be >= 0")
        self.retry_budget = float(get_env("MXNET_SERVE_RETRY_BUDGET", 0.1)
                                  if retry_budget is None else retry_budget)
        if not (0.0 <= self.retry_budget <= 1.0):
            raise MXNetError("retry_budget must be in [0, 1] (0 = no "
                             "budget; MXL-T219 flags it), got %r"
                             % (self.retry_budget,))
        self.dev_type, self.dev_id = resolve_device(dev_type, dev_id)
        self.output_keys = output_keys


class _ModelState:
    """Per-model runtime: queue, worker, bucket cache, breaker, stats."""

    def __init__(self, cfg: ModelConfig):
        if cfg.tier == "int8":
            # resolve the int8 tier ONCE at state build: a still-float
            # graph is rewritten through the quant pass pipeline here, so
            # MXNET_SERVE_TIER=int8 serves the cheaper executable without
            # the caller touching the model files (quant.ensure_tier is a
            # no-op on an already-quantized symbol)
            from ..quant import ensure_tier
            cfg = ensure_tier(cfg)
        self.cfg = cfg
        self.queue = BoundedRequestQueue(cfg.max_queue)
        self.cache = BucketExecutorCache(
            cfg.symbol_json, cfg.param_bytes, input_name=cfg.input_name,
            feature_shape=cfg.feature_shape, buckets=cfg.buckets,
            dev_type=cfg.dev_type, dev_id=cfg.dev_id,
            output_keys=cfg.output_keys, model=cfg.name)
        self.breaker = CircuitBreaker(cfg.breaker_threshold,
                                      cfg.breaker_cooldown_s)
        # declared SLO -> rolling burn-rate guard (tracing.SLOTracker);
        # no objective declared = no tracker, no gauges
        self.slo = (_tracing.SLOTracker(cfg.name, cfg.slo_p99_ms,
                                        cfg.slo_availability)
                    if cfg.slo_p99_ms > 0 else None)
        self.worker: Optional[threading.Thread] = None
        self.lock = make_lock("serving.server._ModelState.lock")
        # held for the duration of one dispatch: a fleet resize acquires
        # it to quiesce (the in-flight batch finishes, the next dispatch
        # waits) before re-binding the bucket cache for a new chip count.
        # Uncontended in single-tenant mode — nothing else takes it.
        self.dispatch_mutex = make_lock("serving.server._ModelState.dispatch_mutex")
        self.counts = {"ok": 0, "shed": 0, "expired": 0, "error": 0}
        self.batches = 0
        self.singles = 0            # isolation re-dispatches after a fault
        self.retries = 0
        self.deadline_violations = 0
        self.latencies: List[float] = []   # ok-request ms, bounded ring
        # tail-tolerance state: the retries+hedges token budget (None =
        # unbounded, flagged by MXL-T219), hedge outcome counts, and the
        # degraded-mode ladder (attached by ModelServer — it needs the
        # server's tracer for edge-triggered transition events)
        self.budget = (RetryBudget(cfg.retry_budget)
                       if cfg.retry_budget > 0 else None)
        self.hedges = {"fired": 0, "won": 0, "lost": 0, "budget_denied": 0}
        self.ladder = None


_LAT_RING = 8192


class ModelServer:
    """The batching front end. Construct with configs, :meth:`start`,
    :meth:`submit`/:meth:`predict`, then :meth:`close` (or let SIGTERM
    drain it).

    >>> server = ModelServer([ModelConfig("m", sym_json, params,
    ...                                   feature_shape=(4,))])
    >>> server.start(warm=True)
    >>> out = server.predict("m", np.zeros(4, "float32"))
    """

    def __init__(self, models: Sequence[ModelConfig], *,
                 drain_on_preemption: bool = True,
                 tracer: Optional[_tracing.Tracer] = None):
        if not models:
            raise MXNetError("ModelServer needs at least one ModelConfig")
        # the request-trace ring (shared across this server's models);
        # defaults to the process-wide ring so tools/mxtrace.py dumps and
        # exemplar lookups see every server in the process
        self.tracer = tracer if tracer is not None else _tracing.get_tracer()
        self._models: Dict[str, _ModelState] = {}
        # memory-aware admission at LOAD time: with a per-chip HBM budget
        # configured (memwatch: MXNET_HBM_BYTES or a known device), a
        # model whose estimated footprint does not fit what the already-
        # accepted models leave is refused typed here — never OOMed onto
        # the chip mid-traffic. No budget (the CPU default) = no check.
        budget = _memwatch.hbm_budget_bytes()
        used = 0
        for cfg in models:
            if cfg.name in self._models:
                raise MXNetError("duplicate model name %r" % cfg.name)
            st = _ModelState(cfg)
            if budget is not None:
                fp = _memwatch.model_footprint(st.cache, model=cfg.name)
                need = _memwatch.per_chip_bytes(fp, st.cache.chips)
                avail = (int(budget)
                         - int(_memwatch.pressure()["ballast_bytes"]) - used)
                if need > avail:
                    self._count_mem_refusal("load")
                    raise MemoryBudgetExceeded(
                        "model %r needs ~%d bytes/chip but only %d of the "
                        "%d-byte HBM budget remain (loaded models hold %d); "
                        "shrink the bucket ladder, raise MXNET_HBM_BYTES, "
                        "or serve it elsewhere"
                        % (cfg.name, need, max(0, avail), int(budget), used))
                used += need
            self._models[cfg.name] = st
        # chip-loss self-healing: the sentinel owns the quarantine set;
        # each model gets a degraded-mode ladder (host-side only — the
        # served StableHLO is bitwise identical, pinned by test_health)
        self._sentinel = _health.DeviceSentinel(self)
        for st in self._models.values():
            st.ladder = _health.DegradedLadder(self, st)
        self._hedger: Optional[_health.HedgeMonitor] = None
        self._drain_on_preemption = bool(drain_on_preemption)
        # multi-tenant fleet controller (serving/fleet.py), attached via
        # FleetController(server=...); None (the default) = fleet mode
        # off — admission, dispatch and the served HLO are bitwise
        # identical to a pre-fleet server (pinned by test_fleet.py)
        self._fleet = None
        # versioned-rollout manager (serving/rollout.py), attached via
        # RolloutManager.attach(server); None (the default) = rollout
        # mode off — submit, stats() and the served HLO are byte-
        # identical to a rollout-less server (pinned by test_rollout.py)
        self._rollout = None
        self._guard = None
        self._started = False
        self._stopped = False
        self._draining = threading.Event()
        self._drained = threading.Event()

    # ------------------------------------------------------------ lifecycle
    def start(self, warm: bool = False) -> "ModelServer":
        if self._started:
            return self
        if self._stopped:
            raise MXNetError("server was closed; build a new one")
        if self._drain_on_preemption:
            from ..resilience import preemption
            self._guard = preemption.acquire()
        for name, st in self._models.items():
            if warm:
                st.cache.warm()
            t = threading.Thread(target=self._worker, args=(st,),
                                 daemon=True, name="mxserve-%s" % name)
            st.worker = t
            t.start()
        if any(st.cfg.hedge for st in self._models.values()):
            self._hedger = _health.HedgeMonitor(self).start()
        self._sentinel.start()      # canary thread only if PROBE_S is set
        self._started = True
        return self

    def begin_drain(self) -> None:
        """Enter draining: accepted work finishes, new work is rejected
        with :class:`Draining`. Idempotent; the SIGTERM path lands here."""
        if not self._draining.is_set():
            self._draining.set()
            logger.info("model server draining: queues reject new work, "
                        "in-flight batches finish")
            # closing the queues makes admission-vs-drain atomic: a submit
            # that already passed the draining check but has not enqueued
            # yet is rejected AT the queue, so no request can land after
            # the worker decided it may exit (it would hang forever)
            for st in self._models.values():
                st.queue.close()
            if self._rollout is not None:
                self._rollout.begin_drain()

    def drain(self, timeout: Optional[float] = None) -> bool:
        """begin_drain + wait for every queue to empty and every worker to
        exit. Returns True when fully drained within ``timeout``."""
        self.begin_drain()
        deadline = None if timeout is None else _now() + timeout
        states = list(self._models.values())
        if self._rollout is not None:
            # live canary versions drain exactly like primary models:
            # accepted work finishes, their workers exit on empty+closed
            states += self._rollout.worker_states()
        for st in states:
            if st.worker is not None:
                left = None if deadline is None else max(0.0, deadline - _now())
                st.worker.join(timeout=left)
                if st.worker.is_alive():
                    return False
        self._drained.set()
        return True

    def close(self, timeout: float = 30.0) -> bool:
        """Drain (bounded), fail anything still queued with ``Draining``,
        release the preemption guard. Returns the drain() verdict."""
        if self._stopped:
            return True
        ok = self.drain(timeout=timeout)
        if self._hedger is not None:
            self._hedger.stop()
        self._sentinel.stop()
        states = list(self._models.values())
        if self._rollout is not None:
            states += self._rollout.worker_states()
        for st in states:
            for req in st.queue.drain_remaining():
                self._complete(st, req, error=Draining(
                    "server closed before this request was dispatched"),
                    outcome="shed", reason="draining")
        if self._rollout is not None:
            # after the sweep above: a shadow waits on its request's answer
            self._rollout.join_shadows(timeout)
        self._stopped = True
        if self._guard is not None:
            from ..resilience import preemption
            preemption.release()
            self._guard = None
        return ok

    def __enter__(self) -> "ModelServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------------ admission
    def _check_draining(self) -> None:
        if self._guard is not None and self._guard.triggered:
            self.begin_drain()
        if self._draining.is_set() or self._stopped:
            raise Draining("server is draining: retry against another "
                           "replica")

    def submit(self, model: str, data, deadline_ms: Optional[float] = None,
               deadline_at: Optional[float] = None,
               trace: Optional[_tracing.TraceContext] = None,
               priority: Optional[str] = None) -> PendingResult:
        """Admit one request (one sample of the model's feature shape).

        ``deadline_ms`` overrides the model's default; ``deadline_at`` is
        an absolute :func:`time.monotonic` deadline (wins over both —
        propagated end-to-end, e.g. from an upstream hop). ``trace`` is
        an upstream :class:`~mxnet_tpu.observability.tracing.TraceContext`
        (e.g. parsed from an HTTP ``traceparent``) the request's span
        timeline continues; None mints a fresh one. ``priority`` is the
        request's fleet priority class ("guaranteed" | "best_effort");
        None defaults to the tenant's policy when a fleet is attached and
        is ignored otherwise. Raises typed :class:`Overloaded` /
        :class:`Draining` (and, fleet mode only, :class:`QuotaExceeded` /
        :class:`Preempted`); executor errors surface on the returned
        :class:`PendingResult`.
        """
        st = self._models.get(model)
        if st is None:
            raise MXNetError("unknown model %r (serving: %s)"
                             % (model, ", ".join(sorted(self._models))))
        if not self._started:
            raise MXNetError("server not started")
        # the rollout traffic splitter: with a live rollout the request
        # hash may route admission to the canary version's own state
        # (queue/breaker/SLO) — deterministic on the trace id, so a
        # client retry never flip-flops versions and the retry/hedge
        # paths below act on whichever version admitted it. No rollout
        # attached = one None check, the path is untouched.
        route = self._rollout.route(model, trace) \
            if self._rollout is not None else None
        if route is not None and route.state is not None:
            st = route.state
        try:
            self._check_draining()
        except Draining:
            self._count(st, "shed")
            raise
        arr = np.asarray(data, dtype=np.float32)
        if tuple(arr.shape) != st.cfg.feature_shape:
            raise MXNetError(
                "request shape %r does not match model %r feature shape %r"
                % (tuple(arr.shape), model, st.cfg.feature_shape))
        now = _now()
        if deadline_at is None:
            dl_ms = (st.cfg.deadline_ms if deadline_ms is None
                     else float(deadline_ms))
            deadline_at = now + dl_ms / 1e3 if dl_ms else None
        req = _Request(arr, deadline_at, now, priority=priority)
        if st.cfg.trace and self.tracer.enabled():
            req.trace = self.tracer.start_request(
                model, ctx=trace, submitted_at=now,
                deadline_ms=((deadline_at - now) * 1e3
                             if deadline_at is not None else None),
                sample=st.cfg.trace_sample)
        try:
            # fleet admission (quota + priority stamping) runs BEFORE the
            # queue so a quota shed never occupies a slot; with no fleet
            # attached this is a single None check — the single-tenant
            # path is otherwise untouched
            if self._fleet is not None:
                self._fleet.admit(st, req)
            # degraded-mode gate AFTER the fleet stamped the priority
            # class: rung 3 admits guaranteed traffic only, rung 4 sheds
            # statically — typed Overloaded, counted reason="degraded"
            st.ladder.admit_check(req)
            shed = st.queue.put(req)
        except (Overloaded, Draining, Preempted) as e:
            if req.trace is not None:
                # admission rejections keep their trace: shed traces are
                # ALWAYS retained by the tail-sampler, so an overloaded
                # client's trace_id resolves in the ring
                req.trace.span("admission", now, _now())
                if isinstance(e, QuotaExceeded):
                    reason = "quota"
                elif getattr(e, "degraded", False):
                    reason = "degraded"
                elif isinstance(e, Overloaded):
                    reason = "overloaded"
                elif isinstance(e, Preempted):
                    reason = "preempted"
                else:
                    reason = "draining"
                self.tracer.finish(
                    req.trace, "shed", latency_ms=(_now() - now) * 1e3,
                    reason=reason)
            self._count(st, "shed")
            raise
        req.enqueued_at = _now()
        if req.trace is not None:
            req.trace.span("admission", now, req.enqueued_at)
        # every admitted request funds the shared retry budget (~10% of
        # traffic by default) that retries AND hedges spend from
        if st.budget is not None:
            st.budget.deposit()
        if self._hedger is not None and st.cfg.hedge:
            self._hedger.register(st, req)
        if route is not None and route.shadow:
            # shadow dual-dispatch: the canary sees the same input on
            # its own executable, the incumbent's answer stays the only
            # one the client gets (agreement evidence, never traffic)
            self._rollout.shadow_dispatch(route.rollout, req)
        for dead in shed:
            self._complete(st, dead, error=DeadlineExceeded(
                "deadline passed while queued (shed at admission)"),
                outcome="expired", reason="shed_at_admission")
        self._gauge_depth(st)
        return req.pending

    def predict(self, model: str, data,
                deadline_ms: Optional[float] = None,
                timeout: Optional[float] = None,
                trace: Optional[_tracing.TraceContext] = None,
                priority: Optional[str] = None) -> np.ndarray:
        """submit + wait: the synchronous convenience."""
        return self.submit(model, data, deadline_ms=deadline_ms,
                           trace=trace, priority=priority
                           ).result(timeout=timeout)

    # ------------------------------------------------------------- workers
    def _worker(self, st: _ModelState) -> None:
        cfg = st.cfg

        def stop_requested() -> bool:
            # flag-only on purpose: take_batch calls this while holding
            # the queue's non-reentrant lock, and begin_drain ->
            # queue.close() re-acquires that same lock — calling it here
            # would wedge the worker (and then drain/close) forever. The
            # latch happens below, outside the lock.
            return ((self._guard is not None and self._guard.triggered)
                    or self._draining.is_set() or self._stopped)

        while True:
            if stop_requested():
                # latch the drain outside the queue lock (idempotent).
                # take_batch keeps sweeping until closed-and-empty, so a
                # submit that raced the close still gets served (drain
                # semantics: accepted work finishes).
                self.begin_drain()
            # sentinel tick: apply pending degraded-ladder effects (the
            # worker owns its model's executable swaps), then — rate-
            # limited — half-open re-admission and de-escalation checks.
            # Runs OUTSIDE dispatch_mutex: effects take it themselves.
            self._sentinel.tick(st)
            # rollout tick (same discipline): gate evaluation, stage
            # promotion and canary retirement ride the worker loop —
            # the hot-swap takes dispatch_mutex itself
            if self._rollout is not None:
                self._rollout.tick(st)
            wait_s = st.queue.effective_wait(cfg.max_wait_ms / 1e3)
            batch, expired = st.queue.take_batch(
                st.cache.max_bucket, wait_s, stop_requested)
            for req in expired:
                self._complete(st, req, error=DeadlineExceeded(
                    "deadline passed while queued (shed before dispatch)"),
                    outcome="expired")
            self._gauge_depth(st)
            if batch is None:
                return              # queue closed and empty: nothing can land
            if not batch:
                continue            # all expired, or drain requested: loop
            try:
                fleet = self._fleet
                if fleet is not None:
                    # weighted-fair pacing: a tenant far ahead of its fair
                    # share yields a bounded beat to the others before its
                    # batch takes the chip
                    fleet.before_dispatch(st, len(batch))
                # dispatch_mutex is the fleet's quiesce point: a resize
                # acquires it, so the in-flight batch finishes on the old
                # binding and the next waits for the new one. Uncontended
                # (single-tenant / no resize) it is one futex op.
                with st.dispatch_mutex:
                    # device work under the quiesce mutex IS the contract:
                    # holding it for exactly one dispatch (sync + retry
                    # backoff included) is what makes resize safe
                    self._dispatch(st, batch)  # mxlint: disable=MXL-C301
            except Exception as e:  # defensive: a worker must never die
                logger.exception("serving worker for %r: unexpected "
                                 "dispatch error: %r", cfg.name, e)
                # the breaker must still get a verdict: a dispatch that
                # died before record_success/record_failure would leave a
                # half-open probe unresolved (wedged in CircuitOpen until
                # the breaker's lost-verdict cooldown)
                st.breaker.record_failure()
                for req in batch:
                    if not req.pending.done():
                        self._complete(st, req, error=ExecutorFault(
                            "internal dispatch error: %r" % (e,)),
                            outcome="error", reason="internal")

    def _dispatch(self, st: _ModelState, batch: List[_Request]) -> None:
        # ONE decision timestamp: the expiry filter and the dispatch_at
        # stamp use the same instant, so "dispatched past its deadline"
        # (the deadline_violations invariant) is structurally impossible
        # to introduce via a gap between the two reads
        dispatch_at = _now()
        ready: List[_Request] = []
        for req in batch:
            if req.pending.done():
                continue    # a hedge already answered it while it queued
            # the last line of the no-expired-work-on-the-chip invariant:
            # anything past deadline at dispatch time is answered, not run
            if req.deadline is not None and req.deadline <= dispatch_at:
                self._complete(st, req, error=DeadlineExceeded(
                    "deadline passed at dispatch"), outcome="expired")
            else:
                ready.append(req)
        if not ready:
            return
        if not st.breaker.allow():
            for req in ready:
                self._complete(st, req, error=CircuitOpen(
                    "circuit breaker open for model %r after repeated "
                    "executor faults" % st.cfg.name), outcome="shed",
                    reason="breaker")
            return
        for req in ready:
            req.dispatch_at = dispatch_at
        arr = np.stack([r.data for r in ready])
        # one shared batch-span id: every batchmate's forward span carries
        # it, so a slow request's timeline names the batch it was fused
        # into (and mxtrace can find its batchmates by the shared id)
        batch_span = _tracing.new_span_id() \
            if any(r.trace is not None for r in ready) else None
        with st.lock:
            retries_before = st.retries
        t_f0 = _now()
        for req in ready:
            req.forward_t0 = t_f0
        try:
            rows = self._run_with_retry(st, arr)
        except Exception as e:
            if _health.is_device_fatal(e):
                # the chip, not the request, is suspect: quarantine it,
                # re-plan the ladder on the survivors and re-dispatch the
                # live batchmates there — never isolate, never retry
                self._on_device_fatal(st, ready, e, t_f0, batch_span,
                                      retries_before)
            elif len(ready) > 1:
                # isolation: one poison request must not fail its
                # batchmates — re-dispatch one by one
                self._dispatch_singly(st, ready, cause=e)
            else:
                st.breaker.record_failure()
                self._trace_forward(st, ready[0], t_f0, _now(),
                                    batch_span, len(ready),
                                    retries_before, outcome_tag="error")
                self._complete(st, ready[0], error=self._fault(e),
                               outcome="error")
            return
        t_f1 = _now()
        st.breaker.record_success()
        with st.lock:
            st.batches += 1
        self._observe_batch(st, len(ready))
        for req in ready:
            self._trace_forward(st, req, t_f0, t_f1, batch_span,
                                len(ready), retries_before)
        for i, req in enumerate(ready):
            self._complete(st, req, value=rows[i], outcome="ok")

    def _on_device_fatal(self, st: _ModelState, ready: List[_Request],
                         exc: BaseException, t_f0: float,
                         batch_span: Optional[str],
                         retries_before: int) -> None:
        """Chip-loss recovery for one failed dispatch. Runs under
        ``dispatch_mutex`` (held by the worker), which doubles as the
        quiesce for the inline rebind: (1) quarantine the blamed chip,
        (2) re-plan the bucket ladder over the survivors
        (``plan_chip_split`` + memory check + ``rebind``), (3) re-
        dispatch the batch's live batchmates on the new binding — in-
        flight work is never silently lost. Budget-exempt: the re-
        dispatch is recovery of ADMITTED work, not extra traffic. Only
        when no feasible re-placement exists (or the re-dispatch fails
        again) do the batchmates fail with typed ``ChipQuarantined`` and
        the degraded ladder escalates."""
        chip = _health.chip_of(exc)
        if chip is None:
            chip = st.cfg.dev_id
        reason = _health.device_fatal_reason(exc)
        self._sentinel.quarantine(chip, reason=reason, model=st.cfg.name)
        plan = _health.replan_after_loss(self, st, chip, exc)
        now = _now()
        still: List[_Request] = []
        for req in ready:
            if req.pending.done():
                continue                        # a hedge answered it
            if req.deadline is not None and req.deadline <= now:
                self._complete(st, req, error=DeadlineExceeded(
                    "deadline passed during chip-loss recovery"),
                    outcome="expired", reason="chip_loss")
            else:
                still.append(req)
        if not still:
            st.breaker.record_failure()
            return
        try:
            arr = np.stack([r.data for r in still])
            rows = self._run_with_retry(st, arr)
        except Exception as e2:
            st.breaker.record_failure()
            st.ladder.escalate("chip_loss:redispatch_failed")
            err = ChipQuarantined(
                "chip %d quarantined (%s) and the re-dispatch on the "
                "survivors failed: retry against another replica"
                % (chip, reason))
            err.__cause__ = e2
            for req in still:
                self._trace_forward(st, req, t_f0, _now(), batch_span,
                                    len(still), retries_before,
                                    outcome_tag="error")
                self._complete(st, req, error=err, outcome="error",
                               reason="chip_loss")
            return
        t_f1 = _now()
        st.breaker.record_success()
        if plan is None and st.cache.chips <= 1:
            # the fault self-cleared but there were no survivors to re-
            # place onto: serve cautiously until probes stay healthy
            st.ladder.escalate("chip_loss:no_survivors")
        with st.lock:
            st.batches += 1
        self._observe_batch(st, len(still))
        for req in still:
            self._trace_forward(st, req, t_f0, t_f1, batch_span,
                                len(still), retries_before)
        for i, req in enumerate(still):
            self._complete(st, req, value=rows[i], outcome="ok")

    def _trace_forward(self, st: _ModelState, req: _Request, t0: float,
                       t1: float, batch_span: Optional[str], batch: int,
                       retries_before: int, outcome_tag: Optional[str] = None,
                       isolated: bool = False) -> None:
        """Record one request's forward span (the device-time stage),
        tagged with the shared batch-span id, batch size, the padded
        bucket and any retries the dispatch burned."""
        rt = req.trace
        if rt is None:
            return
        req.forward_t1 = t1
        with st.lock:
            retries = st.retries - retries_before
        tags: Dict[str, Any] = {"batch": int(batch)}
        if batch_span is not None:
            tags["batch_span"] = batch_span
            rt.batch_span_id = batch_span
            rt.batch_size = int(batch)
        try:
            tags["bucket"] = st.cache.bucket_for(batch)
        except Exception:
            pass
        if retries > 0:
            tags["retries"] = int(retries)
        if isolated:
            tags["isolated"] = True
        if outcome_tag:
            tags["outcome"] = outcome_tag
        rt.span("forward", t0, t1, **tags)

    def _dispatch_singly(self, st: _ModelState, ready: List[_Request],
                         cause: BaseException) -> None:
        logger.warning("batch of %d failed for model %r (%r): isolating "
                       "per-request", len(ready), st.cfg.name, cause)
        any_ok = False
        for req in ready:
            t = _now()                 # one filter-and-stamp instant
            if req.deadline is not None and req.deadline <= t:
                self._complete(st, req, error=DeadlineExceeded(
                    "deadline passed during fault isolation"),
                    outcome="expired", reason="isolation")
                continue
            with st.lock:
                st.singles += 1
                retries_before = st.retries
            req.dispatch_at = t
            req.forward_t0 = t
            try:
                rows = self._run_with_retry(st, req.data[None])
            except Exception as e:
                self._trace_forward(st, req, t, _now(), None, 1,
                                    retries_before, outcome_tag="error",
                                    isolated=True)
                self._complete(st, req, error=self._fault(e),
                               outcome="error", reason="isolation")
            else:
                any_ok = True
                self._observe_batch(st, 1)
                self._trace_forward(st, req, t, _now(), None, 1,
                                    retries_before, isolated=True)
                self._complete(st, req, value=rows[0], outcome="ok")
        if any_ok:
            # at least one isolated re-dispatch succeeded: the executor
            # is healthy and the fault travels with the poison request(s)
            # as typed ExecutorFault — a persistent poison CLIENT must
            # not open the breaker and darken the whole model
            st.breaker.record_success()
        else:
            # every re-dispatch failed — or none happened at all (every
            # batchmate expired before its turn), leaving the batch
            # fault that sent us here as the only executor evidence
            st.breaker.record_failure()

    def _run_with_retry(self, st: _ModelState, arr: np.ndarray) -> np.ndarray:
        from ..resilience.retry import retry_transient

        def on_retry(i, exc, delay):
            with st.lock:
                st.retries += 1
            logger.warning("model %r: transient executor fault "
                           "(attempt %d), retrying in %.3fs: %r",
                           st.cfg.name, i + 1, delay, exc)

        def gate(exc):
            # the shared retry budget: a transient retry spends a token
            # funded by admitted traffic; an empty bucket fails the
            # request NOW (typed, counted) instead of amplifying overload
            if st.budget is None:
                return True
            if st.budget.try_spend("retry"):
                return True
            self._count_budget_denied(st, "retry")
            return False

        try:
            return retry_transient(lambda: st.cache.run(arr),
                                   attempts=st.cfg.retries + 1,
                                   base_delay=0.01, max_delay=0.5,
                                   on_retry=on_retry, gate=gate)
        except Exception as e:
            # the serving dispatch boundary: a device RESOURCE_EXHAUSTED
            # leaves forensics (mxtpu_oom.json, blame table) and becomes
            # typed HBMExhausted; everything else passes through
            oom = _memwatch.to_hbm_exhausted(e, context="serving",
                                             server=self,
                                             model=st.cfg.name)
            if oom is not None:
                raise oom from e
            raise

    @staticmethod
    def _fault(e: BaseException) -> MXNetError:
        # HBMExhausted stays typed through the future: the client must be
        # able to tell "the chip is out of memory" from a poison request
        if isinstance(e, (ServingError, _memwatch.HBMExhausted)):
            return e
        return ExecutorFault("executor failed: %r" % (e,))

    # ---------------------------------------------------------- accounting
    def _complete(self, st: _ModelState, req: _Request, value=None,
                  error=None, outcome="ok", reason=None) -> bool:
        # claim FIRST (PendingResult is first-wins): when a hedge and the
        # primary race, exactly one completer does the accounting below —
        # the loser's result is dropped whole (no double count, no
        # double-finished trace). The event is set only AFTER accounting,
        # so a client that saw result() can trust the counters. Returns
        # whether THIS call won.
        if not req.pending._claim(value=value, error=error,
                                  outcome=outcome):
            return False
        try:
            return self._account(st, req, outcome, reason)
        finally:
            req.pending._ev.set()

    def _account(self, st: _ModelState, req: _Request, outcome,
                 reason) -> bool:
        done_at = _now()
        violated = (outcome == "ok" and req.deadline is not None
                    and req.dispatch_at is not None
                    and req.dispatch_at > req.deadline)
        if violated:
            # must stay zero: the invariant counter the acceptance test
            # reads — a dispatch after deadline is a server bug
            with st.lock:
                st.deadline_violations += 1
        latency_ms = (done_at - req.submitted_at) * 1e3
        kept = self._finish_trace(st, req, done_at, outcome, violated,
                                  reason)
        if outcome == "ok":
            with st.lock:
                st.latencies.append(latency_ms)
                if len(st.latencies) > _LAT_RING:
                    del st.latencies[:len(st.latencies) - _LAT_RING]
            self._observe_latency(st, latency_ms,
                                  trace_id=(req.trace.trace_id
                                            if kept and req.trace is not None
                                            else None))
        self._count(st, outcome,
                    latency_ms if outcome == "ok" else None)
        return True

    def _finish_trace(self, st: _ModelState, req: _Request, done_at: float,
                      outcome: str, violated: bool, reason) -> bool:
        """Seal the request's span timeline: fill the non-overlapping
        stage spans from the request's stamps (spans sum to the request
        latency by construction) and hand it to the tail-sampler.
        Returns True when the trace was retained (the exemplar gate)."""
        rt = req.trace
        if rt is None:
            return False
        enq = req.enqueued_at
        if enq is not None:
            dq = req.dequeued_at
            rt.span("queue", enq, dq if dq is not None else done_at)
            if dq is not None:
                rt.span("assembly", dq,
                        req.dispatch_at if req.dispatch_at is not None
                        else done_at)
            if req.dispatch_at is not None:
                rt.span("dispatch", req.dispatch_at,
                        req.forward_t0 if req.forward_t0 is not None
                        else done_at)
            # the forward span (with batch/bucket/retry tags) was
            # recorded by _trace_forward at dispatch time
            if req.forward_t1 is not None:
                rt.span("respond", req.forward_t1, done_at)
            elif req.forward_t0 is not None:
                # a forward was attempted but never sealed: the batch
                # failed and this request exited (expired during fault
                # isolation, or an internal dispatch error) before any
                # re-dispatch — account the attempt so the spans still
                # sum to the request latency
                rt.span("forward", req.forward_t0, done_at, aborted=True)
        return self.tracer.finish(
            rt, outcome, latency_ms=(done_at - req.submitted_at) * 1e3,
            violated=violated, reason=reason)

    def _count(self, st: _ModelState, outcome: str,
               latency_ms: Optional[float] = None) -> None:
        with st.lock:
            st.counts[outcome] = st.counts.get(outcome, 0) + 1
        if st.slo is not None:
            # every final outcome is one SLO event (sheds and expiries
            # burn the availability budget exactly like slow successes)
            st.slo.record(outcome, latency_ms)
        from ..observability import metrics as _m
        if _m.enabled():
            from ..observability import catalog as _c
            _c.SERVE_REQUESTS.inc(model=st.cfg.name, outcome=outcome)
            if st.cfg.tier == "int8":
                _c.QUANT_SERVE_REQUESTS.inc(model=st.cfg.name,
                                            outcome=outcome)
            ver = getattr(st, "rollout_version", None)
            if ver is not None:
                # per-version outcome attribution while a rollout is
                # (or was) configured: the zero-downtime proof reads
                # these deltas — a retired version's counters stop
                _c.ROLLOUT_VERSION_REQUESTS.inc(
                    model=st.cfg.name, version=ver, outcome=outcome)

    def _observe_latency(self, st: _ModelState, ms: float,
                         trace_id: Optional[str] = None) -> None:
        from ..observability import metrics as _m
        if _m.enabled():
            from ..observability import catalog as _c
            _c.SERVE_LATENCY.observe(ms, exemplar=trace_id,
                                     model=st.cfg.name)

    def _observe_batch(self, st: _ModelState, size: int) -> None:
        from ..observability import metrics as _m
        if _m.enabled():
            from ..observability import catalog as _c
            _c.SERVE_BATCH.observe(size, model=st.cfg.name)

    def _gauge_depth(self, st: _ModelState) -> None:
        if getattr(st, "rollout_canary", False):
            # the model's depth gauge stays the incumbent queue's: two
            # states flapping one {model} gauge would render as noise
            return
        from ..observability import metrics as _m
        if _m.enabled():
            from ..observability import catalog as _c
            _c.SERVE_QUEUE_DEPTH.set(st.queue.depth, model=st.cfg.name)

    @staticmethod
    def _count_mem_refusal(reason: str) -> None:
        from ..observability import metrics as _m
        if _m.enabled():
            from ..observability import catalog as _c
            _c.MEM_REFUSALS.inc(reason=reason)

    @staticmethod
    def _count_budget_denied(st: _ModelState, kind: str) -> None:
        from ..observability import metrics as _m
        if _m.enabled():
            from ..observability import catalog as _c
            _c.RETRY_BUDGET_DENIED.inc(model=st.cfg.name, kind=kind)

    # ------------------------------------------------------------- surface
    def models(self) -> List[str]:
        return sorted(self._models)

    def config(self, model: str) -> ModelConfig:
        return self._models[model].cfg

    def stats(self, model: str) -> Dict[str, Any]:
        st = self._models[model]
        with st.lock:
            lat = np.asarray(st.latencies, np.float64)
            out = {
                "model": model,
                "counts": dict(st.counts),
                "batches": st.batches,
                "singles": st.singles,
                "retries": st.retries,
                "deadline_violations": st.deadline_violations,
                "queue_depth": st.queue.depth,
                "breaker": st.breaker.snapshot(),
                "buckets": list(st.cache.buckets),
                "buckets_compiled": st.cache.compiled_buckets(),
                "bucket_provenance": st.cfg.bucket_provenance,
                "tier": st.cfg.tier,
                "tracing": {"enabled": st.cfg.trace,
                            "sample": st.cfg.trace_sample,
                            "ring_depth": self.tracer.depth},
                "chips": st.cache.chips,
                "device": str(st.cache.device),
                "hedges": dict(st.hedges),
            }
        out["degraded_rung"] = st.ladder.rung if st.ladder is not None \
            else 0
        if st.budget is not None:
            out["retry_budget"] = st.budget.stats()
        out["sentinel"] = self._sentinel.snapshot()
        out["memory"] = _memwatch.model_footprint(st.cache, model=model)
        if st.slo is not None:
            out["slo"] = st.slo.snapshot()
        if self._fleet is not None:
            # only when a fleet is attached: stats() output with fleet
            # mode off is byte-identical to pre-fleet servers
            out["fleet"] = self._fleet.model_status(model)
        if self._rollout is not None:
            # same discipline for rollouts: no manager, no key
            ro = self._rollout.model_status(model)
            if ro is not None:
                out["rollout"] = ro
        if lat.size:
            out["p50_ms"] = float(np.percentile(lat, 50))
            out["p99_ms"] = float(np.percentile(lat, 99))
            out["mean_ms"] = float(lat.mean())
        return out

    def dump_traces(self, path: str) -> str:
        """Write the trace ring to ``path`` (the artifact
        ``tools/mxtrace.py`` pretty-prints)."""
        return self.tracer.write_dump(path)

    def ready(self) -> bool:
        """Readiness: started, not draining/stopped — the /readyz answer.
        (An open breaker keeps ready=true: other models still serve.)"""
        if self._guard is not None and self._guard.triggered:
            self.begin_drain()
        return bool(self._started and not self._draining.is_set()
                    and not self._stopped)

    def health(self) -> Dict[str, Any]:
        """Liveness + per-model detail — the /healthz answer."""
        status = ("stopped" if self._stopped
                  else "draining" if self._draining.is_set()
                  else "serving" if self._started else "created")
        return {"status": status, "ready": self.ready(),
                "models": {name: self.stats(name) for name in self._models}}
