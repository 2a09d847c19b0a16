"""Cost-model-guided autotuner: predict -> rank -> measure -> cache.

``tune()`` turns the PR-6 measurement substrate into *searched* speed
(ROADMAP item 1, the TVM autotuning shape from PAPERS.md: cost-model-ranked
candidates, measure only the top few, feed measurements back):

1. **enumerate** a declared :class:`~.space.SearchSpace` (batch, layout,
   remat, donation, prefetch depth);
2. **predict** each candidate's step time without running it — lower the
   candidate step, feed its ``xla_cost_analysis`` FLOPs/bytes through the
   ``xcost`` roofline model, optionally corrected by a linear model fitted
   on whatever measured ledger rows exist (:mod:`.model`);
3. **measure** only the top-K predicted candidates through the
   :mod:`.ladder` trial harness (one process / one TPU client);
4. **persist** every trial — predicted and measured — as a
   :class:`~mxnet_tpu.observability.xcost.CostLedger` row keyed by both the
   executable fingerprint and a config key, so repeat searches are
   warm-start cached (ranking reproducible from cache without re-lowering)
   and ``tools/perfwatch.py`` can use the best measured row as a baseline.

The returned :class:`TuneResult` carries the ranked trials with explicit
``provenance`` (``predicted`` / ``measured`` / ``cached``) and a best
config that applies directly to a ``DataParallelTrainer`` — bitwise HLO-
identical to building that config by hand (acceptance-tested).

Knobs: ``MXNET_TUNER_CACHE`` (trial ledger path; defaults to
``MXNET_PERF_LEDGER``, else the repo's ``mxtpu_cost_ledger.jsonl``),
``MXNET_TUNER_TOP_K``, ``MXNET_TUNER_STEPS``, ``MXNET_TUNER_WARMUP``,
``MXNET_TUNER_MEASURE``. Docs: ``docs/performance.md``.
"""
from __future__ import annotations

import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..base import MXNetError, get_env, logger, register_config
from ..observability import memwatch as _memwatch
from ..observability import metrics as _metrics
from ..observability import xcost as _xcost
from . import ladder as _ladder
from .model import LinearCorrection, roofline_ms
from .space import Candidate, SearchSpace

__all__ = ["TRIAL_LABEL", "cache_path", "get_cache", "tuner_rows",
           "best_cached", "Trial", "TuneResult", "tune"]

register_config("MXNET_TUNER_CACHE", "", str,
                "Path of the autotuner's trial ledger (JSON-lines, shared "
                "CostLedger format). Empty = MXNET_PERF_LEDGER when set, "
                "else <repo>/mxtpu_cost_ledger.jsonl.")
register_config("MXNET_TUNER_TOP_K", 3, int,
                "How many top-predicted candidates tuner.tune() actually "
                "measures (the TVM predict-then-measure budget).")
register_config("MXNET_TUNER_STEPS", 10, int,
                "Timed steps per measured tuner trial.")
register_config("MXNET_TUNER_WARMUP", 2, int,
                "Warmup steps per measured tuner trial (after the first/"
                "compile call).")
register_config("MXNET_TUNER_MEASURE", True, bool,
                "0 = predict-and-rank only: tune() never dispatches a "
                "timed trial (CPU boxes scoring a TPU search space).")

TRIAL_LABEL = "tuner.trial"


def cache_path() -> str:
    return str(get_env("MXNET_TUNER_CACHE", "")
               or _xcost.ledger_path()
               or os.path.join(_ladder._repo_root(),
                               "mxtpu_cost_ledger.jsonl"))


def get_cache(path: Optional[str] = None) -> _xcost.CostLedger:
    return _xcost.CostLedger(path or cache_path())


def tuner_rows(ledger: Optional[_xcost.CostLedger] = None,
               device_kind: Optional[str] = None,
               model: Optional[str] = None,
               net_class: Optional[str] = None,
               measured_only: bool = False) -> List[Dict[str, Any]]:
    """All tuner trial rows in the cache, oldest first, optionally filtered
    by device kind / model signature / measured-ness. Rows carry TWO model
    signatures: ``model`` (the caller's label, e.g. ``mxtune --model
    resnet50``) and ``net_class`` (the built net's class name — what a
    live trainer can derive about itself, the mxlint MXL-T211 key)."""
    led = ledger if ledger is not None else get_cache()
    out = []
    for r in led.rows():
        if r.get("label") != TRIAL_LABEL:
            continue
        if device_kind is not None and r.get("device_kind") != device_kind:
            continue
        if model is not None and r.get("model") != model:
            continue
        if net_class is not None and r.get("net_class") != net_class:
            continue
        if measured_only and not r.get("measured_step_ms"):
            continue
        out.append(r)
    return out


def best_cached(device_kind: Optional[str] = None,
                model: Optional[str] = None,
                net_class: Optional[str] = None,
                n_devices: Optional[int] = None,
                ledger: Optional[_xcost.CostLedger] = None
                ) -> Optional[Dict[str, Any]]:
    """The best MEASURED tuner row for a device/model signature (highest
    per-chip throughput), or None. This is what mxlint MXL-T211 checks a
    default-lever trainer against (filtered by ``net_class=`` — the only
    signature a live trainer can derive; ``model=`` filters by the
    caller's label).
    Pass ``n_devices`` too when the consumer knows its chip count: a
    global batch tuned on a 32-chip slice is not a recommendation for a
    single chip of the same device kind."""
    rows = tuner_rows(ledger, device_kind=device_kind, model=model,
                      net_class=net_class, measured_only=True)
    if n_devices is not None:
        rows = [r for r in rows
                if int(r.get("n_devices") or 0) == int(n_devices)]
    rows = [r for r in rows if r.get("throughput_img_s_per_chip")]
    if not rows:
        return None
    return max(rows, key=lambda r: float(r["throughput_img_s_per_chip"]))


class Trial:
    """One candidate's journey through the search."""

    def __init__(self, candidate: Candidate, config_key: str,
                 n_devices: int = 1):
        self.candidate = candidate
        self.config_key = config_key
        self.n_devices = max(1, int(n_devices))
        self.fingerprint: Optional[str] = None
        self.cost_row: Optional[Dict[str, Any]] = None
        self.predicted_ms: Optional[float] = None
        self.measured_ms: Optional[float] = None
        self.throughput: Optional[float] = None   # img/s per chip, measured
        self.mfu: Optional[float] = None
        self.provenance = "predicted"
        self.error: Optional[str] = None

    @property
    def predicted_img_s(self) -> Optional[float]:
        """Predicted PER-CHIP throughput — same unit as the measured
        ``throughput``, so a mixed predicted/measured ranking compares
        like with like (the roofline step time is the global step over
        ``n_devices`` chips)."""
        if not self.predicted_ms:
            return None
        return self.candidate.batch / self.predicted_ms * 1e3 \
            / self.n_devices

    @property
    def score(self) -> float:
        """Ranking key: measured per-chip throughput when the trial ran,
        predicted throughput otherwise; unpredictable candidates sink."""
        if self.throughput:
            return float(self.throughput)
        return float(self.predicted_img_s or 0.0)

    @property
    def measured(self) -> bool:
        return self.measured_ms is not None

    def as_dict(self) -> Dict[str, Any]:
        return {"candidate": self.candidate.as_dict(),
                "label": self.candidate.label,
                "provenance": self.provenance,
                "predicted_ms": self.predicted_ms,
                "predicted_img_s": self.predicted_img_s,
                "measured_step_ms": self.measured_ms,
                "throughput_img_s_per_chip": self.throughput,
                "mfu": self.mfu,
                "fingerprint": self.fingerprint,
                "error": self.error}


class TuneResult:
    """Ranked trials + the winning config, applier included."""

    def __init__(self, trials: List[Trial], best: Optional[Trial],
                 device_kind: Optional[str], model: str):
        self.trials = trials
        self.best = best
        self.device_kind = device_kind
        self.model = model

    @property
    def best_config(self) -> Optional[Candidate]:
        return self.best.candidate if self.best else None

    def ranked(self) -> List[Trial]:
        return sorted(self.trials, key=lambda t: t.score, reverse=True)

    def report(self) -> Dict[str, Any]:
        return {"device_kind": self.device_kind, "model": self.model,
                "best": self.best.as_dict() if self.best else None,
                "trials": [t.as_dict() for t in self.ranked()]}

    def build_trainer(self, net, loss_fn, optimizer: str = "sgd",
                      optimizer_params: Optional[Dict] = None, **extra):
        """Apply the best config to a fresh net — delegates to
        :meth:`Candidate.build_trainer` (bitwise HLO round trip)."""
        if self.best is None:
            raise MXNetError("tune() found no usable candidate")
        return self.best.candidate.build_trainer(
            net, loss_fn, optimizer, optimizer_params, **extra)


def _data_sig(arrays) -> List[List[Any]]:
    """Shape/dtype signature of the sample batch — part of the config
    key: the data() callback controls shapes beyond batch/layout (image
    size, classes), and a 128px measurement must never warm-start a
    224px search."""
    return [list(map(int, a.shape)) + [str(a.dtype)] for a in arrays]


def _count_trial(provenance: str) -> None:
    if _metrics.enabled():
        from ..observability import catalog as _catalog
        _catalog.TUNER_TRIALS.inc(provenance=provenance)


def _latest_by_key(rows: Sequence[Dict[str, Any]]
                   ) -> Dict[str, Dict[str, Any]]:
    """config_key -> freshest row, measured rows always beating predicted
    ones of the same key (a measurement supersedes its own prediction)."""
    out: Dict[str, Dict[str, Any]] = {}
    for r in rows:                       # rows() is oldest-first
        k = r.get("config_key")
        if not k:
            continue
        prev = out.get(k)
        if prev is not None and prev.get("measured_step_ms") \
                and not r.get("measured_step_ms"):
            continue
        out[k] = r
    return out


def tune(build: Callable[[Candidate], Tuple[Any, Any]],
         data: Callable[[Candidate], Tuple[Any, Any]],
         space: Optional[SearchSpace] = None, *,
         candidates: Optional[Sequence[Candidate]] = None,
         optimizer: str = "sgd",
         optimizer_params: Optional[Dict] = None,
         compute_dtype=None,
         top_k: Optional[int] = None,
         measure: Optional[bool] = None,
         steps: Optional[int] = None,
         warmup: Optional[int] = None,
         ledger=None,
         model: str = "",
         correction: bool = True,
         feed: bool = False,
         via_passes: bool = False) -> TuneResult:
    """Search the config space for the fastest training-step configuration.

    ``build(candidate) -> (net, loss_fn)`` constructs the model for a
    candidate (layout is a net-level choice); ``data(candidate) ->
    (x, y)`` returns one host sample batch of the candidate's batch size
    and layout. Everything else — lowering, cost analysis, prediction,
    ranking, the measure budget, ledger persistence, warm-start — is the
    tuner's job. Returns a :class:`TuneResult`.

    ``via_passes=True`` routes each candidate's layout dimension
    through the graph-pass pipeline (``Candidate.passes_manager``) instead
    of hand-built net flags: ``build`` must construct the NCHW net, and the
    pass-rewritten step is bitwise-HLO-identical to the hand-flagged one
    (the flag-vs-pass acceptance test), so measurements and warm-start
    cache rows are interchangeable between the two routes.

    ``feed=True`` measures each trial through a device-feed pipeline
    (``io.prefetch_to_device`` at the candidate's ``prefetch_depth``)
    instead of device-resident staging — the only mode in which the
    prefetch dimension can actually differentiate candidates (the
    predictor always scores it neutral: it never changes the compiled
    step).

    On a box whose device peaks are unknown (CPU backend) set
    ``MXNET_PERF_PEAK_FLOPS`` / ``MXNET_PERF_PEAK_HBM_GBPS`` so the
    roofline has a denominator; without them and with ``measure=False``
    nothing can be ranked and ``tune`` raises.
    """
    import jax

    if model == "":
        model = None                       # filled from the first built net
    cands = list(candidates) if candidates is not None else None
    if cands is None:
        space = space or SearchSpace()
        cands = space.enumerate()
    if not cands:
        raise MXNetError("tune(): no candidates to search")
    led = ledger if isinstance(ledger, _xcost.CostLedger) else \
        get_cache(ledger)
    top_k = int(get_env("MXNET_TUNER_TOP_K", 3)) if top_k is None \
        else int(top_k)
    measure = bool(get_env("MXNET_TUNER_MEASURE", True)) if measure is None \
        else bool(measure)
    steps = int(get_env("MXNET_TUNER_STEPS", 10)) if steps is None \
        else int(steps)
    warmup = int(get_env("MXNET_TUNER_WARMUP", 2)) if warmup is None \
        else int(warmup)
    if steps < 1:
        raise MXNetError("tune(): steps must be >= 1 (a measured trial "
                         "needs a timed window), got %d" % steps)
    warmup = max(0, warmup)

    dev = jax.devices()[0]
    device_kind = dev.device_kind
    n_devices = len(jax.devices())

    # ONE read of the (shared, append-only, never-pruned) ledger file;
    # every cache view below filters this in-memory list — the correction
    # fit, the config-key map and the per-trial fingerprint scans must not
    # each re-parse a file that bench windows and live trainers keep
    # growing
    all_rows = [r for r in led.rows() if r.get("label") == TRIAL_LABEL]
    measured_rows = [r for r in all_rows if r.get("measured_step_ms")]

    # learned correction: fitted on whatever measured trial rows this
    # exact setup already has — same device kind, chip count AND feed
    # mode (a feed wall clock embeds pipeline stalls the resident mode
    # never pays; mixing them would bias the fit) — silently a no-op
    # below MIN_FIT_ROWS
    corr = LinearCorrection()
    if correction:
        corr.fit([r for r in measured_rows
                  if r.get("device_kind") == device_kind
                  and int(r.get("n_devices") or 0) == n_devices
                  and bool(r.get("feed")) == feed])

    # probe the model signature once; the built pair is handed to the
    # first candidate's predict iteration instead of being thrown away and
    # rebuilt. A failing probe must not abort the search — it degrades to
    # model="" and the loop records cands[0]'s error like any other
    # candidate failure (same behavior as an explicit model= call)
    probe_ctx = None
    if model is None:
        try:
            probe_ctx = build(cands[0])
            model = type(probe_ctx[0]).__name__
        except Exception as e:
            logger.warning("tuner: model probe (first candidate build) "
                           "failed: %r", e)
            model = ""

    cached = _latest_by_key([r for r in all_rows
                             if r.get("device_kind") == device_kind
                             and r.get("model") == model])
    # fingerprint -> freshest measured row for the cross-config warm
    # start. Device-scoped: a StableHLO digest carries no device kind, so
    # the same program measured on another chip (or chip count) would
    # otherwise donate its wall clock to this search
    by_fingerprint: Dict[str, Dict[str, Any]] = {
        r["fingerprint"]: r for r in measured_rows
        if r.get("fingerprint")
        and r.get("device_kind") == device_kind
        and int(r.get("n_devices") or 0) == n_devices}
    opt_desc = (str(optimizer),
                tuple(sorted((str(k), repr(v)) for k, v in
                             (optimizer_params or {}).items())))
    trials: List[Trial] = []
    for cand in cands:
        def cand_key(sig):
            return cand.key(device_kind, model, n_devices=n_devices,
                            compute_dtype=compute_dtype,
                            optimizer=opt_desc, data_shapes=sig,
                            feed=feed)
        try:
            sample = data(cand)
            sig = _data_sig(sample)
        except Exception as e:
            t = Trial(cand, cand_key(None), n_devices=n_devices)
            t.error = repr(e)[:300]
            trials.append(t)
            logger.warning("tuner: candidate %s data() failed: %r",
                           cand.label, e)
            continue
        key = cand_key(sig)
        t = Trial(cand, key, n_devices=n_devices)
        trials.append(t)
        row = cached.get(key)
        if row is not None:
            probe_ctx = None          # the probe build is not needed
            # warm start: this exact config was scored (or measured) by a
            # previous search — reuse the row, re-lower nothing
            t.cost_row = row
            t.fingerprint = row.get("fingerprint")
            t.predicted_ms = row.get("predicted_ms") or roofline_ms(row)
            if row.get("measured_step_ms"):
                t.measured_ms = float(row["measured_step_ms"])
                t.throughput = row.get("throughput_img_s_per_chip")
                t.mfu = row.get("mfu")
            t.provenance = "cached"
            _count_trial("cached")
            continue
        try:
            if probe_ctx is not None and cand is cands[0]:
                net, loss_fn = probe_ctx
            else:
                net, loss_fn = build(cand)
            probe_ctx = None
            x, y = sample
            trainer = cand.build_trainer(net, loss_fn, optimizer,
                                         optimizer_params,
                                         via_passes=via_passes,
                                         compute_dtype=compute_dtype)
            # local tracing only: data abstracted to shape structs, no
            # compile, nothing dispatched (DataParallelTrainer.lower)
            lowered = trainer.lower(x, y)
            ca = _xcost.cost_of(lowered)
            if not ca:
                raise MXNetError("backend reported no cost analysis")
            row = _xcost.analyze_cost(ca, device_kind=device_kind,
                                      n_devices=n_devices)
            t.fingerprint = trainer._lowered_digest(lowered)
            t.predicted_ms = corr.predict_ms(row)
            row.update({"label": TRIAL_LABEL, "provenance": "predicted",
                        "fingerprint": t.fingerprint, "config_key": key,
                        "tuner_config": cand.as_dict(), "model": model,
                        "net_class": type(net).__name__,
                        "platform": dev.platform,
                        "predicted_ms": t.predicted_ms,
                        "batch": cand.batch,
                        "layout": cand.layout})
            # memory column: the candidate's resident footprint (params +
            # opt-state + batch), estimated host-side off the live trainer
            # lower() just materialized — the predicted-OOM gate below and
            # mxmem's blame ranking read it back from the ledger row
            try:
                fp = trainer.footprint()
                batch_bytes = sum(int(getattr(a, "nbytes", 0) or 0)
                                  for a in (x, y))
                row["footprint"] = fp
                row["footprint_bytes"] = (int(fp["per_chip_bytes"])
                                          + batch_bytes // max(1, n_devices))
            except Exception as e:
                logger.warning("tuner: candidate %s footprint estimate "
                               "failed: %r", cand.label, e)
            t.cost_row = row
            led.append(row)
            # the built trainer is NOT kept: a wide space would otherwise
            # hold every candidate's params/opt-state on device at once
            # (the old perf_lab built one variant at a time — so does the
            # measure phase, which rebuilds its top-K on demand)
            del trainer, net
            _count_trial("predicted")
        except Exception as e:
            t.error = repr(e)[:300]
            logger.warning("tuner: candidate %s failed to predict: %r",
                           cand.label, e)

    scorable = [t for t in trials if t.error is None
                and (t.predicted_ms or t.measured)]
    if not scorable:
        raise MXNetError(
            "tune(): no candidate could be scored — on an unknown device "
            "set MXNET_PERF_PEAK_FLOPS / MXNET_PERF_PEAK_HBM_GBPS so the "
            "roofline has peaks (errors: %s)"
            % "; ".join(filter(None, (t.error for t in trials)))[:300])

    ranked = sorted(scorable, key=lambda t: t.score, reverse=True)

    if measure:
        for t in ranked[:max(0, top_k)]:
            if t.measured:
                continue
            # fingerprint-level warm start: the same executable may have
            # been measured under a different config key (e.g. another
            # model alias) — never pay for a measurement twice. In feed
            # mode the wall clock also depends on the prefetch depth (a
            # feed-level knob invisible to the fingerprint), so only a
            # same-depth donor qualifies there.
            if t.fingerprint:
                def _adoptable(r_):
                    if r_ is None:
                        return None
                    if bool(r_.get("feed")) != feed:
                        return None     # feed vs device-resident clocks
                    if feed and (r_.get("tuner_config") or {}).get(
                            "prefetch_depth") != t.candidate.prefetch_depth:
                        return None
                    return r_
                r = _adoptable(by_fingerprint.get(t.fingerprint))
                if r is None:
                    # measured earlier in THIS loop (two configs lowering
                    # to one executable in the same search)
                    done = [o for o in trials
                            if o is not t and o.measured
                            and o.fingerprint == t.fingerprint
                            and o.cost_row]
                    r = _adoptable(done[-1].cost_row) if done else None
                if r is not None:
                    t.measured_ms = float(r["measured_step_ms"])
                    t.throughput = r.get("throughput_img_s_per_chip")
                    t.mfu = r.get("mfu")
                    t.provenance = "cached"
                    # the adopted facts are persisted under THIS trial's
                    # config identity: --emit-best hands the row to
                    # perfwatch, and best_cached/MXL-T211 filter persisted
                    # rows by model/net_class — an in-memory-only adoption
                    # would hide the measurement from both, and the next
                    # search would re-scan instead of config-key-hitting
                    adopted = dict(r)
                    adopted.update({
                        "config_key": t.config_key,
                        "tuner_config": t.candidate.as_dict(),
                        "model": model, "provenance": "cached",
                        "net_class": (t.cost_row or {}).get("net_class")
                        or r.get("net_class")})
                    led.append(adopted)
                    t.cost_row = adopted
                    _count_trial("cached")
                    continue
            # predicted-OOM gate: a candidate whose estimated footprint
            # exceeds the per-chip HBM budget is skipped LOUDLY before a
            # single buffer lands — measuring it would OOM the search on
            # the real device. Unbudgeted (budget None) measures as ever.
            need = int((t.cost_row or {}).get("footprint_bytes") or 0)
            budget = _memwatch.hbm_budget_bytes()
            if budget is not None and need:
                avail = (int(budget)
                         - int(_memwatch.pressure()["ballast_bytes"]))
                if need > avail:
                    t.error = ("predicted OOM: footprint ~%d bytes/chip "
                               "over the %d-byte HBM budget — not "
                               "measured" % (need, avail))
                    logger.error("tuner: candidate %s SKIPPED (%s)",
                                 t.candidate.label, t.error)
                    flagged = dict(t.cost_row)
                    flagged["predicted_oom"] = True
                    led.append(flagged)
                    t.cost_row = flagged
                    if _metrics.enabled():
                        from ..observability import catalog as _catalog
                        _catalog.MEM_REFUSALS.inc(reason="predicted_oom")
                    continue
            trainer = net = m = None
            try:
                # one trial's trainer alive at a time (perf_lab semantics)
                net, loss_fn = build(t.candidate)
                x, y = data(t.candidate)
                trainer = t.candidate.build_trainer(
                    net, loss_fn, optimizer, optimizer_params,
                    via_passes=via_passes, compute_dtype=compute_dtype)
                m = _ladder.measure_step(
                    trainer, x, y, steps=steps, warmup=warmup, feed=feed,
                    prefetch_depth=t.candidate.prefetch_depth)
                t.measured_ms = m["step_ms"]
                t.throughput = m["img_s"] / n_devices
                t.provenance = "measured"
                row = dict(t.cost_row or {})
                flops = row.get("flops")
                peak = _xcost.peak_flops(device_kind)
                if flops and peak:
                    t.mfu = float(flops) / (
                        m["step_ms"] / 1e3 * peak * n_devices)
                row.update({"label": TRIAL_LABEL, "provenance": "measured",
                            "measured_step_ms": t.measured_ms,
                            "throughput_img_s_per_chip": t.throughput,
                            "mfu": t.mfu, "trial_steps": steps,
                            "trial_warmup": warmup, "feed": feed,
                            "config_key": t.config_key,
                            "tuner_config": t.candidate.as_dict(),
                            "model": model, "fingerprint": t.fingerprint,
                            "loss": m["loss"]})
                led.append(row)
                t.cost_row = row
                _count_trial("measured")
            except Exception as e:
                t.error = repr(e)[:300]
                logger.warning("tuner: candidate %s failed to measure: %r",
                               t.candidate.label, e)
            finally:
                # drop this trial's device state (params/opt-state AND the
                # staged batch riding in m["xd"]/m["yd"]) before the next
                # trial builds — two coexisting trials near the HBM limit
                # would OOM where each alone fits
                trainer = net = m = None

    # the winner: best measured trial when any ran; best prediction else
    measured_ok = [t for t in scorable if t.measured and t.error is None]
    pool = measured_ok or [t for t in scorable if t.error is None]
    best = max(pool, key=lambda t: t.score) if pool else None
    if best is not None and best.mfu and _metrics.enabled():
        from ..observability import catalog as _catalog
        _catalog.TUNER_BEST_MFU.set(float(best.mfu))
    return TuneResult(trials, best, device_kind, model or "")
