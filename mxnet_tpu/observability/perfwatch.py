"""Perf-regression watchdog — compare live/fresh perf facts to baselines.

A measured bench row is ground truth; this module turns one into an
*enforced* floor instead of a number nobody re-reads. Three inputs normalize into one
comparable shape:

- a **bench row** (``{"metric": ..., "value": ...}``) → throughput, mfu,
  flops_per_step;
- a **telemetry snapshot** (``{"metrics": {...}}``) → the live
  ``mxtpu_mfu`` / ``mxtpu_trainer_samples_per_sec`` gauges of a running
  or finished run;
- a **cost-ledger row / JSONL ledger** (``xcost``) → flops_per_step and
  the roof times (a fatter step program is a regression before a single
  wall-clock second is measured).

:func:`compare` checks every metric present on BOTH sides against a
per-metric threshold (percent), honoring direction (throughput/mfu: lower
is worse; flops/step-time: higher is worse). :class:`PerfWatch` attaches
the same comparison to a live run (``ResilientTrainer(perfwatch=...)``):
every ``check_every`` steps it reads the live gauges, and a regression
logs a loud warning + ``mxtpu_perf_regressions_total{metric=}`` — warn,
never kill: a perf regression is a bug, not an emergency stop.

CLI: ``tools/perfwatch.py`` (mxlint exit convention — 0 pass, 1
regression, 2 missing/unloadable artifact).
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Tuple

from ..base import get_env, logger, register_config
from . import catalog as _catalog
from . import metrics as _metrics

__all__ = ["METRIC_DIRECTIONS", "DEFAULT_THRESHOLD_PCT", "normalize",
           "load_artifact", "compare", "PerfWatch"]

register_config("MXNET_PERF_BASELINE", "", str,
                "Default baseline artifact for the perf watchdog (a bench "
                "row / ledger row). Empty = no default: the watch is "
                "disarmed unless a baseline is passed.")

# metric -> +1 (higher is better) / -1 (lower is better)
METRIC_DIRECTIONS: Dict[str, int] = {
    "throughput": +1,          # img/s/chip from a bench row
    "mfu": +1,
    "samples_per_sec": +1,     # live trainer gauge (global, not per-chip)
    "flops_per_step": -1,      # a fatter compiled step is a regression
    "step_ms": -1,
    "qps": +1,                 # serving ledger row (label="serving")
    "p50_ms": -1,              # serving accepted-request latency
    "p99_ms": -1,
    "int8_ms": -1,             # quant ledger row (label="quant")
    "f32_ms": -1,
    "int8_vs_f32": +1,         # int8 speedup eroding is a regression
    "int8_acc": +1,            # and so is int8 accuracy drifting down
    "slo_burn_rate": -1,       # serving SLO error-budget burn (max over
                               # model/window series of mxtpu_slo_burn_rate)
    "degraded_rung": -1,       # self-healing ladder position (max over
                               # models of mxtpu_serve_degraded_rung):
                               # any rung above 0 is degraded service
    "budget_denied": -1,       # retry/hedge duplicates refused by the
                               # retry budget (sum over model/kind of
                               # mxtpu_retry_budget_denied_total)
    "peak_bytes": -1,          # memory ledger row (label="memory"): a
                               # fatter executable is a regression
    "footprint_bytes": -1,     # estimated resident bytes/chip (tuner
                               # trial / memwatch footprint)
    "rollout_agreement": +1,   # shadow top-1 agreement (worst model of
                               # mxtpu_rollout_shadow_agreement, or a
                               # loadgen --during-rollout ledger row):
                               # canary answers drifting from the
                               # incumbent is a regression
    "rollout_rollbacks": -1,   # sum over reasons of
                               # mxtpu_rollout_rollbacks_total: gate
                               # rollbacks trending up is a regression
}

DEFAULT_THRESHOLD_PCT = 10.0


def default_baseline_path() -> str:
    return str(get_env("MXNET_PERF_BASELINE", "") or "")


def normalize(doc: Any, source: str = "") -> Optional[Dict[str, Any]]:
    """Map any supported artifact to ``{"metrics": {name: value}, "kind",
    "source"}`` — or None when the document is not one of them."""
    if not isinstance(doc, dict):
        return None
    if "parsed" in doc and isinstance(doc["parsed"], dict):
        # driver wrapper: the parsed final row of a bench run
        return normalize(doc["parsed"], source=source)
    if "metrics" in doc and isinstance(doc["metrics"], dict):
        vals: Dict[str, float] = {}
        fams = doc["metrics"]

        def gauge(name):
            m = fams.get(name) or {}
            for s in m.get("series", []):
                if not s.get("labels"):
                    return s.get("value")
            return None

        mfu = gauge("mxtpu_mfu")
        sps = gauge("mxtpu_trainer_samples_per_sec")
        if mfu is not None:
            vals["mfu"] = float(mfu)
        if sps is not None:
            vals["samples_per_sec"] = float(sps)
        # SLO burn: worst series wins (labeled model=/window=, so the
        # unlabeled-gauge helper above never sees it)
        burn = None
        for s in (fams.get("mxtpu_slo_burn_rate") or {}).get("series", []):
            v = s.get("value")
            if v is not None:
                burn = float(v) if burn is None else max(burn, float(v))
        if burn is not None:
            vals["slo_burn_rate"] = burn
        # degraded rung: worst model wins (labeled model=)
        rung = None
        for s in (fams.get("mxtpu_serve_degraded_rung") or {}) \
                .get("series", []):
            v = s.get("value")
            if v is not None:
                rung = float(v) if rung is None else max(rung, float(v))
        if rung is not None:
            vals["degraded_rung"] = rung
        # budget denials: total duplicate work refused (model=/kind=)
        denied = None
        for s in (fams.get("mxtpu_retry_budget_denied_total") or {}) \
                .get("series", []):
            v = s.get("value")
            if v is not None:
                denied = (denied or 0.0) + float(v)
        if denied is not None:
            vals["budget_denied"] = denied
        # rollout gate health: worst model's shadow agreement (labeled
        # model=, up-is-good so the MIN is the worst), total rollbacks
        agree = None
        for s in (fams.get("mxtpu_rollout_shadow_agreement") or {}) \
                .get("series", []):
            v = s.get("value")
            if v is not None:
                agree = float(v) if agree is None else min(agree, float(v))
        if agree is not None:
            vals["rollout_agreement"] = agree
        rb = None
        for s in (fams.get("mxtpu_rollout_rollbacks_total") or {}) \
                .get("series", []):
            v = s.get("value")
            if v is not None:
                rb = (rb or 0.0) + float(v)
        if rb is not None:
            vals["rollout_rollbacks"] = rb
        return {"kind": "snapshot", "source": source, "metrics": vals}
    if "metric" in doc and "value" in doc:
        vals = {"throughput": float(doc["value"])}
        if doc.get("mfu") is not None:
            vals["mfu"] = float(doc["mfu"])
        if doc.get("flops_per_step") is not None:
            vals["flops_per_step"] = float(doc["flops_per_step"])
        return {"kind": "bench_row", "source": source, "metrics": vals,
                "provenance": doc.get("provenance"),
                "unit": doc.get("unit")}
    if doc.get("label") == "serving" and (
            doc.get("qps") is not None or doc.get("p99_ms") is not None):
        # serving ledger row (serving/load.py ledger_row): qps up-is-good,
        # accepted-latency percentiles down-is-good
        vals = {}
        for k in ("qps", "p50_ms", "p99_ms"):
            if doc.get(k) is not None:
                vals[k] = float(doc[k])
        ro = doc.get("rollout")
        if isinstance(ro, dict) and ro.get("agreement") is not None:
            # loadgen --during-rollout evidence riding the serving row
            vals["rollout_agreement"] = float(ro["agreement"])
        return {"kind": "serving_row", "source": source, "metrics": vals,
                "model": doc.get("model"),
                "provenance": doc.get("provenance")}
    if doc.get("label") == "fleet" and doc.get("qps") is not None:
        # mixed-tenant fleet ledger row (serving/load.py fleet_row):
        # aggregate qps plus bracketed per-tenant metrics — `p99_ms[a]`
        # compares with `p99_ms`'s direction (down-is-good), so tenants
        # come and go without touching METRIC_DIRECTIONS
        vals = {}
        for k, v in doc.items():
            base_name = k.split("[", 1)[0]
            if base_name in METRIC_DIRECTIONS and v is not None \
                    and isinstance(v, (int, float)):
                vals[k] = float(v)
        return {"kind": "fleet_row", "source": source, "metrics": vals,
                "tenants": doc.get("tenants"),
                "provenance": doc.get("provenance")}
    if doc.get("label") == "quant" and (
            doc.get("int8_ms") is not None or doc.get("f32_ms") is not None):
        # quantization ledger row (quant.compare_latency): latencies
        # down-is-good, speedup and int8 accuracy up-is-good — int8
        # regressions guard exactly like serving ones
        vals = {}
        for k in ("int8_ms", "f32_ms", "int8_vs_f32", "int8_acc"):
            if doc.get(k) is not None:
                vals[k] = float(doc[k])
        return {"kind": "quant_row", "source": source, "metrics": vals,
                "model": doc.get("model"),
                "provenance": doc.get("provenance")}
    if doc.get("label") == "memory" and isinstance(doc.get("memory"), dict):
        # memwatch memory ledger row: per-executable byte accounting —
        # peak down-is-good, so a step/bucket growing its HBM appetite
        # guards exactly like a latency regression
        vals = {}
        if doc.get("peak_memory_bytes") is not None:
            vals["peak_bytes"] = float(doc["peak_memory_bytes"])
        return {"kind": "memory_row", "source": source, "metrics": vals,
                "model": doc.get("model"), "bucket": doc.get("bucket"),
                "mem_label": doc.get("mem_label"),
                "provenance": doc.get("provenance")}
    if "roofline" in doc or "arithmetic_intensity" in doc:
        vals = {}
        if doc.get("flops") is not None:
            vals["flops_per_step"] = float(doc["flops"])
        if doc.get("optimal_ms_compute") is not None:
            vals["step_ms"] = float(doc["optimal_ms_compute"])
        # measured rows (bench windows, tuner trials) carry wall-clock
        # facts next to the compile-time ones — those win over the
        # optimal-roof step time and make the row a full baseline
        # (throughput/mfu/step_ms), e.g. `mxtune --emit-best` output
        if doc.get("measured_step_ms") is not None:
            vals["step_ms"] = float(doc["measured_step_ms"])
        if doc.get("throughput_img_s_per_chip") is not None:
            vals["throughput"] = float(doc["throughput_img_s_per_chip"])
        if doc.get("mfu") is not None:
            vals["mfu"] = float(doc["mfu"])
        if doc.get("footprint_bytes") is not None:
            # tuner trial rows carry the estimated resident bytes/chip:
            # a config whose memory appetite grew guards like step_ms
            vals["footprint_bytes"] = float(doc["footprint_bytes"])
        return {"kind": "ledger_row", "source": source, "metrics": vals,
                "roofline": doc.get("roofline"),
                "provenance": doc.get("provenance")}
    return None


def load_artifact(path: str) -> Tuple[Optional[Dict[str, Any]], str]:
    """Load + normalize one artifact file. JSONL ledgers take their LAST
    row (the freshest executable). Returns (normalized, error) — exactly
    one of the two is truthy."""
    try:
        with open(path) as f:
            text = f.read()
    except OSError as e:
        return None, "cannot read %s: %s" % (path, e)
    doc = None
    try:
        doc = json.loads(text)
    except ValueError:
        # JSON-lines ledger: last parseable row wins
        for ln in reversed(text.splitlines()):
            ln = ln.strip()
            if not ln:
                continue
            try:
                doc = json.loads(ln)
                break
            except ValueError:
                continue
    if doc is None:
        return None, "%s is not JSON or JSON-lines" % path
    norm = normalize(doc, source=path)
    if norm is None:
        return None, ("%s is not a bench row, telemetry snapshot or cost-"
                      "ledger row" % path)
    return norm, ""


def compare(current: Dict[str, Any], baseline: Dict[str, Any],
            thresholds: Optional[Dict[str, float]] = None,
            default_pct: float = DEFAULT_THRESHOLD_PCT) -> Dict[str, Any]:
    """Check every metric present on both sides. Returns ``{"status":
    "ok"|"regression"|"incomparable", "checks": [...]}`` where each check
    carries metric, baseline, current, delta_pct (signed, current vs
    baseline) and regressed."""
    thresholds = dict(thresholds or {})
    cur = current.get("metrics", current) or {}
    base = baseline.get("metrics", baseline) or {}
    checks: List[Dict[str, Any]] = []
    # iterate the union of both sides' metric names (sorted for stable
    # report order): bracketed per-tenant names — `p99_ms[a]` from fleet
    # rows — inherit the base metric's direction, unknown names skip
    for metric in sorted(set(base) | set(cur)):
        direction = METRIC_DIRECTIONS.get(metric)
        if direction is None:
            direction = METRIC_DIRECTIONS.get(metric.split("[", 1)[0])
        if direction is None:
            continue
        b, c = base.get(metric), cur.get(metric)
        if b is None or c is None or float(b) == 0.0:
            continue
        b, c = float(b), float(c)
        delta_pct = (c - b) / abs(b) * 100.0
        worse_pct = -delta_pct if direction > 0 else delta_pct
        thr = float(thresholds.get(metric,
                                   thresholds.get(metric.split("[", 1)[0],
                                                  default_pct)))
        checks.append({"metric": metric, "baseline": b, "current": c,
                       "delta_pct": round(delta_pct, 3),
                       "threshold_pct": thr,
                       "regressed": worse_pct >= thr})
    if not checks:
        status = "incomparable"
    elif any(ch["regressed"] for ch in checks):
        status = "regression"
    else:
        status = "ok"
    return {"status": status, "checks": checks,
            "baseline_source": baseline.get("source"),
            "current_source": current.get("source")}


class PerfWatch:
    """Warn-on-regression hook for a live run.

    >>> rt = ResilientTrainer(..., perfwatch={"check_every": 200})
    # every 200 steps the live mxtpu_mfu / samples_per_sec gauges are
    # compared against the baseline; a breach logs a warning and
    # bumps mxtpu_perf_regressions_total{metric=}.

    ``baseline`` may be a path (bench row / ledger), an
    already-normalized dict, or None for the ``MXNET_PERF_BASELINE`` env. A
    missing baseline disarms the watch with one warning — never an error:
    a fresh clone without bench history must still train.
    """

    def __init__(self, baseline=None, thresholds: Optional[Dict[str, float]] = None,
                 default_pct: float = DEFAULT_THRESHOLD_PCT,
                 check_every: int = 100):
        self.thresholds = dict(thresholds or {})
        self.default_pct = float(default_pct)
        self.check_every = max(1, int(check_every))
        self.last_result: Optional[Dict[str, Any]] = None
        self.events: List[Dict[str, Any]] = []
        self._warned_incomparable = False
        if isinstance(baseline, dict):
            self.baseline = (baseline if "metrics" in baseline
                             else {"kind": "inline", "source": "<dict>",
                                   "metrics": dict(baseline)})
            self.baseline_error = ""
        else:
            path = baseline or default_baseline_path()
            self.baseline, self.baseline_error = load_artifact(path)
            if self.baseline is None:
                logger.warning(
                    "perfwatch disarmed: no usable baseline (%s)",
                    self.baseline_error)

    def disarm(self, reason: str) -> None:
        """Drop the baseline so the watch stops checking — ONE warning, no
        regression spam. Called when the workload's signature changes out
        from under the baseline (e.g. an elastic reshard moved the run to
        a different device count: the old throughput/MFU floor describes a
        mesh that no longer exists). Idempotent."""
        if self.baseline is None:
            return
        self.baseline = None
        self.baseline_error = reason
        logger.warning("perfwatch disarmed: %s", reason)

    # ------------------------------------------------------------ checking
    def live_metrics(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        mfu = _catalog.MFU.value()
        sps = _catalog.SAMPLES_PER_SEC.value()
        if mfu is not None:
            out["mfu"] = float(mfu)
        if sps is not None:
            out["samples_per_sec"] = float(sps)
        burn = None
        for s in _catalog.SLO_BURN.series():
            v = s.get("value")
            if v is not None:
                burn = float(v) if burn is None else max(burn, float(v))
        if burn is not None:
            out["slo_burn_rate"] = burn
        return out

    def check(self, current: Optional[Dict[str, Any]] = None,
              step: Optional[int] = None) -> Optional[Dict[str, Any]]:
        """Compare ``current`` (default: the live gauges) to the baseline.
        Returns the comparison result, or None when disarmed."""
        if self.baseline is None:
            return None
        if current is None:
            current = {"kind": "live", "source": "<registry>",
                       "metrics": self.live_metrics()}
        res = compare(current, self.baseline, thresholds=self.thresholds,
                      default_pct=self.default_pct)
        if step is not None:
            res["step"] = int(step)
        self.last_result = res
        if res["status"] == "incomparable" and not self._warned_incomparable:
            # an armed watch that can never fire is worse than a disarmed
            # one — say so ONCE (e.g. a bare-core bench row with only
            # throughput vs live gauges that only carry mfu/samples_per_sec)
            self._warned_incomparable = True
            logger.warning(
                "perfwatch: baseline %s shares no metric with the current "
                "artifact (baseline has %s, current has %s) — the watch "
                "cannot fire; enable the cost ledger so live MFU is "
                "published, or choose a baseline with mfu/samples_per_sec",
                res.get("baseline_source"),
                sorted((self.baseline.get("metrics") or {})),
                sorted((current.get("metrics") or {})))
        for ch in res["checks"]:
            if not ch["regressed"]:
                continue
            self.events.append(dict(ch, step=step))
            if _metrics.enabled():
                _catalog.PERF_REGRESSIONS.inc(metric=ch["metric"])
            logger.warning(
                "perf regression: %s %.4g vs baseline %.4g (%+.1f%%, "
                "threshold %.1f%%, baseline %s)", ch["metric"],
                ch["current"], ch["baseline"], ch["delta_pct"],
                ch["threshold_pct"], res.get("baseline_source"))
        return res

    def on_step(self, step: int) -> Optional[Dict[str, Any]]:
        """The ResilientTrainer cadence hook: a real check every
        ``check_every`` steps, a no-op otherwise."""
        if self.baseline is None or step % self.check_every != 0:
            return None
        return self.check(step=step)
