"""mxnet_tpu.tuner — the self-tuning perf lab (ROADMAP item 1).

Searches the training-step config space — batch size, NCHW/NHWC layout
(+ space-to-depth stem), remat policy, buffer donation, prefetch depth,
and the comm levers (grad_reduce / grad_reduce_dtype / bucket_bytes) —
instead of requiring a human to run bench ladders:

==========  ============================================================
piece        what it gives you
==========  ============================================================
space        :class:`SearchSpace` / :class:`Candidate` — levers as data,
             appliable to a ``DataParallelTrainer`` bitwise-identically
             to hand-written kwargs
model        roofline predictor over ``xla_cost_analysis`` facts plus a
             learned linear correction fitted on measured ledger rows
ladder       the perf-lab trial harness as an importable library
             (``tools/perf_lab.py`` is now a thin CLI over it)
tuner        :func:`tune` — enumerate, predict, rank, measure top-K,
             persist every trial as a warm-start-cacheable CostLedger row
==========  ============================================================

CLI: ``tools/mxtune.py``. Telemetry: ``mxtpu_tuner_trials_total``,
``mxtpu_tuner_best_mfu``. Docs: ``docs/performance.md``.
"""
from __future__ import annotations

from . import ladder
from . import model
from . import space
from . import tuner
from .ladder import (DEFAULT_VARIANTS, SEED_VARIANTS, VariantSpec,
                     parse_variants, measure_step, run_ladder, run_variant,
                     profile_step, hlo_audit, imperative_lab)
from .model import LinearCorrection, predict_step_ms, roofline_ms
from .space import Candidate, SearchSpace
from .tuner import (TRIAL_LABEL, Trial, TuneResult, best_cached,
                    cache_path, get_cache, tune, tuner_rows)

__all__ = ["ladder", "model", "space", "tuner",
           "DEFAULT_VARIANTS", "SEED_VARIANTS", "VariantSpec",
           "parse_variants", "measure_step", "run_ladder", "run_variant",
           "profile_step", "hlo_audit", "imperative_lab",
           "LinearCorrection", "predict_step_ms", "roofline_ms",
           "Candidate", "SearchSpace",
           "TRIAL_LABEL", "Trial", "TuneResult", "best_cached",
           "cache_path", "get_cache", "tune", "tuner_rows"]
