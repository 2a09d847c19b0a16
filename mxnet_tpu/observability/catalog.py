"""Built-in metric catalog — every family the framework itself publishes.

Declared centrally (not at each instrumentation site) so a snapshot always
contains the full catalog regardless of which subsystems a given run
imported: a dashboard scraping ``mxtpu_kv_publish_ms`` sees the family (with
zero series) even in a run that never created a dist kvstore, instead of a
404-shaped absence. Instrumentation sites import their family objects from
here; user code can mint additional metrics via ``observability.counter``/
``gauge``/``histogram`` freely.

The human-oriented catalog with semantics lives in ``docs/observability.md``
— keep the two in sync.
"""
from __future__ import annotations

from . import metrics as _m

# --------------------------------------------------------------- trainer
STEP_MS = _m.histogram(
    "mxtpu_trainer_step_ms",
    "Time from one DataParallelTrainer.step entry to the next (the wall "
    "step cadence, which back-pressure makes the device's step time; NOT "
    "the time to enqueue a step, which is mxtpu_span_ms{span="
    "\"trainer.enqueue\"}). No sample for a trainer's first step.")
STEPS_TOTAL = _m.counter(
    "mxtpu_trainer_steps_total", "Fused train steps dispatched.")
SAMPLES_TOTAL = _m.counter(
    "mxtpu_trainer_samples_total",
    "Training samples consumed (leading batch dim of the first input).")
SAMPLES_PER_SEC = _m.gauge(
    "mxtpu_trainer_samples_per_sec",
    "Throughput of the most recent step: samples / the time since the "
    "previous step's entry (the cadence, not the enqueue time).")
CAPTURES_TOTAL = _m.counter(
    "mxtpu_trainer_captures_total",
    "Net captures (graph trace + jit rebuild). More than one per input "
    "signature means something is forcing re-capture.")
LOSS_INPUTS = _m.gauge(
    "mxtpu_trainer_loss_inputs",
    "Outputs of the net that the latest capture handed to the loss block: "
    "as many as the loss's hybrid_forward names in front of label (1 for a "
    "loss of one prediction, whatever the net returns).")
GRAD_SKIPPED = _m.gauge(
    "mxtpu_trainer_grad_skipped_steps",
    "Grad-guard skip-step count (published when anomaly_stats()/Monitor "
    "drains the device counters — never synced per step).")
GRAD_NORM_EMA = _m.gauge(
    "mxtpu_trainer_grad_norm_ema", "Grad-guard gradient-norm EMA.")
GRAD_LAST_NORM = _m.gauge(
    "mxtpu_trainer_last_grad_norm", "Gradient norm of the last guarded step.")
STEP_RETRIES = _m.counter(
    "mxtpu_trainer_step_retries_total",
    "Transient step failures retried by ResilientTrainer.")


# ------------------------------------------------------------------- ops
CONV_S2D_LOWERED = _m.counter(
    "mxtpu_conv_s2d_lowered_total",
    "Convolution ops computed through the exact space-to-depth form, row "
    "pairs folded into channels (stride-2 channel-last convs over <= 4 "
    "input channels with an even height: a stem). "
    "Counted when the op is TRACED, so it moves with captures and "
    "compiles, never with steps; a stem net whose counter stays 0 reached "
    "the op in a shape the lowering does not take (NCHW, odd height).")

POOL_BWD_LOWERED = _m.counter(
    "mxtpu_pool_bwd_lowered_total",
    "Max pools whose backward scatters the gradient from the saved winning "
    "tap of each window and not through select-and-scatter (a TPU process; "
    "2-D, <= 9 taps, bfloat16/float32; per device a multiple of 128 rows, "
    "channels of 32, height and width of the strides). Counted when a "
    "DIFFERENTIATED pool is traced: once per capture of a training step, "
    "never per step and never for an inference trace; a net whose counter "
    "stays 0 reached the op in a shape, or in a program whose split of the "
    "batch the op cannot see, that keeps reduce_window's own gradient.")

POOL_SUNK = _m.counter(
    "mxtpu_pool_sunk_total",
    "Max pools computed in front of the BatchNorm apply that fed them "
    "(the fusion pass's _MaxPoolBatchNorm: maxpool(y*s + b) == "
    "|s| * maxpool(sgn(s)*y) + b, exact), so the apply and the ReLU behind "
    "it run on the pooled map. Counted when the op is traced: once per "
    "trace of such a stem, never per step; a net whose counter stays 0 has "
    "no BatchNorm whose only consumer is a max pool, directly or through a "
    "ReLU, or was captured with the fusion pass off.")

FLASH_ATTENTION_LOWERED = _m.counter(
    "mxtpu_flash_attention_lowered_total",
    "Traces of the _contrib_flash_attention op, labeled route= by what its "
    "forward lowered to: \"pallas\" (the Mosaic kernel: a TPU process or the "
    "interpreter, head size a multiple of 128, lengths of 8, a batch the op "
    "can see the split of) or \"xla\" (the plain softmax(q k^T) v form with "
    "the T x T scores in memory). Counted when the op is TRACED, never per "
    "step; a long-context net that reads route=\"xla\" on a TPU fell back "
    "silently and pays for the scores.")

REMAT_SEGMENTS = _m.counter(
    "mxtpu_remat_segments_total",
    "Segments of a symbol graph lowered as one function under "
    "jax.checkpoint (executor._GraphLowering: consecutive nodes that carry "
    "the same force_mirroring attribute, as mx.AttrScope(force_mirroring=) "
    "sets it), counted per trace of the lowered function: a capture of a "
    "looped decoder's step reads passes x layers (its exits are no "
    "segments), a graph without the attribute 0. The backward pass keeps a "
    "segment's inputs and its products that do not widen "
    "(mxtpu_remat_kept_total) and recomputes the rest.")

REMAT_KEPT = _m.counter(
    "mxtpu_remat_kept_total",
    "Values inside recomputed segments that the backward pass keeps besides "
    "the segments' inputs: output 0 of an op registered with product=True "
    "(FullyConnected, Convolution, Deconvolution, dot, batch_dot, "
    "_contrib_flash_attention, _contrib_moe_experts) where it is no larger "
    "than the op's first "
    "operand; a product that widens is recomputed with the cheap ops. "
    "Counted per trace of the lowered function, where "
    "mxtpu_remat_segments_total is: a capture of a looped decoder's step "
    "reads segments x 3 (out-projection, down-projection and attention of "
    "a layer-call; qkv, gate and up widen), a graph without segments 0.")

MOE_LOWERED = _m.counter(
    "mxtpu_moe_lowered_total",
    "Differentiated traces of the _contrib_moe_experts op, labeled route= by "
    "what its products lowered to: \"grouped\" (tokens sorted by expert and "
    "one grouped product a projection, the Mosaic kernels moe_gmm*: a TPU "
    "process or the interpreter, widths of whole lane blocks that fit in "
    "VMEM, a batch whose split over devices the op can see) or \"plain\" "
    "(every held expert over all tokens under a mask: held times the "
    "products). Counted when the op's gradient is TRACED, as "
    "mxtpu_pool_bwd_lowered_total is: once per expert layer of a captured "
    "training step, never per step and never for an inference trace; a "
    "mixture-of-experts net that reads route=\"plain\" on a TPU fell back "
    "silently.")
MOE_EXPERTS_HELD = _m.gauge(
    "mxtpu_moe_experts_held",
    "Experts whose weights the most recently traced _contrib_moe_experts op "
    "was given: this holder's share of mxtpu_moe_experts_routed.")
MOE_EXPERTS_ROUTED = _m.gauge(
    "mxtpu_moe_experts_routed",
    "Experts the router of the most recently traced _contrib_moe_experts op "
    "chooses among (num_experts=; the held ones where it is not given). A "
    "token whose expert is not held gets no expert term here.")

# -------------------------------------------------------------------- io
IO_BATCHES = _m.counter(
    "mxtpu_io_batches_total",
    "Batches delivered by ResilientDataIter, labeled iter= (base iterator "
    "class).")
IO_READ_RETRIES = _m.counter(
    "mxtpu_io_read_retries_total",
    "Transient data-read failures retried with backoff "
    "(ResilientDataIter, MXNET_IO_RETRY_*).")
IO_SKIPPED_BATCHES = _m.counter(
    "mxtpu_io_corrupt_skipped_total",
    "Corrupt batches skipped under MXNET_IO_SKIP_BUDGET (past the budget "
    "the run fails loudly instead).")
IO_QUEUE_DEPTH = _m.gauge(
    "mxtpu_io_queue_depth",
    "Staged batches in a prefetch queue at last delivery, labeled iter=. "
    "Persistently 0 under load = the producer can't keep up.")
IO_FEED_STALL_MS = _m.histogram(
    "mxtpu_io_feed_stall_ms",
    "Time the consumer blocked in next() waiting for data — the host-feed "
    "stall XLA cannot hide.",
    buckets=(0.5, 1, 2.5, 5, 10, 25, 50, 100, 250, 500, 1000, 5000, 30000))

# ---------------------------------------------------------------- module
FIT_EPOCH_MS = _m.histogram(
    "mxtpu_fit_epoch_ms", "Module.fit wall time per epoch.",
    buckets=(100, 500, 1000, 5000, 15000, 60000, 300000, 1800000))
FIT_BATCHES = _m.counter(
    "mxtpu_fit_batches_total", "Batches processed by Module.fit.")

# --------------------------------------------------------------- kvstore
KV_PUBLISH_MS = _m.histogram(
    "mxtpu_kv_publish_ms",
    "dist kvstore weight-publish latency (coordination-service round "
    "trip), per attempt.")
KV_PUBLISH_RETRIES = _m.counter(
    "mxtpu_kv_publish_retries_total",
    "Publish attempts that failed transiently and backed off.")
KV_PUBLISH_FAILURES = _m.counter(
    "mxtpu_kv_publish_failures_total",
    "Publishes that exhausted their retry budget (TransientKVError).")
KV_PUSH_TOTAL = _m.counter(
    "mxtpu_kv_push_total", "kvstore push operations.")
KV_PULL_TOTAL = _m.counter(
    "mxtpu_kv_pull_total", "kvstore pull operations.")

# ------------------------------------------------------------ checkpoint
CKPT_SAVE_MS = _m.histogram(
    "mxtpu_checkpoint_save_ms",
    "ShardedCheckpointer.save wall time, labeled mode=sync|async (async "
    "measures snapshot+dispatch; serialization overlaps training).",
    buckets=(5, 25, 100, 500, 1000, 5000, 15000, 60000, 300000))
CKPT_COMMIT_MS = _m.histogram(
    "mxtpu_checkpoint_commit_ms",
    "Manifest + marker + atomic publish rename time.",
    buckets=(1, 5, 25, 100, 500, 1000, 5000, 15000))
CKPT_RESTORE_MS = _m.histogram(
    "mxtpu_checkpoint_restore_ms", "Checkpoint restore wall time.",
    buckets=(5, 25, 100, 500, 1000, 5000, 15000, 60000, 300000))
CKPT_BYTES = _m.counter(
    "mxtpu_checkpoint_bytes_total", "Bytes committed to checkpoints.")
CKPT_LAST_BYTES = _m.gauge(
    "mxtpu_checkpoint_last_bytes", "Size of the most recent checkpoint.")
CKPT_VERIFY_FAILURES = _m.counter(
    "mxtpu_checkpoint_verify_failures_total",
    "verify() calls that found a torn/uncommitted checkpoint.")

# ------------------------------------------------------------ collectives
COLL_DISPATCHES = _m.counter(
    "mxtpu_collective_dispatches_total",
    "Host-level collective dispatches, labeled op=psum|cp_allreduce|"
    "cp_alltoall|cp_allgather.")
COLL_BYTES = _m.counter(
    "mxtpu_collective_bytes_total",
    "Payload bytes entering host-level collectives, labeled op=.")
COLL_MS = _m.histogram(
    "mxtpu_collective_ms",
    "Measured wall time of one collective operation in the bandwidth lab "
    "(parallel/collbench.py), labeled op=psum|reduce_scatter|all_gather|"
    "ppermute|psum_compressed.",
    buckets=(0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 25, 50, 100, 500, 2000))

# ------------------------------------------------------------- resilience
WATCHDOG_FIRED = _m.counter(
    "mxtpu_watchdog_timeouts_total", "Watchdog deadline expirations.")
PREEMPTIONS = _m.counter(
    "mxtpu_preemptions_total",
    "Preemption signals honored at a step boundary (final save + exit).")
FLIGHT_DUMPS = _m.counter(
    "mxtpu_flight_recorder_dumps_total",
    "Flight-recorder artifacts written, labeled reason=.")
RECOVERY_TRIPS = _m.counter(
    "mxtpu_recovery_trips_total",
    "Recovery-ladder detector trips, labeled kind=skip_streak|"
    "loss_divergence|escalated.")
RECOVERY_ROLLBACKS = _m.counter(
    "mxtpu_recovery_rollbacks_total",
    "Recovery-ladder actions taken, labeled action=cut_scale|rollback|"
    "restore|fail|heal (rollback = in-memory snapshot, restore = durable "
    "checkpoint).")
RECOVERY_RUNG = _m.gauge(
    "mxtpu_recovery_rung",
    "Current recovery-ladder rung (0 = healthy; de-escalates after "
    "MXNET_RECOVERY_HEAL_STEPS clean steps).")
RECOVERY_SNAPSHOTS = _m.counter(
    "mxtpu_recovery_snapshots_total",
    "Rolling in-memory snapshots captured (rollback targets).")
RECOVERY_DEFERRED_SAVES = _m.counter(
    "mxtpu_recovery_deferred_saves_total",
    "Durable checkpoints deferred because guard-skipped steps were still "
    "awaiting rollback replay, labeled kind=periodic|preemption.")
LOSS_SCALE = _m.gauge(
    "mxtpu_loss_scale",
    "Live dynamic loss scale of the in-trace scaler (published when "
    "anomaly_stats()/recovery drains it — never synced per step).")
ELASTIC_RESHARDS = _m.counter(
    "mxtpu_elastic_reshards_total",
    "Elastic N→M topology adoptions completed at restore (ZeRO-1 "
    "opt-state re-tiled, global batch re-split), labeled "
    "direction=grow|shrink.")
ACTIVE_DEVICES = _m.gauge(
    "mxtpu_active_devices",
    "Devices in the live training mesh (set at capture and on every "
    "restore topology check — the number elastic resumes reconcile "
    "checkpoints against).")
ELASTIC_RESHARD_MS = _m.histogram(
    "mxtpu_elastic_reshard_ms",
    "Wall time of one elastic topology adoption: checkpoint restore of "
    "the gathered state + N→M re-tile under the new mesh + provenance.",
    buckets=(5, 25, 100, 500, 1000, 5000, 15000, 60000))

# ------------------------------------------------------------- performance
MFU = _m.gauge(
    "mxtpu_mfu",
    "Live model-FLOPs utilization over the attribution window: the "
    "executable's cost-ledger FLOPs per step divided by (mean step cadence "
    "x per-chip peak FLOP/s x chips). Needs the cost ledger enabled "
    "(MXNET_PERF_LEDGER) and a known/overridden device peak.")
DEVICE_UTIL = _m.gauge(
    "mxtpu_device_util",
    "Fraction of recent steps whose previous result was still executing "
    "when the next dispatch completed — a lag-1 saturation probe: ~1.0 = "
    "device-bound pipeline, ~0.0 = the host/input path is the bottleneck.")
STEP_BREAKDOWN = _m.gauge(
    "mxtpu_step_breakdown_ms",
    "Rolling mean of the wall step cadence decomposed host-side, labeled "
    "bucket=dispatch|h2d_transfer|host_prep|feed_stall|host_other "
    "(semantics in docs/observability.md).")
COST_LEDGER_ROWS = _m.counter(
    "mxtpu_cost_ledger_rows_total",
    "Rows appended to the XLA cost ledger (MXNET_PERF_LEDGER).")
PERF_REGRESSIONS = _m.counter(
    "mxtpu_perf_regressions_total",
    "Perf-watchdog checks that found a metric past its regression "
    "threshold vs the baseline, labeled metric=.")
TUNER_TRIALS = _m.counter(
    "mxtpu_tuner_trials_total",
    "Autotuner trials scored, labeled provenance=predicted|measured|"
    "cached (cached = warm-start ledger hit: nothing re-lowered or "
    "re-run).")
TUNER_BEST_MFU = _m.gauge(
    "mxtpu_tuner_best_mfu",
    "MFU of the best measured candidate from the most recent tuner "
    "search (tuner.tune / tools/mxtune.py).")

# --------------------------------------------------------------- serving
SERVE_REQUESTS = _m.counter(
    "mxtpu_serve_requests_total",
    "Model-server requests by final outcome, labeled model= and "
    "outcome=ok|shed|expired|error (shed = typed admission/breaker/drain "
    "rejection, expired = deadline passed before dispatch — never sent "
    "to the device, error = executor fault after retries+isolation).")
SERVE_LATENCY = _m.histogram(
    "mxtpu_serve_latency_ms",
    "End-to-end latency of OK requests (submit to completed result), "
    "labeled model=. Rejected/expired requests are counted, not timed.",
    buckets=(1, 2.5, 5, 10, 25, 50, 100, 250, 500, 1000, 2500, 10000))
SERVE_BATCH = _m.histogram(
    "mxtpu_serve_batch_size",
    "Rows per dispatched batch BEFORE bucket padding, labeled model=. "
    "Persistently 1 under load = the assembly window is too short.",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512))
SERVE_QUEUE_DEPTH = _m.gauge(
    "mxtpu_serve_queue_depth",
    "Requests queued per model at last admission/dispatch, labeled "
    "model=. Pinned at the queue bound = shedding load.")
SERVE_HEDGES = _m.counter(
    "mxtpu_serve_hedges_total",
    "Hedged (duplicate tail-tolerance) dispatches, labeled model= and "
    "outcome=won|lost|budget_denied (won = the hedge completed the "
    "request first; lost = the primary beat it or the hedge errored — "
    "its result dropped; budget_denied = the retry budget refused to "
    "fund the hedge). won/submitted is the hedge hit rate; a high "
    "budget_denied rate means hedging wants more budget than the "
    "configured fraction allows.")
CHIP_QUARANTINES = _m.counter(
    "mxtpu_chip_quarantines_total",
    "Chips quarantined by the device sentinel after a device-fatal "
    "fault (serving/health.py), labeled reason= (device_lost|enqueue|"
    "data_loss|probe|other). Each quarantine triggers an automatic "
    "bucket-ladder re-plan onto the survivors.")
QUARANTINED_CHIPS = _m.gauge(
    "mxtpu_quarantined_chips",
    "Chips currently quarantined by the device sentinel (unlabeled). "
    "Nonzero = serving on reduced capacity; stuck nonzero past the "
    "cooldown = the half-open re-admission probe keeps failing.")
SERVE_DEGRADED_RUNG = _m.gauge(
    "mxtpu_serve_degraded_rung",
    "Current rung of the per-model degraded-mode ladder, labeled "
    "model=: 0 healthy, 1 reduced buckets (biggest dropped), 2 int8 "
    "tier fallback, 3 guaranteed-traffic-only admission, 4 static shed. "
    "Edge-triggered: transitions also land in the trace ring.")
RETRY_BUDGET_DENIED = _m.counter(
    "mxtpu_retry_budget_denied_total",
    "Retries or hedges refused because the shared retry budget (default "
    "~10% of admitted traffic) was exhausted, labeled model= and "
    "kind=retry|hedge. Denials fail fast and typed — a climbing counter "
    "under overload is the budget doing its job (no retry storm).")

# --------------------------------------------------------------- rollout
ROLLOUT_STAGE = _m.gauge(
    "mxtpu_rollout_stage",
    "Current ramp stage of the model's live rollout, labeled model=: "
    "0 shadow, 1/2/3 the 1%/10%/50% canary stages, 4 the 100% stage "
    "(left there once promoted), -1 rolled back/aborted. Transitions "
    "are edge-triggered and also land in the trace ring as 'rollout' "
    "events.")
ROLLOUT_ROLLBACKS = _m.counter(
    "mxtpu_rollout_rollbacks_total",
    "Automatic or operator rollbacks of a canary version, labeled "
    "reason= (breaker|error_rate|slo_burn|p99_delta|agreement|operator|"
    "abort). One bump per rollback transition, never per request — "
    "perfwatch treats a climbing count as a regression signal "
    "(down-is-good).")
ROLLOUT_SHADOW_AGREEMENT = _m.gauge(
    "mxtpu_rollout_shadow_agreement",
    "Rolling top-1 agreement between the canary's shadow answers and "
    "the incumbent's served answers, labeled model= (1.0 = identical "
    "argmax on every sampled request; the gate rolls back below "
    "MXNET_ROLLOUT_MIN_AGREEMENT). Same statistic the quant "
    "evaluate_agreement harness reports for int8 tiers.")
ROLLOUT_VERSION_REQUESTS = _m.counter(
    "mxtpu_rollout_version_requests_total",
    "Model-server requests attributed to a rollout version, labeled "
    "model=, version= and outcome= (same outcomes as "
    "mxtpu_serve_requests_total). The zero-downtime proof: a retired "
    "version's counters stop moving after the swap, and the per-version "
    "sum equals the model's total while a rollout is configured.")

# ----------------------------------------------------------------- fleet
FLEET_RESIZES = _m.counter(
    "mxtpu_fleet_resizes_total",
    "Fleet chip reallocations (serving/fleet.py FleetController), "
    "labeled direction=grow|shrink — one increment per model whose chip "
    "assignment changed (a reallocation pair bumps grow once and shrink "
    "once). Hysteresis (MXNET_FLEET_DWELL_S) bounds the rate; a counter "
    "climbing faster than one per dwell window per model is thrash.")
FLEET_ACTIVE_CHIPS = _m.gauge(
    "mxtpu_fleet_active_chips",
    "Chips currently assigned to each serving tenant, labeled model=. "
    "The fleet placement map in gauge form; sums to at most the fleet's "
    "total_chips budget.")
FLEET_PREEMPTED = _m.counter(
    "mxtpu_fleet_preempted_total",
    "Best-effort requests shed with typed Preempted (admission or queue "
    "eviction) because a guaranteed tenant was in an SLO excursion, "
    "labeled tenant=. Never silent: every preempted request's future "
    "completes with the typed error.")
FLEET_QUOTA_SHEDS = _m.counter(
    "mxtpu_fleet_quota_sheds_total",
    "Requests shed with typed QuotaExceeded at fleet admission because "
    "the tenant exceeded its declared QPS quota, labeled tenant=. "
    "Attributes overload to the tenant that over-drove, not to server "
    "capacity (which lands in mxtpu_serve_requests_total{outcome=shed}).")
FLEET_RESIZE_MS = _m.histogram(
    "mxtpu_fleet_resize_ms",
    "Wall time of one fleet resize: quiesce the replica's in-flight "
    "batch + re-bind the bucket executor ladder for the new chip count "
    "(params stay placed; buckets recompile lazily on next use).",
    buckets=(0.1, 0.5, 1, 2.5, 5, 10, 25, 50, 100, 250, 1000))

# --------------------------------------------------------------- tracing
TRACE_SPANS = _m.counter(
    "mxtpu_trace_spans_total",
    "Request-trace lifecycle spans recorded, labeled stage=admission|"
    "queue|assembly|dispatch|forward|respond and outcome=ok|shed|"
    "expired|error (every finished request emits its stage spans here "
    "regardless of tail-sampling — the sampler only gates ring "
    "retention, never counting).")
TRACE_RING_DEPTH = _m.gauge(
    "mxtpu_trace_ring_depth",
    "Retained traces in the bounded trace ring (MXNET_TRACE_RING). "
    "Pinned at capacity = the tail is evicting; read it with "
    "tools/mxtrace.py before it rolls.")
TRACE_DROPPED = _m.counter(
    "mxtpu_trace_dropped_total",
    "Finished traces not retained in the ring, labeled reason="
    "sampled_out (boring bulk below MXNET_TRACE_SAMPLE — error/shed/"
    "expired/violating/slow-tail traces are never sampled out) | "
    "evicted (ring at capacity, oldest rolled off).")
SLO_BURN = _m.gauge(
    "mxtpu_slo_burn_rate",
    "Rolling SLO error-budget burn rate, labeled model= and window="
    "fast|slow: the window's SLO-bad fraction divided by the error "
    "budget (1 - availability target). 1.0 = consuming budget exactly "
    "as fast as the target allows; crossing "
    "MXNET_SERVE_SLO_BURN_THRESHOLD on the fast window warns and bumps "
    "mxtpu_perf_regressions_total{metric='slo_burn_rate'}.")

# ------------------------------------------------------------ quantization
QUANT_CALIB_BATCHES = _m.counter(
    "mxtpu_quant_calib_batches_total",
    "Calibration batches streamed through quant.collect, labeled "
    "mode=naive|entropy.")
QUANT_NODES = _m.gauge(
    "mxtpu_quant_nodes",
    "Convolution/FullyConnected nodes rewritten to int8 islands by the "
    "most recent quantize_symbol run, labeled model=.")
QUANT_ACC_DELTA = _m.gauge(
    "mxtpu_quant_acc_delta",
    "fp32-minus-int8 top-1 accuracy delta of the last "
    "quant.evaluate_agreement run (positive = the int8 model lost "
    "accuracy; the flow's ~1% acceptance bar reads this number).")
QUANT_SERVE_REQUESTS = _m.counter(
    "mxtpu_quant_serve_requests_total",
    "Model-server requests answered by an int8-tier model, labeled "
    "model= and outcome= (same outcomes as mxtpu_serve_requests_total — "
    "the int8 slice of serving traffic).")

# ----------------------------------------------------------------- memory
HBM_BYTES_IN_USE = _m.gauge(
    "mxtpu_hbm_bytes_in_use",
    "Live HBM bytes in use per device at the last memwatch.poll_hbm "
    "sample, labeled device=. Real allocator numbers where the backend "
    "has memory_stats(); the synthetic live-set sum (registered state "
    "trees + chaos ballast) on backends without (CPU).")
HBM_PEAK_BYTES = _m.gauge(
    "mxtpu_hbm_peak_bytes",
    "High-watermark HBM bytes across devices (allocator peak_bytes_in_use "
    "where available; the running synthetic peak otherwise). The number "
    "placement budgets must stay above.")
HBM_LARGEST_ALLOC_BYTES = _m.gauge(
    "mxtpu_hbm_largest_alloc_bytes",
    "Largest single live allocation (allocator largest_alloc_size where "
    "available; the largest registered live set otherwise) — the "
    "fragmentation probe: an OOM with in_use well under the limit and "
    "this number large means fragmentation, not demand.")
OOM_TOTAL = _m.counter(
    "mxtpu_oom_total",
    "Device RESOURCE_EXHAUSTED failures classified at a dispatch "
    "boundary, labeled context=serving|trainer|restore. Every increment "
    "has a matching mxtpu_oom.json postmortem artifact.")
MEM_REFUSALS = _m.counter(
    "mxtpu_mem_refusals_total",
    "Memory-aware refusals instead of a device OOM, labeled reason="
    "no_memory (fleet grow/resize whose post-state would not fit the "
    "per-chip HBM budget) | load (ModelServer refused to load a model "
    "whose estimated footprint exceeds the remaining budget) | "
    "rollout (a canary version refused at load because it would not fit "
    "next to the resident versions — the incumbent keeps serving) | "
    "predicted_oom (tuner candidate skipped because its predicted "
    "footprint exceeds the budget).")

# -------------------------------------------------------------- callbacks
SPEEDOMETER_SPS = _m.gauge(
    "mxtpu_speedometer_samples_per_sec",
    "Speedometer throughput (same number as its log line).")
MONITOR_STAT = _m.gauge(
    "mxtpu_monitor_stat",
    "Monitor layer statistics, labeled stat= (the Monitor.toc stream).")

# --------------------------------------------------------------- lockwatch
LOCK_HOLD_MS = _m.histogram(
    "mxtpu_lock_hold_ms",
    "Wall time a lockwatch-instrumented lock was held, labeled site= "
    "(the class-wide lock name, e.g. serving.queueing."
    "BoundedRequestQueue._lock). Only populated under MXNET_LOCKCHECK=1 "
    "— host-side lock telemetry never enters the XLA trace.",
    buckets=(0.01, 0.05, 0.1, 0.5, 1, 5, 10, 50, 100, 500, 1000))
LOCK_CONTENTION = _m.counter(
    "mxtpu_lock_contention_total",
    "Contended acquisitions of a lockwatch-instrumented lock (the "
    "uncontended fast path failed and the thread had to block), labeled "
    "site=. Only populated under MXNET_LOCKCHECK=1.")
LOCKWATCH_FINDINGS = _m.counter(
    "mxtpu_lockwatch_findings_total",
    "Deadlock-hazard findings raised by the runtime lock sanitizer, "
    "labeled rule=MXL-C300 (order inversion seen live) | MXL-C303 "
    "(re-entrant acquire of a non-reentrant lock). Any nonzero value "
    "is a bug report: tools/mxrace.py report pretty-prints the stacks.")
