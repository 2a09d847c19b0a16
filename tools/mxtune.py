#!/usr/bin/env python
"""mxtune — cost-model-guided autotuner CLI (mxnet_tpu.tuner).

Searches the training-step config space (batch, layout, remat, donation,
prefetch depth — and the comm levers grad_reduce / grad_reduce_dtype /
bucket_bytes) with the predict-then-measure loop: every candidate's step
is lowered and scored through the XLA-cost roofline model (plus a learned
correction once measured rows exist), only the top-K predictions are
actually run, and every trial lands in the warm-start ledger cache
(``MXNET_TUNER_CACHE``, CostLedger JSONL) so repeat searches re-lower
nothing.

Usage::

    python tools/mxtune.py --model resnet50 --seed-ladder        # live chip
    python tools/mxtune.py --model resnet50 \\
        --space "batch=256,512;layout=NHWC,NCHW;remat=none,full"
    python tools/mxtune.py --model tiny --space "batch=8,64" \\
        --steps 2 --warmup 1 --cache /tmp/cache.jsonl            # CPU box
    python tools/mxtune.py ... --predict-only --format json
    python tools/mxtune.py ... --emit-best best_row.json         # perfwatch
                                                                 # baseline

On CPU-only boxes the predictor/ranking/cache paths are fully exercisable:
pin synthetic peaks via MXNET_PERF_PEAK_FLOPS / MXNET_PERF_PEAK_HBM_GBPS
(the CPU backend is not in the device table).

Exit codes (mxlint convention): 0 = tuned (the best config beats the
space's baseline candidate on a like-for-like basis), 1 = no improvement
found (the baseline IS the best known config), 2 = cannot run (bad space/
model, no scorable candidate, backend without peaks in predict-only mode).
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)


def _build_fns(args):
    """(build, data, default_space) for the chosen --model."""
    import numpy as np

    if args.model == "resnet50":
        def build(cand):
            import mxnet_tpu as mx
            from mxnet_tpu import gluon
            from mxnet_tpu.gluon.model_zoo import vision
            np.random.seed(0)
            mx.random.seed(0)
            if args.route == "passes":
                # the layout dimension applies as graph passes over ONE
                # NCHW-built net (Candidate.passes_manager): bitwise the
                # same HLO as the hand-flagged net, no per-candidate net
                # zoo variants
                net = vision.resnet50_v1(classes=args.classes)
            else:
                net = vision.resnet50_v1(classes=args.classes,
                                         layout=cand.layout)
            net.initialize(mx.init.Xavier())
            return net, gluon.loss.SoftmaxCrossEntropyLoss()

        def data(cand):
            rng = np.random.RandomState(0)
            x = rng.uniform(-1, 1, cand.data_shape(args.image)) \
                .astype("float32")
            y = rng.randint(0, args.classes, (cand.batch,)) \
                .astype("float32")
            return x, y

        from mxnet_tpu.tuner import SearchSpace
        default_space = SearchSpace(batch=(256, 512),
                                    layout=("NHWC", "NCHW"),
                                    remat=(None, "full"))
        return build, data, default_space

    if args.model == "tiny":
        # a small MLP: exercises the full predict->measure->cache loop in
        # seconds on the CPU backend (layout is a no-op for 2-D data)
        def build(cand):
            import mxnet_tpu as mx
            from mxnet_tpu import gluon
            from mxnet_tpu.gluon import nn
            mx.random.seed(0)
            pfx = "mxtune_b%d_" % cand.batch
            net = nn.HybridSequential(prefix=pfx)
            net.add(nn.Dense(64, activation="relu", prefix=pfx + "d0_"),
                    nn.Dense(args.classes, prefix=pfx + "d1_"))
            net.initialize(mx.init.Xavier())
            return net, gluon.loss.SoftmaxCrossEntropyLoss()

        def data(cand):
            rng = np.random.RandomState(0)
            x = rng.randn(cand.batch, 32).astype("float32")
            y = rng.randint(0, args.classes, (cand.batch,)) \
                .astype("float32")
            return x, y

        from mxnet_tpu.tuner import SearchSpace
        default_space = SearchSpace(batch=(8, 64), layout=("NCHW",))
        return build, data, default_space

    raise ValueError("unknown --model %r (want resnet50|tiny)" % args.model)


def _common_basis(best, base):
    """Compare two trials on their strongest COMMON basis: measured vs
    measured when both ran, predicted vs predicted otherwise. Mixing the
    optimistic roofline with a wall-clock measurement would declare false
    regressions/improvements."""
    if best.measured and base.measured:
        return best.throughput or 0.0, base.throughput or 0.0, "measured"
    return (best.predicted_img_s or 0.0,
            base.predicted_img_s or 0.0, "predicted")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="search (batch, layout, remat, donation, prefetch, "
                    "grad_reduce, grad_reduce_dtype, bucket_bytes) "
                    "with the cost-model-guided autotuner")
    ap.add_argument("--model", default="resnet50",
                    help="resnet50 (the bench north star) or tiny "
                         "(CPU-fast MLP smoke)")
    ap.add_argument("--space", default=None,
                    help="search space, e.g. 'batch=256,512;layout=NHWC;"
                         "remat=none,full;grad_reduce=all_reduce,"
                         "reduce_scatter;grad_reduce_dtype=none,bf16;"
                         "bucket_bytes=none,4194304'")
    ap.add_argument("--seed-ladder", action="store_true",
                    help="search the staged bench ladder variants "
                         "(RMT:512, NHWC:512, NCHW:256) instead "
                         "of a cross-product space")
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--classes", type=int, default=1000)
    ap.add_argument("--steps", type=int, default=None,
                    help="timed steps per measured trial "
                         "(MXNET_TUNER_STEPS)")
    ap.add_argument("--warmup", type=int, default=None)
    ap.add_argument("--top-k", type=int, default=None,
                    help="measured-candidate budget (MXNET_TUNER_TOP_K)")
    ap.add_argument("--predict-only", action="store_true",
                    help="rank by the cost model only; never dispatch a "
                         "timed trial")
    ap.add_argument("--feed", action="store_true",
                    help="measure through the async device feed at each "
                         "candidate's prefetch depth (the only mode in "
                         "which the prefetch dimension differentiates; "
                         "default stages data device-resident like "
                         "perf_lab)")
    ap.add_argument("--route", choices=("passes", "flags"), default="passes",
                    help="how layout candidates apply: 'passes' (the "
                         "default) rewrites one NCHW-built net through the "
                         "graph-pass pipeline — bitwise-identical HLO to "
                         "'flags', which builds hand-flagged net variants")
    ap.add_argument("--cache", default=None,
                    help="trial ledger path (MXNET_TUNER_CACHE)")
    ap.add_argument("--compute-dtype", default=None,
                    help="override trial compute dtype (default: bfloat16 "
                         "on accelerators, none on cpu)")
    ap.add_argument("--min-gain-pct", type=float, default=0.0,
                    help="best must beat the baseline candidate by this "
                         "margin to count as tuned (exit 0)")
    ap.add_argument("--emit-best", default=None, metavar="PATH",
                    help="write the best trial's ledger row as one JSON "
                         "file (a perfwatch --baseline artifact)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    args = ap.parse_args(argv)

    try:
        from mxnet_tpu.base import MXNetError
        from mxnet_tpu import tuner as T
    except Exception as e:
        sys.stderr.write("mxtune: cannot import mxnet_tpu: %r\n" % e)
        return 2

    try:
        build, data, space = _build_fns(args)
        if args.space:
            space = T.SearchSpace.from_spec(args.space)
        candidates = None
        if args.seed_ladder:
            candidates = [T.VariantSpec.parse(tok).to_candidate()
                          for tok in T.SEED_VARIANTS.split(",")]
    except (MXNetError, ValueError) as e:
        sys.stderr.write("mxtune: %s\n" % e)
        return 2

    import jax
    on_accel = any(d.platform != "cpu" for d in jax.devices())
    if on_accel:
        from mxnet_tpu.base import enable_compile_cache
        enable_compile_cache()
    compute_dtype = args.compute_dtype or ("bfloat16" if on_accel else None)

    try:
        result = T.tune(
            build, data, space, candidates=candidates,
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                              "wd": 1e-4},
            compute_dtype=compute_dtype,
            top_k=args.top_k,
            measure=False if args.predict_only else None,
            steps=args.steps, warmup=args.warmup,
            ledger=args.cache, model=args.model, feed=args.feed,
            via_passes=(args.route == "passes"))
    except MXNetError as e:
        sys.stderr.write("mxtune: %s\n" % e)
        return 2
    if result.best is None:
        sys.stderr.write("mxtune: no candidate survived the search\n")
        return 2

    # baseline = the first candidate of the space/ladder (what a user who
    # sets no levers runs); improvement judged on a like-for-like basis
    base_cand = (candidates[0] if candidates
                 else space.baseline())
    base_trial = next((t for t in result.trials
                       if t.candidate == base_cand and t.error is None),
                      None)
    improved, basis, gain_pct = False, "predicted", None
    if base_trial is None:
        improved = True          # baseline itself unusable: anything wins
        basis = "baseline-failed"
    elif result.best.candidate != base_cand:
        b, s, basis = _common_basis(result.best, base_trial)
        if s > 0:
            gain_pct = (b - s) / s * 100.0
            improved = gain_pct > args.min_gain_pct

    report = result.report()
    report["baseline"] = base_cand.as_dict()
    report["improved"] = improved
    report["basis"] = basis
    if gain_pct is not None:
        report["gain_pct"] = round(gain_pct, 2)

    if args.emit_best:
        row = result.best.cost_row
        if row and row.get("measured_step_ms"):
            with open(args.emit_best, "w") as f:
                json.dump(row, f)
            report["emitted_best"] = args.emit_best
        else:
            # a predicted-only row must NOT become a perfwatch baseline:
            # its optimal-roof step_ms is a physical floor no measured run
            # can reach, so every healthy run would read as a regression
            sys.stderr.write(
                "mxtune: --emit-best skipped: the best trial has no "
                "measured facts (predict-only / unmeasured) — a roofline "
                "row is not a wall-clock baseline\n")

    if args.format == "json":
        print(json.dumps(report, indent=1, sort_keys=True))
    else:
        print("mxtune: %s on %s — %d candidate(s), cache %s"
              % (args.model, result.device_kind, len(result.trials),
                 T.cache_path() if args.cache is None else args.cache))
        for t in result.ranked():
            if t.error:
                print("  %-28s ERROR %s" % (t.candidate.label, t.error))
                continue
            meas = ("%8.1f img/s/chip measured" % t.throughput
                    if t.throughput else "   (unmeasured)")
            print("  %-28s %-9s predicted %8.2f ms%s"
                  % (t.candidate.label, t.provenance,
                     t.predicted_ms or float("nan"), " | " + meas))
        best = result.best
        gain = (" (+%.1f%% vs baseline %s, %s basis)"
                % (gain_pct, base_cand.label, basis)
                if gain_pct is not None else "")
        print("best: %s [%s]%s" % (best.candidate.label, best.provenance,
                                   gain))
        if best.mfu:
            print("best mfu: %.4f" % best.mfu)
    return 0 if improved else 1


if __name__ == "__main__":
    sys.exit(main())
