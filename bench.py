#!/usr/bin/env python
"""Benchmark driver: ResNet-50 training throughput on the attached chip.

One process, no child: ``python bench.py`` runs :func:`run_bench`,
``python bench.py --multichip`` runs :func:`run_multichip`. Either fails
(non-zero exit, no metric line) when JAX finds no accelerator, and a
diagnostic phase that fails fails the run — a number is printed only when it
was measured here, on the device named in its row.

Prints JSON lines {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
(the LAST line is the full row) plus diagnostic fields (mfu, flops_per_step,
device_kind, platform, n_chips).

Baseline: the reference's headline ResNet-50 ImageNet training number —
109 img/s on 1x K80 at batch 32 (reference example/image-classification/
README.md:149-156, recorded in BASELINE.md).

The training step is the fused SPMD path (parallel.DataParallelTrainer):
forward+backward+update in one jitted XLA computation, bfloat16 compute with
float32 params/accumulation.
"""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

BASELINE_IMG_S = 109.0  # reference ResNet-50, 1x K80, batch 32


def _peak_flops(device_kind):
    """Per-chip bf16 peak FLOP/s — single source of truth is the perf
    layer's device table (observability/xcost.py, shared with the live MFU
    gauge and the roofline classifier)."""
    from mxnet_tpu.observability.xcost import peak_flops
    return peak_flops(device_kind)


def _require_chip():
    """The devices to measure on. Exits non-zero, printing no metric line,
    when JAX finds no accelerator: a CPU timing is never a benchmark row."""
    import jax
    from mxnet_tpu.base import enable_compile_cache
    print("compile cache: %s" % enable_compile_cache(), file=sys.stderr)
    devices = jax.devices()
    if devices[0].platform == "cpu":
        sys.exit("bench.py: no accelerator found (jax.devices() is %d x %s); "
                 "refusing to measure on the host CPU"
                 % (len(devices), devices[0].device_kind))
    return devices


def run_bench():
    devices = _require_chip()

    import jax
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import vision

    # batch 256 saturates the MXU far better than the reference's 32;
    # per-image math is batch-invariant
    batch = int(os.environ.get("BENCH_BATCH", 256))
    image = int(os.environ.get("BENCH_IMAGE", 224))
    steps = int(os.environ.get("BENCH_STEPS", 30))
    warmup = int(os.environ.get("BENCH_WARMUP", 5))

    # channel-last is the TPU-preferred layout (convs lower to the MXU
    # without layout transposes); overridable for A/B via BENCH_LAYOUT
    layout = os.environ.get("BENCH_LAYOUT", "NHWC")

    np.random.seed(0)
    mx.random.seed(0)   # initializers draw from the framework host stream
    # BENCH_S2D=1 enables the space-to-depth stem (exact 7x7/s2
    # reparameterization, tests/test_s2d_stem.py) — NHWC only
    s2d = os.environ.get("BENCH_S2D") == "1" and layout == "NHWC"
    # BENCH_PASSES=1 measures the graph-pass pipeline INSTEAD of the hand
    # flags: the net is built plain NCHW (like `mxtune --route passes`)
    # and the default pipeline applies layout/s2d as rewrites over the
    # channel-last feed — never both hand flags AND passes, so the row's
    # declared lever config always matches the measured program. Either
    # way the emitted row stamps the provenance.
    bench_passes = os.environ.get("BENCH_PASSES") == "1"
    if bench_passes:
        from mxnet_tpu.passes import PassManager
        net = vision.resnet50_v1(classes=1000)
        trainer_passes = PassManager(None, input_layout="NHWC")
        layout, s2d = "NHWC", False   # the pipeline decides s2d; the
        #                               passes provenance field records it
    else:
        net = vision.resnet50_v1(classes=1000, layout=layout, stem_s2d=s2d)
        trainer_passes = False
    net.initialize(mx.init.Xavier())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = parallel.DataParallelTrainer(
        net, loss_fn, "sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4},
        compute_dtype="bfloat16", passes=trainer_passes)

    shape = (batch, image, image, 3) if layout == "NHWC" \
        else (batch, 3, image, image)
    x = np.random.uniform(-1, 1, shape).astype("float32")
    y = np.random.randint(0, 1000, (batch,)).astype("float32")

    # the synthetic batch is staged on the device once, BEFORE warmup
    # (reference benchmark_score.py measures with synthetic device-resident
    # data too); the feed path is the overlap diagnostic's job below
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = NamedSharding(trainer.mesh, P("dp"))
    t_compile = time.perf_counter()
    loss = trainer.step(x, y)
    float(loss)
    compile_s = time.perf_counter() - t_compile
    print("first step (compile) took %.1fs" % compile_s, file=sys.stderr,
          flush=True)
    xd = jax.device_put(x, spec)
    yd = jax.device_put(y, spec)
    for _ in range(warmup):
        loss = trainer.step(xd, yd)
    float(loss)  # sync

    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step(xd, yd)
    float(loss)  # sync
    dt = time.perf_counter() - t0
    img_per_sec = steps * batch / dt

    n_chips = len(devices)
    per_chip = img_per_sec / n_chips
    device_kind = devices[0].device_kind

    out = {
        "metric": "resnet50_train_throughput_per_chip",
        "value": round(per_chip, 2),
        "unit": "img/s/chip",
        "vs_baseline": round(per_chip / BASELINE_IMG_S, 3),
        "batch": batch, "image": image, "steps": steps,
        "compile_s": round(compile_s, 1),
        "layout": layout + ("+s2d" if s2d else ""),
        "n_chips": n_chips, "device_kind": device_kind,
        "platform": devices[0].platform,
        # graph-pass provenance: which rewrite passes (and rewrite counts)
        # produced this step — perfwatch baselines must be attributable to
        # their lever configuration, hand flags and passes alike
        "passes": trainer.passes_provenance(),
    }

    # ---- MFU from the compiled step's own cost analysis -------------------
    # BOTH FLOP counts are recorded — XLA's, and the analytic ResNet-50
    # estimate (fwd ~= 4.1 GFLOP/image at 224^2, 2 FLOPs/MAC, bwd ~= 2x fwd
    # => ~12.3 GFLOP/image, conv FLOPs ~ HW) — and their disagreement is an
    # explicit row field, never a silent preference (ROADMAP S3 settles
    # which one MFU may be quoted from)
    flops_analytic = 12.3e9 * (image / 224.0) ** 2 * batch
    lowered = trainer._step_fn.lower(
        trainer._params, trainer._aux, trainer._opt_state,
        trainer._guard_state, jax.random.PRNGKey(0), xd, yd)
    ca = lowered.compile().cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    flops_xla = float(ca["flops"])
    peak = _peak_flops(device_kind)
    if peak is None:
        sys.exit("bench.py: device kind %r is not in xcost.DEVICE_PEAKS"
                 % device_kind)
    out["flops_per_step"] = flops_xla
    out["flops_source"] = "xla_cost_analysis"
    out["flops_per_step_analytic"] = flops_analytic
    out["flops_source_disagreement_pct"] = round(
        (flops_xla - flops_analytic) / flops_analytic * 100.0, 1)
    mfu = flops_xla * (steps / dt) / (peak * n_chips)
    out["mfu"] = round(mfu, 4)
    out["peak_flops_assumed"] = peak

    # ---- tuner provenance: when the autotuner cache holds a best measured
    # config for this device kind, stamp it into the row so the history
    # records which levers produced the number (and whether this window ran
    # them). tools/mxtune.py writes the cache.
    from mxnet_tpu.tuner import best_cached
    # model- AND topology-filtered: a cache row from another model
    # (an mxtune --model tiny smoke) or another chip count must never
    # masquerade as provenance for THIS window's configuration
    tuned = best_cached(device_kind=device_kind, model="resnet50",
                        n_devices=n_chips)
    if tuned is not None:
        out["tuned_config"] = tuned.get("tuner_config")
        if tuned.get("throughput_img_s_per_chip"):
            out["tuned_img_s_per_chip"] = round(
                float(tuned["throughput_img_s_per_chip"]), 1)

    # ---- cost-ledger row: the bench window is also a compile-time cost
    # capture — the same append-only ledger the trainer's perf layer and
    # the autotuner read (observability/xcost.py)
    from mxnet_tpu.observability import xcost
    row = xcost.analyze_cost(ca, device_kind=device_kind, n_devices=n_chips)
    row.update({
        "label": "bench.resnet50",
        "fingerprint": trainer._lowered_digest(lowered),
        "platform": devices[0].platform,
        "batch": batch, "image": image, "layout": out["layout"],
        "throughput_img_s_per_chip": per_chip,
        "measured_step_ms": 1e3 * dt / steps,
        "mfu": mfu,
    })
    ledger_path = os.environ.get("MXNET_PERF_LEDGER") or \
        os.path.join(HERE, "mxtpu_cost_ledger.jsonl")
    xcost.CostLedger(ledger_path).append(row)
    out["cost_ledger"] = ledger_path

    # ---- input-overlap diagnostic: batches fed host->device DURING compute
    # via the async device feed (reference PrefetcherIter overlap,
    # src/io/iter_prefetcher.h:1). uint8 on the wire + on-device rescale =
    # the reference's uint8-record pipeline (4x fewer bytes than f32).
    if os.environ.get("BENCH_OVERLAP", "1") == "1":
        import jax.numpy as jnp
        from mxnet_tpu.io import prefetch_to_device

        xu8 = np.random.randint(0, 256, shape).astype("uint8")

        @jax.jit
        def rescale(a):
            return a.astype(jnp.float32) * (2.0 / 255.0) - 1.0

        # pure-wire probe: one synchronous staged batch
        t0 = time.perf_counter()
        jax.block_until_ready(jax.device_put(xu8, spec))
        wire_s = time.perf_counter() - t0
        wire_mbs = xu8.nbytes / wire_s / 1e6
        # per-chip so it compares unit-for-unit with per_chip/ov below
        wire_limit = batch / wire_s / n_chips

        n_feed = 10

        def src():
            for _ in range(n_feed):
                yield (xu8, y)

        it = prefetch_to_device(src(), sharding=spec, depth=2)
        xb, yb = next(it)           # pipeline fill
        loss = trainer.step(rescale(xb), yb)
        t0 = time.perf_counter()
        n_done = 0
        for xb, yb in it:
            loss = trainer.step(rescale(xb), yb)
            n_done += 1
        float(loss)
        ov = n_done * batch / (time.perf_counter() - t0) / n_chips
        out["overlapped_img_s_per_chip"] = round(ov, 2)
        out["overlap_wire_MBps"] = round(wire_mbs, 1)
        out["overlap_efficiency_vs_bound"] = round(
            ov / min(per_chip, wire_limit), 3)
        out["overlapped_note"] = (
            "wire-bound (uint8 wire %.0f MB/s caps feed at %.0f "
            "img/s/chip)" % (wire_mbs, wire_limit)
            if wire_limit < per_chip else "compute-bound")

    # ---- int8 inference diagnostic row ------------------------------------
    if os.environ.get("BENCH_INT8", "1") == "1":
        from mxnet_tpu.contrib.quantization import quantized_resnet_bench
        int8_row = quantized_resnet_bench(net, xd, steps=min(steps, 20))
        out.update(int8_row)
        # the same numbers as a label="quant" ledger row, so the tuner
        # cache / mxlint MXL-T215 / perfwatch see on-chip int8 evidence
        from mxnet_tpu.tuner import get_cache
        i8 = int8_row.get("int8_infer_img_s_per_chip")
        bf = int8_row.get("bf16_infer_img_s_per_chip")
        get_cache().append({
            "label": "quant", "model": "resnet50",
            "net_class": type(net).__name__, "batch": batch,
            "int8_img_s_per_chip": i8, "bf16_img_s_per_chip": bf,
            "int8_ms": round(batch / i8 * 1e3, 4) if i8 else None,
            # the non-quantized baseline here is the bench's bf16
            # run (what the f32 tier actually costs on-chip) —
            # baseline_dtype says so, readers must not report the
            # number as a true-f32 measurement
            "f32_ms": round(batch / bf * 1e3, 4) if bf else None,
            "baseline_dtype": "bf16",
            "int8_vs_f32": int8_row.get("int8_vs_bf16"),
            "device_kind": device_kind,
            "platform": devices[0].platform,
            "provenance": "bench",
        })

    print(json.dumps(out), flush=True)


# --------------------------------------------------------------------------
# Multichip mode: a scaling-efficiency row (img/s/chip at N devices vs 1).
# The measurement itself lives in mxnet_tpu/parallel/collbench.py
# (scaling_row) so the dryrun harness and tests share it; this mode is the
# driver around it, plus a collectives bandwidth mini-sweep for the row's
# context. Knobs: BENCH_MC_MODEL=tiny|resnet50, BENCH_MC_BATCH (per chip),
# BENCH_MC_IMAGE, BENCH_MC_STEPS, BENCH_GRAD_REDUCE, BENCH_REDUCE_DTYPE.
# --------------------------------------------------------------------------
def run_multichip():
    devices = _require_chip()
    from mxnet_tpu.parallel import collbench

    model = os.environ.get("BENCH_MC_MODEL", "resnet50")
    if model not in ("tiny", "resnet50"):
        # an unknown knob value must not stamp false model provenance into
        # the row while silently measuring the tiny default net
        print("BENCH_MC_MODEL must be tiny|resnet50, got %r" % model,
              file=sys.stderr)
        return 2
    batch = int(os.environ.get("BENCH_MC_BATCH", 32))
    image = int(os.environ.get("BENCH_MC_IMAGE", 224))
    steps = int(os.environ.get("BENCH_MC_STEPS", 10))
    grad_reduce = os.environ.get("BENCH_GRAD_REDUCE", "reduce_scatter")
    reduce_dtype = os.environ.get("BENCH_REDUCE_DTYPE") or None

    builder = None
    if model == "resnet50":
        def builder(prefix, classes):
            import mxnet_tpu as mx
            from mxnet_tpu import gluon
            from mxnet_tpu.gluon.model_zoo import vision
            mx.random.seed(0)
            net = vision.resnet50_v1(classes=classes, prefix=prefix)
            net.initialize(mx.init.Xavier())
            return net, gluon.loss.SoftmaxCrossEntropyLoss()

    # provenance decided BEFORE the measurement so the ledger-persisted
    # row and the printed row are identical (model-filtered readers must
    # never see a ledger row missing the identity fields)
    extra = {"model": model,
             "provenance": "live multichip run at %s" % time.strftime(
                 "%Y-%m-%dT%H:%MZ", time.gmtime())}
    row = collbench.scaling_row(
        batch_per_chip=batch, image=image, steps=steps,
        grad_reduce=grad_reduce, grad_reduce_dtype=reduce_dtype,
        builder=builder, extra=extra)
    print(json.dumps(row), flush=True)
    # context: a small collectives sweep at the same device count, so the
    # efficiency number ships next to the bytes/sec curve explaining it
    if os.environ.get("BENCH_MC_COLLECTIVES", "1") == "1":
        collbench.run(device_counts=(len(devices),),
                      payload_sizes=(1 << 20,),
                      steps=max(3, steps // 2), warmup=1,
                      compression=0.5,
                      emit=lambda r: print(json.dumps(r), flush=True))
    return 0


if __name__ == "__main__":
    if "--multichip" in sys.argv:
        sys.exit(run_multichip())
    run_bench()
