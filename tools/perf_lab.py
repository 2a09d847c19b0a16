#!/usr/bin/env python
"""Perf lab for the ResNet-50 north star (BASELINE.json: >=3000 img/s/chip,
MFU >= 0.20 on one chip).

Runs a ladder of training-step variants in ONE process (one process holds
the chip) and prints one JSON line per variant:

    python tools/perf_lab.py                  # default ladder
    PERF_VARIANTS="NHWC:512,NHWC:1024" python tools/perf_lab.py
    PERF_VARIANTS=seed python tools/perf_lab.py   # the staged seed ladder

Also dumps the compiled HLO of the last variant to /tmp/perf_lab_hlo.txt
and greps it for un-fused transposes/converts so BN/ReLU fusion claims are
backed by the compiler's own output, not guesswork.

This is a thin CLI: the trial machinery lives in ``mxnet_tpu/tuner/
ladder.py`` (variants as data, build/measure/report functions) where the
autotuner (``tools/mxtune.py``) shares it.
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)


def main():
    import jax
    from mxnet_tpu.base import enable_compile_cache
    from mxnet_tpu.tuner import ladder

    enable_compile_cache()

    devices = jax.devices()
    on_accel = any(d.platform != "cpu" for d in devices)
    kind = devices[0].device_kind
    print(f"# devices: {len(devices)} x {kind}", file=sys.stderr, flush=True)

    spec_env = os.environ.get("PERF_VARIANTS", ladder.DEFAULT_VARIANTS)
    if spec_env.strip().lower() == "seed":
        spec_env = ladder.SEED_VARIANTS
    variants = ladder.parse_variants(spec_env)

    steps = int(os.environ.get("PERF_STEPS", 30))
    warmup = int(os.environ.get("PERF_WARMUP", 5))
    image = int(os.environ.get("PERF_IMAGE", 224))

    def emit(doc):
        print(json.dumps(doc), flush=True)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    _, last = ladder.run_ladder(variants, steps=steps, warmup=warmup,
                                image=image, on_accel=on_accel,
                                emit=emit, log=log)
    if last is None:
        return
    trainer, xd, yd, layout, batch = last

    # ---- on-chip profile: where does the step actually spend time? --------
    if os.environ.get("PERF_PROFILE", "0") == "1":
        try:
            emit(ladder.profile_step(trainer, xd, yd))
        except Exception as e:
            emit({"profile_error": repr(e)[:300]})

    # ---- fusion audit over the compiled HLO -------------------------------
    try:
        emit(ladder.hlo_audit(trainer, xd, yd))
    except Exception as e:
        emit({"hlo_audit_error": repr(e)[:300]})


if __name__ == "__main__":
    main()
