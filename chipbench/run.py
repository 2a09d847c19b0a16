"""One run of one cell:

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON object as the last line of standard output; exits non-zero
and prints none without a TPU. See chipbench/README.md.
"""
import time
T_START = time.perf_counter()   # set-up runs from process start

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import check, harness, trace, traffic  # noqa: E402


def run(argv, bench_path=None, root=ROOT, require_chip=harness.require_chip):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = harness.load_json(bench_path or os.path.join(root, "BENCHMARK.json"))
    cell, cfg, mix, limits, ref = harness.find_cell(bench, args.workload, root)
    chips = cell["chips"]

    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    devices, peaks = require_chip(chips)
    harness.enable_cache()
    stage = lambda what: harness.stderr(  # noqa: E731
        "chipbench: %7.1f s  %s" % (time.perf_counter() - T_START, what))
    stage("chip found, building the program")
    from mxnet_tpu.observability import jit_hooks

    # ---- set-up: program, inputs, the followed first steps (which compile
    # and warm the one shape the cell uses), warm-up
    n_rows = cfg["batch_per_chip"] * chips
    n_items = n_rows * cfg.get("items_per_row", 1)   # what the rate counts
    net, trainer, mesh, trainable = harness.build_program(
        cfg, ref, args.seed, devices)
    sharding = NamedSharding(mesh, PartitionSpec("dp"))
    stage("program built, making the pool")
    pool = traffic.make_pool(mix, cfg, args.seed, n_rows, sharding)
    feed = traffic.make_feed(mix, pool, sharding)
    steps = mix["followed_steps"]
    stage("following the first %d steps (the first compiles)" % steps)
    prog = harness.follow_program(net, trainer, feed, trainable,
                                  cfg["optimizer"]["learning_rate"], steps,
                                  cfg.get("weight_layout"))
    stage("followed, warming up")
    for _ in range(mix["warmup_steps"]):
        loss = trainer.step(*feed.next())
    float(loss)
    compiles_setup = int(jit_hooks.JIT_COMPILES.value() or 0)
    setup_s = time.perf_counter() - T_START

    # ---- the measured window, then (traced runs) a short traced tail
    stage("window of %g s" % args.seconds)
    spans = harness.Spans()
    win = harness.drive(trainer, feed, args.seconds, mix["in_flight"], spans)
    compiles_window = int(jit_hooks.JIT_COMPILES.value() or 0) - compiles_setup
    reduced, window_s = None, None
    if args.trace:
        stage("traced tail")
        path, window_s, tmp = harness.traced_tail(
            trainer, feed, mix["in_flight"], harness.Spans())
        if path:
            reduced = trace.reduce(trace.read(path), spans=harness.PROGRAM_SPANS)
        shutil.rmtree(tmp, ignore_errors=True)
    peak = harness.memory_peak_bytes(devices)

    # ---- free the program, then the plain reference over the same steps
    feed.close()
    del trainer, net, feed, pool, loss
    gc.collect()
    jax.clear_caches()   # the step's executable holds its 9 GB of scratch
    stage("program freed, following the reference")
    t_ref = time.perf_counter()
    refd = harness.follow_reference(cfg, mix, ref, args.seed, n_rows,
                                    sharding, steps)
    nums = check.numbers(prog, refd)
    rows, correct = check.judge(nums, limits)
    reference_s = time.perf_counter() - t_ref

    run_ = {"cfg": cfg, "mix": mix, "chips": chips, "peaks": peaks,
            "window": win, "n_rows": n_rows, "n_items": n_items,
            "spans": spans.seconds,
            "compiles_window": compiles_window, "compiles_setup": compiles_setup,
            "trace": reduced, "traced_window_s": window_s,
            "flops_per_item": ref.train_flops_per_item(cfg),
            "memory_peak_bytes": peak}
    rate = win["steps"] * n_items / win["seconds"] / chips
    if args.trace:
        metrics = {}
        specs = {m["name"]: m for m in bench["per_layer"]}
        for name, read in harness.metric_readers(bench, args.workload, root).items():
            value = read(run_)
            if value is not None:
                metrics[name] = {"value": value, "unit": specs[name]["unit"]}
    else:
        metrics = {
            "setup_s": {"value": setup_s, "unit": "s"},
            "train.items_per_s_per_chip": {"value": rate, "unit": "items/s/chip"},
            "train.step_ms_p95": {
                "value": 1e3 * harness.percentile(win["step_s"], 0.95), "unit": "ms"}}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": win["steps"],
              "failed": win["failed"], "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = window_s
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    slow = max(range(win["steps"]), key=win["step_s"].__getitem__)
    result["info"] = {"workload": args.workload, "seed": args.seed,
                      "steps": win["steps"], "window_s": win["seconds"],
                      # a rate that reads far off: one stall (and when in the
                      # window), or every step slow?
                      "longest_step_ms": 1e3 * win["step_s"][slow],
                      "longest_step_ends_s": sum(win["step_s"][:slow + 1]),
                      "items_per_s_per_chip": rate, "setup_s": setup_s,
                      "reference_s": reference_s, "program": prog["loss"],
                      "reference": refd["loss"],
                      "recorded": {k: v for k, v in nums.items() if k not in limits}}
    result["compared"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    harness.stderr("chipbench: %s seed %d: %d steps in %.3f s, set-up %.1f s, "
                   "reference %.1f s" % (args.workload, args.seed, win["steps"],
                                         win["seconds"], setup_s, reference_s))
    for k, v, lim in rows:
        harness.stderr("compared %-20s %.6g  limit %.6g  %s"
                       % (k, v, lim, "ok" if v <= lim else "OVER"))
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    run(sys.argv[1:])
