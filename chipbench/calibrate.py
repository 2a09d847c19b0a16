"""Readings that the limits in ``limits/<workload>.json`` are set from, and
the witnesses PERF.md cites (run by hand on the chip, never by a benchmark
run):

    python3 chipbench/calibrate.py --workload <name> --seeds 12 --controls 3 \
        --faults 3 --witnesses 2 [--variant passes_off='{"trainer_kwargs": {"passes": false}}']

ONE process, in phases, so that each compiled program is built once and only
one is loaded at a time: (1) the program's first steps on every seed, and on
the first ``--variant-seeds`` seeds each ``--variant`` (the configuration
with some keys replaced: a witness such as ``passes`` off); (2) the plain
reference on every seed; (3) on the first seeds the control (the reference
with every tensor the program keeps in bfloat16 kept in float8_e4m3, the
nearest precision below), the planted fault (part of the batch left out, the
mean taken over the rest: half on one chip, one chip's shard on four) and
the witness (the reference in bfloat16). Everything is judged by
``check.judge`` against the workload's limits: the program has to pass, the
control and the fault to fail. One JSON line per seed and side goes to standard
output and to chiprun_out/calibrate_<workload>.jsonl.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import check, harness, rounding, traffic  # noqa: E402


def main(argv, bench_path=None, root=ROOT, require_chip=harness.require_chip):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--witnesses", type=int, default=0,
                    help="seeds that also read the reference in bfloat16")
    ap.add_argument("--variant", action="append", default=[],
                    help="name=JSON of configuration keys to replace")
    ap.add_argument("--variant-seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=7000001)
    args = ap.parse_args(argv)
    bench = harness.load_json(bench_path or os.path.join(root, "BENCHMARK.json"))
    cell, cfg, mix, limits, ref = harness.find_cell(bench, args.workload, root)
    chips = cell["chips"]
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    devices, _peaks = require_chip(chips)
    harness.enable_cache()
    t0 = time.perf_counter()
    say = lambda what: harness.stderr(  # noqa: E731
        "calibrate: %7.1f s  %s" % (time.perf_counter() - t0, what))
    n_rows = cfg["batch_per_chip"] * chips
    steps = mix["followed_steps"]
    # spread over the whole range the driver draws from
    seeds = [args.first_seed + n * 178956971 for n in range(args.seeds)]
    variants = [("program", cfg)]
    for v in args.variant:
        name, _, keys = v.partition("=")
        variants.append((name, dict(cfg, **json.loads(keys))))

    def free():
        gc.collect()
        jax.clear_caches()   # a loaded step holds its gigabytes of scratch

    # ---- 1. the program, and its variants on the first seeds
    sides, sharding = {}, None
    for n, seed in enumerate(seeds):
        for who, c in variants[:1 if n >= args.variant_seeds else None]:
            net, trainer, mesh, trainable = harness.build_program(c, ref, seed, devices)
            sharding = NamedSharding(mesh, PartitionSpec("dp"))
            pool = traffic.make_pool(dict(mix, pool=steps), c, seed, n_rows, sharding)
            feed = traffic.make_feed(mix, pool, sharding)
            sides[seed, who] = harness.follow_program(
                net, trainer, feed, trainable, c["optimizer"]["learning_rate"], steps,
                c.get("weight_layout"))
            feed.close()
            del net, trainer, pool, feed
            free()
            say("%s seed %d: losses %s" % (who, seed, sides[seed, who]["loss"]))

    # ---- 2. and 3. the reference, then what is put in the program's place;
    # every side is judged against the reference of its seed as soon as it
    # is there, so that a call cut short keeps what it read
    kinds = [(kind, list(shape)) for kind, shape, t in ref.leaf_specs(cfg) if t]
    out_dir = os.path.join(root, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, "calibrate_%s.jsonl" % args.workload), "a")
    verdicts, lines = {}, []

    def judge(seed, who):
        side, refd = sides[seed, who], sides[seed, "reference"]
        nums = check.numbers(side, refd)
        rows, ok = check.judge(nums, limits)
        verdicts.setdefault(who, []).append(ok)
        worst = {name: ([(i,) + tuple(kinds[i]) + (gap, p, r) for i, gap, p, r in got]
                        if isinstance(got, list) else got)
                 for name, got in check.worst_leaves(side, refd).items()}
        lines.append({
            "workload": args.workload, "seed": seed, "side": who, "correct": ok,
            "over": [k for k, v, lim in rows if not v <= lim], "numbers": nums,
            "loss": side["loss"], "reference_loss": refd["loss"], "worst": worst})
        print(json.dumps(lines[-1]), flush=True)
        out.write(json.dumps(lines[-1]) + "\n")
        out.flush()

    part = n_rows // 2 if chips == 1 else n_rows // chips
    readers = [("reference", len(seeds), {}),
               ("control_float8_e4m3", args.controls, {"rounding": rounding.FLOAT8_E4M3}),
               ("fault_batch_part", args.faults, {"rows": slice(0, part)}),
               ("witness_reference_bfloat16", args.witnesses, {"rounding": rounding.BFLOAT16})]
    for who, count, kw in readers:
        memo = {}
        for seed in seeds[:count]:
            sides[seed, who] = harness.follow_reference(
                cfg, mix, ref, seed, n_rows, sharding, steps, memo=memo, **kw)
            say("%s seed %d: losses %s" % (who, seed, sides[seed, who]["loss"]))
            for side in ([v for v, _c in variants if (seed, v) in sides]
                         if who == "reference" else [who]):
                judge(seed, side)
        del memo
        free()
    out.close()
    for who, oks in verdicts.items():
        harness.stderr("calibrate: %-28s correct on %d of %d seeds"
                       % (who, sum(oks), len(oks)))
    return lines


if __name__ == "__main__":
    main(sys.argv[1:])
