"""The token rehearsal bench (data/tokenbench) on the chip, by hand:

    chiprun -- python3 chipbench/tests/token_rehearsal.py run --seed 11 --seconds 20 --trace 1
    chiprun -- python3 chipbench/tests/token_rehearsal.py calibrate --seeds 12 --controls 3 \
        --faults 3 --witnesses 3

``run`` is ``chipbench/run.py`` on the workload ``lm_chip.train`` (the tiny
token model at sizes whose step takes tens of milliseconds), ``calibrate``
is ``chipbench/calibrate.py`` on it, with the range of every number by side
printed last and written to chiprun_out/token_rehearsal_calibrate.json."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import calibrate, run  # noqa: E402

DATA = os.path.join(ROOT, "chipbench", "tests", "data")
BENCH = os.path.join(DATA, "tokenbench", "BENCHMARK.json")
WORKLOAD = ["--workload", "lm_chip.train"]


def main(argv):
    what, rest = argv[0], argv[1:]
    if what == "run":
        return run.run(WORKLOAD + rest, bench_path=BENCH, root=DATA)
    if what != "calibrate":
        raise SystemExit("token_rehearsal.py: run or calibrate, not %r" % what)
    lines = calibrate.main(WORKLOAD + rest, bench_path=BENCH, root=DATA)
    ranges = {}
    for l in lines:
        side = ranges.setdefault(l["side"], {"correct": [], "numbers": {}})
        side["correct"].append(l["correct"])
        for k, v in l["numbers"].items():
            side["numbers"].setdefault(k, []).append(v)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "token_rehearsal_calibrate.json"), "w") as f:
        json.dump(ranges, f, indent=1)
    for name, side in ranges.items():
        print("%s: correct on %d of %d" % (name, sum(side["correct"]), len(side["correct"])))
        for k, vs in side["numbers"].items():
            print("   %-22s %.6g .. %.6g" % (k, min(vs), max(vs)))
    return lines


if __name__ == "__main__":
    main(sys.argv[1:])
