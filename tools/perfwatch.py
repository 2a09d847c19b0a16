#!/usr/bin/env python
"""perfwatch — compare a perf artifact against a baseline; exit loud.

Compares a CURRENT artifact — a bench row, a telemetry snapshot (the live
``mxtpu_mfu``/``mxtpu_trainer_samples_per_sec`` gauges), or a cost-ledger
row/JSONL — against a BASELINE (``--baseline``, else the
``MXNET_PERF_BASELINE`` env; bench rows, ``{"parsed": row}`` driver
wrappers and ledgers are accepted). Any metric present on
both sides is checked with direction-aware thresholds (throughput/MFU:
lower is a regression; FLOPs-per-step/step-time: higher is).

Usage::

    python tools/perfwatch.py fresh_row.json --baseline last_row.json
    python tools/perfwatch.py ledger.jsonl --baseline old.jsonl --threshold-pct 5
    python tools/perfwatch.py snap.json --baseline last_row.json --format json

Exit codes (mxlint convention): 0 = parity/improvement, 1 = at least one
metric regressed past its threshold, 2 = baseline or current artifact
missing/unloadable/incomparable.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="compare a perf artifact (bench row, telemetry "
                    "snapshot, cost-ledger row) against a baseline")
    ap.add_argument("current", help="bench row JSON, telemetry snapshot "
                                    "JSON, or cost-ledger JSON/JSONL")
    ap.add_argument("--baseline", default=None,
                    help="baseline artifact (default: the "
                         "MXNET_PERF_BASELINE env)")
    ap.add_argument("--threshold-pct", type=float, default=None,
                    help="regression threshold percent applied to every "
                         "metric (default 10)")
    ap.add_argument("--metric-threshold", action="append", default=[],
                    metavar="METRIC=PCT",
                    help="per-metric override, e.g. mfu=5 (repeatable)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    args = ap.parse_args(argv)

    from mxnet_tpu.observability import perfwatch as pw

    thresholds = {}
    for tok in args.metric_threshold:
        try:
            k, v = tok.split("=", 1)
            thresholds[k.strip()] = float(v)
        except ValueError:
            sys.stderr.write("perfwatch: bad --metric-threshold %r "
                             "(want METRIC=PCT)\n" % tok)
            return 2
    default_pct = (args.threshold_pct if args.threshold_pct is not None
                   else pw.DEFAULT_THRESHOLD_PCT)

    baseline_path = args.baseline or pw.default_baseline_path()
    baseline, err = pw.load_artifact(baseline_path)
    if baseline is None:
        sys.stderr.write("perfwatch: no usable baseline: %s\n" % err)
        return 2
    current, err = pw.load_artifact(args.current)
    if current is None:
        sys.stderr.write("perfwatch: no usable current artifact: %s\n" % err)
        return 2

    res = pw.compare(current, baseline, thresholds=thresholds,
                     default_pct=default_pct)
    if args.format == "json":
        print(json.dumps(res, indent=1, sort_keys=True))
    else:
        print("perfwatch: %s (%s) vs baseline %s (%s)"
              % (args.current, current["kind"], baseline_path,
                 baseline["kind"]))
        for ch in res["checks"]:
            print("  %-16s %12.6g -> %12.6g  (%+7.2f%%, threshold %.1f%%)%s"
                  % (ch["metric"], ch["baseline"], ch["current"],
                     ch["delta_pct"], ch["threshold_pct"],
                     "  REGRESSION" if ch["regressed"] else ""))
        print("status: %s" % res["status"])
    if res["status"] == "regression":
        return 1
    if res["status"] == "incomparable":
        sys.stderr.write("perfwatch: artifacts share no comparable metric\n")
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
