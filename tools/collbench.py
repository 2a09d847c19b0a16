#!/usr/bin/env python
"""collbench — collectives bandwidth lab CLI (mxnet_tpu.parallel.collbench).

Measures psum / reduce-scatter / all-gather / ppermute bytes/sec vs device
count and payload size (plus the 2-bit-compressed allreduce against its
dense baseline with ``--compression``), emitting one JSON line per
measurement and persisting every row to the cost ledger so the tuner /
perfwatch / bench provenance all read the same numbers.

Usage::

    python tools/collbench.py                          # full default sweep
    python tools/collbench.py --ops psum,reduce_scatter \\
        --sizes 1M,4M --devices 1,4,8 --compression 0.5
    python tools/collbench.py --ledger /tmp/coll.jsonl --format json

Exit codes (mxlint convention): 0 = every cell measured, 1 = some cells
failed (partial sweep emitted), 2 = cannot run (backend down, bad args).
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

_SUFFIX = {"k": 1 << 10, "m": 1 << 20, "g": 1 << 30}


def _parse_size(tok: str) -> int:
    tok = tok.strip().lower()
    if tok and tok[-1] in _SUFFIX:
        return int(float(tok[:-1]) * _SUFFIX[tok[-1]])
    return int(tok)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="measure collective bytes/sec vs device count and "
                    "payload size")
    ap.add_argument("--ops", default=None,
                    help="comma list of psum,reduce_scatter,all_gather,"
                         "ppermute (default: all)")
    ap.add_argument("--sizes", default="64K,1M,4M",
                    help="payload sizes, K/M/G suffixes ok")
    ap.add_argument("--devices", default=None,
                    help="device counts to sweep (default: 1,2,4,...,all)")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--compression", type=float, default=None,
                    metavar="THRESHOLD",
                    help="also measure the 2-bit-compressed allreduce "
                         "(error-feedback codec) at this threshold against "
                         "the dense psum — the on/off bandwidth comparison")
    ap.add_argument("--ledger", default=None,
                    help="cost-ledger path (default: MXNET_PERF_LEDGER, "
                         "else <repo>/mxtpu_cost_ledger.jsonl)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    args = ap.parse_args(argv)

    try:
        sizes = [_parse_size(t) for t in args.sizes.split(",") if t.strip()]
        counts = ([int(t) for t in args.devices.split(",") if t.strip()]
                  if args.devices else None)
        ops = tuple(t.strip() for t in args.ops.split(",") if t.strip()) \
            if args.ops else None
    except ValueError as e:
        sys.stderr.write("collbench: bad argument: %s\n" % e)
        return 2

    try:
        import jax
        from mxnet_tpu.base import MXNetError
        from mxnet_tpu.observability import xcost
        from mxnet_tpu.parallel import collbench
    except Exception as e:
        sys.stderr.write("collbench: cannot import the backend: %r\n" % e)
        return 2
    try:
        jax.devices()
    except Exception as e:
        sys.stderr.write("collbench: backend init failed: %r\n" % e)
        return 2
    ledger = xcost.CostLedger(
        args.ledger
        or xcost.ledger_path()
        or os.path.join(HERE, "mxtpu_cost_ledger.jsonl"))

    failures = []

    def emit(row):
        if args.format == "json":
            print(json.dumps(row, sort_keys=True), flush=True)
        else:
            extra = ""
            if row.get("compression"):
                extra = " (2bit, %sx fewer wire bytes)" % (
                    round(row["wire_reduction_x"], 1)
                    if row.get("wire_reduction_x") else "?")
            print("%-16s n=%-3d %8.2f KiB  %8.3f ms  %10.1f MB/s%s"
                  % (row["op"], row["n_devices"],
                     row["payload_bytes"] / 1024.0, row["ms"],
                     row["bytes_per_s"] / 1e6, extra), flush=True)

    # rows are counted off the emit stream, not run()'s return value, so a
    # mid-sweep failure still leaves the already-measured cells on stdout/
    # ledger and exits 1 (partial) instead of 2 (nothing ran)
    rows = []

    def land(row):
        rows.append(row)
        emit(row)

    try:
        kwargs = dict(device_counts=counts, payload_sizes=sizes,
                      dtype=args.dtype, steps=args.steps,
                      warmup=args.warmup, compression=args.compression,
                      ledger=ledger, emit=land)
        if ops:
            kwargs["ops"] = ops
        collbench.run(**kwargs)
    except MXNetError as e:
        failures.append(str(e))
        sys.stderr.write("collbench: %s\n" % e)
    except Exception as e:
        failures.append(repr(e))
        sys.stderr.write("collbench: sweep aborted: %r\n" % e)
    if not rows:
        sys.stderr.write("collbench: nothing measured\n")
        return 2
    sys.stderr.write("# %d row(s) -> %s\n" % (len(rows), ledger.path))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
