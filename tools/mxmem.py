#!/usr/bin/env python
"""mxmem — HBM memory observability CLI (memwatch's operator surface).

Reads the artifacts ``mxnet_tpu.observability.memwatch`` produces and
renders terminal-friendly views:

- ``report``      — memory-ledger rows (``label="memory"``: per-executable
                    argument/output/temp/generated-code bytes) ranked by
                    peak, plus the live ``mxtpu_hbm_*`` gauges and
                    ``mxtpu_oom_total`` / ``mxtpu_mem_refusals_total``
                    counters of a telemetry snapshot;
- ``watch``       — the same view re-rendered every N seconds;
- ``postmortem``  — pretty-print an ``mxtpu_oom.json`` OOM artifact:
                    context, exception, the ranked blame table (who held
                    the HBM), top executables, resident bucket ladders
                    and the watermark tail.

Usage::

    python tools/mxmem.py report --ledger mxtpu_cost_ledger.jsonl
    python tools/mxmem.py report /run/metrics.json --ledger ledger.jsonl
    python tools/mxmem.py watch --interval 2 /run/metrics.json
    python tools/mxmem.py postmortem mxtpu_oom.json
    python tools/mxmem.py report --format json --ledger ledger.jsonl

Exit codes (mxlint convention): 0 = healthy, 1 = the artifact shows
memory trouble (an OOM postmortem — by definition — or a snapshot with
``mxtpu_oom_total``/``mxtpu_mem_refusals_total`` above zero), 2 = the
artifact could not be loaded/parsed. Standalone: never imports the
framework, so it renders artifacts from any box.
"""
import argparse
import json
import sys
import time

__all__ = ["main", "load_memory_rows", "render_report", "render_postmortem"]

_TROUBLE_COUNTERS = ("mxtpu_oom_total", "mxtpu_mem_refusals_total")


def _fmt_bytes(v) -> str:
    if v is None:
        return "n/a"
    v = float(v)
    for scale, suffix in ((1 << 30, "GiB"), (1 << 20, "MiB"),
                          (1 << 10, "KiB")):
        if abs(v) >= scale:
            return "%.2f %s" % (v / scale, suffix)
    return "%d B" % int(v)


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def load_memory_rows(path):
    """``label="memory"`` rows of a JSON-lines cost ledger, oldest first
    (corrupt lines skipped — the xcost.CostLedger.rows contract,
    reimplemented so mxmem never imports the framework). Rows that merely
    CARRY a ``memory`` dict (enriched step/trial rows) ride along."""
    rows = []
    with open(path) as f:
        for ln in f:
            ln = ln.strip()
            if not ln:
                continue
            try:
                row = json.loads(ln)
            except ValueError:
                continue
            if isinstance(row, dict) and isinstance(row.get("memory"),
                                                    dict):
                rows.append(row)
    return rows


def _latest_by_fingerprint(rows):
    by_fp, anon = {}, []
    for r in rows:
        fp = r.get("fingerprint")
        if fp:
            by_fp[fp] = r           # oldest-first scan: latest row wins
        else:
            anon.append(r)
    return list(by_fp.values()) + anon


def _peak(row):
    m = row.get("memory") or {}
    peak = row.get("peak_memory_bytes")
    if peak is None:
        peak = (int(m.get("temp_bytes", 0)) + int(m.get("argument_bytes", 0))
                + int(m.get("output_bytes", 0)))
    return int(peak)


def render_report(rows, snap, out, tail: int) -> int:
    """Render ledger rows + snapshot gauges; returns trouble count."""
    trouble = 0
    out.write("mxmem — HBM memory report\n")
    if rows:
        ranked = sorted(_latest_by_fingerprint(rows), key=_peak,
                        reverse=True)
        shown = ranked[:tail]
        out.write("\nmemory ledger (%d executable(s), top %d by peak)\n"
                  % (len(ranked), len(shown)))
        out.write("%-24s %-14s %6s %10s %10s %10s %10s\n"
                  % ("label", "model", "bucket", "peak", "temp", "args",
                     "out"))
        for r in shown:
            m = r.get("memory") or {}
            out.write("%-24s %-14s %6s %10s %10s %10s %10s\n" % (
                str(r.get("mem_label") or r.get("label") or "?")[:24],
                str(r.get("model") or "-")[:14],
                str(r.get("bucket")) if r.get("bucket") is not None
                else "-",
                _fmt_bytes(_peak(r)), _fmt_bytes(m.get("temp_bytes")),
                _fmt_bytes(m.get("argument_bytes")),
                _fmt_bytes(m.get("output_bytes"))))
    if snap is not None:
        fams = snap.get("metrics", {})

        def series(name):
            return (fams.get(name) or {}).get("series", [])

        out.write("\nlive gauges (snapshot pid %s)\n" % snap.get("pid", "?"))
        for name in ("mxtpu_hbm_bytes_in_use", "mxtpu_hbm_peak_bytes",
                     "mxtpu_hbm_largest_alloc_bytes"):
            for s in series(name):
                lbl = s.get("labels") or {}
                out.write("  %-34s %-16s %s\n"
                          % (name,
                             ",".join("%s=%s" % kv
                                      for kv in sorted(lbl.items())) or "-",
                             _fmt_bytes(s.get("value"))))
        for name in _TROUBLE_COUNTERS:
            for s in series(name):
                val = float(s.get("value") or 0)
                if val > 0:
                    trouble += 1
                    lbl = s.get("labels") or {}
                    out.write("  %-34s %-16s %12d !\n"
                              % (name,
                                 ",".join("%s=%s" % kv
                                          for kv in sorted(lbl.items()))
                                 or "-", int(val)))
    if trouble:
        out.write("\n%d memory-trouble signal(s) — see '!' rows\n" % trouble)
    return trouble


def render_postmortem(doc, out, tail: int) -> None:
    out.write("mxmem — OOM postmortem (%s)\n" % (doc.get("context") or "?"))
    ts = doc.get("time")
    if ts:
        out.write("time:      %s\n" % time.strftime(
            "%Y-%m-%d %H:%M:%S", time.localtime(ts)))
    for key in ("model", "trace_id"):
        if doc.get(key):
            out.write("%-10s %s\n" % (key + ":", doc[key]))
    if doc.get("exception"):
        out.write("exception: %s\n" % str(doc["exception"])[:300])
    if doc.get("budget_bytes") is not None:
        out.write("budget:    %s/chip\n" % _fmt_bytes(doc["budget_bytes"]))
    pressure = doc.get("pressure") or {}
    if pressure.get("ballast_bytes"):
        out.write("ballast:   %s (chaos pressure)\n"
                  % _fmt_bytes(pressure["ballast_bytes"]))
    live = doc.get("live") or {}
    if live:
        out.write("live:      in_use %s, peak %s%s\n" % (
            _fmt_bytes(live.get("total_bytes_in_use")),
            _fmt_bytes(live.get("peak_bytes")),
            " (synthetic)" if live.get("synthetic") else ""))
    blame = doc.get("blame") or []
    if blame:
        out.write("\nblame (largest holder first)\n")
        out.write("%-28s %12s\n" % ("holder", "bytes"))
        for b in blame[:tail]:
            out.write("%-28s %12s\n" % (str(b.get("holder"))[:28],
                                        _fmt_bytes(b.get("bytes"))))
    tops = doc.get("top_executables") or []
    if tops:
        out.write("\ntop executables (memory ledger)\n")
        out.write("%-24s %-14s %6s %10s\n"
                  % ("label", "model", "bucket", "peak"))
        for r in tops[:tail]:
            out.write("%-24s %-14s %6s %10s\n" % (
                str(r.get("mem_label") or r.get("label") or "?")[:24],
                str(r.get("model") or "-")[:14],
                str(r.get("bucket")) if r.get("bucket") is not None
                else "-", _fmt_bytes(_peak(r))))
    buckets = doc.get("buckets") or {}
    for model, lad in sorted(buckets.items()):
        out.write("\nmodel %r: resident buckets %s of ladder %s\n"
                  % (model, lad.get("resident"), lad.get("ladder")))
        per = lad.get("per_bucket_bytes") or {}
        for b, info in sorted(per.items(), key=lambda kv: int(kv[0])):
            out.write("  bucket %-6s %-12s (%s)\n"
                      % (b, _fmt_bytes((info or {}).get("bytes")),
                         (info or {}).get("source", "?")))
    tfp = doc.get("trainer_footprint")
    if tfp:
        out.write("\ntrainer footprint: total %s (%s/chip; params %s, "
                  "opt %s)\n" % (
                      _fmt_bytes(tfp.get("total_bytes")),
                      _fmt_bytes(tfp.get("per_chip_bytes")),
                      _fmt_bytes(tfp.get("params_bytes")),
                      _fmt_bytes((tfp.get("opt_state_bytes") or {})
                                 .get("total_bytes"))))
    marks = doc.get("watermarks") or []
    if marks:
        out.write("\nwatermarks (last %d)\n" % min(tail, len(marks)))
        for w in marks[-tail:]:
            out.write("  %s  in_use %s  peak %s\n" % (
                time.strftime("%H:%M:%S", time.localtime(w.get("time", 0))),
                _fmt_bytes(w.get("total_bytes_in_use")),
                _fmt_bytes(w.get("peak_bytes"))))


def run_report(snap_path, ledger_path, tail: int, fmt: str, out) -> int:
    rows, snap = None, None
    errs = []
    if ledger_path:
        try:
            rows = load_memory_rows(ledger_path)
        except OSError as e:
            errs.append("ledger %s: %s" % (ledger_path, e))
    if snap_path:
        try:
            doc = _load_json(snap_path)
            if "metrics" not in doc:
                raise ValueError("not a metrics snapshot")
            snap = doc
        except (OSError, ValueError) as e:
            errs.append("snapshot %s: %s" % (snap_path, e))
    if rows is None and snap is None:
        sys.stderr.write("mxmem: nothing to show (%s)\n"
                         % ("; ".join(errs) or "pass a snapshot and/or "
                            "--ledger"))
        return 2
    for e in errs:
        sys.stderr.write("mxmem: %s\n" % e)
    if fmt == "json":
        out.write(json.dumps({"kind": "mem",
                              "rows": _latest_by_fingerprint(rows or []),
                              "snapshot": snap},
                             indent=1, sort_keys=True) + "\n")
        return 0
    return 1 if render_report(rows or [], snap, out, tail) else 0


def run_postmortem(path: str, tail: int, fmt: str, out) -> int:
    try:
        doc = _load_json(path)
        if doc.get("kind") != "mxtpu_oom":
            raise ValueError("not an mxtpu_oom.json postmortem "
                             "(kind=%r)" % (doc.get("kind"),))
    except (OSError, ValueError) as e:
        sys.stderr.write("mxmem: cannot read %s: %s\n" % (path, e))
        return 2
    if fmt == "json":
        out.write(json.dumps({"kind": "postmortem", "doc": doc},
                             indent=1, sort_keys=True) + "\n")
    else:
        render_postmortem(doc, out, tail)
    return 1        # an OOM artifact IS the anomaly — 0 is never right


def _watch_loop(render, interval: float) -> int:
    rc = 0
    try:
        while True:
            sys.stdout.write("\x1b[2J\x1b[H")     # clear + home
            rc = render()
            sys.stdout.flush()
            time.sleep(interval)
    except KeyboardInterrupt:
        return rc


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    ap = argparse.ArgumentParser(
        prog="mxmem.py",
        description="HBM memory observability: ledger report, live "
                    "watch, OOM postmortems")
    sub = ap.add_subparsers(dest="command", required=True)
    for name in ("report", "watch"):
        sp = sub.add_parser(name)
        sp.add_argument("snapshot", nargs="?", default=None,
                        help="telemetry snapshot JSON (write_snapshot / "
                             "MXNET_TELEMETRY_EXPORT output)")
        sp.add_argument("--ledger", default=None,
                        help="cost-ledger JSONL (MXNET_PERF_LEDGER / "
                             "mxtpu_cost_ledger.jsonl)")
        sp.add_argument("--tail", type=int, default=10,
                        help="executables to show (default 10)")
        sp.add_argument("--format", choices=("text", "json"),
                        default="text")
        if name == "watch":
            sp.add_argument("--interval", type=float, default=2.0,
                            help="seconds between renders (default 2)")
    pp = sub.add_parser("postmortem")
    pp.add_argument("path", help="mxtpu_oom.json artifact")
    pp.add_argument("--tail", type=int, default=10,
                    help="blame/executable/watermark rows (default 10)")
    pp.add_argument("--format", choices=("text", "json"), default="text")
    args = ap.parse_args(argv)

    if args.command == "postmortem":
        return run_postmortem(args.path, args.tail, args.format,
                              sys.stdout)
    if not args.snapshot and not args.ledger:
        ap.error("pass a snapshot and/or --ledger")
    if args.command == "watch":
        return _watch_loop(lambda: run_report(
            args.snapshot, args.ledger, args.tail, args.format,
            sys.stdout), args.interval)
    return run_report(args.snapshot, args.ledger, args.tail, args.format,
                      sys.stdout)


if __name__ == "__main__":
    sys.exit(main())
