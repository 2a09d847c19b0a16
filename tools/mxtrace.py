#!/usr/bin/env python
"""mxtrace — pretty-print the request-trace ring.

Reads a trace-ring dump (written by ``ModelServer.dump_traces``,
``tools/loadgen.py --trace-dump`` or
``observability.tracing.get_tracer().write_dump``) and renders:

- the **summary** view (default): outcome counts + the slowest-N
  retained traces with their dominant stage — where the tail actually
  spends its time;
- ``--errors-only``: only error/shed/expired/deadline-violating traces;
- ``--trace-id ID``: one request's full span timeline — offset,
  duration, proportional bar and tags per lifecycle stage (admission →
  queue → assembly → dispatch → forward → respond);
- ``--format json``: the normalized document; ``--format chrome``: a
  chrome://tracing / Perfetto file (one lane per trace);
- ``--watch N``: re-render every N seconds (live view of a dump an
  exporter keeps rewriting).

Usage::

    python tools/mxtrace.py traces.json
    python tools/mxtrace.py traces.json --errors-only
    python tools/mxtrace.py traces.json --trace-id 3f2a...
    python tools/mxtrace.py traces.json --format chrome > chrome.json

Exit codes (mxlint convention): 0 = healthy (no error/expired/violated
traces in view), 1 = the dump shows anomalies, 2 = the artifact could
not be loaded (or ``--trace-id`` not found).
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

_BAR = 28       # timeline bar width (chars)


def load(path):
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or "traces" not in doc:
        raise ValueError("not a trace-ring dump (expected a 'traces' key)")
    return doc


def _anomalous(t) -> bool:
    # "event" = operational markers (fleet resizes) recorded into the
    # ring for context — informative, not failures; they must not flip
    # the exit code of an otherwise-clean dump
    return t.get("outcome") not in ("ok", "event") or bool(t.get("violated"))


def _dominant_stage(t):
    spans = t.get("spans") or []
    if not spans:
        return "-"
    s = max(spans, key=lambda s: s.get("dur_ms") or 0.0)
    return "%s %.1fms" % (s["stage"], s.get("dur_ms") or 0.0)


def _fmt_ms(v):
    return "%.2f" % v if isinstance(v, (int, float)) else "n/a"


def filter_traces(doc, model=None, errors_only=False):
    out = doc.get("traces") or []
    if model:
        out = [t for t in out if t.get("model") == model]
    if errors_only:
        out = [t for t in out if _anomalous(t)]
    return out


def render_summary(doc, traces, out, slowest: int) -> int:
    ts = doc.get("time")
    out.write("mxtrace — trace ring (pid %s%s)\n" % (
        doc.get("pid", "?"),
        time.strftime(", %Y-%m-%d %H:%M:%S", time.localtime(ts))
        if ts else ""))
    counts = {}
    violated = 0
    for t in traces:
        counts[t.get("outcome") or "?"] = counts.get(
            t.get("outcome") or "?", 0) + 1
        violated += 1 if t.get("violated") else 0
    out.write("retained: %d  (%s%s)\n" % (
        len(traces),
        " ".join("%s=%d" % kv for kv in sorted(counts.items())) or "empty",
        ("  violated=%d" % violated) if violated else ""))
    ranked = sorted(traces, key=lambda t: -(t.get("latency_ms") or 0.0))
    shown = ranked[:slowest]
    if shown:
        out.write("\n%-32s %-10s %-8s %10s %5s %-10s %s\n"
                  % ("trace_id", "model", "outcome", "ms", "batch",
                     "kept", "dominant stage"))
        for t in shown:
            out.write("%-32s %-10s %-8s %10s %5s %-10s %s%s\n" % (
                t.get("trace_id", "?"), str(t.get("model", "?"))[:10],
                t.get("outcome", "?"), _fmt_ms(t.get("latency_ms")),
                t.get("batch_size") or "-",
                t.get("keep_reason") or "-", _dominant_stage(t),
                "  !" if _anomalous(t) else ""))
    bad = sum(1 for t in traces if _anomalous(t))
    if bad:
        out.write("\n%d anomalous trace(s) — '!' rows; inspect one with "
                  "--trace-id\n" % bad)
    return 1 if bad else 0


def render_timeline(t, out) -> int:
    out.write("mxtrace — trace %s\n" % t.get("trace_id", "?"))
    out.write("model=%s  outcome=%s%s%s  latency=%sms  deadline=%sms\n" % (
        t.get("model", "?"), t.get("outcome", "?"),
        ("/" + t["reason"]) if t.get("reason") else "",
        "  VIOLATED" if t.get("violated") else "",
        _fmt_ms(t.get("latency_ms")), _fmt_ms(t.get("deadline_ms"))))
    if t.get("batch_span_id"):
        out.write("batch_span=%s  batch_size=%s (shared with batchmates)\n"
                  % (t["batch_span_id"], t.get("batch_size")))
    spans = sorted(t.get("spans") or [], key=lambda s: s.get("t0_ms", 0.0))
    total = max((s.get("t0_ms", 0.0) + (s.get("dur_ms") or 0.0)
                 for s in spans), default=0.0) or 1.0
    out.write("\n%-10s %10s %10s  %-*s %s\n"
              % ("stage", "at(ms)", "dur(ms)", _BAR, "timeline", "tags"))
    for s in spans:
        t0 = s.get("t0_ms", 0.0)
        dur = s.get("dur_ms") or 0.0
        a = int(round(t0 / total * _BAR))
        b = max(1, int(round(dur / total * _BAR)))
        bar = " " * min(a, _BAR - 1) + "#" * min(b, _BAR - a)
        tags = s.get("tags") or {}
        out.write("%-10s %10.3f %10.3f  %-*s %s\n"
                  % (s.get("stage", "?"), t0, dur, _BAR, bar[:_BAR],
                     " ".join("%s=%s" % kv for kv in sorted(tags.items()))))
    return 1 if _anomalous(t) else 0


def chrome_doc(traces):
    """Chrome-trace JSON from a dump: wall-clock based, one tid lane per
    trace (a *live* merged view with jit/profiler lanes comes from
    ``tracing.Tracer.chrome_trace`` instead)."""
    events = []
    t_min = min((t.get("time") or 0.0 for t in traces), default=0.0)
    for t in traces:
        try:
            tid = int(str(t.get("trace_id", "0"))[:8], 16) % (1 << 31)
        except ValueError:
            tid = 0
        base_us = ((t.get("time") or 0.0) - t_min) * 1e6
        for s in t.get("spans") or []:
            args = {"trace_id": t.get("trace_id"),
                    "model": t.get("model"), "outcome": t.get("outcome")}
            args.update(s.get("tags") or {})
            events.append({
                "name": s.get("stage", "?"), "cat": "serving", "ph": "X",
                "ts": base_us + (s.get("t0_ms") or 0.0) * 1e3,
                "dur": (s.get("dur_ms") or 0.0) * 1e3,
                "pid": 1, "tid": tid, "args": args})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def run_once(args, out) -> int:
    try:
        doc = load(args.path)
    except (OSError, ValueError) as e:
        sys.stderr.write("mxtrace: cannot read %s: %s\n" % (args.path, e))
        return 2
    traces = filter_traces(doc, model=args.model,
                           errors_only=args.errors_only)
    if args.trace_id:
        tid = args.trace_id.lower()
        found = [t for t in traces
                 if str(t.get("trace_id", "")).startswith(tid)]
        if not found:
            sys.stderr.write("mxtrace: trace %r not found in %s (%d "
                             "retained)\n"
                             % (args.trace_id, args.path, len(traces)))
            return 2
        t = found[-1]           # newest wins, same as the ring lookup
        if args.format == "json":
            out.write(json.dumps(t, indent=1, sort_keys=True) + "\n")
            return 1 if _anomalous(t) else 0
        if args.format == "chrome":
            out.write(json.dumps(chrome_doc([t]), indent=1) + "\n")
            return 1 if _anomalous(t) else 0
        return render_timeline(t, out)
    if args.format == "json":
        out.write(json.dumps(dict(doc, traces=traces), indent=1,
                             sort_keys=True) + "\n")
        return 1 if any(_anomalous(t) for t in traces) else 0
    if args.format == "chrome":
        out.write(json.dumps(chrome_doc(traces), indent=1) + "\n")
        return 1 if any(_anomalous(t) for t in traces) else 0
    return render_summary(doc, traces, out, args.slowest)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="pretty-print a request-trace ring dump "
                    "(ModelServer.dump_traces / loadgen --trace-dump)")
    ap.add_argument("path", help="trace-ring dump JSON")
    ap.add_argument("-n", "--slowest", type=int, default=10,
                    help="slowest traces to show in the summary "
                         "(default 10)")
    ap.add_argument("--errors-only", action="store_true",
                    help="only error/shed/expired/violated traces")
    ap.add_argument("--model", default=None, help="filter by model name")
    ap.add_argument("--trace-id", default=None,
                    help="single-timeline view of one trace (prefix "
                         "match; exit 2 when absent)")
    ap.add_argument("--format", choices=("text", "json", "chrome"),
                    default="text")
    ap.add_argument("--watch", type=float, metavar="SECONDS", default=0,
                    help="re-render every N seconds; Ctrl-C to stop — "
                         "exit code reflects the LAST render")
    args = ap.parse_args(argv)

    if args.watch > 0:
        rc = 0
        try:
            while True:
                sys.stdout.write("\x1b[2J\x1b[H")     # clear + home
                rc = run_once(args, sys.stdout)
                sys.stdout.flush()
                time.sleep(args.watch)
        except KeyboardInterrupt:
            return rc
    return run_once(args, sys.stdout)


if __name__ == "__main__":
    sys.exit(main())
