#!/usr/bin/env python
"""loadgen — prove sustained QPS at bounded tail latency against the
batching model server.

Two targets: ``--selfhost`` spins the built-in tiny model (or a given
symbol/params) in-process and drives the full admission → batcher →
bucket-executor path; ``--url`` drives a remote ``tools/mxserve.py`` over
HTTP (/predict, typed rejections mapped from status codes). Either way
the run's verdict follows the serving SLO: every offered request is
paced, accepted-request p50/p99 are measured end to end, and shed /
expired / errored fractions are held against a budget. The result lands
as a ``label="serving"`` CostLedger row so ``tools/perfwatch.py`` guards
serving throughput/latency regressions exactly like training rows.

Usage::

    python tools/loadgen.py --selfhost --qps 200 --duration 3
    python tools/loadgen.py --selfhost --qps 600 --duration 2 \
        --storm 3 --deadline-ms 100          # deliberate overload probe
    python tools/loadgen.py --url http://127.0.0.1:8080 --model tiny \
        --feature-shape 4 --qps 100 --duration 5
    python tools/loadgen.py --selfhost \
        --tenants a:200:guaranteed,b:40:best_effort --fleet-chips 3

Mixed-traffic mode (``--tenants name:qps[:priority],...``, selfhost
only): one tiny-model tenant per entry driven concurrently at its
declared rate; ``--fleet-chips N`` attaches a
``serving.fleet.FleetController`` over an N-chip budget so the run
exercises fair queueing + autoscaling, and ``--storm MULT`` multiplies
the FIRST tenant's rate (the storm tenant). The result lands as one
``label="fleet"`` CostLedger row with bracketed per-tenant metrics
(``p99_ms[a]``…) that ``tools/perfwatch.py`` compares with the base
metric's direction.

Exit codes (mxlint convention): 0 = sustained (degraded fraction within
``--max-degraded-frac`` and p99 within the deadline; every tenant in
--tenants mode), 1 = degraded, 2 = cannot run (bad args, no target).
"""
import argparse
import json
import os
import socket
import sys
import threading
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="load generator for the batching model server")
    tgt = ap.add_mutually_exclusive_group()
    tgt.add_argument("--selfhost", action="store_true",
                     help="serve the model in-process and drive it")
    tgt.add_argument("--url", default=None,
                     help="base URL of a running mxserve (http://host:port)")
    ap.add_argument("--model", default="tiny",
                    help="symbol JSON path or 'tiny' (selfhost); model "
                         "NAME to address (url mode)")
    ap.add_argument("--params", default=None)
    ap.add_argument("--feature-shape", default=None,
                    help="per-sample shape, e.g. 3,224,224 (required for "
                         "a model file and for --url)")
    ap.add_argument("--qps", type=float, default=100.0)
    ap.add_argument("--duration", type=float, default=3.0)
    ap.add_argument("--threads", type=int, default=2)
    ap.add_argument("--storm", type=float, default=None, metavar="MULT",
                    help="multiply --qps by MULT (deliberate overload; "
                         "the verdict still applies — expect exit 1)")
    ap.add_argument("--deadline-ms", type=float, default=None)
    ap.add_argument("--max-queue", type=int, default=None,
                    help="selfhost queue bound")
    ap.add_argument("--buckets", default=None)
    ap.add_argument("--max-degraded-frac", type=float, default=0.01,
                    help="max tolerated shed+expired+error fraction "
                         "before the run is 'degraded'")
    ap.add_argument("--ledger", default=None,
                    help="cost-ledger path for the serving row (default: "
                         "MXNET_PERF_LEDGER; empty default = row printed "
                         "but not persisted)")
    ap.add_argument("--tenants", default=None, metavar="SPEC",
                    help="mixed-traffic mode: name:qps[:priority],... "
                         "(priority guaranteed|best_effort; selfhost "
                         "only) — one tiny-model tenant per entry, "
                         "driven concurrently")
    ap.add_argument("--fleet-chips", type=int, default=None,
                    help="with --tenants: attach a FleetController over "
                         "this chip budget (autoscaler + fair queueing "
                         "live during the run)")
    ap.add_argument("--hedge", action="store_true",
                    help="selfhost: enable hedged requests — a duplicate "
                         "dispatch fires after the rolling-p99-derived "
                         "delay and the first result wins (tail "
                         "tolerance; spend capped by the retry budget)")
    ap.add_argument("--hedge-delay-ms", type=float, default=None,
                    help="hedge fire delay floor before enough latency "
                         "samples exist (default MXNET_SERVE_HEDGE_"
                         "DELAY_MS)")
    ap.add_argument("--retry-budget", type=float, default=None,
                    help="fraction of admitted requests that may be "
                         "duplicated as retries+hedges (0 disables the "
                         "cap; default MXNET_SERVE_RETRY_BUDGET)")
    ap.add_argument("--trace-dump", default=None, metavar="PATH",
                    help="selfhost: write the trace ring to PATH after "
                         "the run (pretty-print with tools/mxtrace.py) — "
                         "the retained tail/error timelines behind the "
                         "reported trace_ids")
    ap.add_argument("--during-rollout", action="store_true",
                    help="selfhost: start a staged rollout of a same-"
                         "weights candidate version mid-run and ramp it "
                         "on fast dwell — the run then reports per-"
                         "version p50/p99 + outcome fractions and the "
                         "rollout timeline (the zero-downtime-swap "
                         "evidence), and the ledger row carries the "
                         "timeline")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    args = ap.parse_args(argv)

    if not (args.selfhost or args.url or args.tenants):
        sys.stderr.write("loadgen: pick a target: --selfhost, --url or "
                         "--tenants\n")
        return 2
    if args.qps <= 0 or args.duration <= 0 or args.threads < 1:
        sys.stderr.write("loadgen: qps/duration/threads must be "
                         "positive\n")
        return 2
    qps = args.qps * (args.storm if args.storm else 1.0)

    if args.during_rollout and not args.selfhost:
        sys.stderr.write("loadgen: --during-rollout is selfhost-only "
                         "(the rollout manager lives in the serving "
                         "process)\n")
        return 2
    if args.tenants:
        if args.url:
            sys.stderr.write("loadgen: --tenants is selfhost-only (the "
                             "fleet lives in the serving process)\n")
            return 2
        return _run_tenants(args)
    if args.url:
        return _run_http(args, qps)
    return _run_selfhost(args, qps)


def _emit(args, stats, row, verdict) -> None:
    if args.format == "json":
        print(json.dumps(row, sort_keys=True), flush=True)
    else:
        print("loadgen: %s  offered=%.0f qps  achieved=%.1f qps  "
              "ok=%d shed=%d expired=%d error=%d unfinished=%d  "
              "p50=%.2fms p99=%.2fms"
              % (verdict, stats.get("qps_offered", 0.0),
                 stats.get("qps", 0.0), stats.get("ok", 0),
                 stats.get("shed", 0), stats.get("expired", 0),
                 stats.get("error", 0), stats.get("unfinished", 0),
                 stats.get("p50_ms", float("nan")),
                 stats.get("p99_ms", float("nan"))), flush=True)
        # clickable evidence, not bare percentiles: the slowest/failed
        # requests' trace_ids resolve in the trace ring (--trace-dump +
        # tools/mxtrace.py --trace-id <id>)
        for t in stats.get("slow_traces") or []:
            print("loadgen: slow   trace %s  %.2fms"
                  % (t["trace_id"], t["ms"]), flush=True)
        for tid in stats.get("failed_traces") or []:
            print("loadgen: failed trace %s" % tid, flush=True)


def _run_selfhost(args, qps) -> int:
    try:
        from mxnet_tpu.observability import xcost
        from mxnet_tpu.serving import ModelServer
        from mxnet_tpu.serving import load as sload
    except Exception as e:
        sys.stderr.write("loadgen: cannot import the backend: %r\n" % e)
        return 2
    hedge_kwargs = {}
    if args.hedge:
        hedge_kwargs["hedge"] = True
    if args.hedge_delay_ms is not None:
        hedge_kwargs["hedge_delay_ms"] = args.hedge_delay_ms
    if args.retry_budget is not None:
        hedge_kwargs["retry_budget"] = args.retry_budget
    try:
        cfg = sload.model_config_from_files(
            args.model, params=args.params,
            feature_shape=args.feature_shape, buckets=args.buckets,
            max_queue=args.max_queue, deadline_ms=args.deadline_ms,
            **hedge_kwargs)
        server = ModelServer([cfg]).start(warm=True)
    except Exception as e:
        sys.stderr.write("loadgen: cannot build the selfhost server: "
                         "%r\n" % e)
        return 2
    ro = rollout_evidence = None
    if args.during_rollout:
        # same-weights candidate: the ramp exercises the whole splitter/
        # gate/hot-swap machinery while answers stay byte-comparable —
        # the run itself is the zero-downtime proof
        try:
            from mxnet_tpu.serving.rollout import RolloutManager
            mgr = RolloutManager.attach(server)
            ro = mgr.start(cfg.name, "candidate",
                           dwell_s=max(0.05, args.duration / 12.0),
                           min_shadow=3, min_requests=3,
                           shadow_sample=0.5)
        except Exception as e:
            server.close(timeout=15.0)
            sys.stderr.write("loadgen: cannot start the rollout: %r\n"
                             % e)
            return 2
    try:
        stats = sload.run_load(server, cfg.name, qps=qps,
                               duration_s=args.duration,
                               threads=args.threads,
                               deadline_ms=args.deadline_ms)
        srv_stats = server.stats(cfg.name)
        if ro is not None:
            rollout_evidence = _rollout_evidence(server, cfg.name, ro)
    finally:
        server.close(timeout=15.0)
    if args.hedge:
        hedges = srv_stats.get("hedges") or {}
        budget = srv_stats.get("retry_budget") or {}
        print("loadgen: hedges fired=%d won=%d lost=%d budget_denied=%d  "
              "budget spent=%s denied=%s"
              % (hedges.get("fired", 0), hedges.get("won", 0),
                 hedges.get("lost", 0), hedges.get("budget_denied", 0),
                 budget.get("spent") or {}, budget.get("denied") or {}),
              flush=True)
    if args.trace_dump:
        try:
            server.dump_traces(args.trace_dump)
        except Exception as e:
            sys.stderr.write("loadgen: trace dump failed: %r\n" % e)
    ledger = (xcost.CostLedger(args.ledger) if args.ledger
              else xcost.get_ledger())
    extra = {"target": "selfhost",
             "slow_traces": stats.get("slow_traces"),
             "failed_traces": stats.get("failed_traces")}
    if rollout_evidence is not None:
        extra["rollout"] = rollout_evidence
    row = sload.ledger_row(stats, ledger=ledger, extra=extra)
    v = sload.verdict(stats, max_degraded_frac=args.max_degraded_frac)
    if (rollout_evidence is not None
            and rollout_evidence["state"] not in ("promoted", "serving")):
        v = "degraded"
    _emit(args, stats, row, v)
    if rollout_evidence is not None:
        _emit_rollout(rollout_evidence)
    return 0 if v == "ok" else 1


def _rollout_evidence(server, model, ro):
    """Per-version latency/outcome readout + the rollout timeline —
    collected while the server (and the canary state) is still alive."""
    import numpy as np

    from mxnet_tpu.observability import catalog as _c

    versions = {}
    outcomes = ("ok", "shed", "expired", "error")

    def _version_row(version, latencies):
        counts = {oc: int(_c.ROLLOUT_VERSION_REQUESTS.value(
            model=model, version=version, outcome=oc) or 0)
            for oc in outcomes}
        total = sum(counts.values())
        row = {"counts": counts,
               "fractions": {oc: (counts[oc] / total if total else 0.0)
                             for oc in outcomes}}
        lat = np.asarray(latencies or [], np.float64)
        if lat.size:
            row["p50_ms"] = float(np.percentile(lat, 50))
            row["p99_ms"] = float(np.percentile(lat, 99))
        return row

    st = server._models.get(model)
    with st.lock:
        inc_lat = list(st.latencies)
    versions[ro.incumbent] = _version_row(ro.incumbent, inc_lat)
    can = ro.canary
    can_lat = []
    if can is not None:
        with can.lock:
            can_lat = list(can.latencies)
    versions[ro.version] = _version_row(ro.version, can_lat)
    return {"version": ro.version, "incumbent": ro.incumbent,
            "state": ro.state, "stage": ro.stage,
            "agreement": ro.agreement(),
            "timeline": [{k: h[k] for k in ("action", "stage", "reason")
                          if k in h} for h in ro.history],
            "versions": versions}


def _emit_rollout(ev) -> None:
    for version in sorted(ev["versions"]):
        row = ev["versions"][version]
        c, fr = row["counts"], row["fractions"]
        tag = " (candidate)" if version == ev["version"] else ""
        print("loadgen: rollout version %-10s ok=%d shed=%d expired=%d "
              "error=%d  ok_frac=%.3f  p50=%s p99=%s%s"
              % (version, c["ok"], c["shed"], c["expired"], c["error"],
                 fr["ok"],
                 ("%.2fms" % row["p50_ms"]) if "p50_ms" in row else "n/a",
                 ("%.2fms" % row["p99_ms"]) if "p99_ms" in row else "n/a",
                 tag), flush=True)
    steps = []
    for h in ev["timeline"]:
        step = h["action"]
        if h.get("stage") and h["action"] == "stage":
            step = "stage:%s" % h["stage"]
        if h.get("reason"):
            step += "(%s)" % h["reason"]
        steps.append(step)
    print("loadgen: rollout %s -> %s  state=%s agreement=%s  timeline: %s"
          % (ev["incumbent"], ev["version"], ev["state"],
             ("%.3f" % ev["agreement"]) if ev["agreement"] is not None
             else "n/a",
             " -> ".join(steps)), flush=True)


def _parse_tenants(spec: str):
    """``a:200:guaranteed,b:40:best_effort`` -> [(name, qps, priority)]."""
    out = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        bits = part.split(":")
        if len(bits) not in (2, 3):
            raise ValueError("tenant entry %r is not name:qps[:priority]"
                             % part)
        name, tqps = bits[0].strip(), float(bits[1])
        prio = bits[2].strip() if len(bits) == 3 else "guaranteed"
        if not name or tqps <= 0:
            raise ValueError("tenant entry %r needs a name and a "
                             "positive qps" % part)
        out.append((name, tqps, prio))
    if len(out) < 2:
        raise ValueError("--tenants needs at least two entries")
    if len({n for n, _, _ in out}) != len(out):
        raise ValueError("duplicate tenant names in --tenants")
    return out


def _run_tenants(args) -> int:
    try:
        from mxnet_tpu.observability import xcost
        from mxnet_tpu.serving import ModelConfig, ModelServer
        from mxnet_tpu.serving import load as sload
    except Exception as e:
        sys.stderr.write("loadgen: cannot import the backend: %r\n" % e)
        return 2
    try:
        tenants = _parse_tenants(args.tenants)
    except ValueError as e:
        sys.stderr.write("loadgen: %s\n" % e)
        return 2

    sym, params, shape, _ = sload.tiny_model()
    cfgs = [ModelConfig(name, sym, params, feature_shape=shape,
                        max_queue=args.max_queue,
                        deadline_ms=args.deadline_ms)
            for name, _, _ in tenants]
    fleet = None
    try:
        server = ModelServer(cfgs)
        if args.fleet_chips is not None:
            from mxnet_tpu.serving.fleet import (FleetController,
                                                 TenantPolicy)
            fleet = FleetController(
                server, args.fleet_chips,
                [TenantPolicy(name, priority=prio)
                 for name, _, prio in tenants])
        server.start(warm=True)
    except Exception as e:
        sys.stderr.write("loadgen: cannot build the tenant fleet: %r\n"
                         % e)
        return 2

    results = {}
    errors = []

    def drive(name, tqps):
        try:
            results[name] = sload.run_load(
                server, name, qps=tqps, duration_s=args.duration,
                threads=args.threads, deadline_ms=args.deadline_ms)
        except Exception as e:         # noqa: BLE001 — surfaced below
            errors.append((name, e))

    storm_mult = args.storm if args.storm else 1.0
    try:
        if fleet is not None:
            fleet.start()
        workers = [threading.Thread(
            target=drive, name="loadgen-%s" % name,
            args=(name, tqps * (storm_mult if i == 0 else 1.0)),
            daemon=True)
            for i, (name, tqps, _) in enumerate(tenants)]
        for w in workers:
            w.start()
        for w in workers:
            w.join()
    finally:
        if fleet is not None:
            fleet.stop()
        server.close(timeout=15.0)
    if errors:
        sys.stderr.write("loadgen: tenant %r failed: %r\n" % errors[0])
        return 2

    worst = "ok"
    for name, tqps, prio in tenants:
        stats = results[name]
        stats["priority"] = prio
        stats["deadline_violations"] = \
            server.stats(name)["deadline_violations"]
        v = sload.verdict(stats, max_degraded_frac=args.max_degraded_frac)
        if v != "ok":
            worst = "degraded"
        if args.format == "text":
            print("loadgen: tenant %-12s %-11s %s  offered=%.0f qps  "
                  "achieved=%.1f qps  ok=%d shed=%d expired=%d error=%d  "
                  "p50=%.2fms p99=%.2fms  deadline_violations=%d"
                  % (name, prio, v, stats.get("qps_offered", 0.0),
                     stats.get("qps", 0.0), stats.get("ok", 0),
                     stats.get("shed", 0), stats.get("expired", 0),
                     stats.get("error", 0),
                     stats.get("p50_ms") or float("nan"),
                     stats.get("p99_ms") or float("nan"),
                     stats["deadline_violations"]), flush=True)
    ledger = (xcost.CostLedger(args.ledger) if args.ledger
              else xcost.get_ledger())
    row = sload.fleet_row(results, ledger=ledger,
                          extra={"target": "selfhost",
                                 "fleet_chips": args.fleet_chips,
                                 "storm": args.storm})
    if args.format == "json":
        print(json.dumps(row, sort_keys=True), flush=True)
    return 0 if worst == "ok" else 1


def _run_http(args, qps) -> int:
    import urllib.error
    import urllib.request

    import numpy as np

    if not args.feature_shape:
        sys.stderr.write("loadgen: --feature-shape is required with "
                         "--url\n")
        return 2
    feat = tuple(int(t) for t in args.feature_shape.split(",") if t.strip())
    url = args.url.rstrip("/") + "/predict"
    payload = json.dumps({
        "model": args.model,
        "data": np.zeros(feat, np.float32).tolist(),
        **({"deadline_ms": args.deadline_ms}
           if args.deadline_ms is not None else {}),
    }).encode()
    # one probe before the paced run: an unreachable target is 'cannot
    # run', not a 100%-error 'degraded'
    try:
        req = urllib.request.Request(url, data=payload,
                                     headers={"Content-Type":
                                              "application/json"})
        urllib.request.urlopen(req, timeout=10.0).read()
    except urllib.error.HTTPError:
        pass                      # server answered: reachable
    except Exception as e:
        sys.stderr.write("loadgen: target unreachable: %r\n" % e)
        return 2

    from mxnet_tpu.observability.tracing import TraceContext
    from mxnet_tpu.serving.chaos import paced_run, trace_evidence

    lock = threading.Lock()
    last_done = [None]
    slow = []      # (ms, trace_id) of ok completions
    failed = []    # trace_ids of expired/errored requests
    stats = {"submitted": 0, "ok": 0, "shed": 0, "expired": 0, "error": 0,
             "unfinished": 0, "latencies_ms": [], "qps_offered": qps,
             "duration_s": args.duration, "model": args.model,
             "deadline_ms": args.deadline_ms}

    def fire():
        with lock:
            stats["submitted"] += 1
        # every request carries a W3C traceparent: the server's span
        # timeline continues OUR trace_id, so the slowest/failed ids
        # reported below resolve in the server's trace ring
        ctx = TraceContext.new()
        t0 = time.monotonic()
        try:
            req = urllib.request.Request(
                url, data=payload,
                headers={"Content-Type": "application/json",
                         "traceparent": ctx.to_traceparent()})
            urllib.request.urlopen(req, timeout=30.0).read()
            t_done = time.monotonic()
            ms = (t_done - t0) * 1e3
            with lock:
                stats["ok"] += 1
                stats["latencies_ms"].append(ms)
                slow.append((ms, ctx.trace_id))
                last_done[0] = (t_done if last_done[0] is None
                                else max(last_done[0], t_done))
        except urllib.error.HTTPError as e:
            key = ("shed" if e.code in (429, 503)
                   else "expired" if e.code == 504 else "error")
            with lock:
                stats[key] += 1
                if key in ("expired", "error"):
                    failed.append(ctx.trace_id)
        except (TimeoutError, socket.timeout):
            # the server never answered within the client timeout: slow,
            # verdict unknown — same taxonomy as request_storm, never
            # folded into 'error' (reserved for executor faults)
            with lock:
                stats["unfinished"] += 1
        except urllib.error.URLError as e:
            with lock:
                if isinstance(e.reason, (TimeoutError, socket.timeout)):
                    stats["unfinished"] += 1
                else:
                    stats["error"] += 1
                    failed.append(ctx.trace_id)
        except Exception:
            with lock:
                stats["error"] += 1
                failed.append(ctx.trace_id)

    from mxnet_tpu.observability import xcost
    from mxnet_tpu.serving import load as sload

    t0 = time.monotonic()
    paced_run(fire, qps=qps, duration_s=args.duration,
              threads=args.threads)
    # shared accounting tail: span-based qps (one request wedged in the
    # 30s urlopen timeout must not read as a throughput collapse),
    # fractions, percentiles — identical to the selfhost path
    sload.finalize_load_stats(stats, t_start=t0, last_done=last_done[0],
                              wall_s=max(1e-9, time.monotonic() - t0))
    stats.update(trace_evidence(slow, failed))
    ledger = (xcost.CostLedger(args.ledger) if args.ledger
              else xcost.get_ledger())
    row = sload.ledger_row(stats, ledger=ledger,
                           extra={"target": args.url,
                                  "slow_traces": stats["slow_traces"],
                                  "failed_traces": stats["failed_traces"]})
    v = sload.verdict(stats, max_degraded_frac=args.max_degraded_frac)
    _emit(args, stats, row, v)
    return 0 if v == "ok" else 1


if __name__ == "__main__":
    sys.exit(main())
