#!/usr/bin/env python
"""mxfleet — operate a multi-tenant serving fleet from the CLI.

The operator surface over ``mxnet_tpu.serving.fleet.FleetController``:
inspect a live fleet's placement/burn state (``status`` / ``watch`` over
``GET /fleetz``), move chips by hand (``resize`` over ``POST
/fleetz/resize`` — the fleet refuses impossible splits with a typed
TopologyMismatch → HTTP 409), and prove the whole control loop in one
process (``selfcheck``: a two-tenant fleet on the built-in tiny model,
optionally under the ``tenant_storm`` chaos scenario, graded on counter
deltas — resizes fired, victim SLO held, zero deadline violations).

Usage::

    python tools/mxfleet.py status   --url http://127.0.0.1:8080
    python tools/mxfleet.py watch    --url ... --interval 2 --count 10
    python tools/mxfleet.py resize   --url ... --model a --chips 2
    python tools/mxfleet.py selfcheck
    python tools/mxfleet.py selfcheck --chaos tenant_storm

Exit codes (mxlint convention): 0 = healthy / resize applied / selfcheck
proved the loop; 1 = degraded (a tenant in excursion, resize refused,
selfcheck failed its acceptance bars); 2 = cannot run (no fleet at the
URL, bad args, backend unavailable).
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)


def _get(url):
    import urllib.request
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.getcode(), json.loads(r.read().decode())


def _post(url, doc):
    import urllib.error
    import urllib.request
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.getcode(), json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode() or "{}")


def _render_status(doc) -> bool:
    """Print one fleet status document; returns True when healthy (no
    tenant in excursion)."""
    print("fleet: %d/%d chips placed  dwell=%gs  burn_threshold=%.2f  "
          "evaluator=%s"
          % (doc["total_chips"] - doc["free_chips"], doc["total_chips"],
             doc["dwell_s"], doc["burn_threshold"],
             "running" if doc.get("evaluator_running") else "stopped"))
    healthy = True
    for name in sorted(doc.get("models", {})):
        m = doc["models"][name]
        burn = m.get("burn")
        flag = ""
        if m.get("in_excursion"):
            flag = "  << SLO EXCURSION"
            healthy = False
        print("  %-12s %d chip(s) [%d..%s]  %-11s q=%-3d burn=%-6s "
              "buckets=%s%s"
              % (name, m["chips"], m["floor_chips"],
                 m["ceiling_chips"] if m["ceiling_chips"] is not None
                 else "*",
                 m["priority"], m["queue_depth"],
                 ("%.2f" % burn) if burn is not None else "n/a",
                 m["buckets"], flag))
    hist = doc.get("history") or []
    for h in hist[-5:]:
        if h.get("action") == "resize":
            print("  resize: %-12s %s %d -> %d (%s)"
                  % (h["model"], h["direction"], h["old_chips"],
                     h["new_chips"], h.get("reason", "")))
        elif h.get("action") == "refused":
            print("  REFUSED: %-12s %s: %s"
                  % (h["model"], h.get("reason"), h.get("detail", "")))
    return healthy


def _cmd_status(args) -> int:
    try:
        code, doc = _get(args.url.rstrip("/") + "/fleetz")
    except Exception as e:
        sys.stderr.write("mxfleet: cannot reach %s: %r\n" % (args.url, e))
        return 2
    if code == 404 or "models" not in doc:
        sys.stderr.write("mxfleet: no fleet controller at %s (fleet mode "
                         "off)\n" % args.url)
        return 2
    return 0 if _render_status(doc) else 1


def _cmd_watch(args) -> int:
    worst = 0
    for i in range(max(1, args.count)):
        if i:
            time.sleep(max(0.1, args.interval))
            print()
        rc = _cmd_status(args)
        if rc == 2:
            return 2
        worst = max(worst, rc)
    return worst


def _cmd_resize(args) -> int:
    try:
        code, doc = _post(args.url.rstrip("/") + "/fleetz/resize",
                          {"model": args.model, "chips": args.chips})
    except Exception as e:
        sys.stderr.write("mxfleet: cannot reach %s: %r\n" % (args.url, e))
        return 2
    if code == 200:
        plan = doc.get("plan", {})
        print("mxfleet: resized %r %s -> %d chip(s); buckets=%s"
              % (args.model, plan.get("direction"), args.chips,
                 plan.get("buckets")))
        return 0
    if code == 409:
        sys.stderr.write("mxfleet: resize REFUSED (typed "
                         "TopologyMismatch): %s\n" % doc.get("error"))
        return 1
    sys.stderr.write("mxfleet: resize failed (%d): %s\n"
                     % (code, doc.get("error")))
    return 2


def _cmd_selfcheck(args) -> int:
    """Prove the control loop in-process: two guaranteed tenants on the
    tiny model over 3 chips, the chip-scaled executor making capacity
    real, and (with --chaos tenant_storm) tenant "a" stormed at ~3x its
    1-chip sustainable QPS while tenant "b" runs its declared load. The
    verdict reads counter deltas: the fleet must have resized (grow
    fired), the victim's accepted p99 must be inside its SLO, and
    deadline_violations must be 0 fleet-wide."""
    try:
        import numpy as np

        from mxnet_tpu.observability import catalog as _c
        from mxnet_tpu.serving import chaos as schaos
        from mxnet_tpu.serving import load as sload
        from mxnet_tpu.serving.fleet import FleetController, TenantPolicy
        from mxnet_tpu.serving.server import ModelConfig, ModelServer
    except Exception as e:
        sys.stderr.write("mxfleet: cannot import the backend: %r\n" % e)
        return 2

    sym, params, shape, _ = sload.tiny_model()
    slo_ms = 200.0
    mk = lambda n: ModelConfig(n, sym, params, feature_shape=shape,
                               buckets=(1, 2, 4, 8), max_queue=64,
                               deadline_ms=400.0, max_wait_ms=2.0,
                               slo_p99_ms=slo_ms, trace_sample=0.05)
    server = ModelServer([mk("a"), mk("b")], drain_on_preemption=False)
    fleet = FleetController(
        server, 3,
        [TenantPolicy("a", quota_qps=1000.0, ceiling_chips=2),
         TenantPolicy("b", chips=2, ceiling_chips=2)],
        dwell_s=1.0, interval_s=0.25, min_events=10)
    server.start(warm=True)
    grew0 = _c.FLEET_RESIZES.value(direction="grow") or 0
    rc = 1
    try:
        if args.chaos == "tenant_storm":
            per_row_s = 0.004            # ~250 rows/s/chip
            with schaos.chip_scaled_executor(server, "a", per_row_s), \
                    schaos.chip_scaled_executor(server, "b", per_row_s):
                fleet.start()
                out = schaos.tenant_storm(
                    server, "a", qps=400.0, duration_s=6.0,
                    victims={"b": 40.0}, threads=4,
                    collect_timeout_s=15.0)
                fleet.stop()
            grew = (_c.FLEET_RESIZES.value(direction="grow") or 0) - grew0
            victim = out["victims"]["b"]
            viol = sum(server.stats(m)["deadline_violations"]
                       for m in ("a", "b"))
            p99 = victim.get("p99_ms")
            ok = (grew >= 1 and viol == 0
                  and p99 is not None and p99 <= slo_ms)
            print("mxfleet selfcheck (tenant_storm): resizes(grow)=%d "
                  "victim_p99=%.1fms (slo %.0f) deadline_violations=%d "
                  "storm_ok=%d victim_ok=%d -> %s"
                  % (grew, p99 if p99 is not None else -1.0, slo_ms,
                     viol, out["storm"]["ok"], victim["ok"],
                     "PASS" if ok else "DEGRADED"), flush=True)
            rc = 0 if ok else 1
        else:
            # storm-free loop proof: manual resize round-trip + one
            # evaluator pass + admission still healthy
            plan = fleet.resize("b", 1)
            plan2 = fleet.resize("a", 2)
            out = server.predict("a", np.zeros(shape, "float32"))
            fleet.evaluate()
            stat = fleet.status()
            ok = (plan["direction"] == "shrink"
                  and plan2["direction"] == "grow"
                  and stat["models"]["a"]["chips"] == 2
                  and out.shape == (3,))
            print("mxfleet selfcheck: a=%d b=%d chips, history=%s -> %s"
                  % (stat["models"]["a"]["chips"],
                     stat["models"]["b"]["chips"],
                     [h["action"] for h in fleet.history()],
                     "PASS" if ok else "DEGRADED"), flush=True)
            rc = 0 if ok else 1
    finally:
        fleet.stop()
        server.close(timeout=10.0)
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="operate a multi-tenant serving fleet: placement "
                    "status, manual resize, closed-loop selfcheck")
    sub = ap.add_subparsers(dest="command", required=True)

    p = sub.add_parser("status", help="one /fleetz snapshot")
    p.add_argument("--url", default="http://127.0.0.1:8080")

    p = sub.add_parser("watch", help="poll /fleetz")
    p.add_argument("--url", default="http://127.0.0.1:8080")
    p.add_argument("--interval", type=float, default=2.0)
    p.add_argument("--count", type=int, default=30)

    p = sub.add_parser("resize", help="manual chip reassignment")
    p.add_argument("--url", default="http://127.0.0.1:8080")
    p.add_argument("--model", required=True)
    p.add_argument("--chips", type=int, required=True)

    p = sub.add_parser("selfcheck",
                       help="prove the control loop in-process")
    p.add_argument("--chaos", choices=("tenant_storm",), default=None)

    args = ap.parse_args(argv)

    if args.command == "status":
        return _cmd_status(args)
    if args.command == "watch":
        return _cmd_watch(args)
    if args.command == "resize":
        return _cmd_resize(args)
    return _cmd_selfcheck(args)


if __name__ == "__main__":
    sys.exit(main())
