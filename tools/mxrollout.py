#!/usr/bin/env python
"""mxrollout — operate safe model rollouts from the CLI.

The operator surface over ``mxnet_tpu.serving.rollout.RolloutManager``:
inspect a live rollout's ramp/gate state (``status`` / ``watch`` over
``GET /rolloutz``), drive the ladder by hand (``start`` / ``promote`` /
``rollback`` / ``abort`` over ``POST /rolloutz`` — typed refusals come
back as HTTP 409), and prove the whole gate loop in one process
(``selfcheck``: a rollout of the built-in tiny model whose canary is
deliberately broken by the ``bad_canary`` chaos injector, graded on
counter deltas — the gate must auto-roll it back with zero deadline
violations and the incumbent restored to 100% of traffic).

Usage::

    python tools/mxrollout.py status   --url http://127.0.0.1:8080
    python tools/mxrollout.py watch    --url ... --interval 2 --count 10
    python tools/mxrollout.py start    --url ... --model m --version v2 \\
        --params new.params --stage shadow
    python tools/mxrollout.py promote  --url ... --model m
    python tools/mxrollout.py rollback --url ... --model m --reason bad
    python tools/mxrollout.py abort    --url ... --model m
    python tools/mxrollout.py selfcheck
    python tools/mxrollout.py selfcheck --chaos skew   # or latency|fault

Exit codes (mxlint convention): 0 = healthy / action applied / selfcheck
proved the gate; 1 = degraded (a rollout rolled back or refused, an
action rejected, selfcheck failed its acceptance bars); 2 = cannot run
(no rollout surface at the URL, bad args, backend unavailable).
"""
import argparse
import base64
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)


def _get(url):
    import urllib.error
    import urllib.request
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.getcode(), json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        # a 404 here is a real answer (rollout mode off), not
        # unreachability — surface the body, don't re-raise
        return e.code, json.loads(e.read().decode() or "{}")


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
    """Print one rollout status document; returns True when healthy (no
    rollout rolled back / refused / flying with a failing gate)."""
    live = doc.get("live") or {}
    rollouts = doc.get("rollouts") or {}
    print("rollout: %d rollout(s) tracked  ladder=%s  live=%s"
          % (len(rollouts), "->".join(doc.get("stages") or []),
             ",".join("%s@%s" % kv for kv in sorted(live.items()))
             or "(all incumbent)"))
    healthy = True
    for name in sorted(rollouts):
        ro = rollouts[name]
        flag = ""
        if ro["state"] in ("rolled_back", "refused"):
            flag = "  << %s%s" % (ro["state"].upper(),
                                  " (%s)" % ro["last_reason"]
                                  if ro.get("last_reason") else "")
            healthy = False
        elif ro.get("last_reason"):
            flag = "  << GATE FAILING (%s)" % ro["last_reason"]
            healthy = False
        sh = ro.get("shadow") or {}
        agree = sh.get("agreement")
        print("  %-12s %s@%-10s stage=%-6s %4.0f%%  dwell=%gs "
              "shadow n=%-4d agree=%-6s auto=%d rollback=%d%s"
              % (name, ro["version"], "(" + ro["state"] + ")",
                 ro["stage"], 100.0 * ro["fraction"], ro["dwell_s"],
                 sh.get("n", 0),
                 ("%.3f" % agree) if agree is not None else "n/a",
                 int(bool(ro.get("auto"))),
                 int(bool(ro.get("rollback_enabled"))), flag))
        can = ro.get("canary")
        if can:
            print("    canary: tier=%s q=%d counts=%s p99=%s"
                  % (can.get("tier"), can.get("queue_depth", 0),
                     can.get("counts"),
                     ("%.1fms" % can["p99_ms"]) if "p99_ms" in can
                     else "n/a"))
        for h in (ro.get("history") or [])[-5:]:
            print("    %-10s stage=%-6s %s"
                  % (h["action"], h.get("stage", "-"),
                     h.get("reason", "")))
    return healthy


def _cmd_status(args) -> int:
    try:
        code, doc = _get(args.url.rstrip("/") + "/rolloutz")
    except Exception as e:
        sys.stderr.write("mxrollout: cannot reach %s: %r\n"
                         % (args.url, e))
        return 2
    if code == 404 or "rollouts" not in doc:
        sys.stderr.write("mxrollout: no rollout manager at %s (rollout "
                         "mode off)\n" % args.url)
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


def _cmd_action(args) -> int:
    doc = {"action": args.command, "model": args.model}
    if args.command == "start":
        doc["version"] = args.version
        if args.stage:
            doc["stage"] = args.stage
        if args.tier:
            doc["tier"] = args.tier
        if args.params:
            try:
                with open(args.params, "rb") as f:
                    doc["param_b64"] = base64.b64encode(
                        f.read()).decode()
            except OSError as e:
                sys.stderr.write("mxrollout: cannot read %s: %r\n"
                                 % (args.params, e))
                return 2
        if args.symbol:
            try:
                with open(args.symbol) as f:
                    doc["symbol_json"] = f.read()
            except OSError as e:
                sys.stderr.write("mxrollout: cannot read %s: %r\n"
                                 % (args.symbol, e))
                return 2
        if args.knob:
            knobs = {}
            for kv in args.knob:
                k, _, v = kv.partition("=")
                try:
                    knobs[k] = json.loads(v)
                except ValueError:
                    knobs[k] = v
            doc["knobs"] = knobs
    elif args.command == "rollback":
        doc["reason"] = args.reason
    try:
        code, out = _post(args.url.rstrip("/") + "/rolloutz", doc)
    except Exception as e:
        sys.stderr.write("mxrollout: cannot reach %s: %r\n"
                         % (args.url, e))
        return 2
    if code == 200:
        print("mxrollout: %s %r -> version=%s state=%s stage=%s (%.0f%%)"
              % (args.command, args.model, out.get("version"),
                 out.get("state"), out.get("stage"),
                 100.0 * (out.get("fraction") or 0.0)))
        return 0
    if code == 409:
        sys.stderr.write("mxrollout: %s REFUSED (typed %s): %s\n"
                         % (args.command, out.get("type"),
                            out.get("error")))
        return 1
    sys.stderr.write("mxrollout: %s failed (%d): %s\n"
                     % (args.command, code, out.get("error")))
    return 2


def _cmd_selfcheck(args) -> int:
    """Prove the gate loop in-process: roll out a deliberately broken
    canary of the tiny model (the ``bad_canary`` chaos injector: skewed
    answers, a latency storm, or deterministic faults) under load. The
    verdict reads counter deltas: the gate must auto-roll the canary
    back (rollbacks counter bumped with the right reason), the incumbent
    must never dispatch past a deadline (deadline_violations == 0), and
    fresh traffic must land 100% on the restored incumbent."""
    try:
        import numpy as np

        from mxnet_tpu.observability import catalog as _c
        from mxnet_tpu.serving import chaos as schaos
        from mxnet_tpu.serving import load as sload
        from mxnet_tpu.serving.rollout import RolloutManager
        from mxnet_tpu.serving.server import ModelConfig, ModelServer
    except Exception as e:
        sys.stderr.write("mxrollout: cannot import the backend: %r\n" % e)
        return 2

    mode = args.chaos or "skew"
    sym, params, shape, _ = sload.tiny_model()
    _, params2, _, _ = sload.tiny_model(seed=1)
    cfg = ModelConfig("m", sym, params, feature_shape=shape,
                      buckets=(1, 2, 4, 8), max_queue=64,
                      deadline_ms=2000.0, max_wait_ms=2.0,
                      trace_sample=0.05)
    server = ModelServer([cfg], drain_on_preemption=False).start(warm=True)
    reasons = {"skew": ("agreement",),
               "latency": ("p99_delta", "slo_burn"),
               "fault": ("error_rate", "breaker")}[mode]
    rb0 = {r: _c.ROLLOUT_ROLLBACKS.value(reason=r) or 0 for r in reasons}
    rc = 1
    try:
        mgr = RolloutManager.attach(server)
        # skew is caught in shadow (no client exposure at all); latency
        # and faults need canary traffic, so enter at the 50%/10% rung
        stage = {"skew": "shadow", "latency": "50", "fault": "10"}[mode]
        ro = mgr.start("m", "v2", param_bytes=params2, stage=stage,
                       dwell_s=60.0,
                       shadow_sample=0.6 if mode == "skew" else 0.0)
        t0 = time.monotonic()
        while ro.state == "loading" and time.monotonic() - t0 < 30:
            time.sleep(0.02)
        if ro.state != "serving":
            sys.stderr.write("mxrollout: canary failed to load: %s\n"
                             % ro.status())
            return 2
        rng = np.random.RandomState(0)
        mk = lambda: rng.randn(*shape).astype(np.float32)
        with schaos.bad_canary(server, "m", mode=mode, delay=0.05):
            t0 = time.monotonic()
            while ro.state == "serving" and time.monotonic() - t0 < 30:
                futs = [server.submit("m", mk()) for _ in range(20)]
                for f in futs:
                    try:
                        f.result(30.0)
                    except Exception:
                        pass            # canary faults are the point
        rolled = ro.state == "rolled_back"
        reason = ro.last_reason
        bumped = any((_c.ROLLOUT_ROLLBACKS.value(reason=r) or 0)
                     - rb0[r] >= 1 for r in reasons)
        # restored: fresh traffic 100% incumbent, all ok
        ok_after = 0
        for f in [server.submit("m", mk()) for _ in range(20)]:
            try:
                f.result(30.0)
                ok_after += 1
            except Exception:
                pass
        viol = server.stats("m")["deadline_violations"]
        ok = (rolled and reason in reasons and bumped
              and ok_after == 20 and viol == 0)
        print("mxrollout selfcheck (bad_canary %s): state=%s reason=%s "
              "rollback_counter=%d incumbent_ok_after=%d/20 "
              "deadline_violations=%d -> %s"
              % (mode, ro.state, reason, int(bumped), ok_after, viol,
                 "PASS" if ok else "DEGRADED"), flush=True)
        rc = 0 if ok else 1
    finally:
        server.close(timeout=10.0)
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="operate safe model rollouts: ramp status, operator "
                    "ladder actions, gate-loop selfcheck")
    sub = ap.add_subparsers(dest="command", required=True)

    p = sub.add_parser("status", help="one /rolloutz snapshot")
    p.add_argument("--url", default="http://127.0.0.1:8080")

    p = sub.add_parser("watch", help="poll /rolloutz")
    p.add_argument("--url", default="http://127.0.0.1:8080")
    p.add_argument("--interval", type=float, default=2.0)
    p.add_argument("--count", type=int, default=30)

    p = sub.add_parser("start", help="begin rolling a version out")
    p.add_argument("--url", default="http://127.0.0.1:8080")
    p.add_argument("--model", required=True)
    p.add_argument("--version", required=True)
    p.add_argument("--params", help="candidate .params file")
    p.add_argument("--symbol", help="candidate symbol json file")
    p.add_argument("--tier", choices=("f32", "int8"))
    p.add_argument("--stage", help="entry stage (default shadow)")
    p.add_argument("--knob", action="append",
                   help="knob override, e.g. --knob dwell_s=5")

    for name, hlp in (("promote", "advance the ramp one stage"),
                      ("abort", "cancel the rollout")):
        p = sub.add_parser(name, help=hlp)
        p.add_argument("--url", default="http://127.0.0.1:8080")
        p.add_argument("--model", required=True)

    p = sub.add_parser("rollback", help="roll the canary back")
    p.add_argument("--url", default="http://127.0.0.1:8080")
    p.add_argument("--model", required=True)
    p.add_argument("--reason", default="operator")

    p = sub.add_parser("selfcheck",
                       help="prove the gate loop in-process")
    p.add_argument("--chaos", choices=("skew", "latency", "fault"),
                   default=None)

    args = ap.parse_args(argv)

    if args.command == "status":
        return _cmd_status(args)
    if args.command == "watch":
        return _cmd_watch(args)
    if args.command in ("start", "promote", "rollback", "abort"):
        return _cmd_action(args)
    return _cmd_selfcheck(args)


if __name__ == "__main__":
    sys.exit(main())
