#!/usr/bin/env python
"""mxserve — run the overload-safe batching model server from the CLI.

Serves a saved symbol + params through ``mxnet_tpu.serving.ModelServer``
(dynamic batching over a bucketed executable cache, admission control,
per-request deadlines, circuit breaker) with /healthz /readyz /predict on
a local HTTP port. SIGTERM drains: in-flight batches finish, the queue
rejects new work, then the process exits 0 — exactly what a rolling
restart wants.

Usage::

    # serve a model file
    python tools/mxserve.py --model model-symbol.json --params model.params \
        --name resnet --feature-shape 3,224,224 --port 8080

    # built-in tiny model (demos, loadgen targets)
    python tools/mxserve.py --model tiny --port 8080

    # no server left behind: one in-process smoke of the full batching
    # path (admission -> batcher -> bucket executor -> drain)
    python tools/mxserve.py --model tiny --selfcheck 16

Exit codes (mxlint convention): 0 = served and drained cleanly /
selfcheck fully ok, 1 = selfcheck degraded (some requests failed), 2 =
cannot run (bad args, model fails to load).
"""
import argparse
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="batching model server with admission control, "
                    "deadlines and graceful degradation")
    ap.add_argument("--model", required=True,
                    help="symbol JSON path, or 'tiny' for the built-in "
                         "demo MLP")
    ap.add_argument("--params", default=None,
                    help="parameter file (reference .params or native "
                         "format); required unless --model tiny")
    ap.add_argument("--name", default=None,
                    help="model name to serve under (default: file stem)")
    ap.add_argument("--feature-shape", default=None,
                    help="per-sample input shape, e.g. 3,224,224 "
                         "(required unless --model tiny)")
    ap.add_argument("--input-name", default="data")
    ap.add_argument("--buckets", default=None,
                    help="comma list of padded-batch buckets (default: "
                         "tuner cache / MXNET_SERVE_BUCKETS / 1,2,...,32)")
    ap.add_argument("--max-queue", type=int, default=None)
    ap.add_argument("--deadline-ms", type=float, default=None)
    ap.add_argument("--max-wait-ms", type=float, default=None)
    ap.add_argument("--port", type=int, default=8080,
                    help="HTTP port for /healthz /readyz /predict "
                         "(0 = ephemeral)")
    ap.add_argument("--no-warm", action="store_true",
                    help="skip compiling every bucket at startup")
    ap.add_argument("--selfcheck", type=int, nargs="?", const=16, default=None,
                    metavar="N",
                    help="serve N smoke requests through the full batching "
                         "path in-process, drain, and exit (no HTTP)")
    ap.add_argument("--chaos", choices=("executor_fault", "device_lost"),
                    default=None,
                    help="selfcheck only: inject a deterministic executor "
                         "fault (degraded exit path) or a DEVICE_LOST "
                         "chip failure (quarantine + re-placement + "
                         "re-dispatch self-healing path)")
    args = ap.parse_args(argv)

    try:
        from mxnet_tpu.serving import ModelServer, ServingEndpoints
        from mxnet_tpu.serving import load as sload
    except Exception as e:
        sys.stderr.write("mxserve: cannot import the backend: %r\n" % e)
        return 2

    try:
        cfg = sload.model_config_from_files(
            args.model, params=args.params,
            feature_shape=args.feature_shape, name=args.name,
            input_name=args.input_name, buckets=args.buckets,
            max_queue=args.max_queue, deadline_ms=args.deadline_ms,
            max_wait_ms=args.max_wait_ms)
    except Exception as e:
        sys.stderr.write("mxserve: cannot load the model: %r\n" % e)
        return 2

    # lint the config before serving — an unbounded queue or missing
    # deadline is exactly the misconfiguration MXL-T214 exists for
    try:
        from mxnet_tpu import analysis
        report = analysis.lint_server(cfg)
        for d in report:
            sys.stderr.write("mxserve: %s\n" % d.render())
    except Exception:
        pass

    try:
        server = ModelServer([cfg]).start(warm=not args.no_warm)
    except Exception as e:
        sys.stderr.write("mxserve: server failed to start: %r\n" % e)
        return 2

    if args.selfcheck is not None:
        return _selfcheck(server, cfg, args.selfcheck, args.chaos)

    endpoints = ServingEndpoints(server, port=args.port).start()
    print("mxserve: serving %r on http://127.0.0.1:%d  "
          "(buckets=%s via %s, max_queue=%d, deadline_ms=%g)"
          % (cfg.name, endpoints.port, list(cfg.buckets),
             cfg.bucket_provenance, cfg.max_queue, cfg.deadline_ms),
          flush=True)
    try:
        # the server's PreemptionGuard turns SIGTERM into begin_drain();
        # we just wait for readiness to drop, then finish the drain
        while server.ready():
            time.sleep(0.2)
        print("mxserve: draining (in-flight batches finish, queue "
              "rejects new work)", flush=True)
    except KeyboardInterrupt:
        server.begin_drain()
    finally:
        drained = server.close(timeout=30.0)
        endpoints.stop()
    print("mxserve: drained=%s" % drained, flush=True)
    return 0 if drained else 1


def _selfcheck(server, cfg, n, chaos_mode) -> int:
    import contextlib

    import numpy as np

    from mxnet_tpu.serving import chaos as schaos

    rng = np.random.RandomState(7)
    if chaos_mode == "executor_fault":
        inject = schaos.executor_fault(server, cfg.name, faults=1 << 30,
                                       transient=False)
    elif chaos_mode == "device_lost":
        inject = schaos.device_lost(server, cfg.name, chip_idx=0)
    else:
        inject = contextlib.nullcontext()
    futures = []
    with inject as chaos_stats:
        for _ in range(max(1, int(n))):
            futures.append(server.submit(
                cfg.name, rng.randn(*cfg.feature_shape).astype("float32")))
        ok = bad = 0
        for f in futures:
            try:
                f.result(timeout=30.0)
                ok += 1
            except Exception:
                bad += 1
    server.close(timeout=10.0)
    stats = server.stats(cfg.name)
    print("mxserve selfcheck: ok=%d failed=%d batches=%d counts=%s"
          % (ok, bad, stats["batches"], stats["counts"]), flush=True)
    if chaos_mode == "device_lost":
        sent = stats.get("sentinel") or {}
        print("mxserve selfcheck: device_lost chip=%d faulted=%d "
              "passed=%d quarantined=%s degraded_rung=%d"
              % (chaos_stats["chip"], chaos_stats["faulted"],
                 chaos_stats["passed"],
                 sorted((sent.get("quarantined") or {}).keys()),
                 stats.get("degraded_rung", 0)), flush=True)
        # the self-healing bar: the chip was actually lost, the sentinel
        # quarantined it, and the re-dispatched requests still answered
        if not chaos_stats["faulted"] or not sent.get("quarantined"):
            return 1
    return 0 if bad == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
