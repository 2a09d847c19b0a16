#!/usr/bin/env python
"""mxquant — the calibrate → quantize → compare CLI (mxnet_tpu.quant).

The reference flow of ``example/quantization/imagenet_gen_qsym.py`` as
three composable subcommands over the pass-route quantizer:

Usage::

    # 1. calibrate: run the fp32 model over synthetic/calib batches and
    #    write a CalibTable JSON artifact
    python tools/mxquant.py calibrate --model model.json --params m.params \
        --feature-shape 3,224,224 --batches 4 --mode entropy --out calib.json

    # 2. quantize: rewrite through the quantize/requantize/dequantize
    #    passes (first/last-layer exclusion defaults) and emit the int8
    #    symbol + params
    python tools/mxquant.py quantize --model model.json --params m.params \
        --feature-shape 3,224,224 --table calib.json \
        --emit model-int8.json --emit-params model-int8.params

    # 3. compare: int8-vs-f32 latency + top-1 agreement, persisting a
    #    label="quant" CostLedger row the tuner/perfwatch/mxlint can read
    python tools/mxquant.py compare --model model.json --params m.params \
        --feature-shape 3,224,224 --steps 10 --eval-samples 64

``--model tiny`` everywhere uses the built-in demo convnet (deterministic
weights, synthetic data) — the hermetic self-test target.

Exit codes (mxlint convention): 0 = ok (quantized nodes > 0, agreement
within ``--acc-tol``), 1 = degraded (nothing quantized / agreement beyond
tolerance), 2 = cannot run (bad args, model fails to load).

Everything runs in this process on the default backend (the chip when
the host has one, else the CPU).
"""
import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)


def _tiny_convnet():
    """Deterministic demo net: conv -> relu -> fc -> relu -> fc, weights
    from a fixed seed. Returns (sym, arg_params, feature_shape)."""
    import numpy as np
    import mxnet_tpu as mx

    rng = np.random.RandomState(0)
    data = mx.sym.Variable("data")
    c = mx.sym.Convolution(data, kernel=(3, 3), num_filter=4, pad=(1, 1),
                           name="conv0")
    r = mx.sym.Activation(c, act_type="relu")
    f = mx.sym.FullyConnected(mx.sym.Flatten(r), num_hidden=8, name="fc0")
    r2 = mx.sym.Activation(f, act_type="relu")
    out = mx.sym.FullyConnected(r2, num_hidden=3, name="fc1")
    arg = {
        "conv0_weight": mx.nd.array(rng.randn(4, 1, 3, 3).astype("f4") * .5),
        "conv0_bias": mx.nd.array(rng.randn(4).astype("f4") * .1),
        "fc0_weight": mx.nd.array(rng.randn(8, 144).astype("f4") * .1),
        "fc0_bias": mx.nd.array(rng.randn(8).astype("f4") * .1),
        "fc1_weight": mx.nd.array(rng.randn(3, 8).astype("f4") * .3),
        "fc1_bias": mx.nd.array(rng.randn(3).astype("f4") * .1),
    }
    return out, arg, {}, (1, 6, 6)


def _load_model(args):
    """-> (sym, arg_params, aux_params, feature_shape)."""
    import mxnet_tpu as mx

    if args.model == "tiny":
        return _tiny_convnet()
    if not args.feature_shape:
        raise ValueError("--feature-shape is required for a model file")
    feat = tuple(int(t) for t in args.feature_shape.split(",") if t.strip())
    with open(args.model) as f:
        sym = mx.sym.load_json(f.read())
    arg, aux = {}, {}
    if args.params:
        # one param-file decoder for every CLI (prefix splitting + the
        # legacy nd_utils fallback): predict_bridge._load_param_bytes
        from mxnet_tpu.native.predict_bridge import _load_param_bytes
        with open(args.params, "rb") as f:
            arg, aux = _load_param_bytes(f.read())
    return sym, arg, aux, feat


def _batches(feat, batch, n, seed=0):
    import numpy as np

    class _B:
        def __init__(self, x):
            import mxnet_tpu as mx
            self.data = [mx.nd.array(x)]

    rng = np.random.RandomState(seed)
    return [_B(rng.randn(batch, *feat).astype("float32")) for _ in range(n)]


def _quant_kwargs(args):
    excluded = tuple(t for t in (args.exclude or "").split(",") if t.strip())
    return dict(excluded_sym_names=excluded,
                exclude_first_conv=not args.no_exclude_first_conv,
                exclude_last_fc=not args.no_exclude_last_fc)


def cmd_calibrate(args) -> int:
    from mxnet_tpu import quant
    sym, arg, aux, feat = _load_model(args)
    table = quant.collect(sym, arg, aux,
                          _batches(feat, args.batch, args.batches),
                          mode=args.mode, model=args.name or args.model)
    table.save(args.out)
    print("mxquant: calibrated %d tensor range(s) over %d example(s) "
          "(mode=%s) -> %s" % (len(table), table.num_examples, table.mode,
                               args.out))
    return 0


def cmd_quantize(args) -> int:
    from mxnet_tpu import interop, quant
    sym, arg, aux, feat = _load_model(args)
    table = quant.CalibTable.load(args.table) if args.table else None
    qsym, qarg, qaux, _ = quant.quantize_model(
        sym, arg, aux, table=table, calib_mode="none",
        model=args.name or args.model, **_quant_kwargs(args))
    n = sum(1 for nn in qsym.topo_nodes()
            if not nn.is_var and nn.op in quant.ACC_OPS)
    if args.emit:
        with open(args.emit, "w") as f:
            f.write(qsym.tojson())
    if args.emit_params:
        live = set(qsym.list_arguments())
        params = {"arg:%s" % k: v for k, v in qarg.items() if k in live}
        params.update({"aux:%s" % k: v for k, v in qaux.items()})
        interop.save_reference_params(args.emit_params, params)
    print("mxquant: %d node(s) quantized%s%s"
          % (n, " -> %s" % args.emit if args.emit else "",
             " (params -> %s)" % args.emit_params if args.emit_params
             else ""))
    if n == 0:
        print("mxquant: nothing quantized (exclusions removed every "
              "candidate?)", file=sys.stderr)
        return 1
    return 0


def cmd_compare(args) -> int:
    import numpy as np
    from mxnet_tpu import quant
    from mxnet_tpu.observability import xcost

    sym, arg, aux, feat = _load_model(args)
    table = quant.CalibTable.load(args.table) if args.table else None
    calib = None if table is not None else \
        _batches(feat, args.batch, args.batches)
    qsym, qarg, qaux, table = quant.quantize_model(
        sym, arg, aux, table=table, calib_iter=calib, calib_mode=args.mode,
        model=args.name or args.model, **_quant_kwargs(args))
    n = sum(1 for nn in qsym.topo_nodes()
            if not nn.is_var and nn.op in quant.ACC_OPS)
    if n == 0:
        print("mxquant: nothing quantized — no comparison to run",
              file=sys.stderr)
        return 1
    # held-out eval batches (different seed than calibration)
    evals = _batches(feat, args.batch,
                     max(1, args.eval_samples // args.batch), seed=1)
    acc = quant.evaluate_agreement(sym, arg, aux, qsym, qarg, qaux, evals)
    ledger = xcost.CostLedger(args.ledger) if args.ledger else None
    x = np.random.RandomState(2).randn(args.batch, *feat).astype("float32")
    row = quant.compare_latency(
        sym, arg, aux, qsym, qarg, qaux, x, steps=args.steps,
        ledger=ledger, model=args.name or args.model, quantized_nodes=n,
        extra={"fp32_acc": acc["fp32_acc"], "int8_acc": acc["int8_acc"],
               "acc_delta": acc["acc_delta"], "eval_n": acc["n"]})
    print(json.dumps(row, sort_keys=True))
    if acc["acc_delta"] > args.acc_tol:
        print("mxquant: DEGRADED — int8 top-1 within %.4f of fp32 required,"
              " got delta %.4f over %d sample(s)"
              % (args.acc_tol, acc["acc_delta"], acc["n"]), file=sys.stderr)
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="mxquant",
        description="calibrate / quantize / compare a model through the "
                    "int8 pass pipeline (mxnet_tpu.quant)")
    sub = ap.add_subparsers(dest="cmd", required=True)

    def common(p):
        p.add_argument("--model", required=True,
                       help="symbol JSON path, or 'tiny' for the built-in "
                            "demo convnet")
        p.add_argument("--params", default=None,
                       help="parameter file (reference .params format)")
        p.add_argument("--feature-shape", default=None,
                       help="per-sample input shape, e.g. 3,224,224 "
                            "(required unless --model tiny)")
        p.add_argument("--name", default=None,
                       help="model signature stamped into tables/rows")
        p.add_argument("--batch", type=int, default=8)
        p.add_argument("--mode", choices=("naive", "entropy"),
                       default="naive",
                       help="calibration estimator (docs/quantization.md)")

    def quant_knobs(p):
        p.add_argument("--table", default=None,
                       help="CalibTable JSON from 'calibrate'")
        p.add_argument("--exclude", default="",
                       help="comma list of node names to keep in float")
        p.add_argument("--no-exclude-first-conv", action="store_true",
                       help="quantize the first conv too (reference "
                            "default keeps it float)")
        p.add_argument("--no-exclude-last-fc", action="store_true",
                       help="quantize the classifier head too")

    pc = sub.add_parser("calibrate", help="collect a CalibTable")
    common(pc)
    pc.add_argument("--batches", type=int, default=2,
                    help="synthetic calibration batches")
    pc.add_argument("--out", required=True, help="CalibTable JSON path")
    pc.set_defaults(fn=cmd_calibrate)

    pq = sub.add_parser("quantize", help="rewrite to int8 via the passes")
    common(pq)
    quant_knobs(pq)
    pq.add_argument("--emit", default=None, help="quantized symbol JSON")
    pq.add_argument("--emit-params", default=None,
                    help="quantized params file")
    pq.set_defaults(fn=cmd_quantize)

    pm = sub.add_parser("compare",
                        help="int8 vs f32 latency + agreement, ledger row")
    common(pm)
    quant_knobs(pm)
    pm.add_argument("--batches", type=int, default=2,
                    help="synthetic calibration batches (no --table)")
    pm.add_argument("--steps", type=int, default=5,
                    help="timed forwards per variant")
    pm.add_argument("--eval-samples", type=int, default=64)
    pm.add_argument("--acc-tol", type=float, default=0.01,
                    help="max tolerated fp32-minus-int8 top-1 delta "
                         "(the ~1%% acceptance bar)")
    pm.add_argument("--ledger", default=None,
                    help="CostLedger path (default: the tuner cache)")
    pm.set_defaults(fn=cmd_compare)

    args = ap.parse_args(argv)

    try:
        return args.fn(args)
    except SystemExit:
        raise
    except Exception as e:
        print("mxquant: cannot run %s: %s: %s"
              % (args.cmd, type(e).__name__, e), file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
