#!/usr/bin/env python
"""mxopt — graph-pass pipeline CLI (mxnet_tpu.passes).

Runs an optimizing pass pipeline over a symbol graph — a saved
``Symbol.tojson`` file, a ``pkg.mod:factory`` returning a Symbol, or a
model-zoo net — and reports per-pass rewrite counts plus before/after
mxlint summaries.  The write-half companion to ``tools/mxlint.py``.

Usage::

    python tools/mxopt.py model-symbol.json --shape data:64,3,224,224
    python tools/mxopt.py --model resnet50 --batch 64
    python tools/mxopt.py graph.json --passes layout,fusion --emit out.json
    python tools/mxopt.py graph.json --format json

Serialized graphs additionally get dead-node elimination for free: nodes
unreachable from any head (mxlint MXL-G106's finding) are dropped on the
``--emit`` round trip, and the count is reported.

Variable re-homing is OFF by default (a rewritten JSON must stay loadable
against the original parameter files); ``--rehome`` enables it and reports
the per-variable value transforms a checkpoint converter would apply.

Exit codes (mxlint convention): 0 = pipeline ran and the rewritten graph
lints clean at/above ``--fail-on``, 1 = findings remain, 2 = the target
could not be loaded / the pipeline could not run.
"""
import argparse
import importlib
import importlib.util
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _resolve(target):
    if ":" in target:
        mod_part, obj_part = target.rsplit(":", 1)
    else:
        mod_part, obj_part = target, None
    if mod_part.endswith(".py") or os.path.sep in mod_part:
        name = os.path.splitext(os.path.basename(mod_part))[0]
        spec = importlib.util.spec_from_file_location(name, mod_part)
        if spec is None:
            raise ImportError(f"cannot load {mod_part!r}")
        mod = importlib.util.module_from_spec(spec)
        sys.modules.setdefault(name, mod)
        spec.loader.exec_module(mod)
    else:
        mod = importlib.import_module(mod_part)
    if obj_part is None:
        return mod
    obj = mod
    for part in obj_part.split("."):
        obj = getattr(obj, part)
    return obj


def _parse_shapes(specs):
    shapes = {}
    for spec in specs or ():
        name, _, dims = spec.partition(":")
        if not dims:
            raise ValueError(f"bad --shape {spec!r} (want name:d1,d2,...)")
        shapes[name.strip()] = tuple(int(d) for d in dims.split(","))
    return shapes


def _zoo_symbol(model, batch, image, classes):
    """Trace a model-zoo net (NCHW) into a Symbol + input shapes."""
    import mxnet_tpu as mx
    from mxnet_tpu import symbol as sym_mod
    from mxnet_tpu.gluon.model_zoo import vision
    factory = getattr(vision, model, None)
    if factory is None:
        raise ValueError(f"unknown model-zoo net {model!r}")
    mx.random.seed(0)
    net = factory(classes=classes)
    net.initialize(mx.init.Xavier())
    import numpy as np
    from mxnet_tpu import nd
    x = np.zeros((batch, 3, image, image), dtype="float32")
    net(nd.array(x))                       # materialize deferred params
    data = sym_mod.Variable("data")
    out = net(data)
    if isinstance(out, (list, tuple)):
        out = out[0]
    shapes = {"data": (batch, 3, image, image)}
    for p in net.collect_params().values():
        shapes[p.name] = tuple(p.shape)
    return out, shapes, {p.name for p in net.collect_params().values()}


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="run an optimizing graph-pass pipeline over a symbol "
                    "graph and report rewrites + lint before/after")
    ap.add_argument("target", nargs="?", default=None,
                    help="saved symbol .json, or pkg.mod:factory returning "
                         "a Symbol (omit with --model)")
    ap.add_argument("--model", default=None,
                    help="model-zoo net to trace instead of a target "
                         "(e.g. resnet50_v1, resnet18_v1)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--classes", type=int, default=1000)
    ap.add_argument("--passes", default=None,
                    help="pipeline spec (MXNET_PASSES grammar), e.g. "
                         "'layout,fusion' or '-fold'; default = the "
                         "default pipeline")
    ap.add_argument("--shape", action="append", metavar="NAME:D1,D2,...",
                    help="input shapes (like simple_bind kwargs); "
                         "repeatable")
    ap.add_argument("--input-layout", choices=("NHWC",), default=None,
                    help="declare channel-last feeds: rank-4 inputs are "
                         "re-homed instead of transposed in-graph")
    ap.add_argument("--rehome", action="store_true",
                    help="allow variable re-homing (NHWC weights); "
                         "reports the value transforms")
    ap.add_argument("--emit", default=None, metavar="PATH",
                    help="write the rewritten graph JSON")
    ap.add_argument("--suppress", action="append", default=[],
                    help="mxlint rule ids to suppress in the reports")
    ap.add_argument("--fail-on", choices=("info", "warning", "error"),
                    default="error")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    args = ap.parse_args(argv)

    try:
        from mxnet_tpu import analysis, passes
        from mxnet_tpu import symbol as sym_mod
    except Exception as e:
        sys.stderr.write("mxopt: cannot import mxnet_tpu: %r\n" % e)
        return 2

    dead_nodes = 0
    param_names = None
    try:
        shapes = _parse_shapes(args.shape)
        if args.model:
            sym, zoo_shapes, param_names = _zoo_symbol(
                args.model, args.batch, args.image, args.classes)
            zoo_shapes.update(shapes)
            shapes = zoo_shapes
        elif args.target and args.target.endswith(".json"):
            with open(args.target) as f:
                raw = f.read()
            data = json.loads(raw)
            if isinstance(data, dict) and "nodes" in data:
                # load_json -> tojson keeps only head-reachable nodes:
                # dead-node elimination is structural on this path
                reach = set()
                stack = [h[0] for h in data.get("heads", [])]
                while stack:
                    i = stack.pop()
                    if i in reach:
                        continue
                    reach.add(i)
                    stack.extend(s for (s, _i, _v)
                                 in data["nodes"][i].get("inputs", []))
                dead_nodes = len(data["nodes"]) - len(reach)
            sym = sym_mod.load_json(raw)
        elif args.target:
            obj = _resolve(args.target)
            sym = obj() if callable(obj) else obj
        else:
            ap.error("need a target or --model")
            return 2
        mgr = passes.PassManager(args.passes,
                                 input_layout=args.input_layout,
                                 rehome_params=bool(args.rehome))
    except Exception as e:
        sys.stderr.write("mxopt: %s\n" % e)
        return 2

    input_vars = tuple(n for n in shapes
                       if param_names is None or n not in param_names)
    lint_before = analysis.lint_symbol(
        sym, shapes=shapes, suppress=args.suppress,
        passes_applied=(), subject="before passes")
    try:
        res = mgr.run(sym, shapes=shapes, input_vars=input_vars,
                      param_names=param_names)
    except Exception as e:
        sys.stderr.write("mxopt: pipeline failed: %s\n" % e)
        return 2
    # lint the rewritten graph with the re-homed shapes (shape math only)
    after_shapes = res.transformed_shapes(shapes)
    lint_after = analysis.lint_symbol(
        res.symbol, shapes=after_shapes, suppress=args.suppress,
        passes_applied=res.names, subject="after passes")

    if args.emit:
        with open(args.emit, "w") as f:
            f.write(res.symbol.tojson())

    report = {
        "pipeline": list(res.names),
        "rewrites": res.counts,
        "total_rewrites": res.total_rewrites,
        "dead_nodes_eliminated": dead_nodes,
        "var_transforms": {k: [s[0] for s in v]
                           for k, v in res.var_transforms.items()},
        "input_layouts": res.input_layouts,
        "lint_before": {"errors": len(lint_before.errors),
                        "warnings": len(lint_before.warnings)},
        "lint_after": {"errors": len(lint_after.errors),
                       "warnings": len(lint_after.warnings)},
    }
    if args.emit:
        report["emitted"] = args.emit
    if args.format == "json":
        print(json.dumps(report, indent=1, sort_keys=True))
    else:
        print("mxopt: pipeline %s" % (",".join(res.names) or "(empty)"))
        for name in res.names:
            print("  %-8s %d rewrite(s)" % (name, res.counts.get(name, 0)))
        if dead_nodes:
            print("  dead-node elimination: %d node(s) dropped" % dead_nodes)
        if res.var_transforms:
            print("  re-homed variables:")
            for k, v in sorted(res.var_transforms.items()):
                print("    %s: %s" % (k, " -> ".join(s[0] for s in v)))
        if res.input_layouts:
            print("  input layouts: %s" % res.input_layouts)
        print("lint before: %d error(s), %d warning(s)"
              % (len(lint_before.errors), len(lint_before.warnings)))
        print("lint after : %d error(s), %d warning(s)"
              % (len(lint_after.errors), len(lint_after.warnings)))
        if args.emit:
            print("emitted -> %s" % args.emit)
    return 0 if lint_after.ok(args.fail_on) else 1


if __name__ == "__main__":
    sys.exit(main())
