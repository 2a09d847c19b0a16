"""Graph-pass manager — optimizing rewrites over symbol graphs.

``analysis/`` is the read-only half of the compiler-pass framework
(mxlint); this package is the write half: Relay/TVM-style rewrite passes
that turn the measured perf levers (NHWC layout, constant folding,
fusion-friendly reordering) into automatic defaults every captured graph
inherits.  ``Module`` and
:class:`~mxnet_tpu.parallel.DataParallelTrainer` run the default pipeline
unless constructed with ``passes=False``; ``MXNET_PASSES`` tunes it;
``tools/mxopt.py`` is the CLI.  Catalog: docs/passes.md.

    from mxnet_tpu import passes
    res = passes.PassManager().run(sym, shapes={"data": (8, 3, 224, 224)})
    res.symbol          # the rewritten graph
    res.counts          # per-pass rewrite counts
    res.var_transforms  # value transforms for re-homed parameters
"""
from .manager import (Pass, PassContext, PassManager, PassResult,
                      DEFAULT_PIPELINE, PASS_REGISTRY, register_pass,
                      default_names, resolve, annotate_graph, apply_spec,
                      spec_shape, provenance)
# importing the pass modules populates PASS_REGISTRY
from .fold import ConstantFoldPass
from .layout import LayoutPass
from .fusion import FusionReorderPass
# the quantization passes register too (names: quantize/requantize/
# dequantize) but stay OPT-IN — quantization changes numerics, so they are
# never part of DEFAULT_PIPELINE.  Imported as a module (not names) so the
# quant→passes→quant import cycle resolves in either entry order;
# mxnet_tpu.quant is the driving surface for these passes.
from ..quant import qpass as _quant_qpass  # noqa: F401

__all__ = ["Pass", "PassContext", "PassManager", "PassResult",
           "DEFAULT_PIPELINE", "PASS_REGISTRY", "register_pass",
           "default_names", "resolve", "annotate_graph", "apply_spec",
           "spec_shape", "provenance",
           "ConstantFoldPass", "LayoutPass", "FusionReorderPass"]
