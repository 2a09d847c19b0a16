"""Finite-difference gradient sweep over the whole differentiable op registry.

Reference parity: ``tests/python/unittest/test_operator.py`` (~7k lines of
numeric-vs-numpy + check_numeric_gradient finite-difference checks driven by
``python/mxnet/test_utils.py``). One parametrized test per unique
differentiable OpDef: analytic autograd gradients vs central differences.

Per-op SPEC entries provide shapes/attrs where the defaults don't apply,
pin non-differentiable inputs (integer indices, labels, aux state) so the
checker only perturbs real float inputs, and pick samplers that keep inputs
away from kinks (|x| in [0.3, 1] for relu-likes) and inside op domains
(arccosh needs x > 1, potrf needs SPD, ...).

Output-layer ops (SoftmaxOutput/SVMOutput/regression outputs/make_loss)
define backward as the LOSS gradient while forward emits predictions, so
finite differences of the forward cannot match by design — they get
closed-form analytic checks at the bottom instead of the sweep.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.ops import registry as _registry
from mxnet_tpu.test_utils import check_numeric_gradient


# ---------------------------------------------------------------------------
# samplers
# ---------------------------------------------------------------------------

def u(*shape, low=-1.0, high=1.0):
    """Uniform sampler factory."""
    def gen(rng):
        return rng.uniform(low, high, size=shape).astype("float32")
    return gen


def away0(*shape, lo=0.3, hi=1.0):
    """Magnitudes in [lo, hi] with random sign — keeps FD off kinks at 0."""
    def gen(rng):
        mag = rng.uniform(lo, hi, size=shape)
        return (mag * rng.choice([-1.0, 1.0], size=shape)).astype("float32")
    return gen


def spread(*shape, step=0.05):
    """Well-separated values (pairwise gaps >> eps) for max/min/sort ties."""
    def gen(rng):
        n = int(np.prod(shape))
        vals = (np.arange(n) - n / 2) * step
        return rng.permutation(vals).reshape(shape).astype("float32")
    return gen


def spd(n, batch=()):
    """Symmetric positive definite (for potrf/potri/inverse/det)."""
    def gen(rng):
        shape = tuple(batch) + (n, n)
        b = rng.uniform(-1, 1, size=shape).astype("float32")
        a = np.einsum("...ij,...kj->...ik", b, b) + np.eye(n, dtype="float32") * n
        return a.astype("float32")
    return gen


def sym_sep(n):
    """Symmetric with well-separated eigenvalues (syevd)."""
    def gen(rng):
        q, _ = np.linalg.qr(rng.uniform(-1, 1, size=(n, n)))
        lam = np.linspace(1.0, 2.0 + n, n)
        return (q @ np.diag(lam) @ q.T).astype("float32")
    return gen


def lower_tri(n, batch=()):
    def gen(rng):
        shape = tuple(batch) + (n, n)
        a = rng.uniform(0.3, 1.0, size=shape).astype("float32")
        a = np.tril(a) + np.eye(n, dtype="float32") * 2
        return a.astype("float32")
    return gen


def const(arr):
    a = np.asarray(arr)
    return lambda rng: a


# ---------------------------------------------------------------------------
# SPEC: op name -> overrides.
#   inputs      samplers for the checked (float, differentiable) inputs
#   fixed       dict pos -> sampler for pinned inputs (indices/labels/aux);
#               positions index the op's full positional arg list
#   attrs       op attrs
#   tol         dict(eps=, rtol=, atol=)
#   skip        reason string (excluded from the sweep, counted separately)
# ---------------------------------------------------------------------------

D = (3, 4)        # default input shape

SPEC = {
    # ---- structured nn ops
    "Activation": dict(attrs={"act_type": "tanh"}),
    "FullyConnected": dict(inputs=[u(3, 4), u(5, 4), u(5)],
                           attrs={"num_hidden": 5}),
    "Convolution": dict(inputs=[u(2, 3, 5, 5), u(4, 3, 3, 3), u(4)],
                        attrs={"kernel": (3, 3), "num_filter": 4},
                        tol=dict(rtol=2e-2, atol=2e-3)),
    "Deconvolution": dict(inputs=[u(2, 3, 4, 4), u(3, 4, 3, 3), u(4)],
                          attrs={"kernel": (3, 3), "num_filter": 4},
                          tol=dict(rtol=2e-2, atol=2e-3)),
    "DeformableConvolution": dict(
        inputs=[u(1, 2, 5, 5), u(1, 18, 3, 3, low=-0.3, high=0.3),
                u(2, 2, 3, 3), u(2)],
        attrs={"kernel": (3, 3), "num_filter": 2},
        tol=dict(rtol=3e-2, atol=3e-3)),
    "Correlation": dict(inputs=[u(1, 2, 5, 5), u(1, 2, 5, 5)],
                        attrs={"kernel_size": 1, "max_displacement": 1},
                        tol=dict(rtol=2e-2, atol=2e-3)),
    "Pooling": dict(inputs=[u(1, 2, 6, 6)],
                    attrs={"kernel": (2, 2), "stride": (2, 2),
                           "pool_type": "avg"}),
    "BatchNorm": dict(inputs=[u(2, 3, 4, 4), u(3, low=0.5, high=1.5), u(3)],
                      fixed={3: const(np.zeros(3, "float32")),
                             4: const(np.ones(3, "float32"))},
                      attrs={"fix_gamma": False},
                      # eps=1e-2: with ~1e-5 float32 roundoff on the summed
                      # output, central differences at 1e-3 are noise-bound
                      tol=dict(eps=1e-2, rtol=3e-2, atol=5e-3)),
    # gamma of both signs: the pool is of sgn(scale) * data; values spread so
    # that no two taps of a window tie
    "_MaxPoolBatchNorm": dict(
        inputs=[spread(2, 3, 4, 4), const(np.array([1.2, -0.8, 0.6], "float32")),
                u(3)],
        fixed={3: const(np.zeros(3, "float32")),
               4: const(np.ones(3, "float32"))},
        attrs={"fix_gamma": False, "pool_kernel": (2, 2),
               "pool_stride": (2, 2)},
        tol=dict(eps=1e-2, rtol=3e-2, atol=5e-3)),
    "LayerNorm": dict(inputs=[u(2, 3, 4), u(4, low=0.5, high=1.5), u(4)],
                      tol=dict(rtol=2e-2, atol=2e-3)),
    "RMSNorm": dict(inputs=[u(2, 3, 4), u(4, low=0.5, high=1.5)],
                    tol=dict(rtol=2e-2, atol=2e-3)),
    "InstanceNorm": dict(inputs=[u(2, 3, 4, 4), u(3, low=0.5, high=1.5), u(3)],
                         tol=dict(eps=1e-2, rtol=3e-2, atol=5e-3)),
    "L2Normalization": dict(inputs=[away0(2, 3, 4)]),
    "LRN": dict(inputs=[u(1, 4, 5, 5)], attrs={"nsize": 3}),
    "LeakyReLU": dict(inputs=[away0(2, 3, 4, 4), u(3, low=0.1, high=0.4)],
                      attrs={"act_type": "prelu"}),
    "Dropout": dict(attrs={"p": 0.0}),      # p=0: deterministic identity path
    "Embedding": dict(inputs=[u(6, 4)],
                      fixed={0: const(np.array([0, 2, 4, 1], "int32"))},
                      attrs={"input_dim": 6, "output_dim": 4}),
    "Softmax": dict(skip="output layer: backward is the CE loss grad"),
    "SoftmaxActivation": dict(),
    "softmax": dict(attrs={"axis": -1}),
    "softmin": dict(),
    "log_softmax": dict(),
    "softmax_cross_entropy": dict(
        inputs=[u(4, 6)], fixed={1: const(np.array([0, 2, 5, 1], "float32"))}),
    "CTCLoss": dict(
        inputs=[u(5, 2, 4)],
        fixed={1: const(np.array([[1, 2], [3, 1]], "float32")),
               2: const(np.array([5, 5], "float32")),
               3: const(np.array([2, 2], "float32"))},
        tol=dict(eps=1e-2, rtol=3e-2, atol=3e-3)),
    "UpSampling": dict(inputs=[u(1, 2, 3, 3)],
                       attrs={"scale": 2, "sample_type": "nearest"}),
    "GridGenerator": dict(inputs=[u(1, 6)],
                          attrs={"transform_type": "affine",
                                 "target_shape": (4, 4)}),
    "BilinearSampler": dict(inputs=[u(1, 2, 4, 4),
                                    u(1, 2, 3, 3, low=-0.8, high=0.8)],
                            tol=dict(rtol=3e-2, atol=3e-3)),
    "SpatialTransformer": dict(inputs=[u(1, 2, 4, 4), u(1, 6, low=-0.3, high=0.3)],
                               attrs={"transform_type": "affine",
                                      "sampler_type": "bilinear",
                                      "target_shape": (3, 3)},
                               tol=dict(rtol=3e-2, atol=3e-3)),
    "AdaptiveAvgPooling2D": dict(inputs=[u(1, 2, 4, 4)],
                                 attrs={"output_size": (2, 2)}),
    "BilinearResize2D": dict(inputs=[u(1, 2, 4, 4)],
                             attrs={"height": 6, "width": 6}),
    "ROIPooling": dict(
        inputs=[spread(1, 2, 6, 6)],
        fixed={1: const(np.array([[0, 0, 0, 3, 3]], "float32"))},
        attrs={"pooled_size": (2, 2), "spatial_scale": 1.0}),
    "ROIAlign": dict(
        inputs=[u(1, 2, 6, 6)],
        fixed={1: const(np.array([[0, 0.5, 0.5, 4.5, 4.5]], "float32"))},
        attrs={"pooled_size": (2, 2), "spatial_scale": 1.0},
        tol=dict(rtol=3e-2, atol=3e-3)),
    "_contrib_PSROIPooling": dict(
        inputs=[u(1, 4, 6, 6)],
        fixed={1: const(np.array([[0, 1, 1, 5, 5]], "float32"))},
        attrs={"spatial_scale": 1.0, "output_dim": 1, "pooled_size": 2,
               "group_size": 2},
        tol=dict(rtol=3e-2, atol=3e-3)),
    "_contrib_DeformablePSROIPooling": dict(
        inputs=[u(1, 4, 6, 6), u(1, 2, 2, 2, low=-0.2, high=0.2)],
        fixed={1: const(np.array([[0, 1, 1, 5, 5]], "float32"))},
        attrs={"spatial_scale": 1.0, "output_dim": 1, "pooled_size": 2,
               "group_size": 2, "trans_std": 0.1},
        tol=dict(rtol=3e-2, atol=3e-3)),
    "RNN": dict(
        inputs=[u(3, 2, 4), u(33), u(1, 2, 3)],
        attrs={"mode": "rnn_tanh", "state_size": 3, "num_layers": 1},
        tol=dict(rtol=3e-2, atol=3e-3)),
    "SequenceMask": dict(inputs=[u(4, 2, 3)],
                         fixed={1: const(np.array([2, 3], "float32"))},
                         attrs={"use_sequence_length": True}),
    "SequenceLast": dict(inputs=[u(4, 2, 3)],
                         fixed={1: const(np.array([2, 3], "float32"))},
                         attrs={"use_sequence_length": True}),
    "SequenceReverse": dict(inputs=[u(4, 2, 3)],
                            fixed={1: const(np.array([2, 3], "float32"))},
                            attrs={"use_sequence_length": True}),
    "_contrib_flash_attention": dict(
        inputs=[u(1, 1, 4, 4), u(1, 1, 4, 4), u(1, 1, 4, 4)],
        tol=dict(rtol=3e-2, atol=3e-3)),
    # routed experts: the expert each token chose is pinned (an integer
    # choice); tokens 2 of 6 chose an expert held elsewhere
    "_contrib_moe_experts": dict(
        inputs=[u(6, 4), u(6, low=0.2, high=1.0), u(2, 3, 4), u(2, 3, 4),
                u(2, 4, 3)],
        fixed={1: const(np.array([0, 1, 3, 0, 1, 2], "float32"))},
        attrs={"first_expert": 0, "num_experts": 4}),
    "_contrib_moe_router": dict(
        skip="an argmax and an integer output: the gate's gradient is held "
             "to the plain reference in test_zaya_decoder"),
    "_contrib_fft": dict(inputs=[u(2, 8)]),
    "_contrib_ifft": dict(inputs=[u(2, 16)]),
    "_contrib_count_sketch": dict(
        inputs=[u(2, 6)],
        fixed={1: const(np.array([0, 3, 1, 2, 0, 3], "float32")),
               2: const(np.array([1, -1, 1, 1, -1, 1], "float32"))},
        attrs={"out_dim": 4}),

    # ---- loss/output layers: FD of forward can't see the loss-grad backward
    "LinearRegressionOutput": dict(skip="output layer: backward is loss grad"),
    "MAERegressionOutput": dict(skip="output layer: backward is loss grad"),
    "LogisticRegressionOutput": dict(skip="output layer: backward is loss grad"),
    "SVMOutput": dict(skip="output layer: backward is loss grad"),
    "make_loss": dict(skip="output layer: grad is ones by definition"),
    "BlockGrad": dict(skip="gradient is zero by definition (checked below)"),
    # subgraph-carrying control flow: attrs reference stored subgraphs, so
    # a generic FD sweep cannot construct them — tests/test_control_flow_sym.py
    # checks their gradients against closed forms instead
    "_foreach": dict(skip="subgraph op (tested in test_control_flow_sym)"),
    "_cond": dict(skip="subgraph op (tested in test_control_flow_sym)"),
    "_while_loop": dict(skip="subgraph op (tested in test_control_flow_sym)"),

    # ---- domain-restricted elemwise
    "arccos": dict(inputs=[u(*D, low=-0.8, high=0.8)]),
    "arcsin": dict(inputs=[u(*D, low=-0.8, high=0.8)]),
    "arctanh": dict(inputs=[u(*D, low=-0.8, high=0.8)]),
    "erfinv": dict(inputs=[u(*D, low=-0.8, high=0.8)]),
    "arccosh": dict(inputs=[u(*D, low=1.2, high=3.0)]),
    "log": dict(inputs=[u(*D, low=0.3, high=3.0)]),
    "log2": dict(inputs=[u(*D, low=0.3, high=3.0)]),
    "log10": dict(inputs=[u(*D, low=0.3, high=3.0)]),
    "log1p": dict(inputs=[u(*D, low=-0.6, high=3.0)]),
    "sqrt": dict(inputs=[u(*D, low=0.3, high=3.0)]),
    "rsqrt": dict(inputs=[u(*D, low=0.3, high=3.0)]),
    "cbrt": dict(inputs=[u(*D, low=0.3, high=3.0)]),
    "rcbrt": dict(inputs=[u(*D, low=0.3, high=3.0)]),
    "gamma": dict(inputs=[u(*D, low=1.2, high=3.0)]),
    "gammaln": dict(inputs=[u(*D, low=1.2, high=3.0)]),
    "digamma": dict(inputs=[u(*D, low=1.2, high=3.0)]),
    "reciprocal": dict(inputs=[away0(*D)]),
    "_rdiv_scalar": dict(inputs=[away0(*D)], attrs={"scalar": 2.0}),
    "_rpower_scalar": dict(inputs=[u(*D)], attrs={"scalar": 2.0}),
    "_power_scalar": dict(inputs=[u(*D, low=0.3, high=2.0)],
                          attrs={"scalar": 1.7}),
    "_power": dict(inputs=[u(*D, low=0.3, high=2.0), u(*D, low=0.5, high=2.0)]),
    "broadcast_power": dict(inputs=[u(3, 4, low=0.3, high=2.0),
                                    u(1, 4, low=0.5, high=2.0)]),
    "tan": dict(inputs=[u(*D, low=-1.2, high=1.2)]),
    "abs": dict(inputs=[away0(*D)]),
    "sign": dict(inputs=[away0(*D)]),
    "relu": dict(inputs=[away0(*D)]),
    "softsign": dict(),
    "hard_sigmoid": dict(inputs=[u(*D, low=-1.5, high=1.5)]),
    "smooth_l1": dict(inputs=[away0(*D, lo=0.3, hi=0.8)]),
    "clip": dict(inputs=[u(*D)], attrs={"a_min": -1.5, "a_max": 1.5}),
    "erf": dict(),
    "expm1": dict(),

    # ---- mod family: keep operands off integer-quotient discontinuities
    "_mod": dict(inputs=[u(*D, low=2.1, high=2.6), u(*D, low=0.9, high=1.1)]),
    "_rmod_scalar": dict(inputs=[u(*D, low=0.9, high=1.1)],
                         attrs={"scalar": 2.5}),
    "_mod_scalar": dict(inputs=[u(*D, low=2.1, high=2.6)],
                        attrs={"scalar": 1.0}),
    "broadcast_mod": dict(inputs=[u(3, 4, low=2.1, high=2.6),
                                  u(1, 4, low=0.9, high=1.1)]),

    # ---- kinked binary: keep elementwise pairs separated
    "_maximum": dict(inputs=[spread(*D), spread(*D)]),
    "_minimum": dict(inputs=[spread(*D), spread(*D)]),
    "broadcast_maximum": dict(inputs=[spread(3, 4), away0(1, 4)]),
    "broadcast_minimum": dict(inputs=[spread(3, 4), away0(1, 4)]),
    "_maximum_scalar": dict(inputs=[away0(*D)], attrs={"scalar": 0.05}),
    "_minimum_scalar": dict(inputs=[away0(*D)], attrs={"scalar": 0.05}),
    "_hypot": dict(inputs=[away0(*D), away0(*D)]),
    "_hypot_scalar": dict(inputs=[away0(*D)], attrs={"scalar": 0.7}),
    "broadcast_hypot": dict(inputs=[away0(3, 4), away0(1, 4)]),
    "_div": dict(inputs=[u(*D), away0(*D)]),
    "broadcast_div": dict(inputs=[u(3, 4), away0(1, 4)]),

    # ---- reductions / ordering: separated values
    "max": dict(inputs=[spread(*D)]),
    "min": dict(inputs=[spread(*D)]),
    "norm": dict(inputs=[away0(*D)]),
    "sort": dict(inputs=[spread(*D)]),
    "prod": dict(inputs=[away0(*D, lo=0.5, hi=1.2)]),
    "nanprod": dict(inputs=[away0(*D, lo=0.5, hi=1.2)]),
    "nansum": dict(),
    "sum": dict(),
    "mean": dict(),

    # ---- scalar arithmetic attrs
    "_plus_scalar": dict(attrs={"scalar": 1.5}),
    "_minus_scalar": dict(attrs={"scalar": 1.5}),
    "_rminus_scalar": dict(attrs={"scalar": 1.5}),
    "_mul_scalar": dict(attrs={"scalar": 1.5}),
    "_div_scalar": dict(attrs={"scalar": 1.5}),

    # ---- shape ops needing attrs
    "Reshape": dict(attrs={"shape": (4, 3)}),
    "Flatten": dict(inputs=[u(2, 3, 4)]),
    "expand_dims": dict(attrs={"axis": 1}),
    "squeeze": dict(inputs=[u(3, 1, 4)]),
    "transpose": dict(),
    "SwapAxis": dict(attrs={"dim1": 0, "dim2": 1}),
    "SliceChannel": dict(inputs=[u(4, 6)],
                         attrs={"num_outputs": 2, "axis": 1}),
    "split_v2": dict(inputs=[u(4, 6)], attrs={"sections": 2, "axis": 1}),
    "slice": dict(attrs={"begin": (0, 1), "end": (2, 3)}),
    "slice_axis": dict(attrs={"axis": 1, "begin": 0, "end": 2}),
    "slice_like": dict(inputs=[u(3, 4)], fixed={1: u(2, 3)}),
    "reshape_like": dict(inputs=[u(3, 4)], fixed={1: u(4, 3)}),
    "broadcast_like": dict(inputs=[u(1, 4)], fixed={1: u(3, 4)}),
    "broadcast_to": dict(inputs=[u(1, 4)], attrs={"shape": (3, 4)}),
    "broadcast_axes": dict(inputs=[u(1, 4)], attrs={"axis": 0, "size": 3}),
    "Pad": dict(inputs=[u(1, 2, 3, 3)],
                attrs={"mode": "constant",
                       "pad_width": (0, 0, 0, 0, 1, 1, 1, 1)}),
    "tile": dict(attrs={"reps": (2, 1)}),
    "repeat": dict(attrs={"repeats": 2}),
    "flip": dict(attrs={"axis": 0}),
    "diag": dict(),
    "depth_to_space": dict(inputs=[u(1, 4, 2, 2)], attrs={"block_size": 2}),
    "space_to_depth": dict(inputs=[u(1, 2, 4, 4)], attrs={"block_size": 2}),
    "cast_storage": dict(attrs={"stype": "default"}),
    "_slice_assign": dict(inputs=[u(4, 5), u(2, 3)],
                          attrs={"begin": (1, 1), "end": (3, 4)}),
    "_slice_assign_scalar": dict(inputs=[u(4, 5)],
                                 attrs={"scalar": 2.0, "begin": (0, 0),
                                        "end": (2, 2)}),
    "Cast": dict(attrs={"dtype": "float32"}),
    "amp_cast": dict(attrs={"dtype": "float32"}),
    "Crop": dict(inputs=[u(1, 2, 5, 6)],
                 attrs={"offset": (1, 2), "h_w": (3, 3)}),

    # ---- indexing with pinned integer inputs
    "take": dict(inputs=[u(5, 3)], fixed={1: const(np.array([0, 2, 4], "int32"))}),
    "batch_take": dict(inputs=[u(3, 4)],
                       fixed={1: const(np.array([1, 0, 3], "int32"))}),
    "pick": dict(inputs=[u(3, 4)],
                 fixed={1: const(np.array([1, 0, 3], "float32"))}),
    "gather_nd": dict(inputs=[u(3, 4)],
                      fixed={1: const(np.array([[0, 2], [1, 3]], "int64").T)}),
    "scatter_nd": dict(inputs=[u(2)],
                       fixed={1: const(np.array([[0, 2], [1, 3]], "int64").T)},
                       attrs={"shape": (3, 4)}),
    "_scatter_set_nd": dict(
        inputs=[u(3, 4), u(2)],
        fixed={2: const(np.array([[0, 2], [1, 3]], "int64").T)},
        attrs={"shape": (3, 4)}),
    "boolean_mask": dict(
        inputs=[u(4, 3)],
        fixed={1: const(np.array([1, 0, 1, 1], "int32"))}),
    "where": dict(inputs=[u(3, 4), u(3, 4)],
                  fixed={0: const((np.arange(12).reshape(3, 4) % 2)
                                  .astype("float32"))}),
    "one_hot": dict(skip="integer op registered differentiable-by-accident"),

    # ---- linalg
    "dot": dict(inputs=[u(3, 4), u(4, 2)]),
    "batch_dot": dict(inputs=[u(2, 3, 4), u(2, 4, 2)]),
    "_linalg_gemm": dict(inputs=[u(3, 4), u(4, 2), u(3, 2)]),
    "_linalg_gemm2": dict(inputs=[u(3, 4), u(4, 2)]),
    "_linalg_syrk": dict(inputs=[u(3, 4)]),
    "_linalg_trmm": dict(inputs=[lower_tri(3), u(3, 4)]),
    "_linalg_trsm": dict(inputs=[lower_tri(3), u(3, 4)],
                         tol=dict(rtol=2e-2, atol=2e-3)),
    "_linalg_potrf": dict(inputs=[spd(3)], tol=dict(rtol=3e-2, atol=3e-3)),
    "_linalg_potri": dict(inputs=[spd(3)], tol=dict(eps=1e-4, rtol=5e-2,
                                                    atol=5e-3)),
    "_linalg_inverse": dict(inputs=[spd(3)], tol=dict(rtol=3e-2, atol=3e-3)),
    "_linalg_det": dict(inputs=[spd(3)], tol=dict(rtol=3e-2, atol=3e-3)),
    "_linalg_slogdet": dict(inputs=[spd(3)], tol=dict(rtol=3e-2, atol=3e-3)),
    "_linalg_sumlogdiag": dict(inputs=[spd(3)]),
    "_linalg_extractdiag": dict(inputs=[u(3, 3)]),
    "_linalg_makediag": dict(inputs=[u(3)]),
    "_linalg_extracttrian": dict(inputs=[u(3, 3)]),
    "_linalg_maketrian": dict(inputs=[u(6)]),
    "_linalg_syevd": dict(inputs=[sym_sep(3)],
                          tol=dict(eps=1e-3, rtol=5e-2, atol=5e-3)),
    "_linalg_gelqf": dict(inputs=[u(2, 4)], tol=dict(rtol=5e-2, atol=5e-3)),

    # ---- variadic
    "Concat": dict(inputs=[u(2, 3), u(2, 3)], attrs={"dim": 0}),
    "ElementWiseSum": dict(inputs=[u(*D), u(*D), u(*D)]),
    "stack": dict(inputs=[u(*D), u(*D)], attrs={"axis": 0}),
    "amp_multicast": dict(inputs=[u(*D), u(*D)], attrs={"num_outputs": 2}),
}


def _unique_differentiable():
    """One entry per unique OpDef with all its registered aliases."""
    by_id = {}
    for name in _registry.list_ops():
        od = _registry.get_op(name)
        if not od.differentiable:
            continue
        by_id.setdefault(id(od), (od, []))[1].append(name)
    out = {}
    for od, names in by_id.values():
        canon = od.name if od.name in names else names[0]
        out[canon] = (od, names)
    return out


def _spec_for(names):
    """SPEC entry looked up under ANY registered alias of the op."""
    for n in names:
        if n in SPEC:
            return SPEC[n]
    return {}


ALL_OPS = _unique_differentiable()
SWEEP = sorted(n for n, (_, names) in ALL_OPS.items()
               if not _spec_for(names).get("skip"))
SKIPPED = sorted(n for n, (_, names) in ALL_OPS.items()
                 if _spec_for(names).get("skip"))


def test_sweep_covers_registry():
    """>= 90% of unique differentiable ops must be in the FD sweep."""
    frac = len(SWEEP) / len(ALL_OPS)
    assert frac >= 0.9, (f"sweep covers {len(SWEEP)}/{len(ALL_OPS)} "
                         f"({frac:.0%}); skipped: {SKIPPED}")


@pytest.mark.parametrize("op_name", SWEEP)
def test_op_gradient(op_name, rng):
    opdef, names = ALL_OPS[op_name]
    spec = _spec_for(names)
    tol = dict(eps=1e-3, rtol=1e-2, atol=1e-3)
    tol.update(spec.get("tol", {}))

    if "inputs" in spec:
        gens = spec["inputs"]
    else:
        # default: one (3, 4) input per declared array argument
        n_args = len(opdef.arg_names() or [None])
        gens = [u(*D)] * n_args
    checked = [g(rng) for g in gens]
    fixed = {pos: g(rng) for pos, g in spec.get("fixed", {}).items()}
    attrs = spec.get("attrs", {})
    fn = getattr(nd, op_name)

    # rebuild the full positional arg list: pinned inputs at their positions,
    # checked (perturbed) inputs filling the free slots in order
    n_total = len(checked) + len(fixed)

    def op_fn(*float_args):
        fa = iter(float_args)
        args = [nd.array(fixed[pos]) if pos in fixed else next(fa)
                for pos in range(n_total)]
        return fn(*args, **attrs)

    check_numeric_gradient(op_fn, checked, **tol)


def test_blockgrad_zero_gradient(rng):
    x = nd.array(rng.randn(3, 4).astype("float32"))
    x.attach_grad()
    with autograd.record():
        y = (nd.BlockGrad(x) * 2).sum()
    y.backward()
    np.testing.assert_allclose(x.grad.asnumpy(), np.zeros((3, 4)))


def test_output_layer_loss_gradients(rng):
    """Output layers: analytic backward equals the closed-form LOSS grad
    (reference softmax_output.cc / regression_output.cc semantics)."""
    # SoftmaxOutput: grad = softmax(x) - onehot(label)
    x = nd.array(rng.randn(4, 5).astype("float32"))
    lbl = nd.array(np.array([0, 2, 4, 1], "float32"))
    x.attach_grad()
    with autograd.record():
        out = nd.SoftmaxOutput(x, lbl)
    out.backward()
    p = np.exp(x.asnumpy() - x.asnumpy().max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    onehot = np.eye(5, dtype="float32")[lbl.asnumpy().astype(int)]
    np.testing.assert_allclose(x.grad.asnumpy(), p - onehot,
                               rtol=1e-5, atol=1e-6)

    # LinearRegressionOutput: grad = (pred - label) / batch
    x = nd.array(rng.randn(4, 3).astype("float32"))
    t = nd.array(rng.randn(4, 3).astype("float32"))
    x.attach_grad()
    with autograd.record():
        out = nd.LinearRegressionOutput(x, t)
    out.backward()
    np.testing.assert_allclose(x.grad.asnumpy(),
                               (x.asnumpy() - t.asnumpy()),
                               rtol=1e-5, atol=1e-6)

    # SVMOutput L1 hinge gradient (reference svm_output.cc:31-47: per-score
    # margins, scaled by regularization_coefficient)
    x = nd.array(rng.randn(4, 5).astype("float32"))
    lbl = nd.array(np.array([0, 2, 4, 1], "float32"))
    x.attach_grad()
    with autograd.record():
        out = nd.SVMOutput(x, lbl, margin=1.0, regularization_coefficient=0.5,
                           use_linear=True)
    out.backward()
    xs = x.asnumpy()
    onehot = np.eye(5, dtype=bool)[lbl.asnumpy().astype(int)]
    g_true = -(1.0 > xs).astype("float32") * 0.5
    g_other = (1.0 > -xs).astype("float32") * 0.5
    grad = np.where(onehot, g_true, g_other)
    np.testing.assert_allclose(x.grad.asnumpy(), grad, rtol=1e-5, atol=1e-6)
