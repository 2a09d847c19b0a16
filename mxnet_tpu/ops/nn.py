"""Neural-network ops: the MXU path.

Reference parity: ``src/operator/nn/`` — FullyConnected
(``fully_connected.cc:239-279``), Convolution/Deconvolution (cuDNN backends
``nn/cudnn/`` replaced by XLA's convolution HLO), Pooling, BatchNorm,
LayerNorm, LRN, Activation/LeakyReLU, softmax family, Dropout, UpSampling.

TPU-first notes: convs/matmuls go through ``lax.conv_general_dilated`` /
``jnp.dot`` so XLA tiles them onto the MXU; elementwise pre/post ops fuse into
the same HLO computation. The cuDNN algo-selection registry
(``cudnn_algoreg-inl.h``) has no equivalent here — XLA autotunes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.ad_checkpoint import checkpoint_name

from .registry import KEPT_IN_SEGMENT, register
from ..base import MXNetError


def _pair(v, n=2):
    if v is None or v == ():
        return (1,) * n
    if isinstance(v, int):
        return (v,) * n
    return tuple(int(x) for x in v)


def _per_channel(v, ax, ndim):
    """A per-channel vector, shaped to broadcast against the data."""
    return v.reshape(tuple(-1 if i == ax else 1 for i in range(ndim)))


def _signed(ax, x, *sign):
    """``x``, or ``x * sign`` for a vector over axis ``ax`` where one is
    given."""
    return x * _per_channel(sign[0], ax, x.ndim) if sign else x


# ---------------------------------------------------------------- FullyConnected
@register("FullyConnected", arg_names=("data", "weight", "bias"),
          product=True)
def _fully_connected(data, weight, bias=None, num_hidden=None, no_bias=False,
                     flatten=True):
    """out = X·Wᵀ + b. Weight layout (num_hidden, input_dim), matching the
    reference (fully_connected.cc:47-93 shape function)."""
    if flatten and data.ndim > 2:
        data = data.reshape(data.shape[0], -1)
    out = jnp.dot(data, weight.T)
    if not no_bias and bias is not None:
        out = out + bias
    return out


# ---------------------------------------------------------------- Convolution
_DEFAULT_CONV_LAYOUT = {1: "NCW", 2: "NCHW", 3: "NCDHW"}


def _conv_layout(nd, layout):
    """Resolve the mxnet layout string (reference conv param `layout`;
    channel-last NHWC/NWC/NDHWC is the TPU-preferred form — convs lower to
    the MXU without transposes). Weight layout follows the data layout as in
    the reference: NCHW->OIHW, NHWC->OHWI."""
    lhs = str(layout) if layout not in (None, "None", "") \
        else _DEFAULT_CONV_LAYOUT[nd]
    rhs = lhs.replace("N", "O").replace("C", "I")
    return lhs, rhs


#: a conv is stem-shaped when folding row pairs into channels still leaves it
#: thin on the contraction axis (3 -> 6 channels); past this the MXU is fed
#: well enough and the rearrangement only adds data movement
_S2D_MAX_IN_CHANNELS = 4


def _s2d_eligible(data_shape, weight_shape, lhs, stride, dilate, num_group):
    """Whether a convolution has a stem's signature: channel-last 2-D, stride
    (2,2), no dilation or groups, few input channels, kernel >= 2 in both
    dims, even height (so the padded height is even too). Decided from what
    the op sees, no knob."""
    if lhs != "NHWC" or stride != (2, 2) or dilate != (1, 1) \
            or int(num_group) != 1 or min(weight_shape[1:3]) < 2:
        return False
    return data_shape[3] <= _S2D_MAX_IN_CHANNELS and data_shape[1] % 2 == 0


def _rows_to_depth2(x, n):
    """(A, 2n, W, C) -> (A, n, W, 2C): row 2i+r lands in block row i at
    channel r*C+c."""
    a, _, w, c = x.shape
    return x.reshape(a, n, 2, w, c).transpose(0, 1, 3, 2, 4) \
            .reshape(a, n, w, 2 * c)


def _conv_s2d(data, weight, pad):
    """The stride-2 NHWC convolution of a few-channel input, computed exactly
    as a stride-(1,2) convolution over the space-to-depth of the data's ROWS:
    (B,H,W,C) -> (B,H/2,W,2C) against a (O,ceil(kh/2),kw,2C) kernel.

    Rows only, because that costs the image nothing: with the batch in the
    lanes and W in the sublanes (XLA:TPU's layout for a stem's input) pairing
    rows regroups outer dimensions, a bitcast, while pairing columns
    de-interleaves sublanes, a pass and a half over the image (PERF.md,
    PR 25). The image is not padded either: the convolution pads, rows in
    blocks. Tap u reads row 2i+u-p, so with p % 2 zero taps in FRONT of the
    kernel (an odd pad shifts the blocks by one tap) and zeros behind it up
    to an even extent, W'[o,du,v,r*C+c] = Wpad[o,2du+r,v,c] under a low
    padding of ceil(p/2) blocks (tests/test_s2d_stem.py pins the algebra).
    The weight stays the caller's (O,kh,kw,C) array: its rearrangement is
    traced, so its gradient comes back in that shape and no padded tap is
    ever a parameter. The barrier keeps XLA from folding the rearrangement
    back into the strided weight-gradient convolution, which it otherwise
    does (the same 1.74 ms op as without the lowering)."""
    (ph, pw), h, kh = pad, data.shape[1], weight.shape[1]
    front = ph % 2
    kh2 = (kh + front + 1) // 2
    lo, n_out = (ph + 1) // 2, (h + 2 * ph - kh) // 2 + 1
    w = lax.pad(weight, jnp.zeros((), weight.dtype),
                [(0, 0, 0), (front, 2 * kh2 - kh - front, 0), (0, 0, 0),
                 (0, 0, 0)])
    return lax.conv_general_dilated(
        _rows_to_depth2(data, h // 2),
        lax.optimization_barrier(_rows_to_depth2(w, kh2)),
        window_strides=(1, 2),
        padding=[(lo, n_out - 1 + kh2 - lo - h // 2), (pw, pw)],
        dimension_numbers=("NHWC", "OHWI", "NHWC"))


@register("Convolution", arg_names=("data", "weight", "bias"),
          product=True)
def _convolution(data, weight, bias=None, kernel=(), stride=(), dilate=(), pad=(),
                 num_filter=1, num_group=1, no_bias=False, workspace=1024,
                 cudnn_tune=None, cudnn_off=False, layout=None):
    nd = len(kernel)
    stride = _pair(stride, nd)
    dilate = _pair(dilate, nd)
    pad = _pair(pad, nd) if pad else (0,) * nd
    lhs, rhs = _conv_layout(nd, layout)
    if _s2d_eligible(data.shape, weight.shape, lhs, stride, dilate, num_group):
        # runs when the op is traced: once per trace of a stem, not per step
        from ..observability import catalog as _catalog, metrics as _metrics
        if _metrics.enabled():
            _catalog.CONV_S2D_LOWERED.inc()
        out = _conv_s2d(data, weight, pad)
    else:
        dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                        (lhs, rhs, lhs))
        out = lax.conv_general_dilated(
            data, weight, window_strides=stride,
            padding=[(p, p) for p in pad],
            rhs_dilation=dilate, dimension_numbers=dn,
            feature_group_count=int(num_group))
    if not no_bias and bias is not None:
        bshape = tuple(-1 if a == "C" else 1 for a in lhs)
        out = out + bias.reshape(bshape)
    return out


@register("Deconvolution", arg_names=("data", "weight", "bias"),
          product=True)
def _deconvolution(data, weight, bias=None, kernel=(), stride=(), dilate=(), pad=(),
                   adj=(), target_shape=(), num_filter=1, num_group=1, no_bias=True,
                   workspace=512, cudnn_tune=None, cudnn_off=False, layout=None):
    """Transposed convolution (reference src/operator/nn/deconvolution.cc):
    the gradient of Convolution wrt its input, expressed directly with
    input dilation so XLA sees one conv HLO."""
    nd = len(kernel)
    stride = _pair(stride, nd)
    dilate = _pair(dilate, nd)
    pad = _pair(pad, nd) if pad else (0,) * nd
    adj = _pair(adj, nd) if adj else (0,) * nd
    if layout not in (None, "None", "") and not str(layout).startswith("NC"):
        raise MXNetError(
            f"Deconvolution supports channel-first layouts only (got "
            f"{layout!r}); the reference restricts NHWC deconv to cuDNN too")
    # weight layout: (in_channels, num_filter//group, *kernel)
    lhs, rhs = _conv_layout(nd, None)
    dn = lax.conv_dimension_numbers(data.shape, weight.shape, (lhs, rhs, lhs))
    k_eff = [(int(kernel[i]) - 1) * dilate[i] + 1 for i in range(nd)]
    padding = [(k_eff[i] - 1 - pad[i], k_eff[i] - 1 - pad[i] + adj[i]) for i in range(nd)]
    g = int(num_group)
    # flip spatial dims and swap in/out channels per group
    w = jnp.flip(weight, axis=tuple(range(2, 2 + nd)))
    ci, co_g = w.shape[0], w.shape[1]
    w = w.reshape((g, ci // g, co_g) + w.shape[2:])
    w = jnp.swapaxes(w, 1, 2).reshape((co_g * g, ci // g) + tuple(w.shape[3:]))
    out = lax.conv_general_dilated(
        data, w, window_strides=(1,) * nd, padding=padding,
        lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=g)
    if not no_bias and bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


# ---------------------------------------------------------------- Pooling
#: a window of this many taps or fewer keeps its winner in one int8; every
#: zoo net's pool is 3x3 or 2x2, a global pool's window is the whole map
_POOL_MAX_TAPS = 9


def _pool_batch_split():
    """How ``jit`` splits the batch of a pool traced here, as far as the op
    can see: ``()`` where the op holds its own rows (a mesh of one device;
    inside a ``shard_map``, every axis Manual; no mesh named in a process
    with a single device), ``(mesh, axis)`` on a named mesh with ONE axis of
    several devices (``DataParallelTrainer`` names its mesh around its
    gradient), None where the op cannot tell: several such axes, of which it
    cannot see the one that holds the batch, or no mesh named where there
    are several devices. A Mosaic kernel is not partitioned automatically,
    so where the op cannot tell it keeps ``reduce_window``."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return () if jax.device_count() == 1 else None
    split = [a for a in mesh.axis_names
             if mesh.shape[a] > 1 and a not in mesh.manual_axes]
    if not split:
        return ()
    if len(split) > 1 or mesh.manual_axes:
        return None
    return mesh, split[0]


def _named_batch_split():
    """``_pool_batch_split`` for the kernels that were there before a mesh
    was ever named (attention, cross-entropy): with no mesh named they run
    as they always have, on the caller's one device's rows (a ``jit`` that
    shards them over several refuses the Mosaic kernel, loudly); under a
    named mesh they follow the pool's rule."""
    if jax.sharding.get_abstract_mesh().empty:
        return ()
    return _pool_batch_split()


def _pool_tap_eligible(data, lhs, kernel, stride, global_pool):
    """Whether a max pool's backward scatters from the saved winning tap
    (``_max_pool_taps``) and not through ``select-and-scatter``: a 2-D window
    of few taps over bfloat16/float32 where the Pallas kernels run (a TPU
    process, or their interpreter), with whole blocks for them in EACH
    device's share of the batch (a multiple of 128 rows, channels of 32,
    height and width of the strides, the row blocks inside VMEM). Decided
    from what the op sees, no knob; everything else keeps ``reduce_window``'s
    own gradient. So does a value that varies over the axes of a ``shard_map``
    that checks them: the kernels' constants would not type there."""
    if global_pool or len(kernel) != 2 \
            or kernel[0] * kernel[1] > _POOL_MAX_TAPS \
            or data.dtype not in (jnp.bfloat16, jnp.float32) \
            or jax.typeof(data).vma:
        return False
    from . import pallas_kernels as _pk
    split = _pool_batch_split() if _pk.use_pallas() else None
    if split is None:
        return False
    h, w, c, n = (data.shape[lhs.index(a)] for a in "HWCN")
    shards = split[0].shape[split[1]] if split else 1
    return n % shards == 0 and _pk.pool_eligible(
        (h, w, c, n // shards), kernel, stride, data.dtype.itemsize)


def _per_shard(kernel_fn, lhs, split):
    """``kernel_fn`` on each device's own rows where ``jit`` splits the batch
    over a mesh axis (``_pool_batch_split``): every rank-4 argument and the
    result carry the batch on the same dimension; a per-channel vector (the
    sign of a sunk pool) is whole on every device."""
    if not split:
        return kernel_fn
    from jax.sharding import PartitionSpec
    mesh, axis = split
    spec = PartitionSpec(*(axis if a == "N" else None for a in lhs))

    def sharded(*args):
        return jax.shard_map(
            kernel_fn, mesh=mesh, out_specs=spec, check_vma=False,
            in_specs=tuple(spec if a.ndim == 4 else PartitionSpec()
                           for a in args))(*args)

    return sharded


@functools.lru_cache(maxsize=None)
def _max_pool_taps(lhs, kernel, stride, padding, split=()):
    """Max pooling whose backward, on the TPU, needs neither the input nor
    ``select-and-scatter``. The value is ``reduce_window``'s. Under
    differentiation the forward also keeps, per OUTPUT element, the first tap
    in row-major window order that equals the maximum (int8: the element
    ``select-and-scatter`` with ``ge`` picks, so ties go where they went), and
    the backward gives each input position the dy of the windows whose saved
    tap it is, summed in float32: two Pallas kernels (PERF.md, PR 28), each
    device on its own rows (``split``, as the op saw it when it was traced).
    Where the program is lowered for another platform (a CPU context in a
    TPU process) both are ``reduce_window``'s own, from the input.

    ``pool(x, sign)`` pools ``x * sign`` for a per-channel ``sign`` of +-1
    (a pool sunk in front of a BatchNorm, ``_max_pool_batch_norm``) without
    a pass over ``x`` for it: the forward kernel multiplies the block it has
    loaded, and the backward folds the sign into dy at the pooled size,
    since scatter(dy) * sign == scatter(dy * sign)."""
    axes = [lhs.index("H"), lhs.index("W")]
    window = tuple(kernel[axes.index(d)] if d in axes else 1 for d in range(4))
    strides = tuple(stride[axes.index(d)] if d in axes else 1 for d in range(4))
    hwcn = tuple(lhs.index(a) for a in "HWCN")
    back = tuple(hwcn.index(d) for d in range(4))
    geometry = (kernel, stride, (padding[axes[0]][0], padding[axes[1]][0]))
    from . import pallas_kernels as _pk

    def route(on_chip, elsewhere, *args):
        """By the platform the program is lowered FOR, not the default
        backend; under the Pallas interpreter (the unit tests) the kernels."""
        if _pk._interpret():
            return on_chip(*args)
        return lax.platform_dependent(*args, tpu=on_chip, default=elsewhere)

    signed = functools.partial(_signed, lhs.index("C"))

    def plain(x, *sign):
        return lax.reduce_window(signed(x, *sign), -jnp.inf, lax.max, window,
                                 strides, padding)

    def fwd_kernel(x, *sign):
        n_out = tuple((x.shape[a] + sum(padding[a]) - k) // s + 1
                      for a, k, s in zip(axes, kernel, stride))
        out, idx = _pk.max_pool_fwd(x.transpose(hwcn), n_out, *geometry,
                                    *sign)
        return out.transpose(back), idx.transpose(back)

    def fwd_plain(x, *sign):
        out = plain(x, *sign)
        return out, jnp.zeros(out.shape, jnp.int8)      # read by nothing

    def fwd(x, *sign):
        # runs when a differentiated pool is traced: once per trace
        from ..observability import catalog as _catalog, metrics as _metrics
        if _metrics.enabled():
            _catalog.POOL_BWD_LOWERED.inc()
        out, idx = route(_per_shard(fwd_kernel, lhs, split), fwd_plain, x,
                         *sign)
        # x is for the other platforms' gradient: on the TPU nothing reads
        # it after the forward, and inside one program XLA lets it go
        return out, (idx, x, sign)

    def bwd(res, dy):
        idx, x, sign = res

        def bwd_kernel(idx, dy):
            # dy as its producer makes it, then the bitcast: without the
            # barrier XLA writes the transpose INTO the convolution fusion
            # that produces dy and tiles it worse (+1.0 ms a step at
            # [512,56,56,64]; PERF.md, PR 28)
            dy = lax.optimization_barrier(dy)
            dx = _pk.max_pool_bwd(
                idx.transpose(hwcn), dy.transpose(hwcn),
                (x.shape[axes[0]], x.shape[axes[1]]), *geometry)
            return dx.transpose(back)

        def on_chip(idx, x, dy, *sign):
            return _per_shard(bwd_kernel, lhs, split)(idx, dy)

        def bwd_plain(idx, x, dy, *sign):
            return jax.vjp(plain, signed(x, *sign))[1](dy)[0]

        dx = route(on_chip, bwd_plain, idx, x, signed(dy, *sign), *sign)
        return (dx,) + tuple(jnp.zeros_like(s) for s in sign)

    pool = jax.custom_vjp(plain)
    pool.defvjp(fwd, bwd)
    return pool


def _pool_geometry(data, kernel, global_pool, stride, pad, pooling_convention,
                   layout):
    """``(lhs, kernel, stride, window, strides, padding)`` of a Pooling's
    attributes over ``data``: the last three as ``reduce_window`` takes them
    (kFull: the output size is the ceiling, reference pooling-inl.h)."""
    nd = data.ndim - 2
    lhs, _ = _conv_layout(nd, layout)
    spatial = [i for i, a in enumerate(lhs) if a not in ("N", "C")]
    if global_pool:
        kernel = tuple(data.shape[i] for i in spatial)
        stride = (1,) * nd
        pad = (0,) * nd
    kernel = _pair(kernel, nd)
    stride = _pair(stride, nd) if stride else kernel if global_pool else (1,) * nd
    pad = _pair(pad, nd) if pad else (0,) * nd
    window = [1] * data.ndim
    strides = [1] * data.ndim
    padding = [(0, 0)] * data.ndim
    for i, ax in enumerate(spatial):
        window[ax] = kernel[i]
        strides[ax] = stride[i]
        lo = hi = pad[i]
        if pooling_convention == "full":
            size = data.shape[ax]
            out_sz = -(-(size + 2 * pad[i] - kernel[i]) // stride[i]) + 1
            need = (out_sz - 1) * stride[i] + kernel[i] - size - pad[i]
            hi = max(need, pad[i])
        padding[ax] = (lo, hi)
    return lhs, kernel, stride, tuple(window), tuple(strides), tuple(padding)


def _max_pool(data, geometry, global_pool, *sign):
    """The max pool of ``data``, or of ``data * sign`` for a per-channel
    ``sign`` of +-1: from the saved winning tap where the kernels take it
    (``_pool_tap_eligible``), else ``reduce_window`` and its own gradient."""
    lhs, kernel, stride, window, strides, padding = geometry
    if _pool_tap_eligible(data, lhs, kernel, stride, global_pool):
        return _max_pool_taps(lhs, kernel, stride, padding,
                              _pool_batch_split())(data, *sign)
    return lax.reduce_window(_signed(lhs.index("C"), data, *sign), -jnp.inf,
                             lax.max, window, strides, padding)


@register("Pooling", arg_names=("data",))
def _pooling(data, kernel=(), pool_type="max", global_pool=False, stride=(), pad=(),
             pooling_convention="valid", cudnn_off=False, p_value=2,
             count_include_pad=True, layout=None):
    geometry = _pool_geometry(data, kernel, global_pool, stride, pad,
                              pooling_convention, layout)
    _, kernel, _, window, strides, padding = geometry
    if pool_type == "max":
        out = _max_pool(data, geometry, global_pool)
    elif pool_type in ("avg", "sum"):
        out = lax.reduce_window(data, 0.0, lax.add, window, strides, padding)
        if pool_type == "avg":
            if count_include_pad:
                denom = 1.0
                for k in kernel:
                    denom *= float(k)
                out = out / denom
            else:
                ones = jnp.ones_like(data)
                cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, padding)
                out = out / cnt
    elif pool_type == "lp":
        p = float(p_value)
        out = lax.reduce_window(jnp.abs(data) ** p, 0.0, lax.add, window, strides,
                                padding) ** (1.0 / p)
    else:
        raise MXNetError(f"bad pool_type {pool_type}")
    return out


# ---------------------------------------------------------------- Norms
def _bn_scale_shift(data, gamma, beta, moving_mean, moving_var, eps, fix_gamma,
                    use_global_stats, ax, is_train):
    """BatchNorm as a per-channel affine map: ``(scale, shift, mean, var)``,
    float32 vectors, with ``out = data * scale + shift``; the statistics are
    the batch's own when training, else the running ones."""
    red = tuple(i for i in range(data.ndim) if i != ax)
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    if is_train and not use_global_stats:
        # ONE pass over the activation for both statistics: E[x] and E[x^2]
        # are sibling reduces of the same input, which XLA fuses into a
        # single multi-output kLoop read (the two-pass mean/centered-var
        # form serializes two full HBM reads of x — measured 30%+ of the
        # ResNet step). Accumulate in f32: the convert fuses INTO the
        # reduce pass, costing no extra traffic for bf16 activations.
        xf = data.astype(jnp.float32)
        mean = jnp.mean(xf, axis=red)
        # clamp: f32 cancellation can push E[x^2]-E[x]^2 a hair negative
        var = jnp.maximum(
            jnp.mean(jnp.square(xf), axis=red) - jnp.square(mean), 0.0)
    else:
        mean = moving_mean.astype(jnp.float32)
        var = moving_var.astype(jnp.float32)
    # fold the whole normalization into per-channel scale/shift vectors so
    # the per-element work is a single fused multiply-add in the data dtype
    # (no f32 promotion of the activation tensor), and the backward's
    # dL/dscale, dL/dshift become one fused (dy, dy*x) reduction pass
    inv = lax.rsqrt(var + eps)
    scale = (inv * g.astype(jnp.float32))
    shift = beta.astype(jnp.float32) - mean * scale
    return scale, shift, mean, var


_BN_ARGS = dict(num_outputs=3,
                arg_names=("data", "gamma", "beta", "moving_mean",
                           "moving_var"),
                aux_args=("moving_mean", "moving_var"))


@register("BatchNorm", **_BN_ARGS)
def _batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3, momentum=0.9,
                fix_gamma=True, use_global_stats=False, output_mean_var=False,
                axis=1, cudnn_off=False, is_train=True):
    ax = int(axis) % data.ndim
    scale, shift, mean, var = _bn_scale_shift(
        data, gamma, beta, moving_mean, moving_var, eps, fix_gamma,
        use_global_stats, ax, is_train)
    out = data * _per_channel(scale.astype(data.dtype), ax, data.ndim) \
        + _per_channel(shift.astype(data.dtype), ax, data.ndim)
    return out, mean.astype(moving_mean.dtype), var.astype(moving_var.dtype)


@register("_MaxPoolBatchNorm", **_BN_ARGS)
def _max_pool_batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
                         momentum=0.9, fix_gamma=True, use_global_stats=False,
                         output_mean_var=False, axis=1, cudnn_off=False,
                         is_train=True, pool_kernel=(), pool_stride=(),
                         pool_pad=(), pool_convention="valid",
                         pool_layout=None):
    """``Pooling(BatchNorm(data), pool_type="max")`` with the pool in front
    of BatchNorm's apply: what the ``fusion`` pass makes of that pair
    (docs/passes.md), so the apply, and whatever per-channel non-decreasing
    map follows it, runs on the pooled map. Exact, rounding included: per
    channel the apply ``y -> y*s + b`` is monotone, non-decreasing for
    s >= 0 and non-increasing for s < 0, and a maximum commutes with a
    non-decreasing map, so

        maxpool(y*s + b) == |s| * maxpool(sgn(s) * y) + b      sgn(0) := +1

    The statistics are BatchNorm's own, from all of ``data``; the pool is
    the Pooling op's (``_max_pool``: kernels, per-shard route, bypasses),
    which takes the sign without a pass over ``data`` for it; the gradient
    is autodiff's. Among taps that the apply ROUNDS to one value the
    gradient goes to the tap whose ``data`` is largest, where the unsunk
    pair gives it to the first of them."""
    ax = int(axis) % data.ndim
    scale, shift, mean, var = _bn_scale_shift(
        data, gamma, beta, moving_mean, moving_var, eps, fix_gamma,
        use_global_stats, ax, is_train)
    # runs when the op is traced: once per trace of such a stem, not per step
    from ..observability import catalog as _catalog, metrics as _metrics
    if _metrics.enabled():
        _catalog.POOL_SUNK.inc()
    scale = scale.astype(data.dtype)
    # |s| below is s * sign, whose derivative at 0 is sgn(0) = +1 too
    sign = jnp.where(scale < 0, -1, 1).astype(data.dtype)
    pooled = _max_pool(
        data, _pool_geometry(data, pool_kernel, False, pool_stride, pool_pad,
                             pool_convention, pool_layout), False, sign)
    out = pooled * _per_channel(scale * sign, ax, data.ndim) \
        + _per_channel(shift.astype(data.dtype), ax, data.ndim)
    return out, mean.astype(moving_mean.dtype), var.astype(moving_var.dtype)


@register("LayerNorm", num_outputs=3, arg_names=("data", "gamma", "beta"))
def _layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    ax = int(axis) % data.ndim
    xf = data.astype(jnp.float32)
    mean = jnp.mean(xf, axis=ax, keepdims=True)           # one fused pass:
    var = jnp.maximum(jnp.mean(jnp.square(xf), axis=ax, keepdims=True)
                      - jnp.square(mean), 0.0)            # sibling reduces
    inv = lax.rsqrt(var + eps)
    shape = tuple(data.shape[ax] if i == ax else 1 for i in range(data.ndim))
    out = ((xf - mean) * inv).astype(data.dtype) * gamma.reshape(shape) \
        + beta.reshape(shape)
    return (out, jnp.squeeze(mean.astype(data.dtype), ax),
            jnp.squeeze(var.astype(data.dtype), ax))


@register("RMSNorm", arg_names=("data", "gamma"))
def _rms_norm(data, gamma=None, axis=-1, eps=1e-6, no_gain=False):
    """``data / sqrt(mean(data**2) + eps) * gamma`` over ``axis``, the
    statistic and the products in float32 whatever the data's type, the
    result in the data's type; ``no_gain=True`` takes no ``gamma`` and leaves
    the result at a root mean square of 1. An op and not a composition
    because a block under a symbolic trace cannot name the type it has to
    cast back to."""
    ax = int(axis) % data.ndim
    xf = data.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(jnp.square(xf), axis=ax, keepdims=True)
                    + jnp.float32(eps))
    if no_gain:
        return (xf * inv).astype(data.dtype)
    g = _per_channel(gamma.astype(jnp.float32), ax, data.ndim)
    return (xf * inv * g).astype(data.dtype)


@register("InstanceNorm", arg_names=("data", "gamma", "beta"))
def _instance_norm(data, gamma, beta, eps=1e-3):
    red = tuple(range(2, data.ndim))
    xf = data.astype(jnp.float32)
    mean = jnp.mean(xf, axis=red, keepdims=True)           # one-pass stats
    var = jnp.maximum(jnp.mean(jnp.square(xf), axis=red, keepdims=True)
                      - jnp.square(mean), 0.0)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return (((xf - mean) * lax.rsqrt(var + eps)).astype(data.dtype)
            * gamma.reshape(shape) + beta.reshape(shape))


@register("LRN", num_outputs=2, arg_names=("data",))
def _lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    """Local response norm across channels (reference src/operator/nn/lrn.cc)."""
    half = int(nsize) // 2
    sq = jnp.square(data)
    padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    windows = sum(padded[:, i:i + data.shape[1]] for i in range(int(nsize)))
    norm = (knorm + (alpha / nsize) * windows) ** beta
    return data / norm, norm


# ---------------------------------------------------------------- Activations
@register("Activation", arg_names=("data",))
def _activation(data, act_type="relu"):
    if act_type == "relu":
        return jax.nn.relu(data)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return data / (1.0 + jnp.abs(data))
    raise MXNetError(f"bad act_type {act_type}")


@register("LeakyReLU", needs_rng=True, arg_names=("data", "gamma"))
def _leaky_relu(data, gamma=None, act_type="leaky", slope=0.25, lower_bound=0.125,
                upper_bound=0.334, rng=None, is_train=True):
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "prelu":
        shape = (1, -1) + (1,) * (data.ndim - 2) if data.ndim > 1 else (-1,)
        return jnp.where(data >= 0, data, gamma.reshape(shape) * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        a, s = 1.6732632423543772, 1.0507009873554805
        return s * jnp.where(data >= 0, data, a * jnp.expm1(data))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "rrelu":
        if is_train and rng is not None:
            sl = jax.random.uniform(rng, data.shape, minval=lower_bound,
                                    maxval=upper_bound, dtype=data.dtype)
        else:
            sl = (lower_bound + upper_bound) / 2.0
        return jnp.where(data >= 0, data, sl * data)
    raise MXNetError(f"bad act_type {act_type}")


# ---------------------------------------------------------------- Softmax family
@register("softmax", arg_names=("data",))
def _softmax(data, axis=-1, temperature=None, length=None, use_length=False,
             dtype=None):
    x = data / temperature if temperature else data
    if use_length and length is not None:
        ax = int(axis) % data.ndim
        pos = jnp.arange(data.shape[ax])
        shape = tuple(data.shape[ax] if i == ax else 1 for i in range(data.ndim))
        lens = length.reshape(tuple(-1 if i == 0 else 1 for i in range(data.ndim)))
        mask = pos.reshape(shape) < lens
        x = jnp.where(mask, x, -jnp.inf)
        out = jax.nn.softmax(x, axis=int(axis))
        return jnp.where(mask, out, 0.0)
    out = jax.nn.softmax(x, axis=int(axis))
    return out.astype(jnp.dtype(dtype)) if dtype else out


@register("log_softmax", arg_names=("data",))
def _log_softmax(data, axis=-1, temperature=None, dtype=None):
    x = data / temperature if temperature else data
    out = jax.nn.log_softmax(x, axis=int(axis))
    return out.astype(jnp.dtype(dtype)) if dtype else out


@register("softmin", arg_names=("data",))
def _softmin(data, axis=-1, temperature=None, dtype=None):
    return _softmax(-data, axis=axis, temperature=temperature, dtype=dtype)


@register("SoftmaxActivation")
def _softmax_activation(data, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


def _softmax_output_fwd(data, label, grad_scale, ignore_label, use_ignore,
                        multi_output, normalization, smooth_alpha):
    if multi_output:
        out = jax.nn.softmax(data, axis=1)
    else:
        out = jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)
    return out


@register("SoftmaxOutput", aliases=["Softmax"], arg_names=("data", "label"))
def _softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0, multi_output=False,
                    use_ignore=False, preserve_shape=False, normalization="null",
                    out_grad=False, smooth_alpha=0.0):
    """Softmax forward with implicit cross-entropy gradient (reference
    src/operator/softmax_output.cc): backward is (p - onehot(label)) * scale,
    expressed via jax.custom_vjp so autograd and the graph compiler both see it.
    """

    @jax.custom_vjp
    def _so(d, l):
        return _softmax_output_fwd(d, l, grad_scale, ignore_label, use_ignore,
                                   multi_output, normalization, smooth_alpha)

    def _fwd(d, l):
        out = _so(d, l)
        return out, (out, l)

    def _bwd(res, g):
        out, l = res
        if multi_output:
            # data (N, C, ...); label (N, ...)
            lab = l.astype(jnp.int32)
            oh = jax.nn.one_hot(lab, out.shape[1], dtype=out.dtype, axis=1)
        else:
            flat = out.reshape(out.shape[0], -1)
            lab = l.reshape(-1).astype(jnp.int32)
            oh = jax.nn.one_hot(lab, flat.shape[-1], dtype=out.dtype).reshape(out.shape)
        if smooth_alpha:
            k = oh.shape[1] if multi_output else oh.reshape(oh.shape[0], -1).shape[-1]
            oh = oh * (1.0 - smooth_alpha) + smooth_alpha / (k - 1) * (1.0 - oh)
        grad = out - oh
        if use_ignore:
            if multi_output:
                mask = (l != ignore_label).astype(out.dtype)
                mask = jnp.expand_dims(mask, 1)
            else:
                mask = (l.reshape(-1) != ignore_label).astype(out.dtype)
                mask = mask.reshape((-1,) + (1,) * (out.ndim - 1))
            grad = grad * mask
        scale = grad_scale
        if normalization == "batch":
            scale = scale / out.shape[0]
        elif normalization == "valid" and use_ignore:
            valid = jnp.maximum(jnp.sum((l != ignore_label).astype(out.dtype)), 1.0)
            grad = grad / valid
        grad = grad * scale
        return grad, jnp.zeros_like(l)

    _so.defvjp(_fwd, _bwd)
    return _so(data, label)


@register("LinearRegressionOutput", arg_names=("data", "label"))
def _linear_regression_output(data, label, grad_scale=1.0):
    @jax.custom_vjp
    def _lr(d, l):
        return d

    def _fwd(d, l):
        return d, (d, l)

    def _bwd(res, g):
        d, l = res
        return ((d - l.reshape(d.shape)) * grad_scale, jnp.zeros_like(l))

    _lr.defvjp(_fwd, _bwd)
    return _lr(data, label)


@register("LogisticRegressionOutput", arg_names=("data", "label"))
def _logistic_regression_output(data, label, grad_scale=1.0):
    @jax.custom_vjp
    def _lr(d, l):
        return jax.nn.sigmoid(d)

    def _fwd(d, l):
        out = jax.nn.sigmoid(d)
        return out, (out, l)

    def _bwd(res, g):
        out, l = res
        return ((out - l.reshape(out.shape)) * grad_scale, jnp.zeros_like(l))

    _lr.defvjp(_fwd, _bwd)
    return _lr(data, label)


@register("MAERegressionOutput", arg_names=("data", "label"))
def _mae_regression_output(data, label, grad_scale=1.0):
    @jax.custom_vjp
    def _lr(d, l):
        return d

    def _fwd(d, l):
        return d, (d, l)

    def _bwd(res, g):
        d, l = res
        return (jnp.sign(d - l.reshape(d.shape)) * grad_scale, jnp.zeros_like(l))

    _lr.defvjp(_fwd, _bwd)
    return _lr(data, label)


# ---------------------------------------------------------------- Dropout
@register("Dropout", needs_rng=True, arg_names=("data",))
def _dropout(data, p=0.5, mode="training", axes=(), cudnn_off=False, rng=None,
             is_train=True):
    if (not is_train and mode != "always") or p <= 0.0 or rng is None:
        return data
    shape = list(data.shape)
    for ax in (axes or ()):
        shape[ax] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(rng, keep, tuple(shape)).astype(data.dtype) / keep
    return data * mask


# ---------------------------------------------------------------- Misc nn
@register("UpSampling", arg_names=("data",))
def _upsampling(*args, scale=1, sample_type="nearest", num_filter=0, num_args=1,
                multi_input_mode="concat", workspace=512):
    data = args[0]
    s = int(scale)
    if sample_type == "nearest":
        out = jnp.repeat(jnp.repeat(data, s, axis=2), s, axis=3)
    else:  # bilinear — args[1] is the (unused) learned weight in inference mode
        n, c, h, w = data.shape
        out = jax.image.resize(data, (n, c, h * s, w * s), method="bilinear")
    return out


@register("GridGenerator")
def _grid_generator(data, transform_type="affine", target_shape=(0, 0)):
    h, w = int(target_shape[0]), int(target_shape[1])
    if transform_type == "affine":
        ys = jnp.linspace(-1.0, 1.0, h)
        xs = jnp.linspace(-1.0, 1.0, w)
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        base = jnp.stack([gx.reshape(-1), gy.reshape(-1), jnp.ones(h * w)], axis=0)
        theta = data.reshape(-1, 2, 3)
        grid = jnp.einsum("nij,jk->nik", theta, base)
        return grid.reshape(-1, 2, h, w)
    return data  # warp type: data is already the flow grid


@register("BilinearSampler")
def _bilinear_sampler(data, grid, cudnn_off=False):
    n, c, h, w = data.shape
    gx = (grid[:, 0] + 1.0) * (w - 1) / 2.0
    gy = (grid[:, 1] + 1.0) * (h - 1) / 2.0
    x0 = jnp.floor(gx); x1 = x0 + 1
    y0 = jnp.floor(gy); y1 = y0 + 1
    wa = (x1 - gx) * (y1 - gy)
    wb = (x1 - gx) * (gy - y0)
    wc = (gx - x0) * (y1 - gy)
    wd = (gx - x0) * (gy - y0)

    def gather(yi, xi):
        yi = jnp.clip(yi.astype(jnp.int32), 0, h - 1)
        xi = jnp.clip(xi.astype(jnp.int32), 0, w - 1)
        bidx = jnp.arange(n).reshape(n, 1, 1)
        return data[bidx, :, yi, xi].transpose(0, 3, 1, 2)

    out = (wa[:, None] * gather(y0, x0) + wb[:, None] * gather(y1, x0)
           + wc[:, None] * gather(y0, x1) + wd[:, None] * gather(y1, x1))
    inb = ((gx >= 0) & (gx <= w - 1) & (gy >= 0) & (gy <= h - 1)).astype(data.dtype)
    return out * inb[:, None]


@register("SpatialTransformer")
def _spatial_transformer(data, loc, target_shape=(0, 0), transform_type="affine",
                         sampler_type="bilinear", cudnn_off=False):
    grid = _grid_generator(loc, transform_type="affine", target_shape=target_shape)
    return _bilinear_sampler(data, grid)


@register("softmax_cross_entropy", arg_names=("data", "label"))
def _softmax_cross_entropy(data, label, per_row=False):
    """Total softmax CE over the batch, shape (1,).

    Reference: ``src/operator/loss_binary_op.cc`` (out = Σ_i CE(row_i)).
    On TPU the per-row CE is the fused Pallas kernel (no materialized
    softmax); gradient is the fused softmax−onehot custom VJP.

    ``per_row=True`` gives each row's CE instead, float32 in the shape of
    ``label``: data (..., C), label (...). What a loss that weights its rows
    (``gluon.loss.ExpectedExitCELoss``) composes with. The kernel is not
    partitioned automatically, so under a named mesh that splits the rows
    over devices (``_named_batch_split``) the log-sum-exp is XLA's.
    """
    from .pallas_kernels import softmax_cross_entropy as _ce
    rows = _ce(data.reshape(-1, data.shape[-1]),
               label.astype(jnp.int32).reshape(-1),
               kernel=_named_batch_split() == ())
    return rows.reshape(label.shape) if per_row else jnp.sum(rows).reshape(1)


@register("_contrib_flash_attention", aliases=["contrib_flash_attention"],
          arg_names=("query", "key", "value"), product=True)
def _flash_attention_op(query, key, value, causal=False, scale=None,
                        q_offset=0, k_offset=0):
    """Blockwise (flash) attention, (B, H, T, D) layout; Pallas kernel on TPU.

    The reference has no attention op (SURVEY.md §5.7) — this is the
    long-context extension the TPU build makes first-class; the same kernel
    is the ring-attention per-step partial (``parallel.ring_attention``).

    A Mosaic kernel is not partitioned automatically, so where ``jit`` splits
    the batch over a named mesh axis (``_named_batch_split``) each device runs
    the kernel on its own rows under ``shard_map``; where the op cannot see
    the split, or the batch does not divide, the forward keeps the plain XLA
    form. ``mxtpu_flash_attention_lowered_total{route=}`` counts traces by
    the route the forward took.
    """
    from . import pallas_kernels as _pk
    attend = functools.partial(
        _pk.flash_attention, causal=bool(causal),
        scale=None if scale is None else float(scale),
        q_offset=int(q_offset), k_offset=int(k_offset))
    kernel = _pk.flash_forward_tiles(query, key)
    split = _named_batch_split() if kernel else ()
    if split is None or (split and query.shape[0] % split[0].shape[split[1]]):
        kernel, split = False, ()
    from ..observability import catalog as _catalog, metrics as _metrics
    if _metrics.enabled():
        _catalog.FLASH_ATTENTION_LOWERED.inc(
            route="pallas" if kernel else "xla")
    if not kernel:
        return attend(query, key, value, kernel=False)
    return _per_shard(attend, "NHTD", split)(query, key, value)


@register("_contrib_rotary_embedding", aliases=["contrib_rotary_embedding"],
          arg_names=("data",))
def _rotary_embedding(data, theta=10000.0, rotary_dim=None):
    """Rotary positions on (..., T, D), half-rotation form: with ``x1, x2``
    the two halves of the last axis, ``[x1 cos - x2 sin, x2 cos + x1 sin]``
    at angle ``position * theta**(-2i/D)``; the position is the index along
    the second-to-last axis. ``rotary_dim`` = R < D turns the first R
    channels alone (halves of R/2, angles ``theta**(-2i/R)``) and leaves
    channels R..D-1 as they are. Angles and products in float32,
    the result in the data's type. An op because the positions are an iota
    of a length a symbolic trace does not know."""
    t, d = data.shape[-2], data.shape[-1]
    r = d if rotary_dim is None else int(rotary_dim)
    if r % 2 or not 0 < r <= d:
        raise MXNetError(f"rotary embedding needs an even number of turned "
                         f"channels within the last axis, got {r} of {d}")
    half = r // 2
    inv_freq = jnp.float32(theta) ** (
        -jnp.arange(half, dtype=jnp.float32) * jnp.float32(2.0 / r))
    pos = jnp.arange(t, dtype=jnp.float32)
    angle = pos[:, None] * inv_freq[None, :]                 # (T, R/2)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    # [x1, x2] * [cos, cos] + [x2, x1] * [-sin, sin]: the halves meet by a
    # roll of the whole axis. Joining two computed halves instead makes
    # XLA:TPU write half a lane tile at a time, and its float32 form at
    # (1, 16, 4096, 128) fails a check inside the compiler (PERF.md, PR 31)
    xf = data.astype(jnp.float32)
    if r == d:
        out = xf * jnp.concatenate([cos, cos], axis=-1) \
            + jnp.roll(xf, half, axis=-1) * jnp.concatenate([-sin, sin], axis=-1)
        return out.astype(data.dtype)
    # the same without a join: behind channel R the tables are 1 and 0, and
    # within it x2 comes from a roll to the left, x1 from one to the right
    still = jnp.zeros((t, d - r), jnp.float32)
    lane = lax.broadcasted_iota(jnp.int32, (t, d), 1)
    swapped = jnp.where(lane < half, jnp.roll(xf, -half, axis=-1),
                        jnp.roll(xf, half, axis=-1))
    out = xf * jnp.concatenate([cos, cos, still + 1.0], axis=-1) \
        + swapped * jnp.concatenate([-sin, sin, still], axis=-1)
    return out.astype(data.dtype)


# ---------------------------------------------------------------- routed experts
def top1(scores):
    """(index, value) of the largest score of each row: the one top-1
    choice of the routed-experts ops here and of ``parallel.expert_parallel``."""
    idx = jnp.argmax(scores, axis=-1).astype(jnp.int32)
    return idx, jnp.take_along_axis(scores, idx[..., None], axis=-1)[..., 0]


@register("_contrib_moe_router", aliases=["contrib_moe_router"],
          num_outputs=3)
def _moe_router(*arrays):
    """The router MLP of a layer of top-1 routed experts, in float32 at the
    highest matmul precision whatever the compute type: (data (..., W),
    down_weight (R, W), down_bias (R,), w1 (R, R), w2 (R, R), w3 (E, R)
    [, state (..., R), mix (R,)]) -> (expert (...) int32, gate (...)
    float32, state (..., R) float32)::

        state = data down_weight^T + down_bias  [+ mix * the layer before's]
        s = softmax(w3 gelu(w2 gelu(w1 state)));  expert = argmax s
        gate = s[expert]

    ``state`` is what the next layer's router mixes in. The choice carries no
    gradient; the gate's value does. A top-1 choice flips where two scores
    tie within rounding, so nothing here is rounded to the compute type."""
    data, down_w, down_b, w1, w2, w3 = arrays[:6]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    mm = lambda a, w: jnp.matmul(  # noqa: E731
        a, f32(w).T, precision=lax.Precision.HIGHEST)
    state = mm(f32(data), down_w) + f32(down_b)
    if len(arrays) == 8:
        state = state + f32(arrays[7]) * f32(arrays[6])
    elif len(arrays) != 6:
        raise MXNetError("_contrib_moe_router takes 6 inputs, or 8 with the "
                         f"layer before's state and its mix, got {len(arrays)}")
    hidden = jax.nn.gelu(mm(jax.nn.gelu(mm(state, w1), approximate=False), w2),
                         approximate=False)
    expert, gate = top1(jax.nn.softmax(mm(hidden, w3), axis=-1))
    return expert, gate, state


def _moe_sorted(expert, first, held, tile):
    """Where each token goes when the tokens of the ``held`` experts from
    ``first`` are sorted by expert, each group padded to whole tiles (an
    empty one to one tile): (pos (T,) row of each token, the row count where
    its expert is not held; src (rows,) token of each row, T where the row
    is padding; tile_group (tiles,), the tiles behind the last group's
    counted to it). Sizes are data, shapes are not: rows = (ceil(T / tile) +
    held) * tile."""
    t = expert.shape[0]
    tiles = -(-t // tile) + held
    local = expert.astype(jnp.int32) - jnp.int32(first)
    here = (local >= 0) & (local < held)
    onehot = (local[:, None] == jnp.arange(held, dtype=jnp.int32)[None, :]) \
        .astype(jnp.int32)
    rank = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=1) - 1
    group_tiles = jnp.maximum(1, -(-jnp.sum(onehot, axis=0) // tile))
    ends = jnp.cumsum(group_tiles)
    start = (ends - group_tiles)[jnp.clip(local, 0, held - 1)] * tile
    pos = jnp.where(here, start + rank, tiles * tile).astype(jnp.int32)
    src = jnp.full((tiles * tile,), t, jnp.int32).at[pos].set(
        jnp.arange(t, dtype=jnp.int32), mode="drop")
    tile_group = jnp.minimum(jnp.searchsorted(
        ends, jnp.arange(tiles, dtype=jnp.int32), side="right"),
        held - 1).astype(jnp.int32)
    return pos, src, tile_group


def _silu_mul(g, u):
    """``silu(g) * u`` in float32, rounded once to the operands' type."""
    gf = g.astype(jnp.float32)
    return (gf * jax.nn.sigmoid(gf) * u.astype(jnp.float32)).astype(g.dtype)


def _moe_plain(x, expert, gate, w_gate, w_up, w_down, first):
    """The held experts' part of the layer, an expert at a time over ALL
    tokens under a mask: ``held`` times the grouped form's products, for
    where its kernels cannot run."""
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(w_gate.shape[0]):
        h = _silu_mul(jnp.dot(x, w_gate[e].T), jnp.dot(x, w_up[e].T))
        o = jnp.dot(h, w_down[e].T).astype(jnp.float32)
        out = out + jnp.where((expert == first + e)[:, None],
                              o * gate[:, None], 0.0)
    return out.astype(x.dtype)


def _moe_grouped_fwd(x, expert, gate, w_gate, w_up, w_down, first, tile):
    from . import pallas_kernels as _pk
    held = w_gate.shape[0]
    pos, src, group = _moe_sorted(expert, first, held, tile)
    gmm = functools.partial(_pk.moe_gmm, tile_group=group, tile=tile,
                            transpose_rhs=True)
    xs = jnp.take(x, src, axis=0, mode="fill", fill_value=0)
    # named for a recomputed segment's policy (executor._GraphLowering), as
    # the attention kernel's residuals are: with these kept a segment's
    # backward pass runs none of the three forward products again
    g = checkpoint_name(gmm(xs, w_gate), KEPT_IN_SEGMENT)
    u = checkpoint_name(gmm(xs, w_up), KEPT_IN_SEGMENT)
    o = gmm(_silu_mul(g, u), w_down)
    # a token whose expert is elsewhere reads row ``rows``: out of range, 0
    og = checkpoint_name(
        jnp.take(o, pos, axis=0, mode="fill", fill_value=0), KEPT_IN_SEGMENT)
    out = (og.astype(jnp.float32) * gate[:, None]).astype(x.dtype)
    return out, (x, gate, pos, src, group, g, u, og, w_gate, w_up, w_down)


def _moe_grouped_bwd(tile, res, dy):
    from . import pallas_kernels as _pk
    x, gate, pos, src, group, g, u, og, w_gate, w_up, w_down = res
    held = w_gate.shape[0]
    plan = dict(tile_group=group, tile=tile)
    dyf = dy.astype(jnp.float32)
    d_gate = jnp.sum(dyf * og.astype(jnp.float32), axis=-1)
    dys = jnp.take((dyf * gate[:, None]).astype(x.dtype), src, axis=0,
                   mode="fill", fill_value=0)
    xs = jnp.take(x, src, axis=0, mode="fill", fill_value=0)
    dh = _pk.moe_gmm(dys, w_down, **plan).astype(jnp.float32)
    d_down = _pk.moe_tgmm(dys, _silu_mul(g, u), groups=held, **plan)
    gf, uf = g.astype(jnp.float32), u.astype(jnp.float32)
    sig = jax.nn.sigmoid(gf)
    dg = (dh * uf * sig * (1.0 + gf * (1.0 - sig))).astype(x.dtype)
    du = (dh * gf * sig).astype(x.dtype)
    dxs = _pk.moe_gmm(dg, w_gate, **plan).astype(jnp.float32) \
        + _pk.moe_gmm(du, w_up, **plan).astype(jnp.float32)
    dx = jnp.take(dxs, pos, axis=0, mode="fill", fill_value=0).astype(x.dtype)
    return (dx, d_gate.astype(gate.dtype),
            _pk.moe_tgmm(dg, xs, groups=held, **plan),
            _pk.moe_tgmm(du, xs, groups=held, **plan), d_down)


@functools.lru_cache(maxsize=None)
def _moe_experts_fn(first, route, tile):
    """The held experts' part of a layer as one differentiable function of
    (x, gate, w_gate, w_up, w_down) by ``route``: ``grouped`` sorts the
    tokens by expert and runs each projection as ONE grouped product
    (``pallas_kernels.moe_gmm``), its backward pass the transposed grouped
    product for the tokens and the per-group outer product for the weights,
    every gather a gather (no scatter-add); ``plain`` is ``_moe_plain`` under
    ``jax.vjp``. Either way a DIFFERENTIATED trace counts one
    ``mxtpu_moe_lowered_total{route=}``."""
    def forward(x, expert, gate, w_gate, w_up, w_down):
        """(the result, what the backward pass needs)."""
        if route == "grouped":
            return _moe_grouped_fwd(x, expert, gate, w_gate, w_up, w_down,
                                    first, tile)
        return jax.vjp(
            lambda *a: _moe_plain(a[0], expert, *a[1:], first),
            x, gate, w_gate, w_up, w_down)

    def fwd(*args):
        from ..observability import catalog as _catalog, metrics as _metrics
        if _metrics.enabled():
            _catalog.MOE_LOWERED.inc(route=route)
        return forward(*args)

    def bwd(res, dy):
        dx, d_gate, *d_w = _moe_grouped_bwd(tile, res, dy) \
            if route == "grouped" else res(dy)
        return (dx, None, d_gate, *d_w)

    core = jax.custom_vjp(lambda *args: forward(*args)[0])
    core.defvjp(fwd, bwd)
    return core


@register("_contrib_moe_experts", aliases=["contrib_moe_experts"],
          arg_names=("data", "expert", "gate", "gate_weight", "up_weight",
                     "down_weight"), product=True)
def _moe_experts(data, expert, gate, gate_weight, up_weight, down_weight,
                 first_expert=0, num_experts=None):
    """The part of a layer of top-1 routed experts that THIS holder's
    experts give: data (..., W), expert (...) the expert each token chose
    among ``num_experts``, gate (...) its gate value, and the stacked weights
    of the ``held`` experts ``first_expert .. first_expert + held - 1``:
    gate_weight and up_weight (held, F, W), down_weight (held, W, F)::

        out[t] = gate[t] * down_e(silu(gate_e x[t]) * up_e x[t]),  e = expert[t]

    and 0 for a token whose expert is held elsewhere: the shares of all
    holders add up to the whole layer. Nothing is dropped and there is no
    capacity: the tokens are sorted by expert, each projection is one
    grouped product over the ``held`` groups (sizes are data, an empty group
    is legal), and the result is put back in the tokens' order. Where the
    grouped kernels cannot run (no TPU and no interpreter, widths that do not
    tile, a mesh the op cannot read) the experts run one at a time over all
    tokens under a mask. Under a named mesh that splits the batch each
    device runs its own rows (``_pool_batch_split``); the weights are whole
    on every device."""
    from . import pallas_kernels as _pk
    from ..observability import catalog as _catalog, metrics as _metrics
    held, hidden, width = gate_weight.shape
    if _metrics.enabled():
        _catalog.MOE_EXPERTS_HELD.set(held)
        _catalog.MOE_EXPERTS_ROUTED.set(
            held if num_experts is None else int(num_experts))
    x = data.reshape(-1, width)
    grouped = _pk.moe_gmm_eligible(width, hidden, x.dtype.itemsize) \
        and x.dtype == gate_weight.dtype and not jax.typeof(x).vma
    split = _named_batch_split() if grouped else ()
    if split is None or (split and x.shape[0] % split[0].shape[split[1]]):
        grouped, split = False, ()
    core = _moe_experts_fn(int(first_expert),
                           "grouped" if grouped else "plain",
                           _pk.MOE_TILE_ROWS)
    args = (x, expert.reshape(-1).astype(jnp.int32),
            gate.reshape(-1).astype(jnp.float32),
            gate_weight, up_weight, down_weight)
    if split:
        from jax.sharding import PartitionSpec
        mesh, axis = split
        rows, whole = PartitionSpec(axis), PartitionSpec()
        core = jax.shard_map(core, mesh=mesh, out_specs=rows, check_vma=False,
                             in_specs=(rows,) * 3 + (whole,) * 3)
    return core(*args).reshape(data.shape)


@register("SVMOutput", arg_names=("data", "label"))
def _svm_output(data, label, margin=1.0, regularization_coefficient=1.0,
                use_linear=False):
    """Hinge-loss output layer (reference src/operator/svm_output.cc):
    forward is identity on the scores; backward writes the L1 (use_linear)
    or squared hinge gradient directly, via jax.custom_vjp like
    SoftmaxOutput."""

    @jax.custom_vjp
    def _svm(d, l):
        return d

    def _fwd(d, l):
        return d, (d, l)

    def _bwd(res, g):
        d, l = res
        k = l.reshape(-1).astype(jnp.int32)
        is_true = jax.nn.one_hot(k, d.shape[1], dtype=bool, axis=-1)
        reg = regularization_coefficient
        if use_linear:
            # L1_SVM (svm_output.cc:31-47)
            g_true = -(margin > d).astype(d.dtype) * reg
            g_other = (margin > -d).astype(d.dtype) * reg
        else:
            # L2_SVM (svm_output.cc:50-66)
            g_true = -2.0 * jnp.maximum(margin - d, 0.0) * reg
            g_other = 2.0 * jnp.maximum(margin + d, 0.0) * reg
        grad = jnp.where(is_true, g_true, g_other)
        return grad, jnp.zeros_like(l)

    _svm.defvjp(_fwd, _bwd)
    return _svm(data, label)
