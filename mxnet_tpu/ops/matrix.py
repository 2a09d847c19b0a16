"""Shape-manipulation and matrix ops.

Reference parity: ``src/operator/tensor/matrix_op.cc`` (reshape with special
codes, transpose, slice family, concat/stack/split, tile/repeat/pad, flip,
depth/space, diag) and ``dot.cc`` / ``la_op`` batch_dot.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register

from ..base import MXNetError


def infer_reshape(src_shape, target, reverse=False):
    """MXNet reshape special codes (reference matrix_op.cc InferReshapeShape):
    0 copy dim; -1 infer one dim; -2 copy all remaining dims; -3 merge next
    two source dims; -4 split a dim into the next two target values."""
    src = list(src_shape)
    tgt = list(target)
    if reverse:
        src = src[::-1]
        tgt = tgt[::-1]
    out = []
    si = 0
    ti = 0
    infer_idx = -1
    while ti < len(tgt):
        t = tgt[ti]
        if t == 0:
            out.append(src[si]); si += 1
        elif t == -1:
            if infer_idx >= 0:
                raise MXNetError("reshape: at most one -1 allowed")
            infer_idx = len(out); out.append(1)
            si += 1 if si < len(src) else 0
        elif t == -2:
            out.extend(src[si:]); si = len(src)
        elif t == -3:
            out.append(src[si] * src[si + 1]); si += 2
        elif t == -4:
            d1, d2 = tgt[ti + 1], tgt[ti + 2]
            ti += 2
            cur = src[si]; si += 1
            if d1 == -1:
                d1 = cur // d2
            if d2 == -1:
                d2 = cur // d1
            out.extend([d1, d2])
        else:
            out.append(t)
            if si < len(src):
                si += 1
        ti += 1
    known = int(np.prod([d for i, d in enumerate(out) if i != infer_idx])) if out else 1
    total = int(np.prod(src_shape)) if src_shape else 1
    if infer_idx >= 0:
        out[infer_idx] = total // max(known, 1)
    if reverse:
        out = out[::-1]
    return tuple(out)


@register("Reshape", aliases=["reshape"])
def _reshape(x, shape=None, reverse=False, target_shape=None, keep_highest=False):
    tgt = shape if shape is not None else target_shape
    return jnp.reshape(x, infer_reshape(x.shape, tgt, reverse=bool(reverse)))


@register("reshape_like")
def _reshape_like(x, like):
    return jnp.reshape(x, like.shape)


@register("Flatten", aliases=["flatten"])
def _flatten(x):
    return jnp.reshape(x, (x.shape[0], -1))


@register("transpose")
def _transpose(x, axes=None):
    if axes is None or axes == ():
        axes = tuple(reversed(range(x.ndim)))
    return jnp.transpose(x, axes)


@register("SwapAxis", aliases=["swapaxes"])
def _swapaxes(x, dim1=0, dim2=0):
    return jnp.swapaxes(x, int(dim1), int(dim2))


@register("expand_dims")
def _expand_dims(x, axis=0):
    return jnp.expand_dims(x, int(axis))


@register("squeeze")
def _squeeze(x, axis=None):
    if axis is None:
        return jnp.squeeze(x)
    if isinstance(axis, int):
        axis = (axis,)
    return jnp.squeeze(x, tuple(axis))


@register("dot", product=True)
def _dot(lhs, rhs, transpose_a=False, transpose_b=False, forward_stype=None):
    # reference tensor/dot-inl.h: reduces over the last axis of lhs and the
    # first axis of rhs (generalized to >2-D operands).
    if transpose_a:
        lhs = jnp.transpose(lhs, tuple(range(1, lhs.ndim)) + (0,)) if lhs.ndim > 2 else lhs.T
    if transpose_b:
        rhs = jnp.transpose(rhs, (rhs.ndim - 1,) + tuple(range(rhs.ndim - 1))) if rhs.ndim > 2 else rhs.T
    return jnp.tensordot(lhs, rhs, axes=([lhs.ndim - 1], [0]))


@register("batch_dot", product=True)
def _batch_dot(lhs, rhs, transpose_a=False, transpose_b=False, forward_stype=None):
    if transpose_a:
        lhs = jnp.swapaxes(lhs, -1, -2)
    if transpose_b:
        rhs = jnp.swapaxes(rhs, -1, -2)
    return jnp.matmul(lhs, rhs)


def _canon_slice(shape, begin, end, step=None):
    slices = []
    step = step or (None,) * len(begin)
    for i, (b, e) in enumerate(zip(begin, end)):
        s = step[i] if i < len(step) and step[i] not in (None, 0) else 1
        slices.append(slice(b, e, s))
    slices += [slice(None)] * (len(shape) - len(slices))
    return tuple(slices)


@register("slice", aliases=["crop"])
def _slice(x, begin=(), end=(), step=None):
    return x[_canon_slice(x.shape, begin, end, step)]


@register("slice_axis")
def _slice_axis(x, axis=0, begin=0, end=None):
    idx = [slice(None)] * x.ndim
    idx[axis] = slice(begin, end)
    return x[tuple(idx)]


@register("slice_like")
def _slice_like(x, like, axes=()):
    axes = tuple(axes) if axes else tuple(range(min(x.ndim, like.ndim)))
    idx = [slice(None)] * x.ndim
    for a in axes:
        idx[a] = slice(0, like.shape[a])
    return x[tuple(idx)]


@register("Concat", aliases=["concat"])
def _concat(*xs, dim=1, num_args=None):
    return jnp.concatenate(xs, axis=int(dim))


@register("stack")
def _stack(*xs, axis=0, num_args=None):
    return jnp.stack(xs, axis=int(axis))


def _split_count(attrs):
    return int(attrs.get("num_outputs", 1))


@register("SliceChannel", aliases=["split"], num_outputs=_split_count)
def _split(x, num_outputs=1, axis=1, squeeze_axis=False):
    parts = jnp.split(x, int(num_outputs), axis=int(axis))
    if squeeze_axis:
        parts = [jnp.squeeze(p, axis=int(axis)) for p in parts]
    return tuple(parts) if len(parts) > 1 else parts[0]


@register("split_v2", num_outputs=lambda a: (len(a.get("indices", ())) + 1
                                             if not a.get("sections") else int(a["sections"])))
def _split_v2(x, indices=(), axis=0, squeeze_axis=False, sections=0):
    if sections:
        parts = jnp.split(x, int(sections), axis=int(axis))
    else:
        parts = jnp.split(x, list(indices), axis=int(axis))
    if squeeze_axis:
        parts = [jnp.squeeze(p, axis=int(axis)) for p in parts]
    return tuple(parts) if len(parts) > 1 else parts[0]


@register("tile")
def _tile(x, reps=()):
    return jnp.tile(x, tuple(reps))


@register("repeat")
def _repeat(x, repeats=1, axis=None):
    return jnp.repeat(x, int(repeats), axis=None if axis is None else int(axis))


@register("Pad", aliases=["pad"])
def _pad(x, mode="constant", pad_width=(), constant_value=0.0):
    pw = list(zip(pad_width[::2], pad_width[1::2]))
    if mode == "constant":
        return jnp.pad(x, pw, mode="constant", constant_values=constant_value)
    if mode == "edge":
        return jnp.pad(x, pw, mode="edge")
    if mode == "reflect":
        return jnp.pad(x, pw, mode="reflect")
    raise MXNetError(f"bad pad mode {mode}")


@register("flip", aliases=["reverse"])
def _flip(x, axis=()):
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    return jnp.flip(x, axis=axes)


@register("depth_to_space")
def _depth_to_space(x, block_size=1):
    b = int(block_size)
    n, c, h, w = x.shape
    y = x.reshape(n, b, b, c // (b * b), h, w)
    y = y.transpose(0, 3, 4, 1, 5, 2)
    return y.reshape(n, c // (b * b), h * b, w * b)


@register("space_to_depth")
def _space_to_depth(x, block_size=1):
    b = int(block_size)
    n, c, h, w = x.shape
    y = x.reshape(n, c, h // b, b, w // b, b)
    y = y.transpose(0, 3, 5, 1, 2, 4)
    return y.reshape(n, c * b * b, h // b, w // b)


@register("diag")
def _diag(x, k=0, axis1=0, axis2=1):
    if x.ndim == 1:
        return jnp.diag(x, k=int(k))
    return jnp.diagonal(x, offset=int(k), axis1=int(axis1), axis2=int(axis2))


@register("shape_array", differentiable=False)
def _shape_array(x):
    return jnp.asarray(x.shape, dtype=jnp.int64)


@register("size_array", differentiable=False)
def _size_array(x):
    return jnp.asarray([x.size], dtype=jnp.int64)
