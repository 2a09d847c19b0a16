"""Final op-name parity tail: gradient-accumulation helpers, sparse-aware
scatter arithmetic, ``*_like`` random samplers, candidate sampling, and the
nnvm image ops.

Reference registrations covered here:
- ``src/operator/tensor/elemwise_binary_op_basic.cc`` ``_grad_add``
- ``src/operator/tensor/square_sum.cc`` ``_square_sum``
- ``src/operator/tensor/elemwise_scatter_op.cc`` ``_scatter_elemwise_div``,
  ``_scatter_plus_scalar``, ``_scatter_minus_scalar``
- ``src/operator/random/sample_op.cc`` ``_random_*_like`` family
- ``src/operator/random/unique_sample_op.cc`` ``_sample_unique_zipfian``
- ``src/operator/contrib/transformer.cc`` ``_contrib_div_sqrt_dim``
- ``src/operator/image/image_random.cc`` ``_image_to_tensor``,
  ``_image_normalize``

TPU-first notes:
- The reference's ``_scatter_*`` ops exist so row_sparse gradients touch only
  occupied rows.  Under XLA a dense elementwise op over the same buffer fuses
  into one HBM pass, so the dense math IS the efficient lowering; the sparse
  storage semantics live at the NDArray layer (``ndarray/sparse.py``).
- ``_sample_unique_zipfian`` (log-uniform candidate sampler for sampled
  softmax) needs data-dependent rejection, which has no fixed-shape XLA
  lowering.  The reference runs it on CPU inside the engine; we do the same
  via a host callback with a fixed output shape.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from .registry import register


# ---------------------------------------------------------------------------
# gradient accumulation / scatter arithmetic
# ---------------------------------------------------------------------------

@register("_grad_add")
def _grad_add(lhs, rhs):
    """Addition used for grad_req='add' accumulation (never overwrites)."""
    return lhs + rhs


@register("_square_sum")
def _square_sum(data, axis=None, keepdims=False):
    if isinstance(axis, (list, tuple)):
        axis = tuple(int(a) for a in axis)
    elif axis is not None:
        axis = int(axis)
    return jnp.sum(jnp.square(data), axis=axis, keepdims=bool(keepdims))


@register("_scatter_elemwise_div")
def _scatter_elemwise_div(lhs, rhs):
    return lhs / rhs


@register("_scatter_plus_scalar")
def _scatter_plus_scalar(data, scalar=0.0):
    return data + scalar


@register("_scatter_minus_scalar")
def _scatter_minus_scalar(data, scalar=0.0):
    return data - scalar


# ---------------------------------------------------------------------------
# *_like random samplers (shape/dtype follow the input tensor)
# ---------------------------------------------------------------------------

def _like(data, draw, rng):
    out = draw(rng, jnp.shape(data))
    return out.astype(jnp.result_type(data))


@register("_random_uniform_like", needs_rng=True, differentiable=False)
def _uniform_like(data, low=0.0, high=1.0, rng=None):
    return _like(data, lambda k, s: jax.random.uniform(
        k, s, minval=low, maxval=high), rng)


@register("_random_normal_like", needs_rng=True, differentiable=False)
def _normal_like(data, loc=0.0, scale=1.0, rng=None):
    return _like(data, lambda k, s: loc + scale * jax.random.normal(k, s), rng)


@register("_random_gamma_like", needs_rng=True, differentiable=False)
def _gamma_like(data, alpha=1.0, beta=1.0, rng=None):
    return _like(data, lambda k, s: jax.random.gamma(k, alpha, s) * beta, rng)


@register("_random_exponential_like", needs_rng=True, differentiable=False)
def _exponential_like(data, lam=1.0, rng=None):
    return _like(data, lambda k, s: jax.random.exponential(k, s) / lam, rng)


@register("_random_poisson_like", needs_rng=True, differentiable=False)
def _poisson_like(data, lam=1.0, rng=None):
    return _like(data, lambda k, s: jax.random.poisson(k, lam, s).astype(
        jnp.float32), rng)


def _neg_binomial_draw(rng, shape, k, p):
    """NB(k, p) as Gamma-Poisson mixture — one vectorised draw, no loop."""
    kg, kp = jax.random.split(rng)
    lam = jax.random.gamma(kg, k, shape) * ((1.0 - p) / p)
    return jax.random.poisson(kp, lam, shape).astype(jnp.float32)


@register("_random_negative_binomial_like", needs_rng=True,
          differentiable=False)
def _neg_binomial_like(data, k=1, p=1.0, rng=None):
    return _like(data, lambda r, s: _neg_binomial_draw(r, s, k, p), rng)


@register("_random_generalized_negative_binomial_like", needs_rng=True,
          differentiable=False)
def _gen_neg_binomial_like(data, mu=1.0, alpha=1.0, rng=None):
    k = 1.0 / alpha
    p = k / (k + mu)
    return _like(data, lambda r, s: _neg_binomial_draw(r, s, k, p), rng)


# ---------------------------------------------------------------------------
# candidate sampling (sampled softmax support)
# ---------------------------------------------------------------------------

@register("_sample_unique_zipfian", num_outputs=2, needs_rng=True,
          differentiable=False, host=True)
def _sample_unique_zipfian(range_max=1, shape=(1,), rng=None):
    """Unique log-uniform (Zipfian) candidate sampler.

    Returns ``(samples, num_tries)`` like the reference
    (``unique_sample_op.cc``): ``samples`` are ``shape[-1]`` distinct class
    ids per row drawn from P(k) = log1p(1/(k+1)) / log(range_max + 1), and
    ``num_tries`` is how many raw draws each row consumed (used to derive
    expected counts).  Rejection sampling has no fixed-shape XLA lowering, so
    this is a host op (``host=True``) like the reference's CPU-only kernel
    (``unique_sample_op.cc`` is FCompute<cpu> only).
    """
    from ..base import MXNetError
    if isinstance(shape, int):
        shape = (shape,)
    shape = tuple(int(s) for s in shape)
    n_rows = int(np.prod(shape[:-1], dtype=np.int64)) if len(shape) > 1 else 1
    n_col = shape[-1]
    range_max = int(range_max)
    if range_max < n_col:
        raise MXNetError(
            f"_sample_unique_zipfian: cannot draw {n_col} unique ids from "
            f"range_max={range_max} (reference unique_sample_op.cc CHECKs "
            "the same precondition)")

    def host_sample(seed):
        rs = np.random.RandomState(int(np.asarray(seed).ravel()[0]) & 0x7FFFFFFF)
        out = np.empty((n_rows, n_col), dtype=np.int32)
        tries = np.empty((n_rows,), dtype=np.int32)
        log_range = np.log(range_max + 1.0)
        for r in range(n_rows):
            seen = []
            seen_set = set()
            t = 0
            while len(seen) < n_col:
                draws = np.minimum(
                    np.exp(rs.uniform(size=n_col) * log_range).astype(np.int64)
                    - 1, range_max - 1)
                for d in draws:
                    if len(seen) >= n_col:
                        break
                    t += 1
                    if int(d) not in seen_set:
                        seen_set.add(int(d))
                        seen.append(int(d))
            out[r] = seen
            tries[r] = t
        return out.reshape(shape), tries.reshape(shape[:-1] or (1,))

    if isinstance(rng, jax.core.Tracer):
        # symbolic/traced path: host callback
        seed = jax.random.randint(rng, (1,), 0, 2**31 - 1)
        return jax.pure_callback(
            host_sample,
            (jax.ShapeDtypeStruct(shape, jnp.int32),
             jax.ShapeDtypeStruct(shape[:-1] or (1,), jnp.int32)),
            seed)
    seed = np.asarray(jax.random.randint(rng, (1,), 0, 2**31 - 1))
    samples, num_tries = host_sample(seed)
    return jnp.asarray(samples), jnp.asarray(num_tries)


# ---------------------------------------------------------------------------
# transformer / image helpers
# ---------------------------------------------------------------------------

@register("_contrib_div_sqrt_dim", aliases=["contrib_div_sqrt_dim"])
def _div_sqrt_dim(data):
    """Scale attention logits by 1/sqrt(d) (``contrib/transformer.cc``)."""
    return data / jnp.sqrt(jnp.asarray(data.shape[-1], data.dtype))


@register("_image_to_tensor", aliases=["image_to_tensor"])
def _image_to_tensor(data):
    """HWC (or NHWC) uint8 [0,255] -> CHW (NCHW) float32 [0,1]."""
    x = data.astype(jnp.float32) / 255.0
    if x.ndim == 3:
        return jnp.transpose(x, (2, 0, 1))
    if x.ndim == 4:
        return jnp.transpose(x, (0, 3, 1, 2))
    return x


@register("_image_normalize", aliases=["image_normalize"])
def _image_normalize(data, mean=0.0, std=1.0):
    """Channelwise (x - mean) / std on CHW / NCHW float images."""
    mean = jnp.asarray(mean, dtype=data.dtype)
    std = jnp.asarray(std, dtype=data.dtype)
    if mean.ndim == 1:
        mean = mean.reshape((-1, 1, 1))
    if std.ndim == 1:
        std = std.reshape((-1, 1, 1))
    return (data - mean) / std
