"""Optimizers.

Reference parity: ``python/mxnet/optimizer/optimizer.py`` (registry, Updater,
SGD/NAG/Adam/AdaGrad/AdaDelta/RMSProp/Ftrl/Signum/FTML/DCASGD/Adamax/Nadam,
multi-precision fp16 master weights) + the fused C++ kernels in
``src/operator/optimizer_op.cc``.

TPU-first: every update rule is a pure jax function jitted per (rule,
hyperparam-signature); scalar hyperparameters that change per step (lr, wd,
rescale) are traced *arguments* so no retrace happens when they change. The
whole update fuses into one XLA kernel per weight — the analogue of the
reference's fused sgd_mom_update kernels — and multi-tensor batches can ride
``jax.jit`` over stacked pytrees in the Trainer fast path.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .base import MXNetError
from .ndarray import NDArray
from .ndarray.ndarray import _unwrap, _wrap

__all__ = ["Optimizer", "SGD", "NAG", "Adam", "AdaGrad", "AdaDelta", "RMSProp",
           "Ftrl", "Signum", "FTML", "DCASGD", "Adamax", "Nadam", "LBSGD",
           "Test", "create", "register", "Updater", "get_updater"]

_OPT_REGISTRY: Dict[str, type] = {}


def register(klass):
    _OPT_REGISTRY[klass.__name__.lower()] = klass
    return klass


def create(name, **kwargs) -> "Optimizer":
    if isinstance(name, Optimizer):
        return name
    key = str(name).lower()
    if key not in _OPT_REGISTRY:
        raise MXNetError(f"unknown optimizer {name!r}")
    return _OPT_REGISTRY[key](**kwargs)


class Optimizer:
    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0, multi_precision=False,
                 param_dict=None):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.clip_gradient = clip_gradient
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count: Dict[int, int] = {}
        self.multi_precision = multi_precision
        self.idx2name = dict(param_idx2name or {})
        self.param_dict = dict(param_dict or {})
        self.lr_mult: Dict[Any, float] = {}
        self.wd_mult: Dict[Any, float] = {}

    # ------------------------------------------------------------- config
    def set_learning_rate(self, lr: float) -> None:
        if self.lr_scheduler is not None:
            raise MXNetError("lr_scheduler is active; set lr on the scheduler")
        self.lr = lr

    @property
    def learning_rate(self) -> float:
        if self.lr_scheduler is not None:
            return self.lr_scheduler(self.num_update)
        return self.lr

    def set_lr_mult(self, args_lr_mult: Dict[Any, float]) -> None:
        self.lr_mult = dict(args_lr_mult)

    def set_wd_mult(self, args_wd_mult: Dict[Any, float]) -> None:
        self.wd_mult = dict(args_wd_mult)

    def _update_count(self, index) -> None:
        if index not in self._index_update_count:
            self._index_update_count[index] = self.begin_num_update
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index], self.num_update)

    def _get_lr(self, index) -> float:
        lr = self.lr_scheduler(self.num_update) if self.lr_scheduler else self.lr
        if index in self.param_dict:
            lr *= self.param_dict[index].lr_mult
        elif index in self.lr_mult:
            lr *= self.lr_mult[index]
        elif index in self.idx2name:
            lr *= self.lr_mult.get(self.idx2name[index], 1.0)
        return lr

    def _get_wd(self, index) -> float:
        wd = self.wd
        if index in self.param_dict:
            wd *= self.param_dict[index].wd_mult
        elif index in self.wd_mult:
            wd *= self.wd_mult[index]
        elif index in self.idx2name:
            name = self.idx2name[index]
            wd *= self.wd_mult.get(name, 1.0)
            if name.endswith(("_gamma", "_beta", "_bias")):
                pass  # reference applies wd_mult from param attrs; default 1
        return wd

    # ------------------------------------------------------------- state
    def create_state(self, index, weight):
        return None

    def create_state_multi_precision(self, index, weight):
        if self.multi_precision and weight.dtype == np.float16:
            master = weight.astype("float32")
            return (master, self.create_state(index, master))
        return self.create_state(index, weight)

    def update(self, index, weight, grad, state):
        raise NotImplementedError

    def update_multi_precision(self, index, weight, grad, state):
        if self.multi_precision and weight.dtype == np.float16:
            master, base_state = state
            grad32 = grad.astype("float32")
            self.update(index, master, grad32, base_state)
            weight._set_data(master._data.astype(jnp.float16))
        else:
            self.update(index, weight, grad, state)

    # serialization for kvstore server-side optimizer (reference
    # kvstore_dist_server.h set_optimizer)
    def __getstate__(self):
        d = self.__dict__.copy()
        d.pop("_jit", None)
        return d


def _clipped(grad, rescale, clip):
    grad = grad * rescale
    if clip is not None:
        grad = jnp.clip(grad, -clip, clip)
    return grad


@register
class SGD(Optimizer):
    """SGD with momentum + weight decay (reference optimizer.py:SGD,
    fused kernel src/operator/optimizer_op.cc sgd_mom_update)."""

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return _wrap(jnp.zeros_like(_unwrap(weight)))

    @staticmethod
    @jax.jit
    def _step(w, g, mom, lr, wd, has_clip, clip, rescale, momentum):
        g = g * rescale
        g = jnp.where(has_clip, jnp.clip(g, -clip, clip), g)
        g = g + wd * w
        if mom is None:
            return w - lr * g, None
        new_mom = momentum * mom - lr * g
        return w + new_mom, new_mom

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        clip = self.clip_gradient
        w, g = _unwrap(weight), _unwrap(grad)
        mom = _unwrap(state) if state is not None else None
        new_w, new_mom = self._step(
            w, g, mom, jnp.float32(lr), jnp.float32(wd),
            jnp.bool_(clip is not None), jnp.float32(clip or 1e30),
            jnp.float32(self.rescale_grad), float(self.momentum))
        weight._set_data(new_w)
        if state is not None:
            state._set_data(new_mom)


@register
class NAG(SGD):
    """Nesterov accelerated SGD (reference optimizer.py:NAG)."""

    @staticmethod
    @jax.jit
    def _step(w, g, mom, lr, wd, has_clip, clip, rescale, momentum):
        g = g * rescale
        g = jnp.where(has_clip, jnp.clip(g, -clip, clip), g)
        g = g + wd * w
        if mom is None:
            return w - lr * g, None
        new_mom = momentum * mom + g
        return w - lr * (g + momentum * new_mom), new_mom


@register
class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def create_state(self, index, weight):
        z = jnp.zeros_like(_unwrap(weight))
        return (_wrap(z), _wrap(z))

    @staticmethod
    @jax.jit
    def _step(w, g, m, v, lr_t, wd, clip, rescale, beta1, beta2, eps):
        g = g * rescale
        g = jnp.where(jnp.isfinite(clip), jnp.clip(g, -clip, clip), g)
        g = g + wd * w
        m = beta1 * m + (1 - beta1) * g
        v = beta2 * v + (1 - beta2) * g * g
        return w - lr_t * m / (jnp.sqrt(v) + eps), m, v

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr, wd = self._get_lr(index), self._get_wd(index)
        lr_t = lr * math.sqrt(1 - self.beta2 ** t) / (1 - self.beta1 ** t)
        m, v = state
        new_w, new_m, new_v = self._step(
            _unwrap(weight), _unwrap(grad), _unwrap(m), _unwrap(v),
            jnp.float32(lr_t), jnp.float32(wd),
            jnp.float32(self.clip_gradient if self.clip_gradient else np.inf),
            jnp.float32(self.rescale_grad), self.beta1, self.beta2,
            jnp.float32(self.epsilon))
        weight._set_data(new_w)
        m._set_data(new_m)
        v._set_data(new_v)


@register
class AdaGrad(Optimizer):
    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return _wrap(jnp.zeros_like(_unwrap(weight)))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = _clipped(_unwrap(grad), self.rescale_grad, self.clip_gradient)
        g = g + wd * _unwrap(weight)
        hist = _unwrap(state) + g * g
        state._set_data(hist)
        weight._set_data(_unwrap(weight) - lr * g / (jnp.sqrt(hist) + self.float_stable_eps))


@register
class AdaDelta(Optimizer):
    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho, self.epsilon = rho, epsilon

    def create_state(self, index, weight):
        z = jnp.zeros_like(_unwrap(weight))
        return (_wrap(z), _wrap(z))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        wd = self._get_wd(index)
        g = _clipped(_unwrap(grad), self.rescale_grad, self.clip_gradient)
        acc_g, acc_delta = state
        ag = self.rho * _unwrap(acc_g) + (1 - self.rho) * g * g
        delta = jnp.sqrt(_unwrap(acc_delta) + self.epsilon) / jnp.sqrt(ag + self.epsilon) * g
        ad = self.rho * _unwrap(acc_delta) + (1 - self.rho) * delta * delta
        acc_g._set_data(ag)
        acc_delta._set_data(ad)
        weight._set_data(_unwrap(weight) - delta - wd * _unwrap(weight))


@register
class RMSProp(Optimizer):
    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9, epsilon=1e-8,
                 centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1, self.gamma2 = gamma1, gamma2
        self.epsilon = epsilon
        self.centered = centered
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        z = jnp.zeros_like(_unwrap(weight))
        if self.centered:
            return (_wrap(z), _wrap(z), _wrap(z))
        return _wrap(z)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = _clipped(_unwrap(grad), self.rescale_grad, self.clip_gradient)
        g = g + wd * _unwrap(weight)
        if self.centered:
            n, gbar, delta = state
            nn = self.gamma1 * _unwrap(n) + (1 - self.gamma1) * g * g
            gb = self.gamma1 * _unwrap(gbar) + (1 - self.gamma1) * g
            d = self.gamma2 * _unwrap(delta) - lr * g / jnp.sqrt(
                nn - gb * gb + self.epsilon)
            n._set_data(nn); gbar._set_data(gb); delta._set_data(d)
            new_w = _unwrap(weight) + d
        else:
            n = state
            nn = (1 - self.gamma1) * g * g + self.gamma1 * _unwrap(n)
            n._set_data(nn)
            new_w = _unwrap(weight) - lr * g / jnp.sqrt(nn + self.epsilon)
        if self.clip_weights:
            new_w = jnp.clip(new_w, -self.clip_weights, self.clip_weights)
        weight._set_data(new_w)


@register
class Ftrl(Optimizer):
    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1, self.beta = lamda1, beta

    def create_state(self, index, weight):
        z = jnp.zeros_like(_unwrap(weight))
        return (_wrap(z), _wrap(z))  # z, n

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = _clipped(_unwrap(grad), self.rescale_grad, self.clip_gradient)
        z, n = state
        w = _unwrap(weight)
        nn = _unwrap(n) + g * g
        sigma = (jnp.sqrt(nn) - jnp.sqrt(_unwrap(n))) / lr
        zz = _unwrap(z) + g - sigma * w
        z._set_data(zz); n._set_data(nn)
        new_w = jnp.where(
            jnp.abs(zz) > self.lamda1,
            -(zz - jnp.sign(zz) * self.lamda1) /
            ((self.beta + jnp.sqrt(nn)) / lr + wd), 0.0)
        weight._set_data(new_w.astype(w.dtype))


@register
class Signum(Optimizer):
    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return _wrap(jnp.zeros_like(_unwrap(weight)))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = _clipped(_unwrap(grad), self.rescale_grad, self.clip_gradient)
        w = _unwrap(weight)
        if state is not None:
            mom = self.momentum * _unwrap(state) - (1 - self.momentum) * (g + wd * w)
            state._set_data(mom)
            new_w = (1 - lr * self.wd_lh) * w + lr * jnp.sign(mom)
        else:
            new_w = (1 - lr * (wd + self.wd_lh)) * w - lr * jnp.sign(g)
        weight._set_data(new_w)


@register
class FTML(Optimizer):
    def __init__(self, learning_rate=0.0025, beta1=0.6, beta2=0.999, epsilon=1e-8,
                 **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def create_state(self, index, weight):
        z = jnp.zeros_like(_unwrap(weight))
        return (_wrap(z), _wrap(z), _wrap(z))  # d, v, z

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = _clipped(_unwrap(grad), self.rescale_grad, self.clip_gradient)
        g = g + wd * _unwrap(weight)
        d, v, z = state
        vv = self.beta2 * _unwrap(v) + (1 - self.beta2) * g * g
        d_t = (1 - self.beta1 ** t) / lr * (
            jnp.sqrt(vv / (1 - self.beta2 ** t)) + self.epsilon)
        sigma = d_t - self.beta1 * _unwrap(d)
        zz = self.beta1 * _unwrap(z) + (1 - self.beta1) * g - sigma * _unwrap(weight)
        d._set_data(d_t); v._set_data(vv); z._set_data(zz)
        weight._set_data(-zz / d_t)


@register
class DCASGD(Optimizer):
    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lamda = lamda

    def create_state(self, index, weight):
        z = jnp.zeros_like(_unwrap(weight))
        return (_wrap(z) if self.momentum != 0 else None, _wrap(_unwrap(weight)))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = _clipped(_unwrap(grad), self.rescale_grad, self.clip_gradient)
        mom, prev = state
        w = _unwrap(weight)
        comp = g + wd * w + self.lamda * g * g * (w - _unwrap(prev))
        if mom is not None:
            m = self.momentum * _unwrap(mom) - lr * comp
            mom._set_data(m)
            new_w = w + m
        else:
            new_w = w - lr * comp
        prev._set_data(w)
        weight._set_data(new_w)


@register
class Adamax(Optimizer):
    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2 = beta1, beta2

    def create_state(self, index, weight):
        z = jnp.zeros_like(_unwrap(weight))
        return (_wrap(z), _wrap(z))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr = self._get_lr(index) / (1 - self.beta1 ** t)
        wd = self._get_wd(index)
        g = _clipped(_unwrap(grad), self.rescale_grad, self.clip_gradient)
        g = g + wd * _unwrap(weight)
        m, u = state
        mm = self.beta1 * _unwrap(m) + (1 - self.beta1) * g
        uu = jnp.maximum(self.beta2 * _unwrap(u), jnp.abs(g))
        m._set_data(mm); u._set_data(uu)
        weight._set_data(_unwrap(weight) - lr * mm / (uu + 1e-8))


@register
class Nadam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.schedule_decay = schedule_decay
        self.m_schedule = 1.0

    def create_state(self, index, weight):
        z = jnp.zeros_like(_unwrap(weight))
        return (_wrap(z), _wrap(z))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = _clipped(_unwrap(grad), self.rescale_grad, self.clip_gradient)
        g = g + wd * _unwrap(weight)
        mom_t = self.beta1 * (1.0 - 0.5 * 0.96 ** (t * self.schedule_decay))
        mom_t1 = self.beta1 * (1.0 - 0.5 * 0.96 ** ((t + 1) * self.schedule_decay))
        self.m_schedule *= mom_t
        m_sched_next = self.m_schedule * mom_t1
        m, v = state
        mm = self.beta1 * _unwrap(m) + (1 - self.beta1) * g
        vv = self.beta2 * _unwrap(v) + (1 - self.beta2) * g * g
        g_prime = g / (1 - self.m_schedule)
        m_prime = mm / (1 - m_sched_next)
        v_prime = vv / (1 - self.beta2 ** t)
        m_bar = (1 - mom_t) * g_prime + mom_t1 * m_prime
        m._set_data(mm); v._set_data(vv)
        weight._set_data(_unwrap(weight) - lr * m_bar /
                         (jnp.sqrt(v_prime) + self.epsilon))


@register
class LBSGD(SGD):
    """Large-batch SGD with LARS-style layer-wise adaptive rate
    (reference optimizer.py:LBSGD)."""

    def __init__(self, momentum=0.0, warmup_strategy="linear", warmup_epochs=5,
                 batch_scale=1, updates_per_epoch=32, begin_epoch=0, num_epochs=60,
                 **kwargs):
        super().__init__(momentum=momentum, **kwargs)

    def update(self, index, weight, grad, state):
        w = _unwrap(weight)
        g = _unwrap(grad)
        wnorm = jnp.linalg.norm(w)
        gnorm = jnp.linalg.norm(g * self.rescale_grad)
        lars = jnp.where(gnorm > 0, wnorm / (gnorm + 1e-9), 1.0)
        lr_save = self.lr
        try:
            self.lr = float(self.lr * jnp.clip(lars, 0.0, 10.0))
            super().update(index, weight, grad, state)
        finally:
            self.lr = lr_save


@register
class Test(Optimizer):
    def create_state(self, index, weight):
        return _wrap(jnp.zeros_like(_unwrap(weight)))

    def update(self, index, weight, grad, state):
        weight._set_data(_unwrap(weight) - self.lr * _unwrap(grad) * self.rescale_grad)


@jax.jit
def _lazy_sgd_rows(w, idx, vals, lr, wd, rescale, clip):
    """SGD on the rows ``idx`` of ``w`` only, one executable per shape."""
    # merge duplicate indices first — the raw (values, indices) ctor permits
    # them, and todense() sums them, so the lazy path must too. ``size=``
    # keeps the shape static whatever the number of duplicates; the fill is
    # out of range, so the scatter drops it
    uniq, inv = jnp.unique(idx, return_inverse=True, size=idx.shape[0],
                           fill_value=w.shape[0])
    g = jnp.clip(jnp.zeros_like(vals).at[inv].add(vals) * rescale, -clip, clip)
    rows = w[uniq]
    return w.at[uniq].set(rows - lr * (g + wd * rows), mode="drop")


class Updater:
    """Closure applying an optimizer with per-index states (reference
    optimizer.py:Updater; serialized to KVStore servers via get_states)."""

    def __init__(self, optimizer: Optimizer):
        self.optimizer = optimizer
        self.states: Dict[Any, Any] = {}
        self.states_synced: Dict[Any, bool] = {}

    def __call__(self, index, grad, weight):
        from .ndarray.sparse import RowSparseNDArray
        if isinstance(grad, RowSparseNDArray):
            if self._lazy_row_sparse_update(index, grad, weight):
                return
            grad = grad.todense()   # stateful optimizers: standard update
        if index not in self.states:
            self.states[index] = self.optimizer.create_state_multi_precision(
                index, weight)
        self.optimizer.update_multi_precision(index, weight, grad,
                                              self.states[index])

    def _lazy_row_sparse_update(self, index, grad, weight) -> bool:
        """Row-sparse lazy update: touch ONLY the rows present in the
        gradient (reference sparse SGD kernel, optimizer_op.cc SGDUpdateEx
        row_sparse path / optimizer.py lazy_update=True). Supported for
        momentum-free SGD, where untouched rows are genuinely unchanged;
        stateful optimizers fall back to a dense update because their
        per-row state must decay every step."""
        opt = self.optimizer
        # plain lazy SGD only: momentum/delay-compensation/master-copy state
        # must evolve every step, which a touched-rows-only update cannot
        # honor; lazy_update=False requests reference std_update semantics
        # (weight decay applied to EVERY row each step)
        if not (type(opt).__name__ == "SGD"
                and getattr(opt, "momentum", 0) == 0
                and getattr(opt, "lazy_update", True)
                and not getattr(opt, "multi_precision", False)):
            return False
        opt._update_count(index)
        if grad._indices.shape[0]:
            weight._set_data(_lazy_sgd_rows(
                weight._data, grad._indices.astype(jnp.int32), grad._values,
                opt._get_lr(index), opt._get_wd(index), opt.rescale_grad,
                getattr(opt, "clip_gradient", None) or math.inf))
        return True

    def get_states(self, dump_optimizer=False):
        import pickle
        if dump_optimizer:
            return pickle.dumps((self.states, self.optimizer))
        return pickle.dumps(self.states)

    def set_states(self, states):
        import pickle
        obj = pickle.loads(states)
        if isinstance(obj, tuple):
            self.states, self.optimizer = obj
        else:
            self.states = obj


def get_updater(optimizer: Optimizer) -> Updater:
    return Updater(optimizer)
