"""Follow the first training steps in the plain reference and reduce its
states to what the comparison holds: each step's loss, the norm of every
trainable leaf's first update over the learning rate (under SGD the gradient
as the optimizer gets it, weight decay included), the norm of its change
after the last followed step, both of those WHOLE as well (the direction
numbers of ``check.py`` need the two sides' leaves side by side: brought to
the host leaf by leaf), and the first step's change of every non-trainable
leaf (BatchNorm's running statistics) whole, a few thousand numbers.

The optimizer is the one the configuration names, written out here in plain
``jax.numpy``: this file imports neither the program nor optax.
``loss_fn(leaves, x, labels)`` is a configuration's reference loss and
returns (loss, the new values of the non-trainable leaves in order); leaves
are a flat list.
"""
import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _norms(a, b):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32)))) for x, y in zip(a, b)])


def leaf_norms(a, b, scale=1.0):
    """[||a_i - b_i|| * scale] on the device, one small vector back."""
    return np.asarray(_norms(list(a), list(b)), np.float64) * scale


def _sgd(opt):
    """SGD with momentum and weight decay (optax's order:
    add_decayed_weights, then sgd's trace). State: the trace."""
    lr, mom, wd = opt["learning_rate"], opt["momentum"], opt["wd"]

    def update(grads, state, weights):
        trace = [g + wd * w + mom * m for g, w, m in zip(grads, weights, state)]
        return [w - lr * m for w, m in zip(weights, trace)], trace

    return (lambda weights: [jnp.zeros_like(w) for w in weights]), update


def _adam(opt):
    """optax.chain(add_decayed_weights(wd), adam(lr, b1, b2, eps)) under the
    program's parameter names: the decay joins the gradient BEFORE the
    moments (L2, not AdamW). State: (step count, first, second moments)."""
    lr, wd = opt["learning_rate"], opt.get("wd", 0.0)
    b1, b2 = opt.get("beta1", 0.9), opt.get("beta2", 0.999)
    eps = opt.get("epsilon", 1e-8)

    def init(weights):
        zeros = [jnp.zeros_like(w) for w in weights]
        return jnp.zeros((), jnp.int32), zeros, list(zeros)

    def update(grads, state, weights):
        count, mu, nu = state
        count = count + 1
        grads = [g + wd * w for g, w in zip(grads, weights)]
        mu = [b1 * m + (1 - b1) * g for m, g in zip(mu, grads)]
        nu = [b2 * v + (1 - b2) * g * g for v, g in zip(nu, grads)]
        c1 = 1 - jnp.float32(b1) ** count
        c2 = 1 - jnp.float32(b2) ** count
        new = [w - lr * (m / c1) / (jnp.sqrt(v / c2) + eps)
               for w, m, v in zip(weights, mu, nu)]
        return new, (count, mu, nu)

    return init, update


OPTIMIZERS = {"sgd": _sgd, "adam": _adam}


def make_step(loss_fn, trainable, opt):
    """(init_state, step) for the optimizer ``opt["name"]`` on every
    trainable leaf. ``init_state(trainable leaves) -> state``; ``step`` is
    jitted: (leaves, state, x, y) -> (leaves, state, loss). A name this file
    does not follow is an error."""
    if opt["name"] not in OPTIMIZERS:
        raise SystemExit("chipbench: follow.py does not follow the optimizer "
                         "%r; it has %s" % (opt["name"], sorted(OPTIMIZERS)))
    init_state, update = OPTIMIZERS[opt["name"]](opt)
    idx = [i for i, t in enumerate(trainable) if t]
    fixed = [i for i, t in enumerate(trainable) if not t]

    @jax.jit
    def step(leaves, state, x, y):
        def of_trainable(tr):
            full = list(leaves)
            for i, v in zip(idx, tr):
                full[i] = v
            return loss_fn(full, x, y)
        tr = [leaves[i] for i in idx]
        (loss, aux), grads = jax.value_and_grad(of_trainable, has_aux=True)(tr)
        new_tr, new_state = update(grads, state, tr)
        new = list(leaves)
        for i, w in zip(idx, new_tr):
            new[i] = w
        for i, v in zip(fixed, aux):
            new[i] = v
        return new, new_state, loss

    return init_state, step


def _host_changes(new, old):
    """[new_i - old_i] as host float32, one leaf at a time."""
    return [np.asarray(a - b, np.float32) for a, b in zip(new, old)]


def follow(init_state, step, leaves, trainable, batches, lr):
    """Run ``step`` (of ``make_step``) over the batches. Returns {"loss":
    [...], "grad1": [...], "dparam": [...], "update1": [arrays], "change":
    [arrays], "state1": [arrays]}; the first four run over the trainable
    leaves in order, ``state1`` over the others."""
    idx = [i for i, t in enumerate(trainable) if t]
    fixed = [i for i, t in enumerate(trainable) if not t]
    w0 = [leaves[i] for i in idx]
    state = init_state(w0)
    cur, losses, grad1, update1, state1 = list(leaves), [], None, None, None
    for n, (x, y) in enumerate(batches):
        cur, state, loss = step(cur, state, x, y)
        losses.append(float(loss))
        if n == 0:
            w1 = [cur[i] for i in idx]
            grad1 = leaf_norms(w0, w1, 1.0 / lr)
            update1 = _host_changes(w1, w0)
            state1 = [np.asarray(cur[i], np.float64) - np.asarray(leaves[i], np.float64)
                      for i in fixed]
    w = [cur[i] for i in idx]
    return {"loss": losses, "grad1": grad1.tolist(),
            "dparam": leaf_norms(w0, w).tolist(), "update1": update1,
            "change": _host_changes(w, w0), "state1": state1}
