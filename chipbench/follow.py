"""Follow the first training steps in the plain reference and reduce its
states to what the comparison holds: each step's loss, the norm of every
trainable leaf's first update over the learning rate (the gradient as SGD
gets it, weight decay included), the norm of its change after the last
followed step, and the first step's change of every non-trainable leaf
(BatchNorm's running statistics) whole, a few thousand numbers.

Imports nothing of the program. ``loss_fn(leaves, x, labels)`` is a
configuration's reference loss and returns (loss, the new values of the
non-trainable leaves in order); leaves are a flat list.
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


def make_step(loss_fn, trainable, opt):
    """One jitted step of SGD with momentum and weight decay on every
    trainable leaf (optax's order: add_decayed_weights, then sgd's trace):
    (leaves, trace, x, y) -> (leaves, trace, loss)."""
    lr, mom, wd = opt["learning_rate"], opt["momentum"], opt["wd"]
    idx = [i for i, t in enumerate(trainable) if t]
    fixed = [i for i, t in enumerate(trainable) if not t]

    @jax.jit
    def step(leaves, trace, x, y):
        def of_trainable(tr):
            full = list(leaves)
            for i, v in zip(idx, tr):
                full[i] = v
            return loss_fn(full, x, y)
        tr = [leaves[i] for i in idx]
        (loss, state), grads = jax.value_and_grad(of_trainable, has_aux=True)(tr)
        new_trace = [g + wd * w + mom * m for g, w, m in zip(grads, tr, trace)]
        new = list(leaves)
        for i, w, m in zip(idx, tr, new_trace):
            new[i] = w - lr * m
        for i, v in zip(fixed, state):
            new[i] = v
        return new, new_trace, loss

    return step


def sgd_follow(step, leaves, trainable, batches, lr):
    """Run ``step`` (of ``make_step``) over the batches. Returns {"loss":
    [...], "grad1": [...], "dparam": [...], "state1": [arrays]}; the two norm
    lists run over the trainable leaves in order, ``state1`` over the others."""
    idx = [i for i, t in enumerate(trainable) if t]
    fixed = [i for i, t in enumerate(trainable) if not t]
    w0 = [leaves[i] for i in idx]
    trace = [jnp.zeros_like(w) for w in w0]
    cur, losses, grad1, state1 = list(leaves), [], None, None
    for n, (x, y) in enumerate(batches):
        cur, trace, loss = step(cur, trace, x, y)
        losses.append(float(loss))
        if n == 0:
            grad1 = leaf_norms(w0, [cur[i] for i in idx], 1.0 / lr)
            state1 = [np.asarray(cur[i], np.float64) - np.asarray(leaves[i], np.float64)
                      for i in fixed]
    dparam = leaf_norms(w0, [cur[i] for i in idx])
    return {"loss": losses, "grad1": grad1.tolist(), "dparam": dparam.tolist(),
            "state1": state1}
