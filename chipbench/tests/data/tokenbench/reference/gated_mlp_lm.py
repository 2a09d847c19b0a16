"""Plain reference of the token rehearsal's model (``chipbench/tests/
tiny_lm.py``): an embedding, ``num_hidden_layers`` gated-SiLU dense layers
each added to its input, an untied vocabulary head; mean cross-entropy of the
next id over every position. float32 at ``highest``; imports nothing of the
program. It has what every family's reference has: ``leaf_specs``, ``init``,
``loss_fn`` (with ``rounding=`` / ``rows=``) and ``train_flops_per_item``.

Layouts: embedding and head (vocabulary, width); dense (out, in)."""
import jax
import jax.numpy as jnp

from chipbench import flops
from chipbench.rounding import fake_quant

HIGHEST = jax.lax.Precision.HIGHEST


def leaf_specs(cfg):
    """(kind, shape, trainable) of every leaf in the block's creation order."""
    v, w, h = cfg["vocab_held"], cfg["hidden_size"], cfg["intermediate_size"]
    out = [("embed", (v, w), True)]
    for _ in range(cfg["num_hidden_layers"]):
        out += [("dense", (h, w), True), ("dense", (h, w), True), ("dense", (w, h), True)]
    return out + [("dense", (v, w), True)]


def init(cfg, key):
    """All leaves from one key: unit-normal embedding rows, Xavier-uniform
    dense weights (magnitude 3, average of the fans). One call, jit it."""
    leaves = []
    for i, (kind, shape, _t) in enumerate(leaf_specs(cfg)):
        k = jax.random.fold_in(key, i)
        if kind == "embed":
            leaves.append(jax.random.normal(k, shape, jnp.float32))
        else:
            bound = (6.0 / (shape[0] + shape[1])) ** 0.5
            leaves.append(jax.random.uniform(k, shape, jnp.float32, -bound, bound))
    return leaves


def train_flops_per_item(cfg):
    """FLOPs one TOKEN requires of a training step: three projections a
    layer and the head over the vocabulary rows held; the lookup is a gather."""
    w, h = cfg["hidden_size"], cfg["intermediate_size"]
    per_token = (cfg["num_hidden_layers"]
                 * (2 * flops.dense_macs(w, h) + flops.dense_macs(h, w))
                 + flops.head_macs(w, cfg["vocab_held"]))
    return flops.train_flops(per_token)


def loss_fn(cfg, leaves, x, labels, rounding=None, rows=None):
    """(mean cross-entropy over every position, []): the net has no
    non-trainable leaf. ``rounding`` keeps every tensor the program keeps in
    its compute type in that format instead; ``rows`` plants the fault "part
    of the batch left out, the mean taken over the rest"."""
    if rows is not None:
        x, labels = x[rows], labels[rows]
    q = lambda t: fake_quant(t, rounding)  # noqa: E731
    mm = lambda a, w: q(jnp.matmul(q(a), q(w).T, precision=HIGHEST))  # noqa: E731
    it = iter(leaves)
    h = q(jnp.take(q(next(it)), x.astype(jnp.int32), axis=0))
    for _ in range(cfg["num_hidden_layers"]):
        gate, up, down = next(it), next(it), next(it)
        a = mm(h, gate)
        h = q(h + mm(q(a * jax.nn.sigmoid(a) * mm(h, up)), down))
    logp = q(jax.nn.log_softmax(mm(h, next(it))))
    picked = jnp.take_along_axis(logp, labels.astype(jnp.int32)[..., None], -1)
    return -jnp.mean(picked), []
