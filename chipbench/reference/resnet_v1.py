"""Plain reference: ResNet v1 (He et al. 2015, arXiv:1512.03385) as the
reference framework's Gluon model zoo builds it, in straightforward
``jax.numpy`` and float32.

It imports nothing of the program under test (``chipbench.flops`` and
``chipbench.rounding`` are the benchmark's own arithmetic). It makes its own
weights from the seed (the harness hands the same weights to the program),
and its ``loss_fn`` is what ``follow.py`` steps.

Departures from the paper, all taken from the zoo's v1 definition and noted
so that nobody "fixes" them: the bottleneck strides on its FIRST 1x1
convolution; the bottleneck's 1x1 convolutions carry a bias (dead under the
BatchNorm that follows, see ``PERF.md``); weight decay is applied to every
trainable leaf, biases and BatchNorm scales included, as the trainer does.

Layouts here: activations NHWC, convolution weights HWIO, dense (out, in).
"""
import functools

import jax
import jax.numpy as jnp

from chipbench import flops
from chipbench.rounding import fake_quant as _fake_quant

BN_EPS = 1e-5
BN_MOMENTUM = 0.9


# ------------------------------------------------------------ structure
def blocks(arch):
    """[(stage, in_channels, channels, stride, downsample)] for every block."""
    out = []
    chans = arch["channels"]
    for i, n in enumerate(arch["layers"]):
        stride = 1 if i == 0 else 2
        cin, c = chans[i], chans[i + 1]
        out.append((i + 1, cin, c, stride, c != cin))
        out.extend((i + 1, c, c, 1, False) for _ in range(n - 1))
    return out


def block_convs(arch, cin, c, stride, downsample):
    """The convolutions of one block, in the zoo's creation order:
    (kernel, in, out, stride, has_bias)."""
    if arch["block"] == "bottleneck":
        m = c // 4
        convs = [(1, cin, m, stride, True), (3, m, m, 1, False),
                 (1, m, c, 1, True)]
    elif arch["block"] == "basic":
        convs = [(3, cin, c, stride, False), (3, c, c, 1, False)]
    else:
        raise ValueError("unknown block %r" % (arch["block"],))
    if downsample:
        convs.append((1, cin, c, stride, False))
    return convs


def conv_layers(cfg):
    """Every convolution and dense layer with the output size it is applied
    at: dicts {kind, k, cin, cout, out_hw} — what ``flops.py`` counts."""
    arch, hw = cfg["arch"], cfg["image"]
    hw = (hw + 2 * 3 - 7) // 2 + 1
    out = [dict(kind="conv", k=7, cin=3, cout=arch["channels"][0], out_hw=hw)]
    hw = (hw + 2 * 1 - 3) // 2 + 1
    for _, cin, c, stride, ds in blocks(arch):
        hw_out = (hw - 1) // stride + 1
        for k, ci, co, _s, _b in block_convs(arch, cin, c, stride, ds):
            out.append(dict(kind="conv", k=k, cin=ci, cout=co, out_hw=hw_out))
        hw = hw_out
    out.append(dict(kind="dense", k=1, cin=arch["channels"][-1],
                    cout=cfg["classes"], out_hw=1))
    return out


def train_flops_per_item(cfg):
    """FLOPs one image requires of a training step: 6 x the multiply-adds
    of every convolution and dense layer (``chipbench/flops.py``)."""
    return flops.train_flops_per_item(conv_layers(cfg))


def leaf_specs(cfg):
    """Every parameter leaf in the zoo's creation order:
    (kind, shape, trainable). kind: conv | dense | bias | gamma | beta |
    mean | var."""
    arch = cfg["arch"]
    out = []

    def bn(c):
        out.extend([("gamma", (c,), True), ("beta", (c,), True),
                    ("mean", (c,), False), ("var", (c,), False)])

    def conv(k, cin, cout, bias):
        out.append(("conv", (k, k, cin, cout), True))
        if bias:
            out.append(("bias", (cout,), True))
        bn(cout)

    conv(7, 3, arch["channels"][0], False)
    for _, cin, c, stride, ds in blocks(arch):
        for k, ci, co, _s, b in block_convs(arch, cin, c, stride, ds):
            conv(k, ci, co, b)
    out.append(("dense", (cfg["classes"], arch["channels"][-1]), True))
    out.append(("bias", (cfg["classes"],), True))
    return out


def init(cfg, key):
    """All leaves from one key: Xavier-uniform weights (magnitude 3, average
    of the fans), zero biases, unit BatchNorm scales. One call, jit it."""
    leaves = []
    for i, (kind, shape, _t) in enumerate(leaf_specs(cfg)):
        if kind == "conv":
            kh, kw, cin, cout = shape
            bound = (6.0 / (kh * kw * (cin + cout))) ** 0.5
        elif kind == "dense":
            bound = (6.0 / (shape[0] + shape[1])) ** 0.5
        if kind in ("conv", "dense"):
            leaves.append(jax.random.uniform(
                jax.random.fold_in(key, i), shape, jnp.float32, -bound, bound))
        elif kind in ("gamma", "var"):
            leaves.append(jnp.ones(shape, jnp.float32))
        else:
            leaves.append(jnp.zeros(shape, jnp.float32))
    return leaves


# -------------------------------------------------------------- forward
def _conv(x, w, stride, rounding):
    pad = (w.shape[0] - 1) // 2
    return jax.lax.conv_general_dilated(
        _fake_quant(x, rounding), _fake_quant(w, rounding), (stride, stride),
        [(pad, pad), (pad, pad)], dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def _bn(x, gamma, beta):
    """Training-mode BatchNorm: (output, batch mean, biased batch variance)."""
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + BN_EPS) * gamma + beta, mean, var


def _take(it, bias):
    w = next(it)
    b = next(it) if bias else None
    gamma, beta, _mean, _var = next(it), next(it), next(it), next(it)
    return w, b, gamma, beta


def _conv_bn(x, it, stride, bias, rounding):
    # the control keeps every tensor the program keeps in bfloat16 in the
    # lower precision instead: operands, the convolution's output, the
    # BatchNorm's output (statistics stay float32, as the program's do)
    w, b, gamma, beta = _take(it, bias)
    y = _fake_quant(_conv(x, w, stride, rounding), rounding)
    if b is not None:
        y = y + b
    y, mean, var = _bn(y, gamma, beta)
    return _fake_quant(y, rounding), [mean, var]


def _block(arch, spec, rounding, x, leaves):
    _, cin, c, stride, ds = spec
    it = iter(leaves)
    convs = block_convs(arch, cin, c, stride, ds)
    body = convs[:-1] if ds else convs
    y, stats = x, []
    for j, (_k, _ci, _co, s, b) in enumerate(body):
        y, st = _conv_bn(y, it, s, b, rounding)
        stats += st
        if j < len(body) - 1:
            y = jnp.maximum(y, 0)
    res = x
    if ds:
        res, st = _conv_bn(x, it, stride, False, rounding)
        stats += st
    return _fake_quant(jnp.maximum(y + res, 0), rounding), stats


def _block_leaf_count(arch, spec):
    _, cin, c, stride, ds = spec
    return sum(5 + int(b) for *_x, b in block_convs(arch, cin, c, stride, ds))


def logits(cfg, leaves, x, rounding=None):
    """Training-mode forward (batch statistics): (logits, [mean, variance of
    every BatchNorm's batch, in leaf order]). Each block is rematerialised
    in the backward pass so that float32 fits the chip."""
    arch = cfg["arch"]
    it = iter(leaves[:5])
    y, stats = _conv_bn(x, it, 2, False, rounding)
    y = jnp.maximum(y, 0)
    y = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), ((0, 0), (1, 1), (1, 1), (0, 0)))
    at = 5
    for spec in blocks(arch):
        n = _block_leaf_count(arch, spec)
        fn = jax.checkpoint(functools.partial(_block, arch, spec, rounding))
        y, st = fn(y, leaves[at:at + n])
        stats += st
        at += n
    feat = jnp.mean(y, (1, 2))
    w, b = leaves[at], leaves[at + 1]
    z = jnp.matmul(_fake_quant(feat, rounding), _fake_quant(w, rounding).T,
                   precision=jax.lax.Precision.HIGHEST) + b
    return z, stats


def loss_fn(cfg, leaves, x, labels, rounding=None, rows=None):
    """(mean softmax cross-entropy, the new value of every non-trainable
    leaf in order: the running means and variances, moved a tenth of the way
    to the batch's as the zoo's BatchNorm moves them). ``rows`` plants the
    fault "part of the batch left out, the mean taken over the rest"."""
    if rows is not None:
        x, labels = x[rows], labels[rows]
    z, stats = logits(cfg, leaves, x, rounding)
    logp = jax.nn.log_softmax(z.astype(jnp.float32))
    picked = jnp.take_along_axis(logp, labels.astype(jnp.int32)[:, None], 1)
    old = [l for l, (_k, _s, t) in zip(leaves, leaf_specs(cfg)) if not t]
    state = [jax.lax.stop_gradient(BN_MOMENTUM * o + (1 - BN_MOMENTUM) * n)
             for o, n in zip(old, stats)]
    return -jnp.mean(picked), state
