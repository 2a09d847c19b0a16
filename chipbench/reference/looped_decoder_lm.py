"""Plain reference of the looped decoder LM (``mxnet_tpu.gluon.contrib.
transformer.LoopedDecoderLM`` under ``gluon.loss.ExpectedExitCELoss``):
float32 at ``highest``, plain ``softmax(q k^T) v`` attention, imports nothing
of the program. It has what every family's reference has: ``leaf_specs``,
``init``, ``loss_fn`` (with ``rounding=`` / ``rows=``) and
``train_flops_per_item``; ``exits`` gives every exit's logits and gate for the
tests.

The mathematics. Ids ``x[B,S]``, next ids ``y[B,S]``; ``h = E[x]``. For pass
``t = 1..T`` (``total_ut_steps``) and layer ``l = 1..L``, the SAME leaves in
every pass::

    a = RMSNorm_1(h);  q, k, v = a Wq, a Wk, a Wv      (heads of head_dim)
    q, k = rope(q), rope(k)    [x1, x2] -> [x1 cos - x2 sin, x2 cos + x1 sin],
                               angle = position * theta**(-2i/head_dim)
    o = softmax(q k^T / sqrt(head_dim) + causal mask) v
    h = h + RMSNorm_2(o Wo)
    m = RMSNorm_3(h);  h = h + RMSNorm_4((silu(m Wg) * (m Wu)) Wd)
    RMSNorm(z) = z / sqrt(mean(z^2) + eps) * g

After the last layer of a pass ``h = RMSNorm_f(h)``: this pass's exit and the
next pass's input. ``z_t = h Wh^T``, ``lam_t = sigmoid(h w + b)`` per token;
``p_t = lam_t prod_{j<t}(1 - lam_j)`` for ``t < T``, ``p_T = prod_{j<T}(1 -
lam_j)``; loss = mean over tokens of ``sum_t p_t CE(z_t, y) - beta H(p)``.
No mask at a document's boundary.

So that it fits on one chip beside ``follow.py``'s 32 bytes a parameter,
every layer-call runs under ``jax.checkpoint``, attention is taken over
blocks of queries and the head over blocks of tokens, each block recomputed
in the backward pass: the same arithmetic in the same order per row.

Layouts: embedding and head (vocabulary, width); dense (out, in); the fused
attention weight's rows are the queries' heads, then the keys', the values'.
"""
import jax
import jax.numpy as jnp

from chipbench import flops
from chipbench.rounding import fake_quant

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
QUERY_BLOCK = 512     # 16 x 512 x 4,096 float32 scores: 134 MB
TOKEN_BLOCK = 1024    # 1,024 x 49,152 float32 logits: 201 MB
LAYER_LEAVES = 9


def _sizes(cfg):
    return (cfg["vocab_held"], cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["head_dim"])


def leaf_specs(cfg):
    """(kind, shape, trainable) of every leaf in the order of the block's
    ``collect_params()``: its own head first, then its children as made.
    Each leaf once, however many passes read it."""
    v, w, f, heads, hd = _sizes(cfg)
    out = [("head", (v, w), True), ("embed", (v, w), True)]
    for _ in range(cfg["num_hidden_layers"]):
        out += [("norm", (w,), True), ("dense", (3 * heads * hd, w), True),
                ("dense", (w, heads * hd), True), ("norm", (w,), True),
                ("norm", (w,), True), ("dense", (f, w), True),
                ("dense", (f, w), True), ("dense", (w, f), True),
                ("norm", (w,), True)]
    return out + [("norm", (w,), True), ("dense", (1, w), True),
                  ("bias", (1,), True)]


def init(cfg, key):
    """All leaves from one key: normal(0, 0.02) matrices, unit gains, a zero
    gate bias. One call, jit it."""
    leaves = []
    for i, (kind, shape, _t) in enumerate(leaf_specs(cfg)):
        if kind == "norm":
            leaves.append(jnp.ones(shape, F32))
        elif kind == "bias":
            leaves.append(jnp.zeros(shape, F32))
        else:
            leaves.append(F32(0.02) * jax.random.normal(
                jax.random.fold_in(key, i), shape, F32))
    return leaves


def train_flops_per_item(cfg):
    """FLOPs one TOKEN requires of a training step: every pass is the
    model's own mathematics and counts; what is recomputed does not."""
    _v, w, f, heads, hd = _sizes(cfg)
    layer = (4 * flops.dense_macs(w, w) + 3 * flops.dense_macs(w, f)
             + flops.causal_attention_macs(cfg["seq_len"], heads, hd, hd))
    one_pass = (cfg["num_hidden_layers"] * layer
                + flops.head_macs(w, cfg["vocab_held"]) + flops.dense_macs(w, 1))
    return flops.train_flops(cfg["total_ut_steps"] * one_pass)


# ------------------------------------------------------------------ layers
def _mm(a, w, q):
    return q(jnp.matmul(a, q(w).T, precision=HIGHEST))


def _rms(z, g, eps, q):
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(z), axis=-1, keepdims=True) + F32(eps))
    return q(z * inv * q(g))


def _rope(t, theta):
    """(B, H, S, D) turned by position, half-rotation form."""
    s, d = t.shape[-2], t.shape[-1]
    half = d // 2
    inv_freq = F32(theta) ** (-jnp.arange(half, dtype=F32) * F32(2.0 / d))
    angle = jnp.arange(s, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], axis=-1)


def _attention(qh, kh, vh):
    """Causal softmax(q k^T / sqrt(d)) v over (B, H, S, D), a block of
    queries at a time, each block recomputed in the backward pass."""
    b, h, s, d = qh.shape
    blk = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    kpos = jnp.arange(s, dtype=jnp.int32)

    @jax.checkpoint
    def one(args):
        qb, start = args                                    # (B, H, blk, D)
        scores = jnp.einsum("bhqd,bhkd->bhqk", qb, kh,
                            precision=HIGHEST) * F32(d ** -0.5)
        qpos = start + jnp.arange(blk, dtype=jnp.int32)
        scores = jnp.where(qpos[:, None] >= kpos[None, :], scores, F32(-1e30))
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1),
                          vh, precision=HIGHEST)

    blocks = qh.reshape(b, h, s // blk, blk, d).transpose(2, 0, 1, 3, 4)
    starts = jnp.arange(s // blk, dtype=jnp.int32) * blk
    out = jax.lax.map(one, (blocks, starts))                # (n, B, H, blk, D)
    return out.transpose(1, 2, 0, 3, 4).reshape(b, h, s, d)


def _layer(cfg, q, h, leaves):
    n1, wqkv, wo, n2, n3, wg, wu, wd, n4 = leaves
    _v, w, _f, heads, hd = _sizes(cfg)
    eps, b, s = cfg["rms_norm_eps"], h.shape[0], h.shape[1]
    qkv = _mm(_rms(h, n1, eps, q), wqkv, q)                 # (B, S, 3 H D)
    qkv = qkv.reshape(b, s, 3 * heads, hd).transpose(0, 2, 1, 3)
    qh = q(_rope(qkv[:, :heads], cfg["rope_theta"]))
    kh = q(_rope(qkv[:, heads:2 * heads], cfg["rope_theta"]))
    o = q(_attention(qh, kh, qkv[:, 2 * heads:]))
    o = o.transpose(0, 2, 1, 3).reshape(b, s, heads * hd)
    h = q(h + _rms(_mm(o, wo, q), n2, eps, q))
    m = _rms(h, n3, eps, q)
    g = _mm(m, wg, q)
    act = q(q(g * q(jax.nn.sigmoid(g))) * _mm(m, wu, q))
    return q(h + _rms(_mm(act, wd, q), n4, eps, q))


def _passes(cfg, leaves, x, q):
    """([the T exit states (B, S, W)], [the T gates (B, S)], the head)."""
    head, embed = leaves[0], leaves[1]
    n = cfg["num_hidden_layers"]
    layers = [leaves[2 + LAYER_LEAVES * i:2 + LAYER_LEAVES * (i + 1)]
              for i in range(n)]
    norm_f, gate_w, gate_b = leaves[2 + LAYER_LEAVES * n:]
    layer = jax.checkpoint(lambda h, lv: _layer(cfg, q, h, lv))
    h = jnp.take(q(embed), x.astype(jnp.int32), axis=0)
    states, gates = [], []
    for _ in range(cfg["total_ut_steps"]):
        for lv in layers:
            h = layer(h, lv)
        h = _rms(h, norm_f, cfg["rms_norm_eps"], q)
        states.append(h)
        z = q(jnp.matmul(h, q(gate_w).T, precision=HIGHEST) + q(gate_b))
        gates.append(q(jax.nn.sigmoid(z[..., 0])))
    return states, gates, head


def exits(cfg, leaves, x, rounding=None):
    """Every exit's logits (T, B, S, V) and gate (T, B, S), whole: a test's
    view, at sizes where the logits fit."""
    q = lambda t: fake_quant(t, rounding)  # noqa: E731
    states, gates, head = _passes(cfg, leaves, x, q)
    return jnp.stack([_mm(h, head, q) for h in states]), jnp.stack(gates)


def _cross_entropy(h, head, labels, q):
    """-log softmax(h head^T)[label] per token (N,), a block of tokens at a
    time, each block's logits recomputed in the backward pass."""
    n, w = h.shape
    blk = TOKEN_BLOCK if n % TOKEN_BLOCK == 0 else n

    @jax.checkpoint
    def one(args):
        hb, yb = args
        logp = jax.nn.log_softmax(_mm(hb, head, q), axis=-1)
        return -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]

    return jax.lax.map(one, (h.reshape(n // blk, blk, w),
                             labels.reshape(n // blk, blk))).reshape(n)


def loss_fn(cfg, leaves, x, labels, rounding=None, rows=None):
    """(mean over tokens of sum_t p_t CE_t - beta H(p), []): the net has no
    non-trainable leaf. ``rounding`` keeps every tensor the program keeps in
    its compute type in that format instead. ``rows`` plants the fault "part
    of the batch left out, the mean taken over the rest": ``x[rows]``,
    except that a slice which would leave a batch (of ONE row, say) empty
    leaves out the second half of every row's positions instead: half the
    tokens left out either way."""
    if rows is not None:
        if len(range(*rows.indices(x.shape[0]))):
            x, labels = x[rows], labels[rows]
        else:
            half = x.shape[1] // 2
            x, labels = x[:, :half], labels[:, :half]
    q = lambda t: fake_quant(t, rounding)  # noqa: E731
    states, gates, head = _passes(cfg, leaves, x, q)
    y = labels.astype(jnp.int32).reshape(-1)
    probs, rest = [], None
    for g in gates[:-1]:
        probs.append(g if rest is None else g * rest)
        rest = (1.0 - g) if rest is None else rest * (1.0 - g)
    probs.append(jnp.ones_like(gates[0]) if rest is None else rest)
    beta = F32(cfg["loss_kwargs"]["beta"])
    total = F32(0.0)
    for h, p in zip(states, probs):
        p = p.reshape(-1)
        ce = _cross_entropy(h.reshape(-1, h.shape[-1]), head, y, q)
        total = total + jnp.mean(p * ce + beta * p * jnp.log(jnp.maximum(p, F32(1e-30))))
    return total, []
