"""Plain reference of the ZAYA1 decoder LM (``mxnet_tpu.gluon.contrib.
transformer.ZayaDecoderLM`` under ``gluon.loss.TiedHeadCELoss``): float32 at
``highest``, plain ``softmax(q k^T) v`` attention, the experts as a loop over
the held experts under a mask, imports nothing of the program. It has what
every family's reference has: ``leaf_specs``, ``init``, ``loss_fn`` (with
``rounding=`` / ``rows=``) and ``train_flops_per_item``; ``logits`` and
``routes`` are the tests' views.

The mathematics (arXiv:2511.17127; its attention arXiv:2510.04476). Ids
``x[B,S]``, next ids ``y[B,S]``; ``h = E[x]``. ``norm`` is RMSNorm with a
gain, ``z / sqrt(mean(z^2) + eps) * g``. For layer ``l = 0..L-1``::

    a = norm_a(h)
    q0, k0 = a Wq, a Wk                     (H and KV heads of D)
    v = [a Wv_0 ; shift(a Wv_1)]            shift(z)_t = z_{t-1}, 0 at t = 0
    [qc ; kc] = conv2(conv1([q0 ; k0]))     along the row, both causal (left
                  padding k - 1), each with a bias: conv1 depthwise, conv2
                  grouped by head
    q = qc + (q0 + k0[its key head]) / 2;   k = kc + (k0 + mean of its query
                                                heads' q0) / 2
    q, k = q / rms(q), k / rms(k) * temperature[kv head]     per head
    q, k = rope(q), rope(k)   on the first R = partial_rotary_factor * D
              channels: [x1, x2] -> [x1 cos - x2 sin, x2 cos + x1 sin],
              angle = position * theta**(-2i/R); channels R.. untouched
    o = softmax(q k^T / sqrt(D) + causal mask) v      a key/value head serves
                                                      its H / KV query heads
    h = h + (o Wo * scale_a + shift_a)
    m = norm_m(h)
    r_l = m Wd + b  [+ mix_l * r_{l-1} for l > 0]                 (float32)
    s = softmax(W3 gelu(W2 gelu(W1 r_l)));  e = argmax s          (float32)
    h = h + (s[e] * Wdown_e(silu(m Wgate_e) * (m Wup_e)) * scale_m + shift_m)
        for e among the experts HELD (first_expert .. first_expert + held
        - 1); a token whose expert is held elsewhere gets shift_m alone

then ``logits = norm_f(h) E^T`` (the head IS the embedding) and the loss is
the mean softmax cross-entropy over all positions. No mask at a document's
boundary.

So that it fits on one chip beside ``follow.py``'s 24 bytes a parameter, each
attention sub-layer, each expert and the rest of each layer run under
``jax.checkpoint``, attention is taken over blocks of queries and the head
over blocks of tokens, each block recomputed in the backward pass: the same
arithmetic in the same order per row.

Layouts: embedding (vocabulary, width); dense (out, in); convolutions (out,
taps, in / groups), the data (B, S, channels); stacked experts (held, out,
in). ``rounding`` rounds every tensor the program keeps in its compute type
(the weights as the step casts them, every activation) and nothing inside
the router, which the program computes in float32 from those inputs.
"""
import jax
import jax.numpy as jnp

from chipbench import flops
from chipbench.rounding import fake_quant

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
QUERY_BLOCK = 512     # 2 x 8 x 512 x 8,192 float32 scores: 268 MB
TOKEN_BLOCK = 1024    # 1,024 x 32,784 float32 logits: 134 MB


def _leaf_counts(cfg):
    """(leaves of a layer's attention sub-layer: norm_a .. shift_a, leaves of
    the first layer; a later layer has its router's mix besides)."""
    kv = cfg["num_key_value_heads"]
    return 11 + kv, 22 + kv


def _sizes(cfg):
    return (cfg["vocab_held"], cfg["hidden_size"], cfg["moe_intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["router_hidden_size"])


def _experts(cfg):
    """(experts the router chooses among, experts held here, the first)."""
    return (cfg["published"]["num_experts"], cfg["num_experts"],
            cfg["deployment"]["first_expert"])


def leaf_specs(cfg):
    """(kind, shape, trainable) of every leaf in the order of the block's
    ``collect_params()``: a block's own parameters, then its children as
    made. The embedding once, though the lookup and the head both read it."""
    v, w, f, heads, kv, hd, r = _sizes(cfg)
    routed, held, _first = _experts(cfg)
    latent = (heads + kv) * hd
    t0, t1 = cfg["cca_time0"], cfg["cca_time1"]
    out = [("embed", (v, w), True)]
    for l in range(cfg["num_hidden_layers"]):
        out += [("norm", (w,), True), ("ones", (kv,), True),
                ("dense", (heads * hd, w), True), ("dense", (kv * hd, w), True)]
        out += [("dense", (hd, w), True)] * kv
        out += [("conv", (latent, t0, 1), True), ("bias", (latent,), True),
                ("conv", (latent, t1, hd), True), ("bias", (latent,), True),
                ("dense", (w, heads * hd), True),
                ("ones", (w,), True), ("bias", (w,), True),
                ("norm", (w,), True),
                ("dense", (r, w), True), ("bias", (r,), True)]
        out += [("bias", (r,), True)] if l else []
        out += [("dense", (r, r), True), ("dense", (r, r), True),
                ("dense", (routed, r), True),
                ("dense", (held, f, w), True), ("dense", (held, f, w), True),
                ("dense", (held, w, f), True),
                ("ones", (w,), True), ("bias", (w,), True)]
    return out + [("norm", (w,), True)]


def init(cfg, key):
    """All leaves from one key: normal(0, 0.02) matrices and convolution
    taps, unit gains, scales and temperatures, zero biases, shifts and router
    mixes. One call, jit it."""
    leaves = []
    for i, (kind, shape, _t) in enumerate(leaf_specs(cfg)):
        if kind in ("norm", "ones"):
            leaves.append(jnp.ones(shape, F32))
        elif kind == "bias":
            leaves.append(jnp.zeros(shape, F32))
        else:
            leaves.append(F32(0.02) * jax.random.normal(
                jax.random.fold_in(key, i), shape, F32))
    return leaves


def train_flops_per_item(cfg):
    """FLOPs one TOKEN requires of a training step on this chip's share: the
    experts it does not hold and the vocabulary rows it does not hold are
    not computed and not counted; nothing recomputed is."""
    _v, w, f, heads, kv, hd, r = _sizes(cfg)
    routed, held, _first = _experts(cfg)
    latent = (heads + kv) * hd
    attention = (flops.dense_macs(w, latent) + flops.dense_macs(w, kv * hd)
                 + flops.dense_macs(heads * hd, w)
                 + latent * cfg["cca_time0"] + latent * hd * cfg["cca_time1"]
                 + flops.causal_attention_macs(cfg["seq_len"], heads, hd, hd))
    router = (flops.dense_macs(w, r) + 2 * flops.dense_macs(r, r)
              + flops.dense_macs(r, routed))
    experts = flops.expert_layer_macs(
        3 * flops.dense_macs(w, f), cfg["num_experts_per_tok"], held / routed)
    return flops.train_flops(
        cfg["num_hidden_layers"] * (attention + router + experts)
        + flops.head_macs(w, cfg["vocab_held"]))


# ------------------------------------------------------------------ layers
def _mm(a, w, q):
    return q(jnp.matmul(a, q(w).T, precision=HIGHEST))


def _rms(z, eps, q, gain=None):
    out = z * jax.lax.rsqrt(jnp.mean(jnp.square(z), axis=-1, keepdims=True)
                            + F32(eps))
    return q(out if gain is None else out * q(gain))


def _rope(t, theta, rot):
    """(B, H, S, D): the first ``rot`` channels turned by position,
    half-rotation form; the others as they are."""
    s, half = t.shape[-2], rot // 2
    inv_freq = F32(theta) ** (-jnp.arange(half, dtype=F32) * F32(2.0 / rot))
    angle = jnp.arange(s, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    t1, t2 = t[..., :half], t[..., half:rot]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin,
                            t[..., rot:]], axis=-1)


def _causal_conv(u, w, b, groups, q):
    """(B, S, C) along S with taps (out, k, in / groups), left padding
    k - 1: position t sees t - k + 1 .. t. Written out tap by tap: tap j
    multiplies the row shifted k - 1 - j positions late, a group at a time
    (no convolution primitive: a product per tap is as plain as it gets)."""
    out_c, k, per = w.shape
    bsz, s, _c = u.shape
    wq = q(w).reshape(groups, out_c // groups, k, per)
    acc = jnp.zeros((bsz, s, groups, out_c // groups), F32)
    for j in range(k):
        late = k - 1 - j
        shifted = jnp.pad(u, ((0, 0), (late, 0), (0, 0)))[:, :s]
        acc = acc + jnp.einsum("bsgi,goi->bsgo",
                               shifted.reshape(bsz, s, groups, per),
                               wq[:, :, j], precision=HIGHEST)
    return q(acc.reshape(bsz, s, out_c) + q(b))


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


def _attention_sublayer(cfg, q, h, leaves):
    _v, _w, _f, heads, kv, hd, _r = _sizes(cfg)
    norm_a, temp, wq, wk = leaves[:4]
    wvs = leaves[4:4 + kv]
    c1w, c1b, c2w, c2b, wo, scale, shift = leaves[4 + kv:]
    eps, b, s, g = cfg["rms_norm_eps"], h.shape[0], h.shape[1], heads // kv
    a = _rms(h, eps, q, norm_a)
    q0, k0 = _mm(a, wq, q), _mm(a, wk, q)
    vs = []
    for i, wv in enumerate(wvs):
        v = _mm(a, wv, q)                                   # (B, S, D)
        vs.append(jnp.pad(v, ((0, 0), (i, 0), (0, 0)))[:, :s])
    latent = (heads + kv) * hd
    mixed = _causal_conv(jnp.concatenate([q0, k0], axis=-1), c1w, c1b,
                         latent, q)
    mixed = _causal_conv(mixed, c2w, c2b, heads + kv, q)
    qc = mixed[..., :heads * hd].reshape(b, s, kv, g, hd)
    kc = mixed[..., heads * hd:].reshape(b, s, kv, 1, hd)
    q0 = q0.reshape(b, s, kv, g, hd)
    k0 = k0.reshape(b, s, kv, 1, hd)
    qq = q(qc + q(q(q0 + k0) * F32(0.5)))
    kk = q(kc + q(q(k0 + q(jnp.mean(q0, axis=3, keepdims=True))) * F32(0.5)))
    qq = _rms(qq, eps, q)
    kk = q(_rms(kk, eps, q) * q(temp).reshape(1, 1, kv, 1, 1))
    rope = cfg["rope_parameters"]["hybrid"]
    rot = int(round(rope["partial_rotary_factor"] * hd))
    turn = lambda t: q(_rope(  # noqa: E731
        t.reshape(b, s, heads, hd).transpose(0, 2, 1, 3), rope["rope_theta"],
        rot))
    kh = turn(jnp.broadcast_to(kk, (b, s, kv, g, hd)))
    vh = jnp.broadcast_to(jnp.stack(vs, axis=2)[:, :, :, None, :],
                          (b, s, kv, g, hd))
    vh = vh.reshape(b, s, heads, hd).transpose(0, 2, 1, 3)
    o = q(_attention(turn(qq), kh, vh))
    o = o.transpose(0, 2, 1, 3).reshape(b, s, heads * hd)
    return q(h + q(q(_mm(o, wo, q) * q(scale)) + q(shift)))


def _router(cfg, q, m, prev, leaves):
    """(expert (B, S) int32, gate (B, S), state (B, S, R)), float32 at
    ``highest`` from the rounded token and the rounded weights; nothing
    inside is rounded."""
    wd, bd = leaves[0], leaves[1]
    mix = leaves[2] if prev is not None else None
    w1, w2, w3 = leaves[-3:]
    mm = lambda a, w: jnp.matmul(a, q(w).T, precision=HIGHEST)  # noqa: E731
    state = mm(m, wd) + q(bd)
    if prev is not None:
        state = state + q(mix) * prev
    hidden = jax.nn.gelu(mm(jax.nn.gelu(mm(state, w1), approximate=False), w2),
                         approximate=False)
    scores = jax.nn.softmax(mm(hidden, w3), axis=-1)
    expert = jnp.argmax(scores, axis=-1).astype(jnp.int32)
    gate = jnp.take_along_axis(scores, expert[..., None], axis=-1)[..., 0]
    return expert, gate, state


def _expert_sublayer(cfg, q, h, prev, leaves):
    _routed, held, first = _experts(cfg)
    norm_m = leaves[0]
    wg, wu, wd, scale, shift = leaves[-5:]
    m = _rms(h, cfg["rms_norm_eps"], q, norm_m)
    expert, gate, state = _router(cfg, q, m, prev, leaves[1:-5])
    gate_ = gate[..., None]

    @jax.checkpoint
    def one(m, gate_, wg_e, wu_e, wd_e, mask):
        g, u = _mm(m, wg_e, q), _mm(m, wu_e, q)
        o = _mm(q(g * jax.nn.sigmoid(g) * u), wd_e, q)
        return jnp.where(mask[..., None], q(o * gate_), F32(0.0))

    out = jnp.zeros_like(h)
    for e in range(held):
        out = out + one(m, gate_, wg[e], wu[e], wd[e], expert == first + e)
    return q(h + q(q(out * q(scale)) + q(shift))), state, expert


def _layers(cfg, leaves, x, q):
    """(the final normed states (B, S, W), [each layer's chosen experts])."""
    n = cfg["num_hidden_layers"]
    attention = jax.checkpoint(
        lambda h, lv: _attention_sublayer(cfg, q, h, lv))
    first = jax.checkpoint(lambda h, lv: _expert_sublayer(cfg, q, h, None, lv))
    later = jax.checkpoint(
        lambda h, prev, lv: _expert_sublayer(cfg, q, h, prev, lv))
    h = jnp.take(q(leaves[0]), x.astype(jnp.int32), axis=0)
    of_attention, of_layer = _leaf_counts(cfg)
    at, state, routes = 1, None, []
    for l in range(n):
        count = of_layer + (1 if l else 0)
        lv = leaves[at:at + count]
        at += count
        h = attention(h, lv[:of_attention])
        if l:
            h, state, expert = later(h, state, lv[of_attention:])
        else:
            h, state, expert = first(h, lv[of_attention:])
        routes.append(expert)
    return _rms(h, cfg["rms_norm_eps"], q, leaves[at]), routes


def logits(cfg, leaves, x, rounding=None):
    """(B, S, V) whole: a test's view, at sizes where the logits fit."""
    q = lambda t: fake_quant(t, rounding)  # noqa: E731
    return _mm(_layers(cfg, leaves, x, q)[0], leaves[0], q)


def routes(cfg, leaves, x, rounding=None):
    """[(B, S) int32 a layer]: the expert every token chose."""
    q = lambda t: fake_quant(t, rounding)  # noqa: E731
    return _layers(cfg, leaves, x, q)[1]


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
    """(mean cross-entropy over all positions, []): the net has no
    non-trainable leaf. ``rounding`` keeps every tensor the program keeps in
    its compute type in that format instead. ``rows`` plants the fault "part
    of the batch left out, the mean taken over the rest": ``x[rows]``,
    except that a slice which would leave the batch empty leaves out the
    second half of every row's positions instead."""
    if rows is not None:
        if len(range(*rows.indices(x.shape[0]))):
            x, labels = x[rows], labels[rows]
        else:
            half = x.shape[1] // 2
            x, labels = x[:, :half], labels[:, :half]
    q = lambda t: fake_quant(t, rounding)  # noqa: E731
    h, _routes = _layers(cfg, leaves, x, q)
    ce = _cross_entropy(h.reshape(-1, h.shape[-1]), leaves[0],
                        labels.astype(jnp.int32).reshape(-1), q)
    return jnp.mean(ce), []
