"""Transformer blocks — the long-context model family (SURVEY.md §5.7).

The reference predates transformers in its model zoo (its long-sequence
story is BucketingModule + fused RNN); this module is the build-new part:
attention blocks whose hot path is the Pallas flash-attention kernel
(``ops/pallas_kernels.py``), hybridizable to ONE XLA program per shape, and
whose sequence dimension shards over a mesh via ``parallel.ring_attention``
/ ``parallel.ulysses`` for contexts longer than one chip's HBM.

Layers:
- ``MultiHeadAttention`` — fused qkv projection, rotary positions on request
  (``rotary_theta=``), flash attention (``F._contrib_flash_attention``),
  output projection.
- ``RMSNorm``, ``GatedFFN`` — the root-mean-square norm (statistic in
  float32) and the gated-SiLU feed-forward of current decoder LMs.
- ``SandwichDecoderCell`` — a causal decoder layer with a norm on each
  sub-layer's input AND output; every call of it is a segment
  (``AttrScope(force_mirroring=)``) of which the lowering keeps the input
  and the products that do not widen, and recomputes the rest in the
  backward pass.
- ``LoopedDecoderLM`` / ``looped_decoder_lm`` — embedding, ONE stack of such
  layers run ``loops`` times over the same parameters, a final norm, an exit
  gate and an untied head; it hands ``gluon.loss.ExpectedExitCELoss`` every
  pass's normed state, every gate and the head's weight.
- ``CompressedLatentAttention``, ``RouterMLP``, ``RoutedExperts``,
  ``ZayaDecoderCell``, ``ZayaDecoderLM`` / ``zaya_decoder_lm`` — a decoder LM
  of top-1 routed experts of which this holder has a share
  (``F._contrib_moe_experts``: dropless, a grouped product), chosen by a
  router MLP that hands its state to the next layer's, with attention in a
  compressed latent (grouped key/value heads, two causal convolutions over
  queries and keys, a shifted value head, partial rotary) and a TIED head:
  it hands ``gluon.loss.TiedHeadCELoss`` its final normed states and the
  embedding's weight.
- ``TransformerEncoderCell`` / ``TransformerDecoderCell`` (causal) —
  pre-norm residual blocks (pre-norm trains stably at depth without warmup
  gymnastics; the post-norm original is available via ``pre_norm=False``).
- ``TransformerEncoder`` — a stack.
- ``SinusoidalPositionalEmbedding`` — the classic fixed encoding.
- ``TransformerLM`` — embeddings + causal stack + tied-or-not output head:
  a GPT-style language model usable with ``DataParallelTrainer``.
"""
from __future__ import annotations

import numpy as np

from ...attribute import AttrScope
from ...base import MXNetError
from ..block import Block, HybridBlock
from ..nn import (Conv1D, Dense, Dropout, Embedding, LayerNorm,
                  HybridSequential)

__all__ = ["MultiHeadAttention", "TransformerEncoderCell",
           "TransformerDecoderCell", "TransformerEncoder",
           "SinusoidalPositionalEmbedding", "TransformerLM", "RMSNorm",
           "GatedFFN", "SandwichDecoderCell", "LoopedDecoderLM",
           "looped_decoder_lm", "CompressedLatentAttention", "RouterMLP",
           "RoutedExperts", "ZayaDecoderCell", "ZayaDecoderLM",
           "zaya_decoder_lm"]


class MultiHeadAttention(HybridBlock):
    """Self-attention with the flash kernel on the hot path.

    Input/output layout (B, T, C); internally (B, H, T, D) for the kernel.
    The fused weight's rows are the queries' heads, then the keys', then the
    values'. ``rotary_theta`` turns queries and keys by their position in
    the row (``F._contrib_rotary_embedding``, half-rotation form).
    """

    def __init__(self, units, num_heads, dropout=0.0, causal=False,
                 use_bias=True, rotary_theta=None, in_units=0, **kw):
        super().__init__(**kw)
        if units % num_heads:
            raise MXNetError(f"units {units} not divisible by heads "
                             f"{num_heads}")
        self._units = units
        self._heads = num_heads
        self._causal = causal
        self._theta = rotary_theta
        with self.name_scope():
            self.qkv = Dense(3 * units, flatten=False, use_bias=use_bias,
                             in_units=in_units, prefix="qkv_")
            self.proj = Dense(units, flatten=False, use_bias=use_bias,
                              in_units=units if in_units else 0,
                              prefix="proj_")
            self.drop = Dropout(dropout)

    def hybrid_forward(self, F, x):
        h, d = self._heads, self._units // self._heads
        qkv = self.qkv(x)                                   # (B, T, 3C)
        qkv = F.reshape(qkv, shape=(0, 0, 3 * h, d))        # (B, T, 3H, D)
        qkv = F.transpose(qkv, axes=(0, 2, 1, 3))           # (B, 3H, T, D)
        q = F.slice_axis(qkv, axis=1, begin=0, end=h)
        k = F.slice_axis(qkv, axis=1, begin=h, end=2 * h)
        v = F.slice_axis(qkv, axis=1, begin=2 * h, end=3 * h)
        if self._theta is not None:
            q = F.contrib_rotary_embedding(q, theta=self._theta)
            k = F.contrib_rotary_embedding(k, theta=self._theta)
        out = F.contrib_flash_attention(q, k, v, causal=self._causal)
        out = F.transpose(out, axes=(0, 2, 1, 3))           # (B, T, H, D)
        out = F.reshape(out, shape=(0, 0, -1))              # (B, T, C)
        return self.drop(self.proj(out))


class _FFN(HybridBlock):
    def __init__(self, units, hidden, dropout, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.fc1 = Dense(hidden, flatten=False, activation="relu",
                             prefix="fc1_")
            self.fc2 = Dense(units, flatten=False, prefix="fc2_")
            self.drop = Dropout(dropout)

    def hybrid_forward(self, F, x):
        return self.drop(self.fc2(self.fc1(x)))


class TransformerEncoderCell(HybridBlock):
    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 pre_norm=True, causal=False, **kw):
        super().__init__(**kw)
        self._pre = pre_norm
        with self.name_scope():
            self.attn = MultiHeadAttention(units, num_heads, dropout,
                                           causal=causal, prefix="attn_")
            self.ffn = _FFN(units, hidden_size, dropout, prefix="ffn_")
            self.ln1 = LayerNorm(prefix="ln1_")
            self.ln2 = LayerNorm(prefix="ln2_")

    def hybrid_forward(self, F, x):
        if self._pre:
            x = x + self.attn(self.ln1(x))
            return x + self.ffn(self.ln2(x))
        x = self.ln1(x + self.attn(x))
        return self.ln2(x + self.ffn(x))


class TransformerDecoderCell(TransformerEncoderCell):
    """Causal (masked) self-attention block — GPT-style decoder cell."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 pre_norm=True, **kw):
        super().__init__(units, hidden_size, num_heads, dropout=dropout,
                         pre_norm=pre_norm, causal=True, **kw)


class TransformerEncoder(HybridBlock):
    def __init__(self, num_layers, units, hidden_size, num_heads,
                 dropout=0.0, pre_norm=True, causal=False, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.layers = HybridSequential(prefix="layers_")
            with self.layers.name_scope():
                for _ in range(num_layers):
                    self.layers.add(TransformerEncoderCell(
                        units, hidden_size, num_heads, dropout,
                        pre_norm=pre_norm, causal=causal))
            self.final_ln = LayerNorm(prefix="lnf_") if pre_norm else None

    def hybrid_forward(self, F, x):
        x = self.layers(x)
        return self.final_ln(x) if self.final_ln is not None else x


class SinusoidalPositionalEmbedding(HybridBlock):
    """Fixed sin/cos table, registered as a Constant (no gradient); sliced
    to the input's length with ``slice_like`` so one table serves every
    bucket length."""

    def __init__(self, max_len, units, **kw):
        super().__init__(**kw)
        pos = np.arange(max_len)[:, None]
        dim = np.arange(0, units, 2)[None, :]
        angle = pos / np.power(10000.0, dim / units)
        table = np.zeros((max_len, units), "float32")
        table[:, 0::2] = np.sin(angle)
        table[:, 1::2] = np.cos(angle[:, : units // 2])
        with self.name_scope():
            self.table = self.params.get_constant("pos_table", table)

    def hybrid_forward(self, F, x, table):
        # x: (B, T, C); table (max_len, C) -> (T, C) -> broadcast over B
        tab = F.slice_like(F.expand_dims(table, axis=0), x, axes=(1,))
        return F.broadcast_add(x, tab)


class TransformerLM(Block):
    """GPT-style causal language model.

    forward(tokens (B, T) int) -> logits (B, T, vocab).
    """

    def __init__(self, vocab_size, units=256, num_layers=4, num_heads=8,
                 hidden_size=None, max_len=1024, dropout=0.0,
                 tie_weights=False, **kw):
        super().__init__(**kw)
        hidden_size = hidden_size or 4 * units
        self._tie = tie_weights
        with self.name_scope():
            self.embed = Embedding(vocab_size, units, prefix="embed_")
            self.pos = SinusoidalPositionalEmbedding(max_len, units)
            self.body = TransformerEncoder(num_layers, units, hidden_size,
                                           num_heads, dropout, pre_norm=True,
                                           causal=True, prefix="body_")
            if not tie_weights:   # tied head reuses the embedding table
                self.head = Dense(vocab_size, flatten=False, use_bias=False,
                                  prefix="head_")

    def forward(self, tokens):
        x = self.pos(self.embed(tokens))
        x = self.body(x)
        if self._tie:
            from ... import nd as _nd
            w = self.embed.weight.data()
            return _nd.dot(x.reshape((-1, x.shape[-1])), w,
                           transpose_b=True).reshape(
                               (x.shape[0], x.shape[1], -1))
        return self.head(x)


class RMSNorm(HybridBlock):
    """``x / sqrt(mean(x**2) + epsilon) * gamma`` over ``axis``; the
    statistic is taken in float32 whatever the compute type (``F.RMSNorm``)."""

    def __init__(self, axis=-1, epsilon=1e-6, gamma_initializer="ones",
                 in_channels=0, **kw):
        super().__init__(**kw)
        self._axis = axis
        self._eps = epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(in_channels,),
                                         init=gamma_initializer,
                                         allow_deferred_init=True)

    def hybrid_forward(self, F, x, gamma):
        return F.RMSNorm(x, gamma, axis=self._axis, eps=self._eps)


class GatedFFN(HybridBlock):
    """``down(silu(gate(x)) * up(x))``, no bias."""

    def __init__(self, units, hidden_size, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.gate = Dense(hidden_size, flatten=False, use_bias=False,
                              in_units=units, prefix="gate_")
            self.up = Dense(hidden_size, flatten=False, use_bias=False,
                            in_units=units, prefix="up_")
            self.down = Dense(units, flatten=False, use_bias=False,
                              in_units=hidden_size, prefix="down_")

    def hybrid_forward(self, F, x):
        a = self.gate(x)
        return self.down(a * F.sigmoid(a) * self.up(x))


class SandwichDecoderCell(HybridBlock):
    """Causal decoder layer with rotary attention, a gated-SiLU FFN and an
    RMSNorm on each sub-layer's input and output::

        x = x + norm2(attn(norm1(x)));  x = x + norm4(ffn(norm3(x)))

    Every CALL of the cell is traced under its own
    ``AttrScope(force_mirroring=<name>)``: the lowering keeps the call's
    input, the products that do not widen their operand (the attention's
    output projection, the FFN's down projection) and the attention's
    output and log-sum-exp, and recomputes the rest in the backward pass:
    the four norms, rotary, the head split, the SiLU gate, the residual
    adds, and the widening qkv, gate and up projections
    (docs/architecture.md). A block that calls one cell several times (a
    looped stack) gets one segment a call."""

    def __init__(self, units, hidden_size, num_heads, rotary_theta=10000.0,
                 epsilon=1e-6, **kw):
        super().__init__(**kw)
        self._calls = 0
        with self.name_scope():
            self.norm1 = RMSNorm(epsilon=epsilon, in_channels=units,
                                 prefix="norm1_")
            self.attn = MultiHeadAttention(
                units, num_heads, causal=True, use_bias=False,
                rotary_theta=rotary_theta, in_units=units, prefix="attn_")
            self.norm2 = RMSNorm(epsilon=epsilon, in_channels=units,
                                 prefix="norm2_")
            self.norm3 = RMSNorm(epsilon=epsilon, in_channels=units,
                                 prefix="norm3_")
            self.ffn = GatedFFN(units, hidden_size, prefix="ffn_")
            self.norm4 = RMSNorm(epsilon=epsilon, in_channels=units,
                                 prefix="norm4_")

    def hybrid_forward(self, F, x):
        self._calls += 1
        with AttrScope(force_mirroring=f"{self.prefix}call{self._calls}"):
            x = x + self.norm2(self.attn(self.norm1(x)))
            return x + self.norm4(self.ffn(self.norm3(x)))


class LoopedDecoderLM(HybridBlock):
    """A decoder LM whose one stack of ``num_layers`` layers runs ``loops``
    times over the same parameters. After each pass the final norm gives
    that pass's exit state, which is also the next pass's input; a gate
    ``sigmoid(state . w + b)`` per token says how likely the model stops
    there.

    ``forward(ids (B, S) int)`` -> ``(states (loops, B, S, units), gates
    (loops, B, S), head_weight (vocab, units))``: what
    ``gluon.loss.ExpectedExitCELoss`` takes before the label, so that the
    head's product is made inside the loss, an exit at a time, and one
    exit's logits live at a time. The layer-calls (``SandwichDecoderCell``)
    are the block's only recomputed segments: ``loops`` x ``num_layers``;
    the exits are none. ``exit_logits(ids)`` gives the logits themselves.
    """

    def __init__(self, vocab_size, units, hidden_size, num_layers, num_heads,
                 loops=1, rotary_theta=10000.0, epsilon=1e-6, **kw):
        super().__init__(**kw)
        self._loops = loops
        with self.name_scope():
            self.embed = Embedding(vocab_size, units, prefix="embed_")
            self.layers = []
            for i in range(num_layers):
                cell = SandwichDecoderCell(
                    units, hidden_size, num_heads, rotary_theta=rotary_theta,
                    epsilon=epsilon, prefix=f"layer{i}_")
                self.register_child(cell, f"layer{i}")
                self.layers.append(cell)
            self.norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                prefix="norm_")
            self.gate = Dense(1, flatten=False, in_units=units, prefix="gate_")
            self.head_weight = self.params.get(
                "head_weight", shape=(vocab_size, units))

    def hybrid_forward(self, F, ids, head_weight):
        h = self.embed(ids)
        states, gates = [], []
        for _ in range(self._loops):
            for cell in self.layers:
                h = cell(h)
            h = self.norm(h)
            states.append(h)
            gates.append(F.sigmoid(F.squeeze(self.gate(h), axis=2)))
        return (F.stack(*states, axis=0), F.stack(*gates, axis=0),
                head_weight)

    def exit_logits(self, ids):
        """(loops, B, S, vocab): every exit's logits, op by op."""
        from ... import nd
        states, _gates, head_weight = self(ids)
        return nd.dot(states, head_weight, transpose_b=True)


def looped_decoder_lm(vocab_size, units, hidden_size, num_layers, num_heads,
                      loops=1, rotary_theta=10000.0, epsilon=1e-6, **kw):
    """A ``LoopedDecoderLM`` from its sizes (a configuration's builder)."""
    return LoopedDecoderLM(vocab_size, units, hidden_size, num_layers,
                           num_heads, loops=loops, rotary_theta=rotary_theta,
                           epsilon=epsilon, **kw)


class CompressedLatentAttention(HybridBlock):
    """Causal self-attention in a compressed latent with convolution mixing
    (arXiv:2510.04476): ``num_heads`` query heads and ``num_kv_heads``
    key/value heads of ``head_dim``, so the latent is ``num_heads *
    head_dim`` wide for the queries and ``num_kv_heads * head_dim`` for keys
    and values, whatever ``units`` is. For a row ``x`` (B, T, units)::

        q0, k0 = x Wq, x Wk
        v = [x Wv_h ; shift(x Wv_h)]  a key/value head sees the token, the
                                      next one the token before it, ...
        [qc ; kc] = conv2(conv1([q0 ; k0]))   along T, both causal: conv1
                    depthwise, conv2 grouped by head, kernels ``conv_kernels``
        q = qc + (q0 + k0[its key head]) / 2
        k = kc + (k0 + mean of its query heads' q0) / 2
        q, k to a root mean square of 1 per head; k times a learned
        temperature per key/value head; rotary on the first
        ``rotary_dim`` channels of every head
        out = causal softmax(q k^T / sqrt(head_dim)) v  Wo

    No bias on the projections, one on each convolution. The attention op
    (``F._contrib_flash_attention``) sees ``num_heads`` key/value heads: each
    is repeated for the query heads it serves."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 conv_kernels=(2, 2), rotary_theta=10000.0, rotary_dim=None,
                 epsilon=1e-6, **kw):
        super().__init__(**kw)
        if num_heads % num_kv_heads:
            raise MXNetError(f"{num_heads} query heads do not divide over "
                             f"{num_kv_heads} key/value heads")
        self._heads, self._kv, self._dim = num_heads, num_kv_heads, head_dim
        self._theta, self._rot, self._eps = rotary_theta, rotary_dim, epsilon
        self._taps = tuple(conv_kernels)
        latent = (num_heads + num_kv_heads) * head_dim
        with self.name_scope():
            self.query = Dense(num_heads * head_dim, flatten=False,
                               use_bias=False, in_units=units, prefix="query_")
            self.key = Dense(num_kv_heads * head_dim, flatten=False,
                             use_bias=False, in_units=units, prefix="key_")
            self.values = []
            for i in range(num_kv_heads):
                proj = Dense(head_dim, flatten=False, use_bias=False,
                             in_units=units, prefix=f"value{i}_")
                self.register_child(proj, f"value{i}")
                self.values.append(proj)
            self.conv1 = Conv1D(latent, self._taps[0], groups=latent,
                                padding=self._taps[0] - 1, layout="NWC",
                                in_channels=latent, prefix="conv1_")
            self.conv2 = Conv1D(latent, self._taps[1],
                                groups=num_heads + num_kv_heads,
                                padding=self._taps[1] - 1, layout="NWC",
                                in_channels=latent, prefix="conv2_")
            self.temperature = self.params.get(
                "temperature", shape=(num_kv_heads,), init="ones")
            self.proj = Dense(units, flatten=False, use_bias=False,
                              in_units=num_heads * head_dim, prefix="proj_")

    @staticmethod
    def _causal(F, conv, x, taps):
        """A convolution padded on both sides, less the positions that look
        ahead: position t sees t - taps + 1 .. t."""
        y = conv(x)
        return y if taps == 1 else F.slice_axis(y, axis=1, begin=0,
                                                end=1 - taps)

    def hybrid_forward(self, F, x, temperature):
        h, kv, d = self._heads, self._kv, self._dim
        q0, k0 = self.query(x), self.key(x)                 # (B, T, H D)
        vs = []
        for i, proj in enumerate(self.values):
            v = proj(x)                                     # (B, T, D)
            if i:   # shift(x) W = shift(x W): zero rows in front, the end cut
                v = F.slice_axis(F.pad(
                    F.expand_dims(v, axis=0), mode="constant",
                    pad_width=(0, 0, 0, 0, i, 0, 0, 0)), axis=2, begin=0,
                    end=-i)
                v = F.squeeze(v, axis=0)
            vs.append(F.expand_dims(v, axis=2))             # (B, T, 1, D)
        v = F.concat(*vs, dim=2) if kv > 1 else vs[0]       # (B, T, KV, D)
        mixed = self._causal(F, self.conv2, self._causal(
            F, self.conv1, F.concat(q0, k0, dim=2), self._taps[0]),
            self._taps[1])
        qc = F.reshape(F.slice_axis(mixed, axis=2, begin=0, end=h * d),
                       shape=(0, 0, kv, h // kv, d))
        kc = F.reshape(F.slice_axis(mixed, axis=2, begin=h * d, end=None),
                       shape=(0, 0, kv, 1, d))
        q0 = F.reshape(q0, shape=(0, 0, kv, h // kv, d))
        k0 = F.reshape(k0, shape=(0, 0, kv, 1, d))
        q = qc + F.broadcast_add(q0, k0) * 0.5
        k = kc + (k0 + F.mean(q0, axis=3, keepdims=True)) * 0.5
        q = F.RMSNorm(q, axis=-1, eps=self._eps, no_gain=True)
        k = F.broadcast_mul(
            F.RMSNorm(k, axis=-1, eps=self._eps, no_gain=True),
            F.reshape(temperature, shape=(1, 1, kv, 1, 1)))
        # (B, T, KV, G, D) -> (B, H, T, D); a key/value head once per query
        # head of its group
        q = F.transpose(F.reshape(q, shape=(0, 0, h, d)), axes=(0, 2, 1, 3))
        k = F.transpose(F.reshape(F.broadcast_axis(
            k, axis=3, size=h // kv), shape=(0, 0, h, d)), axes=(0, 2, 1, 3))
        v = F.transpose(F.reshape(F.broadcast_axis(
            F.expand_dims(v, axis=3), axis=3, size=h // kv),
            shape=(0, 0, h, d)), axes=(0, 2, 1, 3))
        rot = dict(theta=self._theta, rotary_dim=self._rot)
        out = F.contrib_flash_attention(
            F.contrib_rotary_embedding(q, **rot),
            F.contrib_rotary_embedding(k, **rot), v, causal=True)
        out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)), shape=(0, 0, -1))
        return self.proj(out)


class RouterMLP(HybridBlock):
    """The router of a layer of top-1 routed experts (arXiv:2511.17127): a
    projection of the token to ``hidden`` channels, mixed with the layer
    before's router state by a learned vector where there is one
    (``mixes=True``), a three-layer gelu MLP to ``num_experts`` scores, their
    softmax and its top-1. ``forward(x[, state])`` -> (expert (B, T) int32,
    gate (B, T) float32, state (B, T, hidden) float32); float32 throughout
    (``F._contrib_moe_router``)."""

    def __init__(self, units, hidden, num_experts, mixes=False, **kw):
        super().__init__(**kw)
        with self.name_scope():
            get = self.params.get
            self.down_weight = get("down_weight", shape=(hidden, units))
            self.down_bias = get("down_bias", shape=(hidden,), init="zeros")
            if mixes:
                self.mix = get("mix", shape=(hidden,), init="zeros")
            self.w1 = get("w1_weight", shape=(hidden, hidden))
            self.w2 = get("w2_weight", shape=(hidden, hidden))
            self.w3 = get("w3_weight", shape=(num_experts, hidden))

    def hybrid_forward(self, F, x, state=None, *, down_weight, down_bias, w1,
                       w2, w3, mix=None):
        more = () if state is None else (state, mix)
        expert, gate, state = F.contrib_moe_router(
            x, down_weight, down_bias, w1, w2, w3, *more)
        return expert, gate, state


class RoutedExperts(HybridBlock):
    """The gated-SiLU experts ``first_expert .. first_expert + experts_held -
    1`` of a layer of ``num_experts``, their weights stacked:
    ``forward(x, expert, gate)`` is this holder's part of the layer's result
    (``F._contrib_moe_experts``), 0 for a token routed elsewhere."""

    def __init__(self, units, hidden_size, num_experts, experts_held=None,
                 first_expert=0, **kw):
        super().__init__(**kw)
        held = num_experts if experts_held is None else experts_held
        if not 0 <= first_expert <= num_experts - held:
            raise MXNetError(f"experts {first_expert}..{first_expert + held - 1} "
                             f"are not among {num_experts}")
        self._first, self._experts = first_expert, num_experts
        with self.name_scope():
            get = self.params.get
            self.gate_weight = get("gate_weight",
                                   shape=(held, hidden_size, units))
            self.up_weight = get("up_weight", shape=(held, hidden_size, units))
            self.down_weight = get("down_weight",
                                   shape=(held, units, hidden_size))

    def hybrid_forward(self, F, x, expert, gate, gate_weight, up_weight,
                       down_weight):
        return F.contrib_moe_experts(
            x, expert, gate, gate_weight, up_weight, down_weight,
            first_expert=self._first, num_experts=self._experts)


class _Join(HybridBlock):
    """``y * scale + shift``, learned per channel: what a sub-layer's output
    passes on its way into the residual."""

    def __init__(self, units, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.scale = self.params.get("scale", shape=(units,), init="ones")
            self.shift = self.params.get("shift", shape=(units,), init="zeros")

    def hybrid_forward(self, F, y, scale, shift):
        return F.broadcast_add(F.broadcast_mul(y, scale), shift)


class ZayaDecoderCell(HybridBlock):
    """One decoder layer of attention in a compressed latent and top-1
    routed experts, each behind an RMSNorm and joined to the residual through
    a learned scale and shift::

        h = h + join_a(attn(norm_a(h)))
        expert, gate, state = router(norm_m(h)[, the layer before's state])
        h = h + join_m(experts(norm_m(h), expert, gate))

    ``forward(h[, state])`` -> (h, state). Every CALL is one recomputed
    segment (``AttrScope(force_mirroring=)``, as ``SandwichDecoderCell``)."""

    def __init__(self, units, hidden_size, num_heads, num_kv_heads, head_dim,
                 num_experts, router_hidden, experts_held=None,
                 first_expert=0, mixes=False, conv_kernels=(2, 2),
                 rotary_theta=10000.0, rotary_dim=None, epsilon=1e-5, **kw):
        super().__init__(**kw)
        self._calls = 0
        with self.name_scope():
            self.norm_a = RMSNorm(epsilon=epsilon, in_channels=units,
                                  prefix="norm_a_")
            self.attn = CompressedLatentAttention(
                units, num_heads, num_kv_heads, head_dim,
                conv_kernels=conv_kernels, rotary_theta=rotary_theta,
                rotary_dim=rotary_dim, epsilon=epsilon, prefix="attn_")
            self.join_a = _Join(units, prefix="join_a_")
            self.norm_m = RMSNorm(epsilon=epsilon, in_channels=units,
                                  prefix="norm_m_")
            self.router = RouterMLP(units, router_hidden, num_experts,
                                    mixes=mixes, prefix="router_")
            self.experts = RoutedExperts(
                units, hidden_size, num_experts, experts_held=experts_held,
                first_expert=first_expert, prefix="experts_")
            self.join_m = _Join(units, prefix="join_m_")

    def hybrid_forward(self, F, h, state=None):
        self._calls += 1
        with AttrScope(force_mirroring=f"{self.prefix}call{self._calls}"):
            h = h + self.join_a(self.attn(self.norm_a(h)))
            x = self.norm_m(h)
            more = () if state is None else (state,)
            expert, gate, state = self.router(x, *more)
            return h + self.join_m(self.experts(x, expert, gate)), state


class ZayaDecoderLM(HybridBlock):
    """A decoder LM of ``ZayaDecoderCell`` layers with a TIED head: the
    embedding is one parameter, read by the lookup and by the loss.
    ``forward(ids (B, S) int)`` -> (final normed states (B, S, units), the
    embedding's weight (vocab, units)): what ``gluon.loss.TiedHeadCELoss``
    takes before the label, so that the head's product runs inside the loss,
    straight into the fused cross-entropy. The first layer's router has no
    state to mix in; every later one mixes in the layer before's.
    ``logits(ids)`` gives the logits themselves."""

    def __init__(self, vocab_size, units, hidden_size, num_layers, num_heads,
                 num_kv_heads, head_dim, num_experts, router_hidden,
                 experts_held=None, first_expert=0, conv_kernels=(2, 2),
                 rotary_theta=10000.0, rotary_dim=None, epsilon=1e-5, **kw):
        super().__init__(**kw)
        self._vocab, self._units = vocab_size, units
        with self.name_scope():
            self.embed_weight = self.params.get(
                "embed_weight", shape=(vocab_size, units))
            self.layers = []
            for i in range(num_layers):
                cell = ZayaDecoderCell(
                    units, hidden_size, num_heads, num_kv_heads, head_dim,
                    num_experts, router_hidden, experts_held=experts_held,
                    first_expert=first_expert, mixes=i > 0,
                    conv_kernels=conv_kernels, rotary_theta=rotary_theta,
                    rotary_dim=rotary_dim, epsilon=epsilon,
                    prefix=f"layer{i}_")
                self.register_child(cell, f"layer{i}")
                self.layers.append(cell)
            self.norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                prefix="norm_")

    def hybrid_forward(self, F, ids, embed_weight):
        h = F.Embedding(ids, embed_weight, input_dim=self._vocab,
                        output_dim=self._units)
        state = None
        for cell in self.layers:
            h, state = cell(h) if state is None else cell(h, state)
        return self.norm(h), embed_weight

    def logits(self, ids):
        """(B, S, vocab), op by op."""
        from ... import nd
        states, weight = self(ids)
        return nd.dot(states, weight, transpose_b=True)


def zaya_decoder_lm(vocab_size, units, hidden_size, num_layers, num_heads,
                    num_kv_heads, head_dim, num_experts, router_hidden,
                    experts_held=None, first_expert=0, conv_kernels=(2, 2),
                    rotary_theta=10000.0, rotary_dim=None, epsilon=1e-5, **kw):
    """A ``ZayaDecoderLM`` from its sizes (a configuration's builder)."""
    return ZayaDecoderLM(
        vocab_size, units, hidden_size, num_layers, num_heads, num_kv_heads,
        head_dim, num_experts, router_hidden, experts_held=experts_held,
        first_expert=first_expert, conv_kernels=conv_kernels,
        rotary_theta=rotary_theta, rotary_dim=rotary_dim, epsilon=epsilon,
        **kw)
