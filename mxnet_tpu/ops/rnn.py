"""Fused multi-layer RNN op (LSTM/GRU/vanilla).

Reference parity: ``src/operator/rnn-inl.h`` (822 LoC CPU) /
``cudnn_rnn-inl.h`` (fused cuDNN descriptor path), op registration
``src/operator/rnn.cc``; parameter layout matches the reference's packed
vector: all i2h/h2h weights (layer-major, direction-minor), then all biases.
Gate order LSTM: [i, f, g, o]; GRU: [r, z, n] — as in
``python/mxnet/gluon/rnn/rnn_cell.py``.

TPU-first: the input projection for ALL timesteps is one large MXU matmul
(seq*batch, in)·(in, G*h); only the hidden recurrence runs under ``lax.scan``,
keeping the scan body a single (batch, h)·(h, G*h) matmul + elementwise fusion.
This is the standard XLA RNN recipe and replaces the cuDNN descriptor zoo.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register
from ..base import MXNetError

_GATES = {"lstm": 4, "gru": 3, "rnn_relu": 1, "rnn_tanh": 1}


def _match_vma(state, ref):
    """Inside shard_map, scan carries must carry the same varying-manual-axes
    set as values derived from the inputs; a replicated initial state meeting
    a device-varying input projection (the pipeline-parallel case) needs an
    explicit pvary or the scan type check rejects it."""
    extra = tuple(sorted(jax.typeof(ref).vma - jax.typeof(state).vma))
    if extra:
        return lax.pcast(state, extra, to="varying")
    return state


def _lstm_scan(xp, h0, c0, whh, bhh):
    """xp: (T, B, 4H) precomputed input projection."""
    H = h0.shape[-1]
    h0 = _match_vma(h0, xp)
    c0 = _match_vma(c0, xp)

    def step(carry, xt):
        h, c = carry
        gates = xt + jnp.dot(h, whh.T) + bhh
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    (hn, cn), out = lax.scan(step, (h0, c0), xp)
    return out, hn, cn


def _gru_scan(xp, h0, whh, bhh):
    H = h0.shape[-1]
    h0 = _match_vma(h0, xp)
    whh_rz, whh_n = whh[:2 * H], whh[2 * H:]
    bhh_rz, bhh_n = bhh[:2 * H], bhh[2 * H:]

    def step(h, xt):
        xt_rz, xt_n = xt[..., :2 * H], xt[..., 2 * H:]
        rz = jax.nn.sigmoid(xt_rz + jnp.dot(h, whh_rz.T) + bhh_rz)
        r, z = jnp.split(rz, 2, axis=-1)
        n = jnp.tanh(xt_n + r * (jnp.dot(h, whh_n.T) + bhh_n))
        h = (1 - z) * n + z * h
        return h, h

    hn, out = lax.scan(step, h0, xp)
    return out, hn


def _vanilla_scan(xp, h0, whh, bhh, act):
    h0 = _match_vma(h0, xp)

    def step(h, xt):
        h = act(xt + jnp.dot(h, whh.T) + bhh)
        return h, h

    hn, out = lax.scan(step, h0, xp)
    return out, hn


def _unpack_params(params, num_layers, dirs, input_size, H, G):
    """Split the packed parameter vector (reference rnn-inl.h layout)."""
    ws, bs = [], []
    off = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else H * dirs
        for d in range(dirs):
            wih = params[off:off + G * H * in_sz].reshape(G * H, in_sz)
            off += G * H * in_sz
            whh = params[off:off + G * H * H].reshape(G * H, H)
            off += G * H * H
            ws.append((wih, whh))
    for layer in range(num_layers):
        for d in range(dirs):
            bih = params[off:off + G * H]
            off += G * H
            bhh = params[off:off + G * H]
            off += G * H
            bs.append((bih, bhh))
    return ws, bs


def rnn_packed_param_size(mode, num_layers, bidirectional, input_size, H):
    G = _GATES[mode]
    dirs = 2 if bidirectional else 1
    n = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else H * dirs
        n += dirs * (G * H * in_sz + G * H * H)
    n += num_layers * dirs * 2 * G * H
    return n


def _run_layer(x, mode, wih, whh, bih, bhh, h0, c0, reverse=False):
    if reverse:
        x = jnp.flip(x, axis=0)
    T, B = x.shape[0], x.shape[1]
    # a (1, H) initial state stands for "unknown batch" (legacy begin_state);
    # broadcast it up front so the scan carry has a fixed (B, H) shape
    if h0.shape[0] == 1 and B != 1:
        h0 = jnp.broadcast_to(h0, (B, h0.shape[1]))
    if c0 is not None and c0.shape[0] == 1 and B != 1:
        c0 = jnp.broadcast_to(c0, (B, c0.shape[1]))
    xp = jnp.dot(x.reshape(T * B, -1), wih.T).reshape(T, B, -1) + bih
    if mode == "lstm":
        out, hn, cn = _lstm_scan(xp, h0, c0, whh, bhh)
    elif mode == "gru":
        out, hn = _gru_scan(xp, h0, whh, bhh)
        cn = None
    else:
        act = jax.nn.relu if mode == "rnn_relu" else jnp.tanh
        out, hn = _vanilla_scan(xp, h0, whh, bhh, act)
        cn = None
    if reverse:
        out = jnp.flip(out, axis=0)
    return out, hn, cn


def _rnn_nout(attrs):
    if not attrs.get("state_outputs", False):
        return 1
    return 3 if attrs.get("mode", "lstm") == "lstm" else 2


@register("RNN", num_outputs=_rnn_nout,
          arg_names=("data", "parameters", "state", "state_cell"),
          needs_rng=True)
def _rnn(data, parameters, state, state_cell=None, state_size=0, num_layers=1,
         mode="lstm", bidirectional=False, p=0.0, state_outputs=False,
         projection_size=None, use_sequence_length=False, sequence_length=None,
         lstm_state_clip_min=None, lstm_state_clip_max=None,
         lstm_state_clip_nan=False, rng=None, is_train=True):
    """data: (T, B, I); state: (L*dirs, B, H); packed params as reference."""
    if mode not in _GATES:
        raise MXNetError(f"bad RNN mode {mode}")
    G = _GATES[mode]
    H = int(state_size)
    L = int(num_layers)
    dirs = 2 if bidirectional else 1
    I = data.shape[2]
    ws, bs = _unpack_params(parameters, L, dirs, I, H, G)

    x = data
    hn_all, cn_all = [], []
    k = rng if rng is not None else jax.random.PRNGKey(0)
    for layer in range(L):
        outs = []
        for d in range(dirs):
            idx = layer * dirs + d
            wih, whh = ws[idx]
            bih, bhh = bs[idx]
            h0 = state[idx]
            c0 = state_cell[idx] if state_cell is not None else None
            out, hn, cn = _run_layer(x, mode, wih, whh, bih, bhh, h0, c0,
                                     reverse=(d == 1))
            outs.append(out)
            hn_all.append(hn)
            if cn is not None:
                cn_all.append(cn)
        x = outs[0] if dirs == 1 else jnp.concatenate(outs, axis=-1)
        if p > 0.0 and is_train and layer < L - 1:
            k, sub = jax.random.split(k)
            keep = 1.0 - p
            x = x * jax.random.bernoulli(sub, keep, x.shape).astype(x.dtype) / keep

    if not state_outputs:
        return x
    hn = jnp.stack(hn_all, axis=0)
    if mode == "lstm":
        cn = jnp.stack(cn_all, axis=0)
        if lstm_state_clip_min is not None:
            cn = jnp.clip(cn, lstm_state_clip_min, lstm_state_clip_max)
        return x, hn, cn
    return x, hn
