"""Int8 quantization.

Reference parity: ``src/operator/quantization/`` (quantize/dequantize/
requantize, quantized conv/FC, calibration pass
``quantize_graph_pass.cc``) + the driver ``python/mxnet/contrib/quantization.py``.

TPU-first: int8 matmuls feed the MXU natively; quantize/dequantize are
elementwise XLA ops that fuse with their neighbors, so no dedicated
"quantized_conv" kernels are needed — a quantized graph is the float graph
with (quantize → int8 op → dequantize) islands that XLA fuses. Calibration
(entropy/minmax thresholds) runs on host over captured activations.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

import jax.numpy as jnp

from ..base import MXNetError
from ..ndarray import NDArray
from ..ndarray.ndarray import _unwrap, _wrap
# every int8 op — the codec AND quantized_fully_connected — is registered
# at package import time in ops/quantize_ops.py / ops/parity_ops.py, so
# quantized graphs bind (simple_bind included) without importing contrib;
# the re-exports below keep the historical contrib surface working
from ..ops.quantize_ops import (_dequantize, _quantize,  # noqa: F401
                                _quantized_fc, _requantize)


def calib_minmax(activations: np.ndarray):
    return float(np.min(activations)), float(np.max(activations))


def calib_entropy(activations: np.ndarray, num_bins: int = 8001,
                  num_quantized_bins: int = 255,
                  min_percentile: float = None):
    """KL-divergence threshold search (reference quantization.py
    _get_optimal_threshold).

    ``min_percentile`` (default None = pure reference behavior) floors the
    KL-optimal threshold at that percentile of |x|; pass e.g. 99.0 to stop
    a noisy KL search from clipping below the bulk of the distribution.
    This floor is a divergence from the reference when enabled — calibrated
    ranges will differ from reference-calibrated models."""
    arr = np.abs(activations.ravel())
    amax = float(arr.max()) if arr.size else 1.0
    if amax == 0:
        return -1.0, 1.0
    if arr.size < 4 * num_quantized_bins:
        # too few samples for a meaningful KL histogram search (the
        # reference calibrates over full epochs); min/max is strictly
        # better than a noise-driven threshold here
        return -amax, amax
    hist, edges = np.histogram(arr, bins=num_bins, range=(0, amax))
    best_kl, best_t = np.inf, amax
    for i in range(num_quantized_bins, num_bins + 1, num_bins // 64 or 1):
        t = edges[i] if i < len(edges) else amax
        p = hist[:i].astype(np.float64).copy()
        p[-1] += hist[i:].sum()
        if p.sum() == 0:
            continue
        # quantize p into num_quantized_bins then expand back
        factor = len(p) / num_quantized_bins
        q = np.zeros_like(p)
        for j in range(num_quantized_bins):
            lo, hi = int(j * factor), int((j + 1) * factor) or 1
            chunk = p[lo:hi]
            nz = (chunk > 0).sum()
            if nz:
                q[lo:hi] = np.where(chunk > 0, chunk.sum() / nz, 0)
        p /= p.sum()
        qs = q.sum()
        if qs == 0:
            continue
        q /= qs
        mask = p > 0
        kl = float(np.sum(p[mask] * np.log(p[mask] / np.maximum(q[mask], 1e-12))))
        if kl < best_kl:
            best_kl, best_t = kl, t
    if min_percentile is not None:
        best_t = max(best_t, float(np.percentile(arr, min_percentile)))
    return -best_t, best_t


def quantize_params(params: Dict[str, NDArray]):
    """Quantize a parameter dict to int8 + ranges."""
    out = {}
    for name, arr in params.items():
        a = arr.asnumpy()
        amax = float(np.abs(a).max()) or 1.0
        q = np.clip(np.round(a * (127.0 / amax)), -127, 127).astype(np.int8)
        from .. import ndarray as nd
        out[name + "_quantized"] = nd.array(q, dtype="int8")
        out[name + "_min"] = nd.array(np.float32(-amax))
        out[name + "_max"] = nd.array(np.float32(amax))
    return out


def quantize_graph(sym, arg_params, excluded_sym_names=(),
                   calib_ranges=None):
    """The int8 graph pass (reference quantize_graph_pass.cc): rewrite every
    FullyConnected/Convolution node into a quantize -> int8 op -> dequantize
    island. Weights/biases become int8 parameter variables (``*_quantized``
    with ``*_min``/``*_max`` ranges); activations quantize at runtime from
    observed min/max, or from calibrated ranges when ``calib_ranges`` maps a
    node name to (min, max).

    Returns (new_symbol, extra_arg_params) — merge extras into arg_params.
    """
    from .. import ndarray as nd_mod
    from ..symbol.symbol import Symbol, _Node

    calib_ranges = calib_ranges or {}
    excluded = set(excluded_sym_names)
    extra: Dict[str, "object"] = {}
    remap: Dict[int, _Node] = {}

    q_var_cache: Dict[str, tuple] = {}

    def q_param_vars(pname):
        """int8 weight/bias variables backed by quantized params; shared
        params (tied layers) quantize once and reuse the same var nodes."""
        if pname in q_var_cache:
            return q_var_cache[pname]
        # one source of truth for the int8 math: quantize_params
        extra.update(quantize_params({pname: arg_params[pname]}))
        nodes = (_Node(None, pname + "_quantized", {}, []),
                 _Node(None, pname + "_min", {}, []),
                 _Node(None, pname + "_max", {}, []))
        q_var_cache[pname] = nodes
        return nodes

    def new_entry(entry):
        src, idx = entry
        return (remap[id(src)], idx)

    for node in sym.topo_nodes():
        if node.is_var:
            remap[id(node)] = node
            continue
        inputs = [new_entry(e) for e in node.inputs]
        _no_bias = str(node.attrs.get("no_bias", False)).lower() in ("true",
                                                                     "1")
        # same bias discipline as quant.qpass: a node WITH a bias must
        # have it as a param var — never silently zero a computed bias
        bias_quantizable = _no_bias or (
            len(node.inputs) >= 3 and node.inputs[2][0].is_var
            and node.inputs[2][0].name in arg_params)
        quantizable = (node.op in ("FullyConnected", "Convolution")
                       and node.name not in excluded
                       and len(node.inputs) >= 2
                       and node.inputs[1][0].is_var
                       and node.inputs[1][0].name in arg_params
                       and bias_quantizable)
        if not quantizable:
            nn = _Node(node.op, node.name, dict(node.attrs), inputs)
            remap[id(node)] = nn
            continue

        data_e = inputs[0]
        wname = node.inputs[1][0].name
        wq, wmin, wmax = q_param_vars(wname)

        # activation ranges: calibrated constants, else runtime min/max
        if node.name in calib_ranges:
            mn_v, mx_v = calib_ranges[node.name]
            extra[node.name + "_data_min"] = nd_mod.array(np.float32(mn_v))
            extra[node.name + "_data_max"] = nd_mod.array(np.float32(mx_v))
            mn_e = (_Node(None, node.name + "_data_min", {}, []), 0)
            mx_e = (_Node(None, node.name + "_data_max", {}, []), 0)
        else:
            mn_e = (_Node("min", node.name + "_rt_min", {}, [data_e]), 0)
            mx_e = (_Node("max", node.name + "_rt_max", {}, [data_e]), 0)
        qd = _Node("_contrib_quantize", node.name + "_quantize", {},
                   [data_e, mn_e, mx_e])

        no_bias = str(node.attrs.get("no_bias", False)).lower() in ("true",
                                                                    "1")
        if not no_bias and len(node.inputs) >= 3 \
                and node.inputs[2][0].is_var \
                and node.inputs[2][0].name in arg_params:
            bname = node.inputs[2][0].name
        else:
            # the int8 ops take bias positionally: synthesize zeros
            bname = node.name + "_zero_bias"
            out_ch = int(node.attrs.get("num_hidden",
                                        node.attrs.get("num_filter", 1)))
            arg_params = dict(arg_params)
            arg_params[bname] = nd_mod.zeros((out_ch,))
        bq, bmin, bmax = q_param_vars(bname)

        qop = ("_contrib_quantized_fully_connected"
               if node.op == "FullyConnected" else "_contrib_quantized_conv")
        attrs = dict(node.attrs)
        attrs["no_bias"] = False
        # positional order: data, weight, bias, min_data, max_data,
        # min_weight, max_weight, min_bias, max_bias
        qn = _Node(qop, node.name + "_int8", attrs,
                   [(qd, 0), (wq, 0), (bq, 0), (qd, 1), (qd, 2),
                    (wmin, 0), (wmax, 0), (bmin, 0), (bmax, 0)])
        # int32 accumulator -> int8 (requantize) -> float (dequantize),
        # the reference island shape (quantize_graph_pass.cc)
        rq = _Node("_contrib_requantize", node.name + "_requantize", {},
                   [(qn, 0), (qn, 1), (qn, 2)])
        deq = _Node("_contrib_dequantize", node.name + "_dequantize", {},
                    [(rq, 0), (rq, 1), (rq, 2)])
        remap[id(node)] = deq

    new_sym = Symbol([(remap[id(n)], i) for (n, i) in sym._outputs])
    return new_sym, extra


def _collect_calib_ranges(sym, arg_params, aux_params, data_names,
                          calib_data, num_calib_examples, mode,
                          min_percentile=None):
    """Run the FLOAT graph over calibration batches, recording each
    quantizable node's input range (reference calibration pass)."""
    import mxnet_tpu as mx
    from ..symbol.symbol import Symbol

    targets = {}
    for node in sym.topo_nodes():
        if node.op in ("FullyConnected", "Convolution"):
            targets[node.name] = node.inputs[0]
    if not targets:
        return {}
    probe = Symbol(list(targets.values()))
    names = list(targets)
    # streaming stats: 'naive' keeps a running min/max; 'entropy' keeps a
    # bounded subsample per layer — never the full activation history
    # (a real conv net's activations would be tens of GB otherwise)
    minmax = {n: (np.inf, -np.inf) for n in names}
    samples = {n: [] for n in names}
    cap = 1 << 20         # per-layer element budget for the entropy search
    kept = {n: 0 for n in names}
    seen = 0
    exe = None
    rs = np.random.RandomState(0)
    for batch in calib_data:
        datas = batch.data if hasattr(batch, "data") else [batch]
        if exe is None:   # bind ONCE: the executor's jit cache is
            feed = {dn: d for dn, d in zip(data_names, datas)}
            for k, v in arg_params.items():
                feed.setdefault(k, v)
            exe = probe.bind(mx.cpu(), feed,
                             aux_states=dict(aux_params) or None)
            outs = exe.forward()
        else:             # per-instance; later batches reuse the program
            outs = exe.forward(**{dn: d for dn, d in zip(data_names, datas)})
        for n, o in zip(names, outs):
            a = np.asarray(o.asnumpy()).ravel()
            lo, hi = minmax[n]
            minmax[n] = (min(lo, float(a.min())), max(hi, float(a.max())))
            if mode == "entropy" and kept[n] < cap:
                take = min(cap - kept[n], a.size)
                # with-replacement sampling: O(take), statistically
                # equivalent for the KL histogram
                sel = a if take == a.size else a[rs.randint(0, a.size, take)]
                samples[n].append(sel)
                kept[n] += take
        seen += datas[0].shape[0]
        if num_calib_examples and seen >= num_calib_examples:
            break
    ranges = {}
    for n in names:
        if mode == "entropy":
            ranges[n] = calib_entropy(np.concatenate(samples[n])
                                      if samples[n] else np.zeros(1),
                                      min_percentile=min_percentile)
        else:
            ranges[n] = minmax[n]
    return ranges


def _trace_gluon(net):
    """Capture an initialized gluon net as (symbol, arg_params, aux_params)
    using the same symbol trace hybridize() uses."""
    from .. import symbol as sym_mod

    data = sym_mod.Variable("data")
    out = net(data)
    if isinstance(out, (list, tuple)):
        out = out[0]
    var_names = {n.name for n in out.topo_nodes() if n.is_var}
    arg_params, aux_params = {}, {}
    for p in net.collect_params().values():
        if p.name in var_names and p.name != "data":
            dst = aux_params if p.grad_req == "null" else arg_params
            dst[p.name] = p.data()
    return out, arg_params, aux_params


def quantize_model(sym, arg_params, aux_params, data_names=("data",),
                   excluded_sym_names=(), calib_mode="none", calib_data=None,
                   num_calib_examples=None, quantized_dtype="int8",
                   calib_min_percentile=99.0, **kwargs):
    """Driver with the reference signature
    (contrib/quantization.py:quantize_model): rewrites conv/FC into int8
    islands via :func:`quantize_graph`. calib_mode 'none' quantizes
    activations from runtime min/max; 'naive' (min/max over calib_data) and
    'entropy' (KL threshold) bake calibrated constant ranges in.

    ``calib_min_percentile`` (framework extension, NOT in the reference):
    floors the entropy-calibrated threshold at that percentile of |x| so a
    noisy small-sample KL search cannot clip below the bulk of the
    distribution. Default 99.0; pass None for bit-faithful reference
    calibration (ranges then match reference-calibrated models)."""
    if quantized_dtype not in ("int8", "auto"):
        raise MXNetError(f"unsupported quantized_dtype {quantized_dtype!r}")
    calib_ranges = {}
    if calib_mode in ("naive", "entropy"):
        if calib_data is None:
            raise MXNetError(f"calib_mode={calib_mode!r} requires calib_data")
        calib_ranges = _collect_calib_ranges(
            sym, arg_params, aux_params, data_names, calib_data,
            num_calib_examples, calib_mode,
            min_percentile=calib_min_percentile)
    elif calib_mode != "none":
        raise MXNetError(f"unknown calib_mode {calib_mode!r}")
    qsym, extra = quantize_graph(sym, arg_params,
                                 excluded_sym_names=excluded_sym_names,
                                 calib_ranges=calib_ranges)
    qarg = dict(arg_params)
    qarg.update(extra)
    return qsym, qarg, dict(aux_params)
