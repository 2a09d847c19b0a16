"""Executor — lowers a Symbol graph to one compiled XLA computation.

Reference parity: ``include/mxnet/executor.h`` ``Executor::{Bind,SimpleBind,
Forward,Backward,Reshape}`` over ``src/executor/graph_executor.cc``. The
reference's pass pipeline (Gradient :232, PlanMemory :637, AttachOpExecs :647,
InitCachedOps :1072, bulking :1186) is replaced wholesale: the whole graph
becomes a single jitted jax function (XLA does fusion, scheduling and buffer
assignment), and the gradient graph is ``jax.vjp`` of that function — both
passes execute as compiled XLA programs with async dispatch.

Shape inference (``infer_graph_attr_pass.cc:325``) runs via ``jax.eval_shape``
plus per-op parameter-shape rules (the "backward inference" MXNet does for
weight shapes, e.g. FullyConnected weight = (num_hidden, input_dim)).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from .base import MXNetError
from .ops.registry import KEPT_IN_SEGMENT, get_op
from ._imperative import _op_signature_flags
from . import random as _random

__all__ = ["Executor", "PipelinedExecutor", "_GraphLowering"]


# Per-op parameter shape rules: op -> fn(attrs, data_shape) -> {param: shape}.
# This is the TPU equivalent of each op's FInferShape filling in unknown
# weight shapes from the data shape (fully_connected.cc:47-93 etc.).
def _fc_param_shapes(attrs, ds):
    nh = int(attrs["num_hidden"])
    flat = int(np.prod(ds[1:])) if attrs.get("flatten", True) else ds[-1]
    shapes = {"weight": (nh, flat)}
    if not attrs.get("no_bias", False):
        shapes["bias"] = (nh,)
    return shapes


def _conv_param_shapes(attrs, ds):
    nf = int(attrs["num_filter"])
    g = int(attrs.get("num_group", 1))
    kernel = tuple(attrs["kernel"])
    layout = str(attrs.get("layout") or "")
    if layout.endswith("C"):  # channel-last (NHWC): weight is (O, *k, I)
        shapes = {"weight": (nf,) + kernel + (ds[-1] // g,)}
    else:
        shapes = {"weight": (nf, ds[1] // g) + kernel}
    if not attrs.get("no_bias", False):
        shapes["bias"] = (nf,)
    return shapes


def _deconv_param_shapes(attrs, ds):
    nf = int(attrs["num_filter"])
    g = int(attrs.get("num_group", 1))
    kernel = tuple(attrs["kernel"])
    shapes = {"weight": (ds[1], nf // g) + kernel}
    if not attrs.get("no_bias", True):
        shapes["bias"] = (nf,)
    return shapes


def _bn_param_shapes(attrs, ds):
    ax = int(attrs.get("axis", 1)) % len(ds)
    c = ds[ax]
    return {"gamma": (c,), "beta": (c,), "moving_mean": (c,), "moving_var": (c,)}


def _ln_param_shapes(attrs, ds):
    ax = int(attrs.get("axis", -1)) % len(ds)
    return {"gamma": (ds[ax],), "beta": (ds[ax],)}


def _in_param_shapes(attrs, ds):
    return {"gamma": (ds[1],), "beta": (ds[1],)}


def _emb_param_shapes(attrs, ds):
    return {"weight": (int(attrs["input_dim"]), int(attrs["output_dim"]))}


def _prelu_param_shapes(attrs, ds):
    if attrs.get("act_type", "leaky") == "prelu":
        return {"gamma": (ds[1] if len(ds) > 1 else 1,)}
    return {}


def _rnn_param_shapes(attrs, ds):
    # ds is (T, B, I); packed parameter layout per ops/rnn.py (reference
    # rnn-inl.h); state vars are (L*dirs, B, H)
    from .ops.rnn import rnn_packed_param_size
    mode = attrs.get("mode", "lstm")
    H = int(attrs["state_size"])
    L = int(attrs.get("num_layers", 1))
    bi = str(attrs.get("bidirectional", False)) in ("True", "true", "1")
    dirs = 2 if bi else 1
    n = rnn_packed_param_size(mode, L, bi, int(ds[2]), H)
    state = (L * dirs, int(ds[1]), H)
    return {"parameters": (n,), "state": state, "state_cell": state}


def _quantized_fc_param_shapes(attrs, ds):
    # weight/bias shapes match the float op; the range args are scalars —
    # what lets a quantized graph (mxnet_tpu.quant) go through simple_bind
    # exactly like its float twin (reference quantized_fully_connected.cc
    # FInferShape fills the min/max triple the same way)
    s = _fc_param_shapes(dict(attrs, no_bias=False), ds)
    s.update({k: () for k in ("min_data", "max_data", "min_weight",
                              "max_weight", "min_bias", "max_bias")})
    return s


def _quantized_conv_param_shapes(attrs, ds):
    s = _conv_param_shapes(dict(attrs, no_bias=False), ds)
    s.update({k: () for k in ("min_data", "max_data", "min_weight",
                              "max_weight", "min_bias", "max_bias")})
    return s


def _quantize_param_shapes(attrs, ds):
    return {"min_range": (), "max_range": ()}


_PARAM_SHAPE_RULES: Dict[str, Callable] = {
    "FullyConnected": _fc_param_shapes,
    "Convolution": _conv_param_shapes,
    "_contrib_quantized_fully_connected": _quantized_fc_param_shapes,
    "_contrib_quantized_conv": _quantized_conv_param_shapes,
    "_contrib_quantize": _quantize_param_shapes,
    "Deconvolution": _deconv_param_shapes,
    "BatchNorm": _bn_param_shapes,
    "_MaxPoolBatchNorm": _bn_param_shapes,
    "LayerNorm": _ln_param_shapes,
    "InstanceNorm": _in_param_shapes,
    "Embedding": _emb_param_shapes,
    "LeakyReLU": _prelu_param_shapes,
    "RNN": _rnn_param_shapes,
}

# Ops whose extra outputs update auxiliary state during training:
# op -> fn(attrs, in_arrays, out_tuple) -> {input_index: new_value}
def _bn_aux_update(attrs, ins, outs):
    mom = float(attrs.get("momentum", 0.9))
    _, mean, var = outs
    new_mean = ins[3] * mom + mean * (1.0 - mom)
    new_var = ins[4] * mom + var * (1.0 - mom)
    return {3: jax.lax.stop_gradient(new_mean), 4: jax.lax.stop_gradient(new_var)}


_AUX_UPDATE_RULES: Dict[str, Callable] = {
    "BatchNorm": _bn_aux_update, "_MaxPoolBatchNorm": _bn_aux_update}


def _mirror_segments(nodes):
    """Runs of op nodes, consecutive in topological order (variables apart),
    that carry the same ``force_mirroring`` attribute: [[node index, ...]].
    ``mx.AttrScope(force_mirroring=<name>)`` around a part of a graph is how
    a block asks for that part to be recomputed in the backward pass; nodes
    of one scope that other nodes separate make a segment per run."""
    runs, name = [], None
    for i, node in enumerate(nodes):
        if node.is_var:
            continue
        mine = node._attr_dict.get("force_mirroring")
        mine = mine if mine not in (None, "", "0", "False", "false") else None
        if mine is not None and mine == name:
            runs[-1].append(i)
        elif mine is not None:
            runs.append([i])
        name = mine
    return runs


class _GraphLowering:
    """Lowers a Symbol DAG to a pure jax function
    ``fn(inputs: dict, rng) -> (outputs: list, aux_updates: dict)``.

    Nodes traced under one ``AttrScope(force_mirroring=<name>)`` lower as ONE
    function under ``jax.checkpoint`` whose inputs are the values that enter
    the segment. The backward pass keeps those and the result of every
    product inside the segment that is no larger than the product's first
    operand (an op registered with ``product=True``: ``FullyConnected``,
    ``Convolution``, ``dot``, the attention op and its kernel's residuals;
    in a decoder layer the output projection, the FFN's down projection and
    the attention) and recomputes the rest: norms, activations, reshapes,
    residual adds, and the products that widen (a fused qkv projection, an
    FFN's up projections: as many bytes to keep as three layer inputs each).
    The reference's own mirror rule keeps every product (``need_mirror`` in
    ``src/executor/graph_executor.cc`` never mirrors ``Convolution`` or
    ``FullyConnected``); kept whole it does not fit the chip at a decoder
    LM's widths (PERF.md, PR 32). The attribute is a segment's name here,
    not the reference's per-node override of that rule. A graph without the
    attribute lowers node by node, as it always has."""

    def __init__(self, symbol):
        self.symbol = symbol
        self.nodes = symbol.topo_nodes()
        self.var_names = [n.name for n in self.nodes if n.is_var]
        self.has_rng = any(
            n.op is not None and get_op(n.op).needs_rng for n in self.nodes)
        self.segments = _mirror_segments(self.nodes)

    def _plan(self):
        """The op nodes in order, a segment standing where its first node
        stood: [node index | (node indices, entries read from outside,
        entries read outside)]; an entry is (id(node), output index)."""
        nodes = self.nodes
        seg_of = {id(nodes[i]): k for k, run in enumerate(self.segments)
                  for i in run}
        read_outside = {(id(n), idx) for (n, idx) in self.symbol._outputs}
        for node in nodes:
            for (src, idx) in node.inputs:
                if seg_of.get(id(src), -1) != seg_of.get(id(node), -2):
                    read_outside.add((id(src), idx))
        plan = []
        for i, node in enumerate(nodes):
            if node.is_var:
                continue
            k = seg_of.get(id(node))
            if k is None:
                plan.append(i)
            elif self.segments[k][0] == i:
                run = self.segments[k]
                ext = list(dict.fromkeys(
                    (id(src), idx) for j in run
                    for (src, idx) in nodes[j].inputs
                    if seg_of.get(id(src)) != k))
                outs = [(id(nodes[j]), o) for j in run
                        for o in range(nodes[j].num_outputs)
                        if (id(nodes[j]), o) in read_outside]
                plan.append((run, ext, outs))
        return plan

    def lower(self, is_train: bool) -> Callable:
        nodes = self.nodes
        out_entries = self.symbol._outputs
        plan = self._plan()
        keep_products = jax.checkpoint_policies.save_only_these_names(
            KEPT_IN_SEGMENT)

        def run(idxs, vals, rng, aux_updates, kept=None):
            """The nodes ``idxs`` in order, reading and writing ``vals``
            ({(id(node), output index): value}). Inside a segment (``kept``
            is its list) a product that does not widen its first operand
            carries the name the segment's policy keeps, and is listed."""
            for i in idxs:
                node = nodes[i]
                opdef = get_op(node.op)
                in_arrays = [vals[(id(src), idx)] for (src, idx) in node.inputs]
                attrs = dict(node.attrs)
                accepts_train, accepts_rng = _op_signature_flags(opdef)
                if accepts_train and "is_train" not in attrs:
                    attrs["is_train"] = is_train
                if accepts_rng:
                    attrs["rng"] = jax.random.fold_in(rng, i)
                out = opdef.fn(*in_arrays, **attrs)
                out = out if isinstance(out, tuple) else (out,)
                if kept is not None and opdef.product \
                        and out[0].size <= in_arrays[0].size:
                    out = (checkpoint_name(out[0], KEPT_IN_SEGMENT),) + out[1:]
                    kept.append(i)
                vals.update(((id(node), o), v) for o, v in enumerate(out))
                if is_train and node.op in _AUX_UPDATE_RULES:
                    upd = _AUX_UPDATE_RULES[node.op](attrs, in_arrays, out)
                    for in_idx, new_val in upd.items():
                        src, _ = node.inputs[in_idx]
                        if src.is_var:
                            aux_updates[src.name] = new_val

        def segment(idxs, ext, outs, kept):
            def body(ext_vals, rng):
                local, aux_updates = dict(zip(ext, ext_vals)), {}
                run(idxs, local, rng, aux_updates, kept)
                return [local[k] for k in outs], aux_updates
            return jax.checkpoint(body, policy=keep_products)

        def fn(inputs: Dict[str, Any], rng):
            vals: Dict[Tuple[int, int], Any] = {
                (id(n), 0): inputs[n.name] for n in nodes if n.is_var}
            aux_updates: Dict[str, Any] = {}
            kept: List[int] = []
            for unit in plan:
                if isinstance(unit, int):
                    run((unit,), vals, rng, aux_updates)
                    continue
                idxs, ext, outs = unit
                got, upd = segment(idxs, ext, outs, kept)(
                    [vals[k] for k in ext], rng)
                vals.update(zip(outs, got))
                aux_updates.update(upd)
            if self.segments:
                from .observability import catalog, metrics
                if metrics.enabled():
                    catalog.REMAT_SEGMENTS.inc(len(self.segments))
                    catalog.REMAT_KEPT.inc(len(kept))
            return [vals[(id(node), idx)] for (node, idx) in out_entries], \
                aux_updates

        return fn

    @staticmethod
    def _backfill_through_transposes(entry, shape, shapes) -> None:
        """Propagate a rule-derived parameter shape BACKWARD through a
        chain of transpose nodes onto the underlying variable — the graph
        passes (mxnet_tpu.passes) wrap conv weights in layout transposes,
        and ``simple_bind`` must still infer the var's shape."""
        src, _ = entry
        perms = []
        while (not src.is_var and src.op == "transpose" and src.inputs):
            axes = (src.attrs or {}).get("axes")
            if not axes:
                return
            perms.append(tuple(int(a) for a in axes))
            src, _ = src.inputs[0]
        if not src.is_var or src.name in shapes:
            return
        for perm in perms:          # outermost transpose first
            if len(perm) != len(shape):
                return
            inv = [0] * len(perm)
            for i, p in enumerate(perm):
                inv[p] = i
            shape = tuple(shape[i] for i in inv)
        shapes[src.name] = tuple(shape)

    def infer_shapes(self, known: Dict[str, Tuple[int, ...]]):
        """Forward shape inference with parameter-shape backfill."""
        shapes: Dict[str, Tuple[int, ...]] = dict(known)
        dtypes: Dict[str, Any] = {}
        entry_aval: Dict[Tuple[int, int], jax.ShapeDtypeStruct] = {}
        # Fixpoint sweeps: a pass-rewritten graph may interpose transposes
        # between a parameter variable and the op whose rule derives its
        # shape, and topo order visits the transpose BEFORE the rule-owning
        # op — so a node with still-unknown inputs defers to the next sweep
        # (each sweep unlocks at least one more rule-gated stage).  A
        # pristine graph resolves fully in sweep one; when a sweep makes no
        # progress the strict pass below names the first genuinely
        # unresolvable variable.
        op_nodes = [n for n in self.nodes if not n.is_var]
        for _ in range(len(op_nodes) + 1):
            progress = False
            for node in op_nodes:
                if (id(node), 0) in entry_aval:
                    continue
                opdef = get_op(node.op)
                arg_names = opdef.arg_names() or []
                rule = _PARAM_SHAPE_RULES.get(node.op)
                if rule is not None and node.inputs:
                    src0, idx0 = node.inputs[0]
                    ds = (shapes.get(src0.name) if src0.is_var
                          else (tuple(entry_aval[(id(src0), idx0)].shape)
                                if (id(src0), idx0) in entry_aval else None))
                    if ds is not None:
                        try:
                            param_shapes = rule(dict(node.attrs), tuple(ds))
                        except KeyError:
                            param_shapes = {}
                        for i, (src, _) in enumerate(node.inputs):
                            if i < len(arg_names) \
                                    and arg_names[i] in param_shapes:
                                if src.is_var and src.name not in shapes:
                                    shapes[src.name] = \
                                        param_shapes[arg_names[i]]
                                    progress = True
                                elif not src.is_var:
                                    before = len(shapes)
                                    self._backfill_through_transposes(
                                        node.inputs[i],
                                        tuple(param_shapes[arg_names[i]]),
                                        shapes)
                                    progress |= len(shapes) != before
                # build avals for this node's inputs
                in_avals = []
                defer = False
                for (src, idx) in node.inputs:
                    if src.is_var:
                        if src.name not in shapes:
                            defer = True
                            break
                        dt = dtypes.get(src.name, jnp.float32)
                        in_avals.append(
                            jax.ShapeDtypeStruct(shapes[src.name], dt))
                    else:
                        av = entry_aval.get((id(src), idx))
                        if av is None:
                            defer = True
                            break
                        in_avals.append(av)
                if defer:
                    continue
                attrs = dict(node.attrs)
                accepts_train, accepts_rng = _op_signature_flags(opdef)
                if accepts_train and "is_train" not in attrs:
                    attrs["is_train"] = True

                def run(*arrs):
                    kw = dict(attrs)
                    if accepts_rng:
                        kw["rng"] = jax.random.PRNGKey(0)
                    return opdef.fn(*arrs, **kw)

                try:
                    out_avals = jax.eval_shape(run, *in_avals)
                except Exception as e:
                    raise MXNetError(f"shape inference failed at op "
                                     f"{node.op} ({node.name}): {e}") from e
                if not isinstance(out_avals, tuple):
                    out_avals = (out_avals,)
                for i, av in enumerate(out_avals):
                    entry_aval[(id(node), i)] = av
                progress = True
            if not progress:
                break
        # strict pass: name the first unresolved variable/producer
        for node in op_nodes:
            if (id(node), 0) in entry_aval:
                continue
            for (src, _idx) in node.inputs:
                if src.is_var and src.name not in shapes:
                    raise MXNetError(
                        f"shape of variable {src.name!r} cannot be "
                        f"inferred; provide it to infer_shape/simple_bind")
            raise MXNetError(
                f"shape inference failed at op {node.op} ({node.name}): "
                f"inputs unresolved")
        out_shapes = []
        for (node, idx) in self.symbol._outputs:
            if node.is_var:
                out_shapes.append(shapes.get(node.name))
            else:
                out_shapes.append(tuple(entry_aval[(id(node), idx)].shape))
        shapes["__outputs__"] = out_shapes
        return shapes


class Executor:
    """Bound executor: owns arg/grad/aux arrays, forward/backward methods
    (reference GraphExecutor). Forward = one async XLA dispatch; Backward =
    the vjp executable of the same program."""

    def __init__(self, symbol, ctx, args, args_grad=None, grad_req="write",
                 aux_states=None):
        from . import ndarray as nd
        from .ndarray.ndarray import NDArray
        self._symbol = symbol
        self._ctx = ctx
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()

        if isinstance(args, (list, tuple)):
            args = dict(zip(arg_names, args))
        self.arg_dict: Dict[str, NDArray] = dict(args or {})
        if isinstance(args_grad, (list, tuple)):
            args_grad = dict(zip(arg_names, args_grad))
        self.grad_dict: Dict[str, NDArray] = dict(args_grad or {})
        if isinstance(aux_states, (list, tuple)):
            aux_states = dict(zip(aux_names, aux_states))
        self.aux_dict: Dict[str, NDArray] = dict(aux_states or {})

        if isinstance(grad_req, str):
            self.grad_req = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self.grad_req = dict(zip(arg_names, grad_req))
        else:
            self.grad_req = dict(grad_req)

        self._lowering = _GraphLowering(symbol)
        self._jit_cache: Dict[Any, Callable] = {}
        self._pending = None
        self._outputs: List[NDArray] = []
        self.monitor_callback = None

    # ------------------------------------------------------------- helpers
    @property
    def outputs(self) -> List:
        return self._outputs

    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self._symbol.list_arguments()]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self._symbol.list_arguments()]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self._symbol.list_auxiliary_states()]

    #: subclasses set False to run the composed program eagerly (the
    #: placed executor: per-segment programs are jitted individually)
    _jit_outer = True

    def _compiled(self, is_train: bool) -> Callable:
        if is_train not in self._jit_cache:
            raw = self._lowering.lower(is_train)
            self._jit_cache[is_train] = jax.jit(raw) if self._jit_outer \
                else raw
        return self._jit_cache[is_train]

    def _diff_names(self):
        return tuple(n for n in self._symbol.list_arguments()
                     if self.grad_req.get(n, "null") != "null"
                     and n in self.arg_dict)

    def _compiled_train_step(self) -> Callable:
        """ONE jitted XLA computation for forward + default-cotangent backward
        — the whole-graph lowering of SURVEY.md stage 4 (the reference's
        InitCachedOps + bulked segments collapse into this single program).
        Used by forward(is_train=True); backward() then just delivers the
        precomputed grads, so a Module training step is exactly one async
        device dispatch."""
        if "train_step" not in self._jit_cache:
            raw = self._lowering.lower(True)
            diff_names = self._diff_names()
            # MXNET_BACKWARD_DO_MIRROR (graph_executor.cc:232 mirroring):
            # rematerialize the forward during backward instead of keeping
            # every activation — jax.checkpoint is the XLA-native form
            from .base import get_env
            mirror = bool(get_env("MXNET_BACKWARD_DO_MIRROR", False))

            def step(inputs, rng):
                diff = {n: inputs[n] for n in diff_names}
                nondiff = {n: v for n, v in inputs.items()
                           if n not in diff_names}

                def f(d):
                    return raw({**d, **nondiff}, rng)

                if mirror:
                    f = jax.checkpoint(f)
                (outs, aux), vjp_fn = jax.vjp(f, diff)
                cts = [jnp.ones_like(o) for o in outs]
                aux_ct = jax.tree_util.tree_map(jnp.zeros_like, aux)
                (grads,) = vjp_fn((cts, aux_ct))
                return outs, aux, grads

            self._jit_cache["train_step"] = jax.jit(step) \
                if self._jit_outer else step
        return self._jit_cache["train_step"]

    def debug_str(self) -> str:
        """Human-readable lowered program (reference Executor::DebugStr):
        the jaxpr of the inference graph — one line per primitive AFTER
        framework lowering, i.e. what is handed to XLA."""
        from .ndarray.ndarray import _unwrap
        raw = self._lowering.lower(False)
        inputs = {n: _unwrap(a) for n, a in self.arg_dict.items()}
        inputs.update({n: _unwrap(a) for n, a in self.aux_dict.items()})
        jaxpr = jax.make_jaxpr(lambda ins: raw(ins, jax.random.PRNGKey(0)))(
            inputs)
        return str(jaxpr)

    def set_monitor_callback(self, callback, monitor_all=False):
        self.monitor_callback = callback

    def lint(self, suppress=(), passes_applied=None):
        """Static-analyze the bound graph (mxlint graph front end) with the
        exact shapes/dtypes of the bound arrays — what NNVM's validation
        passes would check before InitCachedOps. Returns an
        ``analysis.Report``.  ``passes_applied`` names the graph-pass
        pipeline that produced this graph (Module.lint supplies it) so
        MXL-G107 can flag NCHW convs bound with the layout pass off."""
        from .analysis import lint_symbol
        shapes = {n: tuple(a.shape) for n, a in self.arg_dict.items()}
        shapes.update({n: tuple(a.shape) for n, a in self.aux_dict.items()})
        dtypes = {n: a.dtype for n, a in self.arg_dict.items()}
        dtypes.update({n: a.dtype for n, a in self.aux_dict.items()})
        return lint_symbol(self._symbol, shapes=shapes, dtypes=dtypes,
                           suppress=suppress,
                           passes_applied=passes_applied,
                           subject=f"executor over {self._symbol.name!r}")

    # ------------------------------------------------------------- forward
    def forward(self, is_train: bool = False, **kwargs):
        from .ndarray.ndarray import NDArray, _wrap
        for k, v in kwargs.items():
            if k in self.arg_dict:
                self.arg_dict[k]._set_data(v._data if isinstance(v, NDArray) else
                                           jnp.asarray(v))
            else:
                from .ndarray import array as _arr
                self.arg_dict[k] = v if isinstance(v, NDArray) else _arr(v)
        inputs = {n: a._data for n, a in self.arg_dict.items()}
        inputs.update({n: a._data for n, a in self.aux_dict.items()})
        rng = _random.next_key() if self._lowering.has_rng else jax.random.PRNGKey(0)
        for v in inputs.values():
            if hasattr(v, "devices"):
                rng = jax.device_put(rng, list(v.devices())[0])
                break

        try:
            if is_train:
                outs, aux_updates, grads = self._compiled_train_step()(inputs,
                                                                       rng)
            else:
                outs, _ = self._compiled(False)(inputs, rng)
        except (TypeError, ValueError) as e:
            # graph trace/compile failures (shape mismatches etc.) surface
            # as MXNetError like the reference's bind-time CHECK failures;
            # stale state from a previous successful step must not survive
            # into a later backward()
            self._pending = None
            raise MXNetError(f"graph execution failed: {e}") from e
        except Exception:
            self._pending = None
            raise
        if is_train:
            self._pending = (inputs, rng, outs, grads)
            for name, val in aux_updates.items():
                if name in self.aux_dict:
                    self.aux_dict[name]._set_data(val)
        else:
            self._pending = None
        self._outputs = [_wrap(o) for o in outs]
        if self.monitor_callback is not None:
            for name, o in zip(self._symbol.list_outputs(), self._outputs):
                self.monitor_callback(name, o)
        return self._outputs

    def _compiled_custom_bwd(self) -> Callable:
        """Jitted fwd+bwd with explicit head cotangents (the rare
        backward(out_grads=...) path; recomputes forward inside one program).

        Deliberate cost tradeoff: XLA cannot export a vjp closure across
        program boundaries, so reusing forward's residuals would require
        splitting the default train path into two programs (fwd, then
        fwd+bwd) — slowing the common case ~1.3x to speed this rare one.
        Instead the custom-cotangent path recomputes the forward inside one
        fused program (compiled once, cached); callers looping over custom
        cotangents should pass them via autograd.grad with create_graph
        instead."""
        if "custom_bwd" not in self._jit_cache:
            raw = self._lowering.lower(True)
            diff_names = self._diff_names()

            def step(inputs, rng, cts):
                diff = {n: inputs[n] for n in diff_names}
                nondiff = {n: v for n, v in inputs.items()
                           if n not in diff_names}

                def f(d):
                    return raw({**d, **nondiff}, rng)

                (outs, aux), vjp_fn = jax.vjp(f, diff)
                aux_ct = jax.tree_util.tree_map(jnp.zeros_like, aux)
                (grads,) = vjp_fn((list(cts), aux_ct))
                return grads

            self._jit_cache["custom_bwd"] = jax.jit(step) \
                if self._jit_outer else step
        return self._jit_cache["custom_bwd"]

    # ------------------------------------------------------------- backward
    def backward(self, out_grads=None):
        from .ndarray.ndarray import NDArray
        if self._pending is None:
            raise MXNetError("backward called without forward(is_train=True)")
        inputs, rng, outs, grads = self._pending
        if out_grads is not None:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            cts = tuple(g._data if isinstance(g, NDArray) else jnp.asarray(g)
                        for g in out_grads)
            grads = self._compiled_custom_bwd()(inputs, rng, cts)
        for name, g in grads.items():
            req = self.grad_req.get(name, "null")
            if req == "null" or name not in self.grad_dict:
                continue
            buf = self.grad_dict[name]
            # under group2ctx placement the cotangent may arrive on a
            # different device than the parameter; align the gradient with
            # the ARG array (no-op single-device) so optimizer math
            # (w, g elementwise) and += accumulation stay coherent
            anchor = self.arg_dict.get(name, buf)
            if hasattr(g, "devices") and hasattr(anchor._data, "devices") \
                    and g.devices() != anchor._data.devices():
                g = jax.device_put(g, next(iter(anchor._data.devices())))
            if hasattr(buf._data, "devices") and hasattr(g, "devices") \
                    and req == "add" and buf._data.devices() != g.devices():
                buf._set_data(jax.device_put(buf._data,
                                             next(iter(g.devices()))))
            if req == "add":
                buf._set_data(buf._data + g)
            else:
                buf._set_data(g)
        return [self.grad_dict.get(n) for n in self._symbol.list_arguments()]

    # ------------------------------------------------------------- misc API
    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        from . import ndarray as nd
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        arg_names = self._symbol.list_arguments()
        aux_names = self._symbol.list_auxiliary_states()
        new_args = {}
        new_grads = {}
        for n, s in zip(arg_names, arg_shapes):
            old = self.arg_dict.get(n)
            if old is not None and tuple(old.shape) == tuple(s):
                new_args[n] = old
                if n in self.grad_dict:
                    new_grads[n] = self.grad_dict[n]
            else:
                new_args[n] = nd.zeros(s, ctx=self._ctx)
                if self.grad_req.get(n, "null") != "null":
                    new_grads[n] = nd.zeros(s, ctx=self._ctx)
        new_aux = {n: self.aux_dict.get(n, nd.zeros(s, ctx=self._ctx))
                   for n, s in zip(aux_names, aux_shapes)}
        return self._rebuild(new_args, new_grads, new_aux)

    def _rebuild(self, new_args, new_grads, new_aux):
        """Construct the same-kind executor over new arrays (reshape hook;
        PipelinedExecutor overrides to keep its placement)."""
        return Executor(self._symbol, self._ctx, new_args, new_grads,
                        self.grad_req, new_aux)

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for k, v in (arg_params or {}).items():
            if k in self.arg_dict:
                self.arg_dict[k]._set_data(v._data)
            elif not allow_extra_params:
                raise MXNetError(f"unknown argument {k}")
        for k, v in (aux_params or {}).items():
            if k in self.aux_dict:
                self.aux_dict[k]._set_data(v._data)
            elif not allow_extra_params:
                raise MXNetError(f"unknown aux state {k}")


# --------------------------------------------------------------------------
# Inter-layer model parallelism (group2ctx): placed lowering + executor.
# --------------------------------------------------------------------------

def _assign_devices(symbol, group2ctx, default_ctx):
    """AssignContext (reference common/exec_utils.h:500): map every graph
    node to a concrete jax.Device from its ``ctx_group`` attribute via
    ``group2ctx``; ungrouped op nodes fall to the bind context, ungrouped
    variables co-locate with their first consumer (the reference plans the
    same way to avoid gratuitous copies)."""
    from .context import Context
    nodes = symbol.topo_nodes()
    dev_of_group = {}
    for g, c in (group2ctx or {}).items():
        c = c if isinstance(c, Context) else Context(c)
        dev_of_group[g] = c.jax_device()
    default_dev = default_ctx.jax_device() if default_ctx is not None \
        else jax.devices()[0]
    node_device = {}
    for n in nodes:
        if n.is_var:
            continue
        g = n._attr_dict.get("ctx_group")
        node_device[id(n)] = dev_of_group.get(g, default_dev)
    first_consumer_dev = {}
    for n in nodes:                 # topo order: first consumer wins
        if n.is_var:
            continue
        for (src, _) in n.inputs:
            if src.is_var and id(src) not in first_consumer_dev:
                first_consumer_dev[id(src)] = node_device[id(n)]
    for n in nodes:
        if not n.is_var:
            continue
        g = n._attr_dict.get("ctx_group")
        if g in dev_of_group:
            node_device[id(n)] = dev_of_group[g]
        else:
            node_device[id(n)] = first_consumer_dev.get(id(n), default_dev)
    return node_device


class _PlacedLowering:
    """Device-placed lowering for ``group2ctx`` inter-layer model
    parallelism (reference AssignContext + kCrossDeviceCopy nodes,
    common/exec_utils.h:500, graph_executor.cc:1346).

    Consecutive topo-order nodes on the same device form a SEGMENT; each
    segment lowers to one jitted program whose committed inputs pin it to
    its device, and the host-side transfers between segments are the
    cross-device copies. Pipeline overlap across a stream of calls (e.g.
    microbatches) comes from XLA's per-device async dispatch queues —
    device A starts microbatch k+1 while device B still runs k, which is
    what the reference's DAG engine buys in its model-parallel LSTM case
    (docs/faq/model_parallel_lstm.md)."""

    def __init__(self, symbol, node_device):
        self.symbol = symbol
        self.nodes = symbol.topo_nodes()
        self.var_names = [n.name for n in self.nodes if n.is_var]
        self.has_rng = any(
            n.op is not None and get_op(n.op).needs_rng for n in self.nodes)
        self._gid = {id(n): i for i, n in enumerate(self.nodes)}
        self._node_device = node_device
        segs: List[Tuple[Any, List[int]]] = []
        for i, n in enumerate(self.nodes):
            d = node_device[id(n)]
            if segs and segs[-1][0] == d:
                segs[-1][1].append(i)
            else:
                segs.append((d, [i]))
        self._segments = [(d, tuple(ix)) for d, ix in segs]
        # entries that cross a segment boundary: graph outputs plus any
        # entry whose consumer sits in a different segment (which covers
        # cross-device edges AND same-device segments split by an
        # interleaved group)
        needed: set = set()
        for (node, idx) in symbol._outputs:
            if not node.is_var:
                needed.add((self._gid[id(node)], idx))
        seg_of = {}
        for si, (_, ix) in enumerate(self._segments):
            for i in ix:
                seg_of[i] = si
        for n in self.nodes:
            if n.is_var:
                continue
            for (src, idx) in n.inputs:
                if not src.is_var and \
                        seg_of[self._gid[id(src)]] != seg_of[self._gid[id(n)]]:
                    needed.add((self._gid[id(src)], idx))
        self._boundary = needed
        self._seg_cache: Dict[Any, Tuple] = {}

    # ------------------------------------------------------------ segments
    def _segment_program(self, seg_idx: int, is_train: bool):
        key = (seg_idx, is_train)
        if key in self._seg_cache:
            return self._seg_cache[key]
        _, idxs = self._segments[seg_idx]
        seg_set = set(idxs)
        nodes, gid = self.nodes, self._gid
        # ordered external inputs: var names + boundary entries from
        # other segments
        ext_keys: List[Any] = []
        seen = set()
        for i in idxs:
            n = nodes[i]
            if n.is_var:
                if ("var", n.name) not in seen:
                    seen.add(("var", n.name))
                    ext_keys.append(("var", n.name))
                continue
            for (src, idx) in n.inputs:
                sgid = gid[id(src)]
                if src.is_var:
                    k = ("var", src.name)
                elif sgid not in seg_set:
                    k = (sgid, idx)
                else:
                    continue
                if k not in seen:
                    seen.add(k)
                    ext_keys.append(k)
        out_keys = [k for k in sorted(self._boundary)
                    if k[0] in seg_set and not nodes[k[0]].is_var]

        def seg_raw(ext_vals, rng):
            env = dict(zip(ext_keys, ext_vals))
            local: Dict[Tuple[int, int], Any] = {}
            aux_updates: Dict[str, Any] = {}

            def read(src, idx):
                if src.is_var:
                    return env[("var", src.name)]
                sgid = gid[id(src)]
                if sgid in seg_set:
                    return local[(sgid, idx)]
                return env[(sgid, idx)]

            for i in idxs:
                node = nodes[i]
                if node.is_var:
                    continue
                opdef = get_op(node.op)
                in_arrays = [read(src, idx) for (src, idx) in node.inputs]
                attrs = dict(node.attrs)
                accepts_train, accepts_rng = _op_signature_flags(opdef)
                if accepts_train and "is_train" not in attrs:
                    attrs["is_train"] = is_train
                if accepts_rng:
                    # same stream as _GraphLowering: fold by GLOBAL index
                    attrs["rng"] = jax.random.fold_in(rng, i)
                out = opdef.fn(*in_arrays, **attrs)
                out = out if isinstance(out, tuple) else (out,)
                for oi, o in enumerate(out):
                    local[(i, oi)] = o
                if is_train and node.op in _AUX_UPDATE_RULES:
                    upd = _AUX_UPDATE_RULES[node.op](attrs, in_arrays, out)
                    for in_idx, new_val in upd.items():
                        src, _ = node.inputs[in_idx]
                        if src.is_var:
                            aux_updates[src.name] = new_val
            return [local[k] for k in out_keys], aux_updates

        prog = (jax.jit(seg_raw), ext_keys, out_keys)
        self._seg_cache[key] = prog
        return prog

    # ------------------------------------------------------------- lower
    def lower(self, is_train: bool) -> Callable:
        out_entries = self.symbol._outputs
        gid = self._gid

        def fn(inputs: Dict[str, Any], rng):
            vals: Dict[Tuple[int, int], Any] = {}
            aux_updates: Dict[str, Any] = {}
            for si, (dev, _) in enumerate(self._segments):
                seg_fn, ext_keys, out_keys = self._segment_program(si,
                                                                   is_train)
                ext_vals = []
                for k in ext_keys:
                    v = inputs[k[1]] if k[0] == "var" else vals[k]
                    ext_vals.append(jax.device_put(v, dev))
                outs, aux = seg_fn(ext_vals, jax.device_put(rng, dev))
                vals.update(zip(out_keys, outs))
                aux_updates.update(aux)
            outs = []
            for (node, idx) in out_entries:
                if node.is_var:
                    outs.append(inputs[node.name])
                else:
                    outs.append(vals[(gid[id(node)], idx)])
            return outs, aux_updates

        return fn


class PipelinedExecutor(Executor):
    """Executor honoring ``group2ctx`` placement across DISTINCT devices —
    the reference's inter-layer model parallelism (Symbol.bind group2ctx,
    python/mxnet/symbol/symbol.py:1290; docs/faq/model_parallel_lstm.md).

    The compiled paths swap ``_GraphLowering`` for ``_PlacedLowering`` and
    drop the outer whole-graph jit: per-device segment programs dispatch
    asynchronously and the eager inter-segment transfers are the
    kCrossDeviceCopy edges. forward/backward/arg_dict semantics are
    inherited unchanged."""

    def __init__(self, symbol, ctx, args, args_grad=None, grad_req="write",
                 aux_states=None, group2ctx=None):
        super().__init__(symbol, ctx, args, args_grad, grad_req, aux_states)
        self.group2ctx = dict(group2ctx or {})
        node_device = _assign_devices(symbol, group2ctx, ctx)
        self._lowering = _PlacedLowering(symbol, node_device)
        # commit bound arrays to their assigned devices so the per-call
        # device_put in the placed lowering is a no-op rather than a
        # per-step re-upload of every weight; forward() re-commits lazily
        # because external writers (init_params, optimizers) may rebind an
        # array onto the default device
        self._var_device = {n.name: node_device[id(n)]
                            for n in self._lowering.nodes if n.is_var}
        self._commit_placement()

    def _commit_placement(self) -> None:
        for d in (self.arg_dict, self.aux_dict, self.grad_dict):
            for name, arr in d.items():
                dev = self._var_device.get(name)
                if dev is not None and arr is not None and \
                        dev not in arr._data.devices():
                    arr._set_data(jax.device_put(arr._data, dev))

    def forward(self, is_train: bool = False, **kwargs):
        self._commit_placement()
        return super().forward(is_train=is_train, **kwargs)

    def _rebuild(self, new_args, new_grads, new_aux):
        return PipelinedExecutor(self._symbol, self._ctx, new_args,
                                 new_grads, self.grad_req, new_aux,
                                 group2ctx=self.group2ctx)

    # _compiled/_compiled_train_step/_compiled_custom_bwd are inherited:
    # _jit_outer=False keeps the composed program eager (segments are
    # individually jitted and placed), incl. MXNET_BACKWARD_DO_MIRROR.
    _jit_outer = False
