"""Graph-pass manager — the write half of the compiler-pass framework.

``analysis/`` walks symbol graphs read-only (mxlint); this package REWRITES
them, Relay/TVM-style (PAPERS.md): each measured perf lever becomes a
rewrite pass over the symbol IR, so every net inherits it by construction
instead of by tuning run.  A :class:`PassManager` is an ordered pipeline of
:class:`Pass` instances; ``Module``/``DataParallelTrainer`` run the default
pipeline on every captured graph unless constructed with ``passes=False``.

Pipeline semantics:

* Passes run in declared order over a **functional rebuild** of the node
  DAG — the input :class:`~mxnet_tpu.symbol.Symbol` is never mutated, and a
  pass that rewrites nothing returns the input symbol object unchanged (so
  a no-op pipeline is bitwise-invisible to the jit cache).
* A pass may **re-home a variable** (change its declared layout/shape —
  e.g. an OIHW conv weight becoming OHWI) instead of inserting in-graph
  transposes.  Every re-homing is recorded in the
  :class:`PassResult` as a value transform, and the capture path applies
  it to the parameter values (and its inverse on ``sync_to_net``), so the
  user-visible net keeps its original layout.
* ``MXNET_PASSES`` selects the default pipeline: ``"0"``/``"off"`` disables
  it, ``"layout,fusion"`` runs exactly those passes, ``"-fold"`` runs the
  default set minus a pass.

Pass catalog (docs/passes.md): ``fold`` (constant folding + dead-branch
elimination), ``layout`` (automatic NCHW→NHWC propagation), ``fusion``
(transpose/cast reordering so XLA fuses across layout boundaries).  How a
stride-2 few-channel stem is lowered is not a pass: the Convolution op
decides it from the shapes it sees (``ops/nn.py``).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..base import MXNetError, get_env, logger, register_config

__all__ = ["Pass", "PassContext", "PassResult", "PassManager",
           "DEFAULT_PIPELINE", "PASS_REGISTRY", "register_pass",
           "default_names", "resolve", "annotate_graph", "apply_spec",
           "spec_shape", "provenance"]

register_config(
    "MXNET_PASSES", "", str,
    "Default graph-pass pipeline for Module/DataParallelTrainer capture. "
    "Empty = the built-in default (fold,layout,fusion); '0'/'off' "
    "disables it; 'layout,fusion' runs exactly those; '-fold' runs the "
    "default minus a pass.")

#: the default pipeline contents, in order
DEFAULT_PIPELINE = ("fold", "layout", "fusion")

#: name -> Pass subclass (populated by the pass modules at import)
PASS_REGISTRY: Dict[str, type] = {}


def register_pass(cls) -> type:
    PASS_REGISTRY[cls.name] = cls
    return cls


class Pass:
    """One rewrite pass over a Symbol graph.

    Subclasses set ``name`` and implement ``apply(sym, ctx) ->
    (new_sym, rewrite_count)``.  ``apply`` MUST be functional: return the
    input symbol unchanged when nothing rewrites, never mutate existing
    nodes (re-homed variables are fresh clones)."""

    name = "pass"

    def apply(self, sym, ctx: "PassContext"):
        raise NotImplementedError

    def __repr__(self):
        return f"<Pass {self.name}>"


# --------------------------------------------------------------------------
# shared graph utilities
# --------------------------------------------------------------------------

_NCHW_SPELLINGS = (None, "None", "", "NCHW")


def node_names(sym) -> set:
    return {n.name for n in sym.topo_nodes()}


class Namer:
    """Unique-name generator for pass-inserted nodes.  Seeded with every
    existing node name (and, for partitioned graphs, the inner subgraph
    names) so a rewrite can never collide with a partition boundary — the
    subgraph re-anchoring contract tests/test_passes.py pins."""

    def __init__(self, sym):
        self._taken = set()
        for n in sym.topo_nodes():
            self._taken.add(n.name)
            for key in ("subgraph_id", "then_id", "else_id", "cond_id",
                        "body_id"):
                if n.op is not None and key in (n.attrs or {}):
                    try:
                        from ..subgraph import get_stored_subgraph
                        inner = get_stored_subgraph(int(n.attrs[key]))
                        self._taken |= {m.name for m in inner.topo_nodes()}
                    except Exception:
                        pass

    def fresh(self, base: str) -> str:
        name = base
        i = 0
        while name in self._taken:
            i += 1
            name = f"{base}{i}"
        self._taken.add(name)
        return name


#: ops that own nested subgraphs — passes treat them as opaque barriers
#: (rewriting across a partition/control-flow boundary would desync the
#: stored inner symbol from the outer wiring)
def is_barrier(node) -> bool:
    if node.op is None:
        return False
    if node.op == "_subgraph":
        return True
    attrs = node.attrs or {}
    return any(k in attrs for k in ("subgraph_id", "then_id", "else_id",
                                    "cond_id", "body_id"))


def annotate_graph(sym, shapes: Optional[Dict[str, Sequence[int]]] = None,
                   dtypes: Optional[Dict[str, Any]] = None
                   ) -> Dict[Tuple[int, int], Any]:
    """Tolerant abstract evaluation: map every graph entry ``(id(node),
    out_idx)`` to a ``jax.ShapeDtypeStruct`` (or ``None`` where inference
    fails — passes skip nodes with unknown inputs instead of raising).
    Variables are keyed ``(id(var), 0)``.  The same parameter-shape
    backfill rules the executor uses resolve weight shapes from data
    shapes, so providing the input-batch shapes is usually enough."""
    import jax
    import jax.numpy as jnp
    from ..ops.registry import get_op
    from ..executor import _PARAM_SHAPE_RULES
    from .._imperative import _op_signature_flags
    from ..analysis.graph_lint import _parse_shape_attr, _parse_dtype_attr

    shapes = {k: tuple(v) for k, v in (shapes or {}).items()}
    dtypes = dict(dtypes or {})
    var_shape: Dict[str, Tuple[int, ...]] = {}
    var_dtype: Dict[str, Any] = {}
    nodes = sym.topo_nodes()
    for n in nodes:
        if not n.is_var:
            continue
        s = shapes.get(n.name)
        if s is None and "__shape__" in n._attr_dict:
            s = _parse_shape_attr(n._attr_dict["__shape__"])
        if s is not None:
            var_shape[n.name] = tuple(s)
        dt = dtypes.get(n.name)
        if dt is None and "__dtype__" in n._attr_dict:
            dt = _parse_dtype_attr(n._attr_dict["__dtype__"])
        if dt is not None:
            var_dtype[n.name] = dt

    avals: Dict[Tuple[int, int], Any] = {}
    for node in nodes:
        if node.is_var:
            if node.name in var_shape:
                avals[(id(node), 0)] = jax.ShapeDtypeStruct(
                    var_shape[node.name],
                    np.dtype(var_dtype.get(node.name, np.float32)))
            else:
                avals[(id(node), 0)] = None
            continue
        try:
            opdef = get_op(node.op)
        except MXNetError:
            continue
        if opdef.host:
            continue
        arg_names = opdef.arg_names() or []
        rule = _PARAM_SHAPE_RULES.get(node.op)
        if rule is not None and node.inputs:
            src0, idx0 = node.inputs[0]
            ds = (var_shape.get(src0.name) if src0.is_var
                  else (tuple(avals[(id(src0), idx0)].shape)
                        if avals.get((id(src0), idx0)) is not None else None))
            if ds is not None:
                try:
                    param_shapes = rule(dict(node.attrs), tuple(ds))
                except Exception:
                    param_shapes = {}
                for i, (src, _) in enumerate(node.inputs):
                    if src.is_var and src.name not in var_shape \
                            and i < len(arg_names) \
                            and arg_names[i] in param_shapes:
                        var_shape[src.name] = param_shapes[arg_names[i]]
                        avals[(id(src), 0)] = jax.ShapeDtypeStruct(
                            var_shape[src.name],
                            np.dtype(var_dtype.get(src.name, np.float32)))
        in_avals = []
        ok = True
        for (src, idx) in node.inputs:
            av = avals.get((id(src), idx))
            if av is None:
                ok = False
                break
            in_avals.append(av)
        if not ok:
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
        except Exception:
            continue
        if not isinstance(out_avals, tuple):
            out_avals = (out_avals,)
        for i, av in enumerate(out_avals):
            avals[(id(node), i)] = av
    return avals


# --------------------------------------------------------------------------
# value transforms (re-homed variables)
# --------------------------------------------------------------------------

def _inv_perm(perm):
    inv = [0] * len(perm)
    for i, p in enumerate(perm):
        inv[p] = i
    return tuple(inv)


def apply_spec(spec, value: np.ndarray, inverse: bool = False) -> np.ndarray:
    kind = spec[0]
    if kind == "transpose":
        perm = spec[1]
        return np.transpose(value, _inv_perm(perm) if inverse else perm)
    raise MXNetError(f"unknown variable-transform spec {spec!r}")


def spec_shape(spec, shape: Sequence[int]) -> Tuple[int, ...]:
    """The shape ``apply_spec(spec, ·)`` produces, without materializing a
    value — every transform kind added to ``apply_spec`` adds its shape
    effect HERE (annotate + PassResult.transformed_shape + mxopt all read
    this one function)."""
    shape = tuple(int(d) for d in shape)
    kind = spec[0]
    if kind == "transpose":
        return tuple(shape[i] for i in spec[1])
    raise MXNetError(f"unknown variable-transform spec {spec!r}")


def rehomed_shapes(shapes: Dict[str, Sequence[int]],
                   var_transforms: Dict[str, List[tuple]],
                   input_layouts: Dict[str, str]) -> Dict[str, Tuple]:
    """Original variable shapes -> the shapes the REWRITTEN graph
    declares: value transforms folded through :func:`spec_shape`, NHWC
    re-homed rank-4 inputs permuted.  Shared by ``PassContext.annotate``
    and ``PassResult.transformed_shapes`` (mxopt's after-lint)."""
    out = {k: tuple(int(d) for d in v) for k, v in shapes.items()}
    for name, specs in var_transforms.items():
        if name in out:
            s = out[name]
            for spec in specs:
                s = spec_shape(spec, s)
            out[name] = s
    for name, lay in input_layouts.items():
        s = out.get(name)
        if lay == "NHWC" and s is not None and len(s) == 4:
            out[name] = (s[0], s[2], s[3], s[1])
    return out


def provenance(manager: Optional["PassManager"],
               result: Optional["PassResult"],
               fallback_rewrites: Optional[Dict[str, int]] = None
               ) -> Dict[str, Any]:
    """The ``passes=`` provenance dict stamped into bench/ladder rows —
    ONE schema shared by DataParallelTrainer and Module."""
    if manager is None:
        return {"enabled": False, "pipeline": [], "applied": []}
    prov: Dict[str, Any] = {"enabled": True,
                            "pipeline": list(manager.names)}
    if manager.input_layout:
        prov["input_layout"] = manager.input_layout
    if result is not None:
        prov["applied"] = result.applied
        prov["rewrites"] = {k: v for k, v in result.counts.items() if v}
    else:
        prov["applied"] = []
        if fallback_rewrites:
            prov["rewrites"] = {k: v for k, v in fallback_rewrites.items()
                                if v}
    return prov


# --------------------------------------------------------------------------
# context / result / manager
# --------------------------------------------------------------------------

class PassContext:
    """Per-pipeline-run state shared by the passes: known shapes, which
    variables are inputs vs parameters, re-homing policy, and the
    accumulated variable transforms."""

    def __init__(self, shapes=None, dtypes=None, input_vars: Sequence[str] = (),
                 param_names: Optional[Sequence[str]] = None,
                 rehome_params: bool = False,
                 input_layout: Optional[str] = None):
        self.shapes = dict(shapes or {})
        self.dtypes = dict(dtypes or {})
        self.input_vars = set(input_vars or ())
        self.param_names = set(param_names) if param_names is not None \
            else None
        self.rehome_params = bool(rehome_params)
        # "NHWC" = the caller commits to feeding channel-last batches, so
        # the layout pass may re-home rank-4 input variables instead of
        # inserting a leading transpose (the tuner's flag-vs-pass route)
        self.input_layout = input_layout
        #: var name -> ordered transform specs (applied left to right to
        #: the ORIGINAL value to obtain the rewritten graph's value)
        self.var_transforms: Dict[str, List[tuple]] = {}
        #: NEW variables a pass introduced, with a spec describing how to
        #: derive each value from the original parameter dict (the
        #: quantize pass mints int8 weights + range scalars this way);
        #: materialized by :meth:`PassResult.materialize_params`
        self.synth_params: Dict[str, tuple] = {}
        #: internal source values synthesized specs may reference (e.g. a
        #: zero bias) — never returned to the caller themselves
        self.synth_sources: Dict[str, tuple] = {}
        #: var name -> declared layout after re-homing (inputs only)
        self.input_layouts: Dict[str, str] = {}
        self.counts: Dict[str, int] = {}
        self._aval_cache: Dict[int, Dict] = {}
        self._aval_keep: List[Any] = []   # pin cached symbols (id reuse)

    def can_rehome_param(self, name: str) -> bool:
        if not self.rehome_params:
            return False
        if name in self.input_vars:
            return False
        if self.param_names is not None:
            return name in self.param_names
        return False

    def can_rehome_input(self, name: str) -> bool:
        return self.input_layout == "NHWC" and name in self.input_vars

    def add_var_transform(self, name: str, spec: tuple) -> None:
        self.var_transforms.setdefault(name, []).append(spec)

    def add_synth_param(self, name: str, spec: tuple) -> None:
        """Declare a NEW variable the rewritten graph consumes, derived
        from the original params per ``spec``: ``("const", value)`` a
        literal scalar, ``("quant_of", src, part)`` one leg of the int8
        (quantized/min/max) triple of parameter ``src``."""
        self.synth_params[name] = tuple(spec)

    def add_synth_source(self, name: str, spec: tuple) -> None:
        """Declare an internal source value (``("zeros", shape)``) that
        ``quant_of`` specs may reference but which is not itself a graph
        variable."""
        self.synth_sources[name] = tuple(spec)

    def annotate(self, sym) -> Dict[Tuple[int, int], Any]:
        key = id(sym)
        if key not in self._aval_cache:
            # re-homed vars already carry transforms: their live shapes in
            # THIS graph are the transformed ones
            shapes = rehomed_shapes(self.shapes, self.var_transforms,
                                    self.input_layouts)
            self._aval_cache[key] = annotate_graph(sym, shapes, self.dtypes)
            self._aval_keep.append(sym)
        return self._aval_cache[key]


class PassResult:
    """What a pipeline run produced: the rewritten symbol, per-pass rewrite
    counts, and the variable value transforms the caller must apply."""

    def __init__(self, symbol, ctx: PassContext, names: Sequence[str]):
        self.symbol = symbol
        self.counts = dict(ctx.counts)
        self.var_transforms = {k: list(v)
                               for k, v in ctx.var_transforms.items()}
        self.input_layouts = dict(ctx.input_layouts)
        self.synth_params = dict(ctx.synth_params)
        self.synth_sources = dict(ctx.synth_sources)
        self.names = tuple(names)

    @property
    def total_rewrites(self) -> int:
        return sum(self.counts.values())

    @property
    def applied(self) -> List[str]:
        """Pass names that actually rewrote something."""
        return [n for n in self.names if self.counts.get(n)]

    def transform_var(self, name: str, value):
        v = np.asarray(value)
        for spec in self.var_transforms.get(name, ()):
            v = apply_spec(spec, v)
        return v

    def transformed_shape(self, name: str, shape) -> Tuple[int, ...]:
        """The re-homed shape of variable ``name`` given its original
        ``shape`` (identity when un-transformed) — shape math only."""
        s = tuple(int(d) for d in shape)
        for spec in self.var_transforms.get(name, ()):
            s = spec_shape(spec, s)
        return s

    def transformed_shapes(self, shapes: Dict) -> Dict:
        """Map a whole original-shape dict into the rewritten graph's
        shapes (value transforms + NHWC input re-homing) — what the
        rewritten symbol binds/lints with."""
        return rehomed_shapes(shapes, self.var_transforms,
                              self.input_layouts)

    def inverse_var(self, name: str, value):
        v = np.asarray(value)
        for spec in reversed(self.var_transforms.get(name, ())):
            v = apply_spec(spec, v, inverse=True)
        return v

    def materialize_params(self, arg_params: Dict) -> Dict:
        """Compute the values of every pass-synthesized variable
        (``ctx.add_synth_param``) from the ORIGINAL parameter dict — the
        extra params the caller merges into its bind dict. One source of
        truth for the int8 math: ``contrib.quantization.quantize_params``."""
        if not self.synth_params:
            return {}
        from .. import ndarray as nd_mod
        src = dict(arg_params)
        for name, spec in self.synth_sources.items():
            if spec[0] == "zeros":
                src[name] = nd_mod.zeros(tuple(int(d) for d in spec[1]))
            else:
                raise MXNetError(f"unknown synth-source spec {spec!r}")
        out: Dict[str, Any] = {}
        quant_cache: Dict[str, Dict] = {}
        for name, spec in self.synth_params.items():
            kind = spec[0]
            if kind == "const":
                out[name] = nd_mod.array(np.float32(spec[1]))
            elif kind == "quant_of":
                pname, part = spec[1], spec[2]
                if pname not in quant_cache:
                    from ..contrib.quantization import quantize_params
                    if pname not in src:
                        raise MXNetError(
                            f"synthesized param {name!r} derives from "
                            f"{pname!r}, which is not in arg_params")
                    quant_cache[pname] = quantize_params({pname: src[pname]})
                out[name] = quant_cache[pname][f"{pname}_{part}"]
            else:
                raise MXNetError(f"unknown synth-param spec {spec!r}")
        return out



def default_names(spec: Optional[str] = None) -> Tuple[str, ...]:
    """Resolve a pipeline spelling (the ``MXNET_PASSES`` grammar) to an
    ordered tuple of pass names.  ``None`` reads the env knob."""
    if spec is None:
        spec = str(get_env("MXNET_PASSES", "") or "")
    spec = spec.strip()
    if spec.lower() in ("0", "off", "none", "false"):
        return ()
    if not spec:
        return DEFAULT_PIPELINE
    tokens = [t.strip() for t in spec.split(",") if t.strip()]
    removed = {t[1:].strip() for t in tokens if t.startswith("-")}
    listed = [t for t in tokens if not t.startswith("-")]
    base = list(listed) if listed else list(DEFAULT_PIPELINE)
    for name in set(base) | removed:
        if name not in PASS_REGISTRY:
            raise MXNetError(
                f"unknown graph pass {name!r} "
                f"(registered: {', '.join(sorted(PASS_REGISTRY))})")
    return tuple(n for n in base if n not in removed)


class PassManager:
    """Ordered, configurable pipeline of graph passes.

    ``passes`` may be pass names, :class:`Pass` instances, or a spec string
    in the ``MXNET_PASSES`` grammar; ``None`` takes the env-configured
    default.  ``input_layout="NHWC"`` declares that the caller feeds
    channel-last batches, letting the layout pass re-home rank-4 input
    variables (zero residual transposes — the hand-flag-identical route)."""

    def __init__(self, passes=None, input_layout: Optional[str] = None,
                 rehome_params: bool = True):
        if passes is None or isinstance(passes, str):
            names = default_names(passes)
            self.passes: List[Pass] = [PASS_REGISTRY[n]() for n in names]
        else:
            self.passes = []
            for p in passes:
                if isinstance(p, Pass):
                    self.passes.append(p)
                elif isinstance(p, str):
                    if p not in PASS_REGISTRY:
                        raise MXNetError(f"unknown graph pass {p!r}")
                    self.passes.append(PASS_REGISTRY[p]())
                elif isinstance(p, type) and issubclass(p, Pass):
                    self.passes.append(p())
                else:
                    raise MXNetError(f"not a pass: {p!r}")
        if input_layout not in (None, "NHWC"):
            raise MXNetError("input_layout must be None or 'NHWC', got %r"
                             % (input_layout,))
        self.input_layout = input_layout
        self.rehome_params = bool(rehome_params)

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(p.name for p in self.passes)

    def __len__(self):
        return len(self.passes)

    def __repr__(self):
        return f"<PassManager {','.join(self.names) or '(empty)'}>"

    def init_view(self, arrays):
        """The sample batch as the NET expects it for the deferred-init
        host forward: under ``input_layout='NHWC'`` the caller feeds
        channel-last batches to an NCHW-built net, so rank-4 arrays are
        permuted back to NCHW for initialization only."""
        if self.input_layout != "NHWC":
            return list(arrays)
        import jax
        out = []
        for a in arrays:
            if getattr(a, "ndim", 0) == 4:
                out.append(np.transpose(np.asarray(jax.device_get(a)),
                                        (0, 3, 1, 2)))
            else:
                out.append(a)
        return out

    def run(self, sym, shapes=None, dtypes=None, input_vars: Sequence[str] = (),
            param_names: Optional[Sequence[str]] = None,
            rehome_params: Optional[bool] = None) -> PassResult:
        """Run the pipeline over ``sym``; returns a :class:`PassResult`.
        ``shapes`` plays the ``simple_bind`` kwargs role (data shapes;
        parameter shapes backfill from the executor's rules).  The input
        symbol is never mutated; with zero rewrites ``result.symbol is
        sym``."""
        ctx = PassContext(
            shapes=shapes, dtypes=dtypes, input_vars=input_vars,
            param_names=param_names,
            rehome_params=self.rehome_params if rehome_params is None
            else bool(rehome_params),
            input_layout=self.input_layout)
        cur = sym
        for p in self.passes:
            try:
                cur, n = p.apply(cur, ctx)
            except MXNetError:
                raise
            except Exception as e:
                # a pass must never take down a capture: log and continue
                # with the last good graph (equivalence holds trivially)
                logger.warning("graph pass %r failed, skipped: %r",
                               p.name, e)
                n = 0
            ctx.counts[p.name] = ctx.counts.get(p.name, 0) + int(n)
        return PassResult(cur, ctx, self.names)


def resolve(passes) -> Optional[PassManager]:
    """Normalize the ``passes=`` ctor argument shared by Module and
    DataParallelTrainer: ``None`` = env-default pipeline (may be empty =>
    None), any explicit falsy spelling (``False``/``0``/``""``/``()``) =
    off — only the unset default silently enables (the falsy-spelling
    contract PR-5/PR-7 established for recovery/scaler configs) — a
    :class:`PassManager` = itself, a spec string / sequence = custom."""
    if passes is None:
        mgr = PassManager()
        return mgr if len(mgr) else None
    if passes is True:
        # an EXPLICIT opt-in beats the ambient env knob: MXNET_PASSES=off
        # must not silently disable a trainer that asked for the pipeline
        return PassManager(DEFAULT_PIPELINE)
    if isinstance(passes, PassManager):
        return passes if len(passes) else None
    if not passes or (isinstance(passes, str) and not passes.strip()):
        return None
    mgr = PassManager(passes)
    return mgr if len(mgr) else None
