"""Autograd: tape-based reverse-mode differentiation at op granularity.

Reference parity: ``python/mxnet/autograd.py`` (record/pause/train_mode/
predict_mode/backward/grad/Function) over ``src/imperative/imperative.cc``
(``RecordOp`` :191, ``Backward`` :278, AGInfo tagging).

TPU-first: instead of building an NNVM gradient graph and scheduling it on a
C++ engine, each recorded op runs the cached executable that returns its
outputs and its ``jax.vjp`` pullback (``ops.registry.jitted_op_vjp``: forward
runs exactly once; the pullback holds XLA-resident residuals). ``backward()``
walks the tape in reverse creation order accumulating cotangents — every
pullback call is itself a cached XLA executable, so the backward pass is a
sequence of async device dispatches just like forward.
"""
from __future__ import annotations

import threading
import weakref
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from .base import MXNetError

__all__ = ["record", "pause", "train_mode", "predict_mode", "is_recording",
           "is_training", "set_recording", "set_training", "backward", "grad",
           "mark_variables", "get_symbol", "Function"]

_state = threading.local()


def _st():
    if not hasattr(_state, "recording"):
        _state.recording = False
        _state.training = False
        _state.tape = []
        _state.counter = 0
        _state.pending_nodes = None
    return _state


def is_recording() -> bool:
    return _st().recording


def is_training() -> bool:
    return _st().training


def set_recording(flag: bool) -> bool:
    st = _st()
    old, st.recording = st.recording, flag
    return old


def set_training(flag: bool) -> bool:
    st = _st()
    old, st.training = st.training, flag
    return old


class _Scope:
    def __init__(self, recording: Optional[bool], training: Optional[bool]):
        self._rec, self._train = recording, training

    def __enter__(self):
        st = _st()
        self._old = (st.recording, st.training)
        if self._rec is not None:
            st.recording = self._rec
        if self._train is not None:
            st.training = self._train
        return self

    def __exit__(self, *exc):
        st = _st()
        st.recording, st.training = self._old
        return False


def record(train_mode: bool = True) -> _Scope:
    return _Scope(True, train_mode)


def pause(train_mode: bool = False) -> _Scope:
    return _Scope(False, train_mode)


def train_mode() -> _Scope:
    return _Scope(None, True)


def predict_mode() -> _Scope:
    return _Scope(None, False)


class _Node:
    """One recorded op application (the AGInfo equivalent)."""

    __slots__ = ("vjp_fn", "parents", "parent_slots", "n_outputs", "order",
                 "op_name", "saved_outputs", "primal", "diff_datas", "freed")

    def __init__(self, vjp_fn, parents, parent_slots, n_outputs, order, op_name):
        self.vjp_fn = vjp_fn
        self.parents = parents          # list of (_Node | _Leaf | None)
        self.parent_slots = parent_slots  # output index within parent
        self.n_outputs = n_outputs
        self.order = order
        self.op_name = op_name
        self.saved_outputs = None
        # create_graph support: the differentiable primal closure and its
        # positional (differentiable) input arrays, so the backward of this
        # node can be RE-derived inside a recorded call (jax.vjp composes)
        self.primal = None
        self.diff_datas = None
        self.freed = False      # True once a backward pass released residuals


class _Leaf:
    """A variable with an attached gradient buffer."""

    __slots__ = ("array_ref", "grad_req", "order")

    def __init__(self, array_ref, grad_req="write"):
        self.array_ref = array_ref
        self.grad_req = grad_req
        self.order = -1


def _float_ok(x) -> bool:
    return jnp.issubdtype(x.dtype, jnp.floating) or jnp.issubdtype(x.dtype, jnp.complexfloating)


def _record_invoke(opdef, inputs, in_datas, attrs):
    """Run ``opdef`` through its cached (outputs, pullback) executable and
    record a tape node. Called from _imperative.invoke while recording."""
    from ._imperative import _prepare, _dispatch
    st = _st()
    key, kw = _prepare(opdef, in_datas, attrs)
    diff_idx = tuple(i for i, d in enumerate(in_datas)
                     if hasattr(d, "dtype") and _float_ok(d))
    if not diff_idx:
        st.pending_nodes = None
        return _dispatch(opdef, key, in_datas, kw)
    out, vjp_fn = _dispatch(opdef, key, in_datas, kw, diff_idx)

    def closed(*diff_args):
        full = list(in_datas)
        for i, d in zip(diff_idx, diff_args):
            full[i] = d
        return _dispatch(opdef, key, full, kw)

    diff_args = [in_datas[i] for i in diff_idx]
    parents, slots = [], []
    for i in diff_idx:
        node = getattr(inputs[i], "_ag_node", None)
        slot = getattr(inputs[i], "_ag_slot", 0)
        parents.append(node)
        slots.append(slot)

    n_out = len(out) if isinstance(out, tuple) else 1
    node = _Node(vjp_fn, parents, slots, n_out, st.counter, opdef.name)
    node.primal = closed
    node.diff_datas = diff_args
    if n_out > 1:
        node.saved_outputs = list(out)
    st.counter += 1
    st.tape.append(node)
    st.pending_nodes = node
    return out


def _attach_outputs(outs):
    st = _st()
    node = st.pending_nodes
    st.pending_nodes = None
    if node is None:
        return
    for i, o in enumerate(outs):
        o._ag_node = node
        o._ag_slot = i


def mark_variables(variables, gradients, grad_reqs="write"):
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for v, g, req in zip(variables, gradients, grad_reqs):
        v._grad = g
        v._ag_node = _Leaf(v, req)
        v._ag_slot = 0


def _check_head_grads(heads, head_grads):
    """Reject shape-class mismatches the reference catches at the C API
    boundary (a bare NDArray for a list of heads would otherwise be
    silently row-sliced by head_grads[i])."""
    if head_grads is None:
        return
    if not isinstance(head_grads, (list, tuple)):
        raise MXNetError(
            "head_grads must be None or a list/tuple matching heads; got %s"
            % type(head_grads).__name__)
    if len(head_grads) != len(heads):
        raise MXNetError(
            "head_grads length %d does not match heads length %d"
            % (len(head_grads), len(heads)))


def backward(heads, head_grads=None, retain_graph=False, train_mode=True):
    """Accumulate gradients of ``heads`` into attached leaf grads
    (reference Imperative::Backward, imperative.cc:278)."""
    from .ndarray.ndarray import NDArray
    if isinstance(heads, NDArray):
        heads = [heads]
        if head_grads is not None and not isinstance(head_grads, (list, tuple)):
            head_grads = [head_grads]
    _check_head_grads(heads, head_grads)
    _backward_impl(heads, head_grads, retain_graph, accumulate_to_leaves=True)


def grad(heads, variables, head_grads=None, retain_graph=None, create_graph=False,
         train_mode=True):
    """Return grads of heads wrt variables without touching .grad buffers.

    With ``create_graph=True`` the backward pass itself is recorded on the
    tape (reference Imperative::Backward honoring create_graph,
    imperative.cc:278-460), so the returned grads are differentiable —
    grad-of-grad, gradient penalties, etc. compose to arbitrary order."""
    from .ndarray.ndarray import NDArray, _wrap
    if isinstance(heads, NDArray):
        heads = [heads]
        if head_grads is not None and not isinstance(head_grads, (list, tuple)):
            head_grads = [head_grads]
    if isinstance(variables, NDArray):
        variables = [variables]
    _check_head_grads(heads, head_grads)
    if retain_graph is None:
        retain_graph = create_graph   # reference autograd.grad default
    if create_graph:
        recs = _backward_create_graph(heads, head_grads, variables,
                                      retain_graph=retain_graph)
        out = []
        for r in recs:
            w = _wrap(r._data)
            if r._ag_node is not None:
                w._ag_node = r._ag_node
                w._ag_slot = r._ag_slot
            out.append(w)
        return out
    grads = _backward_impl(heads, head_grads, retain_graph,
                           accumulate_to_leaves=False, wrt=variables)
    return [_wrap(g) for g in grads]


def _backward_impl(heads, head_grads, retain_graph, accumulate_to_leaves=True,
                   wrt=None):
    st = _st()
    # cotangent accumulator keyed by (id(node), slot)
    cotangents: Dict[Any, Any] = {}
    roots: List[_Node] = []
    for i, h in enumerate(heads):
        node = getattr(h, "_ag_node", None)
        if node is None:
            raise MXNetError("head array is not part of a recorded graph "
                             "(did you compute it under autograd.record()?)")
        hg = None
        if head_grads is not None and head_grads[i] is not None:
            hg = head_grads[i]._data if hasattr(head_grads[i], "_data") else head_grads[i]
        else:
            hg = jnp.ones_like(h._data)
        slot = getattr(h, "_ag_slot", 0)
        key = (id(node), slot)
        cotangents[key] = cotangents.get(key, 0) + hg
        if isinstance(node, _Node):
            roots.append(node)

    # collect reachable subgraph
    seen = {}
    stack = list(roots)
    while stack:
        n = stack.pop()
        if id(n) in seen or not isinstance(n, _Node):
            continue
        seen[id(n)] = n
        for p in n.parents:
            if isinstance(p, _Node) and id(p) not in seen:
                stack.append(p)

    order = sorted(seen.values(), key=lambda n: n.order, reverse=True)

    leaf_grads: Dict[int, Any] = {}
    for n in order:
        if all(cotangents.get((id(n), s)) is None
               for s in range(n.n_outputs)):
            continue
        if n.vjp_fn is None:
            # residuals were released by an earlier backward pass —
            # reference ThreadedEngine raises the same way on a re-walked
            # freed graph ("second backward"); never let the None leak as
            # a TypeError
            raise MXNetError(
                f"cannot backward through {n.op_name!r} a second time: its "
                f"residuals were freed; pass retain_graph=True to the "
                f"first backward/grad call")
        # build full cotangent tuple for the vjp
        if n.n_outputs == 1:
            ct0 = cotangents.get((id(n), 0))
            in_cts = n.vjp_fn(ct0)
        else:
            cts = tuple(
                cotangents.get((id(n), s)) if cotangents.get((id(n), s)) is not None
                else jnp.zeros(sh.shape, sh.dtype)
                for s, sh in enumerate(_vjp_out_avals(n)))
            in_cts = n.vjp_fn(cts)
        for p, slot, ict in zip(n.parents, n.parent_slots, in_cts):
            if p is None or ict is None:
                continue
            if isinstance(p, _Leaf):
                key = id(p.array_ref)
                leaf_grads[key] = (leaf_grads.get(key, 0) + ict)
            else:
                k = (id(p), slot)
                cotangents[k] = cotangents.get(k, 0) + ict
        if not retain_graph:
            n.vjp_fn = None       # free residuals eagerly
            n.primal = None       # the closure pins all op inputs
            n.diff_datas = None
            n.freed = True

    # head that IS a leaf (x.backward() on a var directly)
    for i, h in enumerate(heads):
        node = getattr(h, "_ag_node", None)
        if isinstance(node, _Leaf):
            key = id(node.array_ref)
            hg = cotangents[(id(node), getattr(h, "_ag_slot", 0))]
            leaf_grads[key] = leaf_grads.get(key, 0) + hg

    if accumulate_to_leaves:
        _deliver_leaf_grads(leaf_grads)
        if not retain_graph:
            st.tape.clear()
        return None
    else:
        out = []
        for v in wrt:
            g = leaf_grads.get(id(v))
            if g is None:
                g = jnp.zeros_like(v._data)
            out.append(g)
        if not retain_graph:
            st.tape.clear()
        return out


class _Rec:
    """A value with tape provenance flowing through the create_graph
    backward walk (a lightweight stand-in for a full NDArray)."""

    __slots__ = ("_data", "_ag_node", "_ag_slot")

    def __init__(self, data, node=None, slot=0):
        self._data = data
        self._ag_node = node
        self._ag_slot = slot


def _record_call(fn, wrappers, name):
    """Run ``fn(*datas)`` under jax.vjp and push a tape node whose parents
    are the wrappers' provenance — the create_graph recording primitive."""
    st = _st()
    datas = [w._data for w in wrappers]
    out, vjp_fn = jax.vjp(fn, *datas)
    parents = [w._ag_node for w in wrappers]
    slots = [w._ag_slot for w in wrappers]
    n_out = len(out) if isinstance(out, tuple) else 1
    node = _Node(vjp_fn, parents, slots, n_out, st.counter, name)
    node.primal = fn
    node.diff_datas = datas
    if n_out > 1:
        node.saved_outputs = list(out)
    st.counter += 1
    st.tape.append(node)
    return out, node


def _racc(a, b):
    """Recorded accumulation of two provenance-carrying cotangents."""
    if a is None:
        return b
    if b is None:
        return a
    if a._ag_node is None and b._ag_node is None:
        return _Rec(a._data + b._data)
    out, node = _record_call(lambda x, y: x + y, [a, b], "_ct_add")
    return _Rec(out, node, 0)


def _backward_create_graph(heads, head_grads, wrt, retain_graph=True):
    """Backward walk that RECORDS the gradient computation. Each node's
    input cotangents are computed by re-deriving its vjp inside a recorded
    call taking (original inputs, output cotangents) — so gradients flow
    both through the cotangent chain and through the residuals, and jax's
    vjp-of-vjp gives exact higher-order derivatives. The forward of each op
    is recomputed inside its backward (the memory/compute tradeoff the
    reference makes with create_graph's full backward graph)."""
    cotangents: Dict[Any, _Rec] = {}
    roots: List[_Node] = []
    for i, h in enumerate(heads):
        node = getattr(h, "_ag_node", None)
        if node is None:
            raise MXNetError("head array is not part of a recorded graph "
                             "(did you compute it under autograd.record()?)")
        if head_grads is not None and head_grads[i] is not None:
            hgv = head_grads[i]
            hg = _Rec(hgv._data if hasattr(hgv, "_data") else hgv,
                      getattr(hgv, "_ag_node", None),
                      getattr(hgv, "_ag_slot", 0))
        else:
            hg = _Rec(jnp.ones_like(h._data))
        slot = getattr(h, "_ag_slot", 0)
        key = (id(node), slot)
        cotangents[key] = _racc(cotangents.get(key), hg)
        if isinstance(node, _Node):
            roots.append(node)

    seen: Dict[int, _Node] = {}
    stack = list(roots)
    while stack:
        n = stack.pop()
        if id(n) in seen or not isinstance(n, _Node):
            continue
        seen[id(n)] = n
        for p in n.parents:
            if isinstance(p, _Node) and id(p) not in seen:
                stack.append(p)

    order = sorted(seen.values(), key=lambda n: n.order, reverse=True)

    leaf_grads: Dict[int, _Rec] = {}
    for n in order:
        cts = [cotangents.get((id(n), s)) for s in range(n.n_outputs)]
        if all(c is None for c in cts):
            continue
        if n.primal is None:
            if n.freed:
                raise MXNetError(
                    f"create_graph=True reached {n.op_name!r} whose "
                    f"residuals were already freed by a previous backward "
                    f"pass; call the earlier backward/grad with "
                    f"retain_graph=True to keep the graph alive")
            raise MXNetError(
                f"create_graph=True cannot differentiate through "
                f"{n.op_name!r}: its backward is an opaque callback "
                f"(autograd.Function); express it with registry ops instead")
        for s, c in enumerate(cts):
            if c is None:
                proto = (n.saved_outputs[s] if n.saved_outputs is not None
                         else None)
                cts[s] = _Rec(jnp.zeros(proto.shape, proto.dtype))
        k = len(n.diff_datas)
        in_wrappers = [_Rec(d, p, sl) for d, p, sl in
                       zip(n.diff_datas, n.parents, n.parent_slots)]

        def bwd(*args, _primal=n.primal, _k=k):
            d, c = args[:_k], args[_k:]
            out, vjp = jax.vjp(_primal, *d)
            ct_arg = tuple(c) if isinstance(out, tuple) else c[0]
            res = vjp(ct_arg)           # tuple of _k input cotangents
            return res if _k > 1 else res[0]

        outs, node2 = _record_call(bwd, in_wrappers + cts,
                                   "_grad_of_" + n.op_name)
        outs_t = outs if isinstance(outs, tuple) else (outs,)
        for i, (p, slot) in enumerate(zip(n.parents, n.parent_slots)):
            if p is None:
                continue
            ict = _Rec(outs_t[i], node2, i)
            if isinstance(p, _Leaf):
                key = id(p.array_ref)
                leaf_grads[key] = _racc(leaf_grads.get(key), ict)
            else:
                kk = (id(p), slot)
                cotangents[kk] = _racc(cotangents.get(kk), ict)

    # heads that ARE leaves
    for i, h in enumerate(heads):
        node = getattr(h, "_ag_node", None)
        if isinstance(node, _Leaf):
            key = id(node.array_ref)
            hg = cotangents[(id(node), getattr(h, "_ag_slot", 0))]
            leaf_grads[key] = _racc(leaf_grads.get(key), hg)

    if not retain_graph:
        # release residuals of the walked forward nodes AND drop them from
        # the tape so repeated grad(create_graph=True, retain_graph=False)
        # calls cannot grow memory without bound; the freshly recorded
        # _grad_of_* nodes stay alive (they ARE the returned grads)
        walked = set()
        for n in order:
            n.vjp_fn = None
            n.primal = None
            n.diff_datas = None
            n.freed = True
            walked.add(id(n))
        st = _st()
        st.tape = [n for n in st.tape if id(n) not in walked]

    out = []
    for v in wrt:
        g = leaf_grads.get(id(v))
        if g is None:
            g = _Rec(jnp.zeros_like(v._data))
        out.append(g)
    return out


# weak: the registry finds a leaf by id for as long as someone holds it; it
# must not be the one that holds it (a net's parameters and their gradient
# buffers, gigabytes on the device, outlived the net; PERF.md, PR 31)
_all_leaves: "weakref.WeakValueDictionary[int, Any]" = \
    weakref.WeakValueDictionary()


def _register_leaf(arr):
    _all_leaves[id(arr)] = arr


def _deliver_leaf_grads(leaf_grads):
    for key, g in leaf_grads.items():
        arr = _all_leaves.get(key)
        if arr is None:
            continue
        node = getattr(arr, "_ag_node", None)
        req = node.grad_req if isinstance(node, _Leaf) else "write"
        if req == "null":
            continue
        if req == "add" and arr._grad is not None:
            arr._grad._set_data(arr._grad._data + g)
        else:
            arr._grad._set_data(g)


def _vjp_out_avals(node):
    # saved output avals for zero-filling missing cotangents
    if node.saved_outputs is not None:
        return node.saved_outputs
    raise MXNetError(f"internal: missing output avals for {node.op_name}")


def get_symbol(x):
    raise MXNetError("get_symbol: the TPU runtime records jax vjp closures, "
                     "not NNVM nodes; use CachedOp/hybridize to obtain a graph")


class Function:
    """Custom differentiable function (reference autograd.Function,
    python/mxnet/autograd.py:Function). Subclass and implement
    ``forward(self, *inputs)`` and ``backward(self, *output_grads)`` with
    NDArray in/out."""

    def __init__(self):
        self._saved = None

    def save_for_backward(self, *arrays):
        self._saved = arrays

    @property
    def saved_tensors(self):
        return self._saved

    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *output_grads):
        raise NotImplementedError

    def __call__(self, *inputs):
        from .ndarray.ndarray import NDArray, _wrap
        st = _st()
        with pause():
            outputs = self.forward(*inputs)
        single = not isinstance(outputs, (list, tuple))
        outs = [outputs] if single else list(outputs)
        if is_recording():
            func = self

            def vjp_fn(cts):
                cts = (cts,) if not isinstance(cts, tuple) else cts
                with pause():
                    gs = func.backward(*[_wrap(c) for c in cts])
                if not isinstance(gs, (list, tuple)):
                    gs = [gs]
                return tuple(g._data if hasattr(g, "_data") else g for g in gs)

            parents, slots = [], []
            for x in inputs:
                parents.append(getattr(x, "_ag_node", None))
                slots.append(getattr(x, "_ag_slot", 0))
            node = _Node(vjp_fn if len(outs) > 1 else (lambda ct: vjp_fn((ct,))),
                         parents, slots, len(outs), st.counter,
                         type(self).__name__)
            node.saved_outputs = [o._data for o in outs]
            st.counter += 1
            st.tape.append(node)
            for i, o in enumerate(outs):
                o._ag_node = node
                o._ag_slot = i
        return outs[0] if single else outs
