"""Pipeline parallelism over the 'pp' mesh axis.

Absent from the reference (SURVEY.md §2.3: "nearest: DAG-level
auto-parallelism"); built first-class here. GPipe-style schedule expressed
the SPMD way: every device holds ONE stage's parameters (stacked arrays
sharded on their leading 'stage' dim); a ``lax.fori_loop`` runs
n_micro + n_stages - 1 ticks in which each device applies its stage to the
activation it holds and ``ppermute``s the result to the next device.
Bubble fraction = (n-1)/(m+n-1), as usual — choose n_micro accordingly.

Constraint (same as scan-based pipelining generally): all stages share one
activation shape, e.g. a stack of identical transformer/MLP blocks.
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_apply", "GluonPipelineStack", "HeterogeneousPipeline"]


def pipeline_apply(stage_fn: Callable, stacked_params, x_microbatches,
                   mesh: Mesh, axis: str = "pp"):
    """Run ``stage_fn(params_i, x) -> x`` over n_stages = mesh[axis] stages.

    stacked_params: pytree whose leaves have leading dim n_stages (sharded on
    ``axis``). x_microbatches: (n_micro, *batch_shape) replicated input; the
    return is (n_micro, *batch_shape) of the final stage's outputs.
    """
    n_stages = mesh.shape[axis]
    n_micro = x_microbatches.shape[0]
    total = n_micro + n_stages - 1

    def local(params_stacked, xs):
        # params_stacked leaves: (1, ...) local slice -> squeeze stage dim
        params = jax.tree_util.tree_map(lambda a: a[0], params_stacked)
        rank = lax.axis_index(axis)
        from .ring_attention import _pvary
        state = _pvary(jnp.zeros_like(xs[0]), axis)  # activation currently held
        outs = _pvary(jnp.zeros_like(xs), axis)
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(t, carry):
            state, outs = carry
            # stage 0 ingests microbatch t (if any remain)
            feed = xs[jnp.minimum(t, n_micro - 1)]
            state = jnp.where(rank == 0, feed, state)
            new_state = stage_fn(params, state)
            # last stage emits result of microbatch t - (n_stages - 1)
            out_idx = t - (n_stages - 1)
            emit = jnp.logical_and(rank == n_stages - 1, out_idx >= 0)
            slot = jnp.maximum(out_idx, 0)
            outs = outs.at[slot].set(jnp.where(emit, new_state, outs[slot]))
            state = lax.ppermute(new_state, axis, fwd_perm)
            return state, outs

        state, outs = lax.fori_loop(0, total, tick, (state, outs))
        # only the last rank's outs are real; broadcast them
        outs = lax.psum(jnp.where(rank == n_stages - 1, outs, 0.0), axis)
        return outs

    fn = shard_map(local, mesh=mesh,
                   in_specs=(jax.tree_util.tree_map(lambda _: P(axis), stacked_params,
                                                    is_leaf=lambda l: hasattr(l, "shape")),
                             P()),
                   out_specs=P())
    return fn(stacked_params, x_microbatches)


class GluonPipelineStack:
    """Bridge structurally-identical gluon Blocks onto ``pipeline_apply``.

    This is the TPU-native expression of the reference's model-parallel
    LSTM doc case (``docs/faq/model_parallel_lstm.md`` /
    ``group2ctx``-based layer placement): the homogeneous middle of a
    model — e.g. a stack of LSTM layers, each ``(B, T, H) -> (B, T, H)``
    — runs one-stage-per-device over the ``pp`` mesh axis, while the
    heterogeneous ends (embedding, decoder) stay replicated outside.

    Usage::

        stack = GluonPipelineStack(layer_blocks, sample, mesh, axis='pp')
        y_mb = stack.apply(stack.stacked_params, x_microbatches)
        # ... train on a params pytree via jax.grad, then:
        stack.write_back(trained_params)

    The blocks must already be initialized and share parameter structure
    (same shapes in the same topological order); an input microbatch shape
    equals the inter-stage activation shape.
    """

    def __init__(self, blocks, sample, mesh: Mesh, axis: str = "pp"):
        from ..base import MXNetError
        from .. import symbol as sym_mod
        from .. import autograd
        from ..executor import _GraphLowering
        from ..ndarray.ndarray import _unwrap, _wrap

        if mesh.shape[axis] != len(blocks):
            raise MXNetError(
                f"GluonPipelineStack needs one block per '{axis}' device: "
                f"{len(blocks)} blocks vs mesh[{axis!r}]={mesh.shape[axis]}")
        self._blocks = list(blocks)
        self._mesh = mesh
        self._axis = axis

        sample = jnp.asarray(sample)
        with autograd.pause():                 # materialize deferred params
            for b in self._blocks:
                b(_wrap(sample))

        per_block_names = []
        per_block_pmaps = []
        lowering = None
        for b in self._blocks:
            x_sym = sym_mod.Variable("__pp_x")
            out = b(x_sym)
            if isinstance(out, (list, tuple)):
                out = out[0]
            low = _GraphLowering(out)
            names = [n for n in low.var_names if n != "__pp_x"]
            per_block_names.append(names)
            per_block_pmaps.append(
                {p.name: p for p in b.collect_params().values()})
            if lowering is None:
                lowering = low
        shapes0 = [per_block_pmaps[0][n].shape for n in per_block_names[0]]
        for pmap, names in zip(per_block_pmaps[1:], per_block_names[1:]):
            shapes = [pmap[n].shape for n in names]
            if shapes != shapes0:
                raise MXNetError(
                    "pipeline stages must be structurally identical; "
                    f"got param shapes {shapes} vs {shapes0}")
        self._canonical = per_block_names[0]
        self._per_block_names = per_block_names
        self._per_block_pmaps = per_block_pmaps
        raw = lowering.lower(is_train=True)

        has_rng = lowering.has_rng

        def stage_fn(params, x):
            ins = dict(zip(self._canonical, params))
            ins["__pp_x"] = x
            # rng-capable ops (e.g. RNN's dropout arg) get a FIXED stream:
            # the pipeline schedule is traced once, so per-tick rng would
            # leak schedule state into the stage; in-stage dropout is
            # deterministic per trace — put stochastic dropout outside the
            # pipelined stack if that matters
            outs, _ = raw(ins, jax.random.PRNGKey(0) if has_rng else None)
            return outs[0]

        self._stage_fn = stage_fn
        from jax.sharding import NamedSharding
        stage_spec = NamedSharding(mesh, P(axis))
        self.stacked_params = tuple(
            jax.device_put(
                jnp.stack([_unwrap(per_block_pmaps[j][per_block_names[j][i]]
                                   .data())
                           for j in range(len(self._blocks))]), stage_spec)
            for i in range(len(self._canonical)))

    def apply(self, stacked_params, x_microbatches):
        """(n_micro, B, ...) -> (n_micro, B, ...) through the device-mapped
        stage stack (GPipe schedule, differentiable)."""
        from jax.sharding import NamedSharding
        stage_spec = NamedSharding(self._mesh, P(self._axis))
        repl = NamedSharding(self._mesh, P())

        def _put(a, spec):
            # concrete arrays get placed here for caller convenience; under
            # a jit trace placement is the enclosing jit's job (pass
            # mesh-placed params in, as the example recipe does)
            if isinstance(a, jax.core.Tracer):
                return a
            a = jnp.asarray(a)
            return a if a.sharding == spec else jax.device_put(a, spec)

        stacked_params = jax.tree_util.tree_map(
            lambda a: _put(a, stage_spec), stacked_params)
        x_microbatches = _put(x_microbatches, repl)
        return pipeline_apply(self._stage_fn, stacked_params, x_microbatches,
                              self._mesh, self._axis)

    def write_back(self, stacked_params) -> None:
        """Push a trained stacked pytree back into the gluon blocks."""
        for i in range(len(self._canonical)):
            leaf = stacked_params[i]
            for j in range(len(self._blocks)):
                name = self._per_block_names[j][i]
                self._per_block_pmaps[j][name].data()._set_data(
                    jnp.asarray(leaf[j]))


class HeterogeneousPipeline:
    """UNEVEN pipeline stages: arbitrary gluon blocks placed on distinct
    devices (reference docs/faq/model_parallel_lstm.md — embed, LSTM
    layers and decoder on different devices with cross-device copies).

    Unlike :class:`GluonPipelineStack` (one shared stage program ppermuted
    SPMD-style, which requires structurally identical stages), each block
    here becomes its own ``ctx_group`` and the whole chain binds through
    ``PipelinedExecutor``: per-device jitted segment programs with
    explicit transfers. Microbatch overlap comes from XLA's per-device
    async dispatch queues — ``step()`` issues every microbatch's
    forward/backward before synchronizing, so device k runs microbatch m
    while device k+1 still runs m-1 (the GPipe schedule, scheduled by the
    runtime rather than by a traced loop).

    Usage::

        pipe = HeterogeneousPipeline(
            [embed_block, body_block, head_block],
            [mx.cpu(0), mx.cpu(1), mx.cpu(2)],
            sample, loss=gluon.loss.SoftmaxCrossEntropyLoss())
        for epoch in ...:
            loss = pipe.step(x_microbatches, y_microbatches, lr=0.1)
        pipe.write_back()      # trained values -> the gluon blocks
    """

    def __init__(self, blocks, contexts, sample, loss=None):
        from .. import symbol as sym_mod
        from .. import autograd
        from ..attribute import AttrScope
        from ..base import MXNetError
        from ..ndarray.ndarray import _unwrap, _wrap

        if len(blocks) != len(contexts):
            raise MXNetError(
                f"one context per stage: {len(blocks)} blocks vs "
                f"{len(contexts)} contexts")
        self._blocks = list(blocks)
        self._contexts = list(contexts)

        sample = jnp.asarray(sample)
        with autograd.pause():                 # materialize deferred params
            cur_a = _wrap(sample)
            for b in self._blocks:
                cur_a = b(cur_a)
                if isinstance(cur_a, (list, tuple)):
                    cur_a = cur_a[0]

        cur = sym_mod.Variable("data")
        group2ctx = {}
        for i, (b, c) in enumerate(zip(self._blocks, self._contexts)):
            gname = f"pp_stage{i}"
            group2ctx[gname] = c
            with AttrScope(ctx_group=gname):
                cur = b(cur)
                if isinstance(cur, (list, tuple)):
                    cur = cur[0]
        self._raw_symbol = cur        # pre-loss chain, used for inference
        shapes = {"data": tuple(sample.shape)}
        if loss is not None:
            with AttrScope(ctx_group=f"pp_stage{len(blocks) - 1}"):
                label = sym_mod.Variable("label")
                cur = loss(cur, label)

        self._pmap = {}
        for b in self._blocks:
            self._pmap.update({p.name: p for p in b.collect_params().values()})
        self._has_loss = loss is not None
        self._symbol = cur
        self._group2ctx = group2ctx
        self._shapes = shapes
        self._exec = None
        self._infer_exec = None
        self._infer_shape = None

    def _seed_executor(self, ex) -> None:
        """Seed an executor's params: from the current training executor
        when one exists (a rebind must carry trained values forward, not
        reset to the blocks' initial state), else from the gluon blocks."""
        from ..ndarray.ndarray import _unwrap
        src_args = self._exec.arg_dict if self._exec is not None else {}
        src_aux = self._exec.aux_dict if self._exec is not None else {}
        for dst, src in ((ex.arg_dict, src_args), (ex.aux_dict, src_aux)):
            for n, a in dst.items():
                if n in ("data", "label"):
                    continue
                if n in src:
                    a._set_data(src[n]._data)
                elif n in self._pmap:
                    a._set_data(_unwrap(self._pmap[n].data()))

    def _bind(self, data_shape, label_shape):
        shapes = {"data": tuple(data_shape)}
        if self._has_loss:
            shapes["label"] = tuple(label_shape)
        # inputs need no cotangents: step() never reads them, and under
        # grad_req='add' they would cost an extra accumulation per micro
        grad_req = {n: ("null" if n in ("data", "label") else "add")
                    for n in self._symbol.list_arguments()}
        ex = self._symbol.simple_bind(self._contexts[0], grad_req=grad_req,
                                      group2ctx=self._group2ctx, **shapes)
        self._seed_executor(ex)
        self._exec = ex
        self._bound_shapes = (tuple(data_shape),
                              tuple(label_shape) if label_shape else None)

    def forward(self, x):
        """Single-microbatch inference: the PRE-LOSS chain's predictions
        (whether or not a loss block was attached for training), read with
        the current trained weights."""
        from .. import nd
        x = nd.array(x) if not hasattr(x, "_data") else x
        if self._infer_exec is None or self._infer_shape != tuple(x.shape):
            self._infer_exec = self._raw_symbol.simple_bind(
                self._contexts[0], grad_req="null",
                group2ctx=self._group2ctx, data=tuple(x.shape))
            self._infer_shape = tuple(x.shape)
        self._seed_executor(self._infer_exec)
        self._infer_exec.forward(is_train=False, data=x)
        return self._infer_exec.outputs[0]

    def step(self, x_microbatches, y_microbatches, lr=0.05):
        """One GPipe step: accumulate grads over all microbatches (their
        stage programs overlap via async dispatch), then one SGD apply.
        Returns the mean scalar loss."""
        from .. import nd
        from ..base import MXNetError
        if not self._has_loss:
            raise MXNetError("step() needs a loss block at construction")
        n_micro = len(x_microbatches)
        x0 = jnp.asarray(x_microbatches[0])
        y0 = jnp.asarray(y_microbatches[0])
        if self._exec is None or self._bound_shapes != (tuple(x0.shape),
                                                        tuple(y0.shape)):
            self._bind(x0.shape, y0.shape)
        ex = self._exec
        for n in ex.grad_dict:
            g = ex.grad_dict[n]
            g._set_data(jnp.zeros_like(g._data))   # keeps device placement
        losses = []
        for xm, ym in zip(x_microbatches, y_microbatches):
            ex.forward(is_train=True, data=nd.array(jnp.asarray(xm)),
                       label=nd.array(jnp.asarray(ym)))
            losses.append(ex.outputs[0])
            ex.backward()       # grad_req='add' accumulates across micro
        for n, a in ex.arg_dict.items():
            if n in ("data", "label"):
                continue
            g = ex.grad_dict.get(n)
            if g is None:
                continue
            gd = jax.device_put(g._data, next(iter(a._data.devices())))
            a._set_data(a._data - (lr / n_micro) * gd)
        return float(sum(float(l.asnumpy().mean()) for l in losses) / n_micro)

    def write_back(self) -> None:
        """Trained executor values -> the originating gluon blocks,
        re-homed onto each parameter's own device (stage placement must
        not leak into the imperative blocks)."""
        for n, a in list(self._exec.arg_dict.items()) + \
                list(self._exec.aux_dict.items()):
            if n in self._pmap:
                home = self._pmap[n].list_ctx()[0].jax_device()
                self._pmap[n].data()._set_data(
                    jax.device_put(a._data, home))
