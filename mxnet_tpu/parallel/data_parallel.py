"""SPMD data-parallel training.

This is the TPU-native replacement for the reference's whole data-parallel
stack (SURVEY.md §2.3 row 1-2): DataParallelExecutorGroup batch slicing
(executor_group.py:281-310) + KVStore gradient reduction + per-device
optimizer updates collapse into ONE jitted XLA computation over a device
mesh: the batch arrives sharded on the 'dp' axis, XLA inserts the gradient
AllReduce over ICI (latency-hidden behind the backward pass — the reference's
priority-queue overlap, for free), and the optimizer update runs sharded.

The gluon net is captured through the same Symbol trace hybridize() uses;
parameters live as a pytree; after training, ``sync_to_net()`` writes back.
"""
from __future__ import annotations

import contextlib
import functools
import inspect
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError, logger
from ..executor import _GraphLowering
from ..ndarray import NDArray
from ..ndarray.ndarray import _unwrap, _wrap
from ..observability import attribution as _attribution
from ..observability import catalog as _telemetry
from ..observability import flight_recorder as _flight
from ..observability import memwatch as _memwatch
from ..observability import metrics as _metrics
from ..observability import spans as _spans
from ..observability import xcost as _xcost
from ..passes import manager as _passes
from ..resilience import recovery as _recovery
from .mesh import local_mesh

__all__ = ["DataParallelTrainer", "make_train_step", "sgd_momentum_init",
           "sgd_momentum_update"]


# ---- minimal fused optimizer rules usable inside the jitted step ----------
def sgd_momentum_init(params):
    return jax.tree_util.tree_map(jnp.zeros_like, params)


def sgd_momentum_update(params, grads, state, lr, momentum=0.9, wd=0.0):
    def upd(w, g, m):
        g = g + wd * w
        m_new = momentum * m - lr * g
        return w + m_new, m_new

    flat = jax.tree_util.tree_map(upd, params, grads, state)
    new_params = jax.tree_util.tree_map(lambda t: t[0], flat,
                                        is_leaf=lambda t: isinstance(t, tuple))
    new_state = jax.tree_util.tree_map(lambda t: t[1], flat,
                                       is_leaf=lambda t: isinstance(t, tuple))
    return new_params, new_state


@jax.jit
def _copy_tree(tree):
    """Every leaf copied into a buffer of its own (same placement)."""
    return jax.tree_util.tree_map(jnp.copy, tree)


def _shape_key(arrays):
    """Exact (shape, dtype) signature of a batch — the unit the AOT
    executable is keyed to, shared by _aot_key/aot_save/aot_load/step."""
    return [tuple(a.shape) + (str(a.dtype),) for a in arrays]


# ---- grad-anomaly guard (NaN/Inf + norm-spike skip inside the step) -------
def _guard_config(grad_guard):
    """Normalize the ``grad_guard`` ctor arg. None/False = off; True = NaN/
    Inf + spike detection with defaults; a dict overrides ``spike_factor``
    (0 disables spike detection, keeping only the NaN/Inf check),
    ``ema_decay`` and ``warmup`` (good steps before spikes can fire)."""
    if not grad_guard:
        return None
    g = dict(grad_guard) if isinstance(grad_guard, dict) else {}
    return {"spike_factor": float(g.get("spike_factor", 10.0)),
            "ema_decay": float(g.get("ema_decay", 0.99)),
            "warmup": int(g.get("warmup", 5))}


def _guard_init_state():
    return {"ema": jnp.zeros((), jnp.float32),
            "last_norm": jnp.zeros((), jnp.float32),
            "skips": jnp.zeros((), jnp.int32),
            "good": jnp.zeros((), jnp.int32),
            "steps": jnp.zeros((), jnp.int32),
            "last_skipped": jnp.zeros((), jnp.int32)}


def _guard_apply(cfg, gstate, gnorm, new_tree, old_tree):
    """Inside the jitted step: keep ``new_tree`` on a healthy step, fall
    back to ``old_tree`` (skip-step) when the gradient norm is NaN/Inf or
    spikes past ``spike_factor``× its EMA. Returns (tree, new_gstate, bad);
    extra keys riding in ``gstate`` (loss-scaler state, lr_scale) pass
    through untouched."""
    gnorm = gnorm.astype(jnp.float32)
    finite = jnp.isfinite(gnorm)
    if cfg["spike_factor"] > 0:
        warm = gstate["good"] >= cfg["warmup"]
        spike = jnp.logical_and(
            warm, gnorm > cfg["spike_factor"] * gstate["ema"])
    else:
        spike = jnp.zeros((), jnp.bool_)
    bad = jnp.logical_or(jnp.logical_not(finite), spike)
    tree = jax.tree_util.tree_map(
        lambda o, n: jnp.where(bad, o, n), old_tree, new_tree)
    d = cfg["ema_decay"]
    safe_norm = jnp.where(finite, gnorm, gstate["ema"])
    ema = jnp.where(
        bad, gstate["ema"],
        jnp.where(gstate["good"] == 0, safe_norm,
                  d * gstate["ema"] + (1.0 - d) * safe_norm))
    badi = bad.astype(jnp.int32)
    new_gstate = dict(gstate)
    new_gstate.update({"ema": ema, "last_norm": gnorm,
                       "skips": gstate["skips"] + badi,
                       "good": gstate["good"] + (1 - badi),
                       "steps": gstate["steps"] + 1,
                       "last_skipped": badi})
    return tree, new_gstate, bad


def _scaled_loss_run(raw_fn, rng, scale):
    """Innermost loss closure shared by both capture paths: mean f32 loss,
    multiplied by the live scale when one is threaded. The UNSCALED loss
    rides in the aux slot so the host always observes the true value."""
    def run(ins_):
        outs, aux_updates = raw_fn(ins_, rng)
        loss_ = jnp.mean(outs[0].astype(jnp.float32))
        if scale is None:
            return loss_, aux_updates
        return loss_ * scale, (aux_updates, loss_)
    return run


def _unscale_grads(grads, loss, aux_updates, scale, cast_f32):
    """Post-backward epilogue shared by both capture paths: recover the
    unscaled loss smuggled through aux and divide the f32 gradients by the
    scale (exact — the scale stays a power of two)."""
    if scale is not None:
        aux_updates, loss = aux_updates
        grads = {k: g.astype(jnp.float32) / scale for k, g in grads.items()}
    elif cast_f32:
        grads = {k: g.astype(jnp.float32) for k, g in grads.items()}
    return grads, loss, aux_updates


def _guard_scaler_apply(guard_cfg, scaler_cfg, gstate, grads,
                        new_tree, old_tree):
    """Guard + scaler epilogue shared by the fused step and the kv
    apply_step: skip-step on an anomalous gradient norm, then advance the
    in-trace scaler off the same norm (overflow = non-finite)."""
    import optax
    gnorm = optax.global_norm(grads)
    tree, gstate, bad = _guard_apply(guard_cfg, gstate, gnorm,
                                     new_tree, old_tree)
    if scaler_cfg is not None:
        overflow = jnp.logical_not(jnp.isfinite(gnorm))
        gstate = dict(gstate)
        gstate.update(_recovery.scaler_apply(
            scaler_cfg, gstate, overflow, bad))
    return tree, gstate


def _loss_predictions(loss_block) -> int:
    """How many of the net's outputs a loss block takes: the positional
    parameters of its ``hybrid_forward`` in front of ``label`` (one where it
    names none so, as ``TripletLoss``)."""
    forward = getattr(loss_block, "hybrid_forward", None)
    if forward is None:
        return 1
    names = list(inspect.signature(forward).parameters)[1:]
    return names.index("label") if "label" in names[1:] else 1


def _make_optax(optimizer: str, optimizer_params: Dict):
    import optax
    p = dict(optimizer_params or {})
    lr = p.pop("learning_rate", 0.01)
    wd = p.pop("wd", 0.0)
    name = optimizer.lower() if isinstance(optimizer, str) else optimizer
    if name == "sgd":
        mom = p.pop("momentum", 0.0)
        tx = optax.sgd(lr, momentum=mom if mom else None)
    elif name == "nag":
        tx = optax.sgd(lr, momentum=p.pop("momentum", 0.9), nesterov=True)
    elif name == "adam":
        tx = optax.adam(lr, b1=p.pop("beta1", 0.9), b2=p.pop("beta2", 0.999),
                        eps=p.pop("epsilon", 1e-8))
    elif name == "rmsprop":
        tx = optax.rmsprop(lr, decay=p.pop("gamma1", 0.9),
                           eps=p.pop("epsilon", 1e-8))
    elif name == "adagrad":
        tx = optax.adagrad(lr)
    else:
        raise MXNetError(f"fused path does not know optimizer {optimizer!r}; "
                         f"use gluon.Trainer for the full registry")
    if wd:
        tx = optax.chain(optax.add_decayed_weights(wd), tx)
    return tx


class DataParallelTrainer:
    """Jitted whole-step data-parallel trainer for a Gluon net.

    Usage::

        mesh = parallel.auto_mesh()            # all devices on 'dp'
        step = parallel.DataParallelTrainer(net, loss_fn, 'sgd',
                                            {'learning_rate': 0.1}, mesh=mesh)
        loss = step.step(x, y)                 # x, y: global batch
        step.sync_to_net()                     # write back into net params
    """

    def __init__(self, net, loss, optimizer="sgd", optimizer_params=None,
                 mesh: Optional[Mesh] = None, data_axis: str = "dp",
                 compute_dtype=None, donate: bool = True, kvstore=None,
                 remat=None, grad_guard=None, loss_scaling=None,
                 dynamic_lr_scale: bool = False, step_attribution=None,
                 passes=None, grad_reduce: str = "all_reduce",
                 grad_reduce_dtype=None, bucket_bytes: Optional[int] = None,
                 compression=None):
        self._net = net
        self._loss_block = loss
        # graph-pass pipeline run over the captured symbol graph BEFORE
        # lowering (mxnet_tpu.passes): the measured perf levers — NHWC
        # layout propagation, space-to-depth stem, constant folding,
        # fusion-friendly reordering — as automatic defaults.  None =
        # MXNET_PASSES-configured default pipeline; False = off (the
        # captured graph is bitwise what it was before this framework
        # existed); a PassManager / spec string = custom.  Re-homed
        # parameter layouts are handled transparently: the trainer applies
        # the recorded value transforms at capture and inverts them in
        # sync_to_net, so the gluon net keeps its original layout.
        self._passes = _passes.resolve(passes)
        self._pass_result = None
        self._pass_info: Dict[str, Any] = {}
        if mesh is None and kvstore is not None:
            # hybrid mode: the jitted step spans only THIS process's devices
            # (the kvstore is the cross-process channel), so the mesh must
            # be local — a global mesh would make XLA itself the channel
            mesh = local_mesh(data_axis, devices=jax.local_devices())
        self._mesh = mesh or local_mesh(data_axis)
        self._axis = data_axis
        self._compute_dtype = (jnp.dtype(compute_dtype)
                               if compute_dtype is not None else None)
        # rematerialization of the forward during backward — the lever
        # that lets batch 512 fit without XLA spilling (reference
        # MXNET_BACKWARD_DO_MIRROR, graph_executor.cc:232). None = keep
        # all activations; "full" = recompute everything (max memory
        # savings, ~1.3x FLOPs); "dots" = keep matmul outputs only; or
        # pass any jax.checkpoint_policies callable.
        if remat in (None, "none"):
            self._remat_policy = False
        elif remat == "full":
            self._remat_policy = None
        elif remat == "dots":
            self._remat_policy = \
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        elif callable(remat):
            self._remat_policy = remat
        else:
            raise MXNetError(f"unknown remat mode {remat!r}")
        self._remat = remat not in (None, "none")
        self._remat_mode = remat
        # ---- communication-optimization levers (scale-out path) ----------
        # grad_reduce: how the cross-chip gradient reduction runs.
        #   "all_reduce"      (default) XLA's implicit AllReduce; params and
        #                     optimizer state replicated on every chip.
        #   "reduce_scatter"  ZeRO-1 sharded optimizer: gradients are
        #                     reduce-scattered over the data axis, the
        #                     optimizer update runs on each chip's 1/N
        #                     parameter shard (optimizer state LIVES sharded
        #                     — per-chip opt-state HBM shrinks N x), and the
        #                     updated params all-gather back to replication.
        #                     Parameters/state leaves whose leading dim does
        #                     not tile the mesh stay replicated (all-reduce).
        self._grad_reduce = str(grad_reduce or "all_reduce")
        if self._grad_reduce not in ("all_reduce", "reduce_scatter"):
            raise MXNetError(
                f"unknown grad_reduce mode {grad_reduce!r} "
                "(want 'all_reduce' or 'reduce_scatter')")
        # grad_reduce_dtype: the dtype gradients travel in through the
        # reduction (bf16 halves the collective bytes); the unsharded
        # master math stays f32 — grads are cast back before the optimizer
        # consumes them (accumulate-in-f32 semantics, tolerance-tested).
        self._grad_reduce_dtype = None
        if grad_reduce_dtype not in (None, "none", "float32", "f32"):
            alias = {"bf16": "bfloat16", "fp16": "float16"}
            dt = jnp.dtype(alias.get(str(grad_reduce_dtype),
                                     grad_reduce_dtype))
            if not jnp.issubdtype(dt, jnp.floating) or \
                    dt == jnp.dtype(jnp.float64):
                raise MXNetError(
                    f"grad_reduce_dtype must be a sub-f32 float "
                    f"(bfloat16/float16), got {grad_reduce_dtype!r}")
            if dt != jnp.dtype(jnp.float32):
                self._grad_reduce_dtype = dt
        # bucket_bytes: fuse small gradients into flat buckets of this many
        # bytes before the reduction (one collective per bucket instead of
        # one per tensor) — the in-trace twin of collectives.
        # bucketed_allreduce, sharing its bucket_assignment rule. An
        # all-reduce-path lever: the ZeRO path already reduces per-shard.
        self._bucket_bytes = None
        if bucket_bytes not in (None, 0):
            if self._grad_reduce == "reduce_scatter":
                raise MXNetError(
                    "bucket_bytes= is an all_reduce-path lever; "
                    "grad_reduce='reduce_scatter' fuses its own per-leaf "
                    "reduce-scatters (drop one of the two)")
            if kvstore is not None:
                # the kv path pushes gradients per key and the kvstore does
                # its own aggregation; a silently-inert lever would stamp
                # false provenance into comm_config()/tuner rows
                raise MXNetError(
                    "bucket_bytes= applies to the fused in-XLA gradient "
                    "reduction; the kvstore path aggregates with "
                    "MXNET_UPDATE_AGGREGATION_SIZE instead (drop one of "
                    "the two)")
            self._bucket_bytes = int(bucket_bytes)
            if self._bucket_bytes <= 0:
                raise MXNetError(f"bucket_bytes must be positive, got "
                                 f"{bucket_bytes!r}")
        # compression: 2-bit error-feedback gradient compression on the
        # kvstore wire (GradientCompression; reference
        # gradient_compression.cc). A WIRE lever: the compiled programs are
        # untouched, so it deliberately stays out of the AOT key.
        self._compression_params = None
        if compression:
            if kvstore is None:
                raise MXNetError(
                    "compression= rides the kvstore gradient wire; pass "
                    "kvstore= (the fused in-XLA collectives have no "
                    "host-codec hook) or drop compression")
            from ..gradient_compression import GradientCompression
            if isinstance(compression, GradientCompression):
                params = {"type": compression.type,
                          "threshold": compression.threshold}
            else:
                params = dict(compression)
            kvstore.set_gradient_compression(params)
            self._compression_params = params
        # per-leaf ZeRO sharding decisions, derived at capture time
        self._zero_shard: Dict[str, bool] = {}
        self._opt_specs = None
        # recorded for the AOT key: lr/momentum/wd are baked into the
        # compiled executable as constants, so a blob from different
        # hyperparameters must never be silently reused
        self._opt_desc = (str(optimizer),
                          tuple(sorted((str(k), repr(v)) for k, v in
                                       (optimizer_params or {}).items())))
        self._tx = _make_optax(optimizer, optimizer_params)
        # grad-anomaly guard: when enabled, the jitted step computes the
        # global grad norm, skips the update on NaN/Inf or spike steps
        # (params/aux/opt_state pass through unchanged) and counts skips in
        # a small state tree that rides along the step like opt_state. The
        # counters surface through anomaly_stats() / Monitor.install_trainer.
        self._guard_cfg = _guard_config(grad_guard)
        # in-trace dynamic loss scaling (ISSUE 5 tentpole): LossScaler
        # semantics as functional device-scalar state riding in the guard
        # state tree — the loss is multiplied by the live scale before the
        # backward and the f32 grads unscaled after (exact: scale stays a
        # power of two), overflow halves the scale and skips the update,
        # growth_interval clean steps double it. Everything happens INSIDE
        # the jitted step: zero per-step host syncs (contrast
        # contrib.amp.init_trainer's imperative bool(overflow) read).
        self._scaler_cfg = _recovery.scaler_config(loss_scaling)
        if self._scaler_cfg is not None and self._guard_cfg is None:
            # the scaler's overflow response IS the guard's skip-step; a
            # scaler without a guard would rescale but never skip. Any
            # explicit off spelling (False/0/{}) is rejected — only the
            # unset default (None) silently upgrades to guard-on
            if grad_guard is not None:
                raise MXNetError(
                    "loss_scaling= requires the grad-anomaly guard; drop "
                    "grad_guard=%r or disable loss scaling" % (grad_guard,))
            self._guard_cfg = _guard_config(True)
        # a device-scalar multiplier on the optimizer update (recovery
        # ladder's LR backoff lever — lr itself is baked into the compiled
        # executable). Off by default so the step HLO is untouched.
        self._dynamic_lr = bool(dynamic_lr_scale)
        self._guard_state = None
        self._step_fn = None
        self._n_inputs = None
        self._param_names = None
        self._params = None
        self._aux = None
        self._opt_state = None
        self._rng_counter = 0
        self._donate = donate
        # hybrid multi-host mode (reference dist_sync_device: fast intra-node
        # reduce + PS inter-node): the fused step computes LOCAL grads over
        # this process's mesh, the kvstore moves them across processes
        # (optionally 2-bit-compressed on the wire), a second jitted program
        # applies the optimizer. kvstore=None keeps the fully-fused
        # single-program path where XLA's allreduce spans the whole mesh.
        self._kv = kvstore
        self._kv_inited = False
        self._grad_fn = None
        self._apply_fn = None
        self._compiled = None   # AOT-deserialized executable (aot_load)
        self._compiled_shapes = None  # exact input shapes the AOT exe accepts
        # step-time attribution (ISSUE 6): host-side decomposition of the
        # step cadence into dispatch/transfer/feed-stall/... buckets plus
        # live MFU/device-util gauges. Pure bookkeeping around the step —
        # the jitted program and its HLO are untouched (tier-1 guards it).
        self._attr_cfg = _attribution.attribution_config(step_attribution)
        self._prev_entry = None     # perf_counter entry of the last step
        _dev0 = self._mesh.devices.ravel()[0]
        self._perf = (_attribution.StepAttribution(
            self._attr_cfg, device_kind=_dev0.device_kind,
            n_devices=int(self._mesh.devices.size))
            if self._attr_cfg is not None else None)
        # per-executable XLA cost capture (observability.xcost): FLOPs /
        # bytes / roofline row persisted once per compiled step when the
        # ledger is enabled (MXNET_PERF_LEDGER); also the flops source for
        # the live MFU gauge
        self._flops_per_step = None
        self._cost_rows: Dict[Tuple, Any] = {}

    # ------------------------------------------------------------- passes
    def _run_passes(self, loss_sym, data_syms, init_arrays):
        """Run the configured graph-pass pipeline over the captured loss
        graph (mxnet_tpu.passes).  Input shapes come from the init-view
        sample batch (the NET's layout); parameter shapes from the
        materialized gluon params.  A pipeline failure never kills a
        capture — the unrewritten graph is used and a warning logged."""
        from ..passes.layout import is_nchw_conv
        self._pass_result = None
        data_names = [s.name for s in data_syms] + ["__label"]
        nchw_convs = sum(1 for n in loss_sym.topo_nodes()
                         if not n.is_var and is_nchw_conv(n))
        self._pass_info = {
            "nchw_convs": nchw_convs,
            "layout_enabled": (self._passes is not None
                               and "layout" in self._passes.names)}
        if self._passes is None:
            return loss_sym
        shapes = {}
        pnames = set()
        for p in self._net.collect_params().values():
            pnames.add(p.name)
            if p.shape and all(int(d) > 0 for d in p.shape):
                shapes[p.name] = tuple(int(d) for d in p.shape)
        if init_arrays is not None:
            for name, a in zip(data_names, init_arrays):
                if hasattr(a, "shape"):
                    shapes[name] = tuple(int(d) for d in a.shape)
        try:
            res = self._passes.run(loss_sym, shapes=shapes,
                                   input_vars=data_names,
                                   param_names=pnames)
        except Exception as e:
            logger.warning("graph-pass pipeline failed; capturing the "
                           "unrewritten graph: %r", e)
            return loss_sym
        self._pass_info["rewrites"] = dict(res.counts)
        if res.total_rewrites == 0:
            return loss_sym
        self._pass_result = res
        return res.symbol

    def _placed_param(self, name, value):
        """A net parameter's value as the REWRITTEN graph expects it: the
        pass pipeline may have re-homed the variable (NHWC weight), in
        which case the recorded transform maps the net's value into the
        captured layout (sync_to_net applies the inverse)."""
        if self._pass_result is None or \
                name not in self._pass_result.var_transforms:
            # a copy, never the net's own buffer: the step donates its
            # state, and placing an array on a mesh that holds its device
            # aliases it, so the first step would delete the net's value
            return jax.device_put(value, may_alias=False)
        return jnp.asarray(
            self._pass_result.transform_var(name, jax.device_get(value)))

    def passes_provenance(self) -> Dict[str, Any]:
        """Which graph passes this trainer runs and what they rewrote —
        stamped into bench rows so perf baselines are attributable (one
        schema with Module: passes.manager.provenance)."""
        return _passes.provenance(self._passes, self._pass_result,
                                  self._pass_info.get("rewrites"))

    # ------------------------------------------------------------- capture
    @_spans.span("trainer.capture")
    def _capture(self, n_inputs: int, sample_arrays=None):
        """Build the step's program from the net. Children of the span:
        ``trainer.capture.forward`` (the op-by-op forward that settles
        deferred shapes), ``trainer.capture.graph`` (symbolic trace, passes,
        lowering), ``trainer.capture.state`` (params, statistics and
        optimizer state placed on the mesh)."""
        from .. import symbol as sym_mod
        from .. import autograd
        if _metrics.enabled():
            _telemetry.CAPTURES_TOTAL.inc()
            # the live device-set gauge elastic resumes reconcile against
            _telemetry.ACTIVE_DEVICES.set(int(self._mesh.devices.size))
        # a re-capture rebuilds params/opt_state from the net; any loaded
        # executable is keyed to the OLD pytree/placement and must not be
        # re-entered afterwards — and any captured cost rows describe the
        # old executable
        self._compiled = None
        self._compiled_shapes = None
        self._cost_rows = {}
        self._flops_per_step = None
        init_arrays = sample_arrays
        if sample_arrays is not None:
            # materialize deferred-init params with one tiny host forward;
            # the sample batch may arrive pre-sharded over the mesh (e.g.
            # from DeviceFeedIter) — uncommit it to host first so the
            # imperative forward isn't pinned to mismatched devices.
            # Under a passes pipeline with input_layout="NHWC" the caller
            # feeds channel-last batches to an NCHW-built net: init_view
            # permutes rank-4 arrays back for the init forward only.
            if self._passes is not None:
                init_arrays = self._passes.init_view(sample_arrays)
            with _spans.span("trainer.capture.forward"), autograd.pause():
                self._net(*[_wrap(jnp.asarray(jax.device_get(a)))
                            for a in init_arrays[:-1]])
        with _spans.span("trainer.capture.graph"):
            data_syms = [sym_mod.Variable(f"__data{i}")
                         for i in range(n_inputs - 1)]
            label_sym = sym_mod.Variable("__label")
            out = self._net(*data_syms)
            # the loss gets as many of the net's outputs as it takes before
            # the label: a loss of one prediction the first, as ever
            outs = list(out) if isinstance(out, (list, tuple)) else [out]
            outs = outs[:_loss_predictions(self._loss_block)]
            loss_sym = self._loss_block(*outs, label_sym)
            loss_sym = self._run_passes(loss_sym, data_syms, init_arrays)
            lowering = _GraphLowering(loss_sym)
            raw_fn = lowering.lower(is_train=True)
            if _metrics.enabled():
                _telemetry.LOSS_INPUTS.set(len(outs))
        # a block that calls a child several times makes a variable node of
        # the same name per call: every shared parameter once, in first order
        var_names = list(dict.fromkeys(
            n.name for n in loss_sym.topo_nodes() if n.is_var))
        data_names = [s.name for s in data_syms] + ["__label"]
        pmap = {p.name: p for p in self._net.collect_params().values()
                if p.name in var_names}
        param_names = [n for n in var_names
                       if n in pmap and pmap[n].grad_req != "null"]
        aux_names = [n for n in var_names if n in pmap
                     and pmap[n].grad_req == "null"]
        self._param_names = param_names
        self._aux_names = aux_names
        self._pmap = pmap
        with _spans.span("trainer.capture.state"):
            self._params = {n: self._placed_param(n, _unwrap(pmap[n].data()))
                            for n in param_names}
            self._aux = {n: self._placed_param(n, _unwrap(pmap[n].data()))
                         for n in aux_names}
            self._opt_state = self._tx.init(self._params)
        self._guard_state = _guard_init_state()
        if self._scaler_cfg is not None:
            self._guard_state.update(
                _recovery.scaler_init_state(self._scaler_cfg))
        if self._dynamic_lr:
            self._guard_state["lr_scale"] = jnp.ones((), jnp.float32)

        mesh, axis = self._mesh, self._axis
        repl = NamedSharding(mesh, P())
        dataspec = NamedSharding(mesh, P(axis))
        # the mesh the step is partitioned over, named while the net is
        # differentiated: an op that holds a Mosaic kernel (which jit cannot
        # partition by itself) sees from it how its batch will be split.
        # Not where another axis than the data's has devices: the name would
        # not say which axis holds the batch
        over_mesh = functools.partial(jax.sharding.use_abstract_mesh,
                                      mesh.abstract_mesh) \
            if mesh.size == mesh.shape[axis] else contextlib.nullcontext
        cdtype = self._compute_dtype
        tx = self._tx
        guard_cfg = self._guard_cfg
        scaler_cfg = self._scaler_cfg
        # a key (str) rather than a bool flag: closure-captured Python
        # scalars are exactly what mxlint MXL-T202 flags in our own step
        lr_key = "lr_scale" if self._dynamic_lr else None

        # ---- comm-optimization epilogue (grad_reduce / dtype / buckets) --
        # ZeRO-1 shardability: a leaf shards over the data axis when its
        # leading dim tiles the mesh; everything else stays replicated.
        # Optimizer-state leaves mirror their param's shape (sgd momentum,
        # adam mu/nu), so the same shape rule lands the same verdict on a
        # param and its state; scalar counts stay replicated. The divisor
        # is the DATA axis extent — on a multi-axis mesh only 'dp' shards.
        n_dev = int(mesh.shape[axis])
        shard1 = NamedSharding(mesh, P(axis))
        g_mode = self._grad_reduce

        def _zero_ok(v):
            shp = tuple(getattr(v, "shape", ()))
            return (g_mode == "reduce_scatter" and len(shp) >= 1
                    and int(shp[0]) > 0 and int(shp[0]) % n_dev == 0)

        self._zero_shard = {n: _zero_ok(v) for n, v in self._params.items()}
        self._opt_specs = jax.tree_util.tree_map(
            lambda l: shard1 if _zero_ok(l) else repl, self._opt_state)
        if g_mode == "reduce_scatter":
            # the optimizer state LIVES sharded between steps — per-chip
            # opt-state HBM is 1/N of the replicated baseline from step 0
            self._opt_state = jax.tree_util.tree_map(
                jax.device_put, self._opt_state, self._opt_specs)
        zshard = dict(self._zero_shard)
        rdt = self._grad_reduce_dtype
        bucket_names = None
        if self._bucket_bytes:
            from .collectives import bucket_assignment
            itemsize = (jnp.dtype(rdt).itemsize if rdt is not None else 4)
            sizes = [int(np.prod(self._params[n].shape)) * itemsize
                     for n in param_names]
            bucket_names = [[param_names[i] for i in b] for b in
                            bucket_assignment(sizes, self._bucket_bytes)]

        def _shard_tree(t, sp):
            return {k: (jax.lax.with_sharding_constraint(v, sp)
                        if zshard[k] else v) for k, v in t.items()}

        def _reduce_grads(grads):
            """Comm epilogue on the freshly-unscaled f32 grads: cast to the
            wire dtype, fuse buckets (one collective per flat bucket —
            collectives.bucket_assignment order), anchor the ZeRO
            reduce-scatter, cast back to f32 (accumulate-in-f32: the
            master math downstream never sees the wire dtype)."""
            if rdt is not None:
                grads = {k: v.astype(rdt) for k, v in grads.items()}
            if bucket_names is not None:
                out = dict(grads)
                for names_ in bucket_names:
                    flat = jnp.concatenate([grads[n].ravel()
                                            for n in names_]) \
                        if len(names_) > 1 else grads[names_[0]].ravel()
                    flat = jax.lax.with_sharding_constraint(flat, repl)
                    off = 0
                    for n in names_:
                        sz = grads[n].size
                        out[n] = flat[off:off + sz].reshape(grads[n].shape)
                        off += sz
                grads = out
            if g_mode == "reduce_scatter":
                # the constraint sits on the WIRE-dtype value so XLA's
                # implicit psum lowers to a reduce-scatter of those bytes
                grads = _shard_tree(grads, shard1)
            if rdt is not None:
                grads = {k: v.astype(jnp.float32) for k, v in grads.items()}
            return grads

        def _opt_apply(grads, opt_state, params, gstate):
            """Optimizer update bracketed by the ZeRO shard/gather: the
            update runs on each chip's 1/N shard of grads/params/state and
            the fresh params all-gather back to replication. Shared by the
            fused step and the kv apply_step so the two paths cannot
            drift."""
            import optax
            if g_mode == "reduce_scatter":
                grads = _shard_tree(grads, shard1)
                params = _shard_tree(params, shard1)
            updates, new_opt_state = tx.update(grads, opt_state, params)
            if lr_key is not None:
                lrs = gstate[lr_key]
                updates = jax.tree_util.tree_map(lambda u: u * lrs, updates)
            new_params = optax.apply_updates(params, updates)
            if g_mode == "reduce_scatter":
                new_params = _shard_tree(new_params, repl)
            return new_params, new_opt_state

        def train_step(params, aux, opt_state, gstate, rng, *data):
            inputs = {}
            if cdtype is not None:
                inputs.update({k: v.astype(cdtype) for k, v in params.items()})
            else:
                inputs.update(params)
            inputs.update(aux)
            for name, x in zip(data_names, data):
                inputs[name] = x.astype(cdtype) if (
                    cdtype is not None and jnp.issubdtype(x.dtype, jnp.floating)
                    and name != "__label") else x

            # live loss scale (a traced scalar from the state tree): the
            # loss is scaled BEFORE the backward so tiny low-precision
            # grads stay representable, and the f32 grads are unscaled
            # after. Scale transitions are powers of two, so in f32 the
            # round trip is bitwise-exact.
            scale = gstate["loss_scale"] if scaler_cfg is not None else None

            def loss_of(p):
                ins = dict(inputs)
                if cdtype is not None:
                    ins.update({k: v.astype(cdtype) for k, v in p.items()})
                else:
                    ins.update(p)
                run = _scaled_loss_run(raw_fn, rng, scale)
                if self._remat:
                    run = jax.checkpoint(run, policy=self._remat_policy)
                return run(ins)

            with over_mesh():
                (loss, aux_updates), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(params)
            grads, loss, aux_updates = _unscale_grads(
                grads, loss, aux_updates, scale, cdtype is not None)
            grads = _reduce_grads(grads)
            new_params, new_opt_state = _opt_apply(grads, opt_state,
                                                   params, gstate)
            new_aux = dict(aux)
            for k, v in aux_updates.items():
                if k in new_aux:
                    new_aux[k] = v.astype(new_aux[k].dtype)
            if guard_cfg is not None:
                # skip-step: an anomalous gradient keeps params, aux AND
                # opt_state at their pre-step values (a NaN forward would
                # poison batchnorm running stats too)
                (new_params, new_aux, new_opt_state), gstate = \
                    _guard_scaler_apply(guard_cfg, scaler_cfg, gstate, grads,
                                        (new_params, new_aux, new_opt_state),
                                        (params, aux, opt_state))
            return new_params, new_aux, new_opt_state, gstate, loss

        gstate_spec = {k: repl for k in self._guard_state}
        in_shardings = (jax.tree_util.tree_map(lambda _: repl, self._params),
                        {k: repl for k in self._aux},
                        self._opt_specs,
                        gstate_spec,
                        repl) + tuple(dataspec for _ in data_names)
        out_shardings = (jax.tree_util.tree_map(lambda _: repl, self._params),
                         {k: repl for k in self._aux},
                         self._opt_specs,
                         gstate_spec,
                         repl)
        donate = (0, 1, 2, 3) if self._donate else ()
        self._step_fn = jax.jit(train_step, in_shardings=in_shardings,
                                out_shardings=out_shardings,
                                donate_argnums=donate)
        self._n_inputs = n_inputs
        # the first step must see its state where every later step does (on
        # the mesh, as the step's own outputs are): state still sitting on
        # the net's device has another type to jit, and the whole step would
        # be traced and compiled a second time at step two
        with _spans.span("trainer.capture.state"):
            self._place_state()

        if self._kv is not None:
            # with a scaler, grad_step takes the live scale as an extra
            # scalar arg: the backward runs on the SCALED loss, and the
            # grads are unscaled to f32 before they touch the wire, so the
            # kvstore sums plain gradients and every worker (whose state is
            # identical) applies the same scale transition in apply_step.
            def grad_step(params, aux, rng, *data, scale=None):
                inputs = dict(aux)
                for name, x in zip(data_names, data):
                    inputs[name] = x.astype(cdtype) if (
                        cdtype is not None
                        and jnp.issubdtype(x.dtype, jnp.floating)
                        and name != "__label") else x

                def loss_of(p):
                    ins = dict(inputs)
                    if cdtype is not None:
                        ins.update({k: v.astype(cdtype)
                                    for k, v in p.items()})
                    else:
                        ins.update(p)
                    run = _scaled_loss_run(raw_fn, rng, scale)
                    if self._remat:
                        run = jax.checkpoint(run, policy=self._remat_policy)
                    return run(ins)

                with over_mesh():
                    (loss, aux_updates), grads = jax.value_and_grad(
                        loss_of, has_aux=True)(params)
                # kv grads always go to f32 before they touch the wire
                grads, loss, aux_updates = _unscale_grads(
                    grads, loss, aux_updates, scale, True)
                new_aux = dict(aux)
                for k, v in aux_updates.items():
                    if k in new_aux:
                        new_aux[k] = v.astype(new_aux[k].dtype)
                return grads, new_aux, loss

            if scaler_cfg is not None:
                def scaled_grad_step(params, aux, scale, rng, *data):
                    return grad_step(params, aux, rng, *data, scale=scale)

            def apply_step(params, opt_state, gstate, grads):
                new_params, new_opt_state = _opt_apply(grads, opt_state,
                                                       params, gstate)
                if guard_cfg is not None:
                    # guard the synced (cross-worker summed) gradient: a NaN
                    # from ANY worker poisons the sum, so the skip decision
                    # is naturally global. aux was already updated by
                    # grad_step — on the hybrid path only params/opt_state
                    # are protected.
                    (new_params, new_opt_state), gstate = \
                        _guard_scaler_apply(guard_cfg, scaler_cfg, gstate,
                                            grads,
                                            (new_params, new_opt_state),
                                            (params, opt_state))
                return new_params, new_opt_state, gstate

            gspec = jax.tree_util.tree_map(lambda _: repl, self._params)
            # one jit call for both variants: the scaled wrapper only adds
            # a replicated scale scalar ahead of rng
            scaled = scaler_cfg is not None
            self._grad_fn = jax.jit(
                scaled_grad_step if scaled else grad_step,
                in_shardings=(gspec, {k: repl for k in self._aux})
                + ((repl,) if scaled else ()) + (repl,)
                + tuple(dataspec for _ in data_names),
                out_shardings=(gspec, {k: repl for k in self._aux},
                               repl))
            self._apply_fn = jax.jit(
                apply_step,
                in_shardings=(gspec, self._opt_specs, gstate_spec, gspec),
                out_shardings=(gspec, self._opt_specs, gstate_spec),
                donate_argnums=(0, 1, 2) if self._donate else ())

    # ---------------------------------------------------- AOT serialization
    # The compiled fused step can be serialized and reloaded by a LATER
    # process, skipping XLA compilation entirely (the reference's analogue
    # is the cuDNN algo registry persisting autotune results; here we keep
    # the whole executable).
    def _aot_key(self, arrays):
        import jax as _jax
        dev = self._mesh.devices.ravel()[0]
        return {
            "jax": _jax.__version__,
            "device_kind": dev.device_kind,
            "n_devices": int(self._mesh.devices.size),
            "in_shapes": _shape_key(arrays),
            "compute_dtype": str(self._compute_dtype),
            "remat": str(getattr(self, "_remat_mode", None)),
            "optimizer": self._opt_desc,
            # guard thresholds are baked constants in the executable: a blob
            # compiled with different anomaly policy must not be reused
            "grad_guard": repr(sorted(self._guard_cfg.items())
                               if self._guard_cfg else None),
            # ditto for the scaler policy constants and the lr_scale state
            # key — both change the compiled program
            "loss_scaling": repr(sorted(self._scaler_cfg.items())
                                 if self._scaler_cfg else None),
            "dynamic_lr_scale": self._dynamic_lr,
            # the pass pipeline rewrites the captured graph (and may
            # re-home the parameter pytree): a blob compiled under a
            # different pipeline must not be reused (the StableHLO digest
            # is the strong check; this is the cheap first filter)
            "passes": repr((self._passes.names, self._passes.input_layout)
                           if self._passes is not None else None),
            # the comm levers change the compiled programs (collective
            # pattern, wire dtype, bucket fusion) AND the opt-state
            # placement the executable expects; kvstore wire compression
            # deliberately absent — it never enters the executable
            "grad_reduce": self._grad_reduce,
            "grad_reduce_dtype": str(self._grad_reduce_dtype),
            "bucket_bytes": self._bucket_bytes,
        }

    def _lowered_digest(self, lowered) -> str:
        """Hash of the FULL lowered computation (StableHLO text): the model
        graph, loss, optimizer constants — everything baked into the
        executable. This is what actually guarantees a blob matches; the
        config fields in the key are a cheap first filter."""
        import hashlib
        return hashlib.sha256(
            lowered.as_text().encode("utf-8", "replace")).hexdigest()

    def aot_save(self, path, *data) -> None:
        """Compile the fused step for this batch spec and serialize the
        executable (+ a compatibility key) to ``path``."""
        import os
        import pickle
        from jax.experimental.serialize_executable import serialize
        arrays = [_unwrap(d) if isinstance(d, NDArray) else jnp.asarray(d)
                  for d in data]
        if self._step_fn is None or self._n_inputs != len(arrays):
            self._capture(len(arrays), sample_arrays=arrays)
        dataspec = NamedSharding(self._mesh, P(self._axis))
        arrays = [jax.device_put(a, dataspec) for a in arrays]
        rng = jax.random.PRNGKey(0)
        lowered = self._step_fn.lower(
            self._params, self._aux, self._opt_state, self._guard_state,
            rng, *arrays)
        digest = self._lowered_digest(lowered)
        compiled = lowered.compile()
        if _metrics.enabled() and _xcost.enabled():
            # aot_save IS the compile: capture the ledger row here with the
            # compiled executable attached (adds XLA's memory analysis)
            dev = self._mesh.devices.ravel()[0]
            row = _xcost.capture(
                lowered, key=self._aot_key(arrays), fingerprint=digest,
                label="DataParallelTrainer.aot_save",
                device_kind=dev.device_kind, platform=dev.platform,
                n_devices=int(self._mesh.devices.size), compiled=compiled)
            if row is not None:
                self._cost_rows[tuple(_shape_key(arrays))] = row
                if row.get("flops"):
                    self._flops_per_step = float(row["flops"])
        ser, in_tree, out_tree = serialize(compiled)
        tmp = "%s.tmp.%d" % (path, os.getpid())
        with open(tmp, "wb") as f:
            pickle.dump({"key": self._aot_key(arrays), "digest": digest,
                         "exe": ser, "in_tree": in_tree,
                         "out_tree": out_tree}, f)
        os.replace(tmp, path)
        self._compiled = compiled
        self._compiled_shapes = _shape_key(arrays)
        self._place_state()

    def aot_load(self, path, *data) -> bool:
        """Load a serialized step executable; returns False (and stays on
        the jit path) if the blob is missing or its key does not match.

        Trust boundary: the blob is unpickled BEFORE the digest check, so
        ``path`` must point at a cache this process itself wrote — never
        at untrusted bytes. An
        attacker who can write the cache file can already write the code
        that loads it, so the boundary is the filesystem, not the format."""
        import os
        import pickle
        from jax.experimental.serialize_executable import deserialize_and_load
        if not os.path.exists(path):
            return False
        arrays = [_unwrap(d) if isinstance(d, NDArray) else jnp.asarray(d)
                  for d in data]
        try:
            with open(path, "rb") as f:
                blob = pickle.load(f)
        except Exception:
            return False
        if self._step_fn is None or self._n_inputs != len(arrays):
            self._capture(len(arrays), sample_arrays=arrays)
        if blob.get("key") != self._aot_key(arrays):
            return False
        # the executable is keyed to the exact input pytree (param names!);
        # a structural mismatch must be a clean refusal here, not a
        # confusing TypeError at the first step
        my_tree = jax.tree_util.tree_structure(
            ((self._params, self._aux, self._opt_state, self._guard_state,
              jax.random.PRNGKey(0)) + tuple(arrays), {}))
        if str(my_tree) != str(blob["in_tree"]):
            return False
        # strongest check: the blob must come from THIS lowered computation
        # (model graph + loss + baked constants), not merely one with the
        # same shapes. Lowering is local tracing — seconds, not a compile.
        dataspec = NamedSharding(self._mesh, P(self._axis))
        placed = [jax.device_put(a, dataspec) for a in arrays]
        lowered = self._step_fn.lower(
            self._params, self._aux, self._opt_state, self._guard_state,
            jax.random.PRNGKey(0), *placed)
        if blob.get("digest") != self._lowered_digest(lowered):
            return False
        try:
            self._compiled = deserialize_and_load(
                blob["exe"], blob["in_tree"], blob["out_tree"])
        except Exception:
            return False
        self._compiled_shapes = _shape_key(arrays)
        self._place_state()
        return True

    def _place_state(self):
        """Pin params/aux/opt_state to their home shardings (params
        replicated; opt-state per-leaf — ZeRO leaves sharded over the data
        axis): unlike jit, a deserialized executable does not auto-reshard
        its inputs — and every restore path (checkpoint, rolling snapshot)
        funnels through here, so a ZeRO-sharded optimizer lands back
        sharded bitwise."""
        repl = NamedSharding(self._mesh, P())
        put = lambda t: jax.device_put(t, repl)  # noqa: E731
        self._params = jax.tree_util.tree_map(put, self._params)
        self._aux = jax.tree_util.tree_map(put, self._aux)
        if self._opt_specs is not None:
            self._opt_state = jax.tree_util.tree_map(
                jax.device_put, self._opt_state, self._opt_specs)
        else:
            self._opt_state = jax.tree_util.tree_map(put, self._opt_state)
        if self._guard_state is not None:
            self._guard_state = jax.tree_util.tree_map(put, self._guard_state)

    # ------------------------------------------------------------- stepping
    def step(self, *data) -> float:
        """One fused fwd+bwd+allreduce+update step on a global batch.
        Returns the scalar loss (an async device value; float() to sync).

        Telemetry (``observability``): the step is the span ``trainer.step``
        (``unit=("step", n)``) over ``trainer.capture`` (first call and
        re-captures), ``trainer.put``, ``trainer.rng`` and
        ``trainer.enqueue``; the step-time gauges and the flight-recorder
        record are fed from those spans — all strictly host-side, OUTSIDE
        the jitted function, so the compiled HLO is identical with
        telemetry on or off, and nothing here syncs the device (the loss
        stays an async value; the recorder resolves it only at dump time).
        """
        with _spans.span("trainer.step",
                         unit=("step", self._rng_counter + 1)) as root:
            arrays = [_unwrap(d) if isinstance(d, NDArray) else jnp.asarray(d)
                      for d in data]
            if self._step_fn is None or self._n_inputs != len(arrays):
                self._capture(len(arrays), sample_arrays=arrays)
            dataspec = NamedSharding(self._mesh, P(self._axis))
            with _spans.span("trainer.put") as put:
                arrays = [jax.device_put(a, dataspec) for a in arrays]
            from .. import random as _random
            with _spans.span("trainer.rng"):
                rng = jax.random.fold_in(
                    jax.random.PRNGKey(_random.current_seed()),
                    self._rng_counter)
            self._rng_counter += 1
            if _metrics.enabled() and _xcost.enabled():
                # once per executable, BEFORE dispatch (params still alive):
                # lower + cost_analysis + persist the ledger row (host-side
                # metadata only; the compiled program is untouched)
                self._maybe_capture_cost(rng, arrays)
            with _spans.span("trainer.enqueue") as enqueue:
                loss = self._enqueue(rng, arrays)
            if enqueue.t1 is not None:
                self._step_telemetry(root, put, enqueue, arrays, loss)
        return loss

    def _enqueue(self, rng, arrays):
        """Call the step's program(s): enqueue cost, or back-pressure once
        the device's queue is full."""
        try:
            if self._kv is not None:
                return self._kv_step(rng, arrays)
            fn = self._step_fn
            if (self._compiled is not None
                    and _shape_key(arrays) == self._compiled_shapes):
                # the deserialized executable is shape-exact; a batch
                # with other shapes (e.g. a ragged final batch) takes
                # the jit path for that call only, keeping the
                # executable for exact matches
                fn = self._compiled
                rng = jax.device_put(rng, NamedSharding(self._mesh, P()))
            (self._params, self._aux, self._opt_state, self._guard_state,
             loss) = fn(self._params, self._aux, self._opt_state,
                        self._guard_state, rng, *arrays)
            return loss
        except Exception as e:
            # the trainer dispatch boundary: a device RESOURCE_EXHAUSTED
            # leaves forensics (mxtpu_oom.json) and re-raises typed;
            # every other failure passes through untouched
            oom = _memwatch.to_hbm_exhausted(e, context="trainer",
                                             trainer=self)
            if oom is not None:
                raise oom from e
            raise

    def _step_telemetry(self, root, put, enqueue, arrays, loss) -> None:
        """Feed the step's gauges from its spans. The step TIME is the
        entry-to-entry cadence: a step returns as soon as it is enqueued, and
        once the device's queue is full back-pressure makes the cadence the
        device's step time. The first step of a trainer has no cadence."""
        cadence = (root.t0 - self._prev_entry
                   if self._prev_entry is not None else None)
        self._prev_entry = root.t0
        samples = int(arrays[0].shape[0]) if (
            arrays and getattr(arrays[0], "ndim", 0)) else 0
        _telemetry.STEPS_TOTAL.inc()
        if samples:
            _telemetry.SAMPLES_TOTAL.inc(samples)
        if cadence:
            _telemetry.STEP_MS.observe(cadence * 1e3)
            if samples:
                _telemetry.SAMPLES_PER_SEC.set(samples / cadence)
        if self._perf is not None:
            # FLOPs are per-executable: resolve THIS signature's ledger
            # row (a second batch shape is a different program with
            # different FLOPs — MFU must never mix them)
            row = self._cost_rows.get(tuple(_shape_key(arrays)))
            self._flops_per_step = (
                float(row["flops"]) if row and row.get("flops") else None)
            # host-side decomposition + live MFU; the loss reference is
            # kept one step and polled non-blocking, never synced
            self._perf.observe(root.t0, enqueue.t1, cadence_s=cadence,
                               transfer_ms=put.ms, dispatch_ms=enqueue.ms,
                               loss_ref=loss,
                               flops_per_step=self._flops_per_step)
        # rng_counter just advanced: it IS the completed-step count
        # (ResilientTrainer.step_count tracks the same number)
        _flight.record_step(self._rng_counter, loss=loss,
                            step_ms=cadence * 1e3 if cadence else None)

    def _maybe_capture_cost(self, rng, arrays) -> None:
        """Persist this step's cost-ledger row (once per input signature).
        Lowering is local tracing — no compile, no device work — and the
        row is keyed by the same aot_key + StableHLO digest the AOT cache
        trusts. The fused path costs ``_step_fn``; the kv path costs the
        two programs it ACTUALLY runs (``_grad_fn`` + ``_apply_fn``,
        summed — the fused step never executes there and its fingerprint
        would name a nonexistent executable)."""
        key = tuple(_shape_key(arrays))
        if key in self._cost_rows:
            return
        self._cost_rows[key] = None       # one attempt per signature
        try:
            dev = self._mesh.devices.ravel()[0]
            common = dict(key=self._aot_key(arrays),
                          device_kind=dev.device_kind, platform=dev.platform,
                          n_devices=int(self._mesh.devices.size))
            mem_on = _memwatch.capture_enabled()
            if self._kv is None:
                lowered = self._step_fn.lower(
                    self._params, self._aux, self._opt_state,
                    self._guard_state, rng, *arrays)
                row = _xcost.capture(
                    lowered, fingerprint=self._lowered_digest(lowered),
                    label="DataParallelTrainer.step",
                    compile_for_memory=mem_on, **common)
            else:
                gargs = (self._params, self._aux)
                if self._scaler_cfg is not None:
                    gargs += (self._guard_state["loss_scale"],)
                glow = self._grad_fn.lower(*(gargs + (rng,) + tuple(arrays)))
                # grads share the params avals exactly — params stand in
                alow = self._apply_fn.lower(
                    self._params, self._opt_state, self._guard_state,
                    self._params)
                import hashlib
                extra = None
                if mem_on:
                    # the kv step IS two programs: memory is their sum
                    # (same contract as merge_costs — all parts or none)
                    try:
                        mems = [_xcost.memory_of(p.compile())
                                for p in (glow, alow)]
                    except Exception:
                        mems = [None]
                    if all(mems):
                        mem = {k: sum(m[k] for m in mems) for k in mems[0]}
                        extra = {"memory": mem,
                                 "peak_memory_bytes": (
                                     mem["temp_bytes"]
                                     + mem["argument_bytes"]
                                     + mem["output_bytes"])}
                row = _xcost.capture(
                    cost=_xcost.merge_costs(_xcost.cost_of(glow),
                                            _xcost.cost_of(alow)),
                    fingerprint=hashlib.sha256(
                        (self._lowered_digest(glow)
                         + self._lowered_digest(alow)).encode()).hexdigest(),
                    label="DataParallelTrainer.kv_step", extra=extra,
                    **common)
        except Exception as e:   # never let the perf layer kill a step
            logger.warning("cost-ledger capture failed: %r", e)
            return
        if row is not None:
            self._cost_rows[key] = row
            if row.get("flops"):
                self._flops_per_step = float(row["flops"])

    def _kv_step(self, rng, arrays):
        """Grad -> kvstore wire sync (summed across workers; 2-bit codec if
        active) -> jitted optimizer apply."""
        if self._scaler_cfg is not None:
            grads, self._aux, loss = self._grad_fn(
                self._params, self._aux, self._guard_state["loss_scale"],
                rng, *arrays)
        else:
            grads, self._aux, loss = self._grad_fn(
                self._params, self._aux, rng, *arrays)
        kv = self._kv
        # grad_reduce_dtype applies to the kv WIRE too: gradients travel
        # (and merge) in the reduction dtype, and come back to f32 before
        # the jitted apply — same accumulate-in-f32 contract as the fused
        # path's in-trace cast
        rdt = self._grad_reduce_dtype

        def wire(g):
            return g.astype(rdt) if rdt is not None else g

        if not self._kv_inited:
            for n in self._param_names:
                kv.init("dpt_grad_" + n, _wrap(wire(jnp.zeros_like(grads[n]))))
            self._kv_inited = True
            # the apply program spans the local mesh: params must sit
            # replicated on it, not wherever capture left them
            self._place_state()
        for i, n in enumerate(self._param_names):
            kv.push("dpt_grad_" + n, _wrap(wire(grads[n])), priority=-i)
        nworkers = max(1, getattr(kv, "num_workers", 1))
        repl = NamedSharding(self._mesh, P())
        synced = {}
        for n in self._param_names:
            out = _wrap(wire(grads[n]))
            kv.pull("dpt_grad_" + n, out=out)
            # the store round-trip (esp. the codec decode) may land the
            # gradient on a single device; re-replicate over the mesh so
            # the jitted apply sees one consistent placement
            synced[n] = jax.device_put(
                out._data.astype(jnp.float32) / nworkers, repl)
        self._params, self._opt_state, self._guard_state = self._apply_fn(
            self._params, self._opt_state, self._guard_state, synced)
        return loss

    def lower(self, *data):
        """Capture (if needed) and lower the fused step for a batch spec
        WITHOUT compiling or dispatching anything: the data arguments are
        abstracted to shape/dtype structs, and a deferred-init net is
        materialized with a batch-1 host forward only. This is the public
        surface the tuner's predictor and the HLO audit use — cost
        analysis, fingerprinting (``_lowered_digest``) — so external
        modules don't each re-implement the step-state argument list.
        Returns the ``jax.stages.Lowered``."""
        arrays = [_unwrap(d) if isinstance(d, NDArray) else d
                  for d in data]
        if self._step_fn is not None and self._n_inputs != len(arrays):
            # a diagnostics entry point must never silently re-capture a
            # live trainer (params/opt-state reset, loaded AOT executable
            # dropped) — same refusal as analysis.lint_trainer
            raise MXNetError(
                f"lower: batch has {len(arrays)} array(s) but the captured "
                f"step takes {self._n_inputs}; pass a batch of the "
                "training arity (lower never recaptures a live trainer)")
        if self._step_fn is None:
            # one-row slices are enough for deferred-init shape inference
            # and avoid a full-batch host forward in a predict-only path
            sample = [np.asarray(a[:1]) if getattr(a, "ndim", 0) else a
                      for a in arrays]
            self._capture(len(arrays), sample_arrays=sample)
        specs = [jax.ShapeDtypeStruct(tuple(a.shape), np.dtype(a.dtype))
                 for a in arrays]
        rng = jax.random.PRNGKey(0)
        return self._step_fn.lower(
            self._params, self._aux, self._opt_state, self._guard_state,
            rng, *specs)

    def sync_to_net(self) -> None:
        """Write the trained params/aux back into the gluon net (resharded
        onto each parameter's home device).  Pass-re-homed parameters are
        inverse-transformed first, so the net always sees its own layout."""
        def back(n, v):
            if self._pass_result is not None and \
                    n in self._pass_result.var_transforms:
                return jnp.asarray(
                    self._pass_result.inverse_var(n, jax.device_get(v)))
            return v
        # fresh buffers first: moving a mesh-placed array to a device of the
        # mesh aliases it, and the next step's donation would then delete
        # what the net was just given
        params, aux = _copy_tree((self._params, self._aux))
        for n in self._param_names:
            home = self._pmap[n].list_ctx()[0].jax_device()
            self._pmap[n].data()._set_data(
                jax.device_put(back(n, params[n]), home))
        for n in self._aux_names:
            home = self._pmap[n].list_ctx()[0].jax_device()
            self._pmap[n].data()._set_data(
                jax.device_put(back(n, aux[n]), home))

    def lint(self, *data, suppress=()) -> Any:
        """Trace-lint the fused step against a sample batch (mxlint trace
        front end): donation, f64, baked constants, host syncs. Captures the
        net if needed; nothing executes on device. Returns an
        ``analysis.Report``."""
        from ..analysis import lint_trainer
        return lint_trainer(self, *data, suppress=suppress)

    def anomaly_stats(self) -> Dict[str, Any]:
        """Grad-anomaly guard counters (empty dict when the guard is off or
        no step ran): skipped-step count, grad-norm EMA, last step's norm
        and whether it was skipped. Reading syncs the small scalars to host;
        surfaced through ``Monitor.install_trainer``."""
        if self._guard_cfg is None or self._guard_state is None:
            return {}
        gs = self._guard_state
        stats = {"grad_skipped_steps": int(gs["skips"]),
                 "grad_norm_ema": float(gs["ema"]),
                 "last_grad_norm": float(gs["last_norm"]),
                 "last_step_skipped": bool(int(gs["last_skipped"]))}
        if self._scaler_cfg is not None:
            stats["loss_scale"] = float(gs["loss_scale"])
            stats["scaler_overflows"] = int(gs["ls_overflows"])
            stats["scaler_good_steps"] = int(gs["ls_good"])
        if self._dynamic_lr:
            stats["lr_scale"] = float(gs["lr_scale"])
        if _metrics.enabled():
            # publish at drain time (Monitor interval / user poll), never
            # per step — reading the guard scalars syncs the device
            _telemetry.GRAD_SKIPPED.set(stats["grad_skipped_steps"])
            _telemetry.GRAD_NORM_EMA.set(stats["grad_norm_ema"])
            _telemetry.GRAD_LAST_NORM.set(stats["last_grad_norm"])
            if "loss_scale" in stats:
                _telemetry.LOSS_SCALE.set(stats["loss_scale"])
        return stats

    def perf_stats(self) -> Dict[str, Any]:
        """Step-attribution window stats (empty dict when attribution is
        off or no step ran): rolling bucket means, device_util, cadence —
        plus flops_per_step and live MFU when the cost ledger captured this
        executable. All host-side reads; never syncs the device."""
        if self._perf is None or self._perf.steps == 0:
            return {}
        stats = self._perf.stats()
        if self._flops_per_step:
            stats["flops_per_step"] = self._flops_per_step
            mfu = self._perf.mfu(self._flops_per_step)
            if mfu is not None:
                stats["mfu"] = mfu
        return stats

    def topology(self) -> Dict[str, Any]:
        """The mesh identity this trainer trains on: device count, data-
        axis (dp) extent, full mesh axes and the grad-reduce mode. This is
        what ``ResilientTrainer.save`` stamps into every resume manifest
        and what an elastic restore reconciles a checkpoint against
        (``resilience.elastic``)."""
        dev = self._mesh.devices.ravel()[0]
        try:
            dp = int(self._mesh.shape[self._axis])
        except (KeyError, TypeError):
            dp = int(self._mesh.devices.size)
        return {"n_devices": int(self._mesh.devices.size), "dp": dp,
                "axis": self._axis,
                "mesh_axes": {str(n): int(self._mesh.shape[n])
                              for n in self._mesh.axis_names},
                "device_kind": dev.device_kind, "platform": dev.platform,
                "grad_reduce": self._grad_reduce}

    def comm_config(self) -> Dict[str, Any]:
        """The communication-lever configuration this trainer runs — the
        scale-out half of the perf provenance (stamped into bench rows the
        way ``passes_provenance`` stamps the graph-pass half)."""
        return {"grad_reduce": self._grad_reduce,
                "grad_reduce_dtype": (str(self._grad_reduce_dtype)
                                      if self._grad_reduce_dtype is not None
                                      else None),
                "bucket_bytes": self._bucket_bytes,
                "compression": self._compression_params,
                "n_devices": int(self._mesh.devices.size)}

    def opt_state_bytes(self) -> Dict[str, int]:
        """Optimizer-state memory: ``total_bytes`` (the logical tree) and
        ``per_chip_bytes`` (what one chip actually holds — the number the
        ZeRO-1 sharded optimizer divides by N). Empty dict before capture."""
        if self._opt_state is None:
            return {}
        dev0 = self._mesh.devices.ravel()[0]
        total = per_chip = 0
        for leaf in jax.tree_util.tree_leaves(self._opt_state):
            nbytes = int(getattr(leaf, "nbytes", 0))
            total += nbytes
            shards = getattr(leaf, "addressable_shards", None)
            if shards:
                per_chip += sum(int(s.data.nbytes) for s in shards
                                if s.device == dev0)
            else:
                per_chip += nbytes
        return {"total_bytes": total, "per_chip_bytes": per_chip}

    def footprint(self) -> Dict[str, Any]:
        """Estimated resident HBM of this trainer (host-side tree sums —
        never syncs the device): params + aux + guard (replicated: each
        chip holds a full copy), opt-state via :meth:`opt_state_bytes`
        (ZeRO-aware per-chip share), and ``donated_bytes`` — the params +
        opt-state buffers the fused step donates, i.e. the transient the
        step does NOT double-buffer (XLA reuses donated inputs for the
        matching outputs). ``step_peak_bytes`` rides along when the memory
        ledger captured this trainer's executable."""
        params = _memwatch.tree_bytes(self._params)
        aux = _memwatch.tree_bytes(self._aux)
        guard = _memwatch.tree_bytes(self._guard_state)
        opt = self.opt_state_bytes()
        total = params + aux + guard + int(opt.get("total_bytes", 0))
        per_chip = params + aux + guard + int(opt.get("per_chip_bytes", 0))
        fp: Dict[str, Any] = {
            "params_bytes": params, "aux_bytes": aux, "guard_bytes": guard,
            "opt_state_bytes": opt,
            "donated_bytes": params + int(opt.get("total_bytes", 0)),
            "total_bytes": total, "per_chip_bytes": per_chip,
        }
        peaks = [r.get("peak_memory_bytes") for r in
                 (self._cost_rows or {}).values()
                 if r and r.get("peak_memory_bytes")]
        if peaks:
            fp["step_peak_bytes"] = int(max(peaks))
        return fp

    # ------------------------------------------------- recovery state hooks
    def set_loss_scale(self, scale: float) -> None:
        """Host-side override of the in-trace loss scale (the recovery
        ladder's ``cut_scale`` rung). A no-op trainer error when no scaler
        is configured."""
        if self._scaler_cfg is None or self._guard_state is None:
            raise MXNetError("trainer has no in-trace loss scaler "
                             "(construct with loss_scaling=...)")
        # the override obeys the same invariants as every in-trace
        # transition: power of two (bitwise-exact scaling) and the
        # configured clamp range
        _recovery._require_pow2("loss scale override", scale)
        scale = min(max(float(scale), float(self._scaler_cfg["min_scale"])),
                    float(self._scaler_cfg["max_scale"]))
        self._guard_state = dict(self._guard_state)
        self._guard_state["loss_scale"] = jax.device_put(
            jnp.asarray(scale, jnp.float32),
            NamedSharding(self._mesh, P()))
        self._guard_state["ls_good"] = jax.device_put(
            jnp.zeros((), jnp.int32), NamedSharding(self._mesh, P()))

    def set_lr_scale(self, scale: float) -> None:
        """Host-side override of the dynamic LR multiplier (recovery
        rollback backoff / heal restore)."""
        if not self._dynamic_lr or self._guard_state is None:
            raise MXNetError("trainer has no dynamic lr scale "
                             "(construct with dynamic_lr_scale=True)")
        self._guard_state = dict(self._guard_state)
        self._guard_state["lr_scale"] = jax.device_put(
            jnp.asarray(float(scale), jnp.float32),
            NamedSharding(self._mesh, P()))

    @property
    def mesh(self) -> Mesh:
        return self._mesh
