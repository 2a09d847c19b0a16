"""Module — symbolic model with bind/init/forward/backward/update.

Reference parity: ``python/mxnet/module/module.py`` (bind :573+,
init_optimizer, forward/backward, update :644, save/load_checkpoint :165).
"""
from __future__ import annotations

import logging
from typing import Dict, List, Optional

import numpy as np

from .. import initializer as init_mod
from .. import ndarray as nd
from .. import optimizer as opt_mod
from ..base import MXNetError
from ..context import Context, cpu, current_context
from ..ndarray import NDArray
from .base_module import BaseModule
from .executor_group import DataParallelExecutorGroup

__all__ = ["Module"]


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",), label_names=("softmax_label",),
                 logger=logging, context=None, work_load_list=None,
                 fixed_param_names=None, state_names=None, group2ctxs=None,
                 compression_params=None, passes=None):
        super().__init__(logger)
        # graph-pass pipeline (mxnet_tpu.passes) run over the symbol at
        # bind time: None = MXNET_PASSES default, False = off.  The module
        # keeps the ORIGINAL symbol for checkpoints/shape queries; only
        # the executor group binds the rewritten graph.  Variable
        # re-homing is disabled on this path (arg arrays, set_params and
        # load_checkpoint all key on the original shapes), so layout
        # rewrites materialize as in-graph transposes XLA folds away.
        from ..passes import resolve as _resolve_passes
        self._passes = _resolve_passes(passes)
        self._pass_result = None
        if context is None:
            context = [current_context()]
        if isinstance(context, Context):
            context = [context]
        self._context = context
        # inter-layer placement spec (reference Module group2ctxs →
        # AssignContext): one dict per context; the SPMD design needs only
        # the first (per-process), which Symbol.simple_bind maps onto a
        # PipelinedExecutor when it spans distinct devices
        specs = group2ctxs if isinstance(group2ctxs, (list, tuple)) \
            else ([group2ctxs] if group2ctxs else [])
        self._group2ctx = dict(specs[0]) if specs else None
        self._symbol = symbol
        self._data_names = list(data_names or [])
        self._label_names = list(label_names or [])
        self._fixed_param_names = list(fixed_param_names or [])
        arg_names = symbol.list_arguments()
        input_names = set(self._data_names) | set(self._label_names)
        self._param_names = [n for n in arg_names if n not in input_names]
        self._aux_names = symbol.list_auxiliary_states()
        self._arg_params: Dict[str, NDArray] = {}
        self._aux_params: Dict[str, NDArray] = {}
        self._exec_group: Optional[DataParallelExecutorGroup] = None
        self._optimizer = None
        self._updater = None
        self._kvstore = None
        self._compression_params = compression_params
        self._update_on_kvstore = False

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        from ..model import load_checkpoint
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(sym, **kwargs)
        # consumed by init_params() after bind: loaded values win over the
        # initializer (reference Module.load -> set_params flow)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = False
        return mod

    # ------------------------------------------------------------- binding
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._symbol.list_outputs()

    @property
    def data_shapes(self):
        return self._data_shapes

    @property
    def label_shapes(self):
        return self._label_shapes

    @property
    def output_shapes(self):
        outs = [tuple(o.shape) for o in self._exec_group.execs[0].outputs] \
            if self._exec_group.execs[0].outputs else None
        if outs is None and self._data_shapes is not None:
            # before the first forward: infer from the bound input shapes
            feed = {d.name: d.shape for d in self._data_shapes}
            for l in (self._label_shapes or []):
                feed[l.name] = l.shape
            _, outs, _ = self._symbol.infer_shape_partial(**feed)
        return list(zip(self.output_names, outs or []))

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        if self.binded and not force_rebind:
            return
        self._data_shapes = [d if hasattr(d, "name") else
                             _mk_desc(n, d) for n, d in
                             zip(self._data_names, _shapes_of(data_shapes))] \
            if not _is_desc_list(data_shapes) else list(data_shapes)
        if label_shapes:
            self._label_shapes = list(label_shapes) if _is_desc_list(label_shapes) \
                else [_mk_desc(n, s) for n, s in
                      zip(self._label_names, _shapes_of(label_shapes))]
        else:
            self._label_shapes = []
        shared_group = shared_module._exec_group if shared_module else None
        bind_symbol = self._run_passes()
        self._exec_group = DataParallelExecutorGroup(
            bind_symbol, self._context, None, self._data_shapes,
            self._label_shapes, self._param_names, for_training,
            inputs_need_grad, shared_group=shared_group,
            fixed_param_names=self._fixed_param_names, grad_req=grad_req,
            group2ctx=self._group2ctx)
        self.binded = True
        self.for_training = for_training

    def _run_passes(self):
        """The symbol the executor group binds: the pass pipeline's
        rewrite of ``self._symbol`` (or the original when passes are off /
        rewrote nothing).  Never raises — a pipeline failure degrades to
        the unrewritten graph with a warning."""
        self._pass_result = None
        if self._passes is None:
            return self._symbol
        shapes = {d.name: tuple(d.shape) for d in self._data_shapes}
        shapes.update({l.name: tuple(l.shape)
                       for l in (self._label_shapes or [])})
        try:
            res = self._passes.run(self._symbol, shapes=shapes,
                                   input_vars=tuple(shapes),
                                   rehome_params=False)
        except Exception as e:
            self.logger.warning("graph-pass pipeline failed; binding the "
                                "unrewritten graph: %r", e)
            return self._symbol
        if res.total_rewrites == 0:
            return self._symbol
        self._pass_result = res
        return res.symbol

    def passes_provenance(self):
        """Pipeline names + rewrite counts (bench/row attribution; one
        schema with DataParallelTrainer: passes.manager.provenance)."""
        from ..passes import provenance
        return provenance(self._passes, self._pass_result)

    def lint(self, suppress=()):
        """Static-analyze the bound graph with this module's data/label
        shapes (mxlint graph front end). Call after ``bind``; returns an
        ``analysis.Report`` — ``report.assert_clean()`` in tests."""
        assert self.binded, "lint requires a bound module"
        applied = (self._passes.names if self._passes is not None else ())
        return self._exec_group.execs[0].lint(suppress=suppress,
                                              passes_applied=applied)

    # ------------------------------------------------------------- params
    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        if self.params_initialized and not force_init:
            return
        assert self.binded
        initializer = initializer or init_mod.Uniform(0.01)
        ex = self._exec_group.execs[0]
        for name in self._param_names:
            if arg_params is not None and name in arg_params:
                ex.arg_dict[name]._set_data(arg_params[name]._data)
            elif self._arg_params.get(name) is not None:
                ex.arg_dict[name]._set_data(self._arg_params[name]._data)
            else:
                host = np.zeros(ex.arg_dict[name].shape, dtype="float32")
                initializer(name, host)
                ex.arg_dict[name]._set_data(nd.array(host)._data)
        for name in self._aux_names:
            if aux_params is not None and name in aux_params:
                ex.aux_dict[name]._set_data(aux_params[name]._data)
            elif self._aux_params.get(name) is not None:
                ex.aux_dict[name]._set_data(self._aux_params[name]._data)
            else:
                host = np.zeros(ex.aux_dict[name].shape, dtype="float32")
                initializer(name, host)
                ex.aux_dict[name]._set_data(nd.array(host)._data)
        self.params_initialized = True

    def get_params(self):
        arg, aux = {}, {}
        self._exec_group.get_params(arg, aux)
        arg = {k: v for k, v in arg.items() if k in self._param_names}
        return arg, aux

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        if not allow_missing:
            for name in self._param_names:
                if name not in (arg_params or {}):
                    raise MXNetError(f"missing parameter {name}")
        self._exec_group.set_params(arg_params or {}, aux_params or {},
                                    allow_extra=allow_extra)
        self.params_initialized = True

    # ------------------------------------------------------------- optimizer
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            return
        if isinstance(optimizer, str):
            idx2name = {i: n for i, n in enumerate(self._param_names)}
            opt_kwargs = dict(optimizer_params or ())
            # reference module.py: rescale_grad defaults to 1/batch_size
            batch_size = self._data_shapes[0].shape[0] if self._data_shapes else 1
            opt_kwargs.setdefault("rescale_grad", 1.0 / max(batch_size, 1))
            optimizer = opt_mod.create(optimizer, param_idx2name=idx2name,
                                       **opt_kwargs)
        self._optimizer = optimizer
        self._updater = opt_mod.get_updater(optimizer)
        if kvstore:
            from .. import kvstore as kv_mod
            kv = kv_mod.create(kvstore) if isinstance(kvstore, str) else kvstore
            if self._compression_params:
                kv.set_gradient_compression(self._compression_params)
            self._kvstore = kv
            # reference default: optimizer runs on the store when one exists
            # (model.py _create_kvstore update_on_kvstore=True path)
            self._update_on_kvstore = True
            ex = self._exec_group.execs[0]
            if self._update_on_kvstore:
                kv.set_optimizer(self._optimizer)
            for i, name in enumerate(self._param_names):
                kv.init(i, ex.arg_dict[name])
            # pull initial weights back so every dist worker starts from
            # the store's (rank 0's) values — reference _initialize_kvstore
            # pulls right after init (model.py:100-128)
            if kv.num_workers > 1:
                for i, name in enumerate(self._param_names):
                    kv.pull(i, ex.arg_dict[name], priority=-i)
        self.optimizer_initialized = True

    # ------------------------------------------------------------- exec
    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        if is_train is None:
            is_train = self.for_training
        self._exec_group.forward(data_batch, is_train)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._exec_group.backward(out_grads)

    def update(self):
        """Apply gradients (reference module.py:644 →
        _update_params_on_kvstore: push grads, pull weights)."""
        assert self.binded and self.params_initialized and self.optimizer_initialized
        ex = self._exec_group.execs[0]
        # two-phase push-then-pull so the kvstore aggregates dispatches
        # (reference _update_params_on_kvstore_nccl, model.py:130-148)
        live = [(i, name, ex.grad_dict[name])
                for i, name in enumerate(self._param_names)
                if ex.grad_dict.get(name) is not None]
        if self._kvstore is not None and self._update_on_kvstore:
            for i, name, grad in live:
                self._kvstore.push(i, grad, priority=-i)
            for i, name, grad in live:
                self._kvstore.pull(i, ex.arg_dict[name], priority=-i)
        else:
            if self._kvstore is not None:
                for i, name, grad in live:
                    self._kvstore.push(i, grad, priority=-i)
                for i, name, grad in live:
                    self._kvstore.pull(i, grad, priority=-i)
            for i, name, grad in live:
                self._updater(i, grad, ex.arg_dict[name])

    def get_outputs(self, merge_multi_context=True):
        return self._exec_group.get_outputs(merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        return self._exec_group.get_input_grads(merge_multi_context)

    def update_metric(self, eval_metric, labels, pre_sliced=False):
        self._exec_group.update_metric(eval_metric, labels, pre_sliced)

    def install_monitor(self, mon):
        self._exec_group.install_monitor(mon)

    # ------------------------------------------------------------- checkpoint
    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        from ..model import save_checkpoint
        arg, aux = self.get_params()
        save_checkpoint(prefix, epoch, self._symbol, arg, aux)
        if save_optimizer_states:
            payload = self._updater.get_states()
            scaler = getattr(self, "_amp_loss_scaler", None)
            if scaler is not None:
                # AMP runs resume with the loss scale they earned, not
                # init_scale (same envelope as gluon Trainer.save_states)
                from ..contrib import amp
                payload = amp.pack_states(payload, scaler)
            with open(f"{prefix}-{epoch:04d}.states", "wb") as f:
                f.write(payload)

    def load_optimizer_states(self, fname):
        from ..contrib import amp
        with open(fname, "rb") as f:
            payload, scaler_state = amp.unpack_states(f.read())
        self._updater.set_states(payload)
        if scaler_state is not None:
            scaler = getattr(self, "_amp_loss_scaler", None)
            if scaler is None:
                # the state carries everything a scaler needs (scale, growth
                # counter, interval) — attach a restored one here, because
                # unlike the gluon path there is no later init_trainer hook
                # to consume a stash
                scaler = amp.LossScaler()
                self._amp_loss_scaler = scaler
            scaler.load_state_dict(scaler_state)
        else:
            scaler = getattr(self, "_amp_loss_scaler", None)
            if scaler is not None:
                # a non-AMP file: an attached scaler keeping another run's
                # earned scale would graft it onto this lineage
                scaler.reset()

    def reshape(self, data_shapes, label_shapes=None):
        self.bind(data_shapes, label_shapes, for_training=self.for_training,
                  force_rebind=True)


def _is_desc_list(shapes):
    return shapes and hasattr(shapes[0], "name")


def _shapes_of(shapes):
    return [s[1] if isinstance(s, tuple) and len(s) == 2 and
            isinstance(s[0], str) else s for s in shapes]


def _mk_desc(name, shape):
    from ..io.io import DataDesc
    return DataDesc(name, tuple(shape))
