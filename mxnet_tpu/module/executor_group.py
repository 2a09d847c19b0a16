"""DataParallelExecutorGroup.

Reference parity: ``python/mxnet/module/executor_group.py`` (decide_slices
:281-310, per-context executors). TPU-first: one logical executor — SPMD
sharding replaces per-context executor lists, so the "group" holds a single
Executor and the batch-slicing API degenerates to pass-through; the
multi-device path belongs to parallel.DataParallelTrainer. The class is kept
because Module's plumbing (and user code poking ``execs``) expects it.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from .. import ndarray as nd
from ..base import MXNetError, logger as _logger
from ..ndarray import NDArray

__all__ = ["DataParallelExecutorGroup"]


class DataParallelExecutorGroup:
    def __init__(self, symbol, contexts, workload, data_shapes, label_shapes,
                 param_names, for_training, inputs_need_grad, shared_group=None,
                 logger=None, fixed_param_names=None, grad_req="write",
                 state_names=None, group2ctx=None):
        self.symbol = symbol
        self.contexts = contexts
        self.param_names = param_names
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.fixed_param_names = set(fixed_param_names or [])
        self.data_names = [d.name for d in data_shapes]
        self.label_names = [l.name for l in label_shapes] if label_shapes else []

        arg_names = symbol.list_arguments()
        self.grad_req = {}
        for name in arg_names:
            if name in self.fixed_param_names:
                self.grad_req[name] = "null"
            elif name in self.data_names:
                self.grad_req[name] = grad_req if inputs_need_grad else "null"
            elif name in self.label_names:
                self.grad_req[name] = "null"
            else:
                self.grad_req[name] = grad_req if for_training else "null"

        shapes = {d.name: d.shape for d in data_shapes}
        if label_shapes:
            shapes.update({l.name: l.shape for l in label_shapes})
        shared_exec = shared_group.execs[0] if shared_group is not None else None
        ctx = contexts[0]
        if len(contexts) > 1:
            # said aloud, not hidden: a reference script's --gpus 0,1,2,3
            # trains on ONE device here
            _logger.warning(
                "Module binds one executor on %s; the other %d context(s) "
                "are not used. Multi-device data parallelism is "
                "parallel.DataParallelTrainer.", ctx, len(contexts) - 1)
        if shared_exec is not None:
            # bucketing: share argument arrays with the largest-bucket
            # executor; group2ctx rides along so every bucket keeps the
            # same device placement as the default bucket
            if group2ctx is None:
                group2ctx = getattr(shared_exec, "group2ctx", None)
            exec_ = symbol.bind(ctx,
                                {k: v for k, v in shared_exec.arg_dict.items()
                                 if k in arg_names},
                                {k: v for k, v in shared_exec.grad_dict.items()
                                 if k in arg_names},
                                self.grad_req,
                                dict(shared_exec.aux_dict),
                                group2ctx=group2ctx)
            # (re)size data/label arrays for this bucket's shapes
            for name, shape in shapes.items():
                if name not in exec_.arg_dict or \
                        tuple(exec_.arg_dict[name].shape) != tuple(shape):
                    exec_.arg_dict[name] = nd.zeros(shape, ctx=ctx)
        else:
            exec_ = symbol.simple_bind(ctx, grad_req=self.grad_req,
                                       group2ctx=group2ctx, **shapes)
        self.execs = [exec_]

    # ------------------------------------------------------------- data flow
    def forward(self, data_batch, is_train=None):
        ex = self.execs[0]
        kwargs = {}
        for name, arr in zip(self.data_names, data_batch.data):
            kwargs[name] = arr
        if self.label_names and data_batch.label:
            for name, arr in zip(self.label_names, data_batch.label):
                kwargs[name] = arr
        ex.forward(is_train=bool(is_train), **kwargs)

    def backward(self, out_grads=None):
        self.execs[0].backward(out_grads)

    def get_outputs(self, merge_multi_context=True):
        return list(self.execs[0].outputs)

    def get_input_grads(self, merge_multi_context=True):
        ex = self.execs[0]
        return [ex.grad_dict.get(n) for n in self.data_names]

    def update_metric(self, eval_metric, labels, pre_sliced=False):
        eval_metric.update_dict(
            dict(zip(self.label_names, labels or [])),
            dict(zip(self.symbol.list_outputs(), self.execs[0].outputs)))

    # ------------------------------------------------------------- params
    def get_params(self, arg_params, aux_params):
        ex = self.execs[0]
        for name in self.param_names:
            if name in ex.arg_dict:
                arg_params[name] = ex.arg_dict[name].copy()
        for name, arr in ex.aux_dict.items():
            aux_params[name] = arr.copy()

    def set_params(self, arg_params, aux_params, allow_extra=False):
        self.execs[0].copy_params_from(arg_params, aux_params,
                                       allow_extra_params=True)

    def install_monitor(self, mon):
        for ex in self.execs:
            mon.install(ex)
