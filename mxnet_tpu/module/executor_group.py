"""Sharded executor group.

TPU-native redesign of DataParallelExecutorGroup
(python/mxnet/module/executor_group.py:79). The reference builds ONE
EXECUTOR PER DEVICE, scatters batch slices (`decide_slices`, :213-237),
fans out forward/backward, and reduces gradients through Comm/KVStore.

On TPU the idiomatic equivalent is ONE executor over a device Mesh:
- the batch axis is sharded over the mesh ("data" axis) via NamedSharding;
- parameters are replicated;
- XLA inserts the gradient all-reduce over ICI during sharding propagation
  (backward of a replicated param against sharded activations ⇒ psum),
  which is exactly CommDevice::Reduce (comm.h:211-373) without the
  hand-written P2P copies.

The public surface (forward / backward / get_outputs / update_metric /
slices bookkeeping) matches the reference so Module code is unchanged.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import ndarray as nd
from ..base import MXNetError
from ..context import Context
from ..ndarray import NDArray


class DataParallelExecutorGroup:
    def __init__(self, symbol, contexts, workload, data_shapes, label_shapes,
                 param_names, for_training, inputs_need_grad, shared_group=None,
                 logger=None, fixed_param_names=None, grad_req="write",
                 state_names=None, compute_dtype=None):
        self.symbol = symbol
        self.compute_dtype = compute_dtype
        self.contexts = contexts
        self.param_names = param_names
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.fixed_param_names = fixed_param_names or []
        self.state_names = state_names or []
        self.logger = logger

        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.data_names = [x.name if hasattr(x, "name") else x[0] for x in data_shapes]
        self.label_names = (
            [x.name if hasattr(x, "name") else x[0] for x in label_shapes]
            if label_shapes else []
        )

        self.data_shapes = data_shapes
        self.label_shapes = label_shapes

        # ---- mesh over the data axis (SPMD data parallelism) -------------
        devices = [c.jax_device() for c in contexts]
        self._single = len(devices) == 1
        if not self._single:
            self.mesh = Mesh(np.array(devices), ("data",))
            self._data_sharding = NamedSharding(self.mesh, P("data"))
            self._repl_sharding = NamedSharding(self.mesh, P())
        else:
            self.mesh = None

        # grad_req per arg
        if isinstance(grad_req, str):
            base_req = grad_req if for_training else "null"
            self.grad_req = {}
            for name in self.arg_names:
                if name in self.data_names:
                    self.grad_req[name] = base_req if inputs_need_grad else "null"
                elif name in self.label_names:
                    self.grad_req[name] = "null"
                elif name in self.fixed_param_names:
                    self.grad_req[name] = "null"
                else:
                    self.grad_req[name] = base_req
        else:
            self.grad_req = dict(grad_req)

        self._bind(shared_group)
        # reference API compat: slices over the global batch (used by
        # executor_manager-style code and tests)
        self.batch_size = (
            self.data_shapes[0].shape[0]
            if hasattr(self.data_shapes[0], "shape")
            else self.data_shapes[0][1][0]
        )
        k = len(contexts)
        step = self.batch_size // k
        self.slices = [slice(i * step, (i + 1) * step if i < k - 1 else self.batch_size)
                       for i in range(k)]

    @property
    def data_parallel_size(self):
        """Replica count along the data mesh axis (1 when single-device) —
        the N of the ZeRO-1 sharded update's 1/N state shards."""
        return 1 if self.mesh is None else int(dict(self.mesh.shape)["data"])

    # ------------------------------------------------------------------
    def _shape_of(self, desc):
        return tuple(desc.shape) if hasattr(desc, "shape") else tuple(desc[1])

    def _bind(self, shared_group):
        shapes = {}
        for d in self.data_shapes:
            shapes[d.name if hasattr(d, "name") else d[0]] = self._shape_of(d)
        for d in self.label_shapes or []:
            shapes[d.name if hasattr(d, "name") else d[0]] = self._shape_of(d)
        arg_shapes, _, aux_shapes = self.symbol.infer_shape(**shapes)
        ctx0 = self.contexts[0]
        args, grads, auxs = {}, {}, {}
        shared_exec = shared_group._exec if shared_group is not None else None
        for name, shape in zip(self.arg_names, arg_shapes):
            args[name] = self._alloc(shape, replicated=name not in shapes or name in self.param_names)
            if self.grad_req.get(name, "null") != "null":
                grads[name] = self._alloc(shape, replicated=name in self.param_names)
        for name, shape in zip(self.aux_names, aux_shapes):
            auxs[name] = self._alloc(shape, replicated=True)
        from ..executor import Executor

        self._exec = Executor(self.symbol, ctx0, args, grads or None, self.grad_req,
                              auxs, shared_exec=shared_exec,
                              compute_dtype=self.compute_dtype, mesh=self.mesh)
        self.execs = [self._exec]  # reference-compat attribute

    def _alloc(self, shape, replicated=True):
        arr = np.zeros(shape, np.float32)
        if self._single:
            return nd.array(arr, ctx=self.contexts[0])
        sharding = self._repl_sharding if replicated or shape[0] % len(self.contexts) else self._data_sharding
        return NDArray(jax.device_put(arr, sharding))

    # ------------------------------------------------------------------
    def forward(self, data_batch, is_train=None):
        """Scatter the batch over the mesh and run the single sharded
        executor (reference executor_group.py:364 forward)."""
        if is_train is None:
            is_train = self.for_training
        self._load_data(data_batch)
        self._exec.forward(is_train=is_train)
        return self._exec.outputs

    def backward(self, out_grads=None):
        if not self.for_training:
            raise MXNetError("re-bind with for_training=True to run backward")
        self._exec.backward(out_grads)

    def forward_backward(self, data_batch, out_grads=None):
        """Fused train step: one jitted XLA computation for fwd+bwd."""
        self._load_data(data_batch)
        self._exec.forward_backward(out_grads)
        return self._exec.outputs

    def _put(self, target: NDArray, value):
        target._data = self._place(target, value)

    def _place(self, target: NDArray, value):
        # Keep device arrays on device: an NDArray batch feeds straight into
        # device_put (device-to-device, often a no-op) — no host round-trip.
        # The reference's H2D copy is likewise engine-async (SURVEY §3.5).
        # Split from _put so trace-and-fuse feeds place a batch EXACTLY as
        # _load_data would (same cast, same sharding) without touching the
        # exec buffers.
        tgt_dtype = target._data.dtype
        if isinstance(value, NDArray):
            arr = value._data
            if arr.dtype != tgt_dtype:
                arr = arr.astype(tgt_dtype)
        else:
            arr = np.asarray(value).astype(np.dtype(tgt_dtype), copy=False)
        if self._single:
            dev = self.contexts[0].jax_device()
            if isinstance(arr, jax.Array) and not arr.is_deleted() \
                    and arr.sharding.device_set == {dev}:
                return arr  # already resident: no transfer
            return jax.device_put(arr, dev)
        sharding = (
            self._data_sharding
            if arr.shape and arr.shape[0] % len(self.contexts) == 0
            else self._repl_sharding
        )
        return jax.device_put(arr, sharding)

    def _load_data(self, data_batch):
        for name, val in zip(self.data_names, data_batch.data):
            if name in self._exec.arg_dict:
                self._put(self._exec.arg_dict[name], val)
        if self.label_names and data_batch.label:
            for name, val in zip(self.label_names, data_batch.label):
                if name in self._exec.arg_dict:
                    self._put(self._exec.arg_dict[name], val)

    # ------------------------------------------------------------------
    def get_outputs(self, merge_multi_context=True):
        outs = self._exec.outputs
        if merge_multi_context:
            return outs
        return [[o] for o in outs]

    def get_input_grads(self, merge_multi_context=True):
        grads = [self._exec.grad_dict.get(n) for n in self.data_names]
        if merge_multi_context:
            return grads
        return [[g] for g in grads]

    def get_params(self, arg_params, aux_params):
        for name in self.param_names:
            if name in self._exec.arg_dict:
                arg_params[name] = nd.array(self._exec.arg_dict[name].asnumpy())
        for name in self.aux_names:
            aux_params[name] = nd.array(self._exec.aux_dict[name].asnumpy())

    def set_params(self, arg_params, aux_params, allow_extra=False):
        for name, val in (arg_params or {}).items():
            if name in self._exec.arg_dict:
                self._put(self._exec.arg_dict[name], val)
            elif not allow_extra:
                raise MXNetError("set_params: unknown argument %r" % name)
        for name, val in (aux_params or {}).items():
            if name in self._exec.aux_dict:
                self._put(self._exec.aux_dict[name], val)
            elif not allow_extra:
                raise MXNetError("set_params: unknown aux state %r" % name)

    def update_metric(self, eval_metric, labels):
        """Per-batch metric update (the one forced sync point per step, like
        the reference's asnumpy in executor_group.py:525)."""
        eval_metric.update(labels, self._exec.outputs)

    @property
    def grad_arrays(self):
        """Gradient arrays aligned 1:1 with param_arrays (reference shape
        [[per-device]]); None entry for params with grad_req null (fixed)."""
        return [[self._exec.grad_dict.get(n)] for n in self.param_names
                if n in self._exec.arg_dict]

    @property
    def param_arrays(self):
        return [[self._exec.arg_dict[n]] for n in self.param_names if n in self._exec.arg_dict]

    def install_monitor(self, mon):
        for exe in self.execs:
            mon.install(exe)
