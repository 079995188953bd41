"""Module — the concrete training module.

Reimplementation of python/mxnet/module/module.py (SURVEY §2.4): bind builds
the (sharded) executor group, init_optimizer selects kvstore placement and
rescale_grad (module.py:432-511), update dispatches to kvstore or local
updater (module.py:553-570), checkpoints include optimizer state
(module.py:135, 674-704).
"""
from __future__ import annotations

import logging

import numpy as np

import jax
import jax.numpy as jnp

from .. import ndarray as nd
from .. import optimizer as opt
from .. import telemetry as _telemetry
from ..optimizer import (Optimizer, cached_lr_wd_arrays, state_leaves,
                         write_state_leaves)
from ..base import MXNetError
from ..context import Context, cpu
from ..initializer import InitDesc, Uniform
from ..model import (
    BatchEndParam, _create_kvstore, _initialize_kvstore,
    _update_params, _update_params_on_kvstore, load_checkpoint,
    save_checkpoint,
)
from ..parallel import collectives as _collectives
from .base_module import BaseModule, _check_input_names
from .executor_group import DataParallelExecutorGroup


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",), label_names=("softmax_label",),
                 logger=logging, context=None, work_load_list=None,
                 fixed_param_names=None, state_names=None,
                 compute_dtype=None):
        """compute_dtype: mixed-precision compute dtype for the bound
        executors ("bfloat16"; master weights stay fp32) — the Module-level
        surface of Executor's compute_dtype / MXNET_COMPUTE_DTYPE."""
        super().__init__(logger=logger)
        if context is None:
            context = [cpu()]
        if isinstance(context, Context):
            context = [context]
        self._context = context
        self._work_load_list = work_load_list
        self._compute_dtype = compute_dtype

        self._symbol = symbol
        data_names = list(data_names) if data_names is not None else []
        label_names = list(label_names) if label_names is not None else []
        state_names = list(state_names) if state_names is not None else []
        fixed_param_names = list(fixed_param_names) if fixed_param_names is not None else []
        _check_input_names(symbol, data_names, "data", True)
        _check_input_names(symbol, label_names, "label", False)
        _check_input_names(symbol, state_names, "state", True)
        _check_input_names(symbol, fixed_param_names, "fixed_param", True)

        arg_names = symbol.list_arguments()
        input_names = data_names + label_names + state_names
        self._param_names = [x for x in arg_names if x not in input_names]
        self._fixed_param_names = fixed_param_names
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = data_names
        self._label_names = label_names
        self._state_names = state_names
        self._output_names = symbol.list_outputs()

        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False

        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None

        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None
        self._fused_fit = None      # lazy fused fit-step state
        self._fit_steps = 0         # fit_step calls: the spans' `step`
        self._fused_dirty = False   # fused params newer than exec buffers
        self._fused_refresh = False  # exec buffers newer than fused snapshot
        self._monitor_installed = False

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """(reference module.py:115 Module.load)."""
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False,
                        async_write=False):
        """(reference module.py:135). ``async_write=True`` overlaps the
        blob writes with continued training (engine-ordered; see
        engine.push_file_write)."""
        self._symbol.save("%s-symbol.json" % prefix)
        param_name = "%s-%04d.params" % (prefix, epoch)
        self.save_params(param_name, async_write=async_write)
        logging.info("Saved checkpoint to \"%s\"", param_name)
        if save_optimizer_states:
            state_name = "%s-%04d.states" % (prefix, epoch)
            self.save_optimizer_states(state_name,
                                       async_write=async_write)
            logging.info("Saved optimizer state to \"%s\"", state_name)

    # --- properties -------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        return [(n, o.shape) for n, o in
                zip(self._output_names, self._exec_group.get_outputs())] \
            if self._exec_group._exec.outputs else None

    def get_params(self):
        assert self.binded and self.params_initialized
        if self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False):
        """(reference module.py:237)."""
        if self.params_initialized and not force_init:
            return
        assert self.binded, "call bind before initializing the parameters"
        # a fused fit-step threads (donated) parameter buffers of its own;
        # materialize them into the exec buffers, then mark the snapshot
        # stale so explicitly-set parameters take effect on the next step
        # (the compiled step program is kept — no per-epoch recompile)
        self._sync_fused_to_exec()
        self._fused_refresh = True

        if self._arg_params is None:
            self._arg_params = {
                n: nd.zeros(a.shape)
                for n, a in self._exec_group._exec.arg_dict.items()
                if n in self._param_names
            }
        if self._aux_params is None:
            self._aux_params = {
                n: nd.zeros(a.shape)
                for n, a in self._exec_group._exec.aux_dict.items()
            }

        attrs = self._symbol.attr_dict()

        def _impl(name, arr, cache):
            if cache is not None:
                if name in cache:
                    cache_arr = cache[name]
                    if cache_arr is not arr:
                        if cache_arr.shape != arr.shape:
                            raise MXNetError(
                                "shape mismatch for %s: %s vs %s"
                                % (name, cache_arr.shape, arr.shape)
                            )
                        cache_arr.copyto(arr)
                else:
                    if not allow_missing:
                        raise RuntimeError("%s is not presented" % name)
                    if initializer is not None:
                        initializer(InitDesc(name, attrs.get(name)), arr)
            else:
                if initializer is not None:
                    initializer(InitDesc(name, attrs.get(name)), arr)

        for name, arr in sorted(self._arg_params.items()):
            _impl(name, arr, arg_params)
        for name, arr in sorted(self._aux_params.items()):
            _impl(name, arr, aux_params)

        self.params_initialized = True
        self._params_dirty = False
        self._exec_group.set_params(self._arg_params, self._aux_params)

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        """(reference module.py:323: builds DataParallelExecutorGroup)."""
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning("Already binded, ignoring bind()")
            return

        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True

        if not for_training:
            assert not inputs_need_grad

        from ..io import DataDesc

        self._data_shapes = [
            x if hasattr(x, "name") else DataDesc(*x) for x in data_shapes
        ]
        if label_shapes is not None:
            self._label_shapes = [
                x if hasattr(x, "name") else DataDesc(*x) for x in label_shapes
            ]
        else:
            self._label_shapes = None

        shared_group = None
        if shared_module is not None:
            assert shared_module.binded and shared_module.params_initialized
            shared_group = shared_module._exec_group

        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list, self._data_shapes,
            self._label_shapes, self._param_names, for_training, inputs_need_grad,
            shared_group, logger=self.logger,
            fixed_param_names=self._fixed_param_names, grad_req=grad_req,
            state_names=self._state_names, compute_dtype=self._compute_dtype,
        )
        if shared_module is not None:
            self.params_initialized = True
            self._arg_params = shared_module._arg_params
            self._aux_params = shared_module._aux_params
        elif self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)

    def _reset_bind(self):
        self.binded = False
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None
        if self._fused_fit:
            # force_rebind discards the fused state: flush its deferred
            # lockstep counts first or _index_update_count permanently
            # lags num_update (save/resume would serialize wrong t)
            self._materialize_fused_counts(self._fused_fit)
        self._fused_fit = None
        self._fused_dirty = False
        self._fused_refresh = False

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),), force_init=False):
        """(reference module.py:432: kvstore selection + rescale_grad)."""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return

        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self._context), self._arg_params
        )

        batch_size = self._exec_group.batch_size
        if kvstore and "dist" in kvstore.type and "_sync" in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        if isinstance(optimizer, str):
            idx2name = dict(enumerate(self._exec_group.param_names))
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt.create(optimizer, sym=self.symbol,
                                   param_idx2name=idx2name, **optimizer_params)
        else:
            assert isinstance(optimizer, opt.Optimizer)

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None

        if kvstore:
            _initialize_kvstore(
                kvstore=kvstore, param_arrays=self._exec_group.param_arrays,
                arg_params=self._arg_params, param_names=self._param_names,
                update_on_kvstore=update_on_kvstore,
            )
        if update_on_kvstore:
            kvstore.set_optimizer(self._optimizer)
        else:
            self._updater = opt.get_updater(optimizer)

        self.optimizer_initialized = True
        self._sync_fused_to_exec()
        self._fused_fit = None  # re-evaluate fused eligibility
        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    def borrow_optimizer(self, shared_module):
        """Share optimizer/updater state with another Module (reference
        module.py borrow_optimizer, used by BucketingModule)."""
        assert shared_module.optimizer_initialized
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        self.optimizer_initialized = True

    # --- computations -----------------------------------------------------
    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        self._sync_fused_to_exec()
        self._exec_group.forward(data_batch, is_train)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._sync_fused_to_exec()
        self._exec_group.backward(out_grads=out_grads)

    def forward_backward(self, data_batch):
        """Fused path: one jitted XLA computation per step."""
        assert self.binded and self.params_initialized
        self._sync_fused_to_exec()
        self._exec_group.forward_backward(data_batch)

    def update(self):
        """(reference module.py:553; model.py:88-110 update paths)."""
        assert self.binded and self.params_initialized and self.optimizer_initialized
        # the manual path mutates exec/updater buffers directly: retire the
        # fused snapshot (its compiled step is kept; fit_step re-snapshots)
        self._sync_fused_to_exec()
        self._fused_refresh = True
        self._params_dirty = True
        if self._update_on_kvstore:
            _update_params_on_kvstore(
                self._exec_group.param_arrays, self._exec_group.grad_arrays,
                self._kvstore,
            )
        else:
            _update_params(
                self._exec_group.param_arrays, self._exec_group.grad_arrays,
                updater=self._updater, num_device=len(self._context),
                kvstore=self._kvstore,
            )

    def update_metric(self, eval_metric, labels):
        self._exec_group.update_metric(eval_metric, labels)

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        return self._exec_group.get_outputs(merge_multi_context=merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and self.inputs_need_grad
        return self._exec_group.get_input_grads(merge_multi_context=merge_multi_context)

    def _sync_params_from_devices(self):
        self._sync_fused_to_exec()
        self._exec_group.get_params(self._arg_params, self._aux_params)
        self._params_dirty = False

    def save_optimizer_states(self, fname, async_write=False):
        assert self.optimizer_initialized
        self._sync_fused_to_exec()
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            from .. import engine

            blob = self._updater.get_states()  # snapshot at call time

            def write():
                # atomic: tmp + os.replace (crash-safe like save_params)
                import os as _os

                with open(fname + ".tmp", "wb") as fout:
                    fout.write(blob)
                _os.replace(fname + ".tmp", fname)

            engine.push_file_write(fname, write, wait=not async_write,
                                   name="save_optimizer_states")

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        self._sync_fused_to_exec()  # keep fused params; pre-load states moot
        self._fused_fit = None      # rebuild so loaded states are picked up
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            from .. import engine

            engine.wait_for_file(fname)
            with open(fname, "rb") as f:
                self._updater.set_states(f.read())

    # --- resumable training state (mxnet_tpu.resilience) ------------------
    def get_checkpoint_state(self):
        """Everything a resumed job needs, as host arrays: f32 master
        params (``param:<name>``), aux states (``aux:<name>``), optimizer
        state leaves (``opt:<name>:<leaf>``), plus an ``opt_meta`` dict
        with the update counts. The flat dict feeds
        ``resilience.checkpoint.save_sharded`` directly; the snapshot is
        consistent (fused/donated buffers are synced out first).

        When the fused ZeRO state is live, the snapshot reads host copies
        straight off the 1/N device shards (``np.asarray`` assembles the
        flat value shard-by-shard on the host — the contiguous layout
        matches ``checkpoint._shard_range``'s divmod plan, so the write
        stays local). The pre-fix path went through ``get_params``, whose
        fused→exec sync ``replicate_place``s every master leaf — committing
        a FULL replicated copy of params + optimizer state to every device
        just to checkpoint them. The fused snapshot stays authoritative;
        exec buffers are not touched."""
        assert self.binded and self.params_initialized
        fs = self._fused_fit if isinstance(self._fused_fit, dict) else None
        if fs is not None and fs.get("z1") and self.optimizer_initialized:
            return self._sharded_checkpoint_state(fs)
        arg_params, aux_params = self.get_params()  # syncs fused → exec
        arrays = {}
        for n, a in arg_params.items():
            arrays["param:%s" % n] = a.asnumpy()
        for n, a in aux_params.items():
            arrays["aux:%s" % n] = a.asnumpy()
        opt_meta = {}
        if self.optimizer_initialized and self._updater is not None:
            nd_dev = len(self._context)
            for pos, n in enumerate(self._exec_group.param_names):
                leaves = state_leaves(
                    self._updater.states.get(pos * nd_dev))
                if leaves is None:
                    continue
                if not isinstance(leaves, tuple):
                    leaves = (leaves,)
                for li, leaf in enumerate(leaves):
                    if leaf is not None:
                        arrays["opt:%s:%d" % (n, li)] = np.asarray(leaf)
            opt_ = self._optimizer
            opt_meta = {
                "num_update": int(opt_.num_update),
                "index_update_count": {
                    str(k): int(v)
                    for k, v in opt_._index_update_count.items()},
            }
        return arrays, opt_meta

    def _sharded_checkpoint_state(self, fs):
        """ZeRO local-write snapshot: host arrays from the live fused
        1/N-sharded params/optimizer state, without replicating anything
        on device (see :meth:`get_checkpoint_state`)."""
        self._materialize_fused_counts(fs)
        arrays = {}
        for n in fs["names"]:
            arrays["param:%s" % n] = np.asarray(fs["params"][n])
            leaves = fs["states"][n]
            if leaves is None:
                continue
            if not isinstance(leaves, tuple):
                leaves = (leaves,)
            for li, leaf in enumerate(leaves):
                if leaf is not None:
                    arrays["opt:%s:%d" % (n, li)] = np.asarray(leaf)
        for n, a in self._exec_group._exec.aux_dict.items():
            arrays["aux:%s" % n] = a.asnumpy()
        opt_ = self._optimizer
        opt_meta = {
            "num_update": int(opt_.num_update),
            "index_update_count": {
                str(k): int(v)
                for k, v in opt_._index_update_count.items()},
        }
        return arrays, opt_meta

    def restore_checkpoint_state(self, arrays, opt_meta=None):
        """Inverse of :meth:`get_checkpoint_state`: install params, aux,
        optimizer-state leaves and update counts from a (possibly
        resharded) ``resilience.checkpoint`` restore. The fused step
        state is retired so the next ``fit_step`` re-snapshots from the
        restored buffers."""
        assert self.binded and self.params_initialized
        arg_params, aux_params, opt_leaves = {}, {}, {}
        for key, a in arrays.items():
            kind, _, rest = key.partition(":")
            if kind == "param":
                arg_params[rest] = nd.array(a)
            elif kind == "aux":
                aux_params[rest] = nd.array(a)
            elif kind == "opt":
                name, _, li = rest.rpartition(":")
                opt_leaves.setdefault(name, {})[int(li)] = a
            else:
                raise MXNetError("unknown checkpoint key %r" % key)
        self.set_params(arg_params, aux_params,
                        allow_missing=not arg_params)
        if not (self.optimizer_initialized and self._updater is not None):
            return
        self._sync_fused_to_exec()
        self._fused_fit = None  # re-snapshot from the restored buffers
        nd_dev = len(self._context)
        exec_ = self._exec_group._exec
        hyper_key = self._optimizer._hyperparam_key()
        for pos, n in enumerate(self._exec_group.param_names):
            entry = opt_leaves.get(n)
            if not entry:
                continue
            st = self._updater.ensure_state(pos * nd_dev,
                                            exec_.arg_dict[n],
                                            key=hyper_key)
            cur = state_leaves(st)
            if isinstance(cur, tuple):
                vals = tuple(
                    None if c is None else jnp.asarray(
                        entry[i]).astype(c.dtype)
                    for i, c in enumerate(cur))
            else:
                vals = jnp.asarray(entry[0]).astype(cur.dtype)
            write_state_leaves(st, vals)
        if opt_meta:
            opt_ = self._optimizer
            opt_.num_update = int(opt_meta.get("num_update",
                                               opt_.num_update))
            opt_._index_update_count = {
                int(k): int(v)
                for k, v in opt_meta.get("index_update_count",
                                         {}).items()}

    # --- fused fit step ---------------------------------------------------
    def fit_step(self, data_batch):
        """ONE donated XLA program per training step (fwd + bwd + optimizer;
        Executor.make_train_step) when the setup allows it — the whole-step
        analogue of the reference's bulk segments + fused optimizer kernels
        (graph_executor.cc:681-759, optimizer_op.cc). Parameters and
        optimizer state are threaded functionally through donated buffers;
        exec/arg_params buffers are refreshed lazily on get_params/eval.
        Falls back to forward_backward + update otherwise."""
        self._fit_steps += 1
        with _telemetry.span("module.fit_step", domain="module",
                             step=self._fit_steps) as sp:
            prepared = self._fit_step_prepare()
            if prepared is None:
                sp.annotate(path="unfused")
                self.forward_backward(data_batch)
                self.update()
                return
            sp.annotate(path="fused")
            self._fit_step_fused(data_batch, *prepared)

    def _fit_step_prepare(self):
        """The host's part of a fused step before the batch: the fused
        state (built on the first call), the hyper-parameter check, the
        update counts and the lr/wd arrays. ``(fs, lr, wd)``, or None when
        the setup is not eligible for the fused step."""
        with _telemetry.span("module.fit_step.prepare", domain="module",
                             lw_rebuilt=False) as sp:
            fs = self._fused_fit_state()
            if fs is not None and fs["hyper"] != self._optimizer._hyperparam_key():
                # a baked-in hyperparameter (momentum/beta warmup schedule)
                # mutated mid-training: the compiled step traced the old value —
                # sync state out and rebuild (same contract as Updater.update_all)
                self._sync_fused_to_exec()
                self._fused_fit = None
                fs = self._fused_fit_state()
            if fs is None:
                return None
            if self._fused_refresh:
                self._refresh_fused_snapshot(fs)
            opt_ = self._optimizer
            idx_of = fs["idx_of"]
            # constant-lr fast path: when the optimizer uses the BASE
            # effective_lr_wd (not a count-dependent override like Adam's
            # bias correction) and has no scheduler, per-param lr/wd only
            # move via optimizer.lr/.wd or the mult dicts — skip the 2x
            # n_params effective_lr_wd rebuild AND the per-param count loop
            # (~1 ms/step combined on ResNet-50). Counts advance in LOCKSTEP
            # in the fused path, so a single pending counter materializes
            # into _index_update_count whenever the fused state is left
            # (_sync_fused_to_exec) or the slow path below needs exact
            # per-index t.
            static_lw = (opt_.lr_scheduler is None
                         and type(opt_).effective_lr_wd
                         is Optimizer.effective_lr_wd)
            if static_lw:
                fs["pending_counts"] = fs.get("pending_counts", 0) + 1
                opt_.num_update += 1
            else:
                self._materialize_fused_counts(fs)
                for n in fs["names"]:
                    opt_._update_count(idx_of[n])
            # the mult dicts are keyed by value: a reassignment, an addition
            # and an in-place change of an entry all rebuild the arrays
            fp = (None if not static_lw
                  else (opt_.lr, opt_.wd, tuple(opt_.lr_mult.items()),
                        tuple(opt_.wd_mult.items())))
            if fp is None or fs.get("lw_fp") != fp or "lw" not in fs:
                lw = np.array([opt_.effective_lr_wd(idx_of[n])
                               for n in fs["names"]], np.float32)
                # lr/wd arrays cached across steps (constant-lr: no re-upload);
                # committed replicated over the data mesh under ZeRO-1 so the
                # sharded step isn't fed single-device arrays
                lw_sh = None
                if fs.get("z1"):
                    from jax.sharding import NamedSharding, PartitionSpec
                    lw_sh = NamedSharding(fs["mesh"], PartitionSpec())
                _, _, fs["lw"] = cached_lr_wd_arrays(fs.get("lw"), lw,
                                                     sharding=lw_sh)
                fs["lw_fp"] = fp
                sp.annotate(lw_rebuilt=True)
            lr_arr, wd_arr = fs["lw"][1], fs["lw"][2]
            return fs, lr_arr, wd_arr

    def _fit_step_fused(self, data_batch, fs, lr_arr, wd_arr):
        # place the batch with the group's device/sharding logic; the
        # step then reads the executor's data buffers (empty feed dict).
        self._load_batch(data_batch)
        _, fs["params"], fs["states"] = fs["step"](
            fs["params"], fs["states"], {}, lr_arr, wd_arr)
        self._params_dirty = True
        self._fused_dirty = True

    def _load_batch(self, data_batch):
        """Place one batch in the executor's input buffers."""
        with _telemetry.span("module.load_data", domain="module") as sp:
            self._exec_group._load_data(data_batch)
            sp.annotate(bytes=sum(
                a.size * np.dtype(a.dtype).itemsize
                for a in list(data_batch.data) + list(data_batch.label or ())
                if hasattr(a, "dtype")))

    # --- fused-step introspection (chip_smoke.py witnesses these) ----------
    @property
    def fit_step_path(self):
        """Which path :meth:`fit_step` takes: ``"fused"`` (the one donated
        program), ``"unfused"`` (forward_backward + update, because the
        setup was ineligible), or None before the first step decides."""
        if self._fused_fit is None:
            return None
        return "fused" if self._fused_fit else "unfused"

    def fit_step_arrays(self):
        """``(params, states)``: the live jax arrays the fused step threads
        (name -> array, name -> optimizer-state leaves), to read placement
        from. The next step donates them: do not hold on to the buffers."""
        if self.fit_step_path != "fused":
            raise MXNetError("fit_step is not on the fused path (%r)"
                             % self.fit_step_path)
        return self._fused_fit["params"], self._fused_fit["states"]

    def lower_fit_step(self):
        """``jax.stages.Lowered`` of the fused step at the bound shapes and
        current state, after at least one step — ``as_text()`` shows which
        kernels the program holds. Traces and lowers; compiles nothing."""
        params, states = self.fit_step_arrays()
        fs = self._fused_fit
        return fs["step"].lower(params, states, {}, fs["lw"][1],
                                fs["lw"][2])

    def _fused_fit_state(self):
        """Build (once) or fetch the fused-step state; None if ineligible."""
        if self._fused_fit is not None:
            return self._fused_fit or None
        import os
        eligible = (
            os.environ.get("MXNET_FUSED_FIT", "1") != "0"
            and self.optimizer_initialized
            and self._kvstore is None
            and not self._update_on_kvstore
            and self._optimizer is not None
            and self._optimizer.pure_rule() is not None
            and not self.inputs_need_grad
            and not self._monitor_installed
        )
        exec_ = self._exec_group._exec
        names = list(self._exec_group.param_names)
        if eligible and any(exec_.grad_req.get(n) != "write" for n in names):
            eligible = False
        if not eligible:
            self._fused_fit = False  # cache the negative
            return None
        rule = self._optimizer.pure_rule()
        # same state keying as the unfused path (model.py _update_params:
        # index*num_device, single device slot 0 in the sharded-exec design)
        nd_dev = len(self._context)
        idx_of = {n: i * nd_dev for i, n in enumerate(names)}

        def update_fn(params, grads, states, lr_arr, wd_arr):
            new_p, new_s = {}, {}
            for pos, n in enumerate(names):
                new_p[n], new_s[n] = rule(params[n], grads[n], states[n],
                                          lr_arr[pos], wd_arr[pos])
            return new_p, new_s

        # ZeRO-1 sharded update (Xu et al.): over the exec group's data
        # mesh, master weights + optimizer state live 1/N-sharded and the
        # step reduce-scatters grads / all-gathers updated weights inside
        # the one donated program (Executor.make_train_step mesh path)
        mesh = getattr(self._exec_group, "mesh", None)
        stage = _collectives.sharded_stage(mesh)
        z1 = stage >= 1
        step = exec_.make_train_step(update_fn, mesh=mesh)
        # device-side copies: the step donates these, and donation must not
        # delete buffers aliased by exec arg_dict / user-held NDArrays
        params, states = self._fused_snapshot(exec_, names, idx_of, mesh, z1)
        hyper_key = self._optimizer._hyperparam_key()
        self._fused_fit = {"step": step, "params": params, "states": states,
                           "names": names, "idx_of": idx_of,
                           "hyper": hyper_key, "mesh": mesh, "z1": z1,
                           "stage": stage}
        return self._fused_fit

    def _fused_snapshot(self, exec_, names, idx_of, mesh, z1):
        """Donation-safe device copies of params + optimizer state for the
        fused step. Under the ZeRO-1 path params are committed straight to
        their 1/N sharded layout and NEW optimizer state is created from the
        sharded weight (born sharded, never replicated-then-sliced);
        pre-existing state copies are resharded once here."""
        with _telemetry.span("module.fused_snapshot", domain="module") as sp:
            hyper_key = self._optimizer._hyperparam_key()
            if z1:
                params = _collectives.zero1_place(
                    {n: exec_.arg_dict[n]._data for n in names}, mesh)
            else:
                params = {n: jnp.array(exec_.arg_dict[n]._data, copy=True)
                          for n in names}
            states = {}
            for n in names:
                i = idx_of[n]
                if z1:
                    self._updater.ensure_state_sharded(i, exec_.arg_dict[n],
                                                       mesh, key=hyper_key)
                    states[n] = _collectives.zero1_place(
                        state_leaves(self._updater.states[i]), mesh)
                else:
                    self._updater.ensure_state(i, exec_.arg_dict[n],
                                               key=hyper_key)
                    states[n] = state_leaves(self._updater.states[i], copy=True)
            sp.annotate(bytes=sum(
                a.size * a.dtype.itemsize
                for a in jax.tree_util.tree_leaves((params, states))))
        return params, states

    def _refresh_fused_snapshot(self, fs):
        """Re-copy params/optimizer state from exec/updater buffers into the
        fused snapshot (after set_params / a manual update), reusing the
        already-compiled step program. Under ZeRO-1 the refreshed copies go
        straight back to the sharded layout the compiled step expects."""
        exec_ = self._exec_group._exec
        fs["params"], fs["states"] = self._fused_snapshot(
            exec_, fs["names"], fs["idx_of"], fs["mesh"], fs["z1"])
        self._fused_refresh = False
        self._fused_dirty = False

    def _materialize_fused_counts(self, fs):
        """Flush the lockstep pending-step counter into the optimizer's
        per-index update counts (fit_step's constant-lr fast path defers
        them; num_update already advanced per step)."""
        pend = fs.pop("pending_counts", 0)
        if not pend:
            return
        opt_ = self._optimizer
        counts = opt_._index_update_count
        for n in fs["names"]:
            i = fs["idx_of"][n]
            counts[i] = counts.get(i, opt_.begin_num_update) + pend

    def _sync_fused_to_exec(self):
        """Refresh executor arg buffers + updater state NDArrays from the
        fused step's threaded (donated) values."""
        fs = self._fused_fit
        if fs:
            self._materialize_fused_counts(fs)
        if not fs or not self._fused_dirty:
            return
        exec_ = self._exec_group._exec
        for n in fs["names"]:
            p, s = fs["params"][n], fs["states"][n]
            if fs.get("z1"):
                # exec/updater storage is replicated: all-gather the 1/N
                # master shards once on the way out (checkpoint/get_params)
                p = _collectives.replicate_place(p, fs["mesh"])
                s = _collectives.replicate_place(s, fs["mesh"])
            exec_.arg_dict[n]._data = p
            write_state_leaves(self._updater.states.get(fs["idx_of"][n]), s)
        self._fused_dirty = False

    def install_monitor(self, mon):
        assert self.binded
        self._monitor_installed = True
        self._sync_fused_to_exec()
        self._fused_fit = None  # monitor needs per-op taps: unfused path
        self._exec_group.install_monitor(mon)
