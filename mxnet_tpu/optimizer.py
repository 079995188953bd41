"""Optimizers.

Reimplementation of python/mxnet/optimizer.py (SURVEY §2.4): registry +
Optimizer base with lr/wd multipliers, the full zoo (SGD w/ momentum, NAG,
SGLD, ccSGD, DCASGD, Adam, AdaGrad, RMSProp, AdaDelta, Ftrl, Test), and the
Updater with state (de)serialization used by KVStore.

The hot updates dispatch to the *fused* update ops
(ops/optimizer_ops.py ≡ src/operator/tensor/optimizer_op.cc) so the whole
step stays on device in one XLA computation.
"""
from __future__ import annotations

import math
import pickle
from typing import Any, Dict, Optional

import numpy as np

from . import ndarray as nd
from .base import MXNetError
from .ndarray import NDArray

__all__ = ["Optimizer", "SGD", "NAG", "SGLD", "ccSGD", "DCASGD", "Adam",
           "AdaGrad", "RMSProp", "AdaDelta", "Ftrl", "Test", "Updater",
           "create", "register", "get_updater"]

opt_registry: Dict[str, type] = {}


def register(klass):
    opt_registry[klass.__name__.lower()] = klass
    return klass



def cached_lr_wd_arrays(cache, lw, sharding=None):
    """(lr_arr, wd_arr, new_cache): re-upload the stacked lr/wd arrays only
    when the host-side values changed — shared by Updater.update_all and
    Module's fused fit step. `sharding` (e.g. replicated over the data
    mesh for the ZeRO-1 sharded update) commits the uploads to the mesh
    so the fused step isn't fed single-device arrays."""
    import jax
    import jax.numpy as jnp

    if cache is None or not np.array_equal(cache[0], lw):
        lr_arr, wd_arr = jnp.asarray(lw[:, 0]), jnp.asarray(lw[:, 1])
        if sharding is not None:
            lr_arr = jax.device_put(lr_arr, sharding)
            wd_arr = jax.device_put(wd_arr, sharding)
        cache = (lw, lr_arr, wd_arr)
    return cache[1], cache[2], cache


def state_leaves(state, copy=False):
    """Raw jax leaves of an optimizer state (None / NDArray / tuple of
    NDArrays) — shared by the batched updater and Module's fused fit step."""
    import jax.numpy as jnp

    def leaf(x):
        if x is None:
            return None
        return jnp.array(x._data, copy=True) if copy else x._data

    if state is None:
        return None
    if isinstance(state, tuple):
        return tuple(leaf(x) for x in state)
    return leaf(state)


def write_state_leaves(state, leaves):
    """Write raw leaves back into the state's NDArrays (inverse of
    state_leaves)."""
    if state is None:
        return
    if isinstance(state, tuple):
        for old, val in zip(state, leaves):
            if old is not None:
                old._data = val
    else:
        state._data = leaves


def _zeros_like_state(weight):
    """State buffer matching the weight's dtype AND (mesh) sharding, so fused
    updates run where the weight lives."""
    import jax.numpy as jnp

    return NDArray(jnp.zeros_like(weight._data))

class Optimizer:
    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.lr_mult = {}
        self.wd_mult = {}
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count = {}
        self.clip_gradient = clip_gradient
        if param_idx2name is None:
            param_idx2name = {}
        self.idx2name = param_idx2name.copy()
        self.sym = sym
        if sym is not None:
            attrs = sym.attr_dict()
            for name in sym.list_arguments():
                if name in attrs:
                    if "__lr_mult__" in attrs[name]:
                        self.lr_mult[name] = float(attrs[name]["__lr_mult__"])
                    if "__wd_mult__" in attrs[name]:
                        self.wd_mult[name] = float(attrs[name]["__wd_mult__"])

    @staticmethod
    def create_optimizer(name, **kwargs):
        if name.lower() in opt_registry:
            return opt_registry[name.lower()](**kwargs)
        raise ValueError("Cannot find optimizer %s" % name)

    def create_state(self, index, weight):
        return None

    def update(self, index, weight, grad, state):
        raise NotImplementedError()

    def effective_lr_wd(self, index):
        """(lr, wd) actually applied for this key at the current step —
        schedule, lr/wd multipliers, and any step-count folding (Adam bias
        correction) resolved host-side so the device rule stays static."""
        return self._get_lr(index), self._get_wd(index)

    def pure_rule(self):
        """Return fn(w, g, state, lr, wd) -> (new_w, new_state), a pure
        traceable update with hyperparameters closed over, or None if this
        optimizer has no pure form (then the per-key eager path is used).
        lr/wd arrive as dynamic scalars so LR schedules don't retrace.
        Other hyperparameters (momentum, betas, rescale_grad, clip) are
        baked in at trace time — callers caching a compiled rule must
        re-trace if they mutate them (Updater.update_all keys its cache on
        Optimizer._hyperparam_key() for this reason).
        Enables Updater.update_all: the whole parameter tree updated in ONE
        jitted program — the analogue of the reference running its fused
        optimizer kernels (optimizer_op.cc) inside engine bulk segments."""
        return None

    def _pure_prep_grad(self, g, w, wd):
        import jax.numpy as jnp
        g = g * self.rescale_grad
        if self.clip_gradient is not None and self.clip_gradient > 0:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        return g + wd * w

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult.update(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        """Reference semantics (optimizer.py set_wd_mult): params whose name
        does not end in _weight/_gamma default to wd_mult 0, symbol attrs
        override, explicit args override both."""
        self.wd_mult = {}
        for n in self.idx2name.values():
            if not (n.endswith("_weight") or n.endswith("_gamma")):
                self.wd_mult[n] = 0.0
        if self.sym is not None:
            attrs = self.sym.attr_dict()
            for name in self.sym.list_arguments():
                if name in attrs and "__wd_mult__" in attrs[name]:
                    self.wd_mult[name] = float(attrs[name]["__wd_mult__"])
        self.wd_mult.update(args_wd_mult)

    def _update_count(self, index):
        if index not in self._index_update_count:
            self._index_update_count[index] = self.begin_num_update
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index], self.num_update)

    def _get_lr(self, index):
        if self.lr_scheduler is not None:
            lr = self.lr_scheduler(self.num_update)
        else:
            lr = self.lr
        name = self.idx2name.get(index, index)
        if name in self.lr_mult:
            lr *= self.lr_mult[name]
        return lr

    def _get_wd(self, index):
        wd = self.wd
        name = self.idx2name.get(index, index)
        if isinstance(name, str) and name not in self.wd_mult:
            # reference default: no decay for bias / bn params
            if name.endswith("_bias") or name.endswith("_gamma") or name.endswith("_beta"):
                wd = 0.0
        if name in self.wd_mult:
            wd *= self.wd_mult[name]
        return wd

    def _clip_attr(self):
        return -1.0 if self.clip_gradient is None else self.clip_gradient

    # attrs that either enter the jitted rule dynamically (lr/wd via the
    # stacked lr_arr/wd_arr) or are pure bookkeeping — everything else is
    # baked into pure_rule() at trace time and must invalidate caches.
    _DYNAMIC_OR_BOOKKEEPING = frozenset({
        "lr", "wd", "lr_scheduler", "lr_mult", "wd_mult", "idx2name",
        "sym", "num_update", "begin_num_update", "_index_update_count"})

    def _hyperparam_key(self):
        """Hashable tuple of every scalar hyperparameter closed over by
        pure_rule(). Updater.update_all keys its compiled-rule cache on this
        so mutating e.g. momentum/beta1 mid-training (a warmup schedule)
        re-traces instead of being silently ignored on the batched path."""
        items = []
        for k in sorted(vars(self)):
            if k in self._DYNAMIC_OR_BOOKKEEPING:
                continue
            v = getattr(self, k)
            if isinstance(v, np.generic):
                v = v.item()  # np.float32 etc. compare like Python scalars
            if v is None or isinstance(v, (int, float, bool, str)):
                items.append((k, v))
            else:
                # non-scalar hyperparam (array/list/...): key on repr so a
                # mutation still invalidates rather than silently vanishing
                items.append((k, repr(v)))
        return tuple(items)


# convenience alias (reference keeps `create` at module level)
def create(name, **kwargs):
    return Optimizer.create_optimizer(name, **kwargs)


@register
class SGD(Optimizer):
    """SGD with momentum using the fused sgd(_mom)_update kernels."""

    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return _zeros_like_state(weight)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        attrs = {"lr": lr, "wd": wd, "rescale_grad": self.rescale_grad,
                 "clip_gradient": self._clip_attr()}
        if state is None:
            nd.sgd_update(weight, grad, out=weight, **attrs)
        else:
            res = nd.sgd_mom_update(weight, grad, state, momentum=self.momentum, **attrs)
            weight._data = res[0]._data
            state._data = res[1]._data

    def pure_rule(self):
        mom = self.momentum

        def rule(w, g, s, lr, wd):
            g = self._pure_prep_grad(g, w, wd)
            if s is None:
                return w - lr * g, None
            m = mom * s - lr * g
            return w + m, m

        return rule


@register
class NAG(SGD):
    """Nesterov accelerated SGD (reference optimizer.py NAG)."""

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = nd.clip(g, a_min=-self.clip_gradient, a_max=self.clip_gradient)
        if state is not None:
            mom = state
            mom._data = (mom * self.momentum)._data
            g = g + wd * weight
            mom._data = (mom + g)._data
            g = g + self.momentum * mom
            weight._data = (weight - lr * g)._data
        else:
            weight._data = (weight - lr * (g + wd * weight))._data

    def pure_rule(self):
        mom = self.momentum

        def rule(w, g, s, lr, wd):
            g = self._pure_prep_grad(g, w, wd)  # rescale+clip+wd, as update()
            if s is None:
                return w - lr * g, None
            m = s * mom + g
            return w - lr * (g + mom * m), m

        return rule


@register
class SGLD(Optimizer):
    """Stochastic gradient Langevin dynamics (reference optimizer.py SGLD)."""

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = nd.clip(g, a_min=-self.clip_gradient, a_max=self.clip_gradient)
        noise = nd.array(
            np.random.normal(0, math.sqrt(lr), size=weight.shape).astype(np.float32),
            ctx=weight.context,
        )
        weight._data = (weight - (lr / 2) * (g + wd * weight) + noise)._data


@register
class ccSGD(SGD):
    """Kept for API parity (reference ccSGD is SGD with C++ impl)."""


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (reference optimizer.py DCASGD)."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.weight_previous = {}
        self.lamda = lamda

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return (None, weight.copy())
        return (_zeros_like_state(weight), weight.copy())

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = nd.clip(g, a_min=-self.clip_gradient, a_max=self.clip_gradient)
        mon, previous_weight = state
        comp = g + wd * weight + self.lamda * g * g * (weight - previous_weight)
        if mon is not None:
            mon._data = (self.momentum * mon - lr * comp)._data
            delta = mon
        else:
            delta = -lr * comp
        previous_weight._data = weight._data
        weight._data = (weight + delta)._data


@register
class Adam(Optimizer):
    """Adam using the fused adam_update kernel; bias correction folded into
    lr as in the reference (optimizer.py Adam)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (_zeros_like_state(weight), _zeros_like_state(weight))

    def effective_lr_wd(self, index):
        # fold bias correction into lr host-side (reference optimizer.py Adam)
        t = self._index_update_count.get(index, self.begin_num_update) or 1
        lr, wd = self._get_lr(index), self._get_wd(index)
        coef1 = 1.0 - self.beta1 ** t
        coef2 = 1.0 - self.beta2 ** t
        return lr * math.sqrt(coef2) / coef1, wd

    def pure_rule(self):
        b1, b2, eps = self.beta1, self.beta2, self.epsilon

        def rule(w, g, s, lr, wd):
            import jax.numpy as jnp
            mean, var = s
            g = self._pure_prep_grad(g, w, wd)
            mean_t = b1 * mean + (1 - b1) * g
            var_t = b2 * var + (1 - b2) * jnp.square(g)
            return w - lr * mean_t / (jnp.sqrt(var_t) + eps), (mean_t, var_t)

        return rule

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        coef1 = 1.0 - self.beta1 ** t
        coef2 = 1.0 - self.beta2 ** t
        lr *= math.sqrt(coef2) / coef1
        mean, var = state
        res = nd.adam_update(
            weight, grad, mean, var, lr=lr, wd=wd, beta1=self.beta1, beta2=self.beta2,
            epsilon=self.epsilon, rescale_grad=self.rescale_grad,
            clip_gradient=self._clip_attr(),
        )
        weight._data = res[0]._data
        mean._data = res[1]._data
        var._data = res[2]._data


@register
class AdaGrad(Optimizer):
    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return _zeros_like_state(weight)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = nd.clip(g, a_min=-self.clip_gradient, a_max=self.clip_gradient)
        history = state
        history._data = (history + g * g)._data
        weight._data = (weight - lr * (g / nd.sqrt(history + self.float_stable_eps) + wd * weight))._data

    def pure_rule(self):
        eps = self.float_stable_eps

        def rule(w, g, s, lr, wd):
            import jax.numpy as jnp
            g = g * self.rescale_grad
            if self.clip_gradient is not None and self.clip_gradient > 0:
                g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
            h = s + g * g
            return w - lr * (g / jnp.sqrt(h + eps) + wd * w), h

        return rule


@register
class RMSProp(Optimizer):
    """RMSProp; centered=True selects the Graves'13 variant, matching the
    fused rmsprop_update / rmspropalex_update split (optimizer.py RMSProp)."""

    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1 = gamma1
        self.gamma2 = gamma2
        self.centered = centered
        self.epsilon = epsilon
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        if self.centered:
            return (_zeros_like_state(weight), _zeros_like_state(weight),
                    _zeros_like_state(weight))
        return (_zeros_like_state(weight),)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        kwargs = {"lr": lr, "wd": wd, "rescale_grad": self.rescale_grad,
                  "gamma1": self.gamma1, "epsilon": self.epsilon,
                  "clip_gradient": self._clip_attr(),
                  "clip_weights": self.clip_weights if self.clip_weights else -1.0}
        if not self.centered:
            (n,) = state
            res = nd.rmsprop_update(weight, grad, n, **kwargs)
            weight._data = res[0]._data
            n._data = res[1]._data
        else:
            n, g, delta = state
            res = nd.rmspropalex_update(weight, grad, n, g, delta,
                                        gamma2=self.gamma2, **kwargs)
            weight._data = res[0]._data
            n._data = res[1]._data
            g._data = res[2]._data
            delta._data = res[3]._data

    def pure_rule(self):
        g1, g2, eps = self.gamma1, self.gamma2, self.epsilon
        cw = self.clip_weights if self.clip_weights else -1.0
        centered = self.centered

        def rule(w, g, s, lr, wd):
            import jax.numpy as jnp
            g = self._pure_prep_grad(g, w, wd)
            if not centered:
                (n,) = s
                n_t = (1 - g1) * jnp.square(g) + g1 * n
                w_t = w - lr * g / jnp.sqrt(n_t + eps)
                if cw > 0:
                    w_t = jnp.clip(w_t, -cw, cw)
                return w_t, (n_t,)
            n, gs, delta = s
            n_t = (1 - g1) * jnp.square(g) + g1 * n
            g_t = (1 - g1) * g + g1 * gs
            d_t = g2 * delta - lr * g / jnp.sqrt(n_t - jnp.square(g_t) + eps)
            w_t = w + d_t
            if cw > 0:
                w_t = jnp.clip(w_t, -cw, cw)
            return w_t, (n_t, g_t, d_t)

        return rule


@register
class AdaDelta(Optimizer):
    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho = rho
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (_zeros_like_state(weight), _zeros_like_state(weight))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        wd = self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = nd.clip(g, a_min=-self.clip_gradient, a_max=self.clip_gradient)
        acc_g, acc_delta = state
        acc_g._data = (self.rho * acc_g + (1 - self.rho) * g * g)._data
        current_delta = nd.sqrt(acc_delta + self.epsilon) / nd.sqrt(acc_g + self.epsilon) * g
        acc_delta._data = (self.rho * acc_delta + (1 - self.rho) * current_delta * current_delta)._data
        weight._data = (weight - current_delta - wd * weight)._data

    def pure_rule(self):
        rho, eps = self.rho, self.epsilon

        def rule(w, g, s, lr, wd):
            import jax.numpy as jnp
            g = g * self.rescale_grad
            if self.clip_gradient is not None and self.clip_gradient > 0:
                g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
            acc_g, acc_d = s
            acc_g_t = rho * acc_g + (1 - rho) * g * g
            cur = jnp.sqrt(acc_d + eps) / jnp.sqrt(acc_g_t + eps) * g
            acc_d_t = rho * acc_d + (1 - rho) * cur * cur
            return w - cur - wd * w, (acc_g_t, acc_d_t)

        return rule


@register
class Ftrl(Optimizer):
    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1 = lamda1
        self.beta = beta

    def create_state(self, index, weight):
        return (_zeros_like_state(weight), _zeros_like_state(weight))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = nd.clip(g, a_min=-self.clip_gradient, a_max=self.clip_gradient)
        z, n_ = state
        sigma = -nd.sqrt(n_)
        n_._data = (n_ + g * g)._data
        sigma += nd.sqrt(n_)
        sigma /= lr
        z._data = (z + g - sigma * weight)._data
        w_np = z.asnumpy()
        n_np = n_.asnumpy()
        new_w = np.where(
            np.abs(w_np) > self.lamda1,
            -(w_np - np.sign(w_np) * self.lamda1)
            / ((self.beta + np.sqrt(n_np)) / lr + wd),
            0.0,
        ).astype(np.float32)
        weight[:] = new_w


@register
class Test(Optimizer):
    """Simple test optimizer (reference optimizer.py Test)."""

    def create_state(self, index, weight):
        return _zeros_like_state(weight)

    def update(self, index, weight, grad, state):
        weight._data = (weight + grad * self.rescale_grad)._data
        state._data = weight._data


def _state_structure(s):
    """Nested (shape, dtype) signature of an optimizer state tree — used to
    detect when a hyperparameter mutation changed what create_state returns
    (e.g. momentum 0.0 -> 0.9 turns a None state into a buffer)."""
    if s is None:
        return None
    if isinstance(s, tuple):
        return tuple(_state_structure(x) for x in s)
    return (tuple(s.shape), str(s.dtype))


class Updater:
    """Closure applying an optimizer keyed by integer index — the object the
    reference installs into KVStore (optimizer.py get_updater / :768ff)."""

    def __init__(self, optimizer: Optimizer):
        self.optimizer = optimizer
        self.states = {}
        self._state_keys = {}
        self._tree_fn = None
        self._tree_keys = None
        self._lw_cache = None

    def ensure_state(self, index, weight, key=None):
        """Create — or structurally refresh — the state for `index`.
        Refresh matters when a hyperparameter mutation changes the state
        create_state would build: raising momentum from 0.0 (state None) to
        nonzero mid-training must materialize a real momentum buffer, or the
        retraced rule silently keeps running momentum-free SGD.
        Callers looping over many params pass the precomputed `key` so the
        sorted-vars walk happens once per step, not once per param. The
        throwaway create_state on a key change is bounded to once per
        hyperparam mutation (or checkpoint restore) per param — rare events."""
        if key is None:
            key = self.optimizer._hyperparam_key()
        if index not in self.states:
            self.states[index] = self.optimizer.create_state(index, weight)
        elif self._state_keys.get(index) != key:
            fresh = self.optimizer.create_state(index, weight)
            if _state_structure(fresh) != _state_structure(self.states[index]):
                self.states[index] = fresh
        self._state_keys[index] = key
        return self.states[index]

    def ensure_state_sharded(self, index, weight, mesh, axis_name="data",
                             key=None):
        """ensure_state with the weight viewed in its ZeRO-1 layout, so NEW
        state buffers are BORN 1/N-sharded across the data axis
        (_zeros_like_state inherits the weight's sharding) instead of
        allocated replicated and resharded later. Existing states are
        returned untouched — callers reshard those copies themselves."""
        import jax

        from .parallel.collectives import zero1_sharding

        w = weight._data
        sh = zero1_sharding(mesh, w.shape, axis_name)
        if w.sharding != sh:
            w = jax.device_put(w, sh)
        return self.ensure_state(index, NDArray(w), key=key)

    def __call__(self, index, grad, weight):
        self.ensure_state(index, weight)
        self.optimizer.update(index, weight, grad, self.states[index])

    def update_all(self, pairs):
        """Apply the optimizer to many (index, grad, weight) pairs in ONE
        jitted XLA program (optimizer.pure_rule), instead of one dispatch
        per key — the whole-tree analogue of the reference executing its
        fused optimizer kernels (optimizer_op.cc) under engine bulk
        segments. Falls back to per-key eager updates when the optimizer
        has no pure rule. lr/wd enter as dynamic scalars (no retrace when
        an LR schedule changes them)."""
        import jax
        import jax.numpy as jnp

        rule = self.optimizer.pure_rule()
        if rule is None:
            hyper_key = self.optimizer._hyperparam_key()
            for index, grad, weight in pairs:
                self.ensure_state(index, weight, key=hyper_key)
                self.optimizer.update(index, weight, grad, self.states[index])
            return
        opt = self.optimizer
        hyper_key = opt._hyperparam_key()
        for index, _, weight in pairs:
            self.ensure_state(index, weight, key=hyper_key)
            opt._update_count(index)

        keys = tuple(sorted(p[0] for p in pairs))
        by_idx = {p[0]: p for p in pairs}
        weights = {str(i): by_idx[i][2]._data for i in keys}
        grads = {str(i): by_idx[i][1]._data for i in keys}
        states = {str(i): state_leaves(self.states[i]) for i in keys}
        # lr/wd ship as TWO stacked arrays (one h2d transfer each), not
        # hundreds of scalar buffers; indexed inside the jitted program.
        # Cached across steps: constant-lr training re-uploads nothing.
        lw = np.array([opt.effective_lr_wd(i) for i in keys], np.float32)
        lr_arr, wd_arr, self._lw_cache = cached_lr_wd_arrays(
            self._lw_cache, lw)

        if (self._tree_fn is None or self._tree_keys != keys
                or getattr(self, "_tree_hyper", None) != hyper_key):
            def tree_update(weights, grads, states, lr_arr, wd_arr):
                new_w, new_s = {}, {}
                for pos, i in enumerate(keys):
                    k = str(i)
                    new_w[k], new_s[k] = rule(weights[k], grads[k],
                                              states[k], lr_arr[pos],
                                              wd_arr[pos])
                return new_w, new_s

            # donate only the states: weight buffers can be aliased by
            # user-held NDArrays (set_params / _put fast path), and donation
            # would delete them under the caller
            self._tree_fn = jax.jit(tree_update, donate_argnums=(2,))
            self._tree_keys = keys
            self._tree_hyper = hyper_key

        new_w, new_s = self._tree_fn(weights, grads, states, lr_arr, wd_arr)
        for i in keys:
            k = str(i)
            by_idx[i][2]._data = new_w[k]
            write_state_leaves(self.states[i], new_s[k])

    def set_states(self, states):
        blob = pickle.loads(states)
        counts = blob.pop("__update_counts__", None)
        if counts is not None:
            # restore per-index step counts so bias-corrected optimizers
            # (Adam) continue from the right timestep after resume
            self.optimizer._index_update_count = dict(counts)
            if counts:
                self.optimizer.num_update = max(
                    self.optimizer.num_update, max(counts.values()))
        restored = {}
        for k, v in blob.items():
            if isinstance(v, tuple):
                restored[k] = tuple(None if x is None else nd.array(x) for x in v)
            elif v is None:
                restored[k] = None
            else:
                restored[k] = nd.array(v)
        self.states = restored
        self._state_keys = {}  # restored states re-validate lazily

    def get_states(self):
        def conv(v):
            if v is None:
                return None
            if isinstance(v, tuple):
                return tuple(None if x is None else x.asnumpy() for x in v)
            return v.asnumpy()

        blob = {k: conv(v) for k, v in self.states.items()}
        blob["__update_counts__"] = dict(self.optimizer._index_update_count)
        return pickle.dumps(blob)


def get_updater(optimizer: Optimizer) -> Updater:
    return Updater(optimizer)
