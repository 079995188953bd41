"""KVStore — the communication plane.

TPU-native redesign of src/kvstore/ (SURVEY §2.1 #22-26, §5.8). The
*interface* is the reference's: Init/Push/Pull over integer-or-string keys,
set_updater/set_optimizer, rank/num_workers/barrier, type factory
(`create('local'|'device'|'dist_sync'|'dist_device_sync'|'dist_async')`).

The *mechanism* is not a parameter server: on TPU, gradients produced by a
mesh-sharded executor are already all-reduced in-graph by XLA (ICI
collectives inserted from sharding propagation — the CommDevice P2P reduce,
comm.h:211-373, has no hand-written counterpart). What remains for the
KVStore object is:

- `local`/`device`: aggregate per-device gradient NDArrays (tree-sum on
  device) and run the updater on the merged copy — matching
  KVStoreLocal::Push/Pull (kvstore_local.h:50-88). With one sharded executor
  the per-key list has a single, already-reduced entry.
- `dist_sync`/`dist_device_sync`: the same code over a multi-host runtime
  (jax.distributed): every host holds replicated weights, gradient arrays
  are global jax.Arrays whose reduction rode ICI/DCN inside the step;
  the updater is applied identically on every host (deterministic), which
  IS the sync parameter-server semantics (kvstore_dist_server.h:164-198)
  without the server round-trip.
- `dist_async`: per-host immediate updates (Hogwild semantics,
  kvstore_dist_server.h:199-207) — each host updates its own weight copy
  without a barrier; drift is reconciled on explicit `pull` via mean.
"""
from __future__ import annotations

import os
import pickle
import threading
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from . import ndarray as nd
from . import telemetry
from .telemetry import context as _trace_context
from .base import MXNetError
from .ndarray import NDArray

# traffic counters (default-on; MXNET_TELEMETRY=0 makes inc() a no-op),
# created once at import so the hot path is a single bound-method call —
# the registry surfaces them in exposition()/get_name_value()
_push_total = telemetry.registry.counter(
    "kvstore_push_total", help="kvstore push calls (keys)")
_push_bytes = telemetry.registry.counter(
    "kvstore_push_bytes_total", help="gradient bytes pushed")
_pull_total = telemetry.registry.counter(
    "kvstore_pull_total", help="kvstore pull calls (keys)")
_pull_bytes = telemetry.registry.counter(
    "kvstore_pull_bytes_total", help="weight bytes pulled")
_barrier_total = telemetry.registry.counter(
    "kvstore_barrier_total", help="kvstore barrier calls")


class KVStore:
    def __init__(self, kv_type="local"):
        self.type = kv_type
        self._store: Dict[Any, NDArray] = {}
        self._updater: Optional[Callable] = None
        self._optimizer = None
        self._is_dist = "dist" in kv_type

    # --- identity (reference kvstore.h:223-286) ---------------------------
    @property
    def rank(self) -> int:
        return jax.process_index() if self._is_dist else 0

    @property
    def num_workers(self) -> int:
        return jax.process_count() if self._is_dist else 1

    def barrier(self):
        """Global barrier (reference Barrier → ps::Postoffice::Barrier).
        On jax runtime: a tiny all-reduce forces synchronization."""
        _barrier_total.inc()
        if self._is_dist and jax.process_count() > 1:
            from jax.experimental import multihost_utils

            with telemetry.span("kvstore.barrier", domain="kvstore"):
                multihost_utils.sync_global_devices("kvstore_barrier")

    # --- data plane -------------------------------------------------------
    def init(self, key, value):
        """Initialize key(s) (reference KVStore::Init, kvstore.h:64)."""
        keys, values = _key_value(key, value)
        for k, v in zip(keys, values):
            if k in self._store:
                raise MXNetError("duplicate init of key %r" % (k,))
            self._store[k] = v.copy()

    def push(self, key, value, priority=0):
        """Reduce value(s) into the store; run updater if set
        (reference KVStoreLocal::Push, kvstore_local.h:50-73).

        value may be one NDArray or a list (one per device) per key."""
        keys, grouped = _group_kv(key, value)
        nbytes = 0
        with telemetry.span("kvstore.push", domain="kvstore",
                            n_keys=len(keys)):
            for k, vals in zip(keys, grouped):
                merged = _reduce(vals)
                nbytes += merged._data.nbytes
                if self._updater is not None:
                    if k not in self._store:
                        raise MXNetError(
                            "push to uninitialized key %r" % (k,))
                    stored = self._store[k]
                    ssh = stored._data.sharding
                    gsh = merged._data.sharding
                    if ssh != gsh:
                        if (ssh.device_set == gsh.device_set
                                and not ssh.is_fully_replicated):
                            # the stored master value is deliberately sharded
                            # over the same mesh (ZeRO-1 weight-update
                            # layout): bring the merged gradient TO the
                            # shards (the resharding device_put IS the
                            # reduce_scatter leg) instead of destroying the
                            # stored layout
                            merged = NDArray(jax.device_put(merged._data,
                                                            ssh))
                        else:
                            # adopt the gradient's (mesh) sharding so the
                            # fused update runs where the executor's arrays
                            # live — the analogue of the reference's
                            # merge-buffer placement (comm.h:333-361)
                            stored._data = jax.device_put(stored._data, gsh)
                    self._updater(_updater_key(k), merged, stored)
                else:
                    prev = self._store.get(k)
                    if prev is not None:
                        ssh = prev._data.sharding
                        gsh = merged._data.sharding
                        if (ssh != gsh
                                and ssh.device_set == gsh.device_set
                                and not ssh.is_fully_replicated):
                            # no-updater aggregation must not densify a
                            # deliberately sharded stored value (ZeRO
                            # weight layout): reshard the merged result TO
                            # the stored layout before replacing it —
                            # mirrors the updater branch above
                            merged = NDArray(jax.device_put(merged._data,
                                                            ssh))
                    self._store[k] = merged
        _push_total.inc(len(keys))
        _push_bytes.inc(nbytes)

    def pull(self, key, out=None, priority=0):
        """Broadcast stored value into out array(s) (reference
        KVStoreLocal::Pull → Comm::Broadcast, kvstore_local.h:75-88)."""
        keys, grouped = _group_kv(key, out)
        nbytes = 0
        with telemetry.span("kvstore.pull", domain="kvstore",
                            n_keys=len(keys)):
            for k, outs in zip(keys, grouped):
                if k not in self._store:
                    raise MXNetError("pull of uninitialized key %r" % (k,))
                src = self._store[k]
                for o in outs:
                    # broadcast into the target's own sharding (replicated
                    # over the mesh for params) — Comm::Broadcast
                    # (comm.h:268). When the stored value is ZeRO-1 sharded
                    # (dist_sync with the sharded update) this device_put is
                    # the weight all-gather: the puller always receives full
                    # values, never a bare shard
                    if o._data.sharding != src._data.sharding:
                        o._data = jax.device_put(src._data, o._data.sharding)
                    else:
                        o._data = src._data
                    nbytes += o._data.nbytes
        _pull_total.inc(len(keys))
        _pull_bytes.inc(nbytes)

    # --- updater / optimizer ---------------------------------------------
    def set_updater(self, updater):
        self._updater = updater

    _set_updater = set_updater

    def set_optimizer(self, optimizer):
        """Install an optimizer (reference kvstore.py set_optimizer: pickles
        the optimizer to servers in dist mode; here every host constructs the
        same updater and applies it deterministically)."""
        from . import optimizer as opt

        self._optimizer = optimizer
        self._updater = opt.get_updater(optimizer)

    def save_optimizer_states(self, fname):
        if self._updater is None:
            raise MXNetError("optimizer not set")
        with open(fname, "wb") as f:
            f.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        if self._updater is None:
            raise MXNetError("optimizer not set")
        with open(fname, "rb") as f:
            self._updater.set_states(f.read())

    # --- liveness (reference kvstore_dist.h:159-168) ----------------------
    def num_dead_node(self, node_id=0, timeout_sec=60):
        """Dead-node query. jax.distributed's coordinator enforces liveness
        (failed hosts abort the job), so a live process observes 0."""
        return 0

    def send_command_to_servers(self, head, body):
        pass  # no server processes in the collective design

    def __del__(self):
        pass


def _updater_key(k):
    return int(k) if isinstance(k, (int, np.integer)) or (isinstance(k, str) and k.isdigit()) else k


def _key_value(key, value):
    if isinstance(key, (list, tuple)):
        if isinstance(value, (list, tuple)) and len(key) == len(value):
            return list(key), list(value)
        raise MXNetError("key/value length mismatch")
    return [key], [value]


def _group_kv(key, value):
    """Group duplicate keys (reference GroupKVPairs, kvstore_local.h:95-120)."""
    if not isinstance(key, (list, tuple)):
        key = [key]
        value = [value]
    keys: List[Any] = []
    grouped: List[List[NDArray]] = []
    pos: Dict[Any, int] = {}
    for k, v in zip(key, value):
        vals = v if isinstance(v, (list, tuple)) else [v]
        if k in pos:
            grouped[pos[k]].extend(vals)
        else:
            pos[k] = len(keys)
            keys.append(k)
            grouped.append(list(vals))
    return keys, grouped


def _reduce(vals: List[NDArray]) -> NDArray:
    """Tree-sum on device — the CommDevice::Reduce analogue (comm.h:223).
    For a single (possibly mesh-sharded) array this is a no-copy pass-through
    because XLA already reduced it in-graph."""
    if len(vals) == 1:
        return NDArray(vals[0]._data)
    acc = vals[0]._data
    for v in vals[1:]:
        acc = acc + v._data
    return NDArray(acc)


class PSKVStore(KVStore):
    """Parameter-server-backed dist store (kvstore_server.py): weights live
    on the server; push/pull are RPCs — the reference KVStoreDist worker
    (kvstore_dist.h). Selected when a PS URI is configured; the collective
    (in-graph all-reduce) KVStore remains the default dist path."""

    def __init__(self, kv_type):
        super().__init__(kv_type)
        from . import engine
        from .kvstore_server import PSClient, num_workers

        self._n_workers = num_workers()
        self._rank = int(os.environ.get(
            "MXNET_TPU_WORKER_RANK", os.environ.get("DMLC_WORKER_ID", "0")))
        # rank-tagged client: sync merges dedupe per sender (recovery)
        self._client = PSClient(rank=self._rank)
        # PS RPCs are engine ops with one var per key (the reference's
        # KVStoreDist: ZPush/ZPull run on the engine holding the buffer
        # vars, kvstore_dist.h:233-241) — pushes return immediately and
        # overlap the training step; a pull of the same key orders after
        # every outstanding push of that key.
        self._engine = engine
        self._key_vars = {}
        self._rpc_errs = []
        self._errs_lock = threading.Lock()
        # liveness registration (ps-lite heartbeat analogue): hello on the
        # control channel tells the server this rank is up; the reply says
        # whether this is a RECOVERY (the rank was registered before and
        # its connection dropped — reference kvstore_dist.h:39-42). A
        # recovering worker skips the startup barrier (peers are mid-run
        # and will not join it) and pulls current weights — the server's
        # copy is authoritative.
        self._recovery = (self._client.hello(self._rank) == "recovery"
                          or bool(os.environ.get("MXNET_TPU_IS_RECOVERY")))
        self._hb_stop = threading.Event()
        hb = float(os.environ.get("MXNET_TPU_PS_HEARTBEAT", "2"))

        def _heartbeat_loop():
            while not self._hb_stop.wait(hb):
                try:
                    self._client.heartbeat(self._rank)
                except Exception:
                    return  # server gone; workers fail at the next RPC
        if hb > 0:
            threading.Thread(target=_heartbeat_loop, daemon=True).start()
        if self._rank == 0 and not self._recovery:
            # rank-0 worker announces the consistency mode, as in
            # kvstore.cc:31-38 (kSyncMode command to servers)
            self._client.set_sync("async" not in kv_type)

    def _key_var(self, key):
        v = self._key_vars.get(key)
        if v is None:
            v = self._engine.get().new_variable()
            self._key_vars[key] = v
        return v

    def _record_err(self, e):
        with self._errs_lock:
            self._rpc_errs.append(e)

    def _raise_pending(self):
        with self._errs_lock:
            errs, self._rpc_errs = self._rpc_errs, []
        if errs:
            raise errs[0]

    @property
    def rank(self):
        return self._rank

    @property
    def num_workers(self):
        return self._n_workers

    def init(self, key, value):
        keys, values = _key_value(key, value)
        ctx = _trace_context.current_context()
        for k, v in zip(keys, values):
            arr = v.asnumpy()
            self._engine.get().push(
                lambda k=k, arr=arr, c=ctx: self._safe_rpc(
                    lambda: self._client.init(k, arr), c),
                mutable_vars=[self._key_var(k)], name="ps_init")
        self.barrier()

    def _safe_rpc(self, fn, ctx=None):
        """Run an RPC thunk on the engine worker thread; when the
        submitting thread carried a trace context the caller passes it
        here, so the PSClient serializes it as a traceparent header on
        the wire even though the RPC runs threads away."""
        try:
            if ctx is not None:
                with _trace_context.use(ctx):
                    fn()
            else:
                fn()
        except BaseException as e:  # surface at the next sync point
            self._record_err(e)

    def push(self, key, value, priority=0):
        """Async: the RPC (device readback + wire) runs as an engine op
        holding the key's var — the training thread keeps going, exactly
        the reference's engine-threaded ZPush (kvstore_dist.h:233-241)."""
        import jax.numpy as jnp

        keys, grouped = _group_kv(key, value)
        nbytes = 0
        ctx = _trace_context.current_context()
        with telemetry.span("kvstore.push", domain="kvstore",
                            n_keys=len(keys), ps=True,
                            **(ctx.stamps() if ctx is not None else {})):
            for k, vals in zip(keys, grouped):
                merged = _reduce(vals)  # local device reduce before the wire
                nbytes += merged._data.nbytes
                # device-side copy: the caller's buffer may be DONATED by
                # the next fused step before the engine op reads it back;
                # the copy is a fresh buffer, and the D2H readback still
                # overlaps training inside the engine op
                m = NDArray(jnp.copy(merged._data))
                self._engine.get().push(
                    lambda k=k, m=m, c=ctx: self._safe_rpc(
                        lambda: self._client.push(k, m.asnumpy()), c),
                    mutable_vars=[self._key_var(k)], priority=priority,
                    name="ps_push")
        _push_total.inc(len(keys))
        _push_bytes.inc(nbytes)

    def pull(self, key, out=None, priority=0):
        keys, grouped = _group_kv(key, out)
        ctx = _trace_context.current_context()
        with telemetry.span("kvstore.pull", domain="kvstore",
                            n_keys=len(keys), ps=True,
                            **(ctx.stamps() if ctx is not None else {})):
            self._pull_impl(keys, grouped, priority)
        _pull_total.inc(len(keys))
        _pull_bytes.inc(sum(o._data.nbytes
                            for outs in grouped for o in outs))

    def _pull_impl(self, keys, grouped, priority):
        ctx = _trace_context.current_context()
        for k, outs in zip(keys, grouped):
            ref_shape = tuple(outs[0].shape)

            def do_pull(k=k, outs=outs, ref_shape=ref_shape):
                # element count selects the same shard plan as the push
                # side (kvstore_dist.h EncodeKey); sharded pulls are flat
                val = self._client.pull(k, size=int(np.prod(ref_shape)))
                val = np.asarray(val).reshape(ref_shape)
                for o in outs:
                    # preserve the target's mesh sharding (Comm::Broadcast
                    # semantics), as base KVStore.pull does
                    o._data = jax.device_put(val.astype(o.dtype),
                                             o._data.sharding)

            # engine-ordered after every outstanding push of this key
            self._engine.get().push(
                lambda f=do_pull, c=ctx: self._safe_rpc(f, c),
                mutable_vars=[self._key_var(k)],
                priority=priority, name="ps_pull")
        # one pushed barrier over every pulled key: unlike a per-key
        # wait_for_var loop it is a single engine op and orders after the
        # RPCs' host-side completion as well
        self._engine.fence([self._key_var(k) for k in keys],
                           name="ps_pull_fence").wait()
        self._raise_pending()
        # a completed pull means this worker holds current server weights:
        # recovery is over, future barriers are real again
        self._recovery = False

    def set_optimizer(self, optimizer):
        self._optimizer = optimizer
        if self._rank == 0 and not self._recovery:
            self._client.set_optimizer(optimizer)
        self.barrier()

    def num_dead_node(self, node_id=0, timeout_sec=60):
        """Real liveness count from the server's heartbeat registry
        (reference kvstore_dist.h:159-168 GetDeadNodes): workers whose
        control connection dropped or whose heartbeat is older than
        timeout_sec. Rides the dedicated control channel, so it works
        while this worker's data connections are blocked in a sync-mode
        merge — exactly when survivors need to ask."""
        return len(self._client.dead_nodes(timeout_sec))

    def barrier(self):
        _barrier_total.inc()
        with telemetry.span("kvstore.barrier", domain="kvstore", ps=True):
            # flush every queued push/pull first: a barrier with RPCs still
            # in the engine queue would not be a barrier
            self._engine.fence(list(self._key_vars.values()),
                               name="ps_barrier_fence").wait()
        self._raise_pending()
        if self._recovery:
            # startup barrier skip (reference is_recovery,
            # kvstore_dist.h:77-79): the peers' startup barrier completed
            # long ago; joining a fresh one would hang this worker AND
            # poison the count for the peers' next real barrier
            return
        self._client.barrier()

    def finish_recovery(self):
        """Called (or implied by the first completed pull) once a
        recovering worker has the current weights: rejoin normal barrier
        semantics."""
        self._recovery = False

    def stop_server(self):
        self._engine.fence(list(self._key_vars.values()),
                           name="ps_stop_fence").wait()
        self._raise_pending()
        self._hb_stop.set()
        if self._rank == 0:
            self._client.stop()


def create(name="local") -> KVStore:
    """Factory (reference KVStore::Create, src/kvstore/kvstore.cc:17-45).
    dist types use the in-graph collective store unless a parameter server
    is configured (MXNET_TPU_PS_URI / DMLC_PS_ROOT_URI), in which case the
    PS worker client is returned — the reference's `dist_*` topology."""
    if not isinstance(name, str):
        raise TypeError("name must be string")
    valid = (
        "local", "device", "local_allreduce_cpu", "local_allreduce_device",
        "dist_sync", "dist_device_sync", "dist_async", "dist_sync_device",
    )
    if name not in valid:
        raise MXNetError("unknown kvstore type %r (valid: %s)" % (name, valid))
    if "dist" in name:
        import os

        if os.environ.get("MXNET_TPU_PS_URI") or os.environ.get(
                "DMLC_PS_ROOT_URI"):
            return PSKVStore(name)
    return KVStore(name)
