"""Collective primitives + bandwidth harness.

The reference reduces gradients with hand-written tree-sums and P2P copies
(CommCPU/CommDevice, src/kvstore/comm.h:62-373) and ships a bus-bandwidth
measurement tool (tools/bandwidth/, cited by docs/how_to/perf.md). Here the
primitives are XLA collectives (psum/all_gather/ppermute/reduce_scatter)
addressed by mesh axis name — usable both inside shard_map'd code and, via
the jitted wrappers below, on full arrays from host-level code (the
imperative kvstore path).
"""
from __future__ import annotations

import functools
import os
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map  # the parallel modules import it from here
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def axis_size(axis_name):
    """Static size of a mapped mesh axis (or tuple of axes) from inside
    shard_map'd code."""
    return int(jax.lax.axis_size(axis_name))


# --- in-shard_map primitives (use inside manually-sharded code) -----------
def all_reduce(x, axis_name):
    """Sum across a mesh axis (reference Comm::Reduce, comm.h:18-56)."""
    return jax.lax.psum(x, axis_name)


def all_gather(x, axis_name, axis=0, tiled=True):
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name, axis=0):
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=axis,
                                tiled=True)


def ring_shift(x, axis_name, shift=1):
    """Send shard to the next device along a ring (ppermute) — the
    building block of ring attention and the SPMD pipeline."""
    n = axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name, perm)


# --- ZeRO-1 sharded weight update (Xu et al., "Automatic Cross-Replica
# --- Sharding of Weight Update in Data-Parallel Training") ----------------
#
# The weight-update phase of data-parallel training is redundant: every
# replica applies the same optimizer math to the same (all-reduced)
# gradients. Sharding it means each replica reduce_scatters the gradients,
# updates only its 1/N shard of the f32 master weights and optimizer state,
# and all_gathers the updated weights for the next forward. Per-replica
# optimizer-state memory drops ~N x.
#
# Two realizations live here:
# - spec/placement helpers for the AUTOMATIC (GSPMD) path used by
#   Executor.make_train_step: master weights/optimizer state are committed
#   with zero1_sharding and in-jit sharding constraints let XLA's SPMD
#   partitioner place the collectives (on TPU it fuses the gradient
#   all-reduce + shard into reduce-scatter — the paper's pass).
# - zero1_update_local for MANUAL shard_map code (parallel/transformer.py),
#   where the reduce_scatter/all_gather pair is written out explicitly.

def zero1_enabled(mesh: Optional[Mesh], axis_name: str = "data") -> bool:
    """True when a ZeRO sharded update (any stage >= 1) should be used:
    a mesh with a >1-sized axis_name and no MXNET_SHARDED_UPDATE=0
    opt-out. Callers fall back to the replicated update otherwise."""
    return sharded_stage(mesh, axis_name) >= 1


def sharded_stage(mesh: Optional[Mesh], axis_name: str = "data") -> int:
    """ZeRO stage selected by MXNET_SHARDED_UPDATE (Xu et al. + the
    DeepSpeed/ZeRO staging taxonomy):

      0  replicated update (opt-out)
      1  optimizer state + master weights 1/N at rest; whole-tree weight
         gather per step; gradients reduce-scattered at the end of backward
      2  stage 1 + gradients reduce-scattered AS backward emits them
         (bucketed, overlapping the remaining backward compute) — full
         gradient-tree residency is never required
      3  stage 2 + parameters stay 1/N at rest THROUGH the step: each leaf
         is all-gathered on demand and re-gathered in backward (remat)
         instead of held as a residual — param bytes/chip scale 1/N too

    Default is stage 1 (the shipped ZeRO-1 behavior). 0 when there is no
    mesh or the axis is trivial. Values clamp into [0, 3]."""
    if mesh is None:
        return 0
    if int(dict(mesh.shape).get(axis_name, 0)) <= 1:
        return 0
    raw = os.environ.get("MXNET_SHARDED_UPDATE", "1")
    try:
        stage = int(raw)
    except ValueError:
        stage = 1
    return max(0, min(3, stage))


def zero1_partition_spec(shape, n_shards: int, axis_name: str = "data") -> P:
    """PartitionSpec sharding the FIRST dim divisible by n_shards over
    axis_name. Leaves with no divisible dim stay replicated (per-leaf
    assignment rather than padding: uneven trees round-trip exactly, at
    the cost of keeping those — typically tiny bias/gamma — leaves
    unsharded)."""
    for i, d in enumerate(shape):
        if d >= n_shards and d % n_shards == 0:
            return P(*((None,) * i + (axis_name,)))
    return P()


def zero1_sharding(mesh: Mesh, shape, axis_name: str = "data") -> NamedSharding:
    """NamedSharding for one weight/state leaf under the ZeRO-1 layout."""
    n = int(dict(mesh.shape)[axis_name])
    return NamedSharding(mesh, zero1_partition_spec(shape, n, axis_name))


def zero1_place(tree, mesh: Mesh, axis_name: str = "data"):
    """Materialize every leaf of a weight/optimizer-state tree with its
    sharded NamedSharding — used at FIRST BIND so state is born sharded,
    never replicated-then-sliced. Always returns fresh buffers (safe to
    donate even when a leaf already had the target sharding)."""
    def place(a):
        out = jax.device_put(a, zero1_sharding(mesh, a.shape, axis_name))
        if out is a:
            # device_put with a matching sharding aliases; the caller will
            # donate this buffer, so force a real copy
            out = jnp.array(a, copy=True)
        return out

    return jax.tree_util.tree_map(place, tree)


def zero1_constrain(tree, mesh: Mesh, axis_name: str = "data"):
    """In-jit: pin every leaf to its ZeRO-1 sharding. Applied to the
    gradient tree this turns the data-parallel all-reduce into a
    reduce_scatter (each replica keeps only its shard); applied to the
    update's outputs it keeps new weights/state sharded for donation."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.with_sharding_constraint(
            a, zero1_sharding(mesh, a.shape, axis_name)), tree)


def replicate_constrain(tree, mesh: Mesh):
    """In-jit: gather every leaf to full (replicated) form — the weight
    all_gather ahead of the forward pass."""
    repl = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda a: jax.lax.with_sharding_constraint(a, repl), tree)


def replicate_place(tree, mesh: Mesh):
    """Host-level: all-gather a (possibly ZeRO-sharded) tree into fully
    replicated buffers on the mesh — used when sharded master values are
    synced back into replicated executor/updater/kvstore storage."""
    return jax.device_put(tree, NamedSharding(mesh, P()))


def per_device_bytes(tree) -> int:
    """Max over devices of resident bytes for a pytree of jax arrays —
    the per-replica memory the ZeRO-1 layout is shrinking. Replicated
    leaves count fully on every device; sharded leaves 1/N."""
    per: dict = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is None:
            per[None] = per.get(None, 0) + int(getattr(leaf, "nbytes", 0))
            continue
        for s in shards:
            key = getattr(s.device, "id", s.device)
            per[key] = per.get(key, 0) + int(s.data.nbytes)
    return max(per.values()) if per else 0


def zero1_update_local(w, g, update_fn, axis_name: str = "data",
                       mean_grad: bool = True):
    """ZeRO-1 weight update INSIDE shard_map code: reduce_scatter the
    (flattened, padded) local gradient contribution over axis_name, apply
    `update_fn(w_shard, g_shard)` to this replica's 1/N shard, all_gather
    the updated weights back. The cross-replica gradient mean is folded
    into the reduce_scatter (mean_grad=True); padding makes any leaf shape
    round-trip exactly. w must be replicated over axis_name."""
    n = axis_size(axis_name)
    if n == 1:
        return update_fn(w, g)
    idx = jax.lax.axis_index(axis_name)
    size = w.size
    pad = (-size) % n
    gf = jnp.ravel(g)
    wf = jnp.ravel(w)
    if pad:
        gf = jnp.pad(gf, (0, pad))
        wf = jnp.pad(wf, (0, pad))
    chunk = (size + pad) // n
    g_sh = jax.lax.psum_scatter(gf, axis_name, scatter_dimension=0,
                                tiled=True)
    if mean_grad:
        g_sh = g_sh / n
    w_sh = jax.lax.dynamic_slice(wf, (idx * chunk,), (chunk,))
    new_sh = update_fn(w_sh, g_sh)
    nf = jax.lax.all_gather(new_sh, axis_name, axis=0, tiled=True)
    if pad:
        nf = nf[:size]
    return nf.reshape(w.shape).astype(w.dtype)


# --- ZeRO-2: gradients sharded end-to-end -----------------------------------
#
# Stage 1 lets the full gradient tree materialize out of backward and only
# then pins it to the 1/N layout (one constraint group after jax.vjp
# returns). Stage 2 moves the reduce-scatter INTO backward: each parameter
# leaf is wrapped in an identity whose custom cotangent rule constrains the
# incoming gradient to the sharded layout, so the scatter for leaf L is
# emitted adjacent to L's gradient producer and XLA's latency-hiding
# scheduler overlaps it with the remaining backward compute. Small leaves
# are grouped into flat buckets (MXNET_ZERO2_BUCKET_MB, default 4) so the
# wire carries a few large collectives instead of many tiny ones — the
# classic bucketed reduce-scatter. Values are untouched (layout only).

ZERO2_BUCKET_MB_DEFAULT = 4.0


def zero2_bucket_bytes() -> int:
    try:
        mb = float(os.environ.get("MXNET_ZERO2_BUCKET_MB",
                                  str(ZERO2_BUCKET_MB_DEFAULT)))
    except ValueError:
        mb = ZERO2_BUCKET_MB_DEFAULT
    return max(1, int(mb * 1024 * 1024))


def _grad_ct_constrain(x, sharding):
    """Identity whose COTANGENT is pinned to `sharding` — places the
    gradient reduce-scatter at the leaf's grad-producer site in backward."""

    @jax.custom_vjp
    def ident(v):
        return v

    def fwd(v):
        return v, None

    def bwd(_, ct):
        return (jax.lax.with_sharding_constraint(ct, sharding),)

    ident.defvjp(fwd, bwd)
    return ident(x)


def _grad_ct_bucket(leaves, shardings, flat_sharding):
    """Identity on a tuple of (same-dtype) leaves whose cotangents are
    flattened, concatenated and constrained as ONE flat sharded bucket —
    one collective for the whole group — then split back per leaf."""

    @jax.custom_vjp
    def ident(*vs):
        return tuple(vs)

    def fwd(*vs):
        return tuple(vs), None

    def bwd(_, cts):
        flat = jnp.concatenate([jnp.ravel(c) for c in cts])
        flat = jax.lax.with_sharding_constraint(flat, flat_sharding)
        out, off = [], 0
        for c, sh in zip(cts, shardings):
            piece = jax.lax.dynamic_slice(flat, (off,), (c.size,))
            off += c.size
            out.append(jax.lax.with_sharding_constraint(
                piece.reshape(c.shape), sh))
        return tuple(out)

    ident.defvjp(fwd, bwd)
    return ident(*leaves)


def zero2_grad_scatter(full, mesh: Mesh, axis_name: str = "data",
                       bucket_bytes: Optional[int] = None):
    """Wrap a dict of FULL (gathered) param leaves so backward emits
    reduce-scattered gradient shards bucket-by-bucket as it runs. Returns
    a dict with identical values; only the cotangent layout differs.
    Bucket plan: reverse insertion order (~ backward emission order); a
    leaf >= bucket_bytes scatters on its own, smaller leaves group into
    flat same-dtype buckets up to bucket_bytes."""
    if bucket_bytes is None:
        bucket_bytes = zero2_bucket_bytes()
    n = int(dict(mesh.shape)[axis_name])
    flat_sh = NamedSharding(mesh, P(axis_name))
    out = dict(full)
    group: list = []
    group_dtype = None
    group_bytes = 0

    def flush():
        nonlocal group, group_dtype, group_bytes
        if not group:
            return
        names = [nm for nm, _ in group]
        leaves = [lv for _, lv in group]
        shardings = [zero1_sharding(mesh, lv.shape, axis_name)
                     for lv in leaves]
        wrapped = _grad_ct_bucket(leaves, shardings, flat_sh)
        for nm, w in zip(names, wrapped):
            out[nm] = w
        group, group_dtype, group_bytes = [], None, 0

    for name in reversed(list(full)):
        leaf = full[name]
        nbytes = leaf.size * jnp.dtype(leaf.dtype).itemsize
        if nbytes >= bucket_bytes:
            out[name] = _grad_ct_constrain(
                leaf, zero1_sharding(mesh, leaf.shape, axis_name))
            continue
        if group and (jnp.dtype(leaf.dtype) != group_dtype
                      or group_bytes + nbytes > bucket_bytes):
            flush()
        group.append((name, leaf))
        group_dtype = jnp.dtype(leaf.dtype)
        group_bytes += nbytes
    flush()
    return out


# --- ZeRO-3: parameters sharded at rest, gathered on demand -----------------
#
# The gather for each leaf runs INSIDE the differentiated function and is
# tagged with checkpoint_name; the surrounding jax.checkpoint policy saves
# every residual EXCEPT those tags, so backward re-gathers weights from the
# 1/N shards instead of holding full-weight residuals across the step. The
# gathered copy is therefore transient in both passes (freed after its
# consumers), at the cost of a second gather in backward; XLA's
# latency-hiding scheduler starts gather L+1 while layer L computes — the
# one-layer prefetch.

ZERO3_GATHER_NAME = "zero3_allgather"


def _zero3_gather_leaf(x, repl, grad_sharding):
    """Per-leaf gather with an explicit cotangent rule: fwd gathers the
    shard to full (tagged so the remat policy drops it from residuals);
    bwd pins the incoming gradient straight to the 1/N layout — the
    reduce-scatter happens AT the leaf's grad-producer site, never a full
    replicated gradient (jax's default transpose of a sharding constraint
    would re-replicate the cotangent)."""

    @jax.custom_vjp
    def gather(v):
        return jax.lax.with_sharding_constraint(v, repl)

    def fwd(v):
        return jax.lax.with_sharding_constraint(v, repl), None

    def bwd(_, ct):
        return (jax.lax.with_sharding_constraint(ct, grad_sharding),)

    gather.defvjp(fwd, bwd)
    return _checkpoint_name(gather(x), ZERO3_GATHER_NAME)


def zero3_gather(tree, mesh: Mesh, axis_name: str = "data"):
    """In-jit per-leaf gather-on-demand (use INSIDE the function handed to
    zero3_remat so the re-gather in backward and the remat policy both see
    it). Gradients come back already in the 1/N layout."""
    repl = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda a: _zero3_gather_leaf(
            a, repl, zero1_sharding(mesh, a.shape, axis_name)), tree)


def zero3_remat(f):
    """Wrap the fwd function so gathered weights are NOT saved as
    residuals: policy saves anything except ZERO3_GATHER_NAME tags, so
    the only backward recompute is the (re-)gathers themselves."""
    policy = jax.checkpoint_policies.save_any_names_but_these(
        ZERO3_GATHER_NAME)
    return jax.checkpoint(f, policy=policy)


def stage_train_bytes(params, stage: int, n_shards: int,
                      axis_name: str = "data",
                      bucket_bytes: Optional[int] = None):
    """(param_bytes, grad_bytes) per chip implied by the stage's LAYOUT
    CONTRACT for one train step over `params` (dict name -> array-like).

    This is the model behind the train_param_bytes / train_grad_bytes
    gauges: what the program's sharding constraints bound, not a live
    allocator reading (gradients are in-program transients).

      params: stage <= 2 holds the whole gathered tree through fwd+bwd
              (residuals); stage 3 holds the 1/N shards plus one transient
              gathered leaf (remat frees each copy after use).
      grads:  stage <= 1 lets the full tree materialize before the end-of-
              backward scatter; stage >= 2 bounds residency by the shard
              tree plus one in-flight bucket.

    Leaves with no n-divisible dim stay replicated in every stage (the
    zero1_partition_spec contract)."""
    if bucket_bytes is None:
        bucket_bytes = zero2_bucket_bytes()
    full = 0
    shard = 0
    max_leaf = 0
    for leaf in params.values():
        nbytes = int(leaf.size * jnp.dtype(leaf.dtype).itemsize)
        full += nbytes
        max_leaf = max(max_leaf, nbytes)
        if zero1_partition_spec(leaf.shape, n_shards, axis_name) == P():
            shard += nbytes
        else:
            shard += nbytes // n_shards
    if stage >= 3:
        param_bytes = shard + max_leaf
    elif stage >= 1:
        param_bytes = full + shard
    else:
        param_bytes = full
    if stage >= 2:
        # in-flight transient: one bucket, or one big leaf scattering
        # alone; never worse than the unsharded footprint (a bucket
        # larger than the whole tree degenerates to stage-1 residency)
        grad_bytes = min(full, shard + max(bucket_bytes, max_leaf))
    else:
        grad_bytes = full
    return param_bytes, grad_bytes


# --- host-level collectives over a mesh (imperative kvstore path) ---------
def mesh_all_reduce(x, mesh: Mesh, axis: str = "data"):
    """All-reduce stacked per-device contributions: x has a leading axis of
    size mesh.shape[axis] (one slot per device — the kvstore Push value
    list, kvstore_local.h:50-73); returns the replicated sum without the
    leading axis."""
    n = mesh.shape[axis]
    assert x.shape[0] == n, (x.shape, n)

    def f(s):
        return jax.lax.psum(s[0], axis)

    fn = shard_map(f, mesh=mesh, in_specs=(P(axis),), out_specs=P())
    return fn(x)


def barrier(mesh: Mesh):
    """Cross-device barrier: a tiny all-reduce forced to completion
    (reference ps::Postoffice::Barrier semantics)."""
    x = jnp.zeros((mesh.shape["data"], 1), jnp.float32)
    mesh_all_reduce(x, mesh, "data").block_until_ready()


def bus_bandwidth(mesh: Mesh, axis: str = "data", size_mb: float = 64.0,
                  iters: int = 10, dtype=jnp.float32):
    """Measure all-reduce bus bandwidth over a mesh axis — the analogue of
    the reference's tools/bandwidth harness. Returns GB/s of bus bandwidth
    using the standard ring-allreduce accounting 2*(n-1)/n * bytes."""
    n = int(np.prod([mesh.shape[a] for a in (axis,)]))
    itemsize = jnp.dtype(dtype).itemsize
    num = int(size_mb * 1024 * 1024 / itemsize) // n * n
    x = jnp.ones((num,), dtype)
    x = jax.device_put(x, NamedSharding(mesh, P(axis)))

    def f(s):
        return jax.lax.psum(s, axis)

    fn = jax.jit(shard_map(f, mesh=mesh, in_specs=(P(axis),), out_specs=P()))
    fn(x).block_until_ready()  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(x)
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / iters
    bus_bytes = 2 * (n - 1) / max(n, 1) * num * itemsize
    return bus_bytes / dt / 1e9
