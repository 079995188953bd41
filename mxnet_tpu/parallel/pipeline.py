"""Micro-batched pipeline parallelism over the "pipe" axis.

The reference only has layer-placement model parallelism with no
micro-batching (SURVEY §2.2: group2ctx + PlaceDevice inserting
_CrossDeviceCopy, example/model-parallel-lstm) — its pipeline overlap falls
out of engine dataflow. Here the same overlap is expressed as an SPMD
shift-register: every device runs the identical program, holds one stage's
parameters (sharded over "pipe"), and at each tick applies its stage and
ppermutes the activation to its neighbor.

Two schedules:

- ``spmd_pipeline_local`` — GPipe: n_micro microbatches drain forward in
  n_micro + n_stages - 1 ticks; jax.grad differentiates through the scan,
  so backward SAVES every tick's internal activations (memory grows with
  n_micro × per-tick activations). Fine at small depth; the baseline the
  1F1B schedule is equivalence-tested against.
- ``spmd_pipeline_local_1f1b`` — one-forward-one-backward with per-stage
  recompute, as a custom_vjp: the primal runs the cheap forward-only scan
  (nothing retained but the pipeline INPUTS), and the backward runs an
  interleaved scan where each tick does one forward sub-step and one
  backward sub-step. Stage inputs of in-flight microbatches live in a
  ring buffer of depth 2·n_stages - 1 — at most 2(n-1-s)+1 microbatches
  are in flight between stage s's forward of microbatch i and its
  backward (fwd at tick s+i, bwd at tick 2(n-1)-s+i), so LIVE ACTIVATION
  memory is O(n_stages), independent of n_micro. The stage forward is
  recomputed inside each backward sub-step (jax.vjp), trading ~1 extra
  forward per microbatch-stage for the memory bound — the standard
  1F1B + activation-recompute design.

Neither schedule broadcasts the output across the pipe axis when
``broadcast_out=False``: the (n_micro, mb, ...) output is valid ONLY on
the last pipe rank (zeros elsewhere), and callers reduce to a scalar
loss there and psum THAT (parallel/transformer.py) — replacing the old
full-activation-buffer psum with a scalar collective.

MoE support: with ``with_aux=True`` the stage function returns
(h, aux_scalar) and the pipeline returns (out, aux_sum) where aux_sum is
the psum over pipe ranks of every VALID (stage, microbatch) aux
contribution (bubble ticks are masked out — they run the stage on
garbage). The Switch load-balancing loss rides this channel
(parallel/moe.py switch_moe_local).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from .collectives import axis_size, shard_map


def _fwd_scan(stage_fn, stage_params, x_mb, axis, with_aux):
    """Forward-only GPipe scan. Returns (out, aux_sum_local) where `out`
    is populated ONLY on the last pipe rank (zeros elsewhere) and
    aux_sum_local is this rank's masked aux total (0.0 when not
    with_aux)."""
    n = axis_size(axis)
    idx = jax.lax.axis_index(axis)
    n_micro = x_mb.shape[0]
    steps = n_micro + n - 1
    perm = [(j, (j + 1) % n) for j in range(n)]

    def tick(carry, t):
        h_recv, out, aux_sum = carry
        i = t - idx                     # microbatch this stage works on
        valid = (i >= 0) & (i < n_micro)
        h_in = jnp.where(idx == 0,
                         x_mb[jnp.clip(t, 0, n_micro - 1)], h_recv)
        res = stage_fn(stage_params, h_in)
        if with_aux:
            h_out, aux = res
            aux_sum = aux_sum + jnp.where(valid, aux, 0.0)
        else:
            h_out = res
        h_next = jax.lax.ppermute(h_out, axis, perm)
        slot = t - (n - 1)
        emit = (idx == n - 1) & (slot >= 0)
        out = jnp.where(
            emit,
            jax.lax.dynamic_update_index_in_dim(
                out, h_out, jnp.maximum(slot, 0), 0),
            out)
        return (h_next, out, aux_sum), None

    h0 = jnp.zeros_like(x_mb[0])
    out0 = jnp.zeros_like(x_mb)
    # (1,)-shaped aux carry, NOT a scalar: this jax's shard_map autodiff
    # can't emit rank-0 device-varying residuals (its own error text says
    # to "add at least one (singleton) axis"), and a scalar carry here
    # surfaces as exactly such a residual under jax.grad
    (_, out, aux_sum), _ = jax.lax.scan(
        tick, (h0, out0, jnp.zeros((1,), jnp.float32)), jnp.arange(steps))
    return out, aux_sum


def broadcast_from_last(out, axis):
    """Replicate the last pipe rank's buffer to every rank (the legacy
    output convention; callers that reduce to a scalar on the last rank
    skip this and psum the scalar instead)."""
    idx = jax.lax.axis_index(axis)
    n = axis_size(axis)
    return jax.lax.psum(
        jnp.where(idx == n - 1, out, jnp.zeros_like(out)), axis)


def spmd_pipeline_local(stage_fn, stage_params, x_mb, *, axis="pipe",
                        with_aux=False, broadcast_out=True):
    """Per-device GPipe pipeline body (call inside shard_map).

    stage_fn(stage_params, h) -> h — or (h, aux_scalar) with
    ``with_aux=True``.
    stage_params: this device's stage parameters (leading stage axis
    already consumed by the shard_map in_spec).
    x_mb: (n_micro, mb, ...) all microbatches (replicated).
    Returns (n_micro, mb, ...) outputs of the LAST stage — replicated via
    a psum-broadcast when ``broadcast_out`` (legacy), else valid only on
    the last pipe rank. With ``with_aux`` returns (out, aux_sum) where
    aux_sum is replicated over the pipe axis."""
    out, aux_sum = _fwd_scan(stage_fn, stage_params, x_mb, axis, with_aux)
    if broadcast_out:
        out = broadcast_from_last(out, axis)
    if with_aux:
        return out, jax.lax.psum(aux_sum, axis)[0]
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 3, 4))
def spmd_pipeline_local_1f1b(stage_fn, stage_params, x_mb, axis="pipe",
                             with_aux=False):
    """1F1B pipeline body (call inside shard_map): same contract as
    spmd_pipeline_local(..., broadcast_out=False), but backward memory is
    O(n_stages) instead of O(n_micro) — see the module docstring.
    Always returns (out, aux_sum); aux_sum is 0.0 when not with_aux."""
    out, aux = _fwd_scan(stage_fn, stage_params, x_mb, axis, with_aux)
    return out, jax.lax.psum(aux, axis)[0]


def _1f1b_fwd(stage_fn, stage_params, x_mb, axis, with_aux):
    out, aux = _fwd_scan(stage_fn, stage_params, x_mb, axis, with_aux)
    # residuals: pipeline INPUTS only — every stage activation is
    # recomputed in the backward's fwd sub-steps
    return ((out, jax.lax.psum(aux, axis)[0]), (stage_params, x_mb))


def _1f1b_bwd(stage_fn, axis, with_aux, res, cots):
    stage_params, x_mb = res
    dout, daux = cots
    # mirror the transpose of the primal's `psum(aux)`: the cotangent of
    # each rank's LOCAL aux contribution is the SUM of all ranks' output
    # cotangents (shard_map delivers a replicated output's cotangent
    # split across ranks)
    daux = jax.lax.psum(daux, axis)
    n = axis_size(axis)
    idx = jax.lax.axis_index(axis)
    m = x_mb.shape[0]
    ring_depth = 2 * n - 1           # max in-flight microbatches per stage
    steps = 2 * (n - 1) + m          # last bwd: stage 0, mb m-1
    perm_fwd = [(j, (j + 1) % n) for j in range(n)]
    perm_bwd = [(j, (j - 1) % n) for j in range(n)]

    def stage_h(p, h):
        r = stage_fn(p, h)
        return r if with_aux else (r, jnp.zeros((), jnp.float32))

    def tick(carry, u):
        h_recv, g_recv, ring, dparams, dx = carry

        # ---- forward sub-step (GPipe timing: stage s runs mb u - s) ----
        i = u - idx
        fwd_valid = (i >= 0) & (i < m)
        h_in = jnp.where(idx == 0, x_mb[jnp.clip(u, 0, m - 1)], h_recv)
        ring = jnp.where(
            fwd_valid,
            jax.lax.dynamic_update_index_in_dim(
                ring, h_in, jnp.clip(i, 0, m - 1) % ring_depth, 0),
            ring)
        h_out, _ = stage_h(stage_params, h_in)
        h_next = jax.lax.ppermute(h_out, axis, perm_fwd)

        # ---- backward sub-step (stage s runs bwd of mb u - 2(n-1) + s;
        # the cotangent it needs left stage s+1 on the previous tick) ----
        j = u - 2 * (n - 1) + idx
        bwd_valid = (j >= 0) & (j < m)
        jc = jnp.clip(j, 0, m - 1)
        g_in = jnp.where(idx == n - 1, dout[jc], g_recv)
        h_saved = ring[jc % ring_depth]
        _, vjp_fn = jax.vjp(lambda p, hh: stage_h(p, hh), stage_params,
                            h_saved)
        g_aux = jnp.where(bwd_valid, daux, 0.0)
        dp, dh = vjp_fn((jnp.where(bwd_valid, g_in, jnp.zeros_like(g_in)),
                         g_aux))
        dparams = jax.tree_util.tree_map(
            lambda a, b: a + jnp.where(bwd_valid, b, 0.0), dparams, dp)
        # stage 0's input cotangent belongs to x_mb[j]
        dx = jnp.where(
            bwd_valid & (idx == 0),
            jax.lax.dynamic_update_index_in_dim(dx, dh, jc, 0),
            dx)
        g_next = jax.lax.ppermute(
            jnp.where(bwd_valid, dh, jnp.zeros_like(dh)), axis, perm_bwd)
        return (h_next, g_next, ring, dparams, dx), None

    h0 = jnp.zeros_like(x_mb[0])
    g0 = jnp.zeros_like(x_mb[0])
    ring0 = jnp.zeros((ring_depth,) + x_mb.shape[1:], x_mb.dtype)
    dparams0 = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, a.dtype), stage_params)
    dx0 = jnp.zeros_like(x_mb)
    (_, _, _, dparams, dx), _ = jax.lax.scan(
        tick, (h0, g0, ring0, dparams0, dx0), jnp.arange(steps))
    return dparams, dx


spmd_pipeline_local_1f1b.defvjp(_1f1b_fwd, _1f1b_bwd)


def spmd_pipeline(stage_fn, params, x, mesh: Mesh, n_micro: int,
                  axis: str = "pipe", schedule: str = "gpipe"):
    """Full-array entry. params: pytree with leading axis n_stages
    (sharded over `axis`); x: (batch, ...) split into n_micro microbatches.
    Mainly for tests — real models embed the *_local bodies inside their
    own shard_map (parallel/transformer.py)."""
    n = mesh.shape[axis]
    b = x.shape[0]
    assert b % n_micro == 0
    x_mb = x.reshape((n_micro, b // n_micro) + x.shape[1:])

    def body(p, xm):
        sp = jax.tree_util.tree_map(lambda a: a[0], p)  # squeeze stage axis
        if schedule == "1f1b":
            out, _ = spmd_pipeline_local_1f1b(stage_fn, sp, xm, axis, False)
            return broadcast_from_last(out, axis)
        return spmd_pipeline_local(stage_fn, sp, xm, axis=axis)

    pspec = jax.tree_util.tree_map(lambda _: P(axis), params)
    fn = shard_map(body, mesh=mesh, in_specs=(pspec, P()), out_specs=P(),
                   check_vma=False)
    out = fn(params, x_mb)
    return out.reshape((b,) + out.shape[2:])
