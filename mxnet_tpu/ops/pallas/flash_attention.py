"""Flash attention — Pallas TPU kernel with online softmax.

The fused fast path behind the MultiHeadAttention op (ops/attention.py) and
the building block of ring attention (parallel/ring_attention.py). Never
materializes the (Tq, Tk) score matrix in HBM: a grid cell owns one query
block, streams key/value blocks through VMEM, and keeps the softmax
running-max/running-sum in registers (f32) — the standard
memory-bandwidth-optimal formulation for the MXU.

Two VMEM regimes, selected per shape:

- **resident** (seq <= _RESIDENT_MAX): the whole K/V (or, in the dK/dV
  kernel, Q/dO) sequence sits in VMEM per grid cell and an in-kernel loop
  walks its tiles with the carry in registers. Fastest form — no scratch
  traffic, minimal grid steps — but VMEM scales with sequence length, so
  it hits the 16 MiB scoped-VMEM wall just past 8k at head_dim 128.
- **streaming** (longer): the sequence streams through an extra innermost
  grid dim one ~SUPER_TARGET-sized superblock at a time, the kernel loops
  the superblock's tiles in registers, and the carry lives in VMEM
  scratch across supersteps. Nothing in VMEM scales with total sequence
  length, so 16k/32k+ train in the same footprint as 4k. Measured ~1.5-2x
  slower than resident at seqs where both run (per-superstep scratch
  spill/fill + grid overhead), which is why it only engages where
  resident cannot run at all.

Falls back to the XLA reference math off-TPU or for non-tile-aligned
shapes, exactly as the reference falls back from cuDNN to the mshadow
kernel (src/operator/convolution.cc cudnn_off path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ...parallel.mesh import partition_mesh

BLOCK_Q = 256
# Round-5 block sweep on v5e (bq x bk over {256,512,1024}x{256,512},
# forward, causal, D=128): bk=512 wins the FORWARD at every selected
# shape — 1.33x @S2048, 1.66x @S4096, 1.18x @S8192/GQA, 1.26x in the
# 16k streaming regime, 1.08x at the S=1024 selection threshold — with
# identical numerics (bf16 maxdiff 0.016 vs the XLA reference,
# unchanged). The backward kernels are insensitive to both block sizes
# (measured flat), so their cost model is untouched. bq=512 adds
# nothing over bq=256 once bk=512.
BLOCK_K = 512
# Selection gate (the cudnn-autotune "must not lose" contract): measured
# on v5e (examples/transformer/bench_transformer.py micro). With the
# round-5 bk=512 tiles the kernel wins from S=512 up — 1.45-1.57x at
# S=512, 2.9-3.4x at S=2048, 4.8-8.5x at S=4096 — and still loses at
# S=256 (0.78-0.93x: too few tiles to amortize the per-block softmax
# bookkeeping vs XLA's fused einsum). Gate re-placed accordingly
# (was 1024 when the 256-wide tiles made S=512 a 0.91x loss).
MIN_SEQ = 512
# Longest sequence whose K/V (one side) stays whole in VMEM: 8192 * 128
# lanes * 2B = 2 MiB per buffer, measured to fit alongside everything
# else; 16384 exceeds the 16 MiB scoped-VMEM limit (the compile error
# that motivated the streaming regime).
_RESIDENT_MAX = 8192
# Streaming superblock target size (keys or queries per grid step).
SUPER_TARGET = 4096
_NEG_INF = float(jnp.finfo(jnp.float32).min)


def _split_super(t, block, target=None):
    """(super, n_super): split a sequence of length t (a multiple of
    `block`, per the kernel contract) into equal superblocks, each a
    multiple of `block`, sized as close to `target` as divisibility
    allows. The superblock is the unit resident in VMEM per grid step;
    `block` stays the unit of one in-kernel loop iteration."""
    target = target or SUPER_TARGET
    nblocks = t // block
    # a target below the block size would start nsup above nblocks and
    # the divisibility walk could never terminate; one block per
    # superblock is the finest legal split
    nsup = min(max(1, -(-t // target)), nblocks)
    while nblocks % nsup:
        nsup += 1
    return t // nsup, nsup


# --- forward, resident regime ----------------------------------------------

def _fa_kernel_res(q_ref, k_ref, v_ref, o_ref, *maybe_lse_ref, causal,
                   scale, block_k, offset):
    """One (batch*kv-head, group, q-block) grid cell. Writes O, and the
    per-row logsumexp when a ref for it is supplied (training forward —
    the blocked backward needs it; inference skips the extra HBM write).

    Grouped-query layout: q is (B*Hkv, G, Tq, D) against k/v (B*Hkv, Tk,
    D) — the G query heads sharing one kv head iterate in the grid's
    middle dim while the k/v block index stays fixed, so K/V are fetched
    into VMEM once per KV head, not once per query head (the h/hkv
    HBM-bandwidth saving GQA exists for). G=1 is standard MHA.

    ``offset`` = tk - tq: causal masking aligns the LAST query with the
    last key (kv-cache decode), matching the XLA paths' (tk - tq) query
    offset (attention.py dot_product_attention / _grouped_attention)."""
    q = q_ref[0, 0].astype(jnp.float32) * scale       # (BQ, D)
    bq = q.shape[0]
    tk = k_ref.shape[1]
    qi = pl.program_id(2)
    num_k_blocks = pl.cdiv(tk, block_k)

    def body(kb, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (BQ, BK)
        if causal:
            q_pos = qi * bq + offset + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])                # (BQ, BK)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    d = q_ref.shape[-1]
    init = (jnp.zeros((bq, d), jnp.float32),
            jnp.full((bq,), _NEG_INF, jnp.float32),
            jnp.zeros((bq,), jnp.float32))
    if causal:
        # only blocks at or left of the (offset) diagonal contribute
        hi = jax.lax.min(num_k_blocks,
                         pl.cdiv((qi + 1) * bq + offset, block_k))
    else:
        hi = num_k_blocks
    acc, m, l = jax.lax.fori_loop(0, hi, body, init)
    o_ref[0, 0] = (acc / l[:, None]).astype(o_ref.dtype)
    if maybe_lse_ref:
        maybe_lse_ref[0][0, 0, 0] = m + jnp.log(l)


# --- forward, streaming regime ---------------------------------------------

def _fa_kernel_stream(q_ref, k_ref, v_ref, o_ref, *rest, causal, scale,
                      block_k, offset, with_lse, num_super):
    """One (batch*kv-head, group, q-block, k-superblock) grid cell. K/V
    stream through the grid's innermost dim one superblock at a time, the
    kernel loops over its block_k tiles with the online-softmax state in
    registers, and the state is carried ACROSS supersteps in VMEM scratch
    (acc, running max, running sum). O/lse flush on the last superstep."""
    lse_ref = rest[0] if with_lse else None
    acc_ref, m_ref, l_ref = rest[-3:]
    bq = q_ref.shape[2]
    sk = k_ref.shape[1]                                # superblock size
    qi = pl.program_id(2)
    ski = pl.program_id(3)
    inner = pl.cdiv(sk, block_k)

    @pl.when(ski == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale    # (BQ, D)

        def body(kb, carry):
            acc, m_prev, l_prev = carry
            k = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(
                jnp.float32)
            v = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(
                jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)    # (BQ, BK)
            if causal:
                q_pos = qi * bq + offset + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, block_k), 0)
                k_pos = (ski * sk + kb * block_k
                         + jax.lax.broadcasted_iota(
                             jnp.int32, (bq, block_k), 1))
                s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
            m_cur = jnp.max(s, axis=1)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new[:, None])            # (BQ, BK)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=1)
            acc = acc * alpha[:, None] + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return acc, m_new, l_new

        if causal:
            # only tiles at or left of the (offset) diagonal contribute
            hi = jnp.clip(
                pl.cdiv((qi + 1) * bq + offset - ski * sk, block_k),
                0, inner)
        else:
            hi = inner
        # run the superblock with a REGISTER-local carry (seeding the
        # loop from scratch refs measured 2x slower — Mosaic pins the
        # carry to VMEM), then merge with the running state through the
        # logsumexp once per superstep — the ring-attention shard merge
        d = q_ref.shape[-1]
        init = (jnp.zeros((bq, d), jnp.float32),
                jnp.full((bq,), _NEG_INF, jnp.float32),
                jnp.zeros((bq,), jnp.float32))
        acc_l, m_l, l_l = jax.lax.fori_loop(0, hi, body, init)
        m_prev, l_prev = m_ref[0], l_ref[0]
        m_new = jnp.maximum(m_prev, m_l)
        a_prev = jnp.exp(m_prev - m_new)
        a_l = jnp.exp(m_l - m_new)
        m_ref[0] = m_new
        l_ref[0] = l_prev * a_prev + l_l * a_l
        acc_ref[...] = (acc_ref[...] * a_prev[:, None]
                        + acc_l * a_l[:, None])

    if causal:
        # supersteps strictly right of the diagonal contribute nothing:
        # skip the compute (their K/V fetch is also elided — the index
        # map clamps to the diagonal superblock, and Pallas only issues
        # a DMA when the block index CHANGES)
        pl.when(ski * sk <= qi * bq + offset + bq - 1)(_compute)
    else:
        _compute()

    @pl.when(ski == num_super - 1)
    def _finalize():
        l = l_ref[0]
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0, 0, 0] = m_ref[0] + jnp.log(l)


def _kv_stream_idx(block_q, super_k, offset, causal):
    """Index map for K/V superblocks streamed under a (b, g, qi, ski)
    grid. Causal grids clamp ski to this q-block's diagonal superblock so
    the fully-masked tail re-addresses the same superblock (no DMA) while
    the kernel skips its compute."""
    if not causal:
        return lambda b, gi, qi, ski: (b, ski, 0)

    def idx(b, gi, qi, ski):
        hi = jax.lax.div(qi * block_q + block_q - 1 + offset, super_k)
        return (b, jnp.minimum(ski, hi), 0)

    return idx


def _fa_forward(q, k, v, causal, scale, interpret, with_lse=False):
    """q: (B*Hkv, G, Tq, D); k/v: (B*Hkv, Tk, D). Returns (B*Hkv, G, Tq,
    D) [+ lse (B*Hkv, G, 1, Tq) — the singleton keeps the last two block
    dims TPU-tileable]."""
    bkv, g, tq, d = q.shape
    tk = k.shape[1]
    block_q = min(BLOCK_Q, tq)
    block_k = _pick_block(tk, BLOCK_K)
    resident = tk <= _RESIDENT_MAX
    kwargs = {}
    out_specs3 = [pl.BlockSpec((1, 1, block_q, d),
                               lambda b, gi, i: (b, gi, i, 0))]
    out_specs4 = [pl.BlockSpec((1, 1, block_q, d),
                               lambda b, gi, i, ski: (b, gi, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((bkv, g, tq, d), q.dtype)]
    if with_lse:
        # (bkv, g, 1, tq): TPU block rules need the last two block dims
        # divisible by (8, 128) or EQUAL to the array dims — the
        # singleton third dim gives (1, BQ) blocks with 1 == array dim
        out_specs3.append(pl.BlockSpec((1, 1, 1, block_q),
                                       lambda b, gi, i: (b, gi, 0, i)))
        out_specs4.append(pl.BlockSpec((1, 1, 1, block_q),
                                       lambda b, gi, i, ski: (b, gi, 0, i)))
        out_shape.append(jax.ShapeDtypeStruct((bkv, g, 1, tq),
                                              jnp.float32))
    cost = pl.CostEstimate(
        flops=4 * bkv * g * tq * tk * d,
        bytes_accessed=(q.size + k.size + v.size) * q.dtype.itemsize,
        transcendentals=bkv * g * tq * tk,
    )
    if resident:
        kernel = functools.partial(_fa_kernel_res, causal=causal,
                                   scale=scale, block_k=block_k,
                                   offset=tk - tq)
        if not interpret:
            kwargs["compiler_params"] = pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"))
        res = pl.pallas_call(
            kernel,
            grid=(bkv, g, pl.cdiv(tq, block_q)),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d),
                             lambda b, gi, i: (b, gi, i, 0)),
                # k/v block index ignores (gi, i): Pallas re-fetches only
                # on index change, so K/V stream from HBM once per KV head
                pl.BlockSpec((1, tk, d), lambda b, gi, i: (b, 0, 0)),
                pl.BlockSpec((1, tk, d), lambda b, gi, i: (b, 0, 0)),
            ],
            out_specs=out_specs3,
            out_shape=out_shape,
            cost_estimate=cost,
            interpret=interpret,
            **kwargs,
        )(q, k, v)
        return (res[0], res[1]) if with_lse else res[0]
    super_k, num_super = _split_super(tk, block_k)
    kernel = functools.partial(_fa_kernel_stream, causal=causal,
                               scale=scale, block_k=block_k,
                               offset=tk - tq, with_lse=with_lse,
                               num_super=num_super)
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary"))
    kv_idx = _kv_stream_idx(block_q, super_k, tk - tq, causal)
    res = pl.pallas_call(
        kernel,
        grid=(bkv, g, pl.cdiv(tq, block_q), num_super),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, gi, i, ski: (b, gi, i, 0)),
            pl.BlockSpec((1, super_k, d), kv_idx),
            pl.BlockSpec((1, super_k, d), kv_idx),
        ],
        out_specs=out_specs4,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),     # acc
            pltpu.VMEM((1, block_q), jnp.float32),     # running max
            pltpu.VMEM((1, block_q), jnp.float32),     # running sum
        ],
        cost_estimate=cost,
        interpret=interpret,
        **kwargs,
    )(q, k, v)
    return (res[0], res[1]) if with_lse else res[0]


# --- blocked backward (FlashAttention-2 style: no S^2 materialization) ------

def _fa_bwd_dq_kernel_res(q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref,
                          dq_ref, *, causal, scale, block_k, offset):
    """dQ for one (batch*kv-head, group, q-block): stream k/v blocks,
    rebuild p from the saved logsumexp, dq += (p * (dO v^T - D)) @ k *
    scale."""
    q = q_ref[0, 0].astype(jnp.float32)            # (BQ, D)
    do = do_ref[0, 0].astype(jnp.float32)          # (BQ, D)
    lse = lse_ref[0, 0, 0]                         # (BQ,)
    dvec = dvec_ref[0, 0, 0]                       # (BQ,)
    bq = q.shape[0]
    tk = k_ref.shape[1]
    qi = pl.program_id(2)
    num_k_blocks = pl.cdiv(tk, block_k)

    def body(kb, dq):
        k = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * bq + offset + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])              # (BQ, BK), rows sum<=1
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dvec[:, None])
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    hi = (jax.lax.min(num_k_blocks,
                      pl.cdiv((qi + 1) * bq + offset, block_k))
          if causal else num_k_blocks)
    dq = jax.lax.fori_loop(0, hi, body,
                           jnp.zeros((bq, q.shape[1]), jnp.float32))
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)


def _fa_bwd_dq_kernel_stream(q_ref, k_ref, v_ref, do_ref, lse_ref,
                             dvec_ref, dq_ref, dq_acc_ref, *, causal,
                             scale, block_k, offset, num_super):
    """dQ for one (batch*kv-head, group, q-block): k/v SUPERBLOCKS stream
    through the grid's innermost dim, the kernel loops their block_k
    tiles rebuilding p from the saved logsumexp, and dq accumulates
    across supersteps in VMEM scratch, flushed on the last superstep."""
    bq = q_ref.shape[2]
    sk = k_ref.shape[1]
    qi = pl.program_id(2)
    ski = pl.program_id(3)
    inner = pl.cdiv(sk, block_k)

    @pl.when(ski == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)        # (BQ, D)
        do = do_ref[0, 0].astype(jnp.float32)      # (BQ, D)
        lse = lse_ref[0, 0, 0]                     # (BQ,)
        dvec = dvec_ref[0, 0, 0]                   # (BQ,)

        def body(kb, dq):
            k = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(
                jnp.float32)
            v = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(
                jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if causal:
                q_pos = qi * bq + offset + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, block_k), 0)
                k_pos = (ski * sk + kb * block_k
                         + jax.lax.broadcasted_iota(
                             jnp.int32, (bq, block_k), 1))
                s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
            p = jnp.exp(s - lse[:, None])          # (BQ, BK), rows sum<=1
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - dvec[:, None])
            return dq + jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        if causal:
            hi = jnp.clip(
                pl.cdiv((qi + 1) * bq + offset - ski * sk, block_k),
                0, inner)
        else:
            hi = inner
        # register-local accumulation, one scratch add per superstep
        # (seeding the loop carry from scratch pins it to VMEM — see the
        # forward kernel's note)
        dq_l = jax.lax.fori_loop(
            0, hi, body,
            jnp.zeros((q_ref.shape[2], q_ref.shape[3]), jnp.float32))
        dq_acc_ref[...] += dq_l

    if causal:
        pl.when(ski * sk <= qi * bq + offset + bq - 1)(_compute)
    else:
        _compute()

    @pl.when(ski == num_super - 1)
    def _finalize():
        dq_ref[0, 0] = (dq_acc_ref[...] * scale).astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel_res(q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref,
                           dk_ref, dv_ref, *, causal, scale, block_q,
                           offset):
    """dK/dV for one (batch*kv-head, k-block) pair: stream q/dO blocks.
    The grid's LAST dim iterates the query-head group sequentially,
    accumulating each group head's contribution into the same dk/dv
    block (the GQA kv gradient is the sum over its group).

    Known tradeoff of this layout: the q/do/lse/dvec block index changes
    every grid step, so those are re-fetched num_k_blocks times per
    group head (vs once in a (bkv, g, kb)-ordered grid — which would
    break the dk/dv accumulation, since Pallas only accumulates across
    CONSECUTIVE revisits of an output block). The kernel is MXU-bound at
    every selected shape, so the extra q-side DMA rides otherwise-idle
    bandwidth: measured fwd+bwd stays within 1-3% of the old full-H
    layout while temp HBM drops g-fold (docs/perf.md GQA table)."""
    k = k_ref[0].astype(jnp.float32)               # (BK, D)
    v = v_ref[0].astype(jnp.float32)               # (BK, D)
    bk = k.shape[0]
    tq = q_ref.shape[2]
    ki = pl.program_id(1)
    gi = pl.program_id(2)
    num_q_blocks = pl.cdiv(tq, block_q)

    def body(qb, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, 0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, 0, pl.ds(qb * block_q, block_q)]
        dvec = dvec_ref[0, 0, 0, pl.ds(qb * block_q, block_q)]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qb * block_q + offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 0)
            k_pos = ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])              # (BQ, BK)
        dv = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dvec[:, None])
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    # causal: q blocks whose last (offset) query position precedes this
    # k block's start contribute nothing (every entry masked)
    lo = (jax.lax.max(ki * bk - offset, 0) // block_q) if causal else 0
    d = k.shape[1]
    dk, dv = jax.lax.fori_loop(
        lo, num_q_blocks, body,
        (jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, d), jnp.float32)))
    dk = (dk * scale).astype(dk_ref.dtype)
    dv = dv.astype(dv_ref.dtype)

    # first group head initializes the output block; later ones add
    @pl.when(gi == 0)
    def _init():
        dk_ref[0] = dk
        dv_ref[0] = dv

    @pl.when(gi > 0)
    def _accum():
        dk_ref[0] += dk
        dv_ref[0] += dv


def _fa_bwd_dkv_kernel_stream(q_ref, k_ref, v_ref, do_ref, lse_ref,
                              dvec_ref, dk_ref, dv_ref, dk_acc_ref,
                              dv_acc_ref, *, causal, scale, block_q,
                              offset, g, num_q_super):
    """dK/dV for one (batch*kv-head, k-block) pair: q/dO/lse/D stream
    through the two inner grid dims (group head, then q-SUPERBLOCK, whose
    block_q tiles the kernel loops over) while K/V stay resident, and
    dk/dv accumulate across ALL of them in f32 VMEM scratch — the GQA kv
    gradient is the sum over the group — flushed once on the final
    (group, q-superblock) step. Nothing in VMEM scales with total
    sequence length."""
    bk = k_ref.shape[1]
    sq = q_ref.shape[2]                            # q superblock size
    ki = pl.program_id(1)
    gi = pl.program_id(2)
    qsi = pl.program_id(3)
    inner = pl.cdiv(sq, block_q)

    @pl.when((gi == 0) & (qsi == 0))
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    def _compute():
        k = k_ref[0].astype(jnp.float32)           # (BK, D)
        v = v_ref[0].astype(jnp.float32)

        def body(qb, carry):
            dk, dv = carry
            q = q_ref[0, 0, pl.ds(qb * block_q, block_q), :].astype(
                jnp.float32)
            do = do_ref[0, 0, pl.ds(qb * block_q, block_q), :].astype(
                jnp.float32)
            lse = lse_ref[0, 0, 0, pl.ds(qb * block_q, block_q)]
            dvec = dvec_ref[0, 0, 0, pl.ds(qb * block_q, block_q)]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if causal:
                q_pos = (qsi * sq + qb * block_q + offset
                         + jax.lax.broadcasted_iota(
                             jnp.int32, (block_q, bk), 0))
                k_pos = ki * bk + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, bk), 1)
                s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
            p = jnp.exp(s - lse[:, None])          # (BQ, BK)
            dv = dv + jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - dvec[:, None])
            dk = dk + jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return dk, dv

        if causal:
            # tiles whose last (offset) query position precedes this k
            # block's start contribute nothing (every entry masked)
            lo = jnp.clip(
                jax.lax.div(ki * bk - offset - qsi * sq, block_q),
                0, inner)
        else:
            lo = 0
        # register-local accumulation, one scratch add per superstep
        d = k_ref.shape[2]
        dk_l, dv_l = jax.lax.fori_loop(
            lo, inner, body,
            (jnp.zeros((bk, d), jnp.float32),
             jnp.zeros((bk, d), jnp.float32)))
        dk_acc_ref[...] += dk_l
        dv_acc_ref[...] += dv_l

    if causal:
        # q superblocks entirely above the diagonal are skipped; their
        # q-side fetches are elided by the clamped index map
        pl.when(qsi * sq + sq - 1 + offset >= ki * bk)(_compute)
    else:
        _compute()

    @pl.when((gi == g - 1) & (qsi == num_q_super - 1))
    def _finalize():
        dk_ref[0] = (dk_acc_ref[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _fa_backward(q, k, v, o, lse, do, causal, scale, interpret,
                 g_lse=None):
    """q/o/do: (B*Hkv, G, Tq, D); k/v: (B*Hkv, Tk, D); lse: (B*Hkv, G, 1,
    Tq). Returns (dq like q, dk/dv like k/v) — dk/dv already summed over
    the query-head group inside the kernel."""
    bkv, g, tq, d = q.shape
    tk = k.shape[1]
    block_q = min(BLOCK_Q, tq)
    block_k = _pick_block(tk, BLOCK_K)
    # D_i = rowsum(dO * O): one cheap fused XLA pass. A cotangent on the
    # logsumexp output folds in here: d(lse)/ds = p, so ds gains
    # +g_lse*p, i.e. D := D - g_lse (ring attention's merge
    # differentiates through lse).
    dvec = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                   axis=-1)[:, :, None, :]         # (bkv, g, 1, tq)
    if g_lse is not None:
        dvec = dvec - g_lse.astype(jnp.float32)
    kwargs3 = {}
    kwargs4 = {}
    if not interpret:
        kwargs3["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"))
        kwargs4["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary"))
    dq_cost = pl.CostEstimate(
        flops=6 * bkv * g * tq * tk * d,
        bytes_accessed=(q.size + k.size + v.size + do.size)
        * q.dtype.itemsize,
        transcendentals=bkv * g * tq * tk)
    if tk <= _RESIDENT_MAX:
        dq = pl.pallas_call(
            functools.partial(_fa_bwd_dq_kernel_res, causal=causal,
                              scale=scale, block_k=block_k,
                              offset=tk - tq),
            grid=(bkv, g, pl.cdiv(tq, block_q)),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d),
                             lambda b, gi, i: (b, gi, i, 0)),
                pl.BlockSpec((1, tk, d), lambda b, gi, i: (b, 0, 0)),
                pl.BlockSpec((1, tk, d), lambda b, gi, i: (b, 0, 0)),
                pl.BlockSpec((1, 1, block_q, d),
                             lambda b, gi, i: (b, gi, i, 0)),
                pl.BlockSpec((1, 1, 1, block_q),
                             lambda b, gi, i: (b, gi, 0, i)),
                pl.BlockSpec((1, 1, 1, block_q),
                             lambda b, gi, i: (b, gi, 0, i)),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, d),
                                   lambda b, gi, i: (b, gi, i, 0)),
            out_shape=jax.ShapeDtypeStruct((bkv, g, tq, d), q.dtype),
            cost_estimate=dq_cost,
            interpret=interpret,
            **kwargs3,
        )(q, k, v, do, lse, dvec)
    else:
        super_k, num_k_super = _split_super(tk, block_k)
        kv_idx = _kv_stream_idx(block_q, super_k, tk - tq, causal)
        dq = pl.pallas_call(
            functools.partial(_fa_bwd_dq_kernel_stream, causal=causal,
                              scale=scale, block_k=block_k,
                              offset=tk - tq, num_super=num_k_super),
            grid=(bkv, g, pl.cdiv(tq, block_q), num_k_super),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d),
                             lambda b, gi, i, ski: (b, gi, i, 0)),
                pl.BlockSpec((1, super_k, d), kv_idx),
                pl.BlockSpec((1, super_k, d), kv_idx),
                pl.BlockSpec((1, 1, block_q, d),
                             lambda b, gi, i, ski: (b, gi, i, 0)),
                pl.BlockSpec((1, 1, 1, block_q),
                             lambda b, gi, i, ski: (b, gi, 0, i)),
                pl.BlockSpec((1, 1, 1, block_q),
                             lambda b, gi, i, ski: (b, gi, 0, i)),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, d),
                                   lambda b, gi, i, ski: (b, gi, i, 0)),
            out_shape=jax.ShapeDtypeStruct((bkv, g, tq, d), q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            cost_estimate=dq_cost,
            interpret=interpret,
            **kwargs4,
        )(q, k, v, do, lse, dvec)

    dkv_cost = pl.CostEstimate(
        # 4 matmuls per (q,k) tile pair: s, p^T@dO, dO@v^T, ds^T@q
        flops=8 * bkv * g * tq * tk * d,
        bytes_accessed=(q.size + k.size + v.size + do.size)
        * q.dtype.itemsize,
        transcendentals=bkv * g * tq * tk)
    if tq <= _RESIDENT_MAX:
        # dk/dv accumulate over the group inside the kernel; for g > 1
        # the running sum lives in the output block, so keep it f32 and
        # cast after (bf16 += per group head would round g times)
        kv_acc_dtype = k.dtype if g == 1 else jnp.float32
        dk, dv = pl.pallas_call(
            functools.partial(_fa_bwd_dkv_kernel_res, causal=causal,
                              scale=scale, block_q=block_q,
                              offset=tk - tq),
            grid=(bkv, pl.cdiv(tk, block_k), g),
            in_specs=[
                pl.BlockSpec((1, 1, tq, d), lambda b, i, gi: (b, gi, 0, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, i, gi: (b, i, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, i, gi: (b, i, 0)),
                pl.BlockSpec((1, 1, tq, d), lambda b, i, gi: (b, gi, 0, 0)),
                pl.BlockSpec((1, 1, 1, tq), lambda b, i, gi: (b, gi, 0, 0)),
                pl.BlockSpec((1, 1, 1, tq), lambda b, i, gi: (b, gi, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, d), lambda b, i, gi: (b, i, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, i, gi: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bkv, tk, d), kv_acc_dtype),
                jax.ShapeDtypeStruct((bkv, tk, d), kv_acc_dtype),
            ],
            cost_estimate=dkv_cost,
            interpret=interpret,
            **kwargs3,
        )(q, k, v, do, lse, dvec)
        return dq, dk.astype(k.dtype), dv.astype(v.dtype)
    super_q, num_q_super = _split_super(tq, block_q)
    # causal: q superblocks strictly above this k block's diagonal are
    # fully masked; clamp their index so the dead steps re-address the
    # previous superblock (no DMA) while the kernel skips their compute
    if causal:
        def q_idx(b, i, gi, qsi):
            lo = jax.lax.div(jax.lax.max(i * block_k - (tk - tq), 0),
                             super_q)
            return (b, gi, jnp.maximum(qsi, lo), 0)

        def qrow_idx(b, i, gi, qsi):
            lo = jax.lax.div(jax.lax.max(i * block_k - (tk - tq), 0),
                             super_q)
            return (b, gi, 0, jnp.maximum(qsi, lo))
    else:
        q_idx = lambda b, i, gi, qsi: (b, gi, qsi, 0)      # noqa: E731
        qrow_idx = lambda b, i, gi, qsi: (b, gi, 0, qsi)   # noqa: E731
    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel_stream, causal=causal,
                          scale=scale, block_q=block_q, offset=tk - tq,
                          g=g, num_q_super=num_q_super),
        grid=(bkv, pl.cdiv(tk, block_k), g, num_q_super),
        in_specs=[
            pl.BlockSpec((1, 1, super_q, d), q_idx),
            pl.BlockSpec((1, block_k, d), lambda b, i, gi, qsi: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, gi, qsi: (b, i, 0)),
            pl.BlockSpec((1, 1, super_q, d), q_idx),
            pl.BlockSpec((1, 1, 1, super_q), qrow_idx),
            pl.BlockSpec((1, 1, 1, super_q), qrow_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, i, gi, qsi: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, gi, qsi: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bkv, tk, d), k.dtype),
            jax.ShapeDtypeStruct((bkv, tk, d), v.dtype),
        ],
        # dk/dv accumulate over the group AND all q superblocks in f32
        # scratch (a bf16 += per contribution would round many times);
        # single cast at the final flush
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        cost_estimate=dkv_cost,
        interpret=interpret,
        **kwargs4,
    )(q, k, v, do, lse, dvec)
    return dq, dk, dv


def _aligned(t, block):
    return t % min(block, t) == 0


# Finest K tile the kernels accept: the CONTRACT is divisibility by this,
# NOT by BLOCK_K — _pick_block falls back from the preferred (faster)
# 512-wide tile to 256 for lengths like 768/1280/2816, so raising
# BLOCK_K never narrows which shapes qualify (ring-attention chunks
# that are odd multiples of 256 keep their flash path).
_MIN_TILE_K = 256


def _pick_block(t, pref):
    """Largest tile in {pref, pref/2, ..., _MIN_TILE_K} dividing t
    (t itself when t < _MIN_TILE_K)."""
    b = min(pref, t)
    while b > _MIN_TILE_K and t % b:
        b //= 2
    return b


def kernel_qualifies(tq, tk, d, compiled=True, causal=False):
    """The kernel's CORRECTNESS contract: sequence lengths divide into
    whole blocks (a ragged final block would read padding into the
    softmax) — K at the finest `_MIN_TILE_K` granularity (the actual
    tile is picked per shape by `_pick_block`); the compiled path
    additionally needs a lane-aligned head_dim; causal calls need
    tq <= tk (with tq > tk the first tk-tq query rows are FULLY masked —
    the XLA path's finfo.min masking degrades to uniform attention
    there, while the kernel's l=0 would produce NaN). Shared by
    flash_attention() and ring_attention's per-shard selection so the
    two paths cannot drift."""
    return (_aligned(tq, BLOCK_Q) and _aligned(tk, _MIN_TILE_K)
            and (not causal or tq <= tk)
            and (not compiled or d % 128 == 0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q3, k3, v3, causal, scale, interpret):
    return _fa_forward(q3, k3, v3, causal, scale, interpret)


def _flash_fwd(q3, k3, v3, causal, scale, interpret):
    out, lse = _fa_forward(q3, k3, v3, causal, scale, interpret,
                           with_lse=True)
    return out, (q3, k3, v3, out, lse)


def _flash_bwd(causal, scale, interpret, res, g):
    # Blocked FlashAttention-2 backward: rebuilds p per tile from the
    # saved logsumexp — never materializes the (Tq, Tk) score matrix, so
    # long-sequence TRAINING scales like the forward (docs/perf.md
    # attention section; previously this was recompute-through-the-
    # reference-math and the S^2 backward dominated at seq >= 4096).
    q3, k3, v3, o3, lse = res
    return _fa_backward(q3, k3, v3, o3, lse, g, causal, scale, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_with_lse(q4, k3, v3, causal, scale, interpret):
    """(out, lse (bkv, g, 1, tq)) variant — ring attention's per-shard
    compute merges across shards using the logsumexp, so lse is a REAL
    output with its own cotangent here (folded into the D-vector in
    backward)."""
    return _fa_forward(q4, k3, v3, causal, scale, interpret, with_lse=True)


def _flash_with_lse_fwd(q3, k3, v3, causal, scale, interpret):
    out, lse = _fa_forward(q3, k3, v3, causal, scale, interpret,
                           with_lse=True)
    return (out, lse), (q3, k3, v3, out, lse)


def _flash_with_lse_bwd(causal, scale, interpret, res, g):
    q3, k3, v3, o3, lse = res
    g_out, g_lse = g
    return _fa_backward(q3, k3, v3, o3, lse, g_out, causal, scale,
                        interpret, g_lse=g_lse)


_flash_with_lse.defvjp(_flash_with_lse_fwd, _flash_with_lse_bwd)


def flash_attention(q, k, v, causal=False, scale=None, interpret=None):
    """Attention over q (B, H, T, D). Pallas on TPU, XLA reference
    otherwise.

    k/v may carry FEWER heads (B, Hkv, Tk, D) with Hkv dividing H
    (grouped-query / multi-query attention): the kernel grids the query
    heads of a group over the same VMEM-resident K/V block, so K/V HBM
    traffic shrinks by h/hkv — no jnp.repeat materialization. Query head
    i attends kv head i // (H/Hkv) (consecutive q heads share a kv head,
    the same convention as attention.py's grouped einsum)."""
    from .. import attention as _att
    from . import on_tpu

    b, h, tq, d = q.shape
    hkv, tk = k.shape[1], k.shape[2]
    if h % hkv:
        raise ValueError("q heads %d not divisible by kv heads %d"
                         % (h, hkv))
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    def fallback():
        if hkv != h:
            return _att._grouped_attention(q, k, v, hkv, causal,
                                           scale=scale)
        return _att.dot_product_attention(q, k, v, causal=causal,
                                          scale=scale)

    # kernel_qualifies = the correctness contract; MIN_SEQ = the measured
    # perf threshold (auto mode only)
    if interpret is None:
        if not (on_tpu()
                and kernel_qualifies(tq, tk, d, causal=causal)
                and tq >= MIN_SEQ):
            return fallback()
        interpret = False
    elif not kernel_qualifies(tq, tk, d, compiled=not interpret,
                              causal=causal):
        # explicit interpret=True/False forces the kernel past the
        # MIN_SEQ perf gate (tests/benches), but never past the block
        # contract
        return fallback()

    g = h // hkv

    def run(q, k, v):
        rows = q.shape[0] * hkv
        out = _flash(q.reshape(rows, g, tq, d), k.reshape(rows, tk, d),
                     v.reshape(rows, tk, d), causal, scale, interpret)
        return out.reshape(q.shape)

    mesh = partition_mesh()
    if mesh is not None:
        # the SPMD partitioner cannot split a Mosaic kernel: split the
        # batch over the data axis by hand (its rows are independent grid
        # cells), or run it whole on every device where it does not divide
        spec = P("data") if b % mesh.shape["data"] == 0 else P()
        run = jax.shard_map(run, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec, check_vma=False)
    return run(q, k, v)
