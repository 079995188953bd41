"""Flash attention — Pallas TPU kernel with online softmax.

The fused fast path behind the MultiHeadAttention op (ops/attention.py) and
the building block of ring attention (parallel/ring_attention.py). Never
materializes the (Tq, Tk) score matrix in HBM: a grid cell owns one query
block (one key block in the dK/dV kernel), walks the other side's tiles
through VMEM, and keeps the softmax statistics and every accumulator in
f32 — the standard memory-bandwidth-optimal formulation for the MXU.

Four kernels. Three of them (forward, dq, dkv) are each ONE grid over
(batch*kv-head, ..., block, superblock): the walked side arrives one
superblock per grid step, an in-kernel loop walks the superblock's tiles,
and the state (acc / running max / running sum, or dq, or dk and dv) lives
in VMEM scratch that the tile body updates in place, so it crosses tiles
and superblocks the same way. The superblock is sized per shape:

- **resident** (seq <= _RESIDENT_MAX): one superblock, the whole K/V (or,
  in the dK/dV kernel, Q/dO) sequence in VMEM per grid cell — fewest grid
  steps, but VMEM scales with sequence length, so it hits the 16 MiB
  scoped-VMEM wall just past 8k at head_dim 128.
- **streaming** (longer): ~SUPER_TARGET-sized superblocks through the
  grid's innermost dim. Nothing in VMEM scales with total sequence
  length, so 16k/32k+ train in the same footprint as 4k.

The fourth is the **fused backward** (`_fa_bwd_fused_kernel`): dq, dk and
dv from one walk over the live tiles, so s, p, dp and ds are rebuilt once
and a tile costs five products where dq and dkv together cost seven, and
q, dO, lse and D are fetched once a query superblock and not once a key
block. dk's and dv's f32 accumulators are whole in VMEM (2 tk x d x 4
bytes); dq's holds one query superblock, the query side arriving one
superblock per grid step. `_fa_backward` reads from a byte count of the
shapes (`_fused_bwd_vmem_bytes` against `_SCOPED_VMEM`, the VMEM a kernel
gets without asking) the longest superblock that fits (`_fused_q_super`):

- **whole** where the query sequence fits as one superblock (4096 x 4096
  in bfloat16 at head_dim 128): the grid has no superblock axis;
- **superblocked** where only shorter ones fit (8192 in bfloat16: 2048
  rows; 4096 in float32: 2048): a key block wholly past a superblock's
  diagonal or outside its band is a dead grid step, its K/V index
  clamped to a live block (no DMA) and no tile walked;
- the **dq and dkv kernels** where not even one query tile fits, because
  dk's and dv's accumulators alone fill the 16 MiB (16384 in bfloat16).

Measured on a v5e (PERF.md, Findings): 3.51 ms against 2.13 + 2.78 at
4096 causal, 12 heads a KV head, and no slower at any shape it fits; in
superblocks of 2048 at 8192, 7.95 ms against 4.60 + 6.13 (28 heads over
4 KV heads), and 1024-row superblocks 6-9% slower than 2048-row ones.

What the tile loop costs besides its matrix products decides the speed
(PERF.md, Findings, PRs 26 and 32; measured on a v5e at 4096 causal, 12
query heads a KV head):

- the state is updated IN VMEM, not carried through the loop: a carry of
  96-128 vregs (acc, and m and l at one row per sublane) does not fit the
  64 registers, and the loop spilled it at its top and filled it at its
  bottom, 250 of 700 bundles a forward tile;
- dK/dV are computed in TRANSPOSED form (scores as (BK, BQ) tiles: k q^T,
  p^T dO, v dO^T, ds^T q), so every product contracts over the last dim
  of one side, no tile goes through the XLU, and lse and D are used in
  the (1, Tq) layout they are stored in; the fused kernel adds dq's
  product to that body, (ds^T)^T k, the one tile that is transposed;
- 512 x 512 tiles: the MXU loads a 128 x 128 weight tile in the time it
  multiplies 128 rows, so the rows streamed per weight tile (BQ; BK in
  dkv) set how much of its time goes to products;
- the loops are bound by the MXU, not by their bundles: a fused tile is
  2489 bundles where dq's is 1637 and dkv's 2048, and the chip reads 84%
  of the MXU's peak for its five products (dkv 88% for its four).

Operands go to the MXU as f32 and are rounded there to bf16 in its one
pass (measured: the product of f32 operands equals that of their bf16
roundings to 6e-8), so widening the stored bf16 costs the MXU nothing and
rounds p and ds for free; feeding the stored dtype and masking only the
tiles on the diagonal were measured too and gave nothing (same place;
bf16 operands in the fused kernel: 3.475 against 3.517 ms, PR 32).

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
from ..registry import current_node, note_built

# Preferred tiles of all four kernels (`_pick_block` falls back to a
# divisor of the length). PR-26 sweep on v5e, bq x bk over {256,512,1024}
# x {128..1024}, causal S=4096 D=128 group 12, ms a call (PERF.md,
# Findings, PR 26): forward 256x512 2.25, 512x256 2.81, 512x512 2.06,
# 512x1024 2.30, 1024x512 2.20; dq 256x512 2.68, 512x512 2.41, 1024x512
# 2.48, 1024x1024 2.42; dkv 256x512 3.86, 512x512 3.03, 512x1024 3.17,
# 1024x512 3.17. The backward kernels are NOT flat in them any more.
# Fused backward, PR-32 sweep at the same shape: 512x512 3.52, 1024x512
# 3.62, 512x1024 3.57, 256x512 4.37, 512x256 4.29, 256x1024 4.02,
# 1024x256 3.97.
BLOCK_Q = 512
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
_LANES = 128
# A head of 64 runs in 64-lane blocks (`kernel_qualifies`). On a 128 x 128
# MXU neither score product can be full at that head size, whatever the
# layout: q k^T contracts over 64 of the array's 128 rows, and p v fills 64
# of its 128 columns, so a tile takes the time of a head of 128 for half
# the FLOPs, and 50% of the peak is these kernels' ceiling. Two heads side
# by side in 128 lanes change nothing of that (their scores differ, so the
# products stay two, each half empty; only the accumulators' elementwise
# updates would fill their vregs), and heads padded with zeros to 128 do
# the same MXU work while q, k, v, o and their gradients cross HBM at twice
# the size. What the 64-lane blocks cost in VMEM is a head of 128's: a row
# of 64 takes a whole vreg row (`_fused_bwd_vmem_bytes` counts it so).
_HALF_LANES = 64


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


def _superblocks(t, block):
    """(super, n_super) of the walked side: whole while it fits VMEM."""
    return (t, 1) if t <= _RESIDENT_MAX else _split_super(t, block)


# --- what the four tile bodies share ------------------------------------------

_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_NN = (((1,), (0,)), ((), ()))     # a @ b
_TN = (((0,), (0,)), ((), ()))     # a.T @ b


def _dot(a, b, dims):
    """f32 x f32 -> f32: the MXU rounds both operands to bf16 in its one
    pass, so this is the bf16 product at f32 accumulation."""
    return jax.lax.dot_general(a.astype(jnp.float32), b.astype(jnp.float32),
                               dims, preferred_element_type=jnp.float32)


def _keep(shape, q0, k0, q_axis, window=0):
    """Causal keep-mask of one score tile whose first query sits at
    position q0 and first key at k0, queries along ``q_axis``. Positions
    are absolute and a query's includes ``offset`` = tk - tq: causal
    masking aligns the LAST query with the last key (kv-cache decode),
    matching the XLA paths' (tk - tq) query offset (attention.py
    dot_product_attention / _grouped_attention). A ``window`` keeps the
    last ``window`` keys up to the query's own: q - window < k <= q."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    if window:
        return (q_pos >= k_pos) & (k_pos > q_pos - window)
    return q_pos >= k_pos


def _lanes(x, n):
    """x (rows, _LANES), one value a row in every lane, at n lanes: whole
    vregs are reused, nothing moves."""
    if n == x.shape[1]:
        return x
    if n < x.shape[1]:
        return x[:, :n]                 # a head of 64: the vreg's first half
    if n % x.shape[1] == 0:
        return pltpu.repeat(x, n // x.shape[1], 1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _when(known, cond):
    """``pl.when(cond)``, or the bare call where the trace already knows
    that cond holds (one superblock): no branch in the kernel and no
    `cond` to trace."""
    return (lambda body: body()) if known else pl.when(cond)


def _key_tiles(causal, q0, bq, k_base, block_k, n, window=0):
    """(lo, hi): which of the n key tiles from position k_base the query
    block at q0 walks. A causal one stops at the (offset) diagonal; a
    band starts at the tile that holds the first key its first query
    sees, q0 - window + 1."""
    if not causal:
        return 0, n
    hi = jnp.clip(pl.cdiv(q0 + bq - k_base, block_k), 0, n)
    if not window:
        return 0, hi
    lo = jnp.maximum(q0 - window + 1 - k_base, 0) // block_k
    return jnp.minimum(lo, hi), hi


def _query_tiles(causal, k0, bk, q_base, block_q, n, window=0):
    """(lo, hi): which of the n query tiles from (offset) position q_base
    the key block at k0 is walked by, the transposed ``_key_tiles``: from
    the tile on the diagonal to the tile that holds the last query whose
    band reaches the block, k0 + bk + window - 2."""
    if not causal:
        return 0, n
    lo = jnp.clip((k0 - q_base) // block_q, 0, n)
    if not window:
        return lo, n
    hi = jnp.clip(pl.cdiv(k0 + bk + window - 1 - q_base, block_q), 0, n)
    return jnp.minimum(lo, hi), hi


def _walk(tile, lo, hi):
    """Run ``tile(i)`` for i in [lo, hi): the state is in VMEM refs, so
    the loop carries nothing."""
    def body(i, carry):
        tile(i)
        return carry

    jax.lax.fori_loop(lo, hi, body, 0)


# --- forward -------------------------------------------------------------------

def _fa_kernel(q_ref, k_ref, v_ref, o_ref, *rest, causal, scale, block_k,
               offset, with_lse, num_super, window=0):
    """One (batch*kv-head, group, q-block, k-superblock) grid cell: the
    online softmax of the query block over the superblock's block_k
    tiles, its state (acc, running max, running sum; the last two one
    value a row in every lane) in VMEM scratch across tiles and
    supersteps. Writes O on the last superstep, and the per-row logsumexp
    when a ref for it is supplied (training forward — the blocked
    backward needs it; inference skips the extra HBM write).

    Grouped-query layout: q is (B*Hkv, G, Tq, D) against k/v (B*Hkv, Tk,
    D) — the G query heads sharing one kv head iterate in the grid's
    second dim while the k/v block index stays fixed, so K/V are fetched
    into VMEM once per KV head, not once per query head (the h/hkv
    HBM-bandwidth saving GQA exists for). G=1 is standard MHA."""
    lse_ref = rest[0] if with_lse else None
    acc_ref, m_ref, l_ref = rest[-3:]
    bq, d = acc_ref.shape
    sk = k_ref.shape[1]                                # superblock size
    q0 = pl.program_id(2) * bq + offset
    ski = pl.program_id(3)
    k_base = ski * sk
    one = num_super == 1

    @_when(one, ski == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale    # (BQ, D)

        def tile(kb):
            rows = pl.ds(kb * block_k, block_k)
            s = _dot(q, k_ref[0, rows, :], _NT)        # (BQ, BK)
            if causal:
                s = jnp.where(_keep(s.shape, q0, k_base + kb * block_k, 0,
                                    window), s, _NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - _lanes(m_new, block_k))
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
            m_ref[...] = m_new
            acc_ref[...] = (acc_ref[...] * _lanes(alpha, d)
                            + _dot(p, v_ref[0, rows, :], _NN))

        _walk(tile, *_key_tiles(causal, q0, bq, k_base, block_k,
                                sk // block_k, window))

    # causal: supersteps strictly right of the diagonal contribute nothing,
    # nor do those wholly left of a band: skip the compute (their K/V fetch
    # is also elided — the index map clamps to the live superblocks, and
    # Pallas only issues a DMA when the block index CHANGES)
    _when(one or not causal, _super_live(k_base, sk, q0, bq, window))(
        _compute)

    @_when(one, ski == num_super - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / _lanes(l, d)).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0, 0, 0] = (m_ref[...] + jnp.log(l))[:, 0]


def _super_live(k_base, sk, q0, bq, window):
    """Whether the key superblock [k_base, k_base + sk) holds a key that
    the causal query block at (offset) position q0 sees."""
    live = k_base <= q0 + bq - 1
    if window:
        live &= k_base + sk - 1 >= q0 - window + 1
    return live


def _kv_stream_idx(block_q, super_k, offset, causal, window=0):
    """Index map for K/V superblocks streamed under a (b, g, qi, ski)
    grid. Causal grids clamp ski to this q-block's diagonal superblock so
    the fully-masked tail re-addresses the same superblock (no DMA) while
    the kernel skips its compute; a band clamps it from below too, to the
    superblock of the first key the block's first query sees."""
    if not causal:
        return lambda b, gi, qi, ski: (b, ski, 0)

    def idx(b, gi, qi, ski):
        hi = jax.lax.div(qi * block_q + block_q - 1 + offset, super_k)
        ski = jnp.minimum(ski, hi)
        if window:
            ski = jnp.maximum(ski, jax.lax.div(jnp.maximum(
                qi * block_q + offset - window + 1, 0), super_k))
        return (b, ski, 0)

    return idx


def _compiler_params(interpret, grid_dims=4):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel",) + ("arbitrary",) * (grid_dims - 1))}


def _fa_forward(q, k, v, causal, scale, interpret, with_lse=False,
                window=0):
    """q: (B*Hkv, G, Tq, D); k/v: (B*Hkv, Tk, D). Returns (B*Hkv, G, Tq,
    D) [+ lse (B*Hkv, G, 1, Tq) — the singleton keeps the last two block
    dims TPU-tileable]. ``window`` > 0 (causal only) bands the scores:
    the tile walk starts where the band does."""
    bkv, g, tq, d = q.shape
    tk = k.shape[1]
    block_q = _pick_block(tq, BLOCK_Q)
    block_k = _pick_block(tk, BLOCK_K)
    super_k, num_super = _superblocks(tk, block_k)
    q_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda b, gi, i, ski: (b, gi, i, 0))
    # k/v block index ignores (gi, i): Pallas re-fetches only on index
    # change, so resident K/V stream from HBM once per KV head
    kv_spec = pl.BlockSpec((1, super_k, d),
                           _kv_stream_idx(block_q, super_k, tk - tq, causal,
                                          window))
    out_specs = [q_spec]
    out_shape = [jax.ShapeDtypeStruct((bkv, g, tq, d), q.dtype)]
    if with_lse:
        # (bkv, g, 1, tq): TPU block rules need the last two block dims
        # divisible by (8, 128) or EQUAL to the array dims — the
        # singleton third dim gives (1, BQ) blocks with 1 == array dim
        out_specs.append(pl.BlockSpec((1, 1, 1, block_q),
                                      lambda b, gi, i, ski: (b, gi, 0, i)))
        out_shape.append(jax.ShapeDtypeStruct((bkv, g, 1, tq),
                                              jnp.float32))
    res = pl.pallas_call(
        functools.partial(_fa_kernel, causal=causal, scale=scale,
                          block_k=block_k, offset=tk - tq,
                          with_lse=with_lse, num_super=num_super,
                          window=window),
        grid=(bkv, g, tq // block_q, num_super),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),         # acc
            pltpu.VMEM((block_q, _LANES), jnp.float32),    # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),    # running sum
        ],
        cost_estimate=pl.CostEstimate(
            flops=4 * bkv * g * tq * tk * d,
            bytes_accessed=(q.size + k.size + v.size) * q.dtype.itemsize,
            transcendentals=bkv * g * tq * tk),
        interpret=interpret,
        **_compiler_params(interpret),
    )(q, k, v)
    return (res[0], res[1]) if with_lse else res[0]


# --- blocked backward (FlashAttention-2 style: no S^2 materialization) ------

def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref,
                      dq_ref, dq_acc_ref, *, causal, scale, block_k, offset,
                      num_super, window=0):
    """dQ for one (batch*kv-head, group, q-block, k-superblock): rebuild p
    from the saved logsumexp tile by tile, dq += (p * (dO v^T - D)) @ k in
    VMEM scratch, scaled and written on the last superstep."""
    bq = q_ref.shape[2]
    sk = k_ref.shape[1]
    q0 = pl.program_id(2) * bq + offset
    ski = pl.program_id(3)
    k_base = ski * sk
    one = num_super == 1

    @_when(one, ski == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    def _compute():
        q, do = q_ref[0, 0], do_ref[0, 0]              # (BQ, D)
        lse, dvec = lse_ref[0, 0, 0], dvec_ref[0, 0, 0]    # (BQ,)

        def tile(kb):
            rows = pl.ds(kb * block_k, block_k)
            k, v = k_ref[0, rows, :], v_ref[0, rows, :]
            s = _dot(q, k, _NT) * scale
            if causal:
                s = jnp.where(_keep(s.shape, q0, k_base + kb * block_k, 0,
                                    window), s, _NEG_INF)
            # the two columns are spread across lanes HERE, tile by tile:
            # spread once before the loop they hold 2 x BQ/8 vregs through
            # it, and the kernel measured 9% slower
            p = jnp.exp(s - lse[:, None])              # rows sum <= 1
            ds = p * (_dot(do, v, _NT) - dvec[:, None])
            dq_acc_ref[...] += _dot(ds, k, _NN)

        _walk(tile, *_key_tiles(causal, q0, bq, k_base, block_k,
                                sk // block_k, window))

    _when(one or not causal, _super_live(k_base, sk, q0, bq, window))(
        _compute)

    @_when(one, ski == num_super - 1)
    def _finalize():
        dq_ref[0, 0] = (dq_acc_ref[...] * scale).astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref,
                       dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *, causal,
                       scale, block_q, offset, g, num_super, window=0):
    """dK/dV for one (batch*kv-head, k-block): q/dO/lse/D arrive through
    the two inner grid dims (group head, then q-superblock, whose block_q
    tiles the loop walks) while K/V stay put, and dk/dv accumulate across
    ALL of them in f32 VMEM scratch — the GQA kv gradient is the sum over
    the group — written once, in the output dtype, on the final (group,
    q-superblock) step.

    TRANSPOSED form: scores as (BK, BQ) tiles, so the four products (k
    q^T, p^T dO, v dO^T, ds^T q) contract over the last dim of one side
    and no tile is transposed; lse and D are (1, BQ) rows, the layout
    they are stored in.

    Known tradeoff of this grid: the q/do/lse/dvec block index changes
    with the group head, so those are re-fetched num_k_blocks times per
    group head (vs once in a (bkv, g, kb)-ordered grid — which would
    break the dk/dv accumulation across the group). The q-side DMA rides
    otherwise-idle bandwidth: measured fwd+bwd stayed within 1-3% of the
    old full-H layout while temp HBM drops g-fold (docs/perf.md GQA
    table)."""
    bk = k_ref.shape[1]
    sq = q_ref.shape[2]                            # q superblock size
    k0 = pl.program_id(1) * bk
    gi = pl.program_id(2)
    qsi = pl.program_id(3)
    q_base = qsi * sq + offset
    one = num_super == 1

    @_when(one and g == 1, (gi == 0) & (qsi == 0))
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    def _compute():
        k, v = k_ref[0], v_ref[0]                      # (BK, D)

        def tile(qb):
            rows = pl.ds(qb * block_q, block_q)
            q, do = q_ref[0, 0, rows, :], do_ref[0, 0, rows, :]
            st = _dot(k, q, _NT) * scale               # (BK, BQ)
            if causal:
                st = jnp.where(
                    _keep(st.shape, q_base + qb * block_q, k0, 1, window),
                    st, _NEG_INF)
            pt = jnp.exp(st - lse_ref[0, 0, :, rows])
            dv_acc_ref[...] += _dot(pt, do, _NN)
            dst = pt * (_dot(v, do, _NT) - dvec_ref[0, 0, :, rows])
            dk_acc_ref[...] += _dot(dst, q, _NN)

        # causal: tiles whose last (offset) query position precedes this
        # k block's start contribute nothing (every entry masked), nor do
        # those whose band has passed the block
        _walk(tile, *_query_tiles(causal, k0, bk, q_base, block_q,
                                  sq // block_q, window))

    # causal: q superblocks entirely above the diagonal, or past the band,
    # are skipped; their q-side fetches are elided by the clamped index map
    live = q_base + sq - 1 >= k0
    if window:
        live &= q_base <= k0 + bk + window - 2
    _when(one or not causal, live)(_compute)

    @_when(one and g == 1, (gi == g - 1) & (qsi == num_super - 1))
    def _finalize():
        dk_ref[0] = (dk_acc_ref[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _both(a, b):
    """a & b, where b may be a Python bool the trace already knows (one
    query superblock): nothing more to trace then."""
    return a if b is True else a & b


def _fa_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref,
                         dq_ref, dk_ref, dv_ref, dq_acc_ref, dk_acc_ref,
                         dv_acc_ref, *, causal, scale, block_q, offset, g,
                         num_k, num_qs=1, window=0):
    """dQ, dK and dV for one (batch*kv-head, group head, q-superblock,
    k-block): every live tile builds s, p, dp and ds ONCE and spends five
    products on them, where the dq and dkv kernels together spend seven.
    q, dO, lse and D of one query superblock of the head are whole in VMEM
    (fetched once a superblock, not once a key block), K and V arrive one
    block a grid step, and the in-kernel loop walks the superblock's
    block_q tiles from the diagonal on. With one superblock (``num_qs``
    1, the whole query sequence) the grid has no superblock axis.

    The tile body is the dkv kernel's (transposed scores, lse and D as
    the rows they are stored in) plus ``dq[rows] += ds k``, whose left
    side is the one tile of the five products that is transposed. dq's
    f32 accumulator holds the superblock: zeroed at its first key block
    and written, scaled, at its last. dk's and dv's are whole-sequence
    f32 VMEM scratch: a key block's rows are zeroed at the group's first
    head's first superblock and written, in the output dtype, at its last
    head's last — the GQA sum and the sum over superblocks stay one f32
    sum inside the kernel, in the dkv kernel's order (head, superblock,
    tile)."""
    bk = k_ref.shape[1]
    sq = q_ref.shape[2]                                # q superblock size
    gi = pl.program_id(1)
    qsi = pl.program_id(2) if num_qs > 1 else 0
    ki = pl.program_id(3 if num_qs > 1 else 2)
    k0 = ki * bk
    krows = pl.ds(pl.multiple_of(k0, bk), bk)
    q_base = qsi * sq + offset

    @_when(num_k == 1, ki == 0)
    def _init_dq():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    @_when(g == 1 and num_qs == 1, _both(gi == 0, qsi == 0))
    def _init_dkv():
        dk_acc_ref[krows, :] = jnp.zeros((bk, dk_acc_ref.shape[1]),
                                         jnp.float32)
        dv_acc_ref[krows, :] = jnp.zeros((bk, dv_acc_ref.shape[1]),
                                         jnp.float32)

    k, v = k_ref[0], v_ref[0]                          # (BK, D)

    def tile(qb):
        rows = pl.ds(pl.multiple_of(qb * block_q, block_q), block_q)
        q, do = q_ref[0, 0, rows, :], do_ref[0, 0, rows, :]
        st = _dot(k, q, _NT) * scale                   # (BK, BQ)
        if causal:
            st = jnp.where(_keep(st.shape, q_base + qb * block_q, k0, 1,
                                 window), st, _NEG_INF)
        pt = jnp.exp(st - lse_ref[0, 0, :, rows])
        dv_acc_ref[krows, :] += _dot(pt, do, _NN)
        dst = pt * (_dot(v, do, _NT) - dvec_ref[0, 0, :, rows])
        dk_acc_ref[krows, :] += _dot(dst, q, _NN)
        dq_acc_ref[rows, :] += _dot(dst, k, _TN)

    # causal: tiles whose last (offset) query position precedes this k
    # block's start contribute nothing (every entry masked), nor do those
    # whose band has passed the block; a key block dead for the whole
    # superblock walks none (its K/V index is clamped to a live block)
    _walk(tile, *_query_tiles(causal, k0, bk, q_base, block_q,
                              sq // block_q, window))

    @_when(num_k == 1, ki == num_k - 1)
    def _write_dq():
        dq_ref[0, 0] = (dq_acc_ref[...] * scale).astype(dq_ref.dtype)

    @_when(g == 1 and num_qs == 1, _both(gi == g - 1, qsi == num_qs - 1))
    def _write_dkv():
        dk_ref[0] = (dk_acc_ref[krows, :] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[krows, :].astype(dv_ref.dtype)


# The scoped VMEM a Mosaic kernel gets without asking. The fused backward
# lives in it like the other kernels and takes the shapes that fit: a
# `vmem_limit_bytes` on the call, even one of these same 16 MiB, changes
# how XLA builds OTHER fusions of the program around it (measured, PR 32:
# with a 32 MiB limit on this kernel the head's dW fusion of lm_train_4k
# went from 13.5 to 18.4 ms a step, most of what the kernel had won).
_SCOPED_VMEM = 16 * 2 ** 20


def _fused_bwd_vmem_bytes(tq, tk, d, itemsize, q_super=None):
    """What the fused backward holds in VMEM at a query superblock of
    ``q_super`` rows (the whole ``tq`` by default), from the shapes alone:
    every input and output block twice (the pipeline's two buffers: q,
    dO, dq, lse and D at the superblock's rows, K, V, dK and dV at a key
    block's), the f32 accumulators (dq's at the superblock's rows, dk's
    and dv's at the whole key sequence's), and two f32 score-sized tiles
    for what the loop spills (the compiler asks for 1.0-1.5 at 512 x 512:
    lm_train_4k's shape, 15.5 MiB by this count, compiles from 14.5-15 MiB
    up)."""
    sq = q_super or tq
    block_q, block_k = _pick_block(tq, BLOCK_Q), _pick_block(tk, BLOCK_K)
    d = -(-d // _LANES) * _LANES        # a row of 64 takes a whole vreg row
    row = 8 * sq * 4                    # a (1, sq) f32 row pads to 8 sublanes
    blocks = ((2 * sq * d + 2 * block_k * d) * itemsize + 2 * row  # in
              + (sq * d + 2 * block_k * d) * itemsize)             # out
    acc = (sq + 2 * tk) * d * 4
    tiles = 2 * block_q * block_k * 4
    return 2 * blocks + acc + tiles


def _fused_q_super(tq, tk, d, itemsize):
    """Rows of the fused backward's query superblock: the largest divisor
    of ``tq`` that is a whole number of query tiles and whose byte count
    (`_fused_bwd_vmem_bytes`) fits `_SCOPED_VMEM` — ``tq`` itself where the
    whole sequence fits — or None where not even one tile does, because
    dk's and dv's whole-sequence accumulators alone do not."""
    n = tq // _pick_block(tq, BLOCK_Q)
    for parts in range(1, n + 1):
        if n % parts == 0 and _fused_bwd_vmem_bytes(
                tq, tk, d, itemsize, tq // parts) <= _SCOPED_VMEM:
            return tq // parts
    return None


def _fa_backward_fused(args, causal, scale, interpret, window=0,
                       q_super=None):
    """dq, dk, dv from ONE kernel over a (batch*kv-head, group head,
    q-superblock, k-block) grid: `_fa_bwd_fused_kernel`. ``q_super``: the
    query superblock's rows, the whole ``tq`` by default, when the grid
    has no superblock axis."""
    q, k, v = args[:3]
    bkv, g, tq, d = q.shape
    tk = k.shape[1]
    offset = tk - tq
    block_q = _pick_block(tq, BLOCK_Q)
    block_k = _pick_block(tk, BLOCK_K)
    num_k = tk // block_k
    q_super = q_super or tq
    num_qs = tq // q_super
    if num_qs == 1:
        grid = (bkv, g, num_k)

        def at(f):                      # the index maps at superblock 0
            return lambda b, gi, ki: f(b, gi, 0, ki)
    else:
        grid = (bkv, g, num_qs, num_k)

        def at(f):
            return f

    q_spec = pl.BlockSpec((1, 1, q_super, d),
                          at(lambda b, gi, qsi, ki: (b, gi, qsi, 0)))
    qrow_spec = pl.BlockSpec((1, 1, 1, q_super),
                             at(lambda b, gi, qsi, ki: (b, gi, 0, qsi)))
    # a key block wholly past the superblock's diagonal, or outside its
    # band, re-addresses a live one (no DMA) while the kernel walks no
    # tile of it; one superblock keeps the plain map (every causal key
    # block is live for it, but a band's ahead of a long offset)
    kv_spec = pl.BlockSpec((1, block_k, d), at(_kv_stream_idx(
        q_super, block_k, offset, causal and num_qs > 1, window)))
    # dk/dv blocks are written during the group's LAST head's last
    # superblock only: until then the index stays put, and Pallas writes a
    # block back when its index changes, so no block leaves before it is
    # written
    dkv_spec = pl.BlockSpec((1, block_k, d), at(
        lambda b, gi, qsi, ki: (b, jnp.where(
            _both(gi == g - 1, qsi == num_qs - 1), ki, 0), 0)))
    return pl.pallas_call(
        functools.partial(_fa_bwd_fused_kernel, causal=causal, scale=scale,
                          block_q=block_q, offset=offset, g=g,
                          num_k=num_k, num_qs=num_qs, window=window),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, qrow_spec, qrow_spec],
        out_specs=[q_spec, dkv_spec, dkv_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((q_super, d), jnp.float32),
                        pltpu.VMEM((tk, d), jnp.float32),
                        pltpu.VMEM((tk, d), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            # 5 matmuls per (q,k) tile pair: s^T, p^T@dO, v@dO^T, ds^T@q,
            # ds@k
            flops=10 * bkv * g * tq * tk * d,
            bytes_accessed=_bwd_in_bytes(args),
            transcendentals=bkv * g * tq * tk),
        interpret=interpret,
        **_compiler_params(interpret, grid_dims=len(grid)),
    )(*args)


def _row_sums(o, do, g_lse=None):
    """D_i = rowsum(dO * O), (bkv, g, 1, tq) f32: one cheap fused XLA pass
    before either backward path. A cotangent on the logsumexp output
    folds in here: d(lse)/ds = p, so ds gains +g_lse*p, i.e. D := D -
    g_lse (ring attention's merge differentiates through lse)."""
    dvec = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                   axis=-1)[:, :, None, :]
    if g_lse is not None:
        dvec = dvec - g_lse.astype(jnp.float32)
    return dvec


def _bwd_in_bytes(args):
    q, k, v, do = args[:4]
    return (q.size + k.size + v.size + do.size) * q.dtype.itemsize


def _fa_backward_split(args, causal, scale, interpret, window=0):
    """dq from one kernel, dk and dv from another: two walks over the
    same tiles, seven products a tile pair, nothing in VMEM that grows
    with the sequence beyond a superblock."""
    q, k, v = args[:3]
    bkv, g, tq, d = q.shape
    tk = k.shape[1]
    offset = tk - tq
    in_bytes = _bwd_in_bytes(args)

    block_q = _pick_block(tq, BLOCK_Q)
    block_k = _pick_block(tk, BLOCK_K)
    super_k, num_super = _superblocks(tk, block_k)
    q_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda b, gi, i, ski: (b, gi, i, 0))
    qrow_spec = pl.BlockSpec((1, 1, 1, block_q),
                             lambda b, gi, i, ski: (b, gi, 0, i))
    kv_spec = pl.BlockSpec((1, super_k, d),
                           _kv_stream_idx(block_q, super_k, offset, causal,
                                          window))
    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, causal=causal, scale=scale,
                          block_k=block_k, offset=offset,
                          num_super=num_super, window=window),
        grid=(bkv, g, tq // block_q, num_super),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, qrow_spec, qrow_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bkv, g, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=6 * bkv * g * tq * tk * d, bytes_accessed=in_bytes,
            transcendentals=bkv * g * tq * tk),
        interpret=interpret,
        **_compiler_params(interpret),
    )(*args)

    super_q, num_super = _superblocks(tq, block_q)

    # causal: q superblocks strictly above this k block's diagonal are
    # fully masked, as are those past a band; clamp their index so the
    # dead steps re-address a live superblock (no DMA) while the kernel
    # skips their compute
    def first_live(i):
        if not causal:
            return 0
        return jax.lax.div(jax.lax.max(i * block_k - offset, 0), super_q)

    def live(i, qsi):
        qsi = jnp.maximum(qsi, first_live(i))
        if window:
            last = (i + 1) * block_k + window - 2 - offset
            qsi = jnp.minimum(qsi, jax.lax.div(jax.lax.max(last, 0),
                                               super_q))
        return qsi

    q_spec = pl.BlockSpec(
        (1, 1, super_q, d),
        lambda b, i, gi, qsi: (b, gi, live(i, qsi), 0))
    qrow_spec = pl.BlockSpec(
        (1, 1, 1, super_q),
        lambda b, i, gi, qsi: (b, gi, 0, live(i, qsi)))
    kv_spec = pl.BlockSpec((1, block_k, d), lambda b, i, gi, qsi: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, causal=causal, scale=scale,
                          block_q=block_q, offset=offset, g=g,
                          num_super=num_super, window=window),
        grid=(bkv, tk // block_k, g, num_super),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, qrow_spec, qrow_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((bkv, tk, d), k.dtype),
                   jax.ShapeDtypeStruct((bkv, tk, d), v.dtype)],
        # dk/dv accumulate over the group AND all q tiles in f32 scratch
        # (a bf16 += per contribution would round many times); single
        # cast at the final flush
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            # 4 matmuls per (q,k) tile pair: s^T, p^T@dO, v@dO^T, ds^T@q
            flops=8 * bkv * g * tq * tk * d, bytes_accessed=in_bytes,
            transcendentals=bkv * g * tq * tk),
        interpret=interpret,
        **_compiler_params(interpret),
    )(*args)
    return dq, dk, dv


def _fa_backward(q, k, v, o, lse, do, causal, scale, interpret,
                 g_lse=None, window=0, node=""):
    """q/o/do: (B*Hkv, G, Tq, D); k/v: (B*Hkv, Tk, D); lse: (B*Hkv, G, 1,
    Tq). Returns (dq like q, dk/dv like k/v) — dk/dv already summed over
    the query-head group inside the kernel.

    One algorithm, read from the shapes: the fused kernel at the longest
    query superblock whose accumulators fit a kernel's scoped VMEM
    (`_fused_q_super`: the whole sequence up to 4096 x 4096 in bfloat16
    at head_dim 128, 2048 rows at 8192), the dq and dkv kernels where
    not even one query tile fits (dk's and dv's whole-sequence
    accumulators alone fill it: 16384 in bfloat16). Which was built goes
    into the record of the graph node ``node`` whose forward this is the
    backward of (``note_built``: ``backward`` ``"fused"`` or ``"split"``,
    ``q_super`` the superblock's rows, None for the split pair)."""
    args = (q, k, v, do, lse, _row_sums(o, do, g_lse))
    q_super = _fused_q_super(q.shape[2], k.shape[1], q.shape[3],
                             q.dtype.itemsize)
    note_built({"op": "MultiHeadAttention",
                "backward": "fused" if q_super else "split",
                "q_super": q_super}, node=node)
    if q_super:
        return _fa_backward_fused(args, causal, scale, interpret, window,
                                  q_super)
    return _fa_backward_split(args, causal, scale, interpret, window)


def _aligned(t, block):
    return t % min(block, t) == 0


# Finest tile the kernels accept: the CONTRACT is divisibility by this,
# NOT by the preferred blocks — _pick_block falls back from the preferred
# (faster) 512 tile to 256 for lengths like 768/1280/2816, so raising a
# block never narrows which shapes qualify (ring-attention chunks that
# are odd multiples of 256 keep their flash path).
_MIN_TILE = 256


def _pick_block(t, pref):
    """Largest tile in {pref, pref/2, ..., _MIN_TILE} dividing t
    (t itself when t < _MIN_TILE)."""
    b = min(pref, t)
    while b > _MIN_TILE and t % b:
        b //= 2
    return b


def kernel_qualifies(tq, tk, d, compiled=True, causal=False):
    """The kernel's CORRECTNESS contract: sequence lengths divide into
    whole blocks (a ragged final block would read padding into the
    softmax) at the finest `_MIN_TILE` granularity (the actual tiles
    are picked per shape by `_pick_block`); the compiled path
    additionally needs a head_dim of whole lanes, or of HALF a vreg's 128
    (64: the blocks are then 64 lanes wide, q, k, v and every accumulator
    fill the first half of each vreg row, and the MXU contracts over 64
    for the scores and writes 64 columns for the values; `_HALF_LANES`
    says what that costs and what else was weighed); causal calls need
    tq <= tk (with tq > tk the first tk-tq query rows are FULLY masked —
    the XLA path's finfo.min masking degrades to uniform attention
    there, while the kernel's l=0 would produce NaN). Shared by
    flash_attention() and ring_attention's per-shard selection so the
    two paths cannot drift."""
    return (_aligned(tq, _MIN_TILE) and _aligned(tk, _MIN_TILE)
            and (not causal or tq <= tk)
            and (not compiled or d % _LANES == 0 or d == _HALF_LANES))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q3, k3, v3, causal, scale, interpret, window=0, node=""):
    """``node``: the graph node being traced, for the backward's record
    (a custom VJP's backward is traced when the VJP is applied, outside
    the node)."""
    return _fa_forward(q3, k3, v3, causal, scale, interpret, window=window)


def _flash_fwd(q3, k3, v3, causal, scale, interpret, window, node):
    out, lse = _fa_forward(q3, k3, v3, causal, scale, interpret,
                           with_lse=True, window=window)
    return out, (q3, k3, v3, out, lse)


def _flash_bwd(causal, scale, interpret, window, node, res, g):
    # Blocked FlashAttention-2 backward: rebuilds p per tile from the
    # saved logsumexp — never materializes the (Tq, Tk) score matrix, so
    # long-sequence TRAINING scales like the forward (docs/perf.md
    # attention section; previously this was recompute-through-the-
    # reference-math and the S^2 backward dominated at seq >= 4096).
    q3, k3, v3, o3, lse = res
    return _fa_backward(q3, k3, v3, o3, lse, g, causal, scale, interpret,
                        window=window, node=node)


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


def flash_attention(q, k, v, causal=False, scale=None, interpret=None,
                    window=0):
    """Attention over q (B, H, T, D). Pallas on TPU, XLA reference
    otherwise.

    ``window`` > 0 (causal only) bands the scores: query i sees keys j
    with i - window < j <= i, and the kernels walk only the key tiles the
    band reaches, so a band costs its own pairs and not the triangle's.

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
    window = int(window)
    if window < 0 or (window and not causal):
        raise ValueError("window %d: a band is causal and not negative"
                         % window)
    if window >= tk:
        window = 0  # the band holds every causal pair

    def fallback():
        if hkv != h:
            return _att._grouped_attention(q, k, v, hkv, causal,
                                           scale=scale, window=window)
        return _att.dot_product_attention(q, k, v, causal=causal,
                                          scale=scale, window=window)

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
    node = current_node()
    # ``window``: the band that bounds the tile walk of all four kernels
    note_built({"op": "MultiHeadAttention", "head_dim": d, "kernel": True,
                "window": window or None})

    def run(q, k, v):
        rows = q.shape[0] * hkv
        out = _flash(q.reshape(rows, g, tq, d), k.reshape(rows, tk, d),
                     v.reshape(rows, tk, d), causal, scale, interpret,
                     window, node)
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
