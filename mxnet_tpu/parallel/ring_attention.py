"""Ring attention — sequence/context parallelism over the mesh "seq" axis.

The reference has NO sequence parallelism (SURVEY §5.7: long sequences are
handled by bucketing + unrolling); this is the modern TPU-idiomatic
mechanism that replaces it. Q, K, V are sharded along the sequence axis;
each device computes attention of its local query block against the K/V
block it currently holds, then passes K/V to its ring neighbor (ppermute
over ICI) while accumulating the online-softmax statistics — compute and
ICI transfer overlap, and no device ever materializes the full sequence.

Causal masking per ring step: a chunk pair is fully visible (kv earlier
than q), fully masked (kv later — skipped as a zero contribution), or
diagonal (local causal mask), indexed by the source chunk position.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from .collectives import axis_size, shard_map

_NEG = float(jnp.finfo(jnp.float32).min)


def _block_attn(q, k, v, mode, q_off, k_off):
    """Un-normalized blockwise attention with stats.

    q: (B,H,Tq,D), k/v: (B,H,Tk,D). mode: 0=full, 1=causal-diagonal,
    2=skip. Returns (acc f32 (B,H,Tq,D), m (B,H,Tq), l (B,H,Tq))."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    tq, tk = q.shape[-2], k.shape[-2]
    q_pos = q_off + jnp.arange(tq)[:, None]
    k_pos = k_off + jnp.arange(tk)[None, :]
    causal_mask = q_pos >= k_pos
    mask = jnp.where(mode == 1, causal_mask, mode == 0)
    s = jnp.where(mask, s, _NEG)
    m = jnp.max(s, axis=-1)
    m_safe = jnp.maximum(m, _NEG / 2)  # avoid -inf - -inf
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return acc, m_safe, l


def _merge(acc1, m1, l1, acc2, m2, l2):
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    return (acc1 * a1[..., None] + acc2 * a2[..., None],
            m, l1 * a1 + l2 * a2)


def _block_attn_flash(q, k, v, mode, interpret=False):
    """Per-shard compute through the Pallas flash kernel (docs/perf.md:
    2-15.7x over einsum attention at long chunks, blocked fwd AND bwd).

    Returns the same mergeable (acc, m, l) triple as _block_attn via the
    normalized-representation trick: for flash output O and logsumexp L,
    (O, L, 1) merges identically to (sum exp(s-m) v, m, sum exp(s-m)) —
    exp(L - m') * O = exp(m - m') * acc and exp(L - m') * 1 = the scaled
    l. The lse cotangent flows through the custom vjp (folded into the
    backward's D-vector). ``mode`` selects full/diagonal-causal/skip via
    lax.switch (it is data-dependent on the ring position)."""
    from ..ops.pallas.flash_attention import _flash_with_lse

    b, h, t, d = q.shape
    scale = 1.0 / (d ** 0.5)

    def run(is_causal):
        def f():
            # grouped-kernel layout with group size 1 (q: (bh, 1, t, d))
            out, lse = _flash_with_lse(
                q.reshape(b * h, 1, t, d), k.reshape(b * h, t, d),
                v.reshape(b * h, t, d), is_causal, scale, interpret)
            return (out.reshape(b, h, t, d).astype(jnp.float32),
                    lse.reshape(b, h, t),
                    jnp.ones((b, h, t), jnp.float32))
        return f

    def skip():
        return (jnp.zeros((b, h, t, d), jnp.float32),
                jnp.full((b, h, t), _NEG / 2, jnp.float32),
                jnp.zeros((b, h, t), jnp.float32))

    return jax.lax.switch(mode, [run(False), run(True), skip])


def _ring_attn_local(q, k, v, *, axis_name, causal, chunk, use_flash=False):
    """Body run per-device inside shard_map. q/k/v: local (B,H,T/n,D)."""
    n = axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    b, h, t, d = q.shape

    acc = jnp.zeros((b, h, t, d), jnp.float32)
    m = jnp.full((b, h, t), _NEG / 2, jnp.float32)
    l = jnp.zeros((b, h, t), jnp.float32)

    def step(i, carry):
        acc, m, l, kv = carry
        k_cur, v_cur = kv
        src = (my - i) % n  # which chunk we currently hold
        if causal:
            mode = jnp.where(src == my, 1, jnp.where(src < my, 0, 2))
        else:
            mode = jnp.zeros((), jnp.int32)
        if use_flash:
            a2, m2, l2 = _block_attn_flash(
                q, k_cur, v_cur, mode,
                interpret=(use_flash == "interpret"))
        else:
            a2, m2, l2 = _block_attn(q, k_cur, v_cur, mode,
                                     my * chunk, src * chunk)
        acc2, mm, ll = _merge(acc, m, l, a2, m2, l2)
        # overlap-friendly: shift kv for the next step
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (acc2, mm, ll, (k_nxt, v_nxt))

    acc, m, l, _ = jax.lax.fori_loop(0, n, step, (acc, m, l, (k, v)))
    return (acc / jnp.maximum(l, 1e-20)[..., None]).astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, causal=True, seq_axis="seq",
                   use_flash=None):
    """Full-array entry: q/k/v (B, H, T, D) sharded (or shardable) on T
    over `seq_axis`. Composable inside an outer pjit — shard_map nests.

    use_flash: None = auto (Pallas flash kernel per shard when on TPU
    with qualifying chunk shapes — the same selection contract as
    flash_attention); True/False forces; "interpret" runs the kernel in
    interpreter mode (tests)."""
    from ..ops.pallas import flash_attention as _fa
    from ..ops.pallas import on_tpu

    n = mesh.shape[seq_axis]
    t = q.shape[2]
    assert t % n == 0, "sequence length %d not divisible by seq axis %d" % (t, n)
    chunk = t // n
    if use_flash is None:
        use_flash = (on_tpu()
                     and _fa.kernel_qualifies(chunk, chunk, q.shape[-1])
                     and chunk >= _fa.MIN_SEQ)
    elif use_flash and not _fa.kernel_qualifies(
            chunk, chunk, q.shape[-1],
            compiled=(use_flash != "interpret")):
        # forcing the kernel past its block contract would read padding
        # into the softmax — refuse loudly instead of computing garbage
        raise ValueError(
            "ring_attention(use_flash=%r): chunk %d / head_dim %d do not "
            "satisfy the flash kernel's block contract"
            % (use_flash, chunk, q.shape[-1]))
    body = functools.partial(_ring_attn_local, axis_name=seq_axis,
                             causal=causal, chunk=chunk,
                             use_flash=use_flash)
    spec = P(None, None, seq_axis, None)
    fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=False)
    return fn(q, k, v)
