"""Attention & modern-normalization operators.

These extend the reference's op set (which predates attention) to cover the
long-context capability goal (SURVEY §5.7): the framework's idiomatic
replacement for unrolled-RNN sequence handling is transformer attention,
sharded over the mesh by the parallel layer (ring attention /
sequence parallelism in mxnet_tpu.parallel).

``MultiHeadAttention`` is the fusion seam: the default impl is XLA-fused
jnp einsum math; when running on TPU with suitable shapes the executor can
swap in the Pallas flash-attention kernel (ops/pallas/flash_attention.py) —
the same layering as the reference's cuDNN fast paths over mshadow
reference impls (src/operator/cudnn_*.h, SURVEY §2.1 #16).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import defop, get_op, note_built


@defop(
    "LayerNorm",
    arg_names=("data", "gamma", "beta"),
    param_spec={"axis": -1, "eps": 1e-5},
)
def _layer_norm(attrs, data, gamma, beta):
    """Layer normalization over ``axis`` (modern analogue of the reference's
    InstanceNorm/L2Normalization family, src/operator/instance_norm-inl.h)."""
    ax = int(attrs["axis"]) % data.ndim
    mean = jnp.mean(data, axis=ax, keepdims=True)
    var = jnp.var(data, axis=ax, keepdims=True)
    bshape = tuple(data.shape[ax] if i == ax else 1 for i in range(data.ndim))
    out = (data - mean) * jax.lax.rsqrt(var + attrs["eps"])
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


@defop(
    "RMSNorm",
    arg_names=("data", "gamma"),
    param_spec={"axis": -1, "eps": 1e-6},
)
def _rms_norm(attrs, data, gamma):
    """Root-mean-square norm (no centering) — the bandwidth-cheaper norm
    preferred on TPU (one fewer HBM pass than LayerNorm)."""
    ax = int(attrs["axis"]) % data.ndim
    ms = jnp.mean(jnp.square(data), axis=ax, keepdims=True)
    bshape = tuple(data.shape[ax] if i == ax else 1 for i in range(data.ndim))
    return data * jax.lax.rsqrt(ms + attrs["eps"]) * gamma.reshape(bshape)


def rope(x, positions=None, base=10000.0):
    """Rotary position embedding over the last axis of (..., T, D)."""
    d = x.shape[-1]
    half = d // 2
    if positions is None:
        positions = jnp.arange(x.shape[-2])
    freq = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., :, None].astype(jnp.float32) * freq  # (T, half)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = x[..., :half], x[..., half:]
    sin = sin.astype(x.dtype)
    cos = cos.astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _rope_tail(x, dims, base):
    """RoPE over the trailing ``dims`` of each head of (..., T, D), the rest
    as it is; ``dims`` 0: the whole head."""
    if not dims:
        return rope(x, base=base)
    return jnp.concatenate([x[..., :-dims], rope(x[..., -dims:], base=base)],
                           axis=-1)


def _causal_mask(tq, tk, window=0):
    """(tq, tk) bool: query i (at position i + tk - tq, so that kv may be
    longer than q) sees the keys up to its own; with a ``window``, the
    last ``window`` of them."""
    idx_q = jnp.arange(tq)[:, None] + (tk - tq)
    idx_k = jnp.arange(tk)[None, :]
    if window:
        return (idx_q >= idx_k) & (idx_k > idx_q - window)
    return idx_q >= idx_k


def dot_product_attention(q, k, v, causal=False, scale=None, mask=None,
                          window=0):
    """Reference attention math on (B, H, T, D) tensors.

    Computed in float32 accumulation regardless of input dtype (MXU-friendly:
    bf16 inputs, f32 softmax), matching flash-kernel numerics.
    """
    if scale is None:
        scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        cmask = _causal_mask(logits.shape[-2], logits.shape[-1], window)
        logits = jnp.where(cmask, logits, jnp.finfo(jnp.float32).min)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def _mha_arg_names(attrs):
    if attrs.get("qk_norm"):
        return ("query", "key", "value", "q_norm_gamma", "k_norm_gamma")
    return ("query", "key", "value")


@defop(
    "MultiHeadAttention",
    arg_names=_mha_arg_names,
    param_spec={"num_heads": 1, "num_kv_heads": 0, "causal": False,
                "use_rope": False, "use_flash": True, "window": 0,
                "rope_base": 10000.0, "rope_dims": 0, "qk_norm": False,
                "qk_norm_eps": 1e-6},
)
def _multi_head_attention(attrs, query, key, value, q_norm_gamma=None,
                          k_norm_gamma=None):
    """Fused multi-head attention on (B, T, H*D) projected inputs.

    Splits heads, optionally applies RoPE, runs (flash) attention, and
    merges heads. Projections (in/out) live outside this op as
    FullyConnected so tensor-parallel sharding of the head axis is a pure
    data layout (mxnet_tpu.parallel.tensor_parallel).

    ``num_kv_heads`` < num_heads gives grouped-query attention (GQA;
    =1 is multi-query): key/value carry (B, T, num_kv_heads*D) and each
    kv head serves num_heads/num_kv_heads query heads. Both paths keep
    kv at hkv heads end to end — the flash kernel grids query-head
    groups over the VMEM-resident kv block, the XLA path uses a grouped
    einsum — so KV HBM bandwidth shrinks by h/hkv along with the
    projection params/FLOPs. 0 (default) = standard MHA.

    ``window`` > 0 (with ``causal``) lets query i see keys j with
    i - window < j <= i only; 0 is no window. ``rope_base`` is the base
    of the rotary frequencies where ``use_rope`` is set; a layer without
    ``use_rope`` has no position encoding at all. ``rope_dims`` > 0
    rotates the trailing ``rope_dims`` of every q and k head and leaves
    the rest of the head unrotated (latent attention's decoupled key);
    0 rotates the whole head.

    ``qk_norm`` adds two inputs, ``q_norm_gamma`` and ``k_norm_gamma``
    (head size,): every query head and every key head is RMS-normalised
    over its own head size (``qk_norm_eps``) and scaled, one scale shared
    by the heads, BEFORE the rotation.
    """
    h = int(attrs["num_heads"])
    hkv = int(attrs["num_kv_heads"]) or h
    if h % hkv:
        raise ValueError("num_heads %d not divisible by num_kv_heads %d"
                         % (h, hkv))
    b, tq, dm = query.shape
    tk = key.shape[1]
    d = dm // h
    causal = bool(attrs["causal"])
    window = int(attrs["window"])
    if window < 0 or (window and not causal):
        raise ValueError("window %d: a band is causal and not negative"
                         % window)

    def split(x, t, heads):
        return x.reshape(b, t, heads, d).transpose(0, 2, 1, 3)

    q = split(query, tq, h)
    k, v = split(key, tk, hkv), split(value, tk, hkv)
    rope_dims = int(attrs["rope_dims"])
    if not 0 <= rope_dims <= d or rope_dims % 2:
        raise ValueError("rope_dims %d: an even part of a head of %d"
                         % (rope_dims, d))
    # the kernels' gate and their backward add to this record what they
    # build (flash_attention.py); without them it stands as it is
    note_built({"op": "MultiHeadAttention", "head_dim": d,
                "rope_dims": (rope_dims or d) if attrs["use_rope"] else 0,
                "window": None, "kernel": False, "backward": None,
                "q_super": None})
    if attrs["qk_norm"]:
        q = _head_norm(q, q_norm_gamma, attrs["qk_norm_eps"])
        k = _head_norm(k, k_norm_gamma, attrs["qk_norm_eps"])
    if attrs["use_rope"]:
        base = float(attrs["rope_base"])
        q, k = _rope_tail(q, rope_dims, base), _rope_tail(k, rope_dims, base)
    if attrs["use_flash"]:
        # flash_attention owns the selection gate (on-TPU + block
        # contract + MIN_SEQ) and takes narrow (B, Hkv, Tk, D) k/v
        # directly — off the fast path it falls back to the grouped
        # einsum / reference math itself, so the predicate lives in ONE
        # place and the two layers cannot drift
        from .pallas import flash_attention as _fa
        out = _fa.flash_attention(q, k, v, causal=causal, window=window)
    elif hkv != h:
        out = _grouped_attention(q, k, v, hkv, causal, window=window)
    else:
        out = dot_product_attention(q, k, v, causal=causal, window=window)
    return out.transpose(0, 2, 1, 3).reshape(b, tq, dm)


def _head_norm(x, gamma, eps):
    """RMSNorm over the head size of (B, H, T, D), float32 statistics."""
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps)
            * gamma.astype(jnp.float32)).astype(x.dtype)


def _mha_infer(attrs, shapes):
    """The two head-norm scales: (head size,), from the query's width."""
    if len(shapes) > 3 and shapes[0] is not None:
        d = shapes[0][-1] // int(attrs["num_heads"])
        shapes[3] = shapes[3] or (d,)
        shapes[4] = shapes[4] or (d,)
    return shapes


get_op("MultiHeadAttention").infer_params = _mha_infer


@defop(
    "LatentKV",
    arg_names=("latent", "kv"),
    num_outputs=2,
    output_names=("key", "value"),
    param_spec={"num_heads": 1, "head_dim": 0, "rope_dims": 0},
)
def _latent_kv(attrs, latent, kv):
    """Latent attention's key and value, assembled per head (DeepSeek-V2's
    MLA). ``latent`` (B, T, c + r): the kv down-projection, whose trailing
    ``rope_dims`` r are the key's rotary part, one for every head; ``kv``
    (B, T, H * (n + v)): the normed latent's up-projection, a head's
    unrotated key part of ``n = head_dim - r`` and its value of v. Output
    0, the key (B, T, H * head_dim): a head's n and then the shared r, which
    ``MultiHeadAttention`` rotates with ``rope_dims`` r; output 1, the value
    (B, T, H * v)."""
    h, r = int(attrs["num_heads"]), int(attrs["rope_dims"])
    n = int(attrs["head_dim"]) - r
    b, t = kv.shape[:2]
    per_head = kv.reshape(b, t, h, -1)
    if n <= 0 or per_head.shape[-1] <= n:
        raise ValueError("LatentKV: a head's %d up-projected numbers hold no "
                         "key part of %d and a value"
                         % (per_head.shape[-1], n))
    k_rope = jnp.broadcast_to(latent[..., None, latent.shape[-1] - r:],
                              (b, t, h, r))
    key = jnp.concatenate([per_head[..., :n], k_rope.astype(kv.dtype)], -1)
    return key.reshape(b, t, -1), per_head[..., n:].reshape(b, t, -1)


def _grouped_attention(q, k, v, hkv, causal, scale=None, mask=None,
                       window=0):
    """GQA without materializing repeated kv: q (B, H, Tq, D) grouped as
    (B, Hkv, G, Tq, D) against k/v (B, Hkv, Tk, D) — kv streams once per
    GROUP, which is the bandwidth/KV-cache saving GQA exists for.
    ``mask``: optional (B, Tk) bool of valid key positions (broadcast over
    heads/groups/query) — the KV-cache decode path's per-row length mask."""
    b, hh, tq, d = q.shape
    g = hh // hkv
    q5 = q.reshape(b, hkv, g, tq, d)
    if scale is None:
        scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    logits = jnp.einsum("bkgqd,bkld->bkgql", q5, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        cmask = _causal_mask(tq, logits.shape[-1], window)
        logits = jnp.where(cmask, logits, jnp.finfo(jnp.float32).min)
    if mask is not None:
        logits = jnp.where(mask[:, None, None, None, :], logits,
                           jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgql,bkld->bkgqd", probs.astype(v.dtype), v)
    return out.reshape(b, hh, tq, d)


def dequantize_kv(cache, scale):
    """Widen an int8 KV cache view back to f32 for the attention einsum.

    ``cache``: (..., Hkv, C, Dh) int8, ``scale``: (..., C) f32 — one
    scale per cached position, shared across kv heads and head dim (each
    position is written exactly once, so its scale never needs
    requantization). The multiply fuses into the einsum's operand read;
    the HBM-resident slab stays at 1/4 of f32 bytes, which is the whole
    point (docs/deployment.md "Quantized serving").
    """
    return cache.astype(jnp.float32) * scale[..., None, :, None]


def cached_attention(q, k_cache, v_cache, lengths, k_scale=None,
                     v_scale=None):
    """One autoregressive decode step against a padded KV cache.

    ``q``: (B, H, 1, D) — the new token's query (already roped at its
    absolute position). ``k_cache``/``v_cache``: (B, Hkv, C, D) slot
    rows of a KV slab at fixed capacity C, holding each row's keys/values
    at positions [0, lengths[i]] (the new token's k/v already written).
    ``lengths``: (B,) int — the new token's position per row; key slots
    beyond it are masked to exactly zero probability, so a row's output
    is bitwise independent of whatever stale kv other slots or positions
    hold — the invariant continuous batching rests on.

    This is the fixed-shape twin of the prefill-side flash/GQA attention
    (``_multi_head_attention``): same grouped-einsum math, f32 softmax,
    Tq=1. The flash kernel's block contract needs Tq >= block, so the
    decode step stays on the einsum path by construction.

    Low-precision caches (``MXNET_DECODE_KV_DTYPE``): bf16 caches flow
    through the f32-accumulating einsum unchanged; int8 caches carry
    per-position ``k_scale``/``v_scale`` (..., C) and are widened via
    :func:`dequantize_kv` at the einsum input.
    """
    if k_scale is not None:
        k_cache = dequantize_kv(k_cache, k_scale)
        v_cache = dequantize_kv(v_cache, v_scale)
    hkv = k_cache.shape[1]
    cap = k_cache.shape[2]
    mask = jnp.arange(cap)[None, :] <= lengths[:, None]  # (B, C)
    return _grouped_attention(q, k_cache, v_cache, hkv, causal=False,
                              mask=mask)


def prefix_cached_attention(q, k_ctx, v_ctx, ctx_len, k_new, v_new,
                            k_scale=None, v_scale=None):
    """Chunked prefill against a cached prefix (the paged-KV admit path).

    ``q``: (B, H, Tq, D) — queries for ``Tq`` new suffix tokens (already
    roped at absolute positions ``ctx_len + j``). ``k_ctx``/``v_ctx``:
    (B, Hkv, C, D) — the cached prefix at fixed capacity C, valid in
    positions ``[0, ctx_len)``; everything at/after ``ctx_len`` is masked
    to exactly zero probability. ``k_new``/``v_new``: (B, Hkv, Tq, D) —
    the suffix's own keys/values, attended causally (suffix token i sees
    suffix keys 0..i).

    Same grouped-einsum math and f32 softmax as ``cached_attention`` —
    masked lanes contribute exactly 0.0 to the softmax sum, so with
    ``ctx_len == 0`` the result equals plain causal self-attention over
    the suffix, and a shared cached prefix yields the same output as
    recomputing that prefix in-band.

    int8 cached prefixes carry per-position ``k_scale``/``v_scale``
    (..., C), widened at the einsum input like ``cached_attention``;
    ``k_new``/``v_new`` are always full precision (they were just
    computed in-register).
    """
    if k_scale is not None:
        k_ctx = dequantize_kv(k_ctx, k_scale)
        v_ctx = dequantize_kv(v_ctx, v_scale)
    k_new = k_new.astype(k_ctx.dtype)
    v_new = v_new.astype(v_ctx.dtype)
    hkv = k_ctx.shape[1]
    cap = k_ctx.shape[2]
    tq = q.shape[2]
    k_all = jnp.concatenate([k_ctx, k_new], axis=2)
    v_all = jnp.concatenate([v_ctx, v_new], axis=2)
    # ctx keys valid below ctx_len; suffix keys gated by the causal term
    # inside _grouped_attention (idx_q = i + cap admits all ctx keys and
    # exactly the causal suffix prefix).
    ctx_valid = jnp.arange(cap)[None, :] < ctx_len
    suf_valid = jnp.ones((1, tq), bool)
    mask = jnp.concatenate(
        [jnp.broadcast_to(ctx_valid, (q.shape[0], cap)),
         jnp.broadcast_to(suf_valid, (q.shape[0], tq))], axis=1)
    return _grouped_attention(q, k_all, v_all, hkv, causal=True, mask=mask)
