"""Decode-side model: the KV-cache twin of ``models/transformer.py``.

``DecodeModel`` holds the decoder-LM weights in a canonical stacked layout
(per-layer arrays stacked on a leading L axis) plus the architecture facts
the weights alone cannot carry (head counts), and builds the two pure
functions the generate subsystem compiles:

- ``prefill_fn(params, tokens (1, T), length (1,))`` — full causal forward
  over a length-bucketed padded prompt, returning the next-token logits at
  position ``length - 1`` and the prompt's K/V laid out at slab capacity
  ``(L, 1, Hkv, C, Dh)``, ready to be slotted into a replica's KV slab.
- ``decode_fn(params, k_slab, v_slab, lengths (B,), tokens (B,))`` — ONE
  token for every slot at once: write each row's new k/v at position
  ``lengths[i]``, attend over its own prefix only
  (``ops.attention.cached_attention``), return (B, V) logits plus the
  updated slabs (donated — the steady-state step allocates nothing new).

The math mirrors ``models/transformer.py`` op for op (LayerNorm eps 1e-5,
no-bias q/k/v/o, RoPE on split heads at absolute positions, exact-match
gelu FFN, biased head) so a ``DecodeModel`` built from a Predictor's
loaded checkpoint produces the same distribution the fixed-shape serving
path scores — ``tests/test_serving_generate.py`` gates prefill logits
against ``Predictor.forward`` and decode logits against re-prefill.

Row independence is the correctness keystone: every per-position op is
row-local and ``cached_attention`` masks by the row's own length, so a
sequence's logits are bitwise identical regardless of which other
sequences share the batch — the continuous-batching invariant.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.attention import cached_attention, prefix_cached_attention, rope
from ...ops.matrix import quantized_matmul
from ..batcher import ServingError

#: MXNET_DECODE_KV_DTYPE -> slab element type (scales, int8 only, ride in
#: separate f32 slabs — see kv_scale_slab_shape)
KV_SLAB_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
                  "int8": jnp.int8}


@dataclasses.dataclass(frozen=True)
class DecodeSpec:
    """Architecture facts not recoverable from weight shapes."""
    num_heads: int
    num_kv_heads: int = 0  # 0 = MHA (models/transformer.py convention)
    rope_base: float = 10000.0

    @property
    def hkv(self) -> int:
        return self.num_kv_heads or self.num_heads


def _ln(x, g, b, eps=1e-5):
    """ops.attention LayerNorm math (axis -1, eps 1e-5 — the op default
    models/transformer.py binds)."""
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _mm(params, x, name, l=None, act="int8"):
    """``x @ W.T`` with ``W = params[name]`` (``[l]`` when stacked).

    When ``mxnet_tpu.quant`` has rewritten this weight, a sibling
    ``<name>_scale`` entry exists and the matmul routes through
    ``ops.matrix.quantized_matmul`` (``act`` selects native-int8 vs
    dequant-on-load). With no scale entry this emits the exact
    pre-quantization expression — the quant-OFF jaxpr, and therefore the
    compiled program and its streams, are bitwise unchanged."""
    w = params[name] if l is None else params[name][l]
    sname = name + "_scale"
    if sname in params:
        s = params[sname] if l is None else params[sname][l]
        return quantized_matmul(x, w, s, act_dtype=act)
    return x @ w.T


def _quantize_kv(x):
    """Per-position symmetric int8 over the (Hkv, Dh) axes:
    ``x (..., Hkv, t, Dh) -> (q int8 same shape, scale (..., t) f32)``.
    Each cache position is written exactly once, so one scale per
    position never needs requantization — CoW forks copy scale rows
    alongside value blocks (ops.attention.dequantize_kv is the read-side
    inverse)."""
    amax = jnp.max(jnp.abs(x), axis=(-3, -1))
    scale = jnp.maximum(amax, 1e-12).astype(jnp.float32) / 127.0
    q = jnp.clip(jnp.round(x / scale[..., None, :, None]), -127, 127)
    return q.astype(jnp.int8), scale


class DecodeModel:
    """Canonical stacked decoder-LM weights + derived dims.

    ``params`` (all jnp arrays): embed (V, D); stacked per-layer
    ln1_g/ln1_b/ln2_g/ln2_b (L, D), wq (L, D, D), wk/wv (L, Dkv, D),
    wo (L, D, D), w1 (L, F, D), b1 (L, F), w2 (L, D, F), b2 (L, D);
    lnf_g/lnf_b (D,), pred_w (V, D), pred_b (V,). FC weights keep the
    (out, in) orientation of ops.nn.FullyConnected.
    """

    def __init__(self, params: Dict[str, jnp.ndarray], spec: DecodeSpec):
        self.params = params
        self.spec = spec
        # matmul strategy when params carry quantized weights (set by
        # mxnet_tpu.quant.quantize_decode_model); inert without them
        self.quant_act = "int8"
        self.vocab, self.dm = params["embed"].shape
        self.layers = params["wq"].shape[0]
        self.dff = params["w1"].shape[1]
        if self.dm % spec.num_heads:
            raise ServingError("model_dim %d not divisible by num_heads %d"
                               % (self.dm, spec.num_heads))
        self.head_dim = self.dm // spec.num_heads
        want_dkv = self.head_dim * spec.hkv
        if params["wk"].shape[1] != want_dkv:
            raise ServingError(
                "k projection rows %d != num_kv_heads*head_dim %d — wrong "
                "num_heads/num_kv_heads for these weights?"
                % (params["wk"].shape[1], want_dkv))

    # --- construction ----------------------------------------------------
    @classmethod
    def from_arg_params(cls, arg_params: Dict, spec: DecodeSpec,
                        dtype="float32") -> "DecodeModel":
        """Build from ``models/transformer.py`` checkpoint naming (the
        dict a Predictor loads: embed_weight, layer%d_q_weight, ...).
        Accepts NDArray or numpy values."""
        def get(name):
            if name not in arg_params:
                raise ServingError(
                    "decode model: checkpoint lacks %r — is this a "
                    "models/transformer.py decoder LM?" % name)
            v = arg_params[name]
            v = v.asnumpy() if hasattr(v, "asnumpy") else np.asarray(v)
            return jnp.asarray(v.astype(dtype))

        n_layers = 0
        while ("layer%d_q_weight" % n_layers) in arg_params:
            n_layers += 1
        if n_layers == 0:
            raise ServingError("decode model: no layer0_q_weight in params")
        other = [k for k in ("layer0_router_weight", "layer0_gate_weight")
                 if k in arg_params]
        if other or "layer0_ln1_beta" not in arg_params:
            raise ServingError(
                "decode model: the decode builders build the dense LayerNorm "
                "block of models/transformer.py alone; this checkpoint is of "
                "another kind (%s): RMSNorm, expert and window layers train "
                "through Symbol.simple_bind + make_train_step and are not "
                "served yet" % (", ".join(other) or "no layer0_ln1_beta"))
        stacked: Dict[str, list] = {k: [] for k in (
            "ln1_g", "ln1_b", "wq", "wk", "wv", "wo", "ln2_g", "ln2_b",
            "w1", "b1", "w2", "b2")}
        for i in range(n_layers):
            p = "layer%d" % i
            stacked["ln1_g"].append(get(p + "_ln1_gamma"))
            stacked["ln1_b"].append(get(p + "_ln1_beta"))
            stacked["wq"].append(get(p + "_q_weight"))
            stacked["wk"].append(get(p + "_k_weight"))
            stacked["wv"].append(get(p + "_v_weight"))
            stacked["wo"].append(get(p + "_o_weight"))
            stacked["ln2_g"].append(get(p + "_ln2_gamma"))
            stacked["ln2_b"].append(get(p + "_ln2_beta"))
            stacked["w1"].append(get(p + "_ffn1_weight"))
            stacked["b1"].append(get(p + "_ffn1_bias"))
            stacked["w2"].append(get(p + "_ffn2_weight"))
            stacked["b2"].append(get(p + "_ffn2_bias"))
        params = {k: jnp.stack(v) for k, v in stacked.items()}
        params["embed"] = get("embed_weight")
        params["lnf_g"] = get("lnf_gamma")
        params["lnf_b"] = get("lnf_beta")
        params["pred_w"] = get("pred_weight")
        params["pred_b"] = get("pred_bias")
        return cls(params, spec)

    def kv_slab_shape(self, slots: int, capacity: int) -> tuple:
        """(L, slots, Hkv, C, Dh) — one of the two per-replica slabs."""
        return (self.layers, slots, self.spec.hkv, capacity, self.head_dim)

    def kv_scale_slab_shape(self, slots: int, capacity: int) -> tuple:
        """(L, slots, C) — per-position f32 scales for an int8 KV slab
        (one scale per cached position, shared across Hkv and Dh)."""
        return (self.layers, slots, capacity)

    def fingerprint_items(self):
        """(name, array) pairs in stable order, for the progcache model
        fingerprint (weights are program ARGS here, but the fingerprint
        still keys persisted metadata like ladders)."""
        return [(k, self.params[k]) for k in sorted(self.params)]

    # --- the two programs -------------------------------------------------
    def _project(self, h, l, b, t):
        """q/k/v projections of (b, t, D) -> split-head (b, {H|Hkv}, t, Dh),
        roped later (rope needs absolute positions)."""
        p, s = self.params, self.spec
        act = getattr(self, "quant_act", "int8")
        q = _mm(p, h, "wq", l, act)
        k = _mm(p, h, "wk", l, act)
        v = _mm(p, h, "wv", l, act)
        q = q.reshape(b, t, s.num_heads, self.head_dim).transpose(0, 2, 1, 3)
        k = k.reshape(b, t, s.hkv, self.head_dim).transpose(0, 2, 1, 3)
        v = v.reshape(b, t, s.hkv, self.head_dim).transpose(0, 2, 1, 3)
        return q, k, v

    def _mlp(self, x, l):
        p = self.params
        act = getattr(self, "quant_act", "int8")
        h = _ln(x, p["ln2_g"][l], p["ln2_b"][l])
        h = jax.nn.gelu(_mm(p, h, "w1", l, act) + p["b1"][l])
        return x + (_mm(p, h, "w2", l, act) + p["b2"][l])

    def _head(self, x):
        p = self.params
        act = getattr(self, "quant_act", "int8")
        x = _ln(x, p["lnf_g"], p["lnf_b"])
        return _mm(p, x, "pred_w", None, act) + p["pred_b"]

    def build_prefill(self, bucket: int, capacity: int,
                      kv_dtype: str = "float32"):
        """Pure fn (params, tokens (1, T=bucket) i32, length (1,) i32) ->
        (logits (1, V) f32, k (L, 1, Hkv, C, Dh), v (...)). Padded
        positions >= length produce garbage kv that decode never reads
        (masked by length); the causal mask keeps them out of the
        returned last-real-position logits.

        ``kv_dtype`` re-types the RETURNED cache only (in-band prefill
        attention stays full precision — only stored state narrows):
        bf16 casts; int8 quantizes per position and appends (L, 1, C)
        k/v scale arrays to the outputs."""
        if bucket > capacity:
            raise ServingError("prefill bucket %d exceeds kv capacity %d"
                               % (bucket, capacity))
        spec = self.spec
        act = getattr(self, "quant_act", "int8")

        def prefill(params, tokens, length):
            self_p = DecodeModel.__new__(DecodeModel)
            self_p.params = params
            self_p.spec = spec
            self_p.quant_act = act
            self_p.vocab, self_p.dm = params["embed"].shape
            self_p.layers = params["wq"].shape[0]
            self_p.head_dim = self_p.dm // spec.num_heads
            x = jnp.take(params["embed"], tokens.astype(jnp.int32), axis=0)
            ks, vs = [], []
            for l in range(self_p.layers):
                h = _ln(x, params["ln1_g"][l], params["ln1_b"][l])
                q, k, v = self_p._project(h, l, 1, bucket)
                q, k = rope(q, base=spec.rope_base), \
                    rope(k, base=spec.rope_base)
                # same fusion seam as the serving forward path: the flash
                # kernel owns the on-TPU/shape gate and falls back to the
                # grouped einsum / reference math off it
                from ...ops.pallas import flash_attention as _fa
                att = _fa.flash_attention(q, k, v, causal=True)
                att = att.transpose(0, 2, 1, 3).reshape(1, bucket, self_p.dm)
                x = x + _mm(params, att, "wo", l, act)
                x = self_p._mlp(x, l)
                ks.append(k)
                vs.append(v)
            logits = self_p._head(x)  # (1, T, V)
            last = jnp.take_along_axis(
                logits, (length - 1).astype(jnp.int32)[:, None, None], axis=1
            )[:, 0, :]
            pad = ((0, 0), (0, 0), (0, 0), (0, capacity - bucket), (0, 0))
            k_out = jnp.pad(jnp.stack(ks), pad)   # (L, 1, Hkv, C, Dh)
            v_out = jnp.pad(jnp.stack(vs), pad)
            if kv_dtype == "int8":
                kq, k_s = _quantize_kv(k_out)     # scales (L, 1, C)
                vq, v_s = _quantize_kv(v_out)
                return last, kq, vq, k_s, v_s
            if kv_dtype == "bfloat16":
                return (last, k_out.astype(jnp.bfloat16),
                        v_out.astype(jnp.bfloat16))
            return last, k_out, v_out

        return prefill

    def build_decode(self, slots: int, capacity: int,
                     kv_dtype: str = "float32"):
        """Pure fn (params, k_slab, v_slab, lengths (B,) i32, tokens (B,)
        i32) -> (logits (B, V), k_slab, v_slab). Slabs are meant to be
        donated by the compiler wrapper: steady state rewrites C-slices in
        place and allocates only the (B, V) logits. Inactive slots run
        with lengths pinned to 0 — wasted lanes, never wrong lanes.

        ``kv_dtype``: bf16 re-types the slabs (writes cast, reads flow
        through the f32-accumulating einsum). int8 inserts f32 scale
        slabs (L, B, C) into the signature — (params, k_slab, v_slab,
        ks_slab, vs_slab, lengths, tokens) -> (logits, k, v, ks, vs) —
        quantizing each new position BEFORE attention reads the slab, so
        a token's own step sees exactly the values every later step sees.
        f32 keeps the historical jaxpr bitwise (the astype below folds
        away)."""
        spec = self.spec
        act = getattr(self, "quant_act", "int8")

        def body(params, k_slab, v_slab, ks_slab, vs_slab, lengths,
                 tokens):
            dm = params["embed"].shape[1]
            n_layers = params["wq"].shape[0]
            head_dim = dm // spec.num_heads
            lengths = lengths.astype(jnp.int32)
            x = jnp.take(params["embed"], tokens.astype(jnp.int32), axis=0)
            # rope positions: the new token sits at index `length`
            pos = lengths.reshape(slots, 1, 1)
            for l in range(n_layers):
                h = _ln(x, params["ln1_g"][l], params["ln1_b"][l])
                q = _mm(params, h, "wq", l, act).reshape(
                    slots, spec.num_heads, 1, head_dim)
                k_t = _mm(params, h, "wk", l, act).reshape(
                    slots, spec.hkv, 1, head_dim)
                v_t = _mm(params, h, "wv", l, act).reshape(
                    slots, spec.hkv, 1, head_dim)
                q = rope(q, positions=pos, base=spec.rope_base)
                k_t = rope(k_t, positions=pos, base=spec.rope_base)

                def write(cache, new, p):
                    # cache (Hkv, C, Dh), new (Hkv, 1, Dh): row's k/v lands
                    # at its own position p = lengths[i]
                    return jax.lax.dynamic_update_slice(cache, new, (0, p, 0))

                if ks_slab is None:
                    k_l = jax.vmap(write)(k_slab[l],
                                          k_t.astype(k_slab.dtype), lengths)
                    v_l = jax.vmap(write)(v_slab[l],
                                          v_t.astype(v_slab.dtype), lengths)
                    k_slab = k_slab.at[l].set(k_l)
                    v_slab = v_slab.at[l].set(v_l)
                    att = cached_attention(q, k_l, v_l, lengths)
                else:
                    kq, k_s = _quantize_kv(k_t)   # scales (B, 1)
                    vq, v_s = _quantize_kv(v_t)
                    k_l = jax.vmap(write)(k_slab[l], kq, lengths)
                    v_l = jax.vmap(write)(v_slab[l], vq, lengths)

                    def write_s(row, new, p):
                        # row (C,), new (1,): scale lands beside its value
                        return jax.lax.dynamic_update_slice(row, new, (p,))

                    ks_l = jax.vmap(write_s)(ks_slab[l], k_s, lengths)
                    vs_l = jax.vmap(write_s)(vs_slab[l], v_s, lengths)
                    k_slab = k_slab.at[l].set(k_l)
                    v_slab = v_slab.at[l].set(v_l)
                    ks_slab = ks_slab.at[l].set(ks_l)
                    vs_slab = vs_slab.at[l].set(vs_l)
                    att = cached_attention(q, k_l, v_l, lengths,
                                           k_scale=ks_l, v_scale=vs_l)
                att = att.transpose(0, 2, 1, 3).reshape(slots, dm)
                x = x + _mm(params, att, "wo", l, act)
                h2 = _ln(x, params["ln2_g"][l], params["ln2_b"][l])
                h2 = jax.nn.gelu(_mm(params, h2, "w1", l, act)
                                 + params["b1"][l])
                x = x + (_mm(params, h2, "w2", l, act) + params["b2"][l])
            logits = _mm(params, _ln(x, params["lnf_g"], params["lnf_b"]),
                         "pred_w", None, act) + params["pred_b"]
            if ks_slab is None:
                return logits, k_slab, v_slab
            return logits, k_slab, v_slab, ks_slab, vs_slab

        if kv_dtype == "int8":
            def decode(params, k_slab, v_slab, ks_slab, vs_slab, lengths,
                       tokens):
                return body(params, k_slab, v_slab, ks_slab, vs_slab,
                            lengths, tokens)
        else:
            def decode(params, k_slab, v_slab, lengths, tokens):
                return body(params, k_slab, v_slab, None, None, lengths,
                            tokens)

        return decode

    def paged_slab_shape(self, num_blocks: int, block_tokens: int) -> tuple:
        """(L, num_blocks, Hkv, T, Dh) — one of the two paged slabs.
        ``num_blocks`` INCLUDES physical block 0, the reserved /dev/null
        block inactive lanes and padded positions write into."""
        return (self.layers, num_blocks, self.spec.hkv, block_tokens,
                self.head_dim)

    def paged_scale_slab_shape(self, num_blocks: int,
                               block_tokens: int) -> tuple:
        """(L, num_blocks, T) — per-position f32 scales for an int8 paged
        slab (block 0 included, same trash-block discipline)."""
        return (self.layers, num_blocks, block_tokens)

    def build_paged_prefill(self, bucket: int, block_tokens: int,
                            max_blocks: int, kv_dtype: str = "float32"):
        """Pure fn (params, k_slab, v_slab, table (MB,) i32, ctx_len ()
        i32, tokens (1, T=bucket) i32, n (1,) i32, fork_src () i32,
        fork_dst () i32) -> (logits (1, V), k_slab, v_slab).

        The paged admit path folds THREE things into one donated-slab
        program so the program set stays (ladder + one decode):

        1. **Copy-on-write fork**: physical block ``fork_src`` is copied
           into ``fork_dst`` first (both 0 — the trash block — when no
           fork), so a suffix that diverges inside a shared prefix block
           lands in a private copy while every other sharer keeps reading
           the original.
        2. **Chunked prefill over the cached prefix**: the first
           ``ctx_len`` positions are gathered from the slab via ``table``
           (shared prefix blocks materialize ONCE and are only read
           here); the ``n`` suffix tokens attend to that prefix plus
           causally to each other, roped at absolute positions
           ``ctx_len + j``.
        3. **Admit**: each suffix position's k/v is scattered to physical
           block ``table[(ctx_len + j) // T]`` offset ``(ctx_len + j) % T``
           (padded positions j >= n go to trash block 0).

        int8 ``kv_dtype`` adds scale slabs right after the value slabs
        (same donation discipline): (params, k_slab, v_slab, ks_slab,
        vs_slab, table, ...) -> (logits, k, v, ks, vs). The CoW fork
        copies scale blocks alongside value blocks, the prefix gather
        widens through the per-position scales, and the suffix scatter
        stores freshly quantized positions + their scales.
        """
        spec = self.spec
        act = getattr(self, "quant_act", "int8")
        T = int(block_tokens)
        mb = int(max_blocks)
        cap = T * mb

        def body(params, k_slab, v_slab, ks_slab, vs_slab, table, ctx_len,
                 tokens, n, fork_src, fork_dst):
            self_p = DecodeModel.__new__(DecodeModel)
            self_p.params = params
            self_p.spec = spec
            self_p.quant_act = act
            self_p.vocab, self_p.dm = params["embed"].shape
            self_p.layers = params["wq"].shape[0]
            self_p.head_dim = self_p.dm // spec.num_heads
            hkv = spec.hkv
            ctx_len = ctx_len.astype(jnp.int32)
            table = table.astype(jnp.int32)
            # (1) CoW fork: materialize the divergent block privately
            # before anything reads through the table (whose boundary
            # entry already names fork_dst).
            k_slab = k_slab.at[:, fork_dst].set(k_slab[:, fork_src])
            v_slab = v_slab.at[:, fork_dst].set(v_slab[:, fork_src])
            if ks_slab is not None:
                ks_slab = ks_slab.at[:, fork_dst].set(ks_slab[:, fork_src])
                vs_slab = vs_slab.at[:, fork_dst].set(vs_slab[:, fork_src])
            x = jnp.take(params["embed"], tokens.astype(jnp.int32), axis=0)
            j = jnp.arange(bucket, dtype=jnp.int32)
            pos = ctx_len + j                       # absolute positions
            # suffix k/v land at table[pos // T] : pos % T; padded lanes
            # (j >= n) land in trash block 0 (never read unmasked)
            phys = jnp.where(j < n[0],
                             table[jnp.clip(pos // T, 0, mb - 1)], 0)
            off = pos % T
            for l in range(self_p.layers):
                h = _ln(x, params["ln1_g"][l], params["ln1_b"][l])
                q, k, v = self_p._project(h, l, 1, bucket)
                q = rope(q, positions=pos, base=spec.rope_base)
                k = rope(k, positions=pos, base=spec.rope_base)
                # (2) gather the cached prefix through the block table
                k_ctx = k_slab[l][table].transpose(1, 0, 2, 3) \
                    .reshape(1, hkv, cap, self_p.head_dim)
                v_ctx = v_slab[l][table].transpose(1, 0, 2, 3) \
                    .reshape(1, hkv, cap, self_p.head_dim)
                if ks_slab is None:
                    att = prefix_cached_attention(q, k_ctx, v_ctx, ctx_len,
                                                  k, v)
                else:
                    k_sctx = ks_slab[l][table].reshape(1, cap)
                    v_sctx = vs_slab[l][table].reshape(1, cap)
                    att = prefix_cached_attention(q, k_ctx, v_ctx, ctx_len,
                                                  k, v, k_scale=k_sctx,
                                                  v_scale=v_sctx)
                att = att.transpose(0, 2, 1, 3).reshape(1, bucket,
                                                        self_p.dm)
                x = x + _mm(params, att, "wo", l, act)
                x = self_p._mlp(x, l)
                # (3) admit: scatter this layer's suffix k/v into place
                if ks_slab is None:
                    k_slab = k_slab.at[l, phys, :, off, :].set(
                        k[0].transpose(1, 0, 2).astype(k_slab.dtype))
                    v_slab = v_slab.at[l, phys, :, off, :].set(
                        v[0].transpose(1, 0, 2).astype(v_slab.dtype))
                else:
                    kq, k_s = _quantize_kv(k)     # scales (1, bucket)
                    vq, v_s = _quantize_kv(v)
                    k_slab = k_slab.at[l, phys, :, off, :].set(
                        kq[0].transpose(1, 0, 2))
                    v_slab = v_slab.at[l, phys, :, off, :].set(
                        vq[0].transpose(1, 0, 2))
                    ks_slab = ks_slab.at[l, phys, off].set(k_s[0])
                    vs_slab = vs_slab.at[l, phys, off].set(v_s[0])
            logits = self_p._head(x)  # (1, T, V)
            last = jnp.take_along_axis(
                logits, (n - 1).astype(jnp.int32)[:, None, None], axis=1
            )[:, 0, :]
            if ks_slab is None:
                return last, k_slab, v_slab
            return last, k_slab, v_slab, ks_slab, vs_slab

        if kv_dtype == "int8":
            def prefill(params, k_slab, v_slab, ks_slab, vs_slab, table,
                        ctx_len, tokens, n, fork_src, fork_dst):
                return body(params, k_slab, v_slab, ks_slab, vs_slab,
                            table, ctx_len, tokens, n, fork_src, fork_dst)
        else:
            def prefill(params, k_slab, v_slab, table, ctx_len, tokens, n,
                        fork_src, fork_dst):
                return body(params, k_slab, v_slab, None, None, table,
                            ctx_len, tokens, n, fork_src, fork_dst)

        return prefill

    def build_paged_decode(self, slots: int, block_tokens: int,
                           max_blocks: int, kv_dtype: str = "float32"):
        """Pure fn (params, k_slab, v_slab, tables (B, MB) i32, lengths
        (B,) i32, tokens (B,) i32) -> (logits (B, V), k_slab, v_slab).

        The paged twin of ``build_decode``: each row's new k/v is
        scattered to physical block ``tables[i, lengths[i] // T]`` offset
        ``lengths[i] % T`` (the scheduler guarantees that block is
        PRIVATE to row i — copy-on-write resolves sharing before any
        write is scheduled), then attention gathers the row's dense
        (Hkv, C, Dh) view through its table and masks by length exactly
        like the unpaged step. Inactive lanes carry an all-zero table, so
        their writes land in trash block 0 — wasted lanes, never wrong
        lanes, same fixed-shape discipline as the unpaged program.

        int8 ``kv_dtype`` adds scale slabs (L, NB, T) after the value
        slabs, written at the same (phys_w, off_w) site and gathered
        per row as (B, C) for the widening read — see ``build_decode``
        for the read-your-own-write ordering argument.
        """
        spec = self.spec
        act = getattr(self, "quant_act", "int8")
        T = int(block_tokens)
        mb = int(max_blocks)
        cap = T * mb

        def body(params, k_slab, v_slab, ks_slab, vs_slab, tables,
                 lengths, tokens):
            dm = params["embed"].shape[1]
            n_layers = params["wq"].shape[0]
            head_dim = dm // spec.num_heads
            hkv = spec.hkv
            lengths = lengths.astype(jnp.int32)
            tables = tables.astype(jnp.int32)
            x = jnp.take(params["embed"], tokens.astype(jnp.int32), axis=0)
            pos = lengths.reshape(slots, 1, 1)
            # write site per row: its own (always-private) block
            phys_w = jnp.take_along_axis(
                tables, jnp.clip(lengths // T, 0, mb - 1)[:, None],
                axis=1)[:, 0]
            off_w = lengths % T
            for l in range(n_layers):
                h = _ln(x, params["ln1_g"][l], params["ln1_b"][l])
                q = _mm(params, h, "wq", l, act).reshape(
                    slots, spec.num_heads, 1, head_dim)
                k_t = _mm(params, h, "wk", l, act).reshape(
                    slots, hkv, 1, head_dim)
                v_t = _mm(params, h, "wv", l, act).reshape(
                    slots, hkv, 1, head_dim)
                q = rope(q, positions=pos, base=spec.rope_base)
                k_t = rope(k_t, positions=pos, base=spec.rope_base)
                if ks_slab is not None:
                    kq, k_s = _quantize_kv(k_t)   # scales (B, 1)
                    vq, v_s = _quantize_kv(v_t)
                    k_slab = k_slab.at[l, phys_w, :, off_w, :].set(
                        kq[:, :, 0, :])
                    v_slab = v_slab.at[l, phys_w, :, off_w, :].set(
                        vq[:, :, 0, :])
                    ks_slab = ks_slab.at[l, phys_w, off_w].set(k_s[:, 0])
                    vs_slab = vs_slab.at[l, phys_w, off_w].set(v_s[:, 0])
                else:
                    k_slab = k_slab.at[l, phys_w, :, off_w, :].set(
                        k_t[:, :, 0, :].astype(k_slab.dtype))
                    v_slab = v_slab.at[l, phys_w, :, off_w, :].set(
                        v_t[:, :, 0, :].astype(v_slab.dtype))
                # gather each row's dense view (write first, so the new
                # token's k/v is visible to its own attention)
                k_l = k_slab[l][tables].transpose(0, 2, 1, 3, 4) \
                    .reshape(slots, hkv, cap, head_dim)
                v_l = v_slab[l][tables].transpose(0, 2, 1, 3, 4) \
                    .reshape(slots, hkv, cap, head_dim)
                if ks_slab is not None:
                    ks_l = ks_slab[l][tables].reshape(slots, cap)
                    vs_l = vs_slab[l][tables].reshape(slots, cap)
                    att = cached_attention(q, k_l, v_l, lengths,
                                           k_scale=ks_l, v_scale=vs_l)
                else:
                    att = cached_attention(q, k_l, v_l, lengths)
                att = att.transpose(0, 2, 1, 3).reshape(slots, dm)
                x = x + _mm(params, att, "wo", l, act)
                h2 = _ln(x, params["ln2_g"][l], params["ln2_b"][l])
                h2 = jax.nn.gelu(_mm(params, h2, "w1", l, act)
                                 + params["b1"][l])
                x = x + (_mm(params, h2, "w2", l, act) + params["b2"][l])
            logits = _mm(params, _ln(x, params["lnf_g"], params["lnf_b"]),
                         "pred_w", None, act) + params["pred_b"]
            if ks_slab is None:
                return logits, k_slab, v_slab
            return logits, k_slab, v_slab, ks_slab, vs_slab

        if kv_dtype == "int8":
            def decode(params, k_slab, v_slab, ks_slab, vs_slab, tables,
                       lengths, tokens):
                return body(params, k_slab, v_slab, ks_slab, vs_slab,
                            tables, lengths, tokens)
        else:
            def decode(params, k_slab, v_slab, tables, lengths, tokens):
                return body(params, k_slab, v_slab, None, None, tables,
                            lengths, tokens)

        return decode

    def build_verify(self, slots: int, capacity: int, window: int,
                     kv_dtype: str = "float32"):
        """Pure fn (params, k_slab, v_slab, lengths (B,) i32, wtokens
        (B, W) i32) -> (logits (B, W, V), k_slab, v_slab) — the
        speculative-decode verify program (serving/generate/spec.py).

        A batched W-position forward per row: ``wtokens[i] = [last_token,
        d_1 .. d_k]`` (W = k + 1 draft window) sits at absolute positions
        ``lengths[i] + j``, attends to the row's cached prefix
        (``prefix_cached_attention`` with per-row ctx_len — positions
        >= lengths[i] in the slab are masked, so the draft pass's scratch
        writes are invisible) plus causally to earlier window positions,
        and every window position's k/v is scattered back into the slab —
        OVERWRITING the draft model's scratch rows with target-exact
        values, which is what makes rewind a pure length edit. Writes at
        positions >= capacity are dropped (out-of-bounds scatter). Shapes
        are independent of how many draft tokens end up accepted:
        ``logits[i, j]`` is the target's next-token distribution after
        sequence position ``lengths[i] + j``, and the host picks the
        longest matching prefix / runs rejection sampling over it.

        ``kv_dtype``: bf16 writes cast; int8 quantizes each window
        position (same per-position scales as ``build_decode``) and feeds
        the attention the quantized-then-dequantized values, so a window
        position's own logits see exactly the cache bytes every later
        step reads — the read-your-own-write discipline that keeps
        accept-path streams bitwise equal to vanilla decode."""
        spec = self.spec
        act = getattr(self, "quant_act", "int8")
        W = int(window)

        def body(params, k_slab, v_slab, ks_slab, vs_slab, lengths,
                 wtokens):
            dm = params["embed"].shape[1]
            n_layers = params["wq"].shape[0]
            head_dim = dm // spec.num_heads
            hkv = spec.hkv
            lengths = lengths.astype(jnp.int32)
            x = jnp.take(params["embed"], wtokens.astype(jnp.int32), axis=0)
            pos = lengths[:, None] + jnp.arange(W, dtype=jnp.int32)  # (B, W)
            rows = jnp.arange(slots, dtype=jnp.int32)[:, None]       # (B, 1)
            rpos = pos[:, None, :]            # (B, 1, W): rope over heads
            for l in range(n_layers):
                h = _ln(x, params["ln1_g"][l], params["ln1_b"][l])
                q = _mm(params, h, "wq", l, act).reshape(
                    slots, W, spec.num_heads, head_dim).transpose(0, 2, 1, 3)
                k_t = _mm(params, h, "wk", l, act).reshape(
                    slots, W, hkv, head_dim).transpose(0, 2, 1, 3)
                v_t = _mm(params, h, "wv", l, act).reshape(
                    slots, W, hkv, head_dim).transpose(0, 2, 1, 3)
                q = rope(q, positions=rpos, base=spec.rope_base)
                k_t = rope(k_t, positions=rpos, base=spec.rope_base)
                if ks_slab is not None:
                    kq, k_s = _quantize_kv(k_t)   # scales (B, W)
                    vq, v_s = _quantize_kv(v_t)
                    k_slab = k_slab.at[l, rows, :, pos, :].set(
                        kq.transpose(0, 2, 1, 3), mode="drop")
                    v_slab = v_slab.at[l, rows, :, pos, :].set(
                        vq.transpose(0, 2, 1, 3), mode="drop")
                    ks_slab = ks_slab.at[l, rows, pos].set(k_s, mode="drop")
                    vs_slab = vs_slab.at[l, rows, pos].set(v_s, mode="drop")
                    # window keys as later reads will see them: quantized
                    # then widened (dequantize_kv's math, in-register)
                    k_win = kq.astype(jnp.float32) * k_s[:, None, :, None]
                    v_win = vq.astype(jnp.float32) * v_s[:, None, :, None]
                    att = prefix_cached_attention(
                        q, k_slab[l], v_slab[l], lengths[:, None], k_win,
                        v_win, k_scale=ks_slab[l], v_scale=vs_slab[l])
                else:
                    k_w = k_t.astype(k_slab.dtype)
                    v_w = v_t.astype(v_slab.dtype)
                    k_slab = k_slab.at[l, rows, :, pos, :].set(
                        k_w.transpose(0, 2, 1, 3), mode="drop")
                    v_slab = v_slab.at[l, rows, :, pos, :].set(
                        v_w.transpose(0, 2, 1, 3), mode="drop")
                    att = prefix_cached_attention(
                        q, k_slab[l], v_slab[l], lengths[:, None], k_w, v_w)
                att = att.transpose(0, 2, 1, 3).reshape(slots, W, dm)
                x = x + _mm(params, att, "wo", l, act)
                x = self._mlp_p(params, x, l, act)
            logits = _mm(params, _ln(x, params["lnf_g"], params["lnf_b"]),
                         "pred_w", None, act) + params["pred_b"]
            if ks_slab is None:
                return logits, k_slab, v_slab
            return logits, k_slab, v_slab, ks_slab, vs_slab

        if kv_dtype == "int8":
            def verify(params, k_slab, v_slab, ks_slab, vs_slab, lengths,
                       wtokens):
                return body(params, k_slab, v_slab, ks_slab, vs_slab,
                            lengths, wtokens)
        else:
            def verify(params, k_slab, v_slab, lengths, wtokens):
                return body(params, k_slab, v_slab, None, None, lengths,
                            wtokens)

        return verify

    def build_paged_verify(self, slots: int, block_tokens: int,
                           max_blocks: int, window: int,
                           kv_dtype: str = "float32"):
        """Paged twin of ``build_verify``: (params, k_slab, v_slab,
        tables (B, MB) i32, lengths (B,) i32, wtokens (B, W) i32) ->
        (logits (B, W, V), k_slab, v_slab).

        Window position ``lengths[i] + j`` scatters to physical block
        ``tables[i, (lengths[i]+j) // T]`` offset ``% T`` — positions at
        or past capacity, and positions beyond the row's block
        reservation (table entry 0), land in trash block 0, never read
        unmasked. The admission reservation already covers every position
        a stream can ever COMMIT (``min(prompt + max_new, capacity)``),
        so accepted tokens always land in reserved private blocks and the
        speculative tail needs no allocation — rewind stays a host-side
        length edit (``PagedKVCacheManager.truncate``)."""
        spec = self.spec
        act = getattr(self, "quant_act", "int8")
        T = int(block_tokens)
        mb = int(max_blocks)
        cap = T * mb
        W = int(window)

        def body(params, k_slab, v_slab, ks_slab, vs_slab, tables,
                 lengths, wtokens):
            dm = params["embed"].shape[1]
            n_layers = params["wq"].shape[0]
            head_dim = dm // spec.num_heads
            hkv = spec.hkv
            lengths = lengths.astype(jnp.int32)
            tables = tables.astype(jnp.int32)
            x = jnp.take(params["embed"], wtokens.astype(jnp.int32), axis=0)
            pos = lengths[:, None] + jnp.arange(W, dtype=jnp.int32)  # (B, W)
            rpos = pos[:, None, :]
            # write sites: clip is NOT enough here — clamping pos >= cap
            # into the last table entry would wrap onto a REAL block, so
            # out-of-range positions are routed to trash explicitly
            phys = jnp.where(
                pos < cap,
                jnp.take_along_axis(tables,
                                    jnp.clip(pos // T, 0, mb - 1), axis=1),
                0)
            off = pos % T
            for l in range(n_layers):
                h = _ln(x, params["ln1_g"][l], params["ln1_b"][l])
                q = _mm(params, h, "wq", l, act).reshape(
                    slots, W, spec.num_heads, head_dim).transpose(0, 2, 1, 3)
                k_t = _mm(params, h, "wk", l, act).reshape(
                    slots, W, hkv, head_dim).transpose(0, 2, 1, 3)
                v_t = _mm(params, h, "wv", l, act).reshape(
                    slots, W, hkv, head_dim).transpose(0, 2, 1, 3)
                q = rope(q, positions=rpos, base=spec.rope_base)
                k_t = rope(k_t, positions=rpos, base=spec.rope_base)
                if ks_slab is not None:
                    kq, k_s = _quantize_kv(k_t)   # scales (B, W)
                    vq, v_s = _quantize_kv(v_t)
                    k_slab = k_slab.at[l, phys, :, off, :].set(
                        kq.transpose(0, 2, 1, 3))
                    v_slab = v_slab.at[l, phys, :, off, :].set(
                        vq.transpose(0, 2, 1, 3))
                    ks_slab = ks_slab.at[l, phys, off].set(k_s)
                    vs_slab = vs_slab.at[l, phys, off].set(v_s)
                    k_win = kq.astype(jnp.float32) * k_s[:, None, :, None]
                    v_win = vq.astype(jnp.float32) * v_s[:, None, :, None]
                else:
                    k_win = k_t.astype(k_slab.dtype)
                    v_win = v_t.astype(v_slab.dtype)
                    k_slab = k_slab.at[l, phys, :, off, :].set(
                        k_win.transpose(0, 2, 1, 3))
                    v_slab = v_slab.at[l, phys, :, off, :].set(
                        v_win.transpose(0, 2, 1, 3))
                # gather each row's dense ctx view through its table
                # (write-first like build_paged_decode; the window span is
                # masked by the per-row ctx_len anyway)
                k_l = k_slab[l][tables].transpose(0, 2, 1, 3, 4) \
                    .reshape(slots, hkv, cap, head_dim)
                v_l = v_slab[l][tables].transpose(0, 2, 1, 3, 4) \
                    .reshape(slots, hkv, cap, head_dim)
                if ks_slab is not None:
                    ks_l = ks_slab[l][tables].reshape(slots, cap)
                    vs_l = vs_slab[l][tables].reshape(slots, cap)
                    att = prefix_cached_attention(
                        q, k_l, v_l, lengths[:, None], k_win, v_win,
                        k_scale=ks_l, v_scale=vs_l)
                else:
                    att = prefix_cached_attention(
                        q, k_l, v_l, lengths[:, None], k_win, v_win)
                att = att.transpose(0, 2, 1, 3).reshape(slots, W, dm)
                x = x + _mm(params, att, "wo", l, act)
                x = self._mlp_p(params, x, l, act)
            logits = _mm(params, _ln(x, params["lnf_g"], params["lnf_b"]),
                         "pred_w", None, act) + params["pred_b"]
            if ks_slab is None:
                return logits, k_slab, v_slab
            return logits, k_slab, v_slab, ks_slab, vs_slab

        if kv_dtype == "int8":
            def verify(params, k_slab, v_slab, ks_slab, vs_slab, tables,
                       lengths, wtokens):
                return body(params, k_slab, v_slab, ks_slab, vs_slab,
                            tables, lengths, wtokens)
        else:
            def verify(params, k_slab, v_slab, tables, lengths, wtokens):
                return body(params, k_slab, v_slab, None, None, tables,
                            lengths, wtokens)

        return verify

    @staticmethod
    def _mlp_p(params, x, l, act):
        """``_mlp`` against explicit params (builders close over the
        traced params argument, not ``self.params``)."""
        h = _ln(x, params["ln2_g"][l], params["ln2_b"][l])
        h = jax.nn.gelu(_mm(params, h, "w1", l, act) + params["b1"][l])
        return x + (_mm(params, h, "w2", l, act) + params["b2"][l])

    def build_admit(self, slots: int, capacity: int,
                    kv_dtype: str = "float32"):
        """Pure fn (k_slab, v_slab, k_new (L,1,Hkv,C,Dh), v_new, slot i32)
        -> updated slabs (donated): slot a freshly prefilled sequence's kv
        into its allocated row. int8 ``kv_dtype`` extends both sides with
        the (L, 1, C) scale rows prefill returned."""
        if kv_dtype == "int8":
            def admit(k_slab, v_slab, ks_slab, vs_slab, k_new, v_new,
                      ks_new, vs_new, slot):
                slot = slot.astype(jnp.int32)
                z = jnp.int32(0)
                return (jax.lax.dynamic_update_slice(k_slab, k_new,
                                                     (z, slot, z, z, z)),
                        jax.lax.dynamic_update_slice(v_slab, v_new,
                                                     (z, slot, z, z, z)),
                        jax.lax.dynamic_update_slice(ks_slab, ks_new,
                                                     (z, slot, z)),
                        jax.lax.dynamic_update_slice(vs_slab, vs_new,
                                                     (z, slot, z)))

            return admit

        def admit(k_slab, v_slab, k_new, v_new, slot):
            slot = slot.astype(jnp.int32)
            z = jnp.int32(0)
            return (jax.lax.dynamic_update_slice(k_slab, k_new,
                                                 (z, slot, z, z, z)),
                    jax.lax.dynamic_update_slice(v_slab, v_new,
                                                 (z, slot, z, z, z)))

        return admit


def infer_spec_dims(arg_params: Dict) -> Dict[str, int]:
    """Dims recoverable from a models/transformer.py checkpoint (vocab,
    model_dim, ffn_dim, layers) — head counts must come from DecodeSpec."""
    embed = arg_params["embed_weight"]
    shape = embed.shape
    n_layers = 0
    while ("layer%d_q_weight" % n_layers) in arg_params:
        n_layers += 1
    ffn1 = arg_params["layer0_ffn1_weight"]
    return {"vocab": int(shape[0]), "model_dim": int(shape[1]),
            "layers": n_layers, "ffn_dim": int(ffn1.shape[0])}
