"""Family ``qwen3_next_lm``: Qwen/Qwen3-Next-80B-A3B-Instruct (``model_type``
``qwen3_next``) as one chip of a thirty-two-way expert-parallel layer
trains it. Every norm is an RMSNorm at ``rms_norm_eps`` (``N``) whose scale
starts at 1: the published zero-centred norm ``x_hat (1 + w)`` held as the
plain scale ``1 + w``. No bias anywhere.

A period of ``full_attention_interval`` 4 layers: three Gated DeltaNet
layers, then a gated attention layer. A Gated DeltaNet layer, for the normed
input ``u`` (``hidden_size`` 2048), 16 key heads and 32 value heads of 128::

    [q, k, v] = u W_qkv   z = u W_z   [b, a] = u W_ba    (q, k: 16 x 128;
    q, k, v = silu(causal depthwise conv, 4 taps, no bias)  v, z: 32 x 128)
    q, k <- x / sqrt(sum x^2 + 1e-6) a head;  q <- q / sqrt(128)
    beta = sigmoid(b)    g = -exp(A_log) softplus(a + dt_bias)   (float32)
    S_t = e^g_t S_{t-1} + k_t (beta_t (v_t - e^g_t S_{t-1}^T k_t))^T,  S_0 = 0
    o_t = S_t^T q_t                 value head j reads key head floor(j / 2)
    y = (N(o_t) over each value head) * silu(z_t), then W_out (4096 -> 2048)

The gated attention layer: 16 query heads over 2 KV heads at head size 256;
the query and its output gate from ``u``, k and v from ``u``; ``N`` over
each q and k head; RoPE (halves rotated against each other, base
``rope_theta``) over 64 dims of each head (``partial_rotary_factor``);
causal softmax of ``q.k / 16``; each head's output times ``sigmoid`` of its
gate, element by element, then W_o. Every layer's feed-forward is an expert
layer: ``p = softmax(u W_r^T)`` over all 512 experts in float32, the top 10
renormalised; ``y = sum over the chosen AND HELD of p_e down_e(silu(gate_e
u) * up_e u) + sigmoid(u . w_sg) down_s(silu(gate_s u) * up_s u)``. A block
is ``x + mixer(N x)``, then ``+ FFN(N .)``; the head reads ``N`` of the
stack's output.

The chip's share (``configs/qwen3-next-80b-a3b.train.json``): experts
0..held-1 of every expert layer, rows 0..vocab_size-1 of the vocabulary, the
layers ``layers_run`` names. What experts held..511 would add is left out,
here and in the program alike. The configuration file lists under
``assumed`` what the config does not give.

Two halves that share nothing but the seed:

- the program's side: the symbol (``models.get_symbol("transformer-lm",
  ...)`` with its per-layer kinds), the seeded parameters and token batches,
  made on the device in one jitted call;
- the plain reference: forward, loss, gradients and SGD-with-momentum in
  straightforward ``jax.numpy``, float32 at ``highest`` matmul precision,
  no kernel, one sequence and one head at a time, the delta rule TOKEN BY
  TOKEN as written above (no chunk, no WY form: nothing of the program's
  algorithm), its scan recomputed in the backward pass a block of tokens at
  a time so that it fits, the expert layer a masked dense sum over the held
  experts. It imports nothing of the program.

Departures, each marked ``# departure`` below: weights are random from the
seed.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from lib import counts_qwen3next as counts
from lib import refmath
from lib.refmath import seed_key, q8 as _q8

HP = jax.lax.Precision.HIGHEST
UNIT_EPS = 1e-6      # the l2 norm of q and k: the published l2norm's eps
A_LOG_FLOOR = 1e-3   # A_log = log U(A_LOG_FLOOR, 16): log 0 is no weight
SCAN_BLOCK = 256     # tokens a recomputed block of the reference's scan


# --- sizes -------------------------------------------------------------------

def rotary_dims(cfg):
    return int(cfg["head_dim"] * cfg["partial_rotary_factor"])


def _mixer_shapes(cfg, p, mixer):
    """One block's mixer leaves, named and ordered as the package's symbol
    lists them."""
    d = cfg["hidden_size"]
    if mixer == "gdn":
        hk, hv, dk, dv = counts.gdn_sizes(cfg)
        qkv = 2 * hk * dk + hv * dv
        return {p + "gdn_qkv_weight": (qkv, d),
                p + "gdn_ba_weight": (2 * hv, d),
                p + "gdn_core_conv_weight": (qkv,
                                             cfg["linear_conv_kernel_dim"]),
                p + "gdn_core_A_log": (hv,), p + "gdn_core_dt_bias": (hv,),
                p + "gdn_norm_gamma": (dv,), p + "gdn_z_weight": (hv * dv, d),
                p + "gdn_out_weight": (d, hv * dv)}
    h, dh = cfg["num_attention_heads"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * dh
    return {p + "q_weight": (h * dh, d), p + "k_weight": (kv, d),
            p + "v_weight": (kv, d), p + "attn_q_norm_gamma": (dh,),
            p + "attn_k_norm_gamma": (dh,), p + "q_gate_weight": (h * dh, d),
            p + "o_weight": (d, h * dh)}


def _layer_shapes(cfg, p, mixer):
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    fs, held = cfg["shared_expert_intermediate_size"], counts.held(cfg)
    shapes = {p + "ln1_gamma": (d,)}
    shapes.update(_mixer_shapes(cfg, p, mixer))
    shapes.update({p + "ln2_gamma": (d,),
                   p + "router_weight": (cfg["num_experts"], d),
                   p + "gate_weight": (held, f, d),
                   p + "up_weight": (held, f, d),
                   p + "down_weight": (held, d, f),
                   p + "shared_ffn1_weight": (fs, d),
                   p + "shared_ffn3_weight": (fs, d),
                   p + "shared_ffn2_weight": (d, fs),
                   p + "shared_gate_weight": (1, d)})
    return shapes


def param_shapes(cfg):
    """name -> shape, named and ordered as the package's symbol lists them."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    shapes = {"embed_weight": (v, d)}
    for i, mixer in enumerate(counts.layers(cfg)):
        shapes.update(_layer_shapes(cfg, "layer%d_" % i, mixer))
    shapes.update({"lnf_gamma": (d,), "pred_weight": (v, d)})
    return shapes


def step_flops(cfg, traffic):
    return counts.train_step_flops(cfg, traffic["batch"], traffic["seq_len"])


def _scales(cfg):
    """(matrices' std, A's largest draw): ``initializer_range`` and 16, the
    published initialisation's, unless the file says ``a_log_max`` (a toy
    whose state should outlive a chunk)."""
    return (float(cfg.get("initializer_range", 0.02)),
            float(cfg.get("a_log_max", 16.0)))


_KINDS = ("_gamma", "_A_log", "_dt_bias")  # else: a matrix or the taps


def _init_leaf(key, name, shape, scales):
    std, a_max = scales
    if name.endswith("_gamma"):
        return jnp.ones(shape, jnp.float32)
    if name.endswith("_dt_bias"):
        return jnp.ones(shape, jnp.float32)
    if name.endswith("_A_log"):  # departure: drawn from the seed
        return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                          A_LOG_FLOOR, a_max))
    return std * jax.random.normal(key, shape, jnp.float32)  # departure


def init_params(cfg, seed):
    """Every leaf from the seed in one jitted call, on the default device,
    float32 (the trainer's master weights): matrices and the convolution's
    taps normal at ``initializer_range``, A_log the log of a uniform draw
    up to 16, dt_bias and the RMSNorm scales 1."""
    shapes = param_shapes(cfg)
    scales = _scales(cfg)

    @jax.jit
    def make(key0):
        key = jax.random.fold_in(key0, 1)
        return {n: _init_leaf(jax.random.fold_in(key, i), n, s, scales)
                for i, (n, s) in enumerate(shapes.items())}

    return make(seed_key(seed))


def init_leaf(cfg, seed, name):
    """One leaf again, float32 (the same bits ``init_params`` gave)."""
    shapes = param_shapes(cfg)
    key = jax.random.fold_in(jax.random.fold_in(seed_key(seed), 1),
                             list(shapes).index(name))
    kind = next((k for k in _KINDS if name.endswith(k)), "_weight")
    return _leaf_jit(kind, shapes[name], _scales(cfg))(key)


@functools.lru_cache(maxsize=None)
def _leaf_jit(kind, shape, scales):
    return jax.jit(lambda key: _init_leaf(key, kind, shape, scales))


def make_batches(cfg, traffic, seed, n):
    """``n`` batches of token ids uniform over the vocabulary rows held,
    rows all different, and their next-token labels; int32 on the
    device."""
    b, t, v = traffic["batch"], traffic["seq_len"], cfg["vocab_size"]

    @jax.jit
    def make(key):
        ids = jax.random.randint(jax.random.fold_in(key, 2), (n, b, t + 1),
                                 0, v, jnp.int32)
        return ids[:, :, :-1], ids[:, :, 1:]

    x, y = make(seed_key(seed))
    return [({"data": x[i]}, {"softmax_label": y[i]}) for i in range(n)]


def input_descs(cfg, traffic):
    """(name, shape, dtype) of the data and label inputs as bound."""
    shape = (traffic["batch"], traffic["seq_len"])
    return [("data", shape, "int32")], [("softmax_label", shape, "int32")]


# --- the program's side ------------------------------------------------------

def layer_kind(cfg, mixer):
    """The ``LAYER_KINDS`` dict of a layer whose mixer is ``mixer``."""
    kind = {"norm": "rms", "ffn": "experts", "router_input": "ffn",
            "shared_dim": cfg["shared_expert_intermediate_size"],
            "shared_gate": True}
    if mixer == "gdn":
        hk, hv, dk, dv = counts.gdn_sizes(cfg)
        kind.update(mixer="gdn", conv_kernel=cfg["linear_conv_kernel_dim"],
                    gdn_heads=(hk, hv), gdn_dims=(dk, dv))
    else:
        kind.update(mixer="attention", rope_base=float(cfg["rope_theta"]),
                    rope_dims=rotary_dims(cfg), qk_norm=True, attn_gate=True)
    return kind


def symbol(cfg, for_training):
    """The program's own symbol at this configuration's sizes and kinds."""
    from mxnet_tpu import models

    experts = {"num_experts": cfg["num_experts"],
               "experts_held": counts.held(cfg), "first_expert": 0,
               "top_k": cfg["num_experts_per_tok"],
               "norm_topk": bool(cfg["norm_topk_prob"]), "act_type": "silu"}
    return models.get_symbol(
        "transformer-lm", num_classes=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        model_dim=cfg["hidden_size"], ffn_dim=cfg["moe_intermediate_size"],
        num_kv_heads=cfg["num_key_value_heads"],
        layers=[layer_kind(cfg, m) for m in counts.layers(cfg)],
        experts=experts, final_norm="rms", head_bias=False,
        norm_eps=float(cfg["rms_norm_eps"]), scalar_loss=for_training)


def loss_from_outputs(outputs, labels):
    """The scalar-loss head already gives the mean."""
    return float(np.asarray(outputs[0], np.float32).reshape(-1)[0])


# --- the plain reference -----------------------------------------------------

def _mm(x, w, low):
    """x (.., in) @ w (out, in)^T in float32 at ``highest``; ``low`` rounds
    both operands to fp8 first."""
    if low:
        x, w = _q8(x), _q8(w)
    return jnp.einsum("...i,oi->...o", x, w, precision=HP)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _unit(x):
    """Each row over its own length: x / sqrt(sum x^2 + 1e-6)."""
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + UNIT_EPS)


def causal_conv(x, w):
    """x (T, channels), w (channels, taps): y_t = sum_j w[:, j]
    x_(t - taps + 1 + j), zeros before the start."""
    taps = w.shape[1]
    xp = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return sum(w[:, j] * xp[j:j + x.shape[0]] for j in range(taps))


def delta_rule(q, k, v, g, beta, low=False):
    """The recurrence token by token: q, k (T, Hv, dk) (a value head's own
    key head already repeated), v (T, Hv, dv), g and beta (T, Hv) ->
    o (T, Hv, dv). The state (Hv, dk, dv) float32 from zero; the scan
    recomputed in the backward pass ``SCAN_BLOCK`` tokens at a time (the
    states of every token would not fit)."""
    t, hv, dk = q.shape
    if low:
        q, k, v = _q8(q), _q8(k), _q8(v)

    def token(s, xs):
        qt, kt, vt, gt, bt = xs
        s = jnp.exp(gt)[:, None, None] * s
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", s, kt,
                                            precision=HP))
        s = s + kt[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt, precision=HP)

    @jax.checkpoint
    def block(s, xs):
        return jax.lax.scan(token, s, xs)

    pad = -t % SCAN_BLOCK   # tokens that write nothing (beta 0), at the end
    xs = tuple(jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
               for a in (q, k, v, g, beta))
    xs = tuple(a.reshape((-1, SCAN_BLOCK) + a.shape[1:]) for a in xs)
    s0 = jnp.zeros((hv, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(block, s0, xs)
    return o.reshape((-1,) + o.shape[2:])[:t]


def gdn(y, lp, cfg, low=False):
    """y (T, D) normed -> (T, D): a Gated DeltaNet mixer."""
    hk, hv, dk, dv = counts.gdn_sizes(cfg)
    t, wq = y.shape[0], hk * dk
    ba = _mm(y, lp["gdn_ba_weight"], low)
    x = jax.nn.silu(causal_conv(_mm(y, lp["gdn_qkv_weight"], low),
                                lp["gdn_core_conv_weight"]))
    grp = hv // hk
    q = jnp.repeat(_unit(x[:, :wq].reshape(t, hk, dk)), grp, 1) / np.sqrt(dk)
    k = jnp.repeat(_unit(x[:, wq:2 * wq].reshape(t, hk, dk)), grp, 1)
    v = x[:, 2 * wq:].reshape(t, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(lp["gdn_core_A_log"]) * jax.nn.softplus(
        ba[:, hv:] + lp["gdn_core_dt_bias"])
    o = delta_rule(q, k, v, g, beta, low)
    o = _rms(o, lp["gdn_norm_gamma"], cfg["rms_norm_eps"]).reshape(t, -1)
    return _mm(o * jax.nn.silu(_mm(y, lp["gdn_z_weight"], low)),
               lp["gdn_out_weight"], low)


def _rope(x, base):
    """(.., T, r), positions 0..T-1, halves rotated against each other."""
    half = x.shape[-1] // 2
    freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, low):
    """One head: q, k, v (T, dh); causal softmax of q k / sqrt(dh)."""
    t, dh = k.shape
    if low:
        q, k, v = _q8(q), _q8(k), _q8(v)
    s = jnp.einsum("qd,kd->qk", q, k, precision=HP) / np.sqrt(dh)
    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    if low:
        p = _q8(p)
    return jnp.einsum("qk,kd->qd", p, v, precision=HP)


def gated_attention(y, lp, cfg, low=False):
    """y (T, D) normed -> (T, D): the gated attention mixer, one head at a
    time, each recomputed in the backward pass."""
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    t, r, eps = y.shape[0], rotary_dims(cfg), cfg["rms_norm_eps"]
    base = float(cfg["rope_theta"])

    def heads(w, n, norm=None):
        x = _mm(y, w, low).reshape(t, n, dh).transpose(1, 0, 2)
        if norm is not None:
            x = _rms(x, norm, eps)
            x = jnp.concatenate([x[..., :dh - r], _rope(x[..., dh - r:],
                                                        base)], -1)
        return x

    q = heads(lp["q_weight"], h, lp["attn_q_norm_gamma"])
    k = jnp.repeat(heads(lp["k_weight"], hkv, lp["attn_k_norm_gamma"]),
                   h // hkv, 0)
    v = jnp.repeat(heads(lp["v_weight"], hkv), h // hkv, 0)
    head = jax.checkpoint(lambda a: _attention(a[0], a[1], a[2], low))
    att = jax.lax.map(head, (q, k, v)).transpose(1, 0, 2).reshape(t, -1)
    gate = jax.nn.sigmoid(_mm(y, lp["q_gate_weight"], low))
    return _mm(att * gate, lp["o_weight"], low)


def route(x, router_weight, cfg):
    """(weights (T, k), experts chosen (T, k)): the softmax over all the
    experts, the top k renormalised; float32 whatever the control's
    precision (the configuration states a float32 router)."""
    p = jax.nn.softmax(jnp.einsum("ti,ei->te", x, router_weight,
                                  precision=HP), axis=-1)
    w, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return w, idx


def routed(x, lp, cfg, low, first=0):
    """x (T, D): the sum over a token's chosen experts AMONG THOSE HELD
    (``first`` onward) of p * down(silu(gate x) * up x)."""
    w, idx = route(x, lp["router_weight"], cfg)

    def one(y, e):
        wg, wu, wd, number = e
        we = jnp.sum(jnp.where(idx == number, w, 0.0), axis=-1)
        h = jax.nn.silu(_mm(x, wg, low)) * _mm(x, wu, low)
        return y + we[:, None] * _mm(h, wd, low), None

    held = lp["gate_weight"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        lp["gate_weight"], lp["up_weight"], lp["down_weight"],
        first + jnp.arange(held)))
    return y


def shared(x, lp, low=False):
    """The shared expert scaled by its sigmoid gate."""
    out = _mm(jax.nn.silu(_mm(x, lp["shared_ffn1_weight"], low))
              * _mm(x, lp["shared_ffn3_weight"], low),
              lp["shared_ffn2_weight"], low)
    return jax.nn.sigmoid(_mm(x, lp["shared_gate_weight"], low)) * out


def expert_layer(x, lp, cfg, low=False, first=0):
    """The held experts' part and the gated shared expert."""
    return routed(x, lp, cfg, low, first) + shared(x, lp, low)


def _block(x, lp, cfg, mixer, low):
    """x (T, D) through one block; ``lp`` the block's leaves by short
    name."""
    eps = cfg["rms_norm_eps"]
    y = _rms(x, lp["ln1_gamma"], eps)
    x = x + (gdn(y, lp, cfg, low) if mixer == "gdn"
             else gated_attention(y, lp, cfg, low))
    return x + expert_layer(_rms(x, lp["ln2_gamma"], eps), lp, cfg, low)


def _leaves(params, prefix):
    return {n[len(prefix):]: a for n, a in params.items()
            if n.startswith(prefix)}


def ref_state(params, tokens, cfg, low=False):
    """tokens (T,) int -> the stack's normed output (T, D) float32. Each
    block is recomputed in the backward pass, so that a sequence's float32
    activations fit."""
    x = params["embed_weight"][tokens]
    for i, mixer in enumerate(counts.layers(cfg)):
        block = jax.checkpoint(functools.partial(_block, cfg=cfg,
                                                 mixer=mixer, low=low))
        x = block(x, _leaves(params, "layer%d_" % i))
    return _rms(x, params["lnf_gamma"], cfg["rms_norm_eps"])


def _nll(h, head, labels, low):
    """The NLL of every row of h (T, D) against labels (T,); recomputed in
    the backward pass, so that one (T, V) float32 logits array lives at a
    time."""
    logp = jax.nn.log_softmax(_mm(h, head, low), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]


def ref_seq_loss(params, tokens, labels, cfg, low=False):
    """One sequence's next-token NLL, summed over its positions."""
    nll = jax.checkpoint(functools.partial(_nll, low=low))
    return jnp.sum(nll(ref_state(params, tokens, cfg, low),
                       params["pred_weight"], labels))


def make_ref_step(cfg, traffic, low=False):
    """The reference's training step (SGD with momentum, no weight decay),
    one sequence at a time: ``decay(mom)`` gives ``momentum*mom``,
    ``fold(params, mom, tokens, labels) -> (loss_sum, mom)`` folds
    ``-lr*(g_seq/n)`` in, and ``apply(params, mom) -> params``."""
    opt = traffic["optimizer"]
    lr = opt["learning_rate"]
    n_tok = traffic["batch"] * traffic["seq_len"]

    @functools.partial(jax.jit, donate_argnums=(1,))
    def fold(params, mom, tokens, labels):
        loss, g = jax.value_and_grad(ref_seq_loss)(params, tokens, labels,
                                                   cfg, low)
        mom = {n: mom[n] - lr * g[n] / n_tok for n in mom}
        return loss, mom

    @functools.partial(jax.jit, donate_argnums=(0,))
    def decay(mom):
        return {n: opt["momentum"] * mom[n] for n in mom}

    @functools.partial(jax.jit, donate_argnums=(0,))
    def apply(params, mom):
        return {n: params[n] + mom[n] for n in params}

    return fold, decay, apply


def ref_train(cfg, traffic, seed, steps, low=False):
    """Drive the reference from the seed through ``steps`` steps on the same
    batches the program saw. Returns the loss of each step, ``|m1|/lr`` per
    leaf after the first step (the gradient as the optimizer got it) and
    ``|p_steps - p_0|`` per leaf."""
    params = init_params(cfg, seed)
    mom = jax.tree_util.tree_map(jnp.zeros_like, params)
    batches = make_batches(cfg, traffic, seed, steps)
    fold, decay, apply = make_ref_step(cfg, traffic, low)
    lr = traffic["optimizer"]["learning_rate"]
    n_tok = traffic["batch"] * traffic["seq_len"]
    losses, grad_norm = [], None
    for data, label in batches:
        mom = decay(mom)
        total = 0.0
        for row in range(traffic["batch"]):
            loss, mom = fold(params, mom, data["data"][row],
                             label["softmax_label"][row])
            total += float(loss)
        losses.append(total / n_tok)
        if grad_norm is None:
            grad_norm = {n: float(jnp.linalg.norm(a)) / lr
                         for n, a in mom.items()}
            grad_vec = refmath.kept_vectors(mom, 1.0 / lr)
        params = apply(params, mom)
    return {"loss": losses, "grad_norm": grad_norm, "grad_vec": grad_vec,
            **refmath.leaf_changes(
                params, lambda n: init_leaf(cfg, seed, n))}
