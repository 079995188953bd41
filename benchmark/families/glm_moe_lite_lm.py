"""Family ``glm_moe_lite_lm``: zai-org/GLM-4.7-Flash (``model_type``
``glm4_moe_lite``) as one chip of an eight-way expert-parallel layer trains
it. Every norm is an RMSNorm with a plain scale at ``rms_norm_eps``
(``N``); no bias anywhere.

Latent attention (MLA), for a token's normed input ``x`` (``hidden_size``),
20 heads ``h`` of 256 = ``qk_nope_head_dim`` 192 + ``qk_rope_head_dim`` 64::

    c_q = N_q(x W_qa)                                   q_lora_rank 768
    [q_nope_h, q_pe_h] = (c_q W_qb)_h                   192 + 64
    [c_kv, k_pe] = x W_kva                              kv_lora_rank 512 + 64
    [k_nope_h, v_h] = (N_kv(c_kv) W_kvb)_h              192 + v_head_dim 256
    q_h = [q_nope_h, rope(q_pe_h)]   k_h = [k_nope_h, rope(k_pe)]
    o_h = causal softmax(q_h . k_h / sqrt(256)) v_h     out = [o_1..o_20] W_o

one ``k_pe`` for every head; RoPE at base ``rope_theta`` over the 64 rotary
dims, the halves rotated against each other. The feed-forward is a dense
SwiGLU of ``intermediate_size`` in the first ``first_k_dense_replace``
layers and an expert layer after them: ``s = sigmoid(x W_r^T)`` over all 64
experts in float32, the 4 chosen the largest of ``s + expert_bias``, weighted
by ``s`` alone over the chosen scores' sum + 1e-20 times
``routed_scaling_factor``; ``y = sum over the chosen AND HELD of w_e
down_e(silu(gate_e x) * up_e x) + down_s(silu(gate_s x) * up_s x)``, the
shared expert unweighted; the router reads what the experts read. A block
is ``x + MLA(N x)``, then ``+ FFN(N .)``.

The multi-token-prediction module (DeepSeek-V3's): ``h_i`` the stack's output
at i before the final norm, ``t_{i+1}`` the label at i::

    h'_i = W_eh [N_e(E[t_{i+1}]); N_h(h_i)]            2 x 2048 -> 2048
    g    = one expert layer of its own over h'          causal, whole sequence
    loss = CE(W_head N_f(h), t_{i+1})  +  0.3 CE(W_head N_m(g_i), t_{i+2})

each cross-entropy a mean over its own positions (the module's over
0..T-2); ``E`` and ``W_head`` are the model's. The configuration file lists
under ``assumed`` what the config does not give.

The chip's share (``configs/glm-4.7-flash.train.json``): experts
0..held-1 of every expert layer, rows 0..vocab_size-1 of the vocabulary, the
layers ``layers_run`` names and the module. What experts held..63 would add
is left out, here and in the program alike.

Two halves that share nothing but the seed:

- the program's side: the symbol (``models.get_symbol("transformer-lm",
  ..., mtp=)`` with its per-layer kinds), the seeded parameters and token
  batches, made on the device in one jitted call, and the seeded
  ``expert_bias``, written into the executor last bound from the symbol as
  ``lfm2_moe_lm`` does (no driver hook for auxiliary state: PERF.md,
  section 7);
- the plain reference: forward, both losses, gradients and
  SGD-with-momentum in straightforward ``jax.numpy``, float32 at ``highest``
  matmul precision, no kernel, one sequence and one head at a time, the
  expert layer a masked dense sum over the held experts. It imports nothing
  of the program.

Departures, each marked ``# departure`` below: weights and bias are random
from the seed; the bias is never updated.
"""
import functools
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from lib import counts_glm as counts
from lib import refmath
from lib.refmath import seed_key, q8 as _q8

HP = jax.lax.Precision.HIGHEST
ROUTER_EPS = 1e-20  # assumed: the configuration file says so


# --- sizes -------------------------------------------------------------------

def _layer_shapes(cfg, p, ffn):
    """One block's leaves, named as the package's symbol names them."""
    d, h, dh = cfg["hidden_size"], cfg["num_attention_heads"], \
        counts.head_dim(cfg)
    qr, kvr, r = cfg["q_lora_rank"], cfg["kv_lora_rank"], \
        cfg["qk_rope_head_dim"]
    kv = h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
    shapes = {p + "ln1_gamma": (d,), p + "mla_q_a_weight": (qr, d),
              p + "mla_q_norm_gamma": (qr,),
              p + "mla_q_b_weight": (h * dh, qr),
              p + "mla_kv_a_weight": (kvr + r, d),
              p + "mla_kv_norm_gamma": (kvr,),
              p + "mla_kv_b_weight": (kv, kvr),
              p + "o_weight": (d, h * cfg["v_head_dim"]),
              p + "ln2_gamma": (d,)}
    if ffn == "dense":
        f = cfg["intermediate_size"]
        shapes.update({p + "ffn1_weight": (f, d), p + "ffn3_weight": (f, d),
                       p + "ffn2_weight": (d, f)})
        return shapes
    f, held = cfg["moe_intermediate_size"], counts.held(cfg)
    fs = cfg["n_shared_experts"] * f
    shapes.update({p + "router_weight": (cfg["n_routed_experts"], d),
                   p + "gate_weight": (held, f, d),
                   p + "up_weight": (held, f, d),
                   p + "down_weight": (held, d, f),
                   p + "shared_ffn1_weight": (fs, d),
                   p + "shared_ffn3_weight": (fs, d),
                   p + "shared_ffn2_weight": (d, fs)})
    return shapes


def _mtp_layers(cfg):
    if counts.mtp_modules(cfg) != 1:
        raise ValueError("glm_moe_lite_lm: one multi-token-prediction module "
                         "(num_nextn_predict_layers %d)"
                         % counts.mtp_modules(cfg))
    return ["mtp_layer_"]


def param_shapes(cfg):
    """name -> shape, named and ordered as the package's symbol lists them."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    shapes = {"embed_weight": (v, d)}
    for i, ffn in enumerate(counts.layers(cfg)):
        shapes.update(_layer_shapes(cfg, "layer%d_" % i, ffn))
    shapes.update({"lnf_gamma": (d,), "pred_weight": (v, d)})
    for p in _mtp_layers(cfg):
        shapes.update({"mtp_enorm_gamma": (d,), "mtp_hnorm_gamma": (d,),
                       "mtp_eh_proj_weight": (d, 2 * d)})
        shapes.update(_layer_shapes(cfg, p, "experts"))
        shapes["mtp_lnf_gamma"] = (d,)
    return shapes


def state_shapes(cfg):
    """The program's auxiliary states, by the names its symbol gives them:
    one ``expert_bias`` an expert layer, the module's among them."""
    names = ["layer%d_" % i for i, f in enumerate(counts.layers(cfg))
             if f == "experts"] + _mtp_layers(cfg)
    return {p + "experts_expert_bias": (cfg["n_routed_experts"],)
            for p in names}


def step_flops(cfg, traffic):
    return counts.train_step_flops(cfg, traffic["batch"], traffic["seq_len"])


def _scales(cfg):
    """(matrices, bias): normal at ``initializer_range`` and at
    ``expert_bias_scale``, both assumed (the configuration file says
    why)."""
    return (float(cfg.get("initializer_range", 0.02)),
            float(cfg.get("expert_bias_scale", 0.02)))


_KINDS = ("_gamma", "_expert_bias")  # else: a matrix


def _init_leaf(key, name, shape, scales):
    std, bias = scales
    if name.endswith("_gamma"):
        return jnp.ones(shape, jnp.float32)
    if name.endswith("_expert_bias"):
        return bias * jax.random.normal(key, shape, jnp.float32)  # departure
    return std * jax.random.normal(key, shape, jnp.float32)  # departure


def _all_shapes(cfg):
    """Parameters, then states: a leaf's place here keys its draw."""
    return {**param_shapes(cfg), **state_shapes(cfg)}


def _draw(cfg, seed, names):
    """The leaves ``names`` from the seed in one jitted call, on the default
    device, float32."""
    shapes = _all_shapes(cfg)
    scales = _scales(cfg)
    places = {n: i for i, n in enumerate(shapes)}

    @jax.jit
    def make(key0):
        key = jax.random.fold_in(key0, 1)
        return {n: _init_leaf(jax.random.fold_in(key, places[n]), n,
                              shapes[n], scales) for n in names}

    return make(seed_key(seed))


def ref_params(cfg, seed):
    """The parameters alone: what the reference starts from."""
    return _draw(cfg, seed, list(param_shapes(cfg)))


def init_state(cfg, seed):
    """The seeded ``expert_bias`` of every expert layer, float32."""
    return _draw(cfg, seed, list(state_shapes(cfg)))


def init_leaf(cfg, seed, name):
    """One leaf again, float32 (the same bits ``init_params`` gave)."""
    shapes = _all_shapes(cfg)
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

_bound = []  # a weak reference to the executor last bound from `symbol()`


def layer_kind(cfg, ffn):
    """The ``LAYER_KINDS`` dict of a layer whose feed-forward is ``ffn``."""
    kind = {"norm": "rms", "mixer": "latent", "q_rank": cfg["q_lora_rank"],
            "kv_rank": cfg["kv_lora_rank"],
            "rope_dims": cfg["qk_rope_head_dim"],
            "rope_base": float(cfg["rope_theta"])}
    if ffn == "dense":
        kind.update(ffn="swiglu", ffn_dim=cfg["intermediate_size"])
    else:
        kind.update(ffn="experts", router_input="ffn",
                    shared_dim=cfg["n_shared_experts"]
                    * cfg["moe_intermediate_size"])
    return kind


def symbol(cfg, for_training):
    """The program's own symbol at this configuration's sizes and kinds. It
    remembers the executor bound from it, for ``init_params`` to seed the
    auxiliary states of."""
    from mxnet_tpu import models
    from mxnet_tpu.symbol import Symbol

    class _Seeded(Symbol):
        def simple_bind(self, *args, **kwargs):
            exe = super().simple_bind(*args, **kwargs)
            _bound[:] = [weakref.ref(exe)]
            return exe

    if cfg["v_head_dim"] != counts.head_dim(cfg):
        raise ValueError("glm_moe_lite_lm: a value head of %d beside q and k "
                         "heads of %d" % (cfg["v_head_dim"],
                                          counts.head_dim(cfg)))
    experts = {"num_experts": cfg["n_routed_experts"],
               "experts_held": counts.held(cfg), "first_expert": 0,
               "top_k": cfg["num_experts_per_tok"],
               "norm_topk": bool(cfg["norm_topk_prob"]), "act_type": "silu",
               "route": "sigmoid_bias", "norm_eps": ROUTER_EPS,
               "scale": float(cfg["routed_scaling_factor"])}
    sym = models.get_symbol(
        "transformer-lm", num_classes=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], head_dim=counts.head_dim(cfg),
        model_dim=cfg["hidden_size"], ffn_dim=cfg["moe_intermediate_size"],
        num_kv_heads=cfg["num_key_value_heads"],
        layers=[layer_kind(cfg, f) for f in counts.layers(cfg)],
        experts=experts, final_norm="rms", head_bias=False,
        norm_eps=float(cfg["rms_norm_eps"]), scalar_loss=for_training,
        mtp={"layer": layer_kind(cfg, "experts"),
             "weight": float(cfg["mtp_loss_weight"])} if for_training
        else None)
    return _Seeded(sym._entries)


def init_params(cfg, seed):
    """Every parameter from the seed, float32 (the trainer's master
    weights): matrices normal at ``initializer_range``, RMSNorm scales at
    1. The executor last bound from ``symbol()`` gets its ``expert_bias``
    states from the same seed."""
    exe = _bound[0]() if _bound else None
    if exe is None:
        raise RuntimeError(
            "glm_moe_lite_lm.init_params: no live executor bound from "
            "symbol() to seed the expert_bias of (ref_params gives the "
            "parameters alone)")
    state = init_state(cfg, seed)
    if set(state) != set(exe.aux_dict):
        raise RuntimeError(
            "glm_moe_lite_lm.init_params: the executor's auxiliary states %s "
            "are not the family's %s" % (sorted(exe.aux_dict), sorted(state)))
    exe.copy_params_from({}, aux_params=state)
    return ref_params(cfg, seed)


def loss_from_outputs(outputs, labels):
    """The scalar-loss head already gives both means, weighed."""
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


def _rope(x, base):
    """(.., T, r), positions 0..T-1, halves rotated against each other."""
    half = x.shape[-1] // 2
    freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, low):
    """One head: q, k (T, 256), v (T, v_head_dim); causal softmax of
    q k / sqrt(256)."""
    t, dh = k.shape
    if low:
        q, k, v = _q8(q), _q8(k), _q8(v)
    s = jnp.einsum("qd,kd->qk", q, k, precision=HP) / np.sqrt(dh)
    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    if low:
        p = _q8(p)
    return jnp.einsum("qk,kd->qd", p, v, precision=HP)


def mla_heads(y, lp, cfg, low=False):
    """y (T, D) -> the heads' q, k, v, each (H, T, .): the latents, their
    norms, the up-projections, the rotary parts rotated, the one k_pe
    shared by every head."""
    h, nope, r = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                  cfg["qk_rope_head_dim"])
    kvr, t = cfg["kv_lora_rank"], y.shape[0]
    eps, base = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    c_q = _rms(_mm(y, lp["mla_q_a_weight"], low), lp["mla_q_norm_gamma"], eps)
    q = _mm(c_q, lp["mla_q_b_weight"], low).reshape(t, h, -1).transpose(
        1, 0, 2)
    latent = _mm(y, lp["mla_kv_a_weight"], low)
    c_kv, k_pe = latent[:, :kvr], latent[:, kvr:]
    kv = _mm(_rms(c_kv, lp["mla_kv_norm_gamma"], eps), lp["mla_kv_b_weight"],
             low).reshape(t, h, -1).transpose(1, 0, 2)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], base)], -1)
    k_pe = jnp.broadcast_to(_rope(k_pe, base), (h, t, r))
    k = jnp.concatenate([kv[..., :nope], k_pe], -1)
    return q, k, kv[..., nope:]


def _mla(y, lp, cfg, low):
    """y (T, D) -> (T, D): latent attention, one head at a time, each
    recomputed in the backward pass (the (T, T) float32 scores are the large
    thing)."""
    q, k, v = mla_heads(y, lp, cfg, low)
    head = jax.checkpoint(lambda a: _attention(a[0], a[1], a[2], low))
    att = jax.lax.map(head, (q, k, v))
    att = att.transpose(1, 0, 2).reshape(y.shape[0], -1)
    return _mm(att, lp["o_weight"], low)


def _swiglu(x, lp, low, part=""):
    return _mm(jax.nn.silu(_mm(x, lp[part + "ffn1_weight"], low))
               * _mm(x, lp[part + "ffn3_weight"], low),
               lp[part + "ffn2_weight"], low)


def route(x, router_weight, bias, cfg):
    """(weights (T, k), experts chosen (T, k)): float32 whatever the
    control's precision (the configuration states a float32 router)."""
    s = jax.nn.sigmoid(jnp.einsum("ti,ei->te", x, router_weight,
                                  precision=HP))
    _, idx = jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, 1)      # the score alone weighs
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + ROUTER_EPS)
    return w * cfg["routed_scaling_factor"], idx


def routed(x, lp, bias, cfg, low, first=0):
    """x (T, D): the sum over a token's chosen experts AMONG THOSE HELD
    (``first`` onward) of w * down(silu(gate x) * up x)."""
    w, idx = route(x, lp["router_weight"], bias, cfg)

    def one(y, e):
        wg, wu, wd, number = e
        # this expert's weight a token: w where the token chose it, else 0
        we = jnp.sum(jnp.where(idx == number, w, 0.0), axis=-1)
        h = jax.nn.silu(_mm(x, wg, low)) * _mm(x, wu, low)
        return y + we[:, None] * _mm(h, wd, low), None

    held = lp["gate_weight"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        lp["gate_weight"], lp["up_weight"], lp["down_weight"],
        first + jnp.arange(held)))
    return y


def expert_layer(x, lp, bias, cfg, low=False, first=0):
    """The held experts' part and the shared expert, unweighted."""
    return routed(x, lp, bias, cfg, low, first) + _swiglu(x, lp, low,
                                                           "shared_")


def _block(x, lp, bias, cfg, ffn, low):
    """x (T, D) through one block; ``lp`` the block's leaves by short name,
    ``bias`` its ``expert_bias`` (None in a dense layer)."""
    eps = cfg["rms_norm_eps"]
    x = x + _mla(_rms(x, lp["ln1_gamma"], eps), lp, cfg, low)
    y = _rms(x, lp["ln2_gamma"], eps)
    if ffn == "dense":
        return x + _swiglu(y, lp, low)
    return x + expert_layer(y, lp, bias, cfg, low)


def _leaves(params, prefix):
    return {n[len(prefix):]: a for n, a in params.items()
            if n.startswith(prefix)}


def ref_states(params, state, tokens, labels, cfg, low=False):
    """One sequence: tokens and labels (T,) int -> (the main stack's normed
    output, the module's), each (T, D) float32. Each block is recomputed in
    the backward pass, so that a sequence's float32 activations fit."""
    eps = cfg["rms_norm_eps"]
    x = params["embed_weight"][tokens]
    for i, ffn in enumerate(counts.layers(cfg)):
        block = jax.checkpoint(functools.partial(_block, cfg=cfg, ffn=ffn,
                                                 low=low))
        x = block(x, _leaves(params, "layer%d_" % i),
                  state.get("layer%d_experts_expert_bias" % i))
    e = _rms(params["embed_weight"][labels], params["mtp_enorm_gamma"], eps)
    joined = jnp.concatenate([e, _rms(x, params["mtp_hnorm_gamma"], eps)],
                             -1)
    g = _mm(joined, params["mtp_eh_proj_weight"], low)
    block = jax.checkpoint(functools.partial(_block, cfg=cfg, ffn="experts",
                                             low=low))
    g = block(g, _leaves(params, "mtp_layer_"),
              state["mtp_layer_experts_expert_bias"])
    return (_rms(x, params["lnf_gamma"], eps),
            _rms(g, params["mtp_lnf_gamma"], eps))


def _nll(h, head, labels, low):
    """The NLL of every row of h (T, D) against labels (T,) through the
    head; recomputed in the backward pass, so that one (T, V) float32 logits
    array lives at a time."""
    logp = jax.nn.log_softmax(_mm(h, head, low), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]


def ref_seq_loss(params, state, tokens, labels, cfg, low=False):
    """One sequence's share of the step's loss times its T positions: the
    next-token NLL summed, plus the module's weight times its NLL summed
    over positions 0..T-2 against the label one later, scaled by T / (T-1)
    (so that, over the batch's B x T, each term is a mean over its own
    positions)."""
    main, module = ref_states(params, state, tokens, labels, cfg, low)
    nll = jax.checkpoint(functools.partial(_nll, low=low))
    t = tokens.shape[0]
    head = params["pred_weight"]
    ce_mtp = jnp.sum(nll(module[:t - 1], head, labels[1:]))
    return (jnp.sum(nll(main, head, labels))
            + cfg["mtp_loss_weight"] * ce_mtp * t / (t - 1))


def make_ref_step(cfg, traffic, low=False):
    """The reference's training step (SGD with momentum, no weight decay),
    one sequence at a time so that the float32 scores fit: ``decay(mom)``
    gives ``momentum*mom``, ``fold(params, mom, state, tokens, labels) ->
    (loss_sum, mom)`` folds ``-lr*(g_seq/n)`` in, and ``apply(params, mom)
    -> params``. The state (``expert_bias``) is read and never written."""
    opt = traffic["optimizer"]
    lr = opt["learning_rate"]
    n_tok = traffic["batch"] * traffic["seq_len"]

    @functools.partial(jax.jit, donate_argnums=(1,))
    def fold(params, mom, state, tokens, labels):
        loss, g = jax.value_and_grad(ref_seq_loss)(params, state, tokens,
                                                   labels, cfg, low)
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
    params, state = ref_params(cfg, seed), init_state(cfg, seed)
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
            loss, mom = fold(params, mom, state, data["data"][row],
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
