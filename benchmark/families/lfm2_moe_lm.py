"""Family ``lfm2_moe_lm``: LiquidAI/LFM2-24B-A2B (``model_type`` ``lfm2_moe``)
as one chip of an eight-way expert-parallel layer trains it. Pre-RMSNorm
blocks (``norm_eps``, a plain scale, no bias anywhere): ``h = h +
mixer(norm1 h)``, ``h = h + ffn(norm2 h)``. The mixer is a gated short
convolution (``(B, C, X) = split3(W_in u)``, ``a = B * X``, a depthwise
causal convolution of ``conv_L_cache`` taps over ``a``, ``W_out (C * c)``)
or grouped-query attention (32 query heads over 8 KV heads of 64, RMSNorm
over the 64 of every q and k head before RoPE at base 1e6, halves rotated
against each other). The feed-forward is a dense SwiGLU in the leading dense
layer and 64 SwiGLU experts after it, 4 a token: ``s = sigmoid(W_r x)`` in
float32, the experts chosen the 4 largest of ``s + expert_bias``, weighted
by ``s`` alone over the chosen scores' sum + 1e-6, times
``routed_scaling_factor``; the router reads what the experts read. A final
RMSNorm and a head tied to the table.

The chip's share (``configs/lfm2-24b-a2b.train.json``): experts 0..held-1 of
every expert layer, rows 0..vocab_size-1 of the vocabulary, the layers
``layers_run`` names. The router keeps its 64 outputs and its 4 a token, the
weights stay normalised over all 4 chosen, and what experts held..63 would
add is left out, here and in the program alike.

Two halves that share nothing but the seed:

- the program's side: the symbol (``models.get_symbol("transformer-lm",
  ...)`` with its per-layer kinds), the seeded parameters and token batches,
  made on the device in one jitted call, and the seeded ``expert_bias``;
- the plain reference: forward, loss, gradients and SGD-with-momentum in
  straightforward ``jax.numpy``, float32 at ``highest`` matmul precision, no
  kernel, one sequence and one KV group at a time, the expert layer a masked
  dense sum over the held experts. It imports nothing of the program.

``expert_bias`` is auxiliary state of the program (no gradient, not among
the parameters), and the ``train_steps`` driver hands a family the seed for
parameters and batches only. So the symbol this file hands out remembers the
executor bound from it, and ``init_params``, which the driver calls next,
writes the seeded bias into that executor's auxiliary states
(``copy_params_from``), and raises where there is no such executor or its
states are not the family's (``_Seeded``; a hook of the driver's for
auxiliary state is a ``benchmark`` issue's: PERF.md, section 7).

Departures from the published description, each marked ``# departure`` below:
weights, taps and bias are random from the seed; the bias is never updated.
"""
import functools
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from lib import counts_lfm2 as counts
from lib import refmath
from lib.refmath import seed_key, q8 as _q8

HP = jax.lax.Precision.HIGHEST


# --- sizes -------------------------------------------------------------------

def param_shapes(cfg):
    """name -> shape, named and ordered as the package's symbol lists them."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    dh = counts.head_dim(cfg)
    dq, dkv = dh * cfg["num_attention_heads"], dh * cfg["num_key_value_heads"]
    e, held = cfg["num_experts"], counts.held(cfg)
    fd, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    shapes = {"embed_weight": (v, d)}
    for i, (mixer, ffn) in enumerate(counts.layers(cfg)):
        p = "layer%d_" % i
        shapes[p + "ln1_gamma"] = (d,)
        if mixer == "conv":
            shapes.update({p + "conv_in_weight": (3 * d, d),
                           p + "conv_weight": (d, cfg["conv_L_cache"]),
                           p + "conv_out_weight": (d, d)})
        else:
            shapes.update({p + "q_weight": (dq, d), p + "k_weight": (dkv, d),
                           p + "v_weight": (dkv, d),
                           p + "attn_q_norm_gamma": (dh,),
                           p + "attn_k_norm_gamma": (dh,),
                           p + "o_weight": (d, dq)})
        shapes[p + "ln2_gamma"] = (d,)
        if ffn == "dense":
            shapes.update({p + "ffn1_weight": (fd, d),
                           p + "ffn3_weight": (fd, d),
                           p + "ffn2_weight": (d, fd)})
        else:
            shapes.update({p + "router_weight": (e, d),
                           p + "gate_weight": (held, fe, d),
                           p + "up_weight": (held, fe, d),
                           p + "down_weight": (held, d, fe)})
    shapes["lnf_gamma"] = (d,)
    return shapes


def state_shapes(cfg):
    """The program's auxiliary states, by the names its symbol gives them:
    one ``expert_bias`` an expert layer."""
    return {"layer%d_experts_expert_bias" % i: (cfg["num_experts"],)
            for i, (_, ffn) in enumerate(counts.layers(cfg))
            if ffn == "experts" and cfg["use_expert_bias"]}


def step_flops(cfg, traffic):
    return counts.train_step_flops(cfg, traffic["batch"], traffic["seq_len"])


def _scales(cfg):
    """(matrices, taps, bias): normal at ``initializer_range`` (assumed
    0.02); the taps at ``conv_init_scale`` and the bias at
    ``expert_bias_scale``, both assumed (the configuration file says
    why)."""
    return (float(cfg.get("initializer_range", 0.02)),
            float(cfg.get("conv_init_scale", 0.5)),
            float(cfg.get("expert_bias_scale", 0.02)))


_KINDS = ("_gamma", "_expert_bias", "_conv_weight")  # else: a matrix


def _init_leaf(key, name, shape, scales):
    std, taps, bias = scales
    if name.endswith("_gamma"):
        return jnp.ones(shape, jnp.float32)
    if name.endswith("_expert_bias"):
        return bias * jax.random.normal(key, shape, jnp.float32)  # departure
    if name.endswith("_conv_weight"):
        return taps * jax.random.normal(key, shape, jnp.float32)  # departure
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
    """``n`` batches of token ids over the vocabulary rows held, rows all
    different, and their next-token labels; int32 on the device."""
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


def layer_kinds(cfg):
    """One ``LAYER_KINDS`` dict a layer that is run."""
    base = float(cfg["rope_parameters"]["rope_theta"])
    kinds = []
    for mixer, ffn in counts.layers(cfg):
        kind = {"norm": "rms"}
        if mixer == "conv":
            kind.update(mixer="short_conv", conv_kernel=cfg["conv_L_cache"])
        else:
            kind.update(mixer="attention", qk_norm=True, rope_base=base)
        if ffn == "dense":
            kind.update(ffn="swiglu", ffn_dim=cfg["intermediate_size"])
        else:
            kind.update(ffn="experts", router_input="ffn")
        kinds.append(kind)
    return kinds


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

    experts = {"num_experts": cfg["num_experts"],
               "experts_held": counts.held(cfg), "first_expert": 0,
               "top_k": cfg["num_experts_per_tok"],
               "norm_topk": bool(cfg["norm_topk_prob"]), "act_type": "silu",
               "route": "sigmoid_bias" if cfg["use_expert_bias"]
               else "softmax",
               "norm_eps": 1e-6,  # assumed: the configuration file says so
               "scale": float(cfg["routed_scaling_factor"])}
    sym = models.get_symbol(
        "transformer-lm", num_classes=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], head_dim=counts.head_dim(cfg),
        model_dim=cfg["hidden_size"], ffn_dim=cfg["moe_intermediate_size"],
        num_kv_heads=cfg["num_key_value_heads"], layers=layer_kinds(cfg),
        experts=experts, final_norm="rms", head_bias=False,
        norm_eps=float(cfg["norm_eps"]), tie_head=True,
        scalar_loss=for_training)
    return _Seeded(sym._entries)


def init_params(cfg, seed):
    """Every parameter from the seed, float32 (the trainer's master
    weights): matrices normal at ``initializer_range``, taps at
    ``conv_init_scale``, RMSNorm scales at 1. The executor last bound from
    ``symbol()`` gets its ``expert_bias`` states from the same seed."""
    exe = _bound[0]() if _bound else None
    if exe is None:
        raise RuntimeError(
            "lfm2_moe_lm.init_params: no live executor bound from symbol() "
            "to seed the expert_bias of (ref_params gives the parameters "
            "alone)")
    state = init_state(cfg, seed)
    if set(state) != set(exe.aux_dict):
        raise RuntimeError(
            "lfm2_moe_lm.init_params: the executor's auxiliary states %s are "
            "not the family's %s" % (sorted(exe.aux_dict), sorted(state)))
    exe.copy_params_from({}, aux_params=state)
    return ref_params(cfg, seed)


def loss_from_outputs(outputs, labels):
    """The scalar-loss head already gives the mean NLL."""
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
    """(heads, T, Dh), positions 0..T-1, halves rotated against each other."""
    half = x.shape[-1] // 2
    freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, low):
    """One query head against its kv head: q, k, v (T, Dh); causal
    softmax of q k / sqrt(Dh)."""
    t, dh = k.shape
    if low:
        q, k, v = _q8(q), _q8(k), _q8(v)
    s = jnp.einsum("qd,kd->qk", q, k, precision=HP) / np.sqrt(dh)
    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    if low:
        p = _q8(p)
    return jnp.einsum("qk,kd->qd", p, v, precision=HP)


def _attention_mixer(y, lp, cfg, low):
    """y (T, D) -> (T, D): q, k, v, head norms, RoPE, attention, o."""
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  counts.head_dim(cfg))
    t = y.shape[0]
    eps, base = cfg["norm_eps"], float(cfg["rope_parameters"]["rope_theta"])
    q = _mm(y, lp["q_weight"], low).reshape(t, h, dh).transpose(1, 0, 2)
    k = _mm(y, lp["k_weight"], low).reshape(t, hkv, dh).transpose(1, 0, 2)
    v = _mm(y, lp["v_weight"], low).reshape(t, hkv, dh).transpose(1, 0, 2)
    q = _rope(_rms(q, lp["attn_q_norm_gamma"], eps), base)
    k = _rope(_rms(k, lp["attn_k_norm_gamma"], eps), base)
    q = q.reshape(hkv, h // hkv, t, dh)

    # one kv group at a time and in it one query head at a time, each
    # recomputed in the backward pass: the (T, T) float32 scores are the
    # large thing, and a map would else keep every head's for its backward
    def group(a):
        head = jax.checkpoint(lambda qh: _attention(qh, a[1], a[2], low))
        return jax.lax.map(head, a[0])

    att = jax.lax.map(group, (q, k, v))
    att = att.reshape(h, t, dh).transpose(1, 0, 2).reshape(t, h * dh)
    return _mm(att, lp["o_weight"], low)


def _later(a, n):
    """a (T, d) moved n tokens later, zeros before the sequence's start."""
    return jnp.concatenate([jnp.zeros_like(a[:n]), a[:a.shape[0] - n]], 0)


def _conv_mixer(y, lp, low):
    """y (T, D) -> (T, D): the gated short convolution, tap j of L on the
    token L - 1 - j back."""
    b, c, x = jnp.split(_mm(y, lp["conv_in_weight"], low), 3, axis=-1)
    a = b * x
    taps = lp["conv_weight"]                           # (D, L)
    n = taps.shape[1]
    conv = sum(taps[:, j] * _later(a, n - 1 - j) for j in range(n))
    return _mm(c * conv, lp["conv_out_weight"], low)


def _dense_ffn(x, lp, low):
    return _mm(jax.nn.silu(_mm(x, lp["ffn1_weight"], low))
               * _mm(x, lp["ffn3_weight"], low), lp["ffn2_weight"], low)


def route(x, router_weight, bias, cfg):
    """(weights (T, k), experts chosen (T, k)): float32 whatever the
    control's precision (the configuration states a float32 router)."""
    s = jax.nn.sigmoid(jnp.einsum("ti,ei->te", x, router_weight,
                                  precision=HP))
    _, idx = jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, 1)      # the score alone weighs
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    return w * cfg["routed_scaling_factor"], idx


def _experts(x, lp, bias, cfg, low, first=0):
    """x (T, D): sum over a token's chosen experts AMONG THOSE HELD
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


def _block(x, lp, bias, cfg, mixer, ffn, low):
    """x (T, D) through one block; ``lp`` the block's leaves by short name,
    ``bias`` its ``expert_bias`` (None in a dense layer)."""
    y = _rms(x, lp["ln1_gamma"], cfg["norm_eps"])
    x = x + (_conv_mixer(y, lp, low) if mixer == "conv"
             else _attention_mixer(y, lp, cfg, low))
    y = _rms(x, lp["ln2_gamma"], cfg["norm_eps"])
    if ffn == "dense":
        return x + _dense_ffn(y, lp, low)
    return x + _experts(y, lp, bias, cfg, low)


def _layer(params, i):
    p = "layer%d_" % i
    return {n[len(p):]: a for n, a in params.items() if n.startswith(p)}


def ref_logits(params, state, tokens, cfg, low=False):
    """One sequence: tokens (T,) int -> logits (T, V) float32. Each block
    is recomputed in the backward pass, so that a sequence's float32
    activations fit."""
    x = params["embed_weight"][tokens]
    for i, (mixer, ffn) in enumerate(counts.layers(cfg)):
        block = jax.checkpoint(functools.partial(
            _block, cfg=cfg, mixer=mixer, ffn=ffn, low=low))
        x = block(x, _layer(params, i),
                  state.get("layer%d_experts_expert_bias" % i))
    x = _rms(x, params["lnf_gamma"], cfg["norm_eps"])
    return _mm(x, params["embed_weight"], low)     # the head is the table


def ref_seq_loss(params, state, tokens, labels, cfg, low=False):
    """Sum of next-token NLL over one sequence."""
    logits = ref_logits(params, state, tokens, cfg, low)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=1))


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
