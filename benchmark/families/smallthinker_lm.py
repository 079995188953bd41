"""Family ``smallthinker_lm``: PowerInfer/SmallThinker-21BA3B-Instruct as one
chip of a four-way expert-parallel layer trains it. Pre-RMSNorm blocks
(eps 1e-6, a plain scale), grouped-query attention without bias (28 query
heads over 4 KV heads of 128, wider together than the model's 2560), window +
RoPE layers (4096 keys, base 1.5e6, halves rotated against each other)
beside global layers with NO position encoding, a top-6-of-64 router that
reads the ATTENTION's normed input, ReLU-gated experts of width 768 with no
dense feed-forward beside them, a final RMSNorm and an untied bias-free head.

The chip's share (``configs/smallthinker-21b-a3b.train.json``): experts
0..held-1 of every layer, rows 0..vocab_size-1 of the vocabulary. The router
keeps its 64 outputs and its 6 a token, the weights stay normalised over all
6 chosen, and what experts held..63 would add is left out, here and in the
program alike; the partial sum goes on to the next layer.

Two halves that share nothing but the seed:

- the program's side: the symbol (``models.get_symbol("transformer-lm",
  ...)`` with its per-layer kinds) and the seeded parameters and token
  batches, made on the device in one jitted call;
- the plain reference: forward, loss, gradients and SGD-with-momentum in
  straightforward ``jax.numpy``, float32 at ``highest`` matmul precision, no
  kernel, one sequence and one KV group at a time, the expert layer a masked
  dense sum over the held experts. It imports nothing of the program.

Departures from the published description, each marked ``# departure`` below:
the "secondary experts" ``described_as`` mentions have no key in the config
and are not built; weights are random from the seed.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from lib import counts_smallthinker as counts
from lib import refmath
from lib.refmath import seed_key, q8 as _q8

HP = jax.lax.Precision.HIGHEST


# --- sizes -------------------------------------------------------------------

def param_shapes(cfg):
    """name -> shape, named and ordered as the package's symbol lists them."""
    d, f, v = cfg["hidden_size"], cfg["moe_ffn_hidden_size"], cfg["vocab_size"]
    dq = cfg["head_dim"] * cfg["num_attention_heads"]
    dkv = cfg["head_dim"] * cfg["num_key_value_heads"]
    e, held = cfg["moe_num_primary_experts"], counts.held(cfg)
    shapes = {"embed_weight": (v, d)}
    for i in range(cfg["num_hidden_layers"]):
        p = "layer%d_" % i
        shapes.update({
            p + "ln1_gamma": (d,),
            p + "q_weight": (dq, d), p + "k_weight": (dkv, d),
            p + "v_weight": (dkv, d), p + "o_weight": (d, dq),
            p + "ln2_gamma": (d,),
            p + "router_weight": (e, d),
            p + "gate_weight": (held, f, d), p + "up_weight": (held, f, d),
            p + "down_weight": (held, d, f)})
    shapes.update({"lnf_gamma": (d,), "pred_weight": (v, d)})
    return shapes


def step_flops(cfg, traffic):
    return counts.train_step_flops(cfg, traffic["batch"], traffic["seq_len"])


def _std(cfg):
    return float(cfg.get("initializer_range", 0.02))


def _init_leaf(key, name, shape, std):
    if name.endswith("_gamma"):
        return jnp.ones(shape, jnp.float32)
    return std * jax.random.normal(key, shape, jnp.float32)  # departure


def init_params(cfg, seed):
    """Every leaf from the seed in one jitted call, on the default device,
    float32 (the trainer's master weights): matrices normal at
    ``initializer_range`` (assumed 0.02), RMSNorm scales at 1."""
    shapes = param_shapes(cfg)
    std = _std(cfg)

    @jax.jit
    def make(key0):
        key = jax.random.fold_in(key0, 1)
        return {n: _init_leaf(jax.random.fold_in(key, i), n, s, std)
                for i, (n, s) in enumerate(shapes.items())}

    return make(seed_key(seed))


def init_leaf(cfg, seed, name):
    """One leaf again, float32 (the same bits ``init_params`` gave)."""
    shapes = param_shapes(cfg)
    i = list(shapes).index(name)
    key = jax.random.fold_in(jax.random.fold_in(seed_key(seed), 1), i)
    kind = name[name.rindex("_"):]
    return _leaf_jit(kind, shapes[name], _std(cfg))(key)


@functools.lru_cache(maxsize=None)
def _leaf_jit(kind, shape, std):
    return jax.jit(lambda key: _init_leaf(key, kind, shape, std))


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


def symbol(cfg, for_training):
    """The program's own symbol at this configuration's sizes and kinds."""
    from mxnet_tpu import models

    kinds = [{"norm": "rms", "ffn": "experts", "window": window,
              "rope": rope, "rope_base": float(cfg["rope_theta"])}
             for window, rope in counts.layers(cfg)]
    experts = {"num_experts": cfg["moe_num_primary_experts"],
               "experts_held": counts.held(cfg),
               "first_expert": 0,
               "top_k": cfg["moe_num_active_primary_experts"],
               "norm_topk": bool(cfg["norm_topk_prob"]), "act_type": "relu"}
    return models.get_symbol(
        "transformer-lm", num_classes=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        model_dim=cfg["hidden_size"], ffn_dim=cfg["moe_ffn_hidden_size"],
        num_kv_heads=cfg["num_key_value_heads"], layers=kinds,
        experts=experts, final_norm="rms", head_bias=False,
        scalar_loss=for_training)


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


def _attention(q, k, v, window, low):
    """One kv head's group: q (G, T, Dh), k/v (T, Dh); causal softmax, query
    i seeing keys j with i - window < j <= i where there is a window."""
    t, dh = k.shape
    if low:
        q, k, v = _q8(q), _q8(k), _q8(v)
    s = jnp.einsum("gqd,kd->gqk", q, k, precision=HP) / np.sqrt(dh)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = j <= i
    if window:
        mask &= j > i - window
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    if low:
        p = _q8(p)
    return jnp.einsum("gqk,kd->gqd", p, v, precision=HP)


def _experts(x, router_in, lp, cfg, low):
    """x (T, D): sum over a token's chosen experts AMONG THOSE HELD of
    w * down(relu(gate x) * up x). The router, float32 whatever ``low``
    (the configuration states a float32 router), reads ``router_in``."""
    k = cfg["moe_num_active_primary_experts"]
    r = jnp.einsum("ti,ei->te", router_in, lp["router_weight"], precision=HP)
    top, idx = jax.lax.top_k(r, k)                     # the choice: no gradient
    if cfg["norm_topk_prob"]:
        w = jax.nn.softmax(top, axis=-1)               # over the k chosen
    else:
        w = jnp.take_along_axis(jax.nn.softmax(r, axis=-1), idx, 1)

    def one(y, e):
        wg, wu, wd, number = e
        # this expert's weight a token: w where the token chose it, else 0
        we = jnp.sum(jnp.where(idx == number, w, 0.0), axis=-1)
        h = jax.nn.relu(_mm(x, wg, low)) * _mm(x, wu, low)
        return y + we[:, None] * _mm(h, wd, low), None

    held = lp["gate_weight"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        lp["gate_weight"], lp["up_weight"], lp["down_weight"],
        jnp.arange(held)))             # the share held: experts 0..held-1
    return y


def _block(x, lp, cfg, window, rope, low):
    """x (T, D) through one block; ``lp`` the block's leaves by short name."""
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    t = x.shape[0]
    y = _rms(x, lp["ln1_gamma"], cfg["rms_norm_eps"])
    q = _mm(y, lp["q_weight"], low).reshape(t, h, dh).transpose(1, 0, 2)
    k = _mm(y, lp["k_weight"], low).reshape(t, hkv, dh).transpose(1, 0, 2)
    v = _mm(y, lp["v_weight"], low).reshape(t, hkv, dh).transpose(1, 0, 2)
    if rope:  # a layer without it has no position encoding at all
        base = float(cfg["rope_theta"])
        q, k = _rope(q, base), _rope(k, base)
    q = q.reshape(hkv, h // hkv, 1, t, dh)

    # one kv group at a time and in it one query head at a time, each
    # recomputed in the backward pass: the (T, T) float32 scores are the
    # large thing, and a map would else keep every head's for its backward
    def group(a):
        head = jax.checkpoint(
            lambda qh: _attention(qh, a[1], a[2], window, low))
        return jax.lax.map(head, a[0])

    att = jax.lax.map(group, (q, k, v))
    att = att.reshape(h, t, dh).transpose(1, 0, 2).reshape(t, h * dh)
    x = x + _mm(att, lp["o_weight"], low)
    y2 = _rms(x, lp["ln2_gamma"], cfg["rms_norm_eps"])
    # the router reads the ATTENTION's normed input, not the experts'
    return x + _experts(y2, y, lp, cfg, low)


def _layer(params, i):
    p = "layer%d_" % i
    return {n[len(p):]: a for n, a in params.items() if n.startswith(p)}


def ref_logits(params, tokens, cfg, low=False):
    """One sequence: tokens (T,) int -> logits (T, V) float32. Each block
    is recomputed in the backward pass, so that a sequence's float32
    activations fit."""
    x = params["embed_weight"][tokens]
    for i, (window, rope) in enumerate(counts.layers(cfg)):
        block = jax.checkpoint(functools.partial(
            _block, cfg=cfg, window=window, rope=rope, low=low))
        x = block(x, _layer(params, i))
    x = _rms(x, params["lnf_gamma"], cfg["rms_norm_eps"])
    return _mm(x, params["pred_weight"], low)


def ref_seq_loss(params, tokens, labels, cfg, low=False):
    """Sum of next-token NLL over one sequence."""
    logits = ref_logits(params, tokens, cfg, low)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=1))


def make_ref_step(cfg, traffic, low=False):
    """The reference's training step (SGD with momentum, no weight decay),
    one sequence at a time so that the float32 scores fit: ``decay(mom)``
    gives ``momentum*mom``, ``fold(params, mom, tokens, labels) ->
    (loss_sum, mom)`` folds ``-lr*(g_seq/n)`` in, and ``apply(params, mom)
    -> params``."""
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
