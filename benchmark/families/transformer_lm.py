"""Family ``transformer_lm``: the decoder LM that ``models/transformer.py``
builds (pre-LayerNorm blocks, RoPE at base 10000 split in halves, grouped
query attention without q/k/v/o bias, biased tanh-GELU feed-forward, final
LayerNorm, untied biased head).

Two halves that share nothing but the seed:

- the program's side: the symbol (from the package) and the seeded
  parameters and token batches, made on the device in one jitted call;
- the plain reference: forward, loss, gradients and SGD-with-momentum in
  straightforward ``jax.numpy``, float32 at ``highest`` matmul precision, no
  kernel, one sequence at a time. It imports nothing of the program.

A configuration is the published ``config.json`` keys (see
``configs/starcoder2-3b.train.json``).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from lib import counts
from lib import refmath
from lib.refmath import seed_key, q8 as _q8

ROPE_BASE = 10000.0  # the program's; the published rope_theta is a departure
LN_EPS = 1e-5


# --- sizes -------------------------------------------------------------------

def param_shapes(cfg):
    """name -> shape, named and ordered as the package's symbol lists them."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    dkv = counts.lm_head_dim(cfg) * cfg["num_key_value_heads"]
    shapes = {"embed_weight": (v, d)}
    for i in range(cfg["num_hidden_layers"]):
        p = "layer%d_" % i
        shapes.update({
            p + "ln1_gamma": (d,), p + "ln1_beta": (d,),
            p + "q_weight": (d, d), p + "k_weight": (dkv, d),
            p + "v_weight": (dkv, d), p + "o_weight": (d, d),
            p + "ln2_gamma": (d,), p + "ln2_beta": (d,),
            p + "ffn1_weight": (f, d), p + "ffn1_bias": (f,),
            p + "ffn2_weight": (d, f), p + "ffn2_bias": (d,)})
    shapes.update({"lnf_gamma": (d,), "lnf_beta": (d,),
                   "pred_weight": (v, d), "pred_bias": (v,)})
    return shapes


def step_flops(cfg, traffic):
    return counts.lm_train_step_flops(cfg, traffic["batch"],
                                      traffic["seq_len"])


def _std(cfg):
    return float(cfg.get("initializer_range", 0.02))


def _init_leaf(key, name, shape, std):
    if name.endswith("_gamma"):
        return jnp.ones(shape, jnp.float32)
    if name.endswith("_beta"):
        return jnp.zeros(shape, jnp.float32)
    return std * jax.random.normal(key, shape, jnp.float32)


def init_params(cfg, seed):
    """Every leaf from the seed in one jitted call, on the default device,
    float32 (the trainer's master weights). Matrices, embedding and biases
    are normal at the published ``initializer_range``; LayerNorm starts at
    (1, 0)."""
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
    """``n`` batches of token ids, rows all different, and their next-token
    labels; int32 on the device, one jitted call."""
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
    """The program's own symbol at this configuration's sizes."""
    from mxnet_tpu import models

    return models.get_symbol(
        "transformer-lm", num_classes=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        model_dim=cfg["hidden_size"], ffn_dim=cfg["intermediate_size"],
        num_kv_heads=cfg["num_key_value_heads"], scalar_loss=for_training)


def loss_from_outputs(outputs, labels):
    """The scalar-loss head already gives the mean NLL."""
    return float(np.asarray(outputs[0], np.float32).reshape(-1)[0])


# --- the plain reference -----------------------------------------------------

def _mm(x, w, low):
    """x (.., in) @ w (out, in)^T in float32 at ``highest``; ``low`` rounds
    both operands to fp8 first."""
    if low:
        x, w = _q8(x), _q8(w)
    return jnp.einsum("...i,oi->...o", x, w,
                      precision=jax.lax.Precision.HIGHEST)


def _ln(x, g, b):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _rope(x):
    """(heads, T, Dh), positions 0..T-1, halves rotated against each other."""
    half = x.shape[-1] // 2
    freq = ROPE_BASE ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, low):
    """One kv head's group: q (G, T, Dh), k/v (T, Dh); causal softmax."""
    t, dh = k.shape
    hp = jax.lax.Precision.HIGHEST
    if low:
        q, k, v = _q8(q), _q8(k), _q8(v)
    s = jnp.einsum("gqd,kd->gqk", q, k, precision=hp) / np.sqrt(dh)
    mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    if low:
        p = _q8(p)
    return jnp.einsum("gqk,kd->gqd", p, v, precision=hp)


def _block(x, lp, cfg, low):
    """x (T, D) through one block; ``lp`` the block's leaves by short name."""
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = counts.lm_head_dim(cfg)
    t = x.shape[0]
    y = _ln(x, lp["ln1_gamma"], lp["ln1_beta"])
    q = _mm(y, lp["q_weight"], low).reshape(t, h, dh).transpose(1, 0, 2)
    k = _mm(y, lp["k_weight"], low).reshape(t, hkv, dh).transpose(1, 0, 2)
    v = _mm(y, lp["v_weight"], low).reshape(t, hkv, dh).transpose(1, 0, 2)
    q, k = _rope(q), _rope(k)
    q = q.reshape(hkv, h // hkv, t, dh)
    # one kv group at a time: the (G, T, T) scores are the large thing
    att = jax.lax.map(lambda a: _attention(a[0], a[1], a[2], low), (q, k, v))
    att = att.reshape(h, t, dh).transpose(1, 0, 2).reshape(t, h * dh)
    x = x + _mm(att, lp["o_weight"], low)
    y = _ln(x, lp["ln2_gamma"], lp["ln2_beta"])
    y = jax.nn.gelu(_mm(y, lp["ffn1_weight"], low) + lp["ffn1_bias"],
                    approximate=True)
    return x + _mm(y, lp["ffn2_weight"], low) + lp["ffn2_bias"]


def _layer(params, i):
    p = "layer%d_" % i
    return {n[len(p):]: a for n, a in params.items() if n.startswith(p)}


def ref_logits(params, tokens, cfg, low=False):
    """One sequence: tokens (T,) int -> logits (T, V) float32. Each block
    is recomputed in the backward pass, so that a sequence's float32
    activations fit."""
    x = params["embed_weight"][tokens]
    block = jax.checkpoint(functools.partial(_block, cfg=cfg, low=low))
    for i in range(cfg["num_hidden_layers"]):
        x = block(x, _layer(params, i))
    x = _ln(x, params["lnf_gamma"], params["lnf_beta"])
    return _mm(x, params["pred_weight"], low) + params["pred_bias"]


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

