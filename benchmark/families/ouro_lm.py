"""Family ``ouro_lm``: ByteDance/Ouro-2.6B (``model_type`` ``ouro``), a
looped language model: the layers held run ``total_ut_steps`` times a token
with one set of weights, and every pass ends in an exit.

A layer l, on the residual stream ``x`` (every ``N`` an RMSNorm with a plain
scale at ``rms_norm_eps``; four a layer, the sandwich)::

    u  = x + N_l2(Attn_l(N_l1(x)))
    x' = u + N_l4(FFN_l(N_l3(u)))

``Attn`` causal multi-head attention, 16 heads of 128, no bias, RoPE at base
``rope_theta`` on q and k (halves rotated against each other);
``FFN(y) = W_down(silu(W_gate y) * W_up y)``, no bias. The model, a token::

    h_0 = E[id]
    h_t = N_f(layers 0..L-1 of h_{t-1})            t = 1..T
    z_t = W_head h_t                               the exit's logits
    lambda_t = sigmoid(w_g . h_t + b_g)            t < T, float32
    p_t = lambda_t prod_{j<t} (1 - lambda_j)       t < T
    p_T = prod_{j<T} (1 - lambda_j)
    loss = sum_t p_t CE(z_t, y) + beta sum_t p_t log p_t

``h_t`` is both exit t's input and pass t+1's; the step's loss is the mean
over tokens. The configuration file lists under ``assumed`` what the config
does not give (the sandwich, the carried norm, beta, the gate's bias).

Two halves that share nothing but the seed:

- the program's side: the symbol (``models.get_symbol("transformer-lm",
  ..., loops=, exit_loss=)``) and the seeded parameters and token batches,
  made on the device in one jitted call;
- the plain reference: forward, the loss over the exits, gradients and
  SGD-with-momentum in straightforward ``jax.numpy``, float32 at
  ``highest`` matmul precision, no kernel, one sequence and one head at a
  time, the exit distribution as the products above. It imports nothing of
  the program.

Departures from the published description, each marked ``# departure``
below: weights are random from the seed, RMSNorm scales start at 1, the
gate's bias at 0; ``early_exit_threshold`` is for inference and is not used.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from lib import counts_ouro as counts
from lib import refmath
from lib.refmath import seed_key, q8 as _q8

HP = jax.lax.Precision.HIGHEST


# --- sizes -------------------------------------------------------------------

def param_shapes(cfg):
    """name -> shape, named and ordered as the package's symbol lists them:
    one set of leaves a layer held, whatever the passes."""
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    dh = counts.head_dim(cfg)
    dq, dkv = dh * cfg["num_attention_heads"], dh * cfg["num_key_value_heads"]
    shapes = {"embed_weight": (v, d)}
    for i in range(cfg["num_hidden_layers"]):
        p = "layer%d_" % i
        shapes.update({
            p + "ln1_gamma": (d,), p + "q_weight": (dq, d),
            p + "k_weight": (dkv, d), p + "v_weight": (dkv, d),
            p + "o_weight": (d, dq), p + "post1_gamma": (d,),
            p + "ln2_gamma": (d,), p + "ffn1_weight": (f, d),
            p + "ffn3_weight": (f, d), p + "ffn2_weight": (d, f),
            p + "post2_gamma": (d,)})
    shapes.update({"lnf_gamma": (d,), "pred_weight": (v, d),
                   "exit_gate_weight": (1, d), "exit_gate_bias": (1,)})
    return shapes


def step_flops(cfg, traffic):
    return counts.train_step_flops(cfg, traffic["batch"], traffic["seq_len"])


def _init_leaf(key, name, shape, std, bias):
    if name.endswith("_gamma"):
        return jnp.ones(shape, jnp.float32)  # departure
    if name.endswith("exit_gate_bias"):
        return jnp.full(shape, bias, jnp.float32)  # departure
    return std * jax.random.normal(key, shape, jnp.float32)  # departure


def _inits(cfg):
    return (float(cfg.get("initializer_range", 0.02)),
            float(cfg.get("exit_gate_bias_init", 0.0)))


def init_params(cfg, seed):
    """Every leaf from the seed in one jitted call, on the default device,
    float32 (the trainer's master weights): matrices and the table normal
    at ``initializer_range``, RMSNorm scales at 1, the gate's bias at
    ``exit_gate_bias_init``."""
    shapes = param_shapes(cfg)
    inits = _inits(cfg)

    @jax.jit
    def make(key0):
        key = jax.random.fold_in(key0, 1)
        return {n: _init_leaf(jax.random.fold_in(key, i), n, s, *inits)
                for i, (n, s) in enumerate(shapes.items())}

    return make(seed_key(seed))


def init_leaf(cfg, seed, name):
    """One leaf again, float32 (the same bits ``init_params`` gave)."""
    shapes = param_shapes(cfg)
    i = list(shapes).index(name)
    key = jax.random.fold_in(jax.random.fold_in(seed_key(seed), 1), i)
    kind = next((k for k in ("_gamma", "exit_gate_bias") if name.endswith(k)),
                "_weight")
    return _leaf_jit(kind, shapes[name], _inits(cfg))(key)


@functools.lru_cache(maxsize=None)
def _leaf_jit(kind, shape, inits):
    return jax.jit(lambda key: _init_leaf(key, kind, shape, *inits))


def make_batches(cfg, traffic, seed, n):
    """``n`` batches of token ids uniform over the vocabulary, rows all
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

def symbol(cfg, for_training):
    """The program's own symbol: the layers held, ``total_ut_steps``
    passes, an exit after each, the exits' objective."""
    from mxnet_tpu import models

    kind = {"norm": "rms", "ffn": "swiglu", "post_norm": True,
            "rope_base": float(cfg["rope_theta"])}
    return models.get_symbol(
        "transformer-lm", num_classes=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], head_dim=counts.head_dim(cfg),
        model_dim=cfg["hidden_size"], ffn_dim=cfg["intermediate_size"],
        num_kv_heads=cfg["num_key_value_heads"],
        layers=[kind] * cfg["num_hidden_layers"], final_norm="rms",
        head_bias=False, norm_eps=float(cfg["rms_norm_eps"]),
        loops=counts.loops(cfg),
        exit_loss={"beta": float(cfg["exit_entropy_beta"])},
        scalar_loss=for_training)


def loss_from_outputs(outputs, labels):
    """The loss head already gives the mean over tokens."""
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
    """One head: q, k, v (T, Dh); causal softmax of q k / sqrt(Dh)."""
    t, dh = k.shape
    if low:
        q, k, v = _q8(q), _q8(k), _q8(v)
    s = jnp.einsum("qd,kd->qk", q, k, precision=HP) / np.sqrt(dh)
    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    if low:
        p = _q8(p)
    return jnp.einsum("qk,kd->qd", p, v, precision=HP)


def _attn(y, lp, cfg, low):
    """y (T, D) -> (T, D): q, k, v, RoPE, attention, o."""
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, t = counts.head_dim(cfg), y.shape[0]
    base = float(cfg["rope_theta"])
    q = _mm(y, lp["q_weight"], low).reshape(t, h, dh).transpose(1, 0, 2)
    k = _mm(y, lp["k_weight"], low).reshape(t, hkv, dh).transpose(1, 0, 2)
    v = _mm(y, lp["v_weight"], low).reshape(t, hkv, dh).transpose(1, 0, 2)
    q, k = _rope(q, base), _rope(k, base)
    q = q.reshape(hkv, h // hkv, t, dh)

    # one kv group at a time and in it one query head at a time, each
    # recomputed in the backward pass: the (T, T) float32 scores are the
    # large thing
    def group(a):
        head = jax.checkpoint(lambda qh: _attention(qh, a[1], a[2], low))
        return jax.lax.map(head, a[0])

    att = jax.lax.map(group, (q, k, v))
    att = att.reshape(h, t, dh).transpose(1, 0, 2).reshape(t, h * dh)
    return _mm(att, lp["o_weight"], low)


def _ffn(y, lp, low):
    return _mm(jax.nn.silu(_mm(y, lp["ffn1_weight"], low))
               * _mm(y, lp["ffn3_weight"], low), lp["ffn2_weight"], low)


def _block(x, lp, cfg, low):
    """x (T, D) through one layer, the sandwich; ``lp`` the layer's leaves
    by short name."""
    eps = cfg["rms_norm_eps"]
    u = x + _rms(_attn(_rms(x, lp["ln1_gamma"], eps), lp, cfg, low),
                 lp["post1_gamma"], eps)
    return u + _rms(_ffn(_rms(u, lp["ln2_gamma"], eps), lp, low),
                    lp["post2_gamma"], eps)


def _layer(params, i):
    p = "layer%d_" % i
    return {n[len(p):]: a for n, a in params.items() if n.startswith(p)}


def ref_exits(params, tokens, cfg, low=False):
    """One sequence: tokens (T,) int -> the exits' states h_1..h_T, each
    (T, D) float32. Each layer application is recomputed in the backward
    pass."""
    x = params["embed_weight"][tokens]
    block = jax.checkpoint(functools.partial(_block, cfg=cfg, low=low))
    exits = []
    for _ in range(counts.loops(cfg)):
        for i in range(cfg["num_hidden_layers"]):
            x = block(x, _layer(params, i))
        x = _rms(x, params["lnf_gamma"], cfg["rms_norm_eps"])
        exits.append(x)
    return exits


def _exit_nll(h, head, labels, low):
    """Next-token NLL of each token at one exit, (T,); recomputed in the
    backward pass, so that one exit's (T, V) float32 logits live at a
    time."""
    logp = jax.nn.log_softmax(_mm(h, head, low), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]


def exit_distribution(lams):
    """p_1..p_T, each (T,), from the gates lambda_1..lambda_{T-1}."""
    p, stay = [], 1.0
    for lam in lams:
        p.append(lam * stay)
        stay = stay * (1.0 - lam)
    return p + [stay]


def ref_seq_loss(params, tokens, labels, cfg, low=False):
    """The sum over one sequence's tokens of the exits' expected NLL plus
    beta times sum_t p_t log p_t."""
    exits = ref_exits(params, tokens, cfg, low)
    nll = jax.checkpoint(functools.partial(_exit_nll, low=low))
    lams = [jax.nn.sigmoid(_mm(h, params["exit_gate_weight"], low)[:, 0]
                           + params["exit_gate_bias"][0])
            for h in exits[:-1]]
    beta = cfg["exit_entropy_beta"]
    loss = 0.0
    for h, p in zip(exits, exit_distribution(lams)):
        loss = loss + jnp.sum(p * nll(h, params["pred_weight"], labels)
                              + beta * p * jnp.log(p))
    return loss


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
