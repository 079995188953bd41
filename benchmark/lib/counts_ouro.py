"""FLOP and parameter counts of the ``ouro_lm`` family, from shapes: the
benchmark's own arithmetic (2 FLOPs per multiply-add, training = 3 x forward,
recomputation not counted). Nothing here imports the program or JAX.

A configuration is the published ``config.json`` keys of ByteDance/Ouro-2.6B
(``configs/ouro-2.6b.train.json``). The layers held run ``total_ut_steps``
times a step with one set of weights, so every layer's products and its
attention are counted once a pass; each pass ends in an exit, the head's
product and, but for the last pass, the exit gate's (hidden -> 1). The
norms, the gate's sigmoid and the loss are left out beside the products.
"""


def head_dim(cfg):
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg[
        "num_attention_heads"]


def loops(cfg):
    return cfg["total_ut_steps"]


def applications(cfg):
    """Layer applications a token: the layers held, once a pass."""
    return loops(cfg) * cfg["num_hidden_layers"]


def layer_matmul_params(cfg):
    """q, k, v, o without bias and the SwiGLU's three matrices."""
    d, dh = cfg["hidden_size"], head_dim(cfg)
    dq, dkv = dh * cfg["num_attention_heads"], dh * cfg["num_key_value_heads"]
    return 2 * d * dq + 2 * d * dkv + 3 * d * cfg["intermediate_size"]


def layer_params(cfg):
    """A layer with its four RMSNorm scales (the sandwich)."""
    return layer_matmul_params(cfg) + 4 * cfg["hidden_size"]


def params(cfg):
    """Embedding, the layers held, the final norm, the untied head and the
    exit gate (a weight a channel and a bias)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return (v * d + cfg["num_hidden_layers"] * layer_params(cfg) + d
            + v * d + d + 1)


def matmul_flops_per_token(cfg):
    """Forward FLOPs a token outside attention's score and value products:
    every layer's matrices once an application, the head once an exit and
    the gate at every exit but the last; the embedding is a lookup."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return 2 * (applications(cfg) * layer_matmul_params(cfg)
                + loops(cfg) * d * v + (loops(cfg) - 1) * d)


def causal_pairs(seq_len):
    """Query-key pairs of causal attention, the diagonal included."""
    return seq_len * (seq_len + 1) // 2


def attn_flops(cfg, seq_len):
    """Forward FLOPs of QK^T and PV of one layer application over one
    whole sequence, causal."""
    return 2 * 2 * cfg["num_attention_heads"] * head_dim(cfg) * causal_pairs(
        seq_len)


def forward_flops(cfg, seq_len):
    """One whole sequence, forward, every pass."""
    return (seq_len * matmul_flops_per_token(cfg)
            + applications(cfg) * attn_flops(cfg, seq_len))


def train_step_flops(cfg, batch, seq_len):
    """Forward and backward (2 x forward), recomputation not counted."""
    return 3 * batch * forward_flops(cfg, seq_len)
