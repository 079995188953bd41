"""FLOP and byte counts from shapes: the benchmark's own arithmetic.

A copy of the sound part of ``mxnet_tpu/flops.py`` (2 FLOPs per multiply-add,
training = 3 x forward, recomputation not counted), kept here because later
PRs may change the program and may not change the yardstick. Nothing in this
file imports the program or JAX.

A transformer configuration is the published ``config.json`` keys
(``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``intermediate_size``, ``vocab_size``, ``num_hidden_layers``).
"""


def lm_head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def lm_layer_matmul_params(cfg):
    """Parameters of one block that sit in matrix multiplications."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    dkv = lm_head_dim(cfg) * cfg["num_key_value_heads"]
    return d * d + 2 * d * dkv + d * d + 2 * d * f


def lm_layer_params(cfg):
    """All parameters of one block as ``models/transformer.py`` builds it:
    q/k/v/o without bias, two biased feed-forward matrices, two LayerNorms."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return lm_layer_matmul_params(cfg) + f + d + 4 * d


def lm_params(cfg):
    """Embedding, blocks, final LayerNorm, untied biased head."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return (v * d + cfg["num_hidden_layers"] * lm_layer_params(cfg)
            + 2 * d + v * d + v)


def lm_matmul_flops_per_token(cfg):
    """Forward FLOPs per token outside attention's score and value
    products: 2 per weight of every matrix, the head included; the
    embedding is a lookup."""
    return 2 * (cfg["num_hidden_layers"] * lm_layer_matmul_params(cfg)
                + cfg["hidden_size"] * cfg["vocab_size"])


def attn_flops(cfg, q_len, kv_len, causal):
    """Forward FLOPs of QK^T and PV for one sequence in one layer. Causal
    with q_len == kv_len counts the lower triangle with its diagonal;
    q_len < kv_len lets each query see the keys up to its own position."""
    h, dh = cfg["num_attention_heads"], lm_head_dim(cfg)
    if causal:
        first = kv_len - q_len + 1  # keys the first query sees
        pairs = q_len * (first + kv_len) // 2
    else:
        pairs = q_len * kv_len
    return 2 * 2 * h * dh * pairs


def lm_forward_flops(cfg, seq_len):
    """One whole sequence, forward, causal."""
    return (seq_len * lm_matmul_flops_per_token(cfg)
            + cfg["num_hidden_layers"] * attn_flops(cfg, seq_len, seq_len,
                                                    True))


def lm_train_step_flops(cfg, batch, seq_len):
    """Forward and backward (2 x forward), recomputation not counted."""
    return 3 * batch * lm_forward_flops(cfg, seq_len)





def flash_calls(cfg, batch, seq_len, bytes_per_el=2):
    """The three flash kernels of one layer's training step (forward, dq,
    dkv) at (batch, heads, seq, head_dim), causal: FLOPs each has to do by
    its algorithm, and the bytes it has to move once through HBM.

    forward: S = QK^T, O = PV (2 products). dq: S again, dP = dO V^T,
    dQ = dS K (3). dkv: S again, dP, dV = P^T dO, dK = dS^T Q (4). The
    score recomputation is the algorithm's own (it never stores S), so it is
    counted here, and it is not counted in the model FLOPs of an MFU.
    """
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  lm_head_dim(cfg))
    one = 2 * batch * h * dh * (seq_len * (seq_len + 1) // 2)  # one product
    q = batch * h * seq_len * dh * bytes_per_el
    kv = batch * hkv * seq_len * dh * bytes_per_el
    lse = batch * h * seq_len * 4
    return {
        "fwd": {"flops": 2 * one, "bytes": q + 2 * kv + q + lse},
        "dq": {"flops": 3 * one, "bytes": q + 2 * kv + q + q + 2 * lse + q},
        "dkv": {"flops": 4 * one,
                "bytes": q + 2 * kv + q + q + 2 * lse + 2 * kv},
    }
