"""FLOP, byte and parameter counts of the ``smallthinker_lm`` family, from
shapes: the benchmark's own arithmetic (2 FLOPs per multiply-add, training =
3 x forward, recomputation not counted). Nothing here imports the program or
JAX.

A configuration is the published ``config.json`` keys of
PowerInfer/SmallThinker-21BA3B-Instruct, with ``moe_num_primary_experts_held``
beside ``moe_num_primary_experts``: how many of the router's experts this
chip holds (``configs/smallthinker-21b-a3b.train.json``).

MFU counts the MODEL's work, not the implementation's. Which experts a token
takes is data, so the expert matrices are counted at the EXPECTATION under
uniform routing: of a token's ``top_k`` choices, ``held / experts`` fall on
this chip (6 x 16/64 = 1.5 expert feed-forwards a token). Attention is
counted at the pairs its band holds: a window layer's queries see at most
``sliding_window_size`` keys.
"""


def layers(cfg):
    """(window, rope) of each layer that is run: the first
    ``num_hidden_layers`` entries of the published layouts."""
    n = cfg["num_hidden_layers"]
    return [(cfg["sliding_window_size"] if w else 0, bool(r))
            for w, r in zip(cfg["sliding_window_layout"][:n],
                            cfg["rope_layout"][:n])]


def held(cfg):
    return cfg.get("moe_num_primary_experts_held",
                   cfg["moe_num_primary_experts"])


def attn_matmul_params(cfg):
    """q, k, v, o of one layer, without bias."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    dq, dkv = dh * cfg["num_attention_heads"], dh * cfg["num_key_value_heads"]
    return d * dq + 2 * d * dkv + dq * d


def router_params(cfg):
    return cfg["moe_num_primary_experts"] * cfg["hidden_size"]


def expert_params(cfg):
    """One expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def layer_params(cfg):
    """One layer as held here: attention, router, two RMSNorm scales, the
    held experts."""
    return (attn_matmul_params(cfg) + router_params(cfg)
            + 2 * cfg["hidden_size"] + held(cfg) * expert_params(cfg))


def params(cfg):
    """Embedding, layers, final RMSNorm, untied bias-free head."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return cfg["num_hidden_layers"] * layer_params(cfg) + 2 * v * d + d


def expected_assignments_per_token(cfg):
    """Of a token's chosen experts, how many this chip holds, in
    expectation under uniform routing."""
    return (cfg["moe_num_active_primary_experts"] * held(cfg)
            / cfg["moe_num_primary_experts"])


def matmul_flops_per_token(cfg):
    """Forward FLOPs a token outside attention's score and value products:
    2 per weight of the projections, the router and the head; the expert
    matrices at the expected held assignments; the embedding is a lookup."""
    dense = (cfg["num_hidden_layers"]
             * (attn_matmul_params(cfg) + router_params(cfg))
             + cfg["hidden_size"] * cfg["vocab_size"])
    experts = (cfg["num_hidden_layers"] * expert_params(cfg)
               * expected_assignments_per_token(cfg))
    return 2 * dense + int(2 * experts)


def band_pairs(seq_len, window):
    """(query, key) pairs of one causal sequence: query i sees the last
    ``window`` keys up to its own (0: all of them)."""
    if not window or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def attn_flops(cfg, seq_len, window):
    """Forward FLOPs of QK^T and PV for one sequence in one layer."""
    return (2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]
            * band_pairs(seq_len, window))


def forward_flops(cfg, seq_len):
    """One whole sequence, forward."""
    return (seq_len * matmul_flops_per_token(cfg)
            + sum(attn_flops(cfg, seq_len, w) for w, _ in layers(cfg)))


def train_step_flops(cfg, batch, seq_len):
    """Forward and backward (2 x forward), recomputation not counted."""
    return 3 * batch * forward_flops(cfg, seq_len)


def band_flash_calls(cfg, batch, seq_len, bytes_per_el=2):
    """What any score-free attention must do in one training step, a layer:
    forward S = QK^T and O = PV (2 products over the band's pairs), backward
    S again, dP = dO V^T, dV = P^T dO, dK = dS^T Q, dQ = dS K (5), whatever
    kernels implement them; and the bytes each pass moves once through HBM.
    One ``{"fwd", "bwd"}`` dict a layer."""
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = batch * h * seq_len * dh * bytes_per_el
    kv = batch * hkv * seq_len * dh * bytes_per_el
    row = batch * h * seq_len * 4
    out = []
    for window, _ in layers(cfg):
        one = 2 * batch * h * dh * band_pairs(seq_len, window)
        out.append({
            "fwd": {"flops": 2 * one, "bytes": q + 2 * kv + q + row},
            "bwd": {"flops": 5 * one,
                    "bytes": (q + 2 * kv + q + q + 2 * row) + (q + 2 * kv)}})
    return out


def expert_products(cfg, tokens, bytes_per_el=2):
    """The grouped products of one layer's training step at the expected
    held assignments: gate, up and down, each forward, dX and dW (9), with
    the held experts' weights crossing HBM once a product and the sorted
    activations once in and once out. A list of ``{"flops", "bytes"}``."""
    d, f = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    rows = int(tokens * expected_assignments_per_token(cfg))
    weights = held(cfg) * d * f * bytes_per_el
    one = {"flops": 2 * rows * d * f,
           "bytes": weights + rows * (d + f) * bytes_per_el}
    return [dict(one) for _ in range(9)]
