"""FLOP, byte and parameter counts of the ``lfm2_moe_lm`` family, from shapes:
the benchmark's own arithmetic (2 FLOPs per multiply-add, training = 3 x
forward, recomputation not counted). Nothing here imports the program or JAX.

A configuration is the published ``config.json`` keys of
LiquidAI/LFM2-24B-A2B, with ``num_experts_held`` beside ``num_experts`` (how
many of the router's experts this chip holds) and ``layers_run`` (which
entries of the published ``layer_types`` the layers run are; the first
``num_dense_layers`` of them have the dense feed-forward)
(``configs/lfm2-24b-a2b.train.json``).

MFU counts the MODEL's work, not the implementation's. Which experts a token
takes is data, so the expert matrices are counted at the EXPECTATION under
uniform routing: of a token's ``num_experts_per_tok`` choices, ``held /
experts`` fall on this chip (4 x 8/64 = 0.5 expert feed-forwards a token).
Attention is counted at its causal pairs and at the published head size;
the short convolution's taps (2 x 3 FLOPs a channel) are left out beside its
two projections.
"""


def head_dim(cfg):
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg[
        "num_attention_heads"]


def held(cfg):
    return cfg.get("num_experts_held", cfg["num_experts"])


def layers(cfg):
    """(mixer, ffn) of each layer that is run: mixer ``"conv"`` or
    ``"full_attention"`` as the published ``layer_types`` says of the
    entries ``layers_run`` names (all of them without the key), ffn
    ``"dense"`` for the first ``num_dense_layers`` of those and
    ``"experts"`` after."""
    n = cfg["num_hidden_layers"]
    run = cfg.get("layers_run", list(range(n)))
    if len(run) != n:
        raise ValueError("layers_run names %d layers, num_hidden_layers is %d"
                         % (len(run), n))
    return [(cfg["layer_types"][i],
             "dense" if at < cfg["num_dense_layers"] else "experts")
            for at, i in enumerate(run)]


def conv_params(cfg):
    """One gated short convolution: in (d -> 3d), the taps, out (d -> d)."""
    d = cfg["hidden_size"]
    return 3 * d * d + d * cfg["conv_L_cache"] + d * d


def attn_params(cfg):
    """q, k, v, o without bias, and the two head-norm scales."""
    d, dh = cfg["hidden_size"], head_dim(cfg)
    dq, dkv = dh * cfg["num_attention_heads"], dh * cfg["num_key_value_heads"]
    return d * dq + 2 * d * dkv + dq * d + 2 * dh


def dense_ffn_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg):
    return cfg["num_experts"] * cfg["hidden_size"]


def expert_params(cfg):
    """One expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params(cfg, mixer, ffn):
    """One layer as held here: the mixer, two RMSNorm scales, and the dense
    feed-forward or the router and the held experts."""
    mix = conv_params(cfg) if mixer == "conv" else attn_params(cfg)
    feed = dense_ffn_params(cfg) if ffn == "dense" else (
        router_params(cfg) + held(cfg) * expert_params(cfg))
    return mix + 2 * cfg["hidden_size"] + feed


def params(cfg):
    """The table (the head is tied to it), the layers, the final RMSNorm.
    The ``expert_bias`` is state and no parameter."""
    d = cfg["hidden_size"]
    return (sum(layer_params(cfg, m, f) for m, f in layers(cfg))
            + cfg["vocab_size"] * d + d)


def expected_assignments_per_token(cfg):
    """Of a token's chosen experts, how many this chip holds, in
    expectation under uniform routing."""
    return cfg["num_experts_per_tok"] * held(cfg) / cfg["num_experts"]


def matmul_flops_per_token(cfg):
    """Forward FLOPs a token outside attention's score and value products:
    2 per weight of the projections, the dense feed-forward, the routers
    and the head; the expert matrices at the expected held assignments; the
    embedding is a lookup, norm scales and taps are left out."""
    d = cfg["hidden_size"]
    total = d * cfg["vocab_size"]
    for mixer, ffn in layers(cfg):
        total += (4 * d * d if mixer == "conv"
                  else attn_params(cfg) - 2 * head_dim(cfg))
        total += (dense_ffn_params(cfg) if ffn == "dense" else
                  router_params(cfg)
                  + expert_params(cfg) * expected_assignments_per_token(cfg))
    return int(2 * total)


def causal_pairs(seq_len):
    return seq_len * (seq_len + 1) // 2


def attn_flops(cfg, seq_len):
    """Forward FLOPs of QK^T and PV for one sequence in one attention
    layer, at the published head size."""
    return (2 * 2 * cfg["num_attention_heads"] * head_dim(cfg)
            * causal_pairs(seq_len))


def forward_flops(cfg, seq_len):
    """One whole sequence, forward."""
    n_attn = sum(m == "full_attention" for m, _ in layers(cfg))
    return (seq_len * matmul_flops_per_token(cfg)
            + n_attn * attn_flops(cfg, seq_len))


def train_step_flops(cfg, batch, seq_len):
    """Forward and backward (2 x forward), recomputation not counted."""
    return 3 * batch * forward_flops(cfg, seq_len)


def flash_calls(cfg, batch, seq_len, bytes_per_el=2):
    """What any score-free attention must do in one training step, an
    attention layer: forward S = QK^T and O = PV (2 products over the
    causal pairs), backward S again, dP = dO V^T, dV = P^T dO, dK = dS^T Q,
    dQ = dS K (5), at the PUBLISHED head size whatever the kernels pad it
    to; and the bytes each pass moves once through HBM. One ``{"fwd",
    "bwd"}`` dict an attention layer."""
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    q = batch * h * seq_len * dh * bytes_per_el
    kv = batch * hkv * seq_len * dh * bytes_per_el
    row = batch * h * seq_len * 4
    one = 2 * batch * h * dh * causal_pairs(seq_len)
    call = {"fwd": {"flops": 2 * one, "bytes": q + 2 * kv + q + row},
            "bwd": {"flops": 5 * one,
                    "bytes": (q + 2 * kv + q + q + 2 * row) + (q + 2 * kv)}}
    return [dict(call) for m, _ in layers(cfg) if m == "full_attention"]


def expert_products(cfg, tokens, bytes_per_el=2):
    """The grouped products of one expert layer's training step at the
    expected held assignments: gate, up and down, each forward, dX and dW
    (9), with the held experts' weights crossing HBM once a product and
    the sorted activations once in and once out. A list of ``{"flops",
    "bytes"}``."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = int(tokens * expected_assignments_per_token(cfg))
    weights = held(cfg) * d * f * bytes_per_el
    one = {"flops": 2 * rows * d * f,
           "bytes": weights + rows * (d + f) * bytes_per_el}
    return [dict(one) for _ in range(9)]


def expert_layers(cfg):
    return sum(f == "experts" for _, f in layers(cfg))


def short_conv_passes(cfg, tokens, bytes_per_el=2):
    """The bytes the elementwise part of one gated short convolution must
    move in a training step, between the op's two projections: forward,
    read B, C and X and write the gated result (the gate B * X, the taps
    and the gate C in one pass); backward, read them and the incoming
    gradient and write three gradients (the taps' own gradient is 3 numbers
    a channel). A list of ``{"flops", "bytes"}``, bytes alone: the FLOPs
    are a dozen a number moved."""
    act = tokens * cfg["hidden_size"] * bytes_per_el   # one (tokens, d) array
    return [{"flops": 0, "bytes": 3 * act + act},
            {"flops": 0, "bytes": 3 * act + act + 3 * act}]


def conv_layers(cfg):
    return sum(m == "conv" for m, _ in layers(cfg))
