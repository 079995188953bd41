"""FLOP, byte and parameter counts of the ``qwen3_next_lm`` family, from
shapes: the benchmark's own arithmetic (2 FLOPs per multiply-add, training = 3
x forward, recomputation not counted). Nothing here imports the program or
JAX.

A configuration is the published ``config.json`` keys of
Qwen/Qwen3-Next-80B-A3B-Instruct (``model_type`` ``qwen3_next``), with
``num_experts_held`` beside ``num_experts`` (how many of the router's experts
this chip holds) and ``layers_run`` (which published layers run here: layer
i is full attention where ``(i + 1) % full_attention_interval == 0`` and a
Gated DeltaNet layer elsewhere; every layer's feed-forward is an expert
layer, ``decoder_sparse_step`` 1 and no ``mlp_only_layers``)
(``configs/qwen3-next-80b-a3b.train.json``).

MFU counts the MODEL's work, not the implementation's. The routed experts'
matrices are counted at the EXPECTATION under uniform routing: of a token's
``num_experts_per_tok`` choices, ``held / experts`` fall on this chip (10 x
32/512 = 0.625 expert feed-forwards a token); the shared expert and its
gate are counted whole for every token. A Gated DeltaNet layer is counted by
its projections and its core in the CHUNKED form at a fixed chunk of
``CORE_CHUNK`` tokens (``gdn_core_macs``), whatever chunk the program uses;
the gated attention layer by its projections (the gate's among them) and
its causal pairs at head size ``head_dim``. The convolution, the norms, the
gates' elementwise products, RoPE and the loss are left out.
"""

CORE_CHUNK = 64


def held(cfg):
    return cfg.get("num_experts_held", cfg["num_experts"])


def layers(cfg):
    """``"gdn"`` or ``"attention"`` for each layer that is run: the
    published layers ``layers_run`` names (all of them without the key)."""
    n = cfg["num_hidden_layers"]
    run = cfg.get("layers_run", list(range(n)))
    if len(run) != n:
        raise ValueError("layers_run names %d layers, num_hidden_layers is %d"
                         % (len(run), n))
    every = cfg["full_attention_interval"]
    return ["attention" if (i + 1) % every == 0 else "gdn" for i in run]


def gdn_sizes(cfg):
    """(key heads, value heads, key size, value size) of a GDN layer."""
    return (cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"])


def gdn_matmul_params(cfg):
    """The projections d -> q, k of the key heads and v, z of the value
    heads (the published in_proj_qkvz), d -> b, a (one a value head each:
    in_proj_ba) and out_proj, no bias."""
    d = cfg["hidden_size"]
    hk, hv, dk, dv = gdn_sizes(cfg)
    return d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv + hv * dv * d


def gdn_params(cfg):
    """The projections, the convolution's taps over q, k and v, A_log and
    dt_bias (one a value head), and the gated norm's scale (a value
    head's size)."""
    hk, hv, dk, dv = gdn_sizes(cfg)
    channels = 2 * hk * dk + hv * dv
    return (gdn_matmul_params(cfg) + channels * cfg["linear_conv_kernel_dim"]
            + 2 * hv + dv)


def gdn_core_macs(cfg, tokens, chunk=CORE_CHUNK):
    """Multiply-adds of the delta rule's forward over ``tokens`` tokens of
    every value head, in the chunked (WY) form: a chunk's K K^T, Q K^T and
    T (beta e^gam K) (C^2 dk each), T (beta V) and the intra-chunk P U (C^2
    dv each), W S, Q S and the state's update K^T U (C dk dv each), and the
    unit lower-triangular inverse (C^3 / 6)."""
    _, hv, dk, dv = gdn_sizes(cfg)
    c = chunk
    per_chunk = 3 * c * c * dk + 2 * c * c * dv + 3 * c * dk * dv \
        + c ** 3 / 6
    return hv * per_chunk * tokens / c


def attn_matmul_params(cfg):
    """q and its output gate (heads x head_dim each), k and v (KV heads x
    head_dim each), o; no bias."""
    d, h, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["head_dim"])
    return d * 2 * h * dh + 2 * d * cfg["num_key_value_heads"] * dh \
        + h * dh * d


def attn_params(cfg):
    """The projections and the q and k heads' RMSNorm scales."""
    return attn_matmul_params(cfg) + 2 * cfg["head_dim"]


def router_params(cfg):
    return cfg["num_experts"] * cfg["hidden_size"]


def expert_params(cfg):
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg):
    """The shared expert and its gate (d -> 1)."""
    return (3 * cfg["hidden_size"] * cfg["shared_expert_intermediate_size"]
            + cfg["hidden_size"])


def layer_params(cfg, mixer):
    """One layer as held here: its mixer, two RMSNorm scales, the router,
    the held experts and the shared expert with its gate."""
    mix = gdn_params(cfg) if mixer == "gdn" else attn_params(cfg)
    return (mix + 2 * cfg["hidden_size"] + router_params(cfg)
            + held(cfg) * expert_params(cfg) + shared_params(cfg))


def params(cfg):
    """The table, the layers, the final RMSNorm and the untied head."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return v * d + sum(layer_params(cfg, m) for m in layers(cfg)) + d + v * d


def expected_assignments_per_token(cfg):
    """Of a token's chosen experts, how many this chip holds, in
    expectation under uniform routing."""
    return cfg["num_experts_per_tok"] * held(cfg) / cfg["num_experts"]


def ffn_matmul_params(cfg):
    """An expert layer's weights a token touches: the router, the shared
    expert and its gate, and the held experts at their expectation."""
    return (router_params(cfg) + shared_params(cfg)
            + expert_params(cfg) * expected_assignments_per_token(cfg))


def causal_pairs(seq_len):
    return seq_len * (seq_len + 1) // 2


def attn_flops(cfg, seq_len):
    """Forward FLOPs of QK^T and PV for one sequence in one attention
    layer, at the published head size."""
    return 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * causal_pairs(seq_len)


def part_flops(cfg, seq_len):
    """Forward FLOPs of one whole sequence, by part: ``gdn`` (the GDN
    layers' projections and cores), ``attention`` (projections and causal
    pairs), ``experts`` (every expert layer) and ``head``."""
    kinds = layers(cfg)
    gdn = kinds.count("gdn") * (2 * seq_len * gdn_matmul_params(cfg)
                                + 2 * gdn_core_macs(cfg, seq_len))
    att = kinds.count("attention") * (2 * seq_len * attn_matmul_params(cfg)
                                      + attn_flops(cfg, seq_len))
    return {"gdn": gdn, "attention": att,
            "experts": len(kinds) * 2 * seq_len * ffn_matmul_params(cfg),
            "head": 2 * seq_len * cfg["hidden_size"] * cfg["vocab_size"]}


def forward_flops(cfg, seq_len):
    return sum(part_flops(cfg, seq_len).values())


def train_step_flops(cfg, batch, seq_len):
    """Forward and backward (2 x forward), recomputation not counted."""
    return int(3 * batch * forward_flops(cfg, seq_len))


def gated_delta_calls(cfg, batch, seq_len, bytes_per_el=2):
    """What the delta rule's core must do in one training step, a GDN
    layer: the forward (``gdn_core_macs``) and the backward (twice it),
    and the bytes each pass moves once through HBM: q, k (key heads), v
    (value heads) in the compute dtype and g, beta (one float32 a value
    head and token) in, o out; the backward reads them and dO and writes
    the gradients of q, k, v, g and beta. One ``{"fwd", "bwd"}`` dict a GDN
    layer."""
    hk, hv, dk, dv = gdn_sizes(cfg)
    tokens = batch * seq_len
    qk = 2 * tokens * hk * dk * bytes_per_el
    v = tokens * hv * dv * bytes_per_el      # and o, dO, dv each
    gb = 2 * tokens * hv * 4                 # g and beta, or their grads
    flops = 2 * gdn_core_macs(cfg, tokens)
    call = {"fwd": {"flops": flops, "bytes": qk + 2 * v + gb},
            "bwd": {"flops": 2 * flops, "bytes": 2 * qk + 3 * v + 2 * gb}}
    return [dict(call) for _ in range(layers(cfg).count("gdn"))]


def expert_layers(cfg):
    return len(layers(cfg))
