"""FLOP, byte and parameter counts of the ``glm_moe_lite_lm`` family, from
shapes: the benchmark's own arithmetic (2 FLOPs per multiply-add, training = 3
x forward, recomputation not counted). Nothing here imports the program or
JAX.

A configuration is the published ``config.json`` keys of
zai-org/GLM-4.7-Flash (``model_type`` ``glm4_moe_lite``), with
``n_routed_experts_held`` beside ``n_routed_experts`` (how many of the
router's experts this chip holds) and ``layers_run`` (which published layers
run here; those under ``first_k_dense_replace`` have the dense feed-forward)
(``configs/glm-4.7-flash.train.json``).

MFU counts the MODEL's work, not the implementation's. Which experts a token
takes is data, so the routed experts' matrices are counted at the
EXPECTATION under uniform routing: of a token's ``num_experts_per_tok``
choices, ``held / experts`` fall on this chip (4 x 8/64 = 0.5 expert
feed-forwards a token); the shared expert is counted whole for every token.
Latent attention is counted by its projections (q down and up, kv down and
up, o) and its causal pairs at the published head size (``qk_nope_head_dim
+ qk_rope_head_dim``, the value's ``v_head_dim`` the same); the
multi-token-prediction module (``num_nextn_predict_layers``) by its
``eh_proj``, its expert layer and its product with the shared head, at every
position. Norms, the key's assembly, RoPE and the loss are left out.
"""


def head_dim(cfg):
    """A q and k head: its unrotated and its rotary part."""
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def held(cfg):
    return cfg.get("n_routed_experts_held", cfg["n_routed_experts"])


def layers(cfg):
    """``"dense"`` or ``"experts"`` for each layer that is run: the
    published layers ``layers_run`` names (all of them without the key),
    dense under ``first_k_dense_replace``."""
    n = cfg["num_hidden_layers"]
    run = cfg.get("layers_run", list(range(n)))
    if len(run) != n:
        raise ValueError("layers_run names %d layers, num_hidden_layers is %d"
                         % (len(run), n))
    return ["dense" if i < cfg["first_k_dense_replace"] else "experts"
            for i in run]


def mtp_modules(cfg):
    return cfg["num_nextn_predict_layers"]


def mla_matmul_params(cfg):
    """The five projections of latent attention, no bias: q_a (d -> q
    rank), q_b (q rank -> heads x head), kv_a (d -> kv rank + rotary part),
    kv_b (kv rank -> heads x (unrotated key part + value)), o."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return (d * qr + qr * h * head_dim(cfg) + d * (kvr + rope)
            + kvr * h * (nope + v) + h * v * d)


def mla_params(cfg):
    """The projections and the two latents' RMSNorm scales."""
    return mla_matmul_params(cfg) + cfg["q_lora_rank"] + cfg["kv_lora_rank"]


def dense_ffn_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg):
    return cfg["n_routed_experts"] * cfg["hidden_size"]


def expert_params(cfg):
    """One routed or shared expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg):
    return cfg["n_shared_experts"] * expert_params(cfg)


def layer_params(cfg, ffn):
    """One layer as held here: latent attention, two RMSNorm scales, and
    the dense feed-forward or the router, the held experts and the shared
    expert."""
    feed = dense_ffn_params(cfg) if ffn == "dense" else (
        router_params(cfg) + held(cfg) * expert_params(cfg)
        + shared_params(cfg))
    return mla_params(cfg) + 2 * cfg["hidden_size"] + feed


def mtp_params(cfg):
    """One module: its two input norms, ``eh_proj`` (2d -> d), an expert
    layer and its final norm (table and head are the model's)."""
    d = cfg["hidden_size"]
    return 2 * d + 2 * d * d + layer_params(cfg, "experts") + d


def params(cfg):
    """The table, the layers, the final RMSNorm, the untied head and the
    multi-token-prediction modules. ``expert_bias`` is state and no
    parameter."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return (v * d + sum(layer_params(cfg, f) for f in layers(cfg)) + d
            + v * d + mtp_modules(cfg) * mtp_params(cfg))


def expected_assignments_per_token(cfg):
    """Of a token's chosen experts, how many this chip holds, in
    expectation under uniform routing."""
    return cfg["num_experts_per_tok"] * held(cfg) / cfg["n_routed_experts"]


def ffn_matmul_params(cfg, ffn):
    """A feed-forward's weights a token touches: the dense layer's, or the
    router, the shared expert and the held experts at their expectation."""
    if ffn == "dense":
        return dense_ffn_params(cfg)
    return (router_params(cfg) + shared_params(cfg)
            + expert_params(cfg) * expected_assignments_per_token(cfg))


def mla_flops_per_token(cfg):
    """Forward FLOPs a token of every latent attention's projections, the
    module's layer among them."""
    return 2 * mla_matmul_params(cfg) * (len(layers(cfg)) + mtp_modules(cfg))


def matmul_flops_per_token(cfg):
    """Forward FLOPs a token outside attention's score and value products:
    every layer's projections and feed-forward, the module's ``eh_proj``
    and expert layer, and the head once for the model and once a module;
    the embedding is a lookup."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    total = sum(mla_matmul_params(cfg) + ffn_matmul_params(cfg, f)
                for f in layers(cfg))
    total += mtp_modules(cfg) * (2 * d * d + mla_matmul_params(cfg)
                                 + ffn_matmul_params(cfg, "experts"))
    total += (1 + mtp_modules(cfg)) * d * v
    return int(2 * total)


def causal_pairs(seq_len):
    return seq_len * (seq_len + 1) // 2


def attention_layers(cfg):
    return len(layers(cfg)) + mtp_modules(cfg)


def attn_flops(cfg, seq_len):
    """Forward FLOPs of QK^T and PV for one sequence in one attention
    layer, at the published head size."""
    return 2 * 2 * cfg["num_attention_heads"] * head_dim(cfg) * causal_pairs(
        seq_len)


def forward_flops(cfg, seq_len):
    """One whole sequence, forward."""
    return (seq_len * matmul_flops_per_token(cfg)
            + attention_layers(cfg) * attn_flops(cfg, seq_len))


def train_step_flops(cfg, batch, seq_len):
    """Forward and backward (2 x forward), recomputation not counted."""
    return 3 * batch * forward_flops(cfg, seq_len)


def flash_calls(cfg, batch, seq_len, bytes_per_el=2):
    """What any score-free attention must do in one training step, an
    attention layer (the module's among them): forward S = QK^T and O = PV
    (2 products over the causal pairs), backward S again, dP = dO V^T,
    dV = P^T dO, dK = dS^T Q, dQ = dS K (5), at the published head size
    (256: q, k and v alike); and the bytes each pass moves once through
    HBM. One ``{"fwd", "bwd"}`` dict an attention layer."""
    h, dh = cfg["num_attention_heads"], head_dim(cfg)
    q = batch * h * seq_len * dh * bytes_per_el   # and k, v, o, each
    row = batch * h * seq_len * 4
    one = 2 * batch * h * dh * causal_pairs(seq_len)
    call = {"fwd": {"flops": 2 * one, "bytes": 4 * q + row},
            "bwd": {"flops": 5 * one, "bytes": (5 * q + 2 * row) + 3 * q}}
    return [dict(call) for _ in range(attention_layers(cfg))]


def expert_products(cfg, tokens, bytes_per_el=2):
    """The grouped products of one expert layer's training step at the
    expected held assignments: the routed experts' gate, up and down, each
    forward, dX and dW (9), with the held experts' weights crossing HBM once
    a product and the sorted activations once in and once out (the shared
    expert is a dense feed-forward and no grouped product). A list of
    ``{"flops", "bytes"}``."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = int(tokens * expected_assignments_per_token(cfg))
    weights = held(cfg) * d * f * bytes_per_el
    one = {"flops": 2 * rows * d * f,
           "bytes": weights + rows * (d + f) * bytes_per_el}
    return [dict(one) for _ in range(9)]


def expert_layers(cfg):
    """The expert layers that run: those of the stack and the module's."""
    return layers(cfg).count("experts") + mtp_modules(cfg)
