"""The yardstick's own tests of the ``glm_moe_lite_lm`` family and its cell:
the pinned counts, the configuration against the published one, the family's
shapes against the program's symbol, and a toy cell end to end on the CPU
(``correct``; the fp8 control, RoPE over whole heads, an un-normed kv latent,
an unshifted MTP input, the routed experts unscaled and an unchanged state
not correct; the three new metric readers).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import contextlib
import io
import json
import math

import pytest

import test_benchmark as tb
from test_benchmark import load

TOY = "benchmark/tests/data/manifest_toy_glm.json"
CELL = "toy_glm_train"


def cell_config():
    return load("configs", "glm-4.7-flash.train.json")


# --- counts ------------------------------------------------------------------

def test_glm_counts_are_pinned():
    """The configuration's counts at the cell's shapes: 706,518,528
    parameters; 3.92 TFLOP forward and 11.76 a step of one 4096-token
    sequence, latent attention 54% of it."""
    from lib import counts_glm as counts

    cfg = cell_config()
    assert counts.head_dim(cfg) == 256 and counts.held(cfg) == 8
    assert counts.layers(cfg) == ["dense"] + ["experts"] * 4
    assert counts.mla_params(cfg) == 21759232
    assert counts.layer_params(cfg, "dense") == 84677888
    assert counts.layer_params(cfg, "experts") == 106829056
    assert counts.mtp_params(cfg) == 115223808
    assert counts.params(cfg) == 84677888 + 4 * 106829056 + 115223808 \
        + 79300608 == 706518528
    assert counts.expected_assignments_per_token(cfg) == 0.5
    g = 1e9 / 4096  # GFLOP over a sequence, a token's FLOPs at a time
    assert 2 * counts.mla_matmul_params(cfg) / g == pytest.approx(178.2,
                                                                  abs=0.05)
    assert counts.attn_flops(cfg, 4096) / 1e9 == pytest.approx(171.8,
                                                               abs=0.05)
    forward = counts.forward_flops(cfg, 4096)
    assert forward / 1e12 == pytest.approx(3.919, abs=5e-4)
    assert counts.train_step_flops(cfg, 1, 4096) == 3 * forward
    assert counts.train_step_flops(cfg, 1, 4096) / 1e12 == pytest.approx(
        11.76, abs=5e-3)
    mla = (4096 * counts.mla_flops_per_token(cfg)
           + counts.attention_layers(cfg) * counts.attn_flops(cfg, 4096))
    assert mla / forward == pytest.approx(0.536, abs=1e-3)
    calls = counts.flash_calls(cfg, 1, 4096)
    one = 2 * 20 * 256 * counts.causal_pairs(4096)
    assert len(calls) == 6
    assert calls[0]["fwd"]["flops"] == 2 * one
    assert calls[0]["bwd"]["flops"] == 5 * one
    q = 20 * 4096 * 256 * 2
    assert calls[0]["fwd"]["bytes"] == 4 * q + 20 * 4096 * 4
    assert counts.expert_layers(cfg) == 5
    products = counts.expert_products(cfg, 4096)
    assert len(products) == 9
    assert products[0]["flops"] == 2 * 2048 * 2048 * 1536
    assert products[0]["bytes"] == (8 * 2048 * 1536 + 2048 * 3584) * 2
    with pytest.raises(ValueError, match="layers_run"):
        counts.layers(dict(cfg, num_hidden_layers=4))


@pytest.mark.parametrize("config", ["tests/data/toy_glm.json",
                                    "configs/glm-4.7-flash.train.json"])
def test_glm_family_shapes_are_the_symbols(config):
    """By shapes alone (nothing is allocated): the family's leaves are the
    symbol's, in its order, their sum the yardstick's parameter count, and
    its states the symbol's auxiliary states."""
    import run as bench
    from lib import counts_glm as counts

    cfg = load(*config.split("/"))
    fam = bench.load_module("families", "glm_moe_lite_lm")
    sym = fam.symbol(cfg, True)
    data, label = fam.input_descs(cfg, {"batch": 1, "seq_len": 16})
    args, _, aux = sym.infer_shape(**{n: s for n, s, _ in data + label})
    got = {n: s for n, s in zip(sym.list_arguments(), args)
           if n not in ("data", "softmax_label")}
    assert got == fam.param_shapes(cfg)
    assert list(got) == list(fam.param_shapes(cfg))
    assert sum(math.prod(s) for s in got.values()) == counts.params(cfg)
    assert dict(zip(sym.list_auxiliary_states(), aux)) \
        == fam.state_shapes(cfg)


def test_glm_configuration_is_the_published_one():
    """Every number of the catalog row is in the file under its own key but
    for the two that ``reduced`` lists beside the held experts' key; the
    floors hold; the manifest names the cell and its metrics."""
    cfg = cell_config()
    published = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_key_value_heads": 20,
        "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts_held",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts_held"],
            cfg["vocab_size"]) == (5, 8, 154880 // 8)
    assert cfg["published"] == {"num_hidden_layers": 47,
                                "n_routed_experts": 64, "vocab_size": 154880}
    assert cfg["layers_run"] == [0, 1, 2, 3, 4]
    assert cfg["deployment"] and cfg["assumed"] and cfg["departures"]
    assert cfg["mtp_loss_weight"] == 0.3
    assert cfg["device_bytes_reckoned"]["parameters"] == 706518528
    manifest = load("..", "BENCHMARK.json")
    entry = {c["name"]: c for c in manifest["configs"]}["glm-4.7-flash.train"]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    cell = {w["name"]: w for w in manifest["workloads"]}["glm_flash_train_4k"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm-4.7-flash.train", "train_seq4096_glm", 1)
    traffic = load("traffic", "train_seq4096_glm.json")
    assert (traffic["batch"], traffic["seq_len"], traffic["ref_steps"]) == (
        1, 4096, 3)
    assert set(traffic["limits_why"]) == set(traffic["limits"])
    listed = {m["name"] for m in manifest["per_layer"]
              if "glm_flash_train_4k" in m.get("workloads", ())}
    assert {"kernels.flash_d256_roofline", "mla.latent_ms", "mtp.module_ms",
            "kernels.glm_expert_product_roofline",
            "moe.dispatch_rows_ratio", "step.ms.expert_products",
            "step.device_mfu_pct", "step.program_temp_gb"} <= listed
    assert not {"step.ms.short_conv", "kernels.flash_roofline",
                "loop.exit_objective_ms"} & listed
    (mfu,) = [m for m in manifest["end_to_end"]
              if m["name"] == "train_mfu_pct"]
    assert "glm_flash_train_4k" in mfu["workloads"]


# --- the toy cell end to end -------------------------------------------------

def run_toy(seed=5, seconds=0.5, trace=0):
    import run as bench

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        bench.main(["--manifest", TOY, "--workload", CELL, "--seed",
                    str(seed), "--seconds", str(seconds), "--trace",
                    str(trace)], find=tb.fake_find)
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("seed", [11, 3000000019])
def test_glm_timed_path_agrees_with_the_reference(seed):
    """Latent attention, a dense SwiGLU layer, expert layers with a shared
    expert under the sigmoid-and-bias route, the held experts' share and
    the multi-token-prediction module with its objective: bfloat16 through
    ``simple_bind`` + ``make_train_step``, three steps against the float32
    ``ref_train``."""
    result = run_toy(seed=seed)
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_mfu_pct", "setup_s"}


def _by_hand(what, seed=7):
    import run as bench

    fault = bench.load_module("tests", "fault_glm_planted")
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        fault.main(["--manifest", TOY, "--workload", CELL, "--seed",
                    str(seed), "--what", what, "--seconds", "0.5"],
                   find=tb.fake_find)
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_glm_control_comes_out_not_correct():
    """The reference in fp8, put in the program's place, fails a number."""
    result = _by_hand("control")
    assert not result["correct"], result["compared"]


@pytest.mark.parametrize("what", ["rope_all", "kv_unnormed", "mtp_unshifted",
                                  "scale1", "still"])
def test_glm_planted_faults_come_out_not_correct(what):
    """Under the timed path, RoPE over whole heads, a kv latent scaled but
    not normed, an MTP module that embeds the token at i instead of the
    next, routed experts weighed by 1.0 instead of 1.8, and the contract's
    fault of any training cell, a state left as it was: not
    ``correct``."""
    result = _by_hand(what)
    assert not result["correct"], result["compared"]
    if what == "still":
        assert result["numbers"]["change_norm_gap"] == pytest.approx(1.0)


# --- the new metric readers --------------------------------------------------

def _canned(monkeypatch, node_of):
    """A record of the toy's own graph (one operation a node, one more for
    each node ``node_of`` names: its backward's) and a trace of them, a
    millisecond each, two steps traced."""
    import run as bench
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry.programs import graph_nodes

    cfg = load("tests", "data", "toy_glm.json")
    nodes = graph_nodes(bench.load_module("families", "glm_moe_lite_lm")
                        .symbol(cfg, True))
    ops = [{"name": "fusion.%d" % i, "opcode": "fusion",
            "kernel": nodes[n]["op"] == "MultiHeadAttention", "node": n}
           for i, n in enumerate(nodes)]
    ops += [dict(op, name="fusion.%d" % (900 + i)) for i, op in enumerate(ops)
            if node_of(op["node"])]
    seconds = {"%%%s = f32[8]{0} fusion(%%p)" % o["name"]: 1e-3 for o in ops}
    run = {"cfg": cfg, "traffic": load("tests", "data", "toy_train_glm.json"),
           "peaks": load("lib", "peaks.json")["TPU v5 lite"],
           "trace": {"op_seconds": seconds,
                     "programs": {"step": {"runs": 2}}}}
    rec = {"program": "train_step", "ops": ops, "nodes": nodes}
    monkeypatch.setattr(telemetry, "programs", lambda: [rec])
    return run, rec, nodes


@pytest.mark.parametrize("metric,picked,count", [
    ("mla.latent_ms", lambda n: "_mla_" in n, 8 * 4),
    ("mtp.module_ms", lambda n: n.startswith("mtp_"), None)])
def test_glm_node_metrics_read_their_nodes_operations(monkeypatch, metric,
                                                      picked, count):
    """The latent projections' nodes (eight a layer: two down- and two
    up-projections, two norms, the slice and the key's assembly, in three
    layers and the module's), and the module's nodes, forward and backward;
    nothing without a record, a run of the step or such a node."""
    import run as bench
    from mxnet_tpu import telemetry

    reader = bench.load_module("metrics", metric)
    run, rec, nodes = _canned(monkeypatch, picked)
    mine = [n for n in nodes if picked(n)]
    if count is not None:
        assert len(mine) == count
    assert reader.read(run) == pytest.approx(2 * len(mine) * 1e3 * 1e-3 / 2)
    assert reader.read({"trace": None}) is None
    monkeypatch.setattr(telemetry, "programs", lambda: [dict(rec, ops=None)])
    assert reader.read(run) is None
    other = [dict(op, node="layer0_ffn1") for op in rec["ops"]]
    monkeypatch.setattr(telemetry, "programs", lambda: [dict(rec, ops=other)])
    assert reader.read(run) is None
    monkeypatch.delattr(telemetry, "programs")  # a program without records
    assert reader.read(run) is None


def test_glm_flash_roofline_by_the_record_and_by_own_names(monkeypatch):
    """The Mosaic calls under the four attention nodes, by the record; by
    their own names where there is no record; another family's cell or a
    trace without them gives nothing."""
    import run as bench
    from lib import counts_glm as counts
    from mxnet_tpu import telemetry

    reader = bench.load_module("metrics", "kernels.flash_d256_roofline")
    run, rec, nodes = _canned(monkeypatch, lambda n: False)
    cfg, tr = run["cfg"], run["traffic"]
    peaks = run["peaks"]
    least = sum(max(c["flops"] / peaks["bf16_flops_per_s"],
                    c["bytes"] / peaks["hbm_bytes_per_s"])
                for layer in counts.flash_calls(cfg, 1, tr["seq_len"])
                for c in layer.values())
    kernels = [op for op in rec["ops"] if op["kernel"]]
    assert len(kernels) == 4
    assert reader.read(run) == pytest.approx(100 * least * 2 / 4e-3)
    monkeypatch.delattr(telemetry, "programs")
    call = '%%%s = bf16[8]{0} custom-call(%%x), custom_call_target=' \
           '"tpu_custom_call"'
    run["trace"]["op_seconds"] = {call % "jvp_layer1_attn_.1": 1e-3,
                                  call % "transpose_jvp_layer1_attn__.2":
                                  3e-3,
                                  "%fusion.9 = bf16[8]{0} fusion("
                                  "%jvp_layer1_attn_.1)": 5.0}
    run["traffic"] = dict(tr, kernels={"flash": ["_attn_"]})
    assert reader.read(run) == pytest.approx(100 * least * 2 / 4e-3)
    run["trace"]["op_seconds"] = {}
    assert reader.read(run) is None
    other = dict(run, cfg=load("configs", "lfm2-24b-a2b.train.json"))
    assert reader.read(other) is None


def test_glm_expert_product_roofline_by_own_names():
    """The routed experts' grouped kernels by their own names, nine products
    in each of the toy's two expert layers and the module's; a fusion that
    only reads a kernel's result, another family's cell or a trace without
    the kernels gives nothing."""
    import run as bench
    from lib import counts_glm as counts

    reader = bench.load_module("metrics", "kernels.glm_expert_product_roofline")
    cfg = load("tests", "data", "toy_glm.json")
    tr = dict(load("tests", "data", "toy_train_glm.json"),
              kernels={"experts": ["expert_gmm", "expert_tgmm",
                                   "ragged-dot"]})
    peaks = load("lib", "peaks.json")["TPU v5 lite"]
    call = '%%%s = bf16[8]{0} custom-call(%%x), custom_call_target=' \
           '"tpu_custom_call"'
    seconds = {call % "expert_gmm.3": 1e-3, call % "expert_tgmm.4": 3e-3,
               "%fusion.9 = bf16[8]{0} fusion(%expert_gmm.3)": 5.0}
    run = {"cfg": cfg, "traffic": tr, "peaks": peaks,
           "trace": {"op_seconds": seconds,
                     "programs": {"step": {"runs": 2}}}}
    assert counts.expert_layers(cfg) == 3
    least = 3 * sum(max(c["flops"] / peaks["bf16_flops_per_s"],
                        c["bytes"] / peaks["hbm_bytes_per_s"])
                    for c in counts.expert_products(
                        cfg, tr["batch"] * tr["seq_len"]))
    assert reader.read(run) == pytest.approx(100 * least * 2 / 4e-3)
    assert reader.read(dict(run, cfg=load(
        "configs", "lfm2-24b-a2b.train.json"))) is None
    run["trace"]["op_seconds"] = {}
    assert reader.read(run) is None


def test_glm_dispatch_rows_ratio_reads_the_step_span():
    """After a run of the toy cell the accepted reader finds the expert
    layers' static attributes: 32 tokens x 2 of 8 experts chosen x 4
    held: 32 expected, a buffer of the worst case, 64. (The cell's own:
    4096 x 4 = 16384 rows over 2048 expected, 8.)"""
    import run as bench
    from mxnet_tpu import telemetry

    reader = bench.load_module("metrics", "moe.dispatch_rows_ratio")
    telemetry.drain_events()
    assert reader.read({}) is None
    run_toy(seed=3, seconds=0.3)
    assert reader.read({}) == pytest.approx(64 / 32)
