"""The yardstick's own tests of the ``lfm2_moe_lm`` family and its cell: the
pinned counts, the configuration against the published one, the family's
shapes against the program's symbol, and a toy cell end to end on the CPU
(``correct``; the fp8 control, reversed taps, a router that forgot its
bias, half a batch and an unchanged state not correct; the new metric
readers).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import contextlib
import io
import json
import math

import pytest

import test_benchmark as tb
from test_benchmark import load

TOY = "benchmark/tests/data/manifest_toy_lfm2.json"
CELL = "toy_lfm2_train"


def cell_config():
    return load("configs", "lfm2-24b-a2b.train.json")


# --- counts -------------------------------------------------------------------

def test_lfm2_counts_are_pinned():
    from lib import counts_lfm2 as counts

    cfg = cell_config()
    assert counts.head_dim(cfg) == 64 and counts.held(cfg) == 8
    assert counts.layers(cfg) == [
        ("conv", "dense"), ("full_attention", "experts"),
        ("conv", "experts"), ("conv", "experts"), ("conv", "experts")]
    assert counts.conv_params(cfg) == 16783360
    assert counts.attn_params(cfg) == 10485888
    assert counts.dense_ffn_params(cfg) == 72351744
    assert counts.router_params(cfg) == 131072
    assert counts.expert_params(cfg) == 9437184
    assert counts.layer_params(cfg, "conv", "dense") == 89139200
    assert counts.layer_params(cfg, "full_attention", "experts") == 86118528
    assert counts.layer_params(cfg, "conv", "experts") == 92416000
    assert counts.params(cfg) == 469284992
    assert counts.expected_assignments_per_token(cfg) == 0.5
    per_token = 2 * (4 * 4 * 2048 * 2048 + 10485760 + 72351744
                     + 4 * 131072 + 4 * 9437184 // 2 + 2048 * 8192)
    assert counts.matmul_flops_per_token(cfg) == per_token == 372244480
    assert counts.causal_pairs(8192) == 33558528
    assert counts.attn_flops(cfg, 8192) == 4 * 2048 * 33558528
    assert counts.train_step_flops(cfg, 2, 8192) == 3 * 2 * (
        8192 * per_token + 4 * 2048 * 33558528) == 19946029449216
    assert counts.train_step_flops(cfg, 1, 8192) == 9973014724608
    (flash,) = counts.flash_calls(cfg, 2, 8192)
    one = 2 * 2 * 32 * 64 * 33558528
    assert flash["fwd"]["flops"] == 2 * one
    assert flash["bwd"]["flops"] == 5 * one
    q, kv, row = 2 * 32 * 8192 * 64 * 2, 2 * 8 * 8192 * 64 * 2, 2 * 32 * 8192 * 4
    assert flash["fwd"]["bytes"] == 2 * q + 2 * kv + row
    products = counts.expert_products(cfg, 2 * 8192)
    assert len(products) == 9 and counts.expert_layers(cfg) == 4
    assert products[0]["flops"] == 2 * 8192 * 2048 * 1536
    assert products[0]["bytes"] == 2 * (8 * 2048 * 1536
                                        + 8192 * (2048 + 1536))
    passes = counts.short_conv_passes(cfg, 16384)
    act = 16384 * 2048 * 2
    assert counts.conv_layers(cfg) == 4
    assert passes == [{"flops": 0, "bytes": 4 * act},
                      {"flops": 0, "bytes": 7 * act}]
    with pytest.raises(ValueError, match="layers_run"):
        counts.layers(dict(cfg, num_hidden_layers=4))


@pytest.mark.parametrize("config", ["tests/data/toy_lfm2.json",
                                    "configs/lfm2-24b-a2b.train.json"])
def test_lfm2_family_shapes_are_the_symbols(config):
    """By shapes alone (nothing is allocated): the family's leaves are the
    symbol's, in its order, their sum the yardstick's parameter count, and
    its states the symbol's auxiliary states."""
    import run as bench
    from lib import counts_lfm2 as counts

    cfg = load(*config.split("/"))
    fam = bench.load_module("families", "lfm2_moe_lm")
    sym = fam.symbol(cfg, True)
    data, label = fam.input_descs(cfg, {"batch": 2, "seq_len": 16})
    args, _, aux = sym.infer_shape(**{n: s for n, s, _ in data + label})
    got = {n: s for n, s in zip(sym.list_arguments(), args)
           if n not in ("data", "softmax_label")}
    assert got == fam.param_shapes(cfg)
    assert list(got) == list(fam.param_shapes(cfg))
    assert sum(math.prod(s) for s in got.values()) == counts.params(cfg)
    assert dict(zip(sym.list_auxiliary_states(), aux)) \
        == fam.state_shapes(cfg)


def test_lfm2_configuration_is_the_published_one():
    """Every number of the catalog row is in the file under its own key,
    the nested group whole, but for the four that ``reduced`` lists."""
    cfg = cell_config()
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 64,
        "num_experts_per_tok": 4, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "layer_types": (["conv", "conv", "full_attention", "conv"] * 10)}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "num_experts_held", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_experts_held"], cfg["vocab_size"]) == (5, 1, 8, 8192)
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "num_dense_layers": 2, "num_experts": 64,
                                "vocab_size": 65536}
    assert cfg["layers_run"] == [1, 2, 3, 4, 5]
    assert cfg["deployment"] and cfg["assumed"] and cfg["departures"]
    assert cfg["dtype"]["expert_bias"].startswith("float32")
    assert cfg["device_bytes_reckoned"]["parameters"] == 469284992
    manifest = load("..", "BENCHMARK.json")
    entry = {c["name"]: c for c in manifest["configs"]}["lfm2-24b-a2b.train"]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    cell = {w["name"]: w for w in manifest["workloads"]}["lfm2_train_8k"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-24b-a2b.train", "train_seq8192_x2", 1)
    listed = {m["name"] for m in manifest["per_layer"]
              if "lfm2_train_8k" in m.get("workloads", ())}
    assert {"kernels.flash_d64_roofline", "kernels.short_conv_roofline",
            "kernels.lfm2_expert_product_roofline",
            "moe.dispatch_rows_ratio", "step.device_mfu_pct"} <= listed
    for group in ("configs", "workloads", "per_layer"):
        for e in manifest[group]:
            for key in ("why", "layer", "source"):
                assert 1 <= len(e.get(key, "x")) <= 200, e


# --- the toy cell end to end ---------------------------------------------------

def run_toy(seed=5, seconds=1.5, trace=0):
    import run as bench

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        bench.main(["--manifest", TOY, "--workload", CELL, "--seed",
                    str(seed), "--seconds", str(seconds), "--trace",
                    str(trace)], find=tb.fake_find)
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("seed", [11, 3000000019])
def test_lfm2_timed_path_agrees_with_the_reference(seed):
    """Short-convolution and attention layers, a dense SwiGLU layer and
    expert layers under the sigmoid-and-bias route, the held experts'
    share, the tied head, the bias seeded into the executor's auxiliary
    states: bfloat16 through ``simple_bind`` + ``make_train_step``, three
    steps against the float32 ``ref_train``."""
    result = run_toy(seed=seed)
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_mfu_pct", "setup_s"}


def _by_hand(what, seed=7):
    import run as bench

    fault = bench.load_module("tests", "fault_lfm2_planted")
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        fault.main(["--manifest", TOY, "--workload", CELL, "--seed",
                    str(seed), "--what", what], find=tb.fake_find)
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_lfm2_control_comes_out_not_correct():
    """The reference in fp8, put in the program's place, fails a number."""
    result = _by_hand("control")
    assert not result["correct"], result["compared"]


@pytest.mark.parametrize("what", ["taps", "bias", "half", "still"])
def test_lfm2_planted_faults_come_out_not_correct(what):
    """Under the timed path, a convolution that reads its taps in reverse,
    a router that chooses by the score alone (so the seeded bias reached
    the program, and decides), and the contract's two faults of any
    training cell, a step on half of its batch and a state left as it
    was: not ``correct``."""
    result = _by_hand(what)
    assert not result["correct"], result["compared"]
    if what == "still":
        assert result["numbers"]["change_norm_gap"] == pytest.approx(1.0)


# --- the new metric readers ----------------------------------------------------

def _canned_run(cfg, traffic, op_seconds, runs=2):
    return {"cfg": cfg, "traffic": traffic, "steps": runs,
            "peaks": load("lib", "peaks.json")["TPU v5 lite"],
            "trace": {"programs": {"step": {"runs": runs}},
                      "op_seconds": op_seconds}}


def test_lfm2_roofline_readers_on_a_canned_trace():
    """Kernels that ran at exactly twice their floors, two steps traced,
    the names as the chip's trace gives them: a Mosaic call is named for
    its kernel, and a fusion that reads its result names it among its
    operands only."""
    import run as bench

    cfg = cell_config()
    traffic = load("traffic", "train_seq8192_x2.json")
    b = traffic["batch"]
    flash = bench.load_module("metrics", "kernels.flash_d64_roofline")
    experts = bench.load_module("metrics",
                                "kernels.lfm2_expert_product_roofline")
    conv = bench.load_module("metrics", "kernels.short_conv_roofline")
    peak, hbm = 197e12, 819e9

    flash_s = 7 * 2 * b * 32 * 64 * 33558528 / peak
    expert_s = 4 * 9 * 2 * (b * 8192 * 0.5) * 2048 * 1536 / peak
    conv_s = 4 * 11 * b * 8192 * 2048 * 2 / hbm
    call = '%%%s = bf16[8]{0} custom-call(%%x), custom_call_target=' \
           '"tpu_custom_call", frontend_attributes={kernel_metadata={}}'
    node = '%%%s = bf16[8]{0} fusion(%%short_conv_fwd.3), kind=%s, ' \
           'metadata={op_name="jit(one_step)/%s/mul"}'
    run = _canned_run(cfg, traffic, {
        call % "jvp_layer1_attn_.1": 3 * flash_s,
        call % "transpose_jvp_layer1_attn__.2": flash_s,
        call % "expert_gmm.4": 3 * expert_s,
        call % "expert_tgmm.9": expert_s,
        call % "short_conv_fwd.3": 3 * conv_s,
        call % "short_conv_bwd.5": conv_s,
        # none of these is any of the three: readers of the kernels'
        # results (the op's own out projection among them), the
        # attention's projection
        node % ("fusion.7", "kOutput", "jvp(layer0_conv)"): 1.0,
        "%fusion.9 = bf16[8]{0} fusion(%expert_gmm.4, "
        "%jvp_layer1_attn_.1), kind=kLoop": 1.0,
        node % ("fusion.10", "kOutput", "jvp(layer1_q)"): 1.0})
    assert flash.read(run) == pytest.approx(50.0)
    assert experts.read(run) == pytest.approx(50.0)
    assert conv.read(run) == pytest.approx(50.0)
    # the compiler's grouped product, where the gate leaves it the layer
    run["trace"]["op_seconds"] = {
        "%ragged-dot-none.4 = bf16[8]{0} custom-call(%x)": 4 * expert_s}
    assert experts.read(run) == pytest.approx(50.0)
    assert flash.read(run) is None and conv.read(run) is None
    # another family's cell, or a parent without the kernels: nothing
    other = load("configs", "smallthinker-21b-a3b.train.json")
    for reader in (flash, experts, conv):
        assert reader.read(_canned_run(other, traffic, {
            call % "jvp_layer1_attn_.1": 1.0})) is None
        assert reader.read(_canned_run(cfg, traffic, {})) is None


def test_lfm2_dispatch_rows_ratio_reads_the_step_span():
    """After a run of the toy cell the accepted reader finds the expert
    layers' static attributes: 256 tokens x 2 of 8 experts chosen x 4
    held: 256 expected, and a buffer of the worst case, 512. (The cell's
    own: 8192 x 4 = 32768 rows over 4096 expected, 8.)"""
    import run as bench
    from mxnet_tpu import telemetry

    reader = bench.load_module("metrics", "moe.dispatch_rows_ratio")
    telemetry.drain_events()
    assert reader.read({}) is None
    run_toy(seed=3, seconds=0.5)
    assert reader.read({}) == pytest.approx(512 / 256)
