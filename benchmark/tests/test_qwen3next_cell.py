"""The yardstick's own tests of the ``qwen3_next_lm`` family and its cell:
the pinned counts, the configuration against the published one, the family's
shapes against the program's symbol, and a toy cell end to end on the CPU
(``correct``; the fp8 control, the rule without its decay, without its
write strength, with its state reset every chunk and with q and k
unnormed, the attention and the shared expert without their gates, and an
unchanged state not correct; the two new metric readers).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import contextlib
import io
import json
import math

import pytest

import test_benchmark as tb
from test_benchmark import load

QWEN3NEXT_TOY = "benchmark/tests/data/manifest_toy_qwen3next.json"
QWEN3NEXT_CELL = "toy_qwen3next_train"


def qwen3next_config():
    return load("configs", "qwen3-next-80b-a3b.train.json")


# --- counts ------------------------------------------------------------------

def test_qwen3next_counts_are_pinned():
    """The configuration's counts at the cell's shapes: 424,340,544
    parameters (16 experts held); 3.77 TFLOP forward and 11.32 a step of
    one 8192-token sequence, the Gated DeltaNet layers 48% of it, full
    attention 26%, the expert layers 9% and the head 17%."""
    from lib import counts_qwen3next as counts

    cfg = qwen3next_config()
    assert counts.held(cfg) == 16
    assert counts.layers(cfg) == ["gdn"] * 3 + ["attention"]
    assert counts.gdn_matmul_params(cfg) == 2048 * 12288 + 2048 * 64 \
        + 4096 * 2048 == 33685504
    assert counts.gdn_params(cfg) == 33685504 + 8192 * 4 + 64 + 128
    assert counts.attn_params(cfg) == 27263488
    assert counts.layer_params(cfg, "gdn") == 33718464 + 4096 + 1048576 \
        + 16 * 3145728 + 3147776
    assert counts.params(cfg) == 424340544
    assert counts.expected_assignments_per_token(cfg) == 0.3125
    # the core at a chunk of 64: 3 C^2 dk + 2 C^2 dv + 3 C dk dv + C^3 / 6
    # multiply-adds a chunk of a value head
    per_chunk = 3 * 64 * 64 * 128 + 2 * 64 * 64 * 128 + 3 * 64 * 128 * 128 \
        + 64 ** 3 / 6
    assert counts.gdn_core_macs(cfg, 8192) == 32 * 128 * per_chunk
    parts = counts.part_flops(cfg, 8192)
    forward = counts.forward_flops(cfg, 8192)
    assert forward == sum(parts.values())
    assert forward / 1e12 == pytest.approx(3.7717, abs=5e-5)
    assert counts.train_step_flops(cfg, 1, 8192) == int(3 * forward)
    share = {k: v / forward for k, v in parts.items()}
    assert share == pytest.approx({"gdn": 0.477, "attention": 0.264,
                                   "experts": 0.090, "head": 0.169},
                                  abs=1e-3)
    calls = counts.gated_delta_calls(cfg, 1, 8192)
    assert len(calls) == 3
    qk, v, gb = 2 * 8192 * 2048 * 2, 8192 * 4096 * 2, 2 * 8192 * 32 * 4
    assert calls[0]["fwd"] == {"flops": 2 * counts.gdn_core_macs(cfg, 8192),
                               "bytes": qk + 2 * v + gb}
    assert calls[0]["bwd"]["flops"] == 2 * calls[0]["fwd"]["flops"]
    assert calls[0]["bwd"]["bytes"] == 2 * qk + 3 * v + 2 * gb
    with pytest.raises(ValueError, match="layers_run"):
        counts.layers(dict(cfg, num_hidden_layers=8))


@pytest.mark.parametrize("config", ["tests/data/toy_qwen3next.json",
                                    "configs/qwen3-next-80b-a3b.train.json"])
def test_qwen3next_family_shapes_are_the_symbols(config):
    """By shapes alone (nothing is allocated): the family's leaves are the
    symbol's, in its order, and their sum the yardstick's parameter
    count."""
    import run as bench
    from lib import counts_qwen3next as counts

    cfg = load(*config.split("/"))
    fam = bench.load_module("families", "qwen3_next_lm")
    sym = fam.symbol(cfg, True)
    data, label = fam.input_descs(cfg, {"batch": 1, "seq_len": 64})
    args, _, aux = sym.infer_shape(**{n: s for n, s, _ in data + label})
    got = {n: s for n, s in zip(sym.list_arguments(), args)
           if n not in ("data", "softmax_label")}
    assert got == fam.param_shapes(cfg)
    assert list(got) == list(fam.param_shapes(cfg))
    assert sum(math.prod(s) for s in got.values()) == counts.params(cfg)
    assert not sym.list_auxiliary_states()


def test_qwen3next_configuration_is_the_published_one():
    """Every number of the catalog row is in the file under its own key but
    for the two that ``reduced`` lists beside the held experts' key; the
    floors hold; the manifest names the cell and its metrics."""
    cfg = qwen3next_config()
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts_held"],
            cfg["vocab_size"]) == (4, 16, 151936 // 8)
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                "vocab_size": 151936}
    assert cfg["layers_run"] == [0, 1, 2, 3]
    assert cfg["deployment"] and cfg["assumed"] and cfg["departures"]
    assert cfg["device_bytes_reckoned"]["parameters"] == 424340544
    manifest = load("..", "BENCHMARK.json")
    entry = {c["name"]: c for c in manifest["configs"]}[
        "qwen3-next-80b-a3b.train"]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    cell = {w["name"]: w for w in manifest["workloads"]}["qwen3next_train_8k"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3-next-80b-a3b.train", "train_seq8192_qwen3next", 1)
    traffic = load("traffic", "train_seq8192_qwen3next.json")
    assert (traffic["batch"], traffic["seq_len"], traffic["ref_steps"]) == (
        1, 8192, 3)
    assert traffic["optimizer"] == {"learning_rate": 0.01, "momentum": 0.9}
    assert set(traffic["limits_why"]) == set(traffic["limits"])
    listed = {m["name"] for m in manifest["per_layer"]
              if "qwen3next_train_8k" in m.get("workloads", ())}
    assert {"gdn.mixer_ms", "kernels.gated_delta_roofline",
            "moe.dispatch_rows_ratio", "step.ms.expert_products",
            "step.device_mfu_pct", "step.program_temp_gb",
            "device.idle_pct.train", "setup.compile_s"} <= listed
    assert not {"step.ms.short_conv", "kernels.flash_roofline",
                "mla.latent_ms"} & listed
    (mfu,) = [m for m in manifest["end_to_end"]
              if m["name"] == "train_mfu_pct"]
    assert "qwen3next_train_8k" in mfu["workloads"]


# --- the toy cell end to end -------------------------------------------------

def qwen3next_run_toy(seed=5, seconds=0.5, trace=0):
    import run as bench

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        bench.main(["--manifest", QWEN3NEXT_TOY, "--workload",
                    QWEN3NEXT_CELL, "--seed", str(seed), "--seconds",
                    str(seconds), "--trace", str(trace)], find=tb.fake_find)
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("seed", [11, 3000000019])
def test_qwen3next_timed_path_agrees_with_the_reference(seed):
    """Gated DeltaNet layers and a gated attention layer, expert layers with
    a gated shared expert under the softmax route and the held experts'
    share: bfloat16 through ``simple_bind`` + ``make_train_step``, three
    steps against the float32 ``ref_train`` whose rule runs token by
    token."""
    result = qwen3next_run_toy(seed=seed)
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_mfu_pct", "setup_s"}


def _qwen3next_by_hand(what, seed=7):
    import run as bench

    fault = bench.load_module("tests", "fault_qwen3next_planted")
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        fault.main(["--manifest", QWEN3NEXT_TOY, "--workload",
                    QWEN3NEXT_CELL, "--seed", str(seed), "--what", what,
                    "--seconds", "0.5"], find=tb.fake_find)
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_qwen3next_control_comes_out_not_correct():
    """The reference in fp8, put in the program's place, fails a number."""
    result = _qwen3next_by_hand("control")
    assert not result["correct"], result["compared"]


@pytest.mark.parametrize("what", ["no_decay", "no_beta", "chunk_reset",
                                  "qk_unnormed", "attn_ungated",
                                  "shared_ungated", "still"])
def test_qwen3next_planted_faults_come_out_not_correct(what):
    """Under the timed path, the rule without its decay, without its write
    strength, with its state reset every chunk (the toy's 128 tokens are two
    chunks of 64, its A drawn under 0.5, ``a_log_max``, so that a head's
    state outlives a chunk: at the published draw up to 16 most heads
    forget within a few tokens), with q and k unnormed; the attention's and
    the shared expert's outputs without their sigmoid gates; and the
    contract's fault of any training cell, a state left as it was: not
    ``correct``."""
    result = _qwen3next_by_hand(what)
    assert not result["correct"], result["compared"]
    if what == "still":
        assert result["numbers"]["change_norm_gap"] == pytest.approx(1.0)


# --- the new metric readers --------------------------------------------------

def _qwen3next_canned(monkeypatch, node_of):
    """A record of the toy's own graph (one operation a node, one more for
    each node ``node_of`` names: its backward's) and a trace of them, a
    millisecond each, two steps traced."""
    import run as bench
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry.programs import graph_nodes

    cfg = load("tests", "data", "toy_qwen3next.json")
    nodes = graph_nodes(bench.load_module("families", "qwen3_next_lm")
                        .symbol(cfg, True))
    ops = [{"name": "fusion.%d" % i, "opcode": "fusion", "kernel": False,
            "node": n} for i, n in enumerate(nodes)]
    ops += [dict(op, name="fusion.%d" % (900 + i)) for i, op in enumerate(ops)
            if node_of(op["node"])]
    seconds = {"%%%s = f32[8]{0} fusion(%%p)" % o["name"]: 1e-3 for o in ops}
    run = {"cfg": cfg,
           "traffic": load("tests", "data", "toy_train_qwen3next.json"),
           "peaks": load("lib", "peaks.json")["TPU v5 lite"],
           "trace": {"op_seconds": seconds,
                     "programs": {"step": {"runs": 2}}}}
    rec = {"program": "train_step", "ops": ops, "nodes": nodes}
    monkeypatch.setattr(telemetry, "programs", lambda: [rec])
    return run, rec, nodes


def test_qwen3next_mixer_ms_reads_the_gdn_nodes(monkeypatch):
    """The Gated DeltaNet nodes (ten a layer: the q|k|v, b|a and z
    projections, the core, the per-head norm and two reshapes around it,
    silu(z), the gate's product and the out projection, in three layers),
    forward and backward; nothing without a record, a run of the step or
    such a node."""
    import run as bench
    from mxnet_tpu import telemetry

    reader = bench.load_module("metrics", "gdn.mixer_ms")
    picked = lambda n: "_gdn_" in n  # noqa: E731
    run, rec, nodes = _qwen3next_canned(monkeypatch, picked)
    mine = [n for n in nodes if picked(n)]
    assert len(mine) == 10 * 3
    assert reader.read(run) == pytest.approx(2 * len(mine) * 1e3 * 1e-3 / 2)
    assert reader.read({"trace": None}) is None
    monkeypatch.setattr(telemetry, "programs", lambda: [dict(rec, ops=None)])
    assert reader.read(run) is None
    other = [dict(op, node="layer0_ffn1") for op in rec["ops"]]
    monkeypatch.setattr(telemetry, "programs", lambda: [dict(rec, ops=other)])
    assert reader.read(run) is None


def test_qwen3next_gated_delta_roofline_by_own_names():
    """The rule's three kernels by their own names in each of the toy's
    three Gated DeltaNet layers; a fusion that only reads a kernel's
    result, another family's cell or a trace without the kernels gives
    nothing."""
    import run as bench
    from lib import counts_qwen3next as counts

    reader = bench.load_module("metrics", "kernels.gated_delta_roofline")
    cfg = load("tests", "data", "toy_qwen3next.json")
    tr = dict(load("tests", "data", "toy_train_qwen3next.json"),
              kernels={"gated_delta": ["gated_delta_fwd",
                                       "gated_delta_states",
                                       "gated_delta_bwd"]})
    peaks = load("lib", "peaks.json")["TPU v5 lite"]
    call = '%%%s = bf16[8]{0} custom-call(%%x), custom_call_target=' \
           '"tpu_custom_call"'
    seconds = {call % "gated_delta_fwd.3": 1e-3,
               call % "gated_delta_states.4": 1e-3,
               call % "gated_delta_bwd.5": 2e-3,
               "%fusion.9 = bf16[8]{0} fusion(%gated_delta_fwd.3)": 5.0}
    run = {"cfg": cfg, "traffic": tr, "peaks": peaks,
           "trace": {"op_seconds": seconds,
                     "programs": {"step": {"runs": 2}}}}
    calls = counts.gated_delta_calls(cfg, tr["batch"], tr["seq_len"])
    assert len(calls) == 3
    least = sum(max(c["flops"] / peaks["bf16_flops_per_s"],
                    c["bytes"] / peaks["hbm_bytes_per_s"])
                for layer in calls for c in layer.values())
    assert reader.read(run) == pytest.approx(100 * least * 2 / 4e-3)
    assert reader.read(dict(run, cfg=load(
        "configs", "lfm2-24b-a2b.train.json"))) is None
    run["trace"]["op_seconds"] = {}
    assert reader.read(run) is None


def test_qwen3next_dispatch_rows_ratio_reads_the_step_span():
    """After a run of the toy cell the accepted reader finds the expert
    layers' static attributes: 128 tokens x 4 of 16 experts chosen x 8
    held: 256 expected, a buffer of the worst case, 512. (The cell's own:
    8192 x 10 = 81920 rows over 2560 expected, 32.)"""
    import run as bench
    from mxnet_tpu import telemetry

    reader = bench.load_module("metrics", "moe.dispatch_rows_ratio")
    telemetry.drain_events()
    assert reader.read({}) is None
    qwen3next_run_toy(seed=3, seconds=0.3)
    assert reader.read({}) == pytest.approx(512 / 256)
