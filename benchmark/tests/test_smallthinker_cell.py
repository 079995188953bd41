"""The yardstick's own tests of the ``smallthinker_lm`` family and its cell:
the pinned counts, the family's shapes against the program's symbol, and a
toy cell end to end on the CPU (``correct``; the fp8 control and the
clipped-expert fault not correct; the new metric readers).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import contextlib
import io
import json
import math

import pytest

import test_benchmark as tb
from test_benchmark import load

TOY = "benchmark/tests/data/manifest_toy_smallthinker.json"
CELL = "toy_smallthinker_train"


def cell_config():
    return load("configs", "smallthinker-21b-a3b.train.json")


# --- counts -------------------------------------------------------------------

def test_smallthinker_counts_are_pinned():
    from lib import counts_smallthinker as counts

    cfg = cell_config()
    assert counts.layers(cfg) == [(0, False), (4096, True), (4096, True),
                                  (4096, True)]
    assert counts.attn_matmul_params(cfg) == 20971520
    assert counts.router_params(cfg) == 163840
    assert counts.expert_params(cfg) == 5898240
    assert counts.layer_params(cfg) == 20971520 + 163840 + 5120 \
        + 16 * 5898240
    assert counts.params(cfg) == 656529920
    assert counts.expected_assignments_per_token(cfg) == 1.5
    assert counts.matmul_flops_per_token(cfg) == 434339840
    # a global layer's pairs and a window layer's, at 8192 tokens
    assert counts.band_pairs(8192, 0) == 8192 * 8193 // 2 == 33558528
    assert counts.band_pairs(8192, 4096) == 4096 * 4097 // 2 + 4096 * 4096 \
        == 25167872
    assert counts.band_pairs(4, 2) == 1 + 2 + 2 + 2
    assert counts.band_pairs(4, 9) == counts.band_pairs(4, 0) == 10
    attn = 4 * 3584 * (33558528 + 3 * 25167872)
    assert sum(counts.attn_flops(cfg, 8192, w)
               for w, _ in counts.layers(cfg)) == attn == 1563514896384
    assert counts.train_step_flops(cfg, 2, 8192) == 3 * 2 * (
        8192 * 434339840 + attn) == 30729761193984
    flash = counts.band_flash_calls(cfg, 2, 8192)
    one = 2 * 2 * 28 * 128
    assert [c["fwd"]["flops"] for c in flash] == [
        2 * one * 33558528] + [2 * one * 25167872] * 3
    assert flash[1]["bwd"]["flops"] == 5 * one * 25167872
    products = counts.expert_products(cfg, 2 * 8192)
    assert len(products) == 9
    assert products[0]["flops"] == 2 * 24576 * 2560 * 768
    assert products[0]["bytes"] == 2 * (16 * 2560 * 768
                                        + 24576 * (2560 + 768))


@pytest.mark.parametrize("config", ["tests/data/toy_smallthinker.json",
                                    "configs/smallthinker-21b-a3b.train.json"])
def test_smallthinker_family_shapes_are_the_symbols(config):
    """By shapes alone (nothing is allocated): the family's leaves are the
    symbol's, and their sum is the yardstick's parameter count."""
    import run as bench
    from lib import counts_smallthinker as counts

    cfg = load(*config.split("/"))
    fam = bench.load_module("families", "smallthinker_lm")
    sym = fam.symbol(cfg, True)
    data, label = fam.input_descs(cfg, {"batch": 2, "seq_len": 16})
    args, _, _ = sym.infer_shape(**{n: s for n, s, _ in data + label})
    got = {n: s for n, s in zip(sym.list_arguments(), args)
           if n not in ("data", "softmax_label")}
    assert got == fam.param_shapes(cfg)
    assert list(got) == list(fam.param_shapes(cfg))
    assert sum(math.prod(s) for s in got.values()) == counts.params(cfg)


def test_smallthinker_configuration_is_the_published_one():
    """Every number of the catalog row is in the file under its own key,
    but for the three that ``reduced`` lists."""
    cfg = cell_config()
    published = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384, "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
        "num_attention_heads": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_theta": 1500000,
        "sliding_window_size": 4096,
        "rope_layout": [0, 1, 1, 1] * 13,
        "sliding_window_layout": [0, 1, 1, 1] * 13}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers",
                              "moe_num_primary_experts_held", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["moe_num_primary_experts_held"],
            cfg["vocab_size"]) == (4, 16, 37984)
    assert cfg["published"] == {"num_hidden_layers": 52,
                                "moe_num_primary_experts": 64,
                                "vocab_size": 151936}
    assert cfg["deployment"] and cfg["assumed"] and cfg["departures"]
    manifest = load("..", "BENCHMARK.json")
    entry = {c["name"]: c for c in manifest["configs"]}[
        "smallthinker-21b-a3b.train"]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_manifest_lines_fit_the_manifest_rules():
    """A ``why``, a ``layer`` and a ``source`` are one printable line of at
    most 200 characters (a 205-character ``why`` refused this cell once)."""
    manifest = load("..", "BENCHMARK.json")
    lines = [entry[key]
             for group in ("configs", "workloads", "per_layer")
             for entry in manifest[group]
             for key in ("why", "layer", "source") if key in entry]
    assert any("smallthinker" in e["name"] for e in manifest["workloads"])
    for text in lines:
        assert 1 <= len(text) <= 200 and text.isprintable(), text


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
def test_smallthinker_timed_path_agrees_with_the_reference(seed):
    """GQA with a head wider than the model's share, a NoPE global layer
    and three window + RoPE layers, the router on the attention's input,
    the held experts' share: bfloat16 through ``simple_bind`` +
    ``make_train_step`` against the float32 reference."""
    result = run_toy(seed=seed)
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_mfu_pct", "setup_s"}


def _by_hand(what, seed=7):
    import run as bench

    fault = bench.load_module("tests", "fault_expert_clip")
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        fault.main(["--manifest", TOY, "--workload", CELL, "--seed",
                    str(seed), "--what", what], find=tb.fake_find)
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_smallthinker_control_comes_out_not_correct():
    """The reference in fp8, put in the program's place, fails a number."""
    result = _by_hand("control")
    assert not result["correct"], result["compared"]


def test_smallthinker_clipped_experts_come_out_not_correct():
    """An expert layer that clips each expert at its expected load (what a
    capacity factor of 1 does) under the timed path: not ``correct``."""
    result = _by_hand("fault")
    assert not result["correct"], result["compared"]


# --- the new metric readers ----------------------------------------------------

def _canned_run(cfg, traffic, op_seconds, runs=2):
    return {"cfg": cfg, "traffic": traffic, "steps": runs,
            "peaks": load("lib", "peaks.json")["TPU v5 lite"],
            "trace": {"programs": {"step": {"runs": runs}},
                      "op_seconds": op_seconds}}


def test_smallthinker_roofline_readers_on_a_canned_trace():
    import run as bench

    cfg = cell_config()
    traffic = load("traffic", "train_seq8192.json")
    flash = bench.load_module("metrics", "kernels.band_flash_roofline")
    experts = bench.load_module("metrics", "kernels.expert_product_roofline")
    peak = 197e12
    one = 2 * traffic["batch"] * 28 * 128
    flash_s = 7 * one * (33558528 + 3 * 25167872) / peak
    expert_s = 4 * 9 * 2 * (traffic["batch"] * 8192 * 1.5) * 2560 * 768 / peak
    # kernels that ran at exactly twice their floors, two steps traced; the
    # names as the chip's trace gives them: both kinds are Mosaic calls, the
    # Pallas kernels carry `kernel_metadata`, the compiler's grouped product
    # its tiling, and a fusion that reads their results names them too
    call = 'custom-call(%%x), custom_call_target="tpu_custom_call", ' \
           'frontend_attributes={%s}'
    flash_op, grouped_op = call % "kernel_metadata={}", call % (
        'mosaic_fusion_entry_point="true",ragged_dot_tiling="512,512,256"')
    run = _canned_run(cfg, traffic, {
        "%jvp_layer0_attn_.1 = " + flash_op: 3 * flash_s,
        "%transpose_jvp_layer0_attn__.2 = " + flash_op: flash_s,
        "%ragged-dot-none.4 = " + grouped_op: 4 * expert_s,
        "%fusion.9 = fusion(%ragged-dot-none.4, %jvp_layer0_attn_.1)": 1.0})
    assert flash.read(run) == pytest.approx(50.0)
    assert experts.read(run) == pytest.approx(50.0)
    # a program without such kernels, or a dense configuration: nothing
    assert experts.read(_canned_run(cfg, traffic, {"%fusion.9": 1.0})) is None
    dense = load("configs", "starcoder2-3b.train.json")
    assert flash.read(_canned_run(dense, traffic, {flash_op: 1.0})) is None
    assert experts.read(_canned_run(dense, traffic,
                                    {grouped_op: 1.0})) is None


def test_smallthinker_dispatch_rows_ratio_reads_the_step_span():
    """After a run of the toy cell the ring's ``executor.train_step``
    records carry the expert layers' static attributes; a program without
    them reads nothing."""
    import run as bench
    from mxnet_tpu import telemetry

    reader = bench.load_module("metrics", "moe.dispatch_rows_ratio")
    telemetry.drain_events()
    assert reader.read({}) is None
    run_toy(seed=3, seconds=0.5)
    # 256 tokens x 3 of 8 experts chosen x 4 held: 384 expected, and a
    # buffer of twice that, which here is the worst case too
    assert reader.read({}) == pytest.approx(768 / 384)
