"""The yardstick's own tests of the ``ouro_lm`` family and its cell: the
pinned counts, the configuration against the published one, the family's
shapes against the program's symbol, and a toy cell end to end on the CPU
(``correct``; the fp8 control, one pass fewer, a next pass that reads the
un-normed state, the entropy term left out and an unchanged state not
correct; the two new metric readers).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import contextlib
import io
import json
import math

import pytest

import test_benchmark as tb
from test_benchmark import load

TOY = "benchmark/tests/data/manifest_toy_ouro.json"
CELL = "toy_ouro_train"


def cell_config():
    return load("configs", "ouro-2.6b.train.json")


# --- counts -------------------------------------------------------------------

def test_ouro_counts_are_pinned():
    """The issue's arithmetic at the cell's shapes: 51.39 M a layer, 406.9 M
    in all, and 2.718 GFLOP forward a token: 16 layer applications, 4
    heads, 3 gates and causal attention at 4096."""
    from lib import counts_ouro as counts

    cfg = cell_config()
    assert counts.head_dim(cfg) == 128 and counts.loops(cfg) == 4
    assert counts.applications(cfg) == 16
    assert counts.layer_matmul_params(cfg) == 4 * 2048 * 2048 \
        + 3 * 2048 * 5632 == 51380224
    assert counts.layer_params(cfg) == 51388416
    assert counts.params(cfg) == 4 * 51388416 + 2 * 49152 * 2048 + 2048 \
        + 2049 == 406884353
    per_token = 2 * (16 * 51380224 + 4 * 2048 * 49152 + 3 * 2048)
    assert counts.matmul_flops_per_token(cfg) == per_token == 2449485824
    assert counts.causal_pairs(4096) == 8390656
    assert counts.attn_flops(cfg, 4096) == 4 * 2048 * 8390656
    forward = counts.forward_flops(cfg, 4096)
    assert forward == 4096 * per_token + 16 * 4 * 2048 * 8390656
    assert forward / 4096 == pytest.approx(2.718e9, rel=1e-4)
    assert counts.train_step_flops(cfg, 1, 4096) == 3 * forward \
        == 33398621995008
    # the exits' share of the FLOPs: 30% here; at the published 48 layers
    # 3.4%, and 3.9% of the products alone
    heads = 2 * 4 * 2048 * 49152
    assert heads / (forward / 4096) == pytest.approx(0.296, abs=1e-3)
    deep = dict(cfg, num_hidden_layers=48)
    assert heads / (counts.forward_flops(deep, 4096) / 4096) \
        == pytest.approx(0.034, abs=1e-3)
    assert heads / counts.matmul_flops_per_token(deep) \
        == pytest.approx(0.039, abs=1e-3)


@pytest.mark.parametrize("config", ["tests/data/toy_ouro.json",
                                    "configs/ouro-2.6b.train.json"])
def test_ouro_family_shapes_are_the_symbols(config):
    """By shapes alone (nothing is allocated): the family's leaves are the
    symbol's, in its order, once each however many passes read them, and
    their sum the yardstick's parameter count."""
    import run as bench
    from lib import counts_ouro as counts

    cfg = load(*config.split("/"))
    fam = bench.load_module("families", "ouro_lm")
    sym = fam.symbol(cfg, True)
    data, label = fam.input_descs(cfg, {"batch": 1, "seq_len": 16})
    args, _, aux = sym.infer_shape(**{n: s for n, s, _ in data + label})
    got = {n: s for n, s in zip(sym.list_arguments(), args)
           if n not in ("data", "softmax_label")}
    assert got == fam.param_shapes(cfg)
    assert list(got) == list(fam.param_shapes(cfg))
    assert sum(math.prod(s) for s in got.values()) == counts.params(cfg)
    assert aux == []
    loss = [n for n in sym._nodes()
            if not n.is_var and n.op.name == "LoopExitLoss"]
    assert [n.attrs["num_exits"] for n in loss] == [counts.loops(cfg)]


def test_ouro_configuration_is_the_published_one():
    """Every number of the catalog row is in the file under its own key,
    the layer_types whole, but for ``num_hidden_layers``, which
    ``reduced`` lists."""
    cfg = cell_config()
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152,
        "layer_types": ["full_attention"] * 48}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 4
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert cfg["layers_run"] == [0, 1, 2, 3]
    assert cfg["deployment"] and cfg["assumed"] and cfg["departures"]
    assert cfg["exit_entropy_beta"] == 0.1
    assert cfg["device_bytes_reckoned"]["parameters"] == 406884353
    manifest = load("..", "BENCHMARK.json")
    entry = {c["name"]: c for c in manifest["configs"]}["ouro-2.6b.train"]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    cell = {w["name"]: w for w in manifest["workloads"]}["ouro_train_4k"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro-2.6b.train", "train_seq4096_x1", 1)
    traffic = load("traffic", "train_seq4096_x1.json")
    assert (traffic["batch"], traffic["seq_len"], traffic["feed_batches"],
            traffic["ref_steps"], traffic["trace_seconds"]) == (
        1, 4096, 4, 3, 5.0)
    assert set(traffic["limits_why"]) == set(traffic["limits"])
    listed = {m["name"] for m in manifest["per_layer"]
              if "ouro_train_4k" in m.get("workloads", ())}
    assert {"loop.layer_applications", "loop.exit_objective_ms",
            "step.device_mfu_pct", "step.ms.head_loss",
            "step.program_temp_gb"} <= listed
    assert not {"moe.dispatch_rows_ratio", "step.ms.expert_products",
                "step.ms.short_conv"} & listed
    (mfu,) = [m for m in manifest["end_to_end"]
              if m["name"] == "train_mfu_pct"]
    assert "ouro_train_4k" in mfu["workloads"]


# --- the toy cell end to end ---------------------------------------------------

def run_toy(seed=5, seconds=0.5, trace=0):
    import run as bench

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        bench.main(["--manifest", TOY, "--workload", CELL, "--seed",
                    str(seed), "--seconds", str(seconds), "--trace",
                    str(trace)], find=tb.fake_find)
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("seed", [11, 3000000019])
def test_ouro_timed_path_agrees_with_the_reference(seed):
    """Two layers run three times with one set of leaves, the sandwich
    norms, an exit after every pass and the exits' objective: bfloat16
    through ``simple_bind`` + ``make_train_step``, three steps against the
    float32 ``ref_train``."""
    result = run_toy(seed=seed)
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_mfu_pct", "setup_s"}


def _by_hand(what, seed=7):
    import run as bench

    fault = bench.load_module("tests", "fault_ouro_planted")
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        fault.main(["--manifest", TOY, "--workload", CELL, "--seed",
                    str(seed), "--what", what, "--seconds", "0.5"],
                   find=tb.fake_find)
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_ouro_control_comes_out_not_correct():
    """The reference in fp8, put in the program's place, fails a number."""
    result = _by_hand("control")
    assert not result["correct"], result["compared"]


@pytest.mark.parametrize("what", ["passes", "carry", "entropy", "still"])
def test_ouro_planted_faults_come_out_not_correct(what):
    """Under the timed path, a stack run one pass fewer, a next pass that
    reads the state before the final norm, the exits' objective without
    its entropy term, and the contract's fault of any training cell, a
    state left as it was: not ``correct``."""
    result = _by_hand(what)
    assert not result["correct"], result["compared"]
    if what == "still":
        assert result["numbers"]["change_norm_gap"] == pytest.approx(1.0)


# --- the new metric readers ----------------------------------------------------

def test_ouro_layer_applications_reads_the_step_span():
    """After a run of the toy cell the reader finds the loop's static
    attributes: 2 layers held, each run 3 times a step."""
    import run as bench
    from mxnet_tpu import telemetry

    reader = bench.load_module("metrics", "loop.layer_applications")
    telemetry.drain_events()
    assert reader.read({}) is None
    run_toy(seed=3, seconds=0.3)
    assert reader.read({}) == 3.0


def test_ouro_exit_objective_reads_the_loss_nodes_operations(monkeypatch):
    """A record of the toy's own graph and a trace written by hand: the
    operations traced from the ``LoopExitLoss`` node, forward and
    backward, and none of the heads' or the gates'; nothing to read
    without a record, a run of the step or such a node."""
    import run as bench
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry.programs import graph_nodes

    reader = bench.load_module("metrics", "loop.exit_objective_ms")
    cfg = load("tests", "data", "toy_ouro.json")
    nodes = graph_nodes(bench.load_module("families", "ouro_lm").symbol(
        cfg, True))
    ops = [{"name": "fusion.%d" % i, "opcode": "fusion", "kernel": False,
            "node": n} for i, n in enumerate(nodes)]
    mine = {o["name"] for o in ops if o["node"] == "exit_loss"}
    assert len(mine) == 1
    ops.append({"name": "fusion.900", "opcode": "fusion", "kernel": False,
                "node": "exit_loss"})  # the backward's
    seconds = {"%%%s = f32[8]{0} fusion(%%p)" % o["name"]: 1e-3 for o in ops}
    run = {"trace": {"op_seconds": seconds,
                     "programs": {"step": {"runs": 2}}}}
    rec = {"program": "train_step", "ops": ops, "nodes": nodes}
    monkeypatch.setattr(telemetry, "programs", lambda: [rec])
    assert reader.read(run) == pytest.approx(1e3 * 2e-3 / 2)
    assert reader.read({"trace": None}) is None
    monkeypatch.setattr(telemetry, "programs", lambda: [dict(rec, ops=None)])
    assert reader.read(run) is None
    other = {n: v for n, v in nodes.items() if v["op"] != "LoopExitLoss"}
    monkeypatch.setattr(telemetry, "programs",
                        lambda: [dict(rec, nodes=other)])
    assert reader.read(run) is None
    monkeypatch.delattr(telemetry, "programs")  # a program without records
    assert reader.read(run) is None
