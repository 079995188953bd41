"""The ``step.ms.*`` metrics and ``step.program_temp_gb`` against a record
and a trace written by hand, for each toy cell's own graph.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

The record's ``nodes`` are the toy configuration's symbol's (as the program
lists them); its ``ops`` are made here: one fusion a graph node, a Mosaic
call under every attention and convolution node, a grouped kernel and a
``ragged-dot`` for every expert layer, two updates traced outside every
node. The trace holds them all and one asynchronous copy the record lacks.
Which group each belongs to is decided HERE by the node's name, where
``lib/groups.py`` goes by operators and neighbours.
"""
import json
import os
import re
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)

# toy manifest -> the cell of BENCHMARK.json it is the toy of
CELLS = {"manifest_toy": "lm_train_4k",
         "manifest_toy_smallthinker": "smallthinker_train_8k",
         "manifest_toy_lfm2": "lfm2_train_8k"}
BY_NAME = [(r"_(q|k|v|o|attn)$", "attention_rest"),
           (r"_(ffn\d|gelu|silu|glu)$", "feed_forward"),
           (r"_experts$", "expert_moves"), (r"_conv$", "short_conv"),
           (r"^embed$", "embedding"), (r"(^|_)ln[f\d]?$", "rest"),
           (r"^broadcast_add\d+$", "rest")]
RUNS = 2


def _by_hand(node):
    return next((g for pat, g in BY_NAME if re.search(pat, node)),
                "head_loss")


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _made(toy):
    """(record, op_seconds, {group: seconds} by hand)."""
    import run as bench
    from mxnet_tpu.telemetry.programs import graph_nodes

    manifest = bench.load_json(BENCH, "tests", "data", toy + ".json")
    cfg = bench.load_json(ROOT, manifest["configs"][0]["file"])
    sym = bench.load_module("families", cfg["family"]).symbol(cfg, True)
    nodes = graph_nodes(sym)
    ops, want = [], []

    def op(name, node, group, kernel=False, opcode="fusion"):
        ops.append({"name": name, "opcode": opcode, "kernel": kernel,
                    "node": node})
        want.append(group)

    for i, (node, n) in enumerate(nodes.items()):
        op("fusion.%d" % i, node, _by_hand(node))
        if n["op"] == "MultiHeadAttention":
            op("jvp_%s_.%d" % (node, i), node, "flash", True, "custom-call")
        elif n["op"] == "ShortConv":
            op("short_conv_fwd.%d" % i, node, "short_conv", True,
               "custom-call")
        elif n["op"] == "ExpertFFN":
            op("expert_gmm.%d" % i, node, "expert_products", True,
               "custom-call")
            op("ragged-dot.%d" % i, "", "expert_products",
               opcode="ragged-dot")
    op("fusion.9000", "", "update")
    op("convert_fusion", "", "update")
    seconds, by_hand = {}, {}
    for i, (o, group) in enumerate(zip(ops, want)):
        line = "%%%s = f32[8,%d]{1,0} %s(%%fusion.9000, %%expert_gmm.1)" % (
            o["name"], i, o["opcode"])
        seconds[line] = 1e-3 * (i + 1)
        by_hand[group] = by_hand.get(group, 0.0) + seconds[line]
    seconds["%copy-start.3 = (f32[4], u32[]) copy-start(%fusion.1)"] = 0.5
    by_hand["unattributed"] = 0.5
    rec = {"program": "train_step", "step": 1, "build": 7, "ops": ops,
           "nodes": nodes, "layers": [], "read_s": {"text": 0, "parse": 0},
           "memory": {"argument": 8, "output": 8, "alias": 8,
                      "temp": 5670000000, "generated_code": 0,
                      "uncast_table_bytes": 0}}
    return rec, seconds, by_hand


def _run(seconds):
    return {"trace": {"op_seconds": seconds, "programs": {
        "step": {"runs": RUNS, "busy_s": [1.0] * RUNS, "gap_after_s": [0]}}}}


def _read(name, run):
    import run as bench

    return bench.load_module("metrics", name).read(run)


@pytest.mark.parametrize("toy", sorted(CELLS))
def test_every_operation_lands_in_exactly_one_metric(toy, monkeypatch):
    from lib import groups, programs
    from mxnet_tpu import telemetry

    rec, seconds, by_hand = _made(toy)
    monkeypatch.setattr(telemetry, "programs", lambda: [{"program": "x"},
                                                        rec])
    total, where = programs.group_seconds(seconds, rec)
    assert set(total) == set(groups.GROUPS) and len(where) == len(seconds)
    assert where["copy-start.3"] == "unattributed"
    metrics = {m["name"]: m for m in _manifest()["per_layer"]
               if m["name"].startswith("step.ms.")}
    assert sorted(metrics) == sorted("step.ms." + g for g in groups.GROUPS)
    got = {n: _read(n, _run(seconds)) for n in metrics}
    for name, value in got.items():
        want = by_hand.get(name[len("step.ms."):], 0.0)
        assert value == pytest.approx(1e3 * want / RUNS), name
        # the manifest lists the metric for the cells whose graph has it
        assert (value > 0) == (CELLS[toy] in metrics[name]["workloads"]), name
    assert sum(got.values()) == pytest.approx(
        1e3 * sum(seconds.values()) / RUNS)
    assert _read("step.program_temp_gb", {}) == pytest.approx(5.67)


@pytest.mark.parametrize("toy", sorted(CELLS))
def test_nothing_to_read_is_none(toy, monkeypatch):
    """No record (a parent commit, ``MXNET_TELEMETRY=0``), a record of a
    plainly jitted step, a run without a trace or without a run of the
    step: None, and the harness leaves the metric out."""
    from mxnet_tpu import telemetry

    rec, seconds, _ = _made(toy)
    names = [m["name"] for m in _manifest()["per_layer"]
             if m["name"].startswith("step.ms.")]
    plain = dict(rec, ops=None, memory=dict(rec["memory"], temp=None))
    no_step = _run(seconds)
    no_step["trace"]["programs"] = {}
    for found, run in (([], _run(seconds)), ([plain], _run(seconds)),
                       ([rec], {"trace": None}), ([rec], no_step)):
        monkeypatch.setattr(telemetry, "programs", lambda found=found: found)
        assert [_read(n, run) for n in names] == [None] * len(names)
    monkeypatch.setattr(telemetry, "programs", lambda: [plain])
    assert _read("step.program_temp_gb", {}) is None
    monkeypatch.delattr(telemetry, "programs")  # a program without records
    assert _read("step.ms.flash", _run(seconds)) is None
    assert _read("step.program_temp_gb", {}) is None
