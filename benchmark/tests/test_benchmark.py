"""The benchmark's own tests: run by hand and in rehearsal, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Not part of tier-1 (the driver runs ``tests/``). They hold the yardstick to
hand counts and a recorded trace, each family's timed path to its plain
reference at a toy size, the control to coming out not correct, and a run
with the timed path broken underneath to ``correct`` false.
"""
import contextlib
import io
import json
import math
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)
TOY = "benchmark/tests/data/manifest_toy.json"


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


# --- the trace reduction ------------------------------------------------------

def test_trace_reduction_on_the_recorded_trace():
    from lib import trace_reduce

    trace = load("tests", "data", "trace_small.json")
    red = trace_reduce.reduce(trace, program_patterns={"step": ["one_step"]},
                              align="step")
    # three whole steps of 400 us, 1000 us apart; 350 us busy in each
    assert red["window_s"] == pytest.approx(2400e-6)
    assert red["busy_s"] == pytest.approx(3 * 350e-6)
    step = red["programs"]["step"]
    assert step["runs"] == 3
    assert step["busy_s"] == pytest.approx([350e-6] * 3)
    assert step["gap_after_s"] == pytest.approx([600e-6] * 2)
    # the umbrella is not an operation; the kernel is found by name
    assert not any("while" in n for n in red["op_seconds"])
    flash = sum(v for n, v in red["op_seconds"].items() if "flash" in n)
    assert flash == pytest.approx(3 * 150e-6)
    gaps = dict(red["breakdown"]["idle_gaps"])
    # two gaps between steps and the 50 us hole inside each of three steps,
    # all under the host's wait for the loss (50 us counts as a long gap)
    assert gaps["bench.read_loss"] == pytest.approx(2 * 600e-6 + 3 * 50e-6)
    assert gaps["shorter_gaps"] == pytest.approx(0.0)
    assert red["breakdown"]["device_ops"][0][1] == pytest.approx(3 * 150e-6)


def test_interval_arithmetic():
    from lib import trace_reduce as t

    merged = t.union([[5, 7], [0, 2], [1, 3], [7, 8]])
    assert merged == [[0, 3], [5, 8]]
    assert t.total(t.clip(merged, 2, 6)) == 2
    assert t.gaps(merged, 0, 10) == [[3, 5], [8, 10]]


# --- FLOP and byte counts against a hand count ---------------------------------

def test_lm_counts_by_hand():
    from lib import counts

    cfg = load("configs", "starcoder2-3b.train.json")
    d, f, dkv = 3072, 12288, 2 * 128
    layer_mm = d * d + 2 * d * dkv + d * d + 2 * d * f
    assert counts.lm_layer_matmul_params(cfg) == layer_mm == 95944704
    assert counts.lm_layer_params(cfg) == layer_mm + f + d + 4 * d
    full = dict(cfg, num_hidden_layers=30)
    assert counts.lm_params(full) == pytest.approx(3.18e9, rel=0.01)
    # causal attention over 4 positions: 1 + 2 + 3 + 4 pairs, two products
    assert counts.attn_flops(cfg, 4, 4, True) == 2 * 2 * 24 * 128 * 10
    # one query against 100 positions up to its own
    assert counts.attn_flops(cfg, 1, 100, True) == 2 * 2 * 24 * 128 * 100
    # depth 4: 8192 tokens a step, forward and backward
    per_token = 2 * (4 * layer_mm + d * 49152)
    assert counts.lm_train_step_flops(cfg, 2, 4096) == 3 * 2 * (
        4096 * per_token + 4 * 2 * 2 * 24 * 128 * (4096 * 4097 // 2))
    calls = counts.flash_calls(cfg, 2, 4096)
    one = 2 * 2 * 24 * 128 * (4096 * 4097 // 2)
    assert [calls[k]["flops"] for k in ("fwd", "dq", "dkv")] == \
        [2 * one, 3 * one, 4 * one]


@pytest.mark.parametrize("config, family", [
    ("tests/data/toy_lm.json", "transformer_lm"),
    ("configs/starcoder2-3b.train.json", "transformer_lm")])
def test_family_shapes_are_the_symbols(config, family):
    import run as bench

    cfg = load(*config.split("/"))
    fam = bench.load_module("families", family)
    traffic = {"batch": 2, "seq_len": 16}
    sym = fam.symbol(cfg, True)
    data, label = fam.input_descs(cfg, traffic)
    args, _, _ = sym.infer_shape(**{n: s for n, s, _ in data + label})
    got = {n: s for n, s in zip(sym.list_arguments(), args)
           if n not in ("data", "softmax_label")}
    assert got == fam.param_shapes(cfg)
    # and the count of parameters is the yardstick's
    from lib import counts

    assert sum(math.prod(s) for s in got.values()) == counts.lm_params(cfg)


# --- a whole run on the CPU, the look for a chip skipped ----------------------

def fake_find(chips):
    import jax

    return jax.devices()[:chips], load("lib", "peaks.json")["TPU v5 lite"]


def run_cell(cell, seed=5, seconds=2, trace=0):
    import run as bench

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        bench.main(["--manifest", TOY, "--workload", cell, "--seed",
                    str(seed), "--seconds", str(seconds), "--trace",
                    str(trace)], find=fake_find)
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("seed", [11, 3000000019])
def test_timed_path_agrees_with_the_reference(seed):
    result = run_cell("toy_lm_train", seed=seed, seconds=3)
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert list(result)[-1] == "compared"
    assert "setup_s" in result["metrics"]


def _train_numbers(cell, low):
    import run as bench

    manifest = load("tests", "data", "manifest_toy.json")
    w = {c["name"]: c for c in manifest["workloads"]}[cell]
    cfg = json.load(open(os.path.join(ROOT, {
        c["name"]: c for c in manifest["configs"]}[w["config"]]["file"])))
    tr = load(w["traffic"] + ".json")
    fam = bench.load_module("families", cfg["family"])
    drv = bench.load_module("drivers", tr["driver"])
    ref = fam.ref_train(cfg, tr, 7, tr["ref_steps"])
    ctl = fam.ref_train(cfg, tr, 7, tr["ref_steps"], low=low)
    return drv.compare(ctl, ref, tr["limits"])[0]


def test_training_control_comes_out_not_correct():
    """The reference in fp8, put in the program's place, fails a number."""
    checks = _train_numbers("toy_lm_train", low=True)
    assert any(v > limit for _, v, limit in checks), checks
    same = _train_numbers("toy_lm_train", low=False)
    assert all(v <= limit for _, v, limit in same), same


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_training_faults_come_out_not_correct(fault, monkeypatch):
    import run as bench

    drv = bench.load_module("drivers", "train_steps")
    real = drv.Trainer.step

    def state_unchanged(self, feed):
        params, states = self.params, self.states
        keep_p = {n: a + 0 for n, a in params.items()}
        keep_s = {n: a + 0 for n, a in states.items()}
        outs = real(self, feed)
        self.params, self.states = keep_p, keep_s
        return outs

    def half_batch(self, feed):
        import jax.numpy as jnp

        half = {n: jnp.concatenate([a[:a.shape[0] // 2]] * 2)
                for n, a in feed.items()}  # the mean over the first half
        return real(self, half)

    broken = {"state_unchanged": state_unchanged, "half_batch": half_batch}
    real_load = bench.load_module

    def load_module(kind, name):
        mod = real_load(kind, name)
        if kind == "drivers" and name == "train_steps":
            monkeypatch.setattr(mod.Trainer, "step", broken[fault])
        return mod

    monkeypatch.setattr(bench, "load_module", load_module)
    result = run_cell("toy_lm_train")
    assert not result["correct"], result["compared"]
