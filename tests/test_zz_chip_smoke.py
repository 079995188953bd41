"""chip_smoke.py on the CPU: it refuses to run, and its phases pass tiny.

The file sorts last on purpose. The tier-1 command stops at a time limit and
counts the passes it has seen by then, and the suite already overruns that
limit, so a new file must not push existing tests past the cut.
"""
import os
import subprocess
import sys

import mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(
    d_model=128, heads=4, kv_heads=2, ffn=256, layers=1, vocab=128,
    batch=4, seq=64, steps=3,
    prompts=(5, 12, 20), prefill_buckets=(8, 16, 32), new_tokens=6,
    slots=2, block_tokens=8,
    flash_batch=2, stream_seq=0, head64_seqs=(256,), lstm_n=8, lstm_h=128, expert_rows=512,
    expert_ffn=128, interpret=True)


def test_refuses_without_a_chip():
    """Non-zero, before any model is built, naming the platform it found,
    and no verdict on standard output."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert "platform is 'cpu'" in proc.stderr, proc.stderr[-2000:]
    assert proc.stdout == ""


def test_phases_pass_tiny_on_the_cpu_mesh(tmp_path):
    meter = chip_smoke.CompileMeter()
    mx.analysis.compile_witness.enable(True)
    try:
        prefix = str(tmp_path / "lm")
        chip_smoke.phase_kernels(TINY, mx.cpu(0))
        one = chip_smoke.phase_train(TINY, mx.cpu(0), prefix, meter)
        assert one["pallas_calls"] == 0  # no chip: the XLA attention
        arms = chip_smoke.phase_serve(TINY, prefix, meter)
        assert arms["slab"]["steps"] > 0 and arms["paged"]["steps"] > 0
        four = chip_smoke.phase_multichip(
            TINY, [mx.cpu(i) for i in range(4)], prefix, one["losses"],
            meter)
        assert four["sharded_params"] > 0
        assert four["decode_replicas_per_device"].startswith("refused")
    finally:
        mx.analysis.compile_witness.enable(False)
        mx.analysis.compile_witness.reset()
