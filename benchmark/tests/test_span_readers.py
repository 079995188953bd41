"""The per-layer metrics that read the program's own spans, against a ring
written by hand (``data/ring_small.json``) and against the toy cell's ring.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

The canned ring: one ``executor.bind`` of 0.5 s that read two programs back
in 0.05 s; a first step of 1 s whose learning compile took 3.0 s of
``compile_s`` and whose dispatch read 0.25 s from the cache, with one
program-cache hit of 20 ms and one miss inside it; a second set-up step; and
a window of three steps of 1, 3 and 2 ms, the second of which compiled.
"""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)

BY_HAND = {
    "setup.bind_s": 0.5,
    "setup.trace_lower_s": 0.01 + 0.02 + 0.2 + 0.3,
    "setup.compile_s": 3.0,
    "setup.cache_read_s": 0.05 + 0.25 + 0.020,
    "setup.programs_built": 2 + 2 + 1,
    "step.host_dispatch_ms": 2.0,
    "step.compiles_in_window": 1,
}


@pytest.fixture
def canned(monkeypatch):
    from lib import spans

    with open(os.path.join(BENCH, "tests", "data", "ring_small.json")) as f:
        ring = [tuple(e) for e in json.load(f)]
    from mxnet_tpu import telemetry

    monkeypatch.setattr(telemetry, "drain_events", lambda clear=True: ring)
    return spans


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_reader_on_the_canned_ring(canned, name):
    import run as bench

    read = bench.load_module("metrics", name).read
    assert read({"steps": 3}) == pytest.approx(BY_HAND[name])
    # more steps in the window than the ring holds: nothing to read
    assert read({"steps": 6}) is None
    assert read({}) is None


def test_the_window_says_which_step_compiled(canned):
    assert canned.window_compiles({"steps": 3}) == [4]


def test_a_program_without_the_spans_reads_nothing(monkeypatch):
    """The parent commit of the PR that brought the spans, or
    MXNET_TELEMETRY=0: an empty ring, and every reader returns None."""
    import run as bench
    from mxnet_tpu import telemetry

    monkeypatch.setattr(telemetry, "drain_events", lambda clear=True: [])
    for name in BY_HAND:
        assert bench.load_module("metrics", name).read({"steps": 3}) is None


def test_readers_on_the_toy_cell():
    """The readers against the program itself: the toy cell on the CPU,
    then each reader on the ring the run left in this process."""
    import run as bench
    from mxnet_tpu import telemetry
    from test_benchmark import run_cell

    telemetry.reset()
    result = run_cell("toy_lm_train", seed=13, seconds=2)
    run = {"steps": result["attempted"]}
    got = {name: bench.load_module("metrics", name).read(run)
           for name in BY_HAND}
    assert all(v is not None for v in got.values()), got
    assert got["setup.bind_s"] > 0
    assert got["setup.trace_lower_s"] > 0
    assert got["setup.compile_s"] + got["setup.cache_read_s"] > 0
    assert got["setup.programs_built"] >= 1
    assert 0 < got["step.host_dispatch_ms"] < 1e3
    assert got["step.compiles_in_window"] == 0
