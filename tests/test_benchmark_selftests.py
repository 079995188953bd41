"""The yardstick's own tests (``benchmark/tests``), run in tier-1.

``benchmark/`` is closed to edits, so its test files are loaded from
where they are and their tests and fixtures re-exported here: hand counts
against ``lib/counts.py``, the trace reduction on a recorded trace, the
span readers on a canned ring, and the toy LM cell end to end (GQA, scalar
loss, ``simple_bind`` + ``make_train_step``) against its float32 reference,
its control and its planted faults; and the same for the toy SmallThinker
cell (window and NoPE layers, the held experts' share), with its pinned
counts and its metric readers; and for the toy LFM2 cell (short-convolution
and attention layers, a dense SwiGLU layer, the sigmoid-and-bias route, the
tied head); and for the toy Ouro cell (layers run several times with one
set of weights, the sandwich norms, the exits' objective); and for the
toy GLM cell (latent attention, a shared expert, the multi-token-prediction
module); and for the toy Qwen3-Next cell (Gated DeltaNet layers, gated
attention, a gated shared expert); and the ``step.ms.*`` metrics on a
hand-made record and trace for each toy cell's graph
(``test_program_groups``).
"""
import importlib.util
import os
import sys

import pytest

_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "benchmark", "tests")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_DIR, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # test_span_readers imports test_benchmark
    spec.loader.exec_module(mod)
    return mod


for _name in ("test_benchmark", "test_span_readers",
              "test_smallthinker_cell", "test_lfm2_cell", "test_ouro_cell",
              "test_glm_cell", "test_qwen3next_cell",
              "test_program_groups"):
    # tests, fixtures and the helpers they name
    globals().update({k: v for k, v in vars(_load(_name)).items()
                      if not k.startswith("_")})


@pytest.fixture(autouse=True)
def _compile_cache_as_found():
    """``run.py`` turns JAX's persistent compilation cache on for its
    process (``setup_cache``), and a cell run here runs in the tests' own:
    put the settings back, or the worker's later tests read programs from
    the checkout's ``.jax_cache`` and count their compiles wrong
    (``tests/test_progcache.py``)."""
    import jax
    from jax._src import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    found = {n: getattr(jax.config, n) for n in names}
    yield
    if any(getattr(jax.config, n) != v for n, v in found.items()):
        for n, v in found.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()
