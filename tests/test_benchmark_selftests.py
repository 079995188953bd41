"""The yardstick's own tests (``benchmark/tests``), run in tier-1.

``benchmark/`` is closed to edits, so its two test files are loaded from
where they are and their tests and fixtures re-exported here: hand counts
against ``lib/counts.py``, the trace reduction on a recorded trace, the
span readers on a canned ring, and the toy LM cell end to end (GQA, scalar
loss, ``simple_bind`` + ``make_train_step``) against its float32 reference,
its control and its planted faults.
"""
import importlib.util
import os
import sys

_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "benchmark", "tests")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_DIR, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # test_span_readers imports test_benchmark
    spec.loader.exec_module(mod)
    return mod


for _name in ("test_benchmark", "test_span_readers"):
    # tests, fixtures and the helpers they name
    globals().update({k: v for k, v in vars(_load(_name)).items()
                      if not k.startswith("_")})
