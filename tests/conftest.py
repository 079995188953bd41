"""Test configuration: force an 8-device virtual CPU mesh BEFORE jax backend
init — the analogue of the reference's multi-device-without-hardware trick
(tests/python/unittest/test_multi_device_exec.py binds cpu(0..N), SURVEY §4.3).
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Tests are strictly the virtual 8-device CPU mesh, whatever the machine
# holds; the chip is reached through chip_smoke.py and benchmark/run.py.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


# --- quick/full tiers (reference unittest-vs-nightly split, SURVEY §4) -----
# `-m "not slow"` is the default quick tier (ci/run_tests.sh); `--full` (or
# `-m ""`) runs everything. The exhaustive registry sweeps dominate suite
# wall-time (~10 of 17 min) and are nightly-class: completeness GATES stay
# quick so an uncovered op still fails fast.
import pytest  # noqa: E402

_SLOW_FILES = {
    "test_operator_gradients.py": {"test_numeric_gradient"},
    "test_operator_exhaustive.py": None,  # whole file
    "test_consistency.py": {"test_bf16_consistency_grad_ops",
                            "test_bf16_consistency_forward_ops",
                            "test_bf16_consistency_loss_ops"},
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        fname = os.path.basename(str(item.fspath))
        rule = _SLOW_FILES.get(fname, "absent")
        if rule == "absent":
            continue
        if rule is None or item.function.__name__ in rule:
            item.add_marker(pytest.mark.slow)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: exhaustive registry sweeps (nightly tier; "
        "run with ci/run_tests.sh --full)")
    config.addinivalue_line(
        "markers", "parallel: multi-device tests that need the simulated "
        "8-device CPU mesh (this conftest forces it; ci/run_tests.sh runs "
        "them both inside the quick tier and as a dedicated stage)")
