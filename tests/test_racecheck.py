"""Engine happens-before sanitizer (dynamic) + racecheck static pass.

Static half: mxnet_tpu.analysis.racecheck flags undeclared-var-access,
unfenced-host-read, and var-use-after-delete on the known-bad fixtures
while the shipped tree stays clean (test_analysis covers the baseline
gate). Dynamic half: MXNET_ENGINE_SANITIZER / engine.sanitizer_enable()
shadow-tracks per-var access epochs at push time.
"""
import os
import subprocess
import sys
import time

import pytest

from mxnet_tpu import engine
from mxnet_tpu import analysis
from mxnet_tpu.analysis.__main__ import main as cli_main
from mxnet_tpu.resilience import faults

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures", "analysis")


def fixture(name):
    return os.path.join(FIXTURES, name)


# --- static half: the three rule fixtures ------------------------------------
def test_undeclared_var_access_fixture():
    fs = analysis.run_analysis(fixture("undeclared_var_access.py"),
                               checks=("racecheck",))
    hits = [f for f in fs if f.rule == "undeclared-var-access"]
    assert len(hits) == 6
    flagged = {f.qualname.split(":")[-1] for f in hits}
    # each bad site is paired against BOTH prior conflicting sites
    assert flagged == {"bad_direct", "bad_interprocedural", "bad_alias"}
    # both sites are named: the report carries the partner site
    assert all("owner_site" in f.subject or "clean_shared_var" in f.subject
               for f in hits)
    # the interprocedural-only catch: the write is inside `helper`
    assert any(f.qualname.endswith("bad_interprocedural") for f in hits)
    # the shared-var counterpart is never the reported site
    assert all("clean_shared_var" not in f.qualname for f in fs)
    assert all("owner_site" not in f.qualname for f in fs)


def test_unfenced_host_read_fixture():
    fs = analysis.run_analysis(fixture("unfenced_host_read.py"),
                               checks=("racecheck",))
    hits = [f for f in fs if f.rule == "unfenced-host-read"]
    flagged = {f.qualname.split(".")[-1] for f in hits}
    # direct AND one-call-deep push resolved; fenced variants clean
    assert flagged == {"bad_read", "bad_read_interproc"}
    assert all("clean_read" not in f.qualname for f in fs)


def test_var_use_after_delete_fixture():
    fs = analysis.run_analysis(fixture("var_use_after_delete.py"),
                               checks=("racecheck",))
    hits = [f for f in fs if f.rule == "var-use-after-delete"]
    flagged = {f.qualname.split(":")[-1] for f in hits}
    assert flagged == {"bad_push_after_delete", "bad_fence_after_delete"}
    # rebinding to a fresh var resets the record
    assert all("clean_recreate" not in f.qualname for f in fs)


def test_cli_gate_fails_on_racecheck_fixtures():
    for fx in ("undeclared_var_access.py", "unfenced_host_read.py",
               "var_use_after_delete.py"):
        assert cli_main(["--root", fixture(fx), "--baseline", "none",
                         "--fail-on-new"]) == 1, fx


# --- dynamic half: the sanitizer ---------------------------------------------
@pytest.fixture
def san():
    engine.sanitizer_enable(True)
    yield
    engine.sanitizer_enable(False)


def reports(rule=None):
    out = engine.sanitizer_reports()
    return [r for r in out if rule is None or r["rule"] == rule]


def test_undeclared_write_write_race_names_both_sites(san):
    res = []
    v = engine.new_variable()
    engine.guard_state(res, v, "res")
    engine.push(lambda: res.append(1), mutable_vars=[v], name="owner")
    other = engine.new_variable()
    engine.push(lambda: res.append(2), mutable_vars=[other], name="intruder")
    engine.wait_for_all()
    (r,) = reports("undeclared-var-access")
    assert r["op"] == "intruder" and r["other_op"] == "owner"
    # both push sites resolve to THIS file, and the stack is captured
    assert r["site"].startswith("test_racecheck.py:")
    assert r["other_site"].startswith("test_racecheck.py:")
    assert "test_racecheck" in r["stack"]
    assert r["var"] == int(v)


def test_undeclared_read_of_written_state_is_a_race(san):
    res = []
    v = engine.new_variable()
    engine.guard_state(res, v)
    engine.push(lambda: res.append(1), mutable_vars=[v], name="w")
    other = engine.new_variable()
    engine.push(lambda: len(res), const_vars=[other], name="r")
    engine.wait_for_all()
    (r,) = reports("undeclared-var-access")
    assert r["op"] == "r" and r["other_op"] == "w"


def test_interprocedural_only_race_through_helper(san):
    # the guarded state is reachable ONLY through a captured helper one
    # call level deep — the scan must walk into the helper's closure
    stash = {"n": 0}
    v = engine.new_variable()
    engine.guard_state(stash, v, "stash")
    engine.push(lambda: stash.update(n=1), mutable_vars=[v], name="owner")

    def helper():
        stash["n"] += 1

    other = engine.new_variable()
    engine.push(lambda: helper(), mutable_vars=[other], name="deep")
    engine.wait_for_all()
    (r,) = reports("undeclared-var-access")
    assert r["op"] == "deep" and "stash" in r["detail"]


def test_reverse_order_undeclared_then_declared(san):
    res = []
    v = engine.new_variable()
    engine.guard_state(res, v)
    other = engine.new_variable()
    engine.push(lambda: res.append(1), mutable_vars=[other], name="sneak")
    engine.push(lambda: res.append(2), mutable_vars=[v], name="owner")
    engine.wait_for_all()
    (r,) = reports("undeclared-var-access")
    assert r["op"] == "owner" and r["other_op"] == "sneak"


def test_bound_method_instance_state_is_reachable(san):
    class Box:
        def __init__(self):
            self.items = []
            self.var = engine.new_variable()
            engine.guard_state(self.items, self.var, "Box.items")

        def add(self):
            self.items.append(1)

    b = Box()
    engine.push(b.add, mutable_vars=[b.var], name="ok_add")
    other = engine.new_variable()
    engine.push(b.add, mutable_vars=[other], name="bad_add")
    engine.wait_for_all()
    (r,) = reports("undeclared-var-access")
    assert r["op"] == "bad_add" and r["other_op"] == "ok_add"


def test_fence_ordered_pair_is_not_reported(san):
    res = []
    v = engine.new_variable()
    engine.guard_state(res, v)
    engine.push(lambda: res.append(1), mutable_vars=[v], name="a")
    engine.fence([v], name="order").wait(30)
    other = engine.new_variable()
    engine.push(lambda: res.append(2), mutable_vars=[other], name="b")
    engine.wait_for_all()
    assert reports() == []


def test_shared_declared_var_orders_the_pair(san):
    # b skips the guard var but shares w with a: the engine orders them
    res = []
    v, w = engine.new_variable(), engine.new_variable()
    engine.guard_state(res, v)
    engine.push(lambda: res.append(1), mutable_vars=[v, w], name="a")
    engine.push(lambda: res.append(2), mutable_vars=[w], name="b")
    engine.wait_for_all()
    assert reports() == []


def test_wait_for_var_is_a_sync_point(san):
    res = []
    v = engine.new_variable()
    engine.guard_state(res, v)
    engine.push(lambda: res.append(1), mutable_vars=[v], name="a")
    engine.wait_for_var(v)
    other = engine.new_variable()
    engine.push(lambda: res.append(2), mutable_vars=[other], name="b")
    engine.wait_for_all()
    assert reports() == []


def test_use_after_delete_push_and_fence(san):
    v = engine.new_variable()
    engine.delete_variable(v)
    engine.push(lambda: None, const_vars=[v], name="late_push")
    engine.fence([v], name="late_fence").wait(30)
    engine.wait_for_all()
    rs = reports("var-use-after-delete")
    assert {r["op"] for r in rs} == {"late_push", "late_fence"}
    assert all(r["other_op"] == "delete_variable" for r in rs)


def test_fresh_var_resets_the_shadow_record(san):
    v = engine.new_variable()
    engine.delete_variable(v)
    v2 = engine.new_variable()  # native ids are monotonic; python ids reset
    engine.push(lambda: None, const_vars=[v2], name="ok")
    engine.wait_for_all()
    assert reports("var-use-after-delete") == [] or int(v2) != int(v)


# --- composition & switches --------------------------------------------------
def test_sanitizer_composes_with_fault_plan(san):
    faults.install("engine_error op=san_fault nth=1")
    try:
        fired = faults.faults_injected()
        engine.push(lambda: faults.maybe_raise("san_fault:x"),
                    name="san_fault")
        engine.wait_for_all()
        assert faults.faults_injected() == fired + 1
        # the injected op error is NOT a race, and the engine still runs
        assert reports() == []
        v = engine.new_variable()
        done = []
        engine.push(lambda: done.append(1), mutable_vars=[v], name="after")
        engine.fence([v]).wait(30)
        assert done == [1]
        engine.delete_variable(v)
    finally:
        faults.clear()


def test_disabled_is_default_and_inert():
    assert not engine.sanitizer_enabled()
    assert engine.sanitizer_reports() == []
    obj = []
    assert engine.guard_state(obj, 1) is obj  # no-op, returns the object
    engine.unguard_state(obj)
    engine.push(lambda: None, name="noop")
    engine.wait_for_all()
    assert engine.sanitizer_reports() == []


def test_clear_drops_reports_but_keeps_guards(san):
    res = []
    v = engine.new_variable()
    engine.guard_state(res, v)
    engine.push(lambda: res.append(1), mutable_vars=[v], name="a")
    other = engine.new_variable()
    engine.push(lambda: res.append(2), mutable_vars=[other], name="b")
    engine.wait_for_all()
    assert len(reports()) == 1
    engine.sanitizer_clear()
    assert reports() == []
    # the guard itself survives: a third undeclared access re-reports
    other2 = engine.new_variable()
    engine.push(lambda: res.append(3), mutable_vars=[other2], name="c")
    engine.wait_for_all()
    assert len(reports()) == 1


def test_env_switch_enables_at_import():
    env = dict(os.environ, MXNET_ENGINE_SANITIZER="1", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         "from mxnet_tpu import engine; "
         "assert engine.sanitizer_enabled(); print('on')"],
        env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and "on" in out.stdout, out.stderr
