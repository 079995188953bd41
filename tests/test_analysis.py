"""mxnet_tpu.analysis — the static checkers, the fixtures, the CI gate."""
import json
import os
import textwrap

import pytest

from mxnet_tpu import analysis
from mxnet_tpu.analysis import core, engine_lint, lockorder, trace_purity
from mxnet_tpu.analysis.__main__ import main as cli_main
from mxnet_tpu.analysis.witness import LockOrderWitness

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures", "analysis")
PKG = os.path.dirname(os.path.abspath(analysis.__file__))
PKG = os.path.dirname(PKG)  # mxnet_tpu/
BASELINE = os.path.join(os.path.dirname(PKG), "ci", "analysis_baseline.json")


def fixture(name):
    return os.path.join(FIXTURES, name)


def rules_of(findings):
    return {f.rule for f in findings}


# --- the three mandated fixtures ---------------------------------------------
def test_abba_fixture_flags_cycle_and_callback_under_lock():
    fs = analysis.run_analysis(fixture("abba_deadlock.py"))
    rules = rules_of(fs)
    assert "lock-cycle" in rules
    assert "callback-under-lock" in rules
    cyc = next(f for f in fs if f.rule == "lock-cycle")
    # the cycle names both locks of the PR 2 shape
    assert "Metrics._lock" in cyc.subject and "Former._cond" in cyc.subject
    cb = next(f for f in fs if f.rule == "callback-under-lock")
    assert "_error_hook" in cb.subject  # via _fail, interprocedurally


def test_undeclared_mutable_fixture_flags_engine_discipline():
    fs = analysis.run_analysis(fixture("undeclared_mutable.py"))
    rules = rules_of(fs)
    assert "push-async-undeclared-mutable" in rules
    assert "waitall-as-fence" in rules
    assert "push-missing-vars" in rules
    und = next(f for f in fs if f.rule == "push-async-undeclared-mutable")
    assert und.subject.endswith(":results")
    # the clean counterpart (declared mutable var + fence) is NOT flagged
    assert all("good_gather" not in f.qualname for f in fs)


def test_impure_jit_fixture_flags_all_purity_rules():
    fs = analysis.run_analysis(fixture("impure_jit.py"))
    rules = rules_of(fs)
    for rule in ("impure-time", "impure-random", "impure-closure-mutation",
                 "impure-global-mutation", "print-in-trace",
                 "callback-shared-state"):
        assert rule in rules, rule
    # clean_step/clean_norm (jax.random with explicit key) are NOT flagged
    # by the purity checker (compilesurface's stray-jit fires on the bare
    # jax.jit here, by design — scope the cleanliness claim to purity).
    assert all("clean_step" not in f.qualname
               and "clean_norm" not in f.qualname
               for f in fs if f.checker == "purity")


def test_telemetry_in_jit_fixture_flags_trace_time_instrumentation():
    fs = analysis.run_analysis(fixture("telemetry_in_jit.py"))
    hits = [f for f in fs if f.rule == "telemetry-in-jit"]
    # span + registry access in the decorated fn, instant in the
    # shard_map'd fn
    assert {f.qualname.split(":")[-1].split(">")[-1] for f in hits} >= \
        {"instrumented_step", "step"}
    assert any("telemetry.span" in f.subject for f in hits)
    assert any("telemetry.registry.counter" in f.subject for f in hits)
    # a BARE from-imported current_context() in a jitted fn is caught
    # (the thread-local read would be baked in as a trace constant)
    assert any("stamped_step" in f.qualname
               and f.subject == "current_context" for f in hits)
    # the host-side wrapper (not traced) is NOT flagged
    assert all("run" not in f.qualname for f in hits)


def test_raw_write_progcache_fixture_flags_nonatomic_commits():
    fs = analysis.run_analysis(fixture("raw_write_progcache.py"))
    hits = [f for f in fs if f.rule == "raw-binary-commit"]
    # the raw 'wb' commit, the in-place append, and the non-literal mode
    flagged = {f.qualname.split(":")[-1] for f in hits}
    assert flagged == {"bad_store", "bad_append", "bad_dynamic_mode"}
    # the atomic helper itself and read-mode opens are clean
    assert all("_atomic_write_bytes" not in f.qualname for f in hits)
    assert all("good_load" not in f.qualname for f in hits)


def test_progcache_io_scopes_to_progcache_modules_only():
    # a raw write in a NON-progcache file is out of scope for this checker
    fs = analysis.run_analysis(fixture("clean_locks.py"),
                               checks=("progcache_io",))
    assert fs == []


def test_clean_fixture_has_no_findings():
    assert analysis.run_analysis(fixture("clean_locks.py")) == []


# --- the real tree against the checked-in baseline ---------------------------
def test_shipped_tree_has_no_findings_beyond_baseline():
    fs = analysis.run_analysis(PKG)
    baseline = core.load_baseline(BASELINE)
    new, stale = core.diff_against_baseline(fs, baseline)
    assert new == [], "new findings:\n" + "\n".join(f.format() for f in new)
    assert stale == [], "stale baseline entries: %s" % stale


def test_baseline_entries_are_justified():
    data = json.load(open(BASELINE))
    for e in data["findings"]:
        assert e["justification"] and "TODO" not in e["justification"], e


def test_cli_fail_on_new_gate():
    # shipped tree + baseline: green
    assert cli_main(["--fail-on-new"]) == 0
    # fixtures with no baseline: red
    assert cli_main(["--root", fixture("abba_deadlock.py"),
                     "--baseline", "none", "--fail-on-new"]) == 1
    assert cli_main(["--root", fixture("undeclared_mutable.py"),
                     "--baseline", "none", "--fail-on-new"]) == 1
    assert cli_main(["--root", fixture("impure_jit.py"),
                     "--baseline", "none", "--fail-on-new"]) == 1
    assert cli_main(["--root", fixture("telemetry_in_jit.py"),
                     "--baseline", "none", "--fail-on-new"]) == 1
    # clean fixture: green even with no baseline
    assert cli_main(["--root", fixture("clean_locks.py"),
                     "--baseline", "none", "--fail-on-new"]) == 0
    # usage errors
    assert cli_main(["--checks", "nosuch"]) == 2
    assert cli_main(["--root", "/nonexistent/path"]) == 2


# --- fingerprints & baseline mechanics ---------------------------------------
def test_fingerprint_is_line_independent_but_subject_sensitive():
    a = core.Finding("lockorder", "lock-cycle", "x.py", 10, "x:F.f",
                     "A->B", "msg")
    b = core.Finding("lockorder", "lock-cycle", "x.py", 99, "x:F.f",
                     "A->B", "different msg")
    c = core.Finding("lockorder", "lock-cycle", "x.py", 10, "x:F.f",
                     "A->C", "msg")
    assert a.fingerprint == b.fingerprint  # survives unrelated edits
    assert a.fingerprint != c.fingerprint  # but tracks the subject


def test_baseline_update_roundtrip(tmp_path):
    src = tmp_path / "mod.py"
    src.write_text(textwrap.dedent("""
        import threading
        class A:
            def __init__(self, hook):
                self._lock = threading.Lock()
                self._hook = hook
            def go(self):
                with self._lock:
                    self._hook()
    """))
    base = str(tmp_path / "baseline.json")
    # first run: finding is new -> gate fails
    assert cli_main(["--root", str(src), "--baseline", base,
                     "--fail-on-new"]) == 1
    # record it
    assert cli_main(["--root", str(src), "--baseline", base,
                     "--update-baseline"]) == 0
    # now the gate passes; report mode still exits 1 (findings exist)
    assert cli_main(["--root", str(src), "--baseline", base,
                     "--fail-on-new"]) == 0
    assert cli_main(["--root", str(src), "--baseline", base]) == 1
    # fixing the code makes the entry stale but keeps the gate green
    src.write_text("x = 1\n")
    assert cli_main(["--root", str(src), "--baseline", base,
                     "--fail-on-new"]) == 0


# --- declared hierarchy ------------------------------------------------------
def test_peer_locks_and_rank_violations(tmp_path):
    src = tmp_path / "peers.py"
    src.write_text(textwrap.dedent("""
        import threading
        class A:
            def __init__(self, b: "B"):
                self._lock = threading.Lock()
                self._b = b
            def f(self):
                with self._lock:
                    self._b.g()
        class B:
            def __init__(self):
                self._lock = threading.Lock()
            def g(self):
                with self._lock:
                    return 1
    """))
    mods = core.load_modules(str(src))
    # equal rank: peers must not nest
    fs = lockorder.check(mods, hierarchy={"peers.A._lock": 50,
                                          "peers.B._lock": 50})
    assert any(f.rule == "lock-hierarchy" and "PEER" in f.message
               for f in fs)
    # descending rank: violation
    fs = lockorder.check(mods, hierarchy={"peers.A._lock": 60,
                                          "peers.B._lock": 40})
    assert any(f.rule == "lock-hierarchy" and "rank" in f.message
               for f in fs)
    # ascending rank: clean
    fs = lockorder.check(mods, hierarchy={"peers.A._lock": 40,
                                          "peers.B._lock": 60})
    assert not [f for f in fs if f.rule == "lock-hierarchy"]


def test_self_deadlock_detection(tmp_path):
    src = tmp_path / "selfdead.py"
    src.write_text(textwrap.dedent("""
        import threading
        class A:
            def __init__(self):
                self._lock = threading.Lock()
            def outer(self):
                with self._lock:
                    return self.inner()
            def inner(self):
                with self._lock:
                    return 1
    """))
    fs = lockorder.check(core.load_modules(str(src)))
    assert any(f.rule == "lock-self-deadlock" for f in fs)
    # an RLock is reentrant: same shape, no finding
    src2 = tmp_path / "selfok.py"
    src2.write_text(src.read_text().replace("threading.Lock()",
                                            "threading.RLock()"))
    fs2 = lockorder.check(core.load_modules(str(src2)))
    assert not [f for f in fs2 if f.rule == "lock-self-deadlock"]


def test_package_hierarchy_declares_pr2_peers():
    # the PR 2 contract is encoded: former condition and metrics lock are
    # peers, so ANY future nesting between them fails the hierarchy check
    h = analysis.LOCK_HIERARCHY
    assert h["serving.batcher.BatchFormer._cond"] == \
        h["serving.metrics.ServingMetrics._lock"]


# --- runtime witness ---------------------------------------------------------
def test_witness_records_edges_and_violations():
    import threading
    w = LockOrderWitness(hierarchy={"a": 50, "b": 50, "lo": 10, "hi": 20})
    a = w.wrap(threading.Lock(), "a")
    b = w.wrap(threading.Lock(), "b")
    with a:
        with b:       # peers nested: violation
            pass
    lo = w.wrap(threading.Lock(), "lo")
    hi = w.wrap(threading.Lock(), "hi")
    with lo:
        with hi:      # ascending rank: fine
            pass
    assert w.edges() == {("a", "b"): 1, ("lo", "hi"): 1}
    v = w.violations()
    assert len(v) == 1 and "peer" in v[0]
    # metric.py-style surface (the shared metrics path)
    names, values = w.get()
    assert names[-1] == "violations" and values[-1] == 1
    assert dict(w.get_name_value())["edge:a->b"] == 1
    w.reset()
    assert w.edges() == {}


def test_witness_wrapped_condition_still_works():
    import threading
    w = LockOrderWitness()
    cond = w.wrap(threading.Condition(), "c")
    done = []

    def worker():
        with cond:
            done.append(1)
            cond.notify()

    with cond:
        t = threading.Thread(target=worker)
        t.start()
        cond.wait(timeout=5)
    t.join(timeout=5)
    assert done == [1]


# --- analyzer is pure ast ----------------------------------------------------
def test_fixtures_are_never_imported():
    # the fixtures contain deadlocks and impure jits; they must be parsed,
    # not executed. Loading them as SourceModules must not create entries
    # in sys.modules.
    import sys
    before = set(sys.modules)
    analysis.load_modules(FIXTURES)
    assert set(sys.modules) == before


def test_syntax_error_files_are_skipped(tmp_path):
    (tmp_path / "ok.py").write_text("x = 1\n")
    (tmp_path / "broken.py").write_text("def f(:\n")
    mods = analysis.load_modules(str(tmp_path))
    assert [m.relpath for m in mods] == ["ok.py"]
