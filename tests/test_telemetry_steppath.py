"""The trainer's spans (ISSUE 25): on the profiler's clock, with a parent,
on without being asked, and charged with the compiles they caused.

(a) a span recorded while a profiler session runs lies in the session's
xplane; (b) parent and self time across threads; (c) ``make_train_step``:
one ``executor.train_step`` a step, the first compiled with ``build``
children, a reshape compiles once more and the record names the step;
(d) ``Module.fit``: the ``module.*`` children in order under one ``step``;
(e) ``MXNET_TELEMETRY=0`` records none of it; (f) every ``per_layer`` entry
of ``BENCHMARK.json`` has its reader file. The program's record of the step
it built (``telemetry.programs()``): (g) on the plain ``jax.jit`` path, its
program asked back from jit, every working instruction under its graph node
and the compiled program's bytes; (h) asking back builds no program; (i) a
reshape's second record; (j) the master kill and ``reset``; (k) noting
prints nothing, the first read parses once.
"""
import glob
import importlib.util
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler, telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_tracer():
    telemetry.reset()
    telemetry.disable_spans()
    yield
    telemetry.disable_spans()
    telemetry.reset()


def _records(name=None):
    out = [{"name": n, "start": ts, "dur": dur, "args": args or {},
            "tid": tid}
           for _ph, n, _dom, ts, dur, args, tid, _tn
           in telemetry.drain_events(clear=False)]
    out.sort(key=lambda r: r["start"])
    return [r for r in out if name is None or r["name"] == name]


def _mlp(hidden=16):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=hidden, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


# --- (a) one clock ------------------------------------------------------------

def _host_events(logdir):
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    data = ProfileData.from_file(files[-1])
    return [(ev.name, ev.start_ns, ev.duration_ns)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def test_span_lies_in_the_profilers_trace_and_dump_keeps_one_clock(tmp_path):
    """A step-path span and a domain span recorded during a session are
    host events of the xplane, inside the session; ``dump_profile`` then
    writes the trace alone under ``traceEvents`` and the ring beside it."""
    out = tmp_path / "p" / "profile.json"
    profiler.profiler_set_config(filename=str(out))
    profiler.profiler_set_state("run")  # spans on + the jax trace
    with telemetry.span("executor.train_step", domain="executor", step=1):
        with telemetry.span("engine.some_op", domain="engine"):
            jnp.ones(8).block_until_ready()
    tok = telemetry.begin("engine.async_op", domain="engine")
    t = threading.Thread(target=telemetry.end, args=(tok,))
    t.start()
    t.join()
    profiler.profiler_set_state("stop")

    hosts = _host_events(str(tmp_path / "p" / "jax_trace"))
    by_name = {n: (s, d) for n, s, d in hosts}
    for name in ("executor.train_step", "engine.some_op", "engine.async_op"):
        assert name in by_name, sorted(by_name)[:40]
    first = min(s for _n, s, _d in hosts)
    last = max(s + d for _n, s, d in hosts)
    outer, inner = by_name["executor.train_step"], by_name["engine.some_op"]
    assert first <= outer[0] and outer[0] + outer[1] <= last
    assert outer[0] <= inner[0] and inner[0] + inner[1] <= outer[0] + outer[1]

    data = json.load(open(profiler.dump_profile()))
    assert data["clock"] == "profiler"
    traced = {e["name"] for e in data["traceEvents"] if e["ph"] == "X"}
    assert "executor.train_step" in traced
    # the ring's records keep their attributes, in a list of their own
    ring = [e for e in data["ringEvents"]
            if e["name"] == "executor.train_step"]
    assert len(ring) == 1 and ring[0]["args"]["step"] == 1
    assert not any("args" in e and "parent" in e["args"]
                   for e in data["traceEvents"])
    # the next window takes no trace: the ring alone, under traceEvents
    with telemetry.span("executor.train_step", domain="executor", step=2):
        pass
    data = json.load(open(profiler.dump_profile()))
    assert data["clock"] == "monotonic_ns" and "ringEvents" not in data
    assert [e["args"]["step"] for e in data["traceEvents"]
            if e["name"] == "executor.train_step"] == [2]


# --- (b) parent and self time ---------------------------------------------------

def test_parent_and_self_time_on_two_threads():
    telemetry.enable_spans("app")
    seen = {}

    def work(tag):
        with telemetry.span("outer", domain="app", tag=tag) as o:
            with telemetry.span("inner", domain="app", tag=tag) as i:
                seen[tag] = (o.id, i.id, [s.name for s in
                                          telemetry.open_spans()])
            with telemetry.span("inner", domain="app", tag=tag):
                pass

    t = threading.Thread(target=work, args=("b",))
    with telemetry.span("root", domain="app"):
        t.start()          # the other thread's stack is its own:
        work("a")          # "root" is no parent of its spans
        t.join()
    assert telemetry.open_spans() == []
    recs = _records()
    assert len({r["args"]["id"] for r in recs}) == len(recs) == 7
    root = next(r for r in recs if r["name"] == "root")
    for tag in ("a", "b"):
        outer = next(r for r in recs
                     if r["name"] == "outer" and r["args"]["tag"] == tag)
        kids = [r for r in recs if r["args"]["parent"] == outer["args"]["id"]]
        assert [k["name"] for k in kids] == ["inner", "inner"]
        assert all(k["tid"] == outer["tid"] for k in kids)
        assert seen[tag][:2] == (outer["args"]["id"], kids[0]["args"]["id"])
        assert seen[tag][2][-2:] == ["outer", "inner"]
        # self time: the parent's duration less its children's
        assert outer["dur"] - sum(k["dur"] for k in kids) >= 0
        assert outer["args"]["parent"] == (root["args"]["id"]
                                           if tag == "a" else 0)


# --- (c) make_train_step ----------------------------------------------------------

def _train_step(batch, seed=0):
    sym = _mlp()
    exe = sym.simple_bind(mx.cpu(), grad_req={
        n: ("null" if n in ("data", "softmax_label") else "write")
        for n in sym.list_arguments()}, data=(batch, 10),
        softmax_label=(batch,))
    rng = np.random.RandomState(seed)
    params = {n: jnp.asarray(rng.uniform(-0.1, 0.1, a.shape), jnp.float32)
              for n, a in exe.arg_dict.items()
              if n not in ("data", "softmax_label")}

    def update(p, g, s, lr):
        return ({n: p[n] - lr * g[n] for n in p}, s)

    return exe, exe.make_train_step(update), params


def _feed(batch, seed=1):
    rng = np.random.RandomState(seed)
    return {"data": jnp.asarray(rng.randn(batch, 10), jnp.float32),
            "softmax_label": jnp.asarray(rng.randint(0, 4, batch),
                                         jnp.float32)}


def test_train_step_spans_and_the_compiles_they_caused():
    nv0 = dict(telemetry.registry.get_name_value())
    exe, step, params = _train_step(8)
    states = {n: jnp.zeros_like(a) for n, a in params.items()}
    lr = jnp.float32(0.1)
    for _ in range(5):
        outs, params, states = step(params, states, _feed(8), lr)
    jax.block_until_ready(outs)

    (bind,) = _records("executor.bind")  # simple_bind's and the
    assert bind["args"]["n_args"] == 6   # constructor's are one span
    assert bind["args"]["arg_bytes"] == 4 * (8 * 10 + 8 + 16 * 10 + 16
                                             + 4 * 16 + 4)
    steps = _records("executor.train_step")
    assert [s["args"]["step"] for s in steps] == [1, 2, 3, 4, 5]
    first, rest = steps[0], steps[1:]
    assert first["args"]["compiled"] is True
    assert first["args"]["compile_s"] + first["args"].get(
        "cache_read_s", 0) > 0
    assert first["args"]["trace_s"] > 0 and first["args"]["lower_s"] > 0
    assert first["args"]["programs"] >= 1
    assert first["args"]["chain"] == 1 and first["args"]["stage"] == 0
    assert first["args"]["gather_bytes"] > 0
    assert not any("compiled" in s["args"] for s in rest)

    kids = {s["args"]["id"]: [] for s in steps}
    for r in _records():
        if r["args"].get("parent") in kids:
            kids[r["args"]["parent"]].append(r)
    assert [(k["name"], k["args"].get("phase"))
            for k in kids[first["args"]["id"]]] == [
        ("executor.train_step.build", "place"),
        ("executor.train_step.dispatch", None)]
    dispatch = kids[first["args"]["id"]][1]
    assert dispatch["args"]["compiled"] is True  # jit compiled inside it
    # a child's compile is its parent's too, and the parent's is no less
    assert first["args"]["compile_s"] >= dispatch["args"].get(
        "compile_s", 0)
    for s in rest:
        assert [k["name"] for k in kids[s["args"]["id"]]] == [
            "executor.train_step.dispatch"]

    # the registry counted them, by the innermost span open
    nv = dict(telemetry.registry.get_name_value())
    series = 'compiles_total{span="executor.train_step.dispatch"}'
    assert nv[series] >= nv0.get(series, 0) + 1
    assert (nv["compile_seconds_total"]
            + nv["compile_cache_read_seconds_total"]) > (
        nv0.get("compile_seconds_total", 0)
        + nv0.get("compile_cache_read_seconds_total", 0))

    # a forced reshape: one more compiled step, and the record names it
    telemetry.reset()
    for _ in range(3):
        outs, params, states = step(params, states, _feed(4), lr)
    jax.block_until_ready(outs)
    steps = _records("executor.train_step")
    compiled = [s["args"]["step"] for s in steps
                if s["args"].get("compiled")]
    assert [s["args"]["step"] for s in steps] == [6, 7, 8]
    assert compiled == [6]

    # the benchmark's reader over the same ring says so too
    spec = importlib.util.spec_from_file_location(
        "bench_spans", os.path.join(ROOT, "benchmark", "lib", "spans.py"))
    spans = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(spans)
    assert spans.window_compiles({"steps": 3}) == [6]
    assert spans.window_median_ms({"steps": 3}) > 0


def test_compile_outside_any_span_is_no_layers():
    nv0 = dict(telemetry.registry.get_name_value())
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    nv = dict(telemetry.registry.get_name_value())
    series = 'compiles_total{span="none"}'
    assert nv[series] >= nv0.get(series, 0) + 1
    assert _records() == []


def test_nested_jit_traces_are_counted_once():
    """An inner jit is traced inside its caller's trace and reports its own
    duration first: the span's ``trace_s`` is the outer trace, not both."""
    inner = jax.jit(lambda x: jnp.sin(x) * 2)

    def outer(x):
        for _ in range(20):
            x = inner(x) + 1
        return x

    with telemetry.span("executor.train_step", domain="executor") as sp:
        jax.jit(outer)(jnp.arange(5.0)).block_until_ready()
    (rec,) = _records("executor.train_step")
    assert rec["args"]["compiled"] is True
    assert 0 < rec["args"]["trace_s"] <= rec["dur"] / 1e9


# --- (d) Module.fit ----------------------------------------------------------------

def _fit(num_epoch=1):
    rng = np.random.RandomState(0)
    X = rng.randn(48, 10).astype(np.float32)
    y = rng.randint(0, 4, 48).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=16, label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(it, num_epoch=num_epoch, kvstore=None, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    return mod


def test_fit_step_children_in_order_under_one_step():
    mod = _fit()
    assert mod.fit_step_path == "fused"
    recs = _records()
    fits = [r for r in recs if r["name"] == "module.fit_step"]
    assert [f["args"]["step"] for f in fits] == [1, 2, 3]
    assert all(f["args"]["path"] == "fused" for f in fits)
    for f in fits:
        kids = [r for r in recs if r["args"].get("parent") == f["args"]["id"]]
        assert [k["name"] for k in kids] == [
            "module.fit_step.prepare", "module.load_data",
            "executor.train_step"]
        prepare, load, step = kids
        assert prepare["args"]["lw_rebuilt"] is (f["args"]["step"] == 1)
        assert load["args"]["bytes"] == 4 * (16 * 10 + 16)
        # self time of the step: its duration less its children's
        assert f["dur"] - sum(k["dur"] for k in kids) >= 0
        grand = [r["name"] for r in recs
                 if r["args"].get("parent") == step["args"]["id"]]
        assert grand[-1] == "executor.train_step.dispatch"
    # the snapshots are the first step's prepare's, and no later step's
    snaps = [r for r in recs if r["name"] == "module.fused_snapshot"]
    first_prepare = next(r for r in recs
                         if r["name"] == "module.fit_step.prepare")
    assert snaps and all(
        s["args"]["parent"] == first_prepare["args"]["id"]
        and s["args"]["bytes"] > 0 for s in snaps)
    # the loop's own spans, around the step: 3 batches and the end
    top = [r["name"] for r in recs if r["args"].get("parent") == 0
           and r["name"].startswith("module.")]
    assert top == ["module.next_batch", "module.fit_step",
                   "module.update_metric"] * 3 + ["module.next_batch"]
    # at most 8 span records a step
    assert len([r for r in recs if r["name"] in telemetry.STEP_PATH
                and r["name"] != "executor.bind"]) <= 8 * 3 + 3


def test_unfused_fit_step_says_so(monkeypatch):
    monkeypatch.setenv("MXNET_FUSED_FIT", "0")
    mod = _fit()
    assert mod.fit_step_path == "unfused"
    fits = _records("module.fit_step")
    assert [f["args"]["path"] for f in fits] == ["unfused"] * 3
    assert _records("executor.train_step") == []


# --- (e) the master kill -------------------------------------------------------------

def test_master_kill_records_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    nv0 = dict(telemetry.registry.get_name_value())
    assert telemetry.span("executor.train_step", domain="executor") is \
        telemetry.span("anything", domain="engine")
    _fit()
    exe, step, params = _train_step(8, seed=3)
    step(params, {n: jnp.zeros_like(a) for n, a in params.items()},
         _feed(8), jnp.float32(0.1))
    assert _records() == []
    assert telemetry.open_spans() == []
    nv = dict(telemetry.registry.get_name_value())
    for name in ("compile_seconds_total", "compile_cache_read_seconds_total"):
        assert nv.get(name, 0) == nv0.get(name, 0)


def test_domains_stay_off_by_default():
    """Only the step path records unasked: every other domain is off."""
    for domain in ("engine", "kvstore", "serving", "monitor", "executor",
                   "module"):
        assert not telemetry.enabled(domain)
    with telemetry.span("executor.forward", domain="executor"):
        pass
    with telemetry.span("module.anything_else", domain="module"):
        pass
    assert telemetry.begin("executor.train_step", domain="executor") is None
    assert _records() == []
    assert len(telemetry.STEP_PATH) <= 12


# --- (f) the manifest -------------------------------------------------------------------

def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("metric", [m["name"] for m in
                                    _manifest()["per_layer"]])
def test_every_per_layer_metric_has_its_reader(metric):
    entry = next(m for m in _manifest()["per_layer"] if m["name"] == metric)
    path = os.path.join(ROOT, "benchmark", "metrics", metric + ".py")
    assert os.path.exists(path), path
    with open(path) as f:
        src = f.read()
    assert "def read(run)" in src
    assert entry["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    # a reader of the program's spans or of its record's memory names no
    # device trace, and back
    assert ("lib import spans" in src or "programs.memory(" in src) == (
        entry["source"] == "program_span")


# --- (g)-(k) the program's record of its step -------------------------------

def _toy_lm_step(batch=2):
    from mxnet_tpu import models

    sym = models.get_symbol("transformer-lm", num_classes=50, num_layers=1,
                            num_heads=4, model_dim=16, ffn_dim=32,
                            scalar_loss=True)
    inputs = {"data": (batch, 8), "softmax_label": (batch, 8)}
    exe = sym.simple_bind(mx.cpu(), grad_req={
        n: "null" if n in inputs else "write" for n in sym.list_arguments()},
        type_dict=dict.fromkeys(inputs, "int32"), **inputs)
    rng = np.random.RandomState(0)
    params = {n: jnp.asarray(rng.uniform(-0.1, 0.1, a.shape), jnp.float32)
              for n, a in exe.arg_dict.items() if n not in inputs}
    return exe, exe.make_train_step(lambda p, g, s: (
        {n: p[n] - 0.1 * g[n] for n in p}, s)), params


def _lm_feed(batch=2):
    ids = np.random.RandomState(1).randint(0, 50, (batch, 9)).astype(np.int32)
    return {"data": ids[:, :-1], "softmax_label": ids[:, 1:]}


def _programs_module():
    import importlib

    # ``telemetry.programs`` is the function; this is its module
    return importlib.import_module("mxnet_tpu.telemetry.programs")


def test_record_through_the_program_cache(monkeypatch):
    """(g) and (k): one record of the plainly jitted step, whose program
    jit handed back; its ``ops`` are the entry computation's working
    instructions, each under the node its text names; its bytes are the
    compiled program's; the text is parsed at the first read, once."""
    from mxnet_tpu import executor

    assert "MXNET_PROGCACHE_DIR" not in os.environ
    mod, compiled, parses = _programs_module(), [], []
    note, parse = executor._note_program, mod.device_ops
    monkeypatch.setattr(executor, "_note_program", lambda *a, **kw: (
        compiled.append(kw["compiled"]), note(*a, **kw))[1])
    monkeypatch.setattr(mod, "device_ops",
                        lambda text: (parses.append(1), parse(text))[1])
    exe, step, params = _toy_lm_step()
    for _ in range(2):
        _, params, _ = step(params, {}, _lm_feed())
    assert len(compiled) == 1 and compiled[0] is not None and parses == []
    (rec,) = telemetry.programs()
    telemetry.programs()
    assert parses == [1] and rec["read_s"]["parse"] > 0
    assert (rec["program"], rec["step"], rec["build"]) == (
        "train_step", 1, None)

    text = compiled[0].as_text()
    comps, entry = mod.computations(text)
    working = {}
    for line in comps[entry]:
        name, _, opcode, _ = mod.INSTR.match(line).groups()
        if opcode not in mod.FREE + mod.UMBRELLAS and not opcode.endswith(
                ("-start", "-done")):
            working[name] = line
    ops = {op["name"]: op for op in rec["ops"]}
    assert working and set(working) <= set(ops)
    for name, line in working.items():
        called = mod.re.search(r"calls=%(\S+?)[,\s]", line)
        inside = line + "".join(comps.get(called.group(1), ())
                                if called else ())
        node = ops[name]["node"]
        assert ("jvp(%s)" % node in inside) if node else (
            "jvp(" not in inside), name
        assert ops[name]["opcode"] == mod.INSTR.match(line).group(3)
        assert ops[name]["kernel"] is False  # no Mosaic call on the CPU
    named = {op["node"] for op in rec["ops"]}
    assert {"embed", "layer0_attn", "layer0_ffn1", "layer0_ffn2"} <= named
    assert "" in named  # the update, traced outside every node
    assert named - {""} <= set(rec["nodes"])
    assert rec["nodes"]["layer0_attn"] == {
        "op": "MultiHeadAttention",
        "inputs": ["layer0_q", "layer0_k", "layer0_v"]}
    stats = compiled[0].memory_analysis()
    assert rec["memory"] == {
        "argument": stats.argument_size_in_bytes,
        "output": stats.output_size_in_bytes,
        "alias": stats.alias_size_in_bytes,
        "temp": stats.temp_size_in_bytes,
        "generated_code": stats.generated_code_size_in_bytes,
        "uncast_table_bytes": 0}
    assert rec["layers"] == [
        {"op": "MultiHeadAttention", "node": "layer0_attn", "head_dim": 4,
         "rope_dims": 4, "window": None, "kernel": False, "backward": None,
         "q_super": None, "output_gate": False},
        # the backward's choice lands in the record too (16 ids, 50 rows)
        {"op": "Embedding", "node": "embed", "rows": 50, "ids": 16,
         "backward": "direct"}]


def test_record_of_a_plainly_jitted_step_and_of_a_reshape(monkeypatch):
    """(h) and (i): the program jit built is asked back, so ``ops`` and the
    program's bytes are there beside the layers, and asking built no
    program beyond the one the dispatch built; a reshape builds a second
    record and the first is kept."""
    def built():
        return sum(v for k, v in telemetry.registry.get_name_value()
                   if k.startswith("compiles_total"))

    asked, compile_ = [], jax.stages.Lowered.compile

    def counted(self, *args, **kwargs):
        before = built()
        out = compile_(self, *args, **kwargs)
        asked.append(built() - before)
        return out

    monkeypatch.setattr(jax.stages.Lowered, "compile", counted)
    exe, step, params = _toy_lm_step()
    for _ in range(3):
        _, params, _ = step(params, {}, _lm_feed())
    (rec,) = telemetry.programs()
    assert rec["ops"] and rec["read_s"] is not None
    assert rec["build"] is None and rec["step"] == 1
    assert all(rec["memory"][k] is not None
               for k in ("argument", "output", "alias", "temp",
                         "generated_code"))
    assert rec["memory"]["argument"] > 0
    assert [(r["op"], r["node"], r.get("head_dim")) for r in rec["layers"]] == [
        ("MultiHeadAttention", "layer0_attn", 4), ("Embedding", "embed", None)]
    assert rec["nodes"]["embed"]["op"] == "Embedding"
    for _ in range(2):
        _, params, _ = step(params, {}, _lm_feed(batch=4))
    first, second = telemetry.programs()
    assert first is rec and second["step"] == 4 and second["ops"]
    # one step program a shape, built inside the dispatch; asking jit back
    # for it built none
    assert asked == [0, 0]
    dispatched = [r["args"].get("programs")
                  for r in _records("executor.train_step.dispatch")]
    assert dispatched == [1, None, None, 1, None]
    # the same layers, traced again at the new shape: twice the ids
    assert second["layers"] == [dict(r, ids=32) if r["op"] == "Embedding"
                                else r for r in first["layers"]]


def test_record_under_the_master_kill_and_after_reset(monkeypatch):
    """(j)."""
    exe, step, params = _toy_lm_step()
    _, params, _ = step(params, {}, _lm_feed())
    assert len(telemetry.programs()) == 1
    telemetry.reset()
    assert telemetry.programs() == []
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    exe, step, params = _toy_lm_step(batch=3)
    step(params, {}, _lm_feed(batch=3))
    assert telemetry.programs() == []
