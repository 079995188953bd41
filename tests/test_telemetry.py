"""mxnet_tpu.telemetry — tracer, metrics registry, and dump round-trip.

Acceptance gates (ISSUE 4): (a) spans record per-thread and drain to
well-formed chrome://tracing events, (b) the registry renders parseable
Prometheus text including adopted ServingMetrics groups and the engine
pending gauge, (c) ``profiler.dump_profile()`` ALWAYS writes the JSON at
the configured filename (zero events included), (d) a 2-replica serving
burst + kvstore traffic dumps events from the engine, serving, and
kvstore layers with monotonic timestamps per thread.
"""
import gc
import json
import math
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine, kvstore, profiler, serving, telemetry
from mxnet_tpu.serving import ServingConfig
from mxnet_tpu.telemetry import tracer


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Every test starts with empty buffers and spans off, and cannot
    leak an enabled domain into the (shared-process) tier-1 suite."""
    telemetry.reset()
    telemetry.disable_spans()
    yield
    telemetry.disable_spans()
    telemetry.reset()


# --- tracer -----------------------------------------------------------------

def test_span_records_complete_event_with_args():
    telemetry.enable_spans("engine")
    with telemetry.span("op1", domain="engine", vars=3) as sp:
        sp.annotate(extra="y")
    (ev,) = telemetry.drain_events()
    ph, name, domain, ts, dur, args, tid, tname = ev
    assert (ph, name, domain) == ("X", "op1", "engine")
    # every record carries its id and the id of the span that was open on
    # its thread when it began (0: none)
    assert dur >= 0 and args == {"vars": 3, "extra": "y",
                                 "id": args["id"], "parent": 0}
    assert args["id"] > 0
    assert tid == threading.get_ident()


def test_domain_gating_returns_shared_noop():
    telemetry.enable_spans("serving")
    assert telemetry.enabled("serving")
    assert not telemetry.enabled("engine")
    s1 = telemetry.span("a", domain="engine")
    s2 = telemetry.span("b", domain="kvstore")
    assert s1 is s2  # the disabled path allocates nothing
    with s1:
        pass
    assert telemetry.drain_events() == []
    telemetry.enable_spans("all")
    assert telemetry.enabled("engine") and telemetry.enabled("anything")


def test_spans_off_by_default_and_everything_off_under_master_kill(
        monkeypatch):
    assert not telemetry.enabled("engine")
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    telemetry.enable_spans("all")  # no-op under the master kill
    assert not telemetry.enabled("engine")
    c = telemetry.registry.counter("kill_test_total")
    before = c.value
    c.inc(5)
    assert c.value == before
    h = telemetry.registry.histogram("kill_test_h")
    h.observe(1.0)
    assert h.snapshot()[2] == 0
    monkeypatch.delenv("MXNET_TELEMETRY")
    c.inc(2)
    assert c.value == before + 2


def test_begin_end_crosses_threads_onto_begin_buffer():
    telemetry.enable_spans("engine")
    tok = telemetry.begin("async_op", domain="engine", key=1)
    done = threading.Event()

    def completer():
        telemetry.end(tok, ok=True)
        done.set()

    t = threading.Thread(target=completer, name="completer")
    t.start()
    t.join()
    assert done.wait(1)
    (ev,) = telemetry.drain_events()
    ph, name, domain, ts, dur, args, tid, tname = ev
    # the event lands on the BEGINNING thread's buffer so one logical op
    # stays on one trace row; the completing thread is recorded in args
    assert tid == threading.get_ident()
    assert args["ok"] is True and args["end_tid"] != tid
    telemetry.end(None)  # None token (disabled begin) must be a no-op


def test_complete_uses_explicit_timestamps():
    telemetry.enable_spans("serving")
    t0 = telemetry.clock_ns()
    telemetry.complete("queued", domain="serving", start_ns=t0,
                       end_ns=t0 + 5000)
    (ev,) = telemetry.drain_events()
    assert ev[0] == "X" and ev[3] == t0 and ev[4] == 5000


def test_chrome_events_shape_and_per_tid_sort():
    telemetry.enable_spans("all")
    telemetry.instant("marker", domain="engine")
    with telemetry.span("inner", domain="engine"):
        pass
    evs = telemetry.chrome_events()
    metas = [e for e in evs if e["ph"] == "M"]
    assert metas and metas[0]["name"] == "thread_name"
    rest = [e for e in evs if e["ph"] != "M"]
    assert {e["ph"] for e in rest} == {"i", "X"}
    for e in rest:
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["ts"] >= 0
    assert all("dur" in e for e in rest if e["ph"] == "X")
    assert [e for e in rest if e["ph"] == "i"][0]["s"] == "t"
    by_tid = {}
    for e in rest:
        by_tid.setdefault(e["tid"], []).append(e["ts"])
    for ts_list in by_tid.values():
        assert ts_list == sorted(ts_list)


def test_drain_clears_and_buffers_are_bounded_rings():
    telemetry.enable_spans("all")
    telemetry.instant("once", domain="engine")
    assert len(telemetry.drain_events()) == 1
    assert telemetry.drain_events() == []
    assert tracer._buf().events.maxlen == tracer._BUFFER_SIZE


# --- metrics registry -------------------------------------------------------

def test_registry_get_or_create_and_type_conflict():
    c1 = telemetry.registry.counter("reg_test_total", help="h")
    c2 = telemetry.registry.counter("reg_test_total")
    assert c1 is c2
    with pytest.raises(TypeError):
        telemetry.registry.gauge("reg_test_total")


def test_histogram_cumulative_buckets_and_exposition():
    h = telemetry.registry.histogram("reg_h_ms", buckets=(1, 10, 100))
    h.observe(0.5)
    h.observe(5)
    h.observe(50)
    h.observe(5000)
    counts, s, n = h.snapshot()
    assert counts == [1, 1, 1, 1] and n == 4 and s == 5055.5
    text = telemetry.registry.exposition()
    assert 'reg_h_ms_bucket{le="1"} 1' in text
    assert 'reg_h_ms_bucket{le="10"} 2' in text
    assert 'reg_h_ms_bucket{le="100"} 3' in text
    assert 'reg_h_ms_bucket{le="+Inf"} 4' in text
    assert "reg_h_ms_count 4" in text
    assert dict(h.get_name_value())["reg_h_ms_count"] == 4


def test_exposition_is_parseable_prometheus_text():
    telemetry.registry.counter("parse_total", help="a counter").inc()
    telemetry.registry.gauge("parse_g", fn=lambda: float("nan"))
    for line in telemetry.registry.exposition().splitlines():
        if not line or line.startswith("#"):
            continue
        name_part, value = line.rsplit(" ", 1)
        assert name_part
        float(value)  # every sample value parses (NaN included)


def test_gauge_callback_errors_read_as_nan():
    g = telemetry.registry.gauge("boom_g", fn=lambda: 1 / 0)
    assert math.isnan(g.value)


def test_engine_pending_gauge_registered():
    engine.wait_for_all()
    text = telemetry.registry.exposition()
    assert "# TYPE engine_pending_ops gauge" in text
    assert "engine_pending_ops 0" in text


def test_serving_metrics_group_adopted_and_weakref_pruned():
    from mxnet_tpu.serving.metrics import ServingMetrics

    m = ServingMetrics()
    m.record_batch(rows=2, bucket=2, latencies_ms=[1.0, 2.0])
    text = telemetry.registry.exposition()
    tag = '{sid="%d"}' % m.sid
    assert ("serving_qps%s" % tag) in text
    assert ("serving_bucket2_latency_ms_p99%s 2" % tag) in text
    nv = dict(telemetry.registry.get_name_value())
    assert nv["serving_completed"] == 2
    sid = m.sid
    del m, nv
    gc.collect()
    telemetry.registry._snapshot()  # read pass prunes dead weakrefs
    assert all(s != sid for _p, s, _r in telemetry.registry._groups), \
        "dead group not pruned"
    assert ('sid="%d"' % sid) not in telemetry.registry.exposition()


# --- profiler dump ----------------------------------------------------------

def test_dump_profile_always_writes_even_with_zero_events(tmp_path):
    out = tmp_path / "empty_profile.json"
    profiler.profiler_set_config(filename=str(out))
    path = profiler.dump_profile()
    assert path == str(out) and out.exists()
    data = json.loads(out.read_text())
    assert data["traceEvents"] == []


def test_profiler_set_state_brackets_a_profile_window(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_PROFILER_JAX", "0")  # host-only on CPU CI
    out = tmp_path / "window.json"
    profiler.profiler_set_config(filename=str(out))
    profiler.profiler_set_state("run")
    assert telemetry.enabled("engine")  # run turned all domains on
    v = engine.new_variable()
    engine.push(lambda: None, mutable_vars=[v], name="profiled_op")
    engine.fence([v], name="profile_fence").wait()
    profiler.profiler_set_state("stop")
    assert not telemetry.enabled("engine")  # stop restored spans-off
    path = profiler.dump_profile()
    data = json.loads(open(path).read())
    names = {e["name"] for e in data["traceEvents"]}
    assert "engine.fence.wait" in names
    # no jax trace in this window: the file is the ring, on its clock
    assert data["clock"] == "monotonic_ns" and "ringEvents" not in data


# --- the ISSUE 4 round-trip: serving burst -> chrome trace ------------------

def _mlp():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    shapes, _, _ = sym.infer_shape(data=(1, 10))
    params = {n: rng.uniform(-0.1, 0.1, s).astype(np.float32)
              for n, s in zip(sym.list_arguments(), shapes)
              if n not in ("data", "softmax_label")}
    return sym, params


def test_trace_dump_roundtrip_covers_engine_serving_kvstore(tmp_path):
    telemetry.enable_spans("all")

    # kvstore traffic (push/pull/barrier spans + byte counters)
    push0 = dict(telemetry.registry.get_name_value())
    kv = kvstore.create("local")
    w = mx.nd.array(np.ones((4, 2), np.float32))
    kv.init(0, w)
    kv.push(0, mx.nd.array(np.full((4, 2), 0.5, np.float32)))
    out = mx.nd.array(np.zeros((4, 2), np.float32))
    kv.pull(0, out)
    kv.barrier()
    nv = dict(telemetry.registry.get_name_value())
    assert nv["kvstore_push_total"] == push0.get("kvstore_push_total", 0) + 1
    assert nv["kvstore_push_bytes_total"] >= \
        push0.get("kvstore_push_bytes_total", 0) + 4 * 2 * 4
    assert nv["kvstore_barrier_total"] == \
        push0.get("kvstore_barrier_total", 0) + 1

    # 2-replica serving burst
    sym, params = _mlp()
    cfg = ServingConfig(buckets=(1, 2, 4), max_delay_ms=20.0, replicas=2,
                        timeout_ms=10_000.0)
    srv = serving.InferenceServer(sym, params, {"data": (10,)}, config=cfg)
    rng = np.random.RandomState(1)
    results = {}
    with srv:
        def client(i):
            x = rng.uniform(-1, 1, (1, 10)).astype(np.float32)
            results[i] = srv.predict(data=x)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert len(results) == 12

    out_file = tmp_path / "roundtrip.json"
    profiler.profiler_set_config(filename=str(out_file))
    path = profiler.dump_profile()
    data = json.load(open(path))  # chrome://tracing loads exactly this
    evs = data["traceEvents"]
    cats = {e.get("cat") for e in evs}
    assert {"engine", "serving", "kvstore"} <= cats, cats

    # lifecycle stages are all present with their args
    names = {e["name"] for e in evs}
    for expected in ("serving.submit", "serving.queued",
                     "serving.form_batch", "serving.dispatch",
                     "serving.pad", "serving.forward",
                     "kvstore.push", "kvstore.pull"):
        assert expected in names, expected
    disp = [e for e in evs if e["name"] == "serving.dispatch"]
    assert {e["args"]["replica"] for e in disp} == {0, 1}
    assert all("bucket" in e["args"] for e in disp)

    # well-formed: pid/tid ints, ts µs floats, X events carry dur >= 0,
    # and timestamps are monotonic per tid
    by_tid = {}
    for e in evs:
        if e["ph"] == "M":
            continue
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["ts"] >= 0
        if e["ph"] == "X":
            assert e["dur"] >= 0
        by_tid.setdefault(e["tid"], []).append(e["ts"])
    assert len(by_tid) >= 2  # client/former/engine-worker threads
    for ts_list in by_tid.values():
        assert ts_list == sorted(ts_list)

    # a second dump only contains newer events (buffers drained)
    data2 = json.load(open(profiler.dump_profile()))
    assert len(data2["traceEvents"]) < len(evs)


# --- ISSUE 19 satellites: buffer env re-read + exemplars --------------------

def test_buffer_size_env_is_reread_at_ring_creation(monkeypatch):
    """MXNET_TELEMETRY_BUFFER applies to rings created AFTER the env
    change (a fresh thread's first span), not only at import."""
    monkeypatch.setenv("MXNET_TELEMETRY_BUFFER", "32")
    out = []
    t = threading.Thread(target=lambda: out.append(
        tracer._buf().events.maxlen))
    t.start()
    t.join()
    assert out == [32]
    # a bogus value falls back to the import-time default, not a crash
    monkeypatch.setenv("MXNET_TELEMETRY_BUFFER", "not-a-number")
    out2 = []
    t = threading.Thread(target=lambda: out2.append(
        tracer._buf().events.maxlen))
    t.start()
    t.join()
    assert out2 == [tracer._BUFFER_SIZE]


def test_histogram_exemplar_renders_only_on_observed_bucket():
    h = telemetry.registry.histogram("exm_ms", buckets=(1, 10))
    h.observe(0.5)                       # no exemplar
    h.observe(5, exemplar="ab" * 16)     # exemplar on the le=10 bucket
    h.observe(5000, exemplar='tr"icky')  # +Inf bucket; quote escaped
    lines = {l.split(" ", 1)[0]: l
             for l in telemetry.registry.exposition().splitlines()
             if l.startswith("exm_ms_bucket")}
    assert '# {trace_id="%s"} 5 ' % ("ab" * 16) in \
        lines['exm_ms_bucket{le="10"}']
    assert "#" not in lines['exm_ms_bucket{le="1"}']
    assert '# {trace_id="tr\\"icky"} 5000 ' in \
        lines['exm_ms_bucket{le="+Inf"}']
    # cumulative counts are unchanged by exemplars
    assert lines['exm_ms_bucket{le="+Inf"}'].split(" ")[1] == "3"
