"""mxnet_tpu.serving.frontend — HTTP front-end tests (ISSUE 17).

Acceptance gates: (a) route coverage — predict/generate/metrics/healthz/
readyz with request_id echo and structured JSON errors, (b) SSE framing:
a greedy `/v1/generate` stream is token-identical to the in-process
``submit_stream`` (including under speculative decoding), (c) admission
control — batch-class 429 shed with Retry-After, 503 at max_inflight and
while draining, (d) `timeout-ms` header propagation into the batcher's
reject-early feasibility check, (e) interactive-before-batch priority
ordering in the former, (f) SIGTERM graceful drain with zero dropped
streams — plus exposition framing (# HELP/# TYPE for every family) and
the reject-early batcher units.
"""
import base64
import http.client
import json
import os
import re
import signal
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serving, telemetry
from mxnet_tpu.models import transformer as transformer_model
from mxnet_tpu.serving import GenerateConfig, ServingConfig, ServingError
from mxnet_tpu.serving.batcher import BatchFormer, Request
from mxnet_tpu.serving.frontend import (AdmissionController,
                                        FrontendConfig, HttpFrontend,
                                        iter_sse, sse_event)

V, D, L, F, H, HKV = 32, 16, 2, 32, 4, 2


# --- fixtures ---------------------------------------------------------------

def _mlp_symbol():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _mlp_params(sym, seed=0):
    rng = np.random.RandomState(seed)
    shapes, _, _ = sym.infer_shape(data=(1, 10))
    return {n: rng.uniform(-0.1, 0.1, s).astype(np.float32)
            for n, s in zip(sym.list_arguments(), shapes)
            if n not in ("data", "softmax_label")}


def _lm_symbol():
    return transformer_model.get_symbol(
        num_classes=V, num_layers=L, num_heads=H, model_dim=D, ffn_dim=F,
        num_kv_heads=HKV)


def _lm_params(seed=0):
    rng = np.random.RandomState(seed)
    dkv = D // H * HKV
    p = {"embed_weight": rng.randn(V, D).astype(np.float32) * 0.3}
    for i in range(L):
        pre = "layer%d" % i
        p[pre + "_ln1_gamma"] = np.ones(D, np.float32)
        p[pre + "_ln1_beta"] = np.zeros(D, np.float32)
        p[pre + "_q_weight"] = rng.randn(D, D).astype(np.float32) * 0.2
        p[pre + "_k_weight"] = rng.randn(dkv, D).astype(np.float32) * 0.2
        p[pre + "_v_weight"] = rng.randn(dkv, D).astype(np.float32) * 0.2
        p[pre + "_o_weight"] = rng.randn(D, D).astype(np.float32) * 0.2
        p[pre + "_ln2_gamma"] = np.ones(D, np.float32)
        p[pre + "_ln2_beta"] = np.zeros(D, np.float32)
        p[pre + "_ffn1_weight"] = rng.randn(F, D).astype(np.float32) * 0.2
        p[pre + "_ffn1_bias"] = np.zeros(F, np.float32)
        p[pre + "_ffn2_weight"] = rng.randn(D, F).astype(np.float32) * 0.2
        p[pre + "_ffn2_bias"] = np.zeros(D, np.float32)
    p["lnf_gamma"] = np.ones(D, np.float32)
    p["lnf_beta"] = np.zeros(D, np.float32)
    p["pred_weight"] = rng.randn(V, D).astype(np.float32) * 0.2
    p["pred_bias"] = np.zeros(V, np.float32)
    return p


def _mlp_frontend(buckets=(1, 2, 4), max_delay_ms=5.0, queue_depth=64,
                  timeout_ms=5000.0, fe_kw=None):
    sym = _mlp_symbol()
    srv = serving.InferenceServer(
        sym, _mlp_params(sym), {"data": (10,)},
        config=ServingConfig(buckets=buckets, max_delay_ms=max_delay_ms,
                             queue_depth=queue_depth,
                             timeout_ms=timeout_ms, replicas=1))
    fe = HttpFrontend(srv, FrontendConfig(port=0, **(fe_kw or {})))
    return fe, srv


def _lm_frontend(spec=False, max_new_tokens=8, slots=2):
    decode = GenerateConfig(
        num_heads=H, num_kv_heads=HKV, slots=slots, max_context=32,
        prefill_buckets=(4, 8), max_new_tokens=max_new_tokens,
        queue_depth=16, paged=False,
        spec=spec, spec_tokens=3, spec_draft="self",
        kv_dtype="f32", quant_weights="")
    srv = serving.InferenceServer(
        _lm_symbol(), _lm_params(),
        {"data": (8,), "softmax_label": (8,)},
        config=ServingConfig(buckets=(1, 2), max_delay_ms=5.0,
                             timeout_ms=10000.0, replicas=1),
        decode=decode)
    fe = HttpFrontend(srv, FrontendConfig(port=0))
    return fe, srv


# --- tiny stdlib HTTP clients ------------------------------------------------

def _req(port, method, path, body=None, headers=None, timeout=60):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path,
                     None if body is None else json.dumps(body),
                     {"Content-Type": "application/json",
                      **(headers or {})})
        r = conn.getresponse()
        raw = r.read()
        hdrs = {k.lower(): v for k, v in r.getheaders()}
        payload = json.loads(raw) if raw and \
            hdrs.get("content-type", "").startswith("application/json") \
            else raw
        return r.status, hdrs, payload
    finally:
        conn.close()


def _sse(port, body, headers=None, timeout=120, on_event=None):
    """POST /v1/generate and parse the SSE stream fully."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/generate", json.dumps(body),
                     {"Content-Type": "application/json",
                      **(headers or {})})
        r = conn.getresponse()
        hdrs = {k.lower(): v for k, v in r.getheaders()}
        if r.status != 200:
            return r.status, hdrs, json.loads(r.read())
        assert hdrs["content-type"].startswith("text/event-stream")
        events = []
        for ev in iter_sse(r):
            events.append(ev)
            if on_event is not None:
                on_event(ev)
        return r.status, hdrs, events
    finally:
        conn.close()


def _sse_tokens(events):
    toks = [d["token"] for e, d in events if e == "token"]
    # per-token indices are the SSE framing contract
    assert [d["index"] for e, d in events if e == "token"] \
        == list(range(len(toks)))
    return toks


# --- (a) routes --------------------------------------------------------------

def test_health_ready_metrics_and_404():
    fe, srv = _mlp_frontend()
    with fe:
        port = fe.port
        st, _, body = _req(port, "GET", "/healthz")
        assert st == 200 and body["status"] == "ok"
        # started with warm-up in flight; readiness converges quickly on
        # this tiny ladder
        deadline = time.monotonic() + 60
        while True:
            st, _, body = _req(port, "GET", "/readyz")
            if st == 200:
                break
            assert time.monotonic() < deadline, body
            time.sleep(0.01)
        assert srv.ready()
        st, hdrs, raw = _req(port, "GET", "/metrics")
        assert st == 200
        assert hdrs["content-type"] == telemetry.CONTENT_TYPE_LATEST
        text = raw.decode("utf-8")
        assert "# HELP" in text and "# TYPE" in text
        st, hdrs, body = _req(port, "GET", "/nope",
                              headers={"x-request-id": "rid-404"})
        assert st == 404 and body["error"]["code"] == "not_found"
        assert hdrs["x-request-id"] == "rid-404"
        st, _, body = _req(port, "POST", "/v1/nope", body={})
        assert st == 404


def test_predict_roundtrip_and_request_id_echo():
    fe, srv = _mlp_frontend()
    rng = np.random.RandomState(3)
    x = rng.uniform(-1, 1, (1, 10)).astype(np.float32)
    with fe:
        want = srv.predict(data=x)
        st, hdrs, body = _req(fe.port, "POST", "/v1/predict",
                              body={"inputs": {"data": x.tolist()}},
                              headers={"x-request-id": "req-42"})
        assert st == 200
        assert body["request_id"] == "req-42"
        assert hdrs["x-request-id"] == "req-42"
        got = np.asarray(body["outputs"][0], np.float32)
        np.testing.assert_allclose(got, want[0], rtol=1e-5, atol=1e-6)
        # no client id -> one is generated and still echoed
        st, hdrs, body = _req(fe.port, "POST", "/v1/predict",
                              body={"inputs": {"data": x.tolist()}})
        assert st == 200 and body["request_id"] == hdrs["x-request-id"]


def test_predict_b64_raw_tensor_roundtrip():
    """The raw-tensor wire form: b64 input decodes to the same feed as
    the JSON list form, and ``"encoding": "b64"`` returns outputs as
    {b64, shape, dtype} dicts that decode to the same arrays."""
    fe, srv = _mlp_frontend()
    rng = np.random.RandomState(11)
    x = rng.uniform(-1, 1, (3, 10)).astype(np.float32)
    b64_in = {"b64": base64.b64encode(np.ascontiguousarray(x)).decode(),
              "shape": [3, 10], "dtype": "float32"}
    with fe:
        want = srv.predict(data=x)
        # b64 in, json out
        st, _, body = _req(fe.port, "POST", "/v1/predict",
                           body={"inputs": {"data": b64_in}})
        assert st == 200
        np.testing.assert_allclose(
            np.asarray(body["outputs"][0], np.float32), want[0],
            rtol=1e-5, atol=1e-6)
        # b64 in, b64 out (opt-in via the body's "encoding" field)
        st, _, body = _req(fe.port, "POST", "/v1/predict",
                           body={"encoding": "b64",
                                 "inputs": {"data": b64_in}})
        assert st == 200
        out = body["outputs"][0]
        got = np.frombuffer(base64.b64decode(out["b64"]),
                            dtype=np.dtype(out["dtype"])).reshape(
                                out["shape"])
        np.testing.assert_allclose(got, want[0], rtol=1e-5, atol=1e-6)
        # malformed raw-tensor dicts -> 400, not 500
        for bad in ({"b64": "!!!not-base64!!!", "shape": [3, 10]},
                    {"b64": b64_in["b64"], "shape": [7, 10]},
                    {"shape": [3, 10]}):
            st, _, body = _req(fe.port, "POST", "/v1/predict",
                               body={"inputs": {"data": bad}})
            assert st == 400, bad
            assert body["error"]["code"] == "bad_request"


def test_bad_requests_400():
    fe, _ = _mlp_frontend()
    with fe:
        port = fe.port
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("POST", "/v1/predict", b"{not json",
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        assert r.status == 400
        assert json.loads(r.read())["error"]["code"] == "bad_request"
        conn.close()
        st, _, body = _req(port, "POST", "/v1/predict", body={"x": 1})
        assert st == 400 and body["error"]["code"] == "bad_request"
        st, _, body = _req(port, "POST", "/v1/predict",
                           body={"inputs": {"data": [[0.0] * 10]}},
                           headers={"x-priority": "turbo"})
        assert st == 400 and "x-priority" in body["error"]["message"]
        st, _, body = _req(port, "POST", "/v1/generate", body={})
        assert st == 400


# --- (b) SSE identical to in-process ----------------------------------------

@pytest.mark.parametrize("spec", [False, True],
                         ids=["vanilla", "spec_decode"])
def test_sse_generate_token_identical_to_inprocess(spec):
    fe, srv = _lm_frontend(spec=spec, max_new_tokens=6)
    prompt = [3, 7, 1]
    with fe:
        want = srv.generate(prompt, max_new_tokens=6)  # greedy in-process
        st, hdrs, events = _sse(fe.port,
                                {"prompt": prompt, "max_new_tokens": 6},
                                headers={"x-request-id": "sse-1"})
        assert st == 200 and hdrs["x-request-id"] == "sse-1"
        assert _sse_tokens(events) == want
        kinds = [e for e, _ in events]
        assert kinds[-1] == "done" and "error" not in kinds
        done = events[-1][1]
        assert done["request_id"] == "sse-1"
        assert done["tokens"] == len(want)
        assert done["finish_reason"] in ("max_tokens", "eos")
        # non-streaming JSON mode returns the same tokens in one body
        st, _, body = _req(fe.port, "POST", "/v1/generate",
                           body={"prompt": prompt, "max_new_tokens": 6,
                                 "stream": False})
        assert st == 200 and body["tokens"] == want


def test_request_id_rides_token_stream():
    fe, srv = _lm_frontend(max_new_tokens=4)
    with fe:
        stream = srv.submit_stream([5, 2, 9], max_new_tokens=4,
                                   request_id="corr-7")
        assert stream.request_id == "corr-7"
        assert len(stream.tokens(60.0)) == 4


# --- (c) admission control ---------------------------------------------------

def test_batch_class_sheds_429_with_retry_after():
    fe, srv = _mlp_frontend(buckets=(8,), max_delay_ms=400.0,
                            queue_depth=8, fe_kw={"shed_pct": 25.0})
    x = np.zeros((1, 10), np.float32)
    with fe:
        # park 4 requests in the former (window holds them ~400ms: the
        # 8-row bucket never fills) -> depth 4 >= 25% of 8
        parked = [srv.submit(data=x) for _ in range(4)]
        st, hdrs, body = _req(fe.port, "POST", "/v1/predict",
                              body={"inputs": {"data": x.tolist()}},
                              headers={"x-priority": "batch"})
        assert st == 429, body
        assert body["error"]["code"] == "shed"
        assert int(hdrs["retry-after"]) >= 1
        # interactive traffic keeps the headroom above shed_pct
        st, _, body = _req(fe.port, "POST", "/v1/predict",
                           body={"inputs": {"data": x.tolist()}})
        assert st == 200
        for r in parked:
            r.get(30.0)
    m = telemetry.registry.get_name_value()
    assert dict(m).get("http_shed_total", 0) >= 1


def test_admission_unit_inflight_cap_and_draining():
    class _FakeFormer:
        queue_depth = 8
        parallelism = 1

        def depth(self):
            return 0

        def dispatch_ewma_s(self):
            return 0.0

    class _FakeServer:
        _former = _FakeFormer()

    adm = AdmissionController(_FakeServer(), max_inflight=1, shed_pct=80.0)
    d, n = adm.decide(0)
    assert d is None and n == 1
    d2, _ = adm.decide(0)
    assert d2 is not None and d2.status == 503 and d2.code == "overloaded"
    assert d2.retry_after_s >= 1
    adm.exit()
    assert adm.inflight() == 0
    adm.set_draining()
    d3, _ = adm.decide(0)
    assert d3 is not None and d3.status == 503 \
        and d3.code == "shutting_down"


# --- (d) deadline header propagation -----------------------------------------

def test_timeout_ms_header_feeds_reject_early():
    fe, srv = _mlp_frontend(buckets=(1, 2, 4), max_delay_ms=300.0,
                            queue_depth=64)
    x = np.zeros((1, 10), np.float32)
    with fe:
        for s in (0.05, 0.05, 0.05):   # warm the dispatch EWMA: 50 ms
            srv._former.note_dispatch(s)
        parked = [srv.submit(data=x) for _ in range(2)]  # backlog
        st, _, body = _req(fe.port, "POST", "/v1/predict",
                           body={"inputs": {"data": x.tolist()}},
                           headers={"timeout-ms": "10"})
        assert st == 429, body           # infeasible -> reject-early
        assert body["error"]["code"] == "deadline_exceeded"
        st, _, body = _req(fe.port, "POST", "/v1/predict",
                           body={"inputs": {"data": x.tolist()}},
                           headers={"timeout-ms": "10000"})
        assert st == 200                 # feasible deadline is honored
        for r in parked:
            r.get(30.0)
        st, _, body = _req(fe.port, "POST", "/v1/predict",
                           body={"inputs": {"data": x.tolist()}},
                           headers={"timeout-ms": "bogus"})
        assert st == 400


def test_former_reject_early_unit():
    f = BatchFormer(max_batch=4, max_delay_ms=5000.0, queue_depth=64)
    for _ in range(3):
        f.note_dispatch(0.05)
    f.submit(Request({}, 4, None))       # one full batch of backlog
    now = time.monotonic()
    with pytest.raises(ServingError) as ei:
        f.submit(Request({}, 1, now + 0.001))   # 1 ms budget, ~50 ms eta
    assert ei.value.code == "deadline_exceeded"
    assert f.depth() == 1                # never enqueued
    f.submit(Request({}, 1, now + 30.0))        # generous budget is fine
    assert f.depth() == 2
    # cold former (no samples) never rejects on feasibility
    cold = BatchFormer(max_batch=4, max_delay_ms=5000.0, queue_depth=64)
    cold.submit(Request({}, 4, None))
    cold.submit(Request({}, 1, time.monotonic() + 0.001))
    assert cold.depth() == 2
    f.close()
    cold.close()


# --- (e) priority ordering ---------------------------------------------------

def test_interactive_dispatches_before_batch_class():
    f = BatchFormer(max_batch=2, max_delay_ms=5.0, queue_depth=64)
    b1 = Request({}, 1, None, priority=serving.PRIORITY_BATCH)
    b2 = Request({}, 1, None, priority=serving.PRIORITY_BATCH)
    i1 = Request({}, 1, None, priority=serving.PRIORITY_INTERACTIVE)
    i2 = Request({}, 1, None, priority=serving.PRIORITY_INTERACTIVE)
    for r in (b1, b2, i1, i2):           # batch class arrived FIRST
        f.submit(r)
    first = f.next_batch()
    second = f.next_batch()
    assert first == [i1, i2]             # interactive jumps the queue
    assert second == [b1, b2]            # batch class keeps FIFO order
    f.close()
    with pytest.raises(ServingError):
        Request({}, 1, None, priority=7)


# --- (f) SIGTERM drain -------------------------------------------------------

def test_sigterm_drain_completes_streams_zero_drops():
    fe, srv = _lm_frontend(max_new_tokens=12)
    prev = signal.getsignal(signal.SIGTERM)
    try:
        fe.start(wait_ready=True)
        fe.install_signal_handlers()
        first_token = threading.Event()
        result = {}

        def client():
            try:
                result["resp"] = _sse(
                    fe.port, {"prompt": [3, 7, 1], "max_new_tokens": 12},
                    on_event=lambda ev: first_token.set())
            except BaseException as e:  # noqa: BLE001
                result["error"] = e

        t = threading.Thread(target=client, daemon=True)
        t.start()
        assert first_token.wait(120.0), "stream never produced a token"
        os.kill(os.getpid(), signal.SIGTERM)   # rolling-restart signal
        t.join(120.0)
        assert not t.is_alive() and "error" not in result, result
        st, _, events = result["resp"]
        assert st == 200
        kinds = [e for e, _ in events]
        assert kinds[-1] == "done", kinds       # stream ran to completion
        assert "error" not in kinds
        assert len(_sse_tokens(events)) == 12   # every token delivered
        fe._stopped.wait(60.0)                  # drain thread finished
        # the drained server refuses new work (or the socket is gone)
        try:
            st, _, body = _req(fe.port, "POST", "/v1/predict",
                               body={"inputs": {"data": [[0.0] * 10]}},
                               timeout=5)
            assert st == 503
        except OSError:
            pass                                # listener already closed
    finally:
        signal.signal(signal.SIGTERM, prev)
        fe.stop()                               # idempotent


# --- exposition framing ------------------------------------------------------

def test_exposition_help_and_type_for_every_family():
    reg = telemetry.Registry()
    reg.counter("helped_total", help="a documented counter").inc(2)
    reg.counter("bare_total").inc()              # no help declared
    reg.gauge("g_plain").set(1.5)
    reg.gauge("g_lab", labels={"dtype": "int8"}).set(3)
    reg.gauge("g_lab", labels={"dtype": "fp8"}).set(4)
    reg.histogram("h_ms", buckets=(1, 10)).observe(5)

    class _Grp:
        def get_name_value(self):
            return [("qps", 7.0)]

    grp = _Grp()
    reg.register_group("srv", grp)
    text = reg.exposition()
    lines = text.splitlines()
    helped = {l.split()[2] for l in lines if l.startswith("# HELP")}
    typed = {l.split()[2] for l in lines if l.startswith("# TYPE")}
    families = set()
    for l in lines:
        if l.startswith("#") or not l.strip():
            continue
        fam = l.split("{")[0].split(" ")[0]
        for suffix in ("_bucket", "_sum", "_count"):
            if fam.endswith(suffix) and fam[: -len(suffix)] in typed:
                fam = fam[: -len(suffix)]
                break
        families.add(fam)
    assert families, text
    for fam in families:                 # EVERY family is framed
        assert fam in typed, (fam, text)
        assert fam in helped, (fam, text)
    # HELP/TYPE once per family even with multiple labeled series
    assert sum(1 for l in lines if l.startswith("# TYPE g_lab ")) == 1
    assert "# HELP bare_total bare_total" in text  # name fallback
    assert telemetry.CONTENT_TYPE_LATEST.startswith("text/plain")


# --- request tracing + flight recorder (ISSUE 19) ----------------------------

TP = "00-4bf92f3577b34da6a3ce929d0e0e4736-00f067aa0ba902b7-01"
TID = "4bf92f3577b34da6a3ce929d0e0e4736"


@pytest.fixture()
def traced(tmp_path, monkeypatch):
    """Spans on for the serving domain + an isolated flight dir."""
    from mxnet_tpu.telemetry import flight
    monkeypatch.setenv("MXNET_FLIGHT_DIR", str(tmp_path / "flight"))
    prev = telemetry.enabled_domains()
    telemetry.enable_spans("serving")
    flight.reset()
    yield flight
    if prev:
        telemetry.enable_spans(prev)
    else:
        telemetry.disable_spans()
    flight.reset()


def _walk_spans(spans, fn):
    for s in spans:
        fn(s)
        _walk_spans(s.get("children") or [], fn)


def test_traceparent_assembles_one_tree_with_exemplar(traced):
    """The ISSUE acceptance path: a traced /v1/generate leaves ONE
    assembled span tree (queued -> dispatch -> decode.step, recorded on
    distinct threads) addressable by request id AND trace id, with the
    same trace id riding the latency histogram as an exemplar."""
    fe, _ = _lm_frontend(max_new_tokens=4)
    with fe:
        st, hdrs, events = _sse(
            fe.port, {"prompt": [3, 7, 1], "max_new_tokens": 4},
            headers={"traceparent": TP, "x-request-id": "tr-1"})
        assert st == 200
        assert hdrs["x-trace-id"] == TID
        # the response hop carries OUR span id, never the caller's
        assert hdrs["traceparent"].startswith("00-%s-" % TID)
        assert "00f067aa0ba902b7" not in hdrs["traceparent"]
        assert events[-1][0] == "done"
        # request_end fires on the scheduler thread right after the done
        # frame goes out; poll briefly for the assembled tree
        deadline = time.monotonic() + 30
        while True:
            st, _, tree = _req(fe.port, "GET", "/debug/requests/tr-1")
            if st == 200 or time.monotonic() > deadline:
                break
            time.sleep(0.01)
        assert st == 200, tree
        assert tree["trace_id"] == TID and tree["ok"] is True
        names, tids = set(), set()
        _walk_spans(tree["spans"], lambda s: (names.add(s["name"]),
                                              tids.add(s["tid"])))
        assert {"serving.queued", "serving.dispatch",
                "decode.step"} <= names, names
        assert len(tids) >= 2          # spans from distinct threads
        # the same tree is addressable by trace id
        st, _, by_trace = _req(fe.port, "GET", "/debug/requests/" + TID)
        assert st == 200 and by_trace["trace_id"] == TID
        # the latency histogram links back via an OpenMetrics exemplar
        st, _, raw = _req(fe.port, "GET", "/metrics")
        text = raw.decode("utf-8")
        pat = (r'serving_request_latency_ms_bucket\{le="[^"]+"\} \d+'
               r' # \{trace_id="%s"\}' % TID)
        assert re.search(pat, text), text
        # /debug/flight: recorder summary with the completed request
        st, _, summ = _req(fe.port, "GET", "/debug/flight")
        assert st == 200 and summ["enabled"]
        assert any(r["request_id"] == "tr-1" and r["trace_id"] == TID
                   for r in summ["ring"])
        st, _, body = _req(fe.port, "GET", "/debug/requests/absent")
        assert st == 404 and body["error"]["code"] == "not_found"


def test_errors_echo_trace_id_in_body_and_headers(traced):
    fe, _ = _mlp_frontend()
    with fe:
        st, hdrs, body = _req(fe.port, "POST", "/v1/predict",
                              body={"x": 1}, headers={"traceparent": TP})
        assert st == 400 and body["error"]["code"] == "bad_request"
        assert body["trace_id"] == TID
        assert hdrs["x-trace-id"] == TID
        assert hdrs["traceparent"].startswith("00-%s-" % TID)
        # a malformed traceparent is IGNORED per W3C spec: the error
        # still carries a (freshly minted) trace id, never a 4xx for it
        st, _, body = _req(fe.port, "POST", "/v1/predict",
                           body={"x": 1},
                           headers={"traceparent": "not-a-traceparent"})
        assert st == 400
        assert len(body["trace_id"]) == 32 and body["trace_id"] != TID
        # GET routes have no request trace: no trace_id key at all
        st, _, body = _req(fe.port, "GET", "/nope")
        assert st == 404 and "trace_id" not in body


def test_sse_error_event_carries_trace_id(traced):
    """A mid-stream failure travels in-band as an SSE `error` event and
    still echoes the trace id (the stream already holds a 200)."""
    fe, _ = _lm_frontend(max_new_tokens=64)
    with fe:
        # a cold scheduler never reject-earlies; the 50 ms deadline then
        # expires during the first prefill compile -> in-band error
        st, hdrs, resp = _sse(
            fe.port, {"prompt": [3, 7, 1], "max_new_tokens": 64},
            headers={"traceparent": TP, "timeout-ms": "50"})
        if st == 200:
            errs = [d for e, d in resp if e == "error"]
            assert errs, resp
            assert errs[0]["code"] == "deadline_exceeded"
            assert errs[0]["trace_id"] == TID
        else:   # submit-side rejection: the JSON error echoes it too
            assert resp["trace_id"] == TID


# --- strict exposition conformance (ISSUE 19 satellite) ----------------------

_VALUE = r"(?:NaN|[+-]?Inf|[+-]?\d+(?:\.\d+)?(?:[eE][+-]?\d+)?)"
_LVAL = r'(?:[^"\\\n]|\\[\\"n])*'          # only \\ \" \n escapes exist
_LABEL = r'[a-zA-Z_][a-zA-Z0-9_]*="%s"' % _LVAL
_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(\{%s(?:,%s)*\})?'
    r' (%s)'
    r'( # \{trace_id="%s"\} %s %s)?$'
    % (_LABEL, _LABEL, _VALUE, _LVAL, _VALUE, _VALUE))


def _assert_prometheus_conformant(text):
    """Line-by-line strict parse of a text-format 0.0.4 body (plus the
    OpenMetrics exemplar suffix): HELP/TYPE framing precedes every
    sample of its family, label values use only the three legal
    escapes, histogram buckets are cumulative with +Inf == _count, and
    exemplars appear only on histogram _bucket lines."""
    assert text.endswith("\n"), "exposition must end with a newline"
    typed, helped = {}, set()
    buckets, counts, sums = {}, {}, {}
    for line in text.splitlines():
        assert line.strip(), "blank line in exposition"
        if line.startswith("# HELP "):
            parts = line.split(" ", 3)
            assert len(parts) >= 3, line
            fam = parts[2]
            assert fam not in helped, "duplicate HELP for " + fam
            helped.add(fam)
            continue
        if line.startswith("# TYPE "):
            parts = line.split(" ")
            assert len(parts) == 4, line
            fam, kind = parts[2], parts[3]
            assert kind in ("counter", "gauge", "histogram", "summary",
                            "untyped"), line
            assert fam not in typed, "duplicate TYPE for " + fam
            typed[fam] = kind
            continue
        assert not line.startswith("#"), "stray comment: " + line
        m = _SAMPLE_RE.match(line)
        assert m, "unparseable sample line: %r" % line
        name, labels, value, exemplar = m.groups()
        fam = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[: -len(suffix)] in typed:
                fam = name[: -len(suffix)]
                break
        # framing must PRECEDE the family's first sample
        assert fam in typed, "sample before # TYPE: " + line
        assert fam in helped, "sample before # HELP: " + line
        if exemplar:
            assert typed[fam] == "histogram" and name.endswith("_bucket"), \
                "exemplar outside a histogram bucket: " + line
        if typed[fam] == "histogram":
            if name.endswith("_bucket"):
                le = re.search(r'le="([^"]*)"', labels or "")
                assert le, "bucket without le label: " + line
                buckets.setdefault(fam, []).append(
                    (le.group(1), int(value)))
            elif name.endswith("_count"):
                counts[fam] = int(value)
            elif name.endswith("_sum"):
                sums[fam] = value
    assert typed and helped
    for fam, bks in buckets.items():
        assert fam in counts and fam in sums, fam + " missing sum/count"
        les = [le for le, _ in bks]
        vals = [v for _, v in bks]
        assert les[-1] == "+Inf", fam + " last bucket must be +Inf"
        assert les.count("+Inf") == 1
        assert all(a <= b for a, b in zip(vals, vals[1:])), \
            fam + " buckets must be cumulative"
        assert vals[-1] == counts[fam], \
            fam + " +Inf bucket must equal _count"


def test_live_metrics_body_is_strictly_conformant(traced):
    """The FULL /metrics body — every family the process exports,
    including traced-traffic exemplars — survives a strict parse."""
    fe, _ = _mlp_frontend()
    x = np.zeros((1, 10), np.float32)
    with fe:
        st, _, _b = _req(fe.port, "POST", "/v1/predict",
                         body={"inputs": {"data": x.tolist()}},
                         headers={"traceparent": TP})
        assert st == 200
        st, hdrs, raw = _req(fe.port, "GET", "/metrics")
        assert st == 200
    text = raw.decode("utf-8")
    _assert_prometheus_conformant(text)
    assert "serving_request_latency_ms_bucket" in text


def test_exposition_conformant_under_hostile_labels_and_help():
    reg = telemetry.Registry()
    reg.counter("c_total", help="multi\nline \\ help").inc()
    reg.gauge("g", labels={"path": 'a"b\\c\nd'}).set(1)
    reg.gauge("nan_g").set(float("nan"))
    h = reg.histogram("h_ms", buckets=(1, 10))
    h.observe(0.5)
    h.observe(5, exemplar='tr"ace\\id')
    h.observe(100)
    text = reg.exposition()
    _assert_prometheus_conformant(text)
    # the hostile label survives escaped, on one line
    assert '{path="a\\"b\\\\c\\nd"}' in text
    assert "# HELP c_total multi\\nline \\\\ help" in text
    # and the parser itself REJECTS the classic violations
    for bad in ("m_no_type 1\n",
                "# TYPE h histogram\n# HELP h h\n"
                'h_bucket{le="1"} 2\nh_bucket{le="+Inf"} 1\n'
                "h_sum 3\nh_count 1\n",
                '# HELP b b\n# TYPE b counter\nb{l="x\ny"} 1\n'):
        with pytest.raises(AssertionError):
            _assert_prometheus_conformant(bad)
