#!/usr/bin/env python
"""Serve models over HTTP and talk to them with curl or stdlib clients.

Starts two `HttpFrontend`s (docs/deployment.md "HTTP front-end") in one
process: a classifier behind `POST /v1/predict`, and a tiny randomly
initialized LM behind `POST /v1/generate` streaming tokens as SSE. One
front-end serves one `InferenceServer` — an LM head's token-major
output is not servable through the batch-major predict path, so a
deployment that needs both runs both, exactly like this.

    python examples/http-serving/serve.py
    # then, from another shell (ports are printed at startup):
    curl -s localhost:<P>/v1/predict -H 'x-request-id: demo-1' \
         -d '{"inputs": {"data": [[0.1, ..., 0.9]]}}'
    curl -sN localhost:<G>/v1/generate -H 'x-priority: interactive' \
         -d '{"prompt": [3, 7, 1], "max_new_tokens": 16}'
    curl -s localhost:<P>/metrics | grep http_
    kill -TERM <pid>     # graceful drain: open SSE streams finish first

``--selftest`` drives one predict round-trip and one SSE stream with
stdlib clients in-process and exits (the smoke-test mode).
"""
import argparse
import json
import http.client
import os
import signal
import sys
import threading

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import serving  # noqa: E402
from mxnet_tpu.models import transformer  # noqa: E402
from mxnet_tpu.serving.frontend import (FrontendConfig,  # noqa: E402
                                        HttpFrontend, iter_sse)

V, D, L, F, H, HKV = 32, 16, 2, 32, 4, 2    # toy LM shape
IN_DIM, CLASSES = 10, 3                     # toy classifier shape


def build_predict_server():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=CLASSES, name="fc2")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    shapes, _, _ = sym.infer_shape(data=(1, IN_DIM))
    params = {n: rng.uniform(-0.1, 0.1, s).astype(np.float32)
              for n, s in zip(sym.list_arguments(), shapes)
              if n not in ("data", "softmax_label")}
    return serving.InferenceServer(
        sym, params, {"data": (IN_DIM,)},
        config=serving.ServingConfig(buckets=(1, 2, 4), max_delay_ms=3.0))


def build_generate_server():
    sym = transformer.get_symbol(num_classes=V, num_layers=L, num_heads=H,
                                 model_dim=D, ffn_dim=F, num_kv_heads=HKV)
    rng = np.random.RandomState(0)
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
    decode = serving.GenerateConfig(
        num_heads=H, num_kv_heads=HKV, slots=2, max_context=32,
        prefill_buckets=(4, 8), max_new_tokens=16, queue_depth=16)
    return serving.InferenceServer(
        sym, p, {"data": (8,), "softmax_label": (8,)},
        config=serving.ServingConfig(buckets=(1, 2), max_delay_ms=5.0,
                                     timeout_ms=10000.0),
        decode=decode)


def selftest(predict_port, generate_port):
    rng = np.random.RandomState(1)
    x = rng.uniform(-1, 1, (2, IN_DIM)).astype(np.float32)
    conn = http.client.HTTPConnection("127.0.0.1", predict_port, timeout=60)
    conn.request("POST", "/v1/predict",
                 json.dumps({"inputs": {"data": x.tolist()}}),
                 {"Content-Type": "application/json",
                  "x-request-id": "selftest-1"})
    r = conn.getresponse()
    body = json.loads(r.read())
    assert r.status == 200 and body["request_id"] == "selftest-1", body
    probs = np.asarray(body["outputs"][0], np.float32)
    assert probs.shape == (2, CLASSES)
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, rtol=1e-4)
    conn.close()
    print("predict OK: 2 rows -> %s" % (probs.shape,))

    conn = http.client.HTTPConnection("127.0.0.1", generate_port, timeout=120)
    conn.request("POST", "/v1/generate",
                 json.dumps({"prompt": [3, 7, 1], "max_new_tokens": 12}),
                 {"Content-Type": "application/json"})
    r = conn.getresponse()
    assert r.status == 200, r.status
    tokens, done = [], None
    for ev, data in iter_sse(r):
        if ev == "token":
            tokens.append(data["token"])
        elif ev == "done":
            done = data
    conn.close()
    assert done is not None and len(tokens) == 12, (tokens, done)
    print("generate OK: %d SSE tokens, finish_reason=%s"
          % (len(tokens), done["finish_reason"]))
    print("http-serving selftest PASSED")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--predict-port", type=int, default=0,
                    help="0 = ephemeral (MXNET_HTTP_PORT for real deploys)")
    ap.add_argument("--generate-port", type=int, default=0)
    ap.add_argument("--selftest", action="store_true",
                    help="drive one predict + one SSE stream, then exit")
    args = ap.parse_args()

    fe_p = HttpFrontend(build_predict_server(),
                        FrontendConfig(port=args.predict_port))
    fe_g = HttpFrontend(build_generate_server(),
                        FrontendConfig(port=args.generate_port))
    fe_p.start(wait_ready=True)
    fe_g.start(wait_ready=True)
    print("predict  : http://127.0.0.1:%d/v1/predict" % fe_p.port)
    print("generate : http://127.0.0.1:%d/v1/generate  (SSE)" % fe_g.port)
    print("metrics  : http://127.0.0.1:%d/metrics" % fe_p.port)

    if args.selftest:
        try:
            selftest(fe_p.port, fe_g.port)
        finally:
            fe_p.stop(drain=True)
            fe_g.stop(drain=True)
        return

    # SIGTERM/SIGINT -> drain both front-ends (each drain runs off the
    # signal handler thread; open SSE streams finish before exit)
    stopped = threading.Event()

    def _drain(signum, frame):
        def run():
            fe_p.stop(drain=True)
            fe_g.stop(drain=True)
            stopped.set()
        threading.Thread(target=run, daemon=True).start()

    signal.signal(signal.SIGTERM, _drain)
    signal.signal(signal.SIGINT, _drain)
    print("pid %d — kill -TERM to drain gracefully" % os.getpid())
    stopped.wait()


if __name__ == "__main__":
    main()
