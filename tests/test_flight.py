"""telemetry.flight — span tee, ring, tree assembly, anomaly bundles.

Acceptance gates (ISSUE 19): trace-stamped spans tee into per-trace
live timelines from any thread; ``request_end`` moves them into the
bounded ring; ``request_tree`` assembles ONE nested tree addressable by
request id or trace id (batch spans fan into every member trace as
roots); ``on_anomaly`` writes exactly one pid-tagged JSON bundle per
trigger, bounded by ``MXNET_FLIGHT_MAX_BUNDLES``, and bumps
``flight_bundles_total{trigger=...}``.
"""
import json
import os
import threading

import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import context as tctx
from mxnet_tpu.telemetry import flight


@pytest.fixture(autouse=True)
def _clean(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_FLIGHT_DIR", str(tmp_path / "flight"))
    telemetry.reset()
    telemetry.disable_spans()
    flight.reset()
    yield
    telemetry.disable_spans()
    telemetry.reset()
    flight.reset()


def _bundle_dir(tmp_path):
    return tmp_path / "flight"


def test_stamped_spans_tee_into_live_table_cross_thread():
    telemetry.enable_spans("serving")
    ctx = tctx.mint()

    def worker():
        with telemetry.span("serving.dispatch", domain="serving",
                            **ctx.child().stamps()):
            pass

    with telemetry.span("serving.queued", domain="serving",
                        **ctx.child().stamps()):
        pass
    t = threading.Thread(target=worker)
    t.start()
    t.join()
    tree = flight.request_tree(ctx.trace_id)
    assert tree is not None and tree["n_spans"] == 2
    names = {s["name"] for s in tree["spans"]}
    assert names == {"serving.queued", "serving.dispatch"}
    tids = {s["tid"] for s in tree["spans"]}
    assert len(tids) == 2  # recorded from two distinct threads


def test_unstamped_spans_do_not_tee():
    telemetry.enable_spans("serving")
    with telemetry.span("serving.form_batch", domain="serving"):
        pass
    assert flight.summary()["live_traces"] == 0


def test_request_end_moves_live_spans_into_ring_and_tree_nests():
    telemetry.enable_spans("serving")
    ctx = tctx.mint(request_id="r1")
    child = ctx.child()
    with telemetry.span("serving.queued", domain="serving",
                        **child.stamps()):
        with telemetry.span("serving.forward", domain="serving",
                            **child.child().stamps()):
            pass
    flight.request_end(ctx, ok=True, latency_ms=4.2, request_id="r1")
    assert flight.summary()["live_traces"] == 0  # moved, not copied
    tree = flight.request_tree("r1")  # by request id
    assert tree["trace_id"] == ctx.trace_id
    assert tree["ok"] is True and tree["latency_ms"] == 4.2
    # inner span completed FIRST (context-manager exit order) but the
    # assembler still nests it under the queued span via parent_id
    (root,) = [s for s in tree["spans"]
               if s["name"] == "serving.queued"]
    assert [c["name"] for c in root["children"]] == ["serving.forward"]
    assert flight.request_tree(ctx.trace_id)["n_spans"] == 2  # by trace


def test_batch_span_trace_ids_fan_out_to_every_member():
    telemetry.enable_spans("serving")
    a, b = tctx.mint(), tctx.mint()
    with telemetry.span("decode.step", domain="serving",
                        trace_ids=[a.trace_id, b.trace_id],
                        span_id=tctx.mint_span_id()):
        pass
    for ctx in (a, b):
        tree = flight.request_tree(ctx.trace_id)
        assert tree["n_spans"] == 1
        assert tree["spans"][0]["name"] == "decode.step"


def test_on_anomaly_writes_one_bundle_and_bumps_counter(tmp_path):
    # the counter is the process's: an earlier file in this worker may have
    # written deadline_miss bundles already, so compare before and after
    series = 'flight_bundles_total{trigger="deadline_miss"}'
    before = dict(telemetry.registry.get_name_value()).get(series, 0)
    telemetry.enable_spans("serving")
    ctx = tctx.mint(request_id="victim")
    with telemetry.span("serving.queued", domain="serving",
                        **ctx.child().stamps()):
        pass
    path = flight.on_anomaly("deadline_miss", ctx, request_id="victim",
                             latency_ms=12.0)
    assert path is not None and os.path.exists(path)
    assert os.path.basename(path).startswith(
        "flight_deadline_miss_%d_" % os.getpid())
    with open(path) as f:
        bundle = json.load(f)
    assert bundle["trigger"] == "deadline_miss"
    assert bundle["request_id"] == "victim"
    assert bundle["victim"]["n_spans"] == 1
    assert bundle["detail"]["latency_ms"] == 12.0
    assert "MXNET_FLIGHT_DIR" in bundle["config"]
    assert "# TYPE" in bundle["metrics"]  # full exposition rides along
    assert dict(telemetry.registry.get_name_value())[series] == before + 1
    assert "%s %d" % (series, before + 1) in telemetry.registry.exposition()
    assert path in flight.summary()["bundles"]


def test_bundle_cap_bounds_disk_and_counts_drops(monkeypatch):
    # the counter is the process's: an earlier file in this worker may have
    # dropped bundles already, so compare before and after
    series = "flight_bundles_dropped_total"
    before = dict(telemetry.registry.get_name_value()).get(series, 0)
    monkeypatch.setenv("MXNET_FLIGHT_MAX_BUNDLES", "2")
    paths = [flight.on_anomaly("shed", message="m%d" % i)
             for i in range(4)]
    assert len([p for p in paths if p]) == 2
    assert paths[2] is None and paths[3] is None
    expo = telemetry.registry.exposition()
    assert "%s %d" % (series, before + 2) in expo
    # the trigger history still records the capped events
    assert len(flight.summary()["triggers"]) == 4


def test_slow_request_threshold_fires_only_past_it(monkeypatch):
    monkeypatch.setenv("MXNET_SLOW_REQUEST_MS", "50")
    flight.request_end(tctx.mint(), ok=True, latency_ms=10.0)
    assert not flight.summary()["bundles"]
    flight.request_end(tctx.mint(), ok=True, latency_ms=80.0)
    (path,) = flight.summary()["bundles"]
    assert "slow_request" in path


def test_ring_is_bounded_and_disabled_recorder_is_inert(monkeypatch):
    monkeypatch.setenv("MXNET_FLIGHT_RING", "4")
    flight.reset()
    for i in range(10):
        flight.request_end(tctx.mint(request_id="r%d" % i), ok=True,
                           latency_ms=1.0)
    assert len(flight.summary()["ring"]) == 4
    assert flight.request_tree("r0") is None  # aged out
    assert flight.request_tree("r9") is not None
    monkeypatch.setenv("MXNET_FLIGHT_RECORDER", "0")
    flight.reset()
    assert not flight.enabled()
    flight.request_end(tctx.mint(), ok=True, latency_ms=1.0)
    assert flight.on_anomaly("shed") is None
    assert flight.summary()["ring"] == []
