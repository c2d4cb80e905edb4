"""The program's spans at the layer boundaries, on one clock with the
device trace (docs/observability.md "Profiler mirror"): every live span
is mirrored into a running `jax.profiler` capture, the off path stays
the shared no-op, a served pass emits its encode / dispatch / device
wait / write-back phases inside its pass span, `device.execute` covers
launch to results on the host, and every API call yields one
`http.request` span named by its route template."""

from __future__ import annotations

import contextlib
import glob
import json
import os
import sys
import time
import urllib.request

import jax
import pytest

from kube_scheduler_simulator_tpu.server import SimulatorServer, SimulatorService
from kube_scheduler_simulator_tpu.server.httpserver import route_template
from kube_scheduler_simulator_tpu.utils import telemetry

from helpers import node, pod

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
)
import tracereduce  # noqa: E402

PHASES = ("pass.encode", "pass.dispatch", "pass.device_wait", "pass.writeback")


@pytest.fixture()
def rec():
    recorder = telemetry.SpanRecorder(capacity=65536)
    telemetry.activate(recorder)
    yield recorder
    telemetry.deactivate()


def _capture(logdir, body):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return tracereduce.events_from_dir(str(logdir))


def _mirrored(logdir):
    """[name, start s, duration s] of the capture's host events that carry
    the mirror's stat: the program's spans, told apart from XLA's own."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(str(logdir), "**", "*.xplane.pb"), recursive=True)
    return [
        [e.name, e.start_ns / 1e9, e.duration_ns / 1e9]
        for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if telemetry.MIRROR_STAT in list(e.stats)
    ]


class TestProfilerMirror:
    def test_span_appears_in_the_capture_with_its_duration(self, rec, tmp_path):
        def body():
            with telemetry.span("pass.encode"):
                time.sleep(0.05)

        events = _capture(tmp_path, body)
        (ring,) = [iv for iv in telemetry.span_intervals(rec.snapshot()) if iv["name"] == "pass.encode"]
        ring_s = (ring["end_us"] - ring["start_us"]) / 1e6
        host = [h for h in events["host"] if h[0] == "pass.encode"]
        assert len(host) == 1
        assert host[0][2] >= 0.05
        assert host[0][2] == pytest.approx(ring_s, abs=0.005)
        # told apart from XLA's own host events by its stat alone
        assert [p[0] for p in _mirrored(tmp_path)] == ["pass.encode"]

    def test_nested_spans_keep_their_nesting_in_the_capture(self, rec, tmp_path):
        def body():
            with telemetry.span("pass.gang"):
                with telemetry.span("pass.dispatch"):
                    time.sleep(0.01)

        _capture(tmp_path, body)
        spans = {n: (s, d) for n, s, d in _mirrored(tmp_path)}
        (gs, gd), (ds, dd) = spans["pass.gang"], spans["pass.dispatch"]
        assert gs <= ds and ds + dd <= gs + gd

    def test_off_path_is_the_shared_null_span_and_enters_no_annotation(self, monkeypatch):
        telemetry.deactivate()
        monkeypatch.delenv("KSS_TRACE", raising=False)
        calls = []
        monkeypatch.setattr(telemetry, "_mirror", lambda name: calls.append(name))
        sp = telemetry.span("pass.encode")
        assert sp is telemetry._NULL_SPAN
        with sp as inside:
            inside.set(mode="full")
        assert calls == []

    def test_device_windows_are_not_mirrored(self, rec, monkeypatch):
        calls = []

        def mirror(name):
            calls.append(name)
            return contextlib.nullcontext()

        monkeypatch.setattr(telemetry, "_mirror", mirror)
        telemetry.complete("device.execute", 1.0, 2.0, tid=telemetry.DEVICE_TID)
        with telemetry.span("pass.gang"):
            pass
        assert calls == ["pass.gang"]

    def test_set_reaches_both_begin_and_end(self, rec):
        with telemetry.span("pass.encode", pass_id=7) as sp:
            sp.set(mode="full")
        b, e = rec.snapshot()
        assert b["args"] == e["args"] == {"pass": 7, "mode": "full"}


def _gang_service(n_pods=6):
    svc = SimulatorService()
    for i in range(3):
        svc.store.apply("nodes", node(f"n{i}"))
    for i in range(n_pods):
        svc.store.apply("pods", pod(f"p{i}"))
    return svc


def _run_pass(svc, how):
    if how == "gang":
        svc.scheduler.schedule_gang(record=False)
    elif how == "gang-record":
        svc.scheduler.schedule_gang(record=True)
    elif how == "gang-async":
        svc.scheduler.begin_gang_pass(record=False).resolve()
    else:
        svc.scheduler.schedule()


@pytest.mark.parametrize("how", ["gang", "gang-record", "gang-async", "sequential"])
def test_pass_phases_nest_in_order_and_device_execute_is_repaired(rec, how):
    svc = _gang_service()
    _run_pass(svc, how)
    events = rec.snapshot()
    telemetry.check_nesting(events)
    ivs = telemetry.span_intervals(events)
    outer_name = {"gang": "pass.gang", "gang-record": "pass.gang",
                  "gang-async": "pass.gang.dispatch", "sequential": "pass.sequential"}[how]
    (outer,) = [iv for iv in ivs if iv["name"] == outer_name]
    phase = {}
    for name in PHASES:
        (phase[name],) = [iv for iv in ivs if iv["name"] == name]
    order = [phase[n] for n in PHASES]
    for a, b in zip(order, order[1:]):
        assert a["end_us"] <= b["start_us"], (a["name"], b["name"])
    if how != "gang-async":  # the async finish runs under pass.gang.resolve
        assert all(outer["start_us"] <= iv["start_us"] and iv["end_us"] <= outer["end_us"] for iv in order)
    else:
        (resolve,) = [iv for iv in ivs if iv["name"] == "pass.gang.resolve"]
        for name in ("pass.encode", "pass.dispatch"):
            assert outer["start_us"] <= phase[name]["start_us"] and phase[name]["end_us"] <= outer["end_us"]
        for name in ("pass.device_wait", "pass.writeback"):
            assert resolve["start_us"] <= phase[name]["start_us"] and phase[name]["end_us"] <= resolve["end_us"]
    (dev,) = [iv for iv in ivs if iv["name"] == "device.execute"]
    assert dev["tid"] == telemetry.DEVICE_TID
    assert dev["start_us"] >= phase["pass.encode"]["end_us"]
    assert dev["end_us"] >= phase["pass.device_wait"]["end_us"]
    assert dev["end_us"] <= phase["pass.writeback"]["start_us"]
    assert phase["pass.encode"]["args"]["mode"] in ("full", "delta", "cached")
    if how.startswith("gang"):
        assert svc.scheduler.metrics.snapshot()["phases"]["gangFixpointRounds"] >= 1


def test_nothing_to_schedule_emits_no_device_window(rec):
    svc = _gang_service(n_pods=0)
    svc.scheduler.schedule_gang(record=False)
    assert not [ev for ev in rec.snapshot() if ev["name"] == "device.execute"]


def _req(port, method, path, body=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api/v1{path}", method=method,
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=300) as resp:
        return json.loads(resp.read() or b"null")


def test_every_api_call_yields_one_request_span_named_by_route(rec):
    server = SimulatorServer(SimulatorService(), port=0).start()
    try:
        calls = [
            ("PUT", "/resources/nodes", node("node-secret-7")),
            ("PUT", "/resources/pods", pod("pod-secret-9", ns="ns-secret-3")),
            ("GET", "/resources/pods/ns-secret-3/pod-secret-9", None),
            ("POST", "/schedule?mode=gang&record=0", None),
            ("GET", "/resources/pods", None),
            ("DELETE", "/resources/pods/ns-secret-3/pod-secret-9", None),
            ("GET", "/metrics", None),
        ]
        for method, path, body in calls:
            _req(server.port, method, path, body)
        _req(server.port, "GET", "/debug/trace")
        # a handler closes its span just after its response leaves, so the
        # client may be a few calls ahead of the ring: wait for the last
        deadline = time.monotonic() + 30
        while True:
            ivs = telemetry.span_intervals(rec.snapshot())
            requests = [iv for iv in ivs if iv["name"] == "http.request"]
            if len(requests) >= len(calls) or time.monotonic() > deadline:
                break
            time.sleep(0.01)
    finally:
        server.shutdown()
    assert [(iv["args"]["method"], iv["args"]["route"]) for iv in sorted(requests, key=lambda iv: iv["start_us"])] == [
        ("PUT", "PUT /api/v1/resources/nodes"),
        ("PUT", "PUT /api/v1/resources/pods"),
        ("GET", "GET /api/v1/resources/pods/{namespace}/{name}"),
        ("POST", "POST /api/v1/schedule"),
        ("GET", "GET /api/v1/resources/pods"),
        ("DELETE", "DELETE /api/v1/resources/pods/{namespace}/{name}"),
        ("GET", "GET /api/v1/metrics"),
    ]
    assert "secret" not in json.dumps([iv["args"] for iv in requests])
    (gang,) = [iv for iv in ivs if iv["name"] == "pass.gang"]
    (post,) = [iv for iv in requests if iv["args"]["method"] == "POST"]
    assert post["start_us"] <= gang["start_us"] and gang["end_us"] <= post["end_us"]


@pytest.mark.parametrize("method,path,route", [
    ("PUT", "/api/v1/resources/pods", "PUT /api/v1/resources/pods"),
    ("DELETE", "/api/v1/resources/pods/ns1/web-0", "DELETE /api/v1/resources/pods/{namespace}/{name}"),
    ("GET", "/api/v1/resources/nodes/n-17", "GET /api/v1/resources/nodes/{name}"),
    ("POST", "/api/v1/sessions/tenant-a/schedule?mode=gang", "POST /api/v1/sessions/{id}/schedule"),
    ("DELETE", "/api/v1/sessions/tenant-a", "DELETE /api/v1/sessions/{id}"),
    ("GET", "/api/v1/admin/checkpoints/tenant-a", "GET /api/v1/admin/checkpoints/{id}"),
    ("POST", "/api/v1/extender/filter/3", "POST /api/v1/extender/filter/{id}"),
    ("GET", "/", "GET /"),
    ("GET", "/favicon-secret.ico", "GET (not found)"),
    ("GET", "/api/v1/debug/trace", None),
    ("GET", "/api/v1/sessions/tenant-a/events", None),
    ("GET", "/api/v1/listwatchresources", None),
])
def test_route_template(method, path, route):
    assert route_template(method, path) == route
