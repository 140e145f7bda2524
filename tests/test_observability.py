"""Observability unit tests: exposition format, spans, OTLP export,
traceparent propagation, flight recorder, and the per-RPC stage clock.

Pure host-side — no engine, no device dispatch.  The OTLP tests run
against a local in-process HTTP collector stub so the payload shape and
the drop-on-error contract are verified over a real socket.
"""

import http.server
import json
import sys
import threading
import time

import pytest

from ketotpu import flightrec
from ketotpu.flightrec import FlightRecorder, rpc_recording
from ketotpu.observability import (
    _BUCKETS,
    Metrics,
    Tracer,
    format_traceparent,
    parse_traceparent,
)
from ketotpu.otlp import OTLPTracer


class TestExposition:
    def test_histogram_bucket_math_round_trip(self):
        m = Metrics()
        # one sample in the first bucket, one mid-range, one past the top
        m.observe("lat_seconds", 0.0004, help="t")
        m.observe("lat_seconds", 0.003, op="x")
        m.observe("lat_seconds", 0.003, op="x")
        m.observe("lat_seconds", 99.0, op="x")
        text = m.exposition()
        assert "# HELP lat_seconds t" in text
        assert "# TYPE lat_seconds histogram" in text
        # unlabeled series: cumulative buckets all 1 from the first edge on
        assert f'lat_seconds_bucket{{le="{_BUCKETS[0]}"}} 1' in text
        assert 'lat_seconds_bucket{le="+Inf"} 1' in text
        assert "lat_seconds_sum 0.0004" in text
        assert "lat_seconds_count 1" in text
        # labeled series: 0.003 lands at le=0.005 cumulatively; the 99.0
        # overflow shows up only at +Inf
        assert 'lat_seconds_bucket{op="x",le="0.0025"} 0' in text
        assert 'lat_seconds_bucket{op="x",le="0.005"} 2' in text
        assert 'lat_seconds_bucket{op="x",le="10.0"} 2' in text
        assert 'lat_seconds_bucket{op="x",le="+Inf"} 3' in text
        assert 'lat_seconds_count{op="x"} 3' in text
        # histogram_values: the scrape surface the bench publishes from
        vals = m.histogram_values("lat_seconds")
        assert vals[(("op", "x"),)] == (pytest.approx(99.006), 3)
        assert vals[()] == (pytest.approx(0.0004), 1)

    def test_label_escaping(self):
        m = Metrics()
        m.counter("hits_total", 1, path='a"b\\c\nd')
        text = m.exposition()
        assert 'hits_total{path="a\\"b\\\\c\\nd"} 1' in text

    def test_counter_gauge_types_and_getters(self):
        m = Metrics()
        m.counter("c_total", 2, help="c", op="a")
        m.counter("c_total", 3, op="a")
        m.gauge("g", 7.5, help="g")
        m.gauge("g", 8.25)  # gauges overwrite, not accumulate
        text = m.exposition()
        assert "# TYPE c_total counter" in text
        assert 'c_total{op="a"} 5' in text
        assert "# TYPE g gauge" in text
        assert "g 8.25" in text
        assert m.get_counter("c_total", op="a") == 5
        assert m.get_gauge("g") == 8.25


class TestTraceparent:
    def test_round_trip(self):
        tid, sid = "ab" * 16, "cd" * 8
        assert parse_traceparent(format_traceparent(tid, sid)) == (tid, sid)

    @pytest.mark.parametrize("bad", [
        None, "", "garbage", "00-short-cd" + "cd" * 7 + "-01",
        "00-" + "zz" * 16 + "-" + "cd" * 8 + "-01",  # non-hex
        "00-" + "00" * 16 + "-" + "cd" * 8 + "-01",  # all-zero trace id
        "00-" + "ab" * 16 + "-" + "00" * 8 + "-01",  # all-zero span id
    ])
    def test_malformed_returns_none(self, bad):
        assert parse_traceparent(bad) is None

    def test_base_tracer_span_and_traceparent(self):
        m = Metrics()
        t = Tracer(m)
        with t.span("outer", _parent="00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"):
            with t.span("inner"):
                pass
            # the base tracer keeps no ids: nothing to propagate
            assert t.current_traceparent() is None
        vals = m.histogram_values("keto_span_duration_seconds")
        assert (("span", "outer"),) in vals
        assert (("span", "inner"),) in vals


def test_queued_samples_are_all_filed_under_contention():
    """observe() queues without the lock and a reader or every 256th call
    files the queue: with threads switching every microsecond and a reader
    filing beside them, no sample is lost or filed twice."""
    m = Metrics()
    n_threads, each = 16, 1000
    stop = threading.Event()

    def observe(k):
        for i in range(each):
            m.observe("h", 0.001, op=str(k % 2))

    def read():
        while not stop.is_set():
            m.histogram_values("h")
            m.exposition()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    reader = threading.Thread(target=read, daemon=True)
    workers = [threading.Thread(target=observe, args=(k,))
               for k in range(n_threads)]
    try:
        reader.start()
        for w in workers:
            w.start()
        for w in workers:
            w.join(60.0)
    finally:
        stop.set()
        sys.setswitchinterval(interval)
        reader.join(60.0)
    assert not reader.is_alive() and not any(w.is_alive() for w in workers)
    got = m.histogram_values("h")
    assert sum(c for _, c in got.values()) == n_threads * each
    assert sum(s for s, _ in got.values()) == pytest.approx(
        0.001 * n_threads * each)
    (buckets, _, count), = [
        v for k, v in m.histogram_buckets("h").items() if k == (("op", "0"),)]
    assert count == buckets[1] == n_threads // 2 * each  # le 0.001


class _Collector(http.server.BaseHTTPRequestHandler):
    payloads = []
    fail = False

    def do_POST(self):
        body = self.rfile.read(int(self.headers.get("Content-Length") or 0))
        if type(self).fail:
            self.send_response(500)
        else:
            type(self).payloads.append(json.loads(body))
            self.send_response(200)
        self.send_header("Content-Length", "0")
        self.end_headers()

    def log_message(self, fmt, *args):
        pass


@pytest.fixture
def collector():
    _Collector.payloads = []
    _Collector.fail = False
    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Collector)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield "http://127.0.0.1:%d" % httpd.server_address[1]
    httpd.shutdown()
    httpd.server_close()


@pytest.fixture
def otlp(collector):
    # long flush interval: the tests flush explicitly
    t = OTLPTracer(collector, metrics=Metrics(), flush_interval=60.0)
    yield t
    t.close()


class TestOTLP:
    def test_payload_shape_and_span_nesting(self, otlp):
        with otlp.span("parent", detail="p") as tr:
            outer_tp = tr.current_traceparent()
            tr.event("PermissionsChecked", allowed=True)
            with tr.span("child"):
                pass
        otlp.flush()
        assert otlp.exported == 2 and otlp.export_errors == 0
        (payload,) = _Collector.payloads
        scope = payload["resourceSpans"][0]["scopeSpans"][0]
        spans = {s["name"]: s for s in scope["spans"]}
        res_attrs = payload["resourceSpans"][0]["resource"]["attributes"]
        assert {"key": "service.name",
                "value": {"stringValue": "keto-tpu"}} in res_attrs
        parent, child = spans["parent"], spans["child"]
        assert child["traceId"] == parent["traceId"]
        assert child["parentSpanId"] == parent["spanId"]
        assert "parentSpanId" not in parent
        assert int(parent["endTimeUnixNano"]) >= int(
            parent["startTimeUnixNano"]
        )
        assert {"key": "detail",
                "value": {"stringValue": "p"}} in parent["attributes"]
        assert parent["events"][0]["name"] == "PermissionsChecked"
        # the traceparent observed inside the span pointed at the parent
        assert outer_tp == format_traceparent(
            parent["traceId"], parent["spanId"]
        )

    def test_remote_traceparent_adoption(self, otlp):
        tid, sid = "ab" * 16, "cd" * 8
        tp = format_traceparent(tid, sid)
        with otlp.span("root", _parent=tp):
            with otlp.span("nested", _parent=format_traceparent(
                "ef" * 16, "12" * 8
            )):
                pass  # an open local span wins over any remote parent
        otlp.flush()
        spans = {
            s["name"]: s
            for p in _Collector.payloads
            for s in p["resourceSpans"][0]["scopeSpans"][0]["spans"]
        }
        assert spans["root"]["traceId"] == tid
        assert spans["root"]["parentSpanId"] == sid
        assert spans["nested"]["traceId"] == tid
        assert spans["nested"]["parentSpanId"] == spans["root"]["spanId"]

    def test_export_error_drops_batch_never_raises(self, otlp):
        _Collector.fail = True
        with otlp.span("doomed"):
            pass
        otlp.flush()  # must swallow the 500
        assert otlp.export_errors == 1
        assert otlp.exported == 0
        assert otlp.metrics.get_counter("keto_otlp_export_errors_total") == 1
        # the failed batch is dropped, not retried forever
        _Collector.fail = False
        otlp.flush()
        assert _Collector.payloads == []


class TestFlightRecorder:
    def test_keeps_n_slowest_sorted(self):
        fr = FlightRecorder(capacity=3)
        for ms in (5, 50, 1, 30, 10):
            fr.record(ms / 1000.0, {"op": "check", "detail": f"{ms}ms"})
        snap = fr.snapshot()
        assert [e["total_ms"] for e in snap] == [50.0, 30.0, 10.0]
        assert all("ts" in e for e in snap)

    def test_floor_rejects_fast_requests_without_lock(self):
        fr = FlightRecorder(capacity=2)
        fr.record(0.05, {"op": "a"})
        fr.record(0.03, {"op": "b"})
        assert fr._floor == pytest.approx(0.03)
        fr.record(0.001, {"op": "fast"})  # under the floor: rejected
        assert [e["op"] for e in fr.snapshot()] == ["a", "b"]

    def test_max_age_pruning(self):
        fr = FlightRecorder(capacity=8, max_age_s=0.05)
        fr.record(0.01, {"op": "old"})
        time.sleep(0.08)
        assert fr.snapshot() == []
        fr.record(0.02, {"op": "new"})
        assert [e["op"] for e in fr.snapshot()] == ["new"]


class _FakeRegistry:
    def __init__(self):
        self._m = Metrics()
        self._fr = FlightRecorder()
        self._t = Tracer(self._m)

    def metrics(self):
        return self._m

    def flight_recorder(self):
        return self._fr

    def tracer(self):
        return self._t


class TestRpcRecording:
    def test_stages_metrics_and_recorder_entry(self):
        reg = _FakeRegistry()
        with rpc_recording(reg, "check", detail="GET /check"):
            flightrec.note_stage("parse", 0.001)
            flightrec.note_stage("parse", 0.002)  # accumulates per request
            flightrec.note_stage("compute", 0.004)
            flightrec.note(verdict=True, wave=7)
        assert flightrec.current() is None
        vals = reg._m.histogram_values(flightrec.STAGE_METRIC)
        assert vals[(("op", "check"), ("stage", "parse"))] == (
            pytest.approx(0.003), 2,
        )
        assert vals[(("op", "check"), ("stage", "compute"))] == (
            pytest.approx(0.004), 1,
        )
        # the base tracer opens no rpc.<op> wrapper span (PR 37): the
        # request's wall time is its outcome sample alone
        spans = reg._m.histogram_values("keto_span_duration_seconds")
        assert (("span", "rpc.check"),) not in spans
        outcomes = reg._m.histogram_values(flightrec.OUTCOME_METRIC)
        assert outcomes[(("op", "check"), ("outcome", "ok"))][1] == 1
        (entry,) = reg._fr.snapshot()
        assert entry["op"] == "check"
        assert entry["detail"] == "GET /check"
        assert entry["verdict"] is True and entry["wave"] == 7
        assert entry["stages_ms"]["parse"] == pytest.approx(3.0)
        assert entry["total_ms"] >= 0

    def test_reentrant_inner_context_is_passthrough(self):
        reg = _FakeRegistry()
        with rpc_recording(reg, "check") as outer:
            with rpc_recording(reg, "expand"):  # worker-host-inside-serving
                flightrec.note_stage("fallback", 0.002)
            assert flightrec.current() is outer
        assert [e["op"] for e in reg._fr.snapshot()] == ["check"]
        vals = reg._m.histogram_values(flightrec.STAGE_METRIC)
        # the inner note landed on the OUTER request's op
        assert (("op", "check"), ("stage", "fallback")) in vals

    def test_noop_without_context(self):
        # direct engine use / bench inner loops: never raises, records nothing
        flightrec.note_stage("parse", 0.5)
        flightrec.note(verdict=False)
        assert flightrec.current() is None
        assert flightrec.current_traceparent() is None
