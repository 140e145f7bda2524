"""The readers of the raw capture (``trace_spans.py``, ``readers/
trace_scope_time.py``, ``readers/trace_idle_split.py``) on a recorded
capture that has host planes: ``recorded_spans.json``, a cut of a traced
run of drive-10m.singles on the v5e (PR 27; ``python benchmark/
trace_spans.py <x.xplane.pb> recorded_spans.json`` writes one: the first
six fused waves, their operations merged by scope, the host spans over
them).
"""

import json
import os

import pytest

import trace_spans
from readers import trace_idle_split, trace_scope_time
from trace_spans import intersect, subtract, total, union

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded_spans.json")


@pytest.fixture(scope="module")
def recorded():
    return trace_spans.load(RECORDED)


def test_interval_arithmetic():
    a = union([(0, 4), (3, 6), (10, 12)])
    assert a == [[0, 6], [10, 12]]
    b = union([(5, 11)])
    assert intersect(a, b) == [[5, 6], [10, 11]]
    assert subtract(a, b) == [[0, 5], [11, 12]]
    assert total(subtract(a, b)) + total(intersect(a, b)) == total(a)
    assert subtract(a, []) == a and subtract([], a) == []


def test_the_idle_split_adds_up_to_the_idle_share(recorded):
    got = trace_idle_split.split(recorded)
    assert 0 < got["idle"] < got["window"]
    assert got["host_work"] + got["starved"] + got["unnamed"] == (
        pytest.approx(got["idle"]))
    # the idle share is reduce_trace's: the window less the union of the
    # operations, which no shift of the device's clock changes
    plane = recorded["device"][0]
    busy = union((o[1], o[1] + o[2]) for o in plane["ops"])
    spans = [(e[1], e[1] + e[2]) for e in plane["ops"] + plane["modules"]]
    window = max(e for _, e in spans) - min(s for s, _ in spans)
    assert got["window"] == pytest.approx(window / 1e9)
    assert got["idle"] == pytest.approx((window - total(busy)) / 1e9)
    # between two waves of the singles the host is at work on the next
    assert got["host_work"] > got["unnamed"]


def test_a_scopes_time_is_found(recorded):
    scopes, runs = trace_scope_time.table(recorded, "wave_body")
    assert runs == len(recorded["device"][0]["modules"]) == 6
    fast = sum(ns for scope, ns in scopes.items()
               if "/tier/fast/" in scope + "/")
    leopard = sum(ns for scope, ns in scopes.items()
                  if "/tier/leopard/" in scope + "/")
    assert fast > leopard > 0
    # the tiers account for nearly all of the program's operations
    assert (fast + leopard) / sum(scopes.values()) > 0.9
    assert any("/probe/node_table" in scope for scope in scopes)
    assert any("/level4" in scope for scope in scopes)


def test_the_clock_shift_puts_every_start_after_its_launch(recorded):
    shift = trace_spans.clock_shift_ns(recorded)
    assert 0 < shift < 5e6  # about a millisecond on the v5e
    launched = {e[3]: e[1] for line in recorded["host"]
                for e in line["events"] if e[0] == trace_spans.LAUNCH_EVENT}
    paired = [m for m in recorded["device"][0]["modules"]
              if m[3] in launched]
    assert paired
    for m in paired:
        assert m[1] + shift >= launched[m[3]]


def test_a_capture_without_scopes_or_spans_gives_none(recorded, monkeypatch):
    bare = {
        "device": [{"plane": p["plane"], "modules": p["modules"],
                    "ops": [["", o[1], o[2], o[3]] for o in p["ops"]]}
                   for p in recorded["device"]],
        "host": [{"line": line["line"], "events": [
            e for e in line["events"] if not e[0].startswith("keto/")]}
            for line in recorded["host"]],
    }
    monkeypatch.setattr(trace_spans, "of_run", lambda ctx: bare)
    ctx = {"trace": {}}
    spec = {"module": "wave_body", "scope": "/tier/fast(/|$)"}
    assert trace_scope_time.read(spec, ctx) is None
    assert trace_idle_split.read({"spans": "host_work"}, ctx) is None
    # and with them, numbers
    monkeypatch.setattr(trace_spans, "of_run", lambda ctx: recorded)
    assert trace_scope_time.read(spec, ctx) > 0
    assert trace_idle_split.read({"spans": "host_work"}, ctx) > 0
    # off the chip there is no capture to look for
    monkeypatch.undo()
    assert trace_spans.of_run({"trace": None}) is None


def test_the_recorded_capture_is_small():
    assert os.path.getsize(RECORDED) < 500_000
    with open(RECORDED) as f:
        data = json.load(f)
    assert set(data) == {"device", "host"}
