"""A single Check's cycle (PR 37): the seven per-layer metrics that read
it, which cells load them, and the reader of what no server stage
covers."""

import json
import os

import pytest

import manifest
from readers import closed_loop_rest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CYCLE = ("receive_ms.single", "send_ms.single", "server_ms.single",
         "device_compute_ms.single", "wake_ms.single",
         "interp_wait_ms.single", "unseen_ms.single")
SEND = 'keto_rpc_stage_seconds_sum{op="check",stage="send"}'
OUTCOME = 'keto_request_outcome_seconds_sum{op="check",outcome="ok"}'


def _spec():
    with open(os.path.join(BENCH, "metrics", "unseen_ms.json")) as f:
        return json.load(f)


def test_the_cycle_loads_in_the_singles_and_no_other_cell():
    doc = manifest.load()
    for w in doc["workloads"]:
        got = {m["name"] for m, _, _ in manifest.cell(doc, w["name"]).per_layer}
        if w["name"] == "drive-10m.singles":
            assert set(CYCLE) <= got
        else:
            assert not set(CYCLE) & got, w["name"]


def test_the_spec_counts_the_singles_clients():
    with open(os.path.join(BENCH, "traffic", "singles.json")) as f:
        mix = json.load(f)
    assert _spec()["clients"] == mix["processes"] * mix["threads_per_process"]


@pytest.mark.parametrize("delta, rate, want", [
    # 64 clients at 500/s: 128 ms a cycle; the server holds 100 ms of a
    # request (2,000 requests, 200 s) and its send 3 ms (6 s)
    ({OUTCOME: 200.0, SEND: 6.0, "window.requests": 2000.0}, 500.0, 25.0),
    # a program without stage send: nothing to read
    ({OUTCOME: 200.0, "window.requests": 2000.0}, 500.0, None),
    ({OUTCOME: 200.0, SEND: 6.0, "window.requests": 0.0}, 500.0, None),
    ({OUTCOME: 200.0, SEND: 6.0, "window.requests": 2000.0}, 0.0, None),
])
def test_closed_loop_rest_is_the_cycle_less_the_server(delta, rate, want):
    got = closed_loop_rest.read(_spec(), {"delta": delta, "units_per_s": rate})
    assert got == (None if want is None else pytest.approx(want))
