"""The four-chip cell (``drive-10m-mesh4.batch1k``, PR 29): the manifest
loads it with its metrics, its rehearsal runs the mesh path on four CPU
devices and comes out correct (its control not), and the two readers it
brings read what they should: on hand-made input, and on a cut of a
four-plane capture from the v5e-4 (``recorded_spans_mesh4.json``, PR 29,
call A: ``trace_spans.shrink(load(<x>.xplane.pb), modules=4)`` of this
cell's 3 s capture, then of every plane the first execution of each of the
two programs, its operations summed by scope and laid end to end: the
cutter keeps every operation, and two executions a plane are 1.9 MB)."""

import os

import pytest

import manifest
import trace_spans
from readers import scrape_skew, trace_roofline_chips, trace_scope_time
from test_faults import run_cell

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "drive-10m-mesh4.batch1k"
RECORDED = os.path.join(HERE, "recorded_spans_mesh4.json")
MESH_METRICS = {
    "mesh_fast_ms.batch", "mesh_general_ms.batch", "mesh_route_ms.batch",
    "mesh_merge_ms.batch", "mesh_roofline.batch", "mesh_shard_skew.batch",
    "mesh_lock_wait_ms.batch",
}


def test_the_manifest_loads_the_cell_with_its_metrics():
    doc = manifest.load()
    cell = manifest.cell(doc, CELL)
    assert cell.chips == 4 and cell.config["engine"]["mesh_devices"] == 4
    assert {m["name"] for m in cell.end_to_end} >= {"checks_per_s", "setup_s"}
    names = {m["name"] for m, _, _ in cell.per_layer}
    assert MESH_METRICS <= names
    # what every cell that moves the batch rate reports, read on four planes
    assert {"device_idle_pct.batch", "idle_host_work_pct.batch",
            "engine_host_ms.batch", "host_pause_s.batch",
            "compiles_in_window.batch", "oracle_fallback_pct.batch",
            "projection_s", "compile_s", "hbm_peak_bytes"} <= names
    # and no other cell looks for the sharded programs
    one_chip = manifest.cell(doc, "drive-10m.batch1k")
    assert not MESH_METRICS & {m["name"] for m, _, _ in one_chip.per_layer}
    # the two cells differ in the deployment alone
    assert cell.traffic == one_chip.traffic
    for key in ("graph", "tuples", "limits", "control", "batch_rows",
                "rehearsal_graph"):
        assert cell.config[key] == one_chip.config[key], key
    # but for the capture's length: four planes take four times as long
    # to write, and run.py waits span + 120 s for the answer
    daemon = dict(cell.config["daemon"])
    assert daemon.pop("observability") == {"profiler": {"max_seconds": 1.0}}
    assert daemon == one_chip.config["daemon"]


def test_the_rehearsal_runs_the_mesh_and_the_control_fails():
    sound = run_cell(CELL, "", 8, more=["--control"])
    assert sound["correct"] is True
    assert sound["device"] == {"platform": "cpu", "kind": "cpu", "count": 4,
                               "memory_peak_bytes": 0}
    assert sound["compared"]["wrong_answers"]["value"] == 0
    assert sound["compared"]["oracle_fallback_share"]["value"] == 0.0
    assert "checks_per_s" in sound["metrics"]
    # the sharded programs did the work, not a one-device stand-in
    assert set(sound["earlier"]["compiles"]["per_fn"]) == {
        "sharded_check", "sharded_general"}
    assert sound["earlier"]["counters_in_window"][
        "keto_fused_waves_total"] == 0
    control = sound["earlier"]["control"]
    assert control["correct"] is False
    assert control["compared"]["wrong_answers"]["value"] > 0


def test_the_skew_is_the_largest_part_over_the_mean():
    spec = {"series": "keto_mesh_shard_batches", "label": "shard"}
    delta = {'keto_mesh_shard_batches{shard="%d"}' % i: v
             for i, v in enumerate([100.0, 120.0, 90.0, 90.0])}
    delta['keto_mesh_shard_fallbacks{shard="0"}'] = 7.0
    assert scrape_skew.read(spec, {"delta": delta}) == pytest.approx(1.2)
    even = dict.fromkeys(delta, 5.0)
    assert scrape_skew.read(spec, {"delta": even}) == pytest.approx(1.0)
    # one chip reports shard "0" alone; nothing moved: nothing to read
    one = {'keto_mesh_shard_batches{shard="0"}': 40.0}
    assert scrape_skew.read(spec, {"delta": one}) is None
    assert scrape_skew.read(spec, {"delta": dict.fromkeys(delta, 0.0)}) is None
    assert scrape_skew.read(spec, {"delta": {}}) is None


def test_the_roofline_divides_by_every_chips_bandwidth():
    spec = {"module": "sharded_(fast|general)_run", "bytes_per_row": 16}
    trace = {"window_s": 2.0, "modules": {
        "jit__sharded_fast_run": {"seconds": 0.5, "executions": 5.0},
        "jit__sharded_general_run": {"seconds": 1.0, "executions": 5.0},
        "jit__wave_body": {"seconds": 9.0, "executions": 1.0}}}
    ctx = {"trace": trace, "peak": {"hbm_bytes_per_s": 800e9},
           "rows_per_unit": 100.0, "units_per_s": 2e6,
           "device": {"kind": "TPU v5 lite", "count": 4}}
    # 100 rows x 16 B x 2e6/s = 3.2 GB/s over 4 x 800 GB/s = 0.001 s/s,
    # over 1.5 s of the two programs in 2 s traced = 0.75 s/s
    assert trace_roofline_chips.read(spec, ctx) == pytest.approx(
        100.0 * 0.001 / 0.75)
    one = dict(ctx, device={"kind": "TPU v5 lite", "count": 1})
    assert trace_roofline_chips.read(spec, one) == pytest.approx(
        4 * trace_roofline_chips.read(spec, ctx))
    # a parent without the programs, a run off the chip: nothing to read
    bare = dict(ctx, trace=dict(trace, modules={}))
    assert trace_roofline_chips.read(spec, bare) is None
    assert trace_roofline_chips.read(spec, dict(ctx, trace=None)) is None
    with pytest.raises(KeyError, match="peaks.json"):
        trace_roofline_chips.read(spec, dict(ctx, peak=None))


@pytest.fixture(scope="module")
def recorded():
    if not os.path.isfile(RECORDED):
        pytest.skip("no recorded four-plane capture beside the tests")
    return trace_spans.load(RECORDED)


def test_the_recorded_capture_has_four_planes_and_both_programs(recorded):
    assert os.path.getsize(RECORDED) < 500_000
    assert len(recorded["device"]) == 4
    for plane in recorded["device"]:
        names = {m[0].split("(")[0] for m in plane["modules"]}
        assert names == {"jit__sharded_fast_run", "jit__sharded_general_run"}


def test_the_mesh_scopes_are_found_on_every_plane(recorded):
    both = "sharded_(fast|general)_run"
    scopes, runs = trace_scope_time.table(recorded, both)
    assert runs == sum(len(p["modules"]) for p in recorded["device"])
    route = sum(ns for s, ns in scopes.items() if "/mesh/route" in s + "/")
    merge = sum(ns for s, ns in scopes.items() if "/mesh/merge" in s + "/")
    assert route > 0 and merge > 0
    assert route + merge < sum(scopes.values())
    fast, _ = trace_scope_time.table(recorded, "sharded_fast_run")
    assert all("/tier/fast" in s + "/" for s in fast if "/mesh/" in s)
    general, _ = trace_scope_time.table(recorded, "sharded_general_run")
    assert any("/tier/general/" in s + "/" for s in general)
