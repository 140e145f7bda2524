"""The harness's own arithmetic: percentiles, the loader's refusals, the
trace reduction on a small recorded trace."""

import copy
import json
import os

import pytest

import manifest
import reduce_trace
import stats

HERE = os.path.dirname(os.path.abspath(__file__))


# -- percentiles ------------------------------------------------------------


def test_percentile_needs_ten_samples_beyond():
    lat = [i / 1000 for i in range(1, 201)]  # 1..200 ms
    assert stats.percentile(lat, 0, 0.95) == pytest.approx(0.190)
    with pytest.raises(stats.TooFewSamples):
        stats.percentile(lat[:199], 0, 0.95)  # 9 beyond
    with pytest.raises(stats.TooFewSamples):
        stats.percentile(lat, 0, 0.99)  # 2 beyond


def test_failures_count_as_misses():
    lat = [i / 1000 for i in range(1, 201)]
    # 5 failures push the 95th percentile up the sorted answers
    assert stats.percentile(lat[:195], 5, 0.95) == pytest.approx(0.190)
    assert stats.percentile(lat, 5, 0.95) == pytest.approx(0.195)
    with pytest.raises(stats.TooFewSamples):
        stats.percentile(lat[:150], 50, 0.95)  # the tail is failures


def test_rate_counts_the_work_done_inside_the_window():
    records = [(0.0, 1.0, True, 1024), (9.5, 1.0, True, 1024),
               (3.0, 1.0, False, 1024)]
    # the request in flight at the close counts for its half inside
    assert stats.rate(records, 10.0) == pytest.approx(1.5 * 1024 / 10.0)
    assert stats.rate(records[:1], 10.0) == pytest.approx(102.4)
    assert stats.rate(records[2:], 10.0) == 0.0  # a failure is no work


def test_the_summary_says_when_the_slow_requests_were_sent():
    records = [(i * 0.1, 0.1, True, 1) for i in range(100)]
    records[40:44] = [(4.0 + i * 0.01, 1.0, True, 1) for i in range(4)]
    got = stats.summary(records, 10.0)["slow"]
    assert got["count"] == 4 and got["over_ms"] == pytest.approx(200.0)
    assert got["sent_s"] == pytest.approx([4.0, 4.02, 4.03])
    assert got["slowest_sent_s"] == pytest.approx(4.0)
    assert stats.summary(records[:40], 10.0)["slow"]["count"] == 0


# -- the loader -------------------------------------------------------------


def _doc():
    return manifest.load()


def _write(tmp_path, doc):
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    return str(path)


def test_the_committed_manifest_loads_every_cell():
    doc = _doc()
    for w in doc["workloads"]:
        cell = manifest.cell(doc, w["name"])
        assert cell.per_layer and len(cell.end_to_end) >= 2
        for m, spec, _ in cell.per_layer:
            moved = next(e for e in doc["end_to_end"]
                         if e["name"] == m["moves"])
            assert w["name"] in moved.get("workloads", [w["name"]])


@pytest.mark.parametrize("field,value", [
    ("name", "has space"), ("name", "a/b"), ("unit", "checks per s"),
    ("unit", ""), ("better", "faster"), ("source", "guess"),
])
def test_a_bad_name_or_unit_is_refused(tmp_path, field, value):
    doc = copy.deepcopy(_doc())
    doc["end_to_end"][0][field] = value
    with pytest.raises(manifest.ManifestError):
        manifest.load(_write(tmp_path, doc))


def test_a_cell_without_its_files_is_refused():
    doc = copy.deepcopy(_doc())
    doc["workloads"][0]["traffic"] = "no-such-mix"
    with pytest.raises(manifest.ManifestError, match="no file"):
        manifest.cell(doc, doc["workloads"][0]["name"])
    doc = copy.deepcopy(_doc())
    doc["workloads"][0]["config"] = "no-such-config"
    with pytest.raises(manifest.ManifestError, match="no configuration"):
        manifest.cell(doc, doc["workloads"][0]["name"])


def test_an_unknown_reader_kind_or_statistic_is_refused():
    with pytest.raises(manifest.ManifestError, match="unknown reader"):
        manifest.reader("no_such_reader")
    with pytest.raises(manifest.ManifestError, match="unknown kind"):
        manifest.kind("no_such_kind")
    with pytest.raises(manifest.ManifestError, match="unknown statistic"):
        manifest.statistic("no_such_statistic")
    with pytest.raises(manifest.ManifestError):
        manifest.reader("../run")


def test_a_split_metric_reads_the_file_of_its_stem():
    whole = manifest.metric_spec("wave_rows")
    assert manifest.metric_spec("wave_rows.single") == whole
    assert manifest.metric_spec("wave_rows.some-later-cell") == whole
    assert set(whole) == {"reader", "over", "per", "what"}
    with pytest.raises(manifest.ManifestError, match="no file"):
        manifest.metric_spec("no_such_metric.single")


def test_a_mix_that_sends_other_rows_than_the_configuration_states(
        monkeypatch):
    doc = _doc()
    real = manifest._json

    def other_rows(path):
        got = real(path)
        return {**got, "rows": 4096} if path.endswith("batch1k.json") else got

    monkeypatch.setattr(manifest, "_json", other_rows)
    with pytest.raises(manifest.ManifestError, match="rows a batch"):
        manifest.cell(doc, "drive-10m.batch1k")
    manifest.cell(doc, "drive-10m.singles")  # sends no batch: nothing to hold


def test_the_statistics_read_a_windows_records():
    records = [(i * 0.01, (i + 1) / 1000, True, 1) for i in range(200)]
    assert manifest.statistic("percentile_ms").value(
        {"q": 0.95}, records, 10.0) == pytest.approx(190.0)
    assert manifest.statistic("rate").value(
        {}, records, 10.0) == pytest.approx(20.0)
    records[0] = (0.0, 0.001, False, 1)  # a failure is slower than any answer
    assert manifest.statistic("percentile_ms").value(
        {"q": 0.95}, records, 10.0) == pytest.approx(191.0)


def test_a_metric_without_an_end_to_end_metric_to_move_is_refused(tmp_path):
    doc = copy.deepcopy(_doc())
    doc["per_layer"][0]["moves"] = "nothing"
    with pytest.raises(manifest.ManifestError, match="moves"):
        manifest.load(_write(tmp_path, doc))


# -- the trace reduction ----------------------------------------------------


def test_reduction_of_a_hand_made_trace():
    planes = [
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [["work", 0, 10_000_000]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_run_fused_wave(1)", 1_000_000, 2_000_000],
                ["jit_run_fused_wave(1)", 5_000_000, 2_000_000],
                ["jit__run_expand(2)", 8_000_000, 1_000_000]]},
            {"name": "XLA Ops", "events": [
                ["%fusion.1 = s32[8] fusion(s32[9] %g__edge_hi__.1)",
                 1_000_000, 1_000_000],
                ["%fusion.2 = s32[8] fusion(s32[9] %g__nt_ptr__.1)",
                 1_500_000, 1_500_000],  # overlaps fusion.1
                ["%fusion.7 = s32[8] fusion(s32[9] %g__edge_hi__.1)",
                 5_000_000, 2_000_000],
                ["%sort.3 = s32[8] sort(s32[8] %x)", 8_000_000, 1_000_000]]},
        ]},
    ]
    got = reduce_trace.reduce(planes)
    # the device's own span: the host's 10 ms do not count
    assert got["window_s"] == pytest.approx(0.008)
    assert got["busy_s"] == pytest.approx(0.005)  # the union, not the sum
    wave = got["modules"]["jit_run_fused_wave"]
    assert wave == {"seconds": pytest.approx(0.004), "executions": 2}
    assert got["breakdown"]["device_ops"][0] == [
        "fusion(edge_hi)->s32[8]", pytest.approx(0.003)]
    gaps = dict(got["breakdown"]["idle_gaps"])
    assert gaps["after jit_run_fused_wave, before jit_run_fused_wave"] == (
        pytest.approx(0.002))
    assert len(got["breakdown"]["device_ops"]) <= 10


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no device plane"):
        reduce_trace.reduce([{"name": "/host:CPU", "lines": []}])


def test_reduction_of_the_recorded_trace():
    """A cut of a trace of drive-10m.batch1k on the v5e (PR 26: 11 fused
    waves, the first 400 operations; ``reduce_trace.shrink``), with the
    numbers the reduction gave when it was recorded."""
    path = os.path.join(HERE, "recorded_trace.json")
    with open(os.path.join(HERE, "recorded_trace.expected.json")) as f:
        want = json.load(f)
    got = reduce_trace.reduce(reduce_trace.load(path))
    assert got["busy_s"] == pytest.approx(want["busy_s"])
    assert got["window_s"] == pytest.approx(want["window_s"])
    assert 0 < got["busy_s"] <= got["window_s"]
    for name, m in want["modules"].items():
        assert got["modules"][name]["seconds"] == pytest.approx(m["seconds"])
        assert got["modules"][name]["executions"] == m["executions"]
    assert any("wave_body" in k for k in got["modules"])
