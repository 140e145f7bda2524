"""``groups-deep32``: nested-group chains 2 to 32 deep.  The generator
against its configuration, the depth limit against the reference, the
served engine against the reference with the Leopard tier on and off,
what became of the rows tier 0 was asked about, and the control."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import groupmix
import manifest
from graphs import groups_deep as gd
from reference.zanzibar import Reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "groups-deep32.members1k"
MIX = dict(granted_share=0.125, subject_set_share=0.15)


def config():
    return manifest.cell(manifest.load(), CELL).config


@pytest.fixture(scope="module", params=[0, 2**31 + 7])
def world(request):
    return gd.build(config()["rehearsal_graph"], request.param), request.param


def test_the_schema_table_is_the_opl():
    from ketotpu.opl.parser import parse

    namespaces, errors = parse(gd.OPL)
    assert not errors
    got = {ns.name: {gd.RELATIONS.index(r.name): r.subject_set_rewrite
                     for r in ns.relations} for ns in namespaces}
    assert got == {"User": {}, "Group": gd.SCHEMA[gd.NS_G]}


def test_the_configuration_states_its_own_graph():
    from ketotpu.engine.snapshot import device_bytes, resident_bytes

    conf = config()
    g = gd.build(conf["graph"], 0)
    counts = conf["assumed"]["counts"]
    assert len(g) == conf["tuples"] == counts["user_rows"] + counts["edges"]
    assert g.nesting_rows == counts["edges"]
    assert [len(r) for r in g.roots] == [2500, 1250, 625, 312, 156]
    assert g.standalone == 16 and conf["reduced"] == []
    groups = device_bytes(
        tuples=conf["tuples"], nodes=counts["nodes"], edges=counts["edges"],
        subjects=counts["subjects"],
        leopard_pairs=counts["closure_pairs"])
    assert resident_bytes(groups) == conf["device_bytes"]["reckoned"]
    for name, nbytes in conf["device_bytes"]["by_group"].items():
        assert groups[name]["padded"] == nbytes, name
    measured = conf["device_bytes"]["measured_after_init"]
    assert abs(measured - conf["device_bytes"]["reckoned"]) < 0.02 * measured
    pairs = conf["daemon"]["leopard"]["max_pairs"]
    assert counts["closure_pairs"] <= pairs == 1 << 24


def test_a_seed_moves_the_users_and_never_the_counts(world):
    g, seed = world
    other = gd.build(config()["rehearsal_graph"], seed + 1)
    assert len(g) == len(other)
    for c in ("ns", "obj", "rel", "is_set", "s_obj"):
        assert (g.cols[c] == other.cols[c]).all(), c
    assert (g.cols["subj"] != other.cols["subj"]).any()
    # a group holds a user once
    users = g.cols["is_set"] == 0
    pairs = ((g.cols["obj"][users].astype(np.int64) << 32)
             | g.cols["subj"][users])
    assert len(np.unique(pairs)) == int(users.sum())


def test_depth_32_is_the_least_at_which_the_reference_decides_the_deepest(
        world):
    """A user of a 32-deep chain's deepest group is found from its root
    at ``max_read_depth`` 32 and not at 31: the configuration's limit,
    and its control.  (leopard/closure.py's rule asks 33 for the pair.)"""
    g, seed = world
    rng = np.random.default_rng(seed)
    roots = g.roots[g.depths.index(32)]
    deepest = g.members_of(rng, roots + 31)
    for depth, want in ((32, True), (31, False)):
        ref = Reference(g.cols, gd.SCHEMA, max_depth=depth)
        assert [ref.check(gd.NS_G, int(r), gd.R_MEMBERS, int(u))
                for r, u in zip(roots, deepest)] == [want] * len(roots)


def test_the_control_comes_out_wrong(world):
    g, seed = world
    rows = groupmix.rows(g, MIX, np.random.default_rng(seed), 1024)
    ref = Reference(g.cols, gd.SCHEMA, max_depth=32)
    control = Reference(g.cols, gd.SCHEMA,
                        max_depth=config()["control"]["max_depth"])
    wrong = sum(a != b for a, b in zip(
        groupmix.reference_verdicts(control, rows),
        groupmix.reference_verdicts(ref, rows)))
    assert wrong >= 20  # the deepest class's grants: a fortieth of the rows


def test_a_fifth_of_the_rows_on_each_depth_class(world):
    g, seed = world
    rows = groupmix.rows(g, MIX, np.random.default_rng(seed), 1024)
    depth_of = np.zeros(g.G, np.int64)
    for d, roots in zip(g.depths, g.roots):
        depth_of[roots] = d
    per = np.bincount(np.searchsorted(g.depths, depth_of[rows["obj"]]))
    assert list(per) == [206, 205, 205, 204, 204]  # granted and rest dealt
    assert (rows["group"] >= 0).sum() == round(0.15 * (1024 - 128))


@pytest.fixture(scope="module")
def served():
    """The served engine (fused wave, body run eagerly) on the rehearsal
    graph at the configuration's limits, with the index on and off."""
    from ketotpu.engine import fused as fdx
    from ketotpu.engine.tpu import DeviceCheckEngine

    conf = config()
    g = gd.build(conf["rehearsal_graph"], 5)
    store, manager = g.server_store()
    limits = conf["limits"]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fdx, "_run_wave", fdx._wave_body)
        mp.setenv("KETO_NO_ADAPTIVE", "1")
        yield g, {
            on: DeviceCheckEngine(
                store, manager, fused_dispatch=True, fused_retry_lanes=0,
                max_depth=limits["max_read_depth"],
                max_width=limits["max_read_width"],
                frontier=2048, arena=4096,
                leopard={"enabled": on, **conf["daemon"]["leopard"]})
            for on in (True, False)}


def test_the_served_engine_is_the_reference_with_the_leopard_tier_on_and_off(
        served):
    from ketotpu.api.types import RelationTuple

    g, engines = served
    rows = groupmix.rows(g, MIX, np.random.default_rng(11), 1024)
    queries = [RelationTuple.from_json({
        "namespace": "Group", "object": f"g{int(rows['obj'][i])}",
        "relation": "members", **g.subject_json(groupmix.subject(rows, i))})
        for i in range(len(rows["obj"]))]
    limits = config()["limits"]
    want = groupmix.reference_verdicts(
        Reference(g.cols, gd.SCHEMA, max_depth=limits["max_read_depth"],
                  max_width=limits["max_read_width"]), rows)
    on, off = engines[True], engines[False]
    assert on.batch_check(queries) == want
    assert off.batch_check(queries) == want
    assert on.fallbacks == off.fallbacks == 0
    # tier 0 answered clean roots and left the tainted ones and the
    # deepest grants to the 32-level BFS, in one wave's program
    rows_of = on.leopard_rows
    assert sum(rows_of.values()) == len(queries) == on.leopard_answered + (
        rows_of["tainted"] + rows_of["beyond_depth"])
    assert rows_of["answered"] > 0.6 * len(queries)
    assert rows_of["tainted"] > 0 and rows_of["beyond_depth"] > 0
    assert rows_of["ineligible"] == rows_of["dirty"] == 0
    assert set(off.leopard_rows.values()) == {0}


def test_the_rehearsal_is_correct_and_the_control_is_not():
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", CELL, "--seed", "2600000123", "--seconds", "4",
           "--trace", "1", "--rehearsal", "--control"]
    done = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                          timeout=1200)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    assert result["compared"]["wrong_answers"]["value"] == 0
    lines = [json.loads(x) for x in done.stdout.splitlines()[:-1]]
    control = next(d for d in lines if d.get("bench") == "control")
    assert control["correct"] is False
    assert control["compared"]["wrong_answers"]["value"] > 0
    # off the chip the trace readers find nothing; the counter and the
    # phase are read all the same
    assert 60 < result["metrics"]["leopard_answered_pct.deep32"]["value"] < 95
    assert result["metrics"]["closure_build_s"]["value"] > 0


def test_the_leopard_roofline_reckons_lookups_from_the_semantics():
    from readers import leopard_roofline

    assert leopard_roofline.lookup_bytes(9_347_534, 8) == 24 * 8
    assert leopard_roofline.lookup_bytes(2, 8) == 8
    spec = manifest.metric_spec("leopard_roofline.deep32")
    ctx = {
        "trace": {"window_s": 2.0}, "peak": {"hbm_bytes_per_s": 819e9},
        "device": {"kind": "TPU v5 lite"},
        "delta": {'keto_leopard_rows_total{outcome="answered"}': 800.0,
                  'keto_leopard_rows_total{outcome="beyond_depth"}': 200.0,
                  'keto_leopard_rows_total{outcome="tainted"}': 5000.0,
                  "window.seconds": 10.0},
    }
    data = {"device": [{"modules": [["jit__wave_body", 0, 1, 0, 7]],
                        "ops": [["jit(_wave_body)/tier/leopard/probe/pairs/x",
                                 0, 1000, 7],
                                ["jit(_wave_body)/tier/fast/level0/y",
                                 0, 9000, 7]]}]}
    import trace_spans
    real = trace_spans.of_run
    trace_spans.of_run = lambda ctx: data
    try:
        got = leopard_roofline.read(spec, ctx)
    finally:
        trace_spans.of_run = real
    # 100 lookups a second of 192 bytes over 819 GB/s, over 1 us a 2 s
    want = 100.0 * (100 * 192 / 819e9) / (1000 / 1e9 / 2.0)
    assert got == pytest.approx(want)
