"""What the fast BFS's folded levels ran at
(``keto_fused_fast_rung_levels_total{rung}``, engine ``fast_rung_levels``):
each wave adds one for every folded level, by the rung the program
returned for it beside its occupancy counts.

Held on the deep cell's rehearsal graph (chains 2-32 deep, the closure
index on): a wave at depth 32 folds levels 3-30, and tier 0 leaves the
BFS few enough rows that the narrow rungs carry them.
"""

import pathlib
import sys

import numpy as np
import pytest

from ketotpu.api.types import RelationTuple
from ketotpu.engine import fastpath as fp
from ketotpu.engine import fused as fdx
from ketotpu.engine.tpu import DeviceCheckEngine

BENCH = pathlib.Path(__file__).resolve().parent.parent / "benchmark"


@pytest.fixture
def deep(monkeypatch):
    import json

    sys.path.insert(0, str(BENCH))
    try:
        import groupmix
        from graphs import groups_deep as gd
    finally:
        sys.path.remove(str(BENCH))
    conf = json.loads((BENCH / "configs/groups-deep32.json").read_text())
    world = gd.build(conf["rehearsal_graph"], 5)
    store, manager = world.server_store()
    monkeypatch.setenv("KETO_NO_ADAPTIVE", "1")
    limits = conf["limits"]
    eng = DeviceCheckEngine(
        store, manager, fused_dispatch=True, fused_retry_lanes=0,
        max_depth=limits["max_read_depth"], max_width=limits["max_read_width"],
        frontier=2048, arena=4096, leopard=conf["daemon"]["leopard"],
    )
    rows = groupmix.rows(world, dict(granted_share=0.125,
                                     subject_set_share=0.15),
                         np.random.default_rng(11), 200)
    queries = [RelationTuple.from_json({
        "namespace": "Group", "object": f"g{int(rows['obj'][i])}",
        "relation": "members",
        **world.subject_json(groupmix.subject(rows, i))})
        for i in range(len(rows["obj"]))]
    return eng, queries


def test_one_wave_counts_each_folded_level_once(deep, monkeypatch):
    eng, queries = deep
    results = []
    run = fdx.run_fused_wave

    def keep(g, qpack, **kw):
        out = run(g, qpack, **kw)
        results.append((qpack.shape[1], kw["fast_sched"], out))
        return out

    monkeypatch.setattr(fdx, "run_fused_wave", keep)
    before = dict(eng.fast_rung_levels)
    eng.batch_check(queries)
    assert len(results) == 1  # one wave
    q, sched, out = results[0]
    folded = fp.folded_levels(sched)
    assert fp.folded_runs(sched) == ((3, 31),) and folded == 28
    codes = np.asarray(out)[q + len(sched):q + len(sched) + folded]
    delta = {r: eng.fast_rung_levels[r] - before[r] for r in fp.RUNGS}
    assert sum(delta.values()) == folded
    assert delta == {r: int((codes == i).sum())
                     for i, r in enumerate(fp.RUNGS)}
    # tier 0 answers most rows: the BFS's deep levels hold a quarter
    # rung's worth of items and never need the level's full size
    assert delta["quarter"] > 0 and delta["full"] == 0
    assert eng.leopard_rows["answered"] > len(queries) // 2
    eng.batch_check(queries)
    assert sum(eng.fast_rung_levels.values()) == sum(before.values()) + 2 * 28
