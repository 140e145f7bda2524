"""The fast BFS's folded levels and their rungs (``fastpath._fused_body``).

A run of equal levels runs as one loop whose body picks, from the live
count, the smallest of a quarter of the wave's rows, its rows, or the
level's own frontier.  Held here against a plain unrolled loop of
``expand_phase`` + ``pack_phase`` at the full schedule, kept in this file:
``found``, ``over``, ``dirty`` and the per-level occupancy must be the same
bits, on deep chains (the narrow rungs), on a fan-out past the quarter
rung's arena (the full-size redo), with a delta overlay (dirty bits) and
with rewrite columns.  A schedule of depth 7 or less (every depth-5 one,
worst case or adaptive) keeps the unrolled program; the adaptive ladder's
equal levels fold from depth 8.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ketotpu.api.types import RelationTuple
from ketotpu.engine import fastpath as fp
from ketotpu.engine import fused as fdx
from ketotpu.engine.tpu import DeviceCheckEngine
from ketotpu.opl.parser import parse
from ketotpu.storage import InMemoryTupleStore, StaticNamespaceManager
from ketotpu.utils.synth import (
    build_deep_groups, build_synth, synth_queries_mixed,
)

T = RelationTuple.from_string
QUARTER, ROOTS, FULL = (fp.RUNGS.index(r) for r in ("quarter", "roots", "full"))

OPL = """
import { Namespace } from '@ory/keto-namespace-types'
class User implements Namespace {}
class Group implements Namespace {
  related: { members: (User | SubjectSet<Group, "members">)[] }
}
"""


@functools.partial(
    jax.jit, static_argnames=("arena", "nxt_f", "max_width", "probe_only")
)
def _plain_level(g, s, *, arena, nxt_f, max_width, probe_only):
    NS, R = g["f_direct_ok"].shape
    children, q_found, q_over, q_dirty = fp.expand_phase(
        g, s, arena=arena, max_width=max_width, probe_only=probe_only
    )
    nxt, q_over = fp.pack_phase(
        children, q_found, q_over, frontier=nxt_f, ns_dim=NS, rel_dim=R
    )
    return dict(nxt, q_found=q_found, q_over=q_over, q_dirty=q_dirty,
                q_subj=s["q_subj"])


def unrolled(g, enc, act, schedule, max_width):
    """The reference: every level at its full (frontier, arena)."""
    s = fp.init_state(*enc, act, frontier=schedule[0][0])
    s["f_depth"] = jnp.minimum(s["f_depth"], len(schedule))
    occ = []
    last = len(schedule) - 1
    for i, (_, a) in enumerate(schedule):
        occ.append(int(jnp.sum(s["f_qid"] >= 0)))
        s = _plain_level(
            g, s, arena=a, nxt_f=schedule[i + 1][0] if i < last else 1,
            max_width=max_width, probe_only=i == last,
        )
    return (np.asarray(s["q_found"]), np.asarray(s["q_over"]),
            np.asarray(s["q_dirty"]), occ)


def folded(g, enc, act, schedule, max_width):
    res, occ = fp._run_fused(
        g, *enc, jnp.asarray(act), schedule=schedule, max_width=max_width
    )
    occ = np.asarray(occ)
    return (np.asarray(res.found), np.asarray(res.over),
            np.asarray(res.dirty), list(occ[:len(schedule)]),
            occ[len(schedule):])


def encoded(eng, queries, q):
    """The queries' columns padded to ``q`` rows; padding inactive."""
    cols = eng._encode(eng.snapshot(), queries, 0)
    pad = q - len(queries)
    return tuple(np.pad(c, (0, pad), constant_values=-1 if i < 4 else 0)
                 for i, c in enumerate(cols))


def assert_same(g, enc, act, schedule, max_width=100):
    want = unrolled(g, enc, act, schedule, max_width)
    got = folded(g, enc, act, schedule, max_width)
    for name, w, o in zip(("found", "over", "dirty", "occ"), want, got):
        assert np.array_equal(np.asarray(w), np.asarray(o)), name
    assert len(got[4]) == fp.folded_levels(schedule)
    return want, got[3], got[4]


def engine(store, nsm, depth):
    eng = DeviceCheckEngine(store, nsm, max_depth=depth, frontier=8192,
                            arena=16384, leopard={"enabled": False})
    eng.snapshot()
    return eng


@pytest.mark.parametrize("seed", range(4))
def test_a_narrow_arena_merges_as_the_full_one(seed):
    """A narrow rung's children are the full level's first slots, the rest
    dead; sized for the full arena, the hash merge keeps the same owners,
    so the packed frontier is the same, duplicates and collisions alike."""
    rng = np.random.default_rng(seed)
    narrow, full, q = 64, 1024, 16
    cols = dict(
        qid=rng.integers(-1, q, narrow), ns=rng.integers(0, 2, narrow),
        obj=rng.integers(0, 6, narrow), rel=rng.integers(0, 2, narrow),
        d=rng.integers(0, 9, narrow), skip=rng.random(narrow) < 0.5,
        force=rng.random(narrow) < 0.5,
    )
    small = {k: jnp.asarray(v, jnp.int32 if v.dtype != bool else bool)
             for k, v in cols.items()}
    padded = {k: jnp.pad(v, (0, full - narrow),
                         constant_values=-1 if k == "qid" else 0)
              for k, v in small.items()}
    found = jnp.asarray(rng.random(q) < 0.2)
    over = jnp.zeros((q,), bool)
    kw = dict(frontier=96, ns_dim=2, rel_dim=2)
    want = fp.pack_phase(padded, found, over, **kw)
    got = fp.pack_phase(small, found, over, merge_arena=full, **kw)
    for k in want[0]:
        assert np.array_equal(want[0][k], got[0][k]), k
    assert np.array_equal(want[1], got[1])


def test_folded_runs_of_the_schedules_in_use():
    # depth 5, worst case (Drive's cells and the singles): nothing equal
    for q in (256, 1024):
        assert fp.folded_runs(fp.level_schedule(q, 8192, 16384, 5)) == ()
    # depth 32 at 1,024 rows: levels 3-30 (6q / 12q), then the probe level
    deep = fp.level_schedule(1024, 8192, 16384, 32)
    assert fp.folded_runs(deep) == ((3, 31),)
    assert fp.folded_levels(deep) == 28
    # the adaptive ladder's base rung: levels 1-3 are (q, 2q) at depth 5,
    # too few to fold; levels 1-6 at depth 8
    ladder = fp.level_schedule(256, 8192, 16384, 5, mults=(1, 1, 1, 1, 1))
    assert fp.folded_runs(ladder) == ()
    ladder = fp.level_schedule(256, 8192, 16384, 8, mults=(1,) * 8)
    assert fp.folded_runs(ladder) == ((1, 7),)
    # a run whose successor packs into another frontier leaves its last
    # level unrolled
    assert fp.FOLD_MIN == 6
    head, tail = ((8, 32),), ((24, 48), (24, 8))
    assert fp.folded_runs(head + ((16, 32),) * 7 + tail) == ((1, 7),)
    assert fp.folded_runs(head + ((16, 32),) * 6 + tail) == ()


def test_deep_chains_take_the_narrow_rungs():
    """Chains of 32 asked from 12-32 groups above their users: one item a
    row, so the levels run at the quarter or the roots rung, never full;
    a tier-0-style active mask leaves a quarter rung's worth of rows."""
    deep = build_deep_groups(depth=32, n_chains=6, n_users=24, seed=3)
    eng = engine(deep.store, deep.manager, 32)
    rng = np.random.default_rng(5)
    queries = [
        T(f"Group:g{int(rng.integers(6))}_{int(rng.integers(21))}"
          f"#members@u{int(rng.integers(24))}")
        for _ in range(64)
    ]
    q = 64
    enc = encoded(eng, queries, q)
    schedule = fp.level_schedule(q, 8192, 16384, 32)
    g = eng._served_arrays()
    seen = set()
    for share in (1.0, 0.2):
        act = np.arange(q) < int(share * q)
        want, occ, rungs = assert_same(g, enc, act, schedule)
        assert want[0].any() and not want[1].any()
        seen |= set(rungs.tolist())
        lo, hi = fp.folded_runs(schedule)[0]
        for n, code in zip(occ[lo:hi], rungs):
            assert code == (QUARTER if n <= q // 4 else ROOTS), (n, code)
    assert seen == {QUARTER, ROOTS}


def fan_out_store(k):
    """h0 > h1 > ... > h6, h6 holds ``k`` groups w<j>, each a chain
    w<j> > x<j> > y<j> with a user at its end."""
    tuples = [f"Group:h{i}#members@Group:h{i + 1}#members" for i in range(6)]
    for j in range(k):
        tuples += [f"Group:h6#members@Group:w{j}#members",
                   f"Group:w{j}#members@Group:x{j}#members",
                   f"Group:x{j}#members@Group:y{j}#members",
                   f"Group:y{j}#members@User:y{j}u"]
    tuples.append("Group:h0#members@User:h0u")
    store = InMemoryTupleStore()
    store.write_relation_tuples(*[T(t) for t in tuples])
    namespaces, errs = parse(OPL)
    assert not errs, errs
    return store, StaticNamespaceManager(namespaces)


@pytest.mark.parametrize("rows", [2, 4])
def test_fan_out_past_the_quarter_arena_redoes_at_full(rows):
    """Rows walk to h6 at the quarter rung (8 slots, 16 of arena); h6's
    60 children a row do not fit, so that level runs again at full size,
    and the next ones hold more items than rows.  Four rows' 240 children
    pass the full level's 192 slots too: the over bits are its own."""
    store, nsm = fan_out_store(60)
    eng = engine(store, nsm, 12)
    q = 32
    queries = [T("Group:h0#members@User:nobody")] * (rows - 1) + [
        T("Group:h0#members@User:y59u")]
    enc = encoded(eng, queries, q)
    act = np.arange(q) < rows
    schedule = fp.level_schedule(q, 8192, 16384, 12)
    assert fp.folded_runs(schedule)
    want, occ, rungs = assert_same(eng._served_arrays(), enc, act, schedule)
    lo, hi = fp.folded_runs(schedule)[0]
    redo = [n <= q // 4 and c == FULL for n, c in zip(occ[lo:hi], rungs)]
    assert any(redo), (occ, rungs)
    assert any(n > q and c == FULL for n, c in zip(occ[lo:hi], rungs))
    assert want[1].any() == (rows * 60 > schedule[lo][0])


def diamond_store(shared, k_z, single, k_p):
    """h0 > h1 > h2 > h3; h3 holds ``shared`` groups m<i>, each holding all
    of z0 .. z<k_z - 1>, and ``single`` groups n<i>, each holding ``k_p``
    groups p<i>_<j> of its own: a level of the walk meets each z<j>
    ``shared`` times for one query, beside many keys met once."""
    tuples = [f"Group:h{i}#members@Group:h{i + 1}#members" for i in range(3)]
    for i in range(shared):
        tuples.append(f"Group:h3#members@Group:m{i}#members")
        tuples += [f"Group:m{i}#members@Group:z{j}#members"
                   for j in range(k_z)]
    for i in range(single):
        tuples.append(f"Group:h3#members@Group:n{i}#members")
        tuples += [f"Group:n{i}#members@Group:p{i}_{j}#members"
                   for j in range(k_p)]
    tuples += [f"Group:z{j}#members@User:z{j}u" for j in range(k_z)]
    store = InMemoryTupleStore()
    store.write_relation_tuples(*[T(t) for t in tuples])
    namespaces, errs = parse(OPL)
    assert not errs, errs
    return store, StaticNamespaceManager(namespaces)


@pytest.mark.parametrize("shape", [(2, 2, 4, 3), (2, 3, 3, 4), (2, 2, 6, 4)])
def test_duplicate_children_merge_at_a_narrow_rung(shape):
    """One query's items at a folded level make duplicate children beside
    children met once, all inside the quarter rung's 32 slots of arena: the
    narrow rung merges them into the frontier the full level packs."""
    shared, k_z, single, k_p = shape
    store, nsm = diamond_store(*shape)
    eng = engine(store, nsm, 10)
    q = 64
    enc = encoded(eng, [T("Group:h0#members@User:nobody")], q)
    schedule = fp.level_schedule(q, 8192, 16384, 10)
    _, occ, rungs = assert_same(
        eng._served_arrays(), enc, np.arange(q) < 1, schedule)
    lo, _ = fp.folded_runs(schedule)[0]
    assert occ[5] == k_z + single * k_p and rungs[4 - lo] == QUARTER


def test_overlay_dirty_bits_are_the_full_levels():
    """Writes after the snapshot serve through the delta overlay: rows that
    read a dirty edge list carry the dirty bit, at every rung."""
    deep = build_deep_groups(depth=24, n_chains=6, n_users=24, seed=9)
    eng = engine(deep.store, deep.manager, 24)
    eng.snapshot()
    deep.store.write_relation_tuples(
        T("Group:g0_7#members@Group:g1_3#members"),
        T("Group:g2_15#members@u1"),
    )
    deep.store.delete_relation_tuples(T("Group:g3_10#members@Group:g3_11#members"))
    eng.snapshot()
    g = eng._served_arrays()
    assert "ov_dirty" in g
    queries = [T(f"Group:g{c}_0#members@u{u}")
               for c in range(6) for u in range(5)]
    q = 32
    enc = encoded(eng, queries, q)
    schedule = fp.level_schedule(q, 8192, 16384, 24)
    for share in (1.0, 0.25):
        act = np.arange(q) < int(share * len(queries))
        want, _, _ = assert_same(g, enc, act, schedule)
        assert want[2].any()


def test_rewrite_columns_where_items_have_them():
    """Folders, documents and groups: computed subject sets and
    tuple-to-userset rows reach the folded levels, which then run the
    rung with the rewrite columns; levels whose items have none drop
    them.  The bits are the unrolled schedule's either way."""
    graph = build_synth(n_users=40, n_groups=6, n_folders=40, n_docs=80,
                        seed=2)
    depth = 10
    eng = engine(graph.store, graph.manager, depth)
    queries = synth_queries_mixed(graph, 48, seed=4, general_frac=0.0)
    q = 64
    enc = encoded(eng, queries, q)
    schedule = fp.level_schedule(q, 8192, 16384, depth)
    g = eng._served_arrays()
    lo, hi = fp.folded_runs(schedule)[0]
    for share in (1.0, 0.25):
        act = np.arange(q) < int(share * len(queries))
        s = fp.init_state(*enc, act, frontier=schedule[0][0])
        s["f_depth"] = jnp.minimum(s["f_depth"], depth)
        rewrites = []
        for i in range(hi):
            rewrites.append(bool(fp.has_rewrites(g, s)))
            s = _plain_level(g, s, arena=schedule[i][1],
                             nxt_f=schedule[i + 1][0], max_width=100,
                             probe_only=False)
        assert any(rewrites[lo:]), rewrites
        assert_same(g, enc, act, schedule)


def _loops_in_fast_tier(text: str) -> int:
    return len(re.findall(r"stablehlo\.while", text))


def test_depth5_worst_case_wave_has_no_loop():
    """The schedules Drive's check cells and the singles run (depth 5,
    worst case) lower to the unrolled fast tier, as before the fold, and
    so does the adaptive ladder's at depth 5; its equal levels at depth 8
    lower to one loop."""
    store, nsm = fan_out_store(3)
    eng = engine(store, nsm, 5)
    g = eng._served_arrays()
    for q in (256, 1024):
        qpack = np.zeros((10, q), np.int32)
        kw = dict(retry_sched=None, retry_lanes=0, gen=None, gen_retry=None,
                  gen_lanes=0, max_width=100, depth_slack=2)
        worst = fp.level_schedule(q, 8192, 16384, 5)
        text = fdx._run_wave.lower(g, qpack, fast_sched=worst, **kw).as_text()
        assert _loops_in_fast_tier(text) == 0, q
        ladder = fp.level_schedule(q, 8192, 16384, 5, mults=(1,) * 5)
        text = fdx._run_wave.lower(g, qpack, fast_sched=ladder, **kw).as_text()
        assert _loops_in_fast_tier(text) == 0, q
        ladder = fp.level_schedule(q, 8192, 16384, 8, mults=(1,) * 8)
        text = fdx._run_wave.lower(g, qpack, fast_sched=ladder, **kw).as_text()
        assert _loops_in_fast_tier(text) == 1, q


def test_adaptive_ladder_fold_matches_unrolled():
    """Depth 8 at the ladder's base rung: levels 1-6 fold, and the bits
    are the unrolled schedule's."""
    store, nsm = fan_out_store(6)
    eng = engine(store, nsm, 8)
    q = 16
    queries = [T("Group:h0#members@User:nobody"), T("Group:h1#members@User:h0u"),
               T("Group:h3#members@User:nobody"), T("Group:h0#members@User:h0u")]
    enc = encoded(eng, queries, q)
    schedule = fp.level_schedule(q, 8192, 16384, 8, mults=(1,) * 8)
    for n in (4, 1):
        _, _, rungs = assert_same(
            eng._served_arrays(), enc, np.arange(q) < n, schedule)
        assert len(rungs) == 6
