"""Incremental projection tests: vectorized rebuilds + delta overlay.

Covers engine/delta.py: the column cache's vectorized snapshot build must
be array-identical to the reference loop build, and the overlay must keep
device verdicts exact against the latest writes (probes consult the
overlay; explorations through changed CSR rows fall back to the oracle).
"""

import numpy as np
import pytest

from ketotpu.api.types import RelationTuple
from ketotpu.engine import delta as dl
from ketotpu.engine.snapshot import build_snapshot
from ketotpu.engine.tpu import DeviceCheckEngine
from ketotpu.engine.vocab import Vocab
from ketotpu.utils.synth import build_synth, synth_queries

ARRAY_FIELDS = (
    "node_hi", "node_lo", "row_ptr",
    "edge_ns", "edge_obj", "edge_rel", "edge_node",
    "mem_node", "mem_subj",
)


@pytest.fixture(scope="module")
def graph():
    return build_synth(n_users=64, n_groups=8, n_folders=32, n_docs=128)


def test_vectorized_build_matches_loop_build(graph):
    s1 = build_snapshot(graph.store, graph.manager, Vocab())
    cols = dl.TupleColumns(Vocab())
    for t in graph.store.all_tuples():
        cols.apply(1, t)
    s2 = dl.build_snapshot_cols(
        cols, graph.manager, version=graph.store.version
    )
    for f in ARRAY_FIELDS:
        a, b = getattr(s1, f), getattr(s2, f)
        assert a.shape == b.shape and (a == b).all(), f
    assert (s1.n_nodes, s1.n_edges, s1.n_tuples) == (
        s2.n_nodes, s2.n_edges, s2.n_tuples
    )
    assert (s1.taint == s2.taint).all()
    assert s1.dyn_pairs == s2.dyn_pairs


def test_columns_delete_and_compact(graph):
    cols = dl.TupleColumns(Vocab())
    tuples = graph.store.all_tuples()
    for t in tuples:
        cols.apply(1, t)
    for t in tuples[: len(tuples) * 3 // 4]:
        cols.apply(-1, t)
    assert cols.alive_count == len(tuples) - len(tuples) * 3 // 4
    cols.compact()
    assert cols.n == cols.alive_count
    # rebuild after compaction still matches a fresh loop build of the
    # remaining tuples (order preserved)
    s2 = dl.build_snapshot_cols(cols, graph.manager)
    remaining = tuples[len(tuples) * 3 // 4:]
    assert s2.n_tuples == len(remaining)


class TestOverlayEngine:
    @pytest.fixture
    def eng(self, graph):
        return DeviceCheckEngine(
            graph.store, graph.manager,
            frontier=2048, arena=4096, max_batch=512,
        )

    def _parity(self, eng, qs):
        got = eng.batch_check(qs)
        want = [eng.oracle.check_is_member(r) for r in qs]
        assert got == want

    def test_membership_writes_apply_via_overlay(self, graph, eng):
        qs = synth_queries(graph, 300, seed=11)
        self._parity(eng, qs)
        base_rebuilds = eng.rebuilds
        # grant + revoke direct memberships on existing vocabulary: the
        # overlay absorbs them without a rebuild and verdicts stay exact
        existing = [t for t in graph.store.all_tuples() if "@" in str(t)][:4]
        sample = str(existing[0].subject)
        doc = next(t for t in graph.store.all_tuples() if t.relation == "viewers")
        grant = RelationTuple.from_string(
            f"{doc.namespace}:{doc.object}#viewers@{sample}"
        )
        graph.store.write_relation_tuples(grant)
        self._parity(eng, qs)
        direct = eng.batch_check([grant])
        assert direct == [True]
        graph.store.delete_relation_tuples(grant)
        self._parity(eng, qs)
        assert eng.batch_check([grant]) == [
            eng.oracle.check_is_member(grant)
        ]
        assert eng.rebuilds == base_rebuilds
        assert eng.overlay_applies >= 2

    def test_edge_writes_mark_dirty_and_stay_exact(self, graph, eng):
        qs = synth_queries(graph, 300, seed=13)
        self._parity(eng, qs)
        base_rebuilds = eng.rebuilds
        edge = next(
            t
            for t in graph.store.all_tuples()
            if t.relation == "viewers" and "#" in str(t).split("@", 1)[1]
        )
        graph.store.delete_relation_tuples(edge)
        self._parity(eng, qs)  # dirty-node queries fall back to the oracle
        graph.store.write_relation_tuples(edge)
        self._parity(eng, qs)
        assert eng.rebuilds == base_rebuilds  # absorbed by the overlay
        assert eng.fallbacks > 0  # some queries crossed the dirty row

    def test_unrepresentable_change_triggers_rebuild(self, graph, eng):
        qs = synth_queries(graph, 100, seed=17)
        self._parity(eng, qs)
        base_rebuilds = eng.rebuilds
        # brand-new subject string: fits after interning; brand-new
        # namespace does not fit the base table dims -> rebuild
        graph.store.write_relation_tuples(
            RelationTuple.from_string("brandnewns:obj#rel@someone")
        )
        eng.snapshot()
        assert eng.rebuilds == base_rebuilds + 1
        self._parity(eng, qs)

    def test_net_zero_churn_is_absorbed(self, graph, eng):
        # delete-then-reinsert nets to an empty overlay: no rebuild at all
        eng.snapshot()
        base_rebuilds = eng.rebuilds
        many = [
            t for t in graph.store.all_tuples()[:20] if t.relation != "viewers"
        ]
        graph.store.delete_relation_tuples(*many)
        graph.store.write_relation_tuples(*many)
        eng.snapshot()
        assert eng.rebuilds == base_rebuilds
        assert eng._overlay.size()[0] == 0

    def test_general_queries_on_device_with_overlay(self, graph, eng):
        """VERDICT r4 #4: the algebra path consults the overlay tables, so
        AND/NOT queries are answered on-device under pending writes —
        exact against the oracle — and only queries that touch a dirty
        (edge-changed) row fall back to the host."""
        T = RelationTuple.from_string
        dv = next(
            t for t in graph.store.all_tuples()
            if t.namespace == "Doc" and t.relation == "viewers"
            and "#" not in str(t).split("@", 1)[1]
        )
        user, doc = str(dv.subject), dv.object
        q = T(f"Doc:{doc}#edit@{user}")
        assert eng.batch_check([q]) == [True]  # direct viewer, not banned
        base_rebuilds = eng.rebuilds
        ban = T(f"Doc:{doc}#banned@{user}")
        graph.store.write_relation_tuples(ban)
        try:
            # a membership-only overlay (no edge rows changed): the
            # general query is answered ON-DEVICE and sees the write
            ok, needs = eng.batch_check_device_only([q])
            assert not needs[0], "clean overlay must not force fallback"
            assert ok[0] is False  # banned now
            assert eng.rebuilds == base_rebuilds
            self._parity(eng, [q])
        finally:
            graph.store.delete_relation_tuples(ban)
        ok, needs = eng.batch_check_device_only([q])
        assert not needs[0] and ok[0] is True  # un-banned again, on-device
        # deleting a subject-set edge dirties its row: a general query
        # whose pure-OR subtree crosses that row falls back (exactly)
        edge = next(
            t for t in graph.store.all_tuples()
            if t.namespace == "Doc" and t.relation == "parents"
        )
        graph.store.delete_relation_tuples(edge)
        try:
            q2 = T(f"Doc:{edge.object}#edit@{user}")
            ok2, needs2 = eng.batch_check_device_only([q2])
            # either membership was established on-device (trustworthy:
            # probes are overlay-exact and monotone) or the dirty row
            # routed the query to the host — never a silent stale DENY
            assert ok2[0] or needs2[0]
            got = eng.batch_check([q2])
            assert got == [eng.oracle.check_is_member(q2)]
        finally:
            graph.store.write_relation_tuples(edge)

    def test_overlay_threshold_triggers_rebuild(self, graph, eng):
        eng.max_overlay_pairs = 8
        eng.snapshot()
        base_rebuilds = eng.rebuilds
        doc = next(t for t in graph.store.all_tuples() if t.relation == "viewers")
        # 12 distinct new membership pairs on existing vocabulary: more
        # net overlay pairs than the threshold allows
        subjects = sorted(
            {str(t.subject) for t in graph.store.all_tuples() if "#" not in str(t.subject)}
        )[:12]
        graph.store.write_relation_tuples(
            *[
                RelationTuple.from_string(
                    f"{doc.namespace}:{doc.object}#viewers@{s}"
                )
                for s in subjects
            ]
        )
        eng.snapshot()
        assert eng.rebuilds == base_rebuilds + 1


def test_store_change_log_bounded(graph):
    from ketotpu.storage.memory import InMemoryTupleStore

    store = InMemoryTupleStore()
    store._log_cap = 8
    cursor = store.log_head
    for i in range(20):
        store.write_relation_tuples(
            RelationTuple.from_string(f"ns:o{i}#r@s{i}")
        )
    changes, head = store.changes_since(cursor)
    assert changes is None  # cursor fell behind the bounded log
    changes, head2 = store.changes_since(head)
    assert changes == [] and head2 == head


def test_log_overflow_rebuild_sees_all_writes():
    """Regression: when the bounded change log overflows past the engine's
    cursor, the rebuild must rescan the store (not reuse the stale column
    mirror) and later snapshots must resume incremental operation."""
    from ketotpu.opl.parser import parse
    from ketotpu.storage.memory import InMemoryTupleStore
    from ketotpu.storage.namespaces import StaticNamespaceManager

    src = "class ns implements Namespace { related: { r: User[] } }\n" \
          "class User implements Namespace {}"
    namespaces, errors = parse(src)
    assert not errors
    manager = StaticNamespaceManager(namespaces)
    store = InMemoryTupleStore()
    store._log_cap = 8
    store.write_relation_tuples(RelationTuple.from_string("ns:seed#r@u0"))
    eng = DeviceCheckEngine(store, manager, frontier=256, arena=512)
    eng.snapshot()
    # blow past the log capacity between snapshots
    for i in range(20):
        store.write_relation_tuples(
            RelationTuple.from_string(f"ns:o{i}#r@u{i}")
        )
    r0 = eng.rebuilds
    assert eng.batch_check(
        [RelationTuple.from_string("ns:o19#r@u19"),
         RelationTuple.from_string("ns:o19#r@u0")]
    ) == [True, False]
    assert eng.rebuilds == r0 + 1
    # cursor resynced: the next snapshot is incremental again
    store.write_relation_tuples(RelationTuple.from_string("ns:fresh#r@u1"))
    assert eng.batch_check(
        [RelationTuple.from_string("ns:fresh#r@u1")]
    ) == [True]
    assert eng.rebuilds == r0 + 1  # overlay handled it, no extra rebuild


# -- borrowed columns (PR 35) --------------------------------------------------


def test_from_arrays_borrows_the_stores_columns_and_never_writes_them():
    """A columnar store's base segment is adopted without a copy (eight
    padded columns are 8.6 GB at 150M rows); appends grow into arrays of
    the mirror's own and a compaction copies, so the store's arrays stay
    as they were."""
    import numpy as np

    from ketotpu.api.types import RelationTuple, SubjectID
    from ketotpu.utils.synth import build_synth_columnar

    g = build_synth_columnar(n_users=40, n_groups=4, n_folders=12, n_docs=60)
    cols, alive, tail, _ = g.store.export_columns()
    before = {c: v.copy() for c, v in cols.items()}
    mirror = dl.TupleColumns.from_arrays(g.store.vocab, cols, alive)
    n = len(alive)
    assert mirror.n == mirror.cap == n and not mirror._owned
    assert all(np.shares_memory(getattr(mirror, c), cols[c]) for c in cols)
    assert not np.shares_memory(mirror.alive, alive)
    base = dl.build_snapshot_cols(mirror, g.manager, version=0)

    # an append grows into the mirror's own arrays
    new = RelationTuple("Doc", "d0", "viewers", SubjectID("late-user"))
    mirror.apply(1, new)
    assert mirror._owned and mirror.n == n + 1 and mirror.cap >= n + 1
    assert not any(np.shares_memory(getattr(mirror, c), cols[c]) for c in cols)

    # a compaction of a borrowed mirror copies instead of shifting in place
    again = dl.TupleColumns.from_arrays(g.store.vocab, cols, alive)
    again.alive[: (2 * n) // 3] = False
    again.alive_count = int(again.alive.sum())
    again.compact()
    assert again._owned and again.n == n - (2 * n) // 3
    assert (again.obj[: again.n] == before["obj"][(2 * n) // 3:]).all()
    for c in cols:
        assert (cols[c] == before[c]).all(), c
    # and the projection of the untouched store is the one built before
    fresh = dl.build_snapshot_cols(
        dl.TupleColumns.from_arrays(g.store.vocab, cols, alive),
        g.manager, version=0)
    for f in ("row_ptr", "edge_obj", "edge_node", "mem_ord_subj", "node_lo"):
        assert (getattr(fresh, f) == getattr(base, f)).all(), f
