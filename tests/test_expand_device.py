"""Device Expand tests: bit-exact tree parity with the oracle engine.

The device pass produces an ancestor-cycle-bounded superset forest; the
host DFS replay with a global visited set must reproduce
`oracle.ExpandEngine.build_tree` exactly — including cycle leaves, diamond
sharing (first DFS occurrence expands, later ones are leaves), depth-1
truncation, and empty-row pruning (engine.go:54-124 semantics).
"""

import numpy as np
import pytest

from ketotpu.api.types import RelationTuple, SubjectID, SubjectSet
from ketotpu.engine import expand_device as xd
from ketotpu.engine.oracle import ExpandEngine
from ketotpu.engine.tpu import DeviceCheckEngine
from ketotpu.storage.memory import InMemoryTupleStore
from ketotpu.utils.synth import build_synth


def _trees_equal(got, want):
    g = got.to_json() if got else None
    w = want.to_json() if want else None
    return g == w


def _parity(store, manager, roots, rest_depth=0, **kw):
    eng = DeviceCheckEngine(store, manager)
    snap = eng.snapshot()
    oracle = ExpandEngine(store, max_depth=eng.max_depth)
    trees, over = xd.run_expand(
        eng._expand_arrays(), snap, roots, rest_depth,
        max_depth=eng.max_depth, **kw,
    )
    assert not over.any(), "unexpected overflow"
    for root, got in zip(roots, trees):
        want = oracle.build_tree(root, rest_depth)
        assert _trees_equal(got, want), (root, got, want)
    return trees


def _store(lines):
    store = InMemoryTupleStore()
    store.write_relation_tuples(*[RelationTuple.from_string(s) for s in lines])
    return store


class TestParity:
    def test_synth_graph_all_usersets(self):
        graph = build_synth(n_users=48, n_groups=6, n_folders=24, n_docs=96)
        roots = sorted(
            {(t.namespace, t.object, t.relation) for t in graph.store.all_tuples()}
        )
        _parity(
            graph.store, graph.manager,
            [SubjectSet(*r) for r in roots] + [SubjectSet("Doc", "none", "x")],
        )

    def test_cycle_becomes_leaf(self):
        store = _store([
            "g:a#m@g:b#m",
            "g:b#m@g:a#m",
            "g:b#m@alice",
        ])
        trees = _parity(store, None, [SubjectSet("g", "a", "m")])
        js = trees[0].to_json()
        assert "alice" in str(js)

    def test_diamond_first_occurrence_expands(self):
        # shared child: DFS expands it under the first parent only
        store = _store([
            "g:root#m@g:left#m",
            "g:root#m@g:right#m",
            "g:left#m@g:shared#m",
            "g:right#m@g:shared#m",
            "g:shared#m@bob",
        ])
        _parity(store, None, [SubjectSet("g", "root", "m")])

    def test_depth_truncation_leaf(self):
        store = _store([
            "g:a#m@g:b#m",
            "g:b#m@g:c#m",
            "g:c#m@carol",
        ])
        for depth in (1, 2, 3, 4):
            _parity(store, None, [SubjectSet("g", "a", "m")], rest_depth=depth)

    def test_empty_row_prunes_to_none(self):
        store = _store(["g:a#m@alice"])
        eng = DeviceCheckEngine(store, None)
        snap = eng.snapshot()
        trees, over = xd.run_expand(
            eng._expand_arrays(), snap, [SubjectSet("g", "none", "m")], 0,
            max_depth=eng.max_depth,
        )
        assert trees == [None] and not over.any()

    def test_mixed_leaf_and_set_children_in_insertion_order(self):
        store = _store([
            "g:a#m@zed",
            "g:a#m@g:b#m",
            "g:a#m@amy",
            "g:b#m@bob",
        ])
        trees = _parity(store, None, [SubjectSet("g", "a", "m")])
        labels = [str(c.tuple.subject) for c in trees[0].children]
        assert labels == ["zed", "g:b#m", "amy"]  # insertion order


class TestEngineSurface:
    def test_batch_expand_with_subject_ids_and_fallback(self):
        graph = build_synth(n_users=32, n_groups=4, n_folders=16, n_docs=64)
        eng = DeviceCheckEngine(graph.store, graph.manager)
        oracle = ExpandEngine(graph.store, max_depth=eng.max_depth)
        some = next(
            t for t in graph.store.all_tuples() if t.relation == "viewers"
        )
        subjects = [
            SubjectID("alice"),
            SubjectSet(some.namespace, some.object, some.relation),
        ]
        out = eng.batch_expand(subjects)
        assert out[0].type.value == "leaf"
        assert _trees_equal(out[1], oracle.build_tree(subjects[1]))

    def test_batch_expand_overflow_falls_back(self):
        graph = build_synth(n_users=32, n_groups=4, n_folders=16, n_docs=64)
        eng = DeviceCheckEngine(graph.store, graph.manager)
        oracle = ExpandEngine(graph.store, max_depth=eng.max_depth)
        some = next(
            t for t in graph.store.all_tuples() if t.relation == "viewers"
        )
        s = SubjectSet(some.namespace, some.object, some.relation)
        out = eng.batch_expand([s], cap=1)  # force per-root overflow
        assert eng.fallbacks >= 0
        assert _trees_equal(out[0], oracle.build_tree(s))

    def test_batch_expand_under_overlay_sees_pending_writes(self):
        graph = build_synth(n_users=32, n_groups=4, n_folders=16, n_docs=64)
        eng = DeviceCheckEngine(graph.store, graph.manager)
        eng.snapshot()
        doc = next(t for t in graph.store.all_tuples() if t.relation == "viewers")
        graph.store.write_relation_tuples(
            RelationTuple.from_string(
                f"{doc.namespace}:{doc.object}#viewers@newbie"
            )
        )
        s = SubjectSet(doc.namespace, doc.object, "viewers")
        out = eng.batch_expand([s])
        assert "newbie" in str(out[0].to_json())  # fresh against the write

    def test_batch_expand_overlay_exact_without_fallback(self):
        # VERDICT r2 #5: pending writes must NOT blanket-fall the whole
        # batch to the sequential oracle — the device expands base rows
        # and the assembly merges overlay deltas (adds at row end, deletes
        # dropped, added subject-set subtrees expanded with the shared
        # visited set)
        graph = build_synth(n_users=32, n_groups=4, n_folders=16, n_docs=64)
        eng = DeviceCheckEngine(graph.store, graph.manager)
        eng.snapshot()
        oracle = ExpandEngine(graph.store, max_depth=eng.max_depth)
        # a folder that already has a group subject-set viewer (the
        # (Folder, viewers, Group, members) pair pre-exists => the write
        # overlay admits more of them without a rebuild)
        fold = next(
            t for t in graph.store.all_tuples()
            if t.relation == "viewers" and t.namespace == "Folder"
            and not isinstance(t.subject, SubjectID)
        )
        dropped = next(
            t for t in graph.store.all_tuples()
            if t.namespace == fold.namespace and t.object == fold.object
            and t.relation == "viewers" and isinstance(t.subject, SubjectID)
        )
        graph.store.delete_relation_tuples(dropped)
        graph.store.write_relation_tuples(
            RelationTuple.from_string(
                f"Folder:{fold.object}#viewers@Group:g1#members"
            ),
            RelationTuple.from_string(
                f"Folder:{fold.object}#viewers@fresh-user"
            ),
        )
        rebuilds0, fb0 = eng.rebuilds, eng.fallbacks
        s = SubjectSet("Folder", fold.object, "viewers")
        out = eng.batch_expand([s])
        assert eng.rebuilds == rebuilds0, "overlay write must not rebuild"
        assert eng.fallbacks == fb0, "no blanket oracle fallback"
        assert _trees_equal(out[0], oracle.build_tree(s))


class TestOverlayMultiplicity:
    def test_double_insert_appears_twice(self):
        """ADVICE r3: OverlayMembers must classify against the BASE pair
        count like overlay_arrays, and a pair inserted twice
        post-snapshot must appear twice in the expand tree — matching
        live-store pagination, which keeps exact duplicate rows."""
        from ketotpu.engine.oracle import ExpandEngine

        graph = build_synth(n_users=32, n_groups=4, n_folders=16, n_docs=64)
        eng = DeviceCheckEngine(graph.store, graph.manager)
        eng.snapshot()
        doc = next(
            t for t in graph.store.all_tuples() if t.relation == "viewers"
        )
        dup = RelationTuple.from_string(
            f"{doc.namespace}:{doc.object}#viewers@twice"
        )
        # insert the same tuple twice post-snapshot, then delete once —
        # the in-memory store keeps duplicate rows, so one copy survives
        graph.store.write_relation_tuples(dup)
        graph.store.write_relation_tuples(dup)
        s = SubjectSet(doc.namespace, doc.object, "viewers")
        out = eng.batch_expand([s])
        oracle = ExpandEngine(graph.store, max_depth=eng.max_depth)
        assert _trees_equal(out[0], oracle.build_tree(s))
        assert str(out[0].to_json()).count("twice") == 2

    def test_base_pair_delete_then_reinsert_fewer(self):
        """base=2 copies in the snapshot, delete-all then reinsert one:
        the tree must show exactly one surviving copy (count parity with
        the live store, which also moves it to the row end)."""
        from ketotpu.engine.oracle import ExpandEngine

        graph = build_synth(n_users=32, n_groups=4, n_folders=16, n_docs=64)
        doc = next(
            t for t in graph.store.all_tuples() if t.relation == "viewers"
        )
        dup = RelationTuple.from_string(
            f"{doc.namespace}:{doc.object}#viewers@twice"
        )
        graph.store.write_relation_tuples(dup)
        graph.store.write_relation_tuples(dup)  # base will hold 2 copies
        eng = DeviceCheckEngine(graph.store, graph.manager)
        eng.snapshot()
        graph.store.delete_relation_tuples(dup)  # removes BOTH copies
        graph.store.write_relation_tuples(dup)   # one survives
        s = SubjectSet(doc.namespace, doc.object, "viewers")
        out = eng.batch_expand([s])
        oracle = ExpandEngine(graph.store, max_depth=eng.max_depth)
        assert _trees_equal(out[0], oracle.build_tree(s))
        assert str(out[0].to_json()).count("twice") == 1

    def test_base_pair_duplicate_insert_over_existing(self):
        """base=1 copy plus one post-snapshot duplicate insert: two
        copies in the tree, like live-store pagination."""
        from ketotpu.engine.oracle import ExpandEngine

        graph = build_synth(n_users=32, n_groups=4, n_folders=16, n_docs=64)
        doc = next(
            t for t in graph.store.all_tuples() if t.relation == "viewers"
        )
        dup = RelationTuple.from_string(
            f"{doc.namespace}:{doc.object}#viewers@twice"
        )
        graph.store.write_relation_tuples(dup)
        eng = DeviceCheckEngine(graph.store, graph.manager)
        eng.snapshot()
        graph.store.write_relation_tuples(dup)
        s = SubjectSet(doc.namespace, doc.object, "viewers")
        out = eng.batch_expand([s])
        oracle = ExpandEngine(graph.store, max_depth=eng.max_depth)
        assert _trees_equal(out[0], oracle.build_tree(s))
        assert str(out[0].to_json()).count("twice") == 2


class TestRungs:
    """Two rungs of level capacities: the first clamps the schedule at
    ``FIRST_RUNG`` slots a padded root, the roots it overflows run again
    at ``cap``, and only a root that overflows that too goes to the
    oracle; ``expand_roots`` counts which answered."""

    @pytest.mark.parametrize("n_roots,levels", [
        (8, (8, 128, 2048, 2048, 2048)),
        (16, (16, 256, 4096, 4096, 4096)),
        (64, (64, 1024, 16384, 16384, 16384)),
    ])
    def test_first_rung_schedule(self, n_roots, levels):
        cap = xd.rung_cap("first", n_roots, 65536)
        assert xd.expand_schedule(n_roots, 16, 5, cap) == levels
        assert xd.rung_cap("full", n_roots, 65536) == 65536

    @staticmethod
    def _wide_store():
        # level 1: 100 groups, level 2: 1,600, level 3: 3,200 users, past
        # the first rung's 2,048 slots and inside the full rung's 32,768
        lines = [f"g:wide#m@g:a{i}#m" for i in range(100)]
        for i in range(100):
            lines += [f"g:a{i}#m@g:b{i}_{j}#m" for j in range(16)]
            for j in range(16):
                lines += [f"g:b{i}_{j}#m@u{i}_{j}_{k}" for k in range(2)]
        lines += ["g:small#m@g:s1#m", "g:small#m@zoe", "g:s1#m@yan",
                  "g:tiny#m@xia"]
        return _store(lines)

    def _expand(self, roots, **kw):
        store = self._wide_store()
        eng = DeviceCheckEngine(store, None)
        oracle = ExpandEngine(store, max_depth=eng.max_depth)
        out = eng.batch_expand(roots, **kw)
        for root, got in zip(roots, out):
            assert _trees_equal(got, oracle.build_tree(root)), root
        return eng, out

    def test_overflow_answered_by_full_rung(self):
        eng, out = self._expand([SubjectSet("g", "wide", "m")])
        assert str(out[0].to_json()).count("'u99_15_1'") == 1
        assert eng.expand_roots == {"first": 0, "full": 1, "oracle": 0}
        assert eng.fallbacks == 0

    def test_overflow_of_both_rungs_goes_to_the_oracle(self):
        # the full rung clamped at 3,000 slots cannot hold level 3 either
        eng, _ = self._expand([SubjectSet("g", "wide", "m")], cap=3000)
        assert eng.expand_roots == {"first": 0, "full": 0, "oracle": 1}
        assert eng.fallbacks == 1

    def test_mixed_batch_reruns_only_the_overflowed_root(self, monkeypatch):
        calls = []
        real = xd.run_expand

        def spy(g, snap, roots, rest_depth, **kw):
            trees, over = real(g, snap, roots, rest_depth, **kw)
            calls.append((kw["rung"], list(roots), list(trees)))
            return trees, over

        monkeypatch.setattr(xd, "run_expand", spy)
        small = [SubjectSet("g", "small", "m"), SubjectSet("g", "tiny", "m"),
                 SubjectSet("g", "s1", "m")]
        wide = SubjectSet("g", "wide", "m")
        eng, out = self._expand(small + [wide])
        assert [(rung, roots) for rung, roots, _ in calls] == [
            ("first", small + [wide]), ("full", [wide])]
        assert calls[0][2][:3] == out[:3]  # the first rung's trees, as such
        assert eng.expand_roots == {"first": 3, "full": 1, "oracle": 0}

    def test_first_dispatch_warms_the_full_rung(self, monkeypatch):
        """The full rung's program is dispatched beside the first rung's
        first dispatch, once: an overflow later compiles nothing."""
        scheds = []
        real = xd._run_expand

        def spy(*args, schedule):
            scheds.append(schedule)
            return real(*args, schedule=schedule)

        monkeypatch.setattr(xd, "_run_expand", spy)
        monkeypatch.setattr(xd, "_FULL_RUNG_WARM", set())
        store = _store(["g:a#m@g:b#m", "g:b#m@carol"])
        eng = DeviceCheckEngine(store, None)
        for _ in range(2):
            eng.batch_expand([SubjectSet("g", "a", "m")])
        assert scheds == [(8, 128, 2048, 32768, 65536),
                          (8, 128, 2048, 2048, 2048),
                          (8, 128, 2048, 2048, 2048)]
