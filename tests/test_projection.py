"""ISSUE 8: incremental CSR fold + off-path generation-swapped compaction.

Three layers of coverage:

* randomized fold-vs-rebuild parity — `fold_snapshot_cols` must produce
  arrays bit-identical to a from-scratch `build_snapshot_cols` at the same
  cursor under change storms (delete-then-re-add, duplicate tuples,
  new-node creation, whole-node removal), or reject cleanly;
* engine integration — the sync write path absorbs overlay-overflowing
  slices by folding (no full rebuild), and the background compactor
  publishes generations off the serving path with verdict parity after
  catch-up;
* the compile gate — same-shape folds/swaps never re-arm the compile
  observatory (zero new XLA compiles after warm-up), while a genuine
  table-growth change declares cold exactly once.
"""

import random
import time

import numpy as np
import pytest

from ketotpu import compilewatch
from ketotpu.api.types import RelationTuple, SubjectID, SubjectSet
from ketotpu.engine import delta as dl
from ketotpu.engine import hashtab
from ketotpu.engine.tpu import DeviceCheckEngine
from ketotpu.engine.vocab import Vocab
from ketotpu.utils.synth import build_synth, synth_queries

CMP = (
    "node_hi", "node_lo", "row_ptr",
    "edge_ns", "edge_obj", "edge_rel", "edge_node",
    "mem_node", "mem_subj", "mem_row_ptr", "mem_ord_subj",
)


# -- randomized fold parity --------------------------------------------------


def _host_lookup(t, a, b):
    """Host-side replica of the device probe: same salt/mask bucketing,
    the pointer decoded into the bucket's offset and its split level, the
    window of the key's part scanned for its rounds (an entry's key is its
    tag and its second half: ``tag = key_a ^ f(key_b)``)."""
    meta = t["meta"]
    ka, kb = np.array([a]), np.array([b])
    mask = np.uint32(int(meta[1]))
    h = int(hashtab._mix_np(ka, kb, hashtab._SALTS[int(meta[0])])[0] & mask)
    tag = int(hashtab._tag_np(ka, kb, hashtab._SALTS[int(meta[2])])[0])
    u = int(t["ptr"][h]) & 0xFFFFFFFF
    probe = t["pw"].shape[0]
    part = int(hashtab._split_np(ka, kb, hashtab._SALTS[int(meta[3])])[0]) & (
        (1 << (u >> 29)) - 1)
    lo = (u & ((1 << 29) - 1)) + part * (probe // 2)
    assert lo < (int(t["ptr"][h + 1]) & ((1 << 29) - 1)) or u >> 29 == 0
    for j in range(lo, min(lo + probe, len(t["tag"]))):
        if t["tag"][j] == tag:  # the first tag hit, verified once
            if t["key_b"][j] != b:
                return False, -1
            return True, int(t["val"][j]) if "val" in t else -1
    return False, -1


def _table_entries(t):
    """Entries of a table: its slots in use less the slots left empty
    between the parts of its split buckets (``meta`` counts them)."""
    used = hashtab.slots_in_use(t)
    assert int((t["key_b"][:used] < 0).sum()) == int(t["meta"][6])
    return used - int(t["meta"][6])


def _check_tables(snap):
    for i in range(snap.n_nodes):
        ok, v = _host_lookup(
            snap.node_tab, int(snap.node_hi[i]), int(snap.node_lo[i])
        )
        assert ok and v == i, f"node_tab wrong at {i}: {ok}, {v}"
    assert _table_entries(snap.node_tab) == snap.n_nodes
    for i in range(0, snap.n_tuples, max(1, snap.n_tuples // 200)):
        ok, _ = _host_lookup(
            snap.mem_tab, int(snap.mem_node[i]), int(snap.mem_subj[i])
        )
        assert ok, f"mem_tab miss at row {i}"
    assert _table_entries(snap.mem_tab) == snap.n_tuples
    for _ in range(50):
        a = random.randrange(snap.n_nodes + 5)
        b = random.randrange(1 << 20)
        inset = bool(
            np.any((snap.mem_node[: snap.n_tuples] == a)
                   & (snap.mem_subj[: snap.n_tuples] == b))
        )
        ok, _ = _host_lookup(snap.mem_tab, a, b)
        assert ok == inset, f"mem_tab phantom for ({a}, {b})"


def _storm_trial(seed):
    """One randomized storm: returns 'ok' when the fold matched the
    from-scratch build, 'rejected' when the fold declined (a legal answer:
    the caller falls back to a full build), 'empty' for a no-op storm."""
    random.seed(seed)
    g = build_synth(n_users=40, n_groups=6, n_folders=12, n_docs=60)
    cols = dl.TupleColumns(Vocab())
    tuples = g.store.all_tuples()
    for t in tuples:
        cols.apply(1, t)
    base = dl.build_snapshot_cols(cols, g.manager, version=0)

    users = [SubjectID(f"u{seed}x{i}") for i in range(8)] + [
        t.subject for t in tuples if isinstance(t.subject, SubjectID)
    ][:10]
    docs = sorted({t.object for t in tuples if t.namespace == "Doc"})
    changes = []
    live = list(tuples)
    for _ in range(random.randrange(1, 60)):
        r = random.random()
        if r < 0.45 and live:
            # delete an existing tuple (sometimes twice = no-op second)
            t = random.choice(live)
            changes.append((-1, t))
            if random.random() < 0.3:
                changes.append((-1, t))
            else:
                live.remove(t)
        elif r < 0.75:
            # membership add (possibly a brand-new user = new vocab id,
            # possibly a brand-new (rel, obj) node); sometimes immediately
            # delete-then-re-add to exercise FIFO replay
            t = RelationTuple(
                namespace="Doc", object=random.choice(docs),
                relation=random.choice(["viewers", "owners"]),
                subject=random.choice(users),
            )
            changes.append((1, t))
            live.append(t)
            if random.random() < 0.3:
                changes.append((-1, t))
                changes.append((1, t))
        elif r < 0.9 and live:
            # re-add an existing relation-level edge class elsewhere
            sets = [t for t in live if isinstance(t.subject, SubjectSet)]
            if sets:
                t0 = random.choice(sets)
                t = RelationTuple(
                    namespace=t0.namespace, object=random.choice(docs),
                    relation=t0.relation, subject=t0.subject,
                )
                if t.namespace == "Doc":
                    changes.append((1, t))
                    live.append(t)
        elif live:
            # delete every tuple of some (relation, object) -> node removal
            t0 = random.choice(live)
            victims = [
                t for t in live
                if t.namespace == t0.namespace and t.object == t0.object
                and t.relation == t0.relation
            ]
            for t in victims:
                changes.append((-1, t))
                live.remove(t)
    if not changes:
        return "empty"

    for op_, t in changes:
        cols.apply(op_, t)
    try:
        folded = dl.fold_snapshot_cols(base, cols.vocab, changes, version=1)
    except dl.FoldRejected:
        return "rejected"
    scratch = dl.build_snapshot_cols(cols, g.manager, version=1)
    for f in CMP:
        a, b = getattr(folded, f), getattr(scratch, f)
        assert a.shape == b.shape, (f, seed, a.shape, b.shape)
        assert (a == b).all(), (f, seed, np.flatnonzero(a != b)[:10])
    assert (folded.n_nodes, folded.n_edges, folded.n_tuples) == (
        scratch.n_nodes, scratch.n_edges, scratch.n_tuples
    ), seed
    # sub_* parity only where the scratch build scattered a value (the
    # fold legally keeps stale survivors for retired subject-set ids)
    for f in ("sub_ns", "sub_obj", "sub_rel"):
        a, b = getattr(folded, f), getattr(scratch, f)
        m = b != -1
        assert (a[m] == b[m]).all(), (f, seed)
    _check_tables(folded)
    return "ok"


def test_fold_parity_randomized_storms():
    results = {"ok": 0, "rejected": 0, "empty": 0}
    for seed in range(24):
        results[_storm_trial(seed)] += 1
    # the storms intentionally include fold-rejecting shapes (new edge
    # classes, pad crossings); the point is every non-rejected fold was
    # array-identical — and enough folds succeed for that to mean something
    assert results["ok"] >= 5, results


def _full_bucket(t, a, b):
    """Does key (a, b) hash into a bucket of ``t`` that holds four keys or
    more already (a full level-0 bucket, or a split one)?"""
    h = int(hashtab._mix_np(np.array([a]), np.array([b]), hashtab._SALTS[0])[0]
            & np.uint32(int(t["meta"][1])))
    off = hashtab._offsets(t["ptr"])
    return int(off[h + 1] - off[h]) >= 4


def test_fold_into_full_and_split_buckets_splices_without_a_build(monkeypatch):
    """A bound of four rounds makes a full bucket common (an insert meets
    one 0.34 % of the time at a load of 0.6, 1.8 % at 0.99): the fold's
    splice lays such a bucket anew, splitting it, where the parent's gave
    up and the fold built both tables in full (12 s at 10M tuples)."""
    g = build_synth(n_users=120, n_groups=10, n_folders=40, n_docs=500)
    cols = dl.TupleColumns(Vocab())
    have = set(g.store.all_tuples())
    for t in have:
        cols.apply(1, t)
    base = dl.build_snapshot_cols(cols, g.manager, version=0)
    assert base.node_tab["pw"].shape == base.mem_tab["pw"].shape == (4,)
    v = cols.vocab
    ns = v.namespaces.lookup("Doc")
    users = sorted({t.subject.id for t in have
                    if isinstance(t.subject, SubjectID)})
    # tuples whose node key, or whose (node, subject) key, falls into a
    # bucket that is full already: new nodes and new members alike
    new_nodes, new_pairs = [], []
    for d in sorted({t.object for t in have if t.namespace == "Doc"}):
        for rel in ("viewers", "owners"):
            hi = ns * base.num_rels + v.relations.lookup(rel)
            obj = v.objects.lookup(d)
            node = hashtab.lookup_one(base.node_tab, hi, obj)
            if node < 0:
                if _full_bucket(base.node_tab, hi, obj):
                    new_nodes.append(RelationTuple("Doc", d, rel, SubjectID(users[0])))
                continue
            assert (base.node_hi[node], base.node_lo[node]) == (hi, obj)
            for u in users[:40]:
                t = RelationTuple("Doc", d, rel, SubjectID(u))
                if t not in have and _full_bucket(
                        base.mem_tab, node, v.subjects.lookup(SubjectID(u).unique_id())):
                    new_pairs.append(t)
    assert new_nodes and new_pairs, (len(new_nodes), len(new_pairs))
    built = []
    real_build = hashtab.build_table

    def counted(key_a, key_b, val=None, **kw):
        built.append("nt" if val is not None else "mt")
        return real_build(key_a, key_b, val, **kw)

    monkeypatch.setattr(hashtab, "build_table", counted)
    # new members of resident nodes: both tables spliced; then new nodes,
    # which renumber the node ids the membership table is keyed by: that
    # table is built as before, the node table spliced
    snap, version = base, 0
    for adds, may_build in ((new_pairs[:40], []), (new_nodes[:8], ["mt"])):
        changes = [(1, t) for t in adds]
        for op_, t in changes:
            cols.apply(op_, t)
        version += 1
        del built[:]
        folded = dl.fold_snapshot_cols(snap, cols.vocab, changes, version=version)
        assert built == may_build
        for old, new in ((snap.node_tab, folded.node_tab),
                         (snap.mem_tab, folded.mem_tab)):
            assert {k: x.shape for k, x in old.items()} == {
                k: x.shape for k, x in new.items()}
        spliced = ("mem_tab", "node_tab")[len(may_build)]
        assert hashtab.table_stats(getattr(folded, spliced))[
            "split_buckets"] > hashtab.table_stats(getattr(snap, spliced))[
                "split_buckets"]
        _check_tables(folded)
        scratch = dl.build_snapshot_cols(cols, g.manager, version=version)
        for f in CMP:
            assert (getattr(folded, f) == getattr(scratch, f)).all(), f
        for tab in ("node_tab", "mem_tab"):
            assert hashtab.table_stats(getattr(folded, tab)) == hashtab.table_stats(
                getattr(scratch, tab))
        snap = folded


def test_fold_rejects_new_edge_class():
    """A subject-set add whose (ns, rel, sns, srel) class has no base
    tuple could extend the AND/NOT taint closure: the fold must decline
    and let the caller re-project."""
    g = build_synth(n_users=16, n_groups=4, n_folders=4, n_docs=16)
    cols = dl.TupleColumns(Vocab())
    for t in g.store.all_tuples():
        cols.apply(1, t)
    base = dl.build_snapshot_cols(cols, g.manager, version=0)
    t = RelationTuple.from_string("Doc:d0#viewers@Folder:f0")  # no #relation
    cols.apply(1, t)
    with pytest.raises(dl.FoldRejected):
        dl.fold_snapshot_cols(base, cols.vocab, [(1, t)], version=1)


# -- engine integration ------------------------------------------------------


@pytest.fixture(scope="module")
def graph():
    return build_synth(n_users=64, n_groups=8, n_folders=32, n_docs=128)


def _users(graph, n):
    return sorted(
        {
            str(t.subject) for t in graph.store.all_tuples()
            if ":" not in str(t.subject)
        }
    )[:n]


def _parity(eng, qs):
    got = eng.batch_check(qs)
    want = [eng.oracle.check_is_member(r) for r in qs]
    assert got == want


class TestSyncFold:
    def test_overlay_overflow_folds_instead_of_rebuilding(self, graph):
        eng = DeviceCheckEngine(
            graph.store, graph.manager,
            frontier=2048, arena=4096, max_batch=512,
        )
        eng.max_overlay_pairs = 4
        qs = synth_queries(graph, 120, seed=23)
        _parity(eng, qs)
        base_rebuilds = eng.rebuilds
        doc = next(
            t for t in graph.store.all_tuples()
            if t.namespace == "Doc" and t.relation == "viewers"
        )
        grants = [
            RelationTuple.from_string(f"Doc:{doc.object}#viewers@{u}")
            for u in _users(graph, 8)
        ]
        graph.store.write_relation_tuples(*grants)
        try:
            eng.snapshot()
            assert eng.folds >= 1, eng.projection_stats()
            assert eng.rebuilds == base_rebuilds
            assert eng.last_compaction_mode == "fold"
            assert eng.batch_check(grants) == [True] * len(grants)
            _parity(eng, qs)
            st = eng.projection_stats()
            assert st["served_cursor"] == st["log_cursor"]
            assert st["since_base"] == 0  # fold reset the base cursor
        finally:
            graph.store.delete_relation_tuples(*grants)
            eng.snapshot()
        _parity(eng, qs)

    def test_fold_handles_new_node_and_delete_then_readd(self, graph):
        eng = DeviceCheckEngine(
            graph.store, graph.manager,
            frontier=2048, arena=4096, max_batch=512,
        )
        eng.max_overlay_pairs = 2
        qs = synth_queries(graph, 120, seed=29)
        _parity(eng, qs)
        base_rebuilds = eng.rebuilds
        users = _users(graph, 6)
        # brand-new object on an existing (ns, rel): a new CSR node the
        # fold inserts in key order, plus churn on it
        fresh = [
            RelationTuple.from_string(f"Doc:folddoc#viewers@{u}")
            for u in users
        ]
        graph.store.write_relation_tuples(*fresh)
        graph.store.delete_relation_tuples(fresh[0])
        graph.store.write_relation_tuples(fresh[0])
        try:
            eng.snapshot()
            assert eng.rebuilds == base_rebuilds
            assert eng.folds >= 1
            assert eng.batch_check(fresh) == [True] * len(fresh)
            _parity(eng, qs)
        finally:
            graph.store.delete_relation_tuples(*fresh)
            eng.snapshot()
        # the node's membership emptied: the fold removes it again
        assert eng.rebuilds == base_rebuilds
        assert eng.batch_check(fresh) == [False] * len(fresh)
        _parity(eng, qs)


class TestBackgroundCompaction:
    def _wait_caught_up(self, eng, store, timeout=90.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            eng.snapshot()  # any read re-kicks a died-off compactor
            st = eng.projection_stats()
            if (
                st["served_cursor"] == st["log_cursor"]
                and not st["compaction_in_flight"]
            ):
                return st
            time.sleep(0.05)
        raise AssertionError(f"compactor never caught up: {st}")

    def test_writes_stay_visible_and_compactor_catches_up(self, graph):
        eng = DeviceCheckEngine(
            graph.store, graph.manager,
            frontier=2048, arena=4096, max_batch=512,
            compaction={"background": True, "catchup_rounds": 4},
        )
        eng.max_overlay_pairs = 8
        try:
            qs = synth_queries(graph, 120, seed=31)
            _parity(eng, qs)
            # a small write is absorbed by the overlay synchronously —
            # immediately visible, no compaction involved
            doc = next(
                t for t in graph.store.all_tuples()
                if t.namespace == "Doc" and t.relation == "viewers"
            )
            users = _users(graph, 12)
            first = RelationTuple.from_string(
                f"Doc:{doc.object}#viewers@{users[0]}"
            )
            graph.store.write_relation_tuples(first)
            assert eng.batch_check([first]) == [True]
            assert eng.compactions == 0
            # now overflow the overlay: serving stays on the old
            # generation while the compactor folds off-path
            rest = [
                RelationTuple.from_string(f"Doc:{doc.object}#viewers@{u}")
                for u in users[1:]
            ]
            graph.store.write_relation_tuples(*rest)
            st = self._wait_caught_up(eng, graph.store)
            assert eng.compactions >= 1, st
            assert st["pending_changes"] == 0
            assert eng.batch_check(rest) == [True] * len(rest)
            _parity(eng, qs)
            # the consistency cursor now covers every write
            assert eng.consistency_cursors()[0] == graph.store.log_head
            graph.store.delete_relation_tuples(first, *rest)
            self._wait_caught_up(eng, graph.store)
            _parity(eng, qs)
        finally:
            eng.close()

    def test_unfoldable_change_compacts_via_rebuild(self, graph):
        eng = DeviceCheckEngine(
            graph.store, graph.manager,
            frontier=2048, arena=4096, max_batch=512,
            compaction={"background": True},
        )
        try:
            qs = synth_queries(graph, 80, seed=37)
            _parity(eng, qs)
            base_compactions = eng.compactions
            # a brand-new namespace fits neither the overlay nor the fold
            # (compiled table dims): the compactor re-projects off-path
            t = RelationTuple.from_string("bgfreshns:obj#rel@someone")
            graph.store.write_relation_tuples(t)
            st = self._wait_caught_up(eng, graph.store)
            assert eng.compactions >= base_compactions + 1, st
            assert eng.last_compaction_mode == "rebuild"
            _parity(eng, [t] + qs)
        finally:
            graph.store.delete_relation_tuples(
                RelationTuple.from_string("bgfreshns:obj#rel@someone")
            )
            eng.close()


# -- the compile gate --------------------------------------------------------


class TestWarmAcrossSwap:
    def test_same_shape_folds_compile_nothing_after_warm(self, graph):
        """ISSUE 8 acceptance: N same-shape generation swaps after warm-up
        add zero XLA compiles; a genuine shape-growing change declares the
        engine cold (exactly the re-arm point) and re-projects."""
        eng = DeviceCheckEngine(
            graph.store, graph.manager,
            frontier=2048, arena=4096, max_batch=512,
        )
        eng.max_overlay_pairs = 2
        qs = synth_queries(graph, 64, seed=41)
        _parity(eng, qs)  # warm-up: compiles the steady-state shapes
        eng.batch_check(qs[:6])  # ...including the small dispatch bucket
        watch = compilewatch.get()
        watch.declare_warm()
        c0 = watch.compiles_total
        base_folds, base_rebuilds = eng.folds, eng.rebuilds
        docs = [
            t for t in graph.store.all_tuples()
            if t.namespace == "Doc" and t.relation == "viewers"
        ]
        users = _users(graph, 6)
        written = []
        for rnd in range(3):
            grants = [
                RelationTuple.from_string(
                    f"Doc:{docs[rnd].object}#viewers@{u}"
                )
                for u in users
            ]
            graph.store.write_relation_tuples(*grants)
            written.extend(grants)
            assert eng.batch_check(grants) == [True] * len(grants)
        assert eng.folds >= base_folds + 3
        assert eng.rebuilds == base_rebuilds
        assert watch.compiles_total == c0, (
            "XLA compiled across a same-shape generation swap"
        )
        assert watch.warm, "same-shape swaps must not re-arm the observatory"
        # genuine growth: a new namespace widens the compiled tables —
        # the rebuild declares cold (new compiles are legitimate again)
        t = RelationTuple.from_string("warmgrowthns:obj#rel@someone")
        graph.store.write_relation_tuples(t)
        eng.snapshot()
        assert eng.rebuilds == base_rebuilds + 1
        assert not watch.warm
        graph.store.delete_relation_tuples(t, *written)
        eng.snapshot()
        _parity(eng, qs)


def test_projection_stats_vocabulary(graph):
    eng = DeviceCheckEngine(
        graph.store, graph.manager,
        frontier=2048, arena=4096, max_batch=512,
    )
    eng.snapshot()
    st = eng.projection_stats()
    for k in (
        "generation", "rebuilds", "folds", "compactions",
        "compaction_errors", "last_compaction_mode", "background",
        "fold_enabled", "compaction_in_flight", "overlay_active",
        "overlay_pairs", "overlay_dirty", "overlay_pair_cap",
        "overlay_dirty_cap", "pending_changes", "since_base",
        "fold_max_pairs", "snap_cursor", "served_cursor", "log_cursor",
        "projection_build_s", "projection_upload_s", "build_phases",
    ):
        assert k in st, k
    assert st["generation"] >= 1
    assert st["snap_cursor"] <= st["served_cursor"] <= st["log_cursor"]
