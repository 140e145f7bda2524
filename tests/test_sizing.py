"""The sizing function (engine/snapshot.py ``device_bytes``) against the
arrays a projection really builds, the loud caps, and the engine at a node
table filled to its pad: what a graph that fills the chip leans on."""

import numpy as np
import pytest

from ketotpu.api.types import RelationTuple, SubjectID, SubjectSet
from ketotpu.engine import delta as dl
from ketotpu.engine import hashtab
from ketotpu.engine import snapshot as sn
from ketotpu.engine.vocab import Vocab
from ketotpu.leopard import closure as leo
from ketotpu.leopard import device as leodev
from ketotpu.utils.synth import build_synth

PAIR_CAP = 256


@pytest.fixture(scope="module")
def manager():
    return build_synth(n_users=2, n_groups=1, n_folders=1, n_docs=1).manager


def _graph(docs: int, sets: int, users: int, groups: int = 3):
    """``docs`` nodes (``Doc:d<i>#viewers``) of one direct tuple each, from
    ``users`` users in turn, and ``sets`` subject-set tuples on the first
    docs, from ``groups`` group sets: tuples = docs + sets, edges = sets,
    subjects = users + the groups used."""
    rows = [RelationTuple("Doc", f"d{i}", "viewers", SubjectID(f"u{i % users}"))
            for i in range(docs)]
    rows += [RelationTuple("Doc", f"d{i % docs}", "viewers",
                           SubjectSet("Group", f"g{i % groups}", "members"))
             for i in range(sets)]
    return rows


def _built_bytes(snap, leopard=None) -> dict:
    """Bytes by group of the arrays the build made."""
    a = snap.arrays()
    ov = dl.overlay_arrays(dl.OverlayState(), snap, pair_cap=PAIR_CAP)

    def total(keys):
        return sum(np.asarray(a[k]).nbytes for k in keys)

    shipped = leodev.ship_pairs(leopard) or {}
    return {
        "csr": total(["row_ptr", "edge_hi", "edge_obj"]),
        "node_table": total(k for k in a if k.startswith("nt_")),
        "membership_table": total(k for k in a if k.startswith("mt_")),
        "overlay": sum(np.asarray(v).nbytes for v in ov.values()),
        "leopard": sum(v.nbytes for v in shipped.values()),
        "membership": total(["mem_row_ptr", "mem_ord_subj"]),
        "expand_only": total(sn.EXPAND_ONLY_KEYS) - total(
            ["mem_row_ptr", "mem_ord_subj"]),
        "mesh_only": total(sn.MESH_ONLY_KEYS),
    }


def _reckoned(snap, leopard=None) -> dict:
    groups = sn.device_bytes(
        tuples=snap.n_tuples, nodes=snap.n_nodes, edges=snap.n_edges,
        subjects=len(snap.vocab.subjects), pair_cap=PAIR_CAP,
        leopard_pairs=len(leopard.elt_packed) if leopard is not None else 0,
    )
    assert snap.node_tab["pw"].shape == snap.mem_tab["pw"].shape == (4,)
    assert set(groups) == set(sn.DEVICE_GROUPS)
    assert all(g["live"] <= g["padded"] for g in groups.values())
    return {name: g["padded"] for name, g in groups.items()}


# on both sides of a power-of-two edge (pads of 64 and 128) in each count
CASES = {
    "tuples_nodes_at_pad": (64, 0, 8),
    "tuples_nodes_over_pad": (65, 0, 8),
    "edges_at_pad": (100, 64, 8),
    "edges_over_pad": (100, 65, 8),
    "subjects_at_pad": (100, 3, 61),
    "subjects_over_pad": (100, 3, 62),
    "tuples_over_nodes_under": (128, 1, 8),
    "table_buckets_over_floor": (129, 200, 8),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_device_bytes_are_the_built_arrays_bytes(case, manager):
    docs, sets, users = CASES[case]
    cols = dl.TupleColumns(Vocab())
    for t in _graph(docs, sets, users):
        cols.apply(1, t)
    snap = dl.build_snapshot_cols(cols, manager, version=0)
    assert (snap.n_nodes, snap.n_tuples, snap.n_edges) == (
        docs, docs + sets, sets)
    idx = leo.ClosureIndex(max_pairs=1 << 20)
    idx.build_from_cols(cols, manager)
    assert _reckoned(snap, idx) == _built_bytes(snap, idx)
    assert _reckoned(snap) == _built_bytes(snap)


@pytest.mark.parametrize("grow", [3, 40], ids=["inside_pad", "across_pad"])
def test_device_bytes_hold_over_a_fold(grow, manager):
    """A fold splices the arrays instead of building them: the counts it
    ends on size them all the same (inside the pads; a fold that would
    cross one declines and the caller builds in full)."""
    cols = dl.TupleColumns(Vocab())
    for t in _graph(60, 10, 8):
        cols.apply(1, t)
    base = dl.build_snapshot_cols(cols, manager, version=0)
    changes = [(1, RelationTuple("Doc", f"d{i}", "owners", SubjectID("u1")))
               for i in range(grow)] + [(-1, _graph(60, 10, 8)[5])]
    for op, t in changes:
        cols.apply(op, t)
    try:
        snap = dl.fold_snapshot_cols(base, cols.vocab, changes, version=1)
    except dl.FoldRejected:
        assert grow == 40  # 60 + 40 nodes pass the pad of 64
        snap = dl.build_snapshot_cols(cols, manager, version=1)
    assert snap.n_nodes == 60 + grow and snap.n_tuples == 69 + grow
    assert _reckoned(snap) == _built_bytes(snap)


def test_resident_bytes_split_check_from_expand_and_mesh():
    g = sn.device_bytes(tuples=1000, nodes=900, edges=500, subjects=300)
    check = sn.resident_bytes(g)
    assert check == sum(g[k]["padded"] for k in (
        "csr", "node_table", "membership_table", "overlay", "leopard"))
    assert sn.resident_bytes(g, "expand") == (
        g["membership"]["padded"] + g["expand_only"]["padded"])
    assert sn.resident_bytes(g, "mesh", "live") == g["mesh_only"]["live"]


def test_the_150m_drive_graph_is_reckoned_at_seven_gigabytes():
    """The staircase at the counts of ``drive-150m`` (CPU survey, PR 35):
    tuple-sized arrays pad to 2^28, edge- and node-sized to 2^27."""
    g = sn.device_bytes(tuples=150_000_162, nodes=132_697_037,
                        edges=100_758_332, subjects=24_365_625)
    # meta int32[7] and four rounds a table, whatever it holds (PR 36)
    assert g["node_table"]["padded"] == 4 * ((1 << 27) + 1) + 12 * (1 << 27) + 32
    assert g["membership_table"]["padded"] == (
        4 * ((1 << 28) + 1) + 8 * (1 << 28) + 32)
    assert sn.resident_bytes(g) == 7_113_769_112


# -- loud caps ---------------------------------------------------------------


@pytest.mark.parametrize("what", ["tuples", "nodes", "edges", "subjects"])
def test_projection_caps_raise_with_the_count_and_the_cap(what):
    sn.check_caps(**{what: sn.INT32_CAP})
    with pytest.raises(ValueError, match=rf"2147483648 {what} .* 2147483647"):
        sn.check_caps(**{what: sn.INT32_CAP + 1})


@pytest.mark.parametrize("lean", [True, False])
def test_table_caps_raise_with_the_count_and_the_cap(lean, monkeypatch):
    monkeypatch.setattr(hashtab, "_I32MAX", 1023)
    monkeypatch.setattr(hashtab, "_SLOT_CAP", 1023)  # 2^29 - 1 as served
    a = np.arange(1024, dtype=np.int32)
    hashtab.build_table(a[:512], a[:512], lean=lean)  # 512 in 512 / 1024
    with pytest.raises(ValueError, match=r"1024 entries in \d+ buckets .* 1023"):
        hashtab.build_table(a, a, lean=lean)
    if not lean:  # 2n buckets pass the bucket cap before the entries do
        with pytest.raises(ValueError, match=r"600 entries in 2048 buckets"):
            hashtab.build_table(a[:600], a[:600])


@pytest.mark.parametrize("col,top,cap", [
    ("obj", 1 << 28, 1 << 28), ("rel", 1 << 14, 1 << 14)])
def test_forward_index_refuses_an_id_it_would_wrap(col, top, cap):
    from ketotpu.storage.columnar import ColumnarTupleStore

    def store(value):
        cols = {c: np.zeros(4, np.int32) for c in ColumnarTupleStore.COLS}
        cols[col][2] = value
        s = ColumnarTupleStore()
        s.bulk_load_ids(cols)
        return s

    keys, order = store(top - 1)._fwd()
    assert len(keys) == 4 and sorted(order.tolist()) == [0, 1, 2, 3]
    with pytest.raises(ValueError, match=rf"{col} ids below {cap}.*{top}"):
        store(top)._fwd()


# -- the engine at a node table filled to its pad ------------------------------


def _filled_to(nodes: int):
    """A small Drive-style graph padded out with one-tuple nodes until the
    projection holds exactly ``nodes``."""
    from ketotpu.engine.tpu import DeviceCheckEngine

    g = build_synth(n_users=24, n_groups=4, n_folders=10, n_docs=30, seed=35)
    eng = DeviceCheckEngine(g.store, g.manager, frontier=512, arena=1024)
    have = eng.snapshot().n_nodes
    assert have < nodes
    g.store.write_relation_tuples(*[
        RelationTuple("Doc", f"fill{i}", "viewers", SubjectID("u0"))
        for i in range(nodes - have)])
    eng.refresh()
    assert eng.snapshot().n_nodes == nodes
    return g, eng


@pytest.mark.parametrize("nodes,pad", [(127, 128), (129, 256)],
                         ids=["within_2pct_under_the_pad", "a_row_over_it"])
def test_engine_verdicts_equal_the_oracles_at_a_full_node_table(nodes, pad):
    """``drive-150m``'s node table holds 132,697,037 nodes in 134,217,728
    slots (98.9 % full): the pad rows behind the last node, the dirty
    bitset and the table's capacity all sit a hair above the live count."""
    from ketotpu.utils.synth import synth_queries_mixed

    g, eng = _filled_to(nodes)
    snap = eng.snapshot()
    assert len(snap.row_ptr) == pad + 1 and nodes / pad > 0.98 or pad == 256
    assert len(snap.node_tab["tag"]) == pad
    queries = synth_queries_mixed(g, 96, seed=nodes) + [
        RelationTuple("Doc", f"fill{i}", rel, SubjectID(u))
        for i in (0, nodes // 3) for rel in ("view", "edit")
        for u in ("u0", "u1")]
    got = eng.batch_check(queries)
    want = [eng.oracle.check_is_member(q) for q in queries]
    assert list(got) == want
    assert any(want) and not all(want)
    assert eng.fallbacks == 0
    sized = sn.resident_bytes(eng.projection_stats()["device_bytes"])
    held = sum(v.nbytes for v in eng._device_arrays.values()) + sum(
        v.nbytes for v in (eng._leo_device or {}).values())
    assert 0 <= held - sized < 16384  # the compiled rewrite tables
