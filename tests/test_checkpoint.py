"""Snapshot checkpoint/resume tests (SURVEY §5.4): persisted projections
restore bit-identically, and stale/mismatched checkpoints are refused."""

import dataclasses

import numpy as np
import pytest

from ketotpu.api.types import RelationTuple
from ketotpu.engine import checkpoint as ckpt
from ketotpu.engine.snapshot import Snapshot
from ketotpu.engine.tpu import DeviceCheckEngine
from ketotpu.utils.synth import build_synth, synth_queries

T = RelationTuple.from_string


@pytest.fixture(scope="module")
def graph():
    return build_synth(n_users=64, n_groups=8, n_folders=32, n_docs=128)


def _engine(graph):
    return DeviceCheckEngine(
        graph.store, graph.manager, frontier=2048, arena=4096, max_batch=512
    )


def test_roundtrip_bit_identical(graph, tmp_path):
    eng = _engine(graph)
    snap = eng.snapshot()
    path = str(tmp_path / "snap.npz")
    eng.save_checkpoint(path)
    loaded = ckpt.load_snapshot(path)
    for f in dataclasses.fields(Snapshot):
        a, b = getattr(snap, f.name), getattr(loaded, f.name)
        if isinstance(a, np.ndarray):
            assert a.dtype == b.dtype and (a == b).all(), f.name
        elif isinstance(a, int):
            assert a == b, f.name
    assert snap.node_tab.keys() == loaded.node_tab.keys()
    for k in snap.node_tab:
        assert (snap.node_tab[k] == loaded.node_tab[k]).all(), k
    for name in ("namespaces", "objects", "relations", "subjects"):
        assert getattr(snap.vocab, name).strings() == \
            getattr(loaded.vocab, name).strings()


def test_resume_skips_projection_and_answers_identically(graph, tmp_path):
    eng = _engine(graph)
    qs = synth_queries(graph, 200, seed=3)
    want = eng.batch_check(qs)
    path = str(tmp_path / "snap.npz")
    eng.save_checkpoint(path)

    fresh = _engine(graph)
    assert fresh.load_checkpoint(path) is True
    assert fresh.rebuilds == 0  # projection skipped
    assert fresh.batch_check(qs) == want
    assert fresh.rebuilds == 0
    # writes after resume still reach the device (overlay path intact)
    graph.store.write_relation_tuples(T("Group:g0#members@resumed"))
    assert fresh.batch_check(
        [T("Group:g0#members@resumed")]
    ) == [True]


def test_stale_store_version_is_refused(graph, tmp_path):
    eng = _engine(graph)
    path = str(tmp_path / "snap.npz")
    eng.save_checkpoint(path)
    graph.store.write_relation_tuples(T("Group:g1#members@late"))
    fresh = _engine(graph)
    assert fresh.load_checkpoint(path) is False
    # and the fallback projection sees the late write
    assert fresh.batch_check([T("Group:g1#members@late")]) == [True]


def test_config_fingerprint_mismatch_is_refused(graph, tmp_path):
    from ketotpu.opl.parser import parse
    from ketotpu.storage.namespaces import StaticNamespaceManager

    eng = _engine(graph)
    path = str(tmp_path / "snap.npz")
    eng.save_checkpoint(path)
    namespaces, errors = parse("class Other implements Namespace {}")
    assert not errors
    other = DeviceCheckEngine(
        graph.store, StaticNamespaceManager(namespaces),
        frontier=2048, arena=4096,
    )
    assert other.load_checkpoint(path) is False


def test_format_mismatch_is_refused(graph, tmp_path, monkeypatch):
    eng = _engine(graph)
    path = str(tmp_path / "snap.npz")
    eng.save_checkpoint(path)
    monkeypatch.setattr(ckpt, "SNAPSHOT_FORMAT", ckpt.SNAPSHOT_FORMAT + 1)
    with pytest.raises(ckpt.SnapshotFormatError):
        ckpt.load_snapshot(path)
    fresh = _engine(graph)
    assert fresh.load_checkpoint(path) is False  # graceful refusal


def test_a_format_6_checkpoint_is_refused(graph, tmp_path):
    """A file of the format before the split layout: its tables' deeper
    buckets would be missed by four rounds, so it is refused by its
    number (the loader never looks at the columns)."""
    assert ckpt.SNAPSHOT_FORMAT == 7
    eng = _engine(graph)
    path = str(tmp_path / "snap.npz")
    eng.save_checkpoint(path)
    with np.load(path, allow_pickle=False) as z:
        files = {k: z[k] for k in z.files}
    assert int(files["format"]) == 7 and files["nt_meta"].shape == (7,)
    files["format"] = np.int64(6)
    old = str(tmp_path / "v6.npz")
    np.savez(old, **files)
    with pytest.raises(ckpt.SnapshotFormatError, match="format 6"):
        ckpt.load_snapshot(old)
    assert _engine(graph).load_checkpoint(old) is False
    assert _engine(graph).load_checkpoint(path) is True


def test_registry_boot_checkpoint_cycle(tmp_path):
    """engine.checkpoint config: first boot saves, second boot resumes."""
    from ketotpu.driver import Provider, Registry

    path = tmp_path / "proj.npz"
    db = tmp_path / "keto.db"

    def boot():
        reg = Registry(Provider({
            "dsn": f"sqlite://{db}",
            "namespaces": [{"id": 0, "name": "doc", "relations": ["viewers"]}],
            "engine": {
                "kind": "tpu", "frontier": 512, "arena": 1024,
                "max_batch": 256, "checkpoint": str(path),
            },
        }))
        if not db.exists() or True:
            reg.store().migrate_up()
        return reg.init()

    reg1 = boot()
    reg1.store().write_relation_tuples(T("doc:d#viewers@alice"))
    assert reg1.check_engine().batch_check([T("doc:d#viewers@alice")]) == [True]
    # persist the current projection for the next boot
    reg1.check_engine().save_checkpoint(str(path))
    reg1.store().close()

    reg2 = boot()
    eng2 = reg2.check_engine()
    assert eng2.rebuilds == 0  # resumed, not re-projected
    assert eng2.batch_check(
        [T("doc:d#viewers@alice"), T("doc:d#viewers@eve")]
    ) == [True, False]


def test_resume_preserves_overlay_safety_metadata(tmp_path):
    """A resumed snapshot must keep dyn_pairs: an insert that creates a NEW
    relation-level subject-set pair cannot be folded into the overlay (the
    taint classification could be stale) — it must force a rebuild."""
    from ketotpu.opl.parser import parse
    from ketotpu.storage.memory import InMemoryTupleStore
    from ketotpu.storage.namespaces import StaticNamespaceManager

    namespaces, errors = parse(
        "class User implements Namespace {}\n"
        "class Group implements Namespace {\n"
        "  related: { members: (User | Group)[] }\n"
        "}\n"
        "class Doc implements Namespace {\n"
        "  related: { viewers: (User | SubjectSet<Group, \"members\">)[] }\n"
        "  permits = { view: (ctx) => "
        "this.related.viewers.includes(ctx.subject) }\n"
        "}"
    )
    assert not errors
    manager = StaticNamespaceManager(namespaces)
    store = InMemoryTupleStore()
    store.write_relation_tuples(T("Doc:d#viewers@alice"))
    eng = DeviceCheckEngine(store, manager, frontier=512, arena=1024)
    path = str(tmp_path / "snap.npz")
    eng.save_checkpoint(path)

    fresh = DeviceCheckEngine(store, manager, frontier=512, arena=1024)
    assert fresh.load_checkpoint(path) is True
    assert fresh._snap.dyn_pairs == eng._snap.dyn_pairs
    # this subject-set insert creates a relation-level pair absent from the
    # base snapshot: must trigger a full rebuild, not an overlay apply
    store.write_relation_tuples(T("Doc:d#viewers@Group:g#members"))
    store.write_relation_tuples(T("Group:g#members@bob"))
    assert fresh.batch_check([T("Doc:d#view@bob")]) == [True]
    assert fresh.rebuilds >= 1
