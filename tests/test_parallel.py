"""CPU-mesh tests for ketotpu/parallel (VERDICT round-1 items 2 and 4).

conftest.py forces an 8-device virtual CPU platform; every test here builds
a real `jax.sharding.Mesh` and runs the multi-device paths the driver's
`dryrun_multichip` exercises:

* `shard_fast_check` — query-data-parallel fast path (graph replicated),
* `graphshard.sharded_check` — graph partitioned by (namespace, object)
  hash with `lax.all_to_all` child routing and psum-merged found bits,
* `shard_general_check` — the fused AND/NOT algebra program, data-parallel.
"""

import numpy as np
import pytest

from ketotpu.api.types import RelationTuple, SubjectSet
from ketotpu.engine.tpu import DeviceCheckEngine
from ketotpu.parallel import (
    build_sharded_snapshot,
    make_mesh,
    shard_fast_check,
    shard_general_check,
    sharded_check,
)
from ketotpu.parallel.graphshard import shard_of_np
from ketotpu.storage import InMemoryTupleStore
from ketotpu.utils.synth import build_synth, synth_queries

T = RelationTuple.from_string


def _engine_and_queries(n_queries, **synth_kw):
    graph = build_synth(**synth_kw)
    eng = DeviceCheckEngine(graph.store, graph.manager, frontier=1024, arena=4096)
    eng.snapshot()
    queries = synth_queries(graph, n_queries)
    enc = tuple(np.asarray(a) for a in eng._encode(eng.snapshot(), queries, 0))
    want = [eng.oracle.check_is_member(r) for r in queries]
    return eng, graph, queries, enc, want


def test_shard_fast_check_parity():
    eng, _, _, enc, want = _engine_and_queries(
        128, n_users=64, n_groups=8, n_folders=32, n_docs=128
    )
    mesh = make_mesh(8)
    res = shard_fast_check(
        eng._device_arrays, enc, mesh, frontier=1024, arena=4096
    )
    got = np.asarray(res.found).tolist()
    over = np.asarray(res.over)
    assert not over.any()
    assert got == want


def test_shard_fast_check_rejects_uneven_batch():
    eng, _, _, enc, _ = _engine_and_queries(
        128, n_users=16, n_groups=4, n_folders=8, n_docs=16
    )
    mesh = make_mesh(8)
    bad = tuple(a[:100] for a in enc)
    with pytest.raises(ValueError, match="not divisible"):
        shard_fast_check(eng._device_arrays, bad, mesh)


def test_graph_sharded_parity_with_cross_shard_edges():
    eng, graph, queries, enc, want = _engine_and_queries(
        128, n_users=64, n_groups=8, n_folders=32, n_docs=128
    )
    n = 8
    mesh = make_mesh(n, axis="shard")
    snaps, stacked = build_sharded_snapshot(
        graph.store, graph.manager, n, eng._vocab
    )
    # the workload must actually cross shards for this test to mean anything
    v = eng._vocab
    crossings = 0
    for t in graph.store.all_tuples():
        from ketotpu.api.types import SubjectSet

        if isinstance(t.subject, SubjectSet):
            src = shard_of_np(
                np.array([v.namespaces.lookup(t.namespace)]),
                np.array([v.objects.lookup(t.object)]), n,
            )[0]
            dst = shard_of_np(
                np.array([v.namespaces.lookup(t.subject.namespace)]),
                np.array([v.objects.lookup(t.subject.object)]), n,
            )[0]
            crossings += int(src != dst)
    assert crossings > 50, f"only {crossings} cross-shard subject-set edges"

    res = sharded_check(stacked, enc, mesh, frontier=1024, arena=4096)
    got = np.asarray(res.found).tolist()
    over = np.asarray(res.over)
    assert not over.any()
    assert got == want

    # per-shard graph memory actually drops: each shard holds a fraction
    total = sum(s.n_tuples for s in snaps)
    assert total == len(graph.store)
    assert max(s.n_tuples for s in snaps) < len(graph.store) // 2


def test_graph_sharded_overflow_is_monotone():
    """Tiny capacities: overflow may void unfound queries, never found ones."""
    eng, graph, queries, enc, want = _engine_and_queries(
        64, n_users=64, n_groups=8, n_folders=64, n_docs=256
    )
    n = 8
    mesh = make_mesh(n, axis="shard")
    _, stacked = build_sharded_snapshot(graph.store, graph.manager, n, eng._vocab)
    res = sharded_check(stacked, enc, mesh, frontier=64, arena=128)
    got = np.asarray(res.found)
    over = np.asarray(res.over)
    for i, w in enumerate(want):
        if got[i]:
            assert w, f"query {i}: sharded IS but oracle NOT"
        elif not over[i]:
            assert got[i] == w, f"query {i}: clean NOT diverges"


def test_shard_general_check_and_not_path():
    """The fused AND/NOT algebra program runs data-parallel over the mesh
    (graph replicated, packed query block sharded) and matches the
    oracle — this is the mesh engine's general tier."""
    store = InMemoryTupleStore()
    store.write_relation_tuples(
        *[T(f"d:o{i}#editors@u{i % 4}") for i in range(16)],
        *[T(f"d:o{i}#signers@u{i % 3}") for i in range(16)],
    )
    from ketotpu.opl.parser import parse
    from ketotpu.storage import StaticNamespaceManager

    opl = """
import { Namespace, Context } from "@ory/keto-namespace-types"
class User implements Namespace {}
class d implements Namespace {
  related: { editors: User[], signers: User[] }
  permits = {
    finalize: (ctx: Context): boolean =>
      this.related.editors.includes(ctx.subject) &&
      this.related.signers.includes(ctx.subject),
  }
}
"""
    namespaces, errs = parse(opl)
    assert not errs
    nsm = StaticNamespaceManager(namespaces)
    eng = DeviceCheckEngine(store, nsm, frontier=512, arena=1024,
                            cap=2048, gen_arena=2048, vcap=1024)
    eng.snapshot()
    queries = [T(f"d:o{i}#finalize@u{i % 5}") for i in range(16)]
    enc = tuple(np.asarray(a) for a in eng._encode(eng.snapshot(), queries, 0))
    n = 8
    mesh = make_mesh(n)
    qpack = np.stack(
        [*enc, np.ones(len(queries), np.int32)]
    ).astype(np.int32)
    sizes, fast_b, fast_sched, vcap = eng._gen_schedule(len(queries) // n, 1)
    codes, occ = shard_general_check(
        eng._device_arrays, qpack, mesh, axis="data",
        sizes=sizes, fast_b=fast_b, fast_sched=fast_sched, vcap=vcap,
    )
    packed = np.asarray(codes)
    got = ((packed & 3) == 1).tolist()
    over = ((packed >> 2) & 1).astype(bool)
    want = [eng.oracle.check_is_member(r) for r in queries]
    assert np.asarray(occ).shape[0] == n  # one occupancy row per device
    for i, w in enumerate(want):
        if not over[i]:
            assert got[i] == w


def test_sharded_snapshot_memory_scales_down():
    """BASELINE config #5 / VERDICT r1 #4: sharding must actually divide the
    graph — per-shard CSR row counts sum to the total, and every shard holds
    roughly total/n rows, not a replica."""
    graph = build_synth(n_users=256, n_groups=16, n_folders=128, n_docs=512)
    shards, meta = build_sharded_snapshot(graph.store, graph.manager, 8)
    per_shard = [int(s.n_tuples) for s in shards]
    assert sum(per_shard) == len(graph.store)
    assert max(per_shard) < len(graph.store) / 2  # no shard hoards the graph
    assert min(per_shard) > 0


class TestMeshCheckEngine:
    """engine.mesh_devices serving integration: the graph-sharded runner
    behind the registry engine seam (parallel/meshengine.py)."""

    def test_parity_and_write_visibility(self):
        from ketotpu.parallel import MeshCheckEngine

        graph = build_synth(n_users=128, n_groups=8, n_folders=64, n_docs=256)
        eng = MeshCheckEngine(
            graph.store, graph.manager, mesh_devices=8,
            frontier=1024, arena=4096, max_batch=512,
        )
        queries = synth_queries(graph, 192, seed=21)
        want = [eng.oracle.check_is_member(q) for q in queries]
        assert eng.batch_check(queries) == want
        # writes amortize through a full (sharded) rebuild and stay exact
        graph.store.write_relation_tuples(
            RelationTuple.from_string("Group:g0#members@mesh-user")
        )
        assert eng.batch_check(
            [RelationTuple.from_string("Group:g0#members@mesh-user")]
        ) == [True]

    def test_server_boot_with_mesh(self):
        import json as _json
        import pathlib as _pl
        import urllib.request

        from ketotpu.driver import Provider, Registry
        from ketotpu.server import serve_all

        fixtures = _pl.Path(__file__).parent / "fixtures"
        cfg = Provider({
            "serve": {
                n: {"host": "127.0.0.1", "port": 0}
                for n in ("read", "write", "metrics", "opl")
            },
            "namespaces": {
                "location": str(fixtures / "rewrites_namespaces.keto.ts")
            },
            "engine": {
                "kind": "tpu", "mesh_devices": 8, "frontier": 1024,
                "arena": 4096, "max_batch": 256, "coalesce_ms": 0,
            },
        })
        reg = Registry(cfg).init()
        reg.store().write_relation_tuples(
            RelationTuple.from_string("Group:dev#members@bob"),
            RelationTuple.from_string("Folder:keto#viewers@Group:dev#members"),
            RelationTuple.from_string("File:readme#parents@Folder:keto"),
        )
        srv = serve_all(reg)
        try:
            addr = "http://%s:%d" % tuple(srv.addresses["read"])
            for subj, want in (("bob", True), ("eve", False)):
                with urllib.request.urlopen(
                    f"{addr}/relation-tuples/check/openapi?namespace=File"
                    f"&object=readme&relation=view&subject_id={subj}"
                ) as resp:
                    assert _json.loads(resp.read())["allowed"] is want, subj
            # the mesh debug surface rides the metrics port: per-shard
            # rows + controller totals + the live replica map
            maddr = "http://%s:%d" % tuple(srv.addresses["metrics"])
            with urllib.request.urlopen(f"{maddr}/debug/mesh") as resp:
                mesh = _json.loads(resp.read())
            assert len(mesh["shards"]) == 8
            assert mesh["replica_keys"] == 0
            assert mesh["skew"] >= 1.0
            assert mesh["replica_map"] == []
        finally:
            srv.stop()


def test_mesh_engine_overlay_writes_without_reshard():
    # VERDICT r2 #6: mesh writes ride per-shard delta overlays — an
    # interleaved write/check sequence must NOT trigger a full
    # build_sharded_snapshot per write, and verdicts stay overlay-exact
    from ketotpu.parallel import MeshCheckEngine

    graph = build_synth(n_users=128, n_groups=8, n_folders=64, n_docs=256)
    eng = MeshCheckEngine(
        graph.store, graph.manager, mesh_devices=8,
        frontier=1024, arena=4096, max_batch=512,
    )
    eng.snapshot()
    rebuilds0 = eng.rebuilds
    queries = synth_queries(graph, 64, seed=23)

    for k in range(6):
        t = RelationTuple.from_string(f"Doc:d{k}#viewers@mesh-w{k}")
        graph.store.write_relation_tuples(t)
        # the new grant is visible through the sharded overlay probes
        assert eng.check(
            RelationTuple.from_string(f"Doc:d{k}#view@mesh-w{k}")
        ) is True
        # and an interleaved batch still agrees with the oracle
        got = eng.batch_check(queries[: 16 + k])
        want = [eng.oracle.check_is_member(q) for q in queries[: 16 + k]]
        assert got == want
    # revocation is exact too (net-zero overlay entry)
    graph.store.delete_relation_tuples(
        RelationTuple.from_string("Doc:d0#viewers@mesh-w0")
    )
    want = eng.oracle.check_is_member(
        RelationTuple.from_string("Doc:d0#view@mesh-w0")
    )
    assert eng.check(
        RelationTuple.from_string("Doc:d0#view@mesh-w0")
    ) == want
    assert eng.rebuilds == rebuilds0, "writes must not reshard the graph"
    assert eng.overlay_applies >= 6


def test_mesh_engine_subject_set_write_goes_dirty_to_oracle():
    # a subject-set edge write dirties its owner shard's CSR row; queries
    # that touch it must come back via the host oracle (exact), others
    # stay on-device
    from ketotpu.parallel import MeshCheckEngine

    graph = build_synth(n_users=64, n_groups=8, n_folders=32, n_docs=128)
    # prime the (Doc, viewers, Group, members) relation-level pair BEFORE
    # the snapshot: overlay admission only represents writes whose pair is
    # already in the graph's dyn_pairs (a brand-new pair could extend the
    # AND/NOT taint closure and must reshard)
    graph.store.write_relation_tuples(
        RelationTuple.from_string("Doc:d99#viewers@Group:g0#members")
    )
    eng = MeshCheckEngine(
        graph.store, graph.manager, mesh_devices=8,
        frontier=1024, arena=4096, max_batch=512,
    )
    eng.snapshot()
    rebuilds0 = eng.rebuilds
    # pick a g1 member with NO pre-existing access to d5: after the
    # write, the ONLY path runs through the dirty row, so the device
    # cannot establish found and must flag dirty
    member = None
    for u in graph.users:
        if eng.oracle.check_is_member(
            RelationTuple.from_string(f"Group:g1#members@{u}")
        ) and not eng.oracle.check_is_member(
            RelationTuple.from_string(f"Doc:d5#view@{u}")
        ):
            member = u
            break
    assert member is not None
    t = RelationTuple.from_string("Doc:d5#viewers@Group:g1#members")
    graph.store.write_relation_tuples(t)
    fb0 = eng.fallbacks
    assert eng.check(
        RelationTuple.from_string(f"Doc:d5#view@{member}")
    ) is True
    assert eng.fallbacks > fb0, "dirty row must route to the oracle"
    assert eng.rebuilds == rebuilds0


def test_mesh_engine_expand_sees_overlay_writes():
    # batch_expand merges the REPLICATED overlay's deltas host-side; the
    # mesh engine must mirror writes into it (shard overlays carry
    # shard-local node ids that mean nothing to the replicated expand)
    from ketotpu.api.types import SubjectSet
    from ketotpu.parallel import MeshCheckEngine

    graph = build_synth(n_users=64, n_groups=8, n_folders=32, n_docs=128)
    eng = MeshCheckEngine(
        graph.store, graph.manager, mesh_devices=8,
        frontier=1024, arena=4096, max_batch=512,
    )
    eng.snapshot()
    doc = next(
        t for t in graph.store.all_tuples() if t.relation == "viewers"
    )
    graph.store.write_relation_tuples(
        RelationTuple.from_string(
            f"{doc.namespace}:{doc.object}#viewers@mesh-newbie"
        )
    )
    rebuilds0 = eng.rebuilds
    out = eng.batch_expand(
        [SubjectSet(doc.namespace, doc.object, "viewers")]
    )
    assert eng.rebuilds == rebuilds0, "expand write must ride the overlay"
    assert "mesh-newbie" in str(out[0].to_json())


def test_mesh_engine_general_tier_on_device():
    """VERDICT r4 #5: AND/NOT queries run the fused algebra program
    against the SHARDED graph stacks — no replicated copy (the replica
    budget is zeroed to prove nothing falls back to it), no host oracle,
    cross-shard subject-set children routed to their owners."""
    from ketotpu.opl.parser import parse
    from ketotpu.parallel import MeshCheckEngine
    from ketotpu.storage import StaticNamespaceManager

    opl = """
import { Namespace, SubjectSet, Context } from "@ory/keto-namespace-types"
class User implements Namespace {}
class Group implements Namespace { related: { members: User[] } }
class d implements Namespace {
  related: {
    editors: User[], signers: User[],
    viewers: (User | SubjectSet<Group, "members">)[]
  }
  permits = {
    view: (ctx: Context): boolean =>
      this.related.viewers.includes(ctx.subject) ||
      this.related.editors.includes(ctx.subject),
    finalize: (ctx: Context): boolean =>
      this.permits.view(ctx) &&
      this.related.signers.includes(ctx.subject),
  }
}
"""
    namespaces, errs = parse(opl)
    assert not errs
    store = InMemoryTupleStore()
    store.write_relation_tuples(
        *[T(f"d:o{i}#editors@u{i % 4}") for i in range(16)],
        *[T(f"d:o{i}#signers@u{i % 3}") for i in range(16)],
        *[T(f"d:o{i}#viewers@Group:g{i % 3}#members") for i in range(16)],
        *[T(f"Group:g{j}#members@u{j + 2}") for j in range(3)],
    )
    eng = MeshCheckEngine(
        store, StaticNamespaceManager(namespaces),
        mesh_devices=8, frontier=512, arena=1024, gen_arena=2048, vcap=1024,
        replica_budget_mb=0,  # the general tier must not want a replica
    )
    queries = [T(f"d:o{i}#finalize@u{i % 6}") for i in range(24)]
    want = [eng.oracle.check_is_member(q) for q in queries]
    fb0 = eng.fallbacks
    allowed, fallback = eng.batch_check_device_only(queries)
    assert not any(fallback), "general tier must answer on-device"
    assert allowed == want
    assert eng.fallbacks == fb0
    assert eng._device_arrays is None  # no replica was materialized


def test_mesh_engine_replica_budget_falls_back_to_oracle():
    """Over-budget replicas must NOT materialize: expand answers via the
    oracle (exact, bounded memory); general checks are unaffected — they
    run against the sharded stacks and never touch the replica."""
    from ketotpu.opl.parser import parse
    from ketotpu.parallel import MeshCheckEngine
    from ketotpu.storage import StaticNamespaceManager

    opl = """
import { Namespace, Context } from "@ory/keto-namespace-types"
class User implements Namespace {}
class d implements Namespace {
  related: { editors: User[], signers: User[] }
  permits = {
    finalize: (ctx: Context): boolean =>
      this.related.editors.includes(ctx.subject) &&
      this.related.signers.includes(ctx.subject),
  }
}
"""
    namespaces, errs = parse(opl)
    assert not errs
    store = InMemoryTupleStore()
    store.write_relation_tuples(
        *[T(f"d:o{i}#editors@u{i % 4}") for i in range(8)],
        *[T(f"d:o{i}#signers@u{i % 3}") for i in range(8)],
    )
    eng = MeshCheckEngine(
        store, StaticNamespaceManager(namespaces),
        mesh_devices=8, frontier=512, arena=1024,
        replica_budget_mb=0,  # nothing fits: always oracle
    )
    q = T("d:o1#finalize@u1")
    want = eng.oracle.check_is_member(q)
    allowed, fallback = eng.batch_check_device_only([q])
    assert fallback == [False]  # sharded general tier: no replica needed
    assert allowed == [want]
    assert eng.check(q) is want  # full path answers exactly
    out = eng.batch_expand([SubjectSet("d", "o1", "editors")])
    assert out[0] is not None  # oracle expand, no replica materialized
    assert eng._device_arrays is None


def test_mesh_engine_general_synth_differential():
    """Differential check of the SHARDED general tier over the rich synth
    graph (folder-tree TTU chains, group subject-sets, the `edit` =
    !banned && view rewrite): every non-fallback verdict must match the
    oracle, and the Drive-style workload must overwhelmingly stay
    on-device.  The toy-OPL tests pin single shapes; this sweeps the
    real benchmark shape across an 8-shard mesh with no replica."""
    from ketotpu.parallel import MeshCheckEngine
    from ketotpu.utils.synth import synth_queries_mixed

    graph = build_synth(n_users=64, n_groups=8, n_folders=32, n_docs=128,
                        seed=3)
    # the (Doc, viewers, Group, members) pair, for the overlay write below
    graph.store.write_relation_tuples(
        RelationTuple.from_string("Doc:d99#viewers@Group:g0#members"))
    eng = MeshCheckEngine(
        graph.store, graph.manager, mesh_devices=8,
        frontier=1024, arena=4096, gen_arena=4096, vcap=1024,
        max_batch=512, replica_budget_mb=0,
    )
    eng.snapshot()
    queries = synth_queries_mixed(graph, 64, seed=21, general_frac=1.0)
    want = [eng.oracle.check_is_member(q) for q in queries]
    allowed, fallback = eng.batch_check_device_only(queries)
    mismatches = [
        (str(q), got, w)
        for q, got, w, fb in zip(queries, allowed, want, fallback)
        if not fb and got != w
    ]
    assert not mismatches, mismatches[:5]
    # the general tier must answer the overwhelming majority on-device
    assert sum(fallback) <= len(queries) // 8, (
        f"{sum(fallback)}/{len(queries)} fell back"
    )
    # full path stays exact for the fallback slice too
    assert eng.batch_check(queries) == want

    # One seeded mixed wave through every branch the mesh shares with the
    # one-chip launchers (engine/wave.py; tests/test_fused_lanes.py holds
    # those two to the same): rows the overlay made dirty fall back; fast
    # rows made to read "overflowed" on the first pass are answered by the
    # retry as if nothing had happened, and stay in fallback without one.
    member = next(
        u for u in graph.users
        if eng.oracle.check_is_member(T(f"Group:g1#members@{u}"))
        and not eng.oracle.check_is_member(T(f"Doc:d5#view@{u}")))
    graph.store.write_relation_tuples(T("Doc:d5#viewers@Group:g1#members"))
    mixed = synth_queries_mixed(graph, 62, seed=22, general_frac=0.5)
    mixed += [T(f"Doc:d5#view@{member}"), T(f"Doc:d5#edit@{member}")]
    want = np.array([eng.oracle.check_is_member(q) for q in mixed])

    def run(retry):
        before = dict(eng.phase_counts)
        wave = eng._dispatch(mixed, 0)
        allowed, fallback = eng._collect(wave, retry=retry)
        return wave, allowed, fallback, {
            k: v - before.get(k, 0) for k, v in eng.phase_counts.items()
            if v != before.get(k, 0)}

    wave, base_allowed, base_fallback, counts = run(True)
    assert base_fallback[-2:].all(), "both dirty rows are the oracle's"
    assert (base_allowed[~base_fallback] == want[~base_fallback]).all()
    assert "check_mesh_retry" not in counts

    real, passes = eng._fast_bits, []
    answered = wave.err | wave.general | base_fallback
    if wave.leo_res is not None:
        answered |= wave.leo_res[1]
    forced = np.flatnonzero(~answered)[:5]
    assert len(forced) == 5

    def overflowed(res, k):
        bits = real(res, k)
        if not passes:
            bits.found[forced], bits.over[forced] = False, True
        passes.append(k)
        return bits

    eng._fast_bits = overflowed
    retries0 = eng.retries
    _, allowed, fallback, counts = run(True)
    assert passes == [64, 5] and eng.retries - retries0 == 5
    assert (allowed == base_allowed).all()
    assert (fallback == base_fallback).all()
    # the mesh's own span set: the retry under the run lock, and neither
    # of the one-chip collect's phases
    assert counts["check_mesh_retry"] == 1 and counts["check_mesh_fast"] == 1
    assert not {"check_retry", "check_collect_sync"} & set(eng.phase_counts)

    del passes[:]
    _, allowed, fallback, counts = run(False)
    assert passes == [64] and "check_mesh_retry" not in counts
    assert (fallback == base_fallback | np.isin(np.arange(64), forced)).all()
    assert (allowed[~fallback] == want[~fallback]).all()


# ---------------------------------------------------------------------------
# ISSUE 10: production sharded serving — live waves, hot-shard replication,
# skew rebalancing, failover
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_mesh_columnar_block_parity_bit_identical():
    """batch_check_block through the mesh must be bit-identical to the
    single-chip device engine over a randomized mixed workload whose
    subject-set hops cross shards (the synth graph guarantees crossings —
    see test_graph_sharded_parity_with_cross_shard_edges)."""
    from ketotpu.engine import columns
    from ketotpu.parallel import MeshCheckEngine
    from ketotpu.utils.synth import synth_queries_mixed

    graph = build_synth(n_users=128, n_groups=8, n_folders=64, n_docs=256,
                        seed=5)
    dev = DeviceCheckEngine(
        graph.store, graph.manager, frontier=1024, arena=4096, max_batch=512,
    )
    mesh = MeshCheckEngine(
        graph.store, graph.manager, mesh_devices=8,
        frontier=1024, arena=4096, max_batch=512,
    )
    rng = np.random.default_rng(17)
    for _trial in range(3):
        qs = synth_queries_mixed(
            graph, 96, seed=int(rng.integers(1 << 30)), general_frac=0.25
        )
        block = columns.ColumnBlock.from_tuples(qs)
        a_dev, errs_dev = dev.batch_check_block(block, 0)
        a_mesh, errs_mesh = mesh.batch_check_block(block, 0)
        assert not errs_dev and not errs_mesh
        assert np.array_equal(np.asarray(a_dev), np.asarray(a_mesh))


@pytest.mark.slow
def test_mesh_warm_gate_zero_compiles_across_replica_swap():
    """ISSUE 10 satellite: a warmed mesh engine survives a same-shape
    generation swap (replica publish re-ships the stacked partitions)
    with ZERO new XLA compiles — the `_swap_shape_signature` gate."""
    from ketotpu import compilewatch
    from ketotpu.parallel import MeshCheckEngine

    graph = build_synth(n_users=128, n_groups=8, n_folders=64, n_docs=256)
    eng = MeshCheckEngine(
        graph.store, graph.manager, mesh_devices=8,
        frontier=1024, arena=4096, max_batch=512,
    )
    qs = synth_queries(graph, 128, seed=31)
    want = [eng.oracle.check_is_member(q) for q in qs]
    assert eng.batch_check(qs) == want  # warm-up: compiles steady shapes
    qs2 = synth_queries(graph, 128, seed=32)
    want2 = [eng.oracle.check_is_member(q) for q in qs2]
    assert eng.batch_check(qs2) == want2  # same shapes, fresh cache keys
    watch = compilewatch.get()
    watch.declare_warm()
    c0 = watch.compiles_total
    gen0 = eng.generation

    # copy a doc owned by the fullest shard onto the emptiest shard: the
    # copy pads into the existing max-shard shapes, so the swap is
    # signature-stable
    rows = np.array([s.n_tuples for s in eng._shard_snaps])
    target = int(rows.argmin())
    v = eng._vocab
    key = None
    for t in graph.store.all_tuples():
        ns_id = v.namespaces.lookup(t.namespace)
        obj_id = v.objects.lookup(t.object)
        s = int(shard_of_np(np.array([ns_id]), np.array([obj_id]), 8)[0])
        if s == int(rows.argmax()):
            key = (int(ns_id), int(obj_id))
            break
    assert key is not None
    assert eng._publish_replica_map({key: (target,)})
    assert eng.generation == gen0 + 1

    qs3 = synth_queries(graph, 128, seed=33)
    want3 = [eng.oracle.check_is_member(q) for q in qs3]
    assert eng.batch_check(qs3) == want3
    assert watch.compiles_total == c0, (
        "XLA compiled across a same-shape replica publish"
    )
    assert watch.warm, "same-shape swap must not re-arm the observatory"


@pytest.mark.slow
def test_mesh_hot_replication_routes_and_write_visible():
    """Hammering one object makes it sketch-hot; replicate_now publishes a
    copy; subsequent roots route to the less-loaded replica; writes stay
    visible through BOTH the owner and replica overlays."""
    from ketotpu.parallel import MeshCheckEngine

    graph = build_synth(n_users=128, n_groups=8, n_folders=64, n_docs=256)
    eng = MeshCheckEngine(
        graph.store, graph.manager, mesh_devices=8,
        frontier=1024, arena=4096, max_batch=512,
        hot_min=8, replica_max_keys=4,
    )
    users = graph.users[:32]
    hammer = [RelationTuple.from_string(f"Doc:d7#view@{u}") for u in users]
    want = [eng.oracle.check_is_member(q) for q in hammer]
    assert eng.batch_check(hammer) == want
    assert eng.hot_keys(), "sketch must surface the hammered object"

    added = eng.replicate_now()
    assert added >= 1
    st = eng.mesh_stats()
    assert st["replica_keys"] >= 1
    assert st["replications"] >= 1
    assert sum(r["replica_keys"] for r in eng.shard_stats()) >= 1

    # routing now prefers the colder replica over the hammered owner
    rr0 = eng.mesh_stats()["replica_routed"]
    hammer2 = [
        RelationTuple.from_string(f"Doc:d7#view@{u}")
        for u in graph.users[32:64]
    ]
    want2 = [eng.oracle.check_is_member(q) for q in hammer2]
    assert eng.batch_check(hammer2) == want2
    assert eng.mesh_stats()["replica_routed"] > rr0

    # a write on the replicated key folds into owner AND replica overlays:
    # the routed read must see it without a reshard
    rebuilds0 = eng.rebuilds
    graph.store.write_relation_tuples(
        RelationTuple.from_string("Doc:d7#viewers@replica-newbie")
    )
    assert eng.check(
        RelationTuple.from_string("Doc:d7#view@replica-newbie")
    ) is True
    assert eng.rebuilds == rebuilds0

    # broad workload stays oracle-exact after the publish
    qs = synth_queries(graph, 96, seed=13)
    assert eng.batch_check(qs) == [
        eng.oracle.check_is_member(q) for q in qs
    ]


@pytest.mark.slow
def test_mesh_rebalance_on_skew():
    """A skewed routed-root distribution crosses `rebalance_skew`; the
    rebalancer copies hot keys off the loaded shard and republishes via
    generation swap with zero verdict divergence."""
    from ketotpu.parallel import MeshCheckEngine

    graph = build_synth(n_users=128, n_groups=8, n_folders=64, n_docs=256)
    eng = MeshCheckEngine(
        graph.store, graph.manager, mesh_devices=8,
        frontier=1024, arena=4096, max_batch=512,
        hot_min=4, rebalance_skew=2.0,
    )
    eng.snapshot()
    v = eng._vocab
    ns_id = v.namespaces.lookup("Doc")
    by_shard = {}
    for i in range(256):
        obj_id = v.objects.lookup(f"d{i}")
        s = int(shard_of_np(np.array([ns_id]), np.array([obj_id]), 8)[0])
        by_shard.setdefault(s, []).append(i)
    _, docs = max(by_shard.items(), key=lambda kv: len(kv[1]))

    users = graph.users[:16]
    hammer = [
        RelationTuple.from_string(f"Doc:d{d}#view@{u}")
        for d in docs[:4] for u in users
    ]
    want = [eng.oracle.check_is_member(q) for q in hammer]
    assert eng.batch_check(hammer) == want
    assert eng.shard_skew() >= 2.0

    gen0 = eng.generation
    assert eng.rebalance_now() is True
    st = eng.mesh_stats()
    assert st["rebalances"] == 1
    assert st["replica_keys"] >= 1
    assert eng.generation == gen0 + 1

    qs = synth_queries(graph, 96, seed=19)
    assert eng.batch_check(qs) == [
        eng.oracle.check_is_member(q) for q in qs
    ]


@pytest.mark.slow
def test_mesh_shard_failover_and_recovery():
    """A faulted shard degrades its roots to the host oracle (verdicts
    stay exact); fallback attribution moves ONLY on the faulted shard;
    dropping the fault plan recovers the shard on the next dispatch and
    the fallback gauge returns to zero."""
    from ketotpu import faults
    from ketotpu.parallel import MeshCheckEngine

    graph = build_synth(n_users=128, n_groups=8, n_folders=64, n_docs=256)
    eng = MeshCheckEngine(
        graph.store, graph.manager, mesh_devices=8,
        frontier=1024, arena=4096, max_batch=512,
    )
    qs = synth_queries(graph, 128, seed=9)
    want = [eng.oracle.check_is_member(q) for q in qs]
    assert eng.batch_check(qs) == want  # clean warm-up, no faults

    # pick the shard that owns the most of a FRESH query set (cache-missing
    # so the faulted batch really dispatches)
    qs2 = synth_queries(graph, 128, seed=10)
    v = eng._vocab
    owners = shard_of_np(
        np.array([v.namespaces.lookup(q.namespace) for q in qs2]),
        np.array([v.objects.lookup(q.object) for q in qs2]), 8,
    )
    victim = int(np.bincount(owners, minlength=8).argmax())
    want2 = [eng.oracle.check_is_member(q) for q in qs2]
    fb_before = np.array([r["fallbacks"] for r in eng.shard_stats()])

    faults.configure(shard_error_rate=1.0, shard_id=victim)
    try:
        assert eng.batch_check(qs2) == want2  # exact through the oracle
        assert eng._shard_down[victim]
        assert eng.mesh_stats()["shards_down"] == 1
        fb_after = np.array([r["fallbacks"] for r in eng.shard_stats()])
        delta = fb_after - fb_before
        assert delta[victim] > 0, "faulted shard must attribute fallbacks"
        others = [int(d) for i, d in enumerate(delta) if i != victim]
        assert all(d == 0 for d in others), (
            f"fallbacks moved on healthy shards: {delta.tolist()}"
        )
    finally:
        faults.reset()

    # recovery: the next dispatch polls the plan, re-ships the shard, and
    # zeroes its fallback attribution
    qs3 = synth_queries(graph, 64, seed=11)
    assert eng.batch_check(qs3) == [
        eng.oracle.check_is_member(q) for q in qs3
    ]
    assert not eng._shard_down.any()
    assert eng.shard_stats()[victim]["fallbacks"] == 0
    assert eng.mesh_stats()["shard_recoveries"] >= 1


# -- ISSUE 14: cross-host topology plumbing ----------------------------------


def test_host_of_is_a_frozen_wire_contract():
    """Every host of the mesh must compute the same owner for the same
    key across processes, versions, and restarts — the coordinate is
    part of the DCN wire contract, so its values are frozen here.  A
    deliberate hash change must bump the peerlink PROTO."""
    from ketotpu.parallel import host_of

    assert host_of("Doc", "d1", 2) == 0
    assert host_of("Group", "g0", 2) == 0
    assert host_of("Folder", "f3", 5) == 3
    assert host_of("File", "keto/README.md", 3) == 0
    # 1-host topologies short-circuit; the separator keys (ns, obj)
    # unambiguously
    assert host_of("anything", "at-all", 1) == 0
    assert all(0 <= host_of("Doc", f"d{i}", 7) < 7 for i in range(64))


def test_mesh_hosts_config_validation():
    from ketotpu.driver import ConfigError, Provider

    # peers + secret round-trip
    p = Provider({"engine": {"mesh": {"hosts": {
        "host_id": 1,
        "peers": ["10.0.0.1:7701", "10.0.0.2:7701"],
        "secret": "s3",
    }}}})
    assert p.get("engine.mesh.hosts.host_id") == 1
    # host_id must index the peer list
    with pytest.raises(ConfigError) as e:
        Provider({"engine": {"mesh": {"hosts": {
            "host_id": 2,
            "peers": ["10.0.0.1:7701", "10.0.0.2:7701"],
            "secret": "s3",
        }}}})
    assert "engine.mesh.hosts.host_id" in str(e.value)
    # a topology needs at least two hosts
    with pytest.raises(ConfigError):
        Provider({"engine": {"mesh": {"hosts": {
            "host_id": 0, "peers": ["10.0.0.1:7701"], "secret": "s3",
        }}}})
    # and a shared secret (untrusted TCP)
    with pytest.raises(ConfigError) as e:
        Provider({"engine": {"mesh": {"hosts": {
            "host_id": 0,
            "peers": ["10.0.0.1:7701", "10.0.0.2:7701"],
        }}}})
    assert "engine.mesh.hosts.secret" in str(e.value)
    # peers must be host:port strings
    with pytest.raises(ConfigError):
        Provider({"engine": {"mesh": {"hosts": {
            "host_id": 0, "peers": ["nope", "10.0.0.2:7701"],
            "secret": "s3",
        }}}})


# ---------------------------------------------------------------------------
# ISSUE 29: the object-sharded deployment as the benchmark runs it
# (benchmark/configs/drive-10m-mesh4.json): four devices, the Drive graph at
# the rehearsal's counts, batch1k's mix, held to the benchmark's own plain
# reference.  One engine for the three tests, so the two sharded programs
# compile once.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def drive_mesh():
    """The tiny Drive graph (``world``) behind a four-shard MeshCheckEngine
    (``eng``) that has answered one seeded batch of batch1k's mix (``mix``,
    ``rows``) on the device: ``allowed`` and ``fallback``, ``want`` the
    plain reference's verdicts, ``counts`` the engine phases of that one
    batch, ``texts`` the lowered text of the two sharded programs.  The
    batch runs what ``.lower().compile()`` gives, so the programs are
    traced once for the answers and for their text."""
    import json
    import pathlib
    import sys
    import types

    from ketotpu.parallel import MeshCheckEngine, graphshard

    bench = pathlib.Path(__file__).resolve().parent.parent / "benchmark"
    sys.path.insert(0, str(bench))
    try:
        import checkmix
        from graphs import drive
        from reference.zanzibar import Reference
    finally:
        sys.path.remove(str(bench))
    config = json.loads((bench / "configs/drive-10m-mesh4.json").read_text())
    mix = json.loads((bench / "traffic/batch1k.json").read_text())
    world = drive.build(config["rehearsal_graph"], 7)
    store, manager = world.server_store()
    eng = MeshCheckEngine(
        store, manager, mesh_devices=config["engine"]["mesh_devices"],
        frontier=1024, arena=4096, max_batch=512,
        # compile time follows the levels: the Drive schema's only AND/NOT
        # (edit = !banned && view) fits two skeleton levels below its root
        gen_levels=2, gen_arena=2048, vcap=1024,
    )
    rows = checkmix.rows(world, mix, np.random.default_rng(29), 512)
    queries = [
        RelationTuple.from_json(world.tuple_json(
            drive.NS_D, rows["obj"][i], rows["rel"][i],
            checkmix.subject(rows, i)))
        for i in range(512)
    ]
    texts = {}

    def lowered_once(name):
        real = getattr(graphshard, name)

        def call(*args, **static):
            lowered = real.lower(*args, **static)
            texts[name] = lowered.as_text(debug_info=True)
            return lowered.compile()(*args)
        return call

    before = dict(eng.phase_counts)
    with pytest.MonkeyPatch.context() as patch:
        for name in ("_sharded_fast_run", "_sharded_general_run"):
            patch.setattr(graphshard, name, lowered_once(name))
        allowed, fallback = eng.batch_check_device_only(queries)
    counts = {k: v - before.get(k, 0) for k, v in eng.phase_counts.items()}
    want = checkmix.reference_verdicts(
        Reference(world.cols, drive.SCHEMA), rows)
    yield types.SimpleNamespace(
        world=world, mix=mix, rows=rows, want=want, eng=eng, allowed=allowed,
        fallback=fallback, counts=counts, texts=texts)
    eng.close()


def test_mesh_answers_batch1k_mix_as_the_plain_reference(drive_mesh):
    """Sharding may not change an answer: the device path alone (no row
    handed to the host oracle) against benchmark/reference/zanzibar.py,
    which takes the tuples and knows nothing of their layout."""
    from graphs import drive  # the fixture has imported the benchmark's

    m, eng, rows = drive_mesh, drive_mesh.eng, drive_mesh.rows
    assert not np.asarray(m.fallback).any()
    assert eng.fallbacks == 0 and eng.device_failures == 0
    assert list(m.allowed) == m.want
    # the mix is the cell's: the granted eighth is granted, through both
    # permits and with subject-set subjects among the rest
    assert sum(m.want) >= round(m.mix["granted_share"] * 512) - 4
    assert (rows["rel"] == drive.R_EDIT).sum() > 100
    assert (rows["group"] >= 0).sum() > 40
    # a check-only engine never builds the replicated copy that only
    # batch_expand reads (it would land whole on device 0)
    assert eng._device_arrays is None and eng._base_device is None


def test_mesh_shards_add_up_to_the_whole_graph(drive_mesh):
    """Every tuple lies on exactly one shard, the one shard_of_np names,
    and a direct-membership probe answered shard by shard and OR-ed is
    the unsharded snapshot's answer."""
    world, eng = drive_mesh.world, drive_mesh.eng
    n = eng.n_shards
    whole = eng._snap

    def tuples_of(snap):
        """(ns, obj, rel, subject) of every tuple a snapshot holds."""
        node = snap.mem_node[: snap.n_tuples].astype(np.int64)
        hi = snap.node_hi[node].astype(np.int64)
        return np.stack([hi // snap.num_rels, snap.node_lo[node],
                         hi % snap.num_rels,
                         snap.mem_subj[: snap.n_tuples]], axis=1)

    everything = tuples_of(whole)
    assert len(everything) == len(world)
    seen = []
    for s, snap in enumerate(eng._shard_snaps):
        mine = tuples_of(snap)
        assert (shard_of_np(mine[:, 0], mine[:, 1], n) == s).all(), s
        seen.append(mine)
    seen = np.concatenate(seen)
    assert len(seen) == len(everything)  # none twice, none missing
    order = lambda a: a[np.lexsort(a.T[::-1])]  # noqa: E731
    assert (order(seen) == order(everything)).all()

    def member(snap, probe):
        """Whether ``probe`` rows (ns, obj, rel, subject) are tuples of
        ``snap``: the direct-membership probe, on the host."""
        have = {tuple(t) for t in tuples_of(snap).tolist()}
        return np.array([tuple(p) in have for p in probe.tolist()])

    rng = np.random.default_rng(29)
    present = everything[rng.integers(len(everything), size=256)]
    absent = present.copy()
    absent[:, 3] = rng.integers(world.U, size=256)  # mostly no such tuple
    probe = np.concatenate([present, absent])
    by_shard = np.stack([member(s, probe) for s in eng._shard_snaps])
    assert (by_shard.sum(axis=0) <= 1).all()
    want = member(whole, probe)
    assert (by_shard.any(axis=0) == want).all()
    assert want[:256].all() and not want[256:].all()


def test_mesh_programs_carry_their_scopes_and_spans(drive_mesh):
    """What the benchmark's mesh_* metrics read: the scopes in both
    sharded programs' lowered text and the engine phases of a dispatch."""
    eng, counts, texts = drive_mesh.eng, drive_mesh.counts, drive_mesh.texts
    assert counts["check_mesh_fast"] == 1
    assert counts["check_mesh_general"] == 1
    assert counts["check_mesh_lock_wait"] == 2  # once a program
    assert counts["check_mesh_dispatch"] == 1
    assert "check_mesh_retry" not in counts and eng.retries == 0

    fast, general = texts["_sharded_fast_run"], texts["_sharded_general_run"]
    for scope in ["probe/node_table", "probe/mem_table", "mesh/merge",
                  *(f"tier/fast/level{i}/mesh/route" for i in range(5))]:
        assert f"{scope}/" in fast, scope
    for scope in ["tier/general/level0/mesh/merge", "tier/general/up",
                  *(f"tier/general/leaves/level{i}/mesh/route"
                    for i in range(5))]:
        assert f"{scope}/" in general, scope
    for text in (fast, general):
        assert "mesh/route/all_to_all" in text
        assert "mesh/merge/psum" in text
