"""Snapshot: project the tuple store into device-resident graph arrays.

This replaces the reference's SQL round-trips (`internal/persistence/sql/
relationtuples.go:207-287`, `traverser.go:53-191`) with a static-between-
snapshots sparse graph in HBM:

* **node table** — every userset ``(namespace, object, relation)`` that owns
  at least one tuple, as two sorted int32 key columns
  (``hi = ns * num_rels + rel``, ``lo = obj``) for lexicographic binary search.
* **subject-set CSR** — per node, its subject-set tuples in insertion order
  (pagination order parity with `relationtuples.go:216-219`): the one-hop
  frontier of `TraverseSubjectSetExpansion` and `checkTupleToSubjectSet`.
* **membership pairs** — every tuple as a sorted ``(node, subject-key)`` pair;
  one lexicographic search replaces `ExistsRelationTuples`
  (relationtuples.go:249-261).
* **op table** — the compiled rewrite programs (see optable.py).

Arrays are padded to power-of-two buckets so that small write deltas rebuild
into the *same* shapes and the jitted check step does not recompile.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

import numpy as np

from ketotpu.api.types import SubjectSet
from ketotpu.engine import hashtab
from ketotpu.engine.hashtab import build_table
from ketotpu.engine.optable import (
    FlatTables,
    OpTable,
    compile_flat_tables,
    compile_op_table,
)
from ketotpu.engine.vocab import Vocab
from ketotpu.storage.memory import InMemoryTupleStore
from ketotpu.storage.namespaces import NamespaceManager

_I32MAX = np.iinfo(np.int32).max

#: arrays only the device Expand pass reads (expand_device.py) — shipped
#: lazily on first batch_expand, so Check serving never pays their
#: ~160MB of upload and HBM at the 10M-tuple scale
EXPAND_ONLY_KEYS = ("mem_row_ptr", "mem_ord_subj", "sub_ns", "sub_obj",
                    "sub_rel")
#: read only by the legacy task-tree interpreter (device.py, the mesh
#: general tier) — the single-chip fastpath/algebra programs never
#: gather it
MESH_ONLY_KEYS = ("edge_node",)


def _bucket(n: int, floor: int = 64) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


#: what an int32 column holds: ids, row offsets and ``ptr`` entries all
#: ride in one
INT32_CAP = _I32MAX

#: the groups :func:`device_bytes` reckons, and when each reaches the
#: device: with the projection (Check serving), at the first Expand, or
#: on the mesh alone
DEVICE_GROUPS = {
    "csr": "check", "node_table": "check", "membership_table": "check",
    "overlay": "check", "leopard": "check",
    "membership": "expand", "expand_only": "expand", "mesh_only": "mesh",
}


def check_caps(**counts: int) -> None:
    """Raise where a count passes what the projection's int32 columns and
    row offsets hold; a wrapped id would answer for another row, silently."""
    for what, n in counts.items():
        if int(n) > INT32_CAP:
            raise ValueError(
                f"{int(n)} {what} pass the projection's cap of {INT32_CAP} "
                "(int32 ids and row offsets)")


def device_bytes(
    *, tuples: int, nodes: int, edges: int, subjects: int,
    pair_cap: int = 4096, leopard_pairs: int = 0,
) -> Dict[str, Dict[str, int]]:
    """What a projection of these counts takes on the device, by group of
    arrays (:data:`DEVICE_GROUPS`): ``padded`` is the bytes of the arrays
    as the build pads them (every length a power of two, so memory is a
    staircase in the counts), ``live`` what the same arrays would take at
    their exact lengths.  It mirrors the padding rules of this module and
    of engine/delta.py (``_bucket``), engine/hashtab.py (``build_table``:
    a lean table has the power of two at or above its entries in buckets
    and in capacity; the empty slots of its split buckets, a thousandth of
    the entries, are reckoned as none) and leopard/device.py; ``tests/test_sizing.py`` holds
    it to the ``nbytes`` of the arrays a build really makes.  The compiled
    rewrite programs (op and flat tables, a few KB) are left out.  An
    operator reads it before a load (how many tuples fit a chip), the
    projection reports it (``/debug/projection`` ``device_bytes``)."""
    npad, epad = _bucket(nodes), _bucket(edges)
    mpad, spad = _bucket(tuples), _bucket(max(subjects, 1))

    def table(n: int, cols: int) -> Dict[str, int]:
        # ptr, then tag / key_b (/ val), meta int32[7], pw int8[4]: the
        # rounds are the builders' constants, whatever a table holds
        buckets = hashtab._bucket_pow2(max(n, 1), 128)
        cap = hashtab._bucket_pow2(max(n, 1), 64)
        fixed = 28 + hashtab.SNAPSHOT_PROBE
        return {"live": 4 * (n + 1) + 4 * cols * n + fixed,
                "padded": 4 * (buckets + 1) + 4 * cols * cap + fixed}

    pair_cap = max(1, pair_cap)
    # a fixed-shape delta table: 4 x pair_cap buckets, three columns
    delta_tab = 4 * (4 * pair_cap + 1) + 12 * pair_cap + 28 + hashtab.PROBE_SHALLOW
    dirty = _bucket(nodes + pair_cap + 1, 64)
    lpad = 0
    if leopard_pairs:
        from ketotpu.leopard.device import _pair_bucket

        lpad = _pair_bucket(leopard_pairs)
    return {
        # row_ptr, edge_hi, edge_obj
        "csr": {"live": 4 * (nodes + 1) + 8 * edges,
                "padded": 4 * (npad + 1) + 8 * epad},
        "node_table": table(nodes, 3),
        "membership_table": table(tuples, 2),
        # ov_dirty (a bool a node and a virtual node), ov_nbase, and the
        # two fixed-shape delta tables om_, ovt_
        "overlay": {"live": nodes + pair_cap + 1 + 4 + 2 * delta_tab,
                    "padded": dirty + 4 + 2 * delta_tab},
        # sets, elts, hops (leopard/device.py ship_pairs)
        "leopard": {"live": 12 * leopard_pairs, "padded": 12 * lpad},
        # mem_row_ptr, mem_ord_subj
        "membership": {"live": 4 * (nodes + 1) + 4 * tuples,
                       "padded": 4 * (npad + 1) + 4 * mpad},
        # sub_ns, sub_obj, sub_rel
        "expand_only": {"live": 12 * subjects, "padded": 12 * spad},
        # edge_node
        "mesh_only": {"live": 4 * edges, "padded": 4 * epad},
    }


def resident_bytes(groups: Dict[str, Dict[str, int]], when: str = "check",
                   kind: str = "padded") -> int:
    """The bytes of the groups on the device for ``when``."""
    return sum(g[kind] for name, g in groups.items()
               if DEVICE_GROUPS[name] == when)


@dataclass
class Snapshot:
    """Device graph arrays (numpy here; the engine ships them to HBM)."""

    vocab: Vocab
    op: OpTable
    flat: FlatTables  # flattened pure-OR programs (BFS fast path)
    taint: np.ndarray  # bool[NS, R]: relation can reach AND/NOT or a client
    # error through rewrites or live graph edges => general engine, not fastpath
    num_rels: int  # hi-key stride, static per snapshot

    node_hi: np.ndarray  # int32[N'] sorted (pad: I32MAX)
    node_lo: np.ndarray  # int32[N']
    row_ptr: np.ndarray  # int32[N'+1] subject-set CSR (pad rows: empty)
    edge_ns: np.ndarray  # int32[E'] subject-set triple of the edge target
    edge_obj: np.ndarray  # int32[E']
    edge_rel: np.ndarray  # int32[E']
    edge_node: np.ndarray  # int32[E'] node id of the target userset, -1 if none
    mem_node: np.ndarray  # int32[M'] sorted with mem_subj (pad: I32MAX)
    mem_subj: np.ndarray  # int32[M']

    n_nodes: int
    n_edges: int
    n_tuples: int
    version: int = -1

    node_tab: Dict[str, np.ndarray] = None  # hash table (hi, lo) -> node id
    mem_tab: Dict[str, np.ndarray] = None  # hash set of (node, subject)

    # bool[NS, R]: relation can reach a client-error lookup (err-only
    # closure, a subset of taint).  The algebra path's direct-hit
    # short-circuit is legal only where this is False — a device IS must
    # never hide an error the oracle would raise (engine/algebra.py).
    err_reach: np.ndarray = None

    # membership CSR over nodes (device Expand: a row's full member list,
    # leaf subjects included — the CSR above holds only subject-set edges).
    # mem_ord_subj is grouped by node in INSERTION order within each row
    # (children order parity with the store's pagination, engine.go:84-121),
    # unlike mem_subj which is sorted for binary search.
    mem_row_ptr: np.ndarray = None  # int32[N'+1]
    mem_ord_subj: np.ndarray = None  # int32[M']
    # subject decode table over the subject-id space: the (ns, obj, rel)
    # triple for subject-set subjects, -1 for plain SubjectIDs
    sub_ns: np.ndarray = None  # int32[S']
    sub_obj: np.ndarray = None  # int32[S']
    sub_rel: np.ndarray = None  # int32[S']

    def arrays(self) -> Dict[str, np.ndarray]:
        """The pytree of device arrays the jitted step consumes.

        Only arrays some jitted program actually reads ship here — the
        sorted node/membership key columns (node_hi/lo, mem_node/subj)
        stay host-side (checkpointing and host code use them; device
        lookups go through the nt_/mt_ hash tables), which at the
        10M-tuple scale keeps ~200MB off the device upload."""
        return {
            **self.flat.arrays(),
            # (a table that is on the device already goes as it is there)
            **{f"nt_{k}": v for k, v in getattr(
                self.node_tab, "columns", self.node_tab).items()},
            **{f"mt_{k}": v for k, v in getattr(
                self.mem_tab, "columns", self.mem_tab).items()},
            "row_ptr": self.row_ptr,
            # (ns, rel) packed into one word (hi = ns * num_rels + rel,
            # the node-table hi formula): the edge arrays feed arena-sized
            # gathers on the hottest path, and one packed gather + a VPU
            # div/mod decode beats two HBM gathers
            # (int32 throughout: int64 temporaries of an edge column are
            # 1 GB each at 150M tuples, and ns * num_rels + rel is small)
            "edge_hi": np.where(
                self.edge_ns >= 0,
                self.edge_ns * np.int32(self.num_rels) + self.edge_rel,
                np.int32(-1),
            ),
            "edge_obj": self.edge_obj,
            "edge_node": self.edge_node,
            "mem_row_ptr": self.mem_row_ptr,
            "mem_ord_subj": self.mem_ord_subj,
            "sub_ns": self.sub_ns,
            "sub_obj": self.sub_obj,
            "sub_rel": self.sub_rel,
            "p_kind": self.op.p_kind,
            "p_a": self.op.p_a,
            "p_b": self.op.p_b,
            "p_child_ptr": self.op.p_child_ptr,
            "p_child_idx": self.op.p_child_idx,
            "p_child_dec": self.op.p_child_dec,
            "p_child_neg": self.op.p_child_neg,
            "b_ptr": self.op.b_ptr,
            "b_rel": self.op.b_rel,
            "b_probe": self.op.b_probe,
            "prog_root": self.op.prog_root,
            "rel_err": self.op.rel_err,
            "can_sset": self.op.can_sset,
            # algebra-path routing tables (engine/algebra.py): tainted
            # subchecks expand as tree tasks, pure ones delegate to the
            # fused BFS; err_reach gates the IS short-circuit
            "taint": self.taint,
            "err_reach": (
                self.err_reach
                if self.err_reach is not None
                else np.ones_like(self.taint)
            ),
        }

    def check_arrays(self) -> Dict[str, np.ndarray]:
        """arrays() minus the expand-only and mesh-interpreter-only
        tables — the upload the single-chip Check path actually needs."""
        skip = set(EXPAND_ONLY_KEYS) | set(MESH_ONLY_KEYS)
        return {k: v for k, v in self.arrays().items() if k not in skip}

    def node_key(self, ns_id: int, obj_id: int, rel_id: int):
        return ns_id * self.num_rels + rel_id, obj_id


def _compute_taint(
    flat: FlatTables, op: OpTable, dyn_pairs, num_ns: int, num_rel: int
) -> np.ndarray:
    """Which (namespace, relation) pairs may NOT use the BFS fast path.

    Backward reachability over the relation-level edge graph to any pair
    whose program is impure (AND/NOT) or whose lookup is a client error
    (namespace/definitions.go:61): the oracle raises that error at any
    recursion depth, and NOT can flip verdicts, so a query that can *reach*
    such a pair must run on the general interpreter for exact semantics.

    Edges: live subject-set CSR pairs (expansion hops), CSS remaps (same
    namespace), and TTU hops into every namespace the via-relation's live
    edges point at (conservative: over-taint is safe, it just routes more
    queries to the slower engine).
    """
    src: list = []
    dst: list = []
    ns_targets: Dict[tuple, set] = {}
    for sns, srel, ens, erel in dyn_pairs:
        src.append(sns * num_rel + srel)
        dst.append(ens * num_rel + erel)
        ns_targets.setdefault((sns, srel), set()).add(ens)
    kc, kt = flat.css_rel.shape[2], flat.ttu_via.shape[2]
    for ns_id in range(num_ns):
        for rel_id in range(num_rel):
            base = ns_id * num_rel + rel_id
            for k in range(kc):
                r = int(flat.css_rel[ns_id, rel_id, k])
                if r >= 0:
                    src.append(base)
                    dst.append(ns_id * num_rel + r)
            for k in range(kt):
                v = int(flat.ttu_via[ns_id, rel_id, k])
                if v < 0:
                    continue
                tgt = int(flat.ttu_tgt[ns_id, rel_id, k])
                for ens in ns_targets.get((ns_id, v), ()):
                    src.append(base)
                    dst.append(ens * num_rel + tgt)
    taint = (flat.impure | op.rel_err).ravel().copy()
    # err-only closure (subset of taint): gates the algebra path's IS
    # short-circuit — a subtree that cannot raise may be pruned on a
    # direct hit, one that can must evaluate so the oracle owns the raise
    err_reach = op.rel_err.ravel().copy()
    if src:
        src_a = np.asarray(src, np.int64)
        dst_a = np.asarray(dst, np.int64)
        for seeds in (taint, err_reach):
            for _ in range(num_ns * num_rel):
                new = seeds.copy()
                np.logical_or.at(new, src_a, seeds[dst_a])
                if (new == seeds).all():
                    break
                seeds[:] = new
    return taint.reshape(num_ns, num_rel), err_reach.reshape(num_ns, num_rel)


def build_snapshot(
    store: InMemoryTupleStore,
    manager: Optional[NamespaceManager] = None,
    vocab: Optional[Vocab] = None,
    *,
    strict: bool = False,
) -> Snapshot:
    vocab = vocab if vocab is not None else Vocab()
    tuples = store.all_tuples()  # insertion (seq) order
    for t in tuples:
        vocab.intern_tuple(t)
    op = compile_op_table(manager, vocab, strict=strict)
    # the node hi-key stride is the (padded) relation dimension of the op
    # table, so device-side key computation agrees with the build
    num_rels = op.prog_root.shape[1]

    def hi(ns: int, rel: int) -> int:
        return ns * num_rels + rel

    # -- node table ---------------------------------------------------------
    triples = []  # (hi, lo) per tuple LHS
    for t in tuples:
        triples.append(
            (
                hi(vocab.namespaces.lookup(t.namespace), vocab.relations.lookup(t.relation)),
                vocab.objects.lookup(t.object),
            )
        )
    uniq = sorted(set(triples))
    node_id = {k: i for i, k in enumerate(uniq)}
    n_nodes = len(uniq)

    # -- membership pairs ---------------------------------------------------
    pairs = sorted(
        (node_id[k], vocab.subjects.lookup(t.subject.unique_id()))
        for k, t in zip(triples, tuples)
    )
    n_tuples = len(pairs)

    # -- subject-set CSR (insertion order within each row) -------------------
    per_row: Dict[int, list] = {}
    dyn_pairs = set()  # relation-level (src_ns, src_rel, dst_ns, dst_rel)
    for k, t in zip(triples, tuples):
        if not isinstance(t.subject, SubjectSet):
            continue
        s = t.subject
        s_ns = vocab.namespaces.lookup(s.namespace)
        s_obj = vocab.objects.lookup(s.object)
        s_rel = vocab.relations.lookup(s.relation)
        dyn_pairs.add(
            (
                vocab.namespaces.lookup(t.namespace),
                vocab.relations.lookup(t.relation),
                s_ns,
                s_rel,
            )
        )
        per_row.setdefault(node_id[k], []).append(
            (s_ns, s_obj, s_rel, node_id.get((hi(s_ns, s_rel), s_obj), -1))
        )
    n_edges = sum(len(v) for v in per_row.values())

    # -- pack + pad ---------------------------------------------------------
    npad = _bucket(n_nodes)
    epad = _bucket(n_edges)
    mpad = _bucket(n_tuples)

    # node_hi/node_lo and the sorted membership columns stay host-side
    # (checkpointing + overlay binary searches) — exact length, no padding
    node_hi = np.asarray([k[0] for k in uniq], np.int32)
    node_lo = np.asarray([k[1] for k in uniq], np.int32)

    row_ptr = np.zeros(npad + 1, np.int32)
    edge_ns = np.full(epad, -1, np.int32)
    edge_obj = np.full(epad, -1, np.int32)
    edge_rel = np.full(epad, -1, np.int32)
    edge_node = np.full(epad, -1, np.int32)
    e = 0
    for n in range(n_nodes):
        row_ptr[n] = e
        for s_ns, s_obj, s_rel, s_node in per_row.get(n, ()):
            edge_ns[e], edge_obj[e], edge_rel[e], edge_node[e] = s_ns, s_obj, s_rel, s_node
            e += 1
    row_ptr[n_nodes:] = e

    mem_node = np.asarray([p[0] for p in pairs], np.int32)
    mem_subj = np.asarray([p[1] for p in pairs], np.int32)
    mem_row_ptr = np.searchsorted(
        mem_node, np.arange(npad + 1)
    ).astype(np.int32)
    # insertion-ordered member list per node (tuples iterate in seq order)
    mem_ord_subj = np.full(mpad, -1, np.int32)
    fill = mem_row_ptr[: max(n_nodes, 1)].copy()
    for k, t in zip(triples, tuples):
        n = node_id[k]
        mem_ord_subj[fill[n]] = vocab.subjects.lookup(t.subject.unique_id())
        fill[n] += 1

    spad = _bucket(max(len(vocab.subjects), 1))
    sub_ns = np.full(spad, -1, np.int32)
    sub_obj = np.full(spad, -1, np.int32)
    sub_rel = np.full(spad, -1, np.int32)
    for t in tuples:
        s = t.subject
        if isinstance(s, SubjectSet):
            k = vocab.subjects.lookup(s.unique_id())
            sub_ns[k] = vocab.namespaces.lookup(s.namespace)
            sub_obj[k] = vocab.objects.lookup(s.object)
            sub_rel[k] = vocab.relations.lookup(s.relation)

    num_ns = op.prog_root.shape[0]
    flat = compile_flat_tables(
        manager, vocab, strict=strict, num_ns=num_ns, num_rel=num_rels
    )
    taint, err_reach = _compute_taint(flat, op, dyn_pairs, num_ns, num_rels)

    # O(1) device lookups (see hashtab.py)
    node_tab = build_table(
        np.fromiter((k[0] for k in uniq), np.int64, n_nodes),
        np.fromiter((k[1] for k in uniq), np.int64, n_nodes),
        np.arange(n_nodes, dtype=np.int32),
        lean=True, probe=hashtab.SNAPSHOT_PROBE,
    )
    mem_tab = build_table(
        np.fromiter((p[0] for p in pairs), np.int64, n_tuples),
        np.fromiter((p[1] for p in pairs), np.int64, n_tuples),
        lean=True, probe=hashtab.SNAPSHOT_PROBE,
    )

    snap = Snapshot(
        vocab=vocab,
        op=op,
        flat=flat,
        taint=taint,
        err_reach=err_reach,
        num_rels=num_rels,
        node_hi=node_hi,
        node_lo=node_lo,
        row_ptr=row_ptr,
        edge_ns=edge_ns,
        edge_obj=edge_obj,
        edge_rel=edge_rel,
        edge_node=edge_node,
        mem_node=mem_node,
        mem_subj=mem_subj,
        mem_row_ptr=mem_row_ptr,
        mem_ord_subj=mem_ord_subj,
        sub_ns=sub_ns,
        sub_obj=sub_obj,
        sub_rel=sub_rel,
        n_nodes=n_nodes,
        n_edges=n_edges,
        n_tuples=n_tuples,
        version=store.version,
        node_tab=node_tab,
        mem_tab=mem_tab,
    )
    # relation-level edge pairs: the delta overlay consults this to decide
    # whether a new subject-set write could extend the taint closure
    snap.dyn_pairs = dyn_pairs
    return snap
