"""Incremental snapshot projection: column cache + device delta overlay.

Round 1 rebuilt the whole device snapshot with per-tuple Python loops on
every write (`snapshot.py:119-180` then).  This module makes the write path
incremental (SURVEY §7 step 8):

* **TupleColumns** — the store's tuples as append-only numpy id columns,
  maintained O(1) per write from the store's change log
  (`storage/memory.py:changes_since`).  A full rebuild becomes pure
  vectorized numpy (lexsort/unique/searchsorted) over these columns —
  no re-interning, no per-tuple loops.
* **OverlayState / overlay arrays** — between rebuilds, writes project into
  a small device overlay instead of a new snapshot:

  - membership deltas as two extra hash tables (``oa_`` added pairs,
    ``od_`` deleted pairs): the fast path's membership probes consult
    base OR added AND NOT deleted, so **probe verdicts are exact against
    the latest write** even though the base CSR is stale;
  - new ``(namespace, object, relation)`` nodes as a third table
    (``ov_`` → virtual node ids past the base node count);
  - a **dirty bitset** over (base + virtual) node ids marking rows whose
    subject-set edge list changed.  Expanding a dirty row would walk stale
    edges, so the fast path raises a per-query ``dirty`` flag instead and
    the engine answers those queries on the host oracle (which reads the
    live store).  Found-bits established without touching a dirty row are
    trustworthy: probes are overlay-exact and the path to every probed
    node was, by induction, clean.

  The overlay is rejected (forcing a rebuild) when it cannot represent the
  change: a vocab id beyond the base table dims, a new relation-level
  subject-set pair (it could extend the AND/NOT taint closure), or size
  beyond the configured thresholds.

The combination gives write→visibility in O(delta) with exact verdicts,
amortizing full (vectorized) rebuilds over thousands of writes — the
static-between-snapshots + delta design the SURVEY prescribes.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from ketotpu.api.types import RelationTuple, SubjectSet
from ketotpu.engine import hashtab, parallel
from ketotpu.engine.snapshot import Snapshot, _bucket, check_caps
from ketotpu.engine.vocab import Vocab

_I32MAX = np.iinfo(np.int32).max


class TupleColumns:
    """Append-only id columns over the live tuple set (amortized growth)."""

    COLS = ("ns", "obj", "rel", "subj", "is_set", "s_ns", "s_obj", "s_rel")

    def __init__(self, vocab: Vocab):
        self.vocab = vocab
        self.cap = 1024
        self.n = 0
        self.alive_count = 0
        for c in self.COLS:
            setattr(self, c, np.full(self.cap, -1, np.int32))
        self.alive = np.zeros(self.cap, bool)
        # False while the id columns are another owner's arrays
        # (from_arrays, freeze, masked): nothing may be written into them
        self._owned = True
        # tuple identity (vocab id 4-tuple) -> alive row indices (FIFO
        # delete order parity with the store's seq-ordered removal).
        # None = lazy: bulk-adopted columns skip the per-row dict build
        # (the 10M-tuple cliff) and pay it on the first delete instead.
        self._rows_by_key: Optional[Dict[Tuple, List[int]]] = {}

    @classmethod
    def from_arrays(
        cls, vocab: Vocab, cols: Dict[str, np.ndarray], alive: np.ndarray
    ) -> "TupleColumns":
        """Adopt pre-built id columns (a columnar store's base segment)
        without any per-row Python and without a copy: the columns ARE
        the store's arrays, which it never writes (a delete flips its
        alive bitmap), borrowed at their exact length.  The first append
        grows into arrays of this mirror's own, a compaction copies; a
        padded copy of eight columns is 8.6 GB at 150M rows.  The
        row-key index is lazy."""
        self = cls.__new__(cls)
        self.vocab = vocab
        n = int(len(alive))
        self.cap = self.n = n
        for c in cls.COLS:
            setattr(self, c, np.asarray(cols[c][:n], np.int32))
        self._owned = False
        self.alive = np.array(alive[:n], bool)
        self.alive_count = int(self.alive.sum())
        self._rows_by_key = None
        return self

    @classmethod
    def from_tuples(cls, vocab: Vocab, tuples) -> "TupleColumns":
        """Bulk adoption of a plain tuple list (a store rescan, a
        replica's adopted scan): capacity is sized once up front instead
        of paying log2(n) grow-copies of all 8 columns, and the row-key
        index stays lazy like :meth:`from_arrays` — the first delete
        pays for the dict, a bootstrap doesn't."""
        self = cls(vocab)
        n = len(tuples)
        cap = self.cap
        while cap < max(n, 1):
            cap *= 2
        if cap != self.cap:
            self.cap = cap
            for c in cls.COLS:
                setattr(self, c, np.full(cap, -1, np.int32))
            self.alive = np.zeros(cap, bool)
        self._rows_by_key = None
        v = vocab
        ns_c, obj_c, rel_c, subj_c = self.ns, self.obj, self.rel, self.subj
        is_set_c = self.is_set
        sns_c, sobj_c, srel_c = self.s_ns, self.s_obj, self.s_rel
        for i, t in enumerate(tuples):
            v.intern_tuple(t)
            ns_c[i] = v.namespaces.lookup(t.namespace)
            obj_c[i] = v.objects.lookup(t.object)
            rel_c[i] = v.relations.lookup(t.relation)
            subj_c[i] = v.subjects.lookup(t.subject.unique_id())
            if isinstance(t.subject, SubjectSet):
                is_set_c[i] = 1
                sns_c[i] = v.namespaces.lookup(t.subject.namespace)
                sobj_c[i] = v.objects.lookup(t.subject.object)
                srel_c[i] = v.relations.lookup(t.subject.relation)
            else:
                is_set_c[i] = 0
        self.alive[:n] = True
        self.n = n
        self.alive_count = n
        return self

    def masked(self, keep_rows: np.ndarray) -> "TupleColumns":
        """Shallow view with ``alive`` further restricted to ``keep_rows``
        (bool[n]) — shard partitioning without copying the columns."""
        out = TupleColumns.__new__(TupleColumns)
        out.vocab = self.vocab
        out.cap = self.cap
        out.n = self.n
        for c in self.COLS:
            setattr(out, c, getattr(self, c))
        out.alive = self.alive.copy()
        out.alive[: self.n] &= keep_rows[: self.n]
        out.alive_count = int(out.alive[: self.n].sum())
        out._rows_by_key = None
        out._owned = False
        return out

    def freeze(self) -> "TupleColumns":
        """Stable view for an off-thread snapshot build while the original
        keeps absorbing writes.  Appends only touch rows >= the frozen
        ``n`` (growth reallocates, never mutates the prefix) and deletes
        only flip the (copied) alive bitmap, so the id-column prefix this
        view reads is immutable — EXCEPT under ``compact()``, which the
        engine only runs on the blocking rebuild path after invalidating
        the in-flight build's generation token.  The clone must never be
        written."""
        out = TupleColumns.__new__(TupleColumns)
        out.vocab = self.vocab
        out.cap = self.cap
        out.n = self.n
        for c in self.COLS:
            setattr(out, c, getattr(self, c))
        out.alive = self.alive[: self.n].copy()
        out.alive_count = int(out.alive.sum())
        out._rows_by_key = None
        out._owned = False
        return out

    def _key_ids(self, t: RelationTuple) -> Optional[Tuple]:
        """Identity of a tuple in vocab-id space; None when any part is
        unknown to the vocab (such a tuple cannot be in the columns)."""
        v = self.vocab
        ids = (
            v.namespaces.lookup(t.namespace),
            v.objects.lookup(t.object),
            v.relations.lookup(t.relation),
            v.subjects.lookup(t.subject.unique_id()),
        )
        return None if -1 in ids else ids

    def _ensure_key_index(self) -> None:
        if self._rows_by_key is not None:
            return
        idx: Dict[Tuple, List[int]] = {}
        live = np.flatnonzero(self.alive[: self.n])
        keys = zip(
            self.ns[live].tolist(), self.obj[live].tolist(),
            self.rel[live].tolist(), self.subj[live].tolist(),
        )
        for i, key in zip(live.tolist(), keys):
            idx.setdefault(key, []).append(i)
        self._rows_by_key = idx

    def _grow(self) -> None:
        new_cap = max(self.cap * 2, 1024)
        self._owned = True  # the grown arrays are this mirror's own
        for c in self.COLS:
            arr = getattr(self, c)
            grown = np.full(new_cap, -1, np.int32)
            grown[: self.n] = arr[: self.n]
            setattr(self, c, grown)
        grown_alive = np.zeros(new_cap, bool)
        grown_alive[: self.n] = self.alive[: self.n]
        self.alive = grown_alive
        self.cap = new_cap

    def apply(self, op: int, t: RelationTuple) -> None:
        if op > 0:
            self.vocab.intern_tuple(t)
            if self.n == self.cap:
                self._grow()
            i = self.n
            v = self.vocab
            self.ns[i] = v.namespaces.lookup(t.namespace)
            self.obj[i] = v.objects.lookup(t.object)
            self.rel[i] = v.relations.lookup(t.relation)
            self.subj[i] = v.subjects.lookup(t.subject.unique_id())
            if isinstance(t.subject, SubjectSet):
                self.is_set[i] = 1
                self.s_ns[i] = v.namespaces.lookup(t.subject.namespace)
                self.s_obj[i] = v.objects.lookup(t.subject.object)
                self.s_rel[i] = v.relations.lookup(t.subject.relation)
            else:
                self.is_set[i] = 0
            self.alive[i] = True
            self.n += 1
            self.alive_count += 1
            if self._rows_by_key is not None:
                key = (int(self.ns[i]), int(self.obj[i]),
                       int(self.rel[i]), int(self.subj[i]))
                self._rows_by_key.setdefault(key, []).append(i)
        else:
            key = self._key_ids(t)
            if key is None:
                return
            self._ensure_key_index()
            rows = self._rows_by_key.get(key)
            if rows:
                i = rows.pop(0)
                if not rows:
                    del self._rows_by_key[key]
                if self.alive[i]:
                    self.alive[i] = False
                    self.alive_count -= 1

    def compact(self) -> None:
        """Drop dead rows (preserving order) when they dominate."""
        if self.n - self.alive_count <= self.n // 2:
            return
        keep = np.flatnonzero(self.alive[: self.n])
        for c in self.COLS:
            arr = getattr(self, c)
            if not self._owned:  # borrowed: compact into a copy
                arr = np.empty(self.cap, np.int32)
                arr[: len(keep)] = getattr(self, c)[keep]
                setattr(self, c, arr)
            else:
                arr[: len(keep)] = arr[keep]
            arr[len(keep):] = -1
        self._owned = True
        self.alive[: len(keep)] = True
        self.alive[len(keep):] = False
        self.n = len(keep)
        if self._rows_by_key is not None:
            remap = {int(old): new for new, old in enumerate(keep)}
            for key, rows in self._rows_by_key.items():
                self._rows_by_key[key] = [
                    remap[r] for r in rows if r in remap
                ]


#: per-phase wall-time keys ``build_snapshot_cols`` reports (the bench and
#: ``keto_projection_phase_seconds`` carry the same vocabulary)
BUILD_PHASES = ("columns", "sort_unique", "csr_pack", "hashtab", "optable")


def build_snapshot_cols(
    cols: TupleColumns,
    manager,
    *,
    strict: bool = False,
    version: int = -1,
    phases: Optional[Dict[str, float]] = None,
    table_sink=None,
) -> Snapshot:
    """Vectorized snapshot build from the column cache.

    Produces arrays identical to `snapshot.build_snapshot` (same node
    ordering, same insertion-order CSR, same membership sort) without
    per-tuple Python loops — rebuild cost is a few numpy passes, sharded
    across the build pool on multi-core hosts (engine/parallel.py).

    ``phases`` (optional dict) accumulates per-phase wall seconds under
    the BUILD_PHASES keys, so a projection_build_s regression is
    attributable to a specific stage.

    ``table_sink(prefix, table)`` (optional) is handed each hash table the
    moment it is built and returns what the snapshot keeps in its place.
    The tables are the largest arrays and are built first, while little
    else is: a device engine ships each there and keeps the device's
    columns (``hashtab.DeviceTable``), so one table's host copy is gone
    before the next is built (2-3 GB each at 150M tuples).
    """
    import time

    from ketotpu.engine.optable import compile_flat_tables, compile_op_table
    from ketotpu.engine.snapshot import _compute_taint

    ph = phases if phases is not None else {}

    def _mark(key, t0):
        t1 = time.perf_counter()
        ph[key] = ph.get(key, 0.0) + (t1 - t0)
        return t1

    t0 = time.perf_counter()
    vocab = cols.vocab
    op = compile_op_table(manager, vocab, strict=strict)
    num_rels = op.prog_root.shape[1]
    num_ns = op.prog_root.shape[0]
    t0 = _mark("optable", t0)

    # -- columns: live views of the id columns ------------------------------
    # all-alive (the cold build after compaction) takes zero-copy slices;
    # otherwise one gather per column.  The subject-set decode columns are
    # NEVER gathered at full width — later stages index them through the
    # (much smaller) set-row selection instead.
    n_all = cols.n
    if cols.alive_count == n_all:
        live = None
        ns = cols.ns[:n_all]
        obj = cols.obj[:n_all]
        rel = cols.rel[:n_all]
        subj = cols.subj[:n_all]
        is_set = cols.is_set[:n_all]
    else:
        live = np.flatnonzero(cols.alive[:n_all])
        ns = cols.ns[live]
        obj = cols.obj[live]
        rel = cols.rel[live]
        subj = cols.subj[live]
        is_set = cols.is_set[live]
    n_tuples = len(ns)
    t0 = _mark("columns", t0)

    # -- node table (sorted by (hi, lo), ids dense) -------------------------
    # packed key = (ns * num_rels + rel) << 32 | obj, built in place to
    # avoid four 85MB temporaries at the 10M-row scale
    packed = np.empty(n_tuples, np.int64)

    def _pack(lo, hi_):
        seg = packed[lo:hi_]
        np.multiply(ns[lo:hi_], num_rels, out=seg, casting="unsafe")
        seg += rel[lo:hi_]
        seg <<= 32
        seg += obj[lo:hi_]

    parallel.shard_apply(n_tuples, _pack)

    # one stable argsort of the packed key replaces the old
    # unique + searchsorted + argsort(node_of_row) triple: equal packed
    # keys ARE equal nodes and packed order IS node order, so this
    # permutation doubles as the membership insertion order (m_order).
    # From here on every array is dropped where its last reader ends: at
    # 150M rows each int64 column is 1.2 GB, and the function's locals
    # would otherwise all live to its end.
    s1 = np.argsort(packed, kind="stable")
    sp = packed[s1]
    del packed
    mpad = _bucket(n_tuples)
    # insertion-ordered member list per node (device Expand): s1 is stable
    # by node, so it keeps the live rows' append (seq) order within each
    # group; gathered straight into the padded array
    mem_ord_subj = np.empty(mpad, np.int32)
    mem_ord_subj[n_tuples:] = -1
    subj_s1 = mem_ord_subj[:n_tuples]

    def _by_node(lo, hi_):
        # (clip: the indices are a permutation, and "raise" buffers out)
        np.take(subj, s1[lo:hi_], out=subj_s1[lo:hi_], mode="clip")

    parallel.shard_apply(n_tuples, _by_node)
    newg = np.empty(n_tuples, bool)
    if n_tuples:
        newg[0] = True
        np.not_equal(sp[1:], sp[:-1], out=newg[1:])
    uniq_packed = sp[newg]
    del sp
    gid32 = np.cumsum(newg, dtype=np.int32)  # node id + 1 per position
    gid32 -= 1
    n_nodes = len(uniq_packed)

    # membership pairs sorted by (node, subj): node values come free as
    # the group ids (gid32); the subject column only needs sorting WITHIN
    # multi-tuple groups — most nodes own a single tuple, so instead of a
    # full lexsort (the old build's single hottest pass) sort just the
    # multi-group rows by a packed (node, subj) VALUE key.  Singleton
    # rows pass through in s1 order, which is already (node, subj) order.
    mem_node_v = gid32
    mem_subj_v = subj_s1.copy()
    if n_tuples:
        multi = np.empty(n_tuples, bool)  # row sits in a group of size >= 2
        multi[:-1] = newg[1:]
        multi[-1] = True
        multi &= newg
        np.logical_not(multi, out=multi)
        rows_m = np.flatnonzero(multi)
        del multi
        if len(rows_m):
            mk = gid32[rows_m].astype(np.int64)
            mk <<= 32
            mk += subj_s1[rows_m]
            mk.sort()  # values only: grouped by node, subj ascending
            mem_subj_v[rows_m] = mk & 0xFFFFFFFF
            del mk
        del rows_m
    t0 = _mark("sort_unique", t0)

    # -- O(1) device lookups (hashtab.py), before the CSR is packed ----------
    check_caps(tuples=n_tuples, nodes=n_nodes, subjects=len(vocab.subjects))
    node_hi = np.empty(n_nodes, np.int32)
    node_lo = np.empty(n_nodes, np.int32)

    def _node_cols(lo, hi_):
        node_hi[lo:hi_] = uniq_packed[lo:hi_] >> 32
        node_lo[lo:hi_] = uniq_packed[lo:hi_] & 0xFFFFFFFF

    parallel.shard_apply(n_nodes, _node_cols)
    node_tab = hashtab.build_table(
        node_hi,
        node_lo,
        np.arange(n_nodes, dtype=np.int32),
        lean=True, probe=hashtab.SNAPSHOT_PROBE,
    )
    if table_sink is not None:
        node_tab = table_sink("nt", node_tab)
    mem_tab = hashtab.build_table(
        mem_node_v, mem_subj_v,
        lean=True, probe=hashtab.SNAPSHOT_PROBE,
    )
    if table_sink is not None:
        mem_tab = table_sink("mt", mem_tab)
    t0 = _mark("hashtab", t0)

    # -- subject-set CSR (insertion order within each row) -------------------
    # s1 already groups rows by node with seq order preserved, so the set
    # rows in s1 order ARE the edge list (old: flatnonzero + stable argsort)
    sel = np.empty(n_tuples, bool)

    def _sel(lo, hi_):
        np.equal(is_set[s1[lo:hi_]], 1, out=sel[lo:hi_])

    parallel.shard_apply(n_tuples, _sel)
    ss_sorted = s1[sel]  # row index (live-space) per edge, grouped by node
    del s1
    ss_rows = gid32[sel]  # node id per edge
    del sel
    n_edges = len(ss_sorted)

    # only device-bound arrays get _bucket padding; node_hi/node_lo and the
    # sorted membership columns stay host-side (checkpointing + overlay
    # binary searches) and are stored at exact length
    npad = _bucket(n_nodes)
    epad = _bucket(n_edges)
    check_caps(edges=n_edges)

    def edge_col(col):
        """The set rows' ``col``, gathered straight into a padded array."""
        out = np.empty(epad, np.int32)
        out[n_edges:] = -1
        np.take(col, rows_set, out=out[:n_edges], mode="clip")
        return out

    rows_set = ss_sorted if live is None else live[ss_sorted]
    edge_ns, edge_obj, edge_rel = (
        edge_col(cols.s_ns), edge_col(cols.s_obj), edge_col(cols.s_rel))
    del rows_set
    edge_ns_v, edge_obj_v, edge_rel_v = (
        edge_ns[:n_edges], edge_obj[:n_edges], edge_rel[:n_edges])

    row_ptr = np.empty(npad + 1, np.int32)
    row_ptr[0] = 0
    if n_nodes:
        np.cumsum(np.bincount(ss_rows, minlength=n_nodes)[:n_nodes],
                  out=row_ptr[1 : n_nodes + 1])
    row_ptr[n_nodes + 1:] = n_edges

    # -- dynamic relation-level pairs (for taint) ---------------------------
    # one (source (ns, rel), target (ns, rel)) code per edge; the source
    # pair is the high word of the edge's node key.  The codes are few
    # (the square of the op table's padded dims), so a histogram names the
    # distinct ones without sorting a 100M-row column
    e_hi = edge_ns_v.astype(np.int64)
    e_hi *= num_rels
    e_hi += edge_rel_v
    hi_dim = num_ns * num_rels
    dkey = uniq_packed[ss_rows]
    dkey >>= 32
    if n_edges and hi_dim * hi_dim <= (1 << 22) and (
            int(dkey.max()) < hi_dim and int(e_hi.max()) < hi_dim):
        dkey *= hi_dim
        dkey += e_hi
        du = np.flatnonzero(np.bincount(dkey))
        d_src, d_dst = du // hi_dim, du % hi_dim
    else:
        dkey <<= 32
        dkey |= e_hi
        du = np.unique(dkey)
        d_src, d_dst = du >> 32, du & 0xFFFFFFFF
    del dkey
    dyn = set(
        zip(
            (d_src // num_rels).tolist(), (d_src % num_rels).tolist(),
            (d_dst // num_rels).tolist(), (d_dst % num_rels).tolist(),
        )
    )

    # edge target node ids: one binary search an edge, sharded (the
    # searches are independent and numpy releases the GIL for them)
    e_hi <<= 32
    e_hi |= edge_obj_v
    e_packed = e_hi
    del e_hi
    edge_node = np.empty(epad, np.int32)
    edge_node[n_edges:] = -1

    def _targets(lo, hi_):
        want = e_packed[lo:hi_]
        at = np.searchsorted(uniq_packed, want)
        np.minimum(at, max(n_nodes - 1, 0), out=at)
        hit = uniq_packed[at] == want if n_nodes else np.zeros(len(at), bool)
        at[~hit] = -1
        edge_node[lo:hi_] = at

    parallel.shard_apply(n_edges, _targets)
    del e_packed

    del uniq_packed

    mem_node = mem_node_v
    mem_subj = mem_subj_v
    # per-node membership CSR straight from the group boundaries: every
    # node owns >= 1 tuple, so the i-th True in newg IS the row offset of
    # node i (no bincount/cumsum pass over the 10M column)
    mem_row_ptr = np.empty(npad + 1, np.int32)
    mem_row_ptr[n_nodes:] = n_tuples
    if n_nodes:
        mem_row_ptr[:n_nodes] = np.flatnonzero(newg)
    del newg

    spad = _bucket(max(len(vocab.subjects), 1))
    sub_ns = np.full(spad, -1, np.int32)
    sub_obj = np.full(spad, -1, np.int32)
    sub_rel = np.full(spad, -1, np.int32)
    ss_subj = subj[ss_sorted]
    del ss_sorted, ss_rows
    sub_ns[ss_subj] = edge_ns_v
    sub_obj[ss_subj] = edge_obj_v
    sub_rel[ss_subj] = edge_rel_v
    del ss_subj
    t0 = _mark("csr_pack", t0)

    flat = compile_flat_tables(
        manager, vocab, strict=strict, num_ns=num_ns, num_rel=num_rels
    )
    taint, err_reach = _compute_taint(flat, op, dyn, num_ns, num_rels)
    t0 = _mark("optable", t0)

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
        version=version,
        node_tab=node_tab,
        mem_tab=mem_tab,
    )
    snap.dyn_pairs = dyn
    return snap


# -- delta overlay ------------------------------------------------------------


@dataclass
class OverlayState:
    """Accumulated not-yet-rebuilt changes relative to a base snapshot."""

    pair_net: Dict[Tuple[int, int, int], int] = field(default_factory=dict)
    # (hi, lo) of LHS nodes absent from the base node table -> virtual id
    new_nodes: Dict[Tuple[int, int], int] = field(default_factory=dict)
    dirty_nodes: Set[int] = field(default_factory=set)  # base ids + vids

    def size(self) -> Tuple[int, int]:
        return len(self.pair_net), len(self.dirty_nodes)


class OverlayRejected(Exception):
    """The overlay cannot represent this change; full rebuild required."""


def _base_node_id(snap: Snapshot, hi: int, lo: int) -> int:
    i = np.searchsorted(snap.node_hi[: snap.n_nodes], hi)
    while i < snap.n_nodes and snap.node_hi[i] == hi:
        if snap.node_lo[i] == lo:
            return int(i)
        i += 1
    return -1


def _base_pair_count(snap: Snapshot, node: int, subj: int) -> int:
    lo = np.searchsorted(snap.mem_node[: snap.n_tuples], node, side="left")
    hi_ = np.searchsorted(snap.mem_node[: snap.n_tuples], node, side="right")
    seg = snap.mem_subj[lo:hi_]
    return int(np.count_nonzero(seg == subj))


def apply_changes(
    state: OverlayState,
    snap: Snapshot,
    vocab: Vocab,
    changes,
) -> None:
    """Fold store changes into the overlay state; raises OverlayRejected
    when a change is unrepresentable against the base snapshot."""
    num_rels = snap.num_rels
    num_ns = snap.op.prog_root.shape[0]
    dyn_pairs = getattr(snap, "dyn_pairs", None)
    for op_, t in changes:
        # ids must fit the base table dims (vocab only grows)
        ns = vocab.namespaces.lookup(t.namespace)
        rel = vocab.relations.lookup(t.relation)
        if ns < 0 or rel < 0 or ns >= num_ns or rel >= num_rels:
            raise OverlayRejected(f"id overflow for {t.namespace}#{t.relation}")
        obj = vocab.objects.lookup(t.object)
        subj = vocab.subject_key(t.subject)
        if obj < 0 or subj < 0:
            raise OverlayRejected("unknown object/subject id")
        hi = ns * num_rels + rel
        node = _base_node_id(snap, hi, obj)
        if node < 0:
            key = (hi, obj)
            node = state.new_nodes.get(key, -1)
            if node < 0:
                node = snap.n_nodes + len(state.new_nodes)
                state.new_nodes[key] = node

        if isinstance(t.subject, SubjectSet):
            # edge-list change: the row must not be expanded against the
            # stale base CSR
            state.dirty_nodes.add(node)
            if dyn_pairs is not None and op_ > 0:
                sns = vocab.namespaces.lookup(t.subject.namespace)
                srel = vocab.relations.lookup(t.subject.relation)
                if (ns, rel, sns, srel) not in dyn_pairs:
                    # could extend the AND/NOT taint closure
                    raise OverlayRejected("new relation-level edge pair")

        pkey = (node, subj)
        state.pair_net[pkey] = state.pair_net.get(pkey, 0) + op_
        if state.pair_net[pkey] == 0:
            del state.pair_net[pkey]


# probe depth for overlay tables: built sparse enough that two gather
# rounds always suffice — the overlay rides the hottest probe paths
OVERLAY_PROBE = hashtab.PROBE_SHALLOW

# membership-delta payload codes (om_ table values)
OV_ADDED = 1
OV_DELETED = 2


def overlay_arrays(
    state: OverlayState,
    snap: Snapshot,
    *,
    pair_cap: int = 4096,
) -> Dict[str, np.ndarray]:
    """Project the overlay state into FIXED-SHAPE device arrays.

    Keys: ``om_`` merged membership-delta table ((node, subj) ->
    OV_ADDED | OV_DELETED), ``ovt_`` node table ((hi,lo) -> vid),
    ``ov_dirty`` bitset, ``ov_nbase`` scalar (base node count; nodes >= it
    have no base CSR row).

    Shapes are constant for a given base snapshot and ``pair_cap`` (the
    engine's overlay size threshold): an EMPTY state ships minimum content
    in the same arrays, so the jitted program's pytree structure and
    shapes never change as writes land — overlay activation or growth
    must not trigger a recompile (minutes for the fused wave), and each
    write re-ships only these small arrays.
    """
    # a 0 threshold (mesh engine: every write rebuilds) still needs a
    # well-formed empty table
    pair_cap = max(1, pair_cap)
    mem: List[Tuple[int, int, int]] = []
    for (node, subj), net in state.pair_net.items():
        base = _base_pair_count(snap, node, subj) if node < snap.n_nodes else 0
        now = base + net
        if base == 0 and now > 0:
            mem.append((node, subj, OV_ADDED))
        elif base > 0 and now <= 0:
            mem.append((node, subj, OV_DELETED))

    # fixed shapes: 4x buckets keeps the probe-4 bound satisfiable at any
    # fill <= pair_cap; a (rare) salt-schedule failure raises ValueError
    # and the engine falls back to a full rebuild
    shape = (4 * pair_cap, pair_cap)
    om = hashtab.build_table(
        np.asarray([m[0] for m in mem], np.int64),
        np.asarray([m[1] for m in mem], np.int64),
        np.asarray([m[2] for m in mem], np.int32),
        probe=OVERLAY_PROBE,
        fixed_shape=shape,
    )
    ovt = hashtab.build_table(
        np.asarray([k[0] for k in state.new_nodes], np.int64),
        np.asarray([k[1] for k in state.new_nodes], np.int64),
        np.asarray(list(state.new_nodes.values()), np.int32),
        probe=OVERLAY_PROBE,
        fixed_shape=shape,
    )

    # dirty covers base nodes + up to pair_cap virtual nodes: fixed size
    dpad = _bucket(snap.n_nodes + pair_cap + 1, 64)
    dirty = np.zeros(dpad, bool)
    for n in state.dirty_nodes:
        dirty[n] = True

    out = {
        "ov_dirty": dirty,
        "ov_nbase": np.int32(snap.n_nodes),
    }
    out.update({f"om_{k}": v for k, v in om.items()})
    out.update({f"ovt_{k}": v for k, v in ovt.items()})
    return out


# -- incremental CSR fold -----------------------------------------------------


FOLD_PHASES = ("fold_replay", "fold_merge", "fold_hashtab")


class FoldRejected(Exception):
    """The changelog slice cannot fold into the base snapshot; the caller
    must run a full build."""


def _edge_class_counts(snap: Snapshot) -> Dict[int, int]:
    """Per relation-level edge class (src_hi << 32 | dst_hi) edge counts,
    cached on the snapshot: the fold uses these to detect when a delete
    retires the last edge of a class (the taint closure would shrink —
    unfoldable without recompiling op tables)."""
    cached = getattr(snap, "_edge_class_counts", None)
    if cached is not None:
        return cached
    counts: Dict[int, int] = {}
    n_nodes, n_edges = snap.n_nodes, snap.n_edges
    if n_edges:
        per_node = np.diff(snap.row_ptr[: n_nodes + 1].astype(np.int64))
        src_hi = np.repeat(snap.node_hi.astype(np.int64), per_node)
        dst_hi = (
            snap.edge_ns[:n_edges].astype(np.int64) * snap.num_rels
            + snap.edge_rel[:n_edges]
        )
        u, c = np.unique((src_hi << 32) | dst_hi, return_counts=True)
        counts = dict(zip(u.tolist(), c.tolist()))
    snap._edge_class_counts = counts
    return counts


def fold_snapshot_cols(
    snap: Snapshot,
    vocab: Vocab,
    changes,
    *,
    version: int = -1,
    phases: Optional[Dict[str, float]] = None,
) -> Snapshot:
    """Fold a changelog slice into an existing snapshot.

    Instead of re-projecting all N tuples, merge the (sorted) delta into
    the membership and edge arrays, repair the row pointers from count
    cumsums, and splice the hash tables in place: O(delta log N) key work
    plus O(N) memcpy passes — no 10M-row sorts, no full hash builds on the
    common path.  Delete ordering matches the column cache's FIFO
    semantics (base occurrences are consumed before slice-local adds), so
    the folded snapshot is verdict-identical to a from-scratch
    ``build_snapshot_cols`` at the same cursor.

    All padded shapes are preserved (pow2-crossing growth is rejected), so
    a folded snapshot re-ships to the device without changing any jitted
    program's input shapes.

    Raises FoldRejected when the slice cannot fold: ids beyond the
    compiled op/flat table dims, subject-pad or padded-shape overflow, or
    a change to the relation-level edge-pair set in either direction (the
    taint closure would move).  The caller falls back to a full build.

    ``phases`` accumulates per-phase wall seconds under FOLD_PHASES keys.
    """
    import time

    ph = phases if phases is not None else {}

    def _mark(key, t0):
        t1 = time.perf_counter()
        ph[key] = ph.get(key, 0.0) + (t1 - t0)
        return t1

    t0 = time.perf_counter()
    num_rels = snap.num_rels
    num_ns = snap.op.prog_root.shape[0]
    spad = len(snap.sub_ns)
    if _bucket(max(len(vocab.subjects), 1)) != spad:
        raise FoldRejected("subject pad growth")
    dyn = getattr(snap, "dyn_pairs", None)
    if dyn is None:
        raise FoldRejected("base snapshot carries no dyn_pairs")

    n_nodes0 = snap.n_nodes
    n_edges0 = snap.n_edges
    n_tuples0 = snap.n_tuples
    mem_rp = snap.mem_row_ptr
    row_ptr0 = snap.row_ptr

    # -- replay the slice per tuple identity (FIFO delete parity) -----------
    # key = (hi, obj, subj) in id space; every base row is older than any
    # add in the slice, so deletes consume base occurrences first, then
    # slice-local adds oldest-first — exactly TupleColumns.apply's order.
    state: Dict[Tuple[int, int, int], list] = {}  # [base_left, rm, [seqs]]
    info: Dict[Tuple[int, int, int], Tuple[int, int, int, int]] = {}
    node_cache: Dict[Tuple[int, int], int] = {}
    seq = 0
    for op_, t in changes:
        seq += 1
        ns = vocab.namespaces.lookup(t.namespace)
        rel = vocab.relations.lookup(t.relation)
        obj = vocab.objects.lookup(t.object)
        subj = vocab.subject_key(t.subject)
        if op_ <= 0 and min(ns, rel, obj, subj) < 0:
            continue  # delete of a tuple the vocab never saw: no-op
        if ns < 0 or rel < 0 or ns >= num_ns or rel >= num_rels:
            raise FoldRejected("namespace/relation beyond compiled tables")
        if obj < 0 or subj < 0 or subj >= spad:
            raise FoldRejected("object/subject id overflow")
        hi = ns * num_rels + rel
        key = (hi, obj, subj)
        st = state.get(key)
        if st is None:
            nk = (hi, obj)
            node = node_cache.get(nk, -2)
            if node == -2:
                node = _base_node_id(snap, hi, obj)
                node_cache[nk] = node
            base = _base_pair_count(snap, node, subj) if node >= 0 else 0
            st = state[key] = [base, 0, []]
            if isinstance(t.subject, SubjectSet):
                sns = vocab.namespaces.lookup(t.subject.namespace)
                sobj = vocab.objects.lookup(t.subject.object)
                srel = vocab.relations.lookup(t.subject.relation)
                if min(sns, sobj, srel) < 0 or sns >= num_ns or srel >= num_rels:
                    raise FoldRejected("subject-set id overflow")
                info[key] = (1, sns, sobj, srel)
            else:
                info[key] = (0, -1, -1, -1)
        if op_ > 0:
            if info[key][0]:
                sns, srel = info[key][1], info[key][3]
                if (ns, rel, sns, srel) not in dyn:
                    raise FoldRejected("new relation-level edge pair (taint)")
            st[2].append(seq)
        else:
            if st[0] > 0:
                st[0] -= 1
                st[1] += 1
            elif st[2]:
                st[2].pop(0)

    # -- aggregate per node --------------------------------------------------
    mem_rm: Dict[int, list] = {}       # old node id -> [(subj, k)]
    edge_rm: Dict[int, list] = {}      # old node id -> [(sns, sobj, srel, k)]
    adds_by_node: Dict[Tuple[int, int], list] = {}
    class_delta: Dict[int, int] = {}
    final_delta: Dict[int, int] = {}   # old node id -> net membership delta
    new_node_rows: Dict[Tuple[int, int], int] = {}
    sub_scatter: Dict[int, Tuple[int, int, int]] = {}
    for key, (base_left, rm, seqs) in state.items():
        hi, obj, subj = key
        is_set, sns, sobj, srel = info[key]
        node = node_cache[(hi, obj)]
        if rm:
            mem_rm.setdefault(node, []).append((subj, rm))
            if is_set:
                edge_rm.setdefault(node, []).append((sns, sobj, srel, rm))
        if is_set:
            d = len(seqs) - rm
            if d:
                ck = (hi << 32) | (sns * num_rels + srel)
                class_delta[ck] = class_delta.get(ck, 0) + d
            if seqs:
                sub_scatter[subj] = (sns, sobj, srel)
        if seqs:
            adds_by_node.setdefault((hi, obj), []).extend(
                (s_, subj, is_set, sns, sobj, srel) for s_ in seqs
            )
        if node >= 0:
            net = len(seqs) - rm
            if net:
                final_delta[node] = final_delta.get(node, 0) + net
        elif seqs:
            new_node_rows[(hi, obj)] = (
                new_node_rows.get((hi, obj), 0) + len(seqs)
            )

    if class_delta:
        base_classes = _edge_class_counts(snap)
        for ck, d in class_delta.items():
            if base_classes.get(ck, 0) + d <= 0:
                raise FoldRejected("relation-level edge pair retired (taint)")

    # node set changes: removed = membership emptied; inserted = new keys
    removed_ids = sorted(
        n for n, d in final_delta.items()
        if d < 0 and int(mem_rp[n + 1]) - int(mem_rp[n]) + d == 0
    )
    ins_keys = np.array(
        sorted((hi << 32) | obj for (hi, obj) in new_node_rows), np.int64
    )
    n_nodes1 = n_nodes0 - len(removed_ids) + len(ins_keys)
    n_tuples1 = n_tuples0 + sum(len(v[2]) - v[1] for v in state.values())
    e_add_n = sum(1 for a in adds_by_node.values() for e in a if e[2])
    e_rm_n = sum(k for lst in edge_rm.values() for (_, _, _, k) in lst)
    n_edges1 = n_edges0 + e_add_n - e_rm_n
    if (
        _bucket(n_nodes1) != _bucket(n_nodes0)
        or _bucket(n_edges1) != _bucket(n_edges0)
        or _bucket(n_tuples1) != _bucket(n_tuples0)
    ):
        raise FoldRejected("padded shape crossing")
    npad = _bucket(n_nodes1)
    t0 = _mark("fold_replay", t0)

    # -- node renumbering ----------------------------------------------------
    keep_nodes = np.ones(n_nodes0, bool)
    keep_nodes[removed_ids] = False
    kept_old = np.flatnonzero(keep_nodes)
    old_packed = (snap.node_hi.astype(np.int64) << 32) | snap.node_lo.astype(
        np.int64
    )
    kept_keys = old_packed[kept_old]
    shift = np.searchsorted(ins_keys, kept_keys)
    remap = np.full(n_nodes0, -1, np.int32)
    remap[kept_old] = (np.arange(len(kept_old), dtype=np.int64) + shift).astype(
        np.int32
    )
    ins_pos_in_kept = np.searchsorted(kept_keys, ins_keys)
    new_id_of_ins = (
        ins_pos_in_kept + np.arange(len(ins_keys))
    ).astype(np.int32)
    node_keys1 = np.insert(kept_keys, ins_pos_in_kept, ins_keys)
    node_hi1 = (node_keys1 >> 32).astype(np.int32)
    node_lo1 = (node_keys1 & 0xFFFFFFFF).astype(np.int32)
    new_id_by_key = dict(
        zip((int(k) for k in ins_keys), (int(i) for i in new_id_of_ins))
    )
    renumbered = bool(len(ins_keys)) or bool(removed_ids)

    # -- membership merge ----------------------------------------------------
    mem_node0 = snap.mem_node
    mem_subj0 = snap.mem_subj
    ord0 = snap.mem_ord_subj
    keep_mem = np.ones(n_tuples0, bool)
    ord_del: list = []
    rm_per_old = np.zeros(n_nodes0, np.int64)
    for node, lst in mem_rm.items():
        lo = int(mem_rp[node])
        hi_ = int(mem_rp[node + 1])
        seg = mem_subj0[lo:hi_]
        oseg = ord0[lo:hi_]
        for subj, k in lst:
            p = lo + int(np.searchsorted(seg, subj))
            keep_mem[p : p + k] = False
            # the ord column deletes FIRST-k occurrences (FIFO)
            occ = np.flatnonzero(oseg == subj)[:k] + lo
            ord_del.extend(occ.tolist())
            rm_per_old[node] += k
    old_mcnt = np.diff(mem_rp[: n_nodes0 + 1].astype(np.int64))
    kept_mcnt_old = old_mcnt - rm_per_old
    kept_cnt1 = np.zeros(max(n_nodes1, 1), np.int64)
    kept_cnt1[remap[kept_old]] = kept_mcnt_old[kept_old]
    add_cnt1 = np.zeros(max(n_nodes1, 1), np.int64)

    add_mem: list = []   # (new_id, subj)
    add_ord: list = []   # (new_id, seq, subj)
    add_edges: list = []  # (new_id, seq, sns, sobj, srel)
    for (hi, obj), entries in adds_by_node.items():
        old = node_cache[(hi, obj)]
        nid = int(remap[old]) if old >= 0 else new_id_by_key[(hi << 32) | obj]
        for (s_, subj, is_set, sns, sobj, srel) in entries:
            add_mem.append((nid, subj))
            add_ord.append((nid, s_, subj))
            if is_set:
                add_edges.append((nid, s_, sns, sobj, srel))
        add_cnt1[nid] += len(entries)

    kept_node = mem_node0[keep_mem] if ord_del else mem_node0
    kept_subj = mem_subj0[keep_mem] if ord_del else mem_subj0
    new_mem_node = remap[kept_node]
    new_mem_subj = kept_subj
    if add_mem:
        add_mem.sort()
        am_node = np.array([a[0] for a in add_mem], np.int32)
        am_subj = np.array([a[1] for a in add_mem], np.int32)
        kept_key = (new_mem_node.astype(np.int64) << 32) | new_mem_subj.astype(
            np.int64
        )
        add_key = (am_node.astype(np.int64) << 32) | am_subj.astype(np.int64)
        pos = np.searchsorted(kept_key, add_key)
        mem_node1 = np.insert(new_mem_node, pos, am_node)
        mem_subj1 = np.insert(new_mem_subj, pos, am_subj)
    else:
        mem_node1 = new_mem_node
        mem_subj1 = (
            new_mem_subj if new_mem_subj is not mem_subj0 else mem_subj0.copy()
        )
    assert len(mem_node1) == n_tuples1
    cnt1 = kept_cnt1 + add_cnt1
    mem_row_ptr1 = np.empty(npad + 1, np.int32)
    mem_row_ptr1[0] = 0
    if n_nodes1:
        np.cumsum(cnt1[:n_nodes1], out=mem_row_ptr1[1 : n_nodes1 + 1])
    mem_row_ptr1[n_nodes1 + 1:] = n_tuples1

    # insertion-ordered member column: delete FIFO positions, append new
    # rows at each node's segment end (np.insert keeps value order at
    # duplicate positions)
    ord_body = ord0[:n_tuples0]
    if ord_del:
        ord_keep = np.ones(n_tuples0, bool)
        ord_keep[np.array(ord_del, np.int64)] = False
        ord_body = ord_body[ord_keep]
    kept_cum = np.zeros(max(n_nodes1, 1) + 1, np.int64)
    np.cumsum(kept_cnt1, out=kept_cum[1:])
    if add_ord:
        add_ord.sort()  # (node, seq): per-node append order
        ao_pos = kept_cum[np.array([a[0] for a in add_ord], np.int64) + 1]
        ao_val = np.array([a[2] for a in add_ord], np.int32)
        ord_body = np.insert(ord_body, ao_pos, ao_val)
    mpad = _bucket(n_tuples1)
    mem_ord1 = np.empty(mpad, np.int32)
    mem_ord1[:n_tuples1] = ord_body
    mem_ord1[n_tuples1:] = -1

    # -- edge merge ----------------------------------------------------------
    old_ecnt = np.diff(row_ptr0[: n_nodes0 + 1].astype(np.int64))
    e_keep = np.ones(n_edges0, bool)
    erm_per_old = np.zeros(n_nodes0, np.int64)
    for node, lst in edge_rm.items():
        lo = int(row_ptr0[node])
        hi_ = int(row_ptr0[node + 1])
        for sns, sobj, srel, k in lst:
            m = np.flatnonzero(
                (snap.edge_ns[lo:hi_] == sns)
                & (snap.edge_obj[lo:hi_] == sobj)
                & (snap.edge_rel[lo:hi_] == srel)
            )[:k] + lo
            if len(m) != k:  # every set tuple owns exactly one edge
                raise FoldRejected("edge bookkeeping mismatch")
            e_keep[m] = False
            erm_per_old[node] += k
    if e_rm_n:
        e_ns1 = snap.edge_ns[:n_edges0][e_keep]
        e_obj1 = snap.edge_obj[:n_edges0][e_keep]
        e_rel1 = snap.edge_rel[:n_edges0][e_keep]
        en0 = snap.edge_node[:n_edges0][e_keep]
    else:
        e_ns1 = snap.edge_ns[:n_edges0]
        e_obj1 = snap.edge_obj[:n_edges0]
        e_rel1 = snap.edge_rel[:n_edges0]
        en0 = snap.edge_node[:n_edges0]
    en1 = np.where(
        en0 >= 0, remap[np.clip(en0, 0, None)], np.int32(-1)
    ).astype(np.int32)
    if len(ins_keys):
        # dangling edges may now resolve against the inserted nodes
        dang = np.flatnonzero(en1 < 0)
        if len(dang):
            dk = (
                (e_ns1[dang].astype(np.int64) * num_rels + e_rel1[dang]) << 32
            ) | e_obj1[dang].astype(np.int64)
            di = np.searchsorted(ins_keys, dk)
            hit = (di < len(ins_keys)) & (
                ins_keys[np.minimum(di, len(ins_keys) - 1)] == dk
            )
            en1[dang[hit]] = new_id_of_ins[di[hit]]

    kept_ecnt1 = np.zeros(max(n_nodes1, 1), np.int64)
    kept_ecnt1[remap[kept_old]] = (old_ecnt - erm_per_old)[kept_old]
    e_cum = np.zeros(max(n_nodes1, 1) + 1, np.int64)
    np.cumsum(kept_ecnt1, out=e_cum[1:])
    add_ecnt1 = np.zeros(max(n_nodes1, 1), np.int64)
    if add_edges:
        add_edges.sort()  # (node, seq): per-node append order
        ae_nid = np.array([a[0] for a in add_edges], np.int64)
        ae_ns = np.array([a[2] for a in add_edges], np.int32)
        ae_obj = np.array([a[3] for a in add_edges], np.int32)
        ae_rel = np.array([a[4] for a in add_edges], np.int32)
        tk = (
            (ae_ns.astype(np.int64) * num_rels + ae_rel) << 32
        ) | ae_obj.astype(np.int64)
        ti = np.searchsorted(node_keys1, tk)
        thit = (ti < n_nodes1) & (
            node_keys1[np.minimum(ti, max(n_nodes1 - 1, 0))] == tk
        )
        ae_node = np.where(thit, ti, -1).astype(np.int32)
        ae_pos = e_cum[ae_nid + 1]
        e_ns1 = np.insert(e_ns1, ae_pos, ae_ns)
        e_obj1 = np.insert(e_obj1, ae_pos, ae_obj)
        e_rel1 = np.insert(e_rel1, ae_pos, ae_rel)
        en1 = np.insert(en1, ae_pos, ae_node)
        np.add.at(add_ecnt1, ae_nid, 1)
    assert len(e_ns1) == n_edges1
    ecnt1 = kept_ecnt1 + add_ecnt1
    row_ptr1 = np.empty(npad + 1, np.int32)
    row_ptr1[0] = 0
    if n_nodes1:
        np.cumsum(ecnt1[:n_nodes1], out=row_ptr1[1 : n_nodes1 + 1])
    row_ptr1[n_nodes1 + 1:] = n_edges1
    epad = _bucket(n_edges1)

    def pad_edges(v):
        out = np.empty(epad, np.int32)
        out[:n_edges1] = v
        out[n_edges1:] = -1
        return out

    # subject decode columns: scatter new set subjects; stale entries for
    # subjects with no surviving rows are harmless (unreachable through
    # membership) and keeping them preserves the expand path's behaviour
    if sub_scatter:
        sub_ns1 = snap.sub_ns.copy()
        sub_obj1 = snap.sub_obj.copy()
        sub_rel1 = snap.sub_rel.copy()
        for subj, (sns, sobj, srel) in sub_scatter.items():
            sub_ns1[subj] = sns
            sub_obj1[subj] = sobj
            sub_rel1[subj] = srel
    else:
        sub_ns1, sub_obj1, sub_rel1 = snap.sub_ns, snap.sub_obj, snap.sub_rel
    t0 = _mark("fold_merge", t0)

    # -- hash tables: splice in place, rebuild only on shape pressure --------
    rm_keys = old_packed[np.array(removed_ids, np.int64)]
    node_tab = hashtab.splice_table(
        snap.node_tab,
        (rm_keys >> 32).astype(np.int32),
        (rm_keys & 0xFFFFFFFF).astype(np.int32),
        (ins_keys >> 32).astype(np.int32),
        (ins_keys & 0xFFFFFFFF).astype(np.int32),
        new_id_of_ins,
        val_remap=remap,
    )
    if node_tab is None:
        node_tab = hashtab.build_table(
            node_hi1, node_lo1,
            np.arange(n_nodes1, dtype=np.int32),
            lean=True, probe=hashtab.SNAPSHOT_PROBE,
        )
    mem_tab = None
    if not renumbered:
        # (node, subj) keys are stable — splice the per-removal and
        # per-add entries (duplicates remove/insert distinct slots)
        r_node: list = []
        r_subj: list = []
        for node, lst in mem_rm.items():
            for subj, k in lst:
                r_node.extend([node] * k)
                r_subj.extend([subj] * k)
        mem_tab = hashtab.splice_table(
            snap.mem_tab,
            np.array(r_node, np.int32),
            np.array(r_subj, np.int32),
            np.array([a[0] for a in add_mem], np.int32),
            np.array([a[1] for a in add_mem], np.int32),
        )
    if mem_tab is None:
        mem_tab = hashtab.build_table(
            mem_node1, mem_subj1,
            lean=True, probe=hashtab.SNAPSHOT_PROBE,
        )
    t0 = _mark("fold_hashtab", t0)

    out = Snapshot(
        vocab=vocab,
        op=snap.op,
        flat=snap.flat,
        taint=snap.taint,
        err_reach=snap.err_reach,
        num_rels=num_rels,
        node_hi=node_hi1,
        node_lo=node_lo1,
        row_ptr=row_ptr1,
        edge_ns=pad_edges(e_ns1),
        edge_obj=pad_edges(e_obj1),
        edge_rel=pad_edges(e_rel1),
        edge_node=pad_edges(en1),
        mem_node=mem_node1,
        mem_subj=mem_subj1,
        mem_row_ptr=mem_row_ptr1,
        mem_ord_subj=mem_ord1,
        sub_ns=sub_ns1,
        sub_obj=sub_obj1,
        sub_rel=sub_rel1,
        n_nodes=n_nodes1,
        n_edges=n_edges1,
        n_tuples=n_tuples1,
        version=version,
        node_tab=node_tab,
        mem_tab=mem_tab,
    )
    out.dyn_pairs = dyn
    base_classes = getattr(snap, "_edge_class_counts", None)
    if base_classes is not None:
        nc = dict(base_classes)
        for ck, d in class_delta.items():
            nc[ck] = nc.get(ck, 0) + d
        out._edge_class_counts = nc
    return out
