"""Batched device Expand: frontier traversal on TPU, exact DFS replay on host.

The reference's Expand (`internal/expand/engine.go:43-124`) walks one
subject set's membership recursively, with a *global* visited set shared
across the whole tree (first DFS occurrence of a subject expands, later
occurrences render as leaves) and depth truncation.  The shape of the
output tree therefore depends on DFS order — which a data-parallel BFS
cannot reproduce directly.

Split the work instead:

* **device** (`run_expand`) — all roots in one fused dispatch: per level,
  every live item's full member list (the membership CSR built at snapshot
  time — leaf subjects included, unlike the subject-set-only check CSR) is
  gathered into arena slots with per-item parent pointers.  Expansion is
  bounded only by *ancestor* cycles (a per-item ancestor column stack,
  depth <= max_depth, so the check is a handful of compares) and by depth;
  no global visited set.  The result is a superset forest: every DFS-
  reachable subtree is present.
* **host** (`assemble`) — replays the reference's exact recursion over the
  device records: global visited set in DFS order, `None`-pruning of empty
  rows, depth-1 leaf truncation (engine.go:102-106), children in row
  (insertion/pagination) order.  Ancestor-cycle items the device did not
  expand are exactly the items the DFS replay prunes via its visited set
  before looking at their children, so the superset is always sufficient.

Level capacities come in two rungs.  The first (``rung="first"``) clamps
the geometric schedule at ``FIRST_RUNG`` slots a padded root: served trees
are widest at level 1 and hold a few dozen items a level, so the deep
levels are sized for the tree and not for ``cap``.  Per-root arena
overflow surfaces as an ``over`` bit; the engine runs those roots again
on the second rung (``rung="full"``, the schedule clamped at ``cap``
alone), and answers a root that overflows that too with the sequential
oracle.  Trees produced here are bit-identical to
`oracle.ExpandEngine.build_tree` (tests/test_expand_device.py).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ketotpu import compilewatch, profiler
from ketotpu.api.types import (
    RelationTuple,
    Subject,
    SubjectID,
    SubjectSet,
    Tree,
    TreeNodeType,
)
from ketotpu.engine import fastpath as fp
from ketotpu.engine.vocab import Vocab
from ketotpu.engine.xutil import arena_assign


def _mem_deg(g, node):
    ptr = g["mem_row_ptr"]
    safe = jnp.clip(node, 0, ptr.shape[0] - 2)
    deg = ptr[safe + 1] - ptr[safe]
    # overlay-created virtual nodes (>= ov_nbase) have no base member row;
    # their members come entirely from the host-side overlay merge
    ok = node >= 0
    if "ov_nbase" in g:
        ok = ok & (node < g["ov_nbase"])
    return jnp.where(ok, deg, 0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("schedule",))
def _run_expand(
    g: Dict[str, jax.Array],
    r_ns, r_obj, r_rel, r_subj, r_depth,
    *,
    schedule: Tuple[int, ...],
):
    """One fused dispatch for all levels.  ``schedule[l]`` is the item
    capacity of level l (level 0 must hold all roots).  Returns one int32
    buffer, so the host fetches it at once: each level's item records
    (``RECORD`` rows of its capacity, ``unpack``), then the per-root
    overflow flags."""
    R = r_ns.shape[0]
    C0 = schedule[0]

    def pad_to(x, n, fill):
        return jnp.pad(jnp.asarray(x, jnp.int32), (0, n - x.shape[0]),
                       constant_values=fill)

    node = pad_to(fp._node_lookup(g, r_ns, r_obj, r_rel), C0, -1)
    d = pad_to(r_depth, C0, 0)
    subj = pad_to(r_subj, C0, -1)
    root = pad_to(jnp.arange(R, dtype=jnp.int32), C0, -1)
    parent = jnp.full((C0,), -1, jnp.int32)
    live = jnp.arange(C0) < R
    anc: List[jax.Array] = [jnp.where(live, subj, -2)]  # -2: never matches

    over = jnp.zeros((R,), bool)
    levels = []
    for l, cap in enumerate(schedule):
        with jax.named_scope(f"expand/level{l}"):
            deg = jnp.where(live, _mem_deg(g, node), 0)
            levels.append(jnp.stack([parent, subj, d, deg]))
            if l == len(schedule) - 1:
                break
            A = schedule[l + 1]
            counts = jnp.where(live & (d >= 2), deg, 0)
            offsets, _total, ap, ao = arena_assign(counts, A)
            fits = offsets + counts <= A
            rc = jnp.clip(root, 0, R - 1)
            over = over.at[rc].max(live & (counts > 0) & ~fits)

            C = counts.shape[0]
            aps = jnp.clip(ap, 0, C - 1)
            src_ok = (ap >= 0) & fits[aps]
            mbase = g["mem_row_ptr"][jnp.clip(node[aps], 0,
                                              g["mem_row_ptr"].shape[0] - 2)]
            midx = jnp.clip(mbase + ao, 0, g["mem_ord_subj"].shape[0] - 1)
            c_subj = jnp.where(src_ok, g["mem_ord_subj"][midx], -1)
            sc = jnp.clip(c_subj, 0, g["sub_ns"].shape[0] - 1)
            s_ns = jnp.where(c_subj >= 0, g["sub_ns"][sc], -1)
            c_is_set = s_ns >= 0
            c_node = fp._node_lookup(
                g, s_ns, g["sub_obj"][sc], g["sub_rel"][sc]
            )
            c_d = jnp.maximum(d[aps] - 1, 0)
            cyc = jnp.zeros((A,), bool)
            for a in anc:
                cyc = cyc | (a[aps] == c_subj)
            cyc = cyc & c_is_set
            expandable = src_ok & c_is_set & ~cyc

            parent = jnp.where(src_ok, ap, -1)
            subj = c_subj
            node = jnp.where(expandable, c_node, -1)
            d = c_d
            root = jnp.where(src_ok, root[aps], -1)
            live = expandable
            anc = [jnp.where(src_ok, a[aps], -2) for a in anc]
            anc.append(jnp.where(src_ok & c_is_set, c_subj, -2))
    return jnp.concatenate(
        [lv.astype(jnp.int32).ravel() for lv in levels]
        + [over.astype(jnp.int32)]
    )


#: the rows of a level's item records in ``_run_expand``'s buffer
RECORD = ("parent", "subj", "d", "deg")


def unpack(buf: np.ndarray, schedule: Tuple[int, ...]):
    """``_run_expand``'s fetched buffer -> (per-level records, each a dict
    of ``RECORD`` columns, and the per-root overflow flags)."""
    levels, at = [], 0
    for cap in schedule:
        rec = buf[at:at + len(RECORD) * cap].reshape(len(RECORD), cap)
        levels.append(dict(zip(RECORD, rec)))
        at += len(RECORD) * cap
    return levels, buf[at:] > 0


def expand_schedule(n_roots: int, fanout: int, max_depth: int,
                    cap: int) -> Tuple[int, ...]:
    """Item capacities per level: geometric in the expected fan-out,
    clamped to ``cap``; misses surface as per-root overflow bits."""
    out = [n_roots]
    for _ in range(max_depth - 1):
        out.append(min(out[-1] * fanout, cap))
    return tuple(out)


#: the first rung's level capacity, in slots a padded root
FIRST_RUNG = 256


def rung_cap(rung: str, n_padded: int, cap: int) -> int:
    """The level clamp of a rung for ``n_padded`` roots: the first rung
    holds ``FIRST_RUNG`` slots a root, the full rung ``cap``."""
    return min(cap, FIRST_RUNG * n_padded) if rung == "first" else cap


class _Decoder:
    """Reverse vocab: dense ids back to API strings/subjects.

    The uid-decode convention ("id:"/"set:" prefixes from
    ``Subject.unique_id``) is shared with the Leopard listing path —
    ``leopard.hostlist.subject_from_uid`` decodes the same strings when
    ``ListSubjects`` enumerates a closure node's element set, so a subject
    round-trips identically whether it surfaces through an expand tree or
    a listing page."""

    def __init__(self, vocab: Vocab):
        # one id at a time (``Interner.string``): a decoder is made for
        # every tree, and ``strings()`` would list the whole vocabulary
        # for it (a second a million names once they are packed)
        self.ns = vocab.namespaces.string
        self.obj = vocab.objects.string
        self.rel = vocab.relations.string
        self.sub = vocab.subjects.string

    def subject(self, subj_id: int, s_ns: int, s_obj: int, s_rel: int) -> Subject:
        if s_ns >= 0:
            return SubjectSet(self.ns(s_ns), self.obj(s_obj), self.rel(s_rel))
        uid = self.sub(subj_id)
        # unique_id format "id:<subject id>" (api/types.py)
        return SubjectID(uid[3:] if uid.startswith("id:") else uid)

    def subject_from_uid(self, subj_id: int) -> Subject:
        """Decode via the unique-id string alone — works for subjects
        interned AFTER the snapshot (overlay writes), which the snapshot's
        sub_ns/sub_obj/sub_rel arrays do not cover."""
        uid = self.sub(subj_id)
        if uid.startswith("set:"):
            return SubjectSet.from_string(uid[4:])
        return SubjectID(uid[3:] if uid.startswith("id:") else uid)


# public alias: the leopard/ listing surfaces and tests reuse the reverse
# vocab decoder without reaching for a private name
Decoder = _Decoder


class OverlayMembers:
    """Host-side view of the write overlay for Expand: per-node membership
    deltas vs the base snapshot, plus (hi, obj) -> virtual-node resolution.

    Built under the engine's sync lock (a point-in-time copy — the live
    OverlayState keeps mutating as writes land).  Expand is the one read
    path that needs *every* member of a row, so the overlay-exact story is
    host-side: the device enumerates base rows, and `assemble` drops
    deleted members, appends added ones (in write order — matching the
    reference's insertion-ordered pagination, relationtuples.go:216-219),
    and recurses into added subject-sets via the sequential engine.  One
    known divergence: a member deleted and re-added since the snapshot
    keeps its original row position here, while live-store pagination
    would move it to the end."""

    def __init__(self, overlay, snap, vocab: Vocab):
        from ketotpu.engine import delta as dl

        self.added: Dict[int, List[int]] = {}
        self.deleted: Dict[int, set] = {}
        for (node, subj), net in overlay.pair_net.items():
            # classify against the BASE pair count, exactly like
            # overlay_arrays (delta.py): the sign of net alone diverges
            # from live-store membership under duplicate-tuple
            # multiplicity (the in-memory store permits exact duplicate
            # rows), e.g. delete-one-of-two must not drop the member
            base = (
                dl._base_pair_count(snap, node, subj)
                if node < snap.n_nodes
                else 0
            )
            now = base + net
            if now <= 0:
                if base > 0:
                    self.deleted.setdefault(node, set()).add(subj)
            elif now > base:
                # one entry per extra copy: duplicate inserts appear as
                # duplicate rows in live-store pagination
                self.added.setdefault(node, []).extend([subj] * (now - base))
            elif now < base:
                # delete-all-then-reinsert-fewer: drop the base copies and
                # append the surviving count (live pagination also moves
                # the re-inserted copies to the end)
                self.deleted.setdefault(node, set()).add(subj)
                self.added.setdefault(node, []).extend([subj] * now)
        self.new_nodes = dict(overlay.new_nodes)
        self._snap = snap
        self._vocab = vocab

    def resolve(self, s: SubjectSet) -> int:
        """Node id (base or virtual) for a subject set, -1 if unknown."""
        from ketotpu.engine import delta as dl

        v = self._vocab
        ns = v.namespaces.lookup(s.namespace)
        rel = v.relations.lookup(s.relation)
        obj = v.objects.lookup(s.object)
        if ns < 0 or rel < 0 or obj < 0:
            return -1
        hi = ns * self._snap.num_rels + rel
        node = dl._base_node_id(self._snap, hi, obj)
        if node < 0:
            node = self.new_nodes.get((hi, obj), -1)
        return node


def _leaf(subject: Subject) -> Tree:
    return Tree(type=TreeNodeType.LEAF,
                tuple=RelationTuple("", "", "", subject))


def assemble(
    levels: List[Dict[str, np.ndarray]],
    sub_dec: Tuple[np.ndarray, np.ndarray, np.ndarray],
    vocab: Vocab,
    roots: List[SubjectSet],
    ov: Optional[OverlayMembers] = None,
    sub_expand=None,
    skip: Optional[np.ndarray] = None,
) -> List[Optional[Tree]]:
    """Exact DFS replay of expand/engine.go:54-124 over the device records.

    With ``ov`` set, each union node's member list is the base row minus
    deleted pairs plus added pairs; added subject-set members (which the
    device never expanded) recurse through ``sub_expand(subject, depth,
    visited)`` — the sequential engine sharing THIS tree's visited set, so
    the reference's global-DFS-visited semantics hold across the merge.
    Roots flagged in ``skip`` (overflowed: their records are partial) get
    ``None`` without a replay."""
    dec = _Decoder(vocab)
    sub_ns, sub_obj, sub_rel = sub_dec
    n_snap_subj = len(sub_ns)
    # children of item i at level l: slots of level l+1 with parent == i,
    # in slot (row insertion) order
    kids: List[Dict[int, List[int]]] = []
    for nxt in levels[1:]:
        by_parent: Dict[int, List[int]] = {}
        for slot in np.flatnonzero(nxt["parent"] >= 0):
            by_parent.setdefault(int(nxt["parent"][slot]), []).append(int(slot))
        kids.append(by_parent)

    def decode(sid: int) -> Subject:
        if sid < n_snap_subj:
            return dec.subject(
                sid, int(sub_ns[sid]), int(sub_obj[sid]), int(sub_rel[sid])
            )
        return dec.subject_from_uid(sid)

    out: List[Optional[Tree]] = []
    for r, root_subject in enumerate(roots):
        if skip is not None and skip[r]:
            out.append(None)
            continue
        visited = set()

        def build(level: int, slot: int, subject: Subject, depth: int):
            if isinstance(subject, SubjectID):
                return _leaf(subject)
            if subject.unique_id() in visited:
                return None
            visited.add(subject.unique_id())
            base_deg = int(levels[level]["deg"][slot])
            added: List[int] = []
            deleted: set = set()
            if ov is not None:
                node = ov.resolve(subject)
                if node >= 0:
                    added = ov.added.get(node, [])
                    deleted = ov.deleted.get(node, set())
            if base_deg - len(deleted) + len(added) <= 0:
                return None
            tree = Tree(type=TreeNodeType.UNION,
                        tuple=RelationTuple("", "", "", subject))
            if depth <= 1:
                tree.type = TreeNodeType.LEAF
                return tree
            for cslot in kids[level].get(slot, ()):  # row order
                rec = levels[level + 1]
                sid = int(rec["subj"][cslot])
                if sid in deleted:
                    continue
                child_subject = decode(sid)
                child = build(level + 1, cslot, child_subject,
                              int(rec["d"][cslot]))
                if child is None:
                    child = _leaf(child_subject)
                tree.children.append(child)
            for sid in added:  # write order = end of the live row
                child_subject = decode(sid)
                if isinstance(child_subject, SubjectID):
                    tree.children.append(_leaf(child_subject))
                    continue
                child = sub_expand(child_subject, depth - 1, visited)
                if child is None:
                    child = _leaf(child_subject)
                tree.children.append(child)
            return tree

        out.append(build(0, r, root_subject, int(levels[0]["d"][r])))
    return out


def run_expand(
    g: Dict[str, jax.Array],
    snap,
    roots: List[SubjectSet],
    rest_depth: int,
    *,
    max_depth: int = 5,
    fanout: int = 16,
    cap: int = 65536,
    rung: str = "first",
    pad_to: int = 0,
    ov: Optional[OverlayMembers] = None,
    sub_expand=None,
    span=profiler.null_span,
):
    """Device traversal + host assembly for a batch of subject-set roots,
    on one rung of level capacities (``rung_cap``); the roots pad to the
    power of two that holds ``max(len(roots), pad_to)``.

    Returns ``(trees, over)``: per-root Optional[Tree] (None = prune/404)
    and per-root overflow flags (True = the rung could not hold the tree;
    its entry in ``trees`` is None).  ``span`` (the engine's ``_span``)
    times the phases, each with ``rung=``: ``expand_device`` (encode +
    jitted traversal dispatch), ``expand_sync`` (the one D2H fetch of the
    level records), ``expand_assemble`` (host DFS reassembly + tree
    construction).
    """
    vocab = snap.vocab
    if rest_depth <= 0 or max_depth < rest_depth:
        rest_depth = max_depth
    R = len(roots)
    with span("expand_device", roots=R, rung=rung):
        buf, sched = _dispatch_roots(
            g, vocab, roots, rest_depth, fanout, cap, rung, pad_to
        )
    with span("expand_sync", roots=R, rung=rung):
        levels, over = unpack(np.asarray(buf), sched)
        over = over[:R]
    with span("expand_assemble", roots=R, rung=rung):
        trees = assemble(
            levels, (snap.sub_ns, snap.sub_obj, snap.sub_rel), vocab,
            roots, ov=ov, sub_expand=sub_expand, skip=over,
        )
    return trees, over


#: (full-rung schedule, the graph arrays' shapes) already dispatched once
_FULL_RUNG_WARM: set = set()


def _dispatch_roots(g, vocab, roots, rest_depth: int, fanout: int, cap: int,
                    rung: str = "first", pad_to: int = 0):
    """Encode the roots and enqueue the traversal; returns the uncollected
    device buffer and the schedule it runs at.  The first dispatch of a
    first rung also dispatches its full rung once, on padding roots
    alone: a root that overflows later finds that program compiled."""
    R = len(roots)
    # JIT-audit finding: the raw root count used to feed both the input
    # array shapes and schedule[0], so EVERY distinct batch size compiled
    # a fresh expand program.  Pad the encoded roots to a power-of-two
    # bucket instead — padding rows carry node/subject -1 and the kernel
    # already treats missing nodes as degree-0, so they are dead weight
    # the walk never expands and `assemble` never visits (it enumerates
    # only the first len(roots) level-0 slots).
    Rp = 8
    while Rp < max(R, pad_to):
        Rp <<= 1
    r_ns = np.full(Rp, -1, np.int32)
    r_obj = np.full(Rp, -1, np.int32)
    r_rel = np.full(Rp, -1, np.int32)
    r_subj = np.full(Rp, -1, np.int32)
    r_depth = np.zeros(Rp, np.int32)
    if rung == "first":
        # a graph of other shapes is another program; the root arrays
        # hold padding alone until they are filled below
        full = expand_schedule(Rp, fanout, rest_depth, cap)
        key = (full, tuple((k, v.shape) for k, v in sorted(g.items())))
        if key not in _FULL_RUNG_WARM:
            with compilewatch.scope("expand", lambda: f"R={Rp} sched={full}"):
                _run_expand(g, r_ns, r_obj, r_rel, r_subj, r_depth,
                            schedule=full)
            _FULL_RUNG_WARM.add(key)
    r_ns[:R] = np.fromiter(
        (vocab.namespaces.lookup(s.namespace) for s in roots), np.int32, R)
    r_obj[:R] = np.fromiter(
        (vocab.objects.lookup(s.object) for s in roots), np.int32, R)
    r_rel[:R] = np.fromiter(
        (vocab.relations.lookup(s.relation) for s in roots), np.int32, R)
    r_subj[:R] = np.fromiter(
        (vocab.subject_key(s) for s in roots), np.int32, R)
    r_depth[:R] = rest_depth
    sched = expand_schedule(Rp, fanout, rest_depth, rung_cap(rung, Rp, cap))
    with compilewatch.scope("expand", lambda: f"R={Rp} sched={sched}"):
        return _run_expand(
            g, r_ns, r_obj, r_rel, r_subj, r_depth, schedule=sched
        ), sched
