"""Pure-OR BFS fast path: batched reachability checks with a monotone found-bit.

The checkgroup OR semantics of the reference collapse three-valued logic at
every level: the first IS_MEMBER child wins and UNKNOWN children are swallowed
into NOT_MEMBER (`checkgroup/concurrent_checkgroup.go:108-123`, oracle.py
`_group`).  Consequence: for any query whose reachable rewrite closure
contains no AND / NOT and no error-raising relation lookup, Check degenerates
to *depth-bounded multi-source reachability* — the verdict is IS iff some
membership probe fires within the depth budget, else NOT.  No task tree, no
parent pointers, no result propagation: just

* a frontier of ``(query, namespace, object, relation, depth, flags)``
  items (one array row each),
* a per-query monotone ``found`` bit fed by three probe families — direct
  membership (`engine.go:167-208`), the OR-of-computed-subject-sets shortcut
  (`rewrites.go:62-93` / `sql/traverser.go:123-191`), and the EXISTS bit on
  subject-set expansion edges (`engine.go:131-139` /
  `sql/traverser.go:53-121`),
* one level per device step, expanding subject-set CSR rows, flattened
  computed-subject-set entries, and tuple-to-userset rows
  (see `optable.FlatTables` for the flattening and per-edge depth math).

Every child's depth is at least one less than its parent's (expansion hops
decrement at `engine.go:242-245`, batched CSS children at `rewrites.go:86`,
TTU children at `rewrites.go:281`, nested ORs at `rewrites.go:118`), so a
batch completes in exactly ``max_depth`` steps — the host enqueues all steps
asynchronously with **zero** intermediate device syncs, the fix for the
round-1 engine's 64 blocking round-trips per batch.

Capacity semantics are monotone too: ``found`` can only gain queries, so an
arena/frontier overflow poisons only the *not-yet-found* queries of the
affected rows (``q_over``); a query answered IS stays IS.  Fallback work is
therefore ``over & ~found`` instead of round 1's all-or-nothing flag.

The step is split into two phases so the graph-sharded runner
(ketotpu/parallel) can route children between them with an all-to-all:

* ``expand_phase`` — probes + child construction into arena columns;
* ``pack_phase`` — per-(query, node) dedup/merge + compaction into the next
  frontier.

The expansion EXISTS bit is tested at the CHILD's level, not the parent's:
expansion children carry a ``force`` flag and their own self-membership
probe fires on arrival regardless of depth — including width-truncated
children, which ship as probe-only items (depth 0) so the pre-truncation
EXISTS semantics survive.  This replaces an arena-sized member probe at
the parent with a frontier-sized one a level later (cheaper), and it is
the only formulation that shards: the target row lives on the owner shard
of the child's object, so only the owner can probe it.

Kernel strategy (SURVEY §7 step 6, measured on a v5 lite chip): the
per-level cost is bounded by random 1-D gathers from HBM tables, and the
hash probes are most of them: `probe/node_table` and `probe/mem_table`
held 66 % of the 1024-row mixed wave's device time and 75 % of the
singles' before a lookup's rounds were bounded at four (PERF.md §5,
traces of PR 34 and PR 35).  A gather costs 12-13 ns
an element whatever it reads (micro-run, PR 34: 80 us at 6,144 slots, 18
us at 1,536), so a lookup's time is its gather count times its slots
(`hashtab.lookup_gathers`).  Pallas/Mosaic
alternatives were evaluated and rejected with measurements rather than
assumed: (a) one fused [A,16] row gather — 2.5x SLOWER than 16 separate
1-D gathers when benchmarked in isolation, while rewriting this module's
row gathers as flattened 1-D gathers changed end-to-end batch time by
0% (XLA already emits the efficient form in context); (b) a
VMEM-resident table with
`jnp.take` inside a Pallas kernel — Mosaic lowers only same-shape 2-D
`take_along_axis`, not 1-D/arbitrary gather; (c) a scalar `fori_loop`
gather kernel — Mosaic forbids scalar stores to VMEM; (d) one-hot matmul
gathers on the MXU — the on-the-fly one-hot compare costs A*N VPU ops,
which loses to the native gather for every table size in play.  XLA's
gather is the best available primitive for this access pattern on this
hardware, so the engine's wins come from doing *fewer and smaller*
gathers (lean per-level schedules, child-level EXISTS probes, linear
scatter dedup instead of sorts) and from eliminating host round-trips
(fused multi-level dispatch, packed query upload / verdict download).

Exploration order differs from the sequential oracle in one deliberate way:
instead of the oracle's per-expansion-subtree visited sets (DFS order,
`engine.go:119`, `x/graph/graph_utils.go:38-53`), each level merges duplicate
``(query, node)`` items keeping the maximum remaining depth and the most
permissive flags.  The explored set is a superset of the oracle's and a
subset of the visited-set-free depth-bounded closure, so IS verdicts can
exceed the oracle's only on graphs where the oracle's visited set suppresses
a higher-budget revisit — exactly the cases where the reference's
*concurrent* engine (shared visited set raced by goroutines,
`concurrent_checkgroup.go:66-138`) is itself schedule-dependent.  The
differential fuzzer arbitrates such divergences against a visited-free
oracle run (tests/test_fastpath.py).
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ketotpu import compilewatch, profiler
from ketotpu.engine import hashtab
from ketotpu.engine.delta import OV_ADDED, OV_DELETED
from ketotpu.engine.xutil import arena_assign

_I32MAX = jnp.iinfo(jnp.int32).max

ITEM_COLS = ("qid", "ns", "obj", "rel", "d", "skip", "force")


class FastResult(NamedTuple):
    found: jax.Array  # bool[Q]: membership established (monotone)
    over: jax.Array  # bool[Q]: capacity overflow touched this query
    # bool[Q]: exploration read a CSR row the delta overlay marked dirty —
    # the verdict must come from the host oracle (None without an overlay)
    dirty: Optional[jax.Array] = None


def _node_lookup(g: Dict[str, jax.Array], ns, obj, rel):
    """(ns, obj, rel) -> node id or -1.  Stride = padded relation count.
    With a delta overlay, nodes created since the base snapshot resolve to
    virtual ids (>= base node count) through the ``ovt_`` table."""
    num_rels = g["f_direct_ok"].shape[1]
    hi = ns * num_rels + rel
    ok = (ns >= 0) & (obj >= 0) & (rel >= 0)
    with jax.named_scope("probe/node_table"):
        idx, found = hashtab.lookup(hashtab.subtables(g, "nt_"), hi, obj)
        found = found & ok
        res = jnp.where(found, idx, -1)
        if "ovt_ptr" in g:
            vid, vfound = hashtab.lookup(hashtab.subtables(g, "ovt_"), hi, obj)
            res = jnp.where(ok & vfound & ~found, vid, res)
    return res.astype(jnp.int32)


def _member(g: Dict[str, jax.Array], node, subj):
    """Does tuple (node, subject) exist?  ExistsRelationTuples equivalent.
    Overlay-exact: base OR added-since-base AND NOT deleted-since-base, so
    probe verdicts always reflect the latest write."""
    with jax.named_scope("probe/mem_table"):
        _, found = hashtab.lookup(hashtab.subtables(g, "mt_"), node, subj)
        if "om_ptr" in g:
            v, vf = hashtab.lookup(hashtab.subtables(g, "om_"), node, subj)
            found = (
                (found | (vf & (v == OV_ADDED))) & ~(vf & (v == OV_DELETED))
            )
    return found


def _node_dirty(g: Dict[str, jax.Array], node):
    """Did this node's subject-set edge list change since the base?"""
    if "ov_dirty" not in g:
        return jnp.zeros(jnp.shape(node), bool)
    dsz = g["ov_dirty"].shape[0]
    return g["ov_dirty"][jnp.clip(node, 0, dsz - 1)] & (node >= 0)


def _row_deg(g, node):
    safe = jnp.clip(node, 0, g["row_ptr"].shape[0] - 2)
    deg = g["row_ptr"][safe + 1] - g["row_ptr"][safe]
    return jnp.where(node >= 0, deg, 0).astype(jnp.int32)


def init_state(
    q_ns, q_obj, q_rel, q_subj, q_depth, active=None, *, frontier: int
) -> Dict[str, jax.Array]:
    """Roots in slots 0..Q-1; ``active=False`` queries never enter the BFS."""
    Q = q_ns.shape[0]
    if Q > frontier:
        raise ValueError(f"batch {Q} exceeds frontier capacity {frontier}")
    act = np.ones((Q,), bool) if active is None else np.asarray(active, bool)
    return _init_state(q_ns, q_obj, q_rel, q_subj, q_depth, act, frontier=frontier)


@functools.partial(jax.jit, static_argnames=("frontier",))
def _init_state(
    q_ns, q_obj, q_rel, q_subj, q_depth, act, *, frontier: int
) -> Dict[str, jax.Array]:
    Q = q_ns.shape[0]
    iota = jnp.arange(frontier, dtype=jnp.int32)
    in_q = (iota < Q) & jnp.pad(jnp.asarray(act, bool), (0, frontier - Q))

    def pad(x, fill):
        return jnp.where(
            in_q,
            jnp.pad(jnp.asarray(x, jnp.int32), (0, frontier - Q), constant_values=fill),
            fill,
        )

    return dict(
        f_qid=jnp.where(in_q, iota, -1),
        f_ns=pad(q_ns, -1),
        f_obj=pad(q_obj, -1),
        f_rel=pad(q_rel, -1),
        f_depth=pad(q_depth, 0),
        f_skip=jnp.zeros((frontier,), bool),
        f_force=jnp.zeros((frontier,), bool),
        q_found=jnp.zeros((Q,), bool),
        q_over=jnp.zeros((Q,), bool),
        q_dirty=jnp.zeros((Q,), bool),
        q_subj=jnp.asarray(q_subj, jnp.int32),
    )


def expand_phase(
    g: Dict[str, jax.Array],
    s: Dict[str, jax.Array],
    *,
    arena: int,
    max_width: int,
    probe_only: bool = False,
    rewrites: bool = True,
) -> Tuple[Dict[str, jax.Array], jax.Array, jax.Array]:
    """Probes + child construction.  Returns (children[A] cols + alive, found, over).
    ``rewrites=False`` leaves out the computed-subject-set and
    tuple-to-userset columns: exact only where no item's (namespace,
    relation) has one (``has_rewrites``)."""
    A = arena
    F = s["f_qid"].shape[0]
    NS, R = g["f_direct_ok"].shape
    Kc = g["f_css_rel"].shape[2] if rewrites else 0
    Kt = g["f_ttu_via"].shape[2] if rewrites else 0
    Q = s["q_found"].shape[0]

    qid, ns, obj, rel = s["f_qid"], s["f_ns"], s["f_obj"], s["f_rel"]
    d, skip, force = s["f_depth"], s["f_skip"], s["f_force"]
    q_found, q_over, q_subj = s["q_found"], s["q_over"], s["q_subj"]
    q_dirty = s.get("q_dirty", jnp.zeros(q_found.shape, bool))

    qc = jnp.clip(qid, 0, Q - 1)
    live = (qid >= 0) & ~q_found[qc]  # short-circuit: found queries stop
    subj = q_subj[qc]
    nsc = jnp.clip(ns, 0, NS - 1)
    relc = jnp.clip(rel, 0, R - 1)
    cfg = (ns >= 0) & (ns < NS) & (rel >= 0) & (rel < R)
    node = _node_lookup(g, ns, obj, rel)

    dok = jnp.where(cfg, g["f_direct_ok"][nsc, relc], True) & ~skip
    eok = jnp.where(cfg, g["f_expand_ok"][nsc, relc], True)

    # -- probes -------------------------------------------------------------
    # direct: checked at depth-1 with its own <=0 guard (engine.go:242,
    # :167-208) => counts only when d >= 2.  A forced probe stands in for
    # the parent shard's expansion EXISTS bit and ignores depth.
    self_member = _member(g, node, subj)
    found = live & self_member & ((dok & (d >= 2)) | force)

    # batched computed-subject-set probes (rewrites.go:62-93); the rewrite
    # level guard is depth-dec >= 1 (rewrites.go:39)
    if Kc:
        css_rel = jnp.where(cfg[:, None], g["f_css_rel"][nsc, relc], -1)  # [F,Kc]
        css_dec = g["f_css_dec"][nsc, relc]
        css_probe = g["f_css_probe"][nsc, relc]
        css_ok = live[:, None] & (css_rel >= 0) & (d[:, None] - css_dec >= 1)
    for k in range(Kc):
        cnode = _node_lookup(g, ns, obj, css_rel[:, k])
        found = found | (css_ok[:, k] & css_probe[:, k] & _member(g, cnode, subj))

    q_found = q_found.at[qc].max(found)
    live2 = live & ~q_found[qc]

    if probe_only:
        # Probe-only level: the caller guarantees every item has d <= 1
        # (only _run_fused's final level qualifies — depth strictly
        # decreases per level and roots are clamped to the level count),
        # so no child segment can be non-empty — skip the whole arena
        # machinery and return an empty child set.  This must be an
        # explicit flag, NOT inferred from a small arena: a legitimately
        # tiny arena still needs the child path so capacity misses set
        # q_over instead of silently dropping children.
        empty = dict(
            qid=jnp.full((A,), -1, jnp.int32),
            ns=jnp.full((A,), -1, jnp.int32),
            obj=jnp.full((A,), -1, jnp.int32),
            rel=jnp.full((A,), -1, jnp.int32),
            d=jnp.zeros((A,), jnp.int32),
            skip=jnp.zeros((A,), bool),
            force=jnp.zeros((A,), bool),
        )
        return empty, q_found, q_over, q_dirty

    # -- per-item child segments: [expansion | css 0..Kc | ttu 0..Kt] -------
    # expansion runs at depth-1 with a <=0 guard (engine.go:245,:102-110);
    # the full row degree is gathered so found-bits cover pre-truncation
    # results (engine.go:131-139 checks found before the width cut)
    exp_read = live2 & eok & (d >= 2)
    exp_deg = jnp.where(exp_read, _row_deg(g, node), 0)
    if "ov_dirty" in g:
        # a dirty row's base edges are stale: don't expand them, flag the
        # query for the host oracle instead; virtual nodes (>= the base
        # node count) have no base CSR row at all
        nd = _node_dirty(g, node)
        q_dirty = q_dirty.at[qc].max(exp_read & nd)
        exp_deg = jnp.where(nd | (node >= g["ov_nbase"]), 0, exp_deg)
    if Kc:
        css_need = (
            css_ok & live2[:, None] & (d[:, None] - css_dec - 1 >= 1)
        ).astype(jnp.int32)
    if Kt:
        ttu_via = jnp.where(cfg[:, None], g["f_ttu_via"][nsc, relc], -1)  # [F,Kt]
        ttu_tgt = g["f_ttu_tgt"][nsc, relc]
        ttu_dec = g["f_ttu_dec"][nsc, relc]
        # TTU guard is depth < 0 (rewrites.go:247) but children recurse at
        # depth-dec-1 with the root <=0 guard, so rows only matter when
        # d - dec >= 2
        ttu_ok = live2[:, None] & (ttu_via >= 0) & (d[:, None] - ttu_dec >= 2)
    ttu_node_cols = []
    ttu_deg_cols = []
    for k in range(Kt):
        tn = _node_lookup(g, ns, obj, ttu_via[:, k])
        ttu_node_cols.append(tn)
        deg_k = jnp.where(ttu_ok[:, k], _row_deg(g, tn), 0)
        if "ov_dirty" in g:
            nd = _node_dirty(g, tn)
            q_dirty = q_dirty.at[qc].max(ttu_ok[:, k] & nd)
            deg_k = jnp.where(nd | (tn >= g["ov_nbase"]), 0, deg_k)
        ttu_deg_cols.append(deg_k)
    if Kt:
        ttu_nodes = jnp.stack(ttu_node_cols, axis=1)  # [F,Kt]

    seg_len = jnp.stack(
        [exp_deg] + [css_need[:, k] for k in range(Kc)] + ttu_deg_cols, axis=1
    )  # [F, 1+Kc+Kt]
    seg_cum = jnp.cumsum(seg_len, axis=1)
    counts = seg_cum[:, -1]

    # -- arena allocation ---------------------------------------------------
    offsets, _total, ap, ao = arena_assign(counts, A)
    fits = offsets + counts <= A
    q_over = q_over.at[qc].max(live2 & (counts > 0) & ~fits)

    aps = jnp.clip(ap, 0, F - 1)
    src_ok = (ap >= 0) & fits[aps]

    if Kc or Kt:
        # -- segment decomposition per arena slot ---------------------------
        cum_p = seg_cum[aps]  # [A, S]
        S = 1 + Kc + Kt
        seg_idx = jnp.clip(
            jnp.sum((ao[:, None] >= cum_p).astype(jnp.int32), axis=1), 0, S - 1
        )
        prev_cum = jnp.where(
            seg_idx > 0,
            jnp.take_along_axis(cum_p, jnp.clip(seg_idx - 1, 0, S - 1)[:, None], 1)[:, 0],
            0,
        )
        off = ao - prev_cum

        p_ns, p_obj, p_d = ns[aps], obj[aps], d[aps]
        p_qid = qid[aps]

        is_exp = src_ok & (seg_idx == 0)
        is_css = src_ok & (seg_idx >= 1) & (seg_idx <= Kc)
        css_k = jnp.clip(seg_idx - 1, 0, Kc - 1)
        is_ttu = src_ok & (seg_idx > Kc)
        ttu_k = jnp.clip(seg_idx - 1 - Kc, 0, Kt - 1)

        # edge gathers for expansion / ttu rows
        rp = g["row_ptr"]
        base_exp = rp[jnp.clip(node[aps], 0, rp.shape[0] - 2)]
        ttu_node_p = jnp.take_along_axis(ttu_nodes[aps], ttu_k[:, None], 1)[:, 0]
        base_ttu = rp[jnp.clip(ttu_node_p, 0, rp.shape[0] - 2)]
        eidx = jnp.clip(
            jnp.where(is_ttu, base_ttu, base_exp) + off, 0, g["edge_hi"].shape[0] - 1
        )
        e_ns, e_obj, e_rel = _edges(g, eidx)

        css_rel_p = jnp.take_along_axis(css_rel[aps], css_k[:, None], 1)[:, 0]
        css_dec_p = jnp.take_along_axis(css_dec[aps], css_k[:, None], 1)[:, 0]
        ttu_tgt_p = jnp.take_along_axis(ttu_tgt[aps], ttu_k[:, None], 1)[:, 0]
        ttu_dec_p = jnp.take_along_axis(ttu_dec[aps], ttu_k[:, None], 1)[:, 0]

        ch_ns = jnp.where(is_css, p_ns, e_ns)
        ch_obj = jnp.where(is_css, p_obj, e_obj)
        ch_rel = jnp.select([is_css, is_ttu], [css_rel_p, ttu_tgt_p], e_rel)
        ch_d = jnp.select(
            [is_css, is_ttu],
            [p_d - css_dec_p - 1, p_d - ttu_dec_p - 1],
            p_d - 1,
        )
        # expansion children skip the direct re-check — the EXISTS bit just
        # tested it (engine.go:161); batched CSS children likewise
        # (rewrites.go:86); TTU children do not (rewrites.go:281-286)
        ch_skip = is_exp | is_css
    else:  # no rewrite segments: every child is an expansion edge
        p_d, p_qid = d[aps], qid[aps]
        off = ao
        is_exp = src_ok
        rp = g["row_ptr"]
        base_exp = rp[jnp.clip(node[aps], 0, rp.shape[0] - 2)]
        ch_ns, ch_obj, ch_rel = _edges(
            g, jnp.clip(base_exp + off, 0, g["edge_hi"].shape[0] - 1)
        )
        ch_d = p_d - 1
        ch_skip = is_exp
    ch_qid = jnp.where(src_ok, p_qid, -1)

    # width truncation applies to recursion only (engine.go:141-150)
    p_exp_deg = exp_deg[aps]
    trunc = is_exp & (p_exp_deg > max_width) & (off >= max_width - 1)

    # The expansion EXISTS bit (engine.go:131-139) is tested at the CHILD's
    # level via the force flag, not with an arena-sized member probe at the
    # parent: the child's own self_member probe fires regardless of depth
    # when forced, and width-truncated children ship probe-only (d=0) so
    # the pre-truncation EXISTS semantics survive.  One frontier-sized
    # probe next level replaces the largest gather site of the whole step,
    # and single-shard and sharded execution share one child construction
    # (the owner shard does the probe in the sharded runner).
    ch_force = is_exp
    ch_d = jnp.where(trunc, 0, ch_d)
    alive = src_ok & (is_exp | (ch_d >= 1))
    alive = alive & ~q_found[jnp.clip(ch_qid, 0, Q - 1)]

    children = dict(
        qid=jnp.where(alive, ch_qid, -1),
        ns=ch_ns,
        obj=ch_obj,
        rel=ch_rel,
        d=jnp.maximum(ch_d, 0),
        skip=ch_skip,
        force=ch_force,
    )
    return children, q_found, q_over, q_dirty


def _edges(g, eidx):
    """(ns, obj, rel) of the CSR edges at ``eidx``: one packed gather for
    (ns, rel) + one for obj; the div/mod decode is VPU arithmetic, each
    avoided gather is an arena-sized HBM read."""
    R = g["f_direct_ok"].shape[1]
    e_hi, e_obj = g["edge_hi"][eidx], g["edge_obj"][eidx]
    e_ns = jnp.where(e_hi >= 0, e_hi // R, -1)
    e_rel = jnp.where(e_hi >= 0, e_hi % R, -1)
    return e_ns, e_obj, e_rel


def _pack_bits(n: int) -> int:
    return max(int(n - 1).bit_length(), 1)


def pack_phase(
    children: Dict[str, jax.Array],
    q_found: jax.Array,
    q_over: jax.Array,
    *,
    frontier: int,
    ns_dim: int = 0,
    rel_dim: int = 0,
    merge_arena: int = 0,
) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Dedup by (query, node) — max depth, min skip, max force — and compact
    the survivors into the next frontier.  Returns (frontier cols, q_over).
    ``merge_arena`` sizes the hash merge for that many children instead of
    ``children``'s own length: a narrow rung of a folded level passes its
    level's full arena, so its children merge as the full level's would.

    When (qid, ns, rel) fit one int32 (pass ``ns_dim``/``rel_dim``, the
    padded table dims), dedup runs as **linear hash-scatter merge** instead
    of a sort: every alive child scatters into a 2A-slot hash table; the
    max-index child per slot becomes the slot *owner*, all children whose
    key equals the owner's key merge elementwise into the owner
    (max depth / min skip / max force — the merged item's exploration
    supersets every contributor's), and hash-colliding children of *other*
    keys simply pass through unmerged (capacity waste, never a drop).
    Compaction is a prefix-sum scatter.  This replaces the arena-sized
    multi-operand sort that dominated per-level device time; the sort path
    remains as the fallback when the key does not pack into an int32.
    """
    qb = _pack_bits(q_found.shape[0])
    nsb = _pack_bits(ns_dim) if ns_dim else 31
    relb = _pack_bits(rel_dim) if rel_dim else 31
    if qb + nsb + relb <= 31:
        return _pack_scatter(
            children, q_found, q_over, frontier=frontier, nsb=nsb, relb=relb,
            merge_arena=merge_arena,
        )
    return _pack_sort(children, q_found, q_over, frontier=frontier)


def _pack_scatter(
    children: Dict[str, jax.Array],
    q_found: jax.Array,
    q_over: jax.Array,
    *,
    frontier: int,
    nsb: int,
    relb: int,
    merge_arena: int = 0,
) -> Tuple[Dict[str, jax.Array], jax.Array]:
    F = frontier
    Q = q_found.shape[0]
    A = children["qid"].shape[0]
    H = 1 << max((2 * max(A, merge_arena) - 1).bit_length(), 4)
    alive = (children["qid"] >= 0) & ~q_found[jnp.clip(children["qid"], 0, Q - 1)]
    k1 = (
        (children["qid"] << (nsb + relb)) | (children["ns"] << relb) | children["rel"]
    )
    k2 = children["obj"]
    idx = jnp.arange(A, dtype=jnp.int32)
    h = (
        hashtab.mix_device(k1, k2, jnp.uint32(0x9E3779B9)) & jnp.uint32(H - 1)
    ).astype(jnp.int32)
    hs = jnp.where(alive, h, H)  # dead children scatter out of bounds
    own = jnp.full((H,), -1, jnp.int32).at[hs].max(idx, mode="drop")
    owner = own[jnp.clip(h, 0, H - 1)]
    oc = jnp.clip(owner, 0, A - 1)
    same = alive & (k1[oc] == k1) & (k2[oc] == k2)
    ms = jnp.where(same, h, H)  # merge scatters: same-key group only
    d_tab = jnp.full((H,), -1, jnp.int32).at[ms].max(children["d"], mode="drop")
    skip_tab = (
        jnp.ones((H,), jnp.int32)
        .at[ms]
        .min(children["skip"].astype(jnp.int32), mode="drop")
    )
    force_tab = (
        jnp.zeros((H,), jnp.int32)
        .at[ms]
        .max(children["force"].astype(jnp.int32), mode="drop")
    )
    is_owner = alive & (owner == idx)
    survivor = is_owner | (alive & ~same)
    hc = jnp.clip(h, 0, H - 1)
    d_out = jnp.where(is_owner, d_tab[hc], children["d"])
    skip_out = jnp.where(is_owner, skip_tab[hc].astype(bool), children["skip"])
    force_out = jnp.where(is_owner, force_tab[hc].astype(bool), children["force"])

    pos = jnp.cumsum(survivor.astype(jnp.int32)) - 1
    drop = survivor & (pos >= F)
    oq = jnp.where(drop, children["qid"], Q)
    q_over = q_over.at[jnp.clip(oq, 0, Q - 1)].max(drop & (oq < Q))
    spos = jnp.where(survivor & (pos < F), pos, F)

    def scat(fill, val):
        return jnp.full((F,), fill, val.dtype).at[spos].set(val, mode="drop")

    out = dict(
        f_qid=scat(-1, jnp.where(survivor, children["qid"], -1)),
        f_ns=scat(-1, children["ns"]),
        f_obj=scat(-1, children["obj"]),
        f_rel=scat(-1, children["rel"]),
        f_depth=scat(0, d_out),
        f_skip=scat(False, skip_out),
        f_force=scat(False, force_out),
    )
    return out, q_over


def _pack_sort(
    children: Dict[str, jax.Array],
    q_found: jax.Array,
    q_over: jax.Array,
    *,
    frontier: int,
) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Sort-based dedup/compaction (exact group merge, any key width)."""
    F = frontier
    Q = q_found.shape[0]
    A = children["qid"].shape[0]
    alive = (children["qid"] >= 0) & ~q_found[jnp.clip(children["qid"], 0, Q - 1)]

    payload = (
        (children["d"] << 2)
        | (children["skip"].astype(jnp.int32) << 1)
        | children["force"].astype(jnp.int32)
    )
    k3 = jnp.where(alive, children["ns"], _I32MAX)
    k4 = jnp.where(alive, children["rel"], _I32MAX)
    k1 = jnp.where(alive, children["qid"], _I32MAX)
    k2 = jnp.where(alive, children["obj"], _I32MAX)
    sk1, k3s, k4s, sk2, s_pay = jax.lax.sort((k1, k3, k4, k2, payload), num_keys=4)
    valid = sk1 != _I32MAX
    same_prev = (
        (sk1 == jnp.roll(sk1, 1))
        & (k3s == jnp.roll(k3s, 1))
        & (k4s == jnp.roll(k4s, 1))
        & (sk2 == jnp.roll(sk2, 1))
    )
    o_qid, o_ns, o_rel, o_obj = sk1, k3s, k4s, sk2

    s_d = s_pay >> 2
    s_skip = (s_pay >> 1) & 1
    s_force = s_pay & 1
    same_prev = same_prev.at[0].set(False)
    first = valid & ~same_prev
    seg_id = jnp.cumsum(first.astype(jnp.int32)) - 1
    seg_safe = jnp.clip(seg_id, 0, A - 1)
    d_max = jax.ops.segment_max(jnp.where(valid, s_d, -1), seg_safe, num_segments=A)
    skip_min = jax.ops.segment_min(
        jnp.where(valid, s_skip, 1), seg_safe, num_segments=A
    )
    force_max = jax.ops.segment_max(
        jnp.where(valid, s_force, 0), seg_safe, num_segments=A
    )

    pos = jnp.where(first, jnp.cumsum(first.astype(jnp.int32)) - 1, F)
    drop_f = first & (pos >= F)
    oq = jnp.where(valid, o_qid, Q)
    q_over = q_over.at[jnp.clip(oq, 0, Q - 1)].max(drop_f & (oq < Q))
    pos = jnp.where(pos < F, pos, F)

    def scat(fill, val):
        return jnp.full((F,), fill, val.dtype).at[pos].set(val, mode="drop")

    out = dict(
        f_qid=scat(-1, jnp.where(first, o_qid, -1).astype(jnp.int32)),
        f_ns=scat(-1, o_ns.astype(jnp.int32)),
        f_obj=scat(-1, o_obj.astype(jnp.int32)),
        f_rel=scat(-1, o_rel.astype(jnp.int32)),
        f_depth=scat(0, d_max[seg_safe]),
        f_skip=scat(False, skip_min[seg_safe].astype(bool)),
        f_force=scat(False, force_max[seg_safe].astype(bool)),
    )
    return out, q_over


def step_impl(
    g: Dict[str, jax.Array],
    s: Dict[str, jax.Array],
    *,
    frontier: int,
    arena: int,
    max_width: int = 100,
) -> Dict[str, jax.Array]:
    """One whole level: expand + pack (single-shard path)."""
    return _level(g, s, arena=arena, nxt_f=frontier, max_width=max_width)


def _level(g, s, *, arena, nxt_f, max_width, probe_only=False,
           merge_arena=0, rewrites=True):
    """Expand ``s`` into ``arena`` children and pack them into a frontier
    of ``nxt_f`` slots: the next level's state."""
    NS, R = g["f_direct_ok"].shape
    children, q_found, q_over, q_dirty = expand_phase(
        g, s, arena=arena, max_width=max_width, probe_only=probe_only,
        rewrites=rewrites,
    )
    nxt, q_over = pack_phase(
        children, q_found, q_over, frontier=nxt_f, ns_dim=NS, rel_dim=R,
        merge_arena=merge_arena,
    )
    return dict(
        nxt, q_found=q_found, q_over=q_over, q_dirty=q_dirty,
        q_subj=s["q_subj"],
    )


fast_step = functools.partial(
    jax.jit, static_argnames=("frontier", "arena", "max_width"), donate_argnums=(1,)
)(step_impl)


PROBE_ONLY_ARENA = 8  # arena <= this: level runs probes only, no children


#: worst-case per-level frontier multipliers (units of q); also the ceiling
#: the demand-adaptive schedule may never exceed
F_MULT = (1, 4, 5, 6, 6)


def level_schedule(
    q: int, frontier: int, arena: int, max_depth: int, boost: int = 1,
    mults: Optional[Tuple[int, ...]] = None,
) -> Tuple[Tuple[int, int], ...]:
    """Per-level (frontier, arena) sizes: level 0 holds exactly the roots,
    later levels grow geometrically up to the configured caps.  Early levels
    are the common case (short-circuit kills most queries fast), so sizing
    them to the work instead of the worst case is most of the win.

    Default growth is tuned to measured frontier shapes (chains with a
    mid-walk bulge dominate, not explosions: a deny-verdict query walks
    ~1-2 children per item per level until its closure is exhausted);
    ``mults`` overrides it with *measured* per-level multipliers — the
    engine feeds back the fused program's per-level occupancy counts, so
    steady-state batches size every buffer to the workload's actual
    frontier shape instead of the worst case (the per-level cost is
    dominated by array-sized device work, so smaller buffers are a direct
    win).  Capacity misses surface as per-query ``over`` bits and the
    engine retries just those queries at wider caps (tpu.py) — far cheaper
    than sizing every batch for the worst case.  The final level cannot
    produce live children (depth strictly decreases and a child needs
    d >= 1), so it runs probe-only with a token arena.

    ``boost`` scales the demand-driven per-query term (m*q), not just the
    caps: a retry tier must grow the capacity a query's own fan-out gets,
    and when levels are q-bound rather than cap-bound, scaling only the
    caps would change nothing.
    """
    f_mult = F_MULT if mults is None else mults
    out = []
    for lvl in range(max_depth):
        last = lvl == max_depth - 1
        m = f_mult[min(lvl, len(f_mult) - 1)]
        fl = min(boost * m * q, frontier)
        a = 4 * fl if lvl == 0 else 2 * fl  # root fan-out exceeds chain growth
        out.append((fl, PROBE_ONLY_ARENA if last else min(a, arena)))
    return tuple(out)


def _fused_body(
    g: Dict[str, jax.Array],
    q_ns, q_obj, q_rel, q_subj, q_depth, act,
    *,
    schedule: Tuple[Tuple[int, int], ...],
    max_width: int,
) -> "FastResult":
    """All BFS levels in ONE device program: one dispatch per batch instead
    of one per level (each dispatch costs real host-link latency), with the
    per-level buffer sizes of ``schedule``.

    A run of equal levels (``folded_runs``) is one loop whose body runs
    each level at the smallest rung that holds its live items
    (``_rung_level``), and the probe-only level after such a run probes
    the same way.  Returns the verdicts and int32[len(schedule) +
    folded_levels(schedule)]: the live items entering each level, then
    the rung (``RUNGS`` index) each folded level ran at."""
    s = _init_state(
        q_ns, q_obj, q_rel, q_subj, q_depth, act, frontier=schedule[0][0]
    )
    # The final level is probe-only, which is sound only if its items have
    # d <= 1; root depth <= #levels guarantees that (depth strictly
    # decreases per level).  Callers pass rest_depth <= max_depth anyway
    # (engine.go:82-84 global-cap precedence); clamp defensively.
    s["f_depth"] = jnp.minimum(s["f_depth"], len(schedule))
    q = q_ns.shape[0]
    last = len(schedule) - 1
    runs = dict(folded_runs(schedule))
    occ = []  # live items ENTERING each level (occ[0] = roots)
    rungs = []
    i = 0
    while i <= last:
        f, a = schedule[i]
        if i in runs:
            hi = runs[i]
            with jax.named_scope(f"level{i}-{hi - 1}"):
                s, run_occ, run_rungs = _folded_run(
                    g, s, levels=hi - i, f=f, a=a, q=q, max_width=max_width
                )
            occ.extend(run_occ[j] for j in range(hi - i))
            rungs.append(run_rungs)
            i = hi
            continue
        with jax.named_scope(f"level{i}"):
            occ.append(jnp.sum((s["f_qid"] >= 0).astype(jnp.int32)))
            if i == last and last in runs.values():
                s = dict(s, q_found=_probe_rungs(
                    g, s, n=occ[-1], q=q, f=f, max_width=max_width
                ))
            else:
                level = functools.partial(
                    _level, g, arena=a,
                    nxt_f=schedule[i + 1][0] if i < last else 1,
                    max_width=max_width, probe_only=(i == last),
                )
                # where the schedule folds (a deep walk), the levels
                # outside the loop drop the rewrite columns too where no
                # item has one; a schedule that folds nothing keeps its
                # program
                s = _by_rewrites(g, s, level) if runs else level(s)
        i += 1
    res = FastResult(found=s["q_found"], over=s["q_over"], dirty=s["q_dirty"])
    if rungs:
        return res, jnp.concatenate([jnp.stack(occ), *rungs])
    return res, jnp.stack(occ)


#: what a folded level ran at: a frontier of a quarter of the wave's rows,
#: of its rows, or the level's own (keto_fused_fast_rung_levels_total{rung})
RUNGS = ("quarter", "roots", "full")
_FULL = RUNGS.index("full")
_FRONTIER_COLS = ("f_qid", "f_ns", "f_obj", "f_rel", "f_depth", "f_skip",
                  "f_force")


#: the fewest equal levels that run as a loop: its body holds five
#: variants of the level (two narrow rungs, each with and without the
#: rewrite columns, and the full size), so a shorter run would compile
#: larger looped than unrolled; every schedule of depth 7 or less stays
#: unrolled, as it was
FOLD_MIN = 6


def folded_runs(schedule) -> Tuple[Tuple[int, int], ...]:
    """``(first, end)`` of each run of ``FOLD_MIN`` or more consecutive
    non-final levels with one (frontier, arena) that ``_fused_body`` runs
    as one loop.  A run starts at level 1 or later (level 0 holds the
    roots in their rows; a later level's items are packed into the
    frontier's prefix, which the rungs rely on) and its last level packs
    into a frontier of its own size."""
    out = []
    last = len(schedule) - 1
    i = 1
    while i < last:
        end = i + 1
        while end < last and schedule[end] == schedule[i]:
            end += 1
        first = i
        i = end
        if schedule[end][0] != schedule[first][0]:
            end -= 1  # its successor's frontier differs: unrolled
        if end - first >= FOLD_MIN:
            out.append((first, end))
    return tuple(out)


def folded_levels(schedule) -> int:
    """How many levels of ``schedule`` run inside a loop: the rung codes
    after the occupancy counts of ``_fused_body``'s second result."""
    return sum(end - first for first, end in folded_runs(schedule))


def _narrow_rungs(q: int, f: int, a: int):
    """``(code, frontier, arena)`` of the rungs narrower than a level of
    ``f`` / ``a``: a quarter of the wave's ``q`` rows and all of them, each
    with twice its frontier of arena (the schedule's ratio after level 0)."""
    out = []
    for code, r in ((RUNGS.index("quarter"), q // 4), (RUNGS.index("roots"), q)):
        if 1 <= r < f and (not out or r > out[-1][1]):
            out.append((code, r, min(2 * r, a)))
    return tuple(out)


def _smallest_holding(n, sizes):
    """Index of the first of the ascending ``sizes`` that is >= ``n``
    (``len(sizes)`` when none is)."""
    k = jnp.int32(0)
    for r in sizes:
        k = k + (n > r).astype(jnp.int32)
    return k


def _folded_run(g, s, *, levels, f, a, q, max_width):
    """``levels`` equal levels of frontier ``f`` and arena ``a`` as one
    loop over ``_rung_level``; returns the state after them, and for each
    level the live items entering it and the rung it ran at."""

    def body(j, carry):
        st, occ, rung = carry
        n = jnp.sum((st["f_qid"] >= 0).astype(jnp.int32))
        st, code = _rung_level(g, st, n=n, f=f, a=a, q=q, max_width=max_width)
        return st, occ.at[j].set(n), rung.at[j].set(code)

    zeros = jnp.zeros((levels,), jnp.int32)
    return jax.lax.fori_loop(0, levels, body, (s, zeros, zeros))


def _rung_level(g, s, *, n, f, a, q, max_width):
    """One folded level on a state whose ``n`` live items fill the prefix of
    its ``f`` slots, at the smallest rung that holds them.  A narrow rung
    expands the prefix alone into its own arena and packs into all ``f``
    slots, merging as the full level does, so its next state is the full
    level's.  Where its arena or the frontier cannot hold every child (an
    over bit the attempt would set), the level runs again at full size
    from the same state and nothing of the attempt is kept.  Returns the
    next state and the rung's ``RUNGS`` index."""

    def full(st):
        with jax.named_scope("rung/full"):
            return _level(g, st, arena=a, nxt_f=f, max_width=max_width)

    narrow = _narrow_rungs(q, f, a)
    if not narrow:
        return full(s), jnp.int32(_FULL)

    def attempt(code, r, ra, rewrites):
        def run(st):
            with jax.named_scope(f"rung/{RUNGS[code]}"):
                sub = {c: st[c][:r] for c in _FRONTIER_COLS}
                out = _level(
                    g, dict(st, **sub, q_over=jnp.zeros_like(st["q_over"])),
                    arena=ra, nxt_f=f, max_width=max_width, merge_arena=a,
                    rewrites=rewrites,
                )
            return dict(out, q_over=st["q_over"]), ~jnp.any(out["q_over"])
        return run

    def skip(st):
        return st, jnp.zeros((), bool)

    # each narrow rung twice: without the rewrite columns where no item
    # has one (a chain of subject sets), then with them
    k = _smallest_holding(n, [r for _, r, _ in narrow])
    pick = jnp.where(
        k < len(narrow), k + len(narrow) * has_rewrites(g, s), 2 * len(narrow)
    )
    tried, ok = jax.lax.switch(
        pick,
        [attempt(*x, rewrites=False) for x in narrow]
        + [attempt(*x, rewrites=True) for x in narrow] + [skip],
        s,
    )
    out = jax.lax.cond(ok, lambda st: tried, full, s)
    codes = jnp.array([c for c, _, _ in narrow] + [_FULL], jnp.int32)
    return out, jnp.where(ok, codes[k], _FULL)


def _by_rewrites(g, s, level):
    """``level(s, rewrites=...)`` without the rewrite columns where no item
    of ``s`` has one."""
    return jax.lax.cond(
        has_rewrites(g, s),
        functools.partial(level, rewrites=True),
        functools.partial(level, rewrites=False),
        s,
    )


def has_rewrites(g, s):
    """Has any item of ``s`` a computed-subject-set or tuple-to-userset
    column?  Where none has, ``expand_phase(rewrites=False)`` gives the
    same children and bits with the item's own node and membership
    lookups alone."""
    NS, R = g["f_direct_ok"].shape
    ns, rel = s["f_ns"], s["f_rel"]
    cfg = (s["f_qid"] >= 0) & (ns >= 0) & (ns < NS) & (rel >= 0) & (rel < R)
    nsc, relc = jnp.clip(ns, 0, NS - 1), jnp.clip(rel, 0, R - 1)
    rw = jnp.any(g["f_css_rel"][nsc, relc] >= 0, axis=1) | jnp.any(
        g["f_ttu_via"][nsc, relc] >= 0, axis=1
    )
    return jnp.any(cfg & rw)


def _probe_rungs(g, s, *, n, q, f, max_width):
    """The probe-only level after a folded run: its probes on the prefix of
    the smallest rung that holds the ``n`` live items, without the rewrite
    columns where no item has one.  Returns q_found."""

    def probe(r, rewrites):
        def run(st):
            sub = {c: st[c][:r] for c in _FRONTIER_COLS}
            return expand_phase(
                g, dict(st, **sub), arena=PROBE_ONLY_ARENA,
                max_width=max_width, probe_only=True, rewrites=rewrites,
            )[1]
        return run

    sizes = [r for _, r, _ in _narrow_rungs(q, f, PROBE_ONLY_ARENA)] + [f]
    k = _smallest_holding(n, sizes[:-1])
    return jax.lax.switch(
        jnp.where(has_rewrites(g, s), k + len(sizes), k),
        [probe(r, False) for r in sizes] + [probe(r, True) for r in sizes],
        s,
    )


_run_fused = functools.partial(
    jax.jit, static_argnames=("schedule", "max_width")
)(_fused_body)


@functools.partial(jax.jit, static_argnames=("schedule", "max_width"))
def _run_fused_packed(
    g: Dict[str, jax.Array],
    qpack,
    *,
    schedule: Tuple[Tuple[int, int], ...],
    max_width: int,
):
    """Packed-I/O variant: queries arrive as ONE int32[6, Q] array
    (ns, obj, rel, subj, depth, active) and verdicts leave as ONE uint8[Q]
    (bit0 found, bit1 over, bit2 dirty), plus the int32[levels] per-level
    occupancy counts the engine's adaptive scheduler feeds on.  Every
    separate host<->device array transfer is its own PCIe transaction and
    host sync; packing turns 6 uploads + 3 downloads per batch into
    1 + 2 (the occupancy vector is a handful of bytes)."""
    r, occ = _fused_body(
        g, qpack[0], qpack[1], qpack[2], qpack[3], qpack[4],
        qpack[5].astype(bool),
        schedule=schedule, max_width=max_width,
    )
    return (
        r.found.astype(jnp.uint8)
        | (r.over.astype(jnp.uint8) << 1)
        | (r.dirty.astype(jnp.uint8) << 2)
    ), occ


def run_fast_packed(
    g: Dict[str, jax.Array],
    qpack: np.ndarray,
    *,
    frontier: int = 8192,
    arena: int = 32768,
    max_depth: int = 5,
    max_width: int = 100,
    boost: int = 1,
    mults: Optional[Tuple[int, ...]] = None,
    span=profiler.null_span,
):
    """run_fast over a pre-packed int32[6, Q] query block; returns the
    (device) uint8 verdict array and the int32[levels] occupancy vector —
    the caller fetches them with np.asarray when it syncs.  The dispatch's
    host wall time — trace/compile on a fresh shape, async enqueue after —
    is the engine span ``check_fast_dispatch`` (``span``: the engine's
    ``_span``).

    Row 5 of ``qpack`` is the active mask, and callers may clear bits for
    queries answered before dispatch — the engine's Leopard closure index
    (ketotpu/leopard/) intercepts deep-nesting checks this way, so a
    depth-12 membership chain costs one sorted-pair binary search instead
    of twelve BFS levels here.  An inactive query never enters the
    frontier: its verdict byte and over/dirty bits come back zero, which
    the collector relies on (a closure-answered query must not be claimed
    by the overflow-retry or oracle-fallback paths)."""
    Q = qpack.shape[1]
    if Q > frontier:
        raise ValueError(f"batch {Q} exceeds frontier capacity {frontier}")
    sched = level_schedule(Q, frontier, arena, max_depth, boost, mults)
    with span("check_fast_dispatch", rows=Q), compilewatch.scope(
        "fast_packed", lambda: f"Q={Q} sched={sched} width={max_width}"
    ):
        out = _run_fused_packed(g, qpack, schedule=sched, max_width=max_width)
    return out


def run_fast(
    g: Dict[str, jax.Array],
    q_ns,
    q_obj,
    q_rel,
    q_subj,
    q_depth,
    active=None,
    *,
    frontier: int = 8192,
    arena: int = 32768,
    max_depth: int = 5,
    max_width: int = 100,
    boost: int = 1,
) -> FastResult:
    """Run a batch to completion in a single fused device dispatch.

    Exactly ``max_depth`` levels — depth strictly decreases per level, so
    the frontier is provably empty afterwards; no early-exit sync needed.
    ``boost`` widens the per-query capacity schedule (retry tiers).
    """
    Q = q_ns.shape[0]
    if Q > frontier:
        raise ValueError(f"batch {Q} exceeds frontier capacity {frontier}")
    act = np.ones((Q,), bool) if active is None else np.asarray(active, bool)
    sched = level_schedule(Q, frontier, arena, max_depth, boost)
    with compilewatch.scope(
        "fast", lambda: f"Q={Q} sched={sched} width={max_width}"
    ):
        res, _occ = _run_fused(
            g, q_ns, q_obj, q_rel, q_subj, q_depth, act,
            schedule=sched, max_width=max_width,
        )
    return res
