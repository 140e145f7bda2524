"""Fused leveled algebra path: batched AND/NOT checks as ONE device program.

The round-3 general path (a host-stepped task-tree interpreter, retired
in round 5) interpreted the check algebra
over ONE bump-allocated task buffer:
every step re-scanned all `cap` slots, ran multiple result-propagation
passes, and the host synced a flags word per 6-level window to decide
whether to keep stepping.  Measured cost: ~134 checks/s — two orders of
magnitude under the pure-OR fast path — dominated by (a) cap-sized work
per step regardless of live tasks, (b) blocking flag syncs on a
high-latency link, and (c) 128-task-slots-per-root sub-batching.

This module re-derives the general path from the fast path's design rules
(`engine/fastpath.py`): static per-level buffers sized to demand, zero
host round-trips, monotone overflow bits, and — the new structural idea —
**pure-OR subtree delegation**:

* The check algebra (`internal/check/rewrites.go:33-200`, `binop.go:18-73`)
  is an OR/AND/NOT expression DAG whose leaves are graph-reachability
  subproblems.  AND/NOT can only appear in namespace-config rewrite
  programs, so the static taint table (snapshot.py `_compute_taint`)
  tells, per (namespace, relation), whether a subcheck can ever reach an
  AND/NOT or client-error lookup.
* The **down pass** builds the algebra skeleton level by level: each task
  either resolves in place (guards, client errors, direct/forced
  membership probes), or allocates its children into the next level's
  arena with `arena_assign` — no state machine, no cancellation, no pack
  (levels are dense by construction).  A child subcheck whose (ns, rel)
  is NOT tainted becomes a **fast leaf** instead of a subtree: the
  reference semantics collapse every pure-OR check with depth >= 1 to
  IS/NOT reachability (OR swallows UNKNOWN at every level,
  concurrent_checkgroup.go:108-123), which is exactly the fast path's
  contract.
* All fast leaves from all levels are compacted into one sub-batch and
  run through the same fused BFS the fast path uses (`fp.expand_phase` /
  `fp.pack_phase`), with per-leaf skip/force flags preserving the
  expansion EXISTS-bit and batched-CSS probe semantics.
* The **up pass** then resolves combiners bottom-up in D exact
  scatter-add rounds: any-child-ERR first (conservative: ERR routes the
  query to the host oracle, which owns typed-error raising and its
  first-IS-wins evaluation order), then OR / AND / NOT / PASS over
  three-valued child counts (binop.go:18-73, rewrites.go:186-195).

Semantics notes (differential-tested against `engine/oracle.py`):

* Expansion EXISTS bits fire at the CHILD level via a `force` flag
  (engine.go:131-139) — including width-truncated children (probe-only,
  depth 0, engine.go:141-150) and visited-set duplicates: the reference
  tests the EXISTS bit during row iteration BEFORE the visited check
  skips recursion, so duplicates still probe, they just do not expand.
* The visited set (engine.go:119,157-162) covers expansion children
  only, keyed by (scope, ns, obj, rel) in the same open-addressed hash
  set the round-3 interpreter introduced; scopes open at the first expanding
  ancestor and are globally unique via static level bases.
* A direct/forced membership hit short-circuits its whole subtree ONLY
  when the relation's closure cannot raise a client error (`err_reach`
  table): the oracle evaluates [rewrite, direct, expand] in order and
  raises lazily, so a device IS must never hide a reachable raise.
* UNKNOWN needs no overflow bit of its own: a root that exhausts the
  static level budget resolves UNKNOWN and flags `over`, falling back to
  the oracle — exact or fallback, never a wrong verdict.

Capacity semantics are monotone like the fast path: every shortfall
(arena, fast-leaf buffer, visited probe window, level budget) sets the
query's `over` bit; the engine retries at boosted sizes and only then
falls back to the sequential oracle.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ketotpu import compilewatch, profiler
from ketotpu.engine import fastpath as fp
from ketotpu.engine import hashtab
from ketotpu.engine.optable import (
    OP_AND,
    OP_NOT,
    OP_OR,
    OP_PASS,
    P_AND,
    P_BATCHCSS,
    P_CSS,
    P_NOT,
    P_OR,
    P_TTU,
    R_ERR,
    R_IS,
    R_NOT,
    R_UNKNOWN,
)

# the fast path's probe helpers are the OVERLAY-AWARE ones: membership
# consults the om_ delta tables (base OR added AND NOT deleted), node
# lookup resolves overlay-created virtual ids through ovt_ — so the
# algebra program serves exact verdicts against pending writes instead
# of draining every AND/NOT query to the host oracle (VERDICT r4 #4)
from ketotpu.engine.fastpath import (
    _node_dirty,
    _node_lookup,
    _row_deg,
)
from ketotpu.engine.fastpath import _member as _member_raw
from ketotpu.engine.xutil import arena_assign


def _member(g, node, subj):
    return _member_raw(g, node, subj) & (node >= 0) & (subj >= 0)


def _shard_owner(ns, obj, n: int):
    """Owner shard of (namespace, object): the sharded general tier must
    activate each task on the shard that holds its rows, so this is
    graphshard's own partitioning function (a diverged copy would
    classify every task against a slice that does not contain it —
    silent all-deny).  Lazy import: engine->parallel is upside-down
    layering for a module import, and only the shard branch needs it."""
    from ketotpu.parallel.graphshard import shard_of_device

    return shard_of_device(ns, obj, n)


def _deg_guarded(g, node):
    """Edge-row degree with overlay semantics: a dirty row's base edges
    are stale and an overlay-created virtual node (>= ov_nbase) has no
    base CSR row at all — both read as 0 edges, and the caller raises
    the per-query dirty flag so the host oracle answers instead
    (mirrors fastpath.expand_phase's exp_deg handling)."""
    deg = _row_deg(g, node)
    nd = _node_dirty(g, node)
    if "ov_nbase" in g:
        deg = jnp.where(nd | (node >= g["ov_nbase"]), 0, deg)
    return deg, nd

_I32MAX = jnp.iinfo(jnp.int32).max

# task kinds: a tree subcheck, a rewrite-program node, a delegated
# pure-OR leaf (resolved by the fused BFS sub-run)
K_CHECK, K_PROG, K_FAST = 0, 1, 2

# linear-probe window of the visited hash set
_VPROBE = 8


def _init_roots(qpack, Q: int) -> Dict[str, jax.Array]:
    """Level-0 tasks: one tree CHECK per active query."""
    iota = jnp.arange(Q, dtype=jnp.int32)
    act = qpack[5].astype(bool)
    neg = jnp.full((Q,), -1, jnp.int32)
    return dict(
        kind=jnp.zeros((Q,), jnp.int32),  # K_CHECK
        ns=jnp.where(act, qpack[0], -1),
        obj=jnp.where(act, qpack[1], -1),
        rel=jnp.where(act, qpack[2], -1),
        d=jnp.where(act, qpack[4], 0),
        skip=jnp.zeros((Q,), bool),
        force=jnp.zeros((Q,), bool),
        prog=neg,
        qid=jnp.where(act, iota, -1),
        vscope=neg,
        parent=neg,
        neg=jnp.zeros((Q,), bool),
    )


def _classify_level(g, t, q_subj):
    """Resolve in-place leaves; compute child counts and combiner ops.

    Mirrors the retired interpreter's classification phase, with direct/expand
    subchecks flattened into the CHECK task itself (direct membership is a
    probe seed, expansion edges are immediate children at depth-1) — the same
    flattening the fast path uses, engine.go:242-245 depth math intact.
    """
    NS, R = g["f_direct_ok"].shape
    P = g["p_kind"].shape[0]
    F = t["kind"].shape[0]
    Q = q_subj.shape[0]
    i32 = jnp.int32

    active = t["qid"] >= 0
    ns, obj, rel, d = t["ns"], t["obj"], t["rel"], t["d"]
    nsc = jnp.clip(ns, 0, NS - 1)
    relc = jnp.clip(rel, 0, R - 1)
    cfg = (ns >= 0) & (ns < NS) & (rel >= 0) & (rel < R)
    subj = q_subj[jnp.clip(t["qid"], 0, Q - 1)]

    is_check = active & (t["kind"] == K_CHECK)
    is_prog = active & (t["kind"] == K_PROG)

    # -- tree CHECK: rel-err, rewrite root, direct/forced probe, edges ------
    err = is_check & cfg & g["rel_err"][nsc, relc]
    prog_root = jnp.where(cfg, g["prog_root"][nsc, relc], -1)
    has_rw = prog_root >= 0
    node = _node_lookup(g, ns, obj, rel)
    # strict-mode gates are baked into the flat tables (optable.py):
    # direct_ok = !has_rewrite, expand_ok = subject-set-capable types
    dok = jnp.where(cfg, g["f_direct_ok"][nsc, relc], True) & ~t["skip"]
    eok = jnp.where(cfg, g["f_expand_ok"][nsc, relc], True)
    member = _member(g, node, subj)
    # direct counts at depth-1 with its own <=0 guard => d >= 2
    # (engine.go:242,:167-208); a forced probe ignores depth (it stands in
    # for the parent-side EXISTS / batched-CSS probe)
    is_fast = active & (t["kind"] == K_FAST)
    seed = is_check & member & (t["force"] | (dok & (d >= 2)))
    exp_read = (is_check | is_fast) & eok & (d >= 2)
    deg_row, node_nd = _deg_guarded(g, node)
    deg = jnp.where(exp_read, deg_row, 0)
    dirt = exp_read & node_nd
    errable = cfg & g["err_reach"][nsc, relc]
    chk_count = jnp.where(d >= 1, has_rw.astype(i32) + deg, 0)

    # trivial fast leaves — no rewrite program and no reachable
    # subject-set edge — are a single membership probe; resolving them
    # here keeps plain relations (e.g. a !banned operand) out of the BFS
    # sub-batch entirely (the probes above are computed for every slot
    # anyway, so this is free)
    triv = is_fast & ~has_rw & (deg == 0)
    found_t = member & (t["force"] | (dok & (d >= 2)))

    # -- root-prog adoption -------------------------------------------------
    # A CHECK whose only child would be its rewrite program (no direct
    # seed, no expansion edges, no error) is OR-of-one: it may BECOME the
    # program root in place — OR(x) = x for the IS/NOT/ERR a root
    # combiner yields, and the or/and depth guard coincides with the
    # CHECK's.  Saves one full skeleton level per general root.
    adopt = (
        is_check & ~err & ~seed & has_rw & (deg == 0) & (d >= 1)
    )
    is_check = is_check & ~adopt
    is_prog = is_prog | adopt
    prog_eff = jnp.where(adopt, prog_root, t["prog"])

    # -- rewrite-program nodes ---------------------------------------------
    pp = jnp.clip(prog_eff, 0, P - 1)
    pk = g["p_kind"][pp]
    p_deg = g["p_child_ptr"][pp + 1] - g["p_child_ptr"][pp]
    node_ttu = _node_lookup(g, ns, obj, g["p_a"][pp])
    ttu_row, ttu_nd = _deg_guarded(g, node_ttu)
    ttu_deg = jnp.where(is_prog, ttu_row, 0)
    browc = jnp.clip(g["p_a"][pp], 0, g["b_ptr"].shape[0] - 2)
    b_deg = g["b_ptr"][browc + 1] - g["b_ptr"][browc]
    p_oan = is_prog & ((pk == P_OR) | (pk == P_AND))
    p_not = is_prog & (pk == P_NOT)
    p_css = is_prog & (pk == P_CSS)
    p_ttu = is_prog & (pk == P_TTU)
    p_bat = is_prog & (pk == P_BATCHCSS)
    # a TTU node whose via-row changed since the base snapshot cannot
    # trust even a 0 degree — the row may have gained tuples
    dirt = dirt | (p_ttu & ttu_nd)

    # depth guards: <=0 for check/or/and (engine.go:215, rewrites.go:39),
    # <0 for NOT/CSS/TTU (rewrites.go:141,214,247); BATCHCSS has none
    guard = ((is_check | p_oan) & (d <= 0)) | ((p_not | p_css | p_ttu) & (d < 0))
    count = jnp.select(
        [is_check, p_oan, p_not | p_css, p_ttu, p_bat],
        [chk_count, p_deg, jnp.ones((F,), i32), ttu_deg, b_deg],
        0,
    )

    # resolution (order mirrors the oracle: guard first, then
    # err, then probes, then empty-group NOT — binop.go:25-27)
    guard_is = is_check & (d <= 0) & t["force"] & member
    r_guard = guard & ~guard_is
    r_err = err & ~guard
    # IS short-circuit: prunes the whole subtree, legal only when no
    # client error can lurk in it (the oracle raises lazily in
    # [rewrite, direct, expand] order — a hidden raise must fall back)
    r_short = is_check & ~guard & ~err & seed & ~errable
    leaf = r_guard | guard_is | r_err | r_short
    count = jnp.where(leaf | ~active, 0, count)
    r_empty = (is_check | is_prog) & ~leaf & (count == 0)
    resolved = leaf | r_empty
    res = jnp.select(
        [r_err, guard_is | r_short | (r_empty & seed), r_guard],
        [jnp.full((F,), R_ERR, i32), jnp.full((F,), R_IS, i32),
         jnp.full((F,), R_UNKNOWN, i32)],
        jnp.where(r_empty, R_NOT, R_UNKNOWN),
    )
    res = jnp.where(
        triv,
        jnp.where(found_t, R_IS, jnp.where(d >= 1, R_NOT, R_UNKNOWN)),
        res,
    )
    resolved = resolved | triv
    cop = jnp.select(
        [p_oan & (pk == P_AND), p_not, p_css],
        [jnp.full((F,), OP_AND, i32), jnp.full((F,), OP_NOT, i32),
         jnp.full((F,), OP_PASS, i32)],
        jnp.full((F,), OP_OR, i32),
    )

    t = dict(
        t,
        # persist root-prog adoption: the construction phase routes
        # children by kind/prog
        kind=jnp.where(adopt, K_PROG, t["kind"]),
        prog=prog_eff,
        resolved=resolved,
        res=res,
        cop=cop,
        seed=seed & ~resolved,
        nchild=jnp.zeros((F,), i32),
        fast_id=jnp.full((F,), -1, i32),
    )
    aux = dict(
        node=node, prog_root=prog_root,
        r0=(has_rw & (d >= 1)).astype(i32),
        deg=deg, pk=pk, pp=pp, node_ttu=node_ttu,
        dirt=dirt,
    )
    return t, count, aux


def _visited(vset, k1, k2, k3, k4, evc, A: int):
    """Probe-and-insert into the open-addressed visited hash set
    (membership test, in-batch first-occurrence dedup by min arena index,
    insertion — one linear-probe loop)."""
    v1, v2, v3, v4 = vset
    VS = v1.shape[0]
    k1 = jnp.where(evc, k1, _I32MAX)
    k2 = jnp.where(evc, k2, _I32MAX)
    k3 = jnp.where(evc, k3, _I32MAX)
    k4 = jnp.where(evc, k4, _I32MAX)
    salts = jnp.asarray(hashtab._SALTS, jnp.uint32)
    h = (
        hashtab.mix_device(
            hashtab.mix_device(k1, k2, salts[0]).astype(jnp.int32),
            hashtab.mix_device(k3, k4, salts[1]).astype(jnp.int32),
            salts[2],
        )
        & jnp.uint32(VS - 1)
    ).astype(jnp.int32)
    aidx = jnp.arange(A, dtype=jnp.int32)
    seen = jnp.zeros((A,), bool)
    vpend = evc
    for i in range(_VPROBE):
        j = (h + i) & (VS - 1)
        match = (
            vpend & (v1[j] == k1) & (v2[j] == k2)
            & (v3[j] == k3) & (v4[j] == k4)
        )
        seen = seen | match
        vpend = vpend & ~match
        empty = v1[j] == _I32MAX
        claim = jnp.full((VS,), _I32MAX, jnp.int32).at[j].min(
            jnp.where(vpend & empty, aidx, _I32MAX), mode="drop"
        )
        won = vpend & empty & (claim[j] == aidx)
        tgt = jnp.where(won, j, VS)
        v1 = v1.at[tgt].set(k1, mode="drop")
        v2 = v2.at[tgt].set(k2, mode="drop")
        v3 = v3.at[tgt].set(k3, mode="drop")
        v4 = v4.at[tgt].set(k4, mode="drop")
        vpend = vpend & ~won
        nowmatch = (
            vpend & (v1[j] == k1) & (v2[j] == k2)
            & (v3[j] == k3) & (v4[j] == k4)
        )
        seen = seen | nowmatch
        vpend = vpend & ~nowmatch
    return (v1, v2, v3, v4), seen, vpend


def _construct_level(
    g, t, count, aux, vset, q_over, *,
    A: int, level_base: int, max_width: int, Q: int,
    pmine=None,
):
    """Allocate and build the next level's tasks — child allocation,
    edge/program gathers, visited-set insertion — with the per-level
    arena BEING the next level (dense, no pack)."""
    NS, R = g["f_direct_ok"].shape
    F = t["kind"].shape[0]
    i32 = jnp.int32

    counts = jnp.where(t["resolved"] | (t["qid"] < 0), 0, count)
    offsets, _total, ap, ao = arena_assign(counts, A)
    fits = offsets + counts <= A
    overp = (counts > 0) & ~fits
    qc = jnp.clip(t["qid"], 0, Q - 1)
    q_over = q_over.at[qc].max(overp)
    # over-capacity parents resolve UNKNOWN; their queries fall back
    t = dict(
        t,
        resolved=t["resolved"] | overp,
        res=jnp.where(overp, R_UNKNOWN, t["res"]),
        nchild=jnp.where(fits, counts, 0),
    )

    aps = jnp.clip(ap, 0, F - 1)
    valid = (ap >= 0) & fits[aps] & (t["qid"][aps] >= 0)

    pkind = t["kind"][aps]
    ppk = aux["pk"][aps]
    r0 = aux["r0"][aps]
    pns, pobj, prel = t["ns"][aps], t["obj"][aps], t["rel"][aps]
    pd, pqid, pvs = t["d"][aps], t["qid"][aps], t["vscope"][aps]
    ppa = g["p_a"][aux["pp"][aps]]
    ppb = g["p_b"][aux["pp"][aps]]

    c_rw = valid & (pkind == K_CHECK) & (ao < r0)
    c_edge = valid & (pkind == K_CHECK) & (ao >= r0)
    c_prog = valid & (pkind == K_PROG)
    c_oan = c_prog & ((ppk == P_OR) | (ppk == P_AND) | (ppk == P_NOT))
    c_css = c_prog & (ppk == P_CSS)
    c_ttu = c_prog & (ppk == P_TTU)
    c_bat = c_prog & (ppk == P_BATCHCSS)

    # edge gathers (expansion rows for CHECK parents, via-rows for TTU)
    rp = g["row_ptr"]
    eo = ao - r0
    base_exp = rp[jnp.clip(aux["node"][aps], 0, rp.shape[0] - 2)]
    base_ttu = rp[jnp.clip(aux["node_ttu"][aps], 0, rp.shape[0] - 2)]
    eidx = jnp.clip(
        jnp.where(c_ttu, base_ttu + ao, base_exp + eo),
        0, g["edge_hi"].shape[0] - 1,
    )
    e_hi, e_obj = g["edge_hi"][eidx], g["edge_obj"][eidx]
    num_rels = g["prog_root"].shape[1]
    e_ns = jnp.where(e_hi >= 0, e_hi // num_rels, -1)
    e_rel = jnp.where(e_hi >= 0, e_hi % num_rels, -1)

    # program CSR gathers
    pci = jnp.clip(
        g["p_child_ptr"][aux["pp"][aps]] + ao, 0, g["p_child_idx"].shape[0] - 1
    )
    prog_child = g["p_child_idx"][pci]
    prog_dec = g["p_child_dec"][pci]
    prog_neg = g["p_child_neg"][pci]
    # CSS hop collapse: a P_CSS node is a pure relation remap with no row
    # gather of its own (child = CHECK(ns, obj, p_a) at the same depth,
    # rewrites.go:208-230; its d<0 guard is subsumed by the CHECK's d<=0
    # guard) — emitting the subcheck directly removes one skeleton level
    # per computed-subject-set under AND/NOT
    pk2 = g["p_kind"][jnp.clip(prog_child, 0, g["p_kind"].shape[0] - 1)]
    c_cssdir = c_oan & (pk2 == P_CSS)
    css_dir_rel = g["p_a"][jnp.clip(prog_child, 0, g["p_kind"].shape[0] - 1)]

    # batched-CSS row gathers
    bi = jnp.clip(
        g["b_ptr"][jnp.clip(ppa, 0, g["b_ptr"].shape[0] - 2)] + ao,
        0, g["b_rel"].shape[0] - 1,
    )
    brel = g["b_rel"][bi]
    bprobe = g["b_probe"][bi]

    ch_ns = jnp.where(c_edge | c_ttu, e_ns, pns)
    ch_obj = jnp.where(c_edge | c_ttu, e_obj, pobj)
    ch_rel = jnp.select([c_edge, c_ttu, c_css, c_bat, c_cssdir],
                        [e_rel, ppb, ppa, brel, css_dir_rel], prel)
    # depth math: expansion / TTU / batched-CSS children at depth-1
    # (engine.go:245, rewrites.go:281,:86); nested rewrite children at
    # depth - dec (rewrites.go:118); rewrite root and CSS keep depth
    # (engine.go:237, rewrites.go:214)
    ch_d = jnp.select(
        [c_edge | c_ttu | c_bat, c_oan],
        [pd - 1, pd - prog_dec],
        pd,
    )
    ch_prog = jnp.select(
        [c_rw, c_oan & ~c_cssdir], [aux["prog_root"][aps], prog_child], -1
    )
    ch_skip = c_edge | c_bat  # skip_direct (engine.go:161, rewrites.go:86)
    ch_force = c_edge | (c_bat & bprobe)
    # folded InvertResult parity: flips the child's verdict on delivery
    ch_neg = c_oan & prog_neg
    # visited scope: expansion children open a scope at the first
    # expanding ancestor (engine.go:119); slot ids are globally unique
    # via the static level base
    ch_vscope = jnp.where(c_edge & (pvs < 0), level_base + aps, pvs)

    # subcheck children route by the static taint: tainted => tree CHECK,
    # pure => delegated fast leaf (BFS sub-run)
    ch_nsc = jnp.clip(ch_ns, 0, NS - 1)
    ch_relc = jnp.clip(ch_rel, 0, R - 1)
    in_cfg = (ch_ns >= 0) & (ch_ns < NS) & (ch_rel >= 0) & (ch_rel < R)
    tainted = in_cfg & g["taint"][ch_nsc, ch_relc]
    ch_kind = jnp.where(
        c_rw | (c_oan & ~c_cssdir),
        K_PROG,
        jnp.where(tainted, K_CHECK, K_FAST),
    )

    # width truncation (engine.go:141-150): beyond max_width-1 children
    # the EXISTS probe still fires (tested pre-truncation) but recursion
    # stops — probe-only leaves at depth 0
    pdeg = aux["deg"][aps]
    trunc = c_edge & (pdeg > max_width) & (eo >= max_width - 1)

    # visited set covers expansion children only; duplicates keep their
    # EXISTS probe (row iteration probes before the visited check skips
    # recursion, engine.go:131-139,157-162) as probe-only leaves.
    # Sharded: only the parent's OWNER shard has real edge gathers — the
    # other shards' rows are garbage that must not enter the (shard-
    # local) visited set or raise spurious overflow.  Cross-shard
    # duplicate children are tolerated: the visited set exists for
    # capacity/cycle economy, not semantics (OR is idempotent and the
    # depth budget bounds recursion), so per-shard dedup is sound.
    evc = c_edge & ~trunc
    if pmine is not None:
        evc = evc & pmine[aps]
    vset, seen, vpend = _visited(
        vset, ch_vscope, ch_ns, ch_obj, ch_rel, evc, A
    )
    q_over = q_over.at[jnp.clip(pqid, 0, Q - 1)].max(vpend)
    probe_only = trunc | seen | vpend
    ch_kind = jnp.where(c_edge & probe_only, K_FAST, ch_kind)
    ch_d = jnp.where(c_edge & probe_only, 0, ch_d)

    neg = jnp.full((A,), -1, i32)
    child = dict(
        kind=jnp.where(valid, ch_kind, 0),
        ns=jnp.where(valid, ch_ns, -1),
        obj=jnp.where(valid, ch_obj, -1),
        rel=jnp.where(valid, ch_rel, -1),
        d=jnp.where(valid, ch_d, 0),
        skip=valid & ch_skip,
        force=valid & ch_force,
        prog=jnp.where(valid, ch_prog, -1),
        qid=jnp.where(valid, pqid, -1),
        vscope=jnp.where(valid, ch_vscope, -1),
        parent=jnp.where(valid, ap, neg),
        neg=valid & ch_neg,
    )
    return t, child, vset, q_over


def _collect_fast(levels, q_subj, q_over, B: int, Q: int):
    """Compact every K_FAST task across levels into one BFS sub-batch."""
    i32 = jnp.int32
    fb = dict(
        ns=jnp.full((B,), -1, i32),
        obj=jnp.full((B,), -1, i32),
        rel=jnp.full((B,), -1, i32),
        d=jnp.zeros((B,), i32),
        skip=jnp.zeros((B,), bool),
        force=jnp.zeros((B,), bool),
        subj=jnp.zeros((B,), i32),
        valid=jnp.zeros((B,), bool),
    )
    base = jnp.int32(0)
    out_levels = []
    for t in levels:
        # trivially-resolved leaves (no rewrite, no edges) stay out
        m = (t["kind"] == K_FAST) & (t["qid"] >= 0) & ~t["resolved"]
        pos = base + jnp.cumsum(m.astype(i32)) - 1
        ok = m & (pos < B)
        tgt = jnp.where(ok, pos, B)
        fb = dict(
            ns=fb["ns"].at[tgt].set(t["ns"], mode="drop"),
            obj=fb["obj"].at[tgt].set(t["obj"], mode="drop"),
            rel=fb["rel"].at[tgt].set(t["rel"], mode="drop"),
            d=fb["d"].at[tgt].set(jnp.maximum(t["d"], 0), mode="drop"),
            skip=fb["skip"].at[tgt].set(t["skip"], mode="drop"),
            force=fb["force"].at[tgt].set(t["force"], mode="drop"),
            subj=fb["subj"].at[tgt].set(
                q_subj[jnp.clip(t["qid"], 0, Q - 1)], mode="drop"
            ),
            valid=fb["valid"].at[tgt].set(ok, mode="drop"),
        )
        # leaves that do not fit resolve UNKNOWN and flag their query
        drop = m & ~ok
        q_over = q_over.at[jnp.clip(t["qid"], 0, Q - 1)].max(drop)
        out_levels.append(dict(
            t,
            fast_id=jnp.where(ok, pos, -1),
            resolved=t["resolved"] | drop,
            res=jnp.where(drop, R_UNKNOWN, t["res"]),
        ))
        base = base + jnp.sum(m.astype(i32))
    return out_levels, fb, q_over, base


def _fast_subrun(g, fb, *, sched, max_width: int, shard=None):
    """The fast path's fused BFS over the collected pure-OR leaves.

    Leaf depths, skip and force flags carry the mid-tree context
    (skip_direct from expansion / batched-CSS parents, forced EXISTS /
    probe-shortcut probes).  Returns (found, over) per leaf.

    ``shard=(axis_name, n)``: the graph is SHARDED by (ns, obj) — each
    leaf activates on its owner shard, children are routed to their
    owners with all_to_all between levels, and found/over/dirty bits are
    psum-merged (the graphshard.sharded_check loop over a shared global
    leaf index space).
    """
    NS, R = g["f_direct_ok"].shape
    B = fb["ns"].shape[0]
    iota = jnp.arange(B, dtype=jnp.int32)
    active = fb["valid"]
    if shard is not None:
        axis_name, n_sh = shard
        # engine->parallel is upside-down layering for a module import;
        # the routing primitive is only needed on this branch
        from ketotpu.parallel.graphshard import _route

        me = jax.lax.axis_index(axis_name)
        active = active & (_shard_owner(fb["ns"], fb["obj"], n_sh) == me)
    s = dict(
        f_qid=jnp.where(active, iota, -1),
        f_ns=fb["ns"],
        f_obj=fb["obj"],
        f_rel=fb["rel"],
        f_depth=jnp.minimum(fb["d"], len(sched)),
        f_skip=fb["skip"],
        f_force=fb["force"],
        q_found=jnp.zeros((B,), bool),
        q_over=jnp.zeros((B,), bool),
        q_dirty=jnp.zeros((B,), bool),
        q_subj=fb["subj"],
    )
    occ = []  # live leaves ENTERING each level (adaptive-schedule feed)
    for i, (f, a) in enumerate(sched):
        with jax.named_scope(f"level{i}"):
            occ.append(jnp.sum((s["f_qid"] >= 0).astype(jnp.int32)))
            nxt_f = sched[i + 1][0] if i + 1 < len(sched) else 1
            children, q_found, q_over, q_dirty = fp.expand_phase(
                g, s, arena=a, max_width=max_width,
                probe_only=(i == len(sched) - 1),
            )
            if shard is not None:
                children, q_over = _route(
                    children, n_sh, max(a // n_sh, 8), q_over, axis_name
                )
                # merge found bits across shards before packing so arrived
                # children of already-found leaves die immediately
                q_found = (
                    _psum(q_found.astype(jnp.int32), axis_name) > 0
                )
            nxt, q_over = fp.pack_phase(
                children, q_found, q_over, frontier=nxt_f, ns_dim=NS,
                rel_dim=R,
            )
            s = dict(
                nxt, q_found=q_found, q_over=q_over, q_dirty=q_dirty,
                q_subj=s["q_subj"],
            )
    q_found, q_over, q_dirty = s["q_found"], s["q_over"], s["q_dirty"]
    if shard is not None:
        q_found = _psum(q_found.astype(jnp.int32), axis_name) > 0
        q_over = _psum(q_over.astype(jnp.int32), axis_name) > 0
        q_dirty = _psum(q_dirty.astype(jnp.int32), axis_name) > 0
    # found is monotone and overlay-exact (probes consult om_), so a
    # found leaf is trustworthy even when exploration brushed a dirty
    # row; an UNFOUND dirty leaf must be answered by the host oracle
    return q_found, q_over, q_dirty, occ


def run_general_packed_timed(g, qpack, *, span=profiler.null_span, **kw):
    """run_general_packed inside the engine span ``check_gen_dispatch``
    (``span``: the engine's ``_span``): the dispatch's host wall time,
    trace/compile on the first shape, async enqueue after.
    run_general_packed itself is jitted with static argnames and cannot
    carry host-side instrumentation."""
    with span("check_gen_dispatch", rows=qpack.shape[1]), compilewatch.scope(
        "general_packed",
        lambda: f"Q={qpack.shape[1]} sizes={kw.get('sizes')} "
                f"fast_b={kw.get('fast_b')}",
    ):
        out = run_general_packed(g, qpack, **kw)
    return out


def _general_body(
    g: Dict[str, jax.Array],
    qpack,
    *,
    sizes: Tuple[int, ...],
    fast_b: int,
    fast_sched: Tuple[Tuple[int, int], ...],
    max_width: int = 100,
    vcap: int = 4096,
    shard: Tuple[str, int] = None,
):
    """One fused dispatch answering a whole general (AND/NOT) batch.

    Non-jitted body so engine/fused.py can inline it as the general tier
    of the single-program wave cascade; ``run_general_packed`` below is
    the jitted standalone entry the unfused path dispatches.

    ``qpack``: int32[6, Q] (ns, obj, rel, subj, depth, active).
    ``sizes``: per-level task capacities for levels 1..D (level 0 = Q).
    Returns (codes uint8[Q]: bits 0-1 = R_* result, bit 2 = over, bit 3 =
    dirty (a pending-write overlay touched stale state — host oracle must
    answer; a device retry would see the same stale base);
    occ int32[D+2+len(fast_sched)]: skeleton per-level live-task counts
    (D+1), total fast-leaf count, then the BFS sub-run's per-level live
    counts — the layout tpu._update_gen_occ unpacks).

    ``shard=(axis_name, n)`` runs the SAME program against a
    (ns, obj)-hash-sharded graph slice inside a shard_map (the mesh
    engine's general tier, no replica): the (ns, obj) partitioning keeps
    every per-task read — node lookup, membership and batched-CSS
    probes, expansion edge rows, TTU via-rows — on the task's owner
    shard, and the program/config tables are identical on every shard by
    construction.  The skeleton stays GLOBALLY CONSISTENT: every shard
    holds the full level arenas; classification/construction is masked
    to each task's owner and psum-merged (exactly one owner per task, so
    the owner's values survive), which keeps `arena_assign` and the
    whole up pass deterministic and collective-free.  Fast leaves run
    the graphshard BFS (owner-activated, all_to_all-routed children).
    Per-level collective cost: ~a dozen psums of level-sized int32
    arrays riding ICI.
    """
    Q = qpack.shape[1]
    q_subj = qpack[3]
    q_over = jnp.zeros((Q,), bool)
    q_dirty = jnp.zeros((Q,), bool)
    vset = tuple(
        jnp.full((hashtab._bucket_pow2(2 * vcap, 16),), _I32MAX, jnp.int32)
        for _ in range(4)
    )

    if shard is not None:
        axis_name, n_sh = shard
        me = jax.lax.axis_index(axis_name)

        def _mi(x, mine):  # owner-masked int merge (exactly one owner)
            return _psum(jnp.where(mine, x, 0), axis_name)

        def _mb(x, mine):
            return _psum(
                jnp.where(mine, x.astype(jnp.int32), 0), axis_name
            ) > 0

        def _merge_classified(t, count, aux):
            """Keep the owner shard's data-dependent classification for
            every task; recompute the config-derived program fields from
            the merged adoption state."""
            mine = _shard_owner(t["ns"], t["obj"], n_sh) == me
            t = dict(
                t,
                kind=_mi(t["kind"], mine),
                prog=_mi(t["prog"], mine),
                resolved=_mb(t["resolved"], mine),
                res=_mi(t["res"], mine),
                cop=_mi(t["cop"], mine),
                seed=_mb(t["seed"], mine),
            )
            pp = jnp.clip(t["prog"], 0, g["p_kind"].shape[0] - 1)
            aux = dict(
                aux,
                deg=_mi(aux["deg"], mine),
                dirt=_mb(aux["dirt"], mine),
                pp=pp,
                pk=g["p_kind"][pp],
            )
            return t, _mi(count, mine), aux, mine

        def _merge_child(child, pmine):
            """Children carry the values their PARENT's owner computed
            (edge gathers live there); empty rows have exactly one owner
            too (slot 0's), which contributes the shared fill values."""
            F = pmine.shape[0]
            ap = child["parent"]
            mine_p = pmine[jnp.clip(ap, 0, F - 1)]
            out = {}
            for k, v in child.items():
                if v.dtype == jnp.bool_:
                    out[k] = _mb(v, mine_p)
                else:
                    out[k] = _mi(v, mine_p)
            return out

        def _pmax_bool(x):
            return _psum(x.astype(jnp.int32), axis_name) > 0
    else:
        _merge_classified = None

    def _fold_dirty(q_dirty, t, aux):
        return q_dirty.at[jnp.clip(t["qid"], 0, Q - 1)].max(aux["dirt"])

    # -- down pass: build the algebra skeleton ------------------------------
    levels: List[Dict[str, jax.Array]] = [_init_roots(qpack, Q)]
    level_base = 0
    with jax.named_scope("level0"):
        t, count, aux = _classify_level(g, levels[0], q_subj)
        pmine = None
        if shard is not None:
            t, count, aux, pmine = _merge_classified(t, count, aux)
        q_dirty = _fold_dirty(q_dirty, t, aux)
    for lvl, A in enumerate(sizes, 1):
        with jax.named_scope(f"level{lvl}"):
            t, child, vset, q_over = _construct_level(
                g, t, count, aux, vset, q_over,
                A=A, level_base=level_base, max_width=max_width, Q=Q,
                pmine=pmine,
            )
            if shard is not None:
                child = _merge_child(child, pmine)
            levels[-1] = t
            level_base += t["kind"].shape[0]
            levels.append(child)
            t, count, aux = _classify_level(g, child, q_subj)
            if shard is not None:
                t, count, aux, pmine = _merge_classified(t, count, aux)
            q_dirty = _fold_dirty(q_dirty, t, aux)
    # last level: any task still needing children exhausts the level
    # budget — UNKNOWN + over (host fallback).
    # K_FAST tasks never take skeleton children (count stays 0), so they
    # are NOT capped here: they stay unresolved and _collect_fast
    # delegates them to the BFS sub-run like any other level's leaves
    # (a resolved-at-R_UNKNOWN fast leaf would feed the up-pass a silent
    # wrong DENY with no over bit).  K_CHECK/K_PROG with count == 0 were
    # already resolved by _classify_level's r_empty term.
    depth_capped = (t["qid"] >= 0) & ~t["resolved"] & (count > 0)
    q_over = q_over.at[jnp.clip(t["qid"], 0, Q - 1)].max(depth_capped)
    levels[-1] = dict(
        t,
        resolved=t["resolved"] | depth_capped,
        res=jnp.where(depth_capped, R_UNKNOWN, t["res"]),
    )

    # -- delegate pure-OR leaves to the fused BFS ---------------------------
    # (the merged levels are identical on every shard, so the leaf
    # compaction and fast_id assignment form a SHARED global index space
    # — exactly what the sharded sub-run's psum-merged bits need)
    with jax.named_scope("leaves"):
        levels, fb, q_over, fast_n = _collect_fast(
            levels, q_subj, q_over, fast_b, Q
        )
        found, fover, fdirty, fast_occ = _fast_subrun(
            g, fb, sched=fast_sched, max_width=max_width, shard=shard
        )

    # map leaf verdicts back: pure-OR checks with depth >= 1 are exactly
    # IS/NOT (OR swallows UNKNOWN at every level); depth <= 0 is the
    # root guard UNKNOWN unless a forced probe hit.  A found leaf stands
    # even under an overlay (monotone, overlay-exact probes); an unfound
    # leaf that brushed a dirty row marks its root for the host oracle.
    for i, t in enumerate(levels):
        fid = t["fast_id"]
        has = fid >= 0
        fc = jnp.clip(fid, 0, fast_b - 1)
        f_res = jnp.where(
            found[fc], R_IS, jnp.where(t["d"] >= 1, R_NOT, R_UNKNOWN)
        )
        qc = jnp.clip(t["qid"], 0, Q - 1)
        q_over = q_over.at[qc].max(has & fover[fc])
        q_dirty = q_dirty.at[qc].max(has & fdirty[fc] & ~found[fc])
        levels[i] = dict(
            t,
            resolved=t["resolved"] | has,
            res=jnp.where(has, f_res, t["res"]),
        )

    # -- up pass: resolve combiners bottom-up -------------------------------
    # (all children of a level-L task live at level L+1 and are resolved
    # by round order; binop.go:18-73, rewrites.go:186-230 semantics)
    for L in range(len(levels) - 1, 0, -1):
        with jax.named_scope("up"):
            ch, par = levels[L], levels[L - 1]
            Fp = par["kind"].shape[0]
            val = ch["qid"] >= 0
            pt = jnp.where(val, jnp.clip(ch["parent"], 0, Fp - 1), Fp)
            zero = jnp.zeros((Fp,), jnp.int32)
            # folded-NOT parity: a negated edge delivers IS as NOT and vice
            # versa; UNKNOWN and ERR pass through (rewrites.go:186-200)
            eff_is = jnp.where(
                ch["neg"], ch["res"] == R_NOT, ch["res"] == R_IS
            )
            eff_not = jnp.where(
                ch["neg"], ch["res"] == R_IS, ch["res"] == R_NOT
            )
            nis = zero.at[pt].add(eff_is.astype(jnp.int32), mode="drop")
            nnot = zero.at[pt].add(eff_not.astype(jnp.int32), mode="drop")
            nerr = zero.at[pt].add(
                (ch["res"] == R_ERR).astype(jnp.int32), mode="drop"
            )
            unres = (par["qid"] >= 0) & ~par["resolved"]
            val_or = jnp.where((nis > 0) | par["seed"], R_IS, R_NOT)
            val_and = jnp.where(nis == par["nchild"], R_IS, R_NOT)
            val_not = jnp.where(
                nis > 0, R_NOT, jnp.where(nnot > 0, R_IS, R_UNKNOWN)
            )
            val_pass = jnp.where(
                nis > 0, R_IS, jnp.where(nnot > 0, R_NOT, R_UNKNOWN)
            )
            v = jnp.select(
                [nerr > 0, par["cop"] == OP_AND, par["cop"] == OP_NOT,
                 par["cop"] == OP_PASS],
                [jnp.full((Fp,), R_ERR, jnp.int32), val_and, val_not,
                 val_pass],
                val_or,
            )
            levels[L - 1] = dict(
                par,
                res=jnp.where(unres, v, par["res"]),
                resolved=par["resolved"] | unres,
            )

    if shard is not None:
        # visited-set overflow (per-shard) and any other owner-local
        # over/dirty contributions become global; everything else in
        # q_over/q_dirty is already replicated, and OR-merging is
        # idempotent either way
        q_over = _pmax_bool(q_over)
        q_dirty = _pmax_bool(q_dirty)
    codes = (
        levels[0]["res"].astype(jnp.uint8)
        | (q_over.astype(jnp.uint8) << 2)
        | (q_dirty.astype(jnp.uint8) << 3)
    )
    # occupancy feed for the engine's adaptive scheduler: skeleton level
    # counts (D+1), total fast leaves, then the BFS sub-run's per-level
    # live counts (len(fast_sched)) — all in one tiny download
    occ = jnp.stack(
        [jnp.sum((t["qid"] >= 0).astype(jnp.int32)) for t in levels]
        + [fast_n]
        + fast_occ
    )
    return codes, occ


run_general_packed = functools.partial(
    jax.jit,
    static_argnames=(
        "sizes", "fast_b", "fast_sched", "max_width", "vcap", "shard",
    ),
)(_general_body)


def _psum(x, axis_name):
    """``lax.psum`` of the sharded mode's merges, under the scope
    ``mesh/merge`` in a capture.  Defined after the module's last line, and
    every call of it stands on a line only the ``shard=`` mode traces: no
    line of a one-chip program moved, so the persistent cache still knows
    them (compilewatch.place_cache keys by source locations too)."""
    with jax.named_scope("mesh/merge"):
        return jax.lax.psum(x, axis_name)
