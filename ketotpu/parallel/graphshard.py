"""Graph-sharded batch checks: the CSR partitioned across the device mesh.

BASELINE config #5: a 10M-tuple graph object-sharded over a mesh, with
cross-namespace subject-set / tuple-to-userset hops routed over ICI.  The
reference has no analog — it scales out with stateless replicas over one SQL
database (SURVEY §2 parallelism checklist); this layout is the TPU-native
replacement.

Partitioning: a tuple row lives on shard ``hash(namespace, object) % n``
(hashtab's mix, salt 0).  Keying by (namespace, object) — not the full node
key — keeps every relation of an object co-resident, so

* direct membership probes,
* the batched computed-subject-set shortcut (same object, other relation),
* tuple-to-userset via-rows (same object, via relation)

are all shard-local.  Only *children* can cross shards: subject-set
expansion targets and TTU computed targets.  Each BFS level therefore runs

    expand (local gathers)  →  all-to-all (route children to owners)
    →  pack (dedup on arrival)  →  psum (merge found/over bits)

inside one `jax.shard_map`, with `fastpath.expand_phase(sharded=True)`
providing exact EXISTS-bit semantics across shards: expansion children carry
a forced membership probe executed by their owner on arrival, and
width-truncated children ship as probe-only items (depth 0) so the
pre-truncation EXISTS check of `engine.go:131-139` survives sharding.

The all-to-all uses fixed per-destination buckets (capacity = arena / n per
peer); bucket overflow sets the affected queries' ``q_over`` bits — the same
monotone overflow contract as the single-chip engine.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ketotpu import compilewatch
from ketotpu.engine import fastpath as fp
from ketotpu.engine import hashtab
from ketotpu.engine.snapshot import Snapshot
from ketotpu.storage.memory import InMemoryTupleStore
from ketotpu.storage.namespaces import NamespaceManager
from ketotpu.engine.vocab import Vocab


def shard_of_np(ns_ids: np.ndarray, obj_ids: np.ndarray, n_shards: int) -> np.ndarray:
    """Owner shard of (namespace, object) — host side."""
    h = hashtab._mix_np(
        np.asarray(ns_ids, np.int64), np.asarray(obj_ids, np.int64),
        hashtab._SALTS[0],
    )
    return (h % np.uint32(n_shards)).astype(np.int32)


def shard_of_device(ns_ids, obj_ids, n_shards: int):
    h = hashtab.mix_device(ns_ids, obj_ids, jnp.uint32(hashtab._SALTS[0]))
    return (h % jnp.uint32(n_shards)).astype(jnp.int32)


def build_sharded_snapshot(
    store: InMemoryTupleStore,
    manager: Optional[NamespaceManager],
    n_shards: int,
    vocab: Optional[Vocab] = None,
    cols=None,
    replicate: Optional[Dict[Tuple[int, int], Sequence[int]]] = None,
) -> Tuple[List[Snapshot], Dict[str, np.ndarray]]:
    """Partition the store by owner shard and build one snapshot per shard.

    All shards share one vocabulary (ids are global) and are padded to
    common array shapes, so the stacked dict (leading axis = shard) can be
    fed through `shard_map` with the graph partitioned on that axis.

    Partitioning is a vectorized mask over the engine's column mirror
    (``cols``, engine/delta.TupleColumns — passed by the mesh engine so a
    rebuild reuses its freshly synced mirror; built here otherwise), not a
    per-tuple Python loop: each shard's snapshot projects through the same
    `build_snapshot_cols` numpy path as the single-chip engine.

    ``replicate`` maps hot (ns_id, obj_id) keys to extra shards that get a
    COPY of those rows on top of their hash-owned partition.  The hash
    owner always keeps its rows (replication copies, never moves), so
    child routing by hash stays correct; a replicated root query may be
    assigned to any of its replicas via `sharded_check`'s ``assign``
    column.  Replica copies pad into the existing max-shard shapes in the
    common case, so publishing a replica map usually keeps the stacked
    signature — and the jit cache — warm.
    """
    from ketotpu.engine import delta as dl

    vocab = vocab if vocab is not None else Vocab()
    if cols is None:
        exporter = getattr(store, "export_columns", None)
        store_vocab = getattr(store, "vocab", None)
        if exporter is not None and (
            store_vocab is vocab or len(vocab.subjects) == 0
        ):
            carr, alive, tail, _head = exporter()
            cols = dl.TupleColumns.from_arrays(store_vocab, carr, alive)
            for t in tail:
                cols.apply(1, t)
            vocab = store_vocab
        else:
            cols = dl.TupleColumns(vocab)
            for t in store.all_tuples():
                cols.apply(1, t)

    live = np.flatnonzero(cols.alive[: cols.n])
    shard = shard_of_np(cols.ns[live], cols.obj[live], n_shards)
    extra = [np.zeros(0, np.int64)] * n_shards
    if replicate:
        packed = (
            np.asarray(cols.ns[live], np.int64) << 32
        ) | (np.asarray(cols.obj[live], np.int64) & 0xFFFFFFFF)
        for (ns_id, obj_id), shards_for in replicate.items():
            key = (np.int64(ns_id) << 32) | (np.int64(obj_id) & 0xFFFFFFFF)
            rows = live[packed == key]
            if rows.size == 0:
                continue
            for s in shards_for:
                extra[int(s)] = np.concatenate([extra[int(s)], rows])
    version = getattr(store, "version", -1)
    snaps: List[Snapshot] = []
    for s in range(n_shards):
        keep = np.zeros(cols.n, bool)
        keep[live[shard == s]] = True
        keep[extra[s]] = True
        snaps.append(
            dl.build_snapshot_cols(
                cols.masked(keep), manager, version=version
            )
        )

    # pad every per-shard array to the maximum shape, then stack
    keys = snaps[0].arrays().keys()
    stacked: Dict[str, np.ndarray] = {}
    for k in keys:
        arrs = [np.asarray(s.arrays()[k]) for s in snaps]
        shape = tuple(max(a.shape[i] for a in arrs) for i in range(arrs[0].ndim))
        padded = []
        for a in arrs:
            pad = [(0, shape[i] - a.shape[i]) for i in range(a.ndim)]
            fill = 0 if k.endswith("ptr") else (False if a.dtype == bool else -1)
            b = np.pad(a, pad, constant_values=fill)
            if k.endswith("ptr") and a.shape[0] < shape[0]:
                b[a.shape[0]:] = a[-1]  # CSR tail rows stay empty
            padded.append(b)
        stacked[k] = np.stack(padded)
    return snaps, stacked


def _route(children: Dict, n: int, cap: int, q_over, axis: str):
    """Bucket children by owner shard and all-to-all them to owners.

    ``cap`` slots per destination peer; overflow marks q_over (monotone).
    The whole of it (sort, bucketize, ``all_to_all``, unpack) stands under
    the scope ``mesh/route`` in a capture, in both sharded programs.
    """
    with jax.named_scope("mesh/route"):
        Q = q_over.shape[0]
        dest = shard_of_device(children["ns"], children["obj"], n)
        alive = children["qid"] >= 0
        dest = jnp.where(alive, dest, n)  # dead rows sort last

        # stable sort by destination, then slot within each dest bucket
        A = dest.shape[0]
        order = jnp.argsort(dest * (A + 1) + jnp.arange(A, dtype=jnp.int32))
        dsorted = dest[order]
        # position within the destination run
        pos_in_run = jnp.arange(A, dtype=jnp.int32) - jnp.searchsorted(
            dsorted, dsorted, side="left"
        )
        over_b = (dsorted < n) & (pos_in_run >= cap)
        srt = {k: v[order] for k, v in children.items()}
        q_over = q_over.at[jnp.clip(srt["qid"], 0, Q - 1)].max(
            over_b & (srt["qid"] >= 0)
        )

        slot = jnp.where(
            dsorted < n,
            dsorted * cap + jnp.clip(pos_in_run, 0, cap - 1),
            n * cap,
        )
        slot = jnp.where(over_b, n * cap, slot)

        def bucketize(col, fill):
            return (
                jnp.full((n * cap,), fill, col.dtype)
                .at[slot]
                .set(jnp.where(over_b | (dsorted >= n), fill, col), mode="drop")
            )

        send = jnp.stack(
            [
                bucketize(srt["qid"], -1),
                bucketize(srt["ns"], -1),
                bucketize(srt["obj"], -1),
                bucketize(srt["rel"], -1),
                bucketize(srt["d"], 0),
                bucketize(srt["skip"].astype(jnp.int32), 1),
                bucketize(srt["force"].astype(jnp.int32), 0),
            ],
            axis=1,
        ).reshape(n, cap, 7)
        recv = jax.lax.all_to_all(send, axis, 0, 0, tiled=False)
        recv = recv.reshape(n * cap, 7)
        out = dict(
            qid=recv[:, 0],
            ns=recv[:, 1],
            obj=recv[:, 2],
            rel=recv[:, 3],
            d=recv[:, 4],
            skip=recv[:, 5].astype(bool),
            force=recv[:, 6].astype(bool),
        )
        return out, q_over


def _merge_any(bits, axis: str):
    """OR a per-shard verdict bit over the mesh (a ``psum`` of the bits),
    under the scope ``mesh/merge`` in a capture."""
    with jax.named_scope("mesh/merge"):
        return jax.lax.psum(bits.astype(jnp.int32), axis) > 0


def sharded_general_check(
    stacked_g: Dict[str, np.ndarray],
    qpack: np.ndarray,
    mesh: Mesh,
    *,
    axis: str = "shard",
    sizes,
    fast_b: int,
    fast_sched,
    max_width: int = 100,
    vcap: int = 4096,
):
    """General (AND/NOT) checks against the SHARDED graph — no replica.

    The fused algebra program runs on every shard over the full
    (replicated) query block with per-task work owner-masked and merged
    (algebra.run_general_packed's ``shard`` mode): the (ns, obj)
    partitioning keeps all of a task's reads shard-local, children land
    on their owners via the program's merge collectives, and pure-OR
    fast leaves ride the same all_to_all-routed BFS as `sharded_check`.
    Per-device GRAPH memory scales down with mesh size (VERDICT r4 #5);
    only the per-batch skeleton working set is replicated.

    ``sizes``/``fast_sched`` are GLOBAL shapes (the whole batch's
    skeleton lives on every shard).  Returns (codes uint8[Q], occ
    int32[n, L]) with codes replicated-identical across shards.
    """
    with compilewatch.scope(
        "sharded_general",
        lambda: f"Q={qpack.shape[1]} n={mesh.devices.size} "
                f"sizes={tuple(sizes)}",
    ):
        return _sharded_general_run(
            stacked_g, jnp.asarray(qpack, jnp.int32),
            mesh=mesh, axis=axis,
            sizes=tuple(sizes), fast_b=int(fast_b),
            fast_sched=tuple(fast_sched), max_width=max_width, vcap=vcap,
        )


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "axis", "sizes", "fast_b", "fast_sched", "max_width", "vcap",
    ),
)
def _sharded_general_run(
    g, qp, *, mesh: Mesh, axis, sizes, fast_b, fast_sched, max_width, vcap
):
    # module-level jit: the cache must hit across serving dispatches (a
    # per-call closure would retrace + recompile the fused sharded
    # program for every general batch)
    from ketotpu.engine import algebra as alg

    def local(g, qp):
        g = jax.tree_util.tree_map(lambda a: a[0], g)
        # the body itself, as the fused wave calls it: under a nested jit
        # its operations would drop this scope from their names
        with jax.named_scope("tier/general"):
            codes, occ = alg._general_body(
                g, qp, sizes=sizes, fast_b=fast_b, fast_sched=fast_sched,
                max_width=max_width, vcap=vcap,
                shard=(axis, mesh.devices.size),
            )
        return codes, occ[None, :]

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P(axis), g), P()),
        out_specs=(P(), P(axis)),
        check_vma=False,
    )(g, qp)


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "axis", "n", "cap", "frontier", "arena", "max_width",
        "max_depth",
    ),
)
def _sharded_fast_run(
    g, q_ns, q_obj, q_rel, q_subj, q_depth, act, assign, *,
    mesh: Mesh, axis, n, cap, frontier, arena, max_width, max_depth
):
    # module-level jit: the per-call closure this replaces produced a new
    # function object each dispatch, retracing + recompiling the sharded
    # program on every wave — the root cause of the mesh engine's
    # always-cold serving behavior noted in PR 8
    def local(g, q_ns, q_obj, q_rel, q_subj, q_depth, act, assign):
        # P(axis) leaves a leading block dim of 1 on this shard's slice
        g = jax.tree_util.tree_map(lambda a: a[0], g)
        NS, R = g["f_direct_ok"].shape
        me = jax.lax.axis_index(axis)
        # root activation follows the host-provided assignment column —
        # the hash owner by default, a least-loaded replica for hot keys
        mine = assign == me
        with jax.named_scope("tier/fast"):
            s = fp._init_state(
                q_ns, q_obj, q_rel, q_subj, q_depth, act & mine,
                frontier=frontier,
            )
            for i in range(max_depth):
                with jax.named_scope(f"level{i}"):
                    children, q_found, q_over, q_dirty = fp.expand_phase(
                        g, s, arena=arena, max_width=max_width
                    )
                    # children always route to their HASH owner
                    # (replication copies rows, never moves them, so the
                    # owner has them)
                    children, q_over = _route(children, n, cap, q_over, axis)
                    # merge found bits across shards before packing so
                    # arrived children of already-found queries die
                    # immediately
                    q_found = _merge_any(q_found, axis)
                    # ns_dim/rel_dim unlock the linear hash-scatter dedup
                    # — the sort fallback was the dominant per-level cost
                    # on shards
                    nxt, q_over = fp.pack_phase(
                        children, q_found, q_over, frontier=frontier,
                        ns_dim=NS, rel_dim=R,
                    )
                    s = dict(nxt, q_found=q_found, q_over=q_over,
                             q_dirty=q_dirty, q_subj=s["q_subj"])
            q_found = _merge_any(s["q_found"], axis)
            q_over = _merge_any(s["q_over"], axis)
            # a dirty hit on ANY shard voids that query's device verdict
            # (unless found: found-bits are overlay-exact and monotone)
            q_dirty = _merge_any(s["q_dirty"], axis)
        return q_found, q_over, q_dirty

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            jax.tree_util.tree_map(lambda _: P(axis), g),
            P(), P(), P(), P(), P(), P(), P(),
        ),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )(g, q_ns, q_obj, q_rel, q_subj, q_depth, act, assign)


def sharded_check(
    stacked_g: Dict[str, np.ndarray],
    queries: Sequence[np.ndarray],
    mesh: Mesh,
    *,
    axis: str = "shard",
    frontier: int = 2048,
    arena: int = 8192,
    max_depth: int = 5,
    max_width: int = 100,
    active=None,
    assign=None,
) -> fp.FastResult:
    """Check a replicated query batch against the sharded graph.

    Queries are visible to every shard; each root item activates only on
    the shard named by its ``assign`` slot (the hash owner when ``assign``
    is None — replica routing passes an explicit column so hot keys can
    activate on a least-loaded replica instead).  Found/overflow bits are
    psum-merged every level so short-circuit masking works across shards.
    """
    n = mesh.devices.size
    q_ns, q_obj, q_rel, q_subj, q_depth = (
        jnp.asarray(a, jnp.int32) for a in queries
    )
    Q = q_ns.shape[0]
    act = (
        jnp.ones((Q,), bool) if active is None else jnp.asarray(active, bool)
    )
    if assign is None:
        assign = shard_of_np(
            np.clip(np.asarray(queries[0], np.int64), 0, None),
            np.clip(np.asarray(queries[1], np.int64), 0, None), n,
        )
    assign = jnp.asarray(assign, jnp.int32)
    cap = max(arena // max(n, 1), 8)

    with compilewatch.scope(
        "sharded_check",
        lambda: f"Q={Q} n={n} frontier={frontier} arena={arena}",
    ):
        found, over, dirty = _sharded_fast_run(
            stacked_g, q_ns, q_obj, q_rel, q_subj, q_depth, act, assign,
            mesh=mesh, axis=axis, n=n, cap=cap,
            frontier=frontier, arena=arena, max_width=max_width,
            max_depth=max_depth,
        )
    return fp.FastResult(found=found, over=over, dirty=dirty)
