"""MeshCheckEngine: the serving engine over a graph-sharded device mesh.

BASELINE config #5 behind the registry's engine seam: with
``engine.mesh_devices: n`` the registry builds this engine instead of the
single-device one.  The CSR is partitioned by (namespace, object) hash
across an n-device `jax.sharding.Mesh` (parallel/graphshard.py); each BFS
level expands locally, routes cross-shard subject-set / tuple-to-userset
children to their owner shard with `lax.all_to_all`, and merges verdict
bits with `psum` — per-device graph memory drops with mesh size instead
of replicating.

Inherits the single-device engine's whole host surface (encode, classify,
oracle fallback, expand, checkpointing of the base projection) and swaps
the fast-path dispatch.  Sharded differences:

* **writes ride per-shard delta overlays**: each change routes to its
  owner shard (same (ns, obj) hash as the partitioning) and folds into
  that shard's OverlayState against that shard's snapshot — node ids in
  overlay tables are shard-local, so one replicated overlay cannot work.
  EMPTY overlay tables ship with the base stacks so the shard_map
  program's pytree never changes shape when writes land; a write
  re-ships only the (small, fixed-shape) overlay stacks.  Probe verdicts
  stay overlay-exact; queries that touch a dirty CSR row on ANY shard
  come back ``dirty`` (psum-merged) and fall back to the host oracle.
* **overflow retries on-device** at ``retry_scale``x frontier/arena
  before falling back — same two-tier story as the single-chip engine.
* AND/NOT-reachable ("general") queries run the fused algebra program
  (engine/algebra.py) **against the sharded graph itself**
  (graphshard.sharded_general_check): every per-task read is owner-local
  under the (ns, obj) partitioning, classification merges ride psums,
  and pure-OR fast leaves take the same all_to_all-routed BFS as the
  fast path — per-device graph memory keeps scaling down with mesh
  size, and the tier is overlay-aware (per-shard dirty bits psum-merge).
  The host oracle is only the final fallback (overflow, errors, dirty
  rows).  A budget-bounded replicated copy remains ONLY for
  batch_expand, whose host-side tree reassembly reads global node ids.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

from ketotpu import compilewatch, deadline, faults, flightrec, profiler
from ketotpu.cache.hotspot import HotSpotSketch
from ketotpu.engine import delta as dl
from ketotpu.engine import wave as wv
from ketotpu.engine.tpu import DeviceCheckEngine
from ketotpu.engine.wave import _bucket
from ketotpu.parallel import graphshard, peerlink
from ketotpu.parallel.mesh import make_mesh

#: collectives over the host's ONE device backend cannot overlap even
#: across ENGINE INSTANCES (two in-process mesh engines — the multi-host
#: parity tests' topology — share the same CPU/TPU backend, and two
#: in-flight sharded programs interleave their all_to_all rendezvous and
#: starve each other), so the run lock is process-global, not per-engine
_MESH_RUN_LOCK = threading.Lock()

#: set while THIS thread is serving a peer's forwarded rows: the mesh
#: engine must answer those locally — re-forwarding a replica-routed row
#: to its hash owner would bounce between hosts forever
_LOCAL_SERVE = threading.local()

#: separator for the string-keyed cross-host root key (vocab ids are
#: per-process; only the strings mean the same thing on every host)
_KEY_SEP = "\x1f"


def _pack_keys(ns_ids: np.ndarray, obj_ids: np.ndarray) -> np.ndarray:
    """(ns, obj) id pairs packed into one int64 key (vectorized compare)."""
    return (
        np.clip(np.asarray(ns_ids, np.int64), 0, None) << 32
    ) | (np.clip(np.asarray(obj_ids, np.int64), 0, None) & 0xFFFFFFFF)


class MeshCheckEngine(DeviceCheckEngine):
    """Graph-sharded batched checks; oracle fallback on the host."""

    # sharded stacks have their own publish discipline: writes route to
    # per-shard overlays and the escape hatch stays the sharded rebuild —
    # no base-engine fold or background generation swap
    supports_fold = False
    supports_background_compaction = False
    # the mesh runs the base's cascade with its own launchers; the fused
    # wave has never run under shard_map, whatever the shared config says
    supports_fused = False

    def __init__(
        self,
        store,
        namespace_manager=None,
        *,
        mesh_devices: int,
        mesh_axis: str = "shard",
        replica_budget_mb: int = 8192,
        replicate_hot: bool = True,
        hot_min: int = 64,
        replica_max_keys: int = 32,
        rebalance_skew: float = 4.0,
        rebalance_interval_ms: float = 0.0,
        failover: bool = True,
        hostlink=None,
        **kwargs,
    ):
        super().__init__(store, namespace_manager, **kwargs)
        self.mesh = make_mesh(mesh_devices, axis=mesh_axis)
        if self.mesh.devices.size != mesh_devices:
            # make_mesh silently truncates to what exists; serving with
            # fewer devices than shards would DROP the missing shards'
            # tuples as silent denials
            raise ValueError(
                f"engine.mesh_devices={mesh_devices} but only "
                f"{self.mesh.devices.size} JAX devices are available"
            )
        self.mesh_axis = mesh_axis
        self.n_shards = mesh_devices
        self._stacked = None
        self._stacked_base = None
        self._shard_snaps: Optional[List] = None
        self._shard_overlays: Optional[List[dl.OverlayState]] = None
        # ceiling on the lazily-replicated full-graph copy that ONLY
        # batch_expand still uses (its host-side tree reassembly reads
        # global node ids): past this budget expand falls back to the
        # host oracle instead of silently materializing the whole graph
        # on one device.  The general (AND/NOT) tier runs against the
        # sharded stacks and never touches this.
        self.replica_budget_bytes = replica_budget_mb << 20
        # per-shard overlay table capacity; totals still bound by
        # max_overlay_pairs/max_overlay_dirty like the single-chip engine
        self.shard_pair_cap = max(self.max_overlay_pairs // mesh_devices, 256)
        # per-shard serving telemetry (shard_stats / registry gauges):
        # oracle fallbacks attributed to the query's owner shard, and the
        # last general dispatch's per-shard BFS occupancy partials
        self._shard_fallbacks = np.zeros(mesh_devices, np.int64)
        self._shard_gen_occ = np.zeros(mesh_devices)
        # per-shard Leopard closure segments (pair counts by owner set)
        self._leo_shard_pairs = np.zeros(mesh_devices, np.int64)
        self._leo_segments = None
        # -- production serving state (hot replication / rebalance /
        # failover) ----------------------------------------------------
        self.replicate_hot = bool(replicate_hot)
        self.hot_min = int(hot_min)
        self.replica_max_keys = int(replica_max_keys)
        self.rebalance_skew = float(rebalance_skew)
        self.rebalance_interval_ms = float(rebalance_interval_ms)
        self.failover_enabled = bool(failover)
        # count-min sketch over root (ns, obj) keys: the replication
        # controller's hot-key feed (same sketch the cache shield uses)
        self._hot = HotSpotSketch(top_k=max(self.replica_max_keys, 16))
        # (ns_id, obj_id) -> extra shards holding a COPY of the key's
        # rows; published only via the generation-swap in
        # _publish_replica_map, read lock-free on the dispatch path
        self._replica_map: Dict[Tuple[int, int], Tuple[int, ...]] = {}
        # per-shard routed-root counts: the skew signal, the least-loaded
        # replica choice, and the per-shard wave accounting feed
        self._shard_batches = np.zeros(mesh_devices, np.int64)
        self._shard_down = np.zeros(mesh_devices, bool)
        self.replica_routed = 0
        self.replications = 0
        self.rebalances = 0
        self.shard_recoveries = 0
        # collectives over ONE mesh cannot overlap: two in-flight
        # executions of the sharded program interleave their all_to_all
        # rendezvous on the host backend and starve each other, so every
        # device launch (and the shared routing counters) serializes on
        # the process-global run lock (see _MESH_RUN_LOCK)
        self._mesh_run_lock = _MESH_RUN_LOCK
        # -- multi-host topology (parallel/peerlink.py) ------------------
        # the host coordinate partitions SERVING RESPONSIBILITY for root
        # keys, not device memory: every host builds the full sharded
        # graph from the shared store, so any host's verdict for any key
        # is bit-identical — cross-host routing is a throughput/failover
        # decision, never a correctness one
        self.hostlink = hostlink
        self.host_id = hostlink.host_id if hostlink is not None else 0
        self.n_hosts = hostlink.n_hosts if hostlink is not None else 1
        # string-keyed hot sketch for MY owned roots: the cross-host
        # replication controller's feed (the shard-level sketch above
        # keys by per-process vocab ids, useless across hosts)
        self._peer_hot = HotSpotSketch(top_k=max(self.replica_max_keys, 16))
        # key -> remote hosts holding a SERVE-COPY: merged from every
        # owner's heartbeat-published plan plus my own; replaced
        # wholesale (atomic rebind), read lock-free on the dispatch path
        self._peer_replicas: Dict[str, Tuple[int, ...]] = {}
        self._peer_plans: Dict[int, Dict[str, Tuple[int, ...]]] = {}
        self._my_peer_plan: Dict[str, Tuple[int, ...]] = {}
        self._peer_batches = np.zeros(max(self.n_hosts, 1), np.int64)
        self._peer_fallbacks = np.zeros(max(self.n_hosts, 1), np.int64)
        self.peer_deadline_degrades = 0
        self.peer_host_down_events = 0
        self.peer_recover_events = 0
        if hostlink is not None:
            hostlink.attach_engine(self)
        self._rebal_stop = threading.Event()
        self._rebal_thread: Optional[threading.Thread] = None
        if self.rebalance_interval_ms > 0 and mesh_devices > 1:
            t = threading.Thread(
                target=self._rebal_worker, name="keto-mesh-rebalancer",
                daemon=True,
            )
            self._rebal_thread = t
            t.start()

    def _install_leopard(self) -> None:
        """Build the closure index, then partition its element pairs into
        per-shard segments by the OWNER SET's (ns, obj) hash — the same
        partitioning as the CSR, so a shard's segment answers exactly the
        queries whose object node it owns.  The segments replace the
        single replicated device copy: each holds only its shard's slice
        of the sorted pairs (sorting is preserved — the global order is
        by packed (set, element) key, and a subsequence of a sorted array
        is sorted), so per-device closure memory scales down with mesh
        size just like the graph itself."""
        super()._install_leopard()
        # the segments stand in for the replicated HBM copy; probes on the
        # mesh engine take the host searchsorted path (bit-identical)
        self._leo_device = None
        self._leo_segments = None
        self._leo_shard_pairs = np.zeros(self.n_shards, np.int64)
        idx = self._leopard
        if idx is None or len(idx.elt_set) == 0:
            return
        hi = idx.nodes[idx.elt_set.astype(np.int64)] >> 32
        ns = (hi // idx.R).astype(np.int64)
        obj = (idx.nodes[idx.elt_set.astype(np.int64)] & 0xFFFFFFFF)
        shards = graphshard.shard_of_np(ns, obj, self.n_shards)
        self._leo_shard_pairs = np.bincount(
            shards, minlength=self.n_shards
        ).astype(np.int64)
        self._leo_segments = [
            idx.elt_packed[shards == s] for s in range(self.n_shards)
        ]

    def _place(self, stacks):
        """Put host stacks (leading axis = shard) ON the mesh, one slice
        per device, once.  The jitted shard_map programs take them under
        the same sharding, so a dispatch uploads its query pack and
        nothing else — handed over as numpy, the whole graph would ride
        to the devices on every wave."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(
            stacks, NamedSharding(self.mesh, PartitionSpec(self.mesh_axis))
        )

    def _install_device_arrays(self) -> None:
        """Ship the SHARDED stacks (base + EMPTY overlays); the replicated
        copy (only batch_expand reads it) is built lazily so device 0
        doesn't hold the whole graph next to its shard."""
        self._base_device = None
        self._device_arrays = None
        self._expand_extra = None
        self._shard_snaps, stacked_base = (
            graphshard.build_sharded_snapshot(
                self.store, self.namespace_manager, self.n_shards,
                self._vocab, cols=self._cols,
                replicate=self._replica_map,
            )
        )
        self._stacked_base = self._place(stacked_base)
        # overlay admission checks relation-level pairs against dyn_pairs;
        # a shard's own slice sees only a subset of the graph's pairs, so
        # a write whose pair lives on other shards would spuriously
        # reject -> full reshard.  Taint classification runs on the
        # replicated snapshot anyway, so sharing the GLOBAL pair set is
        # exact and strictly reduces resharding.
        if self._snap is not None:
            for sn in self._shard_snaps:
                sn.dyn_pairs = self._snap.dyn_pairs
        self._shard_overlays = [
            dl.OverlayState() for _ in range(self.n_shards)
        ]
        self._stacked = dict(
            self._stacked_base, **self._overlay_stacks()
        )

    def _swap_shape_signature(self):
        """The mesh serves from the sharded STACKS — sign those across a
        generation swap, not the lazily-built replicated expand copy
        (which a rebuild nulls and would read as always-changed)."""
        return self._array_shapes(self._stacked)

    def _overlay_stacks(self):
        """Per-shard overlay arrays, padded to common shapes, stacked
        (leading axis = shard) and placed on the mesh.  Fixed shapes per
        rebuild: om_/ovt_ tables by ``shard_pair_cap``, ov_dirty by the
        max shard node count."""
        ovs = [
            dl.overlay_arrays(o, sn, pair_cap=self.shard_pair_cap)
            for o, sn in zip(self._shard_overlays, self._shard_snaps)
        ]
        out = {}
        for k in ovs[0]:
            arrs = [np.asarray(ov[k]) for ov in ovs]
            if arrs[0].ndim == 0:
                out[k] = np.stack(arrs)
                continue
            m = max(a.shape[0] for a in arrs)
            m = _bucket(m, 64) if k == "ov_dirty" else m
            arrs = [
                np.pad(a, [(0, m - a.shape[0])] + [(0, 0)] * (a.ndim - 1))
                for a in arrs
            ]
            out[k] = np.stack(arrs)
        return self._place(out)

    def _overlay_apply(self, changes) -> bool:
        """Route each change to its owner shard's overlay (the same
        (ns, obj) hash that partitioned the graph) and re-ship only the
        overlay stacks.  False => full rebuild (re-partition).

        The replicated overlay state (self._overlay) is mirrored first:
        batch_expand's host-side delta merge reads it against the
        replicated snapshot (expand_device.OverlayMembers), and its node
        ids are replicated-snapshot ids — the shard overlays' ids are
        shard-local and useless to expand."""
        if self._shard_snaps is None:
            return False
        try:
            dl.apply_changes(self._overlay, self._snap, self._vocab, changes)
        except dl.OverlayRejected:
            return False
        try:
            for op_, t in changes:
                ns = self._vocab.namespaces.lookup(t.namespace)
                obj = self._vocab.objects.lookup(t.object)
                if ns < 0 or obj < 0:
                    return False  # ids not even interned: rebuild
                s = int(graphshard.shard_of_np(
                    np.array([ns]), np.array([obj]), self.n_shards
                )[0])
                # replicated keys fold the change into EVERY copy's
                # overlay too — a replica serving the key's roots must
                # see the same write-visible verdicts as the hash owner
                targets = {s}
                targets.update(
                    self._replica_map.get((int(ns), int(obj)), ())
                )
                for tgt in targets:
                    dl.apply_changes(
                        self._shard_overlays[tgt], self._shard_snaps[tgt],
                        self._vocab, [(op_, t)],
                    )
        except dl.OverlayRejected:
            return False
        pairs = sum(o.size()[0] for o in self._shard_overlays)
        dirty = sum(o.size()[1] for o in self._shard_overlays)
        if pairs > self.max_overlay_pairs or dirty > self.max_overlay_dirty:
            return False
        if any(
            o.size()[0] > self.shard_pair_cap for o in self._shard_overlays
        ):
            return False  # one shard's fixed-shape table would overflow
        try:
            stacks = self._overlay_stacks()
        except ValueError:
            return False
        self._stacked = dict(self._stacked_base, **stacks)
        return True

    def _replica_arrays(self):
        """Bounded lazily-replicated Check arrays (+ overlay tables) for
        batch_expand only, or None when the full graph would exceed
        ``replica_budget_bytes`` — expand falls back to the oracle then."""
        if self._device_arrays is None:
            import jax

            est = sum(
                v.nbytes for v in self._snap.check_arrays().values()
            )
            if est > self.replica_budget_bytes:
                return None
            self._base_device = jax.device_put(self._snap.check_arrays())
            self._device_arrays = dict(
                self._base_device,
                **jax.device_put(
                    dl.overlay_arrays(
                        self._overlay, self._snap,
                        pair_cap=max(self.max_overlay_pairs, 1),
                    )
                ),
            )
        return self._device_arrays

    def _expand_arrays(self):
        if self._replica_arrays() is None:
            return None  # over budget: batch_expand goes to the oracle
        # the expand-only tables extend the bounded replica lazily,
        # exactly like the single-chip engine
        return super()._expand_arrays()

    def _run_locked(self, phase: str, run, **fields):
        """One sharded program, launched AND finished under the run lock:
        collectives over one mesh must not overlap (two in-flight sharded
        programs interleave their all_to_all rendezvous on the host
        backend and starve).  The wait for the lock is the engine phase
        ``check_mesh_lock_wait``, the program ``phase``: ``check_mesh_fast``
        or ``check_mesh_general`` on a wave's first pass, ``check_mesh_retry``
        (``boost=``) for the rows retried at ``retry_scale``."""
        import jax

        with self._span("check_mesh_lock_wait"):
            self._mesh_run_lock.acquire()
        try:
            with self._span(phase, **fields):
                out = run()
                jax.block_until_ready(out)
        finally:
            self._mesh_run_lock.release()
        return out

    def _served_arrays(self):
        return self._stacked

    @staticmethod
    def _ship_table(prefix: str, table):
        """The whole graph's tables stay on the host: what the mesh ships
        is the shards' stacks."""
        return table

    def _sizing_counts(self) -> dict:
        """One chip's share: every shard's arrays pad to the largest
        shard's shapes, so the largest counts size each chip."""
        snaps = self._shard_snaps
        return dict(
            tuples=max(sn.n_tuples for sn in snaps),
            nodes=max(sn.n_nodes for sn in snaps),
            edges=max(sn.n_edges for sn in snaps),
            subjects=max(len(sn.sub_ns) for sn in snaps),
            pair_cap=self.shard_pair_cap,
        )

    def _sync_view(self):
        """The base's atomic view, with the sharded stacks for device
        arrays.  The stamp is the DRAIN cursor where the base takes the
        served one: the mesh has no background compaction
        (``supports_background_compaction``), so every drain is served
        before the lock is released and the two are equal."""
        with self._sync_lock:
            snap = self._snapshot_locked()
            return snap, self._stacked, self._log_cursor

    def _run_fast(self, wave, padded, active, boost: int = 1, rows=None):
        """The sharded BFS for padded rows, finished under the run lock;
        returns (FastResult, no occupancy).  Launched whether or not a
        row is active."""
        assign = wave.assign if rows is None else wave.assign[rows]
        assign = np.pad(assign, (0, len(active) - len(assign)))
        res = self._run_locked(
            "check_mesh_fast" if boost == 1 else "check_mesh_retry",
            lambda: graphshard.sharded_check(
                wave.arrays,
                padded,
                self.mesh,
                axis=self.mesh_axis,
                frontier=boost * self.frontier,
                arena=boost * self.arena,
                max_depth=self.max_depth,
                max_width=self.max_width,
                active=active,
                assign=assign,
            ),
            rows=int(np.count_nonzero(active)), boost=boost,
        )
        return res, None

    def _general_program(self, stacked, qpack, rows, boost, **shapes):
        """One fused algebra dispatch over the SHARDED graph stacks for
        the general (AND/NOT) roots (graphshard.sharded_general_check,
        VERDICT r4 #5): no replicated graph copy — per-device graph
        memory keeps scaling down with mesh size; only the per-batch
        skeleton working set is replicated (GLOBAL shapes: the whole
        batch's skeleton lives on every shard).  Overlay-aware like the
        single-chip program: each shard's slice carries its own overlay
        tables, probes run owner-side, and dirty bits psum-merge."""
        return self._run_locked(
            "check_mesh_general" if boost == 1 else "check_mesh_retry",
            lambda: graphshard.sharded_general_check(
                stacked, qpack, self.mesh, axis=self.mesh_axis, **shapes,
            ),
            rows=rows, boost=boost,
        )

    def _general_occ(self, occ) -> np.ndarray:
        # occ rows: the skeleton level counts and fast_n ([0..D+1]) come
        # from the psum-merged levels — replicated GLOBAL values on every
        # shard (take one row, not the n-fold sum) — while the BFS
        # sub-run counts ([D+2:]) are owner-masked per-shard partials
        # whose sum is the true global
        rows = np.asarray(occ)
        split = self.gen_levels + 2
        self._shard_gen_occ = rows[:, split:].sum(axis=1).astype(float)
        return np.concatenate([rows[0, :split], rows[:, split:].sum(axis=0)])

    def _fast_bits(self, res, k: int) -> wv.FastBits:
        # copies: a retry's bits are written over the first pass's
        found, over = np.array(res.found)[:k], np.array(res.over)[:k]
        dirty = (
            np.array(res.dirty)[:k] if res.dirty is not None
            else np.zeros(k, bool)
        )
        return wv.FastBits(found, over, dirty)

    def _fetch_span(self, phase: str, **fields):
        # every sharded program is finished where it is launched, under
        # the run lock, and timed there (check_mesh_fast / _general /
        # _retry, check_mesh_lock_wait): collect waits for nothing and
        # opens no span of its own
        return profiler.null_span(phase, **fields)

    def _fast_retry_cap(self) -> int:
        # the wave's own cap, not the boosted frontier: a retry never
        # holds more rows than the wave that overflowed
        return self.frontier

    # -- routing / failover -------------------------------------------------

    def _route_assign(self, ns_ids, obj_ids):
        """Per-root serving-shard assignment.  Defaults to the (ns, obj)
        hash owner; roots of replicated hot keys go to the least-loaded
        live copy instead.  Returns (assign, owner) int32 arrays — owner
        is the hash shard (what child routing and fallback attribution
        use), assign is where the root actually activates."""
        n = self.n_shards
        ns = np.clip(np.asarray(ns_ids, np.int64), 0, None)
        obj = np.clip(np.asarray(obj_ids, np.int64), 0, None)
        owner = graphshard.shard_of_np(ns, obj, n)
        assign = owner.copy()
        rep = self._replica_map
        if rep:
            packed = _pack_keys(ns, obj)
            load = self._shard_batches.astype(np.int64)
            for (kns, kobj), extras in rep.items():
                key = (np.int64(kns) << 32) | (
                    np.int64(kobj) & 0xFFFFFFFF
                )
                m = packed == key
                if not m.any():
                    continue
                kowner = int(graphshard.shard_of_np(
                    np.array([kns]), np.array([kobj]), n
                )[0])
                cands = [
                    s for s in dict.fromkeys((kowner, *extras))
                    if not self._shard_down[s]
                ]
                if not cands:
                    continue  # every copy down: stays owner -> oracle
                best = min(cands, key=lambda s: int(load[s]))
                if best != kowner:
                    self.replica_routed += int(m.sum())
                assign[m] = best
        return assign, owner

    def _poll_shard_faults(self) -> None:
        """Advance per-shard up/down state from the fault plan: a rolled
        shard fault marks the shard down (it degrades to replicas / the
        host oracle — the wave keeps serving); a shard the plan stopped
        targeting recovers on the next dispatch."""
        if not self.failover_enabled:
            return
        for s in range(self.n_shards):
            if self._shard_down[s]:
                if not faults.shard_faulted(s):
                    self._recover_shard(s)
            elif faults.shard_down(s):
                self._shard_down[s] = True
                self._device_failure(f"mesh shard {s}")

    def _recover_shard(self, s: int) -> None:
        """Bring a faulted shard back: re-ship its segments (the whole
        stacked view refreshes — the per-shard slices are one device_put
        away) and zero its fallback attribution so recovery is observable
        as `keto_mesh_shard_fallbacks{shard=s}` returning to zero."""
        with self._sync_lock:
            if not self._shard_down[s]:
                return
            self._shard_down[s] = False
            if self._stacked_base is not None:
                self._stacked = dict(
                    self._stacked_base, **self._overlay_stacks()
                )
            self._shard_fallbacks[s] = 0
            self.shard_recoveries += 1

    # -- cross-host routing / serving (parallel/peerlink.py) ----------------

    @staticmethod
    def _query_key_cols(queries):
        """(namespace, object) STRING columns for a wave — the cross-host
        coordinate hashes strings, never per-process vocab ids."""
        if hasattr(queries, "encode_for"):
            return queries.ns, queries.obj
        return (
            [q.namespace for q in queries],
            [q.object for q in queries],
        )

    def _route_hosts(self, queries, cand_mask, rest_depth: int):
        """Split a wave by serving host.  Each row's serve-set is its
        owner host plus any heartbeat-published replica hosts; the
        least-loaded LIVE member serves it.  Rows landing on a peer batch
        into one framed round trip per peer (fired here, joined in
        _after_collect); rows with every copy down — and every cross-host row
        of a wave whose deadline budget is already spent — degrade to the
        oracle instead of blocking the wave."""
        cand = np.flatnonzero(cand_mask)
        if not len(cand):
            return None
        link = self.hostlink
        n = cand_mask.shape[0]
        ns_s, obj_s = self._query_key_cols(queries)
        owner_host = np.fromiter(
            (
                peerlink.host_of(ns_s[i], obj_s[i], self.n_hosts)
                for i in cand
            ),
            np.int32, count=len(cand),
        )
        rep = self._peer_replicas
        if self.replicate_hot:
            mine = cand[owner_host == self.host_id]
            if len(mine):
                self._peer_hot.observe_many(
                    [ns_s[i] + _KEY_SEP + obj_s[i] for i in mine]
                )
        loads = {
            h: (
                float(self._shard_batches.sum()) if h == self.host_id
                else link.peer_load(h)
            )
            for h in range(self.n_hosts)
        }
        downs = {
            h: (False if h == self.host_id else link.peer_down(h))
            for h in range(self.n_hosts)
        }
        sent = np.zeros(n, bool)
        lost = np.zeros(n, bool)
        send: Dict[int, list] = {}
        for pos in range(len(cand)):
            i = int(cand[pos])
            own = int(owner_host[pos])
            extras = rep.get(ns_s[i] + _KEY_SEP + obj_s[i]) if rep else None
            if own == self.host_id and not extras:
                continue  # the common case: I own it, nobody else serves it
            live = [
                h for h in dict.fromkeys((own, *(extras or ())))
                if not downs.get(h, True)
            ]
            if not live:
                # whole serve-set down: this row rides the existing
                # err-mask to the host oracle, attributed to the owner
                lost[i] = True
                self._peer_fallbacks[own] += 1
                continue
            serve = min(live, key=lambda h: loads[h])
            if serve == self.host_id:
                continue
            send.setdefault(serve, []).append(i)
            sent[i] = True
        if not sent.any() and not lost.any():
            return None
        rem = deadline.remaining()
        if rem is not None and rem <= 0 and sent.any():
            # budget already spent: shipping would only return expired —
            # degrade this wave's cross-host rows to the oracle now
            self.peer_deadline_degrades += int(sent.sum())
            for hid, idx in send.items():
                self._peer_fallbacks[hid] += len(idx)
            lost |= sent
            sent = np.zeros(n, bool)
            send = {}
        timeout_s = link.rpc_timeout_s if rem is None else min(
            rem, link.rpc_timeout_s
        )
        pend = {}
        for hid, idx in send.items():
            rows = [queries[i] for i in idx]
            pend[hid] = (
                np.asarray(idx, np.int64),
                link.check_rows_async(hid, rows, rest_depth, timeout_s),
                timeout_s,
            )
            self._peer_batches[hid] += len(idx)
        return {"sent": sent, "lost": lost, "pend": pend}

    def _peer_serve_check(self, rows, rest_depth: int) -> np.ndarray:
        """Answer a peer's forwarded rows from the LOCAL cascade.  The
        local-serve scope pins the whole sub-wave to this host: a
        replica-routed row re-hashed here would forward straight back to
        its owner and bounce forever."""
        prev = getattr(_LOCAL_SERVE, "serving", False)
        _LOCAL_SERVE.serving = True
        try:
            return np.asarray(
                self.batch_check(rows, rest_depth=rest_depth), bool
            )
        finally:
            _LOCAL_SERVE.serving = prev

    def _hb_payload(self) -> dict:
        """What this host publishes on every heartbeat: its load (the
        peers' least-loaded-copy routing signal), shard count, drained
        cursor, and its hot-key replica plan — the consensus-free
        controller's whole protocol rides the heartbeat."""
        plan = self.plan_peer_replicas() if self.replicate_hot else {}
        return {
            "load": float(self._shard_batches.sum()),
            "shards": int(self.n_shards),
            "cursor": int(self._log_cursor),
            "replicas": {k: list(v) for k, v in plan.items()},
        }

    def plan_peer_replicas(self) -> Dict[str, Tuple[int, ...]]:
        """The cross-host replica plan for MY owned hot keys: existing
        placements stick (stability), new hot keys get one copy on the
        least-loaded live remote host.  Copy-never-move like the shard
        controller: every host serves from its own full graph, so a
        serve-copy is a routing fact, not a data move — verdicts stay
        bit-identical wherever a row lands."""
        link = self.hostlink
        if link is None or self.n_hosts < 2:
            return {}
        remote = [h for h in link.live_hosts() if h != self.host_id]
        out: Dict[str, Tuple[int, ...]] = {}
        if remote:
            for key, est in self._peer_hot.top():
                if est < self.hot_min or not isinstance(key, str):
                    continue
                if len(out) >= self.replica_max_keys:
                    break
                kept = tuple(
                    h for h in self._my_peer_plan.get(key, ())
                    if h in remote
                )
                out[key] = kept or (
                    min(remote, key=lambda h: link.peer_load(h)),
                )
        self._my_peer_plan = out
        self._rebuild_peer_replicas()
        return out

    def _merge_peer_replicas(self, hid: int, mapping) -> None:
        """Absorb a peer's heartbeat-published replica plan."""
        self._peer_plans[int(hid)] = {
            str(k): tuple(int(h) for h in v)
            for k, v in (mapping or {}).items()
        }
        self._rebuild_peer_replicas()

    def _rebuild_peer_replicas(self) -> None:
        merged: Dict[str, Tuple[int, ...]] = {}
        for plan in (*self._peer_plans.values(), self._my_peer_plan):
            for k, hosts in plan.items():
                merged[k] = tuple(
                    dict.fromkeys(merged.get(k, ()) + tuple(hosts))
                )
        self._peer_replicas = merged  # atomic rebind: lock-free readers

    def _on_peer_down(self, hid: int) -> None:
        """Heartbeat loss marked a whole peer down: every shard it owns
        is down at once.  Routing reads liveness from the hostlink on
        every wave, so there is nothing to re-ship — the next wave's fast
        roots already reroute to live replicas and the rest degrades to
        the oracle via the err-mask."""
        self.peer_host_down_events += 1

    def _on_peer_up(self, hid: int) -> None:
        """A peer answered again after being down: its owned keys route
        back to it on the next wave (warm rejoin — the peer re-ships its
        own stacks from the shared store before answering)."""
        self.peer_recover_events += 1

    def peer_route_counts(self) -> np.ndarray:
        """Cumulative rows shipped per peer host (the coalescer diffs
        consecutive reads for the wave ledger's per-peer accounting)."""
        return self._peer_batches.copy()

    def mesh_bootstrap(self, hid: int) -> None:
        """Warm-join via segment ship: adopt the peer's projected base
        snapshot (checkpoint codec arrays over the DCN lane) instead of
        re-projecting the store.  Shape-signature gating in the adopt
        path keeps a rejoin at matching shapes free of XLA recompiles."""
        if self.hostlink is None:
            raise RuntimeError("no hostlink attached")
        snap, cursor = self.hostlink.bootstrap_from(int(hid))
        self.adopt_snapshot(snap, cursor=cursor)

    def _route(self, queries, rest_depth, wave, active):
        """What a mesh adds between the cache consult and the launch:
        rows a peer host serves leave the wave, rows on a down shard go
        to the oracle, and every remaining row gets its serving shard."""
        enc = wave.enc
        # cross-host routing BEFORE the shard-level machinery: rows whose
        # serving host is a peer leave the local wave entirely (one framed
        # round trip per peer, launched now so the DCN exchange overlaps
        # the local device run; joined last in _after_collect).  Rows with
        # no live serving host degrade to the oracle via the err-mask.
        if (self.hostlink is not None and self.n_hosts > 1
                and not getattr(_LOCAL_SERVE, "serving", False)):
            wave.peers = self._route_hosts(
                queries, active | wave.general, rest_depth)
            if wave.peers is not None:
                gone = wave.peers["sent"] | wave.peers["lost"]
                active = active & ~gone
                wave.general = wave.general & ~gone
                wave.err = wave.err | gone
        self._poll_shard_faults()
        wave.assign, owner = self._route_assign(enc[0], enc[1])
        if self._shard_down.any():
            # roots whose serving shard is down and that no live replica
            # can absorb degrade to the host oracle; the wave itself keeps
            # serving (general roots activate by hash owner on-device, so
            # a down owner sends them to the oracle too)
            down_fast = active & self._shard_down[wave.assign]
            down_gen = wave.general & self._shard_down[owner]
            active = active & ~down_fast
            wave.general = wave.general & ~down_gen
            wave.err = wave.err | down_fast | down_gen
        if self.replicate_hot and active.any():
            live = np.flatnonzero(active)
            self._hot.observe_many(list(zip(
                np.clip(np.asarray(enc[0])[live], 0, None).tolist(),
                np.clip(np.asarray(enc[1])[live], 0, None).tolist(),
            )))
        # per-shard routed-root accounting: the skew/rebalance signal and
        # the wave ledger's per-shard deltas
        with self._mesh_run_lock:
            np.add.at(self._shard_batches, wave.assign[active], 1)
            if wave.general.any():
                np.add.at(self._shard_batches, owner[wave.general], 1)
        return active

    def _launch(self, wave, padded, active) -> None:
        # both synchronous launches of a wave, and the waits for the run
        # lock between them, as one phase
        with self._span("check_mesh_dispatch", rows=wave.n):
            super()._launch(wave, padded, active)

    def _note_fast_tiers(self, mask, wave) -> None:
        # split the fast-path attribution by serving shard so a divergence
        # record names the exact replica that answered
        assign = wave.assign
        for s in np.unique(assign[mask]):
            flightrec.note_tier(
                f"mesh-shard-{int(s)}", int((assign[mask] == s).sum())
            )

    def _after_collect(self, wave, allowed, fallback) -> None:
        # join the cross-host exchanges LAST and with no lock held: the
        # local device work (including retries) above overlapped the DCN
        # round trips, and a peer serving OUR rows may itself be waiting
        # for this host's run lock
        peer_attr = None
        if wave.peers is not None:
            peer_attr = wave.peers["sent"] | wave.peers["lost"]
            for hid, (idx, pending, tmo) in wave.peers["pend"].items():
                ok = pending.wait(tmo)
                if ok is not None:
                    allowed[idx] = ok
                    fallback[idx] = False
                    if pending.spans:
                        # the peer recorded under OUR trace id and shipped
                        # its host-stamped timeline back with the verdicts
                        # — adopt it into this request's open span buffer
                        # (no-op when no ctx is open, e.g. wave threads)
                        flightrec.merge_spans(pending.spans)
                    continue
                # the peer never answered inside the budget: those rows
                # ride the oracle.  A clean timeout is deadline
                # semantics; an error is the peer dying mid-wave.
                if pending.error is None:
                    self.peer_deadline_degrades += len(idx)
                self._peer_fallbacks[hid] += len(idx)
                fallback[idx] = True
        # peer-degraded rows are attributed per-PEER, not to the local
        # owner shards: a dead host must not smear fallback counts over
        # this host's (healthy) shard gauges
        fb = np.flatnonzero(
            fallback & ~peer_attr if peer_attr is not None else fallback
        )
        if len(fb):
            # attribute each oracle fallback to the query's owner shard
            # (the same (ns, obj) hash that partitioned the graph); err
            # queries may carry -1 ids — clip, the attribution is
            # advisory telemetry, not a routing decision
            shards = graphshard.shard_of_np(
                np.clip(wave.enc[0][fb], 0, None),
                np.clip(wave.enc[1][fb], 0, None),
                self.n_shards,
            )
            np.add.at(self._shard_fallbacks, shards, 1)

    def consistency_cursors(self) -> tuple:
        """Per-shard drained-cursor vector for the freshness barrier and
        the shard field of minted snaptokens.  Today the mesh drains the
        shared changelog in lockstep (one ``changes_since`` call routes
        deltas to every shard overlay inside the same ``_sync_lock``
        section), so all entries are equal — but the vector is the
        wire/API contract that lets a future per-shard drain diverge
        without changing any caller."""
        with self._sync_lock:
            return (self._log_cursor,) * self.n_shards

    # -- hot-shard replication + skew rebalancing ---------------------------

    def hot_keys(self) -> List[Tuple[Tuple[int, int], int]]:
        """Hottest (ns_id, obj_id) root keys from the count-min sketch,
        hottest first, thresholded at ``hot_min`` estimated observations
        and capped at ``replica_max_keys``."""
        out = [
            (key, est) for key, est in self._hot.top()
            if est >= self.hot_min and isinstance(key, tuple)
        ]
        return out[: self.replica_max_keys]

    def shard_skew(self) -> float:
        """max/mean routed-root load ratio — the rebalance trigger."""
        b = self._shard_batches.astype(float)
        mean = float(b.mean())
        return float(b.max() / mean) if mean > 0 else 1.0

    def plan_replicas(self) -> Dict[Tuple[int, int], Tuple[int, ...]]:
        """The replica map the controller would publish now: each hot key
        keeps its existing copies (stability — no oscillation between
        equally-loaded shards) and new hot keys get one copy on the
        least-loaded live non-owner shard."""
        n = self.n_shards
        load = self._shard_batches.astype(np.int64)
        new_map: Dict[Tuple[int, int], Tuple[int, ...]] = {}
        for key, _est in self.hot_keys():
            prev = self._replica_map.get(key)
            if prev:
                new_map[key] = prev
                continue
            owner = int(graphshard.shard_of_np(
                np.array([key[0]]), np.array([key[1]]), n
            )[0])
            others = [
                s for s in range(n)
                if s != owner and not self._shard_down[s]
            ]
            if not others:
                continue
            new_map[key] = (min(others, key=lambda s: int(load[s])),)
        return new_map

    def replicate_now(self) -> int:
        """Synchronously publish replicas for the current hot set.
        Returns the number of newly replicated keys (0 = nothing hot, no
        change, or the publish lost a race with a write)."""
        if not self.replicate_hot or self.n_shards < 2:
            return 0
        new_map = self.plan_replicas()
        fresh = [k for k in new_map if k not in self._replica_map]
        if not fresh or not self._publish_replica_map(new_map):
            return 0
        self.replications += len(fresh)
        return len(fresh)

    def rebalance_now(self) -> bool:
        """Skew-triggered repartition: when the routed-root skew crosses
        ``rebalance_skew``, copy the hottest keys OWNED by the loaded
        shard onto the least-loaded live shard and publish the new
        sharding via generation pointer swap (zero verdict divergence:
        replicas are copies, child routing stays by hash)."""
        if self.n_shards < 2 or self.shard_skew() < self.rebalance_skew:
            return False
        b = self._shard_batches.astype(np.int64)
        hot_shard = int(b.argmax())
        cold = [
            int(s) for s in np.argsort(b)
            if int(s) != hot_shard and not self._shard_down[int(s)]
        ]
        if not cold:
            return False
        new_map = dict(self._replica_map)
        moved = 0
        for key, _est in self.hot_keys():
            owner = int(graphshard.shard_of_np(
                np.array([key[0]]), np.array([key[1]]), self.n_shards
            )[0])
            if owner != hot_shard or cold[0] in new_map.get(key, ()):
                continue
            if len(new_map) >= self.replica_max_keys and key not in new_map:
                break
            new_map[key] = tuple(new_map.get(key, ())) + (cold[0],)
            moved += 1
        if not moved or not self._publish_replica_map(new_map):
            return False
        self.rebalances += 1
        return True

    def _publish_replica_map(self, new_map) -> bool:
        """Generation-swapped replica publish, modeled on the off-path
        compactor: pin the column mirror under the sync lock, build the
        re-replicated sharded snapshot OFF the lock (checks keep serving
        the old sharding), then swap pointers under the lock only if no
        write raced the build.  Same-shape swaps (the common case — the
        replica copies pad into the existing max-shard shapes) keep the
        compile observatory warm."""
        with self._sync_lock:
            self._snapshot_locked()  # drain the changelog first
            if self._cols is None or self._shard_snaps is None:
                return False
            frozen = self._cols.freeze()
            token = self._gen_token
            pin_cursor = self._log_cursor
            vocab = self._vocab
        snaps, stacked_base = graphshard.build_sharded_snapshot(
            self.store, self.namespace_manager, self.n_shards, vocab,
            cols=frozen, replicate=new_map,
        )
        stacked_base = self._place(stacked_base)
        with self._sync_lock:
            if token != self._gen_token or pin_cursor != self._log_cursor:
                return False  # a write landed mid-build: next tick retries
            old_sig = self._swap_shape_signature()
            if self._snap is not None:
                # overlay admission reads the GLOBAL pair set (see
                # _install_device_arrays)
                for sn in snaps:
                    sn.dyn_pairs = self._snap.dyn_pairs
            self._shard_snaps = snaps
            self._stacked_base = stacked_base
            # the rebuilt partitions already include every drained delta,
            # so the per-shard overlays restart empty; the replicated
            # overlay/_snap pair (expand + admission) is untouched
            self._shard_overlays = [
                dl.OverlayState() for _ in range(self.n_shards)
            ]
            self._stacked = dict(stacked_base, **self._overlay_stacks())
            self._replica_map = dict(new_map)
            self.generation += 1
            new_sig = self._swap_shape_signature()
            if old_sig is None or new_sig != old_sig:
                self._gen_sched_cache.clear()
                self._clean_dispatches = 0
                compilewatch.get().declare_cold(
                    "replica publish: stacked shapes changed"
                )
            return True

    def _rebal_worker(self) -> None:
        interval = max(self.rebalance_interval_ms, 1.0) / 1000.0
        while not self._rebal_stop.wait(interval):
            try:
                if not self.rebalance_now() and self.replicate_hot:
                    self.replicate_now()
            except Exception:  # noqa: BLE001 - serving view must stay intact
                self.compaction_errors += 1

    def close(self) -> None:
        if self.hostlink is not None:
            self.hostlink.stop()
        self._rebal_stop.set()
        t = self._rebal_thread
        if t is not None and t.is_alive():
            t.join(timeout=5.0)
        super().close()

    def shard_route_counts(self) -> np.ndarray:
        """Cumulative per-shard routed-root counts (the coalescer diffs
        consecutive reads for the wave ledger's per-shard accounting)."""
        return self._shard_batches.copy()

    def mesh_stats(self) -> dict:
        """Engine-level replication / rebalance / failover counters for
        the registry's mesh gauges."""
        out = {
            "replica_keys": len(self._replica_map),
            "replica_routed": int(self.replica_routed),
            "replications": int(self.replications),
            "rebalances": int(self.rebalances),
            "shard_recoveries": int(self.shard_recoveries),
            "shards_down": int(self._shard_down.sum()),
            "skew": round(self.shard_skew(), 3),
        }
        link = self.hostlink
        if link is not None:
            out.update({
                "host_id": int(self.host_id),
                "n_hosts": int(self.n_hosts),
                "hosts_down": sum(
                    1 for h in range(self.n_hosts)
                    if h != self.host_id and link.peer_down(h)
                ),
                "peer_routed": int(self._peer_batches.sum()),
                "peer_fallbacks": int(self._peer_fallbacks.sum()),
                "peer_deadline_degrades": int(self.peer_deadline_degrades),
                "peer_replica_keys": len(self._peer_replicas),
                "peer_recoveries": int(link.peer_recoveries),
                "peer_frontier_rtt_p50_ms": link.frontier_rtt_p50_ms(),
            })
        return out

    def peer_stats(self) -> List[dict]:
        """Per-peer rows (id, liveness, heartbeat age, load, frontier
        round trips, shipped rows, peer-degraded fallbacks) for
        ``/debug/mesh`` and the registry's peer gauges."""
        link = self.hostlink
        if link is None:
            return []
        rows = link.peer_rows()
        for r in rows:
            hid = r["peer"]
            r["routed"] = int(self._peer_batches[hid])
            r["fallbacks"] = int(self._peer_fallbacks[hid])
        return rows

    def shard_stats(self) -> List[dict]:
        """Per-shard serving counters for the registry's mesh gauges and
        `cli.py status`: overlay pressure, graph size, last general
        dispatch's BFS occupancy partial, and cumulative oracle
        fallbacks attributed by owner shard."""
        ovs = self._shard_overlays or []
        snaps = self._shard_snaps or []
        replica_keys = np.zeros(self.n_shards, np.int64)
        for extras in self._replica_map.values():
            for s in extras:
                replica_keys[int(s)] += 1
        out = []
        for i in range(self.n_shards):
            pairs, dirty = ovs[i].size() if i < len(ovs) else (0, 0)
            nodes = (
                int(getattr(snaps[i], "n_nodes", 0)) if i < len(snaps) else 0
            )
            out.append({
                "shard": i,
                "batches": int(self._shard_batches[i]),
                "fallbacks": int(self._shard_fallbacks[i]),
                "replica_keys": int(replica_keys[i]),
                "down": bool(self._shard_down[i]),
                "overlay_pairs": int(pairs),
                "overlay_dirty": int(dirty),
                "nodes": nodes,
                "gen_occupancy": float(self._shard_gen_occ[i]),
                "leopard_pairs": int(self._leo_shard_pairs[i])
                if i < len(self._leo_shard_pairs) else 0,
            })
        return out
