"""The TPU check engine: host wrapper around the batched device interpreters.

Plays the role of the reference's `check.Engine` (`internal/check/engine.go:
65-95`) behind the same provider seam: callers hand it relation tuples, it
answers allow/deny.  Internally it

1. projects the tuple store into a device snapshot — cached by
   (store version, namespace-config fingerprint) so an OPL hot-reload
   invalidates device state just like a tuple write,
2. interns query strings to dense ids (unknown strings miss everywhere, which
   reproduces "unknown namespace => not allowed", check/handler.go:169-171),
3. routes each query by a per-(namespace, relation) static classification:

   * **fast path** (`fastpath.run_fast`) — pure-OR rewrite closure:
     depth-bounded reachability with a monotone found-bit, `max_depth`
     async device steps, no host syncs;
   * **general path** (`algebra.run_general_packed`) — relations that can
     reach AND / NOT: one fused leveled program that builds the algebra
     skeleton, delegates every pure-OR subtree to the fast path's BFS,
     and resolves combiners bottom-up (three-valued semantics);
   * **host path** — queries whose top-level lookup is a client error
     (namespace/definitions.go:61): the oracle raises the reference's
     exact typed error;

4. retries fast-path queries that overflowed the lean tier-1 capacity
   schedule on the device at ``retry_scale``x wider caps (the overflow
   tail is a few % of a batch, so the fat retry batch is small), and only
   then falls back to the sequential oracle (remaining overflow, or an
   error verdict the oracle must reproduce as a typed exception).

Chunks of a large batch are dispatched asynchronously back-to-back and
collected afterwards, so device execution and the host's result reads
overlap across chunks instead of paying one blocking sync per chunk.

`check()` is the single-query API; `batch_check()` is the throughput surface
(the BatchCheck of BASELINE config #4 — the reference has no batch RPC at
this version, SURVEY §2 proto row).
"""

from __future__ import annotations

import hashlib
import logging
import os
import sys
import threading
import time
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from ketotpu import compilewatch, deadline, faults, flightrec, profiler
from ketotpu.api.types import (
    DeadlineExceededError,
    KetoAPIError,
    RelationTuple,
)
from ketotpu.cache import check_key as cache_check_key
from ketotpu.engine import algebra as alg
from ketotpu.engine import delta as dl
from ketotpu.engine import fastpath as fp
from ketotpu.engine import fused as fdx
from ketotpu.engine import hashtab
from ketotpu.engine import wave as wv
from ketotpu.engine.oracle import (
    DEFAULT_MAX_DEPTH,
    DEFAULT_MAX_WIDTH,
    CheckEngine,
)
from ketotpu.engine.snapshot import Snapshot
from ketotpu.engine.vocab import Vocab
from ketotpu.engine.wave import Ticket, Wave, _bucket, _bucket15
from ketotpu.leopard import closure as leo
from ketotpu.leopard import device as leodev
from ketotpu.leopard import hostlist as leolist
from ketotpu.storage.memory import InMemoryTupleStore
from ketotpu.storage.namespaces import NamespaceManager

_log = logging.getLogger("ketotpu.engine")


#: per-level task multipliers (units of general roots) for the algebra
#: skeleton: level 1 holds the rewrite roots plus root expansion edges,
#: the prog structure fans out over the next few levels, then tainted
#: recursion thins out (pure subtrees leave the skeleton as fast leaves)
_GEN_MULT_HEAD = (3, 4, 4, 4, 3, 3, 2, 2, 2, 2)


def _gen_mults(d: int):
    return tuple(
        _GEN_MULT_HEAD[i] if i < len(_GEN_MULT_HEAD) else 1 for i in range(d)
    )




def config_fingerprint(manager: Optional[NamespaceManager]) -> int:
    """Cheap namespace-config identity for snapshot caching.

    Calling ``namespaces()`` first gives file-backed managers their reload
    window (storage/namespaces.py), then the AST reprs pin the content —
    so a hot-reloaded OPL file rebuilds the snapshot even when the tuple
    store version did not move.
    """
    if manager is None:
        return 0
    # stable across processes (unlike hash(), which is seed-randomized):
    # checkpoint resume compares fingerprints across server restarts
    digest = hashlib.sha256()
    for ns in manager.namespaces():
        digest.update(repr(ns).encode())
        digest.update(b"\x00")
    return int.from_bytes(digest.digest()[:8], "big", signed=True)


class DeviceCheckEngine:
    """Batched permission checks on the device, oracle fallback on the host."""

    # the mesh engine opts out of all three: its device state is per-shard
    # stacks with their own publish discipline and their own launchers
    supports_fold = True
    supports_background_compaction = True
    supports_fused = True

    def __init__(
        self,
        store: InMemoryTupleStore,
        namespace_manager: Optional[NamespaceManager] = None,
        *,
        max_depth: int = DEFAULT_MAX_DEPTH,
        max_width: int = DEFAULT_MAX_WIDTH,
        strict_mode: bool = False,
        frontier: int = 4096,
        arena: int = 8192,
        cap: int = 8192,
        gen_arena: int = 8192,
        vcap: int = 4096,
        max_batch: int = 8192,
        retry_scale: int = 4,
        gen_levels: int = 12,
        gen_levels_max: int = 24,
        fused_dispatch: bool = False,
        fused_retry_lanes: int = 1,
        metrics=None,
        leopard: Optional[dict] = None,
        result_cache=None,
        compaction: Optional[dict] = None,
    ):
        self.store = store
        self.namespace_manager = namespace_manager
        self.max_depth = max_depth
        self.max_width = max_width
        self.strict_mode = strict_mode
        self.frontier = frontier
        self.arena = arena
        self.cap = cap  # general-path task capacity
        self.gen_arena = gen_arena
        self.vcap = vcap
        self.gen_levels = gen_levels
        self.gen_levels_max = gen_levels_max
        self.max_batch = min(max_batch, frontier)
        self.oracle = CheckEngine(
            store,
            namespace_manager,
            max_depth=max_depth,
            max_width=max_width,
            strict_mode=strict_mode,
        )
        # guards every snapshot-state mutation (change-log drain, column
        # mirror, overlay, device-array swap): the daemon calls
        # batch_check/batch_expand from many threads, and two threads
        # draining changes_since with the same cursor would double-apply
        # deltas (a delete then leaves a net-positive overlay entry —
        # revoked permissions keep answering allowed).  Device dispatch
        # and collection stay outside the lock.
        self._sync_lock = threading.RLock()
        self._vocab = Vocab()
        self._snap: Optional[Snapshot] = None
        self._snap_fingerprint: Optional[int] = None
        self._device_arrays = None
        self._cols: Optional[dl.TupleColumns] = None
        self._log_cursor = 0
        self._overlay: Optional[dl.OverlayState] = None
        self._overlay_active = False
        self.max_overlay_pairs = 4096
        self.max_overlay_dirty = 512
        self.retry_scale = retry_scale
        # demand-adaptive level scheduling: EMA of the fused program's
        # per-level frontier occupancy (units of active roots).  None until
        # the first batch reports; dispatches then size per-level buffers
        # to measured demand x headroom instead of the worst case —
        # per-level device cost scales with buffer sizes, and the retry
        # tier catches any underestimate (monotone over bits).
        self._occ_ema: Optional[np.ndarray] = None
        # general-path (algebra) occupancy EMAs: skeleton per-level tasks
        # per root, fast leaves per root, BFS sub-run per-level occupancy
        self._gen_occ_ema: Optional[np.ndarray] = None
        self._gen_fast_ema: Optional[float] = None
        self._gen_fast_occ_ema: Optional[np.ndarray] = None
        self._gen_sched_cache: dict = {}
        # guards the schedule cache + gen EMAs: two serving threads racing
        # _gen_schedule before the freeze landed would mint two distinct
        # fused programs (each a multi-minute compile for the chip)
        self._gen_lock = threading.Lock()
        # measured batch-to-batch occupancy variance on the synth workloads
        # is a few %; underestimates cost one retry dispatch for the
        # overflow tail, so a tight margin wins
        self.occ_headroom = 1.15
        # fused tiered dispatch (engine/fused.py): the whole wave cascade
        # (leopard probe -> fast BFS -> general algebra, with in-program
        # retry lanes) is ONE device program with ONE D2H fetch.  The
        # unfused cascade stays (flag off, mesh engine, diagnostic
        # surfaces).  The SERVING default is ON (engine.fused_dispatch,
        # spec/config.schema.json, wired by the registry); the constructor
        # default stays off: directly-built engines (tests, tooling) keep
        # the per-tier programs, which compile several times faster.
        self.fused_dispatch = bool(fused_dispatch)
        self.fused_retry_lanes = max(int(fused_retry_lanes), 0)
        self.fused_waves = 0  # observability: fused waves collected
        self.fused_d2h_fetches = 0  # observability: D2H fetches (1/wave)
        # rows of fused waves that needed the general tier, and the lanes
        # the program ran it at: their quotient is how full the tier ran
        self.fused_general_rows = 0
        self.fused_general_lanes = 0
        # element gathers one lookup of each served table cost the fused
        # waves, a wave's tables' ``lookup_gathers`` added at its collect
        # (over fused_waves: gathers a lookup, as the waves met them)
        self.fused_probe_gathers = dict.fromkeys(hashtab.TABLES, 0)
        # per-tier row attribution for fused waves, from the returned
        # masks (keto_fused_tier_rows_total; wave-ledger tier deltas)
        self.fused_tier_rows = {
            "cache": 0, "leopard": 0, "fastpath": 0, "general": 0,
            "oracle": 0,
        }
        self.fallbacks = 0  # observability: host-fallback counter
        self.retries = 0  # observability: device-retry (tier-2) counter
        self.rebuilds = 0  # observability: full snapshot rebuilds
        # tickets submitted, their waves (``_cut``); rows a capacity
        # left unanswered on a wave's first pass, by tier
        self.tickets = self.ticket_waves = 0
        self.overflow_rows = {"fast": 0, "general": 0}
        # subject-set roots of batch_expand by what answered them: the
        # first rung of level capacities, the full rung, or the oracle
        self.expand_roots = {"first": 0, "full": 0, "oracle": 0}
        # the fast BFS's folded levels by the rung they ran at
        # (fastpath.RUNGS), counted at collect from the codes a program
        # returns after its occupancy counts
        self.fast_rung_levels = dict.fromkeys(fp.RUNGS, 0)
        self.projection_build_s = 0.0  # host-side snapshot build
        self.projection_upload_s = 0.0  # device upload (blocked)
        self._expand_extra = None  # lazily shipped expand tables
        self.overlay_applies = 0  # observability: O(delta) write applications
        # when set, every full rebuild refreshes this projection checkpoint
        # (engine/checkpoint.py); save failures count, never raise
        self.checkpoint_path: Optional[str] = None
        self.checkpoint_errors = 0
        self.metrics = metrics  # optional Metrics registry for phase hists
        self.dispatches = 0  # observability: device dispatch count
        self.device_failures = 0  # observability: whole-dispatch failures
        # monotonic stamp of the last device failure: health reports the
        # engine ``degraded`` (serving from the CPU oracle) while failures
        # are recent, and recovers on its own once dispatches stay clean
        self._last_device_failure = 0.0
        self.degraded_window = 30.0
        # host-side phase accumulators (seconds / samples): bench sections
        # read these directly; the same samples land in
        # keto_engine_phase_seconds when a Metrics registry is attached
        self.phase_seconds: dict = {}
        self.phase_counts: dict = {}
        self._on_thread = threading.local()  # thread_phase_seconds()
        # Leopard closure index (ketotpu/leopard/): rebuilt with the
        # snapshot, folded incrementally from the same changelog as the
        # overlay; None while disabled or stale (everything then serves
        # through the normal paths)
        lcfg = dict(leopard or {})
        self.leopard_enabled = bool(lcfg.get("enabled", True))
        self._leopard_cfg = {
            "max_pairs": int(lcfg.get("max_pairs", 4_000_000)),
            "rebuild_delta_pairs": int(
                lcfg.get("rebuild_delta_pairs", 4096)
            ),
            "rebuild_dirty_sets": int(lcfg.get("rebuild_dirty_sets", 512)),
        }
        # hot-spot shield (ketotpu/cache/): probed after the Leopard index
        # in _dispatch, refilled in _finish_chunk.  Entries are stamped
        # with the drain cursor captured under the sync lock together with
        # the snapshot they were computed against.
        self.result_cache = result_cache
        self._leopard: Optional[leo.ClosureIndex] = None
        self._leo_device = None
        self.leopard_answered = 0  # checks answered from the index
        self.leopard_hits = 0  # of those, answered allowed
        # rows the index was asked about, by what became of them
        # (keto_leopard_rows_total{outcome}; counted at collect)
        self.leopard_rows = dict.fromkeys(leo.OUTCOMES, 0)
        self.leopard_list_fallbacks = 0  # listings served by the host oracle
        # warm heuristic for the compile observatory: after this many
        # consecutive check dispatches that triggered zero XLA compiles,
        # the engine declares itself warm — any later compile warns
        # loudly (ketotpu/compilewatch.py)
        self._clean_dispatches = 0
        self.warm_after_clean = 2
        # -- incremental fold + off-path compaction (engine/delta.py) -------
        # the overlay's escape hatch used to be a blocking full rebuild
        # (136s-class at 10M tuples).  Two cheaper tiers now sit in front:
        # an incremental CSR fold of the accumulated changelog slice, and
        # (opt-in) a background compactor that builds the next generation
        # off the serving path and publishes it with a pointer swap.
        ccfg = dict(compaction or {})
        self.fold_enabled = (
            bool(ccfg.get("fold", True)) and self.supports_fold
        )
        self.compaction_background = (
            bool(ccfg.get("background", False))
            and self.supports_background_compaction
        )
        self.fold_max_pairs = int(ccfg.get("fold_max_pairs", 200_000))
        self.compact_rounds = int(ccfg.get("catchup_rounds", 8))
        # ordered changelog entries drained since the snapshot the engine
        # serves was built (the fold input); None once the slice outgrew
        # fold_max_pairs — folds are then off until the next full build
        self._since_base: Optional[list] = []
        # background mode only: drained changes the overlay could NOT
        # absorb — serving stays on the stale view (the served cursor lags)
        # until the compactor publishes a generation that covers them
        self._pending: list = []
        # cursor the SERVING state (snapshot + overlay) covers; equals
        # _log_cursor except while background pending exists
        self._served_cursor = 0
        self._snap_cursor = 0  # store cursor the base snapshot was built at
        # generation bookkeeping: the token invalidates in-flight compactor
        # results when a sync rebuild wins the race
        self._gen_token = 0
        self._compact_thread: Optional[threading.Thread] = None
        self.generation = 0  # observability: snapshot generations published
        self.folds = 0  # observability: incremental CSR folds
        self.compactions = 0  # observability: background generation swaps
        self.compaction_errors = 0  # worker failures (served view unaffected)
        self.last_compaction_mode = "none"  # fold | rebuild | none
        self.last_build_phases: dict = {}  # per-phase seconds of last build

    def _phase(self, name: str, dt: float) -> None:
        """File ``dt`` seconds under engine phase ``name``; what a
        :meth:`_span` does when it ends, and what the phases that are timed
        where they run (projection build, leopard build) call."""
        self.phase_seconds[name] = self.phase_seconds.get(name, 0.0) + dt
        self.phase_counts[name] = self.phase_counts.get(name, 0) + 1
        mine = self.thread_phase_seconds()
        mine[name] = mine.get(name, 0.0) + dt
        if self.metrics is not None:
            self.metrics.observe(
                "keto_engine_phase_seconds", dt,
                help="engine phase wall time", phase=name,
            )

    def thread_phase_seconds(self) -> dict:
        """The calling thread's own share of ``phase_seconds``: what a
        caller that dispatches beside others (the coalescer's two wave
        threads, engine/coalesce.py) reads before and after its half of a
        wave, so that one wave's record holds no other's seconds."""
        try:
            return self._on_thread.phases
        except AttributeError:
            mine = self._on_thread.phases = {}
            return mine

    def _span(self, name: str, **fields) -> profiler.Span:
        """``with self._span("check_encode", rows=n):`` one engine phase:
        filed by :meth:`_phase` when it ends and, during a profiler
        capture, a host span ``keto/engine/<name>`` on the device's clock
        (``fields`` ride on it)."""
        return profiler.Span(
            "keto/engine/" + name,
            lambda dt: self._phase(name, dt), **fields,
        )

    def _device_failure(self, what: str) -> None:
        """Count and log a device fault the host is about to cover for;
        called from an ``except`` block the log carries that traceback.
        Degraded answers stay correct, so this log line and the
        keto_engine_device_failures gauge are the only signs that the
        CPU is doing the chip's work."""
        self.device_failures += 1
        self._last_device_failure = time.monotonic()
        _log.log(
            logging.ERROR if self.device_failures == 1 else logging.WARNING,
            "device %s failed (%d so far); the host path answers instead",
            what, self.device_failures,
            exc_info=sys.exc_info()[0] is not None,
        )

    def is_degraded(self) -> bool:
        """True while device dispatches are failing over to the CPU oracle."""
        if not self.device_failures:
            return False
        return (time.monotonic() - self._last_device_failure) < self.degraded_window

    def _rpc_fallback_stage(self, op: str, dt: float) -> None:
        """File oracle-fallback time as the RPC-level ``fallback`` stage.
        Coalesced waves run on the worker thread (no request context), so
        the sample goes straight to the stage histogram there."""
        if flightrec.current() is not None:
            flightrec.note_stage("fallback", dt)
        elif self.metrics is not None:
            self.metrics.observe(
                flightrec.STAGE_METRIC, dt,
                help="per-RPC stage wall time decomposition",
                op=op, stage="fallback",
            )

    # -- snapshot lifecycle -------------------------------------------------
    #
    # Writes reach the device through two tiers (engine/delta.py): O(delta)
    # overlay application for the common case, amortized full (vectorized)
    # rebuilds when the overlay hits its thresholds, cannot represent a
    # change, or the namespace config changed.  Probe verdicts under an
    # overlay are exact; queries whose exploration touches a changed CSR
    # row come back `dirty` and are answered by the host oracle.

    def _sync_cols(self) -> None:
        """Bring the column mirror up to date with the store.  Incremental
        when the change log still covers our cursor; otherwise a full rescan
        (tuples + log head read under one store lock, so no write can land
        between the scan and the cursor).

        Columnar stores (storage/columnar.py) short-circuit the rescan:
        their base segment IS the column layout, so the mirror adopts the
        id arrays wholesale (no per-tuple Python — the 10M-tuple path) and
        only tail rows replay row-wise.  Adoption requires this engine's
        vocab to be empty (fresh boot) or already the store's own — after
        a checkpoint resume the snapshot's vocab owns the id space and the
        slow path re-interns instead."""
        if self._cols is not None:
            changes, head = self.store.changes_since(self._log_cursor)
            if changes is not None:
                for op, t in changes:
                    self._cols.apply(op, t)
                self._log_cursor = head
                return
            self._cols = None  # change log overflowed past our cursor
        exporter = getattr(self.store, "export_columns", None)
        store_vocab = getattr(self.store, "vocab", None)
        if exporter is not None and (
            store_vocab is self._vocab or len(self._vocab.subjects) == 0
        ):
            cols, alive, tail, head = exporter()
            self._vocab = store_vocab
            self._cols = dl.TupleColumns.from_arrays(store_vocab, cols, alive)
            for t in tail:
                self._cols.apply(1, t)
            self._log_cursor = head
            return
        tuples, head = self.store.tuples_and_head()
        self._cols = dl.TupleColumns.from_tuples(self._vocab, tuples)
        self._log_cursor = head

    def _rebuild(self, fingerprint: int) -> None:
        t0 = time.perf_counter()
        ph: dict = {}
        self._sync_cols()
        self._cols.compact()
        self._snap = dl.build_snapshot_cols(
            self._cols,
            self.namespace_manager,
            strict=self.strict_mode,
            version=self.store.version,
            phases=ph,
            table_sink=self._ship_table,
        )
        self.projection_build_s = time.perf_counter() - t0
        self._snap_fingerprint = fingerprint
        self._overlay = dl.OverlayState()
        self._overlay_active = False
        old_shapes = self._swap_shape_signature()
        t0 = time.perf_counter()
        self._install_device_arrays()
        jax.block_until_ready(jax.tree_util.tree_leaves(self._device_arrays))
        self.projection_upload_s = time.perf_counter() - t0
        self.rebuilds += 1
        self.generation += 1
        self._gen_token += 1  # any in-flight compactor result is now stale
        self._snap_cursor = self._log_cursor
        self._served_cursor = self._log_cursor
        self._since_base = []
        self._pending = []
        self.last_compaction_mode = "rebuild"
        self._projection_phases(ph)
        new_shapes = self._swap_shape_signature()
        if (
            old_shapes is not None and new_shapes is not None
            and new_shapes == old_shapes
        ):
            # same-shape regeneration: every jitted program still fits —
            # keep the schedule cache and do NOT re-arm the compile
            # observatory (a compile after this swap is a real regression)
            pass
        else:
            self._gen_sched_cache.clear()  # new graph, re-adapt once
            # new shapes may legitimately compile after a rebuild — the warm
            # alarm re-arms once dispatches run clean again
            self._clean_dispatches = 0
            compilewatch.get().declare_cold("snapshot rebuild")
        self._install_leopard()
        if self.checkpoint_path:
            from ketotpu.engine import checkpoint as ckpt

            try:
                ckpt.save_snapshot(
                    self._snap, self.checkpoint_path,
                    extra={"fingerprint": fingerprint},
                )
            except OSError:
                self.checkpoint_errors += 1

    def _install_leopard(self) -> None:
        """(Re)build the closure index from the column mirror and ship
        the pair array to HBM.  Failures disable the index (None) — the
        engine keeps serving through the normal paths — never raise."""
        self._leopard = None
        self._leo_device = None
        if not self.leopard_enabled or self._cols is None:
            return
        try:
            idx = leo.ClosureIndex(
                max_width=self.max_width, **self._leopard_cfg
            )
            idx.build_from_cols(self._cols, self.namespace_manager)
            idx.bind_vocab(self._vocab)
        except leo.ClosureTooLarge:
            return
        self._leopard = idx
        try:
            self._leo_device = leodev.ship_pairs(idx)
        except Exception:  # noqa: BLE001
            # the index still answers from the host's sorted pairs
            self._leo_device = None
            self._device_failure("leopard pair upload")
        self._phase("leopard_build", idx.build_s)

    def _leopard_fold(self, changes) -> None:
        """Incremental maintenance from the changelog slice already folded
        into the column mirror: additions append closure pairs, deletions
        mark affected set ids dirty.  When the delta cannot represent the
        change (unknown node, thresholds) the index rebuilds vectorized
        from the columns — same two-tier shape as the overlay."""
        if self._leopard is None:
            return
        if self._leopard.apply_changes(changes):
            return
        self._install_leopard()

    def _install_device_arrays(self) -> None:
        """Ship the projection to the device.  Base arrays transfer once
        per rebuild; overlay updates later merge over this dict so a write
        re-ships only the (small) overlay.  EMPTY overlay arrays ship from
        the start so the jitted program's pytree structure is identical
        before and after the first write — overlay activation must never
        trigger a recompile.  (The mesh engine overrides this: it ships
        sharded stacks instead and builds the replicated copy lazily.)"""
        self._base_device = jax.device_put(self._snap.check_arrays())
        self._release_host_tables(self._snap, self._base_device)
        self._expand_extra = None  # expand-only tables ship on first use
        self._device_arrays = dict(
            self._base_device,
            **jax.device_put(
                dl.overlay_arrays(
                    self._overlay, self._snap, pair_cap=self.max_overlay_pairs
                )
            ),
        )

    @staticmethod
    def _ship_table(prefix: str, table):
        """A hash table the projection has just built goes to the device
        at once (``build_snapshot_cols`` ``table_sink``); the snapshot keeps
        the device's columns.  (The mesh engine overrides: its shards'
        tables are stacked on the host first.)"""
        shipped = jax.device_put(table)
        jax.block_until_ready(shipped)
        return hashtab.DeviceTable(shipped)

    @staticmethod
    def _release_host_tables(snap, base) -> None:
        """Once shipped, the two hash tables are read by the device
        programs alone: the snapshot keeps them as the device's columns
        (``hashtab.DeviceTable``) and lets the host copies go."""
        snap.node_tab = hashtab.DeviceTable(hashtab.subtables(base, "nt_"))
        snap.mem_tab = hashtab.DeviceTable(hashtab.subtables(base, "mt_"))

    def _expand_arrays(self):
        """Device arrays for batch_expand: the Check dict plus the
        expand-only tables, shipped lazily — Check serving at 10M tuples
        skips ~160MB of host-to-device upload this way.  (The mesh engine
        overrides this with its replicated copy.)"""
        if self._expand_extra is None:
            from ketotpu.engine.snapshot import EXPAND_ONLY_KEYS

            self._expand_extra = jax.device_put(
                {k: getattr(self._snap, k) for k in EXPAND_ONLY_KEYS}
            )
        return dict(self._device_arrays, **self._expand_extra)

    def snapshot(self) -> Snapshot:
        with self._sync_lock:
            return self._snapshot_locked()

    def _snapshot_locked(self) -> Snapshot:
        fingerprint = config_fingerprint(self.namespace_manager)
        if self._snap is None or self._snap_fingerprint != fingerprint:
            self._rebuild(fingerprint)
            return self._snap
        changes, head = self.store.changes_since(self._log_cursor)
        if changes is None:
            self._rebuild(fingerprint)
            return self._snap
        if changes:
            if self._cols is not None:
                # keep the column mirror current; after a checkpoint resume
                # it is None and _sync_cols rescans at the next rebuild
                for op, t in changes:
                    self._cols.apply(op, t)
            self._log_cursor = head
            self._note_since_base(changes)
            # the closure index folds eagerly at drain time in both modes:
            # it is maintained against the mirror, not the snapshot
            # generation, and answering fresher than the served cursor is
            # always legal (staleness bounds are lower bounds)
            self._leopard_fold(changes)
            if self.compaction_background:
                self._pending.extend(changes)
                if self._absorb_pending():
                    self.overlay_applies += 1
                else:
                    self._kick_compactor()
            else:
                if self._overlay_apply(changes):
                    self._overlay_active = True
                    self.overlay_applies += 1
                    self._served_cursor = self._log_cursor
                elif not self._fold_locked(fingerprint):
                    self._rebuild(fingerprint)
        elif (
            self.compaction_background and self._pending
            and not self._compactor_alive()
        ):
            # un-absorbed writes with no compactor in flight (a previous
            # round gave up or died): any read re-kicks the catch-up
            self._kick_compactor()
        return self._snap

    def _overlay_apply(self, changes) -> bool:
        """Serve ``changes`` through the O(delta) overlay; False = the
        overlay cannot (or should not) represent them and the caller must
        fall back to a full rebuild.  The mesh engine overrides this with
        per-shard overlays routed by the (ns, obj) owner hash."""
        try:
            dl.apply_changes(self._overlay, self._snap, self._vocab, changes)
        except dl.OverlayRejected:
            return False
        pairs, dirty = self._overlay.size()
        if pairs > self.max_overlay_pairs or dirty > self.max_overlay_dirty:
            return False
        try:
            ov = dl.overlay_arrays(
                self._overlay, self._snap, pair_cap=self.max_overlay_pairs
            )
        except ValueError:  # fixed-shape table could not fit the content
            return False
        if self._base_device is None:
            return False
        self._device_arrays = dict(self._base_device, **jax.device_put(ov))
        return True

    # -- incremental fold + off-path compaction ------------------------------

    @staticmethod
    def _array_shapes(d) -> Optional[dict]:
        """Shape+dtype signature of a device dict: the generation-swap
        referee.  Equal signatures mean every jitted program's pytree is
        unchanged and the swap must not re-arm the compile observatory."""
        if d is None:
            return None
        return {
            k: (tuple(getattr(v, "shape", ())), str(getattr(v, "dtype", "")))
            for k, v in d.items()
        }

    def _swap_shape_signature(self) -> Optional[dict]:
        """Signature of the arrays a generation swap actually re-ships.
        The mesh engine overrides this to sign its sharded stacks: its
        replicated ``_device_arrays`` is a lazy expand-only copy that a
        rebuild nulls, which would otherwise read as a shape change (and
        re-arm the compile observatory) on every sharded rebuild."""
        return self._array_shapes(self._device_arrays)

    def _projection_phases(self, ph: dict) -> None:
        """File per-phase build/fold seconds into the engine phase
        accumulators and the keto_projection_phase_seconds histogram."""
        out = {}
        for k, v in ph.items():
            key = k if k.startswith("fold_") else f"build_{k}"
            out[key] = v
            self._phase(key, v)
            if self.metrics is not None:
                self.metrics.observe(
                    "keto_projection_phase_seconds", v,
                    help="projection build/fold phase wall time", phase=key,
                )
        self.last_build_phases = out

    def _note_since_base(self, changes) -> None:
        """Accumulate the drained slice for the fold path; a slice past the
        fold budget can no longer fold and is dropped (folds stay off until
        the next full build resets the base)."""
        if self._since_base is None:
            return
        self._since_base.extend(changes)
        if len(self._since_base) > self.fold_max_pairs:
            self._since_base = None

    def _absorb_pending(self) -> bool:
        """Copy-on-write overlay absorb of the whole pending slice.  The
        live overlay never observes a partial application: on any failure
        (reject, thresholds, table overflow) serving continues on the
        current view unchanged and the compactor takes over."""
        if self._base_device is None:
            return False
        if not self._pending:
            self._served_cursor = self._log_cursor
            return True
        ov = dl.OverlayState(
            pair_net=dict(self._overlay.pair_net),
            new_nodes=dict(self._overlay.new_nodes),
            dirty_nodes=set(self._overlay.dirty_nodes),
        )
        try:
            dl.apply_changes(ov, self._snap, self._vocab, self._pending)
        except (dl.OverlayRejected, ValueError):
            return False
        pairs, dirty = ov.size()
        if pairs > self.max_overlay_pairs or dirty > self.max_overlay_dirty:
            return False
        try:
            arrs = dl.overlay_arrays(
                ov, self._snap, pair_cap=self.max_overlay_pairs
            )
        except ValueError:  # fixed-shape table could not fit the content
            return False
        self._overlay = ov
        self._device_arrays = dict(
            self._base_device, **jax.device_put(arrs)
        )
        self._overlay_active = True
        self._pending = []
        self._served_cursor = self._log_cursor
        return True

    def _fold_locked(self, fingerprint: int) -> bool:
        """Second tier of the sync write path: fold the accumulated
        changelog slice into the base snapshot instead of re-projecting all
        N tuples.  All device shapes are preserved by construction (the
        fold rejects pad crossings), so the swap is recompile-free; only a
        hash table that outgrew its capacity inside the fold changes shape,
        and the observatory is re-armed exactly then."""
        if not self.fold_enabled or not self._since_base:
            return False  # no fold input (or the slice outgrew the budget)
        ph: dict = {}
        t0 = time.perf_counter()
        try:
            snap = dl.fold_snapshot_cols(
                self._snap, self._vocab, self._since_base,
                version=self.store.version, phases=ph,
            )
        except dl.FoldRejected:
            return False
        self.projection_build_s = time.perf_counter() - t0
        old_shapes = self._swap_shape_signature()
        self._snap = snap
        self._snap_fingerprint = fingerprint
        self._snap_cursor = self._log_cursor
        self._since_base = []
        self._pending = []
        self._overlay = dl.OverlayState()
        self._overlay_active = False
        t0 = time.perf_counter()
        self._install_device_arrays()
        jax.block_until_ready(jax.tree_util.tree_leaves(self._device_arrays))
        self.projection_upload_s = time.perf_counter() - t0
        self.generation += 1
        self._gen_token += 1
        self.folds += 1
        self.last_compaction_mode = "fold"
        self._projection_phases(ph)
        new_shapes = self._swap_shape_signature()
        if old_shapes is None or new_shapes != old_shapes:
            self._gen_sched_cache.clear()
            self._clean_dispatches = 0
            compilewatch.get().declare_cold(
                "projection fold: device shapes changed"
            )
        self._served_cursor = self._log_cursor
        return True

    def _compactor_alive(self) -> bool:
        t = self._compact_thread
        return t is not None and t.is_alive()

    def _kick_compactor(self) -> None:
        if self._compactor_alive():
            return
        t = threading.Thread(
            target=self._compact_worker, args=(self._gen_token,),
            name="keto-compactor", daemon=True,
        )
        self._compact_thread = t
        t.start()

    def _compact_worker(self, token: int) -> None:
        """Off-path generation builder.  Pins the inputs under the sync
        lock, builds (fold-else-rebuild) and ships to the device with the
        lock RELEASED — checks keep serving the old generation + overlay —
        then re-takes the lock only for the pointer swap.  A sync rebuild
        racing ahead bumps the generation token and the stale result is
        discarded at the swap gate."""
        try:
            for _ in range(max(1, self.compact_rounds)):
                with self._sync_lock:
                    if token != self._gen_token or self._snap is None:
                        return
                    snap = self._snap
                    fingerprint = self._snap_fingerprint
                    since = (
                        list(self._since_base)
                        if self._since_base is not None else None
                    )
                    pin_cursor = self._log_cursor
                    version = self.store.version
                    frozen = (
                        self._cols.freeze() if self._cols is not None
                        else None
                    )
                # -- build off-lock ----------------------------------------
                ph: dict = {}
                t0 = time.perf_counter()
                mode = "fold"
                new_snap = None
                if self.fold_enabled and since:
                    try:
                        new_snap = dl.fold_snapshot_cols(
                            snap, self._vocab, since,
                            version=version, phases=ph,
                        )
                    except dl.FoldRejected:
                        new_snap = None
                if new_snap is None:
                    if frozen is None:
                        # no mirror to rebuild from (post-checkpoint-resume
                        # boot): fall back to the blocking path once
                        with self._sync_lock:
                            if token == self._gen_token:
                                self._rebuild(fingerprint)
                        return
                    mode = "rebuild"
                    new_snap = dl.build_snapshot_cols(
                        frozen, self.namespace_manager,
                        strict=self.strict_mode,
                        version=version, phases=ph,
                    )
                build_s = time.perf_counter() - t0
                t0 = time.perf_counter()
                base = jax.device_put(new_snap.check_arrays())
                empty_ov = jax.device_put(
                    dl.overlay_arrays(
                        dl.OverlayState(), new_snap,
                        pair_cap=self.max_overlay_pairs,
                    )
                )
                jax.block_until_ready(jax.tree_util.tree_leaves(base))
                upload_s = time.perf_counter() - t0
                # -- swap under the lock -----------------------------------
                with self._sync_lock:
                    if token != self._gen_token:
                        return  # a sync rebuild won the race
                    residual, head = self.store.changes_since(pin_cursor)
                    if residual is None:
                        return  # changelog overflow: next drain rebuilds
                    # drain any store tail the serving path hasn't seen yet,
                    # so mirror/leopard/cursor state stays single-writer
                    tail = residual[self._log_cursor - pin_cursor:]
                    if tail:
                        if self._cols is not None:
                            for op, t in tail:
                                self._cols.apply(op, t)
                        self._log_cursor = head
                        self._note_since_base(tail)
                        self._leopard_fold(tail)
                    old_shapes = self._swap_shape_signature()
                    self._snap = new_snap
                    self._snap_fingerprint = fingerprint
                    self._snap_cursor = pin_cursor
                    self._since_base = list(residual)
                    self._overlay = dl.OverlayState()
                    self._overlay_active = False
                    self._base_device = base
                    self._release_host_tables(new_snap, base)
                    self._device_arrays = dict(base, **empty_ov)
                    self._expand_extra = None
                    self._pending = list(residual)
                    self._served_cursor = pin_cursor
                    self.projection_build_s = build_s
                    self.projection_upload_s = upload_s
                    self.generation += 1
                    self.compactions += 1
                    if mode == "fold":
                        self.folds += 1
                    else:
                        self.rebuilds += 1
                    self.last_compaction_mode = mode
                    self._projection_phases(ph)
                    new_shapes = self._swap_shape_signature()
                    if old_shapes is None or new_shapes != old_shapes:
                        self._gen_sched_cache.clear()
                        self._clean_dispatches = 0
                        compilewatch.get().declare_cold(
                            "generation swap: device shapes changed"
                        )
                    if self._absorb_pending():
                        return  # caught up: overlay covers the residual
                    # residual too large/unrepresentable: loop — the next
                    # round folds it into the generation just published
        except Exception:  # noqa: BLE001 - serving view must stay intact
            self.compaction_errors += 1

    def close(self) -> None:
        """Stop the background compactor (in-flight results are discarded
        at the swap gate)."""
        t = self._compact_thread
        if t is not None and t.is_alive():
            with self._sync_lock:
                self._gen_token += 1
            t.join(timeout=10.0)

    def projection_stats(self) -> dict:
        """Projection/compaction state for status --debug, the flight
        recorder, and the metrics gauges — one consistent read."""
        with self._sync_lock:
            pairs, dirty = (
                self._overlay.size() if self._overlay is not None else (0, 0)
            )
            arrays = self._served_arrays() or {}
            out = {
                "generation": self.generation,
                "rebuilds": self.rebuilds,
                "folds": self.folds,
                "compactions": self.compactions,
                "compaction_errors": self.compaction_errors,
                "last_compaction_mode": self.last_compaction_mode,
                "background": self.compaction_background,
                "fold_enabled": self.fold_enabled,
                "compaction_in_flight": self._compactor_alive(),
                "overlay_active": self._overlay_active,
                "overlay_pairs": pairs,
                "overlay_dirty": dirty,
                "overlay_pair_cap": self.max_overlay_pairs,
                "overlay_dirty_cap": self.max_overlay_dirty,
                "pending_changes": len(self._pending),
                "since_base": (
                    len(self._since_base)
                    if self._since_base is not None else -1
                ),
                "fold_max_pairs": self.fold_max_pairs,
                "snap_cursor": self._snap_cursor,
                "served_cursor": self._served_cursor,
                "log_cursor": self._log_cursor,
                "projection_build_s": round(self.projection_build_s, 6),
                "projection_upload_s": round(self.projection_upload_s, 6),
                "build_phases": {
                    k: round(v, 6)
                    for k, v in self.last_build_phases.items()
                },
                "tag_rejects": dict(hashtab.TAG_REJECTS),
                "device_bytes": self._device_bytes(),
                "expand_roots": dict(self.expand_roots),
            }
        # the served hash tables, as the device programs unroll them
        # (engine/hashtab.py): probe rounds, gathers a lookup, the tag
        # salt (above 0: the invariant walked it) and what the build split
        # to keep the rounds (buckets, deepest level, empty slots).  Read
        # off the lock: ``meta`` is a fetch from the device
        out["tables"] = {
            p: hashtab.table_stats(hashtab.subtables(arrays, p + "_"))
            for p in hashtab.TABLES
            if p + "_meta" in arrays
        }
        return out

    def _device_bytes(self) -> dict:
        """The served projection's device bytes by group of arrays, as
        the sizing function reckons them from the snapshot's counts
        (engine/snapshot.py ``device_bytes``)."""
        if self._snap is None:
            return {}
        from ketotpu.engine.snapshot import device_bytes

        return device_bytes(**self._sizing_counts())

    def _sizing_counts(self) -> dict:
        """What :func:`snapshot.device_bytes` takes, of the served view
        (the mesh engine overrides: a chip holds the largest shard's
        shapes)."""
        snap = self._snap
        leo = self._leopard if self._leo_device is not None else None
        return dict(
            tuples=snap.n_tuples, nodes=snap.n_nodes, edges=snap.n_edges,
            # the vocabulary grows past the build; the decode table's pad
            # is the build's
            subjects=min(len(snap.vocab.subjects), len(snap.sub_ns)),
            pair_cap=self.max_overlay_pairs,
            leopard_pairs=len(leo.elt_packed) if leo is not None else 0,
        )

    @property
    def probe_rounds(self) -> dict:
        """Probe rounds a lookup of each served table unrolls."""
        arrays = self._served_arrays() or {}
        return {p: int(arrays[p + "_pw"].shape[-1])
                for p in hashtab.TABLES if p + "_pw" in arrays}

    def _served_arrays(self):
        """The device-array dict the check programs are served from (the
        mesh engine overrides: its sharded stacks)."""
        return self._device_arrays

    def _sync_view(self):
        """Atomic (snapshot, device_arrays, cursor) view.
        Writers mutate all of these together under ``_sync_lock``, so a
        dispatching thread must capture them together — reading
        ``_device_arrays`` after releasing the lock could pair a new
        snapshot's encodings with an older projection (or vice versa).
        The drain cursor rides along as the freshness stamp for cache
        entries computed against this view: captured under the same lock,
        it is exactly the state the verdicts will describe, never newer."""
        with self._sync_lock:
            snap = self._snapshot_locked()
            # the SERVED cursor, not the drain cursor: under background
            # compaction the drain can run ahead of what the device view
            # covers, and cache entries must be stamped with what the
            # verdicts actually describe
            return snap, self._device_arrays, self._served_cursor

    def refresh(self) -> None:
        """Force a full rebuild (the CheckRequest.latest consistency knob —
        stronger than needed, since overlay probes are already exact)."""
        with self._sync_lock:
            self._rebuild(config_fingerprint(self.namespace_manager))

    def consistency_cursors(self) -> tuple:
        """Drained changelog cursor(s) for the freshness barrier
        (ketotpu/consistency/barrier.py): the serving state covers every
        store delta at positions <= the cursor.  One entry here; the mesh
        engine overrides with a per-shard vector.  Under background
        compaction this lags the drain cursor while un-absorbed writes
        wait on the compactor — the barrier then bound-waits on the
        changelog position, never on a rebuild."""
        with self._sync_lock:
            return (self._served_cursor,)

    # -- checkpoint / resume (SURVEY §5.4) ----------------------------------

    def save_checkpoint(self, path: str) -> None:
        """Persist the current projection; restart skips re-projection when
        the store version and namespace config still match.

        Two capture modes, both one ``_sync_lock`` window:

        * sync compaction (default): an active delta overlay is folded in
          by a full rebuild first — the overlay is not serialized, so
          saving the stale base would persist a projection whose version
          never matches the store;
        * background compaction: a refresh here would tear down the
          in-flight compactor generation and re-arm the compile
          observatory mid-serve, so the checkpoint instead captures the
          base snapshot AND the changelog cursor it was built at (the
          compaction race fix: cols + cursor from the same lock window).
          A load replays the persisted-cursor tail through the normal
          drain, restoring the exact served state."""
        from ketotpu.engine import checkpoint as ckpt

        with self._sync_lock:
            snap = self._snapshot_locked()
            if (
                not self.compaction_background
                and (self._overlay_active or self._pending)
            ):
                self.refresh()
                snap = self._snap
            cursor = self._snap_cursor
            ver, store_head = self.store.version_and_head() if hasattr(
                self.store, "version_and_head"
            ) else (self.store.version, self.store.log_head)
            # stamp the fingerprint the snapshot was BUILT under, not a
            # fresh read: a file-backed config reloading between build and
            # save must not mis-stamp a stale projection as current
            ckpt.save_snapshot(
                snap, path, extra={"fingerprint": self._snap_fingerprint},
                cursor=cursor, head=store_head, store_version=ver,
            )

    def load_checkpoint(self, path: str) -> bool:
        """Install a checkpoint if it matches the live store version and
        namespace config; returns False (and leaves state untouched) when
        it doesn't — the next snapshot() then projects from the store.
        Any load failure (missing, truncated, corrupt, or foreign file) is
        a graceful refusal, never a boot-loop crash."""
        from ketotpu.engine import checkpoint as ckpt

        fingerprint = config_fingerprint(self.namespace_manager)
        try:
            snap, cursor, saved_head, saved_ver = (
                ckpt.load_snapshot_with_cursor(
                    path, want_extra={"fingerprint": fingerprint}
                )
            )
        except Exception:  # noqa: BLE001 - refusal is the contract
            return False
        with self._sync_lock:
            # read the log head BEFORE comparing versions: a write landing
            # between the two reads then fails the version check (reading in
            # the other order would skip that write's log entry forever)
            log_head = self.store.log_head
            # the gate version is the STORE version at save time: under
            # background compaction the base snapshot's own version lags
            # the store (the un-folded tail is replayed below), so the
            # snapshot version only gates legacy stamp-less files
            ver_gate = saved_ver if saved_ver is not None else snap.version
            if ver_gate != self.store.version:
                return False  # store moved since the save: stale projection
            if cursor is None or saved_head is None or cursor == saved_head:
                # head-exact save (pre-cursor file, or no overlay at save
                # time): the base covers everything at this version, adopt
                # at the LOCAL head — a rebooted store restarts its log
                # coordinates at 0 and the old cursor means nothing there
                cursor = log_head
            elif cursor > log_head or log_head < saved_head:
                # a base-at-cursor save needs the tail [cursor, saved_head)
                # replayed from the local log.  A local head short of the
                # saved one means a different coordinate space (fresh-boot
                # log reset: matching version + a shorter log is only
                # reachable by reboot, since entries only land with version
                # bumps) — the tail is gone, refuse rather than serve a
                # base missing acknowledged writes.
                return False
            elif self.store.changes_since(cursor)[0] is None:
                return False  # tail evicted from the bounded log
            self._snap = snap
            self._snap_fingerprint = fingerprint
            self._vocab = snap.vocab
            self._cols = None  # lazily re-mirrored on the next full rebuild
            self._log_cursor = cursor
            self._served_cursor = cursor
            self._snap_cursor = cursor
            self._since_base = []
            self._pending = []
            self._gen_token += 1
            self.generation += 1
            self._overlay = dl.OverlayState()
            self._overlay_active = False
            # no column mirror to build the closure from: the index stays
            # off (listings host-oracle) until the next full rebuild
            self._leopard = None
            self._leo_device = None
            self._install_device_arrays()
            return True

    # -- replication (warm-standby follower, server/workers.py wire ops) ----

    def replication_snapshot(self):
        """Bootstrap payload for a warm-standby follower, captured so no
        concurrent write can fall between the pieces: the served base
        snapshot + the cursor it was built at (one ``_sync_lock`` window —
        a background compactor swap cannot tear them apart), then an
        atomic replica scan of the store, then the changelog tail
        ``[cursor, head)`` sliced to the scan's head.  Returns
        ``(snap, cursor, fingerprint, rows, tail, head, version)``."""
        with self._sync_lock:
            snap = self._snapshot_locked()
            cursor = self._snap_cursor
            fingerprint = self._snap_fingerprint
            rows, head, version = self.store.replica_scan()
            tail, _ = self.store.changes_since(cursor)
            if tail is None:
                # the base predates the bounded log (long-lived overlay):
                # rebuild once so (base, tail) is a consistent pair
                self._rebuild(config_fingerprint(self.namespace_manager))
                snap = self._snap
                cursor = self._snap_cursor
                fingerprint = self._snap_fingerprint
                rows, head, version = self.store.replica_scan()
                tail, _ = self.store.changes_since(cursor)
                tail = tail if tail is not None else []
            # changes_since may already see writes past the replica scan;
            # the follower's replica is anchored at `head`, so ship exactly
            # the tail the scan covers
            tail = tail[: max(0, head - cursor)]
        return snap, cursor, fingerprint, rows, tail, head, version

    def adopt_snapshot(self, snap, *, cursor: int, fingerprint=None) -> None:
        """Install a snapshot shipped from a live owner (standby bootstrap).
        Unlike ``load_checkpoint`` there is no version gate: the caller has
        already anchored the local replica store at the owner's changelog
        coordinates, so the normal drain replays everything past
        ``cursor``."""
        with self._sync_lock:
            self._snap = snap
            self._snap_fingerprint = (
                fingerprint if fingerprint is not None
                else config_fingerprint(self.namespace_manager)
            )
            self._vocab = snap.vocab
            self._cols = None
            self._log_cursor = cursor
            self._served_cursor = cursor
            self._snap_cursor = cursor
            self._since_base = []
            self._pending = []
            self._gen_token += 1
            self.generation += 1
            self._overlay = dl.OverlayState()
            self._overlay_active = False
            self._leopard = None
            self._leo_device = None
            self._install_device_arrays()

    # -- query encoding -----------------------------------------------------

    def _encode(self, snap: Snapshot, queries, rest_depth: int):
        v = snap.vocab
        n = len(queries)
        if hasattr(queries, "encode_for"):
            # columnar batch (engine/columns.py): one vectorized hashtab
            # probe per column instead of n scalar dict walks; repeat
            # encodes against the same vocab only refresh prior misses
            q_ns, q_obj, q_rel, q_subj = queries.encode_for(v)
        else:
            ns_look = v.namespaces.lookup
            obj_look = v.objects.lookup
            rel_look = v.relations.lookup
            subj_look = v.subject_key
            q_ns = np.fromiter((ns_look(q.namespace) for q in queries), np.int32, n)
            q_obj = np.fromiter((obj_look(q.object) for q in queries), np.int32, n)
            q_rel = np.fromiter((rel_look(q.relation) for q in queries), np.int32, n)
            q_subj = np.fromiter((subj_look(q.subject) for q in queries), np.int32, n)
        # global max-depth precedence (engine.go:82-84)
        if rest_depth <= 0 or self.max_depth < rest_depth:
            rest_depth = self.max_depth
        q_depth = np.full(n, rest_depth, np.int32)
        return q_ns, q_obj, q_rel, q_subj, q_depth

    @staticmethod
    def _qkeys(queries, idx, rest_depth: int):
        """Result-cache keys for rows ``idx`` — from columns when the batch
        is a ColumnBlock (no Subject materialization), else per item."""
        ck = getattr(queries, "cache_key", None)
        if ck is not None:
            return [ck(int(i), rest_depth) for i in idx]
        return [cache_check_key(queries[i], rest_depth) for i in idx]

    def _classify(self, snap: Snapshot, q_ns, q_rel):
        """(err, general) masks from the snapshot's static tables.

        err: the oracle must raise the reference's typed client error —
        a configured namespace queried with an undeclared non-empty relation
        (namespace/definitions.go:61).  general: the relation's closure can
        reach AND/NOT or an erroring lookup, so the task-tree interpreter
        runs it (fastpath semantics would be wrong).
        """
        num_ns, num_rel = snap.taint.shape
        ns_ok = q_ns >= 0
        nsc = np.clip(q_ns, 0, num_ns - 1)
        relc = np.clip(q_rel, 0, num_rel - 1)
        ns_cfg = ns_ok & snap.flat.ns_cfg[nsc]
        rel_known = q_rel >= 0
        err = ns_cfg & (~rel_known | snap.op.rel_err[nsc, relc])
        general = ~err & ns_ok & rel_known & snap.taint[nsc, relc]
        return err, general

    # -- demand-adaptive level scheduling -----------------------------------

    def _adaptive_mults(self):
        """Per-level frontier multipliers from the occupancy EMA, or None
        (worst-case F_MULT) before the first report.

        Demand is quantized UP to a small preset ladder (uniform base
        capped by F_MULT) rather than used per-level raw: arbitrary
        per-level tuples make every EMA wobble a brand-new fused program —
        hundreds of distinct XLA executables per process (measured: the
        XLA:CPU backend segfaults under that compile load, and every
        variant costs ~20s compile on any backend).  The ladder bounds the
        engine to at most 4 schedule variants per (batch-size, boost)
        while keeping the buffer-size win of demand sizing."""
        ema = self._occ_ema
        if ema is None or os.environ.get("KETO_NO_ADAPTIVE"):
            return None
        caps = [
            fp.F_MULT[min(lvl, len(fp.F_MULT) - 1)]
            for lvl in range(1, self.max_depth)
        ]
        want = [
            max(1, min(c, int(np.ceil(
                ema[min(lvl, len(ema) - 1)] * self.occ_headroom
            ))))
            for lvl, c in zip(range(1, self.max_depth), caps)
        ]
        for base in (1, 2, 4):
            rung = [min(c, base) for c in caps]
            if all(r >= w for r, w in zip(rung, want)):
                return (1, *rung)
        return None  # worst case: the F_MULT default

    def _take_fast_occ(self, occ: np.ndarray, levels: int) -> None:
        """A fast tier's returned occupancy: the counts of its ``levels``
        levels feed the EMA, the rung codes after them are counted."""
        self._update_occ(occ[:levels])
        ran = np.bincount(occ[levels:], minlength=len(fp.RUNGS))
        for rung, n in zip(fp.RUNGS, ran):
            self.fast_rung_levels[rung] += int(n)

    def _update_occ(self, occ: np.ndarray) -> None:
        """Fold one batch's per-level occupancy counts into the EMA
        (normalized by the batch's active-root count, occ[0])."""
        roots = float(occ[0])
        if roots <= 0:
            return
        ratio = occ.astype(np.float64) / roots
        if self._occ_ema is None or len(self._occ_ema) != len(ratio):
            self._occ_ema = ratio
        else:
            self._occ_ema = 0.5 * self._occ_ema + 0.5 * ratio

    # -- public API ---------------------------------------------------------

    def check(self, r: RelationTuple, rest_depth: int = 0) -> bool:
        return self.batch_check([r], rest_depth)[0]

    def check_is_member(self, r: RelationTuple, rest_depth: int = 0) -> bool:
        return self.check(r, rest_depth)

    def batch_check(
        self, queries: Sequence[RelationTuple], rest_depth: int = 0
    ) -> List[bool]:
        return self.collect(self.submit(queries, rest_depth))

    def submit(self, queries, rest_depth: int = 0) -> Ticket:
        """First half of a check: encode ``queries`` (a tuple sequence or
        a ColumnBlock) and launch them, cut into the waves the frontier
        holds (:meth:`_cut`; ``wave.wave_cap`` rows or fewer are one), without
        waiting for the device.  Everything is dispatched before anything
        is synced on, so device executions queue back to back: the waves
        of one ticket, and the tickets of a caller that submits the next
        batch before it collects this one (engine/coalesce.py).  Never
        raises: a failure rides in the ticket and :meth:`collect` answers
        for it, so the failure contract has one place."""
        if not hasattr(queries, "take"):  # engine/columns.py ColumnBlock
            queries = list(queries)
        ticket = Ticket(queries, rest_depth, time.perf_counter(),
                        compilewatch.get().compiles_total)
        self.tickets += 1
        cap = min(self.max_batch, wv.wave_cap(
            lambda q, f, a: fp.level_schedule(q, f, a, self.max_depth),
            self.frontier, self.arena))
        try:
            like = self._cut(ticket, cap)
            for _, c in ticket.chunks:
                ticket.waves.append(self._dispatch(c, rest_depth, None, like))
        except Exception as e:  # noqa: BLE001 - collect's to handle
            ticket.failure = e
        return ticket

    def collect(self, ticket: Ticket, errs: Optional[dict] = None):
        """Second half: sync each wave in turn, decode, retry, ask the
        oracle for the flagged rows, fill the cache.  Returns the verdicts
        as a list of bool; with ``errs`` (the columnar path's per-item
        contract: a typed oracle error lands in ``errs[row]`` instead of
        aborting the batch) ``(allowed bool array, errs)``.  A typed
        ``KetoAPIError`` passes through (deadline expiry is batch-wide by
        design); any other exception, in either half, is a device failure
        and the whole batch is answered on the oracle."""
        queries, rest_depth = ticket.queries, ticket.rest_depth
        allowed = np.zeros(len(queries), bool)
        try:
            if ticket.failure is not None:
                raise ticket.failure
            for (rows, c), w in zip(ticket.chunks, ticket.waves):
                allowed[rows] = self._finish_chunk(
                    c, w, rest_depth, errs=errs, rows=rows
                )
        except KetoAPIError:
            raise  # typed client errors (and deadline/shed) pass through
        except Exception:  # noqa: BLE001
            # the device dispatch itself died (runtime error, injected
            # fault): the whole batch is servable on the CPU oracle — a
            # degraded answer beats an error for every concurrent caller.
            # Health reports ``degraded`` until dispatches stay clean.
            self._device_failure("check dispatch")
            if errs is not None:
                errs.clear()
            allowed = self._oracle_batch(queries, rest_depth, errs)
        # warm heuristic: consecutive compile-free dispatches mean the
        # steady-state shape set is fully compiled; declare warm so any
        # later compile fires the observatory's after-warm alarm
        watch = compilewatch.get()
        if watch.compiles_total == ticket.compiles_before:
            self._clean_dispatches += 1
            if self._clean_dispatches >= self.warm_after_clean and not watch.warm:
                watch.declare_warm()
        else:
            self._clean_dispatches = 0
        # RPCs that reach the engine without the coalescer (batch routes)
        # still get a device_compute stage; no-op outside a request context
        flightrec.note_stage("device_compute", time.perf_counter() - ticket.t0)
        return allowed.tolist() if errs is None else (allowed, errs)

    def _oracle_batch(self, queries, rest_depth: int,
                      errs: Optional[dict]) -> np.ndarray:
        """Whole-batch oracle fallback (the device dispatch died); typed
        errors are captured per item into ``errs`` where the caller gave
        one (deadline expiry never is), and raised otherwise."""
        t_fb = time.perf_counter()
        out = np.zeros(len(queries), bool)
        with self._span("check_oracle_fallback", rows=len(queries)):
            for i in range(len(queries)):
                deadline.check("oracle fallback")
                self.fallbacks += 1
                try:
                    out[i] = bool(
                        self.oracle.check_is_member(queries[i], rest_depth)
                    )
                except KetoAPIError as e:
                    if errs is None or isinstance(e, DeadlineExceededError):
                        raise
                    errs[i] = e
        self._rpc_fallback_stage("check", time.perf_counter() - t_fb)
        return out

    def _pad(self, arrays, n: int, qpad: int):
        fills = (-1, -1, -1, -1, 1)
        if qpad == n:
            return arrays
        return tuple(
            np.pad(a, (0, qpad - n), constant_values=f)
            for a, f in zip(arrays, fills)
        )

    def _leopard_answers(self, enc, err, general):
        """``((allowed, answered), why)`` from the closure index (bool
        arrays, and closure.WHY_* a row), or ``(None, None)`` while the
        index is off.  Runs under the sync lock so verdicts are
        exact against the latest folded write (same contract as overlay
        probes); the probe itself is one binary search over the sorted
        pairs — on-device for large chunks, host numpy otherwise."""
        if self._leopard is None or self.strict_mode:
            return None, None
        q_ns, q_obj, q_rel, q_subj, q_depth = enc
        n = len(q_ns)
        if n == 0:
            return None, None
        with self._sync_lock:
            idx = self._leopard
            if idx is None:
                return None, None
            nodes, node_hi = idx.node_ids_np(q_ns, q_obj, q_rel)
            why = idx.why_declined(nodes, node_hi)
            probed = None
            if self._leo_device is not None and n >= leodev.DEVICE_PROBE_MIN:
                keys = np.where(
                    (nodes >= 0) & (q_subj >= 0),
                    (nodes.astype(np.int64) << 32)
                    | q_subj.astype(np.int64),
                    np.int64(-1),
                )
                try:
                    probed = leodev.probe_pairs(
                        self._leo_device, keys, _bucket(n)
                    )
                except Exception:  # noqa: BLE001
                    # probed=None: the host searchsorted answers below
                    self._device_failure("leopard probe")
            allowed, answered = idx.answer_checks(
                nodes, q_subj, why, int(q_depth[0]), probed=probed
            )
        answered &= ~(err | general)
        allowed &= answered
        why[err | general] = leo.WHY_INELIGIBLE
        return (allowed, answered), why

    def _dispatch(self, queries: Sequence[RelationTuple], rest_depth: int,
                  fused: Optional[bool] = None, like=(0, 0)):
        """Enqueue one wave's device work; returns the uncollected
        :class:`Wave`.  ``fused`` overrides the engine flag per call (the
        diagnostic surfaces pin the cascade: its tiers show one by one);
        ``like``: pad as a wave of (rows, general rows) would (``wave.Cut``).
        Shared prefix: view, encode, classify, Leopard, cache, route, pad."""
        n = len(queries)
        if n == 0:
            return None
        faults.inject("device_dispatch")
        self.dispatches += 1
        use_fused = self.supports_fused and (
            self.fused_dispatch if fused is None else fused)
        with self._span("check_encode", rows=n):
            snap, arrays, cursor = self._sync_view()
            enc = self._encode(snap, queries, rest_depth)
            err, general = self._classify(snap, enc[0], enc[2])
            active = ~(err | general)
            leo_res = probe = None
            if use_fused:
                # the program finishes the closure probe itself and masks
                # the rows it answers; the cache is told which rows the
                # host already KNOWS are answered
                known, probe, leo_why = self._leopard_modes(
                    enc, err, general, rest_depth)
            else:
                # Leopard first: closure-eligible fast queries resolve as
                # one sorted-pair binary search and leave the device walk
                # entirely (their active bit drops, so the BFS does no
                # work for them)
                leo_res, leo_why = self._leopard_answers(enc, err, general)
                known = leo_res
                if leo_res is not None:
                    active &= ~leo_res[1]
            # hot-spot shield after Leopard: cached verdicts drop their
            # queries from the device walk AND the algebra dispatch
            cache_res = self._cache_consult(queries, rest_depth, err, known)
            if cache_res is not None:
                active &= ~cache_res[0]
                general = general & ~cache_res[0]
            wave = Wave(
                n=n, qpad=wv.wave_rows(max(n, like[0]), self.frontier),
                enc=enc, err=err, general=general, cursor=cursor,
                arrays=arrays, leo_res=leo_res, cache_res=cache_res,
                leo_why=leo_why, gen_like=like[1])
            active = self._route(queries, rest_depth, wave, active)
            padded = self._pad(enc, n, wave.qpad)
            if use_fused:
                launch = self._encode_fused(
                    wave, padded, active, known is not None, probe)
            else:
                active = np.pad(active, (0, wave.qpad - n))
        if use_fused:
            self._dispatch_fused(wave, *launch)
        else:
            self._launch(wave, padded, active)
        return wave

    def _route(self, queries, rest_depth, wave, active):
        """Hook inside ``check_encode``: rows may leave the wave here (into
        ``wave.err``, out of ``wave.general`` and of the returned
        fast-active mask).  One chip serves every row itself."""
        return active

    def _launch(self, wave, padded, active) -> None:
        """Enqueue the cascade's tiers; their uncollected results go into
        ``wave``."""
        wave.fast, wave.occ = self._run_fast(wave, padded, active)
        # the algebra program is overlay-aware (probes consult the om_ delta
        # tables; a stale edge row raises its query's dirty bit, which sends
        # that query to the oracle): general rows dispatch with writes pending
        if wave.general.any():
            wave.gi = np.flatnonzero(wave.general)
            arrays, enc, like = wave.arrays, wave.enc, wave.gen_like
            wave.gen = self._run_general(arrays, enc, wave.gi, like=like)

    def _run_fast(self, wave, padded, active, boost: int = 1, rows=None):
        """Enqueue the fast tier for padded rows; returns the uncollected
        (verdict words, occupancy), or (None, None) when no row is active:
        the whole chunk resolved off-device, so skip the dispatch, not
        just the work.  ONE packed upload + ONE packed verdict download
        per launch: each separate transfer is a full host-link round-trip.
        ``boost`` > 1 is the retry: caps and the per-query schedule both
        scale (with a small retry batch the caps alone don't bind), and
        no adaptive mults — the retry exists because the demand-sized
        tier missed.  ``rows``: which of the wave's rows ``padded`` holds
        (None: all of them), for a launcher with per-row state in the wave."""
        if not active.any():
            return None, None
        first = boost == 1
        qpack = np.stack([*padded, active.astype(np.int32)]).astype(np.int32)
        return fp.run_fast_packed(
            wave.arrays,
            qpack,
            frontier=boost * self.frontier,
            arena=boost * self.arena,
            max_depth=self.max_depth,
            max_width=self.max_width,
            boost=boost,
            mults=self._adaptive_mults() if first else None,
            # a retry is timed whole, as check_retry
            span=self._span if first else profiler.null_span,
        )

    def _leopard_modes(self, enc, err, general, rest_depth):
        """The closure index's half of a fused wave: the leopard work that
        needs dict state (closure.prep_fused_checks), shipped as per-row
        probe modes; answered-masks gate the fast tier in-program, so
        resolved rows are dead weight instead of host-filtered between
        dispatches.  Returns ``(known, (lmode, leo_set, leo_elt,
        leo_dev), why)``: ``known`` is what the result cache is told —
        ``(None, rows the host already KNOWS are answered)``, or None with
        the index off; ``why`` the rows' closure.WHY_* (None: off).  The
        host half is the engine phase ``check_leopard_prep``."""
        q_ns, q_obj, q_rel, q_subj, q_depth = enc
        n = len(q_ns)
        lmode = np.zeros(n, np.int32)
        leo_set = np.full(n, -1, np.int32)
        leo_elt = np.full(n, -1, np.int32)
        leo_dev = why = None
        if self._leopard is not None and not self.strict_mode:
            with self._sync_lock, self._span("check_leopard_prep", rows=n):
                idx = self._leopard
                if idx is not None:
                    nodes, node_hi = idx.node_ids_np(q_ns, q_obj, q_rel)
                    why = idx.why_declined(nodes, node_hi)
                    leo_dev = self._leo_device
                    if leo_dev is not None:
                        lmode = idx.prep_fused_checks(
                            nodes, q_subj, why, rest_depth
                        )
                        probe_ok = (nodes >= 0) & (q_subj >= 0)
                        leo_set = np.where(probe_ok, nodes, -1).astype(
                            np.int32
                        )
                        leo_elt = np.where(probe_ok, q_subj, -1).astype(
                            np.int32
                        )
                    else:
                        # pairs never shipped (device put failed or the
                        # index is empty): the host path answers, encoded
                        # as pre-resolved modes — LM_ALLOW/LM_DENY need
                        # no pairs on the device
                        allowed, answered = idx.answer_checks(
                            nodes, q_subj, why, int(q_depth[0])
                        )
                        lmode[answered & allowed] = leo.LM_ALLOW
                        lmode[answered & ~allowed] = leo.LM_DENY
        lmode[err | general] = leo.LM_NONE
        # the cache sees every row the host KNOWS is unanswered; rows the
        # device probe may yet answer keep leopard precedence at collect
        known = None
        if why is not None:
            why[err | general] = leo.WHY_INELIGIBLE
            known = (None, (lmode == leo.LM_ALLOW) | (lmode == leo.LM_DENY))
        return known, (lmode, leo_set, leo_elt, leo_dev), why

    def _encode_fused(self, wave, padded, fast_elig, has_leo, probe):
        """Host half of the fused launch (inside ``_dispatch``'s
        ``check_encode`` span): everything :meth:`_dispatch_fused` hands
        to the device.  The whole tier cascade (leopard probe -> fast
        BFS -> general algebra, with bounded in-program retry lanes)
        compiles into ONE device program (engine/fused.py) with ONE D2H
        fetch at collect.  The general tier is sized here by the rows
        that need it (``gen_lanes``, the padding :meth:`_run_general`
        gives them) and the program compacts them into that many lanes
        itself: still one upload a wave."""
        lmode, leo_set, leo_elt, leo_dev = probe
        n, qpad, general = wave.n, wave.qpad, wave.general
        pad = qpad - n
        qpack = np.stack([
            *padded,
            np.pad(fast_elig, (0, pad)).astype(np.int32),
            np.pad(general, (0, pad)).astype(np.int32),
            np.pad(lmode, (0, pad)),
            np.pad(leo_set, (0, pad), constant_values=-1),
            np.pad(leo_elt, (0, pad), constant_values=-1),
        ]).astype(np.int32)
        # the general tier compiles OUT of an all-fast wave (XLA's compile
        # cost is superlinear in module size: no traced-but-masked
        # skeleton).  The fast tier stays IN a wave of general rows alone:
        # a shape has two programs, not three, and a single AND/NOT Check
        # warms the program that a mixed wave of singles runs.  Retry
        # lanes stay in with their base tier: overflow shows on device only.
        fast_sched = retry_sched = None
        lanes = 0
        if fast_elig.any() or general.any():
            fast_sched = fp.level_schedule(
                qpad, self.frontier, self.arena, self.max_depth, 1,
                self._adaptive_mults(),
            )
            lanes = self.fused_retry_lanes if self.retry_scale > 1 else 0
            if lanes:
                retry_sched = fp.level_schedule(
                    qpad, self.retry_scale * self.frontier,
                    self.retry_scale * self.arena, self.max_depth,
                    self.retry_scale,
                )
        # one program a bucket the general count falls into
        gen = gen_retry = None
        n_general = int(general.sum())
        gen_lanes = wv.general_lanes(n_general, qpad, wave.gen_like)
        if gen_lanes:
            gen = self._gen_schedule(gen_lanes, 1)
            if self.retry_scale > 1 and self.fused_retry_lanes > 0:
                gen_retry = self._gen_schedule(gen_lanes, self.retry_scale)
        g = wave.arrays
        if leo_dev is not None:
            g = dict(g, leo_sets=leo_dev["sets"],
                     leo_elts=leo_dev["elts"], leo_hops=leo_dev["hops"])
        wave.meta = {
            "has_leo": has_leo,
            "fast_levels": len(fast_sched) if fast_sched is not None else 0,
            "flen": len(fast_sched) + fp.folded_levels(fast_sched)
                    if fast_sched is not None else 0,
            "glen": (len(gen[0]) + 2 + len(gen[2])) if gen is not None
                    else 0,
            "gen_fast_b": gen[1] if gen is not None else 0,
            "gen_rows": n_general, "gen_lanes": gen_lanes,
        }
        scheds = dict(
            fast_sched=fast_sched, retry_sched=retry_sched,
            retry_lanes=lanes, gen=gen, gen_retry=gen_retry,
            gen_lanes=gen_lanes,
        )
        return g, qpack, scheds

    def _dispatch_fused(self, wave, g, qpack, scheds) -> None:
        """Enqueue what :meth:`_encode_fused` prepared; the uncollected
        device result goes into ``wave``."""
        wave.fused = fdx.run_fused_wave(
            g, qpack, **scheds,
            max_width=self.max_width, depth_slack=leo.DEPTH_SLACK,
            span=self._span,
        )

    def _cache_consult(self, queries, rest_depth, err, leo_res):
        """Probe the hot-spot shield for every query not already answered
        (encode errors fall to the oracle for their typed error; Leopard
        answers are cheaper than a probe would be).  Returns
        ``(cached, verdicts)`` bool arrays, or None when the cache is off
        or nothing hit.  How fresh an entry must be to serve is decided
        by the cache from the ambient request context (cache/context.py);
        with no context bound it serves exact-at-fence only, which is
        sound for every consistency mode."""
        rc = self.result_cache
        if rc is None:
            return None
        eligible = ~err
        if leo_res is not None:
            eligible &= ~leo_res[1]
        idx = np.flatnonzero(eligible)
        if len(idx) == 0:
            return None
        with self._span("check_cache", rows=len(idx)):
            hits = rc.lookup_many(self._qkeys(queries, idx, rest_depth))
            cached = np.zeros(err.shape[0], bool)
            vals = np.zeros(err.shape[0], bool)
            for i, h in zip(idx, hits):
                if h is not None:
                    cached[i] = True
                    vals[i] = bool(h.value)
        if not cached.any():
            return None
        return cached, vals

    def _cache_fill(self, queries, wave, rest_depth, allowed,
                    skip=None) -> None:
        """Insert this chunk's freshly computed verdicts, stamped with the
        drain cursor captured with the dispatch's sync view.  Oracle-
        fallback verdicts are included — they were computed from the live
        store, which is at least as fresh as the stamp (the stamp is a
        lower bound, never an over-claim).  Leopard-answered queries are
        skipped: the index answers them cheaper than a probe would.
        ``skip`` marks rows whose oracle fallback raised a typed error in
        the per-item-capture path: their ``allowed`` slot is a stale
        default, never a verdict."""
        rc = self.result_cache
        if rc is None:
            return
        fresh = ~wave.err
        if wave.leo_res is not None:
            fresh &= ~wave.leo_res[1]
        if wave.cache_res is not None:
            fresh &= ~wave.cache_res[0]
        if skip is not None:
            fresh &= ~skip
        idx = np.flatnonzero(fresh)
        if len(idx) == 0:
            return
        with self._span("check_cache_fill", rows=len(idx)):
            keys = self._qkeys(queries, idx, rest_depth)
            for i, key in zip(idx, keys):
                rc.insert(key, bool(allowed[i]), wave.cursor)

    def _gen_schedule(self, q: int, boost: int):
        """Static shapes for one fused algebra dispatch (engine/algebra.py).

        The level budget D is FIXED per tier (``gen_levels``, retry at
        ``gen_levels_max``) rather than derived from the loaded config:
        a config-dependent D made every namespace-config variant a brand
        new fused program, and XLA:CPU dies under that compile load (the
        fuzz suite compiles a fresh OPL per seed; see tests/conftest.py
        on the codegen-split segfault).  Typical AND/NOT skeletons are
        shallow — pure subtrees delegate to the BFS instead of consuming
        levels — so tier 1 covers them; a root that exhausts it resolves
        UNKNOWN+over, retries deeper, and only then falls back.
        """
        with self._gen_lock:
            return self._gen_schedule_locked(q, boost)

    def _gen_schedule_locked(self, q: int, boost: int):
        cached = self._gen_sched_cache.get((q, boost))
        if cached is not None:
            return cached
        D = self.gen_levels if boost <= 1 else self.gen_levels_max
        cap = boost * self.gen_arena
        adaptive = (
            boost <= 1
            and self._gen_occ_ema is not None
            and not os.environ.get("KETO_NO_ADAPTIVE")
        )
        if adaptive:
            # direct demand sizing: per-level skeleton capacity = measured
            # tasks-per-root x headroom, half-octave bucketed.  The freeze
            # below is what bounds compile variants, so no rung ladder is
            # needed — and a ladder's coarse steps left the skeleton at
            # near-worst-case sizes (measured ~5x the live demand, with
            # every padded slot paying the multi-probe classification)
            want = self._gen_occ_ema[:D] * self.occ_headroom
            sizes = tuple(
                int(min(_bucket15(max(int(np.ceil(w * q)), 64), 64), cap))
                for w in want
            )
        else:
            sizes = tuple(
                int(min(_bucket15(m * q * boost, 64), cap))
                for m in _gen_mults(D)
            )
        # fast-leaf buffer: measured leaves-per-root x headroom (default 2)
        fmul = 2.0
        if adaptive and self._gen_fast_ema is not None:
            fmul = max(self._gen_fast_ema * self.occ_headroom, 1 / 16)
        f_cap = boost * self.frontier
        a_cap = boost * self.arena
        fast_b = int(min(
            _bucket15(int(np.ceil(fmul * q)) * boost, 256), f_cap
        ))
        if adaptive and self._gen_fast_occ_ema is not None:
            # BFS levels demand-sized in units of roots (stable when
            # fast_b itself adapts); level 0 is the leaf buffer
            fls = [fast_b] + [
                int(min(_bucket15(max(int(np.ceil(w * q)), 64), 64), f_cap))
                for w in self._gen_fast_occ_ema[1:] * self.occ_headroom
            ]
            fast_sched = tuple(
                (fl,
                 fp.PROBE_ONLY_ARENA if i == len(fls) - 1
                 else min(4 * fl if i == 0 else 2 * fl, a_cap))
                for i, fl in enumerate(fls)
            )
        else:
            fast_sched = fp.level_schedule(
                fast_b, f_cap, a_cap, self.max_depth
            )
        vcap = boost * self.vcap
        if adaptive:
            # the visited set serves tainted-rel expansion children only
            # (typically a small fraction of the skeleton); its probe loop
            # pays VS-sized claim scatters every level, so shrink the
            # table toward demand — an overflow is a per-query over bit
            # and a boosted retry, never a wrong verdict
            vcap = int(min(vcap, max(1024, _bucket15(4 * q))))
        out = (sizes, fast_b, fast_sched, vcap)
        if adaptive:
            # FREEZE the first demand-adapted pick: the EMAs keep updating
            # but must never mint another program shape — a schedule flip
            # mid-serving costs a multi-minute compile on the serving
            # path.  Cleared
            # on rebuild (workload regime changes come with new graphs).
            self._gen_sched_cache[(q, boost)] = out
        return out

    def _update_gen_occ(self, occ: np.ndarray, fast_b: int) -> None:
        """Fold one tier-1 algebra dispatch's occupancy vector into the
        EMAs — all in units of active roots, so the feedback stays stable
        as the adapted buffer sizes themselves change."""
        D = self.gen_levels
        roots = float(occ[0])
        if roots <= 0:
            return
        lev = occ[1: D + 1].astype(np.float64) / roots
        fleaves = float(occ[D + 1]) / roots
        focc = occ[D + 2:].astype(np.float64) / roots
        with self._gen_lock:
            if self._gen_occ_ema is None or len(self._gen_occ_ema) != len(lev):
                self._gen_occ_ema = lev
                self._gen_fast_ema = fleaves
                self._gen_fast_occ_ema = focc
            else:
                self._gen_occ_ema = 0.5 * self._gen_occ_ema + 0.5 * lev
                self._gen_fast_ema = 0.5 * self._gen_fast_ema + 0.5 * fleaves
                if len(focc) == len(self._gen_fast_occ_ema):
                    self._gen_fast_occ_ema = (
                        0.5 * self._gen_fast_occ_ema + 0.5 * focc
                    )
                else:
                    self._gen_fast_occ_ema = focc

    def _run_general(self, arrays, enc, gi, boost: int = 1, like: int = 0):
        """Enqueue ONE fused algebra dispatch for the general (AND/NOT)
        roots — whole-chunk batches, no host round-trips (the round-3
        host-stepped interpreter paid a flags sync per 6 levels and
        ~128-task-slots-per-root sub-batching; VERDICT r3 #1).  Returns an
        uncollected (codes, occ, n, fast_b); ``boost`` widens every
        capacity for the retry tier; ``like``: ``wave.general_lanes``' own."""
        n = len(gi)
        qpad = wv.general_lanes(n, self.max_batch, like)
        genc = self._pad(tuple(a[gi] for a in enc), n, qpad)
        active = np.arange(qpad) < n
        qpack = np.stack([*genc, active.astype(np.int32)]).astype(np.int32)
        sizes, fast_b, fast_sched, vcap = self._gen_schedule(qpad, boost)
        codes, occ = self._general_program(
            arrays, qpack, n, boost, sizes=sizes, fast_b=fast_b,
            fast_sched=fast_sched, max_width=self.max_width, vcap=vcap,
        )
        return codes, occ, n, fast_b

    def _general_program(self, arrays, qpack, rows, boost, **shapes):
        """Launch the algebra program at ``_run_general``'s shapes (the
        mesh launches its sharded one)."""
        return alg.run_general_packed_timed(
            arrays, qpack, span=self._span, **shapes)

    def _general_occ(self, occ) -> np.ndarray:
        """The occupancy vector ``_update_gen_occ`` is fed, from what the
        general launch returned beside its codes."""
        return np.asarray(occ)

    def _fast_bits(self, res, k: int) -> wv.FastBits:
        """(found, over, dirty) of the first ``k`` rows of a fast launch:
        one D2H fetch, all three masks.  Nothing dispatched (the closure
        index answered everything eligible): all-zero bits."""
        if res is None:
            return wv.decode_fast(np.zeros(k, np.uint8))
        return wv.decode_fast(np.asarray(res)[:k])

    def _fetch_span(self, phase: str, **fields):
        """``check_collect_sync`` around the cascade's fetches and
        ``check_retry`` around its retries.  (The mesh's launches are
        finished and timed where they are made: it opens neither.)"""
        return self._span(phase, **fields)

    def _fast_retry_cap(self) -> int:
        """Most rows a fast retry is padded to: the boosted program's own
        frontier."""
        return self.retry_scale * self.frontier

    def _after_collect(self, wave, allowed, fallback) -> None:
        """Hook on the cascade's merged verdicts (in place); one chip has
        nothing to add."""

    def _collect(self, wave, retry: bool = True):
        """Sync one wave's results; device-retry the overflow tail of
        either tier (counted first) at ``retry_scale``x caps before any
        oracle fallback.  Returns (allowed, fallback).  The retry runs
        against the wave's own device arrays: a write landing meanwhile
        must not pair these encodings with a newer projection."""
        if wave.meta is not None:
            return self._collect_fused(wave)
        n = wave.n
        if wave.leo_res is not None:
            self._count_leopard(*wave.leo_res, wave.leo_why)
        boosted = retry and self.retry_scale > 1
        g_is = np.zeros(n, bool)
        g_fb = np.zeros(n, bool)
        if wave.gen is not None:
            codes, occ, rows, fast_b = wave.gen
            with self._fetch_span("check_collect_sync"):
                g = wv.decode_general(np.asarray(codes)[:rows])  # one fetch
                self._update_gen_occ(self._general_occ(occ), fast_b)
            again = wv.general_retry_rows(g)
            self.overflow_rows["general"] += int(again.sum())
            if boosted and again.any():
                ri = wave.gi[again]
                with self._fetch_span("check_retry", rows=len(ri)):
                    self.retries += len(ri)
                    rcodes, _, k, _ = self._run_general(
                        wave.arrays, wave.enc, ri, boost=self.retry_scale)
                    wv.take_retry(
                        g, again, wv.decode_general(np.asarray(rcodes)[:k]))
            g_is[wave.gi] = wv.general_allowed(g)
            g_fb[wave.gi] = wv.general_fallback(g)
        with self._fetch_span("check_collect_sync"):
            f = self._fast_bits(wave.fast, n)
            if wave.occ is not None:
                self._take_fast_occ(np.asarray(wave.occ), self.max_depth)
        again = ~(wave.err | wave.general) & wv.fast_retry_rows(f)
        self.overflow_rows["fast"] += int(again.sum())
        if boosted and again.any():
            ri = np.flatnonzero(again)
            k = len(ri)
            with self._fetch_span("check_retry", rows=k):
                rpad = wv.retry_rows(k, self._fast_retry_cap())
                renc = self._pad(tuple(a[ri] for a in wave.enc), k, rpad)
                self.retries += k
                rres, _ = self._run_fast(
                    wave, renc, np.arange(rpad) < k,
                    boost=self.retry_scale, rows=ri,
                )
                wv.take_retry(f, ri, self._fast_bits(rres, k))
        allowed, fallback = wv.merge(
            wave.err, wave.general, g_is, g_fb, f.found,
            wv.fast_fallback(f), wave.leo_res, wave.cache_res,
        )
        self._after_collect(wave, allowed, fallback)
        return allowed, fallback

    def _count_leopard(self, allowed, answered, why) -> None:
        """Leopard's answers, and every row it was asked about by what
        became of it (``why``: closure.WHY_*), are counted where their wave
        is collected, whichever launcher ran it: every counter a wave
        moves after its launch moves on the collecting thread."""
        self.leopard_answered += int(answered.sum())
        self.leopard_hits += int(allowed.sum())
        for outcome, rows in leo.outcomes(why, answered).items():
            self.leopard_rows[outcome] += rows

    def _collect_fused(self, wave):
        """Sync one fused wave: ONE D2H fetch returns the verdict codes
        AND the per-tier attribution masks (engine/fused.py bit layout).
        Decode, feed the occupancy EMAs, move the leopard, retry and
        overflow counters by the returned masks, and write the leopard
        answers into the wave for ``_note_tiers`` and ``_cache_fill``."""
        meta, n = wave.meta, wave.n
        with self._span("check_collect_sync", rows=n):
            packed = np.asarray(wave.fused)  # the wave's single D2H fetch
        self.fused_waves += 1
        self.fused_d2h_fetches += 1
        self.fused_general_rows += meta["gen_rows"]
        self.fused_general_lanes += meta["gen_lanes"]
        for table, g in hashtab.wave_gathers(wave.arrays).items():
            self.fused_probe_gathers[table] += g
        bits = wv.decode_fused(packed[:n])
        # occupancy EMA feeds (absent tiers ship no occupancy at all)
        f_end = wave.qpad + meta["flen"]
        if meta["flen"]:
            self._take_fast_occ(packed[wave.qpad:f_end], meta["fast_levels"])
        if meta["glen"]:
            self._update_gen_occ(
                packed[f_end:f_end + meta["glen"]], meta["gen_fast_b"])
        self.retries += int(bits.retried.sum()) + int(bits.gen_retried.sum())
        for tier, rows in zip(("fast", "general"), wv.fused_overflowed(bits)):
            self.overflow_rows[tier] += int(rows.sum())
        if meta["has_leo"]:
            wave.leo_res = (bits.leo_allow, bits.leo_ans)
            self._count_leopard(*wave.leo_res, wave.leo_why)
        # fast_fb: of the fast-active rows alone (no leopard or cache hit)
        allowed, fallback = wv.merge(
            wave.err, wave.general, wv.general_allowed(bits.general),
            wv.general_fallback(bits.general), bits.found, bits.fast_fb,
            wave.leo_res, wave.cache_res,
        )
        tiers = wv.attribute(wave.err, fallback, wave.leo_res, wave.cache_res)
        tr = self.fused_tier_rows
        tr["cache"] += int(tiers.cache.sum())
        tr["leopard"] += int(tiers.leopard.sum())
        tr["oracle"] += int(tiers.oracle.sum())
        tr["general"] += int((tiers.device & wave.general).sum())
        tr["fastpath"] += int((tiers.device & ~wave.general).sum())
        return allowed, fallback

    def _note_tiers(self, wave, fallback) -> None:
        """Attribute this chunk's verdicts to the tier that answered them
        (request-anatomy tracing + shadow-plane provenance): cache hits,
        Leopard closure answers, oracle fallbacks, and whatever remains on
        the device.  Best-effort — only a request context open on the
        collecting thread receives the notes (the coalescer's dispatch
        thread has none and skips the work entirely)."""
        if flightrec.current() is None:
            return
        if wave.meta is not None:
            # stamp the request's shadow provenance so a divergence
            # localizes to the fused program vs the cascade
            flightrec.note_fused()
        tiers = wv.attribute(wave.err, fallback, wave.leo_res, wave.cache_res)
        for tier in ("cache", "leopard", "oracle"):
            rows = int(getattr(tiers, tier).sum())
            if rows:
                flightrec.note_tier(tier, rows)
        if tiers.device.any():
            self._note_fast_tiers(tiers.device, wave)

    def _note_fast_tiers(self, mask, wave) -> None:
        """Fast-path attribution hook; the mesh engine overrides this to
        split the count by serving shard."""
        flightrec.note_tier("fastpath", int(mask.sum()))

    def _finish_chunk(
        self, queries, wave, rest_depth: int, errs=None, rows=None
    ) -> np.ndarray:
        """Collect one wave's verdicts as a bool array.  With ``errs``
        (the columnar path's per-item contract) a typed oracle error is
        captured into ``errs[rows[i]]`` (``rows``: the batch rows the wave
        holds) instead of aborting it; deadline expiry still propagates:
        batch-wide by design, the handler fans it out as per-item 504s."""
        if wave is None:
            return np.zeros(0, bool)
        allowed, fallback = self._collect(wave)
        self._note_tiers(wave, fallback)
        skip = None
        if fallback.any():
            t_fb = time.perf_counter()
            fell = np.flatnonzero(fallback)
            with self._span("check_oracle_fallback", rows=len(fell)):
                for i in fell:
                    # oracle reproduces the exact verdict or typed error;
                    # a long fallback tail must not outlive the request's
                    # budget
                    deadline.check("oracle fallback")
                    self.fallbacks += 1
                    try:
                        allowed[i] = self.oracle.check_is_member(
                            queries[i], rest_depth
                        )
                    except KetoAPIError as e:
                        if errs is None or isinstance(e, DeadlineExceededError):
                            raise
                        errs[int(i if rows is None else rows[i])] = e
                        if skip is None:
                            skip = np.zeros(allowed.shape[0], bool)
                        skip[i] = True
            self._rpc_fallback_stage("check", time.perf_counter() - t_fb)
        self._cache_fill(queries, wave, rest_depth, allowed, skip=skip)
        return allowed

    def batch_expand(
        self, subjects, rest_depth: int = 0, *, fanout: int = 16,
        cap: int = 65536,
    ):
        """Batched device Expand (SURVEY §7 step 5): one fused dispatch for
        all subject-set roots, host-side exact DFS reassembly.  SubjectID
        roots are leaves without touching the engine (expand/handler.go:
        115-126).  With a write overlay pending, the device still
        enumerates base rows and the assembly merges the overlay's
        membership deltas host-side (expand_device.OverlayMembers) —
        added subject-set subtrees recurse through the sequential engine
        with the shared visited set, so writes stay exactly visible
        without the blanket fall-to-oracle r2 shipped.  The dispatch runs
        on the first rung of level capacities; the roots it overflows run
        again on the full rung (``cap`` a level), and only the roots that
        overflow that too fall back to the sequential oracle expand (live
        store).  ``expand_roots`` counts which of the three answered."""
        from ketotpu.api.types import SubjectID, SubjectSet, Tree, TreeNodeType
        from ketotpu.engine import expand_device as xd
        from ketotpu.engine.oracle import ExpandEngine

        oracle = ExpandEngine(self.store, max_depth=self.max_depth)
        subjects = list(subjects)
        out: List = [None] * len(subjects)
        set_idx = [i for i, s in enumerate(subjects) if isinstance(s, SubjectSet)]
        for i, s in enumerate(subjects):
            if isinstance(s, SubjectID):
                out[i] = Tree(
                    type=TreeNodeType.LEAF,
                    tuple=RelationTuple("", "", "", s),
                )
        if not set_idx:
            # all-SubjectID expands never touch the engine: don't pay the
            # mesh engine's lazy replicated-graph device transfer (and don't
            # stall concurrent checks on the lock) for leaves
            return out
        with self._span("expand_snapshot"), self._sync_lock:
            snap = self._snapshot_locked()
            overlay_active = self._overlay_active
            xarrays = self._expand_arrays()
            ov = (
                xd.OverlayMembers(self._overlay, snap, self._vocab)
                if overlay_active else None
            )
        roots = [subjects[i] for i in set_idx]
        if xarrays is None:
            # mesh replica over budget: the oracle expands from the live
            # store (exact), instead of silently materializing the whole
            # graph on one device
            for i in set_idx:
                self.fallbacks += 1
                out[i] = oracle.build_tree(subjects[i], rest_depth)
            self.expand_roots["oracle"] += len(set_idx)
            return out

        def run(batch, **kw):
            return xd.run_expand(
                xarrays, snap, batch, rest_depth,
                max_depth=self.max_depth, fanout=fanout, cap=cap, ov=ov,
                sub_expand=oracle._build, span=self._span, **kw,
            )

        try:
            faults.inject("device_dispatch")
            trees, over = run(roots, rung="first")
            by = ["first"] * len(roots)
            redo = np.flatnonzero(over)
            if len(redo):
                # padded like the first rung: the full program it warmed
                full, over_full = run(
                    [roots[k] for k in redo], rung="full", pad_to=len(roots)
                )
                for k, tree, o in zip(redo, full, over_full):
                    trees[k], over[k], by[k] = tree, o, "full"
        except KetoAPIError:
            raise
        except Exception:  # noqa: BLE001
            # device expand died wholesale: every root is servable by the
            # sequential oracle (same degraded-health contract as check)
            self._device_failure("expand dispatch")
            t_fb = time.perf_counter()
            with self._span("expand_oracle_fallback", roots=len(set_idx)):
                for i in set_idx:
                    deadline.check("oracle fallback")
                    self.fallbacks += 1
                    out[i] = oracle.build_tree(subjects[i], rest_depth)
            self.expand_roots["oracle"] += len(set_idx)
            self._rpc_fallback_stage("expand", time.perf_counter() - t_fb)
            return out
        for k, i in enumerate(set_idx):
            out[i] = trees[k]
        if over.any():
            t_fb = time.perf_counter()
            with self._span("expand_oracle_fallback", roots=int(over.sum())):
                for k in np.flatnonzero(over):
                    deadline.check("oracle fallback")
                    self.fallbacks += 1
                    by[k] = "oracle"
                    out[set_idx[k]] = oracle.build_tree(
                        roots[k], rest_depth
                    )
            self._rpc_fallback_stage("expand", time.perf_counter() - t_fb)
        for rung in by:
            self.expand_roots[rung] += 1
        return out

    def batch_check_device_only(
        self, queries: Sequence[RelationTuple], rest_depth: int = 0, retry: bool = True
    ):
        """Device verdicts without oracle fallback: (allowed[], fallback_needed[]).
        Test/diagnostic surface — pinned to the unfused cascade, whose
        host-side tiers honor ``retry=False`` individually (the fused
        program's retry lanes are compiled in)."""
        wave = self._dispatch(list(queries), rest_depth, fused=False)
        if wave is None:
            return [], []
        allowed, fallback = self._collect(wave, retry=retry)
        return allowed.tolist(), fallback.tolist()

    def batch_check_block(self, block, rest_depth: int = 0):
        """Columnar batch check (engine/columns.py ColumnBlock): the whole
        batch stays id columns end to end — no per-item Python object on
        the hot path.  Returns ``(allowed bool array, {row: KetoAPIError})``
        with per-item error isolation: a typed oracle error lands in the
        erroring row's slot, never aborts the block.  Deadline expiry
        still raises batch-wide (one budget, handler fans out 504s)."""
        return self.collect(self.submit(block, rest_depth), errs={})

    def _cut(self, ticket: Ticket, cap: int) -> Tuple[int, int]:
        """Cut a ticket's batch into the waves ``submit`` launches, of
        ``cap`` rows at most (``wave.wave_cap`` of this engine's level
        schedule, under ``max_batch``), by the one rule of engine/wave.py
        (``cut``): ``ticket.chunks`` gets each wave's batch rows and
        queries; returns what every wave pads like (``Cut.like``).  A
        batch that is cut is classified here first, whole, so that its
        AND/NOT rows can be dealt evenly (each wave is classified again,
        against the view it is launched on)."""
        queries, n = ticket.queries, len(ticket.queries)
        general = None
        if n > cap:
            with self._span("check_encode", rows=n):
                snap = self._sync_view()[0]
                enc = self._encode(snap, queries, ticket.rest_depth)
                general = self._classify(snap, enc[0], enc[2])[1]
        cut = wv.cut(n, general, cap)
        self.ticket_waves += len(cut.rows)
        if len(cut.rows) < 2:
            ticket.chunks = [(rows, queries) for rows in cut.rows]
            return cut.like
        lanes = wv.general_lanes(
            cut.like[1], wv.wave_rows(cut.like[0], self.frontier))
        take = getattr(queries, "take", None) or (
            lambda rows: [queries[i] for i in rows])
        with self._span("check_cut", rows=n, waves=len(cut.rows),
                        gen_lanes=lanes):
            ticket.chunks = [(rows, take(rows)) for rows in cut.rows]
        return cut.like

    # -- Leopard listing APIs ------------------------------------------------
    #
    # ListObjects / ListSubjects enumerate the closure index (sorted-pair
    # slices, decoded through the vocab) when the touched set ids are
    # clean, and the host oracle (live-store BFS, ketotpu/leopard/
    # hostlist.py) when a deletion marked them dirty or the index is off.
    # Both paths sort lexicographically, so pagination tokens are
    # interchangeable between them.

    def leopard_stats(self) -> dict:
        """Gauge snapshot for observability (keto_leopard_* metrics)."""
        with self._sync_lock:
            idx = self._leopard
            stats = idx.stats() if idx is not None else {
                "pairs": 0.0, "dirty_sets": 0.0, "fallbacks": 0.0,
                "build_s": 0.0, "builds": 0.0,
            }
        stats["answered"] = float(self.leopard_answered)
        stats["hits"] = float(self.leopard_hits)
        stats["list_fallbacks"] = float(self.leopard_list_fallbacks)
        stats["active"] = 1.0 if idx is not None else 0.0
        return stats

    def list_objects(
        self,
        namespace: str,
        relation: str,
        subject,
        *,
        page_size: int = 0,
        page_token: str = "",
    ):
        """Objects o with ``namespace:o#relation`` reaching ``subject``
        through the set-containment closure; (objects, next_page_token)."""
        with self._span("list_objects"):
            sets = None
            with self._sync_lock:
                self._snapshot_locked()
                idx = self._leopard
                if idx is not None:
                    v = self._vocab
                    lo, hi = idx.node_range(
                        v.namespaces.lookup(namespace),
                        v.relations.lookup(relation),
                    )
                    sets = idx.list_sets_of(v.subject_key(subject), lo, hi)
                if sets is not None:
                    obj_of = self._vocab.objects.string
                    objs = sorted(obj_of(idx.node_obj(s)) for s in sets)
            if sets is None:
                self.leopard_list_fallbacks += 1
                t_fb = time.perf_counter()
                objs = leolist.host_list_objects(
                    self.store, namespace, relation, subject
                )
                self._rpc_fallback_stage(
                    "list_objects", time.perf_counter() - t_fb
                )
        return leolist.paginate(objs, page_token, page_size)

    def list_subjects(
        self,
        namespace: str,
        object: str,
        relation: str,
        *,
        page_size: int = 0,
        page_token: str = "",
    ):
        """Subjects reaching ``namespace:object#relation`` through the
        set-containment closure; (subjects, next_page_token)."""
        with self._span("list_subjects"):
            elems = None
            with self._sync_lock:
                self._snapshot_locked()
                idx = self._leopard
                if idx is not None:
                    v = self._vocab
                    elems = idx.list_elements(idx.node_id(
                        v.namespaces.lookup(namespace),
                        v.objects.lookup(object),
                        v.relations.lookup(relation),
                    ))
                if elems is not None:
                    by_uid = {
                        uid: leolist.subject_from_uid(uid)
                        for uid in map(self._vocab.subjects.string, elems)
                    }
            if elems is None:
                self.leopard_list_fallbacks += 1
                t_fb = time.perf_counter()
                by_uid = leolist.host_list_subjects(
                    self.store, namespace, object, relation
                )
                self._rpc_fallback_stage(
                    "list_subjects", time.perf_counter() - t_fb
                )
            keys, next_token = leolist.paginate(
                sorted(by_uid.keys()), page_token, page_size
            )
        return [by_uid[k] for k in keys], next_token
