"""Request coalescing: concurrent single checks ride one device dispatch.

The reference amortizes per-check cost with goroutine fan-out inside one
request (`checkgroup/concurrent_checkgroup.go`); the TPU engine amortizes
ACROSS requests instead — a single check costs a full device dispatch
(fixed host-link latency + a compiled program sized for thousands), so
serving concurrent Check RPCs one dispatch each wastes almost all of the
machine.  The coalescer queues single checks for up to ``window``
seconds (or until ``max_pending``) and answers the whole wave with one
``batch_check`` call on the underlying engine.

Semantics are unchanged: per-query typed errors (the oracle's client
errors) are re-raised in the calling thread; other queries in the same
wave are unaffected.  ``batch_check`` calls pass straight through — they
are already batched — and every other attribute proxies to the wrapped
engine, so the registry seam (`check.EngineProvider`) sees the same
surface.
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ketotpu import deadline, flightrec, profiler
from ketotpu.api.types import (
    DeadlineExceededError,
    KetoAPIError,
    RelationTuple,
    TooManyRequestsError,
)
from ketotpu.cache import check_key as cache_check_key
from ketotpu.cache import context as cache_context
from ketotpu.engine import columns as colmod

#: the engine's fused-wave counters -> their per-wave delta's field in a
#: wave-ledger entry's ``fused`` group
_FUSED_COUNTERS = {
    "fused_waves": "waves",
    "fused_d2h_fetches": "d2h_fetches",
    "fused_general_rows": "general_rows",
    "fused_general_lanes": "general_lanes",
}


def _fused_counts(inner) -> Tuple[Dict[str, int], Dict[str, int]]:
    """(ledger field -> counter, tier -> rows) of ``inner`` right now;
    zeros for an engine without the fused dispatch."""
    counts = {
        field: int(getattr(inner, attr, 0) or 0)
        for attr, field in _FUSED_COUNTERS.items()
    }
    return counts, dict(getattr(inner, "fused_tier_rows", None) or {})


class _Slot:
    __slots__ = ("tuple", "depth", "bypass", "event", "result", "error",
                 "t_enq", "t_dispatch", "wave", "traceparent", "followers")

    def __init__(self, t: RelationTuple, depth: int, bypass: bool = False):
        self.tuple = t
        self.depth = depth
        self.bypass = bypass
        self.event = threading.Event()
        self.result: Optional[bool] = None
        self.error: Optional[BaseException] = None
        self.t_enq = time.perf_counter()
        self.t_dispatch: Optional[float] = None  # set by the wave worker
        self.wave: Optional[int] = None
        # wave-ledger cross-link: the enqueuing RPC's trace id, and how
        # many identical pending checks singleflight-parked on this slot
        self.traceparent: Optional[str] = None
        self.followers = 0


class _ColumnGroup:
    """One whole columnar batch riding the wave as a single slot-group:
    ONE event for the batch, verdicts come back as a bool array and typed
    per-item errors as a row-indexed dict (engine/columns.py contract) —
    no per-item futures, no per-item Python objects."""

    __slots__ = ("block", "depth", "bypass", "event", "verdicts", "errors",
                 "error", "t_enq", "t_dispatch", "wave", "traceparent",
                 "followers")

    def __init__(self, block, depth: int, bypass: bool = False):
        self.block = block
        self.depth = depth
        self.bypass = bypass
        self.event = threading.Event()
        self.verdicts: Optional[np.ndarray] = None
        self.errors: Dict[int, KetoAPIError] = {}
        self.error: Optional[BaseException] = None
        self.t_enq = time.perf_counter()
        self.t_dispatch: Optional[float] = None
        self.wave: Optional[int] = None
        self.traceparent: Optional[str] = None
        self.followers = 0  # groups never singleflight; ledger parity


class CoalescingEngine:
    """check_is_member batching facade over a (device) check engine."""

    def __init__(self, inner, *, window: float = 0.002,
                 max_pending: int = 4096,
                 batch_max: int = 0,
                 default_timeout: float = 30.0,
                 cache=None, metrics=None, ledger=None,
                 pipeline: bool = True):
        self.inner = inner
        self.window = window
        self.max_pending = max_pending
        # batches up to this size join the wave machinery alongside
        # concurrent singles (one shared device dispatch); larger batches
        # — already device-sized — pass straight through.  0 disables.
        self.batch_max = batch_max
        # wave ledger (ketotpu/waveledger.py): one record per dispatched
        # wave, filed on the worker thread; None = no ledger (direct use)
        self.ledger = ledger
        self._last_cache_hits = 0
        # hot-spot shield: probe before admission (a hit skips the wave
        # window entirely), and collapse identical pending checks onto one
        # slot — the Zanzibar lock-table dedup at the batching seam
        self.cache = cache
        self.metrics = metrics
        self._inflight: dict = {}  # (tuple-str, depth) -> pending _Slot
        # budget for callers with no explicit deadline: no slot may wait
        # forever — a wedged dispatch must surface as DEADLINE_EXCEEDED,
        # not as every serving thread hanging (<= 0 disables the bound)
        self.default_timeout = default_timeout
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._pending: List[_Slot] = []
        self._closed = False
        self.waves = 0  # observability: coalesced dispatch count
        self.coalesced = 0  # observability: queries served via waves
        self.shed = 0  # observability: queries refused on backlog
        self.deadline_exceeded = 0  # observability: slot waits timed out
        self.singleflight_collapsed = 0  # observability: follower joins
        self.cache_hits = 0  # observability: checks served pre-admission
        self.batch_ingested = 0  # observability: batch items ridden on waves
        self.block_waves = 0  # observability: waves carrying column groups
        # double-buffered dispatch: the collector thread cuts wave N+1 and
        # does its host-side prep (grouping, merged-block build, vocab
        # pre-encode) WHILE the dispatcher thread drives wave N through
        # the device — host encode time leaves the wave cadence.  The
        # depth-1 queue is the pair of staging buffers: one wave in
        # flight, one staged.
        self._stage: Optional[queue.Queue] = (
            queue.Queue(maxsize=1) if pipeline else None
        )
        # each wave thread's wall time, partitioned into states (seconds,
        # keto_coalescer_thread_seconds{thread,state}; host spans
        # keto/coalesce/<state> during a profiler capture).  Collector:
        # idle (nothing pending), window, prepare, stage_blocked (a wave
        # staged and one in flight); dispatcher: stage_empty, serve, file
        # (waking the callers + the ledger record).  Unpipelined, the
        # collector serves and files too.
        self.thread_seconds: Dict[Tuple[str, str], float] = {}
        self._collector_states = self._thread_states("collector")
        self._dispatcher_states = self._thread_states("dispatcher")
        self._worker = threading.Thread(
            target=self._run, name="keto-coalescer", daemon=True
        )
        self._worker.start()
        if self._stage is not None:
            self._dispatcher = threading.Thread(
                target=self._run_dispatch, name="keto-wave-dispatch",
                daemon=True,
            )
            self._dispatcher.start()

    # -- engine surface ------------------------------------------------------

    def check(self, r: RelationTuple, rest_depth: int = 0) -> bool:
        return self.check_is_member(r, rest_depth)

    def check_is_member(self, r: RelationTuple, rest_depth: int = 0) -> bool:
        # X-Keto-Cache: bypass rides a thread-local that would not survive
        # the hop onto the wave thread; the slot carries the flag and the
        # wave worker re-binds the scope around the dispatch, so a bypassed
        # check still gets the deadline-bounded slot wait (a wedged device
        # must answer DEADLINE_EXCEEDED, not block the calling thread)
        bypass = cache_context.bypassed()
        if self.cache is not None and not bypass:
            # pre-admission probe: a hit skips the wave window (the whole
            # point of the shield — hot keys should not pay the coalesce
            # latency, let alone a device dispatch).  The request context
            # is still bound on this thread, so token/latest floors apply.
            t_probe = time.perf_counter()
            hit = self.cache.lookup(cache_check_key(r, rest_depth))
            flightrec.note_stage("cache", time.perf_counter() - t_probe)
            if hit is not None:
                self.cache_hits += 1
                flightrec.note_tier("cache")
                return bool(hit.value)
        budget = deadline.remaining()
        if budget is None:
            budget = self.default_timeout if self.default_timeout > 0 else None
        if budget is not None and budget <= 0:
            self.deadline_exceeded += 1
            flightrec.note_stage("deadline", 0.0)
            raise DeadlineExceededError(
                "deadline exceeded before check was enqueued"
            )
        flight_key = (str(r), rest_depth)
        collapsed = False
        with self._wake:
            if self._closed:
                # the worker is gone; never strand the caller on a dead
                # queue — answer directly on the wrapped engine
                return bool(self.inner.check_is_member(r, rest_depth))
            slot = None if bypass else self._inflight.get(flight_key)
            if slot is not None:
                # singleflight: an identical check is already pending —
                # park on ITS slot instead of occupying a second batch
                # slot; the wave worker's verdict fans out to everyone
                collapsed = True
                self.singleflight_collapsed += 1
                slot.followers += 1
            else:
                if len(self._pending) >= self.max_pending:
                    # backlog saturated: shed NOW rather than queue behind
                    # a wave the device may never drain in time
                    self.shed += 1
                    flightrec.note_stage("shed", 0.0)
                    raise TooManyRequestsError(
                        f"check backlog full ({self.max_pending} pending)"
                    )
                slot = _Slot(r, rest_depth, bypass=bypass)
                slot.traceparent = flightrec.current_traceparent()
                self._pending.append(slot)
                if not bypass:
                    # bypass slots never publish into the flight table: a
                    # bypassed check must be recomputed, and later twins
                    # must not read its slot as a cache substitute
                    self._inflight[flight_key] = slot
                self._wake.notify()
        if collapsed and self.metrics is not None:
            self.metrics.counter(
                "keto_singleflight_collapsed_total", 1,
                help="checks served by another caller's in-flight "
                     "computation",
            )
        if not slot.event.wait(budget):
            waited = time.perf_counter() - slot.t_enq
            self.deadline_exceeded += 1
            flightrec.note_stage("deadline", waited)
            # the slot stays owned by the wave worker — it will set the
            # event into the void; this caller is gone
            raise DeadlineExceededError(
                f"check did not complete within {budget:.3f}s "
                f"(waited {waited:.3f}s)"
            )
        # stage decomposition for the RPC that enqueued us: queue wait is
        # enqueue -> wave cut, device compute is wave cut -> wakeup (both
        # no-ops when this thread isn't serving an instrumented RPC)
        done = time.perf_counter()
        if slot.t_dispatch is not None:
            flightrec.note_stage("coalesce_wait", slot.t_dispatch - slot.t_enq)
            flightrec.note_stage("device_compute", done - slot.t_dispatch)
            flightrec.note(wave=slot.wave)
        if slot.error is not None:
            raise slot.error
        return bool(slot.result)

    def batch_check(
        self, queries: Sequence[RelationTuple], rest_depth: int = 0
    ) -> List[bool]:
        n = len(queries)
        if n == 0 or self.batch_max <= 0 or n > self.batch_max:
            # device-sized batches are already amortized — pass through
            return self.inner.batch_check(queries, rest_depth)
        bypass = cache_context.bypassed()
        results: List[Optional[bool]] = [None] * n
        todo = list(range(n))
        if self.cache is not None and not bypass:
            t_probe = time.perf_counter()
            hits = self.cache.lookup_many(
                [cache_check_key(q, rest_depth) for q in queries]
            )
            flightrec.note_stage("cache", time.perf_counter() - t_probe)
            todo = []
            for i, hit in enumerate(hits):
                if hit is not None:
                    self.cache_hits += 1
                    results[i] = bool(hit.value)
                else:
                    todo.append(i)
            if len(todo) < n:
                flightrec.note_tier("cache", n - len(todo))
            if not todo:
                return [bool(v) for v in results]
        # ONE budget shared by every item in the batch: read once here,
        # burned down across the slot waits — items never re-arm timers
        budget = deadline.remaining()
        if budget is None:
            budget = self.default_timeout if self.default_timeout > 0 else None
        if budget is not None and budget <= 0:
            self.deadline_exceeded += 1
            flightrec.note_stage("deadline", 0.0)
            raise DeadlineExceededError(
                "deadline exceeded before batch was enqueued"
            )
        t0 = time.perf_counter()
        entries: List[tuple] = []  # (result index, slot)
        tp = flightrec.current_traceparent()
        with self._wake:
            if self._closed or len(self._pending) + len(todo) > self.max_pending:
                # worker gone, or no room to coalesce — the batch is
                # already a batch, dispatch it directly (the front-door
                # AdmissionController is the shedding authority here)
                entries = None
            else:
                for i in todo:
                    q = queries[i]
                    flight_key = (str(q), rest_depth)
                    slot = None if bypass else self._inflight.get(flight_key)
                    if slot is not None:
                        # singleflight across AND within the batch: twins
                        # park on the pending slot's verdict
                        self.singleflight_collapsed += 1
                        slot.followers += 1
                    else:
                        slot = _Slot(q, rest_depth, bypass=bypass)
                        slot.traceparent = tp
                        self._pending.append(slot)
                        if not bypass:
                            self._inflight[flight_key] = slot
                    entries.append((i, slot))
                self.batch_ingested += len(todo)
                self._wake.notify()
        if entries is None:
            verdicts = self.inner.batch_check(
                [queries[i] for i in todo], rest_depth
            )
            for i, v in zip(todo, verdicts):
                results[i] = bool(v)
            return [bool(v) for v in results]
        waited: set = set()
        last_dispatch = None
        wave_id = None
        for i, slot in entries:
            if id(slot) not in waited:
                waited.add(id(slot))
                left = None
                if budget is not None:
                    left = budget - (time.perf_counter() - t0)
                    if left <= 0 or not slot.event.wait(left):
                        self.deadline_exceeded += 1
                        flightrec.note_stage(
                            "deadline", time.perf_counter() - t0
                        )
                        raise DeadlineExceededError(
                            f"batch did not complete within {budget:.3f}s"
                        )
                else:
                    slot.event.wait()
                if slot.t_dispatch is not None:
                    last_dispatch = slot.t_dispatch
                    wave_id = slot.wave
            if slot.error is not None:
                # typed per-query error: raise like the inner engine would
                raise slot.error
            results[i] = bool(slot.result)
        done = time.perf_counter()
        if last_dispatch is not None:
            flightrec.note_stage("coalesce_wait", last_dispatch - t0)
            flightrec.note_stage("device_compute", done - last_dispatch)
            flightrec.note(wave=wave_id)
        return [bool(v) for v in results]

    def check_block(self, block, rest_depth: int = 0):
        """Columnar batch admission: the whole block joins the wave as ONE
        slot-group and the caller blocks on one event.  Returns
        ``(verdicts bool array, {row: KetoAPIError})``.  Oversized blocks
        (already device-sized), a closed coalescer, or a saturated backlog
        dispatch directly — the front-door AdmissionController is the
        shedding authority for batches, so no 429 is raised here."""
        n = len(block)
        if n == 0:
            return np.zeros(0, bool), {}
        if self.batch_max <= 0 or n > self.batch_max:
            return self._block_direct(block, rest_depth)
        bypass = cache_context.bypassed()
        budget = deadline.remaining()
        if budget is None:
            budget = self.default_timeout if self.default_timeout > 0 else None
        if budget is not None and budget <= 0:
            self.deadline_exceeded += 1
            flightrec.note_stage("deadline", 0.0)
            raise DeadlineExceededError(
                "deadline exceeded before batch was enqueued"
            )
        grp = _ColumnGroup(block, rest_depth, bypass=bypass)
        grp.traceparent = flightrec.current_traceparent()
        with self._wake:
            if self._closed or len(self._pending) + n > self.max_pending:
                direct = True
            else:
                self._pending.append(grp)
                self.batch_ingested += n
                self._wake.notify()
                direct = False
        if direct:
            return self._block_direct(block, rest_depth)
        if not grp.event.wait(budget):
            waited = time.perf_counter() - grp.t_enq
            self.deadline_exceeded += 1
            flightrec.note_stage("deadline", waited)
            raise DeadlineExceededError(
                f"batch did not complete within {budget:.3f}s "
                f"(waited {waited:.3f}s)"
            )
        done = time.perf_counter()
        if grp.t_dispatch is not None:
            flightrec.note_stage("coalesce_wait", grp.t_dispatch - grp.t_enq)
            flightrec.note_stage("device_compute", done - grp.t_dispatch)
            flightrec.note(wave=grp.wave)
        if grp.error is not None:
            raise grp.error
        return grp.verdicts, grp.errors

    def _block_direct(self, block, rest_depth: int):
        bc = getattr(self.inner, "batch_check_block", None)
        if bc is not None:
            return bc(block, rest_depth)
        return colmod.block_check_via_tuples(self.inner, block, rest_depth)

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def _thread_states(self, thread: str) -> profiler.ThreadStates:
        def sink(state: str, seconds: float) -> None:
            key = (thread, state)
            self.thread_seconds[key] = (
                self.thread_seconds.get(key, 0.0) + seconds
            )
            if self.metrics is not None:
                self.metrics.counter(
                    "keto_coalescer_thread_seconds", seconds,
                    help="wall seconds of each coalescer thread by state",
                    thread=thread, state=state,
                )

        return profiler.ThreadStates("keto/coalesce/", sink)

    def flush_thread_states(self) -> None:
        """Hand the seconds of the states still open to the counter (a
        scrape calls this, so a window's delta adds up to its length)."""
        self._collector_states.flush()
        self._dispatcher_states.flush()

    def close(self) -> None:
        with self._wake:
            self._closed = True
            self._wake.notify()
        # defining close() here shadows __getattr__ forwarding, so retire
        # the wrapped engine explicitly (its background compactor thread
        # must be joined before daemon shutdown)
        inner_close = getattr(self.inner, "close", None)
        if callable(inner_close):
            inner_close()

    # -- worker --------------------------------------------------------------

    def _run(self) -> None:
        states = self._collector_states
        while True:
            states.enter("idle")
            with self._wake:
                while not self._pending and not self._closed:
                    self._wake.wait()
                if self._closed and not self._pending:
                    states.close()
                    if self._stage is not None:
                        self._stage.put(None)  # retire the dispatcher
                    return
                states.enter("window")
                # wave window: let concurrent callers pile on for the FULL
                # window (every enqueue notifies, so loop on the deadline
                # rather than trusting a single wait)
                deadline = time.monotonic() + self.window
                while (
                    len(self._pending) < self.max_pending
                    and not self._closed
                ):
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._wake.wait(remaining)
                states.enter("prepare", rows=len(self._pending))
                wave, self._pending = self._pending, []
                # the wave owns its slots now: identical checks arriving
                # from here on start a fresh flight (the cache, refilled
                # by this wave's dispatch, catches them instead)
                self._inflight.clear()
            if self._stage is None:
                self._serve(wave, states=states)
            else:
                # double-buffer handoff: prep (grouping + merged-block
                # build + vocab pre-encode) runs here on the collector
                # while the dispatcher drives the PREVIOUS wave; put()
                # blocks only when a wave is staged AND one is in flight
                prepared = self._prepare(wave)
                states.enter("stage_blocked")
                self._stage.put((wave, prepared))

    def _run_dispatch(self) -> None:
        states = self._dispatcher_states
        while True:
            states.enter("stage_empty")
            item = self._stage.get()
            if item is None:
                states.close()
                return
            wave, prepared = item
            self._serve(wave, prepared, states=states)

    def _prepare(self, wave) -> dict:
        """Host-side wave prep, off the dispatch critical path: group by
        (depth, bypass), split scalar slots from column groups, build the
        merged block per group, and pre-encode it against the engine's
        current vocabulary (append-only ids: anything resolved now is
        still exact at dispatch; misses refresh then)."""
        inner_bc = getattr(self.inner, "batch_check_block", None)
        raw: dict = {}
        for s in wave:
            raw.setdefault((s.depth, s.bypass), []).append(s)
        prepared = {}
        for key, members in raw.items():
            slots = [m for m in members if not isinstance(m, _ColumnGroup)]
            cgroups = [m for m in members if isinstance(m, _ColumnGroup)]
            merged = None
            if cgroups and inner_bc is not None:
                parts = []
                if slots:
                    # scalar singles ride the merged block: their tuples
                    # ARE the pre-materialized items, so the fold is free
                    parts.append(colmod.ColumnBlock.from_tuples(
                        [s.tuple for s in slots]
                    ))
                parts.extend(g.block for g in cgroups)
                merged = colmod.ColumnBlock.concat(parts)
                vocab = getattr(self.inner, "_vocab", None)
                if vocab is not None:
                    try:
                        merged.encode_for(vocab)
                    except Exception:  # noqa: BLE001 - prep is advisory;
                        pass  # the dispatch encode is the authority
            prepared[key] = (slots, cgroups, merged)
        return prepared

    def _serve(self, wave, prepared: Optional[dict] = None, *,
               states: profiler.ThreadStates) -> None:
        self.waves += 1
        # the ledger is the wave-id authority when present so flight
        # recorder entries (wave=) and /debug/waves join on the same id
        wave_id = (
            self.ledger.next_wave_id() if self.ledger is not None
            else self.waves
        )
        rows = sum(
            len(s.block) if isinstance(s, _ColumnGroup) else 1 for s in wave
        )
        self.coalesced += rows
        states.enter("serve", wave=wave_id, rows=rows)
        # engine counter/phase deltas around the dispatches: only one
        # thread dispatches waves (the collector, or the dispatcher when
        # pipelining), so the deltas attribute cleanly
        inner = self.inner
        leo_before = int(getattr(inner, "leopard_answered", 0) or 0)
        fb_before = int(getattr(inner, "fallbacks", 0) or 0)
        phase_before = dict(getattr(inner, "phase_seconds", None) or {})
        # fused tiered dispatch (engine/fused.py): per-wave deltas of the
        # fused-wave count, its D2H fetches (the single-fetch invariant is
        # checked as waves == fetches), the general tier's rows and lanes
        # (how full the tier ran) and the per-tier row attribution
        fused_before = _fused_counts(inner)
        # per-shard wave accounting (mesh serving): routed-root deltas
        # across this wave's dispatches land in the ledger entry
        routes_fn = getattr(inner, "shard_route_counts", None)
        shards_before = routes_fn() if routes_fn is not None else None
        # per-peer wave accounting (multi-host mesh): rows shipped to
        # each peer host across this wave's dispatches
        peers_fn = getattr(inner, "peer_route_counts", None)
        peers_before = peers_fn() if peers_fn is not None else None
        device_s = 0.0
        if prepared is None:
            prepared = self._prepare(wave)
        if any(cg for _, cg, _ in prepared.values()):
            self.block_waves += 1
        for k, ((depth, byp), (slots, cgroups, merged)) in enumerate(
                prepared.items()):
            if k:  # the group before left this thread filing
                states.enter("serve", wave=wave_id, rows=rows)
            t_dispatch = time.perf_counter()
            for s in slots:
                s.t_dispatch = t_dispatch
                s.wave = wave_id
            for g in cgroups:
                g.t_dispatch = t_dispatch
                g.wave = wave_id
            # re-bind the escape hatch on THIS thread for bypass slots so
            # the inner engine's own cache probe/insert honor it (fresh
            # scope per entry — generator context managers are one-shot)
            def _ctx(byp=byp):
                return (cache_context.scope(bypass=True) if byp
                        else contextlib.nullcontext())
            try:
                if merged is not None:
                    self._dispatch_merged(slots, cgroups, merged, depth, _ctx)
                    continue
                for g in cgroups:
                    # inner engine without a block surface (fakes, the CPU
                    # oracle): serve each group through the item shim
                    self._dispatch_group_via_tuples(g, depth, _ctx)
                if not slots:
                    continue
                with _ctx():
                    # one bounded whole-batch retry: a transient device /
                    # runtime hiccup should not error up to max_pending
                    # concurrent callers when a second dispatch would have
                    # succeeded (per-query degradation is still avoided —
                    # it would serialize the wave on this one thread)
                    for attempt in range(2):
                        try:
                            verdicts = self.inner.batch_check(
                                [s.tuple for s in slots], depth
                            )
                            break
                        except KetoAPIError:
                            raise
                        except Exception:  # noqa: BLE001
                            if attempt:
                                raise
                    for s, v in zip(slots, verdicts):
                        s.result = bool(v)
            except KetoAPIError:
                # a typed client error aborted the batch: answer each query
                # individually so only the erroring ones raise
                with _ctx():
                    for s in slots:
                        try:
                            s.result = bool(
                                self.inner.batch_check([s.tuple], depth)[0]
                            )
                        except Exception as e:  # noqa: BLE001
                            s.error = e
            except Exception as e:  # noqa: BLE001
                # retry also failed: raise to every caller and let them
                # retry against a (hopefully) recovered engine
                for s in slots:
                    s.error = e
            finally:
                device_s += time.perf_counter() - t_dispatch
                states.enter("file", wave=wave_id)
                for s in slots:
                    s.event.set()
                for g in cgroups:
                    g.event.set()
        if self.ledger is not None:
            try:
                shard_delta = None
                if shards_before is not None:
                    after = routes_fn()
                    shard_delta = {
                        str(i): int(d)
                        for i, d in enumerate(after - shards_before)
                        if d > 0
                    }
                peer_delta = None
                if peers_before is not None:
                    pafter = peers_fn()
                    peer_delta = {
                        str(i): int(d)
                        for i, d in enumerate(pafter - peers_before)
                        if d > 0
                    }
                self._file_wave(
                    wave_id, wave, len(prepared), device_s,
                    leo_before, fb_before, phase_before,
                    shards=shard_delta, peers=peer_delta,
                    fused_before=fused_before,
                )
            except Exception:  # noqa: BLE001 - diagnostics must never
                pass  # take down the wave worker

    def _dispatch_merged(self, slots, cgroups, merged, depth, _ctx) -> None:
        """ONE columnar dispatch for a (depth, bypass) group's scalar
        slots + column groups; verdicts and typed per-item errors scatter
        back by row offset.  Never raises — failures land on the members
        (scalar-slot semantics match the item-list path: typed batch-wide
        errors re-dispatch singles individually; generic failures after
        the bounded retry error every member)."""
        try:
            with _ctx():
                for attempt in range(2):
                    try:
                        allowed, errs = self.inner.batch_check_block(
                            merged, depth
                        )
                        break
                    except KetoAPIError:
                        raise
                    except Exception:  # noqa: BLE001
                        if attempt:
                            raise
            off = 0
            for s in slots:
                e = errs.get(off)
                if e is not None:
                    s.error = e
                else:
                    s.result = bool(allowed[off])
                off += 1
            for g in cgroups:
                m = len(g.block)
                g.verdicts = allowed[off:off + m].copy()
                g.errors = {
                    i - off: e for i, e in errs.items() if off <= i < off + m
                }
                off += m
        except KetoAPIError as e:
            # batch-wide typed error (deadline, shed): scalar slots retry
            # individually (scalar-wave parity); groups surface the error
            # to their caller, whose handler owns the per-item fan-out
            with _ctx():
                for s in slots:
                    try:
                        s.result = bool(
                            self.inner.batch_check([s.tuple], depth)[0]
                        )
                    except Exception as e2:  # noqa: BLE001
                        s.error = e2
            for g in cgroups:
                g.error = e
        except Exception as e:  # noqa: BLE001
            for s in slots:
                s.error = e
            for g in cgroups:
                g.error = e

    def _dispatch_group_via_tuples(self, g, depth, _ctx) -> None:
        """Serve one column group on an inner engine that only speaks item
        lists; same bounded retry as scalar waves.  Never raises."""
        try:
            with _ctx():
                for attempt in range(2):
                    try:
                        g.verdicts, g.errors = colmod.block_check_via_tuples(
                            self.inner, g.block, depth
                        )
                        return
                    except KetoAPIError:
                        raise
                    except Exception:  # noqa: BLE001
                        if attempt:
                            raise
        except Exception as e:  # noqa: BLE001
            g.error = e

    def _file_wave(self, wave_id: int, wave: List[_Slot], n_groups: int,
                   device_s: float, leo_before: int, fb_before: int,
                   phase_before: dict, shards: Optional[dict] = None,
                   peers: Optional[dict] = None,
                   fused_before: Optional[tuple] = None) -> None:
        """One ledger record per wave: occupancy, waits, device time,
        short-circuit counts, engine phase deltas, slowest traceparents —
        and, when the inner engine is sharded, the per-shard routed-root
        deltas this wave produced (plus per-peer shipped-row deltas on a
        multi-host topology).  Fused-dispatch waves additionally carry
        the per-tier attribution deltas the single D2H fetch returned."""
        inner = self.inner
        waits = sorted(
            (s.t_dispatch - s.t_enq) for s in wave
            if s.t_dispatch is not None
        )
        phase_after = dict(getattr(inner, "phase_seconds", None) or {})
        phase_ms = {
            k: round((phase_after[k] - phase_before.get(k, 0.0)) * 1000.0, 3)
            for k in phase_after
            if phase_after[k] - phase_before.get(k, 0.0) > 0
        }
        # cache hits answer BEFORE admission (they never occupy a slot);
        # the delta since the previous wave is the short-circuit traffic
        # this wave's window interval absorbed
        hits_now = self.cache_hits
        hits_delta = hits_now - self._last_cache_hits
        self._last_cache_hits = hits_now
        slow = sorted(
            (s for s in wave
             if s.t_dispatch is not None and s.traceparent is not None),
            key=lambda s: s.t_dispatch - s.t_enq, reverse=True,
        )[:3]
        fused = dict.fromkeys(_FUSED_COUNTERS.values(), 0)
        fused["tiers"] = {}
        if fused_before is not None:
            before, ftiers = fused_before
            after, now = _fused_counts(inner)
            for field in after:
                fused[field] = max(0, after[field] - before[field])
            fused["tiers"] = {
                t: d for t, d in (
                    (t, int(now[t]) - int(ftiers.get(t, 0))) for t in now
                ) if d > 0
            }
        self.ledger.record({
            "wave": wave_id,
            "size": len(wave),
            # items carried by column groups (a group occupies ONE wave
            # slot however many rows it packs)
            "block_items": sum(
                len(s.block) for s in wave if isinstance(s, _ColumnGroup)
            ),
            "groups": n_groups,
            "window_wait_ms_p50": round(
                waits[len(waits) // 2] * 1000.0, 3
            ) if waits else 0.0,
            "window_wait_ms_max": round(
                waits[-1] * 1000.0, 3
            ) if waits else 0.0,
            "device_ms": round(device_s * 1000.0, 3),
            "singleflight_collapsed": sum(s.followers for s in wave),
            "cache_hits_since_prev": max(0, hits_delta),
            "leopard_answered": max(
                0, int(getattr(inner, "leopard_answered", 0) or 0)
                - leo_before
            ),
            "fallbacks": max(
                0, int(getattr(inner, "fallbacks", 0) or 0) - fb_before
            ),
            "errors": sum(1 for s in wave if s.error is not None),
            "shards": shards or {},
            "peers": peers or {},
            "fused": fused,
            "phase_ms": phase_ms,
            "slowest": [
                {
                    "traceparent": s.traceparent,
                    "wait_ms": round((s.t_dispatch - s.t_enq) * 1000.0, 3),
                }
                for s in slow
            ],
            "ts": round(time.time(), 3),
        })


#: The places a single check waits between its enqueue and its answer
#: (``_run``, ``_run_dispatch``): pending, the wave the collector holds at
#: ``_stage.put``, the staged wave, the wave in flight.  Callers that keep
#: coming fill each with a wave, so a front door has to let in this many
#: waves' worth of them before a wave can be full (server/daemon.py).
PLACES = 4
