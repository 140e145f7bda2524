"""Request coalescing: concurrent single checks ride one device dispatch.

The reference amortizes per-check cost with goroutine fan-out inside one
request (`checkgroup/concurrent_checkgroup.go`); the TPU engine amortizes
ACROSS requests instead — a single check costs a full device dispatch
(fixed host-link latency + a compiled program sized for thousands), so
serving concurrent Check RPCs one dispatch each wastes almost all of the
machine.  The coalescer queues single checks for up to ``window``
seconds (or until ``max_pending``) and answers the whole wave with one
dispatch of the underlying engine: ``submit`` on the thread that cut the
wave, ``collect`` on a second one, so the next wave is encoded and
launched while this one is on the device (``batch_check`` for an engine
without the pair).

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


def _gained(after: Dict[str, float], before: Dict[str, float]) -> dict:
    """The entries of ``after`` that grew since ``before``, by how much."""
    grown = {k: v - before.get(k, 0) for k, v in after.items()}
    return {k: d for k, d in grown.items() if d > 0}


def _twice(first, again):
    """``first()``, and ``again()`` where that failed with anything but a
    typed error: ONE bounded whole-batch retry — a transient device /
    runtime hiccup should not error up to max_pending concurrent callers
    when a second dispatch would have succeeded (per-query degradation is
    still avoided: it would serialize the wave on one thread)."""
    try:
        return first()
    except KetoAPIError:
        raise
    except Exception:  # noqa: BLE001
        return again()


def _phases_here(inner) -> Dict[str, float]:
    """The engine phase seconds the calling thread has spent so far."""
    here = getattr(inner, "thread_phase_seconds", None)
    return dict(here()) if here is not None else {}


def _routes(inner, attr: str) -> Optional[np.ndarray]:
    """A mesh engine's cumulative routed rows per shard or per peer host."""
    fn = getattr(inner, attr, None)
    return fn() if fn is not None else None


def _routed(after, before) -> Dict[str, int]:
    """Rows routed between two readings of :func:`_routes`, by index."""
    if after is None:
        return {}
    return {str(i): int(d) for i, d in enumerate(after - before) if d > 0}


def _submit_side(inner) -> tuple:
    """What a wave's submit moves, read on the thread that submits it:
    this thread's phase seconds and, on a mesh, the rows routed to each
    shard and shipped to each peer host."""
    return (_phases_here(inner), _routes(inner, "shard_route_counts"),
            _routes(inner, "peer_route_counts"))


def _collect_side(inner) -> tuple:
    """What a wave's collect moves, read on the thread that collects it:
    this thread's phase seconds, Leopard's answers, the oracle's
    fallbacks and the fused-wave counters (all counted at collect)."""
    return (_phases_here(inner),
            int(getattr(inner, "leopard_answered", 0) or 0),
            int(getattr(inner, "fallbacks", 0) or 0),
            _fused_counts(inner))


class _Slot:
    __slots__ = ("tuple", "depth", "bypass", "event", "result", "error",
                 "t_enq", "t_dispatch", "t_set", "wave", "traceparent",
                 "followers")

    def __init__(self, t: RelationTuple, depth: int, bypass: bool = False):
        self.tuple = t
        self.depth = depth
        self.bypass = bypass
        self.event = threading.Event()
        self.result: Optional[bool] = None
        self.error: Optional[BaseException] = None
        self.t_enq = time.perf_counter()
        self.t_dispatch: Optional[float] = None  # _Group.stamp
        self.t_set: Optional[float] = None  # _Group.wake
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
                 "error", "t_enq", "t_dispatch", "t_set", "wave",
                 "traceparent", "followers")

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
        self.t_set: Optional[float] = None
        self.wave: Optional[int] = None
        self.traceparent: Optional[str] = None
        self.followers = 0  # groups never singleflight; ledger parity


class _Group:
    """One (depth, bypass) group of a cut wave: its scalar slots, its
    column groups, the merged block that carries both (None: scalar slots
    alone, or an inner engine without a block surface) and, from an inner
    engine with the ``submit`` / ``collect`` pair, the launched ticket —
    or the failure that stopped its launch."""

    __slots__ = ("depth", "bypass", "slots", "cgroups", "merged", "ticket",
                 "failure", "t_dispatch")

    def __init__(self, depth: int, bypass: bool, members):
        self.depth = depth
        self.bypass = bypass
        self.slots = [m for m in members if not isinstance(m, _ColumnGroup)]
        self.cgroups = [m for m in members if isinstance(m, _ColumnGroup)]
        self.merged = None
        self.ticket = None
        self.failure: Optional[BaseException] = None
        self.t_dispatch: Optional[float] = None

    def scope(self):
        """Re-bind the cache escape hatch on the calling thread for a
        group of bypass members, so the inner engine's own cache probe
        (at submit) and insert (at collect) honor it.  A fresh scope per
        entry: generator context managers are one-shot."""
        return (cache_context.scope(bypass=True) if self.bypass
                else contextlib.nullcontext())

    def stamp(self, wave_id: int) -> None:
        """The engine's dispatch of this group starts now."""
        self.t_dispatch = time.perf_counter()
        for m in (*self.slots, *self.cgroups):
            m.t_dispatch = self.t_dispatch
            m.wave = wave_id

    def wake(self) -> None:
        """The members are answered: wake their callers, each member
        stamped with the moment the first of them is woken."""
        t_set = time.perf_counter()
        for m in (*self.slots, *self.cgroups):
            m.t_set = t_set
            m.event.set()


def _note_wait(t_enq: float, m, done: float) -> None:
    """A caller's wait on member ``m`` (enqueued at ``t_enq``, returned at
    ``done``) as three stages that add up to it: ``coalesce_wait`` to the
    start of the wave's dispatch (its submit), ``device_compute`` from
    there to the scatter, the waves launched before it included (the
    wave is in the device's queue), ``wake`` from the scatter until the
    caller runs again; no-ops when this thread serves no instrumented
    RPC."""
    flightrec.note_stage("coalesce_wait", m.t_dispatch - t_enq)
    flightrec.note_stage("device_compute", m.t_set - m.t_dispatch)
    flightrec.note_stage("wake", done - m.t_set)
    flightrec.note(wave=m.wave)


class _Cut:
    """A wave from its cut to its filing: what the collector thread hands
    the dispatcher thread."""

    __slots__ = ("wave_id", "members", "rows", "groups", "submitted")

    def __init__(self, wave_id: int, members, rows: int):
        self.wave_id = wave_id
        self.members = members
        self.rows = rows
        self.groups: List[_Group] = []
        #: (phase seconds, shard rows, peer rows) the submit moved
        self.submitted: tuple = ({}, {}, {})


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
        # waves whose submit returned while an earlier wave was not yet
        # collected (keto_coalescer_waves_ahead_total), and how many
        # submitted waves are uncollected right now (under _lock)
        self.waves_ahead = 0
        self._uncollected = 0
        # two threads, one wave apart: the collector thread cuts wave N+1
        # and SUBMITS it (grouping, merged-block build, and the inner
        # engine's encode + launch, for scalar and merged waves alike)
        # while wave N is on the device; the dispatcher thread only
        # COLLECTS (sync, decode, scatter, wake the callers, file the
        # ledger record).  The device's queue then holds the next wave
        # behind the running one, so host encode time is off the wave
        # period.  An inner engine without the submit / collect pair is
        # dispatched whole by the dispatcher thread.  The depth-1 queue is
        # the pair of staging buffers: one wave being collected, one
        # staged, and a third held by the collector at put().
        self._stage: Optional[queue.Queue] = (
            queue.Queue(maxsize=1) if pipeline else None
        )
        # each wave thread's wall time, partitioned into states (seconds,
        # keto_coalescer_thread_seconds{thread,state}; host spans
        # keto/coalesce/<state> during a profiler capture).  Collector:
        # idle (nothing pending), window, prepare, stage_blocked (a wave
        # staged and one in flight); dispatcher: stage_empty, serve, file
        # (waking the callers + the ledger record).  Unpipelined, the
        # collector serves and files too.  ``prepare`` holds the submit.
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
        if slot.t_dispatch is not None:
            _note_wait(slot.t_enq, slot, time.perf_counter())
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
        last = None  # the member whose wave answered last
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
                if slot.t_dispatch is not None and (
                        last is None or slot.t_set > last.t_set):
                    last = slot
            if slot.error is not None:
                # typed per-query error: raise like the inner engine would
                raise slot.error
            results[i] = bool(slot.result)
        if last is not None:
            _note_wait(t0, last, time.perf_counter())
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
        if grp.t_dispatch is not None:
            _note_wait(grp.t_enq, grp, time.perf_counter())
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
                states.enter("prepare", rows=len(self._pending),
                             ahead=self._uncollected)
                wave, self._pending = self._pending, []
                # the wave owns its slots now: identical checks arriving
                # from here on start a fresh flight (the cache, refilled
                # by this wave's collect, catches them instead)
                self._inflight.clear()
            # the view is taken inside submit, after the cut: every row of
            # the wave was enqueued before it, so a write acknowledged
            # before a Check was sent is in the view that answers it
            cut = self._prepare(wave)
            if self._stage is None:
                self._serve(cut, states=states)
            else:
                # put() blocks only when a wave is staged AND one is being
                # collected: both, and this one, are already launched
                states.enter("stage_blocked")
                self._stage.put(cut)

    def _run_dispatch(self) -> None:
        states = self._dispatcher_states
        while True:
            states.enter("stage_empty")
            cut = self._stage.get()
            if cut is None:
                states.close()
                return
            self._serve(cut, states=states)

    def _prepare(self, wave) -> _Cut:
        """The submit half of a wave, on the thread that cut it: number
        it, group it by (depth, bypass), split scalar slots from column
        groups, build each group's merged block and, where the inner
        engine has the ``submit`` / ``collect`` pair, submit every group
        (scalar slots as a tuple list, a group with column groups as its
        merged block) under the group's cache-bypass scope."""
        inner = self.inner
        self.waves += 1
        rows = sum(
            len(s.block) if isinstance(s, _ColumnGroup) else 1 for s in wave
        )
        self.coalesced += rows
        # the ledger is the wave-id authority when present so flight
        # recorder entries (wave=) and /debug/waves join on the same id
        cut = _Cut(
            self.ledger.next_wave_id() if self.ledger is not None
            else self.waves, wave, rows,
        )
        raw: dict = {}
        for s in wave:
            raw.setdefault((s.depth, s.bypass), []).append(s)
        cut.groups = [_Group(*key, members) for key, members in raw.items()]
        inner_bc = getattr(inner, "batch_check_block", None)
        submit = getattr(inner, "submit", None)
        if getattr(inner, "collect", None) is None:
            submit = None
        before = _submit_side(inner)
        for g in cut.groups:
            if g.cgroups and inner_bc is not None:
                parts = []
                if g.slots:
                    # scalar singles ride the merged block: their tuples
                    # ARE the pre-materialized items, so the fold is free
                    parts.append(colmod.ColumnBlock.from_tuples(
                        [s.tuple for s in g.slots]
                    ))
                parts.extend(c.block for c in g.cgroups)
                g.merged = colmod.ColumnBlock.concat(parts)
            batch = (g.merged if g.merged is not None
                     else [s.tuple for s in g.slots])
            if submit is None or not len(batch):
                continue
            g.stamp(cut.wave_id)
            try:
                with g.scope():
                    g.ticket = submit(batch, g.depth)
            except Exception as e:  # noqa: BLE001 - answered for in _serve
                g.failure = e
        after = _submit_side(inner)
        cut.submitted = (
            _gained(after[0], before[0]),
            _routed(after[1], before[1]), _routed(after[2], before[2]),
        )
        if any(g.cgroups for g in cut.groups):
            self.block_waves += 1
        with self._lock:
            if submit is not None and self._uncollected:
                self.waves_ahead += 1
            self._uncollected += 1
        return cut

    def _serve(self, cut: _Cut, *, states: profiler.ThreadStates) -> None:
        """The collect half of a wave: every group's ticket collected
        (or, from an inner engine without the pair, its dispatch made
        now), verdicts and errors scattered to the members, their events
        set, the ledger record filed."""
        states.enter("serve", wave=cut.wave_id, rows=cut.rows)
        before = _collect_side(self.inner)
        device_s = 0.0
        for k, g in enumerate(cut.groups):
            if k:  # the group before left this thread filing
                states.enter("serve", wave=cut.wave_id, rows=cut.rows)
            if g.t_dispatch is None:
                g.stamp(cut.wave_id)
            try:
                self._answer(g)
            finally:
                device_s += time.perf_counter() - g.t_dispatch
                states.enter("file", wave=cut.wave_id)
                g.wake()
        with self._lock:
            self._uncollected -= 1
        if self.ledger is not None:
            try:
                self._file_wave(cut, device_s, before)
            except Exception:  # noqa: BLE001 - diagnostics must never
                pass  # take down the wave worker

    def _first(self, g: _Group, again, errs: Optional[dict] = None):
        """A group's answer: its ticket collected (or the failure of its
        submit raised), or ``again()``, the whole dispatch on this thread,
        for an inner engine without the pair; once more ``again()`` by
        :func:`_twice`'s rule."""
        def first():
            if g.failure is not None:
                raise g.failure
            if g.ticket is None:
                return again()
            return self.inner.collect(g.ticket, errs)

        return _twice(first, again)

    def _answer_each(self, g: _Group) -> None:
        """A typed error aborted the group's batch: answer each scalar
        slot individually, so only the erroring ones raise."""
        with g.scope():
            for s in g.slots:
                try:
                    s.result = bool(
                        self.inner.batch_check([s.tuple], g.depth)[0]
                    )
                except Exception as e:  # noqa: BLE001
                    s.error = e

    def _answer(self, g: _Group) -> None:
        """Answer one group's members.  Never raises: failures land on
        the members."""
        if g.merged is not None:
            self._answer_merged(g)
            return
        for c in g.cgroups:
            # inner engine without a block surface (fakes, the CPU
            # oracle): serve each group through the item shim
            self._answer_via_tuples(c, g)
        slots, depth = g.slots, g.depth
        if not slots:
            return
        try:
            with g.scope():
                verdicts = self._first(g, lambda: self.inner.batch_check(
                    [s.tuple for s in slots], depth
                ))
            for s, v in zip(slots, verdicts):
                s.result = bool(v)
        except KetoAPIError:
            self._answer_each(g)
        except Exception as e:  # noqa: BLE001
            # retry also failed: raise to every caller and let them
            # retry against a (hopefully) recovered engine
            for s in slots:
                s.error = e

    def _answer_merged(self, g: _Group) -> None:
        """ONE columnar dispatch for a (depth, bypass) group's scalar
        slots + column groups; verdicts and typed per-item errors scatter
        back by row offset.  Never raises — failures land on the members
        (scalar-slot semantics match the item-list path: typed batch-wide
        errors re-dispatch singles individually; generic failures after
        the bounded retry error every member)."""
        slots, cgroups, depth = g.slots, g.cgroups, g.depth
        try:
            with g.scope():
                allowed, errs = self._first(
                    g, lambda: self.inner.batch_check_block(g.merged, depth),
                    errs={},
                )
            off = 0
            for s in slots:
                e = errs.get(off)
                if e is not None:
                    s.error = e
                else:
                    s.result = bool(allowed[off])
                off += 1
            for c in cgroups:
                m = len(c.block)
                c.verdicts = allowed[off:off + m].copy()
                c.errors = {
                    i - off: e for i, e in errs.items() if off <= i < off + m
                }
                off += m
        except KetoAPIError as e:
            # batch-wide typed error (deadline, shed): scalar slots retry
            # individually (scalar-wave parity); groups surface the error
            # to their caller, whose handler owns the per-item fan-out
            self._answer_each(g)
            for c in cgroups:
                c.error = e
        except Exception as e:  # noqa: BLE001
            for m in (*slots, *cgroups):
                m.error = e

    def _answer_via_tuples(self, c: _ColumnGroup, g: _Group) -> None:
        """Serve one column group on an inner engine that only speaks item
        lists; same bounded retry as scalar waves.  Never raises."""
        def call():
            return colmod.block_check_via_tuples(self.inner, c.block, g.depth)

        try:
            with g.scope():
                c.verdicts, c.errors = _twice(call, call)
        except Exception as e:  # noqa: BLE001
            c.error = e

    def _file_wave(self, cut: _Cut, device_s: float, before: tuple) -> None:
        """One ledger record per wave: occupancy, waits, device time,
        short-circuit counts, engine phase deltas, slowest traceparents —
        and, when the inner engine is sharded, the per-shard routed-root
        deltas this wave produced (plus per-peer shipped-row deltas on a
        multi-host topology).  Fused-dispatch waves additionally carry
        the per-tier attribution deltas the single D2H fetch returned.
        Each delta is read on the thread that moves it, around its half
        of the wave (``_submit_side`` in ``_prepare``, ``_collect_side``
        here), so a record holds its own wave's and no other's while the
        next wave is submitted beside this one's collect."""
        wave, wave_id = cut.members, cut.wave_id
        phase_before, leo_before, fb_before, (fused_before, ftiers) = before
        phase_now, leo_now, fb_now, (fused_now, tiers_now) = _collect_side(
            self.inner)
        phase_s, shards, peers = cut.submitted
        phase_s = dict(phase_s)
        for k, d in _gained(phase_now, phase_before).items():
            phase_s[k] = phase_s.get(k, 0.0) + d
        phase_ms = {k: round(d * 1000.0, 3) for k, d in phase_s.items()}
        waits = sorted(
            (s.t_dispatch - s.t_enq) for s in wave
            if s.t_dispatch is not None
        )
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
        fused = {
            field: max(0, fused_now[field] - fused_before[field])
            for field in fused_now
        }
        fused["tiers"] = {
            t: int(d) for t, d in _gained(tiers_now, ftiers).items()
        }
        # probe rounds a lookup of the served tables unrolls (their
        # builders' constant, whatever they hold: engine/hashtab.py)
        fused["rounds"] = dict(getattr(self.inner, "probe_rounds", None) or {})
        self.ledger.record({
            "wave": wave_id,
            "size": len(wave),
            # items carried by column groups (a group occupies ONE wave
            # slot however many rows it packs)
            "block_items": sum(
                len(s.block) for s in wave if isinstance(s, _ColumnGroup)
            ),
            "groups": len(cut.groups),
            # device waves the engine cut the groups' tickets into (one a
            # group unless a group outgrows the frontier: engine/wave.py)
            "ticket_waves": sum(
                len(g.ticket.waves) for g in cut.groups
                if g.ticket is not None
            ),
            "window_wait_ms_p50": round(
                waits[len(waits) // 2] * 1000.0, 3
            ) if waits else 0.0,
            "window_wait_ms_max": round(
                waits[-1] * 1000.0, 3
            ) if waits else 0.0,
            "device_ms": round(device_s * 1000.0, 3),
            "singleflight_collapsed": sum(s.followers for s in wave),
            "cache_hits_since_prev": max(0, hits_delta),
            "leopard_answered": max(0, leo_now - leo_before),
            "fallbacks": max(0, fb_now - fb_before),
            "errors": sum(1 for s in wave if s.error is not None),
            "shards": shards,
            "peers": peers,
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
#: (``_run``, ``_run_dispatch``): pending (not yet cut), the wave the
#: collector holds at ``_stage.put``, the staged wave, the wave being
#: collected — the last three already submitted, so already in the
#: device's queue.  Callers that keep coming fill each with a wave, so a
#: front door has to let in this many waves' worth of them before a wave
#: can be full (server/daemon.py).
PLACES = 4
