"""The host waits no span times: a request's wait for a pool thread, and
the pauses that stop every thread at once.

A Check that arrives while all of the front door's threads are parked
inside handlers waits in the executor's queue, where no handler code runs
and so no stage is noted; and a thread that comes back from the device and
cannot run (a garbage collection over a large heap, a starved scheduler, a
lock somebody holds through an index build) looks, from the span around
it, exactly like a slow device.  This module measures both:

* :class:`StampedPool` — the ``ThreadPoolExecutor`` both front doors use.
  It stamps each submit and, when the work starts, leaves the stamp on the
  worker thread; ``flightrec.rpc_recording`` takes it as the request's
  ``t0`` and notes the difference as stage ``pool_wait``
  (``keto_rpc_stage_seconds{op,stage="pool_wait"}``, the flight recorder,
  promoted traces), and the time from the work's start to the context's
  open as stage ``receive``.
* :class:`PauseWatch` — ``keto_host_pause_seconds{cause}``, a counter of
  seconds that only pauses of :data:`PAUSE_MIN_S` and more add to, so
  nothing is observed per request.  Causes: ``gc`` (``gc.callbacks``, one
  collection from start to stop; the callback may run under any lock, so
  it files nothing itself: the probe thread does, a tick later), ``sched`` (a daemon thread sleeps
  :data:`SCHED_TICK_S` and counts how late it wakes, less the collections
  that fell into the sleep; every tick's lateness, however short, also
  adds to :data:`SCHED_LAG_SECONDS` and :data:`SCHED_TICKS`),
  ``store_lock`` (:class:`TimedRLock`, the in-memory store's lock: the
  wait to acquire it, per waiting thread).  A
  pause of :data:`PAUSE_LOG_S` or more logs one line with the cause, the
  seconds, the thread that paused (for ``store_lock`` the one that
  waited, for ``gc`` the one that collected) and the engine spans open at
  the time (:func:`open_spans`).

The watch is a process singleton (:func:`pauses`) like the compile watch:
``gc.callbacks`` and a store's lock know no registry.  Collections and
the scheduler are watched from the first :meth:`PauseWatch.bind` (a bare
engine in a test starts no thread); a lock's waits count from the start.
"""

from __future__ import annotations

import contextlib
import gc
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

#: shorter pauses are not counted (nothing is observed per request)
PAUSE_MIN_S = 0.05
#: longer pauses log a line
PAUSE_LOG_S = 0.25
#: the scheduling probe's sleep
SCHED_TICK_S = 0.02

PAUSE_METRIC = "keto_host_pause_seconds"
_PAUSE_HELP = "seconds of host pauses of 50 ms and more, by cause"

#: every probe tick's lateness (collections taken out) and the ticks, from
#: the first bind (scrape: ``keto_host_sched_lag_seconds_total`` and
#: ``keto_host_sched_ticks_total``): a thread that wakes from a sleep has to
#: get the interpreter back, so the lateness a tick is what each wake-up
#: pays, short ones included
SCHED_LAG_SECONDS = 0.0
SCHED_TICKS = 0

_local = threading.local()

#: thread ident -> names of the spans and thread states open on that thread,
#: outermost first (profiler.Span and profiler.ThreadStates keep it)
open_by_thread: Dict[int, List[str]] = {}


def open_spans() -> Dict[int, List[str]]:
    """The spans open right now, by thread ident (a racy copy: for logs)."""
    return {tid: list(names)
            for tid, names in list(open_by_thread.items()) if names}


# -- lazy builds ---------------------------------------------------------------

#: seconds the host spent building an index on first use, in whatever
#: thread met it first (scrape: ``keto_host_lazy_build_seconds_total{what}``)
LAZY_BUILD_SECONDS: Dict[str, float] = {"vocab_index": 0.0, "store_fwd": 0.0}
_LAZY_SPANS = {"vocab_index": "keto/vocab/index_build",
               "store_fwd": "keto/store/fwd_build"}


@contextlib.contextmanager
def lazy_build(what: str, entries: int):
    """``with lazy_build(what, entries):`` times one such build into
    :data:`LAZY_BUILD_SECONDS` and, in a process that has loaded jax,
    shows it in a capture as a host span with ``entries=``."""
    name = _LAZY_SPANS[what]
    jax = sys.modules.get("jax")
    span = (jax.profiler.TraceAnnotation(name, entries=int(entries))
            if jax is not None else contextlib.nullcontext())
    names = open_by_thread.setdefault(threading.get_ident(), [])
    names.append(name)
    t0 = time.perf_counter()
    try:
        with span:
            yield
    finally:
        LAZY_BUILD_SECONDS[what] += time.perf_counter() - t0
        names.pop()


# -- pool wait -----------------------------------------------------------------


class StampedPool(ThreadPoolExecutor):
    """A thread pool that tells the work how long it waited for a thread:
    while a submitted call runs, :func:`take_pool_stamp` on its thread
    gives ``(submitted, started)`` in ``time.perf_counter`` seconds.

    It also counts itself: ``busy`` is the number of its threads inside a
    call right now and ``ceiling`` the most it will ever start (a
    ``ThreadPoolExecutor`` starts a thread only when a submit finds none
    idle, so an idle pool holds as many as its busiest moment needed).
    ``door`` names the front door it serves (``grpc`` or ``rest``); the
    registry adds the pools of a door up into
    ``keto_frontdoor_pool_busy|max{door}`` at scrape time."""

    def __init__(self, max_workers: int, *, door: str = "",
                 thread_name_prefix: str = ""):
        super().__init__(max_workers, thread_name_prefix=thread_name_prefix)
        self.door = door
        self.ceiling = max_workers
        self.busy = 0
        self._busy_lock = threading.Lock()

    def submit(self, fn, /, *args, **kwargs):
        submitted = time.perf_counter()

        def stamped():
            _local.stamp = (submitted, time.perf_counter())
            with self._busy_lock:
                self.busy += 1
            try:
                return fn(*args, **kwargs)
            finally:
                with self._busy_lock:
                    self.busy -= 1
                _local.stamp = None

        return super().submit(stamped)


def take_pool_stamp() -> Optional[tuple]:
    """``(submitted, started)`` of the pool call this thread runs, once:
    the first request context opened inside the call owns the wait."""
    stamp = getattr(_local, "stamp", None)
    if stamp is not None:
        _local.stamp = None
    return stamp


# -- host pauses ---------------------------------------------------------------


class PauseWatch:
    """Counts the seconds of host pauses by cause (module docstring)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.seconds: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        self._metrics = None
        self._logger = None
        # (seconds of all finished collections, start of the one under
        # way or None): one tuple, so the sched probe reads both at once
        self._gc = (0.0, None)
        self._collections: list = []  # long ones, until they are filed
        self._armed = False

    def bind(self, metrics=None, logger=None) -> None:
        """Wire the watch to a registry's metrics and logger (last bind
        wins) and, the first time, start watching collections and the
        scheduler."""
        with self._lock:
            self._metrics, self._logger = metrics, logger
            arm, self._armed = not self._armed, True
        if arm:
            gc.callbacks.append(self._on_gc)
            threading.Thread(
                target=self._watch_sched, name="keto-pause-watch",
                daemon=True,
            ).start()

    def unbind(self, metrics) -> None:
        """A registry that shuts down takes its metrics and logger back,
        unless a later bind has replaced them."""
        with self._lock:
            if self._metrics is metrics:
                self._metrics = self._logger = None

    def note(self, cause: str, seconds: float, *, thread: int = 0,
             spans: Optional[Dict[int, List[str]]] = None, **detail) -> None:
        """One pause of ``seconds``; dropped under :data:`PAUSE_MIN_S`.
        ``thread`` (an ident) and ``spans`` say who paused and what was
        open then, where that is not the caller, now."""
        if seconds < PAUSE_MIN_S:
            return
        with self._lock:
            self.seconds[cause] = self.seconds.get(cause, 0.0) + seconds
            self.counts[cause] = self.counts.get(cause, 0) + 1
            metrics, logger = self._metrics, self._logger
        if metrics is not None:
            metrics.counter(PAUSE_METRIC, seconds, help=_PAUSE_HELP,
                            cause=cause)
        if logger is not None and seconds >= PAUSE_LOG_S:
            names = {t.ident: t.name for t in threading.enumerate()}
            thread = thread or threading.get_ident()
            spans = open_spans() if spans is None else spans
            logger.warning(
                "host pause: cause=%s seconds=%.3f thread=%s detail=%s "
                "open_spans=%s",
                cause, seconds, names.get(thread, str(thread)), detail,
                {names.get(tid, str(tid)): open_
                 for tid, open_ in spans.items()},
            )

    def _on_gc(self, phase: str, info: dict) -> None:
        # Called by the collector with the interpreter held, on whichever
        # thread allocated last and under whatever locks that thread
        # holds (a metrics or logging lock as likely as none): so this
        # takes no lock and files nothing.  It leaves the collection in a
        # list (an append is atomic) for :meth:`file_collections`.
        total, since = self._gc
        if phase == "start":
            self._gc = (total, time.perf_counter())
            return
        if since is None:  # bound in the middle of this collection
            return
        dt = time.perf_counter() - since
        self._gc = (total + dt, None)
        if dt >= PAUSE_MIN_S:
            self._collections.append((
                dt, info.get("generation"), info.get("collected"),
                threading.get_ident(),
                open_spans() if dt >= PAUSE_LOG_S else {},
            ))

    def file_collections(self) -> None:
        """Count and log the long collections since the last call, as cause
        ``gc`` (the probe thread, every tick: it holds no lock)."""
        while self._collections:
            dt, generation, collected, thread, spans = self._collections.pop(0)
            self.note("gc", dt, thread=thread, spans=spans,
                      generation=generation, collected=collected)

    def _watch_sched(self) -> None:
        global SCHED_LAG_SECONDS, SCHED_TICKS
        while True:
            gc_before = self._gc[0]
            t0 = time.perf_counter()
            time.sleep(SCHED_TICK_S)
            late = self._late(t0, time.perf_counter(), gc_before)
            SCHED_LAG_SECONDS += max(late, 0.0)  # the one thread that adds
            SCHED_TICKS += 1
            self.note("sched", late)
            self.file_collections()

    def _late(self, t0: float, now: float, gc_before: float) -> float:
        """How late a probe sleep begun at ``t0`` ended at ``now``, less
        the collections in it: their seconds are cause ``gc``'s.  The
        collecting thread may hand over the interpreter the moment its
        collection ends, before the "stop" callback has filed it: then it
        still reads as under way here, and is over."""
        total, since = self._gc
        collecting = total - gc_before
        if since is not None:
            collecting += now - max(since, t0)
        return now - t0 - SCHED_TICK_S - collecting


_pauses: Optional[PauseWatch] = None
_pauses_lock = threading.Lock()


def pauses() -> PauseWatch:
    """The process's pause watch."""
    global _pauses
    if _pauses is None:
        with _pauses_lock:
            if _pauses is None:
                _pauses = PauseWatch()
    return _pauses


class TimedRLock:
    """A ``threading.RLock`` that reports to the pause watch how long an
    acquire had to wait (cause ``store_lock``).  An uncontended acquire
    reads no clock."""

    def __init__(self, cause: str = "store_lock"):
        self._lock = threading.RLock()
        self._cause = cause

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        if self._lock.acquire(False):
            return True
        if not blocking:
            return False
        t0 = time.perf_counter()
        got = self._lock.acquire(True, timeout)
        pauses().note(self._cause, time.perf_counter() - t0)
        return got

    def release(self) -> None:
        self._lock.release()

    def __enter__(self) -> bool:
        return self.acquire()

    def __exit__(self, *exc) -> None:
        self._lock.release()
