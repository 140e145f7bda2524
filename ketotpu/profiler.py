"""On-demand device profiling: ``POST /debug/profile?seconds=N``.

Once the flight recorder has named the slow request, the wave ledger has
named its wave, and the compile log has ruled recompiles out, the last
step of the runbook is a real device trace.  This module wraps
``jax.profiler`` trace capture behind a config gate so an operator can
pull an N-second trace from a LIVE serving process without restarting it
with profiling flags.

Safety properties the REST handler relies on:

* **Config-gated** — disabled by default (``observability.profiler
  .enabled``); a probe against a production box that nobody armed
  returns 403, it does not start writing trace files.
* **One capture at a time** — ``jax.profiler`` keeps global state; a
  second concurrent start would corrupt the first capture.  The lock is
  non-blocking: a busy profiler answers 409 immediately.
* **Bounded** — ``seconds`` is clamped to ``max_seconds``; a typo'd
  ``seconds=3600`` cannot pin the capture thread for an hour.

The capture is read against the program's own host spans, which this
module also defines: :class:`Span` (one timed block: an engine phase) and
:class:`ThreadStates` (a partition of one thread's wall time: the
coalescer's two threads).  Both open a ``jax.profiler.TraceAnnotation``,
which costs a flag read outside a capture and, during one, lands in the
same ``.xplane.pb`` on the same clock as the device's ``XLA Ops``.  So a
capture runs with the Python tracer off (it slowed the host it measured)
unless the operator asks for stacks with ``?python=1``.
"""

from __future__ import annotations

import contextlib
import os
import tempfile
import threading
import time
from typing import Callable, Optional

from jax.profiler import (
    ProfileOptions,
    TraceAnnotation,
    start_trace,
    stop_trace,
)

from ketotpu import hostwaits

# thread ident -> names of the spans and states open on that thread: kept
# where the host-pause log line that quotes them is written, in a module
# that does not import jax (the front doors' workers import it)
_open = hostwaits.open_by_thread


def null_span(name: str, **fields):
    """Stands in for an engine's ``_span`` where a dispatcher is called
    without one (tests, bench, the retry tier inside ``check_retry``)."""
    return contextlib.nullcontext()


class Span:
    """``with Span(name, done, **fields):`` one timed block on this
    thread, as a trace annotation ``name`` carrying ``fields``; its wall
    seconds go to ``done(seconds)`` when it ends."""

    __slots__ = ("name", "_done", "_ann", "_t0")

    def __init__(self, name: str, done: Callable[[float], None], **fields):
        self.name = name
        self._done = done
        self._ann = TraceAnnotation(name, **fields)

    def __enter__(self) -> "Span":
        _open.setdefault(threading.get_ident(), []).append(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        _open[threading.get_ident()].pop()
        self._done(dt)


class ThreadStates:
    """One thread's wall time as a sequence of named states, each an
    annotation ``<prefix><state>``: from the first :meth:`enter` to
    :meth:`close` the thread is in exactly one, so the seconds handed to
    ``sink(state, seconds)`` add up to its wall time.  ``enter`` and
    ``close`` belong to the owning thread; :meth:`flush` may come from any
    (a scrape hands over the seconds of the state still open)."""

    def __init__(self, prefix: str, sink: Callable[[str, float], None]):
        self._prefix = prefix
        self._sink = sink
        self._lock = threading.Lock()
        self._state: Optional[str] = None
        self._ann: Optional[TraceAnnotation] = None
        self._t0 = 0.0

    def enter(self, state: Optional[str], **fields) -> None:
        """Leave the state the thread is in (its seconds go to the sink)
        for ``state``; None leaves the last one."""
        names = _open.setdefault(threading.get_ident(), [])
        with self._lock:
            now = time.perf_counter()
            if self._state is not None:
                self._ann.__exit__(None, None, None)
                names.pop()
                self._sink(self._state, now - self._t0)
            self._state, self._t0 = state, now
        if state is not None:
            names.append(self._prefix + state)
            self._ann = TraceAnnotation(self._prefix + state, **fields)
            self._ann.__enter__()

    def close(self) -> None:
        self.enter(None)

    def flush(self) -> None:
        with self._lock:
            if self._state is not None:
                now = time.perf_counter()
                self._sink(self._state, now - self._t0)
                self._t0 = now


class ProfilerDisabled(RuntimeError):
    """Profiling is not armed in config (`observability.profiler.enabled`)."""


class ProfilerBusy(RuntimeError):
    """A capture is already in progress (jax.profiler state is global)."""


class DeviceProfiler:
    """Config-gated, single-flight jax.profiler trace capture."""

    def __init__(self, enabled: bool = False, out_dir: str = "",
                 max_seconds: float = 60.0):
        self.enabled = bool(enabled)
        self.out_dir = out_dir or ""
        self.max_seconds = float(max_seconds)
        self._lock = threading.Lock()
        self.captures = 0
        self.last_artifact: Optional[str] = None

    def capture(self, seconds: float, python: bool = False) -> dict:
        """Block for ``seconds`` (clamped) of trace capture; returns the
        artifact metadata ``{path, seconds, started_ts, python}``.  The
        host tracer (annotations, runtime calls) is always on; ``python``
        adds the Python tracer's call stacks, which slow the host."""
        if not self.enabled:
            raise ProfilerDisabled(
                "device profiling is disabled; set "
                "observability.profiler.enabled=true to arm it"
            )
        seconds = max(0.1, min(float(seconds), self.max_seconds))
        if not self._lock.acquire(blocking=False):
            raise ProfilerBusy("a profile capture is already in progress")
        try:
            base = self.out_dir or os.path.join(
                tempfile.gettempdir(), "keto-tpu-profiles"
            )
            os.makedirs(base, exist_ok=True)
            started = time.time()
            path = os.path.join(base, f"profile-{int(started)}")
            options = ProfileOptions()
            options.python_tracer_level = 1 if python else 0
            start_trace(path, profiler_options=options)
            try:
                time.sleep(seconds)
            finally:
                stop_trace()
            self.captures += 1
            self.last_artifact = path
            return {
                "path": path,
                "seconds": seconds,
                "started_ts": round(started, 3),
                "python": bool(python),
            }
        finally:
            self._lock.release()
