"""XLA compile observatory: every backend compile counted, labelled, logged.

A stray XLA recompile of a static-shape schedule landing on the serving
path (or inside a timed pass) collapses throughput by orders of magnitude,
and nothing else in the system would notice.  This module turns that
incident class into an alarm: a process-global listener on ``jax.monitoring``'s
``/jax/core/compile/backend_compile_duration`` event counts every
backend compile, attributes it to the engine entry point that triggered
it (host wrappers open a :func:`scope` around their dispatch), emits
``keto_xla_compiles_total{fn}`` / ``keto_xla_compile_seconds``, keeps a
bounded log of compile events (fn, arg-shape signature, duration, wall
time) for ``/debug/compiles``, and logs a LOUD warning when a compile
fires after the engine has declared itself warm.

Design constraints the shape of this module falls out of:

* ``jax.monitoring`` listeners are global and cannot be scoped per
  engine, so the watch is a process singleton (:func:`get`) and engine
  attribution rides a thread-local label stack — the compile event
  fires synchronously on the thread that called the jitted function,
  inside the scope the host wrapper opened.
* Scopes are entered on every dispatch (hot path), so they must cost a
  thread-local append/pop and nothing else: the signature is a lazy
  callable evaluated only when a compile actually fires.
* Unit tests construct engines without a registry; the watch only
  emits metrics/warnings after :meth:`CompileWatch.bind` wires it to a
  live registry (last bind wins — one process, one serving registry).
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional, Union

import jax
from jax import monitoring

# the monitoring event that IS "an XLA compile" (jaxpr trace / MLIR
# lowering events also exist but fire for cache hits on some paths;
# backend_compile only fires when XLA actually builds an executable)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# fired instead of a build when the persistent cache (place_cache) had
# the executable; the compile event above still fires around the lookup,
# with the retrieval's short duration
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

COMPILES_METRIC = "keto_xla_compiles_total"
COMPILE_SECONDS_METRIC = "keto_xla_compile_seconds"

_tls = threading.local()


def _stack() -> List:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


class CompileWatch:
    """Process-wide compile counter + bounded compile log + warm alarm."""

    def __init__(self, log_size: int = 128):
        self._lock = threading.Lock()
        self.compiles_total = 0
        self.compile_seconds_total = 0.0
        self.per_fn: Dict[str, int] = {}
        self.compiles_after_warm = 0
        self.cache_hits = 0  # compiles the persistent cache answered
        self._warm = False
        self._log: deque = deque(maxlen=int(log_size))
        # bound lazily by the serving registry; None in unit tests/bench
        self._metrics = None
        self._logger = None
        self._warn_after_warm = True

    # -- registry seam -------------------------------------------------------

    def bind(self, metrics=None, logger=None, *, warn_after_warm: bool = True,
             log_size: Optional[int] = None) -> None:
        """Wire the watch to a registry's metrics/logger (last bind wins)."""
        with self._lock:
            self._metrics = metrics
            self._logger = logger
            self._warn_after_warm = bool(warn_after_warm)
            if log_size is not None and int(log_size) != self._log.maxlen:
                self._log = deque(self._log, maxlen=int(log_size))

    # -- warm/cold protocol --------------------------------------------------

    @property
    def warm(self) -> bool:
        return self._warm

    def declare_warm(self) -> None:
        """The engine believes every steady-state shape is compiled."""
        self._warm = True

    def declare_cold(self, reason: str = "") -> None:
        """New compiles are legitimate again (snapshot rebuild, resize)."""
        if self._warm and self._logger is not None:
            self._logger.info(
                "compilewatch: engine cold again (%s)", reason or "unspecified"
            )
        self._warm = False

    # -- attribution scope (hot path) ----------------------------------------

    @contextmanager
    def scope(self, fn: str,
              signature: Optional[Union[str, Callable[[], str]]] = None):
        """Attribute compiles fired inside the block to entry point ``fn``.

        ``signature`` describes the arg shapes; pass a zero-arg callable
        to defer formatting until a compile actually fires.
        """
        st = _stack()
        st.append((fn, signature))
        try:
            yield
        finally:
            st.pop()

    # -- listener ------------------------------------------------------------

    def _on_cache_hit(self, event: str, **kwargs) -> None:
        if event == _CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event != _COMPILE_EVENT:
            return
        st = _stack()
        fn, signature = st[-1] if st else ("other", None)
        if callable(signature):
            try:
                signature = signature()
            except Exception:  # noqa: BLE001 - diagnostics never raise
                signature = "?"
        entry = {
            "fn": fn,
            "signature": signature or "",
            "duration_ms": round(float(duration) * 1000.0, 3),
            "ts": round(time.time(), 3),
            "after_warm": self._warm,
        }
        with self._lock:
            self.compiles_total += 1
            self.compile_seconds_total += float(duration)
            self.per_fn[fn] = self.per_fn.get(fn, 0) + 1
            if self._warm:
                self.compiles_after_warm += 1
            self._log.append(entry)
            metrics, logger = self._metrics, self._logger
            warn = self._warm and self._warn_after_warm
        if metrics is not None:
            metrics.counter(
                COMPILES_METRIC, 1,
                help="XLA backend compiles by engine entry point", fn=fn,
            )
            metrics.observe(
                COMPILE_SECONDS_METRIC, float(duration),
                help="XLA backend compile wall seconds", fn=fn,
            )
            if warn:
                metrics.counter(
                    "keto_xla_compiles_after_warm_total", 1,
                    help="compiles after the engine declared itself warm",
                    fn=fn,
                )
        if warn and logger is not None:
            logger.warning(
                "XLA COMPILE AFTER WARM: fn=%s sig=%s duration_ms=%.1f — a "
                "steady-state dispatch hit an uncompiled shape; audit the "
                "static jit args feeding this entry point",
                fn, entry["signature"], entry["duration_ms"],
            )

    # -- introspection -------------------------------------------------------

    def snapshot(self) -> Dict:
        with self._lock:
            return {
                "compiles_total": self.compiles_total,
                "compile_seconds_total": round(self.compile_seconds_total, 6),
                "per_fn": dict(self.per_fn),
                "warm": self._warm,
                "compiles_after_warm": self.compiles_after_warm,
                "cache_hits": self.cache_hits,
                "log": [dict(e) for e in self._log],
            }


_watch: Optional[CompileWatch] = None
_watch_lock = threading.Lock()


def get() -> CompileWatch:
    """The process singleton, listener registered on first use."""
    global _watch
    if _watch is None:
        with _watch_lock:
            if _watch is None:
                w = CompileWatch()
                monitoring.register_event_duration_secs_listener(w._on_event)
                monitoring.register_event_listener(w._on_cache_hit)
                _watch = w
    return _watch


@contextmanager
def scope(fn: str,
          signature: Optional[Union[str, Callable[[], str]]] = None):
    """Module-level convenience: ``with compilewatch.scope("expand", sig):``"""
    with get().scope(fn, signature):
        yield


# -- persistent compile cache --------------------------------------------------

#: where compiled programs persist when the environment names no place: a
#: fixed path inside the checkout (git-ignored).  The directory is part of
#: the cache key, so it must not move between runs — no tempfile, pid or
#: time in it.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def place_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory.

    Every entry point that compiles for the chip (``serve``,
    ``chip_smoke.py``) calls this before its first compile: a cold fused
    wave costs minutes, a cache hit seconds.  ``JAX_COMPILATION_CACHE_DIR``
    wins when set — JAX reads it itself and no directory is set here.  The
    tests never call this (tests/conftest.py says why).

    A program's metadata is made part of its cache key.  By default the
    key ignores it, so a program whose ``jax.named_scope`` names changed
    (engine/fused.py: the tiers the profiler's trace is read by) would
    load the executable an older tree left in the directory, which
    carries the old names or none.  The price: source locations are
    metadata too, so an edit that moves lines in a traced function
    compiles once more."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


@contextmanager
def cache_off():
    """No persistent cache inside the block, whatever the environment says
    (compiles for a described, unattached chip write entries no process
    here can read back, and warn on every later lookup)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
