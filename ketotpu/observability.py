"""Observability: span events, metrics, structured logging.

The reference wires OpenTelemetry + Prometheus + logrus through every layer
(SURVEY §5.1/§5.5).  This module is the dependency-free equivalent:

* **Events** — the semconv span-event vocabulary of `x/events/events.go:14-20`
  (``PermissionsChecked``, ``PermissionsExpanded``, ``RelationtuplesCreated/
  Deleted/Changed``), emitted through ``Tracer.event`` at the same call sites
  (check engine, expand engine, transact handler).
* **Metrics** — a threadsafe counter/histogram registry with Prometheus text
  exposition, served at ``/metrics/prometheus`` on every router and on the
  dedicated metrics port (`registry_default.go:170-182`, `daemon.go:551-566`).
  The device engine records per-batch gauges the SURVEY asks for (batches,
  fallbacks, retries, snapshot rebuilds).
* **Tracer** — span context manager: wall-time histograms per span name plus
  an event sink; ``ketoctx.WithTracerWrapper`` parity = constructor injection
  of a custom Tracer into the Registry.
* **Logger** — stdlib logging with a structured key=value formatter (logrusx
  analog), per-request request logs in the REST router.
"""

from __future__ import annotations

import logging
import math
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, Iterable, List, Optional, Tuple

# -- span events (x/events/events.go:14-20) ---------------------------------

PERMISSIONS_CHECKED = "PermissionsChecked"
PERMISSIONS_EXPANDED = "PermissionsExpanded"
RELATIONTUPLES_CREATED = "RelationtuplesCreated"
RELATIONTUPLES_DELETED = "RelationtuplesDeleted"
RELATIONTUPLES_CHANGED = "RelationtuplesChanged"

_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
            1.0, 2.5, 5.0, 10.0)

#: public histogram bucket bounds (seconds) — the SLO engine snaps its
#: latency target onto one of these so "fraction under target" is exact
BUCKETS = _BUCKETS

#: histogram samples queued before the observe that finds them files them
_FILE_EVERY = 256


# -- W3C trace context (traceparent) -----------------------------------------

def parse_traceparent(value: Optional[str]) -> Optional[Tuple[str, str]]:
    """``00-<32hex traceid>-<16hex spanid>-<flags>`` -> (trace_id, span_id).

    Returns None for anything malformed — a bad header must never fail the
    request, it just starts a fresh trace.
    """
    if not value:
        return None
    parts = value.strip().lower().split("-")
    if len(parts) < 4:
        return None
    _, trace_id, span_id = parts[0], parts[1], parts[2]
    if len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(trace_id, 16), int(span_id, 16)
    except ValueError:
        return None
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return trace_id, span_id


def format_traceparent(trace_id: str, span_id: str) -> str:
    return f"00-{trace_id}-{span_id}-01"


class Metrics:
    """Prometheus-style registry: counters + histograms, text exposition."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], float] = {}
        self._hists: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], List] = {}
        self._gauges: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], float] = {}
        self._help: Dict[str, str] = {}
        # histogram samples not yet filed (observe)
        self._samples: deque = deque()

    def counter(self, name: str, value: float = 1.0, help: str = "", **labels):
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            if help:
                self._help.setdefault(name, help)
            self._counters[key] = self._counters.get(key, 0.0) + value

    def observe(self, name: str, value: float, help: str = "", **labels):
        """One sample of histogram ``name``.  It is queued without the lock
        (a deque's append is atomic) and filed by the next reader, or with
        the others by the observe that finds :data:`_FILE_EVERY` queued: a
        serving thread that waits for the lock has to win the interpreter
        back afterwards, and under load (a request's several stages, 64
        handler threads, gRPC's poller) that wait is what the rate pays."""
        self._samples.append((name, value, help, labels))
        if len(self._samples) >= _FILE_EVERY:
            with self._lock:
                self._file_samples()

    def _file_samples(self) -> None:
        """File the queued histogram samples (the caller holds the lock)."""
        samples = self._samples
        while samples:
            name, value, help, labels = samples.popleft()
            key = (name, tuple(sorted(labels.items())))
            if help:
                self._help.setdefault(name, help)
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = [[0] * (len(_BUCKETS) + 1), 0.0, 0]
            buckets = h[0]
            for i, ub in enumerate(_BUCKETS):
                if value <= ub:
                    buckets[i] += 1
                    break
            else:
                buckets[-1] += 1
            h[1] += value
            h[2] += 1

    def gauge(self, name: str, value: float, help: str = "", **labels):
        """Set (not accumulate) the latest value — device-engine state like
        rebuild counts is owned by the engine and sampled at scrape time."""
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            if help:
                self._help.setdefault(name, help)
            self._gauges[key] = value

    def get_counter(self, name: str, **labels) -> float:
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            return self._counters.get(key, 0.0)

    def counter_total(self, name: str, **match) -> float:
        """Sum over every series of ``name`` whose labels include ``match``
        — unlike :meth:`get_counter` this does not require knowing the
        full label set, so readers survive a series gaining a label."""
        want = set(match.items())
        with self._lock:
            return sum(
                v for (n, labels), v in self._counters.items()
                if n == name and want.issubset(labels)
            )

    def get_gauge(self, name: str, **labels) -> float:
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            return self._gauges.get(key, 0.0)

    def histogram_values(self, name: str) -> Dict[Tuple[Tuple[str, str], ...], Tuple[float, int]]:
        """{label-tuple: (sum, count)} for every series of ``name`` — the
        scrape surface bench.py uses to publish stage/phase breakdowns."""
        with self._lock:
            self._file_samples()
            return {
                labels: (h[1], h[2])
                for (n, labels), h in self._hists.items()
                if n == name
            }

    def histogram_buckets(
        self, name: str
    ) -> Dict[Tuple[Tuple[str, str], ...], Tuple[List[int], float, int]]:
        """{label-tuple: (per-bucket counts incl. +Inf, sum, count)} for
        every series of ``name``.  Bucket bounds are :data:`BUCKETS`; the
        SLO engine reads cumulative-under-target counts off this."""
        with self._lock:
            self._file_samples()
            return {
                labels: (list(h[0]), h[1], h[2])
                for (n, labels), h in self._hists.items()
                if n == name
            }

    @staticmethod
    def _escape_label(value: str) -> str:
        # text format 0.0.4: label values escape backslash, quote, newline
        return (
            str(value)
            .replace("\\", "\\\\")
            .replace('"', '\\"')
            .replace("\n", "\\n")
        )

    @classmethod
    def _fmt_labels(cls, labels: Iterable[Tuple[str, str]], extra: str = "") -> str:
        parts = [f'{k}="{cls._escape_label(v)}"' for k, v in labels]
        if extra:
            parts.append(extra)
        return "{" + ",".join(parts) + "}" if parts else ""

    def exposition(self) -> str:
        """Prometheus text format 0.0.4."""
        lines: List[str] = []
        with self._lock:
            self._file_samples()
            names = sorted(
                {n for n, _ in self._counters}
                | {n for n, _ in self._hists}
                | {n for n, _ in self._gauges}
            )
            for name in names:
                if name in self._help:
                    lines.append(f"# HELP {name} {self._help[name]}")
                ctr_items = [(k, v) for k, v in self._counters.items() if k[0] == name]
                if ctr_items:
                    lines.append(f"# TYPE {name} counter")
                    for (n, labels), v in sorted(ctr_items):
                        fv = int(v) if float(v).is_integer() else v
                        lines.append(f"{name}{self._fmt_labels(labels)} {fv}")
                gauge_items = [
                    (k, v) for k, v in self._gauges.items() if k[0] == name
                ]
                if gauge_items:
                    lines.append(f"# TYPE {name} gauge")
                    for (n, labels), v in sorted(gauge_items):
                        fv = int(v) if float(v).is_integer() else v
                        lines.append(f"{name}{self._fmt_labels(labels)} {fv}")
                hist_items = [(k, v) for k, v in self._hists.items() if k[0] == name]
                if hist_items:
                    lines.append(f"# TYPE {name} histogram")
                    for (n, labels), (buckets, total, count) in sorted(hist_items):
                        acc = 0
                        for i, ub in enumerate(_BUCKETS):
                            acc += buckets[i]
                            le = self._fmt_labels(labels, f'le="{ub}"')
                            lines.append(f"{name}_bucket{le} {acc}")
                        acc += buckets[-1]
                        le = self._fmt_labels(labels, 'le="+Inf"')
                        lines.append(f"{name}_bucket{le} {acc}")
                        lab = self._fmt_labels(labels)
                        lines.append(f"{name}_sum{lab} {total}")
                        lines.append(f"{name}_count{lab} {count}")
        return "\n".join(lines) + "\n"


class Tracer:
    """Span timings + events; inject a subclass for custom exporters
    (the ketoctx.WithTracerWrapper seam, `ketoctx/options.go:42-45`)."""

    def __init__(self, metrics: Optional[Metrics] = None,
                 logger: Optional[logging.Logger] = None):
        self.metrics = metrics
        self.logger = logger

    @contextmanager
    def span(self, name: str, _parent: Optional[str] = None, **attrs):
        """``_parent`` is an incoming W3C ``traceparent`` header; the base
        tracer has no trace ids so it only times — exporters adopt it."""
        t0 = time.perf_counter()
        try:
            yield self
        finally:
            dt = time.perf_counter() - t0
            if self.metrics is not None:
                self.metrics.observe(
                    "keto_span_duration_seconds", dt,
                    help="span wall time", span=name,
                )

    def current_traceparent(self) -> Optional[str]:
        """traceparent for the innermost open span on this thread (None when
        the tracer keeps no ids) — injected into the worker wire protocol so
        OTLP traces stitch across the process boundary."""
        return None

    def event(self, name: str, **attrs):
        """Span-event emission (x/events/events.go AddEvent sites)."""
        if self.metrics is not None:
            self.metrics.counter(
                "keto_events_total", 1, help="span events emitted", event=name
            )
        if self.logger is not None and self.logger.isEnabledFor(logging.DEBUG):
            kv = " ".join(f"{k}={v}" for k, v in attrs.items())
            self.logger.debug("event %s %s", name, kv)


class _KVFormatter(logging.Formatter):
    def format(self, record: logging.LogRecord) -> str:
        base = super().format(record)
        fields = getattr(record, "fields", None)
        if fields:
            kv = " ".join(f"{k}={v}" for k, v in sorted(fields.items()))
            return f"{base} {kv}"
        return base


def make_logger(name: str = "ketotpu", level: str = "info") -> logging.Logger:
    """Structured logger (logrusx analog): level from config, kv fields via
    ``logger.info(..., extra={"fields": {...}})``."""
    logger = logging.getLogger(name)
    if not logger.handlers:
        h = logging.StreamHandler()
        h.setFormatter(
            _KVFormatter("%(asctime)s %(levelname)s %(name)s %(message)s")
        )
        logger.addHandler(h)
        logger.propagate = False
    logger.setLevel(getattr(logging, level.upper(), logging.INFO))
    return logger
