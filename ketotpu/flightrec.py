"""Per-RPC stage clock + flight recorder.

Round 5 shipped 833 RPS at p99 78 ms through the daemon against 87k
checks/s inside the engine, and no profile of where an RPC's
milliseconds go had ever been published (VERDICT weak #1).  This module
is the decomposition layer:

* **Stage clock** — a thread-local per-request context opened at the
  transport edge (REST ``_serve``, gRPC servicer, worker host).  Layers
  below (coalescer, device engine, remote engine) call
  :func:`note_stage` without holding any reference to the registry; each
  stage lands in ``keto_rpc_stage_seconds{op,stage}`` and in the
  request's stage vector.  When no context is open (direct engine use,
  bench inner loops) every note is a no-op costing one thread-local
  read.
* **Flight recorder** — a lock-cheap record of the N slowest recent
  requests (stage vector + wave/batch id + verdict).  The hot path
  compares against an unlocked floor and returns without taking the
  lock for the overwhelming majority of requests; only candidate
  entries (slower than the current N-th slowest) pay for the lock and a
  tiny sort.  Served at ``/debug/flight-recorder`` on the metrics port
  and dumped by ``keto-tpu status --debug``.
"""

from __future__ import annotations

import os
import secrets
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import Dict, List, Optional

from ketotpu import hostwaits
from ketotpu.observability import (
    Tracer,
    format_traceparent,
    parse_traceparent,
)

_local = threading.local()

STAGE_METRIC = "keto_rpc_stage_seconds"
_STAGE_HELP = "per-RPC stage wall time decomposition"

#: per-request end-to-end latency bucketed by op and outcome — the SLO
#: engine's sole feed (slo.py): availability = ok / all outcomes,
#: latency compliance = ok requests under the target bucket / ok total
OUTCOME_METRIC = "keto_request_outcome_seconds"
_OUTCOME_HELP = "request latency by op and outcome (ok/shed/error)"

#: per-request span-buffer cap — a runaway fan-out must not grow an
#: unbounded timeline; the rpc-level span is always appended last
MAX_SPANS = 128


class FlightRecorder:
    """Ring of the N slowest recent requests, cheap on the hot path."""

    def __init__(self, capacity: int = 32, max_age_s: float = 600.0):
        self.capacity = int(capacity)
        self.max_age_s = float(max_age_s)
        self._lock = threading.Lock()
        self._entries: List[Dict] = []  # kept sorted slowest-first
        # unlocked admission floor: requests faster than the current N-th
        # slowest are rejected without taking the lock (stale reads only
        # admit a few extra candidates, never lose a slow one)
        self._floor = 0.0

    def record(self, total_s: float, entry: Dict) -> None:
        if len(self._entries) >= self.capacity and total_s <= self._floor:
            return
        now = time.time()
        entry = dict(entry)
        entry["total_ms"] = round(total_s * 1000.0, 3)
        entry["ts"] = round(now, 3)
        with self._lock:
            horizon = now - self.max_age_s
            kept = [e for e in self._entries if e["ts"] >= horizon]
            kept.append(entry)
            kept.sort(key=lambda e: e["total_ms"], reverse=True)
            del kept[self.capacity:]
            self._entries = kept
            self._floor = (
                kept[-1]["total_ms"] / 1000.0
                if len(kept) >= self.capacity else 0.0
            )

    def snapshot(self) -> List[Dict]:
        now = time.time()
        horizon = now - self.max_age_s
        with self._lock:
            return [dict(e) for e in self._entries if e["ts"] >= horizon]


class _ReqCtx:
    __slots__ = ("op", "detail", "t0", "stages", "info", "metrics",
                 "recorder", "tracer", "trace", "trace_id", "spans")

    def __init__(self, op, detail, t0, metrics, recorder, tracer, trace):
        self.op = op
        self.detail = detail
        self.t0 = t0
        self.stages: Dict[str, float] = {}
        self.info: Dict = {}
        self.metrics = metrics
        self.recorder = recorder
        self.tracer = tracer
        self.trace = trace  # TraceStore, or None when tracing is off
        self.trace_id: Optional[str] = None
        self.spans: List[Dict] = []


def current() -> Optional[_ReqCtx]:
    return getattr(_local, "ctx", None)


def note_stage(stage: str, seconds: float) -> None:
    """Record one stage of the current RPC; no-op outside an RPC."""
    ctx = getattr(_local, "ctx", None)
    if ctx is None:
        return
    ctx.stages[stage] = ctx.stages.get(stage, 0.0) + seconds
    if ctx.metrics is not None:
        ctx.metrics.observe(
            STAGE_METRIC, seconds, help=_STAGE_HELP, op=ctx.op, stage=stage,
        )
    if ctx.trace is not None and len(ctx.spans) < MAX_SPANS:
        # every stage note doubles as a timeline span (epoch-stamped so
        # spans from different processes align on one clock)
        t1 = time.time()
        ctx.spans.append({
            "name": stage,
            "pid": os.getpid(),
            "t0": round(t1 - seconds, 6),
            "t1": round(t1, 6),
            "ms": round(seconds * 1000.0, 3),
        })


def note(**info) -> None:
    """Attach info (wave id, verdict, ...) to the current RPC's record."""
    ctx = getattr(_local, "ctx", None)
    if ctx is not None:
        ctx.info.update(info)


def note_span(name: str, t0: float, t1: float, **attrs) -> None:
    """Append one explicit timeline span (epoch seconds) to the current
    request's span buffer; no-op outside an RPC or with tracing off."""
    ctx = getattr(_local, "ctx", None)
    if ctx is None or ctx.trace is None or len(ctx.spans) >= MAX_SPANS:
        return
    span = {
        "name": name,
        "pid": os.getpid(),
        "t0": round(t0, 6),
        "t1": round(t1, 6),
        "ms": round((t1 - t0) * 1000.0, 3),
    }
    span.update(attrs)
    ctx.spans.append(span)


def merge_spans(spans) -> None:
    """Adopt spans shipped from another process (owner → worker over the
    framed wire) into the current request's timeline."""
    ctx = getattr(_local, "ctx", None)
    if ctx is None or ctx.trace is None or not spans:
        return
    room = MAX_SPANS - len(ctx.spans)
    for s in spans[:room]:
        if isinstance(s, dict):
            ctx.spans.append(dict(s))


def export_spans() -> List[Dict]:
    """Copy of the current request's span buffer plus a provisional
    rpc-level span covering the open context — what the owner ships back
    to the worker inside the wire response."""
    ctx = getattr(_local, "ctx", None)
    if ctx is None or ctx.trace is None:
        return []
    t1 = time.time()
    total = time.perf_counter() - ctx.t0
    out = [dict(s) for s in ctx.spans]
    out.append({
        "name": f"rpc.{ctx.op}",
        "pid": os.getpid(),
        "t0": round(t1 - total, 6),
        "t1": round(t1, 6),
        "ms": round(total * 1000.0, 3),
    })
    return out


def note_tier(tier: str, n: int = 1) -> None:
    """Attribute ``n`` verdicts of the current RPC to an answering tier
    (cache / leopard / fastpath / mesh-shard-N / oracle).  The dominant
    tier lands in ``info["tier"]`` — the shadow plane's provenance."""
    if n <= 0:
        return
    ctx = getattr(_local, "ctx", None)
    if ctx is None:
        return
    tiers = ctx.info.setdefault("tiers", {})
    tiers[tier] = tiers.get(tier, 0) + int(n)
    ctx.info["tier"] = max(tiers.items(), key=lambda kv: kv[1])[0]


def note_fused() -> None:
    """Mark the current RPC as served by a fused-dispatch wave
    (engine/fused.py): shadow divergence records carry the flag so a
    lying verdict localizes to the fused program vs the tier cascade."""
    ctx = getattr(_local, "ctx", None)
    if ctx is not None:
        ctx.info["fused"] = True


def force_promote(reason: str) -> None:
    """Mark the current request's trace for promotion regardless of its
    latency (e.g. a synchronous shadow divergence)."""
    ctx = getattr(_local, "ctx", None)
    if ctx is not None:
        ctx.info["force_promote"] = reason


def current_traceparent() -> Optional[str]:
    """traceparent of the current RPC's span, for wire propagation.

    An exporting tracer answers with the innermost open span's id; the
    base tracer keeps no ids, so fall back to the traceparent captured at
    RPC entry — the worker wire and the wave ledger then still carry the
    caller's trace id instead of nothing."""
    ctx = getattr(_local, "ctx", None)
    if ctx is None:
        return None
    tp = (
        ctx.tracer.current_traceparent() if ctx.tracer is not None else None
    )
    return tp or ctx.info.get("traceparent")


def await_send(call) -> None:
    """Bind the gRPC call (its servicer context) this thread serves now,
    or unbind it with ``None`` (``AccessLogInterceptor``, around every
    unary handler): the first request context that closes under it times
    stage ``send``."""
    _local.call = call


def _time_send(metrics, op: str, t_end: float) -> None:
    """Stage ``send`` of the bound gRPC call, once: from ``t_end``, the
    close of its request context, until the server ends the RPC —
    ``add_callback`` runs on gRPC's poller thread once the status and the
    message are out and the call is closed (serialization, the batch that
    sends them, the poller's turn).  The context has closed by then, so
    the stage is observed into ``keto_rpc_stage_seconds`` alone: it is
    not part of the request's total nor of its span buffer."""
    call = getattr(_local, "call", None)
    if call is None or metrics is None:
        return
    _local.call = None
    call.add_callback(lambda: metrics.observe(
        STAGE_METRIC, time.perf_counter() - t_end, help=_STAGE_HELP,
        op=op, stage="send",
    ))


@contextmanager
def rpc_recording(registry, op: str, *, traceparent: Optional[str] = None,
                  detail: str = "", t0: Optional[float] = None):
    """Open the per-request stage context (transport edge only).

    Collects stage notes from every layer underneath and files the
    request with the flight recorder on exit.  An exporting tracer or an
    embedder's ``tracer_wrapper`` also gets an ``rpc.<op>`` span (adopting
    the caller's W3C traceparent so OTLP traces stitch across worker
    processes); the base tracer, which keeps no ids, does not: its span
    would only time again what ``keto_request_outcome_seconds`` holds.
    Re-entrant: a context already open on this thread (e.g. worker host
    inside a serving thread) wins and this call is a pass-through.  Where
    a front door's pool (hostwaits.StampedPool) ran this thread's call,
    the request starts when the call was submitted: its wait for a thread
    is stage ``pool_wait`` and part of the total, as it is of the
    client's, and the time from the thread's start to this context's
    open (gRPC's receive, the interceptors, the handler's first lines)
    is stage ``receive``.  Under a bound gRPC call (:func:`await_send`)
    the close arms stage ``send``.
    """
    if getattr(_local, "ctx", None) is not None:
        yield
        return
    stamp = hostwaits.take_pool_stamp()
    if stamp is not None:
        t0 = stamp[0]
    metrics = registry.metrics()
    recorder = registry.flight_recorder()
    tracer = registry.tracer()
    trace_store = getattr(registry, "trace_store", None)
    trace = trace_store() if trace_store is not None else None
    ctx = _ReqCtx(op, detail, t0 if t0 is not None else time.perf_counter(),
                  metrics, recorder, tracer, trace)
    _local.ctx = ctx
    if stamp is not None:
        note_stage("pool_wait", stamp[1] - stamp[0])
    span = (nullcontext() if type(tracer) is Tracer
            else tracer.span(f"rpc.{op}", _parent=traceparent, detail=detail))
    try:
        with span:
            # capture the trace id while the span is OPEN (the recorder
            # files the entry after it closes, when an exporting tracer
            # no longer answers): the span's own id when the tracer mints
            # one, else the caller's incoming header — either joins the
            # flight-recorder entry to its OTLP trace and wave record
            tp = tracer.current_traceparent() or traceparent
            if not tp and trace is not None:
                # the base tracer keeps no ids: mint one so the span
                # buffer, the worker wire, and the wave ledger still join
                # on a single trace id
                tp = format_traceparent(
                    secrets.token_hex(16), secrets.token_hex(8)
                )
            if tp:
                ctx.info.setdefault("traceparent", tp)
            parsed = parse_traceparent(ctx.info.get("traceparent"))
            ctx.trace_id = parsed[0] if parsed else None
            if stamp is not None:
                note_stage("receive", time.perf_counter() - stamp[1])
            yield ctx
    finally:
        _local.ctx = None
        t_end = time.perf_counter()
        total = t_end - ctx.t0
        if metrics is not None:
            status = ctx.info.get("status")
            outcome = "ok"
            if isinstance(status, int):
                if status == 429:
                    outcome = "shed"
                elif status >= 500:
                    outcome = "error"
            metrics.observe(
                OUTCOME_METRIC, total, help=_OUTCOME_HELP,
                op=op, outcome=outcome,
            )
        if recorder is not None:
            entry = {
                "op": op,
                "detail": detail,
                "stages_ms": {
                    k: round(v * 1000.0, 3) for k, v in ctx.stages.items()
                },
            }
            entry.update(ctx.info)
            recorder.record(total, entry)
        if trace is not None:
            _complete_trace(ctx, trace, total)
        _time_send(metrics, op, t_end)


def _complete_trace(ctx: _ReqCtx, trace, total: float) -> None:
    """Close the span buffer and hand it to the trace store: tail-based
    sampling decides promotion (slow / errored / shed / deadline / forced);
    fast traces park briefly in the recent ring so an async shadow
    divergence can still force-promote them."""
    t1 = time.time()
    spans = ctx.spans
    spans.append({
        "name": f"rpc.{ctx.op}",
        "pid": os.getpid(),
        "t0": round(t1 - total, 6),
        "t1": round(t1, 6),
        "ms": round(total * 1000.0, 3),
    })
    reasons: List[str] = []
    if total * 1000.0 >= trace.slow_ms:
        reasons.append("slow")
    status = ctx.info.get("status")
    if isinstance(status, int):
        if status == 429:
            reasons.append("shed")
        elif status == 504:
            reasons.append("deadline")
        elif status >= 500:
            reasons.append("error")
    forced = ctx.info.get("force_promote")
    if forced:
        reasons.append(str(forced))
    entry = {
        "trace_id": ctx.trace_id,
        "op": ctx.op,
        "detail": ctx.detail,
        "total_ms": round(total * 1000.0, 3),
        "ts": round(t1, 3),
        "spans": spans,
        "stages_ms": {
            k: round(v * 1000.0, 3) for k, v in ctx.stages.items()
        },
        "info": {
            k: v for k, v in ctx.info.items() if k != "force_promote"
        },
    }
    trace.complete(entry, reasons)
