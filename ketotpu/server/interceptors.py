"""gRPC server interceptors: per-RPC access logging + duration metrics.

The reference chains logging/metrics middleware onto every gRPC server
(`internal/driver/daemon.go:450-486`); this is the same seam for the
Python servers.  `AccessLogInterceptor` wraps every unary handler to

* observe ``keto_grpc_request_duration_seconds{method}`` on the shared
  Metrics registry,
* emit one INFO access line per RPC (method, status, duration, peer)
  when ``log.request_log`` is enabled — health-check RPCs are metered
  but not logged, like the REST access log's health exclusion, and
* bind the call for the request context the handler opens, which times
  stage ``send`` from its close to the RPC's end
  (``flightrec.await_send``).

Embedder-supplied interceptors (ketoctx ``grpc_interceptors``) still run;
this one is prepended so the duration covers the whole chain.
"""

from __future__ import annotations

import time

import grpc

from ketotpu import deadline, flightrec
from ketotpu.server import overload


class AdmissionInterceptor(grpc.ServerInterceptor):
    """In-flight admission + deadline binding for unary methods.

    Before the handler runs this interceptor (a) tries to acquire one
    slot from the registry's shared :class:`AdmissionController`, shedding
    with ``RESOURCE_EXHAUSTED`` when the port is saturated, and (b) binds
    the RPC's ``context.time_remaining()`` as the thread's deadline budget
    so every blocking hop downstream (coalescer slot wait, owner socket,
    oracle fallback) is bounded by what the client granted.  Health RPCs
    are exempt — an overloaded server must still answer probes.
    """

    def __init__(self, registry):
        self.registry = registry

    def intercept_service(self, continuation, handler_call_details):
        handler = continuation(handler_call_details)
        if handler is None or handler.unary_unary is None:
            return handler  # streaming/unknown: pass through untouched
        method = handler_call_details.method
        if method.startswith("/grpc.health."):
            return handler
        registry = self.registry
        inner = handler.unary_unary
        op = method.rsplit("/", 1)[-1].lower()
        klass = overload.classify_grpc_op(op)

        def wrapped(request, context):
            ctl = registry.admission()
            token = ctl.try_acquire(klass=klass)
            if not token:
                m = registry.metrics()
                m.counter(
                    "keto_requests_shed_total", 1.0,
                    help="requests refused by admission control",
                    transport="grpc", klass=klass,
                )
                m.observe(
                    flightrec.STAGE_METRIC, 0.0,
                    help="per-RPC stage wall time decomposition",
                    op=op, stage="shed",
                )
                # the trailing-metadata twin of the REST Retry-After
                # header: load-derived + jittered backoff hint
                context.set_trailing_metadata(
                    (("retry-after", registry.retry_after_hint()),)
                )
                context.abort(
                    grpc.StatusCode.RESOURCE_EXHAUSTED,
                    f"in-flight limit reached ({ctl.limit}); retry later",
                )
            try:
                with deadline.scope(context.time_remaining()):
                    return inner(request, context)
            finally:
                ctl.release(token)

        return grpc.unary_unary_rpc_method_handler(
            wrapped,
            request_deserializer=handler.request_deserializer,
            response_serializer=handler.response_serializer,
        )


class AccessLogInterceptor(grpc.ServerInterceptor):
    """Per-RPC access log + duration histogram for unary methods."""

    def __init__(self, registry):
        self.registry = registry

    def intercept_service(self, continuation, handler_call_details):
        handler = continuation(handler_call_details)
        if handler is None or handler.unary_unary is None:
            return handler  # streaming/unknown: pass through untouched
        method = handler_call_details.method
        registry = self.registry
        inner = handler.unary_unary

        def wrapped(request, context):
            t0 = time.perf_counter()
            status = "OK"
            flightrec.await_send(context)
            try:
                return inner(request, context)
            except Exception:
                status = "ERROR"
                raise
            finally:
                flightrec.await_send(None)
                dt = time.perf_counter() - t0
                # abort()/set_code() paths: report the code the handler set
                code = getattr(context, "code", lambda: None)()
                if code is not None and code != grpc.StatusCode.OK:
                    status = getattr(code, "name", str(code))
                registry.metrics().observe(
                    "keto_grpc_request_duration_seconds", dt,
                    help="gRPC request duration by full method name",
                    method=method,
                )
                if (
                    not method.startswith("/grpc.health.")
                    and bool(registry.config.get("log.request_log", True))
                ):
                    registry.logger().info(
                        "grpc request", extra={"fields": {
                            "method": method,
                            "status": status,
                            "duration_ms": round(dt * 1000.0, 3),
                            "peer": context.peer(),
                        }},
                    )

        return grpc.unary_unary_rpc_method_handler(
            wrapped,
            request_deserializer=handler.request_deserializer,
            response_serializer=handler.response_serializer,
        )
