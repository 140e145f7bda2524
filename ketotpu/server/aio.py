"""Asyncio HTTP/1.1 front end for the REST routers.

Replaces the ``ThreadingHTTPServer`` thread-per-connection model: one
event loop owns every connection (accept, header parsing, keep-alive
idle timeouts, response writes), so concurrency 512+ costs file
descriptors, not threads.  Request HANDLING — route dispatch, admission,
deadline scopes, the flight-recorder stage vector — still runs on a
small worker-thread pool (``limit.http_workers``), because the handler
cores block on the engine; the pool bounds handler concurrency while the
loop keeps accepting and buffering.

Contract parity with the old server (server/rest.py keeps the
``make_http_server`` entry point; the Router/handler surface is
untouched):

* HTTP/1.1 keep-alive by default, ``Connection: close`` and HTTP/1.0
  honored; pipelined requests are answered in order off the same buffer;
* the accept backlog is bounded (``limit.accept_backlog``) — overload
  queues in the kernel and sheds at admission, never as an unbounded
  thread herd;
* per-request flow is the exact _serve flow the threaded handler ran:
  flightrec recording for known ops, admission try/acquire + shed
  metrics, X-Request-Timeout deadline scope, CORS, access log;
* SSE streams (StreamingResponse) detach onto a dedicated pump thread so
  a parked watch subscriber never pins a pool worker; chunks are written
  back through the loop;
* TLS is first-class (``ssl_ctx=``): the handshake runs per-connection
  inside the loop, so a stalled client can never block accepts — the
  deferred-handshake workaround the threaded metrics port needed.
"""

from __future__ import annotations

import asyncio
import json
import socket
import threading
import time
from contextlib import nullcontext
from typing import Optional, Tuple
from urllib.parse import parse_qs, urlparse

from ketotpu import deadline, flightrec
from ketotpu.api.types import KetoAPIError
from ketotpu.server import overload

_ALLOWED_METHODS = {"GET", "POST", "PUT", "DELETE", "PATCH"}
_MAX_HEADER_BYTES = 65536
_MAX_HEADERS = 100

#: sentinel returns from the worker-side handler to the connection loop
_KEEP, _CLOSE, _DETACHED = "keep", "close", "detached"


class _BadRequest(Exception):
    pass


class AsyncHTTPServer:
    """Event-loop HTTP server with the ThreadingHTTPServer lifecycle
    surface the daemon drives: ``server_address``, ``serve_forever()``,
    ``shutdown()``, ``server_close()``."""

    def __init__(self, router, host: str, port: int, *,
                 reuse_port: bool = False, ssl_ctx=None):
        from ketotpu.server import rest as _rest

        self._rest = _rest
        self.router = router
        self.registry = router.r
        self.logger = self.registry.logger()
        cfg = self.registry.config
        self.access_log = bool(cfg.get("log.request_log", True))
        # per-connection idle/read timeout: bounds a stalled client to one
        # file descriptor for at most this long (the threaded server's
        # per-connection read timeout analog)
        self.idle_timeout = 30.0
        backlog = int(cfg.get("limit.accept_backlog", 512))
        workers = max(1, int(cfg.get("limit.http_workers", 8)))
        # pre-created listening socket: the daemon reads .server_address
        # right after construction, before serve_forever runs
        self._sock = socket.create_server(
            (host, port), backlog=backlog, reuse_port=reuse_port,
        )
        self.server_address = self._sock.getsockname()
        self._backlog = backlog
        self._ssl_ctx = ssl_ctx
        self._pool = self.registry.front_door_pool(
            "rest", workers, "http-worker",
        )
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._stop_ev: Optional[asyncio.Event] = None
        self._conn_tasks: set = set()
        self._started = threading.Event()
        self._done = threading.Event()

    # -- lifecycle (ThreadingHTTPServer-shaped) ------------------------------

    def serve_forever(self, poll_interval: float = 0.5) -> None:
        asyncio.run(self._main())

    def shutdown(self) -> None:
        loop = self._loop
        if loop is not None and not loop.is_closed():
            ev = self._stop_ev

            def _stop():
                if ev is not None:
                    ev.set()

            try:
                loop.call_soon_threadsafe(_stop)
            except RuntimeError:  # loop already closed under us
                pass
        if self._started.is_set():
            self._done.wait(timeout=10.0)

    def server_close(self) -> None:
        self._pool.shutdown(wait=False)
        try:
            self._sock.close()
        except OSError:
            pass

    # -- event loop ----------------------------------------------------------

    async def _main(self) -> None:
        self._loop = asyncio.get_running_loop()
        self._stop_ev = asyncio.Event()
        server = await asyncio.start_server(
            self._client, sock=self._sock, ssl=self._ssl_ctx,
            backlog=self._backlog,
            ssl_handshake_timeout=self.idle_timeout if self._ssl_ctx else None,
        )
        self._started.set()
        try:
            await self._stop_ev.wait()
        finally:
            server.close()
            try:
                await server.wait_closed()
            except Exception:  # noqa: BLE001 - shutdown must not raise
                pass
            for t in list(self._conn_tasks):
                t.cancel()
            if self._conn_tasks:
                await asyncio.gather(
                    *self._conn_tasks, return_exceptions=True
                )
            self._done.set()

    async def _client(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        task = asyncio.current_task()
        self._conn_tasks.add(task)
        peer = writer.get_extra_info("peername") or ("?", 0)
        detached = False
        try:
            while True:
                try:
                    line = await asyncio.wait_for(
                        reader.readline(), self.idle_timeout
                    )
                except (asyncio.TimeoutError, TimeoutError):
                    break
                if not line:
                    break  # EOF between requests: clean keep-alive close
                if line in (b"\r\n", b"\n"):
                    continue  # stray CRLF between pipelined requests
                try:
                    method, target, version, headers, body = (
                        await self._read_request(line, reader, writer)
                    )
                except _BadRequest as e:
                    await self._write(
                        writer, _simple_response(400, str(e), close=True)
                    )
                    break
                keep = _wants_keepalive(version, headers)
                outcome = await self._loop.run_in_executor(
                    self._pool, self._handle,
                    method, target, headers, body, peer, writer,
                )
                if outcome == _DETACHED:
                    detached = True
                    return  # the pump thread owns the writer now
                if outcome == _CLOSE or not keep:
                    break
        except asyncio.CancelledError:
            pass
        except (ConnectionError, OSError):
            pass
        finally:
            self._conn_tasks.discard(task)
            if not detached:
                try:
                    writer.close()
                except Exception:  # noqa: BLE001
                    pass

    async def _read_request(self, line: bytes, reader, writer):
        try:
            parts = line.decode("latin-1").rstrip("\r\n").split()
            method, target, version = parts[0], parts[1], parts[2]
        except (IndexError, UnicodeDecodeError):
            raise _BadRequest("malformed request line") from None
        if version not in ("HTTP/1.1", "HTTP/1.0"):
            raise _BadRequest(f"unsupported protocol {version}")
        headers = {}
        total = 0
        while True:
            h = await asyncio.wait_for(reader.readline(), self.idle_timeout)
            if not h:
                raise _BadRequest("unexpected EOF in headers")
            if h in (b"\r\n", b"\n"):
                break
            total += len(h)
            if total > _MAX_HEADER_BYTES or len(headers) >= _MAX_HEADERS:
                raise _BadRequest("headers too large")
            try:
                name, _, value = h.decode("latin-1").partition(":")
            except UnicodeDecodeError:
                raise _BadRequest("malformed header") from None
            headers[name.strip().lower()] = value.strip()
        if headers.get("expect", "").lower() == "100-continue":
            writer.write(b"HTTP/1.1 100 Continue\r\n\r\n")
            await writer.drain()
        body = b""
        try:
            length = int(headers.get("content-length") or 0)
        except ValueError:
            raise _BadRequest("bad Content-Length") from None
        if length > 0:
            try:
                body = await asyncio.wait_for(
                    reader.readexactly(length), self.idle_timeout
                )
            except asyncio.IncompleteReadError:
                raise _BadRequest("truncated body") from None
        return method, target, version, headers, body

    # -- response writes (called from worker threads) ------------------------

    async def _write(self, writer: asyncio.StreamWriter, data: bytes) -> None:
        writer.write(data)
        await writer.drain()

    def _send(self, writer: asyncio.StreamWriter, data: bytes,
              timeout: float = 30.0) -> None:
        fut = asyncio.run_coroutine_threadsafe(
            self._write(writer, data), self._loop
        )
        fut.result(timeout=timeout)

    def _close_writer(self, writer: asyncio.StreamWriter) -> None:
        def _do():
            try:
                writer.close()
            except Exception:  # noqa: BLE001
                pass

        try:
            self._loop.call_soon_threadsafe(_do)
        except RuntimeError:
            pass

    # -- request handling (worker pool) --------------------------------------

    def _handle(self, method: str, target: str, hdrs: dict, body: bytes,
                peer, writer) -> str:
        try:
            return self._serve(method, target, hdrs, body, peer, writer)
        except Exception:  # noqa: BLE001 - connection-level failure
            self.logger.exception("http connection handler failed")
            try:
                self._send(
                    writer,
                    _simple_response(500, "internal error", close=True),
                )
            except Exception:  # noqa: BLE001
                pass
            return _CLOSE

    def _serve(self, method: str, target: str, hdrs: dict, body: bytes,
               peer, writer) -> str:
        rest = self._rest
        router, registry = self.router, self.registry
        if method == "OPTIONS":
            # CORS preflight (rs/cors handles OPTIONS before routing)
            origin = hdrs.get("origin")
            want = hdrs.get("access-control-request-method")
            hs = rest.cors_headers(
                router.cors, origin, request_method=want, preflight=True,
            ) if router.cors else None
            head = _head(204 if hs else 405, list((hs or {}).items())
                         + [("Content-Length", "0")])
            self._send(writer, head)
            return _KEEP
        if method not in _ALLOWED_METHODS:
            self._send(
                writer,
                _simple_response(501, f"unsupported method {method!r}",
                                 close=True),
            )
            return _CLOSE
        t0 = time.perf_counter()
        parsed = urlparse(target)
        query = rest._flatten_query(parse_qs(parsed.query))
        t_parse = time.perf_counter()
        op = rest._RPC_OPS.get(parsed.path)
        rec = flightrec.rpc_recording(
            registry, op, traceparent=hdrs.get("traceparent"),
            detail=f"{method} {parsed.path}", t0=t0,
        ) if op else nullcontext()
        with rec:
            flightrec.note_stage("parse", t_parse - t0)
            ctl = (
                registry.admission()
                if parsed.path not in rest._ADMISSION_EXEMPT else None
            )
            token = 0
            klass = overload.classify_rest_path(parsed.path)
            if ctl is not None and not (
                token := ctl.try_acquire(klass=klass)
            ):
                registry.metrics().counter(
                    "keto_requests_shed_total", 1.0,
                    help="requests refused by admission control",
                    transport="rest", klass=klass,
                )
                registry.metrics().observe(
                    flightrec.STAGE_METRIC, 0.0,
                    help="per-RPC stage wall time decomposition",
                    op=op or "http", stage="shed",
                )
                status, payload, extra = (
                    429,
                    rest._error_body(
                        429,
                        f"in-flight limit reached ({ctl.limit}); "
                        "retry later",
                    ),
                    {"Retry-After": registry.retry_after_hint()},
                )
            else:
                try:
                    try:
                        # per-request budget: the X-Request-Timeout header
                        # bounds every blocking hop downstream
                        budget = deadline.parse_timeout(
                            hdrs.get("x-request-timeout")
                        )
                    except KetoAPIError as e:
                        code = e.status_code or 500
                        status, payload, extra = (
                            code, rest._error_body(code, str(e)), {}
                        )
                    else:
                        with deadline.scope(budget):
                            status, payload, extra = router.dispatch(
                                method, parsed.path,
                                rest.Request(query, body, hdrs),
                            )
                finally:
                    if ctl is not None:
                        ctl.release(token)
            flightrec.note_stage("compute", time.perf_counter() - t_parse)
            flightrec.note(status=status)
            if (op == "check" and isinstance(payload, dict)
                    and "allowed" in payload):
                flightrec.note(verdict=payload["allowed"])
            t_enc = time.perf_counter()
            if isinstance(payload, rest.StreamingResponse):
                return self._serve_stream(
                    method, parsed.path, status, payload, extra, hdrs,
                    peer, writer, t0,
                )
            if payload is None:
                data = b""
                ctype = "application/json"
            elif isinstance(payload, tuple):
                ctype, text = payload
                # bytes pass through untouched: the columnar batch route
                # renders its whole response frame pre-encoded
                data = (text if isinstance(text, (bytes, bytearray))
                        else text.encode("utf-8"))
            else:
                ctype = "application/json"
                data = json.dumps(payload).encode("utf-8")
            headers = [
                ("Content-Type", ctype),
                ("Content-Length", str(len(data))),
            ]
            headers.extend(extra.items())
            if router.cors:
                headers.extend((rest.cors_headers(
                    router.cors, hdrs.get("origin")
                ) or {}).items())
            self._send(writer, _head(status, headers) + data)
            flightrec.note_stage("encode", time.perf_counter() - t_enc)
        dt = time.perf_counter() - t0
        registry.metrics().observe(
            "keto_http_request_duration_seconds", dt,
            help="REST request latency",
            endpoint=router.endpoint, method=method, status=str(status),
        )
        if parsed.path not in ("/health/alive", "/health/ready"):
            if self.access_log:
                self.logger.info(
                    "http_request", extra={"fields": {
                        "method": method,
                        "path": parsed.path,
                        "status": status,
                        "duration_ms": round(dt * 1e3, 3),
                        "peer": "%s:%s" % tuple(peer[:2]),
                        "endpoint": router.endpoint,
                    }},
                )
            else:
                self.logger.debug(
                    "%s %s -> %d (%.1fms)",
                    method, parsed.path, status, dt * 1e3,
                )
        return _KEEP

    def _serve_stream(self, method, path, status, payload, extra, hdrs,
                      peer, writer, t0) -> str:
        """SSE escape hatch: write the head, then detach the stream onto
        its own pump thread so a parked subscriber costs a thread only
        while it is STREAMING — never a pool worker.  The pump owns the
        writer from here; chunk writes ride back through the loop."""
        rest, router, registry = self._rest, self.router, self.registry
        headers = [
            ("Content-Type", payload.content_type),
            ("Cache-Control", "no-store"),
            ("Connection", "close"),
        ]
        headers.extend(extra.items())
        if router.cors:
            headers.extend((rest.cors_headers(
                router.cors, hdrs.get("origin")
            ) or {}).items())
        self._send(writer, _head(status, headers))
        flightrec.note_stage("encode", 0.0)

        def pump():
            try:
                for chunk in payload.iterator:
                    self._send(writer, chunk)
            except Exception:  # noqa: BLE001 - client gone: end the stream
                pass
            finally:
                close = getattr(payload.iterator, "close", None)
                if close is not None:
                    try:
                        close()
                    except Exception:  # noqa: BLE001
                        pass
                self._close_writer(writer)
                dt = time.perf_counter() - t0
                registry.metrics().observe(
                    "keto_http_request_duration_seconds", dt,
                    help="REST request latency",
                    endpoint=router.endpoint, method=method,
                    status=str(status),
                )
                if self.access_log:
                    self.logger.info(
                        "http_stream", extra={"fields": {
                            "method": method,
                            "path": path,
                            "status": status,
                            "duration_ms": round(dt * 1e3, 3),
                            "peer": "%s:%s" % tuple(peer[:2]),
                            "endpoint": router.endpoint,
                        }},
                    )

        threading.Thread(
            target=pump, daemon=True, name="http-sse-pump",
        ).start()
        return _DETACHED


# -- response encoding helpers ------------------------------------------------


def _head(status: int, headers) -> bytes:
    from ketotpu.server.rest import _STATUS_TEXT

    reason = _STATUS_TEXT.get(status, "OK" if status < 400 else "Error")
    lines = [f"HTTP/1.1 {status} {reason}\r\n"]
    for k, v in headers:
        lines.append(f"{k}: {v}\r\n")
    lines.append("\r\n")
    return "".join(lines).encode("latin-1")


def _simple_response(status: int, message: str, *, close: bool = False) -> bytes:
    body = json.dumps({
        "error": {"code": status, "message": message}
    }).encode("utf-8")
    headers = [
        ("Content-Type", "application/json"),
        ("Content-Length", str(len(body))),
    ]
    if close:
        headers.append(("Connection", "close"))
    return _head(status, headers) + body


def _wants_keepalive(version: str, headers: dict) -> bool:
    conn = headers.get("connection", "").lower()
    if version == "HTTP/1.0":
        return conn == "keep-alive"
    return conn != "close"
