"""The serving daemon: 4 multiplexed ports, gRPC + REST on each.

Parity with `internal/driver/daemon.go:105-151,230-315`: the reference
listens on read (:4466), write (:4467), metrics (:4468) and opl (:4469),
cmux-splitting each port into an HTTP/2 gRPC server and an HTTP/1 REST
router.  Python's grpc server owns its listening socket, so the cmux here
is a byte-level multiplexer: the public port accepts the connection, peeks
the first bytes, and splices the stream to an internal gRPC or REST backend
bound on localhost — protocol detection by the HTTP/2 client preface
(``PRI * HTTP/2.0``), exactly what cmux matches on.

gRPC service placement mirrors `daemon.go:488-543`:
  read:   CheckService, ExpandService, ReadService, NamespacesService,
          VersionService, grpc.health.v1.Health
  write:  WriteService, VersionService, Health
  opl:    SyntaxService, VersionService, Health
  metrics: REST only (prometheus + health + version), like the reference's
          plain-HTTP metrics port (daemon.go:189-228).

Graceful shutdown closes acceptors first, then stops backends with a grace
period (daemon.go:109-135).
"""

from __future__ import annotations

import os
import socket
import ssl
import threading
import time
from typing import Dict, List, Optional, Tuple

import grpc

from ketotpu.proto import health_pb2
from ketotpu.proto.services import (
    CHECK_SERVICE,
    EXPAND_SERVICE,
    NAMESPACES_SERVICE,
    READ_SERVICE,
    SYNTAX_SERVICE,
    VERSION_SERVICE,
    WATCH_SERVICE,
    WRITE_SERVICE,
    add_servicer_to_server,
)
from ketotpu.server import rest
from ketotpu.server.handlers import (
    CheckHandler,
    ExpandHandler,
    NamespaceHandler,
    RelationTupleHandler,
    SyntaxHandler,
    VersionHandler,
    WatchHandler,
)

HEALTH_SERVICE = "grpc.health.v1.Health"

# the HTTP/2 client connection preface cmux matches on
_H2_PREFACE = b"PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n"


class HealthServicer:
    """grpc.health.v1.Health Check + Watch over the registry's checks.

    Readiness values follow a three-state convention: ``"ok"``, a
    ``"degraded: ..."`` string (still SERVING — the device engine fell
    back to CPU, or a worker is respawning), or anything else meaning
    down (NOT_SERVING).  ``status --block`` reads the degraded detail off
    the REST readiness body; the gRPC surface keeps the reference's
    binary protocol."""

    #: Watch repolls the registry at this cadence; a status CHANGE is
    #: streamed immediately at the next tick
    watch_interval = 0.3

    def __init__(self, registry):
        self.r = registry

    def _status(self):
        values = self.r.health().values()
        hard = [
            v for v in values
            if v != "ok" and not str(v).startswith("degraded")
        ]
        return (
            health_pb2.HealthCheckResponse.NOT_SERVING
            if hard
            else health_pb2.HealthCheckResponse.SERVING
        )

    def Check(self, request, context):
        return health_pb2.HealthCheckResponse(status=self._status())

    def Watch(self, request, context):
        """Server-streaming health: current status now, then every change."""
        last = None
        while context.is_active():
            status = self._status()
            if status != last:
                last = status
                yield health_pb2.HealthCheckResponse(status=status)
            time.sleep(self.watch_interval)


def _pump(src: socket.socket, dst: socket.socket) -> None:
    try:
        while True:
            data = src.recv(65536)
            if not data:
                break
            dst.sendall(data)
    except OSError:
        pass
    finally:
        for s, how in ((dst, socket.SHUT_WR), (src, socket.SHUT_RD)):
            try:
                s.shutdown(how)
            except OSError:
                pass


class _Mux(threading.Thread):
    """One public port: sniff the preface, splice to gRPC or REST backend.

    With ``ssl_ctx`` set, the public listener terminates TLS (the
    reference's per-port `serve.<iface>.tls`, embedx/config.schema.json:
    260-296): the handshake runs before protocol sniffing and the
    localhost backends stay plaintext.  The context advertises ALPN
    h2 + http/1.1 so gRPC clients negotiate HTTP/2."""

    def __init__(self, host: str, port: int, grpc_addr: Tuple[str, int],
                 rest_addr: Tuple[str, int], logger,
                 ssl_ctx: Optional[ssl.SSLContext] = None,
                 reuse_port: bool = False,
                 sniff_timeout: float = 10.0):
        super().__init__(daemon=True)
        # a client that connects and never speaks is disconnected after
        # this long — it must not hold a splice thread (limit.sniff_timeout_ms)
        self.sniff_timeout = sniff_timeout
        # reuse_port: SO_REUSEPORT worker mode (server/workers.py) — the
        # kernel load-balances accepted connections across processes
        # bound to the same public port
        self.listener = socket.create_server(
            (host, port), reuse_port=reuse_port, backlog=128
        )
        self.addr = self.listener.getsockname()[:2]
        self.grpc_addr = grpc_addr
        self.rest_addr = rest_addr
        self.logger = logger
        self.ssl_ctx = ssl_ctx
        self._closing = threading.Event()

    def run(self) -> None:
        while not self._closing.is_set():
            try:
                conn, _ = self.listener.accept()
            except OSError:
                break
            threading.Thread(
                target=self._splice, args=(conn,),
                name="keto-mux-splice", daemon=True,
            ).start()

    def _splice(self, conn: socket.socket) -> None:
        try:
            conn.settimeout(self.sniff_timeout)
            if self.ssl_ctx is not None:
                conn = self.ssl_ctx.wrap_socket(conn, server_side=True)
            # cmux buffers until it can match.  READ (not MSG_PEEK — TLS
            # sockets cannot peek) until the protocol is decidable; the
            # sniffed bytes are forwarded to the chosen backend below.
            head = b""
            while (
                len(head) < 4 and head == _H2_PREFACE[: len(head)]
            ):
                chunk = conn.recv(len(_H2_PREFACE) - len(head))
                if not chunk:
                    break
                head += chunk
            conn.settimeout(None)
            target = (
                self.grpc_addr if head.startswith(b"PRI ") else self.rest_addr
            )
            backend = socket.create_connection(target)
            if head:
                backend.sendall(head)
        except (OSError, ssl.SSLError) as e:
            self.logger.debug("mux splice failed: %s", e)
            conn.close()
            return
        t = threading.Thread(target=_pump, args=(conn, backend),
                             name="keto-mux-pump", daemon=True)
        t.start()
        _pump(backend, conn)
        # the backend finished talking; reap the client->backend pump.
        # A client that never closes its half would park that pump in
        # recv() forever — and close() from this thread does NOT
        # interrupt a blocked recv(), so fully shut both sockets down
        # first (recv returns EOF), then close.
        t.join(self.sniff_timeout)
        if t.is_alive():
            for s in (conn, backend):
                try:
                    s.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
            t.join(self.sniff_timeout)
        for s in (conn, backend):
            try:
                s.close()
            except OSError:
                pass

    def close(self) -> None:
        self._closing.set()
        try:
            self.listener.close()
        except OSError:
            pass


class Server:
    """ServeAll analog: boot every port, block until stop()."""

    def __init__(self, registry, *, reuse_port: bool = False):
        self.registry = registry
        self.reuse_port = reuse_port
        self.logger = registry.logger()
        self._grpc_servers: List[grpc.Server] = []
        self._http_servers: List = []
        self._muxes: List[_Mux] = []
        self._threads: List[threading.Thread] = []
        self.addresses: Dict[str, Tuple[str, int]] = {}
        self._engine_host = None
        self._session_lane = None
        self._stopped = threading.Event()
        # anonymized usage telemetry (daemon.go:64-98 seam): inert unless
        # sqa.server_url is configured AND the operator did not opt out.
        # Exactly ONE reporter per deployment like the reference: an
        # SO_REUSEPORT worker (reuse_port=True) must not add an N-fold
        # duplicate stream under the same deployment id
        self.sqa = None
        if not reuse_port:
            from ketotpu.sqa import maybe_start

            self.sqa = maybe_start(
                registry.config,
                network_id=str(registry.network_id),
                metrics=registry.metrics(),
                logger=self.logger,
            )

    # -- construction -------------------------------------------------------

    def _grpc_backend(self, services: Dict[str, object]) -> Tuple[str, int]:
        from ketotpu.engine import coalesce, tpu
        from ketotpu.server.interceptors import (
            AccessLogInterceptor,
            AdmissionInterceptor,
        )

        server = grpc.server(
            # A synchronous handler parks its thread until the wave that
            # carries its check has answered, so this ceiling is how many
            # checks may wait inside the server, and one under what the
            # coalescer can hold cuts its waves (16 threads over its four
            # places made waves of four rows).  So: a full wave of the
            # smallest check program (the rows engine/tpu.py:_bucket pads
            # a wave of one to) in each place a check waits for its answer
            # (engine/coalesce.py:PLACES).  What bounds the calls that
            # stay inside is admission (limit.max_inflight, the interceptor
            # below, on the pool's thread); the ceiling is not cut down to
            # that limit, because a call over it needs a thread for the
            # instant it takes to answer RESOURCE_EXHAUSTED.  A thread
            # starts only when a submit finds none idle: 64 callers cost
            # 64 threads, an idle daemon none, and a parked handler blocks
            # on an Event and holds no GIL.  Stamped: a request's wait for
            # a thread is its pool_wait stage (hostwaits.py).
            self.registry.front_door_pool(
                "grpc", coalesce.PLACES * tpu._bucket(1), "grpc-worker",
            ),
            options=[("grpc.so_reuseport", 0)],
            # access-log/metrics interceptor first so its duration covers
            # the embedder-supplied chain (ketoctx
            # WithGRPCUnaryInterceptors, daemon.go:450-486); admission runs
            # inside it so shed RPCs still show in the access log, and it
            # binds the RPC deadline budget around everything downstream
            interceptors=(
                AccessLogInterceptor(self.registry),
                AdmissionInterceptor(self.registry),
                *self.registry.options.grpc_interceptors,
            ),
        )
        for name, servicer in services.items():
            add_servicer_to_server(name, servicer, server)
        port = server.add_insecure_port("127.0.0.1:0")
        server.start()
        self._grpc_servers.append(server)
        return ("127.0.0.1", port)

    def _ssl_context(self, endpoint: str) -> Optional[ssl.SSLContext]:
        """TLS context from serve.<endpoint>.tls, or None (plaintext)."""
        get = getattr(self.registry.config, "tls_config", None)
        tls = get(endpoint) if get else None
        if not tls:
            return None
        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        ctx.load_cert_chain(tls["cert"], tls["key"])
        try:
            ctx.set_alpn_protocols(["h2", "http/1.1"])
        except NotImplementedError:  # pragma: no cover - platform quirk
            pass
        return ctx

    def _rest_backend(self, router: rest.Router) -> Tuple[str, int]:
        httpd = rest.make_http_server(router, "127.0.0.1", 0)
        self._http_servers.append(httpd)
        t = threading.Thread(target=httpd.serve_forever, daemon=True)
        t.start()
        self._threads.append(t)
        return httpd.server_address[:2]

    def start(self) -> "Server":
        r = self.registry
        version = VersionHandler(r)
        health = HealthServicer(r)
        check = CheckHandler(r)
        expand = ExpandHandler(r)
        tuples = RelationTupleHandler(r)
        namespaces = NamespaceHandler(r)
        syntax = SyntaxHandler(r)
        watch = WatchHandler(r)

        ports = {
            "read": (
                {
                    CHECK_SERVICE: check,
                    EXPAND_SERVICE: expand,
                    READ_SERVICE: tuples,
                    WATCH_SERVICE: watch,
                    NAMESPACES_SERVICE: namespaces,
                    VERSION_SERVICE: version,
                    HEALTH_SERVICE: health,
                },
                rest.read_router(r),
            ),
            "write": (
                {
                    WRITE_SERVICE: tuples,
                    VERSION_SERVICE: version,
                    HEALTH_SERVICE: health,
                },
                rest.write_router(r),
            ),
            "opl": (
                {
                    SYNTAX_SERVICE: syntax,
                    VERSION_SERVICE: version,
                    HEALTH_SERVICE: health,
                },
                rest.opl_router(r),
            ),
        }
        for name, (services, router) in ports.items():
            host, port = r.config.listen_on(name)
            grpc_addr = self._grpc_backend(services)
            rest_addr = self._rest_backend(router)
            ctx = self._ssl_context(name)
            sniff_s = float(
                r.config.get("limit.sniff_timeout_ms", 10000)
            ) / 1000.0
            mux = _Mux(host, port, grpc_addr, rest_addr, self.logger,
                       ssl_ctx=ctx, reuse_port=self.reuse_port,
                       sniff_timeout=sniff_s)
            mux.start()
            self._muxes.append(mux)
            self.addresses[name] = mux.addr
            self.logger.info(
                "serving %s on %s:%d (gRPC+REST multiplexed%s)",
                name, *mux.addr, ", TLS" if ctx else "",
            )

        # metrics: plain HTTP, no gRPC, no mux (daemon.go:189-228)
        host, port = r.config.listen_on("metrics")
        ctx = self._ssl_context("metrics")
        # TLS rides the event loop (server/aio.py): per-connection
        # handshakes run inside the loop with their own timeout, so a
        # stalled client can never block accepts — no deferred-handshake
        # socket wrapping needed
        httpd = rest.make_http_server(
            rest.metrics_router(r), host, port,
            reuse_port=self.reuse_port, ssl_ctx=ctx,
        )
        self._http_servers.append(httpd)
        t = threading.Thread(target=httpd.serve_forever, daemon=True)
        t.start()
        self._threads.append(t)
        self.addresses["metrics"] = httpd.server_address[:2]
        self.logger.info("serving metrics on %s:%d", *self.addresses["metrics"])

        # streaming session lane (server/session.py): raw TCP, wire.py
        # frames, one admission acquire per session.  Ephemeral by
        # default (session.port 0) — discover via addresses["session"].
        # SO_REUSEPORT rides self.reuse_port so front-door workers can
        # share one pinned lane port.
        broker = r.session_broker()
        if broker is not None and broker.enabled:
            from ketotpu.server.session import SessionLane

            lane_host = str(r.config.get("session.host") or "") \
                or r.config.listen_on("read")[0]
            lane_port = int(r.config.get("session.port", 0) or 0)
            self._session_lane = SessionLane(
                broker, lane_host, lane_port,
                reuse_port=self.reuse_port,
                front_door=str(os.environ.get("KETO_FRONT_DOOR", "")),
            )
            self._session_lane.start()
            self.addresses["session"] = self._session_lane.address
            self.logger.info(
                "serving session lane on %s:%d",
                *self.addresses["session"],
            )

        # replication channel: a single-process daemon that owns the device
        # engine publishes the engine-host socket when durability.socket is
        # configured, so a warm standby can bootstrap + tail it (the same
        # wire --workers mode uses; in that mode the owner process, not
        # this daemon, hosts the socket)
        repl_sock = str(r.config.get("durability.socket") or "")
        if repl_sock and not self.reuse_port \
                and r._device_engine() is not None:
            from ketotpu.server.workers import EngineHostServer

            self._engine_host = EngineHostServer(
                r, repl_sock, health_fn=r.health,
            ).start()
            self.logger.info(
                "serving engine host (replication wire) on %s", repl_sock
            )
        # close the signal->actuation loop: the overload plane starts
        # AIMD-adjusting the admission limit off SLO burn + wave wait.
        # Started here — not in Registry.init() — so only serving
        # processes pay for the 2Hz control thread; stop() retires it
        # via close_engines()
        ov = r.overload()
        if ov is not None:
            ov.start()
        return self

    # -- lifecycle ----------------------------------------------------------

    def wait(self, timeout: Optional[float] = None) -> None:
        self._stopped.wait(timeout)

    def stop(self, grace: float = 5.0) -> None:
        if self.sqa is not None:
            self.sqa.close()
        if self._session_lane is not None:
            try:
                self._session_lane.stop()
            except Exception:  # noqa: BLE001 - shutdown must not raise
                pass
            self._session_lane = None
        if self._engine_host is not None:
            try:
                self._engine_host.stop()
            except Exception:  # noqa: BLE001 - shutdown must not raise
                pass
            self._engine_host = None
        for mux in self._muxes:
            mux.close()
        # retire the coalescer BEFORE the gRPC backends drain: its wave
        # worker thread and any queued slots must not outlive the daemon
        # (a closed coalescer answers stragglers directly on the inner
        # engine, so in-grace RPCs still complete)
        self.registry.close_engines()
        for s in self._grpc_servers:
            s.stop(grace)
        for httpd in self._http_servers:
            httpd.shutdown()
            httpd.server_close()
        # flush + stop the OTLP exporter AFTER the backends drain so the
        # final requests' spans ship; only if a tracer was ever built —
        # constructing one here just to close it would be pure waste
        tracer = self.registry._tracer
        close = getattr(tracer, "close", None)
        if close is not None:
            try:
                close()
            except Exception:  # noqa: BLE001 - shutdown must not raise
                self.logger.debug("tracer close failed", exc_info=True)
        self._stopped.set()


def serve_all(registry, *, reuse_port: bool = False) -> Server:
    """Build + start the full 4-port daemon (Registry.ServeAll analog)."""
    return Server(registry, reuse_port=reuse_port).start()
