"""Command line interface (`cmd/root.go:36-56` parity).

Verbs:

* ``serve -c config.yml`` — boot the 4-port daemon (cmd/server/serve.go:26)
* ``check <subject> <relation> <namespace> <object>`` — gRPC Check
  (cmd/check/root.go:31-80, incl. subject-set ``ns:obj#rel`` parsing and
  Allowed/Denied output)
* ``expand <relation> <namespace> <object>`` — gRPC Expand, pretty tree
  (cmd/expand/root.go:25-60)
* ``relation-tuple parse|create|get|delete|delete-all``
  (cmd/relationtuple/*.go: parse tuple-grammar to JSON, create/delete from
  JSON files or dirs, get with query flags + pagination + table output,
  delete-all guarded by --force)
* ``namespace validate <file.ts>`` — OPL diagnostics (cmd/namespace/)
* ``status [--block] [--debug]`` — gRPC health watch (cmd/status/root.go:
  24-95); ``--debug`` dumps the flight recorder (slowest recent requests
  with per-stage latencies), wave ledger, compile observatory, and
  projection/compaction state from the metrics port
* ``version``

Client commands talk gRPC to a running daemon, selected by ``--read-remote``
/ ``--write-remote`` (cmd/client/grpc_client.go:28-35; defaults
127.0.0.1:4466 / :4467).
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

import ketotpu
from ketotpu.api.types import KetoAPIError, RelationTuple

READ_REMOTE = "127.0.0.1:4466"
WRITE_REMOTE = "127.0.0.1:4467"


def _cert_host_name(pem: str):
    """Best-effort DNS name / CN out of a PEM cert (for the target-name
    override when pinning a fetched certificate) — None when the private
    stdlib decoder is unavailable."""
    import ssl as _ssl
    import tempfile

    try:
        with tempfile.NamedTemporaryFile("w", suffix=".pem") as f:
            f.write(pem)
            f.flush()
            info = _ssl._ssl._test_decode_cert(f.name)  # noqa: SLF001
        for typ, val in info.get("subjectAltName", ()):
            if typ == "DNS":
                return val
        for rdn in info.get("subject", ()):
            for k, v in rdn:
                if k == "commonName":
                    return v
    except Exception:  # noqa: BLE001 — override is an optimization only
        return None
    return None


def _channel(remote: str, args=None):
    """Client channel with the reference's transport-security surface
    (cmd/client/grpc_client.go:28-80): TLS against the host root bundle
    by DEFAULT, ``--insecure-disable-transport-security`` for plaintext,
    ``--insecure-skip-hostname-verification`` to trust the certificate
    the server presents (python-grpc cannot disable verification, so the
    fetched cert is pinned as the root and the target name overridden —
    same effect for the self-signed case the flag exists for),
    ``--authority``/KETO_AUTHORITY, and KETO_BEARER_TOKEN as per-RPC
    bearer credentials (secure channels only, per the gRPC auth spec)."""
    import grpc

    authority = (
        getattr(args, "authority", "") or os.environ.get("KETO_AUTHORITY", "")
    )
    if getattr(args, "insecure_disable_transport_security", False):
        opts = [("grpc.default_authority", authority)] if authority else None
        return grpc.insecure_channel(remote, options=opts)
    options = []
    if getattr(args, "insecure_skip_hostname_verification", False):
        import ssl as _ssl

        host, sep, port = remote.rpartition(":")
        if not sep:
            host, port = remote, "443"  # gRPC's default TLS port
        try:
            pem = _ssl.get_server_certificate(
                (host or "127.0.0.1", int(port))
            )
        except (OSError, ValueError):
            # server not up yet (status --block polls through this) or an
            # unparsable remote: build default TLS creds so the failure
            # surfaces as grpc.RpcError at RPC time, which every client
            # retry loop already handles
            pem = None
        if pem:
            creds = grpc.ssl_channel_credentials(
                root_certificates=pem.encode()
            )
            name = _cert_host_name(pem)
            if name:
                options.append(("grpc.ssl_target_name_override", name))
        else:
            creds = grpc.ssl_channel_credentials()
    else:
        creds = grpc.ssl_channel_credentials()  # host root CA bundle
    token = os.environ.get("KETO_BEARER_TOKEN", "")
    if token:
        creds = grpc.composite_channel_credentials(
            creds, grpc.access_token_call_credentials(token)
        )
    if authority:
        options.append(("grpc.default_authority", authority))
    return grpc.secure_channel(remote, creds, options=options or None)


def _parse_subject(s: str):
    from ketotpu.api.types import subject_from_string

    return subject_from_string(s)


# -- subcommands -------------------------------------------------------------


def cmd_serve(args) -> int:
    from ketotpu.driver import Provider, Registry
    from ketotpu.server import serve_all

    if getattr(args, "worker_of", ""):
        return cmd_serve_worker(args)
    # every serve mode below owns a device engine: place the persistent
    # compile cache before the first compile (a worker never compiles)
    from ketotpu import compilewatch

    compilewatch.place_cache()
    if getattr(args, "standby", False):
        return cmd_serve_standby(args)
    workers = int(getattr(args, "workers", 0) or 0)
    front_doors = int(getattr(args, "front_doors", 0) or 0)
    if workers > 0 or front_doors > 0:
        return _serve_multiprocess(args, workers, front_doors)
    cfg = Provider(config_file=args.config) if args.config else Provider()
    from ketotpu import faults

    faults.configure_from_config(cfg)
    reg = Registry(cfg)
    reg.logger().info("initializing registry (engine warmup)")
    reg.init()
    srv = serve_all(reg)
    try:
        srv.wait()
    except KeyboardInterrupt:
        reg.logger().info("shutting down gracefully")
        srv.stop()
    return 0


def _serve_multiprocess(args, workers: int, front_doors: int = 0) -> int:
    """--workers N: one device-owner process (this one) + N SO_REUSEPORT
    worker daemons sharing the public ports (server/workers.py).

    The owner holds the JAX device and the real engine and serves
    batched check/expand over a unix socket; workers run the wire stack
    with engine.kind=remote.  All processes share the durable store DSN
    — a ``memory`` DSN cannot span processes and is refused.

    --front-doors N labels the first N children as streaming front
    doors: each binds the SAME session-lane port via SO_REUSEPORT (the
    kernel spreads incoming sessions across them) and exports
    keto_front_door_* metrics under its door label.  A child beyond the
    front-door count runs with its session lane disabled — it still
    serves the 4 public ports, it just doesn't accept streams."""
    import subprocess
    import sys as _sys
    import tempfile

    from ketotpu import faults
    from ketotpu.driver import Provider, Registry
    from ketotpu.server.workers import EngineHostServer, WorkerSupervisor

    cfg = Provider(config_file=args.config) if args.config else Provider()
    faults.configure_from_config(cfg)
    if cfg.dsn() == "memory":
        print(
            "serve --workers needs a shared durable dsn "
            "(sqlite://<file> or postgres://...); 'memory' cannot span "
            "processes",
            file=_sys.stderr,
        )
        return 2
    reg = Registry(cfg)
    log = reg.logger()
    log.info("initializing device owner (engine warmup)")
    reg.init()
    # durability.socket pins the engine-host path (so a warm standby can
    # find the owner); otherwise the socket lives in a fresh 0700
    # directory: a bare mktemp name in world-writable /tmp is squattable
    # between name pick and bind, and the directory mode (not the
    # umask-dependent socket mode) is what actually gates connect
    # permission
    sock = str(cfg.get("durability.socket") or "")
    sockdir = ""
    if not sock:
        sockdir = tempfile.mkdtemp(prefix="keto-engine-")
        sock = os.path.join(sockdir, "engine.sock")
    host = EngineHostServer(reg, sock, health_fn=reg.health).start()

    nchildren = max(workers, front_doors)
    # front doors share ONE session-lane port via SO_REUSEPORT; a
    # config of session.port=0 means each child would bind its own
    # ephemeral lane, so the parent picks one concrete free port here
    # and pins it into every front-door child via the env override
    session_port = 0
    if front_doors > 0:
        session_port = int(cfg.get("session.port", 0) or 0)
        if not session_port:
            import socket as _socket

            probe = _socket.socket()
            probe.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEADDR, 1)
            probe.bind((cfg.listen_on("read")[0] or "", 0))
            session_port = probe.getsockname()[1]
            probe.close()

    def spawn(i: int) -> "subprocess.Popen":
        env = dict(os.environ)
        # a chip belongs to one process, and that is this one: a worker's
        # engine is remote, so whatever it imports must find no device to
        # take (or hang on)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("KETO_FRONT_DOOR", None)
        if front_doors > 0:
            if i < front_doors:
                env["KETO_FRONT_DOOR"] = str(i)
                env["KETO_SESSION_PORT"] = str(session_port)
            else:
                env["KETO_SESSION_ENABLED"] = "false"
        return subprocess.Popen([
            _sys.executable, "-m", "ketotpu.cli", "serve",
            *(["-c", args.config] if args.config else []),
            "--worker-of", sock,
        ], env=env)

    # SIGTERM (systemd, k8s, supervisors) must tear the fleet down the
    # same way ^C does: the default handler would kill only the owner
    # and orphan N workers still holding the SO_REUSEPORT public ports
    import signal

    def _sigterm(signum, frame):
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, _sigterm)

    sup = WorkerSupervisor(spawn, nchildren, log=log.warning)
    # the owner's health (served to workers over the socket's "health"
    # op) reports `degraded` while any worker is down/respawning, so
    # `status --block` can tell a degraded topology from a dead one
    reg.readiness_checks["workers"] = sup.state
    if front_doors > 0:
        log.info(
            "engine host on %s; forking %d workers (%d front doors, "
            "session lane :%d)", sock, nchildren, front_doors,
            session_port,
        )
    else:
        log.info("engine host on %s; forking %d workers", sock, nchildren)
    sup.start()
    rc = 0
    try:
        # supervise, don't just watch: a dead worker (crash, OOM) is
        # respawned with capped backoff; only a worker that keeps dying
        # rapidly — a systemic failure like a port bind race — makes the
        # whole topology exit
        while True:
            code = sup.poll()
            if code is not None:
                rc = code
                break
            if not host.is_alive():
                # the device owner died: respawn it too (workers ride out
                # the gap through their reconnect backoff)
                log.warning("engine host died; restarting")
                host = host.restart()
            time.sleep(0.5)
        sup.terminate()
    except KeyboardInterrupt:
        log.info("shutting down workers")
        sup.terminate()
    finally:
        host.stop()
        if sockdir:
            try:
                os.rmdir(sockdir)
            except OSError:
                pass
    return rc


def cmd_serve_worker(args) -> int:
    """A single SO_REUSEPORT worker: wire stack + remote engine."""
    from ketotpu.driver import Provider, Registry
    from ketotpu.server import serve_all

    cfg = Provider(
        {"engine": {"kind": "remote", "socket": args.worker_of}},
        config_file=args.config,
    ) if args.config else Provider(
        {"engine": {"kind": "remote", "socket": args.worker_of}}
    )
    from ketotpu import faults
    from ketotpu.server.workers import engine_host_readiness

    faults.configure_from_config(cfg)
    reg = Registry(cfg)
    # readiness rides the owner's: unreachable socket = down, and the
    # owner's degraded state (CPU fallback, respawning sibling) shows
    # through this worker's health surface too
    reg.readiness_checks["engine_host"] = engine_host_readiness(args.worker_of)
    srv = serve_all(reg, reuse_port=True)
    try:
        srv.wait()
    except KeyboardInterrupt:
        srv.stop()
    return 0


def cmd_serve_standby(args) -> int:
    """--standby: warm follower beside a live owner (ketotpu/standby.py).

    Replicates the owner's changelog into a LOCAL in-memory replica (the
    constructor dsn override below: the follower must not share the
    owner's durable store — it mirrors it through the wire), stays warm,
    and on owner death or POST /debug/handoff binds the same public
    ports via SO_REUSEPORT and serves — snaptoken-exact."""
    from ketotpu import faults
    from ketotpu.driver import Provider, Registry
    from ketotpu.server import rest, serve_all
    from ketotpu.standby import StandbyError, StandbyFollower

    cfg = Provider({"dsn": "memory"}, config_file=args.config) \
        if args.config else Provider({"dsn": "memory"})
    faults.configure_from_config(cfg)
    sock = str(cfg.get("durability.socket") or "")
    if not sock:
        print(
            "serve --standby needs durability.socket pointing at the "
            "owner's engine-host socket",
            file=sys.stderr,
        )
        return 2
    reg = Registry(cfg)
    log = reg.logger()
    follower = StandbyFollower(reg, sock)
    # pre-promotion observability: the follower's own metrics HTTP port
    # (durability.standby_port) serves the standby gauges, the standby
    # row in /debug/projection, and the POST /debug/handoff trigger —
    # the public 4-port front door still belongs to the owner
    pre_http = None
    standby_port = int(cfg.get("durability.standby_port", 4470) or 0)
    if standby_port:
        import threading as _threading

        host = cfg.listen_on("metrics")[0]
        pre_http = rest.make_http_server(
            rest.metrics_router(reg), host, standby_port
        )
        _threading.Thread(
            target=pre_http.serve_forever, daemon=True,
            name="standby-metrics",
        ).start()
        log.info("standby metrics on %s:%d", host, standby_port)
    import signal

    def _sigterm(signum, frame):
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, _sigterm)
    log.info("standby following owner at %s", sock)
    try:
        reason = follower.run()
    except StandbyError as e:
        print(f"standby: {e}", file=sys.stderr)
        return 2
    except KeyboardInterrupt:
        log.info("standby shutting down (never promoted)")
        follower.close()
        if pre_http is not None:
            pre_http.shutdown()
            pre_http.server_close()
        return 0
    if pre_http is not None:
        # the daemon below owns the real metrics port; drop the
        # pre-promotion listener first so nothing double-serves
        pre_http.shutdown()
        pre_http.server_close()
    log.info("standby promoting (reason=%s); binding front door", reason)
    # become the next owner end-to-end: re-host the engine socket on the
    # same path (EngineHostServer unlinks the dead owner's stale bind;
    # during a deliberate handoff the unlink steals new connections from
    # the draining old owner) so the NEXT standby in a rolling-restart
    # chain has something to attach to
    from ketotpu.server.workers import EngineHostServer

    host_srv = None
    try:
        host_srv = EngineHostServer(reg, sock, health_fn=reg.health).start()
        log.info("serving engine host (replication wire) on %s", sock)
    except OSError as e:
        log.warning("could not re-host engine socket %s: %s", sock, e)
    # SO_REUSEPORT: binds even while the old owner still holds the ports
    # during a deliberate rolling restart; after owner death it simply
    # binds fresh
    srv = serve_all(reg, reuse_port=True)
    try:
        srv.wait()
    except KeyboardInterrupt:
        log.info("shutting down gracefully")
        srv.stop()
    finally:
        if host_srv is not None:
            host_srv.stop()
    return 0


def _batch_check_lines(path: str):
    """Relation tuples from a .jsonl file: each line is either a
    relation-tuple JSON object or a canonical string form
    ("File:doc#view@alice")."""
    tuples = []
    with (sys.stdin if path == "-" else open(path)) as fh:
        for lineno, raw in enumerate(fh, 1):
            line = raw.strip()
            if not line or line.startswith("#"):
                continue
            try:
                try:
                    data = json.loads(line)
                except json.JSONDecodeError:
                    data = line
                if isinstance(data, dict):
                    tuples.append(RelationTuple.from_json(data))
                else:
                    tuples.append(RelationTuple.from_string(str(data)))
            except KetoAPIError as e:
                raise KetoAPIError(f"{path}:{lineno}: {e}") from None
    return tuples


def _check_stream(args) -> int:
    """check --stream FILE.jsonl: the whole file rides ONE StreamCheck
    session — admitted once at the handshake, blocks pipelined through
    the credit window, verdict blocks collected out-of-order and
    printed back in request order."""
    from ketotpu.api.proto_codec import tuple_to_proto
    from ketotpu.proto import stream_service_pb2 as ss
    from ketotpu.proto.services import CheckServiceStub

    try:
        tuples = _batch_check_lines(args.stream)
    except (OSError, KetoAPIError) as e:
        print(f"Could not read stream file: {e}", file=sys.stderr)
        return 1
    if not tuples:
        print("stream file holds no tuples", file=sys.stderr)
        return 1
    rows = 256  # well under the default session.max_block_rows
    blocks = [tuples[i:i + rows] for i in range(0, len(tuples), rows)]

    def requests():
        yield ss.StreamCheckRequest(
            open=True,
            snaptoken=args.snaptoken or "",
            latest=bool(args.latest),
            max_depth=args.max_depth,
        )
        for seq, block in enumerate(blocks):
            yield ss.StreamCheckRequest(
                seq=seq, tuples=[tuple_to_proto(t) for t in block]
            )
        yield ss.StreamCheckRequest(close=True)

    answered = {}
    with _channel(args.read_remote, args) as ch:
        for resp in CheckServiceStub(ch).StreamCheck(requests()):
            if resp.session:
                continue  # handshake grant
            if resp.error and not resp.results:
                if not answered and resp.status in (429, 503, 507):
                    # session refused at the handshake — nothing ran
                    hint = (f" (retry after {resp.retry_after_s}s)"
                            if resp.retry_after_s else "")
                    print(
                        f"Refused({resp.status})\t{resp.error}{hint}",
                        file=sys.stderr,
                    )
                    return 1
                answered[int(resp.seq)] = resp
                continue
            answered[int(resp.seq)] = resp
    all_ok = True
    for seq, block in enumerate(blocks):
        resp = answered.get(seq)
        if resp is None:
            all_ok = False
            for t in block:
                print(f"Error(503)\t{t}\tno verdict (stream cut)")
            continue
        if resp.error and not resp.results:
            all_ok = False
            for t in block:
                print(f"Error({resp.status or 500})\t{t}\t{resp.error}")
            continue
        for t, item in zip(block, resp.results):
            if item.error:
                all_ok = False
                print(f"Error({item.status or 500})\t{t}\t{item.error}")
            else:
                all_ok = all_ok and item.allowed
                print(("Allowed" if item.allowed else "Denied") + f"\t{t}")
    return 0 if all_ok else 1


def cmd_check(args) -> int:
    from ketotpu.api.proto_codec import subject_to_proto, tuple_to_proto
    from ketotpu.proto import check_service_pb2 as cs
    from ketotpu.proto import relation_tuples_pb2 as rts
    from ketotpu.proto.services import CheckServiceStub

    if getattr(args, "stream", ""):
        return _check_stream(args)
    if args.batch:
        # one BatchCheck RPC for the whole file: per-item verdicts come
        # back in request order, a bad line only fails its own item
        from ketotpu.proto import batch_service_pb2 as bs

        try:
            tuples = _batch_check_lines(args.batch)
        except (OSError, KetoAPIError) as e:
            print(f"Could not read batch file: {e}", file=sys.stderr)
            return 1
        if not tuples:
            print("batch file holds no tuples", file=sys.stderr)
            return 1
        req = bs.BatchCheckRequest(
            tuples=[tuple_to_proto(t) for t in tuples],
            max_depth=args.max_depth,
            snaptoken=args.snaptoken or "",
            latest=bool(args.latest),
        )
        with _channel(args.read_remote, args) as ch:
            resp = CheckServiceStub(ch).BatchCheck(req)
        all_ok = True
        for t, item in zip(tuples, resp.results):
            if item.error:
                all_ok = False
                print(f"Error({item.status or 500})\t{t}\t{item.error}")
            else:
                all_ok = all_ok and item.allowed
                print(("Allowed" if item.allowed else "Denied") + f"\t{t}")
        return 0 if all_ok else 1
    if not all((args.subject, args.relation, args.namespace, args.object)):
        print(
            "check needs SUBJECT RELATION NAMESPACE OBJECT "
            "(or --batch FILE.jsonl)", file=sys.stderr,
        )
        return 1
    try:
        subject = _parse_subject(args.subject)
    except KetoAPIError as e:
        print(f"Could not parse subject {args.subject!r}: {e}", file=sys.stderr)
        return 1
    with _channel(args.read_remote, args) as ch:
        resp = CheckServiceStub(ch).Check(
            cs.CheckRequest(
                tuple=rts.RelationTuple(
                    namespace=args.namespace,
                    object=args.object,
                    relation=args.relation,
                    subject=subject_to_proto(subject),
                ),
                max_depth=args.max_depth,
                snaptoken=args.snaptoken or "",
                latest=bool(args.latest),
            )
        )
    print("Allowed" if resp.allowed else "Denied")
    return 0 if resp.allowed else 1


def cmd_expand(args) -> int:
    from ketotpu.api.proto_codec import tree_from_proto
    from ketotpu.proto import expand_service_pb2 as es
    from ketotpu.proto import relation_tuples_pb2 as rts
    from ketotpu.proto.services import ExpandServiceStub

    with _channel(args.read_remote, args) as ch:
        resp = ExpandServiceStub(ch).Expand(
            es.ExpandRequest(
                subject=rts.Subject(
                    set=rts.SubjectSet(
                        namespace=args.namespace,
                        object=args.object,
                        relation=args.relation,
                    )
                ),
                max_depth=args.max_depth,
            )
        )
    if not resp.HasField("tree"):
        print("empty tree")
        return 0
    print(tree_from_proto(resp.tree))
    return 0


def cmd_watch(args) -> int:
    from ketotpu.api.proto_codec import tuple_from_proto
    from ketotpu.proto import watch_service_pb2 as wps
    from ketotpu.proto.services import WatchServiceStub

    with _channel(args.read_remote, args) as ch:
        stream = WatchServiceStub(ch).Watch(
            wps.WatchRelationTuplesRequest(
                snaptoken=args.since, namespace=args.namespace
            )
        )
        try:
            for resp in stream:
                if resp.event == "heartbeat" and not args.heartbeats:
                    continue
                out = {"event": resp.event, "snaptoken": resp.snaptoken}
                if resp.event == "delta":
                    out["action"] = resp.action
                    out["relation_tuple"] = tuple_from_proto(
                        resp.relation_tuple
                    ).to_json()
                print(json.dumps(out), flush=True)
                if resp.event == "resync_required":
                    # cursor fell off the bounded changelog: the caller
                    # must re-list and subscribe fresh
                    return 1
        except KeyboardInterrupt:
            pass
    return 0


def _iter_tuple_files(paths):
    for p in paths:
        path = pathlib.Path(p)
        if path.is_dir():
            yield from sorted(path.glob("*.json"))
        else:
            yield path


def _load_tuples(paths):
    out = []
    for f in _iter_tuple_files(paths):
        data = json.loads(f.read_text())
        items = data if isinstance(data, list) else [data]
        for d in items:
            d.pop("$schema", None)
            out.append(RelationTuple.from_json(d))
    return out


def _transact(remote: str, tuples, action, args=None) -> None:
    from ketotpu.api.proto_codec import tuple_to_proto
    from ketotpu.proto import write_service_pb2 as ws
    from ketotpu.proto.services import WriteServiceStub

    with _channel(remote, args) as ch:
        WriteServiceStub(ch).TransactRelationTuples(
            ws.TransactRelationTuplesRequest(
                relation_tuple_deltas=[
                    ws.RelationTupleDelta(
                        action=action, relation_tuple=tuple_to_proto(t)
                    )
                    for t in tuples
                ]
            )
        )


def cmd_rt_parse(args) -> int:
    # tuple-grammar strings -> JSON (cmd/relationtuple/parse.go:18)
    out = []
    for s in args.tuples:
        try:
            out.append(RelationTuple.from_string(s).to_json())
        except KetoAPIError as e:
            print(f"could not parse {s!r}: {e}", file=sys.stderr)
            return 1
    print(json.dumps(out if len(out) != 1 else out[0], indent=2))
    return 0


def cmd_rt_create(args) -> int:
    from ketotpu.proto import write_service_pb2 as ws

    tuples = _load_tuples(args.files)
    _transact(args.write_remote, tuples, ws.RelationTupleDelta.ACTION_INSERT, args)
    print(f"created {len(tuples)} relation tuples")
    return 0


def cmd_rt_delete(args) -> int:
    from ketotpu.proto import write_service_pb2 as ws

    tuples = _load_tuples(args.files)
    _transact(args.write_remote, tuples, ws.RelationTupleDelta.ACTION_DELETE, args)
    print(f"deleted {len(tuples)} relation tuples")
    return 0


def _query_from_flags(args):
    from ketotpu.api.proto_codec import subject_to_proto
    from ketotpu.proto import relation_tuples_pb2 as rts

    query = rts.RelationQuery()
    if args.namespace:
        query.namespace = args.namespace
    if args.object:
        query.object = args.object
    if args.relation:
        query.relation = args.relation
    if args.subject_id:
        query.subject.id = args.subject_id
    elif args.subject_set:
        query.subject.CopyFrom(subject_to_proto(_parse_subject(args.subject_set)))
    return query


def cmd_rt_get(args) -> int:
    from ketotpu.api.proto_codec import tuple_from_proto
    from ketotpu.proto import read_service_pb2 as rs
    from ketotpu.proto.services import ReadServiceStub

    with _channel(args.read_remote, args) as ch:
        resp = ReadServiceStub(ch).ListRelationTuples(
            rs.ListRelationTuplesRequest(
                relation_query=_query_from_flags(args),
                page_size=args.page_size,
                page_token=args.page_token,
            )
        )
    rows = [tuple_from_proto(t) for t in resp.relation_tuples]
    if args.format == "json":
        print(
            json.dumps(
                {
                    "relation_tuples": [r.to_json() for r in rows],
                    "next_page_token": resp.next_page_token,
                },
                indent=2,
            )
        )
    else:
        # cmdx table output analog (ketoapi/cmd_output.go)
        print(f"{'NAMESPACE':<16}{'OBJECT':<24}{'RELATION NAME':<16}SUBJECT")
        for r in rows:
            print(f"{r.namespace:<16}{r.object:<24}{r.relation:<16}{r.subject}")
        if resp.next_page_token:
            print(f"\nnext page token: {resp.next_page_token}")
    return 0


def cmd_list_objects(args) -> int:
    """`keto-tpu list objects`: reverse query — every object the subject
    reaches in namespace#relation through the engine's closure index."""
    from ketotpu.api.proto_codec import subject_to_proto, tuple_from_proto
    from ketotpu.proto import read_service_pb2 as rs
    from ketotpu.proto import relation_tuples_pb2 as rts
    from ketotpu.proto.services import ReadServiceStub

    try:
        subject = _parse_subject(args.subject)
    except KetoAPIError as e:
        print(f"Could not parse subject {args.subject!r}: {e}", file=sys.stderr)
        return 1
    query = rts.RelationQuery(
        namespace=args.namespace, relation=args.relation
    )
    query.subject.CopyFrom(subject_to_proto(subject))
    with _channel(args.read_remote, args) as ch:
        resp = ReadServiceStub(ch).ListObjects(
            rs.ListRelationTuplesRequest(
                relation_query=query,
                page_size=args.page_size,
                page_token=args.page_token,
            )
        )
    objects = [tuple_from_proto(t).object for t in resp.relation_tuples]
    if args.format == "json":
        print(json.dumps({
            "objects": objects,
            "next_page_token": resp.next_page_token,
        }, indent=2))
    else:
        for o in objects:
            print(o)
        if resp.next_page_token:
            print(f"\nnext page token: {resp.next_page_token}")
    return 0


def cmd_list_subjects(args) -> int:
    """`keto-tpu list subjects`: every subject reaching
    namespace:object#relation (the closure node's element set)."""
    from ketotpu.api.proto_codec import tuple_from_proto
    from ketotpu.proto import read_service_pb2 as rs
    from ketotpu.proto import relation_tuples_pb2 as rts
    from ketotpu.proto.services import ReadServiceStub

    query = rts.RelationQuery(
        namespace=args.namespace, object=args.object, relation=args.relation
    )
    with _channel(args.read_remote, args) as ch:
        resp = ReadServiceStub(ch).ListSubjects(
            rs.ListRelationTuplesRequest(
                relation_query=query,
                page_size=args.page_size,
                page_token=args.page_token,
            )
        )
    subjects = [str(tuple_from_proto(t).subject) for t in resp.relation_tuples]
    if args.format == "json":
        print(json.dumps({
            "subjects": subjects,
            "next_page_token": resp.next_page_token,
        }, indent=2))
    else:
        for s in subjects:
            print(s)
        if resp.next_page_token:
            print(f"\nnext page token: {resp.next_page_token}")
    return 0


def cmd_rt_delete_all(args) -> int:
    from ketotpu.proto import write_service_pb2 as ws
    from ketotpu.proto.services import WriteServiceStub

    if not args.force:
        print(
            "This would delete all relation tuples matching the query. "
            "Re-run with --force to proceed.",
            file=sys.stderr,
        )
        return 1
    with _channel(args.write_remote, args) as ch:
        WriteServiceStub(ch).DeleteRelationTuples(
            ws.DeleteRelationTuplesRequest(relation_query=_query_from_flags(args))
        )
    print("done")
    return 0


def cmd_ns_validate(args) -> int:
    from ketotpu.opl.parser import parse

    src = pathlib.Path(args.file).read_text()
    namespaces, errors = parse(src)
    for e in errors:
        print(e, file=sys.stderr)
    if errors:
        print(f"{len(errors)} parse error(s)", file=sys.stderr)
        return 1
    print(
        f"OK: {len(namespaces)} namespace(s): "
        + ", ".join(n.name for n in namespaces)
    )
    return 0


def _ready_degraded(metrics_remote: str) -> dict:
    """Best-effort readiness detail off the metrics port: the degraded
    map when the daemon reports a degraded-but-serving state, else {}."""
    import urllib.request

    url = f"http://{metrics_remote}/health/ready"
    try:
        with urllib.request.urlopen(url, timeout=2.0) as resp:
            payload = json.loads(resp.read().decode("utf-8"))
    except (OSError, ValueError):
        return {}
    if isinstance(payload, dict) and payload.get("status") == "degraded":
        return payload.get("degraded") or {}
    return {}


def _dump_flight_recorder(metrics_remote: str) -> int:
    """Fetch + pretty-print the flight recorder's slowest-request ring from
    the metrics port's debug endpoint (server/rest.py metrics_router)."""
    import urllib.request

    url = f"http://{metrics_remote}/debug/flight-recorder"
    try:
        with urllib.request.urlopen(url, timeout=5.0) as resp:
            payload = json.loads(resp.read().decode("utf-8"))
    except (OSError, ValueError) as e:
        print(f"flight recorder: unreachable ({url}: {e})", file=sys.stderr)
        return 1
    slowest = payload.get("slowest", [])
    print(f"flight recorder: {len(slowest)} slowest recent request(s)")
    for ent in slowest:
        stages = " ".join(
            f"{k}={v:.2f}ms"
            for k, v in sorted((ent.get("stages_ms") or {}).items())
        )
        extra = {
            k: v for k, v in ent.items()
            if k not in ("op", "detail", "total_ms", "ts", "stages_ms")
        }
        kv = " ".join(f"{k}={v}" for k, v in sorted(extra.items()))
        print(
            f"  {ent.get('total_ms', 0.0):9.2f}ms {ent.get('op', '?'):7s}"
            f" {ent.get('detail', '')} {stages}"
            + (f" {kv}" if kv else "")
        )
    return 0


def _fetch_debug(metrics_remote: str, path: str):
    import urllib.request

    url = f"http://{metrics_remote}{path}"
    try:
        with urllib.request.urlopen(url, timeout=5.0) as resp:
            return json.loads(resp.read().decode("utf-8"))
    except (OSError, ValueError) as e:
        print(f"{path}: unreachable ({url}: {e})", file=sys.stderr)
        return None


def _dump_waves(metrics_remote: str) -> int:
    """Pretty-print the wave ledger (server/rest.py /debug/waves): one
    line per recent wave, joinable to flight-recorder entries on wave=
    and to OTLP traces via the slowest members' traceparents."""
    payload = _fetch_debug(metrics_remote, "/debug/waves?n=16")
    if payload is None:
        return 1
    stats = payload.get("stats", {})
    waves = payload.get("waves", [])
    print(
        f"wave ledger: {stats.get('waves_recorded', 0)} wave(s) recorded, "
        f"size mean={stats.get('wave_size_mean', 0)} "
        f"p95={stats.get('wave_size_p95', 0)}, "
        f"window wait p50={stats.get('window_wait_ms_p50', 0)}ms, "
        f"device p50={stats.get('device_ms_p50', 0)}ms"
    )
    for w in waves:
        phases = " ".join(
            f"{k}={v:.2f}ms"
            for k, v in sorted((w.get("phase_ms") or {}).items())
        )
        slow = " ".join(
            f"{s.get('traceparent')}@{s.get('wait_ms', 0)}ms"
            for s in w.get("slowest", [])
        )
        print(
            f"  wave={w.get('wave'):<6} size={w.get('size'):<5}"
            f" wait_p50={w.get('window_wait_ms_p50', 0):.2f}ms"
            f" device={w.get('device_ms', 0):.2f}ms"
            f" collapsed={w.get('singleflight_collapsed', 0)}"
            f" cache_hits={w.get('cache_hits_since_prev', 0)}"
            f" leopard={w.get('leopard_answered', 0)}"
            f" fallbacks={w.get('fallbacks', 0)}"
            f" errors={w.get('errors', 0)}"
            + (f" {phases}" if phases else "")
            + (f" slowest: {slow}" if slow else "")
        )
    return 0


def _dump_compiles(metrics_remote: str) -> int:
    """Pretty-print the compile observatory (/debug/compiles): per-entry-
    point compile totals plus the recent compile event log."""
    payload = _fetch_debug(metrics_remote, "/debug/compiles")
    if payload is None:
        return 1
    per_fn = " ".join(
        f"{k}={v}" for k, v in sorted(payload.get("per_fn", {}).items())
    )
    print(
        f"xla compiles: {payload.get('compiles_total', 0)} total "
        f"({payload.get('compile_seconds_total', 0.0):.2f}s), "
        f"warm={payload.get('warm', False)}, "
        f"after_warm={payload.get('compiles_after_warm', 0)}"
        + (f" [{per_fn}]" if per_fn else "")
    )
    for ev in payload.get("log", [])[-16:]:
        flag = " AFTER-WARM" if ev.get("after_warm") else ""
        print(
            f"  {ev.get('fn', '?'):16s} {ev.get('duration_ms', 0.0):9.1f}ms"
            f" {ev.get('signature', '')}{flag}"
        )
    return 0


def _dump_projection(metrics_remote: str) -> int:
    """Pretty-print projection/compaction state (/debug/projection):
    snapshot generation, fold/rebuild/compaction counters, overlay
    occupancy and the snap <= served <= log cursor triple."""
    payload = _fetch_debug(metrics_remote, "/debug/projection")
    if payload is None:
        return 1
    if not payload:
        print("projection: n/a (engine kind has no device snapshot)")
        return 0
    print(
        f"projection: gen={payload.get('generation', 0)}"
        f" mode={payload.get('last_compaction_mode', 'none')}"
        f" rebuilds={payload.get('rebuilds', 0)}"
        f" folds={payload.get('folds', 0)}"
        f" compactions={payload.get('compactions', 0)}"
        f" errors={payload.get('compaction_errors', 0)}"
        f" background={payload.get('background', False)}"
        f" in_flight={payload.get('compaction_in_flight', False)}"
    )
    print(
        f"  cursors: snap={payload.get('snap_cursor', 0)}"
        f" served={payload.get('served_cursor', 0)}"
        f" log={payload.get('log_cursor', 0)}"
        f" pending={payload.get('pending_changes', 0)}"
        f" since_base={payload.get('since_base', 0)}"
        f"/{payload.get('fold_max_pairs', 0)}"
    )
    print(
        f"  overlay: active={payload.get('overlay_active', False)}"
        f" pairs={payload.get('overlay_pairs', 0)}"
        f"/{payload.get('overlay_pair_cap', 0)}"
        f" dirty={payload.get('overlay_dirty', 0)}"
        f"/{payload.get('overlay_dirty_cap', 0)}"
    )
    phases = " ".join(
        f"{k}={v}s"
        for k, v in sorted((payload.get("build_phases") or {}).items())
    )
    print(
        f"  last build: {payload.get('projection_build_s', 0.0)}s build,"
        f" {payload.get('projection_upload_s', 0.0)}s upload"
        + (f" [{phases}]" if phases else "")
    )
    repl = payload.get("replication")
    if repl:
        print(
            f"  replication: mode={repl.get('mode', 'async')}"
            f" attached={repl.get('attached', False)}"
            f" acked={repl.get('acked_cursor', -1)}"
            f" waits={repl.get('semi_sync_waits', 0)}"
            f" timeouts={repl.get('ack_timeouts', 0)}"
        )
    stby = payload.get("standby")
    if stby:
        print(
            f"  standby: state={stby.get('state', '?')}"
            f" cursor={stby.get('cursor', 0)}"
            f" owner_head={stby.get('owner_head', -1)}"
            f" lag={stby.get('lag_entries', 0)}"
            f" misses={stby.get('misses', 0)}"
            f"/{stby.get('miss_budget', 0)}"
            f" resyncs={stby.get('resyncs', 0)}"
            f" bootstraps={stby.get('bootstraps', 0)}"
            f" applied={stby.get('applied_entries', 0)}"
        )
    return 0


def _dump_traces(metrics_remote: str) -> int:
    """Pretty-print the tail-sampled trace store (/debug/trace): newest
    promoted request anatomies, each span with its owning pid so a
    worker-routed request visibly spans both processes."""
    payload = _fetch_debug(metrics_remote, "/debug/trace?n=8")
    if payload is None:
        return 1
    if not payload.get("enabled", False):
        print("traces: n/a (observability.trace.enabled is false)")
        return 0
    stats = payload.get("stats", {})
    traces = payload.get("traces", [])
    print(
        f"traces: {len(traces)} promoted shown "
        f"({stats.get('promotions', 0)} promoted "
        f"of {stats.get('completions', 0)} completed, "
        f"slow_ms={stats.get('slow_ms', 0)})"
    )
    for t in traces:
        print(
            f"  trace={t.get('trace_id')} {t.get('op', '?'):7s}"
            f" {t.get('total_ms', 0.0):9.2f}ms"
            f" promoted={','.join(t.get('promoted', []))}"
            f" {t.get('detail', '')}"
        )
        for s in t.get("spans", []):
            extra = {
                k: v for k, v in s.items()
                if k not in ("name", "pid", "t0", "t1", "ms")
            }
            kv = " ".join(f"{k}={v}" for k, v in sorted(extra.items()))
            print(
                f"    [pid {s.get('pid', 0)}] {s.get('name', '?'):18s}"
                f" {s.get('ms', 0.0):9.3f}ms" + (f" {kv}" if kv else "")
            )
    return 0


def _dump_divergence(metrics_remote: str) -> int:
    """Pretty-print the shadow-verification plane (/debug/divergence):
    sampler stats and every ledgered fast-path/oracle disagreement."""
    payload = _fetch_debug(metrics_remote, "/debug/divergence")
    if payload is None:
        return 1
    if not payload.get("enabled", False):
        print("shadow: n/a (plane disabled or worker relay)")
        return 0
    stats = payload.get("stats", {})
    divs = payload.get("divergences", [])
    print(
        f"shadow: {stats.get('checks', 0)} replayed"
        f" (1/{stats.get('sample_rate', 0)} sampled),"
        f" {stats.get('divergences', 0)} divergence(s),"
        f" {stats.get('skipped', 0)} skipped,"
        f" {stats.get('queued', 0)} queued"
    )
    for d in divs:
        print(
            f"  DIVERGED {d.get('tuple')} depth={d.get('depth')}"
            f" served={d.get('served')} oracle={d.get('oracle')}"
            f" tier={d.get('tier')} wave={d.get('wave')}"
            f" generation={d.get('generation')}"
            f" trace={d.get('trace_id')}"
        )
    return 0


def cmd_tenant(args) -> int:
    """Tenant lifecycle over the write port's REST admin surface
    (server/rest.py /admin/tenants; requires tenancy.enabled)."""
    import urllib.error
    import urllib.parse
    import urllib.request

    base = f"http://{args.write_remote}"

    def call(method: str, path: str, body=None):
        data = json.dumps(body).encode("utf-8") if body is not None else None
        req = urllib.request.Request(
            base + path, data=data, method=method,
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=10.0) as resp:
                return json.loads(resp.read().decode("utf-8") or "null")
        except urllib.error.HTTPError as e:
            detail = e.read().decode("utf-8", "replace")
            try:
                detail = json.loads(detail)["error"]["message"]
            except (ValueError, KeyError, TypeError):
                pass
            print(f"{method} {path}: {e.code}: {detail}", file=sys.stderr)
            return None
        except (OSError, ValueError) as e:
            print(f"{method} {path}: unreachable ({e})", file=sys.stderr)
            return None

    if args.tenant_command == "create":
        body = {"id": args.id}
        if args.opl:
            with open(args.opl, encoding="utf-8") as f:
                body["opl"] = f.read()
        out = call("POST", "/admin/tenants", body)
        if out is None:
            return 1
        print(json.dumps(out, indent=2))
        return 0
    if args.tenant_command == "list":
        out = call("GET", "/admin/tenants")
        if out is None:
            return 1
        rows = out.get("tenants", [])
        print(f"{len(rows)} tenant(s)")
        for r in rows:
            flags = [f for f, on in (("default", r.get("default")),
                                     ("opl", r.get("opl_override"))) if on]
            print(
                f"  {r.get('id', '?'):24s}"
                f" tuples={r.get('tuples', 0):<8d}"
                f" checks={r.get('checks', 0):<10d}"
                f" writes={r.get('writes', 0):<8d}"
                f" shed={r.get('shed', 0):<6d}"
                + (f" [{','.join(flags)}]" if flags else "")
            )
        return 0
    # delete
    out = call(
        "DELETE", "/admin/tenants?id=" + urllib.parse.quote(args.id)
    )
    if out is None:
        return 1
    print(json.dumps(out, indent=2))
    return 0


def cmd_status(args) -> int:
    import grpc

    from ketotpu.proto import health_pb2
    from ketotpu.proto.services import _stub_class

    if getattr(args, "debug", False):
        rcs = [
            _dump_flight_recorder(args.metrics_remote),
            _dump_waves(args.metrics_remote),
            _dump_compiles(args.metrics_remote),
            _dump_projection(args.metrics_remote),
            _dump_traces(args.metrics_remote),
            _dump_divergence(args.metrics_remote),
        ]
        return max(rcs)

    deadline = time.monotonic() + args.timeout
    while True:
        # a FRESH channel per attempt: with skip-hostname-verification the
        # channel pins the certificate fetched at creation time — a
        # channel built while the server was still down carries default
        # host-CA creds and could never verify the self-signed cert once
        # it comes up, so --block would time out against a healthy server
        try:
            with _channel(args.read_remote, args) as ch:
                stub = _stub_class("grpc.health.v1.Health")(ch)
                resp = stub.Check(health_pb2.HealthCheckRequest())
                if resp.status == health_pb2.HealthCheckResponse.SERVING:
                    # SERVING covers both healthy and degraded (device
                    # engine on CPU fallback, worker respawning): fetch
                    # the readiness detail to tell them apart
                    degraded = _ready_degraded(args.metrics_remote)
                    if degraded:
                        detail = "; ".join(
                            f"{k}={v}" for k, v in sorted(degraded.items())
                        )
                        print(f"status: SERVING (degraded: {detail})")
                    else:
                        print("status: SERVING")
                    return 0
                print(f"status: {resp.status}")
                if not args.block:
                    return 1
        except grpc.RpcError as e:
            if not args.block:
                print(f"status: unreachable ({e.code()})", file=sys.stderr)
                return 1
        if time.monotonic() > deadline:
            print("status: timeout", file=sys.stderr)
            return 1
        time.sleep(1.0)


def cmd_ns_generate_opl(args) -> int:
    """Legacy namespace config(s) -> an OPL document template
    (cmd/namespace/opl_generate.go:20).  Accepts per-namespace files
    (yaml/json/toml with a top-level name) or whole config files carrying
    a ``namespaces:`` list."""
    import yaml

    from ketotpu.storage.namespaces import DirectoryNamespaceManager

    names = []
    for p in args.files:
        if p.endswith((".json", ".toml")):
            # extension-dispatching per-namespace parser (shared with the
            # legacy directory watcher)
            try:
                names.append(DirectoryNamespaceManager._parse_file(p).name)
            except Exception as e:  # noqa: BLE001 - CLI-facing message
                print(f"{p}: {e}", file=sys.stderr)
                return 1
            continue
        data = yaml.safe_load(pathlib.Path(p).read_text())
        if isinstance(data, dict) and "namespaces" in data:
            data = data["namespaces"]
        items = data if isinstance(data, list) else [data]
        for d in items:
            name = (d or {}).get("name") if isinstance(d, dict) else None
            if not name:
                print(f"{p}: entry without a namespace name", file=sys.stderr)
                return 1
            names.append(str(name))
    print('import { Namespace, Context } from "@ory/keto-namespace-types"\n')
    for name in names:
        print(f"class {name} implements Namespace {{}}\n")
    return 0


def cmd_migrate(args) -> int:
    """Schema migrations for durable dsns (cmd/migrate/, popx analog).
    Runs locally against the configured dsn — no server required."""
    from ketotpu.driver import Provider, Registry

    cfg = Provider(config_file=args.config) if args.config else Provider()
    store = Registry(cfg).store()
    if not hasattr(store, "migrate_up"):
        print("dsn 'memory' has no migrations", file=sys.stderr)
        return 1
    if args.migrate_command == "up":
        n = store.migrate_up()
        print(f"applied {n} migration(s)")
    elif args.migrate_command == "down":
        n = store.migrate_down(args.steps)
        print(f"rolled back {n} migration(s)")
    else:
        for version, state in store.migration_status():
            print(f"{version:<44}{state}")
    return 0


def cmd_version(args) -> int:
    print(ketotpu.__version__)
    return 0


# -- parser ------------------------------------------------------------------


def _add_client_flags(p, write: bool = False) -> None:
    p.add_argument(
        "--read-remote",
        default=os.environ.get("KETO_READ_REMOTE", READ_REMOTE),
        help="read API gRPC remote (host:port; env KETO_READ_REMOTE)",
    )
    if write:
        p.add_argument(
            "--write-remote",
            default=os.environ.get("KETO_WRITE_REMOTE", WRITE_REMOTE),
            help="write API gRPC remote (host:port; env KETO_WRITE_REMOTE)",
        )
    # transport security (cmd/client/grpc_client.go:28-41): TLS against
    # the host roots unless explicitly disabled or downgraded
    p.add_argument(
        "--insecure-disable-transport-security",
        action="store_true",
        help="use a plaintext connection (no TLS)",
    )
    p.add_argument(
        "--insecure-skip-hostname-verification",
        action="store_true",
        help="TLS, but trust whatever certificate the server presents",
    )
    p.add_argument(
        "--authority",
        default="",
        help=":authority header override (env KETO_AUTHORITY)",
    )


def _add_query_flags(p) -> None:
    p.add_argument("--namespace", default="")
    p.add_argument("--object", default="")
    p.add_argument("--relation", default="")
    p.add_argument("--subject-id", default="")
    p.add_argument("--subject-set", default="")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="keto-tpu", description="TPU-native Zanzibar permission server"
    )
    sub = p.add_subparsers(dest="command", required=True)

    serve = sub.add_parser("serve", help="run the 4-port server daemon")
    serve.add_argument("-c", "--config", help="config file (yaml/json)")
    serve.add_argument(
        "--workers", type=int, default=0, metavar="N",
        help="N SO_REUSEPORT worker processes around one device owner "
             "(needs a shared durable dsn)",
    )
    serve.add_argument(
        "--front-doors", type=int, default=0, metavar="N",
        help="label the first N worker children as streaming front "
             "doors sharing one SO_REUSEPORT session-lane port "
             "(implies the --workers topology; needs a shared durable "
             "dsn)",
    )
    serve.add_argument(
        "--worker-of", metavar="SOCKET", default="",
        help="internal: run as a worker forwarding to the device owner "
             "at SOCKET",
    )
    serve.add_argument(
        "--standby", action="store_true",
        help="run as a warm standby following the owner at "
             "durability.socket; takes over the public ports on owner "
             "death or POST /debug/handoff",
    )
    serve.set_defaults(fn=cmd_serve)

    check = sub.add_parser("check", help="check a permission")
    check.add_argument("subject", nargs="?", default="")
    check.add_argument("relation", nargs="?", default="")
    check.add_argument("namespace", nargs="?", default="")
    check.add_argument("object", nargs="?", default="")
    check.add_argument("--max-depth", type=int, default=0)
    check.add_argument(
        "--batch", default="",
        help="check every relation tuple in FILE.jsonl (JSON object or "
             "'Ns:obj#rel@subject' string per line; '-' = stdin) in ONE "
             "BatchCheck RPC; prints one verdict line per tuple",
    )
    check.add_argument(
        "--stream", default="",
        help="check every relation tuple in FILE.jsonl over ONE "
             "streaming session (gRPC StreamCheck): admitted once, "
             "blocks pipelined, verdicts printed in request order",
    )
    check.add_argument(
        "--snaptoken", default="",
        help="at-least-as-fresh consistency floor for the whole batch",
    )
    check.add_argument(
        "--latest", action="store_true",
        help="force a fully fresh read",
    )
    _add_client_flags(check)
    check.set_defaults(fn=cmd_check)

    expand = sub.add_parser("expand", help="expand a subject set")
    expand.add_argument("relation")
    expand.add_argument("namespace")
    expand.add_argument("object")
    expand.add_argument("--max-depth", type=int, default=0)
    _add_client_flags(expand)
    expand.set_defaults(fn=cmd_expand)

    watch = sub.add_parser(
        "watch", help="stream relation-tuple changes (JSON lines)"
    )
    watch.add_argument(
        "--since", default="",
        help="snaptoken to resume from (replays changes after it)",
    )
    watch.add_argument(
        "--namespace", default="", help="only stream this namespace"
    )
    watch.add_argument(
        "--heartbeats", action="store_true",
        help="also print heartbeat events",
    )
    _add_client_flags(watch)
    watch.set_defaults(fn=cmd_watch)

    rt = sub.add_parser("relation-tuple", help="relation tuple commands")
    rtsub = rt.add_subparsers(dest="rt_command", required=True)

    rt_parse = rtsub.add_parser("parse", help="tuple grammar -> JSON")
    rt_parse.add_argument("tuples", nargs="+")
    rt_parse.set_defaults(fn=cmd_rt_parse)

    rt_create = rtsub.add_parser("create", help="create from JSON file(s)/dir")
    rt_create.add_argument("files", nargs="+")
    _add_client_flags(rt_create, write=True)
    rt_create.set_defaults(fn=cmd_rt_create)

    rt_delete = rtsub.add_parser("delete", help="delete from JSON file(s)/dir")
    rt_delete.add_argument("files", nargs="+")
    _add_client_flags(rt_delete, write=True)
    rt_delete.set_defaults(fn=cmd_rt_delete)

    rt_get = rtsub.add_parser("get", help="query relation tuples")
    _add_query_flags(rt_get)
    rt_get.add_argument("--page-size", type=int, default=100)
    rt_get.add_argument("--page-token", default="")
    rt_get.add_argument("--format", choices=("table", "json"), default="table")
    _add_client_flags(rt_get)
    rt_get.set_defaults(fn=cmd_rt_get)

    rt_del_all = rtsub.add_parser("delete-all", help="delete matching tuples")
    _add_query_flags(rt_del_all)
    rt_del_all.add_argument("--force", action="store_true")
    _add_client_flags(rt_del_all, write=True)
    rt_del_all.set_defaults(fn=cmd_rt_delete_all)

    lst = sub.add_parser(
        "list", help="reverse queries over the closure index"
    )
    lstsub = lst.add_subparsers(dest="list_command", required=True)

    lst_obj = lstsub.add_parser(
        "objects", help="objects a subject reaches in namespace#relation"
    )
    lst_obj.add_argument("namespace")
    lst_obj.add_argument("relation")
    lst_obj.add_argument("subject")
    lst_obj.add_argument("--page-size", type=int, default=100)
    lst_obj.add_argument("--page-token", default="")
    lst_obj.add_argument(
        "--format", choices=("table", "json"), default="table"
    )
    _add_client_flags(lst_obj)
    lst_obj.set_defaults(fn=cmd_list_objects)

    lst_sub = lstsub.add_parser(
        "subjects", help="subjects reaching namespace:object#relation"
    )
    lst_sub.add_argument("namespace")
    lst_sub.add_argument("object")
    lst_sub.add_argument("relation")
    lst_sub.add_argument("--page-size", type=int, default=100)
    lst_sub.add_argument("--page-token", default="")
    lst_sub.add_argument(
        "--format", choices=("table", "json"), default="table"
    )
    _add_client_flags(lst_sub)
    lst_sub.set_defaults(fn=cmd_list_subjects)

    ns = sub.add_parser("namespace", help="namespace commands")
    nssub = ns.add_subparsers(dest="ns_command", required=True)
    ns_validate = nssub.add_parser("validate", help="validate an OPL file")
    ns_validate.add_argument("file")
    ns_validate.set_defaults(fn=cmd_ns_validate)
    ns_gen = nssub.add_parser(
        "generate-opl", help="legacy namespace config -> OPL template"
    )
    ns_gen.add_argument("files", nargs="+")
    ns_gen.set_defaults(fn=cmd_ns_generate_opl)

    migrate = sub.add_parser("migrate", help="schema migrations (durable dsn)")
    migrate.add_argument("-c", "--config", help="config file (yaml/json)")
    migsub = migrate.add_subparsers(dest="migrate_command", required=True)
    migsub.add_parser("up", help="apply pending migrations")
    mig_down = migsub.add_parser("down", help="roll back migrations")
    mig_down.add_argument("--steps", type=int, default=1)
    migsub.add_parser("status", help="list migration status")
    migrate.set_defaults(fn=cmd_migrate)

    tenant = sub.add_parser(
        "tenant", help="tenant lifecycle (requires tenancy.enabled)"
    )
    tenant.add_argument(
        "--write-remote",
        default=os.environ.get("KETO_WRITE_REMOTE", "127.0.0.1:4467"),
        help="write-port HTTP remote hosting the /admin/tenants surface"
        " (host:port; env KETO_WRITE_REMOTE)",
    )
    tsub = tenant.add_subparsers(dest="tenant_command", required=True)
    t_create = tsub.add_parser(
        "create", help="create a tenant (idempotent)"
    )
    t_create.add_argument("id")
    t_create.add_argument(
        "--opl", help="OPL file to install as this tenant's namespace config"
    )
    tsub.add_parser("list", help="list tenants with usage counters")
    t_delete = tsub.add_parser(
        "delete", help="delete a tenant and purge its tuples"
    )
    t_delete.add_argument("id")
    tenant.set_defaults(fn=cmd_tenant)

    status = sub.add_parser("status", help="server health status")
    status.add_argument("--block", action="store_true", help="wait until SERVING")
    status.add_argument("--timeout", type=float, default=30.0)
    status.add_argument(
        "--debug", action="store_true",
        help="dump the flight recorder (slowest recent requests with"
        " per-stage latencies) from the metrics port",
    )
    status.add_argument(
        "--metrics-remote",
        default=os.environ.get("KETO_METRICS_REMOTE", "127.0.0.1:4468"),
        help="metrics HTTP remote for --debug"
        " (host:port; env KETO_METRICS_REMOTE)",
    )
    _add_client_flags(status)
    status.set_defaults(fn=cmd_status)

    version = sub.add_parser("version", help="print the version")
    version.set_defaults(fn=cmd_version)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    try:
        return args.fn(args)
    except KetoAPIError as e:
        print(str(e), file=sys.stderr)
        return 1
    except Exception as e:  # noqa: BLE001 - clean errors for RPC failures
        import grpc

        if isinstance(e, grpc.RpcError):
            code = e.code().name if hasattr(e, "code") else "UNKNOWN"
            details = e.details() if hasattr(e, "details") else str(e)
            print(f"rpc error: {code}: {details}", file=sys.stderr)
            return 1
        raise


if __name__ == "__main__":
    raise SystemExit(main())
