"""Streaming check sessions (ISSUE 19): the raw framed session lane
(server/session.py), the gRPC ``StreamCheck`` bidi stream, and the SDK
``check_session`` client.

Covers the session wire unit surface (frame fuzz, truncation, oversize
frames, out-of-order completion, mid-stream deadlines, disconnect with
blocks in flight), session-vs-batch verdict parity across all three
consistency modes, the PR 16 brownout interplay (new sessions refused at
stage >= 2 while ESTABLISHED sessions keep draining), and the SDK's
reconnect-with-replay contract.
"""

import json
import os
import pathlib
import random
import socket
import struct
import threading
import time
import urllib.request

import pytest

from ketotpu.api.types import RelationTuple
from ketotpu.driver import Provider, Registry
from ketotpu.sdk import CheckSession, KetoClient, SDKError
from ketotpu.server import wire
from ketotpu.server.daemon import serve_all
from ketotpu.server.overload import CLASS_INTERACTIVE, classify_grpc_op

FIXTURES = pathlib.Path(__file__).parent / "fixtures"

TUPLES = [
    "Group:dev#members@bob",
    "Group:admin#members@alice",
    "Folder:keto#viewers@Group:dev#members",
    "File:keto/README.md#parents@Folder:keto",
]

# canonical mix: direct hit, subject-set rewrite hit, two denies
CASES = [
    ("Group:dev#members@bob", True),
    ("File:keto/README.md#view@bob", True),
    ("File:keto/README.md#view@alice", False),
    ("File:keto/README.md#view@eve", False),
]


def _registry():
    cfg = {
        "serve": {
            n: {"host": "127.0.0.1", "port": 0}
            for n in ("read", "write", "metrics", "opl")
        },
        "namespaces": {
            "location": str(FIXTURES / "rewrites_namespaces.keto.ts")
        },
        "engine": {
            "kind": "tpu", "frontier": 512, "arena": 2048,
            "max_batch": 128, "coalesce_ms": 2,
            "mesh_devices": 0, "mesh_axis": "shard",
        },
        # the FIRST wave shape compiles ~30-60s on XLA:CPU; the lane's
        # dispatch must not fail it on the default request deadline
        "limit": {"request_timeout_ms": 180000},
        "session": {"credits": 4, "max_block_rows": 256},
        "log": {"request_log": False},
    }
    reg = Registry(Provider(cfg)).init()
    reg.store().write_relation_tuples(
        *[RelationTuple.from_string(s) for s in TUPLES]
    )
    return reg


@pytest.fixture(scope="module")
def server():
    srv = serve_all(_registry())
    yield srv
    srv.stop()


@pytest.fixture(scope="module")
def lane_addr(server):
    return tuple(server.addresses["session"])


@pytest.fixture(scope="module")
def read_url(server):
    return "http://%s:%d" % tuple(server.addresses["read"])


@pytest.fixture(scope="module")
def warm(server, read_url):
    """One streamed block up front so every later test runs against a
    hot wave cache instead of absorbing the first XLA compile."""
    client = KetoClient(read_url, timeout=300.0)
    with client.check_session(tuple(server.addresses["session"])) as sess:
        assert list(sess.stream([["Group:dev#members@bob"]])) == [[True]]
    return True


# -- raw lane helpers --------------------------------------------------------


def _connect(addr):
    sock = socket.create_connection(addr, timeout=120.0)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return sock, sock.makefile("rb")


def _hello(sock, rfile, **kw):
    meta = {"op": "hello", "v": 1}
    meta.update(kw)
    wire.send_frame(sock, meta)
    got = wire.recv_frame(rfile)
    assert got is not None, "server closed during handshake"
    return got[0]


def _send_block(sock, seq, tuples, **kw):
    n, arrays = CheckSession._encode(tuples)
    meta = {"op": "block", "seq": seq, "n": n}
    meta.update(kw)
    wire.send_frame(sock, meta, arrays)


def _recv(rfile):
    got = wire.recv_frame(rfile)
    assert got is not None, "server closed mid-session"
    return got[0], got[1]


# -- lane wire unit surface --------------------------------------------------


class TestLaneWire:
    def test_handshake_block_bye(self, lane_addr, warm):
        sock, rfile = _connect(lane_addr)
        try:
            grant = _hello(sock, rfile)
            assert grant["ok"] and grant["session"]
            assert grant["credits"] == 4
            assert grant["max_block_rows"] == 256
            _send_block(sock, 0, [c for c, _ in CASES])
            meta, arrays = _recv(rfile)
            assert meta["op"] == "verdicts" and meta["seq"] == 0
            assert meta["snaptoken"]
            assert list(map(bool, arrays["ok"])) == [w for _, w in CASES]
            wire.send_frame(sock, {"op": "end"})
            meta, _ = _recv(rfile)
            assert meta["op"] == "bye"
            assert meta["blocks"] == 1 and meta["rows"] == len(CASES)
        finally:
            sock.close()

    def test_out_of_order_completion(self, lane_addr, warm):
        """Many blocks in flight at once: every seq is answered exactly
        once, whatever order the dispatch waves complete in."""
        sock, rfile = _connect(lane_addr)
        try:
            _hello(sock, rfile)
            want = {}
            for seq in range(4):
                cases = [CASES[(seq + j) % len(CASES)] for j in range(3)]
                want[seq] = [w for _, w in cases]
                _send_block(sock, seq, [c for c, _ in cases])
            got = {}
            while len(got) < 4:
                meta, arrays = _recv(rfile)
                assert meta["op"] == "verdicts"
                assert meta["seq"] not in got, "seq answered twice"
                got[meta["seq"]] = list(map(bool, arrays["ok"]))
            assert got == want
        finally:
            sock.close()

    def test_ping_pong_and_bad_blocks(self, lane_addr, warm):
        """Protocol errors answer with an error frame and LEAVE THE
        SESSION UP: duplicate seq, empty block, oversize block."""
        sock, rfile = _connect(lane_addr)
        try:
            _hello(sock, rfile)
            wire.send_frame(sock, {"op": "ping"})
            meta, _ = _recv(rfile)
            assert meta["op"] == "pong"

            _send_block(sock, 0, ["Group:dev#members@bob"])
            meta, arrays = _recv(rfile)
            assert meta["seq"] == 0 and list(arrays["ok"]) == [1]

            # duplicate seq
            _send_block(sock, 0, ["Group:dev#members@bob"])
            meta, _ = _recv(rfile)
            assert meta["op"] == "error" and meta["status"] == 400

            # oversize block (cap is 256 rows)
            _send_block(sock, 1, ["Group:dev#members@bob"] * 257)
            meta, _ = _recv(rfile)
            assert meta["op"] == "error" and meta["status"] == 400

            # the session still serves after both errors
            _send_block(sock, 2, ["Group:dev#members@eve"])
            meta, arrays = _recv(rfile)
            assert meta["op"] == "verdicts" and list(arrays["ok"]) == [0]
        finally:
            sock.close()

    def test_frame_fuzz_closes_cleanly(self, lane_addr, warm, server):
        """Garbage, truncated, and oversize frames kill only THEIR
        connection — the lane keeps accepting new sessions."""
        rng = random.Random(19)
        for payload in (
            bytes(rng.randrange(256) for _ in range(64)),  # garbage
            wire.HEADER.pack(64, 0)[:3],  # truncated header
            wire.HEADER.pack(1 << 30, 1 << 30),  # oversize lengths
            struct.pack("!I", 7),  # half a header
        ):
            sock = socket.create_connection(lane_addr, timeout=30.0)
            sock.sendall(payload)
            sock.close()
        # truncation AFTER a valid handshake: header then hangup
        sock, rfile = _connect(lane_addr)
        _hello(sock, rfile)
        n, arrays = CheckSession._encode(["Group:dev#members@bob"])
        import io

        buf = io.BytesIO()

        class _W:
            def sendall(self, b):
                buf.write(b)

        wire.send_frame(_W(), {"op": "block", "seq": 0, "n": n}, arrays)
        sock.sendall(buf.getvalue()[: max(8, len(buf.getvalue()) // 2)])
        sock.close()

        # the lane survives all of it
        deadline = time.monotonic() + 30.0
        while True:
            sock, rfile = _connect(lane_addr)
            try:
                grant = _hello(sock, rfile)
                assert grant["ok"]
                break
            except AssertionError:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.2)
            finally:
                sock.close()

    def test_mid_stream_deadline(self, lane_addr, warm):
        """A block's deadline_ms is ITS budget: expiry answers every
        unanswered row with a per-item 504 (the columnar partial-results
        contract); the session and later blocks live on."""
        sock, rfile = _connect(lane_addr)
        try:
            _hello(sock, rfile)
            # fresh subjects: no cache hit may answer under the budget —
            # the block must ride a device wave, which alone outlives a
            # 1ms deadline (coalesce window is 2ms)
            _send_block(
                sock, 0,
                [f"Group:dev#members@deadline-{i}" for i in range(32)],
                deadline_ms=1,
            )
            meta, _ = _recv(rfile)
            assert meta["op"] == "verdicts" and meta["seq"] == 0
            errs = {row: code for row, _, code in meta["errs"]}
            assert len(errs) == 32
            assert all(code == 504 for code in errs.values())
            _send_block(sock, 1, ["Group:dev#members@bob"])
            meta, arrays = _recv(rfile)
            assert meta["op"] == "verdicts" and list(arrays["ok"]) == [1]
        finally:
            sock.close()

    def test_disconnect_releases_admission(self, server, lane_addr, warm):
        """Abrupt hangup with a block in flight: the broker must drop
        the session and release its admission grant."""
        broker = server.registry.session_broker()
        base = broker.active()
        sock, rfile = _connect(lane_addr)
        _hello(sock, rfile)
        assert broker.active() == base + 1
        _send_block(sock, 0, [c for c, _ in CASES])
        # no end frame, verdicts possibly still in flight.  The reader
        # holds a reference to the socket: close it too, or the close is
        # deferred and the lane sees no hangup, only its idle expiry
        rfile.close()
        sock.close()
        deadline = time.monotonic() + 30.0
        while broker.active() != base:
            assert time.monotonic() < deadline, \
                "session not reaped after disconnect"
            time.sleep(0.05)

    def test_snaptoken_handshake_pins_floor(self, lane_addr, read_url, warm):
        """A session opened with a snaptoken serves at-least-as-fresh:
        verdict frames echo a token, and a bogus token is refused."""
        sock, rfile = _connect(lane_addr)
        try:
            grant = _hello(sock, rfile)
            assert grant["ok"]
            _send_block(sock, 0, ["Group:dev#members@bob"])
            meta, _ = _recv(rfile)
            token = meta["snaptoken"]
            assert token
        finally:
            sock.close()
        sock, rfile = _connect(lane_addr)
        try:
            grant = _hello(sock, rfile, snaptoken=token)
            assert grant["ok"], grant
            _send_block(sock, 0, ["Group:dev#members@bob"])
            meta, arrays = _recv(rfile)
            assert meta["op"] == "verdicts" and list(arrays["ok"]) == [1]
        finally:
            sock.close()


# -- parity: session verdicts == batch verdicts ------------------------------


def _random_queries(rng, n):
    """Mixed hit/miss/subject-set queries over the fixture universe."""
    users = ["bob", "alice", "eve", "mallory", "trent"]
    out = []
    for _ in range(n):
        kind = rng.randrange(4)
        if kind == 0:
            out.append(
                f"Group:{rng.choice(['dev', 'admin', 'ops'])}#members@"
                f"{rng.choice(users)}"
            )
        elif kind == 1:
            out.append(f"File:keto/README.md#view@{rng.choice(users)}")
        elif kind == 2:
            out.append(
                f"Folder:{rng.choice(['keto', 'other'])}#viewers@"
                f"{rng.choice(users)}"
            )
        else:
            out.append(
                f"Folder:keto#viewers@Group:"
                f"{rng.choice(['dev', 'admin'])}#members"
            )
    return out


def _grpc_batch(server, queries, *, snaptoken="", latest=False):
    import grpc

    from ketotpu.api.proto_codec import tuple_to_proto
    from ketotpu.proto import batch_service_pb2 as bs
    from ketotpu.proto.services import CheckServiceStub

    target = "%s:%d" % tuple(server.addresses["read"])
    req = bs.BatchCheckRequest(
        tuples=[
            tuple_to_proto(RelationTuple.from_string(q)) for q in queries
        ],
        snaptoken=snaptoken, latest=latest,
    )
    with grpc.insecure_channel(target) as ch:
        resp = CheckServiceStub(ch).BatchCheck(req)
    return [bool(r.allowed) for r in resp.results], resp.snaptoken


class TestSessionBatchParity:
    @pytest.mark.parametrize("mode", ["none", "snaptoken", "latest"])
    def test_randomized_parity(self, server, read_url, warm, mode):
        """The acceptance contract: a streamed session answers EXACTLY
        like one BatchCheck for the same queries at the same state, in
        every consistency mode."""
        rng = random.Random({"none": 11, "snaptoken": 22, "latest": 33}[mode])
        queries = _random_queries(rng, 96)
        # a current token first, so the snaptoken mode pins BOTH paths
        # to the same at-least-as-fresh floor
        _, token = _grpc_batch(server, ["Group:dev#members@bob"])
        batch_verdicts, _ = _grpc_batch(
            server, queries,
            snaptoken=token if mode == "snaptoken" else "",
            latest=(mode == "latest"),
        )
        consistency = {
            "none": None, "snaptoken": token, "latest": "latest",
        }[mode]
        client = KetoClient(read_url, timeout=200.0)
        with client.check_session(
            tuple(server.addresses["session"]), consistency=consistency
        ) as sess:
            got = []
            for block in (queries[i: i + 32] for i in range(0, 96, 32)):
                got.extend(sess.stream([block]))
        stream_verdicts = [v for blk in got for v in blk]
        assert stream_verdicts == batch_verdicts


# -- brownout / overload interplay (satellite 6) -----------------------------


class TestBrownout:
    def test_stream_class_is_interactive(self):
        # the gRPC admission interceptor lowercases the method suffix
        assert classify_grpc_op("streamcheck") == CLASS_INTERACTIVE

    def test_refuses_new_keeps_draining(self, server, lane_addr, warm):
        """Brownout stage 2: new handshakes shed with Retry-After while
        an ESTABLISHED interactive session keeps getting verdicts."""
        ov = server.registry.overload()
        assert ov is not None
        sock, rfile = _connect(lane_addr)
        try:
            assert _hello(sock, rfile)["ok"]
            ov.force_stage(2, "test")
            try:
                # a small handshake storm: every one refused, bounded,
                # with a retry hint — no crash, no hang
                for _ in range(8):
                    s2, r2 = _connect(lane_addr)
                    try:
                        nack = _hello(s2, r2)
                        assert nack["ok"] is False
                        assert nack["status"] == 503
                        assert int(nack["retry_after"]) >= 1
                        assert wire.recv_frame(r2) is None  # closed
                    finally:
                        s2.close()
                # the established session drains through the brownout
                _send_block(sock, 0, ["Group:dev#members@bob"])
                meta, arrays = _recv(rfile)
                assert meta["op"] == "verdicts"
                assert list(arrays["ok"]) == [1]
            finally:
                ov.force_stage(0, "test-restore")
        finally:
            sock.close()


# -- gRPC StreamCheck --------------------------------------------------------


class TestGrpcStreamCheck:
    def test_stream_roundtrip(self, server, warm):
        import grpc

        from ketotpu.api.proto_codec import tuple_to_proto
        from ketotpu.proto import stream_service_pb2 as ss
        from ketotpu.proto.services import CheckServiceStub

        target = "%s:%d" % tuple(server.addresses["read"])

        def requests():
            yield ss.StreamCheckRequest(open=True)
            for seq, (case, _) in enumerate(CASES):
                yield ss.StreamCheckRequest(
                    seq=seq,
                    tuples=[tuple_to_proto(RelationTuple.from_string(case))],
                )
            # duplicate seq: answered as a per-block 400, stream lives
            yield ss.StreamCheckRequest(
                seq=0,
                tuples=[tuple_to_proto(
                    RelationTuple.from_string(CASES[0][0])
                )],
            )
            yield ss.StreamCheckRequest(close=True)

        got, dup_errors, grant = {}, [], None
        with grpc.insecure_channel(target) as ch:
            for resp in CheckServiceStub(ch).StreamCheck(requests()):
                if resp.session:
                    grant = resp
                    continue
                if resp.error and not resp.results:
                    dup_errors.append((resp.seq, resp.status))
                    continue
                got[resp.seq] = [r.allowed for r in resp.results]
                assert resp.snaptoken
        assert grant is not None and grant.credits > 0
        assert got == {
            seq: [want] for seq, (_, want) in enumerate(CASES)
        }
        assert dup_errors == [(0, 400)]


# -- SDK reconnect / replay --------------------------------------------------


class TestSdkSession:
    def test_out_of_order_results(self, server, read_url, warm):
        client = KetoClient(read_url, timeout=200.0)
        with client.check_session(
            tuple(server.addresses["session"])
        ) as sess:
            seqs = [
                sess.submit([c for c, _ in CASES]),
                sess.submit(["Group:dev#members@eve"]),
            ]
            got = {seq: v for seq, v, errs in sess.results()}
        assert got[seqs[0]] == [w for _, w in CASES]
        assert got[seqs[1]] == [False]

    def test_reconnect_replays_unacked(self, server, read_url, warm):
        """Kill the transport with a block UNACKED: the session must
        reconnect, replay it on a fresh server session, and still hand
        back its verdicts."""
        client = KetoClient(read_url, timeout=200.0)
        with client.check_session(
            tuple(server.addresses["session"])
        ) as sess:
            first = sess.submit(["Group:dev#members@bob"])
            assert sess.wait(first) == ([True], {})
            seq = sess.submit([c for c, _ in CASES])
            # sever the lane underneath the client before the verdict
            # frame is consumed
            sess._sock.shutdown(socket.SHUT_RDWR)
            verdicts, errs = sess.wait(seq)
            assert errs == {}
            assert verdicts == [w for _, w in CASES]
            assert sess.reconnects == 1
        assert client.retries >= 0

    def test_refusal_surfaces_sdk_error(self, server, read_url, warm):
        """A brownout refusal at the handshake raises SDKError with the
        server's status once the retry budget is spent."""
        ov = server.registry.overload()
        client = KetoClient(read_url, timeout=30.0, max_retries=0)
        ov.force_stage(2, "test")
        try:
            with pytest.raises(SDKError) as exc:
                client.check_session(tuple(server.addresses["session"]))
            assert exc.value.status == 503
        finally:
            ov.force_stage(0, "test-restore")


# -- metrics / config surface ------------------------------------------------


class TestSessionSurface:
    def test_metrics_vocabulary(self, server, read_url, warm):
        host, port = server.addresses["metrics"]
        with urllib.request.urlopen(
            f"http://{host}:{port}/metrics/prometheus", timeout=30.0
        ) as resp:
            body = resp.read().decode()
        assert "keto_session_open_total" in body
        assert "keto_session_active" in body
        assert "keto_session_blocks_total" in body

    def test_env_overrides_map(self):
        cfg = Provider(env={
            "KETO_SESSION_MAX_BLOCK_ROWS": "128",
            "KETO_SESSION_CREDITS": "2",
            "KETO_SESSION_ENABLED": "false",
        })
        assert cfg.get("session.max_block_rows") == 128
        assert cfg.get("session.credits") == 2
        assert cfg.get("session.enabled") is False

    def test_config_validation_rejects_bad_knobs(self):
        with pytest.raises(Exception):
            Provider({"session": {"credits": 0}})
        with pytest.raises(Exception):
            Provider({"session": {"port": 70000}})
