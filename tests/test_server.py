"""E2E serving tests: boot the real daemon, same cases over REST and gRPC.

The reference's e2e suite runs one case list through four transports
(`internal/e2e/full_suit_test.go:51-130`); here the matrix is REST + gRPC
(the CLI transport is exercised in tests/test_cli.py).  Fixtures are the
vendored cat-videos example (direct tuples + wildcard subject) and the
rewrites example OPL (subject-set rewrites), the two acceptance configs of
BASELINE.json.
"""

import json
import pathlib
import socket
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

import grpc
import pytest

from ketotpu.api.types import RelationTuple, SubjectID, SubjectSet
from ketotpu.driver import Provider, Registry
from ketotpu.proto import (
    check_service_pb2 as cs,
)
from ketotpu.proto import (
    expand_service_pb2 as es,
)
from ketotpu.proto import (
    read_service_pb2 as rs,
)
from ketotpu.proto import (
    relation_tuples_pb2 as rts,
)
from ketotpu.proto import (
    write_service_pb2 as ws,
)
from ketotpu.proto.services import (
    CheckServiceStub,
    ExpandServiceStub,
    ReadServiceStub,
    WriteServiceStub,
)
from ketotpu.server import serve_all

FIXTURES = pathlib.Path(__file__).parent / "fixtures"


def _http(method, url, body=None, headers=None):
    req = urllib.request.Request(
        url, data=body, method=method, headers=headers or {}
    )
    try:
        with urllib.request.urlopen(req) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


@pytest.fixture(scope="module")
def server():
    cfg = Provider(
        {
            "serve": {
                n: {"host": "127.0.0.1", "port": 0}
                for n in ("read", "write", "metrics", "opl")
            },
            "namespaces": {
                "location": str(FIXTURES / "rewrites_namespaces.keto.ts")
            },
            "engine": {
                "kind": "tpu",
                "frontier": 1024,
                "arena": 4096,
                "max_batch": 256,
                "retry_scale": 4,
                "mesh_devices": 0,
                "mesh_axis": "shard",
            },
        }
    )
    reg = Registry(cfg).init()
    srv = serve_all(reg)
    # seed the rewrites-example graph shape (contrib/rewrites-example)
    reg.store().write_relation_tuples(
        *[
            RelationTuple.from_string(s)
            for s in [
                "Group:admin#members@alice",
                "Group:dev#members@bob",
                "Folder:keto#viewers@Group:dev#members",
                "File:keto/README.md#parents@Folder:keto",
                "File:private#owners@alice",
            ]
        ]
    )
    yield srv
    srv.stop()


@pytest.fixture(scope="module")
def read_addr(server):
    return "http://%s:%d" % tuple(server.addresses["read"])


@pytest.fixture(scope="module")
def write_addr(server):
    return "http://%s:%d" % tuple(server.addresses["write"])


@pytest.fixture(scope="module")
def read_channel(server):
    ch = grpc.insecure_channel("%s:%d" % tuple(server.addresses["read"]))
    yield ch
    ch.close()


@pytest.fixture(scope="module")
def write_channel(server):
    ch = grpc.insecure_channel("%s:%d" % tuple(server.addresses["write"]))
    yield ch
    ch.close()


# the shared case list (testcases_test.go analog): (tuple string, allowed)
CASES = [
    ("File:keto/README.md#view@bob", True),  # TTU parents -> Folder viewers
    ("File:keto/README.md#view@alice", False),
    ("Folder:keto#view@bob", True),  # viewers expansion through Group
    ("File:private#view@alice", True),  # owners computed userset
    ("File:private#view@bob", False),
    ("File:nonexistent#view@bob", False),
]


def _parse_case(s):
    r = RelationTuple.from_string(s)
    return r


class TestTransportParity:
    def test_rest_and_grpc_agree(self, read_addr, read_channel):
        stub = CheckServiceStub(read_channel)
        for case, want in CASES:
            r = _parse_case(case)
            q = urllib.parse.urlencode(r.to_url_query())
            status, body = _http(
                "GET", f"{read_addr}/relation-tuples/check/openapi?{q}"
            )
            assert status == 200, body
            rest_allowed = json.loads(body)["allowed"]

            from ketotpu.api.proto_codec import tuple_to_proto

            resp = stub.Check(cs.CheckRequest(tuple=tuple_to_proto(r)))
            assert rest_allowed == resp.allowed == want, case

    def test_mirror_status_variant(self, read_addr):
        # /relation-tuples/check mirrors the verdict as 200/403
        r = _parse_case("File:keto/README.md#view@bob")
        q = urllib.parse.urlencode(r.to_url_query())
        status, body = _http("GET", f"{read_addr}/relation-tuples/check?{q}")
        assert status == 200 and json.loads(body)["allowed"] is True
        r2 = _parse_case("File:private#view@bob")
        q2 = urllib.parse.urlencode(r2.to_url_query())
        status2, body2 = _http("GET", f"{read_addr}/relation-tuples/check?{q2}")
        assert status2 == 403 and json.loads(body2)["allowed"] is False

    def test_unknown_namespace_rest_false_grpc_not_found(
        self, read_addr, read_channel
    ):
        q = "namespace=Nope&object=o&relation=r&subject_id=s"
        status, body = _http(
            "GET", f"{read_addr}/relation-tuples/check/openapi?{q}"
        )
        assert status == 200 and json.loads(body)["allowed"] is False
        stub = CheckServiceStub(read_channel)
        with pytest.raises(grpc.RpcError) as e:
            stub.Check(
                cs.CheckRequest(
                    tuple=rts.RelationTuple(
                        namespace="Nope",
                        object="o",
                        relation="r",
                        subject=rts.Subject(id="s"),
                    )
                )
            )
        assert e.value.code() == grpc.StatusCode.NOT_FOUND

    def test_post_check_json(self, read_addr):
        body = json.dumps(
            _parse_case("Folder:keto#view@bob").to_json()
        ).encode()
        status, out = _http(
            "POST",
            f"{read_addr}/relation-tuples/check/openapi",
            body,
            {"Content-Type": "application/json"},
        )
        assert status == 200 and json.loads(out)["allowed"] is True


class TestExpand:
    def test_rest_expand_tree(self, read_addr):
        status, body = _http(
            "GET",
            f"{read_addr}/relation-tuples/expand?"
            "namespace=Folder&object=keto&relation=viewers&max-depth=3",
        )
        assert status == 200
        tree = json.loads(body)
        assert tree["type"] == "union"
        labels = json.dumps(tree)
        assert "bob" in labels

    def test_rest_expand_404_when_empty(self, read_addr):
        status, _ = _http(
            "GET",
            f"{read_addr}/relation-tuples/expand?"
            "namespace=Folder&object=none&relation=viewers",
        )
        assert status == 404

    def test_grpc_expand_subject_id_leaf(self, read_channel):
        stub = ExpandServiceStub(read_channel)
        resp = stub.Expand(
            es.ExpandRequest(subject=rts.Subject(id="alice"), max_depth=2)
        )
        assert resp.tree.node_type == es.NodeType.NODE_TYPE_LEAF

    def test_grpc_expand_tree(self, read_channel):
        stub = ExpandServiceStub(read_channel)
        resp = stub.Expand(
            es.ExpandRequest(
                subject=rts.Subject(
                    set=rts.SubjectSet(
                        namespace="Folder", object="keto", relation="viewers"
                    )
                ),
                max_depth=3,
            )
        )
        assert resp.tree.node_type == es.NodeType.NODE_TYPE_UNION


class TestReadWrite:
    def test_list_with_pagination(self, read_addr, read_channel):
        status, body = _http(
            "GET", f"{read_addr}/relation-tuples?namespace=Group&page_size=1"
        )
        assert status == 200
        page = json.loads(body)
        assert len(page["relation_tuples"]) == 1
        assert page["next_page_token"]
        # gRPC agrees
        stub = ReadServiceStub(read_channel)
        resp = stub.ListRelationTuples(
            rs.ListRelationTuplesRequest(
                relation_query=rts.RelationQuery(namespace="Group"),
                page_size=10,
            )
        )
        assert len(resp.relation_tuples) == 2

    def test_rest_write_delete_cycle(self, read_addr, write_addr):
        t = {
            "namespace": "Group",
            "object": "tmp",
            "relation": "members",
            "subject_id": "zoe",
        }
        status, body = _http(
            "PUT",
            f"{write_addr}/admin/relation-tuples",
            json.dumps(t).encode(),
            {"Content-Type": "application/json"},
        )
        assert status == 201, body
        status, body = _http(
            "GET", f"{read_addr}/relation-tuples?namespace=Group&object=tmp"
        )
        assert len(json.loads(body)["relation_tuples"]) == 1
        # delete validates query params (transact_server.go:193-199)
        status, body = _http(
            "DELETE", f"{write_addr}/admin/relation-tuples?object=tmp"
        )
        assert status == 400  # namespace required
        status, _ = _http(
            "DELETE",
            f"{write_addr}/admin/relation-tuples?namespace=Group&object=tmp",
        )
        assert status == 204
        status, body = _http(
            "GET", f"{read_addr}/relation-tuples?namespace=Group&object=tmp"
        )
        assert json.loads(body)["relation_tuples"] == []

    def test_rest_patch_deltas(self, read_addr, write_addr):
        deltas = [
            {
                "action": "insert",
                "relation_tuple": {
                    "namespace": "Group",
                    "object": "patchgrp",
                    "relation": "members",
                    "subject_id": "pat",
                },
            }
        ]
        status, _ = _http(
            "PATCH",
            f"{write_addr}/admin/relation-tuples",
            json.dumps(deltas).encode(),
            {"Content-Type": "application/json"},
        )
        assert status == 204
        deltas[0]["action"] = "delete"
        status, _ = _http(
            "PATCH",
            f"{write_addr}/admin/relation-tuples",
            json.dumps(deltas).encode(),
            {"Content-Type": "application/json"},
        )
        assert status == 204

    def test_grpc_transact_returns_real_snaptokens(self, write_channel):
        from ketotpu import consistency

        stub = WriteServiceStub(write_channel)

        def delta(action, obj, sid):
            return ws.RelationTupleDelta(
                action=action,
                relation_tuple=rts.RelationTuple(
                    namespace="Group",
                    object=obj,
                    relation="members",
                    subject=rts.Subject(id=sid),
                ),
            )

        resp = stub.TransactRelationTuples(
            ws.TransactRelationTuplesRequest(
                relation_tuple_deltas=[
                    delta(ws.RelationTupleDelta.ACTION_INSERT,
                          "grpcgrp", "gal")
                ]
            )
        )
        assert len(resp.snaptokens) == 1
        tok = consistency.decode(resp.snaptokens[0])
        assert tok.version > 0 and tok.cursor >= 0
        # one token per delta, deletes included: a mixed transact with
        # 2 inserts and 1 delete must return exactly 3 tokens
        resp = stub.TransactRelationTuples(
            ws.TransactRelationTuplesRequest(
                relation_tuple_deltas=[
                    delta(ws.RelationTupleDelta.ACTION_INSERT,
                          "grpcgrp", "hal"),
                    delta(ws.RelationTupleDelta.ACTION_INSERT,
                          "grpcgrp", "ida"),
                    delta(ws.RelationTupleDelta.ACTION_DELETE,
                          "grpcgrp", "gal"),
                ]
            )
        )
        assert len(resp.snaptokens) == 3
        assert all(
            consistency.decode(t).version > 0 for t in resp.snaptokens
        )
        # delete-only transacts mint tokens too (the seed returned none)
        resp = stub.TransactRelationTuples(
            ws.TransactRelationTuplesRequest(
                relation_tuple_deltas=[
                    delta(ws.RelationTupleDelta.ACTION_DELETE,
                          "grpcgrp", "hal"),
                    delta(ws.RelationTupleDelta.ACTION_DELETE,
                          "grpcgrp", "ida"),
                ]
            )
        )
        assert len(resp.snaptokens) == 2
        stub.DeleteRelationTuples(
            ws.DeleteRelationTuplesRequest(
                relation_query=rts.RelationQuery(
                    namespace="Group", object="grpcgrp"
                )
            )
        )


class TestAuxSurfaces:
    def test_health_version_metrics(self, server):
        met = "http://%s:%d" % tuple(server.addresses["metrics"])
        assert _http("GET", f"{met}/health/alive")[0] == 200
        assert _http("GET", f"{met}/health/ready")[0] == 200
        status, body = _http("GET", f"{met}/version")
        assert status == 200 and "version" in json.loads(body)
        status, text = _http("GET", f"{met}/metrics/prometheus")
        assert status == 200
        assert "keto_checks_total" in text
        assert "keto_http_request_duration_seconds" in text

    def test_opl_syntax_check(self, server):
        opl = "http://%s:%d" % tuple(server.addresses["opl"])
        status, body = _http(
            "POST", f"{opl}/opl/syntax/check",
            b"class X implements Namespace {}",
        )
        assert status == 200 and json.loads(body)["errors"] == []
        status, body = _http(
            "POST", f"{opl}/opl/syntax/check", b"class {{ nope"
        )
        errors = json.loads(body)["errors"]
        assert status == 200 and errors
        assert {"message", "start", "end"} <= set(errors[0])

    def test_unknown_route_404_known_route_wrong_method_405(self, read_addr):
        assert _http("GET", f"{read_addr}/nope")[0] == 404
        assert _http("POST", f"{read_addr}/relation-tuples")[0] == 405


class TestSDKTransport:
    """Fourth transport of the e2e matrix (full_suit_test.go:65-94): the
    Python SDK (ketotpu/sdk.py) over REST, same shared case list."""

    @pytest.fixture()
    def sdk(self, read_addr, write_addr):
        from ketotpu.sdk import KetoClient

        return KetoClient(read_addr, write_addr)

    def test_check_cases(self, sdk):
        for case, want in CASES:
            r = _parse_case(case)
            assert sdk.check_tuple(r) is want, case

    def test_expand_and_none(self, sdk):
        from ketotpu.api.types import SubjectSet, TreeNodeType

        tree = sdk.expand(SubjectSet("Folder", "keto", "viewers"), max_depth=3)
        assert tree is not None and tree.type == TreeNodeType.UNION
        assert "bob" in json.dumps(tree.to_json())
        assert sdk.expand(SubjectSet("Folder", "none", "viewers")) is None

    def test_write_list_delete_cycle(self, sdk):
        from ketotpu.api.types import RelationQuery

        t = RelationTuple.from_string("Group:sdk#members@carol")
        created = sdk.create_relation_tuple(t)
        assert created == t
        rows, _ = sdk.list_relation_tuples(RelationQuery(object="sdk"))
        assert rows == [t]
        assert sdk.check_tuple(
            RelationTuple.from_string("Group:sdk#members@carol")
        )
        sdk.delete_relation_tuple(t)
        rows, _ = sdk.list_relation_tuples(RelationQuery(object="sdk"))
        assert rows == []

    def test_patch_deltas(self, sdk):
        from ketotpu.api.types import RelationQuery

        a = RelationTuple.from_string("Group:sdkp#members@dave")
        b = RelationTuple.from_string("Group:sdkp#members@erin")
        sdk.patch([("insert", a), ("insert", b)])
        sdk.patch([("delete", a)])
        rows, _ = sdk.list_relation_tuples(RelationQuery(object="sdkp"))
        assert rows == [b]
        sdk.patch([("delete", b)])

    def test_opl_syntax_check(self, sdk, server):
        from ketotpu.sdk import KetoClient

        opl = KetoClient("http://%s:%d" % tuple(server.addresses["opl"]))
        assert opl.check_opl_syntax("class A implements Namespace {}") == []
        errs = opl.check_opl_syntax("class ??? {")
        assert errs and all("message" in e for e in errs)

    def test_version_and_health(self, sdk):
        import ketotpu

        assert sdk.health() is True
        assert sdk.version() == ketotpu.__version__

    def test_errors_are_typed(self, sdk):
        from ketotpu.api.types import BadRequestError

        with pytest.raises(BadRequestError):
            sdk.list_relation_tuples(page_token="not-a-token")


def test_concurrent_grpc_checks_fill_shared_waves(
        server, read_channel, monkeypatch):
    """48 clients' single Checks all wait inside the server at once, so a
    wave carries what the coalescer's four places leave it of 48, not of
    the 16 threads the gRPC pool used to stop at (waves of four rows)."""
    stub = CheckServiceStub(read_channel)
    reg = server.registry
    # a wave on the CPU takes a few ms, less than this process's threads
    # need to send 48 checks: a window as long as a wave on the chip
    monkeypatch.setattr(reg.check_engine(), "window", 0.03)

    def check(subject):
        return stub.Check(cs.CheckRequest(tuple=rts.RelationTuple(
            namespace="File", object="keto/README.md", relation="view",
            subject=rts.Subject(id=subject),
        )), timeout=120).allowed

    assert check("bob") is True  # the wave program is compiled
    ledger = reg.wave_ledger()
    waves_before = max(w["wave"] for w in ledger.snapshot())

    def gauges():
        reg.sample_engine_metrics()
        m = reg.metrics()
        return (m.get_gauge("keto_engine_coalesced_checks"),
                m.get_gauge("keto_engine_coalesced_waves"))

    checks0, waves0 = gauges()
    clients, rounds = 48, 6
    denied = []

    def client(k):
        # every check another key: no result-cache hit, no singleflight
        denied.extend(check(f"nobody-{k}-{i}") for i in range(rounds))

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
        assert not t.is_alive()
    assert denied == [False] * (clients * rounds)
    checks1, waves1 = gauges()
    assert checks1 - checks0 == clients * rounds
    assert (checks1 - checks0) / (waves1 - waves0) > 4.0
    # and some wave held more checks than sixteen threads could bring
    sizes = [w["size"] for w in ledger.snapshot()
             if w["wave"] > waves_before]
    assert max(sizes) > 16, sizes
    reg.sample_engine_metrics()
    assert reg.metrics().get_gauge(
        "keto_frontdoor_pool_max", door="grpc") == 3 * 1024  # three ports


def test_engine_gauges_on_metrics(server, read_addr):
    status, body = _http("GET", f"{read_addr}/metrics/prometheus")
    assert status == 200
    _, text = body if isinstance(body, tuple) else (None, body)
    assert "keto_engine_snapshot_rebuilds" in text
    assert "keto_engine_oracle_fallbacks" in text


class TestBatchCheck:
    def test_rest_batch_matches_singles(self, read_addr):
        body = json.dumps(
            {"tuples": [_parse_case(c).to_json() for c, _ in CASES]}
        ).encode()
        status, out = _http(
            "POST", f"{read_addr}/relation-tuples/check/batch", body,
            {"Content-Type": "application/json"},
        )
        assert status == 200
        data = json.loads(out)
        assert [r["allowed"] for r in data["results"]] == [w for _, w in CASES]
        from ketotpu import consistency

        assert consistency.decode(data["snaptoken"]).version >= 0

    def test_sdk_batch_check(self, read_addr, write_addr):
        from ketotpu.sdk import KetoClient

        sdk = KetoClient(read_addr, write_addr)
        got = sdk.batch_check([_parse_case(c) for c, _ in CASES])
        assert got == [w for _, w in CASES]

    def test_batch_rejects_malformed(self, read_addr):
        status, _ = _http(
            "POST", f"{read_addr}/relation-tuples/check/batch",
            json.dumps({"nope": 1}).encode(),
            {"Content-Type": "application/json"},
        )
        assert status == 400


def test_batch_check_works_with_oracle_engine():
    """The batch endpoint must serve engine.kind=oracle too (the oracle
    has no batch surface; the handler loops check_is_member)."""
    cfg = Provider(
        {
            "serve": {
                n: {"host": "127.0.0.1", "port": 0}
                for n in ("read", "write", "metrics", "opl")
            },
            "namespaces": {
                "location": str(FIXTURES / "rewrites_namespaces.keto.ts")
            },
            "engine": {"kind": "oracle"},
        }
    )
    reg = Registry(cfg).init()
    reg.store().write_relation_tuples(
        RelationTuple.from_string("Group:g#members@alice")
    )
    srv = serve_all(reg)
    try:
        addr = "http://%s:%d" % tuple(srv.addresses["read"])
        body = json.dumps({"tuples": [
            RelationTuple.from_string("Group:g#members@alice").to_json(),
            RelationTuple.from_string("Group:g#members@bob").to_json(),
        ]}).encode()
        status, out = _http(
            "POST", f"{addr}/relation-tuples/check/batch", body,
            {"Content-Type": "application/json"},
        )
        assert status == 200
        assert [r["allowed"] for r in json.loads(out)["results"]] == [
            True, False,
        ]
    finally:
        srv.stop()


def test_openapi_spec_matches_routes():
    """spec/api.json is the wire-contract artifact (layer 9): every
    method+path it documents must exist in a router table."""
    import pathlib as _pl

    from ketotpu.server import rest as _rest

    spec = json.loads(
        (_pl.Path(__file__).parent.parent / "spec" / "api.json").read_text()
    )
    reg = Registry(Provider({"engine": {"kind": "oracle"}}))
    routes = set()
    for build in (_rest.read_router, _rest.write_router, _rest.opl_router,
                  _rest.metrics_router):
        routes |= set(build(reg).routes)
    for path, ops in spec["paths"].items():
        for method in ops:
            assert (method.upper(), path) in routes, (method, path)


def test_check_latest_serves_fresh_state_without_rebuild(server, read_channel):
    # CheckRequest.latest (check_service.proto:60-66): the engine must
    # answer against the freshest state — by draining the change log into
    # the write-exact overlay, NOT a full reprojection (ADVICE r3: a
    # latest=true client must not stall traffic behind a 10M-tuple
    # rebuild; overlay probes are already exact).
    from ketotpu.proto import check_service_pb2 as cs

    eng = server.registry._device_engine()
    eng.snapshot()  # absorb the fixture's seed writes (new vocab ids
    # force a reprojection; this test is about the incremental path)
    before = eng.rebuilds
    stub = CheckServiceStub(read_channel)
    # a write landed in the store but not yet in the device snapshot;
    # every id is already interned (bob, File:private#owners pre-exist),
    # so the O(delta) overlay can admit it without a reprojection
    server.registry.store().write_relation_tuples(
        RelationTuple("File", "private", "owners", SubjectID("bob"))
    )
    resp = stub.Check(
        cs.CheckRequest(
            tuple=rts.RelationTuple(
                namespace="File", object="private", relation="view",
                subject=rts.Subject(id="bob"),
            ),
            latest=True,
        ),
        timeout=60,
    )
    assert resp.allowed is True  # the pending write is visible
    assert eng.rebuilds == before  # ...without a full reprojection


class TestMuxRobustness:
    """Misbehaving clients must not hold mux threads (server/daemon.py):
    a silent client is dropped after the sniff timeout, and a client
    that never closes its half of a finished exchange must not leak the
    client->backend pump thread."""

    @staticmethod
    def _named(name):
        return [t for t in threading.enumerate() if t.name == name]

    @staticmethod
    def _settle(count, baseline, deadline_s=10.0):
        settle_by = time.monotonic() + deadline_s
        while time.monotonic() < settle_by:
            if count() <= baseline:
                return True
            time.sleep(0.05)
        return count() <= baseline

    def test_silent_client_released_after_sniff_timeout(self, server):
        mux = server._muxes[0]
        old = mux.sniff_timeout
        mux.sniff_timeout = 0.3
        conns = []
        try:
            def splices():
                return len(self._named("keto-mux-splice"))

            baseline = splices()
            # connect and say nothing: each connection parks a splice
            # thread in the protocol sniff
            conns = [socket.create_connection(mux.addr) for _ in range(3)]
            time.sleep(0.1)
            assert splices() > baseline, "sniff must be holding threads"
            assert self._settle(splices, baseline), (
                "silent clients held splice threads past the sniff timeout"
            )
            # and the server actually hung up on them
            conns[0].settimeout(5.0)
            assert conns[0].recv(16) == b""
        finally:
            for c in conns:
                c.close()
            mux.sniff_timeout = old

    def test_half_closed_client_does_not_leak_pump_threads(self, server):
        mux = server._muxes[0]
        old = mux.sniff_timeout
        mux.sniff_timeout = 0.5
        c = None
        try:
            def pumps():
                return len(self._named("keto-mux-pump"))

            baseline = pumps()
            c = socket.create_connection(mux.addr)
            c.sendall(
                b"GET /health/alive HTTP/1.1\r\n"
                b"Host: localhost\r\nConnection: close\r\n\r\n"
            )
            c.settimeout(10.0)
            data = b""
            while True:
                chunk = c.recv(4096)
                if not chunk:
                    break
                data += chunk
            assert b"200" in data.split(b"\r\n", 1)[0]
            # the exchange is over but we never close our socket: the
            # mux must reap its client->backend pump anyway
            assert self._settle(pumps, baseline), (
                "half-closed client leaked a _pump thread"
            )
        finally:
            if c is not None:
                c.close()
            mux.sniff_timeout = old


class TestWorkerMode:
    def test_remote_engine_parity_through_engine_host(self, tmp_path):
        """server/workers.py: a worker-side RemoteCheckEngine forwards
        batches to the owner's unix socket and answers exactly like the
        owner's engine; expand round-trips the tree JSON."""
        from ketotpu.server.workers import (
            EngineHostServer,
            RemoteCheckEngine,
            RemoteExpandEngine,
        )

        owner = Registry(Provider({
            "dsn": f"sqlite://{tmp_path}/w.db",
            "namespaces": {
                "location": str(FIXTURES / "rewrites_namespaces.keto.ts")
            },
            "engine": {"kind": "tpu", "frontier": 512, "arena": 1024,
                       "mesh_devices": 0, "mesh_axis": "shard"},
        }))
        owner.store().migrate_up()
        owner.store().write_relation_tuples(
            *[RelationTuple.from_string(s) for s in [
                "Group:dev#members@bob",
                "Folder:keto#viewers@Group:dev#members",
                "File:keto/README.md#parents@Folder:keto",
            ]]
        )
        owner.init()
        sock = str(tmp_path / "engine.sock")
        host = EngineHostServer(owner, sock).start()
        try:
            remote = RemoteCheckEngine(sock)
            q = RelationTuple.from_string("File:keto/README.md#view@bob")
            deny = RelationTuple.from_string("File:keto/README.md#view@eve")
            assert remote.batch_check([q, deny]) == [True, False]
            assert remote.check_is_member(q) is True
            xp = RemoteExpandEngine(sock, remote)
            tree = xp.build_tree(
                SubjectSet("Folder", "keto", "viewers"), 4
            )
            want = owner.expand_engine().build_tree(
                SubjectSet("Folder", "keto", "viewers"), 4
            )
            assert tree.to_json() == want.to_json()
            # typed errors cross the socket with their status intact
            import pytest as _pytest
            from ketotpu.api.types import KetoAPIError

            with _pytest.raises(KetoAPIError) as ei:
                remote.check(
                    RelationTuple.from_string("Folder:f#nosuch@alice")
                )
            assert ei.value.status_code == 400
        finally:
            host.stop()

    def test_owner_coalesces_single_checks_across_connections(self, tmp_path):
        """ADVICE r4: 1-tuple check requests from workers must enqueue via
        check_is_member — the coalescer's entry point — so concurrent
        singles from every worker merge into shared device waves instead
        of one dispatch per RPC."""
        import threading

        from ketotpu.server.workers import EngineHostServer, RemoteCheckEngine

        owner = Registry(Provider({
            "dsn": f"sqlite://{tmp_path}/wc.db",
            "namespaces": {
                "location": str(FIXTURES / "rewrites_namespaces.keto.ts")
            },
            "engine": {"kind": "tpu", "frontier": 512, "arena": 1024,
                       "mesh_devices": 0, "mesh_axis": "shard",
                       "coalesce_ms": 25.0},
        }))
        n = 12
        owner.store().migrate_up()
        owner.store().write_relation_tuples(
            *[RelationTuple.from_string(s) for s in [
                "Group:dev#members@bob",
                "Folder:keto#viewers@Group:dev#members",
                "File:keto/README.md#parents@Folder:keto",
            ] + [f"Group:dev#members@u{i}" for i in range(n)]]
        )
        owner.init()
        eng = owner.check_engine()
        assert hasattr(eng, "waves"), "expected the coalescing wrapper"
        sock = str(tmp_path / "wc.sock")
        host = EngineHostServer(owner, sock).start()
        try:
            # warm the engine outside the measured window (first dispatch
            # compiles; a slow compile would serialize the waves)
            RemoteCheckEngine(sock).check(
                RelationTuple.from_string("File:keto/README.md#view@bob"))
            w0, c0 = eng.waves, eng.coalesced
            results = [None] * n
            # one RemoteCheckEngine per thread = one socket connection
            # each, like N worker serving threads; each asks its own
            # check, so neither the cache nor an identical in-flight check
            # answers it in place of a wave slot
            def one(i):
                results[i] = RemoteCheckEngine(sock).check(
                    RelationTuple.from_string(
                        f"File:keto/README.md#view@u{i}"))

            threads = [
                threading.Thread(target=one, args=(i,)) for i in range(n)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert results == [True] * n
            assert eng.coalesced - c0 == n, "singles must ride the coalescer"
            assert eng.waves - w0 < n, (
                f"expected shared waves, got {eng.waves - w0} waves for {n} checks"
            )
        finally:
            host.stop()

    def test_worker_registry_builds_remote_engines(self, tmp_path):
        from ketotpu.server.workers import (
            EngineHostServer,
            RemoteCheckEngine,
            RemoteExpandEngine,
        )

        owner = Registry(Provider({
            "dsn": f"sqlite://{tmp_path}/w2.db",
            "engine": {"kind": "oracle"},
        }))
        owner.store().migrate_up()
        owner.store().write_relation_tuples(
            RelationTuple.from_string("g:o#m@alice")
        )
        sock = str(tmp_path / "w2.sock")
        host = EngineHostServer(owner, sock).start()
        try:
            worker = Registry(Provider({
                "dsn": f"sqlite://{tmp_path}/w2.db",
                "engine": {"kind": "remote", "socket": sock},
            }))
            assert isinstance(worker.check_engine(), RemoteCheckEngine)
            assert isinstance(worker.expand_engine(), RemoteExpandEngine)
            assert worker.check_engine().check(
                RelationTuple.from_string("g:o#m@alice")
            ) is True
        finally:
            host.stop()
