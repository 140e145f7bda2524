"""Chaos suite: fault injection, deadline propagation, and load shedding.

Zanzibar's availability contract is "answer inside the deadline or say
you can't" — never hang, never wedge a serving thread forever.  This
suite drives the fault plan in ketotpu/faults.py through every layer
that makes that promise:

* unit: the deadline budget carrier, the fault plan, admission control;
* engine: coalescer slot waits bounded by the budget, backlog shedding,
  device-dispatch errors falling back to the CPU oracle with correct
  verdicts and a degraded health surface;
* worker RPC: connection desync discard, capped-backoff reconnect
  riding out an owner restart, budget forwarding over the unix socket;
* e2e: a wedged engine answers 504/DEADLINE_EXCEEDED fast instead of
  hanging, admission sheds with 429/RESOURCE_EXHAUSTED + Retry-After,
  health Watch streams status flips, and a mixed check/expand storm
  under an active fault plan completes with zero hung requests and
  oracle-correct verdicts (the slow variant runs the full 500-request
  acceptance storm against ``serve --workers 2`` subprocesses).
"""

import json
import os
import pathlib
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

import grpc
import pytest

from ketotpu import deadline, faults
from ketotpu.api.types import (
    BadRequestError,
    DeadlineExceededError,
    KetoAPIError,
    RelationTuple,
    SubjectSet,
    TooManyRequestsError,
)
from ketotpu.driver import Provider, Registry
from ketotpu.proto import check_service_pb2 as cs
from ketotpu.proto import health_pb2
from ketotpu.proto.services import CheckServiceStub, _stub_class
from ketotpu.server import serve_all

FIXTURES = pathlib.Path(__file__).parent / "fixtures"

SEED_TUPLES = [
    "Group:admin#members@alice",
    "Group:dev#members@bob",
    "Folder:keto#viewers@Group:dev#members",
    "File:keto/README.md#parents@Folder:keto",
    "File:private#owners@alice",
]

# (tuple string, expected verdict) — must stay correct under any fault
# plan: non-shed answers are either right or an explicit error
CASES = [
    ("File:keto/README.md#view@bob", True),
    ("File:keto/README.md#view@alice", False),
    ("Folder:keto#view@bob", True),
    ("File:private#view@alice", True),
    ("File:private#view@bob", False),
    ("File:nonexistent#view@bob", False),
]


@pytest.fixture(autouse=True)
def _no_fault_leak(monkeypatch):
    """Every test starts and ends on an inert fault plan.

    Ambient KETO_FAULT_* variables (the CI chaos job sets some) are
    scrubbed for the in-process tests — each test configures exactly the
    plan it asserts against; the subprocess storm passes its own env.
    """
    for k in list(os.environ):
        if k.startswith("KETO_FAULT_"):
            monkeypatch.delenv(k)
    faults.reset()
    yield
    faults.reset()


def _http(method, url, body=None, headers=None, timeout=30.0):
    req = urllib.request.Request(
        url, data=body, method=method, headers=headers or {}
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read().decode(), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(), dict(e.headers)


def _check_url(addr, case):
    q = urllib.parse.urlencode(
        RelationTuple.from_string(case).to_url_query()
    )
    return f"{addr}/relation-tuples/check/openapi?{q}"


# -- deadline module ---------------------------------------------------------


class TestDeadline:
    def test_no_scope_is_passthrough(self):
        assert deadline.current() is None
        assert deadline.remaining() is None
        assert deadline.deadline_ms() is None
        deadline.check()  # no budget, never raises

    def test_scope_binds_and_restores(self):
        with deadline.scope(5.0):
            left = deadline.remaining()
            assert left is not None and 4.0 < left <= 5.0
            assert 4000 < deadline.deadline_ms() <= 5000
        assert deadline.remaining() is None

    def test_nested_scope_keeps_tighter_deadline(self):
        with deadline.scope(5.0):
            outer = deadline.current()
            with deadline.scope(60.0):  # looser: must NOT extend
                assert deadline.current() == outer
            with deadline.scope(0.5):  # tighter: shrinks
                assert deadline.current() < outer
            assert deadline.current() == outer

    def test_none_and_absurd_scopes_are_passthrough(self):
        with deadline.scope(None):
            assert deadline.remaining() is None
        # gRPC reports a huge time_remaining() for deadline-less calls;
        # feeding it into Event.wait() would overflow _PyTime_t
        with deadline.scope(1e9):
            assert deadline.remaining() is None

    def test_check_raises_after_expiry(self):
        with deadline.scope(0.005):
            time.sleep(0.02)
            assert deadline.remaining() <= 0
            assert deadline.deadline_ms() == 0  # clamped, not negative
            with pytest.raises(DeadlineExceededError):
                deadline.check("unit test")

    def test_parse_timeout_formats(self):
        assert deadline.parse_timeout(None) is None
        assert deadline.parse_timeout("") is None
        assert deadline.parse_timeout("50ms") == pytest.approx(0.05)
        assert deadline.parse_timeout("1.5s") == pytest.approx(1.5)
        assert deadline.parse_timeout("2") == pytest.approx(2.0)
        assert deadline.parse_timeout(0.25) == pytest.approx(0.25)

    def test_parse_timeout_rejects_garbage(self):
        for bad in ("soon", "ms", "-1s", "0"):
            with pytest.raises(BadRequestError):
                deadline.parse_timeout(bad)


# -- fault plan --------------------------------------------------------------


class TestFaultPlan:
    def test_inactive_plan_is_a_noop(self):
        assert not faults.plan().active
        faults.inject("device_dispatch")  # must not raise or sleep
        assert faults.should("socket_drop") is False

    def test_device_error_injection_counts(self):
        p = faults.configure(device_error_rate=1.0)
        with pytest.raises(faults.FaultInjected):
            faults.inject("device_dispatch")
        assert p.injected["device_error"] == 1

    def test_latency_rate_defaults_to_always(self):
        p = faults.FaultPlan(latency_ms=5.0)
        assert p.latency_rate == 1.0
        assert faults.FaultPlan(latency_ms=5.0, latency_rate=0.25).latency_rate == 0.25

    def test_seeded_rolls_are_deterministic(self):
        a = faults.FaultPlan(device_error_rate=0.5, seed=7)
        b = faults.FaultPlan(device_error_rate=0.5, seed=7)
        assert [a._roll(0.5) for _ in range(32)] == [
            b._roll(0.5) for _ in range(32)
        ]

    def test_from_env_reads_knobs(self):
        p = faults.FaultPlan.from_env({
            "KETO_FAULT_DEVICE_ERROR_RATE": "0.2",
            "KETO_FAULT_LATENCY_MS": "50",
            "KETO_FAULT_SEED": "3",
        })
        assert p.device_error_rate == 0.2
        assert p.latency_ms == 50.0 and p.latency_rate == 1.0
        assert p.active

    def test_configure_from_config_block(self):
        cfg = Provider({"faults": {"device_stall_ms": 7.0}})
        faults.configure_from_config(cfg)
        assert faults.plan().device_stall_ms == 7.0

    def test_env_wins_over_config(self, monkeypatch):
        monkeypatch.setenv("KETO_FAULT_SOCKET_DROP_RATE", "0.5")
        faults.reset()
        cfg = Provider({"faults": {"device_stall_ms": 7.0}})
        faults.configure_from_config(cfg)
        assert faults.plan().socket_drop_rate == 0.5
        assert faults.plan().device_stall_ms == 0.0


# -- admission control -------------------------------------------------------


class TestAdmission:
    def test_bounded_acquire_release(self):
        from ketotpu.server.admission import AdmissionController

        ctl = AdmissionController(2)
        assert ctl.enabled
        assert ctl.try_acquire() and ctl.try_acquire()
        assert not ctl.try_acquire()  # at the limit: shed
        assert ctl.shed == 1
        ctl.release()
        assert ctl.try_acquire()

    def test_zero_limit_disables(self):
        from ketotpu.server.admission import AdmissionController

        ctl = AdmissionController(0)
        assert not ctl.enabled
        assert all(ctl.try_acquire() for _ in range(1000))
        assert ctl.shed == 0


# -- coalescer deadlines and shedding ---------------------------------------


class _BlockingEngine:
    """Stub inner engine: batch_check blocks on an event (a wedged device)."""

    def __init__(self):
        self.release = threading.Event()
        self.entered = threading.Event()

    def batch_check(self, queries, rest_depth=0):
        self.entered.set()
        self.release.wait(30.0)
        return [True] * len(queries)

    def check_is_member(self, r, rest_depth=0):
        return self.batch_check([r], rest_depth)[0]


class TestCoalescerDeadline:
    def test_default_timeout_bounds_slot_wait(self):
        from ketotpu.engine.coalesce import CoalescingEngine

        inner = _BlockingEngine()
        eng = CoalescingEngine(inner, window=0.001, default_timeout=0.05)
        try:
            t0 = time.monotonic()
            with pytest.raises(DeadlineExceededError):
                eng.check_is_member(RelationTuple.from_string("n:o#r@s"))
            assert time.monotonic() - t0 < 2.0
            assert eng.deadline_exceeded == 1
        finally:
            inner.release.set()
            eng.close()

    def test_request_deadline_tighter_than_default(self):
        from ketotpu.engine.coalesce import CoalescingEngine

        inner = _BlockingEngine()
        eng = CoalescingEngine(inner, window=0.001, default_timeout=30.0)
        try:
            t0 = time.monotonic()
            with deadline.scope(0.05):
                with pytest.raises(DeadlineExceededError):
                    eng.check_is_member(RelationTuple.from_string("n:o#r@s"))
            assert time.monotonic() - t0 < 2.0
        finally:
            inner.release.set()
            eng.close()

    def test_expired_budget_rejected_before_enqueue(self):
        from ketotpu.engine.coalesce import CoalescingEngine

        inner = _BlockingEngine()
        eng = CoalescingEngine(inner, window=0.001)
        try:
            with deadline.scope(0.001):
                time.sleep(0.01)
                with pytest.raises(DeadlineExceededError):
                    eng.check_is_member(RelationTuple.from_string("n:o#r@s"))
            assert not inner.entered.is_set()  # never reached the device
        finally:
            inner.release.set()
            eng.close()

    def test_backlog_full_sheds(self):
        from ketotpu.engine.coalesce import CoalescingEngine

        inner = _BlockingEngine()
        # pipeline=False: with double-buffering on, the collector cuts the
        # filler slots into a staged wave (emptying _pending) before the
        # shed probe runs, so the probe queues and times out instead of
        # shedding.  The single-threaded path keeps the backlog observable
        # while the worker is wedged inside the inner engine.
        eng = CoalescingEngine(inner, window=0.001, max_pending=2,
                               default_timeout=10.0, pipeline=False)
        threads = []
        try:
            # occupy the wave worker inside the blocked inner engine
            t = threading.Thread(
                target=lambda: eng.check_is_member(
                    RelationTuple.from_string("n:o#r@w")
                ),
                daemon=True,
            )
            t.start()
            threads.append(t)
            assert inner.entered.wait(5.0)
            # with the worker stuck, fill the backlog to max_pending...
            for i in range(2):
                ti = threading.Thread(
                    target=lambda i=i: eng.check_is_member(
                        RelationTuple.from_string(f"n:o{i}#r@s")
                    ),
                    daemon=True,
                )
                ti.start()
                threads.append(ti)
            for _ in range(100):
                with eng._lock:
                    if len(eng._pending) >= 2:
                        break
                time.sleep(0.01)
            # ...and the next caller is shed instead of queued
            with pytest.raises(TooManyRequestsError):
                eng.check_is_member(RelationTuple.from_string("n:o#r@shed"))
            assert eng.shed == 1
        finally:
            inner.release.set()
            eng.close()
            for t in threads:
                t.join(timeout=5.0)


# -- device faults fall back to the oracle ----------------------------------


class TestDeviceFaultFallback:
    def test_injected_device_errors_keep_verdicts_correct(self):
        reg = Registry(Provider({
            "namespaces": {
                "location": str(FIXTURES / "rewrites_namespaces.keto.ts")
            },
            "engine": {"kind": "tpu", "frontier": 512, "arena": 1024,
                       "max_batch": 128, "mesh_devices": 0,
                       "mesh_axis": "shard"},
        }))
        reg.store().write_relation_tuples(
            *[RelationTuple.from_string(s) for s in SEED_TUPLES]
        )
        reg.init()
        eng = reg.check_engine()
        dev = getattr(eng, "inner", eng)
        assert not dev.is_degraded()
        faults.configure(device_error_rate=1.0, seed=11)
        queries = [RelationTuple.from_string(c) for c, _ in CASES]
        got = eng.batch_check(queries)
        assert got == [want for _, want in CASES]
        # the engine took failures, served on the oracle, and says so
        assert dev.device_failures > 0
        assert dev.fallbacks >= len(CASES)
        assert dev.is_degraded()
        health = reg.health()
        assert str(health.get("engine", "")).startswith("degraded")
        # recovery: with the fault lifted the device serves again and the
        # degraded flag decays once the window passes
        faults.reset()
        dev.degraded_window = 0.05
        time.sleep(0.1)
        assert eng.batch_check(queries) == [want for _, want in CASES]
        assert not dev.is_degraded()
        assert "engine" not in reg.health()


    @pytest.mark.parametrize("surface", ["check", "block", "expand"])
    def test_device_fault_is_loud(self, surface, caplog):
        """A dead device dispatch is still answered, correctly, from the
        host — so it must not be silent: the failure count reaches the
        scrape and the fault's traceback reaches the log, at error level
        the first time."""
        import logging

        from ketotpu.engine.columns import ColumnBlock
        from ketotpu.engine.oracle import ExpandEngine

        reg = Registry(Provider({
            "namespaces": {
                "location": str(FIXTURES / "rewrites_namespaces.keto.ts")
            },
            "engine": {"kind": "tpu", "frontier": 512, "arena": 1024,
                       "max_batch": 128, "coalesce_ms": 0},
        }))
        reg.store().write_relation_tuples(
            *[RelationTuple.from_string(s) for s in SEED_TUPLES]
        )
        reg.init()
        dev = reg.check_engine()
        queries = [RelationTuple.from_string(c) for c, _ in CASES]
        want = [w for _, w in CASES]
        root = SubjectSet("Folder", "keto", "viewers")
        # the registry's logger does not propagate to the root logger
        # caplog listens on
        logging.getLogger("ketotpu").addHandler(caplog.handler)
        faults.configure(device_error_rate=1.0, seed=11)
        try:
            if surface == "check":
                assert dev.batch_check(queries) == want
            elif surface == "block":
                allowed, errs = dev.batch_check_block(
                    ColumnBlock.from_tuples(queries)
                )
                assert allowed.tolist() == want and not errs
            else:
                tree = dev.batch_expand([root], 3)[0]
                assert tree is not None and tree == ExpandEngine(
                    reg.store(), max_depth=5
                ).build_tree(root, 3)
        finally:
            logging.getLogger("ketotpu").removeHandler(caplog.handler)
        reg.sample_engine_metrics()
        assert reg.metrics().get_gauge("keto_engine_device_failures") == 1
        assert "keto_engine_device_failures 1" in reg.metrics().exposition()
        loud = [
            r for r in caplog.records
            if r.levelno == logging.ERROR and r.exc_info
        ]
        assert len(loud) == 1
        assert loud[0].exc_info[0] is faults.FaultInjected
        assert "host path answers" in loud[0].getMessage()


# -- worker RPC: desync, reconnect backoff, budget forwarding ----------------


def _oracle_host(tmp_path, name):
    owner = Registry(Provider({
        "dsn": f"sqlite://{tmp_path}/{name}.db",
        "namespaces": {
            "location": str(FIXTURES / "rewrites_namespaces.keto.ts")
        },
        "engine": {"kind": "oracle"},
    }))
    owner.store().migrate_up()
    owner.store().write_relation_tuples(
        *[RelationTuple.from_string(s) for s in SEED_TUPLES]
    )
    return owner


class TestRemoteEngineChaos:
    def test_timeout_discards_connection_and_raises_deadline(self, tmp_path):
        from ketotpu.server.workers import _Conn

        # a server that accepts but never answers: the classic desync —
        # after a timed-out exchange the connection MUST be discarded,
        # or the next call would read this request's late response
        path = str(tmp_path / "mute.sock")
        srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        srv.bind(path)
        srv.listen(1)
        try:
            conn = _Conn(path)
            with pytest.raises(TimeoutError):
                conn.call({"op": "ping"}, timeout=0.05)
            assert conn.broken
            with pytest.raises(ConnectionError):
                conn.call({"op": "ping"}, timeout=0.05)
        finally:
            srv.close()

    def test_garbage_response_discards_connection(self, tmp_path):
        from ketotpu.server.workers import _Conn

        path = str(tmp_path / "garbage.sock")
        srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        srv.bind(path)
        srv.listen(1)

        def answer_garbage():
            peer, _ = srv.accept()
            peer.recv(4096)
            peer.sendall(b"not json at all\n")
            peer.close()

        t = threading.Thread(target=answer_garbage, daemon=True)
        t.start()
        try:
            conn = _Conn(path)
            with pytest.raises(ValueError):
                conn.call({"op": "ping"}, timeout=2.0)
            assert conn.broken  # stream desynced: never reuse
        finally:
            srv.close()
            t.join(timeout=5.0)

    def test_typed_error_keeps_connection(self, tmp_path):
        from ketotpu.server.workers import EngineHostServer, RemoteCheckEngine

        owner = _oracle_host(tmp_path, "typed")
        sock = str(tmp_path / "typed.sock")
        host = EngineHostServer(owner, sock).start()
        try:
            remote = RemoteCheckEngine(sock)
            with pytest.raises(KetoAPIError) as ei:
                remote.check(RelationTuple.from_string("Folder:f#nosuch@a"))
            assert ei.value.status_code == 400
            # the exchange completed; the pooled connection still works
            assert remote._conn().broken is False
            assert remote.check(
                RelationTuple.from_string("Folder:keto#view@bob")
            ) is True
            assert remote.reconnects == 0
        finally:
            host.stop()

    def test_injected_socket_drops_retry_through(self, tmp_path):
        from ketotpu.server.workers import EngineHostServer, RemoteCheckEngine

        owner = _oracle_host(tmp_path, "drops")
        sock = str(tmp_path / "drops.sock")
        host = EngineHostServer(owner, sock).start()
        try:
            faults.configure(socket_drop_rate=0.5, seed=5)
            remote = RemoteCheckEngine(sock)
            q = RelationTuple.from_string("Folder:keto#view@bob")
            # P(5 consecutive drops) = 3% per call; 12 calls make a
            # failure astronomically unlikely while guaranteeing several
            # drop->backoff->reconnect cycles at rate 0.5
            assert all(remote.check(q) for _ in range(12))
            assert faults.plan().injected.get("socket_drop", 0) > 0
            assert remote.reconnects > 0
        finally:
            host.stop()

    def test_permanent_drop_exhausts_retries(self, tmp_path):
        from ketotpu.server.workers import EngineHostServer, RemoteCheckEngine

        owner = _oracle_host(tmp_path, "dead")
        sock = str(tmp_path / "dead.sock")
        host = EngineHostServer(owner, sock).start()
        host.stop()  # owner is gone and stays gone
        faults.reset()
        remote = RemoteCheckEngine(sock)
        t0 = time.monotonic()
        with pytest.raises(ConnectionError):
            remote.check(RelationTuple.from_string("Folder:keto#view@bob"))
        # capped backoff: fails in bounded time, not a hang
        assert time.monotonic() - t0 < 5.0

    def test_backoff_rides_out_owner_restart(self, tmp_path):
        from ketotpu.server.workers import EngineHostServer, RemoteCheckEngine

        owner = _oracle_host(tmp_path, "restart")
        sock = str(tmp_path / "restart.sock")
        host = EngineHostServer(owner, sock).start()
        host.stop()
        restarted = {}

        def bring_back():
            time.sleep(0.05)
            restarted["host"] = EngineHostServer(owner, sock).start()

        t = threading.Thread(target=bring_back, daemon=True)
        t.start()
        try:
            remote = RemoteCheckEngine(sock)
            remote.retry_attempts = 10  # generous for slow CI
            assert remote.check(
                RelationTuple.from_string("Folder:keto#view@bob")
            ) is True
            assert remote.reconnects > 0
        finally:
            t.join(timeout=5.0)
            if "host" in restarted:
                restarted["host"].stop()

    def test_deadline_forwarded_over_the_socket(self, tmp_path):
        from ketotpu.server.workers import EngineHostServer, RemoteCheckEngine

        owner = _oracle_host(tmp_path, "fwd")
        sock = str(tmp_path / "fwd.sock")
        host = EngineHostServer(owner, sock).start()
        try:
            # spike the owner handler past the caller's budget: the worker
            # must answer DEADLINE_EXCEEDED, not wait out the spike
            faults.configure(latency_ms=500.0)
            remote = RemoteCheckEngine(sock)
            t0 = time.monotonic()
            with deadline.scope(0.05):
                with pytest.raises(DeadlineExceededError):
                    remote.check(
                        RelationTuple.from_string("Folder:keto#view@bob")
                    )
            assert time.monotonic() - t0 < 2.0
        finally:
            host.stop()

    def test_expired_budget_fails_before_the_wire(self, tmp_path):
        from ketotpu.server.workers import RemoteCheckEngine

        remote = RemoteCheckEngine(str(tmp_path / "never.sock"))
        with deadline.scope(0.001):
            time.sleep(0.01)
            with pytest.raises(DeadlineExceededError):
                remote.check(RelationTuple.from_string("n:o#r@s"))


class TestWorkerSupervisor:
    def test_respawns_dead_worker_with_degraded_state(self):
        from ketotpu.server.workers import WorkerSupervisor

        def spawn(i):
            # worker 0 dies instantly once; everyone else idles
            if i == 0 and not spawned[0]:
                spawned[0] = True
                return subprocess.Popen([sys.executable, "-c", "pass"])
            return subprocess.Popen([sys.executable, "-c",
                                     "import time; time.sleep(30)"])

        spawned = [False]
        sup = WorkerSupervisor(spawn, 2, backoff_base=0.05, backoff_cap=0.1)
        sup.start()
        try:
            deadline_at = time.monotonic() + 10.0
            degraded_seen = False
            while time.monotonic() < deadline_at:
                assert sup.poll() is None
                state = sup.state()
                if state.startswith("degraded"):
                    degraded_seen = True
                if sup.respawns and state == "ok":
                    break
                time.sleep(0.02)
            assert degraded_seen, "death must surface as degraded"
            assert sup.respawns == 1
            assert sup.state() == "ok"
        finally:
            sup.terminate()

    def test_rapid_deaths_give_up(self):
        from ketotpu.server.workers import WorkerSupervisor

        sup = WorkerSupervisor(
            lambda i: subprocess.Popen([sys.executable, "-c", "exit(3)"]),
            1, max_rapid_deaths=3, backoff_base=0.01, backoff_cap=0.02,
        )
        sup.start()
        try:
            rc = None
            deadline_at = time.monotonic() + 15.0
            while rc is None and time.monotonic() < deadline_at:
                rc = sup.poll()
                time.sleep(0.02)
            assert rc == 1, "flapping worker must make the supervisor give up"
        finally:
            sup.terminate()


# -- e2e: daemon under faults ------------------------------------------------


@pytest.fixture(scope="module")
def chaos_server():
    cfg = Provider({
        "serve": {
            n: {"host": "127.0.0.1", "port": 0}
            for n in ("read", "write", "metrics", "opl")
        },
        "namespaces": {
            "location": str(FIXTURES / "rewrites_namespaces.keto.ts")
        },
        "engine": {"kind": "tpu", "frontier": 512, "arena": 2048,
                   "max_batch": 128, "mesh_devices": 0,
                   "mesh_axis": "shard"},
        "limit": {"request_timeout_ms": 10000},
    })
    reg = Registry(cfg).init()
    srv = serve_all(reg)
    reg.store().write_relation_tuples(
        *[RelationTuple.from_string(s) for s in SEED_TUPLES]
    )
    yield srv
    srv.stop()


@pytest.fixture(scope="module")
def read_addr(chaos_server):
    return "http://%s:%d" % tuple(chaos_server.addresses["read"])


@pytest.fixture(scope="module")
def metrics_addr(chaos_server):
    return "http://%s:%d" % tuple(chaos_server.addresses["metrics"])


class TestAdmissionE2E:
    def test_rest_shed_answers_429_with_retry_after(
        self, chaos_server, read_addr, metrics_addr
    ):
        ctl = chaos_server.registry.admission()
        # saturate far past any value the AIMD controller could grow the
        # limit to mid-test: next arrival is shed
        ctl.inflight = 10**9
        try:
            status, body, headers = _http(
                "GET", _check_url(read_addr, CASES[0][0])
            )
            assert status == 429, body
            # load-derived hint: a positive integer, jittered per response
            assert int(headers.get("Retry-After")) >= 1
            assert json.loads(body)["error"]["code"] == 429
            # health stays exempt so probes see through the shed
            astatus, _, _ = _http("GET", f"{read_addr}/health/alive")
            assert astatus == 200
        finally:
            ctl.inflight = 0
        # and a normal request flows again
        status, body, _ = _http("GET", _check_url(read_addr, CASES[0][0]))
        assert status == 200 and json.loads(body)["allowed"] is True
        # shed accounting reaches the scrape surface
        _, text, _ = _http("GET", f"{metrics_addr}/metrics/prometheus")
        assert "keto_requests_shed_total" in text
        assert 'transport="rest"' in text

    def test_grpc_shed_answers_resource_exhausted(
        self, chaos_server, read_addr
    ):
        from ketotpu.api.proto_codec import tuple_to_proto

        ctl = chaos_server.registry.admission()
        addr = "%s:%d" % tuple(chaos_server.addresses["read"])
        with grpc.insecure_channel(addr) as ch:
            stub = CheckServiceStub(ch)
            req = cs.CheckRequest(
                tuple=tuple_to_proto(RelationTuple.from_string(CASES[0][0]))
            )
            assert stub.Check(req).allowed is True  # channel warm
            ctl.inflight = 10**9
            try:
                with pytest.raises(grpc.RpcError) as ei:
                    stub.Check(req)
                assert ei.value.code() == grpc.StatusCode.RESOURCE_EXHAUSTED
                # cooperative retry hint rides the trailing metadata
                trailing = dict(ei.value.trailing_metadata() or ())
                assert int(trailing.get("retry-after", "0")) >= 1
                # health service is exempt: probes still answered
                health = _stub_class("grpc.health.v1.Health")(ch)
                resp = health.Check(health_pb2.HealthCheckRequest())
                assert resp.status == health_pb2.HealthCheckResponse.SERVING
            finally:
                ctl.inflight = 0
            assert stub.Check(req).allowed is True


class TestHealthDegraded:
    def test_degraded_readiness_still_serves(self, chaos_server, metrics_addr):
        reg = chaos_server.registry
        reg.readiness_checks["workers"] = (
            lambda: "degraded: respawning worker(s) 1"
        )
        try:
            status, body, _ = _http("GET", f"{metrics_addr}/health/ready")
            assert status == 200
            payload = json.loads(body)
            assert payload["status"] == "degraded"
            assert "workers" in payload["degraded"]
            # gRPC keeps the binary protocol: degraded is still SERVING
            addr = "%s:%d" % tuple(chaos_server.addresses["read"])
            with grpc.insecure_channel(addr) as ch:
                health = _stub_class("grpc.health.v1.Health")(ch)
                resp = health.Check(health_pb2.HealthCheckRequest())
                assert resp.status == health_pb2.HealthCheckResponse.SERVING
        finally:
            del reg.readiness_checks["workers"]
        status, body, _ = _http("GET", f"{metrics_addr}/health/ready")
        assert status == 200 and json.loads(body)["status"] == "ok"

    def test_watch_streams_status_changes(self, chaos_server):
        reg = chaos_server.registry
        addr = "%s:%d" % tuple(chaos_server.addresses["read"])
        with grpc.insecure_channel(addr) as ch:
            health = _stub_class("grpc.health.v1.Health")(ch)
            stream = health.Watch(health_pb2.HealthCheckRequest(), timeout=15)
            try:
                first = next(stream)
                assert first.status == health_pb2.HealthCheckResponse.SERVING

                def down():
                    raise RuntimeError("db gone")

                reg.readiness_checks["chaos_db"] = down
                try:
                    assert (
                        next(stream).status
                        == health_pb2.HealthCheckResponse.NOT_SERVING
                    )
                finally:
                    del reg.readiness_checks["chaos_db"]
                assert (
                    next(stream).status
                    == health_pb2.HealthCheckResponse.SERVING
                )
            finally:
                stream.cancel()


class TestDeadlineE2E:
    def test_malformed_timeout_header_is_a_client_error(self, read_addr):
        status, body, _ = _http(
            "GET", _check_url(read_addr, CASES[0][0]),
            headers={"X-Request-Timeout": "whenever"},
        )
        assert status == 400, body

    def test_generous_timeout_header_passes_through(self, read_addr):
        status, body, _ = _http(
            "GET", _check_url(read_addr, CASES[0][0]),
            headers={"X-Request-Timeout": "10s"},
        )
        assert status == 200 and json.loads(body)["allowed"] is True


def test_wedged_engine_answers_deadline_exceeded_fast():
    """Acceptance: a 50ms-deadline check against an engine wedged by an
    injected 5s dispatch stall returns 504 (REST) / DEADLINE_EXCEEDED
    (gRPC) quickly, and the stage histogram records the deadline."""
    from ketotpu.api.proto_codec import tuple_to_proto

    cfg = Provider({
        "serve": {
            n: {"host": "127.0.0.1", "port": 0}
            for n in ("read", "write", "metrics", "opl")
        },
        "namespaces": {
            "location": str(FIXTURES / "rewrites_namespaces.keto.ts")
        },
        "engine": {"kind": "tpu", "frontier": 512, "arena": 2048,
                   "max_batch": 128, "mesh_devices": 0,
                   "mesh_axis": "shard"},
    })
    reg = Registry(cfg).init()
    srv = serve_all(reg)
    try:
        reg.store().write_relation_tuples(
            *[RelationTuple.from_string(s) for s in SEED_TUPLES]
        )
        read = "http://%s:%d" % tuple(srv.addresses["read"])
        # warm the serving path (first dispatch compiles) BEFORE wedging
        status, body, _ = _http("GET", _check_url(read, CASES[0][0]))
        assert status == 200, body
        faults.configure(device_stall_ms=5000.0)

        # the warm pass cached this exact verdict — bypass the hot-spot
        # shield so the check actually reaches the wedged device
        t0 = time.monotonic()
        status, body, _ = _http(
            "GET", _check_url(read, CASES[0][0]),
            headers={"X-Request-Timeout": "50ms",
                     "X-Keto-Cache": "bypass"},
        )
        rest_elapsed = time.monotonic() - t0
        assert status == 504, body
        assert json.loads(body)["error"]["code"] == 504
        # acceptance bound is 200ms; allow headroom for CI scheduling
        assert rest_elapsed < 1.0, f"504 took {rest_elapsed:.3f}s"

        addr = "%s:%d" % tuple(srv.addresses["read"])
        with grpc.insecure_channel(addr) as ch:
            stub = CheckServiceStub(ch)
            req = cs.CheckRequest(
                tuple=tuple_to_proto(RelationTuple.from_string(CASES[0][0]))
            )
            t0 = time.monotonic()
            with pytest.raises(grpc.RpcError) as ei:
                stub.Check(req, timeout=0.05,
                           metadata=(("x-keto-cache", "bypass"),))
            grpc_elapsed = time.monotonic() - t0
            assert ei.value.code() == grpc.StatusCode.DEADLINE_EXCEEDED
            assert grpc_elapsed < 1.0, f"took {grpc_elapsed:.3f}s"

        metrics = "http://%s:%d" % tuple(srv.addresses["metrics"])
        _, text, _ = _http("GET", f"{metrics}/metrics/prometheus")
        assert "keto_rpc_stage_seconds" in text
        assert 'stage="deadline"' in text
    finally:
        faults.reset()
        srv.stop()


class TestStormInProcess:
    def test_mixed_storm_under_faults_resolves_everything(
        self, chaos_server, read_addr
    ):
        """Tier-1-sized storm: 80 mixed check/expand requests across 8
        threads under an active fault plan (device errors + latency
        spikes).  Every request must resolve within its deadline with an
        oracle-correct verdict or an explicit shed/deadline status."""
        faults.configure(device_error_rate=0.2, latency_ms=5.0,
                         latency_rate=0.3, seed=42)
        expand_url = (
            f"{read_addr}/relation-tuples/expand?"
            "namespace=Folder&object=keto&relation=viewers&max-depth=3"
        )
        results = []
        lock = threading.Lock()

        def one(i):
            case, want = CASES[i % len(CASES)]
            try:
                if i % 5 == 4:
                    status, body, _ = _http(
                        "GET", expand_url,
                        headers={"X-Request-Timeout": "5s"}, timeout=10.0,
                    )
                    ok = status in (200, 429, 504)
                else:
                    status, body, _ = _http(
                        "GET", _check_url(read_addr, case),
                        headers={"X-Request-Timeout": "5s"}, timeout=10.0,
                    )
                    ok = status in (429, 504) or (
                        status == 200
                        and json.loads(body)["allowed"] is want
                    )
                with lock:
                    results.append((i, status, ok))
            except Exception as e:  # noqa: BLE001 - a hang IS the failure
                with lock:
                    results.append((i, f"exc:{e}", False))

        n = 80
        threads = [
            threading.Thread(target=one, args=(i,), daemon=True)
            for i in range(n)
        ]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
        assert time.monotonic() - t0 < 60.0, "storm wall-clock blew up"
        assert len(results) == n, "every request must resolve (zero hangs)"
        bad = [r for r in results if not r[2]]
        assert not bad, f"wrong verdicts/statuses: {bad[:10]}"


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_acceptance_storm_against_worker_topology(tmp_path):
    """The ISSUE's acceptance storm: 500 mixed check/expand requests
    against ``serve --workers 2`` under device-error rate 0.2, socket
    drops 0.1, and 50ms latency spikes.  Zero hung RPCs: every request
    resolves within its deadline or is shed; non-shed verdicts match
    the oracle."""
    db = tmp_path / "storm.db"
    seed_reg = Registry(Provider({"dsn": f"sqlite://{db}"}))
    seed_reg.store().migrate_up()
    seed_reg.store().write_relation_tuples(
        *[RelationTuple.from_string(s) for s in SEED_TUPLES]
    )

    ports = {n: _free_port() for n in ("read", "write", "metrics", "opl")}
    config = {
        "dsn": f"sqlite://{db}",
        "serve": {
            n: {"host": "127.0.0.1", "port": p} for n, p in ports.items()
        },
        "namespaces": {
            "location": str(FIXTURES / "rewrites_namespaces.keto.ts")
        },
        "engine": {"kind": "tpu", "frontier": 512, "arena": 2048,
                   "max_batch": 128, "mesh_devices": 0,
                   "mesh_axis": "shard"},
        "log": {"request_log": False},
    }
    cfg_path = tmp_path / "storm.json"
    cfg_path.write_text(json.dumps(config))

    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "KETO_FAULT_DEVICE_ERROR_RATE": "0.2",
        "KETO_FAULT_SOCKET_DROP_RATE": "0.1",
        "KETO_FAULT_LATENCY_MS": "50",
        "KETO_FAULT_LATENCY_RATE": "0.2",
        "KETO_FAULT_SEED": "1234",
    })
    proc = subprocess.Popen(
        [sys.executable, "-m", "ketotpu.cli", "serve",
         "-c", str(cfg_path), "--workers", "2"],
        env=env, cwd=str(pathlib.Path(__file__).parent.parent),
    )
    read = f"http://127.0.0.1:{ports['read']}"
    metrics = f"http://127.0.0.1:{ports['metrics']}"
    try:
        ready_by = time.monotonic() + 180.0
        while True:
            assert proc.poll() is None, "serve --workers died during boot"
            try:
                status, _, _ = _http(
                    "GET", f"{metrics}/health/ready", timeout=2.0
                )
                if status == 200:
                    break
            except OSError:
                pass
            assert time.monotonic() < ready_by, "topology never became ready"
            time.sleep(0.5)

        expand_url = (
            f"{read}/relation-tuples/expand?"
            "namespace=Folder&object=keto&relation=viewers&max-depth=3"
        )
        results = []
        lock = threading.Lock()

        def one(i):
            case, want = CASES[i % len(CASES)]
            t0 = time.monotonic()
            try:
                if i % 5 == 4:
                    status, body, _ = _http(
                        "GET", expand_url,
                        headers={"X-Request-Timeout": "10s"}, timeout=20.0,
                    )
                    ok = status in (200, 429, 503, 504)
                else:
                    status, body, _ = _http(
                        "GET", _check_url(read, case),
                        headers={"X-Request-Timeout": "10s"}, timeout=20.0,
                    )
                    # non-shed verdicts MUST match the oracle; sheds and
                    # deadline hits are explicit, bounded answers
                    ok = status in (429, 503, 504) or (
                        status == 200
                        and json.loads(body)["allowed"] is want
                    )
                with lock:
                    results.append((i, status, time.monotonic() - t0, ok))
            except Exception as e:  # noqa: BLE001 - a hang IS the failure
                with lock:
                    results.append(
                        (i, f"exc:{e}", time.monotonic() - t0, False)
                    )

        n = 500
        threads = []
        for batch in range(0, n, 16):
            batch_threads = [
                threading.Thread(target=one, args=(i,), daemon=True)
                for i in range(batch, min(batch + 16, n))
            ]
            for t in batch_threads:
                t.start()
            threads.extend(batch_threads)
            for t in batch_threads:
                t.join(timeout=30.0)
        assert len(results) == n, (
            f"only {len(results)}/{n} requests resolved — hung RPCs"
        )
        bad = [r for r in results if not r[3]]
        assert not bad, f"wrong verdicts/statuses: {bad[:10]}"
        # bounded tails: no request ran past its deadline + overhead
        slow_tail = [r for r in results if r[2] > 15.0]
        assert not slow_tail, f"unbounded tail: {slow_tail[:10]}"
        # the fault plan actually fired (rates are high enough that a
        # fault-free run is impossible at n=500)
        statuses = {r[1] for r in results}
        assert statuses & {200, 429, 503, 504}
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()


# -- ISSUE 10: shard-device fault storm against the mesh engine --------------


class TestMeshShardFaultStorm:
    """A shard-device fault mid-storm must not stop the wave: checks keep
    answering exactly (surviving replicas / host oracle), the per-shard
    fallback gauge moves ONLY on the faulted shard, and dropping the
    plan restores zero-fallback serving with the victim gauge at zero."""

    def test_storm_keeps_answering_and_recovers(self):
        import numpy as np

        from ketotpu.parallel import MeshCheckEngine
        from ketotpu.parallel.graphshard import shard_of_np
        from ketotpu.utils.synth import build_synth, synth_queries

        graph = build_synth(n_users=128, n_groups=8, n_folders=64,
                            n_docs=256, seed=7)
        eng = MeshCheckEngine(
            graph.store, graph.manager, mesh_devices=8,
            frontier=1024, arena=4096, max_batch=512,
        )
        warm = synth_queries(graph, 128, seed=51)
        assert eng.batch_check(warm) == [
            eng.oracle.check_is_member(q) for q in warm
        ]

        rounds = [synth_queries(graph, 64, seed=100 + r) for r in range(6)]
        wants = [
            [eng.oracle.check_is_member(q) for q in qs] for qs in rounds
        ]
        v = eng._vocab
        flat = [q for qs in rounds for q in qs]
        owners = shard_of_np(
            np.array([v.namespaces.lookup(q.namespace) for q in flat]),
            np.array([v.objects.lookup(q.object) for q in flat]), 8,
        )
        victim = int(np.bincount(owners, minlength=8).argmax())
        fb0 = np.array([r["fallbacks"] for r in eng.shard_stats()])

        mismatches = []

        def fire(qs, want):
            got = eng.batch_check(qs)
            if got != want:
                mismatches.append((got, want))

        faults.configure(shard_error_rate=1.0, shard_id=victim)
        try:
            threads = [
                threading.Thread(target=fire, args=(qs, w), daemon=True)
                for qs, w in zip(rounds, wants)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=180.0)
            assert not any(t.is_alive() for t in threads), "storm wedged"
            assert not mismatches, mismatches[:2]
            assert eng.mesh_stats()["shards_down"] == 1
            delta = np.array(
                [r["fallbacks"] for r in eng.shard_stats()]
            ) - fb0
            assert delta[victim] > 0, "faulted shard took no fallbacks"
            assert all(
                int(d) == 0 for i, d in enumerate(delta) if i != victim
            ), f"healthy shards took fallbacks: {delta.tolist()}"
        finally:
            faults.reset()

        # recovery: the next dispatch polls the lifted plan, re-ships the
        # shard, zeroes its gauge — and serving is fallback-free again
        fb1 = np.array([r["fallbacks"] for r in eng.shard_stats()])
        post = synth_queries(graph, 64, seed=200)
        assert eng.batch_check(post) == [
            eng.oracle.check_is_member(q) for q in post
        ]
        assert not eng._shard_down.any()
        stats = eng.shard_stats()
        assert stats[victim]["fallbacks"] == 0
        assert eng.mesh_stats()["shard_recoveries"] >= 1
        after = np.array([r["fallbacks"] for r in stats])
        assert all(
            int(after[i] - fb1[i]) == 0 for i in range(8) if i != victim
        ), "recovered serving must add no fallbacks on healthy shards"


# -- shadow-verification plane under chaos -----------------------------------


class TestShadowZeroDivergenceUnderChaos:
    """The always-on shadow plane must stay at exactly zero divergence
    while the system is being actively hurt: shard/device faults push
    checks onto the oracle fallback (same verdicts, different tier) and
    write storms race the sampler (the same-snapshot guard skips raced
    samples instead of misfiling them as divergences)."""

    def _server(self):
        cfg = Provider({
            "serve": {
                n: {"host": "127.0.0.1", "port": 0}
                for n in ("read", "write", "metrics", "opl")
            },
            "namespaces": {
                "location": str(FIXTURES / "rewrites_namespaces.keto.ts")
            },
            "engine": {"kind": "tpu", "frontier": 512, "arena": 2048,
                       "max_batch": 128},
            # shadow EVERY check so the storm itself is the sample set
            "observability": {"shadow": {"sample_rate": 1}},
            "log": {"request_log": False},
        })
        reg = Registry(cfg).init()
        reg.store().write_relation_tuples(
            *[RelationTuple.from_string(s) for s in SEED_TUPLES]
        )
        return serve_all(reg)

    def _storm(self, read, n, threads=6):
        results = []
        lock = threading.Lock()

        def one(i):
            case, want = CASES[i % len(CASES)]
            try:
                status, body, _ = _http(
                    "GET", _check_url(read, case),
                    headers={"X-Request-Timeout": "10s"}, timeout=20.0,
                )
                ok = status in (429, 503, 504) or (
                    status == 200 and json.loads(body)["allowed"] is want
                )
                with lock:
                    results.append((i, status, ok))
            except Exception as e:  # noqa: BLE001 - a hang IS the failure
                with lock:
                    results.append((i, f"exc:{e}", False))

        for base in range(0, n, threads):
            batch = [
                threading.Thread(target=one, args=(i,), daemon=True)
                for i in range(base, min(base + threads, n))
            ]
            for t in batch:
                t.start()
            for t in batch:
                t.join(timeout=60.0)
        assert len(results) == n, "every request must resolve (zero hangs)"
        bad = [r for r in results if not r[2]]
        assert not bad, f"wrong verdicts/statuses: {bad[:10]}"

    def _assert_clean(self, srv):
        sh = srv.registry.shadow()
        assert sh is not None
        assert sh.drain(timeout=120.0), "shadow replay queue never drained"
        st = sh.stats()
        assert st["divergences"] == 0, sh.ledger()
        assert sh.ledger() == []
        m = srv.registry.metrics()
        assert m.get_counter("keto_shadow_divergence_total") == 0
        return st

    def test_device_fault_storm_zero_divergence(self):
        """Device/shard dispatch faults mid-storm: verdicts keep matching
        the oracle (fallback tier), so the shadow plane — sampling every
        one of them — scores agreement across the board."""
        srv = self._server()
        read = "http://%s:%d" % tuple(srv.addresses["read"])
        try:
            status, body, _ = _http("GET", _check_url(read, CASES[0][0]))
            assert status == 200, body  # warm before hurting the device
            faults.configure(device_error_rate=0.4, latency_ms=2.0,
                             latency_rate=0.2, shard_error_rate=1.0,
                             shard_id=0, seed=9)
            try:
                self._storm(read, n=48)
            finally:
                faults.reset()
            st = self._assert_clean(srv)
            # the storm's checks were actually scored (store is quiet:
            # nothing to go stale against)
            assert st["checks"] >= 40, st
        finally:
            faults.reset()
            srv.stop()

    def test_write_storm_zero_false_divergence(self):
        """A write storm racing the sampler: raced samples are skipped by
        the same-snapshot guard (counted, not scored) and the scored rest
        diverges exactly zero times — no false positives from snapshot
        skew."""
        srv = self._server()
        read = "http://%s:%d" % tuple(srv.addresses["read"])
        reg = srv.registry
        stop = threading.Event()

        def writer():
            i = 0
            while not stop.is_set():
                # unrelated tuples: log_head churns, CASES verdicts don't
                reg.store().write_relation_tuples(
                    RelationTuple.from_string(f"File:junk{i}#owners@nobody")
                )
                i += 1
                time.sleep(0.002)

        w = threading.Thread(target=writer, daemon=True)
        try:
            status, body, _ = _http("GET", _check_url(read, CASES[0][0]))
            assert status == 200, body
            w.start()
            self._storm(read, n=60)
            stop.set()
            w.join(timeout=30.0)
            st = self._assert_clean(srv)
            # the plane did real work under the storm: samples were taken,
            # and every one was either scored clean or skipped as stale
            assert st["checks"] + st["skipped"] >= 50, st
        finally:
            stop.set()
            srv.stop()


# -- ISSUE 14: whole-host kill mid-storm against the 2-host mesh -------------


class TestMeshHostKillStorm:
    """Killing one of two owner hosts mid-storm must not stop (or skew)
    a single wave: heartbeat loss marks every shard the dead peer owns
    down AT ONCE, its rows degrade to cross-host replicas or the host
    oracle, verdicts stay bit-identical throughout (zero divergence),
    the fallback attribution moves ONLY on the dead peer — local shard
    gauges stay flat — and the returning peer rejoins warm."""

    @pytest.mark.slow
    def test_host_kill_mid_storm_zero_divergence(self):
        from ketotpu.parallel import HostLink, MeshCheckEngine
        from ketotpu.utils.synth import build_synth, synth_queries

        graph = build_synth(n_users=128, n_groups=8, n_folders=64,
                            n_docs=256, seed=9)
        links = [
            HostLink(
                h, ["127.0.0.1:0", "127.0.0.1:0"], "chaos-secret",
                heartbeat_ms=100, miss_budget=2, rpc_timeout_ms=180000,
            )
            for h in range(2)
        ]
        a0, a1 = links[0].bind(), links[1].bind()
        links[0].set_peer_addr(1, a1)
        links[1].set_peer_addr(0, a0)
        engs = [
            MeshCheckEngine(
                graph.store, graph.manager, mesh_devices=4,
                frontier=1024, arena=4096, max_batch=512,
                hostlink=links[h],
            )
            for h in range(2)
        ]
        try:
            # warm both hosts locally (XLA compile) before the storm
            warm = synth_queries(graph, 96, seed=61)
            for e in (engs[1], engs[0]):
                e._peer_serve_check(warm, 0)
            for l in links:
                l.heartbeat_now()

            rounds = [
                synth_queries(graph, 64, seed=300 + r) for r in range(8)
            ]
            wants = [
                [engs[0].oracle.check_is_member(q) for q in qs]
                for qs in rounds
            ]
            # absorb first-shape compiles on both sides of the lane so
            # the storm below runs at steady state
            assert engs[0].batch_check(rounds[0]) == wants[0]
            shard_fb0 = int(engs[0]._shard_fallbacks.sum())
            mismatches = []

            def fire(qs, want):
                got = engs[0].batch_check(qs)
                if got != want:
                    mismatches.append((got, want))

            threads = [
                threading.Thread(target=fire, args=(qs, w), daemon=True)
                for qs, w in zip(rounds, wants)
            ]
            for t in threads:
                t.start()
            # kill host 1 mid-storm: its PeerLink goes silent (frames
            # unanswered, heartbeats stop) exactly like a dead process
            time.sleep(0.2)
            faults.configure(peer_down=1)
            for _ in range(links[0].miss_budget):
                links[0].heartbeat_now()
            assert links[0].peer_down(1)
            for t in threads:
                t.join(timeout=300.0)
            assert not any(t.is_alive() for t in threads), "storm wedged"
            assert not mismatches, mismatches[:2]  # zero divergence
            assert engs[0].mesh_stats()["hosts_down"] == 1
            # every degraded verdict is attributed to the dead PEER;
            # the local shard fallback gauges must not move at all
            assert int(engs[0]._peer_fallbacks[1]) > 0
            assert int(engs[0]._shard_fallbacks.sum()) == shard_fb0

            # recovery: clearing the fault and answering one beat marks
            # the host up; rows route cross-host again, still exact
            faults.reset()
            rec0 = links[0].peer_recoveries
            links[0].heartbeat_now()
            assert not links[0].peer_down(1)
            assert links[0].peer_recoveries == rec0 + 1
            routed0 = int(engs[0].peer_route_counts()[1])
            assert engs[0].batch_check(rounds[0]) == wants[0]
            assert int(engs[0].peer_route_counts()[1]) > routed0
            assert engs[0].mesh_stats()["hosts_down"] == 0
        finally:
            faults.reset()
            for e in engs:
                e.close()


# -- ISSUE 17: overload control -----------------------------------------------


class TestWorkerWireBreaker:
    """The worker-wire circuit breaker: injected owner wedges (no
    response frame = transport failure) trip the lane open, open means
    fail-FAST instead of burning the reconnect schedule, and the
    half-open probe closes it the moment the owner answers again."""

    def _remote(self, sock):
        from ketotpu.server.workers import RemoteCheckEngine

        return RemoteCheckEngine(sock, breaker_config={
            "window_s": 10.0, "min_volume": 4,
            "failure_ratio": 0.5, "cooldown_s": 0.3,
        })

    def test_breaker_trips_fails_fast_and_recovers(self, tmp_path):
        from ketotpu.server.workers import EngineHostServer

        owner = _oracle_host(tmp_path, "breaker")
        sock = str(tmp_path / "breaker.sock")
        host = EngineHostServer(owner, sock).start()
        q = RelationTuple.from_string("Folder:keto#view@bob")
        try:
            remote = self._remote(sock)
            assert remote.check(q) is True  # healthy wire, warm pool
            assert remote.breaker.state == "closed"

            # owner wedges: every exchange dies with no response frame
            faults.configure(worker_error_rate=1.0, seed=3)
            with pytest.raises(ConnectionError):
                remote.check(q)
            assert remote.breaker.state == "open"
            assert remote.breaker.trips == 1

            # open = fail fast: no connect, no backoff burn
            t0 = time.monotonic()
            with pytest.raises(ConnectionError) as ei:
                remote.check(q)
            assert time.monotonic() - t0 < 0.1
            assert "circuit breaker open" in str(ei.value)

            # owner heals; past the cooldown one probe closes the lane
            faults.reset()
            time.sleep(0.35)
            assert remote.check(q) is True
            assert remote.breaker.state == "closed"
            # and it stays closed for ordinary traffic
            assert all(remote.check(q) for _ in range(4))
        finally:
            faults.reset()
            host.stop()

    def test_typed_errors_never_trip_the_breaker(self, tmp_path):
        from ketotpu.server.workers import EngineHostServer

        owner = _oracle_host(tmp_path, "typedbrk")
        sock = str(tmp_path / "typedbrk.sock")
        host = EngineHostServer(owner, sock).start()
        try:
            remote = self._remote(sock)
            # typed errors are COMPLETED exchanges on a healthy wire:
            # a burst of client errors must not open the lane
            for _ in range(8):
                with pytest.raises(KetoAPIError):
                    remote.check(
                        RelationTuple.from_string("Folder:f#nosuch@a")
                    )
            assert remote.breaker.state == "closed"
            assert remote.breaker.trips == 0
        finally:
            host.stop()


@pytest.mark.slow
class TestOverloadStorm:
    """The ISSUE 17 acceptance storm: a sustained 2x-capacity mixed
    flood with misbehaving clients (retry-storm fault: the SDK ignores
    Retry-After and its retry budget).  The plane must shed batch before
    interactive, keep answering interactive checks throughout, escalate
    the brownout ladder, give exact verdicts on everything it admits
    (zero shadow divergence), and converge back to normal service once
    the flood stops."""

    def test_two_x_flood_sheds_batch_first_and_converges(self):
        from ketotpu.sdk import KetoClient
        from ketotpu.server.admission import CLASS_BATCH, CLASS_INTERACTIVE

        cfg = Provider({
            "serve": {
                n: {"host": "127.0.0.1", "port": 0}
                for n in ("read", "write", "metrics", "opl")
            },
            "namespaces": {
                "location": str(FIXTURES / "rewrites_namespaces.keto.ts")
            },
            "engine": {"kind": "tpu", "frontier": 512, "arena": 2048,
                       "max_batch": 128},
            # a deliberately small serving capacity so a laptop-sized
            # flood is genuinely 2x+: the AIMD limit lives in [4, 16]
            "limit": {"max_inflight": 8, "request_timeout_ms": 10000},
            "observability": {"shadow": {"sample_rate": 1}},
            "overload": {"floor": 4, "ceiling": 16, "increase": 4,
                         "interval_ms": 100, "hold_ms": 400},
            "log": {"request_log": False},
        })
        reg = Registry(cfg).init()
        srv = serve_all(reg)
        reg.store().write_relation_tuples(
            *[RelationTuple.from_string(s) for s in SEED_TUPLES]
        )
        read = "http://%s:%d" % tuple(srv.addresses["read"])
        try:
            # warm: absorb first-shape compiles before offering load.
            # a cold compile can outlive the 10s request budget (the
            # waiting caller gets 504 while the wave finishes compiling
            # on the worker), so retry until the cache is hot
            status, body = 0, b""
            for _ in range(6):
                status, body, _ = _http(
                    "GET", _check_url(read, CASES[0][0]), timeout=20.0
                )
                if status == 200:
                    break
            assert status == 200, body
            _post_batch = lambda: _http(
                "POST", f"{read}/relation-tuples/batch/check",
                json.dumps({"tuples": [
                    RelationTuple.from_string(c).to_json()
                    for c, _ in CASES[:4] * 2
                ]}).encode(),
                {"Content-Type": "application/json"}, timeout=20.0,
            )
            for _ in range(6):
                status, _, _ = _post_batch()
                if status == 200:
                    break
            assert status == 200

            # misbehaving clients: retries ignore the budget + hint
            faults.configure(retry_storm_rate=1.0, seed=17)
            stop_at = time.monotonic() + 3.0
            lock = threading.Lock()
            inter = {"ok": 0, "shed": 0, "wrong": 0, "hung": 0}
            batch = {"ok": 0, "shed": 0, "hung": 0}

            def interactive_client(i):
                cli = KetoClient(read, max_retries=2, timeout=20.0)
                j = 0
                while time.monotonic() < stop_at:
                    case, want = CASES[(i + j) % len(CASES)]
                    j += 1
                    t = RelationTuple.from_string(case)
                    try:
                        got = cli.check_tuple(t)
                        with lock:
                            if got is want:
                                inter["ok"] += 1
                            else:
                                inter["wrong"] += 1
                    except Exception as e:  # noqa: BLE001
                        name = type(e).__name__
                        with lock:
                            if "429" in str(e) or "503" in str(e):
                                inter["shed"] += 1
                            elif name in ("SDKError",):
                                inter["shed"] += 1
                            else:
                                inter["hung"] += 1

            def batch_client():
                while time.monotonic() < stop_at:
                    try:
                        status, _, _ = _post_batch()
                        with lock:
                            if status == 200:
                                batch["ok"] += 1
                            elif status in (429, 503):
                                batch["shed"] += 1
                            else:
                                batch["hung"] += 1
                    except Exception:  # noqa: BLE001
                        with lock:
                            batch["hung"] += 1

            threads = [
                threading.Thread(
                    target=interactive_client, args=(i,), daemon=True)
                for i in range(12)
            ] + [
                threading.Thread(target=batch_client, daemon=True)
                for _ in range(8)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60.0)
            assert not any(t.is_alive() for t in threads), "storm wedged"
            faults.reset()

            ctl = reg.admission()
            ov = reg.overload()
            # the flood actually overloaded the plane...
            assert ctl.shed > 0, "storm never hit capacity"
            # ...and every admitted verdict was exact
            assert inter["wrong"] == 0
            assert inter["hung"] == 0 and batch["hung"] == 0
            # interactive goodput survived the whole storm
            assert inter["ok"] > 0, (inter, batch)
            # shed ordering: batch sheds, interactive keeps landing —
            # proportionally batch must shed at least as hard
            shed_by = ctl.shed_by_class
            assert shed_by[CLASS_BATCH] > 0, (shed_by, batch)
            inter_tries = inter["ok"] + inter["shed"]
            batch_tries = batch["ok"] + batch["shed"]
            if inter_tries and batch_tries:
                assert (batch["shed"] / batch_tries
                        >= inter["shed"] / inter_tries - 0.05), (
                    inter, batch)
            # the storm was observable: limit + stage published
            m = reg.metrics()
            assert m.get_gauge("keto_admission_limit") >= 1.0
            assert m.counter_total("keto_requests_shed_total") > 0

            # convergence: flood gone, ladder steps down (hold 400ms per
            # stage), interactive flows again without client retries
            deadline_at = time.monotonic() + 15.0
            cli = KetoClient(read, max_retries=0, timeout=10.0)
            last = None
            while time.monotonic() < deadline_at:
                try:
                    assert cli.check_tuple(
                        RelationTuple.from_string(CASES[0][0])
                    ) is CASES[0][1]
                    last = "ok"
                    break
                except Exception as e:  # noqa: BLE001
                    last = e
                    time.sleep(0.2)
            assert last == "ok", f"storm never converged: {last}"
            assert ov is not None and ov.stage <= 1

            # zero divergence: the shadow plane scored the admitted
            # checks and found nothing
            sh = reg.shadow()
            assert sh is not None
            assert sh.drain(timeout=120.0), "shadow queue never drained"
            assert sh.stats()["divergences"] == 0, sh.ledger()
            assert m.get_counter("keto_shadow_divergence_total") == 0
        finally:
            faults.reset()
            srv.stop()
