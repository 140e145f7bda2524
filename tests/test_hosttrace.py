"""Host spans, thread states, pool wait, host pauses and named scopes:
what the program writes for a profiler capture and counts for a scrape
(ketotpu/profiler.py, ketotpu/hostwaits.py, the engine's ``_span``, the
coalescer's thread states, the tier scopes of the device programs).
"""

import gc
import sys
import threading
import time
import types

import pytest

from ketotpu import flightrec, hostwaits
from ketotpu.api.types import RelationTuple, SubjectSet
from ketotpu.engine import coalesce
from ketotpu.engine import expand_device as xd
from ketotpu.engine import fused as fdx
from ketotpu.engine.coalesce import CoalescingEngine
from ketotpu.engine.tpu import DeviceCheckEngine
from ketotpu.flightrec import FlightRecorder
from ketotpu.observability import Metrics, Tracer
from ketotpu.opl.parser import parse
from ketotpu.storage import InMemoryTupleStore, StaticNamespaceManager

T = RelationTuple.from_string

OPL = """
class User implements Namespace {}
class Group implements Namespace {
  related: { members: (User | SubjectSet<Group, "members">)[] }
}
class Doc implements Namespace {
  related: {
    viewers: (User | SubjectSet<Group, "members">)[]
    banned: User[]
  }
  permits = {
    view: (ctx: Context): boolean => this.related.viewers.includes(ctx.subject),
    edit: (ctx: Context): boolean =>
      this.permits.view(ctx) && !this.related.banned.includes(ctx.subject),
  }
}
"""
TUPLES = [
    "Group:g1#members@alice",
    "Group:g0#members@Group:g1#members",
    "Doc:d0#viewers@Group:g0#members",
    "Doc:d0#banned@mallory",
]
KW = dict(frontier=512, arena=1024, cap=2048, gen_arena=2048, vcap=1024)


@pytest.fixture(scope="module")
def engine():
    namespaces, errs = parse(OPL)
    assert not errs, errs
    store = InMemoryTupleStore()
    store.write_relation_tuples(*[T(s) for s in TUPLES])
    eng = DeviceCheckEngine(
        store, StaticNamespaceManager(namespaces), fused_dispatch=True,
        fused_retry_lanes=1, metrics=Metrics(), **KW,
    )
    eng.snapshot()
    return eng


# -- the engine span -----------------------------------------------------------


def test_engine_span_files_a_phase_as_phase_did(engine):
    before = engine.phase_counts.get("check_encode", 0)
    seen = []
    with engine._span("check_encode", rows=3):
        seen = hostwaits.open_spans()[threading.get_ident()]
        time.sleep(0.01)
    assert seen == ["keto/engine/check_encode"]
    assert threading.get_ident() not in hostwaits.open_spans()
    spent = engine.phase_seconds["check_encode"]
    assert engine.phase_counts["check_encode"] == before + 1
    hist = {
        dict(labels)["phase"]: v for labels, v in
        engine.metrics.histogram_values("keto_engine_phase_seconds").items()
    }
    total, count = hist["check_encode"]
    assert count == engine.phase_counts["check_encode"]
    assert total == pytest.approx(spent)
    # a phase timed where it runs files the same way
    engine._phase("check_encode", 0.5)
    assert engine.phase_seconds["check_encode"] == pytest.approx(spent + 0.5)
    assert engine.phase_counts["check_encode"] == before + 2


# -- the coalescer's thread states ---------------------------------------------


class _SlowInner:
    """A check engine that takes 5 ms a wave."""

    phase_seconds: dict = {}

    def batch_check(self, queries, rest_depth=0):
        time.sleep(0.005)
        return [True] * len(queries)


@pytest.mark.parametrize("thread, states", [
    ("collector", {"idle", "window", "prepare", "stage_blocked"}),
    ("dispatcher", {"stage_empty", "serve", "file"}),
])
def test_coalescer_thread_states_partition_wall_time(thread, states):
    metrics = Metrics()
    t0 = time.perf_counter()
    co = CoalescingEngine(_SlowInner(), window=0.002, metrics=metrics)
    try:
        def client(k):
            for i in range(12):
                assert co.check_is_member(T(f"Doc:d{k}#view@u{i}")) is True

        clients = [threading.Thread(target=client, args=(k,))
                   for k in range(6)]
        for c in clients:
            c.start()
        for c in clients:
            c.join(30.0)
            assert not c.is_alive()
        time.sleep(0.05)  # some idle time at the end
        co.flush_thread_states()  # what a scrape does mid-state
    finally:
        co.close()
    co._worker.join(10.0)
    co._dispatcher.join(10.0)
    assert not co._worker.is_alive() and not co._dispatcher.is_alive()
    wall = time.perf_counter() - t0
    mine = {s: v for (t, s), v in co.thread_seconds.items() if t == thread}
    assert set(mine) == states
    assert sum(mine.values()) == pytest.approx(wall, rel=0.02)
    for state, seconds in mine.items():
        assert metrics.get_counter(
            "keto_coalescer_thread_seconds", thread=thread, state=state
        ) == pytest.approx(seconds)


class _CollectSleeps:
    """A check engine with the submit / collect pair whose collect takes
    ``seconds``; it keeps when each ticket was submitted and collected."""

    def __init__(self, seconds):
        self.seconds = seconds
        self.halves = []

    def submit(self, batch, rest_depth=0):
        return time.perf_counter(), len(batch)

    def collect(self, ticket, errs=None):
        time.sleep(self.seconds)
        self.halves.append((ticket[0], time.perf_counter()))
        return [True] * ticket[1]


class _LateEvent(threading.Event):
    """An event whose set reaches the waiter 20 ms late, as a wake-up that
    waits for the interpreter does."""

    def set(self):
        time.sleep(0.02)
        super().set()


@pytest.mark.parametrize("how", ["check_is_member", "batch_check"])
def test_a_coalesced_wait_is_coalesce_wait_device_compute_and_wake(
        how, monkeypatch):
    reg = _Registry()
    inner = _CollectSleeps(0.03)
    co = CoalescingEngine(inner, window=0.002, batch_max=8)
    monkeypatch.setattr(coalesce, "threading",
                        types.SimpleNamespace(Event=_LateEvent))
    try:
        with flightrec.rpc_recording(reg, "check") as ctx:
            t0 = time.perf_counter()
            if how == "check_is_member":
                assert co.check_is_member(T("Doc:d#view@u")) is True
            else:
                assert co.batch_check(
                    [T("Doc:d#view@u1"), T("Doc:d#view@u2")]) == [True] * 2
            waited = time.perf_counter() - t0
            stages = dict(ctx.stages)
    finally:
        monkeypatch.undo()
        co.close()
    parts = stages["coalesce_wait"] + stages["device_compute"] + stages["wake"]
    assert parts == pytest.approx(waited, rel=0.02)
    (submitted, collected), = inner.halves
    # device_compute ends at the scatter, after the collect, and leaves the
    # late wake-up to stage wake
    scatter = t0 + stages["coalesce_wait"] + stages["device_compute"]
    assert collected <= scatter + 1e-4 < collected + 0.01
    assert stages["device_compute"] >= collected - submitted >= 0.03
    assert stages["wake"] >= 0.02


# -- pool wait -----------------------------------------------------------------


class _Registry:
    def __init__(self):
        self._m = Metrics()
        self._fr = FlightRecorder(capacity=8)
        self._t = Tracer()

    def metrics(self):
        return self._m

    def flight_recorder(self):
        return self._fr

    def tracer(self):
        return self._t


def test_pool_wait_is_noted_when_an_rpc_queues_for_a_thread():
    reg = _Registry()
    pool = hostwaits.StampedPool(max_workers=1)

    def rpc(detail):
        with flightrec.rpc_recording(reg, "check", detail=detail) as ctx:
            time.sleep(0.06)
            return dict(ctx.stages)

    try:
        first, second = pool.submit(rpc, "a"), pool.submit(rpc, "b")
        first, second = first.result(30.0), second.result(30.0)
    finally:
        pool.shutdown()
    assert first["pool_wait"] < 0.03
    assert second["pool_wait"] >= 0.055  # waited out the first
    hist = {
        dict(labels)["stage"]: v for labels, v in
        reg.metrics().histogram_values(flightrec.STAGE_METRIC).items()
    }
    total, count = hist["pool_wait"]
    assert count == 2 and total >= 0.055
    # the request's total runs from the submit, as the client's does
    slow = max(reg.flight_recorder().snapshot(), key=lambda e: e["total_ms"])
    assert slow["detail"] == "b" and slow["total_ms"] >= 110.0
    assert slow["stages_ms"]["pool_wait"] >= 55.0
    # outside a pool call nothing is stamped
    assert hostwaits.take_pool_stamp() is None


def test_receive_is_noted_once_a_pool_call_from_its_thread_start():
    reg = _Registry()
    pool = hostwaits.StampedPool(max_workers=2)

    def rpc():
        time.sleep(0.02)  # gRPC's receive and the interceptors
        with flightrec.rpc_recording(reg, "check") as ctx:
            with flightrec.rpc_recording(reg, "check"):  # pass-through
                pass
            first = dict(ctx.stages)
        with flightrec.rpc_recording(reg, "check") as ctx:  # same call
            return first, dict(ctx.stages)

    try:
        got = [f.result(30.0) for f in [pool.submit(rpc) for _ in range(3)]]
    finally:
        pool.shutdown()
    for first, again in got:
        assert first["receive"] >= 0.02
        assert "receive" not in again and "pool_wait" not in again
    _, count = reg.metrics().histogram_values(flightrec.STAGE_METRIC)[
        (("op", "check"), ("stage", "receive"))]
    assert count == 3
    # a request whose thread no pool stamped has no receive
    with flightrec.rpc_recording(reg, "check") as ctx:
        pass
    assert "receive" not in ctx.stages


class _RecvRegistry(_Registry):
    config = {"log.request_log": False}


def test_send_is_observed_once_per_unary_call_after_it_ends():
    import grpc

    from ketotpu.server.interceptors import AccessLogInterceptor

    reg = _RecvRegistry()

    def check(request, context):
        with flightrec.rpc_recording(reg, "check"):
            return request

    server = grpc.server(
        hostwaits.StampedPool(4, door="grpc"),
        interceptors=(AccessLogInterceptor(reg),),
    )
    server.add_generic_rpc_handlers((grpc.method_handlers_generic_handler(
        "keto.test.Stages",
        {"Check": grpc.unary_unary_rpc_method_handler(check)}),))
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()

    def counts():
        hist = reg.metrics().histogram_values(flightrec.STAGE_METRIC)
        return {dict(k)["stage"]: v[1] for k, v in hist.items()}

    try:
        with grpc.insecure_channel(f"127.0.0.1:{port}") as ch:
            call = ch.unary_unary("/keto.test.Stages/Check")
            for _ in range(3):
                assert call(b"x", timeout=30.0) == b"x"
        deadline = time.monotonic() + 10.0
        while counts().get("send", 0) < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.05)  # and no more come
    finally:
        server.stop(0)
    got = counts()
    assert got["send"] == 3 and got["receive"] == 3
    total, _ = reg.metrics().histogram_values(flightrec.STAGE_METRIC)[
        (("op", "check"), ("stage", "send"))]
    assert total >= 0.0
    # the request's total closes before its send
    (_, outcomes), = reg.metrics().histogram_values(
        flightrec.OUTCOME_METRIC).values()
    assert outcomes == 3


# -- host pauses ---------------------------------------------------------------


class _SlowToDie:
    def __init__(self, seconds):
        self.seconds = seconds
        self.me = self  # a cycle: only the collector frees it

    def __del__(self):
        time.sleep(self.seconds)


@pytest.mark.parametrize("seconds, counted", [(0.0, False), (0.07, True)])
def test_gc_pause_counts_only_a_long_collection(seconds, counted):
    watch = hostwaits.PauseWatch()
    watch._metrics = Metrics()
    gc.collect()
    gc.callbacks.append(watch._on_gc)
    try:
        _SlowToDie(seconds)
        gc.collect(0)  # the youngest generation: the big heap stays out
    finally:
        gc.callbacks.remove(watch._on_gc)
    assert watch.seconds == {}  # the callback itself files nothing
    watch.file_collections()  # the probe thread does, every tick
    got = watch._metrics.get_counter(hostwaits.PAUSE_METRIC, cause="gc")
    if counted:
        assert got >= seconds and watch.counts == {"gc": 1}
    else:
        assert got == 0.0 and watch.seconds == {}


def test_a_collection_under_the_metrics_lock_does_not_deadlock():
    """The collector runs its callbacks on the thread that allocated
    last, under whatever lock that thread holds: a long collection that
    starts inside ``Metrics.counter`` must not need that lock again."""
    watch = hostwaits.PauseWatch()
    watch._metrics = metrics = Metrics()
    done = threading.Event()

    def collect_under_the_lock():
        with metrics._lock, watch._lock:
            watch._on_gc("start", {})
            time.sleep(0.06)
            watch._on_gc("stop", {"generation": 2, "collected": 0})
        done.set()

    worker = threading.Thread(target=collect_under_the_lock, daemon=True)
    worker.start()
    assert done.wait(10.0), "the gc callback waits for a lock its thread holds"
    watch.file_collections()
    assert watch.counts == {"gc": 1}
    assert metrics.get_counter(hostwaits.PAUSE_METRIC, cause="gc") >= 0.06


@pytest.mark.parametrize("gc_state, late", [
    ((0.0, None), 0.4),    # no collection: all of it is the scheduler's
    ((0.39, None), 0.01),  # a collection of 0.39 s, filed
    # over, but its thread handed over the interpreter before filing it
    ((0.0, 100.03), 0.01),
])
def test_sched_probe_leaves_a_collection_to_cause_gc(gc_state, late):
    watch = hostwaits.PauseWatch()
    watch._gc = gc_state
    woke = 100.0 + hostwaits.SCHED_TICK_S + 0.4
    assert watch._late(100.0, woke, 0.0) == pytest.approx(late)


def _lag_per_tick(seconds: float) -> tuple:
    lag, ticks = hostwaits.SCHED_LAG_SECONDS, hostwaits.SCHED_TICKS
    time.sleep(seconds)
    n = hostwaits.SCHED_TICKS - ticks
    return (hostwaits.SCHED_LAG_SECONDS - lag) / max(n, 1), n


def test_sched_probe_counts_every_ticks_lateness():
    watch = hostwaits.pauses()
    if not watch._armed:
        watch.bind()  # the process's probe, as a registry starts it
    idle, idle_ticks = _lag_per_tick(0.5)
    stop = threading.Event()

    def spin():  # holds the interpreter but at each switch interval
        while not stop.is_set():
            pass

    interval = sys.getswitchinterval()
    sys.setswitchinterval(0.01)
    busy_thread = threading.Thread(target=spin, daemon=True)
    try:
        busy_thread.start()
        busy, busy_ticks = _lag_per_tick(0.5)
    finally:
        stop.set()
        sys.setswitchinterval(interval)
        busy_thread.join(10.0)
    assert not busy_thread.is_alive()
    assert idle_ticks >= 5 and busy_ticks >= 5
    assert 0.0 <= idle < busy
    assert busy - idle >= 0.002  # a wake-up waits for the spinning thread


@pytest.mark.parametrize("seconds, lines", [(0.2, 0), (0.3, 1)])
def test_a_long_pause_logs_who_paused_and_what_was_open(engine, seconds, lines):
    said = []

    class _Logger:
        def warning(self, fmt, *args):
            said.append(fmt % args)

    watch = hostwaits.PauseWatch()
    watch._logger = _Logger()
    with engine._span("check_collect_sync"):
        watch.note("store_lock", seconds)
    assert watch.seconds == {"store_lock": seconds} and len(said) == lines
    for line in said:
        assert "cause=store_lock seconds=0.300" in line
        assert "thread=" + threading.current_thread().name in line
        assert "keto/engine/check_collect_sync" in line
    # a registry that shuts down takes its own binding back, no later one's
    watch._metrics = metrics = Metrics()
    watch.unbind(Metrics())
    assert watch._metrics is metrics
    watch.unbind(metrics)
    assert watch._metrics is None and watch._logger is None


@pytest.mark.parametrize("held, counted", [(0.0, False), (0.09, True)])
def test_store_lock_pause_counts_a_held_lock(held, counted):
    store = InMemoryTupleStore()
    watch = hostwaits.pauses()
    before = watch.seconds.get("store_lock", 0.0)
    holding = threading.Event()

    def hold():
        with store._lock:
            holding.set()
            time.sleep(held)

    holder = threading.Thread(target=hold)
    holder.start()
    assert holding.wait(10.0)
    store.version_and_head()  # what every snaptoken mint calls
    holder.join(10.0)
    assert not holder.is_alive()
    waited = watch.seconds.get("store_lock", 0.0) - before
    assert (waited >= 0.05) if counted else (waited == 0.0)


# -- named scopes in the device programs ---------------------------------------


def test_wave_program_carries_every_tier_scope(engine, monkeypatch):
    caught = {}

    def catch(g, qpack, **kw):
        kw.pop("span")
        caught.update(g=g, qpack=qpack, static=kw)

    monkeypatch.setattr(fdx, "run_fused_wave", catch)
    engine._dispatch(
        [T("Doc:d0#view@alice"), T("Doc:d0#edit@alice"),
         T("Group:g0#members@alice")], 0)
    static = caught["static"]
    assert static["fast_sched"] is not None and static["gen"] is not None
    assert "leo_sets" in caught["g"]
    text = fdx._run_wave.lower(
        caught["g"], caught["qpack"], **static).as_text(debug_info=True)
    levels = [f"level{i}" for i in range(len(static["fast_sched"]))]
    for scope in ["tier/leopard", "tier/fast", "tier/general",
                  "probe/node_table", "probe/mem_table", "probe/pairs",
                  "retry", "leaves", "up", *levels]:
        assert f"{scope}/" in text or f"{scope}\"" in text, scope
    for i in range(len(static["gen"][0]) + 1):
        assert f"tier/general/level{i}/" in text, i
    assert f"tier/fast/{levels[-1]}/" in text


def test_expand_program_carries_every_level_scope(engine, monkeypatch):
    caught = {}
    real = xd._run_expand

    def catch(g, *roots, schedule):
        caught.update(args=(g, *roots), schedule=schedule)
        return None

    monkeypatch.setattr(xd, "_run_expand", catch)
    xd._dispatch_roots(
        engine._expand_arrays(), engine.snapshot().vocab,
        [SubjectSet("Doc", "d0", "viewers")], 5, 16, 65536)
    text = real.lower(
        *caught["args"], schedule=caught["schedule"]
    ).as_text(debug_info=True)
    assert len(caught["schedule"]) == 5
    for level in range(5):
        assert f"expand/level{level}/" in text, level
    assert "probe/node_table/" in text
