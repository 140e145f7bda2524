"""The gRPC front door's thread pool: how many calls may wait inside the
server, what bounds them, and the gauge that says how many do
(ketotpu/server/daemon.py:_grpc_backend, ketotpu/hostwaits.py:StampedPool).

A handler that blocks stands for a Check parked on its wave: each test
builds the real gRPC backend (pool, access-log and admission interceptors)
around such a handler and counts what gets in.
"""

import sys
import threading
import time

import grpc
import pytest

from ketotpu import hostwaits
from ketotpu.driver import Provider, Registry
from ketotpu.engine import coalesce, tpu
from ketotpu.proto import check_service_pb2 as cs
from ketotpu.proto.services import CHECK_SERVICE, CheckServiceStub
from ketotpu.server.daemon import Server


class _Parked:
    """CheckService whose Check waits until the test lets it go."""

    def __init__(self):
        self.release = threading.Event()
        self.lock = threading.Lock()
        self.pool_waits = []

    def Check(self, request, context):
        submitted, started = hostwaits.take_pool_stamp()
        with self.lock:
            self.pool_waits.append(started - submitted)
        assert self.release.wait(60.0)
        return cs.CheckResponse(allowed=True)

    BatchCheck = StreamCheck = Check  # the service's others, never called

    def entered(self):
        with self.lock:
            return len(self.pool_waits)


class _Door:
    """One gRPC backend over a parked handler, and clients to fill it."""

    def __init__(self, **limit):
        self.registry = Registry(Provider({
            "engine": {"kind": "oracle"}, "limit": limit,
            "log": {"request_log": False},
        }))
        self.server = Server(self.registry)
        self.handler = _Parked()
        self.address = "%s:%d" % self.server._grpc_backend(
            {CHECK_SERVICE: self.handler})
        self.pool, = self.registry._door_pools
        self.channel = grpc.insecure_channel(self.address)
        self.stub = CheckServiceStub(self.channel)

    def call(self, n):
        """Start ``n`` Checks; the futures of their answers."""
        return [self.stub.Check.future(cs.CheckRequest(), timeout=60.0)
                for _ in range(n)]

    def wait_entered(self, n):
        end = time.monotonic() + 30.0
        while self.handler.entered() < n and time.monotonic() < end:
            time.sleep(0.005)
        assert self.handler.entered() == n

    def gauge(self, name):
        self.registry.sample_engine_metrics()
        return self.registry.metrics().get_gauge(name, door="grpc")

    def close(self):
        self.handler.release.set()
        self.channel.close()
        for s in self.server._grpc_servers:
            s.stop(5.0).wait(10.0)


@pytest.fixture
def door(request):
    d = _Door(**getattr(request, "param", {}))
    yield d
    d.close()


def test_ceiling_is_a_full_wave_in_each_of_the_coalescers_places(door):
    assert door.pool.ceiling == coalesce.PLACES * tpu._bucket(1) == 1024
    assert door.pool.door == "grpc"
    assert door.gauge("keto_frontdoor_pool_max") == door.pool.ceiling


@pytest.mark.parametrize("blocked", [16, 40])
def test_a_call_starts_at_once_however_many_are_parked(door, blocked):
    """Sixteen parked handlers used to be the whole pool: the next call
    queued behind them for as long as they waited."""
    parked = door.call(blocked)
    door.wait_entered(blocked)
    late = door.call(1)
    door.wait_entered(blocked + 1)
    assert door.handler.pool_waits[-1] < 0.03
    assert max(door.handler.pool_waits) < 0.03
    door.handler.release.set()
    assert all(f.result(30.0).allowed for f in parked + late)


@pytest.mark.parametrize("door", [{"max_inflight": 32}], indirect=True)
def test_admission_not_the_pool_bounds_the_calls_inside(door):
    """With 32 admitted calls parked, the next eight are refused at once:
    they get a thread, meet the admission limit on it and leave."""
    parked = door.call(32)
    door.wait_entered(32)
    t0 = time.monotonic()
    over = door.call(8)
    for f in over:
        with pytest.raises(grpc.RpcError) as refused:
            f.result(30.0)
        assert refused.value.code() == grpc.StatusCode.RESOURCE_EXHAUSTED
    assert time.monotonic() - t0 < 5.0  # answered while the 32 still wait
    assert door.handler.entered() == 32 and not any(f.done() for f in parked)
    shed = door.registry.metrics().counter_total(
        "keto_requests_shed_total", transport="grpc")
    assert shed == 8
    door.handler.release.set()
    assert all(f.result(30.0).allowed for f in parked)


def test_threads_start_on_demand(door):
    """An idle door has started no thread, a used one no more than the
    calls it has seen, and as many as it held at once."""
    assert len(door.pool._threads) == 0
    door.handler.release.set()
    for _ in range(3):  # one after another: a thread that is idle serves
        assert door.call(1)[0].result(30.0).allowed
    assert 1 <= len(door.pool._threads) <= 3
    door.handler.release.clear()
    parked = door.call(20)
    door.wait_entered(3 + 20)
    assert 20 <= len(door.pool._threads) <= 3 + 20
    assert all(t.name.startswith("grpc-worker") for t in door.pool._threads)
    door.handler.release.set()
    assert all(f.result(30.0).allowed for f in parked)


def test_busy_gauge_counts_the_calls_inside_and_returns_to_zero(door):
    assert door.gauge("keto_frontdoor_pool_busy") == 0
    parked = door.call(24)
    door.wait_entered(24)
    assert door.gauge("keto_frontdoor_pool_busy") == 24
    door.handler.release.set()
    assert all(f.result(30.0).allowed for f in parked)
    end = time.monotonic() + 10.0
    while door.pool.busy and time.monotonic() < end:
        time.sleep(0.005)  # the answer leaves before the thread does
    assert door.gauge("keto_frontdoor_pool_busy") == 0
    text = door.registry.metrics().exposition()
    assert 'keto_frontdoor_pool_busy{door="grpc"} 0' in text
    assert 'keto_frontdoor_pool_max{door="grpc"} 1024' in text


def test_busy_count_loses_no_update_under_contention():
    """More threads than cores enter and leave the pool as fast as they
    can, the interpreter switching between them at every chance: a lost
    add or subtract would leave ``busy`` off zero, or let it pass the
    threads there are."""
    pool = hostwaits.StampedPool(32, door="grpc")
    seen = []
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        calls = [pool.submit(lambda: seen.append(pool.busy))
                 for _ in range(20000)]
        for c in calls:
            c.result(60.0)
    finally:
        sys.setswitchinterval(interval)
        pool.shutdown()
    assert len(seen) == 20000 and 1 <= min(seen) and max(seen) <= 32
    assert pool.busy == 0
