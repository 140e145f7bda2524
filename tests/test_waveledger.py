"""Wave ledger + XLA compile observatory tests (ISSUE 6).

Covers the ledger ring semantics, the flight-recorder <-> wave-ledger
cross-link (``wave=`` one way, slowest-member traceparents the other),
the ``/debug/waves`` + ``/debug/compiles`` endpoints on a live daemon,
the observability.* config block, the profiler gating, and the compile
gate: a warm engine must NOT recompile across repeated mixed-shape
check/expand waves.
"""

import json
import random
import re
import threading
import time
import urllib.error
import urllib.request

import pytest

from ketotpu import compilewatch, flightrec
from ketotpu.api.types import RelationTuple
from ketotpu.compilewatch import _COMPILE_EVENT, CompileWatch
from ketotpu.driver import Provider, Registry
from ketotpu.driver.config import ConfigError
from ketotpu.engine.coalesce import CoalescingEngine
from ketotpu.engine.tpu import DeviceCheckEngine, _bucket15
from ketotpu.flightrec import FlightRecorder
from ketotpu.observability import Metrics, Tracer, make_logger
from ketotpu.profiler import DeviceProfiler, ProfilerDisabled
from ketotpu.server import serve_all
from ketotpu.waveledger import WaveLedger

T = RelationTuple.from_string


# -- ledger ring semantics ---------------------------------------------------


def test_wave_ids_monotonic():
    led = WaveLedger(capacity=4)
    ids = [led.next_wave_id() for _ in range(5)]
    assert ids == sorted(ids) and len(set(ids)) == 5


def test_ring_evicts_but_total_counts():
    led = WaveLedger(capacity=3)
    for i in range(7):
        led.record({"wave": i, "size": i + 1})
    assert led.recorded == 7
    snap = led.snapshot()
    assert len(snap) == 3
    # newest first, oldest evicted
    assert [e["wave"] for e in snap] == [6, 5, 4]


def test_snapshot_filters():
    led = WaveLedger(capacity=8)
    for i in range(5):
        led.record({"wave": i, "size": 1})
    assert [e["wave"] for e in led.snapshot(n=2)] == [4, 3]
    assert [e["wave"] for e in led.snapshot(wave=2)] == [2]
    assert led.snapshot(wave=99) == []


def test_stats_aggregates():
    led = WaveLedger(capacity=16)
    for size, wait, dev in ((1, 0.5, 2.0), (3, 1.5, 4.0), (8, 2.5, 6.0)):
        led.record({
            "wave": size, "size": size,
            "window_wait_ms_p50": wait, "device_ms": dev,
        })
    st = led.stats()
    assert st["waves_recorded"] == 3 and st["waves_in_ring"] == 3
    assert st["wave_size_mean"] == 4.0
    assert st["wave_size_p50"] == 3
    assert st["wave_size_p95"] == 8
    assert st["window_wait_ms_p50"] == 1.5
    assert st["device_ms_p95"] == 6.0
    assert WaveLedger().stats()["wave_size_mean"] == 0.0


# -- compile watch -----------------------------------------------------------


def test_compilewatch_attribution_and_log():
    w = CompileWatch(log_size=2)
    with w.scope("expand", lambda: "R=512"):
        w._on_event(_COMPILE_EVENT, 0.25)
    w._on_event(_COMPILE_EVENT, 0.5)  # outside any scope
    w._on_event("/jax/other/event", 9.9)  # ignored
    snap = w.snapshot()
    assert snap["compiles_total"] == 2
    assert snap["per_fn"] == {"expand": 1, "other": 1}
    assert snap["compile_seconds_total"] == pytest.approx(0.75)
    assert [e["fn"] for e in snap["log"]] == ["expand", "other"]
    assert snap["log"][0]["signature"] == "R=512"
    w._on_event(_COMPILE_EVENT, 0.1)  # log ring holds the newest 2
    assert len(w.snapshot()["log"]) == 2


def test_compilewatch_warm_alarm():
    w = CompileWatch()
    m = Metrics()
    w.bind(m, make_logger(level="critical"))
    w._on_event(_COMPILE_EVENT, 0.1)
    assert not w.warm and w.compiles_after_warm == 0
    w.declare_warm()
    w._on_event(_COMPILE_EVENT, 0.2)
    assert w.compiles_after_warm == 1
    assert m.get_counter("keto_xla_compiles_after_warm_total", fn="other") == 1
    assert m.get_counter(compilewatch.COMPILES_METRIC, fn="other") == 2
    w.declare_cold("rebuild")
    w._on_event(_COMPILE_EVENT, 0.2)
    assert w.compiles_after_warm == 1  # cold again: no alarm

    # a raising signature callable degrades to "?", never raises
    with w.scope("boom", lambda: 1 / 0):
        w._on_event(_COMPILE_EVENT, 0.1)
    assert w.snapshot()["log"][-1]["signature"] == "?"


@pytest.fixture
def cache_dir_restored():
    import jax

    was = jax.config.jax_compilation_cache_dir
    keyed = jax.config.jax_compilation_cache_include_metadata_in_key
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    jax.config.update(
        "jax_compilation_cache_include_metadata_in_key", keyed)


@pytest.mark.parametrize("placed", ["by_environment", "by_default"])
def test_compile_cache_placement(placed, monkeypatch, tmp_path,
                                 cache_dir_restored):
    """place_cache leaves a cache the environment placed to JAX, and
    otherwise names one fixed directory inside the checkout: the path is
    part of the cache key, so nothing of this process may be in it."""
    import os
    import tempfile
    import time

    import jax

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    if placed == "by_environment":
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compilewatch.place_cache() == str(tmp_path)
        # no directory set in code: JAX reads the variable itself
        assert jax.config.jax_compilation_cache_dir == before
        # named scopes are part of a cached program's identity
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        return
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compilewatch.place_cache()
    # another process, another time, another temp dir: the same path
    monkeypatch.setattr(os, "getpid", lambda: 424242)
    monkeypatch.setattr(time, "time", lambda: 1.0)
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    assert compilewatch.place_cache() == first
    assert first == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert jax.config.jax_compilation_cache_include_metadata_in_key


def test_compilewatch_counts_cache_hits():
    w = CompileWatch()
    w._on_cache_hit("/jax/compilation_cache/cache_hits")
    w._on_cache_hit("/jax/compilation_cache/cache_misses")
    assert w.snapshot()["cache_hits"] == 1


# -- wave <-> request cross-link ---------------------------------------------


class _FakeInner:
    """Minimal check engine: answers True, tracks nothing."""

    leopard_answered = 0
    fallbacks = 0
    phase_seconds: dict = {}

    def batch_check(self, queries, rest_depth=0):
        return [True] * len(queries)


class _FakeRegistry:
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


def test_wave_crosslinks_flight_recorder():
    reg = _FakeRegistry()
    led = WaveLedger(capacity=8)
    co = CoalescingEngine(_FakeInner(), window=0.01, ledger=led)
    tp = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
    try:
        with flightrec.rpc_recording(reg, "check", traceparent=tp):
            assert co.check_is_member(T("Doc:d0#view@u1")) is True
    finally:
        co.close()
    # the RPC's flight-recorder entry carries wave= and the traceparent...
    (entry,) = reg.flight_recorder().snapshot()
    assert entry["traceparent"] == tp
    wave_id = entry["wave"]
    # ...and the ledger's record for that wave carries the traceparent back
    (wave,) = led.snapshot(wave=wave_id)
    assert wave["size"] == 1 and wave["errors"] == 0
    assert wave["slowest"][0]["traceparent"] == tp
    assert wave["window_wait_ms_p50"] >= 0.0
    assert led.stats()["waves_recorded"] >= 1


def test_wave_records_singleflight_followers():
    led = WaveLedger()
    co = CoalescingEngine(_FakeInner(), window=0.05, ledger=led)
    q = T("Doc:d0#view@u1")
    results = []
    threads = [
        threading.Thread(target=lambda: results.append(co.check_is_member(q)))
        for _ in range(6)
    ]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        co.close()
    assert results == [True] * 6
    total = sum(w["singleflight_collapsed"] for w in led.snapshot())
    assert total == co.singleflight_collapsed > 0


class _SizingInner(_FakeInner):
    """Counts like the engine's fused collect does: every wave holds a
    seeded count of general rows of up to 1024 (a column group's rows ride
    one slot) and runs them at the engine's half-octave lanes."""

    fused_waves = fused_d2h_fetches = 0
    fused_general_rows = fused_general_lanes = 0

    def __init__(self, seed):
        self._rng = random.Random(seed)
        self.sized = []

    def batch_check(self, queries, rest_depth=0):
        rows = self._rng.choice((0, 1, 256, 257, 333, 1024))
        lanes = min(_bucket15(rows, 256), 1024) if rows else 0
        self.fused_waves += 1
        self.fused_d2h_fetches += 1
        self.fused_general_rows += rows
        self.fused_general_lanes += lanes
        self.sized.append((rows, lanes))
        return [True] * len(queries)


@pytest.mark.parametrize("seed", [30, 31])
def test_wave_records_general_rows_and_lanes(seed):
    """The general tier's rows and lanes of every wave, as the engine
    counted them at collect: per wave in the entry's ``fused`` group, over
    a window as the two sums whose quotient ``wave_general_fill`` reads."""
    inner = _SizingInner(seed)
    led = WaveLedger()
    co = CoalescingEngine(inner, window=0.001, ledger=led)
    before = led.stats()
    try:
        for i in range(8):
            assert co.check_is_member(T(f"Doc:d{i}#edit@u{i}")) is True
    finally:
        co.close()
    waves = list(reversed(led.snapshot()))
    assert [(w["fused"]["general_rows"], w["fused"]["general_lanes"])
            for w in waves] == inner.sized
    assert all(w["fused"]["waves"] == w["fused"]["d2h_fetches"] == 1
               for w in waves)
    after = led.stats()
    moved = tuple(after[k] - before[k]
                  for k in ("fused_general_rows", "fused_general_lanes"))
    assert moved == tuple(map(sum, zip(*inner.sized)))
    assert moved == (inner.fused_general_rows, inner.fused_general_lanes)


def test_general_rows_and_lanes_on_the_scrape():
    """The registry publishes the engine's two counters as they stand:
    a window's delta of each is the engine's own."""
    reg = Registry(Provider({
        "namespaces": [{"name": "Doc"}],
        "engine": {"kind": "tpu", "coalesce_ms": 0},
    })).init()
    try:
        eng = reg.check_engine()
        eng = getattr(eng, "inner", eng)
        names = ("keto_fused_general_rows_total",
                 "keto_fused_general_lanes_total")
        reg.sample_engine_metrics()
        before = [reg.metrics().get_gauge(n) for n in names]
        assert before == [0, 0]
        # what two collected waves of 300 and 333 general rows leave
        eng.fused_general_rows += 300 + 333
        eng.fused_general_lanes += 384 + 384
        reg.sample_engine_metrics()
        assert [reg.metrics().get_gauge(n) for n in names] == [633, 768]
    finally:
        reg.close_engines()


# -- config + registry plumbing ----------------------------------------------


def test_observability_config_defaults():
    cfg = Provider({})
    assert cfg.get("observability.wave_ledger_size") == 256
    assert cfg.get("observability.flight_recorder_size") == 32
    assert cfg.get("observability.flight_recorder_max_age_s") == 600
    assert cfg.get("observability.compile_log_size") == 128
    assert cfg.get("observability.warm_compile_warning") is True
    assert cfg.get("observability.profiler.enabled") is False


@pytest.mark.parametrize("key,bad", [
    ("wave_ledger_size", 0),
    ("flight_recorder_size", -1),
    ("compile_log_size", "big"),
    ("flight_recorder_max_age_s", 0),
    ("warm_compile_warning", "yes"),
    ("profiler", {"enabled": 1}),
    ("profiler", {"max_seconds": -3}),
])
def test_observability_config_validation(key, bad):
    with pytest.raises(ConfigError):
        Provider({"observability": {key: bad}})


def test_registry_observability_plumbing():
    reg = Registry(Provider({
        "namespaces": [{"name": "Doc"}],
        "engine": {"kind": "oracle"},
        "observability": {
            "wave_ledger_size": 7,
            "flight_recorder_size": 5,
            "flight_recorder_max_age_s": 123,
            "compile_log_size": 9,
        },
    }))
    assert reg.wave_ledger().capacity == 7
    assert reg.wave_ledger() is reg.wave_ledger()
    fr = reg.flight_recorder()
    assert fr.capacity == 5 and fr.max_age_s == 123.0
    assert reg.compile_watch() is compilewatch.get()
    assert reg.compile_watch()._log.maxlen == 9
    with pytest.raises(ProfilerDisabled):
        reg.profiler().capture(1.0)


def test_profiler_gating_and_clamp():
    prof = DeviceProfiler(enabled=False)
    with pytest.raises(ProfilerDisabled):
        prof.capture(1.0)
    assert prof.captures == 0


# -- compile gate: warm mixed-shape waves must not recompile -----------------
#
# slow: the warm-up passes are real XLA:CPU compiles (minutes of codegen
# across the mixed check/expand shapes); CI's metrics-smoke job runs the
# slow leg explicitly, tier-1 keeps the unit suites above


@pytest.fixture(scope="module")
def warm_engine():
    from ketotpu.api.types import SubjectSet
    from ketotpu.utils.synth import build_synth, synth_queries_mixed

    graph = build_synth(n_users=64, n_groups=8, n_folders=32, n_docs=128)
    eng = DeviceCheckEngine(
        graph.store, graph.manager, frontier=2048, arena=4096, max_batch=512
    )
    eng.snapshot()
    mixed = synth_queries_mixed(graph, 96, seed=6, general_frac=0.3)
    roots = [SubjectSet("Doc", graph.docs[i % len(graph.docs)], "parents")
             for i in range(8)]
    # two warm passes per shape: the first compiles default-sized
    # programs, the second the demand-adapted variants (bench.py:_fast_path)
    for _ in range(2):
        eng.batch_check(mixed)
        eng.batch_check(mixed[:32])
        eng.batch_expand(roots, 3)
    return eng, mixed, roots


@pytest.mark.slow
def test_warm_engine_never_recompiles(warm_engine):
    eng, mixed, roots = warm_engine
    watch = compilewatch.get()
    before = watch.compiles_total
    for _ in range(3):
        eng.batch_check(mixed)
        eng.batch_check(mixed[:32])
        eng.batch_expand(roots, 3)
    assert watch.compiles_total == before, (
        "steady-state mixed-shape waves recompiled: "
        f"{watch.snapshot()['log'][-5:]}"
    )


@pytest.mark.slow
def test_engine_declares_warm_after_clean_dispatches(warm_engine):
    eng, mixed, _ = warm_engine
    watch = compilewatch.get()
    # the fixture's repeats were clean, so the engine has already seen
    # >= warm_after_clean compile-free dispatches
    assert eng._clean_dispatches >= eng.warm_after_clean or watch.warm
    eng.batch_check(mixed)
    assert watch.warm
    # a snapshot rebuild legitimizes compiles again
    eng.refresh()
    assert not watch.warm
    assert eng._clean_dispatches == 0


# -- live daemon: /debug/waves + /debug/compiles -----------------------------

TUPLES = [
    "Group:admin#members@alice",
    "Doc:readme#viewers@Group:admin#members",
]


@pytest.fixture(scope="module")
def server():
    cfg = Provider(
        {
            "serve": {
                n: {"host": "127.0.0.1", "port": 0}
                for n in ("read", "write", "metrics", "opl")
            },
            "namespaces": [{"name": "Group"}, {"name": "Doc"}],
            "engine": {
                "kind": "tpu",
                "frontier": 1024,
                "arena": 4096,
                "max_batch": 256,
                "coalesce_ms": 5,
            },
            "log": {"request_log": False},
        }
    )
    reg = Registry(cfg).init()
    reg.store().write_relation_tuples(
        *[RelationTuple.from_string(s) for s in TUPLES]
    )
    srv = serve_all(reg)
    yield srv
    srv.stop()


def _get(url):
    with urllib.request.urlopen(url, timeout=30) as resp:
        return resp.read().decode()


@pytest.fixture(scope="module")
def debug_scrape(server):
    read = "http://%s:%d" % tuple(server.addresses["read"])
    metrics = "http://%s:%d" % tuple(server.addresses["metrics"])

    # concurrent singles so the coalescer forms real multi-slot waves
    def check(subject):
        _get(
            f"{read}/relation-tuples/check/openapi?namespace=Doc"
            f"&object=readme&relation=viewers&subject_id={subject}"
        )

    check("alice")  # warm pass: compiles outside the hammer
    threads = [
        threading.Thread(target=check, args=(s,))
        for s in ("alice", "mallory", "alice", "bob", "carol", "alice")
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # an expand rides along: its device program is shape-distinct from
    # anything earlier tests compiled, so the compile observatory is
    # guaranteed a live event while THIS server's metrics are bound
    _get(
        f"{read}/relation-tuples/expand?namespace=Doc&object=readme"
        "&relation=viewers"
    )
    time.sleep(0.2)  # let the wave worker file the last ledger record
    return {
        "metrics": metrics,
        "waves": json.loads(_get(f"{metrics}/debug/waves")),
        "compiles": json.loads(_get(f"{metrics}/debug/compiles")),
        "flight": json.loads(_get(f"{metrics}/debug/flight-recorder")),
        "metrics_text": _get(f"{metrics}/metrics/prometheus"),
    }


@pytest.mark.slow
def test_debug_waves_populated(debug_scrape):
    payload = debug_scrape["waves"]
    assert payload["stats"]["waves_recorded"] >= 1
    assert payload["waves"], "live traffic must file wave records"
    for w in payload["waves"]:
        assert w["size"] >= 1
        assert w["device_ms"] >= 0.0
        assert w["errors"] == 0


@pytest.mark.slow
def test_debug_waves_crosslink_flight_recorder(debug_scrape):
    checks = [
        e for e in debug_scrape["flight"]["slowest"]
        if e["op"] == "check" and "wave" in e
    ]
    assert checks, "coalesced checks must carry wave= in the recorder"
    ledger_ids = {w["wave"] for w in debug_scrape["waves"]["waves"]}
    assert any(e["wave"] in ledger_ids for e in checks)


@pytest.mark.slow
def test_debug_waves_query_params(debug_scrape):
    metrics = debug_scrape["metrics"]
    wave_id = debug_scrape["waves"]["waves"][0]["wave"]
    one = json.loads(_get(f"{metrics}/debug/waves?wave={wave_id}"))
    assert [w["wave"] for w in one["waves"]] == [wave_id]
    limited = json.loads(_get(f"{metrics}/debug/waves?n=1"))
    assert len(limited["waves"]) == 1
    with pytest.raises(urllib.error.HTTPError) as exc:
        _get(f"{metrics}/debug/waves?wave=xyz")
    assert exc.value.code == 400


@pytest.mark.slow
def test_debug_compiles_live(debug_scrape):
    snap = debug_scrape["compiles"]
    assert snap["compiles_total"] >= 1
    assert snap["log"], "compile events must be logged"
    assert sum(snap["per_fn"].values()) == snap["compiles_total"]
    assert "keto_xla_compiles_total" in debug_scrape["metrics_text"]


@pytest.mark.slow
def test_profile_endpoint_gated(debug_scrape):
    req = urllib.request.Request(
        f"{debug_scrape['metrics']}/debug/profile?seconds=1", method="POST"
    )
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(req, timeout=30)
    assert exc.value.code == 403  # profiler unarmed by default


@pytest.mark.slow
def test_wave_series_in_metrics(server, debug_scrape):
    # what a caller's window reads wave size and waits from, now that the
    # ledger's ring quantiles are off the scrape: checks and waves ridden,
    # the coalesce_wait / device_compute stages, the wave threads' states
    metrics = debug_scrape["metrics"]
    text = _get(f"{metrics}/metrics/prometheus")
    assert "keto_wave_size_mean" not in text
    assert "keto_wave_window_wait_ms_p50" not in text
    waves = float(re.search(
        r"^keto_engine_coalesced_waves (\S+)", text, re.M).group(1))
    checks = float(re.search(
        r"^keto_engine_coalesced_checks (\S+)", text, re.M).group(1))
    assert checks >= waves >= 1
    for stage in ("coalesce_wait", "device_compute"):
        assert re.search(
            r'keto_rpc_stage_seconds_count\{op="check",stage="%s"\} [1-9]'
            % stage, text), stage
    assert 'keto_coalescer_thread_seconds{state="serve",thread=' in text
