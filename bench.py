"""Benchmark: end-to-end batched permission checks per second.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...},
naming the platform, device kind and device count it ran on.  Every
section runs under its own guard so one failure does not void the rest,
but the exit code tells: 0 only when every section ran on a TPU; 2 when a
section failed or the platform is not a TPU (there is no CPU fallback: a
CPU timing is not a device number); 3 when the steady-state compile gate
trips (an XLA compile fired inside a timed pass that had been warmed at
the exact shape — a shape-discipline regression; see `_steady`).  The JSON
line is printed BEFORE a nonzero exit so the evidence always lands.

Baseline: the reference's checked-in BenchmarkComputedUsersets figure —
81,280 ns per sequential strict-mode check on in-memory SQLite
(`benchtest.new.txt:5`), i.e. ~12,303 checks/s/core.  `vs_baseline` is the
speedup multiple of this engine's batched throughput over that number.

Sections (the BASELINE.json configs):
  1. fast-path throughput — Drive-style synth graph (CSS+TTU view chains,
     the "5-hop rewrites" shape), 16k-query batches through the public
     batch_check surface (string encode, device dispatch, fallbacks all
     inside the clock), chunk-pipelined;
  2. mixed AND/NOT slice (config #4's rewrites) — `edit` =
     !banned && view routes through the fused algebra program;
     reported separately as general_checks_per_sec;
  3. Expand at depth 5 (config #3) — batched device expand, trees/s;
  4. serving latency (the metric's p50/p99 half) — concurrent single
     Checks through the real gRPC daemon with the coalescer on, plus a
     `serve --workers 2` leg measuring the multi-process topology;
  5. 10M-tuple scale (configs #4/#5 scale) — columnar bulk load,
     projection seconds, device HBM bytes, and checks/s at 10M.

Runs on the ambient JAX platform, one process per chip (chip_smoke.py is
the quicker proof that the program starts there).
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time
import traceback

import numpy as np

BASELINE_NS_PER_OP = 81_280  # reference benchtest.new.txt:5
BATCH = 16384
ROUNDS = 4


def _engine(graph, **kw):
    from ketotpu.engine.tpu import DeviceCheckEngine

    kw.setdefault("frontier", 6 * BATCH)
    kw.setdefault("arena", 12 * BATCH)
    # general-path buffers: 512 AND/NOT roots per dispatch at the measured
    # ~128-task-per-root footprint (tests keep the small defaults)
    kw.setdefault("cap", 65536)
    kw.setdefault("gen_arena", 65536)
    kw.setdefault("vcap", 32768)
    # chunked dispatch: two fused programs in flight per batch — device
    # execution overlaps the host's per-chunk encode/collect
    kw.setdefault("max_batch", BATCH // 2)
    return DeviceCheckEngine(graph.store, graph.manager, **kw)


@contextlib.contextmanager
def _steady(out, section):
    """Steady-state compile gate: every timed pass wrapped in this context
    has already been warmed at its EXACT shape, so any XLA compile firing
    inside it is a shape-discipline regression (an adaptive schedule or
    bucket decision changed between the warm and timed passes) AND it
    poisons the number being measured (a seconds-long compile inside a
    milliseconds-long pass).  Trips the section into
    `steady_state_compiles` and the process into exit code 3."""
    from ketotpu import compilewatch

    w = compilewatch.get()
    before = w.compiles_total
    yield
    delta = w.compiles_total - before
    if delta:
        gate = out.setdefault("steady_state_compiles", {})
        gate[section] = gate.get(section, 0) + delta


class _Sections:
    """Run each bench section under its own guard; a failure records an
    error entry (main() then exits non-zero) and the remaining sections
    still run, so one run localizes every failing section."""

    def __init__(self, out: dict):
        self.out = out

    def run(self, name, fn, *args, **kw):
        try:
            fn(*args, **kw)
            self.out.setdefault("sections_ok", []).append(name)
            return True
        except Exception as e:  # noqa: BLE001 — the bench must finish
            tb = traceback.format_exc(limit=3).strip().splitlines()
            self.out.setdefault("errors", {})[name] = (
                f"{type(e).__name__}: {e} | {tb[-1] if tb else ''}"
            )
            return False


def main() -> int:
    out: dict = {}
    baseline = 1e9 / BASELINE_NS_PER_OP
    state: dict = {}
    sec = _Sections(out)

    # KETO_BENCH_SKIP: comma-separated section names to skip
    skip = set(
        s for s in os.environ.get("KETO_BENCH_SKIP", "").split(",") if s
    )

    def run(name, fn, *a):
        if name in skip:
            out.setdefault("sections_skipped", []).append(name)
            return
        # per-section compile accounting (subprocess sections like
        # serving_workers legitimately read 0: their compiles happen in
        # the worker process)
        from ketotpu import compilewatch

        before = compilewatch.get().compiles_total
        sec.run(name, fn, *a)
        delta = compilewatch.get().compiles_total - before
        if delta:
            out.setdefault("compile_counts", {})[name] = delta

    run("host_build", _host_build, out, state)
    # serving_workers FIRST: a chip belongs to one process at a time, so
    # its subprocess owner must come and go before THIS process touches
    # the device (the `device` section below is the first that does)
    run("serving_workers", _serving_workers, out, state)
    run("device", _device, out)
    run("fast_path", _fast_path, out, state, baseline)
    run("mixed_general", _mixed_general, out, state)
    run("wave_latency", _wave_latency, out, state)
    run("expand", _expand, out, state)
    run("leopard", _leopard, out, state)
    run("jit_shape_audit", _jit_shape_audit, out, state)
    run("serving", _serving, out, state)
    run("serve_northstar", _serve_northstar, out, state)
    run("serve_trace", _serve_trace, out, state)
    run("serve_batch", _serve_batch, out, state)
    run("cache_shield", _cache_shield, out, state)
    run("scale_10m", _scale_10m, out, state, baseline)
    run("scale_10m_mixed", _scale_10m_mixed, out, state)
    run("scale_10m_expand", _scale_10m_expand, out, state)
    run("leopard_10m", _leopard_10m, out, state)
    run("write_visibility", _write_visibility, out, state)
    run("durability", _durability, out, state)

    _publish_phases(out, state)
    from ketotpu import compilewatch

    out["xla_compiles_total"] = compilewatch.get().compiles_total
    tripped = bool(out.get("steady_state_compiles"))
    out["compile_gate"] = "fail" if tripped else "pass"
    print(json.dumps(out))
    if out.get("errors") or out.get("platform") != "tpu":
        return 2
    return 3 if tripped else 0


def _publish_phases(out, state) -> None:
    """Engine-phase wall-time breakdown (engine/tpu.py accumulators) into
    the JSON tail: cumulative milliseconds + sample counts per phase for
    the small-graph engine and the 10M-scale one."""
    for key, eng in (
        ("engine_phase_ms", state.get("eng")),
        ("engine_phase_ms_10m", state.get("beng")),
    ):
        if eng is None or not getattr(eng, "phase_seconds", None):
            continue
        out[key] = {
            name: {
                "total_ms": round(1000 * s, 2),
                "count": eng.phase_counts.get(name, 0),
            }
            for name, s in sorted(eng.phase_seconds.items())
        }


def _host_build(out, state) -> None:
    from ketotpu.utils.synth import build_synth

    graph = build_synth(
        n_users=2000, n_groups=100, n_folders=2000, n_docs=20000, seed=0
    )
    state["graph"] = graph
    out["tuples"] = len(graph.store)


def _device(out) -> None:
    # the device every number below was taken on, as JAX reports it
    import jax

    devices = jax.devices()
    out["platform"] = devices[0].platform
    out["device_kind"] = devices[0].device_kind
    out["device_count"] = len(devices)


def _fast_path(out, state, baseline) -> None:
    from ketotpu.utils.synth import synth_queries

    graph = state["graph"]
    eng = state["eng"] = _engine(graph)
    eng.snapshot()
    queries = synth_queries(graph, BATCH * ROUNDS, seed=2)
    state["queries"] = queries
    batches = [queries[i * BATCH : (i + 1) * BATCH] for i in range(ROUNDS)]
    _, fallback = eng.batch_check_device_only(batches[0])
    eng.batch_check(batches[0])
    eng.batch_check(batches[0])  # second pass compiles the adaptive schedule
    with _steady(out, "fast_path"):
        t0 = time.perf_counter()
        done = 0
        times = []
        for b in batches:
            bt = time.perf_counter()
            done += len(eng.batch_check(b))
            times.append(time.perf_counter() - bt)
        dt = time.perf_counter() - t0
    checks_per_sec = done / dt
    out.update(
        metric="check_throughput",
        value=round(checks_per_sec, 1),
        unit="checks/sec",
        vs_baseline=round(checks_per_sec / baseline, 3),
        batch=BATCH,
        device_fallback_rate=round(float(np.mean(fallback)), 5),
        device_retries=eng.retries,
        oracle_fallbacks=eng.fallbacks,
        p50_batch_ms=round(1000 * sorted(times)[len(times) // 2], 1),
    )


def _mixed_general(out, state) -> None:
    # mixed AND/NOT (BASELINE config #4 rewrites)
    from ketotpu.utils.synth import synth_queries_mixed

    graph, eng = state["graph"], state["eng"]
    mixed = synth_queries_mixed(graph, 10_000, seed=6, general_frac=0.3)
    # warm TWICE at the EXACT timed shape: the first call compiles the
    # default-sized programs and feeds the occupancy EMAs; the second
    # compiles the demand-adapted variant the timed run will execute
    eng.batch_check(mixed)
    eng.batch_check(mixed)
    with _steady(out, "mixed_general"):
        t0 = time.perf_counter()
        got = eng.batch_check(mixed)
        mixed_cps = len(got) / (time.perf_counter() - t0)
    n_general = sum(q.relation == "edit" for q in mixed)
    pure_general = [q for q in mixed if q.relation == "edit"]
    eng.batch_check(pure_general)  # warm: its chunk shape differs from 10k's
    eng.batch_check(pure_general)
    with _steady(out, "mixed_general"):
        t0 = time.perf_counter()
        eng.batch_check(pure_general)
        general_cps = len(pure_general) / (time.perf_counter() - t0)
    out.update(
        mixed_10k_checks_per_sec=round(mixed_cps, 1),
        mixed_general_frac=round(n_general / len(mixed), 3),
        general_checks_per_sec=round(general_cps, 1),
        general_fallbacks=eng.fallbacks - out.get("oracle_fallbacks", 0),
    )


def _wave_latency(out, state) -> None:
    # engine-side wave latency (the p99 <= 2ms half of the metric):
    # device-only dispatch+collect timings per wave size
    eng, queries = state["eng"], state["queries"]
    for wave in (1, 64, 256, 1024):
        wq = queries[:wave]
        eng.batch_check_device_only(wq, retry=False)
        eng.batch_check_device_only(wq, retry=False)  # adaptive-shape warm
        lats = []
        with _steady(out, "wave_latency"):
            for _ in range(20):
                t0 = time.perf_counter()
                eng.batch_check_device_only(wq, retry=False)
                lats.append(time.perf_counter() - t0)
        lats.sort()
        p50 = lats[len(lats) // 2]
        p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))]
        out[f"engine_p50_ms_w{wave}"] = round(1000 * p50, 2)
        out[f"engine_p99_ms_w{wave}"] = round(1000 * p99, 2)


def _expand(out, state) -> None:
    # Expand at depth 5 (BASELINE config #3)
    from ketotpu.api.types import SubjectSet

    graph, eng = state["graph"], state["eng"]
    rng = np.random.default_rng(9)
    roots = [
        SubjectSet("Doc", graph.docs[int(rng.integers(len(graph.docs)))], "parents")
        for _ in range(512)
    ]
    eng.batch_expand(roots, 5)  # compile at the measured batch shape
    fb0 = eng.fallbacks
    with _steady(out, "expand"):
        t0 = time.perf_counter()
        trees = eng.batch_expand(roots, 5)
        expand_tps = len(trees) / (time.perf_counter() - t0)
    # per-call latency (the metric's p50/p99 half for Expand): single-root
    # expands, the interactive shape a UI permission tree fetch hits
    p50, p99 = _expand_latency(eng, roots[:1], samples=40, gate=(out, "expand"))
    out.update(
        expand_trees_per_sec=round(expand_tps, 1),
        expand_depth=5,
        expand_fallback_rate=round((eng.fallbacks - fb0) / len(roots), 4),
        expand_p50_ms=p50,
        expand_p99_ms=p99,
    )


def _expand_latency(eng, roots, *, samples: int, depth: int = 5, gate=None):
    """(p50_ms, p99_ms) over repeated single-root batch_expand calls.
    `gate=(out, section)` arms the steady-state compile gate around the
    timed loop (the 1-root warm call stays outside it)."""
    eng.batch_expand(roots, depth)  # compile the 1-root shape
    lats = []
    ctx = _steady(*gate) if gate else contextlib.nullcontext()
    with ctx:
        for _ in range(samples):
            t0 = time.perf_counter()
            eng.batch_expand(roots, depth)
            lats.append(time.perf_counter() - t0)
    lats.sort()
    p50 = lats[len(lats) // 2]
    p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))]
    return round(1000 * p50, 2), round(1000 * p99, 2)


def _leopard_rates(eng, graph, *, calls: int, seed: int):
    """(list_objects_per_sec, list_subjects_per_sec) through the engine's
    Leopard listing surface, randomized over users/groups."""
    from ketotpu.api.types import SubjectID

    rng = np.random.default_rng(seed)
    users = [
        graph.users[int(rng.integers(len(graph.users)))] for _ in range(calls)
    ]
    groups = [
        graph.groups[int(rng.integers(len(graph.groups)))]
        for _ in range(calls)
    ]
    eng.list_objects("Group", "members", SubjectID(users[0]))  # warm
    t0 = time.perf_counter()
    for u in users:
        eng.list_objects("Group", "members", SubjectID(u), page_size=1000)
    lo_ps = calls / (time.perf_counter() - t0)
    eng.list_subjects("Group", groups[0], "members")
    t0 = time.perf_counter()
    for g in groups:
        eng.list_subjects("Group", g, "members", page_size=1000)
    ls_ps = calls / (time.perf_counter() - t0)
    return round(lo_ps, 1), round(ls_ps, 1)


def _leopard_deep(*, depth, n_chains, n_queries, seed):
    """(p50_batch_ms, oracle_fallback_delta) for deep nested-group checks.

    A dedicated rewrite-free chain graph (utils/synth.build_deep_groups)
    with the engine called directly: every check needs ``depth``
    containment hops, so on the closure path each one is a single binary
    search and NO device program is ever compiled — the whole batch is
    answered pre-dispatch.  n_users stays at the default 64 so the
    deepest groups sit under leopard's max_width taint threshold.  The
    served form of this workload is the benchmark cell
    ``groups-deep32.members1k`` (benchmark/configs/groups-deep32.json):
    chains 2 to 32 deep and 10 to 100 wide behind the REST door, where
    wide groups do send their chains' rows to the device's BFS beside
    the closure tier, in one wave."""
    from ketotpu.engine.tpu import DeviceCheckEngine
    from ketotpu.utils.synth import build_deep_groups, deep_queries

    deep = build_deep_groups(depth=depth, n_chains=n_chains, seed=seed)
    deng = DeviceCheckEngine(deep.store, deep.manager, max_depth=depth + 4)
    deng.snapshot()
    qs = deep_queries(deep, n_queries, depth=depth, seed=seed + 1)
    deng.batch_check(qs)  # builds + folds the closure outside the clock
    fb0 = deng.fallbacks
    lats = []
    for _ in range(20):
        t0 = time.perf_counter()
        deng.batch_check(qs)
        lats.append(time.perf_counter() - t0)
    lats.sort()
    return round(1000 * lats[len(lats) // 2], 2), deng.fallbacks - fb0


def _leopard(out, state) -> None:
    # Leopard closure index (the reverse-query subsystem): listing-API
    # rates on the 31k graph plus depth-12 nested-group checks answered
    # entirely from the closure (zero oracle fallbacks on a clean graph)
    graph, eng = state["graph"], state["eng"]
    st = eng.leopard_stats()
    lo_ps, ls_ps = _leopard_rates(eng, graph, calls=200, seed=21)
    p50, fbs = _leopard_deep(depth=12, n_chains=8, n_queries=256, seed=31)
    out.update(
        closure_build_s=round(float(st.get("build_s", 0.0)), 3),
        closure_pairs=int(st.get("pairs", 0)),
        list_objects_per_sec=lo_ps,
        list_subjects_per_sec=ls_ps,
        deep_check_p50_ms=p50,
        deep_check_depth=12,
        deep_check_batch=256,
        deep_check_fallbacks=int(fbs),
    )


def _leopard_10m(out, state) -> None:
    # the 10M-tuple leg: closure build cost + listing rates against the
    # columnar graph's 1.2M-user membership relation; the deep-check
    # companion runs on a wider chain set (the 10M graph's group nesting
    # is depth-2 by construction, so chains are measured on the dedicated
    # deep shape at larger chain count)
    big, beng = state["big"], state["beng"]
    st = beng.leopard_stats()
    lo_ps, ls_ps = _leopard_rates(beng, big, calls=100, seed=23)
    p50, fbs = _leopard_deep(depth=12, n_chains=64, n_queries=256, seed=33)
    out.update(
        closure_build_s_10m=round(float(st.get("build_s", 0.0)), 3),
        closure_pairs_10m=int(st.get("pairs", 0)),
        list_objects_per_sec_10m=lo_ps,
        list_subjects_per_sec_10m=ls_ps,
        deep_check_p50_ms_10m=p50,
        deep_check_fallbacks_10m=int(fbs),
    )


def _jit_shape_audit(out, state) -> None:
    # Static-jit-arg audit (ISSUE 9): the audited jit entry points hold
    # their compile signatures when the DATA varies inside one shape
    # bucket.  Findings the gate now enforces:
    #   * engine/algebra.run_general_packed + fastpath: qpad buckets via
    #     _bucket/_bucket15 — 260 and 300 queries share one variant;
    #   * engine/expand_device.run_expand: root count pads to a
    #     power-of-two bucket (was a raw compile axis: every distinct
    #     expand batch size compiled a fresh program);
    #   * leopard/device.ship_pairs: the pair arrays pad to a
    #     power-of-two bucket (was raw: every incremental closure
    #     rebuild recompiled the probe on the serving path).
    # Each leg warms one bucket member and times the OTHER inside the
    # steady gate — a compile here is a shape-discipline regression.
    from types import SimpleNamespace

    from ketotpu.api.types import SubjectSet
    from ketotpu.leopard import device as leodev
    from ketotpu.utils.synth import synth_queries

    graph, eng = state["graph"], state["eng"]
    rng = np.random.default_rng(41)
    qs = synth_queries(graph, 300, seed=43)
    eng.batch_check(qs)  # warms the 384/512 buckets
    roots = [
        SubjectSet(
            "Doc", graph.docs[int(rng.integers(len(graph.docs)))], "parents"
        )
        for _ in range(5)
    ]
    eng.batch_expand(roots, 5)  # warms the 8-root bucket

    def mk_dev(n_pairs):
        raw = np.unique(rng.integers(0, 1 << 40, size=2 * n_pairs,
                                     dtype=np.int64))[:n_pairs]
        return leodev.ship_pairs(SimpleNamespace(
            elt_packed=np.sort(raw), elt_hop=np.ones(n_pairs, np.int32)
        ))

    dev_a, dev_b = mk_dev(3000), mk_dev(3500)  # one 4096 pad bucket
    keys = rng.integers(0, 1 << 40, size=2048, dtype=np.int64)
    if dev_a is not None:
        leodev.probe_pairs(dev_a, keys, 2048)  # warms (pairs=4096, pad=2048)
    qs2 = synth_queries(graph, 260, seed=47)
    with _steady(out, "jit_shape_audit"):
        eng.batch_check(qs2)
        eng.batch_expand(roots[:3], 5)
        if dev_b is not None:
            leodev.probe_pairs(dev_b, keys, 2048)
    out["jit_shape_audit_legs"] = 3


def _serving(out, state) -> None:
    # serving latency (RPS + p50/p99 through the daemon): closed-loop
    # clients IN-PROCESS with the server: on a single-core host the wire
    # path (proto + gRPC + GIL) is the binding constraint, not the
    # engine — 64 threads measured pure queueing, 32 keeps the
    # percentiles meaningful
    from bench_serve import run_serving_bench

    out.update(run_serving_bench(state["graph"], concurrency=32, duration=10.0))


def _serve_northstar(out, state) -> None:
    # fused tiered dispatch north star (engine/fused.py): single Checks
    # on the mixed-general workload through a daemon with
    # engine.fused_dispatch ON, at concurrency 1024 and 4096 — RPS + p99
    # per point, zero-divergence gate vs the host oracle, steady-state
    # compile gate, and the single-D2H-per-wave invariant from the wave
    # ledger's fused deltas.  Acceptance: engine wave p50
    # (northstar_wave_device_ms_p50) under the r05 ~3.3 ms unfused
    # cascade number on the same workload.
    from bench_serve import run_northstar_bench

    res = run_northstar_bench(state["graph"])
    # fold the leg's compile gate into the process-wide one (exit 3)
    for sec, n in (res.pop("steady_state_compiles", None) or {}).items():
        gate = out.setdefault("steady_state_compiles", {})
        gate[sec] = gate.get(sec, 0) + n
    out.update(res)


def _serve_trace(out, state) -> None:
    # request-anatomy observatory cost: the single-Check hammer with
    # tail-sampled tracing + the shadow plane (1/50 sampling) ON vs
    # tracing OFF — publishes serve_trace_overhead_pct (acceptance <= 5%)
    # and shadow_divergence_total (must be 0: every serving tier must
    # agree with the host oracle on live traffic)
    from bench_serve import run_trace_overhead_bench

    out.update(run_trace_overhead_bench(
        state["graph"], concurrency=32, duration=6.0
    ))


def _serve_batch(out, state) -> None:
    # batch front door (ISSUE 7, columnar since ISSUE 9):
    # /relation-tuples/batch/check hammered at high concurrency over the
    # async REST server — the acceptance bar is >=30k checks/s at
    # concurrency 512 / batch 512 with ZERO verdict divergence against
    # the single-check endpoint (the columnar path measured 37.8k vs
    # 16.3k scalar on the same single-core CPU host, 2.3x; the old 20k
    # bar predates the columnar decode/encode/dispatch/respond path)
    from bench_serve import run_batch_bench

    out.update(run_batch_bench(state["graph"], concurrency=512, duration=6.0))


def _cache_shield(out, state) -> None:
    # Hot-spot shield microbench (ketotpu/cache/): a 90%-repeat workload
    # through the coalescer path the server actually serves singles on —
    # cache on vs off — plus the singleflight collapse ratio under a
    # same-key thundering herd.  The ISSUE 5 acceptance bar is >=5x
    # checks/sec with the shield on.
    import threading

    from ketotpu.cache import ResultCache
    from ketotpu.engine.coalesce import CoalescingEngine
    from ketotpu.utils.synth import synth_queries

    graph, eng = state["graph"], state["eng"]
    rng = np.random.default_rng(21)
    hot = synth_queries(graph, 8, seed=23)
    cold = synth_queries(graph, 2048, seed=29)
    n = 400
    workload = [
        hot[int(rng.integers(len(hot)))] if rng.random() < 0.9
        else cold[int(rng.integers(len(cold)))]
        for _ in range(n)
    ]

    def drive(co):
        t0 = time.perf_counter()
        for q in workload:
            co.check_is_member(q)
        return n / (time.perf_counter() - t0)

    off = CoalescingEngine(eng, window=0.001)
    drive(off)  # warm compile shapes
    with _steady(out, "cache_shield"):
        uncached_per_sec = drive(off)
    off.close()

    rc = ResultCache(max_entries=65536, shards=8)
    rc.attach_store(graph.store)
    eng.result_cache = rc
    try:
        on = CoalescingEngine(eng, window=0.001, cache=rc)
        drive(on)  # warm the cache
        with _steady(out, "cache_shield"):
            cached_per_sec = drive(on)
        hit_ratio = rc.stats()["hit_ratio"]
        on.close()
    finally:
        eng.result_cache = None

    # singleflight collapse: a 16-thread herd on one key, no cache so
    # every check must either own the slot or join an in-flight twin
    herd = CoalescingEngine(eng, window=0.005)
    per_thread, n_threads = 25, 16
    q = hot[0]

    def hammer():
        for _ in range(per_thread):
            herd.check_is_member(q)

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = per_thread * n_threads
    collapse_ratio = herd.singleflight_collapsed / total
    herd.close()

    out["cache"] = {
        "check_cached_per_sec": round(cached_per_sec, 1),
        "check_uncached_per_sec": round(uncached_per_sec, 1),
        "cached_speedup": round(cached_per_sec / uncached_per_sec, 2),
        "cache_hit_ratio": round(hit_ratio, 4),
        "singleflight_collapse_ratio": round(collapse_ratio, 4),
        "repeat_fraction": 0.9,
    }


def _serving_workers(out, state) -> None:
    # the multi-process topology (`serve --workers 2`): SO_REUSEPORT
    # workers around one device owner — measures the wire-path scaling
    # the workers exist for (parity on a 1-core box, scaling on real
    # multi-core hosts); VERDICT r4 #3
    from bench_serve import run_workers_bench

    out.update(run_workers_bench(state["graph"], concurrency=32, duration=10.0))


def _scale_10m(out, state, baseline) -> None:
    # 10M-tuple scale (columnar load + projection + checks)
    from ketotpu.utils.synth import build_synth_columnar, synth_queries

    t0 = time.perf_counter()
    big = state["big"] = build_synth_columnar(seed=0)
    build_s = time.perf_counter() - t0
    beng = state["beng"] = _engine(big)
    t0 = time.perf_counter()
    beng.snapshot()
    projection_s = time.perf_counter() - t0
    hbm_bytes = sum(
        int(np.asarray(v).nbytes) for v in beng._device_arrays.values()
    )
    bqs = synth_queries(big, 2 * BATCH, seed=3)
    _, bfb = beng.batch_check_device_only(bqs[:BATCH])
    beng.batch_check(bqs[:BATCH])
    beng.batch_check(bqs[:BATCH])
    with _steady(out, "scale_10m"):
        t0 = time.perf_counter()
        bdone = len(beng.batch_check(bqs[BATCH:]))
        big_cps = bdone / (time.perf_counter() - t0)
    out.update(
        tuples_10m=len(big.store),
        build_10m_s=round(build_s, 1),
        projection_s=round(projection_s, 1),
        projection_build_s=round(beng.projection_build_s, 1),
        projection_upload_s=round(beng.projection_upload_s, 1),
        hbm_bytes=hbm_bytes,
        checks_per_sec_10m=round(big_cps, 1),
        vs_baseline_10m=round(big_cps / baseline, 3),
        device_fallback_rate_10m=round(float(np.mean(bfb)), 5),
    )


def _scale_10m_mixed(out, state) -> None:
    # config #4 AT SPEC SCALE (VERDICT r3 #4): mixed AND/NOT 10k batch
    # against the 10M-tuple graph, not the 31k one
    from ketotpu.utils.synth import synth_queries_mixed

    beng = state["beng"]
    bmixed = synth_queries_mixed(state["big"], 10_000, seed=9, general_frac=0.3)
    beng.batch_check(bmixed)
    beng.batch_check(bmixed)
    with _steady(out, "scale_10m_mixed"):
        t0 = time.perf_counter()
        bgot = beng.batch_check(bmixed)
        out["mixed_10k_checks_per_sec_10m"] = round(
            len(bgot) / (time.perf_counter() - t0), 1
        )


def _scale_10m_expand(out, state) -> None:
    # depth-5 Expand over the >=1M-tuple Drive-style hierarchy (config #3
    # says 1M; this runs it on the full 10.6M graph) — includes the lazy
    # expand-table upload in the warm pass, not the timed one
    from ketotpu.api.types import SubjectSet

    big, beng = state["big"], state["beng"]
    fb1 = beng.fallbacks
    rng2 = np.random.default_rng(11)
    xroots = [
        SubjectSet("Doc", big.docs[int(rng2.integers(len(big.docs)))], "parents")
        for _ in range(512)
    ]
    # warm at the MEASURED root-count: _run_expand's schedule is a static
    # jit argument, so a 64-root warm pass compiles a different program
    # and the 512-root timed pass then eats the XLA compile
    beng.batch_expand(xroots, 5)
    # snapshot the engine's cumulative phase counters around the timed
    # pass so the throughput number decomposes into host vs device time
    ph0 = dict(getattr(beng, "phase_seconds", {}) or {})
    with _steady(out, "scale_10m_expand"):
        t0 = time.perf_counter()
        btrees = beng.batch_expand(xroots, 5)
        dt = time.perf_counter() - t0
    ph1 = dict(getattr(beng, "phase_seconds", {}) or {})

    def _delta(*keys):
        return round(sum(ph1.get(k, 0.0) - ph0.get(k, 0.0) for k in keys), 3)

    p50, p99 = _expand_latency(
        beng, xroots[:1], samples=20, gate=(out, "scale_10m_expand")
    )
    out.update(
        expand_trees_per_sec_10m=round(len(btrees) / dt, 1),
        expand_fallback_rate_10m=round(
            (beng.fallbacks - fb1) / max(2 * len(xroots), 1), 4
        ),
        expand_p50_ms_10m=p50,
        expand_p99_ms_10m=p99,
        expand_10m_device_seconds=_delta("expand_device", "expand_sync"),
        expand_10m_host_seconds=_delta(
            "expand_snapshot", "expand_assemble", "expand_oracle_fallback"
        ),
    )


def _write_visibility(out, state) -> None:
    """ISSUE 8: sub-second write visibility at 10M.  A background-
    compaction engine absorbs writes through the overlay (O(delta)),
    folds/compacts generations off the serving path, and checks keep
    serving meanwhile.  Measures write->visible lag, check p99 during a
    forced compaction vs steady state, and the fold-vs-full-build cost."""
    from ketotpu.api.types import RelationTuple
    from ketotpu.utils.synth import synth_queries

    big = state["big"]
    t0 = time.perf_counter()
    weng = _engine(big, compaction={"background": True})
    weng.snapshot()
    out["write_visibility_boot_s"] = round(time.perf_counter() - t0, 1)
    try:
        qs = synth_queries(big, BATCH, seed=21)
        weng.batch_check(qs)
        weng.batch_check(qs)
        weng.batch_check(qs[:1])  # the lag probe's dispatch bucket
        lat = []
        for _ in range(8):
            t0 = time.perf_counter()
            weng.batch_check(qs)
            lat.append((time.perf_counter() - t0) * 1000.0)
        steady_p99 = float(np.percentile(lat, 99))
        steady_cps = len(qs) * len(lat) / (sum(lat) / 1000.0)

        rng = np.random.default_rng(23)

        def _grants(n):
            return [
                RelationTuple.from_string(
                    "Doc:%s#viewers@%s"
                    % (
                        big.docs[int(rng.integers(len(big.docs)))],
                        big.users[int(rng.integers(len(big.users)))],
                    )
                )
                for _ in range(n)
            ]

        def _lag_ms(probe, timeout_s=120.0):
            t0 = time.perf_counter()
            while weng.batch_check([probe]) != [True]:
                if time.perf_counter() - t0 > timeout_s:
                    return timeout_s * 1000.0
            return (time.perf_counter() - t0) * 1000.0

        # -- write bursts riding alongside checks (overlay absorb path) --
        lags, mixed_lat, writes = [], [], 0
        for _ in range(16):
            burst = _grants(8)
            big.store.write_relation_tuples(*burst)
            writes += len(burst)
            lags.append(_lag_ms(burst[-1]))
            t0 = time.perf_counter()
            weng.batch_check(qs)
            mixed_lat.append((time.perf_counter() - t0) * 1000.0)

        # -- forced compaction: overflow the overlay so the compactor
        # must publish a new generation off-path; checks keep running
        # against the old generation until the swap
        burst = _grants(weng.max_overlay_pairs + 512)
        big.store.write_relation_tuples(*burst)
        writes += len(burst)
        lags.append(_lag_ms(burst[-1]))
        lat_during = []
        t_start = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            weng.batch_check(qs)
            lat_during.append((time.perf_counter() - t0) * 1000.0)
            st = weng.projection_stats()
            if (
                st["served_cursor"] == st["log_cursor"]
                and not st["compaction_in_flight"]
            ) or time.perf_counter() - t_start > 180:
                break
        compaction_p99 = float(np.percentile(lat_during, 99))

        st = weng.projection_stats()
        out.update(
            writes_applied=writes,
            write_visible_lag_ms_p50=round(float(np.percentile(lags, 50)), 2),
            write_visible_lag_ms_p99=round(float(np.percentile(lags, 99)), 2),
            check_p99_ms_steady_10m=round(steady_p99, 2),
            check_p99_ms_mixed_10m=round(float(np.percentile(mixed_lat, 99)), 2),
            check_p99_ms_during_compaction=round(compaction_p99, 2),
            compaction_degradation_x=round(
                compaction_p99 / max(steady_p99, 1e-9), 2
            ),
            checks_per_sec_steady_wv=round(steady_cps, 1),
            projection_folds_10m=st["folds"],
            projection_compactions_10m=st["compactions"],
            projection_rebuilds_10m=st["rebuilds"],
            projection_fold_build_s=round(weng.projection_build_s, 3),
            projection_fold_phases=st["build_phases"],
        )
        # the full-build phase decomposition rides along from the primary
        # 10M engine so build-vs-fold cost trends in one report
        beng = state.get("beng")
        if beng is not None:
            out["projection_build_phases"] = (
                beng.projection_stats()["build_phases"]
            )
    finally:
        weng.close()


def _durability(out, state) -> None:
    """ISSUE 12: the warm-standby durability plane at 10M.  Measures the
    replication bootstrap stream (owner capture -> wire roundtrip ->
    replica adopt), the standby's recovery-to-first-verdict after
    adopting (the kill -9 takeover cost floor: projection shipped, no
    rebuild), and the write-path cost of semi-sync acks vs async."""
    import socket as socket_mod
    import threading

    from ketotpu.api.types import RelationTuple
    from ketotpu.engine import checkpoint as ckpt
    from ketotpu.engine.tpu import DeviceCheckEngine
    from ketotpu.server import wire
    from ketotpu.server.workers import ReplicationGate
    from ketotpu.storage.memory import InMemoryTupleStore
    from ketotpu.utils.synth import synth_queries

    big, beng = state["big"], state["beng"]

    # -- bootstrap stream: one frame carries snapshot + scan + tail ------
    t0 = time.perf_counter()
    (snap, cursor, fingerprint, rows, tail, head,
     version) = beng.replication_snapshot()
    capture_s = time.perf_counter() - t0
    arrays = ckpt.snapshot_to_arrays(
        snap, extra={"fingerprint": fingerprint},
        cursor=cursor, head=head, store_version=version,
    )
    wire.pack_tuplecols(arrays, "st", rows)
    wire.pack_changes(arrays, "tl", tail)
    a_sock, b_sock = socket_mod.socketpair()
    sent = {}

    def _send():
        sent["n"] = wire.send_frame(a_sock, {"op": "repl_bootstrap"}, arrays)

    t0 = time.perf_counter()
    tx = threading.Thread(target=_send, daemon=True)
    tx.start()
    rfile = b_sock.makefile("rb")
    meta2, arrays2, nread = wire.recv_frame(rfile)
    tx.join()
    stream_s = time.perf_counter() - t0
    rfile.close()
    a_sock.close()
    b_sock.close()

    # -- replica adopt: store coordinates + device projection ------------
    t0 = time.perf_counter()
    snap2 = ckpt.snapshot_from_arrays(arrays2, {"fingerprint": fingerprint})
    rows2 = wire.unpack_tuplecols(arrays2, "st")
    tail2 = wire.unpack_changes(arrays2, "tl")
    rstore = InMemoryTupleStore()
    rstore.adopt_replica(rows2, head, version, log=tail2, log_start=cursor)
    reng = DeviceCheckEngine(
        rstore, big.manager, frontier=6 * BATCH, arena=12 * BATCH,
        cap=65536, gen_arena=65536, vcap=32768, max_batch=BATCH // 2,
    )
    reng.adopt_snapshot(snap2, cursor=cursor, fingerprint=fingerprint)
    adopt_s = time.perf_counter() - t0
    try:
        # -- recovery-to-first-verdict on the adopted replica ------------
        qs = synth_queries(big, 256, seed=31)
        t0 = time.perf_counter()
        reng.batch_check(qs[:1])
        first_verdict_s = time.perf_counter() - t0
        assert reng.rebuilds == 0, "takeover paid a projection rebuild"
        total_s = capture_s + stream_s + adopt_s
        out.update(
            durability_capture_s=round(capture_s, 2),
            durability_stream_s=round(stream_s, 2),
            durability_stream_mb=round(nread / 1e6, 1),
            durability_stream_mb_s=round(nread / 1e6 / max(stream_s, 1e-9), 1),
            durability_adopt_s=round(adopt_s, 2),
            durability_bootstrap_tuples_per_s=round(
                len(rows2) / max(total_s, 1e-9), 1
            ),
            durability_recovery_first_verdict_s=round(first_verdict_s, 3),
        )
    finally:
        reng.close()

    # -- semi-sync vs async write p99 ------------------------------------
    # an in-process follower acks at a tail-poll cadence; the spread
    # between the two modes is the durability premium a write pays
    def _write_p99(mode: str) -> float:
        store = InMemoryTupleStore()
        gate = ReplicationGate(mode, ack_timeout_ms=2000)
        stop = threading.Event()

        def _acker():
            while not stop.is_set():
                gate.ack(store.log_head)
                time.sleep(0.001)  # durability.poll_ms floor

        t = None
        if mode == "semi-sync":
            gate.ack(0)
            t = threading.Thread(target=_acker, daemon=True)
            t.start()
        lat = []
        for i in range(800):
            tup = RelationTuple.from_string(f"Doc:dura#viewers@w{i}")
            t0 = time.perf_counter()
            store.write_relation_tuples(tup)
            gate.wait_replicated(store.log_head)
            lat.append((time.perf_counter() - t0) * 1000.0)
        stop.set()
        if t is not None:
            t.join(5)
        return float(np.percentile(lat, 99))

    out["durability_write_p99_ms_async"] = round(_write_p99("async"), 3)
    out["durability_write_p99_ms_semi_sync"] = round(
        _write_p99("semi-sync"), 3
    )


if __name__ == "__main__":
    try:
        rc = main()
    except Exception as e:  # noqa: BLE001 — the JSON line always lands
        print(json.dumps({"error": f"{type(e).__name__}: {e}"}))
        rc = 2
    sys.exit(rc)
