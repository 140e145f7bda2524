"""Serving-latency benchmark: concurrent single Check RPCs through the daemon.

The BASELINE metric is "Check RPCs/sec **and p50/p99 latency**" (the
reference measures per-check latency in `internal/check/bench_test.go:
171-183`); bench.py's batch path measures only bulk throughput.  This
drives the real wire path — gRPC `CheckService.Check` against the booted
4-port daemon with the coalescer on — from N closed-loop client threads,
and reports RPS + p50/p99 per-request milliseconds.

Importable (bench.py embeds the numbers in its JSON line) or standalone:

    python bench_serve.py [concurrency] [seconds]
"""

from __future__ import annotations

import json
import sys
import threading
import time
from typing import Dict, List

import numpy as np


def _build_requests(graph, n: int = 4096):
    from ketotpu.api.proto_codec import subject_to_proto
    from ketotpu.proto import check_service_pb2 as cs
    from ketotpu.proto import relation_tuples_pb2 as rts
    from ketotpu.utils.synth import synth_queries

    return [
        cs.CheckRequest(
            tuple=rts.RelationTuple(
                namespace=q.namespace,
                object=q.object,
                relation=q.relation,
                subject=subject_to_proto(q.subject),
            )
        )
        for q in synth_queries(graph, n, seed=5)
    ]


def _hammer(
    target: str, requests, *, concurrency: int, duration: float
) -> Dict[str, float]:
    """Closed-loop client threads firing single Checks at ``target``;
    returns rps / p50 / p99 / errors / elapsed."""
    import grpc

    from ketotpu.proto.services import CheckServiceStub

    lat: List[List[float]] = [[] for _ in range(concurrency)]
    stop = threading.Event()
    errors = [0]

    def client(idx: int) -> None:
        rng = np.random.default_rng(idx)
        with grpc.insecure_channel(target) as ch:
            stub = CheckServiceStub(ch)
            my = lat[idx]
            n_req = len(requests)
            while not stop.is_set():
                r = requests[int(rng.integers(n_req))]
                t0 = time.perf_counter()
                try:
                    stub.Check(r)
                except grpc.RpcError:
                    errors[0] += 1
                    continue
                my.append(time.perf_counter() - t0)

    threads = [
        threading.Thread(target=client, args=(i,), daemon=True)
        for i in range(concurrency)
    ]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(duration)
    stop.set()
    for t in threads:
        t.join(timeout=10.0)
    elapsed = time.perf_counter() - t_start
    all_lat = np.array([x for sub in lat for x in sub])
    done = len(all_lat)
    return {
        "rps": round(done / elapsed, 1),
        "p50_ms": round(float(np.percentile(all_lat, 50)) * 1000, 2)
        if done else -1.0,
        "p99_ms": round(float(np.percentile(all_lat, 99)) * 1000, 2)
        if done else -1.0,
        "seconds": round(elapsed, 1),
        "errors": errors[0],
    }


def run_serving_bench(
    graph=None,
    *,
    concurrency: int = 64,
    duration: float = 10.0,
    coalesce_ms: float = 2.0,
    frontier: int = 16384,
    arena: int = 65536,
    observability=None,
) -> Dict[str, float]:
    """Boot the daemon on the given synth graph and hammer it with single
    Checks; returns {"serve_rps", "serve_p50_ms", "serve_p99_ms",
    "serve_concurrency", ...}.  ``observability`` overrides that config
    section (the trace-overhead leg flips tracing/shadow on and off)."""
    import grpc

    from ketotpu.driver import Provider, Registry
    from ketotpu.proto.services import CheckServiceStub
    from ketotpu.server import serve_all
    from ketotpu.utils.synth import build_synth

    if graph is None:
        graph = build_synth(
            n_users=2000, n_groups=100, n_folders=2000, n_docs=20000, seed=0
        )
    cfg = Provider(
        {
            "serve": {
                n: {"host": "127.0.0.1", "port": 0}
                for n in ("read", "write", "metrics", "opl")
            },
            "engine": {
                "kind": "tpu",
                "frontier": frontier,
                "arena": arena,
                "max_batch": frontier,
                "coalesce_ms": coalesce_ms,
            },
            # one INFO access line per hammered request would swamp stderr
            "log": {"request_log": False},
            **({"observability": observability} if observability else {}),
        }
    )
    reg = Registry(
        cfg, store=graph.store, namespace_manager=graph.manager
    ).init()
    srv = serve_all(reg)
    try:
        host, port = srv.addresses["read"]
        target = f"{host}:{port}"

        # pre-built requests: client-side encode cost out of the loop
        requests = _build_requests(graph)

        # warmup: compile every level shape the coalescer will hit.  A
        # warm-up Check can outlive limit.request_timeout_ms while XLA is
        # still compiling the wave program; the compile keeps running on
        # the wave thread and lands in the in-process cache, so a
        # DEADLINE_EXCEEDED here is retried rather than failing the leg
        with grpc.insecure_channel(target) as ch:
            stub = CheckServiceStub(ch)
            for r in requests[:4]:
                for attempt in range(10):
                    try:
                        stub.Check(r)
                        break
                    except grpc.RpcError as e:
                        if (
                            e.code() != grpc.StatusCode.DEADLINE_EXCEEDED
                            or attempt == 9
                        ):
                            raise

        from ketotpu import compilewatch

        compiles_before = compilewatch.get().compiles_total
        h = _hammer(target, requests, concurrency=concurrency, duration=duration)
        # wave-occupancy picture next to the RPS number: how full the
        # coalescing windows ran and how long admitted requests waited —
        # the wave ledger (ketotpu/waveledger.py) records this per wave,
        # stats() aggregates the ring
        wstats = reg.wave_ledger().stats()
        extra: Dict[str, float] = {}
        sh = reg.shadow()
        if sh is not None:
            # drain the replay queue so the counters below are final —
            # the divergence gate must read a settled number
            sh.drain(timeout=30.0)
            m = reg.metrics()
            extra["shadow_checks_total"] = int(
                m.get_counter("keto_shadow_checks_total")
            )
            extra["shadow_divergence_total"] = int(
                m.get_counter("keto_shadow_divergence_total")
            )
        ts = reg.trace_store()
        if ts is not None:
            extra["trace_promoted"] = int(ts.stats()["promotions"])
        wd = reg.watchdog()
        if wd is not None:
            # settle one final rule pass so incidents from the hammer's
            # tail are counted before the gate reads the number
            wd.tick()
            extra["fleet_incidents"] = int(
                wd.stats()["incidents_filed"]
            )
        slo = reg.slo()
        if slo is not None:
            slo.sample()
            extra["fleet_burn_fast"] = float(slo.max_burn("fast"))
        return {
            **extra,
            "serve_rps": h["rps"],
            "serve_p50_ms": h["p50_ms"],
            "serve_p99_ms": h["p99_ms"],
            "serve_concurrency": concurrency,
            "serve_seconds": h["seconds"],
            "serve_errors": h["errors"],
            "serve_coalesced_waves": getattr(
                reg.check_engine(), "waves", 0
            ),
            "serve_wave_size_mean": wstats.get("wave_size_mean", 0),
            "serve_wave_size_p50": wstats.get("wave_size_p50", 0),
            "serve_wave_size_p95": wstats.get("wave_size_p95", 0),
            "serve_window_wait_ms_p50": wstats.get("window_wait_ms_p50", 0),
            "serve_hammer_compiles": (
                compilewatch.get().compiles_total - compiles_before
            ),
            "serve_stage_ms": _scrape_means(
                reg.metrics(), "keto_rpc_stage_seconds", ("op", "stage")
            ),
            "serve_engine_phase_ms": _scrape_means(
                reg.metrics(), "keto_engine_phase_seconds", ("phase",)
            ),
        }
    finally:
        srv.stop(grace=2.0)


def _hammer_shared(
    target: str, requests, *, concurrency: int, duration: float,
    channels: int = 64,
) -> Dict[str, float]:
    """``_hammer`` with a bounded shared channel pool: the north-star legs
    run thousands of closed-loop clients, and one gRPC channel per client
    would exhaust file descriptors long before the engine saturates."""
    import grpc

    from ketotpu.proto.services import CheckServiceStub

    pool = [
        grpc.insecure_channel(target)
        for _ in range(max(1, min(channels, concurrency)))
    ]
    stubs = [CheckServiceStub(ch) for ch in pool]
    lat: List[List[float]] = [[] for _ in range(concurrency)]
    stop = threading.Event()
    errors = [0]

    def client(idx: int) -> None:
        rng = np.random.default_rng(idx)
        stub = stubs[idx % len(stubs)]
        my = lat[idx]
        n_req = len(requests)
        while not stop.is_set():
            r = requests[int(rng.integers(n_req))]
            t0 = time.perf_counter()
            try:
                stub.Check(r)
            except grpc.RpcError:
                errors[0] += 1
                continue
            my.append(time.perf_counter() - t0)

    threads = [
        threading.Thread(target=client, args=(i,), daemon=True)
        for i in range(concurrency)
    ]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(duration)
    stop.set()
    for t in threads:
        t.join(timeout=10.0)
    elapsed = time.perf_counter() - t_start
    for ch in pool:
        ch.close()
    all_lat = np.array([x for sub in lat for x in sub])
    done = len(all_lat)
    return {
        "rps": round(done / elapsed, 1),
        "p50_ms": round(float(np.percentile(all_lat, 50)) * 1000, 2)
        if done else -1.0,
        "p99_ms": round(float(np.percentile(all_lat, 99)) * 1000, 2)
        if done else -1.0,
        "seconds": round(elapsed, 1),
        "errors": errors[0],
    }


def _hammer_stream_lane(
    read_url: str, session_addr, requests, *, sessions: int,
    block_rows: int, duration: float,
) -> Dict[str, float]:
    """Closed-loop streaming sessions over the raw framed lane
    (server/session.py): each session thread pumps ``block_rows``-row
    columnar blocks through its credit window and harvests verdict
    blocks out-of-order.  Latency is per BLOCK (submit -> verdicts);
    ``checks_per_sec`` counts rows."""
    from ketotpu.sdk import KetoClient

    lat: List[List[float]] = [[] for _ in range(sessions)]
    rows_done = [0] * sessions
    stop = threading.Event()
    errors = [0]
    blocks = [
        requests[i: i + block_rows]
        for i in range(0, len(requests) - block_rows + 1, block_rows)
    ] or [requests]

    def session_client(idx: int) -> None:
        rng = np.random.default_rng(idx)
        client = KetoClient(read_url, timeout=120.0)
        my = lat[idx]
        try:
            with client.check_session(session_addr) as sess:
                sent: Dict[int, float] = {}
                while not stop.is_set():
                    block = blocks[int(rng.integers(len(blocks)))]
                    seq = sess.submit(block)
                    sent[seq] = time.perf_counter()
                    # harvest whatever the credit-window receive loop
                    # already answered (out-of-order completion)
                    for sq in list(sess._results):
                        verdicts, errs = sess._results.pop(sq)
                        t0 = sent.pop(sq, None)
                        if verdicts is None or errs:
                            errors[0] += 1
                            continue
                        if t0 is not None:
                            my.append(time.perf_counter() - t0)
                        rows_done[idx] += len(verdicts)
                for sq, verdicts, errs in sess.results():
                    t0 = sent.pop(sq, None)
                    if verdicts is None or errs:
                        errors[0] += 1
                        continue
                    if t0 is not None:
                        my.append(time.perf_counter() - t0)
                    rows_done[idx] += len(verdicts)
        except Exception:  # noqa: BLE001 - a dead session is an error count
            errors[0] += 1

    threads = [
        threading.Thread(target=session_client, args=(i,), daemon=True)
        for i in range(sessions)
    ]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(duration)
    stop.set()
    for t in threads:
        t.join(timeout=30.0)
    elapsed = time.perf_counter() - t_start
    all_lat = np.array([x for sub in lat for x in sub])
    done = len(all_lat)
    return {
        "rps": round(done / elapsed, 1),
        "checks_per_sec": round(sum(rows_done) / elapsed, 1),
        "p50_ms": round(float(np.percentile(all_lat, 50)) * 1000, 2)
        if done else -1.0,
        "p99_ms": round(float(np.percentile(all_lat, 99)) * 1000, 2)
        if done else -1.0,
        "seconds": round(elapsed, 1),
        "blocks": done,
        "sessions": sessions,
        "errors": errors[0],
    }


def _warm_shared_blocking(
    target: str, requests, *, concurrency: int, rounds: int = 1,
    channels: int = 64,
) -> None:
    """Blocking warm burst for the single-Check legs: ``concurrency``
    clients each complete ``rounds`` full round trips with no time box,
    so a burst that coalesces into a fresh pow2 wave bucket waits out
    the resulting fused compile instead of leaving it in flight for the
    timed pass (the time-boxed warm returns after N seconds regardless;
    a ~90-120s XLA:CPU fused compile then lands inside the gate)."""
    import grpc

    from ketotpu.proto.services import CheckServiceStub

    pool = [
        grpc.insecure_channel(target)
        for _ in range(max(1, min(channels, concurrency)))
    ]
    stubs = [CheckServiceStub(ch) for ch in pool]

    def one(idx: int) -> None:
        rng = np.random.default_rng(3000 + idx)
        stub = stubs[idx % len(stubs)]
        n_req = len(requests)
        for _ in range(rounds):
            try:
                stub.Check(requests[int(rng.integers(n_req))])
            except grpc.RpcError:
                pass

    threads = [
        threading.Thread(target=one, args=(i,), daemon=True)
        for i in range(concurrency)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600.0)
    for ch in pool:
        ch.close()


def _warm_stream_lane(
    read_url: str, session_addr, requests, *, sessions: int,
    block_rows: int, rounds: int = 3, sweep: bool = True,
) -> None:
    """Verdict-BLOCKING warm for the streaming legs: every session pumps
    a full credit window of blocks and waits for EVERY verdict before
    the next round.  The merged-wave shapes the stream path produces
    (sessions x credits blocks coalescing into one device wave) are
    fresh jit buckets the batch legs never compile, and on XLA:CPU a
    fused-wave compile runs 90s+ — a time-boxed warm pass returns with
    the compile still in flight and the timed window then completes
    zero blocks.  Blocking on verdicts makes warm exactly as slow as
    the compiles it exists to absorb."""
    from ketotpu.sdk import KetoClient

    blocks = [
        requests[i: i + block_rows]
        for i in range(0, len(requests) - block_rows + 1, block_rows)
    ] or [requests]

    def one(idx: int) -> None:
        rng = np.random.default_rng(1000 + idx)
        client = KetoClient(read_url, timeout=600.0)
        try:
            with client.check_session(session_addr) as sess:
                # small windows first so partially-merged wave buckets
                # (1-2 blocks) compile too, then full credit windows
                credits = max(1, sess.credits)
                windows = [1, 2] + [credits] * rounds
                for win in windows:
                    seqs = [
                        sess.submit(
                            blocks[int(rng.integers(len(blocks)))]
                        )
                        for _ in range(win)
                    ]
                    for sq in seqs:
                        sess.wait(sq)
                if not sweep:
                    return
                # cache-priming sweep: every block exactly once (this
                # session's share), so the timed pass measures the
                # serving shell over a hot working set — on XLA:CPU a
                # cold fused wave runs ~1s+, and whether the timed
                # window catches hot or cold rows is otherwise a
                # coin flip that whipsaws the stream-vs-batch ratio
                share = blocks[idx::max(1, sessions)]
                for i in range(0, len(share), credits):
                    seqs = [
                        sess.submit(b) for b in share[i: i + credits]
                    ]
                    for sq in seqs:
                        sess.wait(sq)
        except Exception:  # noqa: BLE001 - warm is best-effort
            pass

    threads = [
        threading.Thread(target=one, args=(i,), daemon=True)
        for i in range(sessions)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600.0)


def _warm_grpc_batch(
    target: str, requests, *, concurrency: int, block_rows: int,
    rounds: int = 3,
) -> None:
    """Blocking warm for the per-connection BatchCheck baseline: each
    client completes ``rounds`` full round trips (no time box), so any
    fresh wave-bucket compile the baseline's own coalescing produces is
    paid before its timed window — a stalled baseline would flatter the
    stream-vs-batch ratio."""
    import grpc

    from ketotpu.api.proto_codec import tuple_to_proto
    from ketotpu.proto import batch_service_pb2 as bs
    from ketotpu.proto.services import CheckServiceStub

    protos = [tuple_to_proto(t) for t in requests]
    reqs = [
        bs.BatchCheckRequest(tuples=protos[i: i + block_rows])
        for i in range(0, len(protos) - block_rows + 1, block_rows)
    ] or [bs.BatchCheckRequest(tuples=protos)]
    pool = [grpc.insecure_channel(target)
            for _ in range(max(1, min(8, concurrency)))]
    stubs = [CheckServiceStub(ch) for ch in pool]

    def one(idx: int) -> None:
        rng = np.random.default_rng(2000 + idx)
        stub = stubs[idx % len(stubs)]
        for _ in range(rounds):
            try:
                stub.BatchCheck(reqs[int(rng.integers(len(reqs)))])
            except grpc.RpcError:
                pass

    threads = [
        threading.Thread(target=one, args=(i,), daemon=True)
        for i in range(concurrency)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600.0)
    for ch in pool:
        ch.close()


def _hammer_grpc_batch(
    target: str, requests, *, concurrency: int, block_rows: int,
    duration: float, channels: int = 32,
) -> Dict[str, float]:
    """Closed-loop gRPC BatchCheck clients at the SAME block size as the
    streaming leg — the per-RPC baseline the session lane must beat
    (every request re-enters admission, proto decode, and response
    marshalling; a session pays those once)."""
    import grpc

    from ketotpu.api.proto_codec import tuple_to_proto
    from ketotpu.proto import batch_service_pb2 as bs
    from ketotpu.proto.services import CheckServiceStub

    protos = [tuple_to_proto(t) for t in requests]
    reqs = [
        bs.BatchCheckRequest(tuples=protos[i: i + block_rows])
        for i in range(0, len(protos) - block_rows + 1, block_rows)
    ] or [bs.BatchCheckRequest(tuples=protos)]
    pool = [
        grpc.insecure_channel(target)
        for _ in range(max(1, min(channels, concurrency)))
    ]
    stubs = [CheckServiceStub(ch) for ch in pool]
    lat: List[List[float]] = [[] for _ in range(concurrency)]
    rows_done = [0] * concurrency
    stop = threading.Event()
    errors = [0]

    def client(idx: int) -> None:
        rng = np.random.default_rng(idx)
        stub = stubs[idx % len(stubs)]
        my = lat[idx]
        while not stop.is_set():
            r = reqs[int(rng.integers(len(reqs)))]
            t0 = time.perf_counter()
            try:
                resp = stub.BatchCheck(r)
            except grpc.RpcError:
                errors[0] += 1
                continue
            my.append(time.perf_counter() - t0)
            rows_done[idx] += len(resp.results)

    threads = [
        threading.Thread(target=client, args=(i,), daemon=True)
        for i in range(concurrency)
    ]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(duration)
    stop.set()
    for t in threads:
        t.join(timeout=30.0)
    elapsed = time.perf_counter() - t_start
    for ch in pool:
        ch.close()
    all_lat = np.array([x for sub in lat for x in sub])
    done = len(all_lat)
    return {
        "rps": round(done / elapsed, 1),
        "checks_per_sec": round(sum(rows_done) / elapsed, 1),
        "p50_ms": round(float(np.percentile(all_lat, 50)) * 1000, 2)
        if done else -1.0,
        "p99_ms": round(float(np.percentile(all_lat, 99)) * 1000, 2)
        if done else -1.0,
        "seconds": round(elapsed, 1),
        "errors": errors[0],
    }


def run_northstar_bench(
    graph=None,
    *,
    concurrencies=(1024, 4096),
    duration: float = 8.0,
    frontier: int = 16384,
    arena: int = 65536,
    fused_retry_lanes: int = 1,
    max_wave: int = 0,
) -> Dict[str, float]:
    """North-star serving leg for the fused tiered dispatch
    (engine/fused.py): boot the daemon with ``engine.fused_dispatch`` ON,
    hammer single Checks on the BASELINE mixed-general workload (30%
    AND/NOT ``edit`` permits, subject-set slice) at each concurrency, and
    report RPS + p50/p99 per point.  Three gates ride along:

    * **zero divergence** — 512 served verdicts vs the host oracle at
      the same state must agree exactly (``northstar_divergence == 0``);
    * **steady-state compiles** — the timed hammers run after a warm
      pass at the same shapes under ``bench._steady``; any XLA compile
      inside them lands in ``steady_state_compiles`` (process exit 3);
    * **single D2H per wave** — the wave ledger's fused deltas must show
      ``fused_waves == fused_d2h_fetches`` (``northstar_single_d2h``).
    """
    import grpc

    from ketotpu.api.proto_codec import subject_to_proto
    from ketotpu.driver import Provider, Registry
    from ketotpu.proto import check_service_pb2 as cs
    from ketotpu.proto import relation_tuples_pb2 as rts
    from ketotpu.proto.services import CheckServiceStub
    from ketotpu.server import serve_all
    from ketotpu.utils.synth import build_synth, synth_queries_mixed

    if graph is None:
        graph = build_synth(
            n_users=2000, n_groups=100, n_folders=2000, n_docs=20000, seed=0
        )
    cfg = Provider(
        {
            "serve": {
                n: {"host": "127.0.0.1", "port": 0}
                for n in ("read", "write", "metrics", "opl")
            },
            "engine": {
                "kind": "tpu",
                "fused_dispatch": True,
                "fused_retry_lanes": int(fused_retry_lanes),
                "frontier": frontier,
                "arena": arena,
                # max_wave caps coalesced wave rows (CPU legs: fused
                # wave exec is super-linear in Q on one core — a
                # Q=512 general wave runs seconds while Q<=256 stays
                # interactive); real chips take full-frontier waves
                "max_batch": int(max_wave) or frontier,
                "coalesce_ms": 2,
            },
            # the 4096-client leg must shed nothing: admission caps would
            # measure the limiter, not the fused engine — and the first
            # fused compile takes minutes on XLA:CPU, so the per-request
            # deadline must not fail the warm-up checks
            "limit": {"max_inflight": 0, "request_timeout_ms": 0},
            # streaming leg: enough dispatch workers that every session's
            # full credit window can sit in the coalescer at once —
            # blocks from concurrent sessions pack into shared waves
            # (the default 4-worker pool caps global in-flight blocks
            # and starves the wave window)
            "session": {"dispatch_workers": 64, "max_sessions": 1024},
            "log": {"request_log": False},
        }
    )
    reg = Registry(
        cfg, store=graph.store, namespace_manager=graph.manager
    ).init()
    srv = serve_all(reg)
    try:
        host, port = srv.addresses["read"]
        target = f"{host}:{port}"
        requests = [
            cs.CheckRequest(
                tuple=rts.RelationTuple(
                    namespace=q.namespace,
                    object=q.object,
                    relation=q.relation,
                    subject=subject_to_proto(q.subject),
                )
            )
            for q in synth_queries_mixed(graph, 4096, seed=5)
        ]
        # zero-divergence gate: served verdicts vs the host oracle.
        # Runs FIRST so the expensive fused compiles happen in-process,
        # not under a gRPC warm-up call.
        eng = reg.check_engine()
        inner = getattr(eng, "inner", eng)
        sample = synth_queries_mixed(graph, 512, seed=9)
        served = eng.batch_check(sample)
        want = [inner.oracle.check_is_member(q) for q in sample]
        divergence = sum(1 for g, w in zip(served, want) if g != w)

        with grpc.insecure_channel(target) as ch:
            stub = CheckServiceStub(ch)
            for r in requests[:8]:
                stub.Check(r)

        from bench import _steady

        out: Dict[str, float] = {"northstar_divergence": divergence}
        gate: Dict = {}
        ledger = reg.wave_ledger()
        w0 = ledger.stats() if ledger is not None else {}
        for conc in concurrencies:
            # warm pass at THIS concurrency's exact coalescer wave
            # buckets, unmeasured; then the timed pass under the gate
            _hammer_shared(
                target, requests, concurrency=conc,
                duration=max(2.0, duration * 0.4),
            )
            # the time-boxed warm can leave a fused wave-bucket compile
            # in flight; burst-and-block until a full round is
            # compile-free before opening the gate
            from ketotpu import compilewatch

            cwatch = compilewatch.get()
            for _ in range(5):
                before_c = cwatch.compiles_total
                _warm_shared_blocking(
                    target, requests, concurrency=conc,
                )
                if cwatch.compiles_total == before_c:
                    break
            with _steady(gate, f"serve_northstar_{conc}"):
                h = _hammer_shared(
                    target, requests, concurrency=conc, duration=duration
                )
            out[f"northstar_{conc}_rps"] = h["rps"]
            out[f"northstar_{conc}_p50_ms"] = h["p50_ms"]
            out[f"northstar_{conc}_p99_ms"] = h["p99_ms"]
            out[f"northstar_{conc}_errors"] = h["errors"]

        # -- streaming leg (ISSUE 19): persistent check sessions over the
        # raw framed lane vs per-RPC BatchCheck at the same block size.
        # A session is admitted ONCE and pays proto/admission once, so
        # its row throughput must beat the per-request batch path.
        session_addr = srv.addresses.get("session")
        if session_addr is not None:
            read_url = f"http://{host}:{port}"
            block_rows = 64
            stream_queries = synth_queries_mixed(graph, 4096, seed=7)

            # zero-divergence oracle probe on the STREAM path: one
            # session, one block, verdicts vs the host oracle
            from ketotpu.sdk import KetoClient

            probe_client = KetoClient(read_url, timeout=300.0)
            with probe_client.check_session(session_addr) as psess:
                sq = psess.submit(sample)
                verdicts, errs = psess.wait(sq)
            stream_div = (
                len(sample) if verdicts is None or errs
                else sum(1 for g, w in zip(verdicts, want) if g != w)
            )
            out["serve_stream_divergence"] = stream_div

            w_before = ledger.stats() if ledger is not None else {}
            blocks_total = 0
            for conc in concurrencies:
                # concurrency == in-flight ROWS: each session holds
                # credits x block_rows rows in flight
                sessions = max(1, conc // (block_rows * 8))
                # which pow2 wave bucket a credit-window burst merges
                # into is timing-dependent, and on XLA:CPU each fresh
                # bucket is a ~90s fused compile — so warm until a full
                # round adds ZERO compiles rather than a fixed count
                from ketotpu import compilewatch

                cwatch = compilewatch.get()
                _warm_stream_lane(
                    read_url, session_addr, stream_queries,
                    sessions=sessions, block_rows=block_rows,
                )
                for _ in range(5):
                    before_c = cwatch.compiles_total
                    _warm_stream_lane(
                        read_url, session_addr, stream_queries,
                        sessions=sessions, block_rows=block_rows,
                        rounds=1, sweep=False,
                    )
                    if cwatch.compiles_total == before_c:
                        break
                with _steady(gate, f"serve_stream_{conc}"):
                    hs = _hammer_stream_lane(
                        read_url, session_addr, stream_queries,
                        sessions=sessions, block_rows=block_rows,
                        duration=max(duration, 15.0),
                    )
                blocks_total += hs["blocks"]
                out[f"serve_stream_{conc}_rps"] = hs["rps"]
                out[f"serve_stream_{conc}_checks_per_sec"] = (
                    hs["checks_per_sec"]
                )
                out[f"serve_stream_{conc}_p50_ms"] = hs["p50_ms"]
                out[f"serve_stream_{conc}_p99_ms"] = hs["p99_ms"]
                out[f"serve_stream_{conc}_sessions"] = sessions
                out[f"serve_stream_{conc}_errors"] = hs["errors"]
            if ledger is not None:
                waves = (
                    ledger.stats().get("waves_recorded", 0)
                    - w_before.get("waves_recorded", 0)
                )
                out["serve_stream_blocks_per_wave"] = (
                    round(blocks_total / waves, 2) if waves else 0.0
                )

            # per-CONNECTION baseline: the same number of clients, each
            # a request-response BatchCheck loop at the same block size.
            # A unary client holds ONE block in flight; a session holds
            # a credit window's worth — that pipelining (plus paying
            # admission/decode once) is the row-throughput the gate
            # demands
            top = max(concurrencies)
            baseline_conc = max(1, top // (block_rows * 8))
            _warm_grpc_batch(
                target, stream_queries,
                concurrency=baseline_conc, block_rows=block_rows,
            )
            for _ in range(5):
                before_c = cwatch.compiles_total
                _warm_grpc_batch(
                    target, stream_queries,
                    concurrency=baseline_conc, block_rows=block_rows,
                    rounds=1,
                )
                if cwatch.compiles_total == before_c:
                    break
            hb = _hammer_grpc_batch(
                target, stream_queries,
                concurrency=baseline_conc,
                block_rows=block_rows, duration=max(duration, 15.0),
            )
            out["serve_stream_batch_checks_per_sec"] = hb["checks_per_sec"]
            out["serve_stream_batch_rps"] = hb["rps"]
            stream_cps = out[f"serve_stream_{top}_checks_per_sec"]
            out["serve_stream_vs_batch"] = (
                round(stream_cps / hb["checks_per_sec"], 3)
                if hb["checks_per_sec"] > 0 else 0.0
            )
        steady = gate.get("steady_state_compiles", {})
        out["northstar_steady_state_compiles"] = int(sum(steady.values()))
        if steady:
            out["steady_state_compiles"] = steady
        if ledger is not None:
            ws = ledger.stats()
            out["northstar_wave_device_ms_p50"] = ws.get("device_ms_p50", 0)
            out["northstar_wave_size_p95"] = ws.get("wave_size_p95", 0)
            fw = ws.get("fused_waves", 0) - w0.get("fused_waves", 0)
            fd = (ws.get("fused_d2h_fetches", 0)
                  - w0.get("fused_d2h_fetches", 0))
            out["northstar_fused_waves"] = fw
            out["northstar_fused_d2h_fetches"] = fd
            out["northstar_single_d2h"] = bool(fw > 0 and fw == fd)
            out["northstar_fused_tier_rows"] = ws.get("fused_tier_rows", {})
        return out
    finally:
        srv.stop(grace=2.0)


def run_trace_overhead_bench(
    graph=None,
    *,
    concurrency: int = 64,
    duration: float = 6.0,
    **kw,
) -> Dict[str, float]:
    """Cost of the request-anatomy observatory: the single-Check hammer
    with tail-sampled tracing + an aggressive shadow sampler (1/50) ON,
    then with ``observability.trace.enabled: false`` and the shadow plane
    off.  Publishes ``serve_trace_overhead_pct`` (the acceptance gate is
    <= 5%) and the shadow plane's settled divergence counter (must be 0
    against the synth graph — every tier agrees with the oracle)."""
    from ketotpu.utils.synth import build_synth

    if graph is None:
        graph = build_synth(
            n_users=2000, n_groups=100, n_folders=2000, n_docs=20000, seed=0
        )
    dark = {
        "trace": {"enabled": False},
        "shadow": {"enabled": False},
    }
    lit = {
        "trace": {"enabled": True},
        "shadow": {"enabled": True, "sample_rate": 50},
    }
    # off / on / off: the first off leg absorbs the one-time in-process
    # XLA compiles (both measured-against legs then run warm), and the
    # two off legs average out scheduler noise — a single-leg A/B here
    # systematically billed the compile warm-up to whichever side ran
    # first
    off1 = run_serving_bench(
        graph, concurrency=concurrency, duration=duration,
        observability=dark, **kw,
    )
    # tail-based sampling promotes the TAIL: calibrate the slow threshold
    # to the measured baseline p99 so the on-leg promotes ~1% of requests
    # (the intended regime) — the default 25ms is a production-latency
    # number that an emulated-CPU bench sits entirely above, which would
    # turn tail sampling into promote-everything
    lit["trace"]["slow_ms"] = max(
        25.0, 0.9 * float(off1.get("serve_p99_ms", 0.0))
    )
    on = run_serving_bench(
        graph, concurrency=concurrency, duration=duration,
        observability=lit, **kw,
    )
    off2 = run_serving_bench(
        graph, concurrency=concurrency, duration=duration,
        observability=dark, **kw,
    )
    rps_on = float(on.get("serve_rps", 0.0))
    rps_off = (
        float(off1.get("serve_rps", 0.0))
        + float(off2.get("serve_rps", 0.0))
    ) / 2.0
    p99_off = max(
        float(off1.get("serve_p99_ms", -1.0)),
        float(off2.get("serve_p99_ms", -1.0)),
    )
    pct = (
        round((rps_off - rps_on) / rps_off * 100.0, 2)
        if rps_off > 0 else 0.0
    )
    return {
        "serve_trace_overhead_pct": pct,
        "serve_rps_trace_on": rps_on,
        "serve_rps_trace_off": rps_off,
        "serve_p99_ms_trace_on": on.get("serve_p99_ms", -1.0),
        "serve_p99_ms_trace_off": p99_off,
        "shadow_checks_total": int(on.get("shadow_checks_total", 0)),
        "shadow_divergence_total": int(
            on.get("shadow_divergence_total", 0)
        ),
        "trace_promoted": int(on.get("trace_promoted", 0)),
    }


def run_fleet_overhead_bench(
    graph=None,
    *,
    concurrency: int = 64,
    duration: float = 6.0,
    **kw,
) -> Dict[str, float]:
    """Cost of the fleet health plane: the single-Check hammer with the
    SLO burn-rate engine + regression watchdog ON (1 s rule cadence, far
    hotter than the production 5 s default) against both OFF, same
    off/on/off protocol as the trace-overhead leg.  Publishes
    ``serve_slo_overhead_pct`` (acceptance gate <= 5%) and the lit leg's
    settled incident count — a clean steady-state run must file ZERO
    incidents (an after-warm compile, divergence, or burn alarm here is
    a real regression, not bench noise)."""
    from ketotpu.utils.synth import build_synth

    if graph is None:
        graph = build_synth(
            n_users=2000, n_groups=100, n_folders=2000, n_docs=20000, seed=0
        )
    dark = {
        "slo": {"enabled": False},
        "watchdog": {"enabled": False},
    }
    off1 = run_serving_bench(
        graph, concurrency=concurrency, duration=duration,
        observability=dark, **kw,
    )
    # calibrate the lit leg's latency target from the measured dark leg
    # (same idiom as the trace leg's slow_ms): a clean run is in-SLO by
    # construction whatever the box's speed, while a real regression
    # between legs — drift, divergence, an after-warm compile, or a
    # latency cliff past 2x the dark p99 — still files an incident
    target_ms = max(25.0, 2.0 * float(off1.get("serve_p99_ms", 0.0)))
    lit = {
        "slo": {"enabled": True, "latency_target_ms": target_ms},
        "watchdog": {"enabled": True, "interval_s": 1.0},
    }
    on = run_serving_bench(
        graph, concurrency=concurrency, duration=duration,
        observability=lit, **kw,
    )
    off2 = run_serving_bench(
        graph, concurrency=concurrency, duration=duration,
        observability=dark, **kw,
    )
    rps_on = float(on.get("serve_rps", 0.0))
    rps_off = (
        float(off1.get("serve_rps", 0.0))
        + float(off2.get("serve_rps", 0.0))
    ) / 2.0
    pct = (
        round((rps_off - rps_on) / rps_off * 100.0, 2)
        if rps_off > 0 else 0.0
    )
    return {
        "serve_slo_overhead_pct": pct,
        "serve_rps_fleet_on": rps_on,
        "serve_rps_fleet_off": rps_off,
        "serve_p99_ms_fleet_on": on.get("serve_p99_ms", -1.0),
        "fleet_latency_target_ms": round(target_ms, 2),
        "fleet_incidents": int(on.get("fleet_incidents", 0)),
        "fleet_burn_fast": float(on.get("fleet_burn_fast", 0.0)),
        "serve_errors_fleet_on": int(on.get("serve_errors", 0)),
    }


def _hammer_rest_batch(
    host: str, port: int, bodies: List[bytes], *,
    concurrency: int, duration: float, batch_size: int,
) -> Dict[str, float]:
    """Closed-loop clients POSTing pre-encoded batch bodies over
    keep-alive REST connections; returns rps / checks_per_sec / p50 /
    p99 / errors."""
    import http.client

    lat: List[List[float]] = [[] for _ in range(concurrency)]
    stop = threading.Event()
    errors = [0]

    def client(idx: int) -> None:
        rng = np.random.default_rng(idx)
        conn = http.client.HTTPConnection(host, port, timeout=120.0)
        my = lat[idx]
        n_bodies = len(bodies)
        try:
            while not stop.is_set():
                body = bodies[int(rng.integers(n_bodies))]
                t0 = time.perf_counter()
                try:
                    conn.request(
                        "POST", "/relation-tuples/batch/check", body,
                        {"Content-Type": "application/json"},
                    )
                    resp = conn.getresponse()
                    resp.read()
                    if resp.status != 200:
                        errors[0] += 1
                        continue
                except (OSError, http.client.HTTPException):
                    errors[0] += 1
                    conn.close()
                    conn = http.client.HTTPConnection(
                        host, port, timeout=120.0
                    )
                    continue
                my.append(time.perf_counter() - t0)
        finally:
            conn.close()

    threads = [
        threading.Thread(target=client, args=(i,), daemon=True)
        for i in range(concurrency)
    ]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(duration)
    stop.set()
    for t in threads:
        t.join(timeout=15.0)
    elapsed = time.perf_counter() - t_start
    all_lat = np.array([x for sub in lat for x in sub])
    done = len(all_lat)
    return {
        "rps": round(done / elapsed, 1),
        "checks_per_sec": round(done * batch_size / elapsed, 1),
        "p50_ms": round(float(np.percentile(all_lat, 50)) * 1000, 2)
        if done else -1.0,
        "p99_ms": round(float(np.percentile(all_lat, 99)) * 1000, 2)
        if done else -1.0,
        "errors": errors[0],
    }


def run_batch_bench(
    graph=None,
    *,
    concurrency: int = 512,
    duration: float = 6.0,
    batch_sizes=(64, 512, 4096),
    coalesce_ms: float = 2.0,
    frontier: int = 16384,
    arena: int = 65536,
) -> Dict[str, float]:
    """Batch front door (ISSUE 7): closed-loop clients POSTing
    /relation-tuples/batch/check at high concurrency — the async event
    loop holds the sockets, so 512 connections cost file descriptors,
    not threads.  Publishes per-batch-size RPS + checks/sec + latency,
    a verdict-divergence count against the single-check endpoint, and
    the wave-occupancy picture."""
    import urllib.request

    from ketotpu.driver import Provider, Registry
    from ketotpu.server import serve_all
    from ketotpu.utils.synth import build_synth, synth_queries

    if graph is None:
        graph = build_synth(
            n_users=2000, n_groups=100, n_folders=2000, n_docs=20000, seed=0
        )
    cfg = Provider(
        {
            "serve": {
                n: {"host": "127.0.0.1", "port": 0}
                for n in ("read", "write", "metrics", "opl")
            },
            "engine": {
                "kind": "tpu",
                "frontier": frontier,
                "arena": arena,
                "max_batch": frontier,
                "coalesce_ms": coalesce_ms,
            },
            # the bench measures throughput, not shedding: admission off
            # (the admission interplay has its own tests)
            "limit": {"max_inflight": 0},
            "log": {"request_log": False},
        }
    )
    reg = Registry(
        cfg, store=graph.store, namespace_manager=graph.manager
    ).init()
    srv = serve_all(reg)
    try:
        host, port = srv.addresses["read"]
        queries = synth_queries(graph, 4096, seed=5)
        tuple_jsons = [q.to_json() for q in queries]

        def body_for(offset: int, size: int) -> bytes:
            sel = [
                tuple_jsons[(offset + j) % len(tuple_jsons)]
                for j in range(size)
            ]
            return json.dumps({"tuples": sel}).encode()

        # verdict divergence: the batch endpoint must answer EXACTLY like
        # the single-check endpoint for the same queries at the same state
        def post(path: str, body: bytes) -> dict:
            req = urllib.request.Request(
                f"http://{host}:{port}{path}", data=body,
                headers={"Content-Type": "application/json"}, method="POST",
            )
            with urllib.request.urlopen(req, timeout=120.0) as resp:
                return json.loads(resp.read())

        probe = post(
            "/relation-tuples/batch/check", body_for(0, 512)
        )["results"]
        singles = post(
            "/relation-tuples/check/batch", body_for(0, 512)
        )["results"]
        divergence = sum(
            1 for b, s in zip(probe, singles)
            if b.get("allowed") != s.get("allowed")
        )

        # warmup OUTSIDE the clock: compile every wave shape each batch
        # size will hit (the batch-64 leg otherwise absorbs the compile)
        for bs in batch_sizes:
            post("/relation-tuples/batch/check", body_for(31, bs))

        from ketotpu import compilewatch

        compiles_before = compilewatch.get().compiles_total
        per_size: Dict[str, Dict[str, float]] = {}
        for bs in batch_sizes:
            # a handful of rotating pre-encoded bodies per size: client
            # JSON encode stays out of the measured loop, the server
            # still parses every request in full
            bodies = [body_for(o * 97, bs) for o in range(8)]
            per_size[str(bs)] = _hammer_rest_batch(
                host, port, bodies,
                concurrency=concurrency, duration=duration, batch_size=bs,
            )
        # concurrency-1024 point at the mid batch size: the async loop
        # holds 1024 sockets as file descriptors, so this probes whether
        # the columnar path's throughput holds past the standard
        # concurrency rather than queueing collapse
        c1024 = _hammer_rest_batch(
            host, port, [body_for(o * 97, 512) for o in range(8)],
            concurrency=1024, duration=duration, batch_size=512,
        )
        wstats = reg.wave_ledger().stats()
        eng = reg.check_engine()
        mid = per_size.get("512") or per_size[str(batch_sizes[0])]
        return {
            "serve_batch": per_size,
            "serve_batch_checks_per_sec": mid["checks_per_sec"],
            "serve_batch_rps": mid["rps"],
            "serve_batch_p99_ms": mid["p99_ms"],
            "serve_batch_concurrency": concurrency,
            "serve_batch_verdict_divergence": divergence,
            "serve_batch_errors": sum(
                v["errors"] for v in per_size.values()
            ) + c1024["errors"],
            "serve_batch_c1024": c1024,
            "serve_batch_c1024_checks_per_sec": c1024["checks_per_sec"],
            "serve_batch_ingested": int(getattr(eng, "batch_ingested", 0)),
            "serve_batch_wave_size_mean": wstats.get("wave_size_mean", 0),
            "serve_batch_wave_size_p95": wstats.get("wave_size_p95", 0),
            "serve_batch_window_wait_ms_p50": wstats.get(
                "window_wait_ms_p50", 0
            ),
            "serve_batch_hammer_compiles": (
                compilewatch.get().compiles_total - compiles_before
            ),
            # columnar stage decomposition (decode / encode_ids /
            # wave_wait / respond ride keto_rpc_stage_seconds{op=check})
            "serve_batch_stage_ms": _scrape_means(
                reg.metrics(), "keto_rpc_stage_seconds", ("op", "stage")
            ),
            "serve_batch_block_waves": int(getattr(eng, "block_waves", 0)),
        }
    finally:
        srv.stop(grace=2.0)


def _paced_mixed_load(
    target: str, requests, read_addr, batch_bodies, *,
    rate: float, duration: float, clients: int = 16,
) -> Dict[str, object]:
    """Offer ``rate`` interactive Checks/sec (paced, spread over
    ``clients`` gRPC threads) plus batch POSTs at ~1/16 of that request
    rate; returns per-class admitted/shed/error counts and the latency
    list of ADMITTED interactive checks (sheds answer fast by design —
    mixing them in would flatter the percentile)."""
    import http.client

    import grpc

    from ketotpu.proto.services import CheckServiceStub

    stop = threading.Event()
    lock = threading.Lock()
    counts = {"inter_ok": 0, "inter_shed": 0, "inter_err": 0,
              "batch_ok": 0, "batch_shed": 0, "batch_err": 0}
    lat: List[float] = []

    def inter_client(idx: int) -> None:
        rng = np.random.default_rng(idx)
        interval = clients / max(rate, 1e-6)
        with grpc.insecure_channel(target) as ch:
            stub = CheckServiceStub(ch)
            nxt = time.perf_counter() + rng.uniform(0, interval)
            n_req = len(requests)
            while not stop.is_set():
                now = time.perf_counter()
                if now < nxt:
                    time.sleep(min(nxt - now, 0.05))
                    continue
                nxt += interval
                r = requests[int(rng.integers(n_req))]
                t0 = time.perf_counter()
                try:
                    stub.Check(r, timeout=20.0)
                    dt = time.perf_counter() - t0
                    with lock:
                        counts["inter_ok"] += 1
                        lat.append(dt)
                except grpc.RpcError as e:
                    key = (
                        "inter_shed"
                        if e.code() == grpc.StatusCode.RESOURCE_EXHAUSTED
                        else "inter_err"
                    )
                    with lock:
                        counts[key] += 1

    def batch_client() -> None:
        rng = np.random.default_rng(997)
        host, port = read_addr
        interval = 8.0 / max(rate, 1e-6)
        conn = http.client.HTTPConnection(host, port, timeout=30.0)
        nxt = time.perf_counter()
        try:
            while not stop.is_set():
                now = time.perf_counter()
                if now < nxt:
                    time.sleep(min(nxt - now, 0.05))
                    continue
                nxt += interval
                body = batch_bodies[int(rng.integers(len(batch_bodies)))]
                try:
                    conn.request(
                        "POST", "/relation-tuples/batch/check", body,
                        {"Content-Type": "application/json"},
                    )
                    resp = conn.getresponse()
                    resp.read()
                    key = ("batch_ok" if resp.status == 200 else
                           "batch_shed" if resp.status == 429 else
                           "batch_err")
                    with lock:
                        counts[key] += 1
                except (OSError, http.client.HTTPException):
                    with lock:
                        counts["batch_err"] += 1
                    conn.close()
                    conn = http.client.HTTPConnection(
                        host, port, timeout=30.0
                    )
        finally:
            conn.close()

    threads = [
        threading.Thread(target=inter_client, args=(i,), daemon=True)
        for i in range(clients)
    ] + [threading.Thread(target=batch_client, daemon=True)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(duration)
    stop.set()
    for t in threads:
        t.join(timeout=30.0)
    elapsed = time.perf_counter() - t0
    arr = np.array(lat) if lat else np.array([])
    return {
        **counts,
        "offered_rps": round(rate, 1),
        "goodput_rps": round(counts["inter_ok"] / elapsed, 1),
        "inter_p99_ms": round(float(np.percentile(arr, 99)) * 1000, 2)
        if len(arr) else -1.0,
        "seconds": round(elapsed, 1),
    }


def run_overload_bench(
    graph=None,
    *,
    duration: float = 6.0,
    frontier: int = 4096,
    arena: int = 16384,
) -> Dict[str, object]:
    """ISSUE 17 acceptance sweep: estimate single-check capacity, then
    offer a paced interactive+batch mix at 0.5x/1x/2x/4x of it and
    measure what the overload plane preserves.  The gates (applied by
    __main__, exit 3): goodput at 2x holds >= 80% of goodput at 1x, and
    the interactive p99 of ADMITTED checks at 2x stays within 2x of its
    1x value — i.e. shedding keeps the served work fast instead of
    letting a queue rot everyone's latency."""
    import grpc

    from ketotpu.driver import Provider, Registry
    from ketotpu.proto.services import CheckServiceStub
    from ketotpu.server import serve_all
    from ketotpu.utils.synth import build_synth, synth_queries

    if graph is None:
        graph = build_synth(
            n_users=2000, n_groups=100, n_folders=2000, n_docs=20000, seed=0
        )
    cfg = Provider(
        {
            "serve": {
                n: {"host": "127.0.0.1", "port": 0}
                for n in ("read", "write", "metrics", "opl")
            },
            "engine": {
                "kind": "tpu", "frontier": frontier, "arena": arena,
                "max_batch": frontier,
            },
            # a small fixed seed capacity makes a laptop-sized flood a
            # genuine overload; the AIMD limit adapts inside [16, 256]
            "limit": {"max_inflight": 64, "request_timeout_ms": 15000},
            "overload": {"floor": 16, "ceiling": 256, "increase": 16,
                         "interval_ms": 100, "hold_ms": 1000},
            "log": {"request_log": False},
        }
    )
    reg = Registry(
        cfg, store=graph.store, namespace_manager=graph.manager
    ).init()
    srv = serve_all(reg)
    try:
        host, port = srv.addresses["read"]
        target = f"{host}:{port}"
        requests = _build_requests(graph)
        # 8-item bodies: small enough to fit under the AIMD floor's
        # batch headroom when idle, big enough to shed first under load
        batch_bodies = [
            json.dumps({"tuples": [
                q.to_json() for q in synth_queries(graph, 8, seed=100 + i)
            ]}).encode()
            for i in range(8)
        ]
        # warmup (cold XLA compiles can outlive the request budget:
        # retry until the wave cache is hot)
        with grpc.insecure_channel(target) as ch:
            stub = CheckServiceStub(ch)
            for r in requests[:4]:
                for attempt in range(10):
                    try:
                        stub.Check(r)
                        break
                    except grpc.RpcError as e:
                        if (
                            e.code()
                            != grpc.StatusCode.DEADLINE_EXCEEDED
                            or attempt == 9
                        ):
                            raise
        # capacity estimate: short closed-loop burst
        base = _hammer(
            target, requests, concurrency=16,
            duration=min(3.0, duration),
        )
        base_rps = max(base["rps"], 10.0)
        ov = reg.overload()
        legs: Dict[str, object] = {}
        for mult in (0.5, 1.0, 2.0, 4.0):
            leg = _paced_mixed_load(
                target, requests, srv.addresses["read"], batch_bodies,
                rate=base_rps * mult, duration=duration,
            )
            if ov is not None:
                leg["stage_peak"] = max(
                    leg.get("stage_peak", 0), ov.stage
                )
            legs["x%g" % mult] = leg
            # settle between legs so one leg's brownout does not bleed
            # into the next leg's numbers
            deadline = time.monotonic() + 10.0
            while (ov is not None and ov.stage > 0
                   and time.monotonic() < deadline):
                time.sleep(0.2)
        snap = ov.snapshot() if ov is not None else {}
        return {
            "overload_base_rps": base_rps,
            "overload_legs": legs,
            "overload_goodput_1x": legs["x1"]["goodput_rps"],
            "overload_goodput_2x": legs["x2"]["goodput_rps"],
            "overload_inter_p99_1x": legs["x1"]["inter_p99_ms"],
            "overload_inter_p99_2x": legs["x2"]["inter_p99_ms"],
            "overload_shed_total": snap.get("admission", {}).get("shed", 0),
            "overload_shed_by_class": snap.get("admission", {}).get(
                "shed_by_class", {}
            ),
            "overload_transitions": len(snap.get("transitions", ())),
        }
    finally:
        srv.stop(grace=2.0)


def _hammer_nid(
    target: str, requests, nid: str, *, concurrency: int, duration: float,
    shed_sleep: float = 0.05,
) -> Dict[str, float]:
    """Closed-loop Check clients pinned to one tenant via the
    ``x-keto-network`` metadata key.  Quota sheds (RESOURCE_EXHAUSTED)
    are counted separately and back off ``shed_sleep`` — the Retry-After
    behavior a real client exhibits — so a shed flood measures quota
    isolation, not a python busy-loop."""
    import grpc

    from ketotpu.proto.services import CheckServiceStub

    md = (("x-keto-network", nid),)
    lat: List[List[float]] = [[] for _ in range(concurrency)]
    stop = threading.Event()
    shed = [0]
    errors = [0]

    def client(idx: int) -> None:
        rng = np.random.default_rng(idx)
        with grpc.insecure_channel(target) as ch:
            stub = CheckServiceStub(ch)
            my = lat[idx]
            n_req = len(requests)
            while not stop.is_set():
                r = requests[int(rng.integers(n_req))]
                t0 = time.perf_counter()
                try:
                    stub.Check(r, metadata=md)
                except grpc.RpcError as e:
                    if e.code() == grpc.StatusCode.RESOURCE_EXHAUSTED:
                        shed[0] += 1
                        time.sleep(shed_sleep)
                    else:
                        errors[0] += 1
                    continue
                my.append(time.perf_counter() - t0)

    threads = [
        threading.Thread(target=client, args=(i,), daemon=True)
        for i in range(concurrency)
    ]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(duration)
    stop.set()
    for t in threads:
        t.join(timeout=10.0)
    elapsed = time.perf_counter() - t_start
    all_lat = np.array([x for sub in lat for x in sub])
    done = len(all_lat)
    return {
        "rps": round(done / elapsed, 1),
        "p50_ms": round(float(np.percentile(all_lat, 50)) * 1000, 2)
        if done else -1.0,
        "p99_ms": round(float(np.percentile(all_lat, 99)) * 1000, 2)
        if done else -1.0,
        "errors": errors[0],
        "shed": shed[0],
    }


def run_tenants_bench(
    *,
    concurrency: int = 24,
    duration: float = 5.0,
    tenants: int = 8,
    frontier: int = 8192,
    arena: int = 32768,
) -> Dict[str, float]:
    """Tenant-plane serving bench (ketotpu/tenancy/): one device engine,
    ``tenants`` isolated stores, and the noisy-neighbor scenario the
    quota plane exists for.

    Legs, all against ONE booted daemon (no recompiles across the whole
    run — tenant lifecycle is a generation swap, gated by ``_steady``):

    * quiet     — the victim tenant alone: baseline p99;
    * noisy_off — an aggressor tenant floods with quotas disabled while
      the victim keeps its closed-loop load: the contended p99;
    * noisy_on  — the aggressor's inflight quota drops to a sliver (a
      HOT runtime change, no reboot) and floods again: with per-tenant
      admission the flood sheds out of the aggressor's own bucket and
      the victim's p99 must return to ~baseline (the __main__ gate
      enforces <= 1.25x quiet);
    * mid-flood tenant lifecycle — create / OPL-reload / delete of a
      bystander tenant inside the steady-state compile gate, proving
      lifecycle costs a generation swap and never an XLA compile.
    """
    import grpc

    from ketotpu.driver import Provider, Registry
    from ketotpu.proto.services import CheckServiceStub
    from ketotpu.server import serve_all
    from ketotpu.utils.synth import build_synth

    graph = build_synth(
        n_users=400, n_groups=40, n_folders=200, n_docs=2000, seed=0
    )
    tuples = graph.store.all_tuples()

    cfg = Provider(
        {
            "serve": {
                n: {"host": "127.0.0.1", "port": 0}
                for n in ("read", "write", "metrics", "opl")
            },
            "engine": {
                "kind": "tpu",
                "frontier": frontier,
                "arena": arena,
                "max_batch": frontier,
                "coalesce_ms": 1.0,
            },
            "tenancy": {"enabled": True},
            "log": {"request_log": False},
        }
    )
    reg = Registry(cfg, namespace_manager=graph.manager).init()
    plane = reg.tenant_plane()
    nids = [f"t{i}" for i in range(max(2, tenants))]
    victim, noisy = nids[0], nids[1]
    for nid in nids:
        plane.view_for(nid).write_relation_tuples(*tuples)
    srv = serve_all(reg)
    try:
        host, port = srv.addresses["read"]
        target = f"{host}:{port}"
        requests = _build_requests(graph, n=1024)

        # warm every tenant's routing path + the shared wave shapes at
        # both load levels (victim alone, victim + aggressor)
        with grpc.insecure_channel(target) as ch:
            stub = CheckServiceStub(ch)
            for nid in nids:
                for r in requests[:2]:
                    for attempt in range(10):
                        try:
                            stub.Check(
                                r, metadata=(("x-keto-network", nid),)
                            )
                            break
                        except grpc.RpcError as e:
                            if (
                                e.code()
                                != grpc.StatusCode.DEADLINE_EXCEEDED
                                or attempt == 9
                            ):
                                raise
        warm = max(2.0, duration * 0.4)
        _hammer_nid(target, requests, victim,
                    concurrency=concurrency // 2, duration=warm)
        ag = threading.Thread(
            target=_hammer_nid, args=(target, requests, noisy),
            kwargs=dict(concurrency=concurrency, duration=warm),
            daemon=True,
        )
        ag.start()
        _hammer_nid(target, requests, victim,
                    concurrency=concurrency // 2, duration=warm)
        ag.join(timeout=30.0)

        from bench import _steady

        out: Dict[str, float] = {}
        gate: Dict = {}

        def flood_leg(name: str) -> None:
            box: Dict = {}

            def aggressor() -> None:
                box["agg"] = _hammer_nid(
                    target, requests, noisy,
                    concurrency=concurrency, duration=duration,
                )

            th = threading.Thread(target=aggressor, daemon=True)
            th.start()
            with _steady(gate, f"serve_tenants_{name}"):
                h = _hammer_nid(
                    target, requests, victim,
                    concurrency=concurrency // 2, duration=duration,
                )
            th.join(timeout=30.0)
            agg = box.get("agg", {})
            out[f"tenants_victim_p99_ms_{name}"] = h["p99_ms"]
            out[f"tenants_victim_rps_{name}"] = h["rps"]
            out[f"tenants_victim_errors_{name}"] = h["errors"]
            out[f"tenants_aggressor_rps_{name}"] = agg.get("rps", 0)
            out[f"tenants_aggressor_shed_{name}"] = agg.get("shed", 0)

        # quiet baseline, then the mid-flood lifecycle probe: tenant
        # create + per-tenant OPL reload + delete are generation swaps
        # on warmed programs — zero compiles, inside the same gate
        with _steady(gate, "serve_tenants_quiet"):
            h = _hammer_nid(
                target, requests, victim,
                concurrency=concurrency // 2, duration=duration,
            )
        out["tenants_victim_p99_ms_quiet"] = h["p99_ms"]
        out["tenants_victim_rps_quiet"] = h["rps"]

        with _steady(gate, "serve_tenants_lifecycle"):
            plane.create("bystander")
            plane.set_opl(
                "bystander",
                "class User implements Namespace {}\n"
                "class doc implements Namespace {\n"
                "  related: { owner: User[]; }\n"
                "}\n",
            )
            with grpc.insecure_channel(target) as ch:
                stub = CheckServiceStub(ch)
                try:
                    stub.Check(
                        requests[0],
                        metadata=(("x-keto-network", "bystander"),),
                    )
                except grpc.RpcError as e:
                    # the override REPLACED bystander's namespace set, so
                    # the synth namespace rightly resolves NOT_FOUND —
                    # the routed check still ran the swapped generation
                    if e.code() != grpc.StatusCode.NOT_FOUND:
                        raise
            plane.delete("bystander")

        flood_leg("noisy_off")

        # quota flip is HOT: shrink the aggressor's inflight bucket to a
        # single unit — with the coalescer batching whole waves, even a
        # handful of admitted units sustains full flood throughput, so
        # the guard must squeeze to a sliver to actually yield the box
        plane.quotas_for(noisy).inflight.cap = 1
        flood_leg("noisy_on")

        steady = gate.get("steady_state_compiles", {})
        out["tenants_steady_state_compiles"] = int(sum(steady.values()))
        if steady:
            out["steady_state_compiles"] = steady
        out["tenants_count"] = len(plane.tenant_ids())
        out["tenants_concurrency"] = concurrency
        shed_rows = {
            row["id"]: row["shed"] for row in plane.catalog() if row["shed"]
        }
        out["tenants_shed_by_tenant"] = shed_rows
        return out
    finally:
        srv.stop(grace=2.0)


def run_sharded_child(
    shards: int,
    *,
    concurrency: int = 32,
    duration: float = 6.0,
    zipf: bool = False,
    replicate: bool = True,
) -> Dict[str, float]:
    """One sharded serving leg in ONE process: boot the daemon with
    ``engine.mesh_devices=<shards>`` (1 = the single-chip baseline),
    hammer single Checks over gRPC, and report RPS/p50/p99 + verdict
    divergence against the host oracle + steady-state compiles under the
    ``_steady`` gate.  Run as a CHILD process by ``run_sharded_bench``:
    the shard count needs ``--xla_force_host_platform_device_count`` in
    XLA_FLAGS BEFORE jax imports, which only a fresh interpreter can
    guarantee."""
    import grpc

    from ketotpu.driver import Provider, Registry
    from ketotpu.proto.services import CheckServiceStub
    from ketotpu.server import serve_all
    from ketotpu.utils.synth import build_synth, synth_queries

    graph = build_synth(
        n_users=1024, n_groups=64, n_folders=1024, n_docs=8192, seed=0
    )
    cfg = Provider(
        {
            "serve": {
                n: {"host": "127.0.0.1", "port": 0}
                for n in ("read", "write", "metrics", "opl")
            },
            "engine": {
                "kind": "tpu",
                "mesh_devices": 0 if shards <= 1 else shards,
                "frontier": 4096,
                "arena": 16384,
                "max_batch": 4096,
                "coalesce_ms": 2,
                "mesh": {
                    "replicate_hot": bool(replicate),
                    "hot_min": 32,
                    "replica_max_keys": 8,
                    "rebalance_skew": 2.5,
                    # background controller live during the hammer:
                    # hot keys replicate mid-run via (same-shape)
                    # generation swaps — the _steady gate proves the
                    # swaps stay compile-free
                    "interval_ms": 250 if replicate else 0,
                },
            },
            "limit": {"max_inflight": 0},
            "log": {"request_log": False},
        }
    )
    reg = Registry(
        cfg, store=graph.store, namespace_manager=graph.manager
    ).init()
    srv = serve_all(reg)
    try:
        host, port = srv.addresses["read"]
        target = f"{host}:{port}"
        requests = _build_requests(graph, 2048)
        if zipf:
            # zipfian object popularity: duplicate request slots by a
            # zipf(1.2) draw so _hammer's uniform sampler produces a
            # hot-object-skewed stream (rank 0 hottest)
            rng = np.random.default_rng(7)
            idx = (rng.zipf(1.2, size=8192) - 1) % len(requests)
            requests = [requests[int(i)] for i in idx]
        with grpc.insecure_channel(target) as ch:
            stub = CheckServiceStub(ch)
            for r in requests[:8]:
                stub.Check(r)

        # divergence probe: served verdicts vs the host oracle, same state
        eng = reg.check_engine()
        inner = getattr(eng, "inner", eng)
        sample = synth_queries(graph, 512, seed=9)
        served = eng.batch_check(sample)
        want = [inner.oracle.check_is_member(q) for q in sample]
        divergence = sum(1 for g, w in zip(served, want) if g != w)

        # warm pass at the EXACT hammer shapes (coalescer wave buckets),
        # unmeasured; then the timed pass under the steady-compile gate
        _hammer(
            target, requests, concurrency=concurrency,
            duration=max(2.0, duration * 0.4),
        )
        from bench import _steady

        gate: Dict = {}
        with _steady(gate, "serve_sharded"):
            h = _hammer(
                target, requests, concurrency=concurrency, duration=duration
            )
        steady = gate.get("steady_state_compiles", {}).get(
            "serve_sharded", 0
        )
        res = {
            "shards": shards,
            "rps": h["rps"],
            "p50_ms": h["p50_ms"],
            "p99_ms": h["p99_ms"],
            "errors": h["errors"],
            "divergence": divergence,
            "steady_state_compiles": int(steady),
            "zipf": bool(zipf),
            "replicate": bool(replicate),
        }
        mesh_fn = getattr(inner, "mesh_stats", None)
        if mesh_fn is not None:
            res["mesh"] = mesh_fn()
        return res
    finally:
        srv.stop(grace=2.0)


def run_sharded_bench(
    *,
    concurrency: int = 32,
    duration: float = 6.0,
    shard_counts=(1, 2, 4),
) -> Dict[str, float]:
    """Sharded serving scaling sweep (ISSUE 10): one subprocess per shard
    count (XLA fixes the host device count at import time), uniform
    workload for the RPS-vs-shards curve with zero-divergence and
    zero-steady-compile gates, then a zipfian leg at the top shard count
    with hot-key replication ON vs OFF for the p99 effect."""
    import os
    import subprocess

    def child(shards: int, mode: str, rep: str) -> Dict:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = " ".join(
            f for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        )
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{max(shards, 1)} --xla_cpu_parallel_codegen_split_count=1"
        ).strip()
        p = subprocess.run(
            [
                sys.executable, os.path.abspath(__file__),
                str(concurrency), str(duration), "sharded_child",
                str(shards), mode, rep,
            ],
            capture_output=True, text=True, timeout=1800, env=env,
        )
        line = (
            p.stdout.strip().splitlines()[-1] if p.stdout.strip() else "{}"
        )
        try:
            res = json.loads(line)
        except json.JSONDecodeError:
            res = {"error": (p.stderr or p.stdout)[-400:]}
        res["exit_code"] = p.returncode
        return res

    legs = {str(n): child(n, "uniform", "rep") for n in shard_counts}
    top = max(shard_counts)
    zipf_on = child(top, "zipf", "rep")
    zipf_off = child(top, "zipf", "norep")
    rps = {k: float(v.get("rps", 0)) for k, v in legs.items()}
    return {
        "serve_sharded": legs,
        "serve_sharded_rps": rps,
        "serve_sharded_scaling_ok": (
            rps.get("2", 0) > rps.get("1", 0)
            if "1" in rps and "2" in rps else None
        ),
        "serve_sharded_divergence": sum(
            int(v.get("divergence", 0)) for v in legs.values()
        ),
        "serve_sharded_steady_compiles": sum(
            int(v.get("steady_state_compiles", 0)) for v in legs.values()
        ),
        "serve_sharded_zipf_replication_on": zipf_on,
        "serve_sharded_zipf_replication_off": zipf_off,
        "serve_sharded_zipf_p99_delta_ms": round(
            float(zipf_off.get("p99_ms", -1.0))
            - float(zipf_on.get("p99_ms", -1.0)), 2,
        ),
    }


def _wait_marker(path, timeout: float, what: str) -> None:
    import os

    deadline = time.monotonic() + timeout
    while not os.path.exists(path):
        if time.monotonic() > deadline:
            raise RuntimeError(f"timed out waiting for {what}")
        time.sleep(0.25)


def run_multihost_child(spec_path: str) -> Dict:
    """One owner host of the 2-host loopback mesh (ISSUE 14).  Driven by
    ``run_multihost_bench`` as a subprocess; the JSON spec carries the
    topology (shared sqlite DSN, both PeerLink addresses, this host's
    id/role) and the phase directory both hosts coordinate through with
    marker files.

    Roles:

    * ``victim`` — boots, warms its engine locally, marks itself ready,
      then idles serving DCN frames until the parent kill -9s it (the
      whole-host-failure half of the chaos bar).
    * ``rejoin`` — the restarted victim: boots warm, marks ready, then
      holds a steady-compile gate open from the ``gate_start`` marker to
      ``stop`` — the driver hammers THROUGH it in that window, so the
      gate proves a returning peer serves forwarded waves with ZERO
      after-warm XLA compiles.
    * ``driver`` — serves the gRPC hammer: divergence probes before the
      kill, through it, and after the rejoin; the kill-window hammer and
      the recovered-window hammer both run under steady-compile gates.
    """
    import os

    from ketotpu.driver import Provider, Registry
    from ketotpu.server import serve_all
    from ketotpu.utils.synth import build_synth, synth_queries

    with open(spec_path) as f:
        spec = json.load(f)
    role = spec["role"]
    phase = spec["phase_dir"]
    # the same deterministic synth graph the parent seeded the shared
    # sqlite store from: used ONLY to generate requests
    graph = build_synth(
        n_users=1024, n_groups=64, n_folders=1024, n_docs=8192, seed=0
    )
    cfg = Provider(
        {
            "dsn": spec["dsn"],
            "namespaces": {"location": spec["namespaces"]},
            "serve": {
                n: {"host": "127.0.0.1", "port": p}
                for n, p in spec["serve_ports"].items()
            },
            "engine": {
                "kind": "tpu",
                "mesh_devices": int(spec["shards"]),
                "frontier": 4096,
                "arena": 16384,
                "max_batch": 4096,
                "coalesce_ms": 2,
                "mesh": {
                    "hosts": {
                        "host_id": int(spec["host_id"]),
                        "peers": list(spec["peers"]),
                        "secret": spec["secret"],
                        "heartbeat_ms": 200,
                        "heartbeat_misses": 3,
                        # generous: a first-shape frontier exchange may
                        # sit behind an XLA:CPU compile on either side
                        "rpc_timeout_ms": 240000,
                    },
                },
            },
            # leopard answers fast roots from the local closure index
            # BEFORE cross-host routing is consulted — correct, but it
            # would serve this synth graph entirely locally and leave
            # the DCN lane untested; the lane-live gate below needs real
            # cross-host traffic
            "leopard": {"enabled": False},
            "limit": {"max_inflight": 0},
            "log": {"request_log": False},
        }
    )
    reg = Registry(cfg).init()
    srv = serve_all(reg)
    try:
        eng = reg.check_engine()
        inner = getattr(eng, "inner", eng)
        link = inner.hostlink

        # warm the LOCAL cascade (XLA compiles) before anything crosses
        # the lane: the local-serve scope pins the batch to this host
        warm = synth_queries(graph, 512, seed=5)
        inner._peer_serve_check(warm, 0)
        # ...and at the <=256-row fast bucket forwarded sub-waves land in
        inner._peer_serve_check(warm[:160], 0)

        def probe_divergence(n: int, seed: int) -> int:
            sample = synth_queries(graph, n, seed=seed)
            served = eng.batch_check(sample)
            want = [inner.oracle.check_is_member(q) for q in sample]
            return sum(1 for g, w in zip(served, want) if g != w)

        if role in ("victim", "rejoin"):
            from bench import _steady

            res: Dict = {"role": role, "host_id": spec["host_id"]}
            open(os.path.join(phase, f"{role}_ready"), "w").close()
            if role == "rejoin":
                # hold the after-warm compile gate open across the
                # driver's recovered-window hammer (forwarded waves land
                # here the whole time)
                _wait_marker(
                    os.path.join(phase, "gate_start"), 600.0, "gate_start"
                )
                gate: Dict = {}
                with _steady(gate, "serve_multihost_rejoin"):
                    _wait_marker(
                        os.path.join(phase, "stop"), 600.0, "stop marker"
                    )
                res["after_warm_compiles"] = int(
                    gate.get("steady_state_compiles", {}).get(
                        "serve_multihost_rejoin", 0
                    )
                )
                res["peer"] = inner.mesh_stats()
                with open(os.path.join(phase, "rejoin_result.json"), "w") as f:
                    json.dump(res, f)
            else:
                _wait_marker(
                    os.path.join(phase, "stop"), 600.0, "stop marker"
                )
            return res

        # -- driver host --------------------------------------------------
        from bench import _steady

        conc = int(spec["concurrency"])
        secs = float(spec["duration"])
        host, port = srv.addresses["read"]
        target = f"{host}:{port}"
        requests = _build_requests(graph, 2048)

        _wait_marker(
            os.path.join(phase, "victim_ready"), 600.0, "victim boot"
        )
        # absorb first-shape compiles on BOTH sides of the lane, then
        # prove the lane is live before the storm
        div_a = probe_divergence(256, seed=9)
        div_a += probe_divergence(256, seed=9)
        routed_warm = int(inner.peer_route_counts().sum())
        _hammer(target, requests, concurrency=conc,
                duration=max(2.0, secs * 0.4))

        # timed kill-window hammer: the parent kill -9s the victim
        # mid-window; verdicts must stay exact (replica or oracle) and
        # the wave must never block past its budget
        open(os.path.join(phase, "hammer_start"), "w").close()
        gate: Dict = {}
        with _steady(gate, "serve_multihost"):
            h_kill = _hammer(
                target, requests, concurrency=conc, duration=secs
            )
        div_b = probe_divergence(256, seed=10)
        kill_stats = inner.mesh_stats()

        # recovery: the restarted victim marks ready, the heartbeat loop
        # marks it up, rows route cross-host again
        _wait_marker(
            os.path.join(phase, "rejoin_ready"), 600.0, "victim rejoin"
        )
        recovered = False
        deadline_t = time.monotonic() + 240.0
        while time.monotonic() < deadline_t:
            if inner.mesh_stats().get("hosts_down", 1) == 0:
                recovered = True
                break
            time.sleep(0.5)
        # settle pass re-warms the rejoined peer's forwarded shapes
        # (unmeasured — long enough to play the coalescer's bucket
        # spectrum onto the rejoiner), then the gated recovered-window
        # hammer runs with the rejoin child's own after-warm compile
        # gate open too
        _hammer(target, requests, concurrency=conc,
                duration=max(4.0, secs * 0.8))
        open(os.path.join(phase, "gate_start"), "w").close()
        gate2: Dict = {}
        with _steady(gate2, "serve_multihost_recovered"):
            h_rec = _hammer(
                target, requests, concurrency=conc,
                duration=max(3.0, secs * 0.5),
            )
        div_c = probe_divergence(256, seed=11)
        open(os.path.join(phase, "stop"), "w").close()

        ms = inner.mesh_stats()
        return {
            "role": "driver",
            "rps": h_kill["rps"],
            "p50_ms": h_kill["p50_ms"],
            "p99_ms": h_kill["p99_ms"],
            "errors": h_kill["errors"],
            "recovered_rps": h_rec["rps"],
            "recovered_p99_ms": h_rec["p99_ms"],
            "divergence": div_a + div_b + div_c,
            "steady_state_compiles": int(
                gate.get("steady_state_compiles", {}).get(
                    "serve_multihost", 0
                )
            ) + int(
                gate2.get("steady_state_compiles", {}).get(
                    "serve_multihost_recovered", 0
                )
            ),
            "peer_routed_warm": routed_warm,
            "peer_routed": int(ms.get("peer_routed", 0)),
            "peer_fallbacks_kill_window": int(
                kill_stats.get("peer_fallbacks", 0)
            ),
            "hosts_down_kill_window": int(kill_stats.get("hosts_down", 0)),
            "recovery_observed": bool(recovered),
            "peer_recoveries": int(ms.get("peer_recoveries", 0)),
            "frontier_rtt_p50_ms": float(
                ms.get("peer_frontier_rtt_p50_ms", 0.0)
            ),
        }
    finally:
        srv.stop(grace=2.0)


def run_multihost_bench(
    *,
    concurrency: int = 64,
    duration: float = 8.0,
    shards: int = 4,
) -> Dict:
    """Cross-host mesh chaos sweep (ISSUE 14): two REAL owner processes
    over a loopback DCN lane against one shared sqlite store.  The
    driver host serves a concurrency-N gRPC hammer; mid-window the
    parent kill -9s the victim host, then restarts it.  Gates: zero
    verdict divergence across all three probes (before / during-kill /
    after-rejoin), zero steady-state compiles on the driver, zero
    after-warm compiles on the rejoined victim, and observable
    recovery.  Reports the kill-window and recovered-window RPS/p99 and
    the frontier round-trip p50."""
    import os
    import shutil
    import signal
    import subprocess
    import tempfile

    from ketotpu.storage.sqlite import SQLiteTupleStore
    from ketotpu.utils.synth import SYNTH_OPL, build_synth

    tmp = tempfile.mkdtemp(prefix="keto-multihost-bench-")
    procs: Dict[str, subprocess.Popen] = {}
    pgids: Dict[str, int] = {}

    def spawn(role: str, host_id: int, spec: Dict) -> None:
        spec_path = os.path.join(tmp, f"{role}.json")
        with open(spec_path, "w") as f:
            json.dump(dict(spec, role=role, host_id=host_id), f)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = " ".join(
            x for x in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in x
        )
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={shards}"
            " --xla_cpu_parallel_codegen_split_count=1"
        ).strip()
        p = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             str(concurrency), str(duration), "multihost_child",
             spec_path],
            env=env, start_new_session=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        procs[role] = p
        pgids[role] = os.getpgid(p.pid)
        _CHILD_PGIDS.append(pgids[role])

    try:
        ns_path = os.path.join(tmp, "namespaces.keto.ts")
        with open(ns_path, "w") as f:
            f.write(SYNTH_OPL)
        db_path = os.path.join(tmp, "store.db")
        graph = build_synth(
            n_users=1024, n_groups=64, n_folders=1024, n_docs=8192, seed=0
        )
        store = SQLiteTupleStore(db_path)
        store.migrate_up()
        tuples = graph.store.all_tuples()
        for i in range(0, len(tuples), 10_000):
            store.write_relation_tuples(*tuples[i : i + 10_000])
        store.close()

        peer_ports = [_free_port(), _free_port()]
        peers = [f"127.0.0.1:{p}" for p in peer_ports]
        base = {
            "dsn": f"sqlite://{db_path}",
            "namespaces": f"file://{ns_path}",
            "peers": peers,
            "secret": "multihost-bench-secret",
            "phase_dir": tmp,
            "shards": shards,
            "concurrency": concurrency,
            "duration": duration,
        }

        def ports() -> Dict[str, int]:
            return {
                n: _free_port()
                for n in ("read", "write", "metrics", "opl")
            }

        spawn("victim", 1, dict(base, serve_ports=ports()))
        _wait_marker(
            os.path.join(tmp, "victim_ready"), 600.0, "victim boot"
        )
        spawn("driver", 0, dict(base, serve_ports=ports()))
        _wait_marker(
            os.path.join(tmp, "hammer_start"), 600.0, "driver hammer"
        )

        # kill -9 the victim mid-hammer: a whole host, gone at once
        time.sleep(max(1.0, duration * 0.5))
        os.killpg(pgids["victim"], signal.SIGKILL)
        procs["victim"].wait(timeout=30)

        # restart it on the SAME topology slot (same PeerLink port)
        time.sleep(1.0)
        spawn("rejoin", 1, dict(base, serve_ports=ports()))

        out, err = procs["driver"].communicate(timeout=1800)
        line = out.strip().splitlines()[-1] if out.strip() else "{}"
        try:
            driver = json.loads(line)
        except json.JSONDecodeError:
            driver = {"error": (err or out)[-400:]}
        driver["exit_code"] = procs["driver"].returncode

        rejoin_json = os.path.join(tmp, "rejoin_result.json")
        _wait_marker(rejoin_json, 120.0, "rejoin result")
        with open(rejoin_json) as f:
            rejoin = json.load(f)
        try:
            procs["rejoin"].wait(timeout=120)
        except subprocess.TimeoutExpired:
            pass

        after_warm = int(rejoin.get("after_warm_compiles", -1))
        return {
            "serve_multihost": driver,
            "serve_multihost_rejoin": rejoin,
            "serve_multihost_divergence": int(
                driver.get("divergence", -1)
            ),
            "serve_multihost_steady_compiles": int(
                driver.get("steady_state_compiles", -1)
            ),
            "serve_multihost_rejoin_after_warm_compiles": after_warm,
            "serve_multihost_recovery_observed": bool(
                driver.get("recovery_observed", False)
            ),
            "serve_multihost_peer_routed": int(
                driver.get("peer_routed", 0)
            ),
            "serve_multihost_rps": driver.get("rps", -1.0),
            "serve_multihost_p99_ms": driver.get("p99_ms", -1.0),
            "serve_multihost_recovered_rps": driver.get(
                "recovered_rps", -1.0
            ),
            "serve_multihost_frontier_rtt_p50_ms": driver.get(
                "frontier_rtt_p50_ms", -1.0
            ),
        }
    finally:
        import signal as _sig

        for role, p in procs.items():
            if p.poll() is None:
                try:
                    os.killpg(pgids[role], _sig.SIGTERM)
                    p.wait(timeout=10)
                except (OSError, subprocess.TimeoutExpired):
                    try:
                        os.killpg(pgids[role], _sig.SIGKILL)
                    except OSError:
                        pass
        shutil.rmtree(tmp, ignore_errors=True)


def _scrape_means(metrics, name: str, label_keys) -> Dict[str, float]:
    """Mean milliseconds per histogram series, keyed by the joined label
    values ("check.coalesce_wait") — the per-stage RPC breakdown the bench
    JSON publishes after the hammer run."""
    out: Dict[str, float] = {}
    for labels, (total, count) in metrics.histogram_values(name).items():
        if not count:
            continue
        ld = dict(labels)
        key = ".".join(ld.get(k, "?") for k in label_keys)
        out[key] = round(1000.0 * total / count, 3)
    return out


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


#: process-group ids of live `serve --workers` children: bench.py's
#: SIGTERM handler reaps these before os._exit (the handler skips the
#: finally-block cleanup below, and the group's own session would
#: otherwise survive the driver's kill holding the device)
_CHILD_PGIDS: List[int] = []


def kill_children() -> None:
    import os
    import signal

    for pgid in list(_CHILD_PGIDS):
        try:
            os.killpg(pgid, signal.SIGKILL)
        except OSError:
            pass


def run_workers_bench(
    graph=None,
    *,
    workers: int = 2,
    concurrency: int = 32,
    duration: float = 10.0,
    coalesce_ms: float = 2.0,
    frontier: int = 16384,
    arena: int = 65536,
    boot_timeout: float = 420.0,
) -> Dict[str, float]:
    """Measure the REAL ``serve --workers N`` topology (VERDICT r4 #3):
    one device-owner process + N SO_REUSEPORT worker daemons booted via
    the CLI against a shared sqlite file, hammered like the
    single-process leg.  On a 1-core host parity with ``serve_rps`` is
    the expected outcome (the workers exist to scale the wire path
    across cores); the section exists so multi-core runs show scaling."""
    import grpc
    import os
    import shutil
    import signal
    import subprocess
    import tempfile

    import yaml

    from ketotpu.proto.services import CheckServiceStub
    from ketotpu.storage.sqlite import SQLiteTupleStore
    from ketotpu.utils.synth import SYNTH_OPL, build_synth

    if graph is None:
        graph = build_synth(
            n_users=2000, n_groups=100, n_folders=2000, n_docs=20000, seed=0
        )
    tmp = tempfile.mkdtemp(prefix="keto-workers-bench-")
    proc = None
    pgid = None
    try:
        ns_path = os.path.join(tmp, "namespaces.keto.ts")
        with open(ns_path, "w") as f:
            f.write(SYNTH_OPL)
        db_path = os.path.join(tmp, "store.db")
        store = SQLiteTupleStore(db_path)
        store.migrate_up()
        tuples = graph.store.all_tuples()
        for i in range(0, len(tuples), 10_000):
            store.write_relation_tuples(*tuples[i : i + 10_000])
        store.close()

        requests = _build_requests(graph)
        cfg_path = os.path.join(tmp, "keto.yml")
        target = None
        # two boot attempts: _free_port picks then closes its sockets, so
        # another process can (transiently) grab a port before the
        # workers bind it — a fresh attempt re-picks fresh ports
        for attempt in (1, 2):
            ports = {
                n: _free_port() for n in ("read", "write", "metrics", "opl")
            }
            with open(cfg_path, "w") as f:
                yaml.safe_dump(
                    {
                        "dsn": f"sqlite://{db_path}",
                        "namespaces": {"location": f"file://{ns_path}"},
                        "serve": {
                            n: {"host": "127.0.0.1", "port": p}
                            for n, p in ports.items()
                        },
                        "engine": {
                            "kind": "tpu",
                            "frontier": frontier,
                            "arena": arena,
                            "max_batch": frontier,
                            "coalesce_ms": coalesce_ms,
                        },
                        "log": {"request_log": False},
                    },
                    f,
                )
            proc = subprocess.Popen(
                [sys.executable, "-m", "ketotpu.cli", "serve",
                 "-c", cfg_path, "--workers", str(workers)],
                start_new_session=True,  # one killpg reaps owner + workers
            )
            # capture the pgid NOW: with start_new_session the workers
            # share it and can outlive the owner, whose death makes
            # os.getpgid(proc.pid) unanswerable later
            pgid = os.getpgid(proc.pid)
            _CHILD_PGIDS.append(pgid)
            target = f"127.0.0.1:{ports['read']}"

            # readiness + warmup: the owner compiles the engine snapshot
            # before forking workers, so the first successful Check means
            # the whole topology is up.  The boot budget is SPLIT across
            # the two attempts so a persistent failure cannot double the
            # worst-case hang past the caller's expectation.
            deadline = time.monotonic() + boot_timeout / 2
            ready = False
            boot_err = None
            while time.monotonic() < deadline:
                if proc.poll() is not None:
                    boot_err = (
                        f"serve --workers exited rc={proc.returncode}"
                        " during boot"
                    )
                    break
                try:
                    with grpc.insecure_channel(target) as ch:
                        stub = CheckServiceStub(ch)
                        for r in requests[:4]:
                            stub.Check(r, timeout=120.0)
                    ready = True
                    break
                except grpc.RpcError:
                    time.sleep(2.0)
            if ready:
                break
            if boot_err is None:
                boot_err = (
                    f"workers not ready after {boot_timeout / 2:.0f}s"
                )
            _reap(proc, pgid)
            proc = None
            if attempt == 2:
                raise RuntimeError(boot_err)
        time.sleep(2.0)  # let every SO_REUSEPORT worker finish binding

        h = _hammer(target, requests, concurrency=concurrency, duration=duration)
        return {
            "workers_rps": h["rps"],
            "workers_p50_ms": h["p50_ms"],
            "workers_p99_ms": h["p99_ms"],
            "workers_n": workers,
            "workers_concurrency": concurrency,
            "workers_seconds": h["seconds"],
            "workers_errors": h["errors"],
        }
    finally:
        if proc is not None and pgid is not None:
            _reap(proc, pgid)
        shutil.rmtree(tmp, ignore_errors=True)


def _reap(proc, pgid) -> None:
    """SIGINT (graceful) then SIGKILL a serve --workers process GROUP and
    drop it from the SIGTERM handler's registry.  The group is signaled
    even when the owner itself already exited: with start_new_session
    the workers share the pgid and can outlive the owner (ESRCH for a
    fully-gone group is swallowed)."""
    import os
    import signal
    import subprocess

    try:
        os.killpg(pgid, signal.SIGINT)
    except OSError:
        pass
    try:
        proc.wait(timeout=20)
    except subprocess.TimeoutExpired:
        pass
    try:
        os.killpg(pgid, signal.SIGKILL)
    except OSError:
        pass
    if pgid in _CHILD_PGIDS:
        _CHILD_PGIDS.remove(pgid)


if __name__ == "__main__":
    conc = int(sys.argv[1]) if len(sys.argv) > 1 else 64
    secs = float(sys.argv[2]) if len(sys.argv) > 2 else 10.0
    if len(sys.argv) > 3 and sys.argv[3] == "sharded_child":
        shards = int(sys.argv[4]) if len(sys.argv) > 4 else 1
        mode = sys.argv[5] if len(sys.argv) > 5 else "uniform"
        rep = sys.argv[6] != "norep" if len(sys.argv) > 6 else True
        res = run_sharded_child(
            shards, concurrency=conc, duration=secs,
            zipf=(mode == "zipf"), replicate=rep,
        )
        print(json.dumps(res))
        sys.exit(3 if res.get("steady_state_compiles") else 0)
    elif len(sys.argv) > 3 and sys.argv[3] == "multihost_child":
        res = run_multihost_child(sys.argv[4])
        print(json.dumps(res))
        if res.get("role") == "driver":
            bad = (
                res.get("divergence")
                or res.get("steady_state_compiles")
                or not res.get("recovery_observed")
                # a dead DCN lane serves everything locally and passes
                # the other gates vacuously — require real routing
                or not res.get("peer_routed")
            )
            sys.exit(3 if bad else 0)
        sys.exit(0)
    elif len(sys.argv) > 3 and sys.argv[3] == "serve_multihost":
        shards = int(sys.argv[4]) if len(sys.argv) > 4 else 4
        res = run_multihost_bench(
            concurrency=conc, duration=secs, shards=shards
        )
        print(json.dumps(res))
        bad = (
            res.get("serve_multihost_divergence")
            or res.get("serve_multihost_steady_compiles")
            or res.get("serve_multihost_rejoin_after_warm_compiles")
            or not res.get("serve_multihost_recovery_observed")
            or not res.get("serve_multihost_peer_routed")
        )
        sys.exit(3 if bad else 0)
    elif len(sys.argv) > 3 and sys.argv[3] == "sharded":
        print(json.dumps(run_sharded_bench(concurrency=conc, duration=secs)))
    elif len(sys.argv) > 3 and sys.argv[3] == "workers":
        print(json.dumps(run_workers_bench(concurrency=conc, duration=secs)))
    elif len(sys.argv) > 3 and sys.argv[3] == "batch":
        print(json.dumps(run_batch_bench(concurrency=conc, duration=secs)))
    elif len(sys.argv) > 3 and sys.argv[3] == "northstar":
        res = run_northstar_bench(
            concurrencies=(conc,) if len(sys.argv) > 4 else (1024, 4096),
            duration=secs,
        )
        print(json.dumps(res))
        # streaming gates ride the northstar run: the session lane must
        # answer exactly like the oracle AND beat per-RPC BatchCheck row
        # throughput by >= 1.3x at the same block size (the whole point
        # of paying admission/decode once per session)
        bad = (
            res.get("northstar_steady_state_compiles")
            or res.get("northstar_divergence")
            or res.get("serve_stream_divergence")
            or (
                "serve_stream_vs_batch" in res
                and res["serve_stream_vs_batch"] < 1.3
            )
        )
        sys.exit(3 if bad else 0)
    elif len(sys.argv) > 3 and sys.argv[3] == "overload":
        res = run_overload_bench(duration=secs)
        print(json.dumps(res))
        # acceptance gate: shedding must PRESERVE goodput and the
        # latency of admitted work at 2x offered load — a plane that
        # lets the queue rot fails both
        g1, g2 = res["overload_goodput_1x"], res["overload_goodput_2x"]
        p1, p2 = res["overload_inter_p99_1x"], res["overload_inter_p99_2x"]
        bad = (
            g1 <= 0 or g2 < 0.8 * g1
            or (p1 > 0 and p2 > 2.0 * p1)
        )
        sys.exit(3 if bad else 0)
    elif len(sys.argv) > 3 and sys.argv[3] == "tenants":
        res = run_tenants_bench(concurrency=conc, duration=secs)
        print(json.dumps(res))
        # acceptance gates: (a) per-tenant admission must actually engage
        # (the aggressor sheds out of its own bucket), (b) the victim's
        # p99 under a quota-capped flood stays within 1.25x its quiet
        # baseline, (c) tenant lifecycle mid-flood compiles nothing
        quiet = res.get("tenants_victim_p99_ms_quiet", -1.0)
        guarded = res.get("tenants_victim_p99_ms_noisy_on", -1.0)
        bad = (
            quiet <= 0
            or guarded <= 0
            or guarded > 1.25 * quiet
            or not res.get("tenants_aggressor_shed_noisy_on")
            or res.get("tenants_steady_state_compiles")
        )
        sys.exit(3 if bad else 0)
    elif len(sys.argv) > 3 and sys.argv[3] == "trace":
        print(json.dumps(
            run_trace_overhead_bench(concurrency=conc, duration=secs)
        ))
    elif len(sys.argv) > 3 and sys.argv[3] == "fleet":
        res = run_fleet_overhead_bench(concurrency=conc, duration=secs)
        print(json.dumps(res))
        # acceptance gate: <= 5% serving cost, zero incidents on a clean
        # steady-state run
        sys.exit(
            3 if res.get("serve_slo_overhead_pct", 0.0) > 5.0
            or res.get("fleet_incidents", 0) else 0
        )
    else:
        print(json.dumps(run_serving_bench(concurrency=conc, duration=secs)))
