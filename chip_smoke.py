#!/usr/bin/env python3
"""The quickest proof that keto-tpu still starts and answers on the chip.

One process, one chip.  Builds the Drive-style synth graph from ``--seed``,
boots the real daemon in-process through the normal entry points
(``Provider`` -> ``Registry.init()`` -> ``serve_all``), and drives it over
the wire from a client in the same process: single gRPC Checks, batched
REST Checks (pure-OR and AND/NOT mixed), and depth-5 Expands.  Every verdict
and tree is compared with ``engine/oracle.py`` on the same store; then the
metrics port is scraped, and the run fails unless the device did the work
(no device failure, dispatches and fused waves moved, oracle-fallback share
under 5 %, health not degraded).

Refuses to run without a TPU.  Any failed phase raises: nothing here lets
the script reach its last line, which is the one JSON object the driver
reads.  Earlier lines carry what a bring-up needs: the device, where the
compile cache lives, per-phase wall seconds, compile count and seconds,
peak device memory and the counters.  They are not a benchmark: no rate or
latency is claimed from them.

    python chip_smoke.py                 # one chip, as the driver runs it
    python chip_smoke.py --tuples 100000 # a rehearsal
    python chip_smoke.py --mesh 4        # only the four-chip sharded path
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.metadata
import json
import sys
import time
import urllib.request

#: what the default counts of ``build_synth_columnar`` come to
FULL_TUPLES = 10_619_000
#: REST batch size.  The default engine block (frontier 8192, arena 16384)
#: holds the worst-case level growth of a 1024-row wave; a 4096-row batch
#: overflows tier 1 for a third of its pure-OR rows and lives off the retry
#: lanes (measured on the CPU, PR 23) — bench_serve.py doubles the frontier
#: for that batch, the smoke keeps the defaults and sends what they fit
BATCH = 1024
#: engine settings that differ from the daemon's defaults, and why.
#: fused_retry_lanes=0: the default fused wave (one retry lane, which also
#: brings the 24-level general retry into the program) costs ~500 s of
#: compile per variant for the v5e against ~165 s without it (PR 23,
#: CHANGES.md), and a cold smoke compiles two variants of the mixed wave
#: (worst-case schedule, then the demand-adapted one) plus the all-fast
#: singles wave — more than the 1200 s the driver gives.  Overflow then
#: goes to the oracle, and the fallback-share check below holds it to 5 %.
ENGINE = {"fused_retry_lanes": 0}
#: above this oracle-fallback share the device path is not doing the work
#: (README "Parity and tests": the tests assert the same 5 %)
MAX_FALLBACK_SHARE = 0.05
#: every wire call may sit behind a cold compile of the fused wave
WIRE_TIMEOUT_S = 1100.0


def say(key: str, **fields) -> None:
    print(json.dumps({"smoke": key, **fields}, sort_keys=True), flush=True)


@contextlib.contextmanager
def timed(seconds: dict, name: str):
    """Wall seconds of the block under ``seconds[name]`` (the last block
    of a name wins: ``warm_*`` is the last request of its kind)."""
    t0 = time.perf_counter()
    yield
    seconds[name] = round(time.perf_counter() - t0, 3)


@contextlib.contextmanager
def no_implicit_uploads(on: bool):
    """While ``on``: implicit host-to-device transfers raise, in every
    thread (the server's threads answer, so the process-wide setting, not
    jax's thread-local context manager)."""
    import jax

    was = jax.config.jax_transfer_guard_host_to_device
    if on:
        jax.config.update("jax_transfer_guard_host_to_device", "disallow")
    try:
        yield
    finally:
        jax.config.update("jax_transfer_guard_host_to_device", was)


def build_graph(tuples: int, seed: int):
    """The headline deployment: the ~10.6M-tuple Drive-style graph
    (BASELINE.json configs 3-5), every family scaled alike by --tuples."""
    from ketotpu.utils.synth import build_synth_columnar

    s = tuples / FULL_TUPLES
    return build_synth_columnar(
        n_users=max(int(1_200_000 * s), 64),
        n_groups=max(int(25_000 * s), 8),
        n_folders=max(int(500_000 * s), 32),
        n_docs=max(int(6_500_000 * s), 128),
        seed=seed,
    )


def http_json(url: str, body=None):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, method="POST" if data else "GET",
        headers={"Content-Type": "application/json"} if data else {},
    )
    with urllib.request.urlopen(req, timeout=WIRE_TIMEOUT_S) as resp:
        return json.loads(resp.read().decode())


def scrape(metrics_url: str) -> dict:
    """The ``keto_*`` series of one Prometheus scrape, as floats (a
    labelled series keeps its label text in the key)."""
    with urllib.request.urlopen(
        f"{metrics_url}/metrics/prometheus", timeout=60.0
    ) as resp:
        text = resp.read().decode()
    out = {}
    for line in text.splitlines():
        if line.startswith("keto_"):
            name, _, value = line.rpartition(" ")
            out[name] = float(value)
    return out


def bytes_on(devices, key: str) -> list:
    """``memory_stats()[key]`` of each device."""
    stats = [d.memory_stats() for d in devices]
    require(None not in stats, "a device reports no memory_stats")
    return [s[key] for s in stats]


def require(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def granted_checks(graph, seed: int, want: int):
    """Checks some grant in the store should allow.  A random (doc, user)
    pair is almost never allowed at this scale, so read grants back: a
    doc's own viewers, its parent folder's viewers, and a member of a
    group among those (one, two and three hops)."""
    import numpy as np

    from ketotpu.api.types import (
        RelationQuery,
        RelationTuple,
        SubjectID,
        SubjectSet,
    )

    def subjects(ns, obj, rel):
        found, _ = graph.store.get_relation_tuples(
            RelationQuery(namespace=ns, object=obj, relation=rel),
            page_size=8,
        )
        return [t.subject for t in found]

    rng = np.random.default_rng(seed + 2)
    out = []
    for _ in range(64 * want):
        if len(out) >= want:
            break
        doc = graph.docs[int(rng.integers(len(graph.docs)))]
        holders = subjects("Doc", doc, "viewers")
        for folder in subjects("Doc", doc, "parents"):
            holders += subjects("Folder", folder.object, "viewers")
        for h in list(holders):
            if isinstance(h, SubjectSet):
                holders += subjects(h.namespace, h.object, h.relation)
        users = [h for h in holders if isinstance(h, SubjectID)]
        if users:
            out.append(RelationTuple("Doc", doc, "view", users[-1]))
    return out


def single_checks(target: str, graph, oracle, seed: int, phases) -> int:
    """A few single gRPC Checks, allowed and denied, against the oracle."""
    import grpc

    from ketotpu.api.proto_codec import subject_to_proto
    from ketotpu.proto import check_service_pb2 as cs
    from ketotpu.proto import relation_tuples_pb2 as rts
    from ketotpu.proto.services import CheckServiceStub
    from ketotpu.utils.synth import synth_queries

    pool = granted_checks(graph, seed, 4)
    pool += synth_queries(graph, 4, seed=seed + 1)
    picked = [(q, bool(oracle.check_is_member(q, 0))) for q in pool]
    verdicts = {w for _, w in picked}
    require(verdicts == {True, False},
            f"the single checks hold only {verdicts} verdicts")
    with grpc.insecure_channel(target) as ch:
        stub = CheckServiceStub(ch)
        for i, (q, w) in enumerate(picked):
            req = cs.CheckRequest(tuple=rts.RelationTuple(
                namespace=q.namespace, object=q.object, relation=q.relation,
                subject=subject_to_proto(q.subject),
            ))
            name = "first_check" if i == 0 else "warm_check"
            with timed(phases, name):
                got = stub.Check(req, timeout=WIRE_TIMEOUT_S).allowed
            require(got == w, f"gRPC Check {q} = {got}, oracle says {w}")
    say("single_checks", allowed=sum(w for _, w in picked),
        denied=sum(not w for _, w in picked))
    return len(picked)


def batch_checks(read_url: str, graph, oracle, seed: int, phases,
                 batches: int, guard_last: bool) -> int:
    """``batches`` x BATCH mixed queries over REST, alternating the two
    batch routes (the scalar one and the columnar front door the SDK
    uses), each verdict compared with the oracle.  ``guard_last`` (the
    mesh run) forbids implicit host-to-device transfers during the last,
    warmed batch: the query pack goes up explicitly, a graph handed to the
    program as host arrays would not, and the fault it raises then counts
    as a device failure."""
    import numpy as np

    from ketotpu.api.types import RelationTuple
    from ketotpu.sdk import KetoClient
    from ketotpu.utils.synth import synth_queries_mixed

    client = KetoClient(read_url, timeout=WIRE_TIMEOUT_S, max_retries=0)
    for b in range(batches):
        # random pairs are denied almost always: an eighth of each batch
        # is granted checks, every other one through the AND/NOT permit
        granted = granted_checks(graph, seed + 10 + b, BATCH // 8)
        granted[::2] = [
            RelationTuple(q.namespace, q.object, "edit", q.subject)
            for q in granted[::2]
        ]
        qs = granted + synth_queries_mixed(
            graph, BATCH - len(granted), seed=seed + 10 + b
        )
        order = np.random.default_rng(seed + b).permutation(len(qs))
        qs = [qs[i] for i in order]
        name = "first_batch" if b == 0 else "warm_batch"
        guarded = guard_last and b == batches - 1
        with no_implicit_uploads(guarded), timed(phases, name):
            if b % 2 == 0:
                doc = http_json(
                    f"{read_url}/relation-tuples/check/batch",
                    {"tuples": [q.to_json() for q in qs]},
                )
                got = [bool(r["allowed"]) for r in doc["results"]]
            else:
                got = client.batch_check(qs)
        want = [bool(oracle.check_is_member(q, 0)) for q in qs]
        wrong = [i for i, (g, w) in enumerate(zip(got, want)) if g != w]
        require(
            len(got) == BATCH and not wrong,
            f"batch {b}: {len(wrong)} of {len(got)} verdicts differ from "
            f"the oracle, first {qs[wrong[0]] if wrong else None}",
        )
        require(sum(got) >= BATCH // 16, f"batch {b} allows only {sum(got)}")
        say("batch", index=b, allowed=sum(got), general=sum(
            q.relation == "edit" for q in qs
        ), seconds=phases[name], transfer_guard=guarded)
    return batches * BATCH


def expands(read_url: str, graph, seed: int, phases) -> int:
    """A few depth-5 Expands, each tree compared with the oracle's."""
    import numpy as np

    from ketotpu.api.types import SubjectSet
    from ketotpu.engine.oracle import ExpandEngine
    from ketotpu.sdk import KetoClient

    client = KetoClient(read_url, timeout=WIRE_TIMEOUT_S, max_retries=0)
    oracle = ExpandEngine(graph.store, max_depth=5)
    rng = np.random.default_rng(seed + 100)

    def pick(names):
        return names[int(rng.integers(len(names)))]

    # every 12th folder has both a user and a group among its viewers
    folders12 = graph.folders[::12]
    roots = [
        SubjectSet("Folder", pick(folders12), "viewers"),
        SubjectSet("Folder", pick(folders12), "viewers"),
        SubjectSet("Group", pick(graph.groups), "members"),
        SubjectSet("Group", graph.groups[0], "members"),  # nests g1
        SubjectSet("Doc", pick(graph.docs), "parents"),
    ]
    for i, root in enumerate(roots):
        name = "first_expand" if i == 0 else "warm_expand"
        with timed(phases, name):
            got = client.expand(root, max_depth=5)
        want = oracle.build_tree(root, 5)
        require(got == want, f"Expand {root} differs from the oracle's tree")
        require(got is not None, f"Expand {root} is empty")
    return len(roots)


def mesh_placement(eng, n: int) -> None:
    """--mesh only: the graph is ON the n chips before the first
    dispatch, about 1/n of it each."""
    import jax

    stacked = eng._stacked
    require(stacked is not None, "the mesh engine holds no sharded stacks")
    for k, v in stacked.items():
        require(isinstance(v, jax.Array), f"stack {k} is {type(v).__name__}")
        require(
            len(v.sharding.device_set) == n,
            f"stack {k} lives on {len(v.sharding.device_set)} device(s)",
        )
    in_use = bytes_on(jax.devices(), "bytes_in_use")
    total = sum(int(v.nbytes) for v in stacked.values())
    say("mesh_placement", bytes_in_use=in_use, stacked_bytes=total,
        per_device_share=[round(b / total, 3) for b in in_use])
    for b in in_use:
        require(
            0.5 * total / n <= b <= 2.0 * total / n,
            f"a device holds {b} bytes, not about 1/{n} of {total}",
        )


def smoke(args, devices) -> None:
    """Every phase; raises on the first that fails."""
    from ketotpu.driver import Provider, Registry
    from ketotpu.engine.oracle import CheckEngine
    from ketotpu.server import serve_all

    phases: dict = {}
    mesh = int(args.mesh)
    engine_block = {"kind": "tpu", "mesh_devices": mesh}
    engine_block.update(json.loads(args.engine))
    say("config", engine=engine_block, tuples=args.tuples, seed=args.seed,
        note="engine keys not listed keep the daemon's defaults")

    with timed(phases, "build"):
        graph = build_graph(args.tuples, args.seed)
    say("graph", tuples=len(graph.store), users=len(graph.users),
        groups=len(graph.groups), folders=len(graph.folders),
        docs=len(graph.docs))

    cfg = Provider({
        "serve": {
            n: {"host": "127.0.0.1", "port": 0}
            for n in ("read", "write", "metrics", "opl")
        },
        "engine": engine_block,
        # a cold fused wave compiles for minutes; the default 30 s budget
        # would answer the first requests 504 while the compile goes on
        "limit": {"request_timeout_ms": int(WIRE_TIMEOUT_S * 1000)},
        "log": {"request_log": False},
    })
    with timed(phases, "init"):
        reg = Registry(
            cfg, store=graph.store, namespace_manager=graph.manager
        ).init()
    eng = reg._device_engine()
    require(eng is not None, "engine.kind=tpu built no device engine")
    phases["projection"] = round(eng.projection_build_s, 3)
    phases["upload"] = round(eng.projection_upload_s, 3)
    in_use_after_init = bytes_on(devices, "bytes_in_use")
    srv = serve_all(reg)
    try:
        read = "%s:%d" % tuple(srv.addresses["read"])
        read_url = f"http://{read}"
        metrics_url = "http://%s:%d" % tuple(srv.addresses["metrics"])
        oracle = CheckEngine(graph.store, graph.manager)
        if mesh:
            mesh_placement(eng, mesh)
            n_checks = 0
        else:
            n_checks = single_checks(read, graph, oracle, args.seed, phases)
        n_checks += batch_checks(
            read_url, graph, oracle, args.seed, phases, args.batches,
            guard_last=bool(mesh),
        )
        after_checks = scrape(metrics_url)
        n_expands = expands(read_url, graph, args.seed, phases)
        final = scrape(metrics_url)
        health = http_json(f"{metrics_url}/health/ready")
        compiles = http_json(f"{metrics_url}/debug/compiles")
    finally:
        srv.stop()

    fallback_share = after_checks["keto_engine_oracle_fallbacks"] / n_checks
    say("phases", seconds=phases)
    say("compiles", count=compiles["compiles_total"],
        seconds=compiles["compile_seconds_total"],
        per_fn=compiles["per_fn"], cache_hits=compiles["cache_hits"],
        after_warm=compiles["compiles_after_warm"])
    say("device_memory", bytes_in_use_after_init=in_use_after_init,
        peak_bytes_in_use=bytes_on(devices, "peak_bytes_in_use"))
    say("counters",
        checks=n_checks, expands=n_expands,
        device_failures=final["keto_engine_device_failures"],
        dispatches=final["keto_engine_dispatches"],
        fused_waves=final["keto_fused_waves_total"],
        oracle_fallbacks_checks=after_checks["keto_engine_oracle_fallbacks"],
        oracle_fallbacks_all=final["keto_engine_oracle_fallbacks"],
        fallback_share=round(fallback_share, 5),
        device_retries=final["keto_engine_device_retries"],
        health=health)

    require(final["keto_engine_device_failures"] == 0,
            "the device path failed and the host answered in its place")
    require(final["keto_engine_dispatches"] > 0, "no device dispatch")
    if not mesh:
        require(final["keto_fused_waves_total"] > 0, "no fused wave ran")
    require(fallback_share < MAX_FALLBACK_SHARE,
            f"oracle-fallback share {fallback_share:.4f} >= "
            f"{MAX_FALLBACK_SHARE}")
    require(health == {"status": "ok"}, f"health says {health}")


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tuples", type=int, default=FULL_TUPLES,
                    help="graph size (default: the full ~10.6M deployment)")
    ap.add_argument("--batches", type=int, default=5,
                    help=f"REST batches of {BATCH} mixed queries")
    ap.add_argument("--mesh", type=int, default=0, choices=(0, 4),
                    help="4: run only the four-chip sharded path")
    ap.add_argument("--engine", default=json.dumps(ENGINE),
                    help="JSON merged over the daemon's default engine "
                         "block (default: %(default)s, see ENGINE); "
                         "printed on an early line")
    args = ap.parse_args(argv)

    # the cache is placed before jax compiles anything (ketotpu imports it)
    from ketotpu import compilewatch

    cache_dir = compilewatch.place_cache()
    import jax
    import jaxlib

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU here (platform {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    want = args.mesh or 1
    if len(devices) != want:
        print(f"chip_smoke: {len(devices)} device(s), this run needs {want}",
              file=sys.stderr)
        return 2
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "unknown"
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    say("device", **device, jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu, cache_dir=cache_dir)
    t0 = time.perf_counter()
    smoke(args, devices)
    say("total", seconds=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
