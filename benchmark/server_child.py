#!/usr/bin/env python3
"""The one process that holds the chip: the real daemon, in-process, on
the cell's graph.  ``chip_smoke.py``'s bootstrap, copied.

Started by ``run.py``; speaks JSON lines on stdout (``{"child": ...}``) and
reads one-word commands on stdin: ``finish`` prints the device's memory
and the engine's phase seconds, stops the server and ends the process;
``watch`` (by hand, ``--watch-stalls``) starts the stall watch.
Refuses anything but a TPU with the cell's chips (exit 2) unless
``--rehearsal`` is given, which runs a tiny graph on the CPU.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

#: every wire call may sit behind a cold compile of the fused wave
REQUEST_TIMEOUT_S = 1100.0


def say(key: str, **fields) -> None:
    print(json.dumps({"child": key, **fields}, sort_keys=True), flush=True)


def plant_fault(eng, fault: str) -> None:
    """Tests only (``tests/test_faults.py``): break the timed path where
    an answer is produced, underneath the front doors."""
    if fault == "flip_verdict":
        finish = eng._finish_chunk

        def flipped(*a, **kw):
            allowed = finish(*a, **kw)
            if len(allowed):
                allowed[0] = not allowed[0]
            return allowed

        eng._finish_chunk = flipped
    elif fault == "prune_tree":
        expand = eng.batch_expand

        def pruned(*a, **kw):
            trees = expand(*a, **kw)
            for t in trees:
                if t is not None and t.children:
                    t.children.pop()
            return trees

        eng.batch_expand = pruned
    else:
        raise ValueError(f"unknown fault {fault!r}")


def watch_stalls(eng, limit_s: float) -> None:
    """By hand (``run.py --watch-stalls``): whenever no engine phase has
    ended for ``limit_s`` seconds, write every thread's stack to the log,
    and how long the engine stood still once it moves again."""
    import faulthandler
    import threading

    def watch():
        t0 = moved = time.monotonic()
        seen, dumped = sum(list(eng.phase_counts.values())), False
        while True:
            time.sleep(0.05)
            now, count = time.monotonic(), sum(list(eng.phase_counts.values()))
            if count != seen:
                if dumped:
                    print(f"stall watch: moved again after {now - moved:.2f} "
                          f"s, {now - t0:.2f} s into the window",
                          file=sys.stderr, flush=True)
                seen, moved, dumped = count, now, False
            elif now - moved > limit_s and not dumped:
                print(f"stall watch: no engine phase has ended for "
                      f"{now - moved:.2f} s, {now - t0:.2f} s into the window;"
                      f" phase counts {dict(eng.phase_counts)}",
                      file=sys.stderr, flush=True)
                faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
                dumped = True

    threading.Thread(target=watch, daemon=True).start()


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--graph-seed", type=int, required=True)
    ap.add_argument("--chips", type=int, required=True)
    ap.add_argument("--profile-dir", default="")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--fault", default="")
    ap.add_argument("--watch-stalls", type=float, default=0.0)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    with open(args.config) as f:
        config = json.load(f)

    # the cache is placed before jax compiles anything (ketotpu imports it)
    from ketotpu import compilewatch

    cache_dir = compilewatch.place_cache()
    import jax
    import jaxlib

    devices = jax.devices()
    platform = devices[0].platform
    if not args.rehearsal:
        if platform != "tpu":
            print(f"server_child: no TPU here (platform {platform}); "
                  "nothing was run", file=sys.stderr)
            return 2
        if len(devices) != args.chips:
            print(f"server_child: {len(devices)} device(s), this cell "
                  f"needs {args.chips}", file=sys.stderr)
            return 2
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "unknown"
    say("device", platform=platform, kind=devices[0].device_kind,
        count=len(devices), jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu, cache_dir=cache_dir)

    import manifest
    from ketotpu.driver import Provider, Registry
    from ketotpu.server import serve_all

    phases = {}
    t0 = time.perf_counter()
    params = config["rehearsal_graph" if args.rehearsal else "graph"]
    world = manifest.graph(params["kind"]).build(params, args.graph_seed)
    store, manager = world.server_store()
    phases["build"] = time.perf_counter() - t0
    say("graph", tuples=len(world), seed=args.graph_seed)

    def merged(base: dict, over: dict) -> dict:
        out = dict(base)
        for k, v in over.items():
            out[k] = merged(out[k], v) if isinstance(
                v, dict) and isinstance(out.get(k), dict) else v
        return out

    cfg = Provider(merged(config.get("daemon", {}), {
        "serve": {
            n: {"host": "127.0.0.1", "port": 0}
            for n in ("read", "write", "metrics", "opl")
        },
        "engine": config["engine"],
        "limit": {
            "request_timeout_ms": int(REQUEST_TIMEOUT_S * 1000),
            "max_read_depth": config["limits"]["max_read_depth"],
            "max_read_width": config["limits"]["max_read_width"],
        },
        "log": {"request_log": False},
        "observability": {"profiler": {
            "enabled": bool(args.profile_dir), "dir": args.profile_dir,
        }},
    }))
    t0 = time.perf_counter()
    reg = Registry(cfg, store=store, namespace_manager=manager).init()
    phases["init"] = time.perf_counter() - t0
    eng = reg._device_engine()
    if eng is None:
        raise RuntimeError("engine.kind=tpu built no device engine")
    if args.fault:
        plant_fault(eng, args.fault)
    phases["projection"] = eng.projection_build_s
    phases["upload"] = eng.projection_upload_s

    def memory(key):
        stats = [d.memory_stats() for d in devices]
        return [s[key] if s else None for s in stats]

    in_use = memory("bytes_in_use")
    srv = serve_all(reg)
    try:
        say("serving", addresses={
            k: list(v) for k, v in srv.addresses.items()
        }, bytes_in_use_after_init=in_use, phases=phases,
            seconds_to_serve=time.perf_counter() - t_start)
        for line in sys.stdin:
            if line.strip() == "finish":
                break
            if line.strip() == "watch" and args.watch_stalls:
                watch_stalls(eng, args.watch_stalls)
        say("finished", peak_bytes_in_use=memory("peak_bytes_in_use"),
            bytes_in_use=memory("bytes_in_use"),
            engine_phase_seconds=dict(eng.phase_seconds),
            engine_phase_counts=dict(eng.phase_counts),
            gen_schedules={
                str(k): repr(v) for k, v in eng._gen_sched_cache.items()
            },
            adaptive_mults=repr(eng._adaptive_mults()))
    finally:
        srv.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
