#!/usr/bin/env python3
"""Runs one cell of ``BENCHMARK.json`` once and prints its result line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Three kinds of process.  This one, the parent, never touches the chip: it
pins itself to the CPU backend, reads the cell's data files, starts the
server child (``server_child.py``, the only process on the chip), makes
the graph and the traffic from the seed, warms the cell's own shapes over
the wire, starts the client processes (``loadgen.py``), scrapes the
metrics port before and after the window, stops everything, and then
holds a sample of the answers the clients got against the plain reference
(``reference/zanzibar.py``).  ``--rehearsal`` runs a tiny graph on the
CPU end to end: it proves the plumbing and is never a speed.

Earlier lines (``{"bench": ...}``) carry what a reader of a run needs:
config, phase seconds, compile counts, counters, the generator's own
time, per-client counts.  The last line of standard output is the one
JSON object the driver reads.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse
import json
import multiprocessing
import os
import pickle
import shutil
import subprocess
import sys
import threading
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE]

import manifest  # noqa: E402
import stats  # noqa: E402
from reference.zanzibar import Reference  # noqa: E402

#: the child may compile a cold fused wave before it answers
CHILD_BOOT_TIMEOUT_S = 1150.0
#: a client waits this long for one answer ("a minute past the close")
ANSWER_TIMEOUT_S = 60.0
RUN_DIR = os.path.join(HERE, "out")


def say(key: str, **fields) -> None:
    print(json.dumps({"bench": key, **fields}, sort_keys=True), flush=True)


def http_json(url: str, method: str = "GET", timeout: float = 60.0):
    req = urllib.request.Request(url, method=method)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
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


class Child:
    """The server child and the JSON lines it speaks."""

    def __init__(self, cell, args, graph_seed: int, run_dir: str,
                 env: dict):
        self.lines = {}
        self.err_path = os.path.join(run_dir, "child.err")
        cmd = [
            sys.executable, os.path.join(HERE, "server_child.py"),
            "--config", os.path.join(run_dir, "config.json"),
            "--graph-seed", str(graph_seed), "--chips", str(cell.chips),
        ]
        if args.trace:
            cmd += ["--profile-dir", os.path.join(run_dir, "profile")]
        if args.rehearsal:
            cmd.append("--rehearsal")
        if args.fault:
            cmd += ["--fault", args.fault]
        if args.watch_stalls:
            cmd += ["--watch-stalls", str(args.watch_stalls)]
        self._err = open(self.err_path, "wb")
        self.proc = subprocess.Popen(
            cmd, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self._err, env=env, cwd=ROOT, text=True,
        )

    def wait_for(self, key: str, timeout: float) -> dict:
        """Read the child's lines up to its ``key`` line."""
        timer = threading.Timer(timeout, self.proc.kill)
        timer.start()
        try:
            for line in self.proc.stdout:
                try:
                    doc = json.loads(line)
                except ValueError:
                    continue
                if isinstance(doc, dict) and "child" in doc:
                    self.lines[doc["child"]] = doc
                    say("child_" + doc.pop("child"), **doc)
                    if key in self.lines:
                        return self.lines[key]
        finally:
            timer.cancel()
        raise ChildFailed(self.proc.wait(), self.err_tail())

    def finish(self) -> dict:
        self.proc.stdin.write("finish\n")
        self.proc.stdin.flush()
        got = self.wait_for("finished", 120.0)
        self.close()
        return got

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.proc.stdin.close()
                self.proc.wait(timeout=60.0)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait()
        self._err.close()

    def err_tail(self) -> str:
        self._err.flush()
        with open(self.err_path, "rb") as f:
            return f.read()[-4000:].decode(errors="replace")


class ChildFailed(RuntimeError):
    def __init__(self, code: int, tail: str):
        super().__init__(f"the server child ended with code {code}")
        self.code, self.tail = code, tail


def warm_up(cell, address, metrics_url: str, warm_items) -> dict:
    """Send the cell's own traffic until two rounds in a row compile
    nothing (the engine's own ``warm_after_clean``)."""
    mix = cell.traffic
    client = cell.kind.Client(address, CHILD_BOOT_TIMEOUT_S)
    per_round = int(mix["warm_requests_per_round"])
    clean, seen, rounds = 0, None, 0
    try:
        for rounds in range(1, int(mix["max_warm_rounds"]) + 1):
            t0 = time.monotonic()
            for wire, query in warm_items[(rounds - 1) * per_round:
                                          rounds * per_round]:
                ok, answer = client.call(wire)
                if not (ok and answered(cell.kind, query, answer)):
                    raise RuntimeError(
                        "a warm-up request failed: " + repr(answer[:300]))
            compiles = http_json(f"{metrics_url}/debug/compiles")
            total = compiles["compiles_total"]
            say("warm_round", round=rounds, compiles_total=total,
                seconds=time.monotonic() - t0)
            clean = clean + 1 if total == seen else 0
            seen = total
            if clean >= 2:
                return compiles
    finally:
        client.close()
    raise RuntimeError(
        f"still compiling after {rounds} warm-up rounds: the window would "
        "not be steady")


class Clients:
    """The client processes of one window: up and waiting once built."""

    def __init__(self, cell, address, pool, seconds: float, run_dir: str):
        import loadgen

        mix = cell.traffic
        n_proc = int(mix["processes"])
        ctx = multiprocessing.get_context("spawn")
        self.seconds = seconds
        self.ready, self.go = ctx.Semaphore(0), ctx.Event()
        self.t_start = ctx.Value("d", 0.0)
        indexed = [(i, wire) for i, (wire, _) in enumerate(pool)]
        self.outs = [os.path.join(run_dir, f"client_{p}.pkl")
                     for p in range(n_proc)]
        self.procs = [
            ctx.Process(target=loadgen.client_process, args=(
                mix["kind"], address, indexed[p::n_proc],
                int(mix["threads_per_process"]), seconds, ANSWER_TIMEOUT_S,
                self.ready, self.go, self.t_start, self.outs[p],
            ))
            for p in range(n_proc)
        ]
        for p in self.procs:
            p.start()

    def wait_ready(self) -> None:
        for _ in self.procs:
            if not self.ready.acquire(timeout=120.0):
                raise RuntimeError("a client process did not come up")

    def run(self, mid_window=None) -> list:
        """Open the window, wait for every client, return their records."""
        self.t_start.value = time.monotonic() + 0.25
        self.go.set()
        if mid_window is not None:
            mid_window(self.t_start.value)
        for p in self.procs:
            p.join(self.seconds + ANSWER_TIMEOUT_S + 60.0)
            if p.is_alive() or p.exitcode != 0:
                raise RuntimeError(
                    f"a client process ended badly ({p.exitcode})")
        records = []
        for path in self.outs:
            with open(path, "rb") as f:
                records += pickle.load(f)
        return records

    def close(self) -> None:
        for p in self.procs:
            if p.is_alive():
                p.kill()
            p.join()


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny graph on the CPU; proves plumbing, no speed")
    ap.add_argument("--control", action="store_true",
                    help="also put the control (PERF.md) in the program's "
                         "place on this run's sample and print its verdict, "
                         "which has to be false; the result line is unchanged")
    ap.add_argument("--watch-stalls", type=float, default=0.0,
                    help="by hand: the child writes every thread's stack to "
                         "its log when the engine stands still this long")
    ap.add_argument("--fault", default="",
                    help="tests only: break the timed path in the child")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "ketotpu")):
        print("run.py: the program (ketotpu/) is not in this checkout",
              file=sys.stderr)
        return 2
    doc = manifest.load()
    cell = manifest.cell(doc, args.workload)
    run_dir = os.path.join(RUN_DIR, cell.name)
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)

    # the child gets the machine's environment; this process and the
    # clients stay on the CPU backend and off the chip
    child_env = dict(os.environ)
    child_env.update(cell.config.get("env", {}))
    child_env.setdefault(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    if args.rehearsal:
        child_env["JAX_PLATFORMS"] = "cpu"
    os.environ["JAX_PLATFORMS"] = "cpu"

    seeds = cell.config["graph_seeds"]
    graph_seed = seeds[args.seed % len(seeds)]
    graph_params = cell.config[
        "rehearsal_graph" if args.rehearsal else "graph"]
    config = dict(cell.config)
    if args.rehearsal:
        config["engine"] = {**config["engine"],
                            **config.get("rehearsal_engine", {})}
    with open(os.path.join(run_dir, "config.json"), "w") as f:
        json.dump(config, f)
    say("config", cell=cell.name, seed=args.seed, graph_seed=graph_seed,
        seconds=args.seconds, trace=args.trace, rehearsal=args.rehearsal,
        engine=config["engine"], env=cell.config.get("env", {}),
        traffic=cell.traffic,
        note="engine keys not listed keep the daemon's defaults")

    child = Child(cell, args, graph_seed, run_dir, child_env)
    try:
        return measure(cell, args, child, graph_seed, graph_params, run_dir)
    except ChildFailed as e:
        print(e.tail, file=sys.stderr)
        print(f"run.py: {e}", file=sys.stderr)
        return e.code if e.code not in (0, None) else 1
    finally:
        child.close()


def measure(cell, args, child, graph_seed, graph_params, run_dir):
    import numpy as np

    mix = cell.traffic
    seconds = args.seconds

    # while the child builds, projects and uploads: the same graph here,
    # for the traffic and for the reference
    t0 = time.monotonic()
    world = cell.graph.build(graph_params, graph_seed)
    rng = np.random.default_rng([args.seed, 0x6B65746F])
    n_warm = int(mix["warm_requests_per_round"]) * int(mix["max_warm_rounds"])
    if args.rehearsal:  # a tiny graph holds fewer keys and answers
        mix = cell.traffic = {
            **mix, "pool_requests": mix["rehearsal_pool_requests"],
            "compare_at_least": mix["rehearsal_compare"]}
    pool = cell.kind.make_pool(world, mix, rng,
                               int(mix["pool_requests"]) + n_warm)
    pool, warm_items = pool[:-n_warm], pool[-n_warm:]
    say("traffic", tuples=len(world), pool_requests=len(pool),
        seconds=time.monotonic() - t0)

    serving = child.wait_for("serving", CHILD_BOOT_TIMEOUT_S)
    address = tuple(serving["addresses"]["read"])
    clients = Clients(cell, address, pool, seconds, run_dir)
    try:
        return window_and_after(cell, args, child, world, pool, warm_items,
                                clients, serving, run_dir)
    finally:
        clients.close()


def window_and_after(cell, args, child, world, pool, warm_items, clients,
                     serving, run_dir):
    mix, seconds = cell.traffic, args.seconds
    address = tuple(serving["addresses"]["read"])
    metrics_url = "http://%s:%d" % tuple(serving["addresses"]["metrics"])
    device = {k: child.lines["device"][k]
              for k in ("platform", "kind", "count")}
    compiles_before = warm_up(cell, address, metrics_url, warm_items)

    def profile(t_start):
        """Ask the child for a few seconds of device trace in the middle
        of the window (the call blocks for as long as it traces)."""
        span = min(float(mix.get("trace_seconds", 3.0)), seconds / 2)
        time.sleep(max(t_start + (seconds - span) / 2 - time.monotonic(), 0))
        got = http_json(f"{metrics_url}/debug/profile?seconds={span}",
                        method="POST", timeout=span + 120.0)
        say("profile", **got)

    clients.wait_ready()
    before = scrape(metrics_url)
    setup_s = time.monotonic() - T_PROCESS
    if args.watch_stalls:
        child.proc.stdin.write("watch\n")
        child.proc.stdin.flush()
    raw = clients.run(profile if args.trace else None)
    after = scrape(metrics_url)
    compiles_after = http_json(f"{metrics_url}/debug/compiles")
    health = http_json(f"{metrics_url}/health/ready")
    finished = child.finish()

    # -- the window's arithmetic ---------------------------------------------
    records, answers = [], {}
    for index, sent, lat, ok, answer, gap, wrapped in raw:
        query = pool[index][1]
        ok = bool(ok and answered(cell.kind, query, answer))
        records.append((sent, lat, ok, cell.kind.units(query)))
        if ok:
            answers[index] = answer
    failed = sum(1 for r in records if not r[2])
    gaps = sorted(r[5] for r in raw)
    say("window", **stats.summary(records, seconds),
        generator_gap_ms={"mean": 1e3 * sum(gaps) / max(len(gaps), 1),
                          "max": 1e3 * gaps[-1] if gaps else 0.0},
        pool_wrapped=sum(1 for r in raw if r[6]),
        clients=int(mix["processes"]) * int(mix["threads_per_process"]))
    compiles_in_window = (compiles_after["compiles_total"]
                          - compiles_before["compiles_total"])
    say("compiles", count=compiles_after["compiles_total"],
        seconds=compiles_after["compile_seconds_total"],
        cache_hits=compiles_after["cache_hits"],
        per_fn=compiles_after["per_fn"], in_window=compiles_in_window)
    counters = {
        k: after.get(k, 0.0) - before.get(k, 0.0) for k in (
            "keto_engine_dispatches", "keto_engine_oracle_fallbacks",
            "keto_engine_device_failures", "keto_engine_device_retries",
            "keto_fused_waves_total")
    }
    say("counters_in_window", **counters, health=health)

    values = {"setup_s": setup_s}
    for m in cell.end_to_end:
        how = mix["end_to_end"].get(m["name"])
        if m["name"] == "setup_s":
            continue
        if how is None:
            raise manifest.ManifestError(
                f"traffic {cell.traffic_name} does not say how "
                f"{m['name']} is read")
        values[m["name"]] = manifest.statistic(how["statistic"]).value(
            how, records, seconds)

    fallback_share = counters["keto_engine_oracle_fallbacks"] / max(
        sum(r[3] for r in records), 1)
    numbers, rows_per_unit = compare(cell, args, world, pool, answers, {
        "failed_requests": {"value": failed, "limit": 0},
        "compiles_and_device_failures_in_window": {
            "value": compiles_in_window
            + counters["keto_engine_device_failures"], "limit": 0},
        "oracle_fallback_share": {
            "value": fallback_share,
            "limit_below": float(mix["max_fallback_share"])},
        "health_ok": {"value": int(health == {"status": "ok"}),
                      "limit_at_least": 1},
    })

    peak = [b for b in finished["peak_bytes_in_use"] if b is not None]
    result = {
        "correct": all(held(n) for n in numbers.values()),
        "attempted": len(records),
        "failed": failed,
        "metrics": {},
        "device": {**device, "memory_peak_bytes": max(peak, default=0)},
    }
    if not args.trace:
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {
                "value": values[m["name"]], "unit": m["unit"]}
    else:
        import reduce_trace

        trace = None
        if not args.rehearsal:
            trace = reduce_trace.reduce_dir(os.path.join(run_dir, "profile"))
            result["device"]["busy_s"] = trace["busy_s"]
            result["device"]["window_s"] = trace["window_s"]
            result["breakdown"] = trace["breakdown"]
            say("trace", modules=trace["modules"], planes=trace["planes"],
                window_s=trace["window_s"], busy_s=trace["busy_s"])
        in_window = [r for r in records if r[2] and r[0] + r[1] <= seconds]
        delta = {k: after[k] - before.get(k, 0.0) for k in after}
        delta.update({
            "window.units": float(sum(r[3] for r in in_window)),
            "window.requests": float(len(in_window)),
            "window.seconds": float(seconds),
        })
        with open(os.path.join(HERE, "peaks.json")) as f:
            peaks = json.load(f)["devices"]
        ctx = {
            "delta": delta, "serving": serving, "finished": finished,
            "compiles_before": compiles_before,
            "compiles_after": compiles_after, "trace": trace,
            "rows_per_unit": rows_per_unit,
            "units_per_s": stats.rate(records, seconds),
            "peak": peaks.get(device["kind"]), "device": device,
        }
        for m, spec, reader in cell.per_layer:
            value = reader.read(spec, ctx)
            if value is not None:
                result["metrics"][m["name"]] = {
                    "value": value, "unit": m["unit"]}
    result["compared"] = numbers
    for name, number in numbers.items():
        print(f"compared {name}: {json.dumps(number)}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


def compare(cell, args, world, pool, answers, numbers: dict):
    """``correct``'s numbers: a sample of the answers the clients got,
    drawn from the seed, against the plain reference; each number with
    its limit, ``numbers`` (what the window itself showed) after them.
    Also the tuple rows the reference examined per answer."""
    import numpy as np

    mix, limits = cell.traffic, cell.config["limits"]
    t0 = time.monotonic()
    ref = Reference(world.cols, cell.graph.SCHEMA,
                    max_depth=limits["max_read_depth"],
                    max_width=limits["max_read_width"])
    done = sorted(answers)
    pick = np.random.default_rng([args.seed, 0x73616D70]).permutation(
        len(done))[: int(mix["compare_requests"])]
    sample = [pool[done[i]][1] for i in pick]
    expected = [cell.kind.expected(ref, world, query) for query in sample]
    compared = sum(len(want) for want in expected)

    def verdict(got) -> dict:
        """The compared numbers where the sample was answered ``got``."""
        return {
            "wrong_answers": {"value": sum(
                differing(g, want) for g, want in zip(got, expected)),
                "limit": 0},
            "answers_compared": {
                "value": compared,
                "limit_at_least": int(mix["compare_at_least"])},
            **numbers,
        }

    rows_per_unit = ref.rows_examined / max(compared, 1)
    say("reference", seconds=time.monotonic() - t0, requests=len(sample),
        allowed=sum(1 for want in expected for w in want
                    if w not in (None, False)),
        rows_examined_per_unit=rows_per_unit)
    if args.control:
        # the control in the program's place: the same sample, answered by
        # the reference with a stated guarantee broken, through the same
        # verdict; it has to come out as not correct
        depth = cell.config["control"]["max_depth"]
        control = Reference(world.cols, cell.graph.SCHEMA, max_depth=depth,
                            max_width=limits["max_read_width"])
        got = verdict([cell.kind.expected(control, world, query)
                       for query in sample])
        correct = all(held(n) for n in got.values())
        say("control", max_depth=depth, correct=correct, compared=got)
        print(f"control (max_depth {depth}) correct: {json.dumps(correct)}; "
              f"wrong_answers: {json.dumps(got['wrong_answers'])}",
              file=sys.stderr)
    return verdict([cell.kind.decode(query, answers[done[i]])
                    for i, query in zip(pick, sample)]), rows_per_unit


def held(number: dict) -> bool:
    """Whether a compared number keeps its limit."""
    value = number["value"]
    if "limit_at_least" in number:
        return value >= number["limit_at_least"]
    if "limit_below" in number:
        return value < number["limit_below"]
    return value <= number["limit"]


def answered(kind, query, answer) -> bool:
    """Whether an answer holds one item for every unit of its request."""
    got = kind.decode(query, answer)
    return got is not None and len(got) == kind.units(query)


def differing(got, want) -> int:
    """How many items of ``got`` differ from ``want``, item by item."""
    return sum(1 for g, w in zip(got, want) if g != w) + abs(
        len(got) - len(want))


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
