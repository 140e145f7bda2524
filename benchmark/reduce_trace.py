"""From a profiler trace to numbers: busy and idle time of the device,
device time per XLA module, the heaviest device operations, the longest
idle gaps.  The yardstick for every device_trace metric.

A trace is read into plain data first (``load``): a list of planes, each
``{"name", "lines": [{"name", "events": [[name, start_ns, dur_ns], ...]}]}``.
``load`` reads the profiler's ``.xplane.pb`` (through
``jax.profiler.ProfileData``, in a process that does not hold the chip) or
the same data as ``.json`` (the recorded trace the tests keep;
``python benchmark/reduce_trace.py <x.xplane.pb> <out.json>`` writes one).

What the v5e's trace looks like (looked at by hand, PR 26): one plane
``/device:TPU:0`` per chip with the lines ``XLA Modules`` (one event per
execution of a jitted program, named ``jit_<fn>(<fingerprint>)``: the
fused wave is ``jit__wave_body``), ``XLA Ops`` (one event per operation
inside it, named by its whole HLO line; 16,000 to a 1024-row wave) and
``Async XLA Ops`` (copies, which overlap the ops); host threads, the
Python tracer's among them, sit in ``/host:CPU`` and go on for a second
and more after the device's last event while the profiler stops.  So the
traced window is the span of the device planes' own events, and busy time
the union of the ``XLA Ops`` intervals in it (of ``XLA Modules`` where a
plane has no ops line).
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE, OPS_LINE = "XLA Modules", "XLA Ops"


def load(path: str) -> list:
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                [e.name, int(e.start_ns), int(e.duration_ns)]
                for e in line.events
            ]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def find_trace(profile_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return found[-1]


def module_name(event_name: str) -> str:
    """``jit_run_fused_wave(1234)`` -> ``jit_run_fused_wave``."""
    return event_name.split("(", 1)[0]


def _union(intervals) -> list:
    """Sorted, merged ``[start, end]`` intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _line(plane, name):
    return next((l for l in plane["lines"] if l["name"] == name), None)


TABLE = re.compile(r"%g__(\w+?)__\.")
OP_KIND = re.compile(r"\s([a-z][\w\-]*)\(")
OP_SHAPE = re.compile(r" = \(?(\w+\[[\d,]*\])")


def op_class(hlo_line: str) -> str:
    """An operation's class for the breakdown: what it is, which of the
    graph's tables it reads and the shape it makes,
    ``fusion(edge_hi)->s32[16384]`` for ``%fusion.4039 = s32[16384]{...}
    fusion(s32[8388608]{...} %g__edge_hi__.1, ...)``.  A wave has
    thousands of operations alike; by class they say where the time
    goes."""
    kind = OP_KIND.search(hlo_line)
    shape = OP_SHAPE.search(hlo_line)
    tables = sorted(set(TABLE.findall(hlo_line)))
    head = kind.group(1) if kind else hlo_line.split(" ", 1)[0][:40]
    return f"{head}({','.join(tables)})" + (
        f"->{shape.group(1)}" if shape else "")


def reduce(planes: list) -> dict:
    """``busy_s`` and per-module seconds averaged over the device planes;
    ``window_s`` the span from the first to the last device event."""
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    if not devices:
        raise ValueError(
            "the trace holds no device plane: "
            + ", ".join(p["name"] for p in planes))
    spans = [(e[1], e[1] + e[2]) for p in devices for l in p["lines"]
             if l["name"] in (MODULE_LINE, OPS_LINE) for e in l["events"]]
    if not spans:
        raise ValueError("no operation ran on the device in the trace")
    window_ns = max(e for _, e in spans) - min(s for s, _ in spans)
    busy_ns, modules, ops, gaps = 0, {}, {}, {}
    for plane in devices:
        mods = _line(plane, MODULE_LINE)
        busy_line = _line(plane, OPS_LINE) or mods
        if busy_line is None:
            continue
        merged = _union((e[1], e[1] + e[2]) for e in busy_line["events"])
        busy_ns += sum(end - start for start, end in merged)
        for name, _, dur in (mods["events"] if mods else []):
            m = modules.setdefault(module_name(name), [0, 0])
            m[0] += dur
            m[1] += 1
        for name, _, dur in busy_line["events"]:
            name = op_class(name)
            ops[name] = ops.get(name, 0) + dur
        # an idle gap is named by the programs on either side of it
        runs = sorted((e[1], e[1] + e[2], module_name(e[0]))
                      for e in (mods["events"] if mods else []))
        for (_, end, a), (start, _, b) in zip(runs, runs[1:]):
            if start > end:
                key = f"after {a}, before {b}"
                gaps[key] = gaps.get(key, 0) + (start - end)
    n = len(devices)

    def top(table):
        return [[k, v / n / 1e9] for k, v in sorted(
            table.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "planes": [p["name"] for p in planes],
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "modules": {k: {"seconds": v[0] / n / 1e9, "executions": v[1] / n}
                    for k, v in modules.items()},
        "breakdown": {"device_ops": top(ops), "idle_gaps": top(gaps)},
    }


def reduce_dir(profile_dir: str) -> dict:
    return reduce(load(find_trace(profile_dir)))


def shrink(planes: list, keep_events: int = 400) -> list:
    """A cut of a trace small enough to keep beside the tests: the device
    planes' module and ops lines, the first ``keep_events`` events each."""
    out = []
    for p in planes:
        if not DEVICE_PLANE.match(p["name"]):
            continue
        out.append({"name": p["name"], "lines": [
            {"name": l["name"], "events": sorted(
                l["events"], key=lambda e: e[1])[:keep_events]}
            for l in p["lines"] if l["name"] in (MODULE_LINE, OPS_LINE)
        ]})
    return out


if __name__ == "__main__":
    src, dst = sys.argv[1:3]
    with open(dst, "w") as f:
        json.dump(shrink(load(src)), f)
