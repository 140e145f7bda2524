"""The profiler capture with what ``reduce_trace.load`` drops: the scope
of every device operation and the program's own host spans, on one clock.

``reduce_trace`` reads a capture through ``jax.profiler.ProfileData``,
which gives an event's name, start and duration.  Two things the program
writes into a capture are not there:

* **The named scope of a device operation** (``tier/fast/level2/probe/
  node_table`` ...).  Looked at on the v5e (PR 27, ``jax`` 0.9.0): an ``XLA
  Ops`` event is named by its HLO line *without* ``metadata={op_name=...}``
  and its own stats hold only device offsets; the scope is the stat
  ``tf_op`` of the event's **metadata** (``XEventMetadata.stats``, beside
  ``program_id``, ``hlo_category``, ``source``), e.g.
  ``jit(_wave_body)/tier/fast/level0/probe/node_table/gather:``.
  ``ProfileData`` shows event stats only, so this module parses the
  ``.xplane.pb`` itself, with protobuf and a description of the five
  messages it needs (no generated ``xplane_pb2`` is installed without
  TensorFlow).
* **Host spans**: ``jax.profiler.TraceAnnotation`` events sit on the lines
  of ``/host:CPU``, one line a thread.  Python's thread names are not the
  lines' names (every interpreter thread's line is called ``python``), so
  a thread is known by the spans it carries.  Kept here: every event
  named ``keto/...`` and the runtime's ``DoEnqueueProgram`` events, whose
  ``run_id`` stat pairs a launch with its ``XLA Modules`` event.

**The two clocks.**  In the probe capture the device's events read 0.9 to
1.2 ms *earlier* than the host events that launched them, steadily.
:func:`clock_shift_ns` is the least shift that puts every program's start
at or after its launch; readers add it to device times before they lay
host spans over idle gaps.

``load`` gives plain data (what the tests keep as ``.json``;
``python benchmark/trace_spans.py <x.xplane.pb> <out.json>`` writes a cut):

    {"device": [{"plane", "modules": [[name, start_ns, dur_ns, run_id, program_id]],
                 "ops": [[scope, start_ns, dur_ns, program_id]]}],
     "host": [{"line", "events": [[name, start_ns, dur_ns, run_id]]}]}
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

from reduce_trace import DEVICE_PLANE, MODULE_LINE, OPS_LINE
from reduce_trace import _union as union  # sorted, merged [start, end]

HERE = os.path.dirname(os.path.abspath(__file__))
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "keto/"
LAUNCH_EVENT = "DoEnqueueProgram"

_loaded: dict = {}
_xspace = None


def _xspace_class():
    """The ``XSpace`` message class, built from a description of
    tsl/profiler/protobuf/xplane.proto's fields (maps as their entries)."""
    global _xspace
    if _xspace is not None:
        return _xspace
    from google.protobuf import (
        descriptor_pb2,
        descriptor_pool,
        message_factory,
    )

    F = descriptor_pb2.FieldDescriptorProto
    i64, u64, dbl = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE
    txt, raw = F.TYPE_STRING, F.TYPE_BYTES
    messages = {
        "XStat": [("metadata_id", 1, i64), ("double_value", 2, dbl),
                  ("uint64_value", 3, u64), ("int64_value", 4, i64),
                  ("str_value", 5, txt), ("bytes_value", 6, raw),
                  ("ref_value", 7, u64)],
        "XEvent": [("metadata_id", 1, i64), ("offset_ps", 2, i64),
                   ("duration_ps", 3, i64), ("stats", 4, "*XStat")],
        "XLine": [("name", 2, txt), ("timestamp_ns", 3, i64),
                  ("events", 4, "*XEvent")],
        "XEventMetadata": [("id", 1, i64), ("name", 2, txt),
                           ("stats", 5, "*XStat")],
        "XStatMetadata": [("id", 1, i64), ("name", 2, txt)],
        "EventMetadataEntry": [("key", 1, i64), ("value", 2, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, i64), ("value", 2, "XStatMetadata")],
        "XPlane": [("name", 2, txt), ("lines", 3, "*XLine"),
                   ("event_metadata", 4, "*EventMetadataEntry"),
                   ("stat_metadata", 5, "*StatMetadataEntry")],
        "XSpace": [("planes", 1, "*XPlane")],
    }
    fd = descriptor_pb2.FileDescriptorProto(
        name="keto_xplane.proto", package="keto_xplane", syntax="proto3")
    for name, fields in messages.items():
        msg = fd.message_type.add(name=name)
        for fname, number, kind in fields:
            f = msg.field.add(name=fname, number=number,
                              label=F.LABEL_OPTIONAL)
            if isinstance(kind, str):
                f.type = F.TYPE_MESSAGE
                f.type_name = ".keto_xplane." + kind.lstrip("*")
                if kind.startswith("*"):
                    f.label = F.LABEL_REPEATED
            else:
                f.type = kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    _xspace = message_factory.GetMessageClass(
        pool.FindMessageTypeByName("keto_xplane.XSpace"))
    return _xspace


def _stat(stats, names: dict, wanted: str):
    """The value of the stat called ``wanted`` (a string stat may be held
    by reference to a stat name), or None."""
    for s in stats:
        if names.get(s.metadata_id) == wanted:
            return (s.str_value or names.get(s.ref_value)
                    or s.int64_value or s.uint64_value or None)
    return None


def load(path: str) -> dict:
    """One capture as plain data (module docstring); once a process."""
    if path in _loaded:
        return _loaded[path]
    if path.endswith(".json"):
        with open(path) as f:
            data = json.load(f)
    else:
        space = _xspace_class()()
        with open(path, "rb") as f:
            space.ParseFromString(f.read())
        data = {"device": [], "host": []}
        for plane in space.planes:
            names = {e.key: e.value.name for e in plane.stat_metadata}
            meta = {e.key: e.value for e in plane.event_metadata}
            if DEVICE_PLANE.match(plane.name):
                data["device"].append(_device_plane(plane, names, meta))
            elif plane.name == HOST_PLANE:
                data["host"] += _host_lines(plane, names, meta)
    _loaded[path] = data
    return data


def _device_plane(plane, names, meta) -> dict:
    out = {"plane": plane.name, "modules": [], "ops": []}
    # what an operation's metadata says is the same for all its events
    of_op = {k: (_stat(m.stats, names, "tf_op") or "",
                 _stat(m.stats, names, "program_id") or 0)
             for k, m in meta.items()}
    for line in plane.lines:
        t0 = line.timestamp_ns
        if line.name == MODULE_LINE:
            for e in line.events:
                name = meta[e.metadata_id].name
                program = re.search(r"\((\d+)\)$", name)
                out["modules"].append([
                    name, t0 + e.offset_ps / 1e3, e.duration_ps / 1e3,
                    _stat(e.stats, names, "run_id") or 0,
                    int(program.group(1)) if program else 0])
        elif line.name == OPS_LINE:
            for e in line.events:
                scope, program = of_op[e.metadata_id]
                out["ops"].append([scope, t0 + e.offset_ps / 1e3,
                                   e.duration_ps / 1e3, program])
    return out


def _host_lines(plane, names, meta) -> list:
    keep = {k: m.name for k, m in meta.items()
            if m.name.startswith(SPAN_PREFIX) or m.name == LAUNCH_EVENT}
    lines = []
    for line in plane.lines:
        events = [[keep[e.metadata_id], line.timestamp_ns + e.offset_ps / 1e3,
                   e.duration_ps / 1e3, _stat(e.stats, names, "run_id") or 0]
                  for e in line.events if e.metadata_id in keep]
        if events:
            lines.append({"line": line.name, "events": events})
    return lines


def newest_capture() -> str | None:
    """The newest ``*.xplane.pb`` under ``benchmark/out/*/``: this run's,
    since ``run.py`` clears its cell's directory before it starts."""
    found = glob.glob(os.path.join(HERE, "out", "*", "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def of_run(ctx: dict) -> dict | None:
    """The capture of the run a reader is called for, or None where the
    run has none (off the chip ``ctx["trace"]`` is None)."""
    if ctx.get("trace") is None:
        return None
    path = newest_capture()
    return load(path) if path else None


# -- intervals -----------------------------------------------------------------


def intersect(a: list, b: list) -> list:
    """The overlap of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        start, end = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if start < end:
            out.append([start, end])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: list, b: list) -> list:
    """What of merged list ``a`` lies outside merged list ``b``."""
    if not a:
        return []
    lo, hi = a[0][0], a[-1][1]
    gaps, at = [], lo
    for start, end in b:
        if end <= lo or start >= hi:
            continue
        if start > at:
            gaps.append([at, start])
        at = max(at, end)
    if at < hi:
        gaps.append([at, hi])
    return intersect(a, gaps)


def total(intervals) -> float:
    return sum(end - start for start, end in intervals)


def clock_shift_ns(data: dict) -> float:
    """Nanoseconds to add to device times: the least that puts every
    program's start at or after its own launch on the host (paired by
    ``run_id``); 0 where the capture pairs nothing."""
    launched = {e[3]: e[1] for line in data["host"] for e in line["events"]
                if e[0] == LAUNCH_EVENT and e[3]}
    late = [launched[m[3]] - m[1] for plane in data["device"]
            for m in plane["modules"] if m[3] in launched]
    return max(late + [0.0])


# -- a cut small enough to keep beside the tests -------------------------------


def shrink(data: dict, modules: int = 6) -> dict:
    """The first ``modules`` program executions of each device plane, the
    operations inside them (consecutive ones of one scope merged), and the
    host events that overlap that stretch."""
    out = {"device": [], "host": []}
    lo, hi = float("inf"), 0.0
    for plane in data["device"]:
        mods = sorted(plane["modules"], key=lambda m: m[1])[:modules]
        if not mods:
            continue
        start, end = mods[0][1], mods[-1][1] + mods[-1][2]
        lo, hi = min(lo, start), max(hi, end)
        ops = []
        for scope, t, dur, program in sorted(
                (o for o in plane["ops"] if start <= o[1] < end),
                key=lambda o: o[1]):
            scope = scope.rsplit("/", 1)[0] + "/"  # without the primitive
            if ops and ops[-1][0] == scope and ops[-1][3] == program and (
                    t - (ops[-1][1] + ops[-1][2]) < 2.0):
                ops[-1][2] = t + dur - ops[-1][1]
            else:
                ops.append([scope, t, dur, program])
        out["device"].append(
            {"plane": plane["plane"], "modules": mods, "ops": ops})
    shift = clock_shift_ns(data)
    for line in data["host"]:
        events = [e for e in line["events"]
                  if e[1] + e[2] >= lo + shift - 5e6 and e[1] <= hi + shift + 5e6]
        if events:
            out["host"].append({"line": line["line"], "events": events})
    return out


if __name__ == "__main__":
    src, dst = sys.argv[1:3]
    with open(dst, "w") as f:
        json.dump(shrink(load(src)), f)
