"""What the host was doing while the device stood idle: a share of the
traced span, in percent, by the program's own host spans.

The device's idle time is the traced span less the union of its
operations (``reduce_trace``'s, so the three shares below add up to
``device_idle_pct``).  Over it lie the host spans the program writes into
the capture (``keto/engine/<phase>``, ``keto/coalesce/<state>``), moved
onto the device's clock (``trace_spans.clock_shift_ns``).  An idle instant
is, in this order:

``host_work``  some thread has an engine phase open, or the coalescer
               prepares or files a wave: the host is doing the work that
               comes between two device programs;
``unnamed``    the dispatcher is inside ``serve`` (or the collector blocked
               behind it) with no engine phase open, or no span is open at
               all: host time that no span names yet;
``starved``    only ``idle``, ``window`` or ``stage_empty`` are open: the
               engine waits for requests.

``spans`` names which of the three the metric reads.  A capture without
the program's spans (an older tree's) has nothing to read: None.  Each
span's share of the idle time goes to standard error once a run."""

import sys

import trace_spans
from trace_spans import intersect, subtract, total, union

ENGINE = "keto/engine/"
COALESCE = "keto/coalesce/"
WORK_STATES = ("prepare", "file")
STARVED_STATES = ("idle", "window", "stage_empty")

_splits: dict = {}  # id of a loaded capture -> split(), once a process


def split(data: dict) -> dict | None:
    """Seconds of ``window``, ``idle`` and of each class of idle time,
    averaged over the device planes; ``by_span`` every span's overlap with
    the idle time."""
    spans = {}
    for line in data["host"]:
        for name, start, dur, _ in line["events"]:
            if name.startswith(trace_spans.SPAN_PREFIX):
                spans.setdefault(name, []).append((start, start + dur))
    if not spans or not data["device"]:
        return None
    spans = {name: union(got) for name, got in spans.items()}

    def over(names):
        return union(i for n in names for i in spans.get(n, []))

    work = over([n for n in spans if n.startswith(ENGINE)]
                + [COALESCE + s for s in WORK_STATES])
    starved = over(COALESCE + s for s in STARVED_STATES)
    other = over(n for n in spans if n.startswith(COALESCE)
                 and n[len(COALESCE):] not in WORK_STATES + STARVED_STATES)
    shift = trace_spans.clock_shift_ns(data)
    out = {"window": 0.0, "idle": 0.0, "host_work": 0.0, "starved": 0.0,
           "unnamed": 0.0, "by_span": {}, "shift_ms": shift / 1e6}
    n = len(data["device"])
    for plane in data["device"]:
        busy = plane["ops"] or plane["modules"]
        busy = union((b[1] + shift, b[1] + b[2] + shift) for b in busy)
        every = [(e[1] + shift, e[1] + e[2] + shift)
                 for e in plane["ops"] + plane["modules"]]
        if not every:
            continue
        lo, hi = min(s for s, _ in every), max(e for _, e in every)
        idle = subtract([[lo, hi]], busy)
        in_work = intersect(idle, work)
        rest = subtract(idle, work)
        in_other = intersect(rest, other)
        rest = subtract(rest, other)
        in_starved = intersect(rest, starved)
        out["window"] += (hi - lo) / n / 1e9
        out["idle"] += total(idle) / n / 1e9
        out["host_work"] += total(in_work) / n / 1e9
        out["starved"] += total(in_starved) / n / 1e9
        out["unnamed"] += (total(in_other) + total(rest)
                           - total(in_starved)) / n / 1e9
        for name, got in spans.items():
            out["by_span"][name] = out["by_span"].get(name, 0.0) + total(
                intersect(idle, got)) / n / 1e9
    return out if out["window"] > 0 else None


def say(got: dict) -> None:
    idle = got["idle"] or 1.0
    print(f"trace_idle_split: window {got['window']:.4f} s, idle "
          f"{got['idle']:.4f} s ({100 * got['idle'] / got['window']:.2f} %): "
          f"host_work {got['host_work']:.4f}, starved {got['starved']:.4f}, "
          f"unnamed {got['unnamed']:.4f} s; device clock moved by "
          f"{got['shift_ms']:.3f} ms", file=sys.stderr)
    for name, s in sorted(got["by_span"].items(), key=lambda kv: -kv[1]):
        print(f"  {s:9.4f} s {100 * s / idle:6.2f} % of idle  {name}",
              file=sys.stderr)


def read(spec: dict, ctx: dict):
    data = trace_spans.of_run(ctx)
    if data is None:
        return None
    if id(data) not in _splits:
        _splits[id(data)] = split(data)
        if _splits[id(data)] is not None:
            say(_splits[id(data)])
    got = _splits[id(data)]
    if got is None:
        return None
    return 100.0 * got[spec["spans"]] / got["window"]
