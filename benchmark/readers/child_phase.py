"""Seconds the server child reports for its own set-up phases (its
``serving`` line): the sum of the phases the metric names."""


def read(spec: dict, ctx: dict):
    phases = ctx["serving"]["phases"]
    if not all(p in phases for p in spec["phases"]):
        return None
    return float(sum(phases[p] for p in spec["phases"]))
