"""Seconds the server child's engine filed under its own phases (the
``engine_phase_seconds`` of the child's ``finished`` line, read after the
window): the sum of the phases the metric names, where the engine filed
every one of them."""


def read(spec: dict, ctx: dict):
    phases = ctx["finished"].get("engine_phase_seconds", {})
    if not all(p in phases for p in spec["phases"]):
        return None
    return float(sum(phases[p] for p in spec["phases"]))
