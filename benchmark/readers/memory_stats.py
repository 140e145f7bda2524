"""``memory_stats()`` of the fullest chip, as the child read it after the
window."""


def read(spec: dict, ctx: dict):
    values = [v for v in ctx["finished"].get(spec["field"], []) if v]
    return float(max(values)) if values else None
