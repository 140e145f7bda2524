"""``/debug/compiles``: a field at the window's start, or how far it
moved over the window (``"delta": true``)."""


def read(spec: dict, ctx: dict):
    field = spec["field"]
    after = ctx["compiles_after"].get(field)
    before = ctx["compiles_before"].get(field)
    if before is None or after is None:
        return None
    return float(after - before) if spec.get("delta") else float(before)
