"""How unevenly a labelled series moved over the window: the largest
label value's delta over the mean of all of them (1.0 is even).

``series`` names the series of the metrics port's scrape, ``label`` the
label whose values are compared (``keto_mesh_shard_batches`` by
``shard``).  ``scrape_delta`` divides sums; this needs the parts.  Fewer
than two label values, or nothing moved: nothing to read."""

from __future__ import annotations

from readers.scrape_delta import LABEL


def parts(delta: dict, series: str, label: str) -> dict:
    """``{label value: delta}`` of ``series``."""
    out = {}
    for key, value in delta.items():
        name, _, rest = key.partition("{")
        if name == series:
            at = dict(LABEL.findall(rest)).get(label)
            if at is not None:
                out[at] = out.get(at, 0.0) + value
    return out


def read(spec: dict, ctx: dict):
    moved = parts(ctx["delta"], spec["series"], spec["label"])
    whole = sum(moved.values())
    if len(moved) < 2 or whole <= 0:
        return None
    return max(moved.values()) * len(moved) / whole
