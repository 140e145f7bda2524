"""A quotient of window deltas: ``scale * sum(over) / sum(per)``.

``over`` and ``per`` list series of the metrics port's scrape, each
``{"name": ..., "labels": {label: value}, "only_labels": {label: [values]}}``,
or a
window counter by its bare name (``window.units``, ``window.requests``,
``window.seconds``).  Nothing moved below: nothing to read."""

from __future__ import annotations

import re

LABEL = re.compile(r'(\w+)="([^"]*)"')


def _sum(delta: dict, wanted: list) -> float:
    total = 0.0
    for w in wanted:
        if isinstance(w, str):
            total += delta.get(w, 0.0)
            continue
        for key, value in delta.items():
            name, _, rest = key.partition("{")
            if name != w["name"]:
                continue
            labels = dict(LABEL.findall(rest))
            if any(labels.get(k) != v
                   for k, v in w.get("labels", {}).items()):
                continue
            if any(labels.get(k) not in vs
                   for k, vs in w.get("only_labels", {}).items()):
                continue
            total += value
    return total


def read(spec: dict, ctx: dict):
    per = _sum(ctx["delta"], spec["per"])
    if per <= 0:
        return None
    return float(spec.get("scale", 1.0)) * _sum(ctx["delta"], spec["over"]) / per
