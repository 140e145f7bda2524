"""What of a closed loop's cycle no server stage covers, per request:
``scale * clients / units_per_s`` (each of ``clients`` waits for its
answer before it sends again, so a request's cycle is the clients over
the rate) less ``scale * sum(minus) / sum(per)``, the window deltas of the
server's stages (``scrape_delta``'s series).  A series of ``minus`` that
the scrape does not carry: nothing to read."""

from __future__ import annotations

from readers.scrape_delta import LABEL, _sum


def _carried(delta: dict, want: dict) -> bool:
    for key in delta:
        name, _, rest = key.partition("{")
        labels = dict(LABEL.findall(rest))
        if name == want["name"] and all(
                labels.get(k) == v for k, v in want.get("labels", {}).items()):
            return True
    return False


def read(spec: dict, ctx: dict):
    delta, rate = ctx["delta"], ctx["units_per_s"]
    per = _sum(delta, spec["per"])
    if rate <= 0 or per <= 0 or not all(
            _carried(delta, w) for w in spec["minus"]):
        return None
    scale = float(spec.get("scale", 1.0))
    return scale * (float(spec["clients"]) / rate
                    - _sum(delta, spec["minus"]) / per)
