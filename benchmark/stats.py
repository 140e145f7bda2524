"""The arithmetic of the end-to-end metrics.  No I/O.

A request is a record ``(sent_s, latency_s, ok, units)``: when it was sent
(seconds after the window opened), how long until the last byte of its
answer, whether it was answered as asked, and how many verdicts or trees
it stands for.
"""

from __future__ import annotations

import math

#: a percentile is reported only with this many samples beyond it
SAMPLES_BEYOND = 10


class TooFewSamples(ValueError):
    """The window holds too few requests for the tail the cell reports."""


def percentile(latencies_s, failed: int, q: float) -> float:
    """The ``q`` quantile (nearest rank) of all requests of a window, in
    seconds.  A failed request misses every limit: it counts as slower
    than any answer.  Raises where fewer than ``SAMPLES_BEYOND`` requests
    lie beyond the quantile, or where the quantile itself is a failure."""
    n = len(latencies_s) + failed
    beyond = n - math.ceil(q * n)
    if beyond < SAMPLES_BEYOND:
        raise TooFewSamples(
            f"{n} requests leave {beyond} beyond the {q:.2f} quantile; "
            f"{SAMPLES_BEYOND} are asked for"
        )
    rank = math.ceil(q * n) - 1
    if rank >= len(latencies_s):
        raise TooFewSamples(
            f"{failed} of {n} requests failed: the {q:.2f} quantile is one "
            "of them"
        )
    return sorted(latencies_s)[rank]


def rate(records, seconds: float) -> float:
    """Units answered inside the window over the window's seconds.  A
    request still in flight when the window closes is waited for and
    counts for the share of its time that lay inside the window, so the
    rate does not move in steps of one request (0.8 % where a window
    holds 125 batches) and does not hang on how late the last answer is."""
    done = sum(
        units * min(1.0, (seconds - sent) / lat)
        for sent, lat, ok, units in records if ok and lat > 0
    )
    return done / seconds


def summary(records, seconds: float) -> dict:
    """What the earlier lines print about a window."""
    ok = [r for r in records if r[2]]
    lat = sorted(r[1] for r in ok)
    out = {
        "attempted": len(records),
        "failed": len(records) - len(ok),
        "completed_in_window": sum(
            1 for s, l, good, _ in records if good and s + l <= seconds
        ),
    }
    if lat:
        p50 = lat[len(lat) // 2]
        out["latency_ms"] = {
            "min": 1e3 * lat[0],
            "p50": 1e3 * p50,
            "max": 1e3 * lat[-1],
        }
        # when the slow requests were sent: a tail that sits in one
        # moment of the window is a pause, not the steady tail
        slow = sorted(r[0] for r in ok if r[1] > 2 * p50)
        out["slow"] = {
            "over_ms": 2e3 * p50,
            "count": len(slow),
            "sent_s": [slow[0], slow[len(slow) // 2], slow[-1]] if slow else [],
            "slowest_sent_s": max(ok, key=lambda r: r[1])[0],
        }
    return out
