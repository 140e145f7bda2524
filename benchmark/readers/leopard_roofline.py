"""The Leopard tier's share of the memory roofline, in percent.

The work is reckoned from the semantics, whatever implements them
(:func:`lookup_bytes`): a lookup of one (set, element) pair in a sorted
index of ``pairs`` pairs takes ceil(log2(pairs)) steps, each a read of
one pair, ``bytes_per_step`` (8: two int32).  A lookup is made for every
row the index could search, those it answered and those it found beyond
the depth budget (window delta of ``keto_leopard_rows_total``).  Times
the lookups a second of the window, over the chip's peak bytes per
second: the least device seconds a second of this traffic needs.  Over
the device seconds a second of the traced window that the operations
under the tier's named scope (``scope``) of the matching modules took."""

import math
import re

import trace_spans
from readers import scrape_delta, trace_scope_time

SEARCHED = ("answered", "beyond_depth")


def lookup_bytes(pairs: int, bytes_per_step: int) -> int:
    """Bytes one lookup reads in a sorted index of ``pairs`` pairs."""
    return math.ceil(math.log2(max(int(pairs), 2))) * int(bytes_per_step)


def read(spec: dict, ctx: dict):
    trace, peak = ctx["trace"], ctx["peak"]
    if trace is None or trace["window_s"] <= 0:
        return None
    if peak is None:
        raise KeyError(
            f"device {ctx['device']['kind']!r} is not in peaks.json")
    lookups = scrape_delta._sum(ctx["delta"], [{
        "name": "keto_leopard_rows_total",
        "only_labels": {"outcome": list(SEARCHED)}}])
    data = trace_spans.of_run(ctx)
    if lookups <= 0 or data is None:
        return None
    scopes, _ = trace_scope_time.table(data, spec["module"])
    tier_ns = sum(ns for scope, ns in scopes.items()
                  if re.search(spec["scope"], scope))
    if tier_ns <= 0:
        return None
    bytes_per_s = (lookups / ctx["delta"]["window.seconds"]
                   * lookup_bytes(spec["pairs"], spec["bytes_per_step"]))
    least_s = bytes_per_s / float(peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (tier_ns / 1e9 / trace["window_s"])
