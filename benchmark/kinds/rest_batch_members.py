"""REST ``POST /relation-tuples/batch/check`` of ``Group#members`` rows on
nested-group chains (``groupmix.py``): ``rows`` checks a request through
the columnar front door; the wire, the client and the decoding are
``rest_batch_check``'s, which sends Drive's rows."""

from __future__ import annotations

import groupmix
from kinds.rest_batch_check import Client, decode, units  # noqa: F401


def make_pool(world, mix: dict, rng, n: int) -> list:
    """``n`` requests: ``(wire, query)`` with the body as bytes and the
    rows as arrays for the reference."""
    per = int(mix["rows"])
    pool = []
    for _ in range(n):
        r = groupmix.rows(world, mix, rng, per)
        body = ('{"tuples":[' + ",".join(
            groupmix.tuple_text(r, i) for i in range(per)
        ) + "]}").encode()
        pool.append((body, r))
    return pool


def expected(ref, world, query) -> list:
    return groupmix.reference_verdicts(ref, query)
