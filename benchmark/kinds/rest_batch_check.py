"""REST ``POST /relation-tuples/batch/check``: ``rows`` checks a request,
through the columnar front door (what the SDK's ``batch_check`` sends)."""

from __future__ import annotations

import json

import checkmix
from httpwire import HttpClient

PATH = "/relation-tuples/batch/check"


def make_pool(world, mix: dict, rng, n: int) -> list:
    """``n`` requests: ``(wire, query)`` with the body as bytes and the
    rows as arrays for the reference."""
    per = int(mix["rows"])
    pool = []
    for _ in range(n):
        r = checkmix.rows(world, mix, rng, per)
        body = ('{"tuples":[' + ",".join(
            checkmix.tuple_text(r, i, world) for i in range(per)
        ) + "]}").encode()
        pool.append((body, r))
    return pool


def units(query) -> int:
    return len(query["obj"])


class Client(HttpClient):
    def call(self, wire: bytes):
        """(ok, answer): the response's body as it came."""
        got = self.request("POST", PATH, wire)
        return (False, b"") if got is None else (got[0] == 200, got[1])


def decode(query, answer: bytes):
    """One verdict a row, or None where the body is not a batch answer."""
    try:
        return [bool(r["allowed"]) for r in json.loads(answer)["results"]]
    except (ValueError, KeyError, TypeError):
        return None


def expected(ref, world, query) -> list:
    return checkmix.reference_verdicts(ref, query)
