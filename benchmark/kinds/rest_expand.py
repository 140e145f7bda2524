"""REST ``GET /relation-tuples/expand``: one subject tree a request, roots
drawn in equal parts from the families the mix names, each root once."""

from __future__ import annotations

import json
import urllib.parse

import numpy as np

from graphs import drive
from httpwire import HttpClient


def _roots(world, family: str, rng, n: int):
    """``n`` distinct roots of one family, as (ns, obj, rel)."""
    if family == "folder_viewers_12":  # a user and a group among them
        ids = world.G + np.arange(0, world.F, 12)
        ns, rel = drive.NS_F, drive.R_VIEWERS
    elif family == "group_members":
        ids = np.arange(world.G)
        ns, rel = drive.NS_G, drive.R_MEMBERS
    elif family == "doc_parents":
        ids = world.G + world.F + rng.choice(world.D, size=n, replace=False)
        ns, rel = drive.NS_D, drive.R_PARENTS
    else:
        raise ValueError(f"unknown family of roots {family!r}")
    if len(ids) < n:
        raise ValueError(f"{family} has {len(ids)} roots, {n} are asked for")
    return [(ns, int(o), rel) for o in rng.permutation(ids)[:n]]


def make_pool(world, mix: dict, rng, n: int) -> list:
    families = mix["families"]
    per = -(-n // len(families))
    roots = [r for f in families for r in _roots(world, f, rng, per)]
    pool = []
    for i in rng.permutation(len(roots))[:n]:
        root = roots[i]
        q = urllib.parse.urlencode({
            **world.subject_json(root)["subject_set"],
            "max-depth": str(mix["max_depth"]),
        })
        pool.append(("/relation-tuples/expand?" + q, root))
    return pool


def units(query) -> int:
    return 1


class Client(HttpClient):
    def call(self, wire: str):
        got = self.request("GET", wire)
        if got is None:
            return False, b""
        if got[0] == 404:  # an empty expansion
            return True, b"null"
        return got[0] == 200, got[1]


def decode(query, answer: bytes):
    try:
        return [json.loads(answer)]
    except ValueError:
        return None


def expected(ref, world, query) -> list:
    return [ref.expand(query, world.subject_json)]
