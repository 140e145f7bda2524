"""Rows of ``Group#members`` Check traffic on nested-group chains
(``graphs/groups_deep.py``) from a mix's parameters: which chain root,
which subject.  Every seed gets the same counts of each kind of row in
each depth class, in another order: the seed changes the keys and never
the work."""

from __future__ import annotations

import numpy as np

from graphs import groups_deep as gd


def _dealt(count: int, k: int) -> np.ndarray:
    """``count`` rows over ``k`` classes, evenly, the first ones one more."""
    return count // k + (np.arange(k) < count % k)


def _roots(world, rng, count: int):
    """``count`` chain roots, a fifth (one class in ``len(depths)``) of
    them in each depth class, uniform over the chains of the class; with
    each root's depth."""
    per = _dealt(count, len(world.depths))
    obj = np.concatenate([
        roots[rng.integers(len(roots), size=k)]
        for roots, k in zip(world.roots, per)])
    depth = np.repeat(np.array(world.depths, np.int64), per)
    return obj, depth


def rows(world, mix: dict, rng, n: int) -> dict:
    """``n`` checks of ``Group:<chain root>#members``.  ``granted_share``
    of them ask for a user of the chain's deepest group (the grant needs
    the chain's whole depth); the rest ask for a uniform user, or, for
    ``subject_set_share`` of them, for the members set of a uniform
    group."""
    n_granted = round(mix["granted_share"] * n)
    n_rest = n - n_granted
    obj = np.empty(n, np.int64)
    user = np.empty(n, np.int64)
    group = np.full(n, -1, np.int64)
    g_obj, g_depth = _roots(world, rng, n_granted)
    obj[:n_granted] = g_obj
    user[:n_granted] = world.members_of(rng, g_obj + g_depth - 1)
    obj[n_granted:] = _roots(world, rng, n_rest)[0]
    user[n_granted:] = rng.integers(world.U, size=n_rest)
    sets = n_granted + rng.permutation(n_rest)[
        : round(mix["subject_set_share"] * n_rest)]
    group[sets] = rng.integers(world.G, size=len(sets))
    order = rng.permutation(n)
    return {"obj": obj[order], "user": user[order], "group": group[order]}


def subject(r: dict, i: int):
    g = int(r["group"][i])
    return (gd.NS_G, g, gd.R_MEMBERS) if g >= 0 else int(r["user"][i])


def tuple_text(r: dict, i: int) -> str:
    """One check as the REST API reads it (JSON text)."""
    g = int(r["group"][i])
    subj = (
        '"subject_set":{"namespace":"Group","object":"g%d",'
        '"relation":"members"}' % g
        if g >= 0 else '"subject_id":"u%d"' % r["user"][i]
    )
    return '{"namespace":"Group","object":"g%d","relation":"members",%s}' % (
        r["obj"][i], subj)


def reference_verdicts(ref, r: dict) -> list:
    return [
        ref.check(gd.NS_G, int(r["obj"][i]), gd.R_MEMBERS, subject(r, i))
        for i in range(len(r["obj"]))
    ]
