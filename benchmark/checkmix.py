"""Rows of Check traffic from a mix's parameters: which doc, which permit,
which subject.  Every seed gets the same counts of each kind of row, in
another order, so the seed changes the keys and never the work."""

from __future__ import annotations

import numpy as np

from graphs import drive


def rows(world, mix: dict, rng, n: int) -> dict:
    """``n`` checks on docs.  ``granted_share`` of them are read back from
    grants in the graph (``granted_edit_share`` of those through ``edit``);
    the rest are uniform (doc, subject) pairs, ``edit_share`` of them
    through ``edit`` and ``subject_set_share`` with a group's members as
    the subject."""
    n_granted = round(mix["granted_share"] * n)
    n_rest = n - n_granted
    obj = np.empty(n, np.int64)
    rel = np.full(n, drive.R_VIEW, np.int64)
    user = np.empty(n, np.int64)
    group = np.full(n, -1, np.int64)
    obj[:n_granted], user[:n_granted] = world.granted_views(rng, n_granted)
    edits = rng.permutation(n_granted)[
        : round(mix["granted_edit_share"] * n_granted)]
    rel[edits] = drive.R_EDIT
    base = world.G + world.F
    obj[n_granted:] = base + rng.integers(world.D, size=n_rest)
    user[n_granted:] = rng.integers(world.U, size=n_rest)
    edits = n_granted + rng.permutation(n_rest)[
        : round(mix["edit_share"] * n_rest)]
    rel[edits] = drive.R_EDIT
    sets = n_granted + rng.permutation(n_rest)[
        : round(mix["subject_set_share"] * n_rest)]
    group[sets] = rng.integers(world.G, size=len(sets))
    order = rng.permutation(n)
    return {"obj": obj[order], "rel": rel[order], "user": user[order],
            "group": group[order]}


def subject(r: dict, i: int):
    g = int(r["group"][i])
    return (drive.NS_G, g, drive.R_MEMBERS) if g >= 0 else int(r["user"][i])


def tuple_text(r: dict, i: int, world) -> str:
    """One check as the REST API reads it (JSON text)."""
    g = int(r["group"][i])
    subj = (
        '"subject_set":{"namespace":"Group","object":"g%d",'
        '"relation":"members"}' % g
        if g >= 0 else '"subject_id":"u%d"' % r["user"][i]
    )
    return '{"namespace":"Doc","object":"d%d","relation":"%s",%s}' % (
        r["obj"][i] - world.G - world.F, drive.RELATIONS[r["rel"][i]], subj)


def reference_verdicts(ref, r: dict) -> list:
    return [
        ref.check(drive.NS_D, int(r["obj"][i]), int(r["rel"][i]),
                  subject(r, i))
        for i in range(len(r["obj"]))
    ]
