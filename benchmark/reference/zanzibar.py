"""The plain reference: Keto's Check and Expand semantics, sequentially,
over integer tuple columns.  Imports nothing of the program.

Check follows the decision procedure of Ory Keto's check engine
(internal/check/engine.go, rewrites.go): three-valued membership, a
check group that is a member if any child is (unknown children are
swallowed), NOT that flips member and not-member and keeps unknown, and
the depth budget as the engine spends it (direct and subject-set
expansion at depth-1, computed subject sets at the same depth,
tuple-to-subject-set at depth-1, nested rewrites at depth-1), the width
cut of subject-set expansion, and the visited set that an expansion
subtree creates and hands down.  Expand follows internal/expand/engine.go.
Rows of one (namespace, object, relation) come in the order in which the
columns hold them, which is the order the served store pages them in.

``max_depth`` and ``max_width`` are the deployment's limits
(``limit.max_read_depth`` 5, ``limit.max_read_width`` 100).  The control
(see ``PERF.md``) is this same class built with a smaller ``max_depth``.

A subject is a user number (int) or a set ``(ns, obj, rel)``.
"""

from __future__ import annotations

import numpy as np

UNKNOWN, IS_MEMBER, NOT_MEMBER = 0, 1, 2


class Reference:
    def __init__(self, cols: dict, schema: dict, *, max_depth: int = 5,
                 max_width: int = 100):
        self.schema = schema
        self.max_depth = max_depth
        self.max_width = max_width
        key = ((cols["ns"].astype(np.int64) << 42)
               | (cols["obj"].astype(np.int64) << 14)
               | cols["rel"].astype(np.int64))
        order = np.argsort(key, kind="stable")
        self._keys = key[order]
        self._is_set = cols["is_set"][order]
        self._subj = cols["subj"][order]
        self._set = np.stack(
            [cols["s_ns"][order], cols["s_obj"][order], cols["s_rel"][order]],
            axis=1,
        )
        #: rows the walks looked at (edges followed and membership probes):
        #: the work the semantics ask for, whatever implements them
        self.rows_examined = 0

    # -- the two store questions ---------------------------------------------

    def _span(self, ns, obj, rel):
        want = (int(ns) << 42) | (int(obj) << 14) | int(rel)
        lo = int(np.searchsorted(self._keys, want, side="left"))
        hi = int(np.searchsorted(self._keys, want, side="right"))
        self.rows_examined += max(hi - lo, 1)
        return lo, hi

    def _subject_sets(self, ns, obj, rel):
        lo, hi = self._span(ns, obj, rel)
        return [tuple(int(x) for x in self._set[i])
                for i in range(lo, hi) if self._is_set[i]]

    def _exists(self, ns, obj, rel, subject) -> bool:
        lo, hi = self._span(ns, obj, rel)
        if lo == hi:
            return False
        if isinstance(subject, tuple):
            hit = (self._is_set[lo:hi] == 1) & (
                self._set[lo:hi] == np.asarray(subject)).all(axis=1)
        else:
            hit = (self._is_set[lo:hi] == 0) & (self._subj[lo:hi] == subject)
        return bool(hit.any())

    # -- check ----------------------------------------------------------------

    def check(self, ns, obj, rel, subject) -> bool:
        return self._allowed(ns, obj, rel, subject, self.max_depth,
                             False, None) == IS_MEMBER

    def _rewrite_of(self, ns, rel):
        try:
            return self.schema[ns][rel]
        except KeyError:
            raise ValueError(f"namespace {ns} declares no relation {rel}")

    def _allowed(self, ns, obj, rel, subject, depth, skip_direct, visited):
        if depth <= 0:
            return UNKNOWN
        rewrite = self._rewrite_of(ns, rel)
        if rewrite is not None and self._rewrite(
                ns, obj, subject, rewrite, depth, visited) == IS_MEMBER:
            return IS_MEMBER
        if not skip_direct and depth - 1 > 0 and self._exists(
                ns, obj, rel, subject):
            return IS_MEMBER
        if self._expand_subject(ns, obj, rel, subject, depth - 1,
                                visited) == IS_MEMBER:
            return IS_MEMBER
        return NOT_MEMBER

    def _expand_subject(self, ns, obj, rel, subject, depth, visited):
        if depth <= 0:
            return UNKNOWN
        children = []
        for child in self._subject_sets(ns, obj, rel):
            children.append(child)
            if self._exists(*child, subject):
                return IS_MEMBER
        if len(children) > self.max_width:
            children = children[: self.max_width - 1]
        if visited is None:
            visited = set()
        for child in children:
            if child in visited:
                continue
            visited.add(child)
            if self._allowed(*child, subject, depth, True,
                             visited) == IS_MEMBER:
                return IS_MEMBER
        return NOT_MEMBER

    def _rewrite(self, ns, obj, subject, rewrite, depth, visited):
        if depth <= 0:
            return UNKNOWN
        op, children = rewrite
        checks = []
        if op == "or":
            computed = [c[1] for c in children if c[0] == "computed"]
            children = [c for c in children if c[0] != "computed"]
            if computed:
                checks.append(lambda: self._computed_batch(
                    ns, obj, subject, computed, depth, visited))
        for child in children:
            checks.append(lambda c=child: self._child(
                ns, obj, subject, c, depth, visited, nested=depth - 1))
        if op == "or":
            return (IS_MEMBER if any(c() == IS_MEMBER for c in checks)
                    else NOT_MEMBER)
        return (IS_MEMBER if checks and all(c() == IS_MEMBER for c in checks)
                else NOT_MEMBER)

    def _computed_batch(self, ns, obj, subject, relations, depth, visited):
        for rel in relations:
            if self._exists(ns, obj, rel, subject):
                return IS_MEMBER
        for rel in relations:
            if self._allowed(ns, obj, rel, subject, depth - 1, True,
                             visited) == IS_MEMBER:
                return IS_MEMBER
        return NOT_MEMBER

    def _child(self, ns, obj, subject, child, depth, visited, nested):
        """One rewrite child.  ``nested`` is the depth a nested and/or
        runs at: depth-1 under a rewrite, the same depth under a NOT."""
        kind = child[0]
        if kind in ("or", "and"):
            return self._rewrite(ns, obj, subject, child, nested, visited)
        if depth < 0:
            return UNKNOWN
        if kind == "computed":
            return self._allowed(ns, obj, child[1], subject, depth, False,
                                 visited)
        if kind == "ttu":
            for s_ns, s_obj, _ in self._subject_sets(ns, obj, child[1]):
                if self._allowed(s_ns, s_obj, child[2], subject, depth - 1,
                                 False, visited) == IS_MEMBER:
                    return IS_MEMBER
            return NOT_MEMBER
        if kind == "not":
            got = self._child(ns, obj, subject, child[1], depth, visited,
                              nested=depth)
            return {IS_MEMBER: NOT_MEMBER, NOT_MEMBER: IS_MEMBER}.get(
                got, UNKNOWN)
        raise ValueError(f"unknown rewrite child {child!r}")

    # -- expand ---------------------------------------------------------------

    def expand(self, root, subject_json):
        """The expansion tree of the set ``root`` as the REST API prints
        it, or None (404).  ``subject_json(subject)`` names a subject."""
        return self._build(root, self.max_depth, set(), subject_json)

    def _build(self, subject, depth, visited, subject_json):
        empty = {"namespace": "", "object": "", "relation": ""}
        if not isinstance(subject, tuple):
            return {"type": "leaf",
                    "tuple": {**empty, **subject_json(subject)}}
        if subject in visited:
            return None
        visited.add(subject)
        lo, hi = self._span(*subject)
        if lo == hi:
            return None
        node = {"type": "union", "tuple": {**empty, **subject_json(subject)}}
        if depth <= 1:
            node["type"] = "leaf"
            return node
        children = []
        for i in range(lo, hi):
            child = (tuple(int(x) for x in self._set[i]) if self._is_set[i]
                     else int(self._subj[i]))
            built = self._build(child, depth - 1, visited, subject_json)
            if built is None:
                built = {"type": "leaf",
                         "tuple": {**empty, **subject_json(child)}}
            children.append(built)
        if children:
            node["children"] = children
        return node
