"""Nested-group chains at the depths and widths of upstream's deep x wide
check harness: schema, tuple generator and name scheme.

Ory Keto's ``internal/check/bench_test.go`` (``BenchmarkCheckEngine``)
builds trees 2 to 32 deep and 10 to 100 wide.  Here those shapes hold
config #3's population (``BASELINE.json`` configs[3]: 1.2M users, 25,000
groups): the groups are cut into equal depth classes, each class into
chains of its depth (``g_0#members`` contains ``g_1#members`` contains
... ``g_{d-1}#members``); every group holds its own direct users, a
width drawn once, uniform over ``width_min``..``width_max``.  Groups a
class cannot fill with whole chains stand alone (users only).

The widths come from a fixed stream, not from the seed: every seed makes
the same counts, the same tuples a group and so the same tainted nodes of
the closure index; the seed moves which users land in which group.  A
group's users are consecutive places of one permutation of all users
(taken again from its start once every user has a group), so no group
holds a user twice.

The benchmark's own generator: it imports nothing of the program but in
``server_store``, which only the server child calls.
"""

from __future__ import annotations

import numpy as np

OPL = """
import { Namespace, SubjectSet, Context } from "@ory/keto-namespace-types"

class User implements Namespace {}

class Group implements Namespace {
  related: {
    members: (User | Group)[]
  }
}
"""

NAMESPACES = ("Group",)
NS_G = 0
RELATIONS = ("", "members")
R_MEMBERS = 1
SCHEMA = {NS_G: {R_MEMBERS: None}}

COLS = ("ns", "obj", "rel", "subj", "is_set", "s_ns", "s_obj", "s_rel")

#: the stream the widths are drawn from: a constant of the deployment
WIDTH_STREAM = 0x64656570


class GroupsDeep:
    """One generated graph.  Object ids: groups.  Subject ids: users,
    then the groups' ``members`` sets."""

    def __init__(self, params: dict, seed: int):
        self.U = U = int(params["n_users"])
        self.G = G = int(params["n_groups"])
        self.depths = tuple(int(d) for d in params["depths"])
        per_class = G // len(self.depths)
        #: per depth class: the root group of each chain (chain c of the
        #: class holds groups root .. root + depth - 1)
        self.roots = []
        base = 0
        for d in self.depths:
            n_chains = per_class // d
            self.roots.append(base + d * np.arange(n_chains, dtype=np.int64))
            base += per_class
        # the groups no class fills with whole chains stand alone
        self.standalone = G - sum(d * len(r)
                                  for d, r in zip(self.depths, self.roots))
        self.width = np.random.default_rng(WIDTH_STREAM).integers(
            int(params["width_min"]), int(params["width_max"]) + 1, size=G)
        #: the first of a group's users in ``self.members``
        self.start = np.concatenate([[0], np.cumsum(self.width)])
        perm = np.random.default_rng(seed).permutation(U)
        self.members = perm[np.arange(self.start[-1]) % U]
        segs = []

        def seg(obj, subj, is_set=0, s_obj=-1):
            n = len(obj)
            col = lambda v: (np.full(n, v, np.int32) if np.isscalar(v)
                             else np.asarray(v, np.int32))
            segs.append({
                "ns": col(NS_G), "obj": col(obj), "rel": col(R_MEMBERS),
                "subj": col(subj), "is_set": col(is_set),
                "s_ns": col(NS_G if is_set else -1), "s_obj": col(s_obj),
                "s_rel": col(R_MEMBERS if is_set else -1),
            })

        seg(np.repeat(np.arange(G), self.width), self.members)
        parents = np.concatenate([
            (r[:, None] + np.arange(d - 1)).ravel()
            for d, r in zip(self.depths, self.roots)])
        seg(parents, U + parents + 1, 1, parents + 1)
        self.nesting_rows = len(parents)
        self.cols = {k: np.concatenate([s[k] for s in segs]) for k in COLS}

    def __len__(self) -> int:
        return len(self.cols["ns"])

    # -- names ---------------------------------------------------------------

    def object_name(self, ns: int, obj: int) -> str:
        return f"g{int(obj)}"

    def subject_json(self, subject) -> dict:
        """``subject`` is a user number, or ``(ns, obj, rel)`` of a set."""
        if isinstance(subject, tuple):
            _, obj, _ = subject
            return {"subject_set": {"namespace": "Group",
                                    "object": f"g{int(obj)}",
                                    "relation": "members"}}
        return {"subject_id": f"u{int(subject)}"}

    # -- what the traffic draws from ----------------------------------------

    def members_of(self, rng, groups: np.ndarray) -> np.ndarray:
        """One user of each of ``groups``, uniform among its own."""
        off = (rng.random(len(groups)) * self.width[groups]).astype(np.int64)
        return self.members[self.start[groups] + off]

    def server_store(self):
        """The program's own store and namespace manager holding this
        graph, ids assigned as the columns have them.  Imports the
        program: only the server child calls it."""
        from ketotpu.engine.vocab import Vocab
        from ketotpu.opl.parser import parse
        from ketotpu.storage.columnar import ColumnarTupleStore
        from ketotpu.storage.namespaces import StaticNamespaceManager

        namespaces, errors = parse(OPL)
        if errors:
            raise ValueError(f"the groups schema does not parse: {errors}")
        v = Vocab()
        v.namespaces._ids = {n: i for i, n in enumerate(NAMESPACES)}
        v.objects._ids = {f"g{i}": i for i in range(self.G)}
        v.relations.intern("members")  # "" is pre-interned at 0
        subs = {f"id:u{i}": i for i in range(self.U)}
        for i in range(self.G):
            subs[f"set:Group:g{i}#members"] = len(subs)
        v.subjects._ids = subs
        store = ColumnarTupleStore(v)
        store.bulk_load_ids(self.cols)
        return store, StaticNamespaceManager(namespaces)


def build(params: dict, seed: int) -> GroupsDeep:
    return GroupsDeep(params, seed)
