"""The Drive-style deployment: schema, tuple generator and name scheme.

The benchmark's own copy of what ``ketotpu/utils/synth.py``
(``build_synth_columnar``) makes, kept here so that no later change to the
program moves the data a cell is measured on.  Everything is integer id
columns made by numpy from the seed; the only strings are the name
prefixes (``u<i>``, ``g<i>``, ``f<i>``, ``d<i>``).

``SCHEMA`` states the namespaces' rewrites for the plain reference
(``reference/zanzibar.py``); ``OPL`` is the same schema in the permission
language, which the server child loads.  ``tests/test_reference.py`` holds
the two to each other through the program's parser.
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

class Folder implements Namespace {
  related: {
    parents: Folder[]
    viewers: (User | SubjectSet<Group, "members">)[]
    owners: (User | SubjectSet<Group, "members">)[]
  }
  permits = {
    own: (ctx: Context): boolean =>
      this.related.owners.includes(ctx.subject) ||
      this.related.parents.traverse((p) => p.permits.own(ctx)),
    view: (ctx: Context): boolean =>
      this.related.viewers.includes(ctx.subject) ||
      this.permits.own(ctx) ||
      this.related.parents.traverse((p) => p.permits.view(ctx)),
  }
}

class Doc implements Namespace {
  related: {
    parents: Folder[]
    viewers: (User | SubjectSet<Group, "members">)[]
    owners: (User | SubjectSet<Group, "members">)[]
    banned: User[]
  }
  permits = {
    view: (ctx: Context): boolean =>
      this.related.viewers.includes(ctx.subject) ||
      this.related.owners.includes(ctx.subject) ||
      this.related.parents.traverse((p) => p.permits.view(ctx)),
    edit: (ctx: Context): boolean =>
      !this.related.banned.includes(ctx.subject) &&
      this.permits.view(ctx),
  }
}
"""

NAMESPACES = ("Group", "Folder", "Doc")
NS_G, NS_F, NS_D = 0, 1, 2
RELATIONS = ("", "members", "parents", "viewers", "owners", "banned",
             "own", "view", "edit")
R_EMPTY, R_MEMBERS, R_PARENTS, R_VIEWERS, R_OWNERS, R_BANNED = range(6)
R_OWN, R_VIEW, R_EDIT = 6, 7, 8

# rewrites in the reference's own form: ("or"|"and", [children]),
# ("computed", relation), ("ttu", relation, computed relation),
# ("not", child).  None: a plain relation.  ("User" declares nothing.)
SCHEMA = {
    NS_G: {R_MEMBERS: None},
    NS_F: {
        R_PARENTS: None, R_VIEWERS: None, R_OWNERS: None,
        R_OWN: ("or", [("computed", R_OWNERS),
                       ("ttu", R_PARENTS, R_OWN)]),
        R_VIEW: ("or", [("computed", R_VIEWERS), ("computed", R_OWN),
                        ("ttu", R_PARENTS, R_VIEW)]),
    },
    NS_D: {
        R_PARENTS: None, R_VIEWERS: None, R_OWNERS: None, R_BANNED: None,
        R_VIEW: ("or", [("computed", R_VIEWERS), ("computed", R_OWNERS),
                        ("ttu", R_PARENTS, R_VIEW)]),
        # the permission language parses "!x" into a one-child rewrite
        R_EDIT: ("and", [("or", [("not", ("computed", R_BANNED))]),
                         ("computed", R_VIEW)]),
    },
}

COLS = ("ns", "obj", "rel", "subj", "is_set", "s_ns", "s_obj", "s_rel")


class Drive:
    """One generated graph: id columns plus the arithmetic that names an
    object or a subject.  Object ids: groups, then folders, then docs.
    Subject ids: users, then group sets, then folder sets."""

    def __init__(self, params: dict, seed: int):
        self.U = U = int(params["n_users"])
        self.G = G = int(params["n_groups"])
        self.F = F = int(params["n_folders"])
        self.D = D = int(params["n_docs"])
        fanout = int(params["fanout"])
        self.obj_base = {NS_G: 0, NS_F: G, NS_D: G + F}
        self._granted = None
        rng = np.random.default_rng(seed)
        segs = []

        def seg(ns, obj, rel, subj, s_ns=-1, s_obj=-1, s_rel=-1):
            n = len(obj)
            col = lambda v: (np.full(n, v, np.int32) if np.isscalar(v)
                             else np.asarray(v, np.int32))
            segs.append({
                "ns": col(ns), "obj": col(obj), "rel": col(rel),
                "subj": col(subj), "is_set": col(int(s_ns != -1)),
                "s_ns": col(s_ns), "s_obj": col(s_obj), "s_rel": col(s_rel),
            })

        gset, fset = U, U + G  # subject-id bases of the set subjects
        ui = np.arange(U, dtype=np.int64)
        seg(NS_G, ui % G, R_MEMBERS, ui)
        gi = np.arange(1, G, 3, dtype=np.int64)  # every third group nests
        seg(NS_G, gi - 1, R_MEMBERS, gset + gi, NS_G, gi, R_MEMBERS)
        fi = np.arange(1, F, dtype=np.int64)  # folder tree rooted at f0
        par = (fi - 1) // fanout
        seg(NS_F, G + fi, R_PARENTS, fset + par, NS_F, G + par, R_EMPTY)
        self.f3_user = rng.integers(U, size=len(range(0, F, 3)))
        seg(NS_F, G + np.arange(0, F, 3), R_VIEWERS, self.f3_user)
        seg(NS_F, G + np.arange(0, F, 5), R_OWNERS,
            rng.integers(U, size=len(range(0, F, 5))))
        self.f4_group = g4 = rng.integers(G, size=len(range(0, F, 4)))
        seg(NS_F, G + np.arange(0, F, 4), R_VIEWERS, gset + g4,
            NS_G, g4, R_MEMBERS)
        self.doc_folder = df = rng.integers(F, size=D)
        seg(NS_D, G + F + np.arange(D), R_PARENTS, fset + df,
            NS_F, G + df, R_EMPTY)
        self.d7_user = rng.integers(U, size=len(range(0, D, 7)))
        seg(NS_D, G + F + np.arange(0, D, 7), R_VIEWERS, self.d7_user)
        seg(NS_D, G + F + np.arange(0, D, 11), R_OWNERS,
            rng.integers(U, size=len(range(0, D, 11))))
        seg(NS_D, G + F + np.arange(0, D, 13), R_BANNED,
            rng.integers(U, size=len(range(0, D, 13))))
        self.cols = {k: np.concatenate([s[k] for s in segs]) for k in COLS}

    def __len__(self) -> int:
        return len(self.cols["ns"])

    # -- names ---------------------------------------------------------------

    def object_name(self, ns: int, obj: int) -> str:
        return "gfd"[ns] + str(int(obj) - self.obj_base[ns])

    def subject_json(self, subject) -> dict:
        """``subject`` is a user number, or ``(ns, obj, rel)`` of a set."""
        if isinstance(subject, tuple):
            ns, obj, rel = subject
            return {"subject_set": {
                "namespace": NAMESPACES[ns],
                "object": self.object_name(ns, obj),
                "relation": RELATIONS[rel],
            }}
        return {"subject_id": f"u{int(subject)}"}

    def tuple_json(self, ns, obj, rel, subject) -> dict:
        return {"namespace": NAMESPACES[ns],
                "object": self.object_name(ns, obj),
                "relation": RELATIONS[rel], **self.subject_json(subject)}

    # -- what the traffic draws from ----------------------------------------

    def granted_views(self, rng, n: int):
        """``n`` (doc object id, user) pairs that some grant lets view the
        doc, in equal parts: a doc's own viewer; a viewer of its parent
        folder; a member of a group among that folder's viewers; a member
        of the group nested in such a group (one to four hops, the last at
        the edge of the depth limit).  A random pair is almost never
        allowed at this scale."""
        G, F, U = self.G, self.F, self.U
        base = G + F
        k = -(-n // 4)

        def member_of(g):  # a user u with u % G == g
            count = (U - 1 - g) // G + 1
            return g + G * (rng.random(len(g)) * count).astype(np.int64)

        via_user, via_group, nests = self._granted_index()
        d1 = rng.integers(len(self.d7_user), size=k)
        d2 = via_user[rng.integers(len(via_user), size=k)]
        d3 = via_group[rng.integers(len(via_group), size=k)]
        d4 = nests[rng.integers(len(nests), size=k)]
        docs = np.concatenate([7 * d1, d2, d3, d4])[:n] + base
        users = np.concatenate([
            self.d7_user[d1],
            self.f3_user[self.doc_folder[d2] // 3],
            member_of(self.f4_group[self.doc_folder[d3] // 4]),
            member_of(self.f4_group[self.doc_folder[d4] // 4] + 1),
        ])[:n]
        return docs.astype(np.int64), users.astype(np.int64)

    def _granted_index(self):
        """Docs whose parent folder has a user among its viewers, a group
        among them, and a group that nests another (made once)."""
        if self._granted is None:
            via_user = np.flatnonzero(self.doc_folder % 3 == 0)
            via_group = np.flatnonzero(self.doc_folder % 4 == 0)
            g_of = self.f4_group[self.doc_folder[via_group] // 4]
            nests = via_group[(g_of % 3 == 0) & (g_of + 1 < self.G)]
            self._granted = (via_user, via_group, nests)
        return self._granted

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
            raise ValueError(f"the Drive schema does not parse: {errors}")
        v = Vocab()
        v.namespaces._ids = {n: i for i, n in enumerate(NAMESPACES)}
        objs = {}
        for ns, count in ((NS_G, self.G), (NS_F, self.F), (NS_D, self.D)):
            for i in range(count):
                objs["gfd"[ns] + str(i)] = len(objs)
        v.objects._ids = objs
        for rel in RELATIONS[:R_BANNED + 1]:  # "" is pre-interned at 0
            v.relations.intern(rel)
        subs = {f"id:u{i}": i for i in range(self.U)}
        for i in range(self.G):
            subs[f"set:Group:g{i}#members"] = len(subs)
        for i in range(self.F):
            subs[f"set:Folder:f{i}#"] = len(subs)
        v.subjects._ids = subs
        store = ColumnarTupleStore(v)
        store.bulk_load_ids(self.cols)
        return store, StaticNamespaceManager(namespaces)


def build(params: dict, seed: int) -> Drive:
    return Drive(params, seed)
