"""``drive.py``'s graph, tuple for tuple, handed to the program in bulk.

``drive.Drive.server_store`` names every object and subject into a Python
``dict`` before the program sees one of them: 150 bytes a name, 15 GB and
two minutes of a loop at the 123M names of ``drive-150m``.  This kind
writes the same names in the same order as bytes, with numpy, and hands
the blob to the vocabulary's bulk constructor (``Interner.from_utf8``, the
program's since PR 35): no name is ever a Python string.  A program without
that constructor cannot hold this deployment on the chip host at all (its
projection of 150M tuples asks for some 80 GB), and its server child ends
here, at once, before anything large is built.

The tuples are ``drive.py``'s too, draw for draw, written in place instead
of concatenated.  Everything else (schema, names, what the traffic draws
from, the reference's columns) is ``drive.py``'s own.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from graphs import drive

OPL, SCHEMA, COLS = drive.OPL, drive.SCHEMA, drive.COLS


#: names a worker writes at a time
_PASS = 1 << 20


def numbered(prefix: str, count: int, suffix: str = ""):
    """``prefix + str(i) + suffix`` for ``i`` in ``range(count)`` as ASCII
    bytes one after another, and each name's length: the numbers of one
    digit count are a matrix of bytes, filled a column at a time by a few
    threads (numpy holds no lock while it divides)."""
    head = np.frombuffer(prefix.encode(), np.uint8)
    tail = np.frombuffer(suffix.encode(), np.uint8)
    blobs, lens, work = [], np.empty(count, np.int64), []
    lo, digits = 0, 1
    while lo < count:
        hi = min(10 ** digits, count)
        rows = np.empty((hi - lo, len(head) + digits + len(tail)), np.uint8)
        work += [(rows, lo, at, min(at + _PASS, hi), digits)
                 for at in range(lo, hi, _PASS)]
        blobs.append(rows)
        lens[lo:hi] = rows.shape[1]
        lo, digits = hi, digits + 1

    def fill(rows, first, lo, hi, digits):
        out = rows[lo - first:hi - first]
        i = np.arange(lo, hi, dtype=np.int64 if hi > 1 << 31 else np.int32)
        out[:, :len(head)] = head
        for k in range(digits):  # the most significant digit first
            out[:, len(head) + k] = i // 10 ** (digits - 1 - k) % 10 + 48
        out[:, len(head) + digits:] = tail

    with ThreadPoolExecutor(min(os.cpu_count() or 1, 16)) as pool:
        for done in [pool.submit(fill, *w) for w in work]:
            done.result()
    return [rows.ravel() for rows in blobs], lens


def names(*families):
    """The families' names, one family after another, as one blob."""
    made = [numbered(*family) for family in families]
    return (np.concatenate([b for blobs, _ in made for b in blobs]),
            np.concatenate([lens for _, lens in made]))


class DriveBulk(drive.Drive):
    def __init__(self, params: dict, seed: int):
        """``drive.Drive.__init__``, the same draws in the same order, each
        segment written straight into its place in the eight columns:
        ``drive.py`` makes ten segments of eight arrays (int64 ranges,
        ``np.full``, a cast) and concatenates them, 16 s at 150M rows on
        the chip host; ``tests/test_drive150m.py`` holds the two to each
        other."""
        self.U = U = int(params["n_users"])
        self.G = G = int(params["n_groups"])
        self.F = F = int(params["n_folders"])
        self.D = D = int(params["n_docs"])
        fanout = int(params["fanout"])
        NS_G, NS_F, NS_D = drive.NS_G, drive.NS_F, drive.NS_D
        self.obj_base = {NS_G: 0, NS_F: G, NS_D: G + F}
        self._granted = None
        rng = np.random.default_rng(seed)
        gset, fset = U, U + G  # subject-id bases of the set subjects
        i32 = lambda *a: np.arange(*a, dtype=np.int32)
        users = lambda count: rng.integers(U, size=count)
        gi = i32(1, G, 3)  # every third group nests the next
        par = (i32(1, F) - 1) // fanout  # the folder tree, rooted at f0
        # (ns, obj, rel, subj, set subject or None), lazily: a segment's
        # draws are made when its turn comes, as drive.py makes them
        segments = [
            lambda: (NS_G, i32(U) % G, drive.R_MEMBERS, i32(U), None),
            lambda: (NS_G, gi - 1, drive.R_MEMBERS, gset + gi,
                     (NS_G, gi, drive.R_MEMBERS)),
            lambda: (NS_F, i32(G + 1, G + F), drive.R_PARENTS, fset + par,
                     (NS_F, G + par, drive.R_EMPTY)),
            lambda: (NS_F, i32(G, G + F, 3), drive.R_VIEWERS,
                     self._keep("f3_user", users(len(range(0, F, 3)))),
                     None),
            lambda: (NS_F, i32(G, G + F, 5), drive.R_OWNERS,
                     users(len(range(0, F, 5))), None),
            lambda: (NS_F, i32(G, G + F, 4), drive.R_VIEWERS,
                     gset + self._keep("f4_group", rng.integers(
                         G, size=len(range(0, F, 4)))),
                     (NS_G, self.f4_group, drive.R_MEMBERS)),
            lambda: (NS_D, i32(G + F, G + F + D), drive.R_PARENTS,
                     fset + self._keep("doc_folder",
                                       rng.integers(F, size=D)),
                     (NS_F, G + self.doc_folder, drive.R_EMPTY)),
            lambda: (NS_D, i32(G + F, G + F + D, 7), drive.R_VIEWERS,
                     self._keep("d7_user", users(len(range(0, D, 7)))),
                     None),
            lambda: (NS_D, i32(G + F, G + F + D, 11), drive.R_OWNERS,
                     users(len(range(0, D, 11))), None),
            lambda: (NS_D, i32(G + F, G + F + D, 13), drive.R_BANNED,
                     users(len(range(0, D, 13))), None),
        ]
        counts = [U, len(gi), F - 1, len(range(0, F, 3)),
                  len(range(0, F, 5)), len(range(0, F, 4)), D,
                  len(range(0, D, 7)), len(range(0, D, 11)),
                  len(range(0, D, 13))]
        self.cols = {c: np.empty(sum(counts), np.int32) for c in COLS}
        at = 0
        with ThreadPoolExecutor(len(COLS)) as pool:  # a column a thread
            for make, count in zip(segments, counts):
                ns, obj, rel, subj, to = make()
                assert len(obj) == count
                s_ns, s_obj, s_rel = to if to is not None else (-1, -1, -1)
                values = (ns, obj, rel, subj, int(to is not None),
                          s_ns, s_obj, s_rel)
                list(pool.map(
                    lambda c, v: self.cols[c].__setitem__(
                        slice(at, at + count), v), COLS, values))
                at += count

    def _keep(self, name: str, drawn):
        setattr(self, name, drawn)
        return drawn

    def server_store(self):
        """``drive.Drive.server_store``, the two large id spaces built
        without a dict.  Imports the program: only the server child
        calls it."""
        from ketotpu.engine.vocab import Interner, Vocab
        from ketotpu.opl.parser import parse
        from ketotpu.storage.columnar import ColumnarTupleStore
        from ketotpu.storage.namespaces import StaticNamespaceManager

        namespaces, errors = parse(OPL)
        if errors:
            raise ValueError(f"the Drive schema does not parse: {errors}")
        U, G, F, D = self.U, self.G, self.F, self.D
        v = Vocab()
        # the two large id spaces at once: each build is numpy's, and
        # most of a table's build runs on one core
        with ThreadPoolExecutor(1) as side:
            subjects = side.submit(lambda: Interner.from_utf8(*names(
                ("id:u", U), ("set:Group:g", G, "#members"),
                ("set:Folder:f", F, "#"))))
            v.objects = Interner.from_utf8(
                *names(("g", G), ("f", F), ("d", D)))
            v.subjects = subjects.result()
        for n in drive.NAMESPACES:
            v.namespaces.intern(n)
        for rel in drive.RELATIONS[:drive.R_BANNED + 1]:  # "" is there at 0
            v.relations.intern(rel)
        store = ColumnarTupleStore(v)
        store.bulk_load_ids(self.cols)
        return store, StaticNamespaceManager(namespaces)


def build(params: dict, seed: int) -> DriveBulk:
    return DriveBulk(params, seed)
