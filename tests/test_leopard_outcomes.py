"""What became of every row the Leopard closure index was asked about
(``keto_leopard_rows_total{outcome}``, engine ``leopard_rows``): answered,
or declined as tainted, ineligible, beyond the depth budget or dirty.

Held on a small graph built to hold every cause: a clean chain deeper
than the closure's depth rule allows at the engine's limit, a chain with
a node as wide as the width limit, a relation a rewrite reaches, an
AND/NOT relation, and a deletion.  The causes add up to the rows asked,
the fused wave (tier 0 inside the program) and the cascade count the
same, and where tier 0 answers it answers what the BFS alone does.
"""

import pytest

from ketotpu.api.types import RelationTuple
from ketotpu.engine import CheckEngine
from ketotpu.engine import fused as fdx
from ketotpu.engine.tpu import DeviceCheckEngine
from ketotpu.leopard import closure as leo
from ketotpu.opl.parser import parse
from ketotpu.storage import InMemoryTupleStore, StaticNamespaceManager

T = RelationTuple.from_string
KW = dict(frontier=512, arena=1024, cap=2048, gen_arena=2048, vcap=1024)
MAX_DEPTH = 6
MAX_WIDTH = 5

OPL = """
import { Namespace, SubjectSet, Context } from '@ory/keto-namespace-types'
class User implements Namespace {}
class Group implements Namespace {
  related: { members: (User | SubjectSet<Group, "members">)[] }
}
class Doc implements Namespace {
  related: {
    editors: (User | SubjectSet<Group, "members">)[]
    banned: User[]
  }
  permits = {
    edit: (ctx: Context): boolean =>
      this.related.editors.includes(ctx.subject) &&
      !this.related.banned.includes(ctx.subject),
    view: (ctx: Context): boolean =>
      this.related.editors.includes(ctx.subject),
  }
}
"""


def chain(name, depth, users):
    """``name0`` contains ``name1`` ... ``name<depth-1>``; ``users[i]``
    direct members of ``name<i>``."""
    out = [f"Group:{name}{i}#members@Group:{name}{i + 1}#members"
           for i in range(depth - 1)]
    for i, us in enumerate(users):
        out += [f"Group:{name}{i}#members@User:{u}" for u in us]
    return out


TUPLES = (
    # clean, 6 deep: a user of a5 is 5 hops from a0, found by the BFS at
    # depth 6 and beyond the closure's rule (5 + 2 > 6)
    chain("a", 6, [["a0u"], [], ["a2u"], [], [], ["a5u"]])
    # b2 holds MAX_WIDTH tuples: tainted, and b0, b1 above it
    + chain("b", 4, [["b0u"], [], [f"b2u{i}" for i in range(4)], ["b3u"]])
    # c loses a tuple after the snapshot: c0 and c1 dirty
    + chain("c", 3, [["c0u"], ["c1u"], ["c2u", "c2v"]])
    + ["Doc:d#editors@User:a0u", "Doc:d#banned@User:b0u"]
)

QUERIES = [
    "Group:a0#members@User:a0u",   # answered, allowed
    "Group:a0#members@User:a2u",   # answered, allowed (2 hops)
    "Group:a0#members@User:a5u",   # beyond_depth; the BFS allows
    "Group:a0#members@User:b3u",   # answered, denied
    "Group:a3#members@User:a5u",   # answered, allowed
    "Group:b0#members@User:b3u",   # tainted; the BFS allows
    "Group:b1#members@User:nobody",  # tainted
    "Group:b3#members@User:b3u",   # answered: b3 is below the wide node
    "Group:c0#members@User:c2v",   # dirty after the deletion (c2 and up)
    "Group:a1#members@User:a2u",   # answered, allowed
    "Group:zz#members@User:a0u",   # answered: an unknown group is empty
    "Doc:d#view@User:a0u",         # ineligible: a rewrite reaches view
    "Doc:d#edit@User:a0u",         # ineligible: the AND/NOT tier's row
]
CAUSES = {"answered": 7, "tainted": 2, "ineligible": 2, "beyond_depth": 1,
          "dirty": 1}


@pytest.fixture
def eager(monkeypatch):
    monkeypatch.setattr(fdx, "_run_wave", fdx._wave_body)
    monkeypatch.setenv("KETO_NO_ADAPTIVE", "1")


def engines(**leopard):
    store = InMemoryTupleStore()
    store.write_relation_tuples(*[T(s) for s in TUPLES])
    namespaces, errs = parse(OPL)
    assert not errs, errs
    nsm = StaticNamespaceManager(namespaces)
    kw = dict(KW, max_depth=MAX_DEPTH, max_width=MAX_WIDTH,
              leopard=leopard or None)
    out = {
        "fused": DeviceCheckEngine(store, nsm, fused_dispatch=True,
                                   fused_retry_lanes=1, **kw),
        "cascade": DeviceCheckEngine(store, nsm, fused_dispatch=False, **kw),
    }
    for eng in out.values():
        eng.snapshot()
    store.delete_relation_tuples(T("Group:c2#members@User:c2u"))
    oracle = CheckEngine(store, nsm, max_depth=MAX_DEPTH, max_width=MAX_WIDTH)
    return oracle, out


def test_every_row_asked_lands_in_one_outcome(eager):
    oracle, engs = engines()
    want = [oracle.check_is_member(T(q), 0) for q in QUERIES]
    for name, eng in engs.items():
        assert eng.batch_check([T(q) for q in QUERIES]) == want, name
        assert eng.leopard_rows == CAUSES, name
        assert sum(eng.leopard_rows.values()) == len(QUERIES)
        assert eng.leopard_answered == CAUSES["answered"]


def test_tier0_answers_what_the_bfs_answers(eager):
    """Leopard on and off give the same verdicts, row by row: the rows
    tier 0 answers are answered as the BFS alone answers them."""
    _, on = engines()
    _, off = engines(enabled=False)
    rows = [T(q) for q in QUERIES]
    for name in ("fused", "cascade"):
        got = on[name].batch_check(rows)
        assert got == off[name].batch_check(rows), name
        assert on[name].leopard_answered == CAUSES["answered"]
        assert off[name].leopard_answered == 0
        assert set(off[name].leopard_rows.values()) == {0}


def test_outcomes_partition_by_why_and_answered():
    import numpy as np

    why = np.array([leo.WHY_ELIGIBLE, leo.WHY_ELIGIBLE, leo.WHY_TAINTED,
                    leo.WHY_DIRTY, leo.WHY_INELIGIBLE, leo.WHY_ELIGIBLE],
                   np.int8)
    answered = np.array([True, False, False, False, False, True])
    assert leo.outcomes(why, answered) == {
        "answered": 2, "tainted": 1, "ineligible": 1, "beyond_depth": 1,
        "dirty": 1}
    assert tuple(leo.outcomes(why, answered)) == leo.OUTCOMES
