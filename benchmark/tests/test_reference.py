"""The plain reference against a second witness (the program's own
sequential oracle) at a size a test can hold, and the control."""

import numpy as np
import pytest

import checkmix
from graphs import drive
from reference.zanzibar import Reference

PARAMS = dict(kind="drive", n_users=2000, n_groups=40, n_folders=900,
              n_docs=11000, fanout=4)
MIX = dict(granted_share=0.125, granted_edit_share=0.5, edit_share=0.3,
           subject_set_share=0.15)


@pytest.fixture(scope="module", params=[0, 2**31 + 7])
def world(request):
    return drive.build(PARAMS, request.param), request.param


def test_the_generator_makes_the_programs_synth_graph(world):
    from ketotpu.utils.synth import build_synth_columnar

    g, seed = world
    theirs = build_synth_columnar(
        **{k: v for k, v in PARAMS.items() if k != "kind"}, seed=seed
    ).store.export_columns()[0]
    for c in drive.COLS:
        assert (theirs[c] == g.cols[c]).all(), c


def test_the_schema_table_is_the_opl(world):
    from ketotpu.opl import ast
    from ketotpu.opl.parser import parse

    namespaces, errors = parse(drive.OPL)
    assert not errors

    def table(child):
        if isinstance(child, ast.SubjectSetRewrite):
            op = "and" if child.operation is ast.Operator.AND else "or"
            return (op, [table(c) for c in child.children])
        if isinstance(child, ast.ComputedSubjectSet):
            return ("computed", drive.RELATIONS.index(child.relation))
        if isinstance(child, ast.TupleToSubjectSet):
            return ("ttu", drive.RELATIONS.index(child.relation),
                    drive.RELATIONS.index(
                        child.computed_subject_set_relation))
        return ("not", table(child.child))

    for ns in namespaces:
        if ns.name == "User":
            continue
        got = {
            drive.RELATIONS.index(r.name): (
                table(r.subject_set_rewrite)
                if r.subject_set_rewrite is not None else None)
            for r in ns.relations
        }
        assert got == drive.SCHEMA[drive.NAMESPACES.index(ns.name)]


def test_checks_agree_with_the_programs_oracle(world):
    from ketotpu.api.types import RelationTuple
    from ketotpu.engine.oracle import CheckEngine

    g, seed = world
    store, manager = g.server_store()
    oracle = CheckEngine(store, manager)
    ref = Reference(g.cols, drive.SCHEMA)
    rows = checkmix.rows(g, MIX, np.random.default_rng(seed), 1536)
    got = checkmix.reference_verdicts(ref, rows)
    want = [
        oracle.check_is_member(RelationTuple.from_json(g.tuple_json(
            drive.NS_D, rows["obj"][i], rows["rel"][i],
            checkmix.subject(rows, i))), 0)
        for i in range(len(got))
    ]
    assert got == want
    assert sum(got) >= 1536 // 8 - 8  # the granted share is granted
    assert ref.rows_examined > 0


def test_trees_agree_with_the_programs_oracle(world):
    from ketotpu.api.types import SubjectSet
    from ketotpu.engine.oracle import ExpandEngine

    g, _ = world
    store, _ = g.server_store()
    oracle = ExpandEngine(store, max_depth=5)
    ref = Reference(g.cols, drive.SCHEMA)
    base = g.G + g.F
    roots = (
        [(drive.NS_F, g.G + f, drive.R_VIEWERS) for f in range(0, g.F, 12)]
        + [(drive.NS_G, i, drive.R_MEMBERS) for i in range(g.G)]
        + [(drive.NS_D, base + i, drive.R_PARENTS) for i in range(30)]
        + [(drive.NS_D, base + 3, drive.R_BANNED)]  # no such tuples
    )
    for root in roots:
        name = g.subject_json(root)["subject_set"]
        tree = oracle.build_tree(SubjectSet(**name), 5)
        want = tree.to_json() if tree is not None else None
        assert ref.expand(root, g.subject_json) == want, root


@pytest.mark.parametrize("kind,depth", [("check", 4), ("expand", 3)])
def test_the_control_comes_out_wrong(world, kind, depth):
    """The reference walked short of the depth limit gets answers wrong
    on every seed: what the comparison has to catch."""
    g, seed = world
    ref = Reference(g.cols, drive.SCHEMA)
    control = Reference(g.cols, drive.SCHEMA, max_depth=depth)
    if kind == "check":
        rows = checkmix.rows(g, MIX, np.random.default_rng(seed), 1024)
        wrong = sum(a != b for a, b in zip(
            checkmix.reference_verdicts(control, rows),
            checkmix.reference_verdicts(ref, rows)))
    else:
        roots = [(drive.NS_F, g.G + f, drive.R_VIEWERS)
                 for f in range(0, g.F, 12)]
        wrong = sum(control.expand(r, g.subject_json)
                    != ref.expand(r, g.subject_json) for r in roots)
    assert wrong >= 3
