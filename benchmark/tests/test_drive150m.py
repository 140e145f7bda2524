"""``drive-150m`` (PR 35): the configuration's graph is ``drive-10m``'s
families times 14.125, a factor that is no whole number; the reference is
held to the program's oracle on a graph scaled the same way at a size a
test can hold, and the file's own statements are held to each other."""

import json
import os

import numpy as np
import pytest

import checkmix
import manifest
from graphs import drive
from reference.zanzibar import Reference

SCALE = 14.125
REHEARSAL = dict(n_users=2000, n_groups=40, n_folders=900, n_docs=11000)
PARAMS = dict(kind="drive", fanout=4,
              **{k: int(v * SCALE) for k, v in REHEARSAL.items()})
MIX = dict(granted_share=0.125, granted_edit_share=0.5, edit_share=0.3,
           subject_set_share=0.15)


@pytest.fixture(scope="module")
def world():
    return drive.build(PARAMS, 5)


def test_checks_agree_with_the_programs_oracle_at_a_non_integer_scale(world):
    from ketotpu.api.types import RelationTuple
    from ketotpu.engine.oracle import CheckEngine

    assert PARAMS["n_groups"] == 565 and PARAMS["n_folders"] == 12712
    store, manager = world.server_store()
    oracle = CheckEngine(store, manager)
    ref = Reference(world.cols, drive.SCHEMA)
    rows = checkmix.rows(world, MIX, np.random.default_rng(35), 1024)
    got = checkmix.reference_verdicts(ref, rows)
    want = [
        oracle.check_is_member(RelationTuple.from_json(world.tuple_json(
            drive.NS_D, rows["obj"][i], rows["rel"][i],
            checkmix.subject(rows, i))), 0)
        for i in range(len(got))
    ]
    assert got == want and any(want) and not all(want)
    # the control walks a level short and loses the nested-group grants
    control = Reference(world.cols, drive.SCHEMA, max_depth=4)
    assert checkmix.reference_verdicts(control, rows) != want


def test_the_configuration_states_its_own_graph():
    conf = manifest.cell(manifest.load(), "drive-150m.batch10k").config
    base = manifest.cell(manifest.load(),
                         "drive-10m-batch10k.batch10k").config
    for k, v in base["graph"].items():
        if k.startswith("n_"):
            assert conf["graph"][k] == v * SCALE, k
    g = conf["graph"]
    # the generator's strides give the tuple count without building it
    U, G, F, D = g["n_users"], g["n_groups"], g["n_folders"], g["n_docs"]
    tuples = (U + len(range(1, G, 3)) + (F - 1) + len(range(0, F, 3))
              + len(range(0, F, 5)) + len(range(0, F, 4)) + D
              + len(range(0, D, 7)) + len(range(0, D, 11))
              + len(range(0, D, 13)))
    assert tuples == conf["tuples"] == 150_000_162
    for k in ("engine", "daemon", "env", "limits", "guarantees", "control"):
        assert conf[k] == base[k], k
    assert conf["reduced"] == [] and conf["chips"] == 1
    assert len(conf["graph_seeds"]) == 8 == len(set(conf["graph_seeds"]))


def test_the_reckoned_device_bytes_in_the_file_are_the_sizing_functions():
    from ketotpu.engine.snapshot import device_bytes, resident_bytes

    conf = manifest.cell(manifest.load(), "drive-150m.batch10k").config
    counts = conf["assumed"]["counts"]
    groups = device_bytes(
        tuples=conf["tuples"], nodes=counts["nodes"], edges=counts["edges"],
        subjects=counts["subjects"], nt_rounds=counts["nt_rounds"],
        mt_rounds=counts["mt_rounds"])
    assert resident_bytes(groups) == conf["device_bytes"]["reckoned"]
    measured = conf["device_bytes"]["measured_after_init"]
    assert abs(measured - conf["device_bytes"]["reckoned"]) < 0.02 * measured


def test_the_bulk_kind_hands_over_drives_own_graph():
    """``drive_bulk`` is ``drive``: the same columns, and a vocabulary
    that answers like the one ``drive.py`` builds, name for name."""
    from graphs import drive_bulk

    params = dict(PARAMS, n_users=1500, n_groups=30, n_folders=700,
                  n_docs=2600)
    plain, bulk = drive.build(params, 9), drive_bulk.build(params, 9)
    assert drive_bulk.SCHEMA is drive.SCHEMA
    for c in drive.COLS:
        assert plain.cols[c].dtype == bulk.cols[c].dtype
        assert (plain.cols[c] == bulk.cols[c]).all(), c
    for k in ("f3_user", "f4_group", "doc_folder", "d7_user"):  # the draws
        assert (getattr(plain, k) == getattr(bulk, k)).all(), k
    assert plain.obj_base == bulk.obj_base and len(plain) == len(bulk)
    for got, want in zip(bulk.granted_views(np.random.default_rng(4), 64),
                         plain.granted_views(np.random.default_rng(4), 64)):
        assert (got == want).all()
    (s0, _), (s1, m1) = plain.server_store(), bulk.server_store()
    assert len(m1.namespaces()) == 4
    for space in ("namespaces", "objects", "relations", "subjects"):
        a, b = getattr(s0.vocab, space), getattr(s1.vocab, space)
        assert a.strings() == b.strings(), space
    assert s1.vocab.objects._base is not None and not s1.vocab.objects._ids
    names = ["d7", "f0", "g29", "nope", "d2599", "d2600"]
    assert (s0.vocab.objects.lookup_many(names)
            == s1.vocab.objects.lookup_many(names)).all()
    assert s1.vocab.subjects.lookup("set:Folder:f3#") == 1500 + 30 + 3
    for c in drive.COLS:
        assert (s0.export_columns()[0][c] == s1.export_columns()[0][c]).all()


@pytest.mark.parametrize("family", [("d", 100001), ("id:u", 7),
                                    ("set:Group:g", 12345, "#members")])
def test_names_written_as_bytes_are_pythons_own(family, monkeypatch):
    """``numbered`` is ``prefix + str(i) + suffix``, over every digit
    count and over a worker's edge."""
    from graphs import drive_bulk

    monkeypatch.setattr(drive_bulk, "_PASS", 4096)
    prefix, count, suffix = (*family, "")[:3]
    blob, lens = drive_bulk.names(family)
    want = [f"{prefix}{i}{suffix}" for i in range(count)]
    assert blob.tobytes().decode() == "".join(want)
    assert lens.tolist() == [len(w) for w in want]
