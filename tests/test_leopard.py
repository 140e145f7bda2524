"""Leopard closure-index tests (ketotpu/leopard/).

Property tests: randomized nested-group graphs (depth <= 12, cycles
allowed) must produce identical check verdicts and identical
ListObjects/ListSubjects results on the closure-index path and the host
oracle — before and after randomized write/delete deltas.  Plus the
ISSUE's zero-fallback guarantee: on a clean (rewrite-free, narrow) graph
every deep-nesting check is answered from the index without touching the
oracle, and a slow smoke drives `keto-tpu list` against the real
`serve --workers 2` topology.
"""

import json
import os
import pathlib
import random
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

from ketotpu.api.types import RelationTuple, SubjectID
from ketotpu.engine import CheckEngine
from ketotpu.engine.tpu import DeviceCheckEngine
from ketotpu.leopard import HostListEngine
from ketotpu.opl.ast import Namespace
from ketotpu.storage import InMemoryTupleStore, StaticNamespaceManager

T = RelationTuple.from_string
FIXTURES = pathlib.Path(__file__).parent / "fixtures"
MAX_DEPTH = 16  # covers depth-12 chains plus the closure's +2 depth slack


def _random_graph(rng, *, n_groups=16, n_users=10, depth=12):
    """Nested-group tuples: a guaranteed depth-`depth` containment chain,
    random extra containment edges in BOTH directions (so cycles occur),
    and users scattered over groups."""
    groups = [f"G{i}" for i in range(n_groups)]
    users = [f"u{i}" for i in range(n_users)]
    tuples = set()
    for i in range(min(depth, n_groups) - 1):
        tuples.add(f"g:{groups[i]}#member@g:{groups[i + 1]}#member")
    for _ in range(n_groups):
        a, b = rng.sample(groups, 2)  # direction unconstrained: cycles OK
        tuples.add(f"g:{a}#member@g:{b}#member")
    for u in users:
        for g in rng.sample(groups, rng.randint(1, 3)):
            tuples.add(f"g:{g}#member@{u}")
    return groups, users, sorted(tuples)


def _engines(tuples):
    store = InMemoryTupleStore()
    store.write_relation_tuples(*[T(s) for s in tuples])
    nsm = StaticNamespaceManager([Namespace("g"), Namespace("u")])
    oracle = CheckEngine(store, nsm, max_depth=MAX_DEPTH)
    device = DeviceCheckEngine(
        store, nsm,
        frontier=512, arena=1024, cap=2048, gen_arena=2048, vcap=1024,
        max_depth=MAX_DEPTH,
    )
    return store, oracle, device


def _assert_agreement(oracle, device, groups, users, store):
    host = HostListEngine(store)
    queries = [
        T(f"g:{g}#member@{u}") for g in groups for u in users
    ]
    want = [bool(oracle.check_is_member(q, 0)) for q in queries]
    got = [bool(v) for v in device.batch_check(queries)]
    assert got == want, [
        (str(q), g, w) for q, g, w in zip(queries, got, want) if g != w
    ]
    for u in users:
        a, _ = device.list_objects("g", "member", SubjectID(u), page_size=10_000)
        b, _ = host.list_objects("g", "member", SubjectID(u), page_size=10_000)
        assert list(a) == list(b), f"list_objects({u}): {a} != {b}"
    for g in groups:
        a, _ = device.list_subjects("g", g, "member", page_size=10_000)
        b, _ = host.list_subjects("g", g, "member", page_size=10_000)
        assert sorted(map(str, a)) == sorted(map(str, b)), (
            f"list_subjects({g})"
        )


@pytest.mark.parametrize("seed", range(5))
def test_random_graphs_checks_and_listings_match_oracle(seed):
    rng = random.Random(seed)
    groups, users, tuples = _random_graph(rng)
    store, oracle, device = _engines(tuples)
    _assert_agreement(oracle, device, groups, users, store)

    # randomized deltas: the incremental fold (adds) and the dirty-set
    # path (deletes) must both preserve agreement
    live = list(tuples)
    for round_ in range(3):
        writes = []
        for _ in range(rng.randint(1, 4)):
            g = rng.choice(groups)
            if rng.random() < 0.5:
                writes.append(f"g:{g}#member@u_new{round_}_{rng.randint(0, 3)}")
            else:
                writes.append(
                    f"g:{g}#member@g:{rng.choice(groups)}#member"
                )
        writes = [w for w in writes if w not in live]
        if writes:
            store.write_relation_tuples(*[T(s) for s in writes])
            live.extend(writes)
        if live and rng.random() < 0.8:
            victims = rng.sample(live, rng.randint(1, min(3, len(live))))
            store.delete_relation_tuples(*[T(s) for s in victims])
            live = [s for s in live if s not in victims]
        extra_users = sorted(
            {s.split("@", 1)[1] for s in live if "#member@u" in s
             and "#member@g:" not in s}
        )
        _assert_agreement(
            oracle, device, groups, sorted(set(users) | set(extra_users)),
            store,
        )


def test_deep_chains_answered_without_fallback():
    """Depth-12 chains on a clean graph: every check resolves from the
    closure index — zero oracle fallbacks, verdicts equal to the oracle."""
    from ketotpu.utils.synth import build_deep_groups, deep_queries

    deep = build_deep_groups(depth=12, n_chains=4, n_users=16, seed=5)
    eng = DeviceCheckEngine(deep.store, deep.manager, max_depth=MAX_DEPTH)
    eng.snapshot()
    oracle = CheckEngine(deep.store, deep.manager, max_depth=MAX_DEPTH)
    qs = deep_queries(deep, 64, seed=7)
    fb0 = eng.fallbacks
    ok, needs = eng.batch_check_device_only(qs)
    assert not np.any(needs), "deep checks flagged host fallback"
    assert eng.fallbacks == fb0, "deep checks touched the oracle"
    assert eng.leopard_answered >= len(qs)
    want = [bool(oracle.check_is_member(q, 0)) for q in qs]
    assert [bool(v) for v in ok] == want
    assert any(want) and not all(want)  # the workload exercises both verdicts


def test_leopard_disabled_parity():
    """leopard.enabled=false: verdicts and listings are unchanged (the
    listing surface falls back to the host oracle)."""
    rng = random.Random(99)
    groups, users, tuples = _random_graph(rng)
    store, oracle, _ = _engines(tuples)
    nsm = StaticNamespaceManager([Namespace("g"), Namespace("u")])
    off = DeviceCheckEngine(
        store, nsm,
        frontier=512, arena=1024, cap=2048, gen_arena=2048, vcap=1024,
        max_depth=MAX_DEPTH, leopard={"enabled": False},
    )
    off.snapshot()
    assert off._leopard is None
    _assert_agreement(oracle, off, groups, users, store)
    assert off.leopard_answered == 0
    assert off.leopard_list_fallbacks > 0  # listings served by the host


def test_listing_pagination_walks_everything_once():
    tuples = [
        "g:root#member@g:mid#member",
        "g:mid#member@g:leaf#member",
    ] + [f"g:leaf#member@u{i}" for i in range(7)]
    store, _, device = _engines(tuples)
    full, tok = device.list_subjects("g", "root", "member", page_size=10_000)
    assert tok == ""
    walked, tok = [], ""
    for _ in range(50):
        page, tok = device.list_subjects(
            "g", "root", "member", page_size=2, page_token=tok
        )
        walked.extend(page)
        if not tok:
            break
    assert [str(s) for s in walked] == [str(s) for s in full]
    # and ListObjects the other way around
    full, _ = device.list_objects("g", "member", SubjectID("u3"), page_size=10_000)
    assert full == ["leaf", "mid", "root"]


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_cli_list_against_worker_topology(tmp_path, capsys):
    """`keto-tpu list` against the real `serve --workers 2` topology:
    the worker wire protocol must round-trip both listing RPCs."""
    from ketotpu.driver import Provider, Registry

    db = tmp_path / "leo.db"
    seed_reg = Registry(Provider({"dsn": f"sqlite://{db}"}))
    seed_reg.store().migrate_up()
    seed_reg.store().write_relation_tuples(*[T(s) for s in [
        "Group:admin#members@alice",
        "Group:admin#members@Group:eng#members",
        "Group:eng#members@bob",
    ]])

    ports = {n: _free_port() for n in ("read", "write", "metrics", "opl")}
    config = {
        "dsn": f"sqlite://{db}",
        "serve": {
            n: {"host": "127.0.0.1", "port": p} for n, p in ports.items()
        },
        "namespaces": {
            "location": str(FIXTURES / "rewrites_namespaces.keto.ts")
        },
        "engine": {"kind": "tpu", "frontier": 512, "arena": 2048,
                   "max_batch": 128},
        "log": {"request_log": False},
    }
    cfg_path = tmp_path / "leo.json"
    cfg_path.write_text(json.dumps(config))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # own process group: teardown must reap the owner/worker subprocesses
    # even when the supervisor dies before its signal handling is up
    proc = subprocess.Popen(
        [sys.executable, "-m", "ketotpu.cli", "serve",
         "-c", str(cfg_path), "--workers", "2"],
        env=env, cwd=str(pathlib.Path(__file__).parent.parent),
        start_new_session=True,
    )
    read = f"127.0.0.1:{ports['read']}"
    try:
        ready_by = time.monotonic() + 180.0
        while True:
            assert proc.poll() is None, "serve --workers died during boot"
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{ports['metrics']}/health/ready",
                    timeout=2.0,
                ) as r:
                    if r.status == 200:
                        break
            except OSError:
                pass
            assert time.monotonic() < ready_by, "topology never became ready"
            time.sleep(0.5)

        from ketotpu import cli

        insecure = "--insecure-disable-transport-security"
        rc = cli.main(["list", "objects", "Group", "members", "bob",
                       "--read-remote", read, insecure])
        out = capsys.readouterr().out
        assert rc == 0
        assert "admin" in out and "eng" in out
        rc = cli.main(["list", "subjects", "Group", "admin", "members",
                       "--read-remote", read, insecure])
        out = capsys.readouterr().out
        assert rc == 0
        for want in ("alice", "bob", "Group:eng#members"):
            assert want in out
        # REST leg through the same topology
        with urllib.request.urlopen(
            f"http://{read}/relation-tuples/list-objects?"
            "namespace=Group&relation=members&subject_id=bob",
            timeout=10.0,
        ) as r:
            data = json.loads(r.read())
        assert data["objects"] == ["admin", "eng"]
    finally:
        import signal

        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait(timeout=5)


@pytest.mark.parametrize("rows,too_large", [(40, False), (41, True)])
def test_closure_larger_than_max_pairs_is_refused_before_it_is_built(
        rows, too_large):
    """Every live tuple is an element pair of its own node, so a store of
    more rows than ``max_pairs`` cannot be indexed: the build says so
    before it gathers a column (at 150M rows those are 15 GB), and the
    engine serves without the index."""
    from ketotpu.engine import delta as dl
    from ketotpu.engine.vocab import Vocab
    from ketotpu.leopard import closure as leo

    cols = dl.TupleColumns(Vocab())
    for i in range(rows):
        cols.apply(1, RelationTuple("Group", f"g{i % 7}", "members",
                                    SubjectID(f"u{i}")))
    idx = leo.ClosureIndex(max_pairs=40)
    if too_large:
        with pytest.raises(leo.ClosureTooLarge, match="41 tuples exceed"):
            idx.build_from_cols(cols, None)
        assert idx.pairs == 0 and idx.n_nodes == 0
    else:
        idx.build_from_cols(cols, None)
        assert idx.pairs == rows and idx.n_nodes == 7
