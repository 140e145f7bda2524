"""Fused-dispatch parity: the one-program wave (engine/fused.py) must be
bit-identical to the unfused tier cascade — VERDICTS and per-tier
ATTRIBUTION both — across mixed leopard/fast/general/error waves,
depth/width truncation edges, and write storms with generation swaps.

Breadth runs with the wave body EAGER (``_run_wave`` monkeypatched to
``_wave_body``): the traced body is the exact code the jit compiles, and
each fresh fused shape costs XLA:CPU tens of seconds — one small jitted
leg (marked slow; the CI serve-northstar job runs it) covers the real
compiled path and the steady-state no-recompile gate.

How the wave sizes its general tier (cases by the general share of a
wave) is held in tests/test_fused_lanes.py: xdist hands out whole files,
and this one is the longest of the run.
"""

import numpy as np
import pytest

from ketotpu.api.types import BadRequestError, RelationTuple
from ketotpu.engine import CheckEngine
from ketotpu.engine import fused as fdx
from ketotpu.engine.tpu import DeviceCheckEngine
from ketotpu.opl.ast import Namespace
from ketotpu.opl.parser import parse
from ketotpu.storage import InMemoryTupleStore, StaticNamespaceManager

T = RelationTuple.from_string

# same shapes as test_device_engine: the unfused programs these waves
# compare against are already warm from the rest of the suite
KW = dict(frontier=512, arena=1024, cap=2048, gen_arena=2048, vcap=1024)


@pytest.fixture
def eager(monkeypatch):
    monkeypatch.setattr(fdx, "_run_wave", fdx._wave_body)
    # adaptive schedules feed on per-engine EMA state; pin them off so
    # both engines dispatch the identical schedule every wave
    monkeypatch.setenv("KETO_NO_ADAPTIVE", "1")


def make_pair(namespaces, tuples, *, opl=None, device_kw=None, **kw):
    """Oracle + fused engine + unfused engine over ONE shared store."""
    store = InMemoryTupleStore()
    store.write_relation_tuples(*[T(s) for s in tuples])
    if opl is not None:
        parsed, errs = parse(opl)
        assert not errs, errs
        namespaces = parsed
    nsm = (
        StaticNamespaceManager(namespaces) if namespaces is not None else None
    )
    oracle = CheckEngine(store, nsm, **kw)
    dkw = dict(KW, **(device_kw or {}))
    fused = DeviceCheckEngine(
        store, nsm, fused_dispatch=True, fused_retry_lanes=1, **dkw, **kw
    )
    plain = DeviceCheckEngine(store, nsm, fused_dispatch=False, **dkw, **kw)
    return oracle, fused, plain, store


def counters(eng):
    return {
        "leopard_answered": eng.leopard_answered,
        "leopard_hits": eng.leopard_hits,
        "fallbacks": eng.fallbacks,
        "retries": eng.retries,
    }


def assert_parity(oracle, fused, plain, queries, depth=0, *, exact=True):
    """Verdict parity across all three engines plus counter/attribution
    parity between the two device engines.  ``exact=False`` skips the
    retry-counter comparison (fuzz graphs may overflow, where the fused
    path legitimately routes the tail differently with 0 retry lanes)."""
    want, errq = [], []
    for q in queries:
        try:
            want.append(oracle.check_is_member(T(q), depth))
        except BadRequestError:
            want.append("error")
            errq.append(q)
    ok = [q for q, w in zip(queries, want) if w != "error"]
    want_ok = [w for w in want if w != "error"]
    cf0, cp0 = counters(fused), counters(plain)
    rows0 = sum(fused.fused_tier_rows.values())
    waves0 = fused.fused_waves
    if ok:
        got_f = fused.batch_check([T(q) for q in ok], depth)
        got_p = plain.batch_check([T(q) for q in ok], depth)
        assert got_f == got_p, (
            f"fused/unfused divergence @depth={depth}: "
            f"{[(q, f, p) for q, f, p in zip(ok, got_f, got_p) if f != p]}"
        )
        assert got_f == want_ok, (
            f"fused/oracle divergence @depth={depth}: "
            f"{[(q, f, w) for q, f, w in zip(ok, got_f, want_ok) if f != w]}"
        )
    for q in errq:
        # an error row rides the wave, is flagged by _classify on both
        # paths, and the oracle fallback reproduces the typed error
        with pytest.raises(BadRequestError):
            fused.batch_check([T(q)], depth)
        with pytest.raises(BadRequestError):
            plain.batch_check([T(q)], depth)
    cf = {k: v - cf0[k] for k, v in counters(fused).items()}
    cp = {k: v - cp0[k] for k, v in counters(plain).items()}
    if not exact:
        cf.pop("retries"), cp.pop("retries")
    assert cf == cp, f"counter divergence @depth={depth}: {cf} != {cp}"
    # attribution closure: every real row of every fused wave lands in
    # exactly one tier bucket
    rows = sum(fused.fused_tier_rows.values()) - rows0
    assert rows == len(ok) + len(errq)
    assert fused.fused_waves - waves0 == len(errq) + (1 if ok else 0)
    # the single-fetch invariant the whole design exists for
    assert fused.fused_waves == fused.fused_d2h_fetches


OPL_MIXED = """
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

MIXED_TUPLES = (
    [f"Doc:d{i % 5}#editors@User:u{i}" for i in range(25)]
    + [
        "Group:g#members@User:gm1",
        "Group:g2#members@Group:g#members",
        "Group:g#members@Group:g2#members",  # cycle through nesting
        "Doc:d1#editors@Group:g2#members",
        "Doc:d2#banned@User:u2",
        "Doc:d3#banned@User:u8",
    ]
)


def mixed_queries():
    qs = []
    for i in range(20):
        qs.append(f"Doc:d{i % 5}#view@User:u{i}")        # fast tier
        qs.append(f"Doc:d{i % 5}#edit@User:u{i}")        # general tier
    qs += [
        "Group:g#members@User:gm1",                      # leopard-answerable
        "Group:g2#members@User:gm1",                     # nested closure
        "Doc:d1#view@User:gm1",
        "Doc:d1#edit@User:gm1",
        "Doc:d2#edit@User:u2",                           # banned -> NOT arm
        "Doc:d0#nope@User:u0",                           # undeclared: error
        "Nope:x#view@User:u0",                           # unknown ns: error
    ]
    return qs


class TestMixedWaves:
    def test_mixed_tiers_all_depths(self, eager):
        o, f, p, _ = make_pair(None, MIXED_TUPLES, opl=OPL_MIXED)
        for depth in (0, 1, 2, 3, 6):
            assert_parity(o, f, p, mixed_queries(), depth)
        # the wave actually exercised every device tier
        tr = f.fused_tier_rows
        assert tr["fastpath"] > 0 and tr["general"] > 0
        assert tr["oracle"] > 0  # the two error rows

    def test_leopard_rows_attributed(self, eager):
        o, f, p, _ = make_pair(None, MIXED_TUPLES, opl=OPL_MIXED)
        qs = [
            "Group:g#members@User:gm1",
            "Group:g2#members@User:gm1",
            "Group:g#members@User:nobody",
            "Group:g2#members@User:nobody",
        ]
        assert_parity(o, f, p, qs, 6)
        if f.leopard_answered:  # index built => closure answered on-device
            assert f.fused_tier_rows["leopard"] > 0
            assert f.leopard_answered == p.leopard_answered
            assert f.leopard_hits == p.leopard_hits

    def test_cache_rows_keep_leopard_precedence(self, eager):
        _, f, p, _ = make_pair(None, MIXED_TUPLES, opl=OPL_MIXED)
        qs = [T(q) for q in mixed_queries()[:24]]
        first_f, first_p = f.batch_check(qs, 4), p.batch_check(qs, 4)
        # second pass: identical wave, now cache-warm on both engines
        assert f.batch_check(qs, 4) == first_f
        assert p.batch_check(qs, 4) == first_p
        assert first_f == first_p


class TestTruncationEdges:
    def test_width_truncation(self, eager):
        tuples = [f"w:o#r@w:g{i}#m" for i in range(6)] + ["w:g5#m@user"]
        o, f, p, _ = make_pair(
            [Namespace("w")], tuples, max_width=5
        )
        o.max_width = 5
        for depth in (0, 2):
            assert_parity(o, f, p, ["w:o#r@user", "w:o#r@ghost"], depth)

    def test_depth_exhaustion(self, eager):
        tuples = [
            "test:object#admin@user",
            "test:object#owner@test:object#admin",
            "test:object#access@test:object#owner",
        ]
        o, f, p, _ = make_pair([Namespace("test")], tuples)
        q = ["test:object#access@user", "test:object#owner@user"]
        for depth in (0, 1, 2, 3, 4, 10):
            assert_parity(o, f, p, q, depth)

    def test_cycle(self, eager):
        tuples = [
            "g:a#member@g:b#member",
            "g:b#member@g:a#member",
            "g:b#member@user",
        ]
        o, f, p, _ = make_pair([Namespace("g")], tuples)
        assert_parity(
            o, f, p, ["g:a#member@user", "g:b#member@user", "g:a#member@x"]
        )


class TestWriteStorm:
    def test_generation_swaps_mid_storm(self, eager):
        """Interleave write bursts with mixed waves: every wave must see
        the freshest snapshot+overlay state identically on both paths,
        across overlay folds and full generation swaps."""
        o, f, p, store = make_pair(None, MIXED_TUPLES, opl=OPL_MIXED)
        rng = np.random.default_rng(7)
        qs = mixed_queries()
        for round_ in range(6):
            burst = [
                T(f"Doc:d{rng.integers(5)}#editors@User:w{round_}_{j}")
                for j in range(int(rng.integers(1, 20)))
            ]
            store.write_relation_tuples(*burst)
            if round_ % 2:
                store.delete_relation_tuples(burst[0])
            assert_parity(o, f, p, qs, int(rng.integers(0, 5)), exact=False)
            # both engines absorbed the same writes (fold or rebuild)
            assert f.generation >= 0 and p.generation >= 0
        extra = [f"Doc:d1#view@User:w3_{j}" for j in range(8)]
        assert_parity(o, f, p, extra, 2, exact=False)


def _random_case(rng):
    rels = ["r0", "r1", "r2", "r3"]
    lines = [
        "import { Namespace, SubjectSet, Context } "
        "from '@ory/keto-namespace-types'"
    ]
    namespaces = []
    for i in range(int(rng.integers(1, 3))):
        name = f"N{i}"
        related = "\n".join(f"    {r}: N0[]" for r in rels[:2])
        choices = [
            "this.related.r0.includes(ctx.subject)",
            "this.related.r1.includes(ctx.subject)",
            "this.related.r0.traverse((x) => x.permits.r3(ctx))",
        ]
        k = int(rng.integers(1, 3))
        expr2 = " || ".join(
            rng.choice(choices, size=k, replace=False).tolist()
        )
        style = int(rng.integers(0, 3))
        if style == 0:
            expr3 = ("this.related.r0.includes(ctx.subject) && "
                     "this.related.r1.includes(ctx.subject)")
        elif style == 1:
            expr3 = ("this.related.r0.includes(ctx.subject) && "
                     "!this.related.r1.includes(ctx.subject)")
        else:
            expr3 = "this.related.r1.includes(ctx.subject)"
        lines.append(
            f"class {name} implements Namespace {{\n"
            f"  related: {{\n{related}\n  }}\n"
            f"  permits = {{\n"
            f"    r2: (ctx: Context): boolean =>\n      {expr2},\n"
            f"    r3: (ctx: Context): boolean =>\n      {expr3},\n"
            f"  }}\n}}"
        )
        namespaces.append(name)
    tuples = set()
    for _ in range(int(rng.integers(5, 25))):
        ns = rng.choice(namespaces)
        if rng.random() < 0.5:
            subj = f"u{rng.integers(3)}"
        else:
            subj = f"{rng.choice(namespaces)}:o{rng.integers(4)}#r0"
        tuples.add(f"{ns}:o{rng.integers(4)}#{rng.choice(rels[:2])}@{subj}")
    queries = [
        f"{rng.choice(namespaces)}:o{rng.integers(4)}"
        f"#{rng.choice(rels)}@u{rng.integers(3)}"
        for _ in range(20)
    ]
    return "\n".join(lines), sorted(tuples), queries


@pytest.mark.parametrize("seed", range(8))
def test_fuzz_fused_parity(eager, seed):
    rng = np.random.default_rng(seed)
    source, tuples, queries = _random_case(rng)
    o, f, p, _ = make_pair(None, tuples, opl=source)
    for depth in (0, 2, 4):
        assert_parity(o, f, p, queries, depth, exact=False)


@pytest.mark.slow
def test_fused_jit_compiled_leg(monkeypatch):
    """The real compiled path at small shapes: parity + warm-wave
    stability + ZERO after-warm XLA compiles on a same-shape wave."""
    from ketotpu import compilewatch

    # pin the schedule: the first wave installs the occupancy EMA, and the
    # adaptive ladder would otherwise pick a smaller rung (= new static
    # schedule = one legitimate recompile) on the second wave
    monkeypatch.setenv("KETO_NO_ADAPTIVE", "1")

    o, f, p, _ = make_pair(
        None, MIXED_TUPLES, opl=OPL_MIXED,
        device_kw=dict(
            frontier=256, arena=512, cap=1024, gen_arena=1024, vcap=512,
            gen_levels=2, gen_levels_max=3,
        ),
    )
    qs = [T(q) for q in mixed_queries()[:24]]
    first = f.batch_check(qs, 4)
    assert first == p.batch_check(qs, 4)
    before = compilewatch.get().compiles_total
    assert f.batch_check(qs, 4) == first
    assert compilewatch.get().compiles_total == before, (
        "after-warm recompile on a same-shape fused wave"
    )
    assert f.fused_waves == f.fused_d2h_fetches


def test_config_defaults_and_env_override():
    from ketotpu.driver.config import Provider

    p = Provider(env={})
    assert p.get("engine.fused_dispatch") is True
    assert p.get("engine.fused_retry_lanes") == 1
    p2 = Provider(env={"KETO_ENGINE_FUSED_DISPATCH": "false",
                       "KETO_ENGINE_FUSED_RETRY_LANES": "3"})
    assert p2.get("engine.fused_dispatch") is False
    assert p2.get("engine.fused_retry_lanes") == 3
    from ketotpu.driver.config import ConfigError

    # env={} so conftest's KETO_ENGINE_FUSED_DISPATCH override can't mask
    # the bogus value before validation sees it
    with pytest.raises(ConfigError):
        Provider({"engine": {"fused_retry_lanes": -1}}, env={})
    with pytest.raises(ConfigError):
        Provider({"engine": {"fused_dispatch": "yes"}}, env={})


def test_probe_gather_counter_moves_by_lookup_gathers_a_wave(eager):
    """``keto_fused_probe_gathers_total{table}``: every collected fused
    wave adds each served table's ``lookup_gathers`` (engine/hashtab.py),
    so the counter over the waves is the gathers a lookup cost them."""
    from ketotpu.engine import hashtab

    oracle, fused, plain, _ = make_pair(None, MIXED_TUPLES, opl=OPL_MIXED)
    fused.snapshot()
    per_wave = {p: hashtab.lookup_gathers(hashtab.subtables(
        fused._device_arrays, p + "_")) for p in hashtab.TABLES}
    assert per_wave["nt"] == fused.probe_rounds["nt"] + 3
    assert per_wave["mt"] == fused.probe_rounds["mt"] + 2
    assert set(fused.fused_probe_gathers.values()) == {0}
    for waves in (1, 2, 3):
        fused.batch_check([T(q) for q in mixed_queries()[:6]])
        assert fused.fused_waves == waves
        assert fused.fused_probe_gathers == {
            p: waves * g for p, g in per_wave.items()}
    assert plain.fused_probe_gathers == dict.fromkeys(hashtab.TABLES, 0)
