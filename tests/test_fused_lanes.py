"""The fused wave sizes its general tier by the rows that need it.

``_encode_fused`` picks ``gen_lanes`` from the count of general (AND/NOT)
rows in the wave with the unfused ``_run_general``'s half-octave rule, and
``fused._wave_body`` compacts those rows into that many lanes.  One
parametrised test, cases by the general share of a wave, holds everything
the wave returns — verdicts, the general tier's code/over/dirty/retried
bits, the fast and leopard masks, the fallback mask, the tier attribution
and the occupancy vector ``_update_gen_occ`` is fed — to the unfused
cascade's on the same rows, and the device's own verdicts to the oracle's.

The programs are jitted, with the levels cut so that XLA:CPU compiles each
in seconds, on ONE seeded graph and one pair of engines a module: a case
whose ``(Q, gen_lanes)`` an earlier case ran reuses its programs.  This is
a file of its own, beside tests/test_fused.py, because xdist hands out
whole files and that one is already the longest of the run.
"""

import numpy as np
import pytest

from ketotpu import compilewatch
from ketotpu.engine import fused as fdx
from ketotpu.engine.optable import R_ERR

from test_fused import OPL_MIXED, T, make_pair

DEPTH = 3
#: levels cut to what OPL_MIXED's skeletons need.  An ``edit`` root puts two
#: tasks on level 1, and ``gen_arena`` caps a level at 512: up to 256 roots
#: fit, the 257th and later overflow at the base schedule (over bits, and
#: the oracle's rows) and fit at the retry's 4 x 512
DEVICE_KW = dict(
    frontier=1024, arena=2048, cap=2048, gen_arena=512, vcap=512,
    gen_levels=3, gen_levels_max=4,
)
N_USERS, N_GROUPS, N_DOCS = 80, 8, 64


def seeded_graph(seed):
    """Tuples of OPL_MIXED's schema: docs with a few editors (users, and
    for half of them a group), bans on some of their own editors, groups
    of eight users of which every third nests the next."""
    rng = np.random.default_rng(seed)
    tuples, editors = [], {}
    for g in range(N_GROUPS):
        for u in rng.choice(N_USERS, 8, replace=False):
            tuples.append(f"Group:g{g}#members@User:u{u}")
        if g % 3 == 0:
            tuples.append(
                f"Group:g{g}#members@Group:g{(g + 1) % N_GROUPS}#members")
    for d in range(N_DOCS):
        editors[d] = [int(u) for u in rng.choice(N_USERS, 3, replace=False)]
        for u in editors[d]:
            tuples.append(f"Doc:d{d}#editors@User:u{u}")
        if rng.random() < 0.5:
            tuples.append(
                f"Doc:d{d}#editors@Group:g{rng.integers(N_GROUPS)}#members")
        if rng.random() < 0.4:
            tuples.append(f"Doc:d{d}#banned@User:u{editors[d][0]}")
    return sorted(set(tuples)), editors


def seeded_wave(editors, n, n_general, seed):
    """``n`` rows of which ``n_general`` (at seeded places) are ``edit``;
    the others ``view`` (fast tier), plain ``editors`` and group
    ``members`` (closure index).  A third of the rows ask for a doc's own
    editor, so that both verdicts occur in every tier."""
    rng = np.random.default_rng(seed)
    general = np.zeros(n, bool)
    general[rng.permutation(n)[:n_general]] = True
    rows = []
    for is_general in general:
        d = int(rng.integers(N_DOCS))
        u = (editors[d][int(rng.integers(3))] if rng.random() < 1 / 3
             else int(rng.integers(N_USERS)))
        if is_general:
            rows.append(f"Doc:d{d}#edit@User:u{u}")
            continue
        kind = rng.integers(4)
        if kind == 0:
            rows.append(f"Group:g{d % N_GROUPS}#members@User:u{u}")
        elif kind == 1:
            rows.append(f"Doc:d{d}#editors@User:u{u}")
        else:
            rows.append(f"Doc:d{d}#view@User:u{u}")
    return [T(r) for r in rows], general


@pytest.fixture(scope="module")
def world():
    tuples, editors = seeded_graph(30)
    oracle, fused, plain, _ = make_pair(
        None, tuples, opl=OPL_MIXED, device_kw=DEVICE_KW, max_depth=DEPTH,
    )
    return oracle, fused, plain, editors


@pytest.fixture
def pinned(monkeypatch):
    # one schedule for both engines, whatever the EMAs have seen
    monkeypatch.setenv("KETO_NO_ADAPTIVE", "1")


def run_wave(eng, queries, retry=True):
    """Dispatch and collect one chunk on ``eng``: the wave, the
    verdicts, the fallback mask and what ``_update_gen_occ`` was fed."""
    fed = []
    with pytest.MonkeyPatch.context() as mp:
        real = eng._update_gen_occ
        mp.setattr(
            eng, "_update_gen_occ",
            lambda occ, fast_b: (fed.append((np.array(occ), fast_b)),
                                 real(occ, fast_b)),
        )
        wave = eng._dispatch(queries, 0)
        allowed, fallback = eng._collect(wave, retry=retry)
    return wave, allowed, fallback, fed


# (rows, general rows, gen_lanes expected, retry lanes)
CASES = [
    pytest.param(1024, 0, 0, 0, id="none-of-1024"),
    pytest.param(1024, 1, 256, 0, id="one-of-1024"),
    pytest.param(1024, 255, 256, 0, id="under-the-edge"),
    pytest.param(1024, 256, 256, 0, id="at-the-edge"),
    pytest.param(1024, 257, 384, 0, id="over-the-edge"),
    pytest.param(1024, 307, 384, 0, id="30pct-of-1024"),
    pytest.param(1024, 307, 384, 1, id="30pct-of-1024-retry-lane"),
    pytest.param(256, 256, 256, 0, id="all-of-256"),
]


@pytest.mark.parametrize("n, n_general, lanes, retry_lanes", CASES)
def test_general_tier_runs_at_its_rows(world, pinned, monkeypatch, n,
                                       n_general, lanes, retry_lanes):
    oracle, fused, plain, editors = world
    monkeypatch.setattr(fused, "fused_retry_lanes", retry_lanes)
    queries, general = seeded_wave(editors, n, n_general, seed=n + n_general)
    want = np.array([oracle.check_is_member(q, 0) for q in queries])

    tiers0 = dict(fused.fused_tier_rows)
    sized0 = (fused.fused_general_rows, fused.fused_general_lanes)
    retries0 = (fused.retries, plain.retries)
    fh, f_allowed, f_fallback, f_fed = run_wave(fused, queries)
    ph, p_allowed, p_fallback, p_fed = run_wave(
        plain, queries, retry=bool(retry_lanes))

    # the size follows the rows that need the tier, not the wave
    assert fh.meta["gen_rows"] == n_general
    assert fh.meta["gen_lanes"] == lanes and ph.meta is None
    assert (fused.fused_general_rows - sized0[0],
            fused.fused_general_lanes - sized0[1]) == (n_general, lanes)
    assert (fh.general == general).all() and (ph.general == general).all()

    # verdicts and the fallback mask: the cascade's, and where the device
    # answered, the oracle's
    assert (f_fallback == p_fallback).all()
    assert (f_allowed == p_allowed).all()
    assert (f_allowed[~f_fallback] == want[~f_fallback]).all()
    if n_general >= 255:  # both verdicts occur among the general rows
        assert f_allowed[general].any() and not f_allowed[general].all()
    # the 257th root and later overflow, unless the retry lane takes them
    assert f_fallback[general].sum() == (
        0 if retry_lanes else max(n_general - 256, 0))

    bits = np.asarray(fh.fused)[:n]
    # tier 2, base run: code, over, dirty of every general row
    if n_general:
        base = np.asarray(ph.gen[0])[:n_general].astype(np.int32)
        unres = (((base >> 2) & 1) == 1) & (((base >> 3) & 1) == 0) & (
            (base & 3) != R_ERR)
        retried = ((bits >> 9) & 1).astype(bool)
        assert (retried[general] == (unres if retry_lanes else False)).all()
        kept = ~retried[general]
        assert ((bits[general] & 0xF)[kept] == (base & 0xF)[kept]).all()
        if retry_lanes:
            assert retried.any(), "the case is built to overflow at the base"
            assert not f_fallback[general].any()
            assert (fused.retries - retries0[0]
                    == plain.retries - retries0[1] >= retried.sum())
    assert not (bits[~general] & 0x20F).any()
    # tier 1 and tier 0 masks
    fast = ~general
    if ph.fast is not None:
        assert (((bits >> 4) & 1)[fast]
                == (np.asarray(ph.fast)[:n] & 1)[fast]).all()
    if ph.leo_res is not None:
        assert (((bits >> 6) & 1).astype(bool) == ph.leo_res[1]).all()
        assert (((bits >> 7) & 1).astype(bool) == ph.leo_res[0]).all()

    # what the adaptive EMAs are fed: the cascade's vector, root for root
    assert len(f_fed) == len(p_fed) == (1 if n_general else 0)
    for (f_occ, f_b), (p_occ, p_b) in zip(f_fed, p_fed):
        assert f_b == p_b and (f_occ == p_occ).all()
        assert f_occ[0] == n_general

    # attribution: every row in one tier, by the returned masks
    moved = {t: fused.fused_tier_rows[t] - tiers0[t] for t in tiers0}
    leo = ((bits >> 6) & 1).astype(bool)
    assert moved == {
        "cache": 0,
        "leopard": int(leo.sum()),
        "oracle": int(f_fallback.sum()),
        "general": int((general & ~f_fallback).sum()),
        "fastpath": int((~general & ~leo & ~f_fallback).sum()),
    }


def test_a_wave_of_general_rows_alone_keeps_the_fast_tier(world, monkeypatch):
    """Two programs a shape, not three: the general tier compiles out of
    an all-fast wave, the fast tier stays in whatever a wave with general
    rows holds, so one AND/NOT single warms what a mixed wave runs."""
    _, fused, _, editors = world
    seen = []
    monkeypatch.setattr(
        fused, "_dispatch_fused",
        lambda wave, g, qpack, scheds: seen.append(scheds))
    for n_general in (0, 1, 7, 256):
        queries, _ = seeded_wave(editors, 256, n_general, seed=n_general)
        fused._dispatch(queries, 0)
    single, _ = seeded_wave(editors, 1, 1, seed=9)
    fused._dispatch(single, 0)
    assert [(s["fast_sched"] is not None, s["gen_lanes"]) for s in seen] == [
        (True, 0), (True, 256), (True, 256), (True, 256), (True, 256)]
    assert len({(s["fast_sched"], s["gen"]) for s in seen[1:]}) == 1


def test_one_program_for_every_count_in_a_bucket(world, pinned, monkeypatch):
    """300 and 333 general rows of 1024 both pad to 384 lanes: the second
    wave compiles nothing, and the compile scope's text names the size."""
    _, fused, _, editors = world
    monkeypatch.setattr(fused, "fused_retry_lanes", 0)
    texts = []
    real = compilewatch.scope

    def spy(fn, signature):
        texts.append((fn, signature()))
        return real(fn, signature)

    monkeypatch.setattr(fdx.compilewatch, "scope", spy)
    first, _ = seeded_wave(editors, 1024, 300, seed=1)
    second, _ = seeded_wave(editors, 1024, 333, seed=2)

    def one_wave(queries):
        # a wave of its own: ``batch_check`` would cut 1024 rows by what
        # this file's small frontier holds (engine/wave.py wave_cap)
        fused._finish_chunk(queries, fused._dispatch(queries, 0), 0)

    one_wave(first)
    before = compilewatch.get().compiles_total
    waves = fused.fused_waves
    one_wave(second)
    assert fused.fused_waves == waves + 1
    assert compilewatch.get().compiles_total == before
    assert [fn for fn, _ in texts] == ["fused_wave", "fused_wave"]
    assert texts[0][1] == texts[1][1]
    assert texts[0][1].startswith("Q=1024 GQ=384 ")



def test_launchers_agree_on_a_dirty_row_and_a_forced_overflow(
        world, pinned, monkeypatch):
    """One seeded mixed wave through every branch the launchers share
    (engine/wave.py).  Rows the overlay made dirty fall back on the
    cascade and on the fused wave alike; fast rows made to read
    "overflowed" on the cascade's first pass are answered by its retry as
    if nothing had happened, and stay in ``fallback`` without one.  The
    file's last test: the write leaves a dirty row behind.
    (tests/test_parallel.py holds the mesh's cascade to the same.)"""
    oracle, fused, plain, editors = world
    monkeypatch.setattr(fused, "fused_retry_lanes", 1)
    queries, general = seeded_wave(editors, 1024, 307, seed=1331)
    # a group edge onto a doc that had none: the doc's editors row goes
    # dirty, and the group's member reaches the doc through it alone
    tuples, _ = seeded_graph(30)
    doc = next(d for d in range(N_DOCS)
               if not any(t.startswith(f"Doc:d{d}#editors@Group:")
                          for t in tuples))
    user = next(int(t.split("User:u")[1]) for t in tuples
                if t.startswith("Group:g1#members@User:")
                and int(t.split("User:u")[1]) not in editors[doc])
    fused.snapshot(), plain.snapshot()  # the write below rides the overlay
    plain.store.write_relation_tuples(
        T(f"Doc:d{doc}#editors@Group:g1#members"))
    dirty = [int(np.flatnonzero(~general)[0]), int(np.flatnonzero(general)[0])]
    queries[dirty[0]] = T(f"Doc:d{doc}#view@User:u{user}")
    queries[dirty[1]] = T(f"Doc:d{doc}#edit@User:u{user}")
    want = np.array([oracle.check_is_member(q, 0) for q in queries])
    assert want[dirty[0]]  # through the new edge

    _, f_allowed, f_fallback, _ = run_wave(fused, queries)
    assert f_fallback[dirty[0]], "the device cannot walk the new edge"
    # ... nor can it for the other rows on that doc; nothing else falls
    # back: the retry lanes take every overflow
    assert all(q.object == f"d{doc}"
               for q, fell in zip(queries, f_fallback) if fell)
    assert (f_allowed[~f_fallback] == want[~f_fallback]).all()

    # the cascade, its first fast fetch made to say that five rows the
    # device answered ran over
    real = plain._fast_bits
    forced = np.flatnonzero(
        ~general & np.array([q.relation == "view" for q in queries])
        & ~f_fallback)[:5]
    passes = []

    def overflowed(res, k):
        bits = real(res, k)
        if not passes:
            bits.found[forced], bits.over[forced] = False, True
        passes.append(k)
        return bits

    monkeypatch.setattr(plain, "_fast_bits", overflowed)
    retries0, retry0 = plain.retries, plain.phase_counts.get("check_retry", 0)
    _, p_allowed, p_fallback, _ = run_wave(plain, queries)
    assert passes == [1024, 5]  # the wave, then the retry of the five
    assert (p_allowed == f_allowed).all() and (p_fallback == f_fallback).all()
    # 5 fast rows and the 51 general rows past the base schedule's 256
    assert plain.retries - retries0 == 5 + 307 - 256
    assert plain.phase_counts["check_retry"] - retry0 == 2

    del passes[:]
    _, p_allowed, p_fallback, _ = run_wave(plain, queries, retry=False)
    assert passes == [1024] and p_fallback[forced].all()
    past = np.zeros(1024, bool)
    past[np.flatnonzero(general)[256:]] = True  # the base schedule's 256
    past[forced] = True
    assert (p_fallback == f_fallback | past).all()
    assert (p_allowed[~p_fallback] == want[~p_fallback]).all()
