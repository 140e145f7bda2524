"""The decisions every launcher of a check wave shares (engine/wave.py):
numpy only, no engine, no device program."""

import numpy as np
import pytest

from ketotpu.engine import wave as wv
from ketotpu.engine.optable import R_ERR, R_IS, R_NOT, R_UNKNOWN

B = lambda *bits: np.array(bits, bool)  # noqa: E731


# -- decoding -----------------------------------------------------------------


@pytest.mark.parametrize("word, code, over, dirty", [
    (0, R_UNKNOWN, False, False),
    (1, R_IS, False, False),
    (2, R_NOT, False, False),
    (3, R_ERR, False, False),
    (4, R_UNKNOWN, True, False),
    (8, R_UNKNOWN, False, True),
    (1 | 4 | 8, R_IS, True, True),
    (0x3F0 | 2, R_NOT, False, False),  # a fused word's upper bits are not its
])
def test_general_word(word, code, over, dirty):
    g = wv.decode_general(np.array([word, 0], np.int32))
    assert (g.code[0], g.over[0], g.dirty[0]) == (code, over, dirty)
    assert (g.code[1], g.over[1], g.dirty[1]) == (R_UNKNOWN, False, False)
    assert g.code.dtype == np.int8 and g.over.dtype == g.dirty.dtype == bool


@pytest.mark.parametrize("word, found, over, dirty", [
    (0, False, False, False),
    (1, True, False, False),
    (2, False, True, False),
    (4, False, False, True),
    (7, True, True, True),
])
def test_fast_word(word, found, over, dirty):
    f = wv.decode_fast(np.array([word], np.uint8))  # the program's dtype
    assert (f.found[0], f.over[0], f.dirty[0]) == (found, over, dirty)


FUSED_FIELDS = ["found", "fast_fb", "leo_ans", "leo_allow", "retried",
                "gen_retried"]


@pytest.mark.parametrize("bit", range(10))
def test_fused_word_every_bit_alone(bit):
    """engine/fused.py's table: bits 0-1 the code, 2 over, 3 dirty, then
    found, fast fallback, leopard answered / allowed, the two retried."""
    b = wv.decode_fused(np.array([1 << bit], np.int32))
    got = {
        "code": int(b.general.code[0]), "over": bool(b.general.over[0]),
        "dirty": bool(b.general.dirty[0]),
        **{name: bool(getattr(b, name)[0]) for name in FUSED_FIELDS},
    }
    want = dict.fromkeys(["over", "dirty", *FUSED_FIELDS], False)
    want["code"] = {0: 1, 1: 2}.get(bit, 0)
    if bit >= 2:
        want[["over", "dirty", *FUSED_FIELDS][bit - 2]] = True
    assert got == want


def test_fused_word_combined():
    b = wv.decode_fused(np.array([R_ERR | 4 | 0x10 | 0x80 | 0x200, 0x3FF]))
    assert b.general.code.tolist() == [R_ERR, R_ERR]
    assert b.general.over.tolist() == [True, True]
    assert b.general.dirty.tolist() == [False, True]
    assert b.found.tolist() == [True, True]
    assert b.fast_fb.tolist() == b.leo_ans.tolist() == [False, True]
    assert b.leo_allow.tolist() == b.gen_retried.tolist() == [True, True]
    assert b.retried.tolist() == [False, True]


# -- retry --------------------------------------------------------------------


def test_fast_retry_rule():
    """found beats over and dirty; dirty is never retried."""
    #            clean found  over  over+found dirty dirty+over dirty+found
    f = wv.FastBits(
        found=B(0, 1, 0, 1, 0, 0, 1),
        over=B(0, 0, 1, 1, 0, 1, 0),
        dirty=B(0, 0, 0, 0, 1, 1, 1),
    )
    assert wv.fast_retry_rows(f).tolist() == [0, 0, 1, 0, 0, 0, 0]
    assert wv.fast_fallback(f).tolist() == [0, 0, 1, 0, 1, 1, 0]


def test_general_retry_rule():
    """Overflowed rows are retried unless dirty or in error."""
    g = wv.GeneralBits(
        code=np.array([R_IS, R_UNKNOWN, R_UNKNOWN, R_ERR, R_NOT, R_ERR],
                      np.int8),
        over=B(0, 1, 1, 1, 1, 0),
        dirty=B(0, 0, 1, 0, 0, 0),
    )
    assert wv.general_retry_rows(g).tolist() == [0, 1, 0, 0, 1, 0]
    assert wv.general_fallback(g).tolist() == [0, 1, 1, 1, 1, 1]


def test_a_retry_replaces_the_rows_bits():
    """Row 1 is found by the retry, row 2 overflows again, row 3 turns
    dirty: the first falls back no more, the others still do."""
    f = wv.FastBits(found=B(1, 0, 0, 0), over=B(0, 1, 1, 1),
                    dirty=B(0, 0, 0, 0))
    rows = np.flatnonzero(wv.fast_retry_rows(f))
    assert rows.tolist() == [1, 2, 3]
    wv.take_retry(f, rows, wv.FastBits(B(1, 0, 0), B(0, 1, 0), B(0, 0, 1)))
    assert f.found.tolist() == [1, 1, 0, 0]
    assert wv.fast_fallback(f).tolist() == [0, 0, 1, 1]

    g = wv.decode_general(np.array([R_IS, 4, 4, 4], np.int32))
    again = wv.general_retry_rows(g)  # a mask, as the engine passes it
    wv.take_retry(g, again, wv.decode_general(
        np.array([R_NOT, R_ERR, R_IS | 8], np.int32)))
    assert g.code.tolist() == [R_IS, R_NOT, R_ERR, R_IS]
    assert wv.general_fallback(g).tolist() == [0, 0, 1, 1]


# -- who answers a row --------------------------------------------------------


def test_merge_precedence():
    #             err  general general fast  fast  leo   cached cached+leo
    err = B(1, 0, 0, 0, 0, 0, 0, 0)
    general = B(0, 1, 1, 0, 0, 0, 0, 0)
    ones, zeros = np.ones(8, bool), np.zeros(8, bool)
    leo = (B(0, 0, 0, 0, 0, 1, 0, 1), B(0, 0, 0, 0, 0, 1, 0, 1))
    cache = (B(0, 0, 0, 0, 0, 0, 1, 1), B(0, 0, 0, 0, 0, 0, 1, 0))
    allowed, fallback = wv.merge(
        err, general, g_is=B(1, 1, 0, 1, 1, 1, 1, 1),
        g_fb=B(0, 0, 1, 1, 1, 1, 1, 1), found=B(1, 0, 0, 1, 0, 0, 0, 0),
        fast_fb=B(0, 1, 1, 0, 1, 0, 0, 0), leo_res=leo, cache_res=cache,
    )
    # an err row falls back whatever the device said; a general row reads
    # the general tier's bits and a fast row the fast tier's; the cached
    # row takes the cache's verdict; on the last row the cache hit (a
    # denial) does not claim what Leopard answered (allowed)
    assert allowed.tolist() == [0, 1, 0, 1, 0, 1, 1, 1]
    assert fallback.tolist() == [1, 0, 1, 0, 1, 0, 0, 0]
    tiers = wv.attribute(err, fallback, leo, cache)
    assert tiers.cache.tolist() == [0, 0, 0, 0, 0, 0, 1, 0]
    assert tiers.leopard.tolist() == [0, 0, 0, 0, 0, 1, 0, 1]
    assert tiers.oracle.tolist() == [1, 0, 1, 0, 1, 0, 0, 0]
    assert tiers.device.tolist() == [0, 1, 0, 1, 0, 0, 0, 0]
    assert (sum(tiers) == 1).all()  # every row in exactly one tier

    # with neither index nor cache, the device's bits and err alone
    allowed, fallback = wv.merge(err, general, ones, zeros, ones, zeros)
    assert allowed.tolist() == [0, 1, 1, 1, 1, 1, 1, 1]
    assert fallback.tolist() == err.tolist()
    assert wv.attribute(err, fallback).device.tolist() == (~err).tolist()


# -- padding ------------------------------------------------------------------


@pytest.mark.parametrize("n, rows, lanes, lanes_in_wave, retry", [
    (1, 256, 256, 256, 256),
    (255, 256, 256, 256, 256),
    (256, 256, 256, 256, 256),
    (257, 512, 384, 384, 512),
    (333, 512, 384, 384, 512),
    (1024, 1024, 1024, 1024, 1024),
])
def test_padding_rules(n, rows, lanes, lanes_in_wave, retry):
    assert wv.wave_rows(n, 8192) == rows
    assert wv.general_lanes(n, 4096) == lanes
    # fused: the general rows of a wave, capped by the wave's own rows
    assert wv.general_lanes(n, wv.wave_rows(n, 8192)) == lanes_in_wave
    assert wv.retry_rows(n, 4 * 8192) == retry


def test_padding_edges():
    assert wv.general_lanes(0, 1024) == 0  # no rows, no tier
    # a cap below the bucket wins, in all three
    assert wv.wave_rows(700, 768) == 768
    assert wv.general_lanes(700, 512) == 512
    assert wv.retry_rows(300, 384) == 384
    # half octaves above the floor only
    assert [wv.general_lanes(n, 1 << 20) for n in (385, 513, 769, 1025)] == [
        512, 768, 1024, 1536]


# -- cutting a batch into waves -----------------------------------------------


def _schedule(depth=5):
    from ketotpu.engine import fastpath as fp

    return lambda q, f, a: fp.level_schedule(q, f, a, depth)


@pytest.mark.parametrize("frontier, arena, cap", [
    (8192, 16384, 1024),   # the daemon's defaults: 6 x 1024 <= 8192
    (8192, 8192, 512),     # the arena binds: level 3 wants 2 x 6 x 1024
    (4096, 8192, 512),     # the engine's defaults
    (2048, 4096, 256),
    (256, 512, 256),       # never under the narrowest wave
])
def test_wave_cap_is_the_widest_wave_no_level_clips(frontier, arena, cap):
    schedule = _schedule()
    assert wv.wave_cap(schedule, frontier, arena) == cap
    free = 1 << 62
    if cap > 256:
        assert schedule(cap, frontier, arena) == schedule(cap, free, free)
    assert schedule(2 * cap, frontier, arena) != schedule(2 * cap, free, free)


CAP = 1024


@pytest.mark.parametrize("share", [0.0, 0.33, 1.0])
@pytest.mark.parametrize("n", [1, 700, CAP, CAP + 1, 2 * CAP + 7, 10_000])
def test_cut(n, share):
    """Every row in one wave and back in its place, no wave over the cap,
    one program a ticket, and up to the cap nothing is touched."""
    rng = np.random.default_rng([n, int(share * 100)])
    general = rng.random(n) < share
    cut = wv.cut(n, general, CAP)
    waves = cut.rows
    # (a) a partition, each wave ascending: scattering restores the order
    assert sorted(np.concatenate(waves).tolist()) == list(range(n))
    assert all((np.diff(w) > 0).all() for w in waves)
    back = np.full(n, -1)
    for w in waves:
        back[w] = w
    assert back.tolist() == list(range(n))
    # (b) the fewest waves that hold the batch, none over the cap
    assert len(waves) == -(-n // CAP)
    assert max(len(w) for w in waves) <= CAP
    if n <= CAP:
        # (d) one wave, in order, padded by its own size: today's wave
        assert [w.tolist() for w in waves] == [list(range(n))]
        assert cut.like == (0, 0)
        return
    # (c) equal waves, one row and one general row apart ...
    sizes = [len(w) for w in waves]
    gens = [int(general[w].sum()) for w in waves]
    assert max(sizes) - min(sizes) <= 1 and max(gens) - min(gens) <= 1
    assert cut.like == (max(sizes), max(gens))
    # ... so, padded like the widest, one program: one wave_rows, and one
    # general_lanes bucket among the waves that hold a general row
    rows = {wv.wave_rows(max(k, cut.like[0]), 8192) for k in sizes}
    lanes = {wv.general_lanes(g, wv.wave_rows(cut.like[0], 8192), cut.like[1])
             for g in gens if g}
    assert len(rows) == 1 and len(lanes) <= 1
    assert (not lanes) == (share == 0.0)


def test_cut_pads_a_short_wave_like_the_ticket():
    """cap + 1 rows are waves of 513 and 512: by their own size two
    programs (1024 and 512 rows), padded like the widest one."""
    cut = wv.cut(CAP + 1, np.zeros(CAP + 1, bool), CAP)
    assert [len(w) for w in cut.rows] == [513, 512]
    assert [wv.wave_rows(len(w), 8192) for w in cut.rows] == [1024, 512]
    assert cut.like == (513, 0)
    # 2561 general rows in ten waves: 257 in one, 256 in nine; by their
    # own count two buckets (384 and 256 lanes), like the widest one
    general = np.zeros(10_000, bool)
    general[:2561] = True
    cut = wv.cut(10_000, general, CAP)
    gens = [int(general[w].sum()) for w in cut.rows]
    assert sorted(set(gens)) == [256, 257] and cut.like == (1000, 257)
    assert {wv.general_lanes(g, 1024) for g in gens} == {256, 384}
    assert {wv.general_lanes(g, 1024, cut.like[1]) for g in gens} == {384}


def test_cut_of_nothing_is_no_wave():
    assert wv.cut(0, None, CAP) == wv.Cut([], (0, 0))


def test_fused_overflowed_reads_lanes_and_flags():
    """A row that entered a retry lane overflowed whatever the lane
    found; without lanes the rows still flagged did."""
    word = lambda *bits: sum(1 << b for b in bits)  # noqa: E731
    bits = wv.decode_fused(np.array([
        word(4), word(4, 8), word(5), word(0), word(0, 9), word(2), 0,
    ], np.int32))
    fast, general = wv.fused_overflowed(bits)
    assert fast.tolist() == [0, 1, 1, 0, 0, 0, 0]
    assert general.tolist() == [0, 0, 0, 0, 1, 1, 0]


# -- structure ----------------------------------------------------------------


def test_the_mesh_keeps_only_what_a_mesh_adds():
    """One host prefix and one cascade: the mesh engine overrides hooks,
    not the dispatcher or the collector."""
    from ketotpu.engine.tpu import DeviceCheckEngine
    from ketotpu.parallel.meshengine import MeshCheckEngine

    for name in ("_dispatch", "_collect", "_finish_chunk", "_collect_fused",
                 "_cache_consult", "_cache_fill", "_note_tiers"):
        assert name not in vars(MeshCheckEngine), name
        assert name in vars(DeviceCheckEngine), name
    for hook in ("_sync_view", "_route", "_launch", "_run_fast",
                 "_general_program", "_general_occ", "_fast_bits",
                 "_fetch_span", "_fast_retry_cap", "_after_collect"):
        assert hook in vars(MeshCheckEngine), hook
        assert hook in vars(DeviceCheckEngine), hook
