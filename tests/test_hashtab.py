"""The bucketed hash tables (engine/hashtab.py): a probe round gathers the
tag column alone, the key is verified once at the first tag hit, and the
answers stay exact because no bucket holds two different keys of one tag.
A lookup probes the rounds its builder asked for whatever the table holds:
a bucket of more keys is split in place, its level in the pointer.

The small tables here have one shape (128 buckets, 64 entries, 8 rounds,
a payload) and the loaded ones another (65,536 buckets and entries, 4
rounds), so the cases share TWO jitted ``lookup`` programs on XLA:CPU.
"""

import re

import jax
import numpy as np
import pytest

from ketotpu.engine import hashtab as H

SHAPE = (128, 64)  # what an unpinned build of <= 64 keys lands on, too
MASK = SHAPE[0] - 1
Q = 32  # queries a call, padded with negatives (which never match)

_LOOKUP = jax.jit(lambda t, a, b: H.lookup(t, a, b))


def _bucket(a, b, salt_i=0):
    return H._mix_np(np.asarray(a), np.asarray(b), H._SALTS[salt_i]) & MASK


def _keys(n, seed):
    """n distinct non-negative key pairs over the whole int32 range."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 2**31 - 1, n).astype(np.int32)
    b = rng.permutation(2**20)[:n].astype(np.int32)  # distinct: no pair twice
    return a, b


def _same_bucket(n, seed, bucket=5):
    """n distinct keys that all hash to one bucket under salt 0."""
    a, b = _keys(8192, seed)
    at = np.flatnonzero(_bucket(a, b) == bucket)[:n]
    assert len(at) == n
    return a[at], b[at]


def _clashing(a1, b1, tag_i=0):
    """Another key of (a1, b1)'s bucket AND tag under salt 0 / tag salt
    ``tag_i``, worked out through the tag function: ``a2 = tag1 ^ f(b2)``."""
    b2 = np.arange(1, 1 << 16, dtype=np.int32)
    b2 = b2[b2 != b1]
    salt = H._SALTS[tag_i]
    tag1 = H._tag_np(np.full(len(b2), a1, np.int32),
                     np.full(len(b2), b1, np.int32), salt)
    a2 = H._tag_np(tag1, b2, salt)
    at = np.flatnonzero((a2 >= 0) & (_bucket(a2, b2) == _bucket([a1], [b1])))
    a2, b2 = int(a2[at[0]]), int(b2[at[0]])
    assert (a2, b2) != (a1, b1)
    assert H._tag_np(np.array([a2]), np.array([b2]), salt)[0] == tag1[0]
    return a2, b2


def _answers(t, qa, qb):
    """(value, found) per query from the device program and from the host
    mirror, after checking that the two agree."""
    qa = np.asarray(qa, np.int32)
    qb = np.asarray(qb, np.int32)
    n = len(qa)
    assert n <= Q
    pa = np.full(Q, -1, np.int32)
    pb = np.full(Q, -1, np.int32)
    pa[:n], pb[:n] = qa, qb
    dv, df = (np.asarray(x) for x in _LOOKUP(t, pa, pb))
    hv, hf = H.lookup_np(t, pa, pb)
    np.testing.assert_array_equal(df, hf)
    np.testing.assert_array_equal(dv, hv)
    assert not df[n:].any() and (dv[n:] == -1).all()  # the negative pads
    return dv[:n], df[:n]


def _case_present():
    a, b = _keys(40, 1)
    return H.build_table(a, b, np.arange(40, dtype=np.int32)), a[:Q], b[:Q]


def _case_absent():
    a, b = _keys(40, 2)
    t = H.build_table(a, b, np.arange(40, dtype=np.int32))
    # absent: a resident's second half under another first half, and back
    return t, np.concatenate([a[:16] ^ 1, a[:16]]), np.concatenate(
        [b[:16], b[:16] + 2**20])


def _case_negative():
    a, b = _keys(40, 3)
    t = H.build_table(a, b, np.arange(40, dtype=np.int32))
    qa = np.concatenate([a[:8], -a[8:16] - 1, a[16:24], np.full(8, -1)])
    qb = np.concatenate([b[:8], b[8:16], -b[16:24] - 1, np.full(8, -1)])
    return t, qa, qb


def _case_deepest_bucket():
    a, b = _same_bucket(8, 4)  # one bucket as deep as the probe rounds
    oa, ob = _keys(20, 5)
    a, b = np.concatenate([a, oa]), np.concatenate([b, ob + 2**20])
    t = H.build_table(a, b, np.arange(28, dtype=np.int32))
    assert int(np.diff(t["ptr"]).max()) == t["pw"].shape[0] == 8
    return t, a, b


def _case_duplicates():
    a, b = _keys(20, 6)
    a, b = np.concatenate([a, a[:10], a[:3]]), np.concatenate([b, b[:10], b[:3]])
    return H.build_table(a, b, np.arange(33, dtype=np.int32)), a[:Q], b[:Q]


def _case_empty_fixed():
    e = np.zeros(0, np.int64)
    t = H.build_table(e, e, np.zeros(0, np.int32), fixed_shape=SHAPE)
    a, b = _keys(Q, 7)
    return t, a, b


def _case_full_fixed():
    a, b = _keys(SHAPE[1], 8)
    t = H.build_table(a, b, np.arange(len(a), dtype=np.int32),
                      fixed_shape=SHAPE)
    assert int(t["ptr"][-1]) == SHAPE[1]  # no pad left behind the entries
    return t, a[-Q:], b[-Q:]


CASES = {
    "present": _case_present,
    "absent": _case_absent,
    "negative": _case_negative,
    "deepest_bucket": _case_deepest_bucket,
    "duplicates": _case_duplicates,
    "empty_fixed": _case_empty_fixed,
    "full_fixed": _case_full_fixed,
}


def _entries(t):
    """{key: payloads} of a table, from its own columns: ``key_a`` is the
    tag XOR ``f(key_b)`` (stored nowhere); a slot whose ``key_b`` is -1 is
    empty."""
    n = H.slots_in_use(t)
    b = t["key_b"][:n]
    a = H._tag_np(t["tag"][:n], b, H._SALTS[int(t["meta"][2])])
    assert (t["key_b"][n:] == -1).all() and (t["tag"][n:] == -1).all()
    out = {}
    for ka, kb, v in zip(a.tolist(), b.tolist(), t["val"][:n].tolist()):
        if kb >= 0:
            out.setdefault((ka, kb), set()).add(v)
    return out


def _hold_the_layout(t):
    """The module docstring's invariants, bucket by bucket: a first entry
    lies in its part's window (1), parts lie in order, an empty slot lies
    behind the keys of every window that covers it (2), no two keys of a
    bucket share a tag (3), the other entries of a run of equal keys lie
    behind the last part (4); and ``meta`` counts what is there."""
    probe = t["pw"].shape[0]
    stride = max(probe // 2, 1)
    off = H._offsets(t["ptr"]).astype(np.int64)
    lev = H._levels(t["ptr"])
    meta, kb, tg = t["meta"], t["key_b"], t["tag"]
    assert (np.diff(off) >= 0).all() and lev[-1] == 0
    assert not H._tag_clash(off, tg, kb, probe)
    assert int(meta[4]) == int((lev > 0).sum())
    assert int(meta[5]) == int(lev.max())
    assert int(meta[6]) == int((kb[:off[-1]] < 0).sum())
    for bkt in np.flatnonzero((np.diff(off) > probe) | (lev[:-1] > 0)):
        lo, hi = off[bkt], off[bkt + 1]
        b = kb[lo:hi]
        a = H._tag_np(tg[lo:hi], b, H._SALTS[int(meta[2])])
        part = (H._split_np(a, b, H._SALTS[int(meta[3])])
                & np.uint32((1 << int(lev[bkt])) - 1)).tolist()
        firsts, seen, behind = [], set(), False
        for i, key in enumerate(zip(a.tolist(), b.tolist())):
            if key[1] < 0:
                assert not behind, "an empty slot among the repeated entries"
            elif key in seen:
                behind = True
            else:
                assert not behind, "a key's first entry behind the last part"
                seen.add(key)
                assert not firsts or part[i] >= part[firsts[-1]]
                assert part[i] * stride <= i < part[i] * stride + probe
                firsts.append(i)
        for i in np.flatnonzero(b < 0).tolist():
            assert all(part[j] * stride > i for j in firsts if j > i)


@pytest.mark.parametrize("case", sorted(CASES))
def test_lookup_is_lookup_np_is_a_dict(case):
    t, qa, qb = CASES[case]()
    assert {k: v.shape for k, v in t.items()} == {
        "ptr": (129,), "tag": (64,), "key_b": (64,), "meta": (7,),
        "pw": (8,), "val": (64,),
    }
    _hold_the_layout(t)
    want = _entries(t)
    val, found = _answers(t, qa, qb)
    for a, b, v, f in zip(np.asarray(qa).tolist(), np.asarray(qb).tolist(),
                          val.tolist(), found.tolist()):
        if (a, b) in want:
            assert f and v in want[(a, b)], (a, b, v, f)
        else:
            assert not f and v == -1, (a, b, v, f)
    if case in ("present", "deepest_bucket", "duplicates", "full_fixed"):
        assert found.all()
    if case in ("absent", "empty_fixed"):
        assert not found.any()


def test_absent_key_with_a_residents_tag_in_its_bucket_is_not_found():
    a, b = _keys(30, 9)
    t = H.build_table(a, b, np.arange(30, dtype=np.int32))
    assert int(t["meta"][0]) == 0 and int(t["meta"][2]) == 0
    twins = [_clashing(int(a[i]), int(b[i])) for i in range(8)]
    ta, tb = (np.array(x, np.int32) for x in zip(*twins))
    # the rounds hit (same bucket, same tag); the verify answers
    np.testing.assert_array_equal(
        H._tag_np(ta, tb, H._SALTS[0]), H._tag_np(a[:8], b[:8], H._SALTS[0]))
    val, found = _answers(t, np.concatenate([ta, a[:8]]),
                          np.concatenate([tb, b[:8]]))
    assert not found[:8].any() and (val[:8] == -1).all()
    assert found[8:].all() and (val[8:] == np.arange(8)).all()


def test_two_keys_of_one_bucket_and_tag_take_another_tag_salt():
    a, b = _keys(20, 10)
    a2, b2 = _clashing(int(a[0]), int(b[0]))
    a, b = np.append(a, np.int32(a2)), np.append(b, np.int32(b2))
    before = H.TAG_REJECTS["build"]
    t = H.build_table(a, b, np.arange(21, dtype=np.int32))
    assert int(t["meta"][0]) == 0, "the twin was made for bucket salt 0"
    assert int(t["meta"][2]) == 1 and H.TAG_REJECTS["build"] == before + 1
    assert not H._tag_clash(H._offsets(t["ptr"]), t["tag"], t["key_b"], 8)
    val, found = _answers(t, a, b)
    assert found.all() and (val == np.arange(21)).all()
    assert H.table_stats(t) == {
        "rounds": 8, "lookup_gathers": 11, "tag_salt": 1,
        "split_buckets": 0, "split_level_max": 0, "pad_slots": 0}


@pytest.mark.parametrize("fixed", [SHAPE, None], ids=["fixed_shape", "grown"])
def test_no_tag_salt_left_raises(monkeypatch, fixed):
    """A tag function that cannot tell two keys apart under any salt: the
    build refuses the table (the overlay's callers fall back to a full
    rebuild on this ``ValueError``) and never ships an inexact one."""
    monkeypatch.setattr(
        H, "_tag_np", lambda a, b, salt: np.zeros(np.shape(a), np.int32))
    a, b = _same_bucket(2, 11)
    op = "overlay" if fixed else "build"
    before = H.TAG_REJECTS[op]
    with pytest.raises(ValueError, match="tag salt"):
        H.build_table(a, b, np.arange(2, dtype=np.int32), fixed_shape=fixed)
    assert H.TAG_REJECTS[op] == before + len(H._SALTS)


def test_splice_that_would_break_the_invariant_returns_none():
    a, b = _keys(20, 12)
    t = H.build_table(a, b, np.arange(20, dtype=np.int32))
    assert int(t["meta"][0]) == 0 and int(t["meta"][2]) == 0
    a2, b2 = _clashing(int(a[3]), int(b[3]))
    none = np.zeros(0, np.int32)
    before = H.TAG_REJECTS["splice"]
    assert H.splice_table(t, none, none, np.array([a2], np.int32),
                          np.array([b2], np.int32), np.array([99], np.int32)) is None
    assert H.TAG_REJECTS["splice"] == before + 1
    # the key's own duplicate shares bucket and tag, and is allowed
    dup = H.splice_table(t, none, none, a[3:4], b[3:4], np.array([99], np.int32))
    assert dup is not None and H.TAG_REJECTS["splice"] == before + 1
    assert _entries(dup)[(int(a[3]), int(b[3]))] == {3, 99}


def _splice_cases():
    a, b = _keys(40, 13)
    v = np.arange(40, dtype=np.int32)
    none = np.zeros(0, np.int32)
    base = (a[:30], b[:30], v[:30])
    return {
        "remove": (base, (a[:5], b[:5]), (none, none, none), None),
        "add": (base, (none, none), (a[30:], b[30:], v[30:]), None),
        "both": (base, (a[10:20], b[10:20]), (a[30:], b[30:], v[30:]), None),
        "remap": (base, (a[:5], b[:5]), (a[30:33], b[30:33], v[30:33]),
                  (np.arange(40, dtype=np.int32)[::-1]).copy()),
        # duplicates leave one entry at a time
        "duplicate": ((np.append(a[:30], a[:2]), np.append(b[:30], b[:2]),
                       np.append(v[:30], v[30:32])),
                      (a[:1], b[:1]), (none, none, none), None),
    }


@pytest.mark.parametrize("case", ["remove", "add", "both", "remap", "duplicate"])
def test_spliced_table_answers_like_a_rebuilt_one(case):
    (a, b, v), (ra, rb), (aa, ab, av), remap = _splice_cases()[case]
    t = H.build_table(a, b, v)
    got = H.splice_table(t, ra, rb, aa, ab, av, val_remap=remap)
    assert got is not None
    assert {k: x.shape for k, x in got.items()} == {k: x.shape for k, x in t.items()}
    _hold_the_layout(got)
    want = _entries(t)
    for key in zip(np.asarray(ra).tolist(), np.asarray(rb).tolist()):
        if len(want[key]) == 1:
            del want[key]
        else:  # which of a key's duplicates goes is the table's choice
            want[key] = None
    if remap is not None:
        want = {k: {int(remap[x]) for x in vs} for k, vs in want.items()}
    for key, x in zip(zip(np.asarray(aa).tolist(), np.asarray(ab).tolist()),
                      np.asarray(av).tolist()):
        want.setdefault(key, set()).add(x)
    have = _entries(got)
    assert have.keys() == want.keys()
    assert all(vs is None or have[k] == vs for k, vs in want.items())
    # and through the two lookups, resident and removed keys alike
    qa, qb = np.concatenate([a, aa])[:Q], np.concatenate([b, ab])[:Q]
    val, found = _answers(got, qa, qb)
    for x, y, vv, f in zip(qa.tolist(), qb.tolist(), val.tolist(), found.tolist()):
        assert f == ((x, y) in have) and (not f or vv in have[(x, y)])


@pytest.mark.parametrize("payload", [True, False], ids=["val", "index"])
@pytest.mark.parametrize("rounds", [2, 4, 8, 9])
def test_lowered_lookup_gathers_one_column_a_round(rounds, payload):
    """The mechanism itself, read off the lowered program: ``ptr``, one tag
    a round, one verify, and the payload where the table has one."""
    a, b = _keys(30, 14)
    t = H.build_table(a, b, np.arange(30, dtype=np.int32) if payload else None,
                      probe=rounds, fixed_shape=(256, 64))
    assert t["pw"].shape == (rounds,)
    text = jax.jit(lambda t, a, b: H.lookup(t, a, b)).lower(t, a, b).as_text()
    gathers = len(re.findall(r'= "?stablehlo\.gather\b', text))
    assert gathers == H.lookup_gathers(t) == 1 + rounds + 1 + payload
    assert gathers <= rounds + 4
    assert H.table_stats(t)["lookup_gathers"] == gathers


def test_tag_is_one_function_on_the_host_and_on_the_device():
    rng = np.random.default_rng(15)
    a = rng.integers(-2**31, 2**31 - 1, 256).astype(np.int32)
    b = rng.integers(-2**31, 2**31 - 1, 256).astype(np.int32)
    for salt in H._SALTS[:3]:
        dev = jax.jit(H.tag_device)(a, b, salt)
        np.testing.assert_array_equal(np.asarray(dev), H._tag_np(a, b, salt))
    # a bijection in b: two second halves never share an f
    f = H._tag_np(np.zeros(1 << 16, np.int32), np.arange(1 << 16, dtype=np.int32),
                  H._SALTS[0])
    assert len(np.unique(f)) == 1 << 16


def test_wide_host_query_is_no_int32_key():
    """``lookup_np`` takes what the columnar decode hands it: a query wider
    than int32 matches nothing (its low half may be a resident key)."""
    a, b = _keys(10, 16)
    t = H.build_table(a, b, np.arange(10, dtype=np.int32))
    wide = a.astype(np.int64) + (1 << 32)
    assert not H.lookup_np(t, wide, b.astype(np.int64))[1].any()
    assert H.lookup_np(t, a.astype(np.int64), b.astype(np.int64))[1].all()


def test_projection_stats_show_the_served_tables():
    from ketotpu.engine.tpu import DeviceCheckEngine
    from ketotpu.utils.synth import build_synth

    g = build_synth(n_users=40, n_groups=4, n_folders=20, n_docs=60, seed=17)
    eng = DeviceCheckEngine(g.store, g.manager, frontier=512, arena=1024)
    eng.snapshot()
    ps = eng.projection_stats()
    assert set(ps["tables"]) == {"nt", "mt", "ovt", "om"}
    for name, st in ps["tables"].items():
        assert st["lookup_gathers"] == 1 + st["rounds"] + 1 + (name != "mt")
        assert st["tag_salt"] == 0
    assert ps["tables"]["ovt"]["rounds"] == H.PROBE_SHALLOW
    assert ps["tables"]["nt"]["rounds"] == H.SNAPSHOT_PROBE
    assert ps["tables"]["mt"]["rounds"] == H.SNAPSHOT_PROBE
    assert all(ps["tables"][p]["split_buckets"] == 0 for p in ("ovt", "om"))
    assert set(ps["tag_rejects"]) == {"build", "splice", "overlay", "split"}


# -- a table filled to its pad (PR 35), probed four rounds (PR 36) ------------


@pytest.fixture(scope="module")
def full_table():
    """A lean table at a load of 0.99, as the node table of a 150M-tuple
    graph runs (132.7M keys in 134.2M buckets, whose deepest bucket holds
    11): 2.07M random keys over the whole int32 range in 2^21 buckets,
    plus twelve keys dealt into one bucket; built at the served tables'
    probe of four, so what is deeper is split."""
    n, buckets = 2_076_000, 1 << 21
    rng = np.random.default_rng(35)
    a = rng.integers(0, 2**31, n, dtype=np.int64).astype(np.int32)
    b = rng.integers(0, 2**31, n, dtype=np.int64).astype(np.int32)
    a[:3], b[:3] = [2**31 - 1, 0, 2**31 - 1], [2**31 - 1, 2**31 - 1, 0]
    h = H._mix_np(a, b, H._SALTS[0]) & np.uint32(buckets - 1)
    deep = np.flatnonzero(h == h[5])
    extra_a = rng.integers(0, 2**31, 1 << 25, dtype=np.int64).astype(np.int32)
    extra_b = rng.integers(0, 2**31, 1 << 25, dtype=np.int64).astype(np.int32)
    at = np.flatnonzero(
        (H._mix_np(extra_a, extra_b, H._SALTS[0]) & np.uint32(buckets - 1))
        == h[5])[: 12 - len(deep)]
    a, b = np.concatenate([a, extra_a[at]]), np.concatenate([b, extra_b[at]])
    t = H.build_table(a, b, np.arange(len(a), dtype=np.int32), lean=True,
                      probe=H.SNAPSHOT_PROBE)
    assert len(t["ptr"]) == buckets + 1 and len(a) / buckets > 0.989
    return t, a, b


def test_a_full_table_probes_four_rounds_whatever_it_holds(full_table):
    """What PR 35 unrolled as 12 rounds: the rounds are the builder's
    constant, the capacity is what it was, and the twelve keys of one
    bucket are parted."""
    t, a, b = full_table
    assert t["pw"].shape == (H.SNAPSHOT_PROBE,)
    assert H.lookup_gathers(t) == 7
    assert len(t["tag"]) == H._bucket_pow2(len(a), 64) == 1 << 21
    assert int(t["meta"][0]) == int(t["meta"][2]) == int(t["meta"][3]) == 0
    st = H.table_stats(t)
    # uniform hashing at this load: 0.35 % of the buckets hold over four
    assert 0.002 < st["split_buckets"] / (1 << 21) < 0.005
    assert 2 <= st["split_level_max"] <= 5
    assert st["pad_slots"] < 0.003 * len(a)
    assert H.slots_in_use(t) == len(a) + st["pad_slots"]
    h = int(H._mix_np(a[5:6], b[5:6], H._SALTS[0])[0]) & ((1 << 21) - 1)
    off = H._offsets(t["ptr"])
    assert H._levels(t["ptr"])[h] >= 2 and off[h + 1] - off[h] >= 12
    _hold_the_layout(t)


def test_full_table_host_lookups_find_every_key(full_table):
    t, a, b = full_table
    val, found = H.lookup_np(t, a, b)
    assert found.all()
    # a duplicate random key may answer with its twin's payload
    same = val == np.arange(len(a))
    assert same.mean() > 0.999 and (a[val[~same]] == a[~same]).all()
    miss_v, miss = H.lookup_np(t, a[:4096] ^ 1, b[:4096])
    present = {(int(x), int(y)) for x, y in zip(a[:4096] ^ 1, b[:4096])} & {
        (int(x), int(y)) for x, y in zip(a, b)}
    assert int(miss.sum()) == len(present) and (miss_v[~miss] == -1).all()
    for i in list(range(0, len(a), 70_001)) + list(range(len(a) - 12, len(a))) \
            + [0, 1, 2]:
        assert H.lookup_one(t, int(a[i]), int(b[i])) == val[i]
    assert H.lookup_one(t, int(a[9]) ^ 1, int(b[9])) == -1
    assert H.lookup_one(t, -1, 5) == -1
    assert len(H.repeated_keys(t)) == int((~same).sum())


def test_full_table_device_lookup_is_the_hosts(full_table):
    t, a, b = full_table
    at = np.r_[0:3, len(a) - 12:len(a), 1000:1049]  # the edges, the deep bucket
    qa = np.concatenate([a[at], a[at] ^ 1, [-1]]).astype(np.int32)
    qb = np.concatenate([b[at], b[at], [7]]).astype(np.int32)
    fn = jax.jit(lambda t, x, y: H.lookup(t, x, y))
    val, found = fn(t, qa, qb)
    want_v, want_f = H.lookup_np(t, qa, qb)
    np.testing.assert_array_equal(np.asarray(found), want_f)
    np.testing.assert_array_equal(np.asarray(val), want_v)
    assert want_f[: len(at)].all() and not want_f[-1]
    text = fn.lower(t, qa, qb).as_text()
    gathers = len(re.findall(r'= "?stablehlo\.gather\b', text))
    assert gathers == H.lookup_gathers(t) == 7


def test_wave_gathers_reads_the_served_tables_shapes():
    a, b = _keys(30, 21)
    arrays = {}
    for prefix, val, rounds in (("nt", True, 9), ("mt", False, 8),
                                ("ovt", True, 4)):
        t = H.build_table(a, b, np.arange(30, dtype=np.int32) if val else None,
                          probe=rounds, fixed_shape=(256, 64))
        arrays.update({f"{prefix}_{k}": v for k, v in t.items()})
    assert H.wave_gathers(arrays) == {"nt": 12, "mt": 10, "ovt": 7}
    assert H.wave_gathers(arrays) == {
        p: H.lookup_gathers(H.subtables(arrays, p + "_"))
        for p in ("nt", "mt", "ovt")}


def test_grouped_order_groups_every_bucket_on_a_big_table():
    """Above 2^21 entries the build deals the entries into 256 ranges and
    sorts each on the pool: every bucket's entries stay contiguous."""
    rng = np.random.default_rng(36)
    n, buckets = (1 << 21) + 12345, 1 << 20
    h = rng.integers(0, buckets, n).astype(np.uint32)
    order = H._grouped_order(h, buckets)
    hs = h[order]
    assert (np.diff(hs.astype(np.int64)) >= 0).all()
    assert np.array_equal(np.sort(order), np.arange(n))


# -- the probe depth is a constant of the code (PR 36) ------------------------

LOADED = 1 << 16  # buckets and capacity of every loaded table below
_LOOKUP4 = jax.jit(lambda t, a, b: H.lookup(t, a, b))


def _loaded(load, seed, payload=True):
    """A lean table of ``load * 65,536`` keys at the served tables' probe,
    every twentieth key stored twice (the membership table admits that)."""
    n = int(load * LOADED) - 200
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 2**31, n, dtype=np.int64).astype(np.int32)
    b = rng.integers(0, 2**31, n, dtype=np.int64).astype(np.int32)
    a, b = np.concatenate([a, a[::20][:200]]), np.concatenate([b, b[::20][:200]])
    val = np.arange(len(a), dtype=np.int32) if payload else None
    return H.build_table(a, b, val, lean=True, probe=H.SNAPSHOT_PROBE), a, b


@pytest.mark.parametrize("load", [0.56, 0.63, 0.989])
def test_loaded_table_probes_four_rounds_and_answers_like_a_dict(load):
    t, a, b = _loaded(load, int(load * 1000))
    assert len(a) < LOADED and len(a) / LOADED > load - 0.001
    assert {k: v.shape for k, v in t.items()} == {
        "ptr": (LOADED + 1,), "tag": (LOADED,), "key_b": (LOADED,),
        "meta": (7,), "pw": (4,), "val": (LOADED,)}
    assert len(t["tag"]) == H._bucket_pow2(len(a), 64)
    st = H.table_stats(t)
    assert st["rounds"] == 4 and st["lookup_gathers"] == 7
    assert st["split_buckets"] > 0 and 1 <= st["split_level_max"] <= 5
    assert H.slots_in_use(t) == len(a) + st["pad_slots"] <= LOADED
    _hold_the_layout(t)
    want = _entries(t)
    assert sum(len(v) for v in want.values()) == len(a)
    # present (the repeated keys among them), absent, negative
    qa = np.concatenate([a[:40], a[-200:], a[:200] ^ 1, -a[:20] - 1, a[:20]])
    qb = np.concatenate([b[:40], b[-200:], b[:200], b[:20], -b[:20] - 1])
    deep = np.flatnonzero(H._levels(t["ptr"])[:-1] > 0)
    off = H._offsets(t["ptr"])
    at = np.concatenate([np.arange(off[d], off[d + 1]) for d in deep[:40]])
    at = at[t["key_b"][at] >= 0]  # every key of forty split buckets
    qa = np.concatenate([qa, H._tag_np(t["tag"][at], t["key_b"][at], H._SALTS[0])])
    qb = np.concatenate([qb, t["key_b"][at]]).astype(np.int32)
    qa = qa.astype(np.int32)
    dv, df = (np.asarray(x) for x in _LOOKUP4(t, qa, qb))
    hv, hf = H.lookup_np(t, qa, qb)
    np.testing.assert_array_equal(df, hf)
    np.testing.assert_array_equal(dv, hv)
    for x, y, v, f in zip(qa.tolist(), qb.tolist(), hv.tolist(), hf.tolist()):
        one = H.lookup_one(t, x, y)
        if (x, y) in want:
            assert f and v in want[(x, y)] and one in want[(x, y)]
        else:
            assert not f and v == -1 and one == -1
    assert hf[:240].all() and hf[480:].all() and not hf[440:480].any()
    # a key stored twice is found once, and its other entry is set aside
    rep = H.repeated_keys(t)
    assert len(rep) >= 200 and (t["key_b"][rep] >= 0).all()


def test_graphs_of_different_seeds_share_every_column_shape():
    """The parent unrolled the deepest bucket, so 5 of graph seeds 0-13
    were another program: the shapes are the counts' alone now."""
    shapes = set()
    for seed in range(6):
        for load, payload in ((0.56, True), (0.63, False)):
            t, _, _ = _loaded(load, seed, payload)
            shapes.add((payload, tuple(sorted(
                (k, v.shape, str(v.dtype)) for k, v in t.items()))))
    assert len(shapes) == 2


def _one_bucket(n, seed, want_part=None):
    """``n`` distinct keys of bucket 5 of a 128-bucket table under salt 0
    (all with ``want_part`` in the split hash's low seven bits, if given),
    and forty keys of other buckets."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 2**31, 1 << 20, dtype=np.int64).astype(np.int32)
    b = rng.permutation(1 << 20).astype(np.int32)
    keep = _bucket(a, b) == 5
    if want_part is not None:
        keep &= (H._split_np(a, b, H._SALTS[0]) & np.uint32(127)) == want_part
    at = np.flatnonzero(keep)[:n + 1]
    assert len(at) == n + 1
    oa, ob = _keys(40, seed + 1)
    ob = ob + 2**20
    away = _bucket(oa, ob) != 5
    return (np.concatenate([a[at[:n]], oa[away]]),
            np.concatenate([b[at[:n]], ob[away]]), (int(a[at[n]]), int(b[at[n]])))


def test_nine_keys_of_one_bucket_are_split_and_all_found():
    a, b, (xa, xb) = _one_bucket(9, 40)
    t = H.build_table(a, b, np.arange(len(a), dtype=np.int32),
                      probe=H.SNAPSHOT_PROBE)
    assert t["pw"].shape == (4,) and t["tag"].shape == (64,)
    lev, off = H._levels(t["ptr"]), H._offsets(t["ptr"])
    assert lev[5] >= 2 and off[6] - off[5] >= 9  # 9 keys: four parts at least
    assert H.table_stats(t)["split_buckets"] >= 1
    _hold_the_layout(t)
    qa, qb = np.append(a[:9], xa).astype(np.int32), np.append(b[:9], xb).astype(np.int32)
    pa, pb = np.full(Q, -1, np.int32), np.full(Q, -1, np.int32)
    pa[:10], pb[:10] = qa, qb
    fn = jax.jit(lambda t, x, y: H.lookup(t, x, y))
    dv, df = (np.asarray(x) for x in fn(t, pa, pb))
    hv, hf = H.lookup_np(t, pa, pb)
    np.testing.assert_array_equal(df, hf)
    np.testing.assert_array_equal(dv, hv)
    assert hf[:9].all() and (hv[:9] == np.arange(9)).all()
    assert not hf[9:].any()  # the tenth key of the bucket was never stored
    assert [H.lookup_one(t, int(x), int(y)) for x, y in zip(qa, qb)] == [
        *range(9), -1]
    # the mechanism, read off the lowered program: the pointer, FOUR tags,
    # the verify, the payload; one less without a payload
    text = fn.lower(t, pa, pb).as_text()
    assert len(re.findall(r'= "?stablehlo\.gather\b', text)) == 7
    t2 = H.build_table(a, b, probe=H.SNAPSHOT_PROBE)
    text = fn.lower(t2, pa, pb).as_text()
    assert len(re.findall(r'= "?stablehlo\.gather\b', text)) == 6
    assert H.lookup_np(t2, qa, qb)[1].tolist() == [True] * 9 + [False]


def test_a_bucket_no_three_bits_separate_walks_the_split_salt():
    a, b, _ = _one_bucket(5, 41, want_part=77)
    before = H.TAG_REJECTS["split"]
    t = H.build_table(a, b, np.arange(len(a), dtype=np.int32),
                      probe=H.SNAPSHOT_PROBE)
    assert int(t["meta"][3]) == 1 and H.TAG_REJECTS["split"] == before + 1
    assert int(t["meta"][0]) == 0 and int(t["meta"][2]) == 0
    assert H._levels(t["ptr"])[5] >= 1
    _hold_the_layout(t)
    val, found = H.lookup_np(t, a, b)
    assert found.all() and (val == np.arange(len(a))).all()
    # a fixed-shape table is never split: it keeps its contract
    with pytest.raises(ValueError, match="no salt fits"):
        H.build_table(a[:5], b[:5], np.arange(5, dtype=np.int32),
                      probe=H.SNAPSHOT_PROBE, fixed_shape=(1, 64))


def test_no_split_salt_left_raises(monkeypatch):
    monkeypatch.setattr(
        H, "_split_np", lambda a, b, salt: np.zeros(np.shape(a), np.uint32))
    a, b, _ = _one_bucket(5, 42)
    before = H.TAG_REJECTS["split"]
    with pytest.raises(ValueError, match="split salt"):
        H.build_table(a, b, probe=H.SNAPSHOT_PROBE)
    assert H.TAG_REJECTS["split"] == before + len(H._SALTS)


@pytest.mark.parametrize("into", ["full_bucket", "split_bucket", "emptied"])
def test_splice_into_a_full_or_a_split_bucket_lays_it_anew(into):
    """What made the parent's splice return ``None`` (a bucket growing past
    the recorded rounds) and the fold build in full: the splice lays the
    buckets it touches with the build's own routine."""
    n = {"full_bucket": 4, "split_bucket": 7, "emptied": 6}[into]
    a, b, (xa, xb) = _one_bucket(n, 43)
    v = np.arange(len(a), dtype=np.int32)
    t = H.build_table(a, b, v, probe=H.SNAPSHOT_PROBE)
    assert (H._levels(t["ptr"])[5] > 0) == (n > 4)
    none = np.zeros(0, np.int32)
    if into == "emptied":  # a split bucket whose keys leave joins its parts
        got = H.splice_table(t, a[:4], b[:4], none, none, none)
        a2, b2, v2 = a[4:], b[4:], v[4:]
        assert H._levels(got["ptr"])[5] == 0
    else:
        got = H.splice_table(t, none, none, np.array([xa], np.int32),
                             np.array([xb], np.int32), np.array([99], np.int32))
        a2, b2, v2 = np.append(a, xa), np.append(b, xb), np.append(v, 99)
        assert H._levels(got["ptr"])[5] > 0
    assert got is not None
    assert {k: x.shape for k, x in got.items()} == {k: x.shape for k, x in t.items()}
    _hold_the_layout(got)
    built = H.build_table(a2, b2, v2.astype(np.int32), probe=H.SNAPSHOT_PROBE)
    assert _entries(got) == _entries(built)
    assert H.table_stats(got) == H.table_stats(built)
    qa = np.concatenate([a, [xa]]).astype(np.int32)
    qb = np.concatenate([b, [xb]]).astype(np.int32)
    for x in (H.lookup_np(got, qa, qb), H.lookup_np(built, qa, qb)):
        np.testing.assert_array_equal(x[1], [(p, q) in _entries(built)
                                             for p, q in zip(qa.tolist(), qb.tolist())])
    np.testing.assert_array_equal(H.lookup_np(got, qa, qb)[0],
                                  H.lookup_np(built, qa, qb)[0])


def test_splice_of_a_loaded_table_answers_like_a_rebuilt_one():
    """A fold's worth of edits on a table at a load of 0.63: 200 inserts
    and 100 removals, some into split buckets; never ``None``."""
    t, a, b = _loaded(0.63, 44)
    rng = np.random.default_rng(45)
    deep = np.flatnonzero(H._levels(t["ptr"])[:-1] > 0)
    ia = rng.integers(0, 2**31, 1 << 18, dtype=np.int64).astype(np.int32)
    ib = rng.integers(0, 2**31, 1 << 18, dtype=np.int64).astype(np.int32)
    hit = np.isin(H._mix_np(ia, ib, H._SALTS[0]) & np.uint32(LOADED - 1), deep)
    at = np.r_[np.flatnonzero(hit)[:60], np.flatnonzero(~hit)[:140]]
    assert hit[at].sum() == 60
    rm = rng.permutation(len(a) - 200)[:100]
    got = H.splice_table(t, a[rm], b[rm], ia[at], ib[at],
                         (100_000 + np.arange(200)).astype(np.int32))
    assert got is not None
    assert {k: x.shape for k, x in got.items()} == {k: x.shape for k, x in t.items()}
    _hold_the_layout(got)
    want = _entries(t)
    for i in rm.tolist():
        key = (int(a[i]), int(b[i]))
        want[key] = None if len(want[key]) > 1 else want.pop(key) and None
    want = {k: v for k, v in want.items() if v is not None or k in _entries(got)}
    for k, x in zip(zip(ia[at].tolist(), ib[at].tolist()), range(100_000, 100_200)):
        want.setdefault(k, set())
        if want[k] is not None:
            want[k].add(x)
    have = _entries(got)
    assert have.keys() == want.keys()
    assert all(v is None or have[k] == v for k, v in want.items())
    qa, qb = np.concatenate([a, ia[at]]), np.concatenate([b, ib[at]])
    dv, df = (np.asarray(x) for x in _LOOKUP4(got, qa[-LOADED // 64:], qb[-LOADED // 64:]))
    hv, hf = H.lookup_np(got, qa, qb)
    np.testing.assert_array_equal(df, hf[-LOADED // 64:])
    np.testing.assert_array_equal(dv, hv[-LOADED // 64:])
    for x, y, vv, f in zip(qa.tolist(), qb.tolist(), hv.tolist(), hf.tolist()):
        assert f == ((x, y) in have) and (not f or vv in have[(x, y)])
