"""The bucketed hash tables (engine/hashtab.py): a probe round gathers the
tag column alone, the key is verified once at the first tag hit, and the
answers stay exact because no bucket holds two different keys of one tag.

Every table here has one shape (128 buckets, 64 entries, 8 rounds, a
payload), so the cases share ONE jitted ``lookup`` on XLA:CPU.
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
    tag XOR ``f(key_b)`` (stored nowhere)."""
    n = int(t["ptr"][-1])
    b = t["key_b"][:n]
    a = H._tag_np(t["tag"][:n], b, H._SALTS[int(t["meta"][2])])
    out = {}
    for ka, kb, v in zip(a.tolist(), b.tolist(), t["val"][:n].tolist()):
        out.setdefault((ka, kb), set()).add(v)
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_lookup_is_lookup_np_is_a_dict(case):
    t, qa, qb = CASES[case]()
    assert {k: v.shape for k, v in t.items()} == {
        "ptr": (129,), "tag": (64,), "key_b": (64,), "meta": (3,),
        "pw": (8,), "val": (64,),
    }
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
    assert not H._tag_clash(t["ptr"], t["tag"], t["key_b"], 21, 8)
    val, found = _answers(t, a, b)
    assert found.all() and (val == np.arange(21)).all()
    assert H.table_stats(t) == {"rounds": 8, "lookup_gathers": 11, "tag_salt": 1}


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
    assert not H._tag_clash(got["ptr"], got["tag"], got["key_b"],
                            int(got["ptr"][-1]), 8)
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
    assert set(ps["tag_rejects"]) == {"build", "splice", "overlay"}


# -- a table filled to its pad (PR 35) ----------------------------------------


@pytest.fixture(scope="module")
def full_table():
    """A lean table at a load of 0.99, as the node table of a 150M-tuple
    graph runs (132.7M keys in 134.2M buckets, rounds 11): 2.07M random
    keys over the whole int32 range in 2^21 buckets under the one salt a
    big table takes, plus twelve keys dealt into one bucket, so the
    deepest bucket (the ``pw`` shape) is 12 at a size a test can build."""
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
                      probe=2 * H.SNAPSHOT_PROBE)
    assert len(t["ptr"]) == buckets + 1 and len(a) / buckets > 0.989
    return t, a, b


def test_a_full_table_unrolls_its_deepest_bucket(full_table):
    t, a, b = full_table
    assert t["pw"].shape[0] >= 11
    assert t["pw"].shape[0] == int(np.diff(t["ptr"]).max())
    assert H.lookup_gathers(t) == t["pw"].shape[0] + 3
    assert int(t["meta"][2]) == 0 and H.TAG_REJECTS["build"] >= 0


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
    for i in list(range(0, len(a), 70_001)) + [0, 1, 2, len(a) - 1]:
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
    assert gathers == H.lookup_gathers(t) >= 14


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
