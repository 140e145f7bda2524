"""The vocabulary's bulk form (engine/vocab.py ``Interner.pack``): behind
``lookup`` / ``lookup_many`` / ``intern`` / ``string`` it answers exactly
like the dict it was frozen from."""

import numpy as np
import pytest

from ketotpu.engine import vocab as V


def _strings(n):
    out = [f"d{i}" for i in range(n)]
    out[7], out[100] = "", "ünï-" + "x" * 40  # empty, non-ASCII, long
    out[n // 2] = "id:ué"
    return out


@pytest.fixture(params=["one_pass", "several_passes"])
def packed(request, monkeypatch):
    if request.param == "several_passes":
        monkeypatch.setattr(V, "_PACK_CHUNK", 700)
    n = V._TABLE_MIN * 3
    ref = {s: i for i, s in enumerate(_strings(n))}
    it = V.Interner()
    it._ids = dict(ref)  # as a bulk loader hands it over
    it.pack()
    assert it._base is not None and not it._ids
    return it, ref


QUERIES = ["d0", "d5", "", "ünï-" + "x" * 40, "id:ué", "nope", "d",
           "d99999999", "ünï-", "D5"]


def test_packed_lookup_is_the_dicts(packed):
    it, ref = packed
    assert len(it) == len(ref)
    for s in QUERIES + list(ref)[::97]:
        assert it.lookup(s) == ref.get(s, -1), s
    many = QUERIES * 3 + list(ref)[::11]
    got = it.lookup_many(many)
    assert got.dtype == np.int32
    assert got.tolist() == [ref.get(s, -1) for s in many]
    assert it.lookup_many([]).tolist() == []


def test_packed_strings_come_back_in_id_order(packed):
    it, ref = packed
    assert it.strings() == list(ref)
    for i in (0, 7, 100, len(ref) - 1):
        assert it.string(i) == list(ref)[i]
    assert it.string(len(ref)) is None and it.string(-1) is None


def test_interning_after_a_pack_continues_the_ids(packed):
    it, ref = packed
    n = len(ref)
    assert it.intern("d5") == 5 and it.intern("") == 7  # known: no new id
    assert it.intern("late-a") == n and it.intern("late-b") == n + 1
    assert it.intern("late-a") == n and len(it) == n + 2
    assert it.lookup("late-b") == n + 1 and it.string(n + 1) == "late-b"
    assert it.lookup_many(["late-b", "d5", "zz", "late-a"]).tolist() == [
        n + 1, 5, -1, n]
    assert it.strings()[-2:] == ["late-a", "late-b"]
    # a second pack folds the newer entries in, ids unmoved
    it.pack()
    assert not it._ids and it._base.n == n + 2
    assert it.lookup("late-a") == n and it.lookup("d5") == 5
    assert it.lookup_many(["late-b", "", "zz"]).tolist() == [n + 1, 7, -1]


def test_two_strings_of_one_hash_are_both_found(monkeypatch):
    """The table finds the first entry of a key alone; the second string
    of a masked hash is kept aside at the pack."""
    real = V._halves

    def clashing(blob, off):
        # every "c<i>" hashes like "d<i>": pairs of one 62-bit hash
        twin = np.array(blob)
        twin[off[:-1][twin[off[:-1]] == ord("c")]] = ord("d")
        return real(twin, off)

    monkeypatch.setattr(V, "_halves", clashing)
    n = V._TABLE_MIN
    ref = {s: i for i, s in enumerate(
        [f"d{i}" for i in range(n)] + [f"c{i}" for i in range(0, n, 50)])}
    it = V.Interner()
    it._ids = dict(ref)
    it.pack()
    assert len(it._base.extra) == len(range(0, n, 50))
    many = ["c0", "d0", "c50", "d50", "c1", "d1023", "c1000"]
    assert it.lookup_many(many).tolist() == [ref.get(s, -1) for s in many]
    # the scalar probe hashes the string as it is: its hit is verified
    assert it.lookup("d50") == ref["d50"]


@pytest.mark.parametrize("n", [5, V._TABLE_MIN + 7])
def test_one_hash_for_a_string_alone_in_a_column_and_in_a_blob(n):
    names = [f"d{i}" for i in range(n)]
    names[3], names[4] = "ünï-" + "x" * 40, "exactly8"
    blob, lens = V._utf8(names, n)
    a, b = V._halves(blob, V._offsets(lens))  # one by one under _FEW
    for i in (0, 3, 4, n - 1):
        ha = V._hash_one(names[i].encode())
        assert (ha & V._HALF_MASK, ha >> 31) == (a[i], b[i])
    assert len(set(zip(a.tolist(), b.tolist()))) == n
    with pytest.raises(ValueError, match="a blob of"):
        V.Interner.from_utf8(blob[:-1], lens)


def test_small_interner_stays_a_dict():
    it = V.Interner()
    for s in ("a", "b"):
        it.intern(s)
    it.pack()
    assert it._base is None and it.lookup_many(["b", "z"]).tolist() == [1, -1]


def test_a_large_dict_packs_itself_inside_lookup_many_and_on_doubling():
    it = V.Interner()
    for i in range(V._TABLE_MIN):
        it.intern(f"s{i}")
    assert it._base is None
    assert it.lookup_many(["s3", "q"]).tolist() == [3, -1]
    assert it._base is not None and it._base.n == V._TABLE_MIN
    for i in range(V._TABLE_MIN, 2 * V._TABLE_MIN):
        it.intern(f"s{i}")
    assert it._base.n == V._TABLE_MIN  # newer entries answer from the dict
    assert it.lookup_many(["s2000"]).tolist() == [2000]
    assert it._base.n == 2 * V._TABLE_MIN  # doubled: packed anew


def test_bulk_loaded_store_packs_its_vocabulary_and_decodes_rows():
    from ketotpu.api.types import RelationQuery
    from ketotpu.utils.synth import build_synth_columnar

    g = build_synth_columnar(n_users=1500, n_groups=10, n_folders=30,
                             n_docs=1500)
    v = g.store.vocab
    assert v.objects._base is not None and v.subjects._base is not None
    assert v.namespaces._base is None  # three names stay a dict
    got, _ = g.store.get_relation_tuples(
        RelationQuery(namespace="Doc", object="d7", relation="parents"))
    assert len(got) == 1 and got[0].object == "d7"
    assert got[0].subject.namespace == "Folder"


@pytest.mark.parametrize("n", [5, V._TABLE_MIN * 2 + 3])
def test_from_utf8_is_the_interner_the_same_interns_make(n, monkeypatch):
    """The bulk constructor a loader hands its names to as bytes: the ids
    and answers of interning them one by one, and never a dict of them."""
    monkeypatch.setattr(V, "_HASH_STEP", 500)
    names = [f"set:Folder:f{i}#" for i in range(n)]
    names[2], names[3] = "ünï-" + "x" * 40, "exactly8"
    slow = V.Interner()
    for s in names:
        slow.intern(s)
    fast = V.Interner.from_utf8(*V._utf8(names, n))
    assert len(fast) == n and fast.strings() == slow.strings()
    assert (fast._base is not None) == (n >= V._TABLE_MIN)
    q = names[::7] + ["nope", "set:Folder:f#", "", "exactly8\0"]
    assert fast.lookup_many(q).tolist() == slow.lookup_many(q).tolist()
    assert [fast.lookup(s) for s in q] == [slow.lookup(s) for s in q]
    assert fast.intern("later") == n == slow.intern("later")


def test_registry_init_builds_a_bulk_loaded_stores_forward_index():
    """Left to the first full-key query the index is built under the
    store's lock inside that request; ``Registry.init()`` builds it before
    the store serves, and the build is counted."""
    from ketotpu import hostwaits
    from ketotpu.driver import Provider, Registry
    from ketotpu.utils.synth import build_synth_columnar

    g = build_synth_columnar(n_users=1500, n_groups=10, n_folders=30,
                             n_docs=1500)
    assert g.store._fwd_keys is None
    before = dict(hostwaits.LAZY_BUILD_SECONDS)
    reg = Registry(Provider({"engine": {"kind": "oracle"}}), store=g.store,
                   namespace_manager=g.manager).init()
    try:
        assert g.store._fwd_keys is not None
        assert g.store._fwd_order.dtype == np.int32
        assert hostwaits.LAZY_BUILD_SECONDS["store_fwd"] > before["store_fwd"]
        assert hostwaits.LAZY_BUILD_SECONDS["vocab_index"] >= before[
            "vocab_index"]
    finally:
        reg.close() if hasattr(reg, "close") else None
