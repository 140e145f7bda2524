"""String interning: the host-side vocabulary mapping API strings to dense ids.

The reference maps every namespace/object/subject string to a UUIDv5 before it
touches storage (`internal/relationtuple/uuid_mapping.go:199-267`,
`internal/persistence/sql/uuid_mapping.go:35-74`).  On TPU we go one step
further: dense int32 ids, so graph nodes index directly into CSR arrays.  The
UUID mapper (`ketotpu/api/uuid_map.py`) stays the wire-parity layer; this
vocabulary is the device-id layer.

Interners are append-only so ids remain stable across snapshot rebuilds —
arrays grow, existing ids never move (mirrors the reference's INSERT ON
CONFLICT DO NOTHING mapping writes).

An interner holds its entries in two parts.  New strings go into a Python
``dict``.  :meth:`Interner.pack` freezes what is there into the **bulk
form** (:class:`_Packed`): the strings as one UTF-8 blob with an offset
column, and a bucketed hash table (engine/hashtab.py) keyed on a 62-bit
hash of each string's bytes whose payload is the id; the dict then starts empty
again and holds only what is interned afterwards.  A ``dict`` of strings
costs ~150 bytes an entry (the ``str``, the ``int``, the slot), the bulk
form ~25: at the 123M strings of a 150M-tuple Drive graph that is 18 GB
against 3.  A bulk-loaded store packs its vocabulary at the load
(storage/columnar.py); any other interner packs itself inside the first
``lookup_many`` that finds it large, and again whenever it has doubled.
A bulk loader hands its names over as bytes (:meth:`Interner.from_utf8`):
the hash is computed from the blob, eight bytes a step across the build
pool, so no name becomes a Python ``str`` (123M names: 84 s of a loop
over strings before, PERF.md §6).

``lookup_many`` probes the table once per request column instead of
walking a dict per item.  Every probe hit is verified against the blob (two
distinct strings CAN share a masked hash; the second of such a pair is
kept in a side dict at pack), and misses fall back to the dict of newer
entries."""

from __future__ import annotations

import itertools
import threading
from typing import Dict, List, Optional, Sequence

import numpy as np

from ketotpu import hostwaits
from ketotpu.api.types import RelationTuple, Subject, SubjectSet
from ketotpu.engine import hashtab, parallel

#: interners smaller than this answer straight from the dict — the table
#: build is O(n) and only pays for itself once columns are long-lived
_TABLE_MIN = 1024

_HASH_MASK = (1 << 62) - 1
_HALF_MASK = (1 << 31) - 1
_M64 = (1 << 64) - 1
# odd 64-bit multipliers (the golden ratio's, two of xxhash's primes)
_C1, _C2, _C3 = 0x9E3779B97F4A7C15, 0xC2B2AE3D27D4EB4F, 0x165667B19E3779F9

#: strings a step of the blob hash: its dozen temporaries stay a few MB
_HASH_STEP = 1 << 20
#: below this many strings (a wave of single Checks) the numpy passes'
#: fixed cost (54 us) is more than hashing them one by one (1 us each)
_FEW = 48


def _hash_one(b: bytes) -> int:
    """The 62-bit hash of one string's UTF-8 bytes: the length, then the
    bytes eight at a time as little-endian words (the last zero-filled),
    each folded in by xor, multiply, shift.  No seed: the same bytes hash
    alike in every process, so a blob hashes without its strings."""
    h = ((len(b) + 1) * _C1) & _M64
    for i in range(0, len(b), 8):
        h = ((h ^ int.from_bytes(b[i:i + 8], "little")) * _C2) & _M64
        h ^= h >> 29
    h = (h * _C3) & _M64
    return (h ^ (h >> 32)) & _HASH_MASK


def _hash_step(blob, off, lo: int, hi: int, out) -> None:
    """:func:`_hash_one` of the strings ``lo..hi`` of a blob, all at once,
    into ``out[lo:hi]``."""
    u = np.uint64
    o = off[lo:hi + 1].astype(np.int64)
    base, nb = int(o[0]), int(o[-1] - o[0])
    # the range's bytes as words, two of slack: a string's step reads the
    # word its bytes start in and the next
    words = np.zeros((nb >> 3) + 2, "<u8")
    words.view(np.uint8)[:nb] = blob[base:base + nb]
    ln, rel = np.diff(o), o[:-1] - base
    h = (ln + 1).astype(u) * u(_C1)
    act, j = np.flatnonzero(ln > 0), 0
    while act.size:
        p, rem = rel[act] + 8 * j, ln[act] - 8 * j
        q, s = p >> 3, ((p & 7) << 3).astype(u)
        w = words[q] >> s
        t = u(64) - s  # 8..64 bits from the next word: in two shifts
        w |= (words[q + 1] << (t >> u(1))) << (t - (t >> u(1)))
        short = np.flatnonzero(rem < 8)
        if short.size:
            w[short] &= (u(1) << (rem[short].astype(u) << u(3))) - u(1)
        w ^= h[act]
        w *= u(_C2)
        w ^= w >> u(29)
        h[act] = w
        act, j = act[rem > 8], j + 1
    h *= u(_C3)
    h ^= h >> u(32)
    out[lo:hi] = h & u(_HASH_MASK)


def _halves(blob, off):
    """The two non-negative int32 halves of each string's 62-bit hash
    (hashtab keys must be non-negative), for the strings that lie one
    after another in ``blob`` from ``off[i]`` to ``off[i + 1]``."""
    n = len(off) - 1
    if n < _FEW:
        raw, at = blob.tobytes(), off.tolist()
        ha = np.fromiter((_hash_one(raw[at[i]:at[i + 1]])
                          for i in range(n)), np.uint64, n)
    else:
        ha = _hash_all(blob, off, n)
    return ((ha & np.uint64(_HALF_MASK)).astype(np.int32),
            (ha >> np.uint64(31)).astype(np.int32))


def _hash_all(blob, off, n: int) -> np.ndarray:
    """The strings' hashes, a step at a time across the build pool."""
    ha = np.empty(n, np.uint64)

    def _range(lo, hi):
        for at in range(lo, hi, _HASH_STEP):
            _hash_step(blob, off, at, min(at + _HASH_STEP, hi), ha)

    parallel.shard_apply(n, _range)
    return ha


def _utf8(strs, n: int):
    """``strs`` as one blob of UTF-8 bytes and each one's byte length."""
    joined = "".join(strs)
    blob = joined.encode()
    if len(blob) == len(joined):  # all ASCII: a character is a byte
        lens = np.fromiter(map(len, strs), np.int64, n)
    else:
        lens = np.fromiter((len(s.encode()) for s in strs), np.int64, n)
    return np.frombuffer(blob, np.uint8), lens


def _offsets(lens) -> np.ndarray:
    """Where each string starts in a blob of strings of these lengths,
    and where the last one ends."""
    off = np.zeros(len(lens) + 1, np.int64)
    np.cumsum(lens, out=off[1:])
    return off


#: strings a pass of a pack: what a pass holds beside its results (the
#: joined text) stays a few hundred MB
_PACK_CHUNK = 1 << 22


def _chunks(strs):
    """``strs`` (in id order) as ``(first id, strings)`` passes of
    ``_PACK_CHUNK``."""
    it, lo = iter(strs), 0
    while chunk := list(itertools.islice(it, _PACK_CHUNK)):
        yield lo, chunk
        lo += len(chunk)


def _drain(ids: Dict[str, int]):
    """The same passes over the keys of ``ids``, taken out of the dict as
    they are handed over.  A dict gives up its entries from the end, so
    the passes come last ids first; each pass's strings and ints are
    freed with it, and the emptied dict gives its slots back at the end."""
    hi = len(ids)
    while ids:
        take = [ids.popitem()[0] for _ in range(min(_PACK_CHUNK, len(ids)))]
        take.reverse()
        hi -= len(take)
        yield hi, take
    ids.clear()


class _Packed:
    """``n`` strings frozen in id order: ids are positions."""

    def __init__(self, blob: np.ndarray, off: np.ndarray):
        """The strings whose UTF-8 bytes lie one after another in
        ``blob``, string ``i`` from ``off[i]`` to ``off[i + 1]``."""
        self.n = n = len(off) - 1
        self.blob = blob
        a, b = _halves(blob, off)
        # half the bytes where the blob is under 4 GB
        self.off = off.astype(np.uint32) if off[-1] < (1 << 32) else off
        del off
        self.tab = hashtab.build_table(
            a, b, np.arange(n, dtype=np.int32), lean=True, probe=16)
        del a, b
        # the entry columns at their exact length: the pad to a power of
        # two is for device shapes, and this table never leaves the host
        used = max(hashtab.slots_in_use(self.tab), 1)
        for col in ("tag", "key_b", "val"):
            self.tab[col] = self.tab[col][:used].copy()
        # strings whose masked hash an earlier string has too: the table
        # finds only the first of such a pair
        self.extra: Dict[str, int] = {}
        for i in self.tab["val"][hashtab.repeated_keys(self.tab)].tolist():
            self.extra[self.get(i)] = i

    @classmethod
    def of_strings(cls, chunks, n: int) -> "_Packed":
        """``chunks`` yields ``(first id, strings)`` passes that cover the
        ids once, in any order (a caller may hand over what it frees as
        it goes)."""
        lens = np.zeros(n, np.int64)
        blobs, seen = [], 0
        for lo, chunk in chunks:
            hi = lo + len(chunk)
            blob, lens[lo:hi] = _utf8(chunk, hi - lo)
            blobs.append((lo, blob))
            seen += hi - lo
            del chunk
        assert seen == n
        off = _offsets(lens)
        del lens
        whole = np.empty(int(off[-1]), np.uint8)
        while blobs:
            lo, blob = blobs.pop()
            whole[off[lo]:][:len(blob)] = blob
        return cls(whole, off)

    def get(self, i: int) -> str:
        return self.blob[int(self.off[i]):int(self.off[i + 1])] \
            .tobytes().decode()

    def all(self) -> List[str]:
        blob, off = self.blob.tobytes(), self.off.tolist()
        return [blob[off[i]:off[i + 1]].decode() for i in range(self.n)]

    def find(self, s: str) -> int:
        raw = s.encode()
        ha = _hash_one(raw)
        i = hashtab.lookup_one(self.tab, ha & _HALF_MASK, ha >> 31)
        if i >= 0 and self.blob[int(self.off[i]):int(
                self.off[i + 1])].tobytes() == raw:
            return i
        return self.extra.get(s, -1) if self.extra else -1

    def find_many(self, strs: Sequence[str]) -> np.ndarray:
        """The id of each string, -1 per miss; every hit verified."""
        n = len(strs)
        q_blob, q_len = _utf8(strs, n)
        q_off = _offsets(q_len)
        ids, found = hashtab.lookup_np(self.tab, *_halves(q_blob, q_off))
        hit = np.flatnonzero(found)
        if len(hit):
            # a probe hit only proves the masked hash matched: compare the
            # bytes, all hits in one pass (lengths first, then the bytes
            # of the equal-length ones against the blob's)
            lo = self.off[ids[hit]].astype(np.int64)
            same = (self.off[ids[hit] + 1] - lo) == q_len[hit]
            k, ln = np.flatnonzero(same), q_len[hit][same]
            nz = ln > 0
            if nz.any():
                k, ln = k[nz], ln[nz]
                seg = np.cumsum(ln) - ln  # each string's start in the flat run
                step = np.arange(int(ln.sum())) - np.repeat(seg, ln)
                eq = self.blob[np.repeat(lo[k], ln) + step] == q_blob[
                    np.repeat(q_off[hit][k], ln) + step]
                same[k] = np.logical_and.reduceat(eq, seg)
            for i in hit[~same].tolist():
                ids[i] = self.extra.get(strs[i], -1)
        return ids


class Interner:
    """Append-only string -> int32 id mapping."""

    def __init__(self):
        # entries interned since the last pack (all of them before the
        # first), under their ids
        self._ids: Dict[str, int] = {}
        self._base: Optional[_Packed] = None  # ids [0, _base.n)
        self._rev: List[str] = []  # id - _base.n -> string, grown on demand
        # held by whatever adds an entry, packs, or reads the two parts
        # as one (len, string)
        self._lock = threading.Lock()

    @classmethod
    def from_utf8(cls, blob: np.ndarray, lens: np.ndarray) -> "Interner":
        """An interner of ``len(lens)`` distinct strings under the ids
        0.., their UTF-8 bytes one after another in ``blob`` (uint8, kept,
        not copied), ``lens[i]`` bytes each, frozen straight into the bulk
        form: a bulk loader's names never become a dict, nor a ``str``
        each (123M names: 3 GB where the dict takes 15, and seconds where
        a loop over strings takes minutes)."""
        n = len(lens)
        off = _offsets(lens)
        if len(blob) != off[-1]:
            raise ValueError(
                f"a blob of {len(blob)} bytes for names of {int(off[-1])}")
        self = cls()
        if n < _TABLE_MIN:
            raw = np.asarray(blob, np.uint8).tobytes()
            for i in range(n):
                self.intern(raw[off[i]:off[i + 1]].decode())
            assert len(self._ids) == n
        else:
            with hostwaits.lazy_build("vocab_index", n):
                self._base = _Packed(np.asarray(blob, np.uint8), off)
        return self

    def intern(self, s: str) -> int:
        i = self.lookup(s)
        if i < 0:
            with self._lock:
                i = self.lookup(s)
                if i < 0:
                    i = self._count()
                    self._ids[s] = i
        return i

    def lookup(self, s: str) -> int:
        """-1 for unknown strings (a miss everywhere on device)."""
        i = self._ids.get(s)
        if i is None:
            base = self._base
            return base.find(s) if base is not None else -1
        return i

    def _count(self) -> int:
        base = self._base
        return (base.n if base is not None else 0) + len(self._ids)

    def __len__(self) -> int:
        with self._lock:
            return self._count()

    def string(self, i: int) -> Optional[str]:
        """The string of id ``i``; None where there is none."""
        base = self._base
        if base is not None and 0 <= i < base.n:
            return base.get(i)
        with self._lock:
            j = i - (self._base.n if self._base is not None else 0)
            if j >= len(self._rev) and len(self._ids) > len(self._rev):
                # insertion order is id order: the view only ever extends
                self._rev.extend(itertools.islice(
                    self._ids.keys(), len(self._rev), None))
            return self._rev[j] if 0 <= j < len(self._rev) else None

    def strings(self) -> List[str]:
        with self._lock:
            base = self._base.all() if self._base is not None else []
            return base + list(self._ids.keys())

    # -- bulk form -----------------------------------------------------------

    def pack(self, consume: bool = False) -> None:
        """Freeze every entry into the bulk form (module docstring); a
        small interner stays a dict.

        ``consume`` empties the dict as it goes, so that the strings of
        each pass are freed before the next is read, whoever else still
        holds the dict: a bulk loader's own reference would otherwise
        keep 150 bytes an entry alive beside the bulk form until the
        loader returns (15 GB at 123M strings).  Until the pack ends a
        lookup misses what is already taken, so only a load that nothing
        reads yet may ask for it."""
        with self._lock:
            base, ids, n = self._base, self._ids, self._count()
            if n < _TABLE_MIN or not ids:
                return
            with hostwaits.lazy_build("vocab_index", n):
                if consume and base is None:
                    packed = _Packed.of_strings(_drain(ids), n)
                else:
                    strs = ids if base is None else (
                        base.all() + list(ids.keys()))
                    packed = _Packed.of_strings(_chunks(strs), n)
            # a reader between the two stores sees the new base beside the
            # old dict: the same ids twice over, no wrong answer
            self._base = packed
            self._ids, self._rev = {}, []

    def _index(self) -> Optional[_Packed]:
        """The bulk form, packed anew once the interner has doubled:
        entries interned after a pack answer through the dict."""
        base, n = self._base, self._count()
        if n >= _TABLE_MIN and (base is None or n >= 2 * base.n):
            self.pack()
        return self._base

    def lookup_many(self, strs: Sequence[str]) -> np.ndarray:
        """Vectorized :meth:`lookup` over a whole column; -1 per miss."""
        n = len(strs)
        base = self._index()
        get = self._ids.get
        if base is None or n == 0:
            return np.fromiter((get(s, -1) for s in strs), np.int32, n)
        out = base.find_many(strs)
        if self._ids:
            for i in np.flatnonzero(out < 0).tolist():
                out[i] = get(strs[i], -1)
        return out


class Vocab:
    """The four id spaces of the tuple graph."""

    def __init__(self):
        self.namespaces = Interner()
        self.objects = Interner()
        self.relations = Interner()
        self.subjects = Interner()  # keyed by Subject.unique_id()
        # The empty relation is legal ("the object itself",
        # ketoapi/enc_string.go:79-94) — always present.
        self.relations.intern("")

    def intern_tuple(self, t: RelationTuple) -> None:
        self.namespaces.intern(t.namespace)
        self.objects.intern(t.object)
        self.relations.intern(t.relation)
        self.subjects.intern(t.subject.unique_id())
        if isinstance(t.subject, SubjectSet):
            self.namespaces.intern(t.subject.namespace)
            self.objects.intern(t.subject.object)
            self.relations.intern(t.subject.relation)

    def subject_key(self, s: Optional[Subject]) -> int:
        if s is None:
            return -1
        return self.subjects.lookup(s.unique_id())

    def encode_columns(self, ns, obj, rel, subj_uid):
        """Bulk-encode four request string columns to int32 id columns —
        one vectorized probe per column (engine/hashtab.py), scalar dict
        fallback only for misses.  Byte-for-byte equal to mapping
        ``lookup``/``subject_key`` over the items."""
        return (
            self.namespaces.lookup_many(ns),
            self.objects.lookup_many(obj),
            self.relations.lookup_many(rel),
            self.subjects.lookup_many(subj_uid),
        )

    def pack(self, consume: bool = False) -> None:
        """Freeze the four id spaces into their bulk form
        (:meth:`Interner.pack`), the smaller spaces first: each pack
        frees its dict, and the largest builds with the others small."""
        for interner in sorted(
                (self.namespaces, self.objects, self.relations,
                 self.subjects), key=len):
            interner.pack(consume)
