"""Bucketed hash tables: O(1) device-side key lookups over int32 pairs.

The device engines need two point lookups per frontier hop — node resolution
``(namespace, object, relation) -> node id`` and tuple existence
``(node, subject) -> bool`` (the reference's index probes,
`internal/persistence/sql/traverser.go:53-191` and
`relationtuples.go:249-261`).  Binary search works but compiles badly: the
unrolled log2(N) gather chain is the dominant XLA compile cost of the whole
check step and grows with the graph.  A bucketed hash table probes a fixed
number of slots instead — compile cost is constant and runtime gathers drop
from O(log N) to O(1), which matters at the 10M-tuple target.

Layout (all host-built with vectorized numpy, no per-row Python):

* ``ptr``: int32[buckets+1], a bucket's first slot in the low 29 bits and
  its **split level** ``s`` (0-7) in the high three,
* ``tag``: int32[capacity] ``key_a ^ f(key_b)``, entries grouped by bucket
  (``f`` a 32-bit mix with a salt of its own, :func:`_tag_np`),
* ``key_b``: int32[capacity] the key's second half, in the same order,
* ``val``: int32[capacity] payload (node ids), optional,
* ``meta``: int32[7] = (salt index, bucket mask, tag salt index, split
  salt index; then what the build counted: split buckets, deepest level,
  empty slots between entries) as device scalars,
* ``pw``: int8[probe], the probe rounds as a SHAPE (a jitted lookup reads
  it at trace time).

**The probe depth is a constant of the caller (``probe``, ``D`` below),
not a property of the data.**  A bucket of at most ``D`` different keys is
level 0: its keys lie in the ``D`` slots from its offset.  A deeper bucket
is split in place, as extendible hashing splits a directory entry: the
low ``s`` bits of a second hash of the key (:func:`_split_np`, a mix and a
salt of its own) name one of ``2^s`` parts, and part ``j`` is looked for
in the **window** of ``D`` slots from ``offset + j * (D // 2)``.  Windows
overlap; parts lie one after another in order, and a slot is left empty
(-1 in every column, as the pads behind the entries are) only where a part
would otherwise start before its window.  ``s`` is the smallest level at
which every part's keys fit its window.  A lookup gathers ``ptr[h]``,
decodes offset and level with a few integer operations, and then probes
exactly ``D`` rounds whatever the table holds: ``1 + D + 1`` gathers and
one more for a payload (:func:`lookup_gathers`).  The invariants, each
held by a test (``tests/test_hashtab.py``):

1. a window never begins before its own bucket, and a part's keys lie at
   or after their window's start and inside it;
2. an empty slot lies only behind the keys of every window that covers it
   (it is put between two parts, and a window that reaches it belongs to
   an earlier part);
3. **no two entries of one bucket with different keys share a tag**
   (:func:`_tag_clash`): a window may begin with entries of earlier parts
   of its own bucket, never with another bucket's;
4. of a run of equal keys (the membership table admits duplicates) the
   first lies in its part, the rest behind the bucket's last part, where
   the host's bucket scan (:func:`lookup_one`, :func:`splice_table`) finds
   them: a run counts once toward a part's depth.

A probe round gathers ONE column: ``tag[j]`` against the query's tag.
After the rounds the key is verified once, at the first tag hit:
``tag[j] == qtag`` and ``key_b[j] == b`` together give ``key_a[j] == a``
(the tag is an XOR with ``a``), so ``key_a`` is stored nowhere.  By 1-4
the first hit in a window is the key's own entry whenever the key is
present; entries of following buckets and empty slots may share an absent
query's tag and fail the verify.  ``build_table`` walks the tag salt when
a table breaks 3 and the split salt when no level within three bits
separates a bucket; ``splice_table`` lays the buckets it touches anew and
declines the edit where it would have to walk a salt.  Fixed-shape tables
(the delta overlay's) walk the bucket salt until no bucket is deeper than
``D`` and always read level 0.  Keys are non-negative; -1 is the
empty/pad sentinel and negative queries never match.
"""

from __future__ import annotations

import threading
from collections.abc import Mapping
from typing import Dict, Optional, Tuple

import numpy as np

from ketotpu.engine import parallel

_I32MAX = int(np.iinfo(np.int32).max)

PROBE = 8  # default probe depth: the rounds every lookup of the table unrolls
PROBE_SHALLOW = 4  # for small side tables on hot probe paths (delta overlay)
# the big snapshot tables (node resolution + tuple membership) probe four
# rounds, and that is a bound: the build splits what is deeper (module
# docstring), so neither the table's load nor its size shows in the
# program.  A probe round is NOT free on the chip: a 1-D element gather
# costs 12-13 ns an element whatever it reads, and the two tables' probes
# were two thirds of the 1024-row mixed wave's device time at a deepest
# bucket of 8 (10M keys) to 11 (132.7M keys in 2^27 buckets) unrolled for
# every lane (PERF.md §5, §6: PR 34, PR 35).  Buckets stay at the power of
# two at or above the entries (every bucket is 4 bytes of ptr in HBM).
SNAPSHOT_PROBE = 4

#: ``ptr`` packs a bucket's first slot under its split level: offsets stay
#: under 2^29 at every size one chip holds (2^28 slots for 150M tuples)
_OFF_BITS = 29
_OFF_MASK = (1 << _OFF_BITS) - 1
_SLOT_CAP = _OFF_MASK  # the slots a table may span, so the entries it holds
_MAX_LEVEL = 7  # three bits

def subtables(g, prefix):
    """Extract the sub-dict of a packed table by key prefix: the device
    array dicts carry several hash tables side by side (nt_/mt_/ovt_/om_),
    and every lookup site needs the prefix stripped the same way."""
    return {k[len(prefix):]: v for k, v in g.items() if k.startswith(prefix)}


_SALTS = np.array(
    [0x243F6A88, 0x85A308D3, 0x13198A2E, 0x03707344,
     0xA4093822, 0x299F31D0, 0x082EFA98, 0xEC4E6C89],
    dtype=np.uint32,
)


def _bucket_pow2(n: int, floor: int) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def _mix_np(a: np.ndarray, b: np.ndarray, salt: np.uint32) -> np.ndarray:
    a = a.astype(np.uint32)
    b = b.astype(np.uint32)
    h = (a ^ (b * np.uint32(0x85EBCA77))) * np.uint32(0x9E3779B1) + salt
    h ^= h >> np.uint32(16)
    h *= np.uint32(0xC2B2AE3D)
    h ^= h >> np.uint32(13)
    return h


def mix_device(a, b, salt):
    """The same mix for jnp arrays (int32 in, uint32 lattice, int32 out)."""
    import jax.numpy as jnp

    a = a.astype(jnp.uint32)
    b = b.astype(jnp.uint32)
    h = (a ^ (b * jnp.uint32(0x85EBCA77))) * jnp.uint32(0x9E3779B1) + salt.astype(
        jnp.uint32
    )
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0xC2B2AE3D)
    h = h ^ (h >> jnp.uint32(13))
    return h


def _fmix(f, u):
    """murmur3's 32-bit finalizer over a uint32 lattice: a bijection, so
    two different ``b`` never share an ``f(b)``.  ``u`` casts the constants
    (``np.uint32`` on the host, ``jnp.uint32`` on the device)."""
    f = (f ^ (f >> u(16))) * u(0x85EBCA6B)
    f = (f ^ (f >> u(13))) * u(0xC2B2AE35)
    return f ^ (f >> u(16))


def _tag_np(a: np.ndarray, b: np.ndarray, salt: np.uint32) -> np.ndarray:
    """An entry's (or a query's) tag, ``a ^ f(b + salt)``, as int32.  The
    XOR makes the verify one gather: equal tags and equal ``b`` are equal
    ``a``.  A mix of its own: the bucket hash shares no constant with it."""
    f = _fmix(b.astype(np.uint32) + salt, np.uint32)
    return (a.astype(np.uint32) ^ f).view(np.int32)


def tag_device(a, b, salt):
    """:func:`_tag_np` for jnp arrays (int32 in, int32 out)."""
    import jax
    import jax.numpy as jnp

    f = _fmix(b.astype(jnp.uint32) + salt.astype(jnp.uint32), jnp.uint32)
    return jax.lax.bitcast_convert_type(a.astype(jnp.uint32) ^ f, jnp.int32)


def _split_mix(a, b, salt, u):
    """The hash whose low bits part a split bucket, over a uint32 lattice
    (``u`` casts the constants, as in :func:`_fmix`).  It shares no
    constant with the bucket hash, whose low bits a bucket's keys share."""
    x = (a * u(0xCC9E2D51)) ^ (b * u(0x1B873593) + salt)
    x = (x ^ (x >> u(15))) * u(0x2C1B3C6D)
    x = (x ^ (x >> u(12))) * u(0x297A2D39)
    return x ^ (x >> u(15))


def _split_np(a: np.ndarray, b: np.ndarray, salt: np.uint32) -> np.ndarray:
    return _split_mix(a.astype(np.uint32), b.astype(np.uint32), salt, np.uint32)


def split_device(a, b, salt):
    """:func:`_split_np` for jnp arrays (int32 in, uint32 out)."""
    import jax.numpy as jnp

    return _split_mix(a.astype(jnp.uint32), b.astype(jnp.uint32),
                      salt.astype(jnp.uint32), jnp.uint32)


def _offsets(ptr) -> np.ndarray:
    """The buckets' first slots (int32, ascending; the last is the slots in
    use), without the split levels :func:`_levels` reads."""
    return np.asarray(ptr) & np.int32(_OFF_MASK)


def _levels(ptr) -> np.ndarray:
    """The buckets' split levels: 0, or ``s`` for a bucket of ``2^s`` parts."""
    return (np.asarray(ptr).view(np.uint32) >> np.uint32(_OFF_BITS)).astype(np.uint8)


#: times a layout invariant refused a table: a ``build`` or an ``overlay``
#: (fixed-shape) build walked to another tag salt, a ``splice`` fell back
#: to a full build, a build walked to another ``split`` salt because no
#: level separated a bucket (scrape:
#: ``keto_projection_tag_rejects_total{op}``)
TAG_REJECTS = {"build": 0, "splice": 0, "overlay": 0, "split": 0}
_TAG_REJECTS_LOCK = threading.Lock()  # builds run on the compactor's thread too


def _tag_reject(op: str) -> None:
    with _TAG_REJECTS_LOCK:
        TAG_REJECTS[op] += 1


def _ranges(lo: np.ndarray, width: np.ndarray) -> np.ndarray:
    """``arange(lo[k], lo[k] + width[k])`` for every ``k``, one after
    another."""
    width = np.asarray(width, np.int64)
    ends = np.cumsum(width)
    return (np.arange(int(ends[-1]) if len(ends) else 0, dtype=np.int64)
            + np.repeat(np.asarray(lo, np.int64) - (ends - width), width))


def _index_in_group(g: np.ndarray) -> np.ndarray:
    """Each element's index among the elements of equal ``g``, in order of
    position."""
    o = np.argsort(g, kind="stable")
    gs = g[o]
    starts = np.flatnonzero(np.r_[True, gs[1:] != gs[:-1]]) if len(g) else o
    out = np.empty(len(g), np.int64)
    out[o] = np.arange(len(g)) - np.repeat(
        starts, np.diff(np.r_[starts, len(g)]))
    return out


def _twins(rank, tag, key_b, pos=None):
    """Of entries grouped in buckets (``rank``), by one sort on the tag:
    which are the first of their key (tag and ``key_b``; of equal keys the
    first by ``pos``, if given), and whether two keys of a bucket share a tag."""
    o = np.lexsort((key_b, tag, rank) if pos is None else (pos, key_b, tag, rank))
    twin = (rank[o][1:] == rank[o][:-1]) & (tag[o][1:] == tag[o][:-1])
    same = twin & (key_b[o][1:] == key_b[o][:-1])
    first = np.ones(len(rank), bool)
    first[o[1:][same]] = False
    return first, bool((twin & ~same).any())


def _near_twins(off, tag, key_b, depth: int, same_key: bool):
    """Positions of entries that share bucket and tag with an entry less
    than ``depth`` slots before them, and its ``key_b`` too (``same_key``)
    or not (two keys of one tag): every such pair of a bucket of at most
    ``depth`` slots; an empty slot is no entry.  One compare pass a distance
    over the column; equal tags that close are rare, or duplicates."""
    n = int(off[-1])
    for d in range(1, min(depth, n)):
        at = np.flatnonzero(tag[d:n] == tag[: n - d])
        at = at[(key_b[at] >= 0) & (key_b[at + d] >= 0)]
        at = at[(key_b[at] == key_b[at + d]) == same_key]
        if at.size:
            # the CSR position's bucket: the last offset at or before it
            at = at[np.searchsorted(off, at, side="right")
                    == np.searchsorted(off, at + d, side="right")]
        if at.size:
            yield at + d


def _deep_entries(off, key_b, depth: int):
    """The entries of the buckets of more than ``depth`` slots (under two
    in a hundred keys): their positions and each one's bucket, from 0."""
    deep = np.flatnonzero(off[1:] - off[:-1] > depth)
    width = (off[deep + 1] - off[deep]).astype(np.int64)
    pos = _ranges(off[deep], width)
    keep = key_b[pos] >= 0
    return pos[keep], np.repeat(np.arange(len(deep)), width)[keep]


def _tag_clash(off, tag, key_b, depth: int) -> bool:
    """True when two entries sit in one bucket with one tag and different
    keys: invariant 3, without which a lookup's first tag hit could be
    another key's entry (equal tags with equal ``key_b`` are one key)."""
    if next(_near_twins(off, tag, key_b, depth, False), None) is not None:
        return True
    pos, rank = _deep_entries(off, key_b, depth)
    return _twins(rank, tag[pos], key_b[pos])[1]


def _lay_buckets(rank, nb: int, first, x, probe: int):
    """Where the entries of ``nb`` buckets go (module docstring): the
    build's and the splice's one split routine.  ``rank`` is each entry's
    bucket (0 to ``nb`` - 1), ``first`` whether it is the first of its key,
    ``x`` its split hash.  Returns each bucket's level (-1 where no level
    within three bits fits its parts into their windows), each bucket's
    slots in all (entries and the empty slots between parts), and each
    entry's slot, counted through the buckets' slots laid end to end."""
    stride = max(probe // 2, 1)
    level = np.full(nb, -1, np.int64)
    span = np.zeros(nb, np.int64)
    slot = np.zeros(len(rank), np.int64)
    todo = np.arange(nb)  # buckets no level fits yet, and their entries
    e = np.arange(len(rank))
    for s in range(_MAX_LEVEL + 1):
        if not todo.size:
            break
        parts = 1 << s
        renum = np.full(nb, -1, np.int64)
        renum[todo] = np.arange(todo.size)
        r = renum[rank[e]]
        f = first[e]
        part = (x[e] & np.uint32(parts - 1)).astype(np.int64)
        # different keys a part, then the parts laid in order
        c = np.bincount(r[f] * parts + part[f],
                        minlength=todo.size * parts).reshape(todo.size, parts)
        start = np.empty_like(c)
        end = np.zeros(todo.size, np.int64)
        ok = np.ones(todo.size, bool)
        for j in range(parts):
            held = c[:, j] > 0
            start[:, j] = np.where(held, np.maximum(end, j * stride), end)
            end = start[:, j] + c[:, j]
            ok &= ~held | (end <= j * stride + probe)
        at = ok[r]
        r, f, part = r[at], f[at], part[at]
        # first entries in their parts, the other entries of a run of equal
        # keys behind the last part
        slot[e[at]] = np.where(f, start[r, part], end[r]) + _index_in_group(
            r * (parts + 1) + np.where(f, part, parts))
        span[todo[ok]] = end[ok] + np.bincount(r[~f], minlength=todo.size)[ok]
        level[todo[ok]] = s
        todo, e = todo[~ok], e[~at]
    return level, span, (np.cumsum(span) - span)[rank] + slot


def _add_steps(ptr: np.ndarray, at: np.ndarray, step: np.ndarray) -> None:
    """``ptr[at[k] + 1:] += step[k]`` for every ``k`` (``at`` ascending), in
    place and a block at a time: no temporary as wide as ``ptr``."""
    cum = np.cumsum(step).astype(ptr.dtype)
    edges = np.append(at + 1, len(ptr))
    for k in range(0, len(at), 4096):
        e = edges[k:k + 4097]
        ptr[e[0]:e[-1]] += np.repeat(cum[k:k + 4096], np.diff(e))


def _bincount(h: np.ndarray, buckets: int) -> np.ndarray:
    """Per-bucket entry counts, sharded across the build pool when the
    host has cores to spare (each shard counts its slice; the partials
    sum) — single-core hosts take the plain bincount path."""
    threads = parallel.pool_size()
    n = len(h)
    if threads <= 1 or n < (1 << 21):
        return np.bincount(h, minlength=buckets)
    # partials are buckets-wide int64: at most 1 GB of them (four at the
    # 16.8M buckets of a 10M-key table, one from 134M up)
    shards = min(threads, 4, (1 << 27) // buckets)
    if shards <= 1:
        return np.bincount(h, minlength=buckets)
    step = -(-n // shards)
    parts = [None] * shards

    def _count(i):
        parts[i] = np.bincount(
            h[i * step : min((i + 1) * step, n)], minlength=buckets
        )

    pool = parallel._get_pool(threads)
    futs = [pool.submit(_count, i) for i in range(shards)]
    for f in futs:
        f.result()
    out = parts[0]
    for p in parts[1:]:
        out += p
    return out


def _grouped_order(h: np.ndarray, buckets: int) -> np.ndarray:
    """A permutation grouping entries by bucket id.

    Bucket-CSR layout only needs entries GROUPED by bucket — order within
    a bucket of at most the probe's keys is free (a lookup scans them all;
    the build orders the deeper ones itself) — so each part is sorted by
    the faster non-stable introsort.  On a multi-core host the
    entries are first dealt into 256 ranges of the bucket space by one
    radix pass over the bucket id's top byte, and the pool sorts each
    range in place: beside the permutation itself (8 bytes an entry)
    there is a byte an entry and one range's scratch a thread, where a
    mask and an index list per shard cost 56 bytes an entry, 8 GB at the
    150M entries of a chip-filling graph's membership table."""
    threads = parallel.pool_size()
    n = len(h)
    if threads <= 1 or n < (1 << 21):
        return np.argsort(h)
    shift = max(int(buckets).bit_length() - 1 - 8, 0)
    top = (h >> np.uint32(shift)).astype(np.uint8)
    order = np.argsort(top, kind="stable")  # 8-bit keys: a radix sort
    ends = np.cumsum(np.bincount(top, minlength=256))
    del top

    def _part(i):
        seg = order[ends[i - 1] if i else 0:ends[i]]
        seg[:] = seg[np.argsort(h[seg])]

    pool = parallel._get_pool(threads)
    for f in [pool.submit(_part, i) for i in range(256)]:
        f.result()
    return order


def _gather_spread(n_slots: int, pad_at: np.ndarray, fill) -> None:
    """Run ``fill(lo, hi, src_lo, src_hi, at)`` over the slots, a million
    at a time on every thread of the pool (a gather's temporaries are as
    wide as its range): the slots ``lo`` to ``hi`` take the entries
    ``src_lo`` to ``src_hi`` of the grouped order with an empty slot put
    before each index in ``at`` (``np.insert``'s positions), which is
    where ``pad_at`` (the empty slots, ascending) falls in them."""

    def _shard(start, end):
        for lo in range(start, end, 1 << 20):
            hi = min(lo + (1 << 20), end)
            i0, i1 = np.searchsorted(pad_at, (lo, hi))
            fill(lo, hi, lo - i0, hi - i1, pad_at[i0:i1] - lo - np.arange(i1 - i0))

    parallel.shard_apply(n_slots, _shard)


def build_table(
    key_a: np.ndarray,
    key_b: np.ndarray,
    val: Optional[np.ndarray] = None,
    *,
    # floor raised 16->128 so every toy-scale table (tests, fuzz seeds)
    # lands on ONE shape: distinct shapes mean distinct XLA programs,
    # and per-config recompiles are the suite's dominant cost AND the
    # trigger for the XLA:CPU compile-load crash (tests/conftest.py)
    min_buckets: int = 128,
    # lean tables allocate ~n buckets instead of ~2n: at the 10M-tuple
    # scale the bucket POINTER array alone is 134MB of device upload
    # (and HBM) per table; the price is more buckets deeper than the
    # probe, which the build splits (0.03 % of them at a load of 0.56,
    # 0.35 % at 0.99), and no gather in any lookup.
    lean: bool = False,
    probe: int = PROBE,
    fixed_shape: Optional[Tuple[int, int]] = None,
) -> Dict[str, np.ndarray]:
    """Vectorized build; returns the device-array dict for `lookup`.

    ``probe`` is the number of rounds every lookup of the table unrolls,
    whatever it holds: a bucket of more different keys is split in place
    (module docstring).  Small hot-path side tables (the delta overlay)
    build shallow so their lookups unroll to fewer gather rounds.

    ``fixed_shape=(buckets, cap)`` pins the array shapes: callers that
    re-ship a table with changing content (the delta overlay) pass their
    size thresholds so every rebuild has identical shapes and the jitted
    consumer never recompiles.  Such a table is never split: if no salt
    of the schedule keeps every bucket within the probe bound the build
    raises ``ValueError`` — the caller falls back to a full rebuild."""
    # keys keep their native dtype: the mix only reads the low 32 bits and
    # the entry columns store int32 (int64 here was two copy passes a table)
    key_a = np.asarray(key_a)
    key_b = np.asarray(key_b)
    n = key_a.shape[0]
    if fixed_shape is not None:
        buckets = fixed_shape[0]
        if n > fixed_shape[1]:
            raise ValueError(f"{n} entries exceed fixed cap {fixed_shape[1]}")
    else:
        buckets = _bucket_pow2(max(n if lean else 2 * n, 1), min_buckets)
    if n > _SLOT_CAP or buckets > _I32MAX + 1:
        # ptr holds slot offsets under the split level, meta the bucket mask
        raise ValueError(
            f"{n} entries in {buckets} buckets pass a table's cap of "
            f"{_SLOT_CAP} entries and {_I32MAX + 1} buckets")
    # a grown table takes the first salt: what that leaves deeper than the
    # probe is split.  A fixed-shape table cannot be split (its offsets are
    # its callers' contract) and walks the schedule for a salt that fits.
    salt_i = 0
    h = np.empty(n, np.uint32)
    mask = np.uint32(buckets - 1)
    while True:
        def _hash(lo, hi, _s=_SALTS[salt_i]):
            h[lo:hi] = _mix_np(key_a[lo:hi], key_b[lo:hi], _s) & mask
        parallel.shard_apply(n, _hash)
        counts = _bincount(h, buckets)
        if fixed_shape is None or n == 0 or int(counts.max()) <= probe:
            break
        salt_i += 1
        if salt_i == len(_SALTS):
            raise ValueError(
                f"no salt fits {n} entries in {buckets} buckets at probe {probe}"
            )
    ptr = np.zeros(buckets + 1, np.int32)
    np.cumsum(counts, out=ptr[1:])
    deep = np.flatnonzero(counts > probe)
    deep_n = counts[deep]
    del counts  # buckets-wide int64: 2 GB at 268M buckets
    order = _grouped_order(h, buckets) if n else np.zeros(0, np.int64)
    del h
    # -- the buckets deeper than the probe, split in place ------------------
    split_i = 0
    deep_clash = False
    pad_at = np.zeros(0, np.int64)
    level = np.zeros(0, np.int64)
    if len(deep):
        pos = _ranges(ptr[deep], deep_n)
        rank = np.repeat(np.arange(len(deep)), deep_n)
        src = order[pos]
        ka, kb = key_a[src], key_b[src]
        first, deep_clash = _twins(rank, _tag_np(ka, kb, _SALTS[0]), kb)
        while True:
            level, span, at = _lay_buckets(
                rank, len(deep), first, _split_np(ka, kb, _SALTS[split_i]), probe)
            if int(level.min()) >= 0:
                break
            # a bucket no level within three bits separates (keys that
            # agree in seven bits of the split hash): another split salt
            _tag_reject("split")
            split_i += 1
            if split_i == len(_SALTS):
                raise ValueError(
                    f"no split salt lays {n} entries in {buckets} buckets "
                    f"at probe {probe}")
        # entries in slot order within their bucket; the slots no entry
        # took are the empty ones
        order[pos] = src[np.argsort(at)]
        pads = span - deep_n
        new_off = ptr[deep].astype(np.int64) + (np.cumsum(pads) - pads)
        taken = np.zeros(int(span.sum()), bool)
        taken[at] = True
        pad_at = _ranges(new_off, span)[~taken]
        _add_steps(ptr, deep[pads > 0], pads[pads > 0])
        del pos, src, at, taken, first
    n_slots = n + len(pad_at)
    if n_slots > _SLOT_CAP:
        raise ValueError(f"{n_slots} slots pass a table's cap of {_SLOT_CAP}")
    # the power of two at or above the entries; the empty slots (0.14 % of
    # them at a load of 0.99) take the next only where they stop that short
    cap = fixed_shape[1] if fixed_shape is not None else _bucket_pow2(
        max(n_slots, 1), 64)
    # empty + range fills instead of full(-1) + overwrite: one write pass
    # over the entry region, and the gather through ``order`` shards across cores
    tt = np.empty(cap, np.int32)
    tb = np.empty(cap, np.int32)
    tt[n_slots:] = -1
    tb[n_slots:] = -1
    # the tag column, and the walk of its salt: at random keys a bucket
    # holds two keys of one tag once in a thousand 10M-entry tables, so
    # the second pass is a guard; it rehashes nothing but the tags
    tag_i = 0
    while True:
        if tag_i and len(deep):
            deep_clash = _twins(rank, _tag_np(ka, kb, _SALTS[tag_i]), kb)[1]

        def _fill(lo, hi, src_lo, src_hi, at, _s=_SALTS[tag_i]):
            seg = order[src_lo:src_hi]
            b = key_b[seg].astype(np.int32, copy=False)
            tg = _tag_np(key_a[seg], b, _s)
            if len(at):
                b, tg = np.insert(b, at, -1), np.insert(tg, at, -1)
            tb[lo:hi] = b
            tt[lo:hi] = tg

        if not deep_clash:
            _gather_spread(n_slots, pad_at, _fill)
            if next(_near_twins(ptr, tt, tb, probe, False), None) is None:
                break
        _tag_reject("overlay" if fixed_shape is not None else "build")
        tag_i += 1
        if tag_i == len(_SALTS):
            raise ValueError(
                f"no tag salt keeps {n} entries in {buckets} buckets apart"
            )
    split = deep[level > 0]
    ptr.view(np.uint32)[split] |= (
        level[level > 0].astype(np.uint32) << np.uint32(_OFF_BITS))
    out = {
        "ptr": ptr,
        "tag": tt,
        "key_b": tb,
        "meta": np.array(
            [salt_i, buckets - 1, tag_i, split_i,
             len(split), int(level.max()) if len(level) else 0, len(pad_at)],
            np.int32),
        # probe rounds as SHAPE: jitted lookups read it statically at trace
        # time, and it is the caller's constant, never the data's
        "pw": np.zeros((probe,), np.int8),
    }
    if val is not None:
        val = np.asarray(val, np.int32)
        tv = np.empty(cap, np.int32)
        tv[n_slots:] = -1

        def _vals(lo, hi, src_lo, src_hi, at):
            v = val[order[src_lo:src_hi]]
            tv[lo:hi] = np.insert(v, at, -1) if len(at) else v

        _gather_spread(n_slots, pad_at, _vals)
        out["val"] = tv
    return out


def splice_table(
    t: Dict[str, np.ndarray],
    rm_a: np.ndarray,
    rm_b: np.ndarray,
    add_a: np.ndarray,
    add_b: np.ndarray,
    add_val: Optional[np.ndarray] = None,
    *,
    val_remap: Optional[np.ndarray] = None,
) -> Optional[Dict[str, np.ndarray]]:
    """Incrementally edit a built table without re-hashing its entries.

    Removes ONE entry per (rm_a, rm_b) key (duplicate keys remove distinct
    entries), inserts the add keys into their buckets, and optionally maps
    every surviving payload through ``val_remap`` (int32 gather — the fold
    renumbers node ids).  Every bucket an edit touches is laid anew by the
    build's split routine (:func:`_lay_buckets`: an insert into a full
    bucket splits it, a removal may join its parts again); the others keep
    their slots.  The salts, bucket count, capacity and probe rounds
    (``pw``) are all preserved, so a spliced table re-ships to the device
    without changing the jitted program's pytree.

    Returns None when the edit cannot keep that shape contract — more
    slots than capacity, or a removal key that is not resident
    (inconsistent caller bookkeeping) — or when it would have to walk one
    of the table's salts: an insert that puts two keys of one tag into a
    bucket, or a bucket no level separates.  The caller falls back to a
    full ``build_table``.
    """
    meta = np.asarray(t["meta"])
    mask = np.uint32(int(meta[1]))
    probe = t["pw"].shape[0]
    ptr = np.asarray(t["ptr"])
    off = _offsets(ptr)
    n_old = int(off[-1])
    n_rm, n_add = len(rm_a), len(add_a)
    salt = _SALTS[int(meta[0])]
    tag_salt = _SALTS[int(meta[2])]
    tg, kb, tv = t["tag"], t["key_b"], t.get("val")  # a DeviceTable fetches
    cap = len(kb)
    rm_a, rm_b = np.asarray(rm_a), np.asarray(rm_b)
    add_a, add_b = np.asarray(add_a), np.asarray(add_b, np.int32)

    h_rm = (_mix_np(rm_a, rm_b, salt) & mask).astype(np.int64)
    del_pos = np.empty(n_rm, np.int64)
    used: set = set()
    # a resident key is its tag and its second half (module docstring)
    rm_t_l = _tag_np(rm_a, rm_b, tag_salt).tolist()
    rm_b_l = rm_b.tolist()
    for i in range(n_rm):
        b = int(h_rm[i])
        found = -1
        for j in range(int(off[b]), int(off[b + 1])):
            if j not in used and tg[j] == rm_t_l[i] and kb[j] == rm_b_l[i]:
                found = j
                break
        if found < 0:
            return None
        used.add(found)
        del_pos[i] = found
    h_add = (_mix_np(add_a, add_b, salt) & mask).astype(np.int64)

    # -- the touched buckets: what stays of them and what comes, laid anew --
    touched = np.unique(np.concatenate([h_rm, h_add]))
    width = (off[touched + 1] - off[touched]).astype(np.int64)
    old = _ranges(off[touched], width)  # their slots, leaving as a whole
    keep = np.ones(n_old, bool)
    keep[del_pos] = False
    keep = keep[old] & (kb[old] >= 0)
    stay = old[keep]
    rank = np.concatenate([
        np.repeat(np.arange(len(touched)), width)[keep],
        np.searchsorted(touched, h_add)])
    e_tag = np.concatenate([tg[stay], _tag_np(add_a, add_b, tag_salt)])
    e_b = np.concatenate([kb[stay], add_b])
    first, clash = _twins(rank, e_tag, e_b)
    if clash:
        _tag_reject("splice")  # two keys of one tag: invariant 3
        return None
    level, span, at = _lay_buckets(
        rank, len(touched), first,
        _split_np(_tag_np(e_tag, e_b, tag_salt), e_b, _SALTS[int(meta[3])]),
        probe)
    n_new = n_old - len(old) + int(span.sum())
    if n_new > min(cap, _SLOT_CAP):
        return None
    if len(level) and int(level.min()) < 0:
        _tag_reject("splice")  # no level separates a bucket
        return None

    body = np.ones(n_old, bool)
    body[old] = False
    # each touched bucket's slots go in where its old ones came out
    ins_pos = np.repeat(off[touched] - (np.cumsum(width) - width), span)

    def _column(col, entries):
        block = np.full(int(span.sum()), -1, np.int32)
        block[at] = entries
        out = np.empty(cap, np.int32)
        out[:n_new] = np.insert(col[:n_old][body], ins_pos, block)
        out[n_new:] = -1
        return out

    ptr_new = off.copy()
    moved = span != width
    _add_steps(ptr_new, touched[moved], (span - width)[moved])
    hi = ptr.view(np.uint32) & ~np.uint32(_OFF_MASK)
    hi[touched] = level.astype(np.uint32) << np.uint32(_OFF_BITS)
    meta = meta.copy()
    meta[4] = np.count_nonzero(hi)
    meta[5] = int(hi.max()) >> _OFF_BITS
    meta[6] += int((span.sum() - len(rank)) - (len(old) - len(stay) - n_rm))
    out = {
        "ptr": (ptr_new.view(np.uint32) | hi).view(np.int32),
        "tag": _column(tg, e_tag),
        "key_b": _column(kb, e_b),
        "meta": meta,
        "pw": t["pw"],
    }
    if tv is not None:
        v_ins = (np.asarray(add_val, np.int32) if add_val is not None
                 else np.full(n_add, -1, np.int32))
        if val_remap is not None:
            # empty slots hold -1, which is no index
            remap = np.append(np.asarray(val_remap, np.int32), np.int32(-1))
            tv = remap[tv[:n_old]]
        out["val"] = _column(tv, np.concatenate([tv[stay], v_ins]))
    return out


def lookup_np(t: Dict, a: np.ndarray, b: np.ndarray) -> Tuple:
    """Host-side numpy mirror of :func:`lookup`: (val_or_index, found).

    One vectorized probe over a whole query column — the columnar batch
    decode uses this to encode request strings to vocabulary ids without
    a per-item Python dict walk.  Semantics match the device probe
    exactly: negative queries never match, the window starts at the
    bucket's offset plus the key's part of a split bucket, the rounds
    compare tags and the key is verified once at the first tag hit,
    probing past a bucket's end is safe (a CSR-contiguous entry of
    another bucket that shares the tag fails the verify), and the round
    count comes from the ``pw`` shape."""
    probe = t["pw"].shape[0]
    meta = t["meta"]
    a = np.asarray(a)
    b = np.asarray(b)
    h = (_mix_np(a, b, _SALTS[int(meta[0])])
         & np.uint32(int(meta[1]))).astype(np.int64)
    u = t["ptr"][h].view(np.uint32)
    part = _split_np(a, b, _SALTS[int(meta[3])]) & (
        (np.uint32(1) << (u >> np.uint32(_OFF_BITS))) - np.uint32(1))
    base = (u & np.uint32(_OFF_MASK)).astype(np.int64) + part.astype(
        np.int64) * max(probe // 2, 1)
    tg, kb = t["tag"], t["key_b"]
    cap = kb.shape[0]
    qtag = _tag_np(a, b, _SALTS[int(meta[2])])
    # the tag reads a's low 32 bits: a wider query is no int32 key
    ok = (a >= 0) & (b >= 0) & (a <= np.iinfo(np.int32).max)
    seen = np.zeros(a.shape, bool)
    res_j = np.zeros(a.shape, np.int64)
    for i in range(probe):
        j = np.minimum(base + i, cap - 1)
        hit = tg[j] == qtag
        res_j = np.where(hit & ~seen, j, res_j)
        seen |= hit
    found = ok & seen & (kb[res_j] == b)
    vals = t.get("val")
    payload = vals[res_j] if vals is not None else res_j
    return np.where(found, payload, -1).astype(np.int32), found


_U32 = 0xFFFFFFFF


def lookup_one(t: Dict, a: int, b: int) -> int:
    """:func:`lookup_np` for one key, in plain integers (a numpy call
    costs more than the whole probe): the payload, or the entry's index,
    or -1.  The host knows where the bucket ends, so it scans from the
    key's window to there."""
    if a < 0 or b < 0:
        return -1
    meta = t["meta"]
    h = ((a ^ (b * 0x85EBCA77)) * 0x9E3779B1 + int(_SALTS[meta[0]])) & _U32
    h = ((h ^ (h >> 16)) * 0xC2B2AE3D) & _U32
    h = (h ^ (h >> 13)) & int(meta[1])
    f = (b + int(_SALTS[meta[2]])) & _U32
    f = ((f ^ (f >> 16)) * 0x85EBCA6B) & _U32
    f = ((f ^ (f >> 13)) * 0xC2B2AE35) & _U32
    qtag = (a ^ f ^ (f >> 16)) & _U32
    if qtag >= 1 << 31:
        qtag -= 1 << 32  # the column holds the tag as int32
    ptr = t["ptr"]
    u = int(ptr[h]) & _U32
    lo = u & _OFF_MASK
    if u > _OFF_MASK:  # a split bucket: the key's part, as _split_mix has it
        x = ((a * 0xCC9E2D51) ^ (b * 0x1B873593 + int(_SALTS[meta[3]]))) & _U32
        x = ((x ^ (x >> 15)) * 0x2C1B3C6D) & _U32
        x = ((x ^ (x >> 12)) * 0x297A2D39) & _U32
        x ^= x >> 15
        lo += (x & ((1 << (u >> _OFF_BITS)) - 1)) * max(t["pw"].shape[0] // 2, 1)
    tags = t["tag"][lo:int(ptr[h + 1]) & _OFF_MASK].tolist()
    if qtag not in tags:
        return -1
    j = lo + tags.index(qtag)
    if t["key_b"][j] != b:
        return -1
    vals = t.get("val")
    return int(vals[j]) if vals is not None else j


def repeated_keys(t: Dict) -> np.ndarray:
    """Entry positions (ascending) whose key an earlier entry of the same
    bucket holds too: a lookup finds the first of such a run alone, so a
    caller that stores distinct payloads under what may be equal keys
    (the vocabulary: two strings of one 62-bit hash) keeps these aside."""
    off, tag, key_b, depth = _offsets(t["ptr"]), t["tag"], t["key_b"], t["pw"].shape[0]
    pos, rank = _deep_entries(off, key_b, depth)
    found = [*_near_twins(off, tag, key_b, depth, True),
             pos[~_twins(rank, tag[pos], key_b[pos], pos)[0]]]
    return np.unique(np.concatenate(found))


def slots_in_use(t: Dict) -> int:
    """The slots the table's entries span: the entries and the empty slots
    between the parts of its split buckets (:func:`table_stats`
    ``pad_slots``); what lies behind is the pad to the capacity."""
    return int(t["ptr"][-1])


def lookup(t: Dict, a, b) -> Tuple:
    """Device probe: (val_or_index, found).  Negative queries never match.

    With ``val`` built, returns the payload of the first match; otherwise
    the entry index.  Static gather rounds, no data-dependent control
    flow, safe anywhere in a jitted program.  The round count is the
    table's ``pw`` shape, the probe its builder asked for, and the window
    starts at the bucket's offset plus the key's part of a split bucket,
    both decoded from the one ``ptr`` gather.  A round gathers the tag
    column alone; the key is verified once, at the first tag hit
    (:func:`lookup_gathers`).
    """
    import jax
    import jax.numpy as jnp

    probe = t["pw"].shape[0]
    meta = t["meta"]
    salts = jnp.asarray(_SALTS, np.uint32)

    def salt(i):
        return salts[jnp.clip(meta[i], 0, len(_SALTS) - 1)]

    h = (mix_device(a, b, salt(0)) & meta[1].astype(jnp.uint32)).astype(jnp.int32)
    u = jax.lax.bitcast_convert_type(t["ptr"][h], jnp.uint32)
    part = split_device(a, b, salt(3)) & (
        (jnp.uint32(1) << (u >> jnp.uint32(_OFF_BITS))) - jnp.uint32(1))
    base = (u & jnp.uint32(_OFF_MASK)).astype(jnp.int32) + part.astype(
        jnp.int32) * max(probe // 2, 1)
    cap = t["tag"].shape[0]
    qtag = tag_device(a, b, salt(2))
    seen = jnp.zeros(jnp.shape(a), bool)
    res_j = jnp.zeros(jnp.shape(a), jnp.int32)
    vals = t.get("val", None)
    # No bucket-length check: entries are CSR-contiguous, so probing past
    # the bucket's end reads entries of FOLLOWING buckets (or -1 padding).
    # A window begins in the key's own bucket, which holds no other key of
    # its tag, and an empty slot lies behind the keys of the windows that
    # cover it (the build invariants), so the first tag hit is the key's
    # entry whenever the key is present; a later entry or an empty slot
    # that shares the tag is met only by an absent key, and fails the
    # verify.  Dropping the check removes the ptr[h+1] gather and the
    # per-round bound test from the hottest gather site in the engine.
    for i in range(probe):
        j = jnp.clip(base + i, 0, cap - 1)
        hit = t["tag"][j] == qtag
        res_j = jnp.where(hit & ~seen, j, res_j)
        seen = seen | hit
    # the key, verified once: tag and key_b equal give key_a equal
    found = seen & (a >= 0) & (b >= 0) & (t["key_b"][res_j] == b)
    # one payload gather at the matched index instead of one per round:
    # each avoided gather is a real cost at arena-sized call sites
    payload = vals[res_j] if vals is not None else res_j
    return jnp.where(found, payload, -1), found


def lookup_gathers(t: Dict) -> int:
    """Element gathers one :func:`lookup` of ``t`` issues: ``ptr``, a tag
    a round, the verify, and the payload where the table has one (the
    lowered program is held to it in ``tests/test_hashtab.py``)."""
    return 1 + t["pw"].shape[-1] + 1 + ("val" in t)


class DeviceTable(Mapping):
    """A built table whose columns live on the device alone.  Once a
    table is shipped, only the device programs read it; what still reads
    it on the host (a fold's splice, a checkpoint, a re-ship) is rare and
    brings a column back with each access (``np.asarray``: a copy from a
    chip, a view on the CPU backend).  The host copies of a 150M-tuple
    graph's two tables are 5.4 GB beside the 7 GB the chip holds."""

    def __init__(self, columns: Dict):
        #: the device's arrays, for whoever ships the table again
        self.columns = dict(columns)

    def __getitem__(self, key):
        return np.asarray(self.columns[key])

    def __contains__(self, key) -> bool:
        return key in self.columns

    def __iter__(self):
        return iter(self.columns)

    def __len__(self) -> int:
        return len(self.columns)


#: the served tables' prefixes in the device-array dict: node table,
#: membership table, and the delta overlay's two
TABLES = ("nt", "mt", "ovt", "om")


def wave_gathers(arrays: Dict) -> Dict[str, int]:
    """:func:`lookup_gathers` of each served table, read off the shapes of
    the device-array dict a wave ran against (no fetch, no copy)."""
    return {
        p: 1 + arrays[p + "_pw"].shape[-1] + 1 + (p + "_val" in arrays)
        for p in TABLES if p + "_pw" in arrays
    }


def table_stats(t: Dict) -> Dict:
    """What ``/debug/projection`` shows of a table (host or device
    arrays; a mesh's stack gives one tag salt a shard, and the shards'
    split buckets and empty slots in all)."""
    meta = np.asarray(t["meta"])
    return {
        "rounds": int(t["pw"].shape[-1]),
        "lookup_gathers": lookup_gathers(t),
        "tag_salt": meta[..., 2].tolist(),
        "split_buckets": int(meta[..., 4].sum()),
        "split_level_max": int(meta[..., 5].max()),
        "pad_slots": int(meta[..., 6].sum()),
    }
