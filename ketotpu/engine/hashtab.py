"""Bucketed hash tables: O(1) device-side key lookups over int32 pairs.

The device engines need two point lookups per frontier hop — node resolution
``(namespace, object, relation) -> node id`` and tuple existence
``(node, subject) -> bool`` (the reference's index probes,
`internal/persistence/sql/traverser.go:53-191` and
`relationtuples.go:249-261`).  Binary search works but compiles badly: the
unrolled log2(N) gather chain is the dominant XLA compile cost of the whole
check step and grows with the graph.  A bucketed hash table probes a fixed
``PROBE`` slots instead — compile cost is constant and runtime gathers drop
from O(log N) to O(1), which matters at the 10M-tuple target.

Layout (all host-built with vectorized numpy, no per-row Python):

* ``ptr``: int32[buckets+1] CSR over hash buckets,
* ``tag``: int32[capacity] ``key_a ^ f(key_b)``, entries grouped by bucket
  (``f`` a 32-bit mix with a salt of its own, :func:`_tag_np`),
* ``key_b``: int32[capacity] the key's second half, in the same order,
* ``val``: int32[capacity] payload (node ids), optional,
* ``meta``: int32[3] = (salt index, bucket mask, tag salt index) as
  device scalars.

The build hashes into a fixed 2n-bucket table, walking a salt schedule
for the flattest distribution; the achieved max-bucket depth is carried in
the table's ``pw`` array shape and lookups unroll exactly that many probe
rounds, so device probes never miss a present key.  Keys are non-negative;
-1 is the empty/pad sentinel and negative queries never match.

A probe round gathers ONE column: ``tag[j]`` against the query's tag.
After the rounds the key is verified once, at the first tag hit:
``tag[j] == qtag`` and ``key_b[j] == b`` together give ``key_a[j] == a``
(the tag is an XOR with ``a``), so ``key_a`` is stored nowhere and a lookup
of a ``P``-round table issues ``1 + P + 1`` gathers and one more for a
payload (:func:`lookup_gathers`), where two key columns cost ``1 + 2P``.
The first hit is the key's own entry because of a build invariant, not a
probability: **no two entries of one bucket with different keys share a
tag** (:func:`_tag_clash`).  ``build_table`` walks the tag salt when a
table breaks it, ``splice_table`` declines the edit; entries of following
buckets and pads may share the query's tag and fail the verify.
"""

from __future__ import annotations

import threading
from collections.abc import Mapping
from typing import Dict, Optional, Tuple

import numpy as np

from ketotpu.engine import parallel

_I32MAX = int(np.iinfo(np.int32).max)

PROBE = 8  # default probe depth; the build guarantees max bucket <= probe
PROBE_SHALLOW = 4  # for small side tables on hot probe paths (delta overlay)
# the big snapshot tables (node resolution + tuple membership) TARGET a
# shallower probe than the guaranteed default: fewer unrolled gather
# rounds in the hot BFS loop.  It is a target, not a guarantee: buckets
# are fixed at 2x entries (forcing max-bucket <= 4 at the 10M-entry scale
# needs ~32x-entry bucket arrays and dozens of multi-GB hash/bincount
# passes — measured as the dominant cost of a 10M projection — and every
# bucket is 4 bytes of ptr array in HBM).  A probe round is NOT free on
# the chip: the two tables' probes are 76 % of the 1024-row mixed wave's
# device time and 82 % of the singles' (PERF.md §5, traces of PR 30 and
# PR 32), at a deepest bucket of 8-9 for 10M keys; bounding the depth is
# ROADMAP.md queue 3 item 9.  The salt schedule picks the flattest
# distribution and the achieved depth rides in the table's `pw` array
# SHAPE, so jitted lookups unroll exactly that many rounds (shape changes
# recompile naturally).
SNAPSHOT_PROBE = 4

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


#: times the tag invariant refused a table: a ``build`` or an ``overlay``
#: (fixed-shape) build walked to another tag salt, a ``splice`` fell back
#: to a full build (scrape: ``keto_projection_tag_rejects_total{op}``)
TAG_REJECTS = {"build": 0, "splice": 0, "overlay": 0}
_TAG_REJECTS_LOCK = threading.Lock()  # builds run on the compactor's thread too


def _tag_reject(op: str) -> None:
    with _TAG_REJECTS_LOCK:
        TAG_REJECTS[op] += 1


def _tag_twins(ptr, tag, key_b, n: int, depth: int, same_key: bool):
    """Among the first ``n`` entries (no bucket deeper than ``depth``),
    the positions, a distance at a time, of those that share bucket and
    tag with the entry that distance before them, and its ``key_b`` too
    (``same_key``: the same key again) or not (two keys of one tag).  One
    compare pass a distance; equal 32-bit tags that close are rare (or
    duplicates), so the bucket test runs on a handful of positions."""
    for d in range(1, min(depth, n)):
        at = np.flatnonzero(tag[d:n] == tag[: n - d])
        at = at[(key_b[at] == key_b[at + d]) == same_key]
        if at.size:
            # the CSR position's bucket: the last ptr at or before it
            at = at[np.searchsorted(ptr, at, side="right")
                    == np.searchsorted(ptr, at + d, side="right")]
        if at.size:
            yield at + d


def _tag_clash(ptr, tag, key_b, n: int, depth: int) -> bool:
    """True when two of the first ``n`` entries sit in one bucket with one
    tag and different keys: the layout's one invariant, without which a
    lookup's first tag hit could be another key's entry.  Equal tags with
    equal ``key_b`` are the same key (duplicates are allowed)."""
    return next(_tag_twins(ptr, tag, key_b, n, depth, False), None) is not None


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
    a bucket is free (lookups scan the whole bucket) — so each part is
    sorted by the faster non-stable introsort.  On a multi-core host the
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
    # (and HBM) per table; the price is deeper buckets, one more tag
    # gather a round in every lookup (rounds 8-9 at 10M keys: the probes
    # hold three quarters of a wave, PERF.md §5).  Pair with a probe bound
    # the higher load factor can satisfy on the first salt, or the build
    # burns the whole salt schedule (a bincount+mix per salt) before
    # settling.
    lean: bool = False,
    probe: int = PROBE,
    fixed_shape: Optional[Tuple[int, int]] = None,
) -> Dict[str, np.ndarray]:
    """Vectorized build; returns the device-array dict for `lookup`.

    ``probe`` bounds the max bucket size the build accepts — lookups must
    then pass the same (or larger) probe depth.  Small hot-path side tables
    (the delta overlay) build shallow so their lookups unroll to fewer
    gather rounds.

    ``fixed_shape=(buckets, cap)`` pins the array shapes: callers that
    re-ship a table with changing content (the delta overlay) pass their
    size thresholds so every rebuild has identical shapes and the jitted
    consumer never recompiles.  If the content cannot satisfy the probe
    bound in the fixed bucket count (after the salt schedule) the build
    raises ``ValueError`` — the caller falls back to a full rebuild."""
    # keys keep their native dtype: the mix only reads the low 32 bits and
    # the entry columns store int32, so forcing int64 here was two full
    # copy passes per table at the 10M-entry scale
    key_a = np.asarray(key_a)
    key_b = np.asarray(key_b)
    n = key_a.shape[0]
    if fixed_shape is not None:
        buckets = fixed_shape[0]
        if n > fixed_shape[1]:
            raise ValueError(f"{n} entries exceed fixed cap {fixed_shape[1]}")
    else:
        buckets = _bucket_pow2(max(n if lean else 2 * n, 1), min_buckets)
    if n > _I32MAX or buckets > _I32MAX + 1:
        # ptr holds entry offsets and meta the bucket mask, both int32
        raise ValueError(
            f"{n} entries in {buckets} buckets pass a table's cap of "
            f"{_I32MAX} entries and {_I32MAX + 1} buckets")
    # at lean 10M-entry load factors the max bucket sits above the probe
    # TARGET for every salt (they all draw from the same distribution), so
    # walking the schedule is mix+bincount passes over multi-GB arrays
    # just to settle for salt 0's depth anyway — big tables take the first
    # salt's achieved depth immediately (lookups pay ~1 extra probe round:
    # one tag gather a lookup).  Small and fixed-shape tables keep the full
    # schedule (there a lucky salt genuinely changes the shape/fit).
    max_salts = (
        len(_SALTS) if n <= (1 << 20) or fixed_shape is not None else 1
    )
    salt_i = 0
    best = None  # flattest (max_bucket, salt_i, h, counts) seen
    probe_eff = probe
    h = np.empty(n, np.uint32)
    mask = np.uint32(buckets - 1)
    while True:
        def _hash(lo, hi, _s=_SALTS[salt_i]):
            h[lo:hi] = _mix_np(key_a[lo:hi], key_b[lo:hi], _s) & mask
        parallel.shard_apply(n, _hash)
        counts = _bincount(h, buckets)
        top = int(counts.max()) if n else 0
        if n == 0 or top <= probe:
            probe_eff = max(top, 1)
            break
        if best is None or top < best[0]:
            best = (top, salt_i, counts)
        if salt_i + 1 < max_salts:
            salt_i += 1
        elif fixed_shape is not None:
            raise ValueError(
                f"no salt fits {n} entries in {buckets} buckets at probe {probe}"
            )
        else:
            # salt walk done: settle for the flattest salt's actual bound —
            # lookups pay extra probe rounds instead of the build paying
            # bucket doubling (the 10M-scale projection cliff).  ``h`` is
            # recomputed when a non-final salt won (it is reused in place
            # between rounds).
            probe_eff, best_i, counts = best
            if best_i != salt_i:
                salt_i = best_i

                def _rehash(lo, hi, _s=_SALTS[salt_i]):
                    h[lo:hi] = _mix_np(key_a[lo:hi], key_b[lo:hi], _s) & mask

                parallel.shard_apply(n, _rehash)
            break
    depth = probe_eff  # the deepest bucket, before any pinning
    ptr = np.zeros(buckets + 1, np.int32)
    np.cumsum(counts, out=ptr[1:])
    del counts, best  # buckets-wide int64: 2 GB at 268M buckets
    if n <= 512 and fixed_shape is None:
        # pin the probe depth (== the pw array SHAPE) for small tables:
        # the achieved max-bucket is data-dependent (1 vs 2 vs 3 on a few
        # dozen keys), and a different pw shape is a different jitted
        # program — toy configs (tests, fuzz seeds) must share one
        # compile.  Costs at most probe-1 extra unrolled gather rounds on
        # tables this small; the 10M-scale adaptive depth is untouched.
        probe_eff = max(probe_eff, probe)
    order = _grouped_order(h, buckets) if n else np.zeros(0, np.int64)
    del h
    cap = fixed_shape[1] if fixed_shape is not None else _bucket_pow2(max(n, 1), 64)
    # empty + range fills instead of full(-1) + overwrite: one write pass
    # over the entry region instead of two (real at 10M+ rows), and the
    # gather through ``order`` shards across cores when the host has them
    tt = np.empty(cap, np.int32)
    tb = np.empty(cap, np.int32)
    tt[n:] = -1
    tb[n:] = -1
    # the tag column, and the walk of its salt: at random keys a bucket
    # holds two keys of one tag once in a thousand 10M-entry tables, so
    # the second pass is a guard; it rehashes nothing but the tags
    tag_i = 0
    while True:
        def _fill(lo, hi, _s=_SALTS[tag_i]):
            seg = order[lo:hi]
            b = key_b[seg]
            tb[lo:hi] = b
            tt[lo:hi] = _tag_np(key_a[seg], b, _s)

        parallel.shard_apply(n, _fill)
        if not _tag_clash(ptr, tt, tb, n, depth):
            break
        _tag_reject("overlay" if fixed_shape is not None else "build")
        tag_i += 1
        if tag_i == len(_SALTS):
            raise ValueError(
                f"no tag salt keeps {n} entries in {buckets} buckets apart"
            )
    out = {
        "ptr": ptr,
        "tag": tt,
        "key_b": tb,
        "meta": np.array([salt_i, buckets - 1, tag_i], np.int32),
        # probe depth as SHAPE: jitted lookups read it statically at trace
        # time, so a table that settled for a deeper bound (or achieved a
        # shallower one) unrolls exactly the right number of rounds with
        # no API threading.  Fixed-shape tables pin it to the requested
        # probe so re-shipped overlays never change the pytree.
        "pw": np.zeros(
            (probe if fixed_shape is not None else probe_eff,), np.int8
        ),
    }
    if val is not None:
        tv = np.empty(cap, np.int32)
        tv[:n] = np.asarray(val, np.int32)[order]
        tv[n:] = -1
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
    renumbers node ids).  The salt, bucket count, capacity and probe-depth
    (``pw``) shapes are all preserved, so a spliced table re-ships to the
    device without changing the jitted program's pytree.

    Returns None when the edit cannot keep that shape contract — more
    entries than capacity, a bucket growing past the recorded probe
    rounds, or a removal key that is not resident (inconsistent caller
    bookkeeping) — or when an insert would put two keys of one tag into
    a bucket (the tag salt is the table's, so it cannot be walked here).
    The caller falls back to a full ``build_table``.
    """
    salt_i = int(t["meta"][0])
    mask = np.uint32(int(t["meta"][1]))
    buckets = int(mask) + 1
    cap = len(t["key_b"])
    pw = t["pw"].shape[0]
    ptr = t["ptr"]
    n_old = int(ptr[-1])
    n_rm, n_add = len(rm_a), len(add_a)
    n_new = n_old - n_rm + n_add
    if n_new > cap:
        return None
    salt = _SALTS[salt_i]
    tag_salt = _SALTS[int(t["meta"][2])]
    tg, kb = t["tag"], t["key_b"]

    if n_rm:
        h_rm = (
            _mix_np(np.asarray(rm_a), np.asarray(rm_b), salt) & mask
        ).astype(np.int64)
        del_pos = np.empty(n_rm, np.int64)
        used: set = set()
        # a resident key is its tag and its second half (module docstring)
        rm_t_l = _tag_np(np.asarray(rm_a), np.asarray(rm_b), tag_salt).tolist()
        rm_b_l = np.asarray(rm_b).tolist()
        for i in range(n_rm):
            b = int(h_rm[i])
            found = -1
            for j in range(int(ptr[b]), int(ptr[b + 1])):
                if j not in used and tg[j] == rm_t_l[i] and kb[j] == rm_b_l[i]:
                    found = j
                    break
            if found < 0:
                return None
            used.add(found)
            del_pos[i] = found
        del_per_bucket = np.bincount(h_rm, minlength=buckets)
    else:
        del_pos = np.zeros(0, np.int64)
        del_per_bucket = np.zeros(buckets, np.int64)

    if n_add:
        h_add = (
            _mix_np(np.asarray(add_a), np.asarray(add_b), salt) & mask
        ).astype(np.int64)
        add_per_bucket = np.bincount(h_add, minlength=buckets)
    else:
        h_add = np.zeros(0, np.int64)
        add_per_bucket = np.zeros(buckets, np.int64)

    counts_new = np.diff(ptr.astype(np.int64)) - del_per_bucket + add_per_bucket
    if n_new and int(counts_new.max()) > pw:
        return None

    body_sel = np.ones(n_old, bool)
    body_sel[del_pos] = False
    cum_del = np.zeros(buckets + 1, np.int64)
    np.cumsum(del_per_bucket, out=cum_del[1:])
    ptr_mid = ptr.astype(np.int64) - cum_del
    # insert each add at its bucket's (post-delete) start; order within a
    # bucket is free — lookups scan the whole bucket
    order = np.argsort(h_add, kind="stable")
    ins_pos = ptr_mid[h_add[order]]
    add_b = np.asarray(add_b, np.int32)[order]
    t_body = np.insert(tg[:n_old][body_sel], ins_pos,
                       _tag_np(np.asarray(add_a)[order], add_b, tag_salt))
    b_body = np.insert(kb[:n_old][body_sel], ins_pos, add_b)
    cum_add = np.zeros(buckets + 1, np.int64)
    np.cumsum(add_per_bucket, out=cum_add[1:])
    ptr_new = (ptr_mid + cum_add).astype(np.int32)

    out_t = np.empty(cap, np.int32)
    out_t[:n_new] = t_body
    out_t[n_new:] = -1
    out_b = np.empty(cap, np.int32)
    out_b[:n_new] = b_body
    out_b[n_new:] = -1
    if n_add and _tag_clash(ptr_new, out_t, out_b, n_new, pw):
        _tag_reject("splice")
        return None
    out = {
        "ptr": ptr_new,
        "tag": out_t,
        "key_b": out_b,
        "meta": t["meta"],
        "pw": t["pw"],
    }
    tv = t.get("val")
    if tv is not None:
        v_body = tv[:n_old][body_sel]
        if val_remap is not None:
            v_body = val_remap[v_body]
        v_ins = (
            np.asarray(add_val, np.int32)[order]
            if add_val is not None else np.full(n_add, -1, np.int32)
        )
        v_body = np.insert(v_body, ins_pos, v_ins)
        out_v = np.empty(cap, np.int32)
        out_v[:n_new] = v_body
        out_v[n_new:] = -1
        out["val"] = out_v
    return out


def lookup_np(t: Dict, a: np.ndarray, b: np.ndarray) -> Tuple:
    """Host-side numpy mirror of :func:`lookup`: (val_or_index, found).

    One vectorized probe over a whole query column — the columnar batch
    decode uses this to encode request strings to vocabulary ids without
    a per-item Python dict walk.  Semantics match the device probe
    exactly: negative queries never match, the rounds compare tags and
    the key is verified once at the first tag hit, probing past a
    bucket's end is safe (a CSR-contiguous entry of another bucket that
    shares the tag fails the verify), and the round count comes from the
    ``pw`` shape."""
    probe = t["pw"].shape[0] if "pw" in t else PROBE
    salt = _SALTS[min(int(t["meta"][0]), len(_SALTS) - 1)]
    mask = np.uint32(int(t["meta"][1]))
    a = np.asarray(a)
    b = np.asarray(b)
    h = (_mix_np(a, b, salt) & mask).astype(np.int64)
    base = t["ptr"][h].astype(np.int64)
    tg, kb = t["tag"], t["key_b"]
    cap = kb.shape[0]
    qtag = _tag_np(a, b, _SALTS[min(int(t["meta"][2]), len(_SALTS) - 1)])
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
    or -1.  The host knows where the bucket ends, so it scans that."""
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
    lo = int(ptr[h])
    tags = t["tag"][lo:int(ptr[h + 1])].tolist()
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
    found = list(_tag_twins(t["ptr"], t["tag"], t["key_b"], int(t["ptr"][-1]),
                            t["pw"].shape[0], True))
    return np.unique(np.concatenate(found)) if found else np.zeros(0, np.int64)


def lookup(t: Dict, a, b, *, probe: int = PROBE) -> Tuple:
    """Device probe: (val_or_index, found).  Negative queries never match.

    With ``val`` built, returns the payload of the first match; otherwise
    the entry index.  Static gather rounds, no data-dependent control
    flow, safe anywhere in a jitted program.  The round count comes from
    the table's own ``pw`` shape when present (the build records the
    achieved max-bucket bound there); ``probe`` is the fallback for
    tables predating it.  A round gathers the tag column alone; the key
    is verified once, at the first tag hit (:func:`lookup_gathers`).
    """
    import jax.numpy as jnp

    if "pw" in t:
        probe = t["pw"].shape[0]
    salt = t["meta"][0]
    mask = t["meta"][1]
    salts = jnp.asarray(_SALTS, np.uint32)
    salt_v = salts[jnp.clip(salt, 0, len(_SALTS) - 1)]
    h = (mix_device(a, b, salt_v) & mask.astype(jnp.uint32)).astype(jnp.int32)
    base = t["ptr"][h]
    cap = t["tag"].shape[0]
    qtag = tag_device(a, b, salts[jnp.clip(t["meta"][2], 0, len(_SALTS) - 1)])
    seen = jnp.zeros(jnp.shape(a), bool)
    res_j = jnp.zeros(jnp.shape(a), jnp.int32)
    vals = t.get("val", None)
    # No bucket-length check: entries are CSR-contiguous, so probing past
    # the bucket's end reads entries of FOLLOWING buckets (or -1 padding).
    # The key's own bucket comes first and holds no other key of its tag
    # (the build invariant), so the first tag hit is the key's entry
    # whenever the key is present; a later entry or a pad that shares the
    # tag is met only by an absent key, and fails the verify.  Dropping
    # the check removes the ptr[h+1] gather and the per-round bound test
    # from the hottest gather site in the engine.
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
    arrays; a mesh's stack gives one tag salt a shard)."""
    tag_salt = np.asarray(t["meta"])[..., 2]
    return {
        "rounds": int(t["pw"].shape[-1]),
        "lookup_gathers": lookup_gathers(t),
        "tag_salt": tag_salt.tolist(),
    }
