"""HBM residency for the Leopard pairs + the device binary-search probe.

The packed ``(set_id << 32 | element_id)`` int64 array ships to the
accelerator next to the snapshot CSR (`engine/tpu.py` installs it right
after the base device arrays), and batched membership verdicts are a
single ``jnp.searchsorted`` over the sorted pairs — one binary search
per query instead of an iterative frontier walk.

Compile-variant discipline matches the rest of the engine: query blocks
are padded to power-of-two buckets (`tpu._bucket`) AND the shipped pair
arrays are padded to power-of-two buckets with a +inf key sentinel, so
the jit sees one variant per (pairs_bucket, query_bucket) pair — a
closure rebuild whose pair count lands in the same bucket reuses the
compiled probe.  (JIT-audit finding: before the pad, `pairs.shape[0]`
was a raw compile axis and every incremental rebuild recompiled the
probe ON THE SERVING PATH — the `leopard_probe` AFTER-WARM warning
class.)  Device probing is worth the dispatch overhead for large
batches; small batches stay on the host numpy path (`closure.py`), which
returns bit-identical verdicts.  A device fault raises: the engine
(`engine/tpu.py`) counts and logs it, then answers from the host path.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ketotpu import compilewatch

# below this many probes the host searchsorted wins against a device
# round-trip (dominated by dispatch latency, not the log2(pairs) search)
DEVICE_PROBE_MIN = 2048


#: pairs-column pad sentinel: sorts after every real id (set and element
#: ids are non-negative int32 well below the ceiling) and can never equal
#: one, keeping the binary-search hit test exact on padding.  The pairs
#: ship as TWO sorted int32 columns (set, element) rather than the host's
#: packed int64 keys: with jax's default x64-disabled config a device_put
#: int64 array silently truncates to int32, which both destroys the pad
#: sentinel (int64 max -> -1, sorted FIRST) and overflows the
#: ``set << 32 | element`` packing itself.
_PAIR_PAD = np.iinfo(np.int32).max


def _pair_bucket(n: int, floor: int = 1024) -> int:
    """Power-of-two pad size for the shipped pairs: the probe's compile
    signature then changes only when the closure doubles, not on every
    incremental rebuild."""
    b = floor
    while b < n:
        b <<= 1
    return b


def ship_pairs(index) -> Optional[dict]:
    """Device-put the closure pair columns (padded to a power-of-two
    bucket); None when the index is empty.  The host's sorted packed
    int64 keys split into two int32 columns with the same lexicographic
    order (the packing IS the lexicographic order of its halves), so a
    two-column binary search visits the same positions the host
    searchsorted does."""
    if index is None or len(index.elt_packed) == 0:
        return None
    n = len(index.elt_packed)
    cap = _pair_bucket(n)
    sets = np.full(cap, _PAIR_PAD, np.int32)
    elts = np.full(cap, _PAIR_PAD, np.int32)
    sets[:n] = (index.elt_packed >> 32).astype(np.int32)
    elts[:n] = (index.elt_packed & 0x7FFFFFFF).astype(np.int32)
    hops = np.zeros(cap, np.int32)
    hops[:n] = index.elt_hop
    return {
        "sets": jax.device_put(sets),
        "elts": jax.device_put(elts),
        "hops": jax.device_put(hops),
    }


def probe_in_program(sets, elts, hops, q_set, q_elt):
    """Traced (non-jitted) probe body: one lexicographic binary
    search per query over the two sorted int32 pair columns
    (equivalent to the host's searchsorted over the packed int64
    keys, which jax's default x64-disabled config cannot represent
    on device).  The fused wave cascade (engine/fused.py) inlines
    this as its tier-0 phase — the probe then compiles INTO the wave
    program instead of costing its own dispatch — and the standalone
    ``_probe`` below jits the same body for the unfused path, so
    both paths share one definition and stay bit-identical.  A query
    set id of -1 (ineligible row) can never match: real ids are
    non-negative and padding is ``_PAIR_PAD``.  The unrolled step
    count is derived from the (static) padded capacity, so the
    compiled search is exact for any occupancy."""
    cap = sets.shape[0]
    steps = max(int(cap).bit_length(), 1)
    with jax.named_scope("probe/pairs"):
        lo = jnp.zeros(q_set.shape, jnp.int32)
        hi = jnp.full(q_set.shape, cap, jnp.int32)
        for _ in range(steps):
            mid = (lo + hi) >> 1
            ms, me = sets[mid], elts[mid]
            less = (ms < q_set) | ((ms == q_set) & (me < q_elt))
            lo = jnp.where(less, mid + 1, lo)
            hi = jnp.where(less, hi, mid)
        idx = jnp.clip(lo, 0, cap - 1)
        hit = (sets[idx] == q_set) & (elts[idx] == q_elt)
        return hit, jnp.where(hit, hops[idx], 0)


_probe = jax.jit(probe_in_program)


def probe_pairs(
    dev: Optional[dict], keys: np.ndarray, pad_to: int
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Batched (hit, hop) via the device pairs; None => use host path.
    ``keys`` is the host's packed int64 array (-1 = must-miss row); the
    halves split into int32 columns for the device search."""
    if dev is None or len(keys) < DEVICE_PROBE_MIN:
        return None
    q_set = np.full(pad_to, -1, np.int32)
    q_elt = np.full(pad_to, -1, np.int32)
    q_set[: len(keys)] = (keys >> 32).astype(np.int32)
    q_elt[: len(keys)] = (keys & 0x7FFFFFFF).astype(np.int32)
    # a -1 key's high half is -1 (arithmetic shift), keeping the
    # must-miss contract: no real set id is negative
    q_elt[: len(keys)][keys < 0] = -1
    with compilewatch.scope(
        "leopard_probe",
        lambda: f"pairs={dev['sets'].shape[0]} pad={pad_to}",
    ):
        hit, hop = _probe(
            dev["sets"], dev["elts"], dev["hops"], q_set, q_elt
        )
    hit = np.asarray(hit)[: len(keys)]
    hop = np.asarray(hop)[: len(keys)]
    return hit, hop
