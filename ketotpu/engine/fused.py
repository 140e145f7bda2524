"""Fused tiered dispatch: one compiled device program per wave.

The unfused cascade resolves a wave as host leopard probe ->
``fp.run_fast_packed`` + D2H fetch -> ``_run_general`` + second D2H
fetch -> optional width-escalation re-runs, each separated by a host
sync (engine/tpu.py).  Every one of those syncs stalls the host on the
device and the device on the host, and the three tiers cannot overlap.
(On the chip the fused wave leaves the device idle 0.09 % of a batch1k
window; the unfused cascade, which the mesh still runs, 7.9 %: ledger,
PR 33.)

This module compiles the whole cascade into ONE program:

* **tier 0 — leopard closure probe**: an in-program binary search over
  the already-shipped packed pair arrays (leopard/device.py
  ``probe_in_program``).  The host keeps the half of ``answer_checks``
  that needs dict state (taint/dirty sets, the delta pair dict, the
  rewrite test) and ships it as one int32 probe mode per row
  (closure.LM_*, ``prep_fused_checks``); the device finishes the clean
  rows with the exact base formula.  The split is bit-identical to the
  host path by construction.
* **tier 1 — fast BFS** (``fp._fused_body``): runs with the leopard
  answered-mask folded into its active mask, so closure-answered rows
  are dead weight inside the program instead of host-filtered between
  dispatches.  Width escalation happens as ``retry_lanes`` bounded
  in-program re-runs at the boosted schedule: the overflow tail
  re-walks at retry capacity without a host round-trip, found bits
  accumulate monotonically (a tier-1 IS can never be revoked).
* **tier 2 — general algebra** (``alg._general_body``): the AND/NOT
  rows are compacted in-program into ``gen_lanes`` lanes (the host
  sizes them by the rows that need the tier, with the unfused
  ``_run_general``'s half-octave rule) and run there, plus one boosted
  retry lane on the same lanes mirroring the unfused general overflow
  re-run; the lanes' bits scatter back to their wave rows.

Exactly ONE D2H fetch returns everything the collector needs: per-row
verdict codes AND per-tier attribution masks packed into one int32 bit
field, concatenated with the two occupancy vectors the adaptive
scheduler feeds on.  Layout of the returned int32[Q + F + G] array
(Q = padded wave rows, F = len(fast_sched) occupancy counts plus
``fp.folded_levels(fast_sched)`` rung codes, G = general occ length;
the general lanes are inside the program only: their bits come back
on the wave rows they were gathered from):

=====  ==========================================================
bits   per-row meaning (first Q entries)
=====  ==========================================================
0-1    general R_* verdict code (post-retry)
2      general over (post-retry, folds retry dirty/ERR)
3      general dirty (tier-1: overlay-stale state touched)
4      fast found (monotone across retry lanes)
5      fast fallback (dirty-unfound or still-over after retries)
6      leopard answered
7      leopard allowed
8      fast row entered a retry lane
9      general row entered the retry lane
=====  ==========================================================

Semantics are preserved bit-for-bit against the unfused cascade: the
three-valued MembershipUnknown routing under depth/width truncation is
the same formula on the same masks, over/dirty rows flow to the same
host oracle, and the per-tier masks keep ``note_tier`` tracing,
wave-ledger tier deltas and the leopard counters exact (counters
increment at collect time from the returned masks, so totals match the
unfused dispatch-time increments).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ketotpu import compilewatch, profiler
from ketotpu.engine import algebra as alg
from ketotpu.engine import fastpath as fp
from ketotpu.engine.optable import R_ERR
from ketotpu.leopard import device as leodev
from ketotpu.leopard.closure import (
    LM_ALLOW,
    LM_DENY,
    LM_HIT_ONLY,
    LM_PROBE,
)


def _wave_body(
    g: Dict[str, jax.Array],
    qpack,
    *,
    fast_sched: Tuple[Tuple[int, int], ...],
    retry_sched: Optional[Tuple[Tuple[int, int], ...]],
    retry_lanes: int,
    gen: Tuple,
    gen_retry: Optional[Tuple],
    gen_lanes: int,
    max_width: int,
    depth_slack: int,
):
    """The whole wave cascade, traced once.

    ``qpack``: int32[10, Q] — ns, obj, rel, subj, depth, fast-eligible,
    general, leopard probe mode (closure.LM_*), leopard probe set id,
    leopard probe element id.  The probe ids are -1 on rows the probe
    must miss (ineligible, unknown node/subject — consistent with the
    host path, where a -1 key can never match a non-negative pair).
    ``gen_lanes``: the root count ``gen`` and ``gen_retry`` were sized
    for, at least the wave's general rows and at most Q.

    Each tier's operations carry a ``jax.named_scope`` (``tier/leopard``,
    ``tier/fast``, ``tier/general``; levels and hash probes nest inside),
    which the profiler's trace keeps per operation: device time per tier
    is read from it (PERF.md), and no computation depends on it.

    Absent tiers compile OUT of the program: ``fast_sched=None`` drops
    tier 1 (and its retry lanes), ``gen=None`` drops tier 2 (and its
    retry lane), and a ``g`` without the leopard columns drops tier 0.
    The dispatcher gates on which row classes the wave actually holds —
    XLA compile cost is superlinear in module size, so an all-fast wave
    must not pay for a traced-but-masked general skeleton.
    """
    q_ns, q_obj, q_rel, q_subj, q_depth = (
        qpack[0], qpack[1], qpack[2], qpack[3], qpack[4]
    )
    fast_elig = qpack[5].astype(bool)
    gact = qpack[6].astype(bool)
    lmode = qpack[7]
    Q = qpack.shape[1]
    ones = jnp.ones((Q,), bool)
    zeros = jnp.zeros((Q,), bool)

    # -- tier 0: leopard closure probe -------------------------------------
    # every real row of a chunk shares one rest_depth and row 0 is always
    # real (padding is appended), so q_depth[0] is the scalar the host
    # formula uses
    with jax.named_scope("tier/leopard"):
        if "leo_sets" in g:
            hit, hop = leodev.probe_in_program(
                g["leo_sets"], g["leo_elts"], g["leo_hops"],
                qpack[8], qpack[9],
            )
            ok_depth = hop.astype(jnp.int32) + depth_slack <= q_depth[0]
        else:
            hit = zeros
            ok_depth = zeros
        leo_ans = jnp.select(
            [lmode == LM_PROBE, lmode == LM_ALLOW, lmode == LM_DENY,
             lmode == LM_HIT_ONLY],
            [ok_depth | ~hit, ones, ones, hit & ok_depth],
            zeros,
        )
        leo_allow = jnp.select(
            [lmode == LM_PROBE, lmode == LM_ALLOW, lmode == LM_HIT_ONLY],
            [(ok_depth | ~hit) & hit, ones, hit & ok_depth],
            zeros,
        )

    # -- tier 1: fast BFS, leopard answers done-masked ---------------------
    found = zeros
    fast_fb = zeros
    retried = zeros
    occ_tail = []
    if fast_sched is not None:
        with jax.named_scope("tier/fast"):
            fast_act = fast_elig & ~leo_ans
            fres, focc = fp._fused_body(
                g, q_ns, q_obj, q_rel, q_subj, q_depth, fast_act,
                schedule=fast_sched, max_width=max_width,
            )
            found1, dirty1 = fres.found, fres.dirty
            found = found1
            # in-program width escalation: the overflow tail re-walks at
            # retry capacity inside the same program (the unfused path pays
            # a host round-trip to gather/re-pad it); found is monotone, so
            # lanes only ever add verdicts
            unres = fast_act & fres.over & ~found1 & ~dirty1
            for _ in range(retry_lanes):
                retried = retried | unres
                with jax.named_scope("retry"):
                    rres, _rocc = fp._fused_body(
                        g, q_ns, q_obj, q_rel, q_subj, q_depth, unres,
                        schedule=retry_sched, max_width=max_width,
                    )
                found = found | (unres & rres.found)
                unres = unres & (rres.over | rres.dirty) & ~rres.found
            fast_fb = (fast_act & dirty1 & ~found1) | unres
            occ_tail.append(focc)

    # -- tier 2: general algebra on the rows that need it --------------------
    # every skeleton level, leaf buffer and hash probe of the tier scales
    # with its root count, so the general rows are gathered into gen_lanes
    # lanes (order kept, padding inactive) and their bits scattered back
    gen_bits = jnp.zeros((Q,), jnp.int32)
    if gen is not None:
        with jax.named_scope("tier/general"):
            (gidx,) = jnp.nonzero(gact, size=gen_lanes, fill_value=Q)
            lact = gidx < Q
            lq = jnp.take(qpack[:5], gidx, axis=1, mode="clip")
            gpack = jnp.concatenate([lq, lact.astype(jnp.int32)[None]])
            gcodes, gocc = alg._general_body(
                g, gpack, sizes=gen[0], fast_b=gen[1], fast_sched=gen[2],
                max_width=max_width, vcap=gen[3],
            )
            gcode = (gcodes & 3).astype(jnp.int32)
            gover = ((gcodes >> 2) & 1).astype(bool)
            gdirty = ((gcodes >> 3) & 1).astype(bool)
            gen_retried = jnp.zeros((gen_lanes,), bool)
            if gen_retry is not None:
                gunres = lact & gover & ~gdirty & (gcode != R_ERR)
                gen_retried = gunres
                rpack = jnp.concatenate([lq, gunres.astype(jnp.int32)[None]])
                with jax.named_scope("retry"):
                    rcodes, _rgocc = alg._general_body(
                        g, rpack, sizes=gen_retry[0], fast_b=gen_retry[1],
                        fast_sched=gen_retry[2], max_width=max_width,
                        vcap=gen_retry[3],
                    )
                rcode = (rcodes & 3).astype(jnp.int32)
                rover = ((rcodes >> 2) & 1).astype(bool)
                rdirty = ((rcodes >> 3) & 1).astype(bool)
                gcode = jnp.where(gunres, rcode, gcode)
                gover = jnp.where(
                    gunres, rover | rdirty | (rcode == R_ERR), gover
                )
            lane_bits = (
                gcode
                | (gover.astype(jnp.int32) << 2)
                | (gdirty.astype(jnp.int32) << 3)
                | (gen_retried.astype(jnp.int32) << 9)
            )
            # a general row that found no lane reads over (the oracle
            # answers it); padding lanes carry index Q and drop
            gen_bits = jnp.where(gact, 1 << 2, 0).at[gidx].set(
                lane_bits, mode="drop"
            )
            occ_tail.append(gocc)

    rows = (
        gen_bits
        | (found.astype(jnp.int32) << 4)
        | (fast_fb.astype(jnp.int32) << 5)
        | (leo_ans.astype(jnp.int32) << 6)
        | (leo_allow.astype(jnp.int32) << 7)
        | (retried.astype(jnp.int32) << 8)
    )
    return jnp.concatenate([rows, *occ_tail])


_run_wave = functools.partial(
    jax.jit,
    static_argnames=(
        "fast_sched", "retry_sched", "retry_lanes", "gen", "gen_retry",
        "gen_lanes", "max_width", "depth_slack",
    ),
)(_wave_body)


def run_fused_wave(
    g: Dict[str, jax.Array],
    qpack: np.ndarray,
    *,
    fast_sched: Tuple[Tuple[int, int], ...],
    retry_sched: Optional[Tuple[Tuple[int, int], ...]],
    retry_lanes: int,
    gen: Tuple,
    gen_retry: Optional[Tuple],
    gen_lanes: int = 0,
    max_width: int = 100,
    depth_slack: int = 2,
    span=profiler.null_span,
):
    """Dispatch one fused wave; returns the UNCOLLECTED int32 device array
    (the caller's single ``np.asarray`` is the wave's one D2H fetch).
    The dispatch's host wall time (trace/compile on a fresh shape, async
    enqueue after) is the engine span ``check_fused_dispatch``
    (``span``: the engine's ``_span``)."""
    Q = qpack.shape[1]
    with span("check_fused_dispatch", rows=Q), compilewatch.scope(
        "fused_wave",
        lambda: (
            f"Q={Q} GQ={gen_lanes} fast={fast_sched} "
            f"retry={retry_sched}x{retry_lanes} "
            f"gen={gen} genr={gen_retry} width={max_width}"
        ),
    ):
        out = _run_wave(
            g, qpack,
            fast_sched=fast_sched, retry_sched=retry_sched,
            retry_lanes=retry_lanes, gen=gen, gen_retry=gen_retry,
            gen_lanes=gen_lanes, max_width=max_width,
            depth_slack=depth_slack,
        )
    return out
