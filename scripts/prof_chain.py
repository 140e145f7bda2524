"""Fused single-program vs chained async per-level dispatches.

prof_levels.py times each level as its own synced dispatch.  Hypothesis
this script tests: chaining the levels as 5 separately jitted dispatches
(async, device-resident state, ONE final sync) beats the single fused
program.  (No reading of it on a locally attached chip exists yet.)

Also sweeps batch size and probe depth.
"""

from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")

from ketotpu.engine import fastpath as fp  # noqa: E402
from ketotpu.engine.tpu import DeviceCheckEngine  # noqa: E402
from ketotpu.utils.synth import build_synth, synth_queries  # noqa: E402


def timeit(fn, n=3, warmup=1):
    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return min(ts)


@functools.partial(
    jax.jit, static_argnames=("frontier", "arena", "nxt_frontier",
                              "max_width", "probe_only"))
def one_level(g, s, *, frontier, arena, nxt_frontier, max_width, probe_only):
    NS, R = g["f_direct_ok"].shape
    children, q_found, q_over, q_dirty = fp.expand_phase(
        g, s, arena=arena, max_width=max_width, probe_only=probe_only
    )
    nxt, q_over = fp.pack_phase(
        children, q_found, q_over, frontier=nxt_frontier, ns_dim=NS, rel_dim=R
    )
    return dict(nxt, q_found=q_found, q_over=q_over, q_dirty=q_dirty,
                q_subj=s["q_subj"])


@functools.partial(jax.jit, static_argnames=("frontier",))
def init_packed(qpack, *, frontier):
    return fp._init_state(
        qpack[0], qpack[1], qpack[2], qpack[3],
        jnp.minimum(qpack[4], 5), qpack[5].astype(bool),
        frontier=frontier,
    )


@jax.jit
def verdict(s):
    return (
        s["q_found"].astype(jnp.uint8)
        | (s["q_over"].astype(jnp.uint8) << 1)
        | (s["q_dirty"].astype(jnp.uint8) << 2)
    )


def chained(g, qpack, sched, max_width):
    s = init_packed(qpack, frontier=sched[0][0])
    for i, (f, a) in enumerate(sched):
        nxt_f = sched[i + 1][0] if i + 1 < len(sched) else 1
        s = one_level(
            g, s, frontier=f, arena=a, nxt_frontier=nxt_f,
            max_width=max_width, probe_only=(i == len(sched) - 1),
        )
    return verdict(s)


def main():
    print(f"devices: {jax.devices()}")
    graph = build_synth(
        n_users=2000, n_groups=100, n_folders=2000, n_docs=20000, seed=0
    )
    for batch in (4096, 16384):
        eng = DeviceCheckEngine(
            graph.store, graph.manager,
            frontier=6 * batch, arena=12 * batch, max_batch=batch,
        )
        eng.snapshot()
        queries = synth_queries(graph, batch, seed=2)
        snap = eng.snapshot()
        enc = eng._encode(snap, queries, 0)
        err, general = eng._classify(snap, enc[0], enc[2])
        fast_active = ~(err | general)
        qpack = np.stack([*enc, fast_active.astype(np.int32)]).astype(np.int32)
        g = eng._device_arrays
        sched = fp.level_schedule(batch, eng.frontier, eng.arena, eng.max_depth)

        t_fused = timeit(lambda: fp.run_fast_packed(
            g, qpack, frontier=eng.frontier, arena=eng.arena,
            max_depth=eng.max_depth, max_width=eng.max_width))
        t_chain = timeit(lambda: chained(g, qpack, sched, eng.max_width))
        # sanity: same verdicts
        vf = np.asarray(fp.run_fast_packed(
            g, qpack, frontier=eng.frontier, arena=eng.arena,
            max_depth=eng.max_depth, max_width=eng.max_width)[0])
        vc = np.asarray(chained(g, qpack, sched, eng.max_width))
        assert np.array_equal(vf, vc), "verdict mismatch"
        print(f"batch={batch}: fused={t_fused*1000:8.1f} ms   "
              f"chained={t_chain*1000:8.1f} ms   "
              f"(chained {batch/t_chain:.0f} checks/s)")

        # two batches in flight: dispatch both chains, sync both
        def two():
            v1 = chained(g, qpack, sched, eng.max_width)
            v2 = chained(g, qpack, sched, eng.max_width)
            return v1, v2

        t_two = timeit(two)
        print(f"  two chained batches in flight: {t_two*1000:8.1f} ms "
              f"({2*batch/t_two:.0f} checks/s)")


if __name__ == "__main__":
    main()
