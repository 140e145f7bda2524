"""Per-level device profiling of the fast path on the real chip.

Where does a 16,384-query batch's time go?
Times (a) end-to-end batch_check, (b) the fused dispatch alone, (c) each
level as its own dispatch at the schedule's sizes, (d) host-side encode,
(e) ablations (pack-only / expand-only) at the dominant level's shape.

Run on the machine with the chip, one process at a time:
    python scripts/prof_levels.py [batch]
"""

from __future__ import annotations

import sys
import time

import jax
import numpy as np

sys.path.insert(0, ".")

from ketotpu.engine import fastpath as fp  # noqa: E402
from ketotpu.engine.tpu import DeviceCheckEngine  # noqa: E402
from ketotpu.utils.synth import build_synth, synth_queries  # noqa: E402

BATCH = int(sys.argv[1]) if len(sys.argv) > 1 else 16384


def timeit(fn, n=3, warmup=1):
    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return min(ts)


def main():
    print(f"devices: {jax.devices()}  batch={BATCH}")
    graph = build_synth(
        n_users=2000, n_groups=100, n_folders=2000, n_docs=20000, seed=0
    )
    eng = DeviceCheckEngine(
        graph.store, graph.manager,
        frontier=6 * BATCH, arena=12 * BATCH, max_batch=BATCH,
    )
    t0 = time.perf_counter()
    eng.snapshot()
    print(f"snapshot+upload: {time.perf_counter() - t0:.3f}s")
    queries = synth_queries(graph, BATCH, seed=2)

    # host encode cost
    t0 = time.perf_counter()
    snap = eng.snapshot()
    enc = eng._encode(snap, queries, 0)
    print(f"encode ({BATCH} queries): {time.perf_counter() - t0 :.3f}s")
    err, general = eng._classify(snap, enc[0], enc[2])
    print(f"err={err.sum()} general={general.sum()}")

    # end-to-end
    e2e = timeit(lambda: eng.batch_check(queries))
    print(f"end-to-end batch_check: {e2e*1000:.1f} ms  "
          f"({BATCH/e2e:.0f} checks/s)")

    # fused dispatch alone (device program only, packed I/O)
    fast_active = ~(err | general)
    qpack = np.stack([*enc, fast_active.astype(np.int32)]).astype(np.int32)
    g = eng._device_arrays

    def fused():
        return fp.run_fast_packed(
            g, qpack, frontier=eng.frontier, arena=eng.arena,
            max_depth=eng.max_depth, max_width=eng.max_width,
        )

    t_fused = timeit(fused)
    print(f"fused dispatch: {t_fused*1000:.1f} ms")

    # per-level: run the unfused step at each level's schedule shape
    sched = fp.level_schedule(BATCH, eng.frontier, eng.arena, eng.max_depth)
    print(f"schedule: {sched}")
    s = fp.init_state(*enc, fast_active, frontier=sched[0][0])
    import jax.numpy as jnp

    s["f_depth"] = jnp.minimum(s["f_depth"], len(sched))
    states = [s]
    for i, (f, a) in enumerate(sched):
        nxt_f = sched[i + 1][0] if i + 1 < len(sched) else 1
        last = i == len(sched) - 1

        def level(s=s, a=a, nxt_f=nxt_f, last=last):
            children, q_found, q_over, q_dirty = fp.expand_phase(
                g, s, arena=a, max_width=eng.max_width, probe_only=last
            )
            nxt, q_over = fp.pack_phase(
                children, q_found, q_over, frontier=nxt_f,
                ns_dim=g["f_direct_ok"].shape[0], rel_dim=g["f_direct_ok"].shape[1],
            )
            return dict(nxt, q_found=q_found, q_over=q_over,
                        q_dirty=q_dirty, q_subj=s["q_subj"])

        jlevel = jax.jit(level)
        t_lvl = timeit(jlevel)
        s = jax.block_until_ready(jlevel())
        live = int(np.sum(np.asarray(s["f_qid"]) >= 0))
        found = int(np.sum(np.asarray(s["q_found"])))
        print(f"level {i}: f={f} a={a} -> {t_lvl*1000:7.1f} ms   "
              f"next-frontier live={live}  found={found}")
        states.append(s)

    # ablation at the dominant level (level 1): expand vs pack
    s1 = states[1]
    f1, a1 = sched[1]

    def expand_only():
        return fp.expand_phase(g, s1, arena=a1, max_width=eng.max_width)

    je = jax.jit(expand_only)
    print(f"level1 expand_phase only: {timeit(je)*1000:.1f} ms")

    children, q_found, q_over, q_dirty = jax.block_until_ready(je())

    def pack_only():
        return fp.pack_phase(
            children, q_found, q_over, frontier=sched[2][0],
            ns_dim=g["f_direct_ok"].shape[0],
            rel_dim=g["f_direct_ok"].shape[1],
        )

    print(f"level1 pack_phase only:   {timeit(jax.jit(pack_only))*1000:.1f} ms")


if __name__ == "__main__":
    main()
