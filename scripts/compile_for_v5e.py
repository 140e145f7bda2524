"""Compile the serving programs at FULL levels for a described v5e:2x2.

No chip needed, and nothing runs: this asks the installed TPU compiler what
it would say on the chip — wall seconds (one host core) and
``memory_analysis()`` per program.  tests/test_chip_compile.py guards the
same programs with their levels cut to fit a test; this is the uncut
rehearsal to make before a chip call where a long compile or a four-chip
call is at stake (the default fused wave takes ~8 minutes here).

    python scripts/compile_for_v5e.py [--tuples N] [--batch Q] [--lanes L]
                                      [--what fused,mesh]

``--tuples`` sizes the synth graph whose array shapes the programs are
compiled for (compile cost follows levels, not sizes; memory follows both).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import chip_smoke  # noqa: E402
from ketotpu import compilewatch  # noqa: E402
from ketotpu.engine import fused as fdx  # noqa: E402
from ketotpu.engine.tpu import DeviceCheckEngine  # noqa: E402
from ketotpu.parallel import MeshCheckEngine, graphshard  # noqa: E402
from ketotpu.utils.synth import synth_queries_mixed  # noqa: E402

#: the daemon's default engine block (driver/config.py)
SERVING = dict(frontier=8192, arena=16384, max_batch=8192, retry_scale=4)


class _Captured(Exception):
    pass


def arguments_of(module, name, call):
    """The (args, kwargs) ``call()`` hands to the jitted ``module.name``."""
    def capture(*args, **kwargs):
        raise _Captured(args, kwargs)

    real = getattr(module, name)
    setattr(module, name, capture)
    try:
        call()
    except _Captured as c:
        return c.args
    finally:
        setattr(module, name, real)
    raise RuntimeError(f"{name} was not called")


def on(sharding):
    return lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def report(label, fn, args, static):
    t0 = time.perf_counter()
    lowered = fn.lower(*args, **static)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    print(f"{label}: lower {t1 - t0:.1f} s, compile "
          f"{time.perf_counter() - t1:.1f} s, {compiled.memory_analysis()}",
          flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tuples", type=int, default=200_000)
    ap.add_argument("--batch", type=int, default=chip_smoke.BATCH)
    ap.add_argument("--lanes", type=int, default=1,
                    help="engine.fused_retry_lanes (daemon default 1)")
    ap.add_argument("--what", default="fused,mesh")
    args = ap.parse_args()

    graph = chip_smoke.build_graph(args.tuples, 0)
    queries = synth_queries_mixed(graph, args.batch, seed=7)
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    with compilewatch.cache_off():
        if "fused" in args.what:
            eng = DeviceCheckEngine(
                graph.store, graph.manager, fused_dispatch=True,
                fused_retry_lanes=args.lanes, **SERVING,
            )
            eng.snapshot()
            (g, qpack), static = arguments_of(
                fdx, "run_fused_wave", lambda: eng._dispatch(queries, 0)
            )
            static.pop("span")
            one_chip = on(SingleDeviceSharding(topo.devices[0]))
            report(f"fused wave Q={qpack.shape[1]} lanes={args.lanes}",
                   fdx._run_wave, (one_chip(g), one_chip(qpack)), static)
        if "mesh" in args.what:
            eng = MeshCheckEngine(
                graph.store, graph.manager, mesh_devices=4, **SERVING
            )
            snap = eng.snapshot()

            def placed(captured):
                (stacked, *rest), static = captured
                mesh = Mesh(np.array(topo.devices[:4]), (static["axis"],))
                sharded = on(NamedSharding(mesh, P(static["axis"])))
                replicated = on(NamedSharding(mesh, P()))
                return ((sharded(stacked), *replicated(tuple(rest))),
                        dict(static, mesh=mesh))

            report("_sharded_fast_run", graphshard._sharded_fast_run,
                   *placed(arguments_of(
                       graphshard, "_sharded_fast_run",
                       lambda: eng._dispatch(queries, 0),
                   )))
            enc = eng._encode(snap, queries, 0)
            general = np.flatnonzero(
                np.array([q.relation == "edit" for q in queries])
            )
            report("_sharded_general_run", graphshard._sharded_general_run,
                   *placed(arguments_of(
                       graphshard, "_sharded_general_run",
                       lambda: eng._run_general(
                           eng._stacked, enc, general
                       ),
                   )))


if __name__ == "__main__":
    main()
