"""The serving programs compile for the chip — asked of the TPU compiler
itself, for a described (not attached) ``v5e:2x2``, at no chip time.

Each test takes the arguments a live engine hands its jitted program (the
engine runs on the CPU; the call is intercepted before it executes), turns
them into shapes placed on the described devices, and compiles.  What the
compiler would refuse on the chip — memory, layout, a collective it cannot
partition — it refuses here.  Nothing runs, so a pass says nothing about
results or speed.

What is cut, and why: the compile cost of these programs follows their
operation count (levels x unrolled binary searches), not their buffer
sizes — the default fused wave with its retry lane and 12+24 general
levels takes ~500 s of one core for the v5e (PR 23, CHANGES.md), far too
long for a test.  So the frontier, arena and batch are the default serving
block's (driver/config.py), the levels are cut: no retry lane
(``fused_retry_lanes=0``, the setting chip_smoke.py runs), a read depth of
3 and 2 general levels.  The same functions trace either way; the full
default is compiled by hand before a chip run
(scripts/compile_for_v5e.py).

One file, one process: only one process at a time may load the TPU's
library, so the topology is described in a module-scoped fixture (never at
import) and every compile happens in the test's own process.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from ketotpu import compilewatch
from ketotpu.engine import expand_device as xd
from ketotpu.engine import fused as fdx
from ketotpu.engine.tpu import DeviceCheckEngine
from ketotpu.leopard import device as leodev
from ketotpu.parallel import MeshCheckEngine, graphshard
from ketotpu.utils.synth import build_synth, synth_queries_mixed

#: the default serving block (driver/config.py) ...
SERVING = dict(frontier=8192, arena=16384, max_batch=8192, retry_scale=4)
#: ... and the cuts the module docstring explains
CUT = dict(max_depth=3, gen_levels=2)
BATCH = 1024  # chip_smoke.py's REST batch


class _Captured(Exception):
    """Carries a jitted program's arguments out of the engine."""


def _capture(*args, **kwargs):
    raise _Captured(args, kwargs)


def _arguments_of(monkeypatch, module, name, call):
    """The (args, kwargs) ``call()`` hands to ``module.name``."""
    monkeypatch.setattr(module, name, _capture)
    with pytest.raises(_Captured) as caught:
        call()
    monkeypatch.undo()
    return caught.value.args


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means: cannot ask
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache():
    # a compile for an unattached chip writes cache entries that no process
    # here can read back, and warns on every later lookup
    with compilewatch.cache_off():
        yield


@pytest.fixture(scope="module")
def graph():
    return build_synth(
        n_users=400, n_groups=16, n_folders=200, n_docs=1200, seed=0
    )


@pytest.fixture(scope="module")
def engine(graph):
    eng = DeviceCheckEngine(
        graph.store, graph.manager, fused_dispatch=True,
        fused_retry_lanes=0, **SERVING, **CUT,
    )
    eng.snapshot()
    return eng


@pytest.fixture(scope="module")
def mesh_engine(graph):
    eng = MeshCheckEngine(
        graph.store, graph.manager, mesh_devices=4, **SERVING, **CUT,
    )
    eng.snapshot()
    return eng


def _on(sharding):
    """tree -> the same tree as shapes placed by ``sharding``."""
    def shape_of(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    return lambda tree: jax.tree_util.tree_map(shape_of, tree)


def _compiled(fn, args, static):
    compiled = fn.lower(*args, **static).compile()
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0
    return compiled


def test_fused_wave_compiles_for_v5e(topo, no_cache, engine, graph,
                                     monkeypatch):
    """The one-chip serving program: leopard probe + fast BFS + general
    algebra in one jit, with the schedules _dispatch_fused builds for a
    mixed batch (general rows present, so ``gen`` is in the program)."""
    queries = synth_queries_mixed(graph, BATCH, seed=7, general_frac=0.3)
    (g, qpack), static = _arguments_of(
        monkeypatch, fdx, "run_fused_wave",
        lambda: engine._dispatch(queries, 0),
    )
    static.pop("span")
    assert static["fast_sched"] is not None and static["gen"] is not None
    assert "leo_sets" in g  # tier 0 is in the program
    one_chip = _on(SingleDeviceSharding(topo.devices[0]))
    _compiled(fdx._run_wave, (one_chip(g), one_chip(qpack)), static)


def test_expand_compiles_for_v5e(topo, no_cache, engine, monkeypatch):
    """The batched Expand programs as batch_expand dispatches a depth-5
    request (its default fan-out and cap): the full rung and the first."""
    from ketotpu.api.types import SubjectSet

    roots = [SubjectSet("Folder", f"f{i}", "viewers") for i in range(8)]
    args, static = _arguments_of(
        monkeypatch, xd, "_run_expand",
        lambda: xd.run_expand(
            engine._expand_arrays(), engine.snapshot(), roots, 5,
            max_depth=5, rung="full",
        ),
    )
    assert static["schedule"] == (8, 128, 2048, 32768, 65536)
    first = xd.expand_schedule(8, 16, 5, xd.rung_cap("first", 8, 65536))
    assert first == (8, 128, 2048, 2048, 2048)
    one_chip = _on(SingleDeviceSharding(topo.devices[0]))
    for schedule in (static["schedule"], first):
        _compiled(xd._run_expand, one_chip(args), {"schedule": schedule})


def test_leopard_probe_compiles_for_v5e(topo, no_cache, engine):
    """The standalone closure probe of the unfused cascade."""
    dev = engine._leo_device
    assert dev is not None
    one_chip = _on(SingleDeviceSharding(topo.devices[0]))
    q = np.zeros(4096, np.int32)
    _compiled(
        leodev._probe,
        one_chip((dev["sets"], dev["elts"], dev["hops"], q, q)), {},
    )


def _mesh_arguments(topo, args, static):
    """Re-place captured mesh-program arguments on the described chips: the
    stacked graph sharded over the mesh axis, everything else replicated."""
    mesh = Mesh(np.array(topo.devices[:4]), (static["axis"],))
    stacked, *rest = args
    sharded = _on(NamedSharding(mesh, P(static["axis"])))
    replicated = _on(NamedSharding(mesh, P()))
    return (sharded(stacked), *replicated(tuple(rest))), dict(
        static, mesh=mesh
    )


def test_sharded_fast_run_compiles_for_four_chips(
    topo, no_cache, mesh_engine, graph, monkeypatch
):
    """engine.mesh_devices=4, pure-OR tier: shard_map over the stacked
    graph with all_to_all child routing and psum-merged verdict bits."""
    queries = synth_queries_mixed(graph, BATCH, seed=7, general_frac=0.0)
    args, static = _arguments_of(
        monkeypatch, graphshard, "_sharded_fast_run",
        lambda: mesh_engine._dispatch(queries, 0),
    )
    args, static = _mesh_arguments(topo, args, static)
    text = _compiled(graphshard._sharded_fast_run, args, static).as_text()
    assert "all-to-all" in text and "all-reduce" in text


def test_sharded_general_run_compiles_for_four_chips(
    topo, no_cache, mesh_engine, graph, monkeypatch
):
    """engine.mesh_devices=4, AND/NOT tier against the sharded stacks."""
    queries = synth_queries_mixed(graph, BATCH, seed=7, general_frac=1.0)
    snap = mesh_engine.snapshot()
    enc = mesh_engine._encode(snap, queries, 0)
    args, static = _arguments_of(
        monkeypatch, graphshard, "_sharded_general_run",
        lambda: mesh_engine._run_general(
            mesh_engine._stacked, enc, np.arange(len(queries))
        ),
    )
    args, static = _mesh_arguments(topo, args, static)
    compiled = _compiled(graphshard._sharded_general_run, args, static)
    assert "all-reduce" in compiled.as_text()
