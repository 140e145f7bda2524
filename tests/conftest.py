"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Must run before anything imports jax, hence the env mutation at module import
time (pytest imports conftest.py before collecting test modules).
"""

import os

# Force, don't setdefault: on a machine with a chip the ambient environment
# selects the TPU, and these tests need the virtual 8-device CPU mesh.  The
# chip is driven by chip_smoke.py, one process at a time, never by pytest.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_cpu_parallel_codegen_split_count" not in flags:
    # XLA:CPU's parallel LLVM codegen segfaults sporadically once a process
    # has compiled enough distinct programs (observed repeatedly in this
    # suite: SIGSEGV inside backend_compile_and_load, each program fine in
    # isolation).  Serializing codegen removes the raciness.
    flags = (flags + " --xla_cpu_parallel_codegen_split_count=1").strip()
os.environ["XLA_FLAGS"] = flags

# The persistent compilation cache stays off, also in the `serve` children the
# tests spawn (they inherit this and would otherwise place one,
# compilewatch.place_cache): XLA:CPU's executable serializer aborts (SIGABRT
# in put_executable_and_time) on the shard_map/all_to_all mesh programs of
# tests/test_parallel.py, and an abort takes the whole worker with it.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

# Fused tiered dispatch defaults ON for serving (engine/fused.py), but a
# fused wave's one-program compile is several times a per-tier program's
# on XLA:CPU — across every daemon-booting test here that would blow the
# suite's compile budget (and raise the segfault-threshold program count).
# Tests exercise the unfused cascade unless they opt in explicitly; fused
# parity coverage lives in test_fused.py and the CI serve-northstar job.
os.environ.setdefault("KETO_ENGINE_FUSED_DISPATCH", "false")

import importlib.util  # noqa: E402

if importlib.util.find_spec("xdist") is None:
    # pyproject's addopts carries the xdist flags (-n 4 --dist loadfile);
    # without the plugin installed pytest rejects them as unrecognized and
    # NOTHING can run.  Absorb them as no-ops so the suite degrades to a
    # single serial process (the codegen-split flag above is what actually
    # keeps that stable).
    def pytest_addoption(parser):
        group = parser.getgroup("xdist-fallback")
        # _addoption, not addoption: lowercase short options are reserved
        # in the public API (xdist registers -n the same way)
        group._addoption("-n", "--numprocesses", dest="numprocesses",
                         default=None, help="ignored (pytest-xdist absent)")
        group._addoption("--dist", dest="xdist_dist", default="no",
                         help="ignored (pytest-xdist absent)")
