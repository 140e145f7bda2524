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
# compilewatch.place_cache): XLA:CPU's executable serializer crashes the
# process (SIGABRT or SIGSEGV in put_executable_and_time, SIGSEGV in
# get_executable_and_time's deserialize) on the shard_map/all_to_all mesh
# programs and, once a process holds many programs, on one-chip ones too
# (jax 0.9.0: one run that cached its programs in a directory its workers
# shared lost three of them), and a crash takes the whole worker with it.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

# Fused tiered dispatch defaults ON for serving (engine/fused.py), but a
# fused wave's one-program compile is several times a per-tier program's
# on XLA:CPU — across every daemon-booting test here that would blow the
# suite's compile budget (and raise the segfault-threshold program count).
# Tests exercise the unfused cascade unless they opt in explicitly; fused
# parity coverage lives in test_fused.py and the CI serve-northstar job.
os.environ.setdefault("KETO_ENGINE_FUSED_DISPATCH", "false")

import faulthandler  # noqa: E402
import importlib.util  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402

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


# -- a test's limit, its compiles ---------------------------------------------

#: seconds a test (setup, call and teardown) may take before it fails on its
#: own: twice the longest test seen in the suite.  A hang then costs its own
#: test, not the run's clock and every file queued behind it.  The limit is a
#: SIGALRM, which fails the test once the main thread runs Python again.
TEST_LIMIT_S = 600

#: seconds past the limit after which a test still running is taken to hang
#: where the SIGALRM never reaches (in XLA's compile, or a blocking native
#: wait): the process dumps every thread's stack and exits, and xdist starts
#: a new worker for the files queued behind it
NATIVE_GRACE_S = 60

#: programs a process may compile before its next file starts with none.
#: XLA:CPU's codegen segfaults in a process that holds enough of them
#: (above); a file's first test clears the caches past this many, and below
#: it a file reuses what the files before it on the same worker compiled.
#: One file's eager ops alone come to some 1,400 (test_fused.py).
CLEAR_AFTER_COMPILES = 1500

_compiles_at = pytest.StashKey()  # a test's compile counters at its setup
_last_file = pytest.StashKey()  # the file of the test set up last
_cleared_at = pytest.StashKey()  # the process's compiles at its last clear
_stderr = pytest.StashKey()  # a descriptor of stderr that no capture holds


def _over_time(signum, frame):
    pytest.fail(f"the test ran past its limit of {TEST_LIMIT_S} s",
                pytrace=True)


def pytest_configure(config):
    config.stash[_cleared_at] = 0
    config.stash[_stderr] = os.dup(sys.__stderr__.fileno())
    if threading.current_thread() is threading.main_thread():
        signal.signal(signal.SIGALRM, _over_time)


def pytest_unconfigure(config):
    if _stderr in config.stash:
        os.close(config.stash[_stderr])


# Hooks, not an autouse fixture: a function fixture starts after the module
# fixtures, whose compiles (and hangs) belong to the test that set them up.
@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    import jax

    from ketotpu import compilewatch

    config = item.config
    w = compilewatch.get()
    if item.path != config.stash.get(_last_file, None):
        config.stash[_last_file] = item.path
        if w.compiles_total - config.stash[_cleared_at] >= CLEAR_AFTER_COMPILES:
            config.stash[_cleared_at] = w.compiles_total
            jax.clear_caches()
    item.stash[_compiles_at] = (
        w.compiles_total, w.compile_seconds_total, w.cache_hits)
    if threading.current_thread() is threading.main_thread():
        signal.alarm(TEST_LIMIT_S)
        faulthandler.dump_traceback_later(
            TEST_LIMIT_S + NATIVE_GRACE_S, exit=True,
            file=config.stash[_stderr])
    yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item, nextitem):
    yield
    if threading.current_thread() is threading.main_thread():
        signal.alarm(0)
        faulthandler.cancel_dump_traceback_later()
    from ketotpu import compilewatch

    w = compilewatch.get()
    n0, s0, h0 = item.stash[_compiles_at]
    # the JUnit file (--junitxml) carries them on every test case
    item.user_properties += [
        ("compiles", w.compiles_total - n0),
        ("compile_s", round(w.compile_seconds_total - s0, 3)),
        ("compile_cache_hits", w.cache_hits - h0),
    ]
