"""The test harness of tests/conftest.py, run on small files in a fresh
pytest process: each test's compile counts in the JUnit file, the per-test
limit, and when a file starts with no compiled program."""

import os
import pathlib
import subprocess
import sys
import textwrap
import xml.etree.ElementTree as ET

import conftest

CONFTEST = pathlib.Path(conftest.__file__)
REPO = CONFTEST.parent.parent


def run_pytest(tmp_path, files, *, workers=0, **constants):
    """Run ``files`` (name -> source) under a copy of tests/conftest.py whose
    ``constants`` (name -> value) replace its own, in one fresh pytest
    process (serial, or with ``workers`` xdist workers); the JUnit file's
    cases in the order they ran."""
    source = CONFTEST.read_text() + "".join(
        f"\n{name} = {value!r}\n" for name, value in constants.items())
    (tmp_path / "conftest.py").write_text(source)
    for name, text in files.items():
        (tmp_path / name).write_text(textwrap.dedent(text))
    xml = tmp_path / "junit.xml"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(REPO), env.get("PYTHONPATH")) if p)
    xdist = ["-p", "xdist", "-n", str(workers)] if workers else [
        "-p", "no:xdist"]
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", str(tmp_path), "-q", *xdist,
         "-p", "no:cacheprovider", "-p", "no:randomly",
         f"--rootdir={tmp_path}", f"--junitxml={xml}"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    cases = [
        (case, {p.get("name"): p.get("value") for p in case.iter("property")})
        for case in ET.parse(xml).iter("testcase")
    ]
    return proc, cases


def test_each_test_case_carries_its_compiles(tmp_path):
    proc, cases = run_pytest(tmp_path, {"test_toy.py": """
    import jax
    import jax.numpy as jnp


    def test_compiles_one():
        jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.arange(8.0))


    def test_compiles_none():
        pass
    """})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    (one_case, one), (_, none) = cases
    assert one_case.get("name") == "test_compiles_one"
    assert int(one["compiles"]) >= 1
    assert float(one["compile_s"]) > 0
    assert one["compile_cache_hits"] == "0"
    assert none == {"compiles": "0", "compile_s": "0.0",
                    "compile_cache_hits": "0"}


TOY_FILE = """
import jax.numpy as jnp

from toy import triple


def test_first():
    triple(jnp.arange(8.0))


def test_again():
    triple(jnp.arange(8.0))
"""


def run_toy_files(tmp_path, clear_after):
    """Two files that run one program twice each; the compiles of each of
    their four tests, in the order they ran."""
    (tmp_path / "toy.py").write_text(
        "import jax\n\ntriple = jax.jit(lambda x: x * 3.0)\n")
    proc, cases = run_pytest(
        tmp_path, {"test_a.py": TOY_FILE, "test_b.py": TOY_FILE},
        CLEAR_AFTER_COMPILES=clear_after)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert [(case.get("classname"), case.get("name")) for case, _ in cases] == [
        ("test_a", "test_first"), ("test_a", "test_again"),
        ("test_b", "test_first"), ("test_b", "test_again")]
    return [int(p["compiles"]) for _, p in cases]


def test_each_file_starts_with_no_compiled_program(tmp_path):
    """Once the process has compiled its bound of programs, the next file
    compiles again what the one before it compiled, and not again within
    its own file."""
    a_first, a_again, b_first, b_again = run_toy_files(tmp_path, 1)
    assert a_first >= 1 and b_first == a_first
    assert a_again == b_again == 0


def test_a_file_reuses_the_programs_before_it_below_the_bound(tmp_path):
    a_first, a_again, b_first, b_again = run_toy_files(tmp_path, 10_000)
    assert a_first >= 1
    assert a_again == b_first == b_again == 0


def test_a_test_past_its_limit_fails_alone(tmp_path):
    """The limit fails the test that overran it, and the run goes on."""
    proc, cases = run_pytest(tmp_path, {"test_slow.py": """
    import time


    def test_overruns():
        time.sleep(60)


    def test_runs_after():
        pass
    """}, TEST_LIMIT_S=2)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    (overran, _), (after, _) = cases
    failure = overran.find("failure")
    assert failure is not None
    assert "past its limit of 2 s" in failure.get("message")
    assert after.get("name") == "test_runs_after"
    assert after.find("failure") is None
    assert float(overran.get("time")) < 30


def test_a_test_that_hangs_below_python_ends_its_worker_alone(tmp_path):
    """A test the limit's signal cannot reach (here: the signal is blocked)
    has its stack dumped and its worker ended past the grace; a new worker
    runs the test queued behind it."""
    proc, cases = run_pytest(tmp_path, {"test_hang.py": """
    import signal
    import time


    def test_hangs():
        signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
        time.sleep(120)


    def test_runs_after():
        pass
    """}, workers=1, TEST_LIMIT_S=1, NATIVE_GRACE_S=1)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    out = proc.stdout + proc.stderr
    assert "Timeout (0:00:02)!" in out
    assert "in test_hangs" in out
    by_name = {case.get("name"): case for case, _ in cases}
    crashed = by_name["test_hangs"].find("error")
    assert "crashed while running 'test_hang.py::test_hangs'" in crashed.text
    assert by_name["test_runs_after"].find("failure") is None
