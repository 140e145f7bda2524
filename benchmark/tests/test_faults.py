"""A whole run with the timed path broken underneath has to come out not
correct.  Drives ``run.py --rehearsal`` (tiny graph, CPU; the harness's
look for a chip is what the switch skips) with a fault planted in the
server child where the answer is produced, and once without.  And the
control, put in the program's place on a sound run's sample, has to come
out not correct through the same verdict."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_cell(cell: str, fault: str, seconds: float, more=()) -> dict:
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", cell, "--seed", "77", "--seconds", str(seconds),
           "--trace", "0", "--rehearsal", *more]
    if fault:
        cmd += ["--fault", fault]
    done = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                          timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert list(result)[-1] == "compared"
    assert "compared wrong_answers" in done.stderr
    lines = [json.loads(line) for line in done.stdout.splitlines()[:-1]]
    result["earlier"] = {d["bench"]: d for d in lines if "bench" in d}
    return result


@pytest.mark.parametrize("cell,fault,seconds", [
    ("drive-1m.expand5", "prune_tree", 3),
    ("drive-10m.singles", "flip_verdict", 3),
    ("drive-10m.batch1k", "flip_verdict", 20),
])
def test_an_altered_answer_is_not_correct(cell, fault, seconds):
    broken = run_cell(cell, fault, seconds)
    assert broken["correct"] is False
    assert broken["compared"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("cell,seconds", [
    ("drive-1m.expand5", 3), ("drive-10m.singles", 3),
    ("drive-10m.batch1k", 20),
])
def test_the_sound_path_is_correct_and_the_control_is_not(cell, seconds):
    watch = ["--watch-stalls", "0.3"] if cell == "drive-1m.expand5" else []
    sound = run_cell(cell, "", seconds, more=["--control", *watch])
    assert sound["correct"] is True
    assert sound["compared"]["wrong_answers"]["value"] == 0
    assert sound["device"]["platform"] == "cpu"
    control = sound["earlier"]["control"]
    assert control["correct"] is False
    assert control["compared"]["wrong_answers"]["value"] > 0
    assert set(control["compared"]) == set(sound["compared"])
    if watch:  # Expand ends no engine phase: the watch sees a standstill
        with open(os.path.join(ROOT, "benchmark", "out", cell,
                               "child.err")) as f:
            log = f.read()
        assert "stall watch: no engine phase has ended" in log
        assert "most recent call first" in log
