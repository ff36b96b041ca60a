"""What decides ``correct`` has been shown to fail: the control in the
precision below the configuration's, and the harness driven with the
timed path broken underneath."""
import json
import os
import subprocess
import sys

import pytest

from test_harness import ROOT, _cells, run_harness

FAULTY = os.path.join("tests", "benchmark", "faulty_run.py")
CONTROL = os.path.join("benchmarks", "tools", "control.py")


def _has(cell):
    return cell in [w["name"] for w in _cells()["workloads"]]


@pytest.mark.parametrize("cell,fault", [
    ("opt13_fit", "state_unchanged"),
    ("opt13_fit", "half_batch"),
    ("resnet50_fit", "state_unchanged"),
    ("resnet50_fit", "half_batch"),
    ("opt13_serve_chat", "token_altered"),
])
def test_a_broken_timed_path_comes_out_not_correct(cell, fault):
    if not _has(cell):
        pytest.skip("the benchmark has no cell %s" % cell)
    proc, line = run_harness(
        [fault, "--workload", cell, "--seed", str(2 ** 31 + 5),
         "--seconds", "2", "--trace", "0"], script=FAULTY)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False
    over = [n for n, c in line["compared"].items()
            if c["value"] > c["limit"]]
    assert over, line["compared"]
    assert "OVER" in proc.stderr


def test_the_same_runner_without_a_fault_is_correct():
    proc, line = run_harness(
        ["none", "--workload", "opt13_fit", "--seed", str(2 ** 31 + 5),
         "--seconds", "1", "--trace", "0"], script=FAULTY)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is True


@pytest.mark.parametrize("cell", ["opt13_fit", "resnet50_fit",
                                  "opt13_serve_chat"])
def test_the_control_fails_on_every_seed(cell):
    """The reference (or the served tokens judged) in float8, the step
    below the bfloat16 the configurations compute in, at the rehearsal's
    size and against the rehearsal's limits."""
    if not _has(cell):
        pytest.skip("the benchmark has no cell %s" % cell)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, CONTROL, "--workload", cell, "--seeds", "21,22,23",
         "--seconds", "6", "--rehearse"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    assert [r["seed"] for r in rows] == [21, 22, 23]
    for row in rows:
        assert row["cases"]["control"]["passes"] is False, row
        if "half_batch" in row["cases"]:
            assert row["cases"]["half_batch"]["passes"] is False, row
        if "program" in row["cases"]:
            assert row["cases"]["program"]["passes"] is True, row
