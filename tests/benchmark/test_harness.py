"""The harness end to end at a tiny size on the CPU: every cell
rehearses, a made-up cell added as files is found and run, and nothing is
printed under a device metric's name."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join("benchmarks", "run.py")


def run_harness(args, cwd=ROOT, script=RUN, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    proc = subprocess.run([sys.executable, script] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)
    lines = proc.stdout.strip().splitlines()
    return proc, (json.loads(lines[-1]) if lines else None)


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _check_line(line, cell_metrics):
    assert list(line)[:3] == ["correct", "attempted", "failed"]
    assert list(line)[-1] == "compared"
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device", "compared"}
    # a CPU run gives no device number under a device metric's name
    assert line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"
    assert set(line["rehearsal"]) <= set(cell_metrics)
    for number in line["compared"].values():
        assert set(number) == {"value", "limit"}


@pytest.mark.parametrize("cell", [w["name"] for w in _cells()["workloads"]])
def test_every_cell_rehearses(cell):
    bench = _cells()
    e2e = [m["name"] for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    proc, line = run_harness(["--workload", cell, "--seed",
                              str(2 ** 31 + 17), "--seconds", "2",
                              "--trace", "0", "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    _check_line(line, e2e)
    assert line["correct"] is True, proc.stderr[-2000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert sorted(line["rehearsal"]) == sorted(e2e)
    # each number compared is printed beside its limit at the end of
    # standard error
    tail = proc.stderr.strip().splitlines()[-len(line["compared"]):]
    assert all(t.startswith("compared ") and " limit " in t for t in tail)


@pytest.mark.parametrize("cell", ["opt13_fit", "opt13_serve_chat"])
def test_a_traced_rehearsal_reports_per_layer_names_only(cell):
    bench = _cells()
    if cell not in [w["name"] for w in bench["workloads"]]:
        pytest.skip("the benchmark has no cell %s" % cell)
    layer = [m["name"] for m in bench["per_layer"] if cell in m["workloads"]]
    proc, line = run_harness(["--workload", cell, "--seed", "5",
                              "--seconds", "3", "--trace", "1",
                              "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    _check_line(line, layer)
    assert line["correct"] is True
    assert "window_s" in line["device"] and "breakdown" in line
    assert len(line["breakdown"]["idle_gaps"]) <= 10
    # no share of a peak or of a roofline from a machine without the chip
    assert not [n for n in line["rehearsal"]
                if "mfu" in n or n.endswith("_roofline")]


def test_no_accelerator_is_an_error_and_prints_no_result():
    proc, line = run_harness(["--workload", _cells()["workloads"][0]["name"],
                              "--seed", "1", "--seconds", "1",
                              "--trace", "0"])
    assert proc.returncode != 0 and line is None
    assert "no accelerator" in proc.stderr


def _tree_digest(top):
    import hashlib
    out = {}
    for where, _dirs, files in os.walk(top):
        for name in files:
            path = os.path.join(where, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def _copy_benchmark(tmp_path, with_program):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    if with_program:
        os.symlink(os.path.join(ROOT, "mxnet_tpu"), tmp_path / "mxnet_tpu")


def test_the_benchmark_alone_runs_nothing(tmp_path):
    _copy_benchmark(tmp_path, with_program=False)
    proc, line = run_harness(["--workload", _cells()["workloads"][0]["name"],
                              "--seed", "1", "--seconds", "1", "--trace", "0",
                              "--rehearse"], cwd=tmp_path)
    assert proc.returncode != 0 and line is None


def test_a_made_up_cell_mix_and_metric_are_found_as_files(tmp_path):
    """What a later PR does: add files and entries to ``BENCHMARK.json``,
    edit no file under ``benchmarks``. Its cell reports a metric of its
    own and metrics the benchmark already has."""
    _copy_benchmark(tmp_path, with_program=True)
    before = _tree_digest(tmp_path / "benchmarks")
    b = tmp_path / "benchmarks"
    with open(b / "configs" / "opt-1.3b-l8.json") as f:
        tiny = json.load(f)
    tiny.update(tiny.pop("rehearse"))
    tiny["num_hidden_layers"] = 1
    (b / "configs" / "made-up.json").write_text(json.dumps(tiny))
    (b / "traffic" / "made_up_mix.json").write_text(json.dumps({
        "kind": "packed_tokens", "driver": "fit", "seq_len": 128,
        "rows_per_chip": 2, "kvstore": "local", "eval_metric": "ce",
        "check_steps": 3, "warm_steps": 1, "reference_block_rows": 1,
        "trace_seconds": 1}))
    (b / "limits" / "made_up_cell.json").write_text(json.dumps(
        {"loss_gap": 0.01, "grad_gap": 0.1, "change_gap": 0.1}))
    (b / "readers" / "made_up_reader.py").write_text(
        "def read(run, params):\n"
        "    return params['times'] * run.result['window']['steps']\n")
    metric = {"name": "made_up.steps", "unit": "steps", "better": "higher",
              "source": "program_counter", "layer": "device",
              "moves": "fit_tok_s", "workloads": ["made_up_cell"]}
    (b / "metrics" / "made_up.steps.json").write_text(json.dumps(
        {"reader": "made_up_reader", "params": {"times": 2}}))
    bench = _cells()
    bench["configs"].append({
        "name": "made-up", "source": "nowhere", "reduced": [],
        "file": "benchmarks/configs/made-up.json", "why": "a test"})
    bench["workloads"].append({
        "name": "made_up_cell", "config": "made-up",
        "traffic": "made_up_mix", "chips": 1, "why": "a test"})
    shared = ["data.wait_share.lm", "device.idle_share.lm",
              "step.device_ms.lm"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == "fit_tok_s" or m["name"] in shared:
            m["workloads"].append("made_up_cell")
    bench["per_layer"].append(metric)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    # no file that the benchmark had was edited, and its own test of
    # BENCHMARK.json against the files passes on the copy, each cell's
    # check of its own entries with it
    after = _tree_digest(b)
    assert {k: v for k, v in after.items() if k in before} == before
    shutil.copytree(os.path.join(ROOT, "tests", "benchmark"),
                    tmp_path / "tests" / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "PERF.md"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         os.path.join("tests", "benchmark", "test_spec.py")], cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:]

    # a traced rehearsal reads no device time, so of the shared metrics
    # it reports those that come from the program's spans
    for trace, want in (("0", ["fit_tok_s", "setup_s"]),
                        ("1", ["data.wait_share.lm", "made_up.steps"])):
        proc, line = run_harness(
            ["--workload", "made_up_cell", "--seed", "9", "--seconds", "1",
             "--trace", trace, "--rehearse"], cwd=tmp_path)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert line["correct"] is True, proc.stderr[-2000:]
        assert sorted(line["rehearsal"]) == want
    assert line["rehearsal"]["made_up.steps"]["value"] \
        == 2 * line["attempted"]
