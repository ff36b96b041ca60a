"""The scheduler's clock in every serving cell: ``server.step_period_ms``,
``server.host_cycle_ms`` and ``server.admit_stall_ms``
(``readers/server_counter_mean.py``) and ``server.late_dispatch_share``
(``readers/server_counter_share.py``), all read from counters that
``GenerativeServer`` keeps with no profile running."""
import json
import os
import types

import pytest

from test_harness import run_harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
METRICS = {"server.step_period_ms": "ms", "server.host_cycle_ms": "ms",
           "server.late_dispatch_share": "%", "server.admit_stall_ms": "ms"}
SERVING = ["opt13_serve_chat", "sarvam105_serve_reason",
           "sala_serve_longdoc_chat", "mimo_serve_longctx_chat"]


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def check_entries(bench):
    """The four metrics, found by name: the scheduler's counters, lower
    is better, moving ``tpot_p90_ms`` in every cell that reports it, in
    ``server.ahead_share``'s order."""
    tpot, = [m for m in bench["end_to_end"] if m["name"] == "tpot_p90_ms"]
    for name, unit in METRICS.items():
        entry, = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry["unit"] == unit and entry["better"] == "lower"
        assert entry["source"] == "program_counter"
        assert entry["layer"] == "GenerativeServer scheduler"
        assert entry["moves"] == "tpot_p90_ms"
        assert entry["workloads"][:4] == SERVING
        assert set(entry["workloads"]) <= set(tpot["workloads"])


def test_the_entries_are_the_schedulers_and_every_serving_cell_reports_them():
    check_entries(_bench())


def _read(metric, result):
    from benchmarks.lib import spec
    doc = spec.Cell(REPO, "opt13_serve_chat").metric_file(metric)
    reader = spec.load_module("readers", doc["reader"])
    logged = []
    run = types.SimpleNamespace(reduced=None, cell=None, peaks=None,
                                result=result, log=logged.append)
    return reader.read(run, doc["params"]), logged


COUNTERS = {"s_decode_steps": 500, "s_decode_steps_ahead": 480,
            "s_decode_periods": 400, "s_decode_period_us": 4_800_000,
            "s_decode_collect_wait_us": 2_000_000,
            "s_decode_dispatch_late": 40, "s_admit_stalls": 20,
            "s_admit_stall_us": 900_000, "s_tokens": 9000,
            "bench.tracer_x": 3}


@pytest.mark.parametrize("named", [True, False], ids=["named", "found"])
def test_the_means_and_the_share_of_the_clock(named):
    """Over the server's own counters, whether the result names the
    server or the run holds one server's counters only; the mean's log
    gives the count beside the value."""
    result = {"counters": dict(COUNTERS)}
    if named:
        result["server_name"] = "s"
    want = {"server.step_period_ms": 12.0, "server.host_cycle_ms": 7.0,
            "server.late_dispatch_share": 10.0,
            "server.admit_stall_ms": 45.0}
    logs = {}
    for metric, value in want.items():
        got, logs[metric] = _read(metric, result)
        assert got == pytest.approx(value), metric
    assert logs["server.step_period_ms"] == [
        "s: decode_period_us over 400 decode_periods, 12.0000"]
    assert logs["server.host_cycle_ms"] == [
        "s: decode_period_us less decode_collect_wait_us over 400 "
        "decode_periods, 7.0000"]
    assert logs["server.admit_stall_ms"] == [
        "s: admit_stall_us over 20 admit_stalls, 45.0000"]


def _without(*keys, **values):
    out = {k: v for k, v in COUNTERS.items() if k not in keys}
    out.update(values)
    return out


@pytest.mark.parametrize("metric", sorted(METRICS))
@pytest.mark.parametrize("counters", [
    {},
    _without("s_decode_periods", "s_decode_period_us",
             "s_decode_collect_wait_us", "s_decode_dispatch_late",
             "s_admit_stalls", "s_admit_stall_us"),
    _without(s_decode_periods=0, s_admit_stalls=0),
    _without("s_decode_period_us", "s_decode_collect_wait_us",
             "s_decode_dispatch_late", "s_admit_stall_us"),
    dict(COUNTERS, t_decode_steps=4),
], ids=["no_server", "no_counter", "zero_count", "counts_alone",
        "two_servers"])
def test_nothing_where_the_program_has_no_such_counter(metric, counters):
    """A program from before the clock has the steps and none of its
    counters; a count of 0, counts without their sums, or two servers
    with none named say nothing."""
    assert _read(metric, {"counters": counters})[0] is None


def test_a_traced_rehearsal_of_the_dense_cell_reads_them():
    """The dense cell's result names no server; the clock's counters are
    read from the one server's, and a rehearsal's steps go ahead and its
    later prompts join behind a step in flight."""
    proc, line = run_harness(["--workload", "opt13_serve_chat", "--seed",
                              "1", "--seconds", "3", "--trace", "1",
                              "--rehearse"], cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is True
    got = {name: line["rehearsal"][name]["value"] for name in METRICS}
    assert 0 < got["server.host_cycle_ms"] <= got["server.step_period_ms"]
    assert 0 <= got["server.late_dispatch_share"] <= 100
    assert got["server.admit_stall_ms"] > 0
