"""``server.ahead_share``: the decode steps the scheduler dispatched while
the step before was still in flight, over all its decode steps, in every
serving cell (``readers/server_counter_share.py``)."""
import json
import os
import types

import pytest

from test_harness import run_harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
METRIC = "server.ahead_share"


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def check_entries(bench):
    """The metric, found by name: the scheduler's counter, moving
    ``tpot_p90_ms`` in every cell that reports it, in their order."""
    entry, = [m for m in bench["per_layer"] if m["name"] == METRIC]
    tpot, = [m for m in bench["end_to_end"] if m["name"] == "tpot_p90_ms"]
    assert entry["unit"] == "%" and entry["better"] == "higher"
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "GenerativeServer scheduler"
    assert entry["moves"] == "tpot_p90_ms"
    assert entry["workloads"][:4] == ["opt13_serve_chat",
                                      "sarvam105_serve_reason",
                                      "sala_serve_longdoc_chat",
                                      "mimo_serve_longctx_chat"]
    assert set(entry["workloads"]) <= set(tpot["workloads"])


def test_the_entry_is_the_schedulers_and_every_serving_cell_reports_it():
    check_entries(_bench())


def _run(result):
    return types.SimpleNamespace(reduced=None, cell=None, peaks=None,
                                 result=result)


def _read(result):
    from benchmarks.lib import spec
    from benchmarks.readers import server_counter_share as reader
    doc = spec.Cell(REPO, "opt13_serve_chat").metric_file(METRIC)
    assert doc["reader"] == "server_counter_share"
    return reader.read(_run(result), doc["params"])


@pytest.mark.parametrize("named", [True, False], ids=["named", "found"])
def test_the_share_of_steps_ahead(named):
    """Over the server's own counters, whether the result names the
    server or the run holds one server's counters only."""
    counters = {"s_decode_steps": 40, "s_decode_steps_ahead": 38,
                "s_tokens": 900, "bench.tracer_x": 3}
    result = {"counters": counters}
    if named:
        result["server_name"] = "s"
    assert _read(result) == pytest.approx(95.0)


@pytest.mark.parametrize("counters", [
    {},
    {"s_decode_steps": 40},
    {"s_decode_steps": 0, "s_decode_steps_ahead": 0},
    {"s_decode_steps": 40, "s_decode_steps_ahead": 38,
     "t_decode_steps": 4, "t_decode_steps_ahead": 0},
], ids=["no_server", "no_counter", "no_step", "two_servers"])
def test_nothing_where_the_program_has_no_such_counter(counters):
    """A program from before the counter has the steps and not the part;
    a run with no step or with two servers and none named says nothing."""
    assert _read({"counters": counters}) is None


def test_a_traced_rehearsal_of_the_dense_cell_reads_it():
    """The dense cell's result names no server; the share is read from
    the one server's counters, and steps of a rehearsal go ahead."""
    proc, line = run_harness(["--workload", "opt13_serve_chat", "--seed",
                              str(2 ** 31 + 3), "--seconds", "3",
                              "--trace", "1", "--rehearse"], cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is True
    assert 0 < line["rehearsal"][METRIC]["value"] <= 100
