"""The per-layer readers of the program's own spans: on hand-made lists
whose answers are plain, on the three stretches recorded on the chip
before the program put any annotation in a profile (each reader must
find nothing there and say so), and through a traced rehearsal of the
cells that report them."""
import glob
import json
import os
import types

import pytest

from benchmarks.lib import program_spans, trace
from benchmarks.lib.trace import Event
from benchmarks.readers import (idle_share, idle_under_spans, span_quantile,
                                span_self_ms, span_share)
from test_harness import ROOT, _cells, _check_line, run_harness

DEV = "/device:TPU:0"
OPS = trace.OPS_LINE
HOST = "/host:CPU"

SERVE_CALLS = {"spans": ["mx.gen_decode_step", "mx.gen_prefill"]}


def _run(events, result=None):
    logged = []
    run = types.SimpleNamespace(reduced=trace.Reduced.marked(events),
                                result=result, log=logged.append)
    return run, logged


def _serve_events():
    """Ten seconds marked. The scheduler's line holds two iterations: the
    first admits one request (a prefill) and runs a decode step, the
    second runs a step alone; a third iteration found nothing to decode.
    Another Python thread, whose line has the same name, is inside a span
    of its own all the while."""
    sched, other = "python3", "python3"
    return [
        Event(HOST, "bench", "bench.window", 0.0, 10.0),
        Event(HOST, sched, "mx.gen_iteration", 1.0, 3.0),
        Event(HOST, sched, "mx.gen_admit", 1.0, 1.2),
        Event(HOST, sched, "mx.gen_prefill", 1.1, 1.0),
        Event(HOST, sched, "mx.gen_decode_step", 2.3, 1.5),
        Event(HOST, sched, "mx.gen_decode_dispatch", 2.3, 0.1),
        Event(HOST, sched, "mx.gen_logits_fetch", 2.5, 1.3),
        Event(HOST, sched, "mx.gen_sample", 3.8, 0.1),
        Event(HOST, sched, "mx.gen_iteration", 5.0, 2.0),
        Event(HOST, sched, "mx.gen_admit", 5.0, 0.1),
        Event(HOST, sched, "mx.gen_decode_step", 5.2, 1.5),
        Event(HOST, sched, "mx.gen_sample", 6.7, 0.3),
        Event(HOST, sched, "mx.gen_iteration", 8.0, 0.5),
        Event(HOST, sched, "mx.gen_admit", 8.0, 0.4),
        # straddles the end of the mark: not a whole span of the stretch
        Event(HOST, sched, "mx.gen_iteration", 9.5, 1.0),
        # the chip: busy [1.2, 2.0], [2.4, 3.6], [5.3, 6.5]
        Event(DEV, OPS, "%fusion.1", 1.2, 0.8),
        Event(DEV, OPS, "%copy.2", 2.4, 1.2),
        Event(DEV, OPS, "%copy.2", 5.3, 1.2),
        Event(HOST, other, "$time sleep", 0.0, 10.0),
    ]


def test_spans_nest_by_containment_and_self_time_is_what_is_left():
    run, logged = _run(_serve_events())
    found = program_spans.stretch(run)
    assert program_spans.stretch(run) is found           # made once
    first, second, third = found.named("mx.gen_iteration")
    assert [c.name for c in first.children] == [
        "mx.gen_admit", "mx.gen_decode_step", "mx.gen_sample"]
    step = first.children[1]
    assert [c.name for c in step.children] == [
        "mx.gen_decode_dispatch", "mx.gen_logits_fetch"]
    assert step.self_seconds == pytest.approx(0.1)
    assert first.children[0].children[0].name == "mx.gen_prefill"
    assert first.self_seconds == pytest.approx(3.0 - 1.2 - 1.5 - 0.1)
    assert first.covered({"mx.gen_prefill"}) == pytest.approx(1.0)
    assert third.covered({"mx.gen_decode_step"}) == 0.0
    split = found.split()
    assert split["mx.gen_iteration"][0] == 3
    assert split["mx.gen_iteration"][1] == pytest.approx(5.5)
    assert split["mx.gen_sample"] == (2, pytest.approx(0.4),
                                      pytest.approx(0.4))
    # the whole split is printed once, whoever asks first
    assert len([m for m in logged if "program spans of" in m]) == 1
    assert any("mx.gen_logits_fetch" in m for m in logged)
    assert any("children cover" in m and "mx.gen_iteration" in m
               for m in logged)


def test_threads_that_share_a_lines_name_do_not_adopt_each_other():
    """A recorded stretch knows a line by its name, and every Python
    thread's line has the process's: a span of one thread that happens to
    hold another thread's spans in time is their parent only if it is the
    shortest that does, and a span that merely overlaps is never one."""
    ev = [Event(HOST, "bench", "bench.window", 0.0, 10.0),
          Event(HOST, "python3", "mx.fit_step", 1.0, 4.0),
          Event(HOST, "python3", "mx.fit_data_next", 3.0, 1.5),
          # the producer's thread: overlaps the step, contains nothing
          Event(HOST, "python3", "mx.prefetch_next", 2.0, 6.0),
          Event(HOST, "python3", "mx.io_batch_wait", 6.0, 1.0)]
    run, _logged = _run(ev)
    found = program_spans.stretch(run)
    (step,) = found.named("mx.fit_step")
    assert [c.name for c in step.children] == ["mx.fit_data_next"]
    (wait,) = found.named("mx.io_batch_wait")
    assert wait.parent.name == "mx.prefetch_next"
    assert found.named("mx.prefetch_next")[0].parent is None


def test_span_self_ms():
    run, _logged = _run(_serve_events())
    # an iteration's own cost: less the prefill and the decode step, and
    # only iterations that ran a step count
    params = {"span": "mx.gen_iteration", "holding": "mx.gen_decode_step",
              "minus": ["mx.gen_prefill", "mx.gen_decode_step"]}
    assert span_self_ms.read(run, params) == pytest.approx(
        1e3 * ((3.0 - 1.0 - 1.5) + (2.0 - 1.5)) / 2)
    assert span_self_ms.read(run, {"span": "mx.gen_sample"}) \
        == pytest.approx(200.0)
    assert span_self_ms.read(run, {"span": "mx.no_such"}) is None
    assert span_self_ms.read(
        run, dict(params, holding="mx.no_such")) is None


def test_span_share_is_the_union_over_the_stretch():
    run, _logged = _run(_serve_events())
    assert span_share.read(run, {"spans": ["mx.gen_sample"]}) \
        == pytest.approx(4.0)
    # nested names are counted once
    assert span_share.read(run, {"spans": [
        "mx.gen_decode_step", "mx.gen_logits_fetch"]}) == pytest.approx(30.0)
    assert span_share.read(run, {"spans": ["mx.no_such"]}) is None


def test_idle_in_and_between_calls_add_up_to_the_idle_share():
    run, _logged = _run(_serve_events())
    inside = idle_under_spans.read(run, dict(SERVE_CALLS, inside=True))
    between = idle_under_spans.read(run, dict(SERVE_CALLS, inside=False))
    # idle inside the prefill [1.1, 2.1]: .1 + .1; inside the steps
    # [2.3, 3.8] and [5.2, 6.7]: .1 + .2 and .1 + .2
    assert inside == pytest.approx(100 * 0.8 / 10.0)
    assert inside + between == pytest.approx(idle_share.read(run, {}))
    assert between == pytest.approx(100 * (10.0 - 3.2 - 0.8) / 10.0)
    # a chip with no named span in the stretch reads nothing, not nought
    assert idle_under_spans.read(
        run, {"spans": ["mx.no_such"], "inside": True}) is None


def test_idle_is_read_on_the_chip_that_idles_most():
    ev = _serve_events() + [Event("/device:TPU:1", OPS, "%fusion.1",
                                  0.0, 9.0)]
    run, _logged = _run(ev)
    inside = idle_under_spans.read(run, dict(SERVE_CALLS, inside=True))
    between = idle_under_spans.read(run, dict(SERVE_CALLS, inside=False))
    assert inside + between == pytest.approx(idle_share.read(run, {}))
    assert inside == pytest.approx(8.0)


def test_overlap_of_sorted_intervals():
    a = [(0.0, 1.0), (2.0, 5.0), (6.0, 7.0)]
    b = [(0.5, 2.5), (4.0, 6.5)]
    assert idle_under_spans.overlap_seconds(a, b) == pytest.approx(
        0.5 + 0.5 + 1.0 + 0.5)
    assert idle_under_spans.overlap_seconds(a, []) == 0.0


def test_without_a_device_line_the_idle_readers_stay_out():
    ev = [e for e in _serve_events() if not e.plane.startswith("/device:")]
    run, _logged = _run(ev)
    assert span_share.read(run, {"spans": ["mx.gen_sample"]}) is not None
    assert idle_under_spans.read(run, dict(SERVE_CALLS, inside=True)) is None


def test_span_quantile_reads_the_programs_own_records(monkeypatch):
    rec = types.SimpleNamespace
    rows = [rec(name="gen_queue_wait", t_start=100.0 + i, t_end=100.0 + i
                + 0.01 * (i + 1)) for i in range(11)]
    rows.append(rec(name="gen_queue_wait", t_start=50.0, t_end=59.0))
    rows.append(rec(name="gen_other", t_start=101.0, t_end=109.0))
    monkeypatch.setattr(program_spans, "records", lambda name: [
        r for r in rows if r.name == name])
    logged = []
    run = types.SimpleNamespace(
        log=logged.append,
        result={"window": {"t_open": 99.0, "t_close": 120.0}})
    params = {"span": "gen_queue_wait", "percentile": 90}
    # the eleven that began in the window: 10, 20 .. 110 ms
    assert span_quantile.read(run, params) == pytest.approx(100.0)
    assert "gen_queue_wait in the window: 11" in logged[0]
    run.result["window"] = {"t_open": 0.0, "t_close": 10.0}
    assert span_quantile.read(run, params) is None
    # a program that keeps no records gives nothing, and does not raise
    monkeypatch.setattr(program_spans, "records", lambda name: None)
    assert span_quantile.read(run, params) is None


def test_records_come_from_the_program_when_it_keeps_them():
    import mxnet_tpu as mx
    mx.profiler.set_span_listener(lambda *a: None)
    try:
        mx.profiler.record_span("bench_test.wait", 1.0, 1.5)
    finally:
        mx.profiler.set_span_listener(None)
    (row,) = program_spans.records("bench_test.wait")
    assert row.t_end - row.t_start == 0.5


def _new_metrics():
    """The metrics that this module's readers read, with their files'
    parameters: {name: (reader module, params, cells)}."""
    mine = {"span_self_ms": span_self_ms, "span_share": span_share,
            "idle_under_spans": idle_under_spans,
            "span_quantile": span_quantile}
    out = {}
    for m in _cells()["per_layer"]:
        with open(os.path.join(ROOT, "benchmarks", "metrics",
                               m["name"] + ".json")) as f:
            doc = json.load(f)
        if doc["reader"] in mine:
            out[m["name"]] = (mine[doc["reader"]], doc["params"],
                              m["workloads"])
    return out


def test_ten_metrics_on_four_readers():
    new = _new_metrics()
    assert len(new) == 10
    assert len({reader for reader, _p, _c in new.values()}) == 4


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    ROOT, "benchmarks", "data", "*.trace.json.gz"))), ids=os.path.basename)
def test_a_stretch_recorded_before_the_annotations_reads_nothing(path):
    """The recorded stretches hold no ``mx.*`` annotation, like a profile
    of a program older than they are: every new reader returns ``None``
    there, no number and no error."""
    run, logged = _run(trace.load_json(path),
                       result={"window": {"t_open": 0.0, "t_close": 0.0}})
    assert program_spans.stretch(run) is None
    for name, (reader, params, _cells_) in _new_metrics().items():
        assert reader.read(run, params) is None, name
    assert not logged
    # the accepted reader of the same stretch still reads its number
    assert idle_share.read(run, {}) is not None


SPAN_ONLY = {
    "opt13_serve_chat": ["server.queue_wait_p90_ms", "server.sample_ms",
                         "server.step_host_ms"],
    "opt13_fit": ["fit.host_ms.lm"],
    "resnet50_fit": ["data.batch_place_share.img",
                     "data.batch_wait_share.img", "fit.host_ms.img"],
}


@pytest.mark.parametrize("cell", sorted(SPAN_ONLY))
def test_a_traced_rehearsal_resolves_the_span_metrics(cell):
    """On the CPU the profile has the host's lines and no chip: the seven
    metrics read from spans alone resolve, under ``rehearsal``, and the
    three shares of the chip's idle time stay out."""
    bench = _cells()
    if cell not in [w["name"] for w in bench["workloads"]]:
        pytest.skip("the benchmark has no cell %s" % cell)
    layer = [m["name"] for m in bench["per_layer"] if cell in m["workloads"]]
    new = [n for n, (_r, _p, cells) in _new_metrics().items()
           if cell in cells]
    proc, line = run_harness(["--workload", cell, "--seed",
                              str(2 ** 31 + 29), "--seconds", "3",
                              "--trace", "1", "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    _check_line(line, layer)
    assert line["correct"] is True
    got = sorted(n for n in line["rehearsal"] if n in new)
    assert got == SPAN_ONLY[cell]
    assert sorted(set(new) - set(got)) == sorted(
        n for n in new if n.startswith("device.idle_"))
    assert all(line["rehearsal"][n]["value"] >= 0 for n in got)
    # the whole split is in the log, program spans by name
    assert "program spans of the" in proc.stderr
    for name in {"opt13_serve_chat": ("mx.gen_iteration",
                                      "mx.gen_logits_fetch"),
                 "opt13_fit": ("mx.fit_step", "mx.fit_data_next"),
                 "resnet50_fit": ("mx.io_batch_wait", "mx.io_batch_place",
                                  "mx.fit_callback")}[cell]:
        assert name in proc.stderr, name


def test_idle_time_goes_to_the_innermost_span_open_on_its_line():
    run, logged = _run(_serve_events())
    found = program_spans.stretch(run)
    by = idle_under_spans.idle_by_span(
        idle_under_spans._idle(run, run.reduced, found), found)
    # the first step [2.3, 3.8]: idle [2.3, 2.4] under the dispatch,
    # [3.6, 3.8] under the fetch; the second [5.2, 6.7] has no parts
    assert by["mx.gen_decode_dispatch"] == pytest.approx(0.1)
    assert by["mx.gen_logits_fetch"] == pytest.approx(0.2)
    assert by["mx.gen_decode_step"] == pytest.approx(0.1 + 0.2)
    assert by["mx.gen_prefill"] == pytest.approx(0.1 + 0.1)
    assert by["mx.gen_sample"] == pytest.approx(0.1 + 0.3)
    idle_under_spans.read(run, dict(SERVE_CALLS, inside=True))
    idle_under_spans.read(run, dict(SERVE_CALLS, inside=False))
    assert len([m for m in logged if m.startswith("chip idle")]) == 1
    assert any("mx.gen_logits_fetch" in m and "2.00 %" in m for m in logged)


LAUNCH = {"program": "jit_fn", "within_ms": 2.0,
          "spans": ["mx.gen_decode_dispatch", "mx.gen_prefill"]}


def _skewed_events(lead):
    """Three decode steps of 70 ms, 5 ms apart, and a 3 ms prefill before
    the third; the host fetches for 3 ms after a step ends and is between
    calls for 1 ms. The device's clock runs ``lead`` seconds ahead of the
    host's, so each execution is written that much earlier than it
    ran: 0.2 ms after its launcher opened, in truth."""
    mods = trace.MODULES_LINE
    ev = [Event(HOST, "bench", "bench.window", 0.0, 0.4)]
    t = 0.010
    for k in range(3):
        if k == 2:
            ev += [Event(HOST, "python3", "mx.gen_prefill", t, 0.004),
                   Event(DEV, mods, "jit_fn(7)", t + 0.0002 - lead, 0.003),
                   Event(DEV, OPS, "%fusion.9", t + 0.0002 - lead, 0.003)]
            t += 0.005
        ev += [Event(HOST, "python3", "mx.gen_decode_step", t, 0.0735),
               Event(HOST, "python3", "mx.gen_decode_dispatch", t, 0.001),
               Event(HOST, "python3", "mx.gen_logits_fetch", t + 0.001,
                     0.0725),
               Event(DEV, mods, "jit_fn(3)", t + 0.0002 - lead, 0.070),
               Event(DEV, OPS, "%copy.1", t + 0.0002 - lead, 0.070)]
        t += 0.0745
    return ev


@pytest.mark.parametrize("lead", [0.0, 0.0007])
def test_the_device_clock_is_set_back_by_what_causality_shows(lead):
    """An execution cannot begin before the span that launched it opens:
    where the profile says one did, the device's clock is ahead by at
    least that much, and the split is taken with it set right."""
    params = dict(SERVE_CALLS, inside=False, launch=LAUNCH)
    run, logged = _run(_skewed_events(lead))
    between = idle_under_spans.read(run, params)
    inside = idle_under_spans.read(run, dict(params, inside=True))
    found = program_spans.stretch(run)
    got = idle_under_spans.clock_lead(run.reduced, found, LAUNCH, DEV)
    # what shows is the lead less the 0.2 ms a launch truly takes
    assert got == pytest.approx(max(lead - 0.0002, 0.0), abs=1e-9)
    assert len([m for m in logged if "device clock set" in m]) == 1
    # the answer from the events themselves: idle outside the calls is
    # what neither the chip's work nor a call covers, with the chip's
    # work as early as causality leaves it (the 0.2 ms of a true launch
    # cannot be told from a lead)
    left = min(lead, 0.0002)
    ev = _skewed_events(left)
    busy = [(e.start, e.end) for e in ev if e.line == OPS]
    calls = [(e.start, e.end) for e in ev
             if e.name in ("mx.gen_decode_step", "mx.gen_prefill")]
    want_between = 0.4 - trace.union_seconds(busy + calls)
    want_idle = 0.4 - trace.union_seconds(busy)
    assert between * 0.4 / 100 == pytest.approx(want_between, abs=1e-9)
    assert (inside + between) * 0.4 / 100 == pytest.approx(want_idle,
                                                           abs=1e-9)
    # without the launch the same profile moves the lead, a step, from
    # between the calls into them
    plain, _logged = _run(_skewed_events(lead))
    raw = idle_under_spans.read(plain, dict(SERVE_CALLS, inside=False))
    if lead:
        assert raw < between - 100 * 3 * 0.0004 / 0.4
    else:
        assert raw == pytest.approx(between, abs=1e-9)
