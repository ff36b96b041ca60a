"""The reduction from a profiler trace to what the per-layer metrics
read: on a few hand-made events whose answers are plain, and on a short
stretch recorded on the chip and kept under ``benchmarks/data``."""
import glob
import os
import types

import pytest

from benchmarks.lib import trace
from benchmarks.lib.trace import Event
from benchmarks.readers import (idle_share, program_device_ms,
                                serve_program_ms, train_mfu)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEV = "/device:TPU:0"
OPS, MODS = trace.OPS_LINE, trace.MODULES_LINE
MARKS = ["bench.token.first", "bench.token.next"]


def _events():
    """Ten seconds marked; two executions of ``jit_step`` and three of
    ``jit_fn`` on one chip."""
    ev = [Event("/host:CPU", "main", "bench.window", 0.0, 10.0),
          # step 1: [1, 3], ops cover [1, 2] and [2.5, 3], overlapping
          Event(DEV, MODS, "jit_step(1)", 1.0, 2.0),
          Event(DEV, OPS, "%fusion.1 = f32[] fusion()", 1.0, 0.6),
          Event(DEV, OPS, "%all-reduce.7 = all-reduce()", 1.4, 0.6),
          Event(DEV, OPS, "%_fa_kernel.2", 2.5, 0.5),
          # step 2: [5, 6], busy throughout
          Event(DEV, MODS, "jit_step(1)", 5.0, 1.0),
          Event(DEV, OPS, "%fusion.1 = f32[] fusion()", 5.0, 1.0),
          # a prefill and two decode steps of a server
          Event(DEV, MODS, "jit_fn(2)", 7.0, 0.2),
          Event(DEV, OPS, "%copy.3", 7.0, 0.2),
          Event("/host:CPU", "gen", "bench.token.first", 7.25, 0.001),
          Event(DEV, MODS, "jit_fn(3)", 7.3, 0.1),
          Event(DEV, OPS, "%copy.4", 7.3, 0.05),
          Event("/host:CPU", "gen", "bench.token.next", 7.45, 0.001),
          Event("/host:CPU", "gen", "bench.token.next", 7.46, 0.001),
          Event(DEV, MODS, "jit_fn(3)", 7.5, 0.1),
          Event(DEV, OPS, "%copy.4", 7.5, 0.07),
          Event("/host:CPU", "gen", "bench.token.next", 7.65, 0.001),
          # an execution that straddles the end of the mark is left out
          Event(DEV, MODS, "jit_step(1)", 9.5, 1.0),
          Event(DEV, OPS, "%fusion.1 = f32[] fusion()", 9.5, 1.0),
          # what the host was doing
          Event("/host:CPU", "main", "bench.fit", 0.0, 10.0),
          Event("/host:CPU", "main", "$feed.py:12 next", 3.0, 2.0),
          Event("/host:CPU", "main", "$time sleep", 3.2, 0.5)]
    return ev


@pytest.fixture()
def red():
    return trace.Reduced.marked(_events())


def test_union_and_gaps():
    assert trace.union_seconds([(0, 1), (0.5, 2), (3, 4), (3.2, 3.4)]) == 3.0
    assert trace.union_seconds([]) == 0.0
    assert trace.gaps([(1, 2), (1.5, 3), (5, 6)], 0, 7) \
        == [(0, 1), (3, 5), (6, 7)]


def test_busy_union_and_idle_share(red):
    assert red.planes == [DEV] and red.window == (0.0, 10.0)
    # [1, 2] + [2.5, 3] + [5, 6] + .2 + .05 + .07 + the half second of the
    # straddling step that lies inside the mark
    busy = 1.0 + 0.5 + 1.0 + 0.2 + 0.05 + 0.07 + 0.5
    assert red.busy_seconds(DEV) == pytest.approx(busy)
    assert red.busy_mean_seconds() == pytest.approx(busy)
    assert red.idle_share() == pytest.approx(1 - busy / 10.0)
    run = types.SimpleNamespace(reduced=red)
    assert idle_share.read(run, {}) == pytest.approx(100 * (1 - busy / 10))
    assert idle_share.read(types.SimpleNamespace(reduced=None), {}) is None


def test_the_chip_that_idles_most_sets_the_share():
    ev = _events() + [Event("/device:TPU:1", OPS, "%fusion.1", 0.0, 9.0)]
    red = trace.Reduced.marked(ev)
    assert red.planes == [DEV, "/device:TPU:1"]
    assert red.idle_share() == pytest.approx(1 - 3.32 / 10.0)
    assert red.busy_mean_seconds() == pytest.approx((3.32 + 9.0) / 2)


def test_program_time_is_the_busy_union_inside_whole_executions(red):
    assert red.program_busy_seconds("jit_step") == pytest.approx([1.5, 1.0])
    run = types.SimpleNamespace(reduced=red)
    assert program_device_ms.read(run, {"program": "jit_step"}) \
        == pytest.approx(1250.0)
    assert program_device_ms.read(run, {"program": "jit_other"}) is None


def test_serving_programs_are_told_by_the_token_that_follows(red):
    run = types.SimpleNamespace(reduced=red)
    decode = {"program": "jit_fn", "followed_by": "bench.token.next",
              "marks": MARKS}
    prefill = dict(decode, followed_by="bench.token.first")
    assert serve_program_ms.seconds(run, decode) == pytest.approx([.05, .07])
    assert serve_program_ms.read(run, decode) == pytest.approx(60.0)
    assert serve_program_ms.read(run, prefill) == pytest.approx(200.0)
    assert serve_program_ms.read(
        types.SimpleNamespace(reduced=None), decode) is None


def test_kernel_time_by_name(red):
    assert red.op_seconds("_fa_(bwd_)?kernel") == pytest.approx(0.5)
    assert red.op_seconds_inside("fusion", "jit_step") == pytest.approx(1.6)
    assert trace.op_name("%fusion.123 = f32[8]{0} fusion(...)") == "fusion"
    assert trace.op_name("all-reduce.7") == "all-reduce"
    top = dict(red.top_ops(3))
    assert top["fusion"] == pytest.approx(2.6)


@pytest.mark.parametrize("skew", [-0.004, 0.0, 0.004])
def test_a_skewed_device_clock_does_not_swap_prefill_and_decode(skew):
    """The host marks a first token just after the prefill ends and sends
    the decode step off within a millisecond; the trace's two clocks
    agree no closer than that. A step whose start slips before that mark
    is still a step, and the prefill still a prefill."""
    ev = [Event("/host:CPU", "main", "bench.window", 0.0, 1.0),
          Event("/host:CPU", "gen", "bench.token.next", 0.099, 0.0001)]
    t = 0.1
    for k in range(3):
        # a prefill of 20 ms, its first token, then a step of 72 ms
        ev += [Event(DEV, MODS, "jit_fn(7)", t + skew, 0.020),
               Event(DEV, OPS, "%fusion.1", t + skew, 0.019),
               Event("/host:CPU", "gen", "bench.token.first", t + 0.0203,
                     0.0001),
               Event(DEV, MODS, "jit_fn(9)", t + 0.0208 + skew, 0.072),
               Event(DEV, OPS, "%copy.2", t + 0.0208 + skew, 0.070),
               Event("/host:CPU", "gen", "bench.token.next", t + 0.0935,
                     0.0001)]
        t += 0.1
    run = types.SimpleNamespace(reduced=trace.Reduced.marked(ev))
    decode = {"program": "jit_fn", "followed_by": "bench.token.next",
              "marks": MARKS}
    assert serve_program_ms.seconds(run, decode) \
        == pytest.approx([0.070] * 3)
    assert serve_program_ms.seconds(
        run, dict(decode, followed_by="bench.token.first")) \
        == pytest.approx([0.019] * 3)


def test_idle_gaps_go_to_the_innermost_host_span(red):
    gaps = dict(red.idle_gaps_by_host(10))
    # [3, 5] idle: its middle, 4.0, lies in feed.next but after the sleep
    assert gaps["next(feed.py)"] == pytest.approx(2.0)
    assert "$time sleep" not in gaps
    assert sum(gaps.values()) == pytest.approx(10.0 - 3.32)
    assert trace.host_name("$a/b/decode.py:490 decode_step") \
        == "decode_step(decode.py)"


def test_json_round_trip(tmp_path):
    path = str(tmp_path / "t.json")
    trace.dump_json(_events(), path)
    back = trace.load_json(path)
    assert [(e.plane, e.line, e.name, e.start, e.dur) for e in back] \
        == [(e.plane, e.line, e.name, e.start, e.dur) for e in _events()]


RECORDED = sorted(glob.glob(os.path.join(ROOT, "benchmarks", "data",
                                         "*.trace.json.gz")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_a_recorded_stretch_reduces_the_same_by_brute_force(path):
    """The busy union, the idle share and the program times of a stretch
    recorded on the chip, against a count on a microsecond grid."""
    events = trace.load_json(path)
    red = trace.Reduced.marked(events)
    assert red.planes and red.window_s > 0
    lo, hi = red.window
    plane = red.planes[0]
    n = int(round((hi - lo) * 1e6))
    grid = bytearray(n)
    for e in red.ops(plane):
        a = max(0, int(round((e.start - lo) * 1e6)))
        b = min(n, int(round((e.end - lo) * 1e6)))
        grid[a:b] = b"\x01" * max(0, b - a)
    busy = sum(grid) * 1e-6
    assert red.busy_seconds(plane) == pytest.approx(busy, rel=2e-3)
    assert 0 < red.busy_seconds(plane) <= red.window_s
    assert sum(v for _k, v in red.idle_gaps_by_host(10 ** 6)) \
        == pytest.approx(red.window_s - red.busy_seconds(plane), rel=1e-6)
    whole = [m for m in events if m.line == MODS and m.plane == plane]
    assert whole
    for secs, m in zip(red.program_busy_seconds("."), whole):
        a = int(round((m.start - lo) * 1e6))
        b = int(round((m.end - lo) * 1e6))
        assert secs == pytest.approx(sum(grid[a:b]) * 1e-6, abs=2e-5)
        assert secs <= m.dur * (1 + 1e-9)


def test_the_recorded_serving_stretch_tells_its_prefill_from_its_steps():
    """0.255 s of ``opt13_serve_chat`` on the v5e (my chip run, PR 24):
    a decode step of 72.0 ms, a prefill of 6.6 ms and, the sequence
    having joined, two steps of the next bucket at 73.5 ms. In this run
    the host marked the prefill's first token 0.16 ms before the next
    step's start on the device's clock; in its sister run the device's
    clock ran a quarter of a millisecond early and a rule by the start
    swapped the two."""
    path = os.path.join(ROOT, "benchmarks", "data",
                        "opt13_serve_chat.trace.json.gz")
    red = trace.Reduced.marked(trace.load_json(path))
    run = types.SimpleNamespace(reduced=red)
    decode = {"program": "jit_fn", "followed_by": "bench.token.next",
              "marks": MARKS}
    prefill = dict(decode, followed_by="bench.token.first")
    assert serve_program_ms.seconds(run, decode) \
        == pytest.approx([0.0720, 0.0735, 0.0735], abs=2e-4)
    assert serve_program_ms.seconds(run, prefill) \
        == pytest.approx([0.00664], abs=2e-5)
    # the same answer with the device's clock a millisecond either way
    for skew in (-1e-3, 1e-3):
        moved = [Event(e.plane, e.line, e.name,
                       e.start + (skew if e.plane.startswith("/device:")
                                  else 0.0), e.dur)
                 for e in red.events]
        other = types.SimpleNamespace(reduced=trace.Reduced.marked(moved))
        assert len(serve_program_ms.seconds(other, decode)) == 3
        assert serve_program_ms.read(other, prefill) \
            == pytest.approx(6.64, abs=0.02)
    assert 3 < idle_share.read(run, {}) < 30
    assert red.top_ops(1)[0][0] == "copy"


def test_the_recorded_training_step_reads_its_kernels_share():
    """0.2 s of ``opt13_fit`` on the v5e (my chip run, PR 24): one whole
    step of 149.2 ms, its flash kernels at 12.0 % of their roofline, as
    the traced runs on the chip read."""
    from benchmarks.lib import device, spec
    from benchmarks.readers import flash_roofline
    cell = spec.Cell(ROOT, "opt13_fit")
    red = trace.Reduced.marked(trace.load_json(os.path.join(
        ROOT, "benchmarks", "data", "opt13_fit.trace.json.gz")))
    run = types.SimpleNamespace(
        reduced=red, cell=cell, peaks=device.peaks_table(ROOT)["TPU v5 lite"],
        result={"window": {"rows": 2, "chips": 1}})
    step = cell.metric_file("step.device_ms.lm")["params"]
    assert program_device_ms.read(run, step) == pytest.approx(149.15, abs=0.05)
    flash = cell.metric_file("kernel.flash_roofline.lm")["params"]
    assert flash_roofline.read(run, flash) == pytest.approx(12.0, abs=0.1)
    # the whole step against the chip, on the same device time:
    # 3.23 GFLOP a token x 4096 tokens over 149.15 ms x 197 TFLOP/s
    run.result["window"]["units_per_step"] = 2 * 2048
    mfu = train_mfu.read(run, cell.metric_file("step.mfu.lm")["params"])
    assert mfu == pytest.approx(45.0, abs=0.2)
    run.peaks = None        # a rehearsal has no chip to hold it against
    assert flash_roofline.read(run, flash) is None
    assert train_mfu.read(run, step) is None


def test_the_recorded_image_step_reads_the_steps_own_share():
    """0.4 s of ``resnet50_fit`` on the v5e (my chip run, PR 24): the
    step's 108 ms of device time give 28 % of the peak, whatever share
    of the stretch the chip sat waiting for the JPEG path."""
    from benchmarks.lib import device, spec
    cell = spec.Cell(ROOT, "resnet50_fit")
    red = trace.Reduced.marked(trace.load_json(os.path.join(
        ROOT, "benchmarks", "data", "resnet50_fit.trace.json.gz")))
    run = types.SimpleNamespace(
        reduced=red, cell=cell, peaks=device.peaks_table(ROOT)["TPU v5 lite"],
        result={"window": {"rows": 256, "chips": 1, "units_per_step": 256}})
    params = cell.metric_file("step.mfu.img")["params"]
    ms = program_device_ms.read(run, params)
    assert ms == pytest.approx(108.5, abs=1.0)
    mfu = train_mfu.read(run, params)
    assert mfu == pytest.approx(100 * 23.1e9 * 256 / (ms * 1e-3 * 197e12),
                                rel=0.01)
    assert 25 < mfu < 30 and idle_share.read(run, {}) > 50
