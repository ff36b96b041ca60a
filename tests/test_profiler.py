"""Profiler, Monitor, visualization (reference tests:
tests/python/unittest/test_profiler.py + monitor usage in test_monitor.py)."""
import json
import os
import subprocess
import sys
import tempfile
import threading

import numpy as np
import pytest

import mxnet_tpu as mx


def _mlp():
    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    h = mx.sym.Activation(h, act_type="relu", name="relu1")
    h = mx.sym.FullyConnected(h, num_hidden=2, name="fc2")
    return mx.sym.SoftmaxOutput(h, name="softmax")


def test_profiler_records_ops_and_dumps_chrome_trace():
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "prof.json")
        mx.profiler.set_config(filename=path)
        mx.profiler.set_state("run")
        a = mx.nd.uniform(shape=(8, 8))
        b = mx.nd.dot(a, a)
        (b + 1).asnumpy()
        with mx.profiler.span("my_region", "region", rows=8):
            mx.nd.sum(b).asnumpy()
        mx.profiler.set_state("stop")
        out = mx.profiler.dump()
        assert out == path
        trace = json.load(open(path))
        names = [e["name"] for e in trace["traceEvents"]]
        assert "dot" in names
        assert "my_region" in names
        # a region is a span: the op it ran lies inside it, and its
        # attribute and id are in the event's args
        region = next(e for e in trace["traceEvents"]
                      if e["name"] == "my_region")
        assert region["cat"] == "region" and region["args"]["rows"] == 8
        assert region["args"]["id"] > 0
        inside = [e for e in trace["traceEvents"] if e["name"] == "sum"]
        assert inside and all(
            region["ts"] <= e["ts"]
            and e["ts"] + e["dur"] <= region["ts"] + region["dur"]
            for e in inside)
        # complete events carry real durations; lane-name metadata ("M")
        # and flow events ("s"/"t") are part of the format since mx.obs
        for e in trace["traceEvents"]:
            assert e["ph"] in ("X", "M", "s", "t", "f")
            if e["ph"] == "X":
                assert e["dur"] >= 0


def test_profiler_off_records_nothing():
    mx.profiler.set_state("stop")
    mx.nd.uniform(shape=(4, 4)).asnumpy()
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "p.json")
        mx.profiler.set_config(filename=path)
        mx.profiler.dump()
        assert json.load(open(path))["traceEvents"] == []


def test_monitor_collects_per_op_stats():
    sym = _mlp()
    ex = sym.simple_bind(ctx=mx.cpu(), data=(4, 6),
                         softmax_label=(4,))
    for name, arr in ex.arg_dict.items():
        if name not in ("data", "softmax_label"):
            arr[:] = np.random.RandomState(0).rand(*arr.shape)
    ex.arg_dict["data"][:] = np.random.RandomState(1).rand(4, 6)
    ex.arg_dict["softmax_label"][:] = np.array([0, 1, 0, 1], np.float32)

    mon = mx.mon.Monitor(interval=1, pattern=".*fc.*")
    mon.install(ex)
    mon.tic()
    ex.forward(is_train=True)
    stats = mon.toc()
    names = [k for _, k, _ in stats]
    assert any("fc1" in n for n in names)
    assert any("fc2" in n for n in names)
    assert not any("relu" in n for n in names)   # pattern filtered
    for _, _, v in stats:
        assert float(v) >= 0


def test_monitor_through_module_fit():
    """install_monitor has a real Monitor to receive now (VERDICT 5.1)."""
    rng = np.random.RandomState(0)
    x = rng.uniform(-1, 1, (40, 6)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=20, label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mon = mx.mon.Monitor(interval=2)
    mod.fit(it, optimizer="sgd", initializer=mx.init.Xavier(),
            optimizer_params={"learning_rate": 0.1}, num_epoch=1,
            monitor=mon)
    assert mon.step > 0


def test_print_summary_counts_params(capsys):
    sym = _mlp()
    total = mx.viz.print_summary(sym, shape={"data": (4, 6)})
    # fc1: 6*8+8, fc2: 8*2+2
    assert total == 6 * 8 + 8 + 8 * 2 + 2
    out = capsys.readouterr().out
    assert "fc1" in out and "Total params" in out


def test_plot_network_builds_digraph():
    try:
        import graphviz  # noqa: F401
    except ImportError:
        import pytest
        pytest.skip("graphviz not installed")
    dot = mx.viz.plot_network(_mlp(), shape={"data": (4, 6)})
    src = dot.source
    assert "fc1" in src and "softmax" in src


# ------------------------------------------------------- the span record

@pytest.fixture
def listener():
    """Spans live through a listener alone, as in a benchmark's traced
    run: the profiler stays stopped and MXNET_TPU_OBS off."""
    got = []
    mx.profiler.set_span_listener(lambda *args: got.append(args))
    try:
        yield got
    finally:
        mx.profiler.set_span_listener(None)


def _mine(prefix):
    return {r.name: r for r in mx.profiler.spans()
            if r.name.startswith(prefix)}


def test_span_ids_are_unique_and_parent_is_the_enclosing_span(listener):
    seen = {}

    def other_thread():
        with mx.profiler.span("t1.other") as s:
            seen["other"] = s.id

    with mx.profiler.span("t1.outer") as outer:
        with mx.profiler.span("t1.inner") as inner:
            th = threading.Thread(target=other_thread)
            th.start()
            th.join(timeout=30)
            assert not th.is_alive()
        with mx.profiler.span("t1.second"):
            pass
        mx.profiler.record_span("t1.late", 1.0, 2.0)
    with mx.profiler.span("t1.after"):
        pass
    rec = _mine("t1.")
    assert set(rec) == {"t1.outer", "t1.inner", "t1.other", "t1.second",
                        "t1.late", "t1.after"}
    assert len({r.id for r in rec.values()}) == len(rec)
    assert rec["t1.outer"].id == outer.id and rec["t1.inner"].id == inner.id
    assert rec["t1.outer"].parent is None
    assert rec["t1.inner"].parent == outer.id
    assert rec["t1.second"].parent == outer.id     # the stack unwound
    # another thread's span has no parent here, whatever is open there
    assert rec["t1.other"].parent is None
    assert rec["t1.other"].thread != rec["t1.outer"].thread
    # an after-the-fact record may have begun before what is open now
    assert rec["t1.late"].parent is None
    assert rec["t1.after"].parent is None
    o, i = rec["t1.outer"], rec["t1.inner"]
    assert o.t_start <= i.t_start <= i.t_end <= o.t_end


def test_an_exception_unwinds_the_parent_stack(listener):
    with pytest.raises(ValueError):
        with mx.profiler.span("t2.outer"):
            with mx.profiler.span("t2.inner"):
                raise ValueError("x")
    with mx.profiler.span("t2.next"):
        pass
    rec = _mine("t2.")
    assert rec["t2.inner"].parent == rec["t2.outer"].id
    assert rec["t2.next"].parent is None


def test_attributes_and_flow_reach_spans_and_dump(tmp_path):
    mx.config.set("MXNET_TPU_OBS", 1)
    try:
        with mx.profiler.span("t3.outer", "serve", flow=41, bucket=128,
                              active=3) as outer:
            with mx.profiler.span("t3.inner"):
                pass
        mx.profiler.record_span("t3.late", 1.0, 2.0, flow=41, waited=7)
    finally:
        mx.config.set("MXNET_TPU_OBS", 0)
        mx.config.reset("MXNET_TPU_OBS")
    rec = _mine("t3.")
    assert rec["t3.outer"].attrs == {"bucket": 128, "active": 3}
    assert rec["t3.outer"].flow == 41 and rec["t3.late"].flow == 41
    assert rec["t3.outer"].category == "serve"
    assert rec["t3.late"].attrs == {"waited": 7}
    assert rec["t3.inner"].attrs == {} and rec["t3.inner"].flow is None
    path = str(tmp_path / "t3.json")
    mx.profiler.set_config(filename=path)
    mx.profiler.dump()
    with open(path) as f:
        events = {e["name"]: e for e in json.load(f)["traceEvents"]
                  if e["ph"] == "X"}
    assert events["t3.outer"]["args"] == {
        "bucket": 128, "active": 3, "flow": 41, "id": outer.id}
    assert events["t3.inner"]["args"] == {
        "id": rec["t3.inner"].id, "parent": outer.id}
    assert events["t3.late"]["args"]["waited"] == 7


def test_listener_still_gets_five_arguments_and_spans_fill(listener):
    assert mx.profiler.state() == "stop"
    assert mx.profiler.spans_enabled()
    with mx.profiler.counter_delta() as d:
        with mx.profiler.span("t4.region", "io", flow=9, lane="place",
                              rows=2):
            pass
    assert d.get("obs_spans") == 1
    (args,) = [a for a in listener if a[0] == "t4.region"]
    name, t0, t1, category, lane = args         # five, positional
    assert (category, lane) == ("io", "place") and t1 >= t0
    rec = _mine("t4.")["t4.region"]
    assert (rec.t_start, rec.t_end) == (t0, t1) and rec.attrs == {"rows": 2}
    # a listener alone grows no chrome-trace list
    with tempfile.TemporaryDirectory() as td:
        mx.profiler.set_config(filename=os.path.join(td, "p.json"))
        with open(mx.profiler.dump()) as f:
            assert "t4.region" not in [e["name"] for e in
                                       json.load(f)["traceEvents"]]


def test_the_span_record_is_bounded_and_counts_its_drops(listener,
                                                         monkeypatch):
    import collections
    monkeypatch.setattr(mx.profiler, "_span_ring",
                        collections.deque(maxlen=8))
    with mx.profiler.counter_delta() as d:
        for i in range(20):
            with mx.profiler.span("t5.n%d" % i):
                pass
    kept = [r.name for r in mx.profiler.spans()]
    assert kept == ["t5.n%d" % i for i in range(12, 20)]   # the newest
    assert d.get("profiler_spans_dropped") == 12
    assert d.get("obs_spans") == 20


def test_a_live_span_is_an_annotation_in_an_xla_profile(tmp_path):
    """An XLA profile alone makes spans live, and each lies in it as
    ``mx.<name>`` on its thread's line with its attributes."""
    import glob
    import jax
    from jax.profiler import ProfileData
    assert not mx.profiler.spans_enabled()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert mx.profiler.spans_enabled()
        with mx.profiler.span("t6.outer", bucket=64):
            with mx.profiler.span("t6.inner"):
                mx.nd.ones((4, 4)).asnumpy()
    finally:
        jax.profiler.stop_trace()
    assert not mx.profiler.spans_enabled()
    assert mx.profiler.span("t6.off") is mx.profiler.span("t6.off2")
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("mx.t6."):
                    found[ev.name] = (plane.name, i, ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      dict(ev.stats))
    assert set(found) == {"mx.t6.outer", "mx.t6.inner"}
    o, i = found["mx.t6.outer"], found["mx.t6.inner"]
    assert o[:2] == i[:2] and o[2] <= i[2] <= i[3] <= o[3]
    assert o[4]["bucket"] == 64
    assert {"t6.outer", "t6.inner"} <= set(_mine("t6."))


def test_profiler_imports_and_records_without_jax(tmp_path):
    """``mxnet_tpu.profiler`` needs no jax: imported alone, with jax made
    unimportable, a live span records and carries no annotation."""
    code = """
import importlib.util, os, sys, types
sys.modules["jax"] = None                   # import jax -> ImportError
root = %r
pkg = types.ModuleType("mxnet_tpu"); pkg.__path__ = [root]
sys.modules["mxnet_tpu"] = pkg
def load(name):
    spec = importlib.util.spec_from_file_location(
        "mxnet_tpu." + name, os.path.join(root, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["mxnet_tpu." + name] = mod
    spec.loader.exec_module(mod)
    return mod
load("config")
p = load("profiler")
assert p.span("a") is p.span("b")
p.set_span_listener(lambda *a: None)
with p.span("outer", k=1) as o:
    with p.span("inner"):
        pass
names = [(r.name, r.parent) for r in p.spans()]
assert names == [("inner", o.id), ("outer", None)], names
assert "jax.profiler" not in sys.modules
print("ok")
""" % os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "mxnet_tpu")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0 and proc.stdout.strip() == "ok", proc.stderr
