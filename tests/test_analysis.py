"""mxnet_tpu.analysis — static graph/program analyzer + AST lint (ISSUE 3).

Coverage contract (acceptance criteria):

* every hazard class has a negative test proving its pass FIRES (the test
  fails without the pass) and the clean-graph tests prove it stays silent;
* model-zoo nets (resnet, transformer, transformer+MoE) analyze with zero
  ERROR-level findings;
* the baked-constant pass catches the PR 1 closure-captured-constant
  pattern, and CompileCache signatures for two programs differing only in
  a captured constant never collide;
* ``MXNET_TPU_ANALYZE=strict`` turns ERROR findings into bind-time
  exceptions; ``warn`` logs and proceeds;
* with the knob unset the bind path never imports the analyzer
  (zero-cost guard, asserted in a subprocess).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import sym
from mxnet_tpu.analysis import (Severity, analyze_program, analyze_symbol,
                                diff_baseline, lint_source, load_baseline,
                                write_baseline)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def codes(report, code=None):
    if code is None:
        return [f.code for f in report]
    return [f for f in report if f.code == code]


# ===================================================== graph passes


def test_cycle_detected():
    a = sym.Variable("a")
    s1 = a + 1.0
    s2 = s1 + 2.0
    # close a loop by hand (the API can't build one, but composed/mutated
    # graphs and future passes can)
    s1._entries[0][0].inputs.append((s2._entries[0][0], 0))
    report = analyze_symbol(s2)
    hits = codes(report, "cycle")
    assert hits and hits[0].severity == Severity.ERROR
    assert "cycle" in hits[0].message


def test_no_cycle_on_diamond():
    a = sym.Variable("a")
    left = a + 1.0
    right = a * 2.0
    report = analyze_symbol(left + right)
    assert not codes(report, "cycle")


def test_duplicate_variable_names():
    report = analyze_symbol(sym.Variable("x") + sym.Variable("x"))
    hits = codes(report, "dup-name")
    assert hits and hits[0].severity == Severity.ERROR
    assert "'x'" in hits[0].message


def test_duplicate_op_names():
    d = sym.Variable("data")
    f1 = sym.FullyConnected(d, num_hidden=4, name="fc")
    f2 = sym.FullyConnected(f1, num_hidden=4, name="fc")
    report = analyze_symbol(f2)
    assert codes(report, "dup-name")


def test_unique_names_clean():
    d = sym.Variable("data")
    net = sym.FullyConnected(d, num_hidden=4, name="fc1")
    net = sym.FullyConnected(net, num_hidden=2, name="fc2")
    report = analyze_symbol(net, input_shapes={"data": (2, 8)})
    assert not codes(report, "dup-name")
    assert not codes(report, "dead-node")
    assert not report.errors


def test_dead_output_detected():
    x = sym.Variable("data")
    parts = sym.SliceChannel(x, num_outputs=3, axis=1, name="split")
    report = analyze_symbol(parts[0], input_shapes={"data": (2, 6)})
    hits = codes(report, "dead-node")
    assert hits and hits[0].node == "split"
    assert "[1, 2]" in hits[0].message


def test_all_outputs_used_clean():
    x = sym.Variable("data")
    parts = sym.SliceChannel(x, num_outputs=2, axis=1, name="split")
    report = analyze_symbol(parts[0] + parts[1],
                            input_shapes={"data": (2, 6)})
    assert not codes(report, "dead-node")


def test_unused_input_binding():
    d = sym.Variable("data")
    net = sym.FullyConnected(d, num_hidden=4)
    report = analyze_symbol(net, input_shapes={"data": (2, 8),
                                               "weihgt": (4, 8)})
    hits = codes(report, "unused-input")
    assert hits and "weihgt" in hits[0].message


def test_shape_conflict_names_node_and_shapes():
    d = sym.Variable("data")
    w = sym.Variable("w", shape=(7, 5))          # wrong: data is (4, 11)
    fc = sym.FullyConnected(d, w, num_hidden=7, no_bias=True, name="fc_bad")
    report = analyze_symbol(fc, input_shapes={"data": (4, 11)})
    hits = [f for f in codes(report, "shape-error")
            if f.severity == Severity.ERROR]
    assert hits
    f = hits[0]
    assert f.node == "fc_bad" and f.op == "FullyConnected"
    assert "4x11" in f.message and "7x5" in f.message


def test_shape_clean_net_no_errors():
    d = sym.Variable("data")
    net = sym.FullyConnected(d, num_hidden=4)
    report = analyze_symbol(net, input_shapes={"data": (2, 8)})
    assert not report.errors


def test_cost_model_mlp_flops():
    from mxnet_tpu.models import mlp
    net = mlp.get_symbol(num_classes=10, hidden=(128, 64))
    report = analyze_symbol(net, input_shapes={"data": (32, 784),
                                               "softmax_label": (32,)})
    cost = report.extras["cost"]
    # three matmuls dominate: 2*B*(784*128 + 128*64 + 64*10)
    matmul = 2 * 32 * (784 * 128 + 128 * 64 + 64 * 10)
    assert matmul <= cost["flops"] <= int(matmul * 1.2)
    # bound_bytes counts every bound variable buffer: weights/biases AND
    # the data/label inputs (what bind actually allocates)
    n_params = (784 * 128 + 128 + 128 * 64 + 64 + 64 * 10 + 10)
    assert cost["bound_bytes"] == 4 * (n_params + 32 * 784 + 32)
    assert cost["peak_bytes"] > cost["bound_bytes"] > 0
    assert cost["nodes_skipped"] == 0
    assert codes(report, "cost-model")


# ============================================== symbol-level ergonomics


def test_cost_model_liveness_self_consuming_op():
    """An op consuming the same entry through two edges (b*b) must free
    that entry ONCE — double-freeing deflates `live` and hides any LATER
    peak: here the true peak is the 3 simultaneous buffers at e."""
    a = sym.Variable("a")
    b = a + 0.0
    c = b * b          # b's last use: two edges, one buffer
    d = c + 0.0
    e = c + d          # c, d and e live together: the true 3-buffer peak
    report = analyze_symbol(e, input_shapes={"a": (256, 256)})
    buf = 256 * 256 * 4
    cost = report.extras["cost"]
    assert cost["activation_peak_bytes"] == 3 * buf
    assert cost["peak_bytes"] == cost["bound_bytes"] + \
        cost["activation_peak_bytes"]


def test_symbol_analyze_kwargs_form():
    d = sym.Variable("data")
    net = sym.FullyConnected(d, num_hidden=4)
    report = net.analyze(data=(2, 8))
    assert "cost" in report.extras


def test_mx_analysis_lazy_attribute():
    assert mx.analysis.Severity is Severity
    with pytest.raises(AttributeError):
        mx.no_such_subsystem


def test_module_analyze_bound_shapes():
    from mxnet_tpu.models import mlp
    net = mlp.get_symbol(num_classes=10)
    mod = mx.mod.Module(net, context=mx.cpu(),
                        label_names=("softmax_label",))
    mod.bind(data_shapes=[("data", (4, 784))],
             label_shapes=[("softmax_label", (4,))])
    report = mod.analyze()
    assert not report.errors
    assert report.extras["cost"]["flops"] > 0


def test_infer_shape_failure_names_offending_op():
    d = sym.Variable("data")
    w = sym.Variable("w", shape=(7, 5))
    fc = sym.FullyConnected(d, w, num_hidden=7, no_bias=True,
                            name="fc_ctx")
    with pytest.raises(mx.MXNetError) as exc_info:
        fc.infer_shape(data=(4, 11))
    msg = str(exc_info.value)
    assert "FullyConnected" in msg and "fc_ctx" in msg
    assert "(4,11)" in msg and "(7,5)" in msg
    # and not the raw eval_shape traceback of the whole graph
    assert "eval_shape" not in msg


def test_infer_type_honors_dtype_attr():
    d = sym.Variable("data", dtype=np.float16)
    net = sym.FullyConnected(d, num_hidden=4)
    arg_types, _, _ = net.infer_type()
    by_name = dict(zip(net.list_arguments(), arg_types))
    assert by_name["data"] == np.dtype(np.float16)
    weight = next(n for n in by_name if n.endswith("_weight"))
    assert by_name[weight] == np.dtype(np.float32)


def test_infer_type_invalid_dtype_names_variable():
    d = sym.Variable("data")
    net = sym.FullyConnected(d, num_hidden=4)
    with pytest.raises(mx.MXNetError, match="data"):
        net.infer_type(data="not-a-dtype")


# ===================================================== program passes


def test_baked_const_pattern_pr1():
    """The PR 1 shape: an op closure captures a constant; the program
    bakes it. The pass must fire on the closure-captured version and stay
    silent when the same array is passed as an argument."""
    big = np.ones((256, 256), np.float32)

    def closure_version(x):
        return x @ big                       # baked

    def arg_version(x, w):
        return x @ w                         # passed

    r = analyze_program(jax.jit(closure_version), jnp.ones((8, 256)))
    hits = codes(r, "baked-const")
    assert hits and hits[0].detail["nbytes"] == 256 * 256 * 4
    r = analyze_program(jax.jit(arg_version), jnp.ones((8, 256)),
                        jnp.asarray(big))
    assert not codes(r, "baked-const")


def test_baked_const_threshold():
    small = np.ones((4,), np.float32)
    r = analyze_program(lambda x: x + small, jnp.ones((4,)))
    assert not codes(r, "baked-const")       # tiny consts are fine
    r = analyze_program(lambda x: x + small, jnp.ones((4,)),
                        const_bytes_warn=1)
    assert codes(r, "baked-const")


def test_compile_cache_sigs_differ_for_closure_constants():
    """Two OpDefs wrapping different closure constants must never share a
    compiled-program signature (the PR 1 Scale(2.0)/Scale(3.0) collision):
    registry-external ops sign as (name, per-fn token), and per-call
    ``_Function_*`` ops refuse caching outright."""
    from mxnet_tpu._fused import Uncacheable, op_identity
    from mxnet_tpu.ops.registry import OpDef

    def make(scale):
        def fn(x):
            return x * scale
        return OpDef("Scale", fn)

    a, b = make(2.0), make(3.0)
    assert op_identity(a) != op_identity(b)
    # same object -> stable identity (cache hits still work)
    assert op_identity(a) == op_identity(a)
    with pytest.raises(Uncacheable):
        op_identity(OpDef("_Function_Scale", lambda x: x * 2.0))


def test_f64_promotion_detected_under_x64():
    with jax.enable_x64():
        r = analyze_program(lambda x: x * np.float64(3.0),
                            jnp.ones((4,), jnp.float32))
    assert codes(r, "f64-promotion")


def test_f64_all_f64_is_intentional():
    with jax.enable_x64():
        r = analyze_program(lambda x: x * np.float64(3.0),
                            jnp.ones((4,), jnp.float64))
    assert not codes(r, "f64-promotion")


def test_f64_silent_without_x64():
    r = analyze_program(lambda x: x * np.float64(3.0),
                        jnp.ones((4,), jnp.float32))
    assert not codes(r, "f64-promotion")


def test_host_callback_detected():
    def fn(x):
        return jax.pure_callback(
            lambda a: np.asarray(a),
            jax.ShapeDtypeStruct((4,), np.float32), x)

    r = analyze_program(fn, jnp.ones((4,)))
    hits = codes(r, "host-callback")
    assert hits and hits[0].detail["primitive"] == "pure_callback"
    # callback inside a jitted program is still found (sub-jaxpr walk)
    r = analyze_program(jax.jit(fn), jnp.ones((4,)))
    assert codes(r, "host-callback")
    r = analyze_program(lambda x: x + 1.0, jnp.ones((4,)))
    assert not codes(r, "host-callback")


def test_donation_passthrough_and_unused():
    r = analyze_program(lambda x, y: (x, x + y),
                        jnp.ones((4,)), jnp.ones((4,)),
                        donate_argnums=(0,))
    hits = codes(r, "donation")
    assert hits and hits[0].severity == Severity.ERROR
    assert "returned unchanged" in hits[0].message

    r = analyze_program(lambda x, y: y * 2.0,
                        jnp.ones((4,)), jnp.ones((4,)),
                        donate_argnums=(0,))
    hits = codes(r, "donation")
    assert hits and hits[0].severity == Severity.WARNING
    assert "never consumed" in hits[0].message

    r = analyze_program(lambda x, y: x + y,
                        jnp.ones((4,)), jnp.ones((4,)),
                        donate_argnums=(0,))
    assert not codes(r, "donation")


def test_analyze_executor_program():
    """The executor's fused graph function audits clean through the same
    API (analyze_program over the bound trace)."""
    d = sym.Variable("data")
    net = sym.FullyConnected(d, num_hidden=4)
    ex = net.simple_bind(mx.cpu(), data=(2, 8))
    args = {n: a.data for n, a in ex.arg_dict.items()}
    key = jax.random.PRNGKey(0)
    r = analyze_program(lambda a: ex._fn(a, {}, key, False), args)
    assert not codes(r, "host-callback")
    assert not [f for f in codes(r, "baked-const")
                if f.severity == Severity.ERROR]


# ========================================================= model zoo


def test_zoo_resnet_zero_errors():
    from mxnet_tpu import models
    net = models.get_resnet(num_classes=10, num_layers=8,
                            image_shape="3,32,32")
    report = analyze_symbol(net, input_shapes={"data": (2, 3, 32, 32),
                                               "softmax_label": (2,)})
    assert not report.errors, report.format(Severity.ERROR)
    assert report.extras["cost"]["flops"] > 1e7


def test_zoo_transformer_zero_errors():
    from mxnet_tpu.models import transformer
    net = transformer.get_symbol(vocab_size=128, num_layers=2,
                                 d_model=32, n_heads=2, seq_len=16)
    report = analyze_symbol(net, input_shapes={"data": (2, 16),
                                               "softmax_label": (2, 16)})
    assert not report.errors, report.format(Severity.ERROR)


def test_zoo_moe_transformer_zero_errors():
    from mxnet_tpu.models import transformer
    stages = transformer.get_pipeline_stages(
        vocab_size=64, n_stages=2, layers_per_stage=1, d_model=32,
        n_heads=2, seq_len=8, moe_experts=4)
    shapes = {"data": (2, 8)}
    for i, stage in enumerate(stages):
        report = analyze_symbol(stage, input_shapes=shapes
                                if i == 0 else None)
        assert not report.errors, \
            "stage %d: %s" % (i, report.format(Severity.ERROR))


# ============================================== bind hook / strictness


def test_strict_mode_raises_at_bind():
    mx.config.set("MXNET_TPU_ANALYZE", "strict")
    try:
        net = sym.Variable("x") + sym.Variable("x")   # dup-name ERROR
        with pytest.raises(mx.MXNetError, match="dup-name"):
            net.bind(mx.cpu(), {"x": mx.nd.ones((2,))})
    finally:
        mx.config.reset("MXNET_TPU_ANALYZE")


def test_strict_mode_clean_net_binds():
    mx.config.set("MXNET_TPU_ANALYZE", "strict")
    try:
        d = sym.Variable("data")
        net = sym.FullyConnected(d, num_hidden=4)
        ex = net.simple_bind(mx.cpu(), data=(2, 8))
        out = ex.forward()[0]
        assert out.shape == (2, 4)
    finally:
        mx.config.reset("MXNET_TPU_ANALYZE")


def test_warn_mode_logs_but_binds(caplog):
    import logging
    mx.config.set("MXNET_TPU_ANALYZE", "warn")
    try:
        net = sym.Variable("x") + sym.Variable("x")
        with caplog.at_level(logging.WARNING, "mxnet_tpu.analysis"):
            net.bind(mx.cpu(), {"x": mx.nd.ones((2,))})
        assert any("dup-name" in r.message for r in caplog.records)
    finally:
        mx.config.reset("MXNET_TPU_ANALYZE")


def test_finding_counters_increment():
    from mxnet_tpu import profiler
    before = profiler.get_counter("analysis_dup_name")
    analyze_symbol(sym.Variable("x") + sym.Variable("x"))
    assert profiler.get_counter("analysis_dup_name") == before + 1


def test_analyze_off_is_zero_cost():
    """With MXNET_TPU_ANALYZE unset, binding must never import the
    analyzer package (satellite: the bind path stays exactly as cheap as
    before this subsystem existed)."""
    prog = textwrap.dedent("""
        import sys
        sys.path.insert(0, %r)
        import mxnet_tpu as mx
        from mxnet_tpu import sym
        d = sym.Variable("data")
        net = sym.FullyConnected(d, num_hidden=4)
        ex = net.simple_bind(mx.cpu(), data=(2, 8))
        ex.forward()
        mod = mx.mod.Module(net, context=mx.cpu(), label_names=())
        mod.bind(data_shapes=[("data", (2, 8))])
        assert not any(m.startswith("mxnet_tpu.analysis")
                       for m in sys.modules), "analysis imported while off"
        print("ZERO_COST_OK")
    """) % (REPO,)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    env.pop("MXNET_TPU_ANALYZE", None)
    res = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=240)
    assert res.returncode == 0, res.stderr
    assert "ZERO_COST_OK" in res.stdout


# ============================================================= lint


LOCKED_SYNC = """
import threading

class S:
    def __init__(self):
        self._lock = threading.Lock()

    def fetch(self, arr):
        with self._lock:
            return arr.asnumpy()
"""


def test_lint_host_sync_under_lock():
    report = lint_source(LOCKED_SYNC, path="s.py")
    hits = codes(report, "lock-host-sync")
    assert hits and hits[0].severity == Severity.ERROR
    assert hits[0].func == "S.fetch"
    # the same sync WITHOUT the lock is fine
    clean = LOCKED_SYNC.replace("with self._lock:\n            return",
                                "if True:\n            return")
    assert not codes(lint_source(clean, path="s.py"), "lock-host-sync")


def test_lint_dispatch_under_lock():
    src = """
import threading, jax
lock = threading.Lock()

def go(xs):
    with lock:
        return jax.jit(sum)(xs)
"""
    assert codes(lint_source(src, path="d.py"), "lock-dispatch")


def test_lint_wall_clock():
    src = """
import time

def latency():
    t0 = time.time()
    return time.time() - t0
"""
    assert len(codes(lint_source(src, path="t.py"), "wall-clock")) == 2
    ok = src.replace("time.time()", "time.monotonic()")
    assert not codes(lint_source(ok, path="t.py"), "wall-clock")


def test_lint_eager_loop_sync():
    """A per-batch host sync inside a fit/score/*_loop batch loop fires;
    the same sync in a non-loop function (the deferred get()-boundary
    fetch) stays silent."""
    src = """
def fit(batches, metric):
    for batch in batches:
        metric.log(batch.out.asnumpy())   # per-batch pipeline break
"""
    hits = codes(lint_source(src, path="f.py"), "eager-loop-sync")
    assert hits and hits[0].severity == Severity.WARNING
    assert hits[0].func == "fit"
    # the deferred-sync pattern: same call, but in a get()-style boundary
    ok = src.replace("def fit(", "def get(").replace(
        "for batch in batches:\n        ", "")
    assert not codes(lint_source(ok, path="f.py"), "eager-loop-sync")
    # and a loop in a non-loop-owning function is not flagged either
    other = src.replace("def fit(", "def collect(")
    assert not codes(lint_source(other, path="f.py"), "eager-loop-sync")


def test_lint_nested_function_resets_lock_context():
    src = """
import threading
lock = threading.Lock()

def outer(arr):
    with lock:
        def callback():
            return arr.asnumpy()   # runs later, NOT under the lock
        return callback
"""
    assert not codes(lint_source(src, path="n.py"), "lock-host-sync")


def test_lint_lambda_resets_lock_context():
    src = """
import threading
lock = threading.Lock()

def outer(arr, sink):
    with lock:
        sink.cb = lambda: arr.asnumpy()   # deferred, runs without the lock
"""
    assert not codes(lint_source(src, path="l.py"), "lock-host-sync")


def test_lint_inline_suppression():
    src = LOCKED_SYNC.replace(
        "with self._lock:",
        "with self._lock:  # mx-lint: allow(lock-host-sync)")
    assert not codes(lint_source(src, path="s.py"), "lock-host-sync")


def test_lint_repo_is_clean_against_baseline():
    """The CI gate, in-process: the checked-in baseline covers every
    current finding in mxnet_tpu/ + tools/ — new hazards fail."""
    from mxnet_tpu.analysis import lint_paths
    report = lint_paths([os.path.join(REPO, "mxnet_tpu"),
                         os.path.join(REPO, "tools")])
    baseline = load_baseline(os.path.join(REPO, "tools",
                                          "analysis_baseline.json"))
    fresh = diff_baseline(report, baseline, REPO)
    assert not fresh, "\n".join(f.format() for f in fresh)


def test_baseline_roundtrip_and_new_finding(tmp_path):
    report = lint_source(LOCKED_SYNC, path=str(tmp_path / "s.py"))
    assert len(report) == 1
    bl_path = str(tmp_path / "bl.json")
    write_baseline(report, bl_path, str(tmp_path))
    baseline = load_baseline(bl_path)
    assert sum(baseline.values()) == 1
    # same findings -> clean
    assert not diff_baseline(report, baseline, str(tmp_path))
    # a second finding of the same key overflows the baselined count
    doubled = lint_source(LOCKED_SYNC.replace(
        "return arr.asnumpy()",
        "arr.asnumpy()\n            return arr.asnumpy()"),
        path=str(tmp_path / "s.py"))
    assert len(diff_baseline(doubled, baseline, str(tmp_path))) == 1


# ============================================================== CLI


def test_cli_graph_zoo_and_fail_on():
    from mxnet_tpu.analysis.__main__ import main
    assert main(["graph", "zoo:mlp"]) == 0


def test_cli_lint_baseline_gate(tmp_path):
    from mxnet_tpu.analysis.__main__ import main
    bad = tmp_path / "bad.py"
    bad.write_text(LOCKED_SYNC)
    assert main(["lint", str(bad), "--root", str(tmp_path)]) == 1
    bl = tmp_path / "bl.json"
    assert main(["lint", str(bad), "--root", str(tmp_path),
                 "--write-baseline", str(bl)]) == 0
    assert main(["lint", str(bad), "--root", str(tmp_path),
                 "--baseline", str(bl)]) == 0


def test_cli_self_check():
    from mxnet_tpu.analysis.__main__ import main
    assert main(["self-check"]) == 0
