"""``python -m mxnet_tpu.analysis`` — the analyzer CLI.

Subcommands:

* ``graph <symbol.json | zoo:name>`` — run the graph passes over a saved
  symbol JSON or a model-zoo net (``zoo:resnet18``, ``zoo:mlp``,
  ``zoo:transformer``), with ``--shape name=1,3,224,224`` bindings.
* ``lint <paths...>`` — the AST concurrency/perf lint; ``--baseline``
  fails on drift in either direction (new findings AND stale
  suppressions), ``--write-baseline <file>`` regenerates an arbitrary
  baseline, ``--update-baseline`` regenerates the checked-in CI
  baseline (``tools/analysis_baseline.json`` over ``mxnet_tpu tools``).
* ``audit [targets...]`` — the efficiency auditor (ISSUE 8): memory/
  remat report + roofline classification per zoo net, and the sharding/
  communication audit of the tensor-parallel module on the virtual mesh
  (``tp-mesh`` target, needs 8 devices) plus the cross-island spec
  check. Default targets: ``mlp resnet8 transformer tp-mesh islands``.
* ``self-check`` — the CI gate: model-zoo nets must analyze with zero
  ERROR-level findings.

Exit status: 0 clean, 1 findings at/above the failure threshold
(``--fail-on``, default ERROR for ``graph``/``audit``; any baseline
drift for ``lint``), 2 usage errors.
"""
from __future__ import annotations

import argparse
import os
import sys

from .findings import Severity

__all__ = ["main"]


def _parse_shapes(specs):
    shapes = {}
    for spec in specs or ():
        if "=" not in spec:
            raise SystemExit("--shape expects name=d0,d1,... got %r" % spec)
        name, dims = spec.split("=", 1)
        shapes[name] = tuple(int(d) for d in dims.split(",") if d)
    return shapes


def _zoo_symbol(name: str):
    """Small-config model-zoo builds: fast to analyze, same op surface as
    the production sizes."""
    from .. import models
    from ..models import transformer as _transformer
    if name.startswith("resnet"):
        layers = int(name[len("resnet"):] or 8)
        return (models.get_resnet(num_classes=10, num_layers=layers,
                                  image_shape="3,32,32"),
                {"data": (2, 3, 32, 32), "softmax_label": (2,)})
    if name == "mlp":
        from ..models import mlp
        return (mlp.get_symbol(num_classes=10),
                {"data": (2, 784), "softmax_label": (2,)})
    if name == "transformer":
        return (_transformer.get_symbol(vocab_size=128, num_layers=2,
                                        d_model=32, n_heads=2, seq_len=16),
                {"data": (2, 16), "softmax_label": (2, 16)})
    raise SystemExit("unknown zoo model %r (try resnet8, resnet20, mlp, "
                     "transformer)" % name)


def _cmd_graph(args) -> int:
    from . import analyze_symbol
    if args.target.startswith("zoo:"):
        sym, shapes = _zoo_symbol(args.target[4:])
        shapes.update(_parse_shapes(args.shape))
    else:
        from ..symbol import load
        sym = load(args.target)
        shapes = _parse_shapes(args.shape)
    report = analyze_symbol(sym, input_shapes=shapes or None,
                            context=args.target)
    print(report.format(min_severity=Severity[args.min_severity]))
    fail_at = Severity[args.fail_on]
    return 1 if report.at_least(fail_at) else 0


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _cmd_lint(args) -> int:
    from . import (diff_baseline, lint_paths, load_baseline,
                   stale_baseline, write_baseline)
    if args.update_baseline:
        # regenerate the CHECKED-IN CI baseline with its canonical
        # paths/root, so "fix the drift" is one copy-pasteable command
        root = _repo_root()
        paths = [os.path.join(root, "mxnet_tpu"),
                 os.path.join(root, "tools")]
        target = os.path.join(root, "tools", "analysis_baseline.json")
        report = lint_paths(paths)
        n_keys = write_baseline(report, target, root)
        print("updated %s: %d finding key(s) (%d finding(s))"
              % (target, n_keys, len(report)))
        return 0
    if not args.paths:
        # usage error -> 2, per the module contract (SystemExit with a
        # string message would exit 1 — indistinguishable from drift)
        print("lint needs paths (or --update-baseline)", file=sys.stderr)
        return 2
    root = os.path.abspath(args.root)
    report = lint_paths(args.paths)
    if args.write_baseline:
        n_keys = write_baseline(report, args.write_baseline, root)
        print("wrote %d finding key(s) (%d finding(s)) to %s"
              % (n_keys, len(report), args.write_baseline))
        return 0
    if args.baseline:
        baseline = load_baseline(args.baseline)
        fresh = diff_baseline(report, baseline, root)
        stale = stale_baseline(report, baseline, root)
        known = len(report) - len(fresh)
        if not fresh and not stale:
            print("lint: no baseline drift (%d baselined)" % known)
            return 0
        if fresh:
            print("lint: %d NEW finding(s) (%d baselined):"
                  % (len(fresh), known))
            for f in fresh:
                print(f.format())
        if stale:
            print("lint: %d STALE baseline suppression(s) — the debt "
                  "was paid off; run `python -m mxnet_tpu.analysis lint "
                  "--update-baseline` so the next real finding at these "
                  "keys is not masked:" % len(stale))
            for k, excess in stale.items():
                print("  %s (x%d)" % (k, excess))
        return 1
    print(report.format())
    return 1 if report.findings else 0


# ------------------------------------------------------------------ audit


def _audit_zoo_net(name: str, fail_at) -> int:
    """Memory/remat + roofline audit of one zoo net; returns 1 on
    findings at/above ``fail_at``."""
    import jax
    from . import analyze_symbol, roofline
    from .findings import Severity as S
    if name.startswith("zoo:"):
        name = name[4:]           # accept the graph subcommand's spelling
    sym, shapes = _zoo_symbol(name)
    report = analyze_symbol(sym, input_shapes=shapes, context=name)
    cost = report.extras.get("cost", {})
    remat = report.extras.get("remat", {})
    print("== %s: %.3g GFLOP, est peak %.3g MB (%.3g MB activations)"
          % (name, cost.get("flops", 0) / 1e9,
             cost.get("peak_bytes", 0) / 1e6,
             cost.get("activation_peak_bytes", 0) / 1e6))
    sug = remat.get("suggestion")
    if sug:
        cands = remat.get("candidates", [])
        print("   remat: %d candidate(s), ~%.3g MB recoverable; top: %s"
              % (len(cands), sug["est_bytes_saved"] / 1e6,
                 ", ".join("%s(%s, %.3g MB)"
                           % (c["node"], c["op"], c["bytes"] / 1e6)
                           for c in cands[:3])))
        print("   suggestion: %s" % sug["hint"])
    else:
        print("   remat: no candidates")
    # roofline: compile the bound forward and reconcile with the model
    try:
        from ..context import cpu
        ex = sym.simple_bind(cpu(), **shapes)
        key = jax.random.PRNGKey(0)
        args = {n: a.data for n, a in ex.arg_dict.items()}
        aux = {n: a.data for n, a in ex.aux_dict.items()}
        roofline.analyze_executable(
            lambda a, x: ex._fn(a, x, key, False)[0], args, aux,
            model_flops=float(cost.get("flops") or 0) or None,
            context=name, report=report)
        roof = report.extras.get("roofline", {})
        cls = ("%s-bound, attainable MFU %.2f"
               % (roof["bound"], roof["attainable_mfu"])
               if "bound" in roof else "roofline unknown "
               "(set MXNET_TPU_OBS_PEAK_FLOPS/MXNET_TPU_ANALYZE_HBM_GBPS)")
        print("   roofline: compiled %.3g GFLOP vs model %.3g GFLOP "
              "(ratio %s); %s"
              % (roof.get("compiled_flops", 0) / 1e9,
                 cost.get("flops", 0) / 1e9,
                 roof.get("model_ratio", "n/a"), cls))
    except Exception as exc:                                # noqa: BLE001
        print("   roofline: unavailable (%s: %s)"
              % (type(exc).__name__,
                 (str(exc).splitlines() or [""])[0][:100]))
    for f in report.at_least(S.WARNING):
        print("   " + f.format())
    return 1 if report.at_least(fail_at) else 0


def _audit_tp_mesh(fail_at) -> int:
    """Sharding/communication audit of the Megatron-style TP module on
    the 8-device virtual mesh (the MULTICHIP dryrun twin)."""
    import jax
    from . import analyze_module_sharding
    from .findings import Severity as S
    if len(jax.devices()) < 8:
        print("== tp-mesh: SKIPPED (needs 8 devices; run under "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        return 0
    from .. import symbol as sym_mod
    from ..context import cpu
    from ..initializer import Uniform
    from ..module import Module
    from jax.sharding import PartitionSpec as P

    data = sym_mod.Variable("data")
    h = sym_mod.FullyConnected(data, num_hidden=32, name="fc1")
    h = sym_mod.Activation(h, act_type="tanh")
    h = sym_mod.FullyConnected(h, num_hidden=2, name="fc2")
    net = sym_mod.SoftmaxOutput(h, name="softmax")
    # Megatron split: fc1 column-parallel, fc2 row-parallel — exactly
    # one all-reduce over `model` in the forward (fc2's contraction)
    mod = Module(net, context=[cpu(i) for i in range(8)],
                 mesh_shape={"data": 2, "model": 4},
                 param_shardings={"fc1_weight": P("model", None),
                                  "fc1_bias": P("model"),
                                  "fc2_weight": P(None, "model")})
    mod.bind(data_shapes=[("data", (64, 6))],
             label_shapes=[("softmax_label", (64,))])
    mod.init_params(Uniform(0.01))
    report = analyze_module_sharding(mod)
    comm = report.extras.get("comm", {})
    print("== tp-mesh (data=2 x model=4, Megatron MLP):")
    for axis, agg in sorted(comm.get("per_axis", {}).items()):
        print("   axis %-14s %d collective(s), %.3g KB buffers, "
              "%.3g KB on links, ~%.3g us"
              % (axis, agg["count"], agg["bytes"] / 1e3,
                 agg["link_bytes"] / 1e3, agg["est_us"]))
    if not comm.get("collectives"):
        print("   (no collectives found)")
    for f in report.at_least(S.WARNING):
        print("   " + f.format())
    return 1 if report.at_least(fail_at) else 0


def _audit_islands(fail_at) -> int:
    """Cross-island spec audit: every parallel mode's canonical layout
    claims against the canonical ``data x fsdp x tp`` mesh. Since the
    SpecLayout unification (ROADMAP item 1) this must report ZERO
    disagreements — any finding here is an island drifting from the
    unified layout, and the audit exits 1 on it."""
    import jax
    from . import check_islands
    from ..parallel import sharding_islands
    from ..parallel.layout import SpecLayout
    islands = sharding_islands()
    mesh = None
    if len(jax.devices()) >= 8:
        mesh = SpecLayout(data=2, fsdp=2, tp=2).mesh()
    report = check_islands(islands, mesh=mesh, context="islands")
    status = "unified (zero disagreements)" if not report.findings else \
        "%d finding(s) — an island drifted from the unified SpecLayout" \
        % len(report)
    print("== islands: %d island(s), %s" % (len(islands), status))
    for f in report:
        print("   " + f.format())
    # ANY cross-island finding is a unification regression, not merely
    # advisory — fail the audit on WARNING-level findings here
    return 1 if report.findings else 0


def _cmd_audit(args) -> int:
    fail_at = Severity[args.fail_on]
    targets = args.targets or ["mlp", "resnet8", "transformer", "tp-mesh",
                               "islands"]
    failed = 0
    for t in targets:
        if t == "tp-mesh":
            failed += _audit_tp_mesh(fail_at)
        elif t == "islands":
            failed += _audit_islands(fail_at)
        else:
            try:
                failed += _audit_zoo_net(t, fail_at)
            except SystemExit as exc:
                # a mistyped target is a USAGE error (exit 2), not an
                # audit failure (exit 1) — CI keys on the distinction
                print(exc, file=sys.stderr)
                return 2
    return 1 if failed else 0


def _cmd_self_check(args) -> int:
    """Model-zoo nets must produce zero ERROR-level graph findings — the
    analyzer's own regression gate (a pass that starts mis-firing on known
    -good nets fails CI here, not in user binds) — plus the async-loop
    counter gate: a small async ``fit()`` must do ZERO per-batch host
    syncs and ZERO steady-state recompiles (the loop_* profiler counters
    the fit pipeline reports, docs/architecture/async_loop.md)."""
    from . import analyze_symbol
    failed = 0
    for name in ("resnet8", "mlp", "transformer"):
        sym, shapes = _zoo_symbol(name)
        report = analyze_symbol(sym, input_shapes=shapes, context=name)
        errs = report.errors
        status = "FAIL (%d errors)" % len(errs) if errs else "ok"
        cost = report.extras.get("cost", {})
        print("%-12s %-18s %.3g GFLOP, est peak %.3g MB"
              % (name, status, cost.get("flops", 0) / 1e9,
                 cost.get("peak_bytes", 0) / 1e6))
        for f in errs:
            print("  " + f.format())
        failed += bool(errs)
    failed += _async_loop_counter_check()
    return 1 if failed else 0


def _async_loop_counter_check() -> int:
    """One tiny async fit(); the loop counters must show a clean pipeline:
    0 per-batch host syncs, 0 steady-state recompiles, every batch fed by
    the device-prefetch stage."""
    import numpy as np
    from .. import config, io, module, profiler, symbol
    from ..initializer import Uniform

    data = symbol.Variable("data")
    fc = symbol.FullyConnected(data, num_hidden=8, name="fc1")
    net = symbol.SoftmaxOutput(fc, name="softmax")
    rng = np.random.RandomState(0)
    it = io.NDArrayIter(rng.uniform(-1, 1, (48, 16)).astype(np.float32),
                        rng.randint(0, 8, (48,)).astype(np.float32),
                        batch_size=8)
    from ..context import cpu
    mod = module.Module(net, context=cpu())
    # pin every loop knob: the gate asserts exact counter values, and an
    # ambient MXNET_TPU_DEVICE_PREFETCH=0 (say) would fail the check on
    # healthy code — the check targets the code, not the environment
    knobs = {"MXNET_TPU_ASYNC_WINDOW": 2, "MXNET_TPU_DEVICE_PREFETCH": 2,
             "MXNET_TPU_DEVICE_METRICS": True}
    for k, v in knobs.items():
        config.set(k, v)
    try:
        with profiler.counter_delta() as d:
            mod.fit(it, eval_metric="acc", num_epoch=2, optimizer="sgd",
                    initializer=Uniform(0.01),
                    optimizer_params={"learning_rate": 0.1})
        c = d.all()
    finally:
        for k in knobs:
            config.reset(k)
    checks = (
        ("loop_host_sync", c.get("loop_host_sync", 0), 0),
        ("loop_recompile", c.get("loop_recompile", 0), 0),
        ("loop_prefetch_placed", c.get("loop_prefetch_placed", 0), 12),
    )
    bad = [(k, got, want) for k, got, want in checks if got != want]
    status = "FAIL %s" % bad if bad else "ok"
    print("%-12s %-18s async fit counters: %s" % ("async-loop", status,
          {k: v for k, v in sorted(c.items()) if k.startswith("loop_")}))
    return 1 if bad else 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python -m mxnet_tpu.analysis",
                                description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = p.add_subparsers(dest="cmd", required=True)

    g = sub.add_parser("graph", help="graph passes over a symbol")
    g.add_argument("target", help="symbol JSON path or zoo:<name>")
    g.add_argument("--shape", action="append",
                   help="input shape binding name=d0,d1,... (repeatable)")
    g.add_argument("--min-severity", default="INFO",
                   choices=[s.name for s in Severity])
    g.add_argument("--fail-on", default="ERROR",
                   choices=[s.name for s in Severity])
    g.set_defaults(fn=_cmd_graph)

    l = sub.add_parser("lint", help="AST concurrency/perf lint")
    l.add_argument("paths", nargs="*")
    l.add_argument("--baseline", help="fail on drift vs this baseline "
                                      "JSON (new findings AND stale "
                                      "suppressions)")
    l.add_argument("--write-baseline", help="regenerate the baseline file "
                                            "and exit 0")
    l.add_argument("--update-baseline", action="store_true",
                   help="regenerate the checked-in CI baseline "
                        "(tools/analysis_baseline.json over "
                        "mxnet_tpu+tools) and exit 0")
    l.add_argument("--root", default=".",
                   help="path findings are keyed relative to (default .)")
    l.set_defaults(fn=_cmd_lint)

    a = sub.add_parser("audit",
                       help="efficiency audit: memory/remat + roofline "
                            "per zoo net, sharding/comm on the virtual "
                            "mesh")
    a.add_argument("targets", nargs="*",
                   help="zoo:<name> style targets plus tp-mesh/islands "
                        "(default: mlp resnet8 transformer tp-mesh "
                        "islands)")
    a.add_argument("--fail-on", default="ERROR",
                   choices=[s.name for s in Severity])
    a.set_defaults(fn=_cmd_audit)

    s = sub.add_parser("self-check",
                       help="model zoo must analyze with zero ERRORs")
    s.set_defaults(fn=_cmd_self_check)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
