"""Memory passes: activation liveness, remat opportunities, HBM budget.

The ROADMAP MFU campaign's first lever is memory — batch sizes that
saturate the chip only fit if activations do (item 3a) — and the graph
already tells us statically which activations are worth rematerializing.
Three passes on the PR 3 liveness machinery:

* ``remat-opportunity`` (graph pass, INFO) — rank **long-lived,
  cheap-to-recompute** activations: bytes that must be held from the
  forward until the backward revisits them, against the FLOPs it would
  cost to recompute them from their inputs. The report
  (``Report.extras["remat"]``) carries concrete ``jax.checkpoint``
  policy suggestions ("wrap each repeated block, policy X") whose effect
  is *measurable* through :func:`analyze_program_memory` — the
  acceptance test applies the top suggestion and asserts the analyzed
  peak drops.
* ``hbm-budget`` (graph pass, ERROR) — an enforceable per-device memory
  budget (``MXNET_TPU_ANALYZE_HBM_BUDGET``, e.g. ``16G``): when the
  static peak estimate (bound buffers + activation high-water) exceeds
  it, the finding names the offending arrays and ``strict`` mode rejects
  the bind **before any trace or compile** — on a 6000-chip job the OOM
  bill arrives at bind time, not after the first step.
* :func:`analyze_program_memory` (program-level) — hierarchical jaxpr
  liveness: walk the eqns of a traced program (descending into
  pjit/remat/scan bodies, whose temporaries spike transiently during the
  call) and report the activation high-water plus the largest values
  live at the peak. This is the program twin of the graph cost model's
  ``peak_bytes`` and the metric the remat suggestions move.

The budget knob is parsed with K/M/G/T suffixes (:func:`parse_bytes`).
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Tuple

import numpy as np

from .findings import Report, Severity
from .graph_passes import GraphContext, _nelem, _node_flops, graph_pass

__all__ = ["analyze_program_memory", "parse_bytes", "check_reservation",
           "REMAT_CHEAP_FLOPS_PER_BYTE", "REMAT_TOP_N"]

# recompute cost ceiling for a "cheap" activation: recomputing must cost
# no more than this many FLOPs per byte saved (elementwise/norm/softmax
# chains are ~0.25-8; contractions are 2*K/itemsize and land here only
# for tiny K)
REMAT_CHEAP_FLOPS_PER_BYTE = 16.0
# candidates surfaced as findings (the full ranked list rides in extras)
REMAT_TOP_N = 5
# activations smaller than this are not worth a finding (bytes)
REMAT_MIN_BYTES = 4096

# ops whose outputs a dot-saveable policy would still SAVE (contraction
# outputs); when these dominate the candidate list only the per-block
# nothing_saveable form recovers the bytes
_CONTRACTION_OPS = {"FullyConnected", "dot", "batch_dot", "linalg_gemm2",
                    "Convolution", "Convolution_v1", "Deconvolution"}


def parse_bytes(spec) -> int:
    """``"16G"``/``"16GB"``/``"512MiB"``/``"1.5T"``/plain ints -> bytes
    (0 = unset). Raises ``ValueError`` naming the accepted grammar on
    garbage — callers on the bind path degrade to a finding instead of
    crashing the bind."""
    if spec is None:
        return 0
    s = str(spec).strip()
    if not s:
        return 0
    mult = 1
    m = re.match(r"^([0-9.eE+-]+)\s*([KMGT])(I?B)?$", s, re.IGNORECASE)
    if m:
        mult = {"K": 1 << 10, "M": 1 << 20, "G": 1 << 30,
                "T": 1 << 40}[m.group(2).upper()]
        s = m.group(1)
    try:
        val = int(float(s) * mult)
    except ValueError:
        raise ValueError(
            "cannot parse byte size %r (expected a number with an "
            "optional K/M/G/T[B|iB] suffix, e.g. '16G')" % (spec,))
    if val < 0:
        # a stray minus must not silently disable budget enforcement
        raise ValueError("byte size %r is negative" % (spec,))
    return val


# ------------------------------------------------------- remat opportunity


@graph_pass("remat-opportunity")
def remat_pass(ctx: GraphContext, report: Report) -> None:
    """Rank activations by bytes-held-until-backward vs recompute FLOPs.

    In a training bind every forward intermediate is a residual: it is
    produced at topo position p and must survive until the backward pass
    revisits it — the earlier it is produced, the longer it occupies HBM.
    An activation is a remat candidate when recomputing it from its own
    inputs is cheap (``REMAT_CHEAP_FLOPS_PER_BYTE``). The emitted
    suggestion is a concrete ``jax.checkpoint`` policy:

    * candidates dominated by contraction outputs (matmul/conv) need the
      per-block ``nothing_saveable`` form — a dots-saveable policy would
      keep exactly the bytes we want back;
    * elementwise/norm/softmax-dominated candidates are recovered by
      ``dots_with_no_batch_dims_saveable`` (keep matmuls, recompute the
      cheap tail) — the policy the fused step's
      ``MXNET_EXEC_ENABLE_REMAT`` knob already applies.
    """
    if ctx.has_cycle or not ctx.shapes:
        return
    n_nodes = len(ctx.nodes)
    order = {id(n): i for i, n in enumerate(ctx.nodes)}
    candidates: List[Dict[str, Any]] = []
    for node in ctx.nodes:
        if node.is_variable:
            continue
        in_avals = [ctx.shapes.get((id(src), i)) for src, i in node.inputs]
        out_avals = []
        i = 0
        while (id(node), i) in ctx.shapes:
            out_avals.append(ctx.shapes[(id(node), i)])
            i += 1
        if not out_avals or any(a is None for a in in_avals):
            continue
        out_bytes = sum(_nelem(s) * dt.itemsize for s, dt in out_avals)
        if out_bytes < REMAT_MIN_BYTES:
            continue
        recompute = _node_flops(node, in_avals, out_avals)
        flops_per_byte = recompute / float(out_bytes)
        if flops_per_byte > REMAT_CHEAP_FLOPS_PER_BYTE:
            continue
        # residual lifetime: from production to the end of the forward
        # (the backward walks the graph in reverse, so an activation
        # produced at p is held for ~(n_nodes - p) of the program)
        span = n_nodes - order[id(node)]
        candidates.append({
            "node": node.name, "op": node.op.name,
            "bytes": int(out_bytes), "recompute_flops": int(recompute),
            "flops_per_byte": round(flops_per_byte, 3),
            "live_span": int(span),
            "shape": [list(s) for s, _ in out_avals],
        })
    candidates.sort(key=lambda c: (-c["bytes"], -c["live_span"]))
    if not candidates:
        return
    top = candidates[:REMAT_TOP_N]
    total_bytes = sum(c["bytes"] for c in candidates)
    # bytes-dominance, as documented: only when contraction outputs hold
    # the majority of the recoverable top-N bytes is the aggressive
    # per-block nothing_saveable worth it — a dots-saveable policy would
    # keep exactly those bytes. Otherwise keep the matmuls and recompute
    # the cheap elementwise/norm tail.
    top_bytes = sum(c["bytes"] for c in top) or 1
    contraction_bytes = sum(c["bytes"] for c in top
                            if c["op"] in _CONTRACTION_OPS)
    policy = "nothing_saveable" if contraction_bytes * 2 > top_bytes \
        else "dots_with_no_batch_dims_saveable"
    suggestion = {
        "policy": policy,
        "wrap": "repeated_block",
        "hint": "wrap each repeated block (layer) in jax.checkpoint("
                "block, policy=jax.checkpoint_policies.%s); verify with "
                "analysis.analyze_program_memory on the grad program"
                % policy,
        "est_bytes_saved": int(total_bytes),
    }
    report.extras["remat"] = {"candidates": candidates,
                              "suggestion": suggestion}
    for c in top:
        report.add(
            "remat-opportunity", Severity.INFO,
            "%s output (%s, %.3g MB) is held from topo position %d to the "
            "backward but costs only %.3g FLOP/byte to recompute — "
            "rematerialize it (suggested policy: %s)"
            % (c["op"], "x".join(map(str, c["shape"][0])), c["bytes"] / 1e6,
               n_nodes - c["live_span"], c["flops_per_byte"], policy),
            node=c["node"], op=c["op"], detail=c)


# ------------------------------------------------------------- HBM budget


@graph_pass("hbm-budget")
def budget_pass(ctx: GraphContext, report: Report) -> None:
    """Reject binds whose static peak estimate cannot fit the budget.

    Reads ``Report.extras["cost"]`` (the cost-model pass runs first) and
    the ``MXNET_TPU_ANALYZE_HBM_BUDGET`` knob; the ERROR finding names
    the offending arrays — the largest bound buffers and the activations
    live at the high-water point — so the fix (shard it, remat it,
    shrink the batch) is actionable from the message alone.
    """
    from .. import config as _config
    raw = _config.get("MXNET_TPU_ANALYZE_HBM_BUDGET")
    try:
        budget = parse_bytes(raw)
    except ValueError as exc:
        # a config typo must not brick every bind in warn mode: degrade
        # to a finding that names the knob (strict mode still proceeds —
        # WARNING, not ERROR, because no memory claim was established)
        report.add(
            "hbm-budget", Severity.WARNING,
            "MXNET_TPU_ANALYZE_HBM_BUDGET=%r is unparseable (%s) — the "
            "memory budget is NOT being enforced" % (raw, exc))
        return
    if budget <= 0:
        return
    cost = report.extras.get("cost")
    if not cost:
        return
    peak = int(cost.get("peak_bytes") or 0)
    if peak <= budget:
        report.extras["hbm_budget"] = {"budget_bytes": budget,
                                       "peak_bytes": peak, "fits": True}
        return
    # name the offenders: biggest bound variables + biggest activations
    offenders: List[Tuple[str, str, int]] = []
    for node in ctx.nodes:
        aval = ctx.shapes.get((id(node), 0)) if node.is_variable else None
        if aval is not None:
            offenders.append((node.name, "bound", _nelem(aval[0])
                              * aval[1].itemsize))
    for rec in cost.get("top_nodes", ()):
        offenders.append((rec["node"], "op bytes-moved", int(rec["bytes"])))
    offenders.sort(key=lambda r: -r[2])
    offenders = offenders[:6]
    named = ", ".join("%s (%s, %.3g MB)" % (n, kind, b / 1e6)
                      for n, kind, b in offenders)
    report.extras["hbm_budget"] = {
        "budget_bytes": budget, "peak_bytes": peak, "fits": False,
        "offenders": [{"name": n, "kind": k, "bytes": b}
                      for n, k, b in offenders]}
    report.add(
        "hbm-budget", Severity.ERROR,
        "estimated peak memory %.3g MB exceeds MXNET_TPU_ANALYZE_HBM_BUDGET"
        " %.3g MB — largest contributors: %s (shard/remat them or shrink "
        "the batch; strict mode rejects this bind before any compile)"
        % (peak / 1e6, budget / 1e6, named),
        detail={"budget_bytes": budget, "peak_bytes": peak})


def check_reservation(name: str, nbytes: int,
                      detail: str = "") -> Dict[str, Any]:
    """Audit a long-lived device reservation (the serve KV cache) against
    ``MXNET_TPU_ANALYZE_HBM_BUDGET`` — the runtime twin of the bind-time
    ``hbm-budget`` pass for memory claimed OUTSIDE a graph bind.

    Returns ``{"budget_bytes", "reserved_bytes", "fits"}`` (budget 0 =
    unset, always fits). Over budget: ``MXNET_TPU_ANALYZE=strict`` raises
    :class:`~mxnet_tpu.base.MXNetError` NAMING the reservation before any
    device allocation; ``warn`` logs a WARNING with the same message.
    Callers gate the import of this module on the analyze knob, so the
    analyzer stays unimported when analysis is off.
    """
    import logging
    from .. import config as _config
    from ..base import MXNetError
    raw = _config.get("MXNET_TPU_ANALYZE_HBM_BUDGET")
    try:
        budget = parse_bytes(raw)
    except ValueError as exc:
        logging.getLogger(__name__).warning(
            "MXNET_TPU_ANALYZE_HBM_BUDGET=%r is unparseable (%s) — "
            "reservation %r is NOT being audited", raw, exc, name)
        return {"budget_bytes": 0, "reserved_bytes": int(nbytes),
                "fits": True}
    out = {"budget_bytes": budget, "reserved_bytes": int(nbytes),
           "fits": budget <= 0 or int(nbytes) <= budget}
    if out["fits"]:
        return out
    msg = ("reservation %r (%s%.3g MB) exceeds MXNET_TPU_ANALYZE_HBM_BUDGET"
           " %.3g MB — shrink max_sequences / the decode bucket set, or "
           "enable MXNET_TPU_SERVE_KV_INT8"
           % (name, (detail + ", ") if detail else "",
              nbytes / 1e6, budget / 1e6))
    if _config.get("MXNET_TPU_ANALYZE") == "strict":
        raise MXNetError("hbm-budget: " + msg)
    logging.getLogger(__name__).warning("hbm-budget: %s", msg)
    return out


# ------------------------------------------------- program-level liveness


def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    if aval is None or shape is None:
        return 0
    try:
        return int(np.prod(shape, dtype=np.int64)
                   * np.dtype(aval.dtype).itemsize) if shape \
            else int(np.dtype(aval.dtype).itemsize)
    except Exception:                                       # noqa: BLE001
        return 0


def _sub_jaxprs(eqn):
    from jax._src import core as _core
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for x in vs:
            if isinstance(x, _core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, _core.Jaxpr):
                yield x


def _jaxpr_peak(jaxpr, depth: int = 0) -> Tuple[int, List[Dict[str, Any]]]:
    """Hierarchical liveness high-water of one jaxpr's *intermediates*
    (invars excluded — those are the caller's buffers). Sub-jaxpr bodies
    (pjit/remat/scan/cond) contribute transiently: the high-water
    considers ``live_at_call + sub_peak``, which is exactly how a remat
    body's recompute spike behaves at runtime. Returns (peak_bytes,
    live-set snapshot at the peak)."""
    if depth > 16:
        return 0, []
    last: Dict[int, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            last[id(v)] = i
    for v in jaxpr.outvars:
        last[id(v)] = len(jaxpr.eqns)
    live = 0
    peak = 0
    alive: Dict[int, Tuple[int, str]] = {}
    at_peak: List[Dict[str, Any]] = []

    def snapshot(extra=None):
        rows = sorted(alive.values(), key=lambda r: -r[0])[:5]
        rows = [{"bytes": b, "value": s} for b, s in rows]
        if extra:
            rows.insert(0, extra)
        return rows

    for i, eqn in enumerate(jaxpr.eqns):
        sub_peak = 0
        sub_rows: List[Dict[str, Any]] = []
        for sub in _sub_jaxprs(eqn):
            p, rows = _jaxpr_peak(sub, depth + 1)
            if p > sub_peak:
                sub_peak, sub_rows = p, rows
        if live + sub_peak > peak:
            peak = live + sub_peak
            at_peak = snapshot({"bytes": sub_peak,
                                "value": "%s body (transient)"
                                         % eqn.primitive.name})
        for v in eqn.outvars:
            b = _aval_bytes(v)
            live += b
            aval = getattr(v, "aval", None)
            alive[id(v)] = (b, "%s -> %s%s" % (
                eqn.primitive.name,
                getattr(aval, "dtype", "?"),
                list(getattr(aval, "shape", ()))))
        if live > peak:
            peak = live
            at_peak = snapshot()
        for vid in {id(v) for v in eqn.invars}:
            if last.get(vid) == i and vid in alive:
                live -= alive.pop(vid)[0]
        for v in eqn.outvars:
            # outputs nothing ever consumes (DropVars, unused tuple
            # elements) die right after the peak check — leaving them
            # "live" to the end would inflate every later point
            if id(v) not in last and id(v) in alive:
                live -= alive.pop(id(v))[0]
    return peak, at_peak


def analyze_program_memory(fn, *args, context: str = "program-memory",
                           **kwargs) -> Report:
    """Trace ``fn(*args, **kwargs)`` and report its activation
    high-water via hierarchical jaxpr liveness.

    ``fn`` may be a plain/jitted function or an already-made
    ``ClosedJaxpr``. ``Report.extras["program_memory"]`` carries
    ``activation_peak_bytes`` (intermediates only), ``arg_bytes`` (the
    caller's input buffers), ``peak_bytes`` (their sum — comparable to
    the graph cost model's), and ``top_live`` — the largest values alive
    at the peak, named by producing primitive. This is the measurement
    the remat suggestions move: analyze the grad program plain and with
    the suggested per-block ``jax.checkpoint`` policy and compare.
    """
    import jax
    from jax._src import core as _core

    report = Report(context=context)
    if isinstance(fn, _core.ClosedJaxpr):
        closed = fn
    else:
        closed = jax.make_jaxpr(fn)(*args, **kwargs)
    from .program_passes import _unwrap_pjit
    main = _unwrap_pjit(closed)
    peak, top_live = _jaxpr_peak(main.jaxpr)
    arg_bytes = sum(_aval_bytes(v) for v in main.jaxpr.invars)
    const_bytes = sum(_aval_bytes(v) for v in main.jaxpr.constvars)
    mem = {
        "activation_peak_bytes": int(peak),
        "arg_bytes": int(arg_bytes),
        "const_bytes": int(const_bytes),
        "peak_bytes": int(peak + arg_bytes + const_bytes),
        "n_eqns": len(main.jaxpr.eqns),
        "top_live": top_live,
    }
    report.extras["program_memory"] = mem
    report.add(
        "program-memory", Severity.INFO,
        "activation high-water %.3g MB over %d eqns (+%.3g MB args); "
        "largest at peak: %s"
        % (peak / 1e6, mem["n_eqns"], arg_bytes / 1e6,
           ", ".join("%s (%.3g MB)" % (r["value"], r["bytes"] / 1e6)
                     for r in top_live[:3]) or "n/a"),
        detail=mem)
    return report
