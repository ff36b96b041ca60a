"""Empirical confirmation: short obs-instrumented probe runs.

Each probe is a SUBPROCESS (``python -m mxnet_tpu.tune --probe spec``)
under a hard deadline — the PhaseGuard discipline from ``bench.py``: a
candidate that wedges in trace/compile or thrashes cannot stall the
search; it times out, scores failed, and the partial results stand. The
child applies the candidate's knobs, runs a real ``fit`` over synthetic
batches shaped exactly like the target program, and reports
``mx.obs.probe_score()``: MFU / steps-per-sec measured from the
OBS-warmup boundary (compile excluded), the pod throughput block when a
pod is live, and ``loop_recompile`` — asserted zero, so a thrashing
config can never win.

Process isolation is the point, not a convenience: a probe compiles
executables, mutates config knobs and bumps counters — none of which
may leak into the searching process (subprocess-asserted by the probe
isolation test, same discipline as the zero-cost gates). The child
shares JAX's persistent compilation cache with the searching process, so
the step the winning probe compiled is read from there by the tuned
``fit``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

from .. import profiler as _profiler
from .space import Candidate

__all__ = ["make_spec", "run_probe", "run_probe_child"]

# obs opens its rate window after this many steps (obs.mfu contract);
# probes run warmup + measured steps in one epoch
WARMUP_STEPS = 2


def make_spec(symbol_json: str, data_shapes, label_shapes,
              data_dtypes: Dict[str, str], label_dtypes: Dict[str, str],
              optimizer: str, optimizer_params, candidate: Candidate,
              steps: int, seed: int = 0) -> Dict[str, Any]:
    """The JSON-serializable probe job description."""
    return {
        "symbol": symbol_json,
        "data_shapes": [[str(n), list(s)] for n, s in data_shapes],
        "label_shapes": [[str(n), list(s)]
                         for n, s in (label_shapes or [])],
        "data_dtypes": dict(data_dtypes or {}),
        "label_dtypes": dict(label_dtypes or {}),
        "optimizer": str(optimizer),
        "optimizer_params": dict(optimizer_params or {}),
        "candidate": candidate.to_dict(),
        "steps": int(steps),
        "seed": int(seed),
    }


def _synth_arrays(shapes, dtypes, nbatch: int):
    """Synthetic batches: zeros of the bound dtype — index-safe for
    embedding/label inputs, full-cost for the arithmetic (the values
    are runtime inputs, XLA cannot fold them)."""
    import numpy as np
    out = {}
    for name, shape in shapes:
        dt = np.dtype(dtypes.get(name, "float32"))
        full = (int(shape[0]) * nbatch,) + tuple(int(d)
                                                 for d in shape[1:])
        out[name] = np.zeros(full, dtype=dt)
    return out


def run_probe_child(spec: Dict[str, Any]) -> Dict[str, Any]:
    """Execute one probe in THIS process (the ``--probe`` child entry).
    Returns the score record the parent parses from stdout."""
    import mxnet_tpu as mx

    cand = Candidate.from_dict(spec["candidate"])
    for knob, val in cand.knobs().items():
        mx.config.set(knob, val)
    # a probe must never recurse into the tuner
    mx.config.set("MXNET_TPU_TUNE", "off")

    sym = mx.sym.load_json(spec["symbol"])
    data_shapes = [(n, tuple(s)) for n, s in spec["data_shapes"]]
    label_shapes = [(n, tuple(s)) for n, s in spec["label_shapes"]]
    steps = max(1, int(spec["steps"]))
    nbatch = steps + WARMUP_STEPS

    data = _synth_arrays(data_shapes, spec.get("data_dtypes") or {},
                         nbatch)
    label = _synth_arrays(label_shapes, spec.get("label_dtypes") or {},
                          nbatch) or None
    label_names = [n for n, _ in label_shapes]
    it = mx.io.NDArrayIter(
        data, label, batch_size=int(data_shapes[0][1][0]),
        label_name=label_names[0] if label_names else "softmax_label")

    layout = None
    if cand.layout is not None:
        from ..parallel.layout import SpecLayout
        layout = SpecLayout(data=cand.layout[0], fsdp=cand.layout[1],
                            tp=cand.layout[2])

    mx.random.seed(int(spec.get("seed", 0)))
    mod = mx.mod.Module(sym,
                        data_names=[n for n, _ in data_shapes],
                        label_names=label_names)
    t0 = time.perf_counter()
    # Loss is shape-agnostic (works for seq outputs where "acc" shape
    # checks fail) and device-capable (no async-loop host syncs)
    mod.fit(it, num_epoch=1, optimizer=spec["optimizer"],
            eval_metric=mx.metric.Loss(),
            optimizer_params=dict(spec.get("optimizer_params") or {}),
            grad_accum=cand.grad_accum if cand.grad_accum > 1 else None,
            layout=layout)
    wall = time.perf_counter() - t0
    score = mx.obs.probe_score()
    score["wall_s"] = round(wall, 3)
    score["steps"] = steps
    score["ok"] = bool(score.get("steps_per_sec")) \
        and int(score.get("loop_recompile") or 0) == 0
    if not score["ok"] and not score.get("steps_per_sec"):
        score["why"] = "no rate measured (probe too short?)"
    elif not score["ok"]:
        score["why"] = "loop_recompile=%d — the config thrashes the " \
            "executable cache" % score["loop_recompile"]
    return score


def run_probe(spec: Dict[str, Any],
              deadline_s: float) -> Dict[str, Any]:
    """Launch one probe subprocess and score it. Never raises: a
    timeout, crash or unparseable child yields ``{"ok": False, "why":
    ...}`` and the search moves on (partial results kept)."""
    _profiler.incr_counter("tune_probe")
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    # the probe inherits the platform and the store directory —
    # including a runtime config.set override, which a subprocess would
    # otherwise not see; it must not inherit an armed tuner
    from .. import config as _config
    store = _config.get("MXNET_TPU_TUNE_STORE")
    if store:
        env["MXNET_TPU_TUNE_STORE"] = str(store)
    env["MXNET_TPU_TUNE"] = ""
    env["PYTHONPATH"] = root + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    fd, path = tempfile.mkstemp(prefix="mx-tune-probe-", suffix=".json")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(spec, f)
        t0 = time.perf_counter()
        try:
            proc = subprocess.run(
                [sys.executable, "-m", "mxnet_tpu.tune", "--probe",
                 path],
                capture_output=True, text=True, env=env,
                timeout=max(1.0, float(deadline_s)))
        except subprocess.TimeoutExpired:
            _profiler.incr_counter("tune_probe_fail")
            return {"ok": False,
                    "why": "deadline (%.0fs) expired" % deadline_s,
                    "wall_s": round(time.perf_counter() - t0, 3)}
        wall = round(time.perf_counter() - t0, 3)
        # parse the score line FIRST: a failed probe exits nonzero but
        # still reports its structured "why" on the last stdout line
        for line in reversed(proc.stdout.splitlines()):
            if line.startswith("{"):
                try:
                    score = json.loads(line)
                except ValueError:
                    break
                if not score.get("ok"):
                    _profiler.incr_counter("tune_probe_fail")
                score["wall_s"] = wall
                return score
        _profiler.incr_counter("tune_probe_fail")
        return {"ok": False, "wall_s": wall,
                "why": "probe exited %d with no score line: %s"
                       % (proc.returncode,
                          (proc.stderr or "").strip()[-500:])}
    finally:
        try:
            os.unlink(path)
        except OSError:
            pass


def probe_many(specs: List[Dict[str, Any]], deadline_s: float,
               total_deadline_s: Optional[float] = None,
               log=None) -> List[Optional[Dict[str, Any]]]:
    """Run probes sequentially (each owns the machine's devices for an
    honest rate) under per-probe AND total deadlines; entries past an
    expired total budget are ``None`` (never probed, vs failed)."""
    out: List[Optional[Dict[str, Any]]] = []
    t0 = time.perf_counter()
    for spec in specs:
        if total_deadline_s is not None \
                and time.perf_counter() - t0 > total_deadline_s:
            out.append(None)
            continue
        score = run_probe(spec, deadline_s)
        if log is not None:
            log(spec, score)
        out.append(score)
    return out
