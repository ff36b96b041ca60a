"""Always-on utilization accounting: ``obs_mfu`` / ``obs_flops_per_sec``.

ROADMAP item 3 demands the MFU campaign be self-auditing — until now MFU
existed only inside ``bench.py``'s arithmetic. Here the framework
computes its own: every ``Module`` with a fused train step registers a
weak collector; ``collect()`` (run by ``mx.obs.report()`` and the
Prometheus exposition) measures completed steps per wall second and
multiplies by the static per-step FLOP count from the
:mod:`mxnet_tpu.analysis` cost model (forward FLOPs x3 for a training
step — the same fwd + ~2x-in-bwd convention ``bench.py`` uses).

Two deliberate choices keep the hot loop untouched:

* The per-step cost is two ``perf_counter`` reads and two attribute
  writes (``Module`` records them inline); no locks, no device syncs.
* Rates are measured **between collects**: a collect blocks on the last
  dispatched step (one sync — it is a diagnostic read, exactly a log
  boundary) and the steps/s is (steps since previous collect)/(wall
  since previous collect). ``mx.obs.report()`` and the HTTP ``/metrics``
  endpoint both collect. Call ``report()`` once after warmup and once
  after the measured region — like a Prometheus ``rate()`` — and the
  window excludes compile time. The analysis import happens lazily at
  the first collect, never at bind, preserving the
  ``MXNET_TPU_ANALYZE=off`` zero-cost guarantee.

Peak FLOP/s resolves from the TPU ``device_kind`` (same table as
``bench.py``'s independent math, which stays separate on purpose — the
acceptance cross-check is only meaningful if the two computations do not
share code paths for the rate) or the ``MXNET_TPU_OBS_PEAK_FLOPS``
override for unknown devices and tests.
"""
from __future__ import annotations

import threading
import time
import weakref
from typing import Any, Dict, List, Optional

import numpy as np

from .. import config as _config
from .. import lockcheck as _lockcheck
from .. import profiler as _profiler

__all__ = ["peak_flops", "table_peak_flops", "register_executor", "collect",
           "OBS_WARMUP_STEPS", "TRAIN_FLOP_MULTIPLIER"]

# steps skipped before the rate window opens (the compile steps)
OBS_WARMUP_STEPS = 2
# training step ~ 3x forward FLOPs (fwd + ~2x in bwd) — bench.py's
# TRAIN_FLOPS_PER_IMG uses the same convention
TRAIN_FLOP_MULTIPLIER = 3.0

# dense bf16 peak FLOP/s by TPU generation (device_kind substring match).
# The ONE copy of this table: bench.py imports it too — its rate and FLOP
# math stay independent for the cross-check, but a constants table that
# drifted between the two would fail (or falsely pass) the comparison.
PEAK_FLOPS_BY_DEVICE_KIND = [
    ("v5 lite", 197e12), ("v5e", 197e12), ("v5p", 459e12),
    ("v6", 918e12), ("v4", 275e12), ("v3", 123e12), ("v2", 45e12)]

_reg_lock = _lockcheck.Lock(name="obs.mfu.reg_lock")
# serializes whole collects: two concurrent collectors (report() + a
# /metrics scrape) must not race the read-modify-write of each module's
# rate baseline. Note the baseline itself is SHARED across consumers —
# every collect closes and reopens the window, so an interleaved scrape
# shortens (never skews) a report() pair's window: rates stay
# steady-state estimates, just noisier. Benches following the
# report()-after-warmup / report()-after-region recipe should not point
# a concurrent scraper at the same process during the timed region.
_collect_lock = _lockcheck.Lock(name="obs.mfu.collect_lock")
_executors: List[weakref.ref] = []


def table_peak_flops(device_kind: str) -> Optional[float]:
    """The peaks table alone: None for a device it does not list (which
    ``bench.py`` and ``chip_smoke.py`` treat as an error)."""
    dk = device_kind.lower()
    for sub, peak in PEAK_FLOPS_BY_DEVICE_KIND:
        if sub in dk:
            return peak
    return None


def peak_flops(device_kind: Optional[str] = None) -> Optional[float]:
    """Peak dense FLOP/s: the ``MXNET_TPU_OBS_PEAK_FLOPS`` override wins,
    else the device-kind table; None when unknown (MFU is then not
    fabricated)."""
    override = float(_config.get("MXNET_TPU_OBS_PEAK_FLOPS"))
    if override > 0:
        return override
    if device_kind is None:
        try:
            import jax
            device_kind = jax.devices()[0].device_kind
        except Exception:                                  # noqa: BLE001
            return None
    return table_peak_flops(device_kind or "")


def register_executor(mod) -> None:
    """Weakly register a Module for collection (called from
    ``Module._build_fused_step``; dead refs are swept on every call)."""
    with _reg_lock:
        _executors[:] = [r for r in _executors
                         if r() is not None and r() is not mod]
        _executors.append(weakref.ref(mod))


def _flops_per_step(mod) -> Optional[float]:
    """Static FLOPs of one fused train step via the analysis cost model,
    cached on the module (0.0 caches a failed/unavailable analysis so it
    is attempted once, not per collect)."""
    cached = getattr(mod, "_obs_flops_per_step", None)
    if cached is not None:
        return cached or None
    val = 0.0
    try:
        report = mod.analyze()
        cost = report.extras.get("cost", {})
        fwd = float(cost.get("flops") or 0)
        mult = TRAIN_FLOP_MULTIPLIER \
            if getattr(mod, "optimizer_initialized", False) else 1.0
        val = fwd * mult
        # cost-model bytes ride along for the roofline reconciliation in
        # mx.obs.report() — the "why" next to the MFU number (the train
        # step touches roughly the same tensors ~3x, so the forward
        # intensity is the step intensity to first order)
        mod._obs_cost = {"flops": val,
                         "bytes_moved": float(cost.get("bytes_moved")
                                              or 0) * mult}
    except Exception:                                      # noqa: BLE001
        pass       # partial graphs / custom ops: report without MFU
    mod._obs_flops_per_step = val
    return val or None


def collect() -> List[Dict[str, Any]]:
    """One utilization sample per live registered module; updates the
    ``obs_mfu`` / ``obs_flops_per_sec`` gauges from the busiest one.
    Serialized: see ``_collect_lock`` for the shared-window semantics."""
    with _collect_lock:
        return _collect_locked()


def _collect_locked() -> List[Dict[str, Any]]:
    with _reg_lock:
        refs = list(_executors)
    live = [m for m in (ref() for ref in refs) if m is not None]
    if not live:
        # nothing to rate — and do NOT resolve the peak (jax.devices()
        # would INITIALIZE a backend): a /metrics scrape of a process
        # that never trains, e.g. the pod coordinator's endpoint, must
        # stay backend-free
        return []
    peak = peak_flops()
    out: List[Dict[str, Any]] = []
    best = None
    for mod in live:
        steps = int(getattr(mod, "_obs_steps", 0))
        mesh = getattr(mod, "_mesh", None)
        rec: Dict[str, Any] = {
            "name": getattr(mod, "_obs_label", type(mod).__name__),
            "steps": steps,
            "flops_per_step": _flops_per_step(mod),
            "steps_per_sec": None,
            "flops_per_sec": None,
            "mfu": None,
            "peak_flops": peak,
            "cost": getattr(mod, "_obs_cost", None),
            # mesh provenance so multi-chip benches report MFU PER MESH
            # SHAPE (no parallel import — read the Mesh object directly)
            "mesh": {str(a): int(s) for a, s in
                     zip(mesh.axis_names, mesh.devices.shape)}
            if mesh is not None else None,
        }
        t0 = getattr(mod, "_obs_t0", None)
        # >= so a collect at EXACTLY warmup steps (bench.py's
        # open-the-window report after its 2 warmup iterations) still
        # sets the baseline; dn == 0 then just reports no rate yet
        if steps >= OBS_WARMUP_STEPS and t0 is not None:
            token = None
            step_token = getattr(mod, "_step_token", None)
            if step_token is not None:
                token = step_token()
            if token is not None:
                try:
                    import jax
                    # the rate window must close on COMPLETED device
                    # work, and serializing whole collects (including
                    # this wait) under _collect_lock IS the documented
                    # shared-window semantics — see _collect_lock
                    jax.block_until_ready(token)  # mx-lint: allow(lock-host-sync)
                except Exception:                          # noqa: BLE001
                    pass
            now = time.perf_counter()
            base = getattr(mod, "_obs_baseline", None) \
                or (OBS_WARMUP_STEPS, t0)
            dn, dt = steps - base[0], now - base[1]
            if dn > 0 and dt > 0:
                rec["steps_per_sec"] = dn / dt
            mod._obs_baseline = (steps, now)
        if rec["steps_per_sec"] and rec["flops_per_step"]:
            fs = rec["steps_per_sec"] * rec["flops_per_step"]
            rec["flops_per_sec"] = fs
            if peak:
                # a mesh-bound module's denominator is the WHOLE mesh's
                # peak — flops_per_step is whole-model work, spread over
                # every device of the mesh
                n_dev = int(np.prod(list(rec["mesh"].values()))) \
                    if rec["mesh"] else 1
                rec["mfu"] = fs / (peak * max(1, n_dev))
            if best is None or fs > best["flops_per_sec"]:
                best = rec
        out.append(rec)
    if best is not None:
        _profiler.set_gauge("obs_flops_per_sec", best["flops_per_sec"])
        if best["mfu"] is not None:
            _profiler.set_gauge("obs_mfu", best["mfu"])
    return out
