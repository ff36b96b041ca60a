"""mxnet_tpu.tune — the configuration autotuner (ISSUE 19 tentpole).

Given a module, an optimizer, a batch source and an HBM/wall-clock
budget, :func:`search` finds the training configuration — remat policy
x ``grad_accum`` x async window x ``SpecLayout`` — in three phases:

1. **enumerate** the knob space (:mod:`.space`);
2. **prune statically** with the analysis cost/memory/comm models
   (:mod:`.prune` over ``analysis.tuning``) — configs that cannot bind
   under the HBM budget are rejected without spending a compile;
3. **confirm empirically** with short obs-instrumented probe
   subprocesses under hard deadlines (:mod:`.probe`), scored by
   ``obs_mfu`` / steps-per-sec (pod throughput on a pod) with
   ``loop_recompile == 0`` required.

The winner persists under ``MXNET_TPU_TUNE_STORE`` (:mod:`.store`), so
``fit(tune="auto")`` on a restart is pre-tuned at zero search cost; its
step is read from JAX's persistent compilation cache like any other.

This package is LAZY (PEP 562 in ``mxnet_tpu/__init__``) and imported
only when the tuner is armed — ``MXNET_TPU_TUNE`` unset means it never
loads (zero-cost gate, subprocess-asserted). CLI:
``python -m mxnet_tpu.tune --net mlp --budget 16G``.
"""
from __future__ import annotations

from .probe import make_spec, run_probe
from .search import search
from .space import Candidate, DEFAULT, enumerate_space
from .store import TunedConfig, load_config, program_key, store_config

__all__ = [
    "search", "Candidate", "DEFAULT", "enumerate_space",
    "TunedConfig", "program_key", "load_config", "store_config",
    "make_spec", "run_probe", "tune_fit",
]


def tune_fit(module, train_data, optimizer, optimizer_params,
             mode: str = "auto", budget=None, seed: int = 0):
    """``fit(tune=...)``'s backend: search (or load) the tuned config
    for this module's program and return the :class:`TunedConfig`.

    ``train_data`` must already expose ``provide_data``/``provide_label``
    (fit calls this after reset). The module is NOT mutated here —
    ``fit`` applies the winner's knobs itself so explicit user arguments
    keep precedence."""
    import numpy as np

    data_shapes = [(d.name if hasattr(d, "name") else d[0],
                    tuple(d.shape if hasattr(d, "shape") else d[1]))
                   for d in train_data.provide_data]
    label_desc = getattr(train_data, "provide_label", None) or []
    label_shapes = [(d.name if hasattr(d, "name") else d[0],
                     tuple(d.shape if hasattr(d, "shape") else d[1]))
                    for d in label_desc]

    def _dtypes(descs):
        out = {}
        for d in descs:
            dt = getattr(d, "dtype", None)
            if dt is not None:
                out[d.name if hasattr(d, "name") else d[0]] = \
                    np.dtype(dt).name
        return out

    n_devices = 1
    mesh = getattr(module, "_mesh", None)
    if mesh is not None:
        n_devices = int(getattr(mesh, "size", 1))

    cfg = search(
        module.symbol, data_shapes, label_shapes,
        optimizer=optimizer if isinstance(optimizer, str)
        else type(optimizer).__name__.lower(),
        optimizer_params=optimizer_params, budget=budget,
        n_devices=n_devices, mode=mode, seed=seed,
        data_dtypes=_dtypes(train_data.provide_data),
        label_dtypes=_dtypes(label_desc),
        log=module.logger.info if hasattr(module, "logger") else None)
    return cfg
