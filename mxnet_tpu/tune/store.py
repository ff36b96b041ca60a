"""TunedConfig persistence.

A search result is only worth its wall-clock if a RESTART gets it for
free: the winning :class:`TunedConfig` is serialized as JSON under
``MXNET_TPU_TUNE_STORE``, keyed by a sha256 over what makes the record
applicable (symbol JSON + shapes + optimizer statics + budget + device
kind and count). ``fit(tune="auto")`` loads the record and applies its
knobs: zero search cost, and the step they compile into is read from
JAX's persistent compilation cache like any other program.
"""
from __future__ import annotations

import hashlib
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from .. import profiler as _profiler
from .space import Candidate

__all__ = ["TunedConfig", "program_key", "store_config", "load_config"]

STORE_VERSION = 1


@dataclass
class TunedConfig:
    """The search's winner plus its provenance."""
    candidate: Candidate
    key: str = ""
    source: str = "default"        # probe | static | default
    score: Optional[Dict[str, Any]] = None
    baseline: Optional[Dict[str, Any]] = None   # the default's probe
    searched_s: float = 0.0
    n_probed: int = 0
    n_pruned: int = 0
    audit: List[Dict[str, Any]] = field(default_factory=list)

    def to_dict(self) -> Dict[str, Any]:
        return {"version": STORE_VERSION, "key": self.key,
                "source": self.source,
                "candidate": self.candidate.to_dict(),
                "score": self.score, "baseline": self.baseline,
                "searched_s": round(self.searched_s, 3),
                "n_probed": self.n_probed, "n_pruned": self.n_pruned,
                "audit": self.audit}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "TunedConfig":
        return cls(candidate=Candidate.from_dict(d.get("candidate")
                                                 or {}),
                   key=str(d.get("key", "")),
                   source=str(d.get("source", "default")),
                   score=d.get("score"), baseline=d.get("baseline"),
                   searched_s=float(d.get("searched_s", 0.0)),
                   n_probed=int(d.get("n_probed", 0)),
                   n_pruned=int(d.get("n_pruned", 0)),
                   audit=list(d.get("audit") or []))


def program_key(symbol_json: str, data_shapes, label_shapes,
                optimizer: str, optimizer_params, budget,
                n_devices: int) -> str:
    """The store key: everything that makes a tuned record applicable
    (the device's platform and kind included: a record never outlives
    the chip it was tuned on)."""
    import jax
    dev = jax.devices()[0]
    parts = (
        "tune", jax.default_backend(), getattr(dev, "device_kind", "?"),
        symbol_json,
        sorted((str(n), tuple(s)) for n, s in data_shapes),
        sorted((str(n), tuple(s)) for n, s in (label_shapes or [])),
        str(optimizer), sorted(dict(optimizer_params or {}).items()),
        str(budget or ""), int(n_devices))
    h = hashlib.sha256()
    for p in parts:
        h.update(b"\x00")
        h.update(repr(p).encode())
    return h.hexdigest()


def _path(key: str) -> Optional[str]:
    from .. import config as _config
    d = _config.get("MXNET_TPU_TUNE_STORE")
    if not d:
        return None
    return os.path.join(d, "tune-%s.json" % key)


def store_config(cfg: TunedConfig) -> Optional[str]:
    """Atomically persist ``cfg``; returns the path, or None when no
    store directory is configured."""
    path = _path(cfg.key)
    if path is None:
        return None
    os.makedirs(os.path.dirname(path), exist_ok=True)
    from ..checkpoint.atomic import atomic_open
    with atomic_open(path, "w") as f:
        json.dump(cfg.to_dict(), f, indent=1, sort_keys=True)
    _profiler.incr_counter("tune_store_write")
    return path


def load_config(key: str) -> Optional[TunedConfig]:
    """The stored record for ``key``, or None (missing store dir,
    missing/corrupt record, or a version from the future)."""
    path = _path(key)
    if path is None or not os.path.exists(path):
        _profiler.incr_counter("tune_store_miss")
        return None
    try:
        with open(path) as f:
            d = json.load(f)
        if int(d.get("version", 0)) > STORE_VERSION:
            _profiler.incr_counter("tune_store_miss")
            return None
        cfg = TunedConfig.from_dict(d)
    except (OSError, ValueError, KeyError):
        _profiler.incr_counter("tune_store_miss")
        return None
    _profiler.incr_counter("tune_store_hit")
    return cfg
