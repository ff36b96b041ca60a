"""AOT warm starts: serialized executables so restarts skip compilation.

**Executable cache** (``MXNET_TPU_COMPILE_CACHE=<dir>``): the fused
train step and the executor forward serialize their compiled executables
(``jax.experimental.serialize_executable``) keyed on the framework-level
program signature — symbol JSON, bound shapes/dtypes, optimizer statics,
compile-affecting knobs, and the jax/device fingerprint — so a restarted
``fit``/``serve`` process skips trace AND lower AND backend-compile for
warm programs (``aot_hit``; the CI ``compile-time`` job asserts a warm
second process records zero backend-compile phases for the fused step in
the obs compile accounting). Single-device programs only
(``aot_skip_multidevice``), and only after :func:`supported` proves a
serialize → deserialize → execute → compare round-trip on this backend
(``aot_unsupported``).

JAX's own persistent compile cache is separate and always on: its
directory is chosen in ``config._apply_import_knobs``
(``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``). It
caches multi-device programs too — the warm-cache mis-execution of
collective-bearing CPU executables that an earlier jax needed a fence
for does not reproduce on jax 0.9.0
(``tests/test_pipeline_module.py::test_1f1b_matches_gpipe_one_step``,
warm cache, both ``jit_step`` programs read from it, 10 of 10 runs pass).

Layout: one ``<name>-<sha256>.aotx`` pickle per executable (payload +
pytree defs + fingerprint), written atomically (`checkpoint.atomic`) so
a killed process can never tear an entry. A corrupt or
wrong-fingerprint entry is a miss, never an error.
"""
from __future__ import annotations

import hashlib
import logging
import os
import pickle
from typing import Any, Callable, Iterable, Optional

from . import profiler as _profiler

__all__ = [
    "enabled", "supported", "fingerprint", "digest", "load", "store",
    "load_or_compile", "config_store_dir",
]

log = logging.getLogger(__name__)

_FORMAT_VERSION = 1
_probe_result: Optional[bool] = None


def enabled() -> Optional[str]:
    """The executable-cache directory, or None when the knob is off."""
    from . import config as _config
    d = _config.get("MXNET_TPU_COMPILE_CACHE")
    return d or None


def config_store_dir() -> Optional[str]:
    """Directory for persisted ``TunedConfig`` records (mxnet_tpu.tune):
    ``MXNET_TPU_TUNE_STORE`` when set, else co-located with the AOT
    executable cache — a restarted ``fit(tune="auto")`` finds the tuned
    knobs next to the executables they compile into, keyed by the same
    :func:`digest` fingerprint scheme. None = no persistence."""
    from . import config as _config
    d = _config.get("MXNET_TPU_TUNE_STORE")
    return d or enabled()


# knobs ops read at TRACE time: their value is baked into the compiled
# program, so they must invalidate serialized executables (a stale
# entry would silently run the other variant of the op)
_TRACE_KNOBS = ("MXNET_TPU_LAYERNORM_TWO_PASS",)


def fingerprint() -> str:
    """Everything that invalidates a serialized executable wholesale:
    jax/jaxlib versions, backend platform + device kind, XLA flags,
    trace-time op knobs, and the framework version (op implementations
    change programs)."""
    import jax
    import jaxlib
    from . import __version__ as mx_version
    from . import config as _config
    dev = jax.devices()[0]
    parts = (
        "v%d" % _FORMAT_VERSION, jax.__version__, jaxlib.__version__,
        jax.default_backend(), getattr(dev, "device_kind", "?"),
        os.environ.get("XLA_FLAGS", ""), mx_version,
    ) + tuple("%s=%r" % (k, _config.get(k)) for k in _TRACE_KNOBS)
    return hashlib.sha256("|".join(parts).encode()).hexdigest()


def supported() -> bool:
    """Capability probe, once per process: serialize a trivial compiled
    program, deserialize it, execute it, and compare values. A backend
    or jax build where the round-trip is unavailable or wrong disables
    the executable cache entirely (``aot_unsupported``)."""
    global _probe_result
    if _probe_result is not None:
        return _probe_result
    # unlocked on purpose: a racing second probe just repeats the same
    # idempotent round-trip (holding a mutex across jax dispatch is the
    # lock-dispatch hazard the repo lint rejects)
    try:
        import numpy as np
        import jax
        import jax.numpy as jnp
        from jax.experimental.serialize_executable import (
            deserialize_and_load, serialize)

        # salt the probe program so it can never be served from jax's
        # persistent compile cache: a cache-LOADED executable does not
        # re-serialize on this backend ("Symbols not found") — that case
        # is handled per-store by the verify in store(), and must not
        # fail the whole capability probe
        salt = float(int.from_bytes(os.urandom(4), "big")) / 2**32 + 2.0
        fn = jax.jit(lambda x: x * salt + 1.0)
        x = jnp.arange(8, dtype=jnp.float32)
        compiled = fn.lower(x).compile()
        blob = pickle.dumps(serialize(compiled))
        loaded = deserialize_and_load(*pickle.loads(blob))
        ok = bool(np.array_equal(np.asarray(loaded(x)),
                                 np.asarray(fn(x))))
    except Exception:                                       # noqa: BLE001
        ok = False
    if not ok:
        _profiler.incr_counter("aot_unsupported")
        log.warning(
            "MXNET_TPU_COMPILE_CACHE: executable serialization "
            "round-trip failed on this jax/backend; AOT warm starts "
            "disabled")
    _probe_result = ok
    return ok


def digest(parts: Iterable[Any]) -> str:
    """Collision-resistant digest of the program signature parts (the
    caller supplies symbol JSON, shapes/dtypes, optimizer statics,
    knobs); the device/jax fingerprint is always mixed in."""
    h = hashlib.sha256(fingerprint().encode())
    for p in parts:
        h.update(b"\x00")
        h.update(repr(p).encode())
    return h.hexdigest()


def _path(directory: str, name: str, key: str) -> str:
    return os.path.join(directory, "%s-%s.aotx" % (name, key))


def load(name: str, key: str) -> Optional[Callable]:
    """Deserialize the cached executable for ``(name, key)``; a missing,
    corrupt, or wrong-fingerprint entry is a miss (``aot_miss``)."""
    directory = enabled()
    if directory is None or not supported():
        return None
    path = _path(directory, name, key)
    try:
        with open(path, "rb") as f:
            entry = pickle.load(f)
        if entry.get("version") != _FORMAT_VERSION or \
                entry.get("fingerprint") != fingerprint():
            raise ValueError("stale entry")
        from jax.experimental.serialize_executable import \
            deserialize_and_load
        loaded = deserialize_and_load(entry["payload"], entry["in_tree"],
                                      entry["out_tree"])
    except FileNotFoundError:
        _profiler.incr_counter("aot_miss")
        return None
    except Exception as exc:                                # noqa: BLE001
        _profiler.incr_counter("aot_miss")
        log.info("aot: ignoring unusable cache entry %s (%s)", path, exc)
        return None
    _profiler.incr_counter("aot_hit")
    return loaded


def store(name: str, key: str, compiled) -> bool:
    """Serialize ``compiled`` under ``(name, key)``, atomically
    (``aot_store``). Serialization failures only cost the warm start."""
    directory = enabled()
    if directory is None or not supported():
        return False
    try:
        from jax.experimental.serialize_executable import (
            deserialize_and_load, serialize)
        payload, in_tree, out_tree = serialize(compiled)
        # verify the payload actually deserializes before persisting:
        # an executable that was itself loaded from jax's persistent
        # compile cache serializes "successfully" but its payload lacks
        # the kernel symbols ("Symbols not found" on load) — storing it
        # would cost every future process an aot_error round
        deserialize_and_load(payload, in_tree, out_tree)
        entry = {
            "version": _FORMAT_VERSION, "fingerprint": fingerprint(),
            "name": name, "payload": payload,
            "in_tree": in_tree, "out_tree": out_tree,
        }
        os.makedirs(directory, exist_ok=True)
        from .checkpoint.atomic import atomic_open
        with atomic_open(_path(directory, name, key), "wb") as f:
            pickle.dump(entry, f)
    except Exception as exc:                                # noqa: BLE001
        _profiler.incr_counter("aot_store_unverified")
        log.warning("aot: could not serialize %s: %s", name, exc)
        return False
    _profiler.incr_counter("aot_store")
    return True


def load_or_compile(name: str, key: str, jitted, *args):
    """The warm-start recipe the executor forward and fused step
    hand-roll, as one call: return the cached executable for
    ``(name, key)`` when present, else seed the cache — lower + compile
    ``jitted`` on ``args`` and ``store`` the result (a no-op when the
    cache is off or unsupported; an executable jax served from its own
    persistent compile cache serializes to an unloadable payload, which
    ``store``'s verify refuses).

    Returns ``(compiled, hit)``. Callers keep the first post-``load``
    invocation on COPIES of donated buffers (a bad cache entry must not
    invalidate live state — the ``_fused`` discipline). The caller
    always gets an executable.
    """
    loaded = load(name, key)
    if loaded is not None:
        return loaded, True
    compiled = jitted.lower(*args).compile()
    store(name, key, compiled)
    return compiled, False
