"""Device context abstraction.

Reference: ``python/mxnet/context.py`` (Context class + thread-local default
stack, ``cpu()``/``gpu()`` constructors). The TPU build maps a Context onto a
concrete ``jax.Device``:

* ``cpu(i)``  -> i-th host (CPU) device
* ``tpu(i)``  -> i-th accelerator device; an error when the default backend
  is the CPU (an accelerator context never resolves to a host device)
* ``gpu(i)``  -> alias of ``tpu(i)`` so reference-era scripts that say
  ``mx.gpu(0)`` run unchanged on TPU.

Unlike the reference there is no per-context CUDA stream — XLA owns scheduling
(SURVEY.md §2.1 TPU translation note).
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_devices"]

_devtype2id = {"cpu": 1, "tpu": 2, "gpu": 2}
_devid2type = {1: "cpu", 2: "tpu"}


class Context:
    """A device context, usable as a ``with`` block to set the default device
    (reference: python/mxnet/context.py Context.__enter__/__exit__)."""

    _local = threading.local()
    devtype2str = {1: "cpu", 2: "tpu"}
    devstr2type = {"cpu": 1, "tpu": 2, "gpu": 2}

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx: Optional[Context] = None

    @property
    def device_type(self) -> str:
        return Context.devtype2str[self.device_typeid]

    @property
    def jax_device(self) -> jax.Device:
        """Resolve to the concrete jax.Device (lazy: devices may not exist
        until the backend initializes)."""
        # device ids index the PROCESS-LOCAL view (the reference's gpu(i) is
        # worker-local too); under jax.distributed the global list contains
        # other hosts' non-addressable devices
        if self.device_type == "cpu":
            return jax.local_devices(backend="cpu")[self.device_id]
        devs = jax.local_devices()
        if devs[0].platform == "cpu":
            raise MXNetError(
                "%r: no accelerator — the default JAX backend is %r; use "
                "mx.cpu(i) to run on the host" % (self, devs[0].platform))
        return devs[self.device_id]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __str__(self):
        return self.__repr__()

    def __enter__(self):
        self._old_ctx = getattr(Context._local, "default_ctx", None)
        Context._local.default_ctx = self
        return self

    def __exit__(self, exc_type, exc_value, traceback):
        Context._local.default_ctx = self._old_ctx


def cpu(device_id: int = 0) -> Context:
    """Host (CPU) context (reference: python/mxnet/context.py cpu())."""
    return Context("cpu", device_id)


def tpu(device_id: int = 0) -> Context:
    """TPU chip context — the TPU build's accelerator device."""
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Compatibility alias: reference scripts use mx.gpu(i); on the TPU build
    this addresses the i-th accelerator chip."""
    return Context("tpu", device_id)


def current_context() -> Context:
    """Default context (thread-local stack; reference context.py
    current_context). Falls back to cpu(0)."""
    ctx = getattr(Context._local, "default_ctx", None)
    return ctx if ctx is not None else Context("cpu", 0)


def num_devices(device_type: str = "tpu") -> int:
    """Number of visible devices of a type — replaces the reference's
    mx.context.num_gpus(). A backend that fails to initialize raises."""
    if device_type == "cpu":
        return len(jax.local_devices(backend="cpu"))
    devs = jax.local_devices()
    return len(devs) if devs[0].platform != "cpu" else 0
