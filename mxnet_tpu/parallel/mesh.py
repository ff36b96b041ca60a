"""Device-mesh utilities — the TPU-native distribution substrate.

Reference translation (SURVEY.md §2.21): the reference's
DataParallelExecutorGroup (python/mxnet/module/executor_group.py:99) manually
slices batches across a ctx list and KVStore Comm (src/kvstore/comm.h) sums
gradients device-by-device. On TPU the same capabilities are sharding
annotations on ONE jitted program over a ``jax.sharding.Mesh``: the batch is
sharded over the ``data`` axis, parameters are replicated (or sharded over
``model`` for tensor parallelism), and XLA inserts the psum/all-gather
collectives over ICI.
"""
from __future__ import annotations

import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, \
    Union

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..context import Context

__all__ = ["make_mesh", "data_parallel_mesh", "batch_sharding",
           "replicated_sharding", "shard_batch", "replicate", "P", "Mesh",
           "NamedSharding", "mesh_devices", "sharding_island",
           "axis_sizes", "validate_spec", "resolve_layout_spec",
           "host_partition"]

# a layout maps array name -> PartitionSpec: a dict (exact name match
# wins, then regex fullmatch), a callable name -> spec, a SpecLayout
# (layout.py — overrides + name heuristic, shape-aware), or None
# (everything fully replicated)
Layout = Union[None, Dict[str, Any], Callable[[str], Any]]


def resolve_layout_spec(layout: Layout, name: str, shape=None, dtype=None):
    """Resolve one array's partition spec from a layout — THE canonical
    name->spec resolution, shared by ``Module(param_shardings=...)``
    bind-time placement and checkpoint reshard-on-load (two copies of
    this precedence once drifted in the PR 8 spec-conflict audit; keep
    it single-sourced). ``None`` = replicated.

    A :class:`~mxnet_tpu.parallel.layout.SpecLayout` resolves through
    its own ``spec_for`` (overrides first, then the name heuristic) with
    the array's ``shape`` so divisibility-unsafe specs are never
    emitted; checkpoint keys (``arg:``/``aux:``/``opt:`` prefixes) are
    stripped to the parameter name so optimizer-state leaves follow
    their parameter's spec."""
    if layout is None:
        return None
    if hasattr(layout, "spec_for"):               # SpecLayout (duck-typed)
        lookup = name
        if ":" in name:
            from .layout import strip_ckpt_key
            lookup = strip_ckpt_key(name)
            if lookup is None:                    # rng:/upd: bookkeeping
                return None
        return layout.spec_for(lookup, shape=shape, dtype=dtype)
    if callable(layout):
        return layout(name)
    spec = layout.get(name)
    if spec is None:
        for pat, s in layout.items():
            if re.fullmatch(pat, name):
                return s
    return spec


def sharding_island():
    """This module's canonical layout claims, auditable by
    ``analysis.sharding_passes.check_islands`` — drawn from the unified
    SpecLayout (layout.py) like every other island, so the audit reports
    zero cross-island disagreements (ROADMAP item 1, done)."""
    from .layout import island_specs
    return "mesh", island_specs("mesh")


def mesh_devices(contexts: Optional[Sequence[Context]] = None) -> List[jax.Device]:
    if contexts is not None:
        return [c.jax_device for c in contexts]
    return list(jax.devices())


def make_mesh(shape: Optional[Dict[str, int]] = None,
              contexts: Optional[Sequence[Context]] = None,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a named mesh.

    ``shape`` maps axis name -> size, e.g. ``{"data": 4, "model": 2}``; a
    size of -1 absorbs the remaining devices. Defaults to one ``data`` axis
    over all visible devices.
    """
    devs = list(devices) if devices is not None else mesh_devices(contexts)
    if shape is None:
        shape = {"data": len(devs)}
    names = list(shape.keys())
    sizes = list(shape.values())
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        sizes[sizes.index(-1)] = len(devs) // known
    total = int(np.prod(sizes))
    if total > len(devs):
        raise ValueError("mesh needs %d devices, only %d visible"
                         % (total, len(devs)))
    arr = np.array(devs[:total]).reshape(sizes)
    return Mesh(arr, tuple(names))


def axis_sizes(mesh: Mesh) -> Dict[str, int]:
    """Axis name -> size of a named mesh."""
    return {str(a): int(s)
            for a, s in zip(mesh.axis_names, mesh.devices.shape)}


def validate_spec(mesh: Mesh, spec, shape: Tuple[int, ...],
                  name: str = "<array>") -> None:
    """Reject a PartitionSpec that cannot lay ``shape`` out on ``mesh``:
    unknown axis names, or a sharded dimension the axis sizes do not
    divide. The error NAMES the offending array — elastic reshard-on-load
    and ``Module`` param placement both route here so an N-chip
    checkpoint restored onto an incompatible M-chip mesh fails with the
    array and dimension spelled out, not a shape error deep inside XLA.
    """
    sizes = axis_sizes(mesh)
    parts = tuple(spec) if spec is not None else ()
    if len(parts) > len(shape):
        raise ValueError(
            "%s: partition spec %s has rank %d but array has rank %d"
            % (name, parts, len(parts), len(shape)))
    for dim, part in enumerate(parts):
        if part is None:
            continue
        axes = part if isinstance(part, tuple) else (part,)
        k = 1
        for a in axes:
            if a not in sizes:
                raise ValueError(
                    "%s: partition spec names axis %r but mesh %r has "
                    "axes %s" % (name, a, dict(sizes), sorted(sizes)))
            k *= sizes[a]
        if shape[dim] % k:
            raise ValueError(
                "%s: dimension %d of shape %s is not divisible by the "
                "%d-way sharding over axes %r (mesh %r)"
                % (name, dim, tuple(shape), k, axes, dict(sizes)))


def data_parallel_mesh(contexts: Sequence[Context]) -> Mesh:
    """Mesh with a single ``data`` axis over a ctx list — the TPU twin of
    Module(context=[...]) data parallelism."""
    return make_mesh({"data": len(contexts)}, contexts=contexts)


def batch_sharding(mesh: Mesh, axis: str = "data", batch_dim: int = 0):
    spec = [None] * (batch_dim + 1)
    spec[batch_dim] = axis
    return NamedSharding(mesh, P(*spec))


def replicated_sharding(mesh: Mesh):
    return NamedSharding(mesh, P())


def shard_batch(mesh: Mesh, value, axis: str = "data", batch_dim: int = 0):
    """Place an array batch-sharded over the mesh."""
    return jax.device_put(value, batch_sharding(mesh, axis, batch_dim))


def replicate(mesh: Mesh, value):
    """Place an array fully replicated over the mesh."""
    return jax.device_put(value, replicated_sharding(mesh))


def host_partition(mesh: Optional[Mesh] = None) -> Tuple[int, int]:
    """``(host_rank, host_world)`` for data-plane shard ownership — who
    feeds which slice of the global batch stream (``mx.data.DataLoader
    (part="auto")``).

    Resolution order:

    1. an explicit ``mesh``: its devices' PROCESS set — each host loads
       only the stream slice its addressable devices consume when the
       batch is ``device_put`` onto the ``data`` axis (a single-process
       mesh, however many devices, is one host: device count never
       enters the partition);
    2. the active ``jax.distributed`` pod (state probe only — never
       initializes anything, mirroring ``checkpoint.format.pod_info``);
    3. the DMLC launcher env (``DMLC_WORKER_ID``/``DMLC_NUM_WORKER`` —
       coordinated pods whose children predate jax.distributed init);
    4. ``(0, 1)`` — single host.
    """
    if mesh is not None:
        try:
            procs = sorted({d.process_index
                            for d in np.asarray(mesh.devices).flat})
            if len(procs) > 1:
                me = jax.process_index()
                return (procs.index(me) if me in procs else 0,
                        len(procs))
        except Exception:                              # noqa: BLE001
            pass
    import sys
    if "jax" in sys.modules:
        try:
            from jax._src import distributed as _jdist
            state = _jdist.global_state
            if getattr(state, "client", None) is not None:
                return (int(state.process_id or 0),
                        int(state.num_processes or 1))
        except Exception:                              # noqa: BLE001
            pass
    import os
    try:
        world = int(os.environ.get("DMLC_NUM_WORKER", "1") or 1)
        rank = int(os.environ.get("DMLC_WORKER_ID", "0") or 0)
    except ValueError:
        return 0, 1
    if world > 1:
        return min(rank, world - 1), world
    return 0, 1
