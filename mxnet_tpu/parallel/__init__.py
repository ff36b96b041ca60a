"""mx.parallel — mesh sharding, collectives and sequence parallelism.

The TPU-native replacement for the reference's distribution stack
(SURVEY.md §2.7 KVStore comm, §2.12 ps-lite, §2.21 parallelism checklist):

* data parallel  → batch sharded over a ``data`` mesh axis (mesh.py)
* FSDP / ZeRO → params + optimizer states sharded over ``fsdp`` (layout.py)
* tensor parallel → parameters sharded over the ``tp`` axis (GSPMD)
* model parallel (group2ctx) → per-arg device shardings (executor.py)
* pipeline parallel → GPipe microbatch schedule over a mesh axis (pipeline.py)
* expert parallel → MoE with all_to_all token dispatch (moe.py)
* sequence parallel / long context → ring attention (ring_attention.py)
* multi-host → ``jax.distributed`` + the same mesh spanning hosts

ONE layout ties them together (ROADMAP item 1): :class:`SpecLayout`
(layout.py) is the canonical ``data x fsdp x tp`` mesh + PartitionSpec
policy every island declares its claims in — ``Module.set_layout`` /
``fit(layout=)`` consume it, checkpoint reshard-on-load resolves through
the same funnel, and ``analysis audit islands`` pins the agreement.
"""
from .mesh import (make_mesh, data_parallel_mesh, batch_sharding,
                   replicated_sharding, shard_batch, replicate, P, Mesh,
                   NamedSharding, mesh_devices)
from .ring_attention import (ring_attention, ring_self_attention,
                             local_attention_block)
from .pipeline import pipeline_apply, pipeline_1f1b, stack_stage_params
from .moe import moe_init, moe_apply, moe_share_apply, route_sigmoid

__all__ = ["make_mesh", "data_parallel_mesh", "batch_sharding",
           "replicated_sharding", "shard_batch", "replicate", "P", "Mesh",
           "NamedSharding", "mesh_devices", "ring_attention",
           "ring_self_attention", "local_attention_block",
           "pipeline_apply", "pipeline_1f1b", "stack_stage_params",
           "moe_init", "moe_apply", "moe_share_apply", "route_sigmoid",
           "sharding_islands",
           "SpecLayout", "parameter_spec_from_name"]


def __getattr__(name):
    # layout.py loads lazily (PEP 562): mxnet_tpu/__init__ imports this
    # package eagerly, and the zero-cost contract is that a plain fit
    # (no layout set) never imports the layout module at all — the CI
    # multichip smoke asserts sys.modules stays clean
    if name in ("SpecLayout", "parameter_spec_from_name"):
        from . import layout as _layout
        return getattr(_layout, name)
    raise AttributeError("module %r has no attribute %r"
                         % (__name__, name))


def sharding_islands():
    """Every parallel mode's canonical layout claims, keyed by island
    name — the input of ``analysis.sharding_passes.check_islands``.
    Since the SpecLayout unification (ROADMAP item 1) every island draws
    its claims from the ONE ``data x fsdp x tp`` layout, so the audit
    reports zero disagreements; the audit stays wired so any future
    island that drifts from the canonical layout becomes a finding, not
    a multi-chip bill."""
    # NOTE: `from . import ring_attention` would return the FUNCTION of
    # the same name re-exported above, not the submodule — import the
    # island declarations directly
    from .mesh import sharding_island as _mesh_island
    from .dist import sharding_island as _dist_island
    from .moe import sharding_island as _moe_island
    from .pipeline import sharding_island as _pipe_island
    from .ring_attention import sharding_island as _ring_island
    islands = {}
    for fn in (_mesh_island, _dist_island, _moe_island, _pipe_island,
               _ring_island):
        name, specs = fn()
        islands[name] = specs
    return islands
