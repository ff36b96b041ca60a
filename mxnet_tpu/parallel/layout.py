"""SpecLayout — ONE named-axis layout (``data x fsdp x tp``) for the
whole stack (ROADMAP item 1, docs/architecture/parallelism.md).

Until this module, every parallel mode was its own *sharding island*:
``mesh.py`` assumed a ``data`` axis with replicated params, ``moe.py`` an
``expert`` axis, ``pipeline.py`` a ``pipe`` axis, ``ring_attention.py``
an ``sp`` axis — and the PR 8 ``check_islands`` audit kept the
disagreements (batch-layout split, axes the bound mesh does not carry)
visible in every run. A multi-chip job composed of two modes would pay a
resharding all-to-all at every island boundary, or worse, trace-fail on
a missing axis.

``SpecLayout`` is the unification (the SNIPPETS.md [1]-[3] blueprint):

* **One mesh**: ``data x fsdp x tp`` — always all three axes (a size-1
  axis costs nothing and keeps every PartitionSpec valid on every mesh
  shape, so "pure dp" is just ``data=8, fsdp=1, tp=1``).
* **One batch layout**: inputs shard over ``(data, fsdp)`` — both axes
  are data-parallel for activations; ``fsdp`` additionally shards
  parameters and optimizer states (ZeRO-style).
* **One model axis**: ``tp`` serves tensor parallelism AND the
  expert / pipeline-stage / sequence dimensions of the moe / pipeline /
  ring-attention islands — the same axis name everywhere, so no logical
  array is ever declared with two layouts.
* **One resolver**: :meth:`SpecLayout.spec_for` (explicit overrides
  first, then :func:`parameter_spec_from_name`'s name heuristic) is
  consumed by ``Module`` bind-time placement and checkpoint
  reshard-on-load through the same ``parallel.mesh.resolve_layout_spec``
  funnel, so a checkpoint restored by layout can never resolve
  differently than the bind that consumes it.

GSPMD does the rest: parameters sharded over ``fsdp`` are all-gathered
on use and their gradients reduce-scattered; the per-device resident
bytes of params + optimizer state drop to ``~1/fsdp`` of replicated
(``tools/perf/multichip_bench.py`` proves it against the analyzer's
``fsdp-opportunity`` numbers).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple

import numpy as np

__all__ = ["SpecLayout", "parameter_spec_from_name", "island_specs",
           "resolve_model_axis", "TP_COL_RULES", "TP_ROW_RULES"]

# ---------------------------------------------------------------- name rules
#
# The tensor-parallel name heuristic (docs/architecture/parallelism.md
# carries the full table). mxnet FullyConnected weights are (out, in):
# column-parallel = shard the OUT dim (dim 0), row-parallel = shard the
# IN dim (dim 1) — the Megatron pairing keeps the activation collective
# count at one all-reduce per block. Substring match on the lowercased
# parameter name; first hit wins, column rules before row rules.
TP_COL_RULES: Tuple[str, ...] = (
    "qkv", "q_proj", "k_proj", "v_proj", "query", "key_proj", "value",
    "fc1", "ffn_up", "up_proj", "gate", "wi", "inter", "embed",
)
TP_ROW_RULES: Tuple[str, ...] = (
    "out_proj", "o_proj", "fc2", "ffn_down", "down_proj", "wo", "attn_out",
)


def _divides(dim: int, k: int) -> bool:
    return k > 0 and dim % k == 0


@dataclasses.dataclass(frozen=True)
class SpecLayout:
    """Canonical ``data x fsdp x tp`` layout: axis names + sizes + the
    parameter-spec policy.

    ``data`` may be ``-1`` (absorb the remaining devices at mesh build);
    ``fsdp``/``tp`` must be concrete — the spec heuristic needs their
    sizes for divisibility, and a spec that does not divide is never
    emitted (the array stays replicated on that axis instead).

    ``overrides`` maps parameter names (exact, then regex fullmatch —
    the ``resolve_layout_spec`` precedence) to explicit PartitionSpecs;
    they win over the name heuristic. ``min_shard_bytes`` keeps small
    parameters replicated (an all-gather's latency beats the HBM savings
    below ~1 MiB — the same threshold as the analyzer's
    ``fsdp-opportunity`` pass).
    """

    data: int = -1
    fsdp: int = 1
    tp: int = 1
    data_axis: str = "data"
    fsdp_axis: str = "fsdp"
    tp_axis: str = "tp"
    min_shard_bytes: int = 1 << 20
    overrides: Optional[Mapping[str, Any]] = None

    def __post_init__(self):
        for name, size in (("fsdp", self.fsdp), ("tp", self.tp)):
            if int(size) < 1:
                raise ValueError(
                    "SpecLayout.%s must be a concrete size >= 1 (got %r); "
                    "only data may be -1 (absorb)" % (name, size))
        if self.data == 0 or self.data < -1:
            raise ValueError("SpecLayout.data must be >= 1 or -1 (absorb), "
                             "got %r" % (self.data,))

    # ------------------------------------------------------------- mesh
    def axes(self) -> Dict[str, int]:
        """Axis name -> size, in canonical order (``make_mesh`` input)."""
        return {self.data_axis: int(self.data),
                self.fsdp_axis: int(self.fsdp),
                self.tp_axis: int(self.tp)}

    def sized(self, n_devices: int) -> "SpecLayout":
        """Resolve ``data=-1`` against a device count."""
        if self.data != -1:
            return self
        rest = int(self.fsdp) * int(self.tp)
        if n_devices % rest:
            raise ValueError(
                "layout fsdp*tp=%d does not divide %d devices"
                % (rest, n_devices))
        return dataclasses.replace(self, data=n_devices // rest)

    def world_size(self) -> Optional[int]:
        """Total devices, when fully sized (None while data=-1)."""
        if self.data == -1:
            return None
        return int(self.data) * int(self.fsdp) * int(self.tp)

    def mesh(self, contexts=None, devices=None):
        """Build the canonical ``data x fsdp x tp`` jax Mesh."""
        from .mesh import make_mesh
        return make_mesh(self.axes(), contexts=contexts, devices=devices)

    # ------------------------------------------------------------- specs
    def batch_spec(self):
        """Activations/batches shard over BOTH data-parallel axes."""
        from jax.sharding import PartitionSpec as P
        return P((self.data_axis, self.fsdp_axis))

    def spec_for(self, name: str, shape: Optional[Sequence[int]] = None,
                 dtype=None):
        """THE parameter resolver: explicit overrides first (exact key,
        then regex fullmatch), then the name heuristic. Returns a
        PartitionSpec (``P()`` = replicated); never a spec the layout's
        own axis sizes cannot divide."""
        if self.overrides:
            from .mesh import resolve_layout_spec
            spec = resolve_layout_spec(dict(self.overrides), name)
            if spec is not None:
                return spec
        return parameter_spec_from_name(name, shape=shape, dtype=dtype,
                                        layout=self)

    # the callable-layout protocol (parallel.mesh.Layout): a bare
    # SpecLayout passed where a name->spec callable is expected resolves
    # shape-blind (replicated unless an override names the array);
    # shape-aware callers go through resolve_layout_spec(name, shape=)
    def __call__(self, name: str):
        return self.spec_for(name)


def parameter_spec_from_name(name: str,
                             shape: Optional[Sequence[int]] = None,
                             dtype=None,
                             layout: Optional[SpecLayout] = None):
    """Name-heuristic PartitionSpec (the SNIPPETS.md [2] pattern, made
    shape-safe): ``tp`` placement from the column/row rule tables, then
    ``fsdp`` on the largest remaining dim it divides — but only when the
    array is big enough (``min_shard_bytes``) and the dim divides
    exactly. Unknown shapes resolve replicated (always valid)."""
    from jax.sharding import PartitionSpec as P
    lo = layout or SpecLayout()
    if shape is None or len(shape) == 0:
        return P()
    shape = tuple(int(d) for d in shape)
    parts: list = [None] * len(shape)

    lname = name.lower()
    if lo.tp > 1 and len(shape) >= 2:
        tp_dim = None
        if any(r in lname for r in TP_COL_RULES):
            tp_dim = 0
        elif any(r in lname for r in TP_ROW_RULES):
            tp_dim = 1
        if tp_dim is not None and _divides(shape[tp_dim], lo.tp):
            parts[tp_dim] = lo.tp_axis

    itemsize = np.dtype(dtype or np.float32).itemsize
    nbytes = int(np.prod(shape, dtype=np.int64)) * itemsize
    if lo.fsdp > 1 and nbytes >= lo.min_shard_bytes:
        # largest free dim the fsdp size divides (ties -> lowest index:
        # deterministic, and dim 0 is usually the output/stacking dim)
        best = None
        for i, d in enumerate(shape):
            if parts[i] is not None or not _divides(d, lo.fsdp):
                continue
            if best is None or d > shape[best]:
                best = i
        if best is not None:
            parts[best] = lo.fsdp_axis

    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def resolve_model_axis(mesh, legacy: str) -> str:
    """Default-axis resolution for the mode entry points (moe/pipeline/
    ring attention): the mode's legacy axis name (``expert``/``pipe``/
    ``sp``) when the mesh actually carries it — a mesh built with that
    axis was built FOR that mode, even if it also carries ``tp`` — else
    the canonical ``tp`` axis when present, else the legacy name (which
    then fails loudly at trace time on the missing axis)."""
    names = set(str(a) for a in mesh.axis_names)
    if legacy in names:
        return legacy
    canonical = SpecLayout().tp_axis
    if canonical in names:
        return canonical
    return legacy


# ------------------------------------------------------------- the islands

def island_specs(island: str,
                 layout: Optional[SpecLayout] = None) -> Dict[str, Any]:
    """Canonical layout claims per parallel island, ALL drawn from one
    ``SpecLayout`` — the same logical name maps to the same spec in
    every island, and every axis exists on the canonical mesh, so
    ``analysis.sharding_passes.check_islands`` reports zero
    disagreements (the unification test pins this)."""
    from jax.sharding import PartitionSpec as P
    lo = layout or SpecLayout()
    batch = lo.batch_spec()
    model = lo.tp_axis
    param = P(lo.fsdp_axis)
    table = {
        # data parallel + FSDP: batch over (data, fsdp); parameters and
        # optimizer states sharded over fsdp (replicated when fsdp=1)
        "mesh": {"batch": batch, "param": param},
        # the dist data plane reduces gradients over the SAME dp axes
        # the batch shards over; parameter residency follows mesh's claim
        "dist": {"batch": batch, "param": param},
        # expert parallel: the expert dim of dispatched activations and
        # expert FFN weights rides the model axis (all_to_all over tp)
        "moe": {"batch": batch,
                "expert_in": P(model, None, None),
                "expert_out": P(model, None, None),
                "expert_param": P(model, None, None)},
        # pipeline: stacked per-stage params shard their leading stage
        # axis over the model axis; activations hop via ppermute
        "pipeline": {"batch": batch, "stage_params": P(model)},
        # sequence parallel: q/k/v shard the sequence dim over the model
        # axis ((B, H, S, D) layout)
        "ring_attention": {"batch": batch,
                           "qkv_seq": P(None, None, model, None)},
        # generative serving: the decode KV cache is (slots, S, H * D)
        # per layer — one lane-dense row a position, heads side by side
        # (a D-wide minor dimension made the compiler keep S minor and
        # transpose each layer's slab twice a step). Its row axis IS the
        # head axis, sharded over the model axis in whole heads
        # (n_heads % tp == 0: the serving analogue of tp-sharded
        # attention heads); the int8 per-page scale planes
        # (slots, H, n_pages) follow the same head split
        "serve": {"batch": batch,
                  "kv_cache": P(None, None, model),
                  "kv_scale": P(None, model, None)},
    }
    if island not in table:
        raise ValueError("unknown sharding island %r (have %s)"
                         % (island, sorted(table)))
    return table[island]


# checkpoint keys are prefixed ("arg:fc1_weight", "opt:fc1_weight.0",
# "aux:bn_moving_mean"); layout resolution must see the parameter name
# so optimizer-state leaves follow their parameter's spec
_CKPT_KEY_RE = re.compile(r"^(arg|aux|opt):(?P<name>[^.]+)")


def strip_ckpt_key(name: str) -> Optional[str]:
    """``arg:fc1_weight`` / ``opt:fc1_weight.0.1`` -> ``fc1_weight``;
    None for keys that are not parameter-backed (``rng:*``, ``upd:*`` —
    those stay replicated under a SpecLayout)."""
    m = _CKPT_KEY_RE.match(name)
    return m.group("name") if m else None
