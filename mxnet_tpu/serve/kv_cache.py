"""Preallocated bucketed KV cache for generative decode.

The TPU-native answer to vLLM's PagedAttention allocator under the
finite-executable constraint: instead of a dynamic block table indexed
by gathers (a different program per table shape), the cache is ONE
device-resident array each for K and V —

    K, V: (num_layers, max_slots, max_seq, n_heads * d_head)

— one lane-dense row a position, heads side by side in the row,
preallocated at server start, so geometry never changes, a decode step
writes every slot's new row with one in-place update of the donated
array, and the executable universe stays |prefill buckets| + |decode
buckets|. Why rows: with ``d_head`` minor (64 wide, half a 128-lane row)
the TPU compiler kept ``max_seq`` minor instead and transposed each
layer's slab twice a decode step; a row of ``n_heads * d_head`` keeps
the plain layout, and a reshape splits the heads out where attention
wants them. What *is* paged is the accounting: a host-side
:class:`PageLedger` tracks per-slot sequence lengths in page-sized
chunks (``MXNET_TPU_SERVE_KV_PAGE`` tokens per page), drives the
occupancy gauges, and catches leaks/double-frees loudly — the property
test randomizes join/finish interleavings against it.

int8 mode (``MXNET_TPU_SERVE_KV_INT8``): K/V store as int8 with one f32
scale per (slot, head, page) — the quantized-paged-attention layout —
shrinking the reservation ~4x, which roughly doubles the resident
sequences a fixed ``MXNET_TPU_ANALYZE_HBM_BUDGET`` admits (the
acceptance test pins exactly 2x via :func:`max_slots_for`). Scales ride
separate planes ``(L, slots, H, n_pages)``; a page's scale is taken over
its positions and its head's ``d_head`` lanes of the row, and
dequantization is a reshape to ``(..., n_pages, page, H, d)`` times the
broadcast scale.

Planes. What the cache holds is described as planes, not as K and V: a
:class:`Plane` is a name, a number of layers, a row (width and dtype) a
position, and the cache is one ``(layers, slots, max_seq, width)`` array a
plane, all planes under one ledger. The dense decoder's planes are ``k``
and ``v`` (:func:`dense_planes`; int8 adds their two scale planes); a
latent-attention block keeps one ``latent`` plane, a row ``[c_kv |
k_rope]`` a position on every layer (``serve/mla_moe.py``).
A plane need not hold a row a position: ``tail`` gives a slot's share
any other shape. The third family (``serve/sparse_linear.py``) keeps two
such beside its ``k`` and ``v``: ``kc``, its indexer's compressed keys, a
row a ``stride`` positions (``tail=(max_seq // stride, width)``), and
``state``, its lightning layers' recurrent state, ``(heads * d_head,
d_head)`` float32 a slot whatever ``max_seq`` (kind ``slot_state``). The
ledger pages positions, so it pages the positional planes only; a per-slot
plane costs its whole share for as long as the slot is held, and no
position mask hides what an earlier tenant left in it: the family's
prefill starts a prompt's first chunk from zeros (nothing is zeroed at
``acquire``). ``hbm_bytes``, ``audit`` and :func:`max_slots_for` reckon
from the planes, of every kind.

Budget audit: :meth:`KVCache.audit` runs the analyzer's
``hbm-budget`` reservation check (``analysis.memory_passes
.check_reservation``) at server start — strict mode rejects an
over-budget cache NAMING it before any device allocation; the analysis
package stays unimported while ``MXNET_TPU_ANALYZE=off`` (zero-cost
gate, same discipline as the bind-time passes).
"""
from __future__ import annotations

import math
import threading
from typing import Any, Dict, List, Optional, Tuple

from .. import lockcheck as _lockcheck
from .. import profiler as _profiler
from ..base import MXNetError

__all__ = ["KVCache", "PageLedger", "CacheFull", "Plane", "dense_planes",
           "max_slots_for"]


class CacheFull(MXNetError):
    """acquire() with every slot resident (callers queue, not error)."""


class Plane:
    """One kind of state the cache keeps for every position: ``layers``
    arrays' worth of rows ``width`` wide in ``dtype``, as ``(layers, slots,
    max_seq, width)``. ``tail`` overrides the last two axes (the int8
    scale planes are ``(heads, pages)`` a slot), ``fill`` is the value an
    untouched cache holds, ``kind`` the layout claim a sharded cache
    places the plane by (``slot_state``: a state a slot that does not grow
    with ``max_seq``, and that no sharded layout places yet)."""

    __slots__ = ("name", "layers", "width", "dtype", "tail", "fill", "kind")

    def __init__(self, name: str, layers: int, width: int, dtype: str,
                 tail: Optional[Tuple[int, int]] = None, fill: float = 0.0,
                 kind: str = "kv_cache"):
        self.name = name
        self.layers = int(layers)
        self.width = int(width)
        self.dtype = str(dtype)
        self.tail = None if tail is None else (int(tail[0]), int(tail[1]))
        self.fill = fill
        self.kind = kind

    def shape(self, max_slots: int, max_seq: int) -> Tuple[int, ...]:
        tail = self.tail if self.tail is not None else (max_seq, self.width)
        return (self.layers, int(max_slots)) + tuple(tail)

    def bytes_per_slot(self, max_seq: int) -> int:
        _l, _s, a, b = self.shape(1, max_seq)
        return self.layers * a * b * _itemsize(self.dtype)

    def describe(self) -> str:
        if self.tail is not None:
            return "%s %d layers x %s %s%s" % (
                self.name, self.layers, "x".join(map(str, self.tail)),
                self.dtype, " a slot" if self.kind == "slot_state" else "")
        return "%s %d layers x rows of %d %s" % (self.name, self.layers,
                                                 self.width, self.dtype)


def _itemsize(dtype: str) -> int:
    return {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}[dtype]


def dense_planes(num_layers: int, n_heads: int, d_head: int, max_seq: int,
                 page: int, int8: bool = False) -> List[Plane]:
    """The dense decoder's planes: K and V rows of ``n_heads * d_head``,
    float32, or int8 with one float32 scale per (head, page)."""
    width = n_heads * d_head
    if not int8:
        return [Plane("k", num_layers, width, "float32"),
                Plane("v", num_layers, width, "float32")]
    scale = dict(tail=(n_heads, max_seq // page), fill=1.0, kind="kv_scale")
    # scales start at 1: dequantizing an untouched (zero) page stays zero,
    # and the requantize-on-write max() never sees 0
    return [Plane("k", num_layers, width, "int8"),
            Plane("v", num_layers, width, "int8"),
            Plane("k_scale", num_layers, 0, "float32", **scale),
            Plane("v_scale", num_layers, 0, "float32", **scale)]


def max_slots_for(budget_bytes: int, planes: List[Plane],
                  max_seq: int) -> int:
    """Largest ``max_slots`` whose cache reservation fits the budget —
    the capacity-planning inverse of :meth:`KVCache.hbm_bytes` (the two
    are consistency-tested against each other), reckoned from the
    planes (:func:`dense_planes` gives the dense decoder's)."""
    bytes_slot = sum(p.bytes_per_slot(max_seq) for p in planes)
    return max(0, int(budget_bytes) // bytes_slot)


class PageLedger:
    """Host-side page accounting for the preallocated slot array.

    Pure Python on purpose: the property test drives thousands of
    randomized acquire/grow/release interleavings against it without
    touching a device, and the occupancy gauges the server exports are
    asserted to match this model EXACTLY.

    Invariants (checked by :meth:`check`, raised on violation):
    every slot is free or resident, never both; ``pages_in_use`` equals
    the sum over resident slots of ``ceil(len / page)``; release of a
    free slot (double-free) and growth past ``max_seq`` raise.
    """

    def __init__(self, max_slots: int, max_seq: int, page: int):
        if max_slots < 1:
            raise ValueError("max_slots must be >= 1, got %d" % max_slots)
        if max_seq % page:
            raise ValueError("max_seq %d not a multiple of page %d"
                             % (max_seq, page))
        self.max_slots = int(max_slots)
        self.max_seq = int(max_seq)
        self.page = int(page)
        self.total_pages = self.max_slots * (self.max_seq // self.page)
        self._free: List[int] = list(range(self.max_slots - 1, -1, -1))
        self._len: Dict[int, int] = {}      # resident slot -> seq length
        self._lock = _lockcheck.Lock(name="serve.kv_cache_lock")

    def _pages(self, length: int) -> int:
        return max(1, math.ceil(length / self.page))

    # ------------------------------------------------------------ lifecycle
    def acquire(self, length: int) -> Optional[int]:
        """Claim a free slot for a sequence of ``length`` tokens; None
        when every slot is resident (the scheduler keeps the request
        queued — admission pressure is load-shed at submit, not here)."""
        if not 0 < length <= self.max_seq:
            raise ValueError("sequence length %d outside (0, max_seq=%d]"
                             % (length, self.max_seq))
        with self._lock:
            if not self._free:
                return None
            slot = self._free.pop()
            self._len[slot] = int(length)
            return slot

    def grow(self, slot: int) -> int:
        """One decoded token appended to ``slot``; returns the new
        length. Raises when the slot is not resident or full."""
        with self._lock:
            if slot not in self._len:
                raise MXNetError("kv ledger: grow of non-resident slot %d"
                                 % slot)
            if self._len[slot] >= self.max_seq:
                raise MXNetError("kv ledger: slot %d already at max_seq %d"
                                 % (slot, self.max_seq))
            self._len[slot] += 1
            return self._len[slot]

    def release(self, slot: int) -> int:
        """Free ``slot``'s pages; returns the page count released.
        A release of a non-resident slot is a DOUBLE-FREE and raises —
        silent tolerance here is how allocators leak."""
        with self._lock:
            if slot not in self._len:
                raise MXNetError(
                    "kv ledger: double-free of slot %d (not resident)"
                    % slot)
            pages = self._pages(self._len.pop(slot))
            self._free.append(slot)
            return pages

    # ------------------------------------------------------------- queries
    @property
    def slots_in_use(self) -> int:
        with self._lock:
            return len(self._len)

    @property
    def pages_in_use(self) -> int:
        with self._lock:
            return sum(self._pages(n) for n in self._len.values())

    def length(self, slot: int) -> int:
        with self._lock:
            return self._len[slot]

    def lengths(self) -> Dict[int, int]:
        with self._lock:
            return dict(self._len)

    def occupancy(self) -> float:
        return self.pages_in_use / self.total_pages

    def check(self) -> None:
        """Invariant audit (the property test calls this after every
        step): slot sets partition, page accounting is consistent."""
        with self._lock:
            free = set(self._free)
            used = set(self._len)
            if free & used:
                raise MXNetError("kv ledger: slots both free and resident: "
                                 "%s" % sorted(free & used))
            if len(free) != len(self._free):
                raise MXNetError("kv ledger: duplicate free slots")
            if free | used != set(range(self.max_slots)):
                raise MXNetError("kv ledger: lost slots: %s"
                                 % sorted(set(range(self.max_slots))
                                          - free - used))
            for slot, n in self._len.items():
                if not 0 < n <= self.max_seq:
                    raise MXNetError("kv ledger: slot %d length %d out of "
                                     "range" % (slot, n))


class KVCache:
    """The device-resident cache planes + the ledger + the gauges.

    ``planes`` says what the cache holds (a family's ``planes()``;
    :func:`dense_planes` for the dense decoder). ``state()``/
    ``set_state()`` expose the arrays as a flat tuple, in the planes'
    order, so the jitted prefill/decode programs take and return them as
    donated operands (double-buffer-free in-place update, the fused-step
    discipline). The dense decoder's f32 state is ``(k, v)``; int8 adds
    the scale planes: ``(k, v, k_scale, v_scale)``.
    """

    def __init__(self, planes: List[Plane], max_slots: int, max_seq: int,
                 page: Optional[int] = None, name: str = "serve",
                 mesh=None, layout=None):
        from .. import config as _config
        import jax.numpy as jnp
        self.page = int(page if page is not None
                        else _config.get("MXNET_TPU_SERVE_KV_PAGE"))
        self.max_slots = int(max_slots)
        self.max_seq = int(max_seq)
        self.name = name
        self.ledger = PageLedger(self.max_slots, self.max_seq, self.page)
        self.n_pages = self.max_seq // self.page
        self.planes: List[Plane] = list(planes)
        self.int8 = any(p.dtype == "int8" for p in self.planes)
        self._sharding = self._resolve_sharding(mesh, layout)
        self._arrays: Dict[str, Any] = {
            p.name: self._place(
                jnp.full(p.shape(self.max_slots, self.max_seq), p.fill,
                         jnp.dtype(p.dtype)), p.kind)
            for p in self.planes}
        self._update_gauges()

    def plane(self, name: str):
        """The array of the plane ``name``."""
        return self._arrays[name]

    # ---------------------------------------------------------- sharding
    def _resolve_sharding(self, mesh, layout):
        if mesh is None:
            return None
        from jax.sharding import NamedSharding
        from ..parallel.layout import island_specs
        specs = island_specs("serve", layout)
        # leading layer axis prepends to the per-layer claim
        def lift(spec):
            from jax.sharding import PartitionSpec as P
            return P(None, *spec)
        return {
            "kv_cache": NamedSharding(mesh, lift(specs["kv_cache"])),
            "kv_scale": NamedSharding(mesh, lift(specs["kv_scale"])),
        }

    def _place(self, arr, kind: str):
        if self._sharding is None:
            return arr
        import jax
        if kind not in self._sharding:
            raise MXNetError("kv cache %s: no sharded layout places a "
                             "plane of kind %r" % (self.name, kind))
        return jax.device_put(arr, self._sharding[kind])

    # ------------------------------------------------------------- state
    def state(self) -> Tuple:
        return tuple(self._arrays[p.name] for p in self.planes)

    def set_state(self, state: Tuple) -> None:
        for p, arr in zip(self.planes, state):
            self._arrays[p.name] = arr

    def hbm_bytes(self) -> int:
        """The reservation's device footprint, every plane's."""
        return sum(p.bytes_per_slot(self.max_seq) for p in self.planes) \
            * self.max_slots

    # ----------------------------------------------------------- lifecycle
    def acquire(self, length: int) -> Optional[int]:
        slot = self.ledger.acquire(length)
        if slot is not None:
            self._update_gauges()
        return slot

    def grow(self, slot: int) -> int:
        n = self.ledger.grow(slot)
        self._update_gauges()
        return n

    def release(self, slot: int) -> int:
        pages = self.ledger.release(slot)
        self._update_gauges()
        return pages

    def _update_gauges(self) -> None:
        _profiler.set_gauge(self.name + "_kv_slots_in_use",
                            self.ledger.slots_in_use)
        _profiler.set_gauge(self.name + "_kv_pages_in_use",
                            self.ledger.pages_in_use)
        _profiler.set_gauge(self.name + "_kv_occupancy",
                            self.ledger.occupancy())

    # --------------------------------------------------------------- audit
    def audit(self) -> Dict[str, Any]:
        """hbm-budget audit of the reservation at server start. The
        analysis package is imported ONLY when the analyze knob is on —
        the zero-cost gate the CI job asserts."""
        from .. import config as _config
        if _config.get("MXNET_TPU_ANALYZE") == "off":
            return {"budget_bytes": 0, "reserved_bytes": self.hbm_bytes(),
                    "fits": True}
        from ..analysis.memory_passes import check_reservation
        detail = ("serve KV cache %s: %d slots x %d seq of planes %s"
                  % (self.name, self.max_slots, self.max_seq,
                     "; ".join(p.describe() for p in self.planes)))
        return check_reservation("%s_kv_cache" % self.name,
                                 self.hbm_bytes(), detail=detail)
