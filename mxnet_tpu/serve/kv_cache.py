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

__all__ = ["KVCache", "PageLedger", "CacheFull", "max_slots_for"]


class CacheFull(MXNetError):
    """acquire() with every slot resident (callers queue, not error)."""


def max_slots_for(budget_bytes: int, num_layers: int, n_heads: int,
                  d_head: int, max_seq: int, page: int,
                  int8: bool = False) -> int:
    """Largest ``max_slots`` whose cache reservation fits the budget —
    the capacity-planning inverse of :meth:`KVCache.hbm_bytes` (the two
    are consistency-tested against each other)."""
    per_slot = 2 * num_layers * n_heads * max_seq * d_head  # K and V elems
    if int8:
        bytes_slot = per_slot * 1 \
            + 2 * num_layers * n_heads * (max_seq // page) * 4
    else:
        bytes_slot = per_slot * 4
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
    """The device-resident cache blocks + the ledger + the gauges.

    ``state()``/``set_state()`` expose the arrays as a flat tuple so the
    jitted prefill/decode programs take and return them as donated
    operands (double-buffer-free in-place update, the fused-step
    discipline). f32 state is ``(k, v)``; int8 adds the scale planes:
    ``(k, v, k_scale, v_scale)``.
    """

    def __init__(self, num_layers: int, n_heads: int, d_head: int,
                 max_slots: int, max_seq: int, page: Optional[int] = None,
                 int8: Optional[bool] = None, name: str = "serve",
                 mesh=None, layout=None):
        from .. import config as _config
        import jax.numpy as jnp
        self.page = int(page if page is not None
                        else _config.get("MXNET_TPU_SERVE_KV_PAGE"))
        self.int8 = bool(_config.get("MXNET_TPU_SERVE_KV_INT8")
                         if int8 is None else int8)
        self.num_layers = int(num_layers)
        self.n_heads = int(n_heads)
        self.d_head = int(d_head)
        self.max_slots = int(max_slots)
        self.max_seq = int(max_seq)
        self.name = name
        self.ledger = PageLedger(self.max_slots, self.max_seq, self.page)
        self.n_pages = self.max_seq // self.page
        shape = (self.num_layers, self.max_slots, self.max_seq,
                 self.n_heads * self.d_head)
        sshape = (self.num_layers, self.max_slots, self.n_heads,
                  self.n_pages)
        self._sharding = self._resolve_sharding(mesh, layout)
        kv_dtype = jnp.int8 if self.int8 else jnp.float32
        self.k = self._place(jnp.zeros(shape, kv_dtype), "kv_cache")
        self.v = self._place(jnp.zeros(shape, kv_dtype), "kv_cache")
        if self.int8:
            # scales start at 1: dequantizing an untouched (zero) page
            # stays zero, and the requantize-on-write max() never sees 0
            self.k_scale = self._place(jnp.ones(sshape, jnp.float32),
                                       "kv_scale")
            self.v_scale = self._place(jnp.ones(sshape, jnp.float32),
                                       "kv_scale")
        else:
            self.k_scale = self.v_scale = None
        self._update_gauges()

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
        return jax.device_put(arr, self._sharding[kind])

    # ------------------------------------------------------------- state
    def state(self) -> Tuple:
        if self.int8:
            return (self.k, self.v, self.k_scale, self.v_scale)
        return (self.k, self.v)

    def set_state(self, state: Tuple) -> None:
        if self.int8:
            self.k, self.v, self.k_scale, self.v_scale = state
        else:
            self.k, self.v = state

    def hbm_bytes(self) -> int:
        """The reservation's device footprint (K + V + scale planes)."""
        n = sum(int(a.size) * a.dtype.itemsize for a in self.state())
        return n

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
        detail = ("serve KV cache %s: %d layers x %d slots x %d seq x "
                  "(%d heads x %d d_head) rows, %s"
                  % (self.name, self.num_layers, self.max_slots,
                     self.max_seq, self.n_heads,
                     self.d_head, "int8+scales" if self.int8 else "f32"))
        return check_reservation("%s_kv_cache" % self.name,
                                 self.hbm_bytes(), detail=detail)
