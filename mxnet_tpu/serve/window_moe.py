"""The fourth family ``DecodeEngine`` serves: a stack of full and windowed
grouped-query attention layers, with routed experts behind them
(``models/window_moe.py`` holds the layers' mathematics,
``ops/pallas/gqa_decode_attention.py`` the kernel a decode step reads the
full layers' caches with, ``parallel/moe.py`` the expert layer).

**Cache.** Four planes under one ``KVCache`` (``kv_cache.Plane``). For the
full layers: ``k`` and ``v``, one row a position of ``H_kv * d_k`` and
``H_kv * d_v`` (the two widths differ), as the projections make them. For
the window layers: ``kw`` and ``vw``, a ring of ``W`` rows a slot (the
window's length), position ``p`` in row ``p mod W``: planes a slot that do
not grow with ``max_seq`` (kind ``slot_state``). A ring row is read only
where its position lies in the query's window, so nothing an earlier
tenant left in it is seen: nothing is zeroed at acquire.

**Decode**, one program a sequence bucket ``S_b`` over the whole slot
array. A full layer appends the token's K and V rows at each slot's own
position and reads the slot's first ``S_b`` rows through the Pallas
kernel (only the key blocks a sequence has are fetched, none for a free
slot). A window layer writes the token's rows into row ``pos mod W`` of
the ring and attends over the ring and the sink, in XLA: ``W`` rows a
slot. The step's expert counts ride in two more entries behind the slots'
chosen tokens.

**Prefill in chunks**, programs a ``(chunk, context bucket)`` as the other
chunked families have them. A full layer appends the chunk's rows and
attends over the slot's first ``context`` rows under the causal mask, in
blocks of queries. A window layer attends over the ring's rows before the
chunk (positions ``start - W .. start - 1``, in order) and the chunk's own
under the window mask, then writes back the ring as it stands after the
chunk's last real position.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

from .. import profiler as _profiler
from ..base import MXNetError
from ..models import window_moe as _m
from .decode import (check_chunked, chunk_buckets, chunked_prefill_calls,
                     extract_params, greedy_tokens, over_query_blocks,
                     query_block)

__all__ = ["WindowMoeDecoder", "serves", "make"]

_SCORE_BLOCK_BYTES = 128 << 20      # a block of queries' float32 scores


class WindowMoeDecoder:
    """Family of ``model_type`` ``mimo_v2_flash`` (see the module)."""

    def __init__(self, params: Dict[str, Any], arch: Dict[str, Any]):
        self.arch = self.cfg = _m.Arch(arch)
        _m.check_params(self.arch, params)
        self.params = params
        self.engine = None
        self.cache = None
        self.chunk = 0

    # ------------------------------------------------------------ the cache
    def planes(self, max_seq: int, page: int, int8: bool):
        from .kv_cache import Plane
        if int8:
            raise MXNetError("serve window_moe: the cache has no int8 mode")
        a = self.arch
        full, win = a.kinds[_m.FULL], a.kinds[_m.WINDOW]
        n_f, n_w = len(a.full_layers), len(a.window_layers)
        return [Plane("k", n_f, full.k_row, a.dtype),
                Plane("v", n_f, full.v_row, a.dtype),
                Plane("kw", n_w, 0, a.dtype, tail=(a.window, win.k_row),
                      kind="slot_state"),
                Plane("vw", n_w, 0, a.dtype, tail=(a.window, win.v_row),
                      kind="slot_state")]

    def bind(self, engine) -> None:
        self.engine = engine
        self.cache = engine.cache
        self.chunk = int(engine.prefill_chunk)
        check_chunked(engine, self.chunk, "window_moe")
        self.chunk_buckets: List[int] = chunk_buckets(self.chunk)

    def executable_bound(self) -> int:
        return (len(self.chunk_buckets) + 1) * len(self.engine.seq_buckets)

    def kernel_reads(self, s_b: int) -> bool:
        """Whether bucket ``s_b``'s decode program reads the full layers'
        rows with the Pallas kernel: where the TPU can fetch its blocks.
        The XLA read of the bucket stays as the kernel's reference in the
        CPU tests and for sizes that are no tile."""
        from ..ops.pallas.gqa_decode_attention import tiles
        full = self.arch.kinds[_m.FULL]
        return tiles(full.kv_heads, full.d_k, full.d_v, s_b, self.arch.dtype)

    # ------------------------------------------------------------- dispatch
    def prefill_calls(self, prompt: np.ndarray, slot: int):
        """Chunk after chunk: ``((chunk, context), builder, args, span
        attributes)``."""
        return chunked_prefill_calls(self.engine, self.chunk,
                                     self.chunk_buckets, self.build_prefill,
                                     prompt, slot)

    def step_picked(self, fetched, s_b, pos, active) -> np.ndarray:
        """The slots' tokens out of the decode program's ``picked``; its
        last two entries are the step's assignments and experts hit. The
        key rows the step read are counted here, from the positions: a
        resident sequence's ``pos + 1`` rows on every full layer, at most
        the window's on every window layer."""
        a, name = self.arch, self.engine.name
        _profiler.incr_counter(name + "_moe_assignments", int(fetched[-2]))
        _profiler.incr_counter(name + "_moe_experts_hit", int(fetched[-1]))
        keys = pos[active].astype(np.int64) + 1
        _profiler.incr_counter(name + "_full_rows_read",
                               int(keys.sum()) * len(a.full_layers))
        _profiler.incr_counter(
            name + "_window_rows_read",
            int(np.minimum(keys, a.window).sum()) * len(a.window_layers))
        if self.kernel_reads(s_b):
            _profiler.incr_counter(name + "_gqa_decode_kernel_steps")
        return fetched[:-2]

    # ------------------------------------------------------------- programs
    def build_prefill(self, bucket: Tuple[int, int]):
        import jax
        import jax.numpy as jnp
        from jax import lax
        a = self.arch
        c_b, ctx_b = bucket
        w = a.window
        full, win = a.kinds[_m.FULL], a.kinds[_m.WINDOW]
        dt = jnp.dtype(a.dtype)

        def blocked(n_keys, f, *xs):
            heads = max(full.heads, win.heads)
            return over_query_blocks(
                f, c_b, query_block(heads, c_b, n_keys, _SCORE_BLOCK_BYTES),
                *xs)

        def full_layer(q, k, v, pos, li, slot, start, k_pl, v_pl):
            k_pl = lax.dynamic_update_slice(
                k_pl, k.reshape(c_b, -1).astype(dt)[None, None],
                (li, slot, start, 0))
            v_pl = lax.dynamic_update_slice(
                v_pl, v.reshape(c_b, -1).astype(dt)[None, None],
                (li, slot, start, 0))
            k_ctx = lax.dynamic_slice(k_pl, (li, slot, 0, 0),
                                      (1, 1, ctx_b, full.k_row))[0, 0]
            v_ctx = lax.dynamic_slice(v_pl, (li, slot, 0, 0),
                                      (1, 1, ctx_b, full.v_row))[0, 0]
            keys = jnp.arange(ctx_b, dtype=jnp.int32)
            o = blocked(ctx_b, lambda qb, tb: _m.attend(
                full, qb, k_ctx, v_ctx, _m.full_keep(tb, keys), shared=True),
                q, pos)
            return o, k_pl, v_pl

        def window_layer(p, q, k, v, pos, li, slot, start, last, kw, vw):
            # the ring's rows before the chunk, in position order, then
            # the chunk's own: positions start - W .. start + c_b - 1
            order = _m.ring_order(start, w)

            def context(ring, new, width):
                held = lax.dynamic_slice(ring, (li, slot, 0, 0),
                                         (1, 1, w, width))[0, 0]
                return jnp.concatenate(
                    [held[order], new.reshape(c_b, width).astype(dt)], 0)
            k_ctx = context(kw, k, win.k_row)
            v_ctx = context(vw, v, win.v_row)
            keys = start - w + jnp.arange(w + c_b, dtype=jnp.int32)
            o = blocked(w + c_b, lambda qb, tb: _m.attend(
                win, qb, k_ctx, v_ctx, _m.window_keep(w, tb, keys),
                p["att_sink"], shared=True), q, pos)
            # the ring as it stands after the chunk's last real position
            rows = _m.ring_rows(last, start, w)
            kw = lax.dynamic_update_slice(kw, k_ctx[rows][None, None],
                                          (li, slot, 0, 0))
            vw = lax.dynamic_update_slice(vw, v_ctx[rows][None, None],
                                          (li, slot, 0, 0))
            return o, kw, vw

        def fn(params, state, tokens, slot, start, true_len):
            # tokens (c_b,) int32; slot, start, true_len scalar int32
            k_pl, v_pl, kw, vw = state
            pos = start + jnp.arange(c_b, dtype=jnp.int32)
            real = pos < true_len
            last = jnp.minimum(true_len, start + c_b) - 1
            x = _m.embed(params, tokens)
            fi = wi = 0
            for li in range(a.num_layers):
                p = _m.layer_params(params, li)
                kind = a.kinds[a.attn_types[li]]
                h = _m.rms_norm(x, p["ln1_gamma"], a.eps)
                q, k, v = _m.project(a, kind, p, h, pos)
                if a.attn_types[li] == _m.FULL:
                    o, k_pl, v_pl = full_layer(q, k, v, pos, fi, slot, start,
                                               k_pl, v_pl)
                    fi += 1
                else:
                    o, kw, vw = window_layer(p, q, k, v, pos, wi, slot,
                                             start, last, kw, vw)
                    wi += 1
                x = x + _m.dense(o, p["att_o_weight"])
                y, _counts = _m.ffn(a, p, _m.rms_norm(x, p["ln2_gamma"],
                                                      a.eps),
                                    a.mlp_types[li], real)
                x = x + y
            # only the last REAL token goes through the head, if it lies
            # in this chunk (else the row read is not used by anyone)
            at = jnp.clip(true_len - 1 - start, 0, c_b - 1)
            logits = _m.head(a, params,
                             lax.dynamic_slice(x, (at, 0), (1, a.d)))[0]
            return greedy_tokens(logits), logits, (k_pl, v_pl, kw, vw)

        return jax.jit(fn, donate_argnums=(1,))

    def build_decode(self, s_b: int):
        import jax
        import jax.numpy as jnp
        from ..ops.pallas.gqa_decode_attention import (block_for, fetch_plan,
                                                       gqa_decode_attention)
        a = self.arch
        w = a.window
        full, win = a.kinds[_m.FULL], a.kinds[_m.WINDOW]
        dt = jnp.dtype(a.dtype)
        kernel = self.kernel_reads(s_b)

        def full_layer(q, k, v, pos, li, plan, k_pl, v_pl):
            slots = q.shape[0]
            sl = jnp.arange(slots)
            # every slot's rows at ITS OWN position, in place (an empty
            # slot writes where the next prefill overwrites)
            k_pl = k_pl.at[li, sl, pos].set(k.reshape(slots, -1).astype(dt))
            v_pl = v_pl.at[li, sl, pos].set(v.reshape(slots, -1).astype(dt))
            if kernel:
                o = gqa_decode_attention(q, k_pl, v_pl, li, plan, bucket=s_b,
                                         scale=full.score_scale)
            else:
                keep = _m.full_keep(pos, jnp.arange(s_b, dtype=jnp.int32))
                o = _m.attend(full, q, k_pl[li, :, :s_b],
                              v_pl[li, :, :s_b], keep)
            return o, k_pl, v_pl

        def window_layer(p, q, k, v, pos, li, kw, vw):
            slots = q.shape[0]
            sl = jnp.arange(slots)
            kw = kw.at[li, sl, pos % w].set(k.reshape(slots, -1).astype(dt))
            vw = vw.at[li, sl, pos % w].set(v.reshape(slots, -1).astype(dt))
            # row r holds the last position <= pos that is r mod W: one in
            # the window iff it is not negative, iff r <= pos
            keep = jnp.arange(w, dtype=jnp.int32)[None, :] <= pos[:, None]
            o = _m.attend(win, q, kw[li], vw[li], keep, p["att_sink"])
            return o, kw, vw

        def fn(params, state, tokens, pos, active):
            # tokens/pos (slots,) int32; active (slots,) bool
            k_pl, v_pl, kw, vw = state
            pos_c = jnp.clip(pos, 0, a.max_seq - 1)
            # which key blocks each slot fetches: one plan a step
            plan = fetch_plan(pos_c, active, block_for(s_b)) \
                if kernel else None
            x = _m.embed(params, tokens)
            assignments = jnp.int32(0)
            hit = jnp.int32(0)
            fi = wi = 0
            for li in range(a.num_layers):
                p = _m.layer_params(params, li)
                kind = a.kinds[a.attn_types[li]]
                h = _m.rms_norm(x, p["ln1_gamma"], a.eps)
                q, k, v = _m.project(a, kind, p, h, pos_c)
                if a.attn_types[li] == _m.FULL:
                    o, k_pl, v_pl = full_layer(q, k, v, pos_c, fi, plan,
                                               k_pl, v_pl)
                    fi += 1
                else:
                    o, kw, vw = window_layer(p, q, k, v, pos_c, wi, kw, vw)
                    wi += 1
                x = x + _m.dense(o, p["att_o_weight"])
                y, counts = _m.ffn(a, p, _m.rms_norm(x, p["ln2_gamma"],
                                                     a.eps),
                                   a.mlp_types[li], active)
                x = x + y
                if counts is not None:
                    assignments = assignments + jnp.sum(counts)
                    hit = hit + jnp.sum((counts > 0).astype(jnp.int32))
            logits = _m.head(a, params, x)
            # finished/empty slots carry garbage rows; mask them so a
            # scheduler bug downstream surfaces as -inf-ish logits
            logits = jnp.where(active[:, None], logits, -1e30)
            picked = jnp.concatenate(
                [greedy_tokens(logits), jnp.stack([assignments, hit])])
            return picked, logits, (k_pl, v_pl, kw, vw)

        return jax.jit(fn, donate_argnums=(1,))


def serves(arch: Dict[str, Any]) -> bool:
    """Whether the description is this family's."""
    return arch.get("model_type") == "mimo_v2_flash"


def make(model, arch: Dict[str, Any]) -> WindowMoeDecoder:
    return WindowMoeDecoder(
        extract_params(model, dtype=arch.get("dtype", "bfloat16")), arch)
