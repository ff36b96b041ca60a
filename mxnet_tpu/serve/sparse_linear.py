"""The third family ``DecodeEngine`` serves: a stack whose layers are of
two kinds, block-sparse attention over selected key blocks and lightning
(linear) attention over a recurrent state (``models/sparse_linear.py``
holds the layers' mathematics, ``ops/pallas/sparse_decode_attention.py``
the kernel a decode step reads the selected blocks with).

**Cache.** Four planes under one ``KVCache`` (``kv_cache.Plane``). For the
sparse layers: ``k`` and ``v``, one row of ``kv_heads * d_head`` a
position (fewer key/value heads than query heads: a key/value head is a
lane block of the row), and ``kc``, the indexer's compressed keys, one row
a ``stride`` positions (``max_seq // stride`` rows a slot). For the
lightning layers: ``state``, ``(heads * d_head, d_head)`` float32 a slot
and layer, a plane that does not grow with ``max_seq`` (kind
``slot_state``). No position mask hides a stale state, so **the first
chunk of a prefill starts from zeros** instead of reading the slot
(``<name>_state_resets`` counts them); nothing is zeroed at acquire.

**Decode**, one program a sequence bucket ``S_b`` over the whole slot
array. A lightning layer updates and reads every slot's state in place. A
sparse layer appends the token's K and V rows, completes the compressed
key the token closes (every ``stride``-th position), scores the slot's
first ``S_b // stride`` compressed keys, selects ``topk`` blocks a
key/value head and reads those blocks and no more: K and V bytes do not
grow with the bucket. Behind the slots' tokens ``picked`` carries what
each sparse layer selected and, of what it then read, one number a head
(``followed``): what a comparison with a reference needs to see the
selection and the kernel, which the logits at published widths hardly
show (a sparse layer's output is a mean over ``topk x block`` values).

**Prefill in chunks**, programs a ``(chunk, context bucket)`` as the
second family has them. A lightning layer runs the chunked form from the
slot's state. A sparse layer appends the chunk's rows, completes the
compressed keys the chunk closes, and attends over the slot's first
``context`` rows in blocks of queries under the causal mask and, where the
context is longer than ``topk`` blocks, the selection as a block-level
mask.
"""
from __future__ import annotations

import collections
import zlib
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .. import profiler as _profiler
from ..base import MXNetError
from ..models import sparse_linear as _m
from .decode import (check_chunked, chunk_buckets, chunked_prefill_calls,
                     extract_params, greedy_tokens, over_query_blocks,
                     query_block)

__all__ = ["SparseLinearDecoder", "serves", "make"]

_SCORE_BLOCK_BYTES = 128 << 20      # a block of queries' float32 scores
_FOLLOWED_STEPS = 128               # decode steps a sequence's record keeps
_FOLLOWED_SEQUENCES = 128           # records kept, the newest prompts'


class SparseLinearDecoder:
    """Family of ``model_type`` ``minicpm_sala`` (see the module)."""

    def __init__(self, params: Dict[str, Any], arch: Dict[str, Any]):
        self.arch = self.cfg = _m.Arch(arch)
        _m.check_params(self.arch, params)
        self.params = params
        self.engine = None
        self.cache = None
        self.chunk = 0
        # ``followed``: the prompt's crc32 -> [(pos, blocks, attended)] of
        # its first decode steps, and the list a slot's steps go to
        self._followed: "collections.OrderedDict[int, list]" = \
            collections.OrderedDict()
        self._slot_steps: Dict[int, list] = {}

    # ------------------------------------------------------------ the cache
    def planes(self, max_seq: int, page: int, int8: bool):
        from .kv_cache import Plane
        if int8:
            raise MXNetError("serve sparse_linear: the cache has no int8 "
                             "mode")
        a = self.arch
        n_s, n_l = len(a.sparse_layers), len(a.lightning_layers)
        return [Plane("k", n_s, a.kv_row, a.dtype),
                Plane("v", n_s, a.kv_row, a.dtype),
                Plane("kc", n_s, a.kv_row, a.dtype,
                      tail=(max_seq // a.stride, a.kv_row)),
                Plane("state", n_l, 0, "float32",
                      tail=(a.l_heads * a.l_d, a.l_d), kind="slot_state")]

    def bind(self, engine) -> None:
        self.engine = engine
        self.cache = engine.cache
        self.chunk = int(engine.prefill_chunk)
        a = self.arch
        check_chunked(engine, self.chunk, "sparse_linear")
        if self.chunk % a.stride or any(b % a.block
                                        for b in engine.seq_buckets):
            raise ValueError("the prefill chunk is whole strides of %d and "
                             "a sequence bucket whole blocks of %d"
                             % (a.stride, a.block))
        self.chunk_buckets: List[int] = chunk_buckets(self.chunk, a.stride)

    def executable_bound(self) -> int:
        return (len(self.chunk_buckets) + 1) * len(self.engine.seq_buckets)

    def kernel_reads(self) -> bool:
        """Whether a decode step reads the selected blocks with the
        Pallas kernel: where the TPU can fetch a block as a tile. The
        gather (``models/sparse_linear.py::attend_selected``) is kept as
        the kernel's reference in the CPU tests, for sizes that are no
        tile; no served size takes it on the chip."""
        from ..ops.pallas.sparse_decode_attention import tiles
        a = self.arch
        return tiles(a.block, a.d_head, a.dtype)

    # ------------------------------------------------------------- dispatch
    def prefill_calls(self, prompt: np.ndarray, slot: int):
        """Chunk after chunk: ``((chunk, context), builder, args, span
        attributes)``; the first starts the slot's state from zeros."""
        _profiler.incr_counter(self.engine.name + "_state_resets")
        crc = _crc(prompt)
        self._followed.pop(crc, None)
        self._followed[crc] = self._slot_steps[int(slot)] = []
        if len(self._followed) > _FOLLOWED_SEQUENCES:
            self._followed.popitem(last=False)
        return chunked_prefill_calls(self.engine, self.chunk,
                                     self.chunk_buckets, self.build_prefill,
                                     prompt, slot)

    def step_picked(self, fetched, s_b, pos, active) -> np.ndarray:
        """The slots' tokens out of the decode program's ``picked``. Behind
        them lie every sparse layer's block numbers and the heads' means
        of what was read (``build_decode``): kept for the first
        ``_FOLLOWED_STEPS`` steps of a sequence. The step's
        blocks are counted here, from the positions: a resident sequence
        holds ``pos // block + 1`` blocks on every sparse layer and a step
        reads ``topk`` of them at most."""
        a, name = self.arch, self.engine.name
        held = pos[active].astype(np.int64) // a.block + 1
        layers = len(a.sparse_layers)
        _profiler.incr_counter(name + "_sparse_blocks_resident",
                               int(held.sum()) * layers)
        _profiler.incr_counter(name + "_sparse_blocks_read",
                               int(np.minimum(held, a.topk).sum()) * layers)
        slots = pos.shape[0]
        k = min(a.topk, s_b // a.block)
        n = slots * layers * a.kv_heads * k
        blocks = fetched[slots:slots + n].reshape(slots, layers, a.kv_heads,
                                                  k)
        attended = fetched[slots + n:].view(np.float32).reshape(
            slots, layers, a.heads)
        for slot in np.flatnonzero(active):
            steps = self._slot_steps[int(slot)]
            if len(steps) < _FOLLOWED_STEPS:
                steps.append((int(pos[slot]), blocks[slot].copy(),
                              attended[slot].copy()))
        return fetched[:slots]

    def followed(self, prompt) -> Optional[Dict[str, np.ndarray]]:
        """What the first decode steps of the sequence prefilled last from
        ``prompt`` selected and read, while it is among the
        ``_FOLLOWED_SEQUENCES`` newest: ``pos (n,)`` the steps' positions, ``blocks (n,
        sparse layers, kv_heads, K)`` the block numbers selected, -1 past
        the ``min(pos // block + 1, topk)`` that count, ``attended (n,
        sparse layers, heads)`` the mean over its lanes of each head's
        attention output. At most ``_FOLLOWED_STEPS`` steps; None if the
        record is gone or no step was taken yet."""
        a = self.arch
        steps = self._followed.get(_crc(prompt))
        if not steps:
            return None
        k = max(b.shape[-1] for _p, b, _o in steps)
        blocks = np.full((len(steps), len(a.sparse_layers), a.kv_heads, k),
                         -1, np.int32)
        for i, (p, b, _o) in enumerate(steps):
            valid = min(p // a.block + 1, b.shape[-1])
            blocks[i, :, :, :valid] = b[:, :, :valid]
        return {"pos": np.asarray([p for p, _b, _o in steps], np.int64),
                "blocks": blocks,
                "attended": np.stack([o for _p, _b, o in steps])}

    # ------------------------------------------------------------- programs
    def build_prefill(self, bucket: Tuple[int, int]):
        import jax
        import jax.numpy as jnp
        from jax import lax
        a = self.arch
        c_b, ctx_b = bucket
        blk = query_block(a.heads, c_b, ctx_b, _SCORE_BLOCK_BYTES)
        dt = jnp.dtype(a.dtype)
        n_blocks = ctx_b // a.block
        selects = n_blocks > a.topk

        def blocked(f, *xs):
            return over_query_blocks(f, c_b, blk, *xs)

        def sparse(p, u, pos, li, slot, start, k_pl, v_pl, kc_pl):
            q, k, v = _m.sparse_project(a, p, u)
            k, v = k.astype(dt), v.astype(dt)
            # the stride of rows before the chunk closes a compressed key
            # with the chunk's first stride (at start 0 there is none: what
            # is written then is overwritten below)
            before = lax.dynamic_slice(
                k_pl, (li, slot, jnp.maximum(start - a.stride, 0), 0),
                (1, 1, a.stride, a.kv_row))[0, 0]
            k_pl = lax.dynamic_update_slice(k_pl, k[None, None],
                                            (li, slot, start, 0))
            v_pl = lax.dynamic_update_slice(v_pl, v[None, None],
                                            (li, slot, start, 0))
            means = _m.compress_groups(a, jnp.concatenate([before, k], 0))
            closed = (0.5 * (means[:-1] + means[1:])).astype(dt)
            first = start // a.stride
            kc_pl = lax.dynamic_update_slice(
                kc_pl, closed[None, None, :1],
                (li, slot, jnp.maximum(first - 1, 0), 0))
            # the chunk's last key is closed by the next chunk or by the
            # decode steps; the row written for it here is not read before
            kc_pl = lax.dynamic_update_slice(
                kc_pl, jnp.concatenate([closed[1:], closed[-1:]], 0)[
                    None, None], (li, slot, first, 0))
            k_ctx = lax.dynamic_slice(k_pl, (li, slot, 0, 0),
                                      (1, 1, ctx_b, a.kv_row))[0, 0]
            v_ctx = lax.dynamic_slice(v_pl, (li, slot, 0, 0),
                                      (1, 1, ctx_b, a.kv_row))[0, 0]
            if not selects:
                o = blocked(lambda qb, tb: _m.attend_blocks(
                    a, qb, k_ctx, v_ctx, tb), q, pos)
            else:
                kc = lax.dynamic_slice(
                    kc_pl, (li, slot, 0, 0),
                    (1, 1, ctx_b // a.stride, a.kv_row))[0, 0].reshape(
                        -1, a.kv_heads, a.d_head)

                def rows(qb, tb):
                    s = _m.product("ngid,jgd->ngij", qb, kc) * a.score_scale
                    idx, n_valid = _m.select_blocks(a, s, tb, n_blocks)
                    return _m.attend_blocks(a, qb, k_ctx, v_ctx, tb, idx,
                                            n_valid)
                o = blocked(rows, q, pos)
            return _m.gated_out(p, o, u), k_pl, v_pl, kc_pl

        def fn(params, state, tokens, slot, start, true_len):
            # tokens (c_b,) int32; slot, start, true_len scalar int32
            k_pl, v_pl, kc_pl, st = state
            pos = start + jnp.arange(c_b, dtype=jnp.int32)
            n_real = jnp.clip(true_len - start, 0, c_b)
            x = _m.embed(a, params, tokens)
            si = li_l = 0
            for li, kind in enumerate(a.mixer_types):
                p = _m.layer_params(params, li)
                u = _m.rms_norm(x, p["ln1_gamma"], a.eps)
                if kind == _m.SPARSE:
                    y, k_pl, v_pl, kc_pl = sparse(p, u, pos, si, slot, start,
                                                  k_pl, v_pl, kc_pl)
                    si += 1
                else:
                    q, k, v = _m.lightning_project(a, p, u, pos)
                    held = lax.dynamic_slice(
                        st, (li_l, slot, 0, 0),
                        (1, 1) + st.shape[2:])[0, 0].reshape(
                            a.l_heads, a.l_d, a.l_d)
                    # a prompt's first chunk starts from zeros, whoever
                    # held the slot before
                    held = jnp.where(start == 0, 0.0, held)
                    new, o = _m.lightning_chunk(a, held, q, k, v, n_real)
                    st = lax.dynamic_update_slice(
                        st, new.reshape((1, 1) + st.shape[2:]),
                        (li_l, slot, 0, 0))
                    y = _m.lightning_out(a, p, o, u)
                    li_l += 1
                x = _m.mlp_half(a, p, x + a.residual_scale * y)
            # only the last REAL token goes through the head, if it lies
            # in this chunk (else the row read is not used by anyone)
            at = jnp.clip(true_len - 1 - start, 0, c_b - 1)
            logits = _m.head(a, params,
                             lax.dynamic_slice(x, (at, 0), (1, a.d)))[0]
            return greedy_tokens(logits), logits, (k_pl, v_pl, kc_pl, st)

        return jax.jit(fn, donate_argnums=(1,))

    def build_decode(self, s_b: int):
        import jax
        import jax.numpy as jnp
        from jax import lax
        from ..ops.pallas.sparse_decode_attention import \
            sparse_decode_attention
        a = self.arch
        dt = jnp.dtype(a.dtype)
        n_blocks = s_b // a.block
        n_kc = s_b // a.stride
        kernel = self.kernel_reads()

        def sparse(p, u, pos, active, li, k_pl, v_pl, kc_pl):
            slots = u.shape[0]
            sl = jnp.arange(slots)
            q, k, v = _m.sparse_project(a, p, u)
            # every slot's rows at ITS OWN position, in place (an empty
            # slot writes where the next prefill overwrites)
            k_pl = k_pl.at[li, sl, pos].set(k.astype(dt))
            v_pl = v_pl.at[li, sl, pos].set(v.astype(dt))
            # the compressed key this token closes, if it closes one
            closes = ((pos + 1) % a.stride == 0) & (pos + 1 >= a.kernel)
            j = jnp.maximum((pos + 1 - a.kernel) // a.stride, 0)
            span = jnp.maximum(pos + 1 - a.kernel, 0)[:, None] \
                + jnp.arange(a.kernel)[None, :]
            closed = jnp.mean(k_pl[li, sl[:, None], span].astype(
                jnp.float32), axis=1).astype(dt)
            kc_pl = kc_pl.at[li, sl, j].set(
                jnp.where(closes[:, None], closed, kc_pl[li, sl, j]))
            kc = kc_pl[li, :, :n_kc].reshape(slots, n_kc, a.kv_heads,
                                             a.d_head)
            s = _m.product("ngid,njgd->ngij", q, kc) * a.score_scale
            idx, n_valid = _m.select_blocks(a, s, pos, n_blocks)
            n_valid = jnp.where(active, n_valid, 0)
            if kernel:
                o = sparse_decode_attention(
                    q, k_pl, v_pl, li, idx, n_valid, pos, block=a.block,
                    scale=a.score_scale)
            else:
                o = _m.attend_selected(a, q, k_pl[li, :, :s_b],
                                       v_pl[li, :, :s_b], idx, n_valid, pos)
            # what the host keeps of the step (``step_picked``)
            seen = (idx, jnp.mean(o.reshape(slots, a.heads, a.d_head),
                                  axis=-1))
            return _m.gated_out(p, o, u), k_pl, v_pl, kc_pl, seen

        def fn(params, state, tokens, pos, active):
            # tokens/pos (slots,) int32; active (slots,) bool
            k_pl, v_pl, kc_pl, st = state
            slots = tokens.shape[0]
            pos_c = jnp.clip(pos, 0, a.max_seq - 1)
            x = _m.embed(a, params, tokens)
            si = li_l = 0
            seen = []
            for li, kind in enumerate(a.mixer_types):
                p = _m.layer_params(params, li)
                u = _m.rms_norm(x, p["ln1_gamma"], a.eps)
                if kind == _m.SPARSE:
                    y, k_pl, v_pl, kc_pl, here = sparse(
                        p, u, pos_c, active, si, k_pl, v_pl, kc_pl)
                    seen.append(here)
                    si += 1
                else:
                    q, k, v = _m.lightning_project(a, p, u, pos_c)
                    new, o = _m.lightning_step(
                        a, st[li_l].reshape(slots, a.l_heads, a.l_d, a.l_d),
                        q, k, v)
                    st = st.at[li_l].set(new.reshape(st.shape[1:]))
                    y = _m.lightning_out(a, p, o, u)
                    li_l += 1
                x = _m.mlp_half(a, p, x + a.residual_scale * y)
            logits = _m.head(a, params, x)
            # finished/empty slots carry garbage rows; mask them so a
            # scheduler bug downstream surfaces as -inf-ish logits
            logits = jnp.where(active[:, None], logits, -1e30)
            picked = jnp.concatenate(
                [greedy_tokens(logits),
                 jnp.stack([i for i, _o in seen], axis=1).reshape(-1),
                 lax.bitcast_convert_type(
                     jnp.stack([o for _i, o in seen], axis=1),
                     jnp.int32).reshape(-1)])
            return picked, logits, (k_pl, v_pl, kc_pl, st)

        return jax.jit(fn, donate_argnums=(1,))


def _crc(prompt) -> int:
    return zlib.crc32(np.ascontiguousarray(prompt, np.int32).tobytes())


def serves(arch: Dict[str, Any]) -> bool:
    """Whether the description is this family's."""
    return arch.get("model_type") == "minicpm_sala"


def make(model, arch: Dict[str, Any]) -> SparseLinearDecoder:
    return SparseLinearDecoder(
        extract_params(model, dtype=arch.get("dtype", "bfloat16")), arch)
