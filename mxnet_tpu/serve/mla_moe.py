"""The second family ``DecodeEngine`` serves: a block of latent attention
and routed experts beside a shared one (``models/mla_moe.py`` holds the
layer's mathematics, ``parallel/moe.py`` the expert layer).

**Cache.** One plane (``kv_cache.Plane``): ``latent``, one row ``[RMS(c_kv)
| rotated k_rope]`` a position on every layer, stored in whole 128-lane
tiles (zeros behind the row: ``Arch.row_stored``), in the dtype the
architecture states.

**Decode**, one program a sequence bucket ``S_b`` over the whole slot
array, as the dense family's: the new token's latent row is appended in
place at each slot's own position, and every layer runs the absorbed
attention of all its heads over the slots' first ``S_b`` rows. The step's
expert counts ride in two more entries behind the slots' chosen tokens.

**Prefill in chunks.** A prompt is cut into chunks of ``prefill_chunk``
tokens (the last padded to a power-of-two share of it); a chunk's program
is specialised on ``(chunk, context bucket)``: it appends the chunk's rows
to the slot and attends, per head over keys and values expanded from the
latent, over the slot's first ``context`` rows under the causal mask, in
blocks of queries so that no score tensor outgrows the chip.

Experts: every program routes over all the layer's experts and computes
the share of those held; empty slots and padding are routed nowhere.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

from .. import profiler as _profiler
from ..base import MXNetError
from ..models import mla_moe as _m
from .decode import (check_chunked, chunk_buckets, chunked_prefill_calls,
                     extract_params, greedy_tokens, over_query_blocks,
                     query_block)

__all__ = ["MlaMoeDecoder", "serves", "make"]

_SCORE_BLOCK_BYTES = 256 << 20      # a block of queries' float32 scores


class MlaMoeDecoder:
    """Family of ``model_type`` ``sarvam_mla`` (see the module)."""

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
            raise MXNetError("serve mla_moe: the latent cache has no int8 "
                             "mode")
        a = self.arch
        return [Plane("latent", a.num_layers, a.row_stored, a.dtype)]

    def bind(self, engine) -> None:
        self.engine = engine
        self.cache = engine.cache
        self.chunk = int(engine.prefill_chunk)
        check_chunked(engine, self.chunk, "mla_moe")
        self.chunk_buckets: List[int] = chunk_buckets(self.chunk)

    def executable_bound(self) -> int:
        return (len(self.chunk_buckets) + 1) * len(self.engine.seq_buckets)

    # ------------------------------------------------------------- dispatch
    def prefill_calls(self, prompt: np.ndarray, slot: int):
        """Chunk after chunk: ``((chunk, context), builder, args, span
        attributes)``."""
        return chunked_prefill_calls(self.engine, self.chunk,
                                     self.chunk_buckets, self.build_prefill,
                                     prompt, slot)

    def step_picked(self, fetched, s_b, pos, active) -> np.ndarray:
        """The slots' tokens out of the decode program's ``picked``; its
        last two entries are the step's assignments and experts hit,
        counted here."""
        name = self.engine.name
        _profiler.incr_counter(name + "_moe_assignments", int(fetched[-2]))
        _profiler.incr_counter(name + "_moe_experts_hit", int(fetched[-1]))
        # from the positions and the step's bucket, on the host: every
        # layer reads the bucket's rows of every slot, resident or not
        _profiler.incr_counter(name + "_mla_keys_resident", int(
            pos[active].astype(np.int64).sum() + active.sum()))
        _profiler.incr_counter(name + "_mla_keys_read",
                               int(s_b) * int(pos.shape[0]))
        return fetched[:-2]

    # ------------------------------------------------------------- programs
    def build_prefill(self, bucket: Tuple[int, int]):
        import jax
        import jax.numpy as jnp
        from jax import lax
        a = self.arch
        c_b, ctx_b = bucket
        blk = query_block(a.heads, c_b, ctx_b, _SCORE_BLOCK_BYTES)
        dt = jnp.dtype(a.dtype)

        def blocked(f, *xs):
            return over_query_blocks(f, c_b, blk, *xs)

        def fn(params, state, tokens, slot, start, true_len):
            # tokens (c_b,) int32; slot, start, true_len scalar int32
            latent, = state
            pos = start + jnp.arange(c_b, dtype=jnp.int32)
            real = pos < true_len
            causal = jnp.arange(ctx_b, dtype=jnp.int32)[None, :] \
                <= pos[:, None]
            x = params["tok_embed_weight"][tokens].astype(jnp.float32)
            for li in range(a.num_layers):
                p = _m.layer_params(params, li)
                h = _m.rms_norm(x, p["ln1_gamma"], a.eps)
                q_nope, q_rope, row = _m.mla_project(a, p, h, pos)
                # the chunk's rows go into the slot, then the slot's first
                # ctx_b rows are what the chunk attends over
                latent = lax.dynamic_update_slice(
                    latent, _m.stored_row(a, row, dt)[None, None],
                    (li, slot, start, 0))
                rows = lax.dynamic_slice(
                    latent, (li, slot, 0, 0),
                    (1, 1, ctx_b, a.row_stored))[0, 0]
                k_nope, v = _m.expand_keys_values(a, p, rows)
                att = blocked(
                    lambda qn, qr, ok: _m.attend_per_head(
                        a, qn, qr, k_nope, v, rows[:, a.kv_rank:a.row], ok),
                    q_nope, q_rope, causal)
                x = x + _m.dense(att, p["att_o_weight"])
                y, _counts = _m.ffn(
                    a, p, _m.rms_norm(x, p["ln2_gamma"], a.eps),
                    a.mlp_types[li], real)
                x = x + y
            # only the last REAL token goes through the head, if it lies
            # in this chunk (else the row read is not used by anyone)
            at = jnp.clip(true_len - 1 - start, 0, c_b - 1)
            last = lax.dynamic_slice(x, (at, 0), (1, a.d))
            logits = _m.dense(_m.rms_norm(last, params["final_ln_gamma"],
                                          a.eps), params["lm_head_weight"])
            return greedy_tokens(logits[0]), logits[0], (latent,)

        return jax.jit(fn, donate_argnums=(1,))

    def build_decode(self, s_b: int):
        import jax
        import jax.numpy as jnp
        a = self.arch
        dt = jnp.dtype(a.dtype)

        def fn(params, state, tokens, pos, active):
            # tokens/pos (slots,) int32; active (slots,) bool
            latent, = state
            slots = tokens.shape[0]
            sl = jnp.arange(slots)
            pos_c = jnp.clip(pos, 0, a.max_seq - 1)
            valid = jnp.arange(s_b, dtype=jnp.int32)[None, :] \
                <= pos_c[:, None]                       # (slots, S_b)
            x = params["tok_embed_weight"][tokens].astype(jnp.float32)
            assignments = jnp.int32(0)
            hit = jnp.int32(0)
            for li in range(a.num_layers):
                p = _m.layer_params(params, li)
                h = _m.rms_norm(x, p["ln1_gamma"], a.eps)
                q_nope, q_rope, row = _m.mla_project(a, p, h, pos_c)
                # every slot's row at ITS OWN position, in place (an empty
                # slot writes where the next prefill overwrites)
                latent = latent.at[li, sl, pos_c].set(
                    _m.stored_row(a, row, dt))
                q = _m.absorb_query(a, p, q_nope, q_rope)
                x = x + _m.expand_values(a, p, _m.attend(
                    a, q, latent[li, :, :s_b], valid))
                y, counts = _m.ffn(
                    a, p, _m.rms_norm(x, p["ln2_gamma"], a.eps),
                    a.mlp_types[li], active)
                x = x + y
                if counts is not None:
                    assignments = assignments + jnp.sum(counts)
                    hit = hit + jnp.sum((counts > 0).astype(jnp.int32))
            logits = _m.dense(_m.rms_norm(x, params["final_ln_gamma"],
                                          a.eps), params["lm_head_weight"])
            # finished/empty slots carry garbage rows; mask them so a
            # scheduler bug downstream surfaces as -inf-ish logits
            logits = jnp.where(active[:, None], logits, -1e30)
            picked = jnp.concatenate(
                [greedy_tokens(logits), jnp.stack([assignments, hit])])
            return picked, logits, (latent,)

        return jax.jit(fn, donate_argnums=(1,))


def serves(arch: Dict[str, Any]) -> bool:
    """Whether the description is this family's."""
    return arch.get("model_type") == "sarvam_mla"


def make(model, arch: Dict[str, Any]) -> MlaMoeDecoder:
    return MlaMoeDecoder(
        extract_params(model, dtype=arch.get("dtype", "bfloat16")), arch)
