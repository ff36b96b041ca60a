"""Prefill/decode program split for generative serving.

The zoo transformer (``models/transformer.py``) trains as a Symbol over
fixed ``(N, T)`` geometry; autoregressive serving needs two different
programs, both drawn from a FINITE bucket universe so steady-state
decode is a counter-asserted zero-recompile regime:

* **prefill** — one jitted program per pow2 prompt bucket ``T_b``:
  runs the full causal forward on one padded prompt (dense attention at
  short buckets, :func:`~mxnet_tpu.parallel.ring_attention
  .chunked_causal_attention` — the ring kernel's online-softmax block
  loop, single-device — past ``prefill_chunk``), writes the prompt's
  K/V rows into the cache slot IN-PROGRAM as the projection made them
  (one contiguous ``(T_b, H*d)`` ``dynamic_update_slice`` at
  ``[layer, slot, 0:T_b, :]``; the state operand is donated, so the
  update is in-place on TPU), and returns only the last real token's
  logits (one ``(D,)`` row through the LM head, not a ``(T_b, V)``
  matmul) and, beside them, their argmax.
* **decode** — ONE jitted step per sequence bucket ``S_b`` over the
  WHOLE slot array: embed the freshest token of every resident
  sequence, append its K/V row at the per-slot write position with one
  in-place update of the donated whole array
  (``K.at[layer, arange(slots), pos].set(k_new)``; finished/empty slots
  write into reclaimed space that the next prefill overwrites — a
  masked no-op by construction), attend to rows ``[0:S_b]`` with
  per-slot length masking, and return ``(slots, V)`` logits and their
  ``(slots,)`` argmax.

Every program returns ``(picked, logits, state)``: the greedy token is
chosen inside the program, over the float32 logits the host would see,
and the engine fetches ``picked`` alone. The logits leave the device only
for a sequence that samples (``temperature > 0``): the scheduler says so
step by step.

A decode step is dispatched and collected apart
(:meth:`DecodeEngine.decode_dispatch`, :meth:`DecodeEngine.decode_collect`),
so that step N+1 can run while the host collects step N. Its input
tokens need not reach the host first: the engine runs the family's
decode program behind one ``where`` that takes, for a slot the host
gives no token (``-1``), the token the previous step picked, still on
the device.

The cache is ``(layers, slots, max_seq, H*d)`` (``kv_cache.py`` says
why): no program takes a layer's slab out of it or puts one back. The
float32 cache on one device is read where it lies by the Pallas kernel
of ``ops/pallas/decode_attention.py`` (only the key blocks a sequence
has are fetched, none for a free slot); the int8 and the mesh-sharded
cache read ``K[layer, :, :S_b]`` through XLA, heads split out of the
row by a reshape — one layout, and the read chosen by what the engine
observes of its cache.

The engine has two halves. :class:`DecodeEngine` is the generic one: the
bucket tables, the dispatch under the CompileCache counters, the spans
and counters. What it serves is a *family*: an object
that knows one block's parameters, says which planes its cache holds
(``kv_cache.Plane``) and builds the prefill and decode programs over
them. :class:`DenseDecoder`, below, is the first family (the zoo
transformer, everything this docstring has described so far);
``serve/mla_moe.py`` holds the second (latent attention over a latent
cache plane, routed experts beside a shared one), whose prompts prefill in
chunks, each appended to the cache and attending over it;
``serve/sparse_linear.py`` the third (block-sparse attention over selected
key blocks beside lightning layers whose recurrent state is a plane a
slot); ``serve/window_moe.py`` the fourth (full and windowed grouped-query
attention, the windows a ring a slot, routed experts behind them).
``docs/architecture/serving_families.md`` says what a family owes
the engine.

The executable set is exactly |prompt buckets| + |decode buckets| (the
server's CompileCache counters assert it). A restarted server builds
each program again and reads its compiled code from JAX's persistent
compilation cache (``config._apply_import_knobs``), which is always on.

The decode forward is a pure-jax reimplementation of the Symbol graph,
consuming the SAME parameter dict ``Module.get_params()`` returns —
parity with the training forward is pinned by
``tests/test_serve_decode.py`` (softmax outputs at the last real
position, f32 atol 1e-4). int8 KV mode quantizes pages on write with
requantize-on-scale-growth (fresh scale on page entry, so a page never
inherits a stale tenant's dynamic range) and dequantizes with one
broadcast multiply per read — tolerance documented in the same test.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import profiler as _profiler
from ..base import MXNetError
from ..obs import compiles as _obs_compiles

__all__ = ["DecodeConfig", "DecodeEngine", "DenseDecoder", "extract_params",
           "config_from_params", "sample_token", "PickedRow",
           "greedy_tokens", "family_for", "DecodeFlight"]

_LN_EPS = 1e-5          # ops/nn.py layer_norm default


class DecodeConfig:
    """Static geometry of the served transformer (shapes the programs
    specialize on)."""

    __slots__ = ("num_layers", "d_model", "n_heads", "d_head", "d_ff",
                 "vocab_size", "max_seq")

    def __init__(self, num_layers: int, d_model: int, n_heads: int,
                 d_ff: int, vocab_size: int, max_seq: int):
        if d_model % n_heads:
            raise ValueError("d_model %d not divisible by n_heads %d"
                             % (d_model, n_heads))
        self.num_layers = int(num_layers)
        self.d_model = int(d_model)
        self.n_heads = int(n_heads)
        self.d_head = int(d_model) // int(n_heads)
        self.d_ff = int(d_ff)
        self.vocab_size = int(vocab_size)
        self.max_seq = int(max_seq)


def extract_params(source, dtype: Optional[str] = None) -> Dict[str, Any]:
    """Normalize the served parameters to ``name -> jnp array``: float32
    by default, or the ``dtype`` the architecture states (a leaf that is
    already a device array in that dtype is taken as it is, not carried
    through the host).

    Accepts a bound Module (``get_params()``), an ``(arg, aux)`` tuple,
    or a plain dict of NDArray/numpy arrays — the exact naming the zoo
    transformer Symbol binds (``tok_embed_weight``,
    ``layer%d_att_qkv_weight``, ...).
    """
    import jax.numpy as jnp
    from .. import ndarray as nd_mod
    if hasattr(source, "get_params"):
        arg, aux = source.get_params()
        merged = dict(arg)
        merged.update(aux or {})
    elif isinstance(source, tuple) and len(source) == 2:
        merged = dict(source[0])
        merged.update(source[1] or {})
    else:
        merged = dict(source)
    want = jnp.dtype(dtype or "float32")
    out = {}
    for name, arr in merged.items():
        if dtype is not None and isinstance(arr, jnp.ndarray) \
                and arr.dtype == want:
            out[name] = arr
            continue
        if isinstance(arr, nd_mod.NDArray):
            arr = arr.asnumpy()
        out[name] = jnp.asarray(np.asarray(arr), want)
    return out


def config_from_params(params: Dict[str, Any],
                       n_heads: int) -> DecodeConfig:
    """Infer the transformer geometry from the bound parameter shapes
    (head count is not shape-derivable — the caller states it)."""
    need = ("tok_embed_weight", "pos_embed_weight", "lm_head_weight",
            "layer0_ff1_weight")
    for k in need:
        if k not in params:
            raise MXNetError(
                "serve decode: parameter %r missing — GenerativeServer "
                "serves the zoo transformer naming convention "
                "(models/transformer.py); found %d params"
                % (k, len(params)))
    vocab, d_model = params["tok_embed_weight"].shape
    max_seq = params["pos_embed_weight"].shape[0]
    d_ff = params["layer0_ff1_weight"].shape[0]
    n_layers = 0
    while ("layer%d_att_qkv_weight" % n_layers) in params:
        n_layers += 1
    return DecodeConfig(n_layers, int(d_model), int(n_heads), int(d_ff),
                        int(vocab), int(max_seq))


class PickedRow:
    """A row of logits that stayed on the device: the host has its width
    and its argmax, taken inside the program (:func:`greedy_tokens`).
    What :func:`sample_token` is given for a greedy sequence."""

    __slots__ = ("token", "width")

    def __init__(self, token, width: int):
        self.token = int(token)
        self.width = width

    def __len__(self) -> int:
        return self.width


def sample_token(logits, temperature: float = 0.0,
                 rng: Optional[np.random.Generator] = None) -> int:
    """The scheduler's sampler, called for every token: greedy at
    ``temperature=0`` (deterministic — the batch-composition-invariance
    test keys on it; a :class:`PickedRow` gives the device's choice, an
    array its argmax), else softmax sampling on the host from the
    caller's per-request generator."""
    if temperature <= 0.0:
        return logits.token if isinstance(logits, PickedRow) \
            else int(np.argmax(logits))
    z = logits.astype(np.float64) / float(temperature)
    z -= z.max()
    p = np.exp(z)
    p /= p.sum()
    gen = rng or np.random.default_rng()
    return int(gen.choice(len(p), p=p))


# --------------------------------------------------------------- forward


def _ln(x, gamma, beta):
    """LayerNorm matching ops/nn.py semantics: f32 one-pass stats."""
    import jax.numpy as jnp
    from jax import lax
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    msq = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    var = jnp.maximum(msq - jnp.square(mean), 0.0)
    return (x32 - mean) * lax.rsqrt(var + _LN_EPS) * gamma + beta


def _fc(x, params, name):
    return x @ params[name + "_weight"].T + params[name + "_bias"]


def _qkv_rows(h, params, pfx, d_model):
    """Q, K and V rows ``(T, D)`` of a prompt, each from its own third
    of the fused projection's weight. Three products, so that K and V
    come out contiguous and go into the cache as they are: sliced out
    of one ``(T, 3D)`` result they are strided copies, and at bucket 256
    the TPU compiler then tiled three layers' FFN fusions out of HBM
    (17.9 ms a prefill where its neighbours take 5 to 6)."""
    w = params[pfx + "_att_qkv_weight"].reshape(3, d_model, d_model)
    b = params[pfx + "_att_qkv_bias"].reshape(3, d_model)
    return [h @ w[i].T + b[i] for i in range(3)]


def _quantize_pages(x, page: int, n_heads: int):
    """(T, H*d) f32 rows -> (int8 (T, H*d), scales (H, T // page)) — one
    symmetric scale per (head, page), taken over the page's positions
    and the head's d_head lanes of the row."""
    import jax.numpy as jnp
    t, row = x.shape
    pg = x.reshape(t // page, page, n_heads, row // n_heads)
    scale = jnp.maximum(jnp.max(jnp.abs(pg), axis=(1, 3)) / 127.0, 1e-8)
    q = jnp.clip(jnp.round(pg / scale[:, None, :, None]), -127, 127)
    return q.reshape(t, row).astype(jnp.int8), scale.T


def greedy_tokens(logits):
    """The greedy token of each row of float32 logits, int32: the first
    index of the largest, as ``np.argmax`` of the same array gives it."""
    import jax.numpy as jnp
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


class DenseDecoder:
    """The first family: the dense pre-LayerNorm decoder of
    ``models/transformer.py``, found by parameter name. Its cache planes
    are K and V rows of ``n_heads * d_head`` (int8: and their scales); a
    prompt prefills in one program at its bucket."""

    def __init__(self, params: Dict[str, Any], n_heads: int):
        self.params = params
        self.cfg = config_from_params(params, n_heads)
        self.engine = None
        self.cache = None
        self.prefill_chunk = 512
        self._multi_device = False

    def planes(self, max_seq: int, page: int, int8: bool):
        from .kv_cache import dense_planes
        return dense_planes(self.cfg.num_layers, self.cfg.n_heads,
                            self.cfg.d_head, max_seq, page, int8)

    def bind(self, engine) -> None:
        """Take from the engine what the programs specialize on."""
        self.engine = engine
        self.cache = engine.cache
        self.prefill_chunk = engine.prefill_chunk
        self._multi_device = engine._multi_device

    def executable_bound(self) -> int:
        return len(self.engine.prompt_buckets) + len(self.engine.seq_buckets)

    def prefill_calls(self, prompt: np.ndarray, slot: int):
        """One program for the whole prompt, padded to its bucket."""
        n = int(prompt.shape[0])
        t_b = self.engine.prompt_bucket(n)
        tokens = np.zeros((t_b,), np.int32)
        tokens[:n] = np.asarray(prompt, np.int32)
        yield t_b, self.build_prefill, \
            (tokens, np.int32(slot), np.int32(n)), \
            {"chunk": t_b, "context": t_b}

    def step_picked(self, fetched, s_b, pos, active) -> np.ndarray:
        """The slots' tokens out of the decode program's fetched
        ``picked``, the step counted: here they are all of it."""
        if self.kernel_reads(s_b):
            _profiler.incr_counter(self.engine.name
                                   + "_decode_attn_kernel_steps")
        return fetched

    # ---------------------------------------------------------- builders
    def _attention_full(self, q, k, v):
        """Causal attention over one prompt: q/k/v (H, T, d)."""
        import jax.numpy as jnp
        t = q.shape[1]
        if t > self.prefill_chunk and t % self.prefill_chunk == 0:
            from ..parallel.ring_attention import chunked_causal_attention
            return chunked_causal_attention(q[None], k[None], v[None],
                                            chunk=self.prefill_chunk)[0]
        scale = 1.0 / np.sqrt(self.cfg.d_head)
        s = jnp.einsum("htd,hkd->htk", q, k,
                       preferred_element_type=jnp.float32) * scale
        pos = jnp.arange(t)
        future = (pos[None, :] > pos[:, None]).astype(jnp.float32)
        s = s + future[None] * -1e9      # the training graph's causal bias
        att = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        att = att / jnp.sum(att, axis=-1, keepdims=True)
        return jnp.einsum("htk,hkd->htd", att, v)

    def build_prefill(self, t_b: int):
        import jax
        import jax.numpy as jnp
        from jax import lax
        cfg = self.cfg
        int8 = self.cache.int8
        page = self.cache.page

        def write_layer(state, li, slot, k, v):
            # k/v: (T_b, H*d) rows -> cache block [li, slot, 0:T_b, :],
            # one contiguous update each (int8: and the pages' scales)
            new = (k, v)
            if int8:
                (k, ks), (v, vs) = (_quantize_pages(x, page, cfg.n_heads)
                                    for x in new)
                new = (k, v, ks, vs)
            return tuple(
                lax.dynamic_update_slice(a, n[None, None], (li, slot, 0, 0))
                for a, n in zip(state, new))

        def fn(params, state, tokens, slot, true_len):
            # tokens (T_b,) int32; slot, true_len scalar int32
            x = params["tok_embed_weight"][tokens] \
                + params["pos_embed_weight"][:t_b]          # (T_b, D)
            for li in range(cfg.num_layers):
                pfx = "layer%d" % li
                h = _ln(x, params[pfx + "_ln1_gamma"],
                        params[pfx + "_ln1_beta"])
                rows = _qkv_rows(h, params, pfx, cfg.d_model)
                # the cache takes the rows as the projections made them
                state = write_layer(state, li, slot, rows[1], rows[2])
                q, k, v = (
                    r.reshape(t_b, cfg.n_heads, cfg.d_head)
                    .transpose(1, 0, 2) for r in rows)      # (H, T_b, d)
                ctx = self._attention_full(q, k, v)         # (H, T_b, d)
                ctx = ctx.transpose(1, 0, 2).reshape(t_b, cfg.d_model)
                x = x + _fc(ctx, params, pfx + "_att_proj")
                h2 = _ln(x, params[pfx + "_ln2_gamma"],
                         params[pfx + "_ln2_beta"])
                h2 = jax.nn.relu(_fc(h2, params, pfx + "_ff1"))
                x = x + _fc(h2, params, pfx + "_ff2")
            # only the last REAL token goes through the LM head
            row = lax.dynamic_slice(
                x, (jnp.maximum(true_len - 1, 0), 0), (1, cfg.d_model))
            row = _ln(row, params["final_ln_gamma"],
                      params["final_ln_beta"])
            logits = _fc(row, params, "lm_head")[0]         # (V,)
            return greedy_tokens(logits), logits, state

        return jax.jit(fn, donate_argnums=(1,))

    def _read_bucket(self, state, li: int, s_b: int):
        """Rows [0:S_b] of layer ``li``, dequantized, heads split out of
        the row by a reshape: (slots, S_b, H, d) f32 pair."""
        import jax.numpy as jnp
        cfg = self.cfg
        page = self.cache.page
        heads = (state[0].shape[1], s_b, cfg.n_heads, cfg.d_head)
        k = state[0][li, :, :s_b]                       # (slots, S_b, H*d)
        v = state[1][li, :, :s_b]
        if not self.cache.int8:
            return k.reshape(heads), v.reshape(heads)
        pb = s_b // page

        def deq(q, sc):
            # sc (slots, H, pb): one scale per (head, page)
            f = q.astype(jnp.float32).reshape(heads[0], pb, page,
                                              *heads[2:])
            f = f * sc.transpose(0, 2, 1)[:, :, None, :, None]
            return f.reshape(heads)

        return (deq(k, state[2][li, :, :, :pb]),
                deq(v, state[3][li, :, :, :pb]))

    def kernel_reads(self, s_b: int) -> bool:
        """Whether bucket ``s_b``'s decode program reads the cache with
        the Pallas kernel: the float32 cache on one device, in key
        blocks the TPU can tile. int8 pages are dequantized by the XLA
        read, and a Mosaic call under a multi-device jit would need a
        ``shard_map`` the engine does not give it."""
        from ..ops.pallas.decode_attention import block_for
        return (not self.cache.int8 and not self._multi_device
                and block_for(s_b) % 8 == 0)

    def build_decode(self, s_b: int):
        import jax
        import jax.numpy as jnp
        from ..ops.pallas.decode_attention import (block_for,
                                                   decode_attention,
                                                   fetch_plan)
        cfg = self.cfg
        int8 = self.cache.int8
        page = self.cache.page
        scale = 1.0 / np.sqrt(cfg.d_head)
        kernel = self.kernel_reads(s_b)

        def write_i8(cache, scales, li, new, pos):
            # cache (L, slots, S, H*d) int8, scales (L, slots, H, pages),
            # new (slots, H*d) f32. requantize-on-write: page entry
            # resets the scale (a fresh page must not inherit a stale
            # tenant's dynamic range); in-page growth merges scales
            # upward and requantizes the page — with an unchanged scale
            # the round-trip is exact. Only each slot's one page moves.
            slots = new.shape[0]
            sl = jnp.arange(slots)
            pi = pos // page
            off = pos % page
            rows = pi[:, None] * page + jnp.arange(page)[None, :]
            pg = cache[li, sl[:, None], rows]           # (slots, page, H*d)
            old = scales[li, sl, :, pi]                 # (slots, H)
            entering = (off == 0)[:, None]
            new = new.reshape(slots, cfg.n_heads, cfg.d_head)
            needed = jnp.maximum(
                jnp.max(jnp.abs(new), axis=-1) / 127.0, 1e-8)
            new_scale = jnp.where(entering, needed,
                                  jnp.maximum(old, needed))
            deq = pg.astype(jnp.float32).reshape(
                slots, page, cfg.n_heads, cfg.d_head) \
                * old[:, None, :, None]
            deq = jnp.where(entering[:, None, :, None], 0.0, deq)
            deq = deq.at[sl, off].set(new)
            q = jnp.clip(jnp.round(deq / new_scale[:, None, :, None]),
                         -127, 127).astype(jnp.int8)
            return (cache.at[li, sl[:, None], rows].set(
                        q.reshape(slots, page, -1)),
                    scales.at[li, sl, :, pi].set(new_scale))

        def write_token(state, li, k_new, v_new, pos):
            # k_new/v_new (slots, H*d); pos (slots,). One in-place update
            # of the donated whole array for all slots: every sequence
            # writes its row at ITS OWN position (empty slots write into
            # reclaimed space the next prefill overwrites: a no-op by
            # construction). No layer slab is taken out or put back.
            if int8:
                nk, nks = write_i8(state[0], state[2], li, k_new, pos)
                nv, nvs = write_i8(state[1], state[3], li, v_new, pos)
                return (nk, nv, nks, nvs)
            sl = jnp.arange(k_new.shape[0])
            return (state[0].at[li, sl, pos].set(k_new),
                    state[1].at[li, sl, pos].set(v_new))

        def attend(q, state, li, pos, plan):
            # q (slots, H*d) over keys 0..pos inclusive (the token just
            # written attends to itself, matching the training graph)
            if kernel:
                return decode_attention(q, state[0], state[1], li, plan,
                                        n_heads=cfg.n_heads, bucket=s_b,
                                        scale=scale)
            kb, vb = self._read_bucket(state, li, s_b)
            s = jnp.einsum("shd,skhd->shk",
                           q.reshape(-1, cfg.n_heads, cfg.d_head), kb,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.arange(s_b)[None, :] <= pos[:, None]
            s = jnp.where(mask[:, None, :], s, -1e9)
            att = jax.nn.softmax(s, axis=-1)
            ctx = jnp.einsum("shk,skhd->shd", att, vb)
            return ctx.reshape(-1, cfg.d_model)

        def fn(params, state, tokens, pos, active):
            # tokens/pos (slots,) int32; active (slots,) bool
            pos_c = jnp.clip(pos, 0, cfg.max_seq - 1)
            # which key blocks each slot fetches: one plan a step
            plan = fetch_plan(pos_c, active, block_for(s_b)) \
                if kernel else None
            x = params["tok_embed_weight"][tokens] \
                + params["pos_embed_weight"][pos_c]         # (slots, D)
            for li in range(cfg.num_layers):
                pfx = "layer%d" % li
                h = _ln(x, params[pfx + "_ln1_gamma"],
                        params[pfx + "_ln1_beta"])
                qkv = _fc(h, params, pfx + "_att_qkv")
                qkv = qkv.reshape(-1, 3, cfg.d_model)
                state = write_token(state, li, qkv[:, 1], qkv[:, 2], pos_c)
                ctx = attend(qkv[:, 0], state, li, pos_c, plan)
                x = x + _fc(ctx, params, pfx + "_att_proj")
                h2 = _ln(x, params[pfx + "_ln2_gamma"],
                         params[pfx + "_ln2_beta"])
                h2 = jax.nn.relu(_fc(h2, params, pfx + "_ff1"))
                x = x + _fc(h2, params, pfx + "_ff2")
            x = _ln(x, params["final_ln_gamma"], params["final_ln_beta"])
            logits = _fc(x, params, "lm_head")              # (slots, V)
            # finished/empty slots carry garbage rows; mask them so a
            # scheduler bug downstream surfaces as -inf-ish logits, not
            # a plausible token
            logits = jnp.where(active[:, None], logits, -1e30)
            return greedy_tokens(logits), logits, state

        return jax.jit(fn, donate_argnums=(1,))


# ------------------------------------------- families that prefill in chunks


def chunk_buckets(chunk: int, multiple: int = 1) -> List[int]:
    """The sizes a prompt's last chunk is padded to: power-of-two shares
    of ``chunk`` down to an eighth, those that are whole ``multiple``s."""
    return sorted({c for c in (max(1, chunk >> s) for s in (3, 2, 1, 0))
                   if c % multiple == 0})


def check_chunked(engine, chunk: int, who: str) -> None:
    """What a family that prefills in chunks asks of its engine: one
    device, slots of whole chunks, buckets that reach a slot's end."""
    cache = engine.cache
    if engine._multi_device:
        raise MXNetError("serve %s: a sharded cache is not supported" % who)
    if cache.max_seq % chunk:
        raise ValueError("max_seq %d not a multiple of the prefill "
                         "chunk %d" % (cache.max_seq, chunk))
    if engine.seq_buckets[-1] < cache.max_seq:
        raise ValueError("the sequence buckets end at %d, a slot at %d"
                         % (engine.seq_buckets[-1], cache.max_seq))


def chunked_prefill_calls(engine, chunk: int, buckets: List[int], builder,
                          prompt: np.ndarray, slot: int):
    """A prompt chunk after chunk, as a family's ``prefill_calls`` yields
    them: ``((chunk, context), builder, (tokens, slot, start, length),
    span attributes)``, the last chunk padded to its bucket, the context
    the sequence bucket that holds the chunk's end."""
    n = int(prompt.shape[0])
    for start in range(0, n, chunk):
        left = min(chunk, n - start)
        c_b = next(c for c in buckets if left <= c)
        ctx_b = engine.seq_bucket(start + c_b)
        tokens = np.zeros((c_b,), np.int32)
        tokens[:left] = np.asarray(prompt[start:start + left], np.int32)
        yield (c_b, ctx_b), builder, \
            (tokens, np.int32(slot), np.int32(start), np.int32(n)), \
            {"chunk": c_b, "context": ctx_b}


def query_block(heads: int, c_b: int, ctx_b: int, budget: int) -> int:
    """Queries a block, so that a block's float32 scores over the context
    stay under ``budget`` bytes: a power of two that divides the chunk."""
    blk = max(1, budget // (heads * ctx_b * 4))
    blk = 1 << (blk.bit_length() - 1)
    while c_b % blk:
        blk >>= 1
    return min(blk, c_b)


def over_query_blocks(f, c_b: int, blk: int, *xs):
    """``f`` over blocks of ``blk`` of the chunk's ``c_b`` queries (the
    leading axis of every ``xs``), rows put together."""
    from jax import lax
    if blk == c_b:
        return f(*xs)
    cut = [x.reshape((c_b // blk, blk) + x.shape[1:]) for x in xs]
    out = lax.map(lambda b: f(*b), tuple(cut))
    return out.reshape((c_b,) + out.shape[2:])


def family_for(model, n_heads: Optional[int] = None,
               arch: Optional[Dict[str, Any]] = None):
    """The family that serves ``model``: the dense decoder when only
    ``n_heads`` is told, else the one that claims ``arch``."""
    if arch is None:
        if n_heads is None:
            raise ValueError("GenerativeServer needs n_heads (the dense "
                             "decoder) or arch (a described block)")
        return DenseDecoder(extract_params(model), n_heads)
    # they import this module
    from . import mla_moe, sparse_linear, window_moe
    for family in (mla_moe, sparse_linear, window_moe):
        if family.serves(arch):
            return family.make(model, arch)
    raise ValueError("GenerativeServer serves no model_type %r"
                     % (arch.get("model_type"),))


class DecodeFlight:
    """A decode step dispatched and not yet collected: the device's
    ``picked`` and, if they were asked for, its logits; the step's bucket,
    and the positions and active mask it ran with, the engine's own
    copies. A row the scheduler no longer wants (its sequence ended while
    the step ran) is cleared from ``active`` before the collect, so that
    no family hook counts or keeps it."""

    __slots__ = ("picked", "logits", "bucket", "pos", "active", "waited")

    def __init__(self, picked, logits, bucket: int, pos: np.ndarray,
                 active: np.ndarray):
        self.picked = picked
        self.logits = logits
        self.bucket = bucket
        self.pos = pos
        self.active = active
        # seconds the collect blocked on the device's result
        self.waited = 0.0


class DecodeEngine:
    """The program table: builds and dispatches the
    per-bucket prefill/decode executables of one ``family`` over one
    :class:`KVCache` that holds the family's planes.

    NOT thread-safe by design: every method runs on the owning
    GenerativeServer's scheduler thread (the cache state tuple is
    donated through each dispatch and re-bound from the result — a
    second dispatcher would race the donation).
    """

    def __init__(self, family, cache, compile_cache, name: str = "serve",
                 prompt_buckets: Optional[Sequence[int]] = None,
                 seq_buckets: Optional[Sequence[int]] = None,
                 prefill_chunk: int = 512):
        self.family = family
        self.params = family.params
        self.cfg = family.cfg
        self.cache = cache
        self.compile_cache = compile_cache
        self.name = name
        self.prefill_chunk = int(prefill_chunk)
        from .bucketing import decode_buckets as _ladder
        self.seq_buckets: List[int] = list(
            seq_buckets if seq_buckets is not None
            else _ladder(cache.max_seq, cache.page))
        self.prompt_buckets: List[int] = list(
            prompt_buckets if prompt_buckets is not None
            else self.seq_buckets)
        for b in self.prompt_buckets:
            if b % cache.page:
                raise ValueError("prompt bucket %d not a multiple of the "
                                 "kv page %d" % (b, cache.page))
        # a sharded cache is read through XLA, not by the Pallas kernel
        self._multi_device = cache._sharding is not None
        # the logits' width, for rows that are not fetched
        self.vocab = int(self.params["lm_head_weight"].shape[0])
        # the slots' tokens the last decode step picked, on the device
        self._picked_last = None
        # programs built by a dispatch: one that changes this compiled
        self.programs_built = 0
        self.family.bind(self)

    def executable_bound(self) -> int:
        return self.family.executable_bound()

    def prompt_bucket(self, n: int) -> int:
        for b in self.prompt_buckets:
            if n <= b:
                return b
        raise MXNetError("prompt of %d tokens exceeds max bucket %d"
                         % (n, self.prompt_buckets[-1]))

    def seq_bucket(self, needed: int) -> int:
        for b in self.seq_buckets:
            if needed <= b:
                return b
        raise MXNetError("sequence needs %d cache positions, max bucket %d"
                         % (needed, self.seq_buckets[-1]))

    # ---------------------------------------------------------- dispatch
    def _dispatch(self, kind: str, bucket: int, builder, args: Tuple):
        """Bucket-program dispatch under the CompileCache counter
        discipline: first arrival builds (``<name>_compile``), every
        later arrival is ``<name>_cache_hit`` — zero steady-state
        recompiles is an assertable counter delta, exactly like
        InferenceServer's stateless path."""
        sig = ("gen_" + kind, bucket)
        prog = self.compile_cache.get(sig)
        fresh = prog is None
        if fresh:
            prog = builder(bucket)
            self.programs_built += 1
        with _obs_compiles.scope(self.name, sig):
            out = prog(*args)
        if fresh:
            self.compile_cache.put(sig, prog)
        else:
            self.compile_cache.note_success(sig)
        return out

    def prefill(self, prompt: np.ndarray, slot: int,
                logits: bool = False) -> Tuple[int, Optional[np.ndarray]]:
        """Run one prompt through the family's prefill program(s), writing
        its state into ``slot``: one program at the prompt's bucket, or a
        chunk after a chunk, each appended to the cache and attending
        over it. Returns the last real token's greedy choice and, where
        ``logits`` asks for them (the request samples), its ``(V,)``
        logits as host numpy, else None (the fetch is the device
        fence)."""
        picked = out = None
        for bucket, builder, args, attrs in self.family.prefill_calls(
                prompt, slot):
            with _profiler.span("gen_prefill_chunk", "serve", **attrs):
                picked, out, new_state = self._dispatch(
                    "prefill", bucket, builder,
                    (self.params, self.cache.state()) + tuple(args))
                self.cache.set_state(new_state)
            _profiler.incr_counter(self.name + "_prefill_chunks")
        return int(np.asarray(picked)), \
            (np.asarray(out) if logits else None)

    def _build_step(self, s_b: int):
        """The family's decode program at ``s_b`` behind the merge of the
        step's input tokens: the host's where it gave one, else what the
        previous step picked for the slot. One program, named as the
        family's is (``jit_fn``), the state donated; it returns the
        slots' tokens apart too, for the next step to take."""
        import jax
        import jax.numpy as jnp
        program = self.family.build_decode(s_b)
        slots = self.cache.max_slots

        def fn(params, state, last, tokens, pos, active):
            tokens = jnp.where(tokens < 0, last, tokens)
            picked, logits, state = program(params, state, tokens, pos,
                                            active)
            return picked, logits, state, picked[:slots]

        return jax.jit(fn, donate_argnums=(1,))

    def _no_tokens(self):
        """The first step's stand-in for the previous step's tokens, placed
        as a step's output will be: beside the state (committed to its
        device, or replicated over its mesh), so that the second step
        finds its program compiled for the same placement."""
        import jax
        import jax.numpy as jnp
        zeros = jnp.zeros((self.cache.max_slots,), jnp.int32)
        leaf = self.cache.state()[0]
        if not leaf.committed:
            return zeros
        where = leaf.sharding
        if hasattr(where, "mesh"):
            from jax.sharding import NamedSharding, PartitionSpec
            where = NamedSharding(where.mesh, PartitionSpec())
        return jax.device_put(zeros, where)

    def decode_dispatch(self, tokens: np.ndarray, pos: np.ndarray,
                        active: np.ndarray, logits: bool = False
                        ) -> DecodeFlight:
        """Dispatch one decode step over the whole slot array and return
        it in flight, without waiting for the device. ``tokens[s]`` is
        slot ``s``'s input token, or ``-1`` for the one the previous step
        picked for it (still on the device); ``pos[s]`` its write position
        (current length); inactive slots pass 0/False. ``logits`` keeps
        the ``(slots, V)`` logits for the collect (a resident sequence
        samples)."""
        pos = np.array(pos, np.int32)
        active = np.array(active, bool)
        needed = int(pos[active].max()) + 1 if active.any() else 1
        s_b = self.seq_bucket(needed)
        if self._picked_last is None:
            self._picked_last = self._no_tokens()
        with _profiler.span("gen_decode_dispatch", "serve"):
            picked, out, new_state, self._picked_last = self._dispatch(
                "decode", s_b, self._build_step,
                (self.params, self.cache.state(), self._picked_last,
                 np.asarray(tokens, np.int32), pos, active))
        self.cache.set_state(new_state)
        return DecodeFlight(picked, out if logits else None, s_b, pos,
                            active)

    def decode_collect(self, flight: DecodeFlight
                       ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """The ``(slots,)`` greedy tokens of a step in flight, on host,
        and its ``(slots, V)`` logits where they were asked for, else
        None; the family counts the step from the flight's own positions
        and active rows."""
        # the fetch is the step's device fence: what the scheduler waits
        # here is what is left of the step's device time and the copy of
        # ``picked``, the slots' integers (what a family puts behind them,
        # counts or what a step selected, rides in it: no second
        # transfer). The logits stay on the device unless someone samples.
        with _profiler.span("gen_logits_fetch", "serve"):
            t0 = time.perf_counter()
            picked = np.asarray(flight.picked)
            out = None if flight.logits is None \
                else np.asarray(flight.logits)
            flight.waited = time.perf_counter() - t0
        if out is not None:
            _profiler.incr_counter(self.name + "_decode_logits_fetched")
        return self.family.step_picked(picked, flight.bucket, flight.pos,
                                       flight.active), out

    def decode_ready(self, flight: DecodeFlight) -> bool:
        """Whether a step in flight has finished on the device, asked
        without waiting or fetching: a step found ready when the next is
        dispatched left the chip idle."""
        return flight.picked.is_ready()

    def decode_step(self, tokens: np.ndarray, pos: np.ndarray,
                    active: np.ndarray, logits: bool = False
                    ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """One decode step dispatched and collected: the ``(slots,)``
        greedy tokens on host and, where ``logits`` asks for them, the
        ``(slots, V)`` logits, else None."""
        return self.decode_collect(
            self.decode_dispatch(tokens, pos, active, logits))
