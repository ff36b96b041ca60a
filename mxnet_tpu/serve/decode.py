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
  K/V into the cache slot IN-PROGRAM (the state operand is donated, so
  the update is in-place on TPU), and returns only the last real
  token's logits (one ``(D,)`` row through the LM head, not a
  ``(T_b, V)`` matmul).
* **decode** — ONE jitted step per sequence bucket ``S_b`` over the
  WHOLE slot array: embed the freshest token of every resident
  sequence, append its K/V at the per-slot write position via a vmapped
  ``lax.dynamic_update_slice`` (gather-free; finished/empty slots write
  into reclaimed space that the next prefill overwrites — a masked
  no-op by construction), attend against the static ``[0:S_b]`` cache
  slice with per-slot length masking, and return ``(slots, V)`` logits.

The executable set is exactly |prompt buckets| + |decode buckets| (the
server's CompileCache counters assert it), and each program is
AOT-warm-startable through :mod:`mxnet_tpu.aot` — a restarted server
reaches its first token with zero backend compiles (the CI drill
asserts the obs compile accounting stays empty).

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

from contextlib import nullcontext as _nullcontext
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import profiler as _profiler
from ..base import MXNetError
from ..obs import compiles as _obs_compiles

__all__ = ["DecodeConfig", "DecodeEngine", "extract_params",
           "config_from_params", "sample_token"]

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

    def sig(self) -> Tuple:
        return (self.num_layers, self.d_model, self.n_heads, self.d_ff,
                self.vocab_size, self.max_seq)


def extract_params(source) -> Dict[str, Any]:
    """Normalize the served parameters to ``name -> f32 jnp array``.

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
    out = {}
    for name, arr in merged.items():
        if isinstance(arr, nd_mod.NDArray):
            arr = arr.asnumpy()
        out[name] = jnp.asarray(np.asarray(arr), jnp.float32)
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


def sample_token(logits: np.ndarray, temperature: float = 0.0,
                 rng: Optional[np.random.Generator] = None) -> int:
    """Host-side sampling: greedy at ``temperature=0`` (deterministic —
    the batch-composition-invariance test keys on it), else softmax
    sampling from the caller's per-request generator."""
    if temperature <= 0.0:
        return int(np.argmax(logits))
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


def _quantize_pages(x, page: int):
    """(H, T, d) f32 -> (int8 (H, T, d), scales (H, T // page)) — one
    symmetric scale per (head, page), the quantized-paged-KV layout."""
    import jax.numpy as jnp
    h, t, d = x.shape
    pg = x.reshape(h, t // page, page, d)
    scale = jnp.maximum(jnp.max(jnp.abs(pg), axis=(2, 3)) / 127.0, 1e-8)
    q = jnp.clip(jnp.round(pg / scale[:, :, None, None]), -127, 127)
    return q.reshape(h, t, d).astype(jnp.int8), scale


class DecodeEngine:
    """The program table: builds, AOT-warm-starts and dispatches the
    per-bucket prefill/decode executables over one :class:`KVCache`.

    NOT thread-safe by design: every method runs on the owning
    GenerativeServer's scheduler thread (the cache state tuple is
    donated through each dispatch and re-bound from the result — a
    second dispatcher would race the donation).
    """

    def __init__(self, params: Dict[str, Any], n_heads: int, cache,
                 compile_cache, name: str = "serve",
                 prompt_buckets: Optional[Sequence[int]] = None,
                 seq_buckets: Optional[Sequence[int]] = None,
                 prefill_chunk: int = 512):
        self.params = params
        self.cfg = config_from_params(params, n_heads)
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
        # multi-device (sharded cache) programs are AOT-fenced exactly
        # like the executor forward (aot_skip_multidevice)
        self._multi_device = cache._sharding is not None

    def executable_bound(self) -> int:
        return len(self.prompt_buckets) + len(self.seq_buckets)

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

    def _build_prefill(self, t_b: int):
        import jax
        import jax.numpy as jnp
        from jax import lax
        cfg = self.cfg
        int8 = self.cache.int8
        page = self.cache.page

        def write_layer(state, li, slot, k, v):
            # k/v: (H, T_b, d) -> cache block [li, slot, :, 0:T_b, :]
            if int8:
                ks, vs = state[2], state[3]
                kq, ksc = _quantize_pages(k, page)
                vq, vsc = _quantize_pages(v, page)
                return (
                    lax.dynamic_update_slice(
                        state[0], kq[None, None], (li, slot, 0, 0, 0)),
                    lax.dynamic_update_slice(
                        state[1], vq[None, None], (li, slot, 0, 0, 0)),
                    lax.dynamic_update_slice(
                        ks, ksc[None, None], (li, slot, 0, 0)),
                    lax.dynamic_update_slice(
                        vs, vsc[None, None], (li, slot, 0, 0)),
                )
            return (
                lax.dynamic_update_slice(
                    state[0], k[None, None], (li, slot, 0, 0, 0)),
                lax.dynamic_update_slice(
                    state[1], v[None, None], (li, slot, 0, 0, 0)),
            )

        def fn(params, state, tokens, slot, true_len):
            # tokens (T_b,) int32; slot, true_len scalar int32
            x = params["tok_embed_weight"][tokens] \
                + params["pos_embed_weight"][:t_b]          # (T_b, D)
            for li in range(cfg.num_layers):
                pfx = "layer%d" % li
                h = _ln(x, params[pfx + "_ln1_gamma"],
                        params[pfx + "_ln1_beta"])
                qkv = _fc(h, params, pfx + "_att_qkv")      # (T_b, 3D)
                qkv = qkv.reshape(t_b, 3, cfg.n_heads, cfg.d_head)
                q = qkv[:, 0].transpose(1, 0, 2)            # (H, T_b, d)
                k = qkv[:, 1].transpose(1, 0, 2)
                v = qkv[:, 2].transpose(1, 0, 2)
                state = write_layer(state, li, slot, k, v)
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
            return logits, state

        return jax.jit(fn, donate_argnums=(1,))

    def _read_bucket(self, state, li: int, s_b: int):
        """Cache slice [0:S_b] of layer ``li``, dequantized:
        (slots, H, S_b, d) f32 pair."""
        import jax.numpy as jnp
        page = self.cache.page
        k = state[0][li, :, :, :s_b, :]
        v = state[1][li, :, :, :s_b, :]
        if not self.cache.int8:
            return k, v
        pb = s_b // page
        slots, h = k.shape[0], k.shape[1]
        ks = state[2][li, :, :, :pb]
        vs = state[3][li, :, :, :pb]

        def deq(q, sc):
            f = q.astype(jnp.float32).reshape(slots, h, pb, page, -1)
            return (f * sc[..., None, None]).reshape(slots, h, s_b, -1)

        return deq(k, ks), deq(v, vs)

    def _build_decode(self, s_b: int):
        import jax
        import jax.numpy as jnp
        from jax import lax
        cfg = self.cfg
        int8 = self.cache.int8
        page = self.cache.page
        scale = 1.0 / np.sqrt(cfg.d_head)

        def write_one_f32(cache_s, kn, p):
            # cache_s (H, S, d), kn (H, d), p scalar write position
            return lax.dynamic_update_slice(cache_s, kn[:, None, :],
                                            (0, p, 0))

        def write_one_i8(cache_s, scale_s, kn, p):
            # requantize-on-write: page entry resets the scale (a fresh
            # page must not inherit a stale tenant's dynamic range);
            # in-page growth merges scales upward and requantizes the
            # page — with an unchanged scale the round-trip is exact
            h = cfg.n_heads
            pi = p // page
            off = p % page
            pg = lax.dynamic_slice(cache_s, (0, pi * page, 0),
                                   (h, page, cfg.d_head))
            old = lax.dynamic_slice(scale_s, (0, pi), (h, 1))[:, 0]
            entering = (off == 0)
            deq = jnp.where(entering, 0.0,
                            pg.astype(jnp.float32) * old[:, None, None])
            needed = jnp.maximum(
                jnp.max(jnp.abs(kn), axis=-1) / 127.0, 1e-8)    # (H,)
            new_scale = jnp.where(entering, needed,
                                  jnp.maximum(old, needed))
            deq = lax.dynamic_update_slice(deq, kn[:, None, :], (0, off, 0))
            q = jnp.clip(jnp.round(deq / new_scale[:, None, None]),
                         -127, 127).astype(jnp.int8)
            return (lax.dynamic_update_slice(cache_s, q, (0, pi * page, 0)),
                    lax.dynamic_update_slice(scale_s, new_scale[:, None],
                                             (0, pi)))

        def write_token(state, li, k_new, v_new, pos):
            # k_new/v_new (slots, H, d); pos (slots,) — vmapped over the
            # slot axis, so every sequence writes at ITS OWN position in
            # one gather-free program (empty slots write into reclaimed
            # space the next prefill overwrites: a no-op by construction)
            if int8:
                nk, nks = jax.vmap(write_one_i8)(state[0][li], state[2][li],
                                                 k_new, pos)
                nv, nvs = jax.vmap(write_one_i8)(state[1][li], state[3][li],
                                                 v_new, pos)
                return (state[0].at[li].set(nk), state[1].at[li].set(nv),
                        state[2].at[li].set(nks), state[3].at[li].set(nvs))
            nk = jax.vmap(write_one_f32)(state[0][li], k_new, pos)
            nv = jax.vmap(write_one_f32)(state[1][li], v_new, pos)
            return (state[0].at[li].set(nk), state[1].at[li].set(nv))

        def fn(params, state, tokens, pos, active):
            # tokens/pos (slots,) int32; active (slots,) bool
            pos_c = jnp.clip(pos, 0, cfg.max_seq - 1)
            x = params["tok_embed_weight"][tokens] \
                + params["pos_embed_weight"][pos_c]         # (slots, D)
            for li in range(cfg.num_layers):
                pfx = "layer%d" % li
                h = _ln(x, params[pfx + "_ln1_gamma"],
                        params[pfx + "_ln1_beta"])
                qkv = _fc(h, params, pfx + "_att_qkv")
                qkv = qkv.reshape(-1, 3, cfg.n_heads, cfg.d_head)
                q, k_new, v_new = qkv[:, 0], qkv[:, 1], qkv[:, 2]
                state = write_token(state, li, k_new, v_new, pos_c)
                kb, vb = self._read_bucket(state, li, s_b)
                s = jnp.einsum("shd,shkd->shk", q, kb,
                               preferred_element_type=jnp.float32) * scale
                # keys at 0..pos inclusive (the token just written
                # attends to itself, matching the training graph)
                mask = jnp.arange(s_b)[None, :] <= pos_c[:, None]
                s = jnp.where(mask[:, None, :], s, -1e9)
                att = jax.nn.softmax(s, axis=-1)
                ctx = jnp.einsum("shk,shkd->shd", att, vb)
                ctx = ctx.reshape(-1, cfg.d_model)
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
            return logits, state

        return jax.jit(fn, donate_argnums=(1,))

    # ---------------------------------------------------------- dispatch
    def _sig_parts(self, kind: str, bucket: int) -> Tuple:
        shapes = tuple(sorted((k, tuple(v.shape), str(v.dtype))
                              for k, v in self.params.items()))
        return ("serve", kind, bucket, self.cfg.sig(), shapes,
                self.cache.int8, self.cache.page, self.cache.max_slots,
                self.cache.max_seq, self.prefill_chunk)

    def _dispatch(self, kind: str, bucket: int, builder, args: Tuple):
        """Bucket-program dispatch under the CompileCache counter
        discipline: first arrival builds (``<name>_compile``), every
        later arrival is ``<name>_cache_hit`` — zero steady-state
        recompiles is an assertable counter delta, exactly like
        InferenceServer's stateless path."""
        from .. import aot
        sig = ("gen_" + kind, bucket)
        prog = self.compile_cache.get(sig)
        fresh = prog is None
        if fresh:
            jitted = builder(bucket)
            use_aot = (not self._multi_device and aot.enabled() is not None
                       and aot.supported())
            hit = False
            if use_aot:
                key = aot.digest(self._sig_parts(kind, bucket))
                with _obs_compiles.scope(self.name, sig):
                    prog, hit = aot.load_or_compile(
                        "serve_%s" % kind, key, jitted, *args)
                if hit:
                    # first call of a LOADED executable runs on copies
                    # of the donated cache state: a bad entry must not
                    # invalidate the live buffers (the _fused
                    # discipline). The copy happens OUTSIDE the obs
                    # scope — its incidental jit(copy) must not show up
                    # as a serve-attributed backend compile in the
                    # warm-restart drill.
                    import jax.numpy as jnp
                    args = (args[0],
                            tuple(jnp.array(a) for a in args[1])) \
                        + args[2:]
            else:
                prog = jitted
            with _obs_compiles.scope(self.name, sig) if not hit \
                    else _nullcontext():
                out = prog(*args)
            self.compile_cache.put(sig, prog)
            return out
        with _obs_compiles.scope(self.name, sig):
            out = prog(*args)
        self.compile_cache.note_success(sig)
        return out

    def prefill(self, prompt: np.ndarray, slot: int) -> np.ndarray:
        """Run one prompt through its bucket's prefill program, writing
        its K/V into ``slot``; returns the last real token's logits as
        host numpy (the fetch is the device fence)."""
        n = int(prompt.shape[0])
        t_b = self.prompt_bucket(n)
        tokens = np.zeros((t_b,), np.int32)
        tokens[:n] = np.asarray(prompt, np.int32)
        logits, new_state = self._dispatch(
            "prefill", t_b, self._build_prefill,
            (self.params, self.cache.state(), tokens,
             np.int32(slot), np.int32(n)))
        self.cache.set_state(new_state)
        return np.asarray(logits)

    def decode_step(self, tokens: np.ndarray, pos: np.ndarray,
                    active: np.ndarray) -> np.ndarray:
        """One decode step over the whole slot array; returns
        ``(slots, V)`` logits on host. ``pos[s]`` is the write position
        (current length) of slot ``s``; inactive slots pass 0/False."""
        needed = int(pos[active].max()) + 1 if active.any() else 1
        s_b = self.seq_bucket(needed)
        with _profiler.span("gen_decode_dispatch", "serve"):
            logits, new_state = self._dispatch(
                "decode", s_b, self._build_decode,
                (self.params, self.cache.state(),
                 np.asarray(tokens, np.int32), np.asarray(pos, np.int32),
                 np.asarray(active, bool)))
        self.cache.set_state(new_state)
        # the fetch is the step's device fence: what the scheduler waits
        # here is the step's device time and the copy of the logits
        with _profiler.span("gen_logits_fetch", "serve"):
            return np.asarray(logits)
