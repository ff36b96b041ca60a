"""The serving engine's third family (block-sparse attention over selected
key blocks beside lightning layers whose state is a plane a slot) against
its plain reference (``benchmarks/references/minicpm_sala.py``), at a tiny
size on the CPU, seeded random weights, float32.

Tiny: 4 query heads for 2 key/value heads of 16, 4 lightning heads, stride
1, kernel 2, block 4, top-k 4, window 8: a sequence of 40 positions holds
ten blocks, so selection is at work from position 16 on, and one of 270
crosses 64 blocks. Nothing here is a time.
"""
import json
import os

import numpy as np
import pytest

import _serve_ahead

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAX_SEQ, SLOTS, CHUNK, PAGE = 288, 3, 16, 4
BUCKETS = [32, 96, 288]
TOL = 5e-5      # float32 both sides; logits are of size 1
SPARSE_CONFIG = {"kernel_size": 2, "kernel_stride": 1, "block_size": 4,
                 "init_blocks": 1, "window_size": 8, "topk": 4}


def _config(sparse_config=None, **over):
    from benchmarks.lib import spec
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "minicpm-sala-1chip.json")) as f:
        doc = json.load(f)
    cfg = spec._merge(doc, doc["rehearse"])
    cfg.pop("rehearse")
    cfg["max_position_embeddings"] = MAX_SEQ
    cfg["assumed"]["sparse_config"] = dict(sparse_config or SPARSE_CONFIG)
    cfg.update(over)
    return cfg


def _tiny(cfg, seed=7):
    from benchmarks.builders import minicpm_sala as builder
    from benchmarks.lib import leaves
    params = {k: np.asarray(v) for k, v in
              leaves.make(builder.leaf_specs(cfg), seed).items()}
    # spread the selection's scores: with weights of 0.02 they are flat
    for name in params:
        if name.endswith(("att_q_weight", "att_k_weight")):
            params[name] = params[name] * 12.0
    arch = builder.architecture(cfg)
    arch["dtype"] = "float32"
    return cfg, arch, params


@pytest.fixture(scope="module")
def tiny():
    """(cfg as the reference reads it, arch as the server is told it,
    params as numpy)."""
    return _tiny(_config())


def _engine(tiny, name, chunk=CHUNK, slots=SLOTS, buckets=BUCKETS,
            max_seq=MAX_SEQ):
    from mxnet_tpu._fused import CompileCache
    from mxnet_tpu.serve.decode import DecodeEngine, extract_params
    from mxnet_tpu.serve.kv_cache import KVCache
    from mxnet_tpu.serve.sparse_linear import SparseLinearDecoder
    _cfg, arch, params = tiny
    family = SparseLinearDecoder(extract_params(params, "float32"), arch)
    cache = KVCache(family.planes(max_seq, PAGE, False), max_slots=slots,
                    max_seq=max_seq, page=PAGE, name=name)
    return DecodeEngine(family, cache, CompileCache(name), name=name,
                        seq_buckets=buckets, prefill_chunk=chunk)


def _reference(tiny, tokens):
    import jax.numpy as jnp
    from benchmarks.references import minicpm_sala as ref
    cfg, _arch, params = tiny
    return np.asarray(ref.forward(
        cfg, {k: jnp.asarray(v) for k, v in params.items()},
        jnp.asarray(tokens)))


def test_prefill_in_chunks_then_decode_follows_the_reference(tiny):
    """Three slots of unequal length in every step: a prompt of one padded
    chunk, one of three chunks that selects already, and one of 262 tokens
    whose decode crosses the 64th block; teacher-forced, logits against
    the reference's full forward at every position."""
    import mxnet_tpu as mx
    cfg = tiny[0]
    assert cfg["mixer_types"] == ["minicpm4", "lightning-attn",
                                  "lightning-attn", "minicpm4"]
    eng = _engine(tiny, "slfollow")
    rng = np.random.default_rng(0)
    prompt = [5, 41, 250]
    seqs = [rng.integers(0, cfg["vocab_held"], n + 12) for n in prompt]
    want = [_reference(tiny, s) for s in seqs]
    pos = np.zeros(SLOTS, np.int32)
    for s in range(SLOTS):
        picked, got = eng.prefill(seqs[s][:prompt[s]], s, logits=True)
        assert picked == int(np.argmax(got))
        assert np.abs(got - want[s][prompt[s] - 1]).max() < TOL, s
        pos[s] = prompt[s]
    assert mx.profiler.get_counter("slfollow_state_resets") == 3
    active = np.ones(SLOTS, bool)
    for _ in range(10):
        tokens = np.array([seqs[s][pos[s]] for s in range(SLOTS)], np.int32)
        picked, got = eng.decode_step(tokens, pos, active, logits=True)
        assert got.shape == (SLOTS, cfg["vocab_held"])
        assert (picked == np.argmax(got, axis=-1)).all()
        for s in range(SLOTS):
            assert np.abs(got[s] - want[s][pos[s]]).max() < TOL, (s, pos)
        pos += 1
    assert pos[2] // 4 >= 64
    # a step's blocks, from the positions, on two sparse layers: the first
    # step held 2, 11 and 63 blocks and read 2, 4 and 4 of them
    blocks = np.array([[(n + i) // 4 + 1 for n in prompt]
                       for i in range(10)])
    assert blocks[0].tolist() == [2, 11, 63]
    assert mx.profiler.get_counter("slfollow_sparse_blocks_resident") \
        == 2 * blocks.sum()
    assert mx.profiler.get_counter("slfollow_sparse_blocks_read") \
        == 2 * np.minimum(blocks, 4).sum()


@pytest.mark.parametrize("chunk", [1, 16, 48])
def test_the_same_logits_for_every_chunk_size(tiny, chunk):
    """A 45-token prompt in chunks of 1, of 16 and in one chunk: the same
    logits as the reference's, the same rows and states in the cache."""
    cfg = tiny[0]
    prompt = np.random.default_rng(1).integers(0, cfg["vocab_held"], 45)
    want = _reference(tiny, prompt)[-1]
    eng = _engine(tiny, "slchunk%d" % chunk, chunk=chunk,
                  buckets=[64, 288])
    tok, got = eng.prefill(prompt, 1, logits=True)
    assert np.abs(got - want).max() < TOL and tok == int(np.argmax(want))
    one = _engine(tiny, "slwhole%d" % chunk, chunk=96, buckets=[96, 288])
    one.prefill(prompt, 1)
    k, v, kc, st = (np.asarray(x) for x in eng.cache.state())
    k1, v1, kc1, st1 = (np.asarray(x) for x in one.cache.state())
    assert np.abs(k[:, 1, :45]).max() > 0 and np.abs(st[:, 1]).max() > 0
    assert np.abs(k[:, 1, :45] - k1[:, 1, :45]).max() < TOL
    assert np.abs(v[:, 1, :45] - v1[:, 1, :45]).max() < TOL
    # the compressed keys the prompt closed: 0 .. 43 at stride 1, kernel 2
    assert np.abs(kc[:, 1, :44] - kc1[:, 1, :44]).max() < TOL
    assert np.abs(kc[:, 1, :44] - 0.5 * (k[:, 1, :44] + k[:, 1, 1:45])
                  ).max() < TOL
    assert np.abs(st[:, 1] - st1[:, 1]).max() < TOL


def test_the_planes_and_what_a_slot_costs(tiny):
    """Four planes under one cache: K and V rows and the compressed keys of
    the sparse layers, the state of the lightning layers, which does not
    grow with the slot's length."""
    from mxnet_tpu.serve.kv_cache import max_slots_for
    eng = _engine(tiny, "slplanes")
    planes = eng.cache.planes
    assert [p.name for p in planes] == ["k", "v", "kc", "state"]
    shapes = [tuple(x.shape) for x in eng.cache.state()]
    assert shapes == [(2, SLOTS, MAX_SEQ, 32), (2, SLOTS, MAX_SEQ, 32),
                      (2, SLOTS, MAX_SEQ, 32), (2, SLOTS, 64, 16)]
    assert planes[3].kind == "slot_state" and planes[3].dtype == "float32"
    assert planes[3].bytes_per_slot(MAX_SEQ) \
        == planes[3].bytes_per_slot(8 * MAX_SEQ) == 2 * 64 * 16 * 4
    per_slot = 3 * 2 * MAX_SEQ * 32 * 4 + 2 * 64 * 16 * 4
    assert eng.cache.hbm_bytes() == SLOTS * per_slot
    assert max_slots_for(eng.cache.hbm_bytes(), planes, MAX_SEQ) == SLOTS
    assert "state 2 layers x 64x16 float32 a slot" in "; ".join(
        p.describe() for p in planes)


def test_a_slot_taken_again_starts_from_zeros(tiny):
    """No position mask hides a stale state: after a longer sequence a
    shorter one in the same slot gives what a fresh cache gives, through
    prefill and decode."""
    cfg = tiny[0]
    rng = np.random.default_rng(3)
    long, short = (rng.integers(0, cfg["vocab_held"], n) for n in (90, 21))
    used, fresh = _engine(tiny, "slused"), _engine(tiny, "slfresh")
    used.prefill(long, 0)
    pos = np.array([90, 0, 0], np.int32)
    active = np.array([True, False, False])
    for _ in range(3):
        used.decode_step(np.array([1, 0, 0], np.int32), pos, active)
        pos[0] += 1
    a = used.prefill(short, 0, logits=True)[1]
    b = fresh.prefill(short, 0, logits=True)[1]
    assert np.array_equal(a, b)
    pos = np.array([21, 0, 0], np.int32)
    for step in range(4):
        tokens = np.array([7 + step, 0, 0], np.int32)
        a = used.decode_step(tokens, pos, active, logits=True)[1][0]
        b = fresh.decode_step(tokens, pos, active, logits=True)[1][0]
        assert np.array_equal(a, b)
        pos[0] += 1
    # an empty slot's row is masked
    out = fresh.decode_step(np.zeros(3, np.int32), pos, active,
                            logits=True)[1]
    assert (out[1:] < -1e29).all()


def _arch(tiny):
    from mxnet_tpu.models import sparse_linear as layer
    return layer, layer.Arch(tiny[1])


def test_selection_forced_blocks_ties_and_identity(tiny):
    """Block 0 and the window's blocks are always among the selected; with
    flat scores the rest are the lowest-numbered candidates; at positions
    under ``topk x block`` every block held is selected; with scores that
    single out far blocks those are selected, by falling score."""
    import jax.numpy as jnp
    layer, a = _arch(tiny)
    assert (a.block, a.topk, a.window_blocks, a.init_blocks) == (4, 4, 2, 1)
    n_blocks, n_kc = 16, 64
    t = jnp.asarray([3, 15, 40, 63], jnp.int32)
    flat = jnp.zeros((4, a.kv_heads, a.group, n_kc), jnp.float32)
    idx, count = (np.asarray(x) for x in layer.select_blocks(
        a, flat, t, n_blocks))
    assert idx.shape == (4, a.kv_heads, 4) and count.tolist() == [1, 4, 4, 4]
    # position 3: block 0 alone; 15: blocks 0..3 all; 40 (block 10): 0, 9,
    # 10 forced and, all scores equal, block 1; 63: 0, 14, 15 and 1
    assert sorted(idx[0, 0, :1]) == [0]
    assert sorted(idx[1, 0]) == [0, 1, 2, 3]
    assert sorted(idx[2, 0]) == [0, 1, 9, 10]
    assert sorted(idx[3, 1]) == [0, 1, 14, 15]
    # forced first (ties to the lower index), then the scored
    assert idx[2, 0].tolist() == [0, 9, 10, 1]
    # kernels 21 and 22 lie in block 5 (positions 20..23): it wins over
    # the flat rest for the query at 40, and is no candidate at 15
    peaked = flat.at[:, 0, :, 21].set(9.0)
    idx, count = (np.asarray(x) for x in layer.select_blocks(
        a, peaked, t, n_blocks))
    assert idx[2, 0].tolist() == [0, 9, 10, 5]
    assert idx[2, 1].tolist() == [0, 9, 10, 1]
    assert sorted(idx[1, 0]) == [0, 1, 2, 3]
    # a kernel that is not complete at the query's position scores nothing:
    # kernel 62 covers positions 62, 63
    late = flat.at[:, :, :, 62].set(9.0)
    idx, _ = (np.asarray(x) for x in layer.select_blocks(
        a, late, jnp.asarray([62, 63, 62, 63], jnp.int32), n_blocks))
    assert sorted(idx[0, 0]) == [0, 1, 14, 15]
    assert sorted(idx[1, 0]) == [0, 1, 14, 15]      # block 15 is forced


def test_attention_over_the_selection_is_attention_under_its_mask(tiny):
    """The gather a decode step reads the selected blocks with and the
    block-level mask a prefill chunk uses give the same rows."""
    import jax.numpy as jnp
    layer, a = _arch(tiny)
    rng = np.random.default_rng(4)
    n, s_len = 3, 64
    q = jnp.asarray(rng.normal(size=(n, a.kv_heads, a.group, a.d_head)),
                    jnp.float32)
    k = jnp.asarray(rng.normal(size=(s_len, a.kv_row)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(s_len, a.kv_row)), jnp.float32)
    t = jnp.asarray([9, 38, 63], jnp.int32)
    kc = 0.5 * (k[:-1] + k[1:])
    kc = jnp.concatenate([kc, kc[-1:]], 0).reshape(s_len, a.kv_heads, -1)
    s = layer.product("ngid,jgd->ngij", q, kc) * a.score_scale
    idx, count = layer.select_blocks(a, s, t, s_len // a.block)
    masked = layer.attend_blocks(a, q, k, v, t, idx, count)
    rows = jnp.broadcast_to(k[None], (n,) + k.shape), \
        jnp.broadcast_to(v[None], (n,) + v.shape)
    gathered = layer.attend_selected(a, q, rows[0], rows[1], idx, count, t)
    assert np.abs(np.asarray(masked - gathered)).max() < 1e-5
    # position 9 holds three blocks: all selected, plain causal attention
    plain = layer.attend_blocks(a, q, k, v, t)
    assert np.abs(np.asarray(masked - plain))[0].max() < 1e-5
    assert np.abs(np.asarray(masked - plain))[2].max() > 1e-3


def test_the_pallas_kernel_reads_what_the_gather_reads():
    """``ops/pallas/sparse_decode_attention.py`` in interpreter mode, at a
    tile the chip could fetch (block 16, heads of 128, bfloat16 widened
    off the TPU), against the gather: slots of unequal length, a free
    slot, a slot with fewer blocks than a grid step fetches."""
    import jax.numpy as jnp
    from mxnet_tpu.models import sparse_linear as layer
    from mxnet_tpu.ops.pallas.sparse_decode_attention import (
        sparse_decode_attention, tiles)
    cfg = _config(sparse_config={
        "kernel_size": 8, "kernel_stride": 4, "block_size": 16,
        "init_blocks": 1, "window_size": 32, "topk": 16}, head_dim=128,
        num_attention_heads=8)
    arch = dict(_tiny(cfg)[1], dtype="bfloat16", max_position_embeddings=512)
    a = layer.Arch(arch)
    assert tiles(a.block, a.d_head, a.dtype) and a.group == 4
    assert not tiles(4, 16, "float32")
    rng = np.random.default_rng(5)
    slots, s_len, layers = 4, 512, 2
    q = jnp.asarray(rng.normal(size=(slots, a.kv_heads, a.group, 128)),
                    jnp.float32)
    k = jnp.asarray(rng.normal(size=(layers, slots, s_len, a.kv_row)),
                    jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(layers, slots, s_len, a.kv_row)),
                    jnp.bfloat16)
    t = jnp.asarray([500, 37, 0, 300], jnp.int32)
    active = jnp.asarray([True, True, False, True])
    kc = k[1].astype(jnp.float32).reshape(slots, s_len // 4, 4, a.kv_heads,
                                          128).mean(2)
    s = layer.product("ngid,njgd->ngij", q, kc) * a.score_scale
    idx, count = layer.select_blocks(a, s, t, s_len // a.block)
    count = jnp.where(active, count, 0)
    assert count.tolist() == [16, 3, 0, 16]
    got = sparse_decode_attention(q, k, v, 1, idx, count, t, block=a.block,
                                  scale=a.score_scale)
    want = layer.attend_selected(a, q.astype(jnp.bfloat16), k[1], v[1], idx,
                                 count, t)
    assert got.shape == (slots, 8 * 128) and got.dtype == jnp.float32
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got[2]).max() == 0
    # the kernel rounds the probabilities to the cache's dtype before the
    # product with V, as the gather's product does
    assert np.abs(got - want)[[0, 1, 3]].max() < 2e-2
    assert np.abs(want[[0, 1, 3]]).max() > 0.1


def test_the_first_steps_selection_and_read_are_kept_a_slot(tiny):
    """``SparseLinearDecoder.followed``: behind the tokens a decode step
    carries each sparse layer's block numbers and a mean a head of what
    was read. The record of a prompt holds its first steps' positions, the
    blocks the reference selects there too, -1 past those that count, and
    the means the reference reads out of those blocks; it stops at
    ``_FOLLOWED_STEPS`` steps, outlives the sequence's slot, and goes when
    ``_FOLLOWED_SEQUENCES`` newer prompts have come."""
    import jax.numpy as jnp
    from benchmarks.references import minicpm_sala as ref
    from mxnet_tpu.serve import sparse_linear as served
    cfg, _arch, params = tiny
    eng = _engine(tiny, "slkept")
    family = eng.family
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg["vocab_held"], n) for n in (5, 41)]
    assert family.followed(prompts[1]) is None
    pos = np.zeros(SLOTS, np.int32)
    tokens = np.zeros(SLOTS, np.int32)
    for s, prompt in enumerate(prompts):
        tokens[s] = eng.prefill(prompt, s)[0]
        pos[s] = len(prompt)
    assert family.followed(prompts[1]) is None      # no decode step yet
    active = np.array([True, True, False])
    rows = [list(p) for p in prompts]
    steps = 6
    for _ in range(steps):
        picked, _none = eng.decode_step(tokens, pos, active)
        assert picked.shape == (SLOTS,) and picked.dtype == np.int32
        for s in range(2):
            rows[s].append(int(tokens[s]))
            tokens[s] = picked[s]
        pos[:2] += 1
    for s, prompt in enumerate(prompts):
        seen = family.followed(prompt)
        assert seen["pos"].tolist() == list(range(len(prompt),
                                                  len(prompt) + steps))
        assert seen["blocks"].shape == (steps, 2, 2, 4)
        assert seen["attended"].shape == (steps, 2, 4)
        valid = np.minimum(seen["pos"] // 4 + 1, 4)
        assert ((seen["blocks"] >= 0).sum(axis=-1)
                == valid[:, None, None]).all()
        # the reference, at its own layer inputs, row by row
        row = jnp.asarray(rows[s], jnp.int32)
        x = ref.embed(cfg, jnp.asarray(params["tok_embed_weight"]), row)
        at = jnp.asarray(seen["pos"], jnp.int32)
        si = 0
        for li, kind in enumerate(ref.layer_kinds(cfg)):
            p = {k: jnp.asarray(v) for k, v in ref.layer_params(
                params, li).items()}
            if kind == ref.SPARSE:
                chosen, attended, own = ref.sparse_probe(
                    cfg, p, x, at, jnp.asarray(seen["blocks"][:, si]))
                assert np.array_equal(np.asarray(own), np.asarray(attended))
                mine = (seen["blocks"][:, si, :, :, None]
                        == np.arange(chosen.shape[-1])).any(axis=-2)
                assert (mine == np.asarray(chosen)).all(), (s, li)
                assert np.abs(seen["attended"][:, si]
                              - np.asarray(attended)).max() < 1e-5
                assert np.abs(np.asarray(attended)).max() > 1e-3
                si += 1
            x = ref.ffn_half(cfg, p, ref.attention_half(
                cfg, kind, p, x, jnp.arange(x.shape[0])))
        assert si == 2
    # a record stops at its cap; the slot's next prompt starts another
    # and leaves it as it was
    for _ in range(served._FOLLOWED_STEPS):
        eng.decode_step(tokens, pos, active)
        pos[:2] += 1
    assert len(family.followed(prompts[1])["pos"]) == served._FOLLOWED_STEPS
    again = rng.integers(0, cfg["vocab_held"], 9)
    tokens[1], pos[1] = eng.prefill(again, 1)[0], 9
    eng.decode_step(tokens, pos, active)
    assert family.followed(again)["pos"].tolist() == [9]
    for prompt in prompts:
        kept = family.followed(prompt)["pos"]
        assert kept.tolist() == list(range(
            len(prompt), len(prompt) + served._FOLLOWED_STEPS))
    # the oldest record goes when the newest would be one too many
    for i in range(served._FOLLOWED_SEQUENCES - 3):
        family.prefill_calls(np.full(3, i, np.int32), 2)
    assert family.followed(prompts[0]) is not None
    family.prefill_calls(np.full(4, 0, np.int32), 2)
    assert family.followed(prompts[0]) is None
    assert family.followed(prompts[1]) is not None


@pytest.mark.parametrize("key,value", [("attn_use_rope", True),
                                       ("lightning_use_rope", False)])
def test_a_rotary_the_family_does_not_serve_is_refused(tiny, key, value):
    from mxnet_tpu.models import sparse_linear as layer
    with pytest.raises(ValueError, match="rotary"):
        layer.Arch(dict(tiny[1], **{key: value}))


def test_the_chunked_lightning_form_is_the_recurrence(tiny):
    """``O = ((Q K^T) * D) V + (Q * decay_in) S``, ``S' = lambda^n S + (K *
    decay_out)^T V`` against ``S_t = lambda S_(t-1) + k_t v_t^T`` token by
    token, from a state that is not zero, with a padded last chunk."""
    import jax.numpy as jnp
    layer, a = _arch(tiny)
    rng = np.random.default_rng(6)
    c, real = 16, 11
    shape = (c, a.l_heads, a.l_d)
    q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.float32)
               for _ in range(3))
    s0 = jnp.asarray(rng.normal(size=(a.l_heads, a.l_d, a.l_d)), jnp.float32)
    new, o = layer.lightning_chunk(a, s0, q, k, v, jnp.int32(real))
    state, rows = s0[None], []
    for i in range(real):
        state, oi = layer.lightning_step(a, state, q[i:i + 1], k[i:i + 1],
                                         v[i:i + 1])
        rows.append(np.asarray(oi[0]))
    assert np.abs(np.asarray(o)[:real] - np.stack(rows)).max() < 1e-4
    assert np.abs(np.asarray(new) - np.asarray(state[0])).max() < 1e-4
    # the decay is a head's own: lambda_h = exp(-2^(-8 (h + 1) / H))
    lam = np.exp(-2.0 ** (-8.0 * (np.arange(4) + 1) / 4))
    assert np.allclose(np.exp(-a.decay_rate), lam)
    zero, _ = layer.lightning_step(a, s0[None], 0 * q[:1], 0 * k[:1],
                                   0 * v[:1])
    assert np.allclose(np.asarray(zero[0]), lam[:, None, None]
                       * np.asarray(s0), atol=1e-6)


def test_served_through_the_generative_server(tiny):
    """``GenerativeServer(arch=)`` finds the family by the description's
    ``model_type``; greedy tokens are the reference's own continuation."""
    import mxnet_tpu as mx
    cfg, arch, params = tiny
    srv = mx.serve.GenerativeServer(
        params, arch=arch, max_sequences=2, seq_buckets=BUCKETS,
        prefill_chunk=CHUNK, prefill_tokens=32, page=PAGE, name="slserve")
    try:
        prompt = np.random.default_rng(8).integers(0, cfg["vocab_held"], 37)
        tokens = srv.submit_generate(prompt, max_new_tokens=6).result(
            timeout=600)
    finally:
        srv.close(drain=False, timeout=30)
    seq = list(prompt)
    for tok in tokens:
        assert tok == int(np.argmax(_reference(tiny, np.asarray(seq))[-1]))
        seq.append(tok)
    with pytest.raises(ValueError, match="serves no model_type"):
        mx.serve.GenerativeServer(params, arch=dict(arch, model_type="x"))


# ------------------------------------ step N+1 dispatched before N's tokens

def _ahead_server(tiny, name, slots=None):
    import mxnet_tpu as mx
    _cfg, arch, params = tiny
    return mx.serve.GenerativeServer(
        params, arch=dict(arch), max_sequences=slots or SLOTS,
        seq_buckets=BUCKETS, prefill_chunk=CHUNK, page=PAGE,
        name="slahead" + name)


def _prompts(tiny, seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, tiny[0]["vocab_held"], n) for n in lengths]

def _followed(srv, prompt):
    return srv.engine.family.followed(prompt)



def test_the_engine_takes_the_last_steps_tokens_on_the_device(tiny):
    p = _prompts(tiny, 11, (21, 5))
    _serve_ahead.check_engine_takes_the_last_steps_tokens(
        _engine(tiny, "slaheadserial"), _engine(tiny, "slaheadahead"),
        {0: p[0], 2: p[1]})


def test_greedy_tokens_ahead_are_the_serial_paths(tiny):
    _serve_ahead.check_ahead_equals_serial(
        _ahead_server(tiny, "equal"), _engine(tiny, "slaheadequalserial"),
        _prompts(tiny, 12, (5, 40, 23)))


def test_max_new_tokens_and_max_seq_end_without_an_extra_row(tiny):
    _serve_ahead.check_ends_without_an_extra_row(
        lambda name: _ahead_server(tiny, name), _prompts(tiny, 13, (7,))[0],
        MAX_SEQ)


def test_eos_streams_nothing_past_it_with_a_step_in_flight(tiny):
    _serve_ahead.check_eos_streams_nothing_past_it(
        lambda name, slots=None: _ahead_server(tiny, name, slots),
        *_prompts(tiny, 14, (6, 9, 12)), followed=_followed)


def test_a_sampling_sequence_turns_the_steps_serial(tiny):
    _serve_ahead.check_a_sampler_turns_the_steps_serial(
        lambda name: _ahead_server(tiny, name),
        *_prompts(tiny, 15, (8, 5)))


def test_steps_ahead_are_counted_where_they_are_dispatched(tiny,
                                                           monkeypatch):
    _serve_ahead.check_the_counter_counts_steps_behind_another(
        lambda name: _ahead_server(tiny, name),
        _prompts(tiny, 16, (5, 19, 7)), monkeypatch)


def test_cancel_drills_and_close_leave_nothing_in_flight(tiny):
    _serve_ahead.check_drills_leave_nothing_in_flight(
        lambda name: _ahead_server(tiny, name), _prompts(tiny, 17, (6, 11)))
