"""The serving engine's fourth family (full and windowed grouped-query
attention, the windows a ring a slot, routed experts behind them) against
its plain reference (``benchmarks/references/mimo_window_moe.py``), at a
tiny size on the CPU, seeded random weights, float32.

Tiny (the configuration's ``rehearse`` sizes): published layers 0 to 6 (2
full, 5 window, the first dense), 4 query heads, 2 key/value heads on full
layers and 4 on window layers, keys of 128 lanes for values of 64, a
window of 8, 4 of 32 experts held. Nothing here is a time.
"""
import json
import os

import numpy as np
import pytest

import _serve_ahead

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAX_SEQ, SLOTS, CHUNK, PAGE = 96, 3, 12, 4
BUCKETS = [32, 96]
TOL = 5e-5      # float32 both sides; logits are of size 8


def _config(**over):
    from benchmarks.lib import spec
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "mimo-v2-flash-l7-ep16.json")) as f:
        doc = json.load(f)
    cfg = spec._merge(doc, doc["rehearse"])
    cfg.pop("rehearse")
    cfg["max_position_embeddings"] = MAX_SEQ
    cfg.update(over)
    return cfg


def _tiny(cfg, seed=7):
    from benchmarks.builders import mimo_window_moe as builder
    from benchmarks.lib import leaves
    params = {k: np.asarray(v) for k, v in
              leaves.make(builder.leaf_specs(cfg), seed).items()}
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
    from mxnet_tpu.serve.window_moe import WindowMoeDecoder
    _cfg, arch, params = tiny
    family = WindowMoeDecoder(extract_params(params, "float32"), arch)
    cache = KVCache(family.planes(max_seq, PAGE, False), max_slots=slots,
                    max_seq=max_seq, page=PAGE, name=name)
    return DecodeEngine(family, cache, CompileCache(name), name=name,
                        seq_buckets=buckets, prefill_chunk=chunk)


def _reference(tiny, tokens):
    import jax.numpy as jnp
    from benchmarks.references import mimo_window_moe as ref
    cfg, _arch, params = tiny
    return np.asarray(ref.forward(
        cfg, {k: jnp.asarray(v) for k, v in params.items()},
        jnp.asarray(tokens)))


def test_the_tiny_stack_is_as_described(tiny):
    from mxnet_tpu.models import window_moe as m
    cfg, arch, _params = tiny
    a = m.Arch(arch)
    assert a.attn_types == ["full"] + ["window"] * 4 + ["full", "window"]
    assert a.mlp_types == ["dense"] + ["moe"] * 6
    full, win = a.kinds["full"], a.kinds["window"]
    assert (full.kv_heads, win.kv_heads, a.window) == (2, 4, 8)
    assert full.d_k > full.d_v and (a.experts_held, a.n_routed) == (4, 32)
    assert not full.sink and win.sink
    assert full.rot == int(0.334 * 128) // 2 * 2 and win.freq[1] > 0


def test_prefill_in_chunks_then_decode_follows_the_reference(tiny):
    """Three slots of unequal length in every step: a prompt shorter than
    the window, one of three chunks and one of 61 tokens, chunks of 12
    (no multiple of the window of 8: the rings wrap inside a chunk and
    across its edges); teacher-forced, logits against the reference's
    full forward at every position, and the kernel's steps counted."""
    import mxnet_tpu as mx
    cfg = tiny[0]
    eng = _engine(tiny, "wmfollow")
    assert eng.family.kernel_reads(96) and eng.family.kernel_reads(32)
    rng = np.random.default_rng(0)
    prompt = [5, 30, 61]
    seqs = [rng.integers(0, cfg["vocab_held"], n + 12) for n in prompt]
    want = [_reference(tiny, s) for s in seqs]
    pos = np.zeros(SLOTS, np.int32)
    for s in range(SLOTS):
        picked, got = eng.prefill(seqs[s][:prompt[s]], s, logits=True)
        assert picked == int(np.argmax(got))
        assert np.abs(got - want[s][prompt[s] - 1]).max() < TOL, s
        pos[s] = prompt[s]
    active = np.ones(SLOTS, bool)
    steps = 12
    for _ in range(steps):
        tokens = np.array([seqs[s][pos[s]] for s in range(SLOTS)], np.int32)
        picked, got = eng.decode_step(tokens, pos, active, logits=True)
        assert got.shape == (SLOTS, cfg["vocab_held"])
        assert (picked == np.argmax(got, axis=-1)).all()
        for s in range(SLOTS):
            assert np.abs(got[s] - want[s][pos[s]]).max() < TOL, (s, pos)
        pos += 1
    # a step's key rows, from the positions: pos + 1 on each of 2 full
    # layers, at most 8 on each of 5 window layers
    keys = np.array([[n + i + 1 for n in prompt] for i in range(steps)])
    assert mx.profiler.get_counter("wmfollow_full_rows_read") \
        == 2 * keys.sum()
    assert mx.profiler.get_counter("wmfollow_window_rows_read") \
        == 5 * np.minimum(keys, 8).sum()
    assert mx.profiler.get_counter("wmfollow_gqa_decode_kernel_steps") \
        == steps
    assert mx.profiler.get_counter("wmfollow_moe_assignments") > 0


@pytest.mark.parametrize("chunk", [1, 3, 12, 48])
def test_the_same_logits_and_rings_for_every_chunk_size(tiny, chunk):
    """A 45-token prompt in chunks of 1, 3, 12 and in one chunk: the same
    logits as the reference's, and the same full-layer rows and rings as
    a prefill of the whole prompt at once."""
    cfg = tiny[0]
    prompt = np.random.default_rng(1).integers(0, cfg["vocab_held"], 45)
    want = _reference(tiny, prompt)[-1]
    eng = _engine(tiny, "wmchunk%d" % chunk, chunk=chunk, buckets=[48, 96])
    tok, got = eng.prefill(prompt, 1, logits=True)
    assert np.abs(got - want).max() < TOL and tok == int(np.argmax(want))
    one = _engine(tiny, "wmwhole%d" % chunk, chunk=96, buckets=[96])
    one.prefill(prompt, 1)
    for a, b in zip(eng.cache.state(), one.cache.state()):
        a, b = np.asarray(a)[:, 1], np.asarray(b)[:, 1]
        if a.shape[1] == MAX_SEQ:           # a full layer's plane
            a, b = a[:, :45], b[:, :45]
        assert np.abs(a).max() > 0 and np.abs(a - b).max() < TOL


def test_the_planes_and_what_a_slot_costs(tiny):
    eng = _engine(tiny, "wmplanes")
    planes = eng.cache.planes
    assert [p.name for p in planes] == ["k", "v", "kw", "vw"]
    k, v, kw, vw = (np.asarray(x) for x in eng.cache.state())
    assert k.shape == (2, SLOTS, MAX_SEQ, 2 * 128)
    assert v.shape == (2, SLOTS, MAX_SEQ, 2 * 64)
    # a ring of the window's 8 rows a slot, whatever max_seq
    assert kw.shape == (5, SLOTS, 8, 4 * 128)
    assert vw.shape == (5, SLOTS, 8, 4 * 64)
    assert [p.kind for p in planes] == ["kv_cache", "kv_cache",
                                        "slot_state", "slot_state"]
    assert eng.cache.hbm_bytes() == 4 * SLOTS * (
        2 * MAX_SEQ * (256 + 128) + 5 * 8 * (512 + 256))


def test_a_slot_taken_again_sees_nothing_of_its_last_tenant(tiny):
    """A long prompt fills slot 0's rings and rows; a short one prefilled
    into the same slot afterwards reads none of it: its logits are the
    reference's, in prefill and in the decode steps that follow."""
    cfg = tiny[0]
    rng = np.random.default_rng(2)
    eng = _engine(tiny, "wmreuse")
    eng.prefill(rng.integers(0, cfg["vocab_held"], 70), 0)
    short = rng.integers(0, cfg["vocab_held"], 9)
    want = _reference(tiny, short)
    _tok, got = eng.prefill(short[:3], 0, logits=True)
    assert np.abs(got - want[2]).max() < TOL
    pos = np.array([3, 0, 0], np.int32)
    active = np.array([True, False, False])
    for t in range(3, 9):
        tokens = np.array([short[t], 0, 0], np.int32)
        _p, got = eng.decode_step(tokens, pos, active, logits=True)
        assert np.abs(got[0] - want[t]).max() < TOL, t
        pos[0] += 1


def test_the_sink_enters_the_denominator_only(tiny):
    """A sink of -inf is plain softmax; a large one takes the mass and
    shrinks the output towards zero; both as the reference computes."""
    import jax.numpy as jnp
    from mxnet_tpu.models import window_moe as m
    _cfg, arch, _params = tiny
    win = m.Arch(arch).kinds["window"]
    rng = np.random.default_rng(3)
    n, s = 5, 8
    q = jnp.asarray(rng.normal(size=(n, win.kv_heads, win.group, win.d_k)),
                    jnp.float32)
    k = jnp.asarray(rng.normal(size=(n, s, win.k_row)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(n, s, win.v_row)), jnp.float32)
    keep = jnp.asarray(np.arange(s)[None, :] <= np.arange(n)[:, None] + 3)
    plain = m.attend(win, q, k, v, keep)
    none = m.attend(win, q, k, v, keep, jnp.full((win.heads,), -jnp.inf))
    assert np.abs(np.asarray(plain) - np.asarray(none)).max() < 1e-6
    # by hand: the softmax over the kept keys, per head
    qh = np.asarray(q).reshape(n, win.heads, win.d_k)
    kh = np.repeat(np.asarray(k).reshape(n, s, win.kv_heads, win.d_k),
                   win.group, axis=2)
    vh = np.repeat(np.asarray(v).reshape(n, s, win.kv_heads, win.d_v),
                   win.group, axis=2)
    z = np.einsum("nhd,nshd->nhs", qh, kh) * win.score_scale
    sink = rng.normal(size=win.heads).astype(np.float32)
    for with_sink in (False, True):
        e = np.where(np.asarray(keep)[:, None], np.exp(z), 0.0)
        den = e.sum(-1, keepdims=True) \
            + (np.exp(sink)[None, :, None] if with_sink else 0.0)
        want = np.einsum("nhs,nshd->nhd", e / den, vh).reshape(n, -1)
        got = m.attend(win, q, k, v, keep,
                       jnp.asarray(sink) if with_sink else None)
        assert np.abs(np.asarray(got) - want).max() < 1e-4
    big = m.attend(win, q, k, v, keep, jnp.full((win.heads,), 30.0))
    assert np.abs(np.asarray(big)).max() < 1e-6 * np.abs(
        np.asarray(plain)).max()


@pytest.mark.parametrize("kv,group,d_k,d_v", [(2, 2, 128, 64),
                                              (4, 16, 192, 128),
                                              (8, 8, 192, 128)])
def test_the_pallas_kernel_reads_what_the_xla_read_reads(kv, group, d_k,
                                                         d_v):
    """``gqa_decode_attention``, interpreted, against ``attend`` over the
    bucket's rows: the tiny cell's heads and the published full and window
    layers' (two heads of 192 lanes a product), free slots and slots at
    the first and the last key of a bucket, bfloat16 rows."""
    import jax.numpy as jnp
    from mxnet_tpu.models import window_moe as m
    from mxnet_tpu.ops.pallas import gqa_decode_attention as g
    kind = m.Kind(kv * group, kv, d_k, d_v, 1e4, 0.334, False)
    rng = np.random.default_rng(4)
    layers, slots, max_seq, bucket = 2, 5, 64, 48
    q = jnp.asarray(rng.normal(size=(slots, kv, group, d_k)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(layers, slots, max_seq, kv * d_k)),
                    jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(layers, slots, max_seq, kv * d_v)),
                    jnp.bfloat16)
    pos = jnp.asarray([3, 0, 47, 20, 0], jnp.int32)
    active = jnp.asarray([True, False, True, True, True])
    plan = g.fetch_plan(pos, active, g.block_for(bucket, 16))
    got = np.asarray(g.gqa_decode_attention(q, k, v, 1, plan, bucket=bucket,
                                            scale=kind.score_scale,
                                            block_k=16))
    keep = (jnp.arange(bucket)[None, :] <= pos[:, None]) & active[:, None]
    want = np.asarray(m.attend(kind, q, k[1, :, :bucket], v[1, :, :bucket],
                               keep))
    assert got.shape == (slots, kv * group * d_v)
    assert np.abs(got[1]).max() == 0.0      # a free slot's output is 0
    assert np.abs(got - want).max() < 2e-2 * np.abs(want).max()
    assert g.heads_per_product(kv, d_k, d_v) == (2 if d_k == 192 else kv)


def test_the_expert_shares_add_up_to_the_uncut_layer(tiny):
    """The guide's share test: the eight chips that would hold 4 of the 32
    experts each compute their part of one expert layer with the
    program's layer; the parts add up to the reference's layer with every
    expert held."""
    import jax.numpy as jnp
    from benchmarks.builders import mimo_window_moe as builder
    from benchmarks.lib import leaves
    from benchmarks.references import mimo_window_moe as ref
    from mxnet_tpu.models import window_moe as m
    cfg = _config(n_routed_experts=32)
    arch = builder.architecture(cfg)
    arch["dtype"] = "float32"
    p = {k[len("layer1_"):]: jnp.asarray(v) for k, v in leaves.make(
        builder.leaf_specs(cfg), 11).items() if k.startswith("layer1_")}
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.normal(size=(24, cfg["hidden_size"])), jnp.float32)
    whole = np.asarray(ref.experts(cfg, p, h, "highest", first=0, held=32))
    total = np.zeros_like(whole)
    for first in range(0, 32, 4):
        share = dict(arch, experts_held=[first, 4])
        sliced = dict(p)
        for name in ("experts_gate_weight", "experts_up_weight",
                     "experts_down_weight"):
            sliced[name] = p[name][first:first + 4]
        y, counts = m.ffn(m.Arch(share), sliced, h, "moe",
                          jnp.ones((24,), bool))
        total += np.asarray(y)
        assert counts.shape == (4,)
    assert np.abs(whole).max() > 0
    assert np.abs(total - whole).max() < 1e-4 * np.abs(whole).max()


@pytest.mark.parametrize("key,value", [("n_shared_experts", 1),
                                       ("add_full_attention_sink_bias", True),
                                       ("scoring_func", "softmax")])
def test_what_the_family_does_not_serve_is_refused(tiny, key, value):
    from mxnet_tpu.models import window_moe as m
    arch = dict(tiny[1], **{key: value})
    with pytest.raises(ValueError):
        m.Arch(arch)


def test_served_through_the_generative_server(tiny):
    """``GenerativeServer(arch=...)`` finds the family by its
    ``model_type`` and serves it on the normal path: greedy answers are
    the reference's own tokens, and the family's counters move."""
    import mxnet_tpu as mx
    cfg, arch, params = tiny
    srv = mx.serve.GenerativeServer(
        params, arch=dict(arch), max_sequences=2, seq_buckets=[32, 96],
        prefill_chunk=12, prefill_tokens=12, page=PAGE, name="wmsrv")
    try:
        assert type(srv.engine.family).__name__ == "WindowMoeDecoder"
        rng = np.random.default_rng(6)
        prompts = [rng.integers(0, cfg["vocab_held"], n) for n in (7, 26)]
        handles = [srv.submit_generate(p, max_new_tokens=10)
                   for p in prompts]
        for p, h in zip(prompts, handles):
            toks = list(h.result(timeout=600))
            z = _reference(tiny, np.concatenate([p, toks[:-1]]))
            assert toks == list(np.argmax(z[len(p) - 1:], axis=-1))
    finally:
        srv.close()
    assert mx.profiler.get_counter("wmsrv_window_rows_read") > 0
    assert mx.profiler.get_counter("wmsrv_full_rows_read") > 0


# ------------------------------------ step N+1 dispatched before N's tokens

def _ahead_server(tiny, name, slots=None):
    import mxnet_tpu as mx
    _cfg, arch, params = tiny
    return mx.serve.GenerativeServer(
        params, arch=dict(arch), max_sequences=slots or SLOTS,
        seq_buckets=BUCKETS, prefill_chunk=CHUNK, page=PAGE,
        name="wmahead" + name)


def _prompts(tiny, seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, tiny[0]["vocab_held"], n) for n in lengths]


def test_the_engine_takes_the_last_steps_tokens_on_the_device(tiny):
    p = _prompts(tiny, 11, (21, 5))
    _serve_ahead.check_engine_takes_the_last_steps_tokens(
        _engine(tiny, "wmaheadserial"), _engine(tiny, "wmaheadahead"),
        {0: p[0], 2: p[1]})


def test_greedy_tokens_ahead_are_the_serial_paths(tiny):
    _serve_ahead.check_ahead_equals_serial(
        _ahead_server(tiny, "equal"), _engine(tiny, "wmaheadequalserial"),
        _prompts(tiny, 12, (5, 40, 23)))


def test_max_new_tokens_and_max_seq_end_without_an_extra_row(tiny):
    _serve_ahead.check_ends_without_an_extra_row(
        lambda name: _ahead_server(tiny, name), _prompts(tiny, 13, (7,))[0],
        MAX_SEQ)


def test_eos_streams_nothing_past_it_with_a_step_in_flight(tiny):
    _serve_ahead.check_eos_streams_nothing_past_it(
        lambda name, slots=None: _ahead_server(tiny, name, slots),
        *_prompts(tiny, 14, (6, 9, 12)))


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
