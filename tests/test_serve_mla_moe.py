"""The serving engine's second family (latent attention over a latent
cache plane, routed experts beside a shared one) against its plain
reference (``benchmarks/references/sarvam_mla_moe.py``), at a tiny size on
the CPU, seeded random weights, float32.

The layers are one dense and two sparse, as the benchmark's cut begins;
the chip's share is 4 of 32 routed experts. Nothing here is a time.
"""
import json
import os

import numpy as np
import pytest

import _serve_ahead
import _serve_pick

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAX_SEQ, SLOTS, CHUNK, PAGE = 128, 4, 16, 4
BUCKETS = [16, 32, 64, 128]
TOL = 2e-5      # float32 both sides; logits are of size 1


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "sarvam-105b-l9-ep8.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    """(cfg as the reference reads it, arch as the server is told it,
    params as numpy)."""
    from benchmarks.builders import sarvam_mla_moe as builder
    from benchmarks.lib import leaves, spec
    doc = _config()
    cfg = spec._merge(doc, doc["rehearse"])
    cfg.pop("rehearse")
    cfg["max_position_embeddings"] = MAX_SEQ
    params = {k: np.asarray(v) for k, v in
              leaves.make(builder.leaf_specs(cfg), 7).items()}
    arch = builder.architecture(cfg)
    arch["dtype"] = "float32"
    return cfg, arch, params


def _engine(tiny, name, chunk=CHUNK, slots=SLOTS, dtype="float32"):
    from mxnet_tpu._fused import CompileCache
    from mxnet_tpu.serve.decode import DecodeEngine, extract_params
    from mxnet_tpu.serve.kv_cache import KVCache
    from mxnet_tpu.serve.mla_moe import MlaMoeDecoder
    _cfg, arch, params = tiny
    family = MlaMoeDecoder(extract_params(params, dtype), arch)
    cache = KVCache(family.planes(MAX_SEQ, PAGE, False), max_slots=slots,
                    max_seq=MAX_SEQ, page=PAGE, name=name)
    return DecodeEngine(family, cache, CompileCache(name), name=name,
                        seq_buckets=BUCKETS, prefill_chunk=chunk)


def _reference(tiny, tokens):
    import jax.numpy as jnp
    from benchmarks.references import sarvam_mla_moe as ref
    cfg, _arch, params = tiny
    return np.asarray(ref.forward(
        cfg, {k: jnp.asarray(v) for k, v in params.items()},
        jnp.asarray(tokens)))


def test_prefill_in_chunks_then_decode_follows_the_reference(tiny):
    """Four slots of unequal length in every step, prompts of one chunk,
    of three and of a padded last one; teacher-forced, logits against the
    reference's full forward at every position."""
    cfg = tiny[0]
    assert cfg["num_hidden_layers"] == 3 and cfg["num_experts"] == 4
    eng = _engine(tiny, "mmfollow")
    rng = np.random.default_rng(0)
    prompt = [5, 50, 23, 33]
    seqs = [rng.integers(0, cfg["vocab_held"], n + 22) for n in prompt]
    want = [_reference(tiny, s) for s in seqs]
    pos = np.zeros(SLOTS, np.int32)
    for s in range(SLOTS):
        picked, got = eng.prefill(seqs[s][:prompt[s]], s, logits=True)
        assert picked == int(np.argmax(got))
        assert np.abs(got - want[s][prompt[s] - 1]).max() < TOL
        pos[s] = prompt[s]
    active = np.ones(SLOTS, bool)
    for _ in range(20):
        tokens = np.array([seqs[s][pos[s]] for s in range(SLOTS)], np.int32)
        picked, got = eng.decode_step(tokens, pos, active, logits=True)
        assert got.shape == (SLOTS, cfg["vocab_held"])
        assert (picked == np.argmax(got, axis=-1)).all()
        for s in range(SLOTS):
            assert np.abs(got[s] - want[s][pos[s]]).max() < TOL, (s, pos)
        pos += 1
    # the longest slot crossed a bucket's edge: two decode programs
    assert (eng.seq_bucket(50 + 1), eng.seq_bucket(69 + 1)) == (64, 128)


def test_chunked_prefill_equals_one_shot(tiny):
    """A 45-token prompt in chunks of 16 and in one chunk of 64: the same
    logits, and the same rows in the cache's plane."""
    cfg = tiny[0]
    prompt = np.random.default_rng(1).integers(0, cfg["vocab_held"], 45)
    chunked, whole = _engine(tiny, "mmchunk"), _engine(tiny, "mmwhole",
                                                       chunk=64)
    (ta, a), (tb, b) = (e.prefill(prompt, 1, logits=True)
                        for e in (chunked, whole))
    assert np.abs(a - b).max() < TOL and ta == tb
    (pa,), (pb,) = chunked.cache.state(), whole.cache.state()
    rows_a = np.asarray(pa)[:, 1, :45]
    assert np.abs(rows_a).max() > 0
    assert np.abs(rows_a - np.asarray(pb)[:, 1, :45]).max() < TOL
    # a row is [c_kv 16 | k_rope 8], zeros behind it up to the tile
    assert rows_a.shape[-1] == 128 and np.abs(rows_a[..., 24:]).max() == 0
    assert [c for c, _b, _a, _s in chunked.family.prefill_calls(
        prompt, 1)] == [(16, 16), (16, 32), (16, 64)]


def test_an_empty_slot_is_routed_nowhere_and_masked(tiny):
    import mxnet_tpu as mx
    eng = _engine(tiny, "mmempty")
    cfg = tiny[0]
    prompt = np.random.default_rng(2).integers(0, cfg["vocab_held"], 9)
    eng.prefill(prompt, 2)
    pos = np.array([0, 0, 9, 0], np.int32)
    active = np.array([False, False, True, False])
    before = mx.profiler.get_counter("mmempty_moe_assignments")
    picked, out = eng.decode_step(np.array([0, 0, 3, 0], np.int32), pos,
                                  active, logits=True)
    sent = mx.profiler.get_counter("mmempty_moe_assignments") - before
    assert (out[~active] < -1e29).all() and out.shape == (SLOTS, 256)
    assert picked.shape == (SLOTS,) and picked[2] == np.argmax(out[2])
    # one token, two sparse layers, at most experts-per-token each
    assert 0 <= sent <= 2 * cfg["num_experts_per_tok"]
    assert mx.profiler.get_counter("mmempty_moe_experts_hit") <= sent
    # ten keys resident; the step's bucket reads 16 of each of 4 slots
    assert mx.profiler.get_counter("mmempty_mla_keys_resident") == 10
    assert mx.profiler.get_counter("mmempty_mla_keys_read") == 16 * SLOTS
    assert mx.profiler.get_counter("mmempty_prefill_chunks") == 1


def _server(tiny, name, slots=SLOTS):
    import mxnet_tpu as mx
    _cfg, arch, params = tiny
    return mx.serve.GenerativeServer(
        params, arch=arch, max_sequences=slots, seq_buckets=BUCKETS,
        prefill_chunk=CHUNK, page=PAGE, name=name)


def test_server_serves_it_and_compiles_nothing_after_warm_up(tiny):
    """Through ``GenerativeServer.submit_generate``; greedy tokens are the
    reference's best at every step, and a second round of the same shapes
    builds no program."""
    import mxnet_tpu as mx
    cfg = tiny[0]
    srv = _server(tiny, "mmsrv")
    try:
        rng = np.random.default_rng(3)
        lengths = (5, 40, 23, 70)
        prompts = [rng.integers(0, cfg["vocab_held"], n) for n in lengths]

        def round_():
            hs = [srv.submit_generate(p, max_new_tokens=12)
                  for p in prompts]
            return [h.result(timeout=600) for h in hs]
        outs = round_()
        warm = srv.stats()["compiles"]
        assert warm <= srv.stats()["executable_bound"]
        for p, toks in zip(prompts, outs):
            assert all(0 <= t < cfg["vocab_held"] for t in toks)
            z = _reference(tiny, np.concatenate([p, toks[:-1]]))
            z = z[len(p) - 1 + np.arange(len(toks))]
            gap = z.max(-1) - z[np.arange(len(toks)), toks]
            assert gap.max() < TOL
        assert round_() == outs
        assert srv.stats()["compiles"] == warm
        assert srv.stats()["kv"]["hbm_bytes"] == SLOTS * MAX_SEQ * 4 * (
            3 * 128)
        assert mx.profiler.get_counter("mmsrv_prefill_chunks") == 2 * (
            1 + 3 + 2 + 5)
    finally:
        srv.close()


# ------------------------------------- the token is chosen on the device

def test_picked_is_the_argmax_of_the_steps_logits(tiny):
    cfg = tiny[0]
    rng = np.random.default_rng(4)
    _serve_pick.check_picked_is_the_logits_argmax(
        _engine(tiny, "mmpick"), cfg["vocab_held"],
        {s: rng.integers(0, cfg["vocab_held"], n)
         for s, n in ((0, 21), (1, 5), (3, 40))})


def test_a_greedy_step_fetches_its_tokens_and_no_logits(tiny, monkeypatch):
    prompt = np.random.default_rng(5).integers(0, tiny[0]["vocab_held"], 21)
    _serve_pick.check_engine_fetches_logits_when_asked(
        _engine(tiny, "mmpickfetch"), prompt, monkeypatch)


def test_a_greedy_server_fetches_tokens_only(tiny, monkeypatch):
    rng = np.random.default_rng(6)
    _serve_pick.check_greedy_server_fetches_tokens_only(
        _server(tiny, "mmpickgreedy"),
        [rng.integers(0, tiny[0]["vocab_held"], n) for n in (5, 40, 23)],
        monkeypatch)


def test_a_sampling_request_among_greedy_ones(tiny):
    rng = np.random.default_rng(7)
    p = [rng.integers(0, tiny[0]["vocab_held"], n) for n in (5, 40, 23)]
    _serve_pick.check_a_sampling_request_among_greedy_ones(
        lambda name: _server(tiny, "mmpick" + name),
        [(p[0], {"max_new_tokens": 9}),
         (p[1], {"max_new_tokens": 5, "temperature": 0.8, "seed": 5}),
         (p[2], {"max_new_tokens": 8})])


# ------------------------------------ step N+1 dispatched before N's tokens

def _ahead_server(tiny, name, slots=None):
    return _server(tiny, "mmahead" + name, slots or SLOTS)


def _prompts(tiny, seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, tiny[0]["vocab_held"], n) for n in lengths]


def test_the_engine_takes_the_last_steps_tokens_on_the_device(tiny):
    p = _prompts(tiny, 11, (21, 5, 40))
    _serve_ahead.check_engine_takes_the_last_steps_tokens(
        _engine(tiny, "mmahserial"), _engine(tiny, "mmahahead"),
        {0: p[0], 1: p[1], 3: p[2]})


def test_greedy_tokens_ahead_are_the_serial_paths(tiny):
    _serve_ahead.check_ahead_equals_serial(
        _ahead_server(tiny, "equal"), _engine(tiny, "mmahequalserial"),
        _prompts(tiny, 12, (5, 40, 23, 9)))


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


def test_expert_counters_read_what_they_read_in_the_logits_row(tiny):
    """``moe_assignments`` / ``moe_experts_hit`` ride behind the picked
    tokens as int32 now. Three of four slots resident, six teacher-forced
    steps, the counters read after each: the figures are the ones the
    program gave when they rode in a float32 row under the logits (read
    at the parent of PR 29 on this scenario)."""
    import mxnet_tpu as mx
    cfg = tiny[0]
    eng = _engine(tiny, "mmcounts")
    rng = np.random.default_rng(8)
    pos = np.array([21, 0, 5, 40], np.int32)
    active = pos > 0
    for s in np.flatnonzero(active):
        eng.prefill(rng.integers(0, cfg["vocab_held"], pos[s]), int(s))
    read = []
    for _ in range(6):
        tokens = np.where(active, rng.integers(0, cfg["vocab_held"], SLOTS),
                          0).astype(np.int32)
        eng.decode_step(tokens, pos, active)
        pos[active] += 1
        read.append((mx.profiler.get_counter("mmcounts_moe_assignments"),
                     mx.profiler.get_counter("mmcounts_moe_experts_hit")))
    assert read == [(7, 4), (13, 9), (18, 13), (26, 18), (29, 20), (38, 25)]


def test_server_refuses_what_it_cannot_serve(tiny):
    import mxnet_tpu as mx
    _cfg, arch, params = tiny
    with pytest.raises(ValueError, match="no model_type"):
        mx.serve.GenerativeServer(params, arch=dict(arch, model_type="x"))
    with pytest.raises(ValueError, match="n_heads"):
        mx.serve.GenerativeServer(params)
    short = dict(arch, experts_held=[0, 2])
    with pytest.raises(ValueError, match="experts_gate_weight"):
        mx.serve.GenerativeServer(params, arch=short, max_sequences=2)
    with pytest.raises(ValueError, match="lacks"):
        mx.serve.GenerativeServer(params, arch={"model_type": "sarvam_mla"})


def test_a_family_claims_its_description_and_the_server_names_none(tiny):
    """The entry point asks ``family_for``, which asks the family's own
    module whether a description is its; one that no family claims is
    refused by name."""
    import inspect
    from mxnet_tpu.serve import decode, mla_moe, server
    _cfg, arch, params = tiny
    assert mla_moe.serves(arch)
    assert isinstance(decode.family_for(params, arch=arch),
                      mla_moe.MlaMoeDecoder)
    source = inspect.getsource(server.GenerativeServer.__init__)
    assert "model_type" not in source and "mla_moe" not in source
    assert not mla_moe.serves({"model_type": "made_up"})
    with pytest.raises(ValueError, match="made_up"):
        decode.family_for(params, arch={"model_type": "made_up"})


# ------------------------------------------------------------ the layer's parts


def _layer(tiny, i):
    import jax.numpy as jnp
    from mxnet_tpu.models import mla_moe as layer
    _cfg, arch, params = tiny
    a = layer.Arch(arch)
    p = {k: jnp.asarray(v) for k, v in
         layer.layer_params(params, i).items()}
    return layer, a, p


def test_absorbed_attention_equals_per_head_mla(tiny):
    """The decode step's form over the stored rows of each slot against
    the published per-head form over keys and values expanded from them,
    under ragged masks."""
    import jax.numpy as jnp
    layer, a, p = _layer(tiny, 1)
    rng = np.random.default_rng(4)
    n, s = 6, 40
    h = jnp.asarray(rng.normal(size=(n * s, a.d)).astype(np.float32))
    pos = jnp.tile(jnp.arange(s), n)
    q_nope, q_rope, rows = layer.mla_project(a, p, h, pos)
    rows = layer.stored_row(a, rows, jnp.float32).reshape(n, s, -1)
    keep = jnp.asarray(rng.random((n, s)) < 0.6).at[:, 0].set(True)
    # one query a slot: the last position's
    qn = q_nope.reshape(n, s, a.heads, -1)[:, -1]
    qr = q_rope.reshape(n, s, a.heads, -1)[:, -1]
    absorbed = layer.expand_values(a, p, layer.attend(
        a, layer.absorb_query(a, p, qn, qr), rows, keep))
    for i in range(n):
        k_nope, v = layer.expand_keys_values(a, p, rows[i])
        per_head = layer.dense(layer.attend_per_head(
            a, qn[i:i + 1], qr[i:i + 1], k_nope, v,
            rows[i, :, a.kv_rank:a.row], keep[i:i + 1]), p["att_o_weight"])
        assert np.abs(np.asarray(absorbed[i] - per_head[0])).max() < 1e-5


def test_yarn_frequencies_against_a_hand_table():
    """At the published sizes (64 rotary lanes, base 10000, factor 40 over
    4096, beta 32 and 1): the ramp runs from pair 10 to pair 23."""
    from mxnet_tpu.models.mla_moe import yarn_frequencies
    scaling = _config()["rope_scaling"]
    freq, on_angles, on_scores = yarn_frequencies(64, 10000.0, scaling)
    assert freq.shape == (32,) and on_angles == 1.0
    hand = {0: 1.0,                                 # fast pairs: untouched
            5: 10.0 ** -0.625,
            10: 10.0 ** -1.25,                      # the ramp's foot
            16: 1e-2 * (1 - 6 / 13.0) + 1e-2 / 40 * (6 / 13.0),
            22: 10.0 ** -2.75 * (1 / 13.0) + 10.0 ** -2.75 / 40 * (12 / 13.0),
            23: 10.0 ** -2.875 / 40,                # slow pairs: over 40
            31: 10.0 ** -3.875 / 40}
    for i, want in hand.items():
        assert freq[i] == pytest.approx(want, rel=1e-5), i
    m = 0.1 * np.log(40.0) + 1.0
    assert m == pytest.approx(1.3689, abs=1e-4)
    assert on_scores == pytest.approx(m * m, rel=1e-6)
    # without scaling: the plain rotary, nothing on the scores
    plain, one, scores = yarn_frequencies(8, 100.0, None)
    assert np.allclose(plain, [1.0, 100 ** -0.25, 0.1, 100 ** -0.75])
    assert one == 1.0 and scores == 1.0
    with pytest.raises(ValueError, match="not served"):
        yarn_frequencies(8, 100.0, {"type": "linear", "factor": 2})


def test_the_rotary_turns_lane_i_with_lane_i_plus_half(tiny):
    import jax.numpy as jnp
    layer, a, _p = _layer(tiny, 0)
    x = jnp.asarray(np.random.default_rng(6).normal(size=(3, 2, 8))
                    .astype(np.float32))
    pos = jnp.asarray([0, 1, 7])
    y = np.asarray(layer.rope(a, x, pos))
    assert np.abs(y[0] - np.asarray(x[0])).max() < 1e-6     # position 0
    for i in range(4):
        ang = 7 * float(a.rope_freq[i])
        lo, hi = np.asarray(x[2, :, i]), np.asarray(x[2, :, i + 4])
        assert np.abs(y[2, :, i] - (lo * np.cos(ang) - hi * np.sin(ang))
                      ).max() < 1e-5
        assert np.abs(y[2, :, i + 4] - (lo * np.sin(ang) + hi * np.cos(ang))
                      ).max() < 1e-5
    # the softmax scale carries YaRN's factor squared
    assert a.score_scale == pytest.approx(
        16 ** -0.5 * (0.1 * np.log(40.0) + 1.0) ** 2, rel=1e-6)


# ------------------------------------------------------------- the expert layer


def _expert_case(tiny, tokens=24, bias_scale=1.0):
    """One sparse layer's leaves with ALL its routed experts (32 of 32),
    and tokens to route."""
    import jax.numpy as jnp
    from benchmarks.builders import sarvam_mla_moe as builder
    from benchmarks.lib import leaves
    cfg = dict(tiny[0])
    cfg["num_experts"] = cfg["published"]["num_experts"]
    specs = {n: s for n, s in builder.leaf_specs(cfg).items()
             if n.startswith("layer1_")}
    p = {k[len("layer1_"):]: jnp.asarray(v) * (
        bias_scale if k.endswith("router_bias") else 1.0)
        for k, v in leaves.make(specs, 11).items()}
    h = jnp.asarray(np.random.default_rng(8).normal(
        size=(tokens, cfg["hidden_size"])).astype(np.float32))
    return cfg, p, h


_EXPERT_LEAVES = ("experts_gate_weight", "experts_up_weight",
                  "experts_down_weight")


def test_eight_shares_and_the_shared_expert_once_make_the_whole_layer(tiny):
    import jax.numpy as jnp
    from benchmarks.references import sarvam_mla_moe as ref
    from mxnet_tpu.models import mla_moe as layer
    from mxnet_tpu.parallel.moe import moe_share_apply, route_sigmoid
    cfg, p, h = _expert_case(tiny)
    z = ref.sizes(cfg)
    assert (z["routed"], z["held"], z["first"]) == (32, 32, 0)
    whole = np.asarray(ref.ffn(z, p, h, "sparse", "highest"))
    experts, gates = route_sigmoid(h, p["router_weight"], p["router_bias"],
                                   top_k=z["per_tok"], scaling=z["scaling"])
    total = layer.gated_mlp(h, p["shared_gate_weight"], p["shared_up_weight"],
                            p["shared_down_weight"])
    sent = 0
    for share in range(8):
        lo = 4 * share
        y, counts = moe_share_apply(
            h, experts, gates, *(p[k][lo:lo + 4] for k in _EXPERT_LEAVES),
            first=lo)
        total = total + y
        sent += int(jnp.sum(counts))
        # a share alone is the reference's share
        part = ref.experts(
            z, {**p, **{k: p[k][lo:lo + 4] for k in _EXPERT_LEAVES}},
            h, "highest", first=lo, held=4)
        assert np.abs(np.asarray(y) - np.asarray(part)).max() < 1e-5
    assert sent == h.shape[0] * z["per_tok"]
    assert np.abs(np.asarray(total) - whole).max() < 1e-5


def test_every_token_to_one_expert_drops_none(tiny):
    from benchmarks.references import sarvam_mla_moe as ref
    from mxnet_tpu.parallel.moe import moe_share_apply, route_sigmoid
    cfg, p, h = _expert_case(tiny, tokens=40)
    # a bias no score can match sends every token to expert 5 (and to its
    # seven other choices as before)
    p["router_bias"] = p["router_bias"].at[5].set(10.0)
    z = ref.sizes(cfg)
    experts, gates = route_sigmoid(h, p["router_weight"], p["router_bias"],
                                   top_k=z["per_tok"], scaling=z["scaling"])
    assert (np.asarray(experts) == 5).sum() == 40
    y, counts = moe_share_apply(
        h, experts, gates, *(p[k][4:8] for k in _EXPERT_LEAVES), first=4)
    assert int(counts[1]) == 40
    part = ref.experts(z, {**p, **{k: p[k][4:8] for k in _EXPERT_LEAVES}},
                       h, "highest", first=4, held=4)
    assert np.abs(np.asarray(y) - np.asarray(part)).max() < 1e-5


def test_the_bias_chooses_and_the_score_weighs(tiny):
    import jax
    from mxnet_tpu.parallel.moe import route_sigmoid
    _cfg, p, h = _expert_case(tiny, bias_scale=60.0)
    s = np.asarray(jax.nn.sigmoid(h @ p["router_weight"].T))
    b = np.asarray(p["router_bias"])
    experts, gates = route_sigmoid(h, p["router_weight"], p["router_bias"],
                                   top_k=8, scaling=2.5)
    experts, gates = np.asarray(experts), np.asarray(gates)
    by_score = np.argsort(-s, axis=1)[:, :8]
    by_both = np.argsort(-(s + b), axis=1)[:, :8]
    assert (np.sort(experts, 1) == np.sort(by_both, 1)).all()
    assert (np.sort(by_both, 1) != np.sort(by_score, 1)).any()
    picked = np.take_along_axis(s, experts, 1)
    assert np.abs(gates - 2.5 * picked / picked.sum(1, keepdims=True)
                  ).max() < 1e-6
    assert np.abs(gates.sum(1) - 2.5).max() < 1e-5


# ------------------------------------------------------------------ the cache


def test_planes_reckon_bytes_audit_and_capacity():
    from mxnet_tpu.serve.kv_cache import (KVCache, Plane, dense_planes,
                                          max_slots_for)
    # the benchmark's cache: 48 slots of 4096 rows of 640 on 9 layers
    planes = [Plane("latent", 9, 640, "bfloat16")]
    per_slot = 4096 * 9 * 640 * 2
    assert sum(p.bytes_per_slot(4096) for p in planes) == per_slot
    assert 48 * per_slot == 2264924160                      # 2.26 GB
    assert max_slots_for(4 * per_slot + 5, planes, 4096) == 4
    cache = KVCache([Plane("latent", 5, 128, "float32"),
                     Plane("other", 2, 16, "float32")],
                    max_slots=3, max_seq=64, page=4, name="pl")
    assert [a.shape for a in cache.state()] == [(5, 3, 64, 128),
                                                (2, 3, 64, 16)]
    assert cache.hbm_bytes() == 3 * 64 * 4 * (5 * 128 + 2 * 16)
    assert cache.plane("other").shape == (2, 3, 64, 16)
    assert cache.audit()["reserved_bytes"] == cache.hbm_bytes()
    assert cache.int8 is False
    # the dense decoder's layout is what it was
    for int8 in (False, True):
        planes = dense_planes(3, 4, 16, 64, 16, int8)
        dense = KVCache(planes, max_slots=5, max_seq=64, page=16, name="dn")
        assert [p.name for p in planes] == ["k", "v"] + (
            ["k_scale", "v_scale"] if int8 else [])
        assert dense.plane("k").shape == dense.plane("v").shape \
            == (3, 5, 64, 64)
        assert len(dense.state()) == (4 if int8 else 2)
        assert dense.hbm_bytes() == sum(
            int(np.prod(a.shape)) * a.dtype.itemsize for a in dense.state())
        assert max_slots_for(dense.hbm_bytes(), planes, 64) == 5


def test_extract_params_keeps_a_stated_dtype():
    import jax.numpy as jnp
    from mxnet_tpu.serve.decode import extract_params
    held = jnp.ones((2, 3), jnp.bfloat16)
    out = extract_params({"a": held, "b": np.ones((2,), np.float32)},
                         dtype="bfloat16")
    assert out["a"] is held and out["b"].dtype == jnp.bfloat16
    assert extract_params({"a": held})["a"].dtype == jnp.float32


# ---------------------------------------------------------- the stated dtype


@pytest.mark.parametrize("seed", [7, 8, 9])
def test_bfloat16_as_stated_passes_a_tolerance_that_float8_fails(tiny, seed):
    """Weights and cache in bfloat16, as the configuration states, served
    through prefill in chunks and 29 decode steps. Off the TPU
    ``rtc.product_operands`` widens the rounded operands to float32, so
    this test rounds as the chip does but never runs a bfloat16 product:
    the compiler's own bfloat16 products are exercised on the chip only
    (the cell's ``correct``). The
    logits' error against the float32 reference is under a tolerance that
    the control fails: the reference itself with every product's operands
    rounded to float8_e4m3fn, the step below."""
    import jax.numpy as jnp
    from benchmarks.builders import sarvam_mla_moe as builder
    from benchmarks.lib import leaves
    from benchmarks.references import sarvam_mla_moe as ref
    cfg, arch, _params = tiny
    params = {k: jnp.asarray(v).astype(jnp.bfloat16) for k, v in
              leaves.make(builder.leaf_specs(cfg), seed).items()}
    eng = _engine((cfg, dict(arch, dtype="bfloat16"), params),
                  "mmbf16_%d" % seed, slots=2, dtype="bfloat16")
    assert {str(a.dtype) for a in eng.cache.state()} == {"bfloat16"}
    seq = np.random.default_rng(seed).integers(0, cfg["vocab_held"], 70)
    got = [eng.prefill(seq[:40], 0, logits=True)[1]]
    pos, active = np.array([40, 0], np.int32), np.array([True, False])
    for j in range(29):
        got.append(eng.decode_step(np.array([seq[40 + j], 0], np.int32),
                                   pos, active, logits=True)[1][0])
        pos[0] += 1
    held = {k: v.astype(jnp.float32) for k, v in params.items()}
    want = np.asarray(ref.forward(cfg, held, jnp.asarray(seq[:69])))[39:]
    control = np.asarray(ref.forward(cfg, held, jnp.asarray(seq[:69]),
                                     "fp8"))[39:]

    def rms(e):
        return float(np.sqrt(np.mean(np.square(e))))
    print("bf16 %.5f control %.5f" % (rms(np.stack(got) - want),
                                      rms(control - want)))
    assert rms(np.stack(got) - want) < TOLERANCE
    assert rms(control - want) > TOLERANCE


# logits of std 0.16 at this size; between the two readings of the three
# seeds: bfloat16 0.0003, the control 0.007 to 0.008 (the test prints them)
TOLERANCE = 0.002
