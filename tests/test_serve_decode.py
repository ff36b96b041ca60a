"""serve generative decode — continuous batching + bucketed KV cache
(ISSUE 16 tentpole).

The contract under test: prefill logits match the Module forward
bit-for-bit-ish (f32 ~1e-6) at the last real position, greedy
generation is COMPOSITION-INVARIANT (a sequence decodes the same tokens
alone as co-resident with strangers — padding and slot reuse never
bleed), int8 KV tracks f32 within documented tolerance, the executable
universe stays |prompt buckets| + |decode buckets| with zero
steady-state recompiles (counter-asserted), streaming works (iterator /
result / callback), joins land mid-flight, and the fault matrix holds:
``serve.decode`` kills ONE sequence's future, never the co-resident
batch; ``serve.evict`` fails the handle but still frees the pages.
"""
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faults, profiler
from mxnet_tpu import io as io_mod
from mxnet_tpu.serve import (DeadlineExceeded, GenerativeServer, QueueFull,
                             ServeError, ServerClosed)

import _serve_ahead
import _serve_pick

VOCAB, LAYERS, DMODEL, HEADS, SEQ = 128, 2, 32, 2, 16


def _module(seed=11):
    from mxnet_tpu.models import transformer
    net = transformer.get_symbol(vocab_size=VOCAB, num_layers=LAYERS,
                                 d_model=DMODEL, n_heads=HEADS,
                                 seq_len=SEQ)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (1, SEQ))],
             label_shapes=[("softmax_label", (1, SEQ))])
    mx.random.seed(seed)
    mod.init_params(mx.init.Uniform(0.05))
    return mod


@pytest.fixture(scope="module")
def module():
    return _module()


def _ref_probs(mod, seq):
    """Module forward softmax row at the last real position."""
    data = np.zeros((1, SEQ), np.float32)
    data[0, :len(seq)] = seq
    mod.forward(io_mod.DataBatch(data=[mx.nd.array(data)]), is_train=False)
    return mod.get_outputs()[0].asnumpy().reshape(SEQ, -1)[len(seq) - 1]


def _softmax(x):
    e = np.exp(x - x.max())
    return e / e.sum()


def _server(module, **kw):
    kw.setdefault("max_sequences", 4)
    kw.setdefault("page", 4)
    kw.setdefault("int8", False)
    return GenerativeServer(module, n_heads=HEADS, **kw)


def _dense_engine(params, slots, name, page=4, int8=False):
    """The dense decoder's engine over its own planes: (engine, cache)."""
    from mxnet_tpu._fused import CompileCache
    from mxnet_tpu.serve.decode import DecodeEngine, DenseDecoder
    from mxnet_tpu.serve.kv_cache import KVCache
    family = DenseDecoder(params, HEADS)
    cache = KVCache(family.planes(SEQ, page, int8), max_slots=slots,
                    max_seq=SEQ, page=page, name=name)
    return DecodeEngine(family, cache, CompileCache(name), name=name), cache


# ------------------------------------------------------------- correctness

def test_prefill_logits_match_module_forward(module):
    """The decode engine's prefill IS the model: softmax at the last
    real prompt position matches the bucket-padded Module forward."""
    from mxnet_tpu.serve.decode import extract_params
    params = extract_params(module)
    eng, cache = _dense_engine(params, 2, "parity")
    for prompt in ([3, 11, 7, 2, 9], [1], [5] * 15):
        slot = cache.acquire(len(prompt))
        picked, logits = eng.prefill(np.array(prompt), slot, logits=True)
        assert picked == int(np.argmax(logits))
        err = np.abs(_ref_probs(module, prompt)
                     - _softmax(logits)).max()
        assert err < 1e-4, "prompt %r: %g" % (prompt, err)
        cache.release(slot)


def test_decode_steps_match_full_forward(module):
    """Incremental KV decode == full re-forward at every step (greedy
    tokens identical, probabilities within f32 tolerance)."""
    from mxnet_tpu.serve.decode import extract_params
    params = extract_params(module)
    eng, cache = _dense_engine(params, 2, "steps")
    prompt = [3, 11, 7, 2, 9]
    slot = cache.acquire(len(prompt))
    seq = list(prompt) + [eng.prefill(np.array(prompt), slot)[0]]
    pos = len(prompt)
    for _ in range(6):
        t = np.zeros((2,), np.int32)
        p = np.zeros((2,), np.int32)
        a = np.zeros((2,), bool)
        t[slot], p[slot], a[slot] = seq[-1], pos, True
        picked, logits = eng.decode_step(t, p, a, logits=True)
        assert picked[slot] == np.argmax(logits[slot])
        logits = logits[slot]
        cache.grow(slot)
        pos += 1
        ref = _ref_probs(module, seq)
        assert np.abs(ref - _softmax(logits)).max() < 1e-4
        assert int(np.argmax(logits)) == int(np.argmax(ref))
        seq.append(int(np.argmax(logits)))
    cache.release(slot)


def test_greedy_generation_composition_invariant(module):
    """THE continuous-batching correctness property: a sequence decodes
    the SAME greedy tokens alone as co-resident with other sequences —
    slot packing, masking, and bucket padding never bleed across rows."""
    srv = _server(module, name="alone")
    try:
        solo = {p: srv.submit_generate(list(p), max_new_tokens=6)
                .result(timeout=120)
                for p in ((3, 1, 4), (1, 5), (9, 2, 6, 5))}
    finally:
        srv.close()
    srv = _server(module, name="together")
    try:
        handles = {p: srv.submit_generate(list(p), max_new_tokens=6)
                   for p in solo}
        together = {p: h.result(timeout=120) for p, h in handles.items()}
    finally:
        srv.close()
    assert solo == together


def test_int8_kv_matches_f32_within_tolerance(module):
    """int8 KV documented tolerance: the decode softmax within 5e-2 of
    f32 at every step of the same (teacher-forced) sequence, and the same
    greedy token wherever f32's two best lie further apart than that
    (int8 round-trip is exact while a page's scale is unchanged;
    requantization adds bounded noise). Equal greedy tokens over a free
    run are NOT the tolerance: where two logits tie to within the noise
    either may win, and which one did followed the machine's load."""
    from mxnet_tpu.serve.decode import extract_params
    params = extract_params(module)
    prompt, steps, tol = np.array([3, 11, 7]), 8, 5e-2
    engines = [_dense_engine(params, 2, "q%d" % int8, int8=int8)[0]
               for int8 in (False, True)]
    rows = [eng.prefill(prompt, 1, logits=True)[1] for eng in engines]
    pos, active = np.array([0, len(prompt)], np.int32), \
        np.array([False, True])
    for _ in range(steps + 1):
        f32, i8 = (_softmax(r) for r in rows)
        assert np.abs(f32 - i8).max() < tol
        best = np.sort(f32)[-2:]
        if best[1] - best[0] > 2 * tol:
            assert int(np.argmax(rows[0])) == int(np.argmax(rows[1]))
        # both follow f32's greedy token
        tokens = np.array([0, int(np.argmax(rows[0]))], np.int32)
        rows = [eng.decode_step(tokens, pos, active, logits=True)[1][1]
                for eng in engines]
        pos[1] += 1


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
def test_decode_append_lands_in_its_rows_and_nowhere_else(module, int8):
    """One decode step over ragged write positions — the first row, the
    middle of a page, a page's last row, ``max_seq - 1``, and a free
    slot — changes the cache in row ``[layer, slot, pos[slot], :]`` of
    each slot and nowhere else (int8: within that row's page, whose
    scale may grow), and what an active slot's row holds is the token's
    K and V: what a prefill of the same tokens writes there."""
    from mxnet_tpu.serve.decode import extract_params
    page, slots, free = 4, 5, 4
    where = {0: 0, 1: 6, 2: 7, 3: SEQ - 1}
    params = extract_params(module)

    def engine(name):
        return _dense_engine(params, slots, name, page=page, int8=int8)

    eng, cache = engine("append%d" % int8)
    ref_eng, ref_cache = engine("append_ref%d" % int8)
    rng = np.random.default_rng(5)
    tokens = np.zeros((slots,), np.int32)
    pos = np.zeros((slots,), np.int32)
    active = np.zeros((slots,), bool)
    for slot, p in where.items():
        seq = rng.integers(1, VOCAB, p + 1)
        if p:
            eng.prefill(seq[:p], slot)
        ref_eng.prefill(seq, slot)      # the same tokens, whole
        tokens[slot], pos[slot], active[slot] = seq[p], p, True
    before = [np.asarray(a) for a in cache.state()]
    eng.decode_step(tokens, pos, active)
    after = [np.asarray(a) for a in cache.state()]
    ref = [np.asarray(a) for a in ref_cache.state()]

    def rows(state, which, slot, p):
        """(layers, H, d) f32 of row ``p``, dequantized."""
        r = state[which][:, slot, p].astype(np.float32).reshape(
            LAYERS, HEADS, -1)
        if int8:
            r = r * state[2 + which][:, slot, :, p // page][..., None]
        return r

    may_change = np.zeros(before[0].shape[1:3], bool)   # (slots, seq)
    may_scale = np.zeros((slots, SEQ // page), bool)
    for slot in range(slots):
        lo, hi = (pos[slot] // page * page, pos[slot] // page * page + page) \
            if int8 else (pos[slot], pos[slot] + 1)
        may_change[slot, lo:hi] = True
        may_scale[slot, pos[slot] // page] = True
    for which in (0, 1):
        changed = (before[which] != after[which]).any(axis=(0, 3))
        assert not (changed & ~may_change).any(), np.argwhere(
            changed & ~may_change)
        if int8:
            moved = (before[2 + which] != after[2 + which]).any(axis=(0, 2))
            assert not (moved & ~may_scale).any()
        for slot, p in where.items():
            got, want = rows(after, which, slot, p), \
                rows(ref, which, slot, p)
            # int8: each side is within half a step of its own scale,
            # and the keys it attended to were themselves quantized
            tol = 2.0 * np.abs(want).max() / 127.0 if int8 else 1e-5
            assert np.abs(got - want).max() <= tol, (which, slot, p)
    assert not active[free]


def _ragged_step(n_slots):
    """(pos, active) with ragged lengths and free slots first, between
    and last."""
    pos = np.array([0, 9, 0, 3, SEQ - 1, 0, 12, 0][:n_slots], np.int32)
    active = np.array([False, True, False, True, True, False, True,
                       False][:n_slots])
    return pos, active


@pytest.mark.parametrize("bucket,block_k", [(16, 16), (16, 8), (16, 4),
                                            (8, 4)])
def test_decode_attention_kernel_matches_xla_read(bucket, block_k):
    """The Pallas decode-attention kernel, interpreted, against the
    bucket read it replaces — keys 0..pos of each active slot, several
    key blocks a sequence (online softmax across them), free slots
    before, between and after the active ones. atol 1e-5."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.decode_attention import (decode_attention,
                                                       fetch_plan)
    slots, li = 8, 1
    d_head = DMODEL // HEADS
    rng = np.random.default_rng(3)
    k, v = (jnp.asarray(rng.standard_normal((LAYERS, slots, SEQ, DMODEL)),
                        jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((slots, DMODEL)), jnp.float32)
    pos, active = _ragged_step(slots)
    pos = np.minimum(pos, bucket - 1)
    plan = fetch_plan(jnp.asarray(pos), jnp.asarray(active), block_k)
    got = np.asarray(jax.jit(lambda *a: decode_attention(
        *a, n_heads=HEADS, bucket=bucket, block_k=block_k))(
            q, k, v, li, plan))
    kb = np.asarray(k)[li, :, :bucket].reshape(slots, bucket, HEADS, d_head)
    vb = np.asarray(v)[li, :, :bucket].reshape(slots, bucket, HEADS, d_head)
    s = np.einsum("shd,skhd->shk", np.asarray(q).reshape(
        slots, HEADS, d_head), kb) / np.sqrt(d_head)
    s = np.where((np.arange(bucket)[None] <= pos[:, None])[:, None], s,
                 -1e9)
    att = np.exp(s - s.max(-1, keepdims=True))
    att /= att.sum(-1, keepdims=True)
    want = np.einsum("shk,skhd->shd", att, vb).reshape(slots, DMODEL)
    assert np.abs(got - want)[active].max() < 1e-5
    assert not got[~active].any()           # a free slot's row is 0


def test_fetch_plan_fetches_live_blocks_only():
    """Walking the grid with the plan's index map, a block is fetched
    only when its index changes: the fetches are exactly each active
    slot's blocks 0..pos // block, none for a free slot, none past a
    sequence's length."""
    from mxnet_tpu.ops.pallas.decode_attention import fetch_plan
    block, n_blocks = 4, SEQ // 4
    pos, active = _ragged_step(8)
    slot_of, first, last, live = (np.asarray(a) for a in fetch_plan(
        np.asarray(pos), np.asarray(active), block))
    assert (live == np.where(active, pos, -1)).all()
    walk = [(slot_of[s], min(max(j, first[s]), last[s]))
            for s in range(8) for j in range(n_blocks)]
    fetched = [walk[0]] + [b for a, b in zip(walk, walk[1:]) if b != a]
    assert fetched == [(s, j) for s in range(8) if active[s]
                       for j in range(pos[s] // block + 1)]


def test_decode_program_reads_with_the_kernel_where_it_can(module):
    """The engine picks the read from what it observes: float32 on one
    device in TPU-tileable buckets takes the kernel (counted as
    ``_decode_attn_kernel_steps``), int8 keeps the XLA read; both give
    the logits of the XLA read on a ragged step with free slots."""
    from mxnet_tpu.serve.decode import extract_params
    params = extract_params(module)
    pos, active = _ragged_step(8)
    rng = np.random.default_rng(9)
    prompts = {s: rng.integers(1, VOCAB, pos[s])
               for s in np.flatnonzero(active)}
    tokens = np.where(active, rng.integers(1, VOCAB, 8), 0).astype(np.int32)

    def logits(name, int8=False, xla_read=False):
        eng, cache = _dense_engine(params, 8, name, int8=int8)
        if xla_read:
            eng.family.kernel_reads = lambda s_b: False
        for slot, prompt in prompts.items():
            eng.prefill(prompt, slot)
        steps = profiler.get_counter(name + "_decode_attn_kernel_steps")
        _, out = eng.decode_step(tokens, pos, active, logits=True)
        return out, profiler.get_counter(
            name + "_decode_attn_kernel_steps") - steps

    want, n = logits("readxla", xla_read=True)
    assert n == 0
    got, n = logits("readkernel")
    assert n == 1
    assert np.abs(got - want)[active].max() < 1e-5
    assert (got[~active] == want[~active]).all()    # masked rows
    _, n = logits("readint8", int8=True)
    assert n == 0


# ------------------------------------- the token is chosen on the device

@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
def test_picked_is_the_argmax_of_the_steps_logits(module, int8):
    from mxnet_tpu.serve.decode import extract_params
    eng, _cache = _dense_engine(extract_params(module), 5,
                                "pick%d" % int8, int8=int8)
    _serve_pick.check_picked_is_the_logits_argmax(
        eng, VOCAB, {0: [9, 2, 6], 1: [3, 11, 7, 2, 9], 3: [1]})


def test_a_greedy_step_fetches_its_tokens_and_no_logits(module,
                                                        monkeypatch):
    from mxnet_tpu.serve.decode import extract_params
    eng, _cache = _dense_engine(extract_params(module), 4, "pickfetch")
    _serve_pick.check_engine_fetches_logits_when_asked(
        eng, [3, 11, 7], monkeypatch)


def test_a_greedy_server_fetches_tokens_only(module, monkeypatch):
    _serve_pick.check_greedy_server_fetches_tokens_only(
        _server(module, name="pickgreedy"),
        [[3, 1, 4], [1, 5], [9, 2, 6, 5]], monkeypatch)


def test_a_sampling_request_among_greedy_ones(module):
    """The scheduler asks for the logits only while someone samples;
    the seeded request keeps its stream whoever is resident beside it."""
    _serve_pick.check_a_sampling_request_among_greedy_ones(
        lambda name: _server(module, name="pick" + name),
        [([3, 1, 4], {"max_new_tokens": 9}),
         ([1, 5], {"max_new_tokens": 5, "temperature": 0.8, "seed": 5}),
         ([9, 2, 6, 5], {"max_new_tokens": 8})])


# ------------------------------------ step N+1 dispatched before N's tokens

def _ahead_server(module, name, slots=None):
    kw = {} if slots is None else {"max_sequences": slots}
    return _server(module, name="ahead" + name, **kw)


def test_the_engine_takes_the_last_steps_tokens_on_the_device(module):
    from mxnet_tpu.serve.decode import extract_params
    params = extract_params(module)
    _serve_ahead.check_engine_takes_the_last_steps_tokens(
        _dense_engine(params, 4, "ahserial")[0],
        _dense_engine(params, 4, "ahahead")[0],
        {0: [3, 11, 7], 2: [5, 2], 3: [9]})


def test_the_sharded_cache_takes_the_last_steps_tokens(module4):
    """The same under the tp=4 cache: the first step's stand-in for the
    previous tokens is replicated over the mesh, as every later step's
    tokens are, so no step compiles a program twice (the first two steps
    share a bucket)."""
    from mxnet_tpu._fused import CompileCache
    from mxnet_tpu.parallel import SpecLayout
    from mxnet_tpu.serve.decode import (DecodeEngine, DenseDecoder,
                                        extract_params)
    from mxnet_tpu.serve.kv_cache import KVCache
    lo = SpecLayout(tp=4).sized(8)
    params = extract_params(module4)

    def engine(name, mesh=None):
        family = DenseDecoder(params, HEADS_TP)
        cache = KVCache(family.planes(SEQ, 4, False), max_slots=4,
                        max_seq=SEQ, page=4, name=name, mesh=mesh,
                        layout=lo if mesh is not None else None)
        return DecodeEngine(family, cache, CompileCache(name), name=name)

    _serve_ahead.check_engine_takes_the_last_steps_tokens(
        engine("ahtpserial"), engine("ahtpahead", lo.mesh()),
        {0: [3, 11], 1: [5]})


def test_greedy_tokens_ahead_are_the_serial_paths(module):
    from mxnet_tpu.serve.decode import extract_params
    _serve_ahead.check_ahead_equals_serial(
        _ahead_server(module, "equal"),
        _dense_engine(extract_params(module), 4, "ahequalserial")[0],
        [[3, 1, 4], [1, 5], [9, 2, 6, 5], [7]])


def test_max_new_tokens_and_max_seq_end_without_an_extra_row(module):
    _serve_ahead.check_ends_without_an_extra_row(
        lambda name: _ahead_server(module, name), [2, 7, 1], SEQ)


def test_eos_streams_nothing_past_it_with_a_step_in_flight(module):
    _serve_ahead.check_eos_streams_nothing_past_it(
        lambda name, slots=None: _ahead_server(module, name, slots),
        [7, 3], [4, 4, 1], [6, 2, 9])


def test_a_sampling_sequence_turns_the_steps_serial(module):
    _serve_ahead.check_a_sampler_turns_the_steps_serial(
        lambda name: _ahead_server(module, name), [5, 8], [2, 9, 4])


def test_steps_ahead_are_counted_where_they_are_dispatched(module,
                                                           monkeypatch):
    _serve_ahead.check_the_counter_counts_steps_behind_another(
        lambda name: _ahead_server(module, name),
        [[3, 1, 4], [1, 5], [9, 2]], monkeypatch)


def test_cancel_drills_and_close_leave_nothing_in_flight(module):
    _serve_ahead.check_drills_leave_nothing_in_flight(
        lambda name: _ahead_server(module, name), [[3, 1, 4], [1, 5]])


# ------------------------------------------------ the scheduler's own clock

CLOCK = ("decode_periods", "decode_period_us", "decode_collect_wait_us",
         "decode_dispatch_late", "admit_stalls", "admit_stall_us")
PAUSE = 0.2         # a prefill made this slow stands out of any step


def _clock(srv):
    c = profiler.counters()
    return {k: c.get("%s_%s" % (srv.name, k), 0) for k in CLOCK + (
        "decode_steps_ahead",)}


def _since(srv, before):
    now = _clock(srv)
    return {k: now[k] - before[k] for k in now}


def _warm_server(module, name):
    """A server whose every prompt and decode bucket is compiled: a lone
    prompt of one token decoded to the last position."""
    srv = _ahead_server(module, name)
    srv.submit_generate([1], max_new_tokens=SEQ - 1).result(timeout=600)
    return srv


class _Dispatches:
    """The server's decode dispatches as the test sees them: the reading
    of the host's clock the scheduler took last before each (its own
    start of the dispatch), whether a step was in flight then, and its
    bucket; and what each collect waited, by the dispatch before it."""

    def __init__(self, srv, monkeypatch):
        from mxnet_tpu.serve import server as server_mod
        self.at, self.ahead, self.buckets, self.waits = [], [], [], []
        self.out = 0
        eng, last = srv.engine, [None]
        dispatch, collect = eng.decode_dispatch, eng.decode_collect

        class SchedulerTime:
            def __getattr__(self, name):
                return getattr(time, name)

            def perf_counter(self):
                t = time.perf_counter()
                if threading.current_thread() is srv._worker:
                    last[0] = t
                return t

        def counting_dispatch(*a, **kw):
            self.at.append(last[0])
            self.ahead.append(self.out > 0)
            self.out += 1
            flight = dispatch(*a, **kw)
            self.buckets.append(flight.bucket)
            return flight

        def counting_collect(flight):
            self.out -= 1
            got = collect(flight)
            self.waits.append((len(self.at) - 1, flight.waited))
            return got

        monkeypatch.setattr(server_mod, "time", SchedulerTime())
        monkeypatch.setattr(eng, "decode_dispatch", counting_dispatch)
        monkeypatch.setattr(eng, "decode_collect", counting_collect)

    def periods(self, compiled=()):
        """The gaps between two dispatches whose later one went ahead,
        neither of them at an index in ``compiled``."""
        return [b - a for i, (a, b) in enumerate(zip(self.at, self.at[1:]))
                if self.counted(i, compiled)]

    def counted(self, i, compiled=()):
        """Whether the gap from dispatch ``i`` to the next is a period."""
        return i + 1 < len(self.at) and self.ahead[i + 1] \
            and i not in compiled and i + 1 not in compiled

    def waited(self):
        """What the collects inside the periods waited."""
        return sum(w for i, w in self.waits if self.counted(i))


def _slow_prefill(eng, monkeypatch):
    prefill = eng.prefill

    def slow(*a, **kw):
        time.sleep(PAUSE)
        return prefill(*a, **kw)

    monkeypatch.setattr(eng, "prefill", slow)


def test_the_clock_counts_a_period_for_each_step_dispatched_ahead(
        module, monkeypatch):
    """A period is the gap from one dispatch to the next that goes ahead
    of the step before: one for each step ahead, summed to the
    microsecond, and none across an admission, whose prefill lies in a
    stall instead."""
    srv = _warm_server(module, "clk_periods")
    seen = _Dispatches(srv, monkeypatch)
    _slow_prefill(srv.engine, monkeypatch)
    before = _clock(srv)
    try:
        for h in _serve_ahead.stagger(srv, [[3, 1, 4], [1, 5], [9, 2]],
                                      12):
            h.result(timeout=600)
    finally:
        srv.close()
    got = _since(srv, before)
    want = seen.periods()
    assert got["decode_periods"] == len(want) == got["decode_steps_ahead"]
    assert len(want) >= 10 and max(want) < PAUSE
    # each period rounded to the microsecond, and what each one's collect
    # waited, none where a period holds no collect
    assert abs(got["decode_period_us"] - 1e6 * sum(want)) <= len(want)
    assert abs(got["decode_collect_wait_us"] - 1e6 * seen.waited()) \
        <= len(want)
    assert 0 < got["decode_collect_wait_us"] < got["decode_period_us"]
    assert 0 <= got["decode_dispatch_late"] <= got["decode_periods"]
    # the first prompt found nothing in flight; each later one a step
    assert got["admit_stalls"] <= 2
    assert got["admit_stall_us"] >= got["admit_stalls"] * PAUSE * 1e6


def test_a_compile_breaks_the_chain(module, monkeypatch):
    """A dispatch that builds its program ends no counted period and
    opens none: a fresh server over a lone prompt crosses every decode
    bucket, and the periods counted are the steps ahead less those."""
    srv = _ahead_server(module, "clk_compile")
    seen = _Dispatches(srv, monkeypatch)
    before = _clock(srv)
    try:
        srv.submit_generate([1], max_new_tokens=SEQ - 1).result(timeout=600)
    finally:
        srv.close()
    got = _since(srv, before)
    compiled = {i for i, b in enumerate(seen.buckets)
                if b not in seen.buckets[:i]}
    assert len(compiled) == len(srv.engine.seq_buckets) > 1
    assert got["decode_steps_ahead"] == SEQ - 3
    assert got["decode_periods"] == len(seen.periods(compiled)) \
        < got["decode_steps_ahead"]


def test_a_step_found_done_at_dispatch_is_late(module, monkeypatch):
    """The engine says whether a step in flight is done without waiting
    (a collected one is); the scheduler counts a period late when its
    closing dispatch found the step before done, and asking changes no
    token."""
    from mxnet_tpu.serve.decode import extract_params
    eng = _dense_engine(extract_params(module), 2, "clk_ready")[0]
    slot_tok = eng.prefill(np.asarray([3, 1]), 0)[0]
    flight = eng.decode_dispatch(np.asarray([slot_tok, 0], np.int32),
                                 np.asarray([2, 0], np.int32),
                                 np.asarray([True, False]))
    eng.decode_collect(flight)
    assert eng.decode_ready(flight) and flight.waited >= 0
    prompts, got = [[3, 1, 4], [1, 5], [9, 2, 6, 5]], {}
    for ready in (None, True, False):
        srv = _warm_server(module, "clk_late%s" % ready)
        if ready is not None:
            monkeypatch.setattr(srv.engine, "decode_ready",
                                lambda flight, r=ready: r)
        before = _clock(srv)
        try:
            tokens = [h.result(timeout=600) for h in
                      _serve_ahead.stagger(srv, prompts, 10)]
        finally:
            srv.close()
        got[ready] = (tokens, _since(srv, before))
    assert got[True][0] == got[False][0] == got[None][0]
    late = {r: (c["decode_dispatch_late"], c["decode_periods"])
            for r, (_t, c) in got.items()}
    assert late[True][0] == late[True][1] > 0
    assert late[False][0] == 0 < late[False][1]
    assert 0 <= late[None][0] <= late[None][1]


def test_an_admission_behind_a_step_in_flight_is_one_stall(module,
                                                           monkeypatch):
    """Three prompts that join in one gap drain the step in flight once:
    one stall, from the collect before the first prefill to the next
    dispatch, holding all three prefills. The first prompt, admitted
    into an empty server, stalls nothing."""
    srv = _warm_server(module, "clk_stall")
    eng = srv.engine
    _slow_prefill(eng, monkeypatch)
    hold, held, gate = [False], threading.Event(), threading.Event()
    collect = eng.decode_collect

    def gated(flight):
        if hold[0]:
            hold[0] = False
            held.set()
            gate.wait(60)
        return collect(flight)

    monkeypatch.setattr(eng, "decode_collect", gated)
    before = _clock(srv)
    try:
        first = srv.submit_generate([3, 1, 4], max_new_tokens=12)
        while len(first.tokens_so_far()) < 2:
            time.sleep(0.001)
        hold[0] = True
        assert held.wait(60)
        joins = [srv.submit_generate(p, max_new_tokens=4)
                 for p in ([1, 5], [9, 2], [7])]
        gate.set()
        for h in [first] + joins:
            h.result(timeout=600)
    finally:
        gate.set()
        srv.close()
    got = _since(srv, before)
    assert got["admit_stalls"] == 1
    assert got["admit_stall_us"] >= 3 * PAUSE * 1e6


def test_no_period_is_counted_while_an_xla_profile_runs(module, tmp_path):
    """Nothing is counted while ``jax.profiler`` runs, and the first
    period after it is dropped too; ``xla_profiling`` is true while jax
    holds its profile lock (a profile starting or being written)."""
    import jax
    from jax._src import profiler as jax_profiler
    srv = _warm_server(module, "clk_profile")
    before = _clock(srv)
    try:
        assert not profiler.xla_profiling()
        jax.profiler.start_trace(str(tmp_path))
        try:
            assert profiler.xla_profiling()
            for h in _serve_ahead.stagger(srv, [[3, 1, 4], [1, 5]], 8):
                h.result(timeout=600)
        finally:
            jax.profiler.stop_trace()
        assert not profiler.xla_profiling()
        with jax_profiler._profile_state.lock:
            assert profiler.xla_profiling()
        during = _since(srv, before)
        assert during["decode_steps_ahead"] > 0
        assert all(during[k] == 0 for k in CLOCK)
        lone = []
        for _ in range(2):
            before = _clock(srv)
            srv.submit_generate([2, 7], max_new_tokens=8).result(
                timeout=600)
            lone.append(_since(srv, before))
    finally:
        srv.close()
    assert [c["decode_steps_ahead"] for c in lone] == [6, 6]
    assert [c["decode_periods"] for c in lone] == [5, 6]


# ------------------------------------------------------- scheduler behavior

def test_streaming_iterator_and_callback(module):
    srv = _server(module, name="stream")
    try:
        got = []
        h = srv.submit_generate([2, 4], max_new_tokens=5,
                                on_token=got.append)
        streamed = list(h)
        assert len(streamed) == 5
        assert h.result(timeout=10) == streamed
        assert got == streamed            # callback saw every token
        assert h.done()
    finally:
        srv.close()


def test_eos_stops_generation(module):
    srv = _server(module, name="eos")
    try:
        free = srv.submit_generate([7, 3], max_new_tokens=10)\
            .result(timeout=120)
        eos = free[2]
        toks = srv.submit_generate([7, 3], max_new_tokens=10,
                                   eos_id=eos).result(timeout=120)
        # eos token streamed, then stop: at its FIRST occurrence, which is
        # before index 2 where the model happens to repeat a token (the
        # weights follow numpy's global state, so they vary run to run)
        assert toks == free[:free.index(eos) + 1]
    finally:
        srv.close()


def test_a_request_is_followed_by_its_flow_through_the_scheduler(module):
    """With spans live, a request's queue wait, prefill and eviction
    share its flow id, and the scheduler's spans nest: every decode step
    and every sampling pass inside one iteration (or inside its admission,
    which collects the step in flight before a prefill), a step's dispatch
    and the fetch of the step before it inside the step span, one fetch
    and one sampling pass for every step dispatched."""
    got = []
    profiler.set_span_listener(lambda *a: got.append(a))
    srv = _server(module, name="flowgen", max_sequences=2)
    try:
        handles = [srv.submit_generate([5, 9, 2][:n], max_new_tokens=4)
                   for n in (2, 3, 1)]
        for h in handles:
            assert len(h.result(timeout=120)) == 4
    finally:
        srv.close()
        profiler.set_span_listener(None)
    rec = [r for r in profiler.spans()
           if r.thread.endswith("[flowgen]") or r.name == "gen_queue_wait"]
    by_id = {r.id: r for r in rec}
    by_name = {}
    for r in rec:
        by_name.setdefault(r.name, []).append(r)
    waits = [r for r in by_name["gen_queue_wait"] if r.flow is not None]
    flows = sorted(r.flow for r in waits)[-3:]
    assert len(set(flows)) == 3
    for flow in flows:
        mine = {r.name: r for r in rec if r.flow == flow}
        assert set(mine) == {"gen_queue_wait", "gen_prefill", "gen_evict"}
        assert mine["gen_queue_wait"].parent is None
        # the wait ends where a slot was acquired, before the prefill
        assert mine["gen_queue_wait"].t_end <= mine["gen_prefill"].t_start \
            <= mine["gen_evict"].t_start
        assert by_id[mine["gen_prefill"].parent].name == "gen_admit"
        assert mine["gen_prefill"].attrs["prompt_len"] in (1, 2, 3)
        assert mine["gen_prefill"].attrs["bucket"] >= \
            mine["gen_prefill"].attrs["prompt_len"]
    # the third request waited for a slot: two are resident at most
    assert max(r.t_end - r.t_start for r in waits) > 0
    steps = by_name["gen_decode_step"]
    dispatched = srv.stats()["decode_steps"]
    assert dispatched >= 3 and len(by_name["gen_sample"]) == dispatched
    for r in by_name["gen_admit"]:
        parent = by_id[r.parent]
        assert parent.name == "gen_iteration" and parent.parent is None
    for r in steps + by_name["gen_sample"]:
        parent = by_id[r.parent]
        assert parent.name in ("gen_iteration", "gen_admit")
        assert parent.t_start <= r.t_start and r.t_end <= parent.t_end
    for r in steps:
        assert r.attrs["active"] in (0, 1, 2) and r.attrs["bucket"] > 0
    for name in ("gen_decode_dispatch", "gen_logits_fetch"):
        assert len(by_name[name]) == dispatched
        assert all(by_id[r.parent].name == "gen_decode_step"
                   for r in by_name[name])
    # no step span is empty, and none holds two of either
    inside = {r.id: [] for r in steps}
    for name in ("gen_decode_dispatch", "gen_logits_fetch"):
        for r in by_name[name]:
            inside[r.parent].append(name)
    assert all(held and len(set(held)) == len(held)
               for held in inside.values())
    assert all(by_id[r.parent].name in ("gen_iteration", "gen_admit")
               for r in by_name["gen_evict"])
    # one span a sampling pass, whatever the number of sequences
    assert sum(r.attrs["active"] for r in by_name["gen_sample"]) == 3 * 3
    assert any(a[0] == "gen_iteration" and len(a) == 5 for a in got)


def test_join_mid_flight_and_zero_steady_state_recompiles(module):
    """Requests joining a RUNNING batch don't recompile: after every
    bucket is warm, a second wave of joins + evictions moves the
    compile counter by ZERO while serving real tokens."""
    srv = _server(module, name="joinflight")
    try:
        first = srv.submit_generate([1, 2, 3], max_new_tokens=12)
        while not first.tokens_so_far():
            time.sleep(0.01)
        # join mid-flight, different prompt bucket
        joiners = [srv.submit_generate([5 + i], max_new_tokens=12)
                   for i in range(2)]
        for h in [first] + joiners:
            assert len(h.result(timeout=120)) == 12
        warm_compiles = profiler.get_counter("joinflight_compile")
        assert warm_compiles <= srv.engine.executable_bound()
        # steady state: every bucket warm, so a full second wave is hits
        wave = [srv.submit_generate([i + 1, i + 2], max_new_tokens=9)
                for i in range(4)]
        for h in wave:
            assert len(h.result(timeout=120)) == 9
        assert profiler.get_counter("joinflight_compile") == warm_compiles
        st = srv.stats()
        assert st["compiles"] <= st["executable_bound"]
        assert st["kv"]["slots_in_use"] == 0      # all evicted and freed
        assert st["tokens"] >= 3 * 12 + 4 * 9
        assert st["ttft"] and st["tpot"]          # latency pair populated
    finally:
        srv.close()


def test_deadline_and_queue_full(module):
    srv = _server(module, max_sequences=1, queue_bound=1, name="shed")
    try:
        # soak the single slot so later submits queue
        long_run = srv.submit_generate([1, 2], max_new_tokens=12)
        while srv.stats()["active_sequences"] < 1:
            time.sleep(0.01)
        expired = srv.submit_generate([3], max_new_tokens=2,
                                      timeout=0.0)      # TTFT deadline
        with pytest.raises(QueueFull):
            for _ in range(50):
                srv.submit_generate([4], max_new_tokens=2)
        with pytest.raises(DeadlineExceeded):
            expired.result(timeout=120)
        assert profiler.get_counter("shed_shed") >= 1
        assert profiler.get_counter("shed_deadline_expired") >= 1
        assert len(long_run.result(timeout=120)) == 12
    finally:
        srv.close()


def test_submit_after_close_raises(module):
    srv = _server(module, name="closed")
    srv.close()
    with pytest.raises(ServerClosed):
        srv.submit_generate([1], max_new_tokens=1)


def test_close_drains_waiting_requests(module):
    srv = _server(module, max_sequences=1, queue_bound=8, name="drain")
    handles = [srv.submit_generate([i + 1], max_new_tokens=3)
               for i in range(3)]
    srv.close(drain=True)
    for h in handles:
        assert len(h.result(timeout=10)) == 3


def test_submit_mid_drain_rejected_promptly(module):
    """A submit issued WHILE close(drain=True) is still draining must
    raise ServerClosed immediately — not enqueue behind a scheduler
    that is about to exit (ISSUE 20 satellite)."""
    srv = _server(module, max_sequences=1, queue_bound=8, name="middrain")
    inflight = srv.submit_generate([1, 2], max_new_tokens=10)
    while not inflight.tokens_so_far():
        time.sleep(0.01)
    closer = threading.Thread(target=lambda: srv.close(drain=True))
    closer.start()
    deadline = time.time() + 10
    while not srv._closed and time.time() < deadline:
        time.sleep(0.001)
    assert srv._closed
    t0 = time.time()
    with pytest.raises(ServerClosed):
        srv.submit_generate([9], max_new_tokens=2)
    assert time.time() - t0 < 1.0         # rejected, not queued-then-failed
    # the drain promise still stands for work admitted before the close
    assert len(inflight.result(timeout=120)) == 10
    closer.join(timeout=120)
    assert not closer.is_alive()


def test_second_close_cannot_revoke_drain_promise(module):
    """close() is idempotent the way InferenceServer.close() documents:
    a second close(drain=False) during a first close(drain=True) only
    joins — it must not cancel sequences the first close promised to
    finish."""
    srv = _server(module, max_sequences=1, queue_bound=8, name="reclose")
    slow = srv.submit_generate([3, 5], max_new_tokens=10)
    queued = srv.submit_generate([4], max_new_tokens=3)
    while not slow.tokens_so_far():
        time.sleep(0.01)
    closer = threading.Thread(target=lambda: srv.close(drain=True))
    closer.start()
    while not srv._closed:
        time.sleep(0.001)
    srv.close(drain=False, timeout=120)   # must behave as drain=True
    assert len(slow.result(timeout=120)) == 10
    assert len(queued.result(timeout=120)) == 3
    closer.join(timeout=120)


# --------------------------------------------------------- tp-sharded KV

HEADS_TP = 4


@pytest.fixture(scope="module")
def module4():
    """4-head variant: the tp=4 island needs a head axis it can split
    (2 heads over tp=4 would leave idle shards)."""
    from mxnet_tpu.models import transformer
    net = transformer.get_symbol(vocab_size=VOCAB, num_layers=LAYERS,
                                 d_model=DMODEL, n_heads=HEADS_TP,
                                 seq_len=SEQ)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (1, SEQ))],
             label_shapes=[("softmax_label", (1, SEQ))])
    mx.random.seed(11)
    mod.init_params(mx.init.Uniform(0.05))
    return mod


def test_tp_sharded_kv_decode_parity(module4):
    """GenerativeServer with the KV cache head axis sharded tp=4 over
    the 8-device virtual mesh (``island_specs("serve")``): greedy
    tokens identical to the unsharded server, joins/evictions work, and
    steady-state decode stays at ZERO recompiles (ISSUE 20 satellite)."""
    from mxnet_tpu.parallel import SpecLayout
    lo = SpecLayout(tp=4).sized(8)
    mesh = lo.mesh()
    ref_srv = GenerativeServer(module4, n_heads=HEADS_TP, max_sequences=4,
                               page=4, int8=False, name="tpref")
    try:
        ref = {}
        for p in ([3, 11, 7], [5, 2]):
            ref[tuple(p)] = ref_srv.submit_generate(
                p, max_new_tokens=8).result(timeout=120)
    finally:
        ref_srv.close()
    srv = GenerativeServer(module4, n_heads=HEADS_TP, max_sequences=4,
                           page=4, int8=False, name="tpshard",
                           mesh=mesh, layout=lo)
    try:
        first = srv.submit_generate([3, 11, 7], max_new_tokens=8)
        while not first.tokens_so_far():
            time.sleep(0.01)
        joiner = srv.submit_generate([5, 2], max_new_tokens=8)   # mid-flight
        assert first.result(timeout=240) == ref[(3, 11, 7)]
        assert joiner.result(timeout=240) == ref[(5, 2)]
        warm = profiler.get_counter("tpshard_compile")
        wave = [srv.submit_generate([i + 1, i + 2], max_new_tokens=6)
                for i in range(4)]
        for h in wave:
            assert len(h.result(timeout=240)) == 6
        # every bucket warm: the second wave moved the counter by ZERO
        assert profiler.get_counter("tpshard_compile") == warm
        st = srv.stats()
        assert st["kv"]["slots_in_use"] == 0       # evictions freed pages
    finally:
        srv.close()


def test_tp_sharded_int8_parity(module4):
    """int8 KV under the tp=4 sharding: greedy tokens match the sharded
    f32 server (the quantized page layout shards the same head axis)."""
    from mxnet_tpu.parallel import SpecLayout
    lo = SpecLayout(tp=4).sized(8)
    mesh = lo.mesh()
    out = {}
    for int8 in (False, True):
        srv = GenerativeServer(module4, n_heads=HEADS_TP, max_sequences=4,
                               page=4, int8=int8, mesh=mesh, layout=lo,
                               name="tpq%d" % int8)
        try:
            out[int8] = srv.submit_generate(
                [3, 11, 7], max_new_tokens=8).result(timeout=240)
        finally:
            srv.close()
    assert out[False] == out[True]


def test_capacity_truncation(module):
    """A sequence hitting max_seq finishes (truncated) instead of
    wedging the batch."""
    srv = _server(module, name="trunc")
    try:
        toks = srv.submit_generate([1] * (SEQ - 2), max_new_tokens=50)\
            .result(timeout=120)
        assert 1 <= len(toks) <= SEQ      # bounded by cache capacity
        assert srv.stats()["kv"]["slots_in_use"] == 0
    finally:
        srv.close()


# ------------------------------------------------------------- fault drills

def test_fault_decode_kills_one_sequence_not_batch(module):
    """serve.decode@n kills ONE sequence's future with a legible error;
    co-resident sequences keep decoding to completion."""
    srv = _server(module, name="fdec")
    try:
        # b streaming its first token proves co-residency; steps are
        # ~1ms so the observer can miss a's whole lifetime under GIL
        # scheduling — retry until caught co-resident
        for _ in range(10):
            a = srv.submit_generate([1, 2, 3], max_new_tokens=30)
            while not a.tokens_so_far():
                time.sleep(0.001)
            b = srv.submit_generate([4, 5], max_new_tokens=10)
            while not b.tokens_so_far():
                time.sleep(0.0005)
            if not a.done():
                break
            b.result(timeout=120)      # drain the attempt and retry
        else:
            raise AssertionError("never caught a and b co-resident")
        faults.install("serve.decode@1")
        try:
            # exactly ONE dies (slot reuse is LIFO so which handle holds
            # the victim slot varies); the co-resident completes
            outcomes = []
            for h in (a, b):
                try:
                    outcomes.append(("ok", len(h.result(timeout=120))))
                except ServeError as exc:
                    assert "serve.decode" in str(exc)
                    outcomes.append(("killed", None))
        finally:
            faults.clear()
        assert [k for k, _ in outcomes].count("killed") == 1
        survivor = [n for k, n in outcomes if k == "ok"][0]
        assert survivor in (10, SEQ - 3)  # b's 10, or a truncated
        assert srv.stats()["kv"]["slots_in_use"] == 0
    finally:
        faults.clear()
        srv.close()


def test_fault_evict_fails_handle_but_frees_pages(module):
    """serve.evict@n fails the finishing handle legibly, but the pages
    are STILL freed — an eviction fault must never leak the slot."""
    srv = _server(module, name="fevt")
    try:
        faults.install("serve.evict@1")
        try:
            h = srv.submit_generate([1, 2], max_new_tokens=2)
            with pytest.raises(ServeError, match="serve.evict"):
                h.result(timeout=120)
            assert "pages were still freed" in str(h.exception)
        finally:
            faults.clear()
        st = srv.stats()
        assert st["kv"]["slots_in_use"] == 0      # NO leak
        assert st["kv"]["pages_in_use"] == 0
        # the server still serves after the drill
        assert len(srv.submit_generate([3], max_new_tokens=2)
                   .result(timeout=120)) == 2
    finally:
        faults.clear()
        srv.close()


# ------------------------------------------------------- stats + gate

def test_stats_schema_superset(module):
    """Regression: InferenceServer.stats() keys survive untouched, and
    the generative snapshot carries the documented new keys."""
    srv = _server(module, name="schema")
    try:
        srv.submit_generate([1, 2], max_new_tokens=3).result(timeout=120)
        st = srv.stats()
    finally:
        srv.close()
    for k in ("requests", "compiles", "cache_hits", "shed",
              "deadline_expired"):      # shared with InferenceServer
        assert k in st, k
    for k in ("tokens", "decode_steps", "active_sequences", "waiting",
              "evicted", "executable_bound", "kv", "buckets", "ttft",
              "tpot"):
        assert k in st, k
    for k in ("slots_in_use", "pages_in_use", "occupancy", "max_slots",
              "page", "int8", "hbm_bytes"):
        assert k in st["kv"], k
    assert st["buckets"]["decode"][-1] == SEQ
    for side in ("ttft", "tpot"):
        assert st[side] is not None
        for k in ("p50_ms", "p95_ms", "p99_ms", "window"):
            assert k in st[side], (side, k)


def test_batch_server_stats_schema_unchanged():
    """The pre-existing InferenceServer.stats() schema is pinned — the
    decode work must not have moved it."""
    from mxnet_tpu.gluon import nn
    net = nn.Sequential()
    net.add(nn.Dense(8))
    net.initialize(mx.init.Xavier())
    net(mx.nd.array(np.zeros((1, 4), np.float32)))
    srv = mx.serve.InferenceServer(net, max_batch_size=4, name="pin")
    try:
        srv.submit(np.zeros((4,), np.float32)).result(timeout=120)
        st = srv.stats()
    finally:
        srv.close()
    for k in ("requests", "batches", "avg_batch_rows", "buckets",
              "compiles", "cache_hits"):
        assert k in st, k


def test_zero_cost_import_gate():
    """Importing mxnet_tpu.serve (or mxnet_tpu) must NOT import the
    decode path — kv_cache/decode load lazily on first use."""
    code = (
        "import sys\n"
        "import mxnet_tpu\n"
        "import mxnet_tpu.serve\n"
        "bad = [m for m in sys.modules\n"
        "       if m in ('mxnet_tpu.serve.decode',\n"
        "                'mxnet_tpu.serve.kv_cache')]\n"
        "assert not bad, bad\n"
        "from mxnet_tpu.serve import KVCache\n"
        "assert 'mxnet_tpu.serve.kv_cache' in sys.modules\n"
        "print('GATE-OK')\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=240)
    assert "GATE-OK" in out.stdout, out.stdout + out.stderr
