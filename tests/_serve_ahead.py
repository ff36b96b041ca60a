"""What every serving family owes since decode step N+1 is dispatched
before step N's tokens reach the host, written once: each family's test
file (``test_serve_decode.py``, ``test_serve_mla_moe.py``,
``test_serve_sparse_linear.py``, ``test_serve_window_moe.py``) runs these
over its own engines and servers.
"""
import time

import numpy as np

from mxnet_tpu import faults
from mxnet_tpu.serve import ServeError


def serial_tokens(eng, prompt, n):
    """``n`` greedy tokens of ``prompt`` by the engine's serial path: a
    prefill into slot 0, then decode steps dispatched and collected one
    at a time, every input token given by the host."""
    slots = eng.cache.max_slots
    tokens = np.zeros(slots, np.int32)
    pos = np.zeros(slots, np.int32)
    active = np.zeros(slots, bool)
    tok, _none = eng.prefill(np.asarray(prompt), 0)
    out = [tok]
    tokens[0], pos[0], active[0] = tok, len(prompt), True
    while len(out) < n:
        picked, _none = eng.decode_step(tokens, pos, active)
        out.append(int(picked[0]))
        tokens[0] = picked[0]
        pos[0] += 1
    return out


def check_engine_takes_the_last_steps_tokens(serial, ahead, prompts,
                                             steps=5):
    """Two engines over the same weights: ``serial`` given every token by
    the host, ``ahead`` given ``-1`` (the previous step's pick, still on
    the device) for every slot but those just prefilled, each step
    dispatched before the one before it is collected. The same tokens,
    step for step; and one compile for each bucket the steps used, the
    first step's stand-in for the previous tokens placed as every later
    step's are."""
    from mxnet_tpu.obs import compiles

    def decode_compiles():
        return sum(1 for r in compiles.snapshot()
                   if r["scope"] == ahead.name
                   and (r["signature"] or "").startswith("('gen_decode'"))

    slots = serial.cache.max_slots
    pos = np.zeros(slots, np.int32)
    active = np.zeros(slots, bool)
    host = np.zeros(slots, np.int32)
    for slot, prompt in prompts.items():
        a = serial.prefill(np.asarray(prompt), slot)[0]
        b = ahead.prefill(np.asarray(prompt), slot)[0]
        assert a == b
        host[slot], pos[slot], active[slot] = a, len(prompt), True
    before = decode_compiles()
    want, got, flights, buckets = [], [], [], set()
    given = host.copy()
    for _step in range(steps):
        picked, _none = serial.decode_step(host, pos, active)
        want.append(picked[active].tolist())
        host[active] = picked[active]
        flights.append(ahead.decode_dispatch(given, pos, active))
        buckets.add(flights[-1].bucket)
        given = np.where(active, -1, 0).astype(np.int32)
        if len(flights) == 2:
            got.append(ahead.decode_collect(flights.pop(0))[0][active]
                       .tolist())
        pos[active] += 1
    got.append(ahead.decode_collect(flights.pop(0))[0][active].tolist())
    assert got == want
    assert decode_compiles() - before == len(buckets)


def stagger(srv, prompts, max_new, **kw):
    """Submit ``prompts`` one after another, each once the one before
    has streamed two tokens, so that each joins a batch with a step in
    flight; the handles."""
    handles = []
    for p in prompts:
        if handles:
            while len(handles[-1].tokens_so_far()) < 2 \
                    and not handles[-1].done():
                time.sleep(0.001)
        handles.append(srv.submit_generate(p, max_new_tokens=max_new, **kw))
    return handles


def check_ahead_equals_serial(srv, eng, prompts, max_new=10):
    """Greedy requests admitted at different times, served with steps
    dispatched ahead, get token for token what the engine's serial path
    gives each alone; steps went ahead, and the server ends with
    nothing in flight and its ledger clean."""
    want = [serial_tokens(eng, p, max_new) for p in prompts]
    try:
        got = [h.result(timeout=600)
               for h in stagger(srv, prompts, max_new)]
    finally:
        srv.close()
    assert got == want
    st = srv.stats()
    assert 0 < st["decode_steps_ahead"] < st["decode_steps"]
    assert srv._inflight is None
    srv.cache.ledger.check()
    assert st["kv"]["slots_in_use"] == 0


def check_ends_without_an_extra_row(make_server, prompt, max_seq):
    """``max_new_tokens`` and ``max_seq`` end a lone sequence with no
    row computed past its last token: one decode step a token after the
    prefill's, every one but the first dispatched ahead."""
    srv = make_server("ends")
    try:
        toks = srv.submit_generate(prompt, max_new_tokens=6).result(
            timeout=600)
        assert len(toks) == 6
        st = srv.stats()
        assert st["decode_steps"] == 5 and st["decode_steps_ahead"] == 4
        # three positions left in the slot: three steps, four tokens
        long = np.resize(np.asarray(prompt), max_seq - 3)
        toks = srv.submit_generate(long, max_new_tokens=50).result(
            timeout=600)
        assert len(toks) == 4
        st = srv.stats()
        assert st["decode_steps"] == 5 + 3
        assert st["decode_steps_ahead"] == 4 + 2
    finally:
        srv.close()
    assert srv._inflight is None
    srv.cache.ledger.check()


def first_repeat_free_eos(tokens, at_least=2):
    """An index ``k >= at_least`` whose token first occurs at ``k``: an
    eos there ends the stream after ``k + 1`` tokens."""
    for k in range(at_least, len(tokens)):
        if tokens.index(tokens[k]) == k:
            return k
    raise AssertionError("no token first seen past %d in %s"
                         % (at_least, tokens))


def room(srv, prompt, want):
    """``want`` new tokens, or as many as the slot holds past ``prompt``."""
    return min(want, srv.cache.max_seq - len(prompt) + 1)


def check_eos_streams_nothing_past_it(make_server, prompt, other, then,
                                      followed=None):
    """An ``eos_id`` met mid-stream beside a longer co-resident sequence,
    in a server of two slots with ``then`` waiting for one: the stream
    ends at the eos, though the step after it was already dispatched, and
    ``then`` takes the freed slot. With ``followed`` (the sparse family's
    record of a sequence's decode steps), the eos's record holds one step
    a token streamed after the prefill's, and ``then``'s starts at its own
    length with no row of the dropped step."""
    srv = make_server("eos", slots=2)
    try:
        free = srv.submit_generate(prompt, max_new_tokens=10).result(
            timeout=600)
        k = first_repeat_free_eos(free)
        long = srv.submit_generate(other,
                                   max_new_tokens=room(srv, other, 40))
        _resident(long)
        eos = srv.submit_generate(prompt, max_new_tokens=10,
                                  eos_id=free[k])
        nxt = srv.submit_generate(then, max_new_tokens=4)
        assert eos.result(timeout=600) == free[:k + 1]
        assert len(nxt.result(timeout=600)) == 4
        long.result(timeout=600)
        if followed is not None:
            assert followed(srv, prompt)["pos"].tolist() == list(
                range(len(prompt), len(prompt) + k))
            assert followed(srv, then)["pos"].tolist() == list(
                range(len(then), len(then) + 3))
    finally:
        srv.close()
    assert srv._inflight is None
    srv.cache.ledger.check()


def check_a_sampler_turns_the_steps_serial(make_server, sampled, greedy):
    """A ``temperature > 0`` sequence resident from the first step beside
    a greedy one: every step is collected as it is dispatched (its next
    token is drawn on the host from that step's logits), so none goes
    ahead; the greedy stream is what it is alone."""
    alone = make_server("greedy_alone")
    try:
        want = alone.submit_generate(greedy, max_new_tokens=6).result(
            timeout=600)
    finally:
        alone.close()
    srv = make_server("sampler")
    try:
        s = srv.submit_generate(sampled, max_new_tokens=12,
                                temperature=0.8, seed=3)
        g = srv.submit_generate(greedy, max_new_tokens=6)
        assert g.result(timeout=600) == want
        assert len(s.result(timeout=600)) == 12
    finally:
        srv.close()
    st = srv.stats()
    assert st["decode_steps_ahead"] == 0
    assert st["decode_logits_fetched"] == st["decode_steps"] == 11


def check_the_counter_counts_steps_behind_another(make_server, prompts,
                                                  monkeypatch):
    """``decode_steps_ahead`` is the number of steps the engine was asked
    to dispatch while another was dispatched and not collected, counted
    here at the engine, over traffic that a sampling request turns serial
    for a while."""
    srv = make_server("count_ahead")
    eng = srv.engine
    seen = {"out": 0, "ahead": 0, "steps": 0}
    dispatch, collect = eng.decode_dispatch, eng.decode_collect

    def counting_dispatch(*a, **kw):
        seen["ahead"] += seen["out"] > 0
        seen["out"] += 1
        seen["steps"] += 1
        return dispatch(*a, **kw)

    def counting_collect(flight):
        seen["out"] -= 1
        return collect(flight)

    monkeypatch.setattr(eng, "decode_dispatch", counting_dispatch)
    monkeypatch.setattr(eng, "decode_collect", counting_collect)
    try:
        handles = stagger(srv, prompts[:2], 9)
        handles.append(srv.submit_generate(prompts[2], max_new_tokens=4,
                                           temperature=0.8, seed=1))
        handles += stagger(srv, prompts[:2], 7)
        for h in handles:
            h.result(timeout=600)
    finally:
        srv.close()
    st = srv.stats()
    assert seen["out"] == 0 and seen["steps"] == st["decode_steps"]
    assert 0 < st["decode_steps_ahead"] == seen["ahead"]
    assert st["decode_logits_fetched"] >= 3


def _resident(handle):
    while len(handle.tokens_so_far()) < 2 and not handle.done():
        time.sleep(0.001)


def check_drills_leave_nothing_in_flight(make_server, prompts):
    """Cancellation, the ``serve.decode`` and ``serve.evict`` fault drills
    and ``close(drain=True / False)`` keep their contracts with steps
    dispatched ahead, and each leaves the ledger clean and nothing in
    flight."""
    def clean(srv):
        assert srv._inflight is None
        srv.cache.ledger.check()
        assert srv.stats()["kv"]["slots_in_use"] == 0

    srv = make_server("drills")
    first, second = prompts[:2]
    try:
        # a cancelled stream stops; its co-resident runs to the end
        a = srv.submit_generate(first, max_new_tokens=room(srv, first, 40))
        _resident(a)
        n = room(srv, second, 8)
        b = srv.submit_generate(second, max_new_tokens=n)
        _resident(b)
        a.cancel()
        assert len(b.result(timeout=600)) == n
        a.result(timeout=600)
        clean(srv)
        # serve.decode kills one sequence, never the batch
        n = room(srv, first, 12), room(srv, second, 12)
        a = srv.submit_generate(first, max_new_tokens=n[0])
        _resident(a)
        b = srv.submit_generate(second, max_new_tokens=n[1])
        _resident(b)
        faults.install("serve.decode@1")
        outcomes = []
        try:
            for h in (a, b):
                try:
                    outcomes.append(len(h.result(timeout=600)))
                except ServeError as exc:
                    assert "serve.decode" in str(exc)
                    outcomes.append(None)
        finally:
            faults.clear()
        assert outcomes.count(None) == 1
        assert [o for o in outcomes if o is not None][0] in n
        clean(srv)
        # serve.evict fails the finishing handle and still frees the slot
        faults.install("serve.evict@1")
        try:
            h = srv.submit_generate(second, max_new_tokens=5)
            try:
                h.result(timeout=600)
                raise AssertionError("the evict drill did not fire")
            except ServeError as exc:
                assert "pages were still freed" in str(exc)
        finally:
            faults.clear()
        clean(srv)
    finally:
        faults.clear()
        srv.close()
    clean(srv)
    # close(drain=True) serves what is resident and waiting to the end;
    # close(drain=False) stops what is resident at its next step
    for drain in (True, False):
        srv = make_server("close%d" % drain)
        n = [room(srv, p, 30) for p in prompts]
        hs = [srv.submit_generate(p, max_new_tokens=m)
              for p, m in zip(prompts, n)]
        _resident(hs[0])
        srv.close(drain=drain, timeout=600)
        assert all(h.done() for h in hs)
        got = [len(h.tokens_so_far()) for h in hs]
        if drain:
            assert got == n
        else:
            assert got != n
        clean(srv)
