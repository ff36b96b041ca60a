"""What both serving families owe since the token is chosen on the device
(ISSUE 29), written once: ``test_serve_decode.py`` and
``test_serve_mla_moe.py`` run these over their own engines and servers.
"""
from contextlib import contextmanager

import numpy as np

from mxnet_tpu import profiler


@contextmanager
def device_fetches(monkeypatch):
    """The byte sizes of the device arrays that ``serve/decode.py`` takes
    to the host, in order, while the block runs (every fetch there is an
    ``np.asarray`` of a jax array; host arrays passing through the same
    call are not fetches)."""
    import jax
    from mxnet_tpu.serve import decode
    sizes = []

    class Counting:
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def asarray(a, *args, **kw):
            if isinstance(a, jax.Array):
                sizes.append(a.nbytes)
            return np.asarray(a, *args, **kw)

    with monkeypatch.context() as patch:
        patch.setattr(decode, "np", Counting())
        yield sizes


def check_picked_is_the_logits_argmax(eng, vocab, prompts, steps=6):
    """Prompts into some slots, the others free; over ``steps`` decode
    steps that feed the picked tokens back, with a slot evicted and
    another joining midway: ``picked[slot]`` is ``np.argmax`` of the same
    step's fetched logits for every active slot."""
    slots = eng.cache.max_slots
    tokens = np.zeros(slots, np.int32)
    pos = np.zeros(slots, np.int32)
    active = np.zeros(slots, bool)

    def join(slot, prompt):
        tok, logits = eng.prefill(np.asarray(prompt), slot, logits=True)
        assert isinstance(tok, int) and logits.shape == (vocab,)
        assert tok == int(np.argmax(logits))
        tokens[slot], pos[slot], active[slot] = tok, len(prompt), True

    (late_slot, late), *first = sorted(prompts.items())
    for slot, prompt in first:
        join(slot, prompt)
    assert 0 < active.sum() < slots             # free and resident slots
    for step in range(steps):
        if step == steps // 2:
            gone = int(np.flatnonzero(active)[0])
            tokens[gone], pos[gone], active[gone] = 0, 0, False
            join(late_slot, late)
        picked, logits = eng.decode_step(tokens, pos, active, logits=True)
        assert picked.shape == (slots,) and picked.dtype == np.int32
        assert logits.shape == (slots, vocab)
        assert logits.dtype == np.float32
        assert (picked[active] == np.argmax(logits, -1)[active]).all()
        tokens[active] = picked[active]
        pos[active] += 1


def check_greedy_server_fetches_tokens_only(srv, prompts, monkeypatch):
    """All-greedy traffic: no fetch of the scheduler's is larger than the
    slots' tokens and a family's two counts, and the logits never left
    the device."""
    with device_fetches(monkeypatch) as sizes:
        outs = [h.result(timeout=600) for h in
                [srv.submit_generate(p, max_new_tokens=7) for p in prompts]]
        srv.close()         # the scheduler counts a step after its tokens
    assert all(len(o) == 7 for o in outs)
    st = srv.stats()
    assert st["decode_steps"] >= 6 and st["decode_logits_fetched"] == 0
    # a prefill's token and a step's picked, and nothing else
    assert len(sizes) == len(prompts) + st["decode_steps"]
    assert max(sizes) <= 4 * (srv.cache.max_slots + 2)
    return outs


def check_engine_fetches_logits_when_asked(eng, prompt, monkeypatch):
    """The same at the engine: a step that is not asked for logits returns
    none, fetches at most ``4 * (slots + 2)`` bytes and leaves
    ``<name>_decode_logits_fetched`` alone; one that is asked counts."""
    slots = eng.cache.max_slots
    counter = eng.name + "_decode_logits_fetched"
    with device_fetches(monkeypatch) as sizes:
        tok, none = eng.prefill(np.asarray(prompt), 0)
        assert none is None and sizes == [4]
        tokens = np.zeros(slots, np.int32)
        pos = np.zeros(slots, np.int32)
        active = np.zeros(slots, bool)
        tokens[0], pos[0], active[0] = tok, len(prompt), True
        del sizes[:]
        picked, none = eng.decode_step(tokens, pos, active)
        assert none is None and len(sizes) == 1
        assert sizes[0] <= 4 * (slots + 2)
        assert profiler.get_counter(counter) == 0
        tokens[0], pos[0] = picked[0], pos[0] + 1
        _picked, logits = eng.decode_step(tokens, pos, active, logits=True)
        assert profiler.get_counter(counter) == 1
        assert sizes[-1] == logits.nbytes == slots * logits.shape[1] * 4


def check_a_sampling_request_among_greedy_ones(make_server, requests):
    """``requests``: ``(prompt, kwargs)``, one of them seeded with
    ``temperature > 0`` and the shortest answer. Served together, each
    gets token for token what it gets served alone, the seeded stream
    included; only the steps in which the sampling sequence was resident
    fetched the logits."""
    sampling = [kw for _p, kw in requests if kw.get("temperature", 0) > 0]
    assert len(sampling) == 1
    alone = []
    for i, (prompt, kw) in enumerate(requests):
        srv = make_server("alone%d" % i)
        try:
            alone.append(srv.submit_generate(prompt, **kw)
                         .result(timeout=600))
        finally:
            srv.close()     # the scheduler counts a step after its tokens
        st = srv.stats()
        # a request's first token comes from its prefill
        assert st["decode_steps"] == kw["max_new_tokens"] - 1
        assert st["decode_logits_fetched"] == (
            st["decode_steps"] if kw.get("temperature", 0) > 0 else 0)
    srv = make_server("mixed")
    try:
        together = [h.result(timeout=600) for h in
                    [srv.submit_generate(p, **kw) for p, kw in requests]]
    finally:
        srv.close()
    st = srv.stats()
    assert together == alone
    assert st["decode_logits_fetched"] == sampling[0]["max_new_tokens"] - 1
    assert st["decode_steps"] > st["decode_logits_fetched"]
