"""serve.kv_cache — page ledger properties + budget audit (ISSUE 16).

The allocator contract: randomized join/finish interleavings never leak
or double-free pages (the ledger's ``check()`` invariant audit runs
after EVERY step), the occupancy gauges the server exports match the
host-side model exactly, ``max_slots_for`` is the consistent inverse of
``hbm_bytes`` (and int8 roughly doubles the slots a fixed budget
admits), and the hbm-budget audit rejects an over-budget reservation at
server start NAMING it — while ``MXNET_TPU_ANALYZE=off`` keeps the
analysis package unimported (the zero-cost gate).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config as cfg
from mxnet_tpu import profiler
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serve.kv_cache import (KVCache, PageLedger, dense_planes,
                                      max_slots_for)


def _dense_cache(num_layers, n_heads, d_head, max_slots, max_seq, page,
                 int8=False, name="serve"):
    """A cache of the dense decoder's planes."""
    return KVCache(dense_planes(num_layers, n_heads, d_head, max_seq, page,
                                int8),
                   max_slots=max_slots, max_seq=max_seq, page=page,
                   name=name)


# ------------------------------------------------------------ ledger unit

def test_ledger_basic_lifecycle():
    led = PageLedger(max_slots=4, max_seq=16, page=4)
    assert led.total_pages == 16
    s = led.acquire(5)
    assert s is not None
    assert led.slots_in_use == 1
    assert led.pages_in_use == 2          # ceil(5/4)
    assert led.length(s) == 5
    for _ in range(3):
        led.grow(s)
    assert led.pages_in_use == 2          # 8 tokens still 2 pages
    led.grow(s)
    assert led.pages_in_use == 3          # 9th token opens page 3
    assert led.release(s) == 3
    assert led.slots_in_use == 0 and led.pages_in_use == 0
    led.check()


def test_ledger_double_free_raises():
    led = PageLedger(max_slots=2, max_seq=8, page=4)
    s = led.acquire(3)
    led.release(s)
    with pytest.raises(MXNetError, match="double-free"):
        led.release(s)


def test_ledger_bounds():
    led = PageLedger(max_slots=1, max_seq=8, page=4)
    with pytest.raises(ValueError):
        led.acquire(0)
    with pytest.raises(ValueError):
        led.acquire(9)
    s = led.acquire(8)
    assert led.acquire(1) is None         # full -> None, not an error
    with pytest.raises(MXNetError, match="max_seq"):
        led.grow(s)
    with pytest.raises(MXNetError, match="non-resident"):
        led.grow(s + 1)
    with pytest.raises(ValueError):
        PageLedger(max_slots=2, max_seq=10, page=4)   # 4 does not divide 10


def test_ledger_property_randomized_interleavings():
    """THE allocator property: thousands of random acquire/grow/release
    steps against a parallel host model — the ledger never leaks, never
    double-frees, and its page accounting matches ceil(len/page) exactly
    after every single step."""
    rng = np.random.RandomState(7)
    for trial in range(20):
        max_slots = int(rng.randint(1, 9))
        page = int(rng.choice([2, 4, 8]))
        max_seq = page * int(rng.randint(1, 9))
        led = PageLedger(max_slots, max_seq, page)
        model = {}                        # slot -> length (the oracle)
        for _ in range(200):
            op = rng.randint(3)
            if op == 0:                   # join
                n = int(rng.randint(1, max_seq + 1))
                slot = led.acquire(n)
                if len(model) == max_slots:
                    assert slot is None
                else:
                    assert slot is not None and slot not in model
                    model[slot] = n
            elif op == 1 and model:       # decode one token somewhere
                slot = int(rng.choice(sorted(model)))
                if model[slot] >= max_seq:
                    with pytest.raises(MXNetError):
                        led.grow(slot)
                else:
                    model[slot] += 1
                    assert led.grow(slot) == model[slot]
            elif op == 2 and model:       # finish
                slot = int(rng.choice(sorted(model)))
                expect = -(-model.pop(slot) // page)
                assert led.release(slot) == max(1, expect)
            led.check()
            assert led.slots_in_use == len(model)
            assert led.pages_in_use == sum(
                max(1, -(-n // page)) for n in model.values())
        for slot in sorted(model):
            led.release(slot)
        led.check()
        assert led.pages_in_use == 0


# ------------------------------------------------- cache gauges + geometry

def test_cache_gauges_match_ledger_exactly():
    """The occupancy gauges the server exports ARE the host model —
    asserted equal after every mutation."""
    cache = _dense_cache(num_layers=1, n_heads=2, d_head=4, max_slots=3,
                         max_seq=8, page=4, name="gaugetest")
    rng = np.random.RandomState(3)
    live = []
    for _ in range(60):
        if live and rng.rand() < 0.4:
            cache.release(live.pop(rng.randint(len(live))))
        else:
            s = cache.acquire(int(rng.randint(1, 9)))
            if s is None:
                if live:
                    cache.release(live.pop())
            else:
                live.append(s)
        assert profiler.get_gauge("gaugetest_kv_slots_in_use") == \
            cache.ledger.slots_in_use
        assert profiler.get_gauge("gaugetest_kv_pages_in_use") == \
            cache.ledger.pages_in_use
        assert abs(profiler.get_gauge("gaugetest_kv_occupancy")
                   - cache.ledger.occupancy()) < 1e-12
    for s in live:
        cache.release(s)


def test_max_slots_for_inverts_hbm_bytes():
    """Capacity planning consistency: a cache built with the slots
    max_slots_for admits must fit the budget, and one more slot must
    not."""
    for int8 in (False, True):
        geo = dict(num_layers=2, n_heads=2, d_head=8, max_seq=32, page=8)
        budget = 600_000
        slots = max_slots_for(budget, dense_planes(int8=int8, **geo),
                              geo["max_seq"])
        assert slots >= 1
        cache = _dense_cache(max_slots=slots, int8=int8, name="cap", **geo)
        assert cache.hbm_bytes() <= budget
        bigger = _dense_cache(max_slots=slots + 1, int8=int8, name="cap2",
                              **geo)
        assert bigger.hbm_bytes() > budget


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
def test_cache_is_one_row_a_position(int8):
    """The one layout: K and V are ``(layers, slots, max_seq, heads *
    d_head)`` — a lane-dense row a position, heads side by side — and
    the int8 scale planes stay ``(layers, slots, heads, pages)``; the
    footprint is what the head-major layout reserved."""
    cache = _dense_cache(num_layers=3, n_heads=4, d_head=16, max_slots=5,
                         max_seq=64, page=16, int8=int8, name="rows")
    k, v = cache.plane("k"), cache.plane("v")
    assert k.shape == v.shape == (3, 5, 64, 4 * 16)
    elems = 2 * 3 * 5 * 64 * 4 * 16
    assert cache.int8 is int8
    if int8:
        assert k.dtype == np.int8
        assert cache.plane("k_scale").shape \
            == cache.plane("v_scale").shape == (3, 5, 4, 4)
        assert cache.hbm_bytes() == elems + 2 * 3 * 5 * 4 * 4 * 4
    else:
        assert k.dtype == np.float32
        assert [p.name for p in cache.planes] == ["k", "v"]
        assert cache.hbm_bytes() == elems * 4


def test_int8_doubles_resident_sequences():
    """THE int8 acceptance: same budget, quantized KV admits at least
    2x the resident sequences (int8 payload is 4x smaller; the scale
    planes claw a little back)."""
    geo = dict(num_layers=2, n_heads=4, d_head=16, max_seq=64, page=16)
    budget = 4 * 1024 * 1024
    f32_slots = max_slots_for(budget, dense_planes(int8=False, **geo),
                              geo["max_seq"])
    i8_slots = max_slots_for(budget, dense_planes(int8=True, **geo),
                             geo["max_seq"])
    assert f32_slots >= 1
    assert i8_slots >= 2 * f32_slots


# ------------------------------------------------------------ budget audit

def test_audit_zero_cost_when_analyze_off(monkeypatch):
    import subprocess, sys
    code = (
        "import sys\n"
        "import mxnet_tpu  # noqa: F401\n"
        "from mxnet_tpu.serve.kv_cache import KVCache, dense_planes\n"
        "c = KVCache(dense_planes(1, 2, 4, 8, 4), 2, 8, page=4, name='zc')\n"
        "out = c.audit()\n"
        "assert out['fits'] is True\n"
        "assert not any(m.startswith('mxnet_tpu.analysis')\n"
        "               for m in sys.modules), 'analysis imported'\n"
        "print('ZC-OK')\n")
    env = {"MXNET_TPU_ANALYZE": "off", "JAX_PLATFORMS": "cpu"}
    import os
    full = dict(os.environ); full.update(env)
    out = subprocess.run([sys.executable, "-c", code], env=full,
                         capture_output=True, text=True, timeout=240)
    assert "ZC-OK" in out.stdout, out.stdout + out.stderr


def test_audit_strict_rejects_naming_reservation(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_ANALYZE", "strict")
    monkeypatch.setenv("MXNET_TPU_ANALYZE_HBM_BUDGET", "1K")
    cfg.reset("MXNET_TPU_ANALYZE")
    cfg.reset("MXNET_TPU_ANALYZE_HBM_BUDGET")
    try:
        cache = _dense_cache(num_layers=2, n_heads=2, d_head=8, max_slots=4,
                             max_seq=32, page=8, name="rej")
        with pytest.raises(MXNetError) as err:
            cache.audit()
        msg = str(err.value)
        assert "hbm-budget" in msg
        assert "rej_kv_cache" in msg          # the reservation is NAMED
        assert "MXNET_TPU_SERVE_KV_INT8" in msg   # and the remedy offered
    finally:
        monkeypatch.delenv("MXNET_TPU_ANALYZE")
        monkeypatch.delenv("MXNET_TPU_ANALYZE_HBM_BUDGET")
        cfg.reset("MXNET_TPU_ANALYZE")
        cfg.reset("MXNET_TPU_ANALYZE_HBM_BUDGET")


def test_audit_warn_fits_under_big_budget(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_ANALYZE", "warn")
    monkeypatch.setenv("MXNET_TPU_ANALYZE_HBM_BUDGET", "1G")
    cfg.reset("MXNET_TPU_ANALYZE")
    cfg.reset("MXNET_TPU_ANALYZE_HBM_BUDGET")
    try:
        cache = _dense_cache(num_layers=1, n_heads=2, d_head=4, max_slots=2,
                             max_seq=8, page=4, name="fits")
        out = cache.audit()
        assert out["fits"] is True
        assert out["reserved_bytes"] == cache.hbm_bytes()
    finally:
        monkeypatch.delenv("MXNET_TPU_ANALYZE")
        monkeypatch.delenv("MXNET_TPU_ANALYZE_HBM_BUDGET")
        cfg.reset("MXNET_TPU_ANALYZE")
        cfg.reset("MXNET_TPU_ANALYZE_HBM_BUDGET")


def test_a_plane_a_slot_is_counted_and_does_not_grow_with_max_seq():
    """ISSUE 33: a recurrent state is a plane whose share of a slot has
    its own shape (``tail``) whatever ``max_seq``; ``hbm_bytes``, ``audit``
    and ``max_slots_for`` count it, the ledger pages positions only."""
    from mxnet_tpu.serve.kv_cache import KVCache, Plane, max_slots_for
    planes = [Plane("k", 2, 8, "bfloat16"),
              Plane("kc", 2, 8, "bfloat16", tail=(64 // 4, 8)),
              Plane("state", 3, 0, "float32", tail=(16, 4),
                    kind="slot_state")]
    cache = KVCache(planes, max_slots=5, max_seq=64, page=16, name="kvslot")
    assert [tuple(a.shape) for a in cache.state()] == [
        (2, 5, 64, 8), (2, 5, 16, 8), (3, 5, 16, 4)]
    per_slot = 2 * 64 * 8 * 2 + 2 * 16 * 8 * 2 + 3 * 16 * 4 * 4
    assert cache.hbm_bytes() == 5 * per_slot
    assert cache.audit()["reserved_bytes"] == 5 * per_slot
    assert max_slots_for(5 * per_slot + per_slot - 1, planes, 64) == 5
    assert planes[2].bytes_per_slot(64) == planes[2].bytes_per_slot(4096)
    assert planes[2].describe() == "state 3 layers x 16x4 float32 a slot"
    assert planes[1].describe() == "kc 2 layers x 16x8 bfloat16"
    # the ledger pages positions: a slot's pages follow its length alone
    slot = cache.acquire(17)
    assert cache.ledger.pages_in_use == 2
    cache.release(slot)
    assert float(np.abs(np.asarray(cache.plane("state"))).max()) == 0.0


def test_a_sharded_cache_refuses_a_plane_it_cannot_place():
    import jax
    from jax.sharding import Mesh
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.serve.kv_cache import KVCache, Plane
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(MXNetError, match="no sharded layout places"):
        KVCache([Plane("state", 1, 0, "float32", tail=(8, 4),
                       kind="slot_state")], max_slots=2, max_seq=16, page=4,
                name="kvnoplace", mesh=mesh)
