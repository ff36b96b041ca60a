"""Percentiles, rates and spreads, and that a stall inside the window
moves the rate and both tails."""
import types

import pytest

from benchmarks.lib import stats
from benchmarks.readers import (generator_lag, request_tail,
                                server_occupancy, server_tok_s, window_rate)


def test_percentile_interpolates_between_order_statistics():
    assert stats.percentile([], 90) is None
    assert stats.percentile([3.0], 90) == 3.0
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3.0
    assert stats.percentile(list(range(11)), 90) == 9.0
    assert stats.percentile([0.0, 10.0], 25) == 2.5


def test_a_missing_request_sits_above_every_finished_one():
    done = [float(i) for i in range(1, 10)]         # nine of ten finished
    assert stats.tail(done, 50, 10) == pytest.approx(5.5)
    assert stats.tail(done, 80, 10) == pytest.approx(8.2)
    # the 90th of ten falls between the ninth and the missing tenth
    assert stats.tail(done, 90, 10) is None
    assert stats.tail(done + [10.0], 90, 10) == pytest.approx(9.1)
    with pytest.raises(ValueError):
        stats.tail(done, 50, 5)


def test_rate_is_all_work_over_all_time():
    assert stats.rate(1000, 2.0, 12.0) == 100.0
    with pytest.raises(ValueError):
        stats.rate(1, 5.0, 5.0)


def test_spread_is_the_interquartile_distance_over_the_median():
    vals = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    assert stats.spread(vals) == pytest.approx((104.25 - 100.75) / 102.5)


def _fit_run(steps, seconds):
    return types.SimpleNamespace(result={"window": {
        "units": steps * 1000, "t_open": 100.0, "t_close": 100.0 + seconds}})


def test_a_stall_lowers_the_fit_rate():
    steady = window_rate.read(_fit_run(100, 50.0), {})
    stalled = window_rate.read(_fit_run(90, 50.0), {})     # 5 s lost
    assert steady == 2000.0 and stalled == 1800.0


def _serve_run(stall):
    """Forty requests, one a second, 120 tokens each at 50 ms; a stall of
    2 s at t = 20 delays every token that was due after it."""
    def shift(t):
        return t + 2.0 if stall and t >= 20.0 else t
    reqs = []
    for i in range(40):
        times = [shift(i + 0.1 + 0.05 * j) for j in range(120)]
        reqs.append({"ok": True, "due": float(i), "lag": 0.001 * i,
                     "times": times, "prompt_len": 8})
    return types.SimpleNamespace(result={"window": {
        "requests": reqs, "seconds": 40.0, "max_sequences": 4,
        "tokens_in_window": sum(1 for r in reqs for t in r["times"]
                                if t < 40.0),
        "occupancy": [(0.5 * k, 2) for k in range(80)]}})


def test_a_stall_moves_both_tails():
    def tails(run):
        return [request_tail.read(run, {"what": w, "percentile": 90})
                for w in ("tpot", "ttft")]
    tpot0, ttft0 = tails(_serve_run(False))
    tpot1, ttft1 = tails(_serve_run(True))
    assert tpot0 == pytest.approx(50.0) and ttft0 == pytest.approx(100.0)
    # five of the forty were in flight at the stall: 2 s over 119 gaps
    assert tpot1 == pytest.approx(50.0 + 2000.0 / 119)
    # every request due after it waited for its first token
    assert ttft1 == pytest.approx(2100.0)
    assert server_tok_s.read(_serve_run(True), {}) \
        < server_tok_s.read(_serve_run(False), {})


def test_a_failed_request_counts_as_missing():
    run = _serve_run(False)
    for r in run.result["window"]["requests"][:5]:
        r["ok"] = False
    # five of forty missing: the 90th falls among them
    assert request_tail.read(run, {"what": "ttft", "percentile": 90}) is None
    assert request_tail.read(run, {"what": "ttft", "percentile": 50}) \
        == pytest.approx(100.0)


def test_server_side_readers():
    run = _serve_run(False)
    assert server_tok_s.read(run, {}) == pytest.approx(
        run.result["window"]["tokens_in_window"] / 40.0)
    assert server_occupancy.read(run, {}) == pytest.approx(50.0)
    assert generator_lag.read(run, {"percentile": 99}) == pytest.approx(
        1e3 * stats.percentile([0.001 * i for i in range(40)], 99))
    empty = types.SimpleNamespace(result={"window": {}})
    for reader in (server_tok_s, server_occupancy, generator_lag):
        assert reader.read(empty, {}) is None
