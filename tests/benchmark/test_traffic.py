"""The traffic generators: what repeats from seed to seed and what the
seed sets."""
import json
import os

import numpy as np
import pytest

from benchmarks.generators import open_loop_chat

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "serve_chat_steady.json")) as f:
        return json.load(f)


CFG = {"vocab_size": 50272}
SEEDS = (7, 2 ** 31 + 12345)


def _plans(mix, seconds=51.0):
    return [open_loop_chat.plan(mix, CFG, s, seconds) for s in SEEDS]


def test_same_count_and_same_lengths_for_every_seed(mix):
    a, b = _plans(mix)
    assert len(a["window"]) == len(b["window"]) \
        == open_loop_chat.arrivals_count(mix, 51.0)
    for key, of in (("prompt", len), ("answer", int)):
        la = sorted(of(r[key]) for r in a["window"])
        lb = sorted(of(r[key]) for r in b["window"])
        assert la == lb
    assert [len(r["prompt"]) for r in a["lead_in"]] \
        == [len(r["prompt"]) for r in b["lead_in"]]
    assert [r["answer"] for r in a["lead_in"]] \
        == [r["answer"] for r in b["lead_in"]]


def test_the_seed_sets_order_pairing_and_ids(mix):
    """Without ``schedule_seed`` the run's seed draws the schedule too."""
    mix = {k: v for k, v in mix.items() if k != "schedule_seed"}
    a, b = _plans(mix)
    assert [len(r["prompt"]) for r in a["window"]] \
        != [len(r["prompt"]) for r in b["window"]]
    assert [(len(r["prompt"]), r["answer"]) for r in a["window"]] \
        != [(len(r["prompt"]), r["answer"]) for r in b["window"]]
    again = open_loop_chat.plan(mix, CFG, SEEDS[0], 51.0)
    for r, s in zip(a["window"], again["window"]):
        assert r["due"] == s["due"] and r["answer"] == s["answer"]
        assert np.array_equal(r["prompt"], s["prompt"])


def test_a_schedule_seed_fixes_the_schedule_and_the_seed_draws_the_ids(mix):
    """With ``schedule_seed`` every run's seed offers the same arrivals
    with the same sizes in the same order; the ids are the seed's."""
    fixed = dict(mix, schedule_seed=2 ** 31 + 77)
    a, b = [open_loop_chat.plan(fixed, CFG, s, 51.0) for s in SEEDS]
    for key in ("window", "lead_in"):
        assert [(r.get("due"), len(r["prompt"]), r["answer"]) for r in a[key]] \
            == [(r.get("due"), len(r["prompt"]), r["answer"]) for r in b[key]]
        assert not any(np.array_equal(r["prompt"], s["prompt"])
                       for r, s in zip(a[key], b[key]))
    out_a, out_b = a["lead_out"], b["lead_out"]
    for _ in range(3):
        r, s = next(out_a), next(out_b)
        assert (r["due"], len(r["prompt"]), r["answer"]) \
            == (s["due"], len(s["prompt"]), s["answer"])
    # another schedule seed is another schedule of the same sizes
    other = open_loop_chat.plan(dict(mix, schedule_seed=2 ** 31 + 78), CFG,
                                SEEDS[0], 51.0)
    assert [r["due"] for r in other["window"]] \
        != [r["due"] for r in a["window"]]
    assert sorted(r["answer"] for r in other["window"]) \
        == sorted(r["answer"] for r in a["window"])


def test_lengths_keep_to_the_mix(mix):
    (a, _b) = _plans(mix)
    prompts = [len(r["prompt"]) for r in a["window"]]
    answers = [r["answer"] for r in a["window"]]
    assert min(prompts) >= mix["prompt"]["min"]
    assert max(prompts) <= mix["prompt"]["max"]
    assert min(answers) >= mix["answer"]["min"]
    assert max(answers) <= mix["answer"]["max"]
    # the median of the stratified quantiles is the mix's median
    assert abs(np.median(prompts) - mix["prompt"]["median"]) <= 8
    assert abs(np.median(answers) - mix["answer"]["median"]) <= 4
    for r in a["window"] + a["lead_in"]:
        assert r["prompt"].dtype == np.int32
        assert 0 <= r["prompt"].min() and r["prompt"].max() < 50272


@pytest.mark.parametrize("seconds", [10.0, 51.0])
def test_arrivals_fill_the_window(mix, seconds):
    for plan in _plans(mix, seconds):
        due = [r["due"] for r in plan["window"]]
        n = len(due)
        assert due[0] == 0.0 and due == sorted(due)
        # one mean gap is left after the last arrival
        assert due[-1] == pytest.approx(seconds * (n - 1) / n)
        gaps = np.diff(due)
        # exponential gaps keep their burstiness: the spread of a gap is
        # about its mean
        assert 0.5 < gaps.std() / gaps.mean() < 1.6


def test_due_times_are_fixed_before_the_server_is_met(mix):
    """A plan is a pure function of the mix, the seed and the window:
    nothing the server does can move a due time."""
    a = open_loop_chat.plan(mix, CFG, 5, 20.0)
    b = open_loop_chat.plan(json.loads(json.dumps(mix)), dict(CFG), 5, 20.0)
    assert [r["due"] for r in a["window"]] == [r["due"] for r in b["window"]]
    out = a["lead_out"]
    first = [next(out) for _ in range(3)]
    assert all(r["due"] > 20.0 for r in first)
    assert [r["due"] for r in first] == sorted(r["due"] for r in first)


def test_lead_in_holds_what_a_steady_server_would(mix):
    a, _b = _plans(mix)
    k = mix["lead_in"]["requests"]
    assert len(a["lead_in"]) == k <= mix["max_sequences"]
    left = [r["answer"] for r in a["lead_in"]]
    assert min(left) >= 1 and max(left) <= mix["answer"]["max"]
    # requests caught in flight are at every stage of their answers
    assert len(set(left)) > k // 2


def test_packed_tokens_rows_differ_and_labels_shift():
    import mxnet_tpu as mx
    from benchmarks.generators import packed_tokens
    traffic, cfg = {"seq_len": 16}, {"vocab_size": 97}
    it = packed_tokens.make_iter(mx, traffic, cfg, 2 ** 31 + 3, 4, ROOT)
    again = packed_tokens.make_iter(mx, traffic, cfg, 2 ** 31 + 3, 4, ROOT)
    b = it.next()
    x, y = b.data[0].asnumpy(), b.label[0].asnumpy()
    assert x.shape == (4, 16) and y.shape == (4, 16)
    assert np.array_equal(y, np.roll(x, -1, axis=1))
    assert len({tuple(r) for r in x}) == 4
    assert np.array_equal(again.next().data[0].asnumpy(), x)
    assert not np.array_equal(it.next().data[0].asnumpy(), x)
    assert packed_tokens.units_per_batch(traffic, 4) == 64
