"""Open-loop chat traffic that repeats from seed to seed.

Independent users, one turn each. The window holds a fixed number N of
arrivals (the mix's rate times the window's length): N - 1 exponential
gaps drawn from the seed and rescaled to fill the window, so the
burstiness of a Poisson process stays and the offered load does not vary
with the seed. The N prompt lengths and the N answer lengths are the N
stratified quantiles of their clipped log-normal distributions, the same
two multisets for every seed; the seed sets how they pair, their order
and the token ids. A mix that gives ``schedule_seed`` draws the gaps, the
pairing and the order from that number instead: every run then offers one
schedule of arrivals and sizes, and its seed sets the token ids alone.
Where the tail follows how many requests are in flight and how long their
contexts are, another order is other work. A lead-in is sent at once
before the window, its answers as long as what is left of requests caught
in flight, and a lead-out keeps arriving after it until the window's
requests finish; none of those is counted.
"""
import math
from statistics import NormalDist

import numpy as np


def quantile_lengths(dist, n):
    """The n stratified quantiles of a clipped log-normal, as whole
    numbers, ascending."""
    norm = NormalDist()
    out = []
    for i in range(n):
        z = norm.inv_cdf((i + 0.5) / n)
        length = math.exp(math.log(dist["median"]) + dist["sigma"] * z)
        out.append(int(min(max(round(length), dist["min"]), dist["max"])))
    return out


def arrivals_count(traffic, seconds):
    return max(2, int(round(float(traffic["rate_rps"]) * seconds)))


def due_times(rng, n, seconds):
    """n due times in [0, seconds): the first at 0, n - 1 exponential
    gaps rescaled so that one mean gap is left after the last."""
    gaps = rng.exponential(1.0, n - 1)
    gaps *= seconds * (n - 1) / n / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(gaps)])


def lead_in_lengths(traffic, prompts, answers):
    """(prompt length, answer left) of the requests a steady server would
    hold when the window opens: answers picked with chances in
    proportion to their length, each cut to an evenly spread share of
    what it had. The same for every seed."""
    k = int(traffic["lead_in"]["requests"])
    weights = np.cumsum(answers, dtype=np.float64)
    picks = []
    for j in range(k):
        target = (j + 0.5) / k * weights[-1]
        picks.append(answers[int(np.searchsorted(weights, target))])
    # spread the shares so that long and short answers both get some of
    # each: shares in bit-reversed order against ascending lengths
    order = sorted(range(k), key=lambda j: int(format(j, "08b")[::-1], 2))
    left = [max(1, int(math.ceil(picks[j] * (order[j] + 0.5) / k)))
            for j in range(k)]
    step = max(1, len(prompts) // k)
    return [(prompts[(j * step + step // 2) % len(prompts)], left[j])
            for j in range(k)]


def schedule_rng(traffic, rng):
    """What draws the gaps, the pairing and the order: the run's own
    ``rng``, or one from the mix's ``schedule_seed`` (none: the run's)."""
    if traffic.get("schedule_seed") is None:
        return rng
    return np.random.Generator(np.random.PCG64(int(traffic["schedule_seed"])))


def plan(traffic, cfg, seed, seconds):
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    vocab = int(cfg["vocab_size"])
    n = arrivals_count(traffic, seconds)
    prompts = quantile_lengths(traffic["prompt"], n)
    answers = quantile_lengths(traffic["answer"], n)
    order = schedule_rng(traffic, rng)
    due = due_times(order, n, seconds)
    p_order, a_order = order.permutation(n), order.permutation(n)

    def ids(length):
        return rng.integers(0, vocab, int(length)).astype(np.int32)

    window = [{"due": float(due[i]), "prompt": ids(prompts[p_order[i]]),
               "answer": int(answers[a_order[i]])} for i in range(n)]
    lead_in = [{"prompt": ids(p), "answer": int(a)}
               for p, a in lead_in_lengths(traffic, prompts, answers)]

    def lead_out():
        gap = 1.0 / float(traffic["rate_rps"])
        i = 0
        while True:
            yield {"due": seconds + (i + 1) * gap,
                   "prompt": ids(prompts[p_order[i % n]]),
                   "answer": int(answers[a_order[(i + n // 2) % n]])}
            i += 1

    return {"window": window, "lead_in": lead_in, "lead_out": lead_out(),
            "prompt_lengths": prompts, "answer_lengths": answers}
