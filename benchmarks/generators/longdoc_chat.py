"""Open-loop chat traffic beside long sessions that were there before the
window and are there after it, repeating from seed to seed.

The window is ``open_loop_chat``'s: a fixed number N of arrivals (the
mix's rate times the window's length), exponential gaps from the seed
rescaled to fill the window, prompt and answer lengths the N stratified
quantiles of their clipped log-normal distributions, the same multisets
for every seed. What differs is the lead-in, which is not the window's
mix: first the long sessions, ``long.sessions`` prompts of lengths evenly
spaced from ``long.min`` to ``long.max`` (the same lengths for every seed,
ids from the seed) whose answers of ``long.answer`` tokens last through
the whole window, then as many chat requests as a steady server holds at
the window's rate, their answers cut as ``open_loop_chat`` cuts them. None
of the lead-in is counted in the window's tail; the driver follows the
long sessions for ``correct``.
"""
import numpy as np

from benchmarks.generators import open_loop_chat as chat


def long_lengths(traffic):
    """The long sessions' prompt lengths, ascending: evenly spaced."""
    spec = traffic["long"]
    n, lo, hi = int(spec["sessions"]), int(spec["min"]), int(spec["max"])
    if n == 1:
        return [hi]
    return [lo + int(round(i * (hi - lo) / (n - 1.0))) for i in range(n)]


def plan(traffic, cfg, seed, seconds):
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    vocab = int(cfg["vocab_size"])
    n = chat.arrivals_count(traffic, seconds)
    prompts = chat.quantile_lengths(traffic["prompt"], n)
    answers = chat.quantile_lengths(traffic["answer"], n)
    due = chat.due_times(rng, n, seconds)
    p_order, a_order = rng.permutation(n), rng.permutation(n)

    def ids(length):
        return rng.integers(0, vocab, int(length)).astype(np.int32)

    window = [{"due": float(due[i]), "prompt": ids(prompts[p_order[i]]),
               "answer": int(answers[a_order[i]])} for i in range(n)]
    lead_in = [{"prompt": ids(length),
                "answer": int(traffic["long"]["answer"])}
               for length in long_lengths(traffic)]
    lead_in += [{"prompt": ids(p), "answer": int(a)}
                for p, a in chat.lead_in_lengths(traffic, prompts, answers)]

    def lead_out():
        gap = 1.0 / float(traffic["rate_rps"])
        i = 0
        while True:
            yield {"due": seconds + (i + 1) * gap,
                   "prompt": ids(prompts[p_order[i % n]]),
                   "answer": int(answers[a_order[(i + n // 2) % n]])}
            i += 1

    return {"window": window, "lead_in": lead_in, "lead_out": lead_out(),
            "prompt_lengths": prompts, "answer_lengths": answers,
            "long_lengths": long_lengths(traffic)}
