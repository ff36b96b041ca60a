"""Replay ``GenerativeServer``'s scheduler on a serving cell's own plans,
on the CPU, to see how far the cell's tail moves from seed to seed before
any chip time is spent.

    python benchmarks/tools/replay_scheduler.py --workload <cell> [--seeds 96]

A model, not a measurement: what ``drivers/serve.py::Session.offer`` sends
(lead-in, window, lead-out) goes through ``_admit`` (prompts in chunks
under the prefill budget, back to back) and ``_step`` (one token a
resident sequence) with costs given as arguments,

    step  = --step-ms + --per-sequence-ms * resident
            + --bucket-ms * (the step's bucket / 1024)
    chunk = --chunk-ms + --chunk-per-1024-ms * (chunk's tokens / 1024)

whose defaults are what PR 28 read for ``sarvam105_serve_reason`` on the
v5e (PERF.md section 6). It read that cell's median within 0.5 ms, five
of six seeds within 0.9 ms and both outliers among them as outliers (28.0
and 28.0 where the chip read 28.15 and 30.80); its upper tail is lighter
than the chip's. Prints the median, the deviation from
seed to seed, and how often a set of six, less its farthest run, spreads
by less than --gate. A mix that names its ``schedule_seed`` offers every
seed one plan, so it reads no deviation; take the key out of a copy of
the mix to see what the seed would move.
"""
import argparse
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import spec, stats            # noqa: E402


def _bucket(n, buckets):
    return next((b for b in buckets if n <= b), buckets[-1])


def _chunks(length, chunk):
    """A prompt's chunks, each padded to a power of two from 128."""
    sizes = [b for b in (128, 256, 512, 1024, 2048, 4096) if b <= chunk]
    out = []
    while length > 0:
        out.append(_bucket(min(length, chunk), sizes))
        length -= chunk
    return out


def replay(traffic, plan, seconds, cost):
    """(tpot p90, mean tpot) in ms over the window's requests."""
    lead = float(traffic["lead_in"]["seconds"])
    buckets = list(traffic["seq_buckets"])
    slots = int(traffic["max_sequences"])
    budget_whole = int(traffic.get("prefill_tokens", 10 ** 9))
    chunk = int(traffic["prefill_chunk"])
    arrivals = [(-lead, len(r["prompt"]), r["answer"], False)
                for r in plan["lead_in"]]
    arrivals += [(r["due"], len(r["prompt"]), r["answer"], True)
                 for r in plan["window"]]
    counted = len(plan["window"])
    lead_out = plan["lead_out"]
    t, i, waiting, active, tpots, finished = -lead, 0, [], [], [], 0

    def due_by(now):
        nonlocal i
        while i < len(arrivals) and arrivals[i][0] <= now:
            waiting.append(arrivals[i])
            i += 1
        while i >= len(arrivals):               # the lead-out has no end
            r = next(lead_out)
            arrivals.append((r["due"], len(r["prompt"]), r["answer"], False))
            if arrivals[-1][0] <= now:
                waiting.append(arrivals[-1])
                i += 1
            else:
                break

    def done(seq):
        nonlocal finished
        if seq["counted"]:
            finished += 1
            if seq["n"] > 1:
                tpots.append((seq["last"] - seq["first"]) / (seq["n"] - 1))

    while finished < counted:
        due_by(t)
        if not waiting and not active:
            t = arrivals[i][0]
            continue
        budget = budget_whole
        while waiting and len(active) < slots:
            _due, prompt, answer, is_counted = waiting[0]
            parts = _chunks(prompt, chunk)
            if sum(parts) > budget and budget < budget_whole:
                break
            waiting.pop(0)
            budget -= sum(parts)
            t += sum(cost["chunk_ms"] + cost["chunk_per_1024_ms"] * c / 1024.0
                     for c in parts) / 1e3
            seq = {"pos": prompt, "left": answer - 1, "n": answer,
                   "first": t, "last": t, "counted": is_counted}
            (active.append if seq["left"] > 0 else done)(seq)
            if budget <= 0:
                break
            due_by(t)
        if not active:
            continue
        bucket = _bucket(max(s["pos"] for s in active) + 1, buckets)
        t += (cost["step_ms"] + cost["per_sequence_ms"] * len(active)
              + cost["bucket_ms"] * bucket / 1024.0) / 1e3
        for seq in active:
            seq["pos"] += 1
            seq["left"] -= 1
            seq["last"] = t
        for seq in [s for s in active if s["left"] <= 0]:
            done(seq)
        active = [s for s in active if s["left"] > 0]
    return 1e3 * stats.tail(tpots, 90, counted), 1e3 * statistics.mean(tpots)


def spread_less_farthest(values):
    """A set's spread as the driver reads it: the run farthest from the
    median left out where that narrows it."""
    median = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - median))[:-1]
    return min(stats.spread(values), stats.spread(rest))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=96)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--gate", type=float, default=0.015)
    ap.add_argument("--step-ms", type=float, default=12.6)
    ap.add_argument("--per-sequence-ms", type=float, default=0.184)
    ap.add_argument("--bucket-ms", type=float, default=1.46)
    ap.add_argument("--chunk-ms", type=float, default=15.0)
    ap.add_argument("--chunk-per-1024-ms", type=float, default=66.0)
    args = ap.parse_args(argv)
    cell = spec.Cell(ROOT, args.workload)
    generator = spec.load_module("generators", cell.traffic["kind"])
    cost = {k: getattr(args, k) for k in (
        "step_ms", "per_sequence_ms", "bucket_ms", "chunk_ms",
        "chunk_per_1024_ms")}
    cfg = dict(cell.config, vocab_size=cell.config.get(
        "vocab_held", cell.config["vocab_size"]))
    tails = []
    for k in range(args.seeds):
        plan = generator.plan(cell.traffic, cfg, args.first_seed + 37 * k,
                              args.seconds)
        tails.append(replay(cell.traffic, plan, args.seconds, cost)[0])
    sets = [spread_less_farthest(tails[k:k + 6])
            for k in range(0, len(tails) - 5, 6)]
    print("tpot p90 over %d seeds: median %.2f ms, deviation %.2f %%; %d sets "
          "of six: median spread %.2f %%, %d under %.1f %%"
          % (len(tails), statistics.median(tails),
             100 * statistics.pstdev(tails) / statistics.mean(tails),
             len(sets), 100 * statistics.median(sets),
             sum(1 for s in sets if s < args.gate), 100 * args.gate))
    return 0


if __name__ == "__main__":
    sys.exit(main())
