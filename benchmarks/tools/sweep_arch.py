"""Find the knee of a cell of ``drivers/serve_arch.py``: the highest rate
it sustains.

    python benchmarks/tools/sweep_arch.py --workload <cell>
        --rates 1.2,1.5,... [--seconds 40] [--seed N]

``tools/sweep.py`` names ``drivers.serve.Session`` and the configuration's
``vocab_size``; this is the same sweep over the other driver's session and
the vocabulary held. Run once, on the chip, when a cell is defined. One
server is kept over all the rates; each rate gets the mix's lead-in (as
many requests as a steady server holds at that rate: the rate times the
mix's ``lead_in.stay_seconds``, no more than the slots), a window and its
follow-up, and one line of JSON: the queue (requests sent and not yet
answered by a first token) at the window's start and end, the tails, the
tokens per second completed and the decode step's period. The knee is the
highest rate at which the queue at the end is no longer than at the
start; the cell then runs at four fifths of it, frozen in its traffic
file.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=20261002)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchmarks import run as harness
    harness.prepare_environment(args)
    from benchmarks.drivers import serve, serve_arch
    from benchmarks.lib import device, spec, stats
    from benchmarks.lib.spans import SpanLog
    from benchmarks.readers import request_tail
    from benchmarks.tools.sweep import _waiting

    cell = spec.Cell(ROOT, args.workload, rehearse=args.rehearse)
    device.describe(cell.chips, ROOT, args.rehearse)
    ctx = harness.Run(cell, argparse.Namespace(
        seed=args.seed, seconds=args.seconds, trace=0,
        rehearse=args.rehearse), None)
    generator = spec.load_module("generators", cell.traffic["kind"])
    cfg = serve_arch.held_vocabulary(cell.config)
    session = serve_arch.Session(ctx, SpanLog(False))
    stay = float(cell.traffic["lead_in"]["stay_seconds"])
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell.traffic["rate_rps"] = rate
        cell.traffic["lead_in"]["requests"] = min(
            int(cell.traffic["max_sequences"]), int(round(rate * stay)))
        plan = generator.plan(cell.traffic, cfg, args.seed + i,
                              args.seconds)
        offered = session.offer(plan, args.seconds)
        recs = serve.records_of(offered, cfg["vocab_size"])
        n = len(recs)
        tokens = sum(1 for r in offered["everything"] for t in r.times
                     if 0 <= t - offered["t_open"] < args.seconds)
        row = {"rate_rps": rate, "requests": n,
               "lead_in": cell.traffic["lead_in"]["requests"],
               "failed": sum(1 for r in recs if not r["ok"]),
               "waiting_at_open": _waiting(offered, 0.0),
               "waiting_at_close": _waiting(offered, args.seconds),
               "waiting_mean_last_fifth": sum(
                   _waiting(offered, args.seconds * (0.8 + 0.02 * k))
                   for k in range(10)) / 10.0,
               "tok_s": tokens / args.seconds,
               "step_period_ms": 1e3 * args.seconds
               / max(offered["decode_steps"], 1)}
        for what in ("ttft", "tpot"):
            vals = request_tail.per_request(recs, what)
            for q in (50, 90):
                v = stats.tail(vals, q, n)
                row["%s_p%d_ms" % (what, q)] = None if v is None else 1e3 * v
        print(json.dumps(row), flush=True)
        # let what is left drain before the next rate
        t_end = time.perf_counter() + 60
        while time.perf_counter() < t_end and any(
                r.handle is not None and not r.handle.done()
                for r in offered["everything"]):
            time.sleep(0.2)
    session.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
