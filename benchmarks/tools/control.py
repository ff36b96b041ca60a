"""Read a cell's control and faults: what has to come out as not correct.

    python benchmarks/tools/control.py --workload <cell> --seeds a,b,c
        [--seconds 12] [--rehearse] [--notes <directory>]

The benchmark's own runs never run this. It is run on the chip, at the
cell's own size, when a limit is set (``PERF.md`` keeps the readings),
and at a tiny size by the tests under ``tests/benchmark``. For each seed
it prints one line of JSON: for the control (the reference, or the
program's served tokens judged in the precision below the one the
configuration states) and for each fault the cell can have, every number
compared beside its limit, and whether the case would pass. ``--notes``
keeps each case's notes whole (every leaf's norms) as
``<directory>/<cell>.<seed>.<case>.json``.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--notes")
    args = ap.parse_args(argv)
    from benchmarks import run as harness
    harness.prepare_environment(args)
    from benchmarks.lib import device, spec

    cell = spec.Cell(ROOT, args.workload, rehearse=args.rehearse)
    driver = spec.load_module("drivers", cell.traffic["driver"])
    device.describe(cell.chips, ROOT, args.rehearse)
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = harness.Run(cell, argparse.Namespace(
            seed=seed, seconds=args.seconds, trace=0,
            rehearse=args.rehearse), None)
        cases = {}
        for label, numbers, notes in driver.control(ctx):
            cases[label] = {
                "passes": all(v <= lim for _n, v, lim in numbers),
                "numbers": {n: {"value": v, "limit": lim}
                            for n, v, lim in numbers},
                "read_not_compared": notes.get("read_not_compared", {})}
            if args.notes:
                os.makedirs(args.notes, exist_ok=True)
                with open(os.path.join(args.notes, "%s.%d.%s.json" % (
                        cell.name, seed, label)), "w") as f:
                    json.dump(notes, f)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "cases": cases}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
