"""Cut a short stretch out of a profiler trace and keep it as JSON, small
enough to live in the repository: the recorded trace that the tests of
the trace reduction run on.

    python benchmarks/tools/cut_trace.py --logdir .bench_trace/<cell>
        --after 1.0 --seconds 0.4 --out benchmarks/data/<name>.trace.json.gz

Kept: every device operation and program execution that lies wholly in
the stretch, the benchmark's own annotations in it, and the host events
longer than a millisecond that overlap it (they name the idle gaps). The
stretch itself becomes the one ``bench.window`` annotation.
"""
import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--logdir", required=True)
    ap.add_argument("--after", type=float, default=1.0)
    ap.add_argument("--seconds", type=float, default=0.4)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    from benchmarks.lib import trace
    from benchmarks.lib.tracing import WINDOW_MARK

    events = trace.load_xplane(args.logdir)
    whole = trace.Reduced.marked(events)
    lo = whole.window[0] + args.after
    hi = lo + args.seconds
    kept = [trace.Event("/host:CPU", "bench", WINDOW_MARK, 0.0, hi - lo)]
    for e in events:
        device = e.plane.startswith("/device:")
        if device and e.line in (trace.OPS_LINE, trace.MODULES_LINE):
            if e.start < lo or e.end > hi:
                continue
        elif device:
            continue
        elif e.name == WINDOW_MARK or e.end <= lo or e.start >= hi:
            continue
        elif not e.name.startswith("bench.") and e.dur < 1e-3:
            continue
        kept.append(trace.Event(e.plane, e.line, e.name[:96], e.start - lo,
                                e.dur))
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    trace.dump_json(kept, args.out)
    print("kept %d of %d events, %d bytes"
          % (len(kept), len(events), os.path.getsize(args.out)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
