"""Run one cell of the benchmark once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's data files, hands them to the driver its traffic file
names, and prints one JSON object as the last line of standard output.
Names no cell, configuration or metric: those are files.
"""
import argparse
import json
import os
import sys
import time

_T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _log(msg):
    print("bench[%7.2fs] %s" % (time.perf_counter() - _T_START, msg),
          file=sys.stderr, flush=True)


class Run:
    """What a driver gets, and what the readers read."""

    def __init__(self, cell, args, tracer, peaks=None):
        self.cell = cell
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearse = args.rehearse
        self.tracer = tracer
        self.peaks = peaks      # of the device's kind; None in a rehearsal
        self.log = _log
        self.result = None      # the driver's dict
        self.reduced = None     # lib.trace.Reduced of a traced run


def prepare_environment(args):
    """Before JAX is imported: where the compile cache lives, and for a
    rehearsal the CPU with four virtual devices."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4").strip()
        os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")


def _metrics(run, entries):
    from benchmarks.lib import spec
    out = {}
    for entry in entries:
        doc = run.cell.metric_file(entry["name"])
        reader = spec.load_module("readers", doc["reader"])
        value = reader.read(run, doc.get("params", {}))
        if value is None:
            _log("metric %s: nothing to read" % entry["name"])
            continue
        out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes on the CPU; prints no device metric")
    args = ap.parse_args(argv)
    prepare_environment(args)

    from benchmarks.lib import device, spec, trace as trace_lib
    from benchmarks.lib.tracing import Tracer
    try:
        cell = spec.Cell(ROOT, args.workload, rehearse=args.rehearse)
        info, peaks = device.describe(cell.chips, ROOT, args.rehearse)
    except (spec.SpecError, device.DeviceError) as e:
        _log("cannot run: %s" % e)
        return 3
    tracer = Tracer(os.path.join(ROOT, ".bench_trace", args.workload))
    run = Run(cell, args, tracer, peaks)
    driver = spec.load_module("drivers", cell.traffic["driver"])
    _log("cell %s on %s x%d, seed %d, %.0f s, trace %d"
         % (cell.name, info["kind"], cell.chips, args.seed, args.seconds,
            args.trace))
    run.result = res = driver.run(run)
    res["setup_seconds"] = res["window"]["t_open"] - _T_START

    info["memory_peak_bytes"] = res["memory_peak_bytes"]
    breakdown = None
    if run.trace and res.get("traced"):
        events = trace_lib.load_xplane(res["traced"]["logdir"])
        run.reduced = red = trace_lib.Reduced.marked(events)
        info["busy_s"] = red.busy_mean_seconds()
        info["window_s"] = red.window_s
        breakdown = {
            "device_ops": [[k, v] for k, v in red.top_ops(10)],
            "idle_gaps": [[k, v] for k, v in red.idle_gaps_by_host(10)]}
    metrics = _metrics(run, cell.per_layer if run.trace
                       else cell.end_to_end)
    numbers = res["compared"]["numbers"]
    line = {"correct": res["failed"] == 0 and all(
                value <= limit for _n, value, limit in numbers),
            "attempted": res["attempted"], "failed": res["failed"],
            # a CPU run gives no device number: keep them off the names
            "metrics": {} if args.rehearse else metrics}
    if args.rehearse:
        line["rehearsal"] = metrics
    line["device"] = info
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in numbers}
    notes = res["compared"].get("notes", {})
    for key, val in sorted(notes.items()):
        _log("compared note %s: %s" % (key, json.dumps(val)[:600]))
    # the notes whole (every leaf's norms), for whoever sets a limit
    os.makedirs(tracer.logdir, exist_ok=True)
    with open(os.path.join(tracer.logdir, "compared.json"), "w") as f:
        json.dump({"seed": args.seed, "notes": notes}, f)
    for n, v, lim in numbers:
        print("compared %s %.6g limit %.6g %s"
              % (n, v, lim, "ok" if v <= lim else "OVER"),
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
