"""A percentile of the duration of the program's spans of one name that
began in the measured window, from the program's in-memory records
(``mx.profiler.spans()``; both the records and the window are on the
host's ``perf_counter``). The records are there for as long as the
program's spans were live, which in a traced run is at least the traced
stretch."""
from benchmarks.lib import program_spans, stats


def read(run, params):
    rows = program_spans.records(params["span"])
    if rows is None:
        return None
    w = run.result["window"]
    durs = sorted(r.t_end - r.t_start for r in rows
                  if w["t_open"] <= r.t_start <= w["t_close"])
    if not durs:
        return None
    run.log("%s in the window: %d, median %.2f ms, longest %.2f ms"
            % (params["span"], len(durs),
               1e3 * stats.percentile(durs, 50), 1e3 * durs[-1]))
    return 1e3 * stats.percentile(durs, float(params["percentile"]))
