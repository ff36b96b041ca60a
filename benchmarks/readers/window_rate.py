"""All the work of the window over all its time, closed on a fence."""
from benchmarks.lib import stats


def read(run, params):
    w = run.result["window"]
    if "units" not in w:
        return None
    return stats.rate(w["units"], w["t_open"], w["t_close"])
