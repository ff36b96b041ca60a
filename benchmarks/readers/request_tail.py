"""A percentile, over every request that arrived in the window, of a time
the client saw (``on_token``'s clock). ``what`` is ``tpot`` -- (last
token - first token) / (tokens - 1) of one request -- or ``ttft`` --
first token minus the time the request was *due*. A request that failed
or never finished counts as slower than any that finished."""
from benchmarks.lib import stats


def per_request(requests, what):
    out = []
    for r in requests:
        if not r["ok"]:
            continue
        t = r["times"]
        if what == "tpot":
            if len(t) > 1:
                out.append((t[-1] - t[0]) / (len(t) - 1))
        elif what == "ttft":
            out.append(t[0] - r["due"])
        else:
            raise ValueError("no request time %r" % what)
    return out


def read(run, params):
    w = run.result["window"]
    if "requests" not in w:
        return None
    values = per_request(w["requests"], params["what"])
    value = stats.tail(values, float(params["percentile"]),
                       len(w["requests"]))
    return None if value is None else 1e3 * value
