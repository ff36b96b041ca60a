"""A mean over the server's own counters: ``sum``, less ``minus`` where
one is named, over ``count``, times ``scale`` (``GenerativeServer``'s
scheduler clock: microseconds summed over the periods or stalls counted,
read in ms). The server is found as ``server_counter_share`` finds it.

The counters run from the process's start, as ``server.ahead_share``'s
do: they cover the lead-in and the lead-out as well as the window. They
leave out what the scheduler does not count: a period or stall in which a
program compiled (warm-up), and any stretch of an XLA profile (a traced
run's profiled seconds and the first period after them). Nothing where a
counter is missing or the count is 0."""

_STEPS = "_decode_steps"


def read(run, params):
    counters = run.result.get("counters") or {}
    name = run.result.get("server_name")
    if name is None:
        names = [k[:-len(_STEPS)] for k in counters if k.endswith(_STEPS)]
        if len(names) != 1:
            return None
        name, = names
    minus = params.get("minus")
    keys = [params["sum"], params["count"]] + ([minus] if minus else [])
    got = [counters.get("%s_%s" % (name, key)) for key in keys]
    if any(v is None for v in got) or not got[1]:
        return None
    total, count = got[0] - sum(got[2:]), got[1]
    value = float(params["scale"]) * total / count
    run.log("%s: %s%s over %d %s, %.4f" % (
        name, params["sum"], " less " + minus if minus else "", count,
        params["count"], value))
    return value
