"""One of the server's counters over another, as a share: ``part`` over
``whole``, both from the process's start. The server is the one the
run's result names (``server_name``), else the one whose counters the run
holds (the only name with a ``<name>_decode_steps`` counter). Nothing
where the program has no such counter."""

_STEPS = "_decode_steps"


def read(run, params):
    counters = run.result.get("counters") or {}
    name = run.result.get("server_name")
    if name is None:
        names = [k[:-len(_STEPS)] for k in counters if k.endswith(_STEPS)]
        if len(names) != 1:
            return None
        name, = names
    part = counters.get("%s_%s" % (name, params["part"]))
    whole = counters.get("%s_%s" % (name, params["whole"]))
    if part is None or not whole:
        return None
    return 100.0 * part / whole
