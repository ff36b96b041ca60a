"""Of the key rows the decode steps read, the share the window layers
read: ``<name>_window_rows_read`` over it and ``<name>_full_rows_read``
together (the program counts both from the positions, summed over
resident slots and layers; counters run from the process's start).
Nothing where the program has no such counters."""


def read(run, params):
    counters = run.result.get("counters") or {}
    name = run.result.get("server_name")
    window = counters.get("%s_%s" % (name, params["window"]))
    full = counters.get("%s_%s" % (name, params["full"]))
    if name is None or window is None or full is None \
            or not window + full:
        return None
    return 100.0 * window / (window + full)
