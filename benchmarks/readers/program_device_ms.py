"""Device time of one execution of a compiled program: the union of
device-operation time inside the program's span on the trace's module
line, mean over the whole executions traced."""


def read(run, params):
    if run.reduced is None:
        return None
    secs = run.reduced.program_busy_seconds(params["program"])
    if not secs:
        return None
    return 1e3 * sum(secs) / len(secs)
