"""Device time of one execution of a serving program. The server's
programs share one name, so an execution is told by what the client saw
next: the first token of a request follows a prefill, a later token
follows a decode step (``bench.token.first`` / ``bench.token.next``, the
benchmark's annotations in its token callback). Union of device-operation
time inside the execution's span, mean over whole executions traced."""


def seconds(run, params):
    if run.reduced is None:
        return []
    return run.reduced.program_busy_seconds(
        params["program"], followed_by=params["followed_by"],
        marks=params["marks"])


def read(run, params):
    secs = seconds(run, params)
    if not secs:
        return None
    return 1e3 * sum(secs) / len(secs)
