"""Process start to window open: loading, warming up, compiling or
reading the compile cache, and the first checked steps."""


def read(run, params):
    return run.result["setup_seconds"]
