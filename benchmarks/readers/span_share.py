"""Share of the traced stretch that the program spent inside spans of the
given names (``mx.*`` annotations in the profile; their union, so nested
or repeated names are counted once)."""
from benchmarks.lib import program_spans, trace


def read(run, params):
    found = program_spans.stretch(run)
    if found is None or not found.seconds:
        return None
    inside = found.intervals(set(params["spans"]))
    if not inside:
        return None
    return 100.0 * trace.union_seconds(inside) / found.seconds
