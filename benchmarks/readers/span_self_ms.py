"""What one span costs beyond the parts named: the mean, over the traced
stretch's whole spans of that name, of the span's duration less the time
its descendants named in ``minus`` cover (``mx.*`` annotations in the
profile, nested by containment). With ``holding``, only spans that hold a
descendant of that name count: a scheduler pass that ran no decode step
is no step."""
from benchmarks.lib import program_spans


def read(run, params):
    found = program_spans.stretch(run)
    if found is None:
        return None
    minus = set(params.get("minus", ()))
    spans = found.named(params["span"])
    if params.get("holding"):
        spans = [s for s in spans if s.covered({params["holding"]}) > 0]
    if not spans:
        return None
    own = [s.dur - s.covered(minus) for s in spans]
    return 1e3 * sum(own) / len(own)
