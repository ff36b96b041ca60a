"""Share of the traced stretch in which ``fit``'s thread waited for its
next batch: from the end of one step's metric update to the start of the
next step's dispatch (the program's own spans), less the time the
benchmark's batch-end callback took in between."""


def read(run, params):
    traced = run.result.get("traced")
    spans = run.result.get("spans")
    if not traced or spans is None:
        return None
    lo, hi = traced["t_start"], traced["t_stop"]
    ends = sorted(r[2] for r in spans.named(params["after"])
                  if lo <= r[2] <= hi)
    starts = sorted(r[1] for r in spans.named(params["before"])
                    if lo <= r[1] <= hi)
    if not ends or not starts:
        return None
    waited, j = 0.0, 0
    for e in ends:
        while j < len(starts) and starts[j] < e:
            j += 1
        if j == len(starts):
            break
        waited += (starts[j] - e) - spans.total(params["minus"], e,
                                                starts[j])
    return 100.0 * max(waited, 0.0) / (hi - lo)
