"""Resident sequences over ``max_sequences``: the program's gauge,
sampled once for each decode step of the window, mean over the steps."""


def read(run, params):
    w = run.result["window"]
    if "occupancy" not in w:
        return None
    inside = [a for t, a in w["occupancy"] if 0 <= t < w["seconds"]]
    if not inside:
        return None
    return 100.0 * sum(inside) / len(inside) / w["max_sequences"]
