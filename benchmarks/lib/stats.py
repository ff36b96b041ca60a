"""Percentiles, rates and spreads, as the benchmark defines them."""
import math
import statistics


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between the
    order statistics; ``None`` for no values."""
    vals = sorted(values)
    if not vals:
        return None
    if len(vals) == 1:
        return float(vals[0])
    rank = (len(vals) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(vals) - 1)
    return float(vals[lo] + (vals[hi] - vals[lo]) * (rank - lo))


def tail(values, q, attempted):
    """The q-th percentile over ``attempted`` requests of which only
    ``values`` finished: a missing one counts as slower than any that
    finished, so it sits at the top of the order. ``None`` when the
    percentile falls among the missing."""
    vals = sorted(values)
    if attempted < len(vals) or attempted < 1:
        raise ValueError("attempted %d < finished %d" % (attempted,
                                                          len(vals)))
    rank = (attempted - 1) * q / 100.0
    if not vals or math.ceil(rank) > len(vals) - 1:
        return None
    lo = math.floor(rank)
    hi = math.ceil(rank)
    return float(vals[lo] + (vals[hi] - vals[lo]) * (rank - lo))


def rate(units, t_open, t_close):
    """All the work over all the time of the window."""
    span = t_close - t_open
    if span <= 0:
        raise ValueError("window of %r seconds" % span)
    return units / span


def spread(values):
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles(values, n=4)``)."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
