"""Share of the traced stretch in which the chip is idle while the
program is inside one of the named spans (``mx.*`` annotations, in the
profile beside the device's operations) or, with ``inside`` false, while
it is in none of them. The two add up to the idle share of the same chip:
the one that idles most, as ``idle_share`` reads it.

The profile's device and host clocks disagree by up to a millisecond,
which matters where the gaps are a few milliseconds long. With ``launch``
(a program and the spans inside which the host launches it) the device's
clock is first set later by the least amount that lets no execution of
the program begin before the span that launched it."""
import bisect

from benchmarks.lib import program_spans, trace


def overlap_seconds(a, b):
    """Seconds that two sorted lists of disjoint intervals share."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def clock_lead(red, found, launch, plane):
    """Seconds by which the device's clock runs ahead of the host's, at
    least: the longest that an execution of ``launch["program"]`` began
    before the nearest start of a span among ``launch["spans"]``, looking
    no further than ``launch["within_ms"]`` for it. 0.0 where none did."""
    starts = sorted(s for s, _e in found.intervals(set(launch["spans"])))
    reach = 1e-3 * float(launch["within_ms"])
    lo, hi = red.window
    lead = 0.0
    for m in red.modules(launch["program"], plane):
        if m.start < lo or m.end > hi:
            continue
        i = bisect.bisect_left(starts, m.start)
        # its launcher opened while it ran, as the profile has it: the
        # next launcher's span opens after this execution has ended
        if i < len(starts) and starts[i] - m.start <= reach \
                and starts[i] < m.end:
            lead = max(lead, starts[i] - m.start)
    return lead


def _idle(run, red, found, launch=None):
    """The idle stretches of the chip that idles most, on the host's
    clock as far as ``launch`` lets it be known; made once a run for each
    program that a metric names as launched."""
    made = run.__dict__.setdefault("idle_stretches", {})
    key = launch["program"] if launch else None
    if key not in made:
        lo, hi = red.window
        plane = min(red.planes, key=red.busy_seconds)
        lead = 0.0
        if launch:
            lead = clock_lead(red, found, launch, plane)
            run.log("device clock set %.3f ms later: the longest that %s "
                    "began before the span that launched it"
                    % (1e3 * lead, key))
        made[key] = trace.gaps([(e.start + lead, e.end + lead)
                                for e in red.ops(plane)], lo, hi)
    return made[key]


def idle_seconds(idle, found, names, window):
    """(idle inside the spans, idle outside them), in seconds."""
    outside = overlap_seconds(
        idle, trace.gaps(found.intervals(names), *window))
    return sum(e - s for s, e in idle) - outside, outside


def idle_by_span(idle, found):
    """{span name: idle seconds of the chip while the span was the
    innermost one open on its line}: each span's own time (its children's
    taken out) laid over the idle stretches."""
    starts = [s for s, _e in idle]
    out = {}
    for sp in found.spans:
        own = trace.gaps([(c.start, c.end) for c in sp.children],
                         sp.start, sp.end)
        total = 0.0
        for a, b in own:
            i = max(bisect.bisect_right(starts, a) - 1, 0)
            while i < len(idle) and idle[i][0] < b:
                total += max(0.0, min(b, idle[i][1]) - max(a, idle[i][0]))
                i += 1
        out[sp.name] = out.get(sp.name, 0.0) + total
    return out


def _log_once(run, idle, found, seconds):
    if getattr(run, "idle_by_span_logged", False):
        return
    run.idle_by_span_logged = True
    run.log("chip idle %.4f s of %.3f s; by the program span open "
            "innermost on its line:" % (sum(e - s for s, e in idle),
                                        seconds))
    for name, secs in sorted(idle_by_span(idle, found).items(),
                             key=lambda kv: -kv[1]):
        if secs > 0:
            run.log("  %-26s %9.4f s %6.2f %%"
                    % (name, secs, 100.0 * secs / seconds))


def read(run, params):
    red = getattr(run, "reduced", None)
    found = program_spans.stretch(run)
    if found is None or not red.planes or not red.window_s:
        return None
    names = set(params["spans"])
    if not found.intervals(names):
        return None
    idle = _idle(run, red, found, params.get("launch"))
    _log_once(run, idle, found, red.window_s)
    inside, outside = idle_seconds(idle, found, names, red.window)
    return 100.0 * (inside if params["inside"] else outside) / red.window_s
