"""Reduce a profiler trace to what the per-layer metrics read.

The profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``load_xplane`` turns it into plain events (plane, line, name, start,
duration in seconds) and everything below works on those, so the same
reduction is tested on a small recorded trace kept as JSON.
"""
import bisect
import glob
import json
import os
import re

# lines of a device plane
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_LOOKBACK = 4000


class Event:
    __slots__ = ("plane", "line", "name", "start", "dur")

    def __init__(self, plane, line, name, start, dur):
        self.plane, self.line, self.name = plane, line, name
        self.start, self.dur = float(start), float(dur)

    @property
    def end(self):
        return self.start + self.dur


def load_xplane(logdir):
    """Every event of every plane of the newest trace under ``logdir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % logdir)
    data = ProfileData.from_file(paths[-1])
    events = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                events.append(Event(plane.name, line.name, ev.name,
                                    ev.start_ns * 1e-9,
                                    ev.duration_ns * 1e-9))
    return events


def _open(path, mode):
    import gzip
    return gzip.open(path, mode + "t") if path.endswith(".gz") \
        else open(path, mode)


def load_json(path):
    with _open(path, "r") as f:
        return [Event(*row) for row in json.load(f)]


def dump_json(events, path):
    with _open(path, "w") as f:
        json.dump([[e.plane, e.line, e.name, e.start, e.dur]
                   for e in events], f)


def device_planes(events):
    """Names of the planes that are accelerator chips, in order."""
    names = {e.plane for e in events if e.plane.startswith("/device:")
             and e.line in (OPS_LINE, MODULES_LINE)}
    return sorted(names)


def union_seconds(intervals):
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(intervals, lo, hi):
    """The idle (start, end) stretches of [lo, hi] that no interval
    covers."""
    out, cur = [], lo
    for s, e in sorted(_clip(intervals, lo, hi)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def op_name(name):
    """A stable short name for a device operation: the HLO instruction's
    name without its numeric suffix (``%fusion.123 = ...`` and
    ``fusion.123`` both give ``fusion``)."""
    name = name.strip().lstrip("%").split(" ", 1)[0].split("=", 1)[0]
    return re.sub(r"[.\d]+$", "", name) or name


class Reduced:
    """What the readers get from one traced window."""

    def __init__(self, events, window=None):
        self.events = events
        self.planes = device_planes(events)
        ops = [e for e in events if e.line == OPS_LINE
               and e.plane in self.planes]
        if window is None and ops:
            window = (min(e.start for e in ops), max(e.end for e in ops))
        self.window = window
        self._ops = ops

    @classmethod
    def marked(cls, events, mark="bench.window"):
        """The stretch that the harness marked with one annotation held
        open from the start of tracing to its stop."""
        marks = [e for e in events if e.name == mark
                 and not e.plane.startswith("/device:")]
        if not marks:
            return cls(events)
        m = max(marks, key=lambda e: e.dur)
        return cls(events, window=(m.start, m.end))

    @property
    def window_s(self):
        return (self.window[1] - self.window[0]) if self.window else 0.0

    def ops(self, plane=None):
        return [e for e in self._ops if plane is None or e.plane == plane]

    def busy_seconds(self, plane):
        lo, hi = self.window
        return union_seconds(_clip([(e.start, e.end)
                                    for e in self.ops(plane)], lo, hi))

    def busy_mean_seconds(self):
        """Busy seconds averaged over the chips used."""
        if not self.planes:
            return 0.0
        return sum(self.busy_seconds(p) for p in self.planes) \
            / len(self.planes)

    def idle_share(self):
        """1 - busy/window on the chip that idles most."""
        if not self.planes or not self.window_s:
            return None
        return max(1.0 - self.busy_seconds(p) / self.window_s
                   for p in self.planes)

    def modules(self, pattern, plane=None):
        """Executions of compiled programs whose name matches."""
        plane = plane or (self.planes[0] if self.planes else None)
        rx = re.compile(pattern)
        return [e for e in self.events if e.line == MODULES_LINE
                and e.plane == plane and rx.search(e.name)]

    def program_busy_seconds(self, pattern, plane=None, followed_by=None,
                             marks=()):
        """Per execution of the matching program: the union of device-op
        time inside its span. Returns the list, whole executions only.
        With ``followed_by``, only executions after whose middle the
        first host annotation among ``marks`` has that name. The middle,
        not the start: the host marks what an execution produced just
        after it ends and sends the next one off within a millisecond,
        and the trace's device and host clocks agree no closer than
        that."""
        plane = plane or (self.planes[0] if self.planes else None)
        ops = sorted(((e.start, e.end) for e in self.ops(plane)))
        starts = [s for s, _e in ops]
        seen = sorted((e.start, e.name) for e in self.events
                      if e.name in marks
                      and not e.plane.startswith("/device:"))
        out = []
        lo, hi = self.window
        for m in self.modules(pattern, plane):
            if m.start < lo or m.end > hi:
                continue
            if followed_by is not None:
                i = bisect.bisect_left(seen, (m.start + m.dur / 2.0, ""))
                if i == len(seen) or seen[i][1] != followed_by:
                    continue
            i = bisect.bisect_left(starts, m.start)
            inside = []
            while i < len(ops) and ops[i][0] < m.end:
                inside.append(ops[i])
                i += 1
            out.append(union_seconds(_clip(inside, m.start, m.end)))
        return out

    def op_seconds(self, pattern, plane=None):
        """Total device time of operations whose full name matches."""
        plane = plane or (self.planes[0] if self.planes else None)
        rx = re.compile(pattern)
        return sum(e.dur for e in self.ops(plane) if rx.search(e.name))

    def op_seconds_inside(self, pattern, program, plane=None):
        """Device time of matching operations that ran inside whole
        executions of the matching program."""
        plane = plane or (self.planes[0] if self.planes else None)
        rx = re.compile(pattern)
        lo, hi = self.window
        spans = sorted((m.start, m.end) for m in self.modules(program, plane)
                       if m.start >= lo and m.end <= hi)
        starts = [s for s, _e in spans]
        total = 0.0
        for e in self.ops(plane):
            if not rx.search(e.name):
                continue
            i = bisect.bisect_right(starts, e.start) - 1
            if i >= 0 and e.end <= spans[i][1]:
                total += e.dur
        return total

    def top_ops(self, n=10, plane=None):
        plane = plane or (self.planes[0] if self.planes else None)
        total = {}
        for e in self.ops(plane):
            key = op_name(e.name)
            total[key] = total.get(key, 0.0) + e.dur
        return sorted(total.items(), key=lambda kv: -kv[1])[:n]

    def idle_gaps_by_host(self, n=10, plane=None, host_lines=None):
        """The idle time of the chip attributed to what the host was
        doing: each gap goes to the innermost host span (shortest of
        those that cover the gap's middle)."""
        plane = plane or (self.planes[0] if self.planes else None)
        lo, hi = self.window
        idle = gaps([(e.start, e.end) for e in self.ops(plane)], lo, hi)
        host = [e for e in self.events
                if not e.plane.startswith("/device:") and e.dur > 0
                and (host_lines is None or e.line in host_lines)]
        host.sort(key=lambda e: e.start)
        starts = [h.start for h in host]
        total = {}
        for s, e in idle:
            mid = (s + e) / 2.0
            best = None
            # spans nest, so the innermost one that covers the middle of
            # the gap started among the last few before it
            i = bisect.bisect_right(starts, mid) - 1
            for h in host[max(0, i - _LOOKBACK):i + 1][::-1]:
                if h.end >= mid and (best is None or h.dur < best.dur):
                    best = h
            key = host_name(best.name) if best is not None else "no_host_span"
            total[key] = total.get(key, 0.0) + (e - s)
        return sorted(total.items(), key=lambda kv: -kv[1])[:n]


def host_name(name):
    """A short name for a host span: ``$file.py:123 func`` becomes
    ``func(file.py)``; annotations keep their own name."""
    m = re.match(r"^\$?(?:.*/)?([\w.]+\.py):\d+\s+(.+)$", name)
    if m:
        return "%s(%s)" % (m.group(2), m.group(1))
    return name[:60]
