"""The program's own spans, as the per-layer readers take them.

A live ``mx.profiler.span`` is also a ``jax.profiler.TraceAnnotation``
named ``mx.<name>``, so in a traced run it lies in the profile on its
thread's line, beside the device's operations. ``stretch`` nests those
annotations, line by line, by containment and gives each its self time;
the program's in-memory records (``mx.profiler.spans()``, on the host's
``perf_counter``) serve what is no annotation, such as a wait recorded
after the fact. A program without either gives ``None`` everywhere: the
metric is then left out of the line.
"""
from benchmarks.lib import trace

PREFIX = "mx."


class Span:
    __slots__ = ("name", "start", "end", "where", "parent", "children")

    def __init__(self, name, start, end, where):
        self.name, self.start, self.end = name, start, end
        self.where = where              # (plane, line) of the profile
        self.parent = None
        self.children = []

    @property
    def dur(self):
        return self.end - self.start

    def covered(self, names=None):
        """Seconds of this span that its descendants of the given names
        (every child, if none are given) cover."""
        if names is None:
            inside = [(c.start, c.end) for c in self.children]
        else:
            inside, todo = [], list(self.children)
            while todo:
                c = todo.pop()
                if c.name in names:
                    inside.append((c.start, c.end))
                else:
                    todo.extend(c.children)
        return trace.union_seconds(inside)

    @property
    def self_seconds(self):
        return self.dur - self.covered()


def nest(spans):
    """Give every span its parent: the shortest span of the same line
    that contains it. Where a line is known by its name alone (a recorded
    stretch) several threads may share it, so spans are never assumed to
    close in the order they opened."""
    by_line = {}
    for s in spans:
        by_line.setdefault(s.where, []).append(s)
    for group in by_line.values():
        group.sort(key=lambda s: (s.start, -s.end))
        open_ = []
        for s in group:
            open_ = [o for o in open_ if o.end > s.start]
            holders = [o for o in open_ if o.end >= s.end]
            if holders:
                s.parent = min(holders, key=lambda o: o.dur)
                s.parent.children.append(s)
            open_.append(s)
    return spans


def host_rows(logdir):
    """(name, start, end, where) of every ``mx.*`` annotation in the
    newest profile under ``logdir``. ``where`` tells the host's lines
    apart by their place in the plane: every Python thread's line has the
    process's name, so the name alone would lay the threads' spans over
    one another."""
    import glob
    import os
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return []
    rows = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):
            rows.extend((ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9,
                         (plane.name, i))
                        for ev in line.events if ev.name.startswith(PREFIX))
    return rows


def event_rows(events):
    """The same from a list of ``lib.trace`` events (a recorded or a
    hand-made stretch), where a line is known by its name alone."""
    return [(e.name, e.start, e.end, (e.plane, e.line)) for e in events
            if e.name.startswith(PREFIX)
            and not e.plane.startswith("/device:")]


class Stretch:
    """The ``mx.*`` annotations that lie wholly in the traced stretch."""

    def __init__(self, rows, window):
        self.window = lo, hi = window
        self.spans = nest([Span(*row) for row in rows
                           if row[2] > row[1] and row[1] >= lo
                           and row[2] <= hi])

    @property
    def seconds(self):
        return self.window[1] - self.window[0]

    def named(self, name):
        return [s for s in self.spans if s.name == name]

    def intervals(self, names):
        return [(s.start, s.end) for s in self.spans if s.name in names]

    def split(self):
        """{name: (count, total seconds, self seconds)}."""
        out = {}
        for s in self.spans:
            n, total, own = out.get(s.name, (0, 0.0, 0.0))
            out[s.name] = (n + 1, total + s.dur, own + s.self_seconds)
        return out

    def log(self, log):
        log("program spans of the %.3f s stretch (count, total, self):"
            % self.seconds)
        for name, (n, total, own) in sorted(
                self.split().items(), key=lambda kv: -kv[1][2]):
            log("  %-26s %6d %9.4f s %9.4f s" % (name, n, total, own))
        for name in sorted({s.name for s in self.spans if s.children}):
            whole = self.named(name)
            total = sum(s.dur for s in whole)
            log("  children cover %.2f %% of %s"
                % (100.0 * sum(s.covered() for s in whole) / total, name))


def stretch(run):
    """The run's ``Stretch``, made and logged once; ``None`` without a
    trace or where the program put no annotation in it."""
    if not hasattr(run, "program_stretch"):
        found = None
        red = getattr(run, "reduced", None)
        if red is not None and red.window:
            traced = (getattr(run, "result", None) or {}).get("traced")
            rows = host_rows(traced["logdir"]) if traced \
                else event_rows(red.events)
            found = Stretch(rows, red.window)
            if found.spans:
                found.log(run.log)
            else:
                found = None
        run.program_stretch = found
    return run.program_stretch


def records(name):
    """The program's in-memory records of the spans of that name, or
    ``None`` where the program keeps none."""
    try:
        from mxnet_tpu import profiler
    except ImportError:
        return None
    reader = getattr(profiler, "spans", None)
    if reader is None:
        return None
    return [r for r in reader() if r.name == name]
