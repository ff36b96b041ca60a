"""The benchmark's own spans, around its calls into each layer.

Kept in memory. When enabled each span is also a
``jax.profiler.TraceAnnotation``, so it lands in the profiler's trace on
the device's clock and idle gaps can be named by it; the program's own
spans (``mx.profiler.span``) are collected through its span listener."""
import contextlib
import threading
import time


class SpanLog:
    def __init__(self, enabled):
        self.enabled = bool(enabled)
        self.rows = []          # (name, t0, t1, thread name)
        self._lock = threading.Lock()
        self._profiler = None

    @contextlib.contextmanager
    def span(self, name):
        if not self.enabled:
            yield
            return
        import jax
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self.add(name, t0, time.perf_counter())

    def add(self, name, t0, t1, thread=None):
        with self._lock:
            self.rows.append((name, t0, t1,
                              thread or threading.current_thread().name))

    def listen_to(self, profiler):
        """Collect the program's span closes (``None`` stops)."""
        if profiler is None:
            if self._profiler is not None:
                self._profiler.set_span_listener(None)
            self._profiler = None
            return
        if not self.enabled:
            return
        self._profiler = profiler

        def on_close(name, t0, t1, category, lane):
            self.add("mx." + name, t0, t1, lane)
        profiler.set_span_listener(on_close)

    def total(self, name, lo=None, hi=None):
        """Seconds inside spans of that name, clipped to [lo, hi]."""
        out = 0.0
        for n, t0, t1, _th in list(self.rows):
            if n != name:
                continue
            a = t0 if lo is None else max(t0, lo)
            b = t1 if hi is None else min(t1, hi)
            if b > a:
                out += b - a
        return out

    def named(self, name):
        return [r for r in list(self.rows) if r[0] == name]
