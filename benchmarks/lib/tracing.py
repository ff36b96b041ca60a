"""Start and stop the profiler around a few seconds of the window."""
import os
import shutil
import time

WINDOW_MARK = "bench.window"


class Tracer:
    """The traced stretch is marked by one annotation held open from
    start to stop, so the reduction finds its bounds on the trace's own
    clock."""

    def __init__(self, logdir):
        self.logdir = logdir
        self._mark = None
        self._t0 = None

    def start(self):
        import jax
        shutil.rmtree(self.logdir, ignore_errors=True)
        os.makedirs(self.logdir, exist_ok=True)
        jax.profiler.start_trace(self.logdir)
        self._mark = jax.profiler.TraceAnnotation(WINDOW_MARK)
        self._mark.__enter__()
        self._t0 = time.perf_counter()
        return "running"

    def stop(self, **info):
        import jax
        t1 = time.perf_counter()
        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()
        return dict(info, logdir=self.logdir, t_start=self._t0, t_stop=t1,
                    stop_cost_s=time.perf_counter() - t1)
