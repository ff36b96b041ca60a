"""``mx.profiler`` — execution tracing, counters/gauges/histograms, spans.

Reference: ``python/mxnet/profiler.py`` (profiler_set_config:27,
profiler_set_state:48, dump_profile:64) writing the chrome://tracing JSON
the engine emits in ``src/engine/profiler.cc:127-179``.

Four layers here (docs/architecture/observability.md):

* A framework-level event recorder: while the state is ``run``, every
  imperative op dispatch and every executor graph launch logs a
  chrome-trace complete event (synchronized — the op is blocked on so the
  duration is real device time, the profiler twin of the reference's
  engine sync mode). ``dump_profile()`` writes the standard
  ``{"traceEvents": [...]}`` JSON loadable in chrome://tracing / Perfetto.
* Structured **spans** with stable per-thread **lanes** and chrome-trace
  **flow events**: subsystems wrap their pipeline stages in
  ``span(name, flow=batch_id)`` so one batch's journey (prefetch →
  device-place → fused-step dispatch → metric sync → checkpoint write;
  serve: submit → coalesce → launch) renders as connected slices across
  threads. A span is LIVE while the profiler runs, while the
  ``MXNET_TPU_OBS`` knob is on, while a span listener is installed, or
  while an XLA profile is being taken — otherwise ``span()`` returns a
  shared no-op and allocates nothing (the ``obs_spans`` counter asserts
  that). A live span gets an id, its parent (the innermost span open on
  the same thread), optional small attributes, a record in a bounded
  in-memory ring (:func:`spans`) and a ``jax.profiler.TraceAnnotation``
  named ``mx.<name>``, so that in an XLA profile it sits on its thread's
  line, on the profile's own clock, beside the device's operations.
* The XLA-level profiler: ``start_xla_trace(logdir)`` /
  ``stop_xla_trace()`` wrap ``jax.profiler`` for TensorBoard-grade HLO
  timelines on real hardware.
* Counters/gauges/histograms: always-on, string-keyed, thread-safe —
  used by subsystems to make their hot-path invariants assertable and
  exported in Prometheus text format by :mod:`mxnet_tpu.obs`. The
  checkpoint subsystem's family (docs/architecture/checkpoint.md):
  ``ckpt_block_us`` vs ``ckpt_write_us``, ``ckpt_saved`` / ``ckpt_bytes``
  / ``ckpt_save_async`` / ``ckpt_save_sync``, ``ckpt_backpressure_wait``,
  ``ckpt_write_failed``, ``ckpt_load_ok`` / ``ckpt_load_fallback``,
  ``ckpt_gc_removed``, ``ckpt_sigterm``, and gauges ``ckpt_queue_depth``,
  ``ckpt_last_block_ms``, ``ckpt_last_write_ms``.

Concurrency contract: every mutation of module state (``_state``,
``_filename``, events, counters, gauges, lanes, flow table) happens under
``_lock``. The hot paths read two cached module booleans (``_tracing``,
``_spans_on``) WITHOUT the lock as an early-out — a benign race whose
worst case is one event recorded just after ``set_state("stop")`` or one
skipped just after ``set_state("run")``; the authoritative append is
under the lock, so the event list and the dumped payload are always
internally consistent.
"""
from __future__ import annotations

import bisect
import collections
import itertools
import json
import sys
import threading
import time
from typing import Dict, List, Optional

from . import config as _config

__all__ = [
    "profiler_set_config", "profiler_set_state", "dump_profile",
    "set_config", "set_state", "dump", "pause", "resume",
    "start_xla_trace", "stop_xla_trace", "record_event", "state",
    "incr_counter", "get_counter", "counters", "reset_counters",
    "counter_delta",
    "set_gauge", "get_gauge", "gauges", "reset_gauges",
    "span", "record_span", "spans_enabled", "xla_profiling", "new_flow",
    "spans",
    "SpanRecord",
    "register_thread_lane", "set_span_listener", "blackbox",
    "Histogram", "histogram", "observe", "histograms", "reset_histograms",
]

_lock = threading.Lock()
_state = "stop"
_filename = "profile.json"
_events: List[dict] = []
_counters: dict = {}
_t0 = time.perf_counter()

# bound the in-memory trace: a long obs-on run must not grow without
# limit; overflow is counted so a truncated dump is detectable
_MAX_EVENTS = 1 << 20

# cached fast-path flags (see the concurrency contract above)
_tracing = False
_spans_on = False


def _recompute_enabled_locked() -> None:
    """Refresh the cached fast-path flags; caller holds ``_lock``."""
    global _tracing, _spans_on
    _tracing = _state == "run"
    _spans_on = _tracing or bool(_config.get("MXNET_TPU_OBS"))


def state() -> str:
    return _state


def set_config(filename: str = "profile.json", profile_all: bool = True,
               **_ignored) -> None:
    """(reference: profiler.py:27 profiler_set_config — mode knobs beyond
    the filename collapse: there is no per-subsystem engine here)."""
    global _filename
    with _lock:
        _filename = filename


def set_state(st: str = "stop") -> None:
    """'run' starts recording, 'stop' stops (reference: profiler.py:48)."""
    global _state
    assert st in ("run", "stop"), st
    with _lock:
        _state = st
        _recompute_enabled_locked()


def pause() -> None:
    set_state("stop")


def resume() -> None:
    set_state("run")


# --------------------------------------------------------------- lanes
# A lane is one timeline track in the trace (a chrome ``tid``). Usually a
# lane IS a thread (auto-registered under the thread's name on first
# event), but a pipeline stage that shares a thread may claim its own
# named lane (``span(..., lane="place")``) so its slices render on a
# separate track — the tid is a registered small integer either way,
# replacing the collision-prone ``tid % 100000`` of the original
# recorder. The registry survives ``dump(finished=True)`` so lane ids
# stay stable across dumps within one process.

_lanes: Dict[str, int] = {}            # lane name -> small stable id
_lane_counter = itertools.count(1)
_tls = threading.local()


def _lane_id_locked(name: str) -> int:
    lid = _lanes.get(name)
    if lid is None:
        lid = next(_lane_counter)
        _lanes[name] = lid
    return lid


def register_thread_lane(name: Optional[str] = None) -> int:
    """Name the calling thread's trace lane (defaults to the thread
    name); returns the stable lane id. Subsequent events from this thread
    land on that lane. Re-registering under a new name moves the thread
    to the (possibly fresh) lane."""
    if name is None:
        name = threading.current_thread().name
    with _lock:
        lid = _lane_id_locked(str(name))
    _tls.lane = lid
    return lid


def _current_lane_locked() -> int:
    lid = getattr(_tls, "lane", None)
    if lid is None:
        lid = _lane_id_locked(threading.current_thread().name)
        _tls.lane = lid
    return lid


# --------------------------------------------------------------- flows
# A flow id threads one logical unit of work (a batch, a request) through
# spans on different lanes; the dump carries chrome flow events ("s"
# start / "t" step) that Perfetto renders as arrows between the slices.

_flow_counter = itertools.count(1)
_flows_seen: Dict[int, bool] = {}
_MAX_FLOWS = 8192


def new_flow() -> int:
    """Allocate a process-unique flow id (cheap, lock-free)."""
    return next(_flow_counter)


def _flow_event_locked(fid: int, ts_us: float, lane: int) -> dict:
    if fid in _flows_seen:
        ph = "t"
    else:
        ph = "s"
        if len(_flows_seen) >= _MAX_FLOWS:
            # drop the oldest half: a stale flow re-appearing emits a
            # fresh "s" (one dangling arrow start, not a crash)
            for k in list(_flows_seen)[:_MAX_FLOWS // 2]:
                _flows_seen.pop(k, None)
        _flows_seen[fid] = True
    return {"name": "batch", "cat": "flow", "ph": ph, "id": int(fid),
            "ts": ts_us, "pid": 0, "tid": lane, "bp": "e"}


def record_event(name: str, t_start: float, t_end: float,
                 category: str = "op", flow: Optional[int] = None,
                 lane: Optional[str] = None) -> None:
    """Append one chrome-trace complete event (timestamps from
    time.perf_counter()). Recorded while the profiler state is ``run``
    (op/graph events) — span events come in through :func:`span`, which
    also records under ``MXNET_TPU_OBS``."""
    if not _tracing:
        return
    _append_event(name, t_start, t_end, category, flow, lane)


def _append_event(name, t_start, t_end, category, flow, lane,
                  count_span: bool = False, span_id=None, parent=None,
                  attrs=None) -> None:
    listener = _span_listener
    if listener is not None and count_span:
        # outside _lock: the listener (flight recorder) may snapshot the
        # counter table, which takes this module's lock itself
        try:
            listener(name, t_start, t_end, category, lane)
        except Exception:                                  # noqa: BLE001
            pass
    if count_span:
        if span_id is None:
            span_id = next(_span_ids)
        record = SpanRecord(span_id, parent, name, category, t_start, t_end,
                            flow, lane, threading.current_thread().name,
                            attrs or {})
    with _lock:
        if count_span:
            # the in-memory record is kept for whichever reason the span
            # was live (a listener or an XLA profile alone included)
            if len(_span_ring) == _span_ring.maxlen:
                _counters["profiler_spans_dropped"] = \
                    _counters.get("profiler_spans_dropped", 0) + 1
            _span_ring.append(record)
            _counters["obs_spans"] = _counters.get("obs_spans", 0) + 1
        # authoritative re-check under the lock: a concurrent
        # set_state("stop") + dump() must not observe a half-recorded
        # tail growing behind the serialized payload
        if not (_tracing or (count_span and _spans_on)):
            return
        if len(_events) >= _MAX_EVENTS:
            _counters["profiler_events_dropped"] = \
                _counters.get("profiler_events_dropped", 0) + 1
            return
        lid = _lane_id_locked(lane) if lane is not None \
            else _current_lane_locked()
        ts = (t_start - _t0) * 1e6
        ev = {"name": name, "cat": category, "ph": "X", "ts": ts,
              "dur": (t_end - t_start) * 1e6, "pid": 0, "tid": lid}
        args = dict(attrs) if attrs else {}
        if flow is not None:
            args["flow"] = int(flow)
        if count_span:
            args["id"] = span_id
            if parent is not None:
                args["parent"] = parent
        if args:
            ev["args"] = args
        _events.append(ev)
        if flow is not None:
            _events.append(_flow_event_locked(int(flow), ts, lid))


# --------------------------------------------------------------- spans

# span-close listener (the mx.obs.blackbox flight recorder, or a
# benchmark collecting the program's spans). When set, span() stays LIVE
# even while chrome-trace span recording is off, so the listener sees
# span closes without the chrome-trace list growing (the bounded span
# ring is all that fills); when None (the default) the shared no-op fast
# path is untouched — the zero-cost contract holds.
_span_listener = None


def set_span_listener(fn) -> None:
    """Install (``None`` removes) a callback invoked on every span close
    as ``fn(name, t_start, t_end, category, lane)`` — five positional
    arguments, whatever else a span record carries. Exceptions are
    swallowed — telemetry must never fail the traced code."""
    global _span_listener
    _span_listener = fn


def blackbox():
    """THE flight-recorder gate: the ``mx.obs.blackbox`` module iff
    armed (``MXNET_TPU_OBS_BLACKBOX`` names a directory), else None.
    Every hook site (fit loop, checkpoint writer, pod coordinator,
    fault harness) routes through this one implementation so the
    zero-import discipline — the recorder module never loads when the
    knob is off, subprocess-proven by the CI ``multihost`` gate — is
    maintained in exactly one place. Lives here, next to
    :func:`set_span_listener` (the recorder's other hook), because
    this module is jax-free and already imported by every caller."""
    if not _config.get("MXNET_TPU_OBS_BLACKBOX"):
        return None
    from .obs import blackbox as _bb
    return _bb


# One record of a closed span, as :func:`spans` returns them. ``id`` is
# unique in the process; ``parent`` is the id of the innermost span that
# was open on the same thread when this one opened (``None`` at the top,
# and for an after-the-fact :func:`record_span`, which may have begun
# before whatever is open now); ``flow`` is shared by the spans of one
# request or batch; times are ``time.perf_counter()``.
SpanRecord = collections.namedtuple(
    "SpanRecord", "id parent name category t_start t_end flow lane thread "
                  "attrs")

# bounded like the chrome-trace list: the newest records are kept and
# every record pushed out is counted (``profiler_spans_dropped``)
_MAX_SPANS = 1 << 16
_span_ring: collections.deque = collections.deque(maxlen=_MAX_SPANS)
_span_ids = itertools.count(1)

# jax's own record of a running XLA profile, found once jax's profiler
# module is loaded (never imported from here); False if this jax keeps
# none, and then a profile alone makes no span live
_xla_profile_state = None


def _xla_profiling() -> bool:
    global _xla_profile_state
    st = _xla_profile_state
    if st is None:
        mod = sys.modules.get("jax._src.profiler")
        if mod is None:
            return False
        st = _xla_profile_state = getattr(mod, "_profile_state", False)
    return getattr(st, "profile_session", None) is not None


def xla_profiling() -> bool:
    """Fast, lock-free: True while an XLA profile is starting, running or
    being written (jax holds its profile lock to start and to export one,
    and keeps its session from start to the end of the export). Code that
    times itself on the host's clock leaves such stretches out: the
    profile's own tracers slow the host there."""
    if _xla_profiling():
        return True
    lock = getattr(_xla_profile_state or None, "lock", None)
    return lock is not None and lock.locked()


def spans_enabled() -> bool:
    """Fast, lock-free: True when span() currently records — the
    profiler runs, ``MXNET_TPU_OBS`` is on, a span listener is installed
    or an XLA profile is being taken. Call sites that must compute an
    attribute or allocate a flow id guard it with this."""
    return _spans_on or _span_listener is not None or _xla_profiling()


def spans() -> List[SpanRecord]:
    """The closed spans kept in memory, oldest first (the newest
    ``_MAX_SPANS``; ``profiler_spans_dropped`` counts the rest)."""
    with _lock:
        return list(_span_ring)


class _NoopSpan(object):
    """Shared disabled-mode span: zero allocations per use."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def mark_flow(self, fid):
        pass


_NOOP_SPAN = _NoopSpan()

# jax.profiler.TraceAnnotation, imported on the first live span (this
# module stays importable without jax); False where jax is missing
_trace_annotation = None


def _annotation(name, flow, attrs):
    global _trace_annotation
    cls = _trace_annotation
    if cls is None:
        try:
            from jax.profiler import TraceAnnotation as cls
        except ImportError:
            cls = False
        _trace_annotation = cls
    if cls is False:
        return None
    if flow is not None:
        return cls("mx." + name, flow=int(flow), **attrs)
    return cls("mx." + name, **attrs)


class _Span(object):
    __slots__ = ("name", "category", "flow", "lane", "attrs", "id",
                 "parent", "_t0", "_ann")

    def __init__(self, name, category, flow, lane, attrs):
        self.name = name
        self.category = category
        self.flow = flow
        self.lane = lane
        self.attrs = attrs
        self.id = next(_span_ids)
        self.parent = None
        self._t0 = 0.0
        self._ann = None

    def __enter__(self):
        stack = getattr(_tls, "open_spans", None)
        if stack is None:
            stack = _tls.open_spans = []
        self.parent = stack[-1] if stack else None
        stack.append(self.id)
        self._ann = ann = _annotation(self.name, self.flow, self.attrs)
        if ann is not None:
            ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        stack = _tls.open_spans
        if stack and stack[-1] == self.id:
            stack.pop()
        elif self.id in stack:       # closed out of order: never leave
            stack.remove(self.id)    # a dead id to adopt later spans
        _append_event(self.name, self._t0, t1, self.category, self.flow,
                      self.lane, count_span=True, span_id=self.id,
                      parent=self.parent, attrs=self.attrs)
        return False

    def mark_flow(self, fid) -> None:
        """Emit an extra flow step bound to this span's lane at the
        current time (serve: one batch slice carries many request
        flows)."""
        if fid is None:
            return
        now = time.perf_counter()
        with _lock:
            if not _spans_on or len(_events) >= _MAX_EVENTS:
                return
            lid = _lane_id_locked(self.lane) if self.lane is not None \
                else _current_lane_locked()
            _events.append(_flow_event_locked(int(fid),
                                              (now - _t0) * 1e6, lid))


def record_span(name: str, t_start: float, t_end: float,
                category: str = "span", flow: Optional[int] = None,
                lane: Optional[str] = None, **attrs) -> None:
    """Low-level span record for sites that time conditionally or after
    the fact (the serve coalescer, which only emits when a batch actually
    formed; a request's wait in the queue). Same gating as :func:`span`;
    times are ``time.perf_counter()``; no parent and no annotation in an
    XLA profile."""
    if not spans_enabled():
        return
    _append_event(name, t_start, t_end, category, flow, lane,
                  count_span=True, attrs=attrs)


def span(name: str, category: str = "span", flow: Optional[int] = None,
         lane: Optional[str] = None, **attrs):
    """Context manager timing one pipeline stage into the trace.

    ``flow`` links this slice to the other slices of the same batch or
    request across lanes; ``lane`` overrides the thread's lane with a
    named track; ``attrs`` are a few small values (a bucket, a count)
    kept with the record. No-op (shared singleton, zero allocations)
    unless :func:`spans_enabled`: pass attributes the caller already
    holds, or guard their computation with :func:`spans_enabled`.
    """
    if not spans_enabled():
        return _NOOP_SPAN
    return _Span(name, category, flow, lane, attrs)


# ------------------------------------------------------------- counters
# Always-on framework counters (compile-cache hits/misses and friends —
# the TPU twin of the reference engine's aggregate stats). Unlike trace
# events these are cheap enough to count unconditionally, so tests can
# assert e.g. "one compiled executable per trainer step after warmup"
# without enabling tracing.


def incr_counter(name: str, n: int = 1) -> None:
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def get_counter(name: str) -> int:
    with _lock:
        return _counters.get(name, 0)


def counters() -> dict:
    """Snapshot of all counters."""
    with _lock:
        return dict(_counters)


def reset_counters() -> None:
    with _lock:
        _counters.clear()


class counter_delta(object):
    """Context manager snapshotting the counter table so tests and benches
    can assert on the increments one region produced (``with
    counter_delta() as d: ...; d.get("loop_host_sync")``) without clearing
    the global registry under concurrent users."""

    def __enter__(self):
        self._snap = counters()
        return self

    def __exit__(self, *exc):
        return False

    def get(self, name: str) -> int:
        return get_counter(name) - self._snap.get(name, 0)

    def all(self) -> dict:
        now = counters()
        return {k: v - self._snap.get(k, 0) for k, v in now.items()
                if v != self._snap.get(k, 0)}


# -------------------------------------------------------------- gauges
# Point-in-time values (queue depth, batch occupancy, ...) — unlike the
# monotonic counters above these are set, not accumulated. They share the
# counter registry's cheap always-on discipline so serving dashboards and
# tests can read them without enabling tracing.

_gauges: dict = {}


def set_gauge(name: str, value: float) -> None:
    with _lock:
        _gauges[name] = value


def get_gauge(name: str, default: float = 0.0) -> float:
    with _lock:
        return _gauges.get(name, default)


def gauges() -> dict:
    """Snapshot of all gauges."""
    with _lock:
        return dict(_gauges)


def reset_gauges() -> None:
    with _lock:
        _gauges.clear()


# ---------------------------------------------------------- histograms
# Bounded distribution summaries on fixed log-spaced buckets: O(number of
# buckets) memory at ANY observation volume, O(log buckets) record cost,
# quantile estimates within one bucket (factor 2^0.25 ≈ 19%) of the true
# percentile. The shared primitive behind serve latency percentiles and
# the obs bind-time accounting; exported in Prometheus histogram format
# by mx.obs.render_prometheus().

# 96 log-spaced bounds, 1e-5 .. ~1.4e7 (units are the caller's: seconds
# for latencies spans 10us..~160h, milliseconds for bind times spans
# 10ns..~4h)
_DEFAULT_BOUNDS = tuple(1e-5 * (2.0 ** (i / 4.0)) for i in range(96))


class Histogram(object):
    """Thread-safe fixed-bucket histogram (cumulative since last reset)."""

    __slots__ = ("bounds", "_counts", "_sum", "_count", "_min", "_max",
                 "_hlock")

    def __init__(self, bounds=None):
        self.bounds = tuple(float(b) for b in (bounds or _DEFAULT_BOUNDS))
        assert all(a < b for a, b in zip(self.bounds, self.bounds[1:])), \
            "histogram bounds must be strictly increasing"
        # one overflow bucket past the last bound
        self._counts = [0] * (len(self.bounds) + 1)
        self._sum = 0.0
        self._count = 0
        self._min = None
        self._max = None
        self._hlock = threading.Lock()

    def observe(self, value: float) -> None:
        v = float(value)
        idx = bisect.bisect_left(self.bounds, v)
        with self._hlock:
            self._counts[idx] += 1
            self._sum += v
            self._count += 1
            if self._min is None or v < self._min:
                self._min = v
            if self._max is None or v > self._max:
                self._max = v

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    def reset(self) -> None:
        with self._hlock:
            for i in range(len(self._counts)):
                self._counts[i] = 0
            self._sum = 0.0
            self._count = 0
            self._min = None
            self._max = None

    def snapshot(self) -> dict:
        """Consistent copy: {bounds, counts, sum, count, min, max}."""
        with self._hlock:
            return {"bounds": self.bounds, "counts": list(self._counts),
                    "sum": self._sum, "count": self._count,
                    "min": self._min, "max": self._max}

    def quantile(self, q: float) -> Optional[float]:
        """Estimated q-quantile (0..1): linear interpolation inside the
        bucket holding the target rank; None while empty. Off by at most
        one bucket from the exact order statistic."""
        snap = self.snapshot()
        return _snapshot_quantile(snap, q)

    def quantiles(self, qs) -> List[Optional[float]]:
        snap = self.snapshot()
        return [_snapshot_quantile(snap, q) for q in qs]


def _snapshot_quantile(snap: dict, q: float) -> Optional[float]:
    count = snap["count"]
    if count == 0:
        return None
    q = min(max(float(q), 0.0), 1.0)
    target = q * count
    bounds, counts = snap["bounds"], snap["counts"]
    cum = 0.0
    for i, c in enumerate(counts):
        if c == 0:
            continue
        prev_cum = cum
        cum += c
        if cum >= target:
            lo = bounds[i - 1] if i > 0 else max(
                0.0, snap["min"] if snap["min"] is not None else 0.0)
            hi = bounds[i] if i < len(bounds) else \
                (snap["max"] if snap["max"] is not None else bounds[-1])
            lo = max(lo, snap["min"]) if snap["min"] is not None else lo
            hi = min(hi, snap["max"]) if snap["max"] is not None else hi
            if hi <= lo:
                return lo
            frac = (target - prev_cum) / c
            return lo + (hi - lo) * min(max(frac, 0.0), 1.0)
    return snap["max"]


_histograms: Dict[str, Histogram] = {}


def histogram(name: str, bounds=None) -> Histogram:
    """Get-or-create the registry histogram ``name`` (shared across the
    process, like counters/gauges)."""
    with _lock:
        h = _histograms.get(name)
        if h is None:
            h = Histogram(bounds)
            _histograms[name] = h
        return h


def observe(name: str, value: float) -> None:
    """Record one observation into the registry histogram ``name``."""
    histogram(name).observe(value)


def histograms() -> Dict[str, Histogram]:
    """Snapshot of the histogram registry (name -> Histogram)."""
    with _lock:
        return dict(_histograms)


def reset_histograms() -> None:
    with _lock:
        for h in _histograms.values():
            h.reset()


# serializes the file write of dump() without holding the hot-path
# _lock across disk I/O: two concurrent dump() calls to one filename
# must not interleave their buffered writes into unparseable JSON
_dump_lock = threading.Lock()


def dump(finished: bool = True) -> str:
    """Write the chrome-trace JSON; returns the path (reference:
    profiler.py:64 dump_profile -> engine Profiler::DumpProfile,
    src/engine/profiler.cc:127-179). The payload AND the target filename
    are captured under the lock (so a concurrent ``set_config`` swaps
    cleanly between dumps), and the write itself is serialized under a
    separate dump lock (so concurrent dumps cannot interleave)."""
    with _dump_lock:
        return _dump_locked(finished)


def _dump_locked(finished: bool) -> str:
    with _lock:
        events = list(_events)
        # lane-name metadata first (only for lanes that actually appear)
        # so every used tid renders under its registered name
        used = {e.get("tid") for e in events}
        meta = []
        for name, lid in sorted(_lanes.items(), key=lambda kv: kv[1]):
            if lid not in used:
                continue
            meta.append({"name": "thread_name", "ph": "M", "pid": 0,
                         "tid": lid, "args": {"name": name}})
            meta.append({"name": "thread_sort_index", "ph": "M", "pid": 0,
                         "tid": lid, "args": {"sort_index": lid}})
        payload = {"traceEvents": meta + events, "displayTimeUnit": "ms"}
        path = _filename
        if finished:
            _events.clear()
            _flows_seen.clear()
    if path == "profile.json":
        # shared-filesystem pods: every host dumping the DEFAULT
        # filename would clobber the others' traces — suffix the pod
        # rank (a pure state probe; an explicit set_config() filename
        # is the user's choice and is respected as-is)
        try:
            from .checkpoint.format import pod_info
            prank, pworld = pod_info()
        except Exception:                                  # noqa: BLE001
            prank, pworld = 0, 1
        if pworld > 1:
            path = "profile-p%d.json" % prank
    with open(path, "w") as f:
        json.dump(payload, f)
    return path


# reference-compatible names
profiler_set_config = set_config
profiler_set_state = set_state
dump_profile = dump


# ------------------------------------------------------------- XLA layer


def start_xla_trace(logdir: str) -> None:
    """Start a jax/XLA device trace (TensorBoard format)."""
    import jax
    jax.profiler.start_trace(logdir)


def stop_xla_trace() -> None:
    import jax
    jax.profiler.stop_trace()


# keep the cached span flag honest under runtime knob flips
def _on_obs_knob(_value) -> None:
    with _lock:
        _recompute_enabled_locked()


_config.on_change("MXNET_TPU_OBS", _on_obs_knob)
with _lock:
    _recompute_enabled_locked()
