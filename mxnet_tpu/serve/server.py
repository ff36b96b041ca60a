"""``mx.serve.InferenceServer`` — dynamic-batching inference serving.

The reference deployment story stops at the synchronous, single-request
predict API (``c_predict_api.h:77-178``: SetInput -> Forward ->
GetOutput); production traffic is concurrent and batch-1 dispatch wastes
the accelerator. This module is the serving layer the ROADMAP's
"millions of users" north star needs, built the way production servers
do it (NVIDIA Triton's dynamic batcher, TF Serving's BatchingSession,
Clipper's adaptive batching):

* concurrent callers ``submit()`` single requests and get futures;
* a bounded queue coalesces them into micro-batches under a
  ``max_batch_size`` / ``max_delay_us`` window;
* every batch is padded onto the finite pow2 bucket grid
  (:mod:`.bucketing`) so the jitted executable set is finite and
  steady-state serving does **zero recompiles**;
* results are split back per request, futures resolve after the device
  sync, so recorded latency is real end-to-end time.

Robustness: per-request deadlines (``DeadlineExceeded``), admission
control with load-shedding (``QueueFull``), graceful drain on ``close``,
and the ``MXNET_TPU_SERVE`` kill switch + per-request eager fallback
mirroring the fused-trainer pattern (``_fused.py``): a structure whose
batched build fails is negative-cached with bounded retry and its
traffic degrades to eager per-request forwards instead of erroring.

Observability: per-bucket compile/hit counters ride the shared
:class:`CompileCache` discipline under the ``serve_*`` profiler prefix;
queue depth and batch occupancy are profiler gauges; ``stats()``
snapshots p50/p95/p99 latency, throughput accounting and the per-bucket
table.
"""
from __future__ import annotations

import collections
import threading
import time
import weakref
from concurrent.futures import Future
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from .. import lockcheck as _lockcheck
from .. import ndarray as nd_mod
from .. import profiler as _profiler
from ..obs import compiles as _obs_compiles
from ..obs.http import maybe_start_from_knob as _maybe_metrics
from .._fused import CompileCache, structural_failure
from ..base import MXNetError
from ..context import Context, current_context
from .bucketing import BucketSpec
from .stats import LatencyStats, monotonic

__all__ = ["InferenceServer", "GenerativeServer", "GenerateHandle",
           "ServeError", "ServerClosed", "QueueFull", "DeadlineExceeded",
           "wrap_model"]

# per-bucket stats table bound; the tail aggregates under "(other)"
_MAX_BUCKET_STATS = 1024


class ServeError(MXNetError):
    """Base class for serving errors."""


class ServerClosed(ServeError):
    """submit() after close()."""


class QueueFull(ServeError):
    """Load shed: the admission bound was exceeded (clients should back
    off / retry against another replica — erroring fast beats queueing
    into a latency collapse)."""


class DeadlineExceeded(ServeError):
    """The request's deadline passed before its batch launched."""


def wrap_model(model) -> Callable:
    """Normalize the served model to ``fn(NDArray batch) -> outputs``.

    Accepts a :class:`~mxnet_tpu.predictor.Predictor` (single declared
    input), a bound :class:`~mxnet_tpu.module.BaseModule`, a gluon
    ``Block``, or any callable taking an NDArray batch. Batch geometry
    varies per call (the bucket grid), so the Predictor/Module paths
    feed the underlying executor directly — jit re-specializes once per
    bucket, exactly the finite set the server maintains.

    Ownership: serving a Predictor/Module hands its executor to the
    server (all server-side calls are serialized by the model lock, and
    the Predictor's bound input geometry is restored after each batch).
    Do NOT call ``forward``/``set_input`` on it from other threads
    WHILE it is being served — direct use is safe again after
    ``close()``.
    """
    from ..predictor import Predictor
    from ..module.base_module import BaseModule

    if isinstance(model, Predictor):
        names = sorted(model._input_shapes)
        if len(names) != 1:
            raise ValueError(
                "serve: Predictor has inputs %s; the dynamic batcher "
                "coalesces a single request tensor — wrap multi-input "
                "models in a callable" % (names,))
        name = names[0]

        def predictor_fn(x):
            # restore the bound input buffer afterwards: the bucket
            # batch would otherwise permanently replace the declared
            # (1, ...) geometry, and a later DIRECT predictor.forward()
            # would silently broadcast its input across the bucket rows
            buf = model._exec.arg_dict[name]
            saved = buf._data
            try:
                return list(model._exec.forward(is_train=False,
                                                **{name: x}))
            finally:
                buf._data = saved
                buf._version += 1

        return predictor_fn
    if isinstance(model, BaseModule):
        from .. import io as io_mod

        def module_fn(x):
            model.forward(io_mod.DataBatch(data=[x]), is_train=False)
            return list(model.get_outputs())

        return module_fn
    if callable(model):
        return model
    raise TypeError("serve: cannot wrap %r — expected Predictor, Module, "
                    "gluon Block, or callable" % (type(model).__name__,))


def _resolve(fut: Future, value=None, exc: Optional[BaseException] = None):
    """Complete a future, tolerating caller-side cancel(): a cancelled
    future must never kill the batcher thread."""
    try:
        if exc is not None:
            fut.set_exception(exc)
        else:
            fut.set_result(value)
    except Exception:                                       # noqa: BLE001
        pass


def _serve_loop(server_ref):
    """Batcher thread body. While IDLE it sleeps holding only the
    server's condition variable, never the server itself — so an
    abandoned (un-closed) server is garbage-collectable and the thread
    exits on its next wake instead of pinning the model and polling
    forever. While a batch is pending it holds the server normally."""
    while True:
        srv = server_ref()
        if srv is None:
            return
        cond = srv._cond
        queue = srv._queue          # stable identity (mutated in place)
        with cond:
            has_work = bool(queue)
            closed = srv._closed
        if not has_work:
            if closed:
                return
            srv = None              # the idle sleep must not pin the server
            with cond:
                if not queue:       # re-check under the lock: a submit
                    cond.wait(0.05)  # in the gap must not lose its wakeup
            continue
        try:
            batch = srv._take_batch()
            if batch is None:
                return
            if batch:
                srv._run_batch(batch)
        except Exception:                                  # noqa: BLE001
            # the batcher must never die: _run_batch routes errors into
            # the affected futures; anything that escapes is a bug, but
            # killing the worker would turn it into a silent hang for
            # every later request
            pass
        del srv


class _Request:
    __slots__ = ("data", "rows", "batched", "sample_shape", "bucket_key",
                 "future", "t_submit", "deadline", "flow")

    def __init__(self, data, rows, batched, sample_shape, bucket_key,
                 deadline, flow=None):
        self.data = data
        self.rows = rows
        self.batched = batched
        self.sample_shape = sample_shape
        self.bucket_key = bucket_key
        self.future: Future = Future()
        self.t_submit = monotonic()
        self.deadline = deadline
        self.flow = flow    # trace flow id linking submit -> launch


class InferenceServer:
    """Thread-safe dynamic-batching server over one model.

    Parameters
    ----------
    model : Predictor | Module | Block | callable
        Forward function taking an NDArray batch (leading row axis) and
        returning an NDArray or list of NDArrays with the same leading
        row count. Inference must be row-independent (eval-mode nets
        are) — padded rows must not bleed into real ones.
    max_batch_size, max_delay_us, queue_bound : int, optional
        Coalescing row bound, batching window, and admission bound.
        Defaults come from the ``MXNET_TPU_SERVE_*`` env knobs.
    buckets : BucketSpec, optional
        Full bucket control (explicit ladders, dynamic seq axis). When
        given, ``max_batch_size`` must be left None — the spec owns it.
    ctx : Context, optional
        Device requests are staged to (default: current context).
    name : str
        Prefix for profiler counters/gauges (default ``"serve"``; give
        each server a distinct name to split dashboards).
    metrics_port : int, optional
        Opt-in Prometheus ``/metrics`` endpoint (mx.obs exposition):
        ``None`` defers to the ``MXNET_TPU_OBS_METRICS_PORT`` knob,
        ``-1`` = off, ``0`` = ephemeral port (read ``.metrics_port``
        back), ``>0`` = fixed port. Closed with the server.
    """

    def __init__(self, model, max_batch_size: Optional[int] = None,
                 max_delay_us: Optional[int] = None,
                 queue_bound: Optional[int] = None,
                 buckets: Optional[BucketSpec] = None,
                 ctx: Optional[Context] = None,
                 name: str = "serve",
                 metrics_port: Optional[int] = None):
        from .. import config as _config
        if buckets is not None and max_batch_size is not None:
            raise ValueError("pass max_batch_size or buckets, not both")
        if buckets is None:
            buckets = BucketSpec(max_batch_size if max_batch_size is not None
                                 else _config.get("MXNET_TPU_SERVE_MAX_BATCH"))
        self.buckets = buckets
        self.max_delay_s = (max_delay_us if max_delay_us is not None else
                            _config.get("MXNET_TPU_SERVE_MAX_DELAY_US")) * 1e-6
        self.queue_bound = (queue_bound if queue_bound is not None else
                            _config.get("MXNET_TPU_SERVE_QUEUE_BOUND"))
        self.name = name
        self._model = wrap_model(model)
        self._ctx = ctx or current_context()
        self._single_output: Optional[bool] = None
        # sig -> padded-dispatch runner; counters ride the shared
        # CompileCache scheme (<name>_compile / _cache_hit / ...), so
        # "zero recompiles after warmup" is a counter assertion. The
        # table must hold the WHOLE bucket grid: eviction of a live
        # geometry would re-count its next dispatch as a compile and
        # falsify that observable (4x headroom covers multiple dtypes;
        # unbounded client shape sets — no seq bucketing — get a large
        # table, mirroring the underlying jit cache they also grow).
        grid = self.buckets.executable_bound()
        self.cache = CompileCache(
            name, max_entries=max(4 * grid, 128) if grid else 4096)
        # latency rides the shared obs histogram registry (same-name
        # servers aggregate, mirroring the <name>_* counter discipline)
        # so the Prometheus exposition includes it without extra wiring
        self.latency = LatencyStats(name=name + "_latency_seconds")
        # opt-in Prometheus /metrics endpoint (arg wins over the
        # MXNET_TPU_OBS_METRICS_PORT knob; resolved < 0 = off). This
        # server is deliberately collectable without close() (the worker
        # holds only a weakref) — the finalizer keeps that true for the
        # endpoint too, releasing the bound port when the server is GC'd
        try:
            self._metrics = _maybe_metrics(metrics_port)
        except OSError as exc:
            # an observability knob must never take down the serving
            # path: a port conflict (second server on a fixed port,
            # another process) degrades to no endpoint, loudly
            import logging
            logging.getLogger(__name__).warning(
                "serve[%s]: /metrics endpoint disabled (%s)", name, exc)
            _profiler.incr_counter(name + "_metrics_bind_failed")
            self._metrics = None
        self.metrics_port = self._metrics.port if self._metrics else None
        self._metrics_finalizer = weakref.finalize(
            self, self._metrics.close) if self._metrics else None
        # serializes ALL model invocations: Predictor/Module adapters
        # mutate shared executor state (arg_dict -> forward -> outputs),
        # so a kill-switch eager call in a caller thread must never
        # interleave with the worker's batched call or another caller.
        # Uncontended on the hot batched path (worker-only). allow_sync:
        # _call_model fetches outputs under it by design (the adapter's
        # shared executor state is what the lock serializes — see the
        # mx-lint allow(lock-host-sync) at the call site).
        self._model_lock = _lockcheck.Lock(name="serve.model_lock",
                                           allow_sync=True)
        self._lock = _lockcheck.Lock(name="serve.queue_lock")
        self._cond = _lockcheck.Condition(self._lock)
        self._queue: collections.deque = collections.deque()
        self._closed = False
        self._batches = 0
        self._served = 0
        self._padded_rows = 0
        self._per_bucket: Dict[Tuple, Dict[str, int]] = {}
        # the loop holds only a WEAK reference between iterations: a
        # server dropped without close() must be collectable (a strong
        # ref from a live thread would pin the model + params and poll
        # forever) — the thread exits on the first wake after GC
        self._worker = threading.Thread(
            target=_serve_loop, args=(weakref.ref(self),), daemon=True,
            name="mxnet_tpu.serve[%s]" % name)
        self._worker.start()

    # ------------------------------------------------------------ submit
    def submit(self, data, batched: bool = False,
               timeout: Optional[float] = None) -> Future:
        """Enqueue one request; returns a ``concurrent.futures.Future``.

        ``data`` is one sample (no batch dim) by default; with
        ``batched=True`` its leading axis is rows and the result keeps
        it. The future resolves with host numpy arrays (zero-copy row
        views of the batch fetch — serving results cross a process
        boundary anyway, and per-request device slicing costs more
        than the batched forward). ``timeout`` (seconds) is the request
        deadline: if its batch has not launched by then the future
        fails with :class:`DeadlineExceeded`.

        Raises :class:`QueueFull` (load shed) when the queue is at the
        admission bound, :class:`ServerClosed` after ``close()``.
        """
        from .. import config as _config
        from .. import faults as _faults
        if _faults.ARMED:
            # robustness drill: an injected submit failure must surface
            # on THIS request only — the server keeps serving
            _faults.fire("serve.submit", default_kind="raise")
        x = np.asarray(data.asnumpy() if isinstance(data, nd_mod.NDArray)
                       else data)
        if batched:
            if x.ndim < 1:
                raise ValueError("batched request needs a leading row axis")
            rows, sample_shape = int(x.shape[0]), tuple(x.shape[1:])
            if rows > self.buckets.max_batch_size:
                raise ValueError(
                    "request of %d rows exceeds max_batch_size %d — split "
                    "it client-side" % (rows, self.buckets.max_batch_size))
        else:
            rows, sample_shape = 1, tuple(x.shape)
        # admission-time shape validation: sample_bucket raises on
        # over-long dynamic axes, so bad requests fail fast in the
        # caller, not in the batcher thread
        padded_sample = self.buckets.sample_bucket(sample_shape)
        bucket_key = (padded_sample, str(x.dtype))
        deadline = None if timeout is None else monotonic() + timeout

        if self._closed:
            raise ServerClosed("submit() after close()")
        if not _config.get("MXNET_TPU_SERVE"):
            # kill switch: per-request eager forward in the caller
            # thread — no queue, no batching, no bucketing
            return self._eager_future(x, rows, batched)

        fid = _profiler.new_flow() if _profiler.spans_enabled() else None
        req = _Request(x, rows, batched, sample_shape, bucket_key, deadline,
                       flow=fid)
        with _profiler.span("serve_submit", "serve", flow=fid):
            with self._cond:
                if self._closed:
                    raise ServerClosed("submit() after close()")
                if len(self._queue) >= self.queue_bound:
                    _profiler.incr_counter(self.name + "_shed")
                    raise QueueFull(
                        "queue depth %d at admission bound %d"
                        % (len(self._queue), self.queue_bound))
                self._queue.append(req)
                _profiler.set_gauge(self.name + "_queue_depth",
                                    len(self._queue))
                self._cond.notify_all()
        return req.future

    def __call__(self, data, batched: bool = False,
                 timeout: Optional[float] = None):
        """Blocking convenience: ``submit(...).result()``."""
        return self.submit(data, batched=batched, timeout=timeout).result()

    # ------------------------------------------------------------- close
    def close(self, drain: bool = True, timeout: Optional[float] = None):
        """Stop accepting requests. ``drain=True`` (default) serves
        everything already queued before the worker exits; ``False``
        fails queued requests with :class:`ServerClosed`. Idempotent:
        a second close only joins — it must not drop requests a prior
        ``close(drain=True)`` promised to serve."""
        with self._cond:
            already = self._closed
            self._closed = True
            if already:
                self._cond.notify_all()
                drain = True        # first close's promise stands
            if not drain:
                dropped = list(self._queue)
                self._queue.clear()
            else:
                dropped = []
            self._cond.notify_all()
        for req in dropped:
            _resolve(req.future, exc=ServerClosed("server closed"))
        self._worker.join(timeout)
        if self._metrics_finalizer is not None:
            self._metrics_finalizer()    # idempotent: detaches after one call
            self._metrics = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close(drain=not any(exc))
        return False

    # ------------------------------------------------------------- stats
    def stats(self) -> Dict[str, Any]:
        """Point-in-time serving snapshot (thread-safe)."""
        with self._lock:
            depth = len(self._queue)
            batches, served = self._batches, self._served
            padded = self._padded_rows
            per_bucket = {
                (key if isinstance(key, str)
                 else "%s/%s" % ("x".join(map(str, key[0])), key[1])):
                dict(rec)
                for key, rec in self._per_bucket.items()}
        dispatched = sum(r["rows"] for r in per_bucket.values())
        return {
            "requests": served,
            "batches": batches,
            "queue_depth": depth,
            "avg_batch_rows": round(dispatched / batches, 3) if batches
            else None,
            "occupancy": round(dispatched / (dispatched + padded), 4)
            if dispatched else None,
            "buckets": per_bucket,
            "compiles": _profiler.get_counter(self.name + "_compile"),
            "cache_hits": _profiler.get_counter(self.name + "_cache_hit"),
            "shed": _profiler.get_counter(self.name + "_shed"),
            "deadline_expired": _profiler.get_counter(
                self.name + "_deadline_expired"),
            "eager_fallback": _profiler.get_counter(self.name + "_eager"),
            "latency": self.latency.snapshot(),
        }

    # ----------------------------------------------------------- batcher
    def _take_batch(self) -> Optional[List[_Request]]:
        """Wait (bounded) for a batch: [] when nothing is ready yet
        (caller re-checks liveness and retries), None when the worker
        should exit (closed and drained)."""
        with self._cond:
            if not self._queue:
                if self._closed:
                    return None
                # bounded wait so _serve_loop can drop its strong ref
                # and re-check server liveness between idle ticks
                self._cond.wait(0.05)
                if not self._queue:
                    return None if self._closed else []
            head = self._queue[0]
            _t_co = time.perf_counter() if _profiler.spans_enabled() \
                else None
            window_end = head.t_submit + self.max_delay_s
            while not self._closed:
                now = monotonic()
                if now >= window_end:
                    break
                if self._compatible_rows(head.bucket_key) >= \
                        self.buckets.max_batch_size:
                    break
                # a queued deadline must fire ~when promised, not up to
                # a full batching window late: wake at the earliest of
                # window end / next deadline / the 10 ms arrival tick
                dls = [r.deadline for r in self._queue
                       if r.deadline is not None]
                next_dl = min(dls) if dls else None
                if next_dl is not None and now >= next_dl:
                    break
                tick = window_end - now
                if next_dl is not None:
                    tick = min(tick, next_dl - now)
                self._cond.wait(min(tick, 0.01))
            # pop the head's bucket-mates FIFO, honoring the row bound;
            # other buckets keep their queue positions
            batch, rows, kept = [], 0, []
            now = monotonic()
            expired = []
            for req in self._queue:
                if req.future.cancelled():
                    continue
                if req.deadline is not None and now > req.deadline:
                    expired.append(req)
                    continue
                if req.bucket_key == head.bucket_key and \
                        rows + req.rows <= self.buckets.max_batch_size and \
                        req.future.set_running_or_notify_cancel():
                    batch.append(req)
                    rows += req.rows
                else:
                    kept.append(req)
            # in-place: _serve_loop's idle path holds this deque by
            # identity, so the queue object must never be rebound
            self._queue.clear()
            self._queue.extend(kept)
            _profiler.set_gauge(self.name + "_queue_depth",
                                len(self._queue))
        for req in expired:
            _profiler.incr_counter(self.name + "_deadline_expired")
            _resolve(req.future, exc=DeadlineExceeded(
                "deadline passed %.1f ms before batch launch"
                % ((now - req.deadline) * 1e3)))
        if batch and _t_co is not None:
            # batching-window slice on the batcher lane, linked to the
            # head request's flow (idle ticks emit nothing)
            _profiler.record_span("serve_coalesce", _t_co,
                                  time.perf_counter(), "serve",
                                  flow=batch[0].flow)
        return batch

    def _compatible_rows(self, bucket_key) -> int:
        return sum(r.rows for r in self._queue
                   if r.bucket_key == bucket_key)

    # ---------------------------------------------------------- dispatch
    def _call_model(self, x: nd_mod.NDArray) -> List[np.ndarray]:
        """Run the model and fetch each output to host ONCE. Results are
        numpy: per-request splitting must be zero-copy views — slicing
        NDArrays would dispatch one eager device op per request, which
        measured ~10x the whole batched forward at MLP sizes. The fetch
        doubles as the device sync, so recorded latency is real."""
        # the lock-held host sync is the design here: all model
        # invocations serialize on _model_lock (shared executor state),
        # and fetching inside it is what makes recorded latency real
        with self._model_lock:  # mx-lint: allow(lock-host-sync)
            outs = self._model(x)
            outs = list(outs) if isinstance(outs, (list, tuple)) else [outs]
            if self._single_output is None:
                # one output -> callers get the bare array, not a 1-list
                # (Predictor/Module adapters always hand back lists)
                self._single_output = len(outs) == 1
            return [np.asarray(o.asnumpy()) for o in outs]

    def _assemble(self, batch: List[_Request], bucket_rows: int):
        padded_sample = batch[0].bucket_key[0]
        buf = np.full((bucket_rows,) + padded_sample,
                      self.buckets.pad_value, batch[0].data.dtype)
        r0 = 0
        for req in batch:
            block = req.data if req.batched else req.data[None]
            sl = (slice(r0, r0 + req.rows),) + tuple(
                slice(0, d) for d in req.sample_shape)
            buf[sl] = block
            r0 += req.rows
        return buf, r0

    def _run_batch(self, batch: List[_Request]):
        rows = sum(r.rows for r in batch)
        try:
            bucket_rows = self.buckets.batch_bucket(rows)
            sig = (batch[0].bucket_key, bucket_rows)
            if self.cache.should_skip(sig):
                # negative-cached geometry: its traffic runs eager
                self._fallback_eager(batch)
                return
            buf, _ = self._assemble(batch, bucket_rows)
            # NOTE: the cached "runner" is always _call_model — the real
            # per-geometry executable lives in jax's jit cache, keyed by
            # the same padded shape this sig encodes. CompileCache here
            # supplies the rest of its contract: first-dispatch/hit
            # counters (the zero-recompile observable), bounded-retry
            # negative caching, and the eager-fallback gate.
            runner = self.cache.get(sig)
            fresh = runner is None
            if fresh:
                runner = self._call_model
            try:
                with _profiler.span("serve_launch", "serve",
                                    flow=batch[0].flow) as _sp:
                    for req in batch[1:]:
                        _sp.mark_flow(req.flow)
                    with _obs_compiles.scope(self.name, sig):
                        outs = runner(nd_mod.array(buf, ctx=self._ctx))
            except Exception as exc:                       # noqa: BLE001
                self.cache.mark_failed(sig,
                                       permanent=structural_failure(exc))
                self._fallback_eager(batch)
                return
            if fresh:
                self.cache.put(sig, runner)
            else:
                self.cache.note_success(sig)
        except Exception as exc:                           # noqa: BLE001
            for req in batch:
                _resolve(req.future, exc=exc)
            return
        with self._lock:
            self._batches += 1
            self._served += len(batch)
            self._padded_rows += bucket_rows - rows
            # bounded like every sibling structure (CompileCache table,
            # LatencyStats ring): client-controlled shape sets must not
            # grow the stats table monotonically — the tail aggregates
            key = sig[0]
            if key not in self._per_bucket and \
                    len(self._per_bucket) >= _MAX_BUCKET_STATS:
                key = "(other)"
            rec = self._per_bucket.setdefault(
                key, {"batches": 0, "requests": 0, "rows": 0})
            rec["batches"] += 1
            rec["requests"] += len(batch)
            rec["rows"] += rows
        _profiler.incr_counter(self.name + "_batches")
        _profiler.incr_counter(self.name + "_requests", len(batch))
        _profiler.set_gauge(self.name + "_batch_occupancy",
                            rows / bucket_rows)
        done = monotonic()
        r0 = 0
        try:
            with _profiler.span("serve_resolve", "serve",
                                flow=batch[0].flow):
                for req in batch:
                    if self._single_output:
                        res = outs[0][r0:r0 + req.rows] if req.batched \
                            else outs[0][r0]
                    else:
                        res = [o[r0:r0 + req.rows] if req.batched else o[r0]
                               for o in outs]
                    r0 += req.rows
                    self.latency.record(done - req.t_submit)
                    _resolve(req.future, res)
        except Exception as exc:                           # noqa: BLE001
            # row-contract violation (output leading axis != input rows):
            # every future must still resolve — a dead batcher thread
            # would hang all pending AND future requests silently. The
            # geometry is structurally broken, so pin it to the eager
            # path, where the same error surfaces per request.
            self.cache.mark_failed(sig, permanent=True)
            for req in batch:
                _resolve(req.future, exc=exc)

    # ------------------------------------------------------ eager paths
    def _eager_one(self, x: np.ndarray, batched: bool):
        nd_in = nd_mod.array(x if batched else x[None], ctx=self._ctx)
        outs = self._call_model(nd_in)
        _profiler.incr_counter(self.name + "_eager")
        if self._single_output:
            return outs[0] if batched else outs[0][0]
        return outs if batched else [o[0] for o in outs]

    def _eager_future(self, x, rows, batched) -> Future:
        fut: Future = Future()
        t0 = monotonic()
        try:
            res = self._eager_one(x, batched)
        except Exception as exc:                           # noqa: BLE001
            fut.set_exception(exc)
            return fut
        self.latency.record(monotonic() - t0)
        with self._lock:
            self._served += 1
        fut.set_result(res)
        return fut

    def _fallback_eager(self, batch: List[_Request]):
        """Per-request eager forwards for a batch whose bucketed
        dispatch is unavailable (build failed / negative-cached) — the
        serving twin of the fused trainer's per-param fallback."""
        done_extra = 0
        for req in batch:
            # same deadline contract as the healthy path: a request
            # whose deadline lapsed while earlier fallback forwards ran
            # fails DeadlineExceeded instead of resolving arbitrarily
            # late (callers key retry/hedging logic on that error)
            if req.deadline is not None and monotonic() > req.deadline:
                _profiler.incr_counter(self.name + "_deadline_expired")
                _resolve(req.future, exc=DeadlineExceeded(
                    "deadline passed before eager-fallback dispatch"))
                continue
            try:
                res = self._eager_one(req.data, req.batched)
            except Exception as exc:                       # noqa: BLE001
                _resolve(req.future, exc=exc)
                continue
            self.latency.record(monotonic() - req.t_submit)
            _resolve(req.future, res)
            done_extra += 1
        with self._lock:
            self._served += done_extra


# ===================================================================
# Generative serving: continuous batching over the bucketed KV cache
# ===================================================================


class GenerateHandle:
    """Per-request streaming future: tokens arrive as they are decoded.

    The continuous-batching analogue of ``submit()``'s Future — one
    handle per ``submit_generate()`` call. Iterate it for streaming
    (``for tok in handle: ...`` blocks until each next token), or call
    :meth:`result` for the whole sequence. ``on_token`` (if given) is
    invoked from the scheduler thread per token — it must be fast and
    must not call back into the server.
    """

    def __init__(self, on_token: Optional[Callable[[int], None]] = None):
        self._cond = _lockcheck.Condition(name="serve.stream_cond")
        self._tokens: List[int] = []
        self._done = False
        self._exc: Optional[BaseException] = None
        self._on_token = on_token
        self._cancelled = False

    # ------------------------------------------------- scheduler side
    def _put(self, token: int) -> None:
        with self._cond:
            self._tokens.append(int(token))
            self._cond.notify_all()
        if self._on_token is not None:
            try:
                self._on_token(int(token))
            except Exception:                               # noqa: BLE001
                # a client callback must never kill the scheduler
                pass

    def _finish(self, exc: Optional[BaseException] = None) -> None:
        with self._cond:
            if self._done:
                return
            self._done = True
            self._exc = exc
            self._cond.notify_all()

    # ---------------------------------------------------- caller side
    def cancel(self) -> None:
        """Request eviction at the next step boundary (the sequence's
        pages free there; already-streamed tokens remain valid)."""
        with self._cond:
            self._cancelled = True

    def done(self) -> bool:
        with self._cond:
            return self._done

    @property
    def exception(self) -> Optional[BaseException]:
        with self._cond:
            return self._exc

    def tokens_so_far(self) -> List[int]:
        with self._cond:
            return list(self._tokens)

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block until the sequence finishes; the full token list, or
        raises the sequence's error (an injected decode fault, a
        deadline, ServerClosed)."""
        deadline = None if timeout is None else monotonic() + timeout
        with self._cond:
            while not self._done:
                left = None if deadline is None else deadline - monotonic()
                if left is not None and left <= 0:
                    raise TimeoutError("generation still running after "
                                       "%.1fs" % timeout)
                self._cond.wait(0.1 if left is None else min(left, 0.1))
            if self._exc is not None:
                raise self._exc
            return list(self._tokens)

    def __iter__(self):
        """Stream tokens in decode order; raises the sequence's error
        (if any) after the last streamed token."""
        i = 0
        while True:
            with self._cond:
                while i >= len(self._tokens) and not self._done:
                    self._cond.wait(0.1)
                if i < len(self._tokens):
                    tok = self._tokens[i]
                else:
                    if self._exc is not None:
                        raise self._exc
                    return
            i += 1
            yield tok


class _GenRequest:
    __slots__ = ("prompt", "max_new_tokens", "eos_id", "temperature",
                 "seed", "deadline", "handle", "t_submit", "t_queued",
                 "flow")

    def __init__(self, prompt, max_new_tokens, eos_id, temperature, seed,
                 deadline, handle):
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.eos_id = eos_id
        self.temperature = temperature
        self.seed = seed
        self.deadline = deadline
        self.handle = handle
        self.t_submit = monotonic()
        # the span clock's stamp of the same moment (gen_queue_wait), and
        # the id that the request's spans share: given at submit only
        # while spans are live, else at admission if they are live then
        self.t_queued = time.perf_counter()
        self.flow = _profiler.new_flow() if _profiler.spans_enabled() \
            else None


class _ActiveSeq:
    __slots__ = ("slot", "handle", "pos", "generated", "max_new_tokens",
                 "eos_id", "temperature", "rng", "token", "t_last", "flow",
                 "queued")

    def __init__(self, slot, handle, pos, max_new_tokens, eos_id,
                 temperature, seed, token, flow=None):
        self.slot = slot
        self.flow = flow                # the request's, for gen_evict
        self.handle = handle
        self.pos = pos                  # next cache write position
        self.generated = 1              # prefill samples the first token
        self.max_new_tokens = max_new_tokens
        self.eos_id = eos_id
        self.temperature = temperature
        self.rng = np.random.default_rng(seed) if seed is not None else None
        self.token = token              # freshest sampled token
        self.t_last = monotonic()
        # rows of steps dispatched and not yet collected: pos, generated
        # and token count the delivered tokens only
        self.queued = 0

    def owed(self, max_seq: int) -> bool:
        """Whether the sequence gets a row in the next step dispatched:
        it is owed a token past those in flight, and has a position for
        it."""
        return (self.generated + self.queued < self.max_new_tokens
                and self.pos + self.queued < max_seq)


def _gen_loop(server_ref):
    """Scheduler thread body — same weakref discipline as _serve_loop:
    idle waits hold only the condition variable, so an abandoned server
    is collectable and the thread exits on its next wake."""
    while True:
        srv = server_ref()
        if srv is None:
            return
        cond = srv._cond
        with cond:
            busy = bool(srv._active) or bool(srv._waiting)
            closed = srv._closed
        if not busy:
            if closed:
                return
            waiting, active = srv._waiting, srv._active
            srv = None              # the idle sleep must not pin the server
            with cond:
                if not waiting and not active:  # re-check under the lock:
                    cond.wait(0.05)             # a submit in the gap must
            continue                            # not lose its wakeup
        try:
            srv._iteration()
        except Exception:                                   # noqa: BLE001
            # _iteration routes errors into the affected handles; an
            # escape is a bug but must not silently hang every later
            # request by killing the scheduler
            pass
        del srv


class GenerativeServer:
    """Continuous-batching autoregressive decode server.

    New requests join the RUNNING decode batch at step granularity
    (Orca's iteration-level scheduling): between two decode steps the
    scheduler admits waiting prompts into free KV-cache slots — prefill
    work per gap is bounded by the ``MXNET_TPU_SERVE_PREFILL_TOKENS``
    budget so joins cannot starve resident sequences' inter-token
    latency — and finished sequences evict immediately, freeing their
    pages for the next join. Every geometry that reaches the compiler
    is a bucket (|prompt buckets| + |decode buckets| programs total),
    so steady-state decode does ZERO recompiles, counter-asserted.

    Parameters
    ----------
    model : Module | (arg, aux) | dict
        The zoo-transformer parameter source
        (:func:`~mxnet_tpu.serve.decode.extract_params` naming).
    n_heads : int, optional
        Attention head count of the dense decoder (not shape-derivable).
    arch : dict, optional
        The architecture's description, for a block that parameter names
        and ``n_heads`` do not tell: the published configuration's keys,
        ``model_type`` among them, with the share this chip holds and
        the ``dtype`` the weights and the cache are held in. The family
        whose module claims it serves it (``decode.family_for``);
        without it the dense decoder is served.
    max_sequences : int, optional
        Resident decode sequences = preallocated KV slots (default
        ``MXNET_TPU_SERVE_MAX_SEQUENCES``).
    int8, page : optional
        KV-cache quantized mode / page size (default the
        ``MXNET_TPU_SERVE_KV_INT8`` / ``MXNET_TPU_SERVE_KV_PAGE``
        knobs).
    prefill_tokens : int, optional
        Per-iteration prefill token budget (bucket-padded; default the
        ``MXNET_TPU_SERVE_PREFILL_TOKENS`` knob).
    seq_buckets : sequence of int, optional
        Decode bucket ladder (default ``MXNET_TPU_SERVE_DECODE_BUCKETS``
        or pow2 up to the model's max sequence).
    mesh, layout : optional
        Shard the cache's head axis over the layout's ``tp`` axis
        (``island_specs("serve")``).

    The decode path (kv_cache/decode modules) imports lazily here: a
    process that only uses InferenceServer never pays for it — the CI
    zero-cost gate asserts ``mxnet_tpu.serve.decode`` stays unimported.
    """

    def __init__(self, model, n_heads: Optional[int] = None,
                 max_sequences: Optional[int] = None,
                 int8: Optional[bool] = None, page: Optional[int] = None,
                 prefill_tokens: Optional[int] = None,
                 queue_bound: Optional[int] = None,
                 seq_buckets: Optional[List[int]] = None,
                 prefill_chunk: int = 512,
                 name: str = "serve_gen",
                 metrics_port: Optional[int] = None,
                 mesh=None, layout=None,
                 arch: Optional[Dict[str, Any]] = None):
        from .. import config as _config
        from .kv_cache import KVCache                       # lazy: the
        from .decode import (DecodeEngine, PickedRow,       # zero-cost
                             family_for, sample_token)      # gate
        self.name = name
        self._sample_token = sample_token
        self._picked_row = PickedRow
        family = family_for(model, n_heads=n_heads, arch=arch)
        cfg = family.cfg
        self.max_sequences = int(
            max_sequences if max_sequences is not None
            else _config.get("MXNET_TPU_SERVE_MAX_SEQUENCES"))
        self.prefill_tokens = int(
            prefill_tokens if prefill_tokens is not None
            else _config.get("MXNET_TPU_SERVE_PREFILL_TOKENS"))
        self.queue_bound = (queue_bound if queue_bound is not None else
                            _config.get("MXNET_TPU_SERVE_QUEUE_BOUND"))
        pg = int(page if page is not None
                 else _config.get("MXNET_TPU_SERVE_KV_PAGE"))
        i8 = bool(_config.get("MXNET_TPU_SERVE_KV_INT8")
                  if int8 is None else int8)
        spec = _config.get("MXNET_TPU_SERVE_DECODE_BUCKETS")
        if seq_buckets is None and spec:
            from .bucketing import decode_buckets
            seq_buckets = decode_buckets(cfg.max_seq, pg, spec)
        # the family says which planes its state lives in
        self.cache = KVCache(family.planes(cfg.max_seq, pg, i8),
                             max_slots=self.max_sequences,
                             max_seq=cfg.max_seq, page=pg, name=name,
                             mesh=mesh, layout=layout)
        # hbm-budget audit of the reservation at server START — strict
        # analyze mode rejects an over-budget cache naming it, before
        # the first request ever lands
        self.hbm_audit = self.cache.audit()
        grid_bound = 4 * (len(seq_buckets) * 2 if seq_buckets else 64)
        self.compile_cache = CompileCache(name,
                                          max_entries=max(grid_bound, 128))
        self.engine = DecodeEngine(
            family, self.cache, self.compile_cache, name=name,
            seq_buckets=seq_buckets, prefill_chunk=prefill_chunk)
        from .stats import DecodeLatencyStats
        self.latency = DecodeLatencyStats(name=name)
        try:
            self._metrics = _maybe_metrics(metrics_port)
        except OSError as exc:
            import logging
            logging.getLogger(__name__).warning(
                "serve[%s]: /metrics endpoint disabled (%s)", name, exc)
            _profiler.incr_counter(name + "_metrics_bind_failed")
            self._metrics = None
        self.metrics_port = self._metrics.port if self._metrics else None
        self._metrics_finalizer = weakref.finalize(
            self, self._metrics.close) if self._metrics else None
        self._lock = _lockcheck.Lock(name="serve.gen_lock")
        self._cond = _lockcheck.Condition(self._lock)
        self._waiting: collections.deque = collections.deque()
        self._active: List[_ActiveSeq] = []
        # the decode step dispatched and not yet collected, with the
        # sequences it has rows for: (DecodeFlight, [_ActiveSeq])
        self._inflight = None
        # the scheduler's own clock, always on (``_clock``): the start of
        # the last decode dispatch where a counted period may run from it,
        # whether an XLA profile ran at that dispatch, and an admission's
        # stall open since the collect before its prefill; what the collect
        # since the last dispatch waited
        self._period_from = None
        self._profiled = False
        self._waited = 0.0
        self._stall = None
        for what in ("decode_periods", "decode_period_us",
                     "decode_collect_wait_us", "decode_dispatch_late",
                     "admit_stalls", "admit_stall_us"):
            # present from the start: none counted reads 0, not nothing
            _profiler.incr_counter("%s_%s" % (name, what), 0)
        self._closed = False
        self._drain = True
        self._worker = threading.Thread(
            target=_gen_loop, args=(weakref.ref(self),), daemon=True,
            name="mxnet_tpu.serve.gen[%s]" % name)
        self._worker.start()

    # ------------------------------------------------------------ submit
    def submit_generate(self, prompt, max_new_tokens: int = 32,
                        eos_id: Optional[int] = None,
                        timeout: Optional[float] = None,
                        temperature: float = 0.0,
                        seed: Optional[int] = None,
                        on_token: Optional[Callable[[int], None]] = None
                        ) -> GenerateHandle:
        """Enqueue one prompt for generation; returns a streaming
        :class:`GenerateHandle`.

        ``timeout`` is the TIME-TO-FIRST-TOKEN deadline (queue + prefill;
        once a sequence is resident it decodes to completion — evicting
        a half-decoded sequence wastes its whole KV footprint).
        Raises :class:`QueueFull` at the admission bound,
        :class:`ServerClosed` after ``close()``.
        """
        from .. import faults as _faults
        if _faults.ARMED:
            _faults.fire("serve.submit", default_kind="raise")
        prompt = np.asarray(
            prompt.asnumpy() if isinstance(prompt, nd_mod.NDArray)
            else prompt).astype(np.int64).ravel()
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if prompt.size >= self.cache.max_seq:
            raise ValueError(
                "prompt of %d tokens leaves no room to generate under "
                "max_seq %d" % (prompt.size, self.cache.max_seq))
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        deadline = None if timeout is None else monotonic() + timeout
        handle = GenerateHandle(on_token=on_token)
        req = _GenRequest(prompt, int(max_new_tokens), eos_id,
                          float(temperature), seed, deadline, handle)
        with self._cond:
            if self._closed:
                raise ServerClosed("submit_generate() after close()")
            if len(self._waiting) >= self.queue_bound:
                _profiler.incr_counter(self.name + "_shed")
                raise QueueFull("queue depth %d at admission bound %d"
                                % (len(self._waiting), self.queue_bound))
            self._waiting.append(req)
            _profiler.incr_counter(self.name + "_requests")
            _profiler.set_gauge(self.name + "_waiting",
                                len(self._waiting))
            self._cond.notify_all()
        return handle

    # --------------------------------------------------------- scheduler
    def _iteration(self):
        """One continuous-batching step: admit joins under the prefill
        budget, dispatch the next decode step over every resident
        sequence still owed a token, collect the step before it, stream
        its tokens and evict the finished. Runs only on the scheduler
        thread."""
        with _profiler.span("gen_iteration", "serve",
                            active=len(self._active),
                            waiting=len(self._waiting)):
            self._step()

    def _step(self):
        from .. import faults as _faults
        with _profiler.span("gen_admit", "serve"):
            self._admit()
        # a stall ends at the dispatch that follows it, or not at all
        stall, self._stall = self._stall, None
        with self._lock:
            active = list(self._active)
        # cancelled handles evict at step granularity
        for seq in active:
            if seq.handle._cancelled:
                self._evict(seq, exc=None)
        with self._lock:
            active = list(self._active)
        # capacity-exhausted sequences finish (truncated) BEFORE the
        # step: position max_seq does not exist in the cache
        for seq in active:
            if seq.pos >= self.cache.max_seq:
                self._evict(seq, exc=None)
        with self._lock:
            active = list(self._active)
        if active and _faults.ARMED:
            try:
                _faults.fire("serve.decode", default_kind="raise")
            except _faults.FaultInjected as exc:
                # the drill contract: an injected decode fault kills ONE
                # sequence's stream with a legible error — the lowest
                # resident slot, deterministically — NEVER the batch
                victim = min(active, key=lambda s: s.slot)
                self._evict(victim, exc=ServeError(
                    "injected fault at serve.decode killed the sequence "
                    "in slot %d (%s); co-resident sequences kept "
                    "decoding" % (victim.slot, exc)))
                with self._lock:
                    active = list(self._active)
        if not active:
            # a step in flight whose rows all ended is collected now:
            # nothing is in flight while the server is empty
            self._catch_up()
            return
        # Step N+1 goes out before step N's tokens come back: the host
        # knows every position without them, and the device takes each
        # slot's token from what step N picked. Not while a sequence
        # samples: its next token is drawn on the host from step N's
        # logits, so each step is collected as soon as it is dispatched.
        # (Nothing is in flight then: a sampling sequence joined through
        # a prefill, which collects the step in flight first.)
        sampling = any(seq.temperature > 0.0 for seq in active)
        behind, self._inflight = self._inflight, None
        rows = [seq for seq in active if seq.owed(self.cache.max_seq)]
        # the bucket of the step dispatched, else of the one collected
        bucket = 0
        if _profiler.spans_enabled():
            bucket = self.engine.seq_bucket(
                max(seq.pos + seq.queued for seq in rows) + 1) if rows \
                else behind[0].bucket
        try:
            with _profiler.span("gen_decode_step", "serve", bucket=bucket,
                                active=len(rows)):
                flight = self._launch(rows, sampling, behind, stall) \
                    if rows else None
                if sampling:        # collected as soon as dispatched
                    behind, flight = flight, None
                got = self._fetch(behind) if behind is not None else None
        except Exception as exc:                            # noqa: BLE001
            self._fail_batch(active, exc)
            return
        self._inflight = flight
        if got is not None:
            self._deliver(*got)
        with self._lock:
            empty = not self._active
        if empty:
            self._catch_up()

    def _launch(self, rows: List[_ActiveSeq], sampling: bool, behind, stall):
        """Dispatch one decode step with a row for each of ``rows``: the
        host's token where it has the sequence's freshest, else the one
        the step in flight picks on the device (``-1``). ``behind``: the
        step in flight, if there is one; the step dispatched is then
        counted as going ahead of its collect. ``stall``: an admission's
        stall that this dispatch ends (``_clock``)."""
        slots = self.cache.max_slots
        tokens = np.zeros((slots,), np.int32)
        pos = np.zeros((slots,), np.int32)
        mask = np.zeros((slots,), bool)
        for seq in rows:
            tokens[seq.slot] = -1 if seq.queued else seq.token
            pos[seq.slot] = seq.pos + seq.queued
            mask[seq.slot] = True
            seq.queued += 1
        engine = self.engine
        late = behind is not None and engine.decode_ready(behind[0])
        built = engine.programs_built
        profiled = _profiler.xla_profiling()
        t0 = time.perf_counter()
        flight = engine.decode_dispatch(tokens, pos, mask, logits=sampling)
        self._clock(t0, behind is not None, late, stall, profiled,
                    engine.programs_built != built)
        if behind is not None:
            _profiler.incr_counter(self.name + "_decode_steps_ahead")
        return flight, rows

    def _clock(self, t0, ahead, late, stall, profiled, compiled):
        """Time the scheduler on the host's clock at a decode dispatch that
        started at ``t0``, in integer microseconds, always on.

        A period runs from the start of one decode dispatch to the start
        of the next. It is counted (``<name>_decode_periods``,
        ``_decode_period_us``) only if the later dispatch went ahead (the
        step before was in flight: no catch-up or prefill lies between),
        neither dispatch compiled, and no XLA profile was starting,
        running or being written at either end or at the dispatch before
        (the first period after a profile is dropped too). Within it
        ``_decode_collect_wait_us`` is what the collect of the step before
        blocked on the device, and ``_decode_dispatch_late`` counts the
        periods whose closing dispatch found the step in flight done: the
        chip waited for the host. A ``stall`` (``_admit_stalls``,
        ``_admit_stall_us``) runs from the collect before an admission's
        prefill to this dispatch, under the same conditions."""
        name = self.name
        quiet = not profiled and not compiled
        opened, self._period_from = self._period_from, \
            t0 if quiet and not self._profiled else None
        waited, self._waited = self._waited, 0.0
        self._profiled = profiled
        if not quiet:
            return
        if ahead and opened is not None:
            _profiler.incr_counter(name + "_decode_periods")
            _profiler.incr_counter(name + "_decode_period_us",
                                   round((t0 - opened) * 1e6))
            _profiler.incr_counter(name + "_decode_collect_wait_us",
                                   round(waited * 1e6))
            if late:
                _profiler.incr_counter(name + "_decode_dispatch_late")
        if stall is not None and stall[1] == self.engine.programs_built:
            _profiler.incr_counter(name + "_admit_stalls")
            _profiler.incr_counter(name + "_admit_stall_us",
                                   round((t0 - stall[0]) * 1e6))

    def _fetch(self, inflight):
        """Collect a step in flight: its rows whose sequences ended while
        it ran are cleared first, so that no family hook sees them.
        Returns the rows still served, the step's tokens and its logits
        (None unless someone samples)."""
        flight, rows = inflight
        with self._lock:
            resident = set(self._active)
        live = [seq for seq in rows if seq in resident]
        for seq in rows:
            seq.queued -= 1
        for seq in set(rows).difference(live):
            flight.active[seq.slot] = False
        picked, logits = self.engine.decode_collect(flight)
        self._waited = flight.waited
        _profiler.incr_counter(self.name + "_decode_steps")
        return live, picked, logits

    def _catch_up(self):
        """Collect the step in flight, if there is one, and stream its
        tokens: before a prefill, and when no sequence is left to be
        served by the steps that would follow."""
        inflight, self._inflight = self._inflight, None
        if inflight is None:
            return
        flight, rows = inflight
        try:
            with _profiler.span("gen_decode_step", "serve",
                                bucket=flight.bucket, active=len(rows)):
                got = self._fetch(inflight)
        except Exception as exc:                            # noqa: BLE001
            with self._lock:
                active = list(self._active)
            self._fail_batch(active, exc)
            return
        self._deliver(*got)

    def _fail_batch(self, active: List[_ActiveSeq], exc: BaseException):
        """A REAL decode failure cannot be attributed to one row — every
        resident sequence fails legibly and frees its pages, and nothing
        stays in flight."""
        self._inflight = None
        for seq in active:
            self._evict(seq, exc=ServeError(
                "decode step failed for resident batch: %r" % (exc,)))

    def _deliver(self, live: List[_ActiveSeq], picked, logits):
        """Stream a collected step's tokens to its live rows' sequences and
        evict those that finished."""
        now = monotonic()
        finished = []
        with _profiler.span("gen_sample", "serve", active=len(live)):
            for seq in live:
                # a greedy sequence's row stayed on the device: the
                # sampler gets the device's choice standing for it
                row = logits[seq.slot] if seq.temperature > 0.0 \
                    else self._picked_row(picked[seq.slot],
                                          self.engine.vocab)
                tok = self._sample_token(row, seq.temperature, seq.rng)
                self.latency.tpot.record(now - seq.t_last)
                seq.t_last = now
                seq.handle._put(tok)
                self.cache.grow(seq.slot)
                seq.pos += 1
                seq.generated += 1
                seq.token = tok
                if seq.generated >= seq.max_new_tokens or \
                        (seq.eos_id is not None and tok == seq.eos_id):
                    finished.append(seq)
            _profiler.incr_counter(self.name + "_tokens", len(live))
        for seq in finished:
            self._evict(seq, exc=None)

    def _admit(self):
        """Join waiting requests into free slots under the prefill token
        budget (bucket-padded accounting — padded FLOPs are the cost the
        budget bounds)."""
        budget = self.prefill_tokens
        while True:
            with self._cond:
                if not self._waiting:
                    return
                if self.cache.ledger.slots_in_use >= self.cache.max_slots:
                    return
                req = self._waiting.popleft()
                _profiler.set_gauge(self.name + "_waiting",
                                    len(self._waiting))
            if req.handle._cancelled:
                req.handle._finish()
                continue
            now = monotonic()
            if req.deadline is not None and now > req.deadline:
                _profiler.incr_counter(self.name + "_deadline_expired")
                req.handle._finish(DeadlineExceeded(
                    "TTFT deadline passed %.1f ms before prefill"
                    % ((now - req.deadline) * 1e3)))
                continue
            bucket = self.engine.prompt_bucket(int(req.prompt.size))
            if bucket > budget and budget < self.prefill_tokens:
                # budget spent this gap: requeue at the FRONT (FIFO
                # order survives) and let the decode batch take a step
                with self._cond:
                    self._waiting.appendleft(req)
                    _profiler.set_gauge(self.name + "_waiting",
                                        len(self._waiting))
                return
            slot = self.cache.acquire(int(req.prompt.size))
            if slot is None:
                with self._cond:
                    self._waiting.appendleft(req)
                    _profiler.set_gauge(self.name + "_waiting",
                                        len(self._waiting))
                return
            budget -= bucket
            if _profiler.spans_enabled():
                if req.flow is None:
                    req.flow = _profiler.new_flow()
                _profiler.record_span("gen_queue_wait", req.t_queued,
                                      time.perf_counter(), "serve",
                                      flow=req.flow)
            # a prefill runs behind a collected step, never beside one in
            # flight: every execution is followed by its own tokens. The
            # gap this drains the pipeline for is a stall (``_clock``).
            if self._inflight is not None:
                self._stall = None if self._profiled or \
                    _profiler.xla_profiling() else \
                    (time.perf_counter(), self.engine.programs_built)
            self._catch_up()
            try:
                with _profiler.span("gen_prefill", "serve", flow=req.flow,
                                    bucket=bucket,
                                    prompt_len=int(req.prompt.size)):
                    tok, logits = self.engine.prefill(
                        req.prompt, slot, logits=req.temperature > 0.0)
            except Exception as exc:                        # noqa: BLE001
                self.cache.release(slot)
                req.handle._finish(ServeError(
                    "prefill failed: %r" % (exc,)))
                continue
            rng = np.random.default_rng(req.seed) \
                if req.seed is not None else None
            row = logits if req.temperature > 0.0 \
                else self._picked_row(tok, self.engine.vocab)
            tok = self._sample_token(row, req.temperature, rng)
            self.latency.ttft.record(monotonic() - req.t_submit)
            seq = _ActiveSeq(slot, req.handle, int(req.prompt.size),
                             req.max_new_tokens, req.eos_id,
                             req.temperature, req.seed, tok, req.flow)
            seq.rng = rng
            req.handle._put(tok)
            _profiler.incr_counter(self.name + "_tokens")
            if seq.generated >= seq.max_new_tokens or \
                    (seq.eos_id is not None and tok == seq.eos_id):
                # sequence finished at its first token: pages free now
                self._evict_prefill_only(seq)
                continue
            with self._lock:
                self._active.append(seq)
                _profiler.set_gauge(self.name + "_active_sequences",
                                    len(self._active))
            if budget <= 0:
                return

    # ---------------------------------------------------------- eviction
    def _evict(self, seq: _ActiveSeq, exc: Optional[BaseException]):
        """Remove a sequence from the running batch, ALWAYS freeing its
        pages (the injected-evict drill asserts no leak), then resolve
        its handle."""
        with _profiler.span("gen_evict", "serve", flow=seq.flow):
            with self._lock:
                if seq in self._active:
                    self._active.remove(seq)
                _profiler.set_gauge(self.name + "_active_sequences",
                                    len(self._active))
            fault_exc = self._release(seq)
            seq.handle._finish(exc if exc is not None else fault_exc)

    def _evict_prefill_only(self, seq: _ActiveSeq):
        """A sequence that finished at its prefill token never joined
        the active list — free its slot and resolve."""
        with _profiler.span("gen_evict", "serve", flow=seq.flow):
            seq.handle._finish(self._release(seq))

    def _release(self, seq: _ActiveSeq) -> Optional[BaseException]:
        """Free the sequence's slot; the injected-evict fault, if one
        fired, is returned for the handle (the pages are freed first)."""
        from .. import faults as _faults
        fault_exc = None
        try:
            if _faults.ARMED:
                _faults.fire("serve.evict", default_kind="raise")
        except _faults.FaultInjected as fe:
            fault_exc = ServeError(
                "injected fault at serve.evict while evicting slot %d "
                "(%s); pages were still freed" % (seq.slot, fe))
        finally:
            self.cache.release(seq.slot)
            _profiler.incr_counter(self.name + "_evicted")
        return fault_exc

    # ------------------------------------------------------------- close
    def close(self, drain: bool = True, timeout: Optional[float] = None):
        """Stop accepting requests. ``drain=True`` (default) decodes
        every waiting AND resident sequence to completion first;
        ``False`` fails waiting requests with :class:`ServerClosed` and
        cancels resident sequences at the next step (their pages free
        there). Idempotent: a second close only joins — it must not
        drop requests a prior ``close(drain=True)`` promised to serve.

        Submits racing the close lose cleanly: ``submit_generate``
        checks ``_closed`` under the same condition variable that sets
        it here, so a request issued mid-drain raises
        :class:`ServerClosed` immediately instead of enqueueing behind
        a scheduler that is about to exit."""
        with self._cond:
            already = self._closed
            self._closed = True
            if already:
                drain = True        # first close's promise stands
            if not drain:
                dropped = list(self._waiting)
                self._waiting.clear()
                for seq in self._active:
                    seq.handle._cancelled = True
            else:
                dropped = []
            self._cond.notify_all()
        for req in dropped:
            req.handle._finish(ServerClosed("server closed"))
        self._worker.join(timeout)
        if not self._worker.is_alive():
            # belt-and-braces: if anything slipped into the queue after
            # the scheduler exited (or the join raced an admit), fail it
            # legibly — a handle left in a dead server's queue would
            # hang its caller forever
            with self._cond:
                leftover = list(self._waiting)
                self._waiting.clear()
            for req in leftover:
                req.handle._finish(ServerClosed("server closed"))
        if self._metrics_finalizer is not None:
            self._metrics_finalizer()
            self._metrics = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close(drain=not any(exc))
        return False

    # ------------------------------------------------------------- stats
    def stats(self) -> Dict[str, Any]:
        """Decode-serving snapshot. Superset discipline: the counter
        keys shared with InferenceServer.stats() (requests / compiles /
        cache_hits / shed / deadline_expired) keep their meaning, and
        new keys only ADD — the schema regression test pins both."""
        with self._lock:
            active = len(self._active)
            waiting = len(self._waiting)
        led = self.cache.ledger
        return {
            "requests": _profiler.get_counter(self.name + "_requests"),
            "tokens": _profiler.get_counter(self.name + "_tokens"),
            "decode_steps": _profiler.get_counter(
                self.name + "_decode_steps"),
            # of those, the steps whose program read the cache with the
            # Pallas decode-attention kernel (float32, one device)
            "decode_attn_kernel_steps": _profiler.get_counter(
                self.name + "_decode_attn_kernel_steps"),
            # and those that fetched the logits: a resident sequence
            # sampled (temperature > 0); a greedy step fetches its tokens
            "decode_logits_fetched": _profiler.get_counter(
                self.name + "_decode_logits_fetched"),
            # and those dispatched while the step before was in flight
            "decode_steps_ahead": _profiler.get_counter(
                self.name + "_decode_steps_ahead"),
            "active_sequences": active,
            "waiting": waiting,
            "evicted": _profiler.get_counter(self.name + "_evicted"),
            "compiles": _profiler.get_counter(self.name + "_compile"),
            "cache_hits": _profiler.get_counter(self.name + "_cache_hit"),
            "shed": _profiler.get_counter(self.name + "_shed"),
            "deadline_expired": _profiler.get_counter(
                self.name + "_deadline_expired"),
            "executable_bound": self.engine.executable_bound(),
            "kv": {
                "slots_in_use": led.slots_in_use,
                "pages_in_use": led.pages_in_use,
                "total_pages": led.total_pages,
                "occupancy": round(led.occupancy(), 4),
                "max_slots": self.cache.max_slots,
                "page": self.cache.page,
                "int8": self.cache.int8,
                "hbm_bytes": self.cache.hbm_bytes(),
            },
            "buckets": {"prompt": list(self.engine.prompt_buckets),
                        "decode": list(self.engine.seq_buckets)},
            "ttft": self.latency.ttft.snapshot(),
            "tpot": self.latency.tpot.snapshot(),
        }
