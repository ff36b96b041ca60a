"""``mx.config`` — the environment-variable knob layer.

Reference: the ~30 ``MXNET_*`` env vars of ``docs/how_to/env_var.md:8-125``
backed by ``dmlc::Parameter`` reflection. Same surface here: typed,
documented knobs read from the environment with runtime override, each
wired to a real control point (not parity theater):

* ``MXNET_ENGINE_TYPE=NaiveEngine`` — synchronous dispatch: every
  imperative op blocks until its result is ready, serializing execution
  exactly like the reference's debug engine (env_var.md: the race-
  detection/debug mode, SURVEY §5.2). Default ``ThreadedEngine`` keeps
  XLA's async dispatch.
* ``MXNET_CPU_WORKER_NTHREADS`` — decode/augment worker threads of the
  record iterators (reference: same knob feeding the IO thread pool).
* ``MXNET_PREFETCH_BUFFER`` — batches buffered ahead by the record
  iterators (reference: iter_prefetcher.h depth).
* ``MXNET_EXEC_ENABLE_REMAT`` — rematerialize the fused train step's
  forward under ``jax.checkpoint``: trades recompute FLOPs for activation
  HBM (the TPU form of the reference's memory-saving exec knobs,
  MXNET_EXEC_ENABLE_INPLACE / bulk-exec family).
* ``MXNET_PROFILER_AUTOSTART`` — start the profiler at import
  (reference: same knob).
* ``MXNET_KVSTORE_HEARTBEAT_STALE_SECS`` — seconds without a heartbeat
  before a worker counts as dead (reference: ps-lite
  PS_HEARTBEAT_TIMEOUT feeding get_num_dead_node, SURVEY §5.3).
"""
from __future__ import annotations

import os
from typing import Any, Callable, Dict

__all__ = ["get", "set", "describe", "register", "KNOBS"]


class _Knob:
    def __init__(self, name: str, typ: Callable, default: Any, doc: str):
        self.name = name
        self.typ = typ
        self.default = default
        self.doc = doc


KNOBS: Dict[str, _Knob] = {}
_overrides: Dict[str, Any] = {}
_listeners: Dict[str, list] = {}


def register(name: str, typ, default, doc: str) -> None:
    KNOBS[name] = _Knob(name, typ, default, doc)


def on_change(name: str, fn: Callable[[Any], None]) -> None:
    """Call ``fn(new_value)`` whenever ``set``/``reset`` changes the knob —
    lets hot paths cache a knob as a module-level constant instead of
    re-reading the environment per call."""
    KNOBS[name]   # raise on unknown
    _listeners.setdefault(name, []).append(fn)


def _notify(name: str) -> None:
    for fn in _listeners.get(name, ()):
        fn(get(name))


def _parse_bool(v) -> bool:
    if isinstance(v, bool):
        return v
    return str(v).strip().lower() in ("1", "true", "yes", "on")


register("MXNET_ENGINE_TYPE", str, "ThreadedEngine",
         "NaiveEngine = synchronous op dispatch (debug/race detection); "
         "ThreadedEngine = XLA async dispatch")
register("MXNET_CPU_WORKER_NTHREADS", int, 4,
         "decode/augment worker threads in record iterators")
register("MXNET_PREFETCH_BUFFER", int, 4,
         "batches buffered ahead by record iterators")
register("MXNET_EXEC_ENABLE_REMAT", _parse_bool, False,
         "jax.checkpoint the fused train step's forward (less HBM, more "
         "FLOPs)")
register("MXNET_PROFILER_AUTOSTART", _parse_bool, False,
         "start mx.profiler at import")
register("MXNET_KVSTORE_HEARTBEAT_STALE_SECS", float, 20.0,
         "heartbeat staleness threshold for get_num_dead_node")
register("MXNET_KVSTORE_BIGARRAY_BOUND", int, 1000000,
         "elements per fused-allreduce chunk in the dist kvstore "
         "(reference: big-array server sharding, kvstore_dist.h:292)")
register("MXNET_USE_NATIVE_IO", _parse_bool, True,
         "use the C++ data path (libmxnative: RecordIO codec, jpeg/png "
         "decode, threaded augment pipeline); 0 = pure-Python/cv2 path")
register("MXNET_TPU_FUSED_TRAINER", _parse_bool, True,
         "gluon Trainer.step / Module.update: batch all parameter updates "
         "into one structure-cached, donated jitted program; 0 = eager "
         "per-param dispatch")
register("MXNET_TPU_SERVE", _parse_bool, True,
         "serve.InferenceServer: coalesce concurrent requests into "
         "bucket-padded micro-batches served by a finite executable set; "
         "0 = per-request eager forward in the caller thread (no "
         "batching, no bucketing — the debugging/bisection fallback)")
register("MXNET_TPU_SERVE_MAX_BATCH", int, 32,
         "serve: default micro-batch row bound (requests coalesced per "
         "dispatch; the largest batch bucket)")
register("MXNET_TPU_SERVE_MAX_DELAY_US", int, 2000,
         "serve: default batching window — how long the oldest queued "
         "request may wait for co-riders before the batch launches")
register("MXNET_TPU_SERVE_QUEUE_BOUND", int, 1024,
         "serve: default admission bound; submit() load-sheds (QueueFull) "
         "when this many requests are already queued")
register("MXNET_TPU_SERVE_KV_INT8", _parse_bool, False,
         "serve.GenerativeServer: store the decode KV cache as int8 with "
         "per-page f32 scales instead of f32 — the cache reservation "
         "shrinks ~4x (≈2x max resident sequences under a typical "
         "MXNET_TPU_ANALYZE_HBM_BUDGET once scales and slack are paid), "
         "at a documented logits tolerance (tests/test_serve_decode.py)")
register("MXNET_TPU_SERVE_MAX_SEQUENCES", int, 8,
         "serve.GenerativeServer: default max resident decode sequences "
         "(the KV cache's preallocated slot count; also the decode "
         "batch width). Overridden by the max_sequences argument")
register("MXNET_TPU_SERVE_PREFILL_TOKENS", int, 2048,
         "serve.GenerativeServer: prefill token budget per scheduler "
         "iteration — joins admitted between two decode steps may "
         "prefill at most this many (bucket-padded) prompt tokens, so "
         "a burst of long prompts cannot starve the running batch's "
         "inter-token latency")
register("MXNET_TPU_SERVE_DECODE_BUCKETS", str, "",
         "serve.GenerativeServer: explicit comma-separated decode "
         "sequence-length bucket ladder (e.g. '128,256,512'); empty = "
         "powers of two from the page size up to the model's max "
         "sequence length. Every bucket must be a multiple of the KV "
         "page size (the int8 per-page scale grid)")
register("MXNET_TPU_SERVE_KV_PAGE", int, 16,
         "serve.GenerativeServer: KV-cache page size in tokens — slot "
         "capacity is allocated and freed page-at-a-time, and int8 mode "
         "keeps one quantization scale per page. Must divide every "
         "decode bucket")
register("MXNET_TPU_FLEET", _parse_bool, False,
         "fleet.Gateway: opt-in switch for the multi-replica serving "
         "fleet (mxnet_tpu.fleet). Off = the gateway refuses to start "
         "and the package is never imported by the serve path — "
         "spawning replica subprocesses is an explicit deployment "
         "decision, not a framework default")
register("MXNET_TPU_FLEET_REPLICAS", int, 2,
         "fleet: default replica-world size when Gateway(replicas=) / "
         "python -m mxnet_tpu.fleet serve --replicas is not given — "
         "the env-discovery path for launcher-provisioned worlds")
register("MXNET_TPU_FLEET_STATS_PERIOD", float, 0.5,
         "fleet.Gateway: heartbeat cadence in seconds — each tick "
         "polls every replica's stats() snapshot (queue depth + KV "
         "occupancy feed least-loaded routing) and doubles as the "
         "liveness probe (connection REFUSED marks the replica dead; "
         "a timeout is ambiguous and never kills, the ProbeRing rule)")
register("MXNET_TPU_FLEET_QUEUE_BOUND", int, 256,
         "fleet.Gateway: admission bound on gateway-resident in-flight "
         "requests; beyond it submits shed with QueueFull instead of "
         "growing an unbounded backlog (same contract as the serve "
         "queue bound, one level up)")
register("MXNET_TPU_FLEET_MAX_RESPAWNS", int, 16,
         "fleet: per-replica supervisor respawn budget — a replica "
         "that dies more than this many times is marked failed and "
         "left down (the elastic bounded-restart discipline; backoff "
         "reuses MXNET_TPU_ELASTIC_BACKOFF/_MAX between attempts)")
register("MXNET_TPU_FLEET_SPAWN_TIMEOUT", float, 240.0,
         "fleet: seconds a freshly spawned replica may take to answer "
         "its first PING (model build + bind + compile); past "
         "it the spawn is scored failed and retried under the respawn "
         "budget (PhaseGuard discipline — no unbounded waits)")
def _parse_analyze_mode(v) -> str:
    s = str(v).strip().lower()
    if s in ("", "0", "off", "false", "no", "none"):
        return "off"
    if s in ("warn", "warning", "1", "on", "true", "yes"):
        return "warn"
    if s == "strict":
        return "strict"
    raise ValueError(
        "MXNET_TPU_ANALYZE must be off|warn|strict, got %r" % (v,))


register("MXNET_TPU_ANALYZE", _parse_analyze_mode, "off",
         "run mxnet_tpu.analysis graph passes at Executor/Module bind: "
         "off = analyzer never imported (zero cost), warn = log "
         "WARNING+ findings, strict = raise MXNetError on ERROR "
         "findings before any compile")
register("MXNET_TPU_ANALYZE_HBM_BUDGET", str, "",
         "per-device memory budget for the analysis hbm-budget pass "
         "(bytes, K/M/G/T suffixes: '16G'); when the static peak "
         "estimate (bound buffers + activation high-water) exceeds it "
         "the bind gets an ERROR finding naming the offending arrays — "
         "rejected before any compile under MXNET_TPU_ANALYZE=strict. "
         "Empty = no budget")
register("MXNET_TPU_ANALYZE_HBM_GBPS", float, 0.0,
         "HBM bandwidth (GB/s) for the analysis roofline balance point; "
         "0 = auto-detect from the TPU device_kind table (v2-v6); set "
         "explicitly on unknown devices and in CPU tests")
register("MXNET_TPU_ANALYZE_ICI_GBPS", float, 0.0,
         "per-link ICI bandwidth (GB/s) for the analysis comm cost "
         "model's time estimates; 0 = device_kind table, 50 GB/s for "
         "unknown devices")
register("MXNET_TPU_ASYNC_WINDOW", int, 2,
         "fit(): max train steps dispatched ahead of device completion "
         "(sliding-window sync caps in-flight work); 0 = fully "
         "synchronous per-batch loop (the kill switch — exactly the "
         "pre-async behavior)")
register("MXNET_TPU_DEVICE_PREFETCH", int, 2,
         "fit(): batches device-placed ahead of the step consuming them "
         "(PrefetchingIter device stage, double-buffered H2D overlap); "
         "0 = place each batch synchronously on the critical path")
register("MXNET_TPU_DATA_WORKERS", int, 2,
         "mx.data.DataLoader: default worker PROCESSES decoding disjoint "
         "shard ranges in parallel (overridden by the num_workers "
         "argument); 0 = decode inline in the consumer thread")
register("MXNET_TPU_DATA_QUEUE_DEPTH", int, 4,
         "mx.data.DataLoader: decoded batches buffered per worker "
         "process (the backpressure bound — a stalled consumer parks "
         "the workers instead of buffering the epoch in RAM)")
register("MXNET_TPU_DATA_MP", _parse_bool, True,
         "mx.data.DataLoader: multi-process decode kill switch — 0 "
         "forces the inline single-thread path regardless of "
         "num_workers (same stream order, the bisection fallback)")
register("MXNET_TPU_DEVICE_METRICS", _parse_bool, True,
         "EvalMetric.update_device: accumulate (sum, count) as device "
         "reductions chained after the step, host sync deferred to "
         "get()/log boundaries; 0 = per-batch asnumpy host path")
register("MXNET_TPU_CKPT_ASYNC", _parse_bool, True,
         "mx.checkpoint: hand checkpoint serialization (device fetch, "
         "checksums, npz encode, fsync) to the bounded background writer "
         "thread so the step loop resumes after snapshot capture; 0 = "
         "synchronous saves that block the caller for the full write")
register("MXNET_TPU_CKPT_KEEP", int, 5,
         "mx.checkpoint: retention — keep the newest N valid checkpoints "
         "after each save (keep-every-K survivors and the newest valid "
         "checkpoint are always kept); 0 = keep everything")
register("MXNET_TPU_CKPT_WRITE_RETRIES", int, 3,
         "mx.checkpoint: bounded retry of a failed checkpoint write on "
         "TRANSIENT IO errors (EIO/ENOSPC/EINTR) with exponential "
         "backoff before the failure is recorded and re-raised at "
         "close; each retry counts ckpt_write_retry. 0 = fail on the "
         "first error")
register("MXNET_TPU_FAULTS", str, "",
         "deterministic fault injection: comma list of "
         "<site>@<nth>[:kind] specs fired at named injection points "
         "(ckpt.arrays_write, ckpt.before_rename, ckpt.read_manifest, "
         "fit.batch, serve.submit, ...; kinds eio/enospc/eintr/raise/"
         "sigterm/sigkill/bitflip/truncate — see "
         "docs/architecture/elastic.md). Parsed once at import by "
         "mxnet_tpu.faults; zero-cost when empty. NEVER set in "
         "production")
register("MXNET_TPU_DIST_TIMEOUT", float, 120.0,
         "pod bootstrap: seconds each process waits for the whole pod to "
         "assemble (the roll-call deadline AND jax.distributed's "
         "initialization_timeout). A missing peer fails the bootstrap "
         "with an error naming the absent rank — never a hang")
register("MXNET_TPU_DIST_RETRIES", int, 1,
         "pod bootstrap: re-attempts of the distributed rendezvous after "
         "a timeout (a slow-starting peer gets one more window) before "
         "the error propagates; 0 = fail on the first timeout")
register("MXNET_TPU_HEARTBEAT_PERIOD", float, 5.0,
         "liveness heartbeat publish period in seconds "
         "(dist.heartbeat_start; the staleness deadline is "
         "MXNET_KVSTORE_HEARTBEAT_STALE_SECS on the READER's clock)")
register("MXNET_TPU_ELASTIC_STALL_SECS", float, 0.0,
         "coordinated pod: local stall watchdog — when > 0 and the "
         "training child's progress file stops advancing for this many "
         "seconds, the coordinator requests a POD-WIDE restart (drain + "
         "re-rendezvous; bulk-synchronous training stalls symmetrically, "
         "so one host's wedged child stalls every host — restarting the "
         "pod, not evicting a host, is the only sound response when "
         "every supervisor is still alive). 0 = disabled (long compiles "
         "and first-batch warmup must not trip it)")
register("MXNET_TPU_ELASTIC_DRAIN_GRACE", float, 20.0,
         "coordinated pod drain: seconds between the SIGTERM preemption "
         "notice and the SIGKILL escalation for a child wedged in a "
         "collective whose peer died")
register("MXNET_TPU_CKPT_POD_TIMEOUT", float, 120.0,
         "process-local checkpoint: seconds rank 0 waits for every "
         "host's shard record before the manifest commit (and peers "
         "wait for the commit) — a host dying mid-save aborts the save "
         "as a unit instead of committing a partial checkpoint")
register("MXNET_TPU_KV_RETRIES", int, 2,
         "coordination KV (dist.kv_set/kv_get): bounded re-attempts of a "
         "flaking KV operation (injected via the dist.kv fault site, or "
         "a real transient error) before it propagates; each retry "
         "counts dist_kv_retry. 0 = fail on the first error")
register("MXNET_TPU_PROBE_TIMEOUT", float, 2.0,
         "pod probe ring: per-probe TCP connect/handshake timeout in "
         "seconds (peer liveness adjudication when the control plane is "
         "unreachable; docs/architecture/elastic.md leader fail-over)")
register("MXNET_TPU_PROBE_ATTEMPTS", int, 3,
         "pod probe ring: probes per peer before its status is final — "
         "a single dropped SYN must not misjudge a live host; any "
         "'live' answer wins immediately")
register("MXNET_TPU_FAILOVER_PORT", int, 0,
         "pod control plane: fixed TCP port THIS host would re-host the "
         "coordination KV service on if elected leader (published in "
         "every generation's membership record); 0 = probe a fresh free "
         "port per generation")
register("MXNET_TPU_ELASTIC_MAX_RESTARTS", int, 10,
         "mx.elastic supervisor: restarts allowed before giving up and "
         "returning the child's exit status (exit 143 and crashes both "
         "count as preemptions)")
register("MXNET_TPU_ELASTIC_BACKOFF", float, 1.0,
         "mx.elastic supervisor: base seconds of the exponential "
         "restart backoff (doubles per consecutive restart, plus up to "
         "25 percent jitter)")
register("MXNET_TPU_ELASTIC_BACKOFF_MAX", float, 60.0,
         "mx.elastic supervisor: backoff ceiling in seconds")
register("MXNET_TPU_OBS", _parse_bool, False,
         "mx.obs: record structured spans (per-thread lanes + chrome-trace "
         "flow events linking one batch across prefetch/train/metric/"
         "checkpoint/serve threads) into the profiler event buffer even "
         "while the profiler state is 'stop'; 0 = span() is a shared "
         "no-op (zero allocations — counter-asserted by tests/test_obs.py)")
register("MXNET_TPU_OBS_METRICS_PORT", int, -1,
         "mx.obs: HTTP /metrics exposition (Prometheus text format) "
         "auto-started by serve.InferenceServer: -1 = off, 0 = ephemeral "
         "port (read it back from server.metrics_port), >0 = fixed port")
register("MXNET_TPU_OBS_PEAK_FLOPS", float, 0.0,
         "mx.obs: override the PER-DEVICE peak dense FLOP/s used for "
         "the obs_mfu gauge — a mesh-bound module's denominator is "
         "this times the mesh's device count (0 = auto-detect by TPU "
         "device_kind; set explicitly on unknown devices or in tests)")
register("MXNET_TPU_OBS_BLACKBOX", str, "",
         "mx.obs flight recorder: directory the bounded in-memory event "
         "ring (span closes, counter deltas, fault fires, pod "
         "transitions, checkpoint commit phases) is flushed to as "
         "blackbox-p<rank>.jsonl — periodically and at every terminal "
         "moment (fault fire, SIGTERM/143, NANCHECK abort, watchdog "
         "stall), so a killed host still leaves its last window on "
         "disk. Merge with `python -m mxnet_tpu.obs blackbox <dir>`. "
         "Empty = off (the recorder module is never imported)")
register("MXNET_TPU_OBS_BLACKBOX_RING", int, 512,
         "mx.obs flight recorder: events kept in the in-memory ring "
         "(each flush rewrites the file with exactly this window, so "
         "the on-disk artifact stays bounded at any run length)")
register("MXNET_TPU_OBS_BLACKBOX_FLUSH_SECS", float, 5.0,
         "mx.obs flight recorder: heartbeat flush period in seconds — "
         "the guarantee that a SIGKILL'd host still leaves a window no "
         "older than this on disk; 0 = event-driven flushes only")
register("MXNET_TPU_OBS_STRAGGLER_RATIO", float, 2.0,
         "pod straggler detection: flag a rank when the fastest rank's "
         "local work rate exceeds its by more than this factor "
         "(per-rank step windows published to the coordination KV at "
         "epoch log boundaries — zero extra per-step host syncs; the "
         "leader aggregates into report()'s 'pod' block, the "
         "obs_straggler counter and per-rank /metrics gauges). "
         "0 = disabled (the straggler module is never imported)")


def _parse_nancheck(v) -> str:
    s = str(v).strip().lower()
    if s in ("", "0", "off", "false", "no", "none"):
        return "off"
    if s in ("warn", "warning", "1", "on", "true", "yes"):
        return "warn"
    if s == "abort":
        return "abort"
    raise ValueError(
        "MXNET_TPU_NANCHECK must be off|warn|abort, got %r" % (v,))


register("MXNET_TPU_NANCHECK", _parse_nancheck, "off",
         "non-finite step guard: chain a device-side isfinite reduction "
         "onto every fused train step (zero host syncs — the flag is "
         "fetched at the epoch log boundary, same place as the metric "
         "sync) and count loop_nonfinite when any output went "
         "NaN/Inf. warn = log naming the first non-finite output, "
         "abort = raise MXNetError there; off = nothing is chained "
         "(zero cost)")


def _parse_lockcheck(v) -> str:
    s = str(v).strip().lower()
    if s in ("", "0", "off", "false", "no", "none"):
        return "off"
    if s in ("warn", "warning", "1", "on", "true", "yes"):
        return "warn"
    if s == "abort":
        return "abort"
    raise ValueError(
        "MXNET_TPU_LOCKCHECK must be off|warn|abort, got %r" % (v,))


register("MXNET_TPU_LOCKCHECK", _parse_lockcheck, "off",
         "runtime lock witness: wrap locks created through the "
         "mx.lockcheck funnels (serve scheduler, checkpoint writer, "
         "obs, pod KV, ...) to record actual acquisition order and "
         "flag the first observed lock-order inversion "
         "(lockcheck_inversion) and any device sync under a held lock "
         "(lockcheck_held_sync). warn = log both chains, abort = raise "
         "MXNetError before the inversion's blocking acquire; off = "
         "plain threading primitives, wrapper never constructed "
         "(one module-bool per lock creation)")


def _parse_remat(v) -> str:
    s = str(v).strip()
    low = s.lower()
    if low in ("", "0", "off", "false", "no", "none"):
        return "off"
    if low == "auto":
        return "auto"
    return s   # a jax.checkpoint_policies name, validated at use


register("MXNET_TPU_REMAT", _parse_remat, "off",
         "applied rematerialization for the fused train step: off = "
         "save all activations, auto = apply the policy the analysis "
         "remat-opportunity pass suggests for this graph "
         "(Report.extras['remat']), any other value = a "
         "jax.checkpoint_policies name applied as-is (e.g. "
         "nothing_saveable, dots_with_no_batch_dims_saveable)")
def _parse_tune(v) -> str:
    s = str(v).strip().lower()
    if s in ("", "0", "off", "false", "no", "none"):
        return "off"
    if s in ("auto", "on", "true", "yes", "1"):
        return "auto"
    if s == "static":
        return "static"
    raise ValueError(
        "MXNET_TPU_TUNE must be off|auto|static, got %r" % (v,))


register("MXNET_TPU_TUNE", _parse_tune, "off",
         "fit(): self-tuning performance search (mxnet_tpu.tune) — "
         "auto = load the stored TunedConfig for this program "
         "fingerprint or run the full static-prune + probe search and "
         "apply the winner's knobs before bind; static = static "
         "pruning/ranking only, no probe subprocesses (deterministic); "
         "off = the tune package is never imported (zero cost)")
register("MXNET_TPU_TUNE_PROBE_SECS", float, 120.0,
         "tune.search: per-probe subprocess deadline in seconds "
         "(PhaseGuard discipline — a timed-out probe is scored failed "
         "and the search keeps its partial results)")
register("MXNET_TPU_TUNE_PROBE_STEPS", int, 8,
         "tune.search: measured steps per probe run (after the 2 "
         "obs-warmup steps that absorb the compile)")
register("MXNET_TPU_TUNE_MAX_PROBES", int, 4,
         "tune.search: empirical probe budget — statically-ranked "
         "candidates probed per search (the default config is always "
         "probed in addition); 0 = static-only ranking")
register("MXNET_TPU_TUNE_STORE", str, "",
         "tune: TunedConfig store directory, where a restart finds "
         "the tuned knobs. Empty = no persistence")
register("MXNET_TPU_LAYERNORM_TWO_PASS", _parse_bool, False,
         "LayerNorm: two-pass E[(x-mean)^2] variance instead of the fused "
         "one-pass E[x^2]-E[x]^2 form — restores precision for "
         "large-offset activations at one extra read of x (takes effect "
         "on the next trace; already-compiled programs keep their form)")


def get(name: str):
    """Current value: runtime override > environment > default."""
    knob = KNOBS[name]
    if name in _overrides:
        return _overrides[name]
    raw = os.environ.get(name)
    if raw is None:
        return knob.default
    return knob.typ(raw)


def set(name: str, value) -> None:     # noqa: A001 (reference-style name)
    """Runtime override (takes precedence over the environment)."""
    knob = KNOBS[name]
    _overrides[name] = knob.typ(value)
    _notify(name)


def reset(name: str) -> None:
    """Drop a runtime override, reverting to environment/default."""
    _overrides.pop(name, None)
    _notify(name)


_NO_OVERRIDE = object()


def snapshot_overrides(names) -> Dict[str, Any]:
    """Capture the runtime-override state of ``names`` for a later
    :func:`restore_overrides` — the scoped-set discipline callers like
    ``fit(tune=...)`` use so their knob winners do not outlive the
    call. A name with no current override is recorded as such (its
    restore is :func:`reset`, not a re-``set`` of the computed value,
    so environment changes in between still show through)."""
    return {str(n): _overrides.get(n, _NO_OVERRIDE) for n in names}


def restore_overrides(snapshot: Dict[str, Any]) -> None:
    """Undo every :func:`set` made since the matching
    :func:`snapshot_overrides`: re-instate the old override, or drop
    the knob back to environment/default."""
    for name, value in snapshot.items():
        if value is _NO_OVERRIDE:
            reset(name)
        else:
            set(name, value)


def describe() -> str:
    """Human-readable table of every knob, its value and source
    (reference: env_var.md as a runtime query)."""
    lines = []
    for name, knob in sorted(KNOBS.items()):
        src = "override" if name in _overrides else \
            ("env" if name in os.environ else "default")
        lines.append("%-36s %-22r (%s)  %s"
                     % (name, get(name), src, knob.doc))
    return "\n".join(lines)


# where JAX's persistent compile cache lives when the environment does not
# place it: a fixed path beside the package (the path is part of the cache
# key, so a directory that moves between runs never hits)
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def _apply_import_knobs() -> None:
    """Settings that act once at package import.

    The one place the program chooses a compile-cache directory: JAX's
    own ``JAX_COMPILATION_CACHE_DIR`` wins when it is in the environment
    (nothing here touches the setting then); otherwise the cache goes to
    :data:`DEFAULT_COMPILE_CACHE_DIR`."""
    import jax
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILE_CACHE_DIR)
    # cache every program: a step is many small compiles, not one big one
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if get("MXNET_PROFILER_AUTOSTART"):
        from . import profiler
        profiler.set_state("run")
