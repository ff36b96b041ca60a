"""Multi-host runtime: process bootstrap + cross-process collectives.

Reference: the ps-lite runtime (SURVEY.md §2.12) — ``src/kvstore/
kvstore_dist.h:50-320`` workers push/pull against server processes spawned by
``tools/launch.py``, wired together by DMLC_* environment variables
(``DMLC_PS_ROOT_URI``, ``DMLC_PS_ROOT_PORT``, ``DMLC_NUM_WORKER``,
``DMLC_WORKER_ID``, ``DMLC_ROLE``).

TPU design: there are no server processes. Every process is a worker running
the same SPMD program; ``jax.distributed.initialize`` is the rendezvous
(scheduler) and cross-host reduction is an XLA collective over a one-
device-per-process mesh — DCN/gloo between hosts, ICI within a slice. The
launcher keeps the reference's env protocol so `tools/launch.py -n N cmd`
works unchanged.

This module is the only place that talks to ``jax.distributed``; kvstore's
``dist_*`` types and ``gluon.Trainer`` build on it.
"""
from __future__ import annotations

import os
from functools import partial
from typing import Callable, Dict, Iterable, List, Optional

__all__ = ["initialize", "is_initialized", "cluster_env", "rank",
           "num_workers", "allreduce_sum", "broadcast", "barrier",
           "heartbeat_start", "heartbeat_stop", "num_dead_nodes",
           "dead_ranks", "reset_liveness", "kv_set", "kv_get",
           "free_port", "BootstrapTimeout", "sharding_island",
           "PodKVServer", "PodKVClient", "ProbeRing", "probe_peer",
           "elect_leader", "set_kv_backend", "kv_backend_active"]


def sharding_island():
    """Canonical layout claims of the multi-host data plane (audited by
    ``analysis.sharding_passes.check_islands``): the cross-host gradient
    reduction runs over the SAME ``(data, fsdp)`` axes the batch shards
    over, and parameter residency follows the unified FSDP claim — drawn
    from the one SpecLayout so the audit reports zero cross-island
    disagreements."""
    from .layout import island_specs
    return "dist", island_specs("dist")


def free_port() -> int:
    """Probe a free TCP port (bind 0, read it back, release). The usual
    TOCTOU caveat applies — the pod rendezvous publishes the port and
    rebinds it moments later; ONE shared helper so any future
    hardening (retry, port ranges) lands everywhere at once.
    (tools/launch.py keeps a private copy: the launcher is deliberately
    stdlib-only and runs before the package is importable.)"""
    import socket
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port

_INITIALIZED = False
_COMM = None          # (mesh, local_device) cache
_FN_CACHE = {}


def cluster_env() -> Optional[dict]:
    """Parse the launcher's DMLC_* env protocol; None when not under a
    launcher (reference: ps-lite postoffice reads the same variables)."""
    uri = os.environ.get("DMLC_PS_ROOT_URI")
    port = os.environ.get("DMLC_PS_ROOT_PORT")
    n = os.environ.get("DMLC_NUM_WORKER")
    wid = os.environ.get("DMLC_WORKER_ID")
    if uri is None or port is None or n is None or wid is None:
        return None
    return {"coordinator": "%s:%s" % (uri, port),
            "num_workers": int(n), "rank": int(wid)}


def is_initialized() -> bool:
    return _INITIALIZED


def coordination_active() -> bool:
    """True when a jax.distributed coordination client exists (a pure state
    probe — never initializes a backend)."""
    try:
        from jax._src import distributed as _jdist
        return getattr(_jdist.global_state, "client", None) is not None
    except Exception:
        return False


class BootstrapTimeout(RuntimeError):
    """The pod never fully assembled within the bootstrap deadline. The
    message names the absent rank(s) when the roll-call could tell."""


def _rollcall(coordinator_address: str, n: int, process_id: int,
              deadline: float) -> None:
    """Pre-rendezvous liveness check on the coordinator port, BEFORE
    jax.distributed binds it: every rank proves it is up, so a missing
    peer produces an error NAMING THE ABSENT RANK on every present rank
    instead of N-1 opaque deadline errors (or, on older stacks, a hang).

    Protocol (rank 0 listens; peers connect-with-retry):
      peer -> "mxhb <rank>\\n";  rank 0 -> "ok\\n" once ALL ranks arrived,
      or "missing <r,...>\\n" + close at the deadline.
    Rank 0 releases the port before returning, then jax.distributed's
    coordination service binds it; peers' grpc connects retry until the
    service is up (bounded by initialization_timeout)."""
    import socket
    import time
    host, _, port_s = coordinator_address.rpartition(":")
    port = int(port_s)
    t_end = time.monotonic() + deadline
    if process_id == 0:
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        conns = {}
        try:
            try:
                srv.bind(("", port))
            except OSError:
                # the port is already owned (a prior half-shutdown
                # coordination service): skip the roll-call, the jax
                # rendezvous deadline is the backstop
                srv.close()
                return
            srv.listen(n)
            while len(conns) < n - 1:
                left = t_end - time.monotonic()
                if left <= 0:
                    break
                srv.settimeout(min(left, 1.0))
                try:
                    conn, _addr = srv.accept()
                except socket.timeout:
                    continue
                try:
                    conn.settimeout(min(max(left, 0.1), 5.0))
                    line = conn.makefile("r").readline().strip()
                    if line.startswith("mxhb "):
                        conns[int(line.split()[1])] = conn
                    else:
                        conn.close()
                except (OSError, ValueError, IndexError):
                    conn.close()
            missing = sorted(set(range(1, n)) - set(conns))
            reply = b"ok\n" if not missing else \
                ("missing %s\n" % ",".join(map(str, missing))).encode()
            for conn in conns.values():
                try:
                    conn.sendall(reply)
                except OSError:
                    pass
            if missing:
                raise BootstrapTimeout(
                    "pod bootstrap timed out after %.0fs: rank(s) %s of "
                    "world %d never connected to the coordinator (%s) — "
                    "check that every host launched its worker"
                    % (deadline, ",".join(map(str, missing)), n,
                       coordinator_address))
        finally:
            for conn in conns.values():
                try:
                    conn.close()
                except OSError:
                    pass
            srv.close()
        return
    # peers: connect with retry until the deadline
    while True:
        left = t_end - time.monotonic()
        if left <= 0:
            raise BootstrapTimeout(
                "pod bootstrap timed out after %.0fs: rank %d could not "
                "reach the coordinator (rank 0) at %s — is it up?"
                % (deadline, process_id, coordinator_address))
        try:
            conn = socket.create_connection((host or "127.0.0.1", port),
                                            timeout=min(left, 2.0))
        except OSError:
            time.sleep(min(left, 0.2))
            continue
        try:
            conn.settimeout(max(t_end - time.monotonic(), 0.1))
            conn.sendall(("mxhb %d\n" % process_id).encode())
            line = conn.makefile("r").readline().strip()
        except OSError:
            line = ""
        finally:
            conn.close()
        if line.startswith("missing"):
            raise BootstrapTimeout(
                "pod bootstrap failed: coordinator reports rank(s) %s of "
                "world %d never connected" % (line.split(None, 1)[1], n))
        # "ok" -> proceed; anything else (EOF, grpc noise) means rank 0 is
        # already past roll-call and the coordination service owns the
        # port — jax.distributed.initialize below is the backstop
        return


def initialize(coordinator_address=None, num_processes=None, process_id=None,
               timeout: Optional[float] = None,
               retries: Optional[int] = None,
               rollcall: bool = True):
    """Join the cluster (idempotent). Arguments default to the DMLC_* env.

    Must run before any backend is initialized in this process — the global
    device view and the gloo/DCN collectives are fixed at backend creation.

    Bounded bootstrap: the rendezvous can never hang the pod forever — a
    roll-call on the coordinator port first proves every rank is up
    (failing with :class:`BootstrapTimeout` naming the absent rank), and
    ``jax.distributed.initialize`` itself runs under the same
    ``MXNET_TPU_DIST_TIMEOUT`` deadline with ``MXNET_TPU_DIST_RETRIES``
    bounded re-attempts for slow-but-alive peers.
    """
    global _INITIALIZED
    if _INITIALIZED:
        return
    try:
        from jax._src import distributed as _jdist
        if getattr(_jdist.global_state, "client", None) is not None:
            _INITIALIZED = True   # user already ran jax.distributed.initialize
            return
    except Exception:
        pass
    env = cluster_env()
    if coordinator_address is None and env is not None:
        coordinator_address = env["coordinator"]
        num_processes = env["num_workers"]
        process_id = env["rank"]
    if coordinator_address is None:
        raise RuntimeError(
            "distributed init needs a coordinator: run under tools/launch.py "
            "(sets DMLC_PS_ROOT_URI/PORT, DMLC_NUM_WORKER, DMLC_WORKER_ID) "
            "or pass coordinator_address/num_processes/process_id")
    from .. import config as _config
    if timeout is None:
        timeout = float(_config.get("MXNET_TPU_DIST_TIMEOUT"))
    if retries is None:
        retries = max(0, int(_config.get("MXNET_TPU_DIST_RETRIES")))
    import jax
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            "a jax backend is already initialized; distributed rendezvous "
            "must happen first (create the dist kvstore before touching "
            "devices)")
    try:
        # multi-process CPU collectives ride gloo; TPU backends ignore this
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:
        pass
    n = num_processes or 1
    last_exc: Optional[BaseException] = None
    for attempt in range(retries + 1):
        try:
            # the roll-call is INSIDE the retried window: "a
            # slow-starting peer gets one more window" must cover the
            # stage a slow peer actually fails at
            if rollcall and n > 1:
                _rollcall(coordinator_address, n, process_id or 0,
                          timeout)
            try:
                jax.distributed.initialize(
                    coordinator_address=coordinator_address,
                    num_processes=num_processes, process_id=process_id,
                    initialization_timeout=max(1, int(timeout)))
            except TypeError:     # older jaxlib: no timeout kwarg
                jax.distributed.initialize(
                    coordinator_address=coordinator_address,
                    num_processes=num_processes, process_id=process_id)
            _INITIALIZED = True
            return
        except Exception as exc:                           # noqa: BLE001
            last_exc = exc
            try:
                jax.distributed.shutdown()
            except Exception:                              # noqa: BLE001
                pass
            if attempt < retries:
                import logging
                logging.getLogger(__name__).warning(
                    "distributed rendezvous attempt %d/%d failed (%s); "
                    "retrying", attempt + 1, retries + 1, exc)
    raise BootstrapTimeout(
        "distributed rendezvous failed after %d attempt(s) x %.0fs "
        "(rank %s of %s via %s): %s — a peer is down or unreachable"
        % (retries + 1, timeout, process_id, num_processes,
           coordinator_address, last_exc)) from last_exc


def rank() -> int:
    # authoritative: the coordination-service state (jax.process_index()
    # reads the *default backend*, which may be a single-chip view)
    try:
        from jax._src import distributed as _jdist
        if getattr(_jdist.global_state, "client", None) is not None:
            return _jdist.global_state.process_id or 0
    except Exception:
        pass
    import jax
    try:
        return jax.process_index()
    except Exception:
        return 0


def num_workers() -> int:
    try:
        from jax._src import distributed as _jdist
        if getattr(_jdist.global_state, "client", None) is not None:
            return _jdist.global_state.num_processes or 1
    except Exception:
        pass
    import jax
    try:
        return jax.process_count()
    except Exception:
        return 1


def _comm():
    """One-device-per-process mesh for cross-process reductions.

    Prefers the default backend (a TPU slice spans all processes natively);
    falls back to the CPU backend, whose gloo collectives span hosts when
    ``initialize`` ran first.
    """
    global _COMM
    if _COMM is not None:
        return _COMM
    import numpy as np
    import jax
    from jax.sharding import Mesh

    n = num_workers()

    def pick(devs):
        by_proc = {}
        for d in devs:
            by_proc.setdefault(d.process_index, d)
        if len(by_proc) < n:
            return None
        return [by_proc[i] for i in range(n)]

    devs = pick(jax.devices())
    if devs is None:
        devs = pick(jax.devices("cpu"))
    if devs is None:
        raise RuntimeError(
            "no backend spans all %d processes — was dist.initialize() "
            "called before the first device access?" % n)
    mesh = Mesh(np.array(devs), ("proc",))
    local = devs[rank()]
    _COMM = (mesh, local)
    return _COMM


def _psum_fn(shape, dtype):
    key = ("psum", shape, str(dtype))
    fn = _FN_CACHE.get(key)
    if fn is None:
        import jax
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        mesh, _ = _comm()
        shard = partial(shard_map, mesh=mesh, in_specs=P("proc"),
                        out_specs=P())
        fn = jax.jit(shard(lambda s: jax.lax.psum(s[0], "proc")))
        _FN_CACHE[key] = fn
    return fn


def allreduce_sum(x):
    """Sum an identically-shaped per-process array across all processes;
    returns the reduction as a local jax array (replicated semantics)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = num_workers()
    if n == 1:
        return jnp.asarray(x)
    mesh, local = _comm()
    xl = jax.device_put(jnp.asarray(x), local)
    garr = jax.make_array_from_single_device_arrays(
        (n,) + xl.shape, NamedSharding(mesh, P("proc")), [xl[None]])
    out = _psum_fn(xl.shape, xl.dtype)(garr)
    return out.addressable_data(0)


def broadcast(x, root: int = 0):
    """Every process gets ``root``'s value (psum of one-hot contribution)."""
    import jax.numpy as jnp
    if num_workers() == 1:
        return jnp.asarray(x)
    contrib = jnp.asarray(x) if rank() == root else jnp.zeros_like(
        jnp.asarray(x))
    return allreduce_sum(contrib)


def barrier():
    """Block until every process reaches this point."""
    import jax
    if num_workers() == 1:
        return
    jax.block_until_ready(allreduce_sum(jax.numpy.zeros((1,))))


# ------------------------------------------------------- failure detection


def _client():
    import jax._src.distributed as _jdist
    return getattr(_jdist.global_state, "client", None)


_hb_started = False
_hb_stop = None           # threading.Event for the publisher thread
_hb_thread = None
# reader-side observations: rank -> (last counter, local time first seen)
_hb_seen = {}


def heartbeat_start(period: Optional[float] = None,
                    progress_fn: Optional[Callable[[], object]] = None,
                    as_rank: Optional[int] = None) -> bool:
    """Publish this worker's liveness to the coordinator's key-value store
    every ``period`` seconds (reference: ps-lite worker heartbeats to the
    scheduler, feeding kvstore.h:287 get_num_dead_node). The payload is a
    monotonically increasing beat COUNTER, not a wall-clock timestamp —
    staleness is judged on the reader's own clock, so cross-host clock
    skew cannot fake deaths. Idempotent; returns False when no
    coordination client exists (single process).

    ``period`` defaults to the ``MXNET_TPU_HEARTBEAT_PERIOD`` knob.

    With ``progress_fn``, the beat is PROGRESS-COUPLED: the counter only
    advances when ``progress_fn()`` returns a different token than the
    last tick — the hook for tying a worker's liveness to actual work
    progress (a file mtime, a step counter). A publisher that stops
    progressing stops advancing, and peers' :func:`num_dead_nodes`
    counts it dead once the staleness window passes. NB: couple with
    care in bulk-synchronous pods — one wedged member stalls EVERY
    member's progress, so progress-coupled beats there make the whole
    pod look dead at once (the pod coordinator publishes a plain beat
    for exactly this reason).

    ``as_rank`` names the heartbeat key explicitly (the pod coordinator
    publishes under its ORIGINAL pod rank across control-plane
    re-hostings); default is this process's coordination rank."""
    global _hb_started, _hb_stop, _hb_thread
    import logging
    import threading
    backend = _kv()
    if backend is None:
        return False
    if _hb_started:
        return True
    if period is None:
        from .. import config as _config
        period = float(_config.get("MXNET_TPU_HEARTBEAT_PERIOD"))
    _hb_started = True
    _hb_stop = threading.Event()

    me = "mxnet_hb/%d" % (rank() if as_rank is None else int(as_rank))
    stop = _hb_stop

    def beat():
        n = 0
        warned = False
        last_token = object()       # sentinel: first tick always beats
        while not stop.is_set():
            if progress_fn is None:
                n += 1
            else:
                try:
                    token = progress_fn()
                except Exception:                          # noqa: BLE001
                    token = last_token     # unreadable progress = stalled
                if token != last_token or n == 0:
                    last_token = token
                    n += 1
            try:
                # the captured backend, not kv_set: the fault harness's
                # dist.kv site must keep DETERMINISTIC arrival counts,
                # and a background beat firing it would wreck them
                backend.set(me, str(n))
                warned = False      # recovered: re-arm the warning
            except Exception as exc:
                # transient coordinator hiccups must not kill the beat —
                # a dead thread would report this live worker dead forever
                if not warned:
                    logging.warning("heartbeat publish failed "
                                    "(will keep retrying): %s", exc)
                    warned = True
            stop.wait(period)

    _hb_thread = threading.Thread(target=beat, daemon=True,
                                  name="mxnet-heartbeat")
    _hb_thread.start()
    return True


def heartbeat_stop(timeout: float = 2.0):
    """Stop the publisher thread (e.g. before a deliberate clean exit, so
    peers' ``get_num_dead_node`` sees this worker as *gone* rather than
    freshly-beating). Idempotent."""
    global _hb_started, _hb_stop, _hb_thread
    if _hb_stop is not None:
        _hb_stop.set()
    if _hb_thread is not None:
        _hb_thread.join(timeout)
    _hb_started, _hb_stop, _hb_thread = False, None, None


def dead_ranks(stale_after: float = 20.0, timeout_ms: int = 1000,
               ranks: Optional[Iterable[int]] = None) -> List[int]:
    """Ranks whose heartbeat is missing, or whose beat counter has not
    advanced for ``stale_after`` seconds of the CALLER's clock (two
    observations are needed to declare staleness, so a first call never
    false-positives on a slow-but-alive worker). The pod coordinator
    keys membership decisions on this list; :func:`num_dead_nodes` is
    its count.

    ``ranks`` names the heartbeat keys to check (the pod coordinator
    passes its CURRENT membership's original ranks); default is every
    coordination rank of this process's world.

    The liveness math reads ``time.monotonic()`` ONLY — an NTP step on
    either host must never expire a deadline or resurrect a corpse (the
    ``wall-clock`` lint rule is wired over this module)."""
    import time
    backend = _kv()
    if backend is None:
        return []
    if ranks is None:
        ranks = range(num_workers())
    dead: List[int] = []
    now = time.monotonic()
    for r in ranks:
        try:
            counter = int(backend.get("mxnet_hb/%d" % r, timeout_ms))
        except (TypeError, ValueError):
            dead.append(r)          # never heartbeated within the timeout
            continue
        except Exception:                                  # noqa: BLE001
            dead.append(r)          # backend unreachable: unreadable rank
            continue
        prev = _hb_seen.get(r)
        if prev is None or prev[0] != counter:
            _hb_seen[r] = (counter, now)
        elif now - prev[1] > stale_after:
            dead.append(r)
    return dead


def num_dead_nodes(stale_after: float = 20.0, timeout_ms: int = 1000) -> int:
    """Count of :func:`dead_ranks` (reference: kvstore.h:287
    get_num_dead_node over ps-lite's scheduler heartbeat table)."""
    return len(dead_ranks(stale_after=stale_after, timeout_ms=timeout_ms))


def reset_liveness() -> None:
    """Forget reader-side heartbeat observations (tests, and a monitor
    re-arming after a pod generation change: stale observations of a
    previous generation must not instantly re-declare a rejoined rank
    dead)."""
    _hb_seen.clear()


# --------------------------------------------------- coordination KV store
#
# Two backends serve the same kv_set/kv_get surface:
#
# * the jax.distributed coordination client (training children — the
#   data plane: the checkpoint commit barrier rides it), and
# * a :class:`PodKVClient` installed via :func:`set_kv_backend` (the pod
#   coordinators — the control plane). The control plane CANNOT ride
#   jax's client: its error-polling thread LOG(FATAL)s the whole process
#   the moment the coordination service dies (xla client.h
#   missed_heartbeat_callback; the Python override crashes with
#   std::bad_cast on this jaxlib) — the exact event leader fail-over
#   exists to survive. A coordinator losing its KV server must ADJUDICATE
#   (probe ring), not die.

_KV_BACKEND = None          # PodKVClient installed by the pod coordinator


class _JaxKV(object):
    """Adapter presenting the jax coordination client as a KV backend."""

    def __init__(self, client):
        self._client = client

    def set(self, key: str, value: str) -> None:
        try:
            self._client.key_value_set(key, value, allow_overwrite=True)
        except TypeError:           # older jaxlib: no overwrite kwarg
            try:
                self._client.key_value_delete(key)
            except Exception:                              # noqa: BLE001
                pass
            self._client.key_value_set(key, value)

    def get(self, key: str, timeout_ms: int) -> Optional[str]:
        try:
            v = self._client.blocking_key_value_get(key, int(timeout_ms))
        except Exception:                                  # noqa: BLE001
            return None
        return v.decode() if isinstance(v, bytes) else v


def set_kv_backend(backend) -> None:
    """Install (or with ``None`` remove) an explicit KV backend that
    :func:`kv_set`/:func:`kv_get`/:func:`heartbeat_start`/
    :func:`dead_ranks` use INSTEAD of the jax coordination client. The
    pod coordinator points this at its :class:`PodKVClient`; re-pointing
    it at a re-hosted server is the whole of a control-plane migration."""
    global _KV_BACKEND
    _KV_BACKEND = backend


def kv_backend_active() -> bool:
    return _KV_BACKEND is not None or _client() is not None


def _kv():
    if _KV_BACKEND is not None:
        return _KV_BACKEND
    client = _client()
    return _JaxKV(client) if client is not None else None


def _kv_retries() -> int:
    from .. import config as _config
    return max(0, int(_config.get("MXNET_TPU_KV_RETRIES")))


def kv_set(key: str, value: str) -> None:
    """Publish to the coordination key-value store (overwrite allowed),
    retrying KV flakes (``MXNET_TPU_KV_RETRIES`` bounded attempts, each
    counted ``dist_kv_retry``) before the error propagates. Raises
    RuntimeError when no backend exists. Fault site: ``dist.kv``."""
    import time
    from .. import faults as _faults
    backend = _kv()
    if backend is None:
        raise RuntimeError("kv_set(%r): no coordination KV backend — was "
                           "dist.initialize() called?" % key)
    retries = _kv_retries()
    for attempt in range(retries + 1):
        try:
            if _faults.ARMED:
                _faults.fire("dist.kv", default_kind="raise")
            backend.set(key, value)
            return
        except Exception:                                  # noqa: BLE001
            if attempt >= retries:
                raise
            from .. import profiler as _profiler
            _profiler.incr_counter("dist_kv_retry")
            time.sleep(0.05 * (2 ** attempt))


def kv_get(key: str, timeout_ms: int) -> Optional[str]:
    """Blocking get with a bounded deadline; None on timeout (the caller
    decides whether an absent key is an error — the checkpoint commit
    barrier and the pod rendezvous both do, naming the absent rank).
    Injected KV flakes (fault site ``dist.kv``) are retried with the
    same bounded budget as :func:`kv_set`; an absent key is NOT a flake
    and returns None immediately."""
    import time
    from .. import faults as _faults
    backend = _kv()
    if backend is None:
        raise RuntimeError("kv_get(%r): no coordination KV backend — was "
                           "dist.initialize() called?" % key)
    retries = _kv_retries()
    for attempt in range(retries + 1):
        try:
            if _faults.ARMED:
                _faults.fire("dist.kv", default_kind="raise")
            return backend.get(key, int(timeout_ms))
        except Exception:                                  # noqa: BLE001
            if attempt >= retries:
                raise
            from .. import profiler as _profiler
            _profiler.incr_counter("dist_kv_retry")
            time.sleep(0.05 * (2 ** attempt))


# ----------------------------------------- re-hostable pod control plane
#
# Reference: the ps-lite scheduler is its own tiny process, not a
# training worker — and so is this. A line-based TCP KV service the pod
# coordinators use for rendezvous, heartbeats, restart requests and the
# done barrier. The LEADER (lowest live rank) hosts it; when the
# leader's host dies, the successor re-hosts it on its published
# fail-over port and every survivor re-points its client — no process
# ever has to survive a jax coordination-service death (see the backend
# note above).
#
# Protocol (one UTF-8 line per request/reply; values base64 so any JSON
# payload stays line-safe):
#
#   SET <key> <b64>          -> OK
#   GET <key> <timeout_ms>   -> VAL <b64> | NONE   (server-side blocking
#                               wait for the key, bounded by timeout_ms)
#   PING                     -> PONG
#   CLOCK                    -> CLK <wall_seconds>  (the flight-recorder
#                               clock exchange: NTP-style offset
#                               estimation against the leader's clock)

_KV_MAGIC_PING = b"PING\n"
_KV_MAGIC_PONG = b"PONG\n"


def _b64e(value: str) -> str:
    import base64
    return base64.b64encode(value.encode("utf-8")).decode("ascii")


def _b64d(value: str) -> str:
    import base64
    return base64.b64decode(value.encode("ascii")).decode("utf-8")


class PodKVServer(object):
    """The control-plane KV service (one per pod, on the current
    leader's host). ``stop()`` is abrupt by design — the ``coordsvc``
    fault kind drills exactly this shape (service dead, host alive)."""

    def __init__(self, port: int = 0, host: str = ""):
        import socket
        import threading
        from .. import lockcheck as _lockcheck
        self._store: Dict[str, str] = {}
        self._cond = _lockcheck.Condition(name="dist.podkv_cond")
        self._stopped = False
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind((host, port))
        self._srv.listen(64)
        self.port = self._srv.getsockname()[1]
        self._thread = threading.Thread(target=self._accept_loop,
                                        name="mxpod-kv-server",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        """Close the listener and wake every blocked GET. Idempotent."""
        import socket
        with self._cond:
            if self._stopped:
                return
            self._stopped = True
            self._cond.notify_all()
        try:
            # shutdown BEFORE close: close() alone leaves a concurrently
            # accept()-blocked listener alive in the kernel, silently
            # serving new connections until the next accept returns
            self._srv.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._srv.close()
        except OSError:
            pass

    # ------------------------------------------------------------ server
    def _accept_loop(self) -> None:
        import socket
        import threading
        while True:
            try:
                conn, _addr = self._srv.accept()
            except OSError:
                return              # stop() closed the listener
            threading.Thread(target=self._serve_conn, args=(conn,),
                             daemon=True).start()

    def _serve_conn(self, conn) -> None:
        import time
        try:
            conn.settimeout(300.0)
            rfile = conn.makefile("r", encoding="utf-8", newline="\n")
            for line in rfile:
                parts = line.strip().split(" ")
                if not parts or not parts[0]:
                    continue
                op = parts[0]
                if op == "PING":
                    conn.sendall(_KV_MAGIC_PONG)
                elif op == "CLOCK":
                    # WALL clock on purpose: the reply is compared
                    # against the CALLER's wall clock to estimate the
                    # cross-host offset the blackbox merger aligns on
                    # (monotonic clocks have per-boot arbitrary zeros)
                    conn.sendall(("CLK %r\n"
                                  % time.time()).encode("ascii"))  # mx-lint: allow(wall-clock)
                elif op == "SET" and len(parts) == 3:
                    with self._cond:
                        self._store[parts[1]] = parts[2]
                        self._cond.notify_all()
                    conn.sendall(b"OK\n")
                elif op == "GET" and len(parts) == 3:
                    deadline = time.monotonic() + int(parts[2]) / 1000.0
                    with self._cond:
                        while parts[1] not in self._store \
                                and not self._stopped:
                            left = deadline - time.monotonic()
                            if left <= 0:
                                break
                            self._cond.wait(min(left, 1.0))
                        val = self._store.get(parts[1])
                    conn.sendall(("VAL %s\n" % val).encode("ascii")
                                 if val is not None else b"NONE\n")
                else:
                    conn.sendall(b"ERR\n")
        except OSError:
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass


class PodKVClient(object):
    """One-request-per-connection client of :class:`PodKVServer`.

    Connection failures are judged FAST (a dead server must read as dead
    within one quick retry, not a full blocking window) — bootstrap
    patience lives in :meth:`ping`, which retries connecting until its
    deadline (the follower-waits-for-the-leader's-server window)."""

    def __init__(self, address: str, connect_timeout: Optional[float]
                 = None):
        host, _, port = address.rpartition(":")
        self.address = address
        self._host = host or "127.0.0.1"
        self._port = int(port)
        if connect_timeout is None:
            from .. import config as _config
            connect_timeout = float(_config.get("MXNET_TPU_PROBE_TIMEOUT"))
        self._connect_timeout = float(connect_timeout)

    def _request(self, line: str, read_timeout: float) -> Optional[str]:
        import socket
        import time
        reply = None
        for attempt in range(2):        # one quick re-dial, then give up
            try:
                conn = socket.create_connection(
                    (self._host, self._port),
                    timeout=self._connect_timeout)
            except OSError:
                time.sleep(0.05)
                continue
            try:
                conn.settimeout(read_timeout)
                conn.sendall(line.encode("utf-8"))
                reply = conn.makefile(
                    "r", encoding="utf-8", newline="\n").readline().strip()
            except OSError:
                reply = None
            finally:
                try:
                    conn.close()
                except OSError:
                    pass
            if reply:
                return reply
        return None

    def ping(self, deadline_s: float) -> bool:
        """Bounded wait for the server to answer (bootstrap: the leader
        may not have bound its port yet)."""
        import time
        t_end = time.monotonic() + max(0.0, deadline_s)
        while True:
            if self._request("PING\n", read_timeout=2.0) == "PONG":
                return True
            if time.monotonic() >= t_end:
                return False
            time.sleep(0.2)

    def set(self, key: str, value: str) -> None:
        reply = self._request("SET %s %s\n" % (key, _b64e(value)),
                              read_timeout=10.0)
        if reply != "OK":
            raise OSError("pod KV server %s unreachable for SET %s"
                          % (self.address, key))

    def get(self, key: str, timeout_ms: int) -> Optional[str]:
        reply = self._request(
            "GET %s %d\n" % (key, int(timeout_ms)),
            read_timeout=int(timeout_ms) / 1000.0 + 10.0)
        if reply is None or reply == "NONE":
            return None
        if reply.startswith("VAL "):
            return _b64d(reply[4:])
        return None

    def clock_offset(self, samples: int = 5) -> Optional[float]:
        """NTP-style estimate of ``local_wall - server_wall``: each
        sample brackets a CLOCK request between two local wall reads
        and assumes the server stamped at the midpoint; the minimum-RTT
        sample wins (its midpoint assumption has the tightest error
        bound — half its RTT). None when the server never answered.

        Wall clocks on BOTH ends on purpose — the whole point is to
        compare wall clocks across hosts so the flight-recorder merger
        can align per-host timelines; the RTT bound makes the jumpiness
        of wall time measurable instead of hidden."""
        import time
        best = None
        for _ in range(max(1, int(samples))):
            t0 = time.time()     # mx-lint: allow(wall-clock)
            reply = self._request("CLOCK\n", read_timeout=2.0)
            t1 = time.time()     # mx-lint: allow(wall-clock)
            if not reply or not reply.startswith("CLK "):
                continue
            try:
                server = float(reply[4:])
            except ValueError:
                continue
            rtt = t1 - t0
            offset = (t0 + t1) / 2.0 - server
            if best is None or rtt < best[0]:
                best = (rtt, offset)
        return None if best is None else best[1]


# ------------------------------------------------- peer liveness probes

_PROBE_Q = b"mxpr?\n"
_PROBE_A = b"mxpr!\n"


def _recv_exact(conn, n: int) -> bytes:
    """Read up to ``n`` bytes, looping past short reads; returns what
    arrived before EOF/timeout. TCP is a byte stream — a single recv()
    can short-read a split handshake, and a short-read misjudging a
    LIVE peer as confirmed-dead shrinks the fail-over electorate toward
    split-brain, so the caller classifies on the COMPLETE prefix."""
    buf = b""
    while len(buf) < n:
        try:
            chunk = conn.recv(n - len(buf))
        except OSError:
            break
        if not chunk:
            break
        buf += chunk
    return buf


class ProbeRing(object):
    """Peer-to-peer TCP liveness listener, INDEPENDENT of the
    coordination service: every coordinator runs one and publishes its
    port in the generation's membership record, so when the KV control
    plane goes dark the survivors can still tell "the leader's host
    died" apart from "I am partitioned" — and a healthy majority
    recovers in place instead of draining for a job restart."""

    def __init__(self, port: int = 0):
        import socket
        import threading
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind(("", port))
        self._srv.listen(16)
        self.port = self._srv.getsockname()[1]
        self._thread = threading.Thread(target=self._serve,
                                        name="mxpod-probe-ring",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        import socket
        try:
            self._srv.shutdown(socket.SHUT_RDWR)   # wake a blocked accept
        except OSError:
            pass
        try:
            self._srv.close()
        except OSError:
            pass

    def _serve(self) -> None:
        while True:
            try:
                conn, _addr = self._srv.accept()
            except OSError:
                return
            try:
                conn.settimeout(2.0)
                if _recv_exact(conn, len(_PROBE_Q)) == _PROBE_Q:
                    conn.sendall(_PROBE_A)
            except OSError:
                pass
            finally:
                try:
                    conn.close()
                except OSError:
                    pass


def probe_peer(address: Optional[str],
               timeout: Optional[float] = None) -> str:
    """One liveness probe: ``"live"`` (the peer's probe ring answered),
    ``"dead"`` (its host's TCP stack POSITIVELY refused — the
    coordinator process is gone but the machine answers, e.g. SIGKILL),
    or ``"unreachable"`` (timeout / no route: a dead machine and a
    network partition look identical, so the caller must treat it as
    AMBIGUOUS — the majority arithmetic in the pod coordinator counts
    live vs. everything-not-positively-dead)."""
    import socket
    if not address or address.rpartition(":")[2] in ("", "0"):
        return "unreachable"
    if timeout is None:
        from .. import config as _config
        timeout = float(_config.get("MXNET_TPU_PROBE_TIMEOUT"))
    host, _, port = address.rpartition(":")
    try:
        conn = socket.create_connection((host or "127.0.0.1", int(port)),
                                        timeout=timeout)
    except ConnectionRefusedError:
        return "dead"
    except OSError:
        return "unreachable"
    try:
        conn.settimeout(timeout)
        conn.sendall(_PROBE_Q)
        reply = _recv_exact(conn, len(_PROBE_A))
    except OSError:
        return "unreachable"
    finally:
        try:
            conn.close()
        except OSError:
            pass
    if reply == _PROBE_A:
        return "live"
    if reply and not _PROBE_A.startswith(reply):
        # a recycled port ACTIVELY speaking another protocol is NOT our
        # coordinator: positively dead
        return "dead"
    # silence or a partial prefix (slow peer, split segment): ambiguous —
    # never confirmed-dead on an incomplete handshake
    return "unreachable"


def elect_leader(live: Iterable[int]) -> int:
    """The deterministic election: lowest live rank. Every survivor
    computes it from the SAME generation record + probe results, so no
    communication is needed to agree (and none is available — the
    election runs exactly when the control plane is dark)."""
    return min(live)
