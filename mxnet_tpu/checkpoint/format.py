"""On-disk checkpoint format: atomic directories, verifiable arrays.

A checkpoint is ONE directory ``ckpt-<step>`` under a base directory::

    base/
      ckpt-0000000040/
        arrays.npz       every tensor, stored (uncompressed) npz
        manifest.json    per-array shape/dtype/crc32 + tensor table + meta
      ckpt-0000000080/
      .tmp-ckpt-0000000120.4711   <- a writer died here; never loadable

Atomicity protocol (CheckFreq / Check-N-Run discipline): all files are
written into a ``.tmp-*`` sibling, each fsynced, the temp directory
fsynced, then ``os.rename``d onto the final name and the base directory
fsynced. A ``ckpt-*`` directory therefore either exists with its FULL
contents durable or does not exist at all — ``kill -9`` at any byte of
the write leaves only a ``.tmp-*`` residue that readers never consider
and the next writer garbage-collects.

Verification: the manifest records a crc32 over every array's raw bytes
(plus shape/dtype and file sizes). ``read_checkpoint`` recomputes and
rejects mismatches with :class:`CheckpointCorrupt`; ``load_latest`` then
falls back to the next-newest checkpoint that verifies. The npz container
is loaded with ``allow_pickle=False`` so an untrusted checkpoint can never
execute code (same stance as the legacy ``.params`` codec).

Sharded arrays (mesh-bound modules): a jax array that is not fully
replicated is saved **per shard** — one npz entry per distinct shard with
its index window recorded in the tensor table, alongside the mesh axes and
partition spec — and reassembled into a full host array on read.

Multi-host pods (ISSUE 11): when a ``jax.distributed`` pod is active,
the save goes **process-local** — each host writes ONLY the index
windows it owns into its own ``arrays-p<rank>.npz`` (distinct-window
ownership is derived from the global device→index map, lowest
``(process_index, device id)`` wins, so every host computes the same
partition without communicating), then publishes its shard record to
the coordination KV store AND as a fsynced ``record-p<rank>.json``
file inside the staging dir; rank 0 waits for every record (bounded by
``MXNET_TPU_CKPT_POD_TIMEOUT``), merges them into ONE manifest tagged
with ``world_size`` + per-entry ``process_index``, and commits with the
same fsync+rename protocol. A host dying mid-save means rank 0 times
out and the save aborts AS A UNIT — no partial checkpoint can ever
commit; ``load_latest`` falls back to the newest complete one. Reads
reassemble from all per-host files and reshard onto whatever world
resumes.

Leader death mid-commit (ISSUE 12): if rank 0 itself dies between
shard-record publication and the manifest commit, the KV records died
with the coordination service but the record FILES did not — a
successor leader runs :func:`finalize_staged_pod_saves` to audit each
orphaned staging dir from disk alone and deterministically finalize
(all records present + shard files at recorded sizes → commit the
merged manifest with ``meta.pod_commit`` provenance) or abort (leave
the dir for retention GC). ``load_latest`` never observes a torn
manifest on either path.
"""
from __future__ import annotations

import itertools
import json
import logging
import os
import re
import shutil
import time as _time
import zlib
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..base import MXNetError
from .. import faults as _faults
from . import atomic as _atomic

__all__ = [
    "CheckpointError", "CheckpointCorrupt", "CheckpointNotFound",
    "CheckpointPodError",
    "FORMAT_VERSION", "MANIFEST_NAME", "ARRAYS_NAME",
    "checkpoint_dir_name", "list_checkpoints", "probe_valid",
    "write_checkpoint", "read_manifest", "read_checkpoint", "load_latest",
    "collect_garbage", "resolve_layout_spec", "reshard_tensors",
    "pod_info", "finalize_staged_pod_saves",
]

FORMAT_VERSION = "mxnet_tpu.checkpoint/1"
MANIFEST_NAME = "manifest.json"
ARRAYS_NAME = "arrays.npz"
_DIR_RE = re.compile(r"^ckpt-(\d{10})$")
_TMP_PREFIX = ".tmp-"
# .tmp-ckpt-<step>.<pid>.<seq> — the pid group drives dead-writer reaping;
# the per-process sequence keeps two writers of the SAME step (a queued
# async save racing a SIGTERM sync save) off one tmp path
_TMP_RE = re.compile(r"^\.tmp-ckpt-\d{10}\.(\d+)\.\d+$")
# .tmp-ckpt-<step>.pod.g<gen> — the shared staging dir of a pod save
# (every host writes its arrays-p<rank>.npz into it; reaped by
# collect_garbage once its step finalized, its generation is gone, or
# it aged out — a dead pod's residue has no live pid to key on)
_POD_TMP_RE = re.compile(r"^\.tmp-ckpt-(\d{10})\.pod\.g(.+)$")
_POD_TMP_MAX_AGE = 3600.0
# record-p<rank>.json — each host's fsynced shard record INSIDE the
# staging dir (its KV twin dies with the coordination service; the file
# is what a successor leader finalizes from)
_RECORD_NAME = "record-p%d.json"
_RECORD_RE = re.compile(r"^record-p(\d+)\.json$")
_TMP_SEQ = itertools.count()

log = logging.getLogger(__name__)


class CheckpointError(MXNetError):
    """Base error of the checkpoint subsystem."""


class CheckpointCorrupt(CheckpointError):
    """A checkpoint directory failed verification (torn write by a foreign
    tool, bit rot, truncation): checksum/shape/dtype mismatch or an
    unreadable container."""


class CheckpointNotFound(CheckpointError):
    """No loadable checkpoint exists under the base directory."""


class CheckpointPodError(CheckpointError):
    """A multi-host save could not complete as a unit (a peer died or
    wedged mid-save, the commit barrier timed out). The staged files are
    never renamed into place, so readers never see the partial save; the
    preemption path treats this as best-effort (the newest COMPLETE
    checkpoint is the resume point)."""


def pod_info() -> Tuple[int, int]:
    """(rank, world) of the active ``jax.distributed`` pod, (0, 1) when
    single-process. A pure state probe — never initializes anything and
    never imports ``mxnet_tpu.parallel.dist`` (the zero-cost gate
    asserts a plain single-process run stays free of the pod stack)."""
    import sys
    if "jax" not in sys.modules:
        return 0, 1
    try:
        from jax._src import distributed as _jdist
        state = _jdist.global_state
        if getattr(state, "client", None) is None:
            return 0, 1
        return int(state.process_id or 0), int(state.num_processes or 1)
    except Exception:                                      # noqa: BLE001
        return 0, 1


# Writer injection points for the crash-safety suite, now served by the
# general fault harness (mxnet_tpu.faults): ``MXNET_TPU_FAULTS=
# ckpt.<point>@<n>[:kind]`` fires at the n-th arrival; the PR 5 env
# ``MXNET_TPU_CKPT_TEST_CRASH=<point>@<n>`` still works (faults.py
# parses it as ``ckpt.<point>@<n>:sigkill`` — the honest `kill -9
# mid-write` with deterministic timing). Never set outside tests.
def _maybe_crash(point: str) -> None:
    if _faults.armed_or_env():
        _faults.fire("ckpt." + point, default_kind="sigkill")


def _blackbox():
    """The flight-recorder gate (one implementation:
    ``profiler.blackbox`` — zero-import when the knob is off). The pod
    commit phases recorded here (record published / manifest committed
    / unit abort) are what the post-mortem CLI orders against a
    mid-save death."""
    from .. import profiler as _profiler
    return _profiler.blackbox()


def _crc32(arr: np.ndarray) -> int:
    arr = np.ascontiguousarray(arr)
    return zlib.crc32(memoryview(arr).cast("B")) & 0xFFFFFFFF


def checkpoint_dir_name(step: int) -> str:
    return "ckpt-%010d" % int(step)


# ----------------------------------------------------------- shard codec

def _is_sharded(val: Any) -> bool:
    try:
        import jax
        return isinstance(val, jax.Array) and not val.is_fully_replicated
    except Exception:                                      # noqa: BLE001
        return False


def _shard_index_meta(index, shape) -> List[Optional[List[int]]]:
    """Normalize a shard's index (tuple of slices) to json: per dim
    ``[lo, hi]``, or null for a full dimension."""
    out: List[Optional[List[int]]] = []
    for d, s in enumerate(index):
        lo = 0 if s.start is None else int(s.start)
        hi = int(shape[d]) if s.stop is None else int(s.stop)
        out.append(None if (lo == 0 and hi == int(shape[d]))
                   else [lo, hi])
    # index tuples may be shorter than the rank (trailing full dims)
    out.extend([None] * (len(shape) - len(index)))
    return out


def _decompose(name: str, val: Any, arrays: Dict[str, np.ndarray]
               ) -> Dict[str, Any]:
    """Stage one tensor into the flat array table; returns its tensor-table
    entry. Sharded jax arrays are stored one entry per distinct shard."""
    if not _is_sharded(val):
        arrays[name] = np.asarray(val)
        return {"kind": "full", "key": name}
    sharding = val.sharding
    try:
        from ..parallel.mesh import axis_sizes
        mesh = axis_sizes(sharding.mesh)
        spec = str(tuple(sharding.spec))
    except AttributeError:                   # non-NamedSharding
        mesh, spec = {}, repr(sharding)
    shards_meta = []
    seen = set()
    for shard in val.addressable_shards:
        idx_meta = _shard_index_meta(shard.index, val.shape)
        key_tuple = tuple(tuple(w) if w else None for w in idx_meta)
        if key_tuple in seen:        # replicated copy of the same window
            continue
        seen.add(key_tuple)
        key = "%s@shard%d" % (name, len(shards_meta))
        arrays[key] = np.asarray(shard.data)
        shards_meta.append({"key": key, "index": idx_meta})
    return {"kind": "sharded", "shape": [int(s) for s in val.shape],
            "dtype": str(np.dtype(val.dtype)), "mesh": mesh, "spec": spec,
            "shards": shards_meta}


def _decompose_local(name: str, val: Any, arrays: Dict[str, np.ndarray],
                     rank: int) -> Optional[Dict[str, Any]]:
    """Pod variant of :func:`_decompose`: stage only what THIS process
    owns; returns a partial tensor-table entry (or None when nothing of
    this tensor lives here).

    Ownership of a distinct index window is the lowest
    ``(process_index, device id)`` among the devices holding it — derived
    from the global device→index map, so every host computes the same
    disjoint partition without communicating. Fully-replicated (and
    plain host) tensors are owned by rank 0."""
    if not _is_sharded(val):
        if rank != 0:
            return None
        arrays[name] = np.asarray(val)
        return {"kind": "full", "key": name, "process_index": 0}
    sharding = val.sharding
    try:
        from ..parallel.mesh import axis_sizes
        mesh = axis_sizes(sharding.mesh)
        spec = str(tuple(sharding.spec))
    except AttributeError:                   # non-NamedSharding
        mesh, spec = {}, repr(sharding)
    owners: Dict[Any, Tuple[int, int]] = {}
    pairs = None
    try:
        pairs = [(dev, idx) for dev, idx
                 in sharding.devices_indices_map(val.shape).items()]
    except Exception:                                      # noqa: BLE001
        try:                 # exotic sharding: the global shard view
            pairs = [(sh.device, sh.index) for sh in val.global_shards]
        except Exception:                                  # noqa: BLE001
            # no global window map at all: every host stages its own
            # distinct local windows. Windows REPLICATED across hosts
            # get one copy per host (the read-side coverage mask dedups
            # them), trading bytes for coverage — losing a window
            # entirely would corrupt the save
            pairs = None
    if pairs is not None:
        for dev, idx in pairs:
            meta = _shard_index_meta(idx, val.shape)
            key = tuple(tuple(w) if w else None for w in meta)
            cand = (int(dev.process_index), int(dev.id))
            cur = owners.get(key)
            if cur is None or cand < cur:
                owners[key] = cand
    shards_meta = []
    seen = set()
    for shard in val.addressable_shards:
        idx_meta = _shard_index_meta(shard.index, val.shape)
        key_t = tuple(tuple(w) if w else None for w in idx_meta)
        if key_t in seen:            # replicated copy of the same window
            continue
        owner = owners.get(key_t)
        if owner is not None and owner[0] != rank:
            continue                 # a replica some other host owns
        seen.add(key_t)
        akey = "%s@p%d.s%d" % (name, rank, len(shards_meta))
        arrays[akey] = np.asarray(shard.data)
        shards_meta.append({"key": akey, "index": idx_meta,
                            "process_index": rank})
    if not shards_meta:
        return None
    return {"kind": "sharded", "shape": [int(s) for s in val.shape],
            "dtype": str(np.dtype(val.dtype)), "mesh": mesh, "spec": spec,
            "shards": shards_meta}


def _merge_pod_records(step: int, records: Dict[int, Dict[str, Any]],
                       meta: Optional[Dict[str, Any]], world: int
                       ) -> Dict[str, Any]:
    """Rank 0's manifest merge: one manifest over every host's shard
    record. A record whose (process_index, world_size) tags disagree
    with this commit is a stale host writing into the wrong generation —
    rejected here so it can never reach disk."""
    arrays: Dict[str, Any] = {}
    tensors: Dict[str, Any] = {}
    files: Dict[str, int] = {}
    writers: Dict[str, str] = {}
    for r in sorted(records):
        rec = records[r]
        if int(rec.get("process_index", r)) != r or \
                int(rec.get("world_size", world)) != world:
            raise CheckpointPodError(
                "step %d: shard record of process %d is tagged "
                "process %s / world %s but this commit is world %d — "
                "stale host; aborting the save"
                % (step, r, rec.get("process_index"),
                   rec.get("world_size"), world))
        files[rec["file"]] = int(rec["size"])
        writers[str(r)] = rec["file"]
        for key, arec in rec["arrays"].items():
            if key in arrays:
                raise CheckpointPodError(
                    "step %d: duplicate array key %r from process %d"
                    % (step, key, r))
            arec = dict(arec)
            arec["file"] = rec["file"]
            arec["process_index"] = r
            arrays[key] = arec
        for name, entry in rec["tensors"].items():
            if entry["kind"] == "full":
                tensors[name] = entry
            elif name not in tensors:
                tensors[name] = dict(entry, shards=list(entry["shards"]))
            else:
                tensors[name]["shards"].extend(entry["shards"])
    return {
        "format": FORMAT_VERSION,
        "step": step,
        "world_size": world,
        "writers": writers,
        "arrays": arrays,
        "tensors": tensors,
        "files": files,
        "meta": meta or {},
    }


def _write_checkpoint_pod(base: str, step: int, tensors: Dict[str, Any],
                          meta: Optional[Dict[str, Any]], rank: int,
                          world: int) -> str:
    """Process-local save: every host writes only its own index windows;
    rank 0 merges the records and commits the manifest (see module
    docstring). Checkpoint write cost per host therefore stops scaling
    with pod size."""
    from ..parallel import dist as _dist
    from .. import config as _config
    timeout = float(_config.get("MXNET_TPU_CKPT_POD_TIMEOUT"))
    step = int(step)
    os.makedirs(base, exist_ok=True)
    final = os.path.join(base, checkpoint_dir_name(step))
    if os.path.isdir(final) and probe_valid(final):
        return final     # shared fs: every rank reaches the same answer
    gen = os.environ.get("MXNET_TPU_POD_GEN", "0")
    kv_ns = "mxnet_ckpt/g%s/s%010d" % (gen, step)
    tmp = os.path.join(base, "%sckpt-%010d.pod.g%s"
                       % (_TMP_PREFIX, step, gen))
    if rank == 0 and os.path.isdir(final):
        log.warning("replacing invalid existing checkpoint %s", final)
        shutil.rmtree(final, ignore_errors=True)
    os.makedirs(tmp, exist_ok=True)
    try:
        arrays: Dict[str, np.ndarray] = {}
        table: Dict[str, Any] = {}
        for name, val in tensors.items():
            entry = _decompose_local(name, val, arrays, rank)
            if entry is not None:
                table[name] = entry
        fname = "arrays-p%d.npz" % rank
        arrays_path = os.path.join(tmp, fname)
        if _faults.armed_or_env():
            _faults.fire("ckpt.arrays_write", path=arrays_path,
                         default_kind="eio")
        with open(arrays_path, "wb") as f:
            np.savez(f, **arrays)
            f.flush()
            os.fsync(f.fileno())
        _maybe_crash("after_arrays")
        record = {
            "file": fname, "process_index": rank, "world_size": world,
            "size": os.path.getsize(arrays_path),
            "arrays": {k: {"shape": [int(s) for s in v.shape],
                           "dtype": str(v.dtype),
                           "crc32": _crc32(v),
                           "nbytes": int(v.nbytes)}
                       for k, v in arrays.items()},
            "tensors": table,
        }
        # the shard record is ALSO a file in the staging dir (fsynced,
        # with this rank's view of the manifest meta): coordination-KV
        # entries die with the coordination service, so a SUCCESSOR
        # leader — one whose original rank 0 died between record
        # publication and manifest commit — can still deterministically
        # audit + finalize (or abort) the save from disk alone
        # (:func:`finalize_staged_pod_saves`)
        rec_path = os.path.join(tmp, _RECORD_NAME % rank)
        with open(rec_path, "w") as f:
            json.dump(dict(record, meta=meta or {}), f, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        _dist.kv_set("%s/p%d" % (kv_ns, rank), json.dumps(record))
        _bb = _blackbox()
        if _bb is not None:
            # BEFORE the after_record crash point: a leader killed
            # there must carry "my record published" as its last
            # checkpoint event — the exact fact the successor-finalize
            # audit turns on
            _bb.record("ckpt", "record-published", step=step, gen=gen,
                       rank=rank)
            _bb.flush("ckpt-record")
        # the acceptance ordering drill: the leader dies AFTER its shard
        # record (file + KV) is published but BEFORE the manifest commit
        _maybe_crash("after_record")
        if rank != 0:
            # rank-0 manifest commit barrier: the save only "happened"
            # once rank 0 committed; a bounded wait so a dead rank 0
            # surfaces as an error, never a hang. The window is TWICE
            # rank 0's collection window: rank 0 may legitimately spend
            # the full timeout waiting for the slowest peer's record and
            # then still needs to audit/write/fsync/rename — a peer
            # giving up on the same clock as the collector would declare
            # a checkpoint failed that rank 0 goes on to commit
            commit = _dist.kv_get("%s/commit" % kv_ns,
                                  int(timeout * 2 * 1000))
            if commit is None:
                raise CheckpointPodError(
                    "rank 0 never committed checkpoint step %d within "
                    "%.0fs — the pod save aborted as a unit" % (step,
                                                                timeout))
            return final
        records = {0: record}
        deadline = _time.monotonic() + timeout
        for r in range(1, world):
            left_ms = max(1, int((deadline - _time.monotonic()) * 1000))
            raw = _dist.kv_get("%s/p%d" % (kv_ns, r), left_ms)
            if raw is None:
                raise CheckpointPodError(
                    "process %d of %d never published its shard record "
                    "for step %d within %.0fs — a host died or wedged "
                    "mid-save; aborting the save as a unit (no partial "
                    "checkpoint can commit)" % (r, world, step, timeout))
            records[r] = json.loads(raw)
        # pre-commit staging audit: every record's file must exist on
        # disk at its recorded size. Peers are blocked on the commit key
        # and do NOT rewrite on a rank-0 retry, so their KV records can
        # outlive their files (e.g. a foreign cleanup) — committing a
        # manifest that references a missing file would be a "successful"
        # save that can never load
        for r in sorted(records):
            fpath = os.path.join(tmp, records[r]["file"])
            try:
                size = os.path.getsize(fpath)
            except OSError:
                raise CheckpointPodError(
                    "process %d's shard file %s vanished from the "
                    "staging dir before the step-%d commit; aborting "
                    "the save as a unit"
                    % (r, records[r]["file"], step)) from None
            if size != int(records[r]["size"]):
                raise CheckpointPodError(
                    "process %d's shard file %s is %d bytes on disk "
                    "but its record says %d; aborting the step-%d save "
                    "as a unit" % (r, records[r]["file"], size,
                                   int(records[r]["size"]), step))
        manifest = _merge_pod_records(step, records, meta, world)
        # commit provenance: who landed the manifest, and on which path
        # (a successor-finalized save records the successor's rank here)
        manifest.setdefault("meta", {})["pod_commit"] = {
            "committed_by": 0, "path": "writer", "gen": gen}
        with open(os.path.join(tmp, MANIFEST_NAME), "w") as f:
            json.dump(manifest, f, indent=1, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        _maybe_crash("after_manifest")
        _atomic.fsync_dir(tmp)
        _maybe_crash("before_rename")
        try:
            os.rename(tmp, final)
        except OSError:
            if not os.path.isdir(final):
                raise
            shutil.rmtree(tmp, ignore_errors=True)
        _atomic.fsync_dir(base)
        _dist.kv_set("%s/commit" % kv_ns, final)
        _bb = _blackbox()
        if _bb is not None:
            _bb.record("ckpt", "pod-manifest-commit", step=step,
                       gen=gen, world=world)
        return final
    except CheckpointPodError as exc:
        _bb = _blackbox()
        if _bb is not None:
            _bb.record("ckpt", "pod-abort", step=step, gen=gen,
                       error=str(exc)[:500])
            _bb.flush("ckpt-pod-abort")
        raise
    except BaseException:
        # do NOT rmtree the shared staging dir — peers' shard files live
        # in it, and a transient-error retry on this rank re-enters the
        # SAME dir while peers stay blocked on the commit key (they never
        # rewrite); deleting their files here would let the retry commit
        # a manifest referencing vanished files. The dir is never
        # renamed, so readers never see it; collect_garbage reaps it
        # (finalized step / stale generation / age).
        raise


def _compose(name: str, entry: Dict[str, Any],
             raw: Dict[str, np.ndarray]) -> np.ndarray:
    """Inverse of :func:`_decompose` — reassemble a full host array.

    Coverage is tracked with a boolean mask, not a naive element count:
    index windows written by exotic layouts may OVERLAP (a spec that
    replicates over one axis while sharding another records a window per
    distinct slice, and two checkpoint generations merged by hand can
    overlap partially) — overlapping writes dedup by last-writer-wins
    (each source shard is independently crc-verified upstream, so
    overlapping regions hold identical bytes), while any UNCOVERED
    element is still a hard :class:`CheckpointCorrupt`."""
    if entry["kind"] == "full":
        return raw[entry["key"]]
    shape = tuple(entry["shape"])
    out = np.empty(shape, dtype=np.dtype(entry["dtype"]))
    covered = np.zeros(shape, dtype=bool)
    for sh in entry["shards"]:
        window = tuple(slice(*w) if w else slice(None)
                       for w in sh["index"])
        piece = raw[sh["key"]]
        try:
            # exact-fit only: broadcasting a smaller (crc-valid) shard
            # into a bit-rotted window would mark it covered while
            # silently replicating rows
            if out[window].shape != piece.shape:
                raise ValueError(
                    "shard shape %s does not exactly fill window shape %s"
                    % (piece.shape, out[window].shape))
            out[window] = piece
        except (ValueError, IndexError) as exc:
            raise CheckpointCorrupt(
                "sharded tensor %r: shard %r does not fit window %s: %s"
                % (name, sh["key"], sh["index"], exc)) from None
        covered[window] = True
    if not covered.all():
        missing = int(out.size - np.count_nonzero(covered))
        raise CheckpointCorrupt(
            "sharded tensor %r: shards cover %d of %d elements"
            % (name, out.size - missing, out.size))
    return out


# ----------------------------------------------------------- resharding

# re-exported from parallel.mesh: ONE canonical name->spec resolution
# shared with Module(param_shardings=...) bind-time placement, so a
# checkpoint restored by layout can never resolve differently than the
# bind that will consume it
from ..parallel.mesh import Layout, resolve_layout_spec  # noqa: E402


def reshard_tensors(tensors: Dict[str, np.ndarray], mesh, layout: Layout
                    = None, manifest: Optional[Dict[str, Any]] = None
                    ) -> Dict[str, Any]:
    """Lay reassembled host tensors out onto a (possibly different) mesh.

    This is the elastic half of the checkpoint contract (ROADMAP item 4):
    the manifest records each sharded array's index windows + source
    mesh/spec, :func:`_compose` already reassembles the full host value,
    and this function re-lays it out onto ANY target mesh — N-chip save
    to M-chip restore, down to 1 device and back up, dp/tp/fsdp-style or
    replicated specs. Divisibility is validated per array with the
    offending name in the error (``parallel.mesh.validate_spec``);
    arrays whose recorded source mesh differs from the target count
    ``ckpt_reshard`` (the manifest, when given, provides the recorded
    source meshes)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from .. import profiler as _profiler
    from ..parallel.mesh import axis_sizes, validate_spec
    table = (manifest or {}).get("tensors", {})
    target = axis_sizes(mesh)
    out: Dict[str, Any] = {}
    resharded = 0
    for name, arr in tensors.items():
        # shape-aware resolution: a SpecLayout's heuristic needs the
        # array shape (and strips the arg:/aux:/opt: key prefix itself)
        spec = resolve_layout_spec(layout, name, shape=np.shape(arr),
                                   dtype=getattr(arr, "dtype", None))
        try:
            validate_spec(mesh, spec, np.shape(arr), name=name)
        except ValueError as exc:
            raise CheckpointError("reshard-on-load: %s" % exc) from None
        sharding = NamedSharding(mesh, spec if spec is not None
                                 else PartitionSpec())
        out[name] = jax.device_put(arr, sharding)
        src_mesh = table.get(name, {}).get("mesh")
        if src_mesh is not None and src_mesh != target:
            resharded += 1
    if resharded:
        _profiler.incr_counter("ckpt_reshard", resharded)
    return out


# ------------------------------------------------------------- writing

def write_checkpoint(base: str, step: int, tensors: Dict[str, Any],
                     meta: Optional[Dict[str, Any]] = None) -> str:
    """Write one atomic checkpoint directory; returns its path.

    ``tensors`` maps name -> array-like (numpy or jax; device arrays are
    fetched to host here — call this off the hot thread). If a VALID
    checkpoint already exists at the target directory the write is
    skipped (one state per step: two saves of the same step hold the
    same params/opt state, even if their loop meta differs — e.g. an
    epoch-end save landing on the step of the last mid-epoch save;
    resume handles a landed-on-last-batch checkpoint by falling through
    to the epoch-end processing). An existing directory that FAILS the
    validity probe (bit rot, torn by a foreign tool — the thing resume
    just fell back past) is replaced: it must not block re-checkpointing
    the retraced step forever.

    Under an active ``jax.distributed`` pod this call is COLLECTIVE:
    every process must make it with the same step, each writes only its
    own index windows, and rank 0 commits the merged manifest
    (:func:`_write_checkpoint_pod`).
    """
    rank, world = pod_info()
    if world > 1:
        return _write_checkpoint_pod(base, step, tensors, meta, rank,
                                     world)
    step = int(step)
    os.makedirs(base, exist_ok=True)
    final = os.path.join(base, checkpoint_dir_name(step))
    if os.path.isdir(final):
        if probe_valid(final):
            return final
        log.warning("replacing invalid existing checkpoint %s", final)
        shutil.rmtree(final, ignore_errors=True)
    tmp = os.path.join(base, "%sckpt-%010d.%d.%d"
                       % (_TMP_PREFIX, step, os.getpid(), next(_TMP_SEQ)))
    os.makedirs(tmp)
    try:
        arrays: Dict[str, np.ndarray] = {}
        tensor_table = {name: _decompose(name, val, arrays)
                        for name, val in tensors.items()}
        arrays_path = os.path.join(tmp, ARRAYS_NAME)
        if _faults.armed_or_env():
            # transient-IO drill point (EIO/ENOSPC/EINTR): fires before
            # any byte lands, so the cleanup path removes only the tmp
            # dir and the manager's bounded retry re-enters cleanly
            _faults.fire("ckpt.arrays_write", path=arrays_path,
                         default_kind="eio")
        with open(arrays_path, "wb") as f:
            np.savez(f, **arrays)
            f.flush()
            os.fsync(f.fileno())
        _maybe_crash("after_arrays")
        manifest = {
            "format": FORMAT_VERSION,
            "step": step,
            "arrays": {k: {"shape": [int(s) for s in v.shape],
                           "dtype": str(v.dtype),
                           "crc32": _crc32(v),
                           "nbytes": int(v.nbytes)}
                       for k, v in arrays.items()},
            "tensors": tensor_table,
            "files": {ARRAYS_NAME: os.path.getsize(arrays_path)},
            "meta": meta or {},
        }
        with open(os.path.join(tmp, MANIFEST_NAME), "w") as f:
            json.dump(manifest, f, indent=1, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        _maybe_crash("after_manifest")
        _atomic.fsync_dir(tmp)
        _maybe_crash("before_rename")
        try:
            os.rename(tmp, final)
        except OSError:
            if not os.path.isdir(final):   # a concurrent writer of the
                raise                      # same step won the rename
            shutil.rmtree(tmp, ignore_errors=True)
        _atomic.fsync_dir(base)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    return final


# ------------------------------------------------------------- reading

def list_checkpoints(base: str) -> List[Tuple[int, str]]:
    """``[(step, path)]`` of finalized checkpoint directories, ascending
    by step. ``.tmp-*`` residues are never listed."""
    try:
        names = os.listdir(base)
    except OSError:
        return []
    out = []
    for n in names:
        m = _DIR_RE.match(n)
        if m and os.path.isdir(os.path.join(base, n)):
            out.append((int(m.group(1)), os.path.join(base, n)))
    out.sort()
    return out


def read_manifest(path: str) -> Dict[str, Any]:
    if _faults.armed_or_env():
        # bit-rot/truncation drills: corrupt the manifest ON DISK before
        # the read, so detection + fallback run against a real torn file
        _faults.fire("ckpt.read_manifest",
                     path=os.path.join(path, MANIFEST_NAME),
                     default_kind="bitflip")
    try:
        with open(os.path.join(path, MANIFEST_NAME)) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as exc:
        raise CheckpointCorrupt("unreadable manifest in %s: %s"
                                % (path, exc)) from None
    if not isinstance(manifest, dict) or \
            manifest.get("format") != FORMAT_VERSION:
        raise CheckpointCorrupt(
            "%s: unknown checkpoint format %r"
            % (path, manifest.get("format") if isinstance(manifest, dict)
               else type(manifest)))
    return manifest


def _validate_pod_tags(path: str, manifest: Dict[str, Any]) -> None:
    """Reject a mixed-world save LEGIBLY: every ``process_index`` tag in
    the manifest (writers map, array records, shard entries) must be
    consistent with the committed ``world_size``. A violation means a
    stale host — one still writing with an old generation's world view —
    contaminated the directory; the error names it so the operator knows
    which host to hunt, and ``load_latest`` falls back to the previous
    complete checkpoint instead of failing crc-by-crc."""
    world = int(manifest.get("world_size", 1) or 1)
    for r_s, fname in (manifest.get("writers") or {}).items():
        if int(r_s) >= world:
            raise CheckpointCorrupt(
                "%s: %s was written by process %s, but the manifest "
                "commits world_size=%d — stale host file from a larger "
                "world; rejecting the save as a unit" % (path, fname,
                                                         r_s, world))
    for key, rec in (manifest.get("arrays") or {}).items():
        p = rec.get("process_index")
        if p is not None and int(p) >= world:
            raise CheckpointCorrupt(
                "%s: array %r (file %s) is tagged process %d of a "
                "world-%d-or-larger save, but the manifest commits "
                "world_size=%d — stale host; rejecting the save as a "
                "unit" % (path, key, rec.get("file", ARRAYS_NAME),
                          int(p), int(p) + 1, world))
    for name, entry in (manifest.get("tensors") or {}).items():
        for sh in entry.get("shards") or []:
            p = sh.get("process_index")
            if p is not None and int(p) >= world:
                raise CheckpointCorrupt(
                    "%s: tensor %r shard %r is tagged process %d but "
                    "the manifest commits world_size=%d — stale host"
                    % (path, name, sh.get("key"), int(p), world))


def probe_valid(path: str) -> bool:
    """Cheap validity probe (no checksum pass): manifest parses and the
    container files have the recorded sizes. Used by retention GC so a
    truncated checkpoint never shields a good one from the keep quota."""
    try:
        manifest = read_manifest(path)
        for fname, size in manifest.get("files", {}).items():
            if os.path.getsize(os.path.join(path, fname)) != int(size):
                return False
        return True
    except (CheckpointError, OSError, ValueError, TypeError):
        return False


def read_checkpoint(path: str, verify: bool = True, mesh=None,
                    layout: Layout = None
                    ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Load one checkpoint directory -> (tensors, manifest), verifying
    every array against its manifest record. Raises
    :class:`CheckpointCorrupt` on ANY mismatch (wrong set of arrays,
    shape/dtype drift, checksum failure, unreadable container).

    With ``mesh=`` (and an optional ``layout=`` of name -> PartitionSpec,
    exact or regex), every tensor is additionally RE-LAID-OUT onto that
    mesh after reassembly (:func:`reshard_tensors`) — the checkpoint may
    have been saved from a completely different mesh shape/spec; each
    source shard is checksum-verified before it contributes.

    Pod checkpoints (several ``arrays-p<rank>.npz`` containers) are
    reassembled from every per-host file; a manifest whose
    ``process_index`` tags exceed its committed ``world_size`` is a
    mixed-world partial save (a stale host wrote into the directory) and
    is rejected as a unit, NAMING the stale writer — never a
    checksum-by-checksum failure hunt."""
    manifest = read_manifest(path)
    _validate_pod_tags(path, manifest)
    by_file: Dict[str, Dict[str, Any]] = {}
    for key, rec in manifest["arrays"].items():
        by_file.setdefault(rec.get("file", ARRAYS_NAME), {})[key] = rec
    fire_path = os.path.join(
        path, ARRAYS_NAME if ARRAYS_NAME in by_file or not by_file
        else sorted(by_file)[0])
    if _faults.armed_or_env():
        _faults.fire("ckpt.read_arrays", path=fire_path,
                     default_kind="bitflip")
    raw: Dict[str, np.ndarray] = {}
    try:
        for fname in sorted(by_file):
            want_recs = by_file[fname]
            with np.load(os.path.join(path, fname),
                         allow_pickle=False) as zf:
                names = set(zf.files)
                want = set(want_recs)
                if names != want:
                    raise CheckpointCorrupt(
                        "%s: array set mismatch in %s (missing %s, "
                        "unexpected %s)"
                        % (path, fname, sorted(want - names),
                           sorted(names - want)))
                for key, rec in want_recs.items():
                    arr = zf[key]    # zip-level CRC also checked here
                    if list(arr.shape) != list(rec["shape"]) or \
                            str(arr.dtype) != rec["dtype"]:
                        raise CheckpointCorrupt(
                            "%s: %r is %s%s, manifest says %s%s"
                            % (path, key, arr.dtype, arr.shape,
                               rec["dtype"], tuple(rec["shape"])))
                    if verify and _crc32(arr) != rec["crc32"]:
                        raise CheckpointCorrupt(
                            "%s: checksum mismatch on %r" % (path, key))
                    raw[key] = arr
    except CheckpointError:
        raise
    except Exception as exc:                               # noqa: BLE001
        # zipfile.BadZipFile, zlib.error, OSError, ValueError: all mean
        # the container cannot be trusted
        raise CheckpointCorrupt("%s: unreadable array container: %s"
                                % (path, exc)) from None
    try:
        tensors = {name: _compose(name, entry, raw)
                   for name, entry in manifest.get("tensors", {}).items()}
    except CheckpointError:
        raise
    except Exception as exc:                               # noqa: BLE001
        # KeyError/TypeError from a bit-rotted tensor table (JSON that
        # still parses but references arrays that don't exist) must stay
        # inside the CheckpointCorrupt hierarchy or load_latest's
        # fallback chain breaks
        raise CheckpointCorrupt("%s: corrupt tensor table: %r"
                                % (path, exc)) from None
    if mesh is not None:
        tensors = reshard_tensors(tensors, mesh, layout, manifest=manifest)
    return tensors, manifest


def load_latest(base: str, verify: bool = True, mesh=None,
                layout: Layout = None
                ) -> Tuple[str, Dict[str, Any], Dict[str, Any]]:
    """Newest checkpoint that VERIFIES -> (path, tensors, manifest).

    Corrupt/torn candidates are skipped with a warning (counted
    ``ckpt_load_fallback``); raises :class:`CheckpointNotFound` when
    nothing under ``base`` loads. ``mesh=``/``layout=`` reshard-on-load
    as in :func:`read_checkpoint`."""
    from .. import profiler as _profiler
    entries = list_checkpoints(base)
    for step, path in reversed(entries):
        try:
            tensors, manifest = read_checkpoint(path, verify=verify,
                                                mesh=mesh, layout=layout)
            _profiler.incr_counter("ckpt_load_ok")
            return path, tensors, manifest
        except CheckpointCorrupt as exc:
            _profiler.incr_counter("ckpt_load_fallback")
            log.warning("skipping corrupt checkpoint %s (%s); "
                        "falling back to the previous one", path, exc)
    raise CheckpointNotFound(
        "no loadable checkpoint under %r (%d candidate(s), all invalid)"
        % (base, len(entries)))


# -------------------------------------------- successor finalize / abort

def finalize_staged_pod_saves(base: str, by_rank: int = 0) -> List[str]:
    """Successor-leader audit of orphaned pod staging dirs (ISSUE 12).

    A pod save whose ORIGINAL rank 0 died between shard-record
    publication and manifest commit leaves a ``.tmp-*.pod.g*`` staging
    dir holding every host's ``arrays-p<rank>.npz`` plus its fsynced
    ``record-p<rank>.json`` — everything the commit needed except the
    commit itself. This function lets the next generation's leader
    deterministically FINALIZE or ABORT each such dir:

    * every rank's record file present (the full ``world_size`` set,
      consistently tagged) AND every recorded shard file on disk at its
      recorded size → merge the records into the manifest rank 0 would
      have written (rank 0's record carries the meta), commit it with
      the same fsync→rename protocol, tagged
      ``meta.pod_commit = {path: "successor", committed_by: <rank>}``;
      counted ``ckpt_pod_finalized``;
    * anything missing or inconsistent → LEAVE the dir for retention GC
      (age / stale generation). Readers never saw it; nothing is torn.

    Staging dirs of the CURRENT generation (``MXNET_TPU_POD_GEN``) are
    never touched — they may be a live save in flight. Concurrent
    finalizers (every host resumes through :func:`~mxnet_tpu.elastic.
    resume_dir`) are safe: both build identical manifests and the
    rename is atomic — the loser observes the final dir and stands
    down. Returns the list of finalized checkpoint paths."""
    from .. import profiler as _profiler
    finalized: List[str] = []
    cur_gen = os.environ.get("MXNET_TPU_POD_GEN")
    try:
        names = os.listdir(base)
    except OSError:
        return finalized
    for name in sorted(names):
        m = _POD_TMP_RE.match(name)
        if m is None:
            continue
        step, gen = int(m.group(1)), m.group(2)
        if cur_gen is not None and gen == cur_gen:
            continue                    # possibly a live save in flight
        tmp = os.path.join(base, name)
        final = os.path.join(base, checkpoint_dir_name(step))
        if os.path.isdir(final):
            continue                    # committed; GC reaps the residue
        try:
            records: Dict[int, Dict[str, Any]] = {}
            for fn in os.listdir(tmp):
                rm = _RECORD_RE.match(fn)
                if rm is None:
                    continue
                with open(os.path.join(tmp, fn)) as f:
                    records[int(rm.group(1))] = json.load(f)
            if not records:
                continue                # pre-record death: nothing to audit
            worlds = {int(r.get("world_size", 0)) for r in records.values()}
            if len(worlds) != 1:
                log.warning("pod finalize: %s holds records of mixed "
                            "worlds %s; leaving it for GC", tmp,
                            sorted(worlds))
                continue
            world = worlds.pop()
            if set(records) != set(range(world)):
                log.warning("pod finalize: %s holds records for ranks "
                            "%s of world %d — a host died before "
                            "publishing; leaving the aborted save for "
                            "GC", tmp, sorted(records), world)
                continue
            complete = True
            for r, rec in sorted(records.items()):
                fpath = os.path.join(tmp, rec["file"])
                try:
                    size = os.path.getsize(fpath)
                except OSError:
                    size = -1
                if size != int(rec["size"]):
                    log.warning("pod finalize: %s: rank %d's shard file "
                                "%s is %d bytes, record says %s; leaving "
                                "the save for GC", tmp, r, rec["file"],
                                size, rec["size"])
                    complete = False
                    break
            if not complete:
                continue
            meta = records[0].get("meta") or {}
            manifest = _merge_pod_records(step, records, meta, world)
            manifest.setdefault("meta", {})["pod_commit"] = {
                "committed_by": int(by_rank), "path": "successor",
                "gen": gen}
            # manifest lands under a unique name first so a concurrent
            # finalizer can never interleave a half-written manifest
            part = os.path.join(tmp, "%s.%d" % (MANIFEST_NAME,
                                                os.getpid()))
            with open(part, "w") as f:
                json.dump(manifest, f, indent=1, sort_keys=True)
                f.flush()
                os.fsync(f.fileno())
            os.replace(part, os.path.join(tmp, MANIFEST_NAME))
            _atomic.fsync_dir(tmp)
            try:
                os.rename(tmp, final)
            except OSError:
                if not os.path.isdir(final):
                    raise               # lost to a concurrent finalizer?
            _atomic.fsync_dir(base)
            _profiler.incr_counter("ckpt_pod_finalized")
            _bb = _blackbox()
            if _bb is not None:
                _bb.record("ckpt", "pod-finalized", step=step,
                           gen=gen, by_rank=int(by_rank))
            log.warning("pod finalize: committed orphaned step-%d save "
                        "%s (original leader died mid-commit; finalized "
                        "by rank %d)", step, final, by_rank)
            finalized.append(final)
        except (OSError, ValueError, KeyError, CheckpointError) as exc:
            if os.path.isdir(final):
                finalized.append(final)     # a concurrent finalizer won
                continue
            log.warning("pod finalize: could not audit %s (%s); leaving "
                        "it for GC", tmp, exc)
    return finalized


# ---------------------------------------------------------- retention GC

def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except OSError:
        return True        # EPERM: exists but not ours


def collect_garbage(base: str, keep_last: int,
                    keep_every: Optional[int] = None) -> int:
    """Retention: keep the newest ``keep_last`` VALID checkpoints (plus
    every ``keep_every``-th step forever), delete the remaining valid
    ones, and clear ``.tmp-*`` residues of dead writers. Returns the
    number of checkpoints removed.

    Safety rails: ``keep_last <= 0`` disables deletion entirely; the
    newest valid checkpoint is never deleted; checkpoints that fail the
    validity probe are NEVER auto-deleted (they don't count toward the
    quota either — so GC can never leave only a corrupt checkpoint
    behind) but are logged for the operator."""
    from .. import profiler as _profiler
    removed = 0
    # reap tmp residues of writers that are gone (kill -9 mid-write);
    # pod staging dirs have no live pid to key on — reap them when their
    # step finalized, their generation is over, or they aged out
    cur_gen = os.environ.get("MXNET_TPU_POD_GEN")
    try:
        for name in os.listdir(base):
            m = _TMP_RE.match(name)
            if m and not _pid_alive(int(m.group(1))):
                shutil.rmtree(os.path.join(base, name), ignore_errors=True)
                continue
            pm = _POD_TMP_RE.match(name)
            if pm is None:
                continue
            p = os.path.join(base, name)
            finalized = os.path.isdir(
                os.path.join(base, checkpoint_dir_name(int(pm.group(1)))))
            stale_gen = cur_gen is not None and pm.group(2) != cur_gen
            try:
                aged = (_time.time() - os.path.getmtime(p)
                        ) > _POD_TMP_MAX_AGE
            except OSError:
                aged = False
            if finalized or stale_gen or aged:
                shutil.rmtree(p, ignore_errors=True)
    except OSError:
        pass
    if keep_last is None or keep_last <= 0:
        return 0
    entries = list_checkpoints(base)
    valid = [(s, p) for s, p in entries if probe_valid(p)]
    invalid = [p for s, p in entries if (s, p) not in valid]
    for p in invalid:
        log.warning("retention GC: %s fails the validity probe; leaving "
                    "it for inspection (it does not count toward "
                    "keep-last)", p)
    keep = {p for _s, p in valid[-keep_last:]}
    if keep_every and keep_every > 0:
        keep |= {p for s, p in valid if s % keep_every == 0}
    if valid:
        keep.add(valid[-1][1])
    for _step, path in valid:
        if path in keep:
            continue
        shutil.rmtree(path, ignore_errors=True)
        removed += 1
    if removed:
        _profiler.incr_counter("ckpt_gc_removed", removed)
    return removed
