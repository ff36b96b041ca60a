"""Multi-host pod drill: coordinated elastic training that survives
ANY host death — including the leader's (CI ``multihost`` job; also
driven by tests/test_pod.py::test_pod_smoke_script). Extends the
single-process kill/reshard/resume drill of tools/elastic_smoke.py to
a multi-HOST pod — processes wired by the tools/launch.py DMLC env
protocol, each running ``python -m mxnet_tpu.elastic --coordinated``
over a CPU backend (``JAX_PLATFORMS=cpu``), training data-parallel
through the dist kvstore.

2-host variants, all mid-epoch at a deterministic batch:

* ``hostkill`` — ``host.die@K:hostkill`` SIGKILLs host 1's supervisor
  AND child (the whole "host" vanishes, no cleanup). The survivor
  drains, re-rendezvous at world 1, and finishes; the dead host's
  supervisor must exit -SIGKILL.
* ``wedge``    — ``host.die@K:wedge``: host 1 freezes WHOLE (the
  supervisor is SIGSTOPped, the child spins) — nothing crashes, no
  socket closes, ONLY the heartbeat staleness deadline can catch it.
  Host 0 must count ``elastic_dead_host`` and resume at world 1 while
  host 1 is provably still frozen (the driver reaps it afterwards).
* ``sigkill-child`` — ``fit.batch@K:sigkill`` kills host 1's CHILD
  only (the supervisor survives): the pod must restart POD-WIDE at the
  same world (SPMD cannot restart one rank alone) and still finish.

3-host LEADER fail-over variants (ISSUE 12 acceptance):

* ``leader-kill`` — ``leader.die@K:hostkill`` on host 0, the one
  carrying the control plane: survivors 1 and 2 adjudicate over the
  probe ring, elect rank 1, re-host the KV control plane on its
  published fail-over port, resume at world 2, and finish
  bit-identical with ``elastic_leader_failover == 1``.
* ``leader-cascade`` — kills the gen-0 leader AND then the gen-1
  leader (rank 1): rank 2 alone fails over TWICE and finishes at
  world 1 (``elastic_leader_failover == 2``).
* ``coordsvc`` — ``leader.die@K:coordsvc`` kills ONLY the control-
  plane KV service (every host stays up — the split-brain shape): all
  three coordinators must adjudicate all-live over the probe ring,
  re-elect rank 0, re-host on its fail-over port, and recover IN
  PLACE at world 3 with zero dead hosts and zero reshards.

Every variant's final parameters must be BIT-IDENTICAL to an
uninterrupted 1-host-pod baseline, with zero steady-state recompiles
asserted at every batch of every generation. The model is the same
one-hot "lookup regression" as elastic_smoke (every FP reduction has
exactly one nonzero contributor, so cross-world sums are exact); each
host masks the global batch down to its stride-shard, so the W-host
gradient sum equals the 1-host gradient bit-for-bit.

Also here:

* process-local checkpoint phase: a 2-process pod with 4 virtual
  devices each writes a cross-process-sharded checkpoint — each host's
  ``arrays-p<rank>.npz`` must hold ONLY the index windows it owns; a
  second save SIGKILLed mid-write on one host must abort as a unit
  (rank 0 times out, nothing commits) and ``load_latest`` falls back;
  the driver then reshards the survivor onto a single-device world.
* mid-save LEADER death (both orderings): rank 0 SIGKILLed AFTER its
  shard record published but BEFORE the manifest commit → a successor
  deterministically FINALIZES the save from the file-backed records
  (``finalize_staged_pod_saves``; ``meta.pod_commit.path ==
  "successor"``); killed BEFORE its record → the successor provably
  ABORTS (staging left for GC) and ``load_latest`` never sees a torn
  manifest.
* zero-cost gate: a plain single-process fit must never import
  ``mxnet_tpu.parallel.dist`` (the probe ring and the fail-over
  machinery live there), arm the fault harness, or move any
  ``elastic_*`` / ``fault_injected`` / ``loop_nonfinite`` /
  ``dist_kv_retry`` counter.

Exit 0 + ``POD-DRILL-OK`` on success; any assertion kills CI. Every
subprocess wait carries a hard timeout (PhaseGuard discipline — a
wedged drill fails, it does not hang the pipeline).
"""
import json
import os
import re
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

BATCH, NSAMP, FEAT, OUT = 8, 64, 64, 4
EPOCHS = 3
SEED = 5
DIE_AT = 12                       # batch of the injected host failure
PHASE_TIMEOUT = 420.0

KNOBS = {
    "MXNET_TPU_HEARTBEAT_PERIOD": "0.5",
    "MXNET_KVSTORE_HEARTBEAT_STALE_SECS": "3",
    "MXNET_TPU_ELASTIC_DRAIN_GRACE": "6",
    "MXNET_TPU_CKPT_POD_TIMEOUT": "8",
    "MXNET_TPU_DIST_TIMEOUT": "60",
    "MXNET_TPU_PROBE_TIMEOUT": "1",
    # every "host" of the drill is this machine: advertise a loopback
    # address so a re-hosted control plane / probe ring is reachable
    # (real clusters: the launcher exports each host's routable name)
    "MXNET_TPU_POD_HOST": "127.0.0.1",
}


def _free_port():
    from mxnet_tpu.parallel.dist import free_port
    return free_port()


def _data(rank, world):
    """One-hot lookup samples, masked to this rank's stride-shard: row
    s is e_s (NSAMP == FEAT), zeroed unless s %% world == rank (labels
    too). Every gradient element keeps exactly one nonzero contributor
    GLOBALLY, so the cross-host kvstore sum at world W is bit-identical
    to the 1-host full-batch gradient (see module docstring)."""
    x = np.eye(FEAT, dtype=np.float32)[np.arange(NSAMP) % FEAT]
    rng = np.random.RandomState(3)
    y = rng.uniform(-1, 1, (NSAMP, OUT)).astype(np.float32)
    mine = (np.arange(NSAMP) % world) == rank
    x = x * mine[:, None]
    y = y * mine[:, None]
    return x, y


def _symbol():
    import mxnet_tpu as mx
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=OUT, no_bias=True,
                               name="lut")
    return mx.sym.LinearRegressionOutput(fc, mx.sym.Variable("label"),
                                         name="reg")


# ------------------------------------------------------- training child

def _pod_child(ckpt_dir, out_path):
    import jax
    # the drill runs every pod worker on the CPU backend, whatever the
    # environment says
    jax.config.update("jax_platforms", "cpu")
    import mxnet_tpu as mx
    from mxnet_tpu import elastic, faults, profiler
    gen = int(os.environ.get("MXNET_TPU_POD_GEN", "0"))
    wid = os.environ.get("DMLC_WORKER_ID", "")
    spec = os.environ.get("POD_SMOKE_FAULT", "")
    if spec and gen == 0 and wid == "1":
        faults.install(spec)
    # leader drills: semicolon list of g<gen>w<worker>=<spec> — the
    # worker id is the GENERATION-renumbered one, so "g1w0" targets
    # whoever leads the post-fail-over world (the cascade variant)
    for item in os.environ.get("POD_SMOKE_FAULTS", "").split(";"):
        item = item.strip()
        if not item:
            continue
        cond, _, fspec = item.partition("=")
        g, _, w = cond.partition("w")
        if int(g.lstrip("g")) == gen and w == wid:
            faults.install(fspec)
    # the rendezvous must run before ANY device touch (backend pins the
    # process's device view) — so the kvstore comes before the seed
    kv = mx.kv.create("dist_sync")
    mx.random.seed(SEED)
    rank, world = kv.rank, kv.num_workers
    X, Y = _data(rank, world)
    it = mx.io.NDArrayIter({"data": X}, {"label": Y}, batch_size=BATCH)
    mod = mx.mod.Module(_symbol(), context=mx.cpu(),
                        data_names=("data",), label_names=("label",))

    slp = float(os.environ.get("POD_SMOKE_BATCH_SLEEP", "0"))

    def _no_recompiles(_param):
        n = profiler.get_counter("loop_recompile")
        assert n == 0, "steady-state recompile detected (%d)" % n
        if slp:
            # coordsvc variant: the data plane survives the fault, so
            # training must outlast the coordinators' dark-control-plane
            # detection + drain — pace the batches like a real workload
            time.sleep(slp)

    mod.fit(it, num_epoch=EPOCHS, eval_metric="mse", optimizer="sgd",
            optimizer_params={"learning_rate": 0.3, "momentum": 0.9,
                              "rescale_grad": 1.0 / BATCH},
            kvstore=kv,
            checkpoint=mx.checkpoint.CheckpointConfig(
                ckpt_dir, every_n_batches=2, period_epochs=1,
                keep_last=0),
            resume_from=elastic.resume_dir(ckpt_dir),
            batch_end_callback=_no_recompiles)
    arg, _aux = mod.get_params()
    if rank == 0:
        np.savez(out_path, **{k: v.asnumpy() for k, v in arg.items()})
    kv.barrier()
    print("POD-CHILD-DONE rank=%d world=%d gen=%d recompiles=%d"
          % (rank, world, gen, profiler.get_counter("loop_recompile")),
         flush=True)
    return 0


# -------------------------------------------------- sharded-ckpt child

def _ckpt_child(ckpt_dir):
    from mxnet_tpu import faults
    from mxnet_tpu.parallel import dist
    from mxnet_tpu.checkpoint import (CheckpointPodError, load_latest,
                                      read_checkpoint, write_checkpoint)
    dist.initialize()
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    r, world = dist.rank(), dist.num_workers()
    if r == 1:
        faults.install("ckpt.after_arrays@2:sigkill")
    devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    mesh = Mesh(np.array(devs), ("data",))
    full = np.arange(8 * 16, dtype=np.float32).reshape(8, 16)
    arr = jax.make_array_from_callback(
        full.shape, NamedSharding(mesh, P("data", None)),
        lambda idx: full[idx])
    rep = np.arange(4, dtype=np.float32)
    path = write_checkpoint(ckpt_dir, 1, {"w": arr, "rep": rep})

    if r == 0:
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        assert manifest["world_size"] == 2, manifest["world_size"]
        assert set(manifest["writers"]) == {"0", "1"}, manifest["writers"]
        # per-host ownership: each file holds ONLY windows its process
        # owns; the replicated tensor lives on rank 0 alone
        z0 = np.load(os.path.join(path, "arrays-p0.npz"))
        z1 = np.load(os.path.join(path, "arrays-p1.npz"))
        assert sorted(z0.files) == ["rep", "w@p0.s0", "w@p0.s1",
                                    "w@p0.s2", "w@p0.s3"], z0.files
        assert sorted(z1.files) == ["w@p1.s0", "w@p1.s1", "w@p1.s2",
                                    "w@p1.s3"], z1.files
        rows = sorted(sh["index"][0][0]
                      for sh in manifest["tensors"]["w"]["shards"]
                      if sh["process_index"] == 1)
        assert rows == [4, 5, 6, 7], rows   # proc 1 owns rows 4..7 only
        for key, rec in manifest["arrays"].items():
            assert rec["file"] == "arrays-p%d.npz" % rec["process_index"]

    tensors, _m = read_checkpoint(path)          # reassemble everywhere
    np.testing.assert_array_equal(tensors["w"], full)
    np.testing.assert_array_equal(tensors["rep"], rep)

    # save 2: rank 1 is SIGKILLed after its arrays hit disk but BEFORE
    # its record publishes — rank 0 must time out and abort as a unit
    if r == 1:
        write_checkpoint(ckpt_dir, 2, {"w": arr, "rep": rep})
        raise AssertionError("rank 1 survived its injected SIGKILL")
    try:
        write_checkpoint(ckpt_dir, 2, {"w": arr, "rep": rep})
    except CheckpointPodError as exc:
        assert "never published" in str(exc), exc
    else:
        raise AssertionError("rank 0 committed a partial pod save")
    steps = []
    from mxnet_tpu.checkpoint import list_checkpoints
    steps = [s for s, _p in list_checkpoints(ckpt_dir)]
    assert steps == [1], steps                   # nothing partial landed
    path2, t2, _m2 = load_latest(ckpt_dir)
    assert path2 == path
    np.testing.assert_array_equal(t2["w"], full)
    print("POD-CKPT-CHILD-OK rank=%d world=%d" % (r, world), flush=True)
    sys.stdout.flush()
    os._exit(0)    # skip jax's clean shutdown: the peer is dead


# ----------------------------------------------------------- zero cost

def _zero_cost():
    import mxnet_tpu as mx
    from mxnet_tpu import faults, profiler
    assert not faults.ARMED, "fault harness armed with no knob set"
    mx.random.seed(SEED)
    X, Y = _data(0, 1)
    it = mx.io.NDArrayIter({"data": X}, {"label": Y}, batch_size=BATCH)
    mod = mx.mod.Module(_symbol(), context=mx.cpu(),
                        data_names=("data",), label_names=("label",))
    mod.fit(it, num_epoch=1, eval_metric="mse", optimizer="sgd",
            optimizer_params={"learning_rate": 0.3})
    assert "mxnet_tpu.parallel.dist" not in sys.modules, \
        "the pod stack was imported in a plain single-process fit"
    assert "mxnet_tpu.obs.blackbox" not in sys.modules, \
        "the flight recorder was imported with its knob off"
    assert "mxnet_tpu.obs.straggler" not in sys.modules, \
        "the straggler stack was imported in a single-process fit"
    from mxnet_tpu.checkpoint import pod_info
    assert pod_info() == (0, 1)
    for name in ("fault_injected", "elastic_restart", "elastic_reshard",
                 "elastic_dead_host", "ckpt_preempt_save_failed",
                 "elastic_leader_failover", "loop_nonfinite",
                 "dist_kv_retry", "ckpt_pod_finalized",
                 "obs_blackbox_flush", "obs_straggler",
                 "obs_straggler_publish_failed"):
        assert profiler.get_counter(name) == 0, name
    assert getattr(mod, "_nancheck_fn", None) is None, \
        "NANCHECK=off must chain nothing onto the fused step"
    print("ZERO-COST-OK", flush=True)
    return 0


# -------------------------------------------------------------- driver

def _run(cmd, env, timeout, check=True):
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=timeout)
    if check:
        assert proc.returncode == 0, (cmd, proc.stdout[-4000:],
                                      proc.stderr[-4000:])
    return proc


def _dmlc_env(base, rank, n, port):
    env = dict(base)
    env.update({"DMLC_ROLE": "worker", "DMLC_PS_ROOT_URI": "127.0.0.1",
                "DMLC_PS_ROOT_PORT": str(port),
                "DMLC_NUM_WORKER": str(n), "DMLC_NUM_SERVER": "0",
                "DMLC_WORKER_ID": str(rank)})
    return env


def _assert_blackbox(name, bbdir, base_env, expect_bb):
    """Post-mortem acceptance: after the drill, the merge CLI must name
    the first-dead rank, its last fault site, and produce a merged
    timeline that loads as valid chrome-trace JSON; fail-over
    transitions must be present and clock-ordered."""
    proc = _run([sys.executable, "-m", "mxnet_tpu.obs", "blackbox",
                 bbdir], base_env, 120.0)
    m = re.search(r"POD-BLACKBOX-VERDICT (\{.*\})", proc.stdout)
    assert m, "%s: no verdict in:\n%s" % (name, proc.stdout[-4000:])
    verdict = json.loads(m.group(1))
    assert verdict["first_dead"] == expect_bb["first_dead"], \
        (name, verdict)
    assert verdict.get("last_event"), (name, verdict)
    lf = verdict.get("last_fault")
    assert lf and lf["site"] == expect_bb["fault_site"], (name, verdict)
    assert any(expect_bb["fault_site"] in spec
               for spec in verdict.get("armed_faults", [])), \
        (name, verdict)
    with open(os.path.join(bbdir, "pod-timeline.json")) as f:
        timeline = json.load(f)
    assert isinstance(timeline.get("traceEvents"), list) \
        and timeline["traceEvents"], (name, "empty merged timeline")
    if expect_bb.get("failover_ranks"):
        fos = verdict.get("failovers") or []
        got = {fo["rank"] for fo in fos}
        assert got >= set(expect_bb["failover_ranks"]), (name, fos)
        ts = [fo["t"] for fo in fos]
        assert ts == sorted(ts), (name, "fail-overs not clock-ordered",
                                  fos)
        # clock-ordered ACROSS ranks: every survivor's fail-over comes
        # after the dead leader's last recorded event
        assert all(t >= verdict["last_event"]["t"] for t in ts), \
            (name, verdict["last_event"], fos)
    print("POD-BLACKBOX-OK %s (first_dead=%s fault=%s)"
          % (name, verdict["first_dead"], lf["site"]), flush=True)


def _counters_line(stdout):
    m = re.search(r"POD-COORDINATOR-EXIT rank=(\d+) rc=(-?\d+) "
                  r"restarts=(\d+) reshards=(\d+) dead_hosts=(\d+) "
                  r"failovers=(\d+) counters=(\{.*\})", stdout)
    assert m, "no coordinator exit record in:\n%s" % stdout[-4000:]
    return {"rank": int(m.group(1)), "rc": int(m.group(2)),
            "restarts": int(m.group(3)), "reshards": int(m.group(4)),
            "dead_hosts": int(m.group(5)), "failovers": int(m.group(6)),
            "counters": json.loads(m.group(7))}


def _variant(name, fault, base_env, work, baseline, expect):
    """One pod-failure variant: spawn 2 coordinated supervisors, inject
    the fault on host 1 at batch DIE_AT of generation 0, assert the
    survivor finishes with params bit-identical to the baseline."""
    vdir = os.path.join(work, name)
    os.makedirs(vdir)
    ckpt = os.path.join(vdir, "ckpts")
    out = os.path.join(vdir, "params.npz")
    marker = os.path.join(vdir, "faults.touched")
    bbdir = os.path.join(vdir, "blackbox")
    port = _free_port()
    env = dict(base_env)
    env.update({"POD_SMOKE_FAULT": fault,
                "MXNET_TPU_FAULTS_TOUCH": marker,
                # flight recorder on for every variant: the post-mortem
                # drill (expect["blackbox"]) asserts on the merged
                # timeline after the hostkill; a short heartbeat bounds
                # how stale a SIGKILL'd host's window can be
                "MXNET_TPU_OBS_BLACKBOX": bbdir,
                "MXNET_TPU_OBS_BLACKBOX_FLUSH_SECS": "0.5"})
    cmd = [sys.executable, "-m", "mxnet_tpu.elastic", "--coordinated",
           "--max-restarts", "4", "--",
           os.path.abspath(__file__), "--child", ckpt, out]
    # each supervisor leads its own process group so a frozen host
    # (SIGSTOPped supervisor + wedged child) can be reaped as a unit
    sups = [subprocess.Popen(cmd, env=_dmlc_env(env, r, 2, port),
                             stdout=subprocess.PIPE,
                             stderr=subprocess.PIPE, text=True,
                             start_new_session=True)
            for r in range(2)]
    deadline = time.monotonic() + PHASE_TIMEOUT
    outs = [None, None]
    frozen = expect.get("frozen", False)
    try:
        outs[0] = sups[0].communicate(timeout=deadline - time.monotonic())
        if frozen:
            # the whole point of the wedge variant: host 1 is still
            # frozen AFTER the survivor finished — nothing but the
            # heartbeat deadline ever noticed it
            assert sups[1].poll() is None, \
                "%s: host 1 exited (%s) but was expected frozen" \
                % (name, sups[1].returncode)
            os.killpg(sups[1].pid, signal.SIGKILL)
        outs[1] = sups[1].communicate(
            timeout=max(1.0, deadline - time.monotonic()))
    except subprocess.TimeoutExpired:
        for p in sups:
            if p.poll() is None:
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except OSError:
                    p.kill()
        raise AssertionError(
            "%s: pod drill wedged past %.0fs" % (name, PHASE_TIMEOUT))
    finally:
        for p in sups:
            if p.poll() is None:
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except OSError:
                    p.kill()
                p.wait()

    rc0, rc1 = sups[0].returncode, sups[1].returncode
    dump = "\n".join("--- rank %d rc=%s\n%s\n%s"
                     % (i, p.returncode, o[-4000:], e[-4000:])
                     for i, (p, (o, e)) in enumerate(zip(sups, outs)))
    assert rc0 == 0, "%s: survivor failed\n%s" % (name, dump)
    assert rc1 in expect["rc1"], "%s: host-1 rc %s not in %s\n%s" \
        % (name, rc1, expect["rc1"], dump)

    rec0 = _counters_line(outs[0][0])
    assert rec0["restarts"] >= 1, dump
    assert rec0["reshards"] >= expect["reshards_min"], dump
    if expect.get("dead_hosts_min"):
        assert rec0["dead_hosts"] >= expect["dead_hosts_min"], dump

    with open(marker) as f:
        touched = f.read()
    assert expect["marker"] in touched, (name, touched)

    ref = dict(np.load(baseline))
    got = dict(np.load(out))
    assert set(ref) == set(got), (sorted(ref), sorted(got))
    for k in sorted(ref):
        np.testing.assert_array_equal(ref[k], got[k], err_msg=k)

    # a world-2 generation left process-local checkpoints behind:
    # rank 1 wrote ONLY its own (empty: DP params are replicated and
    # owned by rank 0) arrays file, and the manifest says so
    pod_manifests = []
    for d in sorted(os.listdir(ckpt)):
        mf = os.path.join(ckpt, d, "manifest.json")
        if d.startswith("ckpt-") and os.path.exists(mf):
            with open(mf) as f:
                man = json.load(f)
            if man.get("world_size") == 2:
                pod_manifests.append((os.path.join(ckpt, d), man))
    assert pod_manifests, "no world-2 checkpoint survived in %s" % ckpt
    d, man = pod_manifests[-1]
    assert set(man["writers"]) == {"0", "1"}
    assert os.path.exists(os.path.join(d, "arrays-p0.npz"))
    assert os.path.exists(os.path.join(d, "arrays-p1.npz"))
    assert all(rec["process_index"] == 0
               for rec in man["arrays"].values()), \
        "replicated DP params must all be owned by rank 0"
    if expect.get("blackbox"):
        _assert_blackbox(name, bbdir, base_env, expect["blackbox"])
    print("POD-VARIANT-OK %s (rc1=%s restarts=%d reshards=%d "
          "dead_hosts=%d)" % (name, rc1, rec0["restarts"],
                              rec0["reshards"], rec0["dead_hosts"]),
          flush=True)


def _leader_variant(name, faults_spec, world, base_env, work, baseline,
                    expect):
    """One leader fail-over variant: a ``world``-host pod with
    ``leader.die`` armed through the per-generation POD_SMOKE_FAULTS
    map. Asserts exit codes per rank, the election/fail-over counters
    from the survivors' exit records, the fault marker, and final
    params bit-identical to the uninterrupted baseline."""
    vdir = os.path.join(work, name)
    os.makedirs(vdir)
    ckpt = os.path.join(vdir, "ckpts")
    out = os.path.join(vdir, "params.npz")
    marker = os.path.join(vdir, "faults.touched")
    bbdir = os.path.join(vdir, "blackbox")
    port = _free_port()
    env = dict(base_env)
    env.update({"POD_SMOKE_FAULTS": faults_spec,
                "MXNET_TPU_FAULTS_TOUCH": marker,
                "MXNET_TPU_OBS_BLACKBOX": bbdir,
                "MXNET_TPU_OBS_BLACKBOX_FLUSH_SECS": "0.5"})
    env.update(expect.get("env", {}))
    # budget headroom: one leader loss can cost TWO restarts on a rank
    # whose child died before its monitor saw the dark control plane
    # (child crash + rendezvous fail-over both consume budget)
    cmd = [sys.executable, "-m", "mxnet_tpu.elastic", "--coordinated",
           "--max-restarts", "8", "--",
           os.path.abspath(__file__), "--child", ckpt, out]
    sups = [subprocess.Popen(cmd, env=_dmlc_env(env, r, world, port),
                             stdout=subprocess.PIPE,
                             stderr=subprocess.PIPE, text=True,
                             start_new_session=True)
            for r in range(world)]
    deadline = time.monotonic() + PHASE_TIMEOUT
    outs = [None] * world
    try:
        # highest ranks outlive every fail-over: collect in reverse
        # (rank 0 is the first to die in every leader variant)
        for r in reversed(range(world)):
            outs[r] = sups[r].communicate(
                timeout=max(1.0, deadline - time.monotonic()))
    except subprocess.TimeoutExpired:
        for p in sups:
            if p.poll() is None:
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except OSError:
                    p.kill()
        raise AssertionError(
            "%s: leader drill wedged past %.0fs" % (name, PHASE_TIMEOUT))
    finally:
        for p in sups:
            if p.poll() is None:
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except OSError:
                    p.kill()
                p.wait()

    dump = "\n".join("--- rank %d rc=%s\n%s\n%s"
                     % (i, p.returncode, (o or ("", ""))[0][-4000:],
                        (o or ("", ""))[1][-4000:])
                     for i, (p, o) in enumerate(zip(sups, outs)))
    for r, want in expect["rc"].items():
        assert sups[r].returncode in want, \
            "%s: rank %d rc %s not in %s\n%s" \
            % (name, r, sups[r].returncode, want, dump)
    for r, want in expect["recs"].items():
        rec = _counters_line(outs[r][0])
        assert rec["failovers"] == want["failovers"], \
            "%s: rank %d failovers %d != %d\n%s" \
            % (name, r, rec["failovers"], want["failovers"], dump)
        assert rec["counters"].get("elastic_leader_failover", 0) \
            == want["failovers"], (name, r, rec["counters"], dump)
        assert rec["restarts"] >= want.get("restarts_min", 0), (name, dump)
        assert rec["reshards"] >= want.get("reshards_min", 0), (name, dump)
        if "reshards_max" in want:
            assert rec["reshards"] <= want["reshards_max"], (name, dump)
        if "dead_hosts_max" in want:
            assert rec["dead_hosts"] <= want["dead_hosts_max"], \
                (name, dump)
    with open(marker) as f:
        touched = f.read()
    for needle in expect["marker"]:
        assert needle in touched, (name, needle, touched)

    ref = dict(np.load(baseline))
    got = dict(np.load(out))
    assert set(ref) == set(got), (sorted(ref), sorted(got))
    for k in sorted(ref):
        np.testing.assert_array_equal(ref[k], got[k], err_msg=k)
    if expect.get("manifest_world"):
        worlds = set()
        for d in sorted(os.listdir(ckpt)):
            mf = os.path.join(ckpt, d, "manifest.json")
            if d.startswith("ckpt-") and os.path.exists(mf):
                with open(mf) as f:
                    worlds.add(json.load(f).get("world_size"))
        assert expect["manifest_world"] in worlds, (worlds, dump)
    if expect.get("blackbox"):
        _assert_blackbox(name, bbdir, base_env, expect["blackbox"])
    print("POD-LEADER-VARIANT-OK %s (rcs=%s)"
          % (name, [p.returncode for p in sups]), flush=True)


# --------------------------------------- mid-save leader death drill

def _ckpt_leader_child(ckpt_dir, mode):
    """2-process pod: save 1 commits normally; during save 2 rank 0 is
    SIGKILLed at the armed site (``after-record`` = between shard-
    record publication and manifest commit; ``after-arrays`` = before
    its record exists). Rank 1 must see the save abort as a unit — or
    die with the data plane (the jax client's fatal abort over the
    dead coordination service); both are the host-death shape. The
    DRIVER is the successor that audits."""
    import time as _t
    from mxnet_tpu import faults
    from mxnet_tpu.parallel import dist
    from mxnet_tpu.checkpoint import CheckpointPodError, write_checkpoint
    dist.initialize()
    import jax
    jax.config.update("jax_platforms", "cpu")
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    r, _world = dist.rank(), dist.num_workers()
    if r == 0:
        faults.install("ckpt.%s@2:sigkill" % mode.replace("-", "_"))
    devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    mesh = Mesh(np.array(devs), ("data",))
    full = np.arange(8 * 16, dtype=np.float32).reshape(8, 16)
    arr = jax.make_array_from_callback(
        full.shape, NamedSharding(mesh, P("data", None)),
        lambda idx: full[idx])
    write_checkpoint(ckpt_dir, 1, {"w": arr}, meta={"step": 1})
    if r == 1:
        try:
            write_checkpoint(ckpt_dir, 2, {"w": arr}, meta={"step": 2})
        except CheckpointPodError:
            pass                        # the unit abort — expected
        print("POD-CKPT-LEADER-CHILD-OK rank=1", flush=True)
        sys.stdout.flush()
        os._exit(0)
    # rank 0: give rank 1 time to land its shard record FIRST (the
    # successor audit distinguishes the orderings by which records are
    # durable; a racing mid-write abort is the leave-for-GC case and is
    # covered by the after-arrays ordering)
    _t.sleep(1.5)
    write_checkpoint(ckpt_dir, 2, {"w": arr}, meta={"step": 2})
    raise AssertionError("rank 0 survived its injected SIGKILL")


def _ckpt_leader_phase(work, base_env):
    """Both orderings of the mid-save leader death, audited by the
    driver as the successor leader."""
    from mxnet_tpu.checkpoint import (finalize_staged_pod_saves,
                                      list_checkpoints, load_latest)
    full = np.arange(8 * 16, dtype=np.float32).reshape(8, 16)
    for mode, expect_commit in (("after-record", True),
                                ("after-arrays", False)):
        cdir = os.path.join(work, "ckpt_leader_%s" % mode)
        port = _free_port()
        env = dict(base_env)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        procs = [subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--ckpt-leader-child", cdir, mode],
            env=_dmlc_env(env, r, 2, port), stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True) for r in range(2)]
        outs = [p.communicate(timeout=PHASE_TIMEOUT) for p in procs]
        dump = "\n".join("--- rank %d rc=%s\n%s\n%s"
                         % (i, p.returncode, o[-4000:], e[-4000:])
                         for i, (p, (o, e)) in enumerate(zip(procs,
                                                             outs)))
        assert procs[0].returncode == -signal.SIGKILL, dump
        # clean unit-abort, or the data-plane client's fatal abort over
        # the dead coordination service — both are host-death shapes
        assert procs[1].returncode in (0, -signal.SIGABRT), dump
        steps = [s for s, _p in list_checkpoints(cdir)]
        assert steps == [1], (mode, steps, dump)   # nothing partial
        finalized = finalize_staged_pod_saves(cdir, by_rank=1)
        if expect_commit:
            assert len(finalized) == 1, (mode, finalized, dump)
            _p2, tensors, man = load_latest(cdir)
            assert man["step"] == 2, man["step"]
            assert man["meta"]["pod_commit"]["path"] == "successor", \
                man["meta"]["pod_commit"]
            assert man["meta"]["pod_commit"]["committed_by"] == 1
            np.testing.assert_array_equal(np.asarray(tensors["w"]), full)
        else:
            assert finalized == [], (mode, finalized, dump)
            _p2, _t2, man = load_latest(cdir)
            assert man["step"] == 1, man["step"]   # fell back, not torn
            assert any(n.startswith(".tmp-ckpt-0000000002.pod")
                       for n in os.listdir(cdir)), \
                "aborted staging was not left for GC"
        print("POD-CKPT-LEADER-OK %s" % mode, flush=True)


def main():
    if "--child" in sys.argv:
        i = sys.argv.index("--child")
        return _pod_child(sys.argv[i + 1], sys.argv[i + 2])
    if "--ckpt-child" in sys.argv:
        return _ckpt_child(sys.argv[sys.argv.index("--ckpt-child") + 1])
    if "--ckpt-leader-child" in sys.argv:
        i = sys.argv.index("--ckpt-leader-child")
        return _ckpt_leader_child(sys.argv[i + 1], sys.argv[i + 2])
    if "--baseline" in sys.argv:
        return _pod_child(*sys.argv[sys.argv.index("--baseline") + 1:][:2])
    if "--zero-cost" in sys.argv:
        return _zero_cost()

    work = tempfile.mkdtemp(prefix="pod_smoke_")
    base_env = {**os.environ, "PYTHONPATH": REPO,
                "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "", **KNOBS}
    for k in ("MXNET_TPU_FAULTS", "MXNET_TPU_CKPT_TEST_CRASH",
              "MXNET_TPU_FAULTS_TOUCH", "POD_SMOKE_FAULT",
              "MXNET_TPU_OBS_BLACKBOX", "MXNET_TPU_POD_KV",
              "MXNET_TPU_POD_RANK"):
        base_env.pop(k, None)

    # ---- uninterrupted baseline: a 1-host pod over the full data -----
    baseline = os.path.join(work, "baseline.npz")
    env = _dmlc_env(base_env, 0, 1, _free_port())
    _run([sys.executable, os.path.abspath(__file__), "--baseline",
          os.path.join(work, "baseline_ckpts"), baseline],
         env, PHASE_TIMEOUT)
    assert os.path.exists(baseline)

    # ---- the three failure variants (one retry each: killing tasks
    # under a shared jax coordination service can rarely abort a
    # survivor before it reports — the same allowance test_dist makes)
    variants = [
        ("hostkill", "host.die@%d:hostkill" % DIE_AT,
         {"rc1": (-signal.SIGKILL,), "reshards_min": 1,
          "marker": "host.die@%d:hostkill" % DIE_AT,
          "blackbox": {"first_dead": 1, "fault_site": "host.die"}}),
        ("wedge", "host.die@%d:wedge" % DIE_AT,
         {"rc1": (-signal.SIGKILL,), "frozen": True, "reshards_min": 1,
          "dead_hosts_min": 1,
          "marker": "host.die@%d:wedge" % DIE_AT}),
        ("sigkill-child", "fit.batch@%d:sigkill" % DIE_AT,
         {"rc1": (0,), "reshards_min": 0,
          "marker": "fit.batch@%d:sigkill" % DIE_AT}),
    ]
    for name, fault, expect in variants:
        for attempt in range(2):
            try:
                _variant(name if attempt == 0 else name,
                         fault, base_env,
                         os.path.join(work, "a%d" % attempt), baseline,
                         expect)
                break
            except AssertionError:
                if attempt:
                    raise
                print("POD-VARIANT-RETRY %s" % name, flush=True)

    # ---- leader fail-over variants (3-host pod, ISSUE 12) ------------
    CASCADE_AT = 5
    leader_variants = [
        ("leader-kill", "g0w0=leader.die@%d:hostkill" % DIE_AT, 3,
         {"rc": {0: (-signal.SIGKILL,), 1: (0,), 2: (0,)},
          "recs": {1: {"failovers": 1, "restarts_min": 1,
                       "reshards_min": 1},
                   2: {"failovers": 1, "restarts_min": 1,
                       "reshards_min": 1}},
          "marker": ["leader.die@%d:hostkill" % DIE_AT],
          "manifest_world": 3,
          "blackbox": {"first_dead": 0, "fault_site": "leader.die",
                       "failover_ranks": [1, 2]}}),
        ("leader-cascade",
         "g0w0=leader.die@%d:hostkill;g1w0=leader.die@%d:hostkill"
         % (DIE_AT, CASCADE_AT), 3,
         {"rc": {0: (-signal.SIGKILL,), 1: (-signal.SIGKILL,), 2: (0,)},
          "recs": {2: {"failovers": 2, "restarts_min": 2,
                       "reshards_min": 2}},
          "marker": ["leader.die@%d:hostkill" % DIE_AT,
                     "leader.die@%d:hostkill" % CASCADE_AT]}),
        ("coordsvc", "g0w0=leader.die@%d:coordsvc" % DIE_AT, 3,
         {"rc": {0: (0,), 1: (0,), 2: (0,)},
          "recs": {r: {"failovers": 1, "restarts_min": 1,
                       "reshards_max": 0, "dead_hosts_max": 0}
                   for r in range(3)},
          "marker": ["leader.die@%d:coordsvc" % DIE_AT],
          "env": {"POD_SMOKE_BATCH_SLEEP": "0.3"}}),
    ]
    for name, spec, world, expect in leader_variants:
        for attempt in range(2):
            try:
                _leader_variant(name, spec, world, base_env,
                                os.path.join(work, "l%d" % attempt),
                                baseline, expect)
                break
            except AssertionError:
                if attempt:
                    raise
                print("POD-LEADER-VARIANT-RETRY %s" % name, flush=True)

    # ---- mid-save leader death (successor finalize/abort) ------------
    _ckpt_leader_phase(work, base_env)

    # ---- process-local sharded checkpoint phase ----------------------
    ckpt_dir = os.path.join(work, "sharded_ckpts")
    port = _free_port()
    env = dict(base_env)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--ckpt-child",
         ckpt_dir],
        env=_dmlc_env(env, r, 2, port), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True) for r in range(2)]
    outs = [p.communicate(timeout=PHASE_TIMEOUT) for p in procs]
    dump = "\n".join("--- rank %d rc=%s\n%s\n%s"
                     % (i, p.returncode, o[-4000:], e[-4000:])
                     for i, (p, (o, e)) in enumerate(zip(procs, outs)))
    assert procs[0].returncode == 0, dump
    assert procs[1].returncode == -signal.SIGKILL, dump
    assert "POD-CKPT-CHILD-OK rank=0" in outs[0][0], dump
    # the driver (a 1-process world) reshards the 2-host save onto one
    # device: "read_checkpoint reassembles or reshards across whatever
    # world resumes"
    import jax
    from jax.sharding import Mesh
    from mxnet_tpu.checkpoint import load_latest
    mesh = Mesh(np.array(jax.devices("cpu")[:1]), ("data",))
    _p, tensors, man = load_latest(ckpt_dir, mesh=mesh)
    assert man["world_size"] == 2
    np.testing.assert_array_equal(
        np.asarray(tensors["w"]),
        np.arange(8 * 16, dtype=np.float32).reshape(8, 16))
    print("POD-CKPT-PHASE-OK", flush=True)

    # ---- zero-cost gate ----------------------------------------------
    env = dict(base_env)
    for k in ("DMLC_PS_ROOT_URI", "DMLC_PS_ROOT_PORT",
              "DMLC_NUM_WORKER", "DMLC_WORKER_ID", "DMLC_ROLE"):
        env.pop(k, None)
    proc = _run([sys.executable, os.path.abspath(__file__),
                 "--zero-cost"], env, PHASE_TIMEOUT)
    assert "ZERO-COST-OK" in proc.stdout

    print("POD-DRILL-OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
