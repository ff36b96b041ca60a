"""mx.checkpoint — crash-safety, fault-injection, and exact-resume suite
(docs/architecture/checkpoint.md).

Three contracts under test:

* **atomicity** — ``kill -9`` at ANY byte of a save never damages the
  previous checkpoint (deterministic SIGKILL points via the
  ``MXNET_TPU_CKPT_TEST_CRASH`` hook, in subprocesses);
* **verification** — bit-flips and truncation are detected at load
  (manifest crc32) and ``load_latest`` falls back to the newest VALID
  candidate; retention GC can never delete the only valid checkpoint;
* **exact resume** — ``fit(checkpoint=..., resume_from=...)`` reproduces
  the uninterrupted run's params, aux states, and optimizer states
  bit-identically, at epoch boundaries and mid-epoch, with the async
  window >= 2, on the MLP and the BN+dropout stem (aux + RNG chains).
"""
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config as cfg
from mxnet_tpu import profiler
from mxnet_tpu.checkpoint import (CheckpointConfig, CheckpointCorrupt,
                                  CheckpointManager, CheckpointNotFound,
                                  atomic_open, collect_garbage,
                                  list_checkpoints, load_latest,
                                  probe_valid, read_checkpoint,
                                  write_checkpoint)

BATCH = 8
NSAMP = 64
FEAT = 16
NCLS = 8

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ helpers

def _mlp():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=12, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu", name="relu1")
    fc2 = mx.sym.FullyConnected(act, num_hidden=NCLS, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _stem():
    """Conv + BatchNorm (aux states) + Dropout (executor RNG chain)."""
    data = mx.sym.Variable("data")
    c = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3), pad=(1, 1),
                           name="conv0")
    bn = mx.sym.BatchNorm(c, name="bn0")
    r = mx.sym.Activation(bn, act_type="relu", name="relu0")
    p = mx.sym.Pooling(r, kernel=(2, 2), stride=(2, 2), pool_type="max",
                       name="pool0")
    f = mx.sym.Flatten(p, name="flat")
    dp = mx.sym.Dropout(f, p=0.3, name="drop0")
    fc = mx.sym.FullyConnected(dp, num_hidden=NCLS, name="fc1")
    return mx.sym.SoftmaxOutput(fc, name="softmax")


def _mlp_data():
    rng = np.random.RandomState(0)
    return (rng.uniform(-1, 1, (NSAMP, FEAT)).astype(np.float32),
            rng.randint(0, NCLS, (NSAMP,)).astype(np.float32))


def _stem_data():
    rng = np.random.RandomState(1)
    return (rng.uniform(-1, 1, (NSAMP, 3, 8, 8)).astype(np.float32),
            rng.randint(0, NCLS, (NSAMP,)).astype(np.float32))


def _seed_init(symbol, shapes):
    rng = np.random.RandomState(42)
    args, _, _ = symbol.infer_shape(**shapes)
    init = {}
    for name, shape in zip(symbol.list_arguments(), args):
        if name in shapes:
            continue
        init[name] = mx.nd.array(
            rng.uniform(-0.1, 0.1, shape).astype(np.float32))
    return init


class _Stop(Exception):
    """Simulated crash: abandons fit() from a batch-end callback, exactly
    as abruptly as the loop can be abandoned in-process."""


def _fit(symbol, X, Y, epochs, ckpt=None, resume=None, seed=True,
         stop_after=None, optimizer="sgd", opt_params=None, window=None):
    """One deterministic fit under the checkpoint knobs; returns the
    module's full param+aux dict as numpy."""
    if window is not None:
        cfg.set("MXNET_TPU_ASYNC_WINDOW", window)
    try:
        mx.random.seed(7)
        shapes = {"data": (BATCH,) + X.shape[1:], "softmax_label": (BATCH,)}
        it = mx.io.NDArrayIter(X, Y, batch_size=BATCH)
        mod = mx.mod.Module(symbol, context=mx.cpu())
        kw = {}
        if seed:
            init = _seed_init(symbol, shapes)
            kw["arg_params"] = {k: v.copy() for k, v in init.items()}
        if stop_after is not None:
            calls = [0]

            def cb(_param):
                calls[0] += 1
                if calls[0] >= stop_after:
                    raise _Stop()

            kw["batch_end_callback"] = cb
        try:
            mod.fit(it, num_epoch=epochs, optimizer=optimizer,
                    optimizer_params=opt_params
                    or {"learning_rate": 0.1},
                    checkpoint=ckpt, resume_from=resume, **kw)
        except _Stop:
            pass
        arg, aux = mod.get_params()
        w = {k: v.asnumpy().copy() for k, v in arg.items()}
        w.update({k: v.asnumpy().copy() for k, v in aux.items()})
        return mod, w
    finally:
        if window is not None:
            cfg.reset("MXNET_TPU_ASYNC_WINDOW")


def _assert_equal(w0, w1):
    assert set(w0) == set(w1)
    for k in sorted(w0):
        np.testing.assert_array_equal(w0[k], w1[k], err_msg=k)


def _tensors(step=1):
    rng = np.random.RandomState(step)
    return {"w": rng.normal(size=(32, 16)).astype(np.float32),
            "b": rng.normal(size=(16,)).astype(np.float32)}


# ----------------------------------------------------------- atomic writes

def test_atomic_open_replaces_only_on_success(tmp_path):
    p = str(tmp_path / "f.bin")
    with atomic_open(p, "wb") as f:
        f.write(b"first")
    assert open(p, "rb").read() == b"first"

    with pytest.raises(RuntimeError):
        with atomic_open(p, "wb") as f:
            f.write(b"torn-half-")
            raise RuntimeError("crash mid-write")
    # previous contents intact, no temp residue
    assert open(p, "rb").read() == b"first"
    assert os.listdir(str(tmp_path)) == ["f.bin"]


def test_atomic_open_rejects_read_modes(tmp_path):
    with pytest.raises(ValueError):
        with atomic_open(str(tmp_path / "x"), "r+b"):
            pass


def test_nd_save_failure_preserves_previous_file(tmp_path, monkeypatch):
    p = str(tmp_path / "params.npz")
    mx.nd.save(p, {"a": mx.nd.ones((3,))})

    def boom(*_a, **_k):
        raise OSError("disk on fire")

    monkeypatch.setattr(np, "savez", boom)
    with pytest.raises(OSError):
        mx.nd.save(p, {"a": mx.nd.zeros((3,))})
    monkeypatch.undo()
    out = mx.nd.load(p)                       # old file still loads clean
    np.testing.assert_array_equal(out["a"].asnumpy(), np.ones((3,)))


def test_symbol_and_model_checkpoint_still_roundtrip(tmp_path):
    prefix = str(tmp_path / "model")
    sym = _mlp()
    arg = {"fc1_weight": mx.nd.ones((12, FEAT))}
    mx.model.save_checkpoint(prefix, 3, sym, arg, {})
    s2, a2, x2 = mx.model.load_checkpoint(prefix, 3)
    assert s2.list_arguments() == sym.list_arguments()
    np.testing.assert_array_equal(a2["fc1_weight"].asnumpy(),
                                  arg["fc1_weight"].asnumpy())
    assert x2 == {}


# --------------------------------------------------------- format + verify

def test_write_read_roundtrip_and_meta(tmp_path):
    base = str(tmp_path)
    t = _tensors()
    write_checkpoint(base, 7, t, meta={"loop": {"epoch": 2,
                                                "batches_done": 5}})
    path, tensors, manifest = load_latest(base)
    assert path.endswith("ckpt-0000000007")
    _assert_equal(tensors, {k: np.asarray(v) for k, v in t.items()})
    assert manifest["meta"]["loop"]["batches_done"] == 5


def test_corruption_detected_and_fallback_to_previous(tmp_path):
    base = str(tmp_path)
    write_checkpoint(base, 1, _tensors(1))
    p2 = write_checkpoint(base, 2, _tensors(2))
    # flip one payload byte deep inside the newest arrays container
    arrays = os.path.join(p2, "arrays.npz")
    blob = bytearray(open(arrays, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(arrays, "wb").write(bytes(blob))

    with pytest.raises(CheckpointCorrupt):
        read_checkpoint(p2)
    before = profiler.get_counter("ckpt_load_fallback")
    path, tensors, _ = load_latest(base)
    assert path.endswith("ckpt-0000000001")
    _assert_equal(tensors, {k: np.asarray(v)
                            for k, v in _tensors(1).items()})
    assert profiler.get_counter("ckpt_load_fallback") == before + 1


def test_manifest_tamper_and_truncation_rejected(tmp_path):
    base = str(tmp_path)
    p = write_checkpoint(base, 1, _tensors())
    man_path = os.path.join(p, "manifest.json")
    man = json.load(open(man_path))

    man["arrays"]["w"]["shape"] = [1, 1]          # shape drift
    json.dump(man, open(man_path, "w"))
    with pytest.raises(CheckpointCorrupt):
        read_checkpoint(p)

    open(man_path, "w").write("{half a manif")    # truncation
    with pytest.raises(CheckpointCorrupt):
        read_checkpoint(p)
    assert not probe_valid(p)
    with pytest.raises(CheckpointNotFound):
        load_latest(base)


def test_probe_valid_catches_truncated_arrays(tmp_path):
    base = str(tmp_path)
    p = write_checkpoint(base, 1, _tensors())
    assert probe_valid(p)
    arrays = os.path.join(p, "arrays.npz")
    blob = open(arrays, "rb").read()
    open(arrays, "wb").write(blob[:len(blob) // 2])
    assert not probe_valid(p)


def test_corrupt_tensor_table_stays_in_fallback_chain(tmp_path):
    """Bit rot inside the manifest's tensor TABLE (JSON parses, the
    arrays-set and crc checks still pass) must surface as
    CheckpointCorrupt — a raw KeyError would break load_latest's
    fallback chain."""
    base = str(tmp_path)
    write_checkpoint(base, 1, _tensors(1))
    p2 = write_checkpoint(base, 2, _tensors(2))
    man_path = os.path.join(p2, "manifest.json")
    man = json.load(open(man_path))
    man["tensors"]["w"]["key"] = "nonexistent"
    json.dump(man, open(man_path, "w"))
    with pytest.raises(CheckpointCorrupt):
        read_checkpoint(p2)
    path, _, _ = load_latest(base)
    assert path.endswith("ckpt-0000000001")


def test_rewrite_replaces_invalid_existing_step(tmp_path):
    """A valid ckpt-<step> makes a same-step re-save a no-op, but a
    corrupt one (the checkpoint resume just fell back PAST) must not
    block re-checkpointing the retraced step forever."""
    base = str(tmp_path)
    p = write_checkpoint(base, 1, _tensors(1))
    write_checkpoint(base, 1, _tensors(2))        # skipped: valid exists
    tensors, _ = read_checkpoint(p)
    _assert_equal(tensors, {k: np.asarray(v)
                            for k, v in _tensors(1).items()})
    open(os.path.join(p, "manifest.json"), "w").write("{")
    assert not probe_valid(p)
    write_checkpoint(base, 1, _tensors(3))        # replaces the corpse
    assert probe_valid(p)
    tensors, _ = read_checkpoint(p)
    _assert_equal(tensors, {k: np.asarray(v)
                            for k, v in _tensors(3).items()})


def test_resume_payload_preserves_dtype(tmp_path):
    """arg/aux payloads must round-trip at the SAVED precision —
    nd.array's default would silently cast everything to float32."""
    from mxnet_tpu.checkpoint import restore_latest
    base = str(tmp_path)
    t = {"arg:w64": np.arange(4, dtype=np.float64),
         "arg:w16": np.ones((3,), dtype=np.float16)}
    write_checkpoint(base, 1, t, meta={"param_names": ["w64", "w16"]})
    ck = restore_latest(base)
    nd_args = ck.arg_params_nd()
    assert nd_args["w16"].dtype == np.float16
    # f64 models only exist under x64 (jax stores f32 otherwise), so the
    # f64 leg of the round-trip is asserted there
    import jax
    with jax.enable_x64():
        nd64 = ck.arg_params_nd()["w64"]
        assert nd64.dtype == np.float64
        np.testing.assert_array_equal(nd64.asnumpy(), t["arg:w64"])


def test_no_optimizer_saves_are_not_deduped(tmp_path):
    """A bound-but-no-optimizer snapshot reports step 0 every time; the
    one-state-per-step dedup must not silently drop later saves."""
    class _FakeMod:
        def __init__(self):
            self.v = 0

        def _checkpoint_snapshot(self):
            self.v += 1
            return {"w": np.full((2,), self.v, np.float32)}, {"step": 0}

    mgr = CheckpointManager(CheckpointConfig(str(tmp_path),
                                             async_save=False))
    fm = _FakeMod()
    s1 = mgr.save_module(fm, epoch=0)
    s2 = mgr.save_module(fm, epoch=1)
    assert s2 > s1
    assert len(list_checkpoints(str(tmp_path))) == 2
    _, tensors, _ = load_latest(str(tmp_path))
    assert tensors["w"][0] == 2                   # newest payload won
    mgr.close()


def test_atomic_open_reaps_dead_writer_temps(tmp_path):
    """kill -9 mid-save leaves a hidden temp next to the target; the
    next save of the SAME artifact must reap it (dead pid in the name)
    instead of letting full-size orphans accumulate forever."""
    target = str(tmp_path / "x.bin")
    stale = str(tmp_path / ".x.bin.tmp-999999999-abcd")
    open(stale, "wb").write(b"orphan")
    with atomic_open(target, "wb") as f:
        f.write(b"data")
    assert not os.path.exists(stale)
    assert open(target, "rb").read() == b"data"


def test_atomic_open_honors_umask_permissions(tmp_path):
    """mkstemp creates 0600; the rename must not silently demote
    artifacts from the umask-derived mode plain open() would give."""
    p = str(tmp_path / "artifact.bin")
    with atomic_open(p, "wb") as f:
        f.write(b"payload")
    umask = os.umask(0)
    os.umask(umask)
    assert (os.stat(p).st_mode & 0o777) == (0o666 & ~umask)


# ------------------------------------------------- SIGKILL fault injection

_CRASH_CHILD = r"""
import os, sys
sys.path.insert(0, %(repo)r)
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
from mxnet_tpu.checkpoint import write_checkpoint
base = %(base)r
rng = np.random.RandomState(0)
t = {"w": rng.normal(size=(64, 32)).astype(np.float32)}
write_checkpoint(base, 1, t)                      # clean previous ckpt
os.environ["MXNET_TPU_CKPT_TEST_CRASH"] = %(point)r
write_checkpoint(base, 2, t)                      # SIGKILLed mid-write
print("NOT-REACHED")
"""


@pytest.mark.parametrize("point", ["after_arrays", "after_manifest",
                                   "before_rename"])
def test_sigkill_mid_write_never_loses_previous(tmp_path, point):
    """kill -9 at every deterministic point of the write protocol: the
    previous checkpoint stays the newest loadable state and the residue
    is a .tmp-* directory readers never consider."""
    base = str(tmp_path)
    proc = subprocess.run(
        [sys.executable, "-c",
         _CRASH_CHILD % {"repo": REPO, "base": base, "point": point}],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "PYTHONPATH": ""})
    assert proc.returncode == -signal.SIGKILL, proc.stdout + proc.stderr
    assert "NOT-REACHED" not in proc.stdout

    assert [s for s, _ in list_checkpoints(base)] == [1]
    path, tensors, _ = load_latest(base)
    assert path.endswith("ckpt-0000000001")
    assert tensors["w"].shape == (64, 32)
    # the dead writer left a .tmp residue; GC reaps it (pid is gone)
    residue = [n for n in os.listdir(base) if n.startswith(".tmp-")]
    assert residue
    collect_garbage(base, keep_last=5)
    assert not [n for n in os.listdir(base) if n.startswith(".tmp-")]


# ------------------------------------------------------------ retention GC

def test_gc_keep_last_and_keep_every(tmp_path):
    base = str(tmp_path)
    for s in range(1, 11):
        write_checkpoint(base, s, _tensors(s))
    removed = collect_garbage(base, keep_last=2, keep_every=4)
    steps = [s for s, _ in list_checkpoints(base)]
    assert steps == [4, 8, 9, 10]          # keep-every multiples + last 2
    assert removed == 6


def test_gc_never_deletes_only_valid_checkpoint(tmp_path):
    base = str(tmp_path)
    p1 = write_checkpoint(base, 1, _tensors(1))
    write_checkpoint(base, 2, _tensors(2))
    p3 = write_checkpoint(base, 3, _tensors(3))
    # corrupt the two newest: the single valid one must survive ANY quota
    for p in (p3,):
        open(os.path.join(p, "arrays.npz"), "wb").write(b"junk")
    open(os.path.join(p1, "manifest.json"), "w").write("{")
    collect_garbage(base, keep_last=1)
    steps = [s for s, _ in list_checkpoints(base)]
    assert 2 in steps                      # the only valid one survived
    path, _, _ = load_latest(base)
    assert path.endswith("ckpt-0000000002")
    # corrupt candidates are left for the operator, never auto-deleted
    assert set(steps) == {1, 2, 3}


def test_gc_disabled_and_knob_default(tmp_path):
    base = str(tmp_path)
    for s in range(1, 4):
        write_checkpoint(base, s, _tensors(s))
    assert collect_garbage(base, keep_last=0) == 0
    assert len(list_checkpoints(base)) == 3
    c = CheckpointConfig(base)
    assert c.resolved_keep_last() == cfg.get("MXNET_TPU_CKPT_KEEP")
    assert c.resolved_async() == cfg.get("MXNET_TPU_CKPT_ASYNC")


# --------------------------------------------------------- manager lifecycle

def test_async_write_error_surfaces_at_close(tmp_path):
    blocker = str(tmp_path / "blocker")
    open(blocker, "w").write("a file where the base dir must go")
    mgr = CheckpointManager(CheckpointConfig(
        os.path.join(blocker, "sub"), async_save=True))
    mgr.save({"w": np.ones((4,), np.float32)}, {}, step=1)
    with pytest.raises(mx.checkpoint.CheckpointError):
        mgr.close()
    assert profiler.get_counter("ckpt_write_failed") >= 1


def test_sync_save_blocks_and_writes(tmp_path):
    mgr = CheckpointManager(CheckpointConfig(str(tmp_path),
                                             async_save=False))
    mgr.save({"w": np.ones((4,), np.float32)}, {"k": 1}, step=5)
    assert [s for s, _ in list_checkpoints(str(tmp_path))] == [5]
    mgr.close()


def test_async_blocking_is_fraction_of_write_time(tmp_path):
    """The CheckFreq split, counter-asserted: an async save blocks the
    caller for well under 25%% of the background serialization time (the
    arrays are big enough that npz+crc+fsync dominates queue handoff).
    The writer is drained between saves — real checkpoint periods dwarf
    the write time; back-to-back saturation (bounded-queue backpressure)
    is exercised separately below."""
    mgr = CheckpointManager(CheckpointConfig(str(tmp_path),
                                             async_save=True,
                                             keep_last=0))
    rng = np.random.RandomState(0)
    tensors = {"w%d" % i: rng.normal(size=(256, 256)).astype(np.float32)
               for i in range(8)}          # ~2 MB per save
    with profiler.counter_delta() as d:
        for step in range(1, 6):
            mgr.save(dict(tensors), {}, step=step)
            mgr.wait()
    mgr.close()
    block, write = d.get("ckpt_block_us"), d.get("ckpt_write_us")
    assert write > 0 and d.get("ckpt_saved") == 5
    assert block < 0.25 * write, \
        "async save blocked %dus vs %dus write time" % (block, write)


def test_async_backpressure_bounds_queue(tmp_path):
    """Back-to-back saves past the queue depth must block (bounded
    memory: each queued snapshot pins a parameter generation) and be
    counted, not dropped — every save still reaches disk."""
    mgr = CheckpointManager(CheckpointConfig(str(tmp_path),
                                             async_save=True, keep_last=0,
                                             queue_depth=1))
    rng = np.random.RandomState(0)
    tensors = {"w": rng.normal(size=(512, 512)).astype(np.float32)}
    with profiler.counter_delta() as d:
        for step in range(1, 7):
            mgr.save(dict(tensors), {}, step=step)
        mgr.wait()
    mgr.close()
    assert d.get("ckpt_saved") == 6
    assert d.get("ckpt_backpressure_wait") >= 1
    assert [s for s, _ in list_checkpoints(str(tmp_path))] == \
        list(range(1, 7))


# ------------------------------------------------------------ exact resume

def test_resume_epoch_boundary_parity_mlp(tmp_path):
    X, Y = _mlp_data()
    _, w_ref = _fit(_mlp(), X, Y, epochs=4)
    ckpt = CheckpointConfig(str(tmp_path), period_epochs=1)
    _fit(_mlp(), X, Y, epochs=2, ckpt=ckpt)
    assert list_checkpoints(str(tmp_path))
    _, w_res = _fit(_mlp(), X, Y, epochs=4, ckpt=ckpt,
                    resume=str(tmp_path), seed=False)
    _assert_equal(w_ref, w_res)


def test_resume_mid_epoch_parity_mlp(tmp_path):
    """Killed mid-epoch-1 after a scheduled batch save: the resumed run
    restores loop position + RNG + optimizer state and replays the tail
    bit-identically (params AND optimizer states)."""
    X, Y = _mlp_data()
    ref_mod, w_ref = _fit(_mlp(), X, Y, epochs=2)
    ckpt = CheckpointConfig(str(tmp_path), every_n_batches=3,
                            period_epochs=1)
    _fit(_mlp(), X, Y, epochs=2, ckpt=ckpt, stop_after=11)
    res_mod, w_res = _fit(_mlp(), X, Y, epochs=2, ckpt=ckpt,
                          resume=str(tmp_path), seed=False)
    _assert_equal(w_ref, w_res)
    # optimizer-state parity, leaf by leaf
    ref_states = ref_mod._fused_states
    res_states = res_mod._fused_states
    assert set(ref_states) == set(res_states)
    import jax
    for n in ref_states:
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=n),
            ref_states[n], res_states[n])


def test_resume_parity_bn_stem_async_window2(tmp_path):
    """The hard case from the acceptance criteria: BatchNorm aux states +
    dropout (executor PRNG chain) + adam state tuples, with the async
    in-flight window at depth 2, killed mid-epoch."""
    X, Y = _stem_data()
    _, w_ref = _fit(_stem(), X, Y, epochs=3, optimizer="adam",
                    opt_params={"learning_rate": 0.01}, window=2)
    ckpt = CheckpointConfig(str(tmp_path), every_n_batches=5,
                            period_epochs=1)
    _fit(_stem(), X, Y, epochs=3, ckpt=ckpt, stop_after=13,
         optimizer="adam", opt_params={"learning_rate": 0.01}, window=2)
    _, w_res = _fit(_stem(), X, Y, epochs=3, ckpt=ckpt,
                    resume=str(tmp_path), seed=False, optimizer="adam",
                    opt_params={"learning_rate": 0.01}, window=2)
    _assert_equal(w_ref, w_res)


def test_resume_from_empty_directory_raises(tmp_path):
    X, Y = _mlp_data()
    with pytest.raises(CheckpointNotFound):
        _fit(_mlp(), X, Y, epochs=1, resume=str(tmp_path))


def test_checkpoint_config_accepts_pathlike(tmp_path):
    c = CheckpointConfig.coerce(tmp_path)          # a pathlib.Path
    assert c.directory == str(tmp_path)


def test_preempt_save_survives_stale_async_error(tmp_path):
    """A stale async-write failure from earlier in the run must not
    abort the SIGTERM exit-143 protocol once the final synchronous save
    has landed."""
    class _FakeMod:
        def _checkpoint_snapshot(self):
            return {"w": np.zeros((2,), np.float32)}, {"step": 1}

    mgr = CheckpointManager(CheckpointConfig(str(tmp_path)))
    mgr._last_error = RuntimeError("earlier async write failed")
    mgr.preempt_save(_FakeMod(), epoch=0)          # must NOT raise
    assert [s for s, _ in list_checkpoints(str(tmp_path))] == [1]


def test_metric_state_roundtrip():
    m = mx.metric.Accuracy()
    m.sum_metric, m.num_inst = 13.0, 42
    state = m._ckpt_state()
    m2 = mx.metric.Accuracy()
    assert m2._ckpt_restore(state)
    assert (m2.sum_metric, m2.num_inst) == (13.0, 42)

    comp = mx.metric.CompositeEvalMetric(
        metrics=[mx.metric.Accuracy(), mx.metric.MSE()])
    comp.metrics[0].sum_metric = 3.0
    comp.metrics[1].num_inst = 9
    state = comp._ckpt_state()
    comp2 = mx.metric.CompositeEvalMetric(
        metrics=[mx.metric.Accuracy(), mx.metric.MSE()])
    assert comp2._ckpt_restore(state)
    assert comp2.metrics[0].sum_metric == 3.0
    assert comp2.metrics[1].num_inst == 9
    assert not comp2._ckpt_restore({"kind": "scalar"})   # shape mismatch


def test_composite_metric_restore_is_all_or_nothing():
    """A child failing to restore must not leave its siblings holding the
    snapshot totals while it reports tail-only — on any child failure the
    WHOLE composite resets to the consistent tail-only state."""
    comp = mx.metric.CompositeEvalMetric(
        metrics=[mx.metric.Accuracy(), mx.metric.MSE()])
    comp.metrics[0].sum_metric, comp.metrics[0].num_inst = 3.0, 4
    state = comp._ckpt_state()
    state["children"][1] = {"kind": "bogus"}      # child 1 can't consume
    comp2 = mx.metric.CompositeEvalMetric(
        metrics=[mx.metric.Accuracy(), mx.metric.MSE()])
    assert not comp2._ckpt_restore(state)
    assert comp2.metrics[0].sum_metric == 0.0     # child 0 rolled back
    assert comp2.metrics[0].num_inst == 0


# --------------------------------------- updater round trip (fused trainer)

def test_updater_states_roundtrip_under_fused_trainer():
    """get_states/set_states mid-training under the FUSED eager-update
    path (Module.update -> FusedUpdater): the restored run must continue
    bit-identically, and the restored leaves must be NDArray-wrapped
    OWNED buffers (no aliasing into the pickled blob)."""
    X, Y = _mlp_data()
    shapes = {"data": (BATCH, FEAT), "softmax_label": (BATCH,)}
    init = _seed_init(_mlp(), shapes)

    def make_module():
        mod = mx.mod.Module(_mlp(), context=mx.cpu())
        mod.bind(data_shapes=[("data", (BATCH, FEAT))],
                 label_shapes=[("softmax_label", (BATCH,))])
        mod.init_params(arg_params={k: v.copy() for k, v in init.items()})
        mod.init_optimizer(optimizer="adam",
                           optimizer_params={"learning_rate": 0.01})
        return mod

    def step(mod, i):
        batch = mx.io.DataBatch(
            data=[mx.nd.array(X[i * BATCH:(i + 1) * BATCH])],
            label=[mx.nd.array(Y[i * BATCH:(i + 1) * BATCH])])
        mod.forward(batch, is_train=True)
        mod.backward()
        mod.update()

    # uninterrupted: 6 eager-fused steps
    ref = make_module()
    for i in range(6):
        step(ref, i % 4)
    ref_w, _ = ref.get_params()

    # interrupted at step 3: round trip the updater blob, continue
    a = make_module()
    for i in range(3):
        step(a, i % 4)
    blob = a._updater.get_states()
    state_a, _ = a.get_params()

    b = make_module()
    for i in range(3):
        step(b, i % 4)
    b._updater.set_states(blob)
    for idx, st in b._updater.states.items():
        def check(leaf):
            if leaf is None:
                return
            assert isinstance(leaf, mx.nd.NDArray), \
                "restored leaf %r not rewrapped" % (idx,)
        if isinstance(st, tuple):
            for leaf in st:
                check(leaf)
        else:
            check(st)
    for i in range(3, 6):
        step(b, i % 4)
    b_w, _ = b.get_params()
    for k in ref_w:
        np.testing.assert_array_equal(ref_w[k].asnumpy(),
                                      b_w[k].asnumpy(), err_msg=k)


def test_fused_module_optimizer_states_file_roundtrip(tmp_path):
    """Module.save/load_optimizer_states on the fused-step pytree path,
    mid-training, continues bit-identically (and the file write is
    atomic)."""
    X, Y = _mlp_data()
    fname = str(tmp_path / "opt.states")
    _, w_ref = _fit(_mlp(), X, Y, epochs=2, optimizer="adam",
                    opt_params={"learning_rate": 0.01})

    mx.random.seed(7)
    shapes = {"data": (BATCH, FEAT), "softmax_label": (BATCH,)}
    init = _seed_init(_mlp(), shapes)
    it = mx.io.NDArrayIter(X, Y, batch_size=BATCH)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="adam",
            optimizer_params={"learning_rate": 0.01},
            arg_params={k: v.copy() for k, v in init.items()})
    mod.save_optimizer_states(fname)
    arg, aux = mod.get_params()

    mx.random.seed(7)
    it2 = mx.io.NDArrayIter(X, Y, batch_size=BATCH)
    mod2 = mx.mod.Module(_mlp(), context=mx.cpu())
    mod2.fit(it2, num_epoch=1, optimizer="adam",
             optimizer_params={"learning_rate": 0.01},
             arg_params={k: v.copy() for k, v in init.items()})
    mod2.load_optimizer_states(fname)
    # continue one epoch on each; they must stay in lockstep
    it.reset()
    it2.reset()
    for m, data in ((mod, it), (mod2, it2)):
        for batch in data:
            m._fit_step(batch)
    w1, _ = mod.get_params()
    w2, _ = mod2.get_params()
    for k in w1:
        np.testing.assert_array_equal(w1[k].asnumpy(), w2[k].asnumpy(),
                                      err_msg=k)


def test_dealias_states_copies_shared_buffers():
    """Donation safety: a state leaf sharing a weight's buffer (or
    another state's) must be copied before a donating fused call."""
    import jax.numpy as jnp
    from mxnet_tpu._fused import _dealias_states
    w = jnp.ones((4,))
    s_alias = w                     # the Test-optimizer aliasing shape
    s_own = jnp.zeros((4,))
    out = _dealias_states([w], [s_alias, (s_own, s_own), None])
    assert out[0] is not w and np.array_equal(np.asarray(out[0]),
                                              np.asarray(w))
    first, second = out[1]
    assert first is s_own and second is not s_own   # intra-state dedup
    assert out[2] is None


# ------------------------------------------------- mesh / sharded save-load

def test_sharded_checkpoint_roundtrip_tp_mesh(tmp_path):
    """A tensor-parallel module saves partitioned params per shard with
    index windows in the manifest; resume reassembles and re-shards them
    and the run continues bit-identically with the uninterrupted mesh
    run."""
    from mxnet_tpu.parallel import P
    X, Y = _mlp_data()
    shardings = {"fc1_weight": P("model", None), "fc1_bias": P("model")}

    def run(epochs, ckpt=None, resume=None, seed=True):
        mx.random.seed(7)
        shapes = {"data": (BATCH, FEAT), "softmax_label": (BATCH,)}
        it = mx.io.NDArrayIter(X, Y, batch_size=BATCH)
        mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(4)],
                            mesh_shape={"data": 2, "model": 2},
                            param_shardings=shardings)
        kw = {}
        if seed:
            init = _seed_init(_mlp(), shapes)
            kw["arg_params"] = {k: v.copy() for k, v in init.items()}
        mod.fit(it, num_epoch=epochs, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1},
                checkpoint=ckpt, resume_from=resume, **kw)
        arg, aux = mod.get_params()
        return {k: v.asnumpy().copy() for k, v in arg.items()}

    w_ref = run(3)
    ckpt = CheckpointConfig(str(tmp_path), period_epochs=1)
    run(2, ckpt=ckpt)
    # the manifest records fc1_weight as a sharded tensor with windows
    path, _, manifest = load_latest(str(tmp_path))
    entry = manifest["tensors"]["arg:fc1_weight"]
    assert entry["kind"] == "sharded"
    assert entry["mesh"] == {"data": 2, "model": 2}
    assert len(entry["shards"]) == 2       # 2-way model split, data-replicated
    w_res = run(3, ckpt=ckpt, resume=str(tmp_path), seed=False)
    _assert_equal(w_ref, w_res)


# ----------------------------------------------- preemption + kill -9 smoke

_SIGTERM_CHILD = r"""
import os, signal, sys
sys.path.insert(0, %(repo)r)
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
import mxnet_tpu as mx

rng = np.random.RandomState(0)
X = rng.uniform(-1, 1, (64, 16)).astype(np.float32)
Y = rng.randint(0, 8, (64,)).astype(np.float32)

data = mx.sym.Variable("data")
fc1 = mx.sym.FullyConnected(data, num_hidden=12, name="fc1")
act = mx.sym.Activation(fc1, act_type="relu", name="relu1")
fc2 = mx.sym.FullyConnected(act, num_hidden=8, name="fc2")
sym = mx.sym.SoftmaxOutput(fc2, name="softmax")

r42 = np.random.RandomState(42)
args, _, _ = sym.infer_shape(data=(8, 16), softmax_label=(8,))
init = {n: mx.nd.array(r42.uniform(-0.1, 0.1, s).astype(np.float32))
        for n, s in zip(sym.list_arguments(), args)
        if n not in ("data", "softmax_label")}

mx.random.seed(7)
it = mx.io.NDArrayIter(X, Y, batch_size=8)
mod = mx.mod.Module(sym, context=mx.cpu())
calls = [0]
def cb(param):
    calls[0] += 1
    if calls[0] == 10:        # "preemption notice" mid-epoch-1
        os.kill(os.getpid(), signal.SIGTERM)
cfg = mx.checkpoint.CheckpointConfig(%(base)r, period_epochs=1,
                                     save_on_sigterm=True)
mod.fit(it, num_epoch=50, optimizer="sgd",
        optimizer_params={"learning_rate": 0.1},
        arg_params={k: v.copy() for k, v in init.items()},
        checkpoint=cfg, batch_end_callback=cb)
print("FINISHED-WITHOUT-PREEMPT")
"""


def test_sigterm_preemption_saves_and_exits_143(tmp_path):
    """SIGTERM during fit: the loop finishes the batch, lands a
    synchronous checkpoint, and exits 143; the checkpoint resumes into a
    run bit-identical to an uninterrupted one."""
    base = str(tmp_path)
    proc = subprocess.run(
        [sys.executable, "-c",
         _SIGTERM_CHILD % {"repo": REPO, "base": base}],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": ""})
    assert proc.returncode == 143, proc.stdout + proc.stderr
    assert "FINISHED-WITHOUT-PREEMPT" not in proc.stdout
    assert profiler is not None
    entries = list_checkpoints(base)
    assert entries, "preemption save did not land"
    ckpt = mx.checkpoint.restore_latest(base)
    # the SIGTERM landed mid-epoch-1 (batch 10 of 8-per-epoch)
    assert ckpt.mid_epoch and ckpt.epoch == 1

    X, Y = _mlp_data()
    _, w_ref = _fit(_mlp(), X, Y, epochs=3)
    _, w_res = _fit(_mlp(), X, Y, epochs=3, resume=base, seed=False)
    _assert_equal(w_ref, w_res)


@pytest.mark.slow
def test_kill9_resume_smoke_script():
    """The CI smoke end-to-end: SIGKILL lands DURING an async checkpoint
    write, the torn candidate is skipped, and the resumed run matches the
    uninterrupted one bit-identically (tools/ckpt_kill_resume_smoke.py)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "ckpt_kill_resume_smoke.py")],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "PYTHONPATH": "", "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "KILL-RESUME-PARITY-OK" in proc.stdout
