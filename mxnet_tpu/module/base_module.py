"""BaseModule — the abstract training-loop owner.

Reference: ``python/mxnet/module/base_module.py`` — ``BaseModule`` (line 80)
defines the high-level API (``fit:376``, ``score:213``, ``predict:300``,
``forward_backward:189``) over the abstract bind/forward/backward/update
primitives its subclasses implement.
"""
from __future__ import annotations

import logging
import os
import time
from typing import List, Optional

import numpy as np

from ..base import MXNetError
from .. import faults as _faults
from .. import metric as _metric
from .. import ndarray as nd
from .. import profiler as _profiler
from ..model import BatchEndParam


# the flight-recorder gate (one implementation: profiler.blackbox —
# zero-import when the knob is off). fit() records only at terminal
# moments (preemption, NANCHECK abort) and epoch boundaries — never
# per batch.
_blackbox = _profiler.blackbox

__all__ = ["BaseModule"]


def _as_list(obj):
    if obj is None:
        return []
    if isinstance(obj, (list, tuple)):
        return list(obj)
    return [obj]


def _check_input_names(symbol, names, typename, throw):
    """(reference: base_module.py:33 _check_input_names)."""
    args = set(symbol.list_arguments())
    for name in names:
        if name in args:
            continue
        msg = "You created Module with Module(..., %s_names=%s) but input with name '%s' is not found in symbol.list_arguments(). Did you mean one of:\n\t%s" % (
            typename, str(names), name, "\n\t".join(sorted(args)))
        if throw:
            raise ValueError(msg)
        logging.warning(msg)


class BaseModule(object):
    """The base class of a module (reference: base_module.py:80).

    A module has:
    - binding state (``binded``, ``params_initialized``, ``optimizer_initialized``)
    - data-shape introspection (``data_shapes``, ``label_shapes``, ``output_shapes``)
    - parameter access (``get_params``, ``set_params``, ``init_params``)
    - computation (``forward``, ``backward``, ``update``, ``get_outputs``)
    - and the canonical training loop ``fit``.
    """

    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # ---------------------------------------------------------- properties
    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self) -> List[str]:
        raise NotImplementedError()

    @property
    def output_names(self) -> List[str]:
        raise NotImplementedError()

    @property
    def data_shapes(self):
        raise NotImplementedError()

    @property
    def label_shapes(self):
        raise NotImplementedError()

    @property
    def output_shapes(self):
        raise NotImplementedError()

    # ---------------------------------------------------------- parameters
    def get_params(self):
        raise NotImplementedError()

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        """(reference: base_module.py set_params)."""
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init, allow_extra=allow_extra)

    def save_params(self, fname: str):
        """(reference: base_module.py save_params)."""
        arg_params, aux_params = self.get_params()
        save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
        save_dict.update({("aux:%s" % k): v for k, v in aux_params.items()})
        nd.save(fname, save_dict)

    def load_params(self, fname: str):
        """(reference: base_module.py load_params)."""
        save_dict = nd.load(fname)
        arg_params, aux_params = {}, {}
        for k, value in save_dict.items():
            arg_type, name = k.split(":", 1)
            if arg_type == "arg":
                arg_params[name] = value
            elif arg_type == "aux":
                aux_params[name] = value
            else:
                raise ValueError("Invalid param file " + fname)
        self.set_params(arg_params, aux_params)

    # ---------------------------------------------------------- computation
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError()

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        raise NotImplementedError()

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError()

    def forward_backward(self, data_batch):
        """A convenient function that calls both forward and backward
        (reference: base_module.py:189)."""
        self.forward(data_batch, is_train=True)
        self.backward()

    def install_monitor(self, mon):
        raise NotImplementedError()

    # ---------------------------------------------------------- evaluation
    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0):
        """Run prediction on ``eval_data`` and evaluate (reference:
        base_module.py:213)."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        if not isinstance(eval_metric, _metric.EvalMetric):
            eval_metric = _metric.create(eval_metric)
        eval_metric.reset()
        update_device = getattr(self, "_update_metric_device", None)
        actual_num_batch = 0
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            # device-resident accumulation when the metric supports it:
            # the eval loop then never syncs per batch either (the host
            # fetch happens once, in get_name_value below)
            if update_device is None or \
                    not update_device(eval_metric, eval_batch.label):
                self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                params = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                       eval_metric=eval_metric, locals=locals())
                for callback in _as_list(batch_end_callback):
                    callback(params)
            actual_num_batch += 1
        if score_end_callback is not None:
            params = BatchEndParam(epoch=epoch, nbatch=actual_num_batch,
                                   eval_metric=eval_metric, locals=locals())
            for callback in _as_list(score_end_callback):
                callback(params)
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        """(reference: base_module.py iter_predict)."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad] for out in self.get_outputs()]
            yield outputs, nbatch, eval_batch

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        """Run prediction, collecting outputs (reference: base_module.py:300)."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad].copy()
                       for out in self.get_outputs()]
            output_list.append(outputs)
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                if len(out) != num_outputs:
                    raise ValueError("Cannot merge batches: different numbers "
                                     "of outputs per batch")
            output_list2 = [nd.concatenate([out[i] for out in output_list])
                            for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    # ---------------------------------------------------------- training
    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=None, arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, checkpoint=None, resume_from=None,
            grad_accum=None, layout=None, tune=None):
        """Train the module (reference: base_module.py:376 — the canonical
        forward_backward → update → update_metric loop with epoch/batch
        callbacks and checkpointing hooks).

        Crash-safe checkpointing (docs/architecture/checkpoint.md):
        ``checkpoint=`` takes a ``mx.checkpoint.CheckpointConfig`` (or a
        bare directory path) and auto-saves atomic, verifiable checkpoints
        on the configured schedule — every N-th epoch end, optionally
        every N batches mid-epoch (the in-flight window is drained first
        so the snapshot is a step boundary), and on SIGTERM (preemption:
        the current batch finishes, a synchronous save lands, and the
        process exits with status 143). Serialization runs on a bounded
        background writer; the loop blocks only for snapshot capture.
        ``resume_from=`` names a checkpoint directory: the newest VALID
        checkpoint restores parameters, aux states, optimizer state,
        update counts, both PRNG chains, the epoch/batch position, and
        mid-epoch metric accumulators — a killed-and-resumed run is
        bit-identical to an uninterrupted one (tests/test_checkpoint.py).

        On TPU the per-batch body runs as one fused jitted step when the
        subclass provides ``_fit_step`` (Module does); otherwise it falls
        back to forward_backward + update.

        Async pipeline (docs/architecture/async_loop.md): with
        ``MXNET_TPU_ASYNC_WINDOW > 0`` and an async-capable module the hot
        loop dispatches up to K steps ahead (sliding-window sync), metrics
        accumulate as device reductions with the host fetch deferred to
        log boundaries, and batches are device-placed by a background
        prefetch stage — so steady state does ZERO per-batch host syncs
        (counter-asserted: ``loop_host_sync``). A monitor, a host-callback
        CustomOp program, or ``MXNET_TPU_ASYNC_WINDOW=0`` falls back to
        the fully synchronous per-batch loop.

        ``grad_accum=N`` (docs/architecture/program_model.md,
        compile-time control): microbatch gradient accumulation — the
        fused step splits every batch into N equal microbatches run
        through one ``lax.scan`` with gradient carry, so only one
        microbatch's activations are live at a time while the optimizer
        sees the exact full-batch gradient (BatchNorm statistics advance
        per microbatch). Requires a module with a fused step and
        N | batch size.

        ``layout=`` (docs/architecture/parallelism.md): a
        ``parallel.SpecLayout`` — THE multi-chip entry point. The bind
        builds the canonical ``data x fsdp x tp`` mesh, batches shard
        over ``(data, fsdp)``, parameters AND optimizer states shard per
        the layout's name heuristic (FSDP/ZeRO + tensor parallel), and
        GSPMD inserts the collectives. Composes with ``checkpoint=`` /
        ``resume_from=`` (reshard-on-load resolves through the same
        layout funnel). Requires a module implementing ``set_layout``
        (mx.mod.Module).

        ``tune="auto"`` (docs/architecture/tune.md): before binding,
        load or search the tuned configuration for this program
        (``mxnet_tpu.tune``) and apply it — remat / async-window via
        fit-scoped config overrides (restored when
        fit returns — tuning one fit never reconfigures a later one),
        ``grad_accum`` and ``layout`` through these same arguments when
        the caller left them None (explicit arguments win). ``"static"``
        skips probe subprocesses (model-only pick); default follows the
        ``MXNET_TPU_TUNE`` knob. With a stored config a restarted fit
        reaches its first step pre-tuned with zero search cost.
        """
        assert num_epoch is not None, "please specify number of epochs"
        from ..initializer import Uniform
        from .. import config as _config
        from .. import _fused as _fused_mod
        from .. import random as _random
        if initializer is None:
            # the default initializer draws from the SEEDED mx.random key
            # chain (one split), not the process-global unseeded
            # np.random — two fits after the same mx.random.seed() start
            # from identical weights (the masked-flake source documented
            # in CHANGES PR 4)
            initializer = Uniform(0.01).set_rng(
                _random.derive_numpy_rng("fit_default_init"))

        # --------------------------------------------- checkpoint / resume
        ckpt_mod = None
        ckpt_mgr = None
        resume = None
        uninstall_sigterm = None
        if checkpoint is not None or resume_from is not None:
            from .. import checkpoint as ckpt_mod
        if checkpoint is not None:
            if getattr(self, "_checkpoint_snapshot", None) is None:
                raise MXNetError(
                    "fit(checkpoint=...) requires a module implementing "
                    "_checkpoint_snapshot (mx.mod.Module); %s does not — "
                    "use the legacy epoch_end_callback="
                    "mx.callback.do_checkpoint(...) instead"
                    % type(self).__name__)
            ckpt_mgr = ckpt_mod.CheckpointManager(checkpoint)
        if resume_from is not None:
            resume = ckpt_mod.restore_latest(
                str(resume_from),
                verify=ckpt_mgr.config.verify_on_load if ckpt_mgr else True)
            if arg_params or aux_params:
                self.logger.warning(
                    "fit(resume_from=%s) overrides the explicit "
                    "arg_params/aux_params", resume.path)
            arg_params = resume.arg_params_nd()
            aux_params = resume.aux_params_nd()
            force_init = True
            begin_epoch = resume.resume_epoch
            self.logger.info("resuming from %s (step %d, epoch %d%s)",
                             resume.path, resume.step, begin_epoch,
                             ", batch %d" % resume.batches_done
                             if resume.mid_epoch else "")

        # ------------------------------------------------------------ tune
        # fit(tune="auto"): search (or load) the tuned configuration for
        # this exact program and apply it before anything binds. The knob
        # winners flow through mx.config overrides; grad_accum/layout go
        # through fit's own arguments — but ONLY when the caller left
        # them None (explicit user arguments always win). With a stored
        # config this path costs one JSON read
        # (docs/architecture/tune.md).
        tune_mode = tune if tune is not None \
            else _config.get("MXNET_TPU_TUNE")
        if tune_mode in (True, 1, "on", "1", "yes", "true"):
            tune_mode = "auto"
        tune_knob_snapshot = None
        if tune_mode not in (None, False, 0, "", "off", "0", "no",
                             "false", "none"):
            from .. import tune as _tune   # lazy: only when armed
            budget = _config.get("MXNET_TPU_ANALYZE_HBM_BUDGET") or None
            tuned = _tune.tune_fit(self, train_data, optimizer,
                                   optimizer_params, mode=str(tune_mode),
                                   budget=budget)
            cand = tuned.candidate
            # the overrides are fit-scoped: snapshot the knobs' override
            # state now and restore it in the finally below, so a later
            # fit of a DIFFERENT module with tune off never silently
            # trains under this winner's configuration
            tune_knob_snapshot = _config.snapshot_overrides(cand.knobs())
            for knob, val in cand.knobs().items():
                _config.set(knob, val)
            if grad_accum is None and cand.grad_accum > 1:
                grad_accum = cand.grad_accum
            if layout is None and cand.layout is not None:
                from ..parallel.layout import SpecLayout
                layout = SpecLayout(data=cand.layout[0],
                                    fsdp=cand.layout[1],
                                    tp=cand.layout[2])
            _profiler.incr_counter("tune_applied")
            self.logger.info("fit(tune=%s): applying %s config %s",
                             tune_mode, tuned.source, cand.to_dict())

        if layout is not None:
            lay_setter = getattr(self, "set_layout", None)
            if lay_setter is None:
                raise MXNetError(
                    "fit(layout=...): %s does not support the unified "
                    "SpecLayout (mx.mod.Module does)"
                    % type(self).__name__)
            if force_rebind and getattr(self, "binded", False):
                # the bind below drops the old binding anyway
                # (force_rebind) — drop it first, or set_layout refuses
                # to re-lay a live binding and the documented
                # fit(layout=..., force_rebind=True) path is unreachable
                self.binded = False
            # before bind, so the mesh and every placement honor it
            lay_setter(layout)

        if grad_accum is not None:
            setter = getattr(self, "set_grad_accum", None)
            if setter is not None:
                # before init_optimizer so the fused step builds with it
                setter(grad_accum)
            elif int(grad_accum) > 1:
                raise MXNetError(
                    "fit(grad_accum=%s): %s does not support microbatch "
                    "gradient accumulation" % (grad_accum,
                                               type(self).__name__))

        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)

        if resume is not None:
            restore = getattr(self, "_checkpoint_restore", None)
            if restore is not None:
                restore(resume)
            ckpt_mod.restore_global_rng(resume)

        if validation_metric is None:
            validation_metric = eval_metric
        if not isinstance(eval_metric, _metric.EvalMetric):
            eval_metric = _metric.create(eval_metric)

        fused = getattr(self, "_fit_step", None)

        # ------------------------------------------------ async loop setup
        window = int(_config.get("MXNET_TPU_ASYNC_WINDOW"))
        async_ok = getattr(self, "_async_capable", lambda: False)
        if monitor is not None or fused is None or not async_ok():
            # a monitor taps per-op values (needs the sync loop); modules
            # without a fused step, or with host-callback programs, must
            # stay synchronous (executor.requires_sync_loop)
            window = 0
        update_device = getattr(self, "_update_metric_device", None)
        inflight = _fused_mod.InflightWindow(window)
        step_token = getattr(self, "_step_token", lambda: None)

        def _loader_hook(it, name):
            # a data-plane hook (_mx_cursor / _mx_fast_forward) on the
            # iterator, looking through one user-applied prefetch
            # wrapper (fit's own wrap happens AFTER these resolve, so
            # only a pre-wrapped PrefetchingIter needs unwrapping)
            fn = getattr(it, name, None)
            if fn is None:
                inner = getattr(it, "iters", None)
                if inner:
                    fn = getattr(inner[0], name, None)
            return fn

        resume_skip_eoe = False
        if resume is not None and resume.mid_epoch:
            # fast-forward the INNER iterator past the batches the
            # interrupted run already consumed BEFORE the device-prefetch
            # wrapper spins up its worker (no compute — the restored
            # params/opt state already reflect those batches, and skipped
            # batches must not be device-placed just to be discarded)
            ff = _loader_hook(train_data, "_mx_fast_forward")
            if ff is not None:
                # a cursor-capable loader (mx.data.DataLoader) seeks
                # straight to the batch index — no decode of skipped
                # batches — after validating the saved cursor's stream
                # identity (seed/batch size/record count) against this
                # run's configuration
                ff(begin_epoch, resume.batches_done,
                   cursor=resume.data_cursor)
            else:
                skip_iter = iter(train_data)
                for _ in range(resume.batches_done):
                    try:
                        next(skip_iter)
                    except StopIteration:
                        resume_skip_eoe = True
                        break
        elif resume is not None:
            # epoch-boundary resume: sync a cursor-capable loader's
            # shuffle epoch (and validate stream identity) so epoch
            # begin_epoch's permutation matches what an uninterrupted
            # run would have drawn
            ff = _loader_hook(train_data, "_mx_fast_forward")
            if ff is not None:
                ff(begin_epoch, 0, cursor=resume.data_cursor)

        wrapped = None
        placer_sink = None
        inner_train_data = train_data
        if window > 0:
            depth = int(_config.get("MXNET_TPU_DEVICE_PREFETCH"))
            placer = getattr(self, "_device_placer", lambda: None)()
            if depth > 0 and placer is not None \
                    and hasattr(train_data, "next") \
                    and getattr(train_data, "provide_data", None):
                sink = getattr(train_data, "_mx_set_device_placer", None)
                if sink is not None:
                    # a placement-capable loader (mx.data.DataLoader) IS
                    # the prefetch stage: its delivered batches already
                    # carry device arrays (per-host device_put onto the
                    # mesh data axis, async H2D) — wrapping it in a
                    # PrefetchingIter would re-copy every batch through
                    # an extra worker thread + queue hop
                    sink(placer)
                    placer_sink = train_data
                else:
                    from ..io.io import PrefetchingIter
                    if not isinstance(train_data, PrefetchingIter):
                        train_data = wrapped = PrefetchingIter(
                            train_data, device_placer=placer,
                            device_prefetch=depth)
                    # an iterator the user already wrapped is used
                    # as-is: stacking a second PrefetchingIter would add
                    # a worker thread and a queue hop just for the
                    # placement stage — those batches are placed in
                    # _load_batch instead

        # the data-plane cursor source for checkpoint manifests; called
        # with fit's CONSUMED count (nbatch) — the loader's own
        # delivered count runs prefetch-depth ahead of consumption and
        # would fast-forward a resume past unseen batches
        cursor_fn = _loader_hook(inner_train_data, "_mx_cursor")

        # the training thread's trace lane: step/checkpoint-snapshot spans
        # land here; metric syncs get their own track (docs/architecture/
        # observability.md lane map)
        _profiler.register_thread_lane("train")

        # coordinated pod mode: the per-host supervisor couples its
        # liveness heartbeat to this file — a training process that stops
        # advancing it (wedged collective, hung iterator) is declared
        # dead by the pod once the staleness deadline passes
        progress_path = os.environ.get("MXNET_TPU_ELASTIC_PROGRESS_FILE")

        def _touch_progress(count):
            try:
                with open(progress_path, "w") as pf:
                    pf.write("%d\n" % count)
            except OSError:
                pass

        # pod straggler telemetry (docs/architecture/observability.md):
        # per-rank step windows published at the epoch log boundary —
        # one KV write per window riding the metric_sync fetch, zero
        # extra per-step host syncs. Gated so a plain single-process
        # fit never imports the obs pod stack (zero-cost,
        # subprocess-proven by the CI multihost job).
        straggler = None
        if (os.environ.get("MXNET_TPU_POD_KV")
                or os.environ.get("DMLC_NUM_WORKER", "1")
                not in ("", "0", "1")) \
                and float(_config.get("MXNET_TPU_OBS_STRAGGLER_RATIO")) > 0:
            from ..obs import straggler as _straggler_mod
            straggler = _straggler_mod.FitPublisher.create()

        completed = False
        if ckpt_mgr is not None and ckpt_mgr.config.save_on_sigterm:
            uninstall_sigterm = ckpt_mgr.install_sigterm()
        ckpt_every_n = ckpt_mgr.config.every_n_batches if ckpt_mgr else None
        ckpt_period = max(1, ckpt_mgr.config.period_epochs) if ckpt_mgr \
            else 1
        try:
            for epoch in range(begin_epoch, num_epoch):
                tic = time.perf_counter()
                eval_metric.reset()
                nbatch = 0
                data_iter = iter(train_data)
                end_of_batch = False
                if resume is not None and resume.mid_epoch \
                        and epoch == begin_epoch:
                    # exact mid-epoch resume: restore the metric
                    # accumulators the snapshot folded to host scalars
                    # (the iterator was fast-forwarded past the consumed
                    # batches before the prefetch wrapper was built)
                    if resume.metric_state is not None:
                        restore_m = getattr(eval_metric, "_ckpt_restore",
                                            None)
                        if restore_m is None or \
                                not restore_m(resume.metric_state):
                            self.logger.warning(
                                "resume: could not restore mid-epoch "
                                "metric state; epoch-%d training metrics "
                                "will only cover the resumed tail", epoch)
                    nbatch = resume.batches_done
                    end_of_batch = resume_skip_eoe
                next_data_batch = None
                if not end_of_batch:
                    try:
                        next_data_batch = next(data_iter)
                    except StopIteration:
                        # resume landed exactly on the epoch's last batch:
                        # nothing left to train, fall through to the
                        # epoch-end processing the interrupted run missed
                        end_of_batch = True
                # the straggler window opens fresh per epoch: the
                # epoch-boundary segment (drain/eval/ckpt) is shared
                # pod work, not a rank-local signal
                t_host_mark = None
                while not end_of_batch:
                    if _faults.ARMED:
                        # deterministic preemption/crash drills: the
                        # elastic suite SIGTERMs/SIGKILLs fit at batch K
                        # (MXNET_TPU_FAULTS=fit.batch@K[:kind]); the pod
                        # drill kills or wedges the whole HOST here
                        # (host.die@K[:hostkill|wedge]); the leader
                        # fail-over drill arms leader.die on the host
                        # carrying the control plane
                        # (leader.die@K[:hostkill|coordsvc])
                        _faults.fire("fit.batch", default_kind="sigterm")
                        _faults.fire("host.die", default_kind="hostkill")
                        _faults.fire("leader.die", default_kind="hostkill")
                    data_batch = next_data_batch
                    # the batch's flow id threads its trace slices across
                    # lanes (prefetch -> place -> step -> metric); batches
                    # the prefetch stage produced already carry one
                    fid = getattr(data_batch, "_mx_flow", None)
                    if fid is None and _profiler.spans_enabled():
                        fid = _profiler.new_flow()
                    with _profiler.span("fit_step", "step", flow=fid,
                                        nbatch=nbatch):
                        if monitor is not None:
                            monitor.tic()
                        if straggler is not None:
                            # LOCAL-work window = previous metric fetch →
                            # this dispatch: the host-side inter-step
                            # segment (fault sleeps, SIGSTOP pulses,
                            # input fetch, callbacks) where a rank's OWN
                            # slowness lands. Collective waits surface in
                            # the dispatch/metric regions (async dispatch
                            # defers them to the next device sync), which
                            # this window excludes — counting a peer-wait
                            # as local work would equalize every rank's
                            # rate and hide the straggler.
                            _t_ds = time.perf_counter()
                            if t_host_mark is not None:
                                straggler.step(_t_ds - t_host_mark)
                        with _profiler.span("fused_step_dispatch", "step",
                                            flow=fid):
                            if fused is not None and monitor is None:
                                fused(data_batch)
                            else:
                                self.forward_backward(data_batch)
                                self.update()
                        if window > 0:
                            inflight.push(step_token())
                        # metric BEFORE prepare: prepare may switch the
                        # current bucket module, whose outputs are not this
                        # batch's
                        with _profiler.span("metric_update", "metric",
                                            flow=fid, lane="metric"):
                            if window > 0 and update_device is not None and \
                                    update_device(eval_metric,
                                                  data_batch.label):
                                pass    # chained device reduction, no sync
                            else:
                                if window > 0:
                                    # the async loop had to sync for
                                    # this metric: visible per-batch
                                    # pipeline break
                                    _profiler.incr_counter("loop_host_sync")
                                self.update_metric(eval_metric,
                                                   data_batch.label)
                        if straggler is not None:
                            t_host_mark = time.perf_counter()
                        try:
                            with _profiler.span("fit_data_next", "io"):
                                next_data_batch = next(data_iter)
                                self.prepare(next_data_batch)
                        except StopIteration:
                            end_of_batch = True
                        if straggler is not None and getattr(
                                train_data, "_mx_offthread_fetch", False):
                            # re-derived for the streaming data plane: an
                            # OFF-THREAD fetch (PrefetchingIter queue pop,
                            # DataLoader worker-queue pop) is a data-plane
                            # wait — already surfaced as
                            # loop_prefetch_stall / data_stall — not
                            # rank-local compute; leaving it in the window
                            # would flag a slow LOADER as a straggling
                            # HOST. An inline iterator's decode
                            # happens on this thread and stays counted as
                            # local work (the PR 13 window semantics).
                            t_host_mark = time.perf_counter()
                        if monitor is not None:
                            monitor.toc_print()
                        if batch_end_callback is not None:
                            batch_end_params = BatchEndParam(
                                epoch=epoch, nbatch=nbatch,
                                eval_metric=eval_metric, locals=locals())
                            with _profiler.span("fit_callback", "step"):
                                for callback in _as_list(batch_end_callback):
                                    callback(batch_end_params)
                        nbatch += 1
                        if progress_path:
                            _touch_progress(nbatch)
                        if ckpt_mgr is not None:
                            if ckpt_every_n and nbatch % ckpt_every_n == 0:
                                # the snapshot must be a step boundary: wait
                                # out the in-flight window, then capture (the
                                # cheap phase) and resume the loop while the
                                # writer drains to disk behind it
                                inflight.drain()
                                ckpt_mgr.save_module(
                                    self, epoch=epoch, batches_done=nbatch,
                                    metric=eval_metric,
                                    loader_state=cursor_fn(
                                        epoch=epoch, batches_done=nbatch)
                                    if cursor_fn else None)
                            if ckpt_mgr.preempt_requested:
                                # SIGTERM (preemption notice): finish this
                                # batch, land a SYNCHRONOUS save, and exit
                                # with the conventional 128+15 status
                                inflight.drain()
                                ckpt_mgr.preempt_save(
                                    self, epoch=epoch, batches_done=nbatch,
                                    metric=eval_metric,
                                    loader_state=cursor_fn(
                                        epoch=epoch, batches_done=nbatch)
                                    if cursor_fn else None)
                                self.logger.warning(
                                    "SIGTERM: checkpoint saved at epoch %d "
                                    "batch %d; exiting with status 143",
                                    epoch, nbatch)
                                bb = _blackbox()
                                if bb is not None:
                                    # observed-flag context on the training
                                    # thread — never the signal handler
                                    bb.record("preempt", "sigterm",
                                              epoch=epoch, batch=nbatch)
                                    bb.flush("sigterm")
                                raise SystemExit(143)

                # epoch barrier: wait out in-flight steps so the epoch
                # time is honest and checkpoints/eval see final state
                inflight.drain()
                # the ONE host metric fetch of the epoch (async loop):
                # visible as a metric-lane span at the log boundary
                with _profiler.span("metric_sync", "metric", lane="metric"):
                    name_values = eval_metric.get_name_value()
                if straggler is not None:
                    # the log boundary: the metric fetch just synced the
                    # host, so the window publish adds no device sync —
                    # and rank 0 aggregates the pod's windows here
                    straggler.publish(epoch)
                bb = _blackbox()
                if bb is not None:
                    bb.record("epoch", "end", epoch=epoch, batches=nbatch,
                              metrics={n: round(float(v), 6)
                                       for n, v in name_values})
                for name, val in name_values:
                    self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
                toc = time.perf_counter()
                self.logger.info("Epoch[%d] Time cost=%.3f", epoch, (toc - tic))

                # non-finite step guard (MXNET_TPU_NANCHECK): the ONE
                # host fetch of the device-accumulated isfinite flags,
                # at the same boundary as the metric sync — warn logs,
                # abort raises naming the first non-finite output
                nan_mode = getattr(self, "_nancheck_mode", "off")
                if nan_mode != "off":
                    bad = self._nancheck_poll()
                    if bad is not None:
                        _profiler.incr_counter("loop_nonfinite")
                        msg = ("non-finite values in output %r during "
                               "epoch %d (MXNET_TPU_NANCHECK=%s; a "
                               "diverged loss, inf/NaN inputs, or an "
                               "overflowing update)" % (bad, epoch,
                                                        nan_mode))
                        if nan_mode == "abort":
                            bb = _blackbox()
                            if bb is not None:
                                # NANCHECK abort is a terminal moment:
                                # the window must carry the diverged
                                # output's name
                                bb.record("nancheck", "abort",
                                          output=str(bad), epoch=epoch)
                                bb.flush("nancheck")
                            raise MXNetError(msg)
                        self.logger.warning(msg)

                arg_params_, aux_params_ = self.get_params()
                self.set_params(arg_params_, aux_params_)

                if epoch_end_callback is not None:
                    for callback in _as_list(epoch_end_callback):
                        callback(epoch, self.symbol, arg_params_, aux_params_)

                if eval_data is not None:
                    res = self.score(eval_data, validation_metric,
                                     score_end_callback=eval_end_callback,
                                     batch_end_callback=eval_batch_end_callback,
                                     epoch=epoch)
                    for name, val in res:
                        self.logger.info("Epoch[%d] Validation-%s=%f",
                                         epoch, name, val)

                if ckpt_mgr is not None:
                    # epoch-boundary cursor: the NEXT epoch at batch 0,
                    # which is where a resume from this checkpoint starts
                    _eoe_cursor = cursor_fn(epoch=epoch + 1,
                                            batches_done=0) \
                        if cursor_fn else None
                    if (epoch + 1) % ckpt_period == 0:
                        ckpt_mgr.save_module(self, epoch=epoch,
                                             metric=eval_metric,
                                             loader_state=_eoe_cursor)
                    if ckpt_mgr.preempt_requested:
                        ckpt_mgr.preempt_save(self, epoch=epoch,
                                              metric=eval_metric,
                                              loader_state=_eoe_cursor)
                        self.logger.warning(
                            "SIGTERM: checkpoint saved at end of epoch "
                            "%d; exiting with status 143", epoch)
                        bb = _blackbox()
                        if bb is not None:
                            bb.record("preempt", "sigterm", epoch=epoch)
                            bb.flush("sigterm")
                        raise SystemExit(143)

                # after the FINAL epoch a wrapped iterator must not be
                # reset here: the parked prefetch worker would wake and
                # device-place batches of an epoch that never runs
                # (inflating loop_prefetch_placed past one-per-consumed-
                # batch); close() below stops it, then the inner iterator
                # is reset exactly as the synchronous loop would leave it
                if wrapped is None or epoch < num_epoch - 1:
                    train_data.reset()
            completed = True
        finally:
            if uninstall_sigterm is not None:
                uninstall_sigterm()
            if tune_knob_snapshot is not None:
                # drop the tuned knob overrides back to their pre-fit
                # state (override, environment or default): fit(tune=)
                # configures THIS fit, not the process
                _config.restore_overrides(tune_knob_snapshot)
            if placer_sink is not None:
                # detach so a later fit of the same loader against a
                # different module (or no module) never places onto a
                # dead mesh
                placer_sink._mx_set_device_placer(None)
            if wrapped is not None:
                joined = wrapped.close()
                # leave the user's iterator exactly as the synchronous
                # loop would: freshly reset (the prefetch workers may
                # have pre-pulled batches past the last epoch's reset) —
                # but only if the workers actually exited (resetting an
                # iterator a wedged worker is still inside is a data
                # race) and fit is not unwinding an exception (the sync
                # loop leaves the iterator un-reset then, and a reset
                # raising on the same broken source would mask the
                # original error)
                if joined and completed:
                    inner_train_data.reset()
                elif not joined:
                    self.logger.warning(
                        "prefetch worker did not exit within the close() "
                        "deadline; skipping the final reset of the "
                        "training iterator")
            if ckpt_mgr is not None:
                # drain the background writer; surface the first async
                # write failure ONLY on a clean run (raising here while
                # fit is already unwinding would mask the original error)
                ckpt_mgr.close(raise_errors=completed)

    def prepare(self, data_batch):
        """Prepare the module for processing a data batch (no-op by default;
        BucketingModule switches buckets here — reference: base_module.py
        prepare)."""
        pass
