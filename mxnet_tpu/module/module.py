"""Module — binds a Symbol to devices and drives training.

Reference: ``python/mxnet/module/module.py`` — ``Module`` (line 39):
``bind:351`` creates a DataParallelExecutorGroup, ``init_optimizer:461``
decides update_on_kvstore, ``forward:556``/``backward:598``/``update:615``
drive the executors and the kvstore push/pull.

TPU design (SURVEY.md §2.21 + §7): the per-device executor group collapses
into ONE jitted program. ``context=[...]`` with more than one device builds a
``data``-axis mesh; inputs are batch-sharded, parameters replicated, and the
gradient all-reduce the reference routed through KVStore Comm
(src/kvstore/comm.h:73-380) is inserted by XLA as a psum over ICI. The fit
hot loop uses a fused forward+backward+optimizer-update program with donated
buffers so weights never leave HBM.
"""
from __future__ import annotations

import logging
import os
import time
from typing import Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..context import Context, cpu
from .. import ndarray as nd
from .. import optimizer as opt
from ..executor import Executor, graph_function
from ..initializer import InitDesc
from ..model import _create_kvstore, load_checkpoint, save_checkpoint
from .. import config as _config
from .. import _fused
from .. import profiler as _profiler
from ..obs import compiles as _obs_compiles
from ..obs import mfu as _obs_mfu
from .base_module import BaseModule, _check_input_names
from ..io.io import DataDesc, DeferredImages

__all__ = ["Module"]

# one compiled executable per (shapes, dtypes) signature, shared by every
# checkpoint snapshot of the same model — no donation, so the inputs (the
# live training buffers) stay valid and the outputs are owned copies
_snapshot_copy = jax.jit(lambda xs: [jnp.copy(x) for x in xs])


def _accum_loss_scale(symbol, accum: int) -> float:
    """Gradient rescale that makes an N-microbatch accumulated step
    match the unaccumulated full-batch step.

    Loss-head backward contract (ops/nn.py): ``normalization='null'``
    (and the regression/SVM heads, and plain outputs driven by
    ones-cotangents) produce **per-sample** gradients — summing the N
    microbatch gradients IS the full-batch gradient, scale 1.
    ``normalization='batch'`` divides by the (micro)batch size, so the
    accumulated sum is N x the full-batch mean — scale 1/N (equal-sized
    microbatches make the mean-of-means exact).
    ``normalization='valid'`` divides by a data-dependent count per
    microbatch; no uniform rescale reproduces the full-batch step, so
    it is rejected, as is a mix of batch-mean and per-sample heads."""
    kinds = set()
    for node, _ in symbol._entries:
        if node.is_variable:
            kinds.add("sample")
            continue
        norm = node.attrs.get("normalization")
        if norm == "valid":
            raise MXNetError(
                "grad_accum: %s head %r uses normalization='valid' "
                "(a per-batch valid count cannot be replayed per "
                "microbatch) — use 'batch' or 'null'"
                % (node.op.name, node.name))
        kinds.add("batch" if norm == "batch" else "sample")
    if kinds == {"batch"}:
        return 1.0 / accum
    if "batch" in kinds:
        raise MXNetError(
            "grad_accum: loss heads mix batch-mean and per-sample/sum "
            "normalization; the accumulated gradient cannot be rescaled "
            "consistently — align the heads' normalization")
    return 1.0


class Module(BaseModule):
    """A bound Symbol + parameters + optimizer (reference: module.py:39)."""

    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None, mesh_shape=None, param_shardings=None,
                 layout=None):
        """``mesh_shape``/``param_shardings`` are the tensor-parallel
        surface (SURVEY §2.21): ``mesh_shape={"data": 2, "model": 4}``
        lays the context list out as a 2D mesh, and ``param_shardings``
        maps parameter names (exact or regex) to ``parallel.P`` partition
        specs over those axes — e.g. ``{"fc1_weight": P("model", None)}``
        column-shards fc1. The batch stays sharded over ``data``; XLA
        partitions the matmuls and inserts the tensor-parallel collectives
        from the operand shardings (GSPMD), so the same fused train step
        serves dp, tp, and dp x tp without code changes.

        ``layout`` (docs/architecture/parallelism.md) is the unified
        entry point above both: a ``parallel.SpecLayout`` builds the
        canonical ``data x fsdp x tp`` mesh, shards every batch over
        ``(data, fsdp)``, and resolves each parameter's spec through the
        layout's overrides + name heuristic — parameters AND their
        optimizer states shard over ``fsdp`` (ZeRO-style), with explicit
        ``param_shardings`` still winning per name. The same layout
        object drives checkpoint reshard-on-load
        (``read_checkpoint(layout=...)``), so save/restore can never
        resolve differently than the bind."""
        super().__init__(logger=logger)
        if context is None:
            context = cpu()
        if isinstance(context, Context):
            context = [context]
        self._context: List[Context] = list(context)
        self._mesh_shape = dict(mesh_shape) if mesh_shape else None
        self._param_shardings = dict(param_shardings) \
            if param_shardings else None
        self._layout = None
        self._batch_sharding = None
        if layout is not None:
            self.set_layout(layout)
        # work_load_list existed to weight uneven GPUs
        # (executor_group.py:99); a TPU mesh is homogeneous, accepted and
        # ignored for API compatibility.
        self._work_load_list = work_load_list

        self._symbol = symbol
        data_names = list(data_names) if data_names is not None else []
        label_names = list(label_names) if label_names is not None else []
        state_names = list(state_names) if state_names is not None else []
        fixed_param_names = list(fixed_param_names) if fixed_param_names \
            is not None else []
        _check_input_names(symbol, data_names, "data", True)
        _check_input_names(symbol, label_names, "label", False)
        _check_input_names(symbol, state_names, "state", True)
        _check_input_names(symbol, fixed_param_names, "fixed_param", True)

        arg_names = symbol.list_arguments()
        input_names = data_names + label_names + state_names
        self._param_names = [n for n in arg_names if n not in input_names]
        self._fixed_param_names = fixed_param_names
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = data_names
        self._label_names = [n for n in label_names if n in arg_names]
        self._state_names = state_names
        self._output_names = symbol.list_outputs()

        self._arg_params: Optional[Dict[str, nd.NDArray]] = None
        self._aux_params: Optional[Dict[str, nd.NDArray]] = None
        self._params_dirty = False

        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._fused_updater = None
        self._preload_opt_states = None

        self._exec: Optional[Executor] = None
        self._grad_accum = 1
        self._data_shapes = None
        self._label_shapes = None
        self._grad_req = None
        self._mesh = None
        self._fused = None          # jitted fused train step
        self._fused_out = None      # outputs of the last fused step
        self._fused_states = None   # optimizer-state pytree for fused path
        self._fused_num_update = 0

        # obs utilization accounting (docs/architecture/observability.md):
        # per-step cost is two attribute writes + one perf_counter read;
        # rates/MFU are computed lazily by mx.obs.report()
        self._obs_steps = 0
        self._obs_t0 = None
        self._obs_baseline = None
        self._obs_flops_per_step = None
        self._obs_label = "module"
        self._obs_sig = None

    # ------------------------------------------------------------- loading
    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """Create a Module from a saved checkpoint (reference:
        module.py:114)."""
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        """(reference: module.py:152)."""
        self._sync_params_from_devices()
        save_checkpoint(prefix, epoch, self.symbol, *self.get_params())
        if save_optimizer_states:
            self.save_optimizer_states("%s-%04d.states" % (prefix, epoch))

    # ------------------------------------------------------------- shapes
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        outs = self._exec.outputs
        return list(zip(self._output_names, [o.shape for o in outs]))

    # ------------------------------------------------------------- params
    def get_params(self):
        """(reference: module.py get_params)."""
        assert self.binded and self.params_initialized
        self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def _sync_params_from_devices(self):
        """Copy bound executor values back into _arg_params (reference:
        module.py _sync_params_from_devices). One jax.Array is the single
        source of truth here, so 'sync' is a dict refresh."""
        if not self.binded or not self.params_initialized:
            return
        if self._exec is not None and self._params_dirty:
            for n in self._param_names:
                self._arg_params[n] = self._exec.arg_dict[n]
            for n in self._aux_names:
                self._aux_params[n] = self._exec.aux_dict[n]
            self._params_dirty = False

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        """(reference: module.py init_params — attr-driven InitDesc
        dispatch)."""
        assert self.binded, "call bind before initializing the parameters"
        if self.params_initialized and not force_init:
            return
        attrs = self.symbol.attr_dict()

        def _impl(name, arr, cache):
            if cache is not None and name in cache:
                cache_arr = cache[name]
                if cache_arr is not arr:
                    if cache_arr.shape != arr.shape:
                        raise MXNetError(
                            "shape mismatch for %s: %s vs %s"
                            % (name, cache_arr.shape, arr.shape))
                    arr[:] = cache_arr
            elif cache is not None and not allow_missing:
                raise RuntimeError("%s is not presented" % name)
            elif initializer is not None:
                initializer(InitDesc(name, attrs.get(name, None)), arr)

        for name in self._param_names:
            _impl(name, self._exec.arg_dict[name], arg_params)
        for name in self._aux_names:
            _impl(name, self._exec.aux_dict[name], aux_params)

        self._arg_params = {n: self._exec.arg_dict[n]
                            for n in self._param_names}
        self._aux_params = {n: self._exec.aux_dict[n]
                            for n in self._aux_names}
        self.params_initialized = True
        self._params_dirty = False
        if self._mesh is not None:
            self._replicate_params()

    def set_layout(self, layout) -> None:
        """Install the unified ``parallel.SpecLayout`` (the ROADMAP
        item-1 entry point; ``fit(layout=...)`` routes here): the bind
        builds the canonical ``data x fsdp x tp`` mesh from it, batches
        shard over ``(data, fsdp)``, and every parameter + optimizer
        state resolves its spec through the layout (explicit
        ``param_shardings`` still win per name). Must be called before
        bind — an already-bound module would need force_rebind to re-lay
        its buffers out."""
        if layout is not None and not hasattr(layout, "spec_for"):
            raise MXNetError(
                "set_layout expects a parallel.SpecLayout (got %r)"
                % (type(layout).__name__,))
        if self.binded:
            if layout == self._layout:
                return          # idempotent re-fit with the same layout
            raise MXNetError(
                "set_layout must run before bind (rebind with "
                "force_rebind=True to change an existing module's "
                "layout)")
        if layout is not None and self._mesh_shape is not None:
            raise MXNetError(
                "layout and mesh_shape are mutually exclusive — the "
                "layout IS the mesh shape (axes %r)" % (layout.axes(),))
        self._layout = layout

    def _sharding_for(self, name):
        """Resolve a parameter's NamedSharding: an exact or regex match in
        param_shardings wins (tensor parallel), then the bound
        SpecLayout's overrides + name heuristic (FSDP/tp), else
        replicated (data parallel). Delegates to the canonical resolver
        shared with checkpoint reshard-on-load
        (parallel.mesh.resolve_layout_spec)."""
        from jax.sharding import NamedSharding
        from ..parallel.mesh import replicated_sharding, resolve_layout_spec
        if self._param_shardings:
            spec = resolve_layout_spec(self._param_shardings, name)
            if spec is not None:
                return NamedSharding(self._mesh, spec)
        if self._layout is not None:
            arr = self._exec.arg_dict.get(name) if self._exec is not None \
                else None
            if arr is None and self._exec is not None:
                arr = self._exec.aux_dict.get(name)
            spec = resolve_layout_spec(
                self._layout, name,
                shape=tuple(arr.shape) if arr is not None else None,
                dtype=arr.dtype if arr is not None else None)
            if spec is not None:
                return NamedSharding(self._mesh, spec)
        return replicated_sharding(self._mesh)

    def _batch_rows(self):
        """``(mesh, axes)`` for ``graph_function(batch_rows=)``: the axes
        ``_place_value`` shards the batch dimension over."""
        if self._mesh is None:
            return None
        if self._batch_sharding is not None:
            first = self._batch_sharding.spec[0]
        else:
            first = "data" if "data" in self._mesh.axis_names else None
        if first is None:
            return self._mesh, ()
        return self._mesh, first if isinstance(first, tuple) else (first,)

    def _replicate_params(self):
        """Place parameters on the mesh: replicated over ``data``, and
        partitioned per param_shardings over ``model`` (replaces per-device
        param copies in executor_group.py + kvstore broadcast). Spec
        divisibility is validated per parameter first, so restoring a
        checkpoint onto a mesh its layout cannot divide fails naming the
        offending array (the elastic reshard-on-load contract) instead
        of surfacing as an XLA sharding error."""
        from ..parallel.mesh import validate_spec
        for d in (self._exec.arg_dict, self._exec.aux_dict):
            for name, arr in d.items():
                sharding = self._sharding_for(name)
                try:
                    validate_spec(self._mesh, sharding.spec,
                                  tuple(arr.shape), name=name)
                except ValueError as exc:
                    raise MXNetError("cannot lay out parameters on the "
                                     "bound mesh: %s" % exc) from None
                arr._data = jax.device_put(arr._data, sharding)

    # ------------------------------------------------------------- binding
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        """(reference: module.py:351). Shapes may be (name, shape) tuples or
        DataDesc."""
        if force_rebind:
            self._exec = None
            self.binded = False
        if self.binded:
            self.logger.warning("Already bound, ignoring bind()")
            return

        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad

        self._data_shapes = [x if isinstance(x, DataDesc) else DataDesc(*x)
                             for x in data_shapes]
        self._label_shapes = [x if isinstance(x, DataDesc) else DataDesc(*x)
                              for x in label_shapes] if label_shapes else []

        shape_hints = {d.name: d.shape for d in self._data_shapes}
        shape_hints.update({d.name: d.shape for d in self._label_shapes
                            if d.name in self._symbol.list_arguments()})

        mesh_shape = self._mesh_shape
        if mesh_shape is None and self._layout is not None:
            # the unified layout IS the mesh shape: always all three
            # canonical axes (size-1 axes cost nothing and keep every
            # spec valid on every shape)
            mesh_shape = self._layout.axes()
        if mesh_shape is not None:
            from ..parallel.mesh import make_mesh
            if len(self._context) > 1:
                want = int(np.prod([s for s in mesh_shape.values()
                                    if s != -1]))
                if -1 not in mesh_shape.values() \
                        and want != len(self._context):
                    raise ValueError(
                        "mesh_shape %r uses %d devices but %d contexts "
                        "were given — they must match (use -1 to absorb "
                        "the rest)" % (mesh_shape, want,
                                       len(self._context)))
            self._mesh = make_mesh(mesh_shape,
                                   contexts=self._context
                                   if len(self._context) > 1 else None)
        elif len(self._context) > 1:
            from ..parallel.mesh import data_parallel_mesh
            self._mesh = data_parallel_mesh(self._context)
        else:
            self._mesh = None

        self._batch_sharding = None
        if self._layout is not None and self._mesh is not None:
            # one NamedSharding built per bind (the placer is hot), and
            # the batch divisibility checked HERE so an indivisible
            # batch fails naming the input, not as an XLA error later
            from jax.sharding import NamedSharding
            from ..parallel.mesh import validate_spec
            spec = self._layout.batch_spec()
            for d in self._data_shapes + self._label_shapes:
                if not d.shape:
                    continue
                try:
                    validate_spec(self._mesh, spec, tuple(d.shape),
                                  name=d.name)
                except ValueError as exc:
                    raise MXNetError(
                        "layout: cannot shard the batch over (%s, %s): %s"
                        % (self._layout.data_axis, self._layout.fsdp_axis,
                           exc)) from None
            self._batch_sharding = NamedSharding(self._mesh, spec)

        req = {}
        for n in self._symbol.list_arguments():
            if n in self._data_names:
                req[n] = "write" if inputs_need_grad else "null"
            elif n in self._label_names or n in self._state_names:
                req[n] = "null"
            elif n in self._fixed_param_names:
                req[n] = "null"
            else:
                req[n] = grad_req if for_training else "null"
        self._grad_req = req

        type_dict = {d.name: d.dtype for d in self._data_shapes +
                     self._label_shapes}
        self._exec = self._symbol.simple_bind(
            self._context[0], grad_req=req, type_dict=type_dict,
            batch_rows=self._batch_rows(), **shape_hints)
        self.binded = True

        if self.params_initialized:
            # params were set before bind (Module.load / set_params on an
            # unbound module): push them into the fresh executor (reference:
            # module.py:351 bind → exec_group.set_params)
            self.init_params(arg_params=self._arg_params,
                             aux_params=self._aux_params,
                             allow_missing=False, force_init=True)

        if shared_module is not None and shared_module.params_initialized:
            self.init_params(arg_params=shared_module._arg_params,
                             aux_params=shared_module._aux_params,
                             allow_missing=False, force_init=True)

    # -------------------------------------------------------------- analysis
    def analyze(self, input_shapes=None, input_dtypes=None,
                sharding=False, collectives=False):
        """Run the static analyzer (``mxnet_tpu.analysis``) over this
        module's symbol: graph passes plus the memory passes (remat
        opportunities, HBM budget). Bound modules analyze with their
        actual bound shapes; unbound ones need ``input_shapes``.

        ``sharding=True`` additionally runs the sharding/communication
        audit on a mesh-bound module (spec validity, FSDP opportunities,
        ambiguous regex layering) — with ``collectives=True`` it also
        compiles the bound forward against its shardings and walks the
        partitioned HLO for collectives (``Report.extras["comm"]``;
        compiles one executable, so it is opt-in).

        Returns an ``analysis.Report`` (lazy import — never loaded
        unless called)."""
        from ..analysis import analyze_symbol
        shapes = {k: tuple(v) for k, v in (input_shapes or {}).items()}
        if not shapes and self.binded:
            shapes = {n: tuple(a.shape)
                      for n, a in self._exec.arg_dict.items()}
            shapes.update({n: tuple(a.shape)
                           for n, a in self._exec.aux_dict.items()})
        report = analyze_symbol(self._symbol, input_shapes=shapes or None,
                                input_dtypes=input_dtypes,
                                context="module",
                                grad_accum=getattr(self, "_grad_accum", 1),
                                batch_inputs=list(self._data_names)
                                + list(self._label_names))
        if sharding and self.binded and self._mesh is not None:
            from ..analysis import analyze_module_sharding
            report.extend(analyze_module_sharding(
                self, collectives=collectives))
        return report

    # ------------------------------------------------------------- optimizer
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        """(reference: module.py:461 — builds kvstore, decides
        update_on_kvstore, pickles the optimizer to dist servers)."""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return

        arg_params = {n: self._exec.arg_dict[n] for n in self._param_names}
        kvstore, update_on_kvstore = _create_kvstore(
            kvstore, len(self._context), arg_params)

        # all data inputs share ONE batch size (reference:
        # executor_group.decide_slices asserts this; never summed)
        batch_sizes = {d.shape[0] for d in self._data_shapes if d.shape}
        if len(batch_sizes) > 1:
            raise MXNetError("data inputs disagree on batch size: %s"
                             % [(d.name, d.shape) for d in self._data_shapes])
        batch_size = batch_sizes.pop() if batch_sizes else 1
        if kvstore and "dist" in kvstore.type and "_sync" in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        if isinstance(optimizer, str):
            idx2name = {i: n for i, n in enumerate(self._param_names)}
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt.create(optimizer, sym=self.symbol,
                                   param_idx2name=idx2name,
                                   **optimizer_params)
        else:
            assert isinstance(optimizer, opt.Optimizer)
            if optimizer.rescale_grad != rescale_grad:
                self.logger.warning(
                    "Optimizer created manually outside Module but "
                    "rescale_grad is not normalized to 1.0/batch_size/"
                    "num_workers (%s vs. %s).",
                    optimizer.rescale_grad, rescale_grad)

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None
        optimizer.set_lr_mult({})
        optimizer.set_wd_mult({})

        if kvstore:
            # init kvstore entries; with update_on_kvstore the optimizer runs
            # inside the store (reference: model.py:106)
            for idx, name in enumerate(self._param_names):
                kvstore.init(idx, self._arg_params[name])
            if update_on_kvstore:
                kvstore.set_optimizer(self._optimizer)
        if not update_on_kvstore:
            self._updater = opt.get_updater(optimizer)
            self._fused_updater = _fused.FusedUpdater(self._updater)

        self.optimizer_initialized = True
        self._build_fused_step()

        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    def borrow_optimizer(self, shared_module):
        """(reference: module.py borrow_optimizer — bucketing support)."""
        assert shared_module.optimizer_initialized
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        self._fused_updater = shared_module._fused_updater
        self.optimizer_initialized = True
        self._build_fused_step()

    def save_optimizer_states(self, fname):
        """(reference: module.py:761). With the fused step active, its state
        pytree is the authoritative optimizer state."""
        assert self.optimizer_initialized
        import pickle
        from ..checkpoint.atomic import atomic_open
        if self._fused is not None and self._fused_states is not None:
            states = jax.tree_util.tree_map(np.asarray, self._fused_states)
            with atomic_open(fname, "wb") as fout:
                pickle.dump({"fused": states,
                             "num_update": self._fused_num_update}, fout)
        elif self._update_on_kvstore and self._kvstore is not None:
            self._kvstore.save_optimizer_states(fname)
        else:
            with atomic_open(fname, "wb") as fout:
                fout.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        """(reference: module.py load_optimizer_states)."""
        assert self.optimizer_initialized
        import pickle
        with open(fname, "rb") as fin:
            blob = fin.read()
        try:
            payload = pickle.loads(blob)
        except Exception:
            payload = None
        if isinstance(payload, dict) and "fused" in payload \
                and self._fused is not None:
            # commit each leaf onto its parameter's sharding — an
            # uncommitted jnp.asarray would lower the fused step under a
            # new key (one spurious recompile on the next fit step)
            def _place_state(n, s):
                bound = self._exec.arg_dict.get(n)

                def _leaf(x):
                    if x is None:
                        return None
                    x = jnp.asarray(x)
                    return x if bound is None else \
                        jax.device_put(x, bound.data.sharding)

                return jax.tree_util.tree_map(_leaf, s,
                                              is_leaf=lambda x: x is None)

            self._fused_states = {n: _place_state(n, s)
                                  for n, s in payload["fused"].items()}
            self._fused_num_update = payload["num_update"]
            self._optimizer.num_update = payload["num_update"]
        elif self._update_on_kvstore and self._kvstore is not None:
            self._kvstore.load_optimizer_states(fname)
        else:
            self._updater.set_states(blob)

    # ---------------------------------------------------------- checkpointing
    def _checkpoint_snapshot(self):
        """Capture everything exact resume needs as ``(tensors, meta)`` for
        ``mx.checkpoint`` (docs/architecture/checkpoint.md): parameters,
        aux states, the optimizer-state tree (fused pytree or the eager
        ``Updater`` dict), update counts, and both PRNG chains.

        The capture is the CHEAP phase of the CheckFreq split: one
        ``jnp.copy`` per array — a device-side dispatch, not a host
        transfer — protects each buffer before the next fused step
        donates and invalidates it (the fused jit donates params, states,
        and aux on EVERY backend, CPU included). The device->host fetch,
        checksums, and fsync all happen later on the writer thread. The
        caller must be at a step boundary with the in-flight window
        drained (``fit`` is).
        """
        assert self.binded and self.params_initialized
        from ..checkpoint.manager import key_to_array, tree_encode
        from ..checkpoint.format import CheckpointError
        ex = self._exec

        def grab(v):
            return v.data if isinstance(v, nd.NDArray) else v

        tensors = {}
        for n in self._param_names:
            tensors["arg:" + n] = grab(ex.arg_dict[n])
        for n in self._aux_names:
            tensors["aux:" + n] = grab(ex.aux_dict[n])
        meta = {"param_names": list(self._param_names),
                "aux_names": list(self._aux_names)}

        step = 0
        if self.optimizer_initialized:
            if self._fused is not None and self._fused_states is not None:
                structure = {
                    n: tree_encode("opt:%s" % n, s, tensors, grab)
                    for n, s in self._fused_states.items()}
                step = int(self._fused_num_update)
                meta["optimizer"] = {"kind": "fused",
                                     "structure": structure,
                                     "num_update": step}
            elif self._updater is not None:
                structure = {
                    str(idx): tree_encode("upd:%s" % idx, s, tensors, grab)
                    for idx, s in self._updater.states.items()}
                step = int(self._optimizer.num_update)
                meta["optimizer"] = {
                    "kind": "updater", "structure": structure,
                    "num_update": step,
                    "index_update_count": {
                        str(k): int(v) for k, v in
                        self._optimizer._index_update_count.items()}}
            elif self._update_on_kvstore and self._kvstore is not None \
                    and getattr(self._kvstore, "_updater_obj",
                                None) is not None:
                # SPMD dist kvstore: there is no server process — every
                # rank holds the SAME updater/optimizer state locally
                # (set_optimizer constructs it per process), so the
                # snapshot is as local as the eager-updater case. This is
                # what lets a multi-host pod checkpoint/resume through
                # the ordinary fit(checkpoint=..., resume_from=...) path.
                upd = self._kvstore._updater_obj
                structure = {
                    str(idx): tree_encode("upd:%s" % idx, s, tensors,
                                          grab)
                    for idx, s in upd.states.items()}
                step = int(upd.optimizer.num_update)
                meta["optimizer"] = {
                    "kind": "kvstore", "structure": structure,
                    "num_update": step,
                    "index_update_count": {
                        str(k): int(v) for k, v in
                        upd.optimizer._index_update_count.items()}}
            else:
                raise CheckpointError(
                    "optimizer state lives on the kvstore "
                    "(update_on_kvstore) and the store exposes no local "
                    "updater; mx.checkpoint cannot snapshot it — use "
                    "save_optimizer_states / the legacy "
                    "module_checkpoint callback instead")
        meta["step"] = step

        tensors["rng:executor_key"] = key_to_array(ex._base_key)
        meta["executor_step"] = int(ex._step)
        from .. import random as _random
        tensors["rng:global_key"] = key_to_array(_random.current_key())

        # mesh provenance for elastic resume: a restore onto a DIFFERENT
        # mesh is legitimate (reshard-on-load) but worth counting/logging
        if self._mesh is not None:
            from ..parallel.mesh import axis_sizes
            meta["mesh"] = axis_sizes(self._mesh)
        meta["world_size"] = int(self._mesh.devices.size) \
            if self._mesh is not None else 1
        from ..checkpoint.format import pod_info
        pod_rank, pod_world = pod_info()
        if pod_world > 1:
            # multi-host provenance: a resume at a different pod world
            # is the elastic reshard path (counted at restore)
            meta["pod"] = {"process_index": pod_rank,
                           "world_size": pod_world}

        # protect every captured device buffer in ONE jitted copy program
        # (a single dispatch instead of ~2 per-op milliseconds per array
        # — measurably the difference between ~10% and ~40% of the write
        # time on the bench); output buffers are fresh, so the next fused
        # step is free to donate the originals
        live = {k: v for k, v in tensors.items()
                if isinstance(v, jax.Array)}
        if live:
            copies = _snapshot_copy(list(live.values()))
            tensors.update(zip(live.keys(), copies))
        return tensors, meta

    def _checkpoint_restore(self, ckpt):
        """Replay a :class:`mx.checkpoint.Checkpoint`'s optimizer + RNG
        state onto this bound, optimizer-initialized module (parameters
        are restored separately through ``init_params`` — ``fit`` wires
        both). After this, the next fused step continues the interrupted
        run bit-identically: same optimizer-state bytes, same update
        count (so LR schedules resume mid-curve), same dropout key chain.
        """
        assert self.binded and self.params_initialized \
            and self.optimizer_initialized
        from ..checkpoint.manager import array_to_key, tree_decode
        from ..checkpoint.format import CheckpointCorrupt
        tensors = ckpt.tensors

        # elastic resume accounting: restoring onto a different mesh /
        # world size than the save is the reshard-on-load path — the
        # host tensors were reassembled from the recorded index windows
        # and init_params/_replicate_params re-lay them out per THIS
        # module's mesh and param_shardings
        from ..parallel.mesh import axis_sizes
        saved_mesh = ckpt.meta.get("mesh")
        saved_world = ckpt.meta.get("world_size")
        cur_mesh = axis_sizes(self._mesh) if self._mesh is not None \
            else None
        cur_world = int(self._mesh.devices.size) \
            if self._mesh is not None else 1
        resharded = saved_world is not None and \
            (saved_mesh, int(saved_world)) != (cur_mesh, cur_world)
        if resharded:
            self.logger.info(
                "resume: resharding checkpoint saved on mesh %s "
                "(world %s) onto mesh %s (world %d)",
                saved_mesh, saved_world, cur_mesh, cur_world)
        from ..checkpoint.format import pod_info
        saved_pod = int((ckpt.meta.get("pod") or {}).get("world_size", 1))
        cur_pod = pod_info()[1]
        if saved_pod != cur_pod:
            # host death / pod growth: the surviving world resumes the
            # dead world's checkpoint (reassembled from its per-host
            # index windows)
            self.logger.info(
                "resume: checkpoint saved by a %d-host pod restoring "
                "onto a %d-host pod", saved_pod, cur_pod)
        if resharded or saved_pod != cur_pod:
            # ONE reshard event per resume, however many dimensions
            # (device mesh, pod world) changed at once
            _profiler.incr_counter("elastic_reshard")
        from .base_module import _blackbox
        _bb = _blackbox()
        if _bb is not None:
            # the post-mortem's "where did the survivors pick up":
            # which checkpoint, and whether the restore resharded
            _bb.record("resume", os.path.basename(ckpt.path),
                       step=ckpt.step, resharded=bool(resharded),
                       saved_world=saved_world, cur_world=cur_world,
                       saved_pod=saved_pod, cur_pod=cur_pod)
        opt_meta = ckpt.meta.get("optimizer") or {}
        kind = opt_meta.get("kind")
        if kind == "fused":
            if self._fused is None:
                raise CheckpointCorrupt(
                    "%s holds a fused optimizer-state tree but this "
                    "module has no fused step (kvstore/custom-updater "
                    "binding)" % ckpt.path)
            structure = opt_meta["structure"]
            if set(structure) != set(self._fused_states or {}):
                raise CheckpointCorrupt(
                    "%s: optimizer-state params %s do not match the "
                    "bound module's %s"
                    % (ckpt.path, sorted(structure),
                       sorted(self._fused_states or {})))

            # commit each leaf onto the sharding make_states placed the
            # fresh state on (= the parameter's) — an uncommitted
            # jnp.asarray would re-lower the fused step AND break
            # donation on the first resumed step
            def _restore_state(n, s):
                bound = self._exec.arg_dict.get(n)

                def leaf(x):
                    x = jnp.asarray(x)
                    return x if bound is None else \
                        jax.device_put(x, bound.data.sharding)

                return tree_decode("opt:%s" % n, s, tensors, leaf)

            self._fused_states = {n: _restore_state(n, s)
                                  for n, s in structure.items()}
            self._fused_num_update = int(opt_meta["num_update"])
            self._optimizer.num_update = self._fused_num_update
        elif kind == "updater":
            if self._updater is None:
                raise CheckpointCorrupt(
                    "%s holds eager Updater state but this module has "
                    "no local updater" % ckpt.path)
            states = {}
            for sidx, s in opt_meta["structure"].items():
                idx = int(sidx) if sidx.lstrip("-").isdigit() else sidx
                # preserve the saved dtype (nd.array defaults to f32):
                # an f16 momentum buffer resuming as f32 would make the
                # resumed updates compute at a different precision
                states[idx] = tree_decode(
                    "upd:%s" % sidx, s, tensors,
                    lambda x: nd.array(np.asarray(x),
                                       dtype=np.asarray(x).dtype))
            self._updater.states = states
            self._optimizer.num_update = int(opt_meta["num_update"])
            self._optimizer._index_update_count.update(
                {int(k): int(v) for k, v in
                 opt_meta.get("index_update_count", {}).items()})
            self._fused_num_update = self._optimizer.num_update
        elif kind == "kvstore":
            upd = getattr(self._kvstore, "_updater_obj", None) \
                if self._kvstore is not None else None
            if upd is None:
                raise CheckpointCorrupt(
                    "%s holds kvstore updater state but this module is "
                    "not bound to a kvstore with a local updater "
                    "(resume with the same kvstore= as the save)"
                    % ckpt.path)
            states = {}
            for sidx, s in opt_meta["structure"].items():
                idx = int(sidx) if sidx.lstrip("-").isdigit() else sidx
                states[idx] = tree_decode(
                    "upd:%s" % sidx, s, tensors,
                    lambda x: nd.array(np.asarray(x),
                                       dtype=np.asarray(x).dtype))
            upd.states.update(states)
            upd.optimizer.num_update = int(opt_meta["num_update"])
            upd.optimizer._index_update_count.update(
                {int(k): int(v) for k, v in
                 opt_meta.get("index_update_count", {}).items()})
            # the kvstore weight replicas need no replay: init_optimizer
            # already ran kvstore.init with the RESTORED params (fit
            # restores params before the optimizer), and every rank
            # restored the same checkpoint

        raw = tensors.get("rng:executor_key")
        if raw is not None:
            self._exec._base_key = array_to_key(raw,
                                                like=self._exec._base_key)
        es = ckpt.meta.get("executor_step")
        if es is not None:
            self._exec._step = int(es)

    # ------------------------------------------------------------- fused fit
    def _build_fused_step(self):
        """Compile the fit hot loop: forward + backward + optimizer update as
        ONE donated-buffer XLA program (SURVEY.md §7 'Hard parts').

        The per-step python work reduces to: place the batch, call the
        compiled function, swap the new param/state arrays in. With a mesh
        bound, inputs arrive batch-sharded and GSPMD turns the parameter
        gradients into psum-reduced replicated arrays — the collective the
        reference scheduled manually in kvstore Comm.
        """
        if self._updater is None and not self._update_on_kvstore:
            self._fused = None
            self._check_accum_needs_fused()
            return
        if self._update_on_kvstore and self._kvstore is not None \
                and "dist" in self._kvstore.type:
            self._fused = None  # real parameter-server path: not fusable
            self._check_accum_needs_fused()
            return

        optimizer = self._optimizer
        fn = self._exec._fn
        input_names = set(self._data_names) | set(self._label_names) \
            | set(self._state_names)
        # only grad-bearing params are differentiated + updated; fixed
        # params (grad_req null, reference fixed_param_names) ride along as
        # constants exactly like the eager update() path skips them
        param_names = [n for n in self._param_names
                       if self._grad_req.get(n, "null") != "null"]
        frozen = [n for n in self._symbol.list_arguments()
                  if n not in input_names and n not in param_names]
        name2idx = {n: i for i, n in enumerate(self._param_names)}

        # optimizer states are created eagerly (concrete zeros) and then
        # threaded through the jitted step as a pytree; each leaf is
        # committed onto its parameter's sharding — a fresh uncommitted
        # zeros array lowers under a different key than the committed
        # array the jit returns, which costs one spurious recompile (and
        # an unusable donation) on step 2
        def make_states():
            states = {}
            for n in param_names:
                s = optimizer.create_state(name2idx[n],
                                           self._exec.arg_dict[n])
                sharding = self._exec.arg_dict[n].data.sharding

                def _place(x, _sh=sharding):
                    if x is None:
                        return None
                    x = x.data if isinstance(x, nd.NDArray) else x
                    return jax.device_put(x, _sh)

                states[n] = jax.tree_util.tree_map(
                    _place, s,
                    is_leaf=lambda x: isinstance(x, nd.NDArray) or x is None)
            return states

        # ---- applied rematerialization (MXNET_TPU_REMAT; legacy alias
        # MXNET_EXEC_ENABLE_REMAT), whole-forward form: loss_fn below is
        # wrapped in jax.checkpoint under the resolved save policy.
        # Historical caveat (tools/perf/doc_evidence.py, note_memory.md):
        # on dense-attention transformers this form cuts little (the T^2
        # score tensors must exist during the backward recompute anyway).
        remat_policy = None
        if _config.get("MXNET_TPU_REMAT") != "off" \
                or _config.get("MXNET_EXEC_ENABLE_REMAT"):
            # the executor resolved the same whole-forward policy for
            # its non-fused fwd_bwd path already — reuse it (one
            # analysis run per bind, one remat_applied count)
            remat_policy = getattr(self._exec, "_fwd_bwd_remat", None)
            if remat_policy is None:
                from .. import remat as _remat
                shapes = {n: tuple(a.shape)
                          for n, a in self._exec.arg_dict.items()}
                shapes.update({n: tuple(a.shape)
                               for n, a in self._exec.aux_dict.items()})
                dts = {n: a.dtype for n, a in self._exec.arg_dict.items()}
                # aux dtypes too: BatchNorm running stats must price at
                # their real width in the remat ranking (the PR 8 rule)
                dts.update({n: a.dtype
                            for n, a in self._exec.aux_dict.items()})
                remat_policy, _ = _remat.resolve_policy(
                    self._symbol, input_shapes=shapes, input_dtypes=dts)
                if remat_policy is not None:
                    _profiler.incr_counter("remat_applied")

        # ---- microbatch gradient accumulation (fit(grad_accum=N) /
        # set_grad_accum): the bound batch is split into N equal
        # microbatches driven through ONE lax.scan inside the step, so
        # only one microbatch's activations are ever live — batch sizes
        # that saturate the chip fit in HBM at N× smaller activation
        # high-water. Accumulated gradients are rescaled so the update
        # matches the unaccumulated full-batch step exactly (see
        # _accum_loss_scale for the loss-normalization contract).
        accum = max(1, int(getattr(self, "_grad_accum", 1) or 1))
        accum_scale = 1.0
        if accum > 1:
            for d in (self._data_shapes or []) + (self._label_shapes or []):
                if d.shape and d.shape[0] % accum:
                    raise MXNetError(
                        "grad_accum=%d does not divide the %r batch "
                        "dimension %d" % (accum, d.name, d.shape[0]))
            accum_scale = _accum_loss_scale(self._symbol, accum)
            _profiler.set_gauge("grad_accum", accum)

        def step(params, states, aux, inputs, frozen_vals, key, lr, t):
            def forward(p_in, aux_in, inp, k):
                def loss_fn(p):
                    outs, new_aux = fn({**p, **inp, **frozen_vals},
                                       aux_in, k, True)
                    return outs, new_aux

                if remat_policy is not None:
                    loss_fn = jax.checkpoint(loss_fn, policy=remat_policy)
                (outs, new_aux), vjp = jax.vjp(loss_fn, p_in)
                cts = [jnp.ones_like(o) for o in outs]
                grads = vjp((cts, {k2: jnp.zeros_like(v)
                                   for k2, v in new_aux.items()}))[0]
                return outs, new_aux, grads

            if accum > 1:
                micro = {n: v.reshape((accum, v.shape[0] // accum)
                                      + v.shape[1:])
                         for n, v in inputs.items()}

                def micro_step(carry, xs):
                    g_acc, aux_c = carry
                    # per-microbatch RNG: fold the step key once more so
                    # dropout draws differ across microbatches
                    outs, new_aux, grads = forward(
                        params, aux_c, xs["inp"],
                        jax.random.fold_in(key, xs["i"]))
                    g_acc = jax.tree_util.tree_map(jnp.add, g_acc, grads)
                    # aux (BatchNorm stats) advance sequentially, exactly
                    # like N consecutive small-batch steps
                    return (g_acc, {**aux_c, **new_aux}), outs

                g0 = jax.tree_util.tree_map(jnp.zeros_like,
                                            {n: params[n]
                                             for n in param_names})
                (grads, new_aux), outs_stacked = jax.lax.scan(
                    micro_step, (g0, aux),
                    {"i": jnp.arange(accum, dtype=jnp.int32),
                     "inp": micro})
                if accum_scale != 1.0:
                    grads = {n: g * accum_scale for n, g in grads.items()}
                outs = [o.reshape((-1,) + o.shape[2:])
                        for o in outs_stacked]
            else:
                outs, new_aux, grads = forward(params, aux, inputs, key)
            new_params, new_states = {}, {}
            for n in param_names:
                w, s = optimizer.raw_update(
                    name2idx[n], params[n], grads[n], states[n], lr=lr, t=t)
                new_params[n] = w
                new_states[n] = s
            return outs, new_params, new_states, new_aux

        self._fused_num_update = self._optimizer.num_update
        self._fused_compiles = 0

        # ---- non-finite step guard (MXNET_TPU_NANCHECK): a device-side
        # isfinite reduction chained onto every fused step — same
        # pattern as device metrics, zero host syncs; the flags are
        # fetched once per epoch at the log boundary (_nancheck_poll),
        # where warn logs and abort raises naming the first non-finite
        # output. off = nothing built, nothing chained.
        self._nancheck_mode = _config.get("MXNET_TPU_NANCHECK")
        self._nancheck_fn = None
        self._nancheck_idx = ()
        self._nan_flags = None

        def run(data_batch):
            ex = self._exec
            self._load_batch(data_batch)
            params = {n: ex.arg_dict[n].data for n in param_names}
            states = self._fused_states
            aux = {n: a.data for n, a in ex.aux_dict.items()}
            inputs = {n: ex.arg_dict[n].data for n in
                      (set(self._data_names) | set(self._label_names)
                       | set(self._state_names))
                      if n in ex.arg_dict}
            frozen_vals = {n: ex.arg_dict[n].data for n in frozen}
            ex._step += 1
            key = jax.random.fold_in(ex._base_key, ex._step)
            self._fused_num_update += 1
            t = self._fused_num_update
            self._optimizer.num_update = t
            if self._optimizer.lr_scheduler is not None:
                lr = self._optimizer.lr_scheduler(t)
            else:
                lr = self._optimizer.lr
            call_args = (params, states, aux, inputs, frozen_vals, key,
                         jnp.asarray(lr, jnp.float32),
                         jnp.asarray(t, jnp.int32))
            with _obs_compiles.scope("fused_step", self._obs_sig):
                # looked up on the instance at every step: what replaces
                # _fused_jit after the build runs from the next step on
                outs, new_params, new_states, new_aux = \
                    self._fused_jit(*call_args)
            if self._nancheck_mode != "off":
                self._nancheck_accumulate(outs)
            if accum > 1:
                _profiler.incr_counter("accum_steps", accum)
            n = self._obs_steps + 1
            self._obs_steps = n
            if n == _obs_mfu.OBS_WARMUP_STEPS:
                # rate window opens after the compile steps; report()
                # closes it (and re-opens) at each collect
                self._obs_t0 = time.perf_counter()
            cache_size = getattr(self._fused_jit, "_cache_size", None)
            if cache_size is not None:
                # steady-state recompiles are a bug the async tests assert
                # against; count executable-cache growth past the warmup
                # compile (shape churn, accidental static arg drift)
                n = cache_size()
                if n > self._fused_compiles:
                    if self._fused_compiles > 0:
                        _profiler.incr_counter("loop_recompile",
                                               n - self._fused_compiles)
                    self._fused_compiles = n
            if ex._sync_host_callbacks:
                # callback-bearing program: execute synchronously with
                # the frontend (see executor.py / operator.py — the
                # async-drain deadlock)
                ex._forced_sync(outs)
            for n in param_names:
                ex.arg_dict[n]._data = new_params[n]
                ex.arg_dict[n]._version += 1
            for n, v in new_aux.items():
                ex.aux_dict[n]._data = v
                ex.aux_dict[n]._version += 1
            self._fused_states = new_states
            self._fused_out = [nd.NDArray(o) for o in outs]
            ex._outputs = self._fused_out
            ex._pending = None
            self._params_dirty = True

        # obs identity for compile attribution + the MFU collector; the
        # static FLOP estimate is invalidated here because a rebuild means
        # shapes (reshape) or structure changed
        self._obs_label = "fused_step:%s" % (
            self._output_names[0] if self._output_names else "?")
        self._obs_sig = (self._obs_label,
                         tuple((d.name, tuple(d.shape))
                               for d in self._data_shapes or ()))
        self._obs_flops_per_step = None
        _obs_mfu.register_executor(self)

        if getattr(self, "_fused_states", None) is None or \
                set(self._fused_states) != set(param_names):
            self._fused_states = make_states()

        if self._mesh is not None:
            # pin updated params to their declared shardings — otherwise
            # GSPMD may pick a different output layout after the first
            # step and the user-declared tp partitioning drifts — and pin
            # updated optimizer states to the shardings make_states placed
            # the INPUT states on: with the inputs committed, GSPMD is
            # free to pick a different layout for the returned state (a
            # replicated bias's momentum whose grad arrives model-sharded,
            # say), and a donated input cannot alias an output of a
            # different per-device size
            param_sh = {n: self._sharding_for(n) for n in param_names}
            state_sh = jax.tree_util.tree_map(lambda x: x.sharding,
                                              self._fused_states)
            self._fused_jit = jax.jit(
                step, donate_argnums=(0, 1, 2),
                out_shardings=(None, param_sh, state_sh, None))
        else:
            self._fused_jit = jax.jit(step, donate_argnums=(0, 1, 2))
        self._fused = run

    def _check_accum_needs_fused(self) -> None:
        if getattr(self, "_grad_accum", 1) > 1:
            raise MXNetError(
                "grad_accum > 1 requires the fused train step; this "
                "binding falls back to eager update (kvstore/custom "
                "updater) which cannot microbatch")

    def set_grad_accum(self, n: int) -> None:
        """Microbatch gradient accumulation: the fused step splits every
        bound batch into ``n`` equal microbatches run through one
        ``lax.scan`` with gradient carry, so activation memory scales
        with the microbatch while the optimizer sees the full-batch
        gradient (``fit(grad_accum=n)`` routes here). ``n=1`` restores
        the flat step."""
        n = int(n)
        if n < 1:
            raise MXNetError("grad_accum must be >= 1, got %d" % n)
        if n != getattr(self, "_grad_accum", 1):
            self._grad_accum = n
            if self.optimizer_initialized:
                self._build_fused_step()

    def _fit_step(self, data_batch):
        """One fused train step; fit() uses this when available."""
        if self._fused is None:
            self.forward_backward(data_batch)
            self.update()
        else:
            self._fused(data_batch)

    # ------------------------------------------------- non-finite guard
    def _nancheck_accumulate(self, outs):
        """Chain one tiny jitted reduction onto this step's outputs:
        per-output "ever went non-finite" flags accumulated ON DEVICE
        (async dispatch — the step loop never syncs for it). Integer
        outputs are skipped; a program with no inexact outputs disables
        the guard for this bind."""
        import jax
        import jax.numpy as jnp
        if self._nancheck_fn is None:
            idx = tuple(i for i, o in enumerate(outs)
                        if jnp.issubdtype(o.dtype, jnp.inexact))
            if not idx:
                self._nancheck_mode = "off"
                return
            self._nancheck_idx = idx

            @jax.jit
            def chained(flags, outs_t):
                return tuple(f | ~jnp.all(jnp.isfinite(outs_t[i]))
                             for f, i in zip(flags, idx))

            self._nancheck_fn = chained
        flags = self._nan_flags
        if flags is None:
            flags = tuple(jnp.zeros((), jnp.bool_)
                          for _ in self._nancheck_idx)
        self._nan_flags = self._nancheck_fn(flags, tuple(outs))

    def _nancheck_poll(self) -> Optional[str]:
        """The log-boundary host fetch of the chained flags (the ONE
        sync, same place as the metric sync): returns the name of the
        first non-finite output, or None. Resets the accumulator so
        each epoch is judged on its own steps."""
        flags = self._nan_flags
        if flags is None:
            return None
        import jax
        host = [bool(v) for v in jax.device_get(flags)]
        self._nan_flags = None
        for i, hit in zip(self._nancheck_idx, host):
            if hit:
                names = self._output_names or []
                return names[i] if i < len(names) else "output%d" % i
        return None

    # ------------------------------------------------------------- compute
    def _input_sharding(self, ndim):
        """Where a bound input of rank ``ndim`` lives: the one device, or
        on a mesh batch-sharded / replicated as the layout says."""
        if self._mesh is None:
            return self._context[0].jax_device
        from ..parallel.mesh import batch_sharding, replicated_sharding
        if ndim == 0:
            # rank-0 inputs have no batch dim to shard (bind-time
            # validation skips them the same way) — replicate
            return replicated_sharding(self._mesh)
        if self._batch_sharding is not None:
            # unified layout: the batch shards over BOTH data-parallel
            # axes (data, fsdp) — validated at bind
            return self._batch_sharding
        if "data" in self._mesh.axis_names:
            return batch_sharding(self._mesh)
        # pure tensor-parallel mesh: the batch is replicated
        return replicated_sharding(self._mesh)

    def _place_value(self, name, arr):
        """One input's device placement: dtype cast + shard/replicate per
        the bound mesh (or plain device_put). Shared by the critical-path
        ``_load_batch`` and the background device-prefetch stage, so a
        prefetched batch lands exactly where a synchronous one would.
        An image batch still owed its finish (``io.DeferredImages``)
        crosses as the decoder's uint8 numpy, once, and is finished where
        it lands."""
        tgt = self._exec.arg_dict.get(name)
        if tgt is None:
            return None
        if isinstance(arr, DeferredImages):
            placed = jax.device_put(arr.pixels,
                                    self._input_sharding(arr.pixels.ndim))
            _profiler.incr_counter("io_batches_finished_on_device")
            return arr.finish_placed(placed, tgt.data.dtype)
        val = arr.data if isinstance(arr, nd.NDArray) else \
            jnp.asarray(np.asarray(arr))
        if val.dtype != tgt.data.dtype:
            val = val.astype(tgt.data.dtype)
        return jax.device_put(val, self._input_sharding(val.ndim))

    @staticmethod
    def _batch_data(data_batch):
        """The batch's data inputs as they are to be placed: the deferred
        form where the batch carries one (reading ``.data`` would finish
        it on the host first)."""
        return getattr(data_batch, "deferred", None) or data_batch.data

    def _load_batch(self, data_batch):
        """Place batch data/labels into the bound args; with a mesh, inputs
        are batch-sharded over the `data` axis (the TPU form of
        _load_data/_load_label slicing in executor_group.py:31-75). Batches
        the device-prefetch stage already placed (``_mx_placed``) are
        swapped in without touching the device."""
        ex = self._exec
        data = self._batch_data(data_batch)
        labels = data_batch.label or []
        placed = getattr(data_batch, "_mx_placed", None)

        def place(name, arr):
            if placed is not None and name in placed:
                val = placed[name]
            else:
                val = self._place_value(name, arr)
                if val is None:
                    return
            tgt = ex.arg_dict.get(name)
            if tgt is None:
                return
            tgt._data = val
            tgt._version += 1

        for name, arr in zip(self._data_names, data):
            place(name, arr)
        for name, arr in zip(self._label_names, labels):
            place(name, arr)

    # ----------------------------------------------------------- async loop
    def _async_capable(self) -> bool:
        """True when fit() may run the bounded-in-flight async loop: the
        fused step exists and the bound program carries no host callbacks
        (callback programs must stay synchronous — executor.py
        requires_sync_loop, the PR 2 deadlock)."""
        return (self._fused is not None and self._exec is not None
                and not self._exec.requires_sync_loop)

    def _step_token(self):
        """Completion token of the last fused step (its raw output arrays)
        for the InflightWindow; None when no fused step ran."""
        if self._fused_out is None:
            return None
        return tuple(o.data for o in self._fused_out)

    def _device_placer(self):
        """Callable the PrefetchingIter device stage runs in a background
        thread: issues the H2D placement (honoring mesh input shardings)
        for every data/label input and stashes the placed arrays on the
        batch; ``_load_batch`` then swaps them in with zero device work on
        the critical path."""
        if self._exec is None:
            return None

        def place_batch(data_batch):
            placed = {}
            for name, arr in zip(self._data_names,
                                 self._batch_data(data_batch) or []):
                val = self._place_value(name, arr)
                if val is not None:
                    placed[name] = val
            for name, arr in zip(self._label_names,
                                 data_batch.label or []):
                val = self._place_value(name, arr)
                if val is not None:
                    placed[name] = val
            data_batch._mx_placed = placed
            return data_batch

        return place_batch

    def _update_metric_device(self, eval_metric, labels) -> bool:
        """Device-resident metric update: hand the metric the step's own
        device arrays (labels from the bound args — already placed/sharded
        — and the fused step's outputs) so accumulation is a chained
        device reduction with no host sync. Returns False when the metric
        cannot (custom/numpy metrics) and the caller must run the host
        path."""
        if not eval_metric.device_capable():
            return False
        ex = self._exec
        label_names = self._label_names or \
            [d.name for d in self._label_shapes]
        label_dict = {}
        for name, arr in zip(label_names, labels or []):
            bound = ex.arg_dict.get(name)
            label_dict[name] = bound.data if bound is not None else arr
        preds = dict(zip(self._output_names, self.get_outputs()))
        return eval_metric.update_dict_device(label_dict, preds)

    def forward(self, data_batch, is_train=None):
        """(reference: module.py:556)."""
        assert self.binded and self.params_initialized
        if is_train is None:
            is_train = self.for_training
        self._load_batch(data_batch)
        self._exec.forward(is_train=is_train)
        if is_train:
            self._params_dirty = True  # aux states may advance

    def backward(self, out_grads=None):
        """(reference: module.py:598)."""
        assert self.binded and self.params_initialized
        self._exec.backward(out_grads=out_grads)

    def update(self):
        """Apply gradients (reference: module.py:615 →
        model.py:106 _update_params_on_kvstore)."""
        assert self.binded and self.params_initialized \
            and self.optimizer_initialized
        self._params_dirty = True
        if self._kvstore is not None:
            for idx, name in enumerate(self._param_names):
                grad = self._exec.grad_dict.get(name)
                if grad is None:
                    continue
                weight = self._exec.arg_dict[name]
                self._kvstore.push(idx, grad)
                if self._update_on_kvstore:
                    self._kvstore.pull(idx, out=weight)
                else:
                    self._kvstore.pull(idx, out=grad)
                    self._updater(idx, grad, weight)
        else:
            items = []
            for idx, name in enumerate(self._param_names):
                grad = self._exec.grad_dict.get(name)
                if grad is None:
                    continue
                items.append((idx, self._exec.arg_dict[name], grad))
            # same fused whole-model step as gluon Trainer.step: all
            # updates in one structure-cached jitted program, per-param
            # eager dispatch as the fallback
            if self._fused_updater is not None \
                    and self._fused_updater.try_step(self._updater, items):
                return
            for idx, weight, grad in items:
                self._updater(idx, grad, weight)

    def get_outputs(self, merge_multi_context=True):
        """(reference: module.py get_outputs). One program ⇒ already
        merged."""
        assert self.binded and self.params_initialized
        return self._exec.outputs

    def get_input_grads(self, merge_multi_context=True):
        """(reference: module.py get_input_grads)."""
        assert self.binded and self.params_initialized \
            and self.inputs_need_grad
        return [self._exec.grad_dict.get(n) for n in self._data_names]

    def get_states(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        return [self._exec.arg_dict[n] for n in self._state_names]

    def set_states(self, states=None, value=None):
        assert self.binded and self.params_initialized
        if states is not None:
            for name, val in zip(self._state_names, states):
                self._exec.arg_dict[name]._data = \
                    val.data if isinstance(val, nd.NDArray) else \
                    jnp.asarray(val)
                self._exec.arg_dict[name]._version += 1
        else:
            for name in self._state_names:
                arr = self._exec.arg_dict[name]
                arr._data = jnp.full_like(arr.data, value)
                arr._version += 1

    def update_metric(self, eval_metric, labels):
        """(reference: module.py update_metric → executor_group
        update_metric)."""
        labels = {name: arr for name, arr in
                  zip(self._label_names or
                      [d.name for d in self._label_shapes], labels)}
        preds = dict(zip(self._output_names, self.get_outputs()))
        eval_metric.update_dict(labels, preds)

    def reshape(self, data_shapes, label_shapes=None):
        """(reference: module.py reshape). Shapes re-bind lazily: XLA caches
        one executable per shape signature."""
        assert self.binded
        self._data_shapes = [x if isinstance(x, DataDesc) else DataDesc(*x)
                             for x in data_shapes]
        if label_shapes is not None:
            self._label_shapes = [x if isinstance(x, DataDesc)
                                  else DataDesc(*x) for x in label_shapes]
        kw = {d.name: d.shape for d in self._data_shapes}
        if label_shapes:
            kw.update({d.name: d.shape for d in self._label_shapes})
        self._exec = self._exec.reshape(**kw)
        if self.optimizer_initialized:
            self._build_fused_step()

    def install_monitor(self, mon):
        """(reference: module.py install_monitor)."""
        assert self.binded
        mon.install(self._exec)
