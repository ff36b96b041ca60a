"""Graph executor.

Reference: ``src/executor/graph_executor.cc`` + ``include/mxnet/executor.h``
(SURVEY.md §2.6): the reference binds a symbol into per-node engine ops with a
memory plan; Forward/Backward push cached ops in topo order.

TPU design: the whole bound graph is ONE jitted XLA program (SURVEY.md §7 —
the dependency engine, PlanMemory pass and bulk-exec segments all collapse
into XLA compilation/buffer assignment). Three compiled entry points per
executor:

* forward (inference): jitted graph function.
* forward+backward (training): one jitted program computing outputs AND all
  requested input gradients via ``jax.vjp`` — ``Executor.forward(is_train=
  True)`` defers computation so ``backward()`` runs the fused program once
  (no duplicated forward FLOPs in the fit loop).
* aux states (BatchNorm moving stats) are returned functionally and committed
  after each step (the reference mutates them in-place during Forward).

Model parallelism (`group2ctx`, reference graph_executor.cc:279-393
AssignContext + PlaceDevice + _CrossDeviceCopy): bound arrays are placed on
their group's device and the graph executes op-by-op with explicit boundary
transfers — the reference's one-engine-op-per-node schedule with copy
nodes. One XLA program cannot span explicit single-device placements, so
this mode is NOT wrapped in an outer jit (see graph_function).
"""
from __future__ import annotations

import inspect
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np
import jax
import jax.numpy as jnp

from .base import MXNetError
from .context import Context
from . import ndarray as _nd
from . import random as _random
from .obs import compiles as _obs_compiles

__all__ = ["Executor", "graph_function"]


_IMPLICIT_ATTRS = ("_is_train", "_batch_rows")


def _implicit_attrs(op) -> frozenset:
    """The implicit attributes ``op.fn`` declares as parameters."""
    cached = getattr(op, "_implicit_attrs", None)
    if cached is None:
        try:
            params = inspect.signature(op.fn).parameters
        except (TypeError, ValueError):
            params = ()
        cached = frozenset(a for a in _IMPLICIT_ATTRS if a in params)
        op._implicit_attrs = cached
    return cached


def graph_function(symbol, node_device=None, batch_rows=None):
    """Compile a Symbol into a pure function
    ``fn(args_dict, aux_dict, rng_key, is_train) -> (outputs, new_aux_dict)``.

    The TPU analogue of GraphExecutor::InitCachedOps + RunOps
    (graph_executor.cc:1013-1231): instead of one engine op per node, the
    topo-ordered node list becomes one traced JAX program for XLA to fuse
    and schedule.

    ``node_device`` (optional) maps a node to a jax device for model
    parallelism (group2ctx): each op then runs on its group's device with
    explicit boundary transfers — the PlaceDevice + CopyNode pass of the
    reference (graph_executor.cc:279-393). One XLA program cannot span
    explicit single-device placements, so this mode executes op-by-op
    (exactly the reference's one-engine-op-per-node schedule) and must not
    be wrapped in an outer jit.

    ``batch_rows`` (optional): ``(mesh, axes)`` — the mesh the graph's
    values are placed on and the axes the batch dimension is sharded over
    (``()`` when the batch is replicated). GSPMD partitions ordinary ops
    from the operand shardings alone; the one kind of op it cannot see
    into (a Mosaic kernel) declares a ``_batch_rows`` parameter, receives
    this, and partitions itself.
    """
    from .symbol.symbol import _topo_order

    nodes = _topo_order(symbol._entries)
    node_index = {id(n): i for i, n in enumerate(nodes)}
    entries = list(symbol._entries)

    def fn(args: Dict[str, Any], aux: Dict[str, Any], key, is_train: bool):
        vals: Dict[Any, Any] = {}
        new_aux: Dict[str, Any] = {}

        def exec_node(node):
            idx = node_index[id(node)]
            if node.is_variable:
                if node.name in args:
                    v = args[node.name]
                elif node.name in aux:
                    v = aux[node.name]
                else:
                    raise MXNetError("unbound variable %r" % node.name)
                vals[(id(node), 0)] = v
                return
            ins = [vals[(id(n), i)] for n, i in node.inputs]
            outs = _run_node(node, ins, key, idx, is_train, node_device,
                             batch_rows)
            for i, o in enumerate(outs):
                vals[(id(node), i)] = o
            n_aux = node.op.num_aux
            if n_aux:
                for (src, _), val in zip(node.inputs[-n_aux:],
                                         outs[-n_aux:]):
                    if src.is_variable:
                        new_aux[src.name] = val

        for node in nodes:
            exec_node(node)
        outputs = [vals[(id(n), i)] for n, i in entries]
        return outputs, new_aux

    return fn


def _run_node(node, ins, key, idx, is_train, node_device=None,
              batch_rows=None):
    """Execute one graph node: implicit attrs (_is_train, _batch_rows,
    per-node RNG), group2ctx boundary transfer, tuple-normalized outputs.
    The single definition both graph_function and Executor.monitor_values
    dispatch through, so monitored values cannot drift from executed
    values."""
    attrs = dict(node.attrs)
    attrs.pop("name", None)
    implicit = _implicit_attrs(node.op)
    if "_is_train" in implicit:
        attrs["_is_train"] = is_train
    if "_batch_rows" in implicit:
        attrs["_batch_rows"] = batch_rows
    if node.op.needs_rng:
        attrs["_rng"] = jax.random.fold_in(key, idx)
    if node_device is not None:
        dev = node_device(node)
        if dev is not None:
            # boundary transfer: inputs produced on another group's device
            # hop here (the reference's copy node)
            ins = [jax.device_put(x, dev) for x in ins]
    outs = node.op.fn(*ins, **attrs)
    return outs if isinstance(outs, tuple) else (outs,)


def _normalize_dict(values, names, what):
    if values is None:
        return None
    if isinstance(values, dict):
        return dict(values)
    if isinstance(values, (list, tuple)):
        if len(values) != len(names):
            raise MXNetError("%s: expected %d entries, got %d"
                             % (what, len(names), len(values)))
        return dict(zip(names, values))
    raise MXNetError("%s must be list or dict" % what)


class Executor:
    """Bound computation (reference: include/mxnet/executor.h:52-152)."""

    def __init__(self, symbol, ctx: Context, args, args_grad=None,
                 grad_req="write", aux_states=None, group2ctx=None,
                 shared_exec=None, batch_rows=None):
        self._symbol = symbol
        self._ctx = ctx
        self._arg_names = symbol.list_arguments()
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()

        self.arg_dict: Dict[str, _nd.NDArray] = \
            _normalize_dict(args, self._arg_names, "args") or {}
        missing = [n for n in self._arg_names if n not in self.arg_dict]
        if missing:
            raise MXNetError("bind: missing arguments %s" % missing)
        self.aux_dict: Dict[str, _nd.NDArray] = \
            _normalize_dict(aux_states, self._aux_names, "aux_states") or {}
        missing = [n for n in self._aux_names if n not in self.aux_dict]
        if missing:
            raise MXNetError("bind: missing auxiliary states %s" % missing)

        if isinstance(grad_req, str):
            self._grad_req = {n: grad_req for n in self._arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self._grad_req = dict(zip(self._arg_names, grad_req))
        else:
            self._grad_req = {n: grad_req.get(n, "null")
                              for n in self._arg_names}
        self.grad_dict: Dict[str, _nd.NDArray] = \
            _normalize_dict(args_grad, self._arg_names, "args_grad") or {}
        self._wrt = [n for n in self._arg_names
                     if self._grad_req.get(n, "null") != "null"
                     and n in self.grad_dict]

        # bind-time static analysis (ISSUE 3): graph passes run over the
        # symbol with the bound shapes BEFORE any trace/compile. Gated on
        # the MXNET_TPU_ANALYZE knob with a lazy import so the default
        # (off) pays one dict lookup and never imports the analyzer.
        from . import config as _config
        _analyze_mode = _config.get("MXNET_TPU_ANALYZE")
        if _analyze_mode != "off":
            from .analysis import check_bind as _check_bind
            shapes = {n: tuple(a.shape) for n, a in self.arg_dict.items()}
            shapes.update(
                {n: tuple(a.shape) for n, a in self.aux_dict.items()})
            dtypes = {n: a.dtype for n, a in self.arg_dict.items()}
            # aux dtypes too: the memory passes price BatchNorm running
            # stats against the HBM budget at their real width
            dtypes.update({n: a.dtype for n, a in self.aux_dict.items()})
            _check_bind(symbol, input_shapes=shapes,
                        input_dtypes=dtypes, mode=_analyze_mode,
                        context="bind")

        self._group2ctx = group2ctx
        self._shared_exec = shared_exec
        # compile-accounting label: every jit dispatch below runs under
        # an obs compile scope so a bind/trace wedge is attributable to
        # this executor in mx.obs.report() (docs/architecture/
        # observability.md)
        self._obs_label = "graph:%s" % (
            self._output_names[0] if self._output_names else "?")
        self._batch_rows = batch_rows
        self._fn = graph_function(symbol, self._node_device_fn(),
                                  batch_rows=batch_rows)
        # programs embedding host-callback custom ops must run
        # synchronously with the frontend: async execution + concurrent
        # eager dispatch deadlocks the CPU runtime (the train_rcnn eval
        # hang — see operator.prop_uses_host_callback)
        from . import operator as _operator
        self._sync_host_callbacks = \
            _operator.symbol_has_host_callback(symbol)
        self._base_key = _random.next_key()
        self._step = 0
        self._outputs: Optional[List[_nd.NDArray]] = None
        self._pending = None   # (arg_vals, aux_vals, key) awaiting fused bwd
        self._monitor_callback = None

        in_shardings = self._arg_shardings()
        if in_shardings is not None:
            # the PlaceDevice step (reference graph_executor.cc:279-393):
            # move the bound arrays onto their group's device; the graph
            # then executes op-by-op with boundary transfers (one XLA
            # program cannot span explicit single-device placements)
            arg_sh, aux_sh = in_shardings
            for name, sh in arg_sh.items():
                nd_arr = self.arg_dict[name]
                if nd_arr.data.sharding != sh:
                    nd_arr._data = jax.device_put(nd_arr.data, sh)
                gbuf = self.grad_dict.get(name)
                if gbuf is not None and gbuf.data.sharding != sh:
                    gbuf._data = jax.device_put(gbuf.data, sh)
            for name, sh in aux_sh.items():
                nd_arr = self.aux_dict[name]
                if nd_arr.data.sharding != sh:
                    nd_arr._data = jax.device_put(nd_arr.data, sh)
            self._jit_fwd = self._fn          # staged eager execution
        else:
            self._jit_fwd = jax.jit(self._fn, static_argnums=(3,))

        # ---- applied remat on the NON-FUSED training path:
        # forward_backward + update drivers (kvstore binds, custom
        # updaters, monitor mode) trace fwd_bwd below, which never goes
        # through Module._build_fused_step's wrap. The fused step reuses
        # the policy resolved here (one analysis run per bind); the wrap
        # itself does not reach the fused step's loss_fn.
        self._fwd_bwd_remat = None
        if self._wrt and (
                _config.get("MXNET_TPU_REMAT") != "off"
                or _config.get("MXNET_EXEC_ENABLE_REMAT")):
            from . import remat as _remat
            shapes = {n: tuple(a.shape) for n, a in self.arg_dict.items()}
            shapes.update({n: tuple(a.shape)
                           for n, a in self.aux_dict.items()})
            dts = {n: a.dtype for n, a in self.arg_dict.items()}
            dts.update({n: a.dtype for n, a in self.aux_dict.items()})
            policy, _ = _remat.resolve_policy(
                self._symbol, input_shapes=shapes, input_dtypes=dts)
            if policy is not None:
                self._fwd_bwd_remat = policy
                from . import profiler as _profiler
                _profiler.incr_counter("remat_applied")

        def fwd_bwd(arg_vals, aux_vals, key, head_grads):
            diff = {n: arg_vals[n] for n in self._wrt}
            rest = {n: v for n, v in arg_vals.items() if n not in diff}

            def f(d):
                outs, new_aux = self._fn({**rest, **d}, aux_vals, key, True)
                return outs, new_aux

            if self._fwd_bwd_remat is not None:
                f = jax.checkpoint(f, policy=self._fwd_bwd_remat)
            (outs, new_aux), vjp = jax.vjp(f, diff, has_aux=False)
            cts = [g if g is not None else jnp.ones_like(o)
                   for g, o in zip(head_grads, outs)]
            grads = vjp((cts, {k: jnp.zeros_like(v)
                               for k, v in new_aux.items()}))[0]
            return outs, new_aux, grads

        # group2ctx mode: jax.vjp over the staged fn runs forward op-by-op
        # on the placed devices and replays transposed transfers backward
        self._jit_fwd_bwd = fwd_bwd if in_shardings is not None \
            else jax.jit(fwd_bwd)

    @property
    def requires_sync_loop(self) -> bool:
        """True when programs from this executor must execute synchronously
        with the frontend (host-callback CustomOps — the PR 2 async-drain
        deadlock). The fit loop consults this to force
        ``MXNET_TPU_ASYNC_WINDOW=0`` behavior and skip device prefetch:
        background jax dispatch concurrent with a callback-bearing program
        is exactly the deadlock shape."""
        return self._sync_host_callbacks

    @staticmethod
    def _forced_sync(values) -> None:
        """Block on ``values`` because the program carries host callbacks —
        the one sync the async loop can never remove, counted so tests and
        the analysis self-check can see it (``loop_forced_sync``)."""
        from . import profiler as _profiler
        _profiler.incr_counter("loop_forced_sync")
        jax.block_until_ready(values)

    # ------------------------------------------------------------ placement
    def _node_device_fn(self):
        """Node -> jax device from its ctx_group (None without group2ctx)."""
        if not self._group2ctx:
            return None
        group2ctx = self._group2ctx
        default = self._ctx

        def node_device(node):
            g = node.str_attrs.get("ctx_group")
            ctx = group2ctx.get(g, default) if g else default
            return ctx.jax_device

        return node_device

    # ------------------------------------------------------------ shardings
    def _arg_shardings(self):
        """group2ctx → per-argument SingleDeviceSharding (the PlaceDevice
        pass, reference graph_executor.cc:279-393)."""
        if not self._group2ctx:
            return None
        from .symbol.symbol import _topo_order
        from jax.sharding import SingleDeviceSharding

        group_of: Dict[str, str] = {}
        for node in _topo_order(self._symbol._entries):
            g = node.str_attrs.get("ctx_group")
            if not g:
                continue
            if node.is_variable:
                group_of.setdefault(node.name, g)
            else:
                for src, _ in node.inputs:
                    if src.is_variable:
                        group_of.setdefault(src.name, g)

        def dev_for(name):
            g = group_of.get(name)
            ctx = self._group2ctx.get(g, self._ctx) if g else self._ctx
            return SingleDeviceSharding(ctx.jax_device)

        arg_sh = {n: dev_for(n) for n in self._arg_names}
        aux_sh = {n: dev_for(n) for n in self._aux_names}
        return arg_sh, aux_sh

    # ------------------------------------------------------------ running
    def _gather(self):
        arg_vals = {n: a.data for n, a in self.arg_dict.items()}
        aux_vals = {n: a.data for n, a in self.aux_dict.items()}
        self._step += 1
        key = jax.random.fold_in(self._base_key, self._step)
        return arg_vals, aux_vals, key

    def forward(self, is_train: bool = False, **kwargs) -> List[_nd.NDArray]:
        """(reference: GraphExecutor::Forward, graph_executor.cc:50). With
        ``is_train=True`` the computation is deferred so ``backward`` can run
        the fused forward+backward program once."""
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError("forward: unknown argument %r" % k)
            self.arg_dict[k]._data = v.data if isinstance(v, _nd.NDArray) \
                else jnp.asarray(v)
            self.arg_dict[k]._version += 1
        arg_vals, aux_vals, key = self._gather()
        self._last_is_train = bool(is_train)
        if is_train and self._wrt:
            # deferred: backward() runs the fused fwd+bwd once; forcing
            # outputs here (e.g. for a monitor) would double the forward
            self._pending = (arg_vals, aux_vals, key)
            self._outputs = None
        else:
            with _obs_compiles.scope(self._obs_label):
                outs, new_aux = self._jit_fwd(arg_vals, aux_vals, key,
                                              bool(is_train))
            if self._sync_host_callbacks:
                self._forced_sync(outs)
            self._commit(outs, new_aux)
            self._pending = None
        return self.outputs

    def backward(self, out_grads=None) -> None:
        """(reference: GraphExecutor::Backward, graph_executor.cc:63).
        Runs the fused forward+backward program; gradients are committed to
        ``grad_dict`` honoring grad_req write/add (kAddTo semantics,
        include/mxnet/op_attr_types.h:45-58)."""
        if self._pending is None:
            raise MXNetError("backward called without forward(is_train=True)")
        arg_vals, aux_vals, key = self._pending
        if out_grads is None:
            heads = [None] * len(self._output_names)
        elif isinstance(out_grads, (list, tuple)):
            heads = [g.data if isinstance(g, _nd.NDArray) else jnp.asarray(g)
                     for g in out_grads]
        else:
            heads = [out_grads.data if isinstance(out_grads, _nd.NDArray)
                     else jnp.asarray(out_grads)]
        from . import profiler as _profiler
        if _profiler.state() == "run":
            import time as _time
            _t0 = _time.perf_counter()
            with _obs_compiles.scope(self._obs_label):
                outs, new_aux, grads = self._jit_fwd_bwd(arg_vals, aux_vals,
                                                         key, heads)
            jax.block_until_ready(outs)
            _profiler.record_event("graph_fwd_bwd", _t0,
                                   _time.perf_counter(), "graph")
        else:
            with _obs_compiles.scope(self._obs_label):
                outs, new_aux, grads = self._jit_fwd_bwd(arg_vals, aux_vals,
                                                         key, heads)
        if self._sync_host_callbacks:
            self._forced_sync((outs, grads))
        self._commit(outs, new_aux)
        self._pending = None
        for n, g in grads.items():
            req = self._grad_req.get(n, "null")
            buf = self.grad_dict.get(n)
            if buf is None or req == "null":
                continue
            if req == "add":
                buf._data = buf.data + g.astype(buf.dtype)
            else:
                buf._data = g.astype(buf.dtype)
            buf._version += 1

    def _commit(self, outs, new_aux):
        self._outputs = [_nd.NDArray(o) for o in outs]
        for n, v in new_aux.items():
            a = self.aux_dict[n]
            a._data = v
            a._version += 1
        # monitor fires when real outputs materialize — deduped by step so
        # a forward-then-backward pair (two commits of the same step)
        # reports once
        if self._monitor_callback and \
                getattr(self, "_mon_step", -1) != self._step:
            self._mon_step = self._step
            self._run_monitor()

    @property
    def outputs(self) -> List[_nd.NDArray]:
        """(reference: executor.h outputs). Computes lazily if a deferred
        training forward is pending."""
        if self._outputs is None and self._pending is not None:
            arg_vals, aux_vals, key = self._pending
            with _obs_compiles.scope(self._obs_label):
                outs, new_aux = self._jit_fwd(arg_vals, aux_vals, key, True)
            if self._sync_host_callbacks:
                self._forced_sync(outs)
            self._commit(outs, new_aux)
        if self._outputs is None:
            raise MXNetError("no forward has been run")
        return self._outputs

    @property
    def arg_arrays(self) -> List[_nd.NDArray]:
        return [self.arg_dict[n] for n in self._arg_names]

    @property
    def grad_arrays(self) -> List[Optional[_nd.NDArray]]:
        return [self.grad_dict.get(n) for n in self._arg_names]

    @property
    def aux_arrays(self) -> List[_nd.NDArray]:
        return [self.aux_dict[n] for n in self._aux_names]

    @property
    def output_dict(self) -> Dict[str, _nd.NDArray]:
        return dict(zip(self._output_names, self.outputs))

    def copy_params_from(self, arg_params: Dict[str, _nd.NDArray],
                         aux_params: Optional[Dict[str, _nd.NDArray]] = None,
                         allow_extra_params: bool = False) -> None:
        """(reference: executor.py copy_params_from)."""
        for k, v in arg_params.items():
            if k in self.arg_dict:
                v.copyto(self.arg_dict[k])
            elif not allow_extra_params:
                raise MXNetError("unknown parameter %r" % k)
        if aux_params:
            for k, v in aux_params.items():
                if k in self.aux_dict:
                    v.copyto(self.aux_dict[k])
                elif not allow_extra_params:
                    raise MXNetError("unknown aux state %r" % k)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Return a new executor bound to new shapes (reference: executor.py
        reshape). jit re-specializes per shape automatically; parameters are
        shared by reference."""
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        new_args = {}
        for n, s in zip(self._arg_names, arg_shapes):
            cur = self.arg_dict[n]
            if tuple(cur.shape) == tuple(s):
                new_args[n] = cur
            else:
                new_args[n] = _nd.NDArray(np.zeros(s, dtype=cur.dtype),
                                          ctx=self._ctx)
        new_grads = None
        if self.grad_dict:
            new_grads = {}
            for n in self.grad_dict:
                s = arg_shapes[self._arg_names.index(n)]
                cur = self.grad_dict[n]
                new_grads[n] = cur if tuple(cur.shape) == tuple(s) else \
                    _nd.NDArray(np.zeros(s, dtype=cur.dtype), ctx=self._ctx)
        new_aux = {}
        for n, s in zip(self._aux_names, aux_shapes):
            cur = self.aux_dict[n]
            new_aux[n] = cur if tuple(cur.shape) == tuple(s) else \
                _nd.NDArray(np.zeros(s, dtype=cur.dtype), ctx=self._ctx)
        return Executor(self._symbol, self._ctx, new_args, new_grads,
                        self._grad_req, new_aux, group2ctx=self._group2ctx,
                        shared_exec=self, batch_rows=self._batch_rows)

    # ------------------------------------------------------------ monitor
    def monitor_values(self):
        """Eagerly interpret the graph with the current bindings, yielding
        (node_output_name, NDArray) for EVERY node — the per-op stat tap
        the reference's MonitorExecution installs on each engine op
        (src/executor/graph_executor.cc monitor_callback_). Debug path:
        runs outside the fused jit with the SAME per-node dispatch
        (_run_node) and the last forward's is_train/RNG key; aux states
        reflect the post-commit values (approximate for BatchNorm moving
        stats, exact for everything else)."""
        from .symbol.symbol import _topo_order
        nodes = _topo_order(self._symbol._entries)
        key = jax.random.fold_in(self._base_key, self._step)
        is_train = getattr(self, "_last_is_train", True)
        node_device = self._node_device_fn()
        vals = {}
        for idx, node in enumerate(nodes):
            if node.is_variable:
                src_nd = self.arg_dict.get(node.name)
                if src_nd is None:
                    src_nd = self.aux_dict.get(node.name)
                vals[(id(node), 0)] = src_nd.data
                continue
            ins = [vals[(id(n), i)] for n, i in node.inputs]
            outs = _run_node(node, ins, key, idx, is_train, node_device,
                             self._batch_rows)
            for i, o in enumerate(outs):
                vals[(id(node), i)] = o
                suffix = "_output" if len(outs) == 1 else "_output%d" % i
                yield node.name + suffix, _nd.NDArray(o)

    def set_monitor_callback(self, callback) -> None:
        """(reference: MXExecutorSetMonitorCallback / Monitor support —
        graph_executor.cc:1209 ExecuteMonCallback). Called as
        callback(name, NDArray) for every output after each forward."""
        self._monitor_callback = callback

    def _run_monitor(self):
        for name, arr in zip(self._output_names, self.outputs):
            self._monitor_callback(name, arr)

    def debug_str(self) -> str:
        from .symbol.symbol import _topo_order
        lines = ["Symbol outputs: %s" % ", ".join(self._output_names)]
        for node in _topo_order(self._symbol._entries):
            kind = "var" if node.is_variable else node.op.name
            lines.append("  %-20s %s" % (kind, node.name))
        return "\n".join(lines)
