"""Custom operators written in Python — the user escape hatch.

Reference: ``python/mxnet/operator.py`` (``CustomOp:413``, ``CustomOpProp:480``,
``register:593``) + ``src/operator/custom/custom-inl.h:50-69`` — user code
defines forward/backward over NDArrays, a Prop class declares names/shapes,
and ``register('op_type')`` makes ``mx.nd.Custom``/``mx.sym.Custom`` dispatch
to it by ``op_type``.

TPU design: the user's Python runs on the *host* via ``jax.pure_callback``
(XLA cannot trace arbitrary Python), and the custom gradient plugs into the
program as a ``jax.custom_vjp`` whose backward is a second host callback.
The op integrates with everything built on the registry — Symbol graphs,
Module's fused train step, Gluon blocks, autograd — because "Custom" is an
ordinary registry op. This mirrors how the reference routes custom ops
through the engine as opaque async ops (custom-inl.h Push), at the same
cost model: a host round-trip per call, so use it for glue, not hot loops.

Backend note: host callbacks need a runtime with send/recv support; the
CPU runtime and the TPU runtime (checked on a v5e: ``mx.nd.Custom``
forward and backward on ``mx.tpu(0)``) both have it.

Device-resident fast path: a ``CustomOpProp`` that overrides
``forward_traced`` (and optionally ``backward_traced``) with
jax-traceable code compiles INTO the XLA program — TPU-resident, fused,
no host round trip. Gradients default to jax autodiff of the traced forward. This
is the path hot-loop custom ops should take; the callback path remains
for arbitrary host Python (reference parity:
src/operator/custom/custom.cc:380-405 kLocal semantics).
"""
from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import lockcheck as _lockcheck

__all__ = ["CustomOp", "CustomOpProp", "register", "get_prop_class",
           "PythonOp", "NumpyOp", "NDArrayOp"]

_PROP_REGISTRY: Dict[str, type] = {}

# --------------------------------------------------- host-callback thread
# The user's forward/backward runs eager NDArray code, i.e. it re-enters
# jax dispatch. Executing it directly on the runtime's host-callback
# thread can deadlock: that thread is part of the machinery draining the
# async dispatch queue, so an eval-time custom op issued while queued
# train steps drain waits on a queue that can only drain through the
# thread it is blocking (the train_rcnn eval hang). All callback-path
# custom-op Python therefore runs on ONE dedicated worker thread — the
# callback thread only blocks on the future, and the worker's eager
# dispatches proceed like any ordinary frontend thread's. (One thread,
# not a pool: the reference serializes custom ops through its own
# CustomOperator worker the same way, custom-inl.h Push.)

_cb_lock = _lockcheck.Lock(name="operator.cb_lock")
_cb_executor: Optional[ThreadPoolExecutor] = None
_cb_thread_ident: Optional[int] = None


def _run_on_custom_op_thread(fn, *args):
    global _cb_executor
    if threading.get_ident() == _cb_thread_ident:
        return fn(*args)      # nested custom op: run inline, don't self-wait
    if _cb_executor is None:
        with _cb_lock:
            if _cb_executor is None:
                def _note_ident():
                    global _cb_thread_ident
                    _cb_thread_ident = threading.get_ident()
                _cb_executor = ThreadPoolExecutor(
                    1, thread_name_prefix="mxnet_tpu.custom_op",
                    initializer=_note_ident)
    return _cb_executor.submit(fn, *args).result()


class CustomOp(object):
    """Base class for custom operator implementations (reference:
    python/mxnet/operator.py:413)."""

    def forward(self, is_train, req, in_data, out_data, aux):
        """Compute outputs from ``in_data`` into ``out_data`` via
        :meth:`assign`."""
        raise NotImplementedError

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        """Compute input gradients into ``in_grad`` via :meth:`assign`."""
        raise NotImplementedError

    def assign(self, dst, req, src):
        """Honor the gradient request when writing ``src`` to ``dst``
        (reference: operator.py CustomOp.assign)."""
        if req == "null":
            return
        elif req in ("write", "inplace"):
            dst[:] = src
        elif req == "add":
            dst[:] = dst + src
        else:
            raise ValueError("invalid req %r" % req)


class CustomOpProp(object):
    """Declares a custom op's interface (reference: operator.py:480).

    Subclass and override ``list_arguments``/``list_outputs``/
    ``infer_shape``/``create_operator``. ``needs_top_grad`` says whether
    backward consumes head gradients (False for loss-style ops).
    """

    def __init__(self, need_top_grad=True):
        self.need_top_grad_ = need_top_grad

    def list_arguments(self) -> List[str]:
        return ["data"]

    def list_outputs(self) -> List[str]:
        return ["output"]

    def list_auxiliary_states(self) -> List[str]:
        return []

    def infer_shape(self, in_shape):
        """Default: all inputs share in_shape[0]; every output too
        (reference: operator.py CustomOpProp.infer_shape)."""
        return in_shape, [in_shape[0]] * len(self.list_outputs()), []

    def infer_type(self, in_type):
        return (in_type, [in_type[0]] * len(self.list_outputs()),
                [in_type[0]] * len(self.list_auxiliary_states()))

    def need_top_grad(self) -> bool:
        return self.need_top_grad_

    def forward_traced(self, in_data, is_train):
        """OPTIONAL device-resident fast path: return a tuple of outputs
        computed with jax-traceable code (jnp/lax/Pallas) over the input
        jax arrays. Overriding this method commits the op to the traced
        path: it compiles INTO the XLA program — runs on the TPU, fuses
        with its neighbors, and needs no host round-trip (the callback
        path is host-executed; see docs/new_op.md). Gradients come from jax autodiff
        of this function unless :meth:`backward_traced` is also
        overridden. Leave it un-overridden to use the host-callback
        ``create_operator`` path."""
        raise NotImplementedError

    def backward_traced(self, out_grad, in_data, out_data):
        """OPTIONAL custom gradient for :meth:`forward_traced`: return a
        tuple of input cotangents from jax-traceable code (one per
        input; cotangents for integer inputs are discarded). With
        ``need_top_grad=False`` the incoming ``out_grad`` may be ignored
        (mxnet loss-op semantics). Leave it un-overridden to use jax
        autodiff of ``forward_traced``."""
        raise NotImplementedError

    def create_operator(self, ctx, in_shapes, in_dtypes) -> CustomOp:
        raise NotImplementedError

    def declare_backward_dependency(self, out_grad, in_data, out_data):
        deps = []
        if self.need_top_grad():
            deps.extend(out_grad)
        deps.extend(in_data)
        deps.extend(out_data)
        return deps


def register(reg_name: str):
    """Class decorator: ``@mx.operator.register("my_op")`` on a CustomOpProp
    subclass (reference: operator.py:593)."""

    def _reg(prop_cls):
        if not issubclass(prop_cls, CustomOpProp):
            raise TypeError("register() expects a CustomOpProp subclass")
        _PROP_REGISTRY[reg_name] = prop_cls
        return prop_cls

    return _reg


def prop_uses_host_callback(op_type: str) -> bool:
    """True when this op_type's custom op runs user Python through the
    host-callback path (no ``forward_traced`` override). Programs
    embedding such ops must be executed SYNCHRONOUSLY with the frontend
    (executor.py): the callback's user code re-enters eager jax
    dispatch, and if the frontend thread dispatches concurrently while
    the program is in flight the CPU runtime can deadlock — observed as
    the train_rcnn eval hang (frontend blocked in apply_primitive, the
    runtime waiting on the callback, the callback's dispatches waiting
    on the frontend's lock)."""
    cls = _PROP_REGISTRY.get(op_type)
    if cls is None:
        return True        # unknown yet: be conservative
    return cls.forward_traced is CustomOpProp.forward_traced


def symbol_has_host_callback(symbol) -> bool:
    """Scan a Symbol graph for callback-path Custom ops (see
    :func:`prop_uses_host_callback`)."""
    from .symbol.symbol import _topo_order
    for node in _topo_order(symbol._entries):
        if node.op is not None and node.op.name == "Custom":
            op_type = node.attrs.get("op_type")
            if op_type is None or prop_uses_host_callback(str(op_type)):
                return True
    return False


def get_prop_class(op_type: str) -> type:
    try:
        return _PROP_REGISTRY[op_type]
    except KeyError:
        raise KeyError(
            "custom op type %r not registered — decorate its CustomOpProp "
            "with @mx.operator.register(%r)" % (op_type, op_type)) from None


def _make_prop(op_type: str, attrs: Dict[str, Any]) -> CustomOpProp:
    """Instantiate the Prop with the user attrs (the reference passes every
    attr as a string kwarg, operator.py creator glue)."""
    cls = get_prop_class(op_type)
    kwargs = {k: v for k, v in attrs.items()
              if not k.startswith("_") and k != "op_type"}
    return cls(**kwargs)


# --------------------------------------------------------------- registry op


def _np_dtype(dt):
    return np.dtype(dt)


def _custom_impl(arrays, op_type, attrs, is_train):
    import jax
    from . import ndarray as nd

    prop = _make_prop(op_type, attrs)
    arg_names = prop.list_arguments()
    out_names = prop.list_outputs()
    if prop.list_auxiliary_states():
        raise NotImplementedError(
            "auxiliary states on custom ops are not supported yet")
    if len(arrays) != len(arg_names):
        raise ValueError(
            "custom op %r expects %d inputs %s, got %d"
            % (op_type, len(arg_names), arg_names, len(arrays)))

    in_shapes = [tuple(int(d) for d in a.shape) for a in arrays]
    ishapes, oshapes, _ = prop.infer_shape([list(s) for s in in_shapes])
    itypes, otypes, _ = prop.infer_type([_np_dtype(a.dtype) for a in arrays])
    out_avals = tuple(jax.ShapeDtypeStruct(tuple(s), _np_dtype(t))
                      for s, t in zip(oshapes, otypes))
    in_avals = tuple(jax.ShapeDtypeStruct(s, _np_dtype(a.dtype))
                     for s, a in zip(in_shapes, arrays))

    # device-resident fast path: jax-traceable forward (and optionally
    # backward) compile into the program — no host callback at all
    if type(prop).forward_traced is not CustomOpProp.forward_traced:
        def fwd(*xs):
            outs = tuple(prop.forward_traced(list(xs), is_train))
            if len(outs) != len(out_avals) or any(
                    tuple(o.shape) != a.shape or o.dtype != a.dtype
                    for o, a in zip(outs, out_avals)):
                raise ValueError(
                    "forward_traced of %r returned %s, but infer_shape/"
                    "infer_type declare %s" % (
                        op_type,
                        [(tuple(o.shape), str(o.dtype)) for o in outs],
                        [(a.shape, str(np.dtype(a.dtype)))
                         for a in out_avals]))
            return outs

        if type(prop).backward_traced is CustomOpProp.backward_traced:
            if not prop.need_top_grad():
                # the callback path would DROP the incoming cotangent
                # (loss-op semantics); plain autodiff multiplies by it —
                # a ported loss op would silently train on ~zero grads
                raise ValueError(
                    "custom op %r declares need_top_grad=False (loss-op "
                    "semantics) but overrides only forward_traced; "
                    "autodiff would consume the head gradient it promises "
                    "to ignore — override backward_traced too" % op_type)
            outs = fwd(*arrays)     # plain autodiff handles the grads
            return outs if len(outs) != 1 else outs[0]

        import jax.numpy as jnp

        def cot_for(g, x):
            # custom_vjp demands float0 cotangents for integer primals
            if not jnp.issubdtype(jnp.result_type(x.dtype), jnp.inexact):
                return np.zeros(np.shape(x), jax.dtypes.float0)
            return g.astype(x.dtype)

        @jax.custom_vjp
        def run_t(*xs):
            return fwd(*xs)

        def run_t_fwd(*xs):
            outs = fwd(*xs)
            return outs, (xs, outs)

        def run_t_bwd(res, cts):
            xs, outs = res
            gs = prop.backward_traced(list(cts), list(xs), list(outs))
            if gs is None or len(gs) != len(xs):
                raise ValueError(
                    "backward_traced of %r must return one cotangent "
                    "per input (%d); leave it un-overridden to use "
                    "autodiff" % (op_type, len(xs)))
            return tuple(cot_for(g, x) for g, x in zip(gs, xs))

        run_t.defvjp(run_t_fwd, run_t_bwd)
        outs = run_t(*arrays)
        return outs if len(outs) != 1 else outs[0]
    # one operator instance per call site, like the reference's per-executor
    # instance (custom-inl.h CustomOperator); it lives across executions and
    # may carry state
    op_inst = prop.create_operator("cpu(0)", [list(s) for s in ishapes],
                                   itypes)
    n_in = len(arrays)

    def _forward_impl(*xs):
        in_data = [nd.array(np.asarray(x)) for x in xs]
        out_data = [nd.NDArray(np.zeros(s, t))
                    for s, t in zip(oshapes, otypes)]
        op_inst.forward(is_train=is_train, req=["write"] * len(out_data),
                        in_data=in_data, out_data=out_data, aux=[])
        return tuple(o.asnumpy().astype(t, copy=False)
                     for o, t in zip(out_data, otypes))

    def _backward_impl(xs, outs, cts):
        in_data = [nd.array(np.asarray(x)) for x in xs]
        out_data = [nd.array(np.asarray(o)) for o in outs]
        out_grad = [nd.array(np.asarray(c)) for c in cts] \
            if prop.need_top_grad() else []
        in_grad = [nd.NDArray(np.zeros(s, _np_dtype(a.dtype)))
                   for s, a in zip(in_shapes, xs)]
        op_inst.backward(req=["write"] * n_in, out_grad=out_grad,
                         in_data=in_data, out_data=out_data,
                         in_grad=in_grad, aux=[])
        return tuple(g.asnumpy().astype(a.dtype, copy=False)
                     for g, a in zip(in_grad, xs))

    # the runtime's callback thread must never run user NDArray code
    # itself (deadlock — see _run_on_custom_op_thread)
    def host_forward(*xs):
        return _run_on_custom_op_thread(_forward_impl, *xs)

    def host_backward(xs, outs, cts):
        return _run_on_custom_op_thread(_backward_impl, xs, outs, cts)

    @jax.custom_vjp
    def run(*xs):
        return jax.pure_callback(host_forward, out_avals, *xs)

    def run_fwd(*xs):
        outs = jax.pure_callback(host_forward, out_avals, *xs)
        return outs, (xs, outs)

    def run_bwd(res, cts):
        xs, outs = res
        return jax.pure_callback(host_backward, in_avals, xs, outs, cts)

    run.defvjp(run_fwd, run_bwd)
    outs = run(*arrays)
    # serialize with the frontend: an async in-flight callback program +
    # concurrent eager dispatch is the deadlock recipe above. Eager
    # custom-op call sites pay a sync — the documented cost model for
    # the callback path (host round-trip per call) already says "glue,
    # not hot loops".
    jax.block_until_ready(outs)
    return outs if len(outs) != 1 else outs[0]


def _register_custom_op():
    from .ops.registry import register as reg_op, get_op

    @reg_op("Custom", num_inputs=None)
    def custom(*arrays, op_type=None, _is_train=False, **attrs):
        """Dispatch to a registered CustomOpProp by ``op_type`` (reference:
        src/operator/custom/custom.cc + python/mxnet/operator.py glue)."""
        if op_type is None:
            raise ValueError("Custom op needs op_type=")
        return _custom_impl(arrays, op_type, attrs, bool(_is_train))

    def _prop_of(attrs):
        if "op_type" not in attrs:
            raise ValueError("Custom op needs op_type=")
        return _make_prop(attrs["op_type"], attrs)

    opdef = get_op("Custom")
    opdef.num_outputs = lambda attrs: len(_prop_of(attrs).list_outputs())
    opdef.input_names_fn = lambda attrs: list(_prop_of(attrs).list_arguments())


_register_custom_op()


# -------------------------------------------------- legacy frontend classes


class PythonOp(object):
    """Deprecated-but-supported base for the 0.x custom-op style
    (reference: python/mxnet/operator.py:36 PythonOp — predates
    CustomOp/CustomOpProp). Subclass :class:`NumpyOp` or
    :class:`NDArrayOp`; ``get_symbol(*args)`` splices the op into a
    Symbol graph. Internally each instance registers itself as a modern
    CustomOpProp, so the legacy surface rides the same pure_callback +
    custom_vjp machinery as ``mx.sym.Custom``.
    """

    _counter = [0]

    def __init__(self, need_top_grad=True):
        self.need_top_grad_ = need_top_grad
        self._op_type = None

    # -- the legacy overridables (reference signatures)
    def forward(self, in_data, out_data):
        raise NotImplementedError

    def backward(self, out_grad, in_data, out_data, in_grad):
        raise NotImplementedError

    def infer_shape(self, in_shape):
        """Returns (in_shapes, out_shapes) — the legacy two-tuple."""
        return in_shape, [in_shape[0]]

    def list_arguments(self):
        return ["data"]

    def list_outputs(self):
        return ["output"]

    def need_top_grad(self):
        return self.need_top_grad_

    # -- modern bridge
    def _numpy_mode(self):
        raise NotImplementedError("use NumpyOp or NDArrayOp")

    def _ensure_registered(self):
        if self._op_type is not None:
            return self._op_type
        PythonOp._counter[0] += 1
        op_type = "_legacy_pyop_%d" % PythonOp._counter[0]
        legacy = self
        numpy_mode = self._numpy_mode()

        class _Adapter(CustomOp):
            def forward(self, is_train, req, in_data, out_data, aux):
                if numpy_mode:
                    ins = [a.asnumpy() for a in in_data]
                    outs = [o.asnumpy() for o in out_data]
                    legacy.forward(in_data=ins, out_data=outs)
                    for dst, src in zip(out_data, outs):
                        self.assign(dst, "write", src)
                else:
                    legacy.forward(in_data=in_data, out_data=out_data)

            def backward(self, req, out_grad, in_data, out_data, in_grad,
                         aux):
                if numpy_mode:
                    ogs = [g.asnumpy() for g in out_grad]
                    ins = [a.asnumpy() for a in in_data]
                    outs = [o.asnumpy() for o in out_data]
                    igs = [g.asnumpy() for g in in_grad]
                    legacy.backward(out_grad=ogs, in_data=ins,
                                    out_data=outs, in_grad=igs)
                    for dst, src in zip(in_grad, igs):
                        self.assign(dst, "write", src)
                else:
                    legacy.backward(out_grad=out_grad, in_data=in_data,
                                    out_data=out_data, in_grad=in_grad)

        class _Prop(CustomOpProp):
            def __init__(self, **_):
                super().__init__(need_top_grad=legacy.need_top_grad())

            def list_arguments(self):
                return list(legacy.list_arguments())

            def list_outputs(self):
                return list(legacy.list_outputs())

            def infer_shape(self, in_shape):
                ishapes, oshapes = legacy.infer_shape(in_shape)
                return ishapes, oshapes, []

            def create_operator(self, ctx, shapes, dtypes):
                return _Adapter()

        _PROP_REGISTRY[op_type] = _Prop
        self._op_type = op_type
        return op_type

    def get_symbol(self, *args, **kwargs):
        """Splice this op into a symbolic graph (reference: PythonOp
        get_symbol -> the Custom symbol)."""
        from . import symbol as sym
        op_type = self._ensure_registered()
        return sym.Custom(*args, op_type=op_type, **kwargs)


class NumpyOp(PythonOp):
    """Legacy numpy custom op (reference: operator.py:143): ``forward``/
    ``backward`` receive numpy arrays and mutate ``out_data``/``in_grad``
    in place."""

    def _numpy_mode(self):
        return True


class NDArrayOp(PythonOp):
    """Legacy NDArray custom op (reference: operator.py:243): same
    contract with NDArrays (assign via ``arr[:] = ...``)."""

    def _numpy_mode(self):
        return False
