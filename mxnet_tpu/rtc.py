"""Runtime custom kernels — the TPU twin of ``mx.rtc`` (SURVEY.md §2.22).

Reference: ``include/mxnet/mxrtc.h:42-101`` + ``python/mxnet/rtc.py:24-78``
compile CUDA C strings with NVRTC at runtime and launch them on GPU data.
On TPU the escape hatch is **Pallas**: users write a kernel as a Python
function over ``pl.Ref`` blocks, and :class:`PallasKernel` compiles it with
Mosaic and runs it on NDArrays — same role (hand-written kernels for the
few ops XLA fusion can't produce), idiomatic toolchain.

A kernel can also be registered as a framework op
(:meth:`PallasKernel.register`), making it usable from ``mx.nd.*``,
``mx.sym.*`` and Gluon exactly like built-ins — the TPU analogue of
wiring an RTC kernel behind a Custom op.

Interpreter mode (numerically identical, slow) is what CPU tests and CPU
development use. Unless ``interpret`` is given, a kernel compiles with
Mosaic exactly when its concrete inputs live on a TPU — or, under tracing,
when ``jax.default_backend()`` is ``"tpu"`` — and interprets otherwise.
Asking for a compiled kernel off-TPU is an error, never a silent
substitute.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

__all__ = ["PallasKernel", "CudaModule"]


def resolve_interpret(arrays) -> bool:
    """True (interpreter mode) unless the kernel will run on a TPU.

    Compute follows data placement: the first concrete ``jax.Array``
    decides by the devices it lives on. Tracers (the kernel traced inside
    someone else's jit, as under ``Module``) and host arrays carry no
    device, so the default backend decides.
    """
    import jax
    for a in arrays:
        if isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer):
            return not any(d.platform == "tpu" for d in a.devices())
    return jax.default_backend() != "tpu"


def product_operands(a, w):
    """``(a, w)`` as a product in the weights' dtype takes them: ``a``
    cast to ``w``'s dtype. Off the TPU (XLA's CPU backend has no bfloat16
    product) both come back widened to float32: the same products
    exactly, so a stated dtype can be tested without the chip."""
    import jax.numpy as jnp
    a = a.astype(w.dtype)
    if w.dtype != jnp.float32 and resolve_interpret((a, w)):
        a, w = a.astype(jnp.float32), w.astype(jnp.float32)
    return a, w


class PallasKernel:
    """A compiled Pallas kernel callable on NDArrays.

    Parameters mirror ``pl.pallas_call``: ``kernel_fn`` takes input refs,
    output refs, then scratch refs; ``out_shape`` is one
    ``(shape, dtype)`` pair or a list of them. Extra pallas_call
    keyword arguments (``grid``, ``in_specs``, ``out_specs``,
    ``scratch_shapes``, ``compiler_params``, ...) pass through verbatim.
    """

    def __init__(self, kernel_fn: Callable, out_shape, name: Optional[str]
                 = None, interpret: Optional[bool] = None, **pallas_kwargs):
        import jax
        self._name = name or getattr(kernel_fn, "__name__", "pallas_kernel")
        self._kernel_fn = kernel_fn

        def to_sds(s):
            if isinstance(s, jax.ShapeDtypeStruct):
                return s
            shape, dtype = s
            return jax.ShapeDtypeStruct(tuple(shape), dtype)

        # a (shape, dtype) pair has a non-sequence second element; a list
        # of outputs is a sequence of pairs/ShapeDtypeStructs
        if isinstance(out_shape, (list, tuple)) and out_shape and \
                (isinstance(out_shape[0], jax.ShapeDtypeStruct) or
                 (len(out_shape) != 2 or
                  isinstance(out_shape[1],
                             (list, tuple, jax.ShapeDtypeStruct)))):
            self._out_shape = [to_sds(s) for s in out_shape]
            self._multi = True
        else:
            self._out_shape = to_sds(out_shape)
            self._multi = False
        self._pallas_kwargs = dict(pallas_kwargs)
        self._interpret = interpret
        self._compiled = {}

    def _build(self, interpret: bool):
        fn = self._compiled.get(interpret)
        if fn is None:
            import jax
            from jax.experimental import pallas as pl
            call = pl.pallas_call(
                self._kernel_fn, out_shape=self._out_shape,
                interpret=interpret, **self._pallas_kwargs)
            fn = jax.jit(call)
            self._compiled[interpret] = fn
        return fn

    def _run(self, raw):
        interpret = self._interpret
        if interpret is None:
            interpret = resolve_interpret(raw)
        return self._build(interpret)(*raw)

    def __call__(self, *args):
        """Run on NDArrays (or raw jax arrays); returns NDArray(s)."""
        from . import ndarray as nd
        raw = [a.data if isinstance(a, nd.NDArray) else a for a in args]
        out = self._run(raw)
        if self._multi:
            return tuple(nd.NDArray(o) for o in out)
        return nd.NDArray(out)

    def register(self, op_name: str, num_inputs: Optional[int] = None):
        """Expose the kernel as a framework op (``mx.nd.<op_name>`` /
        ``mx.sym.<op_name>``)."""
        from .ops.registry import register as reg_op
        run = self._run
        multi = self._multi

        @reg_op(op_name, num_inputs=num_inputs)
        def _kernel_op(*arrays):
            out = run(list(arrays))
            return tuple(out) if multi else out

        if multi:
            _kernel_op.num_outputs = len(self._out_shape)

        _kernel_op.fn.__doc__ = "Pallas kernel %r (registered via " \
            "mx.rtc.PallasKernel.register)" % self._name
        return _kernel_op

    def __repr__(self):
        return "PallasKernel(%s)" % self._name


class CudaModule:
    """Reference-API shim (python/mxnet/rtc.py CudaModule). There is no
    NVRTC on TPU; kernels are written in Pallas instead."""

    def __init__(self, *a, **kw):
        raise NotImplementedError(
            "CUDA RTC does not exist on TPU — write the kernel in Pallas "
            "and wrap it with mx.rtc.PallasKernel (see "
            "mxnet_tpu/ops/pallas/flash_attention.py for a worked example)")
