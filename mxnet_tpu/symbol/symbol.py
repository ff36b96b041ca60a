"""Symbol — the declarative graph API.

Reference: ``python/mxnet/symbol.py`` (Symbol class at line 67, composition,
``infer_shape:921``, ``simple_bind:1266``, ``bind:1502``) over the nnvm graph
(SURVEY.md §2.9). The reference Symbol is a C++ nnvm::Symbol handle; here a
Symbol is a small immutable Python DAG over the op registry, and everything
downstream (shape inference, execution, gradients) is JAX tracing of the same
graph:

* ``infer_shape``/``infer_type`` ≡ ``jax.eval_shape`` of the traced graph —
  the reference's per-op FInferShape/FInferType rules disappear.
* ``bind`` produces an :class:`~mxnet_tpu.executor.Executor` that compiles
  the traced graph with ``jax.jit`` (the GraphExecutor + engine collapse).
* JSON save/load keeps the reference's checkpoint container shape
  (``nodes``/``arg_nodes``/``heads`` — src/c_api/c_api_symbolic.cc
  MXSymbolSaveToJSON) so model zoo checkpoints stay portable.
"""
from __future__ import annotations

import ast
import json
import logging
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax

from ..base import MXNetError
from ..name import current_name_manager, current_attr_scope
from ..ops import OP_REGISTRY, OpDef, get_op

__all__ = ["Symbol", "Variable", "var", "Group", "load", "load_json"]


class _Node:
    """One graph node: an op application or a variable (op=None)."""

    __slots__ = ("op", "name", "attrs", "str_attrs", "inputs", "is_aux")

    def __init__(self, op: Optional[OpDef], name: str,
                 attrs: Optional[Dict[str, Any]] = None,
                 inputs: Optional[List[Tuple["_Node", int]]] = None,
                 is_aux: bool = False):
        self.op = op
        self.name = name
        self.attrs = attrs or {}          # op kwargs (python values)
        self.str_attrs: Dict[str, str] = {}  # user attrs (ctx_group, lr_mult…)
        self.inputs = inputs or []
        self.is_aux = is_aux

    @property
    def is_variable(self) -> bool:
        return self.op is None


def _topo_order(entries: Sequence[Tuple[_Node, int]]) -> List[_Node]:
    order: List[_Node] = []
    seen = set()

    def visit(node: _Node):
        if id(node) in seen:
            return
        seen.add(id(node))
        for n, _ in node.inputs:
            visit(n)
        order.append(node)

    for n, _ in entries:
        visit(n)
    return order


class Symbol:
    """An output list over the graph (reference: python/mxnet/symbol.py:67)."""

    __slots__ = ("_entries",)

    def __init__(self, entries: Sequence[Tuple[_Node, int]]):
        self._entries = list(entries)

    # ------------------------------------------------------------ identity
    @property
    def name(self) -> Optional[str]:
        if len(self._entries) == 1:
            return self._entries[0][0].name
        return None

    def __repr__(self):
        names = ", ".join(n.name for n, _ in self._entries)
        return "<Symbol %s>" % names

    def __iter__(self):
        return (self[i] for i in range(len(self._entries)))

    def __len__(self):
        return len(self._entries)

    def __getitem__(self, idx):
        if isinstance(idx, str):
            outputs = self.list_outputs()
            if idx in outputs:
                idx = outputs.index(idx)
            else:
                raise ValueError("output %s not found" % idx)
        return Symbol([self._entries[idx]])

    def get_internals(self) -> "Symbol":
        """Symbol grouping every internal output (reference: symbol.py
        get_internals — the feature-extraction / fine-tune hook)."""
        entries = []
        for node in _topo_order(self._entries):
            if node.is_variable:
                entries.append((node, 0))
            else:
                for i in range(_num_visible_outputs(node)):
                    entries.append((node, i))
        return Symbol(entries)

    def get_children(self) -> Optional["Symbol"]:
        if len(self._entries) != 1 or self._entries[0][0].is_variable:
            return None
        return Symbol(list(self._entries[0][0].inputs))

    # ------------------------------------------------------------ attrs
    def attr(self, key: str) -> Optional[str]:
        if len(self._entries) == 1:
            return self._entries[0][0].str_attrs.get(key)
        return None

    def list_attr(self) -> Dict[str, str]:
        if len(self._entries) == 1:
            return dict(self._entries[0][0].str_attrs)
        return {}

    def attr_dict(self) -> Dict[str, Dict[str, str]]:
        out = {}
        for node in _topo_order(self._entries):
            d = dict(node.str_attrs)
            if node.op is not None:
                d.update({k: _attr_str(v) for k, v in node.attrs.items()})
            if d:
                out[node.name] = d
        return out

    def _set_attr(self, **kwargs):
        for node, _ in self._entries:
            node.str_attrs.update({k: str(v) for k, v in kwargs.items()})

    # ------------------------------------------------------------ listing
    def list_arguments(self) -> List[str]:
        """(reference: symbol.py list_arguments — topo order of variable
        inputs, excluding auxiliary states)."""
        return [n.name for n in _topo_order(self._entries)
                if n.is_variable and not n.is_aux]

    def list_outputs(self) -> List[str]:
        names = []
        for node, idx in self._entries:
            if node.is_variable:
                names.append(node.name)
            else:
                suffix = "_output" if idx == 0 else "_output%d" % idx
                names.append(node.name + suffix)
        return names

    def list_auxiliary_states(self) -> List[str]:
        return [n.name for n in _topo_order(self._entries)
                if n.is_variable and n.is_aux]

    # ------------------------------------------------------------ compose
    def __call__(self, *args, **kwargs):
        """Composition: replace variable inputs with other symbols
        (reference: symbol.py __call__/_compose)."""
        if args and kwargs:
            raise TypeError("compose with either positional or keyword args")
        arg_names = self.list_arguments()
        mapping: Dict[str, Symbol] = {}
        if args:
            for name, s in zip(arg_names, args):
                mapping[name] = s
        else:
            mapping = dict(kwargs)
        replace: Dict[int, Tuple[_Node, int]] = {}
        for node in _topo_order(self._entries):
            if node.is_variable and node.name in mapping:
                sub = mapping[node.name]
                if len(sub._entries) != 1:
                    raise ValueError("can only compose with single-output symbols")
                replace[id(node)] = sub._entries[0]
        memo: Dict[int, _Node] = {}

        def copy(node: _Node) -> Tuple[_Node, int]:
            if id(node) in replace:
                return replace[id(node)]
            if id(node) in memo:
                return (memo[id(node)], 0)
            if node.is_variable:
                return (node, 0)
            new_inputs = []
            for n, i in node.inputs:
                nn, base = copy(n)
                new_inputs.append((nn, i if base == 0 else base))
            nn = _Node(node.op, node.name, dict(node.attrs), new_inputs,
                       node.is_aux)
            nn.str_attrs = dict(node.str_attrs)
            memo[id(node)] = nn
            return (nn, 0)

        entries = []
        for node, idx in self._entries:
            nn, base = copy(node)
            entries.append((nn, idx if isinstance(nn, _Node) and base == 0 else base))
        return Symbol(entries)

    # ------------------------------------------------------------ math
    def _binop(self, other, opname, scalar_op, reverse=False):
        if isinstance(other, Symbol):
            a, b = (other, self) if reverse else (self, other)
            return _create(get_op(opname), [a, b], {}, None)
        return _create(get_op(scalar_op), [self], {"scalar": float(other)}, None)

    def __add__(self, o):
        return self._binop(o, "elemwise_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binop(o, "elemwise_sub", "_minus_scalar")

    def __rsub__(self, o):
        return self._binop(o, "elemwise_sub", "_rminus_scalar", reverse=True)

    def __mul__(self, o):
        return self._binop(o, "elemwise_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binop(o, "elemwise_div", "_div_scalar")

    def __rtruediv__(self, o):
        return self._binop(o, "elemwise_div", "_rdiv_scalar", reverse=True)

    __div__ = __truediv__
    __rdiv__ = __rtruediv__

    def __pow__(self, o):
        return self._binop(o, "broadcast_power", "_power_scalar")

    def __neg__(self):
        return _create(get_op("negative"), [self], {}, None)

    # ------------------------------------------------------------ analysis
    def analyze(self, input_shapes=None, input_dtypes=None,
                **shape_kwargs):
        """Run the static graph analyzer (``mxnet_tpu.analysis``) over this
        symbol: cycle / duplicate-name / dead-node / shape-conflict
        detection plus the FLOP/bytes/memory cost model. Shapes may be
        passed as a dict or as kwargs (``net.analyze(data=(32, 784))``).
        Returns an ``analysis.Report``. Imported lazily — symbols that
        never call this never load the analyzer."""
        from ..analysis import analyze_symbol
        shapes = {k: tuple(v) for k, v in (input_shapes or {}).items()}
        shapes.update({k: tuple(v) for k, v in shape_kwargs.items()
                       if v is not None})
        return analyze_symbol(self, input_shapes=shapes or None,
                              input_dtypes=input_dtypes,
                              context=self.name or "symbol")

    # ------------------------------------------------------------ shape/type
    def infer_shape(self, *args, **kwargs):
        """(reference: symbol.py:921). Returns (arg_shapes, out_shapes,
        aux_shapes); unknown args yield None entries. Failures name the
        offending op node and its input shapes (not the raw
        ``jax.eval_shape`` traceback of the whole graph)."""
        return self._infer_shape_impl(False, *args, **kwargs)

    def infer_shape_partial(self, *args, **kwargs):
        return self._infer_shape_impl(True, *args, **kwargs)

    def _infer_shape_impl(self, partial, *args, **kwargs):
        arg_names = self.list_arguments()
        aux_names = self.list_auxiliary_states()
        known: Dict[str, Tuple[int, ...]] = {}
        if args:
            for n, s in zip(arg_names, args):
                if s is not None:
                    known[n] = tuple(s)
        batch_hint = kwargs.pop("__batch_size__", None)
        known.update({k: tuple(v) for k, v in kwargs.items() if v is not None})
        if batch_hint is not None:
            known["__batch_size__"] = int(batch_hint)

        # Variables whose shapes are derivable from graph structure get
        # resolved by abstract evaluation; others must be provided.
        shapes = _infer_shapes(self, known, partial=partial)
        if shapes is None:
            return None, None, None
        arg_shapes = [shapes.get(n) for n in arg_names]
        aux_shapes = [shapes.get(n) for n in aux_names]
        out_shapes = shapes["__outputs__"]
        return arg_shapes, out_shapes, aux_shapes

    def infer_type(self, *args, **kwargs):
        """(reference: symbol.py infer_type). Everything defaults float32
        unless pinned by the caller or the variable's ``dtype=`` attr.
        Bad dtypes fail naming the offending variable node, not with a
        numpy traceback."""
        arg_names = self.list_arguments()
        dtypes = {}
        for node in _topo_order(self._entries):
            if node.is_variable and "__dtype__" in node.str_attrs:
                dtypes[node.name] = node.str_attrs["__dtype__"]
        if args:
            for n, t in zip(arg_names, args):
                if t is not None:
                    dtypes[n] = t
        dtypes.update({k: v for k, v in kwargs.items() if v is not None})
        arg_types = []
        for n in arg_names:
            try:
                arg_types.append(np.dtype(dtypes.get(n, np.float32)))
            except TypeError as exc:
                raise MXNetError(
                    "infer_type: variable %r has invalid dtype %r (%s)"
                    % (n, dtypes.get(n), exc)) from None
        out_types = [np.dtype(np.float32)] * len(self._entries)
        aux_types = [np.dtype(np.float32)] * len(self.list_auxiliary_states())
        return arg_types, out_types, aux_types

    # ------------------------------------------------------------ save/load
    def tojson(self) -> str:
        """Serialize to the REFERENCE's symbol-JSON schema
        (MXSymbolSaveToJSON -> nnvm saveload; the exact container the
        reference's own checkpoints use and ``MXSymbolCreateFromJSON``
        loads — see tests/python/unittest/save_000800.json): per node
        ``op``/``param`` (op attrs, stringified)/``name``/``inputs``/
        ``attr`` (user attrs), plus ``arg_nodes`` and ``heads``. Files
        written here load in the reference and vice versa."""
        # auto-created aux-state variables (BatchNorm moving stats) are NOT
        # part of the reference's serialized graph — they are re-derived
        # from op metadata on load. Trim them from op inputs, then drop
        # only the aux nodes nothing references anymore (an aux variable
        # used as a head — get_internals — or bound explicitly by the user
        # stays serialized, like the reference's 1.x files).
        topo = _topo_order(self._entries)
        trimmed: Dict[int, list] = {}
        for n in topo:
            ins = list(n.inputs)
            if not n.is_variable and n.op.num_aux:
                k = n.op.num_aux
                tail = ins[len(ins) - k:]
                if len(tail) == k and all(
                        src.is_variable and src.is_aux for src, _ in tail):
                    ins = ins[:len(ins) - k]
            trimmed[id(n)] = ins
        referenced = {id(src) for n in topo for src, _ in trimmed[id(n)]}
        referenced |= {id(n) for n, _ in self._entries}
        nodes = [n for n in topo
                 if not (n.is_variable and n.is_aux
                         and id(n) not in referenced)]
        index = {id(n): i for i, n in enumerate(nodes)}
        out_nodes = []
        for n in nodes:
            ins = trimmed[id(n)]
            entry = {
                "op": "null" if n.is_variable else n.op.name,
                "param": {} if n.is_variable else
                         {k: _attr_str(v) for k, v in n.attrs.items()},
                "name": n.name,
                "inputs": [[index[id(src)], i] for src, i in ins],
                "backward_source_id": -1,
            }
            if n.str_attrs:
                entry["attr"] = dict(n.str_attrs)
            out_nodes.append(entry)
        arg_nodes = [i for i, n in enumerate(nodes) if n.is_variable]
        heads = [[index[id(n)], i] for n, i in self._entries]
        return json.dumps({
            "nodes": out_nodes, "arg_nodes": arg_nodes,
            "heads": heads}, indent=2)

    def save(self, fname: str) -> None:
        from ..checkpoint.atomic import atomic_open
        with atomic_open(fname, "w") as f:
            f.write(self.tojson())

    # ------------------------------------------------------------ eval/bind
    def eval(self, ctx=None, aux_states=None, **kwargs):
        """Evaluate with NDArray inputs (reference: symbol.py eval)."""
        from .. import ndarray as nd
        from ..executor import graph_function
        from .. import autograd as ag
        arg_names = self.list_arguments()
        missing = [n for n in arg_names if n not in kwargs]
        if missing:
            raise MXNetError("eval: missing arguments %s" % missing)
        args = {k: kwargs[k].data for k in arg_names}
        aux_names = self.list_auxiliary_states()
        aux = {}
        for n in aux_names:
            if aux_states and n in aux_states:
                v = aux_states[n]
                aux[n] = v.data if hasattr(v, "data") else jax.numpy.asarray(v)
            else:
                raise MXNetError("eval: missing auxiliary state %s" % n)
        fn = graph_function(self)
        from .. import random as _rnd
        outs, _newaux = fn(args, aux, _rnd.next_key(), ag.is_training())
        return [nd.NDArray(o) for o in outs]

    def bind(self, ctx, args, args_grad=None, grad_req="write",
             aux_states=None, group2ctx=None, shared_exec=None):
        """(reference: symbol.py:1502 → Executor::Bind)."""
        from ..executor import Executor
        return Executor(self, ctx, args, args_grad, grad_req, aux_states,
                        group2ctx=group2ctx, shared_exec=shared_exec)

    def simple_bind(self, ctx, grad_req="write", type_dict=None,
                    group2ctx=None, shared_exec=None, batch_rows=None,
                    **kwargs):
        """(reference: symbol.py:1266 → 40-arg MXExecutorSimpleBind; here:
        infer shapes, allocate args/grads/aux, construct the Executor).
        ``batch_rows``: see ``executor.graph_function``."""
        from .. import ndarray as nd
        from ..executor import Executor
        arg_shapes, _, aux_shapes = self.infer_shape(**kwargs)
        arg_names = self.list_arguments()
        aux_names = self.list_auxiliary_states()
        if arg_shapes is None or any(s is None for s in arg_shapes):
            missing = [n for n, s in zip(arg_names, arg_shapes or []) if s is None]
            raise MXNetError("simple_bind: cannot infer shapes for %s" % missing)
        type_dict = type_dict or {}
        args = {}
        for n, s in zip(arg_names, arg_shapes):
            dt = np.dtype(type_dict.get(n, np.float32))
            args[n] = nd.NDArray(np.zeros(s, dtype=dt), ctx=ctx)
        aux = {}
        for n, s in zip(aux_names, aux_shapes):
            aux[n] = nd.NDArray(np.zeros(s, dtype=np.float32), ctx=ctx)
        args_grad = None
        if grad_req != "null":
            args_grad = {n: nd.NDArray(np.zeros(s, dtype=np.float32), ctx=ctx)
                         for n, s in zip(arg_names, arg_shapes)}
        return Executor(self, ctx, args, args_grad, grad_req, aux,
                        group2ctx=group2ctx, shared_exec=shared_exec,
                        batch_rows=batch_rows)

    # attached op methods (sum, reshape, ...) installed by _attach_methods()


def _attr_str(v) -> str:
    return str(v)


def _parse_attr(s: str):
    try:
        return ast.literal_eval(s)
    except (ValueError, SyntaxError):
        return s


def _num_visible_outputs(node: _Node) -> int:
    op = node.op
    nout = getattr(op, "num_outputs", 1)
    if callable(nout):
        nout = nout(node.attrs)
    return int(nout)


# ------------------------------------------------------------------ factory


def Variable(name: str, attr=None, shape=None, lr_mult=None, wd_mult=None,
             dtype=None, init=None, **kwargs) -> Symbol:
    """(reference: symbol.py Variable)."""
    node = _Node(None, name)
    scope = current_attr_scope()
    attrs = scope.get(attr) if scope else dict(attr or {})
    if shape is not None:
        attrs["__shape__"] = str(tuple(shape))
    if lr_mult is not None:
        attrs["__lr_mult__"] = str(lr_mult)
    if wd_mult is not None:
        attrs["__wd_mult__"] = str(wd_mult)
    if dtype is not None:
        attrs["__dtype__"] = str(np.dtype(dtype))
    if init is not None:
        attrs["__init__"] = init if isinstance(init, str) else init.dumps()
    attrs.update({k: str(v) for k, v in kwargs.items()})
    node.str_attrs = attrs
    return Symbol([(node, 0)])


var = Variable


def Group(symbols: Sequence[Symbol]) -> Symbol:
    """(reference: symbol.py Group)."""
    entries = []
    for s in symbols:
        entries.extend(s._entries)
    return Symbol(entries)


def _create(op: OpDef, input_syms: List[Symbol], attrs: Dict[str, Any],
            name: Optional[str], aux_syms: Optional[List[Symbol]] = None) -> Symbol:
    """Create an op node (the symbolic twin of imperative_invoke)."""
    nm = current_name_manager()
    name = nm.get(name, op.name.lower().replace("_", ""))
    entries: List[Tuple[_Node, int]] = []
    for s in input_syms + (aux_syms or []):
        if len(s._entries) != 1:
            raise MXNetError(
                "op %s input must be single-output symbol" % op.name)
        entries.append(s._entries[0])
    node = _Node(op, name, attrs, entries)
    scope = current_attr_scope()
    if scope:
        node.str_attrs = scope.get(None)
    n_visible = _num_visible_outputs(node)
    return Symbol([(node, i) for i in range(n_visible)])


def make_symbol_function(op: OpDef):
    """Generate the mx.sym.<Op> wrapper from the registry — the analogue of
    the reference's _init_symbol_module autogen (python/mxnet/symbol.py tail).

    Missing weight/bias/aux inputs are auto-created as Variables named
    ``<name>_<input>`` exactly like the reference (e.g. ``fc1_weight``).
    """
    input_names = op.input_names
    aux_names = op.aux_input_names

    def fn(*args, **kwargs):
        name = kwargs.pop("name", None)
        nm = current_name_manager()
        name = nm.get(name, op.name.lower().replace("_", ""))

        inputs: Dict[str, Symbol] = {}
        # ops with attr-dependent interfaces (Custom: the Prop declares
        # list_arguments) resolve their input names from the non-symbol kwargs
        names_fn = getattr(op, "input_names_fn", None)
        if names_fn is not None:
            attr_kwargs = {k: v for k, v in kwargs.items()
                           if not isinstance(v, Symbol)}
            input_names_l = names_fn(attr_kwargs)
        else:
            input_names_l = input_names
        if op.num_inputs is None and names_fn is None and args and all(
                isinstance(a, Symbol) for a in args) and len(args) > 1 \
                and not any(k in kwargs for k in input_names_l):
            # variadic (Concat-style): positional symbols are THE inputs
            attrs = {k: v for k, v in kwargs.items()}
            return _create(op, list(args), attrs, name)
        attrs = {}
        # positional args: Symbols fill tensor-input slots; non-Symbols are
        # positional *attrs* and map onto the op function's parameter at
        # the same position (so sym.reshape(x, (1, 2, 3)) works like the
        # imperative nd.reshape — previously the shape was silently lost)
        fn_param_names = None
        for i, a in enumerate(args):
            if isinstance(a, Symbol):
                if i < len(input_names_l):
                    inputs[input_names_l[i]] = a
                else:
                    raise MXNetError(
                        "%s: too many symbol inputs (expected %s)"
                        % (op.name, input_names_l))
            else:
                if fn_param_names is None:
                    import inspect as _inspect
                    try:
                        fn_param_names = [
                            p.name for p in _inspect.signature(
                                op.fn).parameters.values()
                            if p.kind in (p.POSITIONAL_ONLY,
                                          p.POSITIONAL_OR_KEYWORD)]
                    except (TypeError, ValueError):
                        fn_param_names = []
                if i < len(fn_param_names):
                    attrs[fn_param_names[i]] = a
                else:
                    raise MXNetError(
                        "%s: unexpected positional argument %r"
                        % (op.name, a))
        for k, v in kwargs.items():
            if isinstance(v, Symbol):
                inputs[k] = v
            else:
                attrs[k] = v
        in_syms = []
        for nm_i in input_names_l:
            if nm_i in inputs:
                in_syms.append(inputs[nm_i])
            else:
                if nm_i == "label":
                    in_syms.append(Variable("%s_label" % name))
                else:
                    in_syms.append(Variable("%s_%s" % (name, nm_i)))
        aux_syms = []
        for nm_a in aux_names:
            if nm_a in inputs:
                aux_syms.append(inputs[nm_a])
            else:
                v = Variable("%s_%s" % (name, nm_a))
                v._entries[0][0].is_aux = True
                aux_syms.append(v)
        # no_bias / variadic single-input trimming
        if attrs.get("no_bias") and "bias" in input_names:
            idx = input_names.index("bias")
            if "bias" not in inputs:
                in_syms = in_syms[:idx] + in_syms[idx + 1:]
        return _create(op, in_syms, attrs, name, aux_syms)

    fn.__name__ = op.name
    fn.__doc__ = op.__doc__
    return fn


# ------------------------------------------------------------------ loading


def load_json(json_str: str) -> Symbol:
    """Load a reference-format symbol JSON (the schema of
    ``MXSymbolCreateFromJSON``, src/c_api/c_api_symbolic.cc). Accepts
    every vintage of the container: 0.8-era ``param``+``attr`` (see the
    reference fixture tests/python/unittest/save_000800.json), 1.x-era
    merged ``attrs``, and 2-element or 3-element input/head tuples.
    Auxiliary states are re-derived from each op's aux arity, like the
    reference re-derives them from op metadata on load."""
    g = json.loads(json_str)
    built: List[_Node] = []
    for rn in g["nodes"]:
        if rn["op"] == "null":
            node = _Node(None, rn["name"],
                         is_aux=bool(rn.get("is_aux", False)))
            node.str_attrs = {
                k: str(v) for k, v in
                (rn.get("attr") or rn.get("attrs") or
                 rn.get("str_attrs") or {}).items()}
        else:
            op = get_op(rn["op"])
            if "param" in rn:              # 0.8 era: op attrs live here
                op_attrs = rn["param"]
                user_attrs = rn.get("attr", {})
            else:                          # 1.x era: one merged dict
                merged = dict(rn.get("attrs", {}))
                user_keys = ("ctx_group", "lr_mult", "wd_mult",
                             "__shape__", "__layout__", "__dtype__",
                             "__init__", "force_mirroring")
                user_attrs = {k: merged.pop(k) for k in list(merged)
                              if k in user_keys or k.startswith("__")}
                op_attrs = merged
                user_attrs.update(rn.get("str_attrs", {}))
            attrs = {k: _parse_attr(v) for k, v in op_attrs.items()}
            attrs = _filter_op_attrs(op, attrs, rn["name"])
            inputs = [(built[e[0]], e[1]) for e in rn["inputs"]]
            n_aux = op.num_aux
            if n_aux:
                visible = len(op.input_names)
                if attrs.get("no_bias") and "bias" in op.input_names:
                    visible -= 1
                if len(inputs) >= visible + n_aux:
                    # file serialized the aux states as graph inputs
                    # (reference 1.x style) — adopt them as aux
                    for src, _ in inputs[-n_aux:]:
                        if src.is_variable:
                            src.is_aux = True
                else:
                    # 0.8-style file omits aux states — re-create them by
                    # the <name>_<aux> convention (make_symbol_function)
                    for aux_name in op.aux_input_names:
                        v = _Node(None, "%s_%s" % (rn["name"], aux_name),
                                  is_aux=True)
                        inputs.append((v, 0))
            node = _Node(op, rn["name"], attrs, inputs)
            node.str_attrs = {k: str(v) for k, v in user_attrs.items()}
        built.append(node)
    entries = [(built[e[0]], e[1]) for e in g["heads"]]
    return Symbol(entries)


def _filter_op_attrs(op, attrs, node_name):
    """Drop serialized op params this build doesn't take (workspace,
    cudnn_tune, ... — backend tuning knobs of the reference with no TPU
    meaning), so reference checkpoints load instead of erroring."""
    import inspect
    try:
        params = inspect.signature(op.fn).parameters
    except (TypeError, ValueError):
        return attrs
    if any(p.kind is p.VAR_KEYWORD for p in params.values()):
        return attrs
    known = set(params)
    dropped = [k for k in attrs if k not in known]
    if dropped:
        logging.getLogger(__name__).debug(
            "load_json: dropping unsupported attrs %s of node %r (%s)",
            dropped, node_name, op.name)
    return {k: v for k, v in attrs.items() if k in known}


def load(fname: str) -> Symbol:
    with open(fname) as f:
        return load_json(f.read())


# ------------------------------------------------------------------ shapes


def _eval_node_abstract(node: _Node, in_avals):
    """Abstract-evaluate ONE graph node: the single home of the implicit
    op-invocation protocol (drop ``name``, default ``_is_train``, thread a
    per-node RNG key for sampler ops), shared by ``_derive_param_shapes``,
    the ``infer_shape`` error localizer, and the analyzer's shape pass so
    the protocol cannot drift between them. ``in_avals`` are
    ``jax.ShapeDtypeStruct``s; returns a tuple of them (raises whatever
    the op raises)."""
    import inspect
    attrs = dict(node.attrs)
    attrs.pop("name", None)
    try:
        params = inspect.signature(node.op.fn).parameters
    except (TypeError, ValueError):
        params = {}
    if "_is_train" in params:
        attrs.setdefault("_is_train", True)
    if node.op.needs_rng:
        outs = jax.eval_shape(
            lambda key, *xs: node.op.fn(*xs, _rng=key, **attrs),
            jax.ShapeDtypeStruct((2,), np.uint32), *in_avals)
    else:
        outs = jax.eval_shape(
            lambda *xs: node.op.fn(*xs, **attrs), *in_avals)
    return outs if isinstance(outs, tuple) else (outs,)


def _infer_shapes(sym: Symbol, known: Dict[str, Tuple[int, ...]],
                  partial: bool = False):
    """Abstract-evaluate the graph with jax.eval_shape to derive all
    variable/output shapes (the TPU replacement for nnvm InferShape)."""
    from ..executor import graph_function
    arg_names = sym.list_arguments()
    aux_names = sym.list_auxiliary_states()

    resolved = dict(known)
    batch_size = resolved.pop("__batch_size__", None)
    if batch_size is None:
        # derive the batch hint from the caller-provided input shapes:
        # prefer the canonical "data" input's leading dim (NT/NTC layouts;
        # pass __batch_size__ explicitly for time-major data)
        data_like = [(n, s) for n, s in resolved.items()
                     if s and not str(n).endswith(
                         ("weight", "bias", "gamma", "beta",
                          "moving_mean", "moving_var"))]
        for n, s in data_like:
            if n == "data":
                batch_size = s[0]
                break
        else:
            if data_like:
                batch_size = data_like[0][1][0]
    # shapes pinned on Variables via shape= attr; wildcard (0) dims stand
    # for the batch dimension (reference convention: state_info shapes are
    # (0, H) with __layout__ marking the N axis) and resolve from the
    # caller-provided batch hint
    for node in _topo_order(sym._entries):
        if node.is_variable and "__shape__" in node.str_attrs and \
                node.name not in resolved:
            shape = list(ast.literal_eval(node.str_attrs["__shape__"]))
            if any(s == 0 for s in shape) and batch_size:
                layout = node.str_attrs.get("__layout__", "")
                n_axis = layout.find("N")
                if 0 <= n_axis < len(shape) and shape[n_axis] == 0:
                    shape[n_axis] = int(batch_size)
                else:
                    shape = [int(batch_size) if s == 0 else s
                             for s in shape]
            resolved[node.name] = tuple(shape)

    missing = [n for n in arg_names + aux_names if n not in resolved]
    if missing:
        # derive parameter shapes structurally: walk nodes, use op shape hints
        derived = _derive_param_shapes(sym, resolved)
        resolved.update(derived)
        missing = [n for n in arg_names + aux_names if n not in resolved]
    if missing and not partial:
        raise MXNetError("infer_shape: cannot infer %s (provide its shape)"
                         % missing)
    if missing:
        return None

    fn = graph_function(sym)
    args = {n: jax.ShapeDtypeStruct(tuple(resolved[n]), np.float32)
            for n in arg_names}
    aux = {n: jax.ShapeDtypeStruct(tuple(resolved[n]), np.float32)
           for n in aux_names}
    key = jax.ShapeDtypeStruct((2,), np.uint32)
    try:
        outs, _ = jax.eval_shape(lambda a, x, k: fn(a, x, k, True),
                                 args, aux, key)
    except MXNetError:
        raise
    except Exception as exc:
        raise _shape_error_with_context(sym, resolved, exc) from exc
    shapes = {n: tuple(resolved[n]) for n in arg_names + aux_names}
    shapes["__outputs__"] = [tuple(o.shape) for o in outs]
    return shapes


def _shape_error_with_context(sym, resolved, exc) -> MXNetError:
    """Localize a whole-graph ``jax.eval_shape`` failure to the offending
    op node: re-walk the graph evaluating one node at a time and name the
    first node that rejects its inputs, with the op, the node name, and
    the actual input shapes — instead of a jax traceback that mentions
    neither (ISSUE 3 satellite)."""
    first_line = str(exc).strip().splitlines()
    first_line = first_line[0] if first_line else type(exc).__name__
    shapes: Dict[Tuple[int, int], tuple] = {}

    def shape_of(entry):
        node, idx = entry
        if node.is_variable:
            s = resolved.get(node.name)
            return tuple(s) if s is not None else None
        return shapes.get((id(node), idx))

    for node in _topo_order(sym._entries):
        if node.is_variable:
            continue
        in_shapes = [shape_of(e) for e in node.inputs]
        if any(s is None for s in in_shapes):
            continue
        try:
            outs = _eval_node_abstract(
                node, [jax.ShapeDtypeStruct(s, np.float32)
                       for s in in_shapes])
        except Exception as node_exc:                       # noqa: BLE001
            node_line = str(node_exc).strip().splitlines()
            node_line = node_line[0] if node_line \
                else type(node_exc).__name__
            in_desc = ", ".join(
                "%s=(%s)" % (src.name, ",".join(map(str, s)))
                for (src, _), s in zip(node.inputs, in_shapes))
            return MXNetError(
                "infer_shape: op %s (node %r) rejects its input shapes "
                "[%s]: %s" % (node.op.name, node.name, in_desc, node_line))
        for i, o in enumerate(outs):
            shapes[(id(node), i)] = tuple(o.shape)
    # per-node walk could not localize it (a cross-node interaction):
    # still better than a raw traceback — summarize the failure
    return MXNetError("infer_shape failed: %s" % first_line)


def _derive_param_shapes(sym: Symbol, known: Dict[str, Tuple[int, ...]]):
    """Forward-walk the graph deriving weight/bias/aux shapes from op attrs +
    input shapes (the role of the reference's per-op InferShape rules, e.g.
    convolution-inl.h InferShape). Parameter-owning ops have explicit
    derivation rules; output shapes of every node are then propagated with
    ``jax.eval_shape`` so downstream parameter shapes resolve too — MLP-style
    ``data -> fc -> act -> fc`` infers all weights from the data shape alone,
    exactly like the reference."""
    derived: Dict[str, Tuple[int, ...]] = {}
    shapes: Dict[Tuple[int, int], Tuple[int, ...]] = {}  # (node id, out idx)
    eval_memo: Dict[tuple, Optional[tuple]] = {}         # per-call memo

    def shape_of(entry):
        node, idx = entry
        if node.is_variable:
            s = known.get(node.name) or derived.get(node.name)
            return tuple(s) if s is not None else None
        return shapes.get((id(node), idx))

    for node in _topo_order(sym._entries):
        if node.is_variable:
            continue
        opname = node.op.name
        a = node.attrs
        in_shapes = [shape_of(e) for e in node.inputs]
        ds = in_shapes[0] if in_shapes else None

        def setvar(pos, shape):
            if pos >= len(node.inputs):
                return
            n, _ = node.inputs[pos]
            if n.is_variable and n.name not in known and \
                    n.name not in derived and shape is not None:
                derived[n.name] = tuple(int(x) for x in shape)

        # ---- parameter derivation rules (subset of ops that own params)
        try:
            if ds is not None:
                if opname == "FullyConnected":
                    nh = int(a.get("num_hidden"))
                    flat = int(np.prod(ds[1:])) if a.get("flatten", True) else ds[-1]
                    setvar(1, (nh, flat))
                    setvar(2, (nh,))
                elif opname in ("Convolution", "Convolution_v1"):
                    nf = int(a.get("num_filter"))
                    k = _shape_attr(a.get("kernel"), len(ds) - 2, 1)
                    g = int(a.get("num_group", 1))
                    setvar(1, (nf, ds[1] // g) + k)
                    setvar(2, (nf,))
                elif opname == "Deconvolution":
                    nf = int(a.get("num_filter"))
                    k = _shape_attr(a.get("kernel"), len(ds) - 2, 1)
                    g = int(a.get("num_group", 1))
                    setvar(1, (ds[1], nf // g) + k)
                    setvar(2, (nf,))
                elif opname in ("BatchNorm", "BatchNorm_v1"):
                    ax = int(a.get("axis", 1)) % len(ds)
                    for pos in range(1, 5):
                        setvar(pos, (ds[ax],))
                elif opname == "InstanceNorm":
                    setvar(1, (ds[1],))
                    setvar(2, (ds[1],))
                elif opname == "LayerNorm":
                    ax = int(a.get("axis", -1)) % len(ds)
                    setvar(1, (ds[ax],))
                    setvar(2, (ds[ax],))
                elif opname == "IdentityAttachKLSparseReg":
                    setvar(1, (int(np.prod(ds[1:])),))
                elif opname == "Embedding":
                    setvar(1, (int(a.get("input_dim")),
                               int(a.get("output_dim"))))
                elif opname == "LeakyReLU" and a.get("act_type") == "prelu":
                    setvar(1, (ds[1],))
                elif opname in ("SoftmaxOutput", "LinearRegressionOutput",
                                "MAERegressionOutput",
                                "LogisticRegressionOutput", "SVMOutput"):
                    lbl = (ds[0],) if opname in ("SoftmaxOutput", "SVMOutput") \
                        else ds
                    setvar(1, lbl)
                elif opname == "RNN":
                    # ds = (T, N, input); packed params + initial states
                    from ..ops.rnn_op import rnn_param_size
                    H = int(a.get("state_size"))
                    L = int(a.get("num_layers", 1))
                    mode = a.get("mode", "lstm")
                    dirs = 2 if a.get("bidirectional") else 1
                    setvar(1, (rnn_param_size(L, ds[2], H, mode,
                                              bool(a.get("bidirectional"))),))
                    setvar(2, (L * dirs, ds[1], H))
                    if mode == "lstm":
                        setvar(3, (L * dirs, ds[1], H))
                elif opname == "Custom":
                    # the user's Prop owns the shape rules; its infer_shape
                    # may choke on partially-None shapes (user validation
                    # code) — any failure just skips derivation for the node
                    try:
                        from ..operator import _make_prop
                        prop = _make_prop(a["op_type"], a)
                        ish, _, _ = prop.infer_shape(
                            [list(s) if s is not None else None
                             for s in in_shapes])
                        for pos, s in enumerate(ish):
                            if s is not None:
                                setvar(pos, tuple(int(x) for x in s))
                    except Exception:
                        pass
        except (TypeError, KeyError, ValueError):
            pass

        # ---- abstract-evaluate this node if all inputs are now known.
        # Repeated structures (the 12 identical transformer blocks, say)
        # produce the same (op, attrs, input shapes) over and over; memoize
        # so each unique signature traces once — for custom_vjp-heavy ops
        # (flash attention) this is the difference between seconds and
        # minutes of bind time.
        in_shapes = [shape_of(e) for e in node.inputs]
        if any(s is None for s in in_shapes):
            continue
        ckey = (node.op.name, tuple(in_shapes),
                tuple(sorted((k, repr(v)) for k, v in a.items())))
        if ckey in eval_memo:
            outs = eval_memo[ckey]
            if outs is not None:
                for i, o in enumerate(outs):
                    shapes[(id(node), i)] = o
            continue
        try:
            outs = _eval_node_abstract(
                node, [jax.ShapeDtypeStruct(s, np.float32)
                       for s in in_shapes])
            out_shapes = tuple(tuple(o.shape) for o in outs)
            eval_memo[ckey] = out_shapes
            for i, o in enumerate(out_shapes):
                shapes[(id(node), i)] = o
        except Exception:
            eval_memo[ckey] = None
    return derived


def _shape_attr(v, n, default):
    if v is None:
        return (default,) * n
    if isinstance(v, int):
        return (v,) * n
    t = tuple(int(x) for x in v)
    return t * n if len(t) == 1 else t
