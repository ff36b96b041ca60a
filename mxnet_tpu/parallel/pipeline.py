"""Pipeline parallelism: GPipe-style microbatched schedule over a mesh axis.

TPU-native upgrade of the reference's inter-layer model parallelism
(``group2ctx`` + PlaceDevice inserting _CrossDeviceCopy nodes,
src/executor/graph_executor.cc:279-393, demo example/model-parallel-lstm/
lstm.py:65-129). The reference overlaps stages only through its dependency
engine; here the schedule is explicit SPMD: every device runs the same
program under ``shard_map``, holds one stage's parameters (stacked pytree
sharded over the ``pipe`` axis), and microbatch activations hop stages via
``lax.ppermute`` over ICI. ``M`` microbatches over ``N`` stages take
``M + N - 1`` ticks (the GPipe bubble); everything is a ``lax.scan`` so XLA
sees one compiled loop, and the whole thing is differentiable (``ppermute``
has a transpose rule) so ``jax.grad`` of a pipelined loss just works —
gradients accumulate across microbatches exactly like GPipe.

Heterogeneous models (embed -> blocks -> logits/loss) fit the SPMD
uniformity requirement through ``first_fn``/``last_fn``: the repeated
``stage_fn`` maps a fixed "wire" activation shape to itself, while the
first/last stages adapt raw inputs to the wire and the wire to outputs.
Their (replicated) computations run on every device and are masked to
the owning stage — the standard GPipe-under-SPMD trick: uniformity costs
a little redundant embed/head compute, and buys one compiled program.
"""
from __future__ import annotations

import functools

__all__ = ["pipeline_apply", "pipeline_1f1b", "stack_stage_params",
           "sharding_island"]


def sharding_island():
    """Canonical layout claims of the pipeline island (audited by
    ``analysis.sharding_passes.check_islands``): drawn from the unified
    SpecLayout — the stacked stage-parameter axis rides the canonical
    ``tp`` model axis and the batch layout matches every other island,
    so the audit reports zero cross-island disagreements."""
    from .layout import island_specs
    return "pipeline", island_specs("pipeline")


def _resolve_axis(mesh, axis):
    """``axis=None`` resolves to the legacy ``pipe`` axis when the mesh
    carries it, else the unified SpecLayout's model axis (``tp``) —
    meshes built with a ``pipe`` axis keep working. An explicit axis is
    honored verbatim and must exist on the mesh (typos fail loudly
    instead of silently redirecting to another axis)."""
    if axis is not None:
        if axis not in mesh.axis_names:
            raise ValueError("mesh has no axis %r (axes: %s)"
                             % (axis, tuple(mesh.axis_names)))
        return axis
    from .layout import resolve_model_axis
    return resolve_model_axis(mesh, "pipe")


def stack_stage_params(per_stage_params):
    """Stack a list of per-stage parameter pytrees along a new leading axis.

    The result is what ``pipeline_apply`` expects: each leaf has shape
    ``(n_stages, ...)``; shard the leading axis over the pipe mesh axis.
    All stages must share one parameter structure (equal blocks per
    stage — the usual pipeline layout); adapters that don't fit it go in
    ``first_fn``/``last_fn`` params instead.
    """
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *per_stage_params)


def pipeline_apply(stage_fn, stage_params, inputs, *, mesh, axis=None,
                   first_fn=None, first_params=None,
                   last_fn=None, last_params=None, remat=False):
    """Run ``N = mesh.shape[axis]`` pipeline stages over microbatched input.

    Parameters
    ----------
    stage_fn : callable(params_i, x) -> y
        The per-stage computation; ``y`` must have ``x``'s shape/dtype
        (the pipeline "wire"), so activations can hop devices uniformly.
    stage_params : pytree
        Per-stage parameters stacked on a leading ``n_stages`` axis
        (see ``stack_stage_params``).
    inputs : array or pytree of arrays, each (M, mb, ...)
        ``M`` microbatches. ``M >= N`` keeps the bubble fraction at
        ``(N-1)/(M+N-1)``. A pytree (e.g. ``{"data": ..., "label": ...}``)
        lets the head see per-microbatch side inputs; a bare array is the
        wire itself when ``first_fn`` is None.
    mesh : jax.sharding.Mesh with the ``axis`` dimension.
    first_fn : callable(first_params, raw_mb) -> wire, optional
        Input adapter owned by stage 0 (e.g. embedding lookup: int token
        ids -> hidden states). Its output defines the wire shape/dtype.
        ``first_params`` ride replicated. ``raw_mb`` is the microbatch
        slice of ``inputs`` (same pytree structure).
    last_fn : callable(last_params, wire[, raw_mb]) -> out, optional
        Output head owned by stage N-1 (e.g. final norm + logits, or a
        per-microbatch loss). Defines the returned shape. A 3-argument
        ``last_fn`` also receives the microbatch slice of ``inputs``
        whose wire is finishing — how labels reach a loss head.
    remat : bool
        Wrap ``stage_fn`` in ``jax.checkpoint`` so backward recomputes
        stage activations per microbatch instead of storing all
        ``M x N`` of them (GPipe's activation memory trade).

    Returns the (M, ...) per-microbatch outputs of ``last_fn`` (or of the
    last stage when ``last_fn`` is None).
    """
    import inspect
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    axis = _resolve_axis(mesh, axis)
    n_stages = mesh.shape[axis]
    leaves = jax.tree_util.tree_leaves(inputs)
    n_micro = leaves[0].shape[0]
    if n_micro < 1:
        raise ValueError("need at least one microbatch")
    tree_mb = lambda xs, t: jax.tree_util.tree_map(lambda a: a[t], xs)

    # heterogeneous stages: a list of per-stage fns with a tuple of
    # per-stage param trees (structures may differ). Each device runs its
    # own branch via lax.switch; params ride replicated (P()) since a
    # ragged tuple cannot shard over the pipe axis — the activation
    # schedule still pipelines. Homogeneous callers keep the stacked,
    # param-sharded fast path.
    hetero = isinstance(stage_fn, (list, tuple))
    if hetero:
        if len(stage_fn) != n_stages:
            raise ValueError("got %d stage fns for %d pipeline devices"
                             % (len(stage_fn), n_stages))
        stage_fns = [jax.checkpoint(f) if remat else f for f in stage_fn]
    elif remat:
        stage_fn = jax.checkpoint(stage_fn)

    # a 3-arg head also sees the finishing microbatch's raw inputs
    # (labels for a loss head); keep the 2-arg form working
    if last_fn is not None and \
            len(inspect.signature(last_fn).parameters) >= 3:
        head_fn = last_fn
    elif last_fn is not None:
        head_fn = lambda p, y, raw: last_fn(p, y)
    else:
        head_fn = None

    # wire shape: what hops between devices each tick
    raw_sd = jax.eval_shape(lambda x: tree_mb(x, 0), inputs)
    if first_fn is None:
        wire_sd = raw_sd
        if not isinstance(wire_sd, jax.ShapeDtypeStruct):
            raise ValueError(
                "pytree inputs need a first_fn to define the wire")
    else:
        wire_sd = jax.eval_shape(first_fn, first_params, raw_sd)
    out_sd = wire_sd if head_fn is None else \
        jax.eval_shape(head_fn, last_params, wire_sd, raw_sd)

    # params: leading stage axis sharded over the pipe axis; inputs,
    # outputs, and the first/last adapters replicated (only stage 0
    # reads, only stage N-1 writes — jnp.where keeps SPMD uniform).
    rep = lambda tree: jax.tree_util.tree_map(lambda _: P(), tree)
    param_spec = rep(stage_params) if hetero else \
        jax.tree_util.tree_map(lambda _: P(axis), stage_params)

    def spmd(params, fparams, lparams, xs):
        idx = lax.axis_index(axis)
        if hetero:
            local = params          # full tuple; switch picks the branch
            run_stage = lambda x: lax.switch(
                idx, [lambda op, k=k: stage_fns[k](op[0][k], op[1])
                      for k in range(n_stages)], (local, x))
        else:
            # this device's stage params: shard_map hands us a leading
            # axis of size n_stages/n_stages == 1
            local = jax.tree_util.tree_map(lambda p: p[0], params)
            run_stage = lambda x: stage_fn(local, x)
        ticks = n_micro + n_stages - 1

        def step(carry, t):
            recv, outs = carry
            raw = tree_mb(xs, jnp.clip(t, 0, n_micro - 1))
            z0 = raw if first_fn is None else first_fn(fparams, raw)
            x = jnp.where(idx == 0, z0, recv)
            y = run_stage(x)
            # device i hands its activation to i+1 (the last stage's
            # output stays home and is collected below)
            send = lax.ppermute(
                y, axis, perm=[(i, i + 1) for i in range(n_stages - 1)])
            out_t = t - (n_stages - 1)
            raw_out = tree_mb(xs, jnp.clip(out_t, 0, n_micro - 1))
            take = jnp.logical_and(idx == n_stages - 1,
                                   jnp.logical_and(out_t >= 0,
                                                   out_t < n_micro))
            if head_fn is None:
                out = y
            else:
                # the head must run ONLY on collected ticks — not just be
                # masked after the fact. Loss heads (SoftmaxOutput et al.)
                # have custom vjps that ignore the incoming cotangent, so
                # a merely-masked head would inject a gradient from every
                # bubble/garbage tick on every device; lax.cond keeps the
                # untaken branch out of both forward and backward.
                out = lax.cond(
                    take,
                    lambda args: head_fn(lparams, *args),
                    lambda args: jnp.zeros(out_sd.shape, out_sd.dtype),
                    (y, raw_out))
            slot = jnp.clip(out_t, 0, n_micro - 1)
            outs = lax.dynamic_update_index_in_dim(
                outs,
                jnp.where(take, out, lax.dynamic_index_in_dim(
                    outs, slot, 0, keepdims=False)),
                slot, 0)
            return (send, outs), None

        init = (jnp.zeros(wire_sd.shape, wire_sd.dtype),
                jnp.zeros((n_micro,) + out_sd.shape, out_sd.dtype))
        (_, outs), _ = lax.scan(step, init, jnp.arange(ticks))
        # everyone returns; only the last stage's buffer is real —
        # psum after masking replicates it across the pipe axis
        outs = jnp.where(idx == n_stages - 1, outs, 0)
        return lax.psum(outs, axis)

    fn = shard_map(spmd, mesh=mesh,
                   in_specs=(param_spec, rep(first_params),
                             rep(last_params), P()),
                   out_specs=P(), check_vma=False)
    return fn(stage_params, first_params, last_params, inputs)


def pipeline_1f1b(stage_fns, stage_params, inputs, *, mesh, axis=None,
                  first_fn, first_params, last_fn, last_params, key=None,
                  stage_aux=None):
    """One-forward-one-backward pipeline schedule with a hand-written
    backward (PipeDream-flush class; the modern upgrade of GPipe's
    all-forward-then-all-backward).

    Unlike :func:`pipeline_apply` (whose backward is jax autodiff of the
    forward scan, so all ``M`` microbatch residuals stay live), this
    schedules forward and backward ticks on one lattice: at tick ``t``
    device ``i`` runs the forward of microbatch ``t - i`` and the
    backward of microbatch ``t - (2N-2-i)``, recomputing the stage
    forward from a saved input (activation-remat) for the vjp. Saved
    inputs live in a ring buffer of ``min(M, 2N-1)`` slots — activation
    memory is O(N), not O(M), which is the point of 1F1B. The bubble is
    ``(2N-2)/(M+2N-2)`` of ticks (each tick = 1 fwd + 1 recompute +
    1 bwd), vs GPipe's ``(N-1)/(M+N-1)`` per direction — slightly more
    idle, bounded memory.

    Because the backward is hand-scheduled, this function returns
    gradients directly (do NOT wrap it in ``jax.grad``):

    ``outs, grads = pipeline_1f1b(...)`` where ``grads`` is
    ``{"first": tree, "stages": tuple_of_trees, "last": tree}`` —
    f32-accumulated sums over microbatches, seeded with ones at each
    microbatch's head output (Module backward semantics: loss ops'
    custom vjps define the cotangent and may ignore the seed).

    Parameters mirror :func:`pipeline_apply`'s heterogeneous form:
    ``stage_fns`` is a list of ``fn(params_i, x, key) -> y`` (wire-shaped
    y), ``stage_params`` a tuple of per-stage trees (replicated across
    the mesh — ragged trees cannot shard), ``first_fn(fp, raw, key)``,
    ``last_fn(lp, y, raw, key)``. ``key`` is folded with the microbatch
    index so dropout differs per microbatch and the backward recompute
    replays the forward's randomness exactly.

    ``stage_aux`` (optional): a tuple of per-stage auxiliary-state trees
    (BatchNorm moving stats). When given, stage fns take the 4-ary form
    ``fn(params_i, aux_i, x, key) -> (y, new_aux_i)``; each forward tick
    updates the owning stage's aux (running stats advance once per
    microbatch, like a sequential run), the backward recompute uses the
    tick-current aux, and the final aux tuple is returned:
    ``outs, grads, new_aux = pipeline_1f1b(..., stage_aux=aux)``.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    axis = _resolve_axis(mesh, axis)
    N = mesh.shape[axis]
    # a single callable = homogeneous stacked mode: params/aux leaves
    # carry a leading N axis SHARDED over the pipe axis (same layout as
    # pipeline_apply's fast path) — parameter memory scales, unlike the
    # replicated tuple mode that ragged (heterogeneous) stages need
    stacked = callable(stage_fns)
    lift = lambda f: lambda p, a, x, kk: (f(p, x, kk), a)
    has_aux = stage_aux is not None
    if stacked:
        if not has_aux:
            stage_aux = {}
            stage_fns = lift(stage_fns)
    else:
        if len(stage_fns) != N:
            raise ValueError("got %d stage fns for %d pipeline devices"
                             % (len(stage_fns), N))
        if not has_aux:
            stage_aux = tuple({} for _ in range(N))
            stage_fns = [lift(f) for f in stage_fns]
    leaves = jax.tree_util.tree_leaves(inputs)
    M = leaves[0].shape[0]
    if key is None:
        key = jax.random.PRNGKey(0)
    tree_mb = lambda xs, t: jax.tree_util.tree_map(lambda a: a[t], xs)

    raw_sd = jax.eval_shape(lambda x: tree_mb(x, 0), inputs)
    key_sd = jax.eval_shape(lambda k: k, key)
    wire_sd = jax.eval_shape(first_fn, first_params, raw_sd, key_sd)
    out_sd = jax.eval_shape(last_fn, last_params, wire_sd, raw_sd, key_sd)

    BUF = min(M, 2 * N - 1)
    ticks = M + 2 * N - 2
    rep = lambda tree: jax.tree_util.tree_map(lambda _: P(), tree)
    f32zeros = lambda tree: jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), tree)
    gate = lambda cond_, tree: jax.tree_util.tree_map(
        lambda g: jnp.where(cond_, g, 0.0).astype(jnp.float32), tree)
    acc = lambda a, b: jax.tree_util.tree_map(
        lambda x, y: x + y.astype(jnp.float32), a, b)

    def spmd(params, aux0, fparams, lparams, xs, key):
        idx = lax.axis_index(axis)
        if stacked:
            # my stage's slice of the P(axis)-sharded stacked trees
            local_p = jax.tree_util.tree_map(lambda a: a[0], params)
            local_a0 = jax.tree_util.tree_map(lambda a: a[0], aux0)

            def run_fwd(op):
                _, aux, x, kk = op
                return stage_fns(local_p, aux, x, kk)

            def run_vjp(op):
                _, aux, x, kk, cot = op
                y, pull, _ = jax.vjp(
                    lambda pk, xx: stage_fns(pk, aux, xx, kk),
                    local_p, x, has_aux=True)
                gp, dx = pull(cot.astype(y.dtype))
                return jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32), gp), dx
        else:
            local_a0 = aux0

            def fwd_branch(k):
                def run(op):
                    p, aux, x, kk = op
                    y, new_aux_k = stage_fns[k](p[k], aux[k], x, kk)
                    out_aux = list(aux)
                    out_aux[k] = new_aux_k
                    return y, tuple(out_aux)
                return run

            def vjp_branch(k):
                def run(op):
                    p, aux, x, kk, cot = op
                    y, pull, _ = jax.vjp(
                        lambda pk, xx: stage_fns[k](pk, aux[k], xx, kk),
                        p[k], x, has_aux=True)
                    gp_k, dx = pull(cot.astype(y.dtype))
                    gp = list(f32zeros(params))
                    gp[k] = jax.tree_util.tree_map(
                        lambda g: g.astype(jnp.float32), gp_k)
                    return tuple(gp), dx
                return run

            def run_fwd(op):
                return lax.switch(idx, [fwd_branch(k) for k in range(N)],
                                  op)

            def run_vjp(op):
                return lax.switch(idx, [vjp_branch(k) for k in range(N)],
                                  op)

        def head_vjp(op):
            lp, y, raw, kk = op
            out, pull = jax.vjp(
                lambda l, yy: last_fn(l, yy, raw, kk), lp, y)
            gl, cot = pull(jnp.ones(out.shape, out.dtype))
            return (out,
                    jax.tree_util.tree_map(
                        lambda g: g.astype(jnp.float32), gl),
                    cot.astype(jnp.float32))

        def head_zero(op):
            return (jnp.zeros(out_sd.shape, out_sd.dtype),
                    f32zeros(lparams),
                    jnp.zeros(wire_sd.shape, jnp.float32))

        def first_vjp(op):
            fp, raw, kk, dx = op
            z, pull = jax.vjp(lambda f: first_fn(f, raw, kk), fp)
            (gf,) = pull(dx.astype(z.dtype))
            return jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32), gf)

        def first_zero(op):
            return f32zeros(fparams)

        def step(carry, t):
            fwd_recv, bwd_recv, xbuf, aux_c, gF, gS, gL, outs = carry
            f = t - idx
            b = t - (2 * N - 2 - idx)
            do_f = jnp.logical_and(f >= 0, f < M)
            do_b = jnp.logical_and(b >= 0, b < M)
            raw_f = tree_mb(xs, jnp.clip(f, 0, M - 1))
            raw_b = tree_mb(xs, jnp.clip(b, 0, M - 1))
            key_f = jax.random.fold_in(key, jnp.clip(f, 0, M - 1))
            key_b = jax.random.fold_in(key, jnp.clip(b, 0, M - 1))
            # distinct keys per (microbatch, stage) — otherwise stages
            # built from one template drop identical dropout coordinates.
            # N / N+1 are the adapter's and head's reserved stage slots.
            kf_stage = jax.random.fold_in(key_f, idx)
            kb_stage = jax.random.fold_in(key_b, idx)
            kf_adapter = jax.random.fold_in(key_f, N)
            kb_adapter = jax.random.fold_in(key_b, N)
            kf_head = jax.random.fold_in(key_f, N + 1)

            # ---- forward tick: microbatch f through my stage
            z0 = first_fn(fparams, raw_f, kf_adapter)
            x_in = jnp.where(idx == 0, z0, fwd_recv)
            y, aux_new = run_fwd((params, aux_c, x_in, kf_stage))
            aux_c = jax.tree_util.tree_map(
                lambda new, old: jnp.where(do_f, new, old), aux_new,
                aux_c)
            slot_f = jnp.clip(f, 0, M - 1) % BUF
            old = lax.dynamic_index_in_dim(xbuf, slot_f, 0, keepdims=False)
            xbuf = lax.dynamic_update_index_in_dim(
                xbuf, jnp.where(do_f, x_in, old), slot_f, 0)

            # ---- head: runs only on the last device's valid fwd ticks
            # (lax.cond, not masking: loss vjps ignore the cotangent)
            take = jnp.logical_and(idx == N - 1, do_f)
            out_f, gl_t, cot_head = lax.cond(
                take, head_vjp, head_zero, (lparams, y, raw_f, kf_head))
            gL = acc(gL, gl_t)
            slot_o = jnp.clip(f, 0, M - 1)
            oldo = lax.dynamic_index_in_dim(outs, slot_o, 0,
                                            keepdims=False)
            outs = lax.dynamic_update_index_in_dim(
                outs, jnp.where(take, out_f, oldo), slot_o, 0)

            # ---- backward tick: microbatch b (same-tick head cotangent
            # on the last device, else the cotangent from stage idx+1)
            cot_in = jnp.where(idx == N - 1, cot_head, bwd_recv)
            slot_b = jnp.clip(b, 0, M - 1) % BUF
            x_saved = lax.dynamic_index_in_dim(xbuf, slot_b, 0,
                                               keepdims=False)
            # the recompute uses the tick-current aux: in train mode BN
            # normalizes with batch statistics (aux only collects running
            # stats), so the recomputed activations are exact anyway
            gS_t, dx = run_vjp((params, aux_c, x_saved, kb_stage, cot_in))
            gS = acc(gS, gate(do_b, gS_t))
            gF = acc(gF, lax.cond(
                jnp.logical_and(idx == 0, do_b), first_vjp, first_zero,
                (fparams, raw_b, kb_adapter, dx)))

            fwd_send = lax.ppermute(
                y, axis, perm=[(i, i + 1) for i in range(N - 1)])
            bwd_send = lax.ppermute(
                dx.astype(jnp.float32), axis,
                perm=[(i, i - 1) for i in range(1, N)])
            return (fwd_send, bwd_send, xbuf, aux_c,
                    gF, gS, gL, outs), None

        init = (jnp.zeros(wire_sd.shape, wire_sd.dtype),
                jnp.zeros(wire_sd.shape, jnp.float32),
                jnp.zeros((BUF,) + wire_sd.shape, wire_sd.dtype),
                local_a0,
                f32zeros(fparams),
                f32zeros(local_p) if stacked else f32zeros(params),
                f32zeros(lparams),
                jnp.zeros((M,) + out_sd.shape, out_sd.dtype))
        (_, _, _, aux_c, gF, gS, gL, outs), _ = lax.scan(
            step, init, jnp.arange(ticks))
        # adapter/head grads live on devices 0 / N-1 and outs on the
        # last device — psum assembles them everywhere. Stage grads/aux:
        # stacked mode returns each device's slice (shard_map's P(axis)
        # out_spec reassembles the stacked trees); tuple mode masks the
        # non-owned slots and psums.
        outs = jnp.where(idx == N - 1, outs, 0)
        gL = jax.tree_util.tree_map(
            lambda g: jnp.where(idx == N - 1, g, 0.0), gL)
        gF = jax.tree_util.tree_map(
            lambda g: jnp.where(idx == 0, g, 0.0), gF)
        psum = lambda tree: jax.tree_util.tree_map(
            lambda v: lax.psum(v, axis), tree)
        if stacked:
            lead = lambda tree: jax.tree_util.tree_map(
                lambda v: v[None], tree)
            return psum(outs), psum(gF), lead(gS), psum(gL), lead(aux_c)
        aux_c = tuple(
            jax.tree_util.tree_map(
                lambda v: jnp.where(idx == k, v, 0.0), aux_c[k])
            for k in range(N))
        return psum(outs), psum(gF), psum(gS), psum(gL), psum(aux_c)

    if stacked:
        sh = lambda tree: jax.tree_util.tree_map(lambda _: P(axis), tree)
        stage_in_spec, stage_out_spec = sh(stage_params), \
            (sh(stage_params), sh(stage_aux))
    else:
        stage_in_spec = rep(stage_params)
        stage_out_spec = (rep(stage_params), rep(stage_aux))
    aux_in_spec = stage_out_spec[1]
    fn = shard_map(spmd, mesh=mesh,
                   in_specs=(stage_in_spec, aux_in_spec,
                             rep(first_params), rep(last_params),
                             P(), P()),
                   out_specs=(P(), rep(first_params), stage_out_spec[0],
                              rep(last_params), stage_out_spec[1]),
                   check_vma=False)
    outs, gF, gS, gL, new_aux = fn(stage_params, stage_aux,
                                   first_params, last_params,
                                   inputs, key)
    grads = {"first": gF, "stages": gS, "last": gL}
    if has_aux:
        return outs, grads, new_aux
    return outs, grads
