"""Drive ``Module.fit`` for a window of seconds and check what it learned.

One ``fit`` call runs the whole cell. Its first steps are set-up: they
compile, and their losses, the first gradient (read back from the
optimizer's state after one step) and the parameters' change are kept for
the comparison with the plain reference. The window then opens on a
fence and closes on a fence, in ``fit``'s own batch-end callback, on the
same module, program and iterator; the iterator ends the epoch and
``fit`` returns by its normal path.
"""
import gc
import re
import time

import numpy as np

from benchmarks.lib import device, leaves, spec
from benchmarks.lib.spans import SpanLog


def _contexts(mx, chips, rehearse):
    make = mx.cpu if rehearse else mx.tpu
    return [make(i) for i in range(chips)]


class _Feed:
    """What ``fit`` is given: the generator's iterator, handed over as a
    user's own ``DataIter`` would be. Keeps the check steps' batches for
    the reference and ends the epoch when told to."""

    def __init__(self, inner, keep, spans):
        self._inner = inner
        self.provide_data = inner.provide_data
        self.provide_label = inner.provide_label
        self.batch_size = inner.batch_size
        self.kept = []
        self._keep = keep
        self.stop = False
        self._spans = spans

    def __iter__(self):
        return self

    def reset(self):
        pass

    def __next__(self):
        return self.next()

    def next(self):
        if self.stop:
            raise StopIteration
        with self._spans.span("feed.next"):
            batch = self._inner.next()
            if len(self.kept) < self._keep:
                self.kept.append((batch.data[0].asnumpy(),
                                  batch.label[0].asnumpy()))
            return batch


def run(ctx):
    import jax
    import mxnet_tpu as mx

    cell, log = ctx.cell, ctx.log
    cfg, traffic = cell.config, cell.traffic
    builder = spec.load_module("builders", cfg["builder"])
    optim = spec.load_module("references", "optim")
    generator = spec.load_module("generators", traffic["kind"])
    opt = cfg["assumed"]["optimizer"]
    if cfg["assumed"]["compute_dtype"] != "float32":
        mx.amp.init(cfg["assumed"]["compute_dtype"])
    chips = cell.chips
    rows = int(traffic["rows_per_chip"]) * chips
    check_steps = int(traffic["check_steps"])
    setup_steps = check_steps + int(traffic.get("warm_steps", 1))
    spans = SpanLog(enabled=ctx.trace)

    feed = _Feed(generator.make_iter(mx, traffic, cfg, ctx.seed, rows,
                                     cell.root), check_steps, spans)
    specs = builder.leaf_specs(cfg)
    arg0 = leaves.make(specs, ctx.seed)
    aux0 = leaves.make(builder.aux_specs(cfg), ctx.seed)

    def parts(name):
        return builder.parts(cfg, name)
    sym = builder.symbol(cfg, traffic)
    ctxs = _contexts(mx, chips, ctx.rehearse)
    mod = mx.mod.Module(sym, context=ctxs if chips > 1 else ctxs[0],
                        data_names=[d.name for d in feed.provide_data],
                        label_names=[d.name for d in feed.provide_label])

    st = {"losses": [], "grad": None, "change": None, "t_open": None,
          "t_close": None, "n_open": None, "steps": 0, "trace": None,
          "counters_open": None, "counters_close": None}
    loss_fn = leaves.make_loss_fn()
    param_names = sorted(arg0)

    def fence():
        jax.block_until_ready(
            [mod._exec.arg_dict[n].data for n in param_names[:1]]
            + [o.data for o in mod.get_outputs()])

    def on_batch(param):
        n = param.nbatch
        if st["t_close"] is not None:
            return
        with spans.span("bench.on_batch"):
            if n < check_steps:
                st["losses"].append(loss_fn(mod.get_outputs()[0].data,
                                            feed.kept[n][1]))
                if n == 0:
                    tree, st["grad_factor"] = optim.first_grad(
                        opt, {k: mod._fused_states[k] for k in param_names})
                    st["grad"] = leaves.norms(tree, parts)
                    del tree
                if n == check_steps - 1:
                    st["change"] = leaves.change_norms(
                        specs, ctx.seed,
                        {k: mod._exec.arg_dict[k].data
                         for k in param_names}, parts)
            if n == setup_steps - 1:
                fence()
                st["counters_open"] = dict(mx.profiler.counters())
                st["n_open"] = n
                if ctx.trace:
                    st["trace"] = ctx.tracer.start()
                st["t_open"] = time.perf_counter()
                return
            if st["t_open"] is None:
                return
            now = time.perf_counter()
            if st["trace"] == "running" and \
                    now - st["t_open"] >= float(traffic["trace_seconds"]):
                fence()
                st["trace"] = ctx.tracer.stop(
                    steps=n - st["n_open"], t_open=st["t_open"])
                now = time.perf_counter()
            if now - st["t_open"] >= ctx.seconds:
                fence()
                st["t_close"] = time.perf_counter()
                st["steps"] = n - st["n_open"]
                st["counters_close"] = dict(mx.profiler.counters())
                feed.stop = True

    if ctx.trace:
        spans.listen_to(mx.profiler)
    # The weights come from the seed, not from an initializer: bind and
    # hand them over as a user who loads a checkpoint does, and drop the
    # benchmark's copy before the optimizer's state is made, so that no
    # second model is on the chip when the step's program loads. fit then
    # finds the module bound and initialised and goes its normal way.
    wrap = mx.nd.NDArray
    mod.bind(data_shapes=feed.provide_data, label_shapes=feed.provide_label,
             for_training=True)
    mod.init_params(arg_params={k: wrap(v) for k, v in arg0.items()},
                    aux_params={k: wrap(v) for k, v in aux0.items()})
    jax.block_until_ready([mod._exec.arg_dict[k].data for k in param_names])
    del arg0, aux0
    try:
        with spans.span("bench.fit"):
            mod.fit(feed, num_epoch=1, eval_metric=traffic["eval_metric"],
                    kvstore=traffic["kvstore"], optimizer=opt["name"],
                    optimizer_params=optim.mx_params(opt),
                    batch_end_callback=on_batch)
    finally:
        spans.listen_to(None)
        if st["trace"] == "running":
            ctx.tracer.stop(steps=0, t_open=st["t_open"])
    if st["t_close"] is None:
        raise RuntimeError("fit returned before the window closed")

    peak = device.memory_peak_bytes(log)
    units_per_step = generator.units_per_batch(traffic, rows)
    compiled = {k: st["counters_close"].get(k, 0)
                - st["counters_open"].get(k, 0)
                for k in ("obs_compile_count", "loop_recompile")}
    program = {
        "losses": [float(v) for v in jax.device_get(st["losses"])],
        "grad": {k: abs(st["grad_factor"]) * float(v)
                 for k, v in jax.device_get(st["grad"]).items()},
        "change": {k: float(v)
                   for k, v in jax.device_get(st["change"]).items()}}
    kept = feed.kept
    log("window: %d steps in %.3f s; compiled inside: %r"
        % (st["steps"], st["t_close"] - st["t_open"], compiled))

    # free the program's state, and the memory its loaded step holds
    # reserved, before the reference takes the chip
    del mod, feed, loss_fn
    st["losses"] = st["grad"] = st["change"] = None
    gc.collect()
    jax.clear_caches()

    t_ref = time.perf_counter()
    reference = reference_steps(cell, ctx.seed, kept, rows)
    log("reference: %d steps in %.1f s" % (len(kept),
                                           time.perf_counter() - t_ref))
    compared = compare(program, reference, cell.limits,
                       traffic.get("leaves_compared"))

    window = {"t_open": st["t_open"], "t_close": st["t_close"],
              "units": st["steps"] * units_per_step, "steps": st["steps"],
              "units_per_step": units_per_step, "unit": builder.unit(),
              "compiled_inside": compiled, "chips": chips, "rows": rows}
    failed = 0 if not any(compiled.values()) else st["steps"]
    return {"attempted": st["steps"], "failed": failed, "window": window,
            "compared": compared, "memory_peak_bytes": int(peak),
            "spans": spans, "counters": st["counters_close"],
            "traced": st["trace"] if isinstance(st["trace"], dict) else None}


def reference_steps(cell, seed, batches, rows, precision="highest",
                    fault=None):
    """The plain reference through the same first steps: per-step loss,
    per-leaf norm of the first gradient as the optimizer gets it, and
    per-leaf norm of the parameters' change. ``fault`` plants one of the
    faults a training cell can have in the reference's place
    (``half_batch``, ``state_unchanged``)."""
    import jax
    import jax.numpy as jnp

    cfg, traffic = cell.config, cell.traffic
    builder = spec.load_module("builders", cfg["builder"])
    ref = spec.load_module("references", cfg["reference"])
    optim = spec.load_module("references", "optim")
    opt = cfg["assumed"]["optimizer"]
    specs = builder.leaf_specs(cfg)
    params = leaves.make(specs, seed)
    aux = leaves.make(builder.aux_specs(cfg), seed)

    def parts(name):
        return builder.parts(cfg, name)
    block_grad = ref.make_block_grad(cfg, precision)
    block = int(traffic.get("reference_block_rows", 1))
    rescale = 1.0 / rows            # Module.init_optimizer's default
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    state = optim.init_state(opt, params)
    losses, grad_norms = [], {}
    for t, (x, y) in enumerate(batches, start=1):
        x = generator_ids(x)
        y = generator_ids(y)
        # part of the batch left out, the mean taken over the rest: the
        # rows kept stand in for the rows dropped
        if fault == "half_batch":
            keep = x.shape[0] // 2
            x = np.concatenate([x[:keep]] * 2)
            y = np.concatenate([y[:keep]] * 2)
        acc, total = zeros(params), 0.0
        for r in range(0, x.shape[0], block):
            val, acc, aux = block_grad(params, aux, acc, x[r:r + block],
                                       y[r:r + block])
            total += float(val)
        losses.append(total / ref.loss_units(cfg, x))
        units = ref.grad_units(cfg, x)
        # leaf by leaf, so that no third copy of the model is ever held
        for k in sorted(params):
            g = optim.effective_grad(
                params[k], acc.pop(k) / units, rescale,
                opt["wd"] if leaves.weight_decayed(k) else 0.0)
            if t == 1:
                grad_norms.update(leaves.norms({k: g}, parts))
            if fault != "state_unchanged":
                params[k] = optim.update_leaf(opt, k, params[k], g, state, t)
            del g
    grad_norms = jax.device_get(grad_norms)
    change = jax.device_get(leaves.change_norms(specs, seed, params,
                                                  parts))
    del params, state, aux
    gc.collect()
    return {"losses": losses,
            "grad": {k: float(v) for k, v in grad_norms.items()},
            "change": {k: float(v) for k, v in change.items()}}


def generator_ids(a):
    """Token ids and labels travel as float32 (``NDArrayIter``'s habit);
    image batches stay as they are."""
    a = np.asarray(a)
    return a.astype(np.int32) if a.ndim == 2 else a


def _leaf_gaps(prog, ref, keep=None):
    """For each leaf the gap between the program's norm and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger; worst first."""
    names = [k for k in sorted(ref) if keep is None or k in keep]
    median = float(np.median([ref[k] for k in names]))
    gaps = [(abs(prog[k] - ref[k]) / max(ref[k], median, 1e-30), k)
            for k in names]
    return sorted(gaps, reverse=True)


def compare(program, reference, limits, rule=None):
    """Every number the check reads, and those the cell's limits file
    holds to a limit: [(name, value, limit)]. ``loss_gap`` is the widest
    gap of a step's loss and ``loss1_gap`` the first step's; ``grad_gap``
    and ``change_gap`` are the worst leaf's, the ``_median_`` ones the
    median leaf's. With ``rule`` (the traffic file's ``leaves_compared``)
    the worst leaf is taken over the leaves whose name matches
    ``rule["names"]``, the part of the model where the configuration's
    own precision resolves a single leaf; the median keeps every
    leaf."""
    steps = len(reference["losses"])
    loss_gaps = [abs(p - r) / abs(r) for p, r in
                 zip(program["losses"][:steps], reference["losses"])]
    every = set(reference["grad"])
    steady = every
    if rule:
        steady = {k for k in every if re.search(rule["names"], k)}
    median = float(np.median(list(reference["grad"].values())))
    grad_gaps = _leaf_gaps(program["grad"], reference["grad"])
    # leaves whose gradient is nought to rounding in the reference move
    # under the optimizer by round-off alone: left out of the change
    moving = {k for k, v in reference["grad"].items()
              if v >= 1e-3 * median}
    change_gaps = _leaf_gaps(program["change"], reference["change"], moving)

    def worst(gaps):
        return next(((g, k) for g, k in gaps if k in steady),
                    (float("inf"), None))[0]
    read = {"loss_gap": max(loss_gaps), "loss1_gap": loss_gaps[0],
            "grad_gap": worst(grad_gaps),
            "grad_median_gap": float(np.median([g for g, _k in grad_gaps])),
            "change_gap": worst(change_gaps),
            "change_median_gap": float(np.median(
                [g for g, _k in change_gaps]))}
    unknown = sorted(set(limits) - set(read))
    if unknown:
        raise KeyError("limits for numbers the check does not read: %s"
                       % ", ".join(unknown))
    notes = {"read_not_compared": {k: v for k, v in read.items()
                                   if k not in limits},
             "grad_worst": [(k, round(g, 5)) for g, k in grad_gaps
                            if k in steady][:8],
             "change_worst": [(k, round(g, 5)) for g, k in change_gaps
                              if k in steady][:8],
             "leaves_left_out": sorted(every - moving),
             "leaves_in_the_worst": len(steady),
             "program_losses": program["losses"],
             "reference_losses": reference["losses"],
             "per_leaf": {k: {"grad": [program["grad"][k],
                                       reference["grad"][k]],
                              "change": [program["change"][k],
                                         reference["change"][k]]}
                          for k in sorted(every)}}
    return {"numbers": [(k, read[k], limits[k]) for k in read
                        if k in limits], "notes": notes}


def control(ctx, faults=("half_batch",)):
    """The control and the faults, each put in the program's place and
    held against the reference: [(label, numbers)]. The control is the
    reference computed in the configuration's ``control_precision``, the
    step below its compute dtype. Needs no measured window and nothing of
    the program but its iterator."""
    import mxnet_tpu as mx
    cell = ctx.cell
    cfg, traffic = cell.config, cell.traffic
    generator = spec.load_module("generators", traffic["kind"])
    rows = int(traffic["rows_per_chip"]) * cell.chips
    feed = generator.make_iter(mx, traffic, cfg, ctx.seed, rows, cell.root)
    batches = []
    for _ in range(int(traffic["check_steps"])):
        b = feed.next()
        batches.append((b.data[0].asnumpy(), b.label[0].asnumpy()))
    del feed
    reference = reference_steps(cell, ctx.seed, batches, rows)
    out = []
    cases = [("control", dict(precision=cfg["assumed"]["control_precision"]))]
    cases += [(f, dict(fault=f)) for f in faults]
    for label, how in cases:
        other = reference_steps(cell, ctx.seed, batches, rows, **how)
        compared = compare(other, reference, cell.limits,
                           traffic.get("leaves_compared"))
        out.append((label, compared["numbers"], compared["notes"]))
    return out

