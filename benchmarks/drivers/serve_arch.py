"""Offer open-loop traffic to a ``GenerativeServer`` that is told its
architecture (``arch=``), for a window of seconds.

The window, the clocks, the lead-in, the lead-out, the tracer's stretch, a
request's record and the sample the reference follows are
``drivers/serve.py``'s (its ``Session.offer``, ``_Request``, ``_submit``,
``records_of`` and ``sample`` run here unchanged). What differs is what a
model too large for that driver's way forces:

* the weights are drawn leaf by leaf, each in one jitted call with the key
  and the index ``lib/leaves.py::_draw`` gives it, and cast to the
  configuration's dtype there: no float32 copy of the model is ever held;
* the server is told the architecture (``builders/<name>.architecture``);
* the vocabulary is the slice the chip holds: the generator and the
  records are handed a copy of the configuration whose ``vocab_size`` is
  ``vocab_held``, so that every id lies in the slice;
* the reference is streamed: layer by layer it draws that layer's leaves
  again (the same values, rounded to the served dtype, held in float32)
  and takes every followed row through it, so that it fits beside
  nothing but itself.

``correct``: after the window the reference follows ``check_requests``
finished turns, the longest among them. One number is held to a limit:
``mean_gap``, the gap by which a served token's reference logit lies below
the reference's best, averaged over every served position followed (one
token in a hundred gone wrong moves it). The widest such gap,
``logit_gap``, is read and not compared: it hangs on one token in
thousands, the reference served at the stated precision reads as wide a
one as the program does (``control``'s case ``stated``), and the float8
control on one seed read under three times the program's widest.
"""
import gc
import time

import numpy as np

from benchmarks.drivers import serve
from benchmarks.lib import device, leaves, spec
from benchmarks.lib.spans import SpanLog


def _leaf_drawer(specs, seed, dtype, back_to=None):
    """name -> the leaf, drawn as ``leaves.make`` would draw it and cast
    to ``dtype`` (then to ``back_to``, for the reference) on the device."""
    import jax
    import jax.numpy as jnp
    order = {n: i for i, n in enumerate(sorted(specs))}
    key = leaves.seed_key(seed)

    def one(key, index, spec):
        w = leaves._draw(key, index, *spec)
        if back_to is None:
            return w.astype(dtype)
        # rounded by reduce_precision: a cast there and back is a pair the
        # compiler may drop, and the reference would hold other values
        info = jnp.finfo(dtype)
        return jax.lax.reduce_precision(w, info.nexp, info.nmant).astype(
            back_to)
    draw = jax.jit(one, static_argnames=("spec",))

    def leaf(name):
        shape, mean, std = specs[name]
        return draw(key, np.int32(order[name]),
                    spec=(tuple(shape), float(mean), float(std)))
    return leaf


def held_vocabulary(cfg):
    """The configuration as the generator and the records see it: ids are
    drawn from, and checked against, the rows of the vocabulary held."""
    return dict(cfg, vocab_size=cfg["vocab_held"])


class Session(serve.Session):
    """One server, told its architecture, warmed for the mix's own shapes,
    and the load offered to it (``offer`` is the parent's)."""

    def __init__(self, ctx, spans):          # noqa: super's builds another
        import mxnet_tpu as mx
        self.ctx, self.spans, self._mx = ctx, spans, mx
        cfg, traffic = ctx.cell.config, ctx.cell.traffic
        builder = spec.load_module("builders", cfg["builder"])
        specs = builder.leaf_specs(cfg)
        leaf = _leaf_drawer(specs, ctx.seed, cfg["assumed"]["param_dtype"])
        weights = {n: leaf(n) for n in sorted(specs)}
        self.srv = srv = mx.serve.GenerativeServer(
            weights, arch=builder.architecture(cfg),
            max_sequences=int(traffic["max_sequences"]),
            seq_buckets=list(traffic["seq_buckets"]),
            prefill_chunk=int(traffic["prefill_chunk"]),
            prefill_tokens=int(traffic["prefill_tokens"]),
            page=int(traffic["page"]))
        del weights
        self.steps_seen = {}
        rng = np.random.Generator(np.random.PCG64(int(ctx.seed) + 1))
        # each warm prompt builds its chunks' programs and, with its
        # second token, the decode program of the bucket past its length
        for n in traffic["warm_prompts"]:
            warm = serve._Request("warm", {"prompt": rng.integers(
                0, cfg["vocab_held"], int(n)).astype(np.int32), "answer": 2})
            serve._submit(srv, warm, spans, self.probe)
            if warm.error is not None:
                raise warm.error
            warm.handle.result(timeout=1800)
        ctx.log("warm: %d programs" % srv.stats()["compiles"])


def run(ctx):
    import mxnet_tpu as mx

    cell, log = ctx.cell, ctx.log
    cfg, traffic = cell.config, cell.traffic
    generator = spec.load_module("generators", traffic["kind"])
    spans = SpanLog(enabled=ctx.trace)
    plan = generator.plan(traffic, held_vocabulary(cfg), ctx.seed,
                          ctx.seconds)
    session = Session(ctx, spans)
    offered = session.offer(plan, ctx.seconds)
    t_open, t_close = offered["t_open"], offered["t_close"]
    peak = device.memory_peak_bytes(log)
    counters = dict(mx.profiler.counters())
    name = session.srv.name
    steps_seen = session.steps_seen
    session.close()

    records = serve.records_of(offered, cfg["vocab_held"])
    failed = sum(1 for r in records if not r["ok"])
    everything = offered["everything"]
    token_times = sorted(t - t_open for r in everything for t in r.times)
    in_window = sum(1 for t in token_times if 0 <= t < ctx.seconds)
    steps = offered["decode_steps"]
    log("window: %d requests, %d failed, %d never finished, %d tokens in "
        "%d decode steps (%.3f ms a step, prefills between them counted); "
        "compiled inside: %d"
        % (len(records), failed, offered["never"], in_window, steps,
           1e3 * ctx.seconds / max(steps, 1), offered["compiled_inside"]))

    # free the program's state before the reference takes the chip
    del session
    gc.collect()
    t_ref = time.perf_counter()
    compared = check(cell, ctx.seed, records, log=log)
    log("reference: %.1f s" % (time.perf_counter() - t_ref))

    window = {"t_open": t_open, "t_close": t_close, "seconds": ctx.seconds,
              "requests": [{k: v for k, v in rec.items()
                            if k not in ("prompt", "tokens")}
                           | {"prompt_len": len(rec["prompt"])}
                           for rec in records],
              "tokens_in_window": in_window,
              "all_requests": [{"prompt_len": len(r.prompt),
                                "times": [t - t_open for t in r.times]}
                               for r in everything],
              "occupancy": [(t - t_open, a)
                            for t, a in steps_seen.values()],
              "max_sequences": int(traffic["max_sequences"]),
              "decode_steps": steps,
              "compiled_inside": offered["compiled_inside"]}
    if offered["compiled_inside"]:
        failed = len(records)
    traced = offered["traced"]
    if traced:
        traced["t_start"] -= t_open
        traced["t_stop"] -= t_open
    return {"attempted": len(records), "failed": failed, "window": window,
            "compared": compared, "memory_peak_bytes": int(peak),
            "spans": spans, "counters": counters, "server_name": name,
            "traced": traced}


def followed_rows(cell, seed, records):
    """[(prompt, served tokens)] of the finished turns that
    ``drivers/serve`` samples: the longest and further ones by the seed."""
    picks = serve.sample(records, seed, int(cell.traffic["check_requests"]))
    return [(records[i]["prompt"], records[i]["tokens"]) for i in picks]


def _pad_to(n, step):
    return -(-n // step) * step


def reference_logits(cell, seed, rows, precision, log=None):
    """For each (prompt, tokens) row the reference's logits at every
    served position, streamed layer by layer."""
    import jax
    import jax.numpy as jnp
    t_last = [time.perf_counter()]

    def lap(what, fence=None):
        if log is None:
            return
        if fence is not None:
            jax.block_until_ready(fence)
        now = time.perf_counter()
        log("reference (%s) %s: %.1f s" % (precision, what,
                                           now - t_last[0]))
        t_last[0] = now

    cfg, traffic = cell.config, cell.traffic
    builder = spec.load_module("builders", cfg["builder"])
    ref = spec.load_module("references", cfg["reference"])
    specs = builder.leaf_specs(cfg)
    leaf = _leaf_drawer(specs, seed, cfg["assumed"]["param_dtype"],
                        back_to="float32")
    step = int(traffic["reference_pad"])
    # the rows share one padded length, the longest the mix can make, so
    # that one program a kind of layer serves them all in every run; what
    # lies after a position does not reach it
    length = _pad_to(int(traffic["prompt"]["max"])
                     + int(traffic["answer"]["max"]), step)
    most = _pad_to(int(traffic["answer"]["max"]), step)
    seqs, ats = [], []
    for prompt, tokens in rows:
        n = len(prompt) + len(tokens) - 1
        seq = np.zeros((length,), np.int32)
        seq[:len(prompt)] = prompt
        seq[len(prompt):n] = tokens[:-1]
        seqs.append(seq)
        # the token served at step j was read off position len(prompt)-1+j;
        # padded (the last position again) to the longest answer, so that
        # the head's program has one shape
        at = len(prompt) - 1 + np.arange(most)
        ats.append(np.minimum(at, n - 1))
    embed = leaf("tok_embed_weight")
    xs = [embed[jnp.asarray(s)] for s in seqs]
    del embed
    pos = jnp.arange(length)
    programs = {}
    for i, mlp_type in enumerate(builder.layer_kinds(cfg)):
        pre = "layer%d_" % i
        names = [n[len(pre):] for n in specs if n.startswith(pre)]
        if mlp_type not in programs:
            programs[mlp_type] = ref.make_halves(cfg, mlp_type, precision)
        attend, feed = programs[mlp_type]
        # each half with its own leaves alone beside the rows
        p = {n: leaf(pre + n) for n in names if ref.attention_leaf(n)}
        xs = [attend(p, x, pos) for x in xs]
        p = {n: leaf(pre + n) for n in names if not ref.attention_leaf(n)}
        xs = [feed(p, x) for x in xs]
        lap("layer %d" % i, xs)
        del p
    head = {n: leaf(n) for n in ("final_ln_gamma", "lm_head_weight")}
    logits = ref.make_logits(cfg, precision)
    out = [np.asarray(logits(head, x[jnp.asarray(at)]))[:len(tokens)]
           for x, at, (_p, tokens) in zip(xs, ats, rows)]
    lap("head")
    del xs, head, programs, logits
    gc.collect()
    # a loaded program keeps its temporaries reserved: unload the layers'
    # before another pass (a control's) or another process's work
    jax.clear_caches()
    return out


def _compared(cell, rows, zs, judged):
    """The numbers held to limits and the notes, from the reference's
    logits ``zs`` and the tokens ``judged`` at each row's positions."""
    gaps = [z.max(axis=-1) - z[np.arange(len(j)), np.asarray(j, np.int64)]
            for z, j in zip(zs, judged)]
    every = np.concatenate(gaps) if gaps else np.zeros((0,))
    widest = float(every.max()) if every.size else float("inf")
    mean = float(every.mean()) if every.size else float("inf")
    return {"numbers": [("mean_gap", mean, cell.limits["mean_gap"])],
            "notes": {"logit_gap": widest,
                      "requests_compared": len(rows),
                      "tokens_compared": int(every.size),
                      "row_lengths": [len(p) + len(t) for p, t in rows],
                      "row_gaps": [float(g.max()) for g in gaps],
                      "row_mean_gaps": [float(g.mean()) for g in gaps],
                      "tokens_off_reference_best": int((every > 0).sum())}}


def check(cell, seed, records, log=None):
    rows = followed_rows(cell, seed, records)
    zs = reference_logits(cell, seed, rows, "highest", log) if rows else []
    return _compared(cell, rows, zs, [tokens for _p, tokens in rows])


def control(ctx):
    """A window through the program, then the reference at lower
    precisions on the same prompts and served tokens: at each served
    position the gap of the token that precision puts first, against the
    same reference logits as the program's own tokens. ``stated`` is the
    reference with its products' operands rounded to the precision the
    configuration states (what rounding alone does to the plain
    equations: the program should read like it), ``control`` the one
    below it (``control_precision``), which has to fail. [(label,
    numbers, notes)], the program's own reading first; its notes hold
    the window's ``tpot_p90_ms`` too, so that a control at the cell's own
    ``--seconds`` is one more reading of it."""
    from benchmarks.lib import stats
    from benchmarks.readers import request_tail
    cell = ctx.cell
    cfg = cell.config
    generator = spec.load_module("generators", cell.traffic["kind"])
    plan = generator.plan(cell.traffic, held_vocabulary(cfg), ctx.seed,
                          ctx.seconds)
    session = Session(ctx, SpanLog(False))
    offered = session.offer(plan, ctx.seconds)
    session.close()
    records = serve.records_of(offered, cfg["vocab_held"])
    window = {"requests": len(records),
              "failed": sum(1 for r in records if not r["ok"]),
              "compiled_inside": offered["compiled_inside"]}
    tail = stats.tail(request_tail.per_request(records, "tpot"), 90,
                      len(records))
    window["tpot_p90_ms"] = None if tail is None else 1e3 * tail
    del session, offered
    gc.collect()
    rows = followed_rows(cell, ctx.seed, records)
    zs = reference_logits(cell, ctx.seed, rows, "highest", ctx.log)
    out = []
    cases = [("program", [tokens for _p, tokens in rows], window)]
    for label, key in (("stated", "stated_precision"),
                       ("control", "control_precision")):
        lower = reference_logits(cell, ctx.seed, rows, cfg["assumed"][key],
                                 ctx.log)
        cases.append((label, [z.argmax(axis=-1) for z in lower], {}))
        del lower
    for label, judged, more in cases:
        case = _compared(cell, rows, zs, judged)
        out.append((label, case["numbers"],
                    {"read_not_compared": dict(case["notes"], **more)}))
    return out
