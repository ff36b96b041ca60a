"""``drivers/serve_arch.py`` for a cell whose lead-in holds long sessions
that stay resident through the whole window (``generators/longdoc_chat``).

The server, the weights drawn leaf by leaf, the window, the clocks, the
tracer's stretch and the window's records are ``serve_arch``'s and
``serve``'s, unchanged. What differs is the one thing it must: ``correct``
also follows requests that did not arrive in the window. After the window
the streamed reference follows ``check_requests`` finished window turns
(the longest among them) and ``long.check_sessions`` long sessions (the
longest, and further ones by the seed), each over its prompt and the
first ``long.check_tokens`` tokens served; every row is padded to its own
group's length, so that two programs a kind of layer serve every run.

Three numbers are held to limits. ``mean_gap`` (``serve_arch``'s number)
over all positions followed; each group's own mean is in the notes. At
published widths it cannot see the selection (a sparse layer's output is a
mean over 4096 values, so other blocks read flip no token), so the family
carries out of the timed decode steps what each sparse layer selected and
one number a head of what it then read (``SparseLinearDecoder.followed``),
and on the followed long sessions' decode steps the reference, at its own
layer inputs, says: ``selection_miss``, the share of the block numbers the
program selected that the reference did not select; ``attend_gap``, the
distance between the heads' means the program read and those the reference
reads out of the blocks the program named, over the size of the latter
(a kernel that returns zeros reads 1, one that reads other blocks than
those named about 1). A run in which a long session failed, or was no
longer resident when the window closed, counts every request as failed:
the step the window timed was not the cell's.
"""
import gc
import time

import numpy as np

from benchmarks.drivers import serve, serve_arch
from benchmarks.lib import device, spec
from benchmarks.lib.spans import SpanLog


def long_sessions(cell, offered, family):
    """The lead-in's long sessions as records: ``ok`` if none failed and
    each served at least the tokens that are followed; ``seen`` what the
    family kept of its first decode steps."""
    traffic = cell.traffic
    follow = int(traffic["long"]["check_tokens"])
    t_open, t_close = offered["t_open"], offered["t_close"]
    out = []
    for r in offered["everything"]:
        if r.kind != "lead_in" or len(r.prompt) < int(traffic["long"]["min"]):
            continue
        tokens = list(r.handle.tokens_so_far()) if r.handle else []
        out.append({
            "ok": r.error is None and r.handle.exception is None
            and len(tokens) >= follow,
            "prompt": r.prompt, "tokens": tokens[:follow],
            "served": len(tokens),
            "seen": family.followed(r.prompt),
            "first_token_before_open": bool(r.times) and r.times[0] < t_open,
            "resident_at_close": sum(1 for t in r.times if t < t_close)
            < r.answer and bool(r.times) and r.times[-1] >= t_close - 1.0})
    return out


def followed_rows(cell, seed, records, longs):
    """[(prompt, tokens, padded length, padded answer, seen)]: the window's
    turns, then the long sessions with what the program kept of their
    decode steps."""
    traffic = cell.traffic
    step = int(traffic["reference_pad"])
    pad = serve_arch._pad_to
    rows = []
    length = pad(int(traffic["prompt"]["max"])
                 + int(traffic["answer"]["max"]), step)
    most = pad(int(traffic["answer"]["max"]), step)
    for i in serve.sample(records, seed, int(traffic["check_requests"])):
        rows.append((records[i]["prompt"], records[i]["tokens"], length,
                     most, None))
    follow = int(traffic["long"]["check_tokens"])
    length, most = pad(int(traffic["long"]["max"]) + follow, step), \
        pad(follow, step)
    for i in serve.sample(longs, seed, int(traffic["long"]["check_sessions"])):
        rows.append((longs[i]["prompt"], longs[i]["tokens"], length, most,
                     longs[i].get("seen")))
    return rows


def named_blocks(row):
    """``(at (n,), blocks (n, sparse layers, KV, K))`` of the decode steps
    that served a long row's followed tokens after the first, as the
    program kept them; None where it kept too few."""
    prompt, tokens, _length, _most, seen = row
    n = len(tokens) - 1
    if seen is None or len(seen["pos"]) < n or not np.array_equal(
            seen["pos"][:n], len(prompt) + np.arange(n)):
        return None
    return seen["pos"][:n], seen["blocks"][:n]


def unselected(cell, at, like):
    """The blocks a program that left the scores out would name at the
    positions ``at``: the forced ones and the lowest-numbered others
    (``tests/benchmark/faulty_sala.py``'s ``no_selection``); shaped as
    ``like``."""
    sc = cell.config["assumed"]["sparse_config"]
    out = np.full(like.shape, -1, np.int32)
    for i, t in enumerate(at):
        own = int(t) // sc["block_size"]
        b = np.arange(own + 1)
        forced = (b < sc["init_blocks"]) \
            | (b > own - sc["window_size"] // sc["block_size"])
        order = np.concatenate([b[forced], b[~forced]])[:like.shape[-1]]
        out[i, :, :, :len(order)] = order
    return out


def reference_logits(cell, seed, rows, precision, log=None, named=None):
    """For each row the reference's logits at every served position,
    streamed layer by layer: a layer's leaves are drawn once and every
    row is taken through it. ``named[i]`` is None or ``(at, [blocks, ...])``
    (``named_blocks``): then row ``i`` is probed at every sparse layer, and
    beside the logits come, for each ``blocks`` and sparse layer, the
    reference's own selection at ``at`` and what it reads out of the blocks
    named (``references/<name>.sparse_probe``)."""
    import jax
    import jax.numpy as jnp
    t_last = [time.perf_counter()]

    def lap(what, fence=None):
        if log is None:
            return
        if fence is not None:
            jax.block_until_ready(fence)
        now = time.perf_counter()
        log("reference (%s) %s: %.1f s" % (precision, what, now - t_last[0]))
        t_last[0] = now

    cfg = cell.config
    builder = spec.load_module("builders", cfg["builder"])
    ref = spec.load_module("references", cfg["reference"])
    specs = builder.leaf_specs(cfg)
    leaf = serve_arch._leaf_drawer(specs, seed, cfg["assumed"]["param_dtype"],
                                   back_to="float32")
    seqs, ats = [], []
    for prompt, tokens, length, most, _seen in rows:
        n = len(prompt) + len(tokens) - 1
        seq = np.zeros((length,), np.int32)
        seq[:len(prompt)] = prompt
        seq[len(prompt):n] = tokens[:-1]
        seqs.append(seq)
        # the token served at step j was read off position len(prompt)-1+j
        ats.append(np.minimum(len(prompt) - 1 + np.arange(most), n - 1))
    table = leaf("tok_embed_weight")
    xs = [ref.embed(cfg, table, jnp.asarray(s)) for s in seqs]
    del table
    programs = {}
    named = named or [None] * len(rows)
    probes = [None if given is None else [[] for _b in given[1]]
              for given in named]
    probe = ref.make_probe(cfg, precision) if any(
        g is not None for g in named) else None
    si = 0
    for i, kind in enumerate(builder.layer_kinds(cfg)):
        pre = "layer%d_" % i
        names = [n[len(pre):] for n in specs if n.startswith(pre)]
        if kind not in programs:
            programs[kind] = ref.make_halves(cfg, kind, precision)
        attend, feed = programs[kind]
        p = {n: leaf(pre + n) for n in names if ref.attention_leaf(n)}
        if kind == ref.SPARSE:
            for x, given, got in zip(xs, named, probes):
                for blocks, layers in zip(*((given[1], got) if given
                                            else ((), ()))):
                    layers.append(tuple(np.asarray(a) for a in probe(
                        p, x, jnp.asarray(given[0], jnp.int32),
                        jnp.asarray(blocks[:, si]))))
            si += 1
        xs = [attend(p, x, jnp.arange(x.shape[0])) for x in xs]
        p = {n: leaf(pre + n) for n in names if not ref.attention_leaf(n)}
        xs = [feed(p, x) for x in xs]
        lap("layer %d" % i, xs)
        del p
    head = {n: leaf(n) for n in ("final_ln_gamma", "lm_head_weight")}
    logits = ref.make_logits(cfg, precision)
    out = [np.asarray(logits(head, x[jnp.asarray(at)]))[:len(row[1])]
           for x, at, row in zip(xs, ats, rows)]
    lap("head")
    del xs, head, programs, logits, probe
    gc.collect()
    jax.clear_caches()
    return out, probes


def _mask(blocks, n_blocks):
    """``(n, KV, K)`` block numbers, -1 for none -> ``(n, KV, n_blocks)``
    bool."""
    return (blocks[..., None] == np.arange(n_blocks)).any(axis=-2)


def selection_numbers(truth, judged):
    """``(selection_miss, attend_gap, notes)``. ``truth[i]`` and
    ``judged[i]`` are, for a probed row, a ``(selected (n, KV, n_blocks)
    bool, attended (n, H), ...)`` a sparse layer, and None for a row that
    is not probed: the share of the judged selections that ``truth`` did
    not select, and the distance between the two ``attended`` over the size
    of ``truth``'s. In the notes, layer by layer, ``moved_by_selection``:
    how far what ``truth`` reads out of its own selection lies from what
    it reads out of the blocks named."""
    named = agreed = 0
    num = den = 0.0
    layers = []
    for want, got in zip(truth, judged):
        if want is None or got is None:
            continue
        for li, ((chosen, attended, own), (mine, read)) in enumerate(
                zip(want, (g[:2] for g in got))):
            named += int(mine.sum())
            agreed += int((mine & chosen).sum())
            num += float(np.abs(read - attended).sum())
            den += float(np.abs(attended).sum())
            layers.append({"layer": li, "selected": int(mine.sum()),
                           "also_the_references": int((mine & chosen).sum()),
                           "attend_gap": float(np.abs(read - attended).sum()
                                               / np.abs(attended).sum()),
                           "moved_by_selection": float(
                               np.abs(own - attended).sum()
                               / np.abs(own).sum())})
    if not named or not den:
        return float("inf"), float("inf"), {"selection_layers": layers}
    return 1.0 - agreed / named, num / den, {"selection_layers": layers}


def _of_program(rows, named, probes):
    """``selection_numbers``' two sides for the program's own record: the
    reference's probe under the first blocks named, and what the program
    kept."""
    truth, judged = [], []
    for row, given, probe in zip(rows, named, probes):
        if given is None:
            truth.append(None)
            judged.append(None)
            continue
        n = len(given[0])
        truth.append(probe[0])
        judged.append([(_mask(given[1][0][:, li], chosen.shape[-1]),
                        row[4]["attended"][:n, li])
                       for li, (chosen, _a, _o) in enumerate(probe[0])])
    return truth, judged


def _compared(cell, rows, zs, judged, n_window, truth, selected):
    pairs = [(r[0], r[1]) for r in rows]
    out = serve_arch._compared(cell, pairs, zs, judged)
    miss, gap, notes = selection_numbers(truth, selected)
    if sum(t is not None for t in truth) < len(rows) - n_window:
        # a followed long session whose decode steps the program did not
        # keep is not half a comparison
        miss = gap = float("inf")
    out["numbers"] += [
        ("selection_miss", miss, cell.limits["selection_miss"]),
        ("attend_gap", gap, cell.limits["attend_gap"])]
    out["notes"].update(notes)
    means = out["notes"]["row_mean_gaps"]
    out["notes"]["window_rows"] = n_window
    out["notes"]["window_mean_gap"] = float(np.mean(means[:n_window])) \
        if n_window else None
    out["notes"]["long_mean_gap"] = float(np.mean(means[n_window:])) \
        if len(means) > n_window else None
    return out


def _named(cell, rows, more=()):
    """``reference_logits``' ``named``: each long row's own blocks, then
    what each of ``more`` makes of them."""
    out = []
    for row in rows:
        given = named_blocks(row)
        out.append(given and (given[0], [given[1]] + [
            f(cell, given[0], given[1]) for f in more]))
    return out


def check(cell, seed, records, longs, log=None):
    rows = followed_rows(cell, seed, records, longs)
    n_window = min(int(cell.traffic["check_requests"]),
                   sum(1 for r in records if r["ok"]))
    named = _named(cell, rows)
    zs, probes = reference_logits(cell, seed, rows, "highest", log, named) \
        if rows else ([], [])
    return _compared(cell, rows, zs, [r[1] for r in rows], n_window,
                     *_of_program(rows, named, probes))


def _offer(ctx, spans):
    cell = ctx.cell
    generator = spec.load_module("generators", cell.traffic["kind"])
    plan = generator.plan(cell.traffic,
                          serve_arch.held_vocabulary(cell.config),
                          ctx.seed, ctx.seconds)
    session = serve_arch.Session(ctx, spans)
    offered = session.offer(plan, ctx.seconds)
    return session, offered


def run(ctx):
    import mxnet_tpu as mx

    cell, log = ctx.cell, ctx.log
    cfg, traffic = cell.config, cell.traffic
    spans = SpanLog(enabled=ctx.trace)
    session, offered = _offer(ctx, spans)
    t_open, t_close = offered["t_open"], offered["t_close"]
    peak = device.memory_peak_bytes(log)
    counters = dict(mx.profiler.counters())
    name = session.srv.name
    family = session.srv.engine.family
    steps_seen = session.steps_seen
    session.close()

    records = serve.records_of(offered, cfg["vocab_held"])
    longs = long_sessions(cell, offered, family)
    del family
    failed = sum(1 for r in records if not r["ok"])
    everything = offered["everything"]
    token_times = sorted(t - t_open for r in everything for t in r.times)
    in_window = sum(1 for t in token_times if 0 <= t < ctx.seconds)
    steps = offered["decode_steps"]
    resident = sum(1 for r in longs if r["resident_at_close"])
    ready = sum(1 for r in longs if r["first_token_before_open"])
    log("window: %d requests, %d failed, %d never finished, %d tokens in "
        "%d decode steps (%.3f ms a step, prefills between them counted); "
        "compiled inside: %d; long sessions: %d, decoding at open %d, "
        "resident at close %d, failed %d"
        % (len(records), failed, offered["never"], in_window, steps,
           1e3 * ctx.seconds / max(steps, 1), offered["compiled_inside"],
           len(longs), ready, resident,
           sum(1 for r in longs if not r["ok"])))
    firsts = [r.times[0] for r in everything
              if r.kind == "lead_in" and r.times]
    log("lead-in: %d requests, the last first token %.1f s before the "
        "window opened" % (len(firsts), t_open - max(firsts, default=t_open)))
    held = all(r["ok"] for r in longs) and (ctx.rehearse or (
        resident == len(longs) == int(traffic["long"]["sessions"])
        and ready == len(longs)))

    # free the program's state before the reference takes the chip
    del session
    gc.collect()
    t_ref = time.perf_counter()
    compared = check(cell, ctx.seed, records, longs, log=log)
    compared["notes"].update(long_sessions=len(longs),
                             long_decoding_at_open=ready,
                             long_resident_at_close=resident)
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
    if offered["compiled_inside"] or not held:
        failed = len(records)
    traced = offered["traced"]
    if traced:
        traced["t_start"] -= t_open
        traced["t_stop"] -= t_open
    return {"attempted": len(records), "failed": failed, "window": window,
            "compared": compared, "memory_peak_bytes": int(peak),
            "spans": spans, "counters": counters, "server_name": name,
            "traced": traced}


def control(ctx):
    """``serve_arch.control`` over this driver's rows. ``program``: what
    the timed path served, selected and read. ``stated`` and ``control``:
    the reference at the stated precision and at the one below it, its own
    tokens and selections judged against the reference's, and what it
    reads out of the blocks the program named. ``unselected``: the
    program's tokens, but for its selection the forced blocks and the
    lowest-numbered others, and for what it read what the reference reads
    out of those: a program that leaves the scores out, or whose kernel
    reads other blocks than those named."""
    from benchmarks.lib import stats
    from benchmarks.readers import request_tail
    cell = ctx.cell
    cfg = cell.config
    session, offered = _offer(ctx, SpanLog(False))
    family = session.srv.engine.family
    session.close()
    records = serve.records_of(offered, cfg["vocab_held"])
    longs = long_sessions(cell, offered, family)
    del family
    window = {"requests": len(records),
              "failed": sum(1 for r in records if not r["ok"]),
              "compiled_inside": offered["compiled_inside"],
              "long_resident_at_close": sum(
                  1 for r in longs if r["resident_at_close"])}
    tail = stats.tail(request_tail.per_request(records, "tpot"), 90,
                      len(records))
    window["tpot_p90_ms"] = None if tail is None else 1e3 * tail
    del session, offered
    gc.collect()
    rows = followed_rows(cell, ctx.seed, records, longs)
    n_window = min(int(cell.traffic["check_requests"]),
                   sum(1 for r in records if r["ok"]))
    named = _named(cell, rows, more=(unselected,))
    zs, probes = reference_logits(cell, ctx.seed, rows, "highest", ctx.log,
                                  named)
    served = [r[1] for r in rows]
    truth, kept = _of_program(rows, named, probes)
    cases = [("program", served, truth, kept, window)]
    for label, key in (("stated", "stated_precision"),
                       ("control", "control_precision")):
        lower, low = reference_logits(cell, ctx.seed, rows,
                                      cfg["assumed"][key], ctx.log, named)
        cases.append((label, [z.argmax(axis=-1) for z in lower], truth,
                      [p and p[0] for p in low], {}))
        del lower
    # the blocks a program without scores names, and what lies in them
    cases.append(("unselected", served, truth, [
        given and [(_mask(given[1][1][:, li], chosen.shape[-1]), read)
                   for li, (chosen, read, _o) in enumerate(probe[1])]
        for given, probe in zip(named, probes)], {}))
    out = []
    for label, judged, truth, selected, more in cases:
        case = _compared(cell, rows, zs, judged, n_window, truth, selected)
        out.append((label, case["numbers"],
                    {"read_not_compared": dict(case["notes"], **more)}))
    return out
