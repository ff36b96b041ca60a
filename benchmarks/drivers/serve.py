"""Offer open-loop traffic to ``GenerativeServer`` for a window of seconds.

The load comes from this one thread: it sleeps until each request is due
and calls ``submit_generate``; the server's scheduler thread calls
``on_token``, which stamps every token on the client's clock. A request
is timed from when it was due, not from when it was sent. Requests that
arrive in the window are followed until they finish; the lead-in before
it and the lead-out after it keep the server in steady state and are not
counted.
"""
import gc
import threading
import time

import numpy as np

from benchmarks.lib import device, leaves, spec
from benchmarks.lib.spans import SpanLog


class _Request:
    __slots__ = ("kind", "due", "sent", "prompt", "answer", "times",
                 "handle", "error")

    def __init__(self, kind, item):
        self.kind = kind
        self.due = item.get("due")
        self.prompt = item["prompt"]
        self.answer = item["answer"]
        self.sent = None
        self.times = []
        self.handle = None
        self.error = None


def _submit(srv, req, spans, probe):
    """Send one request. Every token is stamped on the client's clock in
    the server's callback; a traced run also marks it in the profiler's
    trace, first tokens apart from later ones, so that the reduction can
    tell a prefill from a decode step."""
    times = req.times

    def on_token(_tok):
        now = time.perf_counter()
        with spans.span("bench.token.next" if times
                        else "bench.token.first"):
            times.append(now)
            probe(now)
    req.sent = time.perf_counter()
    try:
        with spans.span("bench.submit_generate"):
            req.handle = srv.submit_generate(
                req.prompt, max_new_tokens=req.answer, on_token=on_token)
    except Exception as e:                  # refused: counts as failed
        req.error = e


def _sleep_until(t):
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


class Session:
    """One server, warmed for the mix's own shapes, and the load offered
    to it. ``run`` opens one and offers one window; the rate sweep keeps
    it open over several."""

    def __init__(self, ctx, spans):
        import mxnet_tpu as mx
        self.ctx, self.spans, self._mx = ctx, spans, mx
        cfg, traffic = ctx.cell.config, ctx.cell.traffic
        builder = spec.load_module("builders", cfg["builder"])
        weights = leaves.make(builder.leaf_specs(cfg), ctx.seed)
        self.srv = srv = mx.serve.GenerativeServer(
            weights, n_heads=cfg["num_attention_heads"],
            max_sequences=int(traffic["max_sequences"]),
            seq_buckets=list(traffic["seq_buckets"]),
            prefill_chunk=int(traffic["prefill_chunk"]))
        del weights
        self.steps_seen = {}
        rng = np.random.Generator(np.random.PCG64(int(ctx.seed) + 1))
        for n in traffic["warm_prompts"]:
            warm = _Request("warm", {"prompt": rng.integers(
                0, cfg["vocab_size"], int(n)).astype(np.int32), "answer": 2})
            _submit(srv, warm, spans, self.probe)
            if warm.error is not None:
                raise warm.error
            warm.handle.result(timeout=1200)
        ctx.log("warm: %d programs" % srv.stats()["compiles"])

    def counter(self, what):
        return self._mx.profiler.get_counter(self.srv.name + "_" + what)

    def probe(self, now):
        # one sample of the program's gauge for each decode step
        step = self.counter("decode_steps")
        if step not in self.steps_seen:
            self.steps_seen[step] = (now, self._mx.profiler.get_gauge(
                self.srv.name + "_active_sequences"))

    def offer(self, plan, seconds):
        """Lead-in, the window's arrivals at their due times, and the
        lead-out until the window's requests have finished."""
        ctx, srv, spans = self.ctx, self.srv, self.spans
        traffic = ctx.cell.traffic
        everything = []
        for item in plan["lead_in"]:
            req = _Request("lead_in", item)
            _submit(srv, req, spans, self.probe)
            everything.append(req)
        t_open = time.perf_counter() + float(traffic["lead_in"]["seconds"])
        _sleep_until(t_open)
        at_open = (srv.stats()["compiles"], self.counter("decode_steps"))

        traced = {}
        tracer_thread = None
        if ctx.trace:
            def trace_stretch():
                _sleep_until(t_open + float(traffic["trace_after_seconds"]))
                c0 = self.counter("decode_steps")
                ctx.tracer.start()
                _sleep_until(time.perf_counter()
                             + float(traffic["trace_seconds"]))
                c1 = self.counter("decode_steps")
                traced.update(ctx.tracer.stop(decode_steps=c1 - c0))
            tracer_thread = threading.Thread(target=trace_stretch,
                                             name="bench.tracer")
            tracer_thread.start()

        counted = []
        for item in plan["window"]:
            req = _Request("window", item)
            _sleep_until(t_open + req.due)
            _submit(srv, req, spans, self.probe)
            counted.append(req)
            everything.append(req)
        t_close = t_open + seconds
        _sleep_until(t_close)
        at_close = (srv.stats()["compiles"], self.counter("decode_steps"))

        def pending():
            return [r for r in counted
                    if r.error is None and not r.handle.done()]

        deadline = t_close + float(traffic["follow_seconds"])
        for item in plan["lead_out"]:
            due = t_open + item["due"]
            while pending() and time.perf_counter() < min(due, deadline):
                time.sleep(0.02)
            if not pending() or time.perf_counter() > deadline:
                break
            req = _Request("lead_out", item)
            _submit(srv, req, spans, self.probe)
            everything.append(req)
        if tracer_thread is not None:
            tracer_thread.join()
        for r in everything:
            if r.kind != "window" and r.handle is not None:
                r.handle.cancel()
        return {"t_open": t_open, "t_close": t_close, "counted": counted,
                "everything": everything, "never": len(pending()),
                "compiled_inside": at_close[0] - at_open[0],
                "decode_steps": at_close[1] - at_open[1],
                "traced": traced or None}

    def close(self):
        self.srv.close(drain=False, timeout=30)
        self.srv = None


def records_of(offered, vocab):
    """One record for each request that arrived in the window."""
    t_open = offered["t_open"]
    out = []
    for r in offered["counted"]:
        tokens = list(r.handle.tokens_so_far()) if r.handle else []
        ok = (r.error is None and r.handle.done()
              and r.handle.exception is None
              and len(r.times) == r.answer and len(tokens) == r.answer
              and all(0 <= t < vocab for t in tokens))
        out.append({"ok": ok, "due": r.due, "lag": r.sent - (t_open + r.due),
                    "prompt": r.prompt, "tokens": tokens,
                    "times": [t - t_open for t in r.times]})
    return out


def run(ctx):
    import mxnet_tpu as mx

    cell, log = ctx.cell, ctx.log
    cfg, traffic = cell.config, cell.traffic
    generator = spec.load_module("generators", traffic["kind"])
    spans = SpanLog(enabled=ctx.trace)
    plan = generator.plan(traffic, cfg, ctx.seed, ctx.seconds)
    session = Session(ctx, spans)
    offered = session.offer(plan, ctx.seconds)
    t_open, t_close = offered["t_open"], offered["t_close"]
    peak = device.memory_peak_bytes(log)
    counters = dict(mx.profiler.counters())
    steps_seen = session.steps_seen
    session.close()

    records = records_of(offered, cfg["vocab_size"])
    failed = sum(1 for r in records if not r["ok"])
    everything = offered["everything"]
    token_times = sorted(t - t_open for r in everything for t in r.times)
    in_window = sum(1 for t in token_times if 0 <= t < ctx.seconds)
    log("window: %d requests, %d failed, %d never finished, %d tokens; "
        "compiled inside: %d" % (len(records), failed, offered["never"],
                                 in_window, offered["compiled_inside"]))

    # free the program's state before the reference takes the chip
    del session
    gc.collect()
    t_ref = time.perf_counter()
    compared = check(cell, ctx.seed, records)
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
              "decode_steps": offered["decode_steps"],
              "compiled_inside": offered["compiled_inside"]}
    if offered["compiled_inside"]:
        failed = len(records)
    traced = offered["traced"]
    if traced:
        traced["t_start"] -= t_open
        traced["t_stop"] -= t_open
    return {"attempted": len(records), "failed": failed, "window": window,
            "compared": compared, "memory_peak_bytes": int(peak),
            "spans": spans, "counters": counters, "traced": traced}


def sample(records, seed, how_many):
    """The requests the reference follows: the longest that finished and
    further ones drawn from the seed."""
    done = [i for i, r in enumerate(records) if r["ok"]]
    if not done:
        return []
    longest = max(done, key=lambda i: len(records[i]["prompt"])
                  + len(records[i]["tokens"]))
    rest = [i for i in done if i != longest]
    rng = np.random.Generator(np.random.PCG64(int(seed) + 2))
    rng.shuffle(rest)
    return [longest] + rest[:max(0, how_many - 1)]


def reference_gaps(cell, seed, rows, precision="highest", control=None):
    """For each (prompt, tokens) row: the gap, at every served position,
    by which the token's reference logit lies below the reference's best.
    With ``control`` a precision, the token judged is not the served one
    but the one that precision puts first."""
    import jax.numpy as jnp

    cfg, traffic = cell.config, cell.traffic
    builder = spec.load_module("builders", cfg["builder"])
    ref = spec.load_module("references", cfg["reference"])
    params = leaves.make(builder.leaf_specs(cfg), seed)
    longest = traffic["prompt"]["max"] + traffic["answer"]["max"]
    pad_to = -(-longest // 128) * 128
    most = int(traffic["answer"]["max"])
    forward = ref.make_rows_forward(cfg, precision)
    lower = ref.make_rows_forward(cfg, control) if control else None
    gaps = []
    for prompt, tokens in rows:
        seq = np.zeros((pad_to,), np.int32)
        seq[:len(prompt)] = prompt
        seq[len(prompt):len(prompt) + len(tokens) - 1] = tokens[:-1]
        # the token served at step j was read off position len(prompt)-1+j
        at = np.zeros((most,), np.int32)
        at[:len(tokens)] = len(prompt) - 1 + np.arange(len(tokens))
        z = forward(params, jnp.asarray(seq), jnp.asarray(at))
        judged = np.asarray(tokens, np.int32)
        if lower is not None:
            judged = np.asarray(jnp.argmax(
                lower(params, jnp.asarray(seq), jnp.asarray(at)),
                axis=-1))[:len(tokens)]
        z = np.asarray(z)[:len(tokens)]
        gaps.append(z.max(axis=-1) - z[np.arange(len(tokens)), judged])
    del params
    gc.collect()
    return gaps


def check(cell, seed, records, control=None):
    picks = sample(records, seed, int(cell.traffic["check_requests"]))
    rows = [(records[i]["prompt"], records[i]["tokens"]) for i in picks]
    gaps = reference_gaps(cell, seed, rows, control=control)
    widest = max((float(g.max()) for g in gaps), default=float("inf"))
    tokens = int(sum(len(g) for g in gaps))
    return {"numbers": [("logit_gap", widest, cell.limits["logit_gap"])],
            "notes": {"requests_compared": len(rows),
                      "tokens_compared": tokens,
                      "mean_gap": float(np.mean(np.concatenate(gaps)))
                      if gaps else None,
                      "tokens_off_reference_best": int(sum(
                          int((g > 0).sum()) for g in gaps))}}


def control(ctx):
    """A short window through the program, then the control on the same
    prompts and served tokens: at each served position the gap of the
    token that the configuration's ``control_precision`` puts first.
    [(label, numbers)], the program's own reading first."""
    cell = ctx.cell
    generator = spec.load_module("generators", cell.traffic["kind"])
    plan = generator.plan(cell.traffic, cell.config, ctx.seed, ctx.seconds)
    session = Session(ctx, SpanLog(False))
    offered = session.offer(plan, ctx.seconds)
    session.close()
    records = records_of(offered, cell.config["vocab_size"])
    del session, offered
    gc.collect()
    precision = cell.config["assumed"]["control_precision"]
    return [("program", check(cell, ctx.seed, records)["numbers"], {}),
            ("control", check(cell, ctx.seed, records,
                              control=precision)["numbers"], {})]

