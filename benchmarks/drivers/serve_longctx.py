"""``drivers/serve_arch.py`` for a cell whose lead-in holds long sessions
that stay resident through the whole window (``generators/longdoc_chat``),
judged on logits alone.

The server, the weights drawn leaf by leaf, the window, the clocks, the
tracer's stretch and the window's records are ``serve_arch``'s and
``serve``'s; the long sessions, the rows the reference follows and the
streamed reference are ``serve_longdoc``'s (``_offer``, ``long_sessions``,
``followed_rows``, ``reference_logits`` with no layer probed), unchanged.
What differs from ``serve_longdoc`` is what ``correct`` holds: the logits,
and nothing the program carries out of its decode steps. After the window
the streamed reference follows ``check_requests`` finished window turns
(the longest among them) and ``long.check_sessions`` long sessions (the
longest, and further ones by the seed), each over its prompt and the
first ``long.check_tokens`` tokens served; every row is padded to its own
group's length, so that two programs a kind of layer serve every run.

One number is held to a limit: ``mean_gap`` (``serve_arch``'s), the gap by
which a served token's reference logit lies below the reference's best,
averaged over every position followed; each group's own mean is in the
notes. A run in which a long session failed, was not decoding when the
window opened or was no longer resident when it closed counts every
request as failed: the step the window timed was not the cell's.
"""
import gc
import time
import types

import numpy as np

from benchmarks.drivers import serve, serve_arch, serve_longdoc
from benchmarks.lib import device
from benchmarks.lib.spans import SpanLog

# this family keeps nothing of its decode steps for the comparison: the
# logits hold it
_NOTHING_KEPT = types.SimpleNamespace(followed=lambda prompt: None)


def reference_logits(cell, seed, rows, precision, log=None):
    """``serve_longdoc.reference_logits`` with no layer probed: the
    reference's logits at every served position of each row."""
    return serve_longdoc.reference_logits(cell, seed, rows, precision,
                                          log)[0]


def _compared(cell, rows, zs, judged, n_window, n_long):
    out = serve_arch._compared(cell, [(r[0], r[1]) for r in rows], zs,
                               judged)
    if sum(1 for r in rows[n_window:]) < n_long:
        # a long session that is not followed is not half a comparison
        out["numbers"] = [(n, float("inf"), lim)
                          for n, _v, lim in out["numbers"]]
    means = out["notes"]["row_mean_gaps"]
    out["notes"].update(
        window_rows=n_window,
        window_mean_gap=float(np.mean(means[:n_window])) if n_window
        else None,
        long_mean_gap=float(np.mean(means[n_window:]))
        if len(means) > n_window else None)
    return out


def _rows(cell, seed, records, longs):
    rows = serve_longdoc.followed_rows(cell, seed, records, longs)
    n_window = min(int(cell.traffic["check_requests"]),
                   sum(1 for r in records if r["ok"]))
    n_long = min(int(cell.traffic["long"]["check_sessions"]), len(longs))
    return rows, n_window, n_long


def check(cell, seed, records, longs, log=None):
    rows, n_window, n_long = _rows(cell, seed, records, longs)
    zs = reference_logits(cell, seed, rows, "highest", log) if rows else []
    return _compared(cell, rows, zs, [r[1] for r in rows], n_window, n_long)


def run(ctx):
    import mxnet_tpu as mx

    cell, log = ctx.cell, ctx.log
    cfg, traffic = cell.config, cell.traffic
    spans = SpanLog(enabled=ctx.trace)
    session, offered = serve_longdoc._offer(ctx, spans)
    t_open, t_close = offered["t_open"], offered["t_close"]
    peak = device.memory_peak_bytes(log)
    counters = dict(mx.profiler.counters())
    name = session.srv.name
    steps_seen = session.steps_seen
    session.close()

    records = serve.records_of(offered, cfg["vocab_held"])
    longs = serve_longdoc.long_sessions(cell, offered, _NOTHING_KEPT)
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
    """``serve_arch.control`` over this driver's rows: ``program``, what
    the timed path served; ``stated`` and ``control``, the reference's own
    tokens at the stated precision and at the one below it, each judged
    against the reference's logits."""
    from benchmarks.lib import stats
    from benchmarks.readers import request_tail
    cell = ctx.cell
    cfg = cell.config
    session, offered = serve_longdoc._offer(ctx, SpanLog(False))
    session.close()
    records = serve.records_of(offered, cfg["vocab_held"])
    longs = serve_longdoc.long_sessions(cell, offered, _NOTHING_KEPT)
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
    rows, n_window, n_long = _rows(cell, ctx.seed, records, longs)
    zs = reference_logits(cell, ctx.seed, rows, "highest", ctx.log)
    cases = [("program", [r[1] for r in rows], window)]
    for label, key in (("stated", "stated_precision"),
                       ("control", "control_precision")):
        lower = reference_logits(cell, ctx.seed, rows, cfg["assumed"][key],
                                 ctx.log)
        cases.append((label, [z.argmax(axis=-1) for z in lower], {}))
        del lower
    out = []
    for label, judged, more in cases:
        case = _compared(cell, rows, zs, judged, n_window, n_long)
        out.append((label, case["numbers"],
                    {"read_not_compared": dict(case["notes"], **more)}))
    return out
