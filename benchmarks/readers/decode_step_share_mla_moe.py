"""The ``sarvam_mla`` decode step against the chip, as
``decode_step_share`` reads the dense decoder's: the work the algorithm
needs for the tokens the traced steps produced (``lib/flops_mla_moe.py``:
weights outside the experts once a step, each expert hit once, each
resident sequence's latent rows once) over the steps' device time, as a
share of peak FLOP/s (``of: mfu``) or of the roofline's least time (``of:
roofline``). The experts hit are the program's counters over the whole
run, scaled to the traced steps. Nothing where the program has no such
counters."""
from benchmarks.lib import flops, flops_mla_moe
from benchmarks.readers import serve_program_ms


def traced_steps(run, params):
    """(device seconds of the whole executions traced, steps by the
    program's counter, those steps' share of all the run's), or None."""
    if run.peaks is None:       # a rehearsal has no chip to hold it against
        return None
    traced = run.result.get("traced")
    secs = serve_program_ms.seconds(run, params)
    if not traced or not secs or not traced.get("decode_steps"):
        return None
    counters = run.result.get("counters") or {}
    all_steps = counters.get("%s_decode_steps"
                             % run.result.get("server_name"))
    if not all_steps:
        return None
    steps = traced["decode_steps"]
    return secs, steps, steps / float(all_steps)


def read(run, params):
    found = traced_steps(run, params)
    if found is None:
        return None
    secs, steps, share = found
    traced = run.result["traced"]
    counters, name = run.result["counters"], run.result["server_name"]
    hit = counters.get("%s_moe_experts_hit" % name)
    sent = counters.get("%s_moe_assignments" % name)
    if hit is None or sent is None:
        return None
    cfg = run.cell.config
    # every token but a request's first came from a decode step; it
    # attended from the prompt and the answer so far, itself included
    lengths = [r["prompt_len"] + j + 1
               for r in run.result["window"]["all_requests"]
               for j, t in enumerate(r["times"])
               if j > 0 and traced["t_start"] <= t < traced["t_stop"]]
    if not lengths:
        return None
    need_f, need_b = flops_mla_moe.decode_steps_cost(
        cfg, lengths, steps, hit * share, sent * share,
        cfg["assumed"]["compute_dtype"])
    # the counter's steps against the whole executions the trace holds
    spent = sum(secs) * steps / len(secs)
    if params["of"] == "mfu":
        return 100.0 * need_f / (spent * run.peaks["bf16_flops_per_s"])
    least, _bound = flops.roofline_seconds(need_f, need_b, run.peaks)
    return 100.0 * least / spent
