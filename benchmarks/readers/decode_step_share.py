"""The decode step against the chip. Work the algorithm needs for the
tokens the traced steps produced (every weight once a step, each resident
sequence's keys and values once, at the configuration's compute dtype;
operations from shapes) over the steps' device time: as a share of peak
FLOP/s (``of: mfu``) or of the roofline's least time (``of: roofline``).
"""
from benchmarks.lib import flops
from benchmarks.readers import serve_program_ms


def read(run, params):
    if run.peaks is None:       # a rehearsal has no chip to hold it against
        return None
    traced = run.result.get("traced")
    secs = serve_program_ms.seconds(run, params)
    if not traced or not secs or not traced.get("decode_steps"):
        return None
    cfg = run.cell.config
    # every token but a request's first came from a decode step; it
    # attended to the prompt and the answer so far, itself included
    lengths = [r["prompt_len"] + j + 1
               for r in run.result["window"]["all_requests"]
               for j, t in enumerate(r["times"])
               if j > 0 and traced["t_start"] <= t < traced["t_stop"]]
    if not lengths:
        return None
    steps = traced["decode_steps"]
    need_f, need_b = flops.decode_steps_cost(
        cfg, lengths, steps, cfg["assumed"]["compute_dtype"])
    # the counter's steps against the whole executions the trace holds
    spent = sum(secs) * steps / len(secs)
    if params["of"] == "mfu":
        return 100.0 * need_f / (spent * run.peaks["bf16_flops_per_s"])
    least, _bound = flops.roofline_seconds(need_f, need_b, run.peaks)
    return 100.0 * least / spent
