"""Least time for causal attention forward and backward (the larger of
operations over peak FLOP/s and bytes over peak HBM bytes/s, counted from
shapes at the configuration's compute dtype, whatever implements it) over
the device time of the operations that implement it in the trace."""
from benchmarks.lib import flops


def read(run, params):
    if run.peaks is None:       # a rehearsal has no chip to hold it against
        return None
    if run.reduced is None:
        return None
    red, cell, w = run.reduced, run.cell, run.result["window"]
    steps = len(red.program_busy_seconds(params["program"]))
    # kernel time inside the whole steps traced, against what those
    # steps need
    inside = red.op_seconds_inside(params["ops"], params["program"])
    if not steps or not inside:
        return None
    need_f, need_b = flops.flash_attention_cost(
        cell.config, w["rows"] // w["chips"], cell.traffic["seq_len"],
        cell.config["assumed"]["compute_dtype"])
    least, _bound = flops.roofline_seconds(need_f, need_b, run.peaks)
    return 100.0 * least * steps / inside
