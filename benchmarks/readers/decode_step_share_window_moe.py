"""The ``mimo_v2_flash`` decode step against the chip, as
``decode_step_share_mla_moe`` reads the second family's: the work the
algorithm needs for the tokens the traced steps produced
(``lib/flops_window_moe.py``: weights outside the experts once a step,
each expert hit once, each resident sequence's K and V rows once on every
full layer and at most the window's on every window layer) over the
steps' device time, as a share of peak FLOP/s (``of: mfu``) or of the
roofline's least time (``of: roofline``). The experts hit are the
program's counters over the whole run, scaled to the traced steps.
Nothing where the program has no ``<name>_window_rows_read`` counter: it
is not this family's."""
from benchmarks.lib import flops, flops_window_moe
from benchmarks.readers import decode_step_share_mla_moe as step_share
from benchmarks.readers import decode_step_share_sparse_linear as lengths_of


def read(run, params):
    found = step_share.traced_steps(run, params)
    if found is None:
        return None
    secs, steps, share = found
    counters, name = run.result["counters"], run.result["server_name"]
    hit = counters.get("%s_moe_experts_hit" % name)
    sent = counters.get("%s_moe_assignments" % name)
    if counters.get("%s_window_rows_read" % name) is None or hit is None \
            or sent is None:
        return None
    lengths = lengths_of.traced_lengths(run)
    if not lengths:
        return None
    cfg = run.cell.config
    need_f, need_b = flops_window_moe.decode_steps_cost(
        cfg, lengths, steps, hit * share, sent * share,
        cfg["assumed"]["compute_dtype"])
    # the counter's steps against the whole executions the trace holds
    spent = sum(secs) * steps / len(secs)
    if params["of"] == "mfu":
        return 100.0 * need_f / (spent * run.peaks["bf16_flops_per_s"])
    least, _bound = flops.roofline_seconds(need_f, need_b, run.peaks)
    return 100.0 * least / spent
