"""The ``minicpm_sala`` decode step against the chip, as
``decode_step_share_mla_moe`` reads the second family's: the work the
algorithm needs for the tokens the traced steps produced
(``lib/flops_sparse_linear.py``: every weight once a step; a resident
sequence's selected blocks of K and V and its compressed keys on each
sparse layer, its state read and written on each lightning layer) over
the steps' device time, as a share of peak FLOP/s (``of: mfu``) or of the
roofline's least time (``of: roofline``). Nothing where the program has
no ``<name>_sparse_blocks_read`` counter: it is not this family's."""
from benchmarks.lib import flops, flops_sparse_linear
from benchmarks.readers import decode_step_share_mla_moe as step_share


def traced_lengths(run):
    """Keys each token of the traced stretch attended from: every token
    but a request's first came from a decode step, and attended from the
    prompt and the answer so far, itself included."""
    traced = run.result["traced"]
    return [r["prompt_len"] + j + 1
            for r in run.result["window"]["all_requests"]
            for j, t in enumerate(r["times"])
            if j > 0 and traced["t_start"] <= t < traced["t_stop"]]


def read(run, params):
    found = step_share.traced_steps(run, params)
    if found is None:
        return None
    secs, steps, _share = found
    counters, name = run.result["counters"], run.result["server_name"]
    if counters.get("%s_sparse_blocks_read" % name) is None:
        return None
    lengths = traced_lengths(run)
    if not lengths:
        return None
    cfg = run.cell.config
    need_f, need_b = flops_sparse_linear.decode_steps_cost(
        cfg, lengths, steps, cfg["assumed"]["compute_dtype"])
    # the counter's steps against the whole executions the trace holds
    spent = sum(secs) * steps / len(secs)
    if params["of"] == "mfu":
        return 100.0 * need_f / (spent * run.peaks["bf16_flops_per_s"])
    least, _bound = flops.roofline_seconds(need_f, need_b, run.peaks)
    return 100.0 * least / spent
