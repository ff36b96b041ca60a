"""The expert layer's grouped products against the chip: the least time
for the weights of the routed experts that were hit (each read once a
step and layer) and the products of the assignments sent to them
(``lib/flops_mla_moe.py``), over the device time of the operations that
implement the grouped products (``ops``: XLA's ``ragged-dot`` here)
inside the traced decode steps. The experts hit are the program's
counters over the whole run, taken per step. Nothing where the program
has no such counters or the trace no such operation."""
import bisect
import re

from benchmarks.lib import flops, flops_mla_moe
from benchmarks.readers import decode_step_share_mla_moe as step_share


def decode_executions(red, params):
    """(start, end) of the whole executions of ``program`` in the stretch
    that a later token followed: decode steps, as
    ``Reduced.program_busy_seconds`` tells them from prefills."""
    seen = sorted((e.start, e.name) for e in red.events
                  if e.name in params["marks"]
                  and not e.plane.startswith("/device:"))
    lo, hi = red.window
    out = []
    for m in red.modules(params["program"]):
        if m.start < lo or m.end > hi:
            continue
        i = bisect.bisect_left(seen, (m.start + m.dur / 2.0, ""))
        if i < len(seen) and seen[i][1] == params["followed_by"]:
            out.append((m.start, m.end))
    return sorted(out)


def read(run, params):
    found = step_share.traced_steps(run, params)
    if found is None:
        return None
    counters, name = run.result["counters"], run.result["server_name"]
    hit = counters.get("%s_moe_experts_hit" % name)
    sent = counters.get("%s_moe_assignments" % name)
    all_steps = counters.get("%s_decode_steps" % name)
    if hit is None or sent is None:
        return None
    red = run.reduced
    spans = decode_executions(red, params)
    starts = [s for s, _e in spans]
    rx = re.compile(params["ops"])
    spent = 0.0
    for e in red.ops():
        if rx.search(e.name):
            i = bisect.bisect_right(starts, e.start) - 1
            if i >= 0 and e.end <= spans[i][1]:
                spent += e.dur
    if not spans or not spent:
        return None
    cfg = run.cell.config
    per_step = len(spans) / float(all_steps)
    need_b = flops_mla_moe.experts_hit_bytes(
        cfg, hit * per_step, cfg["assumed"]["compute_dtype"])
    need_f = 2.0 * sent * per_step * flops_mla_moe.expert_params(cfg)
    least, _bound = flops.roofline_seconds(need_f, need_b, run.peaks)
    return 100.0 * least / spent
