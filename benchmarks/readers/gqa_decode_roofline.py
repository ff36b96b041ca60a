"""The kernel that reads the full layers' caches in a decode step, against
the chip: the least time for the K and V rows the traced sequences need
and the attention over them (``lib/flops_window_moe.py::gqa_kernel_cost``,
from the lengths of the sequences the traced steps served) over the device
time of the kernel's operations (``ops``, by the kernel's name) inside the
traced decode steps. Nothing where the trace holds no such operation or
the program has no ``<name>_gqa_decode_kernel_steps`` counter."""
import bisect
import re

from benchmarks.lib import flops, flops_window_moe
from benchmarks.readers import decode_step_share_mla_moe as step_share
from benchmarks.readers import decode_step_share_sparse_linear as lengths_of
from benchmarks.readers import expert_matmul_roofline


def read(run, params):
    found = step_share.traced_steps(run, params)
    if found is None:
        return None
    _secs, steps, _share = found
    counters, name = run.result["counters"], run.result["server_name"]
    if counters.get("%s_gqa_decode_kernel_steps" % name) is None:
        return None
    red = run.reduced
    spans = expert_matmul_roofline.decode_executions(red, params)
    starts = [s for s, _e in spans]
    rx = re.compile(params["ops"])
    spent = 0.0
    for e in red.ops():
        if rx.search(e.name):
            i = bisect.bisect_right(starts, e.start) - 1
            if i >= 0 and e.end <= spans[i][1]:
                spent += e.dur
    lengths = lengths_of.traced_lengths(run)
    if not spans or not spent or not lengths:
        return None
    need_f, need_b = flops_window_moe.gqa_kernel_cost(run.cell.config,
                                                      lengths)
    # the lengths are of all the steps the counter saw in the stretch, the
    # operations of the whole executions the trace holds
    scale = len(spans) / float(steps)
    least, _bound = flops.roofline_seconds(need_f * scale, need_b * scale,
                                           run.peaks)
    return 100.0 * least / spent
