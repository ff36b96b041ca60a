"""The whole step's share of one chip's peak: operations the forward and
backward passes of one step need on one chip (from the configuration's
shapes, recomputation not counted) over the device time of one execution
of the step's program in the trace, over peak bf16 FLOP/s. The time is
``program_device_ms``'s, so the two move as one; what the host and the
data plane cost shows in the end-to-end rate and the idle share, not
here."""
from benchmarks.lib import spec


def read(run, params):
    if run.peaks is None:       # a rehearsal has no chip to hold it against
        return None
    if run.reduced is None:
        return None
    secs = run.reduced.program_busy_seconds(params["program"])
    if not secs:
        return None
    cell, w = run.cell, run.result["window"]
    builder = spec.load_module("builders", cell.config["builder"])
    per_unit = builder.train_flops_per_unit(cell.config, cell.traffic)
    need = per_unit * w["units_per_step"] / w["chips"]
    return 100.0 * need * len(secs) / (sum(secs)
                                       * run.peaks["bf16_flops_per_s"])
