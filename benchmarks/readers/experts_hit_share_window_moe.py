"""``moe.experts_hit_share`` for the ``mimo_v2_flash`` stack, as
``counter_share`` reads the second family's: of the (expert held, routed
layer, decode step) triples, the share that received a token. ``part``
over ``whole`` (steps) times the experts held (``n_routed_experts``) and
the layers whose FFN is routed (``builders/mimo_window_moe.layer_kinds``:
this configuration has no ``num_experts`` or ``first_k_dense_replace``).
Counters run from the process's start. Nothing where the program has no
such counter."""
from benchmarks.builders.mimo_window_moe import layer_kinds


def read(run, params):
    counters = run.result.get("counters") or {}
    name = run.result.get("server_name")
    if name is None:
        return None
    part = counters.get("%s_%s" % (name, params["part"]))
    steps = counters.get("%s_%s" % (name, params["whole"]))
    if part is None or not steps:
        return None
    cfg = run.cell.config
    routed = sum(1 for _attn, ffn in layer_kinds(cfg) if ffn != "dense")
    return 100.0 * part / (steps * cfg["n_routed_experts"] * routed)
