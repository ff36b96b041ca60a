"""One of the server's counters over another, as a share: ``part`` over
``whole``, the whole multiplied by numbers of the configuration where it
counts steps and the part counts things a step has several of (experts
held, on each of the sparse layers). Counters run from the process's
start, part and whole over the same steps. Nothing where the program has
no such counter."""


def read(run, params):
    counters = run.result.get("counters") or {}
    name = run.result.get("server_name")
    if name is None:
        return None
    part = counters.get("%s_%s" % (name, params["part"]))
    whole = counters.get("%s_%s" % (name, params["whole"]))
    if part is None or not whole:
        return None
    cfg = run.cell.config
    for key in params.get("times_config") or []:
        whole *= cfg[key]
    if params.get("times_sparse_layers"):
        whole *= cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return 100.0 * part / whole
