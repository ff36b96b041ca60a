"""How late the load generator ran: a percentile of (sent - due) over the
window's requests. A starved generator must not read as a fast server."""
from benchmarks.lib import stats


def read(run, params):
    w = run.result["window"]
    if "requests" not in w:
        return None
    lags = [r["lag"] for r in w["requests"]]
    value = stats.percentile(lags, float(params["percentile"]))
    return None if value is None else 1e3 * max(value, 0.0)
