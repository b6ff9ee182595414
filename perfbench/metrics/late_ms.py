"""Load generator / RPC front door: 95th percentile of send time minus due
time over the window's txs. A starved generator must not read as a fast
server."""
from perfbench.harness import stats


def read(ctx):
    client = ctx["client"]
    if not client or not client["late_ms"]:
        return None
    return stats.percentile(client["late_ms"], 95)
