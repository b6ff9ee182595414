"""Whole path: the client's median commit latency less the path of the
votes that complete the quorum, which the harness delivers a delay after
the tx is due (``ctx["quorum_delay_ms"]``: the delay at which the delivered
stake first passes 2/3, which is the traffic's ``peer_delay_ms`` where that
is one number): that delay, then the medians of
``vote_wait`` (the tx's first vote in the pool -> ``host_prep`` of the step
that drains it: the engine's pickup and the lane's hold, per tx),
``host_prep``, ``dispatch``, ``collect_wait``, ``route_tally`` (route start
-> the quorum decisions), ``commit_apply`` (decision -> applied, on the
committer thread or inline) and ``publish``. Medians add up only where one
term carries the spread: here ``vote_wait`` does (a step that carries two
txs gives the second a short wait), the stage medians are near constants.
What is left has no span: the injector's lateness and its frame's ingest up
to the first vote's insert, the frame's way from the socket to the
client's clock. ``rpc_ingest``, ``sign_wait`` and ``sign_walk`` are not in
the sum: the node's own vote reaches the pool while the peers' votes wait,
so those spans lie inside ``vote_wait``. ``commit_apply`` runs on past the
event's queueing, where ``publish`` begins, by a few hundredths of a ms.
None where any of the families is empty."""
import statistics

from perfbench.harness import stats

FAMILIES = ("vote_wait", "host_prep", "dispatch", "collect_wait", "route_tally",
            "commit_apply", "publish")


def read(ctx):
    client = ctx["client"]
    if not client or not client["lat_ms"]:
        return None
    spans = [ctx["spans"](family, ctx["t_open"], ctx["t_close"]) for family in FAMILIES]
    if not all(spans):
        return None
    named_ms = float(ctx["quorum_delay_ms"])
    named_ms += sum(1e3 * statistics.median(s) for s in spans)
    return stats.percentile(client["lat_ms"], 50) - named_ms
