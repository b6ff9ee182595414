"""Critical-path attribution: where did the wall time go?

Folds one node's engine pipeline accounting (``TxFlow.pipeline_stats``)
and its trace digest into the host-prep / device / linger / lock-wait /
network breakdown. Not read by the benchmark (perfbench/ reads the
spans and ``pipeline_stats`` itself); a caller with a live node's stats
and digest gets the split from here."""

from __future__ import annotations


def critical_path(pipeline_stats: dict, trace_digest: dict | None = None) -> dict:
    """One node's attribution: seconds + fractions per component.

    Components: ``host_s`` (batch prep + commit routing, minus lock
    wait), ``device_s`` (blocked collecting verify tickets), ``lock_-
    wait_s`` (acquiring the engine mutex), ``linger_s`` (coalescer
    deadline holds, from the trace histogram sums of the per-lane
    families; the priority/bulk split is exposed alongside as
    ``linger_prio_s`` / ``linger_bulk_s`` so a lane-split run shows
    WHICH lane paid the hold), ``network_residual_ms`` (e2e p50 minus
    the sum of in-node stage p50s: gossip transit + queueing the
    in-node stages can't see). A speculative-commit run also reports
    ``spec_saved_s`` — the route-tail seconds the early quorum exit
    removed (engine ``spec`` stats) — as attribution context, not a
    busy component (it is time NOT spent)."""
    stats = pipeline_stats or {}
    lat = (trace_digest or {}).get("latency_ms") or {}

    def sum_s(name: str) -> float:
        return (lat.get(name, {}).get("sum_ms") or 0.0) / 1e3

    lock_wait = stats.get("lock_wait_s", 0.0)
    prep = stats.get("prep_s", 0.0)
    route = stats.get("route_s", 0.0)
    host = max(0.0, prep - lock_wait) + route
    linger_prio = sum_s("linger_prio")
    linger_bulk = sum_s("linger_bulk")
    parts = {
        "host_s": host,
        "device_s": stats.get("dispatch_wait_s", 0.0),
        "lock_wait_s": lock_wait,
        "linger_s": linger_prio + linger_bulk,
    }
    busy = sum(parts.values())
    out = {k: round(v, 4) for k, v in parts.items()}
    if linger_prio > 0.0 or linger_bulk > 0.0:
        out["linger_prio_s"] = round(linger_prio, 4)
        out["linger_bulk_s"] = round(linger_bulk, 4)
    spec = stats.get("spec") or {}
    if spec.get("commits"):
        out["spec_saved_s"] = round(spec.get("saved_s", 0.0), 4)
        out["spec_commits"] = int(spec["commits"])
    # When a host-prep pool ran, split the host bucket into the serial
    # remainder vs time spent waiting on pool shards (pool wait is wall
    # time the caller could NOT overlap — the lever sharded host prep
    # pulls on). host_s stays their sum for downstream compat.
    pool_wait = stats.get("prep_pool_wait_s", 0.0)
    if pool_wait > 0.0:
        out["prep_pool_wait_s"] = round(min(pool_wait, host), 4)
        out["prep_serial_s"] = round(host - min(pool_wait, host), 4)
    # Staging-ring split of the device bucket: device_s keeps its
    # historical meaning (wall seconds blocked collecting tickets =
    # dispatch + the readback slice the ring could NOT hide), aliased
    # as device_dispatch_s; readback_overlap_hidden_s is the D2H
    # transfer seconds the ring ran UNDER the engine's next-batch prep
    # (parallel.staging hidden_s) — attribution context like
    # spec_saved_s: time removed from the critical path, not busy time.
    ring = stats.get("staging") or {}
    if ring.get("slots_total"):
        out["device_dispatch_s"] = out["device_s"]
        out["readback_overlap_hidden_s"] = round(
            ring.get("hidden_s", 0.0), 4
        )
    if busy > 0:
        out["fractions"] = {
            k.removesuffix("_s"): round(v / busy, 4) for k, v in parts.items()
        }
        out["bound"] = max(parts, key=parts.get).removesuffix("_s")
    # network + cross-stage queueing residual, per sampled tx (p50s)
    e2e = lat.get("e2e", {}).get("p50")
    if e2e is not None:
        stage_sum = sum(
            lat.get(n, {}).get("p50") or 0.0
            for n in ("vote_ingest", "linger_prio", "linger_bulk",
                      "host_prep", "dispatch", "device_busy", "route",
                      "commit_apply")
        )
        out["network_residual_ms"] = round(max(0.0, e2e - stage_sum), 3)
    return out


def merge_critical_paths(per_node: list[dict]) -> dict:
    """Sum the seconds components across nodes, recompute fractions —
    the fleet-level line."""
    keys = ("host_s", "device_s", "lock_wait_s", "linger_s")
    total = {k: round(sum(cp.get(k, 0.0) for cp in per_node), 4) for k in keys}
    for k in ("prep_serial_s", "prep_pool_wait_s", "linger_prio_s",
              "linger_bulk_s", "spec_saved_s", "device_dispatch_s",
              "readback_overlap_hidden_s"):
        if any(k in cp for cp in per_node):
            total[k] = round(sum(cp.get(k, 0.0) for cp in per_node), 4)
    if any("spec_commits" in cp for cp in per_node):
        total["spec_commits"] = sum(
            cp.get("spec_commits", 0) for cp in per_node
        )
    busy = sum(total[k] for k in keys)
    if busy > 0:
        total["fractions"] = {
            k.removesuffix("_s"): round(v / busy, 4) for k, v in total.items()
            if k in keys
        }
        total["bound"] = max(keys, key=lambda k: total[k]).removesuffix("_s")
    residuals = [
        cp["network_residual_ms"] for cp in per_node
        if cp.get("network_residual_ms") is not None
    ]
    if residuals:
        total["network_residual_ms"] = round(
            sum(residuals) / len(residuals), 3
        )
    return total


def format_line(cp: dict) -> str:
    """One-line rendering."""
    f = cp.get("fractions") or {}
    parts = " ".join(
        f"{k.removesuffix('_s')}={cp.get(k, 0.0):.3f}s({f.get(k.removesuffix('_s'), 0):.0%})"
        for k in ("host_s", "device_s", "lock_wait_s", "linger_s")
    )
    line = f"critical-path: {parts} bound={cp.get('bound', 'n/a')}"
    if "linger_prio_s" in cp or "linger_bulk_s" in cp:
        line += (
            f" linger[prio={cp.get('linger_prio_s', 0.0):.3f}s"
            f" bulk={cp.get('linger_bulk_s', 0.0):.3f}s]"
        )
    if "prep_pool_wait_s" in cp:
        line += (
            f" host[prep_serial={cp.get('prep_serial_s', 0.0):.3f}s"
            f" prep_pool_wait={cp['prep_pool_wait_s']:.3f}s]"
        )
    if cp.get("readback_overlap_hidden_s") is not None:
        line += (
            f" device[dispatch={cp.get('device_dispatch_s', 0.0):.3f}s"
            f" readback_hidden={cp['readback_overlap_hidden_s']:.3f}s]"
        )
    if cp.get("spec_saved_s") is not None:
        line += (
            f" spec_saved={cp['spec_saved_s']:.3f}s"
            f"({cp.get('spec_commits', 0)})"
        )
    if cp.get("network_residual_ms") is not None:
        line += f" net_residual={cp['network_residual_ms']:.1f}ms"
    return line
