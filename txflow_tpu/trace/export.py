"""Cross-node trace merge + Chrome-trace/Perfetto JSON export.

A tracer ``dump()`` is one node's view; ``to_chrome_trace`` merges any
number of them onto one wall-clock timeline (each tracer records its
``base_wall_ns``/``base_mono`` pair, so monotonic span timestamps from
different processes align to within wall-clock skew — fine on one host,
and good enough to eyeball cross-host gossip latency).

The output is the Chrome JSON trace format (the ``traceEvents`` array
of ``ph:"X"`` complete events) which Perfetto and chrome://tracing open
directly: one process per node, one track per span family in
commit-path order, every event tagged in ``args`` with its tx hash and
the id of the engine step that carried it (0 = none), so the Perfetto
query engine can follow one transaction across nodes and join it to the
stage spans (``tx`` empty, same ``step``) of the step that decided it.
"""

from __future__ import annotations

import json

from .tracer import SPAN_ORDER


def _track_id(name: str) -> int:
    """Stable small tid per span family (commit-path order first)."""
    try:
        return SPAN_ORDER.index(name) + 1
    except ValueError:
        return len(SPAN_ORDER) + 1 + (sum(name.encode()) % 32)


def merge_by_tx(dumps: list[dict]) -> dict[str, list[dict]]:
    """tx hash -> spans from EVERY node, each tagged with its node id
    and converted to wall-clock microseconds."""
    out: dict[str, list[dict]] = {}
    for d in dumps:
        base_wall_us = d.get("base_wall_ns", 0) / 1e3
        base_mono = d.get("base_mono", 0.0)
        node = d.get("node", "")
        for s in d.get("spans", []):
            ts = base_wall_us + (s["start"] - base_mono) * 1e6
            out.setdefault(s["tx"], []).append(
                {
                    "node": node,
                    "name": s["name"],
                    "ts_us": ts,
                    "dur_us": max(0.0, (s["end"] - s["start"]) * 1e6),
                    "step": s.get("step", 0),
                }
            )
    for spans in out.values():
        spans.sort(key=lambda s: s["ts_us"])
    return out


def to_chrome_trace(dumps: list[dict]) -> dict:
    """Merged dumps -> {"traceEvents": [...]} (Perfetto-openable)."""
    events: list[dict] = []
    for pid, d in enumerate(dumps):
        node = d.get("node", "") or f"node-{pid}"
        base_wall_us = d.get("base_wall_ns", 0) / 1e3
        base_mono = d.get("base_mono", 0.0)
        events.append(
            {
                "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                "args": {"name": node},
            }
        )
        named: set[int] = set()
        for s in d.get("spans", []):
            tid = _track_id(s["name"])
            if tid not in named:
                named.add(tid)
                events.append(
                    {
                        "name": "thread_name", "ph": "M", "pid": pid,
                        "tid": tid, "args": {"name": s["name"]},
                    }
                )
            events.append(
                {
                    "name": s["name"],
                    "cat": "txflow",
                    "ph": "X",
                    "pid": pid,
                    "tid": tid,
                    "ts": base_wall_us + (s["start"] - base_mono) * 1e6,
                    "dur": max(0.0, (s["end"] - s["start"]) * 1e6),
                    "args": {"tx": s["tx"], "step": s.get("step", 0)},
                }
            )
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def write_chrome_trace(path: str, dumps: list[dict]) -> int:
    """Write the merged trace; returns the number of span events."""
    doc = to_chrome_trace(dumps)
    with open(path, "w") as f:
        json.dump(doc, f)
    return sum(1 for e in doc["traceEvents"] if e.get("ph") == "X")
