"""Which engine stage covers each idle gap of the device.

    python3 perfbench/study/gap_stages.py --workload val4-flood --seed 7 --seconds 35

A traced run of one cell through the harness's own classes (the run
``run.py --trace 1`` makes), whose profiler trace is read twice: the
harness reduces the device plane as it always does, and this script reads,
from the same ``.xplane.pb``, the host plane beside it. The program enters
every engine stage (``pool_wait``, ``linger_*``, ``host_prep``, ``dispatch``,
``collect_wait``, ``route``) and every collection (``gc_pause``) as a
``jax.profiler.TraceAnnotation``, so both planes are on one clock and an
idle gap of the device, from the end of one ``jit_txflow_verify_tally`` to
the start of the next, can be put down to the stages that ran inside it.

Prints, for the traced steps: the program's name in the trace, the idle
seconds, the seconds of each stage inside the gaps and the share no stage
covers, the longest gaps one by one, where each program started against
its ``dispatch`` annotation (the check that the two planes share a clock),
the stage spans' medians over the whole window and, in a served cell, the
tx's waterfall: every segment from the first vote in the pool to the frame
on the socket, each tx's spans joined to the stage spans of the step that
decided it. Then the run's result line. With ``--out`` the same as JSON.

``tracered.idle_gaps`` names a gap by the program that ended it; naming it
by the stage that covers most of it is a change to the harness, which a
``benchmark`` PR makes (PERF.md section 7).
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

STAGES = ("pool_wait", "linger_bulk", "linger_prio", "host_prep", "dispatch", "collect_wait",
          "route", "gc_pause")  # pickup_wait lies over these and is no annotation
HOST_PLANE_PREFIX = "/host:"


def host_annotations(path: str) -> list[list]:
    """[[stage, start_ns, duration_ns, step], ...] from the host planes."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(HOST_PLANE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in STAGES:
                    step = dict(ev.stats).get("step", 0)
                    out.append([ev.name, int(ev.start_ns), int(ev.duration_ns), int(step)])
    out.sort(key=lambda a: a[1])
    return out


def gaps_between(modules) -> list[tuple[int, int]]:
    """[start, end) of every stretch between the first program's start and
    the last one's in which no program ran."""
    gaps, cursor = [], None
    for _, start, dur in sorted(modules, key=lambda m: m[1]):
        if cursor is not None and start > cursor:
            gaps.append((cursor, start))
        cursor = max(cursor or 0, start + dur)
    return gaps


def lay_stages_on_gaps(gaps, annotations) -> dict:
    """Seconds of each stage inside the gaps, and the seconds of the gaps
    no stage covers (a collection runs inside a stage, so the union is
    taken, not the sum)."""
    by_stage = dict.fromkeys(STAGES, 0)
    per_gap = []
    uncovered_total = 0
    for g0, g1 in gaps:
        inside = dict.fromkeys(STAGES, 0)
        clipped = []
        for name, start, dur, _step in annotations:
            a, b = max(start, g0), min(start + dur, g1)
            if b > a:
                inside[name] += b - a
                clipped.append((a, b))
        covered, cursor = 0, g0
        for a, b in sorted(clipped):
            if b > cursor:
                covered += b - max(a, cursor)
                cursor = b
        uncovered = (g1 - g0) - covered
        uncovered_total += uncovered
        for name, ns in inside.items():
            by_stage[name] += ns
        per_gap.append({
            "gap_s": (g1 - g0) / 1e9, "uncovered_s": uncovered / 1e9,
            "stages_s": {n: ns / 1e9 for n, ns in inside.items() if ns},
        })
    idle = sum(g1 - g0 for g0, g1 in gaps)
    return {
        "gaps": len(gaps), "idle_s": idle / 1e9,
        "stages_s": {n: ns / 1e9 for n, ns in by_stage.items()},
        "stages_share": {n: (ns / idle if idle else None) for n, ns in by_stage.items()},
        "uncovered_s": uncovered_total / 1e9,
        "named_share": (1.0 - uncovered_total / idle) if idle else None,
        "longest": sorted(per_gap, key=lambda g: -g["gap_s"])[:8],
    }


def dispatch_to_program_us(modules, annotations) -> list[list[float]]:
    """For each program, the microseconds from the start and from the end
    of the ``dispatch`` annotation that began last before it to its start.
    ``submit`` enqueues the program and returns, so the program starts
    inside that annotation or just after it (first number positive, second
    small or negative) if the two planes share a clock."""
    spans = sorted((start, start + dur) for name, start, dur, _ in annotations
                   if name == "dispatch")
    out = []
    for _, start, _dur in sorted(modules, key=lambda m: m[1]):
        before = [d for d in spans if d[0] <= start]
        if before:
            out.append([(start - before[-1][0]) / 1e3, (start - before[-1][1]) / 1e3])
    return out


def _median_ms(values) -> float | None:
    return 1e3 * statistics.median(values) if values else None


def stage_medians_ms(spans) -> dict:
    """Median and sum of every stage family over the window's spans."""
    by_name: dict[str, list[float]] = {}
    for s in spans:
        if not s["tx"] or s["name"] == "gc_pause":
            by_name.setdefault(s["name"], []).append(s["end"] - s["start"])
    return {n: {"n": len(v), "median_ms": _median_ms(v), "sum_s": sum(v)}
            for n, v in sorted(by_name.items())}


def served_waterfall_ms(spans) -> dict:
    """A served tx's path through the node, segment by segment, each the
    median over the window's txs of that segment in that tx's own chain:
    the tx's spans joined to the stage spans of the step that decided it
    (``commit_apply``'s step id). The path is that of the tx's votes: from
    the first of them in the pool (``vote_wait``'s start) the segments lie
    end to end to the frame on the socket, so their medians can be laid
    beside the client's latency; ``node_ms`` is that whole stretch. The
    step's ``pickup_wait`` and hold, and the tx's own way in
    (``rpc_ingest``, ``sign_wait``, ``sign_walk``), lie inside ``vote_wait``
    and are given beside it (``own_vote_before_prep``: positive, the
    node's own vote rode the batch)."""
    steps: dict[int, dict] = {}
    txs: dict[str, dict] = {}
    for s in spans:
        if s["tx"] and s["name"] != "gc_pause":
            txs.setdefault(s["tx"], {})[s["name"]] = s
        elif s["step"]:
            steps.setdefault(s["step"], {})[s["name"]] = s
    seg: dict[str, list[float]] = {}
    need_tx = ("rpc_ingest", "sign_wait", "sign_walk", "vote_wait", "quorum_latch",
               "commit_apply", "publish")
    need_step = ("host_prep", "dispatch", "collect_wait", "route")
    for mine in txs.values():
        if not all(n in mine for n in need_tx):
            continue
        step = steps.get(mine["commit_apply"]["step"], {})
        if not all(n in step for n in need_step):
            continue
        wait, prep = mine["vote_wait"], step["host_prep"]
        t = [
            ("vote_wait", wait["start"], prep["start"]),
            ("host_prep", prep["start"], prep["end"]),
            ("prep_to_dispatch", prep["end"], step["dispatch"]["start"]),
            ("dispatch", step["dispatch"]["start"], step["dispatch"]["end"]),
            ("dispatch_to_collect", step["dispatch"]["end"], step["collect_wait"]["start"]),
            ("collect_wait", step["collect_wait"]["start"], step["collect_wait"]["end"]),
            ("collect_to_route", step["collect_wait"]["end"], step["route"]["start"]),
            ("route_to_decision", step["route"]["start"], mine["quorum_latch"]["end"]),
            ("decision_to_event_queued (committer)", mine["quorum_latch"]["end"],
             mine["publish"]["start"]),
            ("publish", mine["publish"]["start"], mine["publish"]["end"]),
        ]
        for name, a, b in t:
            seg.setdefault(name, []).append(b - a)
        seg.setdefault("node_ms", []).append(mine["publish"]["end"] - wait["start"])
        for name in ("rpc_ingest", "sign_wait", "sign_walk"):
            seg.setdefault("own: " + name, []).append(mine[name]["end"] - mine[name]["start"])
        for name in ("pickup_wait", "linger_bulk"):
            if name in step:
                seg.setdefault("step: " + name, []).append(step[name]["end"] - step[name]["start"])
        seg.setdefault("own_vote_before_prep", []).append(
            prep["start"] - mine["sign_walk"]["end"])
        seg.setdefault("request_parsed_after_first_vote", []).append(
            mine["rpc_ingest"]["start"] - wait["start"])
    out = {name: _median_ms(v) for name, v in seg.items()}
    out["txs"] = len(seg.get("node_ms", []))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None, help="the table as JSON, to this file")
    args = ap.parse_args(argv)

    from perfbench.harness import cells, drive, tracered

    seen = {}
    load_xplane = tracered.load_xplane

    def load_and_keep(path, *a, **kw):
        seen["annotations"] = host_annotations(path)
        loaded = load_xplane(path, *a, **kw)
        seen["devices"] = loaded["devices"]
        return loaded

    tracered.load_xplane = load_and_keep
    finish = drive.finish

    def finish_and_keep(cell, opt, device, sut, corp, **kw):
        ctx = kw["ctx"]
        seen["spans"] = [s for s in sut.node.tracer.spans()
                         if ctx["t_open"] <= s["start"] < ctx["t_close"]]
        seen["client_p50_ms"] = (
            statistics.median(ctx["client"]["lat_ms"]) if ctx.get("client") else None
        )
        return finish(cell, opt, device, sut, corp, **kw)

    drive.finish = finish_and_keep
    cell = cells.Cell(args.workload)
    opt = drive.Options(seed=args.seed, seconds=args.seconds, trace=True, t_start=T_START)
    result = drive.run_cell(cell, opt)

    table = {"workload": cell.name, "seed": args.seed}
    annotations = seen.get("annotations", [])
    table["annotations"] = {
        name: sum(1 for a in annotations if a[0] == name) for name in STAGES
    }
    table["stage_spans"] = stage_medians_ms(seen.get("spans", []))
    if seen.get("client_p50_ms") is not None:
        table["client_p50_ms"] = seen["client_p50_ms"]
        table["served_waterfall_ms"] = served_waterfall_ms(seen["spans"])
    devices = seen.get("devices") or {}
    if devices:
        first = sorted(devices.items())[0][1]
        modules = first["modules"]
        table["programs"] = sorted({m[0] for m in modules})
        table["traced_steps"] = len(modules)
        table.update(lay_stages_on_gaps(gaps_between(modules), annotations))
        table["dispatch_to_program_us"] = dispatch_to_program_us(modules, annotations)
    print("gap_stages: " + json.dumps(table, indent=1), file=sys.stderr, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"table": table, "result": result}, f)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
