"""From a profiler trace to device metrics.

``load_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain lists, ``[name, start_ns, duration_ns]``, for each device: the
executions of whole programs (line "XLA Modules") and of single operations
(line "XLA Ops"). Everything below works on those lists, so the reduction
is tested on a small recorded trace without a chip.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
LINE_MODULES = "XLA Modules"
LINE_OPS = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    found = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(hlo: str) -> str:
    """'%pad_add_fusion.8381 = s32[...] fusion(...)' -> 'pad_add_fusion':
    the trace names an operation by its whole HLO line."""
    head = hlo.split(" = ", 1)[0].strip().lstrip("%")
    base, _, suffix = head.rpartition(".")
    return base if base and suffix.isdigit() else head


def load_xplane(path: str, op_modules: int = 4) -> dict:
    """{"devices": {plane name: {"modules": [...], "ops": [...]}},
    "lines": {plane name: [line names]}}. A step program runs tens of
    thousands of operations, so only the operations of the first
    ``op_modules`` program executions are read."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, lines = {}, {}
    for plane in data.planes:
        lines[plane.name] = [line.name for line in plane.lines]
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        by_name = {line.name: line for line in plane.lines}
        dev = {"modules": [], "ops": []}
        if LINE_MODULES in by_name:
            dev["modules"] = [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in by_name[LINE_MODULES].events
            ]
        if LINE_OPS in by_name and dev["modules"]:
            first = sorted(dev["modules"], key=lambda m: m[1])[:op_modules]
            cut = max(start + dur for _, start, dur in first)
            for ev in by_name[LINE_OPS].events:
                start = int(ev.start_ns)
                if start >= cut:
                    break
                dev["ops"].append([op_name(ev.name), start, int(ev.duration_ns)])
        devices[plane.name] = dev
    return {"devices": devices, "lines": lines}


def busy_union(events) -> list[tuple[int, int]]:
    """Merged [start, end) intervals in which some event ran."""
    merged: list[list[int]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def busy_seconds(events) -> float:
    return sum(b - a for a, b in busy_union(events)) / 1e9


def idle_gaps(events, t0: int, t1: int, top: int = 10):
    """The longest gaps of [t0, t1) in which nothing ran, each named by
    the event that ended it: [[name, seconds], ...], longest first."""
    ordered = sorted(events, key=lambda e: e[1])
    gaps, cursor = [], t0
    for name, start, dur in ordered:
        if start > cursor:
            gaps.append(["before_" + name, (start - cursor) / 1e9])
        cursor = max(cursor, start + dur)
    if t1 > cursor:
        gaps.append(["before_window_end", (t1 - cursor) / 1e9])
    gaps.sort(key=lambda g: -g[1])
    return gaps[:top]


def ops_by_time(events, top: int = 10):
    """[[operation name, total seconds], ...], longest first."""
    total: dict[str, int] = {}
    for name, _, dur in events:
        total[name] = total.get(name, 0) + dur
    ranked = sorted(total.items(), key=lambda kv: -kv[1])
    return [[name, ns / 1e9] for name, ns in ranked[:top]]


def step_seconds(modules) -> list[float]:
    """Device time of each program execution."""
    return [dur / 1e9 for _, _, dur in modules]


def reduce_device(dev: dict) -> dict:
    """One device's numbers over the traced window, all on the trace's own
    clock. The window is whole cycles: from the start of the first program
    execution to the start of the last, so it holds as many executions as
    gaps. Busy time is the time the programs inside it ran, less the share
    of it in which no operation ran, that share read from the programs
    whose operations were loaded. A share over 1 means operations were
    laid to the wrong program: an error, not something to cap."""
    modules = sorted(dev["modules"], key=lambda m: m[1])
    if len(modules) < 2:
        return {"busy_s": 0.0, "window_s": 0.0, "steps": step_seconds(modules),
                "device_ops": [], "idle_gaps": [], "ops_share_of_module": None}
    share, scale = 1.0, 1.0
    if dev["ops"]:
        t0 = min(e[1] for e in dev["ops"])
        t1 = max(e[1] + e[2] for e in dev["ops"])
        covered = [m for m in modules if m[1] >= t0 - 1000 and m[1] + m[2] <= t1 + 1000]
        in_modules = sum(m[2] for m in covered)
        if in_modules:
            share = busy_seconds(dev["ops"]) * 1e9 / in_modules
            if share > 1.001:
                raise ValueError(
                    f"operations ran {share:.4f} of their programs' time: "
                    "the trace lays them to the wrong program"
                )
            # operations were read for some programs only: scale their
            # seconds to all the programs of the window
            scale = sum(m[2] for m in modules) / in_modules
    t_first, t_last = modules[0][1], modules[-1][1]
    return {
        "busy_s": share * busy_seconds(modules[:-1]),
        "window_s": (t_last - t_first) / 1e9,
        "steps": step_seconds(modules),
        "device_ops": [[name, s * scale] for name, s in ops_by_time(dev["ops"])],
        "idle_gaps": idle_gaps(modules, t_first, t_last),
        "ops_share_of_module": share,
    }
