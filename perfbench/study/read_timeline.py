"""Read a per-step timeline that ``run.py --timeline`` wrote.

    python3 perfbench/study/read_timeline.py chiprun_out/flood_timeline_*.json

Prints, for each file: the steps inside the window, the cycle (submit to
submit) as median, shortest and longest, host prep and route per step from
``pipeline_stats()``, the collector's pauses by generation, and the longest
cycles beside the full collection that fell into each.
"""

import json
import statistics
import sys


def read(path: str) -> dict:
    d = json.load(open(path))
    w = d["window_s"]
    steps = [s for s in d["steps"] if 0 <= s["t_submit_begin"] < w]
    pairs = list(zip(steps, steps[1:]))
    cycles = [b["t_submit_begin"] - a["t_submit_begin"] for a, b in pairs]
    pauses = [p for p in d["gc_pauses"] if 0 <= p[0] < w]
    full = [(t, s) for t, g, s in pauses if g == 2]

    def full_inside(a, b):
        return sum(s for t, s in full if a <= t < b)

    longest = sorted(
        ((c, a["t_submit_begin"], full_inside(a["t_submit_begin"], b["t_submit_begin"]))
         for c, (a, b) in zip(cycles, pairs)), reverse=True,
    )[:6]
    return {
        "file": path, "window_s": round(w, 3), "steps": len(steps),
        "votes_per_step": sorted({s["votes"] for s in steps}),
        "cycle_ms": {
            "median": round(1e3 * statistics.median(cycles), 1),
            "min": round(1e3 * min(cycles), 1), "max": round(1e3 * max(cycles), 1),
        },
        "prep_ms_per_step": round(1e3 * statistics.median(
            b["prep_s"] - a["prep_s"] for a, b in pairs), 1),
        "route_ms_per_step": round(1e3 * statistics.median(
            b["route_s"] - a["route_s"] for a, b in pairs), 1),
        "gc": {
            f"gen{g}": {
                "n": sum(1 for p in pauses if p[1] == g),
                "total_ms": round(1e3 * sum(p[2] for p in pauses if p[1] == g), 1),
            } for g in (0, 1, 2)
        },
        "full_collections_ms": [round(1e3 * s) for _, s in full],
        "longest_cycles": [
            {"cycle_ms": round(1e3 * c), "at_s": round(t, 2), "full_gc_ms_inside": round(1e3 * g)}
            for c, t, g in longest
        ],
        "feeds": len([f for f in d["feeds"] if 0 <= f[0] < w]),
        "commits_per_100ms_max": max(d["commits_per_100ms"]),
    }


if __name__ == "__main__":
    for path in sys.argv[1:]:
        print(json.dumps(read(path)))
