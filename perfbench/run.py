"""perfbench/run.py — run one cell of BENCHMARK.json on the chip.

    python3 perfbench/run.py --workload val4-flood --seed 7 --seconds 35 --trace 0

One process holds the chip: the node under test, the feeder or the peers'
injector. Signing workers and the served cells' client are processes of
their own that never import JAX. The last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``; with ``--trace 1`` the per-layer metrics and ``breakdown``),
with every number compared beside its limit under ``checks``, which are
also the last lines of standard error. Without an accelerator it exits
non-zero and prints no result.
"""

import time

T_START = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # the builder's own: the control and the spread study, never the driver's
    ap.add_argument("--fault", default=None, help="break the timed path (control runs)")
    ap.add_argument("--timeline", default=None, help="flood: per-step records to this file")
    ap.add_argument("--trace-dump", default=None, help="the traced window's lists to this file")
    args = ap.parse_args(argv)

    from perfbench.harness import cells, drive

    cell = cells.Cell(args.workload)
    opt = drive.Options(
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace), fault=args.fault,
        timeline=args.timeline, trace_dump=args.trace_dump, t_start=T_START,
    )
    result = drive.run_cell(cell, opt)
    for name, check in result["checks"].items():
        print(f"check {name}: {check['value']} (limit {check['limit']})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
