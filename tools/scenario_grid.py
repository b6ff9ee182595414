"""Scenario-grid CLI: walk composed Byzantine × WAN × overload × stake
tiles over real-TCP ProcNets and bank the results matrix.

    JAX_PLATFORMS=cpu python tools/scenario_grid.py --smoke [--seed 7]
    JAX_PLATFORMS=cpu python tools/scenario_grid.py --full            # offline soak
    python tools/scenario_grid.py --smoke --list                      # tile set, no nets
    python tools/scenario_grid.py --smoke --dry-run                   # + drawn schedules
    ... --spec grid.json          # {"seed":7,"n_validators":4,"axes":{"weather":["lan","congested"]}}
    ... --only adv=flooder        # substring filter on tile ids
    ... --out /path/matrix.json   # bank target (default bench_artifacts/scenario_grid_latest.json)

``--smoke`` walks the smoke diagonal (every level of every axis at
least once, incl. one fully-composed tile — CI's bounded posture);
``--full`` walks the configured cross-product. ``--list``/``--dry-run``
review the tile set before committing to a multi-hour run.

Exit codes (scenario/harness.py contract): 0 = every tile green; a
failed walk exits with the MOST SEVERE tile breach — 10 loss,
11 divergence, 13 adversary, 14 liveness, 12 slo, 1 infra/harness. The
final stdout line is always one machine-readable ``RESULT {...}`` JSON
record; nothing greps log banners.
"""

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from txflow_tpu.scenario import bank as bank_mod
from txflow_tpu.scenario import harness as H
from txflow_tpu.scenario.runner import GridRunner
from txflow_tpu.scenario.spec import GridSpec


def tile_set(grid: GridSpec, full: bool, only: str | None):
    tiles = grid.full_tiles() if full else grid.smoke_diagonal()
    kind = "full" if full else "smoke-diagonal"
    if only:
        tiles = [t for t in tiles if only in t.tile_id]
        kind = "filtered"
    return tiles, kind


def main() -> None:
    ap = argparse.ArgumentParser(
        description="scenario grid over real-TCP ProcNets"
    )
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument(
        "--smoke", action="store_true",
        help="walk the smoke diagonal with CI-bounded knobs (default)",
    )
    mode.add_argument(
        "--full", action="store_true",
        help="walk the configured cross-product (offline soak posture)",
    )
    ap.add_argument("--spec", help="GridSpec JSON file (seed/n_validators/axes)")
    ap.add_argument("--seed", type=int, help="grid seed (overrides --spec)")
    ap.add_argument("--only", help="run only tiles whose id contains this substring")
    ap.add_argument(
        "--list", action="store_true",
        help="print the tile walk (one id per line) and exit; no nets",
    )
    ap.add_argument(
        "--dry-run", action="store_true",
        help="print each tile's materialized schedules as JSON and exit; no nets",
    )
    ap.add_argument("--out", help=f"matrix path (default {bank_mod.GRID_LATEST})")
    args = ap.parse_args()

    grid = GridSpec.from_json_file(args.spec) if args.spec else GridSpec()
    if args.seed is not None:
        grid = GridSpec(
            seed=args.seed, n_validators=grid.n_validators, axes=grid.axes
        )
    tiles, kind = tile_set(grid, args.full, args.only)

    if args.list or args.dry_run:
        print(
            f"{kind}: {len(tiles)} tiles, seed {grid.seed}, "
            f"{grid.n_validators} validators"
        )
        for i, t in enumerate(tiles):
            marker = " [composed]" if t.composed else ""
            print(f"  {i:3d}  {t.tile_id}{marker}")
            if args.dry_run:
                plan = grid.materialize(t)
                print(
                    json.dumps(
                        {
                            "schedules": plan.schedules(),
                            "consensus": plan.consensus,
                            "budget_scale": plan.budget_scale,
                            "adversary_index": plan.adversary_index,
                        },
                        indent=2,
                    )
                )
        return

    if not tiles:
        print(f"SOAK STALL: --only {args.only!r} matched no tiles", flush=True)
        sys.exit(H.emit_result("scenario-grid", False, "infra", "empty tile set"))

    out = args.out or bank_mod.GRID_LATEST
    runner = GridRunner(grid, smoke=not args.full)
    error = None
    verdicts: list = []
    try:
        verdicts = runner.run(tiles)
    except KeyboardInterrupt:
        raise
    except Exception as e:  # noqa: BLE001 - the matrix records the wreck
        error = repr(e)

    matrix = bank_mod.build_matrix(grid, kind, verdicts, error=error)
    banked = bank_mod.bank_matrix(matrix, out)
    print(
        f"grid: {matrix['passed']}/{len(verdicts)} tiles green, "
        f"matrix {'banked at ' + out if banked else 'NOT banked (dirty run, clean bank held)'}",
        flush=True,
    )
    for v in verdicts:
        flag = "ok " if v["pass"] else f"{v['breach'] or 'infra'}!"
        print(f"  [{flag:12s}] {v['tile']}  {v.get('detail', '')}".rstrip())

    breaches = [v["breach"] or "infra" for v in verdicts if not v["pass"]]
    if error is not None:
        print(f"SOAK STALL: grid harness failure: {error}", flush=True)
        sys.exit(
            H.emit_result(
                "scenario-grid", False, "infra", error,
                tiles=len(verdicts), fingerprint=matrix["verdict_fingerprint"],
            )
        )
    if breaches:
        worst = H.worst_breach(breaches)
        detail = f"{len(breaches)}/{len(verdicts)} tiles failed"
        print(f"SOAK STALL: {detail}", flush=True)
        sys.exit(
            H.emit_result(
                "scenario-grid", False, worst, detail,
                tiles=len(verdicts), passed=matrix["passed"],
                fingerprint=matrix["verdict_fingerprint"], banked=banked,
            )
        )
    print(
        f"SOAK OK (scenario-grid): {len(verdicts)} tiles green "
        f"({kind}, seed {grid.seed})",
        flush=True,
    )
    sys.exit(
        H.emit_result(
            "scenario-grid", True,
            tiles=len(verdicts), passed=matrix["passed"], kind=kind,
            seed=grid.seed, fingerprint=matrix["verdict_fingerprint"],
            banked=banked, out=out,
        )
    )


if __name__ == "__main__":
    main()
