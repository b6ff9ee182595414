"""perfbench/sweep.py — find a served cell's knee, once, on the chip.

    python3 perfbench/sweep.py --workload val4-served --rates 40,60,90,135,200 --seconds 8

One process, one node, the cell's own configuration and programs; the
rates in rising order, each offered for ``--seconds`` on the cell's
arrival schedule (open loop) after two seconds that are not counted
(the front door's bulk bucket follows the commit rate with a lag). The
knee is the highest rate at which the backlog does not grow and the
generator is not late: every tx committed, the 95th percentile under
``--p95-limit-ms``, lateness under 1 ms. The rate of the cell, a quarter of
the knee, is then written into ``perfbench/cells/<cell>.json`` by hand,
with the table in PERF.md. Prints one JSON line per rate.
"""

import time

T_START = time.monotonic()

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
    ap.add_argument("--rates", required=True, help="tx/s, comma-separated, rising")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=27)
    ap.add_argument("--p95-limit-ms", type=float, default=100.0)
    ap.add_argument("--scalar", action="store_true", help="CPU rehearsal: the scalar verifier")
    args = ap.parse_args(argv)

    from perfbench.harness import cells, drive, peers, stats

    cell = cells.Cell(args.workload)
    traffic, config = cell.traffic, cell.config
    if traffic["kind"] != "served":
        raise SystemExit("a sweep is of a served cell")
    served = cells.kind("served")
    drive.check_runs("served", config, served.RUNS)
    drive.device_info(args.scalar, cell.chips)
    rates = [float(r) for r in args.rates.split(",")]
    skip_s = 2.0
    counts = [round(r * (args.seconds + skip_s)) for r in rates]
    n_vals = int(config["validators"])
    traffic = dict(traffic, warm=traffic["warm"] + [["fused", max(traffic["rungs"]), max(traffic["rungs"])]])
    opt = drive.Options(seed=args.seed, seconds=args.seconds, scalar=args.scalar)
    signers = list(range(1, n_vals))
    groups = peers.frames(peers.delays_of(traffic, n_vals), signers)
    sut, corp, _, _ = drive.set_up(
        config, traffic, opt, sum(counts), signers=signers, sign=True
    )
    sut.start()
    print(f"sweep: set-up {time.monotonic() - T_START:.1f}s", file=sys.stderr, flush=True)
    first = 0
    try:
        for rate, n in zip(rates, counts):
            shed0 = sut.admission_shed()
            disp0 = sut.dispatches()
            offsets = served.offsets_of(traffic, n, rate, args.seed)
            t0_ns, proc, injector = served.served_phase(
                sut, corp, traffic, first_tx=first, offsets_ns=offsets, groups=groups,
                wait_s=10.0,
            )
            time.sleep(max(0.0, t0_ns / 1e9 + skip_s - time.monotonic()))
            shed0 = sut.admission_shed()  # sheds of the ramp are not the rate's
            client = served.collect_client(proc, timeout=args.seconds + 60)
            injector.join(timeout=10)
            skip = round(rate * skip_s)
            lat, late, missed = [], [], 0
            for i in range(skip, n):
                due = t0_ns + offsets[i]
                late.append((client["sent_ns"][i] - due) / 1e6)
                if client["status"][i] != 0 or not client["event_ns"][i]:
                    missed += 1
                else:
                    lat.append((client["event_ns"][i] - due) / 1e6)
            half = len(lat) // 2
            row = {
                "rate_tps": rate, "txs": n - skip, "missed": missed,
                "p50_ms": stats.percentile(lat, 50) if lat else None,
                "p95_ms": stats.percentile(lat, 95) if lat else None,
                # a backlog that grows shows as a second half slower than the first
                "p50_first_half_ms": stats.percentile(lat[:half], 50) if half else None,
                "p50_second_half_ms": stats.percentile(lat[half:], 50) if half else None,
                "late_p95_ms": stats.percentile(late, 95),
                "injector_late_p95_ms": stats.percentile(injector.late_ns, 95) / 1e6,
                "shed": sut.admission_shed() - shed0,
                "dispatches": drive.dispatch_delta(sut.dispatches(), disp0),
            }
            row["sustained"] = bool(
                missed == 0 and row["shed"] == 0 and row["p95_ms"] is not None
                and row["p95_ms"] <= args.p95_limit_ms and row["late_p95_ms"] <= 1.0 and row["p50_second_half_ms"] <= 1.5 * row["p50_first_half_ms"]
            )
            print(json.dumps(row), flush=True)
            first += n
            time.sleep(1.0)
        print(json.dumps({"faults": sut.faults()}), flush=True)
    finally:
        sut.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
