"""Traffic kind ``flood``: every validator's pre-signed votes replayed at the
vote pool's ingest, closed on a bounded backlog (``harness/flood.py`` has
the backlog rule). The window opens once the lead-in has committed and the
backlog stands; the rate is the commit events in it over its seconds.
"""

from __future__ import annotations

import gc
import random
import threading
import time

from perfbench.harness import drive, flood, stats

# what this kind builds (harness/node.py): one hosted node of the set, the
# consensus ticker off, the kvstore app
RUNS = {"hosted_nodes": 1, "consensus_ticker": False, "app": "kvstore"}


def run(cell, opt: drive.Options) -> dict:
    config = cell.config
    drive.check_runs("flood", config, RUNS)
    device = drive.device_info(opt.scalar, cell.chips)
    traffic = drive.sizes(cell, opt)
    n_vals = int(config["validators"])
    chunk, backlog = int(traffic["chunk_txs"]), int(traffic["backlog_txs"])
    rung_txs = max(traffic["rungs"]) // n_vals
    lead_txs = -(-int(traffic["lead_txs"]) // rung_txs) * rung_txs
    n_txs = int(traffic["rate_hint_tps"] * (opt.seconds + traffic["lead_s"])) + backlog + lead_txs
    n_txs = -(-n_txs // rung_txs) * rung_txs

    sut, corp, warm_s, sign_wait_s = drive.set_up(
        config, traffic, opt, n_txs, signers=list(range(n_vals)), sign=False
    )

    recorder = sut.recorder
    wake = threading.Event()
    sut.on_commit = wake.set

    def feed(lo: int, hi: int) -> None:
        sut.seed_txs([corp.tx(i) for i in range(lo, hi)])
        for k in range(n_vals):
            sut.deliver_votes(corp, k, lo, hi, sender=1 + k)

    feeder = flood.BacklogFeeder(backlog, chunk, n_txs, feed, sut.committed)
    thread = flood.FeederThread(feeder, wake)
    tracing = drive.Tracing(opt.trace, opt.scratch, int(traffic["trace_steps"]),
                            lambda: sum(sut.dispatches().values()), opt.trace_dump)
    with drive.GcLog() as gclog:
        sut.start()
        thread.start()
        deadline = time.monotonic() + 180
        while sut.committed() < lead_txs or feeder.outstanding() < backlog - chunk:
            if thread.error is not None or time.monotonic() > deadline:
                thread.halt()
                sut.stop()
                raise RuntimeError(f"the lead-in did not complete: {thread.error!r}")
            time.sleep(0.01)
        gc.collect()
        if sut.compiles is not None:
            sut.compiles.mark()
        count0, disp0, votes0 = sut.counters(), sut.dispatches(), sut.routed_votes()
        fed0 = feeder.fed
        feeder.min_outstanding_before_feed = None
        feeder.max_outstanding = 0
        t_open = time.monotonic()
        setup_s = t_open - opt.t_start
        tracing.arm(t_open, t_open + opt.seconds)
        time.sleep(max(0.0, t_open + opt.seconds - time.monotonic()))
        t_close = time.monotonic()
        count1, disp1, votes1 = sut.counters(), sut.dispatches(), sut.routed_votes()
        in_window_faults = sut.faults()
        thread.halt()
        tracing.stop()
        fed_window = feeder.fed - fed0
        feeder.align(rung_txs)
        deadline = time.monotonic() + opt.commit_wait_s
        while sut.committed() < feeder.fed and time.monotonic() < deadline:
            time.sleep(0.01)
    if thread.error is not None:
        sut.stop()
        raise RuntimeError(f"the feeder failed: {thread.error!r}")
    if feeder.exhausted:
        sut.stop()
        raise RuntimeError(
            f"the corpus of {n_txs} txs ran out inside the window: the node is faster "
            "than rate_hint_tps; a cell with a larger hint measures it"
        )

    commits = list(sut.commit_times)
    window_s = t_close - t_open
    in_window = sum(1 for t in commits if t_open <= t < t_close)
    end_to_end = {
        "commit_tx_per_s": stats.rate_in_window(commits, t_open, t_close),
        "setup_s": setup_s,
    }
    pipe = drive.pipeline_delta(
        count1["pipeline"], count0["pipeline"], ("full_batches", "linger_flushes")
    )
    dispatches = drive.dispatch_delta(disp1, disp0)
    top = f"{max(traffic['rungs'])}x{max(traffic['rungs'])}"
    ctx = {
        "cell": cell.name, "traffic": traffic, "device_kind": device["kind"],
        "window_s": window_s, "t_open": t_open, "t_close": t_close,
        "pipeline": pipe, "counters": {"open": count0, "close": count1},
        "votes": votes1 - votes0, "dispatches": dispatches,
        "commit_times": commits, "rung_votes": max(traffic["rungs"]),
        "rung_slots": max(traffic["rungs"]), "trace": tracing.reduce(),
        "spans": sut.spans, "client": None,
    }
    never = feeder.fed - sut.committed()
    extra = {
        "never_committed": never,
        # the cell's own shape: every dispatch of the window on the top
        # rung, the backlog inside its bounds
        "off_top_rung_dispatches": 0 if opt.scalar else sum(
            n for shape, n in dispatches.items() if shape != top
        ),
        "backlog_over_bound": int(feeder.max_outstanding > backlog + chunk),
        "backlog_ran_dry": int(feeder.min_outstanding_before_feed == 0),
    }
    diagnostics = {
        "window_s": window_s, "commits_in_window": in_window, "fed_in_window": fed_window,
        "fed_total": feeder.fed, "corpus_txs": n_txs, "warm_s": warm_s,
        "sign_wait_s": sign_wait_s, "steps": pipe["steps"],
        "full_batches": pipe["full_batches"], "linger_flushes": pipe["linger_flushes"],
        "votes_per_step": (votes1 - votes0) / max(pipe["steps"], 1),
        "dispatches": dispatches, "backlog_max": feeder.max_outstanding,
        "backlog_min_before_feed": feeder.min_outstanding_before_feed,
        "gc": gclog.within(t_open, t_close), "host_prep": sut.host_prep(),
        "commit_gap_max_ms": 1e3 * stats.max_gap(commits, t_open, t_close),
        "faults_at_close": in_window_faults, "compile_cache": sut.cache_dir,
    }
    if recorder is not None:
        recorder.write(opt.timeline, t_open, t_close, commits, gclog.pauses, thread.feeds)
    rng = random.Random(opt.seed)
    sample = drive.sample(rng, fed0, fed0 + max(fed_window, 1), int(traffic["compare_txs"]))
    return drive.finish(
        cell, opt, device, sut, corp, attempted=fed_window, failed=never,
        sample=sample, own_power=0, extra_numbers=extra, end_to_end=end_to_end,
        ctx=ctx, diagnostics=diagnostics,
    )
