"""Traffic kind ``served``: a client process offers txs over HTTP on the
cell's arrival schedule (``perfbench/arrivals/<arrivals>.py``), whatever the
replies do, and watches ``/websocket`` for the commit events; the node signs
its own vote; the peers' votes for a tx reach the vote pool by their delays
after the tx is due (``harness/peers.py``). The window opens after the
lead-in; the txs judged are those due in it.
"""

from __future__ import annotations

import gc
import json
import os
import random
import subprocess
import sys
import tempfile
import time

from perfbench.harness import cells, corpus, drive, peers, stats

# what this kind builds (harness/node.py): one hosted node of the set, the
# consensus ticker off, the kvstore app
RUNS = {"hosted_nodes": 1, "consensus_ticker": False, "app": "kvstore"}


def start_client(job: dict) -> subprocess.Popen:
    """The client in a process of its own, which never imports JAX. The
    job, with every tx's due time, is its standard input: one argument
    holds 128 KiB at the most."""
    env = dict(os.environ)
    env["PYTHONPATH"] = cells.ROOT + os.pathsep + env.get("PYTHONPATH", "")
    with tempfile.TemporaryFile() as f:
        f.write(json.dumps(job).encode())
        f.seek(0)
        return subprocess.Popen(
            [sys.executable, "-m", "perfbench.harness.client"],
            cwd=cells.ROOT, env=env, stdin=f, stdout=subprocess.PIPE,
        )


def offsets_of(traffic: dict, n_txs: int, rate_tps: float, seed: int) -> list[int]:
    """Every tx's due time, ns after the schedule's start, by the traffic's
    ``arrivals``: computed once, for the client, the injector and the outcomes."""
    offsets = cells.arrivals(traffic["arrivals"])(
        n_txs, rate_tps, seed, traffic.get("arrival_params") or {}
    )
    if len(offsets) != n_txs or offsets[0] < 0 or any(
        b < a for a, b in zip(offsets, offsets[1:])
    ):
        raise ValueError(f"arrivals {traffic['arrivals']!r}: not {n_txs} due times that never fall")
    return offsets


def plan(traffic: dict, seconds: float, seed: int):
    """The schedule of a run, made in set-up: the rate, the window's length
    (the whole txs that ``seconds`` hold at the rate, over the rate), every
    tx's due time (lead-in first), the window's opening in ns after the
    schedule's start, and the txs judged: those due in the window."""
    rate = float(traffic["rate_tps"])
    n_lead = max(1, round(rate * traffic["lead_s"]))
    n_win = max(1, round(rate * seconds))
    offsets = offsets_of(traffic, n_lead + n_win, rate, seed)
    open_ns = int(n_lead * 1e9 / rate)
    close_ns = open_ns + int(n_win * 1e9 / rate)
    judged = [i for i, at in enumerate(offsets) if open_ns <= at < close_ns]
    if not judged:
        raise ValueError(f"arrivals {traffic['arrivals']!r}: no tx is due in the window")
    return rate, n_win / rate, offsets, open_ns, judged


def served_phase(sut, corp, traffic, *, first_tx: int, offsets_ns: list[int], groups,
                 wait_s: float, start_in_s: float = 2.0):
    """Offer txs [first_tx, first_tx + len(offsets_ns)), each at its offset
    after the schedule's t0. Returns t0 (monotonic ns), the client process
    and the injector."""
    host, port = sut.node.rpc.addr
    injector = peers.PeerInjector(sut, corp, first_tx, offsets_ns, groups)
    t0_ns = time.monotonic_ns() + int(start_in_s * 1e9)
    job = {
        "host": host, "port": port, "t0_ns": t0_ns, "offsets_ns": offsets_ns,
        "first_tx": first_tx, "tx_bytes": int(traffic["tx_bytes"]),
        "tag": corp.tag.decode(), "senders": int(traffic["senders"]), "wait_s": wait_s,
    }
    proc = start_client(job)
    injector.begin(t0_ns)
    return t0_ns, proc, injector


def collect_client(proc: subprocess.Popen, timeout: float) -> dict:
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        raise RuntimeError("the client did not end in time")
    if proc.returncode != 0:
        raise RuntimeError(f"the client failed with code {proc.returncode}")
    return json.loads(out)


def served_outcomes(reply: dict, t0_ns: int, offsets_ns: list[int], judged) -> dict:
    """What became of the txs ``judged``, those that were due in the window.

    A tx the front door refused (a 429 when the node sheds, any answer but
    ``code 0``) or whose commit event never came counts ``drive.NEVER_MS`` in the
    percentiles and in ``failed``. Only an acknowledged tx that never
    commits breaks a guarantee (``never``): a refusal is an answer, and
    after a stall of seconds (these machines have them, about one in an
    hour of runs) an open-loop client sends what is overdue at once and a
    sound node sheds part of it."""
    lat_ms, late_ms, refused, never, acknowledged, event_wrong = [], [], [], [], [], 0
    for i in judged:
        due = t0_ns + offsets_ns[i]
        sent = reply["sent_ns"][i]
        late_ms.append((sent - due) / 1e6 if sent else drive.NEVER_MS)
        if reply["status"][i] != 0:
            refused.append(i)
            lat_ms.append(drive.NEVER_MS)
            continue
        acknowledged.append(i)
        event = reply["event_ns"][i]
        if not event:
            never.append(i)
            lat_ms.append(drive.NEVER_MS)
            continue
        if reply["event_code"][i] != 0:
            event_wrong += 1
        lat_ms.append((event - due) / 1e6)
    return {"lat_ms": lat_ms, "late_ms": late_ms, "refused": refused, "never": never,
            "acknowledged": acknowledged, "event_wrong": event_wrong}


def run(cell, opt: drive.Options) -> dict:
    config = cell.config
    drive.check_runs("served", config, RUNS)
    device = drive.device_info(opt.scalar, cell.chips)
    traffic = drive.sizes(cell, opt)
    n_vals = int(config["validators"])
    rate, window_s, offsets, open_ns, judged = plan(traffic, opt.seconds, opt.seed)
    n_txs = len(offsets)
    # the node hosts validator 0 and signs its own vote; the others are peers
    powers = corpus.powers_of(config)
    delays = peers.delays_of(traffic, n_vals)
    signers = list(range(1, n_vals))
    groups = peers.frames(delays, signers)

    sut, corp, warm_s, sign_wait_s = drive.set_up(
        config, traffic, opt, n_txs, signers=signers, sign=True
    )

    tracing = drive.Tracing(opt.trace, opt.scratch, int(traffic["trace_steps"]),
                            lambda: sum(sut.dispatches().values()), opt.trace_dump)
    with drive.GcLog() as gclog:
        sut.start()
        gc.collect()  # a full collection takes a quarter second here: before the lead-in
        t0_ns, proc, injector = served_phase(
            sut, corp, traffic, first_tx=0, offsets_ns=offsets, groups=groups,
            wait_s=opt.commit_wait_s,
        )
        try:
            t_open = (t0_ns + open_ns) / 1e9
            time.sleep(max(0.0, t_open - time.monotonic()))
            if sut.compiles is not None:
                sut.compiles.mark()
            count0, disp0, votes0 = sut.counters(), sut.dispatches(), sut.routed_votes()
            shed0 = sut.admission_shed()
            setup_s = t_open - opt.t_start
            t_close = t_open + window_s
            tracing.arm(t_open, t_close)
            time.sleep(max(0.0, t_close - time.monotonic()))
            count1, disp1, votes1 = sut.counters(), sut.dispatches(), sut.routed_votes()
            tracing.stop()
            reply = collect_client(proc, timeout=opt.commit_wait_s + 30)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        injector.join(timeout=10)
    out = served_outcomes(reply, t0_ns, offsets, judged)
    lat_ms, late_ms = out["lat_ms"], out["late_ms"]
    failed = len(out["refused"]) + len(out["never"])
    end_to_end = {
        "commit_p50_ms": stats.percentile(lat_ms, 50),
        "commit_p95_ms": stats.percentile(lat_ms, 95),
        "setup_s": setup_s,
    }
    pipe = drive.pipeline_delta(count1["pipeline"], count0["pipeline"], ("linger_flushes",))
    dispatches = drive.dispatch_delta(disp1, disp0)
    smallest = min(traffic["rungs"])
    ctx = {
        "cell": cell.name, "traffic": traffic, "device_kind": device["kind"],
        "window_s": t_close - t_open, "t_open": t_open, "t_close": t_close,
        "pipeline": pipe, "counters": {"open": count0, "close": count1},
        "votes": votes1 - votes0, "dispatches": dispatches,
        "commit_times": list(sut.commit_times), "rung_votes": smallest,
        "rung_slots": smallest, "trace": tracing.reduce(), "spans": sut.spans,
        "client": {"late_ms": late_ms, "lat_ms": lat_ms},
        "quorum_delay_ms": peers.quorum_delay_ms(powers, delays, own=0),
    }
    in_window_shed = sut.admission_shed() - shed0
    extra = {"never_committed": len(out["never"]), "event_wrong": out["event_wrong"]}
    diagnostics = {
        "window_s": t_close - t_open, "rate_tps": rate, "txs_in_window": len(judged),
        "lead_txs": judged[0], "warm_s": warm_s, "sign_wait_s": sign_wait_s,
        "steps": pipe["steps"], "dispatches": dispatches,
        "votes_per_step": (votes1 - votes0) / max(pipe["steps"], 1),
        "sender_late_p95_ms": stats.percentile(late_ms, 95),
        "sender_late_max_ms": max(late_ms),
        "injector_late_p95_ms": stats.percentile(injector.late_ns or [0], 95) / 1e6,
        "injector_late_max_ms": max(injector.late_ns or [0]) / 1e6,
        "commit_max_ms": max(lat_ms), "refused_in_window": len(out["refused"]),
        "shed_in_window": in_window_shed,
        "shed_in_lead": shed0, "gc": gclog.within(t_open, t_close),
        "listener_error": reply["listener_error"], "host_prep": sut.host_prep(),
        # an injector that was refused (a full vote pool) stops; its txs then never
        # commit and the run reads not correct, with the counters beside it
        "injector_error": repr(injector.error) if injector.error else None,
        "vote_pool_size": sut.node.tx_vote_pool.size(),
        "compile_cache": sut.cache_dir,
    }
    rng = random.Random(opt.seed)
    acked = out["acknowledged"] or [n_txs - 1]
    picks = drive.sample(rng, 0, len(acked), int(traffic["compare_txs"]))
    sample = [acked[j] for j in picks]
    return drive.finish(
        cell, opt, device, sut, corp, attempted=len(judged), failed=failed,
        sample=sample, own_power=powers[0], extra_numbers=extra,
        end_to_end=end_to_end, ctx=ctx, diagnostics=diagnostics,
    )
