"""Run one cell: set-up, the measured window, the close, the comparison.

Two traffic kinds, told apart by the traffic file's ``kind``: ``flood``
(votes replayed at the vote pool's ingest, closed on a bounded backlog) and
``served`` (a client process offers txs over HTTP on a fixed schedule and
watches for the commit events; the peers' votes for a tx arrive when the tx
is due). Everything a cell is made of comes from its files.
"""

from __future__ import annotations

import gc
import json
import os
import random
import shutil
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field

from . import cells, client, corpus as corpus_mod, flood, reference, stats, tracered

COMMIT_WAIT_S = 60.0  # an answer that comes late is late, not wrong
NEVER_MS = COMMIT_WAIT_S * 1e3


@dataclass
class Options:
    seed: int
    seconds: float
    trace: bool = False
    fault: str | None = None
    timeline: str | None = None  # flood study: per-step records to this file
    trace_dump: str | None = None  # the traced window's plain lists, to this file
    scalar: bool = False  # CPU rehearsal only: the scalar verifier, no chip
    overrides: dict = field(default_factory=dict)  # rehearsal sizes
    t_start: float = field(default_factory=time.monotonic)
    commit_wait_s: float = COMMIT_WAIT_S
    scratch: str = os.path.join(cells.BENCH, ".scratch")


class GcLog:
    """Pauses of Python's collector, by generation."""

    def __init__(self):
        self.pauses: list[tuple[float, int, float]] = []  # (start, gen, seconds)
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.monotonic()
        else:
            self.pauses.append((self._t0, info["generation"], time.monotonic() - self._t0))

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *_exc):
        gc.callbacks.remove(self)

    def within(self, t0: float, t1: float) -> dict:
        inside = [(g, s) for t, g, s in self.pauses if t0 <= t < t1]
        return {
            "n": len(inside),
            "total_ms": 1e3 * sum(s for _, s in inside),
            "max_ms": 1e3 * max((s for _, s in inside), default=0.0),
            "gen2": sum(1 for g, _ in inside if g == 2),
        }


class Tracing:
    """The profiler around the window's last steps.

    The step program runs some 62,000 operations a dispatch and the profiler
    takes about ten seconds to hand over each traced dispatch, so only the
    last ``steps`` dispatches of the window are traced: a watcher starts the
    profiler when what is left of the window is ``steps`` times the mean time
    between the window's dispatches so far, and ``stop`` is called once the
    window has closed and its counters are read. The profiler's minutes then
    fall outside the window, and the counters carry at most the cost of
    tracing those last steps."""

    START_S = 0.15  # start_trace itself took 20 to 90 ms (my chip runs, PR 27), with room
    MAX_S = 4.0  # never trace more of the window than this

    def __init__(self, on: bool, scratch: str, steps: int, dispatched, dump: str | None = None):
        self.on = on
        self.dump = dump
        self.dir = os.path.join(scratch, "trace")
        self.steps, self._dispatched = steps, dispatched
        self.started_before_close_s = self.stop_s = self.read_s = 0.0
        self._thread = None
        self._started = False
        self._cancel = threading.Event()

    def lookback(self, elapsed_s: float, dispatches: int) -> float:
        """Seconds before the close at which to start, from the window so far."""
        if dispatches <= 0:
            return self.MAX_S
        return min(self.MAX_S, self.steps * elapsed_s / dispatches + self.START_S)

    def arm(self, t_open: float, t_close: float) -> None:
        if not self.on:
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        self._thread = threading.Thread(
            target=self._watch, args=(t_open, t_close), name="trace-start", daemon=True
        )
        self._thread.start()

    def _watch(self, t_open: float, t_close: float) -> None:
        import jax

        first = self._dispatched()
        while not self._cancel.is_set():
            now = time.monotonic()
            if t_close - now <= self.lookback(now - t_open, self._dispatched() - first):
                break
            time.sleep(0.002)
        else:
            return
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._started = True
        self.started_before_close_s = t_close - time.monotonic()

    def stop(self) -> None:
        """After the close: end the trace (a minute or two)."""
        if not self.on:
            return
        import jax

        self._cancel.set()
        self._thread.join()
        if not self._started:
            raise RuntimeError("the window closed before the trace was started")
        t0 = time.monotonic()
        jax.profiler.stop_trace()
        self.stop_s = time.monotonic() - t0

    def reduce(self) -> dict | None:
        """Mean over the devices used of the busy seconds and of the
        window; steps, top operations and longest gaps of the first device."""
        if not self.on:
            return None
        t0 = time.monotonic()
        loaded = tracered.load_xplane(tracered.find_xplane(self.dir))
        self.read_s = time.monotonic() - t0
        if self.dump:  # a small recorded trace for the tests
            small = {
                name: {"modules": d["modules"][:6], "ops": d["ops"][:400]}
                for name, d in loaded["devices"].items()
            }
            with open(self.dump, "w") as f:
                json.dump({"devices": small, "lines": loaded["lines"]}, f)
        reduced = [tracered.reduce_device(d) for _, d in sorted(loaded["devices"].items())]
        shutil.rmtree(self.dir, ignore_errors=True)
        if not reduced:
            raise RuntimeError(f"the trace holds no device plane: {loaded['lines']}")
        first = reduced[0]
        return {
            "busy_s": sum(r["busy_s"] for r in reduced) / len(reduced),
            "window_s": sum(r["window_s"] for r in reduced) / len(reduced),
            "steps": first["steps"],
            "device_ops": first["device_ops"],
            "idle_gaps": first["idle_gaps"],
            "took": (self.started_before_close_s, self.stop_s, self.read_s),
        }


def device_info(scalar: bool, chips: int) -> dict:
    """The device as JAX reports it. Without an accelerator, or with
    fewer chips than the cell asks for, there is no run."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if not scalar:
        if dev.platform == "cpu":
            raise SystemExit(
                "perfbench: JAX found no accelerator (platform 'cpu'); a cell is "
                "measured on the chip only"
            )
        if len(devices) < chips:
            raise SystemExit(
                f"perfbench: the cell asks for {chips} chips, JAX has {len(devices)}"
            )
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}


def memory_peak(chips: int) -> int:
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()[: max(1, chips)]
    ]
    return int(max(peaks))


def _delta(after: dict, before: dict, keys) -> dict:
    return {k: after[k] - before[k] for k in keys}


def _dispatch_delta(after: dict, before: dict) -> dict:
    return {k: n - before.get(k, 0) for k, n in after.items() if n - before.get(k, 0)}


def _sample(rng: random.Random, lo: int, hi: int, n: int) -> list[int]:
    """n indexes of [lo, hi) drawn from the seed, the last always in."""
    if hi - lo <= n:
        return list(range(lo, hi))
    picked = set(rng.sample(range(lo, hi - 1), n - 1))
    picked.add(hi - 1)
    return sorted(picked)


def compare(sut, corp, sample, own_power: int) -> dict:
    """The reference's numbers, each a count of violations over the sample."""
    ref = reference.Reference(corp)
    totals = dict.fromkeys(reference.NUMBERS, 0)
    for i in sample:
        rows, stored, value = sut.answer(corp, i)
        for name, n in ref.judge(i, rows, stored, value, own_power).items():
            totals[name] += n
    return totals


def finish(cell, opt, device, sut, corp, *, attempted, failed, sample, own_power,
           extra_numbers, end_to_end, ctx, diagnostics) -> dict:
    """Read the fault counters and the memory peak, free the node, run the
    reference, and put the result line together."""
    fault_counts = sut.faults()
    if not opt.scalar:
        device["memory_peak_bytes"] = memory_peak(cell.chips)
    traced = ctx.get("trace")
    if traced is not None:
        (diagnostics["trace_started_before_close_s"], diagnostics["trace_stop_s"],
         diagnostics["trace_read_s"]) = traced["took"]
        diagnostics["traced_steps"] = len(traced["steps"])
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
    sut.stop()
    diagnostics["compared_txs"] = len(sample)
    t0 = time.monotonic()
    numbers = {**compare(sut, corp, sample, own_power), **fault_counts, **extra_numbers}
    diagnostics["reference_s"] = time.monotonic() - t0
    checks = {name: {"value": n, "limit": reference.LIMIT} for name, n in numbers.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    if opt.trace:
        metrics = {}
        for m in cell.per_layer:
            value = cells.metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {
            m["name"]: {"value": end_to_end[cells.stem(m["name"])], "unit": m["unit"]}
            for m in cell.end_to_end
        }
    result = {
        "correct": correct, "attempted": attempted, "failed": failed,
        "metrics": metrics, "device": device,
    }
    if traced is not None:
        result["breakdown"] = {
            "device_ops": traced["device_ops"], "idle_gaps": traced["idle_gaps"],
        }
    result["workload"] = cell.name
    result["seed"] = opt.seed
    result["diagnostics"] = diagnostics
    result["checks"] = checks  # last: each number compared beside its limit
    return result


def set_up(config: dict, traffic: dict, opt: Options, n_txs: int, *, signers, sign: bool):
    """Build the node and warm the cell's programs while worker processes
    sign the corpus. Returns (node, corpus, seconds warming, seconds then
    still waited for the signatures)."""
    from . import node as node_mod

    builder = corpus_mod.CorpusBuilder(int(traffic["sign_workers"]))
    try:
        pending = builder.start(config, opt.seed, n_txs, int(traffic["tx_bytes"]), signers,
                                opt.fault)
        sut = node_mod.SystemUnderTest(
            config, traffic, rungs=traffic["rungs"], sign=sign, rpc=True,
            scalar=opt.scalar, fault=opt.fault, record=bool(opt.timeline),
            trace_all=opt.trace and bool(traffic.get("trace_every_tx")),
        )
        warm_s = sut.warm(traffic["warm"])
        t0 = time.monotonic()
        corp = pending.result()
        return sut, corp, warm_s, time.monotonic() - t0
    finally:
        builder.close()


def _sizes(cell, opt) -> dict:
    return cells.merged(cell.traffic, opt.overrides)


# ------------------------------------------------------------------ flood


def run_flood(cell, opt: Options) -> dict:
    device = device_info(opt.scalar, cell.chips)
    traffic = _sizes(cell, opt)
    config = cell.config
    n_vals = int(config["validators"])
    chunk, backlog = int(traffic["chunk_txs"]), int(traffic["backlog_txs"])
    rung_txs = max(traffic["rungs"]) // n_vals
    lead_txs = -(-int(traffic["lead_txs"]) // rung_txs) * rung_txs
    n_txs = int(traffic["rate_hint_tps"] * (opt.seconds + traffic["lead_s"])) + backlog + lead_txs
    n_txs = -(-n_txs // rung_txs) * rung_txs

    sut, corp, warm_s, sign_wait_s = set_up(
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
    tracing = Tracing(opt.trace, opt.scratch, int(traffic["trace_steps"]),
                      lambda: sum(sut.dispatches().values()), opt.trace_dump)
    with GcLog() as gclog:
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
        pipe0, disp0, votes0 = sut.pipeline(), sut.dispatches(), sut.routed_votes()
        fed0 = feeder.fed
        feeder.min_outstanding_before_feed = None
        feeder.max_outstanding = 0
        t_open = time.monotonic()
        setup_s = t_open - opt.t_start
        tracing.arm(t_open, t_open + opt.seconds)
        time.sleep(max(0.0, t_open + opt.seconds - time.monotonic()))
        t_close = time.monotonic()
        pipe1, disp1, votes1 = sut.pipeline(), sut.dispatches(), sut.routed_votes()
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
    pipe = _delta(pipe1, pipe0, ("steps", "prep_s", "route_s", "dispatch_wait_s"))
    pipe["full_batches"] = pipe1["coalesce"]["full_batches"] - pipe0["coalesce"]["full_batches"]
    pipe["linger_flushes"] = (
        pipe1["coalesce"]["linger_flushes"] - pipe0["coalesce"]["linger_flushes"]
    )
    dispatches = _dispatch_delta(disp1, disp0)
    top = f"{max(traffic['rungs'])}x{max(traffic['rungs'])}"
    ctx = {
        "cell": cell.name, "traffic": traffic, "device_kind": device["kind"],
        "window_s": window_s, "t_open": t_open, "t_close": t_close,
        "pipeline": pipe, "votes": votes1 - votes0, "dispatches": dispatches,
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
    sample = _sample(rng, fed0, fed0 + max(fed_window, 1), int(traffic["compare_txs"]))
    return finish(
        cell, opt, device, sut, corp, attempted=fed_window, failed=never,
        sample=sample, own_power=0, extra_numbers=extra, end_to_end=end_to_end,
        ctx=ctx, diagnostics=diagnostics,
    )


# ----------------------------------------------------------------- served


class PeerInjector(threading.Thread):
    """Plays the peers: the other validators' votes for tx i reach the
    vote pool ``peer_delay_ms`` after tx i is due, in one frame."""

    def __init__(self, sut, corp, first_tx: int, n_txs: int, t0_ns: int, rate_tps: float,
                 delay_ms: float):
        super().__init__(name="peer-injector", daemon=True)
        self._sut, self._corp, self._first, self._n = sut, corp, first_tx, n_txs
        self._t0, self._period, self._delay = t0_ns, 1e9 / rate_tps, int(delay_ms * 1e6)
        self.late_ns: list[int] = []
        self.error: BaseException | None = None

    def run(self) -> None:
        try:
            for i in range(self._n):
                due = self._t0 + int(i * self._period) + self._delay
                client.sleep_until(due)
                self.late_ns.append(time.monotonic_ns() - due)
                self._sut.deliver_tx_votes(self._corp, self._first + i, sender=1)
        except BaseException as e:
            self.error = e


def start_client(job: dict) -> subprocess.Popen:
    """The client in a process of its own, which never imports JAX."""
    env = dict(os.environ)
    env["PYTHONPATH"] = cells.ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen(
        [sys.executable, "-m", "perfbench.harness.client", json.dumps(job)],
        cwd=cells.ROOT, env=env, stdin=subprocess.DEVNULL, stdout=subprocess.PIPE,
    )


def served_phase(sut, corp, traffic, *, first_tx: int, n_txs: int, rate_tps: float,
                 wait_s: float, start_in_s: float = 2.0):
    """Offer txs [first_tx, first_tx + n_txs) at rate_tps. Returns the
    schedule's t0 (monotonic ns), the client process and the injector."""
    host, port = sut.node.rpc.addr
    t0_ns = time.monotonic_ns() + int(start_in_s * 1e9)
    job = {
        "host": host, "port": port, "t0_ns": t0_ns, "rate_tps": rate_tps,
        "n_txs": n_txs, "first_tx": first_tx, "tx_bytes": int(traffic["tx_bytes"]),
        "tag": corp.tag.decode(), "senders": int(traffic["senders"]), "wait_s": wait_s,
    }
    proc = start_client(job)
    injector = PeerInjector(
        sut, corp, first_tx, n_txs, t0_ns, rate_tps, float(traffic["peer_delay_ms"])
    )
    injector.start()
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


def served_outcomes(reply: dict, t0_ns: int, period_ns: float, first: int, last: int) -> dict:
    """What became of the txs [first, last) that were due in the window.

    A tx the front door refused (a 429 when the node sheds, any answer but
    ``code 0``) or whose commit event never came counts ``NEVER_MS`` in the
    percentiles and in ``failed``. Only an acknowledged tx that never
    commits breaks a guarantee (``never``): a refusal is an answer, and
    after a stall of seconds (these machines have them, about one in an
    hour of runs) an open-loop client sends what is overdue at once and a
    sound node sheds part of it."""
    lat_ms, late_ms, refused, never, acknowledged, event_wrong = [], [], [], [], [], 0
    for i in range(first, last):
        due = t0_ns + int(i * period_ns)
        sent = reply["sent_ns"][i]
        late_ms.append((sent - due) / 1e6 if sent else NEVER_MS)
        if reply["status"][i] != 0:
            refused.append(i)
            lat_ms.append(NEVER_MS)
            continue
        acknowledged.append(i)
        event = reply["event_ns"][i]
        if not event:
            never.append(i)
            lat_ms.append(NEVER_MS)
            continue
        if reply["event_code"][i] != 0:
            event_wrong += 1
        lat_ms.append((event - due) / 1e6)
    return {"lat_ms": lat_ms, "late_ms": late_ms, "refused": refused, "never": never,
            "acknowledged": acknowledged, "event_wrong": event_wrong}


def run_served(cell, opt: Options) -> dict:
    device = device_info(opt.scalar, cell.chips)
    traffic = _sizes(cell, opt)
    config = cell.config
    n_vals = int(config["validators"])
    rate = float(traffic["rate_tps"])
    n_lead = max(1, round(rate * traffic["lead_s"]))
    n_win = max(1, round(rate * opt.seconds))
    n_txs = n_lead + n_win

    sut, corp, warm_s, sign_wait_s = set_up(
        config, traffic, opt, n_txs, signers=list(range(1, n_vals)), sign=True
    )

    tracing = Tracing(opt.trace, opt.scratch, int(traffic["trace_steps"]),
                      lambda: sum(sut.dispatches().values()), opt.trace_dump)
    with GcLog() as gclog:
        sut.start()
        gc.collect()  # a full collection takes a quarter second here: before the lead-in
        t0_ns, proc, injector = served_phase(
            sut, corp, traffic, first_tx=0, n_txs=n_txs, rate_tps=rate,
            wait_s=opt.commit_wait_s,
        )
        try:
            t_open = (t0_ns + int(n_lead * 1e9 / rate)) / 1e9
            time.sleep(max(0.0, t_open - time.monotonic()))
            if sut.compiles is not None:
                sut.compiles.mark()
            pipe0, disp0, votes0 = sut.pipeline(), sut.dispatches(), sut.routed_votes()
            shed0 = sut.admission_shed()
            setup_s = t_open - opt.t_start
            t_close = t_open + n_win / rate
            tracing.arm(t_open, t_close)
            time.sleep(max(0.0, t_close - time.monotonic()))
            pipe1, disp1, votes1 = sut.pipeline(), sut.dispatches(), sut.routed_votes()
            tracing.stop()
            reply = collect_client(proc, timeout=opt.commit_wait_s + 30)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        injector.join(timeout=10)
    out = served_outcomes(reply, t0_ns, 1e9 / rate, n_lead, n_txs)
    lat_ms, late_ms = out["lat_ms"], out["late_ms"]
    failed = len(out["refused"]) + len(out["never"])
    end_to_end = {
        "commit_p50_ms": stats.percentile(lat_ms, 50),
        "commit_p95_ms": stats.percentile(lat_ms, 95),
        "setup_s": setup_s,
    }
    pipe = _delta(pipe1, pipe0, ("steps", "prep_s", "route_s", "dispatch_wait_s"))
    pipe["linger_flushes"] = (
        pipe1["coalesce"]["linger_flushes"] - pipe0["coalesce"]["linger_flushes"]
    )
    dispatches = _dispatch_delta(disp1, disp0)
    smallest = min(traffic["rungs"])
    ctx = {
        "cell": cell.name, "traffic": traffic, "device_kind": device["kind"],
        "window_s": t_close - t_open, "t_open": t_open, "t_close": t_close,
        "pipeline": pipe, "votes": votes1 - votes0, "dispatches": dispatches,
        "commit_times": list(sut.commit_times), "rung_votes": smallest,
        "rung_slots": smallest, "trace": tracing.reduce(), "spans": sut.spans,
        "client": {"late_ms": late_ms, "lat_ms": lat_ms},
    }
    in_window_shed = sut.admission_shed() - shed0
    extra = {"never_committed": len(out["never"]), "event_wrong": out["event_wrong"]}
    diagnostics = {
        "window_s": t_close - t_open, "rate_tps": rate, "txs_in_window": n_win,
        "lead_txs": n_lead, "warm_s": warm_s, "sign_wait_s": sign_wait_s,
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
    picks = _sample(rng, 0, len(acked), int(traffic["compare_txs"]))
    sample = [acked[j] for j in picks]
    return finish(
        cell, opt, device, sut, corp, attempted=n_win, failed=failed,
        sample=sample, own_power=int(config["stake_each"]), extra_numbers=extra,
        end_to_end=end_to_end, ctx=ctx, diagnostics=diagnostics,
    )


KINDS = {"flood": run_flood, "served": run_served}


def run_cell(cell, opt: Options) -> dict:
    kind = cell.traffic["kind"]
    if kind not in KINDS:
        raise KeyError(f"traffic kind {kind!r} is not one of {sorted(KINDS)}")
    os.makedirs(opt.scratch, exist_ok=True)
    return KINDS[kind](cell, opt)
