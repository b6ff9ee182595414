"""Run one cell: what every traffic kind calls, and the way to the kind.

A traffic kind is the file ``perfbench/kinds/<kind>.py`` that the traffic
file's ``kind`` names (``flood``: votes replayed at the vote pool's ingest,
closed on a bounded backlog; ``served``: a client process offers txs over
HTTP on the cell's arrival schedule and watches for the commit events while
the peers' votes arrive by their delays). Here is what a kind is made from:
the options of a run, set-up, the profiler around the window's last steps,
the collector's log, the comparison and the result line. Everything a cell
is made of comes from its files.
"""

from __future__ import annotations

import gc
import json
import os
import random
import shutil
import threading
import time
from dataclasses import dataclass, field

from . import cells, corpus as corpus_mod, reference, tracered

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


def check_runs(kind: str, config: dict, runs: dict) -> None:
    """A kind builds one system under test, and says which in ``runs``: a
    configuration that states anything else (or leaves the key out) is
    refused by the key's name before any set-up, rather than run as
    something its file does not say."""
    for key, runs_value in runs.items():
        stated = config.get(key)
        if type(stated) is not type(runs_value) or stated != runs_value:
            raise ValueError(
                f"configuration {config.get('name')!r} states {key} = {stated!r}; traffic "
                f"kind {kind!r} runs {key} = {runs_value!r} and nothing else: a cell of "
                "this configuration needs a kind file that builds what it states"
            )


def pipeline_delta(close: dict, open_: dict, coalesce_keys) -> dict:
    """What readers get as ``ctx["pipeline"]``: the engine's counters over
    the window, from the two whole readings of ``pipeline_stats()``."""
    pipe = {k: close[k] - open_[k] for k in ("steps", "prep_s", "route_s", "dispatch_wait_s")}
    for k in coalesce_keys:
        pipe[k] = close["coalesce"][k] - open_["coalesce"][k]
    return pipe


def dispatch_delta(after: dict, before: dict) -> dict:
    return {k: n - before.get(k, 0) for k, n in after.items() if n - before.get(k, 0)}


def sample(rng: random.Random, lo: int, hi: int, n: int) -> list[int]:
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


def sizes(cell, opt) -> dict:
    return cells.merged(cell.traffic, opt.overrides)


def run_cell(cell, opt: Options) -> dict:
    kind = cells.kind(cell.traffic["kind"])
    os.makedirs(opt.scratch, exist_ok=True)
    return kind.run(cell, opt)
