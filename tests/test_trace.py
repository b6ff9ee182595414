"""Per-tx tracing (trace/): sampling determinism, ring wraparound, leak
accounting, Prometheus exposition round-trip, Chrome-trace export, the
pipelined-vs-scalar span-parity drill, the LocalNet admission->commit
end-to-end export, and the tier-1 overhead gate (<3% of a scalar
signature verify per traced vote).
"""

import conftest  # noqa: F401

import hashlib
import json
import os
import subprocess
import sys
import time

import pytest

from txflow_tpu.trace.export import merge_by_tx, to_chrome_trace, write_chrome_trace
from txflow_tpu.trace.report import critical_path, format_line, merge_critical_paths
from txflow_tpu.trace.tracer import (
    NULL_TRACER,
    SPAN_COMMIT,
    SPAN_DEVICE,
    SPAN_E2E,
    SPAN_ORDER,
    NullTracer,
    Tracer,
    make_tracer,
)
from txflow_tpu.utils.config import TraceConfig, test_config as make_test_config
from txflow_tpu.utils.metrics import Registry, parse_exposition


def _hash(i: int) -> str:
    return hashlib.sha256(b"trace-tx-%d" % i).hexdigest().upper()


# -- sampling --


def test_sampling_deterministic_and_key_agreement():
    """Same (seed, rate) => same sampled set on every node and every
    replay, and the hex-hash and raw-digest predicates agree (the pools
    sample by key, everything downstream by hex hash)."""
    a = Tracer(TraceConfig(sample_rate=8, seed=42))
    b = Tracer(TraceConfig(sample_rate=8, seed=42))
    picks = []
    for i in range(4096):
        key = hashlib.sha256(b"trace-tx-%d" % i).digest()
        h = key.hex().upper()
        assert a.sampled(h) == b.sampled(h) == a.sampled_key(key)
        picks.append(a.sampled(h))
    frac = sum(picks) / len(picks)
    assert 0.06 < frac < 0.20  # ~1/8 of a uniform hash population
    # a different seed picks a different set
    c = Tracer(TraceConfig(sample_rate=8, seed=43))
    assert [c.sampled(_hash(i)) for i in range(4096)] != picks
    # rate 1 samples everything (the tests' dense mode)
    assert all(
        Tracer(TraceConfig(sample_rate=1)).sampled(_hash(i)) for i in range(64)
    )
    # garbage hashes never sample (defensive, not an error path)
    assert not a.sampled("not-hex!")


def test_ring_wraparound():
    tr = Tracer(TraceConfig(sample_rate=1, ring_capacity=16))
    for i in range(40):
        tr.span(_hash(i), SPAN_COMMIT, float(i), float(i) + 0.5)
    spans = tr.spans()
    assert len(spans) == 16
    # oldest-first, holding exactly the LAST capacity spans
    assert [s["start"] for s in spans] == [float(i) for i in range(24, 40)]
    assert tr.dropped() == 24
    assert tr.digest()["dropped"] == 24
    tr.reset()
    assert tr.spans() == [] and tr.dropped() == 0


def test_open_span_leak_accounting():
    tr = Tracer(TraceConfig(sample_rate=1))
    s1 = tr.begin(_hash(1), SPAN_DEVICE, 1.0)
    s2 = tr.begin(_hash(2), SPAN_DEVICE, 2.0)
    assert tr.open_count() == 2
    tr.finish(s1, 1.5)
    tr.abandon(s2)  # shed work closes without recording
    assert tr.open_count() == 0
    assert [s["name"] for s in tr.spans()] == [SPAN_DEVICE]
    # finish/abandon of id 0 (the NullTracer begin() return) are no-ops
    tr.finish(0)
    tr.abandon(0)
    assert tr.open_count() == 0


def test_anchor_latch_and_fifo_bound():
    tr = Tracer(TraceConfig(sample_rate=1, ring_capacity=16))  # anchor cap 64
    for i in range(70):
        tr.anchor(_hash(i), float(i))
    # the first 6 aged out FIFO; latching them records nothing
    tr.latch(_hash(0), t=100.0)
    assert tr.spans() == []
    tr.latch(_hash(69), t=100.0)
    (span,) = tr.spans()
    assert span["name"] == SPAN_E2E and span["start"] == 69.0
    # anchor is idempotent: re-anchoring does not reset the clock
    tr.anchor(_hash(42), 1.0)
    tr.anchor(_hash(42), 50.0)
    tr.latch(_hash(42), t=60.0)
    # spans() is ordered by start (PR 28), so find the span by its tx
    (s42,) = [s for s in tr.spans() if s["tx"] == _hash(42)]
    assert s42["start"] == 42.0  # the first anchor won


def test_null_tracer_and_config_switch():
    """enabled=False must be zero-cost AND zero-state: every method is a
    constant-return no-op with the same surface as the real tracer."""
    assert make_tracer(TraceConfig(enabled=False)) is NULL_TRACER
    assert isinstance(make_tracer(TraceConfig(enabled=True)), Tracer)
    n = NullTracer()
    assert not n.active
    assert not n.sampled(_hash(1)) and not n.sampled_key(b"\x00" * 32)
    assert n.begin(_hash(1), SPAN_DEVICE) == 0
    n.span(_hash(1), SPAN_DEVICE, 0.0, 1.0)
    n.finish(0)
    n.abandon(0)
    n.anchor(_hash(1))
    assert n.anchored(_hash(1)) is None
    n.latch(_hash(1))
    assert n.open_count() == 0 and n.spans() == []
    assert n.digest()["enabled"] is False
    d = n.dump("node9")
    assert d["node"] == "node9" and d["spans"] == []


# -- metrics exposition --


def test_trace_metrics_prometheus_roundtrip():
    """The txflow_trace_* exposition must survive a scrape-parse: TYPE/
    HELP present, bucket counts cumulative and ending at +Inf, _sum and
    _count consistent with the observations."""
    reg = Registry()
    tr = Tracer(TraceConfig(sample_rate=1), registry=reg)
    for i in range(10):
        tr.span(_hash(i), SPAN_COMMIT, 0.0, 0.003)  # 3ms each
    fams = parse_exposition(reg.expose())
    name = "txflow_trace_span_commit_apply_seconds"
    assert fams[name]["type"] == "histogram"
    buckets = fams[name]["buckets"]
    assert buckets[-1][0] == "+Inf" and buckets[-1][1] == 10
    counts = [c for _, c in buckets]
    assert counts == sorted(counts)  # cumulative
    assert fams[name]["samples"][f"{name}_count"] == 10
    assert abs(fams[name]["samples"][f"{name}_sum"] - 0.03) < 1e-9
    assert fams["txflow_trace_spans_recorded_total"]["samples"][
        "txflow_trace_spans_recorded_total"
    ] == 10
    # digest quantiles: all 10 observations sit in the (2.5ms, 5ms] bucket
    q = tr.digest()["latency_ms"]["commit_apply"]
    assert q["count"] == 10
    assert 2.5 <= q["p50"] <= 5.0 and 2.5 <= q["p999"] <= 5.0


# -- export --


def _fake_dumps():
    # two nodes whose monotonic clocks start at different origins but
    # whose wall clocks agree: the merge must land both on one timeline
    return [
        {
            "node": "node0", "base_wall_ns": 1_000_000_000,
            "base_mono": 100.0, "open_spans": 0, "dropped": 0,
            "spans": [
                {"tx": _hash(1), "name": "mempool_ingest",
                 "start": 100.0, "end": 100.0},
                {"tx": _hash(1), "name": "commit_apply",
                 "start": 100.2, "end": 100.3},
            ],
        },
        {
            "node": "node1", "base_wall_ns": 1_000_000_000,
            "base_mono": 500.0, "open_spans": 0, "dropped": 0,
            "spans": [
                {"tx": _hash(1), "name": "vote_ingest",
                 "start": 500.1, "end": 500.1},
            ],
        },
    ]


def test_merge_by_tx_aligns_wall_clock():
    merged = merge_by_tx(_fake_dumps())
    spans = merged[_hash(1)]
    assert [s["name"] for s in spans] == [
        "mempool_ingest", "vote_ingest", "commit_apply",
    ]  # sorted by wall-clock ts despite different mono origins
    assert spans[0]["node"] == "node0" and spans[1]["node"] == "node1"
    assert spans[1]["ts_us"] - spans[0]["ts_us"] == pytest.approx(1e5)


def test_chrome_trace_structure(tmp_path):
    doc = to_chrome_trace(_fake_dumps())
    assert doc["displayTimeUnit"] == "ms"
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["args"]["name"] for e in meta if e["name"] == "process_name"} == {
        "node0", "node1",
    }
    assert len(xs) == 3
    for e in xs:
        assert e["args"]["tx"] == _hash(1)
        assert e["dur"] >= 0.0
        # track ids follow commit-path order
        assert e["tid"] == SPAN_ORDER.index(e["name"]) + 1
    out = tmp_path / "t.json"
    assert write_chrome_trace(str(out), _fake_dumps()) == 3
    assert len(json.loads(out.read_text())["traceEvents"]) == len(doc["traceEvents"])


def test_trace_export_cli(tmp_path):
    """tools/trace_export.py merges dump files (and unwraps the RPC
    {"result": ...} envelope) into a Perfetto-openable file."""
    d0, d1 = _fake_dumps()
    p0 = tmp_path / "d0.json"
    p1 = tmp_path / "d1.json"
    p0.write_text(json.dumps(d0))
    p1.write_text(json.dumps({"result": d1}))  # as saved from a raw RPC reply
    out = tmp_path / "merged.json"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "trace_export.py"),
         str(p0), str(p1), "--out", str(out)],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, r.stderr
    assert "3 spans from 2 node(s)" in r.stdout
    assert len([
        e for e in json.loads(out.read_text())["traceEvents"] if e["ph"] == "X"
    ]) == 3


# -- critical-path attribution --


def test_critical_path_attribution():
    stats = {"prep_s": 2.0, "lock_wait_s": 0.5, "route_s": 1.0,
             "dispatch_wait_s": 6.0}
    digest = {"latency_ms": {
        "linger_bulk": {"sum_ms": 1500.0, "p50": 1.0},
        "e2e": {"p50": 30.0},
        "vote_ingest": {"p50": 0.0},
        "host_prep": {"p50": 2.0},
        "dispatch": {"p50": 0.5},
        "device_busy": {"p50": 4.5},
        "route": {"p50": 1.0},
        "quorum_latch": {"p50": 0.7},  # a child of route: not summed
        "commit_apply": {"p50": 2.0},
    }}
    cp = critical_path(stats, digest)
    assert cp["host_s"] == pytest.approx(2.5)  # prep - lock_wait + route
    assert cp["device_s"] == 6.0 and cp["lock_wait_s"] == 0.5
    assert cp["linger_s"] == 1.5
    assert cp["bound"] == "device"
    assert sum(cp["fractions"].values()) == pytest.approx(1.0, abs=0.01)
    # e2e p50 30ms minus 11ms of in-node stages = 19ms network residual
    assert cp["network_residual_ms"] == pytest.approx(19.0)
    fleet = merge_critical_paths([cp, cp])
    assert fleet["device_s"] == 12.0 and fleet["bound"] == "device"
    assert fleet["network_residual_ms"] == pytest.approx(19.0)
    assert "bound=device" in format_line(fleet)
    # empty inputs stay shaped (no div-by-zero, no fractions)
    empty = critical_path({}, {})
    assert "fractions" not in empty and "bound" not in empty
    assert merge_critical_paths([]) == {
        "host_s": 0, "device_s": 0, "lock_wait_s": 0, "linger_s": 0,
    }


# -- end-to-end: LocalNet span parity + export --


def _run_traced_net(depth: int, tag: bytes, n_txs: int = 24):
    from txflow_tpu.node import LocalNet

    cfg = make_test_config()
    cfg.trace.sample_rate = 1  # dense: every tx traced
    cfg.engine.pipeline_depth = depth
    net = LocalNet(3, config=cfg, use_device_verifier=False)
    net.start()
    try:
        from txflow_tpu.admission.controller import ErrOverloaded

        txs = [b"%s-%d=v" % (tag, i) for i in range(n_txs)]
        for i, tx in enumerate(txs):
            n0 = net.nodes[0]
            if n0.admission is not None:
                # the RPC edge: admission verdict span, then ingest. The
                # front door may shed under this burst — fine for later
                # txs (family coverage needs SOME admission spans), but
                # tx 0 anchors the ordering assertion, so it must land.
                try:
                    n0.admission.admit_rpc(tx, hashlib.sha256(tx).digest())
                except ErrOverloaded:
                    assert i > 0, "first tx must not be shed on an idle net"
            net.broadcast_tx(tx)
        assert net.wait_all_committed(txs, timeout=120.0)
        # every begun span must close once commits drained (leak gate)
        deadline = time.monotonic() + 10.0
        while any(n.tracer.open_count() for n in net.nodes):
            assert time.monotonic() < deadline, [
                n.tracer.open_count() for n in net.nodes
            ]
            time.sleep(0.05)
        return (
            net.trace_dumps(),
            [n.txflow.pipeline_stats() for n in net.nodes],
            [n.tracer.digest() for n in net.nodes],
        )
    finally:
        net.stop()


def test_localnet_trace_parity_and_export(tmp_path):
    """The pipelined engine and the serial engine must emit the same
    span families for the same workload (parity drill: instrumentation
    lives in the shared prep/submit/collect/route helpers, and a refactor
    that drops a span in one mode fails here) — and the merged export
    must cover admission -> commit for a single tx on one timeline."""
    dumps_pipe, stats_pipe, digests_pipe = _run_traced_net(3, b"tp")
    dumps_ser, _, _ = _run_traced_net(1, b"ts")

    def families(dumps):
        # linger_*, pool_wait and gc_pause excluded: deadline flushes,
        # an engine that found the pool empty, and the collector are
        # timing-dependent.
        # sync_fetch/sync_verify/sync_apply excluded: a node that
        # briefly lags its peers catches up via the sync channel —
        # whether that happens is scheduler timing and topology, not
        # engine mode, and it emits all three families together
        return {
            s["name"] for d in dumps for s in d["spans"]
        } - {"linger_bulk", "linger_prio", "pool_wait", "gc_pause",
             "sync_fetch", "sync_verify", "sync_apply"}

    fam_pipe, fam_ser = families(dumps_pipe), families(dumps_ser)
    assert fam_pipe == fam_ser
    assert {
        "admission", "mempool_ingest", "sign_wait", "sign_walk",
        "vote_ingest", "vote_wait", "pickup_wait", "host_prep", "lock_wait",
        "dispatch",
        "device_busy", "collect_wait", "route", "route_tally",
        "route_purge", "quorum_latch", "commit_apply", "publish", "e2e",
    } <= fam_pipe

    # merged view: one tx's spans cover admission -> commit_apply in
    # wall-clock order, with spans from every node (gossip + votes)
    merged = merge_by_tx(dumps_pipe)
    tx0 = hashlib.sha256(b"tp-0=v").hexdigest().upper()
    spans = merged[tx0]
    names = [s["name"] for s in spans]
    assert names[0] == "admission"
    assert "commit_apply" in names
    assert names.index("admission") < names.index("commit_apply")
    assert {s["node"] for s in spans} == {"node0", "node1", "node2"}

    out = tmp_path / "localnet_trace.json"
    n_events = write_chrome_trace(str(out), dumps_pipe)
    assert n_events == sum(len(d["spans"]) for d in dumps_pipe) > 0

    # critical-path attribution over the live run: busy seconds present,
    # fractions normalized, a bound named, network residual measurable
    # from the e2e digest
    cps = [
        critical_path(s, d) for s, d in zip(stats_pipe, digests_pipe)
    ]
    fleet = merge_critical_paths(cps)
    assert fleet["host_s"] >= 0.0 and "bound" in fleet
    assert any("e2e" in (d.get("latency_ms") or {}) for d in digests_pipe)


# -- overhead gate --


def test_trace_overhead_gate():
    """Default-on tracing must cost <3% of the verify hot path. The unit
    of work on that path is one signature verify; the tracer's per-vote
    cost at the default 1/64 sampling is one sampled() check plus 1/64
    of a span record. Measured against the repo's own scalar ed25519
    verify (the cheapest verifier this repo ever runs per vote)."""
    from txflow_tpu.crypto.ed25519 import public_key_from_seed, sign, verify

    seed = hashlib.sha256(b"trace-overhead").digest()
    pub = public_key_from_seed(seed)
    msg = b"trace-overhead-msg"
    sig = sign(seed, msg)

    n_verify = 30
    t0 = time.perf_counter()
    for _ in range(n_verify):
        assert verify(pub, msg, sig)
    per_verify = (time.perf_counter() - t0) / n_verify

    tr = Tracer(TraceConfig())  # default-on: sample_rate 64
    keys = [hashlib.sha256(b"ov-%d" % i).digest() for i in range(512)]
    hashes = [k.hex().upper() for k in keys]
    n_iter = 20_000
    t0 = time.perf_counter()
    for i in range(n_iter):
        h = hashes[i & 511]
        if tr.sampled_key(keys[i & 511]):
            tr.span(h, SPAN_COMMIT, 0.0, 0.001)
    per_vote = (time.perf_counter() - t0) / n_iter

    ratio = per_vote / per_verify
    assert ratio < 0.03, (
        f"tracing cost {per_vote * 1e6:.2f}us/vote is {ratio:.1%} of a "
        f"scalar verify ({per_verify * 1e3:.2f}ms) — over the 3% budget"
    )


def test_stage_record_overhead_gate():
    """The step record is always on: ten stage records a step, each two
    clock reads, a counter, a Prometheus counter, a ring store with its
    histogram and an inactive profiler annotation, and the two children
    of host_prep that PR 30 added (late_drop, carry_prior: two clock
    reads each and the ring store, no annotation), must cost under 50 us
    (0.01% of a 350 ms flood cycle, under 0.5% of an 11.5 ms served
    commit). The best of several rounds: the gate is on the code's cost,
    not on what else runs on the box."""
    from txflow_tpu.engine.txflow import _Stage, _annotation_cls
    from txflow_tpu.node import LocalNet
    from txflow_tpu.trace.tracer import (
        SPAN_CARRY, SPAN_DISPATCH, SPAN_LATE_DROP, SPAN_PREP, SPAN_ROUTE,
    )

    assert _annotation_cls() is not None  # JAX is here: annotations are entered
    net = LocalNet(1, use_device_verifier=False)  # never started: just an engine
    eng = net.nodes[0].txflow
    assert eng.tracer.active and eng.tracer.metrics is not None
    # the gate is on what a node pays: a plain lock, where the tests'
    # lock audit (conftest) hands the tracer an instrumented one
    import threading

    eng.tracer._lk = threading.Lock()
    from txflow_tpu.utils.clock import monotonic

    names = (SPAN_PREP, SPAN_DISPATCH, SPAN_ROUTE)
    best = clock = float("inf")
    for _ in range(30):
        t0 = time.perf_counter()
        for i in range(100):  # ten steps of ten stages
            with _Stage(eng, names[i % 3], step=i, votes=4096):
                pass
            if i % 10 == 0:  # and each step's two children of host_prep
                eng._stage_done(SPAN_LATE_DROP, monotonic(), monotonic(), i)
                eng._stage_done(SPAN_CARRY, monotonic(), monotonic(), i)
        best = min(best, (time.perf_counter() - t0) / 10)
        t0 = time.perf_counter()
        for _i in range(1000):
            monotonic()
        clock = min(clock, (time.perf_counter() - t0) / 1000)
    # 50 us where a clock read through the seam costs its usual 0.1 us;
    # the shared box sometimes runs a whole process half again slower,
    # and then the bound moves with the clock read measured beside it
    bound = 50e-6 if clock < 0.12e-6 else 520 * clock
    assert best < bound, (
        f"a step's twelve stage records cost {best * 1e6:.1f} us "
        f"(bound {bound * 1e6:.1f}, clock read {clock * 1e6:.3f} us)"
    )
    stats = eng.pipeline_stats()
    assert stats["prep_s"] > 0 and stats["route_s"] > 0  # every sink was fed
    assert len(eng.tracer.spans()) == 3600
    print(f"twelve stage records: {best * 1e6:.1f} us, clock read {clock * 1e6:.3f} us")
