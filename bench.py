"""North-star benchmark: BASELINE config 1, measured end to end.

Protocol (BASELINE.json config 1-2): an N-validator in-process network —
every node runs the full fast path (txvotepool -> batched device
verify+tally -> TxStore persist -> kvstore ABCI execute -> pool purge ->
commitpool) over real gossip reactors wired with in-memory pipes. Txs are
pre-seeded into every mempool and TxVotes are PREGENERATED (signing sits
outside the timed loop, per the config's "pregenerated TxVotes replayed
through txvotepool"); the timed phase streams each validator's votes into
its own node's vote pool in chunks, vote gossip fans them out, and every
node independently verifies, tallies, and commits every tx.

Metric: committed TxVotes/sec summed over nodes (votes inside commit
certificates persisted to TxStores) + p50 tx-commit latency (vote-chunk
injection -> per-node commit event). Baseline: the reference's hot path is
one pure-Go ed25519 verify per vote, single-threaded (reference
txflow/service.go:123-166, ~50-100us/verify => 10-20k votes/s/core;
BASELINE.md). vs_baseline measures against the generous end, 20,000/s,
and is null in the default run, whose engines share one verify-result
cache the reference cannot have: BENCH_SHARE_CACHE=0 gives the ratio.

Platform contract: the bench runs on the platform JAX gives it — on the
CPU only when ``BENCH_PLATFORM=cpu`` says so — in ONE process: it never
probes in a child, never re-executes itself elsewhere and starts no child
that needs the device. Its one JSON line on stdout stamps ``platform``,
``device_kind`` and ``device_count`` as JAX reports them, and any error
exits non-zero instead of printing a number. ``chip_smoke.py`` is the
command that shows the path runs on the chip; this file becomes a cell
table in the benchmark PR (ROADMAP Queue 1 item 1).
"""

import hashlib
import json
import os
import statistics
import sys
import time


def _cli_or_env(flag: str, env: str, default: str) -> str:
    if flag in sys.argv:
        return sys.argv[sys.argv.index(flag) + 1]
    return os.environ.get(env, default)


# --mesh-devices N (BENCH_MESH_DEVICES): shard the device verify across an
# N-way mesh (parallel.mesh). --host-prep-workers N (BENCH_HOST_PREP_WORKERS):
# parallelize the host prep path (sign-bytes assembly + compact-batch prep)
# across N worker threads. Both 0/1 = the single-device, serial-host default.
_MESH_DEVICES = int(_cli_or_env("--mesh-devices", "BENCH_MESH_DEVICES", "0") or 0)
_HOST_PREP_WORKERS = int(
    _cli_or_env("--host-prep-workers", "BENCH_HOST_PREP_WORKERS", "0") or 0
)
# --host-prep-backend {thread,process} (BENCH_HOST_PREP_BACKEND): run the
# host-prep pool as worker THREADS (historical default, GIL-shared) or
# worker PROCESSES over shared memory (engine.hostprep.ProcHostPrepPool —
# sidesteps the GIL for the sign-bytes/compact prep inner loops; falls
# back to threads when process spawn fails). --staging-ring N
# (BENCH_STAGING_RING): depth of the device readback ring (2 = double
# buffering, <=1 = historical synchronous readback). --wide-buckets
# (BENCH_WIDE_BUCKETS=1): let the coalescer drain the verifier ladder's
# rungs above EngineConfig.max_batch, gated by the adaptive linger
# controller's latency verdict.
_HOST_PREP_BACKEND = (
    _cli_or_env("--host-prep-backend", "BENCH_HOST_PREP_BACKEND", "thread")
    or "thread"
)
_STAGING_RING = int(_cli_or_env("--staging-ring", "BENCH_STAGING_RING", "2") or 2)
_WIDE_BUCKETS = (
    "--wide-buckets" in sys.argv
    or os.environ.get("BENCH_WIDE_BUCKETS", "0") == "1"
)
# --validators N (BENCH_VALIDATORS): validator-set size. --committee-size N
# (BENCH_COMMITTEE_SIZE): per-epoch tx-vote committee sampling (committee/)
# — only the deterministic stake-proportional sample signs, certificates
# carry >2/3 of COMMITTEE stake, and verification is one batched device
# call. 0 (default) = full-set seed behavior. The sublinear-certificate
# acceptance config is --validators 256 --committee-size 32: cert votes,
# cert bytes and votes gossiped per tx are then flat in validator count.
_N_VALIDATORS = int(_cli_or_env("--validators", "BENCH_VALIDATORS", "4") or 4)
_COMMITTEE_SIZE = int(
    _cli_or_env("--committee-size", "BENCH_COMMITTEE_SIZE", "0") or 0
)
if _MESH_DEVICES > 1:
    # the CPU platform exposes ONE device unless told otherwise, and the
    # flag is read when jax initializes its backends — so it must be in
    # the environment before ANY jax import below. Harmless on real TPU:
    # it only shapes the host platform.
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + f" --xla_force_host_platform_device_count={_MESH_DEVICES}"
        ).strip()

def _device_stamp() -> dict:
    """The platform this run is on, as JAX reports it. ``BENCH_PLATFORM=cpu``
    is the only way onto the CPU: no probe, no fallback. Asking for any
    other platform than the one JAX gives is an error."""
    want = os.environ.get("BENCH_PLATFORM")
    import jax

    if want == "cpu":
        jax.config.update("jax_platforms", "cpu")  # before any backend exists
    devices = jax.devices()
    dev = devices[0]
    if want and dev.platform != want:
        raise RuntimeError(
            f"BENCH_PLATFORM={want} but JAX runs on {dev.platform!r}"
        )
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(devices),
    }


BASELINE_VOTES_PER_SEC = 20_000.0  # reference CPU ceiling, BASELINE.md


# -- latency-SLO helpers (importable; tests/test_trace.py unit-tests
# these without running a net) --


def lane_quantiles(lat_ms: list) -> dict:
    """p50/p99/p999 (nearest-rank) of one lane's latency sample."""
    if not lat_ms:
        return {"count": 0, "p50_ms": None, "p99_ms": None, "p999_ms": None}
    s = sorted(lat_ms)
    def pick(q):
        return s[min(len(s) - 1, int(q * len(s)))]
    return {
        "count": len(s),
        "p50_ms": round(pick(0.50), 2),
        "p99_ms": round(pick(0.99), 2),
        "p999_ms": round(pick(0.999), 2),
    }


def slo_breached(result: dict, budget_ms) -> bool:
    """Did the run breach the priority-lane p99 budget? A missing lane
    measurement counts as a breach — the gate must not pass on absent
    data."""
    if budget_ms is None:
        return False
    p99 = ((result.get("lanes") or {}).get("priority") or {}).get("p99_ms")
    return p99 is None or p99 > float(budget_ms)


def run_latency_slo(device: dict) -> dict:
    """``--latency-slo``: mixed priority/bulk offered load against a
    LocalNet with the admission front door's fee-lane classifier active;
    reports per-lane p50/p99/p999 inject->commit latency plus the
    host/device critical-path attribution (trace/report.py). Uses the
    scalar verifier — this mode gates tail latency and attribution, not
    device throughput — so it runs identically on CPU and TPU hosts."""
    import statistics as _st  # noqa: F401  (parallel to run_bench imports)

    from txflow_tpu.node import LocalNet
    from txflow_tpu.trace.report import critical_path, merge_critical_paths
    from txflow_tpu.utils.config import test_config
    from txflow_tpu.utils.events import EventTx

    n_vals = _N_VALIDATORS
    n_txs = int(os.environ.get("BENCH_SLO_TXS", "256"))
    prio_frac = float(os.environ.get("BENCH_SLO_PRIORITY_FRAC", "0.25"))
    pace_tps = float(os.environ.get("BENCH_SLO_PACE_TPS", "200"))
    # --net-profile <name> (BENCH_NET_PROFILE): run the SLO under WAN
    # weather (netem/) — every link shaped + the adaptive peer transport
    # on; the result stamps the profile and per-peer RTT/loss so two runs
    # under different weather are comparable at a glance
    net_profile = _cli_or_env("--net-profile", "BENCH_NET_PROFILE", "") or None
    net_seed = int(_cli_or_env("--net-seed", "BENCH_NET_SEED", "11") or 11)
    cfg = test_config()
    cfg.mempool.size = max(cfg.mempool.size, 8 * n_txs)
    cfg.mempool.cache_size = max(cfg.mempool.cache_size, 2 * cfg.mempool.size)
    cfg.trace.sample_rate = int(os.environ.get("BENCH_SLO_SAMPLE_RATE", "4"))
    # the latency mode opts into the full p50 toolkit: deadline-aware
    # lane split (on by default), speculative quorum commit (off by
    # default globally — commit ORDER may shift across txs, certificates
    # don't), and adaptive linger steering against the SLO budget
    cfg.engine.speculative_commit = (
        os.environ.get("BENCH_SLO_SPECULATIVE", "1") == "1"
    )
    cfg.engine.adaptive_linger = (
        os.environ.get("BENCH_SLO_ADAPTIVE_LINGER", "1") == "1"
    )
    if os.environ.get("BENCH_SLO_BUDGET_MS"):
        cfg.engine.slo_budget_ms = float(os.environ["BENCH_SLO_BUDGET_MS"])
    net = LocalNet(
        n_vals,
        chain_id="txflow-bench",
        config=cfg,
        use_device_verifier=False,
        index_txs=False,
        netem=net_profile,
        netem_seed=net_seed,
    )

    # deterministic lane mix: every ceil(1/frac)-th tx carries a
    # fee-prefix above the classifier threshold and rides priority
    stride = max(1, round(1.0 / prio_frac)) if prio_frac > 0 else 0
    corpus = []  # (tx, is_priority)
    for i in range(n_txs):
        if stride and i % stride == 0:
            corpus.append((b"fee=9;p%d=v" % i, True))
        else:
            corpus.append((b"slo-b%d=v" % i, False))

    commit_times = [dict() for _ in net.nodes]

    def make_cb(idx):
        def cb(ev):
            commit_times[idx][ev.data.tx_hash] = time.perf_counter()
        return cb

    for i, node in enumerate(net.nodes):
        node.event_bus.subscribe_callback(EventTx, make_cb(i))

    net.start()
    inject_t: dict[str, float] = {}
    lane_of: dict[str, bool] = {}
    t0 = time.perf_counter()
    interval = 1.0 / pace_tps if pace_tps > 0 else 0.0
    for i, (tx, prio) in enumerate(corpus):
        if interval:
            delay = t0 + i * interval - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
        tx_hash = hashlib.sha256(tx).hexdigest().upper()
        node = net.nodes[i % len(net.nodes)]
        inject_t[tx_hash] = time.perf_counter()
        lane_of[tx_hash] = prio
        node.broadcast_tx(tx)
    ok = net.wait_all_committed([tx for tx, _ in corpus], timeout=300.0)
    if not ok:
        raise RuntimeError("timeout waiting for commits")

    lat = {"priority": [], "bulk": []}
    for times in commit_times:
        for tx_hash, t_inj in inject_t.items():
            t_c = times.get(tx_hash)
            if t_c is not None:
                lane = "priority" if lane_of[tx_hash] else "bulk"
                lat[lane].append((t_c - t_inj) * 1e3)

    pipe_stats = [n.txflow.pipeline_stats() for n in net.nodes]
    per_node = [
        critical_path(s, n.tracer.digest())
        for s, n in zip(pipe_stats, net.nodes)
    ]
    trace_digest = net.nodes[0].tracer.digest()
    network = None
    if net_profile is not None:
        # per-link weather observations (RTT/loss from the in-band pings,
        # shaper counters) — captured BEFORE stop so estimators are live
        peers = {}
        shaper_snap = None
        for node in net.nodes:
            snap = node.switch.net_snapshot()
            shaper_snap = snap.get("shaper") or shaper_snap
            for pid, ps in (snap.get("peers") or {}).items():
                peers[f"{node.node_id}->{pid}"] = {
                    "rtt_ms": ps.get("rtt_ms"),
                    "loss": ps.get("loss"),
                    "quarantined": ps.get("quarantined"),
                }
        # ONE shaper serves the whole LocalNet: any node's view is the
        # aggregate
        network = {
            "profile": net_profile,
            "seed": net_seed,
            "peers": peers,
            "shaper": shaper_snap,
        }
    net.stop()
    lanes = {k: lane_quantiles(v) for k, v in lat.items()}
    return {
        "metric": "latency_slo",
        "net_profile": net_profile,
        "network": network,
        "lanes": lanes,
        # headline numbers at the top level so the bank's supersede rule
        # (and a human eyeballing the artifact) need no nested digging
        "priority_p50_ms": (lanes.get("priority") or {}).get("p50_ms"),
        "priority_p99_ms": (lanes.get("priority") or {}).get("p99_ms"),
        # engine-side lane/spec accounting, summed over nodes
        "lane_stats": {
            "prio_batches": sum(
                (s.get("lanes") or {}).get("prio_batches", 0)
                for s in pipe_stats
            ),
            "prio_votes": sum(
                (s.get("lanes") or {}).get("prio_votes", 0)
                for s in pipe_stats
            ),
        },
        "spec_stats": {
            "enabled": cfg.engine.speculative_commit,
            "commits": sum(
                (s.get("spec") or {}).get("commits", 0) for s in pipe_stats
            ),
            "saved_s": round(
                sum(
                    (s.get("spec") or {}).get("saved_s", 0.0)
                    for s in pipe_stats
                ),
                4,
            ),
        },
        "adaptive_linger": next(
            (
                s["adaptive_linger"]
                for s in pipe_stats
                if s.get("adaptive_linger")
            ),
            None,
        ),
        "critical_path": merge_critical_paths(per_node),
        "critical_path_per_node": per_node,
        "trace_latency_ms": trace_digest.get("latency_ms", {}),
        "trace_sample_rate": trace_digest.get("sample_rate"),
        **device,
        "validators": n_vals,
        "nodes": len(commit_times),
        "txs": n_txs,
        "priority_frac": prio_frac,
        "pace_tps": pace_tps,
    }


def run_bench(device: dict) -> dict:
    from txflow_tpu.node import LocalNet
    from txflow_tpu.types import TxVote
    from txflow_tpu.utils.events import EventTx

    n_vals = _N_VALIDATORS
    # --stake-dist {uniform,whale,longtail} (or BENCH_STAKE_DIST): run the
    # same corpus under a non-uniform stake distribution (faults/stake.py).
    # Uniform powers never exercise the interesting quorum geometry — a
    # whale's single vote being 1/3+ of total, or a long tail where 2n/3
    # needs most of the set — and throughput can differ because quorums
    # latch after different vote counts per tx.
    from txflow_tpu.faults.stake import gini, stake_distribution

    stake_dist = os.environ.get("BENCH_STAKE_DIST", "uniform")
    if "--stake-dist" in sys.argv:
        stake_dist = sys.argv[sys.argv.index("--stake-dist") + 1]
    if stake_dist not in ("uniform", "whale", "longtail"):
        raise ValueError(
            f"--stake-dist must be uniform|whale|longtail, got {stake_dist!r}"
        )
    stake_powers = stake_distribution(
        stake_dist, n_vals, seed=int(os.environ.get("BENCH_STAKE_SEED", "0"))
    )
    # On the CPU (BENCH_PLATFORM=cpu) the TPU-shaped curve kernel is ~100x
    # slower than host crypto, so the default verifier there is the scalar
    # host verifier behind the same VoteVerifier interface, with a smaller
    # corpus (SURVEY §7 hard-part 1); the line says so ("verifier").
    on_cpu = device["platform"] == "cpu"
    verifier_kind = os.environ.get("BENCH_VERIFIER", "scalar" if on_cpu else "device")
    n_txs = int(os.environ.get("BENCH_TXS", "512" if on_cpu else "8192"))
    chunk = int(os.environ.get("BENCH_CHUNK", "512" if on_cpu else "2048"))
    warm_txs = min(64 if on_cpu else 1024, n_txs)

    import hashlib as _h

    from txflow_tpu.types.priv_validator import MockPV
    from txflow_tpu.types.validator import Validator, ValidatorSet

    priv_vals = [
        MockPV(_h.sha256(b"localnet-val%d" % i).digest()) for i in range(n_vals)
    ]
    val_set = ValidatorSet(
        [
            Validator.from_pub_key(pv.get_pub_key(), p)
            for pv, p in zip(priv_vals, stake_powers)
        ]
    )
    # --committee-size N: sample the static (epoch-0) committee exactly as
    # every node will (same chain_id, same sha256 seed domain), so the
    # bench can pregenerate votes for COMMITTEE MEMBERS ONLY — that is
    # the sublinear claim: votes gossiped per tx, certificate votes and
    # verify cost all track committee size, not validator count
    committee_set = None
    signer_idx = list(range(n_vals))
    epoch_config = None
    if _COMMITTEE_SIZE > 0:
        from txflow_tpu.committee import sample_committee
        from txflow_tpu.epoch import EpochConfig

        epoch_config = EpochConfig(committee_size=_COMMITTEE_SIZE)
        committee_set = sample_committee(
            val_set, "txflow-bench", 0, _COMMITTEE_SIZE,
            min_size=epoch_config.committee_min_size,
            min_stake_frac=epoch_config.committee_min_stake_frac,
        )
        members = {v.address for v in committee_set}
        signer_idx = [
            i for i, pv in enumerate(priv_vals) if pv.get_address() in members
        ]
    # the set the verifiers stage on: the committee IS the tally set in
    # committee mode (its quorum_power() is the committee quorum)
    engine_val_set = committee_set if committee_set is not None else val_set

    shared_verifier = None
    device_verifier = None
    warm_registry = None
    if verifier_kind == "device":
        # ONE verifier for all nodes (same validator set): shared device
        # epoch tables, and a single bucket so exactly one kernel shape
        # compiles (the persistent cache then makes reruns warm-start)
        from txflow_tpu.verifier import DeviceVoteVerifier

        bucket = int(os.environ.get("BENCH_BUCKET", "4096"))
        # cross-engine verify-result cache (verifier.VerifyCache): the 4
        # co-located engines see the same gossiped votes; without it each
        # unique vote is device-verified 4x for zero information
        share_cache = os.environ.get("BENCH_SHARE_CACHE", "1") == "1"
        # two buckets: per-engine batches compile at `bucket`; the mux's
        # merged cross-engine batches land in the 4x bucket
        mesh = None
        if _MESH_DEVICES > 1:
            from txflow_tpu.parallel.mesh import make_mesh

            # fewer devices than asked for raises: no silent one-device run
            mesh = make_mesh(_MESH_DEVICES)
        shared_verifier = DeviceVoteVerifier(
            engine_val_set, buckets=(bucket, 4 * bucket), shared_cache=share_cache,
            mesh=mesh, host_prep_workers=_HOST_PREP_WORKERS,
            host_prep_backend=_HOST_PREP_BACKEND, staging_ring=_STAGING_RING,
        )
        device_verifier = shared_verifier  # pre-mux handle for prep stats
        t0 = time.time()
        # warm every shape the run can hit (verifier.warmup full=True:
        # the cached path's _verify_only miss ladder, or the no-cache
        # fused combos) — a cold shape would compile mid-measurement.
        # The registry snapshots the warm set so the result JSON can
        # PROVE the timed phase ran compile-free (r5 postmortem: one
        # missed shape buried the headline under ~160 s of compile).
        from txflow_tpu.engine import ShapeWarmRegistry

        warm_registry = ShapeWarmRegistry(shared_verifier)
        warm_shapes = warm_registry.prewarm(full=True)
        print(
            f"bench: kernel warm in {time.time()-t0:.1f}s "
            f"({len(warm_shapes)} shapes)",
            file=sys.stderr,
        )

        # supplementary metric: steady-state device-step throughput at the
        # bucket size (prep + kernel + packed readback, no pools/gossip/
        # commit) — the capability ceiling the end-to-end number runs under
        import numpy as _np

        _n = bucket
        _sigs = [b"\x00" * 64] * _n
        _vidx = _np.zeros(_n, _np.int64)
        _slot = _np.arange(_n, dtype=_np.int64) % max(_n // n_vals, 1)

        def _probe_msgs(it):
            # distinct per iteration: with the shared VerifyCache on, a
            # repeated batch would measure cache hits, not device work
            return [b"kbench-%d-%d" % (it, i) for i in range(_n)]

        shared_verifier.verify_and_tally(_probe_msgs(-1), _sigs, _vidx, _slot, _n)
        _t0 = time.time()
        for _it in range(3):
            shared_verifier.verify_and_tally(_probe_msgs(_it), _sigs, _vidx, _slot, _n)
        device_step_votes_per_sec = round(3 * _n / (time.time() - _t0), 1)
        print(
            f"bench: device step {device_step_votes_per_sec:.0f} votes/s",
            file=sys.stderr,
        )

        # measured on-TPU: merged cross-engine batches LOST ~17% end to end
        # (10.6k vs 12.7k votes/s) — per-vote kernel cost is nearly flat in
        # batch size (27.6 us at 4096 vs 25.6 at 16384), so the mux's
        # padding waste on partial merges + gather latency outweigh the
        # ~8 ms fixed per-call cost it amortizes. Kept opt-in for hardware
        # where the fixed per-call cost is larger.
        if os.environ.get("BENCH_MUX", "0") == "1":
            from txflow_tpu.verifier import VerifierMux

            shared_verifier = VerifierMux(
                shared_verifier,
                max_batch_per_caller=bucket,
                gather_wait=float(os.environ.get("BENCH_MUX_WAIT", "0.02")),
            )
            shared_verifier.start()
    elif committee_set is not None:
        # committee mode on the CPU fallback: ONE BatchCertVerifier
        # staged on the committee, shared by all nodes — every engine
        # verify batch is a single fused ed25519_batch dispatch (the
        # verifier's batch_calls counter is stamped into the result as
        # evidence). No verify cache: the cache-claim protocol is a
        # per-signature loop and would defeat the one-call-per-batch
        # claim this config exists to measure.
        from txflow_tpu.committee import BatchCertVerifier

        shared_verifier = BatchCertVerifier(engine_val_set)
    else:
        # CPU fallback: ONE scalar verifier with the cross-engine verify
        # cache shared by all nodes — host ed25519 is ~269 us/verify on
        # this class of core, and without the cache every vote pays it
        # once per node
        from txflow_tpu.verifier import ScalarVoteVerifier

        if os.environ.get("BENCH_SHARE_CACHE", "1") == "1":
            shared_verifier = ScalarVoteVerifier(val_set, shared_cache=True)

    from txflow_tpu.utils.config import test_config

    cfg = test_config()
    # pools must hold the whole pregenerated corpus (default caps mirror the
    # reference's 5000-tx mempool; the bench replays n_txs + warmup at once)
    cfg.mempool.size = max(cfg.mempool.size, 4 * (n_txs + warm_txs) * (n_vals + 1))
    cfg.mempool.cache_size = max(cfg.mempool.cache_size, 2 * cfg.mempool.size)
    if verifier_kind == "device":
        # one device step has a fixed cost (kernel + single packed
        # readback) regardless of fill, so hold steps until they approach
        # the bucket instead of firing at the CPU-tuned 256
        cfg.engine.min_batch = int(os.environ.get("BENCH_MIN_BATCH", "3072"))
        # at saturation the pool always holds >= min_batch so the hold
        # never fires; it only delays LIGHT-load steps, i.e. it is pure
        # added latency in the p50 phase — keep it short
        cfg.engine.batch_wait = float(os.environ.get("BENCH_BATCH_WAIT", "0.05"))
    # amortize the ABCI app-Commit fence over groups of fast-path commits
    # (per-tx delivery/certificates/events unchanged; engine/execution.py
    # apply_tx_batch). 1 = reference-faithful per-tx fence.
    # measured on-TPU: per-tx fencing (1) beat interval 16 end-to-end
    # (12.7k vs 9.7k votes/s) — the fence is not the binding cost there
    cfg.engine.commit_interval = int(os.environ.get("BENCH_COMMIT_INTERVAL", "1"))
    cfg.engine.idle_flush = float(os.environ.get("BENCH_IDLE_FLUSH", cfg.engine.idle_flush))
    # verify tickets in flight per engine (<=1 = serial reference loop)
    cfg.engine.pipeline_depth = int(
        os.environ.get("BENCH_PIPELINE_DEPTH", cfg.engine.pipeline_depth)
    )
    # shape-stable coalescing: engines dispatch only canonical bucket
    # sizes (full buckets, or linger flushes padded to one) so every
    # batch lands on a prewarmed shape — compile_in_run == 0 by design
    cfg.engine.coalesce = os.environ.get("BENCH_COALESCE", "1") == "1"
    cfg.engine.coalesce_linger = float(
        os.environ.get("BENCH_COALESCE_LINGER", cfg.engine.coalesce_linger)
    )
    # adaptive pipeline depth from the live overlap ratio (opt-in: the
    # banked baselines were measured at fixed depth)
    cfg.engine.adaptive_depth = os.environ.get("BENCH_ADAPTIVE_DEPTH", "0") == "1"
    # background warmup instead of the blocking prewarm above (opt-in —
    # the bench's default contract prewarms fully so the timed phase is
    # provably compile-free; this exercises the serve-while-compiling
    # path: cold batches take the scalar fallback until promotion)
    cfg.engine.background_warmup = (
        os.environ.get("BENCH_BACKGROUND_WARMUP", "0") == "1"
    )
    # mesh-sharded verify + multi-worker host prep: the shared verifier
    # above already carries the mesh; mirroring the knobs into the engine
    # config makes the coalescer round bucket targets to shard
    # divisibility and wires each engine's prep loop to the (shared)
    # host-prep pool
    cfg.engine.mesh_devices = _MESH_DEVICES
    cfg.engine.host_prep_workers = _HOST_PREP_WORKERS
    cfg.engine.host_prep_backend = _HOST_PREP_BACKEND
    cfg.engine.staging_ring = _STAGING_RING
    cfg.engine.wide_buckets = _WIDE_BUCKETS

    # BASELINE config 5: BENCH_CONSENSUS=1 runs the block-path ticker
    # DURING the vote flood (blocks carry the fast-path commits as Vtxs).
    # Blocks tick at a REAL commit cadence: with skip_timeout_commit the
    # ticker fires back-to-back and reaps every tx into block.Txs before
    # the fast path's batching window elapses (measured: 29 blocks, zero
    # fast-path certificates) — which measures the fallback, not the
    # fast path the config exists to exercise.
    with_consensus = os.environ.get("BENCH_CONSENSUS", "0") == "1"
    if with_consensus:
        cfg.consensus.skip_timeout_commit = False
        cfg.consensus.timeout_commit = float(
            os.environ.get("BENCH_TIMEOUT_COMMIT", "1.0")
        )

    # 16/64-validator configs host 4 full nodes: the other validators'
    # votes are pregenerated and replayed (indistinguishable from votes
    # gossiped in from remote peers), so the run scales the REAL config
    # 2-3 axes — [V] epoch-table gather, 2/3-of-64 quorum math, votes/tx
    # volume — without co-locating 64 full-mesh nodes in one process
    # (~4k threads on one core: the r5 64-val run never finished).
    # consensus-enabled runs default to hosting EVERY validator: the
    # block path needs 2/3 of the consensus voters present. That caps how
    # large a consensus bench can be — co-locating tens of full-mesh
    # nodes in one process measures thread thrash, not the protocol (the
    # 64-node r5 run never finished) — so fail fast instead of hanging.
    if with_consensus and n_vals > 8:
        raise ValueError(
            f"BENCH_CONSENSUS=1 hosts all {n_vals} validators as full "
            "in-process nodes; beyond 8 that topology thrashes one host "
            "(use <= 8 validators for consensus-enabled runs)"
        )
    default_nodes = n_vals if with_consensus else min(n_vals, 4)
    n_nodes = int(os.environ.get("BENCH_NODES", str(default_nodes)))
    if with_consensus and n_nodes < n_vals:
        # the block path needs 2/3 of the CONSENSUS voters hosted; with a
        # 4-of-16 subset blocks can never commit and the run would
        # silently measure zero consensus interference (config 5's whole
        # point). Host every validator for consensus-enabled runs.
        raise ValueError(
            f"BENCH_CONSENSUS=1 requires hosting all {n_vals} validators "
            f"(BENCH_NODES={n_nodes}): a hosted subset cannot reach block "
            "quorum"
        )
    net = LocalNet(
        n_vals,
        chain_id="txflow-bench",
        config=cfg,
        use_device_verifier=verifier_kind == "device",
        sign=False,  # pregenerated-vote replay: no signTxRoutine
        mempool_broadcast=False,  # txs are pre-seeded on every node
        priv_vals=priv_vals,
        verifier=shared_verifier,
        enable_consensus=with_consensus,
        index_txs=False,  # nothing queries /tx_search during the bench
        n_nodes=n_nodes,
        voting_powers=stake_powers,
        epoch_config=epoch_config,
    )

    # -- pregenerate txs + every validator's votes (untimed) --
    # BASELINE config 4 (adversarial mix): --byzantine-frac 0.25 (or
    # BENCH_BYZANTINE=0.25) corrupts that fraction of validator 0's
    # signatures; quorum still forms from the honest 3/4, the invalid
    # votes burn verify work, and the run asserts none of them ever lands
    # in a commit certificate.
    byz_frac = float(_cli_or_env("--byzantine-frac", "BENCH_BYZANTINE", "0") or 0)

    # committee mode: ONLY committee members sign — that is the gossip
    # saving itself (votes per tx = committee size). The latency probe
    # anchors on the first signer, which is validator 0 only when it made
    # the sample.
    probe_vi = signer_idx[0]

    def make_corpus(tag: str, count: int):
        txs = [b"%s-%d=v" % (tag.encode(), i) for i in range(count)]
        votes_by_val: list[list[TxVote]] = [[] for _ in range(n_vals)]
        for t_i, tx in enumerate(txs):
            tx_key = hashlib.sha256(tx).digest()
            tx_hash = tx_key.hex().upper()
            for vi in signer_idx:
                pv = net.priv_vals[vi]
                vote = TxVote(
                    height=0,
                    tx_hash=tx_hash,
                    tx_key=tx_key,
                    validator_address=pv.get_address(),
                )
                pv.sign_tx_vote("txflow-bench", vote)
                if vi == 0 and byz_frac > 0 and (t_i % 100) < byz_frac * 100:
                    sig = bytearray(vote.signature)
                    sig[7] ^= 0xFF
                    vote.signature = bytes(sig)
                votes_by_val[vi].append(vote)
        return txs, votes_by_val

    warm_corpus = make_corpus("warm", warm_txs)
    main_corpus = make_corpus("tx", n_txs)

    # commit-latency probes: per node, tx_hash -> commit wall time
    commit_times: list[dict[str, float]] = [dict() for _ in net.nodes]

    def make_cb(idx):
        def cb(ev):
            commit_times[idx][ev.data.tx_hash] = time.perf_counter()

        return cb

    for i, node in enumerate(net.nodes):
        node.event_bus.subscribe_callback(EventTx, make_cb(i))

    net.start()

    def seed_and_replay(txs, votes_by_val, chunk_size, pace_votes_per_sec=0.0):
        """Seed txs everywhere, then stream votes in chunks; returns
        (wall_seconds, inject_time per tx_hash). With a pace, chunks are
        released on a fixed schedule (offered load) instead of back to
        back — that is what makes the measured commit latency a SERVICE
        latency rather than a saturated-queue depth."""
        # txs are seeded per chunk, right before their votes: seeding the
        # whole corpus up front lets the block ticker (BENCH_CONSENSUS=1)
        # reap not-yet-voted txs into blocks and front-run the replayed
        # vote flood (measured: negative commit "latencies", zero
        # fast-path certificates) — in a live system a validator signs
        # within milliseconds of mempool arrival, which per-chunk seeding
        # models and up-front seeding does not.
        inject_t: dict[str, float] = {}
        t0 = time.perf_counter()
        chunk_interval = (
            (chunk_size * len(signer_idx)) / pace_votes_per_sec
            if pace_votes_per_sec
            else 0.0
        )
        for i, base in enumerate(range(0, len(txs), chunk_size)):
            if chunk_interval:
                target = t0 + i * chunk_interval
                delay = target - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
            # batched seeding: one lock-group ingest per (node, chunk)
            # instead of a lock acquire + notify per item on this thread
            # (r5 instrumented profile: 32768 per-vote check_tx calls)
            tx_chunk = txs[base : base + chunk_size]
            for node in net.nodes:
                node.mempool.check_tx_many(tx_chunk)
            t_chunk = time.perf_counter()
            # validator vi's votes enter at node vi % n_nodes: with more
            # validators than hosted nodes (configs 2-3) the extra
            # validators' votes arrive as if gossiped in from remote
            # peers, spread across the hosted nodes' ingest points
            for vi in signer_idx:
                node = net.nodes[vi % len(net.nodes)]
                vote_chunk = votes_by_val[vi][base : base + chunk_size]
                if vi == probe_vi:
                    for vote in vote_chunk:
                        inject_t[vote.tx_hash] = t_chunk
                node.tx_vote_pool.check_tx_many(vote_chunk)
        ok = net.wait_all_committed(txs, timeout=600.0)
        wall = time.perf_counter() - t0
        if not ok:
            raise RuntimeError("timeout waiting for commits")
        return wall, inject_t

    def p50_of(inject_t) -> float:
        lat_ms = []
        for times in commit_times:
            for tx_hash, t_inj in inject_t.items():
                t_c = times.get(tx_hash)
                if t_c is not None:
                    lat_ms.append((t_c - t_inj) * 1e3)
        return statistics.median(lat_ms) if lat_ms else float("nan")

    # warmup: compiles every kernel shape + exercises the full pipeline
    seed_and_replay(*warm_corpus, chunk)
    warm_committed = net.committed_votes_total()

    # phase 1 — THROUGHPUT: the whole corpus offered as fast as possible
    wall, _ = seed_and_replay(*main_corpus, chunk)
    committed = net.committed_votes_total() - warm_committed
    votes_per_sec = committed / wall

    # Residual-compile guard (r5 postmortem: a 169 s phase 1 contained
    # ~160 s of ONE remote kernel compile for a shape the warmup missed,
    # and the contaminated 580-votes/s headline got banked). End-to-end
    # throughput can be host-bound to a fraction of the device-step rate,
    # but a result BELOW device_step/5 is not a steady state this
    # pipeline can produce — by then the compile is banked in the
    # persistent cache, so one rerun with a fresh corpus measures clean.
    phase1_rerun = False
    first_pass_votes_per_sec = votes_per_sec
    audit_corpora = [main_corpus]
    if (
        verifier_kind == "device"
        and device_step_votes_per_sec > 0
        and votes_per_sec < device_step_votes_per_sec / 5
    ):
        print(
            f"bench: phase 1 at {votes_per_sec:.0f} votes/s << device step "
            f"{device_step_votes_per_sec:.0f} — suspected in-run compile; "
            "re-measuring once",
            file=sys.stderr,
        )
        rerun_corpus = make_corpus("rerun", n_txs)
        audit_corpora.append(rerun_corpus)
        before = net.committed_votes_total()
        wall2, _ = seed_and_replay(*rerun_corpus, chunk)
        committed2 = net.committed_votes_total() - before
        rerun_votes_per_sec = committed2 / wall2
        phase1_rerun = True
        if rerun_votes_per_sec > 2 * votes_per_sec:
            # materially faster warm rerun CONFIRMS the compile theory:
            # report the warm steady state as the headline
            committed, wall, votes_per_sec = committed2, wall2, rerun_votes_per_sec

    # phase 2 — LATENCY: a smaller corpus offered at ~60% of measured
    # capacity, in small chunks, so p50 reflects pipeline service time.
    # The pacing axis must match the capacity axis: seed_and_replay paces
    # INJECTED votes (n_txs * n_vals unique votes per run), so capacity is
    # measured on that same axis from phase 1's wall clock — votes_per_sec
    # (committed, summed over nodes) is ~n_nodes x larger and would pace
    # the wrong load (r3 review finding).
    injected_per_sec = (n_txs * len(signer_idx)) / wall
    p50 = float("nan")
    if os.environ.get("BENCH_LATENCY", "1") == "1":
        lat_txs = max(64, min(n_txs // 4, 2048))
        lat_corpus = make_corpus("lat", lat_txs)
        lat_chunk = max(8, min(chunk // 8, 256))
        _, inject_t = seed_and_replay(
            *lat_corpus, lat_chunk, 0.6 * injected_per_sec
        )
        p50 = p50_of(inject_t)

    # phase 2b — LATENCY SWEEP (judge r4 item 9: the reference's headline
    # is realtime per-tx commit): p50 at light offered loads, where the
    # engine's idle_flush mode should commit a tx's vote burst without
    # sitting out the full batch_wait. BENCH_LATENCY_SWEEP=0 skips.
    latency_sweep = {}
    if (
        os.environ.get("BENCH_LATENCY", "1") == "1"
        and os.environ.get("BENCH_LATENCY_SWEEP", "1") == "1"
    ):
        for frac in (0.1, 0.3):
            sw_txs = max(32, lat_txs // 4)
            sw_corpus = make_corpus("sweep%d" % int(frac * 100), sw_txs)
            _, sw_inject = seed_and_replay(
                *sw_corpus, max(4, lat_chunk // 4), frac * injected_per_sec
            )
            latency_sweep["p50_ms_at_%d%%" % int(frac * 100)] = round(
                p50_of(sw_inject), 2
            )
        latency_sweep["p50_ms_at_60%"] = round(p50, 2)

    result = {
        "metric": "committed_txvotes_per_sec",
        "value": round(votes_per_sec, 1),
        "unit": "votes/s",
        "vs_baseline": round(votes_per_sec / BASELINE_VOTES_PER_SEC, 3),
        # None, not NaN: json.dumps renders NaN as a bare token that
        # strict RFC-8259 parsers (jq, Go) reject
        "p50_commit_latency_ms": round(p50, 2) if p50 == p50 else None,
        "latency_offered_load": "60% of measured throughput",
        **({"latency_sweep": latency_sweep} if latency_sweep else {}),
        **device,
        "verifier": verifier_kind,
        "validators": n_vals,
        "nodes": len(net.nodes),
        "txs": n_txs,
        "committed_votes": committed,
        "wall_s": round(wall, 3),
        "app_commit_interval": cfg.engine.commit_interval,
        # stake geometry of the run: the Gini coefficient summarizes how
        # concentrated the distribution was (0 = uniform), so two runs'
        # numbers are comparable without re-deriving the power list
        "stake_dist": stake_dist,
        "stake_gini": round(gini(stake_powers), 4),
        # sublinear-certificate axes (committee/): 0 committee_size =
        # full-set seed behavior — legacy bank entries without the key
        # default to 0 on load, so every entry is comparable
        "committee_size": committee_set.size() if committee_set is not None else 0,
        "votes_gossiped_per_tx": len(signer_idx),
    }
    # measured certificate geometry, from committed certs (not the model):
    # in committee mode vote count must track COMMITTEE quorum, flat in
    # validator count; in full-set mode this documents the linear cost
    # the committee config removes
    from txflow_tpu.types import encode_tx_vote as _enc_vote

    cert_votes = []
    cert_bytes = []
    for tx in main_corpus[0][:16]:
        cvs = net.nodes[0].tx_store.load_tx_votes(
            hashlib.sha256(tx).hexdigest().upper()
        )
        if cvs:
            cert_votes.append(len(cvs))
            cert_bytes.append(sum(len(_enc_vote(v)) for v in cvs))
    if cert_votes:
        result["cert_votes"] = round(sum(cert_votes) / len(cert_votes), 1)
        result["cert_bytes"] = round(sum(cert_bytes) / len(cert_bytes))
    if committee_set is not None and hasattr(shared_verifier, "batch_calls"):
        # evidence the verify path was the fused one: device dispatches
        # vs per-signature fallthroughs for small batches
        result["cert_verify_batch_calls"] = shared_verifier.batch_calls
        result["cert_verify_scalar_calls"] = shared_verifier.scalar_calls
        result["cert_verify_batched_votes"] = shared_verifier.batched_votes
    if verifier_kind == "device":
        result["device_step_votes_per_sec"] = device_step_votes_per_sec
    if phase1_rerun:
        # both passes recorded: a reader must be able to tell a CONFIRMED
        # compile (rerun much faster -> rerun is the headline) from a
        # genuine bottleneck (rerun similar -> FIRST pass stays headline)
        result["phase1_first_pass_votes_per_sec"] = round(
            first_pass_votes_per_sec, 1
        )
        result["phase1_rerun_votes_per_sec"] = round(rerun_votes_per_sec, 1)
        result["phase1_compile_confirmed"] = (
            rerun_votes_per_sec > 2 * first_pass_votes_per_sec
        )
    if byz_frac > 0:
        result["byzantine_fraction"] = byz_frac
        byz_addr = net.priv_vals[0].get_address()
        # corrupted votes must never appear in a certificate: validator 0's
        # honest vote for a corrupted slot was never injected, so its
        # address simply must be absent from those txs' certificates
        bad = 0
        # per-corpus enumerate: make_corpus corrupts by each tx's index
        # WITHIN ITS OWN corpus — a concatenated walk would audit honest
        # slots (spurious failure) and skip corrupted ones (r5 review)
        audit_txs = [
            (t_i, tx) for corpus in audit_corpora for t_i, tx in enumerate(corpus[0])
        ]
        for node in net.nodes:
            for t_i, tx in audit_txs:
                if (t_i % 100) < byz_frac * 100:
                    votes = node.tx_store.load_tx_votes(
                        hashlib.sha256(tx).hexdigest().upper()
                    )
                    if votes and byz_addr in {v.validator_address for v in votes}:
                        bad += 1
        result["byzantine_votes_in_certificates"] = bad
        # where the adversarial load was absorbed: pre-verify gate drops
        # (unknown/stale/replayed, before any device work) vs invalid
        # verdicts (paid for a verify slot). Direct pool injection skips
        # the gossip reactor, so drops here come from replay/stale
        # filtering only — the gossip-path gate is drilled in
        # tests/test_byzantine_gossip.py.
        snaps = [n.byzantine_ledger.snapshot() for n in net.nodes]
        pre_drops = sum(s["pre_verify_drops"] for s in snaps)
        invalid = sum(
            int(n.txflow.metrics.invalid_votes.value()) for n in net.nodes
        )
        verified = sum(
            int(n.txflow.metrics.verified_votes.value()) for n in net.nodes
        )
        result["byzantine_pre_verify_drops"] = pre_drops
        result["byzantine_pre_verify_drop_rate"] = round(
            pre_drops / max(pre_drops + verified + invalid, 1), 4
        )
        result["byzantine_invalid_votes"] = invalid
        if bad:
            # a corrupted signature landing in a commit certificate is a
            # soundness regression, not a perf data point — fail loudly
            raise AssertionError(
                f"{bad} byzantine votes appeared in commit certificates"
            )
    if with_consensus:
        result["consensus"] = True
        result["block_height"] = max(n.block_store.height() for n in net.nodes)
    # verify-pipeline overlap: device-busy / engine-active wall time,
    # averaged over nodes (1.0 = verify calls back to back; low values
    # mean host prep/routing dominates — see COMPONENTS.md for tuning)
    pipe_stats = [n.txflow.pipeline_stats() for n in net.nodes]
    ratios = [s["overlap_ratio"] for s in pipe_stats if s["overlap_ratio"] is not None]
    result["pipeline_depth"] = cfg.engine.pipeline_depth
    if ratios:
        result["overlap_ratio"] = round(sum(ratios) / len(ratios), 4)
    # shape-stable coalescing audit (engine._BatchCoalescer, summed over
    # nodes): coalesced_batches dispatched at exactly a canonical bucket
    # (zero padding), linger_flushes partial by deadline, and
    # cold_fallback_votes served on the CPU path while background warmup
    # compiled their shape (0 unless BENCH_BACKGROUND_WARMUP=1)
    # host-prep attribution: sign-bytes assembly wall time and pool-shard
    # wait summed over engines, plus the shared verifier's compact-prep
    # split — this is what the ">= 2x host-prep reduction on a mesh"
    # acceptance check reads
    result["mesh_devices"] = (
        getattr(device_verifier, "_n_shards", 1)
        if device_verifier is not None
        else 0
    )
    result["host_prep_workers"] = _HOST_PREP_WORKERS
    # live backend, per node (a failed process spawn falls back to
    # threads — the result records what actually ran, so bank entries
    # from process- and thread-backend runs are comparable by label)
    backends = {
        s.get("host_prep_backend") for s in pipe_stats
        if s.get("host_prep_backend")
    }
    result["host_prep_backend"] = (
        sorted(backends)[0] if len(backends) == 1
        else (sorted(backends) or None)
    )
    host_prep = {
        "sign_s": round(sum(s.get("prep_sign_s", 0.0) for s in pipe_stats), 4),
        "pool_wait_s": round(
            sum(s.get("prep_pool_wait_s", 0.0) for s in pipe_stats), 4
        ),
    }
    if device_verifier is not None:
        ps = device_verifier.prep_stats()
        host_prep["compact_s"] = round(ps.get("compact_s", 0.0), 4)
        host_prep["compact_pool_wait_s"] = round(
            ps.get("compact_pool_wait_s", 0.0), 4
        )
        pool = getattr(device_verifier, "_host_pool", None)
        pool_stats = pool.stats() if pool is not None else {}
        if pool_stats.get("backend") == "process":
            # shared-memory traffic of the process backend: segment
            # bytes shipped per prep call (engine.hostprep _run_typed)
            host_prep["shm_calls"] = pool_stats.get("shm_calls", 0)
            host_prep["shm_bytes_total"] = pool_stats.get(
                "shm_bytes_total", 0
            )
            host_prep["proc_wait_s"] = round(
                pool_stats.get("proc_wait_s", 0.0), 4
            )
    result["host_prep"] = host_prep
    # double-buffered readback: ring depth + the hidden-overlap ledger
    # (parallel.staging; readback seconds that ran under the engine's
    # next-batch prep instead of on the critical path)
    result["staging_ring"] = _STAGING_RING
    ring_stats = [s.get("staging") for s in pipe_stats if s.get("staging")]
    if device_verifier is not None and not ring_stats:
        dv_ring = device_verifier.staging_stats()
        if dv_ring is not None:
            ring_stats = [dv_ring]
    if ring_stats:
        # engines share the verifier's ring: the snapshots are the same
        # counters, take the freshest rather than summing duplicates
        ring = max(ring_stats, key=lambda r: r.get("slots_total", 0))
        result["staging"] = {
            "depth": ring.get("depth"),
            "slots_total": ring.get("slots_total", 0),
            "readback_s": round(ring.get("readback_s", 0.0), 4),
            "hidden_s": round(ring.get("hidden_s", 0.0), 4),
            "overlap_frac": round(
                ring.get("hidden_s", 0.0) / ring["readback_s"], 4
            ) if ring.get("readback_s") else 0.0,
        }
    coalesce = [s.get("coalesce") or {} for s in pipe_stats]
    result["coalesced_batches"] = sum(c.get("full_batches", 0) for c in coalesce)
    result["linger_flushes"] = sum(c.get("linger_flushes", 0) for c in coalesce)
    result["cold_fallback_votes"] = sum(
        c.get("cold_fallback_votes", 0) for c in coalesce
    )
    if cfg.engine.adaptive_depth:
        depths = [
            (s.get("adaptive_depth") or {}).get("depth") for s in pipe_stats
        ]
        result["adaptive_depth_final"] = [d for d in depths if d is not None]
    if warm_registry is not None:
        # compile-contamination audit: warm_shapes is the prewarmed set,
        # cold_shapes every shape that compiled DURING the timed phases
        result["warm_shapes"] = len(warm_registry.warmed)
        cold = warm_registry.cold_shapes()
        result["compile_in_run"] = bool(cold)
        if cold:
            result["cold_shapes"] = [list(s) for s in cold]
    else:
        # scalar runs have no device programs — nothing can compile
        # mid-run; emit the key anyway so --assert-warm and dashboards
        # read one schema
        result.setdefault("compile_in_run", False)
    if shared_verifier is not None and hasattr(shared_verifier, "stop"):
        result["verifier_mux"] = True
        net.stop()
        shared_verifier.stop()
    else:
        net.stop()
    return result


_ARTIFACT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "bench_artifacts")


def _is_contaminated(entry: dict) -> bool:
    """Did this banked measurement's timed phase contain a compile?

    Explicit ``contaminated`` flag first (written by every bank since the
    supersede rule landed). Legacy entries are judged by their own
    evidence: a recorded in-run compile, or — for entries banked before
    ``compile_in_run`` existed at all — a measurement_note that already
    declares itself compromised/superseded (the r5 580-votes/s entry)."""
    if entry.get("contaminated") is not None:
        return bool(entry["contaminated"])
    if entry.get("compile_in_run"):
        return True
    note = str(entry.get("measurement_note", "")).lower()
    return "compile_in_run" not in entry and (
        "contaminated" in note or "superseded" in note
    )


_COMMITTEE_LATEST = os.path.join(_ARTIFACT_DIR, "committee_latest.json")


def _bank_committee_result(result: dict) -> None:
    """Persist committee-mode measurements under the clean-supersede
    contract: a clean run always overwrites, a contaminated run (a
    compile inside its timed phase) never displaces a clean banked
    entry. Banked on any platform: the committee_size / cert_votes /
    votes_gossiped_per_tx geometry is platform-independent evidence."""
    try:
        os.makedirs(_ARTIFACT_DIR, exist_ok=True)
        result = dict(
            result,
            measured_at_unix=round(time.time(), 1),
            contaminated=bool(result.get("compile_in_run")),
        )
        existing = _load_banked_committee()
        if (
            existing is not None
            and result["contaminated"]
            and not _is_contaminated(existing)
        ):
            return
        with open(_COMMITTEE_LATEST, "w") as f:
            f.write(json.dumps(result))
    except OSError:
        pass


def _load_banked_committee() -> dict | None:
    try:
        with open(_COMMITTEE_LATEST) as f:
            return json.loads(f.read())
    except (OSError, ValueError):
        return None


_LATENCY_LATEST = os.path.join(_ARTIFACT_DIR, "latency_latest.json")


def _latency_clean(entry: dict) -> bool:
    """Is this latency-SLO measurement fit to be the banked reference?

    Clean means the run actually measured the priority lane (p50 AND p99
    present), finished without an error, and did not breach its own SLO
    gate. Mirrors _is_contaminated's spirit for the throughput bank: a
    banked artifact that mostly measured a timeout is worse than a stale
    clean one."""
    if entry.get("error"):
        return False
    if entry.get("slo_breach"):
        return False
    return (
        entry.get("priority_p50_ms") is not None
        and entry.get("priority_p99_ms") is not None
    )


def _bank_latency_result(result: dict) -> None:
    """Persist the latency-SLO measurement under the clean-supersede
    contract: a clean run always overwrites; a dirty run (error / breach
    / missing lane data) never displaces a clean banked entry — so a
    latency regression cannot silently replace the reference numbers it
    regressed from."""
    try:
        os.makedirs(_ARTIFACT_DIR, exist_ok=True)
        result = dict(result, measured_at_unix=round(time.time(), 1))
        existing = _load_banked_latency()
        if (
            existing is not None
            and not _latency_clean(result)
            and _latency_clean(existing)
        ):
            return
        with open(_LATENCY_LATEST, "w") as f:
            f.write(json.dumps(result))
    except OSError:
        pass


def _load_banked_latency() -> dict | None:
    try:
        with open(_LATENCY_LATEST) as f:
            return json.loads(f.read())
    except (OSError, ValueError):
        return None


def _stamp_lint(result: dict) -> None:
    """Stamp the tree's static-analysis posture into the result.

    A banked measurement is only trustworthy if the code that produced it
    held the repo invariants the txlint passes encode (no hot-loop syncs,
    no recompile hazards, ...). The digest fingerprints the lint REPORT —
    rule inventory plus every (path, line, rule) finding — so two results
    with equal digests ran under the identical lint verdict, and a result
    from a dirty tree says so on its face. Never fails the bench."""
    try:
        from txflow_tpu.analysis import core as _lint_core

        report = _lint_core.lint_tree(os.path.dirname(os.path.abspath(__file__)))
        blob = json.dumps(
            {
                "rules": sorted(_lint_core.RULES),
                "violations": [
                    [v.path, v.line, v.rule] for v in report["violations"]
                ],
                "suppressed": len(report["suppressed"]),
                "files": report["files_scanned"],
            },
            sort_keys=True,
        )
        result["lint"] = {
            "clean": not report["violations"] and not report["errors"],
            "digest": hashlib.sha256(blob.encode()).hexdigest()[:12],
        }
    except Exception as e:  # pragma: no cover - never block a measurement
        result["lint"] = {"clean": None, "error": repr(e)[:120]}


def main():
    device = _device_stamp()
    from txflow_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()  # before the first compile
    if "--latency-slo" in sys.argv:
        # tail-latency SLO gate (mirror of --assert-warm's contract: the
        # result line prints, then a breach exits 3). An error in the run
        # is an error of the bench: it propagates and exits non-zero.
        budget = os.environ.get("BENCH_SLO_P99_MS")
        if "--slo-p99-ms" in sys.argv:
            budget = sys.argv[sys.argv.index("--slo-p99-ms") + 1]
        result = run_latency_slo(device)
        if budget is not None:
            result["slo_p99_ms"] = float(budget)
            result["slo_breach"] = slo_breached(result, budget)
        _stamp_lint(result)
        _bank_latency_result(result)
        print(json.dumps(result))
        if result.get("slo_breach"):
            p99 = ((result.get("lanes") or {}).get("priority") or {}).get(
                "p99_ms"
            )
            print(
                f"bench: --latency-slo failed: priority-lane p99 {p99} ms "
                f"over budget {budget} ms",
                file=sys.stderr,
            )
            sys.exit(3)
        return
    result = run_bench(device)
    if _COMMITTEE_SIZE == 0 and os.environ.get("BENCH_SHARE_CACHE", "1") == "1":
        # one process per chip: the no-cache number cannot come from a
        # child of this process (the parent holds the device), so the
        # line says which run gives it — and carries no ratio of its own:
        # the shared-cache value over the reference's rate is the inflated
        # comparison vs_baseline must never be
        result["vs_baseline"] = None
        result["metric_definition"] = (
            "committed certificate votes summed over all co-located "
            "nodes per wall second; default config shares one verify-"
            "result cache across the nodes' engines, which the reference "
            "cannot do: run with BENCH_SHARE_CACHE=0 for the number to "
            "set against it"
        )
    # stamp before banking so bank entries carry the lint posture too
    _stamp_lint(result)
    if _COMMITTEE_SIZE > 0 and result.get("value", 0) > 0:
        _bank_committee_result(result)
    print(json.dumps(result))
    if "--assert-warm" in sys.argv or os.environ.get("BENCH_ASSERT_WARM") == "1":
        # CI gate for the shape-stable hot path: with prewarm enabled the
        # steady state must be compile-free — any in-run compile (a shape
        # the registry failed to enumerate, or prewarm off) fails the run
        # AFTER the result line so the measurement is still recorded
        if result.get("compile_in_run"):
            print(
                "bench: --assert-warm failed: hot path compiled in-run "
                f"(cold shapes: {result.get('cold_shapes')})",
                file=sys.stderr,
            )
            sys.exit(3)


if __name__ == "__main__":
    main()
