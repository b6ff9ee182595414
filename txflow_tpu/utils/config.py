"""Configuration (reference: tendermint TOML ``cfg.Config`` passed to NewNode).

Defaults mirror tendermint v0.31.2's: mempool size/caps
(txvotepool/txvotepool.go:198-208 reads config.Mempool), consensus timeouts
(consensus/state.go:809-816), instrumentation toggles. Plain dataclasses —
load/save as JSON or TOML-ish dicts; no CLI layer exists in the reference
(it is a library), and none is required here.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field, asdict


@dataclass
class MempoolConfig:
    size: int = 5000
    max_txs_bytes: int = 1024 * 1024 * 1024  # 1GB
    cache_size: int = 10000
    max_msg_bytes: int = 1024 * 1024  # max gossip msg (consensus/reactor.go:28)
    broadcast: bool = True
    wal_dir: str = ""  # empty = WAL disabled

    @property
    def wal_enabled(self) -> bool:
        return self.wal_dir != ""


@dataclass
class ConsensusConfig:
    # all in seconds (reference uses ms in TOML)
    timeout_propose: float = 3.0
    timeout_propose_delta: float = 0.5
    timeout_prevote: float = 1.0
    timeout_prevote_delta: float = 0.5
    timeout_precommit: float = 1.0
    timeout_precommit_delta: float = 0.5
    timeout_commit: float = 1.0
    skip_timeout_commit: bool = False
    create_empty_blocks: bool = True
    create_empty_blocks_interval: float = 0.0
    peer_gossip_sleep: float = 0.1
    peer_query_maj23_sleep: float = 2.0
    wal_dir: str = ""

    def propose_timeout(self, round_: int) -> float:
        return self.timeout_propose + self.timeout_propose_delta * round_

    def prevote_timeout(self, round_: int) -> float:
        return self.timeout_prevote + self.timeout_prevote_delta * round_

    def precommit_timeout(self, round_: int) -> float:
        return self.timeout_precommit + self.timeout_precommit_delta * round_


@dataclass
class P2PConfig:
    laddr: str = "tcp://0.0.0.0:26656"
    persistent_peers: str = ""
    max_num_inbound_peers: int = 40
    max_num_outbound_peers: int = 10
    send_rate: int = 5 * 1024 * 1024
    recv_rate: int = 5 * 1024 * 1024
    flush_throttle: float = 0.1
    pex: bool = True


@dataclass
class RPCConfig:
    laddr: str = "tcp://127.0.0.1:26657"
    max_open_connections: int = 900


@dataclass
class InstrumentationConfig:
    prometheus: bool = False
    prometheus_listen_addr: str = ":26660"
    namespace: str = "txflow"


@dataclass
class EngineConfig:
    """Fast-path aggregation engine (no reference analog; device batching).

    The reference processes votes one at a time (txflow/service.go:123-166);
    these knobs govern the batched device pipeline that replaces it.
    """

    max_batch: int = 16384  # votes per device step
    max_slots: int = 4096  # concurrent in-flight txs per step
    use_device: bool = True  # False = scalar golden verifier (debug)
    poll_interval: float = 0.002  # seconds to wait when the pool is empty
    # batch forming: hold a step for up to batch_wait while fewer than
    # min_batch votes are pending, so streaming arrivals coalesce into
    # device-sized batches instead of overhead-dominated tiny kernel calls
    min_batch: int = 256
    batch_wait: float = 0.004
    # light-load latency mode: while coalescing, if no new vote arrives
    # for this long and work is already pending, process what we have
    # instead of sitting out the full batch_wait — at 10% offered load a
    # tx's votes arrive as one burst and then stall, so waiting for
    # min_batch only adds latency (r4 verdict item 9: the reference's
    # headline is realtime per-tx commit, README.md:10). 0 disables.
    # With a coalescer a held batch that completes a tx's quorum flushes
    # at once; this bounds the holds that decide no tx.
    idle_flush: float = 0.002
    # verify pipeline: how many device verify calls the engine keeps in
    # flight via the verifier's submit/collect split (verifier.VerifyTicket).
    # At 2, batch N+1's host prep (drain + sign bytes + prepare_compact)
    # and batch N-1's commit routing overlap batch N's kernel execution;
    # tickets are collected in submission order, so commit certificates
    # stay bit-identical to the serial path. <=1 = serial reference loop.
    pipeline_depth: int = 2
    # adaptive pipeline depth: let an AdaptiveDepthController grow/shrink
    # the pipelined loop's in-flight ticket budget between
    # [pipeline_depth_min, pipeline_depth_max] from the live overlap
    # ratio (engine.adaptive; closes the ROADMAP static-depth item).
    # pipeline_depth above stays the starting point. Off by default:
    # deterministic depth is what the banked bench baselines were tuned
    # at, and the controller needs windows of steps to say anything.
    adaptive_depth: bool = False
    pipeline_depth_min: int = 2
    pipeline_depth_max: int = 8
    # shape-stable batch coalescing (engine.txflow._BatchCoalescer): when
    # the verifier exposes canonical buckets, dispatch only full-bucket
    # batches (zero padding waste, always-prewarmed shapes) and hold
    # partial ones until the held votes, with the stake already routed,
    # complete some tx's quorum (flushed at once: nothing still to come
    # can change that tx), else until coalesce_linger elapses from the
    # first held vote, then flush whatever coalesced (padded to its
    # bucket — still a canonical shape). coalesce_linger bounds every
    # hold that decides no tx. Scalar verifiers have no buckets and keep
    # the min_batch/batch_wait forming logic unchanged.
    coalesce: bool = True
    coalesce_linger: float = 0.004
    # prewarm every kernel shape the verify pipeline can produce at
    # start() (engine.shapes.ShapeWarmRegistry) so no cold compile lands
    # inside the pipeline. Off by default: tests build engines constantly
    # and the full warmup compiles the whole bucket ladder; bench/nodes
    # that own a device verifier opt in.
    prewarm_shapes: bool = False
    # background warmup (engine.shapes.BackgroundWarmer): serve from
    # start() with ZERO blocking compile — a side thread walks the shape
    # enumeration compiling cold shapes while batches whose shape is
    # still cold route through the scalar/CPU fallback, then promote to
    # the device the moment their shape lands. The streaming alternative
    # to prewarm_shapes' stop-the-world warmup.
    background_warmup: bool = False
    # overlap commit side-effects (TxStore persist, ABCI execute, pool
    # purge) with the next device verify call via a per-engine committer
    # thread (SURVEY §7 hard-part 5); False = reference-faithful inline
    # commits inside the step
    pipeline_commits: bool = True
    # group commit: the committer fences the ABCI app Commit once per up-to-
    # this-many fast-path txs instead of per tx (reference: strictly per tx,
    # txflowstate/execution.go:112-155). Each tx still gets its own
    # DeliverTx, TxStore certificate, mempool removal, and commit event —
    # only the app-Commit fence is amortized. Requires the app's hash to be
    # a function of applied txs, not of Commit call cadence (true of the
    # kvstore/counter apps and of the handshake replay path, which replays
    # per tx). 1 = reference-faithful.
    commit_interval: int = 1
    # mesh-sharded verify (parallel/mesh.py): shard each padded device
    # batch data-parallel across this many devices of the default
    # backend (one psum tally per step). 0 or 1 = single-device verify.
    # Bucket widths are rounded up to mesh divisibility by the verifier
    # and the coalescer, so the warm bucket ladder is unchanged in
    # count — only in width — and epoch restages stay zero-recompile.
    mesh_devices: int = 0
    # sharded host-prep pool (engine/hostprep.py): worker threads that
    # parallelize sign-bytes assembly and nibble/window prep. The native
    # prep (_prep.so) releases the GIL inside ctypes, so sharding rows
    # across workers is real parallelism even on GIL builds. 0 = serial
    # prep on the engine thread (reference behavior).
    host_prep_workers: int = 0
    # host-prep backend (engine.hostprep.make_host_pool): "thread" keeps
    # the caller-steals thread pool; "process" runs worker PROCESSES that
    # assemble sign-bytes/compact arrays into shared-memory segments —
    # past the GIL entirely, for the pure-Python prep slices threads
    # can't parallelize. Degrades to "thread" automatically when workers
    # can't spawn; assembled batches are byte-identical either way.
    host_prep_backend: str = "thread"
    # double-buffered device readback (parallel.staging.StagingRing):
    # depth of the readback ring on the device verifier. At 2, batch N's
    # device_put + dispatch overlaps batch N-1's packed readback (the
    # ring thread pulls results eagerly); <=1 restores the synchronous
    # readback at collect. Certificates are byte-identical either way —
    # the ring only moves WHERE np.asarray runs.
    staging_ring: int = 2
    # wide coalescer rungs (engine.txflow._BatchCoalescer): let the bulk
    # lane target bucket-ladder rungs ABOVE max_batch (the verifier's
    # ladder already compiles them) so per-call overhead amortizes over
    # bigger steps at sustained load. Gated by the AdaptiveLingerController
    # when adaptive_linger is on — wide rungs disarm the moment the SLO
    # bank runs hot, so latency never pays for the amortization. Off by
    # default: the banked bench baselines were tuned at the classic cap.
    wide_buckets: bool = False
    # deadline-aware verify lanes (engine.txflow): split the drain into
    # a PRIORITY lane — the pool's priority ingest log (admission fee
    # lanes), dispatched in small short-linger batches AHEAD of the bulk
    # backlog — and a BULK lane keeping today's throughput linger. With
    # no admission wiring the priority log stays empty and the lane
    # costs one decide(0) per fill pass.
    lane_split: bool = True
    # priority-lane linger: how long a partial priority batch may
    # coalesce before flushing (the deadline the lane exists to honor);
    # the bulk lane keeps coalesce_linger
    priority_linger: float = 0.001
    # largest priority-lane dispatch: bucket-ladder rungs at or under
    # this (rounded up to the mesh shard multiple, PR 10) are the lane's
    # full-batch targets; with no ladder (scalar verifier) the lane
    # dispatches at this cap
    priority_bucket_cap: int = 512
    # adaptive per-lane linger (engine.adaptive.AdaptiveLingerController):
    # steer both lane lingers from the live trace digest against
    # slo_budget_ms. Off by default — it needs an active tracer and
    # windows of traffic to say anything.
    adaptive_linger: bool = False
    slo_budget_ms: float = 50.0
    # speculative quorum commit (engine.txflow._route_result): at collect
    # time, route votes whose slot's device tally readback already shows
    # 2n/3 stake FIRST, so their commits leave for the committer before
    # the rest of the batch routes. The host TxVoteSet still decides
    # every quorum (the device bit is only a routing-ORDER hint, it may
    # be stale in either direction under pipelining) — certificates stay
    # byte-identical to the scalar golden path. Off by default: the
    # early exit reorders commits ACROSS txs within a batch, and the
    # serial-vs-pipelined golden tests pin strict commit order; the
    # latency bench and latency-sensitive deployments opt in.
    speculative_commit: bool = False


@dataclass
class TraceConfig:
    """Per-transaction tracing (trace/tracer.py; default-ON).

    ``sample_rate`` is 1-in-N txs by hash (deterministic across nodes
    and replays; 1 = trace every tx). ``enabled=False`` swaps in the
    zero-cost NullTracer — no ring, no histograms, no sampling checks
    beyond one attribute read. ``ring_capacity`` bounds the per-node
    span ring; old spans are overwritten (counted as dropped)."""

    enabled: bool = True
    sample_rate: int = 64
    seed: int = 0
    ring_capacity: int = 8192


@dataclass
class Config:
    chain_id: str = "txflow-chain"
    root_dir: str = ""
    mempool: MempoolConfig = field(default_factory=MempoolConfig)
    consensus: ConsensusConfig = field(default_factory=ConsensusConfig)
    p2p: P2PConfig = field(default_factory=P2PConfig)
    rpc: RPCConfig = field(default_factory=RPCConfig)
    instrumentation: InstrumentationConfig = field(default_factory=InstrumentationConfig)
    engine: EngineConfig = field(default_factory=EngineConfig)
    trace: TraceConfig = field(default_factory=TraceConfig)

    def to_dict(self) -> dict:
        return asdict(self)

    def db_dir(self) -> str:
        return os.path.join(self.root_dir, "data") if self.root_dir else ""


def test_config(root_dir: str = "") -> Config:
    """Fast-timeout config for tests (reference cfg.ResetTestRoot)."""
    c = Config(root_dir=root_dir)
    c.consensus.timeout_propose = 0.4
    c.consensus.timeout_propose_delta = 0.2
    c.consensus.timeout_prevote = 0.2
    c.consensus.timeout_prevote_delta = 0.2
    c.consensus.timeout_precommit = 0.2
    c.consensus.timeout_precommit_delta = 0.2
    c.consensus.timeout_commit = 0.1
    c.consensus.skip_timeout_commit = True
    c.consensus.peer_gossip_sleep = 0.005
    c.mempool.cache_size = 1000
    return c
