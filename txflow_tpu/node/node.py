"""Node: the composition root (reference node/node.go:555-826).

Assembles, in reference order: DBs/stores -> ABCI proxy connections ->
event bus -> pools (mempool + commitpool + txvotepool) -> TxExecutor +
TxFlow -> p2p switch with the mempool/txvote reactors. The reference's
wiring bug — txvotepool/commitpool reactors created but never
``AddReactor``'d into the switch (node/node.go:488-505 vs :822) — is
fixed here: every reactor registers its channel.

The block-path consensus reactor and the RPC listeners attach to the same
skeleton as those layers land.
"""

from __future__ import annotations

import hashlib
import threading
from dataclasses import dataclass, field

from ..abci.application import Application
from ..abci.proxy import AppConns
from ..consensus.reactor import ConsensusReactor
from ..consensus.replay import Handshaker
from ..consensus.state import ConsensusState
from ..engine.execution import TxExecutor
from ..engine.txflow import TxFlow
from ..p2p import Switch
from ..pool.mempool import Mempool
from ..pool.txvotepool import TxVotePool
from ..reactors import MempoolReactor, StateView, TxVoteReactor
from ..state import BlockExecutor, StateStore, state_from_genesis
from ..store.block_store import BlockStore
from ..store.db import MemDB
from ..store.tx_store import TxStore
from ..types.genesis import GenesisDoc, GenesisValidator
from ..types.priv_validator import PrivValidator
from ..types.validator import ValidatorSet
from ..utils.collector import COLLECTOR
from ..utils.config import Config, EngineConfig
from ..utils.events import EventBus
from ..utils.metrics import Registry, TxFlowMetrics


@dataclass
class NodeConfig:
    """Assembly knobs beyond the TOML-ish Config sections."""

    config: Config = field(default_factory=Config)
    gossip_batch: int = 4096
    use_device_verifier: bool = True
    # per-reactor broadcast toggles (None = follow config.mempool.broadcast)
    mempool_broadcast: bool | None = None
    vote_broadcast: bool | None = None
    # False disables the signTxRoutine (pregenerated-vote replay benches)
    # WITHOUT removing the node's validator identity from consensus
    sign_votes: bool = True
    # block-path consensus (the BFT ticker fallback); off = fast path only
    enable_consensus: bool = True
    consensus_wal_path: str = ""
    ticker_factory: object = None
    # HTTP RPC + metrics listener (reference startRPC, node/node.go:878-
    # 1007); port 0 = ephemeral (read Node.rpc.addr), None = no listener
    rpc_port: int | None = None
    rpc_host: str = "127.0.0.1"
    # tx indexer (reference TxIndexConfig "kv"/"null", node/node.go:211-238):
    # False = the "null" indexer, no per-commit index rows
    index_txs: bool = True
    # simplified gRPC BroadcastAPI (reference node/node.go:972-986);
    # port 0 = ephemeral (read Node.grpc.port), None = no listener
    grpc_port: int | None = None
    # ed25519 node key seed: enables authenticated secret connections on
    # TCP links (reference p2p.LoadOrGenNodeKey, node/node.go:72)
    node_key_seed: bytes | None = None
    # anti-entropy re-gossip cadence for lossy links (chaos rigs, real
    # networks); None = single-pass cursor walks (reactor docstrings)
    regossip_interval: float | None = None
    # wrap a node-built DeviceVoteVerifier in ResilientVoteVerifier
    # (bounded retry -> CPU fallback -> device re-promotion) so a device
    # failure degrades throughput instead of erroring the vote path
    resilient_verifier: bool = True
    # self-healing liveness layer (health/): quorum-stall watchdog, peer
    # scoring + reconnect backoff, degraded-mode registry behind the RPC
    # /health endpoint. Strictly additive to the data path (re-offers are
    # dedup'd, eviction requires a reconnector); False drops the monitor
    # thread entirely
    health: bool = True
    # HealthConfig override (None = defaults; see health/config.py)
    health_config: object = None
    # accountable vote gossip (health/byzantine.py): ByzantineConfig
    # override for the per-peer strike ledger + invalid-rate circuit
    # breaker. None = defaults; the ledger itself is always assembled
    # (it is a few dicts — the hooks are no-ops without traffic)
    byzantine_config: object = None
    # overload-resilient front door (admission/): edge dedup before any
    # signature work, pool-pressure backpressure to RPC (429) and ingest
    # gossip, fee/priority mempool lanes. False = open door (seed
    # behavior)
    admission: bool = True
    # AdmissionConfig override (None = defaults; see admission/config.py)
    admission_config: object = None
    # tx -> lane callable override (None = the fee-prefix classifier);
    # must be a deterministic function of the tx bytes
    lane_classifier: object = None
    # PEX address-book reactor (p2p/pex.py): learns/persists peer dial
    # addresses and keeps the mesh connected; also feeds the health
    # layer's reconnect hook. None = auto (on exactly when config.p2p.pex
    # and the switch has a node key, i.e. real TCP assemblies)
    pex: bool | None = None
    # address-book persistence path ("" = in-memory only)
    addrbook_path: str = ""
    # dynamic validator sets (epoch/): scheduled rotation + evidence-
    # driven slashing at deterministic epoch boundaries. None or
    # length=0 = static set (seed behavior); see epoch/config.py
    epoch_config: object = None
    # catch-up sync (sync/): every node serves committed ranges on the
    # sync channel; the client half (lag detection + fetch/verify/apply)
    # runs only when this is on. False = serve-only is also off (seed
    # behavior — recovery is the consensus-block path alone)
    sync: bool = True
    # SyncConfig override (None = defaults; see sync/config.py)
    sync_config: object = None
    # adaptive peer transport (p2p/adaptive.py): per-peer RTT/loss
    # estimators + pinger, adaptive send timeouts, bounded send queues
    # with oldest-bulk drop, slow-peer quarantine folded into the health
    # scoreboard. Opt-in (False = exact legacy switch behavior) so seeded
    # chaos drills stay bit-identical; the WAN matrix and netem rigs
    # enable it
    net: bool = False
    # NetTransportConfig override (None = defaults; see p2p/adaptive.py)
    net_config: object = None
    # netem.LinkShaper (or None): wraps every peer connection in WAN
    # weather — install at assembly so links created by PEX/reconnects
    # are shaped too, not just the initial dials
    link_shaper: object = None


class Node:
    def __init__(
        self,
        node_id: str,
        chain_id: str,
        val_set: ValidatorSet,
        app: Application,
        priv_val: PrivValidator | None = None,
        node_config: NodeConfig | None = None,
        tx_store_db=None,
        state_db=None,
        block_db=None,
        verifier=None,
        mesh=None,
        genesis: GenesisDoc | None = None,
    ):
        nc = node_config or NodeConfig()
        self.node_id = node_id
        self.chain_id = chain_id
        self.config = nc.config
        self.priv_val = priv_val

        # -- replicated state (reference state.State; node/node.go:570) --
        if genesis is None:
            genesis = GenesisDoc(
                chain_id=chain_id,
                validators=[
                    GenesisValidator(v.pub_key, v.voting_power) for v in val_set
                ],
            )
        self.genesis = genesis
        self.state_store = StateStore(state_db if state_db is not None else MemDB())
        loaded = self.state_store.load()
        self.chain_state = loaded if loaded is not None else state_from_genesis(genesis)
        self._state_mtx = threading.Lock()
        self._last_block_height = self.chain_state.last_block_height
        self._val_set = self.chain_state.validators

        # -- app + proxy (node/node.go:576). An address string instead of
        # an Application instance crosses the process boundary: the app
        # runs elsewhere behind abci.server.ABCIServer and the node drives
        # it over the socket protocol (abci/wire.py) — the reference's
        # createAndStartProxyAppConns socket mode --
        if isinstance(app, str):
            from ..abci.client import RemoteAppConns

            self.app = None
            self.proxy_app = RemoteAppConns(app)
        else:
            self.app = app
            self.proxy_app = AppConns(app)

        # -- event bus + tx indexer service (node/node.go:585, :211-238).
        # The indexer follows the reference's config gate (index rows are
        # unbounded MemDB growth): on by default like the reference's
        # "kv" indexer, but benches/workers that never serve /tx_search
        # switch it off via NodeConfig.index_txs --
        self.event_bus = EventBus()
        self.tx_indexer = None
        if nc.index_txs:
            from ..services.indexer import TxIndexer

            self.tx_indexer = TxIndexer(MemDB())
            self.tx_indexer.subscribe(self.event_bus)

        # -- pools (node/node.go:627-633); WALs per node under the config's
        # wal_dir (reference InitWAL at OnStart, node/node.go:805-808) --
        wal_dir = self.config.mempool.wal_dir
        self.mempool = Mempool(
            self.config.mempool,
            proxy_app_conn=self.proxy_app.mempool,
            wal_path=f"{wal_dir}/mempool-{node_id}.wal" if wal_dir else "",
        )
        self.commitpool = Mempool(self.config.mempool)  # fast-committed txs for blocks
        self.tx_vote_pool = TxVotePool(
            self.config.mempool,
            wal_path=f"{wal_dir}/txvotes-{node_id}.wal" if wal_dir else "",
        )
        if wal_dir:
            self.mempool.replay_wal()
            self.tx_vote_pool.replay_wal()

        # -- stores + executors (node/node.go:645-668) --
        self.tx_store = TxStore(tx_store_db if tx_store_db is not None else MemDB())
        # per-node registry: N in-proc nodes must not share counters
        self.metrics_registry = Registry()
        self.metrics = TxFlowMetrics(self.metrics_registry)

        # -- per-tx tracing (trace/): ONE tracer per node, attached to
        # every traced hot-path component below (pools, admission,
        # engine, gossip reactors). config.trace.enabled=False swaps in
        # the NullTracer — same surface, zero cost. The commitpool stays
        # untraced: it re-ingests already-committed txs and would
        # double-anchor their e2e spans --
        from ..trace.tracer import make_tracer

        self.tracer = make_tracer(
            self.config.trace, registry=self.metrics_registry, node_id=node_id
        )
        self.mempool.tracer = self.tracer
        self.tx_vote_pool.tracer = self.tracer

        # -- accountable vote gossip (health/byzantine.py): ONE ledger
        # per node, shared by the reactor (pre-check drops + quarantine
        # gate), the engine (invalid-verdict attribution), and the sync
        # client (forged-data strikes). Built before the engine/reactors
        # so their hooks bind at assembly; the scoreboard half is wired
        # after the health layer exists below --
        from ..health.byzantine import ByzantineLedger

        self.byzantine_ledger = ByzantineLedger(
            nc.byzantine_config, metrics_registry=self.metrics_registry
        )

        # -- epoch manager (epoch/): slashing + scheduled rotation folded
        # into EndBlock validator updates at deterministic boundaries.
        # Every node runs the same pure fold over the committed chain, so
        # the derived set is identical everywhere (no gossip, no vote) --
        self.epoch_manager = None
        if nc.epoch_config is not None and getattr(nc.epoch_config, "length", 0) > 0:
            from ..epoch import EpochManager
            from ..utils.metrics import EpochMetrics

            self.epoch_manager = EpochManager(
                nc.epoch_config, metrics=EpochMetrics(self.metrics_registry)
            )

        # -- committee sampling (committee/): per-epoch stake-proportional
        # tx-vote committee, derived deterministically from (chain_id,
        # epoch) on every node. Independent of the epoch_manager gate:
        # length=0 + committee_size>0 is a valid static-committee posture
        # (the bench config). Full-set mode (committee_size=0, default)
        # leaves all of this None — zero behavior change --
        self.committee_schedule = None
        self._committee = None
        if nc.epoch_config is not None and getattr(
            nc.epoch_config, "committee_size", 0
        ) > 0:
            from ..committee import CommitteeSchedule

            self.committee_schedule = CommitteeSchedule(chain_id, nc.epoch_config)
            self._committee = self.committee_schedule.for_vote_height(
                self._last_block_height, self._val_set
            )
            self.byzantine_ledger.committee_rescale(
                self._committee.size() / max(self._val_set.size(), 1)
            )

        # -- admission front door (admission/): sits between the RPC/
        # gossip edges and the mempool; also supplies the pool's lane
        # classifier so every ingress path lands txs in the right lane --
        self.admission = None
        if nc.admission:
            from ..admission import AdmissionController

            self.admission = AdmissionController(
                self.mempool,
                cfg=nc.admission_config,
                registry=self.metrics_registry,
                classifier=nc.lane_classifier,
            )
            # adaptive bulk rate: the bucket fill tracks the engine's
            # live commit rate (EWMA * headroom with hysteresis) instead
            # of the static cfg knob — see controller._sample_commit_rate
            self.admission.commit_rate_source = (
                lambda m=self.metrics: m.committed_txs.value()
            )
            self.admission.tracer = self.tracer
            self.mempool.lane_of = self.admission.lane_of
            # votes inherit their tx's lane (vote.tx_key -> mempool entry),
            # so the verify engine's priority drain covers the whole
            # commit path, not just the mempool walks
            self.tx_vote_pool.lane_of_vote = (
                lambda vote, _pool=self.mempool: _pool.lane_of_key(vote.tx_key)
            )
        self.tx_executor = TxExecutor(
            self.proxy_app.consensus, self.mempool, self.event_bus, self.metrics
        )
        # honor the config's engine section (batching knobs); only the
        # device/scalar choice is a NodeConfig assembly concern
        import dataclasses

        engine_cfg = dataclasses.replace(
            self.config.engine, use_device=nc.use_device_verifier
        )
        # committee mode: the engine's tally set IS the committee — its
        # quorum_power() is the committee quorum, and a constant committee
        # size keeps the device verifier's compile shapes constant across
        # epoch swaps (zero-recompile restage)
        engine_vals = self._committee if self._committee is not None else self._val_set
        if verifier is None and nc.use_device_verifier and mesh is not None:
            from ..verifier import DeviceVoteVerifier, ResilientVoteVerifier

            verifier = DeviceVoteVerifier(
                engine_vals, mesh=mesh,
                host_prep_workers=int(engine_cfg.host_prep_workers or 0),
            )
            if nc.resilient_verifier:
                verifier = ResilientVoteVerifier(verifier)
        self.txflow = TxFlow(
            chain_id,
            self._last_block_height,
            engine_vals,
            self.tx_vote_pool,
            self.mempool,
            self.commitpool,
            self.tx_executor,
            self.tx_store,
            config=engine_cfg,
            verifier=verifier,
            metrics=self.metrics,
        )
        # before txflow.start(): the coalescer built at start() captures
        # the tracer for its linger spans
        self.txflow.tracer = self.tracer
        self.tx_executor.tracer = self.tracer  # the publish span's begin
        # every valid=False verdict becomes a ledger strike against the
        # peer whose delivery originated the vote (engine _route_result)
        self.txflow.on_invalid_votes = self.byzantine_ledger.note_invalid_origins

        # -- switch + reactors (node/node.go:688-722; wiring bug fixed) --
        self.switch = Switch(node_id, node_seed=nc.node_key_seed)
        if nc.link_shaper is not None:
            self.switch.set_link_shaper(nc.link_shaper)
        if nc.net:
            self.switch.configure_net(nc.net_config)
        mp_bcast = (
            nc.mempool_broadcast
            if nc.mempool_broadcast is not None
            else self.config.mempool.broadcast
        )
        vote_bcast = (
            nc.vote_broadcast
            if nc.vote_broadcast is not None
            else self.config.mempool.broadcast
        )
        self.mempool_reactor = MempoolReactor(
            self.mempool,
            broadcast=mp_bcast,
            batch_size=nc.gossip_batch,
            regossip_interval=nc.regossip_interval,
            admission=self.admission,
        )
        self.txvote_reactor = TxVoteReactor(
            self.state_view,
            self.mempool,
            self.tx_vote_pool,
            priv_val=priv_val if nc.sign_votes else None,
            broadcast=vote_bcast,
            batch_size=nc.gossip_batch,
            regossip_interval=nc.regossip_interval,
        )
        self.mempool_reactor.tracer = self.tracer
        self.txvote_reactor.tracer = self.tracer
        # quarantine gate + O(1) pre-check drop accounting at vote ingest
        self.txvote_reactor.ledger = self.byzantine_ledger
        self.switch.add_reactor("mempool", self.mempool_reactor)
        self.switch.add_reactor("txvote", self.txvote_reactor)

        # -- PEX address book (p2p/pex.py; reference p2p/pex — channel
        # 0x00): auto-on for keyed TCP assemblies, where dial addresses
        # are learnable and re-dials authenticate; in-memory LocalNet
        # pipes have no dialable addresses, so auto stays off there --
        self.address_book = None
        self.pex = None
        pex_on = (
            self.config.p2p.pex and nc.node_key_seed is not None
            if nc.pex is None
            else nc.pex
        )
        if pex_on:
            from ..p2p.pex import AddressBook, PEXReactor

            self.address_book = AddressBook(nc.addrbook_path)
            self.pex = PEXReactor(self.address_book)
            self.switch.add_reactor("pex", self.pex)

        # -- evidence pool + reactor (node/node.go:354-367; channel 0x38) --
        from ..pool.evidence import EvidencePool
        from ..reactors.evidence_reactor import EvidenceReactor

        # committed-evidence markers share the block store's db (prefix
        # EV:): any node that persists blocks also persists the markers,
        # so the already-committed check survives restarts and fast-sync
        # (r3 advisor: an in-memory set diverges between honest nodes)
        self._block_db = block_db if block_db is not None else MemDB()
        self.evidence_pool = EvidencePool(
            chain_id,
            lambda: self.state_view().validators,
            event_bus=self.event_bus,
            db=self._block_db,
            # epoch-correct admission: verify against the set of the
            # height the offending vote was cast in (per-height snapshots
            # persisted by StateStore.save; None falls back to current)
            val_set_at=lambda h: self.state_store.load_validators(h),
        )
        self.evidence_reactor = EvidenceReactor(self.evidence_pool)
        self.switch.add_reactor("evidence", self.evidence_reactor)

        # -- block path: stores + executor + consensus (node/node.go:636-680) --
        self.block_store = BlockStore(self._block_db)
        self.block_executor = BlockExecutor(
            self.state_store,
            self.proxy_app.consensus,
            self.mempool,
            self.commitpool,
            event_bus=self.event_bus,
            evidence_pool=self.evidence_pool,
            epoch_manager=self.epoch_manager,
        )
        self.consensus: ConsensusState | None = None
        self.consensus_reactor: ConsensusReactor | None = None
        if nc.enable_consensus:
            self.consensus = ConsensusState(
                self.config.consensus,
                self.chain_state,
                self.block_executor,
                self.block_store,
                tx_notifier=self.mempool,
                commitpool=self.commitpool,
                tx_store=self.tx_store,
                priv_val=priv_val,
                event_bus=self.event_bus,
                wal_path=nc.consensus_wal_path,
                ticker_factory=nc.ticker_factory,
                on_commit=self._on_block_commit,
            )
            self.consensus.vtx_claimer = self.txflow.claim_vtx
            self.consensus.on_evidence = lambda ev: self.evidence_pool.add(ev)
            self.block_executor.tx_reserved = self.txflow.is_tx_reserved
            self.consensus_reactor = ConsensusReactor(self.consensus)
            self.switch.add_reactor("consensus", self.consensus_reactor)

        # -- RPC + metrics listener (node/node.go:878-1007) --
        self.rpc = None
        if nc.rpc_port is not None:
            from ..rpc import RPCServer

            self.rpc = RPCServer(self, host=nc.rpc_host, port=nc.rpc_port)
        self.grpc = None
        if nc.grpc_port is not None:
            from ..rpc.grpc_server import GRPCBroadcastServer

            self.grpc = GRPCBroadcastServer(self, host=nc.rpc_host, port=nc.grpc_port)

        # -- self-healing liveness layer (health/monitor.py) --
        self.health = None
        if nc.health:
            from ..health import HealthMonitor

            self.health = HealthMonitor(self, nc.health_config)
            # strikes now reach the same score -> floor -> evict/backoff
            # machinery that drives the rest of peer health
            self.byzantine_ledger.scoreboard = self.health.scoreboard
            if self.address_book is not None:
                # default reconnect hook for TCP assemblies: evicted
                # peers re-dial via the PEX address book (the jittered
                # backoff lives in the scoreboard — health/peers.py)
                from ..p2p.pex import book_reconnector

                self.health.set_reconnector(
                    book_reconnector(self.switch, self.address_book)
                )

        # -- catch-up sync (sync/): server half on every sync-enabled
        # node (read-only range serving), client half on its own thread.
        # Assembled after health so Byzantine strikes reach the same
        # scoreboard that drives eviction + reconnect backoff --
        self.sync_reactor = None
        self.sync_manager = None
        if nc.sync:
            from ..sync import SyncManager, SyncReactor
            from ..utils.metrics import SyncMetrics

            self.sync_reactor = SyncReactor(
                self.tx_store,
                state_store=self.state_store,
                current_vals=lambda: self.state_view().validators,
                config=nc.sync_config,
            )
            self.sync_manager = SyncManager(
                chain_id,
                self.tx_store,
                self.txflow,
                self.switch,
                state_store=self.state_store,
                config=nc.sync_config,
                scoreboard=self.health.scoreboard if self.health else None,
                metrics=SyncMetrics(self.metrics_registry),
                tracer=self.tracer,
                ledger=self.byzantine_ledger,
                committee=self.committee_schedule,
            )
            self.sync_reactor.manager = self.sync_manager
            self.switch.add_reactor("sync", self.sync_reactor)

        # -- durable-path degradation -> admission coupling: a node that
        # can no longer persist (disk full / EIO) sheds ingest load like
        # an overloaded one instead of accepting txs it cannot recover --
        if self.admission is not None:
            self.admission.degraded_source = lambda: (
                self.txflow.storage_degraded
                or self.mempool.wal_degraded
                or self.tx_vote_pool.wal_degraded
            )

        self._started = False

    # -- state view read by reactors (reference reads state.State) --

    def state_view(self) -> StateView:
        with self._state_mtx:
            return StateView(
                self.chain_id,
                self._last_block_height,
                self._val_set,
                committee=self._committee,
            )

    def _engine_val_set(self, height: int, full: ValidatorSet) -> ValidatorSet:
        """The set the engine tallies against at ``height``: the epoch's
        sampled committee in committee mode, the full set otherwise.
        Tracks ``self._committee`` (the reactor pre-check view) and
        restates the breaker thresholds whenever the committee actually
        changes (epoch boundary or slash-rotated full set)."""
        if self.committee_schedule is None:
            return full
        committee = self.committee_schedule.for_vote_height(height, full)
        with self._state_mtx:
            changed = committee is not self._committee
            self._committee = committee
        if changed:
            self.byzantine_ledger.committee_rescale(
                committee.size() / max(full.size(), 1)
            )
        return committee

    def update_state(self, height: int, val_set: ValidatorSet | None = None) -> None:
        """Block boundary: advance height / rotate validators."""
        with self._state_mtx:
            self._last_block_height = height
            if val_set is not None:
                self._val_set = val_set
            full = self._val_set
        self.txflow.update_state(height, self._engine_val_set(height, full))
        self.txvote_reactor.broadcast_height(height)
        self.mempool_reactor.broadcast_height(height)
        self.evidence_pool.prune(height)
        if self.epoch_manager is not None:
            m = self.epoch_manager.metrics
            if m is not None:
                cur = self.state_view().validators
                m.number.set(self.epoch_manager.cfg.epoch_of(height))
                m.length.set(self.epoch_manager.cfg.length)
                m.validators.set(cur.size())
                m.total_power.set(cur.total_voting_power())
                m.quorum_power.set(cur.quorum_power())

    def _on_block_commit(self, new_state, block=None) -> None:
        """Consensus commit hook: sync the fast path to the new height and
        (possibly) rotated validator set (node/node.go's implicit coupling
        via shared state). Vtx double-apply protection lives in the
        claim_vtx wiring, exercised during apply_block itself."""
        self.chain_state = new_state
        if block is not None and block.evidence:
            # committed proofs stop gossiping/pending on EVERY node
            # (reference evpool.Update inside ApplyBlock)
            self.evidence_pool.mark_committed(block.evidence)
        self.update_state(new_state.last_block_height, new_state.validators)

    # -- lifecycle (reference OnStart :768-826 / OnStop :829-874) --

    def start(self) -> None:
        if self._started:
            return
        self._started = True
        # handshake-replay the app against the stores (node/node.go:599);
        # the handshake may advance state past the snapshot loaded in
        # __init__ (crash between block save and state save) — every
        # component keyed on height/validators must adopt the result
        handshaker = Handshaker(
            self.state_store,
            self.chain_state,
            self.block_store,
            genesis=self.genesis,
            tx_store=self.tx_store,
            mempool=self.mempool,
        )
        new_state = handshaker.handshake(self.proxy_app)
        if handshaker.unapplied_commits:
            # certificates whose bytes were unavailable at replay: hand
            # them to the engine's deferral map — a catchup block's vtx
            # (claim_vtx) or late mempool gossip delivers them
            self.txflow.register_unapplied(handshaker.unapplied_commits)
        if new_state.last_block_height != self.chain_state.last_block_height:
            self.chain_state = new_state
            with self._state_mtx:
                self._last_block_height = new_state.last_block_height
                self._val_set = new_state.validators
            self.txflow.update_state(
                new_state.last_block_height,
                self._engine_val_set(
                    new_state.last_block_height, new_state.validators
                ),
            )
            if self.consensus is not None:
                self.consensus.reset_to_state(new_state)
        if self.epoch_manager is not None:
            # refill the pending-offense ledger from committed evidence in
            # the current (partial) epoch, so a crash between an offense
            # landing on-chain and its boundary cannot forgive the slash
            self.epoch_manager.rebuild(
                self.block_store, self.chain_state.last_block_height
            )
        self.switch.start()
        # the start-up heap frozen out of the collector, full collections
        # by what survives; before the hook, so its own collection is no span
        COLLECTOR.install()
        self.tracer.install_gc_hook()  # gc_pause spans while the node runs
        self.txflow.start()
        if self.consensus is not None:
            self.consensus.start()
        if self.rpc is not None:
            self.rpc.start()
        if self.grpc is not None:
            self.grpc.start()
        if self.health is not None:
            self.health.start()
        if self.sync_manager is not None:
            self.sync_manager.start()

    def stop(self) -> None:
        if not self._started:
            return
        self._started = False
        if self.sync_manager is not None:
            self.sync_manager.stop()
        if self.health is not None:
            self.health.stop()
        if self.rpc is not None:
            self.rpc.stop()
        if self.grpc is not None:
            self.grpc.stop()
        if self.consensus is not None:
            self.consensus.stop()
        self.txflow.stop()
        self.tracer.remove_gc_hook()
        COLLECTOR.remove()  # the last node of the process restores gc as found
        self.switch.stop()
        self.mempool.close_wal()
        self.tx_vote_pool.close_wal()
        if hasattr(self.proxy_app, "close"):  # remote ABCI sockets
            self.proxy_app.close()

    # -- client surface (RPC broadcast_tx analog until the HTTP layer lands) --

    def broadcast_tx(self, tx: bytes) -> None:
        """Client tx ingress: local CheckTx; gossip + votes follow."""
        self.mempool.check_tx(tx)

    def is_committed(self, tx: bytes) -> bool:
        tx_hash = hashlib.sha256(tx).hexdigest().upper()
        return self.txflow.is_tx_committed(tx_hash)

    @property
    def committed_height_view(self) -> int:
        with self._state_mtx:
            return self._last_block_height
