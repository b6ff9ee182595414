"""LocalNet: an N-validator in-process network over in-memory pipes.

The rebuild's analog of the reference's in-process testnets
(p2p.MakeConnectedSwitches + real reactors, txvotepool/reactor_test.go:
47-66, consensus/common_test.go:576-656) — and the measurement rig for the
BASELINE configs ("4-validator in-proc net, kvstore app, pregenerated
TxVotes replayed through txvotepool").

Every node runs the full fast path: mempool gossip -> signTxRoutine ->
vote gossip -> batched device verify+tally -> per-tx commit against its
own app instance. All nodes share one process and (on TPU) one chip; the
device kernel is shared-compiled across nodes (ops.tally.compact_step_packed_jit).
"""

from __future__ import annotations

import hashlib
import time

from ..abci.kvstore import KVStoreApplication
from ..p2p import connect_switches
from ..types.priv_validator import MockPV, PrivValidator
from ..types.validator import Validator, ValidatorSet
from ..utils.config import Config, test_config
from .node import Node, NodeConfig


class LocalNet:
    def __init__(
        self,
        n_validators: int = 4,
        chain_id: str = "txflow-localnet",
        app_factory=KVStoreApplication,
        config: Config | None = None,
        use_device_verifier: bool = True,
        voting_power: int = 10,
        priv_vals: list[PrivValidator] | None = None,
        gossip_batch: int = 4096,
        sign: bool = True,
        mempool_broadcast: bool | None = None,
        enable_consensus: bool = False,
        ticker_factory=None,
        wal_dir: str = "",
        verifier=None,
        rpc: bool = False,  # True: each node serves HTTP RPC on an ephemeral port
        index_txs: bool = True,
        n_nodes: int | None = None,
        fault_plan=None,  # FaultSpec/FaultPlan/ChaosRouter: chaos p2p (faults/)
        regossip_interval: float | None = None,
        health: bool = True,
        health_config=None,  # HealthConfig override (health/config.py)
        byzantine_config=None,  # ByzantineConfig override (health/byzantine.py)
        voting_powers: list[int] | None = None,  # per-validator stake override
        epoch_config=None,  # EpochConfig: rotation/slashing (epoch/)
        sync: bool = True,  # catch-up sync channel + client (sync/)
        sync_config=None,  # SyncConfig override (sync/config.py)
        netem=None,  # profile name / NetProfile / LinkShaper: WAN weather (netem/)
        netem_seed: int = 0,  # shaper PRNG seed (ignored for a prebuilt LinkShaper)
        net: bool | None = None,  # adaptive transport; None = on iff netem is set
        net_config=None,  # NetTransportConfig override (p2p/adaptive.py)
    ):
        """n_nodes: host only the first n_nodes validators as full nodes
        (default: one node per validator). A large validator set does not
        imply co-locating every validator in THIS process: the bench's
        16/64-validator configs keep 4 hosted nodes — the other
        validators' votes arrive pregenerated, exactly like votes from
        remote peers — because 64 full-mesh in-proc nodes (~4k threads)
        measures thread thrash, not the protocol (r5: the 64-validator
        CPU bench never completed). Quorum still needs 2/3 of the WHOLE
        set's stake."""
        self.chain_id = chain_id
        if priv_vals is None:
            priv_vals = [
                MockPV(hashlib.sha256(b"localnet-val%d" % i).digest())
                for i in range(n_validators)
            ]
        self.priv_vals = priv_vals
        # non-uniform stake (voting_powers, e.g. faults.stake_distribution)
        # exercises quorum math that uniform powers can never reach: a
        # whale's single vote can be 1/3+ of the total
        if voting_powers is not None and len(voting_powers) != len(priv_vals):
            raise ValueError(
                f"voting_powers must have {len(priv_vals)} entries, "
                f"got {len(voting_powers)}"
            )
        powers = voting_powers or [voting_power] * len(priv_vals)
        self.val_set = ValidatorSet(
            [
                Validator.from_pub_key(pv.get_pub_key(), p)
                for pv, p in zip(priv_vals, powers)
            ]
        )
        cfg = config or test_config()
        self.nodes: list[Node] = []
        if n_nodes is not None and not 1 <= n_nodes <= len(priv_vals):
            raise ValueError(
                f"n_nodes must be in [1, {len(priv_vals)}], got {n_nodes}"
            )
        if enable_consensus and n_nodes is not None and n_nodes < len(priv_vals):
            # a hosted subset cannot reach block
            # quorum — the missing validators never prevote, so consensus
            # silently hangs at round 0 instead of failing fast
            raise ValueError(
                f"enable_consensus requires hosting all {len(priv_vals)} "
                f"validators (n_nodes={n_nodes}): a hosted subset cannot "
                "reach block quorum"
            )
        # chaos rig (faults/): accept a FaultSpec, a FaultPlan, or a
        # pre-built ChaosRouter; installed on every switch in start().
        # Lossy links need the reactors' anti-entropy re-walk for
        # liveness — default it on (250 ms) whenever chaos is active.
        self.chaos: "ChaosRouter | None" = None
        if fault_plan is not None:
            from ..faults import ChaosRouter
            from ..faults.chaos import FaultPlan, FaultSpec

            if isinstance(fault_plan, (FaultSpec, FaultPlan)):
                fault_plan = ChaosRouter(fault_plan)
            self.chaos = fault_plan
            if regossip_interval is None:
                regossip_interval = 0.25
        # network weather (netem/): ONE shaper serves the whole net so a
        # live set_profile() walks every link at once; installed on each
        # switch at assembly (node_config) so PEX/reconnect links created
        # later are shaped too. Weather implies frame loss below the
        # reliable lane — default the anti-entropy re-walk on, like chaos.
        self.shaper = None
        if netem is not None:
            from ..netem import LinkShaper

            if isinstance(netem, LinkShaper):
                self.shaper = netem
            else:
                self.shaper = LinkShaper(netem, seed=netem_seed)
            if regossip_interval is None:
                regossip_interval = 0.25
            # in-proc pipes have no PEX ensure-loop: a peer torn down by a
            # weather-corrupted frame must heal through the scoreboard's
            # backoff re-dial instead
            if health_config is None:
                from ..health.config import HealthConfig

                health_config = HealthConfig(redial_lost_peers=True)
        self._net = bool(net) if net is not None else self.shaper is not None
        self._net_config = net_config
        # rebuild inputs, kept so durable members can be crashed and
        # revived over their on-disk artifacts (make_durable/revive_node)
        self._cfg = cfg
        self._app_factory = app_factory
        self._verifier = verifier
        self._gossip_batch = gossip_batch
        self._use_device_verifier = use_device_verifier
        self._mempool_broadcast = mempool_broadcast
        self._enable_consensus = enable_consensus
        self._sign = sign
        self._rpc = rpc
        self._index_txs = index_txs
        self._ticker_factory = ticker_factory
        self._wal_dir = wal_dir
        self._regossip_interval = regossip_interval
        self._health = health
        self._health_config = health_config
        self._byzantine_config = byzantine_config
        self._epoch_config = epoch_config
        self._sync = sync
        self._sync_config = sync_config
        self._durable_roots: dict[int, str] = {}
        self._down: set[int] = set()
        hosted = priv_vals if n_nodes is None else priv_vals[:n_nodes]
        for i, _pv in enumerate(hosted):
            self.nodes.append(self._build_node(i))

    def _build_node(self, i: int) -> Node:
        root = self._durable_roots.get(i)
        dbs = {}
        cfg = self._cfg
        consensus_wal = (
            f"{self._wal_dir}/node{i}-consensus.wal" if self._wal_dir else ""
        )
        if root is not None:
            import copy

            from ..store.db import FileDB

            dbs = {
                "tx_store_db": FileDB(f"{root}/txstore.db"),
                "state_db": FileDB(f"{root}/state.db"),
                "block_db": FileDB(f"{root}/blocks.db"),
            }
            consensus_wal = f"{root}/consensus.wal"
            # pool WALs too (CrashDrill parity): a private config copy so
            # the in-memory members don't start writing WALs as well
            cfg = copy.deepcopy(cfg)
            cfg.mempool.wal_dir = root
        return Node(
            node_id=f"node{i}",
            chain_id=self.chain_id,
            val_set=self.val_set,
            app=self._app_factory(),
            # a shared verifier instance (same val_set for every node)
            # reuses one set of device epoch tables + compiled shapes
            verifier=self._verifier,
            priv_val=self.priv_vals[i],
            node_config=NodeConfig(
                config=cfg,
                gossip_batch=self._gossip_batch,
                use_device_verifier=self._use_device_verifier,
                mempool_broadcast=self._mempool_broadcast,
                enable_consensus=self._enable_consensus,
                # sign=False: fast-path votes are injected externally
                # (pregenerated-vote replay, BASELINE config 1); the
                # node keeps its consensus identity either way
                sign_votes=self._sign,
                rpc_port=0 if self._rpc else None,
                index_txs=self._index_txs,
                ticker_factory=self._ticker_factory,
                consensus_wal_path=consensus_wal,
                regossip_interval=self._regossip_interval,
                health=self._health,
                health_config=self._health_config,
                byzantine_config=self._byzantine_config,
                epoch_config=self._epoch_config,
                sync=self._sync,
                sync_config=self._sync_config,
                net=self._net,
                net_config=self._net_config,
                link_shaper=self.shaper,
            ),
            **dbs,
        )

    def start(self) -> None:
        if self.chaos is not None:
            # before connect: interceptors must cover the peers the full
            # mesh is about to create
            self.chaos.install([n.switch for n in self.nodes])
        for node in self.nodes:
            node.start()
        # full mesh (reference MakeConnectedSwitches connects all pairs)
        for i in range(len(self.nodes)):
            for j in range(i + 1, len(self.nodes)):
                connect_switches(self.nodes[i].switch, self.nodes[j].switch)
        # health monitors can only heal links they can re-dial: give each
        # one a reconnector so peer-score evictions become reconnect
        # cycles instead of permanent degradation
        roster = [n.switch.node_id for n in self.nodes]
        for node in self.nodes:
            if node.health is not None:
                node.health.set_reconnector(self._make_reconnector(node))
                # full-mesh roster: redial_lost_peers rigs heal links torn
                # down before the scoreboard ever observed them
                node.health.set_expected_peers(roster)

    def _make_reconnector(self, node: Node):
        """Closure handed to node's PeerScoreBoard: re-dial a peer by
        switch id over a fresh in-memory pipe (the LocalNet analog of the
        reference's persistent-peer redial loop)."""

        def reconnect(dst_id: str) -> bool:
            target = None
            for other in self.nodes:
                if other is not node and other.switch.node_id == dst_id:
                    target = other
                    break
            if target is None or not target.switch.is_running:
                return False
            if not node.switch.is_running:
                return False
            if node.switch.get_peer(dst_id) is not None:
                return True  # raced with an inbound redial: already healed
            # the evicting side dropped its end; the far side may still
            # hold the dead half of the old pipe — clear it first or
            # add_peer_conn rejects the redial as a duplicate
            stale = target.switch.get_peer(node.switch.node_id)
            if stale is not None:
                target.switch.stop_peer(stale, reason="stale half-link")
            connect_switches(node.switch, target.switch)
            return True

        return reconnect

    # -- durable members: crash/revive drills (faults/crash.py analog) --

    def make_durable(self, i: int, root: str) -> None:
        """Rebuild node i (pre-start) over FileDB stores + consensus WAL
        under ``root`` so it can be crashed and revived in place."""
        if self.nodes[i]._started:
            raise RuntimeError("make_durable must run before start()")
        self._durable_roots[i] = root
        self.nodes[i] = self._build_node(i)

    def crash_node(self, i: int) -> Node:
        """Stop node i in place (peers see the link die); state survives
        only what its stores persisted. Returns the stopped node."""
        node = self.nodes[i]
        node.stop()
        self._down.add(i)
        return node

    def wipe_node(self, i: int) -> None:
        """Delete node i's durable artifacts while it is down — the
        wipe-and-rejoin drill. revive_node then rebuilds it over EMPTY
        stores (a freshly-joined node for all practical purposes) and it
        must recover the committed set from peers via catch-up sync."""
        if i not in self._down:
            raise RuntimeError(f"node {i} must be crashed before wiping")
        root = self._durable_roots.get(i)
        if root is None:
            raise RuntimeError(f"node {i} has no durable root to wipe")
        import os
        import shutil

        shutil.rmtree(root, ignore_errors=True)
        os.makedirs(root, exist_ok=True)

    def revive_node(self, i: int) -> Node:
        """Rebuild node i over its durable artifacts (fresh app instance,
        handshake replay + catchup) and rejoin the mesh."""
        if i not in self._down:
            raise RuntimeError(f"node {i} is not down")
        node = self._build_node(i)
        self.nodes[i] = node
        if self.chaos is not None:
            self.chaos.install([node.switch])
        node.start()
        for j, other in enumerate(self.nodes):
            if j != i and j not in self._down:
                connect_switches(node.switch, other.switch)
        if node.health is not None:
            node.health.set_reconnector(self._make_reconnector(node))
            node.health.set_expected_peers([n.switch.node_id for n in self.nodes])
        self._down.discard(i)
        return node

    def stop(self) -> None:
        for node in self.nodes:
            node.stop()
        if self.chaos is not None:
            self.chaos.uninstall()

    def set_net_profile(self, profile, links=None) -> None:
        """Swap the WAN weather live on every link (netem rigs only)."""
        if self.shaper is None:
            raise RuntimeError("LocalNet was built without netem")
        self.shaper.set_profile(profile, links=links)

    # -- client helpers --

    def broadcast_tx(self, tx: bytes, node_index: int = 0) -> None:
        self.nodes[node_index].broadcast_tx(tx)

    def wait_all_committed(
        self, txs: list[bytes], timeout: float = 30.0, poll: float = 0.01
    ) -> bool:
        """Block until every node has committed every tx (or timeout)."""
        hashes = [hashlib.sha256(tx).hexdigest().upper() for tx in txs]
        deadline = time.monotonic() + timeout
        for node in self.nodes:
            for h in hashes:
                while not node.tx_store.has_tx(h):
                    if time.monotonic() > deadline:
                        return False
                    time.sleep(poll)
        # certificates are decision-time facts; wait for the pipelined
        # committers' ABCI applies to drain too, so callers can compare
        # app state across nodes right after this returns
        for node in self.nodes:
            while not node.txflow.commits_drained():
                if time.monotonic() > deadline:
                    return False
                time.sleep(poll)
        return True

    def committed_votes_total(self) -> int:
        """Sum over nodes of votes in committed certificates."""
        return sum(int(n.metrics.committed_votes.value()) for n in self.nodes)

    # -- tracing (trace/) --

    def trace_dumps(self) -> list[dict]:
        """Per-node span-ring dumps (the /trace RPC payload, in-proc)."""
        return [n.tracer.dump(n.node_id) for n in self.nodes]

    def export_trace(self, path: str) -> int:
        """Merge every node's span ring into one Chrome-trace JSON file
        (open in Perfetto / chrome://tracing). Returns the number of
        span events written."""
        from ..trace.export import write_chrome_trace

        return write_chrome_trace(path, self.trace_dumps())
