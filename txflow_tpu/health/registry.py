"""DegradedModeRegistry: one aggregation point for "how degraded is this
node" — ResilientVoteVerifier counters, quorum-stall watchdog firings,
and peer churn — mirrored into ``utils.metrics`` health gauges and
snapshotted as the RPC ``/health`` payload.

The registry owns no threads: the HealthMonitor tick calls ``refresh``
and the watchdog / peer scorer call the ``note_*`` event hooks. Events
are double-counted on purpose into both plain ints (cheap snapshot) and
the metrics registry (Prometheus exposition) so ``/health`` and
``/metrics`` can never disagree about totals.
"""

from __future__ import annotations

import threading

from ..analysis.lockgraph import make_lock

from ..utils.metrics import HealthMetrics, NetMetrics, Registry, ScenarioMetrics


class DegradedModeRegistry:
    def __init__(self, metrics_registry: Registry):
        self.metrics = HealthMetrics(metrics_registry)
        self.net_metrics = NetMetrics(metrics_registry)
        self.scenario_metrics = ScenarioMetrics(metrics_registry)
        self._mtx = make_lock("health.DegradedModeRegistry._mtx")
        # event totals (watchdog + peer scorer hooks)
        self.watchdog_firings = 0
        self.watchdog_escalations = 0
        self.reoffered_votes = 0
        self.reoffered_txs = 0
        self.peer_evictions = 0
        self.peer_reconnects = 0
        self.reconnect_failures = 0
        # state refreshed each tick
        self._progress: dict = {}
        self._verifier: dict = {}
        self._peers: dict = {}
        self._epoch: dict = {}
        self._sync: dict = {}
        self._storage: dict = {}
        self._network: dict = {}
        self._byzantine: dict = {}
        self._scenario: dict = {}
        self._watchdog_state: dict = {"inflight": 0, "oldest_stall_age": 0.0}
        self._healthy = True

    # -- event hooks --

    def note_watchdog_fired(self, escalated: bool, votes: int, txs: int) -> None:
        with self._mtx:
            self.watchdog_firings += 1
            if escalated:
                self.watchdog_escalations += 1
            self.reoffered_votes += votes
            self.reoffered_txs += txs
        m = self.metrics
        m.watchdog_firings.add(1)
        if escalated:
            m.watchdog_escalations.add(1)
        if votes:
            m.reoffered_votes.add(votes)
        if txs:
            m.reoffered_txs.add(txs)

    def note_peer_evicted(self) -> None:
        with self._mtx:
            self.peer_evictions += 1
        self.metrics.peer_evictions.add(1)

    def note_peer_reconnected(self) -> None:
        with self._mtx:
            self.peer_reconnects += 1
        self.metrics.peer_reconnects.add(1)

    def note_reconnect_failed(self) -> None:
        with self._mtx:
            self.reconnect_failures += 1
        self.metrics.reconnect_failures.add(1)

    def set_scenario(self, info: dict | None) -> None:
        """Publish (or clear, with ``None``/``{}``) the scenario-grid
        tile currently driving this node (scenario/ runner, via the
        procnode ``{"cmd": "scenario"}`` control). The dict lands
        verbatim as the ``/health`` "scenario" section; the numeric
        shape is mirrored into the ``txflow_scenario_*`` gauges."""
        info = dict(info or {})
        with self._mtx:
            self._scenario = info
        self.scenario_metrics.refresh_from(info)

    # -- tick refresh --

    def set_watchdog_state(self, inflight: int, oldest_stall_age: float) -> None:
        with self._mtx:
            self._watchdog_state = {
                "inflight": inflight,
                "oldest_stall_age": round(oldest_stall_age, 3),
            }
        self.metrics.inflight_txs.set(inflight)
        self.metrics.oldest_stall_age.set(oldest_stall_age)

    def refresh(self, node) -> None:
        """Pull the per-subsystem progress signals off the node. Runs on
        the monitor thread; every read below is a thread-safe node
        surface (pool seq counters, metrics gauges, switch peer list)."""
        verifier = getattr(node.txflow, "verifier", None)
        vstate: dict = {}
        if verifier is not None and hasattr(verifier, "device_healthy"):
            vstate = {
                "device_healthy": bool(verifier.device_healthy),
                "demotions": verifier.demotions,
                "repromotions": verifier.repromotions,
                "device_failures": verifier.device_failures,
                "fallback_calls": verifier.fallback_calls,
                "last_error": repr(verifier.last_error)
                if verifier.last_error is not None
                else None,
            }
            m = self.metrics
            m.verifier_demotions.set(vstate["demotions"])
            m.verifier_repromotions.set(vstate["repromotions"])
            m.verifier_device_failures.set(vstate["device_failures"])
            m.verifier_fallback_calls.set(vstate["fallback_calls"])
            m.verifier_device_healthy.set(1.0 if vstate["device_healthy"] else 0.0)
        n_peers = node.switch.n_peers()
        self.metrics.n_peers.set(n_peers)
        # network weather (p2p/adaptive.py + netem/): per-peer RTT/loss/
        # backlog, quarantine state, and shaper counters — republished as
        # txflow_net_* gauges and the /health "network" section
        network: dict = {}
        net_snapshot = getattr(node.switch, "net_snapshot", None)
        if net_snapshot is not None:
            network = net_snapshot()
            self.net_metrics.refresh_from(network)
        progress = {
            "fast_path_height": node.committed_height_view,
            "consensus_height": (
                node.consensus.state.last_block_height
                if node.consensus is not None
                else None
            ),
            "mempool_seq": node.mempool.seq(),
            "mempool_size": node.mempool.size(),
            "txvote_seq": node.tx_vote_pool.seq(),
            "txvotepool_size": node.tx_vote_pool.size(),
            # keys the dedup sets have pushed out at capacity: once a set
            # is full every new key evicts one (utils/cache.py), so these
            # rise with the ingest rate; a vote set that evicts faster
            # than txs commit re-admits relayed votes as new
            "mempool_dedup_evictions": node.mempool.cache.evictions,
            "txvote_dedup_evictions": node.tx_vote_pool.cache.evictions,
            "committed_evictions": node.txflow.committed_evictions,
            "committed_txs": int(node.metrics.committed_txs.value()),
        }
        # what the vote pool's ingest costs (thread CPU: another thread's
        # hold of the interpreter lock is not in it) and which way the
        # votes' bytes came: fast + general + primed = votes; general > 0
        # means votes of a shape off the canonical one are being offered
        ingest = node.tx_vote_pool.ingest_stats()
        for name, value in ingest.items():
            progress["txvote_ingest_" + name] = value
            getattr(self.metrics, "txvote_ingest_" + name).set(value)
        pipe = getattr(node.txflow, "pipeline_stats", None)
        if pipe is not None:
            # verify-pipeline health: a collapsing overlap ratio with a
            # healthy device lane means the engine is host-bound, not
            # device-bound — a different remediation than demotion.
            # progress.pipeline.late_votes / dup_votes / late_verified
            # count the votes that arrived for nothing (dropped in prep,
            # or verified before routing found the tx committed: a rising
            # late_verified is device work thrown away); carried_slots and
            # open_vote_sets what stays open from step to step; quorums /
            # quorum_rows / quorum_steps what the decided quorums took
            # (rows and steps a certificate, summed: perfbench cert_rows
            # and quorum_steps read them);
            # full_collections / full_collect_s / survivors /
            # frozen_objects are the process's collector schedule; h2d
            # the steps' host->device puts and bytes (two puts a step)
            stats = pipe()
            progress["pipeline"] = stats
            if stats["overlap_ratio"] is not None:
                self.metrics.pipeline_overlap.set(stats["overlap_ratio"])
            self.metrics.pipeline_depth_now.set(stats.get("depth") or 0)
            # shape-lifecycle health: sustained cold-fallback growth means
            # the warmer is behind (or wedged) and the node is serving on
            # the slow path — visible here before throughput graphs sag
            coalesce = stats.get("coalesce")
            if coalesce is not None:
                self.metrics.warmup_cold_votes.set(
                    coalesce.get("cold_fallback_votes", 0)
                )
        # the liveness verdict: degraded when the device lane is demoted,
        # a tx has been stalled past ~2 deadlines, or the node has no
        # peers while work is pending
        # validator-set lifecycle (epoch/): operators read slash events
        # and the current epoch from /health without scraping /metrics
        em = getattr(node, "epoch_manager", None)
        epoch_state = em.snapshot() if em is not None else {}
        rot = getattr(node.txflow, "last_rotation", None)
        if rot is not None:
            epoch_state["last_engine_rotation"] = dict(rot)
        # catch-up sync (sync/manager.py): lag + state machine snapshot.
        # "syncing" is self-healing and stays healthy; "fallback" means
        # no peer can serve this node — degraded until the consensus
        # block path (or a recovered peer) closes the gap
        sm = getattr(node, "sync_manager", None)
        sync_state = sm.snapshot() if sm is not None else {}
        # accountable vote gossip (health/byzantine.py): the unified
        # strike ledger — gossip verdict strikes, pre-verify drops by
        # reason, sync forgery strikes, and active quarantines — in one
        # section, so "who is attacking this node and what is it
        # costing" never requires correlating three subsystems
        bl = getattr(node, "byzantine_ledger", None)
        byz_state = bl.snapshot() if bl is not None else {}
        # durable-path degradation (engine save / pool WALs): a node that
        # cannot persist commits is loudly degraded, never silently lossy
        storage_state = {
            "degraded": bool(getattr(node.txflow, "storage_degraded", False)),
            "errors": getattr(node.txflow, "storage_errors", 0),
            "last_error": getattr(node.txflow, "storage_last_error", ""),
            "mempool_wal_degraded": bool(getattr(node.mempool, "wal_degraded", False)),
            "txvote_wal_degraded": bool(
                getattr(node.tx_vote_pool, "wal_degraded", False)
            ),
        }
        storage_degraded = (
            storage_state["degraded"]
            or storage_state["mempool_wal_degraded"]
            or storage_state["txvote_wal_degraded"]
        )
        stalled = self._watchdog_state["oldest_stall_age"]
        healthy = (
            (not vstate or vstate["device_healthy"])
            and stalled < 2 * max(self._stall_timeout_hint, 0.001)
            and not (n_peers == 0 and progress["txvotepool_size"] > 0)
            and sync_state.get("state") != "fallback"
            and not storage_degraded
        )
        with self._mtx:
            self._progress = progress
            self._verifier = vstate
            self._peers = {"n_peers": n_peers}
            self._epoch = epoch_state
            self._sync = sync_state
            self._storage = storage_state
            self._network = network
            self._byzantine = byz_state
            self._healthy = healthy
        self.metrics.healthy.set(1.0 if healthy else 0.0)

    _stall_timeout_hint: float = 2.0  # monitor sets this from its config

    # -- snapshots --

    @property
    def healthy(self) -> bool:
        with self._mtx:
            return self._healthy

    def snapshot(self, peer_scores: dict | None = None) -> dict:
        with self._mtx:
            return {
                "healthy": self._healthy,
                "watchdog": {
                    "firings": self.watchdog_firings,
                    "escalations": self.watchdog_escalations,
                    "reoffered_votes": self.reoffered_votes,
                    "reoffered_txs": self.reoffered_txs,
                    **self._watchdog_state,
                },
                "peers": {
                    **self._peers,
                    "evictions": self.peer_evictions,
                    "reconnects": self.peer_reconnects,
                    "reconnect_failures": self.reconnect_failures,
                    "scores": peer_scores or {},
                },
                "verifier": dict(self._verifier),
                "progress": dict(self._progress),
                "epoch": dict(self._epoch),
                "sync": dict(self._sync),
                "storage": dict(self._storage),
                "network": dict(self._network),
                "byzantine": dict(self._byzantine),
                "scenario": dict(self._scenario),
            }
