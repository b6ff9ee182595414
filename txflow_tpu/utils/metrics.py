"""Prometheus-style metrics (reference: go-kit metrics per subsystem).

Mirrors the surface of consensus/metrics.go, txflowstate/metrics.go and the
mempool metrics: Gauge / Counter / Histogram with label support, a process
registry, and a text exposition dump compatible with the Prometheus format
served at the instrumentation endpoint (node/node.go:988-1007).
"""

from __future__ import annotations

import threading
from collections import defaultdict


class _Metric:
    def __init__(self, name: str, help_: str = ""):
        self.name = name
        self.help = help_
        self._mtx = threading.Lock()

    def _header(self, kind: str) -> str:
        # HELP before TYPE, help text with newlines/backslashes escaped
        # per the exposition-format spec — scrapers (and our own
        # parse_exposition) reject a bare newline inside a comment
        lines = []
        if self.help:
            esc = self.help.replace("\\", "\\\\").replace("\n", "\\n")
            lines.append(f"# HELP {self.name} {esc}")
        lines.append(f"# TYPE {self.name} {kind}")
        return "\n".join(lines) + "\n"


class Gauge(_Metric):
    def __init__(self, name: str, help_: str = ""):
        super().__init__(name, help_)
        self._v = 0.0

    def set(self, v: float) -> None:
        with self._mtx:
            self._v = v

    def add(self, v: float) -> None:
        with self._mtx:
            self._v += v

    def value(self) -> float:
        with self._mtx:
            return self._v

    def expose(self) -> str:
        return self._header("gauge") + f"{self.name} {self.value()}\n"


class Counter(_Metric):
    def __init__(self, name: str, help_: str = ""):
        super().__init__(name, help_)
        self._v = 0.0

    def add(self, v: float = 1.0) -> None:
        with self._mtx:
            self._v += v

    def value(self) -> float:
        with self._mtx:
            return self._v

    def expose(self) -> str:
        return self._header("counter") + f"{self.name} {self.value()}\n"


class Histogram(_Metric):
    """Fixed-bucket histogram (sum/count + cumulative buckets)."""

    DEFAULT_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0)

    def __init__(self, name: str, help_: str = "", buckets=DEFAULT_BUCKETS):
        super().__init__(name, help_)
        self.buckets = tuple(buckets)
        self._counts = [0] * (len(self.buckets) + 1)
        self._sum = 0.0
        self._count = 0

    def observe(self, v: float) -> None:
        with self._mtx:
            self._sum += v
            self._count += 1
            for i, b in enumerate(self.buckets):
                if v <= b:
                    self._counts[i] += 1
                    return
            self._counts[-1] += 1

    def expose(self) -> str:
        with self._mtx:
            lines = [self._header("histogram").rstrip("\n")]
            cum = 0
            for i, b in enumerate(self.buckets):
                cum += self._counts[i]
                lines.append(f'{self.name}_bucket{{le="{b}"}} {cum}')
            cum += self._counts[-1]
            lines.append(f'{self.name}_bucket{{le="+Inf"}} {cum}')
            lines.append(f"{self.name}_sum {self._sum}")
            lines.append(f"{self.name}_count {self._count}")
            return "\n".join(lines) + "\n"

    def quantile(self, q: float) -> float | None:
        """Estimated q-quantile (0 < q < 1) by linear interpolation
        inside the owning bucket — the standard histogram_quantile
        estimate, so a /health digest and a PromQL dashboard agree.
        None when empty; observations past the last finite bucket clamp
        to that bucket's upper bound (+Inf has no midpoint to guess)."""
        with self._mtx:
            if self._count == 0:
                return None
            rank = q * self._count
            cum = 0
            lo = 0.0
            for i, b in enumerate(self.buckets):
                prev = cum
                cum += self._counts[i]
                if cum >= rank:
                    frac = (rank - prev) / max(self._counts[i], 1)
                    return lo + (b - lo) * frac
                lo = b
            return float(self.buckets[-1]) if self.buckets else None


class Registry:
    def __init__(self, namespace: str = "txflow"):
        self.namespace = namespace
        self._mtx = threading.Lock()
        self._metrics: dict[str, _Metric] = {}

    def _reg(self, cls, subsystem: str, name: str, help_: str, **kw):
        full = f"{self.namespace}_{subsystem}_{name}"
        with self._mtx:
            m = self._metrics.get(full)
            if m is None:
                m = cls(full, help_, **kw)
                self._metrics[full] = m
            return m

    def gauge(self, subsystem: str, name: str, help_: str = "") -> Gauge:
        return self._reg(Gauge, subsystem, name, help_)

    def counter(self, subsystem: str, name: str, help_: str = "") -> Counter:
        return self._reg(Counter, subsystem, name, help_)

    def histogram(self, subsystem: str, name: str, help_: str = "", buckets=Histogram.DEFAULT_BUCKETS) -> Histogram:
        return self._reg(Histogram, subsystem, name, help_, buckets=buckets)

    def expose(self) -> str:
        with self._mtx:
            return "".join(m.expose() for m in self._metrics.values())


GLOBAL = Registry()


def parse_exposition(text: str) -> dict[str, dict]:
    """Parse a Prometheus text exposition back into per-family dicts.

    Scrape-compliance oracle for the tests (and the soak's metric
    assertions): every family maps to ``{"type": ..., "help": ...,
    "samples": {sample_name_or_(name, labels): value}}``. Histogram
    families additionally get ``"buckets"``: an ordered
    ``[(le_string, cumulative_count), ...]`` ending at ``+Inf``.
    Raises ValueError on lines a Prometheus scraper would reject."""
    families: dict[str, dict] = {}

    def family_of(sample_name: str) -> str:
        for suffix in ("_bucket", "_sum", "_count"):
            base = sample_name.removesuffix(suffix)
            if base != sample_name and base in families:
                return base
        return sample_name

    for raw in text.splitlines():
        line = raw.strip()
        if not line:
            continue
        if line.startswith("#"):
            parts = line.split(None, 3)
            if len(parts) < 3 or parts[1] not in ("HELP", "TYPE"):
                raise ValueError(f"malformed comment line: {raw!r}")
            fam = families.setdefault(
                parts[2], {"type": None, "help": "", "samples": {}, "buckets": []}
            )
            if parts[1] == "TYPE":
                fam["type"] = parts[3] if len(parts) > 3 else "untyped"
            else:
                fam["help"] = parts[3] if len(parts) > 3 else ""
            continue
        # sample line: name[{labels}] value
        name_part, _, value_part = line.rpartition(" ")
        if not name_part:
            raise ValueError(f"malformed sample line: {raw!r}")
        value = float(value_part)  # ValueError on garbage
        labels = ""
        name = name_part
        if "{" in name_part:
            name, _, rest = name_part.partition("{")
            labels, close, trailer = rest.partition("}")
            if not close or trailer.strip():
                raise ValueError(f"malformed labels: {raw!r}")
        fam = families.setdefault(
            family_of(name), {"type": None, "help": "", "samples": {}, "buckets": []}
        )
        key = name if not labels else (name, labels)
        fam["samples"][key] = value
        if name.endswith("_bucket"):
            le = None
            for pair in labels.split(","):
                k, _, v = pair.partition("=")
                if k.strip() == "le":
                    le = v.strip().strip('"')
            if le is None:
                raise ValueError(f"histogram bucket without le label: {raw!r}")
            fam["buckets"].append((le, value))
    # structural checks a scraper enforces on histograms
    for base, fam in families.items():
        if fam["type"] != "histogram":
            continue
        buckets = fam["buckets"]
        if not buckets or buckets[-1][0] != "+Inf":
            raise ValueError(f"{base}: histogram missing +Inf bucket")
        counts = [c for _, c in buckets]
        if counts != sorted(counts):
            raise ValueError(f"{base}: bucket counts not cumulative")
        if fam["samples"].get(base + "_count") != buckets[-1][1]:
            raise ValueError(f"{base}: _count != +Inf cumulative count")
        if base + "_sum" not in fam["samples"]:
            raise ValueError(f"{base}: missing _sum")
    return families


class HealthMetrics:
    """Self-healing / degraded-mode metrics (health/ subsystem).

    Counters are monotonic event totals (watchdog firings, peer churn);
    gauges mirror current state (liveness verdict, verifier demotion
    state, in-flight stall depth) so the Prometheus exposition and the
    RPC ``/health`` endpoint read the same registry."""

    def __init__(self, registry: "Registry | None" = None):
        r = registry or GLOBAL
        self.healthy = r.gauge("health", "healthy", "1 = all progress signals live")
        self.watchdog_firings = r.counter("health", "watchdog_firings", "quorum-stall watchdog firings")
        self.watchdog_escalations = r.counter("health", "watchdog_escalations", "stall re-offers escalated to all peers")
        self.reoffered_votes = r.counter("health", "reoffered_votes", "votes re-offered by the watchdog")
        self.reoffered_txs = r.counter("health", "reoffered_txs", "txs re-offered by the watchdog")
        self.inflight_txs = r.gauge("health", "inflight_txs", "txs below quorum right now")
        self.oldest_stall_age = r.gauge("health", "oldest_stall_seconds", "age of the oldest sub-quorum tx")
        self.peer_evictions = r.counter("health", "peer_evictions", "peers evicted by score")
        self.peer_reconnects = r.counter("health", "peer_reconnects", "score-driven reconnects that succeeded")
        self.reconnect_failures = r.counter("health", "reconnect_failures", "reconnect attempts that failed")
        self.n_peers = r.gauge("health", "n_peers", "connected peers")
        self.verifier_demotions = r.gauge("health", "verifier_demotions", "device->fallback demotions")
        self.verifier_repromotions = r.gauge("health", "verifier_repromotions", "fallback->device re-promotions")
        self.verifier_device_failures = r.gauge("health", "verifier_device_failures", "device verify errors")
        self.verifier_fallback_calls = r.gauge("health", "verifier_fallback_calls", "batches served by the CPU fallback")
        self.verifier_device_healthy = r.gauge("health", "verifier_device_healthy", "1 = device lane serving")
        self.pipeline_overlap = r.gauge("health", "pipeline_overlap_ratio", "engine verify-pipeline overlap (device-busy / active)")
        self.warmup_cold_votes = r.gauge("health", "warmup_cold_fallback_votes", "votes served by the CPU fallback awaiting shape promotion")
        self.pipeline_depth_now = r.gauge("health", "pipeline_depth", "engine's current (possibly adaptive) pipeline depth")
        # the vote pool's ingest (pool/txvotepool.py check_tx_many), republished from the pool's own totals each tick
        self.txvote_ingest_votes = r.gauge("health", "txvote_ingest_votes", "votes offered to the vote pool's ingest")
        self.txvote_ingest_cpu_s = r.gauge("health", "txvote_ingest_cpu_s", "thread CPU seconds of the vote pool's ingest frames")
        self.txvote_ingest_fast = r.gauge("health", "txvote_ingest_fast", "ingested votes whose wire form was laid out in one pass")
        self.txvote_ingest_general = r.gauge("health", "txvote_ingest_general", "ingested votes encoded by encode_tx_vote (a shape off the canonical one)")
        self.txvote_ingest_primed = r.gauge("health", "txvote_ingest_primed", "ingested votes that arrived with their wire form (gossip decode, WAL replay)")


class NetMetrics:
    """Network-weather metrics (p2p/adaptive.py + netem/ subsystems).

    All values mirror the switch's ``net_snapshot()`` — counters live as
    plain ints on estimators/shapers (bumped lock-free on hot paths) and
    are republished as absolute gauges on each health tick, so /metrics,
    /health's "network" section, and bench stamps read one source."""

    def __init__(self, registry: "Registry | None" = None):
        r = registry or GLOBAL
        self.peers = r.gauge("net", "peers", "peers with link estimators")
        self.quarantined = r.gauge("net", "quarantined_peers", "peers currently quarantined for bad weather")
        self.quarantine_transitions = r.gauge("net", "quarantine_transitions", "quarantine enter/leave events (all peers)")
        self.rtt_ms_max = r.gauge("net", "peer_rtt_ms_max", "worst per-peer smoothed RTT (ms)")
        self.loss_max = r.gauge("net", "peer_loss_max", "worst per-peer ping-loss EWMA")
        self.pings_sent = r.gauge("net", "pings_sent", "link probes sent (all peers)")
        self.pongs = r.gauge("net", "pongs", "link probe replies received (all peers)")
        self.ping_timeouts = r.gauge("net", "ping_timeouts", "link probes expired unanswered (all peers)")
        self.sendq_dropped = r.gauge("net", "sendq_dropped", "oldest-bulk frames dropped by bounded send queues")
        self.shaped_frames = r.gauge("net", "shaped_frames", "frames through the link shaper")
        self.shaped_dropped = r.gauge("net", "shaped_dropped", "frames lost by shaper weather (random loss)")
        self.shaped_flap_dropped = r.gauge("net", "shaped_flap_dropped", "frames lost in shaper flap down-windows")
        self.shaped_queue_dropped = r.gauge("net", "shaped_queue_dropped", "frames tail-dropped by shaper pacing queues")
        self.shaped_duplicated = r.gauge("net", "shaped_duplicated", "frames duplicated by the shaper")
        self.shaped_corrupted = r.gauge("net", "shaped_corrupted", "frames with a shaper-flipped payload byte")

    def refresh_from(self, snap: dict) -> None:
        """Republish a Switch.net_snapshot() as absolute gauge values."""
        peers = snap.get("peers", {})
        self.peers.set(len(peers))
        self.quarantined.set(snap.get("quarantined", 0))
        self.sendq_dropped.set(snap.get("sendq_dropped", 0))
        rtts = [p["rtt_ms"] for p in peers.values() if p.get("rtt_ms") is not None]
        self.rtt_ms_max.set(max(rtts) if rtts else 0.0)
        losses = [p.get("loss", 0.0) for p in peers.values()]
        self.loss_max.set(max(losses) if losses else 0.0)
        for field, attr in (
            ("transitions", self.quarantine_transitions),
            ("pings_sent", self.pings_sent),
            ("pongs", self.pongs),
            ("ping_timeouts", self.ping_timeouts),
        ):
            attr.set(sum(p.get(field, 0) for p in peers.values()))
        shaper = snap.get("shaper")
        if shaper is not None:
            total = shaper.get("total", {})
            self.shaped_frames.set(total.get("frames", 0))
            self.shaped_dropped.set(total.get("dropped", 0))
            self.shaped_flap_dropped.set(total.get("flap_dropped", 0))
            self.shaped_queue_dropped.set(total.get("queue_dropped", 0))
            self.shaped_duplicated.set(total.get("duplicated", 0))
            self.shaped_corrupted.set(total.get("corrupted", 0))


class ScenarioMetrics:
    """Scenario-grid observability (scenario/ subsystem).

    A node driven by a grid tile publishes which tile and how far along
    the walk — so an operator watching /metrics mid-soak can correlate a
    latency spike with "tile 7 of 12, flood + flapping" without parsing
    runner logs. The tile's string identity (axis levels) lives in the
    /health "scenario" section; gauges carry only the numeric shape."""

    def __init__(self, registry: "Registry | None" = None):
        r = registry or GLOBAL
        self.active = r.gauge("scenario", "active", "1 while a scenario tile drives this node")
        self.tile_index = r.gauge("scenario", "tile_index", "zero-based index of the running tile (-1 when idle)")
        self.tiles_total = r.gauge("scenario", "tiles_total", "tile count of the running grid walk")
        self.tile_started_unix = r.gauge("scenario", "tile_started_unix", "wall-clock start of the running tile (unix seconds)")

    def refresh_from(self, info: dict) -> None:
        """Republish a registry scenario-section dict (possibly empty)."""
        self.active.set(1.0 if info.get("active") else 0.0)
        self.tile_index.set(float(info.get("tile_index", -1)))
        self.tiles_total.set(float(info.get("tiles_total", 0)))
        self.tile_started_unix.set(float(info.get("started_unix", 0.0)))


class AdmissionMetrics:
    """Front-door admission metrics (admission/ subsystem).

    Every shed path counts: rejected traffic must be visible in the
    exposition, never a silent drop (ISSUE 6 acceptance). Gauges mirror
    the controller's cached overload verdict so dashboards and the
    429-emitting RPC read the same state."""

    def __init__(self, registry: "Registry | None" = None):
        r = registry or GLOBAL
        self.admitted_priority = r.counter("admission", "admitted_priority", "priority-lane txs admitted at the RPC edge")
        self.admitted_bulk = r.counter("admission", "admitted_bulk", "bulk-lane txs admitted at the RPC edge")
        self.rejected_dup = r.counter("admission", "rejected_dup", "replayed tx bytes rejected by the edge dedup")
        self.rejected_overload = r.counter("admission", "rejected_overload", "bulk txs shed at the RPC edge (429) under overload/headroom")
        self.rejected_gossip = r.counter("admission", "rejected_gossip", "gossiped bulk txs shed before CheckTx under overload")
        self.rejected_peer = r.counter("admission", "rejected_peer", "gossiped txs shed by the per-peer rate bucket")
        self.overloaded = r.gauge("admission", "overloaded", "1 = pool past high water (hysteresis)")
        self.occupancy = r.gauge("admission", "pool_occupancy", "pool fill fraction at the last pressure poll")
        # adaptive bulk rate (derived from the engine's live commit rate
        # with hysteresis; controller._effective_bulk_rate): the gauge is
        # what the token bucket is actually refilling at RIGHT NOW
        self.bulk_rate_effective = r.gauge("admission", "bulk_rate_effective", "current bulk token-bucket fill rate (tx/s)")
        self.commit_rate = r.gauge("admission", "commit_rate_observed", "EWMA of the engine commit rate the bulk bucket tracks (tx/s)")
        # per-sender fairness inside the priority lane (ISSUE 9 satellite,
        # closing the PR 6 follow-up): one sender flooding fee-bearing txs
        # must not starve other priority senders
        self.priority_sender_limited = r.counter("admission", "priority_sender_limited", "priority txs past their sender's token budget (demoted to bulk shed rules)")
        self.priority_sender_shed = r.counter("admission", "priority_sender_shed", "over-budget priority txs shed at the RPC edge (429)")
        self.priority_sender_tracked = r.gauge("admission", "priority_sender_tracked", "distinct priority senders in the fairness table")


class EpochMetrics:
    """Validator-set lifecycle metrics (epoch/ subsystem).

    Exposed as ``txflow_epoch_*``. Gauges describe the CURRENT epoch and
    set (number, size, powers, quorum); counters are monotonic lifecycle
    events (boundaries crossed, slashes, scheduled rotations). The node
    refreshes the set gauges on every update_state so a slash is visible
    the block its power change lands (see README runbook)."""

    def __init__(self, registry: "Registry | None" = None):
        r = registry or GLOBAL
        self.number = r.gauge("epoch", "number", "current epoch (0-based)")
        self.length = r.gauge("epoch", "length_blocks", "blocks per epoch (0 = epochs disabled)")
        self.validators = r.gauge("epoch", "validators", "validators in the current set")
        self.total_power = r.gauge("epoch", "total_voting_power", "total stake of the current set")
        self.quorum_power = r.gauge("epoch", "quorum_power", "2n/3+1 stake threshold of the current set")
        self.boundaries = r.counter("epoch", "boundaries_total", "epoch boundary blocks committed")
        self.slashes = r.counter("epoch", "slashes_total", "validators slashed at boundaries")
        self.rotations = r.counter("epoch", "rotations_total", "scheduled rotation entries applied at boundaries")
        self.pending_slashes = r.gauge("epoch", "pending_slashes", "offenders awaiting the next boundary")


class SyncMetrics:
    """Catch-up sync metrics (sync/ subsystem, ``txflow_sync_*``).

    The lag gauge and state gauge (0 idle / 1 syncing / 2 fallback) are
    the operator's first look at a recovering node; the Byzantine /
    timeout counters tell WHY a node keeps rotating servers. Server-side
    ``served_txs`` rides the same registry so one exposition shows both
    halves."""

    def __init__(self, registry: "Registry | None" = None):
        r = registry or GLOBAL
        self.lag = r.gauge("sync", "lag", "commits the best peer advert is ahead of us")
        self.state = r.gauge("sync", "state", "0=idle 1=syncing 2=consensus-block fallback")
        self.ranges_fetched = r.counter("sync", "ranges_fetched", "range responses verified and applied")
        self.txs_fetched = r.counter("sync", "txs_fetched", "committed txs fetched from peers (post-dedup)")
        self.txs_applied = r.counter("sync", "txs_applied", "fetched txs applied through the commit seam")
        self.verify_failures = r.counter("sync", "verify_failures", "fetched certificates failing re-verification")
        self.byzantine_strikes = r.counter("sync", "byzantine_strikes", "sync servers caught serving forged/truncated data")
        self.timeouts = r.counter("sync", "timeouts", "range requests that stalled past the timeout")
        self.rotations = r.counter("sync", "rotations", "serving-peer rotations (stall or strike)")
        self.fallbacks = r.counter("sync", "fallbacks", "degradations to the consensus-block fallback")
        self.served_txs = r.counter("sync", "served_txs", "committed txs this node served to catching-up peers")


class ByzantineMetrics:
    """Accountable vote gossip (health/byzantine.py, ``txflow_byzantine_*``).

    Strikes and quarantines are the unified ledger's totals across BOTH
    sources (gossip verdict attribution and sync-server forgery); the
    ``drop_*`` counters break out the O(1) ingest pre-checks so an
    operator can see WHAT a flooding peer was sending without reading
    per-peer /health detail. The Registry has no label support — one
    counter per drop reason, keyed in ``drop_counters`` for the ledger."""

    def __init__(self, registry: "Registry | None" = None):
        r = registry or GLOBAL
        self.strikes = r.counter("byzantine", "strikes", "misbehavior strikes recorded against peers (gossip + sync)")
        self.quarantines = r.counter("byzantine", "quarantines", "peer vote-traffic quarantines (circuit breaker trips)")
        self.invalid_votes = r.counter("byzantine", "invalid_votes", "device valid=False verdicts attributed to a relaying peer")
        self.drop_unknown_validator = r.counter("byzantine", "drop_unknown_validator", "votes dropped pre-verify: signer not in the validator set")
        self.drop_stale_height = r.counter("byzantine", "drop_stale_height", "votes dropped pre-verify: height behind the stale slack")
        self.drop_replayed_sig = r.counter("byzantine", "drop_replayed_sig", "votes dropped pre-verify: same peer re-sent an identical signature")
        self.drop_quarantined = r.counter("byzantine", "drop_quarantined", "vote segments dropped whole-frame from quarantined peers")
        self.drop_non_committee = r.counter("byzantine", "drop_non_committee", "votes dropped pre-verify: signer not in the epoch's tx-vote committee")
        self.quarantined_peers = r.gauge("byzantine", "quarantined_peers", "peers currently under vote-traffic quarantine")
        self.drop_counters = {
            "unknown_validator": self.drop_unknown_validator,
            "stale_height": self.drop_stale_height,
            "replayed_sig": self.drop_replayed_sig,
            "quarantined": self.drop_quarantined,
            "non_committee": self.drop_non_committee,
        }


class TxFlowMetrics:
    """Fast-path metrics (reference txflowstate/metrics.go:17-45)."""

    def __init__(self, registry: Registry | None = None):
        r = registry or GLOBAL
        self.height = r.gauge("txflow", "height", "committed fast-path height")
        self.committed_txs = r.counter("txflow", "committed_txs", "txs committed via fast path")
        self.committed_votes = r.counter("txflow", "committed_votes", "votes in committed quorums")
        self.verified_votes = r.counter("txflow", "verified_votes", "signatures batch-verified")
        self.invalid_votes = r.counter("txflow", "invalid_votes", "votes failing verification")
        self.batch_size = r.histogram("txflow", "batch_size", "device batch occupancy", buckets=(64, 256, 1024, 4096, 16384, 65536))
        # durable-path degradation (disk full / EIO): commits stay applied
        # in memory, the failure is surfaced loudly here + /health
        self.storage_errors = r.counter("txflow", "storage_errors", "durable writes failed (ENOSPC/EIO) — node degraded, not crashed")
        self.step_time = r.histogram("txflow", "step_seconds", "aggregation step wall time")
        self.tx_processing_time = r.histogram("txflow", "tx_processing_seconds", "ApplyTx wall time")
        # verify-pipeline observability (engine pipelined loop): depth is
        # the tickets currently in flight; overlap_ratio is device-busy
        # wall time over engine-active wall time (1.0 = the device never
        # waited on host prep/routing); device_idle is the accumulated
        # active time with NO verify call in flight — the gap the
        # pipeline exists to close. The *_seconds counters are the
        # per-stage breakdown.
        self.pipeline_depth = r.gauge("txflow", "pipeline_depth", "verify tickets in flight")
        self.pipeline_overlap_ratio = r.gauge("txflow", "pipeline_overlap_ratio", "dispatch -> result usable on the host (device time plus the readback thread waiting for the interpreter lock), summed, / engine-active wall time")
        self.pipeline_device_idle = r.gauge("txflow", "pipeline_device_idle_seconds", "engine-active seconds less the dispatch -> result-usable seconds: a lower bound of device idle time")
        self.pipeline_prep_seconds = r.counter("txflow", "pipeline_prep_seconds", "host batch-prep + dispatch seconds")
        self.pipeline_wait_seconds = r.counter("txflow", "pipeline_wait_seconds", "seconds blocked collecting tickets")
        self.pipeline_route_seconds = r.counter("txflow", "pipeline_route_seconds", "commit-routing seconds")
        # shape-stable batch coalescing (engine.txflow._BatchCoalescer):
        # full_batches dispatched at exactly a canonical bucket (zero
        # padding waste), linger_flushes dispatched partial by deadline,
        # quorum_flushes dispatched partial once the held votes completed
        # a tx's quorum (quorum_probed: pool entries the probe read)
        self.coalesce_full_batches = r.counter("coalesce", "full_batches", "batches dispatched at a full canonical bucket")
        self.coalesce_linger_flushes = r.counter("coalesce", "linger_flushes", "partial buckets flushed by the linger deadline")
        self.coalesce_quorum_flushes = r.counter("coalesce", "quorum_flushes", "partial buckets flushed because the held votes completed a tx's quorum")
        self.coalesce_quorum_probed = r.counter("coalesce", "quorum_probed", "held votes the quorum probe read")
        # background shape warmup (engine.shapes.BackgroundWarmer): votes
        # the engine served via the scalar fallback while their device
        # shape was still compiling, and shapes promoted so far
        self.warmup_cold_fallback_votes = r.counter("warmup", "cold_fallback_votes", "votes served by the CPU fallback while their shape compiled")
        self.warmup_warm_shapes = r.gauge("warmup", "warm_shapes", "kernel shapes compiled and promoted")
        # adaptive pipeline depth (engine.adaptive.AdaptiveDepthController)
        self.pipeline_depth_target = r.gauge("txflow", "pipeline_depth_target", "adaptive controller's current depth target")
        self.pipeline_depth_changes = r.counter("txflow", "pipeline_depth_changes", "adaptive depth adjustments applied")
        # deadline-aware verify lanes (ISSUE 12): priority-lane dispatch
        # volume, speculative quorum commits and the route-tail seconds
        # the early exit removed, adaptive per-lane linger adjustments
        self.lane_prio_batches = r.counter("lanes", "prio_batches", "verify batches dispatched through the priority lane")
        self.lane_prio_votes = r.counter("lanes", "prio_votes", "votes dispatched through the priority lane")
        self.spec_commits = r.counter("txflow", "spec_commits", "commits routed early on the device quorum hint")
        self.spec_saved_seconds = r.counter("txflow", "spec_saved_seconds", "route-tail seconds removed by speculative commits")
        self.adaptive_linger_changes = r.counter("txflow", "adaptive_linger_changes", "adaptive lane-linger adjustments applied")
        # engine-side epoch churn (TxFlow.update_state): a rotation is one
        # validator-set swap observed by this engine; restages swap device
        # constants in place (zero recompiles), rebuilds construct a fresh
        # verifier (capacity exceeded / int32 cap / non-restagable type)
        self.epoch_rotations = r.counter("epoch", "engine_rotations_total", "validator-set changes applied by the engine")
        self.epoch_restages = r.counter("epoch", "engine_restages_total", "rotations served by an in-place verifier restage")
        self.epoch_rebuilds = r.counter("epoch", "engine_rebuilds_total", "rotations that forced a full verifier rebuild")
        self.epoch_votes_dropped = r.counter("epoch", "engine_votes_dropped_total", "in-flight votes discarded (validator left the set)")
        self.epoch_rotation_commits = r.counter("epoch", "engine_rotation_commits_total", "txs committed because rotation lowered the quorum")
