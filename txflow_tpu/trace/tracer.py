"""Per-transaction tracing: bounded span rings + latency histograms.

The commit path is instrumented with named spans (SPAN_* constants
below) recorded into a per-node preallocated ring buffer. Design
constraints, in order:

1. **Low overhead, always on.** The sampling decision is one integer
   xor/mod derived from the tx hash (deterministic across nodes and
   replays — never Python ``hash()``, which is PYTHONHASHSEED-salted),
   and a recorded span is one tuple store under a leaf lock. The tier-1
   overhead gate (tests/test_trace.py) pins the per-vote cost under 3%
   of a scalar signature verify.
2. **Deterministic timestamps.** Every clock read routes through the
   ``utils/clock.py`` monotonic seam, enforced by txlint's
   ``trace-clock`` pass over the traced modules — replays pin one
   module and get reproducible spans.
3. **Zero-cost off switch.** ``TraceConfig(enabled=False)`` yields the
   ``NullTracer``: every method is a constant-return no-op, no ring, no
   histograms.

Leak accounting: ``begin()``/``finish()`` pairs (device tickets in
flight, commit queue residency, commit events on their way to a
socket) are tracked in an open table; ``open_count()`` must return 0
after quiescence — ``tools/soak.py --overload`` asserts this over RPC,
the same class of check as the PR 3 drain-on-stop claim-leak proof.

Every record is ``(tx, name, start, end, step)``: ``step`` is the id of
the engine step that carried or decided it (0 = none), so a tx's
waterfall joins the step's. **Stage spans** (``STAGE_SPANS``: one per
engine stage per step, ``tx`` empty, recorded whatever the tx sampling
says) live in a ring of their own, so a flood of per-tx spans never
evicts the step record; collector pauses (``gc_pause``, the generation
in ``tx``) are stage spans kept in a third, lock-free ring because the
hook that records them can fire while this tracer's lock is held.
"""

from __future__ import annotations

import gc
import sys
import threading
from collections import deque

from ..analysis.lockgraph import make_lock
from ..utils.clock import monotonic, now_ns
from ..utils.config import TraceConfig
from ..utils.metrics import GLOBAL, Registry

# canonical span names, in commit-path order (export assigns one
# Perfetto track per name, in this order)
SPAN_ADMISSION = "admission"
SPAN_TX_INGEST = "mempool_ingest"
SPAN_GOSSIP_INGEST = "gossip_ingest"
SPAN_SIGN = "sign_walk"
SPAN_VOTE_INGEST = "vote_ingest"
# accountable gossip (health/byzantine.py): a vote rejected by the O(1)
# ingest pre-checks (unknown validator / stale height) — zero-length
# marker at the drop instant, so a trace shows WHERE hostile traffic
# died relative to the honest pipeline
SPAN_PRE_DROP = "pre_verify_drop"
# a sampled tx's first vote in the pool -> host_prep of the step that
# drains it: what its votes waited for the engine and in the lane's hold
# (per tx: a step that carries two txs has two, of different lengths)
SPAN_VOTE_WAIT = "vote_wait"
# served-tx waits: request parsed -> tx in the mempool (rpc/server.py),
# mempool insert -> the sign walk takes the tx up (txvote_reactor.py)
SPAN_RPC = "rpc_ingest"
SPAN_SIGN_WAIT = "sign_wait"
# engine stage spans, in engine order (engine/txflow.py records each
# through ONE helper that also feeds pipeline_stats(), the Prometheus
# pipeline_*_seconds counters and the profiler annotation)
SPAN_POOL_WAIT = "pool_wait"
# first vote accepted by the pool since the engine's last drain -> the
# engine takes the batch up: its lane's hold begins, or its host_prep
# where nothing is held. The thread hop from the inserting thread, or,
# with a step in flight, the rest of that step's dispatch/collect/route.
# It lies over those stages: no stretch of the engine thread's own time
SPAN_PICKUP = "pickup_wait"
SPAN_LOCK_WAIT = "lock_wait"
# children of host_prep, for the state a step carries over from earlier
# steps. late_drop: the pool removal of drained votes that can never be
# added (their tx has committed, or their validator's vote is already in
# the open set); the scan that finds them is the drain loop itself and
# is not separable, and a drain that drops nothing records none.
# carry_prior: the loop that reads each slot's stake out of the open vote
# sets into the step's prior array, one a step
SPAN_LATE_DROP = "late_drop"
SPAN_CARRY = "carry_prior"
# per-lane coalescer holds (ISSUE 12 verify lanes): the engine's bulk
# lane records linger_bulk, the priority lane linger_prio — report.py
# sums both into the critical-path linger bucket
SPAN_LINGER_PRIO = "linger_prio"
SPAN_LINGER_BULK = "linger_bulk"
# speculative quorum commit: decision-to-route-end window of a commit
# that left early on the device quorum hint — its length IS the route
# tail the early exit removed for that tx
SPAN_SPEC = "spec_commit"
SPAN_PREP = "host_prep"
SPAN_DISPATCH = "dispatch"
# max(dispatch end, previous step's ready) -> packed result usable on
# the host, as a host thread could stamp it (the staging ring's thread,
# once it holds the interpreter lock again). Spans of one verifier never
# overlap. NOT the device's own time: under load the stamp is late by
# what that thread waited for the lock (the device's idle share is the
# profiler's to give). Begun at dispatch, finished at collect: an
# orphaned ticket is an open span
SPAN_DEVICE = "device_busy"
SPAN_COLLECT = "collect_wait"
SPAN_ROUTE = "route"
SPAN_ROUTE_TALLY = "route_tally"
SPAN_ROUTE_COMMIT = "route_commit"
SPAN_ROUTE_PURGE = "route_purge"
SPAN_QUORUM = "quorum_latch"
# per tx: the start of the pickup_wait of the step that completes the
# tx's quorum (the pool's first vote since the previous drain; that
# step's host_prep where none came) -> the commit decision. The node's
# own share of a quorum whose last frame arrives last: the frames'
# delays before that step are not in it
SPAN_QUORUM_WAIT = "quorum_wait"
SPAN_COMMIT = "commit_apply"
# commit event queued (engine/execution.py) -> frame handed to a
# websocket subscriber's socket (rpc/server.py), or event_bus.publish's
# return where nobody subscribes
SPAN_PUBLISH = "publish"
# a collection of Python's collector, generation in ``tx`` ("gen0".."gen2")
SPAN_GC = "gc_pause"
# catch-up sync (sync/): fetch = request sent -> response received,
# verify = certificate batch re-verification, apply = commit-seam apply
SPAN_SYNC_FETCH = "sync_fetch"
SPAN_SYNC_VERIFY = "sync_verify"
SPAN_SYNC_APPLY = "sync_apply"
SPAN_E2E = "e2e"

SPAN_ORDER = (
    SPAN_RPC, SPAN_ADMISSION, SPAN_TX_INGEST, SPAN_GOSSIP_INGEST,
    SPAN_SIGN_WAIT, SPAN_SIGN, SPAN_VOTE_INGEST, SPAN_PRE_DROP, SPAN_VOTE_WAIT,
    SPAN_POOL_WAIT, SPAN_PICKUP, SPAN_LINGER_PRIO, SPAN_LINGER_BULK, SPAN_PREP,
    SPAN_LOCK_WAIT, SPAN_LATE_DROP, SPAN_CARRY, SPAN_DISPATCH, SPAN_DEVICE,
    SPAN_COLLECT, SPAN_ROUTE, SPAN_ROUTE_TALLY, SPAN_QUORUM, SPAN_QUORUM_WAIT,
    SPAN_SPEC, SPAN_ROUTE_COMMIT, SPAN_COMMIT, SPAN_ROUTE_PURGE, SPAN_PUBLISH, SPAN_GC,
    SPAN_SYNC_FETCH, SPAN_SYNC_VERIFY, SPAN_SYNC_APPLY, SPAN_E2E,
)

# the step record: kept apart from the per-tx ring (see module docstring)
STAGE_SPANS = frozenset((
    SPAN_POOL_WAIT, SPAN_PICKUP, SPAN_LINGER_PRIO, SPAN_LINGER_BULK, SPAN_PREP,
    SPAN_LOCK_WAIT, SPAN_LATE_DROP, SPAN_CARRY, SPAN_DISPATCH, SPAN_DEVICE,
    SPAN_COLLECT, SPAN_ROUTE, SPAN_ROUTE_TALLY, SPAN_ROUTE_COMMIT,
    SPAN_ROUTE_PURGE, SPAN_GC,
))


class NullTracer:
    """Zero-cost stand-in when tracing is off: same surface, no state."""

    active = False

    def sampled(self, tx_hash) -> bool:
        return False

    def sampled_key(self, key) -> bool:
        return False

    def span(self, tx_hash, name, start, end, step=0) -> None:
        pass

    def begin(self, tx_hash, name, start=None, step=0) -> int:
        return 0

    def finish(self, span_id, end=None, start=None) -> None:
        pass

    def abandon(self, span_id) -> None:
        pass

    def anchor(self, tx_hash, t=None) -> None:
        pass

    def anchored(self, tx_hash) -> None:
        return None

    def first_vote(self, tx_hash, t) -> None:
        pass

    def take_first_vote(self, tx_hash) -> None:
        return None

    def latch(self, tx_hash, name=SPAN_E2E, t=None) -> None:
        pass

    def install_gc_hook(self) -> None:
        pass

    def remove_gc_hook(self) -> None:
        pass

    def open_count(self) -> int:
        return 0

    def spans(self) -> list:
        return []

    def digest(self) -> dict:
        return {"enabled": False, "open_spans": 0, "recorded": 0, "dropped": 0}

    def dump(self, node_id: str = "") -> dict:
        return {
            "node": node_id,
            "base_wall_ns": 0,
            "base_mono": 0.0,
            "spans": [],
            "open_spans": 0,
        }

    def reset(self) -> None:
        pass


NULL_TRACER = NullTracer()

# fine-grained low end (sub-ms host stages) up through multi-second
# commit tails — one ladder for every span family so digests compare
LATENCY_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
    0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)


class TraceMetrics:
    """``txflow_trace_*`` bundle: per-span-name latency histograms plus
    the recorded/open counters the soak's leak assertion scrapes."""

    def __init__(self, registry: Registry | None = None):
        r = registry or GLOBAL
        self._r = r
        self.spans_recorded = r.counter(
            "trace", "spans_recorded_total", "trace spans recorded into the ring"
        )
        self.open_spans = r.gauge(
            "trace", "open_spans", "begun spans not yet finished (0 after quiescence)"
        )
        self._hists: dict[str, object] = {}

    def observe(self, name: str, duration_s: float) -> None:
        h = self._hists.get(name)
        if h is None:
            # Registry._reg dedupes under its own lock, so a racing first
            # observe lands on the same Histogram instance
            h = self._r.histogram(
                "trace", f"span_{name}_seconds",
                f"{name} span duration", buckets=LATENCY_BUCKETS,
            )
            self._hists[name] = h
        h.observe(duration_s)
        self.spans_recorded.add(1)

    def quantiles_ms(self) -> dict[str, dict]:
        out: dict[str, dict] = {}
        for name in sorted(self._hists):
            h = self._hists[name]
            q = {
                "p50": h.quantile(0.5),
                "p99": h.quantile(0.99),
                "p999": h.quantile(0.999),
            }
            out[name] = {
                k: (round(v * 1e3, 3) if v is not None else None)
                for k, v in q.items()
            }
            out[name]["count"] = h._count
            out[name]["sum_ms"] = round(h._sum * 1e3, 3)
        return out


class _Ring:
    """Preallocated overwrite-oldest record store (caller holds the lock)."""

    __slots__ = ("cap", "buf", "n")

    def __init__(self, cap: int):
        self.cap = cap
        self.buf: list = [None] * cap
        self.n = 0  # records ever stored (Tracer.span); slot = n % cap

    def items(self) -> list:
        if self.n <= self.cap:
            return self.buf[: self.n]
        i = self.n % self.cap
        return self.buf[i:] + self.buf[:i]

    def dropped(self) -> int:
        return max(0, self.n - self.cap)


class _GcHook:
    """The process's ONE ``gc.callbacks`` entry, fanned out to the
    tracers of the nodes that are running. It never takes a tracer's
    lock: a collection can start at any bytecode, inside ``span``
    too, and the lock is not reentrant."""

    def __init__(self):
        self.tracers: list = []
        self.mtx = threading.Lock()
        self._t0 = 0.0
        self._ann = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            # on the profiler's clock too, where the engine has brought
            # JAX's profiler in (never imported from here)
            prof = sys.modules.get("jax.profiler")
            if prof is not None:
                self._ann = prof.TraceAnnotation(SPAN_GC, generation=info["generation"])
                self._ann.__enter__()
            self._t0 = monotonic()
            return
        t1 = monotonic()
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
        rec = (f"gen{info['generation']}", SPAN_GC, self._t0, t1, 0)
        for tr in self.tracers:
            tr._gc.append(rec)
            tr._gc_n += 1


_GC_HOOK = _GcHook()


class Tracer:
    """Per-node span recorder. All timestamps are utils.clock.monotonic
    seconds; ``base_wall_ns``/``base_mono`` anchor them to the wall
    clock so cross-node merges land on one timeline (export.py)."""

    active = True

    def __init__(
        self,
        config: TraceConfig | None = None,
        registry: Registry | None = None,
        node_id: str = "",
    ):
        cfg = config or TraceConfig()
        self.sample_rate = max(1, int(cfg.sample_rate))
        self.seed = int(cfg.seed) & 0xFFFFFFFF
        self.capacity = max(16, int(cfg.ring_capacity))
        self.node_id = node_id
        self._ring = _Ring(self.capacity)  # per-tx spans
        self._stages = _Ring(self.capacity)  # STAGE_SPANS: the step record
        # collector pauses, appended by _GcHook without the lock
        self._gc: deque = deque(maxlen=self.capacity)
        self._gc_n = 0
        self._gc_folded = 0  # pauses already observed into the histogram
        self._open: dict[int, tuple] = {}
        self._next_id = 1
        self._anchors: dict[str, float] = {}
        self._first_votes: dict[str, float] = {}
        self._anchor_cap = 4 * self.capacity
        self._lk = make_lock("trace.Tracer._lk")
        self.base_wall_ns = now_ns()
        self.base_mono = monotonic()
        self.metrics = TraceMetrics(registry) if registry is not None else None

    # -- sampling (deterministic: same txs on every node / every replay) --

    def sampled(self, tx_hash: str) -> bool:
        """1-in-sample_rate by the leading 32 bits of the tx hash."""
        try:
            v = int(tx_hash[:8], 16)
        except (ValueError, TypeError):
            return False
        return (v ^ self.seed) % self.sample_rate == 0

    def sampled_key(self, key: bytes) -> bool:
        """Same predicate from the raw digest (key[:4] == hex[:8])."""
        if len(key) < 4:
            return False
        return (int.from_bytes(key[:4], "big") ^ self.seed) % self.sample_rate == 0

    # -- span recording --

    def span(self, tx_hash: str, name: str, start: float, end: float,
             step: int = 0) -> None:
        """Record a complete span (both ends measured by the caller).
        ``step`` names the engine step that carried or decided it."""
        ring = self._stages if name in STAGE_SPANS else self._ring
        rec = (tx_hash, name, start, end, step)
        with self._lk:
            ring.buf[ring.n % ring.cap] = rec
            ring.n += 1
        if self.metrics is not None:
            self.metrics.observe(name, max(0.0, end - start))

    def begin(self, tx_hash: str, name: str, start: float | None = None,
              step: int = 0) -> int:
        """Open a cross-thread span; returns an id for finish()/abandon().
        Every begun span must be closed — open_count() is the leak
        detector the soak asserts against."""
        t = monotonic() if start is None else start
        with self._lk:
            sid = self._next_id
            self._next_id += 1
            self._open[sid] = (tx_hash, name, t, step)
        return sid

    def finish(self, span_id: int, end: float | None = None,
               start: float | None = None) -> None:
        """Close and record. ``start`` replaces the begin time where the
        true start is known only now (device_busy: the later of its
        dispatch and the previous step's ready time)."""
        if not span_id:
            return
        t = monotonic() if end is None else end
        with self._lk:
            entry = self._open.pop(span_id, None)
        if entry is not None:
            t0 = entry[2] if start is None else start
            self.span(entry[0], entry[1], t0, t, entry[3])

    def abandon(self, span_id: int) -> None:
        """Close without recording (work shed or superseded mid-span)."""
        if not span_id:
            return
        with self._lk:
            self._open.pop(span_id, None)

    # -- end-to-end anchoring (first ingest -> commit) --

    def anchor(self, tx_hash: str, t: float | None = None) -> None:
        """First-seen timestamp for the e2e span (idempotent). Bounded:
        anchors for txs that never commit (shed, evicted) age out FIFO
        instead of growing without bound."""
        tm = monotonic() if t is None else t
        with self._lk:
            if tx_hash in self._anchors:
                return
            if len(self._anchors) >= self._anchor_cap:
                self._anchors.pop(next(iter(self._anchors)))
            self._anchors[tx_hash] = tm

    def anchored(self, tx_hash: str) -> float | None:
        """The anchor's time, left in place (the sign walk's wait starts
        at the mempool insert, which is where a tx is anchored)."""
        with self._lk:
            return self._anchors.get(tx_hash)

    def first_vote(self, tx_hash: str, t: float) -> None:
        """When a sampled tx's first vote entered the vote pool (kept
        once, bounded like the anchors): vote_wait's start."""
        with self._lk:
            if tx_hash in self._first_votes:
                return
            if len(self._first_votes) >= self._anchor_cap:
                self._first_votes.pop(next(iter(self._first_votes)))
            self._first_votes[tx_hash] = t

    def take_first_vote(self, tx_hash: str) -> float | None:
        with self._lk:
            return self._first_votes.pop(tx_hash, None)

    def latch(self, tx_hash: str, name: str = SPAN_E2E, t: float | None = None) -> None:
        """Close the anchored span (commit applied). No-op when the
        anchor aged out or the tx was never anchored."""
        with self._lk:
            t0 = self._anchors.pop(tx_hash, None)
        if t0 is not None:
            self.span(tx_hash, name, t0, monotonic() if t is None else t)

    # -- collector pauses --

    def install_gc_hook(self) -> None:
        """Record gc_pause spans from now on (Node.start). One
        ``gc.callbacks`` entry a process however many tracers ask."""
        hook = _GC_HOOK
        with hook.mtx:
            if self in hook.tracers:
                return
            if not hook.tracers:
                gc.callbacks.append(hook)
            hook.tracers = hook.tracers + [self]  # the hook iterates lock-free

    def remove_gc_hook(self) -> None:
        hook = _GC_HOOK
        with hook.mtx:
            if self not in hook.tracers:
                return
            hook.tracers = [t for t in hook.tracers if t is not self]
            if not hook.tracers:
                gc.callbacks.remove(hook)

    # -- introspection --

    def open_count(self) -> int:
        with self._lk:
            return len(self._open)

    def spans(self) -> list[dict]:
        """All three rings merged by start, as export-ready dicts."""
        with self._lk:
            buf = self._ring.items() + self._stages.items()
        buf += list(self._gc)
        buf.sort(key=lambda r: r[2])
        return [
            {"tx": tx, "name": name, "start": s, "end": e, "step": step}
            for (tx, name, s, e, step) in buf
        ]

    def dropped(self) -> int:
        """Per-tx spans overwritten before anyone read them."""
        with self._lk:
            return self._ring.dropped()

    def digest(self) -> dict:
        """p50/p99/p999 per span family + leak counters (/health).
        ``dropped`` counts overwritten per-tx spans, ``stage_dropped``
        overwritten stage spans (each ring holds ``ring_capacity``)."""
        with self._lk:
            recorded = self._ring.n + self._stages.n
            dropped = self._ring.dropped()
            stage_dropped = self._stages.dropped()
            open_spans = len(self._open)
        gc_n = self._gc_n
        d = {
            "enabled": True,
            "sample_rate": self.sample_rate,
            "recorded": recorded + gc_n,
            "dropped": dropped,
            "stage_dropped": stage_dropped + max(0, gc_n - self.capacity),
            "open_spans": open_spans,
        }
        if self.metrics is not None:
            # the hook cannot touch the histograms' locks: fold here
            new = min(gc_n - self._gc_folded, len(self._gc))
            self._gc_folded = gc_n
            if new > 0:
                for rec in list(self._gc)[-new:]:
                    self.metrics.observe(SPAN_GC, rec[3] - rec[2])
            self.metrics.open_spans.set(open_spans)
            d["latency_ms"] = self.metrics.quantiles_ms()
        return d

    def dump(self, node_id: str = "") -> dict:
        """Everything export/merge needs from one node."""
        return {
            "node": node_id or self.node_id,
            "base_wall_ns": self.base_wall_ns,
            "base_mono": self.base_mono,
            "open_spans": self.open_count(),
            "dropped": self.dropped(),
            "spans": self.spans(),
        }

    def reset(self) -> None:
        with self._lk:
            self._ring = _Ring(self.capacity)
            self._stages = _Ring(self.capacity)
            self._open.clear()
            self._anchors.clear()
            self._first_votes.clear()
        self._gc.clear()


def make_tracer(
    config: TraceConfig | None = None,
    registry: Registry | None = None,
    node_id: str = "",
):
    """Tracer or NullTracer per config — the ONE construction seam."""
    cfg = config or TraceConfig()
    if not getattr(cfg, "enabled", True):
        return NULL_TRACER
    return Tracer(cfg, registry=registry, node_id=node_id)
