"""TxFlow: per-tx vote aggregation + instant commit (reference txflow/service.go).

The reference's ``checkMaj23Routine`` walks the vote-pool CList one vote at
a time, verifying each ed25519 signature on the host under a mutex
(:123-166 -> types/vote_set.go:81-131). Here one aggregation **step**:

1. drains a batch of pending votes from the pool (insertion order — the
   canonical intra-batch order, so replays and the scalar model agree);
2. assigns a tx slot per distinct tx hash and gathers each slot's prior
   accumulated stake from its host TxVoteSet;
3. runs the batched device verify+tally (one XLA program: ed25519 double
   scalar mult + segment-sum stake + quorum compare);
4. routes each verified vote into its authoritative ``TxVoteSet`` via the
   reference-identical decision path (first-signature-wins, conflict
   rejection) and, for every tx that crossed 2/3:
   save to TxStore -> fetch tx from mempool by key -> ApplyTx -> purge the
   quorum's votes from the pool -> push tx into the commitpool (exactly the
   sequence of txflow/service.go:216-232).

Divergences from the reference (defects fixed, per SURVEY.md §0):
- committed TxVoteSets are dropped from the in-flight map (the reference
  leaks them, service.go:200-209); late votes for a committed tx are
  discarded via the committed-cache/TxStore check;
- votes that can never be added (invalid signature, conflicting signature,
  unknown validator) are removed from the pool instead of lingering
  forever in the CList.
"""

from __future__ import annotations

import contextlib
import queue as _queue
import threading

import numpy as np

from ..pool.mempool import Mempool
from ..pool.txvotepool import TxVotePool
from ..store.tx_store import TxStore
from ..trace.tracer import (
    NULL_TRACER,
    SPAN_CARRY,
    SPAN_COLLECT,
    SPAN_COMMIT,
    SPAN_DEVICE,
    SPAN_DISPATCH,
    SPAN_LATE_DROP,
    SPAN_LINGER_BULK,
    SPAN_LINGER_PRIO,
    SPAN_LOCK_WAIT,
    SPAN_PICKUP,
    SPAN_POOL_WAIT,
    SPAN_PREP,
    SPAN_QUORUM,
    SPAN_QUORUM_WAIT,
    SPAN_ROUTE,
    SPAN_ROUTE_COMMIT,
    SPAN_ROUTE_PURGE,
    SPAN_ROUTE_TALLY,
    SPAN_SPEC,
    SPAN_VOTE_WAIT,
)
from ..types import TxVote, TxVoteSet
from ..types.validator import ValidatorSet
from ..analysis.lockgraph import make_rlock
from ..analysis.racegraph import shared_field
from ..utils.cache import make_lru
from ..utils.clock import monotonic
from ..utils.collector import COLLECTOR
from ..utils.config import EngineConfig
from ..utils.failpoints import FailpointError
from ..utils.metrics import TxFlowMetrics
from ..verifier import DeviceVoteVerifier, ReadyTicket, ScalarVoteVerifier
from .execution import TxExecutor


# below this many drained votes the host-pool shard bookkeeping costs
# more than the parallel assembly saves (mirrors ops.ed25519_batch's
# _POOL_MIN_ROWS; light-load steps stay serial either way)
_POOL_MIN_VOTES = 256

_ANNOTATION: list = []  # the class below, looked up once


def _annotation_cls():
    """``jax.profiler.TraceAnnotation``, imported on first use (a null
    context where JAX is absent). Outside a profiler session it is an
    inactive TraceMe, well under a microsecond; inside one the stage
    lands in the host plane of the ``.xplane.pb``, on the device
    events' clock."""
    if not _ANNOTATION:
        try:
            from jax.profiler import TraceAnnotation
        except Exception:
            def TraceAnnotation(name, **kw):
                return contextlib.nullcontext()
        _ANNOTATION.append(TraceAnnotation)
    return _ANNOTATION[0]


class _Stage:
    """The ONE record site of an engine stage: two clock reads around the
    work feed every sink (``TxFlow._stage_done``: the pipeline_stats()
    counter, the Prometheus pipeline_*_seconds counter, the stage span)
    and the work runs inside a profiler annotation of the same name.
    Entered per stage per step, never per vote or per tx."""

    __slots__ = ("_eng", "_name", "step", "skip", "_ann", "t0", "t1")

    def __init__(self, eng: "TxFlow", name: str, step: int = 0, votes: int = 0):
        self._eng = eng
        self._name = name
        self.step = step  # may be corrected before exit (host_prep)
        self.skip = False  # set inside: the work turned out to be none
        self._ann = _annotation_cls()(name, step=step, votes=votes)
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "_Stage":
        self._ann.__enter__()
        self.t0 = monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = monotonic()
        self._ann.__exit__(*exc)
        if not self.skip:
            self._eng._stage_done(self._name, self.t0, self.t1, self.step)
        return False


class _StepPrep:
    """Host-side product of one pool drain: everything the verify call
    and the routing pass need. In the pipelined loop this is built while
    the PREVIOUS batch's kernel is still in flight; the dedup/prior state
    it snapshots may therefore be one batch stale, which is safe because
    routing re-validates every vote against vote_sets/_committed at
    collect time and quorum is decided by the host TxVoteSet, never by
    the device's (possibly stale-prior) maj23 output."""

    __slots__ = (
        "keys", "votes", "slots", "n_slots", "prior", "msgs", "sigs",
        "val_idx", "dropped", "verifier", "t0", "step",
        "trace_txs", "dispatch_end", "device_sid", "lane",
        "drop_t", "carry_t", "pickup_t0",
    )

    def __init__(self, t0: float, lane: str | None = None):
        self.keys: list[bytes] = []
        self.votes: list[TxVote] = []
        self.slots: list[int] = []
        self.n_slots = 0
        self.prior = None
        self.msgs: list[bytes] = []
        self.sigs: list[bytes] = []
        self.val_idx = None
        self.dropped = 0
        self.verifier = None
        self.t0 = t0
        # the engine's step id (0 until a batch with votes is formed):
        # every stage span of the step and every per-tx span the step
        # decides carries it
        self.step = 0
        # sampled tx hashes in this batch: each gets its vote_wait span
        self.trace_txs: list[str] = []
        self.dispatch_end = t0
        # open device_busy span (begun at dispatch, finished at collect:
        # the leak check proves no ticket is ever orphaned)
        self.device_sid = 0
        # which drain lane produced this batch ("prio" / "bulk" / None =
        # merged legacy drain): routes requeues back to the lane's own
        # retry list so a priority repeat never queues behind bulk
        self.lane = lane
        # clock reads around the late_drop / carry_prior work, taken
        # under _mtx and recorded by _prep_batch once the lock is free
        # (None = the drain dropped nothing / formed no batch)
        self.drop_t: tuple[float, float] | None = None
        self.carry_t: tuple[float, float] | None = None
        # where the step's pickup_wait began (the pool's first vote since
        # the previous drain), else its host_prep: each quorum_wait span
        # this step decides starts here
        self.pickup_t0 = t0


class _BatchCoalescer:
    """Shape-stable batch sizing: dispatch full canonical buckets, hold
    partials until a linger deadline.

    The device compiles one XLA program per batch-bucket shape; a batch
    of arbitrary gossip-delivered size pads up to its bucket, wasting the
    pad fraction of every kernel call — and a size past the prewarmed
    ladder compiles mid-run. This coalescer makes the engine emit ONLY
    sizes from the verifier's own bucket ladder (>= min_batch, <= the
    drain cap): when the pending backlog covers a bucket, exactly that
    bucket is drained (zero padding, guaranteed-warm shape, remainder
    carries to the next decision); otherwise the partial backlog is held
    until the held votes complete some tx's quorum by stake (``probe``,
    the engine's _QuorumProbe: nothing that could change that tx's
    outcome is still to come), ``linger`` elapses from its first vote, or
    the pool goes idle (note_idle, the idle_flush analog), then flushes
    at whatever size coalesced — still padded to a canonical bucket by
    the verifier. A hold that decides no tx ends on the clock alone.

    decide() is called from the engine thread only; the counters feed
    txflow_coalesce_* metrics and pipeline_stats()["coalesce"]."""

    __slots__ = (
        "targets", "linger", "full_batches", "linger_flushes",
        "quorum_flushes", "_probe",
        "_deadline", "_idle", "_clock", "_metrics", "_tracer", "_hold_t0",
        "span_name", "flush_t0", "wide_from", "wide_ok", "wide_full_batches",
    )

    def __init__(self, buckets, cap: int, min_batch: int, linger: float,
                 metrics=None, clock=monotonic, tracer=None,
                 multiple: int = 1, span_name: str = SPAN_LINGER_BULK,
                 wide_from: int | None = None, probe=None):
        # mesh divisibility: a sharded verifier pads every dispatch up to
        # a multiple of its shard count anyway (verifier.bucket_size), so
        # round the full-bucket targets here and drain exactly what the
        # compiled sharded shape holds — zero pad waste on full buckets,
        # same ladder length
        m = max(1, int(multiple))
        targets = sorted(
            {-(-b // m) * m for b in buckets if min_batch <= b <= cap}
        )
        # no bucket fits the [min_batch, cap] band: degrade to cap-sized
        # dispatches (still one stable shape — cap is the largest bucket)
        self.targets = targets or [-(-cap // m) * m]
        self.linger = linger
        self.full_batches = 0
        self.linger_flushes = 0
        # holds ended by the probe, before their deadline; the probe is
        # asked only while a partial batch is held (never where a full
        # bucket is handed out) and returns whether the held backlog
        # completes a quorum
        self.quorum_flushes = 0
        self._probe = probe
        self._deadline: float | None = None
        self._idle = False
        self._clock = clock
        self._metrics = metrics
        self._tracer = tracer or NULL_TRACER
        self._hold_t0 = 0.0
        # per-lane trace family (linger_prio / linger_bulk): report.py
        # attributes the hold to the lane that paid it
        self.span_name = span_name
        # when the hold began that the last decide() flushed (0.0 = it
        # handed out a full bucket, nothing was held): where the batch's
        # pickup_wait ends
        self.flush_t0 = 0.0
        # wide-rung gate (EngineConfig.wide_buckets): rungs ABOVE
        # wide_from are eligible only while wide_ok holds — the adaptive
        # linger controller clears it (set_wide) when batch latency
        # breaches budget, since one 65536-row dispatch that blows the
        # deadline costs more than the per-call overhead it saved. A
        # coalescer built without wide_from (wide_from=None) has no
        # wide rungs, so the gate is inert.
        self.wide_from = None if wide_from is None else int(wide_from)
        self.wide_ok = True
        self.wide_full_batches = 0

    def decide(self, pending: int, step: int = 0) -> int:
        """Votes to dispatch NOW: a full canonical bucket, the whole
        backlog on linger/idle expiry or once it completes a quorum, or 0
        (keep coalescing). ``step`` is the id the dispatched batch will
        take (the linger span's)."""
        if pending <= 0:
            self._deadline = None
            self._idle = False
            return 0
        full = 0
        for b in self.targets:
            if pending >= b:
                if (
                    self.wide_from is not None
                    and b > self.wide_from
                    and not self.wide_ok
                ):
                    break  # wide rungs gated off: stop at the classic cap
                full = b
            else:
                break
        if full:
            self._deadline = None
            self._idle = False
            self.flush_t0 = 0.0
            self.full_batches += 1
            if self.wide_from is not None and full > self.wide_from:
                self.wide_full_batches += 1
            if self._metrics is not None:
                self._metrics.coalesce_full_batches.add(1)
            return full
        now = self._clock()
        if self._deadline is None:
            self._deadline = now + self.linger
            self._hold_t0 = now
        if now >= self._deadline or self._idle:
            self.linger_flushes += 1
            if self._metrics is not None:
                self._metrics.coalesce_linger_flushes.add(1)
        elif self._probe is not None and self._probe():
            self.quorum_flushes += 1
            if self._metrics is not None:
                self._metrics.coalesce_quorum_flushes.add(1)
        else:
            return 0
        self._deadline = None
        self._idle = False
        self.flush_t0 = self._hold_t0
        if self._tracer.active:
            # batch-level hold: no single tx owns it, so the span is
            # tagged with the empty tx (report.py attributes linger
            # from the histogram sum, not per tx)
            self._tracer.span("", self.span_name, self._hold_t0, now, step)
        return pending

    @property
    def holding(self) -> bool:
        """A partial batch is held: the engine's pool waits meanwhile
        belong to this lane's linger span."""
        return self._deadline is not None

    def set_wide(self, ok: bool) -> None:
        """Gate the wide rungs (called from the engine thread by
        ``_steer_lingers`` with the adaptive controller's verdict)."""
        self.wide_ok = bool(ok)

    def note_idle(self) -> None:
        """The pool wait timed out with votes pending and nothing new
        arriving: flush on the next decide instead of riding out the
        full linger (light-load latency, the idle_flush rationale)."""
        if self._deadline is not None:
            self._idle = True

    def wait_budget(self, poll: float, idle_flush: float) -> float:
        """Bound for the engine's pool wait so a linger flush fires on
        time and idle detection happens on the idle_flush scale."""
        budget = poll
        if self._deadline is not None:
            rem = self._deadline - self._clock()
            if rem <= 0:
                # deadline already expired: the flush is due NOW — the
                # old 0.5 ms floor here held every late linger flush for
                # one extra poll past its deadline (ISSUE 12 small fix)
                return 0.0
            budget = min(budget, max(rem, 0.0005))
            if idle_flush > 0:
                budget = min(budget, idle_flush)
        return budget


class _QuorumProbe:
    """Does the backlog one lane's coalescer holds complete some tx's
    quorum by stake? For each tx it keeps the held validators (each
    counted once, by the power of the engine's validator set) and their
    stake; a tx is complete where that stake plus the verified stake of
    its open vote set reaches ``val_set.quorum_power()``.

    Incremental: a call reads only the lane's pool entries appended since
    the last (its own cursor, which a drain resets to the lane's drain
    cursor: what is still undrained), and looks again only at the txs
    that gained a vote, or at every held tx once a step has been routed
    since (routing adds stake to the open sets). Votes of a step in
    flight (drained, not yet routed) are in neither sum, so it can
    under-count, which keeps the hold on its clock, and never counts a
    vote twice. A held signature that fails on the device only moves
    that tx's quorum one step later: the device tally and routing decide
    every commit. Engine thread only; pool and vote sets are read under
    the engine's _mtx."""

    __slots__ = ("_eng", "_prio", "_cursor", "_held", "_touched",
                 "_routed", "_seed", "_metrics", "probed")

    def __init__(self, eng: "TxFlow", prio: bool, metrics=None):
        self._eng = eng
        self._prio = prio
        self._metrics = metrics
        self.probed = 0  # pool entries read
        self.reset(0)

    def reset(self, cursor: int) -> None:
        """A drain of this lane took its votes up to ``cursor``: start
        over from what is undrained (the lane's requeued votes, read on
        the next call, then the log from the cursor)."""
        self._cursor = cursor
        # tx hash -> [held stake, {validator address: power}]
        self._held: dict[str, list] = {}
        self._touched: set[str] = set()
        self._routed = -1
        self._seed = True

    def _hold(self, vote: TxVote) -> None:
        eng = self._eng
        tx_hash = vote.tx_hash
        addr = vote.validator_address
        power = eng._addr_power.get(addr)
        if not power or eng._committed.__contains__(_hash_key(tx_hash)):
            return
        rec = self._held.get(tx_hash)
        if rec is None:
            rec = self._held[tx_hash] = [0, {}]
        elif addr in rec[1]:
            return
        rec[1][addr] = power
        rec[0] += power
        self._touched.add(tx_hash)

    def _complete(self, tx_hash: str) -> bool:
        eng = self._eng
        if eng._committed.__contains__(_hash_key(tx_hash)):
            del self._held[tx_hash]
            return False
        stake, held = self._held[tx_hash]
        vs = eng.vote_sets.get(tx_hash)
        need = eng.val_set.quorum_power() - (vs.stake() if vs is not None else 0)
        if stake < need:
            return False
        if vs is None:
            return True
        got = 0
        for addr, power in held.items():
            if vs.get_by_address(addr) is None:  # not routed already
                got += power
                if got >= need:
                    return True
        return False

    def __call__(self) -> bool:
        eng = self._eng
        pool = eng.tx_vote_pool
        cap = eng._drain_cap
        with eng._mtx:
            skip = ()
            if self._seed:
                self._seed = False
                for _k, vote in eng._retry_prio if self._prio else eng._retry:
                    self._hold(vote)
            if self._prio:
                raw, self._cursor = pool.priority_entries_from(self._cursor, cap)
            elif eng._prio_lane is not None:
                raw, self._cursor = pool.bulk_entries_from(self._cursor, cap)
            else:
                # merged drain: priority votes it took ahead of the main
                # log are in flight, not held
                raw, self._cursor = pool.entries_from(self._cursor, cap)
                skip = eng._prio_drained
            for key, vote, _h, _s in raw:
                if key not in skip:
                    self._hold(vote)
            self.probed += len(raw)
            if self._metrics is not None and raw:
                self._metrics.coalesce_quorum_probed.add(len(raw))
            txs = self._touched
            self._touched = set()
            if eng._pipe_steps != self._routed:
                self._routed = eng._pipe_steps
                txs = list(self._held)
            for tx_hash in txs:
                if tx_hash in self._held and self._complete(tx_hash):
                    return True
        return False


class TxFlow:
    def __init__(
        self,
        chain_id: str,
        height: int,
        val_set: ValidatorSet,
        tx_vote_pool: TxVotePool,
        mempool: Mempool,
        commitpool: Mempool,
        tx_executor: TxExecutor,
        tx_store: TxStore,
        config: EngineConfig | None = None,
        verifier=None,
        metrics: TxFlowMetrics | None = None,
    ):
        self.chain_id = chain_id
        self.height = height
        self.val_set = val_set
        self.tx_vote_pool = tx_vote_pool
        self.mempool = mempool
        self.commitpool = commitpool
        self.tx_executor = tx_executor
        self.tx_store = tx_store
        self.config = config or EngineConfig()
        self.metrics = metrics or TxFlowMetrics()
        if verifier is not None:
            self.verifier = verifier
        elif self.config.use_device:
            from ..verifier import ResilientVoteVerifier

            # mesh-sharded verify (EngineConfig.mesh_devices): shard the
            # vote axis across the first N devices of the default
            # backend. Asked for N and given fewer is an error (make_mesh
            # raises): a node configured for a slice must not come up on
            # one device in silence.
            mesh = None
            if int(self.config.mesh_devices or 0) > 1:
                from ..parallel.mesh import make_mesh

                mesh = make_mesh(int(self.config.mesh_devices))
            try:
                # resilient by default: a device fault mid-run degrades to
                # the scalar golden model (retry/backoff/re-probe policy,
                # verifier.ResilientVoteVerifier) instead of erroring the
                # vote path; decisions are bit-identical either way
                self.verifier = ResilientVoteVerifier(
                    DeviceVoteVerifier(
                        val_set,
                        mesh=mesh,
                        host_prep_workers=int(
                            self.config.host_prep_workers or 0
                        ),
                        host_prep_backend=str(
                            self.config.host_prep_backend or "thread"
                        ),
                        staging_ring=int(self.config.staging_ring),
                    )
                )
            except ValueError:  # total power >= 2^30: int32 tally overflow
                self.verifier = ScalarVoteVerifier(val_set)
        else:
            self.verifier = ScalarVoteVerifier(val_set)
        self._addr_to_idx = {v.address: i for i, v in enumerate(val_set)}
        self._addr_power = {v.address: v.voting_power for v in val_set}
        # drains larger than the verifier's largest bucket would compile a
        # fresh kernel shape per batch size (verifier.DeviceVoteVerifier)
        self._drain_cap = min(
            self.config.max_batch,
            getattr(self.verifier, "max_batch", self.config.max_batch),
        )
        # wide coalescer rungs (EngineConfig.wide_buckets): let drains
        # reach the verifier ladder's rungs ABOVE config.max_batch —
        # they are canonical compiled shapes already (DEFAULT_BUCKETS
        # tops out past the default cap precisely for this), so wider
        # steps amortize per-call overhead with zero new compiles. The
        # classic cap survives as the coalescer's wide_from gate line.
        self._classic_drain_cap = self._drain_cap
        if self.config.wide_buckets:
            buckets = self._verifier_buckets()
            if buckets:
                self._drain_cap = max(self._drain_cap, max(buckets))
        self.vote_sets: dict[str, TxVoteSet] = {}  # in-flight only
        # in-flight vote sets: the step/prep thread, the route stage, the
        # committer, the sync apply path, and RPC snapshot readers all
        # cross here under the engine RLock
        self._sh_votesets = shared_field("engine.TxFlow.vote_sets")  # txlint: shared(self._mtx)
        self._committed = make_lru(1 << 16)  # recently committed tx hashes
        # ingest-log cursor: each pool entry is visited by step() exactly
        # once via the stable-cursor walk (in-batch repeats re-queue on
        # _retry). The previous skip-set drain re-walked EVERY live pool
        # entry per step — O(pool) per step, ~1.6 ms at bench depth (r5
        # instrumented profile).
        self._drain_cursor = 0
        self._retry: list[tuple[bytes, TxVote]] = []
        # priority-lane drain (admission subsystem): priority-tx votes are
        # drained through the pool's priority log AHEAD of the main-log
        # walk, so under a deep bulk backlog they verify in the NEXT step
        # instead of queueing behind thousands of bulk votes. Keys drained
        # this way are remembered until the main-log cursor passes them
        # (each appears in the main log exactly once), so no vote is
        # prepped twice.
        self._prio_drain_cursor = 0
        self._prio_drained: set[bytes] = set()
        # lane-split drain (ISSUE 12): with a priority lane built in
        # start(), the priority log and the bulk main-log walk
        # (bulk_entries_from) become an exact partition and each lane
        # keeps its own retry list — a priority in-batch repeat must
        # requeue into the priority lane, never behind the bulk backlog
        self._retry_prio: list[tuple[bytes, TxVote]] = []
        self._prio_lane: _BatchCoalescer | None = None
        # each lane's quorum probe: ends its coalescer's hold once the
        # held votes complete a tx's quorum (reset by each drain)
        self._probe_bulk = _QuorumProbe(self, prio=False, metrics=self.metrics)
        self._probe_prio = _QuorumProbe(self, prio=True)
        self._linger_ctrl = None
        self._lane_prio_batches = 0
        self._lane_prio_votes = 0
        # speculative quorum commit accounting (_route_result): commits
        # routed early on the device quorum hint, and the route-tail
        # seconds the early exit removed (sum over spec commits of
        # route-end minus decision time)
        self._spec_commits = 0
        self._spec_saved_s = 0.0
        self._mtx = make_rlock("engine.TxFlow._mtx")
        self._running = False
        self._thread: threading.Thread | None = None
        # commit pipeline (SURVEY §7 hard-part 5): quorum decisions flow to
        # a dedicated committer thread so TxStore/ABCI/purge work overlaps
        # the next device verify instead of serializing behind it
        self._commit_q: _queue.SimpleQueue = _queue.SimpleQueue()
        self._committer: threading.Thread | None = None
        # decision/apply lag accounting: a certificate exists (TxStore,
        # _committed mark) the moment a quorum is DECIDED, while the ABCI
        # apply runs on the committer thread a beat later — these counters
        # let callers wait for the apply side to drain (commits_drained)
        self._decided_count = 0
        self._applied_count = 0
        # quorum-before-tx: a vote quorum can arrive (gossip) before the
        # tx bytes reach the local mempool — the certificate is saved but
        # the ABCI apply must WAIT for the bytes (r5 soak: after
        # partition/heal churn, a node held the certificate, skipped the
        # apply, and claim_vtx then blocked the block path's delivery too
        # — permanent per-node state divergence). tx_hash -> tx_key of
        # decided-but-unapplied txs, guarded by _mtx; drained by the
        # committer retry and by claim_vtx (block delivers it instead).
        self._unapplied: dict[str, bytes] = {}
        self.app_hash = b""
        # verify-pipeline accounting (engine thread only; racy reads by
        # pipeline_stats are fine), fed by _stage_done alone: busy is the
        # sum of the device_busy spans (dispatched -> result usable on
        # the host, never overlapping), active the engine's own
        # host_prep/dispatch/collect_wait/route segments. overlap_ratio =
        # busy/active. busy is a host-side reading: it holds the device's
        # time AND what the readback thread waited for the interpreter
        # lock before it could stamp the result (20 ms a step in the
        # flood, PERF.md), so active - busy is a lower bound of the
        # device's idle time, not the idle time.
        self._pipe_steps = 0
        self._pipe_prep_s = 0.0
        self._pipe_wait_s = 0.0
        self._pipe_route_s = 0.0
        self._pipe_busy_s = 0.0
        self._pipe_active_s = 0.0
        self._pipe_lock_wait_s = 0.0
        # step ids (_prep_batch takes one per batch it forms), the last
        # step's ready time (device_busy never starts before it), and the
        # start of a pool_wait still open across idle polls (0 = none)
        self._step_seq = 0
        self._last_ready = 0.0
        self._idle_t0 = 0.0
        # in-flight state carried from step to step (engine thread only):
        # drained votes dropped in prep because their tx had committed
        # (late) or their validator's vote was already in the open set
        # (dup; also counted where routing finds the same after the
        # verify), votes the device verified for a tx that routing then
        # found committed, slots of a step whose vote set already held
        # stake, and the most vote sets open at once
        self._late_votes = 0
        self._dup_votes = 0
        self._late_verified = 0
        self._carried_slots = 0
        self._open_vote_sets = 0
        # what the quorums took, counted where routing decides them:
        # commits, their certificate rows, and the steps that added a
        # vote to each one's set up to the deciding step, summed
        self._quorums = 0
        self._quorum_rows = 0
        self._quorum_steps = 0
        # host-prep split (trace/report.py prep_serial vs prep_pool_wait):
        # sign_s is the assembly stage's wall time, pool_wait_s the slice
        # of it this thread spent parked behind pool shards it didn't run
        self._pipe_prep_sign_s = 0.0
        self._pipe_prep_pool_wait_s = 0.0
        # sharded host-prep pool (engine.hostprep), wired in start():
        # device verifiers share ONE pool across co-located engines via
        # ensure_host_pool; scalar verifiers get an engine-owned pool
        self._host_pool = None
        self._own_host_pool = False
        # durable-path degradation (ENOSPC/EIO/failpoint on TxStore
        # writes): the commit stays applied in memory and the node keeps
        # serving, but it flags itself degraded — surfaced via /health
        # ("storage" section) and the admission front door, which sheds
        # while degraded. Crashing would lose the in-memory committed
        # state; silence would hide that durability is gone.
        self.storage_degraded = False
        self.storage_errors = 0
        self.storage_last_error = ""
        # per-tx tracing (trace/tracer.py): wired by the node before
        # start(); NULL_TRACER keeps every hook a no-op attribute check
        self.tracer = NULL_TRACER
        # accountable gossip (health/byzantine.py, wired by the node):
        # called outside _mtx with the ingest-origin sender id of every
        # valid=False verdict in a routed batch. None = zero cost.
        self.on_invalid_votes = None
        # tx_hash -> open commit_apply span id (begun at decision time
        # under _mtx, finished by whichever path applies: committer
        # batch, inline effects, late delivery, or a block via claim_vtx)
        self._commit_spans: dict[str, int] = {}
        # last step's (decided, requeued, dropped) — tests reconcile these
        # against the step() return (decided + dropped; requeued votes are
        # NOT counted: they re-enter via _retry and would double-count)
        self.last_step_stats: dict | None = None
        self._shape_registry = None
        # shape-stability layer (built in start(); None = feature off):
        # the coalescer sizes drains to canonical buckets, the warm gate
        # (a ShapeWarmRegistry) + cold fallback route still-cold shapes
        # through the scalar path while the BackgroundWarmer compiles
        # them, and the depth controller adapts the pipelined loop's
        # in-flight budget from the live overlap ratio
        self._coalescer: _BatchCoalescer | None = None
        self._warm_gate = None
        self._cold_fallback = None
        self._warmer = None
        self._depth_ctrl = None
        self._cold_fallback_votes = 0
        self._prewarm_failures = 0
        # last epoch rotation applied by update_state (None = never):
        # drills assert restaged (no rebuild => no recompile window) and
        # reconcile dropped/committed counts across nodes
        self.last_rotation: dict | None = None

    # ---- lifecycle (reference OnStart :80-87) ----

    def start(self) -> None:
        with self._mtx:
            if self._running:
                return
            self._running = True
        if self.config.prewarm_shapes and self._shape_registry is None:
            # compile every shape the pipeline can hit BEFORE serving: a
            # cold compile inside the pipelined loop stalls the in-flight
            # ticket and everything queued behind it (engine.shapes)
            from .shapes import ShapeWarmRegistry

            self._shape_registry = ShapeWarmRegistry(self.verifier)
            try:
                self._shape_registry.prewarm(full=True)
            except Exception:
                # serving goes on (the first batch of a cold shape
                # compiles in the loop, or degrades via
                # ResilientVoteVerifier) but the failure is counted
                # where pipeline_stats() readers can see it
                self._prewarm_failures += 1
        if self.config.background_warmup and self._warm_gate is None:
            self._setup_background_warmup()
        if self.config.coalesce and self._coalescer is None:
            buckets = self._verifier_buckets()
            if buckets:
                self._coalescer = _BatchCoalescer(
                    buckets,
                    cap=self._drain_cap,
                    min_batch=self.config.min_batch,
                    linger=self.config.coalesce_linger,
                    metrics=self.metrics,
                    tracer=self.tracer,
                    # full-bucket drains land exactly on the sharded
                    # verifier's rounded shapes (verifier.bucket_size)
                    multiple=self._verifier_shards(),
                    span_name=SPAN_LINGER_BULK,
                    # rungs past the classic cap are latency-gated
                    # (wide_buckets); None when the cap wasn't widened
                    wide_from=(
                        self._classic_drain_cap
                        if self._drain_cap > self._classic_drain_cap
                        else None
                    ),
                    probe=self._probe_bulk,
                )
        if self.config.lane_split and self._prio_lane is None:
            # priority verify lane (ISSUE 12): small shard-divisible
            # bucket targets capped at priority_bucket_cap, a short
            # deadline (priority_linger), drained from the pool's
            # priority log AHEAD of every bulk dispatch. Built even
            # without a bucket ladder (scalar verifier — the _BatchCo-
            # alescer degrades to cap-sized dispatches): the lane is
            # about preemption, not shapes, and with no admission
            # wiring the priority log is empty and decide(0) is free.
            self._prio_lane = _BatchCoalescer(
                self._verifier_buckets() or (),
                cap=min(
                    max(1, int(self.config.priority_bucket_cap)),
                    self._drain_cap,
                ),
                min_batch=1,
                linger=self.config.priority_linger,
                tracer=self.tracer,
                multiple=self._verifier_shards(),
                span_name=SPAN_LINGER_PRIO,
                probe=self._probe_prio,
            )
        if self.config.adaptive_linger and self._linger_ctrl is None:
            from .adaptive import AdaptiveLingerController

            self._linger_ctrl = AdaptiveLingerController(
                slo_budget_ms=self.config.slo_budget_ms,
                prio_linger=self.config.priority_linger,
                bulk_linger=self.config.coalesce_linger,
            )
        if int(self.config.host_prep_workers or 0) > 1 and self._host_pool is None:
            from .shapes import _unwrap_device

            dev = _unwrap_device(self.verifier)
            if dev is not None:
                # shared verifier => shared pool: N co-located engines
                # must not spawn N * workers threads (ensure_host_pool
                # is first-sizer-wins)
                self._host_pool = dev.ensure_host_pool(
                    int(self.config.host_prep_workers),
                    backend=str(self.config.host_prep_backend or "thread"),
                )
            else:
                from .hostprep import make_host_pool

                # make_host_pool falls back to the thread backend when
                # process spawn fails (HostPoolSpawnError swallowed)
                self._host_pool = make_host_pool(
                    int(self.config.host_prep_workers),
                    backend=str(self.config.host_prep_backend or "thread"),
                    name="hostprep-engine",
                )
                self._own_host_pool = True
        if self.config.adaptive_depth and self._depth_ctrl is None:
            from .adaptive import AdaptiveDepthController

            self._depth_ctrl = AdaptiveDepthController(
                depth=max(2, int(self.config.pipeline_depth)),
                min_depth=self.config.pipeline_depth_min,
                max_depth=self.config.pipeline_depth_max,
            )
            self.metrics.pipeline_depth_target.set(self._depth_ctrl.depth)
        self.tx_vote_pool.enable_txs_available()
        if self.config.pipeline_commits:
            self._committer = threading.Thread(
                target=self._committer_run, name="txflow-commit", daemon=True
            )
            self._committer.start()
        self._thread = threading.Thread(target=self._run, name="txflow", daemon=True)
        self._thread.start()

    def _verifier_buckets(self):
        """Canonical bucket ladder for coalescing: the verifier's own
        buckets attribute when present (duck-typed — tests attach one to
        a scalar verifier), else the wrapped device verifier's."""
        buckets = getattr(self.verifier, "buckets", None)
        if buckets:
            return buckets
        from .shapes import _unwrap_device

        dev = _unwrap_device(self.verifier)
        return dev.buckets if dev is not None else None

    def _verifier_shards(self) -> int:
        """Mesh shard count of the (possibly wrapped) device verifier;
        1 for scalar/single-device."""
        from .shapes import _unwrap_device

        dev = _unwrap_device(self.verifier)
        shards = getattr(dev, "_n_shards", 1) if dev is not None else 1
        return max(1, int(shards))

    def _setup_background_warmup(self) -> None:
        """Wire the cold-shape gate: a shared ShapeWarmRegistry as the
        warmth oracle, a scalar fallback for batches whose shape is
        still cold, and the BackgroundWarmer
        thread that compiles the enumeration concurrently with serving.
        No-op for scalar verifiers — nothing compiles there."""
        from .shapes import BackgroundWarmer, ShapeWarmRegistry

        registry = self._shape_registry
        if registry is None:
            registry = ShapeWarmRegistry(self.verifier)
            self._shape_registry = registry
        if registry.device is None:
            return
        self._warm_gate = registry
        self._cold_fallback = ScalarVoteVerifier(self.val_set)
        self._warmer = BackgroundWarmer(registry, full=True)
        self._warmer.start()

    def _target_depth(self) -> int:
        ctrl = self._depth_ctrl
        if ctrl is not None:
            return ctrl.depth
        return max(2, int(self.config.pipeline_depth))

    def stop(self) -> None:
        with self._mtx:
            self._running = False
        if self._warmer is not None:
            self._warmer.stop()
            self._warmer = None
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        if self._committer is not None:
            self._commit_q.put(None)  # drain sentinel
            self._committer.join(timeout=10)
            self._committer = None
        if self._own_host_pool and self._host_pool is not None:
            # engine-owned pool only: a verifier-attached pool is shared
            # with other engines and outlives this one
            self._host_pool.close()
            self._host_pool = None
            self._own_host_pool = False
        # flush queued commit events so indexer/subscribers see every
        # committed tx before shutdown returns
        self.tx_executor.drain_events()

    def _run(self) -> None:
        if self.config.pipeline_depth >= 2:
            self._run_pipelined()
        else:
            self._run_serial()

    def _prio_pending(self) -> int:
        """Priority-lane backlog estimate: priority ingests not yet
        walked plus the lane's own requeues (over-counts only removed-
        not-yet-walked entries — the same safe coalescing estimate the
        main log's seq gives)."""
        return (
            self.tx_vote_pool.prio_seq()
            - self._prio_drain_cursor
            + len(self._retry_prio)
        )

    def _bulk_pending(self) -> int:
        """Bulk-lane backlog estimate. In lane-split mode the main-log
        seq counts priority ingests too, so subtract the priority lane's
        own backlog — both sides over-count dead entries, so the
        difference stays a safe coalescing estimate that self-corrects
        as the cursors advance."""
        pending = self.tx_vote_pool.seq() - self._drain_cursor + len(self._retry)
        if self._prio_lane is not None:
            pending -= max(
                self.tx_vote_pool.prio_seq() - self._prio_drain_cursor, 0
            )
        return max(pending, 0)

    def _bulk_quantum(self) -> int:
        """Bulk drain cap per step when the priority lane is on but no
        bucket ladder exists (scalar verify): the verify of one bulk
        batch is the priority lane's preemption gap — scalar verify has
        no batch amortization (PR 6 soak finding), so a min_batch-sized
        drain (256 default) is over a second of head-of-line blocking
        for any priority vote that lands mid-verify on a 1-core box.
        While priority traffic exists, drain bulk in small shard-rounded
        quanta; a run that never saw a priority ingest keeps the full
        min_batch drain — there is nothing to preempt, and more steps is
        pure per-step overhead for the throughput benches."""
        if self.tx_vote_pool.prio_seq() == 0:
            return max(int(self.config.min_batch), 64)
        m = max(1, self._verifier_shards())
        return -(-64 // m) * m

    def _steer_lingers(self) -> None:
        """Adaptive per-lane linger (AdaptiveLingerController): feed the
        live trace digest, push changed lingers into the lane
        coalescers. Called once per collected batch; the controller
        rate-limits its own digest pulls."""
        ctrl = self._linger_ctrl
        if ctrl is None or not self.tracer.active:
            return
        if ctrl.maybe_observe(self.tracer.digest, monotonic()):
            if self._prio_lane is not None:
                self._prio_lane.linger = ctrl.prio_linger
            if self._coalescer is not None:
                self._coalescer.linger = ctrl.bulk_linger
                # latency verdict also gates the wide bucket rungs: a
                # budget breach shuts the >classic-cap drains off until
                # batch p50 recovers (adaptive.wide_ok hysteresis)
                self._coalescer.set_wide(getattr(ctrl, "wide_ok", True))
            self.metrics.adaptive_linger_changes.add(1)

    def _run_serial(self) -> None:
        # Idle on the pool's per-vote sequence counter, NOT the once-per-
        # height txs_available event: when every pool vote is already in an
        # in-flight vote set (awaiting quorum) step() returns 0 while the
        # event stays set, which would spin this loop at 100% CPU. The seq
        # is sampled before step() so a vote arriving mid-step wakes us
        # immediately instead of being missed for a poll interval.
        co = self._coalescer
        pl = self._prio_lane
        lane_bulk = "bulk" if pl is not None else None
        while True:
            with self._mtx:
                if not self._running:
                    self._end_pool_wait()
                    return
            seq_before = self.tx_vote_pool.seq()
            processed = 0
            if pl is not None:
                # priority lane first, always: a dispatchable priority
                # batch (full small bucket or expired deadline) preempts
                # any bulk work this iteration would start
                plimit = pl.decide(self._prio_pending(), self._step_seq + 1)
                if plimit > 0:
                    processed += self.step(limit=plimit, lane="prio")
            if co is not None:
                # shape-stable sizing replaces min_batch/_form_batch: the
                # coalescer hands out full canonical buckets (or a linger
                # flush), and 0 means keep accumulating
                limit = co.decide(self._bulk_pending(), self._step_seq + 1)
                if limit > 0:
                    processed += self.step(limit=limit, lane=lane_bulk)
            else:
                if pl is not None:
                    # bound the forming hold by the priority lane's own
                    # deadline so an armed priority linger fires on time,
                    # and drain in quanta so a bulk verify never blocks
                    # priority preemption for a whole backlog
                    self._form_batch(
                        budget=pl.wait_budget(
                            self.config.batch_wait, self.config.idle_flush
                        )
                    )
                    processed += self.step(
                        limit=self._bulk_quantum(), lane=lane_bulk
                    )
                else:
                    self._form_batch()
                    processed += self.step()
            self._steer_lingers()
            if self._committer is None and self._unapplied:
                # no committer thread to run the deferred-apply retry
                self._apply_unapplied()
            if processed == 0 and (co is not None or not self._retry):
                budget = self.config.poll_interval
                if co is not None:
                    budget = co.wait_budget(budget, self.config.idle_flush)
                if pl is not None:
                    budget = pl.wait_budget(budget, self.config.idle_flush)
                got = self._pool_wait(seq_before, budget)
                if got == seq_before:
                    if co is not None:
                        co.note_idle()
                    if pl is not None:
                        pl.note_idle()

    def _pool_wait(self, seq: int, timeout: float) -> int:
        """The engine thread blocked on the vote pool: one pool_wait span
        from the first block to the wake, however many idle polls lie
        between (_end_pool_wait closes it where work starts anyway).
        While a coalescer holds a partial batch the wait belongs to that
        lane's linger span instead, and is annotated as such."""
        co, pl = self._coalescer, self._prio_lane
        held = pl if pl is not None and pl.holding else co
        if held is not None and held.holding:
            self._end_pool_wait()
            name = held.span_name
        else:
            name = SPAN_POOL_WAIT
            if not self._idle_t0:
                self._idle_t0 = monotonic()
        with _annotation_cls()(name):
            got = self.tx_vote_pool.wait_for_new(seq, timeout=timeout)
        if got != seq:
            self._end_pool_wait()
        return got

    def _end_pool_wait(self, t1: float | None = None) -> None:
        if self._idle_t0:
            t0, self._idle_t0 = self._idle_t0, 0.0
            self._stage_done(SPAN_POOL_WAIT, t0, monotonic() if t1 is None else t1, 0)

    def _stage_done(self, name: str, t0: float, t1: float, step: int,
                    sid: int = 0) -> None:
        """Every sink of one stage's two clock reads: the
        pipeline_stats() counter, the Prometheus counter and the stage
        span (``sid``: the span was begun at dispatch and is finished
        here). prep_s keeps its meaning: host_prep + dispatch."""
        dur = t1 - t0
        m = self.metrics
        if name == SPAN_PREP or name == SPAN_DISPATCH:
            self._pipe_prep_s += dur
            self._pipe_active_s += dur
            m.pipeline_prep_seconds.add(dur)
        elif name == SPAN_COLLECT:
            self._pipe_wait_s += dur
            self._pipe_active_s += dur
            m.pipeline_wait_seconds.add(dur)
        elif name == SPAN_ROUTE:
            self._pipe_route_s += dur
            self._pipe_active_s += dur
            m.pipeline_route_seconds.add(dur)
        elif name == SPAN_LOCK_WAIT:
            self._pipe_lock_wait_s += dur
        elif name == SPAN_DEVICE:
            self._pipe_busy_s += dur
            active, busy = self._pipe_active_s, self._pipe_busy_s
            if active > 0:
                m.pipeline_overlap_ratio.set(min(busy / active, 1.0))
                m.pipeline_device_idle.set(max(active - busy, 0.0))
        if sid:
            self.tracer.finish(sid, end=t1, start=t0)
        else:
            self.tracer.span("", name, t0, t1, step)

    def _run_pipelined(self) -> None:
        """Three-stage verify pipeline: host prep (stage 1) and commit
        routing (stage 3) overlap the device verify in flight (stage 2).

        Up to pipeline_depth tickets ride the verifier's submit/collect
        split; the oldest is collected and ROUTED IN SUBMISSION ORDER, so
        the pool's ingest-log order — the canonical order the serial path
        routes in — is preserved and commit certificates are bit-identical
        to the serial loop (routing re-validates each vote against
        vote_sets/_committed at collect time; see _StepPrep on staleness).
        On stop, every in-flight ticket is still collected and routed —
        no orphaned tickets, no leaked cache claims, no lost votes."""
        from collections import deque

        inflight: deque[tuple[_StepPrep, object]] = deque()
        m = self.metrics
        co = self._coalescer
        pl = self._prio_lane
        lane_bulk = "bulk" if pl is not None else None
        ctrl = self._depth_ctrl
        try:
            while True:
                with self._mtx:
                    if not self._running:
                        return
                depth = self._target_depth()
                seq_before = self.tx_vote_pool.seq()
                # fill stage: prep+dispatch until the pipeline is full or
                # the pool has nothing batchable. Batch coalescing only
                # WAITS when nothing is in flight — with a ticket pending,
                # the wait is free (the device is busy anyway), so a
                # follow-up batch is dispatched only once min_batch votes
                # have coalesced; dribbles stay in the pool for the next
                # fill instead of burning a full step preamble + routing
                # pass per couple of votes (the serial loop coalesces
                # EVERY step — dispatching sub-min_batch batches here made
                # the CPU bench 10x slower, not faster). With a coalescer,
                # the bucket ladder replaces min_batch/_form_batch: only
                # full canonical buckets (or linger flushes) dispatch.
                while len(inflight) < depth:
                    if pl is not None:
                        # priority lane preempts every bulk dispatch this
                        # fill would make: a dispatchable priority batch
                        # (full small bucket or expired deadline) rides
                        # the NEXT ticket, never behind a bulk backlog
                        plimit = pl.decide(self._prio_pending(), self._step_seq + 1)
                        if plimit > 0:
                            prep = self._prep_batch(limit=plimit, lane="prio")
                            if prep is not None:
                                if prep.votes:
                                    inflight.append(
                                        (prep, self._submit_prep(prep))
                                    )
                                    m.pipeline_depth.set(len(inflight))
                                continue
                            # estimate raced a purge (nothing drained):
                            # fall through to the bulk lane this pass
                    if co is not None:
                        limit = co.decide(self._bulk_pending(), self._step_seq + 1)
                        if limit <= 0:
                            break
                        prep = self._prep_batch(limit=limit, lane=lane_bulk)
                    else:
                        if not inflight:
                            if pl is not None:
                                # bound the forming hold by the priority
                                # lane's own deadline (see _run_serial)
                                self._form_batch(
                                    budget=pl.wait_budget(
                                        self.config.batch_wait,
                                        self.config.idle_flush,
                                    )
                                )
                            else:
                                self._form_batch()
                        else:
                            if self._bulk_pending() < max(
                                1, self.config.min_batch
                            ):
                                break
                        prep = self._prep_batch(
                            limit=(
                                self._bulk_quantum()
                                if pl is not None
                                else None
                            ),
                            lane=lane_bulk,
                        )
                    if prep is None:
                        break
                    if not prep.votes:
                        continue  # drop-only drain: cursor advanced, go on
                    inflight.append((prep, self._submit_prep(prep)))
                    m.pipeline_depth.set(len(inflight))
                if not inflight:
                    if self._committer is None and self._unapplied:
                        self._apply_unapplied()
                    if co is None and pl is None:
                        if not self._retry:
                            self._pool_wait(seq_before, self.config.poll_interval)
                        continue
                    budget = self.config.poll_interval
                    if co is not None:
                        budget = co.wait_budget(budget, self.config.idle_flush)
                    if pl is not None:
                        budget = pl.wait_budget(budget, self.config.idle_flush)
                    got = self._pool_wait(seq_before, budget)
                    if got == seq_before:
                        if co is not None:
                            co.note_idle()
                        if pl is not None:
                            pl.note_idle()
                    continue
                prep, ticket = inflight.popleft()
                m.pipeline_depth.set(len(inflight))
                result = self._collect(prep, ticket)
                self._route_result(prep, result)
                self._pipe_steps += 1
                self._steer_lingers()
                if ctrl is not None:
                    new_depth = ctrl.observe(
                        self._pipe_busy_s, self._pipe_active_s, self._pipe_steps
                    )
                    if new_depth != depth:
                        m.pipeline_depth_target.set(new_depth)
                        m.pipeline_depth_changes.add(1)
                if self._committer is None and self._unapplied:
                    self._apply_unapplied()
        finally:
            # drain stage: stop() (or a crash) must not orphan tickets —
            # collect and route the tail in submission order so decided
            # votes reach their vote sets
            while inflight:
                prep, ticket = inflight.popleft()
                try:
                    self._route_result(prep, self._collect(prep, ticket))
                except Exception:
                    # a failed collect must not leak its open device
                    # span (no-op when _collect already finished it)
                    self.tracer.abandon(prep.device_sid)
                    import traceback

                    traceback.print_exc()
            self._end_pool_wait()
            m.pipeline_depth.set(0)

    def _form_batch(self, budget: float | None = None) -> None:
        """Hold up to batch_wait for min_batch pending votes to coalesce.

        Bounded added latency (batch_wait) in exchange for device-sized
        batches: one kernel call per thousands of votes instead of one per
        gossip arrival (SURVEY §7 hard-part 5). ``budget`` caps the hold
        below batch_wait — the lane-split loops pass the priority lane's
        wait_budget so an armed priority deadline fires on time instead
        of waiting out a full bulk forming window."""
        min_batch = self.config.min_batch
        if min_batch <= 1:
            return
        wait = self.config.batch_wait
        if budget is not None:
            wait = min(wait, max(budget, 0.0))
        deadline = monotonic() + wait
        idle_flush = self.config.idle_flush
        while True:
            # unvisited ingest ≈ seq (log end) minus the drain cursor:
            # both advance monotonically, so this over-counts only by the
            # removed-not-yet-visited entries — a safe coalescing estimate
            seq_now = self.tx_vote_pool.seq()
            pending = seq_now - self._drain_cursor + len(self._retry)
            remaining = deadline - monotonic()
            if pending >= min_batch or remaining <= 0:
                return
            # adaptive wait: at light load arrivals come in per-tx bursts
            # and then stall — once votes are pending and nothing new
            # arrives within idle_flush, process NOW (p50 stops paying
            # batch_wait); under sustained load new votes keep landing
            # inside the window, so coalescing to min_batch is unchanged
            timeout = remaining
            if idle_flush > 0 and pending > 0:
                timeout = min(remaining, idle_flush)
            got = self._pool_wait(seq_now, timeout)
            if got == seq_now and pending > 0:
                return

    # ---- batched aggregation step ----

    def step(self, limit: int | None = None, lane: str | None = None) -> int:
        """One serial verify+tally+commit round (prep -> submit -> collect
        -> route, no overlap); returns votes PROCESSED this step: votes
        routed to a decision (added / rejected / late) plus votes dropped
        at drain time. Votes the verifier deferred (in-batch repeats) are
        NOT counted — they re-enter via _retry and are counted by the step
        that finally decides them (the
        old ``len(votes) + len(drop_now)`` counted those twice). The
        decided/requeued/dropped split is published in last_step_stats;
        decided + requeued always reconciles to the verified batch size.
        ``limit`` caps the batch (retries + fresh drain) below the drain
        cap — the coalescer passes a canonical bucket size here.
        ``lane`` selects the drain source ("prio" / "bulk" / None =
        merged legacy drain — see _prep_batch).
        """
        prep = self._prep_batch(limit=limit, lane=lane)
        if prep is None:
            return 0
        if not prep.votes:
            self.last_step_stats = {
                "decided": 0, "requeued": 0, "dropped": prep.dropped,
                "batch": 0,
            }
            return prep.dropped
        # device verify OUTSIDE the engine lock: holding _mtx across the
        # ~100+ ms kernel+readback would serialize every consensus-path
        # claim/reservation check behind full verify steps (r3 review).
        # Routing re-validates against vote_sets/_committed, so concurrent
        # claims during the call stay correct.
        ticket = self._submit_prep(prep)
        result = self._collect(prep, ticket)
        decided, _ = self._route_result(prep, result)
        self._pipe_steps += 1
        return decided + prep.dropped

    def _sign_bytes_proc(self, votes, pool) -> "list[bytes] | None":
        """Sign bytes for a drain batch via the PROCESS host pool.

        Mirrors types.tx_vote.sign_bytes_many exactly — cache scan
        inline (hits are free and never cross a process boundary),
        misses encoded by worker processes over shared memory
        (hostprep.ProcHostPrepPool.sign_bytes_shm), caches primed with
        the returned bytes. Returns None when the shm path declines
        (hostile field bounds, broken pool) so the caller can fall back
        to the thread/serial encode — same bytes on every path (parity
        pinned by tests/test_procprep.py)."""
        out: list[bytes | None] = [None] * len(votes)
        miss: list[int] = []
        for i, v in enumerate(votes):
            c = v._sb_cache
            if c is not None and c[0] == self.chain_id:
                out[i] = c[1]
            else:
                miss.append(i)
        if miss:
            res = pool.sign_bytes_shm(
                [votes[i].height for i in miss],
                [votes[i].tx_hash for i in miss],
                [votes[i].timestamp_ns for i in miss],
                self.chain_id,
            )
            if res is None:
                return None
            rows, wait_s = res
            self._pipe_prep_pool_wait_s += wait_s
            for j, i in enumerate(miss):
                out[i] = rows[j]
                if votes[i].signature is not None:  # immutable once signed
                    object.__setattr__(
                        votes[i], "_sb_cache", (self.chain_id, rows[j])
                    )
        return out  # type: ignore[return-value]

    def _prep_batch(
        self, limit: int | None = None, lane: str | None = None
    ) -> "_StepPrep | None":
        """Stage 1: drain the pool, dedup against committed/held votes,
        assign tx slots, gather prior stake, and build sign bytes — all
        host work, under _mtx. Returns None when nothing was drained; a
        prep with empty ``votes`` when everything drained was dropped.
        ``limit`` is the total batch target (retries included) — the
        coalescer passes a canonical bucket size so the dispatched batch
        lands exactly on a prewarmed shape.

        ``lane`` selects the drain source (ISSUE 12 lane split):
        "prio" walks ONLY the pool's priority log (+ the lane's own
        retries), "bulk" walks the main log skipping ingest-frozen
        priority entries (bulk_entries_from) — together an exact
        partition, so neither lane needs the merged path's
        _prio_drained dedup set. None keeps the legacy merged drain
        (priority log ahead of the main-log walk, dedup via
        _prio_drained) for direct step() callers and lane_split=False."""
        target = self._drain_cap if limit is None else min(limit, self._drain_cap)
        # the step id this drain will take if it forms a batch with votes
        # (the engine thread is the only taker); a drain that only drops
        # records under step 0, one that finds nothing records nothing
        # (an idle loop without a coalescer comes here every poll)
        with _Stage(self, SPAN_PREP, self._step_seq + 1, target) as st:
            prep, lk_acq = self._drain_and_assemble(st.t0, target, lane)
            if prep is None:
                st.skip = True
            else:
                if lane == "prio":
                    self._probe_prio.reset(self._prio_drain_cursor)
                else:
                    self._probe_bulk.reset(self._drain_cursor)
                st.step = prep.step
                self._end_pool_wait(st.t0)
                self._stage_done(SPAN_LOCK_WAIT, st.t0, lk_acq, st.step)
                if prep.drop_t is not None:
                    self._stage_done(SPAN_LATE_DROP, *prep.drop_t, st.step)
                if prep.carry_t is not None:
                    self._stage_done(SPAN_CARRY, *prep.carry_t, st.step)
                self._votes_waited(prep, lane, st.t0, st.step)
        return prep

    def _votes_waited(self, prep: "_StepPrep", lane: str | None,
                      t_prep: float, step: int) -> None:
        """What the drained votes waited for this step. pickup_wait (the
        step's): the pool's first vote since the last drain -> the batch
        taken up (its lane's hold began, or this prep where a full bucket
        was handed out at once). vote_wait (each sampled tx's): its first
        vote in the pool -> this prep."""
        co = self._prio_lane if lane == "prio" else self._coalescer
        t_in = self.tx_vote_pool.take_first_new()
        t_up = (co.flush_t0 if co is not None else 0.0) or t_prep
        if 0.0 < t_in < t_up:
            self._stage_done(SPAN_PICKUP, t_in, t_up, step)
        if 0.0 < t_in < t_prep:
            prep.pickup_t0 = t_in
        tr = self.tracer
        for tx_hash in prep.trace_txs:
            t_vote = tr.take_first_vote(tx_hash)
            if t_vote is not None:
                tr.span(tx_hash, SPAN_VOTE_WAIT, t_vote, t_prep, step)

    def _drain_and_assemble(
        self, t0: float, target: int, lane: str | None
    ) -> "tuple[_StepPrep | None, float]":
        """_prep_batch's work; returns the prep and the time _mtx was
        acquired (the gap from t0 is mutex queueing, not host prep —
        report.py subtracts it from the host component)."""
        with self._mtx:
            lk_acq = monotonic()
            if lane == "prio":
                praw, self._prio_drain_cursor = (
                    self.tx_vote_pool.priority_entries_from(
                        self._prio_drain_cursor,
                        limit=max(target - len(self._retry_prio), 0),
                    )
                )
                batch = self._retry_prio + [(k, v) for k, v, _h, _s in praw]
                self._retry_prio = []
            elif lane == "bulk":
                raw, self._drain_cursor = self.tx_vote_pool.bulk_entries_from(
                    self._drain_cursor,
                    limit=max(target - len(self._retry), 0),
                )
                batch = self._retry + [(k, v) for k, v, _h, _s in raw]
                self._retry = []
            else:
                # priority-lane votes first: under overload the main log
                # can be thousands of bulk votes deep, and a priority tx's
                # quorum must not wait out that backlog (admission lanes,
                # ISSUE 6)
                praw, self._prio_drain_cursor = (
                    self.tx_vote_pool.priority_entries_from(
                        self._prio_drain_cursor,
                        limit=max(target - len(self._retry), 0),
                    )
                )
                drained = self._prio_drained
                drained.update(k for k, _v, _h, _s in praw)
                raw, self._drain_cursor = self.tx_vote_pool.entries_from(
                    self._drain_cursor,
                    limit=max(target - len(self._retry) - len(praw), 0),
                )
                fresh: list[tuple[bytes, TxVote]] = []
                for k, v, _h, _s in raw:
                    if k in drained:
                        drained.discard(k)  # main log reached it: done
                        continue
                    fresh.append((k, v))
                if len(drained) > 8192:
                    # keys whose main-log entry was compacted away before
                    # the cursor reached them (committed early) would
                    # accumulate; keep only keys the pool still holds
                    has = self.tx_vote_pool.has
                    self._prio_drained = {k for k in drained if has(k)}
                batch = (
                    self._retry + [(k, v) for k, v, _h, _s in praw] + fresh
                )
                self._retry = []
            if not batch:
                return None, lk_acq
            prep = _StepPrep(t0, lane=lane)
            keys, votes, slots = prep.keys, prep.votes, prep.slots
            slot_of: dict[str, int] = {}
            drop_now: list[bytes] = []
            n_late = 0
            self._sh_votesets.note_read()
            for bi, (key, vote) in enumerate(batch):
                if self._committed.__contains__(_hash_key(vote.tx_hash)) or (
                    vote.tx_hash not in self.vote_sets
                    and self.tx_store.has_tx(vote.tx_hash)
                ):
                    drop_now.append(key)  # late vote for a committed tx
                    n_late += 1
                    continue
                vs = self.vote_sets.get(vote.tx_hash)
                if vs is not None and vs.get_by_address(vote.validator_address) is not None:
                    # the set already holds a vote from this validator:
                    # identical signature = silent dup, different = an
                    # honest re-sign (timestamped sign bytes — NOT
                    # equivocation, types/evidence.py docstring); both are
                    # dropped first-signature-wins like the reference
                    drop_now.append(key)
                    continue
                if (
                    vote.tx_hash not in slot_of
                    and len(slot_of) >= self.config.max_slots
                ):
                    # leave the tail for the next step (the cursor has
                    # already passed it, so it re-queues explicitly) — in
                    # the lane's OWN retry list: a priority tail must
                    # never re-enter behind the bulk backlog
                    if lane == "prio":
                        self._retry_prio.extend(batch[bi:])
                    else:
                        self._retry.extend(batch[bi:])
                    break
                slot = slot_of.setdefault(vote.tx_hash, len(slot_of))
                keys.append(key)
                votes.append(vote)
                slots.append(slot)
            if drop_now:
                t_drop = monotonic()
                self.tx_vote_pool.remove(drop_now)
                prep.drop_t = (t_drop, monotonic())
                self._late_votes += n_late
                self._dup_votes += len(drop_now) - n_late
            prep.dropped = len(drop_now)
            if not votes:
                return prep, lk_acq
            self._step_seq += 1
            prep.step = self._step_seq

            n_slots = len(slot_of)
            prior = np.zeros(n_slots, np.int64)
            t_carry = monotonic()
            carried = 0
            for tx_hash, s in slot_of.items():
                vs = self.vote_sets.get(tx_hash)
                if vs is not None:
                    prior[s] = vs.stake()
                    carried += 1
            prep.carry_t = (t_carry, monotonic())
            self._carried_slots += carried
            prep.n_slots = n_slots
            prep.prior = prior

            tr = self.tracer
            if tr.active:
                # unique txs only (n_slots <= max_slots, not batch size):
                # one int parse per distinct hash
                prep.trace_txs = [h for h in slot_of if tr.sampled(h)]

            # snapshot the set-epoch references this drain belongs to:
            # update_state replaces both wholesale under _mtx, so the
            # assembly below reads a consistent pair outside the lock
            addr_to_idx = self._addr_to_idx
            prep.verifier = self.verifier
        # sign-bytes / signature / validator-index assembly: pure
        # per-vote work over the drained (engine-local) batch, moved OUT
        # from under _mtx — consensus-path claims and gossip ingest no
        # longer queue behind the heaviest slice of host prep — and
        # sharded across the host pool when one is attached (contiguous
        # slices in vote order, so the assembled batch is byte-identical
        # to the serial path; parity pinned by tests/test_mesh_engine.py)
        from ..types.tx_vote import sign_bytes_many

        pool = self._host_pool
        t_sign = monotonic()
        msgs = None
        if (
            pool is not None
            and getattr(pool, "backend", "thread") == "process"
            and getattr(pool, "healthy", False)
            and len(votes) >= _POOL_MIN_VOTES
        ):
            # process backend: sign-bytes encode runs in worker PROCESSES
            # over shared memory (no GIL contention with the engine
            # thread). None return = hostile field bounds or a broken
            # pool — fall through to the thread/serial paths below.
            msgs = self._sign_bytes_proc(votes, pool)
        if msgs is not None:
            prep.msgs = msgs
            prep.sigs = [v.signature or b"" for v in votes]
            prep.val_idx = np.array(
                [addr_to_idx.get(v.validator_address, -1) for v in votes],
                dtype=np.int64,
            )
        elif pool is not None and pool.workers > 1 and len(votes) >= _POOL_MIN_VOTES:

            def _assemble(lo: int, hi: int):
                vs = votes[lo:hi]
                return (
                    sign_bytes_many(vs, self.chain_id),
                    [v.signature or b"" for v in vs],
                    [addr_to_idx.get(v.validator_address, -1) for v in vs],
                )

            parts, wait_s = pool.map_shards(len(votes), _assemble)
            prep.msgs = [m for p in parts for m in p[0]]
            prep.sigs = [s for p in parts for s in p[1]]
            prep.val_idx = np.array(
                [i for p in parts for i in p[2]], dtype=np.int64
            )
            self._pipe_prep_pool_wait_s += wait_s
        else:
            prep.msgs = sign_bytes_many(votes, self.chain_id)
            prep.sigs = [v.signature or b"" for v in votes]
            prep.val_idx = np.array(
                [addr_to_idx.get(v.validator_address, -1) for v in votes],
                dtype=np.int64,
            )
        self._pipe_prep_sign_s += monotonic() - t_sign
        return prep, lk_acq

    def _submit_prep(self, prep: "_StepPrep"):
        """Stage 2 dispatch: hand the prepped batch to the verifier. With
        a submit/collect verifier the kernel is enqueued and this returns
        immediately; otherwise the verify runs inline and the ticket is
        already complete (same decisions, no overlap).

        Cold-shape gate (background warmup): when the batch's device
        shape has not compiled yet, the batch is demoted to the scalar
        fallback — the SAME verdicts, just on the host — instead of
        stalling the whole pipeline behind a synchronous compile. The
        BackgroundWarmer flips the gate shape by shape; once warm,
        batches promote to the device and never come back."""
        with _Stage(self, SPAN_DISPATCH, prep.step, len(prep.votes)) as st:
            ticket = self._dispatch(prep)
        prep.dispatch_end = st.t1
        # open across the pipelined in-flight gap — a begin/finish pair so
        # the soak's leak check also proves no ticket is ever orphaned
        # (the PR 3 drain-on-stop claim)
        prep.device_sid = self.tracer.begin("", SPAN_DEVICE, st.t1, prep.step)
        return ticket

    def _dispatch(self, prep: "_StepPrep"):
        if prep.lane == "prio":
            self._lane_prio_batches += 1
            self._lane_prio_votes += len(prep.votes)
            self.metrics.lane_prio_batches.add(1)
            self.metrics.lane_prio_votes.add(len(prep.votes))
        gate = self._warm_gate
        if (
            gate is not None
            and self._cold_fallback is not None
            and prep.verifier is self.verifier
            and not gate.is_batch_warm(len(prep.votes), prep.n_slots)
        ):
            prep.verifier = self._cold_fallback
            self._cold_fallback_votes += len(prep.votes)
            self.metrics.warmup_cold_fallback_votes.add(len(prep.votes))
        sub = getattr(prep.verifier, "submit", None)
        if sub is not None:
            ticket = sub(
                prep.msgs, prep.sigs, prep.val_idx,
                np.array(prep.slots, np.int32), prep.n_slots,
                prior_stake=prep.prior,
            )
        else:
            ticket = ReadyTicket(
                prep.verifier.verify_and_tally(
                    prep.msgs, prep.sigs, prep.val_idx,
                    np.array(prep.slots, np.int32), prep.n_slots,
                    prior_stake=prep.prior,
                )
            )
        return ticket

    def _collect(self, prep: "_StepPrep", ticket):
        """Stage 2 collect: block for the ticket's readback
        (collect_wait), then close the step's device_busy span: from the
        later of its dispatch and the previous step's ready time to its
        own ready time — the moment the packed result was usable on the
        host, as the ticket stamped it (the staging ring's thread, once
        it held the interpreter lock again), or the end of collect_wait
        where the ticket carries no stamp. In-order collection makes the
        spans of one verifier disjoint. Their sum is dispatch -> result
        usable, step by step: the device's time plus the stamping
        thread's wait for the lock, so an upper bound of the device's
        busy time, never the device's own reading."""
        with _Stage(self, SPAN_COLLECT, prep.step, len(prep.votes)) as st:
            result = ticket.result()
        start = max(prep.dispatch_end, self._last_ready)
        ready = max(getattr(ticket, "ready_t", None) or st.t1, start)
        self._last_ready = ready
        sid, prep.device_sid = prep.device_sid, 0
        self._stage_done(SPAN_DEVICE, start, ready, prep.step, sid=sid)
        return result

    def _route_result(self, prep: "_StepPrep", result) -> tuple[int, int]:
        """Stage 3: route the verified batch in submission (= pool ingest)
        order into the authoritative vote sets, committing inline the
        moment a set crosses 2/3. Returns (decided, requeued);
        decided + requeued == len(prep.votes) always."""
        with _Stage(self, SPAN_ROUTE, prep.step, len(prep.votes)) as st:
            out = self._route(prep, result, st.t0)
        self.metrics.step_time.observe(st.t1 - prep.t0)
        return out

    def _route(self, prep: "_StepPrep", result, t0: float) -> tuple[int, int]:
        """_route_result's work, in three child spans that tile it:
        route_tally (under _mtx: routing, quorum decisions, removal of
        votes that can never be added, then the accountability hook),
        route_commit (the inline _commit_effects loop: TxStore, ABCI,
        commitpool, events; not recorded where a committer thread
        applies the commits, whose work is each tx's commit_apply) and
        route_purge (the step's one pool purge)."""
        step = prep.step
        keys, votes = prep.keys, prep.votes
        requeued = 0
        tr = self.tracer
        # inline-commit decisions made under _mtx; their store/ABCI
        # side-effects run AFTER the lock is released (see below)
        inline_commits: list[tuple[TxVoteSet, list[TxVote], bytes | None]] = []
        # speculative quorum commit (ISSUE 12): decision timestamps of
        # commits routed on the device's maj23 hint, and their open
        # spec_commit span ids (finished at route end — the tail the
        # early exit removed)
        spec_t: list[float] = []
        spec_sids: list[int] = []
        with self._mtx:
            self._sh_votesets.note_write()
            self.metrics.batch_size.observe(len(votes))
            self.metrics.verified_votes.add(int(result.valid.sum()))

            # route decisions in batch order (canonical) into the vote sets,
            # committing INLINE the moment a set crosses 2/3 — exactly the
            # reference's per-vote order (service.go:192-234), so commit
            # certificates are identical to the serial path, not padded
            # with same-batch late votes
            bad_keys: list[bytes] = []
            late_verified = dup_verified = 0
            # the valid=False slice only (bad_keys also carries late/dup
            # removals, which are NOT peer misbehavior): resolved to
            # ingest origins for the accountability hook below
            invalid_keys: list[bytes] = []
            purge_votes: list[TxVote] = []  # quorum votes, ONE pool purge/step
            # a requeue re-enters through the lane that drained it — a
            # priority repeat must never wait out the bulk backlog
            retry_lane = (
                self._retry_prio if prep.lane == "prio" else self._retry
            )
            # per-element numpy bool indexing costs ~100 ns each at batch
            # scale — lists are ~5x cheaper in this Python loop
            valid_l = result.valid.tolist()
            dropped_l = result.dropped.tolist()
            n = len(votes)
            # speculative quorum commit: the ticket's readback carries a
            # per-slot maj23 hint (prior stake + this batch's tally over
            # the 2n/3 line). Route the hinted slots' votes FIRST so
            # their commit decisions — and the committer's store/apply
            # effects behind them — start the instant the readback lands
            # instead of after the whole drain routes. The hint is only a
            # ROUTING-ORDER hint: in pipelined mode the prior snapshot
            # can be a batch stale either way, so the host TxVoteSet
            # below still decides every quorum. All votes of one tx share
            # one slot, so the partition reorders only ACROSS txs (both
            # halves keep ascending batch order within themselves):
            # certificates stay byte-identical to the scalar golden path,
            # only cross-tx commit order may shift — which is why
            # speculative_commit defaults off (utils/config.py).
            order = None
            spec_n = 0
            if self.config.speculative_commit:
                maj_l = result.maj23.tolist()
                slots_l = prep.slots
                first = [i for i in range(n) if maj_l[slots_l[i]]]
                if first and len(first) < n:
                    order = first + [
                        i for i in range(n) if not maj_l[slots_l[i]]
                    ]
                    spec_n = len(first)
            for pos in range(n):
                i = order[pos] if order is not None else pos
                vote = votes[i]
                if dropped_l[i]:
                    # in-batch (slot, validator) repeat: the cursor has
                    # passed this entry, so re-queue it for the next step
                    retry_lane.append((keys[i], vote))
                    requeued += 1
                    continue
                if not valid_l[i]:
                    self.metrics.invalid_votes.add(1)
                    bad_keys.append(keys[i])
                    invalid_keys.append(keys[i])
                    continue
                vs = self.vote_sets.get(vote.tx_hash)
                if vs is None:
                    if self._committed.__contains__(_hash_key(vote.tx_hash)):
                        # late: committed since this batch was prepped
                        # (earlier in it, or by the step in flight then)
                        bad_keys.append(keys[i])
                        late_verified += 1
                        continue
                    vs = TxVoteSet(
                        self.chain_id, self.height, vote.tx_hash, vote.tx_key, self.val_set
                    )
                    self.vote_sets[vote.tx_hash] = vs
                added, err = vs.add_verified_vote(vote)
                if added:
                    if vs.last_step != step:
                        vs.last_step = step
                        vs.steps += 1
                    if vs.has_two_thirds_majority():
                        self._quorums += 1
                        self._quorum_rows += len(vs.votes)
                        self._quorum_steps += vs.steps
                        in_spec = pos < spec_n
                        traced = tr.active and tr.sampled(vote.tx_hash)
                        if in_spec or traced:
                            now = monotonic()
                            if traced:
                                # routing latency up to THIS decision:
                                # result available (route start) ->
                                # quorum latched
                                tr.span(vote.tx_hash, SPAN_QUORUM, t0, now, step)
                                tr.span(vote.tx_hash, SPAN_QUORUM_WAIT,
                                        prep.pickup_t0, now, step)
                            if in_spec:
                                spec_t.append(now)
                                if traced:
                                    spec_sids.append(
                                        tr.begin(vote.tx_hash, SPAN_SPEC, now, step)
                                    )
                        if self._committer is not None:
                            self._enqueue_commit(vs, step)
                        else:
                            # decision bookkeeping only — the effects
                            # (save_tx fsync, ABCI apply round trip) must
                            # not run under _mtx: they stalled every
                            # try_add_vote/claim/stat reader behind disk
                            # and socket (lock-blocking finding, fixed)
                            inline_commits.append(self._decide_commit(vs, step))
                else:
                    bad_keys.append(keys[i])  # dup/conflict: can never add
                    dup_verified += 1
            self._late_verified += late_verified
            self._dup_votes += dup_verified
            if len(self.vote_sets) > self._open_vote_sets:
                self._open_vote_sets = len(self.vote_sets)
            invalid_origins = None
            if invalid_keys and self.on_invalid_votes is not None:
                # resolve BEFORE the remove below wipes the entries —
                # same pool-lock-under-_mtx order as remove itself
                invalid_origins = self.tx_vote_pool.origins_of(invalid_keys)
            if bad_keys:
                self.tx_vote_pool.remove(bad_keys)

        if invalid_origins is not None:
            # accountability hook (health/byzantine.py ledger, wired by
            # the node): each valid=False verdict, attributed to the peer
            # whose delivery created the pool entry. Outside _mtx — the
            # ledger takes its own lock and may punish the scoreboard;
            # a hook fault must never take down the verify step.
            try:
                self.on_invalid_votes(invalid_origins)
            except Exception:
                pass

        t_commit = t_tally = monotonic()
        if inline_commits:
            for vs, quorum_votes, tx in inline_commits:
                # decision order preserved; _commit_effects re-acquires
                # _mtx only to resolve deferred-apply ownership
                self._commit_effects(
                    vs, quorum_votes, purge_votes, tx=tx, deferred=tx is None
                )
            t_commit = monotonic()
        if purge_votes:
            # one pool update per step (per-tx updates paid an O(log)
            # bookkeeping walk per commit — r3 step profile: 0.9 ms each)
            self.tx_vote_pool.update(self.height, purge_votes)

        t1 = monotonic()
        self._stage_done(SPAN_ROUTE_TALLY, t0, t_tally, step)
        if inline_commits:
            self._stage_done(SPAN_ROUTE_COMMIT, t_tally, t_commit, step)
        self._stage_done(SPAN_ROUTE_PURGE, t_commit, t1, step)
        if spec_t:
            # saved tail per spec commit: route end minus its decision
            # time — the wait the early exit removed from its latency
            self._spec_commits += len(spec_t)
            saved = 0.0
            for t in spec_t:
                saved += t1 - t
            self._spec_saved_s += saved
            self.metrics.spec_commits.add(len(spec_t))
            self.metrics.spec_saved_seconds.add(saved)
        for sid in spec_sids:
            # always closed here — the drain-on-stop invariant (zero open
            # spec_commit spans) rides the same finally-drain as device
            tr.finish(sid, t1)
        decided = len(votes) - requeued
        self.last_step_stats = {
            "decided": decided, "requeued": requeued,
            "dropped": prep.dropped, "batch": len(votes),
        }
        return decided, requeued

    def pipeline_stats(self) -> dict:
        """Verify-pipeline observability snapshot (health registry,
        perfbench), every second of it from _stage_done.
        overlap_ratio is the device_busy spans' sum (dispatch -> result
        usable on the host: the device's time and the readback thread's
        wait for the interpreter lock) over engine-active wall time:
        ~1.0 means a result was always on its way while the engine
        prepped and routed (a scalar verifier works inline, inside
        dispatch, and reads 0); idle_gap_s is a lower bound of the
        device's idle time — what raising pipeline_depth / retuning
        min_batch+batch_wait should shrink. The device's own idle share
        is the profiler's to give."""
        active = self._pipe_active_s
        busy = min(self._pipe_busy_s, active)
        ctrl = self._depth_ctrl
        stats = {
            "depth": (
                ctrl.depth if ctrl is not None else int(self.config.pipeline_depth)
            ),
            "steps": self._pipe_steps,
            "overlap_ratio": round(busy / active, 4) if active > 0 else None,
            "device_busy_s": round(self._pipe_busy_s, 4),
            "active_s": round(active, 4),
            "idle_gap_s": round(max(active - busy, 0.0), 4),
            "prep_s": round(self._pipe_prep_s, 4),
            "dispatch_wait_s": round(self._pipe_wait_s, 4),
            "route_s": round(self._pipe_route_s, 4),
            "lock_wait_s": round(self._pipe_lock_wait_s, 4),
            # what the in-flight state cost (see __init__): votes dropped
            # in prep as late or dup, votes verified and then found late,
            # slots that carried prior stake, most vote sets open at once
            "late_votes": self._late_votes,
            "dup_votes": self._dup_votes,
            "late_verified": self._late_verified,
            "carried_slots": self._carried_slots,
            "open_vote_sets": self._open_vote_sets,
            # quorums decided, their certificate rows and the steps that
            # fed each one, summed (rows / quorums = a certificate's rows)
            "quorums": self._quorums,
            "quorum_rows": self._quorum_rows,
            "quorum_steps": self._quorum_steps,
            # host-prep split: sign/assembly stage wall time, and the
            # slice of it spent parked on host-pool shards (report.py
            # prep_serial vs prep_pool_wait)
            "prep_sign_s": round(self._pipe_prep_sign_s, 4),
            "prep_pool_wait_s": round(self._pipe_prep_pool_wait_s, 4),
            "host_prep_workers": (
                self._host_pool.workers if self._host_pool is not None else 0
            ),
            # live backend, not the configured one: a failed process
            # spawn falls back to threads and this reports the truth
            "host_prep_backend": (
                getattr(self._host_pool, "backend", "thread")
                if self._host_pool is not None
                else None
            ),
            "mesh_devices": self._verifier_shards(),
        }
        # the process's collector schedule (utils/collector.py; one for
        # every node of the process): full collections since the first
        # node started and their seconds, what the last one left, and
        # the start-up heap kept out of them
        stats.update(COLLECTOR.stats())
        co = self._coalescer
        stats["coalesce"] = {
            "enabled": co is not None,
            "full_batches": co.full_batches if co is not None else 0,
            "linger_flushes": co.linger_flushes if co is not None else 0,
            # holds ended because the held votes completed a quorum, and
            # the pool entries the probe read to find out
            "quorum_flushes": co.quorum_flushes if co is not None else 0,
            "quorum_probed": self._probe_bulk.probed,
            "cold_fallback_votes": self._cold_fallback_votes,
            "prewarm_failures": self._prewarm_failures,
            # wide-rung ladder (wide_buckets): gate line, live verdict,
            # and how many drains actually rode the wide rungs
            "wide_from": co.wide_from if co is not None else None,
            "wide_ok": co.wide_ok if co is not None else None,
            "wide_full_batches": (
                co.wide_full_batches if co is not None else 0
            ),
        }
        pl = self._prio_lane
        stats["lanes"] = {
            "enabled": pl is not None,
            "prio_batches": self._lane_prio_batches,
            "prio_votes": self._lane_prio_votes,
            "prio_full_batches": pl.full_batches if pl is not None else 0,
            "prio_linger_flushes": pl.linger_flushes if pl is not None else 0,
            "prio_quorum_flushes": pl.quorum_flushes if pl is not None else 0,
            # live lingers (adaptive_linger steers these at runtime)
            "prio_linger_ms": (
                round(pl.linger * 1e3, 4) if pl is not None else None
            ),
            "bulk_linger_ms": (
                round(co.linger * 1e3, 4) if co is not None else None
            ),
        }
        stats["spec"] = {
            "enabled": bool(self.config.speculative_commit),
            "commits": self._spec_commits,
            "saved_s": round(self._spec_saved_s, 4),
        }
        if self._linger_ctrl is not None:
            stats["adaptive_linger"] = self._linger_ctrl.stats()
        gate = self._warm_gate
        if gate is not None:
            warm = len(gate.warmed)
            stats["warmup"] = {
                "warm_shapes": warm,
                "total_shapes": len(gate.enumerate_shapes(full=True)),
                "done": self._warmer.done() if self._warmer is not None else None,
            }
            self.metrics.warmup_warm_shapes.set(warm)
        if ctrl is not None:
            stats["adaptive_depth"] = ctrl.stats()
        from .shapes import _unwrap_device

        dev = _unwrap_device(self.verifier)
        if dev is not None:
            ring = getattr(dev, "staging_stats", None)
            ring_stats = ring() if ring is not None else None
            if ring_stats is not None:
                stats["staging"] = ring_stats
            # the steps' host->device puts: two a step, vote rows and
            # slot state (DeviceVoteVerifier.submit)
            stats["h2d"] = dev.h2d_stats()
        return stats

    # ---- scalar parity API (reference TryAddVote :169-188) ----

    def try_add_vote(self, vote: TxVote) -> tuple[bool, Exception | None]:
        with self._mtx:
            return self._add_vote_scalar(vote)  # txlint: allow(lock-blocking) -- golden scalar path: reference-exact synchronous commit semantics; serving traffic uses _route_result, whose effects run unlocked

    def _add_vote_scalar(self, vote: TxVote) -> tuple[bool, Exception | None]:
        """Reference-exact scalar path (used by tests as the golden engine)."""
        self._sh_votesets.note_write()
        if self._committed.__contains__(_hash_key(vote.tx_hash)) or (
            vote.tx_hash not in self.vote_sets and self.tx_store.has_tx(vote.tx_hash)
        ):
            return False, None
        vs = self.vote_sets.get(vote.tx_hash)
        if vs is None:
            vs = TxVoteSet(self.chain_id, self.height, vote.tx_hash, vote.tx_key, self.val_set)
            self.vote_sets[vote.tx_hash] = vs
        added, err = vs.add_vote(vote)
        if added and vs.has_two_thirds_majority():
            self._commit_tx(vs)
        return added, err

    # ---- commit (reference addVote :216-232) ----

    def _trace_commit_begin(self, tx_hash: str, step: int = 0) -> None:
        """Open the commit_apply span at DECISION time (caller holds
        _mtx, like the _committed mark it shadows), under the id of the
        step that decided it."""
        tr = self.tracer
        if tr.active and tr.sampled(tx_hash):
            self._commit_spans[tx_hash] = tr.begin(tx_hash, SPAN_COMMIT, None, step)

    def _trace_commit_end(self, tx_hash: str) -> None:
        """Close the commit_apply span from whichever path delivered the
        apply (committer batch, inline effects, late delivery, block via
        claim_vtx) and latch the e2e anchor. Safe from any thread; _mtx
        is reentrant for callers already holding it."""
        tr = self.tracer
        if not tr.active:
            return
        with self._mtx:
            sid = self._commit_spans.pop(tx_hash, None)
        if sid:
            tr.finish(sid)
        tr.latch(tx_hash)  # no-op when the tx was never anchored

    def _decide_commit(
        self, vs: TxVoteSet, step: int = 0
    ) -> tuple[TxVoteSet, list[TxVote], bytes | None]:
        """Locked half of an inline commit (pipeline_commits=False): the
        same decision bookkeeping _enqueue_commit does for the committer
        thread, but the effects run on THIS thread once _route_result
        drops _mtx. The tx bytes and the _unapplied registration must
        both happen here, atomically with the _committed mark — see
        _enqueue_commit's comments for both races."""
        quorum_votes = vs.get_votes()
        self._sh_votesets.note_write()
        self.vote_sets.pop(vs.tx_hash, None)
        self._committed.push(_hash_key(vs.tx_hash))
        self._trace_commit_begin(vs.tx_hash, step)
        tx = self.mempool.get_tx(vs.tx_key)
        if tx is None:
            self._unapplied[vs.tx_hash] = vs.tx_key
        return vs, quorum_votes, tx

    def _commit_tx(self, vs: TxVoteSet, purge_batch: list | None = None) -> None:
        """Inline commit (scalar golden path / pipeline_commits=False)."""
        quorum_votes = vs.get_votes()
        # fixed leak: drop the in-flight set, remember the hash
        self._sh_votesets.note_write()
        self.vote_sets.pop(vs.tx_hash, None)
        self._committed.push(_hash_key(vs.tx_hash))
        self._commit_effects(vs, quorum_votes, purge_batch)
        if purge_batch is None:
            self.tx_vote_pool.update(self.height, quorum_votes)

    def _enqueue_commit(self, vs: TxVoteSet, step: int = 0) -> None:
        """Step-side half of a pipelined commit: engine bookkeeping now,
        side-effects on the committer thread (in decision order). The tx
        BYTES are captured here — by the time the committer runs, a block
        carrying this tx as a vtx may have purged the mempool (its claim
        saw our _committed mark and skipped delivery, counting on us), and
        a late get_tx(None) would silently drop the apply."""
        self._sh_votesets.note_write()
        self.vote_sets.pop(vs.tx_hash, None)
        self._committed.push(_hash_key(vs.tx_hash))
        self._decided_count += 1
        self._trace_commit_begin(vs.tx_hash, step)
        tx = self.mempool.get_tx(vs.tx_key)
        if tx is None:
            # bytes absent at DECISION time: the deferral must be visible
            # the same instant the _committed mark is (both under _mtx) —
            # registering it later on the committer left a window where
            # claim_vtx saw "committed" without "unapplied" and skipped
            # the block delivery (r5 review): permanent divergence
            self._unapplied[vs.tx_hash] = vs.tx_key
        self._commit_q.put((vs, vs.votes_snapshot(), tx))

    def _commit_effects(
        self,
        vs: TxVoteSet,
        quorum_votes: list[TxVote],
        purge_batch: list | None,
        tx: bytes | None = None,
        deferred: bool = False,
    ) -> None:
        """Store + execute + commitpool effects (reference addVote
        :216-232 sequence). Runs under _mtx only on the scalar golden
        path (_commit_tx); _route_result's inline path calls it unlocked.

        deferred=True means the tx bytes were absent at DECISION time and
        an _unapplied entry was registered under _mtx (_decide_commit) —
        by now the block path (claim_vtx) may own the delivery, or the
        bytes may have arrived: resolve ownership under _mtx exactly like
        _commit_batch does, and never apply twice."""
        had_tx = tx is not None
        try:
            self.tx_store.save_tx(vs, votes=quorum_votes, tx=tx)
        except (OSError, FailpointError) as e:
            self._note_storage_error(e)
        if tx is None:
            with self._mtx:
                if deferred and vs.tx_hash not in self._unapplied:
                    pass  # claim_vtx handed the delivery to a block
                else:
                    tx = self.mempool.get_tx(vs.tx_key)
                    if tx is None:
                        # bytes not here yet: defer (see _unapplied in
                        # __init__); no-op re-registration when deferred
                        self._unapplied[vs.tx_hash] = vs.tx_key
                    elif deferred:
                        del self._unapplied[vs.tx_hash]
        if tx is not None and not had_tx:
            self._save_tx_bytes_late(vs.tx_hash, tx)
        if tx is not None:
            # the hash handed to events/indexer must describe the tx actually
            # fetched and applied: tx came from mempool.get_tx(vs.tx_key), and
            # the mempool keys by sha256, so the key IS sha256(tx). vs.tx_hash
            # is NOT safe here — sign bytes zero TxKey (module docstring of
            # types.tx_vote), so a relayer can pair a valid signature for hash
            # H with a forged tx_key and desynchronize the two.
            app_hash, _ = self.tx_executor.apply_tx(
                self.height, tx, vs.tx_key.hex().upper(), tx_key=vs.tx_key
            )
            self.app_hash = app_hash
            self.metrics.committed_txs.add(1)
            try:
                self.commitpool.check_tx(tx, key=vs.tx_key)
            except Exception:
                pass  # commitpool dup (e.g. replays) is harmless
            self._trace_commit_end(vs.tx_hash)
        self.metrics.committed_votes.add(len(quorum_votes))
        if purge_batch is not None:
            purge_batch.extend(quorum_votes)

    def _committer_run(self) -> None:
        purge: list[TxVote] = []
        interval = max(1, self.config.commit_interval)

        def flush() -> None:
            if not purge:
                return
            self.tx_vote_pool.update(self.height, purge)
            purge.clear()

        stop = False
        while not stop:
            try:
                item = self._commit_q.get(timeout=0.05)
            except _queue.Empty:
                flush()
                self._apply_unapplied()
                continue
            if item is None:  # stop() sentinel, queued after last commit
                flush()
                return
            # drain the WHOLE backlog for this wake: store writes and pool
            # purges amortize over the backlog regardless of
            # commit_interval (which only governs the ABCI Commit fence
            # cadence inside _commit_batch) — one db write group + one
            # purge per wake instead of per commit (r4 judge profile)
            batch = [item]
            while len(batch) < 1024:
                try:
                    nxt = self._commit_q.get_nowait()
                except _queue.Empty:
                    break
                if nxt is None:  # commit what we have, then exit
                    stop = True
                    break
                batch.append(nxt)
            try:
                self._commit_batch(batch, purge, interval)
            except Exception:
                import traceback

                traceback.print_exc()
            if stop or len(purge) >= 8192 or self._commit_q.empty():
                flush()
                self._apply_unapplied()

    def _commit_batch(
        self, items: list, purge: list[TxVote], interval: int = 1
    ) -> None:
        """Committer-side effects for one wake's backlog of decided txs.

        The backlog-wide parts — TxStore certificate rows (store-then-
        apply, same order as _commit_effects) and vote purges — run ONCE
        per wake; delivery runs per tx IN DECISION ORDER, with the ABCI
        app Commit fence after every `interval` txs (interval=1 is the
        reference-faithful per-tx apply_tx path, txflow/service.go:216-
        232; >1 amortizes the fence via apply_tx_batch)."""
        # one store write group for the whole wake (one lock / append /
        # fsync instead of ~6 locked db ops per commit — r4 judge profile);
        # items are (vs, votes, tx): the decision-time bytes ride along so
        # catch-up servers can hand them to wiped peers (T: rows)
        try:
            self.tx_store.save_txs_batch(items)
        except (OSError, FailpointError) as e:
            self._note_storage_error(e)
        apply_items: list[tuple] = []
        deferred = 0
        retired = 0  # applied by claim_vtx/_apply_unapplied before this wake
        for vs, votes, tx in items:
            self.metrics.committed_votes.add(len(votes))
            purge.extend(votes)
            if tx is None:
                # deferral was registered at decision time; try to retire
                # it now — unless claim_vtx already handed the delivery to
                # a block (or _apply_unapplied beat this wake to it): then
                # we must NOT apply, and the +1 applied credit was ALREADY
                # taken by whoever retired it — counting it again here
                # would let commits_drained() report True over live queued
                # commits (r5 review: applied running ahead of decided)
                with self._mtx:
                    if vs.tx_hash not in self._unapplied:
                        retired += 1
                        continue  # another path owns/owned the delivery
                    tx = self.mempool.get_tx(vs.tx_key)
                    if tx is None:
                        deferred += 1
                        continue  # still waiting for bytes
                    del self._unapplied[vs.tx_hash]
                self._save_tx_bytes_late(vs.tx_hash, tx)
            apply_items.append((vs, tx))
        if not apply_items:
            with self._mtx:
                # under _mtx: claim_vtx's locked += 1 for a different
                # deferred tx must not be lost to this read-modify-write
                self._applied_count += len(items) - deferred - retired
            return
        for base in range(0, len(apply_items), interval):
            group = apply_items[base : base + interval]
            if len(group) == 1:
                vs, tx = group[0]
                app_hash, _ = self.tx_executor.apply_tx(
                    self.height, tx, vs.tx_key.hex().upper(), tx_key=vs.tx_key
                )
            else:
                app_hash, _ = self.tx_executor.apply_tx_batch(
                    self.height,
                    [(tx, vs.tx_key.hex().upper()) for vs, tx in group],
                    keys=[vs.tx_key for vs, _ in group],
                )
            self.app_hash = app_hash
        self.metrics.committed_txs.add(len(apply_items))
        self.commitpool.push_committed_many(
            [tx for _, tx in apply_items], [vs.tx_key for vs, _ in apply_items]
        )
        for vs, _tx in apply_items:
            self._trace_commit_end(vs.tx_hash)
        with self._mtx:  # see the early-return comment above
            self._applied_count += len(items) - deferred - retired

    def _note_storage_error(self, exc: BaseException) -> None:
        """A durable-path write failed (ENOSPC/EIO or an armed failpoint):
        degrade loudly instead of crashing. The commit stays applied in
        memory; health surfaces the flag ("storage" section) and the
        admission front door sheds while it is set."""
        self.storage_degraded = True
        self.storage_errors += 1
        self.storage_last_error = repr(exc)
        m = getattr(self.metrics, "storage_errors", None)
        if m is not None:
            m.add(1)

    def _save_tx_bytes_late(self, tx_hash: str, tx: bytes) -> None:
        """T: row for a certificate whose bytes arrived after the save
        (deferred apply) — never under _mtx, and never fatal."""
        try:
            self.tx_store.save_tx_bytes(tx_hash, tx)
        except (OSError, FailpointError) as e:
            self._note_storage_error(e)

    def apply_synced_commit(
        self, vs: TxVoteSet, votes: list[TxVote], tx: bytes
    ) -> bool:
        """Commit a certificate fetched (and already verified) by the
        catch-up client (sync/manager.py), sharing the live commit seam:
        the _committed mark is pushed under _mtx exactly like a fast-path
        decision, so a racing local quorum or claim_vtx sees it and never
        double-applies; the TxStore save assigns the next local seq, so
        the per-node commit-order log extends in the server's order;
        store-then-apply ordering matches _commit_effects.

        The caller MUST have verified the certificate (2n/3 stake at the
        vote height's validator set) and that sha256(tx) matches
        vs.tx_hash — sign bytes zero TxKey (types.tx_vote), so the vote's
        own tx_key field is forgeable and is never trusted here.

        Returns False when the tx was already committed locally (dedup:
        overlap between a sync range and live gossip is normal)."""
        import hashlib

        tx_key = hashlib.sha256(tx).digest()
        tx_hash = tx_key.hex().upper()
        with self._mtx:
            if self._committed.__contains__(_hash_key(tx_hash)) or (
                self.tx_store.has_tx(tx_hash)
            ):
                return False
            self._sh_votesets.note_write()
            live = self.vote_sets.pop(tx_hash, None)
            self._committed.push(_hash_key(tx_hash))
            self._decided_count += 1
        if live is not None:
            # a below-quorum local aggregation was racing the sync apply:
            # release its pool votes (same leak claim_vtx plugs)
            self.tx_vote_pool.update(self.height, live.votes_snapshot())
        try:
            self.tx_store.save_tx(vs, votes=votes, tx=tx)
        except (OSError, FailpointError) as e:
            self._note_storage_error(e)
        app_hash, _ = self.tx_executor.apply_tx(
            self.height, tx, tx_hash, tx_key=tx_key
        )
        self.app_hash = app_hash
        self.metrics.committed_txs.add(1)
        self.metrics.committed_votes.add(len(votes))
        try:
            self.commitpool.check_tx(tx, key=tx_key)
        except Exception:
            pass  # commitpool dup (e.g. replays) is harmless
        with self._mtx:
            self._applied_count += 1
        return True

    def commits_drained(self) -> bool:
        """True when every decided commit has been applied (the pipelined
        committer's queue is empty AND its in-flight wake finished).
        Decision-time facts (certificates, is_tx_committed) lead the ABCI
        app state by the pipeline depth; tests/operators comparing app
        hashes across nodes must wait for this. Also covers the event
        worker: a drained engine has PUBLISHED every commit event (each
        subscriber's own queue is its own concern)."""
        return (
            self._applied_count >= self._decided_count
            and not self._unapplied
            and self.tx_executor.events_drained()
        )

    def register_unapplied(self, pairs: list[tuple[str, bytes]]) -> None:
        """Adopt decided-but-unapplied txs from a restart handshake (see
        Handshaker.unapplied_commits): the certificate predates this
        process, the apply is still owed — delivery follows the same
        deferral rules as live quorum-before-tx commits."""
        with self._mtx:
            for tx_hash, tx_key in pairs:
                if tx_hash not in self._unapplied:
                    # each owed apply counts as a decided commit from the
                    # prior life, balancing the += 1 its eventual delivery
                    # (claim_vtx / retry) credits — otherwise applied
                    # would run ahead of decided and commits_drained()
                    # could report True over live queued commits (r5
                    # review)
                    self._decided_count += 1
                self._unapplied[tx_hash] = tx_key

    def _apply_unapplied(self) -> None:
        """Late delivery: apply decided txs whose bytes have since
        arrived in the mempool (committer thread; see _unapplied)."""
        with self._mtx:
            if not self._unapplied:
                return
            pending = list(self._unapplied.items())
        for tx_hash, tx_key in pending:
            tx = self.mempool.get_tx(tx_key)
            if tx is None:
                continue
            with self._mtx:
                # claim_vtx may have handed this tx to the block path
                # in the meantime — never apply twice
                if tx_hash not in self._unapplied:
                    continue
                del self._unapplied[tx_hash]
            self._save_tx_bytes_late(tx_hash, tx)
            app_hash, _ = self.tx_executor.apply_tx(
                self.height, tx, tx_key.hex().upper(), tx_key=tx_key
            )
            self.app_hash = app_hash
            self.metrics.committed_txs.add(1)
            self.commitpool.push_committed_many([tx], [tx_key])
            self._trace_commit_end(tx_hash)
            with self._mtx:  # racing claim_vtx's locked increment
                self._applied_count += 1

    def inflight_snapshot(self) -> list[tuple[str, int]]:
        """(tx_hash, stake) for every tx still aggregating below quorum —
        the quorum-stall watchdog's progress signal (health/watchdog.py).
        TxVoteSet.stake() takes the per-set lock, so read it outside the
        engine lock to keep the snapshot cheap under load."""
        with self._mtx:
            self._sh_votesets.note_read()
            sets = list(self.vote_sets.values())
        return [(vs.tx_hash, vs.stake()) for vs in sets]

    @property
    def committed_evictions(self) -> int:
        """Hashes the recently-committed set has pushed out at capacity
        (health/registry.py reports it beside the pools' counters)."""
        return self._committed.evictions

    def is_tx_committed(self, tx_hash: str) -> bool:
        """Committed via EITHER path: the fast path (TxStore certificate)
        or a block that carried it (engine claim mark). A tx reaped into a
        block before its votes aggregated commits without ever touching
        the TxStore."""
        with self._mtx:
            return self._committed.__contains__(
                _hash_key(tx_hash)
            ) or self.tx_store.has_tx(tx_hash)

    def is_tx_reserved(self, tx: bytes) -> bool:
        """True if the fast path owns this tx: already committed, queued
        for commit, or actively aggregating votes. Proposers exclude
        reserved txs from block.Txs — a block carrying a tx that the fast
        path commits before the block applies would double-deliver it
        (r3 fork postmortem: a reaped tx landed in block.Txs, every
        fast-path node applied it twice and forked from catch-up nodes)."""
        import hashlib

        tx_key = hashlib.sha256(tx).digest()
        tx_hash = tx_key.hex().upper()
        with self._mtx:
            if self._committed.__contains__(_hash_key(tx_hash)) or (
                self.tx_store.has_tx(tx_hash)
            ):
                return True
            self._sh_votesets.note_read()
            if tx_hash not in self.vote_sets:
                return False
            # An in-flight vote set only reserves the tx if a fast quorum
            # is actually POSSIBLE: for a block-only tx (app CheckTx
            # fast_path=False) honest validators never sign, so a single
            # byzantine vote would otherwise wedge it forever — reserved
            # out of every proposal, never fast-committed (r5 review:
            # one stray vote silently censored a validator rotation)
            return self.mempool.fast_path_of(tx_key) is not False

    def claim_vtx(self, tx: bytes) -> bool:
        """Block-path arbitration for a vtx about to be applied with a
        block: True = the local fast path has NOT applied it (deliver it
        with the block; the engine marks it committed so a late local
        quorum can never apply it a second time), False = already applied
        (or queued) locally — skip it.

        Must be atomic w.r.t. the engine's own commit decision: checking
        the tx STORE alone races the pipelined committer (r3 postmortem:
        finalize saw 'not committed', delivered the vtx, then the queued
        fast-path commit applied it again — app hash forked from honest
        catch-up nodes). ``_committed`` is pushed at decision time, before
        the committer queue, so cache ∨ store is the authoritative answer.
        """
        import hashlib

        tx_hash = hashlib.sha256(tx).hexdigest().upper()
        with self._mtx:
            if tx_hash in self._unapplied:
                # the fast path DECIDED this tx (certificate saved) but
                # never had its bytes to apply — the block has them:
                # deliver with the block and retire the deferral (r5
                # soak: treating certificate-exists as applied left the
                # tx permanently unapplied on this node)
                del self._unapplied[tx_hash]
                self._applied_count += 1  # the block's apply stands in
                self._trace_commit_end(tx_hash)
                return True
            if self._committed.__contains__(_hash_key(tx_hash)) or (
                self.tx_store.has_tx(tx_hash)
            ):
                return False
            self._sh_votesets.note_write()
            vs = self.vote_sets.pop(tx_hash, None)
            self._committed.push(_hash_key(tx_hash))
            # durable marker: the in-memory LRU can evict, and a tx that
            # committed only via a block has no TxStore certificate —
            # is_tx_committed must never regress to False for it
            self.tx_store.mark_block_committed(tx_hash)  # txlint: allow(lock-blocking) -- claim must be atomic with the commit decision (r3 app-hash fork); marker is one buffered db put, no fsync on this path
            if vs is not None:
                # release the set's aggregated votes from the pool — the
                # drain cursor has passed them and no engine commit will
                # ever purge them now (leak: pool fills, fast path stalls)
                self.tx_vote_pool.update(self.height, vs.votes_snapshot())
            self._trace_commit_end(tx_hash)  # block delivery: latch e2e
            return True

    # ---- queries (reference LoadCommit :116-120) ----

    def load_commit(self, tx_hash: str):
        return self.tx_store.load_tx_commit(tx_hash)

    def update_state(self, height: int, val_set: ValidatorSet) -> None:
        """Block boundary: new height / possibly rotated validator set.

        On a rotated set (epoch boundary: slashing / scheduled join-leave-
        re-weight), churn safety on the hot path means three things, all
        done under _mtx so no verify step sees a half-rotated engine:

        1. verifier RESTAGE, not rebuild: the device constants swap in
           place (same padded shapes, same bucket ladder, same compiled
           programs, same warm gate) — zero in-run
           compiles. Rebuild only when restage is impossible (capacity
           exceeded by a large join, int32 tally cap, or a non-restagable
           verifier type).
        2. every in-flight TxVoteSet is re-evaluated against the new set:
           votes from removed validators are discarded, sums recomputed
           at the new powers, and a set that now clears the (possibly
           lower) quorum commits immediately. Already-latched
           certificates are immutable (TxVoteSet.revalidate).
        3. the address->index map swaps with the verifier, so votes
           prepped after this point gather the new epoch's table rows.
        """
        with self._mtx:
            self.height = height
            # content comparison, not identity: every block commit hands in
            # a fresh ValidatorSet copy (execution.update_state copies
            # next_validators), and re-staging once per block would churn
            # device transfers for an unchanged set
            if val_set is self.val_set or val_set.hash() == self.val_set.hash():
                return
            from ..verifier import ResilientVoteVerifier

            base = self.verifier
            restaged = False
            rs = getattr(base, "restage", None)
            if rs is not None:
                try:
                    restaged = bool(rs(val_set))
                except ValueError:
                    restaged = False  # int32 tally cap: rebuild as scalar
            if restaged:
                verifier = base  # same object, new stage — nothing to swap
                if self._cold_fallback is not None:
                    # the warm-gate's scalar lane must rotate in lockstep
                    # (it serves cold shapes with the SAME decisions)
                    self._cold_fallback.restage(val_set)
            else:
                # Build the new verifier BEFORE swapping any engine state so
                # a constructor failure cannot leave val_set/_addr_to_idx
                # pointing at the new epoch while the verifier still gathers
                # the old epoch's tables (wrong results, not an error).
                resilient = isinstance(base, ResilientVoteVerifier)
                if resilient:
                    base = base.device
                if isinstance(base, DeviceVoteVerifier):
                    try:
                        verifier = DeviceVoteVerifier(
                            val_set,
                            mesh=base.mesh,
                            buckets=base.buckets,
                        )
                        if resilient:
                            # keep the degradation wrapper across rotations
                            verifier = ResilientVoteVerifier(verifier)
                    except ValueError:
                        # total power >= 2^30: int32 device tally would
                        # overflow — documented fallback to the host path
                        verifier = ScalarVoteVerifier(val_set)
                else:
                    verifier = ScalarVoteVerifier(val_set)
            self.val_set = val_set
            self._addr_to_idx = {v.address: i for i, v in enumerate(val_set)}
            self._addr_power = {v.address: v.voting_power for v in val_set}
            self.verifier = verifier
            if not restaged and self._warm_gate is not None:
                # the shape-stability layer tracks the OLD verifier's
                # device: rebuild gate/fallback/warmer against the new
                # epoch (new epoch tables, same bucket ladder — banked
                # compiles still hit the persistent cache)
                if self._warmer is not None:
                    self._warmer.stop(timeout=0.0)
                    self._warmer = None
                self._shape_registry = None
                self._warm_gate = None
                self._cold_fallback = None
                self._setup_background_warmup()
            # churn safety: re-evaluate every in-flight quorum against the
            # new set (removed validators' votes discarded, sums re-weighted,
            # latched certificates untouched — TxVoteSet.revalidate)
            dropped = 0
            newly_quorate = []
            self._sh_votesets.note_write()
            for vs in list(self.vote_sets.values()):
                d, quorate = vs.revalidate(val_set)
                dropped += d
                if quorate:
                    newly_quorate.append(vs)
            for vs in newly_quorate:
                # a shrinking total power can push a pending tx OVER the
                # 2n/3 line with no new vote arriving — commit it now, on
                # the reference-exact inline path (try_add_vote precedent)
                self._commit_tx(vs)  # txlint: allow(lock-blocking) -- epoch-boundary path (rare, not serving traffic): same reference-exact inline commit the golden scalar path uses
            self.last_rotation = {
                "height": height,
                "restaged": restaged,
                "votes_dropped": dropped,
                "commits_on_rotation": len(newly_quorate),
                "val_set_hash": val_set.hash().hex(),
            }
            m = self.metrics
            m.epoch_rotations.add(1)
            (m.epoch_restages if restaged else m.epoch_rebuilds).add(1)
            if dropped:
                m.epoch_votes_dropped.add(dropped)
            if newly_quorate:
                m.epoch_rotation_commits.add(len(newly_quorate))


def _hash_key(tx_hash: str) -> bytes:
    return tx_hash.encode()
