"""txlint static passes (see core.RULES for the rule inventory).

Every pass is heuristic AST analysis tuned to THIS repo's idioms — lock
attributes are named ``*_mtx``/``*_lock``/``*_cond``, blocking surfaces
are a known vocabulary (ticket.result, sendall, check_tx_sync, save_tx,
...), hot loops live in named TxFlow methods. The goal is a zero-noise
gate over this tree, not a general-purpose linter: false negatives are
the runtime auditor's job (analysis.lockgraph), false positives are
suppressed inline with a justification.
"""

from __future__ import annotations

import ast
import re

from .core import LintPass, ModuleSource, Violation

# ---------------------------------------------------------------------------
# lock-blocking
# ---------------------------------------------------------------------------

# attribute names that read as a mutex when used in `with ...:`
_LOCK_SEGMENTS = {"mtx", "mu", "lock", "rlock", "wlock", "lk", "cv", "cond", "condition"}

# receiver-name patterns
_QUEUE_RE = re.compile(r"(^|[._])(q|queue|jobs|inbox|outbox)$|queue", re.I)
_SOCKISH_RE = re.compile(r"sock|conn|peer", re.I)
_WAL_RE = re.compile(r"wal", re.I)

# method names that are a blocking round trip / durability point wherever
# they appear (socket ABCI calls, store writes, pool condition waits)
_BLOCKING_ATTRS = {
    "check_tx_sync": "ABCI CheckTx round trip",
    "deliver_tx_sync": "ABCI DeliverTx round trip",
    "commit_sync": "ABCI Commit round trip",
    "flush_sync": "ABCI Flush round trip",
    "query_sync": "ABCI Query round trip",
    "info_sync": "ABCI Info round trip",
    "apply_tx": "ABCI apply round trip",
    "apply_tx_batch": "ABCI apply round trip",
    "save_tx": "store write (fsync at height edges)",
    "save_txs_batch": "store write (fsync at height edges)",
    "set_many": "db batch write (possible fsync)",
    "mark_block_committed": "store write",
    "wait_for_new": "pool condition wait",
    "block_until_ready": "device sync",
    "sendall": "socket write",
    "recv": "socket read",
    "recv_into": "socket read",
    "accept": "socket accept",
}


def _expr_str(node: ast.AST) -> str:
    """Dotted-name rendering of simple receiver expressions ("self._mtx",
    "self.pool.cache"); empty string for anything fancier."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_lockish(expr: str) -> bool:
    last = expr.rsplit(".", 1)[-1]
    segs = set(last.strip("_").lower().split("_"))
    if segs & _LOCK_SEGMENTS:
        return True
    return last.lower().endswith(("lock", "mtx"))


def _numeric_const(node: ast.AST) -> bool:
    return isinstance(node, ast.Constant) and isinstance(node.value, (int, float))


def _blocking_reason(call: ast.Call, held: tuple[str, ...]) -> str | None:
    """Why this call is blocking, or None. `held` = dotted lock exprs of
    the enclosing with-blocks (used to allow cond.wait on the held cond)."""
    func = call.func
    if isinstance(func, ast.Name):
        if func.id == "sleep":
            return "sleep()"
        return None
    if not isinstance(func, ast.Attribute):
        return None
    attr = func.attr
    recv = _expr_str(func.value)
    if attr == "sleep":
        return f"{recv or '?'}.sleep()"
    if attr == "result" and not call.args and not call.keywords:
        return "ticket.result() — blocks on the in-flight device verify"
    if attr in _BLOCKING_ATTRS:
        return f".{attr}() — {_BLOCKING_ATTRS[attr]}"
    if attr == "join":
        # thread-like join: no args, timeout kwarg, or one numeric arg.
        # (str.join / os.path.join always take a non-numeric argument.)
        if not call.args and not call.keywords:
            return ".join() — thread join"
        if any(k.arg == "timeout" for k in call.keywords):
            return ".join(timeout=...) — thread join"
        if len(call.args) == 1 and _numeric_const(call.args[0]):
            return ".join(t) — thread join"
        return None
    if attr == "get" and _QUEUE_RE.search(recv):
        for k in call.keywords:
            if (
                k.arg == "block"
                and isinstance(k.value, ast.Constant)
                and k.value.value is False
            ):
                return None
        return f"{recv}.get() — queue wait"
    if attr == "put" and any(k.arg == "timeout" for k in call.keywords):
        return f"{recv}.put(timeout=...) — bounded queue wait"
    if attr in ("send", "connect") and _SOCKISH_RE.search(recv):
        return f"{recv}.{attr}() — socket/peer I/O"
    if attr == "write" and _WAL_RE.search(recv):
        return f"{recv}.write() — WAL append"
    if attr in ("wait", "wait_for"):
        # cond.wait() on the lock you hold RELEASES it — that's the one
        # sanctioned blocking call under a lock
        if recv and recv in held:
            return None
        return f"{recv or '?'}.{attr}() — event/condition wait"
    return None


class LockDisciplinePass(LintPass):
    """No blocking call while lexically inside `with <lock>:`.

    Two detection layers per class:
    1. direct: a blocking call (vocabulary above) inside a lock scope;
    2. taint: a `self.m()` call inside a lock scope where method `m`
       (fixpoint over same-class `self.` calls) contains an unsuppressed
       blocking call — catching effects buried one or more frames below
       the `with`. Suppressing the seed line sanctions the whole chain.
    """

    name = "lock-blocking"

    def run(self, module: ModuleSource) -> list[Violation]:
        out: list[Violation] = []
        for node in ast.walk(module.tree):
            if isinstance(node, ast.ClassDef):
                out.extend(self._run_class(module, node))
        # module-level functions (rare; no self-taint possible)
        for node in module.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.extend(self._walk_func(module, node, tainted={}, seeds={}))
        return out

    # -- class-level taint fixpoint --

    def _run_class(self, module: ModuleSource, cls: ast.ClassDef) -> list[Violation]:
        methods = {
            n.name: n
            for n in cls.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        # seed: method -> (line, reason) of its first unsuppressed blocking call
        seeds: dict[str, tuple[int, str]] = {}
        calls: dict[str, set[str]] = {name: set() for name in methods}
        for name, fn in methods.items():
            for sub in ast.walk(fn):
                if not isinstance(sub, ast.Call):
                    continue
                reason = _blocking_reason(sub, held=())
                if reason is not None and not module.line_suppressed(
                    self.name, sub.lineno
                ):
                    seeds.setdefault(name, (sub.lineno, reason))
                f = sub.func
                if (
                    isinstance(f, ast.Attribute)
                    and isinstance(f.value, ast.Name)
                    and f.value.id == "self"
                    and f.attr in methods
                ):
                    calls[name].add(f.attr)
        # fixpoint: tainted = transitively reaches a seed via self. calls
        tainted: dict[str, tuple[int, str]] = dict(seeds)
        changed = True
        while changed:
            changed = False
            for name in methods:
                if name in tainted:
                    continue
                for callee in calls[name]:
                    if callee in tainted:
                        line, reason = tainted[callee]
                        tainted[name] = (line, reason)
                        changed = True
                        break
        out: list[Violation] = []
        for fn in methods.values():
            out.extend(self._walk_func(module, fn, tainted=tainted, seeds=seeds))
        return out

    # -- lexical lock-scope walk --

    def _walk_func(
        self,
        module: ModuleSource,
        fn: ast.AST,
        tainted: dict[str, tuple[int, str]],
        seeds: dict[str, tuple[int, str]],
    ) -> list[Violation]:
        out: list[Violation] = []

        def visit(node: ast.AST, held: tuple[str, ...]) -> None:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                if node is not fn:
                    return  # nested defs execute later, outside this scope
            if isinstance(node, ast.With):
                new_held = held
                for item in node.items:
                    expr = _expr_str(item.context_expr)
                    if expr and _is_lockish(expr):
                        new_held = new_held + (expr,)
                for child in ast.iter_child_nodes(node):
                    visit(child, new_held)
                return
            if isinstance(node, ast.Call) and held:
                reason = _blocking_reason(node, held)
                if reason is not None:
                    out.append(
                        Violation(
                            self.name, module.path, node.lineno,
                            f"{reason} while holding {held[-1]}",
                        )
                    )
                else:
                    f = node.func
                    if (
                        isinstance(f, ast.Attribute)
                        and isinstance(f.value, ast.Name)
                        and f.value.id == "self"
                        and f.attr in tainted
                    ):
                        line, why = tainted[f.attr]
                        out.append(
                            Violation(
                                self.name, module.path, node.lineno,
                                f"self.{f.attr}() while holding {held[-1]} — "
                                f"reaches blocking {why} (line {line})",
                            )
                        )
            for child in ast.iter_child_nodes(node):
                visit(child, held)

        for stmt in fn.body:
            visit(stmt, ())
        return out


# ---------------------------------------------------------------------------
# nondeterminism
# ---------------------------------------------------------------------------

# consensus-critical scope: certificate contents and commit decisions must
# be reproducible across nodes/replays
_DETERMINISM_SCOPE = (
    "txflow_tpu/types/vote_set.py",
    "txflow_tpu/engine/txflow.py",
    "txflow_tpu/consensus/",
    # committee election must be identical on every node — any clock or
    # rng leak here forks the committee (and thus the quorum) silently
    "txflow_tpu/committee/",
)

_CLOCK_SEAM = "txflow_tpu/utils/clock.py"


class DeterminismPass(LintPass):
    """No wall clock, unseeded rng, or set-iteration-order dependence in
    consensus-critical modules, except through the utils.clock seam."""

    name = "nondeterminism"

    def run(self, module: ModuleSource) -> list[Violation]:
        if module.path == _CLOCK_SEAM:
            return []  # the seam itself wraps the wall clock
        if not module.path.startswith(_DETERMINISM_SCOPE):
            return []
        out: list[Violation] = []
        seam_names = self._seam_imports(module)
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call):
                out.extend(self._check_call(module, node, seam_names))
            elif isinstance(node, (ast.For, ast.comprehension)):
                it = node.iter
                if _is_set_expr(it):
                    line = getattr(node, "lineno", getattr(it, "lineno", 1))
                    out.append(
                        Violation(
                            self.name, module.path, line,
                            "iteration over a set — order varies per process "
                            "(PYTHONHASHSEED); sort or use an ordered container",
                        )
                    )
        return out

    def _seam_imports(self, module: ModuleSource) -> set[str]:
        """Names bound from utils.clock — calls through them are allowed."""
        names: set[str] = set()
        for node in ast.walk(module.tree):
            if isinstance(node, ast.ImportFrom) and node.module and (
                node.module.endswith("utils.clock") or node.module == "clock"
            ):
                for a in node.names:
                    names.add(a.asname or a.name)
        return names

    def _check_call(
        self, module: ModuleSource, call: ast.Call, seam: set[str]
    ) -> list[Violation]:
        func = call.func
        name = _expr_str(func) if isinstance(func, (ast.Attribute, ast.Name)) else ""
        root = name.split(".", 1)[0]
        if root in seam:
            return []
        if name in ("time.time", "time.time_ns"):
            return [
                Violation(
                    self.name, module.path, call.lineno,
                    f"{name}() in a consensus-critical module — route through "
                    "utils.clock so replays/tests can pin the clock",
                )
            ]
        if root == "random":
            # random.Random(seed) is the sanctioned seeded constructor
            if name == "random.Random" and call.args:
                return []
            return [
                Violation(
                    self.name, module.path, call.lineno,
                    f"{name}() — unseeded process-global rng in a "
                    "consensus-critical module",
                )
            ]
        if root in ("uuid", "secrets") or name == "os.urandom":
            return [
                Violation(
                    self.name, module.path, call.lineno,
                    f"{name}() — nondeterministic value source in a "
                    "consensus-critical module",
                )
            ]
        return []


def _is_set_expr(node: ast.AST) -> bool:
    if isinstance(node, (ast.Set, ast.SetComp)):
        return True
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
        return node.func.id in ("set", "frozenset")
    return False


# ---------------------------------------------------------------------------
# thread-join
# ---------------------------------------------------------------------------


class ThreadLifecyclePass(LintPass):
    """Every Thread(...) created in a class must be daemon=True or joined
    somewhere in the same class (stop()/close()/join-on-name)."""

    name = "thread-join"

    def run(self, module: ModuleSource) -> list[Violation]:
        out: list[Violation] = []
        for node in ast.walk(module.tree):
            if isinstance(node, ast.ClassDef):
                out.extend(self._run_class(module, node))
        return out

    def _run_class(self, module: ModuleSource, cls: ast.ClassDef) -> list[Violation]:
        creations: list[ast.Call] = []
        joins = False
        for sub in ast.walk(cls):
            if isinstance(sub, ast.Call):
                f = sub.func
                fname = _expr_str(f) if isinstance(f, (ast.Attribute, ast.Name)) else ""
                if fname.endswith("Thread") and fname.split(".", 1)[0] in (
                    "threading", "Thread", "_t",
                ):
                    creations.append(sub)
                elif isinstance(f, ast.Attribute) and f.attr == "join":
                    joins = True
        out: list[Violation] = []
        for call in creations:
            daemon = any(
                k.arg == "daemon"
                and isinstance(k.value, ast.Constant)
                and k.value.value is True
                for k in call.keywords
            )
            if daemon or joins:
                continue
            out.append(
                Violation(
                    self.name, module.path, call.lineno,
                    f"Thread created in {cls.name} is neither daemon=True nor "
                    "joined anywhere in the class — a leaked thread outlives "
                    "stop() and keeps the process alive",
                )
            )
        return out


# ---------------------------------------------------------------------------
# hotpath-sync
# ---------------------------------------------------------------------------

# the pipelined engine loops: one host sync here stalls every in-flight
# ticket behind it (COMPONENTS.md "Verify pipeline")
_HOT_FUNCS = {
    "txflow_tpu/engine/txflow.py": {
        "_run_pipelined", "_form_batch", "step", "_prep_batch",
        "_submit_prep", "_collect", "_route_result",
        # lane-split + speculative-commit helpers (ISSUE 12): all run
        # inside the fill/route stages of the pipelined loop
        "_prio_pending", "_bulk_pending", "_bulk_quantum",
        "_steer_lingers", "_sign_bytes_proc",
    },
    # the staging ring's whole point is that the ONLY np.asarray lives
    # in its dedicated readback thread (StageSlot._run): the caller-
    # facing enter/exit paths must never force the transfer themselves,
    # or the ring silently degrades to the synchronous readback it
    # replaced. (StagingRing.submit's bounded semaphore wait is
    # backpressure by contract — this pin is about device syncs, not
    # blocking in general.)
    "txflow_tpu/parallel/staging.py": {"submit", "result"},
}

_HOT_ATTRS = {
    "item": ".item() forces a device->host readback per element",
    "asarray": "np.asarray on a device array is a blocking transfer",
    "device_get": "explicit host readback",
    "block_until_ready": "full device sync",
}


# admit-path functions that must never block: they run inline on every
# RPC handler thread and the gossip receive path, so one blocking call
# stalls the whole front door (the shed path must stay O(1) — that is
# the backpressure contract). Checked against the same blocking-call
# vocabulary as lock-blocking, with NO lock held.
_HOT_NOBLOCK_FUNCS = {
    "txflow_tpu/admission/controller.py": {
        "admit_rpc", "admit_gossip", "lane_of", "overloaded",
        "_bulk_shed", "_bulk_rate_exceeded", "forget", "gossip_paused",
        "_sample_commit_rate", "_effective_bulk_rate", "_peer_rate_exceeded",
        "_priority_sender_exceeded", "_storage_degraded",
    },
    # host-prep pool enqueue: called from inside the engine's batch-prep
    # window on every drain. One job alloc + one lock-free SimpleQueue
    # put — if submit ever grows a lock or a bounded wait, the pool
    # serializes the very path it exists to parallelize.
    "txflow_tpu/engine/hostprep.py": {"submit"},
    # the shaper's send sits INSIDE every switch send-loop iteration: it
    # must only draw from the seeded rng, push onto the delivery heap and
    # return — the wire wait lives in the shaper's own deliver thread.
    # A blocking call here turns weather latency into sender stall.
    "txflow_tpu/netem/shaper.py": {"send", "try_send"},
    # the accountable-gossip ledger sits on the vote-gossip receive path
    # (quarantine gate + per-frame accounting) and the engine's verdict
    # routing (invalid-origin attribution). A Byzantine flood IS the load
    # these run under — a blocking call here hands the attacker a stall
    # primitive on the exact path built to absorb them.
    "txflow_tpu/health/byzantine.py": {
        "quarantined", "note_frame", "note_invalid_origins",
        "register_peer", "note_sync_strike", "strikes_of",
        "_judge_locked", "_trip_locked",
    },
    # committee resolution sits on the vote-gossip pre-check path (the
    # reactor's StateView.committee read resolves through these on every
    # epoch swap) and inside the engine's update_state: a cache miss
    # re-samples with pure sha256 arithmetic — never a lock wait, never
    # I/O. One blocking call here stalls every gossip receive thread at
    # once at the epoch boundary.
    "txflow_tpu/committee/sampler.py": {
        "sample_committee", "committee_seed", "committee_at",
        "for_vote_height", "epoch_for_vote_height",
    },
}


class HotPathPass(LintPass):
    name = "hotpath-sync"

    def run(self, module: ModuleSource) -> list[Violation]:
        hot = _HOT_FUNCS.get(module.path, set())
        noblock = _HOT_NOBLOCK_FUNCS.get(module.path, set())
        if not hot and not noblock:
            return []
        out: list[Violation] = []
        for node in ast.walk(module.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if node.name in hot:
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Call) and isinstance(sub.func, ast.Attribute):
                        attr = sub.func.attr
                        if attr in _HOT_ATTRS:
                            out.append(
                                Violation(
                                    self.name, module.path, sub.lineno,
                                    f".{attr}() in hot function {node.name}: "
                                    f"{_HOT_ATTRS[attr]}",
                                )
                            )
            if node.name in noblock:
                for sub in ast.walk(node):
                    if not isinstance(sub, ast.Call):
                        continue
                    reason = _blocking_reason(sub, held=())
                    if reason is not None:
                        out.append(
                            Violation(
                                self.name, module.path, sub.lineno,
                                f"blocking {reason} in admit-path function "
                                f"{node.name}: the front door must shed, "
                                f"never stall",
                            )
                        )
        return out


# ---------------------------------------------------------------------------
# trace-clock
# ---------------------------------------------------------------------------

# traced hot-path scope: every module the per-tx tracer (trace/) stamps
# spans in. Timestamps here MUST come through the utils.clock seam, or a
# test that pins the clock sees half the spans on a different timeline
# and cross-node merge (trace/export.py) loses alignment. engine/ is
# scoped to the ONE traced file: execution.py keeps perf_counter for its
# untraced ABCI accounting.
_TRACE_SCOPE = (
    "txflow_tpu/engine/txflow.py",
    "txflow_tpu/engine/hostprep.py",
    # the linger controller's cadence gate shares the engine's traced
    # timeline (maybe_observe takes `now` from the caller, but any future
    # internal timestamp must come through the same seam)
    "txflow_tpu/engine/adaptive.py",
    # worker-process prep core: shard busy_s rides the done-queue acks
    # into pool stats that sit next to traced engine spans — same seam
    # so a pinned-clock test keeps both on one timeline
    "txflow_tpu/prep_proc.py",
    # staging-ring overlap ledger (hidden_s/readback_s) is compared
    # against traced device spans in report.py — same seam required
    "txflow_tpu/parallel/staging.py",
    "txflow_tpu/trace/",
    "txflow_tpu/admission/controller.py",
    "txflow_tpu/pool/",
    "txflow_tpu/reactors/",
    "txflow_tpu/sync/",
    # committee sampling + batched cert verify ride the reactor pre-check
    # and sync verify paths above — same traced timeline, same seam
    "txflow_tpu/committee/",
    # weather timestamps (due times, flap schedule) must share the traced
    # timeline: a pinned-clock test that shapes links would otherwise see
    # deliveries scheduled on a clock the spans don't use
    "txflow_tpu/netem/",
    # quarantine expiry and breaker windows live on the gossip receive
    # path's timeline: a pinned-clock drill must be able to walk a peer
    # into and out of quarantine deterministically
    "txflow_tpu/health/byzantine.py",
)

# the forbidden time.* names: every raw timestamp source. time.sleep is
# fine — pacing isn't a span timestamp.
_RAW_CLOCK_NAMES = {
    "time", "time_ns", "monotonic", "monotonic_ns",
    "perf_counter", "perf_counter_ns",
}


class TraceClockPass(LintPass):
    """No raw ``time.*`` timestamp source in a traced hot-path module.

    Flags attribute references (not just calls — passing ``time.monotonic``
    as a callback smuggles the raw clock just as effectively) and
    ``from time import ...`` of the timestamp names. The seam module
    itself (utils/clock.py) is outside the scope by construction."""

    name = "trace-clock"

    def run(self, module: ModuleSource) -> list[Violation]:
        if module.path == "txflow_tpu/utils/clock.py":
            return []  # the seam wraps the raw clock
        if not module.path.startswith(_TRACE_SCOPE):
            return []
        out: list[Violation] = []
        for node in ast.walk(module.tree):
            if (
                isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "time"
                and node.attr in _RAW_CLOCK_NAMES
            ):
                out.append(
                    Violation(
                        self.name, module.path, node.lineno,
                        f"time.{node.attr} in a traced hot-path module — "
                        "route through utils.clock so pinned-clock tests and "
                        "cross-node trace merge stay on one timeline",
                    )
                )
            elif isinstance(node, ast.ImportFrom) and node.module == "time":
                for a in node.names:
                    if a.name in _RAW_CLOCK_NAMES:
                        out.append(
                            Violation(
                                self.name, module.path, node.lineno,
                                f"from time import {a.name} in a traced "
                                "hot-path module — route through utils.clock",
                            )
                        )
        return out


# ---------------------------------------------------------------------------
# unlocked-lru
# ---------------------------------------------------------------------------


class UnlockedLRUPass(LintPass):
    """UnlockedLRUCache carries a CPython/GIL safety argument; the ONE
    place allowed to weigh it is utils.cache.make_lru."""

    name = "unlocked-lru"

    def run(self, module: ModuleSource) -> list[Violation]:
        if module.path == "txflow_tpu/utils/cache.py":
            return []
        out: list[Violation] = []
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call):
                f = node.func
                fname = _expr_str(f) if isinstance(f, (ast.Attribute, ast.Name)) else ""
                if fname.rsplit(".", 1)[-1] == "UnlockedLRUCache":
                    out.append(
                        Violation(
                            self.name, module.path, node.lineno,
                            "direct UnlockedLRUCache(...) — construct via "
                            "utils.cache.make_lru so the GIL check lives in "
                            "one place",
                        )
                    )
        return out


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------

# Module-wide implicit device->host sync hunt: hotpath-sync pins the
# enumerated engine-loop functions; this pass covers the REST of the hot
# modules, where a float()/int()/np.asarray on a device value is just as
# much a stall — it only hides better because the function isn't on the
# pipelined loop (yet). Device provenance is tracked per function:
# results of jnp.* expressions, calls to *_jit/*_fused/*_kernel names,
# and the verifier's jitted `self._fn` dispatch.
_HOSTSYNC_SCOPE = (
    "txflow_tpu/engine/",
    "txflow_tpu/ops/",
    "txflow_tpu/parallel/",
    "txflow_tpu/committee/",
    "txflow_tpu/verifier.py",
)

# sanctioned readback seams: the named functions EXIST to be the one
# blocking transfer on their path (COMPONENTS.md "Verify pipeline")
_HOSTSYNC_SEAMS = {
    # the staging ring's dedicated readback thread
    ("txflow_tpu/parallel/staging.py", "_run"),
    # the verifier's single ring-aware blocking readback
    ("txflow_tpu/verifier.py", "_force_readback"),
    # convenience host API: prepared batch in, bool[B] out, by contract
    ("txflow_tpu/ops/ed25519_batch.py", "verify_batch"),
    # certificate tally: ONE fused device call, one readback, batched
    ("txflow_tpu/committee/certverify.py", "verify_and_tally"),
}

_DEVICE_ROOTS = {"jnp"}
_DEVICE_FN_SUFFIXES = ("_jit", "_fused", "_kernel")
_DEVICE_ATTRS = {"_fn"}  # the verifier's jitted dispatch callable


def _device_producer_call(call: ast.Call) -> bool:
    f = call.func
    while isinstance(f, ast.Call):  # _kernel()(...) — unwrap to the maker
        f = f.func
    name = _expr_str(f) if isinstance(f, (ast.Attribute, ast.Name)) else ""
    if not name:
        return False
    root = name.split(".", 1)[0]
    last = name.rsplit(".", 1)[-1]
    if root in _DEVICE_ROOTS or name.startswith("jax.numpy."):
        return True
    return last.endswith(_DEVICE_FN_SUFFIXES) or last in _DEVICE_ATTRS


def _device_flavored(node: ast.AST, tainted: set[str]) -> bool:
    """True when the expression's value plausibly lives on device."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in tainted:
            return True
        if isinstance(sub, ast.Attribute):
            expr = _expr_str(sub)
            if expr.split(".", 1)[0] in _DEVICE_ROOTS or expr.startswith(
                "jax.numpy."
            ):
                return True
        if isinstance(sub, ast.Call) and _device_producer_call(sub):
            return True
    return False


class HostSyncPass(LintPass):
    """Implicit host syncs on device values in hot modules, outside the
    sanctioned StagingRing/readback seams.

    Flags, per function: ``.item()`` / ``.block_until_ready()`` /
    ``jax.device_get`` unconditionally, and ``float(x)`` / ``int(x)`` /
    ``np.asarray(x)`` when ``x`` is device-flavored (a jnp expression, a
    call to a jitted kernel, or a local bound from one)."""

    name = "host-sync"

    def run(self, module: ModuleSource) -> list[Violation]:
        if not module.path.startswith(_HOSTSYNC_SCOPE):
            return []
        hot = _HOT_FUNCS.get(module.path, set())
        out: list[Violation] = []
        for node in ast.walk(module.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if node.name in hot:
                continue  # hotpath-sync already pins these, don't double-report
            if (module.path, node.name) in _HOSTSYNC_SEAMS:
                continue
            out.extend(self._check_func(module, node))
        return out

    def _check_func(self, module: ModuleSource, fn: ast.AST) -> list[Violation]:
        tainted = self._tainted_names(fn)
        out: list[Violation] = []
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call):
                continue
            f = sub.func
            if isinstance(f, ast.Attribute):
                recv = _expr_str(f.value)
                if f.attr == "item" and not sub.args:
                    out.append(self._v(module, sub,
                                       ".item() — per-element device readback"))
                elif f.attr == "block_until_ready":
                    out.append(self._v(module, sub,
                                       ".block_until_ready() — full device sync"))
                elif f.attr == "device_get":
                    out.append(self._v(module, sub,
                                       "device_get — explicit host readback"))
                elif (
                    f.attr == "asarray"
                    and recv.split(".", 1)[0] in ("np", "numpy")
                    and sub.args
                    and _device_flavored(sub.args[0], tainted)
                ):
                    out.append(self._v(
                        module, sub,
                        "np.asarray on a device value — blocking transfer",
                    ))
            elif isinstance(f, ast.Name) and f.id in ("float", "int"):
                if sub.args and _device_flavored(sub.args[0], tainted):
                    out.append(self._v(
                        module, sub,
                        f"{f.id}() on a device value — scalar readback sync",
                    ))
        return out

    def _tainted_names(self, fn: ast.AST) -> set[str]:
        tainted: set[str] = set()
        for _ in range(4):  # tiny fixpoint: chains of assignments
            before = len(tainted)
            for sub in ast.walk(fn):
                if isinstance(sub, ast.Assign) and _device_flavored(
                    sub.value, tainted
                ):
                    for tgt in sub.targets:
                        for t in ast.walk(tgt):
                            if isinstance(t, ast.Name):
                                tainted.add(t.id)
            if len(tainted) == before:
                break
        return tainted

    def _v(self, module: ModuleSource, node: ast.AST, why: str) -> Violation:
        return Violation(
            self.name, module.path, node.lineno,
            f"{why}; route through the StagingRing/_force_readback seam "
            "or move off the hot module",
        )


# ---------------------------------------------------------------------------
# recompile-hazard
# ---------------------------------------------------------------------------

# The zero-recompile contract (engine/shapes.py): every compiled shape
# must come off the bucket ladder or the warm registry. A dispatch-site
# shape arg that doesn't provably flow from the blessed helpers is a
# latent recompile — it works until the first unbucketed batch size, then
# costs a full XLA compile mid-flight.
_SHAPE_SCOPE = (
    "txflow_tpu/verifier.py",
    "txflow_tpu/engine/shapes.py",
    "txflow_tpu/engine/txflow.py",
    "txflow_tpu/parallel/mesh.py",
    "txflow_tpu/committee/certverify.py",
)

# blessed shape sources: the ladder + prediction helpers
_SHAPE_FUNCS = {
    "bucket_size", "_generating_size", "predicted_shapes",
    "shapes_for_batch", "enumerate_shapes", "_rung",
}

# blessed shape-carrying attributes (ladder config, not raw input sizes)
_SHAPE_ATTRS = {"buckets", "max_batch", "capacity", "_n_shards"}


class RecompileHazardPass(LintPass):
    """Shape args at dispatch sinks must provably flow from the bucket
    ladder. Sinks: ``pack_step_inputs(batch, slot, B, prior, S, q)``'s
    padded sizes B and S and ``shapes_used.add(t)``'s tuple elements. Provenance propagates through assignments, BinOps
    with a ladder-derived operand (``pad = b - n``), subscripts of
    blessed attrs (``self.buckets[0]``), min/max, and conditionals."""

    name = "recompile-hazard"

    def run(self, module: ModuleSource) -> list[Violation]:
        if module.path not in _SHAPE_SCOPE:
            return []
        out: list[Violation] = []
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.extend(self._check_func(module, node))
        return out

    def _check_func(self, module: ModuleSource, fn: ast.AST) -> list[Violation]:
        safe = self._safe_names(fn)
        out: list[Violation] = []
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call):
                continue
            f = sub.func
            fname = _expr_str(f) if isinstance(f, (ast.Attribute, ast.Name)) else ""
            last = fname.rsplit(".", 1)[-1]
            if last == "pack_step_inputs" and len(sub.args) >= 5:
                if not all(self._is_safe(sub.args[i], safe) for i in (2, 4)):
                    out.append(Violation(
                        self.name, module.path, sub.lineno,
                        "pack_step_inputs size does not flow from the bucket "
                        "ladder (bucket_size/ShapeWarmRegistry) — every new "
                        "raw size is a fresh XLA compile",
                    ))
            elif (
                last == "add"
                and isinstance(f, ast.Attribute)
                and _expr_str(f.value).rsplit(".", 1)[-1] == "shapes_used"
                and sub.args
            ):
                arg = sub.args[0]
                elts = arg.elts if isinstance(arg, ast.Tuple) else [arg]
                for e in elts:
                    if not self._is_safe(e, safe):
                        out.append(Violation(
                            self.name, module.path, sub.lineno,
                            "shapes_used entry element does not flow from "
                            "the bucket ladder — the warm registry would "
                            "bank an unreachable (or unbounded) shape",
                        ))
                        break
        return out

    def _safe_names(self, fn: ast.AST) -> set[str]:
        safe: set[str] = set()
        for _ in range(6):
            before = len(safe)
            for sub in ast.walk(fn):
                if isinstance(sub, ast.Assign):
                    if self._is_safe(sub.value, safe):
                        for tgt in sub.targets:
                            for t in ast.walk(tgt):
                                if isinstance(t, ast.Name):
                                    safe.add(t.id)
                elif isinstance(sub, (ast.For,)) and self._is_safe(
                    sub.iter, safe
                ):
                    for t in ast.walk(sub.target):
                        if isinstance(t, ast.Name):
                            safe.add(t.id)
            if len(safe) == before:
                break
        return safe

    def _is_safe(self, node: ast.AST, safe: set[str]) -> bool:
        if isinstance(node, ast.Constant):
            return isinstance(node.value, (int, str))
        if isinstance(node, ast.Name):
            return node.id in safe
        if isinstance(node, ast.Attribute):
            return node.attr in _SHAPE_ATTRS
        if isinstance(node, ast.Subscript):
            return self._is_safe(node.value, safe)
        if isinstance(node, ast.UnaryOp):
            return self._is_safe(node.operand, safe)
        if isinstance(node, ast.IfExp):
            return self._is_safe(node.body, safe) and self._is_safe(
                node.orelse, safe
            )
        if isinstance(node, ast.Tuple):
            return all(self._is_safe(e, safe) for e in node.elts)
        if isinstance(node, ast.BinOp):
            # ladder provenance survives arithmetic with raw sizes
            # (pad = b - n) but a bare-constant operand does not bless
            # the other side (n + 1 is still a raw size)
            return self._ladderish(node.left, safe) or self._ladderish(
                node.right, safe
            )
        if isinstance(node, ast.Call):
            fname = (
                _expr_str(node.func)
                if isinstance(node.func, (ast.Attribute, ast.Name))
                else ""
            )
            last = fname.rsplit(".", 1)[-1]
            if last in _SHAPE_FUNCS:
                return True
            if last in ("min", "max"):
                return any(self._ladderish(a, safe) for a in node.args)
        return False

    def _ladderish(self, node: ast.AST, safe: set[str]) -> bool:
        return not isinstance(node, ast.Constant) and self._is_safe(node, safe)


# ---------------------------------------------------------------------------
# seed-domain
# ---------------------------------------------------------------------------

_DOMAINS_MODULE = "txflow_tpu/utils/domains.py"


def _domain_tag_literal(value) -> bool:
    """A bytes literal that reads as a PRNG domain tag: a pipe-separated
    domain format (not a bare joiner/suffix starting with '|') or the
    versioned txflow/ namespace."""
    if not isinstance(value, bytes):
        return False
    if value.startswith(b"txflow/"):
        return True
    return b"|" in value and not value.startswith(b"|")


class SeedDomainPass(LintPass):
    """Every PRNG domain tag lives in utils.domains (the ONE registry,
    duplicate-checked at import): an inline raw domain literal inside a
    sha256()/update() call can silently collide with a registered stream.
    The registry itself is also re-checked statically for duplicate
    literals, so a broken registry fails lint even if never imported."""

    name = "seed-domain"

    def run(self, module: ModuleSource) -> list[Violation]:
        if module.path == _DOMAINS_MODULE:
            return self._check_registry(module)
        out: list[Violation] = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            fname = _expr_str(f) if isinstance(f, (ast.Attribute, ast.Name)) else ""
            last = fname.rsplit(".", 1)[-1]
            if last not in ("sha256", "update"):
                continue
            for arg in node.args:
                for sub in ast.walk(arg):
                    if isinstance(sub, ast.Constant) and _domain_tag_literal(
                        sub.value
                    ):
                        out.append(Violation(
                            self.name, module.path, sub.lineno,
                            f"inline PRNG domain literal {sub.value!r} — "
                            "register the tag in utils.domains and import "
                            "it, so collisions fail fast in one place",
                        ))
        return out

    def _check_registry(self, module: ModuleSource) -> list[Violation]:
        out: list[Violation] = []
        names: dict[str, int] = {}
        tags: dict[bytes, int] = {}
        for node in ast.walk(module.tree):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "_register"
                and len(node.args) == 2
            ):
                continue
            nm, tag = node.args
            if isinstance(nm, ast.Constant) and isinstance(nm.value, str):
                if nm.value in names:
                    out.append(Violation(
                        self.name, module.path, node.lineno,
                        f"duplicate domain name {nm.value!r} "
                        f"(first registered line {names[nm.value]})",
                    ))
                else:
                    names[nm.value] = node.lineno
            if isinstance(tag, ast.Constant) and isinstance(tag.value, bytes):
                if tag.value in tags:
                    out.append(Violation(
                        self.name, module.path, node.lineno,
                        f"duplicate domain tag {tag.value!r} "
                        f"(first registered line {tags[tag.value]})",
                    ))
                else:
                    tags[tag.value] = node.lineno
        return out


# ---------------------------------------------------------------------------
# shared-decl
# ---------------------------------------------------------------------------

_SHARED_RE = re.compile(r"#\s*txlint:\s*shared\(([^)]*)\)")


class SharedDeclPass(LintPass):
    """Every ``shared_field(...)`` declaration carries the static intent
    annotation ``# txlint: shared(<lock>)`` naming the lock that is
    supposed to guard the field (or ``handoff`` for ownership-transfer
    protocols) — and every such annotation sits on a real declaration.
    The runtime race auditor then checks the intent against what threads
    actually held."""

    name = "shared-decl"

    def run(self, module: ModuleSource) -> list[Violation]:
        if module.path.startswith("txflow_tpu/analysis/"):
            return []  # the auditor's own docs spell the annotation
        annotations: dict[int, str] = {}
        for i, line in enumerate(module.lines, 1):
            m = _SHARED_RE.search(line)
            if m is not None:
                annotations[i] = m.group(1).strip()
        out: list[Violation] = []
        used: set[int] = set()
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            fname = _expr_str(f) if isinstance(f, (ast.Attribute, ast.Name)) else ""
            if fname.rsplit(".", 1)[-1] != "shared_field":
                continue
            span = range(node.lineno, (node.end_lineno or node.lineno) + 1)
            ann_line = next((i for i in span if i in annotations), None)
            if ann_line is None:
                out.append(Violation(
                    self.name, module.path, node.lineno,
                    "shared_field() without a `# txlint: shared(<lock>)` "
                    "annotation naming the guarding lock (or `handoff`)",
                ))
                continue
            used.add(ann_line)
            expr = annotations[ann_line]
            if expr != "handoff" and not _is_lockish(expr):
                out.append(Violation(
                    self.name, module.path, ann_line,
                    f"shared({expr}) names neither a lock-like expression "
                    "nor `handoff`",
                ))
        for i in sorted(set(annotations) - used):
            out.append(Violation(
                self.name, module.path, i,
                "dangling `# txlint: shared(...)` annotation — no "
                "shared_field() declaration on this line",
            ))
        return out
