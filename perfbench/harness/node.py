"""The system under test: one validator node on one chip.

The only file of the benchmark that imports the program. It builds one
full ``Node`` (``LocalNet(n_validators, n_nodes=1)``: RPC front door,
admission, mempool, sign walk, vote pool, engine, ``DeviceVoteVerifier``,
commit to the TxStore and the kvstore app), warms the shapes a cell names,
reads the program's own counters, and hands answers to the reference as
plain tuples. The other validators are peers played by the harness.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from txflow_tpu import native
from txflow_tpu.abci.kvstore import KVStoreApplication
from txflow_tpu.engine.shapes import ShapeWarmRegistry
from txflow_tpu.node import LocalNet
from txflow_tpu.pool.mempool import TxInfo
from txflow_tpu.types import TxVote
from txflow_tpu.types.priv_validator import MockPV
from txflow_tpu.types.validator import Validator, ValidatorSet
from txflow_tpu.utils.compile_cache import use_compile_cache
from txflow_tpu.utils.config import test_config
from txflow_tpu.utils.events import EventTx
from txflow_tpu.verifier import (
    DeviceVoteVerifier,
    ResilientVoteVerifier,
    ScalarVoteVerifier,
)

from . import corpus as corpus_mod

FAULTS = ("accept_all", "reject_some", "app_corrupt")


class CompileLog:
    """Counts XLA backend compiles as JAX itself reports them (copied from
    chip_smoke.py, PR 22)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self._mark = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **_kw) -> None:
        if event == self.EVENT:
            self.n += 1
            self.seconds += seconds

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> None:
        self._mark = self.n

    def since_mark(self) -> int:
        return self.n - self._mark


class _FaultTicket:
    def __init__(self, inner, alter):
        self._inner, self._alter = inner, alter

    def result(self):
        return self._alter(self._inner.result())


class FaultyVerifier:
    """The timed path broken underneath, for the control and the tests:
    a verdict altered where it is produced. ``accept_all`` calls every
    vote valid that is not an in-batch repeat; ``reject_some`` calls
    invalid every vote of a tx in every fourth slot."""

    def __init__(self, inner, fault: str):
        self.inner = inner
        self.fault = fault

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def _alter(self, tx_slot):
        slots = np.asarray(tx_slot)

        def alter(result):
            valid = np.array(result.valid, copy=True)
            if self.fault == "accept_all":
                valid |= ~np.asarray(result.dropped)
            else:
                valid &= (slots[: len(valid)] % 4) != 0
            return dataclasses.replace(result, valid=valid)

        return alter

    def submit(self, msgs, sigs, val_idx, tx_slot, n_slots, **kw):
        ticket = self.inner.submit(msgs, sigs, val_idx, tx_slot, n_slots, **kw)
        return _FaultTicket(ticket, self._alter(tx_slot))

    def verify_and_tally(self, msgs, sigs, val_idx, tx_slot, n_slots, **kw):
        return self.submit(msgs, sigs, val_idx, tx_slot, n_slots, **kw).result()


class _RecTicket:
    def __init__(self, inner, rec):
        self._inner, self._rec = inner, rec

    def result(self):
        self._rec["t_collect_begin"] = time.monotonic()
        out = self._inner.result()
        self._rec["t_collect_end"] = time.monotonic()
        self._rec["valid"] = int(np.asarray(out.valid).sum())
        return out


class StepRecorder:
    """The flood study's per-step timeline: one record per engine step,
    taken around the verifier's submit and collect. Only a run with
    ``--timeline`` has it in the path."""

    def __init__(self, inner):
        self.inner = inner
        self.steps: list[dict] = []
        self.pipeline = None  # set once the node exists

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def submit(self, msgs, sigs, val_idx, tx_slot, n_slots, **kw):
        rec = {"t_submit_begin": time.monotonic(), "votes": len(msgs), "slots": int(n_slots)}
        if self.pipeline is not None:
            stats = self.pipeline()
            rec.update(prep_s=stats["prep_s"], route_s=stats["route_s"],
                       dispatch_wait_s=stats["dispatch_wait_s"])
        ticket = self.inner.submit(msgs, sigs, val_idx, tx_slot, n_slots, **kw)
        rec["t_submit_end"] = time.monotonic()
        self.steps.append(rec)
        return _RecTicket(ticket, rec)

    def verify_and_tally(self, msgs, sigs, val_idx, tx_slot, n_slots, **kw):
        return self.submit(msgs, sigs, val_idx, tx_slot, n_slots, **kw).result()

    def write(self, path: str, t_open: float, t_close: float, commits, gc_pauses, feeds) -> None:
        """One JSON object: times in seconds from the window's opening."""
        import json

        def rel(t):
            return round(t - t_open, 6)

        steps = []
        for s in self.steps:
            row = {k: (rel(v) if k.startswith("t_") else v) for k, v in s.items()}
            lo, hi = s["t_submit_begin"], s.get("t_collect_end", s["t_submit_end"])
            row["commits_until_collect"] = sum(1 for t in commits if t <= hi)
            steps.append(row)
        with open(path, "w") as f:
            json.dump({
                "window_s": t_close - t_open,
                "steps": steps,
                "gc_pauses": [[rel(t), g, round(s, 6)] for t, g, s in gc_pauses],
                "feeds": [[rel(t), n] for t, n in feeds],
                "commits_per_100ms": _histogram(commits, t_open, t_close, 0.1),
            }, f)


def _histogram(times, t0: float, t1: float, width: float) -> list[int]:
    bins = [0] * max(1, int((t1 - t0) / width + 0.999))
    for t in times:
        if t0 <= t < t1:
            bins[min(int((t - t0) / width), len(bins) - 1)] += 1
    return bins


class _CorruptingApp(KVStoreApplication):
    """The commit broken underneath: every eighth key gets another value."""

    def deliver_tx(self, tx: bytes):
        if tx[-1] % 8 == 0:
            key, _, value = tx.partition(b"=")
            tx = key + b"=" + value[:-1] + b"!"
        return super().deliver_tx(tx)


_oset = object.__setattr__


def _vote(tx_hash: str, tx_key: bytes, timestamp_ns: int, addr: bytes, sig: bytes) -> TxVote:
    """A signed TxVote as the wire decoder would leave it, built the way
    ``TxVote.copy`` builds one: the dataclass constructor goes through a
    ``__setattr__`` that clears four caches on each of six fields, thirty
    calls a vote, and the feeder shares the node's interpreter lock."""
    v = TxVote.__new__(TxVote)
    _oset(v, "height", 0)
    _oset(v, "tx_hash", tx_hash)
    _oset(v, "tx_key", tx_key)
    _oset(v, "timestamp_ns", timestamp_ns)
    _oset(v, "validator_address", addr)
    _oset(v, "signature", sig)
    _oset(v, "_sb_cache", None)
    _oset(v, "_wire_cache", None)
    _oset(v, "_vk_cache", None)
    _oset(v, "_seg_cache", None)
    return v


class SystemUnderTest:
    def __init__(self, config: dict, traffic: dict, *, rungs, sign: bool, rpc: bool,
                 scalar: bool = False, fault: str | None = None,
                 trace_all: bool = False, record: bool = False):
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.config = config
        n_vals = int(config["validators"])
        key_seed = config["assumed"]["key_seed"]
        self.priv_vals = [
            MockPV(corpus_mod.validator_seed(key_seed, v)) for v in range(n_vals)
        ]
        cfg = test_config()
        for key, value in (traffic.get("engine") or {}).items():
            if not hasattr(cfg.engine, key):
                raise KeyError(f"engine has no setting {key!r}")
            setattr(cfg.engine, key, value)
        pools = traffic.get("pools") or {}
        cfg.mempool.size = max(cfg.mempool.size, int(pools.get("size", 0)))
        cfg.mempool.cache_size = max(cfg.mempool.cache_size, int(pools.get("cache_size", 0)))
        if trace_all:
            cfg.trace.sample_rate = 1
            cfg.trace.ring_capacity = 1 << 18
        self.compiles = None
        self.device = None
        self.registry = None
        self.cache_dir = None
        powers = corpus_mod.powers_of(config)
        self.val_set = ValidatorSet([
            Validator.from_pub_key(pv.get_pub_key(), power)
            for pv, power in zip(self.priv_vals, powers)
        ])
        if scalar:
            verifier = ScalarVoteVerifier(self.val_set)
            verifier.buckets = tuple(rungs)  # the coalescer reads the ladder
        else:
            self.cache_dir = use_compile_cache()
            native.rebuild()  # from the sources git has, or raise
            self.compiles = CompileLog()
            self.device = DeviceVoteVerifier(self.val_set, buckets=tuple(rungs))
            verifier = ResilientVoteVerifier(self.device)
            self.registry = ShapeWarmRegistry(verifier)
        self.resilient = verifier
        if fault in ("accept_all", "reject_some"):
            verifier = FaultyVerifier(verifier, fault)
        self.recorder = None
        if record:
            verifier = self.recorder = StepRecorder(verifier)
        self.net = LocalNet(
            n_vals, chain_id=config["chain_id"], priv_vals=self.priv_vals,
            voting_powers=powers, config=cfg,
            use_device_verifier=not scalar, verifier=verifier, sign=sign,
            mempool_broadcast=False, enable_consensus=False, rpc=rpc,
            index_txs=False, n_nodes=1,
            app_factory=_CorruptingApp if fault == "app_corrupt" else KVStoreApplication,
        )
        self.node = self.net.nodes[0]
        if self.recorder is not None:
            self.recorder.pipeline = self.node.txflow.pipeline_stats
        self.on_commit = None  # a hook of the traffic driver's
        self.commit_times: list[float] = []
        self.node.event_bus.subscribe_callback(EventTx, self._on_commit)
        self._addr = [pv.get_address() for pv in self.priv_vals]
        self._started = False

    # -- lifecycle --

    def warm(self, shapes) -> float:
        """Compile or load exactly the programs a cell names, one after
        the other (in threads of their own they took 90 s where in turn
        they take 57 s: my chip run, PR 27)."""
        if self.registry is None:
            return 0.0
        t0 = time.monotonic()
        for kind, votes, slots in shapes:
            if not self.registry.warm_shape((kind, int(votes), int(slots))):
                raise RuntimeError(f"could not warm shape {(kind, votes, slots)}")
        return time.monotonic() - t0

    def start(self) -> None:
        self.net.start()
        self._started = True

    def stop(self) -> None:
        if self._started:
            self.net.stop()
            self._started = False
        if self.compiles is not None:
            self.compiles.close()
            self.compiles = None

    def _on_commit(self, _ev) -> None:
        self.commit_times.append(time.monotonic())
        hook = self.on_commit
        if hook is not None:
            hook()

    # -- traffic in, as a peer's gossip delivers it --

    def seed_txs(self, txs: list[bytes]) -> None:
        for err in self.node.mempool.check_tx_many(txs):
            if err is not None:
                raise RuntimeError(f"mempool refused a tx: {err!r}")

    def deliver_votes(self, corpus, k: int, lo: int, hi: int, sender: int) -> None:
        """Signer k's votes on txs [lo, hi) into the vote pool's ingest."""
        v = corpus.signer_idx[k]
        addr = self._addr[v]
        keys, sigs = corpus.tx_keys, corpus.sigs[k]
        ts0 = corpus_mod.vote_timestamp(0, corpus.n_vals, v)
        n_vals = corpus.n_vals
        votes = []
        for i in range(lo, hi):
            key = keys[32 * i : 32 * i + 32]
            votes.append(_vote(key.hex().upper(), key, ts0 + i * n_vals, addr,
                               sigs[64 * i : 64 * i + 64]))
        self._ingest(votes, sender)

    def deliver_tx_votes(self, corpus, i: int, signers, sender: int) -> None:
        """The votes on tx i of ``signers``, (place in the corpus, validator)
        pairs made in set-up, in one frame from one relaying peer."""
        key = corpus.tx_key(i)
        hx = key.hex().upper()
        n_vals = corpus.n_vals
        self._ingest([
            _vote(hx, key, corpus_mod.vote_timestamp(i, n_vals, v), self._addr[v],
                  corpus.sig(k, i))
            for k, v in signers
        ], sender)

    def _ingest(self, votes, sender: int) -> None:
        for err in self.node.tx_vote_pool.check_tx_many(votes, TxInfo(sender)):
            if err is not None:
                raise RuntimeError(f"vote pool refused a vote: {err!r}")

    # -- counters and answers out --

    def committed(self) -> int:
        return len(self.commit_times)

    def pipeline(self) -> dict:
        return self.node.txflow.pipeline_stats()

    def counters(self) -> dict:
        """Every counter the program keeps, whole, by the layer that keeps
        it: a kind reads it as the window opens and as it closes, and a
        reader takes what it needs from ``ctx["counters"]``."""
        return {
            "pipeline": self.node.txflow.pipeline_stats(),
            "ingest": self.node.tx_vote_pool.ingest_stats(),
        }

    def dispatches(self) -> dict:
        if self.device is None:
            return {}
        return {
            f"{votes}x{slots}": n
            for (_, votes, slots), n in self.device.shapes_used.counts().items()
        }

    def routed_votes(self) -> int:
        """Votes in the batches the engine has routed so far, valid or
        not: the sum of the ``batch_size`` histogram, read from its
        exposition (the histogram has no accessor)."""
        hist = self.node.metrics.batch_size
        for line in hist.expose().splitlines():
            if line.startswith(hist.name + "_sum "):
                return int(float(line.split()[1]))
        raise RuntimeError("the batch_size histogram exposes no sum")

    def faults(self) -> dict:
        """What must be nought in a window served by the chip."""
        stats = self.pipeline()["coalesce"]
        out = {
            "cold_fallback_votes": stats["cold_fallback_votes"],
            "prewarm_failures": stats["prewarm_failures"],
        }
        r = self.resilient
        if isinstance(r, ResilientVoteVerifier):
            out.update(
                device_failures=r.device_failures, fallback_calls=r.fallback_calls,
                demotions=r.demotions, device_unhealthy=int(not r.device_healthy),
            )
        if self.registry is not None:
            out["cold_shapes"] = len(self.registry.cold_shapes())
        if self.compiles is not None:
            out["compiles_in_window"] = self.compiles.since_mark()
        return out

    def admission_shed(self) -> int:
        return int(self.node.admission.metrics.rejected_overload.value())

    def spans(self, name: str, t0: float, t1: float) -> list[float]:
        """Durations (s) of the program's txtrace spans of one family
        that began in [t0, t1) (the tracer's clock is time.monotonic)."""
        return [
            s["end"] - s["start"] for s in self.node.tracer.spans()
            if s["name"] == name and t0 <= s["start"] < t1
        ]

    def answer(self, corpus, i: int):
        """(certificate rows, stored tx bytes, app value) for tx i."""
        hx = corpus.tx_key(i).hex().upper()
        commit = self.node.tx_store.load_tx_commit(hx)
        rows = None
        if commit is not None and commit.commits:
            rows = [
                (cs.validator_address, cs.signature, cs.timestamp_ns, cs.height, cs.tx_hash)
                for cs in commit.commits
            ]
        key = corpus.tx(i).partition(b"=")[0]
        return rows, self.node.tx_store.load_tx_bytes(hx), self.node.app.state.get(key)

    def host_prep(self) -> str:
        return native.serving()
