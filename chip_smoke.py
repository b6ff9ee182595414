"""chip_smoke.py — the quickest proof that the fast path runs on the chip.

    python chip_smoke.py             # one TPU chip: phases A and B
    python chip_smoke.py --chips 4   # the mesh phase and its comparison only

One process, one chip, no child that needs the device. It exits non-zero
unless JAX reports a TPU — there is no probe in a subprocess and no retry
on the CPU — and unless every check of every phase holds. The last line of
standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``;
everything else worth reading is printed on earlier lines. Those lines are
notes, not metrics: no rate is claimed from this run.

Phase A  one ``DeviceVoteVerifier`` against the scalar golden model on one
         4,096-vote batch of really signed votes (BASELINE config 1's
         4-validator set; a quarter corrupted, some signed for another
         chain, some duplicate (validator, tx) pairs): valid masks,
         per-slot stake and maj23 identical.
Phase B  the served path: ``LocalNet(4, use_device_verifier=True,
         rpc=True)`` over a device verifier whose bucket ladder is fixed
         here (``BUCKETS``), every reachable shape warmed before traffic,
         4,096 transactions of 250 bytes over HTTP ``/broadcast_tx`` to
         the four front doors in turn, every validator signing its own
         votes. All four nodes commit all of them; every certificate is
         re-verified signature by signature on the host and carries more
         than 2/3 of the stake; committed set and kvstore content agree
         across nodes; and the chip did it — zero device failures,
         fallbacks, demotions, cold-shape votes and compiles in the
         traffic window. That traffic rides the smallest program only
         (one process serves four nodes, and its host saturates long
         before a batch outgrows 64 votes), so a second net on the same
         warmed verifier then takes 1,024 more from four concurrent
         clients under a config that holds each engine step a second
         (``coalescing_config``): held to the same guarantees and
         counters, and at least one of its batches must ride the
         4,096 rung — the engine leg at the size the flood cells of
         perfbench/ dispatch.
--chips 4  the phase-A batch through ``DeviceVoteVerifier(mesh=
         make_mesh(4))``, the single-device verifier and the scalar
         model, identical; each device held a quarter of the vote axis.

The phases are plain functions that take their sizes, so
``tests/test_chip_smoke.py`` runs them small on the CPU.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import hashlib
import http.client
import importlib.metadata
import json
import os
import sys
import time
import urllib.parse

import numpy as np

from txflow_tpu import native
from txflow_tpu.admission.config import AdmissionConfig
from txflow_tpu.engine.shapes import ShapeWarmRegistry
from txflow_tpu.node import LocalNet
from txflow_tpu.parallel.mesh import make_mesh
from txflow_tpu.types import TxVote
from txflow_tpu.types.priv_validator import MockPV
from txflow_tpu.types.validator import Validator, ValidatorSet
from txflow_tpu.utils.compile_cache import use_compile_cache
from txflow_tpu.utils.config import Config, test_config
from txflow_tpu.verifier import (
    DeviceVoteVerifier,
    ResilientVoteVerifier,
    ScalarVoteVerifier,
)

CHAIN_ID = "txflow-smoke"
# The smoke's whole ladder. Votes pad to a rung, slots (unique txs in the
# batch, never more than its votes) pad to a rung at or under it, so three
# (votes, slots) programs exist: (64, 64), (4096, 64), (4096, 4096). Each
# costs about a minute and a half of compile cold, nearly flat in size — the default
# six-rung ladder would spend the chip call compiling. 4,096 is the rung
# the benchmark's flood cells ride (perfbench/configs/*.json).
BUCKETS = (64, 4096)
N_VOTES = 4096  # phase A / mesh phase batch
N_TXS = 4096  # phase B, paced on LocalNet's default config
N_BURST = 1024  # phase B, concurrent: a 4,096-vote rung's worth of votes
TX_BYTES = 250  # Tendermint's load tool's default tx size
# Phase B, paced: txs offered but not yet committed on every node. Every tx
# puts four votes in flight, and LocalNet's default config gives each vote
# pool a 1,000-entry dedup cache: once the votes in flight outgrow it,
# relayed votes are taken for new, re-verified and relayed again, and the
# backlog feeds itself (ROADMAP Queue 2). 64 txs keep the votes in flight
# at a quarter of that cache; the concurrent part sizes the cache instead.
WINDOW = 64
# Phase B, concurrent: how long an engine holds a step for votes to
# coalesce. Four clients at the admission floor offer 160 tx/s, so a
# second gathers some hundreds of votes a node: past the 64 rung, which is
# all the 4,096 rung asks for.
HOLD_S = 1.0
MAX_SHAPES = 4


class SmokeFailure(AssertionError):
    """A check of a phase did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def note(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def baseline_validator_set(n: int = 4):
    """BASELINE config 1's set: n validators of equal power, on the keys
    LocalNet derives by default."""
    priv_vals = [
        MockPV(hashlib.sha256(b"localnet-val%d" % i).digest()) for i in range(n)
    ]
    val_set = ValidatorSet(
        [Validator.from_pub_key(pv.get_pub_key(), 10) for pv in priv_vals]
    )
    return priv_vals, val_set


def make_vote_batch(seed: int, n_votes: int, priv_vals, val_set):
    """One batch of really signed TxVotes as verifier inputs, from a seed.

    n_votes/len(priv_vals) txs, every validator voting on each. A quarter
    of the signatures are corrupted, 1/32 are signed for another chain id,
    and 1/32 of the entries are overwritten with a copy of another entry
    (duplicate (validator, tx) pairs). Returns (msgs, sigs, val_idx,
    tx_slot, n_slots)."""
    n_vals = len(priv_vals)
    n_slots = n_votes // n_vals
    check(n_slots * n_vals == n_votes, f"{n_votes} votes not a multiple of {n_vals}")
    rng = np.random.default_rng(seed)

    def pick(count: int) -> np.ndarray:
        mask = np.zeros(n_votes, bool)
        mask[rng.choice(n_votes, size=max(1, count), replace=False)] = True
        return mask

    corrupt = pick(n_votes // 4)
    foreign = pick(n_votes // 32)
    msgs, sigs, val_idx, tx_slot = [], [], [], []
    for i in range(n_votes):
        slot, vi = divmod(i, n_vals)
        pv = priv_vals[vi]
        tx_key = hashlib.sha256(b"smoke-%d-tx%d" % (seed, slot)).digest()
        vote = TxVote(
            height=0,
            tx_hash=tx_key.hex().upper(),
            tx_key=tx_key,
            timestamp_ns=1_700_000_000_000_000_000 + i,
            validator_address=pv.get_address(),
        )
        pv.sign_tx_vote("some-other-chain" if foreign[i] else CHAIN_ID, vote)
        sig = bytearray(vote.signature)
        if corrupt[i]:
            sig[int(rng.integers(64))] ^= 1 << int(rng.integers(8))
        msgs.append(vote.sign_bytes(CHAIN_ID))
        sigs.append(bytes(sig))
        # the set orders validators by address, not by key derivation
        val_idx.append(val_set.get_by_address(pv.get_address())[0])
        tx_slot.append(slot)
    for dst in np.flatnonzero(pick(n_votes // 32)):
        src = (int(dst) + 1 + int(rng.integers(n_votes - 1))) % n_votes
        msgs[dst], sigs[dst] = msgs[src], sigs[src]
        val_idx[dst], tx_slot[dst] = val_idx[src], tx_slot[src]
    return (
        msgs, sigs, np.array(val_idx, np.int64), np.array(tx_slot, np.int64),
        n_slots,
    )


def same_result(a, b, what: str) -> None:
    for name in ("valid", "stake", "maj23", "dropped"):
        x, y = np.asarray(getattr(a, name)), np.asarray(getattr(b, name))
        check(
            x.shape == y.shape and bool(np.array_equal(x, y)),
            f"{what}: {name} differs "
            f"({int(np.sum(x != y)) if x.shape == y.shape else 'shape'})",
        )


# ---------------------------------------------------------------- phase A


def phase_golden(n_votes: int = N_VOTES, buckets=BUCKETS, seed: int = 22) -> dict:
    """Device against the scalar golden model on one real-rung batch."""
    t0 = time.perf_counter()
    priv_vals, val_set = baseline_validator_set()
    batch = make_vote_batch(seed, n_votes, priv_vals, val_set)
    t_made = time.perf_counter()
    device = DeviceVoteVerifier(val_set, buckets=buckets)
    got = device.verify_and_tally(*batch)
    t_first = time.perf_counter()
    again = device.verify_and_tally(*batch)
    t_second = time.perf_counter()
    want = ScalarVoteVerifier(val_set).verify_and_tally(*batch)
    same_result(got, want, "device vs scalar")
    same_result(again, want, "device (second dispatch) vs scalar")
    valid = np.asarray(want.valid)
    maj = np.asarray(want.maj23)
    # the batch must exercise both verdicts and both quorum outcomes, or
    # parity on it proves nothing
    check(0 < valid.sum() < len(valid), "batch has no mix of valid/invalid")
    check(0 < maj.sum() < len(maj), "batch has no mix of quorum/no quorum")
    check(np.asarray(want.dropped).any(), "batch has no duplicate pairs")
    shapes = sorted(device.shapes_used.counts())
    out = {
        "votes": n_votes,
        "slots": batch[4],
        "shape": shapes,
        "valid": int(valid.sum()),
        "dropped": int(np.asarray(want.dropped).sum()),
        "maj23_slots": int(maj.sum()),
        "sign_s": round(t_made - t0, 3),
        "first_dispatch_s": round(t_first - t_made, 3),
        "second_dispatch_s": round(t_second - t_first, 3),
        "wall_s": round(time.perf_counter() - t0, 3),
    }
    note(f"phase A parity ok: {json.dumps(out)}")
    return out


# ---------------------------------------------------------------- phase B


def make_txs(seed: int, n_txs: int, tx_bytes: int) -> list[bytes]:
    """``key=value`` kvstore txs of exactly tx_bytes, from a seed."""
    txs = []
    for i in range(n_txs):
        head = b"smoke%d-k%07d=" % (seed, i)
        fill = hashlib.sha256(head).hexdigest().encode()
        body = (fill * (tx_bytes // len(fill) + 1))[: tx_bytes - len(head)]
        txs.append(head + body)
    return txs


def send_paced(addrs, txs, rate_tps: float, committed, window: int) -> dict:
    """Offer txs over HTTP ``/broadcast_tx`` to the front doors in turn,
    paced so that admission sheds none: never closer together than
    1/rate_tps, and never more than ``window`` txs ahead of
    ``committed()`` (the count every node has committed) — a backlog
    slows the host path it waits on, so an unbounded one would grow until
    the pools fill. A shed, refused or duplicate verdict fails the smoke.
    The first ten rounds go at half rate: every front door's bulk token
    bucket starts with a single token."""
    conns = [http.client.HTTPConnection(h, p, timeout=30) for h, p in addrs]
    slow = 10 * len(addrs)
    late, held = [], 0.0
    t_next = t0 = time.perf_counter()
    try:
        for i, tx in enumerate(txs):
            delay = t_next - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            t_due = time.perf_counter()
            while i - committed() >= window:
                time.sleep(0.002)
            t_send = time.perf_counter()
            held += t_send - t_due
            late.append(t_due - t_next)
            t_next = t_send + (2.0 if i < slow else 1.0) / rate_tps
            conn = conns[i % len(conns)]
            conn.request(
                "GET", "/broadcast_tx?tx=" + urllib.parse.quote("0x" + tx.hex())
            )
            resp = conn.getresponse()
            body = json.loads(resp.read())
            res = body.get("result") or {}
            check(
                resp.status == 200 and res.get("code") == 0
                and not res.get("duplicate"),
                f"tx {i} not admitted by {addrs[i % len(conns)]}: "
                f"HTTP {resp.status} {body}",
            )
    finally:
        for c in conns:
            c.close()
    return {
        "send_s": round(time.perf_counter() - t0, 3),
        "sender_late_max_ms": round(max(late) * 1e3, 2),
        "held_by_window_s": round(held, 3),
    }


def send_concurrent(addrs, txs, rate_tps: float) -> dict:
    """One client a front door, all at once, each offering its share of
    txs at its door's share of rate_tps and never waiting for a commit."""
    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(len(addrs)) as pool:
        clients = [
            pool.submit(
                send_paced, [addr], txs[k :: len(addrs)], rate_tps / len(addrs),
                committed=lambda: 0, window=len(txs),  # a window that never binds
            )
            for k, addr in enumerate(addrs)
        ]
        late = [c.result()["sender_late_max_ms"] for c in clients]
    return {
        "send_s": round(time.perf_counter() - t0, 3),
        "sender_late_max_ms": max(late),
    }


def coalescing_config(n_txs: int, n_nodes: int) -> Config:
    """LocalNet's default config, with each engine holding a step up to
    HOLD_S for votes to coalesce into one batch (the linger, and the two
    waits that would flush a partial batch the moment gossip pauses), and
    with pools and dedup caches sized for every vote of n_txs in flight
    at once."""
    cfg = test_config()
    cfg.mempool.size = max(cfg.mempool.size, 2 * n_txs * n_nodes)
    cfg.mempool.cache_size = max(cfg.mempool.cache_size, 2 * cfg.mempool.size)
    cfg.engine.coalesce_linger = HOLD_S
    cfg.engine.poll_interval = HOLD_S
    cfg.engine.idle_flush = 0.0
    return cfg


def verify_certificates(net, val_set, tx_hashes) -> dict:
    """Hold the run to the guarantees: every node's certificate for every
    tx re-verified signature by signature with the host ed25519, distinct
    validators of the set only, more than 2/3 of the stake."""
    quorum = val_set.quorum_power()  # floor(2/3 total) + 1
    verified: set = set()
    sigs_checked = 0
    for node in net.nodes:
        for h in tx_hashes:
            commit = node.tx_store.load_tx_commit(h)
            check(commit is not None and commit.commits, f"{node.node_id}: no certificate for {h}")
            stake, seen = 0, set()
            for cs in commit.commits:
                _, val = val_set.get_by_address(cs.validator_address)
                check(val is not None, f"{node.node_id} {h}: signer not in the set")
                check(cs.validator_address not in seen, f"{node.node_id} {h}: signer twice")
                check(cs.tx_hash == h, f"{node.node_id} {h}: vote for another tx")
                seen.add(cs.validator_address)
                key = (h, cs.validator_address, cs.signature, cs.timestamp_ns, cs.height)
                if key not in verified:
                    err = cs.to_vote().verify(CHAIN_ID, val.pub_key)
                    check(err is None, f"{node.node_id} {h}: {err}")
                    verified.add(key)
                    sigs_checked += 1
                stake += val.voting_power
            check(stake >= quorum, f"{node.node_id} {h}: stake {stake} < quorum {quorum}")
    return {"certificates": len(net.nodes) * len(tx_hashes), "host_verifies": sigs_checked}


def serve_and_audit(
    net, val_set, txs, commit_timeout: float, compiles, concurrent_clients: bool
) -> dict:
    """One traffic window of phase B on a started net, then the audit that
    holds the run to the guarantees. Everything read from the nodes is
    read here, before the caller stops them."""
    n_txs = len(txs)
    tx_hashes = [hashlib.sha256(tx).hexdigest().upper() for tx in txs]
    addrs = [n.rpc.addr for n in net.nodes]
    # each front door grants bulk at least bulk_rate_floor tx/s whatever
    # the commit rate does (admission/controller.py): staying under it on
    # every node is the pace at which admission sheds none
    rate_tps = 0.8 * len(net.nodes) * AdmissionConfig().bulk_rate_floor
    compiles.mark()
    t0 = time.perf_counter()
    if concurrent_clients:
        sent = send_concurrent(addrs, txs, rate_tps)
    else:
        sent = send_paced(
            addrs, txs, rate_tps,
            committed=lambda: min(
                int(n.metrics.committed_txs.value()) for n in net.nodes
            ),
            window=WINDOW,
        )
    ok = net.wait_all_committed(txs, timeout=commit_timeout)
    traffic_s = time.perf_counter() - t0
    compiles_in_traffic = compiles.since_mark()
    committed = [len(n.tx_store.committed_hashes_in_order()) for n in net.nodes]
    check(ok, f"timeout: committed per node {committed} of {n_txs}")

    t0 = time.perf_counter()
    certs = verify_certificates(net, val_set, tx_hashes)
    want_set = set(tx_hashes)
    want_kv = dict(tx.split(b"=", 1) for tx in txs)
    for node in net.nodes:
        got = node.tx_store.committed_hashes_in_order()
        check(
            len(got) == n_txs and set(got) == want_set,
            f"{node.node_id}: committed set differs from what was sent",
        )
        check(
            dict(node.app.state) == want_kv,
            f"{node.node_id}: kvstore content differs from what was sent",
        )
    stats = [n.txflow.pipeline_stats() for n in net.nodes]
    return {
        "txs": n_txs,
        "rate_cap_tps": rate_tps,
        **sent,
        "traffic_s": round(traffic_s, 3),
        "audit_s": round(time.perf_counter() - t0, 3),
        **certs,
        "engine_steps": [s["steps"] for s in stats],
        "compiles_in_traffic": compiles_in_traffic,
        "cold_fallback_votes": sum(
            s["coalesce"]["cold_fallback_votes"] for s in stats
        ),
        "prewarm_failures": sum(s["coalesce"]["prewarm_failures"] for s in stats),
        "admission_shed": sum(
            int(n.admission.metrics.rejected_overload.value()) for n in net.nodes
        ),
    }


def on_top_rung(dispatches: dict, buckets) -> int:
    """Dispatches whose vote axis was the ladder's largest rung."""
    return sum(n for (_, votes, _), n in dispatches.items() if votes == max(buckets))


def phase_served(
    n_txs: int = N_TXS,
    n_burst: int = N_BURST,
    buckets=BUCKETS,
    tx_bytes: int = TX_BYTES,
    seed: int = 22,
    wrap_device=None,
    commit_timeout: float = 600.0,
) -> dict:
    """The served path on a fixed, pre-warmed ladder: n_txs paced through
    ``LocalNet``'s default config, then n_burst from concurrent clients
    through ``coalescing_config``, on one verifier. ``wrap_device`` puts a
    wrapper between the resilience policy and the device verifier (tests
    inject ``faults.FlakyVerifier``)."""
    t_phase = time.perf_counter()
    n_nodes = 4
    priv_vals, val_set = baseline_validator_set(n_nodes)
    device = DeviceVoteVerifier(val_set, buckets=buckets)
    verifier = ResilientVoteVerifier(
        device if wrap_device is None else wrap_device(device)
    )
    registry = ShapeWarmRegistry(verifier)
    txs = make_txs(seed, n_txs + n_burst, tx_bytes)
    runs, dispatches = {}, {}
    with CompileLog() as compiles:
        # warm exactly what the ladder can reach, apart from everything else
        t0 = time.perf_counter()
        warmed = registry.prewarm(full=True)
        warm_s = time.perf_counter() - t0
        note(
            f"phase B warmed {len(warmed)} shapes in {warm_s:.1f}s "
            f"({compiles.summary()}): {warmed}"
        )
        check(len(warmed) <= MAX_SHAPES, f"{len(warmed)} shapes > {MAX_SHAPES}")
        check(
            set(warmed) == set(registry.enumerate_shapes(full=True)),
            f"warmed {warmed} != reachable {registry.enumerate_shapes(full=True)}",
        )

        for name, part, config in (
            ("paced", txs[:n_txs], None),
            ("burst", txs[n_txs:], coalescing_config(n_burst, n_nodes)),
        ):
            before = device.shapes_used.counts()
            net = LocalNet(
                n_nodes,
                chain_id=CHAIN_ID,
                use_device_verifier=True,
                rpc=True,
                priv_vals=priv_vals,
                verifier=verifier,
                config=config,
            )
            net.start()
            try:
                run = serve_and_audit(
                    net, val_set, part, commit_timeout, compiles,
                    concurrent_clients=config is not None,
                )
            finally:
                net.stop()
            dispatches[name] = {
                shape: n - before.get(shape, 0)
                for shape, n in sorted(device.shapes_used.counts().items())
            }
            run["dispatches"] = {str(k): v for k, v in dispatches[name].items()}
            runs[name] = run

    # what proves the chip did it: every one of these must be zero/empty
    zero_in_each = (
        "cold_fallback_votes", "prewarm_failures", "compiles_in_traffic",
        "admission_shed",
    )
    must_be_none = {
        "device_failures": verifier.device_failures,
        "fallback_calls": verifier.fallback_calls,
        "demotions": verifier.demotions,
        "cold_shapes": registry.cold_shapes(),
        **{k: sum(run[k] for run in runs.values()) for k in zero_in_each},
    }
    out = {
        "tx_bytes": tx_bytes,
        "warm_shapes": len(warmed),
        "warm_s": round(warm_s, 3),
        **runs,
        **must_be_none,
        "wall_s": round(time.perf_counter() - t_phase, 3),
    }
    note(f"phase B: {json.dumps(out, default=str)}")
    for name, value in must_be_none.items():
        check(not value, f"phase B: {name} = {value}, want none")
    for name, counts in dispatches.items():
        check(
            sum(counts.values()) > 0,
            f"phase B, {name}: no batch reached the device",
        )
    top = on_top_rung(dispatches["burst"], buckets)
    check(
        top > 0,
        f"phase B, burst: no batch rode the {max(buckets)} rung: "
        f"{dispatches['burst']}",
    )
    check(verifier.device_healthy, "phase B: device verifier ended demoted")
    note(
        f"phase B ok: {n_txs} + {n_burst} txs committed on all {n_nodes} "
        f"nodes, {sum(run['certificates'] for run in runs.values())} "
        "certificates re-verified on the host, zero fallbacks, demotions, "
        "cold-shape votes and in-traffic compiles, "
        f"{top} batches on the {max(buckets)} rung"
    )
    return out


# ------------------------------------------------------------- --chips 4


def phase_mesh(
    n_votes: int = N_VOTES, buckets=BUCKETS, n_chips: int = 4, seed: int = 22
) -> dict:
    """The mesh path and what it is compared with: the phase-A batch
    through the sharded verifier, the single-device verifier and the
    scalar model — identical — with each device holding 1/n_chips of the
    vote axis of the vote rows and of the packed result."""
    t0 = time.perf_counter()
    priv_vals, val_set = baseline_validator_set()
    batch = make_vote_batch(seed, n_votes, priv_vals, val_set)
    mesh = make_mesh(n_chips)  # raises when jax.devices() has fewer
    sharded = DeviceVoteVerifier(val_set, mesh=mesh, buckets=buckets)
    single = DeviceVoteVerifier(val_set, buckets=buckets)

    held = {}
    step = sharded._fn

    def spy(*args):
        out = step(*args)
        # (rows, tables, powers, slot_state): the vote rows split over
        # the mesh, the rest replicated
        held["votes"] = [
            [(s.device.id, s.data.shape[0]) for s in a.addressable_shards]
            for a in args[:1]
        ]
        held["replicated"] = [
            [(s.device.id, s.data.shape) for s in a.addressable_shards]
            for a in args[1:]
        ]
        held["packed"] = [
            (s.device.id, s.data.shape[0]) for s in out.addressable_shards
        ]
        return out

    sharded._fn = spy
    got_mesh = sharded.verify_and_tally(*batch)
    sharded._fn = step
    t_mesh = time.perf_counter()
    got_one = single.verify_and_tally(*batch)
    want = ScalarVoteVerifier(val_set).verify_and_tally(*batch)
    same_result(got_mesh, got_one, "mesh vs single device")
    same_result(got_mesh, want, "mesh vs scalar")
    same_result(got_one, want, "single device vs scalar")

    (_, b, b_slots), = sharded.shapes_used.counts()
    ids = sorted(d.id for d in mesh.devices.flat)
    check(len(ids) == n_chips, f"mesh has {len(ids)} devices, want {n_chips}")
    for shards in held["votes"]:
        check(
            sorted(d for d, _ in shards) == ids
            and all(rows == b // n_chips for _, rows in shards),
            f"vote axis not split {b}//{n_chips} over {ids}: {shards}",
        )
    for shards in held["replicated"]:
        check(
            sorted(d for d, _ in shards) == ids
            and len({shape for _, shape in shards}) == 1,
            f"epoch constants / slot state not replicated over {ids}: {shards}",
        )
    per_shard = b // n_chips + 2 * b_slots
    check(
        sorted(d for d, _ in held["packed"]) == ids
        and all(rows == per_shard for _, rows in held["packed"]),
        f"packed result not {per_shard} rows per device: {held['packed']}",
    )
    out = {
        "votes": n_votes,
        "bucket": b,
        "chips": n_chips,
        "device_ids": ids,
        "vote_rows_per_device": b // n_chips,
        "packed_rows_per_device": per_shard,
        "valid": int(np.asarray(want.valid).sum()),
        "maj23_slots": int(np.asarray(want.maj23).sum()),
        "mesh_first_dispatch_s": round(t_mesh - t0, 3),
        "wall_s": round(time.perf_counter() - t0, 3),
    }
    note(f"mesh phase ok: {json.dumps(out)}")
    return out


# ------------------------------------------------------------------ notes


class CompileLog:
    """Counts XLA backend compiles as JAX itself reports them."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self._mark = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def __enter__(self):
        return self

    def __exit__(self, *_exc) -> None:
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

    def summary(self) -> str:
        return (
            f"{self.n} backend compiles, {self.seconds:.1f}s, "
            f"{self.cache_hits} from the persistent cache"
        )


def cache_entries(path: str) -> int:
    try:
        return len(os.listdir(path))
    except FileNotFoundError:
        return 0


def result_line(platform: str, kind: str, count: int) -> str:
    """The last line of standard output: the device as JAX reports it,
    and nothing more."""
    return json.dumps(
        {"ok": True, "device": {"platform": platform, "kind": kind, "count": count}}
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 = the mesh phase and its single-device comparison only",
    )
    ap.add_argument("--seed", type=int, default=22, help="votes and txs are made from it")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
            "this script runs on the chip only — tests/test_chip_smoke.py "
            "runs its phases on the CPU",
            file=sys.stderr,
        )
        return 2
    if len(devices) < args.chips:
        print(
            f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
            f"JAX has {len(devices)}",
            file=sys.stderr,
        )
        return 2

    t_start = time.perf_counter()
    cache_dir = use_compile_cache()
    cold = cache_entries(cache_dir)
    import jaxlib

    note(
        f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
        f"libtpu {importlib.metadata.version('libtpu')} "
        f"({'; '.join(dev.client.platform_version.splitlines())})"
    )
    note(f"device {dev.platform} {dev.device_kind!r} x{len(devices)}")
    note(
        f"compile cache {cache_dir} "
        f"({'cold' if cold == 0 else f'{cold} entries'})"
    )
    t0 = time.perf_counter()
    native.rebuild()  # from prep.c + codec.c as git has them, or raise
    note(f"native prep built from source in {time.perf_counter() - t0:.1f}s")

    with CompileLog() as compiles:
        if args.chips == 4:
            phase_mesh(seed=args.seed)
        else:
            phase_golden(seed=args.seed)
            phase_served(seed=args.seed)
    note(f"host prep served by: {native.serving()}")
    note(f"compiles: {compiles.summary()}")
    note(
        f"compile cache now holds {cache_entries(cache_dir)} entries "
        f"(was {cold})"
    )
    stats = dev.memory_stats() or {}
    note(f"device peak bytes in use: {stats.get('peak_bytes_in_use', 'not reported')}")
    note(f"wall {time.perf_counter() - t_start:.1f}s")
    print(result_line(dev.platform, dev.device_kind, len(devices)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
