"""Mesh-sharded verify in the serving engine + the multi-worker host path.

Three contracts from the mesh/host-pool work:

1. a DeviceVoteVerifier over an N-way mesh (pow2 AND non-pow2, full and
   partial buckets) makes decisions byte-identical to the single-device
   and scalar golden paths — certificates included;
2. a mid-run epoch restage on a mesh verifier stays inside the prewarmed
   shape set (zero in-run compiles: restaging swaps tables/powers, never
   program shapes);
3. the host-prep pool (engine sign-bytes assembly and compact-batch prep)
   is a pure parallelization — outputs equal the serial path bit for bit.
"""

import hashlib

import numpy as np
import pytest

from test_engine import make_engine, make_pvs, sign_vote
from test_pipeline import _mixed_stream, _wait_quiescent
from test_pipeline import make_engine as make_threaded_engine
from test_verifier import make_batch, make_valset
from txflow_tpu.crypto import ed25519 as host_ed
from txflow_tpu.engine.hostprep import HostPrepPool
from txflow_tpu.engine.shapes import ShapeWarmRegistry
from txflow_tpu.engine.txflow import _BatchCoalescer
from txflow_tpu.ops import ed25519_batch
from txflow_tpu.parallel import make_mesh
from txflow_tpu.types import Validator, ValidatorSet
from txflow_tpu.verifier import (
    DeviceVoteVerifier,
    ScalarVoteVerifier,
    bucket_size,
)

BUCKETS = (32, 128)  # small ladder: CPU-sized compiles across mesh variants


# ---- verifier-level mesh parity ---------------------------------------


# tier-1 keeps the 4-way mesh (the acceptance device count) — the mesh
# case also checks the scalar and single-device paths, so [1] adds no
# coverage it lacks; every other cardinality compiles its own shapes
# (~45s each on the 1-core CI box) and rides the slow lane
@pytest.mark.parametrize(
    "n_shards",
    [
        pytest.param(1, marks=pytest.mark.slow),
        pytest.param(2, marks=pytest.mark.slow),
        pytest.param(3, marks=pytest.mark.slow),
        4,
        pytest.param(8, marks=pytest.mark.slow),
    ],
)
def test_mesh_parity_randomized(n_shards):
    """Mesh vs single-device vs scalar on an adversarial batch whose size
    is NOT shard-divisible (partial bucket: padding differs per mesh)."""
    vals, seeds = make_valset(4)
    msgs, sigs, vidx, slot = make_batch(
        vals, seeds, n_txs=7, corrupt=("ok", "flip", "ok", "wrongkey", "badidx")
    )
    # 7 txs x 4 validators = 28 votes: partial on every rung of BUCKETS
    n_slots = 7
    prior = np.array([0, 25, 0, 0, 10, 0, 0], dtype=np.int64)

    scalar = ScalarVoteVerifier(vals)
    single = DeviceVoteVerifier(vals, buckets=BUCKETS)
    mesh = make_mesh(n_shards) if n_shards > 1 else None
    sharded = DeviceVoteVerifier(vals, buckets=BUCKETS, mesh=mesh)
    assert sharded._n_shards == n_shards

    r_s = scalar.verify_and_tally(msgs, sigs, vidx, slot, n_slots, prior)
    r_1 = single.verify_and_tally(msgs, sigs, vidx, slot, n_slots, prior)
    r_n = sharded.verify_and_tally(msgs, sigs, vidx, slot, n_slots, prior)
    for r in (r_1, r_n):
        np.testing.assert_array_equal(r_s.valid, r.valid)
        np.testing.assert_array_equal(r_s.stake, r.stake.astype(np.int64))
        np.testing.assert_array_equal(r_s.maj23, r.maj23)
        np.testing.assert_array_equal(r_s.dropped, r.dropped)


def test_bucket_size_rounds_before_selecting():
    """Round-then-select: a drain sized exactly at a shard-rounded rung
    pads zero instead of spilling to the next rung (a 258-vote drain on 3
    shards is the rounded 256 bucket, not 1026)."""
    assert bucket_size(258, (256, 1024), multiple=3) == 258
    assert bucket_size(256, (256, 1024), multiple=3) == 258
    assert bucket_size(259, (256, 1024), multiple=3) == 1026
    # multiple=1 unchanged
    assert bucket_size(256, (256, 1024)) == 256
    assert bucket_size(257, (256, 1024)) == 1024
    # above the ladder: round the count itself
    assert bucket_size(1027, (256, 1024), multiple=4) == 1028


def test_coalescer_targets_round_to_shard_multiple():
    co = _BatchCoalescer((256, 1024), cap=2048, min_batch=1, linger=0.01,
                         multiple=3)
    assert co.targets == [258, 1026]
    co1 = _BatchCoalescer((256, 1024), cap=2048, min_batch=1, linger=0.01)
    assert co1.targets == [256, 1024]


# ---- engine-level certificate parity ----------------------------------


def _drain(flow):
    while flow.step():
        pass


@pytest.mark.slow
def test_mesh_engine_certificates_byte_identical():
    """Same adversarial stream through a single-device engine and a
    4-way-mesh engine (host pool on): byte-identical certificates, app
    state, and commit order."""
    import random

    rng = random.Random(7)
    pvs, vals = make_pvs(7)
    txs = [b"mesh%d=%d" % (i, i) for i in range(10)]
    stream = []
    for tx in txs:
        for vi in rng.sample(range(7), rng.randint(3, 7)):
            vote = sign_vote(pvs[vi], tx)
            if rng.random() < 0.15:
                vote.signature = bytes(64)
            stream.append(vote)
    rng.shuffle(stream)

    def run(verifier):
        flow, mem, _, pool, store, app, _ = make_engine(
            vals, verifier=verifier, max_batch=17
        )
        for tx in txs:
            mem.check_tx(tx)
        for v in stream:
            try:
                pool.check_tx(v.copy())
            except Exception:
                pass
        _drain(flow)
        return flow, store, app

    flow_1, store_1, app_1 = run(DeviceVoteVerifier(vals, buckets=BUCKETS))
    flow_m, store_m, app_m = run(
        DeviceVoteVerifier(
            vals, buckets=BUCKETS, mesh=make_mesh(4), host_prep_workers=3
        )
    )

    assert app_m.tx_count == app_1.tx_count
    assert app_m.state == app_1.state
    assert app_m.digest == app_1.digest  # commit ORDER identical
    committed = 0
    for tx in txs:
        tx_hash = hashlib.sha256(tx).hexdigest().upper()
        c1 = store_1.load_tx_commit(tx_hash)
        cm = store_m.load_tx_commit(tx_hash)
        assert (c1 is None) == (cm is None)
        if c1 is not None:
            committed += 1
            assert [
                (c.validator_address, c.signature, c.timestamp_ns)
                for c in c1.commits
            ] == [
                (c.validator_address, c.signature, c.timestamp_ns)
                for c in cm.commits
            ]
    assert committed > 0, "stream never formed a quorum — test is vacuous"
    for tx_hash, vs in flow_1.vote_sets.items():
        assert flow_m.vote_sets[tx_hash].stake() == vs.stake()


@pytest.mark.slow
@pytest.mark.parametrize("tail_votes", [4, 5], ids=["tail_short", "tail_quorum"])
def test_mesh_engine_linger_flush_parity(tail_votes):
    """Threaded coalescing engine on a 3-way mesh (non-pow2): a sub-bucket
    tail one vote short of its quorum leaves via the linger deadline, one
    that completes it leaves at once, and every decision still matches
    the scalar golden path."""
    pvs, vals = make_pvs(7)  # quorum 47 -> 5 votes needed
    txs = [b"ml%d=%d" % (i, i) for i in range(8)]
    stream = _mixed_stream(pvs, txs, seed=13)
    tail_tx = b"ml-tail=1"
    # stake 40 < 47 decides nothing; 50 >= 47 completes the quorum
    tail = [sign_vote(pv, tail_tx) for pv in pvs[:tail_votes]]

    flow_s, mem_s, _, store_s, app_s = make_threaded_engine(
        vals, use_device=False
    )
    for tx in txs + [tail_tx]:
        mem_s.check_tx(tx)
    for v in stream + tail:
        flow_s.try_add_vote(v.copy())

    verifier = DeviceVoteVerifier(vals, buckets=(8, 32), mesh=make_mesh(3))
    verifier.warmup(full=True)  # compile OUTSIDE the drain-wait windows
    flow_m, mem_m, pool_m, store_m, app_m = make_threaded_engine(
        vals,
        verifier=verifier,
        max_batch=32,
        min_batch=4,
        pipeline_depth=2,
        coalesce=True,
        coalesce_linger=0.02,
        mesh_devices=3,
    )
    for tx in txs + [tail_tx]:
        mem_m.check_tx(tx)
    flow_m.start()
    try:
        co = flow_m._coalescer
        assert co is not None and co.targets == [9, 33]  # shard-rounded
        for v in stream:
            try:
                pool_m.check_tx(v)
            except Exception:
                pass
        assert _wait_quiescent(flow_m, pool_m, timeout=90.0), (
            "mesh engine never drained"
        )
        before = (co.linger_flushes, co.quorum_flushes)
        pool_m.check_tx_many(tail)
        assert _wait_quiescent(flow_m, pool_m, timeout=90.0), (
            "tail never flushed"
        )
        if tail_votes == 4:
            assert co.linger_flushes > before[0], "tail left without a linger flush"
            assert co.quorum_flushes == before[1]
        else:
            assert co.quorum_flushes > before[1], "tail left without a quorum flush"
    finally:
        flow_m.stop()

    assert app_m.tx_count == app_s.tx_count
    assert app_m.state == app_s.state
    assert app_m.digest == app_s.digest
    for tx in txs + [tail_tx]:
        tx_hash = hashlib.sha256(tx).hexdigest().upper()
        cs = store_s.load_tx_commit(tx_hash)
        cm = store_m.load_tx_commit(tx_hash)
        assert (cs is None) == (cm is None)
        if cs is not None:
            assert [
                (c.validator_address, c.signature) for c in cs.commits
            ] == [(c.validator_address, c.signature) for c in cm.commits]


# ---- epoch restage: zero in-run compiles ------------------------------


def test_mesh_epoch_restage_zero_recompile():
    """Prewarm a mesh verifier, verify, rotate the validator set mid-run
    (same cardinality: an epoch rotation), verify again — every dispatch
    stays inside the prewarmed shape set."""
    vals, seeds = make_valset(4)
    # single-rung ladder: full prewarm is ONE mesh-4 shape — ("fused",
    # 32, 32), the same shape test_mesh_parity_randomized[4] compiles, so
    # in-suite this test rides that jit cache instead of paying 3 compiles
    verifier = DeviceVoteVerifier(vals, buckets=(32,), mesh=make_mesh(4))
    registry = ShapeWarmRegistry(verifier)
    registry.prewarm(full=True)

    msgs, sigs, vidx, slot = make_batch(vals, seeds, n_txs=6)
    r1 = verifier.verify_and_tally(msgs, sigs, vidx, slot, 6)
    assert r1.valid.any()

    # rotation: 4 NEW keys, same set size -> same table/power shapes
    new_seeds = [hashlib.sha256(b"rot%d" % i).digest() for i in range(4)]
    new_pubs = [host_ed.public_key_from_seed(s) for s in new_seeds]
    new_vals = ValidatorSet(
        [Validator.from_pub_key(p, 10) for p in new_pubs]
    )
    seed_by_pub = dict(zip(new_pubs, new_seeds))
    new_seeds = [seed_by_pub[v.pub_key] for v in new_vals.validators]
    assert verifier.restage(new_vals)

    msgs2, sigs2, vidx2, slot2 = make_batch(new_vals, new_seeds, n_txs=5)
    r2 = verifier.verify_and_tally(msgs2, sigs2, vidx2, slot2, 5)
    scalar = ScalarVoteVerifier(new_vals)
    r2_s = scalar.verify_and_tally(msgs2, sigs2, vidx2, slot2, 5)
    np.testing.assert_array_equal(r2_s.valid, r2.valid)
    np.testing.assert_array_equal(r2_s.stake, r2.stake.astype(np.int64))

    assert registry.cold_shapes() == [], (
        "epoch restage compiled a new shape mid-run"
    )


# ---- host-prep pool parity --------------------------------------------


def test_host_pool_compact_prep_parity():
    """Pooled prepare_compact == serial prepare_compact, field for field,
    at a size above the pool threshold and with adversarial rows."""
    vals, seeds = make_valset(4)
    n = 600  # > _POOL_MIN_ROWS, not worker-divisible
    msgs, sigs, vidx, _ = make_batch(
        vals, seeds, n_txs=150, corrupt=("ok", "flip", "wrongkey", "badidx")
    )
    msgs, sigs, vidx = msgs[:n], sigs[:n], vidx[:n]
    epoch = ed25519_batch.EpochTables([v.pub_key for v in vals.validators])

    serial = ed25519_batch.prepare_compact(msgs, sigs, vidx, epoch)
    pool = HostPrepPool(4, name="hostprep-test")
    try:
        pooled = ed25519_batch.prepare_compact(
            msgs, sigs, vidx, epoch, pool=pool
        )
        stats = pool.stats()
        assert stats["jobs_total"] > 0, "pool never ran a shard"
    finally:
        pool.close()
    for field in ("s_nibbles", "h_nibbles", "val_idx", "r_y", "r_sign",
                  "pre_ok"):
        np.testing.assert_array_equal(
            getattr(serial, field), getattr(pooled, field), err_msg=field
        )


def test_engine_pooled_sign_assembly_parity():
    """A >=256-vote drain through an engine with host_prep_workers set
    takes the pooled sign-bytes assembly and still matches the scalar
    golden path."""
    pvs, vals = make_pvs(4)
    txs = [b"hp%d=%d" % (i, i) for i in range(80)]  # 80*4 = 320 votes
    stream = [sign_vote(pv, tx) for tx in txs for pv in pvs]

    flow_s, mem_s, _, store_s, app_s = make_threaded_engine(
        vals, use_device=False
    )
    for tx in txs:
        mem_s.check_tx(tx)
    for v in stream:
        flow_s.try_add_vote(v.copy())

    flow_p, mem_p, pool_p, store_p, app_p = make_threaded_engine(
        vals, use_device=False, host_prep_workers=4, max_batch=1024
    )
    for tx in txs:
        mem_p.check_tx(tx)
    for v in stream:  # queue the whole corpus BEFORE start: one big drain
        pool_p.check_tx(v)
    flow_p.start()
    try:
        assert _wait_quiescent(flow_p, pool_p), "pooled engine never drained"
        # capture BEFORE stop(): an engine-owned pool is closed and nulled
        # on stop (bench/profile_host read pipeline_stats pre-stop too)
        stats = flow_p.pipeline_stats()
        assert flow_p._host_pool is not None
        pool_stats = flow_p._host_pool.stats()
    finally:
        flow_p.stop()

    assert stats["host_prep_workers"] == 4
    assert pool_stats["jobs_total"] > 0, (
        "drain never took the pooled assembly path"
    )
    assert app_p.tx_count == app_s.tx_count
    assert app_p.state == app_s.state
    assert app_p.digest == app_s.digest
    for tx in txs:
        tx_hash = hashlib.sha256(tx).hexdigest().upper()
        cs = store_s.load_tx_commit(tx_hash)
        cp = store_p.load_tx_commit(tx_hash)
        assert cs is not None and cp is not None
        assert [
            (c.validator_address, c.signature) for c in cs.commits
        ] == [(c.validator_address, c.signature) for c in cp.commits]
