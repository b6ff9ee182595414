"""VoteVerifier parity: device (and sharded-device) vs the scalar golden model.

Mirrors the reference's quorum tests (types/vote_set_test.go) at the batch
level, plus BASELINE config 4's adversarial mix: honest votes, corrupted
signatures, wrong-key signatures, off-range validator indices, and padding.
Commit decisions must be bit-identical across all three implementations.
"""

import hashlib

import numpy as np
import pytest

from txflow_tpu.crypto import ed25519 as host_ed
from txflow_tpu.parallel import make_mesh
from txflow_tpu.types import TxVote, Validator, ValidatorSet, canonical_sign_bytes
from txflow_tpu.verifier import (
    DeviceVoteVerifier,
    ScalarVoteVerifier,
    bucket_size,
    first_occurrence_mask,
)

CHAIN_ID = "txflow-test"


def make_valset(n, power=10):
    seeds = [hashlib.sha256(b"val%d" % i).digest() for i in range(n)]
    pubs = [host_ed.public_key_from_seed(s) for s in seeds]
    vals = ValidatorSet([Validator.from_pub_key(p, power) for p in pubs])
    # map validator order back to seeds (ValidatorSet sorts by address)
    seed_by_pub = dict(zip(pubs, seeds))
    return vals, [seed_by_pub[v.pub_key] for v in vals]


def make_batch(vals, seeds, n_txs, corrupt=()):
    """One vote per (tx, validator); corrupt[i] flavors in arrival order."""
    msgs, sigs, vidx, slot = [], [], [], []
    k = 0
    for t in range(n_txs):
        tx_hash = hashlib.sha256(b"tx%d" % t).hexdigest().upper()
        tx_key = hashlib.sha256(b"key%d" % t).digest()
        for vi in range(len(seeds)):
            msg = canonical_sign_bytes(CHAIN_ID, 1, tx_hash, 1700000000_000000000 + t)
            sig = host_ed.sign(seeds[vi], msg)
            mode = corrupt[k % len(corrupt)] if corrupt else "ok"
            if mode == "flip":
                sig = sig[:10] + bytes([sig[10] ^ 1]) + sig[11:]
            elif mode == "wrongkey":
                sig = host_ed.sign(seeds[(vi + 1) % len(seeds)], msg)
            elif mode == "badidx":
                vidx.append(len(seeds) + 5)
                msgs.append(msg), sigs.append(sig), slot.append(t)
                k += 1
                continue
            msgs.append(msg), sigs.append(sig), vidx.append(vi), slot.append(t)
            k += 1
    return msgs, sigs, np.array(vidx), np.array(slot)


@pytest.fixture(scope="module")
def valset4():
    return make_valset(4)


def assert_parity(vals, msgs, sigs, vidx, slot, n_slots, prior=None, device=None):
    scalar = ScalarVoteVerifier(vals)
    device = device or DeviceVoteVerifier(vals)
    r_s = scalar.verify_and_tally(msgs, sigs, vidx, slot, n_slots, prior)
    r_d = device.verify_and_tally(msgs, sigs, vidx, slot, n_slots, prior)
    np.testing.assert_array_equal(r_s.valid, r_d.valid)
    np.testing.assert_array_equal(r_s.stake, r_d.stake.astype(np.int64))
    np.testing.assert_array_equal(r_s.maj23, r_d.maj23)
    np.testing.assert_array_equal(r_s.dropped, r_d.dropped)
    return r_s


def test_all_honest_quorum(valset4):
    vals, seeds = valset4
    msgs, sigs, vidx, slot = make_batch(vals, seeds, n_txs=3)
    r = assert_parity(vals, msgs, sigs, vidx, slot, n_slots=3)
    assert r.valid.all()
    assert r.maj23.all()
    assert (r.stake == vals.total_voting_power()).all()


def test_adversarial_mix(valset4):
    vals, seeds = valset4
    msgs, sigs, vidx, slot = make_batch(
        vals, seeds, n_txs=4, corrupt=("ok", "flip", "wrongkey", "badidx")
    )
    r = assert_parity(vals, msgs, sigs, vidx, slot, n_slots=4)
    assert not r.valid.all() and r.valid.any()
    # with only 1-2 of 4 honest votes per tx, no quorum anywhere
    assert not r.maj23.any()


def test_prior_stake_latches_quorum(valset4):
    """Quorum accumulates across batches exactly like the incremental reference."""
    vals, seeds = valset4
    msgs, sigs, vidx, slot = make_batch(vals, seeds, n_txs=1)
    # batch 1: two honest votes -> 20/40 stake, below quorum (27)
    r1 = assert_parity(vals, msgs[:2], sigs[:2], vidx[:2], slot[:2], 1)
    assert not r1.maj23[0] and r1.stake[0] == 20
    # batch 2: one more vote on top of prior -> 30 >= 27
    r2 = assert_parity(vals, msgs[2:3], sigs[2:3], vidx[2:3], slot[2:3], 1, prior=r1.stake)
    assert r2.maj23[0] and r2.stake[0] == 30


@pytest.mark.slow  # 8-way mesh compile: ~80s on the 1-core CPU CI box
def test_sharded_matches_single_device(valset4):
    vals, seeds = valset4
    mesh = make_mesh(8)
    msgs, sigs, vidx, slot = make_batch(
        vals, seeds, n_txs=5, corrupt=("ok", "ok", "flip")
    )
    sharded = DeviceVoteVerifier(vals, mesh=mesh)
    single = DeviceVoteVerifier(vals)
    r_m = sharded.verify_and_tally(msgs, sigs, vidx, slot, 5)
    r_1 = single.verify_and_tally(msgs, sigs, vidx, slot, 5)
    np.testing.assert_array_equal(r_m.valid, r_1.valid)
    np.testing.assert_array_equal(r_m.stake, r_1.stake)
    np.testing.assert_array_equal(r_m.maj23, r_1.maj23)


def test_replayed_vote_not_double_counted(valset4):
    """A (tx, validator) pair repeated in one batch contributes power once.

    The reference can never double-count one validator's stake
    (first-signature-wins, types/vote_set.go:109-131); an adversary
    replaying one honest vote must not be able to fake a quorum.
    """
    vals, seeds = valset4
    msgs, sigs, vidx, slot = make_batch(vals, seeds, n_txs=1)
    # one honest vote replayed 3x + one fresh honest vote = 2 real voters
    m = [msgs[0]] * 3 + [msgs[1]]
    s = [sigs[0]] * 3 + [sigs[1]]
    vi = np.array([vidx[0]] * 3 + [vidx[1]])
    sl = np.array([0, 0, 0, 0])
    r = assert_parity(vals, m, s, vi, sl, n_slots=1)
    assert r.stake[0] == 20 and not r.maj23[0]
    np.testing.assert_array_equal(r.dropped, [False, True, True, False])
    assert r.valid.tolist() == [True, False, False, True]


def test_bucket_size():
    assert bucket_size(1) == 64
    assert bucket_size(64) == 64
    assert bucket_size(65) == 256
    assert bucket_size(70000, multiple=8) == 70000
    assert bucket_size(70001, multiple=8) == 70008


@pytest.mark.slow  # two 8-way mesh compiles: ~60s on the 1-core CPU CI box
def test_ring_tally_matches_psum_step():
    """The explicit ppermute ring all-reduce must produce bit-identical
    tallies to the psum formulation over the virtual mesh."""
    from txflow_tpu.ops import ed25519_batch, tally
    from txflow_tpu.parallel.mesh import (
        sharded_compact_step_packed_cached,
        sharded_ring_step,
    )

    vals, seeds = make_valset(4)
    epoch = ed25519_batch.EpochTables([v.pub_key for v in vals])
    msgs, sigs, vidx, slot = make_batch(
        vals, seeds, n_txs=4, corrupt=("ok", "ok", "flip")
    )
    batch = ed25519_batch.prepare_compact(msgs, sigs, vidx, epoch)
    b = batch.size + (-batch.size) % 8
    n_slots = 4
    rows, slot_state = tally.pack_step_inputs(
        batch, np.asarray(slot, np.int32), b, None, n_slots, vals.quorum_power()
    )
    args = (rows, epoch.tables, vals.powers_array().astype(np.int32), slot_state)
    mesh = make_mesh(8)
    packed = np.asarray(sharded_compact_step_packed_cached(mesh)(*args)).reshape(8, -1)
    bs = b // 8
    valid, ring_stake, ring_maj = (np.asarray(x) for x in sharded_ring_step(mesh)(*args))
    np.testing.assert_array_equal(packed[:, :bs].reshape(-1).astype(bool), valid)
    # ring outputs are per-shard copies of the global: every shard's slice
    # must equal the psum-replicated global
    stake = ring_stake.reshape(8, -1)
    maj = ring_maj.reshape(8, -1)
    for sh in range(8):
        np.testing.assert_array_equal(stake[sh], packed[0, bs : bs + n_slots])
        np.testing.assert_array_equal(maj[sh], packed[0, bs + n_slots :].astype(bool))


@pytest.mark.parametrize("nv", [16, 64])
def test_large_validator_set_parity(nv):
    """Device/scalar parity at BASELINE configs 2-3 validator counts (the
    [V,16,4,NLIMB] epoch-table gather at V=16/64 — the shapes the TPU
    bench sweeps; adversarial mix included)."""
    vals, seeds = make_valset(nv)
    msgs, sigs, vidx, slot = make_batch(
        vals, seeds, n_txs=3, corrupt=("ok", "flip", "ok", "wrongkey")
    )
    assert_parity(vals, msgs, sigs, vidx, slot, 3)


# ---- the one device path: first occurrences, rung edges, predicted shapes ----

LADDER = (8, 32)  # two CPU-sized rungs (the ladder of test_staging_ring)
R = LADDER[0]


@pytest.fixture(scope="module")
def ladder_verifier(valset4):
    return DeviceVoteVerifier(valset4[0], buckets=LADDER)


@pytest.mark.parametrize(
    "slot,val",
    [
        pytest.param([], [], id="empty"),
        pytest.param([0, 1, 0, 2, 1, 0], [0, 0, 0, 1, 0, 1], id="dense"),
        pytest.param([0, 10**9, 0, 5 * 10**8, 10**9], [3, 1, 3, 2, 1], id="sort-branch"),
        pytest.param([0, 0, 1, 1, 0, 1], [-1, 7, -1, 9, -1, 9], id="off-range-validators"),
        pytest.param([4] * 6, [2] * 6, id="all-repeats-of-first"),
    ],
)
def test_first_occurrence_mask_matches_dict_reference(slot, val):
    seen, want = {}, []
    for pair in zip(slot, val):
        want.append(pair not in seen)
        seen[pair] = True
    got = first_occurrence_mask(np.array(slot, np.int64), np.array(val, np.int64))
    assert got.dtype == bool
    assert got.tolist() == want


@pytest.mark.parametrize("n", [1, R - 1, R, R + 1])
def test_fused_parity_at_rung_edges(valset4, ladder_verifier, n):
    """Either side of a rung's edge the device agrees with the golden
    model row for row: padding rows never tally and never come back."""
    vals, seeds = valset4
    msgs, sigs, vidx, slot = make_batch(vals, seeds, n_txs=3)
    msgs, sigs, vidx, slot = msgs[:n], sigs[:n], vidx[:n], slot[:n]
    sigs[0] = sigs[0][:10] + bytes([sigs[0][10] ^ 1]) + sigs[0][11:]
    n_slots = int(slot.max()) + 1
    r = assert_parity(vals, msgs, sigs, vidx, slot, n_slots, device=ladder_verifier)
    assert len(r.valid) == len(r.dropped) == n
    assert len(r.stake) == len(r.maj23) == n_slots
    assert r.valid.tolist() == [False] + [True] * (n - 1)
    assert int(r.stake.sum()) == 10 * (n - 1)


@pytest.mark.parametrize("n,n_slots", [(1, 1), (R, R), (R + 1, 1), (R + 1, R + 1)])
def test_predicted_shapes_is_what_submit_dispatches(ladder_verifier, n, n_slots):
    """The cold-shape gate's prediction is the dispatch: a wrong one is a
    compile inside a measured window that the gate called warm."""
    dev = ladder_verifier
    before = dev.shapes_used.counts()
    predicted = dev.predicted_shapes(n, n_slots)
    dev.submit(
        [b""] * n, [b""] * n, np.zeros(n, np.int64),
        np.arange(n, dtype=np.int64) % n_slots, n_slots,
    ).result()
    after = dev.shapes_used.counts()
    dispatched = [s for s, c in after.items() if c != before.get(s, 0)]
    assert dispatched == predicted


def test_warmup_full_compiles_every_reachable_shape(valset4):
    """warmup(full=True) dispatches (b, b) and (b, smallest) for every
    rung b — a shape left cold compiles mid-measurement on the first
    batch that hits it (r5: a 169 s throughput phase was ~160 s of one
    such compile); the default warmup(n) only n's own combo."""
    vals, _seeds = valset4
    dev = DeviceVoteVerifier(vals, buckets=LADDER)
    dev.warmup(full=True)
    want = {("fused", b, s) for b in LADDER for s in (b, R)}
    assert dev.shapes_used.snapshot() == want

    dev2 = DeviceVoteVerifier(vals, buckets=LADDER)
    dev2.warmup(R + 1)
    assert dev2.shapes_used.snapshot() == {("fused", LADDER[1], R)}


# ---- the step's inputs: vote rows + slot state, two puts a step ----

STEP_LADDER = (8, 64)  # CPU-sized rungs: 23 votes ride the 64 rung, 5 slots the 8


@pytest.fixture(scope="module")
def step_verifiers(valset4):
    """One verifier per path, built once: every case below shares its
    compiled (64, 8) program."""
    vals = valset4[0]
    return {
        "single": DeviceVoteVerifier(vals, buckets=STEP_LADDER),
        "mesh4": DeviceVoteVerifier(vals, buckets=STEP_LADDER, mesh=make_mesh(4)),
    }


def _mixed_step_batch(vals, seeds, seed):
    """A seeded batch with every kind of row the step must get right:
    invalid signatures, a vote signed for another chain, repeated (tx,
    validator) pairs, padding rows (23 votes on the 64 rung), and prior
    stake that crosses a tx's quorum together with one valid vote."""
    rng = np.random.default_rng(seed)
    n_txs = 5
    msgs, sigs, vidx, slot = make_batch(vals, seeds, n_txs)
    msgs, sigs, vidx, slot = list(msgs), list(sigs), list(vidx), list(slot)
    crossing = int(rng.integers(n_txs))  # its one valid vote + prior 20 >= 27
    at_crossing = [i for i in range(len(msgs)) if slot[i] == crossing]
    others = [i for i in range(len(msgs)) if slot[i] != crossing]
    picked = rng.choice(others, 5, replace=False)
    flipped = list(picked[:3]) + list(rng.permutation(at_crossing)[1:])

    for i in flipped:  # invalid signatures: one bit flipped
        k = int(rng.integers(64))
        sigs[i] = sigs[i][:k] + bytes([sigs[i][k] ^ (1 << int(rng.integers(8)))]) + sigs[i][k + 1 :]
    for i in picked[3:]:  # signed for another chain: never verifies here
        t = int(slot[i])
        tx_hash = hashlib.sha256(b"tx%d" % t).hexdigest().upper()
        other = canonical_sign_bytes("other-chain", 1, tx_hash, 1700000000_000000000 + t)
        sigs[i] = host_ed.sign(seeds[int(vidx[i])], other)
    for i in rng.choice(len(msgs), 3, replace=False):  # repeats of a pair
        msgs.append(msgs[i]), sigs.append(sigs[i]), vidx.append(vidx[i]), slot.append(slot[i])
    order = rng.permutation(len(msgs))
    msgs = [msgs[i] for i in order]
    sigs = [sigs[i] for i in order]
    vidx = np.array(vidx)[order]
    slot = np.array(slot)[order]
    # 20 of the 27 needed: the one valid vote of the batch crosses it
    prior = np.zeros(n_txs, np.int64)
    prior[crossing] = 20
    return msgs, sigs, vidx, slot, n_txs, prior


@pytest.mark.parametrize("path", ["single", "mesh4"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_packed_rows_step_matches_scalar(valset4, step_verifiers, path, seed):
    """The step on its two buffers (vote rows, slot state) agrees with the
    golden model field by field, on one device and over a 4-way mesh."""
    vals, seeds = valset4
    msgs, sigs, vidx, slot, n_slots, prior = _mixed_step_batch(vals, seeds, seed)
    dev = step_verifiers[path]
    r = assert_parity(vals, msgs, sigs, vidx, slot, n_slots, prior, device=dev)
    assert dev.predicted_shapes(len(msgs), n_slots) == [("fused", 64, 8)]
    assert r.dropped.sum() >= 1 and (~r.valid & ~r.dropped).sum() >= 5
    # the quorum the prior stake crossed: the batch alone is short of it
    q = vals.quorum_power()
    crossed = np.flatnonzero((prior > 0) & (prior < q) & r.maj23)
    assert len(crossed) == 1 and r.stake[crossed[0]] - prior[crossed[0]] == 10


@pytest.mark.parametrize("path", ["single", "mesh4"])
def test_submit_puts_two_buffers_and_nothing_implicit(
    valset4, step_verifiers, path, monkeypatch
):
    """A step's inputs reach the device in two explicit puts (vote rows,
    slot state); the jitted call itself transfers nothing from the host,
    which the transfer guard would refuse."""
    import jax

    from txflow_tpu.ops import tally

    vals, seeds = valset4
    msgs, sigs, vidx, slot, n_slots, prior = _mixed_step_batch(vals, seeds, 7)
    dev = step_verifiers[path]
    want = ScalarVoteVerifier(vals).verify_and_tally(msgs, sigs, vidx, slot, n_slots, prior)

    put = []
    real_put = jax.device_put

    def counting_put(x, *args, **kwargs):
        put.extend(jax.tree_util.tree_leaves(x))
        return real_put(x, *args, **kwargs)

    monkeypatch.setattr(jax, "device_put", counting_put)
    before = dev.h2d_stats()
    with jax.transfer_guard_host_to_device("disallow"):
        got = dev.submit(msgs, sigs, vidx, slot, n_slots, prior_stake=prior).result()
        # the guard is live on this backend: a host array handed to the
        # same program is refused
        rows = np.zeros((64, tally.ROW_BYTES), np.uint8)
        with pytest.raises(Exception, match="host-to-device"):
            dev._fn(rows, dev._tables_dev, dev._powers_dev, np.zeros(9, np.int32))
    after = dev.h2d_stats()
    assert [(p.dtype, p.shape) for p in put] == [
        (np.uint8, (64, tally.ROW_BYTES)), (np.int32, (8 + 1,)),
    ]
    assert after["h2d_puts"] - before["h2d_puts"] == 2
    assert after["h2d_bytes"] - before["h2d_bytes"] == 64 * tally.ROW_BYTES + 4 * (8 + 1)
    np.testing.assert_array_equal(got.valid, want.valid)
    np.testing.assert_array_equal(got.stake, want.stake)
    np.testing.assert_array_equal(got.maj23, want.maj23)


def test_localnet_counts_two_puts_a_step():
    """Over a LocalNet run on one shared verifier, pipeline_stats()["h2d"]
    adds two puts and b * ROW_BYTES + 4 * (b_slots + 1) bytes for every
    engine step, and every engine step is one device dispatch."""
    import chip_smoke
    from txflow_tpu.engine.shapes import ShapeWarmRegistry
    from txflow_tpu.node import LocalNet
    from txflow_tpu.ops import tally
    from txflow_tpu.verifier import ResilientVoteVerifier

    priv_vals, val_set = chip_smoke.baseline_validator_set(4)
    device = DeviceVoteVerifier(val_set, buckets=(64,))
    verifier = ResilientVoteVerifier(device)
    ShapeWarmRegistry(verifier).prewarm(full=True)
    net = LocalNet(
        4, use_device_verifier=True, priv_vals=priv_vals, verifier=verifier,
    )
    net.start()
    try:
        h2d0 = net.nodes[0].txflow.pipeline_stats()["h2d"]
        shapes0 = device.shapes_used.counts()
        steps0 = [n.txflow.pipeline_stats()["steps"] for n in net.nodes]
        txs = [b"h2d%d=%d" % (i, i) for i in range(4)]
        for tx in txs:
            net.broadcast_tx(tx)
        assert net.wait_all_committed(txs, timeout=300)
    finally:
        net.stop()
    stats = [n.txflow.pipeline_stats() for n in net.nodes]
    assert all(s["h2d"] == stats[0]["h2d"] for s in stats)  # one verifier
    steps = sum(s["steps"] for s in stats) - sum(steps0)
    dispatched = {
        shape: n - shapes0.get(shape, 0)
        for shape, n in device.shapes_used.counts().items()
    }
    assert steps > 0 and sum(dispatched.values()) == steps
    h2d = stats[0]["h2d"]
    assert h2d["h2d_puts"] - h2d0["h2d_puts"] == 2 * steps
    assert h2d["h2d_bytes"] - h2d0["h2d_bytes"] == sum(
        n * (b * tally.ROW_BYTES + 4 * (b_slots + 1))
        for (_, b, b_slots), n in dispatched.items()
    )
