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
    import numpy as _np

    from txflow_tpu.ops import ed25519_batch
    from txflow_tpu.parallel import make_mesh
    from txflow_tpu.parallel.mesh import sharded_compact_step, sharded_ring_step

    vals, seeds = make_valset(4)
    epoch = ed25519_batch.EpochTables([v.pub_key for v in vals])
    msgs, sigs, vidx, slot = make_batch(
        vals, seeds, n_txs=4, corrupt=("ok", "ok", "flip")
    )
    batch = ed25519_batch.prepare_compact(msgs, sigs, vidx, epoch)
    n = batch.size
    pad = (-n) % 8
    import numpy as np

    def p(a):
        return np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)])

    args = (
        p(batch.s_nibbles), p(batch.h_nibbles), p(batch.val_idx),
        p(batch.r_y), p(batch.r_sign), p(batch.pre_ok),
        np.concatenate([np.asarray(slot, np.int32), np.full(pad, -1, np.int32)]),
        epoch.tables, vals.powers_array().astype(np.int32),
        np.zeros(4, np.int32), np.int32(vals.quorum_power()),
    )
    mesh = make_mesh(8)
    a = sharded_compact_step(mesh)(*args)
    b = sharded_ring_step(mesh)(*args)
    _np.testing.assert_array_equal(_np.asarray(a[0]), _np.asarray(b[0]))
    # ring outputs are per-shard copies of the global: every shard's slice
    # must equal the psum-replicated global
    stake = _np.asarray(b[1]).reshape(8, -1)
    maj = _np.asarray(b[2]).reshape(8, -1)
    for sh in range(8):
        _np.testing.assert_array_equal(stake[sh], _np.asarray(a[1]))
        _np.testing.assert_array_equal(maj[sh], _np.asarray(a[2]))


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
