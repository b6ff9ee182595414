"""Stake-weighted quorum tally as a device reduction.

The reference accumulates voting power one vote at a time under a mutex
(types/vote_set.go:143-166: ``sum += power; maj23 = sum >= total*2/3+1``).
Here the tally over a whole batch of verified votes is a segment-sum over
tx slots followed by a threshold compare — one fused XLA reduction, and the
cross-device combine is a single ``psum`` over the vote-sharding mesh axis.

Voting powers are int64 in the reference. The device tally uses int32 —
with per-batch dedup, per-slot batch stake and prior stake are each at
most the total power, so their sum stays below 2^31 whenever total power
is below 2^30. ``DeviceVoteVerifier`` enforces that bound at construction
and raises, directing such sets to ``ScalarVoteVerifier`` (host int64
accumulation); tendermint itself caps total power at 2^63/8, and practical
validator sets are far below 2^30.

Names a profiler can find: the functions handed to ``jax.jit`` here are
named, so the step's program is ``jit_txflow_verify_tally(<fingerprint>)``
in a device trace (``_unpacked`` / ``_generic`` for the other two), and
``jax.named_scope`` marks where ``decompress``, ``double_scalar_mul``,
``encode_compare`` (ops/ed25519_batch.py, ops/curve.py) and ``tally``
begin. Scopes only name operations: numerics, shapes and fusion are as
before.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def tally_kernel(valid, tx_slot, power, n_slots: int):
    """Per-slot stake sums for one device shard.

    valid: bool[B] (verified signatures), tx_slot: int32[B] slot id per vote
    (-1 or >= n_slots = no slot / padding), power: int32[B] voting power of
    the vote's validator. Returns int32[n_slots].
    """
    with jax.named_scope("tally"):
        contrib = jnp.where(valid, power, 0)
        slot = jnp.clip(tx_slot, 0, n_slots - 1)
        in_range = (tx_slot >= 0) & (tx_slot < n_slots)
        return jax.ops.segment_sum(
            jnp.where(in_range, contrib, 0), slot, num_segments=n_slots
        )


def verify_and_tally(verify_fn, axis_name: str | None = None):
    """Compose a verify kernel with the quorum tally.

    Returns f(verify_inputs..., tx_slot, power, prior_stake, quorum) ->
    (valid[B], stake[n_slots], maj23[n_slots]).

    prior_stake carries stake already accumulated for each slot in earlier
    batches (the engine's running TxVoteSet sums), so maj23 latches across
    batches exactly like the incremental reference. When ``axis_name`` is
    given the stake partial-sums are psum-combined across the vote-sharded
    mesh axis (ICI collective), giving every shard the global tally.
    """

    def txflow_verify_tally_generic(verify_inputs, tx_slot, power, prior_stake, quorum):
        valid = verify_fn(*verify_inputs, axis_name=axis_name)
        stake = tally_kernel(valid, tx_slot, power, prior_stake.shape[0])
        if axis_name is not None:
            stake = jax.lax.psum(stake, axis_name)
        total = prior_stake + stake
        return valid, total, total >= quorum

    return txflow_verify_tally_generic


# One vote's row of the step's input: everything the device needs of a vote
# in one C-contiguous uint8 host buffer, so that a step's votes reach the
# device in ONE host->device transfer — each transfer pays a fixed setup
# cost that dominates small steps, as each readback does. ``val_idx`` and
# ``tx_slot`` are little-endian int32, bitcast back on the device; the row
# is padded to ``ROW_BYTES`` with zeros: 170 bytes rounded up to a 64-byte
# multiple (a put of uint8[b, W] took the same time on a v5e for every W
# from 170 to 256).
ROW_BYTES = 192
VOTE_ROW = np.dtype({
    "names": ["s_nibbles", "h_nibbles", "r_y", "val_idx", "tx_slot", "r_sign", "pre_ok"],
    "formats": [(np.uint8, (64,)), (np.uint8, (64,)), (np.uint8, (32,)), "<i4", "<i4",
                np.uint8, np.uint8],
    "offsets": [0, 64, 128, 160, 164, 168, 169],
    "itemsize": ROW_BYTES,
})


def pack_step_inputs(batch, tx_slot, b: int, prior_stake, b_slots: int, quorum: int):
    """Host side of the step's input layout: (rows uint8[b, ROW_BYTES],
    slot_state int32[b_slots + 1]).

    ``batch`` is an ``ed25519_batch.CompactBatch`` of n <= b votes with
    ``tx_slot`` int[n]; rows n..b-1 are padding: all zero but ``tx_slot``
    -1, so ``pre_ok`` 0 and no slot — they verify false and tally nothing.
    ``slot_state`` is the prior stake of each slot (``prior_stake``,
    zero-padded to ``b_slots``; None = no prior) followed by the quorum.
    """
    n = batch.size
    rec = np.zeros(b, VOTE_ROW)
    rec["s_nibbles"][:n] = batch.s_nibbles
    rec["h_nibbles"][:n] = batch.h_nibbles
    rec["r_y"][:n] = batch.r_y
    rec["val_idx"][:n] = batch.val_idx
    rec["tx_slot"][:n] = tx_slot
    rec["tx_slot"][n:] = -1
    rec["r_sign"][:n] = batch.r_sign
    rec["pre_ok"][:n] = batch.pre_ok
    state = np.zeros(b_slots + 1, np.int32)
    if prior_stake is not None:
        state[: len(prior_stake)] = prior_stake
    state[-1] = quorum
    return rec.view(np.uint8).reshape(b, ROW_BYTES), state


def unpack_vote_rows(rows):
    """Device side of ``VOTE_ROW``: uint8[B, ROW_BYTES] -> (s_nib, h_nib,
    val_idx, r_y, r_sign, pre_ok, tx_slot), the verify kernel's inputs."""

    def col(name, width):
        off = VOTE_ROW.fields[name][1]
        return rows[:, off : off + width]

    def int32(name):
        return jax.lax.bitcast_convert_type(col(name, 4), jnp.int32)

    return (
        col("s_nibbles", 64),
        col("h_nibbles", 64),
        int32("val_idx"),
        col("r_y", 32),
        col("r_sign", 1)[:, 0],
        col("pre_ok", 1)[:, 0] != 0,
        int32("tx_slot"),
    )


def compact_step(axis_name: str | None = None):
    """The fused aggregation step over a compact batch (the hot path).

    f(rows, tables, powers, slot_state) -> (valid[B], stake[S], maj23[S]).

    Per-call inputs are two buffers: ``rows`` uint8[B, ROW_BYTES], one
    ``VOTE_ROW`` a vote (S and h as 64 nibbles each, R's 32 bytes, the
    validator index and tx slot as little-endian int32, R's sign bit and
    the host pre-check), and ``slot_state`` int32[S + 1], the prior stake
    of each tx slot followed by the quorum. Per-epoch constants
    (``tables`` [V,16,4,32], ``powers`` int32[V]) stay device-resident
    across batches. Voting power is gathered on device by validator
    index — a vote contributes iff its signature verified.
    """
    from . import ed25519_batch

    def txflow_verify_tally_unpacked(rows, tables, powers, slot_state):
        s_nib, h_nib, val_idx, r_y, r_sign, pre_ok, tx_slot = unpack_vote_rows(rows)
        prior_stake, quorum = slot_state[:-1], slot_state[-1]
        valid = ed25519_batch.verify_kernel_gather(
            s_nib, h_nib, val_idx, tables, r_y, r_sign, pre_ok,
            axis_name=axis_name,
        )
        power = jnp.take(powers, val_idx)
        stake = tally_kernel(valid, tx_slot, power, prior_stake.shape[0])
        if axis_name is not None:
            stake = jax.lax.psum(stake, axis_name)
        total = prior_stake + stake
        return valid, total, total >= quorum

    return txflow_verify_tally_unpacked


@functools.lru_cache(maxsize=None)
def compact_step_packed_jit(axis_name: str | None = None):
    """Process-wide shared jit of ``compact_step_packed``.

    Every ``DeviceVoteVerifier`` in the process (an in-proc validator net
    runs one per node) must share ONE compiled program per input shape —
    epoch tables and powers are arguments, so nothing per-verifier is
    baked in. A fresh jit per verifier would compile N times (~tens of
    seconds each on TPU)."""
    return jax.jit(compact_step_packed(axis_name))


def compact_step_packed(axis_name: str | None = None):
    """compact_step with the three outputs packed into ONE int32 vector.

    f(rows, tables, powers, slot_state) -> int32[B/n + 2*S] per shard,
    inputs as ``compact_step``: two host->device transfers a step in,
    one readback out. Readback layout per shard: [valid (B/n) | stake (S)
    | maj23 (S)], all int32, concatenated. One device->host transfer
    instead of three — each readback pays a fixed setup cost that
    dominates small reads, so three arrays cost nearly three times one.
    With a mesh the stake/maj segments are the psum-replicated globals,
    repeated per shard (the host reads shard 0's).
    """
    inner = compact_step(axis_name)

    def txflow_verify_tally(*args):
        valid, total, maj = inner(*args)
        total = total.astype(jnp.int32)
        maj = maj.astype(jnp.int32)
        if axis_name is not None:
            # stake/maj are psum-replicated (device-invariant); concatenating
            # them with the device-varying valid segment needs an explicit
            # variance cast for the VMA checker
            total = jax.lax.pcast(total, axis_name, to="varying")
            maj = jax.lax.pcast(maj, axis_name, to="varying")
        return jnp.concatenate([valid.astype(jnp.int32), total, maj])

    return txflow_verify_tally
