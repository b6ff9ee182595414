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

import jax
import jax.numpy as jnp


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


import functools


@functools.lru_cache(maxsize=None)
def compact_step_jit(axis_name: str | None = None):
    """Process-wide shared jit of ``compact_step``.

    Every ``DeviceVoteVerifier`` in the process (an in-proc validator net
    runs one per node) must share ONE compiled program per input shape —
    epoch tables and powers are arguments, so nothing per-verifier is
    baked in. Constructing a fresh ``jax.jit(compact_step())`` per
    verifier would compile N times (~tens of seconds each on TPU)."""
    return jax.jit(compact_step(axis_name))


def compact_step(axis_name: str | None = None):
    """The fused aggregation step over a compact batch (the hot path).

    f(s_nib, h_nib, val_idx, r_y, r_sign, pre_ok, tx_slot, tables, powers,
      prior_stake, quorum) -> (valid[B], stake[n_slots], maj23[n_slots]).

    Per-epoch constants (``tables`` [V,16,4,32], ``powers`` int32[V]) stay
    device-resident across batches; per-vote inputs are compact uint8/int32
    (~162 B/vote of H2D). Voting power is gathered on device by validator
    index — a vote contributes iff its signature verified.
    """
    from . import ed25519_batch

    def txflow_verify_tally_unpacked(
        s_nib, h_nib, val_idx, r_y, r_sign, pre_ok, tx_slot, tables, powers,
        prior_stake, quorum,
    ):
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
    """Shared jit of the packed-output compact step (see compact_step_packed)."""
    return jax.jit(compact_step_packed(axis_name))


def compact_step_packed(axis_name: str | None = None):
    """compact_step with the three outputs packed into ONE int32 vector.

    Readback layout per shard: [valid (B/n) | stake (S) | maj23 (S)], all
    int32, concatenated. One device->host transfer instead of three — each
    readback pays a fixed setup cost that dominates small reads, so three
    arrays cost nearly three times one. With a mesh the stake/maj segments
    are the psum-replicated globals, repeated per shard (the host reads
    shard 0's).
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
