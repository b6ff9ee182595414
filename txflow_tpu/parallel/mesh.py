"""Mesh construction + shard_map'd verify/tally.

Sharding layout (tpu-first, not a translation of the reference's per-peer
goroutines):

- vote-batch axis ("votes"): fully sharded — the fused step's vote rows
  (``ops.tally.VOTE_ROW``: scalar nibbles, R encoding, validator index,
  slot, masks) are split across devices, and so are the naive path's
  gathered pubkey tables; the curve kernel runs embarrassingly parallel.
- tx-slot stake vector: computed as per-shard partial segment-sums, then
  ``psum`` over the mesh axis — one ICI collective per step — so every
  shard holds the identical global tally and quorum mask (replicated out).

This function is what ``__graft_entry__.dryrun_multichip`` compiles over an
N-virtual-device mesh, and what the engine uses on a real multi-chip slice.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops import ed25519_batch, tally

VOTE_AXIS = "votes"


def make_mesh(n_devices: int | None = None, axis_name: str = VOTE_AXIS) -> Mesh:
    """1-D mesh over the first n_devices (default: all) local devices.

    Asked for more devices than ``jax.devices()`` has is an error, never a
    smaller mesh: a caller that asked for four chips and silently ran on
    one would report a sharded run that did not happen."""
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise ValueError(
                f"make_mesh: asked for {n_devices} devices, jax.devices() "
                f"has {len(devs)} ({devs[0].platform})"
            )
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis_name,))


def sharded_verify_and_tally(mesh: Mesh, axis_name: str = VOTE_AXIS):
    """jit(shard_map) of verify+tally: votes sharded, tally psum-replicated.

    Returns f(verify_inputs_tuple, tx_slot, power, prior_stake, quorum) ->
    (valid[B] sharded, stake[n_slots] replicated, maj23[n_slots] replicated)
    with n_slots taken from prior_stake's shape (jit re-specializes per
    shape). B must be divisible by mesh.size (the verifier pads to buckets
    that are).
    """
    inner = tally.verify_and_tally(ed25519_batch.verify_kernel, axis_name=axis_name)

    vote_specs = (P(axis_name), P(axis_name), P(axis_name), P(axis_name), P(axis_name), P(axis_name))
    f = shard_map(
        inner,
        mesh=mesh,
        in_specs=(vote_specs, P(axis_name), P(axis_name), P(), P()),
        out_specs=(P(axis_name), P(), P()),
        # VMA checker ON: the scalar-mul loop carry is pvary'd to the vote
        # axis at init (ops.curve.double_scalar_mul), so its variance type
        # is consistent throughout.
    )
    return jax.jit(f)


import functools


@functools.lru_cache(maxsize=8)
def sharded_compact_step_packed_cached(mesh: Mesh, axis_name: str = VOTE_AXIS):
    """jit(shard_map) of the packed fused step (ops.tally.compact_step_packed),
    shared process-wide (Mesh is hashable) for the same reason as
    ``tally.compact_step_packed_jit``: N in-proc nodes over one mesh must
    reuse one compiled program per shape.

    f(rows, tables, powers, slot_state), the single-device signature. The
    vote rows (uint8[B, ROW_BYTES], one ``tally.VOTE_ROW`` a vote) split
    over the vote axis, each shard verifying B/n whole rows; the per-epoch
    tables and powers and the slot state (prior stake + quorum) are
    replicated; per-shard partial stake tallies psum over ICI. Per-shard
    output [B/n + 2*S] int32, sharded over the vote axis -> host sees
    [B + 2*S*n]; the stake/maj segments repeat the psum-replicated global
    per shard (one D2H readback)."""
    inner = tally.compact_step_packed(axis_name=axis_name)
    f = shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(axis_name), P(), P(), P()),
        out_specs=P(axis_name),
    )
    return jax.jit(f)


def ring_tally(stake_partial, axis_name: str = VOTE_AXIS):
    """All-reduce a per-shard partial stake tally around the ICI ring.

    The ``psum`` the compact step uses lets XLA pick the collective; this
    is the explicit ring formulation (the ring-attention analog for the
    vote axis): N-1 ``ppermute`` rotations, each shard accumulating its
    neighbor's partial, after which every shard holds the global tally.
    Useful when the tally should overlap with other per-shard work on
    real ICI (XLA schedules each hop independently) and as the pattern
    template for future ring-style kernels.
    """
    n = int(jax.lax.axis_size(axis_name))
    perm = [(i, (i + 1) % n) for i in range(n)]

    def hop(_, carry):
        rotating, total = carry
        rotating = jax.lax.ppermute(rotating, axis_name, perm)
        return rotating, total + rotating

    _, total = jax.lax.fori_loop(
        0, n - 1, hop, (stake_partial, stake_partial)
    )
    return total


def sharded_ring_step(mesh: Mesh, axis_name: str = VOTE_AXIS):
    """Compact fused step with the ring all-reduce instead of psum.

    Bit-identical tallies to ``sharded_compact_step_packed_cached`` (integer
    addition is associative/commutative and every shard contributes
    exactly once) — pinned by tests/test_verifier.py's mesh parity test.
    Same inputs: f(rows, tables, powers, slot_state).

    Output layout difference, for honesty with the static VMA checker: a
    ppermute chain does not PROVE replication the way psum does, so the
    stake/maj outputs are declared per-shard — shape [n_shards * S], each
    shard's identical copy of the global concatenated; take shard 0's
    slice. (The checker stays ON; suppressing it was round-2 review
    finding #7 and is not coming back.)
    """
    def inner(rows, tables, powers, slot_state):
        s_nib, h_nib, val_idx, r_y, r_sign, pre_ok, tx_slot = (
            tally.unpack_vote_rows(rows)
        )
        prior_stake, quorum = slot_state[:-1], slot_state[-1]
        valid = ed25519_batch.verify_kernel_gather(
            s_nib, h_nib, val_idx, tables, r_y, r_sign, pre_ok,
            axis_name=axis_name,
        )
        power = jnp.take(powers, val_idx)
        partial = tally.tally_kernel(
            valid, tx_slot, power, prior_stake.shape[0]
        )
        total = prior_stake + ring_tally(partial, axis_name)
        return valid, total, total >= quorum

    v = P(axis_name)
    f = shard_map(
        inner,
        mesh=mesh,
        in_specs=(v, P(), P(), P()),
        out_specs=(v, v, v),
    )
    return jax.jit(f)
