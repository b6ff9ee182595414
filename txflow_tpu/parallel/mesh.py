"""Mesh construction + shard_map'd verify/tally.

Sharding layout (tpu-first, not a translation of the reference's per-peer
goroutines):

- vote-batch axis ("votes"): fully sharded — every per-vote array
  (scalar nibbles, pubkey window tables, R encodings, masks, slots, powers)
  is split across devices; the curve kernel runs embarrassingly parallel.
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
def sharded_compact_step_cached(mesh: Mesh, axis_name: str = VOTE_AXIS):
    """Process-wide shared jit of the sharded step (Mesh is hashable).

    Shared for the same reason as ``tally.compact_step_jit``: N in-proc
    nodes over one mesh must reuse one compiled program per shape."""
    return sharded_compact_step(mesh, axis_name)


def sharded_compact_step(mesh: Mesh, axis_name: str = VOTE_AXIS):
    """jit(shard_map) of the compact fused step (ops.tally.compact_step).

    Per-vote arrays shard over the vote axis; the per-epoch table/power
    constants and the prior-stake/quorum scalars are replicated; per-shard
    partial stake tallies psum over ICI. Same call signature as the
    single-device compact step.
    """
    inner = tally.compact_step(axis_name=axis_name)
    v = P(axis_name)
    f = shard_map(
        inner,
        mesh=mesh,
        in_specs=(v, v, v, v, v, v, v, P(), P(), P(), P()),
        out_specs=(v, P(), P()),
    )
    return jax.jit(f)


@functools.lru_cache(maxsize=8)
def sharded_compact_step_packed_cached(mesh: Mesh, axis_name: str = VOTE_AXIS):
    """Packed-output sharded step (single D2H readback; tally.compact_step_
    packed docstring). Per-shard output [B/n + 2*S] int32, sharded over the
    vote axis -> host sees [B + 2*S*n]; the stake/maj segments repeat the
    psum-replicated global per shard."""
    inner = tally.compact_step_packed(axis_name=axis_name)
    v = P(axis_name)
    f = shard_map(
        inner,
        mesh=mesh,
        in_specs=(v, v, v, v, v, v, v, P(), P(), P(), P()),
        out_specs=v,
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

    Bit-identical tallies to ``sharded_compact_step`` (integer addition is
    associative/commutative and every shard contributes exactly once) —
    pinned by tests/test_verifier.py's mesh parity test.

    Output layout difference, for honesty with the static VMA checker: a
    ppermute chain does not PROVE replication the way psum does, so the
    stake/maj outputs are declared per-shard — shape [n_shards * S], each
    shard's identical copy of the global concatenated; take shard 0's
    slice. (The checker stays ON; suppressing it was round-2 review
    finding #7 and is not coming back.)
    """
    from ..ops import ed25519_batch

    def inner(s_nib, h_nib, val_idx, r_y, r_sign, pre_ok, tx_slot,
              tables, powers, prior_stake, quorum):
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
        in_specs=(v, v, v, v, v, v, v, P(), P(), P(), P()),
        out_specs=(v, v, v),
    )
    return jax.jit(f)
