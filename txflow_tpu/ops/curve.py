"""Batched edwards25519 point arithmetic on limb tensors.

Representations (each coordinate an int32 limb tensor ``[..., 32]``):

- **Extended** (X, Y, Z, T): x = X/Z, y = Y/Z, T = XY/Z — the working form.
- **PNiels** (Y+X, Y-X, Z, 2dT): precomputed form making addition cost 8 muls.
  Host-built window tables store affine entries (Z = 1) in this form.

All ops are branch-free and vectorized over leading batch dims, so the
double-scalar multiplication [s]B + [h](-A) — the per-vote work Go does
serially in crypto/ed25519 (reference types/tx_vote.go:110-119) — runs for
thousands of votes in one XLA program.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..crypto import ed25519 as host_ed
from . import fe

# 2*d mod p, as a canonical limb constant.
D2_INT = (2 * host_ed.D) % host_ed.P
D2_LIMBS = fe.int_to_limbs(D2_INT)

TABLE_WINDOW = 4
TABLE_SIZE = 1 << TABLE_WINDOW  # 16
NWINDOWS = 64  # 256 bits / 4


def ext_identity(batch_shape):
    z = jnp.zeros((*batch_shape, fe.NLIMB), dtype=jnp.int32)
    one = z.at[..., 0].set(1)
    return (z, one, one, z)


def ext_double(p, compute_t: bool = True):
    """Dedicated doubling (RFC 8032 section 5.1.4 'dbl-2008-hwcd')."""
    X1, Y1, Z1, _ = p
    A = fe.fe_sq(X1)
    B = fe.fe_sq(Y1)
    C = fe.fe_mul_small(fe.fe_sq(Z1), 2)
    H = fe.fe_add(A, B)
    # E = H - (X1+Y1)^2  (carry the sum before squaring to respect bounds)
    E = fe.fe_sub(H, fe.fe_sq(fe.fe_carry(fe.fe_add(X1, Y1), passes=2)))
    G = fe.fe_sub(A, B)
    F = fe.fe_add(C, G)
    X3 = fe.fe_mul(E, F)
    Y3 = fe.fe_mul(G, H)
    Z3 = fe.fe_mul(F, G)
    T3 = fe.fe_mul(E, H) if compute_t else X3
    return (X3, Y3, Z3, T3)


def pniels_add(p, n):
    """Extended + PNiels -> Extended ('madd-2008-hwcd-3' generalized to Z2)."""
    X1, Y1, Z1, T1 = p
    YpX2, YmX2, Z2, T2d2 = n
    A = fe.fe_mul(fe.fe_sub(Y1, X1), YmX2)
    B = fe.fe_mul(fe.fe_carry(fe.fe_add(Y1, X1), passes=2), YpX2)
    C = fe.fe_mul(T1, T2d2)
    D = fe.fe_mul_small(fe.fe_mul(Z1, Z2), 2)
    E = fe.fe_sub(B, A)
    F = fe.fe_sub(D, C)
    G = fe.fe_add(D, C)
    H = fe.fe_add(B, A)
    return (
        fe.fe_mul(E, F),
        fe.fe_mul(G, H),
        fe.fe_mul(F, G),
        fe.fe_mul(E, H),
    )


def table_select(table, nibble):
    """Select window entries from a PNiels table by per-item nibble.

    table: [..., 16, 4, 32] (leading dims broadcast against nibble's batch);
    nibble: int32 [...] in [0, 16). Returns PNiels coords, each [..., 32].
    Uses a one-hot contraction (MXU/VPU-friendly; also constant-time, which
    the serial reference path is not).
    """
    onehot = (
        nibble[..., None] == jnp.arange(TABLE_SIZE, dtype=jnp.int32)
    ).astype(jnp.int32)
    if table.ndim == 3:  # shared table [16, 4, 32]
        sel = jnp.einsum("...w,wcl->...cl", onehot, table)
    else:  # per-item table [..., 16, 4, 32]
        sel = jnp.einsum("...w,...wcl->...cl", onehot, table)
    return (sel[..., 0, :], sel[..., 1, :], sel[..., 2, :], sel[..., 3, :])


def table_select_indexed(tables_flat, idx):
    """Select PNiels entries from a SHARED flattened table by scalar index.

    tables_flat: [E, 4*32] (all validators' window entries, row-major);
    idx: int32 [...] in [0, E). Two lowerings, same bit-exact result:

    - E <= 2048: one-hot matmul [..., E] @ [E, 128]. Inputs cast to
      bfloat16 — exact, since one-hot entries are 0/1 and limbs are < 256
      (8 significand bits) — with a float32 accumulator, so the MXU does
      the select instead of the VPU walking a gather. This is the hot
      configuration (validator sets <= 128).
    - E > 2048: plain row gather (the one-hot operand would dwarf the
      table itself).

    Either way the full per-item window table [B, 16, 4, 32] of the naive
    path is never materialized — selection happens inside the scan step,
    one window at a time (the materialized form measured super-linear HBM
    cost past ~16k votes on v5e, r3).
    """
    import math

    E = tables_flat.shape[0]
    batch = math.prod(idx.shape) if idx.shape else 1
    # the one-hot matmul only pays off when the batch actually fills MXU
    # tiles, and for tiny batches it once compiled pathologically slowly
    # (an 8-vote entry() program, r3) — small or huge-table cases take
    # the plain gather
    if E <= 2048 and batch >= 256:
        # dtype must represent every table limb EXACTLY: radix-8 limbs
        # (< 256) fit bfloat16's 8 significand bits; radix-13 limbs
        # (< 8192) need float32 (24 bits). One-hot entries are 0/1 and the
        # accumulator is f32 either way, so the select stays bit-exact.
        sel_dtype = jnp.bfloat16 if fe.RADIX == 8 else jnp.float32
        onehot = (
            idx[..., None] == jnp.arange(E, dtype=jnp.int32)
        ).astype(sel_dtype)
        sel = jax.lax.dot_general(
            onehot,
            tables_flat.astype(sel_dtype),
            (((onehot.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            # f32 path (radix-13): default MXU precision truncates f32
            # operands to bf16, which loses the low ~5 bits of 13-bit
            # limbs — HIGHEST keeps the pass bit-exact (r5 review); the
            # bf16 path is exact by construction (limbs < 256)
            precision=(
                None if sel_dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST
            ),
        ).astype(jnp.int32)
    else:
        sel = jnp.take(tables_flat, idx, axis=0)
    sel = sel.reshape(*idx.shape, 4, fe.NLIMB)
    return (sel[..., 0, :], sel[..., 1, :], sel[..., 2, :], sel[..., 3, :])


def double_scalar_mul_indexed(
    s_nibbles, h_nibbles, base_table, tables, val_idx, axis_name=None
):
    """[s]B + [h]A' with A' looked up per item from shared epoch tables.

    tables: [V, 16, 4, 32] device-resident epoch tables; val_idx: int32 [B].
    Identical results to ``double_scalar_mul`` over gathered per-item
    tables, but the gather collapses to an in-loop indexed select (see
    ``table_select_indexed``), so HBM holds one [V*16, 128] table total
    instead of 8 KiB per vote.
    """
    n_vals = tables.shape[0]
    tables_flat = tables.reshape(n_vals * TABLE_SIZE, 4 * fe.NLIMB)
    base = val_idx * TABLE_SIZE

    def step(w, acc):
        acc = ext_double(acc, compute_t=False)
        acc = ext_double(acc, compute_t=False)
        acc = ext_double(acc, compute_t=False)
        acc = ext_double(acc, compute_t=True)
        s_nib = jax.lax.dynamic_index_in_dim(s_nibbles, w, axis=-1, keepdims=False)
        h_nib = jax.lax.dynamic_index_in_dim(h_nibbles, w, axis=-1, keepdims=False)
        acc = pniels_add(acc, table_select(base_table, s_nib))
        acc = pniels_add(acc, table_select_indexed(tables_flat, base + h_nib))
        return acc

    with jax.named_scope("double_scalar_mul"):
        init = ext_identity(s_nibbles.shape[:-1])
        if axis_name is not None:
            init = tuple(jax.lax.pcast(t, axis_name, to="varying") for t in init)
        return jax.lax.fori_loop(0, NWINDOWS, step, init)


def double_scalar_mul(s_nibbles, h_nibbles, base_table, a_tables, axis_name=None):
    """Compute [s]B + [h]A' batched, A' given by per-item PNiels tables.

    s_nibbles, h_nibbles: int32 [B, 64], most-significant nibble first.
    base_table: [16, 4, 32] PNiels multiples of B (host precomputed).
    a_tables:   [B, 16, 4, 32] PNiels multiples of A' (per-validator epoch
                tables gathered per vote; A' = -A for verification).
    Returns an Extended point.

    64 lax.fori_loop window steps of (4 doublings + 2 table additions); a
    uniform body (doubling the identity start is a no-op) keeps the compiled
    program one window-step long instead of 64.

    Under shard_map (``axis_name`` set) the identity start is marked
    device-varying with ``lax.pvary`` so the loop carry has a consistent
    variance type — the per-vote table additions make it varying anyway —
    and the static VMA checker can stay ON.
    """

    def step(w, acc):
        acc = ext_double(acc, compute_t=False)
        acc = ext_double(acc, compute_t=False)
        acc = ext_double(acc, compute_t=False)
        acc = ext_double(acc, compute_t=True)
        s_nib = jax.lax.dynamic_index_in_dim(s_nibbles, w, axis=-1, keepdims=False)
        h_nib = jax.lax.dynamic_index_in_dim(h_nibbles, w, axis=-1, keepdims=False)
        acc = pniels_add(acc, table_select(base_table, s_nib))
        acc = pniels_add(acc, table_select(a_tables, h_nib))
        return acc

    init = ext_identity(s_nibbles.shape[:-1])
    if axis_name is not None:
        # the sharded wrappers run with the VMA checker ON, which needs
        # this variance cast
        init = tuple(jax.lax.pcast(t, axis_name, to="varying") for t in init)
    with jax.named_scope("double_scalar_mul"):
        return jax.lax.fori_loop(0, NWINDOWS, step, init)


def ext_encode(p):
    """Canonical compressed encoding pieces: (y_frozen [...,32], x_parity [...]).

    encode(P) = y with the parity of x in bit 255 (host_ed.point_compress);
    returning the frozen y limbs + parity lets the caller compare against
    raw signature bytes exactly as Go does.
    """
    X, Y, Z, _ = p
    zinv = fe.fe_inv(Z)
    y = fe.fe_freeze(fe.fe_mul(Y, zinv))
    x = fe.fe_freeze(fe.fe_mul(X, zinv))
    return y, fe.fe_parity_frozen(x)


# ----------------------------------------------------------------------------
# Host-side table construction (numpy/python ints; once per validator epoch).


def _affine_pniels(pt) -> np.ndarray:
    """Host: extended python-int point -> affine PNiels limb block [4, 32]."""
    x, y, z, _ = pt
    zinv = pow(z, host_ed.P - 2, host_ed.P)
    xa, ya = (x * zinv) % host_ed.P, (y * zinv) % host_ed.P
    return np.stack(
        [
            fe.int_to_limbs((ya + xa) % host_ed.P),
            fe.int_to_limbs((ya - xa) % host_ed.P),
            fe.int_to_limbs(1),
            fe.int_to_limbs((2 * host_ed.D * xa * ya) % host_ed.P),
        ]
    )


def build_pniels_table(pt) -> np.ndarray:
    """Host: window table [16, 4, 32] of {0..15} * pt (entry 0 = identity)."""
    rows = [
        np.stack(
            [
                fe.int_to_limbs(1),
                fe.int_to_limbs(1),
                fe.int_to_limbs(1),
                fe.int_to_limbs(0),
            ]
        )
    ]
    acc = host_ed.IDENTITY
    for _ in range(1, TABLE_SIZE):
        acc = host_ed.point_add(acc, pt)
        rows.append(_affine_pniels(acc))
    return np.stack(rows)  # [16, 4, 32]


BASE_TABLE = build_pniels_table(host_ed.BASE)


def scalar_to_nibbles(s: int) -> np.ndarray:
    """Host: 256-bit scalar -> [64] int32 nibbles, most significant first."""
    return np.array(
        [(s >> (4 * (NWINDOWS - 1 - i))) & 0xF for i in range(NWINDOWS)],
        dtype=np.int32,
    )
