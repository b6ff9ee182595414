"""Batched GF(2^255-19) field arithmetic in radix-2^8 int32 limbs.

TPU-first bignum design (replaces nothing in the reference — go-txflow does all
ed25519 math one signature at a time on CPU via Go's crypto/ed25519,
types/tx_vote.go:110-119):

- A field element is an int32 tensor ``[..., 32]`` of little-endian radix-256
  limbs. All ops are elementwise/vectorized over the leading batch dims — no
  data-dependent control flow, so the whole verifier jits into one XLA program
  and shards over a device mesh with ``shard_map``.
- Radix 2^8 keeps every partial product <= 255*255 < 2^16 and every column sum
  of a 32x32 limb convolution <= 32*2^16 < 2^21, far inside int32 — and inside
  float32's 2^24 exact-integer window, so the inner convolution can later be
  lowered to an MXU f32 matmul or a pallas kernel without changing semantics.
- Carry propagation is a few data-parallel passes (no sequential limb scan);
  only the final canonical freeze (needed once per verify, for the
  encode(P) == R byte comparison Go does) uses an exact borrow scan.

Bounds discipline (checked by tests/test_fe.py):
- "normalized": limbs in [0, 512)   — output of fe_carry/fe_mul/fe_sub.
- fe_mul/fe_sq inputs must have limbs in [0, 1311]; sums of two normalized
  values (fe_add output, <= 1024) are therefore legal mul inputs.
- fe_sub(a, b) adds the limbwise constant 8*p before subtracting, so the
  borrow-free requirement is per-limb: b[0] <= 8*0xED = 1896,
  b[1..30] <= 8*0xFF = 2040, b[31] <= 8*0x7F = 1016. All call sites pass
  normalized-or-added values (limbs <= 1024), well inside every bound.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from . import _fe_common as _common

NLIMB = 32
RADIX = 8
MASK = (1 << RADIX) - 1

# p = 2^255 - 19, little-endian radix-256 limbs.
P_INT = 2**255 - 19
P_LIMBS = np.array([0xED] + [0xFF] * 30 + [0x7F], dtype=np.int32)
# Limbwise 8*p: a value ≡ 0 (mod p) that dominates any subtrahend within
# the per-limb bounds documented above, making limbwise subtraction
# borrow-free.
EIGHT_P_LIMBS = 8 * P_LIMBS

# Anti-diagonal gather plan for the 32x32 limb product: column k of the
# product accumulates a[i] * b[k-i]; _IDX/_VALID pre-encode the k-i map.
_K = np.arange(2 * NLIMB - 1)
_I = np.arange(NLIMB)
_IDX = np.clip(_K[None, :] - _I[:, None], 0, NLIMB - 1)  # [32, 63]
_VALID = (_K[None, :] - _I[:, None] >= 0) & (_K[None, :] - _I[:, None] < NLIMB)


def int_to_limbs(x: int) -> np.ndarray:
    """Host helper: python int -> canonical limb vector."""
    return np.array([(x >> (RADIX * i)) & MASK for i in range(NLIMB)], dtype=np.int32)


def limbs_to_int(limbs) -> int:
    """Host helper: limb vector (any bounds) -> python int."""
    out = 0
    for i, v in enumerate(np.asarray(limbs).tolist()):
        out += int(v) << (RADIX * i)
    return out


def bytes_to_limbs(b: bytes) -> np.ndarray:
    assert len(b) == 32
    return np.frombuffer(b, dtype=np.uint8).astype(np.int32)


def fe_carry(x, passes: int = 4):
    """Data-parallel carry with 2^256 ≡ 38 wraparound.

    Each pass moves carries one limb up; the carry out of limb 31 re-enters
    limb 0 scaled by 38. For inputs bounded by 2^29 (worst case out of the
    fe_mul fold) four passes bring every limb under 512.
    """
    for _ in range(passes):
        hi = x >> RADIX
        lo = x & MASK
        wrapped = jnp.concatenate([38 * hi[..., NLIMB - 1 :], hi[..., : NLIMB - 1]], axis=-1)
        x = lo + wrapped
    return x


def fe_add(a, b):
    """Limbwise add; output limbs <= 1024 when inputs are normalized."""
    return a + b


def fe_sub(a, b):
    """a - b mod p, borrow-free via the 8p offset; output normalized."""
    return fe_carry(a + jnp.asarray(EIGHT_P_LIMBS) - b, passes=2)


import os


def fe_mul(a, b):
    """Product mod 2^255-19 (normalized limbs). Inputs: limbs <= 1311.

    32x32 limb convolution (formulation per ``_conv_mode``), then the
    2^256 ≡ 38 fold of the high 31 columns, then carries.
    """
    if _common.conv_mode() == "pad":
        nd = a.ndim
        c = None
        for i in range(NLIMB):
            t = jnp.pad(
                a[..., i : i + 1] * b, [(0, 0)] * (nd - 1) + [(i, NLIMB - 1 - i)]
            )
            c = t if c is None else c + t
    else:
        bsh = jnp.where(jnp.asarray(_VALID), b[..., jnp.asarray(_IDX)], 0)
        c = jnp.einsum("...i,...ik->...k", a, bsh)  # [..., 63]
    hi = jnp.pad(c[..., NLIMB:], [(0, 0)] * (c.ndim - 1) + [(0, 1)])
    # Worst legal input (limbs 1311) folds to < 2^31; five carry passes are
    # needed for the big limb-0 carry to fully settle (it moves up one limb
    # per pass: 0 -> 1 -> 2 -> 3 -> done).
    return fe_carry(c[..., :NLIMB] + 38 * hi, passes=5)


def fe_sq(a):
    return fe_mul(a, a)


def fe_mul_small(a, c: int):
    """Multiply by a small scalar constant (c <= ~2^20); output normalized."""
    return fe_carry(a * c)


def fe_freeze(x):
    """Exact canonical reduction: limbs in [0,256) and value < p.

    Used once per verification for the byte-exact encode(P) == sig[:32]
    comparison (Go compares encodings, never decompressing R). Two borrow
    scans subtract p at most twice: after carrying, the value is < 2^256 =
    2p + 38, so two conditional subtractions always land in [0, p).
    """
    x = fe_carry(x, passes=6)  # limbs <= ~293, value < 2^256
    p = jnp.asarray(P_LIMBS)
    for _ in range(2):
        # Exact x - p with sequential borrow (31 cheap steps, once per verify).
        diff = x - p
        borrows = []
        borrow = jnp.zeros_like(x[..., 0])
        for i in range(NLIMB):
            d = diff[..., i] - borrow
            borrow = (d < 0).astype(x.dtype)
            borrows.append(d + (borrow << RADIX))
        sub = jnp.stack(borrows, axis=-1)
        x = jnp.where((borrow == 0)[..., None], sub, x)
    # Final carry normalization to strict [0, 256) limbs.
    return fe_carry(x, passes=2)


def bytes_to_limbs_device(b):
    """[..., 32] uint8 LE bytes -> [..., NLIMB] int32 limbs (jit-able).
    Radix 2^8: limbs ARE the bytes."""
    return jnp.asarray(b).astype(jnp.int32)


fe_is_equal_frozen = _common.fe_is_equal_frozen
fe_parity_frozen = _common.fe_parity_frozen
fe_inv = _common.make_inv(fe_mul)


# ---------------------------------------------------------------------------
# Radix switch: TXFLOW_FE_RADIX=13 swaps in the 20-limb radix-2^13
# implementation (fe13.py) for the whole process — curve tables, epoch
# tables, and kernels all build on these symbols at import time, so the
# choice must be made before anything imports ops.curve. Default stays
# radix-8 (the TPU-measured configuration) until a live A/B on hardware
# confirms the 20-limb kernel.
if os.environ.get("TXFLOW_FE_RADIX") == "13":
    from . import fe13 as _fe13

    NLIMB = _fe13.NLIMB
    RADIX = _fe13.RADIX
    MASK = _fe13.MASK
    P_LIMBS = _fe13.P_LIMBS
    int_to_limbs = _fe13.int_to_limbs
    limbs_to_int = _fe13.limbs_to_int
    bytes_to_limbs = _fe13.bytes_to_limbs
    bytes_to_limbs_device = _fe13.bytes_to_limbs_device
    fe_carry = _fe13.fe_carry
    fe_add = _fe13.fe_add
    fe_sub = _fe13.fe_sub
    fe_mul = _fe13.fe_mul
    fe_sq = _fe13.fe_sq
    fe_mul_small = _fe13.fe_mul_small
    fe_freeze = _fe13.fe_freeze
    fe_is_equal_frozen = _fe13.fe_is_equal_frozen
    fe_parity_frozen = _fe13.fe_parity_frozen
    fe_inv = _fe13.fe_inv
