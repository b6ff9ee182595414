"""Batched ed25519 verification: the TPU replacement for the reference's
one-vote-at-a-time Go verify (types/tx_vote.go:110-119, serialized through
txflow/service.go:123-166).

Work split, designed for the hardware:

- **Host** does all byte-level work: signature parsing, the S < L malleability
  check ("ScMinimal"), SHA-512(R || A || msg) mod L (hashlib; ~1 us per vote,
  never the bottleneck), scalar->nibble decomposition, and — once per
  validator-set epoch — pubkey decompression + 16-entry window tables of -A
  per validator.
- **Device** does all curve math: the batched double-scalar multiplication
  P = [s]B + [h](-A) and the canonical encode(P) == sig[:32] comparison,
  branch-free over the whole batch.

Accept/reject decisions are bit-identical to ``crypto.ed25519.verify_pure``
(the audited golden model of Go's crypto/ed25519) — tested including
adversarial non-canonical encodings.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from ..crypto import ed25519 as host_ed
from . import curve, fe


@dataclass
class PreparedBatch:
    """Host-prepared device inputs for a batch of B signature checks."""

    s_nibbles: np.ndarray  # [B, 64] int32, MSB-first nibbles of S
    h_nibbles: np.ndarray  # [B, 64] int32, MSB-first nibbles of h = H(R|A|m) mod L
    a_tables: np.ndarray  # [B, 16, 4, 32] int32 PNiels tables of -A (gathered)
    r_y: np.ndarray  # [B, 32] int32: low 255 bits of sig[:32] as limbs
    r_sign: np.ndarray  # [B] int32: bit 255 of sig[:32]
    pre_ok: np.ndarray  # [B] bool: host pre-checks passed (S<L, key on curve)

    @property
    def size(self) -> int:
        return self.s_nibbles.shape[0]


def neg_pubkey_table(pub_key: bytes) -> tuple[np.ndarray, bool]:
    """Host: window table of -A for one pubkey; ok=False if off-curve.

    Off-curve keys get an identity-filled table and are force-rejected via
    the pre_ok mask (matching Go, which rejects at decompression).
    """
    A = host_ed.point_decompress(pub_key)
    if A is None:
        return curve.build_pniels_table(host_ed.IDENTITY), False
    return curve.build_pniels_table(host_ed.point_neg(A)), True


class EpochTables:
    """Per-validator-set-epoch device constants: one -A table per validator.

    The reference re-fetches the pubkey and re-verifies per vote
    (types/vote_set.go:117-119); here decompression and windowing are
    amortized across the epoch (validator sets change only at block
    boundaries, state/execution.go:390-414).
    """

    def __init__(self, pub_keys: list[bytes]):
        tables, oks = [], []
        for pk in pub_keys:
            t, ok = neg_pubkey_table(pk)
            tables.append(t)
            oks.append(ok)
        self.pub_keys = list(pub_keys)
        self.tables = np.stack(tables) if tables else np.zeros((0, 16, 4, fe.NLIMB), np.int32)
        self.key_ok = np.array(oks, dtype=bool)
        # [V, 32] uint8 key bytes for the native batch prep's per-vote
        # gather. Malformed key lengths (key_ok already False -> the vote is
        # force-rejected) get a zero row: joining raw would crash or, worse,
        # shift every later validator's row by the length error.
        self.pub_arr = (
            np.frombuffer(
                b"".join(pk if len(pk) == 32 else bytes(32) for pk in pub_keys),
                np.uint8,
            )
            .reshape(-1, 32)
            .copy()
            if pub_keys
            else np.zeros((0, 32), np.uint8)
        )
        self._device_tables = None

    def device_tables(self):
        """Epoch tables as a device array, uploaded once and cached."""
        if self._device_tables is None:
            self._device_tables = jnp.asarray(self.tables)
        return self._device_tables


def prepare_batch(
    msgs: list[bytes],
    sigs: list[bytes],
    val_idx: np.ndarray,
    epoch: EpochTables,
) -> PreparedBatch:
    """Host prep for verify: msgs[i] signed by validator val_idx[i] with sigs[i]."""
    n = len(msgs)
    s_nib = np.zeros((n, curve.NWINDOWS), np.int32)
    h_nib = np.zeros((n, curve.NWINDOWS), np.int32)
    r_y = np.zeros((n, fe.NLIMB), np.int32)
    r_sign = np.zeros(n, np.int32)
    pre_ok = np.zeros(n, bool)
    for i, (msg, sig) in enumerate(zip(msgs, sigs)):
        vi = int(val_idx[i])
        if len(sig) != 64 or not (0 <= vi < len(epoch.pub_keys)):
            continue
        s = int.from_bytes(sig[32:], "little")
        if s >= host_ed.L:  # ScMinimal
            continue
        if not epoch.key_ok[vi]:
            continue
        pub = epoch.pub_keys[vi]
        h = (
            int.from_bytes(hashlib.sha512(sig[:32] + pub + msg).digest(), "little")
            % host_ed.L
        )
        s_nib[i] = curve.scalar_to_nibbles(s)
        h_nib[i] = curve.scalar_to_nibbles(h)
        r_bytes = bytearray(sig[:32])
        r_sign[i] = r_bytes[31] >> 7
        r_bytes[31] &= 0x7F  # low 255 bits only (radix-agnostic: byte level)
        r_y[i] = fe.bytes_to_limbs(bytes(r_bytes))
        pre_ok[i] = True
    a_tables = (
        epoch.tables[np.clip(val_idx, 0, max(len(epoch.pub_keys) - 1, 0))]
        if len(epoch.pub_keys)
        else np.zeros((n, 16, 4, fe.NLIMB), np.int32)
    )
    return PreparedBatch(s_nib, h_nib, a_tables, r_y, r_sign, pre_ok)


def verify_kernel(s_nibbles, h_nibbles, a_tables, r_y, r_sign, pre_ok, axis_name=None):
    """Device kernel: bool[B] of Go-equivalent signature validity.

    Jit/shard_map-able; all inputs are fixed-shape arrays. Computes
    P = [S]B + [h](-A) and accepts iff the canonical encoding of P equals
    the signature's R bytes — exactly Go's comparison, which also rejects
    non-canonical R encodings for free.
    """
    p = curve.double_scalar_mul(
        s_nibbles, h_nibbles, jnp.asarray(curve.BASE_TABLE), a_tables,
        axis_name=axis_name,
    )
    with jax.named_scope("encode_compare"):
        y, x_parity = curve.ext_encode(p)
        enc_match = fe.fe_is_equal_frozen(y, r_y) & (x_parity == r_sign)
        return enc_match & pre_ok


verify_kernel_jit = jax.jit(verify_kernel)


# ----------------------------------------------------------------------------
# Compact path: minimal H2D traffic, device-side epoch-table gather.
#
# The naive path above ships a gathered [B, 16, 4, 32] int32 table block per
# batch (~8 KiB/vote — measured to cap sustained throughput at ~80k votes/s
# on PCIe-class links). Here the per-epoch tables live on device once and
# the fused step ships each vote as one ``ops.tally.VOTE_ROW`` (u8 nibbles
# + R bytes + indices, 170 bytes padded to ``ROW_BYTES``); the validator
# gather happens device-side inside the jit.


@dataclass
class CompactBatch:
    """Host-prepared compact device inputs for a batch of B checks."""

    s_nibbles: np.ndarray  # [B, 64] uint8, MSB-first nibbles of S
    h_nibbles: np.ndarray  # [B, 64] uint8, MSB-first nibbles of h mod L
    val_idx: np.ndarray  # [B] int32 validator index (clipped on device)
    r_y: np.ndarray  # [B, 32] uint8 low 255 bits of sig[:32]
    r_sign: np.ndarray  # [B] uint8 bit 255 of sig[:32]
    pre_ok: np.ndarray  # [B] bool host pre-checks passed
    # seconds the preparing thread spent parked behind host-pool shards
    # it didn't run itself (0.0 on the serial path) — prep accounting
    # only, never part of the batch's identity
    pool_wait_s: float = 0.0

    @property
    def size(self) -> int:
        return self.s_nibbles.shape[0]


def nibbles_from_le_bytes(b: np.ndarray) -> np.ndarray:
    """[B, 32] little-endian uint8 scalars -> [B, 64] MSB-first nibbles."""
    rev = b[:, ::-1]
    out = np.empty((b.shape[0], 64), np.uint8)
    out[:, 0::2] = rev >> 4
    out[:, 1::2] = rev & 15
    return out


# below this many rows a pooled prep loses to its own shard bookkeeping
# (job objects + events cost ~10 us/shard; a 256-row native prep is ~50 us)
_POOL_MIN_ROWS = 256


def prepare_compact(
    msgs: list[bytes],
    sigs: list[bytes],
    val_idx: np.ndarray,
    epoch: EpochTables,
    pool=None,
) -> CompactBatch:
    """Host prep: native C batch (SHA-512 + mod L + ScMinimal) when the
    compiler-built module is available, else the vectorized numpy path
    (``_prepare_compact_np``); ``_prepare_compact_py`` is the per-row
    parity oracle for both (tests/test_native_prep.py, test_mesh_engine).

    ``pool`` (engine.hostprep.HostPrepPool): shard the rows contiguously
    across workers — every row is prepared independently, so the
    concatenated shards are byte-identical to the serial prep. The native
    prep releases the GIL inside ctypes, so sharding is real parallelism;
    the caller reads the queue-wait share back off
    ``CompactBatch.pool_wait_s``."""
    from .. import native

    fn = (
        _prepare_compact_native
        if len(msgs) and native.available()
        else _prepare_compact_np
    )
    n = len(msgs)
    if pool is None or pool.workers <= 1 or n < _POOL_MIN_ROWS:
        return fn(msgs, sigs, val_idx, epoch)
    if getattr(pool, "backend", "thread") == "process":
        # typed shared-memory path: workers run the same row core
        # (prep_proc.prep_rows_cat[_native]) over contiguous shards of
        # the cat-form batch, writing straight into the output segment.
        # Falls back to the thread shards below if the pool has degraded.
        out = pool.prepare_compact_shm(msgs, sigs, np.asarray(val_idx), epoch)
        if out is not None:
            s_nib, h_nib, vidx, r_y, r_sign, pre_ok, wait_s = out
            return CompactBatch(
                s_nib, h_nib, vidx, r_y, r_sign, pre_ok, pool_wait_s=wait_s
            )
    vi = np.asarray(val_idx)

    def _shard(lo: int, hi: int) -> CompactBatch:
        return fn(msgs[lo:hi], sigs[lo:hi], vi[lo:hi], epoch)

    parts, wait_s = pool.map_shards(n, _shard)
    if len(parts) == 1:
        parts[0].pool_wait_s = wait_s
        return parts[0]
    out = CompactBatch(
        np.concatenate([p.s_nibbles for p in parts]),
        np.concatenate([p.h_nibbles for p in parts]),
        np.concatenate([p.val_idx for p in parts]),
        np.concatenate([p.r_y for p in parts]),
        np.concatenate([p.r_sign for p in parts]),
        np.concatenate([p.pre_ok for p in parts]),
        pool_wait_s=wait_s,
    )
    return out


def _prepare_compact_native(
    msgs: list[bytes],
    sigs: list[bytes],
    val_idx: np.ndarray,
    epoch: EpochTables,
) -> CompactBatch:
    from .. import native

    n = len(msgs)
    n_vals = len(epoch.pub_keys)
    vi = np.asarray(val_idx, dtype=np.int64)
    clipped = np.clip(vi, 0, max(n_vals - 1, 0))
    idx_ok = (vi >= 0) & (vi < n_vals)
    sig_ok = np.fromiter((len(s) == 64 for s in sigs), bool, n)
    sig_cat = (
        b"".join(sigs)
        if bool(sig_ok.all())
        else b"".join(s if len(s) == 64 else _ZERO64 for s in sigs)
    )
    sig_arr = np.frombuffer(sig_cat, np.uint8).reshape(n, 64)
    offs = np.zeros(n + 1, np.int64)
    np.cumsum(np.fromiter((len(m) for m in msgs), np.int64, n), out=offs[1:])
    msg_cat = np.frombuffer(b"".join(msgs), np.uint8)
    ok_in = (idx_ok & sig_ok & (epoch.key_ok[clipped] if n_vals else False)).astype(
        np.uint8
    )
    pubs = epoch.pub_arr[clipped] if n_vals else np.zeros((n, 32), np.uint8)
    s_le, h_le, pre_ok = native.prep_batch(msg_cat, offs, sig_arr, pubs, ok_in)
    # match the Python path bit-for-bit: failed rows stay all-zero
    r_y = np.where(pre_ok[:, None], sig_arr[:, :32], 0).astype(np.uint8)
    r_sign = (r_y[:, 31] >> 7).astype(np.uint8)
    r_y[:, 31] &= 0x7F
    return CompactBatch(
        nibbles_from_le_bytes(s_le),
        nibbles_from_le_bytes(h_le),
        clipped.astype(np.int32),
        r_y,
        r_sign,
        pre_ok,
    )


_ZERO64 = bytes(64)


def _prepare_compact_py(
    msgs: list[bytes],
    sigs: list[bytes],
    val_idx: np.ndarray,
    epoch: EpochTables,
) -> CompactBatch:
    n = len(msgs)
    n_vals = len(epoch.pub_keys)
    vi = np.asarray(val_idx, dtype=np.int64)
    idx_ok = (vi >= 0) & (vi < n_vals)
    sig_arr = np.zeros((n, 64), np.uint8)
    s_le = np.zeros((n, 32), np.uint8)
    h_le = np.zeros((n, 32), np.uint8)
    pre_ok = np.zeros(n, bool)
    for i in range(n):
        sig = sigs[i]
        if len(sig) != 64 or not idx_ok[i] or not epoch.key_ok[vi[i]]:
            continue
        s = int.from_bytes(sig[32:], "little")
        if s >= host_ed.L:  # ScMinimal
            continue
        pub = epoch.pub_keys[vi[i]]
        h = (
            int.from_bytes(hashlib.sha512(sig[:32] + pub + msgs[i]).digest(), "little")
            % host_ed.L
        )
        sig_arr[i] = np.frombuffer(sig, np.uint8)
        s_le[i] = np.frombuffer(sig[32:], np.uint8)
        h_le[i] = np.frombuffer(h.to_bytes(32, "little"), np.uint8)
        pre_ok[i] = True
    r_y = sig_arr[:, :32].copy()
    r_sign = (r_y[:, 31] >> 7).astype(np.uint8)
    r_y[:, 31] &= 0x7F
    return CompactBatch(
        nibbles_from_le_bytes(s_le),
        nibbles_from_le_bytes(h_le),
        np.clip(vi, 0, max(n_vals - 1, 0)).astype(np.int32),
        r_y,
        r_sign,
        pre_ok,
    )


def _prepare_compact_np(
    msgs: list[bytes],
    sigs: list[bytes],
    val_idx: np.ndarray,
    epoch: EpochTables,
) -> CompactBatch:
    """Vectorized numpy prep — the serving path when native/_prep.so is
    unavailable (no C compiler in the container).

    Bit-identical to ``_prepare_compact_py`` (pinned by
    tests/test_mesh_engine.py). The row math lives in
    ``prep_proc.prep_rows_cat`` — the SAME function process-pool workers
    run over shared-memory shards — so there is exactly one numpy
    implementation and thread/process/serial assembly parity holds by
    construction, not by duplicated code."""
    from ..prep_proc import cat_msgs, cat_sigs, prep_rows_cat

    msg_cat, offs = cat_msgs(msgs)
    sig_arr, sig_ok = cat_sigs(sigs)
    s_nib, h_nib, vidx, r_y, r_sign, ok = prep_rows_cat(
        msg_cat, offs, sig_arr, sig_ok,
        np.asarray(val_idx, dtype=np.int64), epoch.pub_arr, epoch.key_ok,
    )
    return CompactBatch(s_nib, h_nib, vidx, r_y, r_sign, ok)


def verify_kernel_gather(
    s_nibbles, h_nibbles, val_idx, tables, r_y, r_sign, pre_ok, axis_name=None
):
    """Device kernel with on-device epoch-table gather.

    tables: [V, 16, 4, 32] int32, device-resident per epoch. Per-vote inputs
    are compact uint8; widened to int32 on device. Decisions are identical
    to ``verify_kernel``; the per-item window table is never materialized
    (``curve.double_scalar_mul_indexed`` selects inside the scan step).

    Scope ``decompress``: the public keys were decompressed on the host,
    once an epoch (``EpochTables``); what is left of it on the device is
    the widening of the compact per-vote inputs to int32 limbs.
    """
    with jax.named_scope("decompress"):
        s_limbs = s_nibbles.astype(jnp.int32)
        h_limbs = h_nibbles.astype(jnp.int32)
        r_limbs = fe.bytes_to_limbs_device(r_y)
        r_par = r_sign.astype(jnp.int32)
    p = curve.double_scalar_mul_indexed(
        s_limbs, h_limbs, jnp.asarray(curve.BASE_TABLE), tables, val_idx,
        axis_name=axis_name,
    )
    with jax.named_scope("encode_compare"):
        y, x_parity = curve.ext_encode(p)
        enc_match = fe.fe_is_equal_frozen(y, r_limbs) & (x_parity == r_par)
        return enc_match & pre_ok


def verify_batch(batch: PreparedBatch) -> np.ndarray:
    """Convenience host API: prepared batch -> bool[B] validity."""
    return np.asarray(
        verify_kernel_jit(
            jnp.asarray(batch.s_nibbles),
            jnp.asarray(batch.h_nibbles),
            jnp.asarray(batch.a_tables),
            jnp.asarray(batch.r_y),
            jnp.asarray(batch.r_sign),
            jnp.asarray(batch.pre_ok),
        )
    )
