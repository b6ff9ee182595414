"""BatchCertVerifier: scalar decisions, one device call per certificate batch.

"Performance of EdDSA and BLS Signatures in Committee-Based Consensus"
(arxiv 2302.00418): committee certificates are small enough that the
per-signature host verify loop is dominated by per-call overhead —
batch-verifying the whole certificate in one fused device call is the
win. The substrate already exists: ``ops.ed25519_batch`` keeps the
epoch's pubkey window tables device-resident (``EpochTables``) and
gathers them inside the jit (``verify_kernel_gather``), so a
certificate ships as ~162 compact bytes per vote.

This class is a drop-in ``ScalarVoteVerifier``: identical
verify-and-tally decisions (the parity tests pin them vote-for-vote),
with the per-signature ``host_ed.verify`` loop replaced by ONE
``ed25519_batch`` dispatch per call. The sync/follower certificate
re-check constructs one per val-set fingerprint (sync/manager.py
``_verifier_for``) so a whole response's certificates verify in one
call per epoch group; committee-mode engines can mount it directly
(``submit`` routes through the overridden ``verify_and_tally``).

Shape discipline: batches pad to a pow2 rung so every certificate size
shares a handful of compiled programs, and the staged table shape [V]
is a compile dimension — a committee swap of EQUAL size restages with
zero recompiles (the ``_DeviceStage`` contract, inherited here via
``restage``). Below ``min_batch`` rows a kernel launch costs more than
the scalar loop, so small calls fall through to the parent — decisions
are identical either way.
"""

from __future__ import annotations

import numpy as np

from ..ops import ed25519_batch as ops_ed
from ..types.validator import ValidatorSet
from ..verifier import ScalarVoteVerifier, TallyResult, first_occurrence_mask

# one jitted program per (rung, V) pair, shared by every instance in the
# process — the gather kernel itself is the one DeviceVoteVerifier runs
_gather_jit = None


def _kernel():
    global _gather_jit
    if _gather_jit is None:
        import jax

        _gather_jit = jax.jit(ops_ed.verify_kernel_gather)
    return _gather_jit


def _rung(n: int) -> int:
    """pow2 padding rung (floor 8): bounds compiled shapes to
    log2(max certificate batch) programs per val-set size."""
    target = max(int(n), 8)
    return 1 << (target - 1).bit_length()


class BatchCertVerifier(ScalarVoteVerifier):
    def __init__(
        self,
        val_set: ValidatorSet,
        min_batch: int = 4,
    ):
        super().__init__(val_set)
        self.min_batch = int(min_batch)
        # one-tuple batch stage, same atomicity contract as the parent's
        # _stage: the batch path reads it ONCE per call, so a concurrent
        # restage can never mix one epoch's tables with another's powers
        self._batch_stage = (
            val_set,
            self._pub_keys,
            self._powers,
            ops_ed.EpochTables(self._pub_keys),
        )
        # evidence counters (tests read these): device
        # dispatches vs scalar fallthroughs, and total rows batched
        self.batch_calls = 0
        self.scalar_calls = 0
        self.batched_votes = 0

    def restage(self, new_val_set: ValidatorSet) -> bool:
        super().restage(new_val_set)
        self._batch_stage = (
            new_val_set,
            self._pub_keys,
            self._powers,
            ops_ed.EpochTables(self._pub_keys),
        )
        return True

    def verify_and_tally(
        self,
        msgs,
        sigs,
        val_idx,
        tx_slot,
        n_slots,
        prior_stake=None,
        quorum=None,
    ) -> TallyResult:
        n = len(msgs)
        if n < self.min_batch:
            self.scalar_calls += 1
            return super().verify_and_tally(
                msgs, sigs, val_idx, tx_slot, n_slots,
                prior_stake=prior_stake, quorum=quorum,
            )
        val_set, pub_keys, powers, tables = self._batch_stage
        val_idx = np.asarray(val_idx, dtype=np.int64)
        tx_slot = np.asarray(tx_slot, dtype=np.int64)
        keep = first_occurrence_mask(tx_slot, val_idx)

        # host prep: compact nibbles + pre-checks (ScMinimal, key-on-curve,
        # index range — out-of-range rows come back pre_ok=False)
        batch = ops_ed.prepare_compact(
            msgs, sigs, val_idx.astype(np.int32), tables
        )
        pad = _rung(n)
        s_nib = np.zeros((pad, batch.s_nibbles.shape[1]), np.uint8)
        h_nib = np.zeros((pad, batch.h_nibbles.shape[1]), np.uint8)
        vi = np.zeros(pad, np.int32)
        r_y = np.zeros((pad, batch.r_y.shape[1]), np.uint8)
        r_sign = np.zeros(pad, np.uint8)
        pre_ok = np.zeros(pad, bool)
        s_nib[:n] = batch.s_nibbles
        h_nib[:n] = batch.h_nibbles
        vi[:n] = batch.val_idx
        r_y[:n] = batch.r_y
        r_sign[:n] = batch.r_sign
        pre_ok[:n] = batch.pre_ok

        # ONE fused device call for the whole certificate batch; padding
        # rows carry pre_ok=False and are rejected inside the kernel
        out = _kernel()(
            s_nib, h_nib, vi, tables.device_tables(), r_y, r_sign, pre_ok
        )
        self.batch_calls += 1
        self.batched_votes += n
        valid = np.asarray(out)[:n].copy()
        # duplicate (slot, validator) rows verified fine but must not
        # tally twice — the parent never verifies them at all; either
        # way they land valid=False + dropped (decision parity)
        valid &= keep

        stake = (
            np.zeros(n_slots, dtype=np.int64)
            if prior_stake is None
            else np.asarray(prior_stake, dtype=np.int64).copy()
        )
        ok = valid & (tx_slot >= 0) & (tx_slot < n_slots)
        if ok.any():
            np.add.at(
                stake, tx_slot[ok], powers[val_idx[ok]].astype(np.int64)
            )
        q = val_set.quorum_power() if quorum is None else quorum
        pending = np.zeros(n, dtype=bool)
        return TallyResult(valid, stake, stake >= q, ~keep | pending)
