"""The least time the chip could take for a rung of votes.

Counted from the shape alone, whatever implements the kernel. One stated
reference formulation of an ed25519 verification (RFC 8032, section
5.1.7, as ref10 computes it), in field multiplications (a squaring counts
as one):

- decompress the public key A: one square root of a ratio, i.e. one
  exponentiation by (p-5)/8 = 250 squarings + 11 multiplications, and 4
  multiplications around it: 265;
- the double-scalar multiplication [s]B - [h]A, both scalars in 64 signed
  radix-16 digits (Straus): 252 doublings of 4 squarings + 4
  multiplications (dbl-2008-hwcd) and 128 additions of 8 multiplications
  (add-2008-hwcd-3): 252*8 + 128*8 = 3,040;
- encode the result to compare it with R: one inversion, 254 squarings +
  11 multiplications, and 2 multiplications: 267.

3,572 field multiplications a vote. One multiplication of two 255-bit
field elements in radix 2^8 is a 32 x 32 limb product: 1,024 8-bit
multiply-adds, 2,048 integer operations (carries and the reduction by 19
are left out: the count is a floor). That radix is the one an MXU
formulation would use (ROADMAP Queue 1 item 7), so the count is held
against the chip's int8 peak. Today's kernel multiplies int32 limbs on the
VPU and cannot reach that peak; the share says how far the step is from
what the chip could do, not from what this kernel could.

Bytes a vote, in and out of a rung: two 64-nibble scalar arrays, the
32-byte R.y, its sign, the validator index, pre_ok and the slot (int32
each where not bytes), and one validity byte back; per slot the prior
stake in and stake and maj23 out.
"""

from __future__ import annotations

import json
import os

FIELD_MULS_PER_VERIFY = 265 + (252 * 8 + 128 * 8) + 267
INT8_OPS_PER_FIELD_MUL = 2 * 32 * 32
BYTES_PER_VOTE = 64 + 64 + 32 + 4 + 4 + 4 + 4 + 1
BYTES_PER_SLOT = 4 + 4 + 4

_PEAKS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of a device kind. A kind that is not in the
    table is an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)["device_kinds"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {_PEAKS}")
    return table[device_kind]


def rung_ops(votes: int) -> int:
    return votes * FIELD_MULS_PER_VERIFY * INT8_OPS_PER_FIELD_MUL


def rung_bytes(votes: int, slots: int) -> int:
    return votes * BYTES_PER_VOTE + slots * BYTES_PER_SLOT


def least_seconds(votes: int, slots: int, device_kind: str) -> tuple[float, str]:
    """(seconds, which peak bounds it) for one rung."""
    p = peaks(device_kind)
    by_ops = rung_ops(votes) / p["int8_ops_per_s"]
    by_bytes = rung_bytes(votes, slots) / p["hbm_bytes_per_s"]
    return (by_ops, "int8_ops_per_s") if by_ops >= by_bytes else (by_bytes, "hbm_bytes_per_s")


def roofline_share(votes: int, slots: int, device_seconds: float, device_kind: str) -> float:
    """Percent of the roofline: least time over the device time taken."""
    least, _ = least_seconds(votes, slots, device_kind)
    return 100.0 * least / device_seconds
